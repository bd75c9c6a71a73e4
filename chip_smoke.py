"""Bring-up smoke of the Radic determinant service on a TPU.

Drives the service's own entry points once on the chip and checks what
comes out:

  (a) device — ``jax.devices()`` must be TPUs; any other platform exits
      non-zero before anything runs;
  (b) the paper's workload — one determinant of a (12, 32) matrix,
      C(32, 12) = 225,792,840 signed minors, through
      ``radic_det(A, backend="pallas")`` (``DetEngine`` → fused kernel),
      against the jnp backend on the chip, plus a (8, 20) matrix against
      the float64 oracle;
  (c) serving — an in-process ``DetQueue(backend="pallas")`` built as
      ``det_serve --backend pallas`` builds it serves a few hundred
      seeded value and gradient requests, every result checked.

  python chip_smoke.py              # one chip: phases (a)-(c)
  python chip_smoke.py --chips 4    # four chips: (a) and the mesh phase

With ``--chips 4`` only the mesh phase runs, next to the one-chip result
it is compared with: the (12, 32) rank space cut into one grain per
device by ``radic_det_distributed(backend="pallas")``, and the rank ×
batch path of ``radic_det_batched_distributed``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  Data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.parallel.compat import enable_entry_point_cache  # noqa: E402

# Tolerances.  Everything on the chip computes in f32 (it has no f64).
# A Radic determinant is a signed sum of N = C(n, m) minors of typical
# size rms (their root mean square): a random walk of size
# SCALE = sqrt(N)·rms that may cancel to far less.  Rounding errors
# follow the terms, not the sum, so value errors are measured in units
# of SCALE; measured against |value| a well-computed sum that happens to
# cancel would look wrong.
#
# The kernel keeps one f32 accumulator per lane over N/T terms; that
# rounding walk is about eps·sqrt(N / 2T)·SCALE: 4e-5·SCALE at (12, 32)
# with T = 256, 1e-6·SCALE at (8, 20).
#
# REF_RTOL — against the jnp backend on the chip.  Its minors of order
# m ≥ 4 go through jnp.linalg.det = sign·exp(Σ log|u_ii|), and the chip's
# f32 log/exp round each minor to about 1e-5 of its size, a second
# walk of about 1e-5·SCALE.  1e-3·SCALE leaves a margin above both; a
# wrong sign or a dropped tile of 256 minors is 16·rms or more, which
# is above it for N < 2.5e8.  Gradients: the max-norm of the difference
# against 1e-3 of the max-norm of the reference gradient.
REF_RTOL = 1e-3
# ORACLE_RTOL — against the float64 oracle, where only the kernel
# rounds: 1e-4·SCALE is a hundredfold margin at (8, 20).
ORACLE_RTOL = 1e-4

PAPER_SHAPE = (12, 32)    # C(32, 12) = 225,792,840 < 2**31
ORACLE_SHAPE = (8, 20)
SERVE_REQUESTS = 256
SERVE_MAX_M, SERVE_MAX_N = 8, 20
MESH_BATCH = (64, 8, 24)
# the jnp reference at PAPER_SHAPE: 3,446 chunks of 65,536 ranks
PAPER_REF_CHUNK = 1 << 16


def _now() -> float:
    return time.perf_counter()


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        _fail(msg)


def device_info() -> dict:
    """Phase (a): the platform must be a TPU, never a CPU fallback."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        _fail(f"no TPU: jax found platform {d.platform!r} "
              f"({len(devs)} device(s))")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"(a) device: {info}", flush=True)
    return info


def minor_rms(A: np.ndarray, rng, samples: int = 4096) -> float:
    """RMS of the m×m minor determinants of ``A`` over random column
    subsets, in float64 — the term size of the Radic sum."""
    m, n = A.shape
    cols = np.stack([np.sort(rng.choice(n, m, replace=False))
                     for _ in range(samples)])
    dets = np.linalg.det(np.transpose(A[:, cols], (1, 0, 2)).astype(
        np.float64))
    return float(np.sqrt(np.mean(dets ** 2)))


def radic_scale(A: np.ndarray, rng) -> float:
    """SCALE = sqrt(C(n, m))·rms: the size of the Radic sum's terms."""
    from repro.core import comb
    m, n = A.shape
    return math.sqrt(comb(n, m)) * minor_rms(A, rng)


def assert_compiled(fn, *args) -> None:
    """The program lowers to a Mosaic kernel (``tpu_custom_call``), and the
    kernels resolve to compiled, not interpreted, mode."""
    from repro.kernels.radic_fused import resolve_interpret
    check(resolve_interpret(None) is False,
          "Pallas kernels would run in interpret mode")
    text = jax.jit(fn).lower(*args).as_text()
    check("tpu_custom_call" in text,
          f"{getattr(fn, '__name__', fn)} lowered without a TPU kernel")


def timed(fn, *args):
    """(result as numpy, seconds of the first call, seconds of the
    second); the first call compiles, the second runs warm."""
    t0 = _now()
    out = np.asarray(jax.block_until_ready(fn(*args)))
    first = _now() - t0
    t0 = _now()
    out2 = np.asarray(jax.block_until_ready(fn(*args)))
    warm = _now() - t0
    check(np.array_equal(out, out2), "a repeated call changed its result")
    return out, first, warm


def phase_paper(rng) -> float:
    """Phase (b); returns the one-chip (12, 32) determinant."""
    from repro.core import comb, radic_det, radic_det_oracle
    m, n = PAPER_SHAPE
    total = comb(n, m)
    A = rng.standard_normal((m, n)).astype(np.float32)
    Aj = jnp.asarray(A)

    def pallas_det(a):
        return radic_det(a, backend="pallas")

    assert_compiled(pallas_det, Aj)
    got, first, warm = timed(pallas_det, Aj)
    got = float(got)
    print(f"(b) radic_det pallas ({m},{n}): {total} ranks, value {got!r}, "
          f"first call {first:.3f} s, warm {warm:.3f} s, "
          f"compile ~{first - warm:.3f} s, "
          f"{total / warm:.4e} ranks/s", flush=True)

    t0 = _now()
    ref = float(radic_det(Aj, chunk=PAPER_REF_CHUNK))   # the jnp backend
    t_ref = _now() - t0
    scale = radic_scale(A, rng)
    err = abs(got - ref)
    print(f"(b) radic_det jnp ({m},{n}): value {ref!r}, {t_ref:.3f} s "
          f"compile included; |pallas - jnp| = {err:.4e}, "
          f"SCALE = {scale:.4e}, ratio {err / scale:.3e} "
          f"(limit {REF_RTOL})", flush=True)
    check(np.isfinite(got) and err <= REF_RTOL * scale,
          f"{PAPER_SHAPE} pallas {got} vs jnp {ref}")

    B = rng.standard_normal(ORACLE_SHAPE).astype(np.float32)
    Bj = jnp.asarray(B)
    got8 = float(pallas_det(Bj))
    jnp8 = float(radic_det(Bj))
    want8 = radic_det_oracle(B)
    scale8 = radic_scale(B, rng)
    err8 = abs(got8 - want8)
    print(f"(b) {ORACLE_SHAPE} vs oracle {want8!r}: pallas {got8!r} ratio "
          f"{err8 / scale8:.3e} (limit {ORACLE_RTOL}); jnp {jnp8!r} ratio "
          f"{abs(jnp8 - want8) / scale8:.3e}", flush=True)
    check(err8 <= ORACLE_RTOL * scale8, f"{ORACLE_SHAPE} pallas {got8} vs "
          f"oracle {want8}")
    return got


def serve_requests(rng, num: int, grad_frac: float):
    """The seeded request mix: m ∈ [2, 8], n ∈ [m, 20], about
    ``grad_frac`` of them gradient requests with cotangent 1."""
    mats, grads = [], []
    for _ in range(num):
        m = int(rng.integers(2, SERVE_MAX_M + 1))
        n = int(rng.integers(m, SERVE_MAX_N + 1))
        mats.append(rng.standard_normal((m, n)).astype(np.float32))
        grads.append((bool(rng.random() < grad_frac), 1.0))
    return mats, grads


def phase_serve(rng) -> None:
    """Phase (c): the in-process queue ``det_serve --backend pallas``
    builds, every result checked."""
    from repro.core import radic_det, radic_det_batched, radic_det_oracle
    from repro.kernels import ops
    from repro.launch.det_queue import BucketPolicy, DetQueue
    num = SERVE_REQUESTS
    mats, grads = serve_requests(rng, num, 0.25)
    # the serving kernels lower to Mosaic too
    As = jnp.zeros((4, 3, 7), jnp.float32)
    assert_compiled(ops.radic_det_batched_pallas, As)
    assert_compiled(lambda a, c: ops.radic_det_batched_grad_pallas(a, c),
                    As, jnp.ones((4,), jnp.float32))

    policy = BucketPolicy(max_batch=64, mode="auto")
    t0 = _now()
    with DetQueue(chunk=2048, backend="pallas", policy=policy) as q:
        futs = q.submit_many(mats, grads)
        got = [f.result(timeout=900) for f in futs]
        stats = q.snapshot()
    wall = _now() - t0
    n_grad = sum(g for g, _ in grads)
    print(f"(c) DetQueue pallas: {num} requests ({n_grad} gradient) served "
          f"in {wall:.3f} s, compiles included; batches {stats['batches']},"
          f" plans {stats['plan_cache']['size']}", flush=True)
    check(stats["completed"] == num, f"completed {stats['completed']}")

    # values against the jnp backend on the chip, one batch per shape
    by_shape: dict = {}
    for i, (A, (g, _)) in enumerate(zip(mats, grads)):
        if not g:
            by_shape.setdefault(A.shape, []).append(i)
    value_idx = [i for idx in by_shape.values() for i in idx]
    scale = {i: radic_scale(mats[i], rng) for i in value_idx}
    t0 = _now()
    worst = 0.0
    for shape, idx in by_shape.items():
        ref = np.asarray(radic_det_batched(
            jnp.asarray(np.stack([mats[i] for i in idx]))))
        for i, r in zip(idx, ref):
            worst = max(worst, abs(float(got[i]) - r) / scale[i])
    print(f"(c) {len(value_idx)} values vs jnp on the chip: worst "
          f"{worst:.3e} of SCALE (limit {REF_RTOL}), {_now() - t0:.3f} s",
          flush=True)
    check(worst <= REF_RTOL, f"served value off by {worst} of SCALE")

    sample = sorted(rng.choice(value_idx, size=16, replace=False).tolist())
    worst_o = max(abs(float(got[i]) - radic_det_oracle(mats[i])) / scale[i]
                  for i in sample)
    print(f"(c) {len(sample)} sampled values vs oracle: worst "
          f"{worst_o:.3e} of SCALE (limit {ORACLE_RTOL})", flush=True)
    check(worst_o <= ORACLE_RTOL,
          f"served value off the oracle by {worst_o} of SCALE")

    t0 = _now()
    grad_fn = jax.grad(radic_det)
    worst_g = 0.0
    for i, (g, _) in enumerate(grads):
        if g:
            want = np.asarray(grad_fn(jnp.asarray(mats[i])))
            check(got[i].shape == want.shape, f"grad shape {got[i].shape}")
            worst_g = max(worst_g, np.max(np.abs(got[i] - want))
                          / np.max(np.abs(want)))
    print(f"(c) {n_grad} gradients vs jax.grad(radic_det): worst "
          f"{worst_g:.3e} of the max entry (limit {REF_RTOL}), "
          f"{_now() - t0:.3f} s", flush=True)
    check(worst_g <= REF_RTOL, f"served gradient off by {worst_g}")


def phase_mesh(rng, chips: int) -> None:
    """Phase (d): the mesh paths on every chip of the host, each against
    the one-chip result in this process."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import (comb, plan_grains, radic_det,
                            radic_det_batched,
                            radic_det_batched_distributed,
                            radic_det_distributed)
    from repro.kernels import ops
    devs = jax.devices()
    check(len(devs) == chips, f"{len(devs)} devices, --chips {chips}")
    m, n = PAPER_SHAPE
    total = comb(n, m)
    A_np = rng.standard_normal((m, n)).astype(np.float32)
    A = jnp.asarray(A_np)
    mesh = Mesh(np.array(devs), ("workers",))

    one, _, warm1 = timed(lambda a: radic_det(a, backend="pallas"), A)
    many, first_d, warm_d = timed(
        lambda a: radic_det_distributed(a, mesh=mesh, backend="pallas"), A)
    one, many = float(one), float(many)
    print(f"(d) grains ({m},{n}) on {len(devs)} chips: {many!r} in "
          f"{warm_d:.3f} s (first {first_d:.3f} s); one chip {one!r} in "
          f"{warm1:.3f} s; speed-up {warm1 / warm_d:.2f}x", flush=True)
    scale = radic_scale(A_np, rng)
    check(abs(many - one) <= REF_RTOL * scale,
          f"mesh {many} vs one chip {one}")

    # Each device's grain as the mesh program is handed it (the sharded
    # (start, length) rows), and that grain's partial computed on that
    # device alone: the partials must add up to the mesh result.
    starts, lengths = plan_grains(total, len(devs))
    grains = jax.device_put(np.stack([starts, lengths], axis=1),
                            NamedSharding(mesh, P("workers")))
    parts = []
    for shard in sorted(grains.addressable_shards,
                        key=lambda s: s.device.id):
        q0, cnt = (int(v) for v in np.asarray(shard.data)[0])
        check(cnt > 0, f"device {shard.device.id} got no grain")
        t0 = _now()
        part = float(ops.radic_det_pallas(
            jax.device_put(A, shard.device), q0, cnt))
        parts.append(part)
        print(f"(d) device {shard.device.id}: ranks [{q0}, {q0 + cnt}) = "
              f"{cnt} ({100.0 * cnt / total:.2f}% of the grains), partial "
              f"{part!r} on that device in {_now() - t0:.3f} s", flush=True)
    err = abs(math.fsum(parts) - many)
    print(f"(d) sum of the device partials vs mesh: {err / scale:.3e} of "
          f"SCALE (limit {REF_RTOL})", flush=True)
    check(err <= REF_RTOL * scale, f"partials {parts} vs mesh {many}")

    # rank × batch: batch over one axis, rank space over the other
    mesh2 = Mesh(np.array(devs).reshape(2, chips // 2), ("batch", "ranks"))
    As_np = rng.standard_normal(MESH_BATCH).astype(np.float32)
    As = jnp.asarray(As_np)
    want = np.asarray(radic_det_batched(As, backend="pallas"))
    got = np.asarray(radic_det_batched_distributed(
        As, mesh=mesh2, batch_axis="batch", backend="pallas"))
    worst = max(abs(float(g) - float(w)) / radic_scale(a, rng)
                for g, w, a in zip(got, want, As_np))
    print(f"(d) rank x batch {MESH_BATCH} on a {dict(mesh2.shape)} mesh vs "
          f"one chip: worst {worst:.3e} of SCALE (limit {REF_RTOL})",
          flush=True)
    check(worst <= REF_RTOL, f"rank x batch off by {worst} of SCALE")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_entry_point_cache()
    info = device_info()
    rng = np.random.default_rng(args.seed)
    t0 = _now()
    if args.chips == 1:
        check(info["count"] >= 1, "no device")
        phase_paper(rng)
        phase_serve(rng)
    else:
        phase_mesh(rng, args.chips)
    print(f"chip_smoke: all phases passed in {_now() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
