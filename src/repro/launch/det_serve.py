"""Determinant serving CLI: drive the async pipelined
:class:`repro.launch.det_queue.DetQueue` (default), the multi-worker
:class:`repro.launch.det_front.DetFront` (``--workers N``) or the
synchronous :func:`drain_queue` reference over a queue of heterogeneous
matrices.

Requests are arbitrary (m_i, n_i) matrices.  All paths group them by
shape (one bucket = one C(n, m) rank space = one Pascal table = one
compiled program), pad each bucket's batch dim (bounded by
``--max-batch``) and evaluate buckets with
:func:`repro.core.radic_det_batched` — one dispatch per padded group
instead of one per matrix.  Zero-padding is sound: ``det(0) = 0`` and
padded rows are sliced off before results are returned in arrival
order.  The async path additionally overlaps host staging with device
execution and re-buckets dynamically (DESIGN_SERVE.md); the front
shards the shape buckets over workers, routing by canonical plan key
(DESIGN_FRONT.md) behind a pluggable transport (``launch/transport.py``).

  PYTHONPATH=src python -m repro.launch.det_serve --num 64 \
      --max-m 4 --max-n 10 --backend jnp --verify
  PYTHONPATH=src python -m repro.launch.det_serve --num 256 --sync
  PYTHONPATH=src python -m repro.launch.det_serve --num 256 --workers 2

A *multi-host* pool is two shell commands — start one worker daemon per
host, then point a front at them:

  host-a$ PYTHONPATH=src python -m repro.launch.det_serve \
      --listen 0.0.0.0:7341
  host-b$ PYTHONPATH=src python -m repro.launch.det_serve \
      --num 256 --connect host-a:7341,host-c:7341

The daemon is configuration-free: the front's ``--connect`` handshake
ships the full serving config (policy, dtype, admission control), so
routing and bucketing can never disagree across hosts.  Peer death is
detected by heartbeat deadline + per-batch acks and the front re-routes
deterministically (DESIGN_FRONT.md has the protocol spec and failure
semantics table).

The pool is *elastic* (DESIGN_FRONT.md, "Dynamic membership"): a front
started with ``--accept HOST:PORT`` admits workers that dial in later
(``det_serve --join front-host:PORT`` — same hello/ready handshake, so
late joiners get the same config), and ``--autoscale MAX`` runs the SLO
controller from ``launch/autoscale.py`` that grows/retires workers
between 1 and MAX against the front's live stats.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comb, radic_det_batched
from repro.launch.det_queue import (BucketPolicy, DetQueue, LoadShedError,
                                    bucket_by_shape, pad_capacity)
from repro.parallel.compat import enable_entry_point_cache

__all__ = ["bucket_by_shape", "pad_capacity", "drain_queue", "main"]


def drain_queue(mats, *, chunk: int = 2048, backend: str = "jnp",
                max_batch: int = 64, mesh=None, batch_axis=None,
                dtype=np.float32):
    """Synchronous reference: evaluate every queued matrix in the calling
    thread; returns ``(dets, stats)``.

    Stage → dispatch → block, one group at a time — the baseline the
    pipelined :class:`DetQueue` is benchmarked against
    (``benchmarks/perf_serve.py``).  ``dets`` is a list of floats in
    arrival order.  ``stats`` maps each (m, n) bucket to a dict with
    ``count`` (matrices), ``dispatches`` (device round-trips), ``ranks``
    (minors evaluated, excluding padding), ``wall_s``, ``mats_per_s``
    and ``ranks_per_s``.
    """
    out: list[float | None] = [None] * len(mats)
    stats: dict[tuple[int, int], dict] = {}
    for (m, n), idxs in bucket_by_shape(mats).items():
        t0 = time.perf_counter()
        dispatches = 0
        for base in range(0, len(idxs), max_batch):
            grp = idxs[base:base + max_batch]
            cap = pad_capacity(len(grp), max_batch)
            stack = np.zeros((cap, m, n), dtype=dtype)
            for j, i in enumerate(grp):
                stack[j] = np.asarray(mats[i], dtype=dtype)
            dets = radic_det_batched(jnp.asarray(stack), chunk=chunk,
                                     backend=backend, mesh=mesh,
                                     batch_axis=batch_axis)
            dets = np.asarray(jax.block_until_ready(dets))
            dispatches += 1
            for j, i in enumerate(grp):
                out[i] = float(dets[j])
        wall = time.perf_counter() - t0
        ranks = comb(n, m) * len(idxs) if m <= n else 0
        stats[(m, n)] = {
            "count": len(idxs),
            "dispatches": dispatches,
            "ranks": ranks,
            "wall_s": wall,
            "mats_per_s": len(idxs) / wall if wall > 0 else float("inf"),
            "ranks_per_s": ranks / wall if wall > 0 else float("inf"),
        }
    return out, stats


def _serve_tolerating_sheds(q, mats, grads=None):
    """Submit-all + wait-all like ``DetQueue.serve``, but a shed request
    yields ``None`` instead of raising — with ``--max-pending`` a
    synthetic burst larger than the bound sheds by design, and the CLI
    should report that, not crash on it.  Works on anything with the
    queue surface (``DetQueue`` and ``DetFront`` alike).  ``grads`` is
    the per-request ``(grad, cotangent)`` list both surfaces accept;
    grad requests resolve to (m, n) ndarrays instead of floats."""
    futs = q.submit_many(mats) if grads is None \
        else q.submit_many(mats, grads)
    dets = []
    for f in futs:
        try:
            dets.append(f.result())
        except LoadShedError:
            dets.append(None)
    q.poll(timeout=0)
    return dets


# a request's mean wait, and the four segments it splits into
_WAITS = ("wait_s", "queued_s", "staging_s", "inflight_s", "delivery_s")


def _print_buckets(buckets: dict) -> None:
    """The per-bucket CSV table: counts and mean seconds per request."""
    print("bucket_m,bucket_n,count,batches,ranks,"
          + ",".join("mean_" + w for w in _WAITS))
    for (m, n), b in sorted(buckets.items()):
        k = max(1, b["count"])
        print(f"{m},{n},{b['count']},{b['batches']},{b['ranks']},"
              + ",".join(f"{b[w] / k:.4f}" for w in _WAITS))


def _serve_front(front, mats, label: str, num: int, backend: str,
                 grads=None):
    """Warm + timed pass through any DetFront, then the front report
    (shared by ``--workers`` and ``--connect``); returns
    ``(dets, stats, wall)``."""
    _serve_tolerating_sheds(front, mats, grads)  # warm: compile programs
    front.reset_stats()  # report the timed pass only
    t0 = time.perf_counter()
    dets = _serve_tolerating_sheds(front, mats, grads)
    wall = time.perf_counter() - t0
    stats = front.snapshot()
    f, tot = stats["front"], stats["total"]
    print(f"# det_serve[{label}]: {num} requests, backend={backend}")
    print(f"front: workers={f['workers_alive']}/{f['workers_total']} "
          f"rerouted={f['rerouted']} worker_deaths={f['worker_deaths']} "
          f"shed={f['shed']} errors={f['errors']} "
          f"degraded={f['degraded']} joined={f['joined']} "
          f"stragglers_drained={f['stragglers_drained']}")
    print(f"total: batches={tot['batches']} "
          f"dispatches={tot['dispatches']} "
          f"merged_requests={tot['merged_requests']} "
          f"padded_slots={tot['padded_slots']} "
          f"backlog_peak={tot['backlog_peak']} "
          f"plan_cache={tot['plan_cache']['size']} "
          f"(hits={tot['plan_cache']['hits']} "
          f"misses={tot['plan_cache']['misses']})")
    print("worker,routed,completed,batches,shed,backlog_peak,plans")
    for wid, snap in sorted(stats["workers"].items()):
        print(f"{wid},{f['routed'].get(wid, 0)},{snap['completed']},"
              f"{snap['batches']},{snap['shed']},"
              f"{snap['backlog_peak']},{snap['plan_cache']['size']}")
    _print_buckets(tot["buckets"])
    return dets, stats, wall


def _serve_scaled(front, mats, label: str, num: int, backend: str,
                  autoscale_max: int, grads=None):
    """``_serve_front``, optionally under the SLO autoscaler.

    CLI runs are seconds long, so the controller gets a fast cadence and
    short cooldown here; long-lived deployments should keep the
    :class:`~repro.launch.autoscale.AutoscalePolicy` defaults."""
    if not autoscale_max:
        return _serve_front(front, mats, label, num, backend, grads)
    from repro.launch.autoscale import Autoscaler
    with Autoscaler(front, min_workers=1, max_workers=autoscale_max,
                    interval_s=0.25, cooldown_s=2.0) as scaler:
        out = _serve_front(front, mats, f"{label}+autoscale{autoscale_max}",
                           num, backend, grads)
    print(f"autoscale: up={scaler.scaled_up} down={scaler.scaled_down} "
          f"stalls={scaler.stalls}")
    return out


def _random_queue(num: int, max_m: int, max_n: int, seed: int):
    rng = np.random.default_rng(seed)
    mats = []
    for _ in range(num):
        m = int(rng.integers(1, max_m + 1))
        n = int(rng.integers(m, max_n + 1))
        mats.append(rng.normal(size=(m, n)).astype(np.float32))
    return mats


def main(argv=None):
    ap = argparse.ArgumentParser(
        epilog="multi-host recipe: start `--listen 0.0.0.0:7341` on every "
               "worker host, then run the front with "
               "`--connect hostA:7341,hostB:7341` — the front's handshake "
               "ships the serving config, so daemons take no tuning flags; "
               "see DESIGN_FRONT.md for the wire protocol and failure "
               "semantics.  Single-host fast path: `--workers N --shm` "
               "moves matrix payloads into a per-worker shared-memory ring "
               "(zero pickling of matrix bytes, bit-identical results; "
               "DESIGN_FRONT.md §shm ring protocol), and launching through "
               "`tools/launch_env.sh` preloads tcmalloc and pins the XLA "
               "host-device count for multi-device CPU runs.  "
               "`--plan-store DIR` makes compiles survive restarts: plan "
               "artifacts persist under DIR, the next run restores instead "
               "of recompiling, and workers joining via --join are prefilled "
               "with the front's live plan families before admission "
               "(DESIGN_PERSIST.md).")
    ap.add_argument("--num", type=int, default=64,
                    help="queued requests to synthesize")
    ap.add_argument("--max-m", type=int, default=4)
    ap.add_argument("--max-n", type=int, default=10)
    ap.add_argument("--chunk", type=int, default=2048)
    ap.add_argument("--backend", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sync", action="store_true",
                    help="use the synchronous drain_queue reference")
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through the multi-worker DetFront with N "
                         "worker processes (0 = in-process DetQueue)")
    ap.add_argument("--shm", action="store_true",
                    help="--workers: carry matrix payloads over a per-"
                         "worker shared-memory ring instead of the pickled "
                         "queue (same-host only, bit-identical results)")
    ap.add_argument("--listen", type=str, default="",
                    help="run as a worker daemon on HOST:PORT instead of "
                         "serving a synthetic queue (the front's --connect "
                         "handshake ships the config; combine with "
                         "--serve-once for tests)")
    ap.add_argument("--serve-once", action="store_true",
                    help="with --listen: exit after the first front "
                         "session ends")
    ap.add_argument("--join", type=str, default="",
                    help="run as a worker daemon that dials INTO a running "
                         "front's --accept listener at HOST:PORT (live "
                         "join: same handshake as --listen, direction "
                         "reversed; exits when the front session ends)")
    ap.add_argument("--accept", type=str, default="",
                    help="--connect/--workers: also listen on HOST:PORT "
                         "for workers that dial in later with --join "
                         "(port 0 = ephemeral; the bound address is in "
                         "snapshot()['front']['accept_address'])")
    ap.add_argument("--autoscale", type=int, default=0,
                    help="--connect/--workers: run the SLO autoscaler, "
                         "growing/retiring workers between 1 and N "
                         "(0 = static pool; see launch/autoscale.py)")
    ap.add_argument("--plan-store", type=str, default="", metavar="DIR",
                    help="persist compiled DetEngine plans under DIR and "
                         "restore them on the next run (plan-cache misses "
                         "consult the store before compiling; writes are "
                         "async and never block dispatch; see "
                         "DESIGN_PERSIST.md)")
    ap.add_argument("--prefill", action="store_true",
                    help="--connect/--workers: ship joining workers the "
                         "front's live plan families in the join handshake "
                         "so they warm up (store first, compile second) "
                         "before admission (on by default when --plan-store "
                         "is set)")
    ap.add_argument("--connect", type=str, default="",
                    help="serve through a DetFront over remote worker "
                         "daemons: comma-separated host:port list, one "
                         "address per worker (see --listen)")
    ap.add_argument("--heartbeat", type=float, default=1.0,
                    help="--connect: worker heartbeat cadence in seconds "
                         "(a peer silent for 5 beats is declared dead)")
    ap.add_argument("--ack-timeout", type=float, default=0.0,
                    help="--connect/--workers: declare a worker dead when "
                         "a batch stays unacknowledged this long "
                         "(0 = disabled; bounds frame loss, not compute)")
    ap.add_argument("--policy", choices=("auto", "merge", "never"),
                    default="auto", help="re-bucketing mode (async path)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="admission-control backlog bound for the async "
                         "path (0 = unbounded; shed requests raise "
                         "LoadShedError on their futures)")
    ap.add_argument("--grad-frac", type=float, default=0.0,
                    help="fraction of requests submitted as gradient "
                         "requests (cotangent 1.0): their futures resolve "
                         "to the (m, n) ndarray d(det)/dA instead of a "
                         "float — async and front paths only "
                         "(DESIGN_GRAD.md)")
    ap.add_argument("--verify", action="store_true",
                    help="cross-check every result against the exact "
                         "oracle (gradient requests against jax.grad of "
                         "the flat evaluator)")
    args = ap.parse_args(argv)
    if not 0.0 <= args.grad_frac <= 1.0:
        ap.error("--grad-frac must be in [0, 1]")
    if args.grad_frac > 0 and args.sync:
        ap.error("--grad-frac needs the async or front path (drop --sync)")
    enable_entry_point_cache()  # before any engine exists

    if args.listen:
        # worker daemon mode: no synthetic queue, no report — just a
        # DetQueue+DetEngine behind a socket, config shipped by the front
        from repro.launch.transport import parse_hostport, run_worker_server
        host, port = parse_hostport(args.listen)
        run_worker_server(host, port, serve_once=args.serve_once)
        return None, None

    if args.join:
        # live-join daemon mode: dial a running front's --accept listener
        # and serve that one session (config still ships front→worker)
        from repro.launch.transport import run_worker_client
        run_worker_client(args.join)
        return None, None

    mats = _random_queue(args.num, args.max_m, args.max_n, args.seed)
    grads = None
    if args.grad_frac > 0:
        # seed-derived, so the same command line always submits the same
        # value/grad mix (the verify leg depends on it)
        grng = np.random.default_rng(args.seed + 1)
        grads = [(bool(grng.random() < args.grad_frac), 1.0) for _ in mats]

    if args.sync:
        # warm pass compiles every (bucket shape, padded batch) program so
        # the reported drain is steady-state serving, not compile time
        drain_queue(mats, chunk=args.chunk, backend=args.backend,
                    max_batch=args.max_batch)
        t0 = time.perf_counter()
        dets, stats = drain_queue(mats, chunk=args.chunk,
                                  backend=args.backend,
                                  max_batch=args.max_batch)
        wall = time.perf_counter() - t0
        print(f"# det_serve[sync]: {args.num} requests, {len(stats)} shape "
              f"buckets, backend={args.backend}")
        print("bucket_m,bucket_n,count,dispatches,ranks,wall_s,"
              "mats_per_s,ranks_per_s")
        for (m, n), s in stats.items():
            print(f"{m},{n},{s['count']},{s['dispatches']},{s['ranks']},"
                  f"{s['wall_s']:.4f},{s['mats_per_s']:.1f},"
                  f"{s['ranks_per_s']:.3e}")
    elif args.connect:
        from repro.launch.det_front import DetFront
        from repro.launch.transport import SocketTransport
        addrs = [a.strip() for a in args.connect.split(",") if a.strip()]
        policy = BucketPolicy(max_batch=args.max_batch, mode=args.policy)
        transport = SocketTransport(addrs, heartbeat_s=args.heartbeat)
        with DetFront(transport=transport, chunk=args.chunk,
                      backend=args.backend, policy=policy,
                      max_pending=args.max_pending or None,
                      ack_timeout_s=args.ack_timeout or None,
                      accept=args.accept or None,
                      persist_dir=args.plan_store or None,
                      prefill=args.prefill or None) as front:
            dets, stats, wall = _serve_scaled(
                front, mats, f"front x{len(addrs)}@socket/{args.policy}",
                args.num, args.backend, args.autoscale, grads)
    elif args.workers > 0:
        from repro.launch.det_front import DetFront
        policy = BucketPolicy(max_batch=args.max_batch, mode=args.policy)
        wire = "shm" if args.shm else "local"
        with DetFront(workers=args.workers, chunk=args.chunk,
                      backend=args.backend, policy=policy,
                      max_pending=args.max_pending or None,
                      ack_timeout_s=args.ack_timeout or None,
                      accept=args.accept or None, shm=args.shm,
                      persist_dir=args.plan_store or None,
                      prefill=args.prefill or None) as front:
            dets, stats, wall = _serve_scaled(
                front, mats, f"front x{args.workers}@{wire}/{args.policy}",
                args.num, args.backend, args.autoscale, grads)
    else:
        policy = BucketPolicy(max_batch=args.max_batch, mode=args.policy)
        with DetQueue(chunk=args.chunk, backend=args.backend, policy=policy,
                      max_pending=args.max_pending or None,
                      persist_dir=args.plan_store or None) as q:
            _serve_tolerating_sheds(q, mats, grads)  # warm: compile programs
            q.reset_stats()  # report the timed pass only, not warm+compile
            t0 = time.perf_counter()
            dets = _serve_tolerating_sheds(q, mats, grads)
            wall = time.perf_counter() - t0
            stats = q.snapshot()
        print(f"# det_serve[async/{args.policy}]: {args.num} requests, "
              f"backend={args.backend}")
        print(f"batches={stats['batches']} dispatches={stats['dispatches']} "
              f"merged_requests={stats['merged_requests']} "
              f"padded_slots={stats['padded_slots']} "
              f"shed={stats['shed']} backlog_peak={stats['backlog_peak']} "
              f"plan_cache={stats['plan_cache']['size']}/"
              f"{stats['plan_cache']['max_plans']}")
        _print_buckets(stats["buckets"])
    print(f"total,{args.num} mats,{wall:.4f}s,{args.num / wall:.1f} mats/s")

    if args.verify:
        from repro.core import radic_det, radic_det_oracle
        worst = worst_g = 0.0
        for i, (A, got) in enumerate(zip(mats, dets)):
            if got is None:  # shed under --max-pending: nothing to check
                continue
            if grads is not None and grads[i][0]:
                # gradient request: reference is jax.grad through the
                # differentiable evaluator (a different code path —
                # direct unbatched eval vs the staged/padded batch)
                want_g = np.asarray(jax.grad(radic_det)(jnp.asarray(A)))
                err = np.max(np.abs(np.asarray(got) - want_g))
                worst_g = max(worst_g, err / max(1.0, np.max(np.abs(want_g))))
                continue
            want = radic_det_oracle(np.asarray(A))
            worst = max(worst, abs(got - want) / max(1.0, abs(want)))
        print(f"verify: worst rel err {worst:.2e}"
              + (f", worst grad rel err {worst_g:.2e}"
                 if grads is not None else ""))
        assert worst <= 2e-3, worst
        assert worst_g <= 2e-3, worst_g
    return dets, stats


if __name__ == "__main__":
    main()
