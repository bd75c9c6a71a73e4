"""Backend parity: the fused Pallas batched kernel and the jnp flat path
must agree on identical inputs, across dtypes and the bucket capacities
the serving tier dispatches (1, 2, max_batch).

The kernel computes in float32 internally (TPU VPU/MXU), so the float64
leg — run in a subprocess with x64 enabled to keep this process's global
config untouched — asserts parity at float32 precision while checking
the jnp path really produced float64.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_batched_evaluator, radic_det_batched

REPO = os.path.dirname(os.path.dirname(__file__))

MAX_BATCH = 8  # the bucket capacity this battery serves at
CAPACITIES = (1, 2, MAX_BATCH)
SHAPES = [(2, 6), (3, 7), (1, 5), (3, 3), (4, 9)]


@pytest.mark.parametrize("cap", CAPACITIES)
@pytest.mark.parametrize("m,n", SHAPES)
def test_backends_agree_float32(m, n, cap, rng):
    As = jnp.asarray(rng.normal(size=(cap, m, n)).astype(np.float32))
    got_pallas = np.asarray(radic_det_batched(As, backend="pallas"))
    got_jnp = np.asarray(radic_det_batched(As, chunk=64))
    assert got_pallas.shape == got_jnp.shape == (cap,)
    np.testing.assert_allclose(got_pallas, got_jnp, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("cap", CAPACITIES)
def test_evaluator_backends_agree(cap, rng):
    """The bound-shape evaluators (DetQueue's dispatch path) agree the
    same way the one-shot entry points do."""
    m, n = 3, 8
    As = jnp.asarray(rng.normal(size=(cap, m, n)).astype(np.float32))
    ev_jnp = make_batched_evaluator(m, n, chunk=64)
    ev_pal = make_batched_evaluator(m, n, backend="pallas")
    np.testing.assert_allclose(np.asarray(ev_pal(As)), np.asarray(ev_jnp(As)),
                               rtol=1e-3, atol=1e-4)


def _paper_limit() -> float:
    """The paper cell's ``value_err`` limit, in units of the term size
    SCALE = sqrt(det(A·Aᵀ)) (``bench/configs/paper_det.json``)."""
    with open(os.path.join(REPO, "bench", "configs", "paper_det.json")) as f:
        return json.load(f)["limits"]["value_err"]


@pytest.mark.parametrize("cap", CAPACITIES)
@pytest.mark.parametrize("m,n", SHAPES)
def test_batch_composition_invariance(m, n, cap, rng):
    """A matrix's value does not depend on the batch it is served in.
    At cap ≥ 2 each matrix is bit-identical when its batch is permuted
    and when its companions are redrawn: every sublane and lane runs its
    own elimination, so the serving tier's bit-identity contract (any
    routing, merging or splitting of requests gives the 1-process
    ``DetQueue``'s bits) rests on this.  At cap 1 there is no companion;
    the one matrix's value stays within the paper cell's limit of the
    float64 oracle."""
    from repro.kernels import ops, ref
    As = rng.normal(size=(cap, m, n)).astype(np.float32)
    got = np.asarray(ops.radic_det_batched_pallas(jnp.asarray(As)))
    if cap == 1:
        scale = np.sqrt(np.linalg.det(As[0].astype(np.float64)
                                      @ As[0].T.astype(np.float64)))
        err = abs(float(got[0]) - ref.radic_det_oracle(As[0])) / scale
        assert err <= _paper_limit(), err
        return
    perm = rng.permutation(cap)
    if (perm == np.arange(cap)).all():
        perm = np.roll(perm, 1)
    permuted = np.asarray(ops.radic_det_batched_pallas(jnp.asarray(As[perm])))
    np.testing.assert_array_equal(permuted, got[perm])
    others = rng.normal(size=(cap, m, n)).astype(np.float32)
    others[0] = As[-1]
    mixed = np.asarray(ops.radic_det_batched_pallas(jnp.asarray(others)))
    np.testing.assert_array_equal(mixed[0], got[-1])


def test_combo_kernel_bit_identical_partial_ranges(rng):
    """Rank-range partials (the distributed grain path), including a
    range that straddles a tile boundary: each matrix's partial is the
    float64 oracle's to f32 rounding (≤ 84 terms of size O(1)), and
    bit-identical when the batch is reversed."""
    from repro.kernels import ops, ref
    m, n = 3, 9  # C(9, 3) = 84
    As = rng.normal(size=(4, m, n)).astype(np.float32)
    for q_start, count in [(0, 84), (10, 40), (60, 24)]:
        got = np.asarray(ops.radic_det_batched_pallas(
            jnp.asarray(As), q_start, count, tile=32))
        want = [ref.radic_partial_ref(A, q_start, count) for A in As]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        back = np.asarray(ops.radic_det_batched_pallas(
            jnp.asarray(As[::-1]), q_start, count, tile=32))
        np.testing.assert_array_equal(back, got[::-1])


X64_PARITY = textwrap.dedent("""
    import os
    os.environ["JAX_ENABLE_X64"] = "True"
    import numpy as np, jax, jax.numpy as jnp
    assert jax.config.jax_enable_x64
    from repro.core import radic_det_batched
    rng = np.random.default_rng(0)
    for cap in (1, 2, 8):
        for (m, n) in [(2, 6), (3, 7), (3, 3)]:
            As = jnp.asarray(rng.normal(size=(cap, m, n)))  # float64
            got_j = np.asarray(radic_det_batched(As, chunk=64))
            assert got_j.dtype == np.float64, got_j.dtype
            got_p = np.asarray(radic_det_batched(As, backend="pallas"))
            # kernel math is f32 internally: parity at f32 precision
            assert np.allclose(got_p, got_j, rtol=1e-3, atol=1e-4), \\
                (cap, m, n, got_p, got_j)
    print("X64_PARITY_OK")
""")


def test_backends_agree_float64_when_enabled():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", X64_PARITY],
                         capture_output=True, text=True, env=env, cwd=REPO)
    assert "X64_PARITY_OK" in out.stdout, (out.stdout, out.stderr[-2000:])
