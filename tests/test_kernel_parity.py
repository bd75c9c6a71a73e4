"""Backend parity: the fused Pallas batched kernel and the jnp flat path
must agree on identical inputs, across dtypes and the bucket capacities
the serving tier dispatches (1, 2, max_batch).

The kernel computes in float32 internally (TPU VPU/MXU), so the float64
leg — run in a subprocess with x64 enabled to keep this process's global
config untouched — asserts parity at float32 precision while checking
the jnp path really produced float64.
"""

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


@pytest.mark.parametrize("cap", CAPACITIES)
@pytest.mark.parametrize("m,n", SHAPES)
def test_combo_kernel_bit_identical_to_bygrid(m, n, cap, rng):
    """The combo-reuse batched kernel (tile-only grid, batch contracted
    in-kernel) must be *bit-identical* to the legacy (B, num_tiles)
    grid: per-lane math is unchanged, only the sharing of the unranked
    tile differs.  Exact equality, not allclose — any reassociation of
    the per-matrix reduce would break the serving tier's bit-identity
    story."""
    from repro.kernels import ops
    As = jnp.asarray(rng.normal(size=(cap, m, n)).astype(np.float32))
    combo = np.asarray(ops.radic_det_batched_pallas(As))
    bygrid = np.asarray(ops.radic_det_batched_pallas_bygrid(As))
    np.testing.assert_array_equal(combo, bygrid)


def test_combo_kernel_bit_identical_partial_ranges(rng):
    """Rank-range partials (the distributed grain path) agree, including
    a range that straddles a tile boundary.  Not to the bit: the combo
    kernel keeps one accumulator per lane across tiles and sums the
    lanes at the end, while the by-grid kernel sums each tile first.
    Over ≤ 84 terms of size O(1) the two association orders differ by
    a few f32 ulps of the largest partial, hence rtol 1e-5 / atol 1e-5."""
    from repro.kernels import ops
    m, n = 3, 9  # C(9, 3) = 84
    As = jnp.asarray(rng.normal(size=(4, m, n)).astype(np.float32))
    for q_start, count in [(0, 84), (10, 40), (60, 24)]:
        combo = np.asarray(ops.radic_det_batched_pallas(
            As, q_start, count, tile=32))
        bygrid = np.asarray(ops.radic_det_batched_pallas_bygrid(
            As, q_start, count, tile=32))
        np.testing.assert_allclose(combo, bygrid, rtol=1e-5, atol=1e-5)


X64_PARITY = textwrap.dedent("""
    import os
    os.environ["JAX_ENABLE_X64"] = "True"
    import numpy as np, jax, jax.numpy as jnp
    assert jax.config.jax_enable_x64
    from repro.core import radic_det_batched
    from repro.kernels import ops
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
            # combo-reuse vs legacy grid stays bitwise under x64 too
            got_c = np.asarray(ops.radic_det_batched_pallas(As))
            got_g = np.asarray(ops.radic_det_batched_pallas_bygrid(As))
            assert np.array_equal(got_c, got_g), (cap, m, n, got_c, got_g)
    print("X64_PARITY_OK")
""")


def test_backends_agree_float64_when_enabled():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", X64_PARITY],
                         capture_output=True, text=True, env=env, cwd=REPO)
    assert "X64_PARITY_OK" in out.stdout, (out.stdout, out.stderr[-2000:])
