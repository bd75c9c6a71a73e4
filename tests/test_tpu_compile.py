"""The main-path kernels compile for a TPU v5e at real widths.

Compiled against a described ``v5e:2x2`` topology, without a chip:
Mosaic refuses here what it would refuse on the chip (unlowerable ops,
block shapes off the tiling, more scoped VMEM than a kernel may use), so
these tests guard every change at no chip time.  Interpret mode, which
every other kernel test runs in, accepts all of those.

Widths: the flat kernel at the paper's (12, 32) — C(32, 12) =
225,792,840 < 2³¹ ranks; the batched combo kernel at the serving bound
``BucketPolicy.max_batch`` = 64 with (8, 24); the gradient kernel at
B = 16 with (8, 24); and the four-chip grains program at (12, 32).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.pascal import comb
from repro.kernels import radic_fused as rf

TILE = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=False)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled, name):
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel"
    print(name, compiled.memory_analysis())


def _op_names(compiled) -> set[str]:
    """The HLO instruction names as a device trace names its ops, read
    the way the benchmark's kernel readers read them."""
    from bench.trace import op_name
    return {op_name(line.strip()) for line in compiled.as_text().splitlines()
            if " = " in line}


def _rank_space(m, n):
    total = comb(n, m)
    assert total < 2 ** 31
    return total, -(-total // TILE) * TILE


def test_flat_kernel_paper_width(one_chip, no_persistent_cache):
    m, n = 12, 32
    total, padded = _rank_space(m, n)
    f = jax.jit(lambda A, t: rf.radic_partial_pallas(
        A, t, 0, total, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((m, n), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32)).compile()
    _check(compiled, "flat (12, 32)")


def test_batched_combo_kernel_max_batch(one_chip, no_persistent_cache):
    B, m, n = 64, 8, 24
    total, padded = _rank_space(m, n)
    f = jax.jit(lambda A, t: rf.radic_batched_partial_pallas(
        A, t, 0, total, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((B, m, n), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32)).compile()
    _check(compiled, "batched combo (64, 8, 24)")
    # radic_combo_roofline.* match this name in the device trace
    assert "radic_batched_partial_pallas" in _op_names(compiled)


def test_grad_combo_kernel(one_chip, no_persistent_cache):
    B, m, n = 16, 8, 24
    total, padded = _rank_space(m, n)
    f = jax.jit(lambda A, c, t: rf.radic_batched_grad_partial_pallas(
        A, c, t, 0, total, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((B, m, n), one_chip), _spec((B,), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32)).compile()
    _check(compiled, "grad combo (16, 8, 24)")
    assert "radic_batched_grad_partial_pallas" in _op_names(compiled)


def test_mesh_grains_four_chips(topo, no_persistent_cache, monkeypatch):
    """The grains program of ``radic_det_distributed(backend="pallas")``
    over the 2x2 host: one kernel per chip and an all-reduce.  The
    kernels choose interpret mode from the (CPU) default backend, so the
    test steers them to compiled mode."""
    from repro.core.distributed import make_distributed_evaluator
    monkeypatch.setattr(rf, "resolve_interpret",
                        lambda i: False if i is None else i)
    mesh = Mesh(np.array(topo.devices), ("workers",))
    ev = make_distributed_evaluator(12, 32, mesh=mesh, backend="pallas")
    compiled = jax.jit(ev).lower(
        _spec((12, 32), NamedSharding(mesh, P()))).compile()
    _check(compiled, "mesh grains (12, 32) on 2x2")
    assert "all-reduce" in compiled.as_text()
    assert "radic_batched_partial_pallas" in _op_names(compiled)
