"""The main-path kernels compile for a TPU v5e at real widths.

Compiled against a described ``v5e:2x2`` topology, without a chip:
Mosaic refuses here what it would refuse on the chip (unlowerable ops,
block shapes off the tiling, more scoped VMEM than a kernel may use), so
these tests guard every change at no chip time.  Interpret mode, which
every other kernel test runs in, accepts all of those.

Widths: the flat kernel at the paper's (12, 32) — C(32, 12) =
225,792,840 < 2³¹ ranks — and the same B = 1 stack through the batched
entry point, whose 8 rank rows per step must stay one kernel under the
benchmark's name and gather from A's bf16 parts; the batched combo
kernel at the serving bound ``BucketPolicy.max_batch`` = 64 with
(8, 24); the gradient kernel at B = 16 with (8, 24); the four-chip
grains program at (12, 32); the windowed B = 1 program at (12, 38),
C(38, 12) = 2,707,475,148 > 2³¹ − 1 ranks, in two rank windows; and the
four-chip windowed program at (12, 42), two rank windows per chip.
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


def _op_list(compiled) -> list[str]:
    """The HLO instruction names as a device trace names its ops, read
    the way the benchmark's kernel readers read them."""
    from bench.trace import op_name
    return [op_name(line.strip()) for line in compiled.as_text().splitlines()
            if " = " in line]


def _op_names(compiled) -> set[str]:
    return set(_op_list(compiled))


def _kernel_line(compiled, name: str) -> str:
    (line,) = [x for x in compiled.as_text().splitlines()
               if x.strip().startswith(f"%{name}")]
    return line


def _parts_operand(m: int, n: int) -> str:
    """The B = 1 layout's gather operand: A's three bf16 parts side by
    side, each padded to a whole bf16 tile of columns
    (``common.rank_row_operand``)."""
    return f"bf16[1,{m},{3 * -(-n // 16) * 16}]"


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


def test_rank_rows_kernel_paper_width(one_chip, no_persistent_cache):
    """B = 1 at (12, 32), tile 256: 8 rank rows of the one matrix per
    grid step, still exactly one kernel named as the value kernel, so
    ``radic_combo_roofline.solve`` sees all of its time."""
    m, n = 12, 32
    total, padded = _rank_space(m, n)
    assert rf.rank_rows(1) == 8
    f = jax.jit(lambda A, t: rf.radic_batched_partial_pallas(
        A, t, 0, total, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((1, m, n), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32)).compile()
    _check(compiled, "rank rows (1, 12, 32)")
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert _op_list(compiled).count("radic_batched_partial_pallas") == 1
    # it gathers from A's bf16 parts, not from f32 A
    assert _parts_operand(m, n) in _kernel_line(
        compiled, "radic_batched_partial_pallas")


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
    # within int32 the mesh program holds no windowed op
    assert "radic_windowed_partial_pallas" not in _op_names(compiled)


def test_mesh_windows_four_chips_past_int32(topo, no_persistent_cache,
                                            monkeypatch):
    """The (12, 42) program of ``radic_det_distributed(backend="pallas")``
    over the 2x2 host: C(42, 12) = 11,058,116,888 ranks, each chip's grain
    in 2 rank windows, one windowed kernel per chip (the SPMD program is
    each chip's) gathering from A's bf16 parts, and an all-reduce."""
    from repro.core.distributed import make_distributed_evaluator
    from repro.kernels import radic_windowed as rw
    steer = lambda i: False if i is None else i  # noqa: E731
    monkeypatch.setattr(rf, "resolve_interpret", steer)
    monkeypatch.setattr(rw, "resolve_interpret", steer)
    mesh = Mesh(np.array(topo.devices), ("workers",))
    ev = make_distributed_evaluator(12, 42, mesh=mesh, backend="pallas")
    compiled = jax.jit(ev).lower(
        _spec((12, 42), NamedSharding(mesh, P()))).compile()
    _check(compiled, "mesh windows (12, 42) on 2x2")
    assert "all-reduce" in compiled.as_text()
    ops_ = _op_list(compiled)
    assert ops_.count("radic_windowed_partial_pallas") == 1
    assert "radic_batched_partial_pallas" not in ops_
    assert _parts_operand(12, 42) in _kernel_line(
        compiled, "radic_windowed_partial_pallas")


@pytest.mark.parametrize("m,n,windows", [(12, 38, 2), (20, 40, 65)])
def test_windowed_kernel_past_int32(one_chip, no_persistent_cache, m, n,
                                    windows):
    """(12, 38) and (20, 40), tile 256: the one matrix's rank windows run
    in one program, exactly one kernel named as the windowed kernel, so
    ``radic_window_roofline.solve`` sees all of its time."""
    from repro.core.engine import plan_rank_windows
    from repro.kernels.radic_windowed import radic_windowed_partial_pallas
    total = comb(n, m)
    assert total > 2 ** 31 - 1
    w = plan_rank_windows(m, n, 0, total)
    assert len(w) == windows
    padded = -(-max(w.lengths) // TILE) * TILE
    f = jax.jit(lambda A, t, c, s: radic_windowed_partial_pallas(
        A, t, c, s, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((m, n), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32),
                       _spec((len(w),), one_chip, jnp.int32),
                       _spec((len(w), n), one_chip, jnp.int32)).compile()
    _check(compiled, f"windowed ({m}, {n})")
    ops_ = _op_list(compiled)
    assert ops_.count("radic_windowed_partial_pallas") == 1
    assert "radic_batched_partial_pallas" not in ops_
    assert _parts_operand(m, n) in _kernel_line(
        compiled, "radic_windowed_partial_pallas")


def test_int32_paper_program_has_no_windowed_op(one_chip,
                                                no_persistent_cache):
    """The (12, 32) B = 1 program within int32 keeps its one value kernel
    and holds no windowed op."""
    m, n = 12, 32
    total, padded = _rank_space(m, n)
    f = jax.jit(lambda A, t: rf.radic_batched_partial_pallas(
        A, t, 0, total, padded, tile=TILE, interpret=False))
    compiled = f.lower(_spec((1, m, n), one_chip),
                       _spec((n + 1, m + 1), one_chip, jnp.int32)).compile()
    ops_ = _op_list(compiled)
    assert ops_.count("radic_batched_partial_pallas") == 1
    assert "radic_windowed_partial_pallas" not in ops_
