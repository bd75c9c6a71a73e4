"""Direct unit tests for the jax seam.

The subprocess mesh tests (test_distributed / test_pipeline) prove the
end-to-end paths, but bury any seam regression inside an ``assert "OK"
in stdout``.  These tests check the forwarding to ``jax.shard_map`` via
monkeypatch, and ``pvary`` plus the reduction helpers on a real
single-device mesh, so a regression fails with a readable message.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel import compat


def _ident(x):
    return x


def _fake_shard_map(monkeypatch, captured):
    def fake(f, *, mesh, in_specs, out_specs, check_vma=True):
        captured.update(f=f, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=check_vma)
        return "mapped"

    monkeypatch.setattr(jax, "shard_map", fake)


def test_resolves_installed_jax(monkeypatch):
    captured = {}
    _fake_shard_map(monkeypatch, captured)
    assert compat.shard_map(_ident, mesh="M", in_specs=(P(),),
                            out_specs=P()) == "mapped"
    assert captured["f"] is _ident and captured["mesh"] == "M"
    assert captured["in_specs"] == (P(),) and captured["out_specs"] == P()


def test_new_spelling_gets_check_vma(monkeypatch):
    captured = {}
    _fake_shard_map(monkeypatch, captured)
    out = compat.shard_map(_ident, mesh="M", in_specs=(P(),),
                           out_specs=P(), check_vma=False)
    assert out == "mapped"
    assert captured["check_vma"] is False


def _two_axis_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("a", "b"))


def test_old_spelling_gets_check_rep():
    """A value already varying over every requested axis comes back
    as it is: jax refuses a varying cast over an axis it varies over."""
    mesh = _two_axis_mesh()

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(P(("a", "b")),), out_specs=P(("a", "b")))
    def f(x):
        y = compat.pvary(x, ("a", "b"))
        assert y is x
        assert jax.typeof(y).vma == {"a", "b"}
        return y

    np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                  np.arange(4.0))


def test_pvary_prefers_pvary_then_pcast():
    """A replicated value is cast to varying over every requested axis."""
    mesh = _two_axis_mesh()

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(P(),), out_specs=P(("a", "b")))
    def f(x):
        assert jax.typeof(x).vma == frozenset()
        y = compat.pvary(x, ("a", "b"))
        assert jax.typeof(y).vma == {"a", "b"}
        return y

    np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                  np.arange(4.0))


def test_both_check_spellings_rejected():
    """The jax 0.4 ``check_rep`` spelling is gone from the seam."""
    with pytest.raises(TypeError):
        compat.shard_map(_ident, mesh="M", in_specs=(), out_specs=P(),
                         check_rep=False)


def test_check_flag_omitted_means_native_default(monkeypatch):
    captured = {}
    _fake_shard_map(monkeypatch, captured)
    compat.shard_map(_ident, mesh="M", in_specs=(), out_specs=P())
    assert captured["check_vma"] is True  # native default untouched


def test_pvary_casts_only_missing_axes(monkeypatch):
    """A value varying over some of the axes is cast over the rest
    only, through ``pcast(..., to="varying")``."""
    calls = []
    real = jax.lax.pcast

    def spy(x, axes, *, to):
        calls.append((tuple(axes), to))
        return real(x, axes, to=to)

    monkeypatch.setattr(jax.lax, "pcast", spy)
    mesh = _two_axis_mesh()

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(P("a"),), out_specs=P(("a", "b")))
    def f(x):
        return compat.pvary(x, ("a", "b"))

    f(jnp.arange(4.0))
    assert calls == [(("b",), "varying")]
    assert compat.pvary(7, ()) == 7      # no axes -> always identity


def test_psum_scalar_and_axis_size_on_real_mesh():
    mesh = Mesh(np.array(jax.devices()[:1]), ("w",))

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(P("w"),), out_specs=P())
    def total(x):
        return compat.psum_scalar(jnp.sum(x), ("w",))

    assert float(total(jnp.arange(4.0))) == 6.0

    @functools.partial(compat.shard_map, mesh=mesh,
                       in_specs=(P("w"),), out_specs=P())
    def size(x):
        return jnp.zeros(()) + compat.axis_size("w")

    assert int(size(jnp.arange(2.0))) == 1
    assert compat.psum_scalar(5, ()) == 5  # no axes -> identity


def test_no_direct_shard_map_access_outside_compat():
    """Acceptance: jax.shard_map spellings only inside parallel/compat.

    Delegates to reprolint's compat-seam pass (tools/lint), which
    supersedes the old textual grep: the AST pass also catches aliased
    imports, ``from``-imports, resolved attribute chains and ``getattr``
    spellings, and — unlike the grep — does not false-positive on
    docstrings that merely *mention* the forbidden names.
    """
    import pathlib
    import sys
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from tools.lint import lint_paths
    from tools.lint.passes import CompatSeamPass
    findings, n_files = lint_paths([str(root / "src")], [CompatSeamPass()])
    assert n_files > 0
    assert not findings, "\n".join(f.render() for f in findings)


@pytest.fixture
def cache_config():
    """Restore the process-global cache options the seam sets."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in names}
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache as cc
    cc.reset_cache()


@pytest.mark.parametrize("case", ["unset", "env", "cpu"])
def test_entry_point_cache_placement(case, cache_config, monkeypatch,
                                     tmp_path):
    """Unset: one fixed dir inside the checkout.  A dir already given
    (``JAX_COMPILATION_CACHE_DIR``, which jax reads into this option):
    kept, no other set.  A CPU-pinned process: no cache at all."""
    import pathlib
    checkout = pathlib.Path(compat.__file__).resolve().parents[3]
    if case == "cpu":
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    else:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    if case == "env":
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    want = {"unset": str(checkout / ".jax_cache"), "env": str(tmp_path),
            "cpu": None}[case]
    assert compat.enable_entry_point_cache() is (case != "cpu")
    assert jax.config.jax_compilation_cache_dir == want
    compat.enable_entry_point_cache()  # a second entry point: no change
    assert jax.config.jax_compilation_cache_dir == want
