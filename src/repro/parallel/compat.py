"""The single seam between the repo and jax's per-device SPMD surface.

Every mesh call site goes through this module, so the rest of the code
is spelled one way and a jax rename touches one file.  The supported
jax is the installed one, 0.9 (DESIGN_COMPAT.md):

* ``shard_map`` — ``jax.shard_map`` with its ``check_vma`` flag.
* ``pvary`` — marks a value as device-varying so it can enter
  collectives under the varying-manual-axes (VMA) type system.  Only the
  axes the value does not already vary over are cast, with
  ``jax.lax.pcast(..., to="varying")``; a cast over an axis the operand
  already varies over is an error in jax.
* ``psum_scalar`` — ``pvary`` + one ``psum`` per mesh axis name.  This is
  the repo's reduction idiom for grain/chunk partials; keeping it here
  means call sites never touch ``jax.lax.psum`` axis plumbing directly.
* ``axis_size`` — ``jax.lax.axis_size``.

No other module may read ``jax.shard_map`` / ``jax.experimental.
shard_map`` / ``jax.lax.pvary`` / ``jax.lax.pcast`` — reprolint's
``compat-seam`` pass enforces the ``shard_map`` half.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Callable, Sequence

import jax

__all__ = ["shard_map", "pvary", "psum_scalar", "axis_size",
           "export_supported", "serialize_lowered", "deserialize_exported",
           "enable_compilation_cache",
           "enable_entry_point_cache"]


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_vma: bool | None = None):
    """``jax.shard_map`` of ``f`` over ``mesh``.  ``f`` is the only
    positional argument, so ``functools.partial(shard_map, mesh=...,
    in_specs=..., out_specs=...)`` works as a decorator; ``check_vma``
    left at None keeps jax's default."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def pvary(x, axis_names: Sequence[str]):
    """Mark ``x`` as varying over ``axis_names`` inside shard_map; axes
    ``x`` already varies over are left as they are."""
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axis_names if a not in vma)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def axis_size(axis_name: str):
    """Size of a bound mesh axis."""
    return jax.lax.axis_size(axis_name)


# --------------------------------------------------------- AOT export seam
#
# ``jax.export`` serializes a lowered/compiled program to bytes and
# reloads it in another process.  The plan store treats export as a pure
# acceleration: when any of these return None the store falls back to
# metadata-only persistence (re-lower from cached statics), which is
# always correct — so every failure path below degrades, never raises.
#
# Blob reload is additionally **opt-in** (``REPRO_PLAN_BLOBS=1``): on
# jax 0.4.37's CPU backend (measured there, not since) a reloaded
# executable whose program contains LAPACK custom calls (every
# ``jnp.linalg`` LU — i.e. every determinant program in this repo)
# segfaulted at first call, because the serialized form bakes in native
# custom-call pointers that do not survive the process boundary.  That
# failure is a hard crash, not an exception, so it cannot be caught and
# degraded at use time — it has to be gated off up front.  The safe
# cross-process compile-skip is the XLA persistent compilation cache
# (:func:`enable_compilation_cache` below), which is content-addressed
# and re-links custom calls at load.

_BLOBS_ENV = "REPRO_PLAN_BLOBS"


def _export_module():
    if os.environ.get(_BLOBS_ENV, "") != "1":
        return None
    try:
        import jax.export as mod  # real submodule since 0.4.30; a plain
        # getattr on the lazily-populated ``jax`` namespace misses it
    except Exception:
        return None
    if hasattr(mod, "export") and hasattr(mod, "deserialize"):
        return mod
    return None


def export_supported() -> bool:
    """Whether this jax can serialize AOT executables for the plan store."""
    return _export_module() is not None


def enable_entry_point_cache() -> bool:
    """The entry points' compile cache, set before any engine exists:
    the dir ``JAX_COMPILATION_CACHE_DIR`` names (jax reads it itself),
    else ``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of the cache key.  A process pinned to the CPU
    (``JAX_PLATFORMS=cpu``: tests, CPU debugging) gets none — its
    compiles are cheap, and a shared on-disk cache would couple the
    timing of one run to the runs before it."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return False
    return enable_compilation_cache(
        str(Path(__file__).resolve().parents[3] / ".jax_cache"))


def enable_compilation_cache(path: str) -> bool:
    """Point jax's persistent compilation cache at ``path``; True if on.

    Deferential: a cache dir already configured — by
    ``JAX_COMPILATION_CACHE_DIR``, the user, or an earlier call — is left
    untouched.  Entry points call it through
    :func:`enable_entry_point_cache`, the plan store with
    ``<persist_dir>/xla-cache`` (DESIGN_PERSIST.md),
    which therefore applies only where nothing was configured before.
    Failures raise: a misconfigured cache must not turn into a silent
    cold compile of every program.
    """
    if jax.config.jax_compilation_cache_dir is not None:
        return True
    jax.config.update("jax_compilation_cache_dir", path)
    # Plan-family compiles are the whole point of the cache here, and
    # some are quick — cache everything, not just slow compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches cache-off at the first compile of the process; if
    # anything compiled before us (warm-up jits, an import-time trace),
    # the dir just set is ignored.  reset_cache drops the latch so the
    # next compile re-reads the config.
    from jax.experimental.compilation_cache import compilation_cache as _cc
    _cc.reset_cache()
    return True


def serialize_lowered(fn, *args) -> bytes | None:
    """Serialize jitted ``fn`` specialized to ``args`` → bytes, or None.

    ``args`` are abstract specs (``ShapeDtypeStruct``) or concrete
    arrays; the serialized form captures the StableHLO of the same
    program ``fn.lower(*args).compile()`` would build, so a reload
    compiles to a bit-identical executable.
    """
    mod = _export_module()
    if mod is None:
        return None
    try:
        # .serialize() hands back a bytearray on some jax legs; the plan
        # store's blob contract is immutable plain bytes
        return bytes(mod.export(fn)(*args).serialize())
    except Exception:
        return None


def deserialize_exported(blob: bytes):
    """Reload a :func:`serialize_lowered` blob → callable, or None.

    The returned callable re-traces through ``exported.call`` under jit;
    callers treat None (unsupported jax, stale/foreign blob) as a store
    miss and re-lower from statics instead.
    """
    mod = _export_module()
    if mod is None:
        return None
    try:
        exported = mod.deserialize(blob)
        return jax.jit(exported.call)
    except Exception:
        return None


def psum_scalar(x, axis_names: Sequence[str]):
    """Sum ``x`` over every named mesh axis (inside shard_map).

    Works on replicated *or* varying operands: the operand is first
    ``pvary``'d over the axes it does not vary over yet, then reduced
    one axis at a time.
    """
    axes = tuple(axis_names)
    if not axes:
        return x
    x = pvary(x, axes)
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x
