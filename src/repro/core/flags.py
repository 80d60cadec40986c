"""Process-local tracing flags.

``force_unroll`` is used by the dry-run cost probes: XLA's
``cost_analysis()`` counts a while-loop body ONCE (not x trip count), so
any scanned loop (layers, attention KV chunks, SSM chunks) hides its
true cost.  The probes lower a 1-unit and a 2-unit model with every scan
unrolled to straightline HLO, giving exact per-unit costs that are then
extrapolated to the full depth (launch/dryrun.py).
"""
from __future__ import annotations

import contextlib
import threading

_local = threading.local()

__all__ = ["dryrun_unroll", "force_unroll", "scan_unroll_arg",
           "default_interpret", "enable_compile_cache"]


def default_interpret() -> bool:
    """Default ``interpret=`` for pallas kernels: False on TPU backends.

    Every pallas call site (mpmm, flashattn) resolves ``interpret=None``
    through this helper, so kernels compile to Mosaic on TPU and fall
    back to the (slow, bit-exact) interpreter elsewhere — the seed's
    hardcoded ``interpret=True`` silently interpreted on real TPUs.
    """
    import jax

    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is overridden.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout — a fixed path, because the path is part of
    the cache key (a per-run temporary directory would never hit).
    Called by entry points (``chip_smoke.py``, ``launch/serve.py``),
    never at import.  Returns the directory in use.
    """
    import os
    import pathlib

    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def dryrun_unroll() -> bool:
    return getattr(_local, "unroll", False)


def scan_unroll_arg():
    """Value for jax.lax.scan(..., unroll=...) at a loop call site."""
    return True if dryrun_unroll() else 1


@contextlib.contextmanager
def force_unroll(on: bool = True):
    old = getattr(_local, "unroll", False)
    _local.unroll = on
    try:
        yield
    finally:
        _local.unroll = old
