"""Central accessors for every ``REPRO_*`` environment knob, and for the
facts about the process the code observes instead of asking for.

This module is the ONE place the codebase reads its environment
switches.  Nothing outside it may call ``os.environ.get("REPRO_...")``
— `tools/check_docs.py` scans the tree for strays, and also checks
that every knob in :data:`KNOBS` appears in the README env-var
reference ("Which knob do I turn"), so a new knob cannot land without
documentation.

Knob table
----------

========================  =======  ========================================
knob                      default  meaning
========================  =======  ========================================
REPRO_BOUNDARY_BACKEND    unset    Overrides ``backend="auto"`` resolution
                                   for every boundary op
                                   (`core.boundary.resolve_backend`):
                                   ``reference`` or ``pallas``.  Unset:
                                   pallas on TPU, reference elsewhere.
REPRO_ONCORE_PRNG         ``0``    ``1`` opts the Pallas encode kernels
                                   into on-core PRNG stochastic rounding
                                   (TPU-only; relaxes ref<->pallas parity
                                   to the statistical gate).
========================  =======  ========================================

Accessors read ``os.environ`` at call time, so tests may
``monkeypatch.setenv`` freely.

Observed, not configured: whether Pallas kernels run in interpret mode
(`pallas_interpret` — exactly when the default backend is not a TPU),
and where JAX keeps its persistent compilation cache
(`use_compile_cache`).
"""
from __future__ import annotations

import os

# name -> (default, one-line doc).  The keys are the exported knob set
# tools/check_docs.py cross-checks against the README reference table.
KNOBS = {
    "REPRO_BOUNDARY_BACKEND": (
        "", "force the boundary codec backend: reference | pallas"),
    "REPRO_ONCORE_PRNG": (
        "0", "1 = on-core TPU PRNG stochastic rounding (statistical gate)"),
}

# <checkout>/.jax_cache — fixed, so a later run in the same checkout
# finds what an earlier one compiled (the path is part of the cache key)
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _get(name: str) -> str:
    return os.environ.get(name, KNOBS[name][0])


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: exactly when the
    default backend is not a TPU.  Asked at the first kernel call, never
    at import, so importing the kernels initializes no backend."""
    import jax
    return jax.default_backend() != "tpu"


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before the first
    compile.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
    it and nothing else is set here; otherwise the cache goes to the
    fixed in-checkout :data:`DEFAULT_COMPILE_CACHE`.  Returns the
    directory in use."""
    import jax
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE


def boundary_backend_override() -> str:
    """The forced boundary backend ('' = no override, resolve by
    platform).  Consulted on every ``backend="auto"`` resolution."""
    return _get("REPRO_BOUNDARY_BACKEND")


def oncore_prng() -> bool:
    """Whether the on-core PRNG encode opt-in is active (TPU-only)."""
    return _get("REPRO_ONCORE_PRNG") == "1"
