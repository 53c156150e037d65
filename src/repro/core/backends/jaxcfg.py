"""JAX persistent compilation cache, armed before the first jit.

A restarted advisory server or campaign re-traces its designs in
milliseconds but would re-jit every evaluator from scratch; with the
persistent cache the second launch deserializes its executables instead.
The cache lives where JAX's own ``JAX_COMPILATION_CACHE_DIR`` says when
that is set (JAX reads it; this module then sets no directory).  When it
is not set, the cache lives in a fixed directory inside the checkout,
``<repo>/.jax_cache`` — fixed because the directory is part of what a
later process must find again; an installed (non-checkout) package then
sets none.

:func:`configure_jax` is called by :mod:`repro.core.backends.operands`
— the single module every jax-backed backend imports first — so the
cache is armed before the first ``jax.jit`` trace no matter which
backend compiles first.  The numpy worklist path never imports this
module's jax side.

The thresholds are zeroed because our kernels are small and fast to
compile *individually* — it is the dozens of (graph, bucket) jit-cache
entries a warm campaign accumulates that make a cold restart slow, and
the default "only cache slow compiles" heuristic would skip all of them.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[4]
#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``<repo>/.jax_cache`` when this package runs from a source checkout
#: (``src/`` layout next to ``pyproject.toml``), else None — an installed
#: package has no checkout to keep a cache in, and then caches nothing
DEFAULT_DIR = (_CHECKOUT / ".jax_cache"
               if (_CHECKOUT / "pyproject.toml").is_file() else None)


def configure_jax() -> None:
    """Arm JAX's persistent compilation cache.  Safe to call at any point
    before or after jax initializes — the cache is consulted at compile
    time, not at backend-init time."""
    import jax
    if not os.environ.get(ENV_VAR) and DEFAULT_DIR is not None:
        DEFAULT_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    # cache everything: restart latency is dominated by the *number* of
    # re-jits, not by any single slow compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
