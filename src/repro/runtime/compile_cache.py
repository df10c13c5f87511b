"""Where jax's persistent compilation cache lives, set once per process.

The directory is chosen in this order:

1. ``JAX_COMPILATION_CACHE_DIR`` when it is set — the process's launcher
   owns the cache and nothing here overrides it, not even an explicit
   ``--compile-cache``;
2. the explicit directory a caller passes (``--compile-cache``, the
   ``compile_cache`` a campaign hands its worker processes);
3. ``<checkout>/.jax-cache`` — a fixed path, so every run of the same
   checkout finds the compiles of the runs before it (a directory named
   after a temporary store, a pid or the time would never hit).

:func:`configure` must run before the process's first jit: jax decides
at its first compile whether the persistent cache is in use, and never
looks again.  The CLI entry points and ``chip_smoke.py`` call it first
thing.  Import contract: stdlib-only at import (``repro check``), so a
campaign parent that must not touch jax can resolve the directory for
its workers with :func:`cache_dir`.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax-cache`` (this file is ``<checkout>/src/repro/
#: runtime/compile_cache.py``); listed in ``.gitignore``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax-cache")

_configured: str | None = None
_counts = {"hits": 0, "misses": 0}


def cache_dir(explicit: str | None = None) -> str:
    """The cache directory this process should use (see module doc)."""
    env = os.environ.get(ENV)
    if env:
        return os.path.abspath(env)
    return os.path.abspath(explicit) if explicit else DEFAULT_DIR


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _counts["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _counts["misses"] += 1


def configure(explicit: str | None = None) -> str:
    """Point jax's persistent compilation cache at :func:`cache_dir` and
    count its hits and misses.  Call once, before the first jit; a
    second call with a different directory raises instead of being
    silently ignored by jax."""
    global _configured
    path = cache_dir(explicit)
    if _configured is not None:
        if _configured != path:
            raise RuntimeError(
                f"compilation cache already configured at {_configured}; "
                f"cannot move it to {path} in the same process")
        return path
    import jax
    from jax import monitoring
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, however quick its compile: small CPU
    # compiles are what worker processes share most often
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    monitoring.register_event_listener(_on_event)
    _configured = path
    return path


def counters() -> dict:
    """Persistent-cache hits and misses since :func:`configure`, and the
    configured directory (None before it)."""
    return {"persistent_cache_hits": _counts["hits"],
            "persistent_cache_misses": _counts["misses"],
            "cache_dir": _configured}
