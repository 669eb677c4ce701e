"""JAX's persistent compilation cache, placed once for the whole program.

Compiling is a large share of a cold run — a training step, an epoch
program, every serving prefill rung — and the cache's directory is part of
its key, so a directory that moves never hits. One function places it,
called before the first compile by every entry point (the CLI,
``TransformerLM``'s step builders, ``fit_epochs``, the serve engine,
``bench.py``, ``chip_smoke.py``):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already honours it; this module
  sets no directory.
- unset: the cache lives at ``<checkout>/.jax_cache`` — a fixed path
  derived from the package's location, so every process of a checkout
  shares it.

Every compile is persisted (the min-compile-time and min-entry-size floors
are zeroed): a cold start wants the whole program set replayed, not just
the slow members. Configuration is lazy — nothing happens at import.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

logger = logging.getLogger(__name__)

__all__ = ["compile_cache_dir", "ensure_compile_cache",
           "compile_cache_stats"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_LOCK = threading.Lock()
_CONFIGURED: Optional[str] = None


def compile_cache_dir() -> str:
    """The directory the persistent cache uses: ``JAX_COMPILATION_CACHE_DIR``
    when set, ``<checkout>/.jax_cache`` otherwise."""
    return os.environ.get(_ENV, "").strip() or _CHECKOUT_DIR


def ensure_compile_cache() -> str:
    """Turn the persistent cache on before the caller's first compile and
    return its directory. Idempotent and cheap after the first call."""
    global _CONFIGURED
    if _CONFIGURED is not None:
        return _CONFIGURED
    with _LOCK:
        if _CONFIGURED is not None:
            return _CONFIGURED
        import jax

        d = os.environ.get(_ENV, "").strip()
        if not d:
            d = _CHECKOUT_DIR
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _CONFIGURED = d
        from deeplearning4j_tpu.monitor import record_counter, tracer

        tracer().event("compile_cache.configured", dir=d)
        record_counter("compile_cache_configured_total")
        logger.info("persistent XLA compilation cache at %s", d)
        return d


def compile_cache_stats() -> dict:
    """On-disk view of the persistent cache: ``{dir, configured,
    entries, bytes}`` — what a bench artifact reports so warm-start
    claims are checkable."""
    d = compile_cache_dir()
    entries = 0
    size = 0
    for root, _dirs, files in os.walk(d):
        for f in files:
            entries += 1
            try:
                size += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return {"dir": d, "configured": _CONFIGURED == d,
            "entries": entries, "bytes": size}
