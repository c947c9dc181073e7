"""Persistent XLA compilation cache: recompiles hit disk, not the compiler.

Thin, idempotent wrapper over ``jax.experimental.compilation_cache``: once
:func:`enable` points JAX at a cache directory, every XLA backend compile
first probes the on-disk cache (keyed by HLO + compile options + backend
version). A restarted server or trainer re-traces its programs but the
expensive backend compile becomes a millisecond disk load — the difference
between the ~0.5–2 s per-bucket compile tax and warm-path restart latency.

Where the cache lives (:func:`enable`): ``$JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads it itself and no other directory is set in code),
else the directory the caller names, else the fixed ``.jax_cache/`` at the
root of the checkout. The path is part of every entry's key, so it never
depends on a temporary directory, a process id or the time.

Attribution: :class:`CompileEvents` snapshots JAX's monitoring counters for
persistent-cache hits (``/jax/compilation_cache/cache_hits`` — a disk load)
vs misses (``cache_misses`` — a true XLA compile). The serving stats use
the deltas across one jit call to split ``bucket_compiles`` (real compiles)
from ``cache_loads`` (jit-cache growth satisfied from disk): with the cache
enabled a recompile still grows the in-memory jit cache, but it costs
milliseconds and must not be reported as a compile.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Tuple

import jax

log = logging.getLogger(__name__)

_lock = threading.Lock()
_enabled_dir: Optional[str] = None
_listener_installed = False

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# monotonically increasing event totals, guarded by _lock
_counts = {"hits": 0, "misses": 0}


def _on_event(event: str, **kw) -> None:
    if event == _HIT_EVENT:
        with _lock:
            _counts["hits"] += 1
    elif event == _MISS_EVENT:
        with _lock:
            _counts["misses"] += 1


def _install_listener() -> None:
    global _listener_installed
    if not _listener_installed:
        jax.monitoring.register_event_listener(_on_event)
        _listener_installed = True


DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable(cache_dir: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Lookup order: ``$JAX_COMPILATION_CACHE_DIR``, then ``cache_dir``, then
    :data:`DEFAULT_DIR`. Idempotent; switching to a different directory
    logs a warning — the cache is process-global JAX config, so the last
    caller wins. The entry-size/compile-time floors are dropped so every
    program is cached (the default floors skip small fast compiles, which
    is exactly the wrong policy for a bucket ladder of mid-size programs).
    """
    global _enabled_dir
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    target = env_dir or cache_dir or DEFAULT_DIR
    with _lock:
        already = _enabled_dir
    if already == target:
        return target
    if already is not None:
        log.warning("compile cache moving from %s to %s (process-global "
                    "JAX config: last caller wins)", already, target)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", target)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax initializes its cache object once, at the first compile that
    # probes it — any compile before enable() (imports, PRNG setup) would
    # freeze the cache as disabled for the whole process. Reset so the
    # next probe re-initializes against the directory just configured.
    from jax._src.compilation_cache import reset_cache
    reset_cache()
    _install_listener()
    with _lock:
        _enabled_dir = target
    log.info("persistent XLA compilation cache enabled at %s", target)
    return target


def enabled_dir() -> Optional[str]:
    """The active cache directory, or None when the cache is off."""
    with _lock:
        return _enabled_dir


class CompileEvents:
    """Snapshot/delta view of the persistent-cache hit/miss counters.

    ``delta()`` returns ``(misses, hits)`` accumulated since the snapshot
    (or construction) — the attribution signal for one jit call. When the
    cache (or the monitoring listener) is off both counters stay zero and
    callers fall back to counting every fresh program as a compile.
    """

    def __init__(self):
        self.snapshot()

    def snapshot(self) -> None:
        with _lock:
            self._hits = _counts["hits"]
            self._misses = _counts["misses"]

    def delta(self) -> Tuple[int, int]:
        with _lock:
            return (_counts["misses"] - self._misses,
                    _counts["hits"] - self._hits)
