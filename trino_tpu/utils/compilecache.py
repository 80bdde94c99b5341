"""Persistent XLA compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there: JAX reads
the variable itself and this module sets no directory in code, so whoever
starts the program (a driver, a chip run's wrapper) places the cache and
every process of that run shares it.  Where it is not set, the cache sits at
one fixed path inside the checkout (``<repo>/.jax_cache/<host fingerprint>``)
— fixed because the path is part of the cache key's surroundings: a
directory that moves never hits.

The fingerprint level exists for XLA:CPU: its AOT entries bake in the
compile host's CPU feature set (+avx512*, +prefer-no-scatter, ...), loading
an entry compiled on a different machine fails with "Target machine feature
... is not supported" and recompiles, so one shared directory poisons runs
on heterogeneous hosts.  (Reference analogue: the specialized-class cache in
sql/gen/ExpressionCompiler.java:38 is in-process and has no such issue;
ours persists across processes, which is what makes repeat query latency
drop from ~30s to seconds.)
"""

from __future__ import annotations

import hashlib
import os
import platform
import threading

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def jax_cache_dir(repo_root: str) -> str:
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((ln for ln in f if ln.startswith("flags")), "")
    except OSError:
        flags = ""
    fp = hashlib.sha1((platform.machine() + flags).encode()).hexdigest()[:12]
    return os.path.join(repo_root, ".jax_cache", fp)


def enable_persistent_cache(repo_root: str | None = None) -> None:
    """Turn the on-disk compile cache on (idempotent).  The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
    checkout's fixed path."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", jax_cache_dir(repo_root or _REPO_ROOT)
        )
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_on_event)
    # 0.1s: a plan run op by op (the compile-budget fallback, plans with
    # host-collected aggregates) dispatches hundreds of small per-op
    # programs; on a 1-core host even "small" compiles are ~0.5s, and
    # leaving them uncached keeps repeat latency high
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


# JAX reports every persistent-cache outcome as a monitoring event on the
# thread that compiles: a hit when an executable was read back, a miss when
# a fresh compile was written.  Compiles below the persistence threshold
# report neither.  Counting per thread keeps concurrent compiles (the
# compile service's pool) from claiming each other's outcomes.
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_listening = False
_tls = threading.local()


def _on_event(event: str, **_kw) -> None:
    if event == _HIT:
        _tls.hits = getattr(_tls, "hits", 0) + 1
    elif event == _MISS:
        _tls.misses = getattr(_tls, "misses", 0) + 1


def cache_events() -> tuple[int, int]:
    """(hits, misses) the calling thread's compiles have reported so far."""
    return getattr(_tls, "hits", 0), getattr(_tls, "misses", 0)


def cache_outcome(before: tuple[int, int]) -> str:
    """Persistent-cache outcome of the compiles this thread ran since
    `before` (a cache_events() snapshot): 'hit' | 'miss' | 'uncached'
    (no cache directory, or nothing reached the persistence threshold)."""
    if not _listening or cache_dir() is None:
        return "uncached"
    hits, misses = cache_events()
    if misses > before[1]:
        return "miss"
    return "hit" if hits > before[0] else "uncached"


def cache_dir() -> str | None:
    """The directory actually in force (None == no persistent cache)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def cache_stats() -> dict:
    """On-disk XLA cache footprint for /metrics (entries + bytes) of the
    directory in force; scraped lazily so the walk only happens when
    somebody actually looks."""
    d = cache_dir()
    entries = 0
    size = 0
    # jax shards entries into nested subdirectories; a top-level listdir
    # under-reports the footprint (and blinds the profiler's hit/miss
    # inference, which watches the entry-count delta per compile)
    for root, _dirs, files in os.walk(d or os.devnull):
        for name in files:
            try:
                size += os.path.getsize(os.path.join(root, name))
                entries += 1
            except OSError:
                pass  # entry evicted mid-walk
    return {"dir": d, "entries": entries, "bytes": size}
