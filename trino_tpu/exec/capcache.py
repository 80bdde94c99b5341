"""Persistent learned-capacity cache.

The executor's capacity protocol (exec/compiler.py) sizes every stateful
node (join expansion, group-by, TopN candidates) and retries at the next
power-of-two tier on overflow — but each retry at a new capacity is a whole
new XLA program (q03 SF1: a 215s TPU recompile for one undersized TopN
buffer).  In-process, `_learned_caps` remembers converged capacities; this
module persists them to disk keyed by (plan, input shapes) so FRESH
processes — bench runs, CI re-runs, the next driver round — start at the
converged tiers and compile exactly one program.

Capacities depend only on the plan and the data, never on the host, so the
cache survives process restarts under `.jax_cache/caps_cache.json` next to
the XLA compile cache (utils/compilecache.py) — a build artifact, not a
source file.  `TRINO_TPU_CAPS_CACHE` overrides the location (CI runs that
want a warm start can point it at a persistent path).

Reference analogue: runtime-adaptive statistics feedback
(sql/planner/AdaptivePlanner.java) persisted across queries, in miniature.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Optional

__all__ = ["load_caps", "store_caps"]

from ..utils.metrics import GLOBAL as _METRICS

_CAPS_LOOKUPS = _METRICS.counter(
    "trino_tpu_caps_cache_lookups_total",
    "Persistent learned-capacity cache lookups",
    ("result",),
)

_LOCK = threading.Lock()
_MAX_ENTRIES = 1024
_mem: Optional[dict] = None  # file contents, loaded once per process


def _path() -> str:
    env = os.environ.get("TRINO_TPU_CAPS_CACHE")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache", "caps_cache.json")


def _key(plan, inputs: dict, scope: str = "") -> str:
    """`scope`: what else the capacities depend on (the SPMD executor's
    are per device, so it names its device count); "" for one device."""
    from ..plan.serde import plan_to_json

    shapes = sorted((k, int(p.capacity)) for k, p in inputs.items())
    text = plan_to_json(plan) + "|" + repr(shapes) + scope
    return hashlib.sha1(text.encode()).hexdigest()[:24]


def _load_file() -> dict:
    global _mem
    if _mem is None:
        try:
            with open(_path()) as f:
                _mem = json.load(f)
        except Exception:
            _mem = {}
    return _mem


def load_caps(plan, inputs: dict, scope: str = "") -> Optional[dict[int, int]]:
    """Converged capacities for (plan, input shapes), or None.  A stale hit
    (code drift renumbering nodes) is harmless: wrong caps just re-enter the
    normal overflow-retry path, which re-stores the corrected tiers."""
    try:
        key = _key(plan, inputs, scope)
    except Exception:  # unserializable plan: no persistence, no failure
        return None
    with _LOCK:
        entry = _load_file().get(key)
    _CAPS_LOOKUPS.labels("miss" if entry is None else "hit").inc()
    if entry is None:
        return None
    return {int(k): int(v) for k, v in entry.items()}


def store_caps(plan, inputs: dict, caps: dict[int, int], scope: str = "") -> None:
    try:
        key = _key(plan, inputs, scope)
    except Exception:
        return
    entry = {str(k): int(v) for k, v in caps.items()}
    with _LOCK:
        mem = _load_file()
        if mem.get(key) == entry:
            return
        mem[key] = entry
        if len(mem) > _MAX_ENTRIES:  # drop oldest half (insertion order)
            for k in list(mem)[: len(mem) - _MAX_ENTRIES // 2]:
                del mem[k]
        try:
            parent = os.path.dirname(_path())
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = _path() + ".tmp"
            with open(tmp, "w") as f:
                json.dump(mem, f, indent=0, sort_keys=True)
            os.replace(tmp, _path())
        except OSError:
            pass  # read-only checkout: in-memory cache still works
