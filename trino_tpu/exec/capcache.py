"""Persistent learned-capacity cache.

The capacity protocol (exec/compiler.py `LocalExecutor.execute`, inherited
by exec/spmd.py) in five steps, each a whole XLA program when it changes a
tier (q03 SF1: a 215s TPU recompile for one undersized TopN buffer):

1. size: `_initial_caps` gives every stateful node (join expansion frame,
   group-by, distinct, compaction point, unnest, TopN candidates, the
   repartition exchange's bucket) a power-of-two tier from statistics;
2. run: the compiled program reports every such node's true need;
3. grow on overflow: the node goes to the next tier that holds its need,
   and the plan runs again;
4. tighten once: after the run that first converges a plan, every node
   whose need lies far under its tier gets `_pow2(2 * need + 1024)` —
   kernel work scales with the tier, not with live rows.  Once per (plan,
   input shapes, scope) and process: afterwards tiers only grow, so a plan
   whose bindings alternate small and large settles on the large tier;
5. persist: `_learned_caps` remembers the tiers in the executor, this
   module on disk keyed by (plan, input shapes, scope), so that NEW
   executors and processes — a task's, a bench run's, the next driver
   round's — start at the converged tiers and compile exactly one program.

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

TIGHTENED = _METRICS.counter(
    "trino_tpu_capacity_tightened_total",
    "Plan nodes whose capacity tier was tightened after a converged run",
    ("node",),
)

_LOCK = threading.Lock()
_MAX_ENTRIES = 1024
_mem: Optional[dict] = None  # file contents, loaded once per process
# keys this process has stored: they have had their one tightening (step 4)
_settled: set[str] = set()


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
        _settled.clear()  # another file: nothing of it was converged here
        try:
            with open(_path()) as f:
                _mem = json.load(f)
        except Exception:
            _mem = {}
    return _mem


def load_caps(
    plan, inputs: dict, scope: str = ""
) -> tuple[Optional[dict[int, int]], bool]:
    """(capacities for (plan, input shapes, scope) or None, settled).
    `settled`: an executor of this process stored them, so they have been
    tightened and from here on only grow; an entry that was merely found in
    the file (another process's, a seeded one) has its tightening to come.
    A stale hit (code drift renumbering nodes) is harmless: wrong caps just
    re-enter the normal overflow-retry path, which re-stores the corrected
    tiers."""
    try:
        key = _key(plan, inputs, scope)
    except Exception:  # unserializable plan: no persistence, no failure
        return None, False
    with _LOCK:
        entry = _load_file().get(key)
        settled = key in _settled
    _CAPS_LOOKUPS.labels("miss" if entry is None else "hit").inc()
    if entry is None:
        return None, settled
    return {int(k): int(v) for k, v in entry.items()}, settled


def store_caps(plan, inputs: dict, caps: dict[int, int], scope: str = "") -> None:
    try:
        key = _key(plan, inputs, scope)
    except Exception:
        return
    entry = {str(k): int(v) for k, v in caps.items()}
    with _LOCK:
        mem = _load_file()
        _settled.add(key)
        if mem.get(key) == entry:
            return
        mem[key] = entry
        if len(mem) > _MAX_ENTRIES:  # drop oldest half (insertion order)
            for k in list(mem)[: len(mem) - _MAX_ENTRIES // 2]:
                del mem[k]
                _settled.discard(k)
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
