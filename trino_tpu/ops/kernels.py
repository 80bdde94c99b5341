"""Data-plane kernel policy and dispatch accounting.

One Pallas kernel replaces a relational hot path when a static gate says
the shape fits: the fused scan (ops/pallas/fused.py) stands in for a
Filter/Project chain under an Aggregate whose keys are small dictionary
columns.  Two more serve the paths of ops/relops.py from below: the
segmented reduction (segreduce.py) and the radix top-n (topk.py).  Joins and
keyed group-bys sort (PERF.md section 6, PR 45: the hash-table kernels that
stood beside the sort paths lost on the chip and went).  This module is the
one place the choice is configured and observed:

  * KernelPolicy — per-statement switches (runtime/session.py properties
    `data_plane_kernels`, `pallas_interpret`), re-applied by the engine
    before each statement the same way compile props are.
  * record_dispatch() — increments
    trino_tpu_kernel_dispatch_total{op,impl} and, while a plan trace is
    active, appends the event to that trace's capture so EXPLAIN ANALYZE
    can print `-- kernel:` footer lines.  Dispatch is recorded at TRACE
    time (kernel selection), once per compiled program — a jit-cache hit
    re-runs the selected kernel without re-counting.
  * events_for(plan) — the captured events of the last trace of `plan`
    (plans are frozen dataclasses, so they key a bounded dict directly);
    describe(plan) — the same as text, one line an event: EXPLAIN ANALYZE's
    `-- kernel:` lines and the `dispatch` span's `kernels`.

ops and their impls:
  fused_pipeline (fused.py): "pallas" = the fused scan was selected.
  segment_reduce (segreduce.py — the accumulator under every group-by path
    and the radix histograms) and top_n (topk.py radix select): "pallas",
    counted when selected only.
  group_by (relops.group_aggregate): "sort" = the sorted group-by, its
    detail what moved: `cap 16777216; sort carries 1 cols, ends carry 3
    words` — the operands that rode the group sort beside its keys, and the
    32-bit words the compaction of the group ends carried.  (The global and
    the direct-code forms record their segment_reduce only.)
  join (relops.equi_join): "sort", detail `semi+residual build 4096 probe
    60000466 -> C 16384`: the kind (inner | left | full | semi | anti |
    null_anti | mark | mark_in, `+residual` where non-equality conjuncts
    ride the join), build lanes, probe lanes, the expansion frame.
  join_rank (one event a traced join, beside its join event): how the
    probe's bounds over the sorted build side were found — "merged" = a
    running count over ONE sort of build ++ probe hashes, "scan" = a binary
    search (few probes against many keys: relops.rank_form); the detail is
    `60000466 ++ 4096 lanes -> C 16384` (build lanes, probe lanes, the
    expansion frame).
  join_filter (one more event for a join that filters or marks its left
    page — semi, anti, null_anti, mark, mark_in; relops.filter_form): how it
    found its one bit a probe row — "rank" = no residual, off ONE sort of
    build ++ probe lanes by the key's own words (the run of equal keys holds
    a build row or not); "minmax" = a residual that is one comparison of a
    probe-side with a build-side integer, asked of the run's smallest and
    largest build value, which ride the same sort; "frame" = the inner
    join's expansion frame (any other residual, a floating-point key, few
    probes by a binary search).  rank and minmax build no frame and report no
    need (`required` lacks the node).  The detail is `semi+residual 60000466
    ++ 2097152 lanes, 1 key words, ne of the run's min and max`, or for a
    frame `... lanes -> C 16777216`.
  compact (relops.compact_rows): "carry" = the columns rode the
    compaction's sort, "gather" = they were fetched through its
    permutation; the detail is `60000466 -> 33554432 lanes, 5 words`.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

from ..utils import metrics as _metrics

__all__ = [
    "KernelPolicy", "get_policy", "set_policy", "policy_key",
    "record_dispatch", "begin_capture", "end_capture", "remember",
    "events_for", "describe",
]


@dataclass(frozen=True)
class KernelPolicy:
    enabled: bool = True     # the fused scan may be selected (data_plane_kernels)
    interpret: bool = False  # run kernels interpreted (CPU CI path)


_DEFAULT = KernelPolicy()
_POLICY = _DEFAULT

_DISPATCH = _metrics.GLOBAL.counter(
    "trino_tpu_kernel_dispatch_total",
    "Data-plane kernel selections at plan-trace time, by relational op "
    "(group_by | join | join_rank | join_filter | fused_pipeline | "
    "segment_reduce | top_n | compact) and implementation (pallas = Pallas "
    "TPU kernel; group_by and join: sort; join_rank: merged = one sort of "
    "build ++ probe hashes, scan = a binary search; join_filter: rank | "
    "minmax = a filtering join answered off its rank with no expansion "
    "frame, frame = through one; segment_reduce and top_n count their Pallas "
    "selections only; compact: carry = the columns rode the compaction's "
    "sort, gather = they were fetched through its permutation)",
    ("op", "impl"),
)

JOIN_ROWS = _metrics.GLOBAL.counter(
    "trino_tpu_join_rows_total",
    "Output rows of inner equi-joins: estimated = what the join-order cost "
    "model gave each join of a statement Engine() planned (plan/reorder.py "
    "join_estimates, the planner span's join_estimates), actual = what the "
    "compiled program reported for each such join in a converged execution "
    "(the device_wait span's frames); far apart, the order was chosen blind",
    ("what",),
)

FUSED_SCATTER = _metrics.GLOBAL.counter(
    "trino_tpu_fused_scatter_total",
    "Fused scan programs traced, by the form the kernel scatters its "
    "streams into their groups with (ops/pallas/fused.py: vpu = one select "
    "and add per group and stream, no one-hot; mxu = a one-hot matmul as "
    "wide as the key domain's lane tiles)",
    ("form",),
)

FUSED_OPERANDS = _metrics.GLOBAL.counter(
    "trino_tpu_fused_operands_total",
    "Row operands of the fused scan programs traced (columns, validity "
    "masks, a page's live mask), by how each reaches the kernel "
    "(ops/pallas/fused.py: resident = the resident array as it is, read in "
    "place; prepared = a cast or a 64-bit column's hi/lo split made before "
    "the kernel, once a query)",
    ("form",),
)


def get_policy() -> KernelPolicy:
    return _POLICY


def set_policy(policy: KernelPolicy) -> None:
    global _POLICY
    _POLICY = policy


def policy_key() -> tuple:
    """Fingerprint for executor jit-cache keys: a changed policy must compile
    a new program (the kernel choice is baked into the trace).  The pallas
    module-level overrides ride along because they too are read at trace
    time: compiled programs outlive them in the process-global
    CompileService done-map, and an interpreted (f32-matmul) segsum program
    must never be swapped in for an exact-f64 request with the same avals."""
    from .pallas import hashagg, segreduce, topk

    p = _POLICY
    return (p.enabled, p.interpret,
            segreduce.INTERPRET, hashagg.INTERPRET, topk.FORCE)


# --------------------------------------------------------- event capture

_TLS = threading.local()
_EVENTS_LOCK = threading.Lock()
_EVENTS: dict = {}  # plan -> tuple[(op, impl, detail)]
_EVENTS_MAX = 256


def record_dispatch(op: str, impl: str, detail: str = "") -> None:
    _DISPATCH.labels(op=op, impl=impl).inc()
    cap = getattr(_TLS, "capture", None)
    if cap is not None:
        cap.append((op, impl, detail))


def begin_capture() -> list:
    cap: list = []
    _TLS.capture = cap
    return cap


def end_capture() -> None:
    _TLS.capture = None


def remember(plan, events) -> None:
    """Associate a trace's dispatch events with its plan (last trace wins —
    the retry loop's final capacities decide the kernels that actually ran)."""
    try:
        hash(plan)
    except TypeError:
        return
    with _EVENTS_LOCK:
        if len(_EVENTS) >= _EVENTS_MAX:
            _EVENTS.clear()
        _EVENTS[plan] = tuple(events)


def events_for(plan) -> tuple:
    try:
        hash(plan)
    except TypeError:
        return ()
    with _EVENTS_LOCK:
        return _EVENTS.get(plan, ())


def describe(plan) -> list[str]:
    """`<impl> <op> (<detail>)` for each event of the plan's last trace."""
    return [
        f"{impl} {op}" + (f" ({detail})" if detail else "")
        for op, impl, detail in events_for(plan)
    ]
