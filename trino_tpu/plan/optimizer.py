"""Plan optimizer passes.

The reference runs 231 iterative rules over a Memo (sql/planner/iterative/,
PlanOptimizers.java).  This build's planner already does the load-bearing
rewrites inline (predicate pushdown, cross-join elimination, decorrelation,
OR factoring); this module holds the passes that work better as whole-plan
rewrites.  Current passes:

- prune_columns: projection pushdown all the way into TableScan
  (reference: PruneUnreferencedOutputs / PruneTableScanColumns rules).
  Matters doubly on TPU: narrower pages mean fewer HBM-resident arrays
  gathered through every join.
- push_filters: predicates sink to the smallest subtree that covers their
  columns, and so do the joins that ARE predicates: a filtering join (semi /
  anti / null_anti: IN, EXISTS, NOT EXISTS, NOT IN) tests one row of its
  left input at a time against a set that does not depend on that row's
  neighbours, so it commutes with an inner join over its left input exactly
  as `x > 5` does.  The planner lays it over the whole FROM clause; here it
  sinks, subquery plan and all, to the input that makes its key, wherever
  plan/stats.py estimates that input no larger than the one it stood on
  (TPC-H q18: from a 6M-row three-way join down to orders — every join above
  then runs on the ~60 orders that pass; reference:
  PredicatePushDown.java's semi-join handling).  Needs catalogs.
- reorder_joins (plan/reorder.py): Selinger-style cost-based join order
  over connected inner-equi-join regions (reference: ReorderJoins.java,
  EliminateCrossJoins.java); needs catalogs for stats, so it only runs
  when the caller passes them.  A sunk filtering join is a leaf of its
  region, estimated as the filter it is.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

from ..utils.metrics import GLOBAL as _METRICS
from .ir import FieldRef, IrExpr, field_refs, remap, substitute
from .nodes import (
    Aggregate, AggCall, Concat, Distinct, EnforceSingleRow, Filter, Join,
    Limit, PlanNode, Project, Sort, SortKey, TableScan, TopN, Unnest, Values,
    Window, WindowCall,
)

__all__ = ["optimize", "prune_columns", "SEMI_JOINS_SUNK"]

# joins that filter their left input and add nothing to it (mark joins add a
# column: they are projections, and stay where the planner put them)
_FILTERING = ("semi", "anti", "null_anti")

SEMI_JOINS_SUNK = _METRICS.counter(
    "trino_tpu_plan_semi_join_sunk_total",
    "Filtering joins (IN / EXISTS / NOT IN / NOT EXISTS) that the optimizer"
    " sank below a join over their left input",
    ("kind",),
)


def optimize(plan: PlanNode, catalogs=None, session=None) -> PlanNode:
    # push filters first: reorder's cost model reads relation stats AFTER
    # their local predicates (a filter stuck above the join region would make
    # every order look cost-equal) — and after the filtering joins that sank
    # with them, each a leaf of the region it landed in
    plan = push_filters(plan, catalogs)
    reorder_on = (
        session is None or session.get("join_reordering_strategy") == "AUTOMATIC"
    )
    if catalogs is not None and reorder_on:
        from .reorder import reorder_joins

        plan = reorder_joins(plan, catalogs)
    # prune AFTER reordering: the restoring projections reorder_joins leaves
    # behind get folded into the scans here
    plan = prune_columns(plan)
    if catalogs is not None:
        plan = insert_compaction(plan, catalogs)
    return plan


# Compaction points are inserted wherever a BIG frame might collapse
# (filters and semi/anti/mark membership tests over >=64k-lane inputs).
# Whether each point actually compacts is decided at RUNTIME: the initial
# capacity starts at the stats estimate (usually ~= the input frame, a
# pass-through no-op), and after a run observes the TRUE surviving count
# the executor shrinks the tier (exec/compiler.py) — the one extra
# 2-operand sort then pays for itself because EVERY downstream
# sort/join/aggregation runs at the collapsed capacity (TPC-H q18: the
# semi-joined orders frame — the semi join sinks there, push_filters — is
# 1.5M lanes with 63 live rows at SF1, and both inner joins above it run in
# 2,048-lane frames; stats cannot see HAVING selectivity, the runtime
# can).  Reference analogue: AdaptivePlanner re-optimizing from runtime
# stats.
_COMPACT_MIN_SRC = 65536


def _row_estimates(catalogs):
    """-> rows(node): plan/stats.py's row estimate, kept per node.
    estimate() is unmemoized by design ("memoization is the caller's
    concern"); a pass that asks for every node of a plan is O(n^2) in its
    depth without this."""
    from .stats import estimate

    memo: dict[PlanNode, float] = {}

    def rows(n: PlanNode) -> float:
        hit = memo.get(n)
        if hit is None:
            hit = memo[n] = estimate(n, catalogs).rows
        return hit

    return rows


def insert_compaction(plan: PlanNode, catalogs) -> PlanNode:
    """Insert (initially pass-through) Compact points above filters and
    semi/anti membership tests over large frames.  Idempotent: re-running
    over an already-compacted plan adds no second wrapper."""
    from .nodes import Compact

    rows = _row_estimates(catalogs)

    def child_rows(n: PlanNode) -> float:
        try:
            return max(rows(n), 1.0)
        except Exception:
            return 1.0

    def visit(node: PlanNode) -> PlanNode:
        if isinstance(node, Compact):
            inner = visit(node.child)
            return inner if isinstance(inner, Compact) else Compact(inner)
        kids = node.children
        if kids:
            new_kids = tuple(visit(c) for c in kids)
            if new_kids != kids:
                node = _replace_kids(node, new_kids)
        wrap = False
        if isinstance(node, Filter):
            wrap = child_rows(node.child) >= _COMPACT_MIN_SRC
        elif isinstance(node, Join) and node.kind in _FILTERING:
            wrap = child_rows(node.left) >= _COMPACT_MIN_SRC
        if wrap:
            return Compact(node)
        return node

    return visit(plan)


def _replace_kids(node: PlanNode, kids):
    import dataclasses

    from .nodes import Concat, Join

    if isinstance(node, Join):
        return dataclasses.replace(node, left=kids[0], right=kids[1])
    if isinstance(node, Concat):
        return dataclasses.replace(node, inputs=kids)
    return dataclasses.replace(node, child=kids[0])


def push_filters(plan: PlanNode, catalogs=None) -> PlanNode:
    """Predicate pushdown as a whole-plan pass (reference:
    PredicatePushDown.java / PushPredicateThroughProjectIntoRowNumber etc.):
    WHERE conjuncts written over explicit JOIN ... ON trees sink to the
    smallest subtree covering their column references.  The planner pushes
    single-relation predicates for comma-joins at plan time; this pass covers
    the explicit-join and post-planning shapes.  With `catalogs`, filtering
    joins sink after them (`_sink_filtering_joins`): the predicates are at
    their leaves by then, so the estimates the joins sink by include them."""
    from .ir import Call

    def conjuncts_of(e: IrExpr) -> list[IrExpr]:
        if isinstance(e, Call) and e.op == "and":
            return conjuncts_of(e.args[0]) + conjuncts_of(e.args[1])
        return [e]

    def wrap(node: PlanNode, preds: list[IrExpr]) -> PlanNode:
        for p in preds:
            node = Filter(node, p)
        return node

    def push(node: PlanNode, preds: list[IrExpr]) -> PlanNode:
        if isinstance(node, Filter):
            return push(node.child, preds + conjuncts_of(node.predicate))

        if isinstance(node, Project):
            below = [substitute(p, node.expressions) for p in preds]
            return Project(push(node.child, below), node.expressions, node.names)

        if isinstance(node, Join):
            nl = len(node.left.output_types)
            lp: list[IrExpr] = []
            rp: list[IrExpr] = []
            keep: list[IrExpr] = []
            for p in preds:
                refs = field_refs(p)
                if node.kind in ("inner", "semi", "anti", "null_anti", "cross",
                                 "mark", "mark_in"):
                    # semi/anti output IS the left schema; filtering left rows
                    # commutes with the (anti-)membership test (mark joins:
                    # left-field predicates commute, the $mark column at
                    # index nl stays behind the `keep` guard)
                    if all(i < nl for i in refs):
                        lp.append(p)
                    elif node.kind == "inner" and refs and all(i >= nl for i in refs):
                        rp.append(remap(p, {i: i - nl for i in refs}))
                    else:
                        keep.append(p)
                elif node.kind == "left":
                    # left-side predicates commute with null-extension;
                    # right-side ones do NOT (they'd drop extended rows)
                    if all(i < nl for i in refs):
                        lp.append(p)
                    else:
                        keep.append(p)
                else:
                    keep.append(p)
            new = dataclasses.replace(
                node, left=push(node.left, lp), right=push(node.right, rp)
            )
            return wrap(new, keep)

        # leaves / barriers (Aggregate: grouping-sets NULL-ed keys make key
        # pushdown unsound in general; Limit/TopN/Window change row sets):
        # recurse for nested filters, keep preds here
        if isinstance(node, (Sort, Distinct)):
            # filtering commutes with ordering and with duplicate elimination
            return dataclasses.replace(node, child=push(node.child, preds))
        children = tuple(push(c, []) for c in node.children)
        if children:
            if isinstance(node, Concat):
                node = dataclasses.replace(node, inputs=children)
            else:
                node = dataclasses.replace(node, child=children[0])
        return wrap(node, preds)

    plan = push(plan, [])
    if catalogs is not None:
        plan = _sink_filtering_joins(plan, catalogs)
    return plan


def _sink_filtering_joins(plan: PlanNode, catalogs) -> PlanNode:
    """Move every semi / anti / null_anti join down its left input to the
    input it filters cheapest; SEMI_JOINS_SUNK counts the joins moved.

    Such a join keeps or drops each left row by a function of that row's key
    fields (and the left fields of its residual) and of the right input as a
    whole; its output is its left schema.  So it moves wherever a predicate
    over those fields may move (`push` above), its right subtree untouched:

    - through Project, the keys and the residual's left references rewritten
      by `substitute` over the projection's expressions (IR is pure);
    - through Filter, Sort, Distinct, Compact and other filtering joins, and
      through the left of a mark join: filters of one row set commute;
    - into the left or the right of an `inner` join, whichever makes every
      referenced field (right: indices less the left width); into the left of
      a `cross` join (its right is one row) and the preserved (left) side of
      a `left` join — never a null-extended side, whose rows the join above
      would bring back as NULLs;
    - not through Aggregate, Window, Limit, TopN, Unnest, Concat,
      EnforceSingleRow: they change the row set, a filter below is another
      query.  A join whose fields span both sides of a join stays above it.

    Where it may lose is under a join far more selective than it (TPC-H q16:
    partsupp's 800k rows against their join with a filtered part; q21: 6M
    lineitems against the 74k that survive three joins), so it adapts by the
    one thing it can observe: of the inputs on its way down it takes the one
    plan/stats.py estimates smallest, the deepest of equals (an FK->PK join
    estimates as its FK side: below it every join runs on the filtered
    rows), and only if the way there crosses a join — under Projects and
    Filters alone there is nothing to gain."""
    rows = _row_estimates(catalogs)

    def visit(node: PlanNode) -> PlanNode:
        kids = node.children
        if kids:
            new_kids = tuple(visit(c) for c in kids)
            if new_kids != kids:
                node = _replace_kids(node, new_kids)
        if isinstance(node, Join) and node.kind in _FILTERING:
            moved = _sink(node, rows)
            if moved is not None:
                SEMI_JOINS_SUNK.labels(node.kind).inc()
                return moved
        return node

    return visit(plan)


class _Stand(NamedTuple):
    """An input a filtering join may stand on, on its way down."""

    node: PlanNode
    keys: tuple[IrExpr, ...]  # the join's left keys over `node`'s output
    residual: Optional[IrExpr]  # its residual over `node`'s output ++ right
    slot: Optional[str]  # the field of the stand above that holds `node`
    crossed: bool  # a join lies between the first stand and this one


def _step_down(node: PlanNode, refs: set[int]):
    """One step of a filter over fields `refs` of `node`'s output into a
    child: (the child's slot, the child, each referenced field as an
    expression over the child's output, whether the step crosses a join), or
    None where the filter has to stay above `node`."""
    from .nodes import Compact

    def same(base: int = 0) -> dict[int, IrExpr]:
        return {i: FieldRef(i - base, node.output_types[i]) for i in refs}

    if isinstance(node, Project):
        return "child", node.child, {i: node.expressions[i] for i in refs}, False
    if isinstance(node, (Filter, Sort, Distinct, Compact)):
        return "child", node.child, same(), False
    if isinstance(node, Join) and refs:
        nl = len(node.left.output_types)
        if all(i < nl for i in refs):
            if node.kind in _FILTERING + ("mark", "mark_in"):
                return "left", node.left, same(), False
            if node.kind in ("inner", "cross", "left"):
                return "left", node.left, same(), True
        elif node.kind == "inner" and all(i >= nl for i in refs):
            return "right", node.right, same(nl), True
    return None


def _sink(join: Join, rows) -> Optional[PlanNode]:
    """`join` on the input of its left subtree that `rows` estimates
    smallest (see `_sink_filtering_joins`), or None if it stands there."""
    right_types = join.right.output_types
    way = [_Stand(join.left, join.left_keys, join.residual, None, False)]
    while True:
        at = way[-1]
        nl = len(at.node.output_types)
        refs: set[int] = set()
        for k in at.keys:
            refs |= field_refs(k)
        if at.residual is not None:
            refs |= {i for i in field_refs(at.residual) if i < nl}
        step = _step_down(at.node, refs)
        if step is None:
            break
        slot, child, exprs, crosses = step
        residual = at.residual
        if residual is not None:
            # the residual reads left ++ right: its right half follows the
            # left input's new width
            new_nl = len(child.output_types)
            both = dict(exprs)
            for j, t in enumerate(right_types):
                both[nl + j] = FieldRef(new_nl + j, t)
            residual = substitute(residual, both)
        keys = tuple(substitute(k, exprs) for k in at.keys)
        way.append(_Stand(child, keys, residual, slot, at.crossed or crosses))
    best = min(range(len(way)), key=lambda i: (rows(way[i].node), -i))
    if not way[best].crossed:
        return None
    at = way[best]
    new: PlanNode = dataclasses.replace(
        join, left=at.node, left_keys=at.keys, residual=at.residual
    )
    for i in range(best, 0, -1):
        new = dataclasses.replace(way[i - 1].node, **{way[i].slot: new})
    return new


def prune_columns(plan: PlanNode) -> PlanNode:
    new_plan, _ = _prune(plan, set(range(len(plan.output_types))))
    return new_plan


def _prune(node: PlanNode, needed: set[int]) -> tuple[PlanNode, dict[int, int]]:
    """Returns (new_node, mapping old-output-index -> new-output-index).
    `needed` indices are guaranteed present in the new node's output."""

    if isinstance(node, TableScan):
        keep = sorted(needed) if needed else [0]  # never emit zero-column scans
        mapping = {old: i for i, old in enumerate(keep)}
        new = TableScan(
            node.catalog,
            node.table,
            tuple(node.column_names[i] for i in keep),
            tuple(node.output_types[i] for i in keep),
        )
        return new, mapping

    if isinstance(node, Filter):
        child_needed = set(needed) | field_refs(node.predicate)
        child, m = _prune(node.child, child_needed)
        return Filter(child, remap(node.predicate, m)), m

    if isinstance(node, Project):
        keep = sorted(needed) if needed else [0]
        child_needed: set[int] = set()
        for i in keep:
            child_needed |= field_refs(node.expressions[i])
        child, m = _prune(node.child, child_needed)
        mapping = {old: i for i, old in enumerate(keep)}
        new = Project(
            child,
            tuple(remap(node.expressions[i], m) for i in keep),
            tuple(node.names[i] for i in keep),
        )
        return new, mapping

    if isinstance(node, Aggregate):
        nk = len(node.group_keys)
        keep_aggs = sorted(i for i in range(len(node.aggs)) if (nk + i) in needed)
        child_needed: set[int] = set()
        for k in node.group_keys:
            child_needed |= field_refs(k)
        for i in keep_aggs:
            for a_arg in (node.aggs[i].arg, node.aggs[i].arg2):
                if a_arg is not None:
                    child_needed |= field_refs(a_arg)
            for k, _asc, _nf in node.aggs[i].order_keys:
                child_needed |= field_refs(k)
        child, m = _prune(node.child, child_needed)
        new_keys = tuple(remap(k, m) for k in node.group_keys)
        new_aggs = tuple(
            AggCall(
                node.aggs[i].fn,
                None if node.aggs[i].arg is None else remap(node.aggs[i].arg, m),
                node.aggs[i].type,
                node.aggs[i].distinct,
                node.aggs[i].param,
                None if node.aggs[i].arg2 is None else remap(node.aggs[i].arg2, m),
                node.aggs[i].sep,
                tuple(
                    (remap(k, m), asc, nf)
                    for k, asc, nf in node.aggs[i].order_keys
                ),
            )
            for i in keep_aggs
        )
        names = tuple(node.names[i] for i in range(nk)) + tuple(
            node.names[nk + i] for i in keep_aggs
        )
        mapping = {i: i for i in range(nk)}
        for pos, i in enumerate(keep_aggs):
            mapping[nk + i] = nk + pos
        return Aggregate(child, new_keys, new_aggs, names, node.step), mapping

    if isinstance(node, Join):
        nl = len(node.left.output_types)
        left_needed = {i for i in needed if i < nl}
        right_needed = (
            set()
            if node.kind in ("semi", "anti", "null_anti", "mark", "mark_in")
            else {i - nl for i in needed if i >= nl}
        )
        for k in node.left_keys:
            left_needed |= field_refs(k)
        for k in node.right_keys:
            right_needed |= field_refs(k)
        if node.residual is not None:
            for i in field_refs(node.residual):
                if i < nl:
                    left_needed.add(i)
                else:
                    right_needed.add(i - nl)
        left, ml = _prune(node.left, left_needed)
        right, mr = _prune(node.right, right_needed)
        new_nl = len(left.output_types)
        concat_map = dict(ml)
        for old, new in mr.items():
            concat_map[nl + old] = new_nl + new
        new = Join(
            node.kind,
            left,
            right,
            tuple(remap(k, ml) for k in node.left_keys),
            tuple(remap(k, mr) for k in node.right_keys),
            None if node.residual is None else remap(node.residual, concat_map),
            node.distribution,
        )
        if node.kind in ("semi", "anti", "null_anti"):
            return new, ml
        if node.kind in ("mark", "mark_in"):
            # the $mark column rides at index nl -> new_nl after pruning
            mark_map = dict(ml)
            mark_map[nl] = new_nl
            return new, mark_map
        return new, concat_map

    if isinstance(node, (Sort, TopN)):
        child_needed = set(needed)
        for k in node.keys:
            child_needed |= field_refs(k.expr)
        child, m = _prune(node.child, child_needed)
        new_keys = tuple(
            SortKey(remap(k.expr, m), k.ascending, k.nulls_first) for k in node.keys
        )
        if isinstance(node, TopN):
            return TopN(child, new_keys, node.count), m
        return Sort(child, new_keys), m

    if isinstance(node, Limit):
        child, m = _prune(node.child, needed)
        return Limit(child, node.count), m

    if isinstance(node, Distinct):
        # DISTINCT is defined over its full input schema: keep everything
        child, m = _prune(node.child, set(range(len(node.child.output_types))))
        return Distinct(child), m

    if isinstance(node, EnforceSingleRow):
        child, m = _prune(node.child, needed)
        return EnforceSingleRow(child), m

    if isinstance(node, Values):
        return node, {i: i for i in range(len(node.types))}

    if isinstance(node, Concat):
        keep = sorted(needed) if needed else [0]
        new_inputs = []
        for c in node.inputs:
            pc, m = _prune(c, set(keep))
            # normalize each input to exactly [keep] in order so rows align
            exprs = tuple(
                FieldRef(m[i], node.output_types[i]) for i in keep
            )
            names = tuple(node.output_names[i] for i in keep)
            new_inputs.append(Project(pc, exprs, names))
        mapping = {old: pos for pos, old in enumerate(keep)}
        return Concat(tuple(new_inputs)), mapping

    if isinstance(node, Unnest):
        nc = len(node.child.output_types)
        n_el = len(node.arrays)
        child_needed = {i for i in needed if i < nc}
        for a in node.arrays:
            child_needed |= field_refs(a)
        child, m = _prune(node.child, child_needed)
        new_nc = len(child.output_types)
        new = Unnest(
            child,
            tuple(remap(a, m) for a in node.arrays),
            node.element_names,
            node.element_types,
            node.with_ordinality,
            node.outer,
            node.ordinality_name,
        )
        mapping = dict(m)
        for i in range(n_el + (1 if node.with_ordinality else 0)):
            mapping[nc + i] = new_nc + i
        return new, mapping

    if isinstance(node, Window):
        nc = len(node.child.output_types)
        keep_calls = sorted(i for i in range(len(node.calls)) if (nc + i) in needed)
        child_needed = {i for i in needed if i < nc}
        for k in node.partition_by:
            child_needed |= field_refs(k)
        for k in node.order_by:
            child_needed |= field_refs(k.expr)
        for i in keep_calls:
            for a in node.calls[i].args:
                child_needed |= field_refs(a)
        child, m = _prune(node.child, child_needed)
        new_nc = len(child.output_types)
        new = Window(
            child,
            tuple(remap(k, m) for k in node.partition_by),
            tuple(SortKey(remap(k.expr, m), k.ascending, k.nulls_first) for k in node.order_by),
            tuple(
                WindowCall(
                    node.calls[i].fn,
                    tuple(remap(a, m) for a in node.calls[i].args),
                    node.calls[i].type,
                    node.calls[i].frame,
                )
                for i in keep_calls
            ),
            tuple(node.call_names[i] for i in keep_calls),
        )
        mapping = dict(m)
        for pos, i in enumerate(keep_calls):
            mapping[nc + i] = new_nc + pos
        return new, mapping

    from .nodes import Compact as _Compact

    if isinstance(node, _Compact):
        child, m = _prune(node.child, needed)
        return _Compact(child), m

    from .nodes import MatchRecognize as _MR

    if isinstance(node, _MR):
        # opaque to pruning: DEFINE/MEASURES reference child fields through
        # shifted-column and primitive indirection, so the child keeps its
        # full schema and the node's outputs pass through unchanged
        import dataclasses as _dc

        child, _ = _prune(node.child, set(range(len(node.child.output_types))))
        new = node if child is node.child else _dc.replace(node, child=child)
        return new, {i: i for i in range(len(node.output_types))}

    raise NotImplementedError(f"prune: {type(node).__name__}")
