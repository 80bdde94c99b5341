"""Plan statistics: cardinality + column-stats propagation for costing.

A compact analogue of the reference's stats calculator stack
(cost/StatsCalculator, FilterStatsCalculator, JoinStatsRule,
AggregationStatsRule): connector-supplied base stats (NDV, min/max, null
fraction — spi/statistics) propagate bottom-up through Filter/Project, and
the estimators that matter for physical decisions use them:

- filter selectivity: equality -> 1/NDV, range -> fraction of [min,max],
  IN -> k/NDV, conjunction multiplies (independence assumption)
- join output: |L|*|R| / max(NDV(lk), NDV(rk))  (the classic Selinger form;
  FK->PK joins collapse to |L|)
- aggregate output: min(child rows, product of group-key NDVs)

Used by plan/distribute.py to choose join distribution (broadcast vs
partitioned) and by the executor's capacity planning.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..connectors.spi import CatalogManager, ColumnStats, LazyStats
from .ir import Call, Const, FieldRef, InListIr, IrExpr, LikeIr
from .nodes import (
    Compact,
    Aggregate, Concat, Distinct, Exchange, Filter, Join, Limit, PlanNode,
    Project, RemoteSource, Sort, TableScan, TopN, Values, Window,
)

__all__ = ["PlanStats", "estimate", "scan_rows"]

_DEFAULT_FILTER_SEL = 0.3
_DEFAULT_ROWS = 1_000_000.0


@dataclass(frozen=True)
class PlanStats:
    rows: float
    # output column index -> ColumnStats (only where derivable)
    columns: dict


def scan_rows(node: TableScan, catalogs: CatalogManager):
    """Physical row count of a scanned table, or ``None`` when the connector
    cannot say.  Split enumeration wants the *actual* count — falling back to
    the statistical default would mint phantom splits for a tiny no-stats
    table — so unlike :func:`estimate` this never substitutes a guess."""
    conn = catalogs.get(node.catalog)
    try:
        n = conn.estimated_row_count(node.table)
        if n is not None:
            return float(n)
    except Exception:
        pass
    try:
        ts = conn.table_stats(node.table)
        if ts is not None:
            return float(ts.row_count)
    except Exception:
        pass
    return None


def estimate(node: PlanNode, catalogs: CatalogManager) -> PlanStats:
    """Bottom-up stats for a plan node (memoization is the caller's concern;
    plans are small)."""
    if isinstance(node, TableScan):
        conn = catalogs.get(node.catalog)
        ts = None
        try:
            ts = conn.table_stats(node.table)
        except Exception:
            ts = None
        if ts is not None:
            names = node.column_names
            cols = LazyStats(
                [i for i, name in enumerate(names) if name in ts.columns],
                lambda i: ts.columns[names[i]],
            )
            return PlanStats(ts.row_count, cols)
        n = conn.estimated_row_count(node.table)
        return PlanStats(float(n) if n is not None else _DEFAULT_ROWS, {})

    if isinstance(node, Compact):
        return estimate(node.child, catalogs)

    if isinstance(node, Filter):
        child = estimate(node.child, catalogs)
        sel = _selectivity(node.predicate, child)
        # columns the predicate DIRECTLY constrains get a targeted NDV
        # (eq -> 1, IN -> k, range -> frac * ndv — reference:
        # FilterStatsCalculator per-domain narrowing)
        targeted = _targeted_ndv(node.predicate, child)

        def survive(ndv: Optional[float]) -> Optional[float]:
            # distinct-value survival under row selectivity `sel` for columns
            # the predicate does NOT directly constrain: with rows/ndv
            # repetitions per value, P(value keeps >=1 row) =
            # 1-(1-sel)^(rows/ndv).  Linear ndv*sel wildly UNDERestimates
            # surviving NDV on repeated keys (fact-table FKs keep ~every
            # key), which inflated Selinger join outputs 3-60x (the join
            # divisor shrank) and with them the join capacity frames.
            if ndv is None or ndv <= 0:
                return ndv
            reps = max(1.0, child.rows / ndv)
            return max(1.0, ndv * (1.0 - (1.0 - min(sel, 1.0)) ** reps))

        def filtered(i: int) -> ColumnStats:
            c = child.columns[i]
            nd = targeted[i] if i in targeted else survive(c.ndv)
            return ColumnStats(nd, c.min, c.max, c.null_fraction)

        return PlanStats(
            max(1.0, child.rows * sel), LazyStats(child.columns, filtered))

    if isinstance(node, Project):
        child = estimate(node.child, catalogs)
        return PlanStats(child.rows, _passed_through(node.expressions, child))

    if isinstance(node, (Exchange, Sort, Window)):
        child = estimate(node.child, catalogs)
        return PlanStats(child.rows, child.columns)

    if isinstance(node, Aggregate):
        child = estimate(node.child, catalogs)
        if not node.group_keys:
            return PlanStats(1.0, {})
        groups = 1.0
        known = True
        for k in node.group_keys:
            nd = _expr_ndv(k, child)
            if nd is None:
                known = False
                break
            groups *= nd
        if not known:
            groups = max(1.0, 0.1 * child.rows)
        rows = max(1.0, min(child.rows, groups))
        return PlanStats(rows, _passed_through(node.group_keys, child))

    if isinstance(node, Distinct):
        child = estimate(node.child, catalogs)
        return PlanStats(max(1.0, 0.5 * child.rows), child.columns)

    if isinstance(node, Join):
        left = estimate(node.left, catalogs)
        right = estimate(node.right, catalogs)
        if node.kind in ("semi", "anti", "null_anti"):
            return PlanStats(max(1.0, 0.5 * left.rows), left.columns)
        if node.kind in ("mark", "mark_in"):  # row-preserving: adds a column
            return PlanStats(left.rows, left.columns)
        if node.kind == "cross":
            return PlanStats(left.rows, left.columns)
        ndv = None
        for lk, rk in zip(node.left_keys, node.right_keys):
            ln = _expr_ndv(lk, left)
            rn = _expr_ndv(rk, right)
            for v in (ln, rn):
                if v is not None:
                    ndv = v if ndv is None else max(ndv, v)
        if ndv:
            rows = max(1.0, left.rows * right.rows / ndv)
        else:
            rows = max(left.rows, right.rows)
        if node.kind == "left":
            rows = max(rows, left.rows)
        off = len(node.left.output_types)
        cols = LazyStats(
            list(left.columns) + [off + i for i in right.columns],
            lambda i: left.columns[i] if i in left.columns else right.columns[i - off],
        )
        return PlanStats(rows, cols)

    if isinstance(node, (TopN, Limit)):
        child = estimate(node.child, catalogs)
        return PlanStats(float(min(node.count, child.rows)), child.columns)

    from .nodes import EnforceSingleRow

    if isinstance(node, EnforceSingleRow):
        child = estimate(node.child, catalogs)
        return PlanStats(1.0, child.columns)

    if isinstance(node, Values):
        return PlanStats(float(len(node.rows)), {})

    if isinstance(node, Concat):
        rows = sum(estimate(c, catalogs).rows for c in node.inputs)
        return PlanStats(rows, {})

    if isinstance(node, RemoteSource):
        return PlanStats(_DEFAULT_ROWS, {})

    from .nodes import Unnest

    if isinstance(node, Unnest):
        # average array cardinality is unknown without histogram stats; 3x is
        # the conventional guess (capacity retries correct at runtime)
        child = estimate(node.child, catalogs)
        return PlanStats(max(1.0, child.rows * 3.0), child.columns)

    return PlanStats(_DEFAULT_ROWS, {})


def _passed_through(exprs, child: PlanStats) -> LazyStats:
    """Statistics of the outputs that are plain references to a column of
    `child`.  Which outputs have any is known from the keys; a column's are
    asked of the child when someone asks them of this node — a Project over
    an unpruned scan names every column of its table, and a connector may
    have to generate a column to say anything of it."""
    refs = {
        i: e.index
        for i, e in enumerate(exprs)
        if isinstance(e, FieldRef) and e.index in child.columns
    }
    return LazyStats(refs, lambda i: child.columns[refs[i]])


def _expr_ndv(e: IrExpr, stats: PlanStats) -> Optional[float]:
    if isinstance(e, FieldRef) and e.index in stats.columns:
        return stats.columns[e.index].ndv
    if isinstance(e, Const):
        return 1.0
    return None


def _targeted_ndv(pred: IrExpr, stats: PlanStats) -> dict[int, float]:
    """NDV of columns a top-level conjunct constrains directly:
    eq const -> 1, IN (k values) -> k, range -> the conjunct's own
    selectivity fraction of the column NDV."""
    out: dict[int, float] = {}

    def visit(p: IrExpr) -> None:
        if isinstance(p, Call) and p.op == "and":
            visit(p.args[0])
            visit(p.args[1])
            return
        if isinstance(p, InListIr) and not p.negated and isinstance(
            _uncast(p.operand), FieldRef
        ):
            out[_uncast(p.operand).index] = float(max(1, len(p.values)))
            return
        if isinstance(p, Call) and p.op in ("eq", "lt", "le", "gt", "ge"):
            a = _uncast(p.args[0])
            b = _uncast(p.args[1]) if len(p.args) > 1 else None
            ref = a if isinstance(a, FieldRef) else (b if isinstance(b, FieldRef) else None)
            const_side = b if ref is a else a
            if ref is None or not isinstance(const_side, Const):
                return
            c = stats.columns.get(ref.index)
            if p.op == "eq":
                out[ref.index] = 1.0
            elif c is not None and c.ndv:
                frac = _selectivity(p, stats)
                out[ref.index] = max(1.0, c.ndv * frac)

    visit(pred)
    return out


def _selectivity(pred: IrExpr, stats: PlanStats) -> float:
    """FilterStatsCalculator in miniature: conjuncts multiply."""
    if isinstance(pred, Call):
        op = pred.op
        if op == "and":
            return _selectivity(pred.args[0], stats) * _selectivity(pred.args[1], stats)
        if op == "or":
            a = _selectivity(pred.args[0], stats)
            b = _selectivity(pred.args[1], stats)
            return min(1.0, a + b - a * b)
        if op == "not":
            return max(0.0, 1.0 - _selectivity(pred.args[0], stats))
        if op in ("eq", "ne", "lt", "le", "gt", "ge"):
            col, const, flipped = _col_const(pred, stats)
            if flipped:  # const <op> col  ==  col <flip(op)> const
                op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}.get(op, op)
            if op == "eq":
                if col is not None and col.ndv:
                    return min(1.0, 1.0 / col.ndv)
                return 0.1
            if op == "ne":
                if col is not None and col.ndv:
                    return max(0.0, 1.0 - 1.0 / col.ndv)
                return 0.9
            # range predicates: interpolate within [min, max]
            if col is not None and const is not None and col.min is not None and col.max is not None and col.max > col.min:
                frac = (const - col.min) / (col.max - col.min)
                frac = min(1.0, max(0.0, frac))
                return frac if op in ("lt", "le") else 1.0 - frac
            return _DEFAULT_FILTER_SEL
        if op == "is_null":
            col, _, _ = _col_const(pred, stats)
            return col.null_fraction if col is not None else 0.05
    if isinstance(pred, InListIr):
        col = (
            stats.columns.get(pred.operand.index)
            if isinstance(pred.operand, FieldRef)
            else None
        )
        if col is not None and col.ndv:
            sel = min(1.0, len(pred.values) / col.ndv)
        else:
            sel = min(1.0, 0.1 * len(pred.values))
        return 1.0 - sel if pred.negated else sel
    if isinstance(pred, LikeIr):
        return 0.25 if not pred.negated else 0.75
    return _DEFAULT_FILTER_SEL


def _uncast(e: IrExpr) -> IrExpr:
    # see through casts of plain column refs (decimal coercion wraps them)
    while isinstance(e, Call) and e.op == "cast" and len(e.args) == 1:
        e = e.args[0]
    return e


def _col_const(pred: Call, stats: PlanStats):
    """(column stats, numeric constant, flipped) for col <op> const shapes,
    either side, seeing through coercion casts; flipped=True means the
    column was on the RIGHT (const <op> col), so range ops must mirror.

    NOTE: range interpolation compares the constant against the column's
    min/max in LANE units — for decimals both are scaled ints of the same
    scale (casts rescale the const at fold time), so the fraction is right.
    """
    a = _uncast(pred.args[0])
    b = _uncast(pred.args[1]) if len(pred.args) > 1 else None
    col = const = None
    flipped = False
    if isinstance(a, FieldRef):
        col = stats.columns.get(a.index)
        if isinstance(b, Const) and isinstance(b.value, (int, float)):
            const = float(b.value)
    elif isinstance(b, FieldRef):
        flipped = True
        col = stats.columns.get(b.index)
        if isinstance(a, Const) and isinstance(a.value, (int, float)):
            const = float(a.value)
    return col, const, flipped
