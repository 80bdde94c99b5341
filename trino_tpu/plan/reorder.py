"""Stats-driven join reordering.

The reference reorders joins inside the memo optimizer
(sql/planner/iterative/rule/ReorderJoins.java, EliminateCrossJoins.java),
costing orders with JoinStatsRule estimates.  Here the same decision runs as
a whole-plan pass (beside prune_columns): flatten each maximal inner-equi-
join region into a join graph over its leaf relations, cost candidate
left-deep orders with the Selinger formula over plan/stats.py NDVs
(rows(S join r) = rows(S) * rows(r) / prod over connecting edges of
max(ndv_l, ndv_r)), pick the cheapest by total intermediate rows — exact
subset DP for small regions, greedy for wide ones — and rebuild the region
left-deep with a restoring projection on top.

Only inner joins reorder (outer/semi join order is semantics-bearing), and
only along connected edges (a reorder never introduces a cross product the
author didn't write).  A filtering semi / anti join that push_filters sank
into the region (plan/optimizer.py `_sink_filtering_joins`; it runs first)
is a LEAF of it, like a relation under its Filter: it travels with the
relation it stands on, and the orders are costed with its estimate (half
its input, plan/stats.py) — TPC-H q18's region is lineitem, customer and
the semi-joined orders.

`join_estimates` reads a FINISHED plan the way the pass costed it: the
leaves of each region in the order they are joined, and for every inner
equi-join the rows the Selinger formula gives its output (the numbers
`_dp_order` compared) — what the `planner` span carries as `join_order` and
`join_estimates`, to be held against the rows each join really made.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..connectors.spi import CatalogManager
from ..data.types import BOOLEAN
from .ir import Call, FieldRef, IrExpr, field_refs, remap
from .nodes import Filter, Join, PlanNode, Project, TableScan, walk
from .stats import estimate, _expr_ndv

__all__ = ["reorder_joins", "join_estimates", "is_ordered_join"]

# exact subset DP up to this many relations; greedy beyond (2^10 subsets is
# still instant, and TPC-DS Q64's region is 8-way)
_DP_LIMIT = 10


def reorder_joins(plan: PlanNode, catalogs: CatalogManager) -> PlanNode:
    def rw(node: PlanNode) -> PlanNode:
        if _is_region_root(node):
            return _reorder_region(node, rw, catalogs)
        return _with_children(node, tuple(rw(c) for c in node.children))
    return rw(plan)


def is_ordered_join(node: PlanNode) -> bool:
    """An inner equi-join: what a region is made of, and what
    `join_estimates` has a number for."""
    return isinstance(node, Join) and node.kind == "inner" and bool(node.left_keys)


def _is_region_root(node: PlanNode) -> bool:
    # a region is worth reordering only when it spans >= 3 relations (the
    # 2-way build/probe side choice belongs to plan/distribute.py)
    if not is_ordered_join(node):
        return False
    return _count_rels(node) >= 3


def _count_rels(node: PlanNode) -> int:
    if is_ordered_join(node):
        return _count_rels(node.left) + _count_rels(node.right)
    return 1


def _with_children(node: PlanNode, children: tuple[PlanNode, ...]) -> PlanNode:
    if not children:
        return node
    if isinstance(node, Join):
        return dataclasses.replace(node, left=children[0], right=children[1])
    from .nodes import Concat

    if isinstance(node, Concat):
        return dataclasses.replace(node, inputs=children)
    return dataclasses.replace(node, child=children[0])


def _shift(e: IrExpr, off: int) -> IrExpr:
    if off == 0:
        return e
    return remap(e, {i: i + off for i in field_refs(e)})


class _Region:
    """A maximal inner-equi-join tree as a join graph: its leaf relations
    left to right, the equi conditions between two of them as edges, and the
    Selinger row count of joining one more relation to a set of them."""

    def __init__(self, root: Join, leaf, catalogs: CatalogManager):
        # ---- flatten: relations in original left-to-right order + conditions
        # in region-global indices (the region's output schema IS the
        # concatenation of its relations' outputs, so child-local key indices
        # shift by the left subtree's width)
        self.rels = rels = []  # list[PlanNode]
        conds: list[tuple[IrExpr, IrExpr]] = []  # equi pairs, global indices
        self.resids = resids = []  # non-equi / multi-rel predicates, global

        def flatten(node: PlanNode, base: int) -> int:
            """Returns the node's output width; appends leaf relations.
            `base` is the node's starting index in the region-global schema
            (the subtree's child-local key indices shift by it)."""
            if is_ordered_join(node):
                lw = flatten(node.left, base)
                rw_ = flatten(node.right, base + lw)
                for lk, rk in zip(node.left_keys, node.right_keys):
                    conds.append((_shift(lk, base), _shift(rk, base + lw)))
                if node.residual is not None:
                    # residual is over (left ++ right) = this subtree's span
                    resids.append(_shift(node.residual, base))
                return lw + rw_
            rels.append(leaf(node))  # the pass recurses here for nested regions
            return len(node.output_types)

        self.total_w = flatten(root, 0)
        self.offsets: list[int] = []
        off = 0
        for r in rels:
            self.offsets.append(off)
            off += len(r.output_types)

        # ---- classify conditions into graph edges vs residual predicates
        # edge: (rel_a, rel_b, expr_a_global, expr_b_global)
        self.edges = edges = []
        for a, b in conds:
            ra = {self.rel_of(i) for i in field_refs(a)}
            rb = {self.rel_of(i) for i in field_refs(b)}
            if len(ra) == 1 and len(rb) == 1 and ra != rb:
                edges.append((ra.pop(), rb.pop(), a, b))
            else:
                # a key pair spanning >2 relations can't be a graph edge; keep
                # it as an equality residual (NULL keys drop either way)
                resids.append(Call("eq", (a, b), BOOLEAN))

        # ---- per-relation stats (filters are already pushed into relations)
        rel_stats = [estimate(r, catalogs) for r in rels]
        self.rel_rows = rel_rows = [max(1.0, s.rows) for s in rel_stats]

        def edge_ndv(eidx: int) -> float:
            ra, rb, ea, eb = edges[eidx]
            nda = _expr_ndv(self.to_local(ea, ra), rel_stats[ra])
            ndb = _expr_ndv(self.to_local(eb, rb), rel_stats[rb])
            known = [v for v in (nda, ndb) if v]
            if known:
                return max(known)
            # FK->PK default: assume the join collapses to the larger side
            return min(rel_rows[ra], rel_rows[rb])

        self.ndvs = [max(1.0, edge_ndv(i)) for i in range(len(edges))]
        self.adj: dict[int, list[int]] = {i: [] for i in range(len(rels))}
        for ei, (ra, rb, _, _) in enumerate(edges):
            self.adj[ra].append(ei)
            self.adj[rb].append(ei)

    def rel_of(self, idx: int) -> int:
        for i in range(len(self.rels) - 1, -1, -1):
            if idx >= self.offsets[i]:
                return i
        return 0

    def to_local(self, e: IrExpr, r: int) -> IrExpr:
        return remap(e, {i: i - self.offsets[r] for i in field_refs(e)})

    def join_rows(self, rows_s: float, members: frozenset, r: int) -> Optional[float]:
        sel = 1.0
        connected = False
        for ei in self.adj[r]:
            ra, rb, _, _ = self.edges[ei]
            other = rb if ra == r else ra
            if other in members:
                connected = True
                sel /= self.ndvs[ei]
        if not connected:
            return None
        return max(1.0, rows_s * self.rel_rows[r] * sel)


def _reorder_region(root: Join, rw, catalogs: CatalogManager) -> PlanNode:
    region = _Region(root, rw, catalogs)
    rels, offsets, edges, resids = region.rels, region.offsets, region.edges, region.resids
    total_w, rel_of, to_local = region.total_w, region.rel_of, region.to_local
    n = len(rels)
    if not edges:
        return _rebuild_original(root, rw)
    rel_rows, ndvs, adj, join_rows = region.rel_rows, region.ndvs, region.adj, region.join_rows

    order = (
        _dp_order(n, rel_rows, join_rows)
        if n <= _DP_LIMIT
        else _greedy_order(n, rel_rows, join_rows, edges, ndvs)
    )
    if order is None or order == list(range(n)):
        return _rebuild_original(root, rw)

    # ---- rebuild left-deep in the chosen order
    acc = rels[order[0]]
    acc_rels = [order[0]]
    applied = [False] * len(resids)

    def acc_index(i: int) -> int:
        """Global index -> index in the accumulated (reordered) schema."""
        r = rel_of(i)
        a_off = 0
        for ar in acc_rels:
            if ar == r:
                break
            a_off += len(rels[ar].output_types)
        return a_off + (i - offsets[r])

    def global_to_acc(e: IrExpr) -> IrExpr:
        return remap(e, {i: acc_index(i) for i in field_refs(e)})

    for r in order[1:]:
        lkeys, rkeys = [], []
        for ei in adj[r]:
            ra, rb, ea, eb = edges[ei]
            other, e_other, e_r = (rb, eb, ea) if ra == r else (ra, ea, eb)
            if other in acc_rels:
                lkeys.append(global_to_acc(e_other))
                rkeys.append(to_local(e_r, r))
        acc = Join("inner", acc, rels[r], tuple(lkeys), tuple(rkeys))
        acc_rels.append(r)
        # residuals fire at the first point all their relations are joined
        have = set(acc_rels)
        for i, pred in enumerate(resids):
            if not applied[i] and {rel_of(j) for j in field_refs(pred)} <= have:
                acc = Filter(acc, global_to_acc(pred))
                applied[i] = True

    # restore the region's original column order (and schema) on top
    out_exprs = tuple(
        FieldRef(acc_index(i), root.output_types[i]) for i in range(total_w)
    )
    return Project(acc, out_exprs, tuple(root.output_names))


def _rebuild_original(root: Join, rw) -> PlanNode:
    """Keep the syntactic order but still recurse into the relations."""
    def rb(node: PlanNode) -> PlanNode:
        if is_ordered_join(node):
            return dataclasses.replace(node, left=rb(node.left), right=rb(node.right))
        return rw(node)
    return rb(root)


def _dp_order(n, rel_rows, join_rows) -> Optional[list[int]]:
    """Exact left-deep DP over connected subsets: dp[S] = (cost, rows, order)
    with cost = sum of intermediate result sizes (ReorderJoins' cost-compare
    in miniature)."""
    dp: dict[frozenset, tuple[float, float, list[int]]] = {}
    for i in range(n):
        dp[frozenset([i])] = (0.0, rel_rows[i], [i])
    for _size in range(2, n + 1):
        new: dict[frozenset, tuple[float, float, list[int]]] = {}
        for s, (cost, rows, order) in dp.items():
            if len(s) != _size - 1:
                continue
            for r in range(n):
                if r in s:
                    continue
                jr = join_rows(rows, s, r)
                if jr is None:
                    continue
                ns = s | {r}
                ncost = cost + jr
                cur = new.get(ns)
                if cur is None or ncost < cur[0]:
                    new[ns] = (ncost, jr, order + [r])
        if not new:
            return None  # graph disconnected at some width: keep original
        dp.update(new)
    full = dp.get(frozenset(range(n)))
    return full[2] if full else None


def _greedy_order(n, rel_rows, join_rows, edges, ndvs) -> Optional[list[int]]:
    """Wide regions: start from the cheapest edge, then repeatedly absorb the
    connected relation that minimizes the next intermediate size."""
    best0 = None
    for ei, (ra, rb, _, _) in enumerate(edges):
        rows = max(1.0, rel_rows[ra] * rel_rows[rb] / ndvs[ei])
        start = [ra, rb] if rel_rows[ra] >= rel_rows[rb] else [rb, ra]
        if best0 is None or rows < best0[0]:
            best0 = (rows, start)
    if best0 is None:
        return None
    rows, order = best0
    members = frozenset(order)
    while len(order) < n:
        best = None
        for r in range(n):
            if r in members:
                continue
            jr = join_rows(rows, members, r)
            if jr is None:
                continue
            if best is None or jr < best[0]:
                best = (jr, r)
        if best is None:
            return None
        rows, r = best
        order.append(r)
        members = members | {r}
    return order


def join_estimates(plan: PlanNode, catalogs: CatalogManager) -> tuple[list, dict]:
    """Of a finished plan -> (join_order, estimates).  `join_order`: for each
    region of three or more relations, its leaves in the order they are
    joined (a leaf is named by the tables under it).  `estimates`: preorder
    node id (exec/compiler.py `_node_ids`) -> the rows the region's cost
    model gives that inner equi-join's output — `_Region.join_rows` over the
    sets it joins, the number `_dp_order` summed; a join of two joined sets
    (no order of this pass builds one) reads plan/stats.py `estimate`."""
    orders: list[list[str]] = []
    rows_of: dict[int, float] = {}  # id(join node) -> rows

    def leaf_name(rel: PlanNode) -> str:
        return "+".join(n.table for n in walk(rel) if isinstance(n, TableScan)) or type(rel).__name__

    def read_region(root: Join) -> None:
        region = _Region(root, lambda n: n, catalogs)
        if len(region.rels) >= 3:
            orders.append([leaf_name(r) for r in region.rels])
        at = iter(range(len(region.rels)))

        def rec(node: PlanNode) -> tuple[frozenset, float]:
            if not is_ordered_join(node):
                i = next(at)
                return frozenset([i]), region.rel_rows[i]
            ml, rl = rec(node.left)
            mr, rr = rec(node.right)
            rows = None
            if len(mr) == 1:
                rows = region.join_rows(rl, ml, next(iter(mr)))
            elif len(ml) == 1:
                rows = region.join_rows(rr, mr, next(iter(ml)))
            if rows is None:
                rows = estimate(node, catalogs).rows
            rows_of[id(node)] = rows
            return ml | mr, rows

        rec(root)

    def visit(node: PlanNode, inside: bool) -> None:
        if is_ordered_join(node) and not inside:
            read_region(node)
        for c in node.children:
            visit(c, is_ordered_join(node) and is_ordered_join(c))

    visit(plan, False)
    return orders, {
        i: rows_of[id(n)] for i, n in enumerate(walk(plan)) if id(n) in rows_of
    }
