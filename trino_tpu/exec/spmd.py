"""SPMD executor: one jitted program over a jax.sharding.Mesh.

The reference's distributed runtime is coordinator-driven task orchestration:
PlanFragmenter cuts the plan at exchanges, the scheduler posts fragments to
workers over HTTP, and pages stream between tasks
(execution/scheduler/PipelinedQueryScheduler.java:164, server/remotetask/
HttpRemoteTask.java:135).  On a TPU slice the natural shape is inverted:
ONE SPMD program runs the whole multi-fragment plan on every chip under
shard_map; fragment boundaries become XLA collectives over ICI (parallel/
exchange.py) instead of HTTP hops, so multi-stage joins never leave HBM.

Scans are split across devices by row range — the reference's
SOURCE_DISTRIBUTION split scheduling (SystemPartitioningHandle.java:47,
NodeScheduler.java:51) with splits pinned round-robin.

The host keeps the reference's coordinator responsibilities that remain:
capacity planning (stats), the overflow-retry loop, and result fetch.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..connectors.spi import CatalogManager
from ..data.page import Page
from ..ops.expr import param_context
from ..parallel.exchange import AXIS, planned_exchanges, reckon
from ..plan.nodes import Exchange, Join, PlanNode, TableScan, TopN
from .compiler import (
    LocalExecutor, _child_ids, _pack_required, _pow2, _trace_plan,
)

__all__ = ["SpmdExecutor"]


class SpmdExecutor(LocalExecutor):
    """LocalExecutor.execute with three things put in their SPMD form: a
    scan's page (row ranges over the mesh), the first capacities (per
    device, exchanges sized) and the program (one shard_map).  Programs come
    from the compile service, capacities from the capacity cache and spans
    open as on one device."""

    def __init__(
        self,
        catalogs: CatalogManager,
        default_catalog: str = "tpch",
        devices: Optional[Sequence] = None,
    ):
        super().__init__(catalogs, default_catalog)
        if devices is None:
            devices = jax.devices()
        self.devices = list(devices)
        self.mesh = Mesh(np.array(self.devices), (AXIS,))
        # a program is compiled for its devices, capacities are per device
        self._program_scope = ("spmd", AXIS, tuple(d.id for d in self.devices))
        self._caps_scope = f"|spmd{len(self.devices)}"
        # (catalog, table, columns, scan version, pad) -> the sharded page
        self._sharded_pages: dict = {}

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # ----------------------------------------------------------- input shards
    def sharded_table_page(self, node: TableScan) -> Page:
        """Global arrays laid out [D * cap_local]: device d owns rows
        [d*cap_local, (d+1)*cap_local); trailing pad rows are dead.

        Every host column goes straight to a NamedSharding over the mesh, so
        each device receives its own row range and no device ever holds the
        table whole.  A page is kept while the connector vouches for the
        version it was read at (Connector.scan_version; a connector that
        cannot tell gets one read per executor): a repeated query uploads
        nothing."""
        conn = self.catalogs.get(node.catalog)
        key = (node.catalog, node.table, tuple(node.column_names),
               conn.scan_version(node.table), self.split_pad_rows)
        kept = self._sharded_pages.get(key)
        if kept is not None:
            return kept
        for k in [k for k in self._sharded_pages if k[:3] == key[:3]]:
            del self._sharded_pages[k]  # read at a version that is gone
        D = self.num_devices
        sharding = NamedSharding(self.mesh, P(AXIS))
        host_rows = []  # of every array placed: the scan's rows before padding

        def place(arr):
            host = np.asarray(arr)
            host_rows.append(len(host))
            cap_local = max(1, -(-len(host) // D))
            if self.split_pad_rows:
                # pow2-bucket the per-device shard like the split-driven
                # distributed path: two data scales share shard shape classes
                pad = int(self.split_pad_rows)
                cap_local = -(-cap_local // pad) * pad
            padded = np.zeros((D * cap_local,), dtype=host.dtype)
            padded[: len(host)] = host
            return jax.device_put(padded, sharding)

        cols, n_live = self._load_columns(
            conn, node.table, list(node.column_names), (), place)
        cols = tuple(cols[c] for c in node.column_names)
        n = host_rows[0]
        page = Page(cols, place(np.arange(n) < (n if n_live is None else n_live)))
        self._sharded_pages[key] = page
        return page

    def _scan_page(self, nid: int, node: TableScan) -> Page:
        return self.sharded_table_page(node)

    # -------------------------------------------------------------- execution
    def explain_analyze(self, plan: PlanNode, remote_pages=None):
        """SPMD EXPLAIN ANALYZE: the whole plan is ONE fused program, so
        per-operator wall time is not separable — but exact per-operator row
        counts (psum-reduced over shards) come out of the compiled run.
        Returns (page, stats) with stats[nid] = {"rows": int}."""
        prev = self.collect_operator_stats
        self.collect_operator_stats = True
        try:
            page = self.execute(plan)
        finally:
            self.collect_operator_stats = prev
        stats = {
            nid: {"rows": s["rows"]}
            for nid, s in self.last_operator_stats.items()
        }
        return page, stats

    def _initial_caps(self, nodes, inputs) -> dict[int, int]:
        """Like LocalExecutor._initial_caps but sizes are per-device and
        Exchange nodes get bucket capacities."""
        D = self.num_devices
        caps: dict[int, int] = {}

        def size_of(nid: int, n: PlanNode) -> int:
            from ..plan.nodes import Aggregate, Distinct, Limit

            if isinstance(n, TableScan):
                return inputs[str(nid)].capacity // D
            child_ids = _child_ids(nodes, nid)
            child_sizes = [size_of(c, nodes[c]) for c in child_ids]
            if isinstance(n, Exchange):
                if n.kind in ("gather", "broadcast"):
                    return D * child_sizes[0]
                B = _pow2(max(64, 2 * child_sizes[0] // max(D, 1)))
                caps[nid] = B
                return D * B
            if isinstance(n, (Aggregate, Distinct)):
                caps[nid] = _pow2(max(child_sizes[0], 1))
                return caps[nid]
            if isinstance(n, Join):
                if n.kind == "cross":
                    return child_sizes[0]
                hard = _pow2(max(max(child_sizes), 1))
                if n.kind in ("semi", "anti", "null_anti", "mark", "mark_in"):
                    caps[nid] = hard
                    return child_sizes[0]
                # stats-sized expansion frame per device (same rationale as
                # LocalExecutor._initial_caps: kernel work scales with
                # CAPACITY lanes, and worst-case frames made small joins
                # cost like full-table ones); the retry loop corrects
                # underestimates
                try:
                    from ..plan.stats import estimate as _est

                    hint = int(_est(n, self.catalogs).rows * 1.3 // max(D, 1)) + 16
                    caps[nid] = min(hard, _pow2(max(2 * hint, 4096)))
                except Exception:
                    caps[nid] = hard
                if n.kind == "left":
                    return caps[nid] + child_sizes[0]
                return caps[nid]
            if isinstance(n, TopN):
                return min(n.count, child_sizes[0])
            # a Compact gets no capacity: unset, _trace_plan passes it
            # through and execute() has nothing to shrink (per-shard
            # capacities already divide by D)
            from ..plan.nodes import Unnest, Values

            if isinstance(n, Values):
                return max(len(n.rows), 1)
            if isinstance(n, Unnest):
                caps[nid] = _pow2(max(child_sizes[0] * 4, 1024))
                return caps[nid]
            return child_sizes[0]

        size_of(0, nodes[0])
        return caps

    def _make_call(self, plan: PlanNode, caps: dict[int, int], collect: bool):
        """The traced entry point: `_trace_plan` on every device's shard
        under one shard_map.  holder["dispatch"] is what every dispatch of
        the program says of it: its devices and — reckoned by
        parallel/exchange.py while the plan is traced, and again over what
        survived into the lowered program — its collectives by kind and the
        bytes a device hands to its exchanges."""
        from jax import shard_map

        D = self.num_devices
        holder: dict = {"keys": None, "dispatch": {"devices": D}}

        def step(pages, params):
            with param_context(params):
                return _trace_plan(plan, pages, caps, D, AXIS, collect_stats=collect)

        smapped = shard_map(
            step, mesh=self.mesh, in_specs=(P(AXIS), P()), out_specs=P(),
            check_vma=False,
        )

        def call(pages, params=(), _holder=holder):
            with planned_exchanges() as planned:
                out_page, req = smapped(pages, tuple(params))
            _holder["planned"] = planned
            _holder["dispatch"].update(reckon(planned))
            return out_page, _pack_required(req, _holder)

        def lowered(program, _holder=holder):
            _holder["dispatch"].update(
                reckon(_holder["planned"], program.as_text(debug_info=True)))

        holder["lowered"] = lowered  # LocalExecutor._run's build calls it
        return call, holder

    def _trace_eager(self, plan, inputs, caps, params=(), collect=False):
        """`_run`'s fallback on the mesh: the same shard_map un-jitted,
        dispatched primitive by primitive over every device — many times
        the seconds the whole program takes to compile on a virtual CPU
        mesh, so only for a program that is not to be had in time."""
        call, holder = self._make_call(plan, caps, collect)
        out_page, packed = call(inputs, params)
        return out_page, dict(zip(holder["keys"], np.asarray(packed).tolist()))
