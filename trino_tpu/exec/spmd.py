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
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..connectors.spi import CatalogManager
from ..data.page import Column, Page
from ..parallel.exchange import AXIS
from ..plan.nodes import Exchange, Join, PlanNode, TableScan, TopN
from .compiler import (
    _EAGER_SIZING_LIMIT, LocalExecutor, _child_ids, _node_ids, _pow2, _trace_plan,
)

__all__ = ["SpmdExecutor"]


class SpmdExecutor(LocalExecutor):
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
        self._sharded_pages: dict = {}

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    # ----------------------------------------------------------- input shards
    def sharded_table_page(self, node: TableScan) -> Page:
        """Global arrays laid out [D * cap_local]: device d owns rows
        [d*cap_local, (d+1)*cap_local); trailing pad rows are dead.

        Every array is placed with a NamedSharding over the mesh, so each
        device receives only its own row range (a plain jnp.asarray would
        land the whole table on the first device and reshard it at every
        dispatch).  Pages are cached per (table, columns, generation): a
        repeated query uploads nothing."""
        conn = self.catalogs.get(node.catalog)
        key = (node.catalog, node.table, tuple(node.column_names),
               getattr(conn, "generation", 0), self.split_pad_rows)
        cached = self._sharded_pages.get(key)
        if cached is not None:
            return cached
        D = self.num_devices
        full = self.table_page(node.catalog, node.table, node.column_names, node.output_types)
        n = full.capacity
        cap_local = max(1, -(-n // D))
        if self.split_pad_rows:
            # pow2-bucket the per-device shard like the split-driven
            # distributed path: two data scales share shard shape classes
            pad = int(self.split_pad_rows)
            cap_local = -(-cap_local // pad) * pad
        total = D * cap_local
        sharding = NamedSharding(self.mesh, P(AXIS))

        def place(arr):
            host = np.asarray(arr)
            padded = np.zeros((total,), dtype=host.dtype)
            padded[:n] = host
            return jax.device_put(padded, sharding)

        cols = [
            Column(
                col.type,
                place(col.data),
                None if col.valid is None else place(col.valid),
                col.dictionary,
                None if col.data2 is None else place(col.data2),
            )
            for col in full.columns
        ]
        page = Page(tuple(cols), place(full.live_mask()))
        self._sharded_pages[key] = page
        # table_page() staged the whole table on the first device: release
        # it, or that device keeps a full copy next to its quarter
        self._table_cols.clear()
        self._table_pages.clear()
        return page

    # -------------------------------------------------------------- execution
    def execute(self, plan: PlanNode) -> Page:
        nodes = _node_ids(plan)
        scans = {i: n for i, n in nodes.items() if isinstance(n, TableScan)}
        inputs = {str(i): self.sharded_table_page(n) for i, n in scans.items()}
        caps = self._learned_caps.get(plan)
        if caps is None:
            caps = self._initial_caps_spmd(nodes, inputs)
            total_rows = sum(p.capacity for p in inputs.values())
            if total_rows <= _EAGER_SIZING_LIMIT:
                # converge capacities with EAGER shard_map execution (per-op
                # dispatch, no whole-program compile per attempt) — same
                # rationale as LocalExecutor: each retry otherwise recompiles
                # the whole SPMD program, which on a virtual 8-device CPU
                # mesh costs minutes
                for _ in range(16):
                    _, required = self._run_spmd(plan, inputs, caps, eager=True)
                    overflow = {
                        nid: int(req)
                        for nid, req in required.items()
                        if nid in caps and int(req) > caps[nid]
                    }
                    if not overflow:
                        break
                    for nid, req in overflow.items():
                        caps[nid] = _pow2(max(req, caps[nid] * 2))
        # capacity bucketing (ROADMAP 2a), same as LocalExecutor.execute:
        # quantize every fed capacity onto a pow2 tier so near-identical
        # shapes share one SPMD program; also un-aliases the learned dict
        # from the retry loop's in-place growth below
        caps = {nid: _pow2(max(int(c), 1)) for nid, c in caps.items()}
        for _ in range(14):
            out_page, required = self._run_spmd(plan, inputs, caps)
            for key, val in required.items():
                if isinstance(key, int) and key < 0 and int(val) > 1:
                    raise RuntimeError(
                        "Scalar sub-query has returned multiple rows"
                    )
            overflow = {
                nid: int(req)
                for nid, req in required.items()
                if nid in caps and int(req) > caps[nid]
            }
            if not overflow:
                self._learned_caps[plan] = caps
                if self.collect_operator_stats:
                    jax.block_until_ready([c.data for c in out_page.columns])
                    self._record_operator_stats(nodes, required)
                return out_page
            for nid, req in overflow.items():
                caps[nid] = _pow2(max(req, caps[nid] * 2))
        raise RuntimeError(f"capacity retry loop did not converge: {caps}")

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

    def _initial_caps_spmd(self, nodes, inputs) -> dict[int, int]:
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
            from ..plan.nodes import Compact as _Compact

            if isinstance(n, _Compact):
                # SPMD leaves compaction points as pass-throughs (per-shard
                # capacities already divide by D; the adaptive shrink is a
                # LocalExecutor feature)
                caps[nid] = _pow2(max(child_sizes[0], 1))
                return child_sizes[0]
            from ..plan.nodes import Unnest, Values

            if isinstance(n, Values):
                return max(len(n.rows), 1)
            if isinstance(n, Unnest):
                caps[nid] = _pow2(max(child_sizes[0] * 4, 1024))
                return caps[nid]
            return child_sizes[0]

        size_of(0, nodes[0])
        return caps

    def _run_spmd(
        self,
        plan: PlanNode,
        inputs: dict[str, Page],
        caps: dict[int, int],
        eager: bool = False,
    ):
        from jax import shard_map

        D = self.num_devices
        mesh = self.mesh
        collect = self.collect_operator_stats

        def step(pages):
            return _trace_plan(plan, pages, caps, D, AXIS, collect_stats=collect)

        def smap(fn):
            return shard_map(
                fn, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(),
                check_vma=False,
            )

        if eager:
            out_page, required = smap(step)(inputs)
            return out_page, jax.device_get(required)

        from ..ops.kernels import policy_key

        cache_key = ("spmd", plan, collect, tuple(sorted(caps.items())),
                     tuple(sorted((k, p.capacity) for k, p in inputs.items())),
                     policy_key())
        if cache_key not in self._jit_cache:
            smapped = smap(step)
            # pack overflow counters into one vector (see LocalExecutor._run:
            # per-scalar device_get calls each synchronise with the device)
            holder: dict = {"keys": None}

            def call(pages, _holder=holder):
                out_page, req = smapped(pages)
                keys = sorted(req, key=repr)
                _holder["keys"] = keys
                packed = (
                    jnp.stack([jnp.asarray(req[k], jnp.int64) for k in keys])
                    if keys
                    else jnp.zeros((0,), jnp.int64)
                )
                return out_page, packed

            self._jit_cache[cache_key] = (jax.jit(call), holder)
        fn, holder = self._jit_cache[cache_key]
        out_page, packed = fn(inputs)
        vals = np.asarray(packed)
        return out_page, dict(zip(holder["keys"], vals.tolist()))
