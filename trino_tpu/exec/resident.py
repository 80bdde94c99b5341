"""Device-resident column store: table columns that outlive the executor
that uploaded them.

A served query gets a new LocalExecutor (runtime/coordinator.py root
fragment, runtime/worker.py task), and an executor's own column cache dies
with it — at TPC-H SF1 that re-read, re-narrowed and re-uploaded 96 MB of
lineitem for a 0.8 ms q06 kernel.  The OWNER of the executors (Engine,
Coordinator, Worker) holds one ResidentStore and hands it to each executor
the way it hands over its tracer; LocalExecutor.table_page asks the store
before it reads the connector.  An executor built with no store keeps its
columns to itself, as the spill and revoke paths must (they exist to
release HBM between slices).

What is kept: the unfiltered, unpadded columns of one (connector object,
table ref, split) at the version the connector vouches for
(Connector.scan_version).  A connector that cannot tell (None) is never
asked here.  Keyed by the connector OBJECT, not the catalog's name, so a
catalog registered again under the same name starts empty; a table seen at
another version drops the columns of the old one.

Bounded: past BUDGET_SHARE of the device's memory the least recently used
table's columns leave the store.  A running query holds its own references
to the columns of its pages, so eviction never frees under it.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict
from typing import Callable, Optional, Sequence

from ..data.page import Column
from ..utils import metrics as _metrics

__all__ = ["ResidentStore", "BUDGET_SHARE"]

# Share of the device's `bytes_limit` the store may hold.  The rest stays
# with the queries' working sets (Engine._device_memory_budget plans a query
# against 80% of the device; at SF1 every TPC-H column together is under 6%).
BUDGET_SHARE = 0.5


def _device_budget_bytes() -> int:
    """BUDGET_SHARE of the first device's memory; 0 (no bound known) on a
    backend that reports none, e.g. the CPU."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(int(stats.get("bytes_limit") or 0) * BUDGET_SHARE)


class _Table:
    """The resident columns of one (connector, table ref, split)."""

    __slots__ = ("conn", "version", "columns", "live_rows", "nbytes", "loading")

    def __init__(self, conn, version):
        self.conn = weakref.ref(conn)  # the store must not keep a catalog alive
        self.version = version
        self.columns: dict[str, Column] = {}
        # rows that are live when the columns are padded (an empty table
        # pads to one dead row); None == every row
        self.live_rows: Optional[int] = None
        self.nbytes = 0
        self.loading = threading.Lock()  # single flight, per table


class ResidentStore:
    def __init__(self, registry: Optional[_metrics.MetricsRegistry] = None):
        registry = registry if registry is not None else _metrics.GLOBAL
        self._lock = threading.Lock()
        # (id(connector), table ref, split) -> _Table, least recently used first
        self._tables: "OrderedDict[tuple, _Table]" = OrderedDict()
        # None until the first upload asks the device; tests set it
        self.budget_bytes: Optional[int] = None
        self.nbytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # tables this store let go (evicted, or replaced by another version):
        # a long-lived executor drops its page memo when this moves, so that
        # its pages do not pin what the store released
        self.released = 0
        self._m_lookups = registry.counter(
            "trino_tpu_resident_columns_total",
            "Scan columns asked of the device-resident column store: hit ="
            " already on the device, miss = read from the connector and uploaded",
            ("result",),
        )
        self._m_evictions = registry.counter(
            "trino_tpu_resident_evictions_total",
            "Tables whose columns left the resident store to keep it in budget",
        )
        self._m_bytes = registry.gauge(
            "trino_tpu_resident_bytes",
            "Device bytes held by the resident column store",
        )

    def columns(
        self,
        conn,
        table: str,
        split: tuple,
        version,
        names: Sequence[str],
        load: Callable[[list], tuple],
    ) -> tuple[tuple, Optional[int]]:
        """-> (the Columns for `names`, live rows or None for all).

        `load(missing names) -> ({name: Column}, live rows or None)` reads
        and uploads what is not resident; per table one load runs at a time
        and the callers that waited for it find its columns (single flight).
        """
        key = (id(conn), table, split)
        with self._lock:
            t = self._tables.get(key)
            if t is not None and (t.conn() is not conn or t.version != version):
                # another version of the table (or a dead connector's
                # recycled id): its columns answer no query any more
                self._drop(key)
                t = None
            if t is None:
                t = self._tables[key] = _Table(conn, version)
            self._tables.move_to_end(key)
        loaded = 0
        if any(n not in t.columns for n in names):
            with t.loading:
                missing = [n for n in names if n not in t.columns]
                if missing:
                    cols, live_rows = load(missing)
                    loaded = len(missing)
                    with self._lock:
                        t.columns.update(cols)
                        if live_rows is not None:
                            t.live_rows = live_rows
                        if self._tables.get(key) is t:  # still the store's
                            added = sum(c.nbytes for c in cols.values())
                            t.nbytes += added
                            self.nbytes += added
                            self._trim()
        with self._lock:
            self.hits += len(names) - loaded
            self.misses += loaded
            self._m_bytes.set(self.nbytes)
        if loaded:
            self._m_lookups.labels("miss").inc(loaded)
        if len(names) - loaded:
            self._m_lookups.labels("hit").inc(len(names) - loaded)
        return tuple(t.columns[n] for n in names), t.live_rows

    def _drop(self, key) -> None:
        self.nbytes -= self._tables.pop(key).nbytes
        self.released += 1

    def _trim(self) -> None:
        """Under the lock: forget dead connectors' tables, then the least
        recently used ones until the store is inside its budget."""
        for key in [k for k, t in self._tables.items() if t.conn() is None]:
            self._drop(key)
        if self.budget_bytes is None:
            self.budget_bytes = _device_budget_bytes()
        while self.budget_bytes and self.nbytes > self.budget_bytes and self._tables:
            self._drop(next(iter(self._tables)))
            self.evictions += 1
            self._m_evictions.inc()
