"""Connector SPI: how table data enters the engine.

Mirrors the reference's plugin surface (core/trino-spi/src/main/java/io/trino/
spi/connector/: Connector, ConnectorMetadata, ConnectorSplitManager,
ConnectorPageSource) reduced to the TPU data flow: connectors enumerate
*splits* (host-side row ranges), and each split materializes as numpy column
arrays that the executor uploads to HBM as a Page.
"""

from __future__ import annotations

import abc
import threading
import time
from collections.abc import Mapping
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from ..data.types import Type

__all__ = [
    "ColumnSchema", "TableSchema", "Split", "Connector", "CatalogManager",
    "ColumnStats", "TableStats", "compute_table_stats", "StagedWrite",
    "WriteConflictError", "staged_nbytes",
]


class WriteConflictError(RuntimeError):
    """The staged write's expected table version no longer matches at the
    commit point — another writer committed first.  The transaction layer
    (runtime/txn.py) arbitrates this into a typed WRITE_CONFLICT with
    bounded recompute-and-retry."""

    def __init__(self, table: str, expected, found):
        self.table = table
        self.expected = expected
        self.found = found
        super().__init__(
            f"write conflict on {table}: expected version {expected!r}, "
            f"found {found!r}"
        )


def staged_nbytes(columns: dict) -> int:
    """Approximate host bytes a staged batch holds (object/string lanes
    estimated by value length — nbytes of an object array is pointer size)."""
    total = 0
    for arr in columns.values():
        a = np.ma.getdata(arr) if isinstance(arr, np.ma.MaskedArray) else arr
        a = np.asarray(a)
        if a.dtype == object:
            total += int(sum(len(str(v)) for v in a.tolist())) + 8 * len(a)
        else:
            total += int(a.nbytes)
    return total


# guards lazy creation of per-connector write-transaction state (connectors
# don't share an __init__ chain, so the staged-write registry is attached on
# first use)
_SPI_INIT_LOCK = threading.Lock()


class StagedWrite:
    """A connector-side write transaction handle (reference:
    spi/connector/ConnectorMetadata.beginInsert / finishInsert).

    All new data accumulates here, invisible to readers, until commit_write
    swaps it in at a single atomic point guarded by a version CAS.  Staged
    bytes are leased against the node disk pool when the owning connector
    exposes one (`conn.disk_pool`), so runaway staging hits the PR 16 disk
    governor instead of the filesystem.
    """

    def __init__(self, conn: "Connector", table: str, txn_id: str,
                 operation: str, expected_version) -> None:
        self.conn = conn
        self.table = table
        self.txn_id = txn_id
        self.operation = operation  # insert | create | delete | update | merge
        self.expected_version = expected_version
        self.created_at = time.time()
        self.replace = False          # truncate-then-insert (whole-table swap)
        self.creates: list = []       # [(table_name, [ColumnSchema, ...])]
        self.inserts: list[dict] = [] # staged column batches, applied in order
        self.staged_bytes = 0
        self.leases: list = []
        self.done = False

    # -- staging --------------------------------------------------------
    def stage_create(self, columns: Sequence["ColumnSchema"]) -> None:
        self.creates.append((self.table, list(columns)))

    def stage_truncate(self) -> None:
        self.replace = True

    def stage_insert(self, data: dict) -> None:
        nbytes = staged_nbytes(data)
        pool = getattr(self.conn, "disk_pool", None)
        if pool is not None and nbytes:
            self.leases.append(pool.reserve(
                owner=f"txn:{self.txn_id}", nbytes=nbytes,
                timeout_s=getattr(self.conn, "write_stage_timeout_s", 10.0),
                what="write-stage"))
        self.inserts.append(data)
        self.staged_bytes += nbytes

    def release_leases(self) -> int:
        freed = self.staged_bytes
        for lease in self.leases:
            try:
                lease.release()
            except Exception:
                pass
        self.leases = []
        return freed


@dataclass(frozen=True)
class ColumnSchema:
    name: str
    type: Type


@dataclass(frozen=True)
class ColumnStats:
    """Reference: spi/statistics/ColumnStatistics (NDV, range, null fraction)
    feeding the cost calculators (cost/FilterStatsCalculator, JoinStatsRule)."""

    ndv: Optional[float] = None
    min: Optional[float] = None  # numeric/date lanes only
    max: Optional[float] = None
    null_fraction: float = 0.0


class LazyStats(Mapping):
    """key -> ColumnStats, each worked out by `make(key)` when it is first
    asked for and kept.  The planner sizes relations before it prunes their
    columns, and a connector may have to read a column to say anything of it
    — the TPC-H connector to generate it: 16 columns of a 60M-row lineitem
    for a statement that reads four.  Asking whether a key is there, or for
    the keys, makes nothing."""

    def __init__(self, keys, make):
        self._keys = tuple(keys)
        self._make = make
        self._made: dict = {}

    def __getitem__(self, key):
        if key not in self._made:
            if key not in self._keys:
                raise KeyError(key)
            self._made[key] = self._make(key)
        return self._made[key]

    def __contains__(self, key) -> bool:
        return key in self._keys

    def __iter__(self):
        return iter(self._keys)

    def __len__(self) -> int:
        return len(self._keys)


@dataclass(frozen=True)
class TableStats:
    row_count: float
    columns: Mapping  # name -> ColumnStats (a dict, or LazyStats)


# Above this row count NDV comes from a fixed-size random sample (the
# reference likewise estimates NDV — ANALYZE collects HLL sketches, not
# exact counts).  Exact np.unique over an SF1 lineitem column is an 18s
# sort per column; planning must not scan the data it is planning over.
_NDV_SAMPLE_ROWS = 262_144


def _estimate_ndv(base: np.ndarray, n_total: int, rng_seed: int = 0) -> float:
    """NDV from a uniform sample via the GEE estimator of Charikar et al.
    (sqrt(n/r) correction for singletons): d_hat = sqrt(n/r)*f1 + (d_s - f1)
    where d_s = distinct-in-sample, f1 = values seen exactly once."""
    r = len(base)
    if r == 0:
        return 0.0
    _, counts = np.unique(base, return_counts=True)
    d_s = float(len(counts))
    if r >= n_total:
        return d_s
    f1 = float((counts == 1).sum())
    d_hat = np.sqrt(n_total / r) * f1 + (d_s - f1)
    return float(min(max(d_hat, d_s), n_total))


def compute_table_stats(data: dict, max_ndv_rows: int = _NDV_SAMPLE_ROWS) -> TableStats:
    """Stats from in-memory columns (generator/memory connectors).
    min/max/null-fraction are exact (cheap vectorized passes); NDV is exact
    up to max_ndv_rows and GEE-sample-estimated above it, so planning cost
    stays O(sample) regardless of table size."""
    if not data:
        return TableStats(0.0, {})
    n = len(next(iter(data.values())))
    cols = {}
    samples: dict[int, np.ndarray] = {}  # per column length (null counts vary)
    for name, arr in data.items():
        nulls = 0.0
        base = arr
        if isinstance(arr, np.ma.MaskedArray):
            nulls = float(np.ma.getmaskarray(arr).sum()) / max(n, 1)
            base = arr.compressed()
        ndv = mn = mx = None
        if len(base):
            if len(base) <= max_ndv_rows:
                ndv = float(len(np.unique(base)))
            else:
                take = samples.get(len(base))
                if take is None:
                    rng = np.random.default_rng(0xD5)
                    # GEE assumes a without-replacement sample; duplicates
                    # from with-replacement draws deflate f1 and bias NDV low
                    take = rng.choice(
                        len(base),
                        min(_NDV_SAMPLE_ROWS, len(base)),
                        replace=False,
                    )
                    samples[len(base)] = take
                ndv = _estimate_ndv(base[take], len(base))
        if len(base) and base.dtype != object and np.issubdtype(base.dtype, np.number):
            mn = float(base.min())
            mx = float(base.max())
        cols[name] = ColumnStats(ndv, mn, mx, nulls)
    return TableStats(float(n), cols)


@dataclass(frozen=True)
class TableSchema:
    name: str
    columns: tuple[ColumnSchema, ...]

    def column_names(self) -> list[str]:
        return [c.name for c in self.columns]

    def column_index(self, name: str) -> int:
        for i, c in enumerate(self.columns):
            if c.name == name:
                return i
        raise KeyError(name)

    def type_of(self, name: str) -> Type:
        return self.columns[self.column_index(name)].type


@dataclass(frozen=True)
class Split:
    """A unit of scan parallelism (reference: spi/connector/ConnectorSplit).

    `part`/`num_parts` partition the table by row range; the scheduler assigns
    splits to workers (reference: NodeScheduler.java:51).
    """

    catalog: str
    table: str
    part: int
    num_parts: int


class Connector(abc.ABC):
    """A data source (reference: spi/Plugin.java -> ConnectorFactory)."""

    name: str

    def table_partitioning(self, table: str):
        """(bucket columns, bucket count) for connector-bucketed tables, or
        None (reference: spi/connector/ConnectorNodePartitioningProvider —
        pre-partitioned tables execute without a reshuffle when the bucket
        function matches the engine's hash partitioning)."""
        return None

    @abc.abstractmethod
    def list_tables(self) -> list[str]: ...

    @abc.abstractmethod
    def table_schema(self, table: str) -> TableSchema: ...

    @abc.abstractmethod
    def get_splits(self, table: str, desired_parts: int) -> list[Split]: ...

    @abc.abstractmethod
    def read_split(
        self, split: Split, columns: Sequence[str]
    ) -> dict[str, np.ndarray]:
        """Materialize the requested columns of a split as host arrays."""

    def read_split_from(self, split: Split, columns: Sequence[str]) -> tuple[dict, str]:
        """-> (read_split's columns, where they came from): what `scan_load`
        reports as its `source`.  "connector" unless the connector can say
        more (the TPC-H connector: "file" or "generated")."""
        return self.read_split(split, columns), "connector"

    def estimated_row_count(self, table: str) -> Optional[int]:
        """Optional stats for the cost-based optimizer."""
        return None

    def table_stats(self, table: str) -> Optional[TableStats]:
        """Optional column-level stats (NDV/min/max/null fraction) for the
        cost-based optimizer (reference: ConnectorMetadata.getTableStatistics)."""
        return None

    # -- transactional write SPI ---------------------------------------
    # Reference: ConnectorMetadata.beginInsert/finishInsert and Iceberg's
    # commitTransaction.  begin_write stages, commit_write swaps atomically
    # under a version CAS, abort_write discards.  Connectors override
    # _apply_staged (the swap) and write_version (the CAS token); the
    # handle registry / locking / committed-marker bookkeeping is shared.

    def _write_state(self):
        state = getattr(self, "_txn_state", None)
        if state is None:
            with _SPI_INIT_LOCK:
                state = getattr(self, "_txn_state", None)
                if state is None:
                    state = {
                        "lock": threading.Lock(),
                        "staged": {},     # txn_id -> StagedWrite
                        "committed": {},  # txn_id -> applied row count
                    }
                    self._txn_state = state
        return state

    def scan_version(self, table: str):
        """What a scan of `table` would read now, as an opaque hashable
        token, or None for "cannot tell" (the default: data that can change
        behind the engine, such as a directory of files or a remote
        database).  Columns read at one token may answer every later scan
        that sees the same token (exec/resident.py keeps them on the
        device), so a connector that returns one promises that the token
        moves with every change, and only AFTER the new data is readable."""
        return None

    def write_version(self, table: str):
        """Opaque CAS token for the table's current committed state.  The
        default is the connector-wide generation counter (coarse: any write
        conflicts with any other); iceberg narrows it to the per-table
        snapshot id."""
        return getattr(self, "generation", 0)

    def begin_write(self, table: str, txn_id: str, operation: str) -> StagedWrite:
        state = self._write_state()
        handle = StagedWrite(self, table, txn_id, operation,
                             self.write_version(table))
        with state["lock"]:
            state["staged"][txn_id] = handle
        return handle

    def commit_write(self, handle: StagedWrite) -> int:
        """Atomic point: CAS the expected version, apply the staged data,
        record the commit marker.  Raises WriteConflictError when another
        writer got there first; the staged data stays intact for retry/abort."""
        state = self._write_state()
        with state["lock"]:
            found = self.write_version(handle.table)
            if found != handle.expected_version:
                raise WriteConflictError(handle.table, handle.expected_version, found)
            rows = self._apply_staged(handle)
            state["committed"][handle.txn_id] = rows
            state["staged"].pop(handle.txn_id, None)
        handle.release_leases()
        handle.done = True
        return rows

    def abort_write(self, handle: StagedWrite) -> int:
        """Discard staged data; the live table was never touched."""
        state = self._write_state()
        with state["lock"]:
            state["staged"].pop(handle.txn_id, None)
        freed = handle.release_leases()
        self._discard_staged(handle)
        handle.done = True
        return freed

    def _apply_staged(self, handle: StagedWrite) -> int:
        """Swap staged data into the live table.  Runs under the write lock
        with the CAS already validated.  Returns rows applied."""
        rows = 0
        for name, columns in handle.creates:
            self.create_table(name, columns)  # type: ignore[attr-defined]
        if handle.replace and not handle.creates:
            self.truncate(handle.table)  # type: ignore[attr-defined]
        for data in handle.inserts:
            n = self.insert(handle.table, data)  # type: ignore[attr-defined]
            rows += int(n) if n is not None else (
                len(next(iter(data.values()))) if data else 0)
        return rows

    def _discard_staged(self, handle: StagedWrite) -> None:
        """Connector hook: delete any on-disk staging artifacts."""
        handle.inserts = []
        handle.creates = []

    def txn_committed(self, table: str, txn_id: str) -> Optional[int]:
        """Commit marker probe for replay: rows applied by txn_id, or None.
        Connector state is the truth — the journal's marker may be missing
        when the coordinator died between connector commit and journal ack."""
        state = self._write_state()
        with state["lock"]:
            return state["committed"].get(txn_id)

    def orphaned_staging(self) -> dict:
        """txn_id -> age in seconds for every staged-but-unresolved write;
        the coordinator's janitor sweep reclaims stale ones."""
        state = self._write_state()
        now = time.time()
        with state["lock"]:
            return {t: now - h.created_at for t, h in state["staged"].items()}

    def reclaim_staging(self, txn_id: str) -> int:
        """Abort an orphaned staged write by id; returns staged bytes freed."""
        state = self._write_state()
        with state["lock"]:
            handle = state["staged"].get(txn_id)
        if handle is None:
            return 0
        return self.abort_write(handle)


class CatalogManager:
    """Registry of named catalogs (reference: metadata/CatalogManager)."""

    def __init__(self) -> None:
        self._catalogs: dict[str, Connector] = {}

    def register(self, name: str, connector: Connector) -> None:
        self._catalogs[name] = connector

    def get(self, name: str) -> Connector:
        if name not in self._catalogs:
            raise KeyError(f"catalog not registered: {name}")
        return self._catalogs[name]

    def names(self) -> list[str]:
        return sorted(self._catalogs)
