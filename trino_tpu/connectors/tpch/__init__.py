"""TPC-H connector: deterministic generated data (reference: plugin/trino-tpch)."""

from __future__ import annotations

import threading
from collections.abc import Mapping
from typing import Optional, Sequence

import numpy as np

from ...data.page import CodedStrings
from ..spi import (
    ColumnSchema, ColumnStats, Connector, LazyStats, Split, TableSchema,
    TableStats, compute_table_stats,
)
from . import columns as _columns
from .generator import SCALE_TINY, TPCH_SCHEMAS, table_row_count

__all__ = ["TpchConnector", "SCALE_TINY", "tpch_data"]


class _Table(Mapping):
    """What `tpch_data` returns: column name -> numpy array, as
    `generator.generate_table` gives them, each column taken from its file
    (columns.py: memory-mapped, read-only; generated and written first where
    there is none) when it is first asked for and kept.  A scan asks `raw`
    and gets string columns as they are stored, dictionary coded."""

    def __init__(self, table: str, scale: float):
        self.table, self.scale = table, scale
        self._names = [c for c, _t in TPCH_SCHEMAS[table]]
        self._raw: dict = {}
        self._decoded: dict = {}
        self._rows: Optional[int] = None  # known once a column is loaded
        self._lock = threading.Lock()

    def raw(self, columns: Sequence[str]) -> tuple[dict, str]:
        """-> ({column: array or CodedStrings}, "generated" if this call had
        to generate a column, else "file")."""
        source = "file"
        with self._lock:  # one generation pass at a time per table
            missing = [c for c in columns if c not in self._raw]
            if missing:
                got, source = _columns.load(self.table, self.scale, missing)
                self._raw.update(got)
                self._rows = len(got[missing[0]])
        return {c: self._raw[c] for c in columns}, source

    @property
    def rows(self) -> int:
        if self._rows is None:
            self.raw(self._names[:1])
        return self._rows

    def loaded(self) -> bool:
        return self._rows is not None

    def __getitem__(self, column: str) -> np.ndarray:
        if column not in self._names:
            raise KeyError(column)
        col = self.raw([column])[0][column]
        if isinstance(col, CodedStrings):
            if column not in self._decoded:  # one object per call site's id()
                self._decoded[column] = col.decode()
            return self._decoded[column]
        return col

    def __iter__(self):
        return iter(self._names)

    def __len__(self) -> int:
        return len(self._names)


# Module-level cache: (table, scale) -> the table.  Generation is
# deterministic so caching is safe; tests and benches reuse the same data.
_TABLES: dict[tuple[str, float], _Table] = {}
_STATS: dict[tuple[str, float], TableStats] = {}
_TABLES_LOCK = threading.Lock()


def tpch_data(table: str, scale: float) -> _Table:
    key = (table, scale)
    with _TABLES_LOCK:
        if key not in _TABLES:
            _TABLES[key] = _Table(table, scale)
        return _TABLES[key]


class TpchConnector(Connector):
    """Schemas named like the reference's tpch catalog: scale comes from the
    connector instance (tpch.tiny == TpchConnector(scale=0.01))."""

    name = "tpch"

    def __init__(self, scale: float = SCALE_TINY):
        self.scale = scale

    def list_tables(self) -> list[str]:
        return list(TPCH_SCHEMAS)

    def table_schema(self, table: str) -> TableSchema:
        if table not in TPCH_SCHEMAS:
            raise KeyError(f"tpch table not found: {table}")
        return TableSchema(table, tuple(ColumnSchema(n, t) for n, t in TPCH_SCHEMAS[table]))

    def get_splits(self, table: str, desired_parts: int) -> list[Split]:
        return [Split("tpch", table, p, desired_parts) for p in range(desired_parts)]

    def read_split(self, split: Split, columns: Sequence[str]) -> dict:
        return self.read_split_from(split, columns)[0]

    def read_split_from(self, split: Split, columns: Sequence[str]) -> tuple[dict, str]:
        """String columns come as data/page.py CodedStrings (the stored codes
        and dictionary), everything else as numpy arrays; "generated" if
        this call had to generate a column, else "file"."""
        data = tpch_data(split.table, self.scale)
        cols, source = data.raw(columns)
        n = data.rows
        lo = split.part * n // split.num_parts
        hi = (split.part + 1) * n // split.num_parts
        return {c: cols[c][lo:hi] for c in columns}, source

    def scan_version(self, table: str):
        return self.scale  # generated from (table, scale): it never changes

    def estimated_row_count(self, table: str) -> Optional[int]:
        data = tpch_data(table, self.scale)
        if data.loaded():
            return data.rows
        return table_row_count(table, self.scale)

    def table_stats(self, table: str):
        """Exact column stats over the generated data (reference:
        TpchMetadata.getTableStatistics serves precomputed stats)."""
        key = (table, self.scale)
        if key not in _STATS:
            data = tpch_data(table, self.scale)

            def column_stats(column: str) -> ColumnStats:
                col = data.raw([column])[0][column]
                if isinstance(col, CodedStrings):  # as many codes as strings
                    s = compute_table_stats({column: col.codes}).columns[column]
                    return ColumnStats(s.ndv, None, None, s.null_fraction)
                return compute_table_stats({column: col}).columns[column]

            _STATS[key] = TableStats(float(data.rows), LazyStats(data, column_stats))
        return _STATS[key]
