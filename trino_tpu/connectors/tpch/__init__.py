"""TPC-H connector: deterministic generated data (reference: plugin/trino-tpch)."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..spi import ColumnSchema, Connector, Split, TableSchema
from .generator import SCALE_TINY, TPCH_SCHEMAS, generate_table

__all__ = ["TpchConnector", "SCALE_TINY", "tpch_data"]

# Module-level cache: (table, scale) -> column arrays.  Generation is
# deterministic so caching is safe; tests and benches reuse the same data.
_CACHE: dict[tuple[str, float], dict[str, np.ndarray]] = {}
_STATS: dict[tuple[str, float], object] = {}


def tpch_data(table: str, scale: float) -> dict[str, np.ndarray]:
    key = (table, scale)
    if key not in _CACHE:
        _CACHE[key] = generate_table(table, scale)
    return _CACHE[key]


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

    def read_split(self, split: Split, columns: Sequence[str]) -> dict[str, np.ndarray]:
        data = tpch_data(split.table, self.scale)
        n = len(next(iter(data.values())))
        lo = split.part * n // split.num_parts
        hi = (split.part + 1) * n // split.num_parts
        return {c: data[c][lo:hi] for c in columns}

    def scan_version(self, table: str):
        return self.scale  # generated from (table, scale): it never changes

    def estimated_row_count(self, table: str) -> Optional[int]:
        data = _CACHE.get((table, self.scale))
        if data is not None:
            return len(next(iter(data.values())))
        from .generator import table_row_count

        return table_row_count(table, self.scale)

    def table_stats(self, table: str):
        """Exact column stats over the generated data (reference:
        TpchMetadata.getTableStatistics serves precomputed stats)."""
        key = (table, self.scale)
        if key not in _STATS:
            from ..spi import compute_table_stats

            _STATS[key] = compute_table_stats(tpch_data(table, self.scale))
        return _STATS[key]
