"""The least bytes a statement has to read: every row of each column it
references, read once, at the width the column has when resident on the
device.  From shapes alone — never from XLA's cost analysis.  If a later
change lets a query skip rows, correcting this function is a `benchmark`
issue's.
"""

from __future__ import annotations


def least_bytes(template: dict, schemas: dict, row_counts: dict,
                column_bytes: dict) -> int:
    """template["columns"]: table -> referenced columns; schemas: table ->
    [(column, type)]; row_counts: table -> rows; column_bytes: type -> bytes."""
    total = 0
    for table, columns in template["columns"].items():
        types = {c: str(t) for c, t in schemas[table]}
        for c in columns:
            total += int(row_counts[table]) * int(column_bytes[types[c]])
    return total
