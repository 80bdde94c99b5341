"""Generated TPC-H columns kept as files.

The generator is deterministic, so a column of (table, scale) is made once
per machine, not once per process: whoever asks first generates it and
writes it, everyone after reads it memory-mapped.  At SF10 lineitem is 60M
rows — a minute of generation and gigabytes of heap for every process that
would make it itself, most of it for columns no statement reads.

One file per (generator fingerprint, scale, table, column):

    <dir>/<fingerprint>/sf<scale>/<table>/<column>.npy        numbers; string codes
    <dir>/<fingerprint>/sf<scale>/<table>/<column>.dict.json  a string column's values

`<dir>` is TRINO_TPU_TPCH_CACHE, else `trino_tpu_tpch` under the system's
temporary directory: outside the checkout (gigabytes inside it would make it
too large to copy) and not the compile cache's.  The fingerprint is a hash
of the generator's seed and source and of numpy's version, so files of
another generator are never read.  A file is written under a temporary name
and renamed: two processes that miss together both write, the content is the
same, and a reader never sees part of a file.  A string column is its
sorted distinct values and int32 codes into them (data/page.py
CodedStrings), which is what a scan uploads: no process holds 60M Python
strings, and nothing codes an object array again.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import tempfile
import threading
import time

import numpy as np

from ...data.page import CodedStrings
from ...utils import metrics as _metrics
from . import generator

__all__ = ["ENV", "cache_dir", "load"]

ENV = "TRINO_TPU_TPCH_CACHE"

COLUMNS = _metrics.GLOBAL.counter(
    "trino_tpu_tpch_columns_total",
    "TPC-H columns this process took into use: file = found as a file and"
    " memory-mapped, generated = generated here (and written for the next)",
    ("source",),
)
SECONDS = _metrics.GLOBAL.counter(
    "trino_tpu_tpch_column_seconds_total",
    "Seconds spent opening TPC-H column files (file) and generating and"
    " writing columns (generated)",
    ("source",),
)


def cache_dir() -> str:
    return os.environ.get(ENV) or os.path.join(tempfile.gettempdir(), "trino_tpu_tpch")


@functools.lru_cache(maxsize=None)
def fingerprint() -> str:
    with open(generator.__file__, "rb") as f:
        source = f.read()
    h = hashlib.sha256(repr((generator._SEED, np.__version__)).encode() + source)
    return h.hexdigest()[:16]


def _folder(table: str, scale: float) -> str:
    return os.path.join(cache_dir(), fingerprint(), f"sf{float(scale)!r}", table)


def _write(path: str, save) -> None:
    """`save(file)` under a name of this thread's own, then the rename."""
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        with open(tmp, "wb") as f:
            save(f)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _store(folder: str, name: str, column) -> None:
    values = None
    if isinstance(column, generator.Coded):
        values, column = column.normalised()
    elif column.dtype == object:  # strings made row by row: coded here, once
        values, codes = np.unique(column, return_inverse=True)
        column = codes.astype(np.int32)
    if values is not None:  # before the codes: whoever finds them finds these
        _write(os.path.join(folder, name + ".dict.json"),
               lambda f: f.write(json.dumps(values.tolist()).encode()))
    _write(os.path.join(folder, name + ".npy"), lambda f: np.save(f, column))


def _open(folder: str, name: str, is_string: bool):
    """The column from its file(s), or None where there is none to read."""
    try:
        data = np.load(os.path.join(folder, name + ".npy"), mmap_mode="r")
        if not is_string:
            return data
        with open(os.path.join(folder, name + ".dict.json")) as f:
            values = json.load(f)
    except (OSError, ValueError):
        return None
    return CodedStrings(data, np.asarray(values, dtype=object))


def load(table: str, scale: float, columns) -> tuple[dict, str]:
    """-> ({column: a read-only memory-mapped array, or CodedStrings over
    one}, "file" or "generated": whether this call had to generate)."""
    folder = _folder(table, scale)
    types = dict(generator.TPCH_SCHEMAS[table])
    t0 = time.perf_counter()
    out = {c: _open(folder, c, types[c].is_string) for c in columns}
    found = [c for c in columns if out[c] is not None]
    if found:
        COLUMNS.labels("file").inc(len(found))
        SECONDS.labels("file").inc(time.perf_counter() - t0)
    missing = [c for c in columns if out[c] is None]
    if not missing:
        return out, "file"
    t0 = time.perf_counter()
    os.makedirs(folder, exist_ok=True)
    made = generator.generate_columns(table, scale, missing)
    for c in list(made):
        # what the pass made beside the columns asked for is written too,
        # unless it is there: the next miss then needs no pass
        if c in missing or not os.path.exists(os.path.join(folder, c + ".npy")):
            _store(folder, c, made.pop(c))
    del made
    for c in missing:
        out[c] = _open(folder, c, types[c].is_string)
        if out[c] is None:
            raise OSError(f"tpch column file of {table}.{c} cannot be read back from {folder}")
    COLUMNS.labels("generated").inc(len(missing))
    SECONDS.labels("generated").inc(time.perf_counter() - t0)
    return out, "generated"
