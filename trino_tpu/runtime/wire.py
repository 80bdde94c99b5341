"""Host-side page wire helpers for the multi-host data plane.

Pages crossing DCN are compacted to host columns, framed and compressed by
the C++ serde (trino_tpu/native), and rebuilt into device pages on the
receiving task — the reference's PagesSerdes + PositionsAppender path
(execution/buffer/, operator/output/PagePartitioner.java:135)."""

from __future__ import annotations

import struct
import zlib
from typing import Sequence

import numpy as np

from ..data.page import Column, Page
from ..data.types import Type
from ..native import page_serde
from ..ops.expr import column_val, eval_expr
from ..plan.ir import IrExpr
from ..utils.metrics import GLOBAL as _METRICS

__all__ = [
    "page_to_wire", "page_to_wire_chunks", "wire_to_page", "partition_page",
    "frame_chunk", "unframe_chunk", "PageTransportError", "FRAME_MAGIC",
]

# Target rows per wire chunk: bounds single HTTP transfers and lets the
# consumer acknowledge-and-free incrementally (the reference bounds transfer
# by bytes via exchange.max-response-size; rows are our natural unit).
CHUNK_ROWS = 262_144

# ---------------------------------------------------------- page integrity
# Every wire chunk carries an end-to-end integrity frame: 4-byte magic +
# little-endian crc32 of the payload (reference: PagesSerde XXH64 page
# checksums, serde/PagesSerdeUtil).  The frame survives every hop — worker
# output buffer, HTTP exchange fetch, spool commit file, out-of-core spill
# file — so a flipped bit anywhere between producer serialization and
# consumer deserialization surfaces as a typed PAGE_TRANSPORT_ERROR instead
# of silently wrong rows, and the fetch path retries through the existing
# token-resume machinery.
FRAME_MAGIC = b"TPG1"
_FRAME_HEADER = len(FRAME_MAGIC) + 4

_TRANSPORT_ERRORS = _METRICS.counter(
    "trino_tpu_page_transport_errors_total",
    "Exchange frames rejected by crc32 verification",
)


class PageTransportError(RuntimeError):
    """A wire chunk failed integrity verification (bad magic or crc32
    mismatch).  Message carries the [PAGE_TRANSPORT_ERROR] error code."""

    def __init__(self, detail: str):
        super().__init__(f"{detail} [PAGE_TRANSPORT_ERROR]")


def frame_chunk(blob: bytes) -> bytes:
    """magic + crc32(payload) + payload."""
    return FRAME_MAGIC + struct.pack("<I", zlib.crc32(blob) & 0xFFFFFFFF) + blob


def unframe_chunk(framed: bytes) -> bytes:
    """Verify and strip the integrity frame; raises PageTransportError on
    bad magic, truncated header, or checksum mismatch."""
    if len(framed) < _FRAME_HEADER or framed[:4] != FRAME_MAGIC:
        _TRANSPORT_ERRORS.inc()
        raise PageTransportError(
            f"wire chunk missing integrity frame "
            f"(len={len(framed)}, head={framed[:4]!r})"
        )
    (want,) = struct.unpack_from("<I", framed, 4)
    payload = framed[_FRAME_HEADER:]
    got = zlib.crc32(payload) & 0xFFFFFFFF
    if got != want:
        _TRANSPORT_ERRORS.inc()
        raise PageTransportError(
            f"wire chunk crc32 mismatch: expected {want:#010x}, got {got:#010x}"
        )
    return payload


def _host_columns(page: Page) -> tuple[list[np.ndarray], list, list, np.ndarray]:
    import jax

    # one batched device->host transfer (per-array fetches synchronise
    # with the device once each; see data/page.py _fetch_host)
    fetched = jax.device_get(
        [page.live_mask()] + [(c.data, c.valid, c.data2) for c in page.columns]
    )
    live = np.asarray(fetched[0])
    host = fetched[1:]
    idx = np.nonzero(live)[0]
    datas, valids, datas2 = [], [], []
    for col, (hdata, hvalid, hdata2) in zip(page.columns, host):
        data = np.asarray(hdata)[idx]
        if col.type.is_array:
            # arrays cross the wire as JSON text (codes are process-local);
            # wire_to_page re-encodes into the receiver's dictionary
            import json as _json

            if len(idx):
                vals = col.dictionary.values[
                    np.clip(data, 0, max(len(col.dictionary) - 1, 0))
                ]
                data = np.array([_json.dumps(list(v)) for v in vals], dtype=object)
            else:
                data = np.array([], dtype=object)
        elif col.type.is_string:
            data = (
                col.dictionary.values[np.clip(data, 0, max(len(col.dictionary) - 1, 0))]
                if len(idx)
                else np.array([], dtype=object)
            )
        datas.append(data)
        valids.append(None if hvalid is None else np.asarray(hvalid)[idx])
        datas2.append(
            None if hdata2 is None else np.asarray(hdata2, np.int64)[idx]
        )
    return datas, valids, datas2, idx


def page_to_wire(page: Page, row_mask: np.ndarray = None) -> bytes:
    """Serialize (optionally a row subset of) a page."""
    datas, valids, datas2, idx = _host_columns(page)
    if row_mask is not None:
        keep = row_mask[: len(idx)] if len(row_mask) != len(idx) else row_mask
        datas = [d[keep] for d in datas]
        valids = [None if v is None else v[keep] for v in valids]
        datas2 = [None if d2 is None else d2[keep] for d2 in datas2]
    cols: dict[str, np.ndarray] = {}
    for i, (d, v, d2) in enumerate(zip(datas, valids, datas2)):
        cols[f"c{i:04d}"] = d
        if v is not None:
            cols[f"v{i:04d}"] = v
        if d2 is not None:
            cols[f"d{i:04d}"] = d2
    return frame_chunk(page_serde().serialize_columns(cols))


def page_to_wire_chunks(page: Page, chunk_rows: int = 0) -> list[bytes]:
    """Serialize a page as a sequence of independently-deserializable wire
    chunks of <= chunk_rows live rows each (token-addressed by index in the
    output buffer protocol; reference: PartitionedOutputBuffer pages)."""
    chunk_rows = chunk_rows or CHUNK_ROWS  # late-bound so tests can shrink it
    datas, valids, datas2, idx = _host_columns(page)
    n = len(idx)
    nchunks = max(1, -(-n // chunk_rows))
    out = []
    for c in range(nchunks):
        sl = slice(c * chunk_rows, min((c + 1) * chunk_rows, n))
        cols: dict[str, np.ndarray] = {}
        for i, (d, v, d2) in enumerate(zip(datas, valids, datas2)):
            cols[f"c{i:04d}"] = d[sl]
            if v is not None:
                cols[f"v{i:04d}"] = v[sl]
            if d2 is not None:
                cols[f"d{i:04d}"] = d2[sl]
        out.append(frame_chunk(page_serde().serialize_columns(cols)))
    return out


def _chunk_blob_columns(cols_p: dict, n: int, chunk_rows: int) -> list[bytes]:
    nchunks = max(1, -(-n // chunk_rows))
    out = []
    for c in range(nchunks):
        sl = slice(c * chunk_rows, min((c + 1) * chunk_rows, n))
        out.append(
            frame_chunk(
                page_serde().serialize_columns(
                    {k: v[sl] for k, v in cols_p.items()}
                )
            )
        )
    return out


def wire_to_page(
    blobs: Sequence[bytes], types: Sequence[Type], pad_pow2: bool = False
) -> Page:
    """Concatenate wire pages from multiple producers into one device page.
    Empty inputs produce a 1-row all-dead page (kernels need capacity >= 1).

    pad_pow2 pads the capacity to the next power of two with dead rows so
    repeated executions over varying input sizes collapse into O(log n)
    compiled shape classes (the out-of-core executor runs P slices through
    one jit cache this way)."""
    serde = page_serde()
    # unframe_chunk verifies each blob's crc32; blobs arriving without a
    # frame (unit tests feeding raw serde output) pass through untouched
    parts = [
        serde.deserialize_columns(
            unframe_chunk(b) if b[:4] == FRAME_MAGIC else b
        )
        for b in blobs
    ]
    total = sum(
        len(p[f"c{0:04d}"]) for p in parts if f"c{0:04d}" in p
    ) if types else 0
    if total == 0:
        import numpy as _np

        from ..data.page import Column as _Col

        cols = []
        for t in types:
            data = _np.zeros((1,), dtype=object if t.is_string else t.np_dtype)
            if t.is_string:
                data[0] = ""
            cols.append(_Col.from_numpy(t, data))
        import jax.numpy as _jnp

        return Page(tuple(cols), _jnp.zeros((1,), _jnp.bool_))
    cap = total
    if pad_pow2:
        cap = 1 << max(0, (total - 1).bit_length())
    columns: list[Column] = []
    for i, t in enumerate(types):
        wire_obj = t.is_string or t.is_array  # object lanes on the wire
        datas = [p[f"c{i:04d}"] for p in parts if f"c{i:04d}" in p]
        if datas:
            data = np.concatenate(datas)
        else:
            data = np.empty((0,), dtype=object if wire_obj else t.np_dtype)
        n = len(data)
        has_valid = any(f"v{i:04d}" in p for p in parts)
        valid = None
        if has_valid:
            vparts = []
            for p in parts:
                if f"v{i:04d}" in p:
                    vparts.append(p[f"v{i:04d}"].astype(np.bool_))
                elif f"c{i:04d}" in p:
                    vparts.append(np.ones(len(p[f"c{i:04d}"]), dtype=np.bool_))
            valid = np.concatenate(vparts) if vparts else None
        if t.is_string:
            # re-home NULL slots to a real value before dictionary encoding
            if valid is not None and len(data):
                data = data.copy()
                data[~valid] = ""
        if t.is_array:
            # JSON text -> tuples (Column.from_numpy dictionary-encodes)
            import json as _json

            decoded = np.empty(len(data), dtype=object)
            for j, s in enumerate(data):
                decoded[j] = tuple(_json.loads(s)) if isinstance(s, str) and s else ()
            data = decoded
        has_limbs = any(f"d{i:04d}" in p for p in parts)
        hi = None
        if has_limbs:
            # decimal128 high limb: producers that stayed single-lane send
            # no "d" key — their high limb is the sign extension of the lane
            hparts = []
            for p in parts:
                if f"d{i:04d}" in p:
                    hparts.append(np.asarray(p[f"d{i:04d}"], np.int64))
                elif f"c{i:04d}" in p:
                    hparts.append(
                        np.asarray(p[f"c{i:04d}"], np.int64) >> 63
                    )
            hi = np.concatenate(hparts) if hparts else np.empty((0,), np.int64)
        if cap > n:
            fill = np.zeros((cap - n,), dtype=object if wire_obj else t.np_dtype)
            if t.is_string:
                fill[:] = ""
            elif t.is_array:
                for j in range(len(fill)):
                    fill[j] = ()
            data = np.concatenate([data, fill])
            if valid is not None:
                valid = np.concatenate([valid, np.zeros(cap - n, np.bool_)])
            if hi is not None:
                hi = np.concatenate([hi, np.zeros(cap - n, np.int64)])
        if hi is not None:
            import jax.numpy as _jnp

            columns.append(
                Column(
                    t,
                    _jnp.asarray(np.asarray(data, np.int64)),
                    None if valid is None else _jnp.asarray(valid),
                    None,
                    _jnp.asarray(hi),
                )
            )
        else:
            columns.append(Column.from_numpy(t, data, valid))
    live = None
    if cap > total:
        import jax.numpy as _jnp

        live = _jnp.arange(cap, dtype=_jnp.int32) < total
    return Page(tuple(columns), live)


def bucket_assignments(
    arrays: dict, key_cols: Sequence[str], nbuckets: int
) -> "np.ndarray":
    """Row -> bucket id using THE engine partition hash (identical chain to
    partition_page below and the device exchange), so connector-bucketed
    tables align with engine hash partitioning (reference:
    ConnectorNodePartitioningProvider + BucketNodeMap).  NULL keys route to
    bucket 0, matching the exchanges."""
    import hashlib

    n = len(next(iter(arrays.values()))) if arrays else 0
    h = np.zeros(n, dtype=np.uint64)
    ok = np.ones(n, dtype=bool)
    for c in key_cols:
        vals = arrays[c]
        if isinstance(vals, np.ma.MaskedArray):
            ok &= ~np.ma.getmaskarray(vals)
            vals = np.ma.getdata(vals)
        if vals.dtype == object:
            # string value-hash: same blake2b-8 as Dictionary.hash64()
            bits = np.asarray(
                [
                    int.from_bytes(
                        hashlib.blake2b(str(v).encode(), digest_size=8).digest(),
                        "little",
                    )
                    for v in vals
                ],
                dtype=np.uint64,
            )
        elif np.issubdtype(vals.dtype, np.floating):
            bits = vals.astype(np.float64).view(np.uint64)
        else:
            bits = vals.astype(np.int64).view(np.uint64)
        h = _mix64_np(h ^ _mix64_np(bits))
    b = (h % np.uint64(max(nbuckets, 1))).astype(np.int64)
    return np.where(ok, b, 0)


def partition_page(
    page: Page, keys: Sequence[IrExpr], nparts: int, chunk_rows: int = 0
) -> list[list[bytes]]:
    """Hash-route rows into nparts sequences of wire chunks (reference:
    PagePartitioner.partitionPage:135 feeding PartitionedOutputBuffer).
    VARCHAR keys hash by dictionary VALUE (stable across tasks whose
    dictionaries differ)."""
    chunk_rows = chunk_rows or CHUNK_ROWS  # late-bound so tests can shrink it
    cap = page.capacity
    cols = [column_val(c) for c in page.columns]
    live = np.asarray(page.live_mask())
    idx = np.nonzero(live)[0]

    h = np.zeros(cap, dtype=np.uint64)
    keys_ok = np.ones(cap, dtype=bool)
    for k in keys:
        kv = eval_expr(k, cols, cap)
        if kv.valid is not None:
            keys_ok &= np.asarray(kv.valid)
        if kv.dict is not None:
            # Dictionary.hash64(): the shared value-hash table — must match
            # ops/relops.py _combined_hash so host and device partitioning
            # route equal strings identically
            table = kv.dict.hash64()
            codes = np.asarray(kv.data)
            bits = table[np.clip(codes, 0, len(table) - 1)]
        else:
            data = np.asarray(kv.data)
            if np.issubdtype(data.dtype, np.floating):
                bits = data.astype(np.float64).view(np.uint64)
            else:
                bits = data.astype(np.int64).view(np.uint64)
            if kv.data2 is not None:
                # mirror ops/relops.py _combined_hash: mix hi only when it
                # adds information beyond sign extension of the low lane
                lo = data.astype(np.int64)
                hi = np.asarray(kv.data2).astype(np.int64)
                extra = np.where(
                    hi == (lo >> 63),
                    np.uint64(0),
                    _mix64_np(hi.view(np.uint64)),
                )
                bits = bits ^ extra
        h = _mix64_np(h ^ _mix64_np(bits))
    part = (h % np.uint64(max(nparts, 1))).astype(np.int64)
    # NULL-key rows route to partition 0 (matching the device exchange,
    # parallel/exchange.py) so e.g. a distributed GROUP BY on a nullable key
    # keeps the NULL group on one partition instead of splitting it by
    # whatever garbage the dead lanes carry.
    part = np.where(keys_ok, part, 0)

    datas, valids, datas2, _ = _host_columns(page)
    part_live = part[idx]
    out = []
    for p in range(nparts):
        keep = part_live == p
        cols_p: dict[str, np.ndarray] = {}
        for i, (d, v, d2) in enumerate(zip(datas, valids, datas2)):
            cols_p[f"c{i:04d}"] = d[keep]
            if v is not None:
                cols_p[f"v{i:04d}"] = v[keep]
            if d2 is not None:
                cols_p[f"d{i:04d}"] = d2[keep]
        out.append(_chunk_blob_columns(cols_p, int(keep.sum()), chunk_rows))
    return out


def _mix64_np(x: np.ndarray) -> np.ndarray:
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


