"""SPMD exchange kernels: the ICI data plane.

The reference moves pages between tasks over HTTP long-polls
(operator/output/PagePartitioner.java:135 -> PartitionedOutputBuffer ->
HttpPageBufferClient.java:355 -> ExchangeOperator.java:234).  Inside a TPU
slice that whole path collapses to XLA collectives traced into the jitted
step, executing over ICI with no host involvement:

  repartition : hash(keys) % D -> bucket-sort rows into a [D, B] send
                buffer -> lax.all_to_all -> flatten received buckets
  broadcast   : lax.all_gather of the local shard (replicated build sides)
  gather      : same collective; semantically "everyone gets everything"
                (the reference's GATHER distribution to a single node —
                replication is the SPMD equivalent)

Bucket capacity B is static; the kernel reports the true max bucket fill
(pmax across devices) so the host can retry a bigger tier — backpressure by
recompilation instead of the reference's blocking isBlocked() futures.

Page integrity boundary: exchanges that leave the slice as HOST BYTES
(HTTP fetches, spool files) carry a crc32 frame verified on every read
(runtime/wire.py frame_chunk/unframe_chunk — the reference's PagesSerde
checksums).  THIS path intentionally carries none: ICI collectives never
materialize host bytes (link-layer CRC + ECC cover the transfer), so the
frame is applied exactly where data first becomes bytes — page_to_wire* on
the producing worker — and checked wherever bytes are consumed.
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Sequence

import jax
import jax.numpy as jnp

from ..ops.expr import ColumnVal
from ..ops.relops import _combined_hash  # shared key hashing (join/exchange)

__all__ = ["repartition", "gather_all", "planned_exchanges", "reckon", "AXIS"]

AXIS = "workers"

from ..utils.metrics import GLOBAL as _METRICS

# host-side, trace-time accounting: shapes are static, so the planned
# per-device collective payload is known when the exchange is traced.
# Incremented once per compiled program, not per dispatch.
_EXCHANGE_PLANNED_BYTES = _METRICS.counter(
    "trino_tpu_spmd_exchange_planned_bytes_total",
    "Per-device collective payload bytes planned at trace time",
    ("kind",),
)

# The same reckoning per PROGRAM: whoever traces one opens
# planned_exchanges() around the trace (exec/spmd.py) and keeps the tally
# with the program, so every dispatch of it can say what it moves.
_TALLY = threading.local()
_TAG = "xchg"  # a collective's scope is `xchg<its index in the tally>`


@contextlib.contextmanager
def planned_exchanges():
    """-> the tally of what is traced on this thread inside the block: one
    (collective, per-device bytes entering it) per collective, in trace
    order.  `reckon` sums it up."""
    before = getattr(_TALLY, "open", None)
    tally = _TALLY.open = []
    try:
        yield tally
    finally:
        _TALLY.open = before


def reckon(tally: list, lowered_text: str | None = None) -> dict:
    """{"exchanges": {collective: how many the program holds},
    "exchange_bytes": per-device payload entering them}.  `all_to_all` and
    `all_gather` carry rows; `pmax_count` is the one-scalar all_gather by
    which the devices agree on an overflow counter (latency, no bytes).
    With the lowered program's text (debug info on) only the collectives
    that survived into it count: a gathered column nothing reads afterwards
    is traced and then dropped, and moves nothing."""
    alive = range(len(tally)) if lowered_text is None else sorted(
        {int(i) for i in re.findall(rf"\b{_TAG}(\d+)/", lowered_text)})
    out: dict = {"exchanges": {}, "exchange_bytes": 0}
    for i in alive:
        collective, nbytes = tally[i]
        out["exchanges"][collective] = out["exchanges"].get(collective, 0) + 1
        out["exchange_bytes"] += nbytes
    return out


def _planned(collective: str, nbytes: int = 0):
    """Enters one collective into the open tally -> the scope to trace it
    under, by which `reckon` finds it again in the lowered program."""
    tally = getattr(_TALLY, "open", None)
    if tally is None:
        return contextlib.nullcontext()
    tally.append((collective, nbytes))
    return jax.named_scope(f"{_TAG}{len(tally) - 1}")


def pmax_count(value, axis: str):
    """Cross-device max of a row counter, agreed on by every device.

    Not `lax.pmax`: counters are int64, and the TPU compiler lowers a 64-bit
    all-reduce only for Sum ("Supported lowering only of Sum all reduce"),
    so an int64 pmax compiles on the CPU's virtual devices and is refused on
    a real multi-chip mesh.  An all_gather is data movement, which it does
    split into 32-bit halves; the max is then local."""
    with _planned("pmax_count"):
        return jnp.max(jax.lax.all_gather(value, axis))


def _plan(kind: str, x: jnp.ndarray, collective: str):
    """One array enters an exchange of `kind` through one `collective`: its
    bytes are the lanes on this device, before any send-buffer padding.
    -> the scope to trace the collective under."""
    nbytes = int(x.size) * x.dtype.itemsize
    _EXCHANGE_PLANNED_BYTES.labels(kind).inc(nbytes)
    return _planned(collective, nbytes)


def gather_all(cols: Sequence[ColumnVal], live: jnp.ndarray, axis: str = AXIS):
    """Replicate the local shard to every device (broadcast/gather)."""
    out_cols = []
    for cv in cols:
        data = _flatten_gather(cv.data, axis)
        valid = None if cv.valid is None else _flatten_gather(cv.valid, axis)
        data2 = None if cv.data2 is None else _flatten_gather(cv.data2, axis)
        out_cols.append(ColumnVal(data, valid, cv.dict, cv.type, data2))
    return out_cols, _flatten_gather(live, axis)


def _flatten_gather(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    with _plan("gather", x, "all_gather"):
        g = jax.lax.all_gather(x, axis)  # [D, n, ...]
    return g.reshape((-1,) + g.shape[2:])


def repartition(
    cols: Sequence[ColumnVal],
    live: jnp.ndarray,
    keys: Sequence[ColumnVal],
    num_devices: int,
    bucket_capacity: int,
    axis: str = AXIS,
):
    """Hash-route rows to devices; returns (cols, live, required_bucket).

    Local output capacity is D * bucket_capacity.  Rows with NULL keys hash
    to partition 0 (they can never equi-match, but anti-join semantics need
    them kept).
    """
    n = live.shape[0]
    D = num_devices
    B = bucket_capacity

    h = _combined_hash(keys, live, n, sentinel=0)
    part = jnp.where(live, h % D, 0).astype(jnp.int32)
    part = jnp.where(live, part, D)  # dead rows -> dropped bucket

    # stable bucket sort by partition id
    iota = jnp.arange(n, dtype=jnp.int32)
    part_s, perm = jax.lax.sort([part, iota], num_keys=1, is_stable=True)
    # rank within bucket = position - first index of the bucket
    first_idx = jnp.searchsorted(part_s, jnp.arange(D + 1, dtype=jnp.int32), side="left")
    counts = first_idx[1:] - first_idx[:-1]  # [D+1] -> per-partition counts
    rank = jnp.arange(n, dtype=jnp.int32) - jnp.take(
        first_idx, jnp.minimum(part_s, D)
    )
    required = jnp.max(counts[:D]) if D > 0 else jnp.int32(0)
    required = pmax_count(required, axis)

    # scatter sorted rows into [D, B] send buffers (overflow rows dropped --
    # the host retries with bigger B before trusting results)
    slot = jnp.where((part_s < D) & (rank < B), part_s * B + rank, D * B)

    def to_buckets(x_sorted: jnp.ndarray) -> jnp.ndarray:
        flat = jnp.zeros((D * B + 1,) + x_sorted.shape[1:], x_sorted.dtype)
        flat = flat.at[slot].set(x_sorted, mode="drop")
        return flat[: D * B].reshape((D, B) + x_sorted.shape[1:])

    def route(x: jnp.ndarray, x_sorted: jnp.ndarray) -> jnp.ndarray:
        sent = to_buckets(x_sorted)
        with _plan("repartition", x, "all_to_all"):
            recv = jax.lax.all_to_all(sent, axis, split_axis=0, concat_axis=0)
        return recv.reshape((-1,) + recv.shape[2:])

    out_live = route(live, jnp.take(live, perm) & (rank < B) & (part_s < D))

    out_cols = []
    for cv in cols:
        data, valid, data2 = (
            None if x is None else route(x, jnp.take(x, perm))
            for x in (cv.data, cv.valid, cv.data2)
        )
        out_cols.append(ColumnVal(data, valid, cv.dict, cv.type, data2))
    return out_cols, out_live, required
