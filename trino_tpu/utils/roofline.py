"""Device roofline registry: what memory bandwidth is this node's device
actually capable of, so achieved GB/s (profiler bytes-accessed over
execute wall) can be expressed as %-of-roofline — the live closure of
ROADMAP item 1's "fast as the hardware allows" claim.

Two sources, chosen by platform:

- **TPU: a static HBM table by device kind.**  Datasheet peak HBM
  bandwidth per chip; matched by substring against
  ``jax.devices()[0].device_kind`` so minor kind-string variations
  ("TPU v5 lite", "TPU v5e") still resolve.
- **CPU: calibrated once at boot** via a small STREAM-triad probe
  (``a = b + s*c`` over arrays sized well past L3), cached on disk so
  repeated processes on the same host skip the probe.  Cache path:
  ``$TRINO_TPU_ROOFLINE_CACHE`` or ``<tmpdir>/trino_tpu_roofline.json``.

Everything is lazy — nothing touches jax or runs the probe at import.
The CPU probe degrades to a conservative default; a TPU whose device kind
is not in the table raises (callers treat the roofline as telemetry and
report none, rather than a percentage of a guessed peak).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Optional

from . import metrics as _metrics

__all__ = [
    "TPU_HBM_GBPS",
    "DEFAULT_CPU_GBPS",
    "calibrate_cpu_gbps",
    "device_roofline",
    "pct_of_roofline",
    "observe_signature_gbps",
    "reset_cache",
]

# achieved memory bandwidth per executed jit signature (bytes-accessed
# from cost_analysis() over measured execute wall) — the live histogram
# behind the EXPLAIN ANALYZE %-of-roofline footer
SIGNATURE_GBPS = _metrics.GLOBAL.histogram(
    "trino_tpu_signature_gb_per_sec",
    "Achieved memory bandwidth (GB/s) per executed fragment jit "
    "signature: cost_analysis() bytes-accessed over execute wall",
    buckets=(0.1, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
             250.0, 500.0, 1000.0, 2500.0),
)

# datasheet peak HBM bandwidth (GB/s) per chip, keyed by a substring of
# jax's device_kind string; first match wins, most-specific first
TPU_HBM_GBPS: tuple[tuple[str, float], ...] = (
    ("v6e", 1640.0),
    ("v6 lite", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)

# conservative DDR fallback when /proc is absent and the probe fails
DEFAULT_CPU_GBPS = 10.0

_lock = threading.Lock()
_cached: Optional[dict] = None


def _cache_path() -> str:
    return os.environ.get(
        "TRINO_TPU_ROOFLINE_CACHE",
        os.path.join(tempfile.gettempdir(), "trino_tpu_roofline.json"),
    )


def calibrate_cpu_gbps(
    cache_path: Optional[str] = None, force: bool = False
) -> float:
    """STREAM-triad sustained bandwidth in GB/s, cached on disk.

    The probe is deliberately small (3 x 2M float64 = 48 MB working set,
    best of 3 reps, well under 100 ms on anything modern) — it measures
    the memory system, not the scheduler, and boot must not stall."""
    path = cache_path or _cache_path()
    if not force:
        try:
            with open(path) as f:
                saved = json.load(f)
            v = float(saved["cpu_gbps"])
            if v > 0:
                return v
        except (OSError, KeyError, ValueError, TypeError):
            pass
    gbps = _stream_triad_gbps()
    try:
        with open(path, "w") as f:
            json.dump({"cpu_gbps": round(gbps, 3), "ts": time.time()}, f)
    except OSError:
        pass  # read-only tmpdir: recalibrate next boot
    return gbps


def _stream_triad_gbps() -> float:
    try:
        import numpy as np
    except Exception:
        return DEFAULT_CPU_GBPS
    n = 2_000_000
    try:
        b = np.random.default_rng(0).random(n)
        c = np.random.default_rng(1).random(n)
        a = np.empty(n)
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            np.add(b, 0.42 * c, out=a)
            dt = time.perf_counter() - t0
            if dt > 0:
                # STREAM triad convention: 24 bytes per element
                # (read b, read c, write a)
                best = max(best, 24.0 * n / dt / 1e9)
        return best or DEFAULT_CPU_GBPS
    except Exception:
        return DEFAULT_CPU_GBPS


def device_roofline(cache_path: Optional[str] = None) -> dict:
    """``{platform, device_kind, hbm_gbps, source}`` for this process's
    default device.  Computed once per process (first caller pays the
    CPU probe unless the disk cache answers)."""
    global _cached
    with _lock:
        if _cached is not None:
            return dict(_cached)
    import jax

    dev = jax.devices()[0]  # no device is an error, never "cpu"
    platform = str(dev.platform).lower()
    kind = str(dev.device_kind)
    if platform == "tpu":
        low = kind.lower()
        gbps = next((v for frag, v in TPU_HBM_GBPS if frag in low), None)
        if gbps is None:
            # a device that is not in the table is an error, not a default:
            # a guessed peak would make every %-of-roofline figure a guess
            raise LookupError(
                f"no HBM bandwidth on record for TPU device_kind {kind!r};"
                f" add it to TPU_HBM_GBPS with its source"
            )
        info = {
            "platform": platform,
            "device_kind": kind,
            "hbm_gbps": gbps,
            "source": "table",
        }
    else:
        gbps = calibrate_cpu_gbps(cache_path=cache_path)
        info = {
            "platform": platform,
            "device_kind": kind,
            "hbm_gbps": round(gbps, 3),
            "source": "calibrated" if gbps != DEFAULT_CPU_GBPS else "default",
        }
    with _lock:
        _cached = info
    return dict(info)


def pct_of_roofline(gbps: float) -> float:
    """Achieved GB/s as a percentage of this device's roofline."""
    peak = device_roofline().get("hbm_gbps") or 0.0
    if peak <= 0:
        return 0.0
    return 100.0 * float(gbps) / peak


def observe_signature_gbps(gbps: float) -> None:
    SIGNATURE_GBPS.observe(float(gbps))


def reset_cache() -> None:
    """Forget the per-process memo (tests exercising the disk cache)."""
    global _cached
    with _lock:
        _cached = None
