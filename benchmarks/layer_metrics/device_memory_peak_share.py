"""device: the largest share of a device's memory that was in use at once,
from the program's own gauges — `trino_tpu_device_memory_peak_bytes` over
`trino_tpu_device_memory_limit_bytes` (exec/compiler.py: the device's
`memory_stats()`, read each time a statement's program has run; the peak is
the process's high-water mark, set-up included).  A program without the
gauges, or a backend that keeps no such counters, leaves nothing to read."""


def read(ctx):
    from trino_tpu.utils.metrics import GLOBAL

    peak = GLOBAL.gauge("trino_tpu_device_memory_peak_bytes").value()
    limit = GLOBAL.gauge("trino_tpu_device_memory_limit_bytes").value()
    if peak <= 0 or limit <= 0:  # never set: no such program, or no such counters
        return None
    return 100.0 * peak / limit
