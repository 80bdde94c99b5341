"""device: the share of the traced slice in which no operation ran on the
device (1 - busy / slice), from the profiler's trace."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
