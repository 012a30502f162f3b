"""Window wall time minus device busy time: what the host (and waiting)
costs beyond the device's own work."""
from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    busy = trace_reduce.busy_s(ctx["trace"])
    if busy is None:
        return None
    return scaled(max(ctx["window_s"] - busy, 0.0), ctx, spec)
