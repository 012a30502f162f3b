"""Union of the device's operation intervals in the traced window."""
from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    return scaled(trace_reduce.busy_s(ctx["trace"]), ctx, spec)
