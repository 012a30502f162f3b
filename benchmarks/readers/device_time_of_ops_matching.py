"""Sum of the device self time of operations whose name matches `ops`
(a regular expression)."""
from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled


def read(ctx, spec):
    if ctx["trace"] is None:
        return None
    return scaled(trace_reduce.time_of_ops_matching(ctx["trace"], spec["ops"]),
                  ctx, spec)
