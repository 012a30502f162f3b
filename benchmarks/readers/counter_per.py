"""One of the program's own counters over another: `counters[counter]` x
`scale` (default 1000: a counter of seconds reads in ms) over
`counters[per_counter]`, or over `per`, a count the kind reports.  None when
either is missing or zero: a program without the counter (an earlier
commit) reports nothing."""

from benchmarks.lib.readerlib import per


def read(ctx, spec):
    counters = ctx["counters"] or {}
    value = counters.get(spec["counter"])
    if "per_counter" in spec:
        den = counters.get(spec["per_counter"])
    else:
        den = per(ctx, spec)
    if value is None or not den:
        return None
    return value * float(spec.get("scale", 1000.0)) / den
