"""One of the program's own totals over the traced window, per something.

The program keeps one table behind its `areal/` host spans and host
counters (`areal_tpu/utils/telemetry.py`: `t_<span>_s`, `n_<span>`, plain
counters) and, beside the totals since it started, the totals of the
current or last profiler session: `telemetry.session_totals()`, the spans
that ENDED in it, each whole and with what its body counted.  The harness
opens and closes its traced window with `jax.profiler.start_trace` /
`stop_trace`, so that table is the window's, taken by the program itself.

`table[total]` x `scale` (default 1000: seconds read in ms) over
`table[per_total]`, or over `per`, a count the kind reports, or over the
window's seconds when the metric file names neither.  None when the program
has no `session_totals` (an earlier commit), when it lacks the total, or
when the divisor is zero: the metric is then left out of the line."""

from benchmarks.lib.readerlib import per


def read(ctx, spec):
    try:
        from areal_tpu.utils import telemetry

        table = telemetry.session_totals()
    except (ImportError, AttributeError):
        return None
    value = table.get(spec["total"])
    if "per_total" in spec:
        den = table.get(spec["per_total"])
    elif "per" in spec:
        den = per(ctx, spec)
    else:
        den = ctx["window_s"]
    if value is None or not den:
        return None
    return value * float(spec.get("scale", 1000.0)) / den
