"""What every reader shares: the context a run hands over, and the divisor.

ctx = {
  "trace":    lib.trace_reduce.Trace of the traced window, or None,
  "window_s": host-clock length of the window,
  "counts":   {"steps" | "dispatches" | "output_tokens" | ...: n} in the window,
  "spans":    lib.spans.Spans (host-clock totals and handed-back values),
  "counters": the program's own counters, as deltas over the window,
  "work":     operations/bytes the window's work needs (lib/flops.py),
  "peaks":    the device's row of lib/device.py PEAKS (None in a rehearsal),
  "compiles": {"lowered", "compiled", "compile_s"} inside the window,
}

A reader returns a float, or None when there is nothing to read; the
harness then leaves the metric out of the line.
"""


def per(ctx, spec):
    """The divisor a metric file names under `per` (1 when it names none);
    None when the window counted none of it."""
    key = spec.get("per")
    if key is None:
        return 1.0
    n = ctx["counts"].get(key)
    return float(n) if n else None


def scaled(value_s, ctx, spec):
    """seconds -> the metric's unit (`scale`, default 1000 for ms) per divisor."""
    d = per(ctx, spec)
    if value_s is None or d is None:
        return None
    return value_s * float(spec.get("scale", 1000.0)) / d
