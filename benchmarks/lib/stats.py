"""Metric arithmetic kept with the benchmark (a copy in spirit of
`areal_tpu/obs/trace.py dist_summary`, which may change; this may not)."""

import math


def percentile(values, q):
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty list."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def dist_summary(values):
    """{n, mean, p50, p95, max} of a list; {"n": 0} for none."""
    if not values:
        return {"n": 0}
    return {
        "n": len(values),
        "mean": sum(values) / len(values),
        "p50": percentile(values, 50),
        "p95": percentile(values, 95),
        "max": float(max(values)),
    }


def iqr_share(values):
    """Spread as the contract defines it: (Q3 - Q1) / median with
    `statistics.quantiles(values, n=4)`."""
    import statistics

    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
