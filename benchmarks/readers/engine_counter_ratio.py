"""100 * sum(counters[numerator]) / sum(counters[denominator]) from the
program's own counters over the window."""


def read(ctx, spec):
    c = ctx["counters"]
    if not c:
        return None
    den = sum(c.get(k, 0) for k in spec["denominator"])
    if not den:
        return None
    return 100.0 * sum(c.get(k, 0) for k in spec["numerator"]) / den
