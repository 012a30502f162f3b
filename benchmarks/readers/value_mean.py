"""Mean of a number the program handed back each time (`value`), times `scale`."""


def read(ctx, spec):
    vals = ctx["spans"].values.get(spec["value"])
    if not vals:
        return None
    return sum(vals) / len(vals) * float(spec.get("scale", 1000.0))
