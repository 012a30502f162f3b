"""Programs lowered inside the window (compiled, or fetched from the
persistent cache: either way a shape the warm-up missed)."""


def read(ctx, spec):
    return float(ctx["compiles"]["lowered"])
