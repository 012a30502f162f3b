"""Host-clock total of one of the benchmark's spans (`span`)."""
from benchmarks.lib.readerlib import scaled


def read(ctx, spec):
    return scaled(ctx["spans"].total_s.get(spec["span"]), ctx, spec)
