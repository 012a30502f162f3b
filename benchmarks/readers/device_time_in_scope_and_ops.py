"""Device self time of the operations whose scope path matches `scope`
(`readers/device_time_in_scope.py`) PLUS that of the operations whose own
name matches `ops` (`readers/device_time_of_ops_matching.py`); `per`,
`scale` as the other readers.

For work the compiler names itself: the chip's grouped-matmul kernel
behind `lax.ragged_dot` comes out of an expansion pass as `ragged-dot-none`
and `ragged-dot-metadata` custom calls whose `op_name` holds no
`jax.named_scope`, so the scope reader files them under no scope at all
(57 % of a `rollout_hybrid_moe` token; my chip run, PR 32).  The two sets
are disjoint as long as `ops` names only such operations.  None when
neither matches anything."""

from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled
from benchmarks.readers.decode_bytes_roofline import scope_s


def scope_and_ops_s(ctx, spec):
    if ctx["trace"] is None:
        return None
    parts = [scope_s(ctx, spec["scope"]),
             trace_reduce.time_of_ops_matching(ctx["trace"], spec["ops"])]
    parts = [p for p in parts if p]
    return sum(parts) if parts else None


def read(ctx, spec):
    return scaled(scope_and_ops_s(ctx, spec), ctx, spec)
