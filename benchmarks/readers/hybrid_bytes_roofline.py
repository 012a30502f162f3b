"""Share (%) of the memory roofline of a hybrid stack's decode path, from
bytes the window's passes must move at least: `lib.hybrid_work.<bytes>(
config, work, counters)` (the engine's own counters say how many passes ran
and how many held experts they touched) over the peak HBM bytes/s, over a
device time of the traced window: the self time of the operations whose
scope path matches `scope` (plus, with `ops`, of those the compiler names
itself: `readers/device_time_in_scope_and_ops.py`), else the whole span of
the programs matching `modules` (`readers/decode_bytes_roofline.py`, whose
two clocks these are).  `ops` matches by name in every program, prefill's
too: the share then errs low.

Bytes-bound: a decode pass of a hundred slots is far under the chip's ridge.
The bytes are a floor (lib/hybrid_work.py), so the share cannot pass 100%.
None without a chip's peaks, without the counter or the slot count (a kind
that hands none over, a program that counts none), or when nothing matching
ran.
"""

from benchmarks.lib import hybrid_work, loader
from benchmarks.readers.decode_bytes_roofline import scope_s
from benchmarks.readers.device_time_in_scope_and_ops import scope_and_ops_s
from benchmarks.readers.device_time_of_modules_matching import modules_s


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    counters, work = ctx["counters"] or {}, ctx["work"] or {}
    if not counters.get(spec["counter"]) or not work.get("n_slots"):
        return None
    if "scope" in spec and "ops" in spec:
        took = scope_and_ops_s(ctx, spec)
    elif "scope" in spec:
        took = scope_s(ctx, spec["scope"])
    else:
        took, _ = modules_s(ctx["trace"], spec["modules"])
    if not took:
        return None
    config = loader.load_config(spec["config"])
    need = getattr(hybrid_work, spec["bytes"])(config, work, counters)
    if not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
