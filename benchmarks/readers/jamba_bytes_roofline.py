"""Share (%) of the memory roofline of a `jamba` stack's decode path, from
bytes the window's passes must move at least: `lib.jamba_work.<bytes>(
config, work, counters)` (the engine's own counters say how many passes ran
and how many rows' states they stepped) over the peak HBM bytes/s, over a
device time of the traced window: the self time of the operations whose
scope path matches `scope`, else the whole span of the programs matching
`modules` (`readers/decode_bytes_roofline.py`, whose two clocks these are).

Bytes-bound: a decode pass of a few hundred slots is far under the chip's
ridge.  The bytes are a floor (lib/jamba_work.py), so the share cannot pass
100%.  None without a chip's peaks, without the counters (a program that
counts no `state_rows_stepped`, as every one before this reader), or when
nothing matching ran.
"""

from benchmarks.lib import jamba_work, loader
from benchmarks.readers.decode_bytes_roofline import scope_s
from benchmarks.readers.device_time_of_modules_matching import modules_s


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    counters = ctx["counters"] or {}
    if not all(counters.get(k) for k in spec["counters"]):
        return None
    if "scope" in spec:
        took = scope_s(ctx, spec["scope"])
    else:
        took, _ = modules_s(ctx["trace"], spec["modules"])
    if not took:
        return None
    config = loader.load_config(spec["config"])
    need = getattr(jamba_work, spec["bytes"])(config, ctx["work"], counters)
    if not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
