"""Share (%) of the memory roofline of a windowed stack's decode path (full
and sliding attention layers, gated experts at a share), from bytes the
window's passes must move at least: `lib.swa_work.<bytes>(config, work,
counters)` (the engine's own counters say how many passes ran, how many
columns the full layers' attention read, how many live slots' rings the
sliding layers' read and how many held experts were touched) over the peak
HBM bytes/s, over a device time of the traced window: the self time of the
operations whose scope path matches `scope` (plus, with `ops`, of those the
compiler names itself: `readers/device_time_in_scope_and_ops.py`), else the
whole span of the programs matching `modules`
(`readers/decode_bytes_roofline.py`, whose two clocks these are;
`readers/latent_bytes_roofline.py` is the same reader over
`lib/latent_work.py`).  `ops` matches by name in every program, prefill's
too: the share then errs low.

Bytes-bound: a decode pass of sixty-four slots is far under the chip's
ridge.  The bytes are a floor (lib/swa_work.py), so the share cannot pass
100%.  None without a chip's peaks, without the counter (a kind that hands
none over, a program that counts none: the parent of the PR that added the
family), or when nothing matching ran.
"""

from benchmarks.lib import loader, swa_work
from benchmarks.readers.decode_bytes_roofline import scope_s
from benchmarks.readers.device_time_in_scope_and_ops import scope_and_ops_s
from benchmarks.readers.device_time_of_modules_matching import modules_s


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    counters, work = ctx["counters"] or {}, ctx["work"] or {}
    if not counters.get(spec["counter"]):
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
    need = getattr(swa_work, spec["bytes"])(config, work, counters)
    if not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
