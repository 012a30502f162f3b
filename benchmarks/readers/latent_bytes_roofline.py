"""Share (%) of the memory roofline of a latent-attention stack's decode
path, from bytes the window's passes must move at least:
`lib.latent_work.<bytes>(config, work, counters)` (the engine's own counters
say how many passes ran, how many held experts they touched and how many
latent rows attention read) over the peak HBM bytes/s, over a device time
of the traced window: the self time of the operations whose scope path
matches `scope`, else the whole span of the programs matching `modules`
(`readers/decode_bytes_roofline.py`, whose two clocks these are;
`readers/hybrid_bytes_roofline.py` is the same reader over
`lib/hybrid_work.py`, which it imports by name).

Bytes-bound: a decode pass of forty slots is far under the chip's ridge.
The bytes are a floor (lib/latent_work.py), so the share cannot pass 100%.
None without a chip's peaks, without the counter (a kind that hands none
over, a program that counts none: the parent of the PR that added the
family), or when nothing matching ran.
"""

from benchmarks.lib import latent_work, loader
from benchmarks.readers.decode_bytes_roofline import scope_s
from benchmarks.readers.device_time_of_modules_matching import modules_s


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    counters, work = ctx["counters"] or {}, ctx["work"] or {}
    if not counters.get(spec["counter"]):
        return None
    if "scope" in spec:
        took = scope_s(ctx, spec["scope"])
    else:
        took, _ = modules_s(ctx["trace"], spec["modules"])
    if not took:
        return None
    config = loader.load_config(spec["config"])
    need = getattr(latent_work, spec["bytes"])(config, work, counters)
    if not need:
        return None
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
