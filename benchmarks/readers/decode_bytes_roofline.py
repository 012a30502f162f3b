"""Share (%) of the memory roofline of the decode path, from bytes the
passes must move at least: `counters[counter]` forward passes x
`lib.retention_work.<bytes>(config, work["n_slots"])` over the peak HBM
bytes/s, over a device time of the traced window:

- with `scope`: the self time of the operations whose scope path matches it
  (`readers/device_time_in_scope.py`; the path begins with the program, as
  in `jit(_decode_chunk)/.../layers/while/body/retention/...`, so a pattern
  can hold a metric to the decode programs);
- else with `modules`: the whole span of the programs matching it.

Bytes-bound: a decode pass of a few slots is far under the chip's ridge.
The bytes are a floor (lib/retention_work.py), so the share cannot pass
100%.  None without a chip's peaks, without the counter or the slot count
(a kind that hands none over), or when nothing matching ran.
"""

import re

from benchmarks.lib import loader, retention_work
from benchmarks.readers import device_time_in_scope
from benchmarks.readers.device_time_of_modules_matching import modules_s


def scope_s(ctx, pattern):
    trace = ctx["trace"]
    if trace is None or not trace.device_ops:
        return None
    times = device_time_in_scope.scope_rows(ctx)
    want = re.compile(pattern)
    total = sum(ns for rows in times.values() for path, ns, _, _ in rows
                if want.search(path))
    return total / len(times) / 1e9


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    passes = (ctx["counters"] or {}).get(spec["counter"])
    n_slots = (ctx["work"] or {}).get("n_slots")
    if not passes or not n_slots:
        return None
    if "scope" in spec:
        took = scope_s(ctx, spec["scope"])
    else:
        took, _ = modules_s(ctx["trace"], spec["modules"])
    if not took:
        return None
    config = loader.load_config(spec["config"])
    need = passes * getattr(retention_work, spec["bytes"])(config, n_slots)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
