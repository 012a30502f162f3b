"""Total device time of the programs (events of the `XLA Modules` line)
whose name matches `modules` (a regular expression), averaged over chips.
With `"share_of": "busy"` the result is 100 x that over the device's busy
time (`trace_reduce.busy_s`), else seconds through `per` / `scale`.

A program event is named `<module name>(<id>)`, the module name being
`jit_<function>`; the time is the program's whole span on the device, the
gaps between its operations included.  None when the trace has no program
line; 0 when it has one and nothing matches.
"""

import json
import re

from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled


def modules_s(trace, pattern):
    """(seconds averaged over chips, {module name: seconds on chip 0})."""
    if trace is None or not trace.device_modules:
        return None, {}
    rx = re.compile(pattern)
    total, matched = 0, {}
    for chip, events in trace.device_modules.items():
        for name, _, dur in events:
            if rx.search(name):
                total += dur
                if chip == min(trace.device_modules):
                    base = name.rsplit("(", 1)[0]
                    matched[base] = matched.get(base, 0) + dur / 1e9
    return total / len(trace.device_modules) / 1e9, matched


def read(ctx, spec):
    took, matched = modules_s(ctx["trace"], spec["modules"])
    if took is None:
        return None
    print(json.dumps({"diag": {"phase": "module_metric",
                               "metric": spec["name"], "matched": matched}}),
          flush=True)
    if spec.get("share_of") == "busy":
        busy = trace_reduce.busy_s(ctx["trace"])
        return 100.0 * took / busy if busy else None
    return scaled(took, ctx, spec)
