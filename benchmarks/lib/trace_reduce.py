"""From a profiler trace to numbers: one generic reduction.

`read_xplane(dir)` turns the newest `.xplane.pb` under a trace directory
into a plain `Trace` (lists of `(name, start_ns, duration_ns)` tuples), and
everything else here works on that plain structure, so that the arithmetic
can be checked on a hand-built event list.

What is what in a TPU trace (JAX 0.9, TPU v5 lite): a plane per chip named
`/device:TPU:<n>`; in it a line `XLA Ops` with one event per executed HLO
operation (control flow such as `while` spans the operations inside it, so
events nest), a line `XLA Modules` with one event per executed program, and
`Steps`.  Host threads are lines of the plane `/host:CPU`; the benchmark's
own spans appear there under the names `bench/<span>`.
"""

import glob
import os
import re
from dataclasses import dataclass, field

from benchmarks.lib.spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# gaps shorter than this are summed under one name, not looked up one by one
SMALL_GAP_NS = 50_000
SMALL_GAP = "(gaps under 50 us)"


@dataclass
class Trace:
    # chip index -> [(name, start_ns, dur_ns)] of executed operations
    device_ops: dict = field(default_factory=dict)
    # chip index -> [(name, start_ns, dur_ns)] of executed programs
    device_modules: dict = field(default_factory=dict)
    # the benchmark's host spans: [(name without prefix, start_ns, dur_ns)]
    host_spans: list = field(default_factory=list)
    # what the file held, for the diagnostics line
    lines_seen: dict = field(default_factory=dict)


def read_xplane(trace_dir):
    """Newest `*.xplane.pb` under `trace_dir` -> Trace, None if there is none."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = None
            if m and line.name in (OP_LINE, MODULE_LINE):
                events = [(e.name, int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                dst = (tr.device_ops if line.name == OP_LINE
                       else tr.device_modules)
                dst.setdefault(int(m.group(1)), []).extend(events)
            elif not m:
                spans = [(e.name[len(PREFIX):], int(e.start_ns),
                          int(e.duration_ns))
                         for e in line.events if e.name.startswith(PREFIX)]
                tr.host_spans.extend(spans)
            n = len(events) if events is not None else sum(1 for _ in line.events)
            tr.lines_seen[f"{plane.name}|{line.name}"] = n
    return tr


# ---------------------------------------------------------------------------
# arithmetic on plain event lists
# ---------------------------------------------------------------------------


def union_ns(events):
    """Total length of the union of the events' intervals."""
    total, end = 0, None
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def self_times(events):
    """[(name, self_ns)]: each event's duration minus the part its nested
    children cover (events on one line nest, they never cross)."""
    out = []
    stack = []  # [name, end_ns, self_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    while stack:
        top = stack.pop()
        out.append((top[0], top[2]))
    return out


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the chips that ran
    any.  Programs' intervals stand in where the file has no op line."""
    per_chip = trace.device_ops or trace.device_modules
    if not per_chip:
        return None
    return sum(union_ns(ev) for ev in per_chip.values()) / len(per_chip) / 1e9


def op_name(text):
    """An event of the op line is named by its whole HLO instruction
    (`%name = type op(operands), attributes`); the operation's own name is
    what stands before ` = `.  Matching a pattern against the whole text
    would also hit every operation that merely consumes a match."""
    return text.split(" = ", 1)[0].lstrip("%")


def short_name(text, limit=120):
    """`name: what follows the result type`, cut to `limit` characters."""
    name, _, rest = text.partition(" = ")
    return (name.lstrip("%") + (": " + rest if rest else ""))[:limit]


def time_of_ops_matching(trace, pattern):
    """Self time (s) of operations whose own name matches, averaged over
    chips; None when nothing matches (the metric is then left out)."""
    rx = re.compile(pattern)
    if not trace.device_ops:
        return None
    tot, hits = 0, 0
    for ev in trace.device_ops.values():
        for name, ns in self_times(ev):
            if rx.search(op_name(name)):
                tot += ns
                hits += 1
    return tot / len(trace.device_ops) / 1e9 if hits else None


def matched_ops(trace, pattern, k=6):
    """[[short name, seconds]] of what a pattern matches on chip 0, for the
    diagnostics line: a reader's pattern is checked by eye once."""
    rx = re.compile(pattern)
    acc = {}
    if trace.device_ops:
        for name, ns in self_times(trace.device_ops[min(trace.device_ops)]):
            if rx.search(op_name(name)):
                acc[short_name(name, 160)] = acc.get(short_name(name, 160), 0) + ns
    return [[n, ns / 1e9] for n, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def top_device_ops(trace, k=10):
    """[[short name, seconds]] by self time, chip 0."""
    if not trace.device_ops:
        return []
    chip = min(trace.device_ops)
    acc = {}
    for name, ns in self_times(trace.device_ops[chip]):
        name = short_name(name)
        acc[name] = acc.get(name, 0) + ns
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


def idle_gaps(trace, k=10):
    """Idle time on chip 0 by what the host was doing: each gap between
    device intervals is given to the innermost benchmark span that covers
    its midpoint (`(no span)` otherwise); -> [[span, seconds]] top k."""
    per_chip = trace.device_ops or trace.device_modules
    if not per_chip:
        return []
    ev = sorted(per_chip[min(per_chip)], key=lambda e: e[1])
    gaps, end = [], None
    for _, s, d in ev:
        if end is not None and s > end:
            gaps.append((end, s))
        end = max(end or 0, s + d)
    acc = {}
    spans = sorted(trace.host_spans, key=lambda e: e[2])  # innermost first
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            acc[SMALL_GAP] = acc.get(SMALL_GAP, 0) + (b - a)
            continue
        mid = (a + b) // 2
        name = next((n for n, s, d in spans if s <= mid < s + d), "(no span)")
        acc[name] = acc.get(name, 0) + (b - a)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


def alignment_ms(trace, window_span="window"):
    """How far the first device event lies from the start of the benchmark's
    window span on the trace's clock (ms); a large number means host and
    device clocks are not aligned and gap attribution is not to be trusted."""
    per_chip = trace.device_ops or trace.device_modules
    win = [e for e in trace.host_spans if e[0] == window_span]
    if not per_chip or not win:
        return None
    first = min(e[1] for ev in per_chip.values() for e in ev)
    return (first - win[0][1]) / 1e6
