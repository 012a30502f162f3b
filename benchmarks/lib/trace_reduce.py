"""From a profiler trace to numbers: one generic reduction.

`read_xplane(dir)` turns the newest `.xplane.pb` under a trace directory
into a plain `Trace` (lists of `(name, start_ns, duration_ns)` tuples), and
everything else here works on that plain structure, so that the arithmetic
can be checked on a hand-built event list.  A traced window of 40 s holds
millions of operation events behind a few thousand distinct names, and
every reader asks about the same events: they are sorted and their self
times taken ONCE a trace (`self_events`, kept on the `Trace`), and what asks
by name (`time_of_ops_matching`, `top_device_ops`, `matched_ops`) works on
the totals by distinct name (`self_by_name`), so a pattern is tried once a
name, not once an event (PR 55).

What is what in a TPU trace (JAX 0.9, TPU v5 lite): a plane per chip named
`/device:TPU:<n>`; in it a line `XLA Ops` with one event per executed HLO
operation (control flow such as `while` spans the operations inside it, so
events nest), a line `XLA Modules` with one event per executed program, and
`Steps`.  Host threads are lines of the plane `/host:CPU`; the benchmark's
own spans appear there under the names `bench/<span>`, the program's
(`utils/telemetry.py span`) under `areal/<span>`.
"""

import glob
import os
import re
from dataclasses import dataclass, field

from benchmarks.lib.spans import PREFIX

# the program's own host spans (`areal_tpu/utils/telemetry.py span`)
PROGRAM_PREFIX = "areal/"
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
    # the program's host spans, by the thread (line) each lay on:
    # {line name: [(name WITH its `areal/` prefix, start_ns, dur_ns)]}
    program_spans: dict = field(default_factory=dict)
    # what the file held, for the diagnostics line
    lines_seen: dict = field(default_factory=dict)
    # what was worked out once for every reader (`_once`)
    kept: dict = field(default_factory=dict, repr=False, compare=False)


def read_xplane(trace_dir):
    """Newest `*.xplane.pb` under `trace_dir` -> Trace, None if there is none."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace()
    # one string a distinct name: an operation's name is its whole HLO
    # instruction, and millions of events share a few thousand of them
    names = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            n = 0
            if m and line.name in (OP_LINE, MODULE_LINE):
                events = [(names.setdefault(name := e.name, name),
                           int(e.start_ns), int(e.duration_ns))
                          for e in line.events]
                n = len(events)
                dst = (tr.device_ops if line.name == OP_LINE
                       else tr.device_modules)
                dst.setdefault(int(m.group(1)), []).extend(events)
            elif m:
                n = sum(1 for _ in line.events)
            else:
                for e in line.events:
                    n += 1
                    name = e.name
                    if name.startswith(PREFIX):
                        tr.host_spans.append((name[len(PREFIX):],
                                              int(e.start_ns),
                                              int(e.duration_ns)))
                    elif name.startswith(PROGRAM_PREFIX):
                        tr.program_spans.setdefault(line.name, []).append(
                            (name, int(e.start_ns), int(e.duration_ns)))
            tr.lines_seen[f"{plane.name}|{line.name}"] = n
    return tr


# ---------------------------------------------------------------------------
# arithmetic on plain event lists
# ---------------------------------------------------------------------------


def _once(trace, key, make):
    """`make()` the first time `key` is asked of this trace, kept after."""
    if key not in trace.kept:
        trace.kept[key] = make()
    return trace.kept[key]


def by_start(events):
    """The events by start, an enclosing one before what it encloses: the
    order every walk below takes them in."""
    return sorted(events, key=lambda e: (e[1], -e[2]))


def ops_by_start(trace, chip):
    """`by_start` of a chip's operations (programs where the file has no op
    line), sorted once a trace."""
    per_chip = trace.device_ops or trace.device_modules
    return _once(trace, ("by_start", chip), lambda: by_start(per_chip[chip]))


def union_ns(events, in_order=False):
    """Total length of the union of the events' intervals (`in_order`: the
    events come sorted by start)."""
    total, end = 0, None
    for _, s, d in events if in_order else sorted(events, key=lambda e: e[1]):
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
    return [(name, ns) for name, _, ns in _self_events(by_start(events))]


def _self_events(ordered):
    """[(name, start_ns, self_ns)] of events that come `by_start`, each
    when the walk leaves it."""
    out = []
    stack = []  # [name, start_ns, end_ns, self_ns]
    for name, s, d in ordered:
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= d
        stack.append([name, s, s + d, d])
    while stack:
        top = stack.pop()
        out.append((top[0], top[1], top[3]))
    return out


def self_events(trace, chip):
    """[(name, start_ns, self_ns)] of a chip's operations, once a trace."""
    return _once(trace, ("self_events", chip),
                 lambda: _self_events(ops_by_start(trace, chip)))


def self_by_name(trace, chip):
    """{name: self_ns of all its events} of a chip's operations, in the
    order `self_events` first meets each name; once a trace."""
    def total():
        acc = {}
        for name, _, ns in self_events(trace, chip):
            acc[name] = acc.get(name, 0) + ns
        return acc
    return _once(trace, ("self_by_name", chip), total)


def busy_s(trace):
    """Seconds in which an operation ran, averaged over the chips that ran
    any.  Programs' intervals stand in where the file has no op line."""
    per_chip = trace.device_ops or trace.device_modules
    if not per_chip:
        return None
    return _once(trace, "busy_s", lambda: sum(
        union_ns(ops_by_start(trace, chip), in_order=True)
        for chip in per_chip) / len(per_chip) / 1e9)


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
    for chip in trace.device_ops:
        for name, ns in self_by_name(trace, chip).items():
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
        for name, ns in self_by_name(trace, min(trace.device_ops)).items():
            if rx.search(op_name(name)):
                short = short_name(name, 160)
                acc[short] = acc.get(short, 0) + ns
    return [[n, ns / 1e9] for n, ns in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def top_device_ops(trace, k=10):
    """[[short name, seconds]] by self time, chip 0."""
    if not trace.device_ops:
        return []
    acc = {}
    for name, ns in self_by_name(trace, min(trace.device_ops)).items():
        name = short_name(name)
        acc[name] = acc.get(name, 0) + ns
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in top]


# the engine's step phases (`gen/engine.py step`): the thread that holds
# them is asked first what a gap was spent on
STEP_SPAN = PROGRAM_PREFIX + "step_"
NO_SPAN = "(no span)"


def _innermost(spans, at):
    """The name of the shortest span that covers `at`, None without one
    (`spans` come shortest first)."""
    return next((n for n, s, d in spans if s <= at < s + d), None)


def idle_gaps(trace, k=10):
    """Idle time on chip 0 by what the host was doing: each gap between
    device intervals is given to the innermost span that covers its
    midpoint: a span of the PROGRAM (`areal/<name>`, named with its prefix)
    before one of the benchmark (bare name), the program's threads asked
    one by one, the thread of the engine's step phases first; `(no span)`
    otherwise.  -> [[span, seconds]] top k."""
    per_chip = trace.device_ops or trace.device_modules
    if not per_chip:
        return []
    gaps, end = [], None
    for _, s, d in ops_by_start(trace, min(per_chip)):
        if end is not None and s > end:
            gaps.append((end, s))
        end = max(end or 0, s + d)
    acc = {}
    threads = sorted(
        trace.program_spans.items(),
        key=lambda kv: (-sum(n.startswith(STEP_SPAN) for n, _, _ in kv[1]),
                        kv[0]))
    # each thread's spans shortest first, the benchmark's own spans last
    asked = [sorted(spans, key=lambda e: e[2])
             for spans in [s for _, s in threads] + [trace.host_spans]]
    for a, b in gaps:
        if b - a < SMALL_GAP_NS:
            acc[SMALL_GAP] = acc.get(SMALL_GAP, 0) + (b - a)
            continue
        mid = (a + b) // 2
        name = next((n for n in (_innermost(spans, mid) for spans in asked)
                     if n is not None), NO_SPAN)
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
