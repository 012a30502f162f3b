"""Self time of the device operations whose SCOPE PATH matches `scope` (a
regular expression) and does not match `not_scope` (optional), averaged over
chips; `per`, `scale` as the other readers, or `per_counter`: one of the
program's own counters as the divisor (`decode_passes`: where a cell's
window holds a few steps of uneven size, device time per forward pass of
the decode path repeats and device time per step does not).

The scope path of an operation is the `op_name` of its HLO instruction: the
`jax.named_scope` names it was written under, as JAX wraps them
(`jit(train_step)/transpose(jvp(layers))/while/body/checkpoint/mlp/dot_general`).
Where it comes from, in this order:

(a) the event's own text, if it holds `metadata={op_name="..."`.  A TPU
    trace of JAX 0.9 does not: an `XLA Ops` event is named by its HLO
    instruction WITHOUT the metadata (PERF.md, Findings of PR 24).
(b) the compiled programs themselves.  Every executable this process
    compiled is kept (`kept_executables.py`, a `jax.monitoring` listener
    that importing this file registers, before the cell runs and in an
    untraced run too), its optimised HLO text gives `{instruction name:
    op_name}`, and each operation event is given to the `XLA Modules` event
    that covers it in time.  Several executables
    share one module name (`jit__decode_chunk`, one per key-window bucket):
    a program of the trace, known by its module event's name and id, is
    matched to the executables of that name that hold EVERY instruction
    name seen under it, and an instruction counts only if all of them give
    it the same `op_name`.  What cannot be named exactly goes under the
    path `(unresolved)`, which matches no scope; it is never guessed.

`requires` (optional regex): the metric is left out unless some operation's
path matches it; the `*_unscoped_*` metrics name the vocabulary there, so
that a program without scopes reports nothing instead of everything.

A metric file's regular expressions are written against real paths; the
first call prints what each metric matched on a diagnostics line.
"""

import bisect
import json
import re

from benchmarks.lib import trace_reduce
from benchmarks.lib.readerlib import scaled
from benchmarks.readers import kept_executables

OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
UNRESOLVED = "(unresolved)"

INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = ")
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
# computations an instruction runs: a loop's body and condition, a call's
# or a conditional's targets (a fusion's `calls=` too: harmless)
CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
UNNAMED = "/(unnamed)"


def instruction_scopes(hlo_text):
    """{instruction name: op_name} of one program's optimised HLO text.

    An instruction may run over several lines (a Pallas kernel's attributes
    hold line breaks before its `metadata`).  An instruction without an
    `op_name` (the compiler's own layout copies, async copy starts and
    waits) takes the path of the control-flow instruction whose computation
    it runs in, plus `/(unnamed)`: a copy inside the layer scan's loop body
    is under `.../layers/while/(unnamed)`, one in the entry computation
    stays without a path."""
    scopes, where, owner = {}, {}, {}
    comp, name, body = None, None, []

    def close():
        if name is None:
            return
        text = " ".join(body)
        m = OP_NAME.search(text)
        scopes[name] = m.group(1) if m else ""
        where[name] = comp
        for one, several in CALLED.findall(text):
            for c in [one] if one else re.findall(r"[\w.\-]+", several):
                owner.setdefault(c, name)

    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            close()
            name, body = m.group(1), [line]
            continue
        c = COMPUTATION.match(line)
        if c or line == "}":
            close()
            name, body = None, []
            comp = c.group(1) if c else None
        elif name is not None:
            body.append(line)
    close()

    def inherited(instr, depth=0):
        own = scopes[instr]
        if own or depth > 32:
            return own
        up = owner.get(where[instr])
        return inherited(up, depth + 1) if up in scopes else ""

    out = {}
    for instr, own in scopes.items():
        if not own:
            up = inherited(instr)
            own = up + UNNAMED if up else ""
        out[instr] = own
    return out


def kept_programs(module_names):
    """{module name: [{instruction: op_name}]} of the kept executables whose
    module name is among `module_names` (only those are parsed)."""
    return {name: [instruction_scopes(t) for t in texts]
            for name, texts in kept_executables.hlo_texts(module_names).items()}


def self_events(events):
    """[(name, start_ns, self_ns)]: `trace_reduce.self_times` with each
    event's start kept, to place it under a program."""
    out, stack = [], []  # stack: [name, start, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= d
        stack.append([name, s, s + d, d])
    out.extend((t[0], t[1], t[3]) for t in stack)
    return out


def module_base(event_name):
    """`jit__decode_chunk(1578...)` -> `jit__decode_chunk`."""
    return event_name.rsplit("(", 1)[0] if event_name.endswith(")") else event_name


def scope_times(trace, programs=None):
    """{chip: [(scope path, self_ns, instruction name)]} for every operation
    event of the trace; `programs` is `kept_programs`' shape (a test hands
    it in)."""
    placed = {}  # chip -> [(program event name | None, own name | path, ns)]
    for chip, events in trace.device_ops.items():
        mods = sorted(trace.device_modules.get(chip, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        rows = []
        for text, start, self_ns in self_events(events):
            m = OP_NAME.search(text)
            own = trace_reduce.op_name(text)
            if m:  # (a): the event names its own scope path
                rows.append((None, m.group(1), self_ns, own))
                continue
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < mods[i][1] + mods[i][2]
            # an operation outside every program event cannot be named
            rows.append((mods[i][0], own, self_ns, own) if inside
                        else (None, UNRESOLVED, self_ns, own))
        placed[chip] = rows
    seen = {}  # program event name -> instruction names seen under it
    for rows in placed.values():
        for prog, name, _, _ in rows:
            if prog is not None:
                seen.setdefault(prog, set()).add(name)
    if seen and programs is None:
        programs = kept_programs({module_base(p) for p in seen})
    fits = {
        prog: [m for m in (programs or {}).get(module_base(prog), [])
               if names <= m.keys()]
        for prog, names in seen.items()
    }

    def path(prog, name):
        if prog is None:
            return name
        paths = {m[name] for m in fits[prog]}
        return paths.pop() if len(paths) == 1 else UNRESOLVED

    return {chip: [(path(prog, name), ns, own) for prog, name, ns, own in rows]
            for chip, rows in placed.items()}


def read(ctx, spec):
    trace = ctx["trace"]
    if trace is None or not trace.device_ops:
        return None
    if "_scope_times" not in ctx:
        ctx["_scope_times"] = scope_times(trace, ctx.get("programs"))
    times = ctx["_scope_times"]
    want = re.compile(spec["scope"])
    unwanted = re.compile(spec["not_scope"]) if spec.get("not_scope") else None
    requires = re.compile(spec["requires"]) if spec.get("requires") else None
    total, hits, met, matched = 0, 0, requires is None, {}
    for chip, rows in times.items():
        for path, ns, own in rows:
            if not met and requires.search(path):
                met = True
            if want.search(path) and not (unwanted and unwanted.search(path)):
                total += ns
                hits += 1
                if chip == min(times):
                    # by scope; what has no op_name, by its own name
                    key = (path.rsplit("/", 1)[0][-100:] if path else
                           "(no op_name) " + re.sub(r"[.\d]+$", "", own))
                    matched[key] = matched.get(key, 0) + ns
    top = sorted(matched.items(), key=lambda kv: -kv[1])[:8]
    print(json.dumps({"diag": {
        "phase": "scope_metric", "metric": spec["name"], "events": hits,
        "matched": [[k, ns / 1e9] for k, ns in top],
        "unresolved_s": sum(ns for p, ns, _ in times[min(times)]
                            if p == UNRESOLVED) / 1e9}}), flush=True)
    if not hits or not met:
        return None
    took_s = total / len(times) / 1e9
    if "per_counter" not in spec:
        return scaled(took_s, ctx, spec)
    n = (ctx["counters"] or {}).get(spec["per_counter"])
    return took_s * float(spec.get("scale", 1000.0)) / n if n else None
