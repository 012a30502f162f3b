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


def module_base(event_name):
    """`jit__decode_chunk(1578...)` -> `jit__decode_chunk`."""
    return event_name.rsplit("(", 1)[0] if event_name.endswith(")") else event_name


def scope_times(trace, programs=None):
    """{chip: [(scope path, self_ns, instruction name, events)]}: the
    operation events of the trace, those of one instruction under one
    program event summed to a row, rows in the order the walk first met
    them; `programs` is `kept_programs`' shape (a test hands it in)."""
    placed = {}  # chip -> {(program event name | None, event text): [ns, n]}
    for chip in trace.device_ops:
        mods = sorted(trace.device_modules.get(chip, []), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        ends = [m[1] + m[2] for m in mods]
        rows = placed[chip] = {}
        for text, start, self_ns in trace_reduce.self_events(trace, chip):
            i = bisect.bisect_right(starts, start) - 1
            # an operation outside every program event cannot be named
            prog = mods[i][0] if i >= 0 and start < ends[i] else None
            row = rows.get((prog, text))
            if row is None:
                rows[(prog, text)] = [self_ns, 1]
            else:
                row[0] += self_ns
                row[1] += 1
    # (a): an event that names its own scope path needs no program
    own_path = {}
    seen = {}  # program event name -> instruction names seen under it
    for rows in placed.values():
        for prog, text in rows:
            if text not in own_path:
                m = OP_NAME.search(text)
                own_path[text] = (m.group(1) if m else None,
                                  trace_reduce.op_name(text))
            if prog is not None and own_path[text][0] is None:
                seen.setdefault(prog, set()).add(own_path[text][1])
    if seen and programs is None:
        programs = kept_programs({module_base(p) for p in seen})
    fits = {
        prog: [m for m in (programs or {}).get(module_base(prog), [])
               if names <= m.keys()]
        for prog, names in seen.items()
    }

    def path(prog, text):
        stated, own = own_path[text]
        if stated is not None:
            return stated
        if prog is None:
            return UNRESOLVED
        paths = {m[own] for m in fits[prog]}
        return paths.pop() if len(paths) == 1 else UNRESOLVED

    return {chip: [(path(prog, text), ns, own_path[text][1], n)
                   for (prog, text), (ns, n) in rows.items()]
            for chip, rows in placed.items()}


def scope_rows(ctx):
    """`scope_times` of the run's trace, worked out once for every metric."""
    if "_scope_times" not in ctx:
        ctx["_scope_times"] = scope_times(ctx["trace"], ctx.get("programs"))
    return ctx["_scope_times"]


def read(ctx, spec):
    trace = ctx["trace"]
    if trace is None or not trace.device_ops:
        return None
    times = scope_rows(ctx)
    want = re.compile(spec["scope"])
    unwanted = re.compile(spec["not_scope"]) if spec.get("not_scope") else None
    requires = re.compile(spec["requires"]) if spec.get("requires") else None
    total, hits, met, matched = 0, 0, requires is None, {}
    for chip, rows in times.items():
        for path, ns, own, n in rows:
            if not met and requires.search(path):
                met = True
            if want.search(path) and not (unwanted and unwanted.search(path)):
                total += ns
                hits += n
                if chip == min(times):
                    # by scope; what has no op_name, by its own name
                    key = (path.rsplit("/", 1)[0][-100:] if path else
                           "(no op_name) " + re.sub(r"[.\d]+$", "", own))
                    matched[key] = matched.get(key, 0) + ns
    top = sorted(matched.items(), key=lambda kv: -kv[1])[:8]
    print(json.dumps({"diag": {
        "phase": "scope_metric", "metric": spec["name"], "events": hits,
        "matched": [[k, ns / 1e9] for k, ns in top],
        "unresolved_s": sum(ns for p, ns, _, _ in times[min(times)]
                            if p == UNRESOLVED) / 1e9}}), flush=True)
    if not hits or not met:
        return None
    took_s = total / len(times) / 1e9
    if "per_counter" not in spec:
        return scaled(took_s, ctx, spec)
    n = (ctx["counters"] or {}).get(spec["per_counter"])
    return took_s * float(spec.get("scale", 1000.0)) / n if n else None
