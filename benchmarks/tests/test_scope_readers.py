"""CPU tests of the readers PR 24 added, on hand-built traces."""

import json

import pytest

from benchmarks.lib import loader
from benchmarks.lib import trace_reduce as tr
from benchmarks.readers import device_time_in_scope as dts

US = 1000
ANY = "(^|[/(])(layers|sampler|loss)([)/]|$)"


def ctx_of(trace, **kw):
    return {"trace": trace, "counts": {"steps": 2, "output_tokens": 10},
            "counters": {}, "peaks": {"hbm_bytes_per_s": 819e9},
            "window_s": 1.0, **kw}


def spec(reader, **kw):
    return {"name": "m", "reader": reader, **kw}


def two_program_trace():
    """Chip 0 runs program A then program B; both hold a `fusion.5`, under
    other scopes.  A's `while.1` nests its fusions.  Chip 1 runs A only."""
    a_ops = [
        ("%while.1 = (s32[]) while(%t), body=%b", 0, 100 * US),
        ("%fusion.5 = bf16[8] fusion(%p), kind=kLoop", 10 * US, 30 * US),
        ("%fusion.7 = bf16[8] fusion(%fusion.5), kind=kLoop", 50 * US, 40 * US),
    ]
    b_ops = [
        ("%fusion.5 = f32[4] fusion(%q), kind=kLoop", 200 * US, 50 * US),
        ("%sort.9 = f32[4] sort(%fusion.5)", 250 * US, 20 * US),
    ]
    trace = tr.Trace(
        device_ops={0: a_ops + b_ops, 1: list(a_ops)},
        device_modules={
            0: [("jit_a(111)", 0, 100 * US), ("jit_b(222)", 200 * US, 80 * US)],
            1: [("jit_a(111)", 0, 100 * US)],
        },
    )
    programs = {
        "jit_a": [{"while.1": "jit(a)/layers/while", "fusion.5":
                   "jit(a)/layers/while/body/mlp/dot_general", "fusion.7":
                   "jit(a)/transpose(jvp(layers))/while/body/mlp/dot_general",
                   "copy.1": ""}],
        "jit_b": [{"fusion.5": "jit(b)/sampler/mul", "sort.9":
                   "jit(b)/sampler/top_k"}],
    }
    return trace, programs


def test_scope_time_by_program_nested_while_and_two_chips():
    trace, programs = two_program_trace()
    read = loader.load_reader("device_time_in_scope")
    ctx = ctx_of(trace, programs=programs)
    # mlp: fusion.5 of program A (30) + fusion.7 (40) on each chip; the
    # fusion.5 of program B is the sampler's
    v = read(ctx, spec("device_time_in_scope", scope="/mlp/", per="steps"))
    assert v == pytest.approx(70e-6 * 1000 / 2)
    fwd = read(ctx, spec("device_time_in_scope", scope="/mlp/",
                         not_scope=r"transpose\(", scale=1e6))
    assert fwd == pytest.approx(30.0)
    # the while's own self time (100 - 30 - 40) is under `layers`
    lay = read(ctx, spec("device_time_in_scope", scope="(^|[/(])layers[)/]",
                         scale=1e6))
    assert lay == pytest.approx(100.0)
    # sampler: only chip 0 ran it, and the time is averaged over chips
    smp = read(ctx, spec("device_time_in_scope", scope="/sampler/", scale=1e6))
    assert smp == pytest.approx(70.0 / 2)
    assert read(ctx, spec("device_time_in_scope", scope="/kv_write/")) is None
    # per one of the program's counters; nothing without the counter
    per_pass = spec("device_time_in_scope", scope="/sampler/",
                    per_counter="decode_passes")
    assert read(dict(ctx, counters={"decode_passes": 7}),
                per_pass) == pytest.approx(0.035 / 7)
    assert read(ctx, per_pass) is None


def test_scope_from_the_event_s_own_metadata_needs_no_program():
    ops = [
        ('%fusion.1 = bf16[8] fusion(%p), metadata={op_name="jit(f)/sampler/'
         'top_k" source_file="x.py"}', 0, 10 * US),
        ("%copy.2 = bf16[8] copy(%p)", 20 * US, 5 * US),
    ]
    trace = tr.Trace(device_ops={0: ops},
                     device_modules={0: [("jit_f(1)", 0, 30 * US)]})
    read = loader.load_reader("device_time_in_scope")
    ctx = ctx_of(trace, programs={"jit_f": [{"copy.2": ""}]})
    assert read(ctx, spec("device_time_in_scope", scope="/sampler/",
                          scale=1e6)) == pytest.approx(10.0)
    # an instruction without metadata is unscoped, not unresolved
    assert read(ctx, spec("device_time_in_scope", scope="", not_scope=ANY,
                          requires=ANY, scale=1e6)) == pytest.approx(5.0)


def test_what_cannot_be_named_exactly_is_unresolved_never_guessed():
    trace, programs = two_program_trace()
    # two executables named jit_a that both hold every instruction seen and
    # disagree on fusion.5; and no executable at all for jit_b
    programs = {"jit_a": programs["jit_a"] + [
        dict(programs["jit_a"][0], **{"fusion.5": "jit(a)/embed/gather"})]}
    times = dts.scope_times(trace, programs)
    paths = {}
    for p, ns, _, _ in times[0]:
        paths[p] = paths.get(p, 0) + ns
    assert paths[dts.UNRESOLVED] == (30 + 50 + 20) * US
    assert "jit(a)/embed/gather" not in paths
    # an executable that lacks an instruction seen under the program is not
    # that program: the one left decides alone
    programs["jit_a"][1].pop("fusion.7")
    times = dts.scope_times(trace, programs)
    # a row: path, self time, instruction, the events summed into it
    assert ("jit(a)/layers/while/body/mlp/dot_general", 30 * US,
            "fusion.5", 1) in times[0]


def test_unscoped_is_left_out_for_a_program_without_scopes():
    trace, programs = two_program_trace()
    bare = {k: [{i: "jit(x)/mul" for i in m} for m in v]
            for k, v in programs.items()}
    read = loader.load_reader("device_time_in_scope")
    s = spec("device_time_in_scope", scope="", not_scope=ANY, requires=ANY)
    assert read(ctx_of(trace, programs=bare), s) is None
    assert read(ctx_of(trace, programs=programs), s) is None  # all scoped
    assert read(ctx_of(None), s) is None
    assert read(ctx_of(tr.Trace()), s) is None


def test_instruction_scopes_parses_optimised_hlo_text():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "%fused (p: f32[4]) -> f32[4] {",
        '  ROOT %mul.1 = f32[4] multiply(%p, %p), metadata={op_name="jit(f)/'
        'loss/mul" stack_frame_id=3}',
        "}",
        "%body.7 (t: (s32[], f32[4])) -> (s32[], f32[4]) {",
        "  %copy-done.2 = f32[4] copy-done(%cs)",
        '  ROOT %fusion.9 = f32[4] fusion(%copy-done.2), kind=kLoop, '
        'calls=%fused, metadata={op_name="jit(f)/layers/while/body/mlp/mul"}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %copy.3 = f32[4] copy(%a)",
        # a Pallas kernel's attributes hold line breaks before the metadata
        "  %splash_fwd.1 = f32[4] custom-call(%copy.3), frontend_attributes="
        "{kernel_metadata={",
        '"xprof_metadata":"{\\"block_q\\": 512}"',
        '}}, metadata={op_name="jit(f)/jvp(layers)/attn/splash_fwd"}',
        "  %while.4 = (s32[], f32[4]) while(%t), condition=%cond.6, "
        'body=%body.7, metadata={op_name="jit(f)/layers/while"}',
        '  ROOT %fusion.2 = f32[4] fusion(%copy.3), kind=kLoop, calls=%fused,'
        ' metadata={op_name="jit(f)/loss/mul"}',
        "}",
    ])
    got = dts.instruction_scopes(text)
    assert got == {
        "mul.1": "jit(f)/loss/mul",
        # the compiler's own copy: in the entry computation it has no path,
        # in the loop's body it is under the loop
        "copy.3": "",
        "copy-done.2": "jit(f)/layers/while/(unnamed)",
        "fusion.9": "jit(f)/layers/while/body/mlp/mul",
        "splash_fwd.1": "jit(f)/jvp(layers)/attn/splash_fwd",
        "while.4": "jit(f)/layers/while",
        "fusion.2": "jit(f)/loss/mul",
    }


def test_modules_matching_seconds_and_share_of_busy():
    trace, _ = two_program_trace()
    read = loader.load_reader("device_time_of_modules_matching")
    s = spec("device_time_of_modules_matching", modules=r"^jit_b\(")
    # 80 us on chip 0, none on chip 1
    assert read(ctx_of(trace), dict(s, scale=1e6)) == pytest.approx(40.0)
    busy = tr.busy_s(trace)
    assert read(ctx_of(trace), dict(s, share_of="busy")) == pytest.approx(
        100.0 * 40e-6 / busy)
    assert read(ctx_of(trace), dict(s, modules="^jit_none")) == 0.0
    assert read(ctx_of(tr.Trace()), s) is None
    assert read(ctx_of(None), s) is None


def test_counter_per_counter_and_per_count():
    read = loader.load_reader("counter_per")
    ctx = ctx_of(None, counters={"t_step_fetch_s": 1.5, "engine_steps": 3})
    assert read(ctx, spec("counter_per", counter="t_step_fetch_s",
                          per_counter="engine_steps")) == pytest.approx(500.0)
    assert read(ctx, spec("counter_per", counter="t_step_fetch_s",
                          per="steps", scale=1)) == pytest.approx(0.75)
    assert read(ctx, spec("counter_per", counter="t_step_admit_s",
                          per_counter="engine_steps")) is None
    assert read(ctx, spec("counter_per", counter="t_step_fetch_s",
                          per_counter="admitted")) is None


def test_weight_read_roofline_errs_low_and_needs_its_counter():
    from benchmarks.lib import flops

    trace, _ = two_program_trace()
    read = loader.load_reader("weight_read_roofline")
    s = spec("weight_read_roofline", modules=r"^jit_a\(", counter="decode_passes",
             config="qwen2.5-1.5b")
    n = flops.dense_param_count(loader.load_config("qwen2.5-1.5b"))
    ctx = ctx_of(trace, counters={"decode_passes": 4})
    want = 100.0 * (4 * 2 * n / 819e9) / 100e-6
    assert read(ctx, s) == pytest.approx(want)
    assert read(ctx_of(trace), s) is None  # an earlier commit: no counter
    assert read(dict(ctx, peaks=None), s) is None  # the CPU rehearsal
    assert read(ctx, dict(s, modules="^jit_none")) is None


def test_every_new_metric_file_is_declared_and_loads():
    bench = json.load(open(loader.BENCH_ROOT + "/../BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for cell in ("train_2k", "rollout_decode", "grpo_async_loop"):
        for m in loader.load_layer_metrics(cell):
            d = declared[m["name"]]
            # both list the cells, or neither does (every cell of `moves`)
            assert d.get("workloads") == m.get("cells") and d["unit"] == m["unit"]
            assert d["moves"] == m["moves"] and d["layer"] == m["layer"]
            assert d["source"] == m["source"]
