import pytest

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.spans import Spans

US = 1000


def hand_trace():
    """Chip 0: a `while` of 100 us holding two ops, a gap, a lone kernel."""
    ops = [
        ("%while.1 = (s32[]) while(...)", 0, 100 * US),
        ("%fusion.1 = bf16[8] fusion(%p)", 10 * US, 30 * US),
        ("%splash_fwd.3 = bf16[8] custom-call(%q)", 50 * US, 40 * US),
        ("%fusion.9 = bf16[8] fusion(%splash_fwd.3)", 200 * US, 50 * US),
    ]
    spans = [("window", 0, 400 * US), ("engine_step", 90 * US, 120 * US)]
    return tr.Trace(device_ops={0: ops}, host_spans=spans)


def test_busy_is_the_union_of_nested_and_disjoint_intervals():
    t = hand_trace()
    assert tr.union_ns(t.device_ops[0]) == 150 * US
    assert tr.busy_s(t) == pytest.approx(150e-6)


def test_self_time_subtracts_children():
    selfs = dict(tr.self_times(hand_trace().device_ops[0]))
    assert selfs["%while.1 = (s32[]) while(...)"] == 30 * US
    assert selfs["%fusion.1 = bf16[8] fusion(%p)"] == 30 * US


def test_ops_matching_uses_the_operation_s_own_name():
    t = hand_trace()
    # fusion.9 consumes %splash_fwd.3 but is not a splash kernel
    assert tr.time_of_ops_matching(t, "splash") == pytest.approx(40e-6)
    assert tr.time_of_ops_matching(t, "no_such_kernel") is None
    assert tr.op_name("%a.1 = f32[] add(%b, %c)") == "a.1"


def test_gap_goes_to_the_innermost_span_covering_it():
    gaps = dict(tr.idle_gaps(hand_trace()))
    # the one gap, 100..200 us, has its midpoint inside engine_step
    assert gaps == {"engine_step": pytest.approx(100e-6)}


def test_small_gaps_are_lumped():
    t = tr.Trace(device_ops={0: [("a", 0, 10 * US), ("b", 12 * US, 10 * US)]})
    assert dict(tr.idle_gaps(t)) == {tr.SMALL_GAP: pytest.approx(2e-6)}


def _gap_trace(program_spans, host_spans=(), gap=(100, 300)):
    """Two operations with ONE gap between them (us), and spans around it."""
    a, b = gap
    return tr.Trace(
        device_ops={0: [("%a = f32[] a()", 0, a * US),
                        ("%b = f32[] b()", b * US, 10 * US)]},
        host_spans=[(n, s * US, d * US) for n, s, d in host_spans],
        program_spans={line: [(n, s * US, d * US) for n, s, d in spans]
                       for line, spans in program_spans.items()})


@pytest.mark.parametrize("program_spans, host_spans, gap, want", [
    # nested spans on one thread: the innermost that covers the midpoint,
    # the program's name with its prefix, before the benchmark's span
    ({"main/1": [("areal/step_fetch", 50, 400), ("areal/fetch_inner", 150, 100)]},
     [("window", 0, 1000), ("engine_step", 40, 500)], (100, 300),
     {"areal/fetch_inner": 200e-6}),
    # two threads overlap over the gap: the thread of the engine's step
    # phases is asked first, whatever the other thread's span or name
    ({"a-trainer/9": [("areal/update", 190, 20)],
      "worker/2": [("areal/step_admit", 0, 90), ("areal/step_fetch", 90, 400)]},
     [("engine_step", 0, 600)], (100, 300), {"areal/step_fetch": 200e-6}),
    # ... and the other thread is asked where the engine's covers nothing
    ({"a-trainer/9": [("areal/update", 150, 100)],
      "worker/2": [("areal/step_admit", 0, 90)]},
     [("engine_step", 0, 600)], (100, 300), {"areal/update": 200e-6}),
    # no span of the program: the benchmark's, bare, as before
    ({}, [("window", 0, 1000), ("engine_step", 40, 500)], (100, 300),
     {"engine_step": 200e-6}),
    # a gap under 50 us is lumped, whoever covers it
    ({"worker/2": [("areal/step_fetch", 0, 400)]}, [("window", 0, 1000)],
     (100, 140), {tr.SMALL_GAP: 40e-6}),
    # a gap no span covers
    ({"worker/2": [("areal/step_fetch", 0, 120)]}, [("engine_step", 0, 150)],
     (100, 300), {tr.NO_SPAN: 200e-6}),
])
def test_a_gap_names_what_the_program_was_doing(program_spans, host_spans,
                                                gap, want):
    got = dict(tr.idle_gaps(_gap_trace(program_spans, host_spans, gap)))
    assert got == {k: pytest.approx(v) for k, v in want.items()}


def test_the_breakdown_is_no_metric():
    """`host_span_total` and the readers do not see the program's spans."""
    t = _gap_trace({"worker/2": [("areal/step_fetch", 0, 400)]},
                   [("engine_step", 0, 600)])
    assert [n for n, _, _ in t.host_spans] == ["engine_step"]
    assert tr.busy_s(t) == pytest.approx(110e-6)


def test_events_are_sorted_and_their_self_times_taken_once(monkeypatch):
    """Every reader asks the same events: one sort and one walk a trace,
    and a pattern is tried once a distinct name."""
    t = hand_trace()
    t.device_ops[0] = t.device_ops[0] * 1  # a list of its own
    calls = {"sort": 0, "walk": 0}
    by_start, walk = tr.by_start, tr._self_events
    monkeypatch.setattr(tr, "by_start", lambda ev: (
        calls.__setitem__("sort", calls["sort"] + 1), by_start(ev))[1])
    monkeypatch.setattr(tr, "_self_events", lambda ev: (
        calls.__setitem__("walk", calls["walk"] + 1), walk(ev))[1])
    first = (tr.busy_s(t), tr.top_device_ops(t), tr.idle_gaps(t),
             tr.time_of_ops_matching(t, "splash"), tr.matched_ops(t, "fusion"))
    again = (tr.busy_s(t), tr.top_device_ops(t), tr.idle_gaps(t),
             tr.time_of_ops_matching(t, "splash"), tr.matched_ops(t, "fusion"))
    assert first == again and calls == {"sort": 1, "walk": 1}
    # the totals by name are what the walk over events gives
    by_name = {}
    for name, ns in tr.self_times(hand_trace().device_ops[0]):
        by_name[name] = by_name.get(name, 0) + ns
    assert tr.self_by_name(t, 0) == by_name


def test_top_ops_and_modules_fallback():
    t = hand_trace()
    top = tr.top_device_ops(t, k=2)
    assert top[0][0].startswith("fusion.9") and top[0][1] == pytest.approx(50e-6)
    only_modules = tr.Trace(device_modules={0: [("jit_step", 0, 5 * US)]})
    assert tr.busy_s(only_modules) == pytest.approx(5e-6)
    assert tr.busy_s(tr.Trace()) is None


def test_readers_on_the_hand_trace():
    from benchmarks.lib import loader

    spans = Spans()
    spans.total_s["prepare_batch"] = 2.0
    spans.values["publish_pause_s"] = [0.002, 0.004]
    ctx = {"trace": hand_trace(), "window_s": 400e-6,
           "counts": {"steps": 2, "dispatches": 0}, "spans": spans,
           "counters": {"shared_tokens": 70, "reused_tokens": 10,
                        "prefill_tokens": 15, "suffix_tokens": 5},
           "work": {"attention_flops": 197e12 * 4e-6},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "compiles": {"lowered": 3}}
    read = lambda name, **spec: loader.load_reader(name)(ctx, spec)  # noqa: E731
    assert read("device_busy", per="steps") == pytest.approx(0.075)
    assert read("wall_minus_device_busy", per="steps") == pytest.approx(0.125)
    assert read("wall_minus_device_busy", per="dispatches") is None
    assert read("device_time_of_ops_matching", ops="splash") == pytest.approx(0.04)
    assert read("roofline_share", ops="splash", work="attention_flops",
                bound="flops") == pytest.approx(10.0)
    assert read("host_span_total", span="prepare_batch", per="steps") == 1000.0
    assert read("host_span_total", span="absent") is None
    assert read("value_mean", value="publish_pause_s") == pytest.approx(3.0)
    assert read("engine_counter_ratio", numerator=["shared_tokens", "reused_tokens"],
                denominator=["shared_tokens", "reused_tokens", "prefill_tokens",
                             "suffix_tokens"]) == pytest.approx(80.0)
    assert read("window_compiles") == 3.0


def test_recorded_cpu_trace_round_trip(tmp_path):
    """A real (tiny) profile written by this JAX: the benchmark's spans are
    found on the host plane; a CPU has no device plane, so busy is None."""
    import jax
    import jax.numpy as jnp

    spans = Spans()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("window"):
        with spans.span("engine_step"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = tr.read_xplane(str(tmp_path))
    assert {n for n, _, _ in t.host_spans} == {"window", "engine_step"}
    assert t.program_spans == {}
    assert tr.busy_s(t) is None and tr.idle_gaps(t) == []
    assert tr.read_xplane(str(tmp_path / "nothing_here")) is None
