"""CPU tests of what PR 49 added to the yardstick: the configuration's
arithmetic from shapes (`lib/swa_work.py` against a hand count, against the
file's `bench.bytes`, and against the program's own `init_params` and
`init_kv_cache`), the reader `swa_bytes_roofline` on a hand-built trace (and
on a program without the counters: nothing, and no raise), the data files
of `rollout_swa_moe_16k`, the kind's two refusals (exit 4 and 5) and its
draw, the cell's CPU rehearsal (whose comparison with the float32 reference
has to be exact there) and its control's (which has to come out not
`correct`)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import loader
from benchmarks.lib import swa_work as sw
from benchmarks.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000
CELL, CONFIG = "rollout_swa_moe_16k", "mimo-v2.5"
NEW_METRICS = (
    "rollout_attn_local_ms_per_token.swa", "rollout_attn_global_ms_per_token.swa",
    "rollout_cache_write_ms_per_token.swa", "rollout_moe_ms_per_token.swa",
    "rollout_moe_experts_ms_per_token.swa", "rollout_ffn_dense_ms_per_token.swa",
    "rollout_experts_touched_pct.swa", "rollout_expert_tokens_per_expert.swa",
    "rollout_live_slots_per_pass.swa", "attn_global_roofline.swa",
    "attn_local_roofline.swa", "moe_roofline.rollout_swa",
    "decode_roofline.rollout_swa",
)

# the toy size of the repo's tests (tests/test_mimo_v2.py)
TOY = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 7, "num_attention_heads": 8,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4, "head_dim": 24,
    "v_head_dim": 16, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "sliding_window": 8,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "n_routed_experts": 4, "experts_held": {"first": 4, "of": 16},
    "num_experts_per_tok": 4, "vocab_size": 256,
    "bench": {"dtype": "bfloat16", "cache_dtype": "bfloat16"},
}


def test_bytes_at_the_toy_size_are_a_hand_count():
    full = 64 * 8 * 24 + 64 * 2 * 40 + 8 * 16 * 64
    sliding = 64 * 8 * 24 + 64 * 4 * 40 + 8 * 16 * 64 + 8
    assert (sw.attention_params(TOY, False), sw.attention_params(TOY, True)) \
        == (full, sliding) == (25_600, 30_728)
    assert sw.kinds(TOY) == (2, 5, 1, 6)
    assert sw.dense_ffn_params(TOY) == 3 * 64 * 128
    assert sw.router_params(TOY) == 64 * 16 + 16
    assert sw.expert_params(TOY) == 3 * 64 * 32
    fixed = 2 * full + 5 * sliding + 3 * 64 * 128 + 6 * 1_040 + 7 * 128
    assert sw.fixed_params(TOY) == fixed
    assert sw.parameters_held(TOY) == fixed + 6 * 4 * 6_144 + 2 * 256 * 64 + 64
    assert (sw.full_values_per_position(TOY), sw.ring_positions(TOY),
            sw.ring_values_per_slot(TOY)) == (80, 8, 8 * 4 * 40)
    assert sw.pool_bytes(TOY, 5, 64) == 5 * 2 * (64 * 2 * 80 + 5 * 1_280)
    assert sw.ring_positions({**TOY, "sliding_window": 11}) == 16
    counters = {"decode_passes": 10, "experts_touched": 30,
                "kv_columns_read": 5_000, "tokens_delivered": 70}
    assert sw.attn_global_bytes(TOY, {}, counters) == 5_000 * 2 * 80 * 2
    assert sw.attn_local_bytes(TOY, {}, counters) == 70 * 5 * 1_280 * 2
    assert sw.moe_bytes(TOY, {}, counters) == (30 * 6_144 + 10 * 6 * 1_040) * 2
    per_pass = (fixed + 64 * 257) * 2
    assert sw.decode_bytes(TOY, {}, counters) == (
        10 * per_pass + 30 * 6_144 * 2 + 1_600_000 + 896_000
        + 70 * (2 * 80 + 5 * 160) * 2)
    assert sw.decode_bytes(TOY, {}, {}) == 0


def test_bytes_of_the_cell_are_the_issue_s_arithmetic():
    hf = loader.load_config(CONFIG)
    b = hf["bench"]["bytes"]
    per = b["per_block_parameters"]
    assert sw.attention_params(hf, False) == per["full_attention"] == 89_128_960
    assert sw.attention_params(hf, True) \
        == per["sliding_attention_with_sinks"] == 94_371_904
    assert sw.dense_ffn_params(hf) == per["dense_ffn"] == 201_326_592
    assert sw.expert_params(hf) == per["one_routed_expert"] == 25_165_824
    assert sw.router_params(hf) == per["router_and_bias"] == 1_048_832
    assert per["layer_0_full_dense"] == 290_463_744
    assert per["sliding_expert_layer_16_held"] == 498_082_112
    assert per["full_expert_layer_16_held"] == 492_839_168
    assert per["embedding_and_head"] == 156_237_824
    assert sw.parameters_held(hf) == b["parameters_held"] == 3_429_955_392
    assert b["weight_bytes_bfloat16"] == 2 * b["parameters_held"]
    assert sw.full_values_per_position(hf) * 2 \
        == b["full_layer_bytes_per_position"] == 2_560
    assert sw.ring_values_per_slot(hf) * 2 \
        == b["sliding_layer_ring_bytes_per_slot"] == 655_360
    assert sw.pool_bytes(hf, 65, 16_384) == b["pool_bytes_65_rows_of_16384"] \
        == 65 * (16_384 * 5_120 + 3_276_800)
    assert b["pool_bytes_without_the_window"] == 65 * 16_384 * 30_720
    # a pass with every held expert touched, before any column is read
    full = sw.decode_bytes(hf, {}, {"decode_passes": 1, "experts_touched": 96})
    assert 6.7e9 < full < 6.8e9


def test_the_shapes_of_the_program_hold_the_file_s_count():
    import jax
    import numpy as np

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import init_kv_cache

    hf = loader.load_config(CONFIG)
    cfg = TransformerConfig.from_hf(hf).replace(
        dtype="bfloat16", param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == sw.parameters_held(hf)
    pool = jax.eval_shape(lambda: init_kv_cache(cfg, 65, 16384, "bfloat16"))
    assert sum(a.size * 2 for a in pool.values()) \
        == hf["bench"]["bytes"]["pool_bytes_65_rows_of_16384"]
    # a sliding layer keeps the window and no `max_seq_len` axis
    assert pool["wk"].shape == (5, 65, 128, 8 * 192)
    assert pool["wv"].shape == (5, 65, 128, 8 * 128)
    assert pool["k"].shape == (2, 65, 16384, 4 * 192)


def _trace():
    """Two decode programs and a prefill on one chip: `fusion.1` under
    `attn_global`, `fusion.2` under `attn_local`, `fusion.3` under `moe`,
    `fusion.5` under `window_write`, and a grouped product the compiler
    named itself."""
    ops, mods, t = [], [], 0
    for name in ("jit__decode_chunk(11)", "jit__prefill(22)",
                 "jit__decode_chunk(11)"):
        mods.append((name, t, 95 * US))
        ops.append(("%fusion.1 = f32[4] fusion(%p), kind=kLoop", t, 30 * US))
        ops.append(("%fusion.2 = f32[4] fusion(%p), kind=kLoop",
                    t + 30 * US, 10 * US))
        ops.append(("%fusion.3 = f32[4] fusion(%q), kind=kLoop",
                    t + 40 * US, 10 * US))
        ops.append(("%ragged-dot-none.4 = bf16[8,4] custom-call(%a, %b)",
                    t + 50 * US, 30 * US))
        ops.append(("%fusion.5 = f32[4] fusion(%r), kind=kLoop",
                    t + 80 * US, 5 * US))
        t += 100 * US
    paths = lambda prog: {  # noqa: E731
        "fusion.1": f"jit({prog})/while/body/layers/attn/attn_global/dot",
        "fusion.2": f"jit({prog})/while/body/layers/attn/attn_local/dot",
        "fusion.3": f"jit({prog})/while/body/layers/moe/moe_router/sort",
        "fusion.5": f"jit({prog})/while/body/layers/window_write/scatter",
        "ragged-dot-none.4": "ragged-dot-none"}
    programs = {"jit__decode_chunk": [paths("_decode_chunk")],
                "jit__prefill": [paths("_prefill")]}
    return tr.Trace(device_ops={0: ops}, device_modules={0: mods}), programs


def _ctx():
    trace, programs = _trace()
    return {"trace": trace, "programs": programs,
            "counts": {"output_tokens": 100},
            "counters": {"decode_passes": 16, "experts_touched": 16 * 70,
                         "expert_slots": 16 * 96, "kv_columns_read": 3_000_000,
                         "expert_assignments_held": 16 * 70 * 2,
                         "tokens_delivered": 16 * 36},
            "work": {"n_slots": 64},
            "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}


def _metric(name):
    with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_roofline_reader_on_a_synthetic_trace():
    read = loader.load_reader("swa_bytes_roofline")
    hf, ctx = loader.load_config(CONFIG), _ctx()
    args = (hf, ctx["work"], ctx["counters"])
    # the decode programs' attn_global scope alone (2 x 30 us)
    got = read(ctx, _metric("attn_global_roofline.swa"))
    assert got == pytest.approx(100 * 3_000_000 * 5_120 / 819e9 / 60e-6)
    got = read(ctx, _metric("attn_local_roofline.swa"))
    assert got == pytest.approx(
        100 * 16 * 36 * 3_276_800 / 819e9 / 20e-6)
    # the decode programs' moe scope (2 x 10 us) and the grouped products by
    # name in every program (3 x 30 us)
    got = read(ctx, _metric("moe_roofline.rollout_swa"))
    assert got == pytest.approx(100 * sw.moe_bytes(*args) / 819e9 / 110e-6)
    # the whole span of the decode programs (2 x 95 us)
    got = read(ctx, _metric("decode_roofline.rollout_swa"))
    assert got == pytest.approx(100 * sw.decode_bytes(*args) / 819e9 / 190e-6)


def test_scope_and_counter_metrics_on_a_synthetic_trace():
    ctx = _ctx()

    def value(name):
        spec = _metric(name)
        return loader.load_reader(spec["reader"])(ctx, spec)

    assert value("rollout_attn_global_ms_per_token.swa") == pytest.approx(0.09 / 100)
    assert value("rollout_attn_local_ms_per_token.swa") == pytest.approx(0.03 / 100)
    assert value("rollout_cache_write_ms_per_token.swa") == pytest.approx(0.015 / 100)
    assert value("rollout_moe_ms_per_token.swa") == pytest.approx(0.12 / 100)
    assert value("rollout_moe_experts_ms_per_token.swa") == pytest.approx(0.09 / 100)
    assert value("rollout_ffn_dense_ms_per_token.swa") is None  # none in the trace
    assert value("rollout_experts_touched_pct.swa") == pytest.approx(100 * 70 / 96)
    assert value("rollout_expert_tokens_per_expert.swa") == pytest.approx(2.0)
    assert value("rollout_live_slots_per_pass.swa") == pytest.approx(36.0)


@pytest.mark.parametrize("drop", ["peaks", "counter", "trace"])
def test_roofline_reader_reads_nothing_rather_than_raise(drop):
    """The parent of the PR that added the family has no such counter: the
    line then leaves the metric out."""
    read = loader.load_reader("swa_bytes_roofline")
    for name in NEW_METRICS[-4:]:
        ctx, spec = _ctx(), _metric(name)
        if drop == "peaks":
            ctx["peaks"] = None
        elif drop == "counter":
            ctx["counters"] = {}
        else:
            ctx["trace"] = tr.Trace(device_ops={0: []}, device_modules={0: []})
            ctx["programs"] = {}
        assert read(ctx, spec) is None


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_its_metrics_are_declared_and_found():
    bench = _bench()
    cell = loader.load_cell(CELL)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": CELL,
                     "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == "rollout_tokens_per_s")
    assert CELL in moved["workloads"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    found = {m["name"] for m in loader.load_layer_metrics(CELL)}
    for name in NEW_METRICS:
        spec = _metric(name)
        assert spec["cells"] == declared[name]["workloads"] == [CELL]
        assert spec["moves"] == declared[name]["moves"] == "rollout_tokens_per_s"
        assert (spec["unit"], spec["layer"], spec["source"]) == tuple(
            declared[name][k] for k in ("unit", "layer", "source"))
        assert name in found
    # the rollout metrics without `cells` are inherited
    inherited = {m["name"] for m in bench["per_layer"]
                 if m["moves"] == "rollout_tokens_per_s" and "workloads" not in m}
    assert {"rollout_device_ms_per_token", "rollout_step_fetch_ms",
            "rollout_window_compiles"} <= inherited <= found
    assert found == inherited | {
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) <= found
    assert len(bench["per_layer"]) <= 128
    cfg = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == loader.load_config(CONFIG)["bench"]["reduced"]


def test_the_traffic_is_the_issue_s():
    from benchmarks.lib import traffic

    cell = loader.load_cell(CELL)
    t = cell["traffic"]
    assert (t["n_groups"], t["group_size"], t["groups_in_flight"]) == (64, 8, 8)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 3072,
                               "sigma": 0.9, "lo": 256, "hi": 14336}
    assert t["output_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.6, "lo": 128, "hi": 1024}
    assert (t["temperature"], t["top_p"], t["top_k"]) == (1.0, 1.0, 0)
    assert cell["engine"] == {"n_slots": 64, "max_seq_len": 16384}
    assert cell["trace_seconds"] == _bench()["run_seconds"]
    groups = traffic.rollout_groups(t, 19072, [0, 0])
    lens = sorted(len(g["prompt"]) for g in groups)
    # short and long in one queue: a quarter under 1.7k, a quarter over 5.6k
    assert lens[15] < 1_700 and lens[48] > 5_600
    # the ramp's eight groups hold a prompt over `check.long_prompt`, so a
    # finished request on one is there to compare whatever the window holds
    first = [len(g["prompt"]) for g in groups[:8]]
    assert max(first) >= cell["check"]["long_prompt"] == 8192
    assert cell["check"]["requests"] == 4
    # a seed changes ids and not work
    again = traffic.rollout_groups(t, 19072, [7, 0])
    assert [len(g["prompt"]) for g in again] == [len(g["prompt"]) for g in groups]
    assert [g["budgets"] for g in again] == [g["budgets"] for g in groups]


def test_the_configuration_states_its_cut_and_what_it_assumed():
    hf = loader.load_config(CONFIG)
    b = hf["bench"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert b["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in ("hybrid_layer_pattern", "moe_layer_freq"):
            assert hf[key] == value[:7] and key in b["reduced"]
        elif key in b["reduced"]:
            assert b["published"][key] == value and hf[key] != value, key
        else:
            assert hf[key] == value, key
    assert b["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "hybrid_layer_pattern",
                            "moe_layer_freq"]
    assert (hf["num_hidden_layers"], hf["n_routed_experts"], hf["vocab_size"]) \
        == (7, 16, 19072)
    assert hf["experts_held"] == {"first": 0, "of": 256}
    assert set(b["reduced_how"]) == set(b["reduced"])
    for key, about in b["assumed"].items():
        assert {"value", "from"} <= set(about), key
    assert {"value_scale", "window", "partial_rotary", "sink", "sink_draw",
            "no_qk_norm", "ignored_keys", "e_score_correction_bias",
            "embedding_draw", "router_held_draw"} \
        <= set(b["assumed"])
    assert {"training", "encoders", "multi_token_prediction", "checkpoint"} \
        <= set(b["not_built"])
    assert (b["dtype"], b["cache_dtype"]) == ("bfloat16", "bfloat16")
    assert b["reference"] == "reference_mimo_v2"


def _run(script, *extra, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script),
         "--workload", CELL, "--seconds", "2", "--trace", "0",
         "--cpu-rehearsal", *extra],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _lines(out):
    assert out.returncode == 0, out.stderr[-2000:]
    return [json.loads(x) for x in out.stdout.splitlines() if x.startswith("{")]


def test_the_cell_s_rehearsal_is_exact():
    """The cell end to end at a toy size: closed loop, a group's prompt
    prefilled once and its columns and rings copied, decode past many turns
    of a ring of 8, the pool freed, then the float32 reference, in float32."""
    lines = _lines(_run("benchmarks/run.py", "--seed", "3000000019"))
    line = lines[-1]
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"rehearsal.rollout_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert set(line["compared"]) == {"logprob_mean_abs", "failed"}
    window = next(x["diag"] for x in lines
                  if x.get("diag", {}).get("phase") == "window")
    rep = window["checks"]["reference"]
    assert rep["max_abs"] < 1e-4 and rep["n"] > 50
    c = window["checks"]["counters"]
    assert c["copy_calls"] > 0 and c["shared_tokens"] > 0
    assert c["window_copies"] == c["state_copies"] > 0
    assert c["expert_slots"] == c["decode_passes"] * 6 * 4
    assert 0 < c["experts_touched"] <= c["expert_slots"]
    assert c["expert_assignments_held"] >= c["experts_touched"]
    assert c["kv_columns_read"] >= 20 * c["tokens_delivered"]
    # every decode dispatch goes through the full layers' paged kernel
    # (`ops/windowed_decode.py`, PR 50)
    assert c["ragged_dispatches"] == c["decode_calls"] > 0
    assert window["compiles_in_window"]["compiled"] == 0
    # the plan by reach holds every admission pass the loop made
    assert window["checks"]["unplanned_passes"] == []


def test_the_control_s_rehearsal_is_not_correct():
    """`controls/rollout_swa_moe_16k.json`: the pool in float8; the pool
    check lets a pool the FILES state through, the log-probs do not."""
    with open(os.path.join(REPO, "benchmarks/controls", f"{CELL}.json")) as f:
        control = json.load(f)
    assert control["patch"] == {"engine": {"kv_dtype": "float8_e4m3fn"}}
    line = _lines(_run("benchmarks/tests/control_run.py",
                       "--seed", "3000000021"))[-1]
    assert not line["correct"] and line["failed"] == 0
    for name in control["fails"]:
        assert line["compared"][name]["value"] > line["compared"][name]["limit"]


def _kind():
    return loader._load_module("kinds", "rollout_swa",
                               os.path.join(REPO, "benchmarks"))


def _toy_hf():
    return {**loader.load_config(CONFIG), **TOY, "sliding_window_size": 8,
            "swa_num_attention_heads": 8, "swa_head_dim": 24,
            "swa_v_head_dim": 16}


def test_a_model_that_is_not_the_file_s_is_named():
    """Exit 4 in `run`: another family built under the name, other heads,
    another window, no sink, other experts."""
    from areal_tpu.models.model_config import TransformerConfig

    kind = _kind()
    hf = _toy_hf()
    cfg = TransformerConfig.from_hf(hf)
    assert kind.model_as_stated(cfg, hf) == ""
    assert "attn_kind" in kind.model_as_stated(
        cfg.replace(attn_kind="softmax"), hf)
    for field, other in (("swa_num_kv_heads", 2), ("sliding_window", 16),
                         ("sink_sliding", False), ("swa_rope_theta", 1e7),
                         ("attn_value_scale", 1.0), ("v_head_dim", 24)):
        assert field in kind.model_as_stated(
            cfg.replace(**{field: other}), hf)
    assert "experts held" in kind.model_as_stated(
        cfg.replace(experts_held=(0, 8)), hf)


@pytest.mark.parametrize("case,why", [
    ("as stated", ""), ("float8 stated", ""),
    ("float8 pool", "not bfloat16"), ("columns for a sliding layer", "a ring of 8"),
    ("short", "a ring of 8"), ("no scratch row", "a ring of 8"),
])
def test_a_pool_that_is_not_as_stated_is_named(case, why):
    """Exit 5 in `run`: the pool's dtype and size are held by looking at the
    pool, not by the log-probs alone.  A sliding layer that keeps
    `max_seq_len` columns is another deployment."""
    import jax.numpy as jnp

    rows, M = 7, 32  # 6 slots and the engine's scratch row
    dt = jnp.float8_e4m3fn if case.startswith("float8") else jnp.bfloat16
    if case == "no scratch row":
        rows = 6
    ring = M if case == "columns for a sliding layer" else 8
    full = 16 if case == "short" else M
    pool = {"k": jnp.zeros((2, rows, full, 48), dt),
            "v": jnp.zeros((2, rows, full, 32), dt),
            "wk": jnp.zeros((5, rows, ring, 96), dt),
            "wv": jnp.zeros((5, rows, ring, 64), dt)}
    e = {"kv_dtype": "float8_e4m3fn"} if case == "float8 stated" else {}
    got = _kind().pool_as_stated(pool, TOY, e, 6, M)
    assert (got == "") if not why else (why in got), got


def test_the_draw_moves_the_sinks_the_bias_and_the_embedding_and_leaves_the_rest():
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = {"embedding": jnp.ones((4, 4)),
         "layers": {"full": {"wo": jnp.ones((2, 4, 4))},
                    "sliding": {"wq": jnp.ones((5, 4, 4)),
                                "sink": jnp.zeros((5, 64), jnp.float32)},
                    "input_norm": jnp.ones((7, 8)),
                    "moe": {"router": jax.random.normal(
                                jax.random.PRNGKey(5), (6, 1024, 256)),
                            "router_bias": jnp.zeros((6, 256))}}}
    hf = loader.load_config(CONFIG)
    q = _kind().trained_like_draw(p, hf, jax.random.PRNGKey(3))
    bias = np.asarray(q["layers"]["moe"]["router_bias"])
    std = hf["bench"]["assumed"]["e_score_correction_bias"]["std"]
    assert std == 0.002  # a hundredth of a sigmoid score's spread
    assert 0.9 < bias.std() / std < 1.1 and abs(bias.mean()) < std / 10
    # the held experts (the first 16 of 256): for every token their logits
    # sum to zero, at the norm of the layer's columns; the others as drawn
    assert "router_held_draw" in hf["bench"]["assumed"]
    was = np.asarray(p["layers"]["moe"]["router"])
    router = np.asarray(q["layers"]["moe"]["router"])
    np.testing.assert_array_equal(router[..., 16:], was[..., 16:])
    assert np.abs(was[..., :16].sum(-1)).max() > 1.0
    assert np.abs(router[..., :16].sum(-1)).max() < 1e-5
    assert np.abs(bias[:, :16].sum(-1)).max() < 1e-7 < np.abs(
        bias[:, 16:32].sum(-1)).min()
    norms = np.linalg.norm(router, axis=1)  # [layers, experts]
    want = np.sqrt((np.linalg.norm(was, axis=1) ** 2).mean(-1))
    np.testing.assert_allclose(
        np.sqrt((norms[:, :16] ** 2).mean(-1)), want, rtol=1e-5)
    assert np.abs(norms[:, :16] / want[:, None] - 1).max() < 0.03 < np.abs(
        np.linalg.norm(was[..., :16], axis=1) / want[:, None] - 1).max()
    sink = np.asarray(q["layers"]["sliding"]["sink"])
    assert sink.dtype == np.float32 and sink.shape == (5, 64)
    assert 2.8 < sink.mean() < 3.2 and 0.75 < sink.std() < 1.25
    assert "sink" not in q["layers"]["full"]
    np.testing.assert_array_equal(q["layers"]["sliding"]["wq"], 1.0)
    np.testing.assert_array_equal(q["layers"]["full"]["wo"], 1.0)
    np.testing.assert_array_equal(q["layers"]["input_norm"], 1.0)
    # a token's own row outweighs its context's average in the first layer
    np.testing.assert_array_equal(q["embedding"], 8.0)
    assert hf["bench"]["assumed"]["embedding_draw"]["scale"] == 8.0
