"""CPU tests of what PR 44 added to the yardstick: the configuration's
arithmetic from shapes, the byte functions of `lib/latent_work.py` against a
hand count, the reader `latent_bytes_roofline` on a hand-built trace (and
on a program without the counters: nothing, and no raise), the data files
of `rollout_latent_8k`, the kind's two refusals (exit 4 and 5), the cell's
CPU rehearsal (whose comparison with the float32 reference has to be exact
there) and its control's (which has to come out not `correct`)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import latent_work as lw
from benchmarks.lib import loader
from benchmarks.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000
CELL, CONFIG = "rollout_latent_8k", "longcat-flash-omni"
NEW_METRICS = (
    "rollout_mla_attn_ms_per_token.latent", "rollout_mla_proj_ms_per_token.latent",
    "rollout_latent_write_ms_per_token.latent",
    "rollout_ffn_dense_ms_per_token.latent", "rollout_moe_ms_per_token.latent",
    "rollout_moe_experts_ms_per_token.latent",
    "rollout_identity_choice_pct.latent", "rollout_experts_touched_pct.latent",
    "rollout_live_slots_per_pass.latent", "mla_attn_roofline.latent",
    "decode_roofline.rollout_latent",
)

# the toy size of the repo's tests (tests/test_longcat_model.py)
TOY = {
    "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": 48, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "qk_nope_head_dim": 16, "n_routed_experts": 4,
    "experts_held": {"first": 2, "of": 8}, "zero_expert_num": 4,
    "moe_topk": 3, "vocab_size": 256,
    "bench": {"dtype": "bfloat16", "cache_dtype": "bfloat16"},
}


def test_bytes_at_the_toy_size_are_a_hand_count():
    sub = 64 * 48 + 48 + 48 * 4 * 24 + 64 * 40 + 32 + 32 * 4 * 32 + 4 * 16 * 64
    assert lw.attention_sublayer_params(TOY) == sub == 18_512
    assert lw.dense_ffn_params(TOY) == 3 * 64 * 96
    assert lw.router_params(TOY) == 64 * 12 + 12  # 8 routed + 4 identity
    assert lw.expert_params(TOY) == 3 * 64 * 32
    fixed = 2 * sub + 2 * 3 * 64 * 96 + 4 * 64 + 780
    assert lw.layer_fixed_params(TOY) == fixed
    assert (lw.row_values(TOY), lw.cache_values_per_token(TOY),
            lw.cache_bytes_per_token(TOY)) == (40, 160, 320)
    counters = {"decode_passes": 10, "experts_touched": 30,
                "latent_rows_read": 5_000, "expert_assignments": 10 * 7 * 6}
    assert lw.mla_attn_bytes(TOY, {}, counters) == 5_000 * 40 * 2
    assert lw.rows_written(TOY, counters) == 10 * 7 * 4  # 7 slots a pass
    per_pass = (2 * fixed + 64 * 257) * 2
    assert lw.decode_bytes(TOY, {}, counters) == (
        10 * per_pass + 30 * 6_144 * 2 + 400_000 + 280 * 80)
    assert lw.decode_bytes(TOY, {}, {}) == 0


def test_bytes_of_the_cell_are_the_issue_s_arithmetic():
    hf = loader.load_config(CONFIG)
    b = hf["bench"]["bytes"]
    per = b["per_block_parameters"]
    assert lw.attention_sublayer_params(hf) == per["latent_attention_sublayer"] \
        == 90_572_800
    assert lw.dense_ffn_params(hf) == per["dense_ffn"] == 226_492_416
    assert lw.router_params(hf) == per["router_and_bias"] == 4_719_360
    assert lw.layer_fixed_params(hf) == per["layer_outside_routed_experts"] \
        == 638_874_368
    assert lw.expert_params(hf) == per["one_routed_expert"] == 37_748_736
    assert lw.parameters_held(hf) == b["parameters_held"] == 5_172_749_312
    assert b["weight_bytes_bfloat16"] == 2 * b["parameters_held"]
    assert lw.cache_bytes_per_token(hf) == b["cache_bytes_per_token"] == 9_216
    assert b["pool_bytes_41_rows_of_8192"] == 41 * 8_192 * 9_216
    assert b["plain_kv_bytes_per_token_same_heads"] == 327_680
    # a pass with every held expert touched, before any row is read
    full = lw.decode_bytes(hf, {}, {"decode_passes": 1, "experts_touched": 64})
    assert 10.1e9 < full < 10.2e9


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
    assert n == lw.parameters_held(hf)
    pool = jax.eval_shape(lambda: init_kv_cache(cfg, 41, 8192, "bfloat16"))
    assert sum(a.size * 2 for a in pool.values()) \
        == hf["bench"]["bytes"]["pool_bytes_41_rows_of_8192"]


def _trace():
    """Two decode programs and a prefill on one chip: `fusion.1` under
    `mla_attn`, `fusion.2` under `moe`, `fusion.4` under `latent_write`, and
    a grouped product the compiler named itself."""
    ops, mods, t = [], [], 0
    for name in ("jit__decode_chunk(11)", "jit__prefill(22)",
                 "jit__decode_chunk(11)"):
        mods.append((name, t, 95 * US))
        ops.append(("%fusion.1 = f32[4] fusion(%p), kind=kLoop", t, 40 * US))
        ops.append(("%fusion.2 = f32[4] fusion(%q), kind=kLoop",
                    t + 40 * US, 10 * US))
        ops.append(("%ragged-dot-none.3 = bf16[8,4] custom-call(%a, %b)",
                    t + 50 * US, 30 * US))
        ops.append(("%fusion.4 = f32[4] fusion(%r), kind=kLoop",
                    t + 80 * US, 5 * US))
        t += 100 * US
    paths = lambda prog: {  # noqa: E731
        "fusion.1": f"jit({prog})/while/body/layers/mla_attn/bhc,bck->bhk/dot",
        "fusion.2": f"jit({prog})/while/body/layers/moe/moe_router/sort",
        "fusion.4": f"jit({prog})/while/body/layers/latent_write/while/body/dus",
        "ragged-dot-none.3": "ragged-dot-none"}
    programs = {"jit__decode_chunk": [paths("_decode_chunk")],
                "jit__prefill": [paths("_prefill")]}
    return tr.Trace(device_ops={0: ops}, device_modules={0: mods}), programs


def _ctx():
    trace, programs = _trace()
    return {"trace": trace, "programs": programs,
            "counts": {"output_tokens": 100},
            "counters": {"decode_passes": 16, "experts_touched": 16 * 30,
                         "expert_slots": 16 * 64, "latent_rows_read": 4_000_000,
                         "expert_assignments": 16 * 30 * 48,
                         "identity_assignments": 16 * 30 * 16,
                         "tokens_delivered": 16 * 28},
            "work": {"n_slots": 40},
            "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}


def _metric(name):
    with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_roofline_reader_on_a_synthetic_trace():
    read = loader.load_reader("latent_bytes_roofline")
    hf, ctx = loader.load_config(CONFIG), _ctx()
    args = (hf, ctx["work"], ctx["counters"])
    # the decode programs' mla_attn scope alone (2 x 40 us)
    got = read(ctx, _metric("mla_attn_roofline.latent"))
    assert got == pytest.approx(100 * 4_000_000 * 1152 / 819e9 / 80e-6)
    # the whole span of the decode programs (2 x 95 us)
    got = read(ctx, _metric("decode_roofline.rollout_latent"))
    assert got == pytest.approx(100 * lw.decode_bytes(*args) / 819e9 / 190e-6)


def test_scope_and_counter_metrics_on_a_synthetic_trace():
    ctx = _ctx()

    def value(name):
        spec = _metric(name)
        return loader.load_reader(spec["reader"])(ctx, spec)

    assert value("rollout_mla_attn_ms_per_token.latent") == pytest.approx(0.12 / 100)
    assert value("rollout_latent_write_ms_per_token.latent") == pytest.approx(0.015 / 100)
    # the moe scope (3 x 10 us) and the grouped products by name (3 x 30 us)
    assert value("rollout_moe_ms_per_token.latent") == pytest.approx(0.12 / 100)
    assert value("rollout_moe_experts_ms_per_token.latent") == pytest.approx(0.09 / 100)
    assert value("rollout_identity_choice_pct.latent") == pytest.approx(100 / 3)
    assert value("rollout_experts_touched_pct.latent") == pytest.approx(100 * 30 / 64)
    assert value("rollout_live_slots_per_pass.latent") == pytest.approx(28.0)


@pytest.mark.parametrize("drop", ["peaks", "counter", "trace"])
def test_roofline_reader_reads_nothing_rather_than_raise(drop):
    """The parent of the PR that added the family has no such counter: the
    line then leaves the metric out."""
    read = loader.load_reader("latent_bytes_roofline")
    for name in ("mla_attn_roofline.latent", "decode_roofline.rollout_latent"):
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
    # the rollout metrics without `cells` are inherited, whatever their
    # number that day; nothing else is found
    inherited = {m["name"] for m in bench["per_layer"]
                 if m["moves"] == "rollout_tokens_per_s" and "workloads" not in m}
    assert {"rollout_device_ms_per_token", "rollout_step_fetch_ms",
            "rollout_window_compiles"} <= inherited <= found
    assert found == inherited | {
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) <= found
    cfg = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == loader.load_config(CONFIG)["bench"]["reduced"]


def test_the_traced_window_is_the_measured_one():
    """Every group holds a budget of the top eighth and the ramp starts all
    the groups in flight within its few steps, so nothing is admitted until
    the first group's longest member ends, most of a window later.  A
    shorter traced window holds decode chunks only, and
    `rollout_shared_prefill_pct` (inherited: every rollout cell has to print
    it) finds nothing to read there (PR 44's first check)."""
    from benchmarks.lib import traffic

    cell = loader.load_cell(CELL)
    assert cell["trace_seconds"] == _bench()["run_seconds"]
    t = cell["traffic"]
    groups = traffic.rollout_groups(t, 16384, [0, 0])
    first = min(max(g["budgets"]) for g in groups[: t["groups_in_flight"]])
    # 30 ms a pass on the chip: the first admission of the window comes
    # 20 s or more after the ramp
    assert first >= 700


def test_the_configuration_states_its_cut_and_what_it_assumed():
    hf = loader.load_config(CONFIG)
    b = hf["bench"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Omni")
    assert b["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in b["reduced"]:
            assert b["published"][key] == value and hf[key] != value, key
        else:
            assert hf[key] == value, key
    assert b["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert (hf["num_layers"], hf["n_routed_experts"], hf["vocab_size"]) \
        == (4, 16, 16384)
    assert hf["experts_held"] == {"first": 0, "of": 512}
    assert set(b["reduced_how"]) == set(b["reduced"])
    for key, about in b["assumed"].items():
        assert {"value", "from"} <= set(about), key
    assert {"training", "encoders_and_codec", "bias_balancing_update"} \
        <= set(b["not_built"])
    assert (b["dtype"], b["cache_dtype"]) == ("bfloat16", "bfloat16")
    assert b["reference"] == "reference_longcat_flash"


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
    prefilled once and its rows copied, the pool freed, then the float32
    reference, in float32."""
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
    assert c["expert_slots"] == c["decode_passes"] * 2 * 4
    assert c["expert_assignments"] % 6 == 0
    assert 0 < c["identity_assignments"] < c["expert_assignments"]
    assert 0 < c["experts_touched"] <= c["expert_slots"]
    assert c["latent_rows_read"] >= 4 * 20 * c["expert_assignments"] // 6
    assert window["compiles_in_window"]["compiled"] == 0


def test_the_control_s_rehearsal_is_not_correct():
    """`controls/rollout_latent_8k.json`: the latent pool in float8; the
    pool check lets a pool the FILES state through, the log-probs do not."""
    with open(os.path.join(REPO, "benchmarks/controls", f"{CELL}.json")) as f:
        control = json.load(f)
    assert control["patch"] == {"engine": {"kv_dtype": "float8_e4m3fn"}}
    line = _lines(_run("benchmarks/tests/control_run.py",
                       "--seed", "3000000021"))[-1]
    assert not line["correct"] and line["failed"] == 0
    for name in control["fails"]:
        assert line["compared"][name]["value"] > line["compared"][name]["limit"]


def _kind():
    return loader._load_module("kinds", "rollout_latent",
                               os.path.join(REPO, "benchmarks"))


def test_a_model_that_is_not_the_file_s_is_named():
    """Exit 4 in `run`: a dense model built under the name (a program that
    reads the file as another family), other widths, other experts."""
    from areal_tpu.models.model_config import TransformerConfig

    kind = _kind()
    hf = {**loader.load_config(CONFIG), **TOY}
    cfg = TransformerConfig.from_hf(hf)
    assert kind.model_as_stated(cfg, hf) == ""
    assert "attn_kind" in kind.model_as_stated(
        cfg.replace(attn_kind="softmax"), hf)
    assert "kv_lora_rank" in kind.model_as_stated(cfg, {**hf, "kv_lora_rank": 64})
    assert "experts held" in kind.model_as_stated(
        cfg.replace(experts_held=(0, 8)), hf)


@pytest.mark.parametrize("case,why", [
    ("as stated", ""), ("float8 stated", ""),
    ("float8 pool", "not bfloat16"), ("head-expanded", "values a position"),
    ("short", "values a position"), ("no scratch row", "values a position"),
])
def test_a_pool_that_is_not_as_stated_is_named(case, why):
    """Exit 5 in `run`: the pool's dtype and size are held by looking at the
    pool, not by the log-probs alone."""
    import jax.numpy as jnp

    rows, M = 7, 32  # 6 slots and the engine's scratch row
    shape = {"head-expanded": (4, rows, 4 * 40, M), "short": (4, rows, 40, 16),
             "no scratch row": (4, 6, 40, M)}.get(case, (4, rows, 40, M))
    dt = jnp.float8_e4m3fn if case.startswith("float8") else jnp.bfloat16
    e = {"kv_dtype": "float8_e4m3fn"} if case == "float8 stated" else {}
    got = _kind().pool_as_stated({"lat": jnp.zeros(shape, dt)}, TOY, e, 6, M)
    assert (got == "") if not why else (why in got), got


def test_the_draw_scales_the_latents_norms_and_the_bias_and_leaves_the_rest():
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = {"embedding": jnp.ones((4, 4)),
         "layers": {"attn": {"wo": jnp.ones((1, 2, 4, 4)),
                             "q_norm": jnp.ones((4, 2, 1536), jnp.bfloat16),
                             "kv_norm": jnp.ones((4, 2, 512), jnp.bfloat16)},
                    "input_norm": jnp.ones((4, 2, 8)),
                    "moe": {"router": jnp.ones((4, 4, 768)),
                            "router_bias": jnp.zeros((4, 768))}}}
    hf = loader.load_config(CONFIG)
    q = _kind().trained_like_draw(p, hf, jax.random.PRNGKey(3))
    bias = np.asarray(q["layers"]["moe"]["router_bias"])
    assert 0.9 < bias.std() * 768 / 0.04 < 1.1 and abs(bias.mean()) < 2e-5
    attn = q["layers"]["attn"]
    # times the model's sqrt(6144 / rank): one
    np.testing.assert_allclose(np.asarray(attn["q_norm"], np.float32), 0.5)
    np.testing.assert_allclose(
        np.asarray(attn["kv_norm"], np.float32) * (6144 / 512) ** 0.5, 1.0,
        rtol=4e-3)
    assert attn["kv_norm"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(q["layers"]["moe"]["router"], 1.0)
    np.testing.assert_array_equal(attn["wo"], 1.0)
    np.testing.assert_array_equal(q["layers"]["input_norm"], 1.0)
    off = {**hf, "mla_scale_q_lora": False, "mla_scale_kv_lora": False}
    q = _kind().trained_like_draw(p, off, jax.random.PRNGKey(3))
    np.testing.assert_array_equal(q["layers"]["attn"]["kv_norm"], 1.0)
