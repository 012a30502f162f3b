"""CPU tests of what PR 32 added to the yardstick: the byte functions of
`lib/hybrid_work.py` against a hand count, the readers
`hybrid_bytes_roofline` and `device_time_in_scope_and_ops` on hand-built
traces, the data files of `rollout_hybrid_moe`, the kind's two refusals
(exit 4 and 5) and the cell's CPU rehearsal (whose comparison with the
float32 reference has to be exact there)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import hybrid_work as hw
from benchmarks.lib import loader
from benchmarks.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000
CELL, CONFIG = "rollout_hybrid_moe", "nemotron3-super-120b"

# the toy size of the repo's tests: pattern ME*ME, hidden 64, 8 experts
# top-3 with 4 held, state 16
TOY = {
    "hybrid_override_pattern": "ME*ME", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "vocab_size": 128,
    "n_routed_experts": 4, "experts_held": {"first": 2, "of": 8},
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96,
    "bench": {"dtype": "bfloat16", "state_dtype": "float32"},
}


def test_bytes_at_the_toy_size_are_a_hand_count():
    # Mamba: in_proj 64 x (64 z + 128 xBC + 8 dt), 4 taps + bias over 128
    # channels, dt_bias / A_log / D, the gated norm, out_proj, the pre-norm
    m = 64 * (64 + 128 + 8) + 4 * 128 + 128 + 3 * 8 + 64 + 64 * 64 + 64
    assert hw.mamba_block_params(TOY) == m == 17_688
    assert hw.attention_block_params(TOY) == 64 * 64 * 2 + 64 * 32 * 2 + 64
    # router over ALL 8 experts + its bias, two latent projections, the
    # shared expert, the pre-norm
    fixed = 64 * 8 + 8 + 2 * 64 * 32 + 2 * 64 * 96 + 64
    assert hw.moe_fixed_params(TOY) == fixed == 16_968
    assert hw.expert_params(TOY) == 2 * 32 * 48
    # a slot: 2 Mamba blocks x (8 x 8 x 16 float32 + 3 x 128 bfloat16)
    assert hw.state_bytes_per_slot(TOY) == 2 * (1024 * 4 + 384 * 2) == 9_728
    assert hw.kv_bytes_per_token(TOY) == 1 * 2 * 2 * 16 * 2
    work, c = {"n_slots": 6}, {"decode_passes": 10, "experts_touched": 55}
    ssm = 10 * (2 * m * 2 + 2 * 6 * 9_728)
    assert hw.ssm_bytes(TOY, work, c) == ssm
    moe = 10 * 2 * fixed * 2 + 55 * 2 * 32 * 48 * 2
    assert hw.moe_bytes(TOY, work, c) == moe
    rest = 10 * (hw.attention_block_params(TOY) + 64 * 129) * 2
    assert hw.decode_bytes(TOY, work, c) == ssm + moe + rest
    # an expert nobody was routed to is not read; no passes, no bytes
    assert hw.moe_bytes(TOY, work, {"decode_passes": 10}) == 10 * 2 * fixed * 2
    assert hw.decode_bytes(TOY, work, {}) == 0


def test_bytes_of_the_cell_are_the_issue_s_arithmetic():
    hf = loader.load_config(CONFIG)
    b = hf["bench"]["bytes"]
    assert hw.mamba_block_params(hf) == b["per_block_parameters"]["M"] == 109_640_064
    assert hw.attention_block_params(hf) == b["per_block_parameters"]["*"] == 35_655_680
    assert hw.moe_fixed_params(hf) == 54_530_560
    assert hw.expert_params(hf) == b["per_block_parameters"]["one_routed_expert"]
    assert hw.state_bytes_per_slot(hf) == b["state_and_window_bytes_per_slot"]
    assert hw.kv_bytes_per_token(hf) == b["kv_bytes_per_token"] == 1024
    assert b["pool_bytes_129_rows_x_2048"] == 129 * (21_278_720 + 2048 * 1024)
    one = {"decode_passes": 1, "experts_touched": 5 * 128}
    floor = hw.decode_bytes(hf, {"n_slots": 128}, one)
    assert floor / 1e9 == pytest.approx(14.475, abs=1e-3)
    assert floor / 819e9 == pytest.approx(0.01767, abs=1e-5)
    assert hw.ssm_bytes(hf, {"n_slots": 128}, one) / 1e9 == pytest.approx(6.544, abs=1e-3)
    assert hw.moe_bytes(hf, {"n_slots": 128}, one) / 1e9 == pytest.approx(7.592, abs=1e-3)


def _trace():
    """Two decode programs and a prefill on one chip: `fusion.1` under
    `ssm`, `fusion.2` under `moe`, and a grouped product the compiler named
    itself (no scope in its op_name)."""
    ops, mods, t = [], [], 0
    for name in ("jit__decode_chunk(11)", "jit__prefill(22)",
                 "jit__decode_chunk(11)"):
        mods.append((name, t, 90 * US))
        ops.append(("%fusion.1 = f32[4] fusion(%p), kind=kLoop", t, 40 * US))
        ops.append(("%fusion.2 = f32[4] fusion(%q), kind=kLoop",
                    t + 40 * US, 10 * US))
        ops.append(("%ragged-dot-none.3 = bf16[8,4] custom-call(%a, %b)",
                    t + 50 * US, 30 * US))
        t += 100 * US
    paths = lambda prog: {  # noqa: E731
        "fusion.1": f"jit({prog})/while/body/layers/ssm/ssm_scan/mul",
        "fusion.2": f"jit({prog})/while/body/layers/moe/moe_router/sort",
        "ragged-dot-none.3": "ragged-dot-none"}
    programs = {"jit__decode_chunk": [paths("_decode_chunk")],
                "jit__prefill": [paths("_prefill")]}
    return tr.Trace(device_ops={0: ops}, device_modules={0: mods}), programs


def _ctx():
    trace, programs = _trace()
    return {"trace": trace, "programs": programs,
            "counts": {"output_tokens": 100},
            "counters": {"decode_passes": 16, "experts_touched": 16 * 600},
            "work": {"n_slots": 128},
            "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}


def _metric(name):
    with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_roofline_readers_on_a_synthetic_trace():
    read = loader.load_reader("hybrid_bytes_roofline")
    hf, ctx = loader.load_config(CONFIG), _ctx()
    args = (hf, ctx["work"], ctx["counters"])
    # ssm: the decode programs' ssm scope alone (2 x 40 us)
    got = read(ctx, _metric("ssm_roofline.rollout_hybrid"))
    assert got == pytest.approx(100 * hw.ssm_bytes(*args) / 819e9 / 80e-6)
    # moe: the decode programs' moe scope (2 x 10 us) and the grouped
    # products by name, the prefill's too (3 x 30 us): errs low
    got = read(ctx, _metric("moe_roofline.rollout_hybrid"))
    assert got == pytest.approx(100 * hw.moe_bytes(*args) / 819e9 / 110e-6)
    got = read(ctx, _metric("decode_roofline.rollout_hybrid"))
    assert got == pytest.approx(100 * hw.decode_bytes(*args) / 819e9 / 180e-6)


def test_scope_and_ops_reader_adds_what_the_compiler_named_itself():
    read = loader.load_reader("device_time_in_scope_and_ops")
    ctx = _ctx()
    spec = _metric("rollout_moe_ms_per_token.hybrid")
    # every program's moe scope (3 x 10 us) + grouped products (3 x 30 us)
    assert read(ctx, spec) == pytest.approx(120e-6 * 1e3 / 100)
    spec = _metric("rollout_moe_experts_ms_per_token.hybrid")
    assert read(ctx, spec) == pytest.approx(90e-6 * 1e3 / 100)
    assert read({**ctx, "trace": None}, spec) is None
    nothing = {**spec, "scope": "/no_such_scope/", "ops": "^no_such_op"}
    assert read(_ctx(), nothing) is None


def test_the_unscoped_alarm_leaves_out_what_the_moe_metrics_read_by_name():
    """The grouped products carry no scope; the moe metrics read them by
    name, so the cell's `unscoped` metric must not count them again."""
    read = loader.load_reader("device_time_in_scope")
    spec = _metric("rollout_unscoped_ms_per_token")  # every rollout cell's
    assert "cells" not in spec
    assert read(_ctx(), spec) is None  # everything else there has a scope
    ctx = _ctx()
    ctx["trace"].device_ops[0].append(
        ("%copy.9 = f32[4] copy(%p)", 300 * US, 7 * US))
    for paths in ctx["programs"].values():
        paths[0]["copy.9"] = ""
    ctx["trace"].device_modules[0].append(("jit__prefill(22)", 300 * US, 9 * US))
    assert read(ctx, spec) == pytest.approx(7e-6 * 1e3 / 100)
    with_them = {**spec, "not_scope": spec["not_scope"].replace("|^ragged-dot", "")}
    assert read(ctx, with_them) == pytest.approx(97e-6 * 1e3 / 100)


def test_live_slots_a_pass_is_delivered_tokens_over_passes():
    """The engine's own `tokens_delivered` (the kind adds no counter of its
    own for it), in the file the dense cell reads too."""
    read = loader.load_reader("counter_per")
    spec = _metric("rollout_live_slots_per_pass")
    assert CELL in spec["cells"] and "rollout_decode" in spec["cells"]
    ctx = {"counters": {"tokens_delivered": 65_029, "decode_passes": 880},
           "counts": {}}
    assert read(ctx, spec) == pytest.approx(73.9, abs=0.01)
    assert read({"counters": {"decode_passes": 880}, "counts": {}}, spec) is None


@pytest.mark.parametrize("drop", ["peaks", "counter", "n_slots", "trace"])
def test_roofline_reader_reads_nothing_rather_than_raise(drop):
    """The parent commit counts no passes of this kind and has no such
    scope: the metric is left out of its line, nothing raises."""
    read = loader.load_reader("hybrid_bytes_roofline")
    ctx, spec = _ctx(), _metric("moe_roofline.rollout_hybrid")
    if drop == "peaks":
        ctx["peaks"] = None
    elif drop == "counter":
        ctx["counters"] = {}
    elif drop == "n_slots":
        ctx["work"] = {}
    else:
        ctx["trace"] = None
    assert read(ctx, spec) is None


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_and_its_metrics_are_declared_and_found():
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    data = loader.load_cell(CELL)
    assert entry["config"] == data["config"] == CONFIG
    assert entry["chips"] == data["chips"] == 1
    assert entry["why"] == data["why"] and len(entry["why"]) <= 200
    loader.load_kind(data["kind"])
    found = {m["name"] for m in loader.load_layer_metrics(CELL)}
    # an entry without `workloads` is every cell's that reports its `moves`
    mine = [m for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])
            and m["moves"] == "rollout_tokens_per_s"]
    assert found == {m["name"] for m in mine}
    assert loader.end_to_end_metrics(CELL) == ["rollout_tokens_per_s", "setup_s"]
    # what only this stack has to read stays its own; the engine's, the
    # sampler's and the head's metrics are the three rollout cells' one entry
    own = {m["name"] for m in mine if m.get("workloads") == [CELL]}
    assert own == {
        "rollout_ssm_ms_per_token.hybrid", "rollout_moe_ms_per_token.hybrid",
        "rollout_moe_experts_ms_per_token.hybrid",
        "rollout_expert_tokens_per_expert.hybrid",
        "rollout_experts_touched_pct.hybrid", "ssm_roofline.rollout_hybrid",
        "moe_roofline.rollout_hybrid", "decode_roofline.rollout_hybrid"}
    # the names the cell needs are there, however many entries the day has
    inherited = {m["name"] for m in mine if "workloads" not in m}
    assert own | inherited <= found
    assert {"rollout_device_ms_per_token", "rollout_step_fetch_ms",
            "rollout_window_compiles", "rollout_sampler_ms_per_token",
            "rollout_lm_head_ms_per_token"} <= inherited
    tr_ = data["traffic"]
    assert (tr_["groups_in_flight"], tr_["group_size"]) == (20, 8)
    assert data["engine"] == {"n_slots": 128, "max_seq_len": 2048}


def test_the_configuration_states_its_cut_and_what_it_assumed():
    bench = _bench()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    hf = loader.load_config(CONFIG)
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == hf["bench"]["reduced"] == reduced
    assert set(hf["bench"]["reduced_how"]) == set(reduced)
    assert entry["source"] == hf["bench"]["source"] and len(entry["why"]) <= 200
    assert (hf["num_hidden_layers"], hf["hybrid_override_pattern"],
            hf["n_routed_experts"], hf["vocab_size"]) == (
        11, "MEMEMEM*EME", 128, 32768)
    pub = hf["bench"]["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (88, 512, 131072)
    assert pub["hybrid_override_pattern"].startswith("MEMEMEM*EME")
    assert hf["experts_held"] == {"first": 0, "of": 512}
    # every published width is as the catalog has it
    row = next(json.loads(x) for x in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in x) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row:
        for k, v in row["config"].items():
            if k not in reduced:
                assert hf[k] == v, k
    for key, about in hf["bench"]["assumed"].items():
        assert {"value", "from"} <= set(about), key
    assert "multi_token_prediction" in hf["bench"]["not_built"]
    assert hf["bench"]["state_dtype"] == "float32"
    assert hf["bench"]["reference"] == "reference_nemotron_h"


def _run(*extra, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/run.py"),
         "--workload", CELL, "--seconds", "2", "--trace", "0",
         "--cpu-rehearsal", *extra],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_the_cell_s_rehearsal_is_exact():
    """The cell end to end at a toy size: closed loop, fan-out of state,
    window and K/V, late siblings, the pool freed, then the float32
    reference, in float32."""
    out = _run("--seed", "3000000019")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines() if x.startswith("{")]
    line = lines[-1]
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"rehearsal.rollout_tokens_per_s",
                                    "rehearsal.setup_s"}
    window = next(x["diag"] for x in lines
                  if x.get("diag", {}).get("phase") == "window")
    rep = window["checks"]["reference"]
    assert rep["max_abs"] < 1e-4 and rep["n"] > 50
    # ... and the state the program left in the pool for live sequences is
    # the state of the reference's own recurrence over the same ids
    state = window["checks"]["state"]
    assert window["checks"]["state_ok"] and state["n"] == 4
    assert max(state["slow_heads_rel_err"] + state["all_heads_rel_err"]) < 1e-5
    assert min(state["lengths"]) > 30
    c = window["checks"]["counters"]
    assert c["state_copies"] > 0 and c["sibling_reprefills"] > 0
    assert c["copy_calls"] > 0 and c["experts_touched"] > 0
    assert c["expert_slots"] == c["decode_passes"] * 2 * 4
    assert c["tokens_delivered"] > 8 * c["decode_passes"] / 2  # of 8 slots
    assert window["compiles_in_window"]["compiled"] == 0


def _root_with(tmp_path, edit):
    import shutil

    root = tmp_path / "benchmarks"
    for d in ("workloads", "configs"):
        os.makedirs(root / d)
    shutil.copy(os.path.join(REPO, f"benchmarks/workloads/{CELL}.json"),
                root / "workloads")
    with open(os.path.join(REPO, f"benchmarks/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    edit(cfg)
    (root / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    return str(root)


def test_a_program_that_does_not_know_the_family_ends_at_once(tmp_path):
    """What the parent commit does with the new cell: `from_hf` refuses the
    `model_type`, the run ends with a non-zero exit and no result line."""
    root = _root_with(tmp_path, lambda c: c.update(model_type="nemotron_x"))
    out = _run("--seed", "1", "--bench-root", root, timeout=300)
    assert out.returncode not in (0, 4, 5), out.returncode
    assert "unsupported model_type" in out.stderr
    assert not any(x.startswith('{"correct"') for x in out.stdout.splitlines())


def _kind():
    return loader._load_module("kinds", "rollout_hybrid",
                               os.path.join(REPO, "benchmarks"))


class _Model:
    def __init__(self, kinds, held, n):
        self.layer_kinds, self.held_range, self.num_experts = kinds, held, n


@pytest.mark.parametrize("model,why", [
    (_Model(tuple("MEMEMEM*EME"), (0, 128), 512), ""),
    (_Model(None, (0, 128), 512), "block kinds None"),
    (_Model(tuple("MEMEMEM*EMM"), (0, 128), 512), "not the pattern"),
    (_Model(tuple("MEMEMEM*EME"), (0, 512), 512), "held, not (0, 128) of 512"),
    (_Model(tuple("MEMEMEM*EME"), (128, 256), 512), "held, not (0, 128)"),
    (_Model(tuple("MEMEMEM*EME"), (0, 128), 128), "of 128 held"),
])
def test_a_model_that_is_not_the_file_s_is_named(model, why):
    """Exit 4 in `run`: another stack, or other experts than the file's."""
    got = _kind().built_as_stated(model, loader.load_config(CONFIG))
    assert (got == "") if not why else (why in got), got


@pytest.mark.parametrize("case,why", [
    ("as stated", ""),
    ("bfloat16 state", "'s' is bfloat16, not float32"),
    ("no window", "pool leaves"),
    ("short state", "state and windows hold"),
    ("wide columns", "keys and values hold"),
    ("few rows", "pool rows"),
])
def test_a_pool_that_is_not_as_stated_is_named(case, why):
    """Exit 5 in `run`: `bench.state_dtype` and the stated sizes are held
    by looking at the pool, not by the log-probs alone."""
    import jax.numpy as jnp

    rows, M = 7, 32  # 6 slots and the engine's scratch row
    dt = jnp.bfloat16 if case == "bfloat16 state" else jnp.float32
    cache = {"s": jnp.zeros((2, rows, 8, 8, 16), dt),
             "c": jnp.zeros((2, rows, 3, 128), jnp.bfloat16),
             "k": jnp.zeros((1, rows, M, 2, 16), jnp.bfloat16),
             "v": jnp.zeros((1, rows, M, 2, 16), jnp.bfloat16)}
    if case == "no window":
        del cache["c"]
    if case == "short state":
        cache["s"] = cache["s"][..., :8]
    if case == "wide columns":
        cache["k"] = jnp.zeros((1, rows, M, 4, 16), jnp.bfloat16)
    n_slots = 9 if case == "few rows" else 6
    got = _kind().pool_as_stated(cache, TOY, n_slots, M)
    assert (got == "") if not why else (why in got), got


def _toy_model():
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig

    kind = _kind()
    hf = {**loader.load_config(CONFIG), **kind.REHEARSAL_HF,
          "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
          "vocab_size": 128}
    cfg = TransformerConfig.from_hf(hf).replace(
        dtype="float32", param_dtype="float32", remat=False)
    return kind, hf, init_params(cfg, jax.random.PRNGKey(5))


@pytest.mark.parametrize("pool,ok", [("float32", True), ("bfloat16", False),
                                     ("short", False)])
def test_the_state_comparison_sees_how_the_state_is_kept(pool, ok):
    """`check_states` on the reference's own states: exact; rounded to
    bfloat16 once (far less than a bfloat16 pool loses over hundreds of
    steps) it is already over the rehearsal's limit; fewer live slots than
    `check.state_slots` is not correct."""
    import jax.numpy as jnp
    import numpy as np

    kind, hf, params = _toy_model()
    ref = loader._load_module("lib", "reference_nemotron_h",
                              os.path.join(REPO, "benchmarks"))
    rng = np.random.default_rng(0)
    lens = [40, 33, 25]
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in lens]
    ids = np.zeros((3, 40), np.int32)
    for i, q in enumerate(seqs):
        ids[i, : len(q)] = q
    states = []
    ref.hidden_states(params, hf, ids, states, lens)
    # padding behind a row's length does not reach its state
    alone = []
    ref.hidden_states(params, hf, seqs[2][None], alone)
    np.testing.assert_allclose(states[0][2], alone[0][0], rtol=1e-4, atol=1e-6)
    as_pool = np.stack([np.asarray(s) for s in states])  # [n_ssm, B, H, P, N]
    if pool == "bfloat16":
        as_pool = np.asarray(jnp.asarray(as_pool).astype(jnp.bfloat16), np.float32)
    pooled = [(q, as_pool[:, i]) for i, q in enumerate(seqs)]
    chk = {"state_slots": 4 if pool == "short" else 3, "tol_state": 0.008}
    got, rep = kind.check_states(ref, params, hf, chk, pooled, rehearsal=True)
    assert got is ok, rep
    if pool != "short":
        assert len(rep["slow_heads_rel_err"]) == 2 and rep["lengths"] == lens
        if not ok:  # one rounding: 2^-9 relative, under the chip's limit
            assert 5e-4 < rep["slow_heads_rel_err"][0] < 5e-3
            assert kind.check_states(ref, params, hf, chk, pooled, False)[0]


def test_slow_heads_are_those_of_the_smallest_decay_rate():
    import jax.numpy as jnp
    import numpy as np

    ref = loader._load_module("lib", "reference_nemotron_h",
                              os.path.join(REPO, "benchmarks"))
    dt = jnp.asarray([[0.1, 0.001, 0.01, 0.05, 0.002, 0.1, 0.1, 0.1]])
    p = {"layers": {"M": {"dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                          "A_log": jnp.log(jnp.asarray(
                              [[1.0, 16.0, 1.0, 1.0, 2.0, 1.0, 1.0, 1.0]]))}}}
    # rates 0.1, 0.016, 0.01, 0.05, 0.004, ...: the two slowest
    assert sorted(ref.slow_heads(p, 0)) == [2, 4]
    err = ref.state_error(np.full((1, 2, 3, 4), 1.01), np.ones((1, 2, 3, 4)))
    np.testing.assert_allclose(err, 0.01, rtol=1e-9)


class _Engine:
    def __init__(self):
        import types

        import numpy as np

        self.slot_req = [None, object(), object(), None, object()]
        self.lengths = np.asarray([9, 5, 7, 0, 6])
        self.seq_tokens = np.arange(5 * 10).reshape(5, 10)
        self.pool = types.SimpleNamespace(row=lambda s: 4 - s)
        self.cache = {"s": np.arange(2 * 5).reshape(2, 5, 1, 1, 1) * 1.0}


def test_pooled_states_are_the_longest_live_slots_rows():
    got = _kind().pooled_states(_Engine(), 2)
    assert [ids.tolist() for ids, _ in got] == [
        list(range(20, 27)), list(range(40, 46))]  # slots 2 and 4; 0 is free
    assert [s[:, 0, 0, 0].tolist() for _, s in got] == [[2.0, 7.0], [0.0, 5.0]]


def test_the_draw_makes_a_block_a_small_update_of_the_stream():
    import jax
    import jax.numpy as jnp
    import numpy as np

    hf = loader.load_config(CONFIG)
    p = {"embedding": jnp.ones((8, 4096)) / 64.0,
         "layers": {"M": {"w_out": jnp.ones((1, 4, 4)), "w_in": jnp.ones((1, 2)),
                          "dt_bias": jnp.zeros((1, 3))},
                    "*": {"attn": {"wo": jnp.ones((1, 4, 4)),
                                   "wq": jnp.ones((1, 2))}},
                    "E": {"w_l2": jnp.ones((1, 4, 4)), "ws2": jnp.ones((1, 4, 4)),
                          "w1": jnp.ones((1, 2)),
                          "router_bias": jnp.zeros((1, 512))}}}
    q = _kind().trained_like_draw(p, hf, jax.random.PRNGKey(3))
    down = 1 / np.sqrt(88)
    np.testing.assert_allclose(q["embedding"], 1.0, rtol=1e-6)
    for leaf in (q["layers"]["M"]["w_out"], q["layers"]["*"]["attn"]["wo"],
                 q["layers"]["E"]["w_l2"], q["layers"]["E"]["ws2"]):
        np.testing.assert_allclose(leaf, down, rtol=1e-6)
    for leaf in (q["layers"]["M"]["w_in"], q["layers"]["*"]["attn"]["wq"],
                 q["layers"]["E"]["w1"]):
        np.testing.assert_array_equal(leaf, 1.0)
    np.testing.assert_array_equal(q["layers"]["M"]["dt_bias"], 0.0)
    bias = np.asarray(q["layers"]["E"]["router_bias"])
    assert 0.015 < bias.std() < 0.025 and abs(bias.mean()) < 0.005
