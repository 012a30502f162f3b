"""CPU tests of what PR 42 added to the yardstick: the work functions of
`lib/afmoe_work.py` against a hand count (pairs by mask, expert operations
from a counter), the data files of `train_moe_swa_16k` through
`lib/loader.py`, the readers that read them on hand-built contexts, the
kind's refusal of a model that is not the file's (exit 4) and the cell's
CPU rehearsal (whose comparison with the float32 reference has to be near
exact there)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import afmoe_work as work
from benchmarks.lib import flops, loader

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL, CONFIG = "train_moe_swa_16k", "trinity-mini"
S, F = "sliding_attention", "full_attention"

TOY = {"num_attention_heads": 4, "head_dim": 16, "num_hidden_layers": 3,
       "layer_types": [S, F, S], "sliding_window": 4, "hidden_size": 64,
       "moe_intermediate_size": 48}


def test_pairs_by_mask_are_a_hand_count():
    # 6 tokens, window 4: rows see 1, 2, 3, 4, 4, 4 keys; causal 1..6
    assert work.pairs(6, 4) == 18 and work.pairs(6) == 21
    assert work.pairs(3, 4) == work.pairs(3) == 6  # shorter than the window
    assert work.pairs(4, 4) == work.pairs(4) == 10  # the window's edge
    assert work.pairs(5, 4) == work.pairs(5) - 1  # the first key drops out
    # two sequences of 6 and 3, two sliding layers and one full:
    # 12 x H x hd x (2 x (18 + 6) + (21 + 6))
    assert work.attention_flops([6, 3], TOY) == 12 * 4 * 16 * (48 + 27)
    assert work.attention_flops([6, 3], TOY, backward=False) == 4 * 4 * 16 * 75
    # every layer full and long sequences: the causal count of lib/flops.py,
    # but for the diagonal it counts half of
    full = {**TOY, "layer_types": [F, F, F]}
    got = work.attention_flops([4000], full)
    assert got == pytest.approx(
        3 * flops.causal_attention_flops([4000], 4, 16), rel=1e-3)
    # a causal count reads a 16k sequence's sliding layer 4.3 times too high
    assert work.pairs(16384) / work.pairs(16384, 2048) == pytest.approx(4.27, abs=0.01)


def test_expert_operations_come_from_the_counter():
    # 10 rows through gate, up, down (2 x 64 x 48 each), backward twice that
    assert work.expert_flops(10, TOY) == 18 * 64 * 48 * 10
    assert work.expert_flops(10, TOY, backward=False) == 6 * 64 * 48 * 10
    assert work.expert_flops(0, TOY) == 0


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


OWN = {
    "train_attn_local_ms_per_step", "train_attn_global_ms_per_step",
    "train_moe_ms_per_step", "train_moe_experts_ms_per_step",
    "moe_roofline.train", "train_expert_rows_per_expert",
    "train_expert_load_max_over_mean", "train_attn_blocks_run_pct"}


def test_the_cell_and_its_metrics_are_declared_and_found():
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    data = loader.load_cell(CELL)
    assert entry["config"] == data["config"] == CONFIG
    assert entry["chips"] == data["chips"] == 1
    assert entry["why"] == data["why"] and len(entry["why"]) <= 200
    loader.load_kind(data["kind"])
    found = {m["name"] for m in loader.load_layer_metrics(CELL)}
    mine = [m for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])
            and m["moves"] == "train_tokens_per_s"]
    # what every training cell inherits, and the eight of this stack: the
    # names the cell needs are THERE (how many entries a day had is not
    # this cell's to say: the contract's cap is 128)
    inherited = {m["name"] for m in mine if "workloads" not in m}
    assert found == {m["name"] for m in mine} and inherited | OWN <= found
    assert {m["name"] for m in mine if m.get("workloads") == [CELL]} == OWN
    assert {"train_device_ms_per_step", "train_host_ms_per_step",
            "train_optimizer_ms_per_step"} <= inherited
    assert loader.end_to_end_metrics(CELL) == ["train_tokens_per_s", "setup_s"]
    assert len(bench["per_layer"]) <= 128
    # train_16k's traffic and actor, but for the sizes' seed
    control = loader.load_cell("train_16k")
    assert data["actor"] == control["actor"]
    assert {k: v for k, v in data["traffic"].items() if k != "size_seed"} == {
        k: v for k, v in control["traffic"].items() if k != "size_seed"}
    assert data["traffic"]["size_seed"] != control["traffic"]["size_seed"]
    assert data["trace_seconds"] == 8


def test_the_configuration_states_its_cut_and_what_it_assumed():
    bench = _bench()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    hf = loader.load_config(CONFIG)
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types",
               "num_experts", "vocab_size"]
    assert entry["reduced"] == hf["bench"]["reduced"] == reduced
    assert set(hf["bench"]["reduced_how"]) == set(reduced)
    assert entry["source"] == hf["bench"]["source"] and len(entry["why"]) <= 200
    assert (hf["num_hidden_layers"], hf["num_dense_layers"],
            hf["num_experts"], hf["vocab_size"]) == (9, 1, 16, 25024)
    pub = hf["bench"]["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (32, 2, 128, 200192)
    # the model's own layers 1-9: S . S F S S S F S S
    assert hf["layer_types"] == pub["layer_types"][1:10]
    assert [t == S for t in hf["layer_types"]] == [
        True, True, False, True, True, True, False, True, True]
    assert hf["experts_held"] == {"first": 0, "of": 128}
    # every published width is as the catalog has it
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(x) for x in open(catalog) if '"Trinity-Mini"' in x)
        for k, v in row["config"].items():
            if k not in reduced:
                assert hf[k] == v, k
    for key, about in hf["bench"]["assumed"].items():
        assert {"value", "from"} <= set(about), key
    for key in ("published", "deployment", "bytes", "not_built", "assumed"):
        assert hf["bench"][key], key
    assert {"expert_bias_update", "load_balance_loss"} <= set(
        hf["bench"]["not_built"])
    assert hf["bench"]["reference"] == "reference_afmoe"
    # the issue's arithmetic, from the file's own keys
    D, V = hf["hidden_size"], hf["vocab_size"]
    attn = D * 4096 * 3 + D * 512 * 2 + 2 * 128
    dense = attn + 4 * D + 3 * D * hf["intermediate_size"]
    fixed = attn + 4 * D + D * 128 + 128 + 3 * D * 1024
    held = dense + 8 * (fixed + 16 * 3 * D * 1024) + 2 * V * D + D
    b = hf["bench"]["bytes"]
    assert held == b["parameters_held"] == 1_243_428_096
    assert b["trainer_state_bytes_bfloat16_params_grads_adam"] == 8 * held
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.utils.profiling import param_count

    assert param_count(TransformerConfig.from_hf(hf)) == held


def _metric(name):
    with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def test_the_counter_metrics_read_what_the_kind_hands_back():
    counters = {"expert_assignments_held": 30 * 8 * 16 * 1000.0,
                "expert_slots": 30 * 8 * 16, "expert_load_max": 30 * 1300.0,
                "expert_load_mean": 30 * 1000.0,
                "attn_blocks_run": 30 * 700.0, "attn_blocks_static": 30 * 1000.0}
    ctx = {"counters": counters, "counts": {"steps": 30}}
    read = {n: loader.load_reader(_metric(n)["reader"]) for n in OWN}

    def value(name, c=ctx):
        return read[name](c, _metric(name))

    assert value("train_expert_rows_per_expert") == pytest.approx(1000.0)
    assert value("train_expert_load_max_over_mean") == pytest.approx(1.3)
    assert value("train_attn_blocks_run_pct") == pytest.approx(70.0)
    # a program without the counters (the parent) reports nothing
    empty = {"counters": {}, "counts": {"steps": 30}}
    for name in ("train_expert_rows_per_expert",
                 "train_expert_load_max_over_mean", "train_attn_blocks_run_pct"):
        assert value(name, empty) is None
    # and the trace readers nothing without a trace
    none = {"trace": None, "peaks": None, "work": {}, "counts": {"steps": 3},
            "counters": {}}
    for name in OWN - {"train_expert_rows_per_expert",
                       "train_expert_load_max_over_mean",
                       "train_attn_blocks_run_pct"}:
        assert value(name, none) is None, name


def _run(*extra, timeout=900):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/run.py"),
         "--workload", CELL, "--seconds", "2", "--trace", "0",
         "--cpu-rehearsal", *extra],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_the_cell_s_rehearsal_is_exact():
    """The cell end to end at a toy size in float32: the actor's log-probs
    against the reference on both spans, train steps with their counters."""
    out = _run("--seed", "3000000019")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(x) for x in out.stdout.splitlines() if x.startswith("{")]
    line = lines[-1]
    assert line["correct"] and line["failed"] == 0 and line["rehearsal"]
    assert set(line["metrics"]) == {"rehearsal.train_tokens_per_s",
                                    "rehearsal.setup_s"}
    window = next(x["diag"] for x in lines
                  if x.get("diag", {}).get("phase") == "window")
    rep = window["checks"]["reference"]
    assert rep["max_abs"] < 1e-4 and rep["n"] > 50
    # the second span lies past the window (32) of the toy's sliding layers
    assert rep["spans"] == [[0, 16], [48, 63]] and max(rep["by_span"]) < 1e-5
    c = window["checks"]["counters"]
    steps = window["counts"]["steps"]
    assert c["expert_slots"] == steps * 3 * 4
    # half of the 8 experts held, top-2: about one assignment a token a layer
    assert 0.5 < c["expert_assignments_held"] / (steps * 3 * 481) < 1.5
    assert c["expert_load_max"] >= c["expert_load_mean"] > 0
    assert window["compiles_in_window"]["compiled"] == 0
    assert window["checks"]["moving"]


def test_a_program_that_does_not_know_the_family_ends_at_once(tmp_path):
    """What the parent commit does with the new cell: `from_hf` refuses the
    `model_type`, the run ends with a non-zero exit and no result line."""
    import shutil

    root = tmp_path / "benchmarks"
    for d in ("workloads", "configs"):
        os.makedirs(root / d)
    shutil.copy(os.path.join(REPO, f"benchmarks/workloads/{CELL}.json"),
                root / "workloads")
    with open(os.path.join(REPO, f"benchmarks/configs/{CONFIG}.json")) as f:
        cfg = json.load(f)
    cfg["model_type"] = "afmoe_x"
    (root / "configs" / f"{CONFIG}.json").write_text(json.dumps(cfg))
    out = _run("--seed", "1", "--bench-root", str(root), timeout=300)
    assert out.returncode not in (0, 4), out.returncode
    assert "unsupported model_type" in out.stderr
    assert not any(x.startswith('{"correct"') for x in out.stdout.splitlines())


class _Model:
    def __init__(self, kinds, sliding, held, n):
        self.ffn_kinds, self.layer_is_sliding = kinds, sliding
        self.held_range, self.num_experts = held, n


SLIDING = (True, True, False, True, True, True, False, True, True)


@pytest.mark.parametrize("model,why", [
    (_Model(("dense",) + ("moe",) * 8, SLIDING, (0, 16), 128), ""),
    (_Model(None, SLIDING, (0, 16), 128), "FFN kinds"),
    (_Model(("dense",) + ("moe",) * 8, (True,) * 9, (0, 16), 128), "sliding"),
    (_Model(("dense",) + ("moe",) * 8, SLIDING, (0, 16), 16), "held"),
])
def test_a_model_that_is_not_the_file_s_is_named(model, why):
    kind = loader._load_module("kinds", "train_ref",
                               os.path.join(REPO, "benchmarks"))
    got = kind.built_as_stated(model, loader.load_config(CONFIG))
    assert (got == "") if not why else (why in got)


def test_the_spans_follow_the_window():
    kind = loader._load_module("kinds", "train_ref",
                               os.path.join(REPO, "benchmarks"))
    hf, chk = {"sliding_window": 2048}, {"tokens": 256}
    assert kind.check_spans(hf, chk, [8658, 4059], False) == (
        2560, [(0, 256), (2304, 2559)])
    # a sequence that ends before the second span gives the first alone
    assert kind.check_spans(hf, chk, [8658, 2000], False) == (256, [(0, 255)])
