"""CPU tests of what PR 52 added to the yardstick: the data files of
`rollout_ssm_dense_4k`, `lib/jamba_work.py` against a hand count (the file's
`bench.bytes` and the program's own shapes are held in tier 1:
`tests/test_jamba_model.py`), the reader `jamba_bytes_roofline` and the scope
metrics on a hand-built trace, and on a program without the counters (the
parent of PR 52): nothing, and no raise.  The cell's rehearsal and its
control's run through `test_controls.py` and the commands in
`.claude/skills/verify/SKILL.md`."""

import json
import os

import pytest

from benchmarks.lib import jamba_work as jw
from benchmarks.lib import loader
from benchmarks.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000
CELL, CONFIG = "rollout_ssm_dense_4k", "jamba2-3b"
NEW_METRICS = (
    "rollout_ssm_ms_per_token.mamba1", "rollout_ssm_scan_ms_per_token.mamba1",
    "rollout_ffn_dense_ms_per_token.mamba1", "rollout_attn_ms_per_token.mamba1",
    "rollout_state_copy_ms_per_token.mamba1",
    "rollout_live_slots_per_pass.mamba1", "ssm_roofline.rollout_mamba1",
    "decode_roofline.rollout_mamba1",
)


def test_the_cell_and_its_metrics_are_declared_and_found():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = loader.load_cell(CELL)
    assert (cell["config"], cell["kind"], cell["chips"]) == (
        CONFIG, "rollout_ssm", 1)
    assert cell["engine"] == {"n_slots": 384, "max_seq_len": 4096}
    assert cell["traffic"]["groups_in_flight"] * cell["traffic"]["group_size"] == 384
    declared = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert declared["why"] == cell["why"] and len(cell["why"]) <= 200
    config = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert config["reduced"] == [] == loader.load_config(CONFIG)["bench"]["reduced"]
    assert len(config["why"]) <= 200 and "\n" not in config["why"]
    found = {m["name"] for m in loader.load_layer_metrics(CELL)}
    assert set(NEW_METRICS) <= found
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL], name
    # ... and every inherited metric of a rollout cell
    inherited = {m["name"] for m in bench["per_layer"]
                 if m["moves"] == "rollout_tokens_per_s" and "workloads" not in m}
    assert inherited <= found and len(found) == len(inherited) + len(NEW_METRICS)
    loader.load_kind(cell["kind"])


def test_bytes_of_a_pass_are_a_hand_count():
    hf = loader.load_config(CONFIG)
    assert jw.n_layers(hf) == (26, 2)
    mixer = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560 + 160 + 16 + 16)
    assert jw.mamba_mixer_params(hf) == mixer == 41_241_792
    assert jw.state_bytes_per_slot(hf) == 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    c = {"decode_passes": 3, "state_rows_stepped": 3 * 385}
    assert jw.ssm_bytes(hf, {}, c) == 3 * (
        26 * mixer * 2 + 2 * 385 * 9_318_400)
    assert jw.decode_bytes(hf, {}, c) == 3 * (
        2 * 3_029_337_472 + 2 * 385 * 9_318_400)
    # the issue's split of a pass's floor: state 53 %, weights 45 %
    one = {"decode_passes": 1, "state_rows_stepped": 385}
    assert 0.53 < 2 * 385 * 9_318_400 / (jw.decode_bytes(hf, {}, one) + 0.3e9) < 0.54


def _ctx():
    """Two decode programs and a prefill on one chip: `fusion.1` under the
    selective scan, `fusion.2` under `ssm` beside it, `fusion.3` under
    `ffn_dense`, `fusion.4` under `attn`."""
    ops, mods, t = [], [], 0
    for name in ("jit__decode_chunk(11)", "jit__prefill(22)",
                 "jit__decode_chunk(11)"):
        mods.append((name, t, 95 * US))
        for i, (lo, took) in enumerate(((0, 40), (40, 20), (60, 25), (85, 5))):
            ops.append((f"%fusion.{i + 1} = f32[4] fusion(%p), kind=kLoop",
                        t + lo * US, took * US))
        t += 100 * US
    paths = lambda prog: {  # noqa: E731
        "fusion.1": f"jit({prog})/while/body/layers/while/body/ssm/ssm_scan/mul",
        "fusion.2": f"jit({prog})/while/body/layers/while/body/ssm/dot",
        "fusion.3": f"jit({prog})/while/body/layers/while/body/ffn_dense/dot",
        "fusion.4": f"jit({prog})/while/body/layers/attn/dot"}
    programs = {"jit__decode_chunk": [paths("_decode_chunk")],
                "jit__prefill": [paths("_prefill")]}
    return {"trace": tr.Trace(device_ops={0: ops}, device_modules={0: mods}),
            "programs": programs, "counts": {"output_tokens": 100},
            "counters": {"decode_passes": 16, "state_rows_stepped": 16 * 385,
                         "tokens_delivered": 16 * 180},
            "work": {"n_slots": 384, "config": CONFIG},
            "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}


def _metric(name):
    with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def _value(ctx, name):
    spec = _metric(name)
    return loader.load_reader(spec["reader"])(ctx, spec)


def test_the_metrics_on_a_synthetic_trace():
    ctx, hf = _ctx(), loader.load_config(CONFIG)
    assert _value(ctx, "rollout_ssm_scan_ms_per_token.mamba1") == pytest.approx(0.12 / 100)
    assert _value(ctx, "rollout_ssm_ms_per_token.mamba1") == pytest.approx(0.18 / 100)
    assert _value(ctx, "rollout_ffn_dense_ms_per_token.mamba1") == pytest.approx(0.075 / 100)
    assert _value(ctx, "rollout_attn_ms_per_token.mamba1") == pytest.approx(0.015 / 100)
    assert _value(ctx, "rollout_state_copy_ms_per_token.mamba1") is None
    assert _value(ctx, "rollout_live_slots_per_pass.mamba1") == pytest.approx(180.0)
    # the decode programs' `ssm` scope alone (2 x 60 us) / their whole span
    args = (hf, ctx["work"], ctx["counters"])
    assert _value(ctx, "ssm_roofline.rollout_mamba1") == pytest.approx(
        100 * jw.ssm_bytes(*args) / 819e9 / 120e-6)
    assert _value(ctx, "decode_roofline.rollout_mamba1") == pytest.approx(
        100 * jw.decode_bytes(*args) / 819e9 / 190e-6)


@pytest.mark.parametrize("drop", ["peaks", "counter", "trace"])
def test_the_roofline_reader_reads_nothing_rather_than_raise(drop):
    """The parent of PR 52 counts no `state_rows_stepped`: the line then
    leaves the metric out."""
    ctx = _ctx()
    if drop == "peaks":
        ctx["peaks"] = None
    elif drop == "counter":
        del ctx["counters"]["state_rows_stepped"]
    else:
        ctx["trace"] = tr.Trace(device_ops={0: []}, device_modules={0: []})
    for name in ("ssm_roofline.rollout_mamba1", "decode_roofline.rollout_mamba1"):
        assert _value(ctx, name) is None
