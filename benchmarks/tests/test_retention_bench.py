"""CPU tests of what PR 27 added to the yardstick: the byte functions of
`lib/retention_work.py`, the reader `decode_bytes_roofline` on hand-built
traces, the data files of the two new cells, and the CPU rehearsal of
`rollout_retention` (whose comparison with the float32 reference has to be
exact there)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import loader, retention_work as rw
from benchmarks.lib import trace_reduce as tr

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000


def test_bytes_of_a_pass_are_the_issue_s_arithmetic():
    hf = loader.load_config("brumby-14b")
    assert rw.feature_dim(128) == 8256
    assert rw.layer_param_count(hf) == 330_352_896
    assert rw.state_bytes_per_slot(hf) == 8 * 8 * 8256 * 129 * 4 == 272_646_144
    assert rw.weight_bytes_per_pass(hf) == 2 * (
        8 * 330_352_896 + 151_936 * 5120 + 5120)
    assert rw.retention_state_bytes(hf, 16) == 2 * 16 * 272_646_144
    assert rw.decode_pass_bytes(hf, 16) / 819e9 == pytest.approx(0.0190, abs=1e-4)
    with pytest.raises(ValueError):
        rw.feature_dim(128, 3)


def _trace():
    """Two decode programs and one prefill on one chip; the decode programs
    hold `fusion.1` under retention and `fusion.2` under the sampler."""
    ops, mods, t = [], [], 0
    for name, parts in (("jit__decode_chunk(11)", (40, 10)),
                        ("jit__prefill(22)", (30, 5)),
                        ("jit__decode_chunk(11)", (40, 10))):
        mods.append((name, t, 60 * US))
        ops.append(("%fusion.1 = f32[4] fusion(%p), kind=kLoop", t, parts[0] * US))
        ops.append(("%fusion.2 = f32[4] fusion(%q), kind=kLoop",
                    t + parts[0] * US, parts[1] * US))
        t += 100 * US
    programs = {
        "jit__decode_chunk": [{
            "fusion.1": "jit(_decode_chunk)/while/body/layers/while/body/retention/add",
            "fusion.2": "jit(_decode_chunk)/while/body/sampler/sort"}],
        "jit__prefill": [{
            "fusion.1": "jit(_prefill)/layers/while/body/retention/dot_general",
            "fusion.2": "jit(_prefill)/sampler/sort"}],
    }
    return tr.Trace(device_ops={0: ops}, device_modules={0: mods}), programs


def _spec(**kw):
    return {"name": "m", "reader": "decode_bytes_roofline",
            "counter": "decode_passes", "config": "brumby-14b", **kw}


def test_roofline_reader_on_a_synthetic_trace():
    read = loader.load_reader("decode_bytes_roofline")
    trace, programs = _trace()
    hf = loader.load_config("brumby-14b")
    ctx = {"trace": trace, "programs": programs, "counts": {},
           "counters": {"decode_passes": 16}, "work": {"n_slots": 16},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}
    scope = r"^jit\(_decode_chunk\)/.*(^|[/(])(retention)([)/]|$)"
    got = read(ctx, _spec(scope=scope, bytes="retention_state_bytes"))
    floor_s = 16 * rw.retention_state_bytes(hf, 16) / 819e9
    # the prefill program's retention time (30 us) is not the decode path's
    assert got == pytest.approx(100 * floor_s / 80e-6)
    whole = read(ctx, _spec(modules=r"^jit__decode_chunk\(",
                            bytes="decode_pass_bytes"))
    assert whole == pytest.approx(
        100 * 16 * rw.decode_pass_bytes(hf, 16) / 819e9 / 120e-6)
    # the metric files say the same thing
    for name in ("retention_roofline.rollout", "decode_roofline.rollout_retention"):
        with open(os.path.join(REPO, "benchmarks/layer_metrics", f"{name}.json")) as f:
            m = json.load(f)
        assert read(ctx, m) in (pytest.approx(got), pytest.approx(whole))


@pytest.mark.parametrize("drop", ["peaks", "counter", "n_slots", "trace",
                                  "nothing_ran"])
def test_roofline_reader_reads_nothing_rather_than_raise(drop):
    """An earlier commit of the program has neither the scope nor the
    counter; a rehearsal has no peaks."""
    read = loader.load_reader("decode_bytes_roofline")
    trace, programs = _trace()
    ctx = {"trace": trace, "programs": programs, "counts": {},
           "counters": {"decode_passes": 16}, "work": {"n_slots": 16},
           "peaks": {"hbm_bytes_per_s": 819e9}, "window_s": 1.0}
    spec = _spec(scope="/retention/", bytes="retention_state_bytes")
    if drop == "peaks":
        ctx["peaks"] = None
    elif drop == "counter":
        ctx["counters"] = {}
    elif drop == "n_slots":
        ctx["work"] = {}
    elif drop == "trace":
        ctx["trace"] = None
    else:
        spec["scope"] = "/no_such_scope/"
    assert read(ctx, spec) is None


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


# copies that `tests/test_host_totals.py` opens by name; a benchmark PR may
# not edit that file, so these three wait for a PR that may (PERF.md, 7)
KEPT_BY_A_TIER_1_TEST = {
    "train_pack_ms_per_step.16k", "train_update_dispatch_ms_per_step.16k",
    "rollout_live_slots_per_pass.retention"}


@pytest.mark.parametrize("cell", ["rollout_retention", "train_16k"])
def test_new_cells_and_their_metrics_are_declared_and_found(cell):
    bench = _bench()
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    data = loader.load_cell(cell)
    assert entry["config"] == data["config"] and entry["chips"] == data["chips"] == 1
    assert entry["why"] == data["why"] and len(entry["why"]) <= 200
    loader.load_kind(data["kind"])
    found = {m["name"] for m in loader.load_layer_metrics(cell)}
    e2e = loader.end_to_end_metrics(cell)
    assert "setup_s" in e2e and len(e2e) == 2
    # an entry without `workloads` is every cell's that reports its `moves`
    declared = {m["name"] for m in bench["per_layer"]
                if cell in m.get("workloads", [cell]) and m["moves"] in e2e}
    assert found == declared and found
    assert not any(n.endswith(".16k") or n.endswith(".retention")
                   for n in found
                   if n not in KEPT_BY_A_TIER_1_TEST)


def test_train_16k_packs_two_long_sequences_into_one_row():
    from benchmarks.lib import traffic

    cell = loader.load_cell("train_16k")
    pairs = traffic.train_sequence_lengths(cell["traffic"])
    assert [p + r for p, r in pairs] == [8682, 7442]
    assert cell["actor"] == loader.load_cell("train_2k")["actor"]
    assert cell["check"] == loader.load_cell("train_2k")["check"]


def test_the_configuration_states_its_cut_and_what_it_assumed():
    bench = _bench()
    entry = next(c for c in bench["configs"] if c["name"] == "brumby-14b")
    hf = loader.load_config("brumby-14b")
    assert entry["reduced"] == hf["bench"]["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == hf["bench"]["source"]
    assert hf["num_hidden_layers"] == 8 and hf["bench"]["published"] == {
        "num_hidden_layers": 40}
    for key in ("retention_degree", "gate", "qk_norm", "rope", "score_scale",
                "normaliser", "retention_eps", "retention_chunk", "state",
                "gate_checkpoint_name", "gate_draw"):
        assert {"value", "from"} <= set(hf["bench"]["assumed"][key]), key
    assert hf["bench"]["state_dtype"] == "float32"


def test_rollout_retention_rehearsal_is_exact():
    """The cell end to end at a toy size: closed loop, fan-out, late
    siblings, the pool freed, then the float32 reference, in float32."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/run.py"),
         "--workload", "rollout_retention", "--seed", "3000000019",
         "--seconds", "2", "--trace", "0", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
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
    c = window["checks"]["counters"]
    assert c["state_copies"] > 0 and c["sibling_reprefills"] > 0
    assert window["compiles_in_window"]["compiled"] == 0


def test_a_program_without_the_kind_is_stopped_at_once(tmp_path):
    """What the parent commit does with the new cell: the kind exits with
    code 4 before anything is built (here: a configuration that builds a
    softmax model under the cell's kind)."""
    import shutil

    root = tmp_path / "benchmarks"
    for d in ("workloads", "configs"):
        os.makedirs(root / d)
    shutil.copy(os.path.join(REPO, "benchmarks/workloads/rollout_retention.json"),
                root / "workloads")
    with open(os.path.join(REPO, "benchmarks/configs/brumby-14b.json")) as f:
        cfg = json.load(f)
    cfg["model_type"] = "qwen3"
    (root / "configs" / "brumby-14b.json").write_text(json.dumps(cfg))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks/run.py"),
         "--workload", "rollout_retention", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--cpu-rehearsal", "--bench-root", str(root)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 4, (out.returncode, out.stderr[-1000:])
    assert "not power retention" in out.stderr
    assert not any(x.startswith('{"correct"') for x in out.stdout.splitlines())


def _kind():
    return loader._load_module("kinds", "rollout_ref",
                               os.path.join(REPO, "benchmarks"))


@pytest.mark.parametrize("case,why", [
    ("float32", ""),
    ("bfloat16", "is bfloat16, not float32"),
    ("short", "bytes in"),
    ("columns", "pool leaves"),
])
def test_a_pool_that_is_not_as_stated_is_named(case, why):
    """`bench.state_dtype` is held by looking at the pool, not by the
    log-probs alone: a narrower pool stops the run (exit 5 in `run`)."""
    import jax.numpy as jnp

    hf = loader.load_config("brumby-14b")
    hf = {**hf, "num_hidden_layers": 1, "num_key_value_heads": 1,
          "num_attention_heads": 1, "head_dim": 4}
    F, rows = 10, 3  # 2 slots and the engine's scratch row
    dt = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    cache = {"s": jnp.zeros((1, rows, 1, F, 4), dt),
             "z": jnp.zeros((1, rows, 1, F), dt)}
    if case == "short":
        cache["z"] = cache["z"][..., :5]
    if case == "columns":
        cache = {"k": cache["s"], "v": cache["s"]}
    got = _kind().pool_as_stated(cache, hf, 2)
    assert (got == "") if not why else (why in got), got


def test_the_gate_draw_puts_gates_near_one():
    """A random bias-free gate remembers 1 / ln 2 tokens at most; with the
    configuration's `gate_draw` the float32 reference reads tens of tokens
    and more at a toy width (hundreds to thousands at the real one)."""
    import jax
    import numpy as np

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig

    hf = loader.load_config("brumby-14b")
    hf = {**hf, "hidden_size": 256, "intermediate_size": 512,
          "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
          "num_hidden_layers": 2, "vocab_size": 512}
    ref = loader._load_module("lib", "reference_power_retention",
                              os.path.join(REPO, "benchmarks"))
    cfg = TransformerConfig.from_hf(hf).replace(
        dtype="float32", param_dtype="float32", remat=False)
    plain = init_params(cfg, jax.random.PRNGKey(5))
    ids = np.random.default_rng(0).integers(0, 512, (2, 64))
    memory = {}
    for name, p in (("plain", plain),
                    ("drawn", _kind().trained_like_gates(plain, hf))):
        log = []
        ref.hidden_states(p, hf, ids, log)
        memory[name] = [-1.0 / g for g in log]
    assert max(memory["plain"]) < 1.0 / np.log(2.0) + 1e-3
    assert min(memory["drawn"]) > 20.0, memory
