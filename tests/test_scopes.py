"""The tracing vocabulary: `jax.named_scope` names, the `areal/` host span
helper and the step-phase counters in `GenEngine.stats`.

The scope names are a closed set (`utils/profiling.py SCOPES`): the
benchmark's per-layer metrics select device time by them, so a name that
drifts silently takes a metric with it.  These tests lower the hot programs
at toy size on the CPU and read the `op_name` paths of the compiled HLO."""

import os
import re
import time

import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config
from areal_tpu.utils import stats as stats_tracker
from areal_tpu.utils import telemetry
from areal_tpu.utils.profiling import SCOPE_NAMES

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "areal_tpu")
LAYER_PARTS = ("attn_qkv", "attn", "attn_out", "mlp")
STEP_PHASES = ("admit", "sync", "dispatch", "fetch", "deliver")


def _instructions(hlo_text):
    """[(instruction name, opcode, op_name path)] of every instruction that
    carries an `op_name`."""
    rx = re.compile(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(.*op_name="([^"]*)"',
        re.M,
    )
    return rx.findall(hlo_text)


def _paths(hlo_text):
    return {p for _, _, p in _instructions(hlo_text)}


def _under(paths, *scopes, word=None, no_word=None):
    """Is there a path that runs through every one of `scopes` in order (each
    as a component, bare or wrapped as `jvp(x)` / `transpose(jvp(x))`),
    holding `word` and none of `no_word`?"""
    rx = re.compile(
        ".*".join(rf"(?:^|[/(]){re.escape(s)}(?:[)/]|$)" for s in scopes)
    )
    return any(
        rx.search(p)
        and (word is None or word in p)
        and not any(w in p for w in (no_word or ()))
        for p in paths
    )


def test_only_names_of_the_vocabulary_are_used_in_the_package():
    """`grep named_scope areal_tpu`: every string in a call is in SCOPES,
    and every name of SCOPES is used somewhere."""
    used = set()
    call = re.compile(r"named_scope\(([^)]*)\)")
    for root, _, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                for args in call.findall(f.read()):
                    names = re.findall(r'"([^"]+)"', args)
                    assert names, f"{fn}: named_scope({args}) names no literal"
                    used.update(names)
    assert used - set(SCOPE_NAMES) == set(), used - set(SCOPE_NAMES)
    assert set(SCOPE_NAMES) - used == set(), set(SCOPE_NAMES) - used
    assert len(set(SCOPE_NAMES)) == len(SCOPE_NAMES)


@pytest.fixture(scope="module")
def train_paths():
    """op_name paths of the real train-step program (GRPO loss, remat on)
    and of the log-prob forward, from a toy `JaxPPOActor`."""
    from areal_tpu.engine.ppo import JaxPPOActor

    # tied head, as the benchmark's models: `lm_head` then holds the
    # embedding's transpose
    model_cfg = tiny_config(vocab_size=64, qkv_bias=True,
                            hf_architecture="Qwen2ForCausalLM",
                            tie_word_embeddings=True)
    cfg = PPOActorConfig(
        experiment_name="t", trial_name="t", init_from_scratch=True,
        dtype="float32", gradient_checkpointing=True, mesh=MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                  weight_decay=0.0),
        pack_length_quantum=16, group_size=4, ppo_n_minibatches=1,
        eps_clip=0.2,
    )
    actor = JaxPPOActor(cfg, model_config=model_cfg)
    actor.initialize(ft_spec=FinetuneSpec(1, 64, 8))
    rng = np.random.default_rng(0)
    B, L = 8, 16
    loss_mask = np.zeros((B, L), np.float32)
    loss_mask[:, 4:] = 1.0
    batch = {
        "input_ids": rng.integers(0, 64, (B, L)).astype(np.int32),
        "attention_mask": np.ones((B, L), bool),
        "loss_mask": loss_mask,
        "logprobs": rng.normal(-1.0, 0.1, (B, L)).astype(np.float32) * loss_mask,
        "rewards": rng.integers(0, 2, B).astype(np.float32),
        "versions": np.zeros((B, L), np.int32),
    }
    batch["prox_logp"] = actor.compute_logp(batch)
    actor.compute_advantages(batch)
    actor.ppo_update(batch)
    eng = actor  # JaxPPOActor is the train engine
    fwd_key = next(k for k in eng._forward_cache if k[0] == "fwd")
    fwd = eng._forward_cache[fwd_key]
    yield {
        "train": _paths(eng.train_step_hlo()),
        "export": eng.export_device_params,
        "forward_name": fwd.__name__,
    }
    actor.destroy()


def test_train_step_names_every_layer_in_forward_remat_and_backward(train_paths):
    p = train_paths["train"]
    assert any(x.startswith("jit(train_step)/") for x in p)
    for part in LAYER_PARTS:
        # forward: under jvp(layers), not in the backward
        assert _under(p, "layers", part, no_word=("transpose(", "rematted")), part
        # the remat forward inside the backward scan
        assert _under(p, "layers", part, word="rematted_computation"), part
        # the backward proper
        assert _under(p, "layers", part, word="transpose(",
                      no_word=("rematted",)), part
    for scope in ("embed", "final_norm", "lm_head", "optimizer"):
        assert _under(p, scope), scope
    # the loss holds the fused cross-entropy, forward and hand-written backward
    assert _under(p, "loss", "xent", no_word=("transpose(",))
    assert _under(p, "loss", "xent", word="transpose(")
    # the optimizer is not differentiated, nothing of the layers leaks into it
    assert not _under(p, "optimizer", "layers")


def test_trainer_programs_have_names_of_their_own(train_paths):
    import jax

    assert train_paths["forward_name"] == "fwd_step"
    train_paths["export"]()
    names = {e.hlo_modules()[0].name
             for e in jax.devices()[0].client.live_executables()}
    assert {"jit_train_step", "jit_fwd_step", "jit_gae_padded",
            "jit_export_params"} <= names, names


@pytest.fixture(scope="module")
def engine():
    import jax

    cfg = tiny_config(vocab_size=97, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    # the copy path's names: its window read and its one scatter are what
    # `kv_write` means (the kernel's programs: the test after these)
    return GenEngine(cfg, params=params, n_slots=4, max_seq_len=128,
                     prompt_bucket=16, decode_chunk=4, ragged_attn=False)


def _group(n, prompt, new, tag):
    return [GenRequest(rid=f"{tag}-{i}", input_ids=list(prompt),
                       max_new_tokens=new, temperature=1.0,
                       group_id=tag, group_n=n) for i in range(n)]


def _programs_compiled_by(run):
    """Compiled text, by module name, of the programs `run()` compiles.
    Only those: an engine of another test file that this worker ran before
    keeps its executables until the collector gets to it, and its
    `jit__decode_chunk` has another kind's layers."""
    import jax

    client = jax.devices()[0].client
    before = client.live_executables()
    run()
    out = {}
    for e in client.live_executables():
        if not any(e is b for b in before):
            m = e.hlo_modules()[0]
            out.setdefault(m.name, []).append(m.to_string())
    return out


@pytest.fixture(scope="module")
def engine_programs(engine):
    """Compiled text of the engine's programs by module name, after a group
    of siblings went through it (fresh prefill, suffix prefill with the
    fused fan-out copy, decode chunks)."""
    prompt = list(range(3, 3 + 21))
    out = _programs_compiled_by(
        lambda: engine.generate_blocking(_group(3, prompt, 6, "warm")))
    assert engine.stats["suffix_calls"] >= 1 and engine.stats["copy_calls"] >= 1
    out = {name: out[name] for name in out if name in (
        "jit__prefill", "jit__suffix_prefill", "jit__decode_chunk")}
    assert set(out) == {"jit__prefill", "jit__suffix_prefill",
                        "jit__decode_chunk"}, set(out)
    return out


@pytest.mark.parametrize("program,extra", [
    ("jit__prefill", ()),
    ("jit__suffix_prefill", ("kv_copy",)),
    ("jit__decode_chunk", ()),
])
def test_engine_programs_name_every_layer(engine_programs, program, extra):
    for text in engine_programs[program]:
        p = _paths(text)
        for part in LAYER_PARTS:
            assert _under(p, "layers", part), (program, part)
        # a layer reads its window of the cache (fresh prefill attends the
        # prompt's own K/V and reads none); the write comes after the scan
        if program != "jit__prefill":
            assert _under(p, "layers", "kv_write"), program
        for scope in ("embed", "final_norm", "lm_head", "sampler",
                      "kv_write") + extra:
            assert _under(p, scope), (program, scope)
        # nothing is differentiated or rematerialised when serving
        assert not any("transpose(" in x or "rematted" in x for x in p)


def test_sampler_sort_and_cache_update_sit_under_their_scopes(engine_programs):
    for text in engine_programs["jit__decode_chunk"]:
        ins = _instructions(text)
        ranked = [p for _, op, p in ins if op in ("sort", "topk")
                  or "TopK" in p or p.endswith("/top_k")]
        assert ranked and all("/sampler/" in p for p in ranked), ranked
        writes = [p for _, op, p in ins
                  if op in ("scatter", "dynamic-update-slice")
                  and p.endswith("/scatter")]
        # ONE scatter a leaf for all layers' new columns, after the layer
        # scan: no layer writes the cache
        assert len(writes) == 2 and all(
            "/kv_write/" in p and "/layers/" not in p for p in writes), writes
        # the score and value products are attention's, not the cache's
        dots = [p for _, op, p in ins if p.endswith("/dot_general")
                and "/layers/" in p]
        assert any("/attn/" in p for p in dots)
        assert not any("/kv_write/" in p for p in dots)


def test_the_paged_kernel_s_decode_chunk_writes_inside_attn():
    """An engine left to its default takes the paged attention kernel: the
    decode chunk names every part of a layer, and the cache write is the
    kernel's own, so all of it is `attn` and no `kv_write` is left in the
    program (`loop_attn_ms_per_pass` reads that scope)."""
    import jax

    cfg = tiny_config(vocab_size=89, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    eng = GenEngine(cfg, params=init_params(cfg, jax.random.PRNGKey(0)),
                    n_slots=4, max_seq_len=128, prompt_bucket=16,
                    decode_chunk=4)
    assert eng._ragged_ok
    eng.generate_blocking(_group(2, list(range(3, 3 + 21)), 6, "kwarm"))
    texts = [
        m.to_string()
        for e in jax.devices()[0].client.live_executables()
        for m in e.hlo_modules()[:1]
        if m.name == "jit__decode_chunk" and "89]" in m.to_string()
    ]
    assert texts
    for text in texts:
        p = _paths(text)
        for part in LAYER_PARTS:
            assert _under(p, "layers", part), part
        assert not _under(p, "kv_write")
        for scope in ("embed", "final_norm", "lm_head", "sampler"):
            assert _under(p, scope), scope


@pytest.fixture(scope="module")
def retention_programs():
    """The same three programs of an engine whose model is of the
    power-retention kind (a group of siblings went through it)."""
    import jax

    from areal_tpu.models.model_config import TransformerConfig

    cfg = TransformerConfig.from_hf({
        "model_type": "brumby", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 101,
        "tie_word_embeddings": False,
    }).replace(dtype="float32", remat=False, retention_chunk=8,
               eos_token_id=None)
    eng = GenEngine(cfg, params=init_params(cfg, jax.random.PRNGKey(0)),
                    n_slots=4, max_seq_len=128, prompt_bucket=16,
                    decode_chunk=4)
    out = _programs_compiled_by(lambda: eng.generate_blocking(
        _group(3, list(range(3, 3 + 21)), 6, "rwarm")))
    assert eng.stats["state_copies"] == 2
    return {name: [t for t in texts if "/retention/" in t]
            for name, texts in out.items() if name.startswith("jit__")}


@pytest.mark.parametrize("program,extra", [
    ("jit__prefill", ()),
    ("jit__suffix_prefill", ("state_copy",)),
    ("jit__decode_chunk", ()),
])
def test_retention_programs_name_every_layer(retention_programs, program,
                                             extra):
    assert retention_programs[program]
    for text in retention_programs[program]:
        p = _paths(text)
        for part in ("attn_qkv", "retention", "attn_out", "mlp") + extra:
            assert _under(p, "layers", part), (program, part)
        for scope in ("embed", "final_norm", "lm_head", "sampler"):
            assert _under(p, scope), (program, scope)
        # in place of attention over columns and the cache write
        assert not _under(p, "layers", "attn")
        assert not _under(p, "layers", "kv_write")
        assert not _under(p, "kv_copy")


def test_the_state_s_update_and_read_out_sit_under_retention(
        retention_programs):
    for text in retention_programs["jit__decode_chunk"]:
        ins = _instructions(text)
        writes = [p for _, op, p in ins if op == "dynamic-update-slice"
                  and "/layers/" in p and "/retention/" in p]
        assert writes, [p for _, op, p in ins if op == "dynamic-update-slice"]
        dots = [p for _, _, p in ins if p.endswith("/dot_general")
                and "/retention/" in p]
        assert dots


@pytest.fixture(scope="module")
def selective_scan_chunks():
    """Compiled text of the decode chunks of an engine whose model is a
    hybrid stack of selective-scan layers (the `jamba` toy), left to its
    default: the state kernel, interpreted, so its steps are plain
    operations under the call's name."""
    from tests.test_jamba_model import CFG, _params

    eng = GenEngine(CFG, params=_params(), n_slots=4, max_seq_len=128,
                    prompt_bucket=16, decode_chunk=4, kv_dtype="float32")
    assert eng.ragged_attn
    out = _programs_compiled_by(lambda: eng.generate_blocking(
        _group(3, list(range(3, 3 + 21)), 6, "swarm")))
    assert eng.stats["ragged_dispatches"] == eng.stats["decode_calls"] > 0
    return out["jit__decode_chunk"]


def test_the_state_kernel_s_call_sits_under_ssm_scan(selective_scan_chunks):
    """`rollout_ssm_scan_ms_per_token.mamba1`, `rollout_ssm_ms_per_token.
    mamba1` and `ssm_roofline.rollout_mamba1` find the kernel's device time
    by `layers/.../ssm/ssm_scan`; the window's slice and update stay under
    `ssm`, and nothing of the state is sliced out or written back there."""
    assert selective_scan_chunks
    for text in selective_scan_chunks:
        ins = _instructions(text)
        calls = {p for _, _, p in ins if "/mamba1_decode/" in p}
        assert calls
        for p in calls:
            assert re.match(r"jit\(_decode_chunk\)/", p), p
            assert _under({p}, "layers", "ssm_scan"), p
            assert "/ssm/ssm_scan/mamba1_decode/" in p, p
        # the convolution window alone is written back a block at a time
        writes = [(name, p) for name, op, p in ins
                  if op == "dynamic-update-slice" and "/layers/" in p
                  and "/ssm/" in p and "/mamba1_decode/" not in p]
        assert writes and all(
            p.endswith("/ssm/dynamic_update_slice") for _, p in writes), writes


@pytest.mark.parametrize("fn,static", [
    ("gather_kv_prefix", (2,)),
    ("scatter_kv_prefix", ()),
    ("copy_kv_prefix", (3,)),
])
def test_cache_copies_sit_under_kv_copy(fn, static):
    """The host tier's gather and scatter are programs of their own (the
    engine jits them bare), the fan-out copy is fused into the suffix
    prefill: all three carry the scope themselves."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kv_copy

    cache = {k: jnp.zeros((2, 5, 32, 2, 4), jnp.float32) for k in ("k", "v")}
    row = jnp.asarray(1, jnp.int32)
    args = {
        "gather_kv_prefix": (cache, row, 16),
        "scatter_kv_prefix": (
            cache, {k: jnp.ones((2, 16, 2, 4)) for k in cache}, row),
        "copy_kv_prefix": (cache, jnp.asarray([0, 0], jnp.int32),
                           jnp.asarray([1, 2], jnp.int32), 16),
    }[fn]
    text = jax.jit(getattr(kv_copy, fn), static_argnums=static).lower(
        *args).compile().as_text()
    # a parameter's op_name is its argument's name; operations start `jit(`
    p = {x for x in _paths(text) if x.startswith("jit(")}
    assert p and all(_under([x], "kv_copy") for x in p), p


def test_step_phases_tile_step_and_count_decode_passes(engine):
    eng = engine
    s0 = dict(eng.stats)
    for i, r in enumerate(_group(4, list(range(5, 5 + 19)), 24, "tile")):
        r.rid, r.group_id, r.group_n = f"tile-{i}", None, 1
        eng.submit(r)
    wall = 0.0
    steps = 0
    while eng.active_count() or steps == 0:
        t0 = time.perf_counter()
        eng.step()
        wall += time.perf_counter() - t0
        steps += 1
        assert steps < 200
    d = {k: eng.stats[k] - s0[k] for k in eng.stats}
    phases = sum(d[f"t_step_{ph}_s"] for ph in STEP_PHASES)
    assert all(d[f"t_step_{ph}_s"] > 0 for ph in STEP_PHASES), d
    assert phases <= wall
    assert phases >= 0.95 * wall, (phases, wall)
    assert d["engine_steps"] == steps
    assert d["decode_calls"] >= 6
    assert d["decode_passes"] == eng.decode_chunk * d["decode_calls"]
    assert d["admitted"] == 4 and d["t_queue_wait_s"] > 0


def test_tokens_delivered_is_what_the_decode_passes_handed_out(engine):
    """`tokens_delivered` over `decode_passes` is the slots live in a pass:
    every token of every request except its first, which its prefill
    sampled and the admit phase recorded."""
    eng = engine
    s0 = dict(eng.stats)
    reqs = [GenRequest(rid=f"live-{i}", input_ids=list(range(7, 7 + 11 + i)),
                       max_new_tokens=5 + 6 * i, temperature=1.0)
            for i in range(4)]
    returned = 0
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.active_count() or steps == 0:
        returned += eng.step()
        steps += 1
        assert steps < 200
    d = {k: eng.stats[k] - s0[k] for k in eng.stats}
    outputs = sum(len(r.output_tokens) for r in reqs)
    assert outputs == sum(r.max_new_tokens for r in reqs)
    assert d["tokens_delivered"] == returned == outputs - len(reqs)
    assert 0 < d["tokens_delivered"] / d["decode_passes"] <= eng.n_slots


def test_span_adds_to_totals_nests_and_survives_an_exception():
    totals = {}
    with telemetry.span("outer", totals):
        with telemetry.span("inner", totals):
            time.sleep(0.002)
        with telemetry.span("inner", totals):
            time.sleep(0.002)
    assert totals["t_outer_s"] >= totals["t_inner_s"] >= 0.004
    with pytest.raises(KeyError):
        with telemetry.span("broken", totals):
            raise KeyError("x")
    assert totals["t_broken_s"] >= 0.0
    with telemetry.span("untimed"):  # annotation only
        pass
    assert "t_untimed_s" not in totals


def test_span_leaves_the_event_log_alone():
    was = telemetry.is_enabled()
    telemetry.set_enabled(True)
    try:
        n0 = len(telemetry.EVENTS)
        with telemetry.span("quiet", {}):
            pass
        assert len(telemetry.EVENTS) == n0
    finally:
        telemetry.set_enabled(was)


def test_record_timing_is_a_span_and_still_fills_the_timings():
    tr = stats_tracker.StatsTracker()
    with tr.record_timing("rollout"):
        time.sleep(0.002)
    with tr.scope("actor"), tr.record_timing("ppo_update"):
        pass
    assert tr._timing["rollout"][0] >= 0.002
    assert len(tr._timing["actor/ppo_update"]) == 1
