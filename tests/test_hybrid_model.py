"""A hybrid stack (`model_type: "nemotron_h"`: Mamba-2, attention and latent
mixture-of-experts blocks, each one mixer under one pre-norm) against the
plain float32 reference the benchmark carries
(`benchmarks/lib/reference_nemotron_h.py`), at a toy size on the CPU:
pattern `ME*ME`, hidden 64, 8 routed experts top-3 of which 4 are held,
state 16, float32, seeded random weights."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf as hf_io
from areal_tpu.models import moe
from areal_tpu.models import transformer as tf
from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.ops import mamba2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.lib import reference_nemotron_h as ref  # noqa: E402

HF = {
    "model_type": "nemotron_h", "architectures": ["NemotronHForCausalLM"],
    "hybrid_override_pattern": "ME*ME", "num_hidden_layers": 5,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "vocab_size": 128, "n_routed_experts": 4,
    "experts_held": {"first": 2, "of": 8}, "num_experts_per_tok": 3,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "layer_norm_epsilon": 1e-5,
    "intermediate_size": 48, "max_position_embeddings": 4096,
    "rope_theta": 10000, "tie_word_embeddings": False,
}


def _cfg(hf=HF):
    return TransformerConfig.from_hf(hf).replace(
        dtype="float32", param_dtype="float32", remat=False, eos_token_id=None)


CFG = _cfg()


def _params(cfg=CFG, seed=0):
    p = tf.init_params(cfg, jax.random.PRNGKey(seed))
    # selection (score + bias) and weight (score) must differ
    p["layers"]["E"]["router_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 5), p["layers"]["E"]["router_bias"].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 128, (3, 30)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(ref.logits(params, HF, ids))


def test_from_hf_builds_the_three_kinds_and_the_share():
    assert CFG.layer_kinds == ("M", "E", "*", "M", "E")
    assert CFG.num_experts == 8 and CFG.experts_held == (2, 6)
    assert CFG.held_range == (2, 6) and CFG.pos_emb == "none"
    assert CFG.router_kind == "sigmoid" and CFG.routed_scaling_factor == 2.5
    assert tf.slot_kind(CFG).holds == {"kv", "state"}
    assert tf.slot_kind(CFG.replace(layer_kinds=None)).holds == {"kv"}


def test_to_hf_round_trips():
    again = _cfg(CFG.to_hf_dict())
    assert again == CFG
    whole = _cfg({**HF, "n_routed_experts": 8, "experts_held": None})
    assert whole.experts_held is None and whole.held_range == (0, 8)
    assert "experts_held" not in whole.to_hf_dict()


@pytest.mark.parametrize("bad", [
    {"hybrid_override_pattern": "MEX*E"},
    {"hybrid_override_pattern": "ME*M"},
    {"experts_held": {"first": 6, "of": 8}},
    {"n_group": 2},
    {"mlp_hidden_act": "silu"},
    {"use_bias": True},
])
def test_from_hf_refuses_what_it_does_not_build(bad):
    with pytest.raises(ValueError):
        TransformerConfig.from_hf({**HF, **bad})


def test_the_catalog_s_config_is_built_at_published_widths():
    cfg = TransformerConfig.from_hf(
        os.path.join(REPO, "benchmarks/configs/nemotron3-super-120b.json"))
    assert "".join(cfg.layer_kinds) == "MEMEMEM*EME"
    assert (cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.mamba_n_groups, cfg.conv_kernel) == (
        4096, 128, 64, 128, 8, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (32, 2, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.held_range) == (512, 22, 5.0, (0, 128))
    assert (cfg.moe_latent_size, cfg.moe_intermediate_size,
            cfg.moe_shared_intermediate_size, cfg.vocab_size) == (
        1024, 2688, 5376, 32768)
    cache = jax.eval_shape(lambda: tf.init_kv_cache(cfg, 129, 2048))
    assert cache["k"].shape == (1, 129, 2048, 2, 128)
    assert cache["s"].shape == (5, 129, 128, 64, 128)
    assert cache["s"].dtype == jnp.float32
    assert cache["c"].shape == (5, 129, 3, 10240)


def test_checkpoint_names_round_trip(params):
    state = list(hf_io.params_to_hf_state(params, CFG))
    names = {n for n, _ in state}
    for want_name in (
        "backbone.embeddings.weight", "backbone.norm_f.weight",
        "lm_head.weight", "backbone.layers.0.norm.weight",
        "backbone.layers.0.mixer.in_proj.weight",
        "backbone.layers.0.mixer.conv1d.weight",
        "backbone.layers.0.mixer.conv1d.bias",
        "backbone.layers.0.mixer.A_log", "backbone.layers.0.mixer.D",
        "backbone.layers.0.mixer.dt_bias",
        "backbone.layers.0.mixer.norm.weight",
        "backbone.layers.0.mixer.out_proj.weight",
        "backbone.layers.2.mixer.q_proj.weight",
        "backbone.layers.2.mixer.o_proj.weight",
        "backbone.layers.1.mixer.gate.weight",
        "backbone.layers.1.mixer.gate.e_score_correction_bias",
        # the share holds experts 2-5 of 8, under their own ids
        "backbone.layers.1.mixer.experts.2.up_proj.weight",
        "backbone.layers.4.mixer.experts.5.down_proj.weight",
        "backbone.layers.1.mixer.shared_experts.up_proj.weight",
        "backbone.layers.1.mixer.fc1_latent_proj.weight",
        "backbone.layers.1.mixer.fc2_latent_proj.weight",
    ):
        assert want_name in names
    assert "backbone.layers.1.mixer.experts.0.up_proj.weight" not in names
    conv = dict(state)["backbone.layers.0.mixer.conv1d.weight"]
    assert conv.shape == (CFG.mamba_conv_dim, 1, 4)  # torch Conv1d
    back = hf_io.state_to_params(iter(state), CFG, "float32")
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_an_incomplete_checkpoint_is_refused(params):
    state = [(n, a) for n, a in hf_io.params_to_hf_state(params, CFG)
             if n != "backbone.layers.4.mixer.experts.3.up_proj.weight"]
    with pytest.raises(ValueError, match="E.w1"):
        hf_io.state_to_params(iter(state), CFG, "float32")


# ---------------------------------------------------------------------------
# (b) Mamba-2: the chunked form equals the step recurrence
# ---------------------------------------------------------------------------


def _ssm_inputs(B, T, seed=0):
    H, P, G, N = 8, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (B, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0),
        A=-jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5)),
        Bm=jax.random.normal(ks[3], (B, T, G, N)),
        Cm=jax.random.normal(ks[4], (B, T, G, N)),
        D=jax.random.normal(ks[5], (H,)),
    )


def _by_steps(a, T0=0, T1=None, state=None):
    B, T = a["x"].shape[:2]
    T1 = T if T1 is None else T1
    S = jnp.zeros((B, 8, 8, 16)) if state is None else state
    ys = []
    for t in range(T0, T1):
        y, S = mamba2.ssd_step(a["x"][:, t], a["dt"][:, t], a["A"],
                               a["Bm"][:, t], a["Cm"][:, t], a["D"], S)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("T", [8, 16, 13, 21, 5])
def test_chunked_ssd_equals_the_step_recurrence(T):
    a = _ssm_inputs(2, T)
    seg = jnp.zeros((2, T), jnp.int32)
    y, S = mamba2.ssd_chunked(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"],
                              a["D"], seg, chunk=8)
    want_y, want_S = _by_steps(a)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)


def test_chunked_ssd_continues_from_a_state_and_skips_padding():
    a = _ssm_inputs(2, 21, seed=1)
    _, S9 = _by_steps(a, 0, 9)
    want_y, want_S = _by_steps(a, 9, 21, S9)
    tail = {k: (v[:, 9:] if v.ndim > 1 else v) for k, v in a.items()}
    # row 1 ends 4 tokens early: its state is the one after 17 tokens
    seg = jnp.asarray([[0] * 12, [0] * 8 + [-1] * 4], jnp.int32)
    y, S = mamba2.ssd_chunked(tail["x"], tail["dt"], tail["A"], tail["Bm"],
                              tail["Cm"], tail["D"], seg, state0=S9, chunk=8)
    np.testing.assert_allclose(y[0], want_y[0], atol=2e-5)
    np.testing.assert_allclose(y[1, :8], want_y[1, :8], atol=2e-5)
    np.testing.assert_allclose(S[0], want_S[0], atol=2e-5)
    np.testing.assert_allclose(S[1], _by_steps(a, 9, 17, S9)[1][1], atol=2e-5)


def test_a_new_segment_starts_from_an_empty_state():
    a = _ssm_inputs(1, 20, seed=2)
    seg = jnp.asarray([[0] * 11 + [1] * 9], jnp.int32)
    y, _ = mamba2.ssd_chunked(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"],
                              a["D"], seg, chunk=8)
    second = {k: (v[:, 11:] if v.ndim > 1 else v) for k, v in a.items()}
    np.testing.assert_allclose(y[:, 11:], _by_steps(second)[0], atol=2e-5)
    np.testing.assert_allclose(y[:, :11], _by_steps(a, 0, 11)[0], atol=2e-5)


def test_an_idle_slot_keeps_state_and_window_to_the_bit():
    a = _ssm_inputs(2, 1, seed=3)
    S = jax.random.normal(jax.random.PRNGKey(9), (2, 8, 8, 16))
    active = jnp.asarray([True, False])
    _, S1 = mamba2.ssd_step(a["x"][:, 0], a["dt"][:, 0], a["A"], a["Bm"][:, 0],
                            a["Cm"][:, 0], a["D"], S, active=active)
    np.testing.assert_array_equal(S1[1], S[1])
    assert not np.array_equal(S1[0], S[0])
    win = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 12))
    w, b = jnp.ones((4, 12)), jnp.zeros((12,))
    _, win1 = mamba2.conv_step(jnp.ones((2, 12)), w, b, win, active)
    np.testing.assert_array_equal(win1[1], win[1])
    np.testing.assert_array_equal(win1[0, :2], win[0, 1:])


@pytest.mark.parametrize("T", [3, 9])
def test_the_convolution_in_chunks_equals_its_steps(T):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (2, T, 12))
    w, b = jax.random.normal(ks[1], (4, 12)), jax.random.normal(ks[2], (12,))
    win0 = jax.random.normal(ks[3], (2, 3, 12))
    seg = jnp.asarray([[0] * T, [0] * (T - 2) + [-1] * 2], jnp.int32)
    out, win = mamba2.causal_conv(x, w, b, seg, win0)
    cur, outs = win0, []
    for t in range(T):
        o, cur = mamba2.conv_step(x[:, t], w, b, cur,
                                  active=jnp.asarray(seg[:, t] >= 0))
        outs.append(o)
    np.testing.assert_allclose(out[0], jnp.stack(outs, 1)[0], atol=1e-5)
    np.testing.assert_allclose(out[1, : T - 2], jnp.stack(outs, 1)[1, : T - 2],
                               atol=1e-5)
    np.testing.assert_allclose(win, cur, atol=1e-6)


# ---------------------------------------------------------------------------
# (d) the routers
# ---------------------------------------------------------------------------


def test_the_sigmoid_router_chooses_by_score_plus_bias_and_weighs_by_score():
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    lp = {"router": jax.random.normal(jax.random.PRNGKey(1), (64, 8)) / 8,
          "router_bias": jnp.asarray([0.5, 0, 0, -0.5, 0, 0.3, 0, 0])}
    w, idx = moe.route_sigmoid(CFG, lp, x)
    s = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    want_idx = np.argsort(-(s + np.asarray(lp["router_bias"])), -1)[:, :3]
    assert (np.sort(idx, -1) == np.sort(want_idx, -1)).all()
    # the bias moved the choice, and stays out of the weight
    assert (np.sort(idx, -1) != np.sort(np.argsort(-s, -1)[:, :3], -1)).any()
    chosen = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    raw, _ = moe.route_sigmoid(CFG.replace(norm_topk_prob=False), lp, x)
    np.testing.assert_allclose(raw, 2.5 * chosen, rtol=1e-5)


QWEN_MOE = {
    "model_type": "qwen3_moe", "architectures": ["Qwen3MoeForCausalLM"],
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 1,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
    "vocab_size": 64, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 16,
}


@pytest.mark.parametrize("flag", [True, False])
def test_norm_topk_prob_is_honoured_for_qwen3_moe(flag):
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # it used to warn and renormalise
        cfg = TransformerConfig.from_hf({**QWEN_MOE, "norm_topk_prob": flag})
    assert cfg.norm_topk_prob is flag
    assert cfg.to_hf_dict()["norm_topk_prob"] is flag
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 32))
    lp = {"router": jax.random.normal(jax.random.PRNGKey(1), (32, 4))}
    probs, gates, idx = moe._route(lp, x, 2, cfg.norm_topk_prob)
    top = np.take_along_axis(np.asarray(probs), np.asarray(idx), -1)
    np.testing.assert_allclose(
        gates, top / top.sum(-1, keepdims=True) if flag else top, rtol=1e-6)


def test_mixtral_and_a_silent_qwen_config_keep_their_routing():
    assert TransformerConfig.from_hf(
        {**QWEN_MOE, "model_type": "mixtral", "num_local_experts": 4}
    ).norm_topk_prob is True
    # transformers' Qwen3MoeConfig defaults the key to false
    assert TransformerConfig.from_hf(QWEN_MOE).norm_topk_prob is False


@pytest.mark.parametrize("impl", ["dropless", "capacity"])
def test_moe_ffn_follows_the_flag(impl):
    cfg = TransformerConfig.from_hf({**QWEN_MOE, "norm_topk_prob": False}
                                    ).replace(dtype="float32", moe_impl=impl,
                                              moe_capacity_factor=4.0)
    p = tf.init_params(cfg, jax.random.PRNGKey(0))["layers"]["moe"]
    lp = jax.tree_util.tree_map(lambda a: a[0], p)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 32))
    raw, _ = moe.moe_ffn(cfg, lp, h, jnp.float32)
    norm, _ = moe.moe_ffn(cfg.replace(norm_topk_prob=True), lp, h, jnp.float32)
    probs, gates, _ = moe._route(lp, h[0], 2, False)
    # every expert output scales with its gate: a token's result is the
    # renormalised one times the sum of its two raw gates
    np.testing.assert_allclose(
        raw[0], norm[0] * np.asarray(gates).sum(-1, keepdims=True),
        rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# (c) THE SHARE TEST
# ---------------------------------------------------------------------------


def test_the_four_shares_of_an_expert_block_add_up_to_the_uncut_block():
    """Each of four shares holds 2 of the 8 routed experts, routes over all
    8 and computes its own experts' part; with the shared expert, which
    every share computes alike, counted once, the parts add up to what the
    uncut reference gives for the whole block."""
    whole_hf = {**HF, "n_routed_experts": 8, "experts_held": None}
    whole = _params(_cfg(whole_hf), seed=3)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 9, 64))
    lp_whole = ref.block_params(whole, "E", 1)
    sh = ref.shapes(whole_hf)
    want, _ = ref.moe_block(
        x, lp_whole, top_k=sh["top_k"], scale=sh["scale"],
        norm_topk=sh["norm_topk"], first=0, n_held=8, eps=sh["eps"])
    h = ref._rms(x, lp_whole["input_norm"], 1e-5)
    shared = ref._relu2(h @ lp_whole["ws1"]) @ lp_whole["ws2"]
    parts = []
    for first in (0, 2, 4, 6):
        cfg = _cfg({**HF, "n_routed_experts": 2,
                    "experts_held": {"first": first, "of": 8}})
        stack = dict(whole["layers"]["E"])
        stack["w1"] = stack["w1"][:, first: first + 2]
        stack["w2"] = stack["w2"][:, first: first + 2]
        lp = {k: (v if k in ("w1", "w2") else v[1]) for k, v in stack.items()}
        out, counters = tf._moe_block(cfg, {**lp, "block": 1}, x, None)
        parts.append(out - x)
        # the share's own reference agrees with it, too
        own, _ = ref.moe_block(
            x, {**lp_whole, "w1": lp["w1"][1], "w2": lp["w2"][1]},
            top_k=3, scale=2.5, norm_topk=True, first=first, n_held=2,
            eps=1e-5)
        np.testing.assert_allclose(out, own, atol=2e-5)
        assert 0 < int(counters[0]) <= 2 * 9 * 3
    total = sum(parts) - 3 * shared
    np.testing.assert_allclose(x + total, want, atol=5e-5)
    # and no share alone is the block
    assert float(jnp.abs(x + parts[0] - want).max()) > 1e-2


# ---------------------------------------------------------------------------
# (a) the cache forwards against the reference's full forward pass: logits
# ---------------------------------------------------------------------------


def test_the_packed_forward_gives_the_reference_s_logits(params, ids, want):
    pos = np.broadcast_to(np.arange(30, dtype=np.int32), ids.shape)
    got = tf.forward(params, CFG, jnp.asarray(ids), jnp.asarray(pos),
                     jnp.zeros(ids.shape, jnp.int32))
    np.testing.assert_allclose(got, want, atol=3e-5)


@pytest.fixture(scope="module")
def served(params, ids, want):
    """Three prompts prefilled into scattered slots of a 6-slot pool (one
    padded row to the scratch row), six decode steps of the whole block
    with the other slots idle."""
    cache = tf.init_kv_cache(CFG, 7, 64, "float32")
    plen = np.array([13, 9, 11], np.int32)
    pids = np.zeros((4, 16), np.int32)
    for i in range(3):
        pids[i, : plen[i]] = ids[i, : plen[i]]
    slots = np.array([4, 1, 2, 6], np.int32)
    logits, cache = jax.jit(lambda p, c: tf.forward_prefill(
        p, CFG, jnp.asarray(pids), jnp.asarray(np.append(plen, 1)), c,
        jnp.asarray(slots)))(params, cache)
    errs = [float(np.abs(np.asarray(logits[i]) - want[i, plen[i] - 1]).max())
            for i in range(3)]
    idle_before = jax.tree_util.tree_map(lambda a: np.asarray(a[:, 0]), cache)
    step = jax.jit(lambda p, c, t, ln, a: tf.forward_decode_hybrid(
        p, CFG, t, ln, c, key_window=32, slot_base=0, active=a))
    lengths, toks = np.zeros(7, np.int32), np.zeros(7, np.int32)
    active = np.zeros(7, bool)
    cur, dec_errs, counts = plen.copy(), [], []
    for _ in range(6):
        for i in range(3):
            s = slots[i]
            lengths[s], toks[s], active[s] = cur[i], ids[i, cur[i]], True
        logits, cache, cnt = step(params, cache, jnp.asarray(toks),
                                  jnp.asarray(lengths), jnp.asarray(active))
        dec_errs.append(max(
            float(np.abs(np.asarray(logits[slots[i]]) - want[i, cur[i]]).max())
            for i in range(3)))
        counts.append(np.asarray(cnt))
        cur += 1
    return cache, cur, errs, dec_errs, counts, idle_before


def test_fresh_prefill_gives_the_reference_s_logits(served):
    assert max(served[2]) < 3e-5


def test_decode_chunks_give_the_reference_s_logits(served):
    assert max(served[3]) < 3e-5


def test_decode_counts_assignments_of_live_slots_to_held_experts(served):
    for held, touched in served[4]:
        # 3 live slots x top-3 x 2 expert blocks, some routed elsewhere;
        # touched counts held experts of both blocks, idle slots' rows too
        assert 0 < held <= 18 and 0 < touched <= 8


def test_an_idle_slot_s_rows_are_left_as_they_were(served):
    cache, idle_before = served[0], served[5]
    for name in ("s", "c", "k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[name][:, 0]),
                                      idle_before[name])


def test_suffix_prefill_after_a_sibling_copy_gives_the_reference_s_logits(
        served, params, ids, want):
    """Two rows continue slot 4's sequence from ITS state, window and K/V
    columns (the fan-out copy) into slots 0 and 3, with suffixes of
    different lengths in one bucket."""
    cache, cur = served[0], served[1]
    start = int(cur[0])
    sids = np.zeros((2, 8), np.int32)
    sids[0, :5] = ids[0, start: start + 5]
    sids[1, :3] = ids[0, start: start + 3]
    logits, cache = jax.jit(lambda p, c: tf.forward_prefill_cached(
        p, CFG, jnp.asarray(sids), jnp.asarray([start, start], jnp.int32),
        jnp.asarray([5, 3], jnp.int32), c, jnp.asarray([0, 3], jnp.int32),
        copy_src=jnp.asarray([4, 4], jnp.int32), copy_block=32,
        key_window=32))(params, cache)
    assert float(np.abs(np.asarray(logits[0]) - want[0, start + 4]).max()) < 3e-5
    assert float(np.abs(np.asarray(logits[1]) - want[0, start + 2]).max()) < 3e-5
    # the source row is as it was; the siblings hold the prefix's columns
    np.testing.assert_array_equal(cache["k"][:, 0, :start],
                                  cache["k"][:, 4, :start])


def test_verify_and_the_ragged_kernel_are_refused_by_name(params):
    cache = tf.init_kv_cache(CFG, 3, 32, "float32")
    z = jnp.zeros((3,), jnp.int32)
    with pytest.raises(ValueError, match="spec_decode"):
        tf.forward_verify(params, CFG, jnp.zeros((3, 2), jnp.int32), z, cache)
    with pytest.raises(ValueError, match="ragged_attn"):
        tf.forward_decode(params, CFG, z, z, cache, ragged=True, rows=z)


# ---------------------------------------------------------------------------
# (e) gradients of the packed forward
# ---------------------------------------------------------------------------


def test_gradients_of_the_packed_forward_equal_the_reference_s(params):
    """Two sequences packed into one row with padding behind them: the
    Mamba state and the convolution restart at the segment boundary, and
    every parameter's gradient is the reference's over the two sequences."""
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, 128, 11), rng.integers(0, 128, 9)
    packed = {
        "input_ids": jnp.asarray(np.concatenate([a, b, np.zeros(4)]), jnp.int32),
        "positions": jnp.asarray(
            np.concatenate([np.arange(11), np.arange(9), np.zeros(4)]), jnp.int32),
        "segment_ids": jnp.asarray([0] * 11 + [1] * 9 + [-1] * 4, jnp.int32),
    }
    probe = jnp.asarray(rng.normal(size=(20, 128)), jnp.float32)

    def ours(p):
        return jnp.sum(tf.forward_packed(p, CFG, packed)[:20] * probe)

    def theirs(p):
        la = ref.logits(p, HF, a[None].astype(np.int32))[0]
        lb = ref.logits(p, HF, b[None].astype(np.int32))[0]
        return jnp.sum(jnp.concatenate([la, lb]) * probe)

    np.testing.assert_allclose(ours(params), theirs(params), rtol=1e-5)
    got, want_g = jax.grad(ours)(params), jax.grad(theirs)(params)
    flat_g, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want_g)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - w).max()) < 2e-4 * scale + 1e-6, path
    # remat changes nothing but memory
    again = jax.grad(lambda p: jnp.sum(tf.forward_packed(
        p, CFG.replace(remat=True), packed)[:20] * probe))(params)
    for g, w in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(g, w, atol=1e-5)
