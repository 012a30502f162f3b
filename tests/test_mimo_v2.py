"""`mimo_v2` (MiMo-V2-Flash, the language model of MiMo-V2.5) on the CPU, at
toy widths with the published RATIOS (a key of 24 beside a value of 16, a
window of 8, 2 full + 5 sliding layers with 2 / 4 kv heads, 4 of 16 experts
held): the cache forwards (`models/windowed.py`: prompt, suffix on a
reused prefix, a sibling's copy, decode through the columns and the ring
past three turns of the ring) against the plain reference's full forward
(`benchmarks/lib/reference_mimo_v2.py`) on LOGITS; one test a mechanism
that fails when the mechanism is left out; the shares' parts adding up to
the uncut layer; the configuration's round trip and refusals; the engine's
refusals by the table, its fan-out copy and its reuse rule."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.models import init_params, moe, windowed
from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.models.transformer import (
    GATED_EXPERTS_KIND,
    forward_prefill,
    forward_prefill_cached,
    init_kv_cache,
    slot_kind,
)
from areal_tpu.ops import attention
from benchmarks.lib import reference_mimo_v2 as ref

HF = {
    "model_type": "mimo_v2", "architectures": ["MiMoV2ForCausalLM"],
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 7,
    "num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16, "swa_v_head_dim": 16,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "sliding_window": 8, "sliding_window_size": 8,
    "rope_theta": 1e7, "swa_rope_theta": 1e4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "attention_bias": False,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "layernorm_epsilon": 1e-5, "n_routed_experts": 4,
    "experts_held": {"first": 4, "of": 16}, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "routed_scaling_factor": None, "n_shared_experts": None,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
    "hidden_act": "silu",
}
TOL = 2e-4  # float32 program against the float32 reference, on logits
W = 8


def _cfg(hf=HF):
    return TransformerConfig.from_hf(hf).replace(
        dtype="float32", param_dtype="float32")


def _params(cfg, seed=0):
    """The program's draw, with sinks that take a share of the mass and a
    small selection bias (a zero sink is under a percent of eight keys'
    mass, a zero bias makes choice and weight one thing)."""
    p = init_params(cfg, jax.random.PRNGKey(seed))
    layers = dict(p["layers"])
    key = jax.random.PRNGKey(seed + 100)
    for i, kind in enumerate(("full", "sliding")):
        if "sink" in layers.get(kind, {}):
            shape = layers[kind]["sink"].shape
            layers[kind] = {**layers[kind], "sink": 2.0 + jax.random.normal(
                jax.random.fold_in(key, i), shape)}
    layers["moe"] = {**layers["moe"], "router_bias": 0.02 * jax.random.normal(
        jax.random.fold_in(key, 9), layers["moe"]["router_bias"].shape)}
    return {**p, "layers": layers}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, _params(cfg)


def _ids(n, seed=1):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, HF["vocab_size"]))


@functools.lru_cache(maxsize=None)
def _prefill_fn(cfg):
    return jax.jit(lambda params, cache, row, n, slot: forward_prefill(
        params, cfg, row, n, cache, slot))


def _prefill(cfg, params, cache, ids, slot, bucket):
    row = np.zeros((1, bucket), np.int32)
    row[0, : len(ids)] = ids
    return _prefill_fn(cfg)(
        params, cache, jnp.asarray(row), jnp.array([len(ids)]),
        jnp.array([slot]))


@functools.lru_cache(maxsize=None)
def _decode_fn(cfg, K):
    return jax.jit(lambda params, cache, tokens, lengths, active:
                   windowed.forward_decode(
                       params, cfg, tokens, lengths, cache, key_window=K,
                       slot_base=0, active=active))


def _decode(cfg, params, cache, tokens, lengths, active, K):
    return _decode_fn(cfg, K)(
        params, cache, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(lengths, jnp.int32), jnp.asarray(active))


# ---------------------------------------------------------------------------
# the configuration


def test_round_trip_and_what_the_keys_mean():
    cfg = _cfg()
    assert _cfg(cfg.to_hf_dict()) == cfg
    assert (cfg.attn_kind, cfg.num_kv_heads, cfg.swa_num_kv_heads) == (
        "windowed", 2, 4)
    assert (cfg.head_dim_, cfg.v_head_dim, cfg.rotary_dim) == (24, 16, 8)
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (1e7, 1e4)
    assert cfg.layer_is_sliding == (False, True, True, True, True, False, True)
    assert (cfg.leading_dense_layers, cfg.sliding_window, cfg.window_ring) \
        == (1, 8, 8)
    assert (cfg.sink_sliding, cfg.sink_full, cfg.attn_value_scale) == (
        True, False, 0.707)
    assert (cfg.num_experts, cfg.held_range, cfg.router_kind) == (
        16, (4, 8), "sigmoid")
    assert cfg.routed_scaling_factor == 1.0 and cfg.norm_topk_prob
    # not the gated-experts family the engine refuses whole
    assert cfg.ffn_kinds is None and slot_kind(cfg).name == "windowed"
    assert windowed.layer_plan(cfg)[:2] == [
        ("full", 0, "mlp", 0), ("sliding", 0, "moe", 0)]
    assert windowed.layer_plan(cfg)[5] == ("full", 1, "moe", 4)


def test_the_published_row_builds():
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", "configs", "mimo-v2.5.json")
    with open(path) as f:
        hf = json.load(f)
    cfg = TransformerConfig.from_hf(hf)
    assert (cfg.num_layers, cfg.num_heads, cfg.head_dim_, cfg.v_head_dim,
            cfg.rotary_dim, cfg.window_ring) == (7, 64, 192, 128, 64, 128)
    assert (cfg.num_kv_heads, cfg.swa_num_kv_heads) == (4, 8)
    assert (cfg.num_experts, cfg.held_range) == (256, (0, 16))


@pytest.mark.parametrize("patch,why", [
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_group": 2}, "group-limited"),
    ({"n_shared_experts": 1}, "shared experts"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"moe_layer_freq": [0, 1, 0, 1, 1, 1, 1]}, "moe_layer_freq"),
    ({"hybrid_layer_pattern": [0, 1, 1]}, "hybrid_layer_pattern"),
    ({"swa_head_dim": 32}, "swa_head_dim"),
])
def test_what_from_hf_does_not_build_is_refused_by_name(patch, why):
    with pytest.raises(ValueError, match=why):
        TransformerConfig.from_hf({**HF, **patch})


def test_training_and_checkpoints_are_refused_by_name(model):
    from areal_tpu.models import hf as hf_io
    from areal_tpu.models.transformer import forward

    cfg, params = model
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="mimo_v2"):
        forward(params, cfg, ids, ids, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="mimo_v2"):
        list(hf_io.params_to_hf_state(params, cfg))


# ---------------------------------------------------------------------------
# the cache forwards against the reference


def test_the_sliding_leaves_hold_a_window_and_no_max_seq_len_axis(model):
    cfg, _ = model
    cache = init_kv_cache(cfg, 5, 80, "bfloat16")
    shapes = {k: v.shape for k, v in cache.items()}
    assert shapes == {"k": (2, 5, 80, 2 * 24), "v": (2, 5, 80, 2 * 16),
                      "wk": (5, 5, W, 4 * 24), "wv": (5, 5, W, 4 * 16)}
    assert 80 not in shapes["wk"] and 80 not in shapes["wv"]
    # a window that is no multiple of eight is rounded up to one
    assert _cfg({**HF, "sliding_window": 11,
                 "sliding_window_size": 11}).window_ring == 16


def test_ring_positions():
    got = np.asarray(windowed.ring_positions(jnp.array([0, 3, 8, 21]), 8))
    assert (got[0] < 0).all()
    np.testing.assert_array_equal(got[1], [0, 1, 2, -5, -4, -3, -2, -1])
    np.testing.assert_array_equal(got[2], np.arange(8))
    np.testing.assert_array_equal(got[3], [16, 17, 18, 19, 20, 13, 14, 15])


def test_prefill_then_decode_past_three_turns_of_the_ring(model):
    """A prompt of 13 into slot 1 of 3, then 30 decode steps (position 43 >
    13 + 3 x 8): every step's logits against the reference's full forward;
    slot 0 idles beside it and slot 2 holds another sequence."""
    cfg, params = model
    ids, other = _ids(44), _ids(44, seed=7)
    want = np.asarray(ref.logits(params, HF, ids[None]))[0]
    want_other = np.asarray(ref.logits(params, HF, other[None]))[0]
    cache = init_kv_cache(cfg, 4, 64, "float32")
    logits, cache = _prefill(cfg, params, cache, ids[:13], 1, 16)
    np.testing.assert_allclose(logits[0], want[12], atol=TOL)
    logits, cache = _prefill(cfg, params, cache, other[:9], 2, 16)
    np.testing.assert_allclose(logits[0], want_other[8], atol=TOL)
    worst = 0.0
    for i in range(30):
        logits, cache, counters = _decode(
            cfg, params, cache, [0, ids[13 + i], other[9 + i]],
            [5, 13 + i, 9 + i], [False, True, True], 48)
        worst = max(worst, float(jnp.abs(logits[1] - want[13 + i]).max()),
                    float(jnp.abs(logits[2] - want_other[9 + i]).max()))
    assert worst < TOL, worst
    counters = dict(zip(windowed.DECODE_COUNTERS, np.asarray(counters)))
    assert counters["kv_columns_read"] == 43 + 39
    assert counters["expert_slots"] == 6 * 4
    assert 0 < counters["experts_touched"] <= 24
    assert 0 < counters["expert_assignments_held"] <= 2 * 4 * 6
    # the idle slot wrote nothing
    assert not np.asarray(cache["wk"][:, 0]).any()
    assert not np.asarray(cache["k"][:, 0]).any()


@pytest.mark.parametrize("start,n", [(13, 9), (21, 5), (5, 2)])
def test_a_suffix_on_a_reused_prefix(model, start, n):
    """The first `start` tokens prefilled, the next `n` as a suffix over the
    slot's columns and ring (start before, at and past a turn of the ring)."""
    cfg, params = model
    ids = _ids(start + n, seed=start)
    want = np.asarray(ref.logits(params, HF, ids[None]))[0]
    cache = init_kv_cache(cfg, 3, 64, "float32")
    _, cache = _prefill(cfg, params, cache, ids[:start], 1, 32)
    row = np.zeros((1, 16), np.int32)
    row[0, :n] = ids[start:]
    logits, cache = forward_prefill_cached(
        params, cfg, jnp.asarray(row), jnp.array([start]), jnp.array([n]),
        cache, jnp.array([1]), key_window=32)
    np.testing.assert_allclose(logits[0], want[start + n - 1], atol=TOL)
    # and the cache it left decodes on
    nxt = _ids(1, seed=99)
    full = np.asarray(ref.logits(
        params, HF, np.concatenate([ids, nxt])[None]))[0]
    logits, _, _ = _decode(cfg, params, cache, [0, nxt[0], 0],
                           [0, start + n, 0], [False, True, False], 32)
    np.testing.assert_allclose(logits[1], full[-1], atol=TOL)


def test_a_sibling_starts_from_its_representative_s_copy(model):
    """One prefill of the shared span into slot 0, then ONE suffix dispatch
    in which slots 2 and 3 take slot 0's columns and rings and all three
    compute the last prompt token; then each decodes its own token."""
    cfg, params = model
    ids = _ids(27, seed=5)
    want = np.asarray(ref.logits(params, HF, ids[None]))[0]
    cache = init_kv_cache(cfg, 5, 64, "float32")
    _, cache = _prefill(cfg, params, cache, ids[:26], 0, 32)
    row = np.zeros((4, 16), np.int32)
    row[:, 0] = ids[26]
    logits, cache = forward_prefill_cached(
        params, cfg, jnp.asarray(row), jnp.full((4,), 26), jnp.ones((4,), jnp.int32),
        cache, jnp.array([2, 3, 0, 4]), copy_src=jnp.array([0, 0, 0, 4]),
        copy_block=32, key_window=32)
    for r in range(3):
        np.testing.assert_allclose(logits[r], want[26], atol=TOL)
    for leaf in ("wk", "wv"):
        np.testing.assert_array_equal(cache[leaf][:, 2], cache[leaf][:, 0])
    np.testing.assert_array_equal(cache["k"][:, 3, :27], cache["k"][:, 0, :27])
    toks = _ids(3, seed=11)
    logits, _, _ = _decode(
        cfg, params, cache, [toks[0], 0, toks[1], toks[2], 0],
        [27, 0, 27, 27, 0], [True, False, True, True, False], 32)
    for r, t in zip((0, 2, 3), toks):
        full = np.asarray(ref.logits(
            params, HF, np.concatenate([ids, [t]])[None]))[0]
        np.testing.assert_allclose(logits[r], full[-1], atol=TOL)


# ---------------------------------------------------------------------------
# one test a mechanism: left out, the logits leave the reference


def _without(mechanism, cfg, params):
    """The program with one mechanism left out (the reference keeps it)."""
    layers = params["layers"]
    if mechanism == "sink":
        sliding = {k: v for k, v in layers["sliding"].items() if k != "sink"}
        return cfg.replace(sink_sliding=False), {
            **params, "layers": {**layers, "sliding": sliding}}
    if mechanism == "window":
        return cfg.replace(sliding_window=64), params
    if mechanism == "value_scale":
        return cfg.replace(attn_value_scale=1.0), params
    if mechanism == "second_rotary_base":
        return cfg.replace(swa_rope_theta=cfg.rope_theta), params
    if mechanism == "partial_rotary":
        return cfg.replace(partial_rotary_factor=1.0), params
    assert mechanism == "kv_heads_by_kind"
    # the sliding layers on the full layers' two kv heads: their first two
    n = cfg.num_kv_heads
    sliding = {**layers["sliding"],
               "wk": layers["sliding"]["wk"][:, : n * 24],
               "wv": layers["sliding"]["wv"][:, : n * 16]}
    return cfg.replace(swa_num_kv_heads=n), {
        **params, "layers": {**layers, "sliding": sliding}}


@pytest.mark.parametrize("mechanism", [
    "sink", "window", "value_scale", "second_rotary_base", "partial_rotary",
    "kv_heads_by_kind"])
def test_a_mechanism_left_out_shows_in_the_logits(model, mechanism):
    cfg, params = model
    ids = _ids(30, seed=3)
    want = np.asarray(ref.logits(params, HF, ids[None]))[0]
    worst = {}
    for name, (c, p) in (("whole", (cfg, params)),
                         ("without", _without(mechanism, cfg, params))):
        cache = init_kv_cache(c, 2, 64, "float32")
        logits, cache = _prefill(c, p, cache, ids[:20], 0, 32)
        d = [float(jnp.abs(logits[0] - want[19]).max())]
        for i in range(20, 30):
            logits, cache, _ = _decode(
                c, p, cache, [ids[i], 0], [i, 0], [True, False], 32)
            d.append(float(jnp.abs(logits[0] - want[i]).max()))
        worst[name] = (d[0], max(d[1:]))
    assert max(worst["whole"]) < TOL
    # in the prompt's program and in the decode steps alike
    assert min(worst["without"]) > 20 * TOL, worst


# ---------------------------------------------------------------------------
# the experts at a share


def test_the_shares_parts_add_up_to_the_uncut_layer(model):
    """16 experts in 4 shares of 4: the four parts add up to what one
    program holding all 16 gives, and to the reference's."""
    cfg, params = model
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["moe"])
    key = jax.random.PRNGKey(4)
    all16 = {name: jax.random.normal(
        jax.random.fold_in(key, i), (16,) + lp[name].shape[1:]) / 8
        for i, name in enumerate(("w_gate", "w_up", "w_down"))}
    h = jax.random.normal(jax.random.fold_in(key, 7), (2, 9, 64))
    uncut, c_all = moe.gated_moe_ffn(
        cfg.replace(experts_held=None), {**lp, **all16}, h, jnp.float32)
    assert int(c_all[0]) == 2 * 9 * 4  # every assignment is held
    parts, rows = [], 0
    for first in range(0, 16, 4):
        share = {k: v[first: first + 4] for k, v in all16.items()}
        out, c = moe.gated_moe_ffn(
            cfg.replace(experts_held=(first, first + 4)), {**lp, **share}, h,
            jnp.float32)
        want = ref.moe(h.reshape(18, 64), {**lp, **share}, top_k=4,
                       renorm=True, scale=1.0, first=first, n_held=4)
        np.testing.assert_allclose(out.reshape(18, 64), want, atol=2e-5)
        parts.append(out)
        rows += int(c[0])
    assert rows == 2 * 9 * 4
    np.testing.assert_allclose(sum(parts), uncut, atol=5e-5)


def test_rows_nobody_reads_stay_out_of_the_dispatch(model):
    """`drop_invalid`: an idle slot's row reaches no expert, comes back
    zero, and the experts only it would have touched are not counted."""
    cfg, params = model
    moe_p = params["layers"]["moe"]
    lp = {"router": moe_p["router"][2], "router_bias": moe_p["router_bias"][2],
          "w_gate": moe_p["w_gate"], "w_up": moe_p["w_up"],
          "w_down": moe_p["w_down"], "block": 2}
    own = jax.tree_util.tree_map(lambda a: a[2], moe_p)
    h = jax.random.normal(jax.random.PRNGKey(8), (6, 1, 64))
    valid = jnp.array([True, False, True, True, False, True])[:, None]
    kept, c_kept = moe.gated_moe_ffn(cfg, lp, h, jnp.float32, valid)
    out, c = moe.gated_moe_ffn(cfg, lp, h, jnp.float32, valid, drop_invalid=True)
    # the stack with the layer's index is the layer's own experts
    np.testing.assert_allclose(
        kept, moe.gated_moe_ffn(cfg, own, h, jnp.float32, valid)[0], atol=1e-6)
    np.testing.assert_allclose(out[valid[:, 0]], kept[valid[:, 0]], atol=1e-6)
    assert not np.asarray(out[~valid[:, 0]]).any()
    np.testing.assert_array_equal(c[jnp.array([0, 1, 3])],
                                  c_kept[jnp.array([0, 1, 3])])
    assert int(c[2]) <= int(c_kept[2]) and 0 < int(c[3]) <= 4


# ---------------------------------------------------------------------------
# the sink in the two attention cores


def test_the_oracle_s_sink_column_takes_mass_and_adds_no_value():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 5, 4, 8))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 5, 2, 8))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 5, 2, 6))
    sinks = jnp.array([0.5, -1.0, 2.0, 0.0])
    mask = jnp.tril(jnp.ones((5, 5), bool))[None, None]
    got = attention.naive_attention(q, k, v, mask, sinks=sinks)
    assert got.shape == (1, 5, 4, 6)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) / 8 ** 0.5
    e = jnp.where(mask, jnp.exp(s), 0.0)
    p = e / (jnp.exp(sinks)[None, :, None, None] + e.sum(-1, keepdims=True))
    want = jnp.einsum("bhqk,bkhv->bqhv", p, jnp.repeat(v, 2, axis=2))
    np.testing.assert_allclose(got, want, atol=1e-5)
    plain = attention.naive_attention(q, k, v, mask)
    assert float(jnp.abs(got - plain).max()) > 0.05


@pytest.mark.parametrize("window", [None, 128])
def test_the_splash_kernel_takes_the_sinks_and_the_two_widths(
        monkeypatch, window):
    """The prompt's attention as the chip runs it (interpreted here): the
    splash kernel under a causal or a local mask with a sink a query head,
    keys of 192 beside values of 128, two queries a kv head, one row
    narrowed by its padding, against the blocked product."""
    monkeypatch.setattr(attention, "INTERPRET", True)
    assert windowed._splash_applies(256)
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, 256, 4, 192))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 192))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 128))
    sink = jnp.array([1.0, 3.0, -2.0, 4.0])
    valid = (jnp.arange(256) < 200)[None]
    got = windowed._attend_fresh(q, k, v, valid, window, sink, 192 ** -0.5)
    monkeypatch.setattr(windowed, "_splash_applies", lambda T: False)
    want = windowed._attend_fresh(q, k, v, valid, window, sink, 192 ** -0.5)
    np.testing.assert_allclose(got[:, :200], want[:, :200], atol=2e-3)
    none = windowed._attend_fresh(q, k, v, valid, window, None, 192 ** -0.5)
    assert float(jnp.abs(none[:, :200] - want[:, :200]).max()) > 0.05


# ---------------------------------------------------------------------------
# the engine


def _engine(model, **kw):
    cfg, params = model
    return GenEngine(cfg, params=params, n_slots=8, max_seq_len=64,
                     prompt_bucket=16, kv_dtype="float32", decode_chunk=4,
                     **kw)


def _drain(eng, reqs):
    for _ in range(200):
        if all(r.stop_reason for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _logprob_gap(params, req):
    seq = np.asarray(list(req.input_ids) + list(req.output_tokens))[None]
    want = np.asarray(ref.next_token_logprobs(params, HF, seq))[0]
    P = len(req.input_ids)
    return float(np.abs(
        want[P - 1:] - np.asarray(req.output_logprobs)).max())


@pytest.mark.parametrize("option,why", [
    ({"spec_decode": True}, "rejected draft"),
    ({"host_offload": True}, "ring has no prefix"),
    ({"decode_tiers": 2}, "one tier"),
    ({"tp": 2}, "two head layouts under tp"),
    ({"ep": 2}, "exchange between expert shares"),
])
def test_what_the_kind_lacks_is_refused_at_construction(model, option, why):
    with pytest.raises(ValueError, match=why):
        _engine(model, **option)


def test_the_table_s_sixth_row(model):
    cfg, _ = model
    kind = slot_kind(cfg)
    assert kind.holds == frozenset({"kv", "window"})
    assert set(kind.lacks) == {"verify", "host_tier", "handoff", "tiers",
                               "tp", "ep"}
    assert kind.counters == windowed.DECODE_COUNTERS
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 64, "bfloat16"))
    assert kind.kernel_refusal(cfg, cache, 64, "bfloat16", 1) == ""
    assert kind.admit_tokens(cfg, 64) == 64
    # what afmoe still lacks names neither the window nor the experts
    why = GATED_EXPERTS_KIND.lacks["generate"]
    assert "output gate" in why and "sliding layers only" in why
    assert "mimo_v2" in why


def test_a_group_through_the_engine_is_one_prefill_and_three_copies(model):
    eng = _engine(model)
    assert eng.ragged_attn and eng._state and eng._window
    prompt = [int(t) for t in _ids(27, seed=21)]
    reqs = [GenRequest(rid=f"r{i}", input_ids=prompt, max_new_tokens=14 + i,
                       temperature=1.0, group_id="g", group_n=4)
            for i in range(4)]
    eng.submit_batch(reqs)
    _drain(eng, reqs)
    s = eng.stats
    assert (s["prefill_calls"], s["suffix_calls"], s["copy_calls"]) == (1, 1, 1)
    assert s["prefill_tokens"] == 26 and s["shared_tokens"] == 3 * 26
    assert s["window_copies"] == s["state_copies"] == 3
    ring = sum(int(eng.cache[k].nbytes) for k in ("wk", "wv")) // 9
    assert s["window_copy_bytes"] == 3 * ring
    assert s["expert_slots"] == s["decode_passes"] * 6 * 4
    assert 0 < s["experts_touched"] <= s["expert_slots"]
    assert s["kv_columns_read"] > 4 * 27
    # positions 27 .. 44 are past two turns of the ring
    for r in reqs:
        assert len(r.output_tokens) == r.max_new_tokens
        assert _logprob_gap(eng.params, r) < TOL


@pytest.mark.parametrize("kv_dtype,kernel", [
    ("float32", True), ("bfloat16", True), ("float8_e4m3fn", False)])
def test_nobody_said_takes_the_kernel_wherever_the_pool_is_read(
        model, kv_dtype, kernel):
    """`ragged_attn=None` is the kind's kernel on a 2- or 4-byte pool and
    the copy path on a float8 one (the benchmark's control), without a
    word; asked for by name there, the refusal's words come back."""
    cfg, params = model
    kw = dict(params=params, n_slots=4, max_seq_len=64, prompt_bucket=16,
              kv_dtype=kv_dtype)
    eng = GenEngine(cfg, **kw)
    assert eng.ragged_attn is kernel and eng._ragged_ok is kernel
    assert not GenEngine(cfg, ragged_attn=False, **kw).ragged_attn
    if kernel:
        assert GenEngine(cfg, ragged_attn=True, **kw).ragged_attn
    else:
        with pytest.raises(ValueError, match="ragged_attn requested but the "
                           "windowed kernel reads 2- or 4-byte columns"):
            GenEngine(cfg, ragged_attn=True, **kw)


def test_a_sink_on_the_full_layers_keeps_the_copy_path():
    """No configuration runs one: the kernel's softmax has no sink, and the
    table says so instead of building it."""
    cfg = _cfg({**HF, "add_full_attention_sink_bias": True})
    eng = GenEngine(cfg, n_slots=4, max_seq_len=64, prompt_bucket=16,
                    kv_dtype="float32")
    assert not eng.ragged_attn
    with pytest.raises(ValueError, match="add_full_attention_sink_bias"):
        GenEngine(cfg, n_slots=4, max_seq_len=64, prompt_bucket=16,
                  kv_dtype="float32", ragged_attn=True)


def _group(tag, prompt, n=4):
    return [GenRequest(rid=f"{tag}{i}", input_ids=prompt,
                       max_new_tokens=26 + i, temperature=1.0,
                       stream_id=100 + i, group_id=tag, group_n=n)
            for i in range(n)]


def test_the_kernel_s_engine_and_the_copy_path_s_tell_the_same_story(model):
    """A group of four on one prompt (one prefill, the sibling copy, one
    suffix dispatch) and a lone request beside it, decoded past three turns
    of the ring of 8: the same tokens, log-probs to float32's rounding, and
    every decode dispatch of the default engine went through the kernel;
    what it counts as attended is the columns by length, as the device
    counts them."""
    prompt = [int(t) for t in _ids(27, seed=21)]
    lone = [int(t) for t in _ids(9, seed=22)]
    runs = {}
    for ragged in (None, False):
        eng = _engine(model, ragged_attn=ragged)
        reqs = _group("g", prompt) + [GenRequest(
            rid="lone", input_ids=lone, max_new_tokens=40, temperature=1.0,
            stream_id=7)]
        eng.submit_batch(reqs)
        _drain(eng, reqs)
        runs[ragged] = (eng, reqs)
    (kern, a), (copy, b) = runs[None], runs[False]
    for ra, rb in zip(a, b):
        assert ra.output_tokens == rb.output_tokens, ra.rid
        assert len(ra.output_tokens) == ra.max_new_tokens
        np.testing.assert_allclose(
            ra.output_logprobs, rb.output_logprobs, atol=2e-5)
        assert _logprob_gap(kern.params, ra) < TOL
    ks, cs = kern.stats, copy.stats
    assert ks["ragged_dispatches"] == ks["decode_calls"] > 0
    assert cs["ragged_dispatches"] == 0 and cs["decode_calls"] > 0
    for name in ("prefill_calls", "suffix_calls", "copy_calls",
                 "decode_passes", "kv_columns_read", "window_copies"):
        assert ks[name] == cs[name], name
    assert ks["decode_attended_cols"] == ks["kv_columns_read"]
    assert ks["decode_attended_cols"] < cs["decode_attended_cols"]


def test_a_ring_is_reused_whole_or_not_at_all(model):
    """A retained slot continues a prompt that extends its WHOLE sequence;
    a prompt that shares only a part of it computes that part again, and
    is counted."""
    eng = _engine(model)
    first = GenRequest(rid="a", input_ids=[int(t) for t in _ids(30, seed=31)],
                       max_new_tokens=5, temperature=1.0)
    # 1 token from the prefill + one whole chunk of 4: the ring ends where
    # the host's count does
    eng.submit_batch([first])
    _drain(eng, [first])
    whole = list(first.input_ids) + list(first.output_tokens)
    # the next turn: everything so far, and more (the last sampled token
    # was never fed, so the slot holds one position less than `whole`)
    turn = GenRequest(rid="b", input_ids=whole + [5, 6, 7], max_new_tokens=5,
                      temperature=1.0)
    eng.submit_batch([turn])
    _drain(eng, [turn])
    assert turn.cache_hit_tokens == len(whole) - 1
    assert eng.stats["state_reuse_dropped"] == 0
    assert _logprob_gap(eng.params, turn) < TOL
    # a branch from the middle of what a slot retains
    branch = GenRequest(rid="c", input_ids=whole[:25] + [9, 9, 9],
                        max_new_tokens=5, temperature=1.0)
    eng.submit_batch([branch])
    _drain(eng, [branch])
    assert branch.cache_hit_tokens == 0
    assert eng.stats["state_reuse_dropped"] == 1
    assert _logprob_gap(eng.params, branch) < TOL


def test_generation_goes_on_over_a_live_swap_of_weights(model):
    """`swap_weights_live` keeps the cache, as for every kind."""
    cfg, params = model
    eng = _engine(model)
    req = GenRequest(rid="s", input_ids=[int(t) for t in _ids(20, seed=41)],
                     max_new_tokens=12, temperature=1.0)
    eng.submit_batch([req])
    eng.step()
    before = len(req.output_tokens)
    eng.swap_weights_live(params, version=1)
    _drain(eng, [req])
    assert 0 < before < len(req.output_tokens) == 12
    assert _logprob_gap(eng.params, req) < TOL
