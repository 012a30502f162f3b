"""`longcat_flash` (LongCat-Flash, the language model of LongCat-Flash-Omni):
latent attention in double layers around a shortcut expert layer with
identity experts, on the cache forwards.  Toy widths on the CPU (hidden 64,
4 heads of 16 + 8 | 16, latents 48 and 32, 8 routed experts of which 4 are
held + 4 identity experts, top-3, float32, seeded random weights), against
the benchmark's plain reference (`benchmarks/lib/reference_longcat_flash.py`).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import init_params, latent, moe
from areal_tpu.models import transformer as tf
from areal_tpu.models.model_config import TransformerConfig
from benchmarks.lib import reference_longcat_flash as ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HF = {
    "attention_bias": False, "vocab_size": 256, "hidden_size": 64,
    "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4,
    "experts_held": {"first": 2, "of": 8}, "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
}
CFG = TransformerConfig.from_hf(HF).replace(
    dtype="float32", param_dtype="float32", eos_token_id=None)
TOL = 2e-5  # float32 logits of magnitude one, different orders of summation


def _params(cfg=CFG, seed=0):
    p = init_params(cfg, jax.random.PRNGKey(seed))
    bias = p["layers"]["moe"]["router_bias"]
    p["layers"]["moe"]["router_bias"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(seed + 5), bias.shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 256)


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(ref.logits(params, HF, ids))


def test_the_family_is_read_from_its_own_keys():
    assert CFG.attn_kind == "latent" and tf.is_latent(CFG)
    assert (CFG.num_layers, CFG.attn_sublayers) == (2, 4)
    assert (CFG.head_dim_, CFG.v_head_dim, CFG.latent_row_dim) == (24, 16, 40)
    assert (CFG.num_experts, CFG.held_range, CFG.zero_expert_num) == (8, (2, 6), 4)
    assert CFG.router_kind == "softmax" and not CFG.norm_topk_prob
    assert CFG.routed_scaling_factor == 6 and CFG.num_experts_per_tok == 3
    assert CFG.ffn_kinds is None and CFG.layer_kinds is None
    assert tf.slot_kind(CFG).holds == frozenset({"latent"})


def test_from_hf_to_hf_round_trip():
    d = CFG.to_hf_dict()
    for key, value in HF.items():
        assert d[key] == value, key
    again = TransformerConfig.from_hf(d).replace(
        dtype="float32", param_dtype="float32", eos_token_id=None)
    assert again == CFG.replace(hf_architecture=again.hf_architecture)


@pytest.mark.parametrize("key,value", [
    ("zero_expert_type", "copy"), ("attention_method", "GQA"),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("attention_bias", True),
])
def test_what_is_not_built_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        TransformerConfig.from_hf(
            {**HF, "model_type": "longcat_flash", key: value})


def test_the_packed_training_forward_refuses_the_family(params):
    with pytest.raises(NotImplementedError, match="longcat_flash"):
        tf.forward_packed(params, CFG, {
            "input_ids": jnp.zeros(8, jnp.int32),
            "positions": jnp.arange(8), "segment_ids": jnp.zeros(8, jnp.int32),
        })


def test_checkpoint_names_round_trip(params):
    from areal_tpu.models import hf

    state = dict(hf.params_to_hf_state(params, CFG))
    assert "model.layers.1.self_attn.1.kv_a_proj_with_mqa.weight" in state
    assert "model.layers.0.mlp.router.e_score_correction_bias" in state
    assert "model.layers.1.mlp.experts.5.down_proj.weight" in state
    assert "model.layers.1.mlp.experts.6.down_proj.weight" not in state  # held: 2-5
    assert state["model.layers.0.mlps.1.gate_proj.weight"].shape == (96, 64)
    back = hf.state_to_params(iter(state.items()), CFG, "float32")
    flat, flat_back = (dict(jax.tree_util.tree_leaves_with_path(t))
                       for t in (params, back))
    assert flat.keys() == flat_back.keys()
    for path, a in flat.items():
        np.testing.assert_array_equal(np.asarray(a), flat_back[path], str(path))
    del state["model.layers.1.input_layernorm.0.weight"]
    with pytest.raises(ValueError, match="incomplete weights"):
        hf.state_to_params(iter(state.items()), CFG, "float32")


def _published():
    with open(os.path.join(
            REPO, "benchmarks/configs/longcat-flash-omni.json")) as f:
        return json.load(f)


def test_the_published_width_cut_holds_what_its_file_says():
    """Parameters from shapes only, and the latent pool of the cell."""
    hf = _published()
    cfg = TransformerConfig.from_hf(hf).replace(
        dtype="bfloat16", param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == hf["bench"]["bytes"]["parameters_held"] == 5_172_749_312
    pool = jax.eval_shape(lambda: tf.init_kv_cache(cfg, 41, 8192, "bfloat16"))
    assert set(pool) == {"lat"}
    assert pool["lat"].shape == (8, 41, 576, 8192)
    assert pool["lat"].size * 2 == 41 * 8192 * 9216 \
        == hf["bench"]["bytes"]["pool_bytes_41_rows_of_8192"]
    assert tf.kv_cache_partition_specs(cfg).keys() == pool.keys()
    specs = tf.param_partition_specs(cfg, tp=1)
    assert (jax.tree_util.tree_structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree_util.tree_structure(shapes))


def _prefill(params, ids, lens, slots, cache, bucket):
    pad = jnp.zeros((ids.shape[0], bucket), jnp.int32)
    pad = pad.at[:, : ids.shape[1]].set(ids)
    return jax.jit(lambda p, c: tf.forward_prefill(
        p, CFG, pad, jnp.asarray(lens), c, jnp.asarray(slots)))(params, cache)


@pytest.mark.parametrize("lens", [(40, 40), (17, 33), (1, 2)])
def test_the_prefill_program_gives_the_reference_s_logits(params, ids, want, lens):
    cache = tf.init_kv_cache(CFG, 5, 64, "float32")
    logits, cache = _prefill(params, ids, lens, (3, 0), cache, 64)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(
            np.asarray(logits[b]), want[b, n - 1], atol=TOL, rtol=0)
    # a prompt's rows lie at its positions, nothing past its length written
    lat = np.asarray(cache["lat"])
    assert lat.shape == (4, 5, 40, 64)
    assert np.abs(lat[:, 3, :, : lens[0]]).min(axis=(0, 1)).max() > 0
    assert not lat[:, 3, :, lens[0]:].any() and not lat[:, (1, 2, 4)].any()


def _decode(params, cache, slots, n_slots, tokens, lengths, window=64,
            ragged=False):
    tok = jnp.zeros(n_slots, jnp.int32).at[jnp.asarray(slots)].set(tokens)
    ln = jnp.zeros(n_slots, jnp.int32).at[jnp.asarray(slots)].set(lengths)
    active = jnp.zeros(n_slots, bool).at[jnp.asarray(slots)].set(True)
    return jax.jit(lambda p, c: latent.forward_decode(
        p, CFG, tok, ln, c, key_window=window, active=active,
        ragged=ragged))(params, cache)


@pytest.mark.parametrize("ragged", [False, True], ids=["copy", "kernel"])
def test_decode_through_the_latent_cache_gives_the_reference_s_logits(
        params, ids, want, ragged):
    """Prefill 20 tokens, then every further token through the cache:
    logits at every position, not tokens; on the copy of the window and on
    the paged kernel (`ops/latent_decode.py`, interpreted)."""
    cache = tf.init_kv_cache(CFG, 5, 64, "float32")
    _, cache = _prefill(params, ids[:, :20], (20, 20), (1, 3), cache, 32)
    for t in range(20, 40):
        untouched = np.asarray(cache["lat"][:, (0, 2, 4)])
        logits, cache, counts = _decode(
            params, cache, (1, 3), 5, ids[:, t], jnp.asarray([t, t]),
            ragged=ragged)
        np.testing.assert_allclose(
            np.asarray(logits)[[1, 3]], want[:, t], atol=TOL, rtol=0)
        # an inactive slot writes nothing
        np.testing.assert_array_equal(
            untouched, np.asarray(cache["lat"][:, (0, 2, 4)]))
        c = dict(zip(latent.DECODE_COUNTERS, np.asarray(counts)))
        assert c["expert_assignments"] == 2 * 3 * 2  # slots x k x layers
        assert 0 <= c["identity_assignments"] <= 12
        assert c["expert_assignments_held"] <= 12 - c["identity_assignments"]
        assert c["latent_rows_read"] == 2 * (t + 1) * 4  # slots, rows, sublayers


def test_a_suffix_on_a_retained_prefix_and_a_sibling_s_copy(params, ids, want):
    """Row 0 continues its own retained prefix (16 positions) in its slot;
    row 1's sibling takes the shared prompt's first 16 columns from slot 3
    into slot 0 and computes the rest; then both decode on."""
    cache = tf.init_kv_cache(CFG, 5, 64, "float32")
    _, cache = _prefill(params, ids[:, :16], (16, 16), (1, 3), cache, 16)
    sfx = jnp.zeros((2, 32), jnp.int32)
    sfx = sfx.at[0, :14].set(ids[0, 16:30]).at[1, :20].set(ids[1, 16:36])
    logits, cache = jax.jit(lambda p, c: tf.forward_prefill_cached(
        p, CFG, sfx, jnp.asarray([16, 16]), jnp.asarray([14, 20]), c,
        jnp.asarray([1, 0]), copy_src=jnp.asarray([1, 3]), copy_block=16,
        key_window=64))(params, cache)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0, 29], atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(logits[1]), want[1, 35], atol=TOL, rtol=0)
    np.testing.assert_array_equal(  # the copy, and nothing past a suffix's end
        np.asarray(cache["lat"][:, 0, :, :16]), np.asarray(cache["lat"][:, 3, :, :16]))
    assert not np.asarray(cache["lat"][:, 1, :, 30:]).any()
    logits, cache, _ = _decode(
        params, cache, (1, 0), 5, jnp.asarray([ids[0, 30], ids[1, 36]]),
        jnp.asarray([30, 36]))
    np.testing.assert_allclose(np.asarray(logits[1]), want[0, 30], atol=TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(logits[0]), want[1, 36], atol=TOL, rtol=0)


def test_a_suffix_that_ends_at_the_pool_s_end_is_written_where_it_lies(
        params, ids, want):
    """The block of a suffix bucket does not fit behind `starts`: it is
    laid back and overlaid, and what lay before it stays."""
    cache = tf.init_kv_cache(CFG, 3, 40, "float32")
    _, cache = _prefill(params, ids[:1, :30], (30,), (1,), cache, 32)
    before = np.asarray(cache["lat"][:, 1, :, :30])
    sfx = jnp.zeros((1, 16), jnp.int32).at[0, :10].set(ids[0, 30:40])
    logits, cache = jax.jit(lambda p, c: tf.forward_prefill_cached(
        p, CFG, sfx, jnp.asarray([30]), jnp.asarray([10]), c,
        jnp.asarray([1]), key_window=40))(params, cache)
    np.testing.assert_allclose(np.asarray(logits[0]), want[0, 39], atol=TOL, rtol=0)
    np.testing.assert_array_equal(before, np.asarray(cache["lat"][:, 1, :, :30]))


def test_absorbed_attention_equals_expanded_attention(params):
    """The two forms on one sublayer: the chunk's own positions expanded,
    against the same rows cached and attended absorbed, one query at a time
    (both decode paths) and as a suffix."""
    ap = jax.tree_util.tree_map(
        lambda a: a[1, 0], latent.by_head(CFG, params["layers"]["attn"]))
    T = 32
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, 64))
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    cos, sin = tf.rope_cos_sin(pos, CFG.qk_rope_head_dim, CFG.rope_theta)
    q_nope, q_rope, row = latent.mla_project(CFG, ap, h, cos, sin)
    full = np.asarray(latent.expanded_attend(
        CFG, ap, q_nope, q_rope, row, jnp.ones((1, T), bool)))
    lat = jnp.zeros((4, 2, 40, 48)).at[2, 1, :, :16].set(row[0, :16].T)
    at = {"K": 48, "slot_base": 1, "slots": jnp.asarray([1]),
          "starts": jnp.asarray([16]), "n_real": jnp.asarray([16])}
    suffix = latent.absorbed_attend(
        CFG, ap, q_nope[:, 16:], q_rope[:, 16:], row[:, 16:], lat, 2, at)
    np.testing.assert_allclose(np.asarray(suffix), full[:, 16:], atol=2e-6, rtol=0)
    lat = lat.at[2, 1, :, :T - 1].set(row[0, :T - 1].T)
    # one query: over the copy of the window, and through the paged kernel
    for paged in ({"ragged": False},
                  {"ragged": True, "live": jnp.ones((1,), bool)}):
        one = latent.absorbed_attend(
            CFG, ap, q_nope[:, -1:], q_rope[:, -1:], row[:, -1:], lat, 2,
            {**at, "starts": jnp.asarray([T - 1]), **paged})
        np.testing.assert_allclose(
            np.asarray(one), full[:, -1:], atol=2e-6, rtol=0)


def test_the_expert_branch_leaves_after_the_first_attention_and_joins_last(params):
    """`s` is computed from N_2(a0) and added after FFN_1: moved to either
    dense FFN's place the layer reads something else."""
    sh = ref.shapes(HF)
    layers, l = params["layers"], 1
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    eps = sh["eps"]
    attn = {k: sh[k] for k in ("H", "nope", "rope", "vd", "rkv", "q_scale",
                               "kv_scale", "eps", "theta")}
    pick = ref._pick

    def norm(a, name, i):
        return ref._rms(a, layers[name][l, i], eps)

    def expert(h):
        return ref.moe(h, pick(layers["moe"], l), top_k=sh["top_k"],
                       scale=sh["scale"], n_routed=sh["n_routed"],
                       first=sh["first"], n_held=sh["n_held"])[0]

    with jax.default_matmul_precision("highest"):
        a0 = x + ref.mla(norm(x, "input_norm", 0), pick(layers["attn"], l, 0), **attn)
        h0 = norm(a0, "post_attn_norm", 0)
        b0 = a0 + ref.dense_ffn(h0, pick(layers["mlp"], l, 0))
        a1 = b0 + ref.mla(norm(b0, "input_norm", 1), pick(layers["attn"], l, 1), **attn)
        h1 = norm(a1, "post_attn_norm", 1)
        tail = a1 + ref.dense_ffn(h1, pick(layers["mlp"], l, 1))
        right = tail + expert(h0)
        from_the_second_norm = tail + expert(h1)
        b0_early = b0 + expert(h0)  # joined before the second attention
        a1_early = b0_early + ref.mla(
            norm(b0_early, "input_norm", 1), pick(layers["attn"], l, 1), **attn)
        joined_early = a1_early + ref.dense_ffn(
            norm(a1_early, "post_attn_norm", 1), pick(layers["mlp"], l, 1))

        pos = jnp.arange(24, dtype=jnp.int32)[None]
        cos, sin = tf.rope_cos_sin(pos, CFG.qk_rope_head_dim, CFG.rope_theta)
        lat = jnp.zeros((4, 1, 40, 24))
        at = {"fresh": True, "valid": jnp.ones((1, 24), bool)}

        def attend(ap, h, j):
            return latent.latent_attend(CFG, ap, h, cos, sin, lat, j, at)[0]

        got, _ = latent.double_layer(
            CFG, {**layers, "attn": latent.by_head(CFG, layers["attn"])}, l,
            x[None], attend, jnp.ones((1, 24), bool))
    got = np.asarray(got[0])
    np.testing.assert_allclose(got, np.asarray(ref.double_layer(x, layers, l, sh)),
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(got, np.asarray(right), atol=TOL, rtol=0)
    for wrong in (from_the_second_norm, joined_early):
        assert np.abs(got - np.asarray(wrong)).max() > 0.05


def _moe_lp(params, l, bias=None):
    mo = params["layers"]["moe"]
    return {"router": mo["router"][l],
            "router_bias": mo["router_bias"][l] if bias is None else bias,
            "w_gate": mo["w_gate"], "w_up": mo["w_up"], "w_down": mo["w_down"],
            "block": l}


def test_the_router_chooses_by_p_plus_b_and_weighs_by_6_p(params):
    """All 12 outputs scored by one softmax, the top 3 of p + b chosen,
    weights 6 p of the chosen, not renormalised."""
    h = jax.random.normal(jax.random.PRNGKey(11), (50, 64))
    bias = jnp.asarray([0.3, 0, 0, 0, 0, -0.3, 0, 0, 0, 0.2, 0, 0], jnp.float32)
    lp = _moe_lp(params, 0, bias=bias)
    with jax.default_matmul_precision("highest"):
        w, idx = moe.route_softmax_bias(CFG, lp, h)
        p = np.asarray(jax.nn.softmax(h @ lp["router"], axis=-1))
    assert p.shape == (50, 12)
    want_idx = np.argsort(-(p + np.asarray(bias)), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    assert (np.sort(want_idx, -1) != np.sort(np.argsort(-p, -1)[:, :3], -1)).any()
    np.testing.assert_allclose(
        np.asarray(w), 6 * np.take_along_axis(p, np.asarray(idx), -1), rtol=1e-6)
    assert not np.allclose(np.asarray(w).sum(-1), 6.0)
    w_ref, idx_ref = ref.route(h, lp, 3, 6.0)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))


def test_a_token_that_chooses_identity_experts_only_is_scaled_once(params):
    """Twelve... here three choices, all identity experts: exactly
    6 (sum p) h, with no expert's product in it."""
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 9, 64))
    bias = jnp.where(jnp.arange(12) >= 8, 10.0, 0.0)
    lp = _moe_lp(params, 1, bias=bias)
    with jax.default_matmul_precision("highest"):
        out, counters = moe.identity_moe_ffn(CFG, lp, h, jnp.float32)
        p = jax.nn.softmax(h[0] @ lp["router"], axis=-1)
    chosen = jnp.sort(p[:, 8:], axis=-1)[:, 1:].sum(-1)  # the 3 largest of 4
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(6 * chosen[:, None] * h[0]), rtol=1e-5,
        atol=1e-6)
    assert dict(zip(moe.IDENTITY_MOE_COUNTERS, np.asarray(counters))) == {
        "expert_assignments": 27, "identity_assignments": 27,
        "expert_assignments_held": 0, "experts_touched": 0}


def test_the_shares_and_the_identity_part_once_add_up_to_the_uncut_layer(params):
    """THE SHARE TEST: windows of held experts tiling 0..8, each share's
    routed part + the identity part; the shares summed with the identity
    part counted once equal the uncut reference's expert layer."""
    key = jax.random.PRNGKey(21)
    D, F, E = 64, 32, 8
    dense = lambda k, shape, fan: jax.random.normal(k, shape) / np.sqrt(fan)  # noqa: E731
    ks = jax.random.split(key, 4)
    full = {"w_gate": dense(ks[0], (2, E, D, F), D),
            "w_up": dense(ks[1], (2, E, D, F), D),
            "w_down": dense(ks[2], (2, E, F, D), F)}
    h = jax.random.normal(ks[3], (2, 20, D))
    l = 1
    base = _moe_lp(params, l)
    uncut = {**{k: base[k] for k in ("router", "router_bias")},
             **{k: v[l] for k, v in full.items()}}
    with jax.default_matmul_precision("highest"):
        want, idx = ref.moe(h.reshape(-1, D), uncut, top_k=3, scale=6.0,
                            n_routed=E, first=0, n_held=E)
        identity, _ = ref.moe(h.reshape(-1, D), uncut, top_k=3, scale=6.0,
                              n_routed=E, first=0, n_held=0)
        total, held_rows = 0, 0
        windows = [(0, 3), (3, 4), (4, 8)]
        for lo, hi in windows:
            cfg = CFG.replace(experts_held=(lo, hi))
            lp = {**base, **{k: v[:, lo:hi] for k, v in full.items()}}
            out, counters = moe.identity_moe_ffn(cfg, lp, h, jnp.float32)
            total = total + out.reshape(-1, D)
            held_rows += int(counters[2])
        total = total - (len(windows) - 1) * identity
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=TOL, rtol=0)
    assert np.abs(np.asarray(identity)).max() > 0.1  # the part is there
    # every routed assignment was held by exactly one share
    assert held_rows == int((np.asarray(idx) < E).sum())


def test_the_splash_path_of_a_fresh_prompt_equals_the_blocked_product(
        params, monkeypatch):
    """On the chip a fresh prompt attends through the splash kernel (query
    and key 24 wide here beside a value of 16, one row narrowed by its
    padding); interpreted here, against the plain product."""
    from areal_tpu.ops import attention

    ap = jax.tree_util.tree_map(
        lambda a: a[0, 1], latent.by_head(CFG, params["layers"]["attn"]))
    T = 256
    h = jax.random.normal(jax.random.PRNGKey(4), (1, T, 64))
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    cos, sin = tf.rope_cos_sin(pos, CFG.qk_rope_head_dim, CFG.rope_theta)
    q_nope, q_rope, row = latent.mla_project(CFG, ap, h, cos, sin)
    valid = pos < 150
    assert not latent._splash_applies(T)
    want = np.asarray(latent.expanded_attend(CFG, ap, q_nope, q_rope, row, valid))
    monkeypatch.setattr(attention, "INTERPRET", True)
    assert latent._splash_applies(T) and not latent._splash_applies(64)
    got = np.asarray(latent.expanded_attend(CFG, ap, q_nope, q_rope, row, valid))
    np.testing.assert_allclose(got[:, :150], want[:, :150], atol=2e-5, rtol=0)
