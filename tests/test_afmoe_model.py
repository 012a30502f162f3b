"""A stack of gated experts behind a leading dense layer (`model_type:
"afmoe"`: sliding and full attention layers in one stack, q/k norm, rotary
embedding on sliding layers only, the attention output under a sigmoid
gate, four norms a block, sigmoid-routed SwiGLU experts at a share beside
one shared expert) against the plain float32 reference the benchmark
carries (`benchmarks/lib/reference_afmoe.py`), at a toy size on the CPU:
layers S . S F S with the first dense, hidden 64, 8 routed experts top-2 of
which 4 are held, window 8 in rows of 40, float32, seeded random weights."""

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
from areal_tpu.ops import attention as attn_mod
from areal_tpu.ops.functional import grpo_loss_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.lib import afmoe_work  # noqa: E402
from benchmarks.lib import reference_afmoe as ref  # noqa: E402

S, F = "sliding_attention", "full_attention"
HF = {
    "model_type": "afmoe", "architectures": ["AfmoeForCausalLM"],
    "num_hidden_layers": 4, "num_dense_layers": 1, "layer_types": [S, S, F, S],
    "sliding_window": 8, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 48, "vocab_size": 128, "num_experts": 4,
    "experts_held": {"first": 2, "of": 8}, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "n_group": 1, "topk_group": 1, "mup_enabled": True,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "hidden_act": "silu",
}


def _cfg(hf=HF, **kw):
    return TransformerConfig.from_hf(hf).replace(**{
        "dtype": "float32", "param_dtype": "float32", "remat": False,
        "eos_token_id": None, **kw})


CFG = _cfg()


def _params(cfg=CFG, seed=0):
    p = tf.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 5), 16))
    # selection (score + bias) and weight (score) must differ, and a norm
    # whose weight is one hides a norm applied in the wrong place
    m = p["layers"]["moe"]["moe"]
    m["router_bias"] = 0.1 * jax.random.normal(next(keys), m["router_bias"].shape)
    for kind in p["layers"]:
        blk = p["layers"][kind]
        for name in ("input_norm", "sandwich_attn_norm", "post_attn_norm",
                     "sandwich_ffn_norm"):
            blk[name] = 1 + 0.2 * jax.random.normal(next(keys), blk[name].shape)
        for name in ("q_norm", "k_norm"):
            blk["attn"][name] = 1 + 0.2 * jax.random.normal(
                next(keys), blk["attn"][name].shape)
    return p


@pytest.fixture(scope="module")
def params():
    return _params()


def _packed(rng, lens=(22, 14), pad=4, vocab=128):
    """Sequences of `lens` packed into one row with `pad` padding behind."""
    seqs = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    packed = {
        "input_ids": jnp.asarray(np.concatenate(seqs + [np.zeros(pad)]), jnp.int32),
        "positions": jnp.asarray(np.concatenate(
            [np.arange(n) for n in lens] + [np.zeros(pad)]), jnp.int32),
        "segment_ids": jnp.asarray(np.concatenate(
            [np.full(n, i) for i, n in enumerate(lens)] + [np.full(pad, -1)]),
            jnp.int32),
    }
    return seqs, packed


# ---------------------------------------------------------------------------
# the configuration and the checkpoint names
# ---------------------------------------------------------------------------


def test_from_hf_builds_the_stack_and_the_share():
    assert CFG.ffn_kinds == ("dense", "moe", "moe", "moe")
    assert CFG.layer_is_sliding == (True, True, False, True)
    assert CFG.sliding_window == 8 and CFG.rope_layers == "sliding"
    assert CFG.num_experts == 8 and CFG.held_range == (2, 6)
    assert CFG.router_kind == "sigmoid" and CFG.routed_scaling_factor == 2.826
    assert CFG.attn_gate and CFG.qk_norm and CFG.sandwich_norms
    assert CFG.scale_embeddings and CFG.moe_aux_coef == 0.0
    assert tf.slot_kind(CFG).holds == {"kv"}
    # a dense model knows nothing of it
    assert TransformerConfig().ffn_kinds is None


def test_to_hf_round_trips():
    first = TransformerConfig.from_hf(HF)
    assert TransformerConfig.from_hf(first.to_hf_dict()) == first
    d = CFG.to_hf_dict()
    assert d["model_type"] == "afmoe" and d["num_experts"] == 4
    assert d["experts_held"] == {"first": 2, "of": 8}
    assert d["layer_types"] == HF["layer_types"] and d["num_dense_layers"] == 1
    whole = TransformerConfig.from_hf({**HF, "num_experts": 8, "experts_held": None})
    assert whole.experts_held is None and "experts_held" not in whole.to_hf_dict()


@pytest.mark.parametrize("bad", [
    {"score_func": "softmax"}, {"n_group": 2}, {"num_shared_experts": 2},
    {"layer_types": [S, F]}, {"rope_scaling": {"type": "yarn"}},
    {"experts_held": {"first": 6, "of": 8}}, {"num_dense_layers": 4},
])
def test_from_hf_refuses_what_it_does_not_build(bad):
    with pytest.raises(ValueError):
        TransformerConfig.from_hf({**HF, **bad})


def test_checkpoint_names_round_trip(params, tmp_path):
    state = list(hf_io.params_to_hf_state(params, CFG))
    names = {n for n, _ in state}
    for want_name in (
        "model.embed_tokens.weight", "model.norm.weight", "lm_head.weight",
        "model.layers.0.self_attn.q_proj.weight",
        "model.layers.0.self_attn.gate_proj.weight",
        "model.layers.0.self_attn.q_norm.weight",
        "model.layers.0.self_attn.k_norm.weight",
        "model.layers.0.input_layernorm.weight",
        "model.layers.0.post_attention_layernorm.weight",
        "model.layers.0.pre_mlp_layernorm.weight",
        "model.layers.0.post_mlp_layernorm.weight",
        "model.layers.0.mlp.gate_proj.weight",
        "model.layers.1.mlp.router.gate.weight",
        "model.layers.1.mlp.expert_bias",
        "model.layers.1.mlp.shared_experts.down_proj.weight",
        # the share holds experts 2-5 of 8, under their own ids
        "model.layers.1.mlp.experts.2.gate_proj.weight",
        "model.layers.3.mlp.experts.5.down_proj.weight",
        "model.layers.3.self_attn.o_proj.weight",
    ):
        assert want_name in names
    assert "model.layers.1.mlp.experts.0.up_proj.weight" not in names
    assert "model.layers.1.mlp.gate_proj.weight" not in names
    back = hf_io.state_to_params(iter(state), CFG, "float32")
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(
        params)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="incomplete weights"):
        hf_io.state_to_params(
            (kv for kv in state if "experts.3.up_proj" not in kv[0]),
            CFG, "float32")
    # the repo's own export, through the files
    hf_io.save_hf_checkpoint(params, CFG, str(tmp_path), save_dtype="float32")
    loaded, cfg2 = hf_io.load_hf_params(str(tmp_path), dtype="float32")
    assert cfg2.ffn_kinds == CFG.ffn_kinds and cfg2.held_range == (2, 6)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(loaded)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# one rule for a sliding layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,W", [(12, 5), (9, 9), (7, 12), (384, 130)])
def test_the_window_s_edge_is_one_rule(T, W):
    """`make_attention_mask`, the splash kernel's `LocalMask`, the
    reference's `sees` and the benchmark's count of attended pairs all read
    "key j is seen iff i - window < j <= i"."""
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    want = (j <= i) & (j > i - W)
    pos = jnp.arange(T)[None]
    dense = np.asarray(attn_mod.make_attention_mask(
        jnp.zeros((1, T), jnp.int32), pos, W))[0, 0]
    np.testing.assert_array_equal(dense, want)
    np.testing.assert_array_equal(np.asarray(ref.sees(i, j, W)), want)
    np.testing.assert_array_equal(attn_mod._mask_for(T, W)[:, :], want)
    assert afmoe_work.pairs(T, W) == int(want.sum())
    # the edge itself: the key `W` back is out, the one before it is in
    if T > W:
        assert not dense[W, 0] and dense[W, 1] and dense[W - 1, 0]
    # and a full layer
    np.testing.assert_array_equal(attn_mod._mask_for(T, None)[:, :], j <= i)
    assert afmoe_work.pairs(T) == int((j <= i).sum())


def test_a_sliding_layer_forgets_what_a_full_layer_sees(params):
    """Changing a token more than `window` back leaves a sliding layer's
    output at the last position as it was and changes a full layer's."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64))
    pos = jnp.arange(24)[None]
    seg = jnp.zeros((1, 24), jnp.int32)
    cos, sin = tf.rope_cos_sin(pos, 16, 10000.0)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])

    def last(x, sliding):
        mask = tf.make_attention_mask(seg, pos, 8 if sliding else None)
        y, _ = tf._layer_forward(CFG, None, lp, x, cos, sin, seg, pos, mask,
                                 sliding=sliding)
        return np.asarray(y[0, -1])

    # position 23 sees 16..23 in a sliding layer: 15 is the first one out
    far, near = x.at[0, 15].add(1.0), x.at[0, 16].add(1.0)
    np.testing.assert_array_equal(last(far, True), last(x, True))
    assert np.abs(last(near, True) - last(x, True)).max() > 1e-4
    assert np.abs(last(far, False) - last(x, False)).max() > 1e-4


def test_rotary_embedding_is_on_sliding_layers_only(params):
    """A full layer's output does not depend on the positions it is given;
    a sliding layer's does."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 64))
    seg = jnp.zeros((1, 12), jnp.int32)
    pos = jnp.arange(12)[None]
    mask = tf.make_attention_mask(seg, pos, None)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["moe"])

    def out(shift, sliding):
        cos, sin = tf.rope_cos_sin(pos * shift, 16, 10000.0)
        return np.asarray(tf._layer_forward(
            CFG, None, lp, x, cos, sin, seg, pos, mask, sliding=sliding)[0])

    np.testing.assert_array_equal(out(1, False), out(3, False))
    assert np.abs(out(1, True) - out(3, True)).max() > 1e-3


# ---------------------------------------------------------------------------
# the expert layer at a share
# ---------------------------------------------------------------------------


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Each of eight shares holds 1 of the 8 routed experts, routes over all
    8 and computes its own expert's part; with the shared expert, which
    every share computes alike, counted once, the parts add up to what the
    uncut reference gives for the whole layer."""
    whole_hf = {**HF, "num_experts": 8, "experts_held": None}
    whole = _params(_cfg(whole_hf), seed=3)
    m = jax.random.normal(jax.random.PRNGKey(11), (2, 9, 64))
    mp = ref.block_params(whole, "moe", 1)["moe"]
    flat = m.reshape(18, 64)
    w, idx, _ = ref.route(flat, mp, 2, 2.826, True)
    want = ref.experts(flat, mp, w, idx, 0, 8).reshape(2, 9, 64)
    shared = ref._swiglu(flat, mp["ws_gate"], mp["ws_up"],
                         mp["ws_down"]).reshape(2, 9, 64)
    parts, rows = [], 0
    for first in range(8):
        cfg = _cfg({**HF, "num_experts": 1,
                    "experts_held": {"first": first, "of": 8}})
        lp = {k: (v[first: first + 1] if k in ("w_gate", "w_up", "w_down")
                  else v) for k, v in mp.items()}
        out, counters = moe.gated_moe_ffn(cfg, lp, m, jnp.float32)
        parts.append(out)
        rows += int(counters[0])
        # the share's own reference agrees with it, too
        own = ref.experts(flat, lp, w, idx, first, 1).reshape(2, 9, 64)
        np.testing.assert_allclose(out, own, atol=2e-5)
        assert int(counters[1]) == int(counters[0])  # one expert held
    assert rows == 18 * 2  # every assignment lands on exactly one share
    np.testing.assert_allclose(sum(parts) - 7 * shared, want, atol=5e-5)
    # and no share alone is the layer
    assert float(jnp.abs(parts[0] - want).max()) > 1e-2


# 256 tokens x top-2 of 16 experts, 2 of them held: an even router would
# send 64 rows here; the sorted buffers are blocks of 128 rows (96 padded
# to the grouped product's multiple)
BLOCK_HF = {**HF, "num_experts": 2, "experts_held": {"first": 3, "of": 16}}


def _steered(held_rows):
    """One expert layer whose router reads a token's choice off the token
    itself (logit of expert e = coordinate e), and 256 tokens of which
    exactly `held_rows` (token, choice) pairs go to the held experts 3 and
    4 (None: as the draw falls)."""
    cfg = _cfg(BLOCK_HF)
    lp = jax.tree_util.tree_map(
        lambda a: a[0], _params(cfg, seed=4)["layers"]["moe"]["moe"])
    lp["router"] = jnp.eye(64, 16)
    m = np.array(jax.random.normal(jax.random.PRNGKey(21), (256, 64)))
    if held_rows is not None:
        # every token takes two of the experts held elsewhere, the first
        # `held_rows` - 256 tokens both held ones, the next ones one of them
        both = max(0, held_rows - 256)
        one = held_rows - 2 * both
        choice = np.tile([[7, 9]], (256, 1))
        choice[:both] = [3, 4]
        choice[both: both + one, 0] = [3, 4] * (one // 2) + [3] * (one % 2)
        m[:, :16] *= 0.1
        np.put_along_axis(m, choice, 4.0 + m[:, :2], axis=1)
    return cfg, lp, jnp.asarray(m.reshape(2, 128, 64))


@pytest.mark.parametrize("held_rows,buffered", [
    (0, 0), (None, 128), (128, 128), (129, 256), (256, 256), (257, 384),
    (512, 512),
])
def test_the_buffers_follow_the_rows_held_and_every_block_count_is_the_layer(
        held_rows, buffered, monkeypatch):
    """None held (no trip of the loop), the draw's eighth, a block's edge
    and one row more (a second trip), the next edge and one more, and every
    row held: the layer runs the blocks its rows need, says so
    (`expert_rows_buffered`), and its output and every gradient are those
    of ONE block over all N * k assignments and the float32 reference's."""
    cfg, lp, m = _steered(held_rows)
    assert moe.held_row_block(256, 2, 2, 16) == 128
    probe = jax.random.normal(jax.random.PRNGKey(22), m.shape)

    def ours(lp, m):
        out, counters = moe.gated_moe_ffn(cfg, lp, m, jnp.float32)
        return jnp.sum(out * probe), (out, counters)

    def theirs(lp, m):
        flat = m.reshape(256, 64)
        w, idx, _ = ref.route(flat, lp, 2, 2.826, True)
        return jnp.sum(ref.experts(flat, lp, w, idx, 3, 2).reshape(m.shape) * probe)

    grad = jax.value_and_grad(ours, argnums=(0, 1), has_aux=True)
    (_, (out, counters)), got = grad(lp, m)
    if held_rows is not None:
        assert int(counters[0]) == held_rows
    else:
        assert 0 < int(counters[0]) <= 128
    assert int(counters[2]) == buffered
    monkeypatch.setattr(moe, "held_row_block", lambda *a: 512)
    (_, (all_out, all_counters)), whole = grad(lp, m)
    assert int(all_counters[2]) == (512 if held_rows != 0 else 0)
    np.testing.assert_allclose(out, all_out, atol=2e-5)
    want = jax.grad(theirs, argnums=(0, 1))(lp, m)
    for (path, g), t, w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                               jax.tree_util.tree_leaves(whole),
                               jax.tree_util.tree_leaves(want)):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - t).max()) < 1e-5 * scale + 1e-7, path
        assert float(jnp.abs(g - w).max()) < 3e-4 * scale + 1e-7, path
    # the router learns through the rows held here, and only through them
    for name in ("router", "w_gate"):
        assert (float(jnp.abs(got[0][name]).max()) > 0) == (held_rows != 0)


def test_a_block_follows_the_even_share():
    """1.5 x the rows an even router would send here, in the grouped
    product's multiples, and never more than all assignments."""
    assert moe.held_row_block(16384, 8, 16, 128) == 24576
    assert moe.held_row_block(40, 2, 4, 8) == 128
    assert moe.held_row_block(1000, 8, 128, 128) == 8064


def test_the_router_scores_and_chooses_in_float32():
    """bfloat16 activations and weights, float32 scores and top-k: the
    choice equals the float32 computation's on the same (rounded) inputs."""
    cfg = _cfg(dtype="bfloat16")
    lp = jax.tree_util.tree_map(
        lambda a: a[0], _params()["layers"]["moe"]["moe"])
    lp = {**lp, "router": lp["router"].astype(jnp.bfloat16)}
    x = jax.random.normal(jax.random.PRNGKey(4), (50, 64)).astype(jnp.bfloat16)
    w, idx = moe.route_sigmoid(cfg, lp, x)
    assert w.dtype == jnp.float32
    s = jax.nn.sigmoid(np.asarray(x, np.float32) @ np.asarray(lp["router"], np.float32))
    want = np.argsort(-(s + np.asarray(lp["router_bias"])), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))


def test_counters_leave_padding_out(params):
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"]["moe"]["moe"])
    m = jax.random.normal(jax.random.PRNGKey(5), (1, 20, 64))
    valid = (jnp.arange(20) < 15)[None]
    out_all, c_all = moe.gated_moe_ffn(CFG, lp, m, jnp.float32)
    out, c = moe.gated_moe_ffn(CFG, lp, m, jnp.float32, valid)
    np.testing.assert_array_equal(out, out_all)  # counters only
    _, c15 = moe.gated_moe_ffn(CFG, lp, m[:, :15], jnp.float32)
    np.testing.assert_array_equal(c, c15)
    assert int(c[0]) < int(c_all[0]) and 0 < int(c[1]) <= int(c[0])
    # the sorted buffers hold the padding's assignments too: 20 x top-2 fit
    # one block
    assert int(c[2]) == int(c_all[2]) == 128


# ---------------------------------------------------------------------------
# the packed forward, the loss and its gradients against the reference
# ---------------------------------------------------------------------------


def test_the_packed_forward_gives_the_reference_s_logits(params):
    seqs, packed = _packed(np.random.default_rng(0))
    got = tf.forward_packed(params, CFG, packed)
    for lo, seq in zip((0, 22), seqs):
        want = ref.logits(params, HF, seq[None])[0]
        np.testing.assert_allclose(got[lo: lo + len(seq)], want, atol=5e-5)
    # remat changes nothing but memory
    again = tf.forward_packed(params, CFG.replace(remat=True), packed)
    np.testing.assert_allclose(again, got, atol=1e-5)


def test_a_kind_is_one_scan_over_periods_of_its_pattern():
    """Layers 1-9 of the published model: the eight expert layers S F S S
    S F S S are two scan steps of the period S F S S; groups of two layers
    keep the period; a pattern that does not repeat is one step."""
    lt = [S, S, F, S, S, S, F, S, S]
    cfg = _cfg({**HF, "num_hidden_layers": 9, "layer_types": lt})
    assert tf._kind_scan_plan(cfg) == [("dense", 0, 1, 1), ("moe", 1, 8, 4)]
    assert tf.effective_scan_unroll(cfg.replace(scan_unroll=4)) == 2
    two = cfg.replace(leading_dense_layers=2, num_layers=10,
                      layer_is_sliding=(True,) + cfg.layer_is_sliding,
                      layer_group_size=2)
    assert tf._kind_scan_plan(two) == [("dense", 0, 2, 2), ("moe", 2, 8, 4)]
    with pytest.raises(ValueError, match="must divide the 1 dense"):
        tf._kind_scan_plan(cfg.replace(layer_group_size=2))
    odd = _cfg({**HF, "num_hidden_layers": 6, "layer_types": [S, S, F, S, S, S]})
    assert tf._kind_scan_plan(odd) == [("dense", 0, 1, 1), ("moe", 1, 5, 5)]
    # and the forward agrees with the reference through two scan steps
    params = _params(cfg, seed=2)
    seqs, packed = _packed(np.random.default_rng(5))
    hf9 = {**HF, "num_hidden_layers": 9, "layer_types": lt}
    for kw in ({}, {"remat": True, "scan_unroll": 2}):
        got = tf.forward_packed(params, cfg.replace(**kw), packed)
        for lo, seq in zip((0, 22), seqs):
            want = ref.logits(params, hf9, seq[None])[0]
            np.testing.assert_allclose(got[lo: lo + len(seq)], want, atol=1e-4)


def _grpo_batch(rng, seqs, packed):
    T = packed["input_ids"].shape[0]
    real = np.asarray(packed["segment_ids"]) >= 0
    # predictor-aligned: entry t is about token t + 1, never across a seam
    loss_mask = real.copy()
    for end in np.cumsum([len(s) for s in seqs]):
        loss_mask[end - 1] = False
    loss_mask[:3] = False  # a prompt
    return {
        **packed,
        "loss_mask": jnp.asarray(loss_mask, jnp.float32),
        "logprobs": jnp.asarray(rng.normal(-4.8, 0.3, T), jnp.float32),
        "prox_logp": jnp.asarray(rng.normal(-4.8, 0.3, T), jnp.float32),
        "advantages": jnp.asarray(rng.normal(0, 1, T), jnp.float32),
    }


def _unpacked(batch, seqs, key):
    """A packed [T] array as one row a sequence, predictor-aligned [B, L-1]
    (zero behind a shorter sequence's end)."""
    L = max(len(s) for s in seqs)
    out, lo = np.zeros((len(seqs), L - 1), np.float32), 0
    for i, s in enumerate(seqs):
        out[i, : len(s) - 1] = np.asarray(batch[key])[lo: lo + len(s) - 1]
        lo += len(s)
    return jnp.asarray(out)


def test_the_grpo_loss_and_every_gradient_equal_the_reference_s(params):
    """Two sequences packed into one row with padding behind them, through
    the deferred head and the fused cross-entropy, under the decoupled GRPO
    loss the actor uses: the loss and every parameter's gradient are the
    reference's over the two sequences; the selection bias gets none."""
    rng = np.random.default_rng(3)
    seqs, packed = _packed(rng)
    batch = _grpo_batch(rng, seqs, packed)
    weight = float(batch["loss_mask"].sum())
    # pull the probabilities near the proximal ones so that the clip binds
    # on some tokens and not on others
    logp = jax.nn.log_softmax(tf.forward_packed(params, CFG, packed), -1)
    own = jnp.take_along_axis(
        logp, jnp.roll(packed["input_ids"], -1)[:, None], -1)[:, 0]
    batch["prox_logp"] = own + 0.3 * batch["prox_logp"] + 1.4
    batch["logprobs"] = batch["prox_logp"] + 0.05 * batch["logprobs"]

    def ours(p, cfg=CFG):
        out = tf.forward_lm(
            p, cfg, packed["input_ids"][None], packed["positions"][None],
            packed["segment_ids"][None])
        out = out._replace(hidden=out.hidden[0])
        loss, stats = grpo_loss_fn(out, batch, eps_clip=0.2)
        return loss / weight, (stats, out.counters)

    L = max(len(s) for s in seqs)
    ids = np.zeros((2, L), np.int32)
    for i, s in enumerate(seqs):
        ids[i, : len(s)] = s

    def theirs(p):
        return ref.grpo_loss(
            p, HF, ids, _unpacked(batch, seqs, "loss_mask"),
            _unpacked(batch, seqs, "logprobs"),
            _unpacked(batch, seqs, "advantages"),
            _unpacked(batch, seqs, "prox_logp"), 0.2, weight)

    (loss, (stats, counters)), got = jax.value_and_grad(ours, has_aux=True)(params)
    want_loss, want = jax.value_and_grad(theirs)(params)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    assert 0 < float(stats["clip_ratio"]) < weight
    flat_g, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_w = jax.tree_util.tree_leaves(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), w in zip(flat_g, flat_w):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - w).max()) < 3e-4 * scale + 1e-7, path
    bias = got["layers"]["moe"]["moe"]["router_bias"]
    assert float(jnp.abs(bias).max()) == 0.0
    assert float(jnp.abs(got["layers"]["moe"]["moe"]["router"]).max()) > 0
    # 36 tokens x top-2 over 3 expert layers, half of the experts held
    assert 0 < int(counters["expert_assignments_held"]) < 36 * 2 * 3
    assert 0 < int(counters["expert_load_max"]) <= 36
    # remat changes nothing but memory
    again = jax.grad(lambda p: ours(p, CFG.replace(remat=True))[0])(params)
    for g, w in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(g, w, atol=1e-6)


# ---------------------------------------------------------------------------
# both splash masks in one program
# ---------------------------------------------------------------------------

SPLASH_HF = {
    **HF, "num_hidden_layers": 3, "layer_types": [S, F, S],
    "sliding_window": 130, "hidden_size": 64, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 128, "num_experts": 2,
    "experts_held": {"first": 0, "of": 4},
}


def test_splash_runs_both_masks_in_one_program(monkeypatch):
    """One row of 640 (five blocks of 128) holding 300 + 250 tokens, window
    130: with the Pallas kernels interpreted, a sliding layer runs under
    `LocalMask` and a full one under `CausalMask` in the same program, and
    the logits and gradients are the einsum path's under its two [T, T]
    masks.  The block counts are split by kind."""
    cfg = _cfg(SPLASH_HF)
    params = _params(cfg, seed=1)
    _, packed = _packed(np.random.default_rng(1), lens=(300, 250), pad=90)
    probe = jnp.asarray(
        np.random.default_rng(2).normal(size=(640, 128)), jnp.float32)

    def run(p):
        # padding rows (a segment of their own under the kernel, attending
        # nothing under the einsum mask) are nobody's to read
        return jnp.sum((tf.forward_packed(p, cfg, packed) * probe)[:550])

    want, want_g = jax.value_and_grad(run)(params)
    assert attn_mod.implementations_taken()[(640, 2, 1, 128)] == "einsum"
    seg = packed["segment_ids"][None]
    assert tf.attention_block_counts(cfg, seg) == {}
    monkeypatch.setattr(attn_mod, "INTERPRET", True)
    made = []
    real = attn_mod._make_kernel
    monkeypatch.setattr(
        attn_mod, "_make_kernel",
        lambda T, g, window, *a, **kw: made.append(window) or real(
            T, g, window, *a, **kw))
    got, got_g = jax.value_and_grad(run)(params)
    assert attn_mod.implementations_taken()[(640, 2, 1, 128)] == "splash"
    assert set(made) == {130, None}
    np.testing.assert_allclose(got, want, rtol=1e-4)
    for (path, g), w in zip(
            jax.tree_util.tree_flatten_with_path(got_g)[0],
            jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.abs(w).max()) + 1e-6
        assert float(jnp.abs(g - w).max()) < 1e-3 * scale + 1e-6, path
    counts = {k: int(v) for k, v in
              tf.attention_block_counts(cfg, seg).items()}
    # blocks (q, kv) of 128: the sequences cover blocks 0-2 and 2-4.  The
    # causal mask holds 15, of which (3, 0), (3, 1), (4, 0), (4, 1) join no
    # sequence; the window (130: two blocks back at most) holds 12, of
    # which (3, 1) joins none
    assert counts == {
        "attn_blocks_run_local": 11, "attn_blocks_causal_local": 12,
        "attn_blocks_run_global": 11, "attn_blocks_causal_global": 15,
    }


# ---------------------------------------------------------------------------
# through the actor
# ---------------------------------------------------------------------------


def test_the_actor_trains_it_and_keeps_the_bias_out_of_the_optimizer():
    """`JaxPPOActor` (`compute_logp`, `compute_advantages`, `ppo_update`)
    with no option set for the family: the log-probs are the reference's on
    the actor's own parameters, the step's stats count the held experts'
    rows, every new scope is in the step program in all three passes, and
    the selection bias has no optimizer state, is not decayed and does not
    move while every other leaf does."""
    import re

    from areal_tpu.api.config import (
        MeshConfig, MicroBatchSpec, NormConfig, OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo import JaxPPOActor

    cfg = PPOActorConfig(
        experiment_name="afmoe", trial_name="t", init_from_scratch=True,
        dtype="float32", param_dtype="float32", gradient_checkpointing=True,
        remat_policy="full", scan_unroll=4, mesh=MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                  weight_decay=0.1),
        pack_length_quantum=40, max_pack_length=40, group_size=1,
        ppo_n_minibatches=1, use_decoupled_loss=True,
        adv_norm=NormConfig(mean_level="batch", std_level="batch"),
    )
    actor = JaxPPOActor(cfg, model_config=TransformerConfig.from_hf(HF))
    actor.initialize(ft_spec=FinetuneSpec(1, 16, 4))
    bias0 = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (3, 8))
    actor.params["layers"]["moe"]["moe"]["router_bias"] = jax.device_put(
        bias0, actor.params["layers"]["moe"]["moe"]["router_bias"].sharding)
    before = jax.tree_util.tree_map(np.asarray, actor.params)

    rng = np.random.default_rng(4)
    lens = np.array([22, 14])
    mask = np.arange(22)[None, :] < lens[:, None]
    ids = (rng.integers(0, 128, mask.shape) * mask).astype(np.int32)
    batch = {
        "input_ids": ids, "attention_mask": mask,
        "loss_mask": (mask & (np.arange(22)[None] >= 3)).astype(np.float32),
        "rewards": np.array([1.0, 0.0], np.float32),
        "versions": np.zeros(mask.shape, np.int32),
    }
    batch["prox_logp"] = np.asarray(actor.compute_logp(batch))
    for i, n in enumerate(lens):
        want = ref.next_token_logprobs(before, HF, ids[i: i + 1, :n])[0]
        np.testing.assert_allclose(batch["prox_logp"][i, : n - 1], want, atol=5e-5)
    batch["logprobs"] = (batch["prox_logp"] + rng.normal(
        0, 0.02, mask.shape).astype(np.float32)) * mask
    actor.compute_advantages(batch)
    stats = actor.ppo_update(batch)[-1]
    assert np.isfinite(stats["loss"]) and stats["grad_norm"] > 0
    assert 0 < stats["expert_assignments_held"] < 36 * 2 * 3
    assert 0 < stats["expert_load_max"] <= 36
    # three expert layers, each one block of 128 rows (40 x top-2)
    assert stats["expert_rows_buffered"] == 3 * 128
    assert "moe_aux_loss" not in stats and "attn_blocks_run" not in stats
    # three expert layers S F S: a pattern of one period, one scan step
    assert stats["effective_scan_unroll"] == 1.0
    assert tf._kind_scan_plan(actor.model_config) == [
        ("dense", 0, 1, 1), ("moe", 1, 3, 3)]

    after = jax.tree_util.tree_map(np.asarray, actor.params)
    np.testing.assert_array_equal(
        after["layers"]["moe"]["moe"]["router_bias"], np.asarray(bias0))
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(np.abs(a - b).max() > 0), before, after)
    moved["layers"]["moe"]["moe"].pop("router_bias")
    assert all(jax.tree_util.tree_leaves(moved))
    # no moments for the bias: the optimizer's state holds one leaf fewer
    # a moment than the parameters
    n_params = len(jax.tree_util.tree_leaves(actor.params))
    shapes = [a.shape for a in jax.tree_util.tree_leaves(actor.opt_state)
              if getattr(a, "ndim", 0) > 0]
    assert len(shapes) == 2 * (n_params - 1) and (3, 8) not in shapes

    paths = {m for m in re.findall(r'op_name="([^"]*)"', actor.train_step_hlo())}

    def under(*scopes, word=None, no_word=()):
        rx = re.compile(".*".join(
            rf"(?:^|[/(]){re.escape(s)}(?=[)/]|$)" for s in scopes))
        return any(rx.search(p) and (word is None or word in p)
                   and not any(w in p for w in no_word) for p in paths)

    for inner in (("attn", "attn_local"), ("attn", "attn_global"),
                  ("attn_gate",), ("moe", "moe_router"),
                  ("moe", "moe_experts"), ("moe", "moe_shared"), ("mlp",)):
        assert under("layers", *inner, no_word=("transpose(", "rematted")), inner
        assert under("layers", *inner, word="rematted_computation"), inner
        assert under("layers", *inner, word="transpose(",
                     no_word=("rematted",)), inner
    actor.destroy()
