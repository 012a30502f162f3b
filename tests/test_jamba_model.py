"""A hybrid stack of the `jamba` family (a Mamba-1 or attention mixer and
then a dense gated FFN a layer: two blocks of the heterogeneous stack)
against the plain float32 reference the benchmark carries
(`benchmarks/lib/reference_jamba.py`), at a toy size on the CPU with the
published RATIOS: d_inner = 2 x hidden, 16 state columns, step-size rank
hidden / 16, attention at layer 3 of a period of 6 (published: 7 of 14),
four query heads over ONE kv head, a tied head; 12 layers = 24 blocks,
hidden 64, float32, seeded random weights."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import hf as hf_io
from areal_tpu.models import transformer as tf
from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.ops import mamba1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.lib import reference_jamba as ref  # noqa: E402

HF = {
    "model_type": "jamba", "architectures": ["JambaForCausalLM"],
    "attn_layer_offset": 3, "attn_layer_period": 6,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 4, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 4096, "num_attention_heads": 4,
    "num_experts": 1, "num_experts_per_tok": 1, "num_hidden_layers": 12,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
}
# float32 rounding over 24 blocks and 160 positions reads up to 1e-4
TOL = 3e-4
# a prompt past two chunks of the sequence form
P_LONG = 2 * mamba1.CHUNK + 21


def _cfg(hf=HF):
    return TransformerConfig.from_hf(hf).replace(
        dtype="float32", param_dtype="float32", remat=False, eos_token_id=None)


CFG = _cfg()


def _params(cfg=CFG, seed=0):
    """Drawn, and then every leaf a mechanism rests on moved off the value
    at which leaving the mechanism out would change nothing."""
    p = tf.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 7), 8))
    S = dict(p["layers"]["S"])
    for name in ("dt_norm", "b_norm", "c_norm"):
        S[name] = 1.0 + 0.5 * jax.random.normal(next(keys), S[name].shape)
    S["D"] = 1.0 + 0.5 * jax.random.normal(next(keys), S["D"].shape)
    S["conv_b"] = 0.5 * jax.random.normal(next(keys), S["conv_b"].shape)
    # steps of 0.02 to 0.5: the state carries tens of positions
    S["dt_bias"] = S["dt_bias"] + 3.0
    return {**p, "layers": {**p["layers"], "S": S}}


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(
        0, 128, (3, P_LONG + 12)).astype(np.int32)


@pytest.fixture(scope="module")
def want(params, ids):
    return np.asarray(ref.logits(params, HF, ids))


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------


def test_from_hf_builds_two_blocks_a_layer():
    kinds = CFG.layer_kinds
    assert len(kinds) == CFG.num_layers == 24
    assert kinds[1::2] == ("-",) * 12
    assert "".join(kinds[0::2]) == "SSS*SSSSS*SS"
    assert (CFG.mamba_d_inner, CFG.ssm_state_size, CFG.mamba_dt_rank,
            CFG.conv_kernel, CFG.mamba_conv_dim) == (128, 16, 4, 4, 128)
    assert CFG.pos_emb == "none" and CFG.tie_word_embeddings
    assert CFG.head_dim_ == 16 and CFG.num_kv_heads == 1
    assert tf.is_hybrid(CFG) and tf.slot_kind(CFG) is tf.HYBRID_KIND
    assert CFG.ssm_kind == "S"


def test_to_hf_round_trips():
    cfg = TransformerConfig.from_hf(HF)
    d = cfg.to_hf_dict()
    assert TransformerConfig.from_hf(d) == cfg
    for k in ("attn_layer_period", "attn_layer_offset", "expert_layer_period",
              "expert_layer_offset", "mamba_d_state", "mamba_d_conv",
              "mamba_expand", "mamba_dt_rank", "mamba_conv_bias",
              "mamba_proj_bias", "num_hidden_layers", "num_experts"):
        assert d[k] == HF[k], k


@pytest.mark.parametrize("bad, word", [
    ({"num_experts": 16, "num_experts_per_tok": 2}, "num_experts 16"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mamba_conv_bias": False}, "conv bias"),
    ({"sliding_window": 128}, "sliding_window"),
    ({"attn_layer_period": 64, "attn_layer_offset": 40},
     "both attention and Mamba"),
])
def test_from_hf_refuses_what_it_does_not_build(bad, word):
    with pytest.raises(ValueError, match=word):
        TransformerConfig.from_hf({**HF, **bad})


def test_a_checkpoint_is_refused_by_name(params):
    with pytest.raises(NotImplementedError, match="jamba"):
        list(hf_io.params_to_hf_state(params, CFG))


def test_the_plan_scans_the_runs_and_unrolls_the_rest():
    plan = tf._hybrid_plan(CFG.layer_kinds)
    assert plan == (
        (("S", "-"), 3), (("*",), 1), (("-", "S"), 5), (("-",), 1),
        (("*",), 1), (("-", "S"), 2), (("-",), 1))
    # the published stack: 56 blocks in 7 entries
    big = TransformerConfig.from_hf({
        **HF, "num_hidden_layers": 28, "attn_layer_period": 14,
        "attn_layer_offset": 7}).layer_kinds
    assert tf._hybrid_plan(big) == (
        (("S", "-"), 7), (("*",), 1), (("-", "S"), 13), (("-",), 1),
        (("*",), 1), (("-", "S"), 6), (("-",), 1))
    # nemotron_h's own pattern has no run: unrolled, block for block
    assert tf._hybrid_plan(tuple("MEMEMEM*EME")) == tuple(
        ((k,), 1) for k in "MEMEMEM*EME")


def test_a_stack_of_both_recurrences_is_refused():
    with pytest.raises(ValueError, match="exactly one of"):
        tf.is_hybrid(CFG.replace(layer_kinds=("S", "M", "*"), num_layers=3))


# ---------------------------------------------------------------------------
# the selective scan: chunked against stepped
# ---------------------------------------------------------------------------


def _scan_inputs(B, T, C=24, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, T, C)),
            jax.nn.softplus(jax.random.normal(k[1], (B, T, C)) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (C, N))),
            jax.random.normal(k[3], (B, T, N)),
            jax.random.normal(k[4], (B, T, N)),
            jax.random.normal(k[5], (C,)))


def _stepped(u, dt, A, Bm, Cm, D, seg, S=None):
    B, T, C = u.shape
    S = jnp.zeros((B, A.shape[1], C)) if S is None else S
    ys = []
    for t in range(T):
        if t:
            new = (seg[:, t] != seg[:, t - 1]) & (seg[:, t] >= 0)
            S = jnp.where(new[:, None, None], 0.0, S)
        y, S = mamba1.selective_step(
            u[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, S,
            active=seg[:, t] >= 0)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("T, chunk", [(5, 8), (37, 8), (64, 16), (150, 64)])
def test_the_chunked_scan_equals_the_step_recurrence(T, chunk):
    args = _scan_inputs(2, T)
    seg = jnp.zeros((2, T), jnp.int32)
    y, S = mamba1.selective_scan_chunked(*args, seg, chunk=chunk)
    y2, S2 = _stepped(*args, seg)
    np.testing.assert_allclose(y, y2, atol=2e-5)
    np.testing.assert_allclose(S, S2, atol=2e-5)


def test_the_chunked_scan_continues_from_a_state_and_skips_padding():
    u, dt, A, Bm, Cm, D = _scan_inputs(2, 40)
    seg = jnp.zeros((2, 40), jnp.int32)
    y_all, S_all = mamba1.selective_scan_chunked(u, dt, A, Bm, Cm, D, seg, chunk=8)
    cut = lambda a, lo, hi: a[:, lo:hi]  # noqa: E731
    _, S0 = mamba1.selective_scan_chunked(
        cut(u, 0, 17), cut(dt, 0, 17), A, cut(Bm, 0, 17), cut(Cm, 0, 17), D,
        seg[:, :17], chunk=8)
    # the rest, with five padded positions behind it
    pad = lambda a: jnp.pad(  # noqa: E731
        cut(a, 17, 40), [(0, 0), (0, 5)] + [(0, 0)] * (a.ndim - 2),
        constant_values=7.0)
    seg2 = jnp.concatenate([seg[:, 17:], -jnp.ones((2, 5), jnp.int32)], 1)
    y2, S2 = mamba1.selective_scan_chunked(
        pad(u), pad(dt), A, pad(Bm), pad(Cm), D, seg2, state0=S0, chunk=8)
    np.testing.assert_allclose(y2[:, :23], y_all[:, 17:], atol=2e-5)
    np.testing.assert_allclose(S2, S_all, atol=2e-5)


def test_a_new_segment_starts_from_an_empty_state():
    u, dt, A, Bm, Cm, D = _scan_inputs(1, 30)
    seg = jnp.asarray([[0] * 11 + [1] * 14 + [-1] * 5])
    y, S = mamba1.selective_scan_chunked(u, dt, A, Bm, Cm, D, seg, chunk=8)
    alone, S1 = mamba1.selective_scan_chunked(
        u[:, 11:25], dt[:, 11:25], A, Bm[:, 11:25], Cm[:, 11:25], D,
        jnp.zeros((1, 14), jnp.int32), chunk=8)
    np.testing.assert_allclose(y[:, 11:25], alone, atol=2e-5)
    np.testing.assert_allclose(S, S1, atol=2e-5)


def test_an_idle_slot_keeps_its_state_to_the_bit():
    u, dt, A, Bm, Cm, D = _scan_inputs(3, 1)
    S = jax.random.normal(jax.random.PRNGKey(9), (3, 16, 24))
    _, new = mamba1.selective_step(
        u[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, S,
        active=jnp.asarray([True, False, True]))
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(S[1]))
    assert float(jnp.abs(new[0] - S[0]).max()) > 1e-3


def test_the_admission_bound_follows_the_scan_s_arrays():
    # u, dt, y a token in float32 at 5,120 channels: 4,096 tokens in 256 MiB
    assert mamba1.admit_tokens(5120) == 4096
    assert mamba1.admit_tokens(2 * 5120) == 2048
    big = TransformerConfig.from_hf({**HF, "hidden_size": 2560,
                                     "num_attention_heads": 20})
    assert tf.HYBRID_KIND.admit_tokens(big, 4096) == 4096


# ---------------------------------------------------------------------------
# the forwards against the reference's full forward pass: logits
# ---------------------------------------------------------------------------


def test_the_packed_forward_gives_the_reference_s_logits(params, ids, want):
    pos = np.broadcast_to(np.arange(ids.shape[1], dtype=np.int32), ids.shape)
    got = tf.forward(params, CFG, jnp.asarray(ids), jnp.asarray(pos),
                     jnp.zeros(ids.shape, jnp.int32))
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_packed_row_of_three_segments_equals_the_three_alone(params, ids):
    lens = (70, 9, 33)
    seqs = [ids[i, :n] for i, n in enumerate(lens)]
    packed = {
        "input_ids": jnp.asarray(np.concatenate(seqs + [np.zeros(6)]), jnp.int32),
        "positions": jnp.asarray(np.concatenate(
            [np.arange(n) for n in lens] + [np.zeros(6)]), jnp.int32),
        "segment_ids": jnp.asarray(
            sum(([i] * n for i, n in enumerate(lens)), []) + [-1] * 6, jnp.int32),
    }
    got = np.asarray(tf.forward_packed(params, CFG, packed))
    lo = 0
    for s in seqs:
        alone = np.asarray(ref.logits(params, HF, s[None]))[0]
        np.testing.assert_allclose(got[lo: lo + len(s)], alone, atol=TOL)
        lo += len(s)


@pytest.fixture(scope="module")
def served(params, ids, want):
    """Three prompts (one past two chunks) prefilled into scattered slots of
    a 6-slot pool (one padded row to the scratch row), six decode steps of
    the whole block with the other slots idle."""
    M = 256
    cache = tf.init_kv_cache(CFG, 7, M, "float32")
    plen = np.array([P_LONG, 9, 70], np.int32)
    pids = np.zeros((4, 160), np.int32)
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
        p, CFG, t, ln, c, key_window=M, slot_base=0, active=a))
    lengths, toks = np.zeros(7, np.int32), np.zeros(7, np.int32)
    active = np.zeros(7, bool)
    cur, dec_errs = plen.copy(), []
    for _ in range(6):
        for i in range(3):
            s = slots[i]
            lengths[s], toks[s], active[s] = cur[i], ids[i, cur[i]], True
        logits, cache, _ = step(params, cache, jnp.asarray(toks),
                                jnp.asarray(lengths), jnp.asarray(active))
        dec_errs.append(max(
            float(np.abs(np.asarray(logits[slots[i]]) - want[i, cur[i]]).max())
            for i in range(3)))
        cur += 1
    return cache, cur, errs, dec_errs, idle_before


def test_the_pool_holds_state_window_and_columns(served):
    cache = served[0]
    assert cache["s"].shape == (10, 7, 16, 128)  # channels last
    assert cache["s"].dtype == jnp.float32
    assert cache["c"].shape == (10, 7, 3, 128)
    assert cache["k"].shape == (2, 7, 256, 1, 16)


def test_fresh_prefill_past_two_chunks_gives_the_reference_s_logits(served):
    assert max(served[2]) < TOL


def test_decode_through_the_pool_gives_the_reference_s_logits(served):
    assert max(served[3]) < TOL


def test_the_pooled_state_is_the_reference_s(served, params, ids):
    cache, cur = served[0], served[1]
    states = []
    ref.hidden_states(params, HF, ids[:1, : int(cur[0])], states)
    assert len(states) == 10
    for j, wanted in enumerate(states):
        got = np.asarray(cache["s"][j, 4]).T  # [d_inner, N] as published
        assert ref.state_error(got[None], wanted).max() < 1e-3, j
    assert len(ref.slow_channels(params, 0)) == 32


def test_an_idle_slot_s_rows_are_left_as_they_were(served):
    cache, idle_before = served[0], served[4]
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
        copy_src=jnp.asarray([4, 4], jnp.int32), copy_block=256,
        key_window=256))(params, cache)
    assert float(np.abs(np.asarray(logits[0]) - want[0, start + 4]).max()) < TOL
    assert float(np.abs(np.asarray(logits[1]) - want[0, start + 2]).max()) < TOL
    np.testing.assert_array_equal(cache["k"][:, 0, :start],
                                  cache["k"][:, 4, :start])


def test_a_suffix_on_the_slot_s_own_prefix_gives_the_reference_s_logits(
        served, params, ids, want):
    """Slot 2 continues from its own state and columns (retained reuse)."""
    cache, cur = served[0], served[1]
    start = int(cur[2])
    sids = np.zeros((1, 8), np.int32)
    sids[0, :6] = ids[2, start: start + 6]
    logits, _ = jax.jit(lambda p, c: tf.forward_prefill_cached(
        p, CFG, jnp.asarray(sids), jnp.asarray([start], jnp.int32),
        jnp.asarray([6], jnp.int32), c, jnp.asarray([2], jnp.int32),
        key_window=256))(params, cache)
    assert float(np.abs(np.asarray(logits[0]) - want[2, start + 5]).max()) < TOL


# ---------------------------------------------------------------------------
# one test a mechanism: left out of the program, the logits leave the
# reference (which keeps it)
# ---------------------------------------------------------------------------


def _prefill_and_decode(cfg, p, ids_row, want_row):
    """-> (error of the prompt's last logits, worst error of 6 decode
    steps) for one sequence through a two-slot pool."""
    P = 40
    cache = tf.init_kv_cache(cfg, 2, 64, "float32")
    pids = np.zeros((1, 48), np.int32)
    pids[0, :P] = ids_row[:P]
    # fresh programs every call: a mechanism patched out is traced anew
    logits, cache = jax.jit(lambda p, c: tf.forward_prefill(
        p, cfg, jnp.asarray(pids), jnp.asarray([P]), c, jnp.asarray([0])))(
            p, cache)
    step = jax.jit(lambda p, c, tok, ln: tf.forward_decode_hybrid(
        p, cfg, tok, ln, c, key_window=64, slot_base=0,
        active=jnp.asarray([True, False])))
    d = [float(jnp.abs(logits[0] - want_row[P - 1]).max())]
    for t in range(P, P + 6):
        logits, cache, _ = step(
            p, cache, jnp.asarray([ids_row[t], 0]), jnp.asarray([t, 0]))
        d.append(float(jnp.abs(logits[0] - want_row[t]).max()))
    return d[0], max(d[1:])


def _without(mechanism, params, monkeypatch):
    """The program with one mechanism left out -> its parameters."""
    S = dict(params["layers"]["S"])
    if mechanism == "skip_D":
        S["D"] = jnp.zeros_like(S["D"])
    elif mechanism == "conv_bias":
        S["conv_b"] = jnp.zeros_like(S["conv_b"])
    elif mechanism == "decay_a_column":
        # every column of a channel decays at the channel's mean rate
        A = jnp.exp(S["A_log"]).mean(-1, keepdims=True)
        S["A_log"] = jnp.broadcast_to(jnp.log(A), S["A_log"].shape)
    elif mechanism == "inner_norms":
        # the step-size, B and C projections as Mamba-1 has them: unnormed
        widths = {CFG.mamba_dt_rank, CFG.ssm_state_size}
        real = tf.rms_norm
        monkeypatch.setattr(tf, "rms_norm", lambda x, w, *a, **k: (
            x if x.shape[-1] in widths else real(x, w, *a, **k)))
    else:
        assert mechanism == "no_positions"
        # a rotary embedding on the attention layers' queries and keys
        real = tf._attn_inputs

        def with_rope(cfg, lp, x, cos, sin, dtype):
            q, k, v = real(cfg, lp, x, cos, sin, dtype)
            T = x.shape[1]
            if T == 1:  # a decode step: the position is not handed here
                return q, k, v
            pos = jnp.broadcast_to(jnp.arange(T), x.shape[:2])
            cos, sin = tf.rope_cos_sin(pos, cfg.head_dim_, 10000.0)
            return tf.apply_rope(q, cos, sin), tf.apply_rope(k, cos, sin), v

        monkeypatch.setattr(tf, "_attn_inputs", with_rope)
    return {**params, "layers": {**params["layers"], "S": S}}


@pytest.mark.parametrize("mechanism", [
    "inner_norms", "skip_D", "conv_bias", "decay_a_column", "no_positions"])
def test_a_mechanism_left_out_shows_in_the_logits(
        params, ids, want, mechanism, monkeypatch):
    whole = _prefill_and_decode(CFG, params, ids[1], want[1])
    assert max(whole) < TOL
    without = _prefill_and_decode(
        CFG, _without(mechanism, params, monkeypatch), ids[1], want[1])
    # in the prompt's program and in the decode steps alike
    assert min(without) > 10 * TOL, (mechanism, without)


# ---------------------------------------------------------------------------
# gradients of the packed forward
# ---------------------------------------------------------------------------


def test_gradients_of_the_packed_forward_equal_the_reference_s(params):
    """Two sequences packed into one row with padding behind them: the
    state and the convolution restart at the segment boundary, and every
    parameter's gradient is the reference's over the two sequences."""
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, 128, 70), rng.integers(0, 128, 9)
    packed = {
        "input_ids": jnp.asarray(np.concatenate([a, b, np.zeros(4)]), jnp.int32),
        "positions": jnp.asarray(
            np.concatenate([np.arange(70), np.arange(9), np.zeros(4)]), jnp.int32),
        "segment_ids": jnp.asarray([0] * 70 + [1] * 9 + [-1] * 4, jnp.int32),
    }
    probe = jnp.asarray(rng.normal(size=(79, 128)), jnp.float32)

    def ours(p, cfg=CFG):
        return jnp.sum(tf.forward_packed(p, cfg, packed)[:79] * probe)

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
    again = jax.grad(lambda p: ours(p, CFG.replace(remat=True)))(params)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(again)):
        scale = float(jnp.abs(g).max()) + 1e-6
        assert float(jnp.abs(g - r).max()) < 2e-5 * scale + 1e-6


# ---------------------------------------------------------------------------
# the benchmark's configuration: published keys, bytes as stated
# ---------------------------------------------------------------------------


def test_the_published_configuration_s_bytes_are_the_program_s():
    """`benchmarks/configs/jamba2-3b.json` states its sizes (`bench.bytes`);
    the program's own shapes at the published keys give the same, and so do
    the benchmark's byte functions (`lib/jamba_work.py`)."""
    import json

    from benchmarks.lib import jamba_work as jw

    with open(os.path.join(REPO, "benchmarks/configs/jamba2-3b.json")) as f:
        hf = json.load(f)
    stated = hf["bench"]["bytes"]
    assert hf["bench"]["reduced"] == []
    cfg = TransformerConfig.from_hf(hf).replace(
        dtype="bfloat16", param_dtype="bfloat16")
    assert cfg.num_layers == 56 and cfg.n_kind("S") == 26 and cfg.n_kind("*") == 2
    assert [l for l, k in enumerate(cfg.layer_kinds[0::2]) if k == "*"] == [7, 21]
    shapes = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == stated["parameters"] == jw.parameters(hf) == 3_029_337_472
    per = stated["per_layer_parameters"]
    assert per["mamba_mixer"] == jw.mamba_mixer_params(hf) == 41_241_792
    assert per["attention_mixer"] == jw.attention_mixer_params(hf) == 13_762_560
    assert per["ffn_and_two_norms"] == jw.ffn_and_norms_params(hf) == 62_919_680
    assert stated["weights_bytes_bfloat16"] == 2 * n
    pool = jax.eval_shape(lambda: tf.init_kv_cache(cfg, 385, 4096, "bfloat16"))
    state = sum(int(np.prod(pool[k].shape)) * pool[k].dtype.itemsize
                for k in ("s", "c"))
    kv = sum(int(np.prod(pool[k].shape)) * pool[k].dtype.itemsize
             for k in ("k", "v"))
    assert pool["s"].shape == (26, 385, 16, 5120)
    assert pool["c"].shape == (26, 385, 3, 5120)
    assert state == 385 * stated["state_and_window_bytes_per_slot"]
    assert stated["state_and_window_bytes_per_slot"] == (
        jw.state_bytes_per_slot(hf)) == 9_318_400
    assert kv == 385 * 4096 * stated["kv_bytes_per_token"]
    assert stated["kv_bytes_per_token"] == jw.kv_bytes_per_token(hf) == 1024
    assert state + kv == stated["pool_bytes_385_rows_x_4096"]
    # a pass of 385 rows: what the two roofline metrics divide by
    c = {"decode_passes": 1, "state_rows_stepped": 385}
    assert jw.ssm_bytes(hf, {}, c) == (
        26 * 41_241_792 * 2 + 2 * 385 * 9_318_400)
    assert jw.decode_bytes(hf, {}, c) == 2 * n + 2 * 385 * 9_318_400
