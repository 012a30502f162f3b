"""Power retention (`ops/power_retention.py`) and the model kind built on it
(`attn_kind="power_retention"`, brumby) against the plain float32 reference
the benchmark carries (`benchmarks/lib/reference_power_retention.py`: the
quadratic form, no chunks, no state), at a tiny size on the CPU: head_dim
16, 4 q / 2 kv heads, 2 layers, seeded random weights, float32."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tf
from areal_tpu.models.model_config import TransformerConfig, tiny_config
from areal_tpu.ops import power_retention as pr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.lib import reference_power_retention as ref  # noqa: E402

# the published keys of the catalog's row, shrunk; what the reference reads
HF = {
    "model_type": "brumby", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu",
}
B, T, H, HKV, D = 2, 37, 4, 2, 16


def retention_quadratic(q, k, v, log_g, segment_ids, degree=2):
    """The definition, with the whole [T, T] weight matrix and segment
    resets: what the two forms of the op are tested against."""
    B, T, H, d = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    hp = jax.lax.Precision.HIGHEST
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    lg = jnp.repeat(log_g, G, axis=2)  # [B, T, H]
    valid = segment_ids >= 0
    lg = jnp.where(valid[..., None], lg, 0.0)
    cum = jnp.cumsum(lg, axis=1)
    sc = jnp.einsum("bthd,bjhd->bhtj", q, k, precision=hp) * d ** -0.5
    pair = (
        jnp.tril(jnp.ones((T, T), bool))[None]
        & valid[:, :, None] & valid[:, None, :]
        & (segment_ids[:, :, None] == segment_ids[:, None, :])
    )
    cum_h = jnp.moveaxis(cum, 2, 1)  # [B, H, T]
    decay = jnp.exp(
        jnp.where(pair[:, None], cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf)
    )
    a = decay * sc ** degree
    y = jnp.einsum("bhtj,bjhd->bthd", a, v, precision=hp)
    return y / (jnp.moveaxis(a.sum(-1), 1, 2)[..., None] + pr.EPS)


@pytest.fixture(scope="module")
def qkvg():
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    log_g = jax.nn.log_sigmoid(2 * f(B, T, HKV) + 1)
    return f(B, T, H, D), f(B, T, HKV, D), f(B, T, HKV, D), log_g


def _segments(boundaries, pad_from=None):
    seg = np.zeros((B, T), np.int32)
    for i, b in enumerate(boundaries):
        seg[0, b:] = i + 1
    if pad_from is not None:
        seg[:, pad_from:] = -1
    return jnp.asarray(seg)


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig.from_hf(HF).replace(
        dtype="float32", param_dtype="float32", remat=False,
        retention_chunk=8)
    assert cfg.attn_kind == "power_retention" and cfg.qk_norm
    params = tf.init_params(cfg, jax.random.PRNGKey(3))
    # norms other than one, so that a dropped norm weight would show
    key = jax.random.PRNGKey(4)
    for name in ("q_norm", "k_norm"):
        key, k = jax.random.split(key)
        a = params["layers"]["attn"][name]
        params["layers"]["attn"][name] = a + 0.1 * jax.random.normal(k, a.shape)
    return cfg, params


def test_phi_factors_the_squared_score(qkvg):
    q, k, _, _ = qkvg
    want = jnp.einsum("bthd,bthd->bth", q[:, :, :HKV], k) ** 2
    got = jnp.einsum("bthf,bthf->bth", pr.phi(q[:, :, :HKV]), pr.phi(k))
    assert pr.phi(k).shape[-1] == pr.feature_dim(D) == D * (D + 1) // 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_only_degree_two_has_a_feature_map():
    with pytest.raises(ValueError, match="degree 3"):
        pr.feature_dim(16, 3)


@pytest.mark.parametrize("chunk", [8, 16, 37, 64, 5, 12])
@pytest.mark.parametrize("boundaries,pad_from", [
    ((), None),  # one sequence a row
    ((10, 25), 35),  # boundaries inside chunks, padded tail
    ((8, 16), None),  # boundaries on the edges of chunks of 8
])
def test_chunked_form_equals_the_quadratic_form(qkvg, chunk, boundaries,
                                                pad_from):
    q, k, v, lg = qkvg
    seg = _segments(boundaries, pad_from)
    want = retention_quadratic(q, k, v, lg, seg)
    got, _ = pr.retention_chunked(q, k, v, lg, seg, chunk=chunk)
    m = np.asarray(seg >= 0)[..., None, None]
    np.testing.assert_allclose(got * m, want * m, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("row,length", [(0, 37), (1, 30)])
def test_a_scan_of_steps_equals_the_quadratic_form(qkvg, row, length):
    q, k, v, lg = (x[row:row + 1] for x in qkvg)
    seg = jnp.zeros((1, T), jnp.int32)
    want = retention_quadratic(q, k, v, lg, seg)

    def step(state, x):
        y, state = pr.retention_step(*x, state)
        return state, y

    xs = tuple(jnp.moveaxis(a[:, :length], 1, 0) for a in (q, k, v, lg))
    state, ys = jax.lax.scan(step, pr.init_state(1, HKV, D), xs)
    np.testing.assert_allclose(jnp.moveaxis(ys, 0, 1), want[:, :length],
                               rtol=1e-4, atol=2e-5)
    # and the chunked form ends in the same state
    _, end = pr.retention_chunked(
        q, k, v, lg, jnp.where(jnp.arange(T)[None] < length, 0, -1), chunk=8)
    np.testing.assert_allclose(end.s, state.s, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(end.z, state.z, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("cut", [5, 8, 13, 29])
def test_continuation_from_a_state_equals_the_whole(qkvg, cut):
    q, k, v, lg = (x[1:2] for x in qkvg)
    seg = jnp.zeros((1, T), jnp.int32)
    want, end = pr.retention_chunked(q, k, v, lg, seg, chunk=8)
    _, mid = pr.retention_chunked(
        q[:, :cut], k[:, :cut], v[:, :cut], lg[:, :cut], seg[:, :cut], chunk=8)
    got, end2 = pr.retention_chunked(
        q[:, cut:], k[:, cut:], v[:, cut:], lg[:, cut:], seg[:, cut:],
        state0=mid, chunk=8)
    np.testing.assert_allclose(got, want[:, cut:], rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(end2.s, end.s, rtol=1e-4, atol=2e-5)


def test_an_idle_slot_keeps_its_state_to_the_bit(qkvg):
    q, k, v, lg = qkvg
    state = pr.RetentionState(
        jnp.ones((B, HKV, pr.feature_dim(D), D)) * 0.3,
        jnp.ones((B, HKV, pr.feature_dim(D))) * 0.7)
    _, new = pr.retention_step(q[:, 0], k[:, 0], v[:, 0], lg[:, 0], state,
                               active=jnp.asarray([True, False]))
    assert np.array_equal(new.s[1], state.s[1])
    assert np.array_equal(new.z[1], state.z[1])
    assert not np.array_equal(new.s[0], state.s[0])


def test_gradients_of_the_chunked_form(qkvg):
    q, k, v, lg = qkvg
    seg = _segments((10, 25), 35)
    m = (seg >= 0)[..., None, None]
    w = jnp.asarray(np.random.default_rng(1).normal(size=q.shape), jnp.float32)

    def loss(fn, *a):
        return jnp.sum(fn(*a) * m * w)

    got = jax.grad(
        lambda *a: loss(lambda *b: pr.retention_chunked(*b, seg, chunk=8)[0], *a),
        argnums=(0, 1, 2, 3))(q, k, v, lg)
    want = jax.grad(
        lambda *a: loss(lambda *b: retention_quadratic(*b, seg), *a),
        argnums=(0, 1, 2, 3))(q, k, v, lg)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------------------
# the model kind against the benchmark's reference
# ---------------------------------------------------------------------------


def _ref_logits(params, ids):
    x = ref.hidden_states(params, HF, ids)
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(jnp.float32)


def _packed(lens, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, HF["vocab_size"], sum(lens)).astype(np.int32)
    pos = np.concatenate([np.arange(n) for n in lens]).astype(np.int32)
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)]).astype(np.int32)
    return ids, pos, seg


@pytest.mark.parametrize("T_", [5, 8, 29])
def test_forward_equals_the_reference(model, T_):
    cfg, params = model
    ids = np.random.default_rng(T_).integers(0, 512, (2, T_)).astype(np.int32)
    pos = np.broadcast_to(np.arange(T_, dtype=np.int32), (2, T_))
    got = tf.forward(params, cfg, ids, pos, np.zeros((2, T_), np.int32))
    np.testing.assert_allclose(got, _ref_logits(params, ids), rtol=2e-4,
                               atol=2e-4)


def test_packed_forward_resets_the_state_at_every_segment(model):
    cfg, params = model
    lens = (11, 7, 13)  # boundaries inside chunks of 8
    ids, pos, seg = _packed(lens)
    pad = 3
    packed = {
        "input_ids": jnp.asarray(np.pad(ids, (0, pad))),
        "positions": jnp.asarray(np.pad(pos, (0, pad))),
        "segment_ids": jnp.asarray(np.pad(seg, (0, pad), constant_values=-1)),
    }
    got = tf.forward_packed(params, cfg, packed)
    lo = 0
    for n in lens:
        want = _ref_logits(params, ids[None, lo:lo + n])[0]
        np.testing.assert_allclose(got[lo:lo + n], want, rtol=2e-4, atol=2e-4)
        lo += n


def test_gradients_of_the_grpo_loss_equal_the_reference_s(model):
    from areal_tpu.ops.functional import grpo_loss_fn

    cfg, params = model
    lens = (11, 7, 13)
    ids, pos, seg = _packed(lens, seed=5)
    n = sum(lens)
    rng = np.random.default_rng(6)
    ends = np.cumsum(lens) - 1
    loss_mask = np.ones(n, np.float32)
    loss_mask[ends] = 0.0  # the label there is the next sequence's token
    loss_mask[:3] = 0.0
    batch = {
        "input_ids": jnp.asarray(ids),
        "loss_mask": jnp.asarray(loss_mask),
        "logprobs": jnp.asarray(rng.normal(-6.0, 0.3, n), jnp.float32),
        "prox_logp": jnp.asarray(rng.normal(-6.0, 0.3, n), jnp.float32),
        "advantages": jnp.asarray(rng.normal(0, 1, n), jnp.float32),
    }

    def system(p):
        logits = tf.forward(p, cfg, ids[None], pos[None], seg[None])[0]
        return grpo_loss_fn(logits, batch, eps_clip=0.2)[0]

    def reference(p):
        lo, rows = 0, []
        for m in lens:
            x = ref.hidden_states(p, HF, ids[None, lo:lo + m])[0]
            rows.append(x @ p["lm_head"].astype(jnp.float32))
            lo += m
        return grpo_loss_fn(jnp.concatenate(rows), batch, eps_clip=0.2)[0]

    (l1, g1), (l2, g2) = (jax.value_and_grad(f)(params)
                          for f in (system, reference))
    np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-5)
    flat1 = jax.tree_util.tree_leaves_with_path(g1)
    flat2 = dict(jax.tree_util.tree_leaves_with_path(g2))
    assert float(jnp.abs(g1["layers"]["attn"]["wg"]).max()) > 0
    for path, a in flat1:
        b = flat2[path]
        scale = float(jnp.abs(b).max()) + 1e-12
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-3,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("prompt_len", [5, 8, 21])  # under, on, over a chunk
def test_prefill_then_decode_through_the_state(model, prompt_len):
    cfg, params = model
    total = prompt_len + 12
    ids = np.random.default_rng(prompt_len).integers(0, 512, (1, total)).astype(np.int32)
    want = _ref_logits(params, ids)[0]
    cache = tf.init_kv_cache(cfg, 4, 64)
    assert set(cache) == {"s", "z"} and cache["s"].dtype == jnp.float32
    assert cache["s"].shape == (2, 4, HKV, pr.feature_dim(D), D)
    pad = np.zeros((2, 32), np.int32)
    pad[0, :prompt_len] = ids[0, :prompt_len]
    pad[1, :3] = ids[0, :3]
    logits, cache = tf.forward_prefill(
        params, cfg, pad, jnp.asarray([prompt_len, 3]), cache,
        jnp.asarray([2, 0]))
    np.testing.assert_allclose(logits[0], want[prompt_len - 1], rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(logits[1], want[2], rtol=2e-4, atol=2e-4)
    lens = jnp.asarray([3, 0, prompt_len])
    idle = np.asarray(cache["s"][:, 1])
    for t in range(prompt_len, total):
        # slot 1 idles, slot 0 follows three tokens behind
        toks = jnp.asarray([ids[0, 3 + t - prompt_len], 0, ids[0, t]])
        logits, cache = tf.forward_decode(
            params, cfg, toks, lens, cache, slot_base=0,
            active=jnp.asarray([True, False, True]))
        np.testing.assert_allclose(logits[2], want[t], rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(logits[0], want[3 + t - prompt_len],
                                   rtol=3e-4, atol=3e-4)
        lens = lens + jnp.asarray([1, 0, 1])
    assert np.array_equal(np.asarray(cache["s"][:, 1]), idle)


def test_fan_out_gives_siblings_the_logits_of_eight_separate_prefills(model):
    """The shared span is prefilled once into the representative's slot and
    every member continues from a copy of that state: the same first-token
    logits as a prefill of its own for each."""
    cfg, params = model
    P = 21
    prompt = np.random.default_rng(9).integers(0, 512, P).astype(np.int32)
    cache = tf.init_kv_cache(cfg, 9, 64)
    pad = np.zeros((1, 32), np.int32)
    pad[0, :P - 1] = prompt[:P - 1]
    _, cache = tf.forward_prefill(params, cfg, pad, jnp.asarray([P - 1]),
                                  cache, jnp.asarray([3]))
    slots = jnp.arange(8, dtype=jnp.int32)
    last = np.zeros((8, 16), np.int32)
    last[:, 0] = prompt[P - 1]
    got, cache = tf.forward_prefill_cached(
        params, cfg, last, jnp.full(8, P - 1), jnp.ones(8, jnp.int32), cache,
        slots, copy_src=jnp.full(8, 3))
    own = np.zeros((8, 32), np.int32)
    own[:, :P] = prompt
    want, cache2 = tf.forward_prefill(
        params, cfg, own, jnp.full(8, P), tf.init_kv_cache(cfg, 9, 64), slots)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(cache["s"][:, :8], cache2["s"][:, :8],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(got[0], _ref_logits(params, prompt[None])[0, -1],
                               rtol=2e-4, atol=2e-4)


def test_verify_refuses_the_kind(model):
    cfg, params = model
    with pytest.raises(ValueError, match="spec_decode"):
        tf.forward_verify(params, cfg, jnp.zeros((2, 3), jnp.int32),
                          jnp.zeros(2, jnp.int32), tf.init_kv_cache(cfg, 3, 64))


# ---------------------------------------------------------------------------
# configuration and checkpoint names
# ---------------------------------------------------------------------------


def _catalog_config():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r["config"] for r in rows if r["name"] == "Brumby-14B-Base")


def test_from_hf_builds_the_retention_kind_from_the_catalog_s_config():
    cfg = TransformerConfig.from_hf(_catalog_config())
    assert cfg.attn_kind == "power_retention" and cfg.qk_norm
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (
        40, 40, 8, 128)
    assert not cfg.qkv_bias and not cfg.tie_word_embeddings
    assert cfg.retention_degree == 2 and cfg.sliding_window is None


def test_the_benchmark_s_file_holds_the_catalog_s_numbers():
    want = _catalog_config()
    with open(os.path.join(REPO, "benchmarks/configs/brumby-14b.json")) as f:
        have = json.load(f)
    differs = {k for k, v in want.items() if have.get(k, "absent") != v}
    assert differs == set(have["bench"]["reduced"]) == {"num_hidden_layers"}


def test_brumby_round_trips_through_to_hf_dict():
    cfg = TransformerConfig.from_hf(HF)
    d = cfg.to_hf_dict()
    assert d["model_type"] == "brumby" and d["retention_degree"] == 2
    again = TransformerConfig.from_hf(d)
    assert again.replace(hf_architecture=cfg.hf_architecture) == cfg


@pytest.mark.parametrize("model_type", ["mamba2", "brumbie", "deepseek_v3"])
def test_an_unknown_model_type_is_refused_not_read_as_llama(model_type):
    with pytest.raises(ValueError, match=model_type):
        TransformerConfig.from_hf({**HF, "model_type": model_type})


def test_the_gate_round_trips_through_a_checkpoint(model, tmp_path):
    from areal_tpu.models.hf import (
        load_hf_params,
        params_to_hf_state,
        save_hf_checkpoint,
        state_to_params,
    )

    cfg, params = model
    names = dict(params_to_hf_state(params, cfg))
    g = names["model.layers.1.self_attn.g_proj.weight"]
    assert g.shape == (HKV, HF["hidden_size"])  # HF linears are [out, in]
    save_hf_checkpoint(params, cfg, str(tmp_path), save_dtype="float32")
    loaded, cfg2 = load_hf_params(str(tmp_path), dtype="float32")
    assert cfg2.attn_kind == "power_retention"
    np.testing.assert_array_equal(loaded["layers"]["attn"]["wg"],
                                  params["layers"]["attn"]["wg"])
    no_gate = [(k, v) for k, v in names.items() if "g_proj" not in k]
    with pytest.raises(ValueError, match="g_proj"):
        state_to_params(iter(no_gate), cfg)


def test_the_gate_is_sharded_with_the_kv_heads(model):
    cfg, params = model
    specs = tf.param_partition_specs(cfg)
    assert specs["layers"]["attn"]["wg"] == specs["layers"]["attn"]["wk"]
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)
    ) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, specs,
                               is_leaf=lambda x: isinstance(x, tf.P)))
    assert tf.kv_cache_partition_specs(cfg)["s"] == tf.P(
        None, None, "tp", None, None)
