"""The stacked projections viewed by head before a layer is taken
(`models/windowed.py by_head`, `models/latent.py by_head`): the product
over a layer of the view, `[heads, d_head, in]`, against the form it
replaced, the flat product `[heads * d_head, in]` followed by the reshape
to heads (written out below as it stood).  The two are one sum in another
order of axes: float32 on the CPU gives the same numbers to a few units in
the last place (`CLOSE`), for both attention kinds of
`tests/test_mimo_v2.py`'s toy configuration (rotary on 8 of a head's 24
dims, a key of 24 beside a value of 16) and for
`tests/test_longcat_model.py`'s, and a toy engine of each kind samples the
same tokens at the same log-probs either way (prefill, the siblings'
suffix, decode).  What the view is FOR is the chip's compiler's business:
`tests/test_tpu_compile.py` holds that no layer of a stack is copied out."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from areal_tpu.models import latent, windowed
from areal_tpu.models.transformer import apply_rope, rope_cos_sin
from tests import test_longcat_model, test_mimo_v2
from tests.engine_attrs import build_engine

MIMO = test_mimo_v2._cfg()
LONGCAT = test_longcat_model.CFG
# one sum in two orders of axes: the CPU's product blocks `[heads * d, in]`
# and `[heads, d, in]` differently, a few units in the last place
CLOSE = {"atol": 1e-6, "rtol": 2e-6}


def _flat_project(cfg, ap, kind, h, cos, sin, pool_dtype):
    """`windowed._project` over the stored leaves, as it stood."""
    dtype = h.dtype
    B, T, _ = h.shape
    H, Hkv = cfg.num_heads, windowed.kv_heads(cfg, kind)
    dq, rot = cfg.head_dim_, cfg.rotary_dim

    def rotate(a):
        return jnp.concatenate(
            [apply_rope(a[..., :rot], cos, sin), a[..., rot:]], axis=-1)

    q = jnp.einsum("btd,hd->bth", h, ap["wq"].astype(dtype))
    k = jnp.einsum("btd,hd->bth", h, ap["wk"].astype(dtype))
    v = jnp.einsum("btd,hd->bth", h, ap["wv"].astype(dtype))
    q = rotate(q.reshape(B, T, H, dq))
    k = rotate(k.reshape(B, T, Hkv, dq))
    v = v.reshape(B, T, Hkv, -1) * jnp.asarray(cfg.attn_value_scale, dtype)
    return q, k.astype(pool_dtype), v.astype(pool_dtype)


def _flat_mla_project(cfg, ap, h, cos, sin):
    """`latent.mla_project` over the stored leaves, as it stood."""
    dtype = h.dtype
    B, T, D = h.shape
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    cq = latent._scaled_norm(
        jnp.einsum("btd,dr->btr", h, ap["wq_a"].astype(dtype)),
        ap["q_norm"], cfg.rms_norm_eps,
        math.sqrt(D / cfg.q_lora_rank) if cfg.mla_scale_q_lora else 1.0)
    q = jnp.einsum("btr,hr->bth", cq, ap["wq_b"].astype(dtype))
    q = q.reshape(B, T, H, cfg.head_dim_)
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    ckr = jnp.einsum("btd,dr->btr", h, ap["wkv_a"].astype(dtype))
    c = latent._scaled_norm(
        ckr[..., :cfg.kv_lora_rank], ap["kv_norm"], cfg.rms_norm_eps,
        math.sqrt(D / cfg.kv_lora_rank) if cfg.mla_scale_kv_lora else 1.0)
    kr = apply_rope(ckr[..., None, cfg.kv_lora_rank:], cos, sin)[:, :, 0]
    return q_nope, q_rope, jnp.concatenate([c, kr], axis=-1)


def _stream(cfg, B=3, T=5):
    h = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.hidden_size))
    pos = 11 + jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    return h, pos


@pytest.mark.parametrize("kind,j", [
    (windowed.FULL, 0), (windowed.FULL, 1), (windowed.SLIDING, 0),
    (windowed.SLIDING, 3)])
def test_a_windowed_layer_s_projections_over_the_view(kind, j):
    cfg = MIMO
    assert 0 < cfg.rotary_dim < cfg.head_dim_ and windowed.value_dim(cfg) == 16
    stacked = test_mimo_v2._params(cfg)["layers"][kind]
    view = windowed.by_head(cfg, kind, stacked)
    n, Hkv = stacked["wq"].shape[0], windowed.kv_heads(cfg, kind)
    assert view["wq"].shape == (n, cfg.num_heads, 24, cfg.hidden_size)
    assert view["wk"].shape == (n, Hkv, 24, cfg.hidden_size)
    assert view["wv"].shape == (n, Hkv, 16, cfg.hidden_size)
    assert all(view[name] is stacked[name] for name in stacked
               if name not in ("wq", "wk", "wv"))
    h, pos = _stream(cfg)
    theta = cfg.swa_rope_theta if kind == windowed.SLIDING else cfg.rope_theta
    cos, sin = rope_cos_sin(pos, cfg.rotary_dim, theta)
    got = windowed._project(
        cfg, windowed._sub(view, j), kind, h, cos, sin, jnp.float32)
    want = _flat_project(
        cfg, windowed._sub(stacked, j), kind, h, cos, sin, jnp.float32)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **CLOSE)


@pytest.mark.parametrize("l,i", [(0, 0), (0, 1), (1, 1)])
def test_a_latent_sublayer_s_projections_over_the_view(l, i):
    cfg = LONGCAT
    stacked = test_longcat_model._params()["layers"]["attn"]
    view = latent.by_head(cfg, stacked)
    assert view["wq_b"].shape == stacked["wq_b"].shape[:2] + (
        cfg.num_heads, cfg.head_dim_, cfg.q_lora_rank)
    assert all(view[name] is stacked[name] for name in stacked
               if name != "wq_b")
    h, pos = _stream(cfg)
    cos, sin = rope_cos_sin(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
    got = latent.mla_project(cfg, latent._sub(view, l, i), h, cos, sin)
    want = _flat_mla_project(cfg, latent._sub(stacked, l, i), h, cos, sin)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **CLOSE)


def _stored(monkeypatch, family):
    """The family's cache forwards as they stood: no view, the flat
    product."""
    if family == "mimo_v2":
        monkeypatch.setattr(windowed, "by_head", lambda cfg, kind, a: a)
        monkeypatch.setattr(windowed, "_project", _flat_project)
    else:
        monkeypatch.setattr(latent, "by_head", lambda cfg, attn: attn)
        monkeypatch.setattr(latent, "mla_project", _flat_mla_project)


@pytest.mark.parametrize("ragged_attn", [None, False], ids=["kernel", "copy"])
@pytest.mark.parametrize("family", ["mimo_v2", "longcat_flash"])
def test_a_toy_engine_tells_the_flat_product_s_story(
        family, ragged_attn, monkeypatch):
    """A group of three on one prompt (one prefill, the siblings' suffix
    over the copied prefix) beside a lone request, decoded past the toy
    window: the same tokens, the same log-probs."""
    cfg, params = (
        (MIMO, test_mimo_v2._params(MIMO)) if family == "mimo_v2"
        else (LONGCAT, test_longcat_model._params()))
    rng = np.random.default_rng(5)
    prompt, lone = (rng.integers(0, 256, n).tolist() for n in (27, 9))

    def story():
        eng = build_engine(
            cfg, params, n_slots=6, max_seq_len=64, prompt_bucket=16, seed=1,
            decode_chunk=4, kv_dtype="float32", ragged_attn=ragged_attn)
        reqs = [GenRequest(rid=f"g{i}", input_ids=list(prompt),
                           max_new_tokens=9 + 2 * i, temperature=1.0,
                           group_id="g", group_n=3) for i in range(3)]
        reqs.append(GenRequest(rid="lone", input_ids=list(lone),
                               max_new_tokens=12, temperature=1.0))
        eng.submit_batch(reqs)
        for _ in range(200):
            if all(r.stop_reason for r in reqs):
                break
            eng.step()
        s = eng.stats
        assert s["prefill_calls"] >= 2 and s["suffix_calls"] >= 1
        assert bool(s["ragged_dispatches"]) == (ragged_attn is None)
        return [(r.output_tokens, r.output_logprobs) for r in reqs]

    got = story()
    _stored(monkeypatch, family)
    want = story()
    for (tokens, logps), (tokens_was, logps_was) in zip(got, want):
        assert len(tokens) >= 9 and list(tokens) == list(tokens_was)
        np.testing.assert_allclose(logps, logps_was, **CLOSE)
