"""The plain reference: a dense Qwen2 / Qwen3 decoder forward in
`jax.numpy`, float32, matmuls at precision "highest", no kernels, no cache,
no packing.  It follows the published architecture (pre-norm RMSNorm
blocks, grouped-query attention with rotary embeddings in the half-rotation
convention, optional q/k/v bias, optional per-head q/k RMSNorm before the
rotation (Qwen3), SwiGLU MLP, optionally tied LM head) and reads only the
keys of the published `config.json`.

It is fed the cell's own parameters one layer at a time, so no second full
copy of the model lives on the chip: `layer_params(params, i)` slices layer
i out of the program's stacked leaves and this file upcasts that slice.

The parameter tree it reads (`embedding`, `layers.attn.{wq,wk,wv,wo,bq,bk,
bv,q_norm,k_norm}`, `layers.{input_norm,post_attn_norm}`, `layers.mlp.
{w_gate,w_up,w_down}`, `final_norm`, `lm_head`) is the program's storage
layout, nothing more: weights are [in, out].
"""

import functools

import jax
import jax.numpy as jnp

HEAD_CHUNK = 16384


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, positions, theta):
    """x [B, T, H, hd]; rotate_half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [B, T, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hf_shape(hf):
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // H
    return H, Hkv, hd


@functools.partial(jax.jit, static_argnames=("H", "Hkv", "hd", "eps", "theta",
                                             "qk_norm"))
def _layer(x, lp, positions, H, Hkv, hd, eps, theta, qk_norm):
    """One decoder block over whole sequences x [B, T, D], causal."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        B, T, _ = x.shape
        a = lp["attn"]
        h = _rms(x, f32(lp["input_norm"]), eps)
        q, k, v = h @ f32(a["wq"]), h @ f32(a["wk"]), h @ f32(a["wv"])
        if "bq" in a:
            q, k, v = q + f32(a["bq"]), k + f32(a["bk"]), v + f32(a["bv"])
        q = q.reshape(B, T, H, hd)
        k = k.reshape(B, T, Hkv, hd)
        v = v.reshape(B, T, Hkv, hd)
        if qk_norm:
            q = _rms(q, f32(a["q_norm"]), eps)
            k = _rms(k, f32(a["k_norm"]), eps)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, H * hd)
        x = x + o @ f32(a["wo"])
        h = _rms(x, f32(lp["post_attn_norm"]), eps)
        m = lp["mlp"]
        x = x + (jax.nn.silu(h @ f32(m["w_gate"])) * (h @ f32(m["w_up"]))) @ f32(
            m["w_down"])
        return x


@jax.jit
def _head_chunk(x, w_chunk, labels, lo):
    """x [N, D] f32, w_chunk [C, D] (rows of the [V, D] head) ->
    (logsumexp over the chunk [N], the label's logit where it falls in the
    chunk else 0 [N])."""
    with jax.default_matmul_precision("highest"):
        logits = x @ w_chunk.astype(jnp.float32).T  # [N, C]
        lse = jax.nn.logsumexp(logits, axis=-1)
        idx = labels - lo
        inside = (idx >= 0) & (idx < w_chunk.shape[0])
        picked = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, w_chunk.shape[0] - 1)[:, None], axis=-1
        )[:, 0]
        return lse, jnp.where(inside, picked, 0.0)


def layer_params(params, i):
    """Layer i of the program's stacked [L, ...] leaves."""
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


def next_token_logprobs(params, hf, ids):
    """ids [B, T] int32 -> float32 [B, T-1]: log p(ids[:, t+1] | ids[:, :t+1])
    at temperature 1 over the whole vocabulary.  Sequences padded at the END
    are fine: position t only sees positions <= t."""
    H, Hkv, hd = hf_shape(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    qk_norm = hf.get("model_type") in ("qwen3", "qwen3_moe")
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jnp.take(params["embedding"], ids, axis=0).astype(jnp.float32)
    for i in range(int(hf["num_hidden_layers"])):
        x = _layer(x, layer_params(params, i), positions, H=H, Hkv=Hkv,
                   hd=hd, eps=eps, theta=theta, qk_norm=qk_norm)
    x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
    head = params.get("lm_head")
    head = params["embedding"] if head is None else head.T  # [V, D]
    xs = x[:, :-1].reshape(B * (T - 1), -1)
    labels = ids[:, 1:].reshape(-1)
    V = head.shape[0]
    lses, picked = [], jnp.zeros(xs.shape[0], jnp.float32)
    for lo in range(0, V, HEAD_CHUNK):
        lse, pk = _head_chunk(xs, head[lo: lo + HEAD_CHUNK], labels, lo)
        lses.append(lse)
        picked = picked + pk
    lse = jax.nn.logsumexp(jnp.stack(lses, 0), axis=0)
    return (picked - lse).reshape(B, T - 1)


def compare_logprobs(got, want, mask, tol_mean, tol_max):
    """-> (ok, {"n", "mean_abs", "max_abs"}) over positions where mask."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    mask = np.asarray(mask, bool)
    d = np.abs(got - want)[mask]
    rep = {"n": int(d.size), "mean_abs": float(d.mean()) if d.size else None,
           "max_abs": float(d.max()) if d.size else None,
           "tol_mean": tol_mean, "tol_max": tol_max}
    ok = bool(d.size and np.isfinite(d).all()
              and rep["mean_abs"] <= tol_mean and rep["max_abs"] <= tol_max)
    return ok, rep


def compared(rep):
    """{name: {"value", "limit"}} of a `compare_logprobs` report: what a
    kind hands the harness to print beside `correct`."""
    return {name: {"value": rep.get(value), "limit": rep[limit]}
            for name, value, limit in (
                ("logprob_mean_abs", "mean_abs", "tol_mean"),
                ("logprob_max_abs", "max_abs", "tol_max"))
            if limit in rep}
