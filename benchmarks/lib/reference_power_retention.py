"""The plain reference of the power-retention decoder (Brumby): Qwen3's
block (pre-norm RMSNorm, grouped queries, per-head q/k RMSNorm before the
rotation, rotary embeddings in the half-rotation convention, SwiGLU MLP,
untied LM head) with softmax attention replaced, in every layer, by power
retention of degree p with one scalar gate per kv head:

    log g_t = logsigmoid(W_g h_t)                            [Hkv]
    a_tj    = exp(sum_{s=j+1..t} log g_s) * ((q_t . k_j) / sqrt(d))^p,  j <= t
    y_t     = sum_j a_tj v_j / (sum_j a_tj + eps)

in `jax.numpy`, float32, matmuls at precision "highest", in the QUADRATIC
form: the whole [T, T] weight matrix with the cumulative gate, an explicit
division, no chunks, no state, no cache, no packing.  Source: Manifest AI,
"Scaling Context Requires Rethinking Attention" (arXiv:2507.04239); what
the published `config.json` does not say is listed in the configuration
file's `bench.assumed`; p and eps are read from there (`retention_degree`,
`retention_eps`; the program's are `TransformerConfig.retention_degree` and
`ops/power_retention.py EPS`).

Fed the cell's own parameters one layer at a time, like `lib/reference.py`,
whose `_rms`, `_rope`, chunked head and comparison it uses; the block and
the retention are its own, and nothing comes from `areal_tpu`.  Reads `layers.attn.{wq,wk,wv,wo,wg,q_norm,k_norm}`,
`layers.{input_norm,post_attn_norm}`, `layers.mlp.{w_gate,w_up,w_down}`,
`embedding`, `final_norm`, `lm_head`; weights are [in, out].
"""

import functools

import jax
import jax.numpy as jnp

# what is not the architecture's is lib/reference.py's: the norm, the
# rotation, the chunked head, the comparison
from benchmarks.lib.reference import (  # noqa: F401
    HEAD_CHUNK,
    _head_chunk,
    _rms,
    _rope,
    compare_logprobs,
    hf_shape,
    layer_params,
)


def retention(q, k, v, log_g, degree, ret_eps):
    """q [B, T, H, d], k/v [B, T, Hkv, d], log_g [B, T, Hkv] -> [B, T, H, d]."""
    B, T, H, d = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    lg = jnp.repeat(log_g, rep, axis=2)  # [B, T, H]
    cum = jnp.moveaxis(jnp.cumsum(lg, axis=1), 1, 2)  # [B, H, T]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None]
    # sum_{s=j+1..t} log g_s = cum_t - cum_j
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    a = decay * s ** degree
    num = jnp.einsum("bhqk,bkhd->bqhd", a, v)
    den = jnp.moveaxis(a.sum(-1), 1, 2)[..., None]  # [B, T, H, 1]
    return num / (den + ret_eps)


@functools.partial(jax.jit, static_argnames=("H", "Hkv", "hd", "eps", "theta",
                                             "degree", "ret_eps"))
def _layer(x, lp, positions, H, Hkv, hd, eps, theta, degree, ret_eps):
    """One decoder block over whole sequences x [B, T, D], causal."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        B, T, _ = x.shape
        a = lp["attn"]
        h = _rms(x, f32(lp["input_norm"]), eps)
        q = (h @ f32(a["wq"])).reshape(B, T, H, hd)
        k = (h @ f32(a["wk"])).reshape(B, T, Hkv, hd)
        v = (h @ f32(a["wv"])).reshape(B, T, Hkv, hd)
        log_g = jax.nn.log_sigmoid(h @ f32(a["wg"]))  # [B, T, Hkv]
        q = _rms(q, f32(a["q_norm"]), eps)
        k = _rms(k, f32(a["k_norm"]), eps)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        o = retention(q, k, v, log_g, degree, ret_eps).reshape(B, T, H * hd)
        x = x + o @ f32(a["wo"])
        h = _rms(x, f32(lp["post_attn_norm"]), eps)
        m = lp["mlp"]
        x = x + (jax.nn.silu(h @ f32(m["w_gate"])) * (h @ f32(m["w_up"]))) @ f32(
            m["w_down"])
        return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _mean_log_gate(x, lp, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, lp["input_norm"].astype(jnp.float32), eps)
        return jax.nn.log_sigmoid(
            h @ lp["attn"]["wg"].astype(jnp.float32)).mean()


def hidden_states(params, hf, ids, gate_log=None):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32.  A list
    given as `gate_log` receives each layer's mean log g over the batch."""
    H, Hkv, hd = hf_shape(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    assumed = hf.get("bench", {}).get("assumed", {})
    degree = int(assumed.get("retention_degree", {}).get("value", 2))
    ret_eps = float(assumed.get("retention_eps", {}).get("value", 1e-6))
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jnp.take(params["embedding"], ids, axis=0).astype(jnp.float32)
    for i in range(int(hf["num_hidden_layers"])):
        if gate_log is not None:
            gate_log.append(float(_mean_log_gate(x, layer_params(params, i), eps)))
        x = _layer(x, layer_params(params, i), positions, H=H, Hkv=Hkv,
                   hd=hd, eps=eps, theta=theta, degree=degree, ret_eps=ret_eps)
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def next_token_logprobs(params, hf, ids, gate_log=None):
    """ids [B, T] int32 -> float32 [B, T-1]: log p(ids[:, t+1] | ids[:, :t+1])
    at temperature 1 over the whole vocabulary.  Sequences padded at the END
    are fine: position t only sees positions <= t."""
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    x = hidden_states(params, hf, ids, gate_log)
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
