"""The plain reference of the `longcat_flash` decoder (LongCat-Flash, the
language model of LongCat-Flash-Omni; technical report arXiv:2509.01322):
`jax.numpy`, float32, matmuls at precision "highest", whole sequences one
at a time, expanded attention: no cache, no absorbed form, no kernels, no
grouped products, no batching.

`x` is the residual stream, `N_i` RMSNorm (eps `rms_norm_eps`, the weight is
the scale); layer `l` holds attention sublayers `2l` and `2l + 1`:

    a0 = x  + MLA_0(N_1(x))            first latent attention
    h0 = N_2(a0)
    s  = MoE(h0)                       shortcut: routed + identity experts,
                                       NO shared expert
    b0 = a0 + FFN_0(h0)                dense SwiGLU hidden -> ffn_hidden_size
    a1 = b0 + MLA_1(N_3(b0))           second latent attention
    y  = a1 + FFN_1(N_4(a1)) + s       the expert output joins here

then the final norm and the untied head.

`MLA(h)` at position t, H heads, rotary embedding on the `qk_rope_head_dim`
dims only (theta `rope_theta`, half-rotation pairing, no scaling):
`cq = Nq(h Wqa) * sqrt(hidden / q_lora_rank)`, `q = cq Wqb` -> per head
`[q_nope | q_rope]`; `[c | kr] = h Wkva`, `c = Nkv(c) * sqrt(hidden /
kv_lora_rank)`, `kr = rope(kr)`, one for all heads; per head `[k_nope | v] =
c Wkvb`; `score = (q_nope . k_nope + rope(q_rope) . kr) / sqrt(nope + rope)`,
causal softmax, `out = concat_h(sum p v) Wo`.  The two `sqrt` factors are
on where `mla_scale_q_lora` / `mla_scale_kv_lora` say so.

`MoE(h)`: `p = softmax(h Wr)` over `n_routed + zero_expert_num` outputs; the
`moe_topk` largest of `p + b` are chosen (`b` the selection bias); weight
`w_e = routed_scaling_factor * p_e`, not renormalised; `s = sum over chosen
e < n_routed of w_e SwiGLU_e(h) + (sum over chosen e >= n_routed of w_e) h`.
The routed sum runs over the experts HELD (`experts_held`: {"first", "of"},
`n_routed_experts` of them), one at a time for every token with the weight
zero where the token did not choose it: what the other shares of the
deployment would add is left out.  The identity part needs no weights:
every share computes it, and it is counted once when shares are summed.

Departures from the publisher's code (none could be re-read offline; each
is in the configuration file's `bench.assumed` with its origin): SiLU
gates; softmax scoring and the bias's shape and use; no renormalisation;
the two scale factors as written above; the score scale; half-rotation
pairing of the rotary dims (the publisher interleaves: with random weights
a permutation of columns); the untied head.

Fed the cell's own parameters one sub-block at a time (a layer upcast whole
does not fit beside the weights); the norm, the rotation, the chunked head
and the comparison are `lib/reference.py`'s, and nothing comes from
`areal_tpu`.  Reads `layers.attn.{wq_a,q_norm,wq_b,wkv_a,kv_norm,wkv_b,wo}`
[L, 2, ...], `layers.mlp.{w_gate,w_up,w_down}` [L, 2, ...],
`layers.{input_norm,post_attn_norm}` [L, 2, D], `layers.moe.{router,
router_bias}` [L, ...], `layers.moe.{w_gate,w_up,w_down}` [L, held, ...],
`embedding`, `final_norm`, `lm_head`; weights are [in, out], but `wq_b` and
`wkv_b` [out, in].
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import (  # noqa: F401
    HEAD_CHUNK,
    _head_chunk,
    _rms,
    _rope,
    compare_logprobs,
)

# query positions scored at once: [H, QUERY_BLOCK, T] float32 scores
QUERY_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def shapes(hf):
    share = hf.get("experts_held")
    n_held = int(hf["n_routed_experts"])
    return {
        "H": int(hf["num_attention_heads"]),
        "nope": int(hf["qk_nope_head_dim"]),
        "rope": int(hf["qk_rope_head_dim"]),
        "vd": int(hf["v_head_dim"]),
        "rq": int(hf["q_lora_rank"]),
        "rkv": int(hf["kv_lora_rank"]),
        "q_scale": math.sqrt(hf["hidden_size"] / hf["q_lora_rank"])
        if hf.get("mla_scale_q_lora") else 1.0,
        "kv_scale": math.sqrt(hf["hidden_size"] / hf["kv_lora_rank"])
        if hf.get("mla_scale_kv_lora") else 1.0,
        "eps": float(hf["rms_norm_eps"]),
        "theta": float(hf["rope_theta"]),
        "top_k": int(hf["moe_topk"]),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "n_routed": int(share["of"]) if share else n_held,
        "first": int(share["first"]) if share else 0,
        "n_held": n_held,
    }


@functools.partial(jax.jit, static_argnames=(
    "H", "nope", "rope", "vd", "rkv", "q_scale", "kv_scale", "eps", "theta"))
def mla(h, ap, H, nope, rope, vd, rkv, q_scale, kv_scale, eps, theta):
    """One latent attention sublayer over ONE whole sequence: h [T, D], the
    normed stream -> [T, D].  Keys and values are expanded for every
    position; queries are scored a block at a time."""
    with jax.default_matmul_precision("highest"):
        T = h.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        cq = _rms(h @ _f32(ap["wq_a"]), _f32(ap["q_norm"]), eps) * q_scale
        q = (cq @ _f32(ap["wq_b"]).T).reshape(T, H, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[None, ..., nope:], pos, theta)[0]], -1)
        ckr = h @ _f32(ap["wkv_a"])
        c = _rms(ckr[:, :rkv], _f32(ap["kv_norm"]), eps) * kv_scale
        kr = _rope(ckr[None, :, None, rkv:], pos, theta)[0]  # [T, 1, rope]
        kv = (c @ _f32(ap["wkv_b"]).T).reshape(T, H, nope + vd)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(kr, (T, H, rope))], -1)
        v = kv[..., nope:]
        qb = min(QUERY_BLOCK, T)
        pad = -T % qb
        qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, H, nope + rope)

        def block(args):
            i, qi = args
            scores = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(nope + rope)
            q_pos = i * qb + jnp.arange(qb)
            scores = jnp.where(
                pos[0][None, None, :] <= q_pos[None, :, None], scores, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(scores, -1), v)

        o = jax.lax.map(block, (jnp.arange(qs.shape[0]), qs))
        return o.reshape(-1, H * vd)[:T] @ _f32(ap["wo"])


@jax.jit
def dense_ffn(h, mp):
    """SwiGLU, h [T, D] -> [T, D]."""
    with jax.default_matmul_precision("highest"):
        mid = jax.nn.silu(h @ _f32(mp["w_gate"])) * (h @ _f32(mp["w_up"]))
        return mid @ _f32(mp["w_down"])


def route(h, mo, top_k, scale):
    """h [T, D] float32 -> (weights [T, k], chosen router outputs [T, k])."""
    p = jax.nn.softmax(h @ _f32(mo["router"]), axis=-1)
    _, idx = jax.lax.top_k(p + _f32(mo["router_bias"]), top_k)
    return scale * jnp.take_along_axis(p, idx, axis=-1), idx


@functools.partial(jax.jit, static_argnames=(
    "top_k", "scale", "n_routed", "first", "n_held", "with_identity"))
def moe(h, mo, top_k, scale, n_routed, first, n_held, with_identity=True):
    """The expert layer over h [T, D]: the held experts' part (+ the
    identity experts' part) -> ([T, D], the chosen outputs [T, k])."""
    with jax.default_matmul_precision("highest"):
        w, idx = route(h, mo, top_k, scale)

        def one_expert(acc, e):
            wg, wu, wd, eid = e
            # this expert's weight for every token: zero where not chosen
            we = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)
            mid = jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))
            return acc + we[:, None] * (mid @ _f32(wd)), None

        out = jnp.zeros_like(h)
        if n_held:
            out, _ = jax.lax.scan(one_expert, out, (
                mo["w_gate"], mo["w_up"], mo["w_down"],
                first + jnp.arange(n_held)))
        if with_identity:
            w_id = jnp.sum(jnp.where(idx >= n_routed, w, 0.0), axis=-1)
            out = out + w_id[:, None] * h
        return out, idx


def _pick(tree, *index):
    return jax.tree_util.tree_map(lambda a: a[index], tree)


def double_layer(x, layers, l, sh):
    """Layer l over one sequence x [T, D] float32 -> y [T, D]."""
    attn = {k: sh[k] for k in ("H", "nope", "rope", "vd", "rkv", "q_scale",
                               "kv_scale", "eps", "theta")}
    norm = lambda a, name, i: _rms(a, _f32(layers[name][l, i]), sh["eps"])  # noqa: E731
    a0 = x + mla(norm(x, "input_norm", 0), _pick(layers["attn"], l, 0), **attn)
    h0 = norm(a0, "post_attn_norm", 0)
    s, _ = moe(h0, _pick(layers["moe"], l), top_k=sh["top_k"],
               scale=sh["scale"], n_routed=sh["n_routed"], first=sh["first"],
               n_held=sh["n_held"])
    b0 = a0 + dense_ffn(h0, _pick(layers["mlp"], l, 0))
    a1 = b0 + mla(norm(b0, "input_norm", 1), _pick(layers["attn"], l, 1), **attn)
    return a1 + dense_ffn(
        norm(a1, "post_attn_norm", 1), _pick(layers["mlp"], l, 1)) + s


def hidden_states(params, hf, ids):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32, one
    sequence at a time."""
    sh = shapes(hf)
    ids = jnp.asarray(ids, jnp.int32)
    rows = []
    for b in range(ids.shape[0]):
        x = _f32(jnp.take(params["embedding"], ids[b], axis=0))
        for l in range(int(hf["num_layers"])):
            x = double_layer(x, params["layers"], l, sh)
        rows.append(_rms(x, _f32(params["final_norm"]), sh["eps"]))
    return jnp.stack(rows)


def logits(params, hf, ids):
    """ids [B, T] -> float32 logits [B, T, V] (small sizes: the tests)."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, hf, ids) @ _f32(params["lm_head"])


def next_token_logprobs(params, hf, ids):
    """ids [B, T] int32 -> float32 [B, T-1]: log p(ids[:, t+1] | ids[:, :t+1])
    at temperature 1 over the vocabulary held.  Sequences padded at the END
    are fine: position t only sees positions <= t."""
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    x = hidden_states(params, hf, ids)
    head = params["lm_head"].T  # [V, D]
    xs = x[:, :-1].reshape(B * (T - 1), -1)
    labels = ids[:, 1:].reshape(-1)
    lses, picked = [], jnp.zeros(xs.shape[0], jnp.float32)
    for lo in range(0, head.shape[0], HEAD_CHUNK):
        lse, pk = _head_chunk(xs, head[lo: lo + HEAD_CHUNK], labels, lo)
        lses.append(lse)
        picked = picked + pk
    lse = jax.nn.logsumexp(jnp.stack(lses, 0), axis=0)
    return (picked - lse).reshape(B, T - 1)
