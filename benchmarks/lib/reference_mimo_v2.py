"""The plain reference of the `mimo_v2` decoder (MiMo-V2-Flash, the language
model of MiMo-V2.5): `jax.numpy`, float32, matmuls at precision "highest",
whole sequences one at a time: no cache, no ring, no kernel, no sort, no
grouped product, no batching.

`x` is the residual stream, `N` RMSNorm (eps `layernorm_epsilon`, the
weight is the scale), two a block:

    h = x + Attn_l(N(x))
    y = h + FFN_l(N(h))

then the final norm and the untied head.

`Attn_l` is FULL where `hybrid_layer_pattern[l] == 0`, else SLIDING.  Both
have `num_attention_heads` query heads, a query and key `head_dim` wide and
a value `v_head_dim` wide times `attention_value_scale`, no bias, no q/k
norm.  By kind: `num_key_value_heads` kv heads and rotary base `rope_theta`
(full), `swa_num_key_value_heads` and `swa_rope_theta` (sliding).  The
rotary embedding (half-rotation pairing) turns the leading
int(`partial_rotary_factor` x head_dim) dims of q and k, the others pass.
Scores `s_ij = q_i . k_j / sqrt(head_dim)` for j <= i and, in a sliding
layer, only j > i - `sliding_window` (`sees`: the one place the rule is
written here; the window counts the query's own position).  A full layer:
plain softmax.  A sliding layer with `add_swa_attention_sink_bias`:
`p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij))`, `b_h` one learned scalar
a query head, which takes mass and adds no value (`add_full_attention_
sink_bias` gives the full layers one the same way).  Queries are scored a
block at a time so that 15k positions fit.

`FFN_l`: SwiGLU at `intermediate_size` where `moe_layer_freq[l] == 0`; else
`s = sigmoid(h W_r)` over ALL routed experts, the `num_experts_per_tok`
largest of `s + bias` chosen (`noaux_tc`, one group), weights
`s_e / sum_chosen s` (`norm_topk_prob`) times `routed_scaling_factor` (1
where null), `sum_e w_e SwiGLU_e(h)` at `moe_intermediate_size`, no shared
expert.  The sum runs over the experts HELD (`experts_held`: {"first",
"of"}, `n_routed_experts` of them), one at a time for every token with the
weight zero where the token did not choose it: what the other shares of the
deployment would add is left out.

Departures from the publisher's code (it could not be re-read offline; each
is in the configuration file's `bench.assumed`): the value scale on v; the
window's count; which dims rotate and their pairing; no q/k norm;
`attention_chunk_size` and `attention_projection_layout` change no
equation and are not read.

Fed the cell's own parameters a layer at a time; the norm, the rotation,
the chunked head and the comparison are `lib/reference.py`'s, and nothing
comes from `areal_tpu`.  Reads `layers.{full,sliding}.{wq,wk,wv,wo,sink}`
[n_kind, ...], `layers.mlp.{w_gate,w_up,w_down}` [n_dense, ...],
`layers.moe.{router,router_bias}` [n_moe, ...], `layers.moe.{w_gate,w_up,
w_down}` [n_moe, held, ...], `layers.{input_norm,post_attn_norm}` [L, D],
`embedding`, `final_norm`, `lm_head`; weights are [in, out], but `wq`,
`wk` and `wv` [out, in].
"""

import functools

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
    scale = hf.get("routed_scaling_factor")
    return {
        "H": int(hf["num_attention_heads"]),
        "dq": int(hf["head_dim"]),
        "dv": int(hf["v_head_dim"]),
        "rot": int(hf.get("partial_rotary_factor", 1.0) * hf["head_dim"]),
        "v_scale": float(hf.get("attention_value_scale") or 1.0),
        "window": int(hf["sliding_window"]),
        "eps": float(hf["layernorm_epsilon"]),
        "Hkv": {False: int(hf["num_key_value_heads"]),
                True: int(hf["swa_num_key_value_heads"])},
        "theta": {False: float(hf["rope_theta"]),
                  True: float(hf["swa_rope_theta"])},
        "top_k": int(hf["num_experts_per_tok"]),
        "renorm": bool(hf.get("norm_topk_prob", True)),
        "scale": 1.0 if scale is None else float(scale),
        "first": int(share["first"]) if share else 0,
        "n_held": n_held,
    }


def sees(q_pos, k_pos, window):
    """bool [Q, K]: query i sees key j <= i and, under a window, only
    j > i - window."""
    keep = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        keep = keep & (k_pos[None, :] > q_pos[:, None] - window)
    return keep


@functools.partial(jax.jit, static_argnames=(
    "H", "Hkv", "dq", "dv", "rot", "v_scale", "theta", "window"))
def attention(h, ap, H, Hkv, dq, dv, rot, v_scale, theta, window):
    """One attention layer over ONE whole sequence: h [T, D], the normed
    stream -> [T, D].  `window` None = a full layer; `ap["sink"]` [H] where
    the layer's softmax has one."""
    with jax.default_matmul_precision("highest"):
        T = h.shape[0]
        pos = jnp.arange(T, dtype=jnp.int32)

        def rotate(a):  # [T, heads, dq]
            if not rot:
                return a
            if rot == dq:
                return _rope(a[None], pos[None], theta)[0]
            return jnp.concatenate(
                [_rope(a[None, ..., :rot], pos[None], theta)[0], a[..., rot:]],
                axis=-1)

        q = rotate((h @ _f32(ap["wq"]).T).reshape(T, H, dq))
        k = rotate((h @ _f32(ap["wk"]).T).reshape(T, Hkv, dq))
        v = (h @ _f32(ap["wv"]).T).reshape(T, Hkv, dv) * v_scale
        # query head i reads kv head i // (H / Hkv)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        sink = _f32(ap["sink"]) if "sink" in ap else None
        qb = min(QUERY_BLOCK, T)
        pad = -T % qb
        qs = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, qb, H, dq)

        def block(args):
            i, qi = args
            s = jnp.einsum("qhd,khd->hqk", qi, k) / jnp.sqrt(jnp.float32(dq))
            keep = sees(i * qb + jnp.arange(qb), pos, window)
            s = jnp.where(keep[None], s, -jnp.inf)
            if sink is None:
                p = jax.nn.softmax(s, axis=-1)
            else:
                # the sink as a key of its own that carries no value
                col = jnp.broadcast_to(sink[:, None, None], (H, qb, 1))
                p = jax.nn.softmax(
                    jnp.concatenate([s, col], axis=-1), axis=-1)[..., :T]
            return jnp.einsum("hqk,khv->qhv", p, v)

        o = jax.lax.map(block, (jnp.arange(qs.shape[0]), qs))
        return o.reshape(-1, H * dv)[:T] @ _f32(ap["wo"])


@jax.jit
def dense_ffn(h, mp):
    """SwiGLU, h [T, D] -> [T, D]."""
    with jax.default_matmul_precision("highest"):
        mid = jax.nn.silu(h @ _f32(mp["w_gate"])) * (h @ _f32(mp["w_up"]))
        return mid @ _f32(mp["w_down"])


def route(h, mo, top_k, renorm, scale):
    """h [T, D] float32 -> (weights [T, k], chosen experts [T, k])."""
    s = jax.nn.sigmoid(h @ _f32(mo["router"]))
    _, idx = jax.lax.top_k(s + _f32(mo["router_bias"]), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return scale * w, idx


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renorm", "scale", "first", "n_held"))
def moe(h, mo, top_k, renorm, scale, first, n_held):
    """The expert layer over h [T, D]: the held experts' part -> [T, D]."""
    with jax.default_matmul_precision("highest"):
        w, idx = route(h, mo, top_k, renorm, scale)

        def one_expert(acc, e):
            wg, wu, wd, eid = e
            # this expert's weight for every token: zero where not chosen
            we = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)
            mid = jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))
            return acc + we[:, None] * (mid @ _f32(wd)), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
            mo["w_gate"], mo["w_up"], mo["w_down"],
            first + jnp.arange(n_held)))
        return out


def _pick(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def hidden_states(params, hf, ids):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32, one
    sequence at a time."""
    sh = shapes(hf)
    layers = params["layers"]
    ids = jnp.asarray(ids, jnp.int32)
    rows = []
    for b in range(ids.shape[0]):
        x = _f32(jnp.take(params["embedding"], ids[b], axis=0))
        n = {"full": 0, "sliding": 0, "mlp": 0, "moe": 0}
        for l in range(int(hf["num_hidden_layers"])):
            sliding = bool(hf["hybrid_layer_pattern"][l])
            kind = "sliding" if sliding else "full"
            h = _rms(x, _f32(layers["input_norm"][l]), sh["eps"])
            x = x + attention(
                h, _pick(layers[kind], n[kind]), H=sh["H"],
                Hkv=sh["Hkv"][sliding], dq=sh["dq"], dv=sh["dv"],
                rot=sh["rot"], v_scale=sh["v_scale"],
                theta=sh["theta"][sliding],
                window=sh["window"] if sliding else None)
            n[kind] += 1
            h = _rms(x, _f32(layers["post_attn_norm"][l]), sh["eps"])
            if hf["moe_layer_freq"][l]:
                x = x + moe(
                    h, _pick(layers["moe"], n["moe"]), top_k=sh["top_k"],
                    renorm=sh["renorm"], scale=sh["scale"],
                    first=sh["first"], n_held=sh["n_held"])
                n["moe"] += 1
            else:
                x = x + dense_ffn(h, _pick(layers["mlp"], n["mlp"]))
                n["mlp"] += 1
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
