"""The plain reference of the `afmoe` decoder (Arcee Trinity): every block
attention + FFN under four RMS norms,

    a  = input_layernorm(h)
    h <- h + post_attention_layernorm( W_o ( attn(q, k, v) * sigmoid(W_g a) ) )
    m  = pre_mlp_layernorm(h)
    h <- h + post_mlp_layernorm( FFN(m) )

in `jax.numpy`, float32, matmuls at precision "highest", whole sequences: no
kernel, no sort, no grouped product, no packing, no remat.

- attention: q, k, v = W_q a, W_k a, W_v a (no bias), q and k RMS-normed per
  head, rotary embedding (half rotation, `rope_theta`) on SLIDING layers
  only, grouped queries, causal softmax at 1 / sqrt(head_dim) under a
  [T, T] mask per layer kind: a full layer sees keys j <= i, a sliding one
  i - sliding_window < j <= i (`sees`, the one place the rule is written
  here).  Computed in blocks of `Q_BLOCK` queries so that a 16k sequence
  fits; the output times sigmoid(W_g a) elementwise, then W_o.
- layers [0, num_dense_layers): FFN = SwiGLU at `intermediate_size`.
- the others: s = sigmoid(W_r m) over all routed experts; the top k of
  s + expert_bias chosen; w = s[chosen] / (sum + 1e-20) if `route_norm`,
  times `route_scale`; FFN(m) = shared(m) + sum_e w_e expert_e(m), every
  expert SwiGLU at `moe_intermediate_size`.  The sum is a loop over the
  experts HELD (`experts_held`: {"first", "of"}; `num_experts` of them) with
  the weight zero where a token did not choose the expert: what the other
  shares of the deployment would add is left out.
- the embedding times sqrt(hidden_size) if `mup_enabled`; final RMS norm,
  untied head.

Departures from the published modeling code (transformers'
`modeling_afmoe.py`, which could not be re-read offline: `bench.assumed` of
the configuration file lists what the config has no key for), each marked
DEPARTURE below: the held share; float32 throughout; a forced routing
choice for the flip count.

For the tests it gives the GRPO loss the actor uses (`grpo_loss`: the
decoupled PPO objective over next-token log-probs) and, through `jax.grad`,
its gradients.  Fed the program's own parameters one block at a time;
nothing comes from `areal_tpu`.  Reads `layers.{dense,moe}.{attn.{wq,wk,wv,
wo,wg,q_norm,k_norm}, input_norm, sandwich_attn_norm, post_attn_norm,
sandwich_ffn_norm}`, `layers.dense.mlp.{w_gate,w_up,w_down}`, `layers.moe.
moe.{router,router_bias,w_gate,w_up,w_down,ws_gate,ws_up,ws_down}`,
`embedding`, `final_norm`, `lm_head`; weights are [in, out].
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
    compared,
)

Q_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def shapes(hf):
    """The sizes this file reads from the configuration's keys."""
    n_held = int(hf["num_experts"])
    share = hf.get("experts_held") or {"first": 0, "of": n_held}
    return {
        "eps": float(hf["rms_norm_eps"]), "theta": float(hf["rope_theta"]),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf["head_dim"]),
        "window": int(hf["sliding_window"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "scale": float(hf.get("route_scale", 1.0)),
        "norm_topk": bool(hf.get("route_norm", True)),
        "first": int(share["first"]), "n_held": n_held,
    }


def layer_plan(hf):
    """[(FFN kind "dense" | "moe", index among its kind, sliding)] of every
    layer, from `num_dense_layers` and `layer_types`."""
    n_dense, seen, plan = int(hf.get("num_dense_layers", 0)), {}, []
    for i, t in enumerate(hf["layer_types"][: int(hf["num_hidden_layers"])]):
        kind = "dense" if i < n_dense else "moe"
        plan.append((kind, seen.get(kind, 0), t == "sliding_attention"))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def sees(i, j, window=None):
    """Whether query position i attends key position j: causal, and in a
    sliding layer no further back than `window` positions, the query's own
    among them: i - window < j <= i."""
    ok = j <= i
    return ok if window is None else ok & (j > i - window)


def attention(x, lp, positions, heads, kv_heads, head_dim, eps, theta, window):
    """The attention half of a block, before W_o's residual add: x [B, T, D]
    -> W_o (attn * sigmoid(W_g a)) [B, T, D].  `window` None = a full layer
    (and no rotary embedding)."""
    B, T, _ = x.shape
    a = lp["attn"]
    h = _rms(x, _f32(lp["input_norm"]), eps)
    q = (h @ _f32(a["wq"])).reshape(B, T, heads, head_dim)
    k = (h @ _f32(a["wk"])).reshape(B, T, kv_heads, head_dim)
    v = (h @ _f32(a["wv"])).reshape(B, T, kv_heads, head_dim)
    q, k = _rms(q, _f32(a["q_norm"]), eps), _rms(k, _f32(a["k_norm"]), eps)
    if window is not None:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        hi = min(T, lo + Q_BLOCK)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi]) / jnp.sqrt(
            jnp.float32(head_dim))
        mask = sees(jnp.arange(lo, hi)[:, None], jnp.arange(hi)[None, :], window)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi]))
    o = jnp.concatenate(outs, axis=1).reshape(B, T, heads * head_dim)
    return (o * jax.nn.sigmoid(h @ _f32(a["wg"]))) @ _f32(a["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


def route(m, mp, top_k, scale, norm_topk, forced=None):
    """m [N, D] float32 -> (weights [N, k], chosen expert ids [N, k], the
    ids this router would choose itself).  DEPARTURE: `forced` [N, k], if
    given, replaces the choice (not the scores), to price the choices that
    flip on the program's rounding."""
    s = jax.nn.sigmoid(m @ _f32(mp["router"]))
    _, own = jax.lax.top_k(s + _f32(mp["router_bias"]), top_k)
    idx = own if forced is None else forced
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scale, idx, own


def experts(m, mp, w, idx, first, n_held, with_shared=True):
    """m [N, D] -> the held experts' part of sum_e w_e expert_e(m) (+ the
    shared expert).  DEPARTURE: the published layer holds every expert; here
    the loop runs over ids [first, first + n_held) only."""

    def one_expert(acc, e):
        w_gate, w_up, w_down, eid = e
        # this expert's weight for every token: zero where not chosen
        we = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)  # [N]
        return acc + we[:, None] * _swiglu(m, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (mp["w_gate"], mp["w_up"], mp["w_down"], first + jnp.arange(n_held)),
    )
    if with_shared:
        out = out + _swiglu(m, mp["ws_gate"], mp["ws_up"], mp["ws_down"])
    return out


@functools.partial(jax.jit, static_argnames=(
    "sliding", "heads", "kv_heads", "head_dim", "eps", "theta", "window",
    "top_k", "scale", "norm_topk", "first", "n_held"))
def block(x, lp, positions, forced=None, *, sliding, heads, kv_heads,
          head_dim, eps, theta, window, top_k, scale, norm_topk, first,
          n_held):
    """One block over whole sequences x [B, T, D] -> (x, the ids its router
    would choose [B, T, k]; None from a dense block)."""
    with jax.default_matmul_precision("highest"):
        B, T, D = x.shape
        att = attention(x, lp, positions, heads, kv_heads, head_dim, eps,
                        theta, window if sliding else None)
        x = x + _rms(att, _f32(lp["sandwich_attn_norm"]), eps)
        m = _rms(x, _f32(lp["post_attn_norm"]), eps).reshape(B * T, D)
        own = None
        if "mlp" in lp:
            out = _swiglu(m, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                          lp["mlp"]["w_down"])
        else:
            w, idx, own = route(
                m, lp["moe"], top_k, scale, norm_topk,
                None if forced is None else forced.reshape(B * T, top_k))
            out = experts(m, lp["moe"], w, idx, first, n_held)
            own = own.reshape(B, T, top_k)
        out = _rms(out.reshape(B, T, D), _f32(lp["sandwich_ffn_norm"]), eps)
        return x + out, own


def block_params(params, kind, j):
    """Block j of its kind, out of the program's per-kind stacked leaves."""
    return jax.tree_util.tree_map(lambda a: a[j], params["layers"][kind])


def hidden_states(params, hf, ids, chosen=None, forced=None):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32.  A list
    given as `chosen` receives each expert block's own choice [B, T, k];
    `forced` (one [B, T, k] an expert block) replaces the choices."""
    sh = shapes(hf)
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = _f32(jnp.take(params["embedding"], ids, axis=0))
    if hf.get("mup_enabled", False):
        x = x * jnp.sqrt(jnp.float32(hf["hidden_size"]))
    for kind, j, sliding in layer_plan(hf):
        f = None if forced is None or kind != "moe" else forced[j]
        x, own = block(x, block_params(params, kind, j), positions, f,
                       sliding=sliding, **sh)
        if chosen is not None and own is not None:
            chosen.append(own)
    return _rms(x, _f32(params["final_norm"]), sh["eps"])


def logits(params, hf, ids):
    """ids [B, T] -> float32 logits [B, T, V] (small sizes: the tests)."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, hf, ids) @ _f32(params["lm_head"])


def next_token_logprobs(params, hf, ids, chosen=None, forced=None):
    """ids [B, T] int32 -> float32 [B, T-1]: log p(ids[:, t+1] | ids[:, :t+1])
    at temperature 1 over the vocabulary held.  Sequences padded at the END
    are fine: position t only sees positions <= t."""
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    x = hidden_states(params, hf, ids, chosen, forced)
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


def grpo_loss(params, hf, ids, loss_mask, old_logp, advantages, prox_logp,
              eps_clip, total_weight):
    """The actor's loss over whole sequences ids [B, T] (the other arrays
    predictor-aligned [B, T - 1]: entry t is about token t + 1): the
    decoupled PPO objective, ratio exp(logp - prox) clipped to 1 +- eps,
    max of the two surrogates, times the behaviour weight exp(prox - old),
    summed over `loss_mask` and divided by `total_weight`."""
    logp = jax.nn.log_softmax(logits(params, hf, ids)[:, :-1], axis=-1)
    logp = jnp.take_along_axis(
        logp, jnp.asarray(ids, jnp.int32)[:, 1:, None], axis=-1)[..., 0]
    ratio = jnp.exp(logp - prox_logp)
    clipped = jnp.clip(ratio, 1.0 - eps_clip, 1.0 + eps_clip)
    pg = jnp.maximum(-advantages * ratio, -advantages * clipped)
    behav = jnp.where(loss_mask > 0, jnp.exp(prox_logp - old_logp), 0.0)
    return jnp.sum(pg * behav * loss_mask) / total_weight
