"""The plain reference of the `nemotron_h` decoder (NVIDIA Nemotron-H /
Nemotron 3): a stack of blocks of three kinds by `hybrid_override_pattern`,
each ONE mixer under one pre-norm and one residual,

    x <- x + mixer_kind(RMSNorm(x; w_i, eps)),   then norm_f and the untied head

in `jax.numpy`, float32, matmuls at precision "highest", whole sequences: no
cache, no chunks, no state pool, no grouped products, no packing.  Besides
the log-probs it gives each Mamba block's state after a row's first `lens`
tokens (`hidden_states(..., states, lens)`), for the comparison with what
the program left in its pool (`state_error`, `slow_heads`).

- `M`, Mamba-2.  [z | xBC | dt] = W_in h (widths d_inner | d_inner + 2 G N |
  heads); xBC <- silu(causal depthwise conv1d(xBC, kernel K, bias)); split x
  [H, P], B [G, N], C [G, N]; dt <- softplus(dt + dt_bias); A = -exp(A_log),
  one scalar a head; head h reads group h // (H / G).  The recurrence itself,
  a `lax.scan` over time:  S_h <- exp(dt_h A_h) S_h + dt_h x_h B_g^T,
  y_h = S_h C_g + D_h x_h.  y <- RMSNorm over groups of d_inner / G channels
  of y * silu(z); out W_out y.
- `*`, attention.  Grouped queries, no bias, causal softmax at scale
  1 / sqrt(head_dim), NO rotary embedding.
- `E`, latent mixture of experts.  s = sigmoid(W_r h) over all routed
  experts; the top k of s + e_score_correction_bias are chosen; weights s of
  the chosen, over their sum if `norm_topk_prob`, times
  `routed_scaling_factor`.  u = W_l1 h; expert e: W2_e relu(W1_e u)^2;
  routed = W_l2 (sum_e w_e f_e(u)); shared expert W2_s relu(W1_s h)^2 at the
  model's width; output routed + shared.  The sum runs over the experts
  HELD (`experts_held`: {"first", "of"}, `n_routed_experts` of them), one at
  a time for every token with the weight zero where the token did not
  choose it: what the other shares of the deployment would add is left out.

Source: the published `NemotronH` modeling code and Mamba-2 (arXiv:2405.
21060); what the published `config.json` does not say is listed in the
configuration file's `bench.assumed`.  Fed the cell's own parameters one
block at a time; the norm, the chunked head and the comparison are
`lib/reference.py`'s, and nothing comes from `areal_tpu`.  Reads
`layers.M.{input_norm,w_in,conv_w,conv_b,dt_bias,A_log,D,gate_norm,w_out}`,
`layers.*.{input_norm,attn.{wq,wk,wv,wo}}`, `layers.E.{input_norm,router,
router_bias,w_l1,w_l2,w1,w2,ws1,ws2}`, `embedding`, `final_norm`,
`lm_head`; weights are [in, out], conv taps [K, channels] with the last tap
on the current column.
"""

import functools

import jax
import jax.numpy as jnp

from benchmarks.lib.reference import (  # noqa: F401
    HEAD_CHUNK,
    _head_chunk,
    _rms,
    compare_logprobs,
)


def _f32(a):
    return a.astype(jnp.float32)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def shapes(hf):
    """The sizes this file reads from the configuration's keys."""
    H, P = int(hf["mamba_num_heads"]), int(hf["mamba_head_dim"])
    G, N = int(hf["n_groups"]), int(hf["ssm_state_size"])
    n_held = int(hf["n_routed_experts"])
    share = hf.get("experts_held") or {"first": 0, "of": n_held}
    return {
        "H": H, "P": P, "G": G, "N": N, "K": int(hf["conv_kernel"]),
        "eps": float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5))),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf["head_dim"]),
        "top_k": int(hf["num_experts_per_tok"]),
        "scale": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "first": int(share["first"]), "n_held": n_held,
    }


@functools.partial(jax.jit, static_argnames=("H", "P", "G", "N", "K", "eps"))
def mamba_block(x, lp, lens, H, P, G, N, K, eps):
    """x [B, T, D], lens int [B] -> (x + Mamba-2 mixer of the normed x, the
    state [B, H, P, N] with each row's first `lens` tokens in it)."""
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        d_in = H * P
        h = _rms(x, _f32(lp["input_norm"]), eps)
        zxd = h @ _f32(lp["w_in"])
        z = zxd[..., :d_in]
        xbc = zxd[..., d_in: 2 * d_in + 2 * G * N]
        dt = zxd[..., 2 * d_in + 2 * G * N:]
        # causal depthwise convolution: tap k reaches back K - 1 - k columns
        w, b = _f32(lp["conv_w"]), _f32(lp["conv_b"])
        run = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = b + sum(run[:, k: k + T] * w[k] for k in range(K))
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :d_in].reshape(B, T, H, P)
        bm = xbc[..., d_in: d_in + G * N].reshape(B, T, G, N)
        cm = xbc[..., d_in + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))  # [B, T, H]
        A = -jnp.exp(_f32(lp["A_log"]))  # [H]
        rep = H // G
        bh = jnp.repeat(bm, rep, axis=2)  # [B, T, H, N]
        ch = jnp.repeat(cm, rep, axis=2)

        def step(carry, t):
            S, kept = carry
            x_t, dt_t, b_t, c_t, i = t  # [B, H, P], [B, H], [B, H, N] x 2
            S = (jnp.exp(dt_t * A)[..., None, None] * S
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            kept = jnp.where((i + 1 == lens)[:, None, None, None], S, kept)
            return (S, kept), jnp.einsum("bhpn,bhn->bhp", S, c_t)

        tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        zero = jnp.zeros((B, H, P, N), jnp.float32)
        (_, kept), ys = jax.lax.scan(
            step, (zero, zero),
            (tm(xs), tm(dt), tm(bh), tm(ch), jnp.arange(T)),
        )
        y = jnp.moveaxis(ys, 0, 1) + _f32(lp["D"])[None, None, :, None] * xs
        g = (y.reshape(B, T, d_in) * jax.nn.silu(z)).reshape(B, T, G, d_in // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
        y = g.reshape(B, T, d_in) * _f32(lp["gate_norm"])
        return x + y @ _f32(lp["w_out"]), kept


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "eps"))
def attention_block(x, lp, heads, kv_heads, head_dim, eps):
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        a = lp["attn"]
        h = _rms(x, _f32(lp["input_norm"]), eps)
        q = (h @ _f32(a["wq"])).reshape(B, T, heads, head_dim)
        k = (h @ _f32(a["wk"])).reshape(B, T, kv_heads, head_dim)
        v = (h @ _f32(a["wv"])).reshape(B, T, kv_heads, head_dim)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(head_dim))
        causal = jnp.tril(jnp.ones((T, T), bool))
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, heads * head_dim)
        return x + o @ _f32(a["wo"])


def route(h, lp, top_k, scale, norm_topk):
    """h [N, D] float32 -> (weights [N, k], chosen expert ids [N, k])."""
    s = jax.nn.sigmoid(h @ _f32(lp["router"]))
    _, idx = jax.lax.top_k(s + _f32(lp["router_bias"]), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / w.sum(-1, keepdims=True)
    return w * scale, idx


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "norm_topk",
                                             "first", "n_held", "eps",
                                             "with_shared"))
def moe_block(x, lp, top_k, scale, norm_topk, first, n_held, eps,
              with_shared=True):
    """-> (x + routed part of the held experts (+ the shared expert), the
    chosen expert ids [B, T, k])."""
    with jax.default_matmul_precision("highest"):
        B, T, D = x.shape
        h = _rms(x, _f32(lp["input_norm"]), eps).reshape(B * T, D)
        w, idx = route(h, lp, top_k, scale, norm_topk)
        u = h @ _f32(lp["w_l1"])

        def one_expert(acc, e):
            w1, w2, eid = e
            # this expert's weight for every token: zero where not chosen
            we = jnp.sum(jnp.where(idx == eid, w, 0.0), axis=-1)  # [N]
            return acc + we[:, None] * (_relu2(u @ _f32(w1)) @ _f32(w2)), None

        lat, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(u),
            (lp["w1"], lp["w2"], first + jnp.arange(n_held)),
        )
        out = lat @ _f32(lp["w_l2"])
        if with_shared:
            out = out + _relu2(h @ _f32(lp["ws1"])) @ _f32(lp["ws2"])
        return x + out.reshape(B, T, D), idx.reshape(B, T, top_k)


def block_params(params, kind, j):
    """Block j of its kind, out of the program's per-kind stacked leaves."""
    return jax.tree_util.tree_map(lambda a: a[j], params["layers"][kind])


def hidden_states(params, hf, ids, states=None, lens=None):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32.  A list
    given as `states` receives each Mamba block's state [B, H, P, N] after
    each row's first `lens` tokens (default: all T)."""
    sh = shapes(hf)
    ids = jnp.asarray(ids, jnp.int32)
    lens = jnp.full(ids.shape[:1], ids.shape[1], jnp.int32) if lens is None \
        else jnp.asarray(lens, jnp.int32)
    x = _f32(jnp.take(params["embedding"], ids, axis=0))
    nth = {}
    for kind in hf["hybrid_override_pattern"]:
        j = nth.get(kind, 0)
        nth[kind] = j + 1
        lp = block_params(params, kind, j)
        if kind == "M":
            x, S = mamba_block(x, lp, lens, H=sh["H"], P=sh["P"], G=sh["G"],
                               N=sh["N"], K=sh["K"], eps=sh["eps"])
            if states is not None:
                states.append(S)
        elif kind == "*":
            x = attention_block(x, lp, heads=sh["heads"],
                                kv_heads=sh["kv_heads"],
                                head_dim=sh["head_dim"], eps=sh["eps"])
        elif kind == "E":
            x, _ = moe_block(x, lp, top_k=sh["top_k"], scale=sh["scale"],
                             norm_topk=sh["norm_topk"], first=sh["first"],
                             n_held=sh["n_held"], eps=sh["eps"])
        else:
            raise ValueError(f"block kind {kind!r} in the pattern")
    return _rms(x, _f32(params["final_norm"]), sh["eps"])


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


def slow_heads(params, j, share=0.25):
    """The `share` of Mamba block j's heads that remember longest (smallest
    nominal decay rate softplus(dt_bias) * exp(A_log) a token): where a
    state's precision shows, since what is rounded away at every step adds
    up over a head's memory."""
    import numpy as np

    m = params["layers"]["M"]
    rate = np.asarray(jax.nn.softplus(_f32(m["dt_bias"][j]))
                      * jnp.exp(_f32(m["A_log"][j])))
    return np.argsort(rate)[: max(1, int(len(rate) * share))]


def state_error(got, want):
    """got, want [B, H, P, N] -> [B, H]: each head's |got - want| over
    |want| (Frobenius)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.square(got - want).sum((-2, -1))
                   / np.maximum(np.square(want).sum((-2, -1)), 1e-300))
