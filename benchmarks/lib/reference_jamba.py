"""The plain reference of the `jamba` decoder (AI21 Jamba, dense variant:
`num_experts` 1): every layer a mixer and then a dense gated FFN, each under
its own pre-norm and residual,

    h = x + Mixer_l(RMSNorm(x)),   y = h + FFN(RMSNorm(h)),

then the final RMSNorm and the head (the embedding itself where
`tie_word_embeddings`), in `jax.numpy`, float32, matmuls at precision
"highest", whole sequences: no cache, no chunks, no state pool, no packing,
the recurrence a plain loop over positions, attention in blocks of queries.
Besides the log-probs it gives each Mamba layer's state after a row's first
`lens` tokens (`hidden_states(..., states, lens)`), for the comparison with
what the program left in its pool (`state_error`, `slow_channels`).

- Mixer_l is attention where `l % attn_layer_period == attn_layer_offset`,
  else Mamba-1.
- Mamba-1, d_inner = `mamba_expand` x hidden, N = `mamba_d_state`, R =
  `mamba_dt_rank`, K = `mamba_d_conv`:  [u | z] = W_in h (no bias);  u <-
  silu(causal depthwise conv1d(u, kernel K) + b_conv);  [r | B | C] = W_x u
  (widths R | N | N);  r, B, C <- RMSNorm(r), RMSNorm(B), RMSNorm(C), each
  with a weight of its own (the family's addition to Mamba-1);  dt =
  softplus(W_dt r + b_dt), one a channel;  A = -exp(A_log) [d_inner, N];
  for every channel c and state column n

      S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
      y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]

  out = W_out (y * silu(z)) (no bias).
- Attention: grouped queries (20 heads over 1 kv head at the published
  size), no bias, no q/k norm, NO positional encoding, causal softmax at
  1 / sqrt(head size), head size hidden / heads.
- FFN: W_down (silu(W_gate h) * W_up h).

Source: Mamba (arXiv:2312.00752), Jamba (arXiv:2403.19887) and the
published `jamba` configuration's keys; what the published `config.json`
does not say is listed in the configuration file's `bench.assumed`.  Fed
the cell's own parameters one block at a time; the norm, the chunked head
and the comparison are `lib/reference.py`'s, and nothing comes from
`areal_tpu`.  Reads `layers.S.{input_norm,w_in,conv_w,conv_b,w_x,dt_norm,
b_norm,c_norm,w_dt,dt_bias,A_log,D,w_out}`, `layers.*.{input_norm,attn.{wq,
wk,wv,wo}}`, `layers.-.{input_norm,mlp.{w_gate,w_up,w_down}}`, `embedding`,
`final_norm` (`lm_head` where the head is untied); weights are [in, out],
conv taps [K, channels] with the last tap on the current column.
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

# queries a block of the attention: [B, heads, Q_BLOCK, T] float32 scores
Q_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def shapes(hf):
    """The sizes this file reads from the configuration's keys."""
    D, heads = int(hf["hidden_size"]), int(hf["num_attention_heads"])
    return {
        "d_in": int(hf["mamba_expand"]) * D, "N": int(hf["mamba_d_state"]),
        "R": int(hf["mamba_dt_rank"]), "K": int(hf["mamba_d_conv"]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "heads": heads, "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": D // heads,
    }


def mixer_kinds(hf):
    """"*" (attention) or "S" (Mamba-1) for every published layer."""
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    return ["*" if l % period == offset else "S"
            for l in range(int(hf["num_hidden_layers"]))]


@functools.partial(jax.jit, static_argnames=("d_in", "N", "R", "K", "eps"))
def mamba_block(x, lp, lens, d_in, N, R, K, eps):
    """x [B, T, D], lens int [B] -> (x + Mamba-1 mixer of the normed x, the
    state [B, d_inner, N] with each row's first `lens` tokens in it)."""
    with jax.default_matmul_precision("highest"):
        B, T, _ = x.shape
        h = _rms(x, _f32(lp["input_norm"]), eps)
        uz = h @ _f32(lp["w_in"])
        u, z = uz[..., :d_in], uz[..., d_in:]
        # causal depthwise convolution: tap k reaches back K - 1 - k columns
        w, b = _f32(lp["conv_w"]), _f32(lp["conv_b"])
        run = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        u = jax.nn.silu(b + sum(run[:, k: k + T] * w[k] for k in range(K)))
        rbc = u @ _f32(lp["w_x"])
        r = _rms(rbc[..., :R], _f32(lp["dt_norm"]), eps)
        bm = _rms(rbc[..., R: R + N], _f32(lp["b_norm"]), eps)
        cm = _rms(rbc[..., R + N:], _f32(lp["c_norm"]), eps)
        dt = jax.nn.softplus(r @ _f32(lp["w_dt"]) + _f32(lp["dt_bias"]))
        A = -jnp.exp(_f32(lp["A_log"]))  # [d_in, N]

        def step(carry, t):
            S, kept = carry
            u_t, dt_t, b_t, c_t, i = t  # [B, d_in] x 2, [B, N] x 2
            S = (jnp.exp(dt_t[..., None] * A) * S
                 + (dt_t * u_t)[..., None] * b_t[:, None, :])
            kept = jnp.where((i + 1 == lens)[:, None, None], S, kept)
            return (S, kept), jnp.sum(S * c_t[:, None, :], axis=-1)

        tm = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
        zero = jnp.zeros((B, d_in, N), jnp.float32)
        (_, kept), ys = jax.lax.scan(
            step, (zero, zero), (tm(u), tm(dt), tm(bm), tm(cm), jnp.arange(T)),
        )
        y = jnp.moveaxis(ys, 0, 1) + _f32(lp["D"]) * u
        return x + (y * jax.nn.silu(z)) @ _f32(lp["w_out"]), kept


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
        key_pos = jnp.arange(T)
        out = []
        for lo in range(0, T, Q_BLOCK):
            qb = q[:, lo: lo + Q_BLOCK]
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(
                jnp.float32(head_dim))
            sees = key_pos[None, :] <= (lo + jnp.arange(qb.shape[1]))[:, None]
            p = jax.nn.softmax(jnp.where(sees[None, None], s, -jnp.inf), -1)
            out.append(jnp.einsum("bhqk,bkhd->bqhd", p, v))
        o = jnp.concatenate(out, axis=1).reshape(B, T, heads * head_dim)
        return x + o @ _f32(a["wo"])


@functools.partial(jax.jit, static_argnames=("eps",))
def ffn_block(x, lp, eps):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, _f32(lp["input_norm"]), eps)
        m = lp["mlp"]
        g = jax.nn.silu(h @ _f32(m["w_gate"])) * (h @ _f32(m["w_up"]))
        return x + g @ _f32(m["w_down"])


def block_params(params, kind, j):
    """Block j of its kind, out of the program's per-kind stacked leaves."""
    return jax.tree_util.tree_map(lambda a: a[j], params["layers"][kind])


def hidden_states(params, hf, ids, states=None, lens=None):
    """ids [B, T] -> final-norm hidden states [B, T, D] float32.  A list
    given as `states` receives each Mamba layer's state [B, d_inner, N]
    after each row's first `lens` tokens (default: all T)."""
    sh = shapes(hf)
    ids = jnp.asarray(ids, jnp.int32)
    lens = jnp.full(ids.shape[:1], ids.shape[1], jnp.int32) if lens is None \
        else jnp.asarray(lens, jnp.int32)
    x = _f32(jnp.take(params["embedding"], ids, axis=0))
    nth = {"S": 0, "*": 0}
    for l, kind in enumerate(mixer_kinds(hf)):
        lp = block_params(params, kind, nth[kind])
        nth[kind] += 1
        if kind == "S":
            x, S = mamba_block(x, lp, lens, d_in=sh["d_in"], N=sh["N"],
                               R=sh["R"], K=sh["K"], eps=sh["eps"])
            if states is not None:
                states.append(S)
        else:
            x = attention_block(x, lp, heads=sh["heads"],
                                kv_heads=sh["kv_heads"],
                                head_dim=sh["head_dim"], eps=sh["eps"])
        x = ffn_block(x, block_params(params, "-", l), eps=sh["eps"])
    return _rms(x, _f32(params["final_norm"]), sh["eps"])


def _head(params, hf):
    """[V, D]: the embedding itself where the head is tied."""
    if hf.get("tie_word_embeddings", False):
        return params["embedding"]
    return params["lm_head"].T


def logits(params, hf, ids):
    """ids [B, T] -> float32 logits [B, T, V] (small sizes: the tests)."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, hf, ids) @ _f32(_head(params, hf)).T


def next_token_logprobs(params, hf, ids):
    """ids [B, T] int32 -> float32 [B, T-1]: log p(ids[:, t+1] | ids[:, :t+1])
    at temperature 1.  Sequences padded at the END are fine: position t only
    sees positions <= t."""
    ids = jnp.asarray(ids, jnp.int32)
    B, T = ids.shape
    x = hidden_states(params, hf, ids)
    head = _head(params, hf)
    xs = x[:, :-1].reshape(B * (T - 1), -1)
    labels = ids[:, 1:].reshape(-1)
    lses, picked = [], jnp.zeros(xs.shape[0], jnp.float32)
    for lo in range(0, head.shape[0], HEAD_CHUNK):
        lse, pk = _head_chunk(xs, head[lo: lo + HEAD_CHUNK], labels, lo)
        lses.append(lse)
        picked = picked + pk
    lse = jax.nn.logsumexp(jnp.stack(lses, 0), axis=0)
    return (picked - lse).reshape(B, T - 1)


def slow_channels(params, j, share=0.25):
    """The `share` of Mamba layer j's channels that remember longest
    (smallest nominal step size softplus(dt_bias); a channel's column n
    then decays by exp(-dt (n + 1)) a token): where a state's precision
    shows, since what is rounded away at every step adds up over a
    channel's memory."""
    import numpy as np

    rate = np.asarray(jax.nn.softplus(_f32(params["layers"]["S"]["dt_bias"][j])))
    return np.argsort(rate)[: max(1, int(len(rate) * share))]


def state_error(got, want):
    """got, want [B, d_inner, N] -> [B, d_inner]: each channel's |got -
    want| over |want| (over its N columns)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(np.square(got - want).sum(-1)
                   / np.maximum(np.square(want).sum(-1), 1e-300))
