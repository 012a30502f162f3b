"""Fused LM-head cross-entropy / logprob with a hand-written VJP.

VERDICT r3 #2: the chunked-scan head cost ~196 ms/step against a ~155 ms
4-matmul-pass floor (fwd + bwd logits recompute + dx + dW; storing [T, V]
logits for a 3-pass backward needs ~5 GB and cannot fit next to the
resident optimizer state).  The ~40 ms gap was pure overhead: fp32 logits
materialisation, the scan transpose shuttling a [D, V] fp32 head-cotangent
carry through every token chunk, and entropy/argmax work that re-read the
logits — all for outputs the GRPO loss uses as *stats only*.

This implementation is the TPU counterpart of the reference's
vocab-parallel cross-entropy (realhf/impl/model/parallelism/
tensor_parallel/modules.py:1180 vocab_parallel_cross_entropy) — same
discipline (never hold full fp32 logits), achieved by **vocab chunking
with an online softmax** instead of sharding vocab across ranks:

- forward: one `lax.scan` over vocab chunks keeps running (max, sumexp,
  sum(exp*l), picked-logit, argmax) carries of size [T] — logits exist
  only as a [T, cv] bf16 block inside each step;
- backward: recomputes each vocab chunk's logits once, forms
  dlogits = g_lp * (onehot - p) in-register, accumulates dx in a [T, D]
  fp32 carry (~100 MB — vs the [D, V] ~933 MB carry the token-chunked
  scan transpose dragged through every step) and writes each dW vocab
  slice exactly once;
- entropy is returned for stats but its gradient term is only computed
  when the caller actually trains on it (`entropy_grad`); the argmax
  "correct" output is always gradient-free.
"""
# areal-lint: hot-path

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _vocab_chunk(v: int, target: int) -> int:
    """MXU-friendly chunk width: a multiple of 128 (the systolic array's
    lane width — an exact-divisor rule would hand Qwen's 151936 = 2^7*1187
    vocab a 4748-wide chunk that tiles terribly), capped at the padded
    vocab size.  The final partial chunk is handled by masking."""
    return min(_round_up(v, 128), _round_up(target, 128))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_xent(inv_t, cv, with_entropy, entropy_grad, h, head, labels):
    out, _ = _fused_xent_fwd(inv_t, cv, with_entropy, entropy_grad, h, head, labels)
    return out


def _fused_xent_fwd(inv_t, cv, with_entropy, entropy_grad, h, head, labels):
    N, D = h.shape
    V = head.shape[1]
    nv = -(-V // cv)
    neg = jnp.float32(-1e30)
    wp = _pad_head(head, nv * cv)

    def one_chunk(carry, i):
        m, s, mu_un, picked, amax_v, amax_i = carry
        wc = jax.lax.dynamic_slice_in_dim(wp, i * cv, cv, axis=1)
        logits = (h @ wc).astype(jnp.float32) * inv_t
        # mask the padded tail of the last chunk out of the softmax
        logits = jnp.where(i * cv + jnp.arange(cv) < V, logits, neg)
        cm = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, cm)
        alpha = jnp.exp(m - m_new)
        ex = jnp.exp(logits - m_new[:, None])
        s = s * alpha + jnp.sum(ex, axis=-1)
        rel = labels - i * cv
        inrange = (rel >= 0) & (rel < cv)
        got = jnp.take_along_axis(
            logits, jnp.clip(rel, 0, cv - 1)[:, None], axis=1
        )[:, 0]
        picked = jnp.where(inrange, got, picked)
        if with_entropy:
            mu_un = mu_un * alpha + jnp.sum(ex * logits, axis=-1)
            ci = jnp.argmax(logits, axis=-1) + i * cv
            better = cm > amax_v
            amax_v = jnp.where(better, cm, amax_v)
            amax_i = jnp.where(better, ci, amax_i)
        return (m_new, s, mu_un, picked, amax_v, amax_i), None

    init = (
        jnp.full((N,), neg),
        jnp.zeros((N,), jnp.float32),
        jnp.zeros((N,), jnp.float32),
        jnp.zeros((N,), jnp.float32),
        jnp.full((N,), neg),
        jnp.zeros((N,), jnp.int32),
    )
    (m, s, mu_un, picked, _, amax_i), _ = jax.lax.scan(
        one_chunk, init, jnp.arange(nv)
    )
    logz = m + jnp.log(s)
    logp = picked - logz
    if with_entropy:
        mu = mu_un / s
        ent = logz - mu
        corr = (amax_i == labels).astype(jnp.float32)
    else:
        mu = jnp.zeros_like(logz)
        ent = jnp.zeros_like(logz)
        corr = jnp.zeros_like(logz)
    return (logp, ent, corr), (h, head, labels, logz, mu)


def _pad_head(head, vp: int):
    V = head.shape[1]
    if vp == V:
        return head
    return jnp.pad(head, ((0, 0), (0, vp - V)))


def _fused_xent_bwd(inv_t, cv, with_entropy, entropy_grad, res, g):
    h, head, labels, logz, mu = res
    g_lp, g_ent, _ = g  # corr is gradient-free by construction
    N, D = h.shape
    V = head.shape[1]
    nv = -(-V // cv)
    wp = _pad_head(head, nv * cv)
    g_lp = g_lp.astype(jnp.float32)
    g_ent = g_ent.astype(jnp.float32)

    def one(dx, i):
        wc = jax.lax.dynamic_slice_in_dim(wp, i * cv, cv, axis=1)
        logits = (h @ wc).astype(jnp.float32) * inv_t
        # padded-tail logits produce p=0 via the same mask the fwd used
        logits = jnp.where(
            i * cv + jnp.arange(cv) < V, logits, jnp.float32(-1e30)
        )
        p = jnp.exp(logits - logz[:, None])  # [N, cv]
        rel = labels - i * cv
        onehot = jnp.arange(cv)[None, :] == rel[:, None]
        d = g_lp[:, None] * (onehot.astype(jnp.float32) - p)
        if entropy_grad:
            # d ent / d logit_v = p_v * (mu - logit_v)
            d = d + g_ent[:, None] * p * (mu[:, None] - logits)
        draw = (d * inv_t).astype(h.dtype)  # back through the scale + cast
        dx = dx + jnp.einsum(
            "nc,dc->nd", draw, wc, preferred_element_type=jnp.float32
        )
        dwc = jnp.einsum(
            "nd,nc->dc", h, draw, preferred_element_type=jnp.float32
        )
        return dx, dwc

    dx, dws = jax.lax.scan(one, jnp.zeros((N, D), jnp.float32), jnp.arange(nv))
    # dws [nv, D, cv] -> [D, Vp] -> [D, V]; each slice was written once
    dhead = (
        jnp.swapaxes(dws, 0, 1).reshape(D, nv * cv)[:, :V].astype(head.dtype)
    )
    return (
        dx.astype(h.dtype),
        dhead,
        np.zeros(labels.shape, dtype=jax.dtypes.float0),
    )


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def fused_logprobs_entropy(
    hidden: jax.Array,  # [N, D]
    head: jax.Array,  # [D, V]
    labels: jax.Array,  # int [N]
    temperature: float = 1.0,
    vocab_chunk: int = 8192,
    with_entropy: bool = True,
    entropy_grad: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(logprobs, entropy, argmax-correct) of `labels`, fp32 [N] each.

    `entropy_grad=False` (the GRPO default: entropy_coef == 0 means the
    entropy is logged, never trained on) drops the p*(mu - logits) term
    from the backward — one less elementwise pass over each recomputed
    logits block.  Entropy values are still exact either way.
    """
    cv = _vocab_chunk(head.shape[1], vocab_chunk)
    # the scope at the call reaches the hand-written backward too (as
    # `transpose(jvp(xent))`)
    with jax.named_scope("xent"):
        return _fused_xent(
            float(1.0 / temperature), cv, bool(with_entropy),
            bool(entropy_grad), hidden, head, labels.astype(jnp.int32),
        )
