"""Power retention: attention whose weights are a power of the score.

    a_tj = exp(sum_{s=j+1..t} log g_s) * ((q_t . k_j) / sqrt(d))^p     j <= t
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

(Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; degree p = 2 with a scalar gate per kv head.)  For p = 2
the weight factors through the symmetric degree-2 features `phi`, with
phi(q) . phi(k) = (q . k)^2, so a sequence is summarised by a state of
fixed size per kv head:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T   [F, d]      z_t = g_t z_{t-1} + phi(k_t)   [F]
    y_t = c phi(q_t)^T S_t / (c phi(q_t)^T z_t + eps),   c = d^(-p/2),  F = d (d + 1) / 2

Two forms of the same function:

- `retention_chunked`: a sequence in chunks of C tokens.  Inside a chunk the
  weights are the explicit [C, C] matrix; what lies before the chunk comes
  in through the state.  Training (packed rows: the state resets at every
  segment start), prefill, and continuation from a state.
- `retention_step`: one token against the state.  Decode.

Neither builds `phi` of more than a chunk, nor a score matrix beyond one
chunk.  The state and every sum into it are float32; the matmul operands
have the dtype of q/k/v.  Plain `jax.numpy`, differentiable.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


class RetentionState(NamedTuple):
    s: jax.Array  # [B, Hkv, F, d] float32
    z: jax.Array  # [B, Hkv, F] float32


def feature_dim(head_dim: int, degree: int = 2) -> int:
    if degree != 2:
        raise ValueError(
            f"power retention of degree {degree} is not implemented: only "
            "degree 2 has its symmetric feature map here"
        )
    return head_dim * (head_dim + 1) // 2


def phi(x: jax.Array) -> jax.Array:
    """[..., d] -> [..., d (d + 1) / 2]: x_a x_b for a <= b, the mixed terms
    times sqrt(2), so that phi(q) . phi(k) == (q . k)^2.  The two factors
    are picked by one-hot matmuls (exact: one term a sum), which the MXU
    does where a gather along lanes would crawl."""
    d = x.shape[-1]
    ia, ib = np.triu_indices(d)
    rows = jnp.arange(d, dtype=jnp.int32)[:, None]
    pick_a = (rows == jnp.asarray(ia, jnp.int32)[None, :]).astype(x.dtype)
    pick_b = (rows == jnp.asarray(ib, jnp.int32)[None, :]).astype(x.dtype)
    # a float32 operand would be cut to bfloat16 by the TPU's default
    prec = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    xa = jnp.einsum("...d,df->...f", x, pick_a, precision=prec)
    xb = jnp.einsum("...d,df->...f", x, pick_b, precision=prec)
    w = jnp.asarray(np.where(ia < ib, np.sqrt(2.0), 1.0), jnp.float32)
    return (xa.astype(jnp.float32) * xb.astype(jnp.float32) * w).astype(x.dtype)


def init_state(batch: int, n_kv: int, head_dim: int, degree: int = 2):
    F = feature_dim(head_dim, degree)
    return RetentionState(
        jnp.zeros((batch, n_kv, F, head_dim), jnp.float32),
        jnp.zeros((batch, n_kv, F), jnp.float32),
    )


def _chunk_one_head(q, k, v, lg, seg, valid, s0, z0, seg_prev, degree):
    """One chunk, one kv head, every row of the batch.

    q [B, C, G, d], k/v [B, C, d], lg [B, C] f32 (0 on padding), seg [B, C],
    valid [B, C], s0 [B, F, d], z0 [B, F], seg_prev [B]: the segment the
    state belongs to.  -> y [B, C, G, d] f32, s1, z1."""
    d = q.shape[-1]
    dt = q.dtype
    f32 = jnp.float32
    C = q.shape[1]
    cum = jnp.cumsum(lg, axis=1)  # [B, C]: log-decay from the chunk's start
    # inside the chunk: explicit weights
    sc = jnp.einsum("bcgd,bjd->bgcj", q, k, preferred_element_type=f32)
    sc = (sc * (d ** -0.5)) ** degree
    tri = jnp.tril(jnp.ones((C, C), bool))
    pair = (
        tri[None]
        & valid[:, :, None] & valid[:, None, :]
        & (seg[:, :, None] == seg[:, None, :])
    )  # [B, C(t), C(j)]
    decay = jnp.exp(jnp.where(pair, cum[:, :, None] - cum[:, None, :], -jnp.inf))
    a = sc * decay[:, None]  # [B, G, C, C] f32
    num = jnp.einsum("bgcj,bjd->bcgd", a.astype(dt), v, preferred_element_type=f32)
    den = jnp.transpose(a.sum(-1), (0, 2, 1))  # [B, C, G]
    # before the chunk: through the state, while its segment lasts
    from_state = valid & (seg == seg_prev[:, None])
    w_t = jnp.where(from_state, jnp.exp(cum), 0.0)  # [B, C]
    pq = phi(q)  # [B, C, G, F]
    c = float(d) ** (-degree / 2.0)
    num = num + c * w_t[:, :, None, None] * jnp.einsum(
        "bcgf,bfd->bcgd", pq, s0, preferred_element_type=f32
    )
    den = den + c * w_t[:, :, None] * jnp.einsum(
        "bcgf,bf->bcg", pq, z0, preferred_element_type=f32
    )
    y = num / (den[..., None] + EPS)
    # the state after the chunk belongs to its last valid token's segment
    # (padding comes last in a row and is transparent: no decay, no term)
    last = jnp.max(jnp.where(valid, jnp.arange(C)[None, :], -1), axis=1)  # [B]
    seg_end = jnp.where(
        last >= 0,
        jnp.take_along_axis(seg, jnp.maximum(last, 0)[:, None], axis=1)[:, 0],
        seg_prev,
    )
    total = cum[:, -1]
    w_j = jnp.where(
        valid & (seg == seg_end[:, None]), jnp.exp(total[:, None] - cum), 0.0
    )  # [B, C]
    keep = jnp.where(seg_prev == seg_end, jnp.exp(total), 0.0)  # [B]
    pk = phi(k)  # [B, C, F]
    pkw = (pk.astype(f32) * w_j[..., None]).astype(dt)
    s1 = keep[:, None, None] * s0 + jnp.einsum(
        "bcf,bcd->bfd", pkw, v, preferred_element_type=f32
    )
    z1 = keep[:, None] * z0 + jnp.sum(pkw.astype(f32), axis=1)
    return y, s1, z1, seg_end


def retention_chunked(
    q: jax.Array,  # [B, T, H, d]
    k: jax.Array,  # [B, T, Hkv, d]
    v: jax.Array,  # [B, T, Hkv, d]
    log_g: jax.Array,  # [B, T, Hkv] log of the gate, <= 0
    segment_ids: jax.Array,  # int [B, T]; < 0 = padding, last in its row
    state0: Optional[RetentionState] = None,
    chunk: int = 128,
    degree: int = 2,
) -> Tuple[jax.Array, RetentionState]:
    """-> (y [B, T, H, d] in q's dtype, the state after each row's last
    valid token).  `state0` belongs to the segment of each row's first
    token (continuation); without it the rows start empty.  A new segment
    id starts from an empty state (packed training rows)."""
    B, T, H, d = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    C = min(int(chunk), T)
    pad = (-T) % C
    if pad:
        padw = lambda x, val=0: jnp.pad(  # noqa: E731
            x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2), constant_values=val
        )
        q, k, v, log_g = padw(q), padw(k), padw(v), padw(log_g)
        segment_ids = padw(segment_ids, -1)
    n = (T + pad) // C
    valid = segment_ids >= 0
    lg = jnp.where(valid[..., None], log_g.astype(jnp.float32), 0.0)
    if state0 is None:
        state0 = init_state(B, Hkv, d, degree)

    def split(x):  # [B, n*C, ...] -> [n, B, C, ...]
        return jnp.moveaxis(x.reshape((B, n, C) + x.shape[2:]), 1, 0)

    xs = (
        split(q.reshape(B, n * C, Hkv, G, d)), split(k), split(v), split(lg),
        split(segment_ids), split(valid),
    )

    @jax.checkpoint
    def body(carry, x):
        s, z, seg_prev = carry
        qc, kc, vc, lgc, segc, validc = x

        def head(args):
            qh, kh, vh, lgh, sh, zh = args
            y, s1, z1, seg_end = _chunk_one_head(
                qh, kh, vh, lgh, segc, validc, sh, zh, seg_prev, degree
            )
            return y, s1, z1, seg_end

        # one kv head at a time: phi of a chunk's queries is C x G x F
        # numbers a row, and all heads at once would not fit beside a model
        y, s1, z1, seg_end = jax.lax.map(
            head,
            (
                jnp.moveaxis(qc, 2, 0), jnp.moveaxis(kc, 2, 0),
                jnp.moveaxis(vc, 2, 0), jnp.moveaxis(lgc, 2, 0),
                jnp.moveaxis(s, 1, 0), jnp.moveaxis(z, 1, 0),
            ),
        )
        # y [Hkv, B, C, G, d] -> [B, C, Hkv, G, d]
        y = jnp.transpose(y, (1, 2, 0, 3, 4))
        return (
            jnp.moveaxis(s1, 0, 1), jnp.moveaxis(z1, 0, 1), seg_end[0]
        ), y

    (s, z, _), ys = jax.lax.scan(
        body, (state0.s, state0.z, segment_ids[:, 0]), xs
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * C, H, d)[:, :T]
    return y.astype(q.dtype), RetentionState(s, z)


def retention_step(
    q: jax.Array,  # [B, H, d]
    k: jax.Array,  # [B, Hkv, d]
    v: jax.Array,  # [B, Hkv, d]
    log_g: jax.Array,  # [B, Hkv]
    state: RetentionState,
    active: Optional[jax.Array] = None,  # bool [B]; False leaves the state
    degree: int = 2,
) -> Tuple[jax.Array, RetentionState]:
    """One token: -> (y [B, H, d] in q's dtype, the state with the token in
    it).  The read-out is taken from the state as it was, plus the token's
    own term, so one pass over `S` serves both it and the update:
    phi(q)^T S_t = g phi(q)^T S_{t-1} + (phi(q) . phi(k)) v."""
    B, H, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    f32 = jnp.float32
    s, z = state
    g = jnp.exp(log_g.astype(f32))  # [B, Hkv]
    pq = phi(q.reshape(B, Hkv, G, d))  # [B, Hkv, G, F]
    pk = phi(k)  # [B, Hkv, F]
    if active is not None:
        # an idle slot keeps its state to the bit: g = 1, no term
        g = jnp.where(active[:, None], g, 1.0)
        pk = jnp.where(active[:, None, None], pk, jnp.zeros_like(pk))
    pkf = pk.astype(f32)
    own = jnp.einsum("bkgf,bkf->bkg", pq, pk, preferred_element_type=f32)
    num = g[..., None, None] * jnp.einsum(
        "bkgf,bkfd->bkgd", pq, s, preferred_element_type=f32
    ) + own[..., None] * v.astype(f32)[:, :, None, :]
    den = g[..., None] * jnp.einsum(
        "bkgf,bkf->bkg", pq, z, preferred_element_type=f32
    ) + own
    c = float(d) ** (-degree / 2.0)
    y = (c * num) / (c * den[..., None] + EPS)
    s1 = g[..., None, None] * s + pkf[..., None] * v.astype(f32)[:, :, None, :]
    z1 = g[..., None] * z + pkf
    return y.reshape(B, H, d).astype(q.dtype), RetentionState(s1, z1)
