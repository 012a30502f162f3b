"""Mamba-2: a causal depthwise convolution and a diagonal state-space
recurrence (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060; the mixer
of the `nemotron_h` family).

Per head h of P channels, with group g = h // (H / G) of the B/C
projections and a state of N columns:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S [P, N], A < 0 a scalar
    y_t = S_t C_t + D x_t

Two forms of the same function:

- `ssd_chunked`: a sequence in chunks of Q tokens (the SSD form).  Inside a
  chunk the weights are the explicit [Q, Q] matrix exp(cum_t - cum_j) dt_j
  (C_t . B_j); what lies before the chunk comes in through the state.
  Training (packed rows: the state resets at every segment start), prefill,
  and continuation from a state.
- `ssd_step`: one token against the state.  Decode.

and the convolution in the same two forms (`causal_conv`, `conv_step`),
whose state is the window of the last K - 1 input columns.

The state and every sum into it are float32 (float32 products at precision
"highest": a TPU would cut a float32 operand to bfloat16 by default); a
padded position (segment id < 0, last in its row) is transparent: dt = 0,
no decay and no term.  Plain `jax.numpy`, differentiable.
"""

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def causal_conv(
    x: jax.Array,  # [B, T, C]
    w: jax.Array,  # [K, C] taps, w[K - 1] on the current column
    b: jax.Array,  # [C]
    segment_ids: jax.Array,  # int [B, T]; < 0 = padding, last in its row
    window0: Optional[jax.Array] = None,  # [B, K - 1, C] columns before x
) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution -> (out [B, T, C], the window after each
    row's last valid column [B, K - 1, C]).  A tap never reaches into
    another segment (packed rows); `window0` belongs to the segment of each
    row's first token."""
    B, T, C = x.shape
    K = w.shape[0]
    if window0 is None:
        window0 = jnp.zeros((B, K - 1, C), x.dtype)
    run = jnp.concatenate([window0.astype(x.dtype), x], axis=1)  # [B, K-1+T, C]
    seg_run = jnp.concatenate(
        [jnp.broadcast_to(segment_ids[:, :1], (B, K - 1)), segment_ids], axis=1
    )
    out = jnp.broadcast_to(b.astype(jnp.float32), (B, T, C))
    for k in range(K):
        same = seg_run[:, k: k + T] == segment_ids  # [B, T]
        tap = jnp.where(same[..., None], run[:, k: k + T], 0).astype(jnp.float32)
        out = out + tap * w[k].astype(jnp.float32)
    n_valid = jnp.sum(segment_ids >= 0, axis=1)  # padding comes last
    window = jax.vmap(
        lambda r, n: jax.lax.dynamic_slice_in_dim(r, n, K - 1, axis=0)
    )(run, n_valid)
    return out.astype(x.dtype), window


def conv_step(
    x: jax.Array,  # [B, C] the new column
    w: jax.Array,  # [K, C]
    b: jax.Array,  # [C]
    window: jax.Array,  # [B, K - 1, C]
    active: Optional[jax.Array] = None,  # bool [B]; False leaves the window
) -> Tuple[jax.Array, jax.Array]:
    """One column -> (out [B, C], the window with the column in it)."""
    run = jnp.concatenate([window.astype(x.dtype), x[:, None]], axis=1)
    out = jnp.einsum(
        "bkc,kc->bc", run.astype(jnp.float32), w.astype(jnp.float32),
        precision=_HI,
    ) + b.astype(jnp.float32)
    new = run[:, 1:].astype(window.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, window)
    return out.astype(x.dtype), new


def _expand(g: jax.Array, H: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(g, H // g.shape[-2], axis=-2)


def ssd_chunked(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B, T, H] after softplus, > 0
    A: jax.Array,  # [H] < 0
    Bm: jax.Array,  # [B, T, G, N]
    Cm: jax.Array,  # [B, T, G, N]
    D: jax.Array,  # [H]
    segment_ids: jax.Array,  # int [B, T]; < 0 = padding, last in its row
    state0: Optional[jax.Array] = None,  # [B, H, P, N] float32
    chunk: int = 128,
) -> Tuple[jax.Array, jax.Array]:
    """-> (y [B, T, H, P] in x's dtype, the state after each row's last
    valid token, float32).  `state0` belongs to the segment of each row's
    first token; without it the rows start empty.  A new segment id starts
    from an empty state."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    f32 = jnp.float32
    Q = min(int(chunk), T)
    pad = (-T) % Q
    if pad:
        padw = lambda a, val=0: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2), constant_values=val
        )
        x, dt, Bm, Cm = padw(x), padw(dt), padw(Bm), padw(Cm)
        segment_ids = padw(segment_ids, -1)
    n = (T + pad) // Q
    valid = segment_ids >= 0
    # padding belongs to the segment before it and adds nothing to it
    seg = jax.lax.cummax(segment_ids, axis=1)
    dtf = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    if state0 is None:
        state0 = jnp.zeros((B, H, P, N), f32)

    def split(a):  # [B, n*Q, ...] -> [n, B, Q, ...]
        return jnp.moveaxis(a.reshape((B, n, Q) + a.shape[2:]), 1, 0)

    xs = (split(x.astype(f32)), split(dtf), split(Bm.astype(f32)),
          split(Cm.astype(f32)), split(seg))
    Af = A.astype(f32)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    @jax.checkpoint
    def body(carry, c):
        S, seg_prev = carry
        xc, dtc, Bc, Cc, segc = c
        cum = jnp.cumsum(dtc * Af, axis=1)  # [B, Q, H] log-decay from the start
        pair = tri[None] & (segc[:, :, None] == segc[:, None, :])  # [B, Q, Q]
        cumh = jnp.moveaxis(cum, 2, 1)  # [B, H, Q]
        decay = jnp.exp(jnp.where(
            pair[:, None], cumh[:, :, :, None] - cumh[:, :, None, :], -jnp.inf
        ))  # [B, H, Q(t), Q(j)]
        cb = jnp.einsum("btgn,bjgn->bgtj", Cc, Bc, precision=_HI)
        w = jnp.repeat(cb, H // G, axis=1) * decay * jnp.moveaxis(
            dtc, 2, 1)[:, :, None, :]
        y = jnp.einsum("bhtj,bjhp->bthp", w, xc, precision=_HI)
        # before the chunk: through the state, while its segment lasts
        from_state = jnp.where(
            (segc == seg_prev[:, None])[..., None], jnp.exp(cum), 0.0
        )  # [B, Q, H]
        y = y + from_state[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", _expand(Cc, H), S, precision=_HI
        )
        y = y + D.astype(f32)[None, None, :, None] * xc
        seg_end = segc[:, -1]
        total = cum[:, -1]  # [B, H]
        w_j = jnp.where(
            (segc == seg_end[:, None])[..., None],
            jnp.exp(total[:, None] - cum), 0.0,
        ) * dtc  # [B, Q, H]
        keep = jnp.where((seg_prev == seg_end)[:, None], jnp.exp(total), 0.0)
        S = keep[..., None, None] * S + jnp.einsum(
            "bjhp,bjhn->bhpn", xc * w_j[..., None], _expand(Bc, H),
            precision=_HI,
        )
        return (S, seg_end), y

    (S, _), ys = jax.lax.scan(body, (state0.astype(f32), seg[:, 0]), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, n * Q, H, P)[:, :T]
    return y.astype(x.dtype), S


def ssd_step(
    x: jax.Array,  # [B, H, P]
    dt: jax.Array,  # [B, H] after softplus
    A: jax.Array,  # [H]
    Bm: jax.Array,  # [B, G, N]
    Cm: jax.Array,  # [B, G, N]
    D: jax.Array,  # [H]
    state: jax.Array,  # [B, H, P, N] float32
    active: Optional[jax.Array] = None,  # bool [B]; False leaves the state
) -> Tuple[jax.Array, jax.Array]:
    """One token: -> (y [B, H, P] in x's dtype, the state with the token in
    it).  Elementwise in float32, one pass over the state: the products are
    too thin for the matrix unit and the state's bytes are the cost."""
    H = x.shape[1]
    f32 = jnp.float32
    dtf = dt.astype(f32)
    if active is not None:
        # an idle slot keeps its state to the bit: decay 1, no term
        dtf = jnp.where(active[:, None], dtf, 0.0)
    xf = x.astype(f32)
    decay = jnp.exp(dtf * A.astype(f32))  # [B, H]
    Bh = _expand(Bm.astype(f32), H)  # [B, H, N]
    Ch = _expand(Cm.astype(f32), H)
    new = decay[..., None, None] * state + (
        (dtf[..., None] * xf)[..., None] * Bh[:, :, None, :]
    )
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1) + D.astype(f32)[None, :, None] * xf
    return y.astype(x.dtype), new


def gated_group_norm(
    y: jax.Array,  # [..., d_inner]
    z: jax.Array,  # [..., d_inner] the gate
    weight: jax.Array,  # [d_inner]
    n_groups: int,
    eps: float,
) -> jax.Array:
    """RMSNorm over groups of d_inner / n_groups channels of y * silu(z)."""
    dtype = y.dtype
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (n_groups, shape[-1] // n_groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * weight.astype(f32)).astype(dtype)
