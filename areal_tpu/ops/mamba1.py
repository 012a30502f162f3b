"""Mamba-1: the selective scan (Gu & Dao, "Mamba: Linear-Time Sequence
Modeling with Selective State Spaces", arXiv:2312.00752; the mixer of the
`jamba` family).

Per channel c of d_inner and state column n of N, with a step size dt of
the channel's own and B, C shared by all channels of a token:

    S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]           A < 0

The decay is one number a channel AND state column, so no [Q, Q] matrix of
a chunk exists as in `ops/mamba2.py` (whose decay is a scalar a head): the
recurrence is elementwise over [N, C] and sequential over positions.  Two
forms of the same function:

- `selective_scan_chunked`: a sequence in chunks of `CHUNK` positions.
  Training (packed rows: the state resets at every segment start),
  prefill, and continuation from a state.
- `selective_step`: one token against the state.  Decode.

The convolution before it is `ops/mamba2.py`'s (`causal_conv`,
`conv_step`) over the d_inner channels of u.

The state is held [.., N, C]: the channels last, where the chip's lanes
are (a last axis of 16 would be padded to 128 or laid out anew by the
compiler).  The state and every sum into it are float32; a padded position
(segment id < 0, last in its row) is transparent: dt = 0, no decay and no
term.  Plain `jax.numpy`, differentiable.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# Positions a chunk of the sequence form holds.  Inside a chunk the
# positions are stepped one after the other with the state carried (no
# array of a chunk's states exists in the forward pass); the chunk is what
# the backward pass recomputes at a time, and it then holds every state of
# the chunk: [CHUNK, N, C] float32 a row, 0.33 MB a position at Jamba's
# 16 x 5,120, so 21 MB a row at 64, beside one carried state (0.33 MB) for
# each of a row's T / 64 chunks: 84 MB a layer for a packed row of 16,384.
# At 16 the carried states of that row are 335 MB a layer, at 256 a chunk's
# states are 84 MB a row before a single layer's activations: 64 is where
# the two meet.  On the chip (v5e, one layer at 5,120 channels, PR 52) the
# form reads 3.7 ms for one row of 2,048 positions, 2.4 ms for 4 x 512, 1.5
# ms for 8 x 128: 1.8 us a position.  An associative scan over a chunk of 16
# or 64 (it builds the chunk's [Q, N, C] decays and terms) read 5.0-6.2, 5.2-
# 37.6 and 15.6-21.3 ms, and unrolling 4 to 64 positions an iteration of the
# loop within a tenth of none: the plain loop is kept
CHUNK = 64


class StateAt(NamedTuple):
    """A block of slots' states named where they lie, for the decode
    kernel (`ops/mamba1_decode.py`): the pool leaf whole, the layer (traced:
    the leaf rides the scans of the traversal) and the block's first slot."""

    pool: jax.Array  # [n_ssm, S, N, C] float32
    layer: jax.Array  # int32 scalar
    slot_base: int


def admit_tokens(d_inner: int) -> int:
    """The most padded tokens (rows x bucket) one prefill dispatch of the
    sequence form should take: it holds u, dt and y a token in float32
    [d_inner] each whatever the chunk (the states are carried, not kept),
    and 256 MiB of them a Mamba block is what fits beside the weights and a
    full pool; a power of two, as the engine's buckets are."""
    per_token = 3 * 4 * int(d_inner)
    return 1 << ((256 << 20) // per_token).bit_length() - 1


def _step(S, ut, dtt, Bt, Ct, At):
    """One position: S [B, N, C], ut / dtt [B, C], Bt / Ct [B, N], At [N, C]
    -> (new state, y without the D term [B, C])."""
    S = jnp.exp(dtt[:, None, :] * At) * S + (dtt * ut)[:, None, :] * Bt[:, :, None]
    return S, jnp.sum(S * Ct[:, :, None], axis=1)


def selective_scan_chunked(
    u: jax.Array,  # [B, T, C] after the convolution and its SiLU
    dt: jax.Array,  # [B, T, C] after softplus, > 0
    A: jax.Array,  # [C, N] < 0
    Bm: jax.Array,  # [B, T, N]
    Cm: jax.Array,  # [B, T, N]
    D: jax.Array,  # [C]
    segment_ids: jax.Array,  # int [B, T]; < 0 = padding, last in its row
    state0: Optional[jax.Array] = None,  # [B, N, C] float32
    chunk: int = CHUNK,
) -> Tuple[jax.Array, jax.Array]:
    """-> (y [B, T, C] in u's dtype, the state after each row's last valid
    token [B, N, C] float32).  `state0` belongs to the segment of each
    row's first token; without it the rows start empty.  A new segment id
    starts from an empty state."""
    B, T, C = u.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    Q = min(int(chunk), T)
    pad = (-T) % Q
    if pad:
        padw = lambda a, val=0: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2), constant_values=val
        )
        u, dt, Bm, Cm = padw(u), padw(dt), padw(Bm), padw(Cm)
        segment_ids = padw(segment_ids, -1)
    n = (T + pad) // Q
    valid = segment_ids >= 0
    # padding belongs to the segment before it and adds nothing to it
    seg = jax.lax.cummax(segment_ids, axis=1)
    dtf = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    # a segment's first position starts from nothing: the state before it
    # is dropped, whatever it held
    prev = jnp.concatenate([seg[:, :1], seg[:, :-1]], axis=1)
    keep = (seg == prev).astype(f32)  # [B, T]; a row's first keeps state0
    if state0 is None:
        state0 = jnp.zeros((B, N, C), f32)

    def split(a):  # [B, n*Q, ...] -> [n, Q, B, ...]
        a = a.reshape((B, n, Q) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 1)

    xs = (split(u.astype(f32)), split(dtf), split(Bm.astype(f32)),
          split(Cm.astype(f32)), split(keep))
    At = A.astype(f32).T  # [N, C]

    def position(S, p):
        ut, dtt, Bt, Ct, kt = p
        return _step(S * kt[:, None, None], ut, dtt, Bt, Ct, At)

    @jax.checkpoint
    def body(S, c):
        return jax.lax.scan(position, S, c)

    S, ys = jax.lax.scan(body, state0.astype(f32), xs)  # ys [n, Q, B, C]
    y = jnp.moveaxis(ys.reshape(n * Q, B, C), 0, 1)[:, :T]
    y = y + D.astype(f32) * u[:, :T].astype(f32)
    return y.astype(u.dtype), S


def selective_step(
    u: jax.Array,  # [B, C]
    dt: jax.Array,  # [B, C] after softplus
    A: jax.Array,  # [C, N]
    Bm: jax.Array,  # [B, N]
    Cm: jax.Array,  # [B, N]
    D: jax.Array,  # [C]
    state: jax.Array,  # [B, N, C] float32
    active: Optional[jax.Array] = None,  # bool [B]; False leaves the state
) -> Tuple[jax.Array, jax.Array]:
    """One token: -> (y [B, C] in u's dtype, the state with the token in
    it).  Elementwise in float32, one pass over the state: the state's
    bytes are the cost."""
    f32 = jnp.float32
    dtf = dt.astype(f32)
    if active is not None:
        # an idle slot keeps its state to the bit: decay 1, no term
        dtf = jnp.where(active[:, None], dtf, 0.0)
    uf = u.astype(f32)
    new, y = _step(state, uf, dtf, Bm.astype(f32), Cm.astype(f32),
                   A.astype(f32).T)
    return (y + D.astype(f32) * uf).astype(u.dtype), new
