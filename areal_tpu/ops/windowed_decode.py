"""Paged decode attention over a full layer's columns of the `windowed`
slot kind (`models/windowed.py`): one new query a slot against that slot's
cached keys and values, read from the pool where they lie, by LENGTH and
ONCE.

The pool leaves are `k` [n_full, S, M, Hkv * dq] and `v` [n_full, S, M,
Hkv * dv]: position-major, the kv heads side by side in one row, a key
wider than a value (`mimo_v2`: four heads of 192 beside four of 128, six
and four whole lane groups a position).  The copy path (`_attend_decode`,
`ragged=False`) slices eight slots' first K columns out of both leaves, K
the bucket of the LONGEST live slot, and reads the copies again for the
scores and the weighted sum.  This kernel leaves the pool in HBM and walks
slot b's positions [0, starts[b]) a tile of `BLOCK` positions at a time: a
[tk, Hkv * dq] tile of keys and a [tk, Hkv * dv] tile of values are each
fetched once, under a float32 running maximum, sum and accumulator (online
softmax).  The new column, which the pool does not hold yet, opens the
softmax as a one-column part, so a slot of length 0 attends it alone; an
inactive slot fetches nothing and returns zeros.  Tiles past a slot's
length are neither fetched nor computed: the grid is the slots, the walk an
inner loop of `cdiv(starts[b], tk)` steps, and the next pair of tiles (the
next live slot's first one too) travels while this one computes.

The head layout is `_softmax_parts`'s: a row is never split by head (192
values are one and a half lane groups).  Query head (h, g) is widened to
the whole row with zeros outside kv head h's part, so ONE product of all H
queries with a tile gives every head's scores, and of the weighted sum over
whole rows each head keeps its kv head's part: Hkv times the operations, on
a step that waits for the tiles' bytes.

It shares nothing with `ops/ragged_decode.py` (heads of 128 interleaved by
position, a slot's whole window gathered into VMEM, `naive_attention`'s
operations bit for bit) or `ops/latent_decode.py` (positions minor, a tile
is key and value at once) but the interpreter switch.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.ragged_decode import _interpret_mode

# positions a tile: two [BLOCK, Hkv * dq] and two [BLOCK, Hkv * dv] buffers
# live in VMEM (2.6 MB of a 16-bit pool of 768 + 512 values a position)
# beside a [H, BLOCK] block of scores
BLOCK = 512


def _block(M: int) -> int:
    """Positions a tile of a pool of M: `BLOCK`, or all of a shorter pool;
    0 where neither divides M."""
    if M % BLOCK == 0:
        return BLOCK
    return M if M < BLOCK else 0


def windowed_refusal(cfg, cache, max_seq_len: int, kv_dtype, tp: int) -> str:
    """Why the kernel cannot serve this pool in this process, or ""
    (`SlotKind.kernel_refusal` of the `windowed` kind: evaluated once at
    engine init, from the configuration and the pool's own leaves, which
    say its dtype; `tp > 1` the kind refuses by name before this).  A tile
    is widened by a plain cast, which the chip's kernel compiler takes from
    2- and 4-byte values; it is cut along the positions, whole rows of whole
    lane groups.  The softmax has no sink: a family that puts one on its
    FULL layers (`add_full_attention_sink_bias`) keeps the copy path.  A
    test's flag or an explicit CPU run interprets the kernel, whatever the
    widths; any other backend has neither (utils/runtime.py
    kernel_backend)."""
    k, v = cache["k"], cache["v"]
    itemsize = k.dtype.itemsize
    pool = (f"a pool of [{max_seq_len}, {k.shape[3]} + {v.shape[3]}] x "
            f"{itemsize} byte(s) a slot")
    if itemsize not in (2, 4):
        return f"the windowed kernel reads 2- or 4-byte columns, not {pool}"
    if cfg.sink_full:
        return (
            "the windowed kernel's softmax has no sink, and this family puts "
            "one on its full layers (add_full_attention_sink_bias)")
    tk = _block(max_seq_len)
    if not tk:
        return (
            f"the windowed kernel walks tiles of {BLOCK} positions, which do "
            f"not divide {pool}"
        )
    try:
        interpret = _interpret_mode(None)
    except RuntimeError as e:
        return str(e)
    group = 32 // itemsize  # sublanes of one tile of the pool's dtype
    tiles = k.shape[3] % 128 == 0 and v.shape[3] % 128 == 0 and tk % group == 0
    if not interpret and not tiles:
        return (
            f"the TPU's kernel compiler does not tile {pool} (rows of whole "
            f"lane groups of 128, positions in groups of {group})"
        )
    return ""


def _kernel(
    # scalar prefetch (SMEM)
    starts_ref,  # int32 [B] cached columns a slot attends: positions [0, start)
    nblk_ref,  # int32 [B] tiles that span covers; 0 for an inactive slot
    par_ref,  # int32 [B] which buffer takes the slot's first tile
    next_ref,  # int32 [B + 1]: [0] the first slot with a tile, [b + 1] the
    # first one after b; B where there is none
    live_ref,  # int32 [B]
    # blocked inputs (VMEM)
    q_ref,  # [1, H, Rk] compute dtype: each head widened to the whole row
    kn_ref,  # [1, 1, Rk] compute dtype: the column the pool does not hold yet
    vn_ref,  # [1, 1, Rv]
    k_hbm,  # [n_full, S, M, Rk] ANY: the pool, read by DMA
    v_hbm,  # [n_full, S, M, Rv] ANY
    # output
    out_ref,  # [1, H, Rv]: every head over whole rows
    # scratch
    kbuf_ref,  # VMEM [2, tk, Rk] pool dtype
    vbuf_ref,  # VMEM [2, tk, Rv] pool dtype
    sem,  # DMA [2, 2]: (key | value, buffer)
    *,
    j: int,
    slot_base: int,
    tk: int,
    scale: float,
):
    B = starts_ref.shape[0]
    i = pl.program_id(0)
    n = nblk_ref[i]
    dtype = q_ref.dtype
    f32 = jnp.float32

    def tile_dmas(slot, kb, b):
        at = pl.ds(pl.multiple_of(kb * tk, tk), tk)
        return [
            pltpu.make_async_copy(
                hbm.at[j, slot_base + slot, at], buf.at[b], sem.at[which, b])
            for which, (hbm, buf) in enumerate(
                ((k_hbm, kbuf_ref), (v_hbm, vbuf_ref)))
        ]

    @pl.when(i == 0)
    def _():
        head = next_ref[0]

        @pl.when(head < B)
        def _():
            for dma in tile_dmas(head, 0, par_ref[head]):
                dma.start()

    @pl.when(live_ref[i] == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[i] != 0)
    def _():
        q = q_ref[0]  # [H, Rk]
        start = starts_ref[i]
        # the new column opens the softmax: its probability is 1 at its own
        # maximum, and its value the accumulator
        m0 = jnp.sum(
            q.astype(f32) * kn_ref[0].astype(f32), axis=-1, keepdims=True
        ) * scale
        l0 = jnp.ones_like(m0)
        acc0 = jnp.broadcast_to(
            vn_ref[0].astype(f32), (q.shape[0], vn_ref.shape[2]))
        # bfloat16 operands have one precision; naming it keeps a process-
        # wide jax_default_matmul_precision (the CPU suite sets "highest")
        # from asking for a multi-pass product of 16-bit inputs
        precision = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None
        lowest = jnp.finfo(f32).min

        def one_tile(kb, carry):
            m, l, acc = carry
            b = (par_ref[i] + kb) % 2

            # what is walked next travels while this tile computes: this
            # slot's next tile, or the first one of the next slot that has any
            more = kb + 1 < n
            ahead = jnp.where(more, i, next_ref[i + 1])

            @pl.when(ahead < B)
            def _():
                for dma in tile_dmas(ahead, jnp.where(more, kb + 1, 0), 1 - b):
                    dma.start()

            wait_k, wait_v = tile_dmas(i, kb, b)
            wait_k.wait()
            s = jax.lax.dot_general(
                q, kbuf_ref[b].astype(dtype), (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=f32,
            ) * scale  # [H, tk]
            pos = kb * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < start, s, lowest)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            wait_v.wait()
            # the probabilities narrowed before the weighted sum, as the
            # copy path narrows them
            pv = jnp.dot(
                p.astype(dtype), vbuf_ref[b].astype(dtype),
                precision=precision, preferred_element_type=f32,
            )  # [H, Rv]
            return m_new, l, alpha * acc + pv

        _, l, acc = jax.lax.fori_loop(0, n, one_tile, (m0, l0, acc0))
        out_ref[0] = (acc / l).astype(dtype)


def windowed_decode_attention(
    q: jax.Array,  # [B, H, dq] compute dtype
    k_new: jax.Array,  # [B, Hkv * dq] compute dtype, rounded through the pool's
    v_new: jax.Array,  # [B, Hkv * dv]
    pool_k: jax.Array,  # [n_full, S, M, Hkv * dq] the pool leaf
    pool_v: jax.Array,  # [n_full, S, M, Hkv * dv]
    starts: jax.Array,  # int32 [B] cached columns attended, each < M
    live: jax.Array,  # bool [B]
    *,
    j: int,  # the layer among the full ones
    slot_base: int,  # the block's first slot
    scale: float,
    block: Optional[int] = None,  # a test's tile width
    interpret: Optional[bool] = None,
):
    """softmax(scale * q . [keys of slot b below starts[b] | k_new]) times
    the values, each query head over its kv head's part of the rows -> [B,
    H, dv]; zeros for a slot that is not live.  The pool is only read."""
    B, H, dq = q.shape
    M, Rk = pool_k.shape[2:]
    Rv = pool_v.shape[3]
    Hkv = Rk // dq
    G = H // Hkv
    tk = block or _block(M)
    if not tk or M % tk:
        raise ValueError(f"tiles of {tk} positions do not divide a pool of {M}")
    # query head (h, g) over the whole row, zeros outside kv head h's part
    eye = jnp.eye(Hkv, dtype=q.dtype)
    wide = jnp.einsum(
        "bhgd,hj->bhgjd", q.reshape(B, Hkv, G, dq), eye).reshape(B, H, Rk)
    live = live.astype(jnp.int32)
    nblk = jnp.where(live > 0, (starts + tk - 1) // tk, 0).astype(jnp.int32)
    before = jnp.cumsum(nblk) - nblk
    slots = jnp.arange(B, dtype=jnp.int32)
    following = jax.lax.cummin(jnp.where(nblk > 0, slots, B), reverse=True)
    following = jnp.concatenate([following, jnp.full((1,), B, jnp.int32)])
    interp = _interpret_mode(interpret)
    kernel = functools.partial(
        _kernel, j=j, slot_base=slot_base, tk=tk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, Rk), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, Rk), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, Rv), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, Rv), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tk, Rk), pool_k.dtype),
            pltpu.VMEM((2, tk, Rv), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Rv), q.dtype),
        interpret=interp,
        name="windowed_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ) if not interp else None,
    )(starts.astype(jnp.int32), nblk, (before % 2).astype(jnp.int32),
      following, live, wide, k_new[:, None], v_new[:, None], pool_k, pool_v)
    # of the weighted sum over whole rows each head keeps its kv head's part
    return jnp.einsum(
        "bhgjv,hj->bhgv", out.reshape(B, Hkv, G, Hkv, Rv // Hkv), eye
    ).reshape(B, H, Rv // Hkv)
