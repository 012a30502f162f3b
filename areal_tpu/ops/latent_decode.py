"""Paged decode attention over latent rows: one new query a slot against
that slot's cached rows, read from the pool where they lie, by LENGTH and
ONCE.

The pool leaf `lat` is [2 L, S, row, M] (`models/latent.py`): a slot's rows
of one sublayer are a [row, M] matrix, the POSITIONS minor, no head axis;
every query head attends the same rows, and a row's value is its first
`kv_lora_rank` entries.  The copy path (`absorbed_attend`, `ragged=False`)
slices the block's whole key window out of the pool (40 x 576 x 8,192
values a sublayer and pass, whatever the lengths) and reads the copy twice
more.  This kernel leaves the pool in HBM and walks slot b's positions
[0, starts[b]) a [row, block] tile at a time: the tile is fetched once and
serves both products as it lies (scores `q @ tile`, weighted sum
`p @ tile[:C]^T` contracting both minor axes), under a float32 running
maximum, sum and accumulator (online softmax).  The new row, which the
pool does not hold yet, opens the softmax as a one-column part, so a slot
of length 0 attends it alone; an inactive slot fetches nothing and returns
zeros.  Tiles past a slot's length are neither fetched nor computed: the
grid is the slots, the walk an inner loop of `cdiv(starts[b], block)`
steps, and the next tile (the next live slot's first one too) travels while
this one computes.

It shares nothing with `ops/ragged_decode.py` but the interpreter switch:
that kernel gathers a slot's whole K/V window into VMEM, position-major
with heads interleaved, and repeats `naive_attention`'s operations bit for
bit; one latent window at 8,192 is 9.4 MB, positions are minor, and a tile
is key and value at once.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.ragged_decode import _interpret_mode

# positions a tile: two [row, BLOCK] buffers live in VMEM (2.4 MB of a
# 16-bit pool of 576-value rows) beside a [H, BLOCK] block of scores
BLOCK = 1024


def _block(M: int) -> int:
    """Positions a tile of a pool of M: `BLOCK`, or all of a shorter pool;
    0 where neither divides M."""
    if M % BLOCK == 0:
        return BLOCK
    return M if M < BLOCK else 0


def latent_refusal(
    row: int, kv_lora_rank: int, max_seq_len: int, itemsize: int
) -> str:
    """Why the kernel cannot serve a pool of [row, max_seq_len] matrices of
    `itemsize`-byte values in this process, or "" (`ops/ragged_decode.py
    kernel_refusal`'s sibling: evaluated once at engine init).  A tile is
    widened by a plain cast, which the chip's kernel compiler takes from 2-
    and 4-byte values; it is cut along the positions, and its value half is
    a slice of whole sublane groups.  A test's flag or an explicit CPU run
    interprets the kernel, whatever the widths; any other backend has
    neither (utils/runtime.py kernel_backend)."""
    pool = f"a pool of [{row}, {max_seq_len}] x {itemsize} byte(s) a slot"
    if itemsize not in (2, 4):
        return f"the latent kernel reads 2- or 4-byte rows, not {pool}"
    tk = _block(max_seq_len)
    if not tk:
        return (
            f"the latent kernel walks tiles of {BLOCK} positions, which do "
            f"not divide {pool}"
        )
    try:
        interpret = _interpret_mode(None)
    except RuntimeError as e:
        return str(e)
    group = 32 // itemsize  # sublanes of one tile of the pool's dtype
    tiles = row % group == 0 and kv_lora_rank % group == 0 and tk % 128 == 0
    if not interpret and not tiles:
        return (
            f"the TPU's kernel compiler does not tile {pool} (rows and "
            f"their value half in groups of {group}, positions in 128s)"
        )
    return ""


def _kernel(
    # scalar prefetch (SMEM)
    starts_ref,  # int32 [B] cached rows a slot attends: positions [0, start)
    nblk_ref,  # int32 [B] tiles that span covers; 0 for an inactive slot
    par_ref,  # int32 [B] which buffer takes the slot's first tile
    next_ref,  # int32 [B + 1]: [0] the first slot with a tile, [b + 1] the
    # first one after b; B where there is none
    live_ref,  # int32 [B]
    # blocked inputs (VMEM)
    q_ref,  # [1, H, row] compute dtype, `W_kvb`'s key half folded in
    new_ref,  # [1, 1, row] compute dtype: the row the pool does not hold yet
    lat_hbm,  # [2 L, S, row, M] ANY: the pool, read by DMA
    # output
    out_ref,  # [1, H, C]
    # scratch
    buf_ref,  # VMEM [2, row, tk] pool dtype
    sem,  # DMA [2]
    *,
    j: int,
    slot_base: int,
    C: int,
    tk: int,
    scale: float,
):
    B = starts_ref.shape[0]
    i = pl.program_id(0)
    n = nblk_ref[i]
    dtype = q_ref.dtype
    f32 = jnp.float32

    def tile_dma(slot, kb, b):
        return pltpu.make_async_copy(
            lat_hbm.at[j, slot_base + slot, :,
                       pl.ds(pl.multiple_of(kb * tk, tk), tk)],
            buf_ref.at[b], sem.at[b],
        )

    @pl.when(i == 0)
    def _():
        head = next_ref[0]

        @pl.when(head < B)
        def _():
            tile_dma(head, 0, par_ref[head]).start()

    @pl.when(live_ref[i] == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(live_ref[i] != 0)
    def _():
        q = q_ref[0]  # [H, row]
        new = new_ref[0].astype(f32)  # [1, row]
        start = starts_ref[i]
        # the new row's column opens the softmax: its probability is 1 at
        # its own maximum, and its value the accumulator
        m0 = jnp.sum(q.astype(f32) * new, axis=-1, keepdims=True) * scale
        l0 = jnp.ones_like(m0)
        acc0 = jnp.broadcast_to(new[:, :C], (q.shape[0], C))
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
                tile_dma(ahead, jnp.where(more, kb + 1, 0), 1 - b).start()

            tile_dma(i, kb, b).wait()
            tile = buf_ref[b].astype(dtype)  # [row, tk]
            s = jnp.dot(
                q, tile, precision=precision, preferred_element_type=f32
            ) * scale  # [H, tk]
            pos = kb * tk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < start, s, lowest)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            # the probabilities narrowed before the weighted sum, as the
            # copy path narrows them
            pv = jax.lax.dot_general(
                p.astype(dtype), tile[:C], (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=f32,
            )  # [H, C]
            return m_new, l, alpha * acc + pv

        _, l, acc = jax.lax.fori_loop(0, n, one_tile, (m0, l0, acc0))
        out_ref[0] = (acc / l).astype(dtype)


def latent_decode_attention(
    q: jax.Array,  # [B, H, row] compute dtype
    new: jax.Array,  # [B, row] compute dtype, rounded through the pool's
    lat: jax.Array,  # [2 L, S, row, M] the pool leaf
    starts: jax.Array,  # int32 [B] cached rows attended, each < M
    live: jax.Array,  # bool [B]
    *,
    j: int,  # the sublayer
    slot_base: int,  # the block's first slot
    kv_lora_rank: int,
    scale: float,
    block: Optional[int] = None,  # a test's tile width
    interpret: Optional[bool] = None,
):
    """softmax(scale * q . [rows of slot b below starts[b] | new]) times the
    rows' first `kv_lora_rank` entries -> [B, H, kv_lora_rank]; zeros for a
    slot that is not live.  The pool is only read."""
    B, H, R = q.shape
    M = lat.shape[3]
    tk = block or _block(M)
    if not tk or M % tk:
        raise ValueError(f"tiles of {tk} positions do not divide a pool of {M}")
    live = live.astype(jnp.int32)
    nblk = jnp.where(live > 0, (starts + tk - 1) // tk, 0).astype(jnp.int32)
    before = jnp.cumsum(nblk) - nblk
    slots = jnp.arange(B, dtype=jnp.int32)
    following = jax.lax.cummin(jnp.where(nblk > 0, slots, B), reverse=True)
    following = jnp.concatenate([following, jnp.full((1,), B, jnp.int32)])
    interp = _interpret_mode(interpret)
    kernel = functools.partial(
        _kernel, j=j, slot_base=slot_base, C=kv_lora_rank, tk=tk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((1, 1, R), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, kv_lora_rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, R, tk), lat.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, kv_lora_rank), q.dtype),
        interpret=interp,
        name="latent_decode",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ) if not interp else None,
    )(starts.astype(jnp.int32), nblk, (before % 2).astype(jnp.int32),
      following, live, q, new[:, None], lat)
