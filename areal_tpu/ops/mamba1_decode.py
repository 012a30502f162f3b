"""A decode step of the selective scan over the state pool where it lies:
each LIVE slot's state is read once, gives the new state and the read-out
from the same tile, and is written back to the place it came from.

The pool leaf `s` is [n_ssm, S, N, C] float32 (`models/transformer.py
init_kv_cache`): one [N, C] state a Mamba-1 layer and slot, the channels on
the lanes (320 KB at Jamba's 16 x 5,120).  The plain path (`ops/mamba1.py
selective_step` between a `dynamic_slice` and a `dynamic_update_slice`)
comes out of XLA as one fusion that reads and rewrites the layer's whole
block, an idle row with decay 1 to stay the same, and a second that reads
all of it again for `y`.  Here the pool stays in HBM, ALIASED to the output
(the scans' carry stays one buffer), and the kernel moves states itself: a
grid step holds eight slots' `u`, `dt`, `B | C` and `y` (blocked, so the
pipeline brings them), and walks those of the eight that are live.  A live
slot's state is fetched into one of two buffers while the slot before it
computes, stepped into one of two others, and sent back while the next one
computes; the walk runs on across grid steps, so the only waits without
work are for the first state in and the last two out.  An idle slot costs a
test of a scalar: nothing of it is fetched or written, and its read-out is
zeros.  (A grid step a slot, with the pool blocked by one state and the
pipeline moving it, is 0.3 us a step whoever is live: 10,010 steps a pass
at 385 slots, a third of this kernel's time; PERF.md section 6, PR 53.)

    S'[n, c] = exp(dt[c] A[n, c]) S[n, c] + (dt[c] u[c]) B[n]
    y[c]     = sum_n S'[n, c] C[n] + D[c] u[c]

All of it float32 on the vector unit, as `selective_step` has it: the
products, the exponential of the float32 product, both sums (on the chip
the stepped state and `y` come out bit-identical to the plain path's).
`B` and `C` come in as rows and are turned onto the sublanes by a masked
sum over the lanes, one term a sum and so exact.  The convolution window, a
tenth of the state's bytes, stays `jax.numpy` (`ops/mamba2.py conv_step`).
It shares nothing with `ops/retention_decode.py` (a state of 4.2 MB a head,
which the pipeline moves a grid step at a time, and two products on the
MXU) but the interpreter switch and the rule for an idle slot.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.ragged_decode import _interpret_mode

# slots a grid step holds: the sublanes of a float32 tile of u, dt and y
ROWS = 8
# two states in flight each way, and the tiles of u, dt and y twice beside
# them (a sixth state's worth at 16 columns): the kernel compiler's default
VMEM_BYTES = 16 << 20


def mamba1_refusal(d_inner: int, d_state: int, state_itemsize: int,
                   tp: int = 1) -> str:
    """Why the kernel cannot step a pool of [d_state, d_inner] states of
    `state_itemsize`-byte values in this process, or "" (`ops/ragged_decode.py
    kernel_refusal`'s sibling: evaluated once at engine init).  The state is
    float32 and every sum into it stays so; a hybrid stack runs on one
    device, and the kernel is not partitioned.  A test's flag or an explicit
    CPU run interprets the kernel, whatever the widths; any other backend
    has neither (utils/runtime.py kernel_backend).  The chip's kernel
    compiler wants the channels to fill the 128 lanes and the state columns
    the 8 sublanes, and four states beside their inputs inside the kernel's
    VMEM."""
    state = (f"a state of [{d_state}, {d_inner}] x {state_itemsize} byte(s) "
             "a layer")
    if state_itemsize != 4:
        return f"the selective-scan kernel steps a float32 state, not {state}"
    if tp > 1:
        return (f"tp={tp} shards the state pool, and the selective-scan "
                "kernel is not partitioned")
    try:
        interpret = _interpret_mode(None)
    except RuntimeError as e:
        return str(e)
    if not interpret and (d_inner % 128 or d_state % 8):
        return (
            f"the TPU's kernel compiler does not tile {state} (channels of "
            "a multiple of 128 lanes, state columns of a multiple of 8)"
        )
    if not interpret and 6 * d_state * d_inner * state_itemsize > VMEM_BYTES:
        return (
            f"{state}, two in flight each way, does not fit the "
            f"selective-scan kernel's VMEM budget of {VMEM_BYTES >> 20} MiB"
        )
    return ""


def _kernel(
    # scalar prefetch (SMEM)
    layer_ref,  # int32 [1]
    live_ref,  # int32 [G * ROWS]: the block's slots, then zeros
    next_ref,  # int32 [G * ROWS + 1]: the first live slot at or after i, else n
    rank_ref,  # int32 [G * ROWS]: live slots before i
    # blocked inputs (VMEM)
    u_ref,  # [ROWS, C] float32: the step's eight slots
    dt_ref,  # [ROWS, C] float32
    bc_ref,  # [ROWS, W] float32: B, then C, then zeros to whole lanes
    a_ref,  # [N, C] float32: A transposed, the layer's
    d_ref,  # [1, C] float32
    s_hbm,  # [n_ssm, S, N, C] ANY: the pool, read by DMA
    # outputs
    y_ref,  # [ROWS, C] float32
    o_hbm,  # the pool again (the same buffer), written by DMA
    # scratch
    in_ref,  # VMEM [2, N, C] float32: states as they came
    out_ref,  # VMEM [2, N, C] float32: states stepped
    in_sem,  # DMA [2]
    out_sem,  # DMA [2]
    *,
    n: int,
    slot_base: int,
    tile: int,
):
    g = pl.program_id(0)
    _, N, C = in_ref.shape
    W = bc_ref.shape[1]
    layer = layer_ref[0]

    def fetch(slot, p):
        return pltpu.make_async_copy(
            s_hbm.at[layer, slot_base + slot], in_ref.at[p], in_sem.at[p])

    def put_back(slot, p):
        return pltpu.make_async_copy(
            out_ref.at[p], o_hbm.at[layer, slot_base + slot], out_sem.at[p])

    @pl.when(g == 0)
    def _():
        head = next_ref[0]

        @pl.when(head < n)
        def _():
            fetch(head, 0).start()

    y_ref[...] = jnp.zeros_like(y_ref)

    def one_slot(r, _):
        i = g * ROWS + r

        @pl.when(live_ref[i] != 0)
        def _():
            k = rank_ref[i]
            p = k % 2
            # the next live slot's state travels while this one computes
            ahead = next_ref[i + 1]

            @pl.when(ahead < n)
            def _():
                fetch(ahead, 1 - p).start()

            fetch(i, p).wait()

            # the state stepped two live slots ago has left this buffer
            @pl.when(k >= 2)
            def _():
                put_back(i, p).wait()

            # B and C from the lanes of a row onto the sublanes: one term a
            # sum
            bc = jnp.broadcast_to(bc_ref[pl.ds(r, 1), :], (N, W))
            at = jax.lax.broadcasted_iota(jnp.int32, (N, W), 1)
            own = jax.lax.broadcasted_iota(jnp.int32, (N, W), 0)
            Bn = jnp.sum(jnp.where(at == own, bc, 0.0), axis=1, keepdims=True)
            Cn = jnp.sum(
                jnp.where(at == own + N, bc, 0.0), axis=1, keepdims=True)
            for c0 in range(0, C, tile):
                at_c = pl.ds(c0, tile)
                u = u_ref[pl.ds(r, 1), at_c]  # [1, tile]
                dt = dt_ref[pl.ds(r, 1), at_c]
                S = (jnp.exp(dt * a_ref[:, at_c]) * in_ref[p, :, at_c]
                     + (dt * u) * Bn)
                out_ref[p, :, at_c] = S
                y_ref[pl.ds(r, 1), at_c] = (
                    jnp.sum(S * Cn, axis=0, keepdims=True)
                    + d_ref[:, at_c] * u
                )
            put_back(i, p).start()

        return 0

    jax.lax.fori_loop(0, ROWS, one_slot, 0)

    # the last two states stepped are on their way back when the grid ends
    @pl.when(g == pl.num_programs(0) - 1)
    def _():
        total = rank_ref[g * ROWS + ROWS - 1] + live_ref[g * ROWS + ROWS - 1]

        @pl.when(total >= 1)
        def _():
            put_back(0, (total - 1) % 2).wait()

        @pl.when(total >= 2)
        def _():
            put_back(0, total % 2).wait()


def selective_decode_step(
    u: jax.Array,  # [n, C]
    dt: jax.Array,  # [n, C] after softplus
    A: jax.Array,  # [C, N]
    Bm: jax.Array,  # [n, N]
    Cm: jax.Array,  # [n, N]
    D: jax.Array,  # [C]
    s: jax.Array,  # [n_ssm, S, N, C] float32: the pool leaf, stepped in place
    active: jax.Array,  # bool [n]; False leaves the slot's state
    *,
    layer: jax.Array,  # int32 scalar, traced
    slot_base: int,  # the block's first slot
    interpret: Optional[bool] = None,
):
    """`selective_step` for slots [slot_base, slot_base + n) of `layer`,
    against the pool where it lies -> (y [n, C] in u's dtype, zeros for an
    idle slot; the pool with the live slots' states of `layer` stepped,
    everything else as it was)."""
    n, C = u.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    G = -(-n // ROWS)
    live = jnp.pad(active.astype(jnp.int32), (0, G * ROWS - n))
    slots = jnp.arange(G * ROWS, dtype=jnp.int32)
    following = jax.lax.cummin(jnp.where(live > 0, slots, n), reverse=True)
    following = jnp.concatenate([following, jnp.full((1,), n, jnp.int32)])
    rank = (jnp.cumsum(live) - live).astype(jnp.int32)
    interp = _interpret_mode(interpret)
    # B | C a slot, in whole lanes
    W = -(-2 * N // 128) * 128
    bc = jnp.concatenate([Bm.astype(f32), Cm.astype(f32)], axis=-1)
    bc = jnp.pad(bc, ((0, 0), (0, W - 2 * N)))
    # channels a product: eight vregs of state at 16 columns
    tile = next((t for t in (512, 256, 128) if C % t == 0), C)

    def rows(g, *_):
        return (g, 0)

    def whole(g, *_):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G,),
        in_specs=[
            pl.BlockSpec((ROWS, C), rows),
            pl.BlockSpec((ROWS, C), rows),
            pl.BlockSpec((ROWS, W), rows),
            pl.BlockSpec((N, C), whole),
            pl.BlockSpec((1, C), whole),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=[
            pl.BlockSpec((ROWS, C), rows),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, N, C), f32),
            pltpu.VMEM((2, N, C), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, s = pl.pallas_call(
        functools.partial(_kernel, n=n, slot_base=slot_base, tile=tile),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, C), f32),
            jax.ShapeDtypeStruct(s.shape, s.dtype),
        ],
        # operand 9 (after the four prefetched scalars, u, dt, B | C, A, D)
        input_output_aliases={9: 1},
        interpret=interp,
        name="mamba1_decode",
        compiler_params=pltpu.CompilerParams(
            # a state's fetch is started a live slot ahead: the walk's order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES,
        ) if not interp else None,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live, following, rank,
      u.astype(f32), dt.astype(f32), bc, A.astype(f32).T,
      D.astype(f32)[None, :], s)
    return y.astype(u.dtype), s
