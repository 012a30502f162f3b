"""A decode step of power retention over the state pool where it lies: each
live slot's state is read ONCE, serves the read-out and the update, and is
written back to the place it came from.

The pool leaf `s` is [L, S, Hkv, F, d] float32 (`models/transformer.py
init_kv_cache`): one [F, d] state a layer, slot and kv head, 4.2 MB at
d = 128.  The plain path (`ops/power_retention.py retention_step` between a
`dynamic_slice` and a `dynamic_update_slice`) reads the block's states
twice a pass, once for `phi(q)^T S` and once for `g S + phi(k) v^T`, and
reads and writes an idle slot's state whole to leave it the same.  This
kernel's grid is the block's slots times the kv heads; the pool is blocked
by one state a step, indexed by the prefetched layer and slot, and ALIASED
to the output, so the layer scan's carry stays one buffer and the pipeline
fetches the next state and writes the last one back while this one
computes.  A state is walked 128 feature rows at a time: the rows give the
read-out's partial `phi(q)[G, rows] @ S[rows]` (float32 accumulator) and
are written back as `g S[rows] + phi(k)[rows, None] v[None, :]`.  An idle
slot's steps point at the state the walk holds anyway (the last live one
before it, or the first live one after), so nothing of it is fetched or
written, and its read-out is zeros.

The features come in as `retention_step` forms them, F on the lanes.  The
MXU takes both products: the read-out with the state's rows as its
right-hand side, split into three bfloat16 parts whose sum is the float32
row (16-bit features: one single-pass product a part, exact in the
features, where the plain path's `einsum` rounds the state to one such part
on the chip), and the outer product `phi(k) v^T` as `diag(phi(k)) @ [v; v;
...]`, one term a sum and exact, which spares a move of `phi(k)` from lanes
to sublanes.  The kernel waits for the state's bytes, not for either
(PERF.md section 6, PR 47).

`z`, 1/128 of the bytes, stays `jax.numpy`.  It shares nothing with
`ops/ragged_decode.py` (pages of K/V columns gathered through a page table)
or `ops/latent_decode.py` (latent rows walked by length) but the
interpreter switch: this one reads and WRITES a state of fixed size.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from areal_tpu.ops.power_retention import EPS, feature_dim, phi
from areal_tpu.ops.ragged_decode import _interpret_mode

# feature rows a product: one lane tile of the features, 16 vregs of state
ROWS = 128
# a state is held four times (two in flight each way), and a fifth of it is
# room for the features and the walk's values; the chip has 128 MiB of VMEM,
# of which a kernel is given 16 by default
VMEM_BYTES = 64 << 20


def retention_refusal(head_dim: int, state_itemsize: int, tp: int = 1) -> str:
    """Why the kernel cannot step a pool of [F, head_dim] states of
    `state_itemsize`-byte values in this process, or "" (`ops/ragged_decode.py
    kernel_refusal`'s sibling: evaluated once at engine init).  The state is
    float32 and every sum into it stays so; under tp the pool is sharded by
    kv head and the kernel is not partitioned.  A test's flag or an explicit
    CPU run interprets the kernel, whatever the widths; any other backend
    has neither (utils/runtime.py kernel_backend).  The chip's kernel
    compiler wants the state's minor axis to fill the 128 lanes, and four
    states beside their features inside the kernel's VMEM."""
    F = feature_dim(head_dim)
    state = f"a state of [{F}, {head_dim}] x {state_itemsize} byte(s) a head"
    if state_itemsize != 4:
        return f"the retention kernel steps a float32 state, not {state}"
    if tp > 1:
        return (
            f"tp={tp} shards the state pool by kv head, and the retention "
            "kernel is not partitioned"
        )
    try:
        interpret = _interpret_mode(None)
    except RuntimeError as e:
        return str(e)
    if not interpret and head_dim % 128:
        return (
            f"the TPU's kernel compiler does not tile {state} (heads of a "
            "multiple of 128 lanes)"
        )
    if not interpret and 5 * F * head_dim * state_itemsize > VMEM_BYTES:
        return (
            f"{state}, two in flight each way, does not fit the retention "
            f"kernel's VMEM budget of {VMEM_BYTES >> 20} MiB"
        )
    return ""


def _kernel(
    # scalar prefetch (SMEM)
    layer_ref,  # int32 [1]
    slot_ref,  # int32 [n] the slot of the block a step's state is: its own,
    # or for an idle slot the one the walk holds (see `_walk`)
    head_ref,  # int32 [n] -1: the step's own head; else the head held
    live_ref,  # int32 [n]
    # blocked inputs (VMEM)
    f_ref,  # [R, Fp] rows 0..G-1 phi(q) of the group, row G phi(k), F on lanes
    vg_ref,  # [2, d] float32: v, and the gate on every lane
    s_ref,  # [F, d] float32: the state, out of the pool
    # outputs
    num_ref,  # [G, d] float32: g phi(q)^T S as it was
    o_ref,  # [F, d]: the state stepped, into the pool (the same buffer)
    *,
    G: int,
):
    b, h = pl.program_id(0), pl.program_id(1)
    F, d = s_ref.shape
    R = f_ref.shape[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    narrow = f_ref.dtype == bf16
    highest = jax.lax.Precision.HIGHEST

    # nobody live (an idle first slot points at itself): every step points
    # at the block's first state, which the pipeline fetches and writes back
    # once; it goes back as it came
    @pl.when((b == 0) & (h == 0) & (live_ref[0] == 0) & (slot_ref[0] == 0))
    def _():
        o_ref[...] = s_ref[...]

    @pl.when(live_ref[b] == 0)
    def _():
        num_ref[...] = jnp.zeros_like(num_ref)

    @pl.when(live_ref[b] != 0)
    def _():
        v = vg_ref[0:1, :]
        g = vg_ref[1:2, :]
        eye = (
            jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
        )
        vb = jnp.broadcast_to(v, (ROWS, d))
        if narrow:
            vb = vb.astype(bf16)  # exact: v came in 16 bits

        def rows_at(r0, rows, acc):
            """Feature rows [r0, r0 + rows) of the state: their part of the
            read-out, and the rows stepped."""
            s = s_ref[pl.ds(r0, rows), :]  # [rows, d]
            f = f_ref[:, pl.ds(r0, ROWS)]  # [R, ROWS]; zeros past F
            fq = f if rows == ROWS else f[:, :rows]
            pk = jnp.broadcast_to(f[G:G + 1, :].astype(f32), (ROWS, ROWS))
            diag = jnp.where(eye, pk, 0.0)
            if narrow:
                # three 16-bit parts sum to the float32 row; single-pass
                # products of 16-bit operands, exact term by term
                rem = s
                for part in range(3):
                    piece = rem.astype(bf16)
                    acc = acc + jnp.dot(fq, piece, preferred_element_type=f32)
                    if part < 2:
                        rem = rem - piece.astype(f32)
                outer = jnp.dot(
                    diag.astype(bf16), vb, preferred_element_type=f32
                )
            else:
                acc = acc + jnp.dot(
                    fq, s, precision=highest, preferred_element_type=f32
                )
                outer = jnp.dot(
                    diag, vb, precision=highest, preferred_element_type=f32
                )
            if rows != ROWS:
                outer = outer[:rows]
            o_ref[pl.ds(r0, rows), :] = g * s + outer
            return acc

        full, tail = divmod(F, ROWS)
        # the loop's body a few products long: the chip's kernel compiler
        # unrolls a loop whole or not at all
        per = next(u for u in (4, 2, 1) if full % u == 0)

        def some(i, acc):
            for u in range(per):
                r0 = pl.multiple_of((i * per + u) * ROWS, ROWS)
                acc = rows_at(r0, ROWS, acc)
            return acc

        acc = jax.lax.fori_loop(0, full // per, some, jnp.zeros((R, d), f32))
        if tail:
            acc = rows_at(full * ROWS, tail, acc)
        num_ref[...] = (g * acc)[:G]


def _walk(live: jax.Array, n_heads: int):
    """Which state each step of the grid points at -> (slot [n], head [n]).
    A live slot's steps walk its own heads (head -1).  An idle slot's steps
    stay on the state the walk holds at that point: the last head of the
    last live slot before it, or, before the first live slot, that slot's
    first head; the pipeline then neither fetches nor writes anything for
    them.  With nobody live they stay on the block's first state."""
    n = live.shape[0]
    slots = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live > 0, slots, -1))
    first = jnp.argmax(live > 0).astype(jnp.int32)
    slot = jnp.where(before >= 0, before, first)
    head = jnp.where(live > 0, -1, jnp.where(before >= 0, n_heads - 1, 0))
    return slot, head.astype(jnp.int32)


def retention_decode_state(
    feats: jax.Array,  # [n, Hkv, G + 1, F]: phi of the query group, then of k
    v: jax.Array,  # [n, Hkv, d]
    g: jax.Array,  # [n, Hkv] float32 gate
    s: jax.Array,  # [L, S, Hkv, F, d] float32: the pool leaf, stepped in place
    live: jax.Array,  # bool [n]
    *,
    layer: jax.Array,  # int32 scalar, traced: the pool rides the layer scan
    slot_base: int,  # the block's first slot
    interpret: Optional[bool] = None,
):
    """-> (g phi(q)^T S as it was [n, Hkv, G, d] float32, zeros for a slot
    that is not live; the pool with the live slots' states of `layer`
    stepped, everything else as it was)."""
    n, Hkv, G1, F = feats.shape
    G = G1 - 1
    d = s.shape[-1]
    f32 = jnp.float32
    if feats.dtype != jnp.bfloat16:
        feats = feats.astype(f32)
    # whole tiles of features: rows in eights, lanes in 128s, zeros past F
    R = -(-G1 // 8) * 8
    Fp = -(-F // ROWS) * ROWS
    feats = jnp.pad(feats, ((0, 0), (0, 0), (0, R - G1), (0, Fp - F)))
    vg = jnp.stack(
        [v.astype(f32), jnp.broadcast_to(g.astype(f32)[..., None], v.shape)],
        axis=2,
    )
    live = live.astype(jnp.int32)
    slot, head = _walk(live, Hkv)
    interp = _interpret_mode(interpret)

    def state_at(b, h, layer_ref, slot_ref, head_ref, live_ref):
        held = head_ref[b]
        return (
            layer_ref[0], slot_base + slot_ref[b],
            jnp.where(held < 0, h, held), 0, 0,
        )

    def own(b, h, *_):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n, Hkv),
        in_specs=[
            pl.BlockSpec((None, None, R, Fp), own),
            pl.BlockSpec((None, None, 2, d), own),
            pl.BlockSpec((None, None, None, F, d), state_at),
        ],
        out_specs=[
            pl.BlockSpec((None, None, G, d), own),
            pl.BlockSpec((None, None, None, F, d), state_at),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, G=G),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, Hkv, G, d), f32),
            jax.ShapeDtypeStruct(s.shape, s.dtype),
        ],
        # operand 6 (after the four prefetched scalars, feats and vg)
        input_output_aliases={6: 1},
        interpret=interp,
        name="retention_decode",
        compiler_params=pltpu.CompilerParams(
            # an idle slot's steps lean on the order of the walk
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES,
        ) if not interp else None,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slot, head, live,
      feats, vg, s)


def retention_decode_step(
    q: jax.Array,  # [n, H, d]
    k: jax.Array,  # [n, Hkv, d]
    v: jax.Array,  # [n, Hkv, d]
    log_g: jax.Array,  # [n, Hkv]
    s: jax.Array,  # [L, S, Hkv, F, d] float32 pool leaf
    z: jax.Array,  # [L, S, Hkv, F] float32 pool leaf
    active: jax.Array,  # bool [n]; False leaves the slot's state
    *,
    layer: jax.Array,  # int32 scalar, traced
    slot_base: int,
    degree: int = 2,
    interpret: Optional[bool] = None,
):
    """`retention_step` for slots [slot_base, slot_base + n) of `layer`,
    against the pool where it lies -> (y [n, H, d] in q's dtype, zeros for
    an idle slot; s; z).  The same identity: the read-out is taken from the
    state as it was, plus the token's own term."""
    n, H, d = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    f32 = jnp.float32
    g = jnp.exp(log_g.astype(f32))  # [n, Hkv]
    # two feature maps, joined for the kernel alone: with `phi(k)` a slice
    # of one map over [q; k] the chip's compiler re-lays the `z` leaf inside
    # the layer scan and its update is a strided copy (a tenth of the cell's
    # tokens/s: PERF.md section 6, PR 47)
    pq = phi(q.reshape(n, Hkv, G, d))  # [n, Hkv, G, F]
    pk = phi(k)  # [n, Hkv, F]
    num, s = retention_decode_state(
        jnp.concatenate([pq, pk[:, :, None]], axis=2), v, g, s, active,
        layer=layer, slot_base=slot_base, interpret=interpret,
    )
    # the normaliser is 1/128 of the bytes: sliced, stepped, written back
    z0 = jax.lax.dynamic_slice(
        z, (layer, slot_base, 0, 0), (1, n) + z.shape[2:]
    )[0]
    own = jnp.einsum("bkgf,bkf->bkg", pq, pk, preferred_element_type=f32)
    num = num + own[..., None] * v.astype(f32)[:, :, None, :]
    den = g[..., None] * jnp.einsum(
        "bkgf,bkf->bkg", pq, z0, preferred_element_type=f32
    ) + own
    c = float(d) ** (-degree / 2.0)
    y = (c * num) / (c * den[..., None] + EPS)
    y = jnp.where(active[:, None, None, None], y, 0.0)
    # an idle slot keeps its normaliser to the bit
    z1 = jnp.where(
        active[:, None, None], g[..., None] * z0 + pk.astype(f32), z0
    )
    z = jax.lax.dynamic_update_slice(z, z1[None], (layer, slot_base, 0, 0))
    return y.reshape(n, H, d).astype(q.dtype), s, z
