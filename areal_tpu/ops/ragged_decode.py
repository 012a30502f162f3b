"""Ragged paged-decode attention: one Pallas program per slot grid.

Role counterpart of vLLM's PagedAttention / SGLang's ragged decode kernels
(PAPERS.md; SNIPPETS [1] shows the `pallas_call` + `shard_map` idiom this
module follows).  The dense decode path (`models/transformer.py
forward_decode`) pays three XLA ops per layer — a scatter append, a
row gather, and a [B, K] bucketed matmul over the tier's FULL K bucket —
for every slot, long or short.  This kernel fuses all three into one
program over the slot grid and makes the KV *read* ragged: each slot DMAs
only the `ceil((length + T) / page)` pages its occupied span covers out of
its page-table row, so HBM traffic tracks per-slot occupancy instead of
the cohort ceiling, and the per-tier dispatch fan-out collapses to a
single program (`gen/engine.py step`).

Generalised query tile: `T = 1` is plain decode; `T = D + 1` scores the
pending token plus D speculative drafts in the same kernel — verification
rides the decode read for free, which is what lets the engine collapse
decode + verify into one dispatch per step.

Exactness discipline: the kernel is BIT-IDENTICAL
to the dense bucketed path, not merely close.  A classic online-softmax
accumulation (rescale by exp(m_old - m_new) per visiting page) cannot be —
its division/rescale order differs from `jax.nn.softmax` — so the kernel
instead gathers the occupied pages into a VMEM scratch of the static
K-bucket width and then applies the op sequence of
`ops/attention.py naive_attention` (per kv head: scores dot with a float32
accumulator -> scale -> softcap -> mask -> softmax -> output dot).  Masked
tail columns have their scores replaced and carry exact-zero softmax mass
(exp(MASK_VALUE - max) underflows to 0.0), and the scratch past a slot's
span holds zeros or an earlier slot's cache columns, finite either way
(zeroed once a call), which contribute exact zeros to the output
contraction, so the page-windowed result equals the full-bucket result
bit-for-bit — the same width-invariance the dense windowed path already
relies on.  The bandwidth win survives: reads drop from K to the occupied
span; only the compute shape stays at K.

What a pass costs is the pages' bytes (PERF.md, PR 33): the scratch is two
windows, and slot i + 1's pages are in flight while slot i computes; the
heads' scores are stacked so that scale, mask and softmax run once over
full vector registers, not once a head over a tile of `T * group` rows.

What the TPU's kernel compiler takes decides the form (PR 21; it refused
the first version outright): dots are 2-D with a float32 accumulator, so
the caller hands `q` over head-major (`[B, Hkv, T * group, hd]`, one tile
of query rows per kv head) and the cache — position-major, kv heads
interleaved, never copied — is split per head by a strided load from the
scratch (a uint32 view for 16-bit caches, whose two heads of one position
share a sublane).  `naive_attention` computes the same two dots in the
same batch-leading form, which is what keeps both sides equal on XLA:CPU
too, where a dot's rounding follows its operand layout.

The K/V append write is fused in: new keys/values are DMA'd into the
slot's page-table row at its write positions (index M = scatter-drop,
mirroring the dense path's idle-slot/overflow clamp) and stored at the
same positions of the gathered window, which then holds what the dense
write-then-read order would have read back.

`INTERPRET` (or a run started with `JAX_PLATFORMS=cpu`) runs the SAME
kernel through the Pallas interpreter, so CPU tier-1 tests exercise the
real program, not a shadow implementation.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from areal_tpu.ops.attention import MASK_VALUE, MIN_KEY_COLUMNS
from areal_tpu.utils.runtime import kernel_backend

# Tests may force interpret mode explicitly.  An explicit CPU run
# (`JAX_PLATFORMS=cpu`) interprets too: the kernel is the only decode path
# when ragged_attn is on, so the CPU suite must execute it.  Any other
# non-TPU backend raises (utils/runtime.py kernel_backend).
INTERPRET = False

# VMEM budget for ONE slot's K and V window (two [K, Hkv, hd] buffers; the
# kernel holds two such pairs, this slot's and the next one's, and asks
# the compiler for that much plus 8 MB).  Engines whose worst-case bucket
# would overflow this take the copy path at init (gen/engine.py).
RAGGED_VMEM_BYTES = 8 << 20


def _interpret_mode(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return kernel_backend(INTERPRET) != "tpu"


def ragged_supported(
    max_key_window: int,
    num_kv_heads: int,
    head_dim: int,
    kv_itemsize: int,
    tp: int = 1,
) -> bool:
    """The VMEM gate: the worst-case (max bucket) K/V scratch for one slot
    must fit the budget."""
    hkv = max(1, num_kv_heads // max(1, tp))
    scratch = 2 * max_key_window * hkv * head_dim * kv_itemsize
    return scratch <= RAGGED_VMEM_BYTES


def kernel_refusal(
    max_key_window: int,
    num_kv_heads: int,
    head_dim: int,
    kv_itemsize: int,
    tp: int = 1,
) -> str:
    """Why the kernel cannot serve an engine of this window and these heads
    in this process, or "": evaluated once at engine init, so the
    dispatch-site flag is engine-lifetime config (areal-lint C6 value
    lattice).  The window must pass the VMEM gate.  `_heads` splits a 2-
    or 4-byte cache, and a 16-bit one by pairs of heads.  A TPU lowers the
    kernel, and its compiler takes what it can tile (compiled for a
    described v5e, PR 33): heads of 128 lanes, a power of two of them a
    shard, and at least two of a 16-bit cache, whose two heads of one
    position share a sublane.  A test's flag or an explicit CPU run
    interprets it, whatever the widths; any other backend has neither
    (utils/runtime.py kernel_backend)."""
    hkv = max(1, num_kv_heads // max(1, tp))
    heads = f"{hkv} kv head(s) x {head_dim} of {kv_itemsize} byte(s)"
    if not ragged_supported(
        max_key_window, num_kv_heads, head_dim, kv_itemsize, tp
    ):
        return (
            f"a {max_key_window}-column window of {heads} does not fit the "
            "kernel's VMEM budget (ops/ragged_decode.py ragged_supported): "
            "lower max_seq_len or drop ragged_attn"
        )
    if kv_itemsize not in (2, 4) or (kv_itemsize == 2 and hkv > 1 and hkv % 2):
        return (
            "the kernel splits the heads of a 2- or 4-byte cache (a 16-bit "
            f"one by pairs), not {heads}"
        )
    try:
        interpret = _interpret_mode(None)
    except RuntimeError as e:
        return str(e)
    tiles = head_dim == 128 and hkv & (hkv - 1) == 0 and hkv * kv_itemsize >= 4
    if not interpret and not tiles:
        return (
            f"the TPU's kernel compiler does not tile {heads} (heads of 128, "
            "a power of two of them, two or more of a 16-bit cache)"
        )
    return ""


def _heads(flat_ref, base, n_heads: int, K: int):
    """Yields each kv head's rows, `(h, [K, hd] value)`, out of a window of
    the flat `[rows, hd]` view of the scratch (position-major, heads
    interleaved — the cache's own layout; the window starts at row `base`,
    a multiple of `n_heads`).  32-bit caches take a sublane-strided load.
    A 16-bit cache packs two consecutive rows into one 32-bit sublane,
    which the strided load cannot split, so a pair of heads is read once
    as uint32 words and each half is moved into the high half of a float32
    — an exact bfloat16 -> float32 widening (the idiom of the upstream TPU
    ragged_paged_attention kernel)."""
    if n_heads == 1:
        yield 0, flat_ref[pl.ds(base, K), :]
    elif flat_ref.dtype.itemsize == 4:
        for h in range(n_heads):
            yield h, flat_ref[pl.ds(base + h, K, stride=n_heads), :]
    else:
        pairs = flat_ref.bitcast(jnp.uint32)  # [rows // 2, hd]
        half = n_heads // 2
        for pair in range(half):
            start = base // 2 + pair
            word = pairs[pl.ds(start, K), :] if half == 1 else pairs[
                pl.ds(start, K, stride=half), :]
            for h, bits in (
                (2 * pair, word << 16),
                (2 * pair + 1, word & jnp.uint32(0xFFFF0000)),
            ):
                yield h, pltpu.bitcast(bits, jnp.float32).astype(jnp.bfloat16)


def _kernel(
    # scalar prefetch (SMEM)
    rows_ref,  # int32 [B] physical cache row per slot (page table)
    npages_ref,  # int32 [B] full pages the slot's span covers
    tail_ref,  # int32 [B] 1 -> also copy the static tail (K % page != 0)
    widx_ref,  # int32 [B, T] write positions; M = scatter-drop
    # blocked inputs (VMEM)
    q_ref,  # [1, Hkv, T * group, hd] compute dtype, head-major
    kn_ref,  # [1, T, Hkv, hd] kv dtype (pre-cast: write-then-read order)
    vn_ref,  # [1, T, Hkv, hd]
    mask_ref,  # int32 [1, T, Kc] attended-position mask
    ck_hbm,  # [S, M, Hkv, hd] ANY — full cache, read via DMA
    cv_hbm,
    # outputs
    out_ref,  # [1, Hkv, T * group, hd]
    ck_out,  # aliased with ck_hbm (in-place append)
    cv_out,
    # scratch
    ks_ref,  # VMEM [2, Kc, Hkv, hd] kv dtype: this slot's window, the next's
    vs_ref,
    s_ref,  # VMEM float32 [Hkv * T * group, Kc]: scores, then softmax
    gather_sem,  # DMA [2]: one a window buffer
    write_sem,  # DMA: the append
    *,
    T: int,
    K: int,  # gathered window: cache columns [0, K)
    M: int,
    page: int,
    group: int,
    hd: int,
    logit_softcap: Optional[float],
):
    Kc = ks_ref.shape[1]  # compute width: max(K, MIN_KEY_COLUMNS)
    i = pl.program_id(0)
    buf = i % 2
    n_full = K // page
    tail = K - n_full * page
    caches = ((ck_out, ks_ref, kn_ref), (cv_out, vs_ref, vn_ref))

    def gather(slot, b, start: bool):
        """The occupied pages of `slot`'s page-table row into window buffer
        `b`: every page DMA goes out before anybody waits (equal-sized
        copies share the buffer's one semaphore), and the waits are issued
        a grid step later, when the slot computes."""
        row = rows_ref[slot]

        def page_dma(p, _):
            for hbm, scr, _new in caches:
                cp = pltpu.make_async_copy(
                    hbm.at[row, pl.ds(p * page, page)],
                    scr.at[b, pl.ds(p * page, page)],
                    gather_sem.at[b],
                )
                cp.start() if start else cp.wait()
            return 0

        jax.lax.fori_loop(0, npages_ref[slot], page_dma, 0)
        if tail:
            # K not page-aligned (key_window == max_seq_len off the pow2
            # ladder): the remainder is a STATIC slice, copied when the
            # span reaches past the last full page
            @pl.when(tail_ref[slot] > 0)
            def _():
                for hbm, scr, _new in caches:
                    cp = pltpu.make_async_copy(
                        hbm.at[row, pl.ds(n_full * page, tail)],
                        scr.at[b, pl.ds(n_full * page, tail)],
                        gather_sem.at[b],
                    )
                    cp.start() if start else cp.wait()

    # Columns past a slot's span are masked: their scores are replaced
    # (whatever the K buffer holds there) and their softmax mass is an
    # exact zero, so V may hold any FINITE value there and the output
    # contraction still adds exact zeros: the page-windowed result equals
    # the full-bucket result bit for bit.  Zeroing both buffers once a
    # call is enough: afterwards they hold zeros or an earlier slot's
    # cache columns.
    @pl.when(i == 0)
    def _():
        ks_ref[...] = jnp.zeros_like(ks_ref)
        vs_ref[...] = jnp.zeros_like(vs_ref)
        gather(0, 0, start=True)

    # the next slot's pages travel while this slot computes
    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        gather(i + 1, 1 - buf, start=True)

    gather(i, buf, start=False)

    # fused append: the new K/V goes to the page-table row (HBM) and, at
    # the same index, into the gathered window, which then holds what the
    # dense path's write-then-read order would have read back.  widx == M
    # is the dense scatter-drop sentinel (idle slot / padding position of
    # a short draft): nothing is written.  Page-table rows are distinct,
    # so the next slot's gather, already in flight, reads no row this
    # append touches.
    row = rows_ref[i]

    def append(t):
        return [
            pltpu.make_async_copy(
                new.at[0, pl.ds(t, 1)], hbm.at[row, pl.ds(widx_ref[i, t], 1)],
                write_sem,
            )
            for hbm, _scr, new in caches
        ]

    for t in range(T):
        wi = widx_ref[i, t]

        @pl.when(wi < M)
        def _():
            for cp in append(t):
                cp.start()

        @pl.when(wi < K)
        def _():
            for _hbm, scr, new in caches:
                scr[buf, pl.ds(wi, 1)] = new[0, pl.ds(t, 1)]

    # naive_attention's op order (ops/attention.py), in the form the TPU's
    # compiler takes: per kv head, 2-D dots with a float32 accumulator over
    # that head's [T * group] query rows (the wrapper hands q over head-
    # major).  The accumulator is rounded to the compute dtype exactly
    # where the dense einsum rounds its result, so a bfloat16 model sees
    # the same scores and outputs.
    dtype = q_ref.dtype
    n_kv = ks_ref.shape[2]
    ks_flat = ks_ref.reshape(2 * Kc * n_kv, hd)
    vs_flat = vs_ref.reshape(2 * Kc * n_kv, hd)
    base = pl.multiple_of(buf * (Kc * n_kv), Kc * n_kv)
    # query row r = t * group + g attends what position t may: an exact
    # row select over the [T, K] mask block
    mask = mask_ref[0, pl.ds(0, 1), :]
    if T > 1:
        r = jax.lax.broadcasted_iota(jnp.int32, (T * group, Kc), 0)
        for t in range(1, T):
            mask = jnp.where(r >= t * group, mask_ref[0, pl.ds(t, 1), :], mask)
    mask = mask != 0
    contract_last = (((1,), (1,)), ((), ()))
    # bfloat16 operands have one precision; naming it keeps a process-wide
    # jax_default_matmul_precision (the CPU suite sets "highest") from
    # asking Mosaic for a multi-pass product of 16-bit inputs
    precision = jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None
    # every head's scores first, stacked head-major in one [Hkv * rows, Kc]
    # block, so that scale, mask and softmax run once over full vector
    # registers instead of once a head over a tile of `rows` (2 for a
    # decode step of Qwen3-0.6B) sublanes; row by row it is the same
    # arithmetic
    R = T * group
    for h, k in _heads(ks_flat, base, n_kv, Kc):
        s_ref[pl.ds(h * R, R), :] = jax.lax.dot_general(
            q_ref[0, h], k.astype(dtype), contract_last, precision=precision,
            preferred_element_type=jnp.float32,
        )
    scores = s_ref[...] * (1.0 / np.sqrt(hd))
    if logit_softcap:
        # barrier-pinned to match naive_attention exactly — see the
        # twin comment there (the simplifier otherwise merges the
        # scale/softcap constants differently per compilation context)
        scores = jax.lax.optimization_barrier(scores)
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
        scores = jax.lax.optimization_barrier(scores)
    if T > 1:
        mask = jnp.tile(mask, (n_kv, 1))  # the same rows under every head
    scores = jnp.where(mask, scores, MASK_VALUE)
    s_ref[...] = jax.nn.softmax(scores, axis=-1)
    for h, v in _heads(vs_flat, base, n_kv, Kc):
        out_ref[0, h] = jnp.dot(
            s_ref[pl.ds(h * R, R), :].astype(dtype), v.astype(dtype),
            precision=precision, preferred_element_type=jnp.float32,
        ).astype(dtype)

    # the append has left its VMEM block before the pipeline refills it
    for t in range(T):
        @pl.when(widx_ref[i, t] < M)
        def _():
            for cp in append(t):
                cp.wait()


def _ragged_call(
    q, k_new, v_new, ck, cv, rows, npages, tail, widx, mask,
    *, K: int, page: int, logit_softcap: Optional[float], interpret: bool,
):
    B, T, Hq, hd = q.shape
    Hkv = ck.shape[2]
    M = ck.shape[1]
    group = Hq // Hkv
    Kc = mask.shape[-1]
    kernel = functools.partial(
        _kernel,
        T=T, K=K, M=M, page=page, group=group, hd=hd,
        logit_softcap=logit_softcap,
    )
    # q heads are kv-major: [B, T, Hkv, group, hd] -> [B, Hkv, T * group, hd]
    # puts each kv head's query rows in one leading-indexed 2-D tile
    q_heads = q.reshape(B, T, Hkv, group, hd).transpose(0, 2, 1, 3, 4)
    q_heads = q_heads.reshape(B, Hkv, T * group, hd)
    head_block = pl.BlockSpec((1, Hkv, T * group, hd), lambda i, *_: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            head_block,
            pl.BlockSpec((1, T, Hkv, hd), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, T, Hkv, hd), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, T, Kc), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=[
            head_block,
            pl.BlockSpec(memory_space=pltpu.ANY),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, Kc, Hkv, hd), ck.dtype),
            pltpu.VMEM((2, Kc, Hkv, hd), cv.dtype),
            pltpu.VMEM((Hkv * T * group, Kc), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q_heads.shape, q.dtype),
            jax.ShapeDtypeStruct(ck.shape, ck.dtype),
            jax.ShapeDtypeStruct(cv.shape, cv.dtype),
        ],
        # operand indices INCLUDE the scalar-prefetch args: q=4 ... ck=8,
        # cv=9; the cache updates in place (the dense path's donated-scan
        # analogue)
        input_output_aliases={8: 1, 9: 2},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two window buffers each for K and V, and room for a head's
            # widened rows and the pipeline's blocks
            vmem_limit_bytes=4 * Kc * Hkv * hd * ck.dtype.itemsize + (8 << 20),
        ) if not interpret else None,
    )
    out, ck, cv = fn(
        rows, npages, tail, widx, q_heads, k_new, v_new, mask, ck, cv
    )
    out = out.reshape(B, Hkv, T, group, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, Hq, hd), ck, cv


def ragged_paged_attention(
    q: jax.Array,  # [B, T, Hq, hd] compute dtype (rope already applied)
    k_new: jax.Array,  # [B, T, Hkv, hd] kv dtype (caller casts — the
    v_new: jax.Array,  # dense path rounds through the cache dtype too)
    ck: jax.Array,  # [S_total, M, Hkv, hd] cache keys (one layer)
    cv: jax.Array,
    rows: jax.Array,  # int32 [B] physical row per slot (page table)
    lengths: jax.Array,  # int32 [B] cache fill per slot
    widx: jax.Array,  # int32 [B, T] write positions; M = drop
    mask: jax.Array,  # bool [B, T, K] attended cache positions
    *,
    key_window: int,  # STATIC bucketed compute width (K)
    page_size: int,  # STATIC page granularity (the prompt-bucket quantum)
    logit_softcap: Optional[float] = None,
    mesh: Optional[Mesh] = None,  # tp>1: shard kv heads via shard_map
    interpret: Optional[bool] = None,
):
    """Fused ragged decode/verify attention for one layer of a slot grid.

    Returns `(attn_out [B, T, Hq, hd], ck, cv)` with the new K/V appended
    into the cache — bit-identical to the dense sequence
    ``set -> take -> naive_attention`` over the same `key_window`, while
    reading only each slot's occupied pages.  `T = 1` is decode; `T > 1`
    is speculative verification (same program family, wider query tile).
    """
    B, T = q.shape[:2]
    M = ck.shape[1]
    K = min(key_window, M)
    page = min(page_size, K)
    n_full = K // page
    # span covers every attended/written position: cache cols [0, len + T)
    # and, whatever the caller's write positions, every one of them (the
    # kernel writes to HBM first and reads the new rows back with the span)
    written = jnp.max(jnp.where(widx < M, widx + 1, 0), axis=1)
    span = jnp.minimum(jnp.maximum(lengths + T, written), K)
    npages = jnp.minimum((span + page - 1) // page, n_full).astype(jnp.int32)
    tail = (span > n_full * page).astype(jnp.int32)
    # the score matrix keeps at least MIN_KEY_COLUMNS columns, like the
    # dense path's (ops/attention.py): a narrower window computes over
    # masked zero columns
    mask_i32 = jnp.pad(
        mask.astype(jnp.int32),
        ((0, 0), (0, 0), (0, max(K, MIN_KEY_COLUMNS) - K)),
    )
    interp = _interpret_mode(interpret)
    call = functools.partial(
        _ragged_call, K=K, page=page, logit_softcap=logit_softcap,
        interpret=interp,
    )
    if mesh is None or mesh.shape.get("tp", 1) <= 1:
        return call(
            q, k_new, v_new, ck, cv, rows, npages, tail, widx, mask_i32
        )
    # tp>1 serving path (SNIPPETS [1] pattern): kv heads ride the mesh's
    # tp axis exactly as the engine's cache sharding lays them out; q
    # heads are kv-major so the same split keeps each query group with
    # its kv head.  Per-shard compute is the identical op sequence, so
    # bit-identity holds shard-locally and the concat restores the dense
    # layout.
    kvs = P(None, None, "tp", None)
    return jax.shard_map(
        call,
        mesh=mesh,
        in_specs=(
            kvs,  # q [B, T, Hq, hd] — kv-major head split
            kvs,  # k_new
            kvs,  # v_new
            kvs,  # ck [S, M, Hkv, hd]
            kvs,  # cv
            P(None),  # rows
            P(None),  # npages
            P(None),  # tail
            P(None, None),  # widx
            P(None, None, None),  # mask
        ),
        out_specs=(kvs, kvs, kvs),
        check_vma=False,
    )(q, k_new, v_new, ck, cv, rows, npages, tail, widx, mask_i32)
