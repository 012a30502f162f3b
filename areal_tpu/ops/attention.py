"""Segment-masked attention with a Pallas splash-attention fast path.

Role counterpart of the reference's flash-attn varlen attention
(realhf/impl/model/modules/attn.py:307: flash_attn_varlen_func over packed
cu_seqlens batches) and of the SDPA fallback in lite's HF models.  TPU-first
design differences:

- Packed variable-length batches are expressed with **segment ids** (-1 =
  padding), not cu_seqlens; causality is by buffer index, which equals
  per-segment position order because packed segments are contiguous.
- The fast path is the TPU splash-attention Pallas kernel
  (`jax.experimental.pallas.ops.tpu.splash_attention`): blockwise online
  softmax, never materialises the [T, S] score matrix, and skips fully-masked
  key blocks — the property that makes 32k-context training feasible where
  the naive einsum path's O(T^2) memory is hopeless.
- GQA runs the MQA kernel vmapped over kv heads (q grouped per kv head).
- The kernel's skip decision reads a per-block mask.  Upstream builds it
  from the STATIC causal (or windowed) mask and applies segment ids inside a
  block, after its scores are computed.  A device that holds ONE packed row
  narrows that block mask, inside the traced step, by the row's segment ids
  (`block_overlap`, `_narrow_mask_info`): a block runs only where its
  queries and keys share a sequence (288 of the 528 causal blocks of a
  16,384 row holding 8,682 + 7,442).  What it rests on: segments are
  contiguous and their ids rise along the row, padding is -1 (kept as a
  last segment of its own); under any other order the interval test only
  drops fewer blocks, never a needed one.  A block left out added exact
  zeros, so the bits do not change.  `block_counts` says how often it
  engages: the train step returns it as `attn_blocks_run` /
  `attn_blocks_causal`.  More rows than one keep the static mask
  (`_splash_rows` says why).
- Under a `jax.sharding.Mesh` the kernel is wrapped in `shard_map`: batch
  rows over (dp, fsdp), kv heads over tp, and the **query sequence over sp**
  (the kernel is built with q_seq_shards so its block schedule stays
  causal-load-balanced).  K/V stay whole along the sequence — GSPMD inserts
  the all-gather — which is the DeepSpeed-Ulysses memory regime the
  reference gets from areal/utils/ulysses.py.
- The naive einsum path remains for CPU tests, odd head dims, and as the
  numerical reference; both paths share one public entry point.
"""
# areal-lint: hot-path

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _sk,
)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as _sm,
)

from areal_tpu.utils import logging
from areal_tpu.utils.runtime import kernel_backend

logger = logging.getLogger("ops.attention")

MASK_VALUE = -2.3819763e38

# The windowed decode paths (key-window buckets, length-cohort tiers, the
# ragged kernel) rely on attention over a wider zero-masked window giving
# the same bits as over a narrower one.  XLA:CPU computes a dot with fewer
# than 64 output columns by a different routine with different rounding,
# so the score matrix never has fewer: narrower windows are padded with
# masked zero keys.  On the TPU the lane tile is 128 and this costs nothing.
MIN_KEY_COLUMNS = 64

# (T, Hq, Hkv, hd) -> "splash" | "einsum" | "ring": what each traced
# training/forward program took, so the choice between the kernel and the
# O(T^2) einsum is never silent
_IMPLS_TAKEN: Dict[Tuple[int, int, int, int], str] = {}


def record_impl(impl: str, T: int, Hq: int, Hkv: int, hd: int) -> None:
    """Called at trace time by the model's forward: note, and log once,
    which attention implementation the program being compiled took."""
    key = (T, Hq, Hkv, hd)
    if _IMPLS_TAKEN.get(key) != impl:
        _IMPLS_TAKEN[key] = impl
        logger.info(
            f"attention: {impl} for T={T} Hq={Hq} Hkv={Hkv} hd={hd}"
        )


def implementations_taken() -> Dict[Tuple[int, int, int, int], str]:
    return dict(_IMPLS_TAKEN)


@jax.custom_jvp
def _pin(x: jax.Array) -> jax.Array:
    """Identity that lowers to `lax.optimization_barrier`, with a pass-through
    tangent: the barrier has no differentiation rule on the installed jaxlib,
    and the bit-identity contract it protects (see `naive_attention`) only
    covers the inference forward — training gradients flow through the
    unbarriered graph unchanged."""
    return jax.lax.optimization_barrier(x)


@_pin.defjvp
def _pin_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    return jax.lax.optimization_barrier(x), t


# Tests flip this to run the Pallas kernels in interpret mode on the CPU
# mesh — the only way to exercise the sharded splash path without 8 chips.
# Nothing else turns interpret mode on (utils/runtime.py kernel_backend).
INTERPRET = False


# ---------------------------------------------------------------------------
# Naive reference path (CPU fallback + numerics oracle)
# ---------------------------------------------------------------------------


def make_attention_mask(
    segment_ids: jax.Array,
    positions: jax.Array,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """[B, T] segment ids (-1 = pad) -> bool [B, 1, T, T] mask.

    Causality is by *position within the segment*, so packed layouts where
    each sequence restarts positions at 0 are handled uniformly with padded
    layouts (positions strictly increase inside a segment).
    """
    seg_q = segment_ids[:, :, None]
    seg_k = segment_ids[:, None, :]
    same = (seg_q == seg_k) & (seg_q >= 0)
    pos_q = positions[:, :, None]
    pos_k = positions[:, None, :]
    causal = pos_k <= pos_q
    mask = same & causal
    if sliding_window is not None:
        mask &= pos_k > pos_q - sliding_window
    return mask[:, None, :, :]


def naive_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, S, Hkv, hd]
    v: jax.Array,  # [B, S, Hkv, hd]
    mask: jax.Array,  # bool [B, 1, T, S]
    logit_softcap: Optional[float] = None,
    sinks: Optional[jax.Array] = None,  # [Hq]
) -> jax.Array:
    """Grouped-query attention with fp32 softmax. Returns [B, T, Hq, hd]
    (the value's width where it is narrower than the key's).  `sinks`: one
    learned scalar a query head that joins the softmax as a key of its own
    and adds no value: it takes mass, the other weights sum to less than 1.

    Both contractions are batched over (batch, kv head) with that head's
    `T * group` query rows as the matrix rows — operands head-major, batch
    dimensions leading.  `ops/ragged_decode.py` runs the same two dots per
    kv head inside its Pallas kernel (the only contraction layout the TPU's
    kernel compiler takes), which is what keeps the two paths bit-identical.
    """
    B, T, Hq, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if S < MIN_KEY_COLUMNS:
        pad = ((0, 0), (0, MIN_KEY_COLUMNS - S), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        mask = jnp.pad(mask, ((0, 0),) * (mask.ndim - 1) + pad[1:2])
        S = MIN_KEY_COLUMNS
    q = q.reshape(B, T, Hkv, group, hd).transpose(0, 2, 1, 3, 4)
    q = q.reshape(B, Hkv, T * group, hd)
    k = k.transpose(0, 2, 1, 3)  # [B, Hkv, S, hd]
    v = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum(
        "bkmh,bksh->bkms", q, k, preferred_element_type=jnp.float32
    )
    scores *= 1.0 / np.sqrt(hd)
    if logit_softcap:
        # barrier-pinned: XLA's algebraic simplifier merges the scale /
        # softcap constants differently depending on the surrounding
        # graph, which breaks the bit-identity contract between this
        # dense path and the ragged Pallas kernel (ops/ragged_decode.py
        # pins the same literal sequence).  The barriers force the
        # written div/tanh/mul order in every compilation context.
        scores = _pin(scores)
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
        scores = _pin(scores)
    mask = mask[:, :, :, None, :] if mask.ndim == 4 else mask  # [B,1,T,1,S]
    scores = jnp.where(mask, scores.reshape(B, Hkv, T, group, S), MASK_VALUE)
    if sinks is not None:
        # the sink as one more column of scores, dropped after the softmax
        col = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, Hkv, 1, group, 1),
            (B, Hkv, T, group, 1),
        )
        scores = jnp.concatenate([scores, col], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    if sinks is not None:
        probs = probs[..., :S]
    probs = probs.reshape(B, Hkv, T * group, S)
    out = jnp.einsum(
        "bkms,bksh->bkmh", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    out = out.reshape(B, Hkv, T, group, -1).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, Hq, -1)


# ---------------------------------------------------------------------------
# Splash kernel construction
# ---------------------------------------------------------------------------


def splash_supported(T: int, Hq: int, Hkv: int, hd: int, sp: int = 1) -> bool:
    """Shapes the kernel can tile; everything else takes the einsum path,
    as does an explicit CPU run (`JAX_PLATFORMS=cpu`, where the einsum is
    the oracle).  A non-TPU backend nobody asked for raises.
    `sp` = sequence shards: each shard's query extent must stay blockable."""
    return (
        kernel_backend(INTERPRET) != "cpu"
        and T >= 256
        and T % (128 * sp) == 0
        and hd % 128 == 0
        and Hq % Hkv == 0
    )


def _mask_for(T: int, sliding_window: Optional[int]) -> "_sm.Mask":
    if sliding_window is not None:
        # causal left-window: q - w < k <= q
        return _sm.LocalMask((T, T), (sliding_window - 1, 0), 0)
    return _sm.CausalMask((T, T))


@functools.lru_cache(maxsize=64)
def _make_kernel(
    T: int,
    group: int,
    sliding_window: Optional[int],
    logit_softcap: Optional[float],
    q_seq_shards: int,
    interpret: bool = False,
):
    """Build (and cache — mask-info preprocessing is host-side numpy) the
    MQA splash kernel for one (seq-len, q-group) shape."""
    mask = _sm.MultiHeadMask([_mask_for(T, sliding_window) for _ in range(group)])
    # block sizes must DIVIDE the per-shard query extent (the kernel
    # rejects them otherwise): largest 128-multiple <= 512 that divides —
    # e.g. a 768-token packed row gets 384, not a crashing 512.
    # splash_supported guarantees ext % 128 == 0, so the search always
    # terminates at >= 128; assert rather than loop to 0 for direct callers
    ext = T // q_seq_shards
    if ext % 128:
        raise ValueError(
            f"per-shard query extent {ext} must be a multiple of 128 "
            "(gate shapes through splash_supported)"
        )
    block = min(512, ext)
    while ext % block:
        block -= 128
    block_sizes = _sk.BlockSizes(
        block_q=block,
        block_kv=block,
        block_kv_compute=block,
        block_q_dkv=block,
        block_kv_dkv=block,
        block_kv_dkv_compute=block,
        block_q_dq=block,
        block_kv_dq=block,
    )
    # make_* calls jnp.array on the host-side mask info; when the kernel is
    # first built during a jit trace (lru_cache defers to first use) that
    # would capture per-trace tracers in the cached kernel and leak them
    # into later traces — force concrete compile-time values instead
    with jax.ensure_compile_time_eval():
        return _sk.make_splash_mqa_single_device(
            mask=mask,
            block_sizes=block_sizes,
            attn_logits_soft_cap=logit_softcap,
            q_seq_shards=q_seq_shards,
            interpret=interpret,
        )


# padding (-1) as the LAST id of a row: with ids rising along the row a
# block's ids are then the interval [first, last] whether or not it ends in
# padding, and padding stays a segment of its own, as the kernel's in-block
# mask has it (q id == kv id), so a padded row of the softmax keeps its
# diagonal block and its sum never reads 0
_PAD_ID = np.iinfo(np.int32).max


def block_overlap(
    seg_q: jax.Array,  # int32 [Tq]
    seg_kv: jax.Array,  # int32 [Tkv]
    block_q: int,
    block_kv: int,
) -> jax.Array:
    """bool [Tq // block_q, Tkv // block_kv]: may query block i of a row
    hold a position of the same segment as key block j.

    Compares the blocks' [min id, max id] intervals: two blocks that share
    an id always intersect, whatever the order of the ids, so a block that
    is needed is never dropped; with contiguous segments whose ids rise
    along the row (`segment_attention`'s invariant) the intervals are the
    blocks' id sets and the answer is exact."""

    def span(seg, block):
        ids = jnp.where(seg < 0, _PAD_ID, seg).reshape(-1, block)
        return ids.min(-1), ids.max(-1)

    q_lo, q_hi = span(seg_q, block_q)
    k_lo, k_hi = span(seg_kv, block_kv)
    return (q_lo[:, None] <= k_hi[None, :]) & (k_lo[None, :] <= q_hi[:, None])


def _narrowed_block_mask(info, overlap: jax.Array, dkv: bool) -> jax.Array:
    """`info.block_mask` [heads or 1, i, j] with 0 where the block that grid
    step (i, j) works on is false in `overlap` [q blocks, kv blocks]; 1 and
    2 keep their meaning.  Which block that is comes from `data_next` (the
    kv block of a forward / dq step, the q block of a dkv step), so a shrunk
    grid (sliding window, the dkv kernel's) stays right.  A dense compare,
    no gather: these arrays hold a few hundred entries."""
    nxt = info.data_next.astype(jnp.int32)  # [h, i, j]
    if dkv:  # step (i, j): q block nxt[i, j] against kv block j
        pick = nxt[..., None] == jnp.arange(overlap.shape[0])
        live = (pick & overlap.T[None, None]).any(-1)
    else:  # step (i, j): q block i against kv block nxt[i, j]
        pick = nxt[..., None] == jnp.arange(overlap.shape[1])
        live = (pick & overlap[None, :, None]).any(-1)
    return jnp.where(live, info.block_mask, 0)


def _narrow_mask_info(info, overlap: jax.Array, dkv: bool):
    """One kernel's `MaskInfo` with the blocks of `overlap` that are false
    taken out of the grid (`_narrowed_block_mask`).  A step that does not
    run then names the operands of the next one that does, in the order the
    grid is walked, as the upstream preprocessing does for the static mask:
    a skipped step fetches nothing it will not use."""
    block_mask = _narrowed_block_mask(info, overlap, dkv)
    # the forward and dq grids are walked (head, i, j), j fastest; the dkv
    # grid (j, head, i), i fastest
    walk = (lambda a: a.swapaxes(1, 2)) if dkv else (lambda a: a)
    # for each step the first step at or after it, along the fastest axis,
    # that runs; past the end of the axis the first of the next line (every
    # line of a causal or windowed grid holds its diagonal block; where a
    # line held none the hint would name a block that nothing reads)
    run = walk(block_mask > 0)  # [h, lines, n]
    n = run.shape[2]
    at = jnp.arange(n)
    first = jnp.where(
        run[:, :, None, :] & (at >= at[:, None]), at, n
    ).min(-1)  # n where no later step of the line runs

    def at_next_run(field):
        line = walk(field.astype(jnp.int32))
        here = jnp.sum(
            jnp.where(first[..., None] == at, line[:, :, None, :], 0), -1
        )
        line_first = jnp.roll(here[:, :, :1], -1, axis=1)
        return walk(jnp.where(first < n, here, line_first)).astype(field.dtype)

    return info._replace(
        block_mask=block_mask.astype(info.block_mask.dtype),
        data_next=at_next_run(info.data_next),
        mask_next=None if info.mask_next is None
        else at_next_run(info.mask_next),
    )


def _narrowed(kernel, seg_q: jax.Array, seg_kv: jax.Array):
    """`kernel` for ONE row whose segment ids are `seg_q` [Tq] / `seg_kv`
    [T]: each mask info narrowed under its own overlap (their block sizes
    are independent)."""
    bs = kernel.kwargs["block_sizes"]
    infos = (
        (kernel.fwd_mask_info, bs.block_q, bs.block_kv, False),
        (kernel.dq_mask_info, bs.block_q_dq, bs.block_kv_dq, False),
        (kernel.dkv_mask_info, bs.block_q_dkv, bs.block_kv_dkv, True),
    )
    return _sk.SplashAttentionKernel(
        *(
            None if info is None else _narrow_mask_info(
                info, block_overlap(seg_q, seg_kv, bq, bkv), dkv
            )
            for info, bq, bkv, dkv in infos
        ),
        **kernel.kwargs,
    )


def _narrows(rows: int) -> bool:
    """Whether `_splash_rows` narrows the block mask for this many rows."""
    return rows == 1


def _splash_rows(kernel, qs, ks, vs, seg_q, seg_kv, sinks=None):
    """qs [B, Hkv, group, Tq, hd], ks / vs [B, Hkv, T, hd], seg_q [B, Tq],
    seg_kv [B, T] -> [B, Hkv, group, Tq, hd]: the MQA kernel over the kv
    heads (they share the mask) of every row; `sinks` [Hkv, group], one
    scalar a query head in its softmax's denominator, where the model has
    them.  `_splash_call` and the body
    of `_sharded_splash` both end here; a sharded query axis brings its
    shard of the mask infos and of `seg_q`, and every lookup in
    `_narrow_mask_info` is relative to them.

    ONE row runs under a block mask narrowed by its own segment ids: every
    block that runs computes what it computed under the static mask, in the
    same order, and a block left out added exact zeros.  MORE rows keep the
    static mask: a mask that differs by row cannot ride `jax.vmap` (a
    batched scalar-prefetch operand makes Pallas loop over the rows), and
    measured on the v5e every other traversal cost more, where no block can
    be skipped, than `jax.vmap`'s one grid over rows and heads: the rows
    laid end to end as one sequence 4-11 % of the kernels' time (2 x 8192
    to 16 x 1024), a loop of per-row calls 11 % at 8 x 2048, Pallas's own
    loop 48 % (PERF.md section 6, PR 37).  So the choice follows the static
    shape; long rows are few (one a device at 16k on a 16 GB chip)."""

    def heads(kern, qr, kr, vr, sq, skv):
        sids = _sk.SegmentIds(q=sq, kv=skv)
        if sinks is not None:
            return jax.vmap(kern, in_axes=(0, 0, 0, None, 0))(
                qr, kr, vr, sids, sinks)
        return jax.vmap(kern, in_axes=(0, 0, 0, None))(qr, kr, vr, sids)

    if not _narrows(qs.shape[0]):
        return jax.vmap(functools.partial(heads, kernel))(
            qs, ks, vs, seg_q, seg_kv
        )
    kern = _narrowed(kernel, seg_q[0], seg_kv[0])
    return heads(kern, qs[0], ks[0], vs[0], seg_q[0], seg_kv[0])[None]


def _splash_call(kernel, q, k, v, segment_ids, group: int, sinks=None):
    """q [B, T, Hq, hd], k [B, T, Hkv, hd], v [B, T, Hkv, hd_v],
    segment_ids [B, T] -> [B, T, Hq, hd_v] on one device (hd_v is hd but for
    keys of 192 beside values of 128: latent attention's expanded ones, a
    windowed stack's); `sinks` [Hq] as `naive_attention` takes them."""
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    qs = (q * float(1.0 / np.sqrt(hd))).transpose(0, 2, 1, 3)  # [B, Hq, T, hd]
    qs = qs.reshape(B, Hkv, group, T, hd)
    ks = k.transpose(0, 2, 1, 3)  # [B, Hkv, T, hd]
    vs = v.transpose(0, 2, 1, 3)
    if sinks is not None:
        sinks = sinks.astype(jnp.float32).reshape(Hkv, group)
    out = _splash_rows(kernel, qs, ks, vs, segment_ids, segment_ids, sinks)
    return out.reshape(B, Hq, T, v.shape[-1]).transpose(0, 2, 1, 3)


def block_counts(
    segment_ids: jax.Array,  # int32 [B, T]
    group: int,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    sp: int = 1,
    row_shards: int = 1,
) -> Tuple[jax.Array, jax.Array]:
    """(blocks run, blocks the static mask alone runs) of the forward
    kernel's grid for one kv head, summed over these rows: how often the
    narrowing engages.  Read off what the forward kernel is given (same
    kernel object, `block_overlap`, `_narrowed_block_mask`, `_narrows` of the
    rows one device holds: `row_shards` devices share them)."""
    B, T = segment_ids.shape
    kernel = _make_kernel(
        T, group, sliding_window, logit_softcap, sp, interpret=INTERPRET
    )
    info, bs = kernel.fwd_mask_info, kernel.kwargs["block_sizes"]
    causal = jnp.sum(info.block_mask > 0, dtype=jnp.int32)
    if not _narrows(B // row_shards):
        return B * causal, B * causal

    def run(seg):
        overlap = block_overlap(seg, seg, bs.block_q, bs.block_kv)
        block_mask = _narrowed_block_mask(info, overlap, dkv=False)
        return jnp.sum(block_mask > 0, dtype=jnp.int32)

    return jnp.sum(jax.vmap(run)(segment_ids.astype(jnp.int32))), B * causal


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def ring_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    v: jax.Array,  # [B, T, Hkv, hd]
    segment_ids: jax.Array,  # int32 [B, T], -1 = padding
    positions: jax.Array,  # int32 [B, T]
    mesh: Mesh,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Ring attention over the `sp` mesh axis: K/V are SHARDED along the
    sequence (unlike the splash path, where K/V stay whole per shard — the
    Ulysses memory regime) and rotate around the ring via `ppermute`, with
    a blockwise online softmax accumulating each visiting block.

    This is the context-parallel regime the reference lacks outright
    (SURVEY.md §2.4 "Ring attention: not present"): per-chip attention
    memory is O(T/sp) for q AND k/v, so the context ceiling scales with the
    ring size.  Differentiable (shard_map + ppermute transpose), segment-
    masked, GQA-aware; causality and the optional sliding window are
    evaluated per visiting block from the rotating (positions, segment_ids)
    metadata, so packed rows work exactly as in the naive/splash paths.
    """
    sp = mesh.shape["sp"]
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    batch = ("dp", "fsdp", "ep")
    perm = [(j, (j + 1) % sp) for j in range(sp)]
    scale = float(1.0 / np.sqrt(hd))

    def body(qb, kb, vb, segq, posq, segk, posk):
        # qb [b, Tl, Hkv_l, group, hd]; kb/vb [b, Tl, Hkv_l, hd]
        b, Tl = qb.shape[:2]
        hkv = kb.shape[2]
        m = jnp.full((b, hkv, group, Tl), MASK_VALUE, jnp.float32)
        l = jnp.zeros((b, hkv, group, Tl), jnp.float32)
        acc = jnp.zeros((b, hkv, group, Tl, hd), jnp.float32)
        for _ in range(sp):
            scores = jnp.einsum(
                "btkgh,bskh->bkgts", qb, kb
            ).astype(jnp.float32) * scale
            if logit_softcap:
                scores = jnp.tanh(scores / logit_softcap) * logit_softcap
            mask = (
                (segq[:, :, None] == segk[:, None, :])
                & (segq[:, :, None] >= 0)
                & (posk[:, None, :] <= posq[:, :, None])
            )
            if sliding_window is not None:
                mask &= posk[:, None, :] > posq[:, :, None] - sliding_window
            mask = mask[:, None, None, :, :]  # [b,1,1,Tl,Ts]
            # mask BEFORE the exp so its argument is always <= 0: raw masked
            # scores minus m_new == MASK_VALUE would overflow exp to inf in
            # the unselected where-branch and poison the backward (the
            # where-grad trap); the outer where still zeroes the
            # exp(0) == 1 that all-masked rows (m_new == MASK_VALUE) produce
            smx = jnp.where(mask, scores, MASK_VALUE)
            m_new = jnp.maximum(m, jnp.max(smx, axis=-1))
            p = jnp.where(mask, jnp.exp(smx - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgts,bskh->bkgth", p, vb.astype(jnp.float32)
            )
            m = m_new
            kb, vb, segk, posk = (
                jax.lax.ppermute(x, "sp", perm) for x in (kb, vb, segk, posk)
            )
        out = acc / jnp.maximum(l[..., None], 1e-20)  # pad rows: l == 0 -> 0
        return out.astype(qb.dtype)

    qg = q.reshape(B, T, Hkv, group, hd)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch, "sp", "tp", None, None),  # q [B, T, Hkv, group, hd]
            P(batch, "sp", "tp", None),  # k — sequence SHARDED
            P(batch, "sp", "tp", None),  # v
            P(batch, "sp"),  # q-side segment ids
            P(batch, "sp"),  # q-side positions
            P(batch, "sp"),  # rotating k-side segment ids
            P(batch, "sp"),  # rotating k-side positions
        ),
        out_specs=P(batch, "tp", None, "sp", None),  # [B, Hkv, group, T, hd]
        check_vma=False,
    )(qg, k, v, segment_ids, positions, segment_ids, positions)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, Hq, hd)


def segment_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    v: jax.Array,  # [B, T, Hkv, hd]
    segment_ids: jax.Array,  # int32 [B, T], -1 = padding
    positions: jax.Array,  # int32 [B, T] (per-segment positions)
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    impl: str = "auto",  # auto | splash | naive | ring
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Causal segment-masked self-attention over packed/padded rows.

    Requires packed segments to be contiguous with per-segment positions
    increasing by 1 per buffer slot (the layout `pack_into_rows` emits), so
    buffer-index causality equals position causality — the invariant that
    lets the splash kernel use its lazy causal mask instead of a
    materialised one.  Segment ids rise along the row and padding is -1:
    what lets a single row skip the blocks no sequence spans exactly
    (`block_overlap`).
    """
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    if impl == "ring":
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            return ring_attention(
                q, k, v, segment_ids, positions, mesh,
                sliding_window=sliding_window, logit_softcap=logit_softcap,
            )
        impl = "auto"  # no ring without an sp axis — use the normal ladder
    if impl == "auto":
        sp = mesh.shape["sp"] if mesh is not None else 1
        impl = "splash" if splash_supported(T, Hq, Hkv, hd, sp=sp) else "naive"
    if impl == "naive":
        mask = make_attention_mask(segment_ids, positions, sliding_window)
        return naive_attention(q, k, v, mask, logit_softcap)

    group = Hq // Hkv
    segment_ids = segment_ids.astype(jnp.int32)
    if mesh is None or all(s == 1 for s in mesh.shape.values()):
        kernel = _make_kernel(
            T, group, sliding_window, logit_softcap, 1, interpret=INTERPRET
        )
        return _splash_call(kernel, q, k, v, segment_ids, group)
    return _sharded_splash(
        q, k, v, segment_ids, mesh, group, sliding_window, logit_softcap
    )


def _sharded_splash(
    q, k, v, segment_ids, mesh: Mesh, group, sliding_window, logit_softcap
):
    """shard_map-wrapped splash: batch over (dp, fsdp), kv heads over tp,
    query sequence over sp; K/V whole along sequence (Ulysses memory
    regime).  The kernel is built with q_seq_shards and its mask-info arrays
    are sharded with `manual_sharding_spec` so each sp shard runs only its
    causally-needed blocks."""
    sp = mesh.shape["sp"]
    T = q.shape[1]
    kernel = _make_kernel(
        T, group, sliding_window, logit_softcap, sp, interpret=INTERPRET
    )
    kernel_spec = kernel.manual_sharding_spec(
        NamedSharding(mesh, P(None, "sp"))  # (head, q_seq) mask-info layout
    )
    batch = ("dp", "fsdp", "ep")

    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    qs = (q * float(1.0 / np.sqrt(hd))).transpose(0, 2, 1, 3).reshape(B, Hkv, group, T, hd)
    ks = k.transpose(0, 2, 1, 3)
    vs = v.transpose(0, 2, 1, 3)
    out = jax.shard_map(
        _splash_rows,
        mesh=mesh,
        in_specs=(
            kernel_spec,
            P(batch, "tp", None, "sp", None),  # q: [B, Hkv, group, T, hd]
            P(batch, "tp", None, None),  # k: [B, Hkv, S, hd] — S whole
            P(batch, "tp", None, None),
            P(batch, "sp"),  # q segment ids
            P(batch, None),  # kv segment ids — whole
        ),
        out_specs=P(batch, "tp", None, "sp", None),
        check_vma=False,
    )(kernel, qs, ks, vs, segment_ids, segment_ids)
    return out.reshape(B, Hq, T, v.shape[-1]).transpose(0, 2, 1, 3)
