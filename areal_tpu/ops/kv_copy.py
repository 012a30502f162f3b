"""Cross-slot KV prefix fan-out and device<->host prefix transfers.

GRPO samples every group as `group_size` requests over the SAME prompt, and
tree-search / multi-turn branches share a transcript prefix.  The engine
prefills one representative per prefix-cluster and then *copies* the computed
prefix K/V from the representative's cache row into every sibling slot —
one batched gather/scatter over the cache pytree for ALL clusters in the
admission pass, entirely on device — so siblings prefill only their
per-request suffix.

Shape discipline (the same O(log) compiled-program budget as admission):

- `block` (copied positions) is STATIC and always comes from the engine's
  prompt-bucket ladder (`round_up_to_bucket`), so copy programs share the
  prefill buckets' signature family instead of minting one per prefix
  length.
- `src_slots`/`dst_slots` are padded to a power of two with the scratch
  slot (a scratch->scratch self-copy is a harmless no-op), so destination
  counts bucket the same way admission rows do.
- Every cluster in a pass whose prefix shares a block bucket rides ONE
  call: `src_slots[i]` is destination i's own representative, so a pass
  admitting eight groups costs one dispatch, not eight.

Copying a full `block >= prefix_len` is safe without masking: positions in
`[prefix_len, block)` hold the representative's (or stale) K/V, but every
consumer overwrites them before they can be attended — the sibling's suffix
prefill writes `[prefix_len, prefix_len + P)` before its queries run, and
decode writes position `lengths` each step before attending `<= lengths`
(the same frontier invariant padded suffix rows already rely on).
"""
# areal-lint: hot-path

from typing import Dict

import jax
import jax.numpy as jnp


def _position_axis(key: str) -> int:
    """The axis of a cache leaf that counts positions: 2 of `k`, `v`
    [L, S, M, Hkv, hd]; the last of the latent rows `lat` [L, S, row, M]
    (models/transformer.py init_kv_cache)."""
    return 3 if key == "lat" else 2


def gather_kv_prefix(
    cache: Dict[str, jax.Array],
    row: jax.Array,  # int32 scalar: physical cache row to extract
    block: int,  # STATIC bucketed prefix length (positions extracted)
) -> Dict[str, jax.Array]:
    """Extract cache positions [0, block) of one physical row for a
    host-DRAM spill: {key: [L, block, Hkv, hd]}.

    `row` is traced (one program per block bucket serves every slot) and
    `block` rides the prompt-bucket ladder, so the spill path adds one
    C6-budgeted program family of size `ladder`, not one per (slot, len).
    The caller downloads the result with np.asarray — the only host sync
    on the spill path, at the admission boundary where the engine already
    syncs its planning state.
    """
    out = {}
    with jax.named_scope("kv_copy"):
        for key, buf in cache.items():
            rowbuf = jax.lax.dynamic_index_in_dim(
                buf, row, axis=1, keepdims=False
            )  # [L, M, Hkv, hd]
            out[key] = jax.lax.slice_in_dim(
                rowbuf, 0, block, axis=_position_axis(key) - 1)
    return out


def scatter_kv_prefix(
    cache: Dict[str, jax.Array],
    host_kv: Dict[str, jax.Array],  # {key: [L, block, Hkv, hd]} from gather
    row: jax.Array,  # int32 scalar: physical cache row to restore into
) -> Dict[str, jax.Array]:
    """Write a host-spilled prefix back into one physical row (swap-in on
    a radix hit); returns the updated cache pytree (cache donated by the
    engine's jit wrapper, so the restore is in-place on device).

    The round trip is bit-identical: gather slices raw cache bytes, the
    host keeps them in the cache dtype, and this scatter writes them back
    untouched — a swapped-in prefix attends exactly like one that never
    left HBM, which is what keeps counter-keyed streams invariant to
    spill/swap scheduling.
    """
    out = {}
    with jax.named_scope("kv_copy"):
        for key, buf in cache.items():
            blk = host_kv[key].astype(buf.dtype)[:, None]  # [L, 1, block, ...]
            out[key] = jax.lax.dynamic_update_slice(
                buf, blk, (0, row, 0, 0, 0)
            )
    return out


def copy_kv_prefix(
    cache: Dict[str, jax.Array],
    src_slots: jax.Array,  # int32 [d]: source cache row per destination
    dst_slots: jax.Array,  # int32 [d]: sibling rows (scratch-padded pow2)
    block: int,  # STATIC bucketed prefix length (positions copied)
) -> Dict[str, jax.Array]:
    """Copy cache positions [0, block) of `src_slots[i]` into
    `dst_slots[i]` for every layer; returns the updated cache pytree.

    Cache layout is [L, S, M, Hkv, hd] (models/transformer.py
    init_kv_cache).  The source rows gather once ([L, d, block, Hkv, hd])
    and scatter to the destinations in one pass — jitted by the engine
    with the cache donated, this lowers to a gather + one
    dynamic-update-slice-style scatter without any host round-trip.
    """
    out = {}
    with jax.named_scope("kv_copy"):
        for key, buf in cache.items():
            # [L, d, block, Hkv, hd], or a latent leaf's [L, d, row, block]
            span = (slice(None),) * (_position_axis(key) - 2) + (slice(block),)
            blk = buf[(slice(None), src_slots) + span]
            # scratch-padded rows self-copy identical values, so the scatter
            # stays deterministic even with duplicate pad indices
            out[key] = buf.at[(slice(None), dst_slots) + span].set(blk)
    return out


def copy_window(
    cache: Dict[str, jax.Array],  # the ring leaves [L, S, W, Hkv * hd]
    src_slots: jax.Array,  # int32 [d]
    dst_slots: jax.Array,  # int32 [d]
) -> Dict[str, jax.Array]:
    """Copy the sliding layers' rings of `src_slots[i]` WHOLE into
    `dst_slots[i]` (models/windowed.py: a ring holds the last W positions
    at their position mod W, so it is valid at one length only and has no
    prefix to cut out); returns the updated leaves.  A row that continues
    its own slot copies itself."""
    with jax.named_scope("window_copy"):
        return {
            key: buf.at[:, dst_slots].set(buf[:, src_slots])
            for key, buf in cache.items()
        }
