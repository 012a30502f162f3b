"""Full and sliding softmax layers in one stack, each kind with its own kv
heads, and a slot of the serving cache that holds COLUMNS for the one kind
and a WINDOW for the other (`mimo_v2`: MiMo-V2-Flash, the language model of
MiMo-V2.5).

Block l, hidden x, `N` RMS norms (two a block):

    h = x + Attn_l(N(x))
    y = h + FFN_l(N(h))

`Attn_l` is full where `cfg.layer_is_sliding[l]` is false, else sliding.
Both kinds have `num_heads` query heads with a query and key `head_dim`
wide beside a value of `v_head_dim` times `attn_value_scale`; the kv heads
(`num_kv_heads` / `swa_num_kv_heads`) and the rotary base (`rope_theta` /
`swa_rope_theta`) go by kind, and the rotary embedding turns the leading
`cfg.rotary_dim` dims of a head only.  A full layer's query at position i
attends every j <= i under a plain softmax; a sliding layer's only
j > i - `sliding_window`, and where the family says so (`sink_sliding`)
one learned scalar a query head joins the softmax's denominator: it takes
mass and adds no value.  `FFN_l` is a dense gated MLP in the
`leading_dense_layers`, then sigmoid-routed gated experts with no shared
expert, of which this program holds a share (`models/moe.py
gated_moe_ffn`).

The parameters are stacked by kind: `layers["full"]` / `layers["sliding"]`
hold the attention leaves of their layers in the stack's order,
`layers["mlp"]` / `layers["moe"]` the FFNs', the two norms carry [L, D].

The cache has four leaves.  `k` [n_full, S, M, Hkv * head_dim] and `v`
[.., Hkv * v_head_dim] hold a full layer's columns by position, the kv
heads side by side in one row (four heads of 192 are six of the chip's
lanes' worth; with a head axis the chip lays the pool out by position
last, and the one scatter that writes a pass's columns then lays the whole
pool out anew: compiled for a described v5e, 4 GB).  `wk` / `wv`
[n_sliding, S, W, Hkv_swa * ..] hold a sliding layer's last W =
`cfg.window_ring` positions as a RING: position p lies at p mod W.  Keys are stored rotated, so a ring needs no order: a step
attends the whole ring under a mask of the positions its entries hold
(`ring_positions`), and a sliding layer never holds or reads `max_seq_len`
columns.  A ring is valid only at the length it was taken at (an entry
once overwritten is gone), so the engine reuses a retained slot of this
kind as it does a recurrent state: whole or not at all.

`_attend` is the one place a chunk's queries meet a slot's cache, for a
fresh prompt (its own positions alone: the splash kernel on the chip, under
a `LocalMask` and with the sinks for a sliding layer, else queries a block
at a time so that no [H, T, T] array of scores exists), a suffix (a
block of queries at a time over the rows' columns or rings and the chunk's
own keys, one softmax over both) and a decode step (a full
layer reads each live slot's columns by length out of the pool where it
lies, through the paged kernel of `ops/windowed_decode.py` where the engine
resolved `ragged_attn` to it (`forward_decode(ragged=True)`), else copies
its block's bucketed key window out of the pool a few slots at a time, as
the columns kind's copy path does; a sliding layer reads its block's rings
whole).  The pool is only read while the layers run; every
layer's new columns and ring entries are written after the last layer.

Layers are unrolled (their number is small at a pipeline stage's share);
the held experts of every expert layer go into `gated_moe_ffn` whole, with
the layer's index.
"""
# areal-lint: hot-path

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.models.moe import gated_moe_ffn
from areal_tpu.ops import attention as splash
from areal_tpu.ops.kv_copy import copy_kv_prefix, copy_window
from areal_tpu.ops.windowed_decode import windowed_decode_attention
from areal_tpu.models.transformer import (
    Params,
    _embed,
    _head_logits,
    _last_token_logits,
    apply_rope,
    causal_window,
    rms_norm,
    rope_cos_sin,
)
from areal_tpu.utils.runtime import kernel_backend

FULL, SLIDING = "full", "sliding"

# what a decode pass counts, in the order of `forward_decode`'s counters:
# the columns the full layers' attention had to read (positions attended,
# summed over live slots: what ONE full layer reads), the (token, expert)
# assignments of live slots to experts held here and the held experts that
# got any (summed over the expert layers), and the held experts there were
# to touch (expert layers x experts held)
DECODE_COUNTERS = (
    "kv_columns_read", "expert_assignments_held", "experts_touched",
    "expert_slots",
)

# query positions scored at once: of a fresh prompt on the blocked path, of
# a suffix over its slot's cache; the tokens a dense FFN takes at a time;
# the slots whose key window a full layer's decode step copies at once (64
# slots' windows of 16,384 positions are 2.7 GB a layer)
_FRESH_BLOCK = 128
_CACHED_BLOCK = 4
_FFN_BLOCK = 2048
_DECODE_ROWS = 8

_LOWEST = float(jnp.finfo(jnp.float32).min)


def kv_heads(cfg: TransformerConfig, kind: str) -> int:
    return cfg.swa_num_kv_heads if kind == SLIDING else cfg.num_kv_heads


def value_dim(cfg: TransformerConfig) -> int:
    return cfg.v_head_dim or cfg.head_dim_


def layer_plan(cfg: TransformerConfig) -> List[Tuple[str, int, str, int]]:
    """(attention kind, its index among that kind's layers, "mlp" | "moe",
    its index among that kind's) for every layer of the stack."""
    plan, n = [], {FULL: 0, SLIDING: 0, "mlp": 0, "moe": 0}
    sliding = cfg.layer_is_sliding or (False,) * cfg.num_layers
    for l in range(cfg.num_layers):
        a = SLIDING if sliding[l] else FULL
        f = "mlp" if l < cfg.leading_dense_layers else "moe"
        plan.append((a, n[a], f, n[f]))
        n[a] += 1
        n[f] += 1
    return plan


def n_layers(cfg: TransformerConfig, kind: str) -> int:
    return sum(1 for a, _, f, _ in layer_plan(cfg) if kind in (a, f))


def cache_leaves(cfg: TransformerConfig, n_slots: int, max_len: int, dtype):
    """{leaf: (shape, dtype)} of `init_kv_cache` for this kind."""
    dq, dv, W = cfg.head_dim_, value_dim(cfg), cfg.window_ring
    nf, ns = n_layers(cfg, FULL), n_layers(cfg, SLIDING)
    hf, hs = kv_heads(cfg, FULL), kv_heads(cfg, SLIDING)
    dt = jnp.dtype(dtype)
    return {
        "k": ((nf, n_slots, max_len, hf * dq), dt),
        "v": ((nf, n_slots, max_len, hf * dv), dt),
        "wk": ((ns, n_slots, W, hs * dq), dt),
        "wv": ((ns, n_slots, W, hs * dv), dt),
    }


def cache_partition_specs() -> Dict[str, P]:
    none = P(None, None, None, None)
    return {"k": none, "v": none, "wk": none, "wv": none}


def ring_positions(start, W: int):
    """int32 [.., W]: the position each entry of a ring holds when the slot
    has taken in `start` [..] positions: the largest p < start with
    p mod W == r, negative where the entry was never written."""
    last = start[..., None].astype(jnp.int32) - 1
    r = jnp.arange(W, dtype=jnp.int32)
    return last - jnp.mod(last - r, W)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _project(cfg: TransformerConfig, ap: Params, kind: str, h, cos, sin,
             pool_dtype):
    """h [B, T, D] -> (q [B, T, H, dq], k [B, T, Hkv, dq], v [B, T, Hkv, dv]
    times the value scale), q and k rotated on their leading rotary dims, k
    and v through the pool's dtype, as a column read back would be.  `ap`
    holds one layer of `by_head`'s view, the projections [heads, d, D]: the
    product gives the heads their axis, and no reshape follows it for the
    compiler to move onto the weight (see `by_head`)."""
    dtype = h.dtype
    dq, rot = cfg.head_dim_, cfg.rotary_dim

    def rotate(a):
        if not rot:
            return a
        if rot == dq:
            return apply_rope(a, cos, sin)
        return jnp.concatenate(
            [apply_rope(a[..., :rot], cos, sin), a[..., rot:]], axis=-1)

    q = rotate(jnp.einsum("btd,hkd->bthk", h, ap["wq"].astype(dtype)))
    k = rotate(jnp.einsum("btd,hkd->bthk", h, ap["wk"].astype(dtype)))
    v = jnp.einsum("btd,hkd->bthk", h, ap["wv"].astype(dtype))
    v = v * jnp.asarray(cfg.attn_value_scale, dtype)
    return q, k.astype(pool_dtype), v.astype(pool_dtype)


def _softmax_parts(q, parts, sink, scale: float, Hkv: int):
    """One float32 softmax over the keys of every part together (and the
    sink), then the weighted sum of the parts' values.  q [B, T, H, dq]; a
    part is (k [B, K, Hkv * dq], v [B, K, Hkv * dv], keep bool [B, T, K]),
    the kv heads side by side in one row as the pool holds them; sink [H]
    or None -> [B, T, H, dv] in q's dtype.

    A row of the pool is never split by head (a head's 192 values do not
    fill the chip's lanes, and a split would lay the window out anew):
    query head (h, g) is widened to the whole row with zeros outside kv
    head h's part, so that ONE product of all H queries with the rows gives
    every head's scores, and of the weighted sum over whole rows each head
    keeps its kv head's part.  Hkv times the operations, on a step that
    waits for the rows' bytes."""
    dtype = q.dtype
    B, T, H, dq = q.shape
    G = H // Hkv
    eye = jnp.eye(Hkv, dtype=dtype)
    qp = jnp.einsum(
        "bthgd,hj->bthgjd", q.reshape(B, T, Hkv, G, dq), eye
    ).reshape(B, T, H, Hkv * dq)
    scores = [
        jnp.where(
            keep[:, None],
            jnp.einsum("bthd,bkd->bhtk", qp, k,
                       preferred_element_type=jnp.float32) * scale,
            _LOWEST)
        for k, _, keep in parts
    ]
    m = scores[0].max(-1)
    for sc in scores[1:]:
        m = jnp.maximum(m, sc.max(-1))
    total = 0.0
    if sink is not None:
        b = sink.astype(jnp.float32).reshape(1, H, 1)
        m = jnp.maximum(m, b)
        total = jnp.exp(b - m)
    o = 0.0
    for sc, (_, v, _) in zip(scores, parts):
        e = jnp.exp(sc - m[..., None])
        total = total + e.sum(-1)
        o = o + jnp.einsum("bhtk,bkv->bhtv", e.astype(dtype), v,
                           preferred_element_type=jnp.float32)
    o = o / total[..., None]  # [B, H, T, Hkv * dv]
    o = jnp.einsum(
        "bhgtjv,hj->bthgv", o.reshape(B, Hkv, G, T, Hkv, -1),
        eye.astype(jnp.float32))
    return o.reshape(B, T, H, -1).astype(dtype)


def _rows(a):
    """[B, T, Hkv, d] -> [B, T, Hkv * d]: the kv heads side by side."""
    return a.reshape(a.shape[:2] + (-1,))


def _splash_applies(T: int) -> bool:
    """Whether a fresh prompt of T (padded) positions attends through the
    splash kernel (`ops/attention.py`): on the chip, for lengths it tiles.
    An explicit CPU run takes the blocked product, its oracle."""
    return (
        kernel_backend(splash.INTERPRET) != "cpu" and T >= 256 and T % 128 == 0
    )


def _attend_fresh(q, k, v, valid, window, sink, scale: float):
    """Causal attention of a chunk over ITS OWN positions 0 .. T - 1 ->
    [B, T, H, dv]; `valid` [B, T] says which positions are tokens (a row's
    padding trails them)."""
    B, T, H, dq = q.shape
    G = H // k.shape[2]
    if _splash_applies(T):
        kernel = splash._make_kernel(
            T, G, window, None, 1, interpret=splash.INTERPRET)
        return splash._splash_call(
            kernel, q, k, v, jnp.where(valid, 0, -1).astype(jnp.int32), G,
            sinks=sink)
    qb = math.gcd(T, _FRESH_BLOCK)
    key_pos = jnp.arange(T, dtype=jnp.int32)

    def block(args):
        first, qs = args  # (), [B, qb, H, dq]
        keep = causal_window(
            first + jnp.arange(qb, dtype=jnp.int32), key_pos, window)
        return _softmax_parts(
            qs, [(_rows(k), _rows(v), jnp.broadcast_to(keep, (B, qb, T)))],
            sink, scale, k.shape[2])

    out = jax.lax.map(block, (
        jnp.arange(T // qb, dtype=jnp.int32) * qb,
        jnp.moveaxis(q.reshape(B, T // qb, qb, H, dq), 1, 0),
    ))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, -1)


def _old_keep(old_pos, start, q_pos, window):
    """bool [.., T, K]: which cached entries (holding `old_pos` [.., K]) the
    queries at `q_pos` [.., T] of a chunk that starts at `start` [..]
    attend."""
    keep = (old_pos >= 0) & (old_pos < start[..., None])
    keep = jnp.broadcast_to(
        keep[..., None, :], q_pos.shape + old_pos.shape[-1:])
    if window is not None:
        keep = keep & (old_pos[..., None, :] > q_pos[..., None] - window)
    return keep


def _attend_suffix(q, k, v, pool_k, pool_v, j: int, at: Dict, window, sink,
                   scale: float, Hkv: int):
    """A suffix's queries over their slots' cache (the first `K` columns of
    a full layer, the ring of a sliding one) and the chunk's own keys,
    `_CACHED_BLOCK` queries of every row at a time, blocks past every row's
    tokens skipped -> [B, T, H, dv].  The rows' windows are sliced out once
    and attended as one batch (one row at a time, the compiler laid the
    WHOLE pool out anew for the products, and a gather by slot it cut into
    pieces of every slot's rows: compiled for a described v5e, 2 GB copied
    a dispatch and 4.5 GB held)."""
    dtype = q.dtype
    B, T, H, dq = q.shape
    K = pool_k.shape[2] if window is not None else at["K"]
    tb = math.gcd(T, _CACHED_BLOCK)
    slots, starts, n_real = at["slots"], at["starts"], at["n_real"]

    def windows(pool):
        return jnp.concatenate([
            jax.lax.dynamic_slice(
                pool, (j, slots[b], 0, 0), (1, 1, K) + pool.shape[3:])[0]
            for b in range(B)
        ]).astype(dtype)

    ko, vo = windows(pool_k), windows(pool_v)
    kn, vn = k.astype(dtype), v.astype(dtype)
    old_pos = (
        jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (B, K))
        if window is None else ring_positions(starts, K))
    new_pos = jnp.arange(T, dtype=jnp.int32)
    is_token = new_pos[None, None, :] < n_real[:, None, None]

    def one_block(args):
        qs, first = args  # [B, tb, H, dq], ()

        def attend():
            offs = first + jnp.arange(tb, dtype=jnp.int32)
            keep_old = _old_keep(
                old_pos, starts, starts[:, None] + offs[None, :], window)
            keep_new = causal_window(offs, new_pos, window)[None] & is_token
            return _softmax_parts(
                qs, [(ko, vo, keep_old), (kn, vn, keep_new)], sink, scale, Hkv)

        return jax.lax.cond(
            first < jnp.max(n_real), attend,
            lambda: jnp.zeros((B, tb, H, vn.shape[-1] // Hkv), dtype))

    out = jax.lax.map(one_block, (
        jnp.moveaxis(q.reshape(B, T // tb, tb, H, dq), 1, 0),
        jnp.arange(T // tb, dtype=jnp.int32) * tb,
    ))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, -1)


def _attend_decode(q, k, v, pool_k, pool_v, j: int, at: Dict, window, sink,
                   scale: float, Hkv: int):
    """One new query a slot of the block from `at["slot_base"]` over the
    slot's cache and its own new column -> [B, 1, H, dv].  A sliding layer
    reads the block's rings whole; a full layer goes through the paged
    kernel (`at["ragged"]`: columns by length out of the pool where it
    lies, inactive slots zeros) or copies `_DECODE_ROWS` slots' first K
    columns at a time (a group with no live slot skipped)."""
    dtype = q.dtype
    B, _, H, dq = q.shape
    starts, live, base = at["starts"], at["live"], at["slot_base"]
    kn, vn = k.astype(dtype), v.astype(dtype)
    if window is None and at["ragged"]:
        return windowed_decode_attention(
            q[:, 0], kn[:, 0], vn[:, 0], pool_k, pool_v, starts, live, j=j,
            slot_base=base, scale=scale)[:, None]
    own = jnp.ones((B, 1, 1), bool)
    if window is not None:
        W = pool_k.shape[2]
        ko, vo = (
            jax.lax.dynamic_slice(
                pool, (j, base, 0, 0), (1, B, W) + pool.shape[3:]
            )[0].astype(dtype) for pool in (pool_k, pool_v))
        keep = _old_keep(
            ring_positions(starts, W), starts, starts[:, None], window)
        return _softmax_parts(
            q, [(ko, vo, keep), (kn, vn, own)], sink, scale, Hkv)
    K = at["K"]
    R = math.gcd(B, _DECODE_ROWS)
    key_pos = jnp.arange(K, dtype=jnp.int32)

    def group(args):
        i, qs, ks, vs, st, lv = args

        def attend():
            # the window is read when its queries are there, not before
            ko, vo = (
                jax.lax.dynamic_slice(
                    pool, (j, base + i * R, 0, 0), (1, R, K) + pool.shape[3:]
                )[0].astype(dtype) for pool in (pool_k, pool_v))
            keep = (key_pos[None, :] < st[:, None])[:, None, :]
            return _softmax_parts(
                qs, [(ko, vo, keep), (ks, vs, own[:R])], sink, scale, Hkv)

        return jax.lax.cond(
            jnp.any(lv), attend,
            lambda: jnp.zeros((R, 1, H, vs.shape[-1] // Hkv), dtype))

    split = lambda a: a.reshape((B // R, R) + a.shape[1:])  # noqa: E731
    out = jax.lax.map(group, (
        jnp.arange(B // R, dtype=jnp.int32), split(q), split(kn), split(vn),
        split(starts), split(live)))
    return out.reshape(B, 1, H, -1)


def _attend(cfg: TransformerConfig, ap: Params, kind: str, j: int, h,
            rope, cache, at: Dict):
    """One layer's attention over a chunk -> (its output [B, T, D], the
    chunk's new keys and values for the cache)."""
    dtype = h.dtype
    B, T, _ = h.shape
    window = cfg.sliding_window if kind == SLIDING else None
    pool_k, pool_v = (
        (cache["wk"], cache["wv"]) if kind == SLIDING
        else (cache["k"], cache["v"]))
    sink = ap.get("sink")
    scale = cfg.head_dim_ ** -0.5
    with jax.named_scope("attn_qkv"):
        q, k, v = _project(cfg, ap, kind, h, *rope[kind], pool_k.dtype)
    with jax.named_scope("attn"), jax.named_scope(
            "attn_local" if kind == SLIDING else "attn_global"):
        Hkv = k.shape[2]
        if at["fresh"]:
            o = _attend_fresh(q, k.astype(dtype), v.astype(dtype),
                              at["valid"], window, sink, scale)
        else:
            attend = _attend_decode if at["decode"] else _attend_suffix
            o = attend(q, _rows(k), _rows(v), pool_k, pool_v, j, at, window,
                       sink, scale, Hkv)
    with jax.named_scope("attn_out"):
        out = jnp.einsum(
            "bte,ed->btd", o.reshape(B, T, -1), ap["wo"].astype(dtype))
    return out, (_rows(k), _rows(v))


# ---------------------------------------------------------------------------
# the cache's writes
# ---------------------------------------------------------------------------


def _write_cache(cfg: TransformerConfig, cache, new: Dict, at: Dict):
    """Every layer's new keys and values into the pool, after the last
    layer: a full layer's column of position `starts + u` for the first
    `n_write` tokens u of each row, a sliding layer's last
    `min(n_write, W)` tokens at their position mod W.  Padding, idle slots
    and what lies past the pool's end are dropped."""
    slots, starts, n_write = at["slots"], at["starts"], at["n_write"]
    cache = dict(cache)
    if new[FULL]:
        M = cache["k"].shape[2]
        T = new[FULL][0][0].shape[1]
        offs = jnp.arange(T, dtype=jnp.int32)[None, :]
        widx = jnp.where(offs < n_write[:, None], starts[:, None] + offs, M)
        with jax.named_scope("kv_write"):
            # a scatter a layer: one over all layers takes the layer axis
            # into its window, and the chip then wants the pool laid out
            # with that axis beside the row's (compiled for a described
            # v5e: the whole pool copied there and back)
            for name, cols in zip(("k", "v"), zip(*new[FULL])):
                for j, col in enumerate(cols):
                    cache[name] = cache[name].at[j, slots[:, None], widx].set(
                        col, mode="drop")
    if new[SLIDING]:
        W = cache["wk"].shape[2]
        T = new[SLIDING][0][0].shape[1]
        n = min(W, T)
        # the chunk's last n tokens: the only ones a ring can still hold
        idx = n_write[:, None] - n + jnp.arange(n, dtype=jnp.int32)[None, :]
        widx = jnp.where(idx >= 0, jnp.mod(starts[:, None] + idx, W), W)
        take = jnp.clip(idx, 0, T - 1)[:, :, None]
        with jax.named_scope("window_write"):
            for name, cols in zip(("wk", "wv"), zip(*new[SLIDING])):
                for j, col in enumerate(cols):
                    cache[name] = cache[name].at[j, slots[:, None], widx].set(
                        jnp.take_along_axis(col, take, axis=1), mode="drop")
    return cache


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def by_head(cfg: TransformerConfig, kind: str, stacked: Params) -> Params:
    """A kind's stacked attention leaves with the three projections out of
    the stream viewed [n, heads, d_head, D] (stored [n, heads * d_head, D];
    a head's 192 or 128 rows are whole tiles, so the view moves nothing).
    The view is taken of the STACK, before `_sub` takes a layer: taken of
    the layer, behind the product, the compiler puts the bitcast between
    the layer's slice and the product and then copies the slice out instead
    of reading it as the product's operand: 822 MB of `wq` / `wk` / `wv`
    written and read again in every decode pass (compiled for a described
    v5e).  The barrier keeps the view where it is: a slice from 0 of a
    reshape's first axis, layer 0's, the compiler rewrites as the reshape
    of a slice, which is the form above again (233 MB a pass)."""
    heads = {"wq": cfg.num_heads, "wk": kv_heads(cfg, kind),
             "wv": kv_heads(cfg, kind)}
    view = {
        name: w.reshape(w.shape[0], heads[name], -1, w.shape[-1])
        for name, w in stacked.items() if name in heads
    }
    return {**stacked, **jax.lax.optimization_barrier(view)}


def _sub(tree: Params, i: int) -> Params:
    """Layer i of stacked leaves (the attention's through `by_head`: the
    slice of a view by head is one the product reads in place)."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _dense_ffn(mp: Params, a, dtype):
    """SwiGLU; a long prompt a block of tokens at a time (unrolled: a loop
    would have the weights' slices copied in as its operands)."""

    def mlp(a):
        gate = jnp.einsum("btd,df->btf", a, mp["w_gate"].astype(dtype))
        up = jnp.einsum("btd,df->btf", a, mp["w_up"].astype(dtype))
        return jnp.einsum(
            "btf,fd->btd", jax.nn.silu(gate) * up, mp["w_down"].astype(dtype))

    B, T, D = a.shape
    if B * T <= _FFN_BLOCK:
        return mlp(a)
    flat = a.reshape(1, B * T, D)
    return jnp.concatenate([
        mlp(flat[:, i: i + _FFN_BLOCK]) for i in range(0, B * T, _FFN_BLOCK)
    ], axis=1).reshape(B, T, D)


def _cache_forward(params: Params, cfg: TransformerConfig, x, rope, cache,
                   at: Dict, valid):
    """Every layer over a chunk -> (final-norm hidden, the cache, the expert
    layers' counters int32 [2] summed: assignments held, experts touched)."""
    dtype = x.dtype
    layers, eps = params["layers"], cfg.rms_norm_eps
    new = {FULL: [], SLIDING: []}
    counters = jnp.zeros((2,), jnp.int32)
    with jax.named_scope("layers"):
        attn = {kind: by_head(cfg, kind, layers[kind])
                for kind in (FULL, SLIDING) if kind in layers}
        for l, (kind, j, ffn, i) in enumerate(layer_plan(cfg)):
            h = rms_norm(x, layers["input_norm"][l], eps)
            out, kv = _attend(
                cfg, _sub(attn[kind], j), kind, j, h, rope, cache, at)
            new[kind].append(kv)
            x = x + out
            h = rms_norm(x, layers["post_attn_norm"][l], eps)
            if ffn == "mlp":
                with jax.named_scope("ffn_dense"):
                    x = x + _dense_ffn(_sub(layers["mlp"], i), h, dtype)
                continue
            with jax.named_scope("moe"):
                moe = layers["moe"]
                out, c = gated_moe_ffn(cfg, {
                    "router": moe["router"][i],
                    "router_bias": moe["router_bias"][i],
                    "w_gate": moe["w_gate"], "w_up": moe["w_up"],
                    "w_down": moe["w_down"], "block": i,
                }, h, dtype, valid, drop_invalid=True)
                x = x + out
                counters = counters + jnp.stack([c[0], c[3]])
        cache = _write_cache(cfg, cache, new, at)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], eps)
    return x, cache, counters


def _rope_tables(cfg: TransformerConfig, positions):
    rot = cfg.rotary_dim
    return {
        FULL: rope_cos_sin(positions, rot, cfg.rope_theta),
        SLIDING: rope_cos_sin(positions, rot, cfg.swa_rope_theta),
    }


def forward_prefill(params, cfg, input_ids, prompt_lens, cache, slot_ids):
    """`transformer.forward_prefill` of this kind -> (last-token logits,
    the cache with the prompts' columns and rings in `slot_ids`)."""
    S, T = input_ids.shape
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
        rope = _rope_tables(cfg, positions)
        x = _embed(params, cfg, input_ids, dtype, positions=positions)
        valid = positions < prompt_lens[:, None]
    at = {"fresh": True, "decode": False, "slots": slot_ids, "valid": valid,
          "starts": jnp.zeros((S,), jnp.int32), "n_write": prompt_lens}
    x, cache, _ = _cache_forward(params, cfg, x, rope, cache, at, valid)
    return _last_token_logits(params, cfg, x, prompt_lens, dtype), cache


def forward_prefill_cached(
    params, cfg, input_ids, starts, suffix_lens, cache, slot_ids,
    copy_src=None, copy_block: int = 0, key_window: Optional[int] = None,
):
    """`transformer.forward_prefill_cached` of this kind: a sibling first
    takes the shared prompt's columns [0, copy_block) of every full layer
    and every sliding layer's ring WHOLE from `copy_src` (a ring holds the
    window at one length: the length its source was prefilled to, which the
    engine makes the row's `starts`), then the suffix attends the slot's
    first `key_window` columns and its ring, and is written."""
    S, T = input_ids.shape
    M = cache["k"].shape[2]
    if copy_block and copy_src is not None:
        cache = {
            **copy_kv_prefix({"k": cache["k"], "v": cache["v"]},
                             copy_src, slot_ids, copy_block),
            **copy_window({"wk": cache["wk"], "wv": cache["wv"]},
                          copy_src, slot_ids),
        }
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        offs = jnp.arange(T, dtype=jnp.int32)
        positions = starts[:, None] + offs[None, :]
        rope = _rope_tables(cfg, positions)
        x = _embed(params, cfg, input_ids, dtype, positions=positions)
        valid = offs[None, :] < suffix_lens[:, None]
    at = {
        "fresh": False, "decode": False, "slots": slot_ids, "starts": starts,
        "n_real": suffix_lens, "n_write": jnp.minimum(suffix_lens, M - starts),
        "K": min(key_window, M) if key_window else M,
    }
    x, cache, _ = _cache_forward(params, cfg, x, rope, cache, at, valid)
    return _last_token_logits(params, cfg, x, suffix_lens, dtype), cache


def forward_decode(
    params, cfg, tokens, lengths, cache, key_window: Optional[int] = None,
    slot_base: int = 0, active=None, ragged: bool = False, **_,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """One decode step of the block of slots from `slot_base` -> (logits
    [B, V], new cache, counters int32 by `DECODE_COUNTERS`).  The block's
    columns and rings are stepped where they lie (one tier, the identity
    page table), as a hybrid stack's are; an idle slot writes nothing and
    none of its rows reaches an expert.  `ragged` (static) takes the paged
    kernel over the pool for the attention of every FULL layer."""
    B = tokens.shape[0]
    M = cache["k"].shape[2]
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        positions = lengths[:, None].astype(jnp.int32)
        rope = _rope_tables(cfg, positions)
        x = _embed(params, cfg, tokens[:, None], dtype, positions=positions)
        # as `transformer.forward_decode`: a slot past the window clamps
        # into its last column, an idle one writes nothing
        at_pos = jnp.minimum(lengths, K - 1).astype(jnp.int32)
        live = jnp.ones((B,), bool) if active is None else active
        columns = jnp.sum(jnp.where(live, at_pos + 1, 0), dtype=jnp.int32)
    at = {
        "fresh": False, "decode": True,
        "slots": slot_base + jnp.arange(B, dtype=jnp.int32),
        "starts": at_pos, "n_write": live.astype(jnp.int32), "K": K,
        "slot_base": slot_base, "live": live, "ragged": ragged,
    }
    x, cache, counters = _cache_forward(
        params, cfg, x, rope, cache, at, live[:, None])
    lo, hi = cfg.held_range
    counters = jnp.concatenate([
        columns[None], counters,
        jnp.full((1,), n_layers(cfg, "moe") * (hi - lo), jnp.int32)])
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x[:, 0], dtype), cache, counters


# ---------------------------------------------------------------------------
# Init & partitioning
# ---------------------------------------------------------------------------


def init_params(cfg: TransformerConfig, rng: jax.Array, dense) -> Params:
    """The stack by kind (the module's head): attention leaves [n_kind, ...]
    with weights [in, out], but the three projections out of the stream,
    `wq`, `wk` and `wv`, [out, in] (the chip's compiler wants them so, and
    copied 770 MB of them into that layout in every decode chunk: compiled
    for a described v5e; the traversal reads them through `by_head`'s view,
    [n, heads, d_head, D]), and, where the kind's softmax has one, the sink
    [n_kind, H] (float32, zero: the softmax of a model without it but for
    one unit of mass); the dense FFNs [n_dense, ...]; the expert layers'
    router over ALL experts, its selection bias (float32, zero: a buffer)
    and the experts held here [n_moe, held, ...]; two norms a layer."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, V, F, L = (cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size,
                  cfg.num_layers)
    H, dq, dv = cfg.num_heads, cfg.head_dim_, value_dim(cfg)
    keys = iter(jax.random.split(rng, 24))
    layers: Params = {
        "input_norm": jnp.ones((L, D), pdt),
        "post_attn_norm": jnp.ones((L, D), pdt),
    }
    for kind, has_sink in ((FULL, cfg.sink_full), (SLIDING, cfg.sink_sliding)):
        n, Hkv = n_layers(cfg, kind), kv_heads(cfg, kind)
        if not n:
            continue
        layers[kind] = {
            "wq": dense(next(keys), (n, H * dq, D), D),
            "wk": dense(next(keys), (n, Hkv * dq, D), D),
            "wv": dense(next(keys), (n, Hkv * dv, D), D),
            "wo": dense(next(keys), (n, H * dv, D), H * dv),
        }
        if has_sink:
            layers[kind]["sink"] = jnp.zeros((n, H), jnp.float32)
    n = n_layers(cfg, "mlp")
    if n:
        layers["mlp"] = {
            "w_gate": dense(next(keys), (n, D, F), D),
            "w_up": dense(next(keys), (n, D, F), D),
            "w_down": dense(next(keys), (n, F, D), F),
        }
    n = n_layers(cfg, "moe")
    if n:
        lo, hi = cfg.held_range
        Fm = cfg.moe_intermediate_size
        layers["moe"] = {
            "router": dense(next(keys), (n, D, cfg.num_experts), D),
            "router_bias": jnp.zeros((n, cfg.num_experts), jnp.float32),
            "w_gate": dense(next(keys), (n, hi - lo, D, Fm), D),
            "w_up": dense(next(keys), (n, hi - lo, D, Fm), D),
            "w_down": dense(next(keys), (n, hi - lo, Fm, D), Fm),
        }
    params: Params = {
        "embedding": dense(next(keys), (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), pdt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (D, V), D)
    return params


def partition_specs(cfg: TransformerConfig, vocab_axis) -> Params:
    """On a serving mesh every leaf whole but the vocabulary (the engine
    refuses tp > 1 and ep > 1 for this kind)."""
    shapes = jax.eval_shape(
        lambda: init_params(
            cfg, jax.random.PRNGKey(0), lambda k, shape, fan: jnp.zeros(shape))
    )
    specs = jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)), shapes)
    specs["embedding"] = P(vocab_axis, None)
    if "lm_head" in specs:
        specs["lm_head"] = P(None, vocab_axis)
    return specs
