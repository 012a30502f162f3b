"""Latent attention with a latent cache, and the double layer built on it
(`longcat_flash`: LongCat-Flash, the language model of LongCat-Flash-Omni).

A layer is NOT one mixer and one FFN.  With `N_i` RMS norms and `x` the
residual stream, layer `l` holds attention sublayers `2l` and `2l + 1`:

    a0 = x  + MLA_0(N_1(x))
    h0 = N_2(a0)
    s  = MoE(h0)                   shortcut: routed + identity experts
    b0 = a0 + FFN_0(h0)
    a1 = b0 + MLA_1(N_3(b0))
    y  = a1 + FFN_1(N_4(a1)) + s   the expert output joins here

`MLA(h)` projects the query through a normed latent of `q_lora_rank`, and
keys and values of ALL heads out of ONE normed latent `c` of `kv_lora_rank`
a position, beside one rotary key `kr` the heads share; a head's query and
key are `[nope | rope]` wide, its value `v_head_dim`.  The serving cache
holds `[c | kr]` (after norm, scale and rotation), `cfg.latent_row_dim`
values a position and sublayer with no head axis: leaf `lat`
[2 L, S, row, M], the POSITIONS last: a slot's window is then a [row, K]
matrix whose two products with the queries (scores, weighted sum) both
take it as it lies, and no axis is padded to the chip's tiles (576 values
are four and a half lanes' worth; 8,192 positions are 64).

`latent_attend` is the one place that makes a chunk's rows and attends
over a slot's rows, for a fresh prompt, a suffix and a decode step
(`write_rows` puts every sublayer's rows into the pool after the last
layer).  Over cached rows it never expands keys and values: `W_kvb`'s key half is folded
into the query and its value half applied after the weighted sum of latent
rows (the "absorbed" form).  A fresh prompt expands its own positions and
attends them through the splash kernel (on the chip) or in blocks.  Both
forms are one mathematics.

A decode step (one new query a slot) attends through the paged kernel of
`ops/latent_decode.py` where the engine resolved `ragged_attn` to it
(`forward_decode(ragged=True)`): the pool stays where it lies and each live
slot's rows are read by LENGTH, once, a [row, block] tile serving both
products.  Otherwise (`ragged=False`: a pool the kernel does not read, a
backend without it) the block's key window is sliced out of the pool by
its BUCKET and the two products read the copy.  A suffix goes row by row
over the slot's window either way.

Layers are unrolled (their number is small at a pipeline stage's share):
the held experts of every layer go into `moe.identity_moe_ffn` whole, with
the layer's index.
"""
# areal-lint: hot-path

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.models.moe import IDENTITY_MOE_COUNTERS, identity_moe_ffn
from areal_tpu.ops import attention as splash
from areal_tpu.ops.latent_decode import latent_decode_attention
from areal_tpu.models.transformer import (
    Params,
    _embed,
    _head_logits,
    _last_token_logits,
    _mlp,
    apply_rope,
    causal_window,
    rms_norm,
    rope_cos_sin,
)
from areal_tpu.utils.runtime import kernel_backend

# what a decode pass counts, in the order of `forward_decode`'s counters:
# the expert layer's, then the latent rows its attention read (positions
# attended, summed over slots and sublayers)
DECODE_COUNTERS = IDENTITY_MOE_COUNTERS + ("latent_rows_read",)

# query positions of one block of the attention over cached rows (a block
# past a row's real tokens is skipped) and of the expanded attention of a
# fresh prompt, and the tokens a dense FFN takes at a time: what a prompt
# of `max_seq_len` tokens needs beside the weights and the pool
_CACHED_BLOCK = 16
_FRESH_BLOCK = 128
_FFN_BLOCK = 2048


def _scaled_norm(x, weight, eps: float, scale: float):
    """RMS norm times a constant, the constant inside the float32 part."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32) * scale).astype(dtype)


def mla_project(cfg: TransformerConfig, ap: Params, h, cos, sin):
    """One sublayer's projections: h [B, T, D] -> (q_nope [B, T, H, nope],
    q_rope [B, T, H, rope] rotated, row [B, T, kv_lora + rope]: the normed
    and scaled latent beside the rotated shared key, what the cache holds).
    `ap` is a sublayer of `by_head`'s view (`wq_b` [H, d_head, r]): the
    product gives the heads their axis, no reshape follows it."""
    dtype = h.dtype
    D, nope = h.shape[-1], cfg.qk_nope_head_dim
    with jax.named_scope("mla_q"):
        cq = _scaled_norm(
            jnp.einsum("btd,dr->btr", h, ap["wq_a"].astype(dtype)),
            ap["q_norm"], cfg.rms_norm_eps,
            math.sqrt(D / cfg.q_lora_rank) if cfg.mla_scale_q_lora else 1.0,
        )
        q = jnp.einsum("btr,hkr->bthk", cq, ap["wq_b"].astype(dtype))
        q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    with jax.named_scope("mla_kv"):
        ckr = jnp.einsum("btd,dr->btr", h, ap["wkv_a"].astype(dtype))
        c = _scaled_norm(
            ckr[..., :cfg.kv_lora_rank], ap["kv_norm"], cfg.rms_norm_eps,
            math.sqrt(D / cfg.kv_lora_rank) if cfg.mla_scale_kv_lora else 1.0,
        )
        kr = apply_rope(ckr[..., None, cfg.kv_lora_rank:], cos, sin)[:, :, 0]
        row = jnp.concatenate([c, kr], axis=-1)
    return q_nope, q_rope, row


def _kv_b(cfg: TransformerConfig, ap: Params, dtype):
    """`W_kvb` by head -> (key half [H, nope, c], value half [H, v, c])."""
    w = ap["wkv_b"].astype(dtype).reshape(
        cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.kv_lora_rank
    )
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


def _softmax_rows(scores, keep):
    """float32 softmax over the last axis of the kept entries."""
    return jax.nn.softmax(
        jnp.where(keep, scores, jnp.finfo(jnp.float32).min), axis=-1
    )


def _splash_applies(T: int) -> bool:
    """Whether a fresh prompt of T (padded) positions attends through the
    splash kernel (`ops/attention.py`): on the chip, for lengths it tiles.
    An explicit CPU run takes the blocked product below, its oracle."""
    return (
        kernel_backend(splash.INTERPRET) != "cpu" and T >= 256 and T % 128 == 0
    )


def expanded_attend(cfg: TransformerConfig, ap: Params, q_nope, q_rope, row,
                    valid):
    """Causal attention of a chunk over ITS OWN positions 0 .. T - 1 with
    keys and values expanded from the chunk's rows -> [B, T, H, v]; `valid`
    [B, T] says which positions are tokens (a row's padding trails them).

    On the chip the splash kernel (a head's query and key 192 wide beside a
    value of 128; one row runs under a block mask narrowed by `valid`, so
    neither the blocks above the diagonal nor those of padding run).
    Elsewhere plain XLA: the queries go `_FRESH_BLOCK` at a time against
    all T keys under the causal mask, so that no [H, T, T] array of scores
    exists (19 TFLOP/s on the chip, 36 ms a sublayer at 4,096 positions
    where the whole prefill took 405: my chip run, PR 44)."""
    dtype = row.dtype
    B, T, H, _ = q_nope.shape
    w_k, w_v = _kv_b(cfg, ap, dtype)
    c, kr = row[..., :cfg.kv_lora_rank], row[..., cfg.kv_lora_rank:]
    k = jnp.concatenate([
        jnp.einsum("btc,hnc->bthn", c, w_k),
        jnp.broadcast_to(kr[:, :, None], (B, T, H, kr.shape[-1])),
    ], axis=-1)
    v = jnp.einsum("btc,hvc->bthv", c, w_v)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if _splash_applies(T):
        kernel = splash._make_kernel(
            T, 1, None, None, 1, interpret=splash.INTERPRET)
        return splash._splash_call(
            kernel, q, k, v, jnp.where(valid, 0, -1).astype(jnp.int32), 1)
    scale = cfg.head_dim_ ** -0.5
    qb = min(_FRESH_BLOCK, T)
    key_pos = jnp.arange(T, dtype=jnp.int32)

    def block(args):
        first, qs = args  # (), [B, qb, H, dq]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", qs, k, preferred_element_type=jnp.float32
        ) * scale
        q_pos = first + jnp.arange(qb, dtype=jnp.int32)
        p = _softmax_rows(scores, causal_window(q_pos, key_pos))
        return jnp.einsum("bhqk,bkhv->bqhv", p.astype(dtype), v)

    out = jax.lax.map(block, (
        jnp.arange(T // qb, dtype=jnp.int32) * qb,
        jnp.moveaxis(q.reshape(B, T // qb, qb, H, q.shape[-1]), 1, 0),
    ))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, -1)


def _two_part_softmax_sum(s_old, s_new, v_old, v_new, eq_old, eq_new, dtype):
    """softmax over the keys of both parts together, in float32, then the
    weighted sum of both parts' values: scores `s_old` [..., K] against the
    cached rows `v_old`, `s_new` [..., T] against this chunk's `v_new`
    (masked entries at the float32 minimum)."""
    m = jnp.maximum(s_old.max(-1), s_new.max(-1))[..., None]
    e_old, e_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
    total = e_old.sum(-1) + e_new.sum(-1)
    o = (jnp.einsum(eq_old, e_old.astype(dtype), v_old,
                    preferred_element_type=jnp.float32)
         + jnp.einsum(eq_new, e_new.astype(dtype), v_new,
                      preferred_element_type=jnp.float32))
    return o, total


def absorbed_attend(
    cfg: TransformerConfig,
    ap: Params,
    q_nope,  # [B, T, H, nope]
    q_rope,  # [B, T, H, rope]
    row,  # [B, T, row]: this chunk's rows, which the pool does not hold yet
    lat,  # the pool leaf [2 L, S, row, M]
    j,  # the sublayer
    at: Dict,
):
    """Attention over each slot's cached rows [0, `at["starts"]`) within the
    first K and this chunk's own rows, keys and values never expanded ->
    [B, T, H, v]: `W_kvb`'s key half folded into the query, scores against
    the latent rows themselves, the weighted sum of latent rows, then
    `W_kvb`'s value half.  The pool is only read (this chunk's rows are
    written after the last layer, all sublayers in one scatter: a write
    inside the traversal copies the pool, compiled for a described v5e);
    the softmax runs over cached and new keys together, as if the rows had
    been written first.  A decode step (T = 1) goes through the paged
    kernel (`at["ragged"]`: rows by length, inactive slots zeros) or reads
    the block's window in one product; a suffix goes row by row and a block
    of `_CACHED_BLOCK` queries at a time, blocks of padding skipped."""
    dtype = q_nope.dtype
    B, T, H, _ = q_nope.shape
    R, C = lat.shape[2], cfg.kv_lora_rank
    K, starts = at["K"], at["starts"]
    w_k, w_v = _kv_b(cfg, ap, dtype)
    scale = cfg.head_dim_ ** -0.5
    lowest = jnp.finfo(jnp.float32).min
    key_pos = jnp.arange(K, dtype=jnp.int32)
    with jax.named_scope("mla_q"):
        q = jnp.concatenate(
            [jnp.einsum("bthn,hnc->bthc", q_nope, w_k), q_rope], axis=-1
        )  # [B, T, H, row]
    with jax.named_scope("mla_attn"):
        if T == 1 and at["ragged"]:
            # the paged kernel: each live slot's rows by length, once, out
            # of the pool where it lies (no slice, no copy, no barrier)
            o = latent_decode_attention(
                q[:, 0], row[:, 0], lat, starts, at["live"], j=j,
                slot_base=at["slot_base"], kv_lora_rank=C, scale=scale,
            )[:, None]
        elif T == 1:
            # the window is read when its queries are there, not before:
            # left to itself the compiler reads every sublayer's window at
            # the top of the pass and holds them all (compiled for a
            # described v5e: 8 x 369 MB at 41 slots of 8,192)
            q, lat = jax.lax.optimization_barrier((q, lat))
            win = jax.lax.dynamic_slice(
                lat, (j, at["slot_base"], 0, 0), (1, B, R, K))[0].astype(dtype)
            # the mask as a [B, 1, K] term of the sum: as a select over
            # [B, H, K] its constant arm was materialised, 86 MB a sublayer
            # and pass with no scope (my traced run, PR 44)
            s_old = jnp.einsum(
                "bhc,bck->bhk", q[:, 0], win,
                preferred_element_type=jnp.float32,
            ) * scale + jnp.where(
                key_pos[None, None] < starts[:, None, None], 0.0, lowest)
            s_new = jnp.einsum(
                "bhc,bc->bh", q[:, 0], row[:, 0],
                preferred_element_type=jnp.float32,
            )[..., None] * scale
            o, total = _two_part_softmax_sum(
                s_old, s_new, win[:, :C], row[:, :, :C],
                "bhk,bck->bhc", "bht,btc->bhc", dtype)
            o = (o / total[..., None]).astype(dtype)[:, None]
        else:
            tb = min(_CACHED_BLOCK, T)
            nb = T // tb
            new_pos = jnp.arange(T, dtype=jnp.int32)

            def one_row(args):
                qr, new, slot, start, n = args  # [T, H, R], [T, R], (), (), ()
                win = jax.lax.dynamic_slice(
                    lat, (j, slot, 0, 0), (1, 1, R, K))[0, 0].astype(dtype)

                def one_block(args):
                    qs, first = args  # [tb, H, R], ()

                    def attend():
                        s_old = jnp.einsum(
                            "thc,ck->htk", qs, win,
                            preferred_element_type=jnp.float32,
                        ) * scale + jnp.where(key_pos < start, 0.0, lowest)
                        s_new = jnp.einsum(
                            "thc,uc->htu", qs, new,
                            preferred_element_type=jnp.float32,
                        ) * scale
                        s_new = jnp.where(
                            causal_window(first + jnp.arange(tb), new_pos),
                            s_new, lowest)
                        o, total = _two_part_softmax_sum(
                            s_old, s_new, win[:C], new[:, :C],
                            "htk,ck->thc", "htu,uc->thc", dtype)
                        return (o / total.T[..., None]).astype(dtype)

                    return jax.lax.cond(
                        first < n, attend,
                        lambda: jnp.zeros((tb, H, C), dtype),
                    )

                out = jax.lax.map(one_block, (
                    qr.reshape(nb, tb, H, R),
                    jnp.arange(nb, dtype=jnp.int32) * tb,
                ))
                return out.reshape(T, H, C)

            o = jax.lax.map(
                one_row, (q, row, at["slots"], starts, at["n_real"]))
    with jax.named_scope("mla_out"):
        return jnp.einsum("bthc,hvc->bthv", o, w_v)


def latent_attend(
    cfg: TransformerConfig,
    ap: Params,  # one sublayer's attention leaves
    h,  # [B, T, D] the normed stream
    cos,
    sin,
    lat,  # the pool leaf [2 L, S, row, M]
    j: int,  # the sublayer
    at: Dict,  # where the chunk lies (`forward_*` below build it)
):
    """This chunk's latent rows and its attention over the slot's rows ->
    (the sublayer's output [B, T, D], the rows [B, T, row] for the pool):
    the ONE attention-and-cache step of a fresh prompt (`at["fresh"]`:
    nothing is cached before the chunk, which attends its own positions
    expanded), of a suffix and of a decode step (both over the slot's
    cached rows and the chunk's own, absorbed).  `write_rows` puts the rows
    of every sublayer into the pool after the last layer."""
    dtype = h.dtype
    B, T, _ = h.shape
    q_nope, q_rope, row = mla_project(cfg, ap, h, cos, sin)
    # through the pool's dtype, as a row read back from it would be
    row = row.astype(lat.dtype)
    if at["fresh"]:
        with jax.named_scope("mla_attn"):
            o = expanded_attend(
                cfg, ap, q_nope, q_rope, row.astype(dtype), at["valid"])
    else:
        o = absorbed_attend(
            cfg, ap, q_nope, q_rope, row.astype(dtype), lat, j, at)
    with jax.named_scope("mla_out"):
        out = jnp.einsum(
            "bte,ed->btd", o.reshape(B, T, -1), ap["wo"].astype(dtype)
        )
    return out, row


def write_rows(lat, rows, at: Dict):
    """The chunk's rows of every sublayer into the pool, after the last
    layer: row b's first `at["n_write"][b]` positions at `at["starts"][b]`
    onward of slot `at["slots"][b]`, nothing else touched (no padding, no
    inactive slot's row, nothing past the pool's end).  One block a row,
    read, overlaid and put back where it lies: a scatter over (slot,
    position) makes the compiler lay the whole pool out anew, there and
    back, in every program (compiled for a described v5e)."""
    M = lat.shape[3]
    new = jnp.swapaxes(jnp.stack(rows), 2, 3)  # [2 L, B, row, T]
    B, T = new.shape[1], new.shape[3]
    offs = jnp.arange(T, dtype=jnp.int32)

    def one_row(b, lat):
        start, n = at["starts"][b], at["n_write"][b]
        # where a block of T positions holding `start` fits the pool
        first = jnp.clip(start, 0, M - T)
        index = (0, at["slots"][b], 0, first)
        block = jax.lax.dynamic_slice(
            lat, index, (new.shape[0], 1, new.shape[2], T))[:, 0]
        mine = jnp.roll(new[:, b], start - first, axis=-1)
        keep = (offs >= start - first) & (offs < start - first + n)
        block = jnp.where(keep, mine, block)
        return jax.lax.dynamic_update_slice(lat, block[:, None], index)

    with jax.named_scope("latent_write"):
        return jax.lax.fori_loop(0, B, one_row, lat)


def by_head(cfg: TransformerConfig, attn: Params) -> Params:
    """The stacked attention leaves with the query's up-projection viewed
    [L, 2, H, d_head, r] (stored [L, 2, H * d_head, r]; a head's 192 rows
    are whole tiles, so the view moves nothing).  The view is taken of the
    STACK, before `_sub` takes a sublayer: taken of the sublayer, behind
    the product, the compiler puts the bitcast between the sublayer's slice
    and the product and then copies the slice out instead of reading it as
    the product's operand: 302 MB of `wq_b` written and read again in every
    decode pass (compiled for a described v5e).  `wkv_b` stays as stored:
    `_kv_b` cuts each head's rows into a key and a value half, which no
    view of the stack makes a slice the product reads in place (its 134 MB
    are still copied a pass)."""
    w = attn["wq_b"]
    return {**attn, "wq_b": w.reshape(
        w.shape[:2] + (cfg.num_heads, -1) + w.shape[3:])}


def _sub(tree: Params, l: int, i: Optional[int] = None) -> Params:
    """Layer l (and sublayer i) of stacked leaves."""
    pick = (lambda a: a[l]) if i is None else (lambda a: a[l, i])
    return jax.tree_util.tree_map(pick, tree)


def double_layer(
    cfg: TransformerConfig,
    layers: Params,  # the stacked layers, "attn" through `by_head`
    l: int,
    x,  # [B, T, D]
    attend,  # (attention leaves, normed stream, sublayer) -> its output
    valid,  # bool [B, T]: rows somebody reads
):
    """Layer l by the equations at the top -> (y, the expert layer's
    counters)."""
    dtype = x.dtype
    eps = cfg.rms_norm_eps

    def norm(a, name, i):
        return rms_norm(a, layers[name][l, i], eps)

    def ffn(a, i):
        with jax.named_scope("ffn_dense"):
            mp = {"mlp": _sub(layers["mlp"], l, i)}
            B, T, D = a.shape
            if B * T <= _FFN_BLOCK:
                return _mlp(mp, a, dtype, cfg)
            # a long prompt: a block of tokens at a time (unrolled: a loop
            # would have the weights' slices copied in as its operands)
            flat = a.reshape(1, B * T, D)
            return jnp.concatenate([
                _mlp(mp, flat[:, i: i + _FFN_BLOCK], dtype, cfg)
                for i in range(0, B * T, _FFN_BLOCK)
            ], axis=1).reshape(B, T, D)

    a0 = x + attend(_sub(layers["attn"], l, 0), norm(x, "input_norm", 0), 2 * l)
    h0 = norm(a0, "post_attn_norm", 0)
    with jax.named_scope("moe"):
        moe = layers["moe"]
        s, counters = identity_moe_ffn(cfg, {
            "router": moe["router"][l], "router_bias": moe["router_bias"][l],
            "w_gate": moe["w_gate"], "w_up": moe["w_up"],
            "w_down": moe["w_down"], "block": l,
        }, h0, dtype, valid)
    b0 = a0 + ffn(h0, 0)
    a1 = b0 + attend(
        _sub(layers["attn"], l, 1), norm(b0, "input_norm", 1), 2 * l + 1)
    return a1 + ffn(norm(a1, "post_attn_norm", 1), 1) + s, counters


def _cache_forward(params: Params, cfg: TransformerConfig, x, cos, sin,
                   cache, at: Dict, valid):
    """Every layer over a chunk -> (final-norm hidden, the cache, the expert
    counters summed over the layers)."""
    lat = cache["lat"]
    counters = jnp.zeros((len(IDENTITY_MOE_COUNTERS),), jnp.int32)
    rows = []

    def attend(ap, h, j):
        out, row = latent_attend(cfg, ap, h, cos, sin, lat, j, at)
        rows.append(row)
        return out

    with jax.named_scope("layers"):
        layers = params["layers"]
        layers = {**layers, "attn": by_head(cfg, layers["attn"])}
        for l in range(cfg.num_layers):
            x, c = double_layer(cfg, layers, l, x, attend, valid)
            counters = counters + c
        lat = write_rows(lat, rows, at)
    with jax.named_scope("final_norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, {**cache, "lat": lat}, counters


def forward_prefill(params, cfg, input_ids, prompt_lens, cache, slot_ids):
    """`transformer.forward_prefill` of this kind -> (last-token logits,
    the cache with the prompts' rows in `slot_ids`)."""
    S, T = input_ids.shape
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (S, T))
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
        x = _embed(params, cfg, input_ids, dtype, positions=positions)
        valid = positions < prompt_lens[:, None]
    at = {"fresh": True, "slots": slot_ids, "valid": valid,
          "starts": jnp.zeros((S,), jnp.int32), "n_write": prompt_lens}
    x, cache, _ = _cache_forward(params, cfg, x, cos, sin, cache, at, valid)
    return _last_token_logits(params, cfg, x, prompt_lens, dtype), cache


def forward_prefill_cached(
    params, cfg, input_ids, starts, suffix_lens, cache, slot_ids,
    copy_src=None, copy_block: int = 0, key_window: Optional[int] = None,
):
    """`transformer.forward_prefill_cached` of this kind: the rows of
    [0, copy_block) come from `copy_src` first (a sibling takes the shared
    prompt's columns, as K/V columns are taken), then the suffix's rows are
    written and attend the slot's first `key_window` rows."""
    S, T = input_ids.shape
    M = cache["lat"].shape[3]
    if copy_block and copy_src is not None:
        from areal_tpu.ops.kv_copy import copy_kv_prefix

        cache = {**cache, **copy_kv_prefix(
            {"lat": cache["lat"]}, copy_src, slot_ids, copy_block)}
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        offs = jnp.arange(T, dtype=jnp.int32)
        positions = starts[:, None] + offs[None, :]
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
        x = _embed(params, cfg, input_ids, dtype, positions=positions)
        valid = offs[None, :] < suffix_lens[:, None]
    at = {
        "fresh": False, "slots": slot_ids, "starts": starts,
        "n_real": suffix_lens, "n_write": jnp.minimum(suffix_lens, M - starts),
        "K": min(key_window, M) if key_window else M,
    }
    x, cache, _ = _cache_forward(params, cfg, x, cos, sin, cache, at, valid)
    return _last_token_logits(params, cfg, x, suffix_lens, dtype), cache


def forward_decode(
    params, cfg, tokens, lengths, cache, key_window: Optional[int] = None,
    slot_base: int = 0, active=None, ragged: bool = False, **_,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """One decode step of the block of slots from `slot_base` -> (logits
    [B, V], new cache, counters int32 by `DECODE_COUNTERS`).  The rows are
    stepped where they lie (one tier, the identity page table), as a hybrid
    stack's are; an inactive slot writes nothing.  `ragged` (static) takes
    the paged kernel over the pool for the attention of every sublayer."""
    B = tokens.shape[0]
    M = cache["lat"].shape[3]
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        positions = lengths[:, None].astype(jnp.int32)
        cos, sin = rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
        x = _embed(params, cfg, tokens[:, None], dtype, positions=positions)
        # as `transformer.forward_decode`: a slot past the window clamps
        # into its last column, an inactive one writes nothing
        at_pos = jnp.minimum(lengths, K - 1).astype(jnp.int32)
        live = jnp.ones((B,), bool) if active is None else active
        rows_read = jnp.sum(jnp.where(live, at_pos + 1, 0), dtype=jnp.int32)
    at = {
        "fresh": False, "slots": slot_base + jnp.arange(B, dtype=jnp.int32),
        "starts": at_pos, "n_write": live.astype(jnp.int32), "K": K,
        "slot_base": slot_base, "live": live, "ragged": ragged,
    }
    x, cache, counters = _cache_forward(
        params, cfg, x, cos, sin, cache, at, live[:, None])
    counters = jnp.concatenate(
        [counters, (rows_read * cfg.attn_sublayers)[None]])
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x[:, 0], dtype), cache, counters


def init_params(cfg: TransformerConfig, rng: jax.Array, dense) -> Params:
    """The stacked double layers: leaves of the two attention sublayers,
    the two dense FFNs and their four norms carry [L, 2, ...] (weights
    [in, out], but the two up-projections out of the latents, `wq_b` and
    `wkv_b`, [out, in]: the chip's compiler wants them so and transposed
    them otherwise; my traced run, PR 44.  That did NOT end the 436 MB
    copied in a decode chunk: those were every sublayer's slice of `wq_b`,
    302 MB, and of `wkv_b`, 134 MB, written out in every decode PASS
    because a reshape by head stood between the slice and its product;
    `by_head` takes `wq_b`'s away, `wkv_b`'s are still copied); the expert
    layer the router over ALL outputs (routed and identity experts), its
    selection bias (float32, zero: a buffer) and the experts held here,
    [L, held, ...]."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, V, F, L = (cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size,
                  cfg.num_layers)
    H, rq, rkv = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Fm = cfg.moe_intermediate_size
    lo, hi = cfg.held_range
    outputs = cfg.num_experts + cfg.zero_expert_num
    keys = iter(jax.random.split(rng, 16))
    layers = {
        "attn": {
            "wq_a": dense(next(keys), (L, 2, D, rq), D),
            "q_norm": jnp.ones((L, 2, rq), pdt),
            "wq_b": dense(next(keys), (L, 2, H * (nope + rope), rq), rq),
            "wkv_a": dense(next(keys), (L, 2, D, rkv + rope), D),
            "kv_norm": jnp.ones((L, 2, rkv), pdt),
            "wkv_b": dense(next(keys), (L, 2, H * (nope + vd), rkv), rkv),
            "wo": dense(next(keys), (L, 2, H * vd, D), H * vd),
        },
        "mlp": {
            "w_gate": dense(next(keys), (L, 2, D, F), D),
            "w_up": dense(next(keys), (L, 2, D, F), D),
            "w_down": dense(next(keys), (L, 2, F, D), F),
        },
        "input_norm": jnp.ones((L, 2, D), pdt),
        "post_attn_norm": jnp.ones((L, 2, D), pdt),
        "moe": {
            "router": dense(next(keys), (L, D, outputs), D),
            "router_bias": jnp.zeros((L, outputs), jnp.float32),
            "w_gate": dense(next(keys), (L, hi - lo, D, Fm), D),
            "w_up": dense(next(keys), (L, hi - lo, D, Fm), D),
            "w_down": dense(next(keys), (L, hi - lo, Fm, D), Fm),
        },
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
    refuses tp > 1 and ep > 1 for this kind: latent attention under `tp`
    and the exchange between expert shares are not built)."""
    shapes = jax.eval_shape(
        lambda: init_params(
            cfg, jax.random.PRNGKey(0), lambda k, shape, fan: jnp.zeros(shape))
    )
    specs = jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)), shapes)
    specs["embedding"] = P(vocab_axis, None)
    if "lm_head" in specs:
        specs["lm_head"] = P(None, vocab_axis)
    return specs
