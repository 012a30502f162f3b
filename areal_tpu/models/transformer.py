"""Functional decoder-only transformer, TPU-first.

Capability counterpart of the reference's model runtimes (lite: HF
AutoModelForCausalLM under FSDP2, areal/engine/base_hf_engine.py:46; legacy:
ReaLModel, realhf/impl/model/nn/real_llm_api.py:100 with flash-attn varlen
attention, realhf/impl/model/modules/attn.py:307).  Design differences:

- Pure functions over a parameter pytree; no module system.  `jax.jit`
  closes over the static `TransformerConfig`.
- **Layer stacking + `lax.scan`**: all layers' weights live in single leaves
  with a leading `num_layers` axis.  One layer is traced/compiled once
  regardless of depth, and `jax.checkpoint` gives per-layer rematerialisation
  (the HBM/FLOPs trade the reference gets from torch activation ckpt).
- **Packed sequences via segment ids**: variable-length batches arrive as a
  flat token buffer `[B, T]` (usually B=1) with `segment_ids`; attention
  masks `seg_i == seg_j & causal`, replacing flash-attn varlen cu_seqlens.
  Padding tokens carry segment_id -1 and attend to nothing.
- **A heterogeneous stack** (`cfg.layer_kinds`; nemotron_h, jamba): blocks
  of five kinds (Mamba-2, attention, latent mixture of experts; Mamba-1 and
  a dense gated FFN), each ONE mixer under one pre-norm and one residual.
  Weights are stacked per kind and one traversal follows the pattern with
  static kinds (`_hybrid_traverse`): unrolled, but for a run of Mamba and
  FFN blocks that repeats, which is ONE `lax.scan` over its repeats
  (`_hybrid_plan`).  A slot of the serving cache then holds the recurrent
  state and convolution window of every Mamba block AND the K/V columns of
  every attention block.
- Compute in bf16 on the MXU, master params fp32; softmax and norms in fp32.
- Sharding is expressed once in `param_partition_specs` and applied by the
  engine via NamedSharding; GSPMD inserts the collectives.
"""
# areal-lint: hot-path

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from areal_tpu.models.model_config import (
    ATTN,
    FFN,
    LAYER_KINDS,
    MAMBA,
    MAMBA1,
    MOE,
    SSM_KINDS,
    TransformerConfig,
)
from areal_tpu.ops.attention import (  # noqa: F401 — re-exported for gen paths
    make_attention_mask,
    naive_attention as attention,
    block_counts as splash_block_counts,
    record_impl as record_attention_impl,
    segment_attention,
    splash_supported,
)
from areal_tpu.ops.mamba2 import (
    causal_conv,
    conv_step,
    gated_group_norm,
    ssd_chunked,
    ssd_step,
)
from areal_tpu.ops.mamba1 import (
    StateAt,
    admit_tokens as mamba1_admit_tokens,
    selective_scan_chunked,
    selective_step,
)
from areal_tpu.ops.power_retention import (
    RetentionState,
    feature_dim as retention_feature_dim,
    retention_chunked,
    retention_step,
)
from areal_tpu.ops.ragged_decode import kernel_refusal, ragged_paged_attention

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, unit_offset: bool = False
) -> jax.Array:
    """`unit_offset` reads the weight as zero-centered (effective scale
    1 + w) — the gemma-family convention."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if unit_offset:
        w = 1.0 + w
    return (x * w).astype(dtype)


def layer_norm(
    x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float
) -> jax.Array:
    """Mean-centred LayerNorm with bias (gpt2 family), fp32 numerics."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (
        x * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(dtype)


def _norm(
    cfg: TransformerConfig, x: jax.Array, tree: Params, name: str
) -> jax.Array:
    """Normalise with the config's norm flavour; `tree[name]` is the weight,
    `tree[name + "_b"]` the LayerNorm bias."""
    if cfg.norm_type == "layernorm":
        return layer_norm(x, tree[name], tree[name + "_b"], cfg.rms_norm_eps)
    return rms_norm(x, tree[name], cfg.rms_norm_eps, cfg.norm_unit_offset)


def _act(cfg: TransformerConfig):
    if cfg.hidden_act == "silu":
        return jax.nn.silu
    if cfg.hidden_act in ("gelu_pytorch_tanh", "gelu_tanh"):
        return functools.partial(jax.nn.gelu, approximate=True)
    if cfg.hidden_act == "gelu":
        return functools.partial(jax.nn.gelu, approximate=False)
    raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")


def _embed(
    params: Params,
    cfg: TransformerConfig,
    ids: jax.Array,
    dtype,
    positions: Optional[jax.Array] = None,
):
    x = jnp.take(params["embedding"].astype(dtype), ids, axis=0)
    if cfg.scale_embeddings:
        # gemma multiplies by sqrt(D) rounded in the compute dtype
        x = x * jnp.asarray(cfg.hidden_size**0.5, dtype)
    if cfg.pos_emb == "learned":
        x = x + jnp.take(
            params["pos_embedding"].astype(dtype), positions, axis=0
        )
    return x


def _layer_sliding_flags(cfg: TransformerConfig) -> jax.Array:
    """bool [L]: whether each layer uses the sliding window (gemma2
    alternation); all-False when windows are uniform/absent."""
    if cfg.sliding_window is not None and cfg.layer_is_sliding is not None:
        return jnp.asarray(cfg.layer_is_sliding, bool)
    return jnp.zeros((cfg.num_layers,), bool)


def _head_logits(params: Params, cfg: TransformerConfig, x: jax.Array, dtype):
    head = params.get("lm_head")
    if head is None:
        head = params["embedding"].T
    eq = "btd,dv->btv" if x.ndim == 3 else "bd,dv->bv"
    logits = jnp.einsum(eq, x, head.astype(dtype))
    if cfg.final_logit_softcap:
        cap = cfg.final_logit_softcap
        logits = jnp.tanh(logits / cap) * cap
    return logits


def rope_cos_sin(positions: jax.Array, head_dim: int, theta: float):
    """positions [B, T] -> cos/sin [B, T, head_dim//2] in fp32."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B,T,hd/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, T, H, hd]; HF 'half rotation' convention (rotate_half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]  # [B,T,1,hd/2]
    sin = sin[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Layer / model forward
# ---------------------------------------------------------------------------


def _ffn(cfg: TransformerConfig, lp: Params, h: jax.Array, dtype, valid=None):
    """Dense MLP or MoE block, by what the layer holds; returns (out,
    aux-loss scalar fp32), or (out, counters int32 [4]) from the gated
    experts at a share (`models/moe.py gated_moe_ffn`)."""
    if "moe" not in lp:
        return _mlp(lp, h, dtype, cfg), jnp.zeros((), jnp.float32)
    if cfg.ffn_kinds is not None:
        from areal_tpu.models.moe import gated_moe_ffn

        return gated_moe_ffn(cfg, lp["moe"], h, dtype, valid)
    from areal_tpu.models.moe import moe_ffn

    return moe_ffn(cfg, lp["moe"], h, dtype)


def _attn_inputs(cfg: TransformerConfig, lp: Params, x, cos, sin, dtype):
    """Input norm, q/k/v projections (bias, q/k norm) and RoPE: what every
    layer variant does before it touches the cache or attends."""
    with jax.named_scope("attn_qkv"):
        h = _norm(cfg, x, lp, "input_norm")
        q, k, v = _qkv(cfg, lp, h, dtype)
        if cfg.pos_emb == "rope" and cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    return q, k, v


def _attn_out_and_ffn(
    cfg: TransformerConfig,
    lp: Params,
    x: jax.Array,  # [B, T, D] residual stream
    attn: jax.Array,  # [B, T, H, hd] attention output
    dtype,
    name_outputs: bool = False,  # tag mlp_out for the remat policies
    valid: Optional[jax.Array] = None,  # bool [B, T]: rows that are tokens
):
    """Output projection + residual, then the FFN block + residual: what
    every layer variant does after attention.  Returns (x, MoE aux loss or
    `_ffn`'s expert counters)."""
    B, T = attn.shape[:2]
    if cfg.attn_gate:
        with jax.named_scope("attn_gate"):
            # the input-normed stream once more (`_attn_inputs` has it too;
            # the compiler keeps one)
            h = _norm(cfg, x, lp, "input_norm")
            gate = jax.nn.sigmoid(
                _proj(cfg, lp["attn"], "wg", h, x.dtype).astype(jnp.float32)
            )
            attn = (attn.reshape(B, T, cfg.q_size) * gate).astype(x.dtype)
    with jax.named_scope("attn_out"):
        delta = _proj(
            cfg, lp["attn"], "wo", attn.reshape(B, T, cfg.q_size), dtype,
            bias="bo",
        )
        if cfg.sandwich_norms:
            delta = _norm(cfg, delta, lp, "sandwich_attn_norm")
        x = x + delta
    with jax.named_scope("moe" if "moe" in lp else "mlp"):
        h = _norm(cfg, x, lp, "post_attn_norm")
        ffn_out, aux = _ffn(cfg, lp, h, dtype, valid)
        if name_outputs:
            ffn_out = jax.ad_checkpoint.checkpoint_name(ffn_out, "mlp_out")
        if cfg.sandwich_norms:
            ffn_out = _norm(cfg, ffn_out, lp, "sandwich_ffn_norm")
        return x + ffn_out, aux


def is_retention(cfg: TransformerConfig) -> bool:
    if cfg.attn_kind in ("softmax", "latent", "windowed"):
        return False
    if cfg.attn_kind != "power_retention":
        raise ValueError(
            f"unknown attn_kind {cfg.attn_kind!r}; use 'softmax', "
            "'power_retention', 'latent' or 'windowed'"
        )
    return True


def is_latent(cfg: TransformerConfig) -> bool:
    """Latent attention in double layers (`models/latent.py`): a slot of
    the serving cache holds one latent row a position and sublayer."""
    return cfg.attn_kind == "latent"


def is_windowed(cfg: TransformerConfig) -> bool:
    """Full and sliding layers with their own kv heads in one stack
    (`models/windowed.py`): a slot of the serving cache holds columns for
    the full layers and a ring of the window for the sliding ones."""
    return cfg.attn_kind == "windowed"


def _retention_layer(
    cfg: TransformerConfig,
    lp: Params,
    x: jax.Array,  # [B, T, D]
    cos: jax.Array,
    sin: jax.Array,
    seg: jax.Array,  # [B, T] segment ids; < 0 = padding
    state: Optional[RetentionState] = None,
    decode: bool = False,  # T == 1, one recurrent step against `state`
    active: Optional[jax.Array] = None,  # decode: False leaves the state
    name_outputs: bool = False,
    pool_at: Optional[tuple] = None,  # decode on the kernel: (layer, first
    # slot); `state` is then the pool's leaves, stepped where they lie
):
    """One decoder block of the power-retention kind, in every mode: train
    (no state in, the state out dropped; a new segment id resets), prefill
    (no state in), continue (`state` in) and decode (one step).  Returns
    (x, MoE aux loss, state with the block's tokens in it)."""
    dtype = x.dtype
    q, k, v = _attn_inputs(cfg, lp, x, cos, sin, dtype)
    with jax.named_scope("attn_qkv"):
        # one scalar gate a kv head, from the input-normed residual
        h = _norm(cfg, x, lp, "input_norm")
        log_g = jax.nn.log_sigmoid(
            jnp.einsum(
                "btd,dh->bth", h, lp["attn"]["wg"].astype(dtype),
                preferred_element_type=jnp.float32,
            )
        )
    with jax.named_scope("retention"):
        if pool_at is not None:
            # imported where the decode branch is traced: a process that
            # trains or prefills never loads the kernel's module
            from areal_tpu.ops.retention_decode import retention_decode_step

            y, s, z = retention_decode_step(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state.s, state.z,
                jnp.ones(q.shape[:1], bool) if active is None else active,
                layer=pool_at[0], slot_base=pool_at[1],
                degree=cfg.retention_degree,
            )
            y, state = y[:, None], RetentionState(s, z)
        elif decode:
            y, state = retention_step(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state, active=active,
                degree=cfg.retention_degree,
            )
            y = y[:, None]
        else:
            y, state = retention_chunked(
                q, k, v, log_g, seg, state0=state,
                chunk=cfg.retention_chunk, degree=cfg.retention_degree,
            )
        if name_outputs:
            y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
    x, aux = _attn_out_and_ffn(cfg, lp, x, y, dtype, name_outputs=name_outputs)
    return x, aux, state


def is_hybrid(cfg: TransformerConfig) -> bool:
    """A heterogeneous stack: blocks of `cfg.layer_kinds`, one mixer each.
    The cache forwards are built for a hybrid Mamba stack: BOTH Mamba
    blocks, of ONE of the two recurrences (a slot's state leaf has one
    shape), and attention blocks (a slot holds state and columns);
    anything else is refused."""
    if cfg.layer_kinds is None:
        return False
    kinds = set(cfg.layer_kinds)
    if (kinds - set(LAYER_KINDS) or ATTN not in kinds
            or len(kinds & set(SSM_KINDS)) != 1
            or len(cfg.layer_kinds) != cfg.num_layers):
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: {cfg.num_layers} of "
            f"{LAYER_KINDS} wanted for a hybrid Mamba stack, {ATTN!r} and "
            f"exactly one of {SSM_KINDS} among them"
        )
    return True


# leaves of the serving cache that hold one column a position (the others
# hold a state of fixed size)
COLUMN_LEAVES = ("k", "v", "lat")


def _ssm_conv(lp: Params, x, seg, window, decode: bool, active):
    """The causal depthwise convolution of a Mamba block, of either
    recurrence, and its SiLU -> (x, the window with the tokens in it): a
    sequence (`causal_conv`) or one column against the window (decode)."""
    with jax.named_scope("ssm_conv"):
        if decode:
            x, window = conv_step(
                x[:, 0], lp["conv_w"], lp["conv_b"], window, active
            )
            x = x[:, None]
        else:
            x, window = causal_conv(x, lp["conv_w"], lp["conv_b"], seg, window)
        return jax.nn.silu(x), window


def _mamba_block(
    cfg: TransformerConfig,
    lp: Params,
    x: jax.Array,  # [B, T, D]
    seg: jax.Array,  # [B, T] segment ids; < 0 = padding
    state: Optional[jax.Array] = None,  # [B, H, P, N] float32
    window: Optional[jax.Array] = None,  # [B, K - 1, conv_dim]
    decode: bool = False,  # T == 1, one recurrent step
    active: Optional[jax.Array] = None,  # decode: False leaves state + window
):
    """One Mamba-2 block in every mode (train and prefill: no state in;
    continue: state and window in; decode: one step) -> (x, state, window)
    with the block's tokens in them."""
    dtype = x.dtype
    B, T, _ = x.shape
    H, Pd = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N = cfg.mamba_n_groups, cfg.ssm_state_size
    d_in, conv_dim = cfg.mamba_d_inner, cfg.mamba_conv_dim
    f32 = jnp.float32
    with jax.named_scope("ssm"):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        zxd = jnp.einsum("btd,de->bte", h, lp["w_in"].astype(dtype))
        z, xbc, dt = jnp.split(zxd, [d_in, d_in + conv_dim], axis=-1)
        xbc, window = _ssm_conv(lp, xbc, seg, window, decode, active)
        xs, bm, cm = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        A = -jnp.exp(lp["A_log"].astype(f32))
        xs = xs.reshape(B, T, H, Pd)
        bm, cm = bm.reshape(B, T, G, N), cm.reshape(B, T, G, N)
        with jax.named_scope("ssm_scan"):
            if decode:
                y, state = ssd_step(
                    xs[:, 0], dt[:, 0], A, bm[:, 0], cm[:, 0], lp["D"], state,
                    active=active,
                )
                y = y[:, None]
            else:
                y, state = ssd_chunked(
                    xs, dt, A, bm, cm, lp["D"], seg, state0=state,
                    chunk=cfg.mamba_chunk,
                )
        y = gated_group_norm(
            y.reshape(B, T, d_in), z, lp["gate_norm"], G, cfg.rms_norm_eps
        )
        out = jnp.einsum("bte,ed->btd", y, lp["w_out"].astype(dtype))
        return x + out, state, window


def _mamba1_block(
    cfg: TransformerConfig,
    lp: Params,
    x: jax.Array,  # [B, T, D]
    seg: jax.Array,  # [B, T] segment ids; < 0 = padding
    state=None,  # [B, N, d_inner] float32; decode on the kernel: a
    # `StateAt` (the pool leaf whole, the layer, the block's first slot)
    window: Optional[jax.Array] = None,  # [B, K - 1, d_inner]
    decode: bool = False,  # T == 1, one recurrent step
    active: Optional[jax.Array] = None,  # decode: False leaves state + window
):
    """One Mamba-1 block (jamba) in every mode, as `_mamba_block` has them
    -> (x, state, window).  [u | z] = W_in h; u through the causal
    convolution and SiLU; [r | B | C] = W_x u, each under an RMS norm of
    its own (the family's addition to Mamba-1); dt = softplus(W_dt r +
    b_dt) in float32, one a channel; the selective scan
    (`ops/mamba1.py`); out = W_out (y * SiLU(z)).  A decode step given its
    states where they lie steps them there (`ops/mamba1_decode.py`) and
    hands the pool leaf back as the state."""
    dtype = x.dtype
    d_in, N, R = cfg.mamba_d_inner, cfg.ssm_state_size, cfg.mamba_dt_rank
    f32 = jnp.float32
    eps = cfg.rms_norm_eps
    with jax.named_scope("ssm"):
        h = rms_norm(x, lp["input_norm"], eps)
        uz = jnp.einsum("btd,de->bte", h, lp["w_in"].astype(dtype))
        u, z = jnp.split(uz, [d_in], axis=-1)
        u, window = _ssm_conv(lp, u, seg, window, decode, active)
        rbc = jnp.einsum("bte,er->btr", u, lp["w_x"].astype(dtype))
        r, bm, cm = jnp.split(rbc, [R, R + N], axis=-1)
        r = rms_norm(r, lp["dt_norm"], eps)
        bm = rms_norm(bm, lp["b_norm"], eps)
        cm = rms_norm(cm, lp["c_norm"], eps)
        dt = jax.nn.softplus(
            jnp.einsum("btr,re->bte", r, lp["w_dt"].astype(dtype)).astype(f32)
            + lp["dt_bias"].astype(f32)
        )
        A = -jnp.exp(lp["A_log"].astype(f32))
        with jax.named_scope("ssm_scan"):
            if decode:
                token = (u[:, 0], dt[:, 0], A, bm[:, 0], cm[:, 0], lp["D"])
                if isinstance(state, StateAt):
                    # imported here: a process that trains never loads the
                    # kernel's module
                    from areal_tpu.ops.mamba1_decode import (
                        selective_decode_step,
                    )

                    y, state = selective_decode_step(
                        *token, state.pool, active, layer=state.layer,
                        slot_base=state.slot_base,
                    )
                else:
                    y, state = selective_step(*token, state, active=active)
                y = y[:, None]
            else:
                y, state = selective_scan_chunked(
                    u, dt, A, bm, cm, lp["D"], seg, state0=state
                )
        y = y * jax.nn.silu(z)
        out = jnp.einsum("bte,ed->btd", y, lp["w_out"].astype(dtype))
        return x + out, state, window


def _ffn_block(cfg: TransformerConfig, lp: Params, x: jax.Array):
    """One dense gated FFN block of a hybrid stack: pre-norm, gate / up /
    down, residual."""
    with jax.named_scope("ffn_dense"):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        return x + _mlp(lp, h, x.dtype, cfg)


_SSM_BLOCKS = {MAMBA: _mamba_block, MAMBA1: _mamba1_block}
# the kinds a run of the traversal may hold: no columns, no counters
_RUN_KINDS = frozenset(_SSM_BLOCKS) | {FFN}


def _attn_block(cfg: TransformerConfig, lp: Params, x: jax.Array, attend):
    """One attention block of a hybrid stack: pre-norm, q/k/v (no bias, no
    rotary embedding: the family carries no positional encoding),
    `attend(q, k, v)`, output projection, residual -> (x, (k, v))."""
    dtype = x.dtype
    B, T, _ = x.shape
    q, k, v = _attn_inputs(cfg, lp, x, None, None, dtype)
    attn, kv = attend(q, k, v)
    with jax.named_scope("attn_out"):
        out = _proj(cfg, lp["attn"], "wo", attn.reshape(B, T, cfg.q_size), dtype)
        return x + out, kv


def _moe_block(cfg: TransformerConfig, lp: Params, x: jax.Array, valid):
    """One latent mixture-of-experts block -> (x, counters int32 [2])."""
    from areal_tpu.models.moe import latent_moe_ffn

    with jax.named_scope("moe"):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        out, counters = latent_moe_ffn(cfg, lp, h, x.dtype, valid)
        return x + out, counters


@functools.lru_cache(maxsize=None)
def _hybrid_plan(kinds: tuple) -> tuple:
    """A hybrid stack's blocks as ((period of kinds, repeats), ...) in
    order: a run of Mamba and FFN blocks that repeats a period of at most
    four blocks twice or more is one entry (stepped by a scan over its
    repeats, so a program holds the period once), every other block an
    entry of its own (repeats 1, unrolled).  Attention and expert blocks
    are never in a run: their columns and counters leave the traversal
    block by block.  Jamba's 56 blocks: 7 x (S -), *, 13 x (- S), -, *,
    6 x (- S), -; nemotron_h's M E M E M E M * E M E has no such run and
    stays unrolled, block for block what it was."""
    plan, i, n = [], 0, len(kinds)
    while i < n:
        best = ((kinds[i],), 1)
        for p in range(1, 5):
            period = kinds[i: i + p]
            if len(period) < p or not set(period) <= _RUN_KINDS:
                break
            r = 1
            while kinds[i + r * p: i + (r + 1) * p] == period:
                r += 1
            if r > 1 and p * r > len(best[0]) * best[1]:
                best = (period, r)
        plan.append(best)
        i += len(best[0]) * best[1]
    return tuple(plan)


def _hybrid_traverse(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,  # [B, T, D] embedded tokens
    seg: jax.Array,  # [B, T]; < 0 = padding
    attend,  # (j, q, k, v) -> (attention output, what to keep of k, v)
    cache=None,
    state_in=None,  # (cache, j) -> (state, window) of Mamba block j
    state_out=None,  # (cache, j, state, window) -> cache
    decode: bool = False,
    active: Optional[jax.Array] = None,
    remat: bool = False,
):
    """The one traversal of a hybrid stack: the blocks in the pattern's
    order, each kind reading the j-th slice of its own stacked weights,
    unrolled but for the runs `_hybrid_plan` finds -> (final-norm hidden,
    cache, [kept k/v of each attention block], expert counters summed over
    the expert blocks)."""
    valid = seg >= 0 if active is None else active[:, None]
    counters = jnp.zeros((2,), jnp.int32)
    kept = []
    nth = dict.fromkeys(LAYER_KINDS, 0)

    def wrap(fn):
        return jax.checkpoint(fn) if remat else fn

    def ssm_or_ffn(kind, lp, j, x, cache):
        """Block j (static, or traced inside a run's scan) of a kind
        without columns and without counters -> (x, cache)."""
        if kind == FFN:
            return wrap(functools.partial(_ffn_block, cfg))(lp, x), cache
        state, window = (
            state_in(cache, j) if state_in is not None else (None, None)
        )
        x, state, window = wrap(functools.partial(
            _SSM_BLOCKS[kind], cfg, decode=decode
        ))(lp, x, seg, state, window, active=active)
        if state_out is not None:
            cache = state_out(cache, j, state, window)
        return x, cache

    def run(period, repeats, first, x, cache):
        """`repeats` times the blocks of `period`, the first of them block
        `first[kind]` of its kind: one scan, each step reading its blocks
        out of the kinds' whole stacks where they lie (a slice of a stack
        handed to the scan would be written out first)."""
        per = {k: period.count(k) for k in set(period)}

        def step(carry, i):
            x, cache = carry
            seen = dict.fromkeys(per, 0)
            for kind in period:
                j = first[kind] + i * per[kind] + seen[kind]
                seen[kind] += 1
                lp = jax.tree_util.tree_map(
                    lambda a, j=j: jax.lax.dynamic_index_in_dim(
                        a, j, keepdims=False),
                    params["layers"][kind],
                )
                x, cache = ssm_or_ffn(kind, lp, j, x, cache)
            return (x, cache), None

        (x, cache), _ = jax.lax.scan(
            step, (x, cache), jnp.arange(repeats, dtype=jnp.int32))
        return x, cache

    with jax.named_scope("layers"):
        for period, repeats in _hybrid_plan(cfg.layer_kinds):
            if repeats > 1:
                x, cache = run(period, repeats, dict(nth), x, cache)
                for kind in period:
                    nth[kind] += repeats
                continue
            (kind,) = period
            j = nth[kind]
            nth[kind] += 1
            # block j of its kind; the routed experts stay stacked (below)
            stack = params["layers"][kind]
            lp = jax.tree_util.tree_map(
                lambda a, j=j: a[j],
                {k: v for k, v in stack.items() if k not in ("w1", "w2")},
            )
            if kind in _RUN_KINDS:
                x, cache = ssm_or_ffn(kind, lp, j, x, cache)
            elif kind == ATTN:
                x, kv = _attn_block(cfg, lp, x, functools.partial(attend, j))
                kept.append(kv)
            else:
                # the routed experts of ALL expert blocks go in whole, with
                # this block's index: a slice of them would be copied out
                # for the grouped product (1.4 GB a block a pass; compiled
                # for a described v5e, PR 32)
                lp = {**lp, "w1": stack["w1"], "w2": stack["w2"], "block": j}
                x, c = wrap(functools.partial(_moe_block, cfg))(lp, x, valid)
                counters = counters + c
    with jax.named_scope("final_norm"):
        x = _norm(cfg, x, params, "final_norm")
    return x, cache, kept, counters


def _splash_applies(cfg: TransformerConfig, T: int, sp: int) -> bool:
    """Whether the cache-free forward over rows of T takes the splash
    kernel (ring attention aside: that needs the mesh and is asked first)."""
    return (
        cfg.attn_impl != "naive"
        and not is_retention(cfg)
        and not is_hybrid(cfg)
        # splash masks are static per kernel: gemma2's layer scan chooses
        # by a traced flag; a stack whose scan step unrolls a period of the
        # pattern (`_dense_moe_layers`) knows each layer's kind statically
        and not (cfg.sliding_window is not None
                 and cfg.layer_is_sliding is not None
                 and cfg.ffn_kinds is None)
        and splash_supported(
            T, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_, sp=sp
        )
    )


def attention_block_counts(
    cfg: TransformerConfig,
    segment_ids: jax.Array,  # int32 [B, T]
    mesh: Optional[Mesh] = None,
) -> Dict[str, jax.Array]:
    """`attn_blocks_run` / `attn_blocks_causal`: the blocks ONE layer's
    splash forward runs for one kv head over these rows, and the blocks the
    static mask alone would run (`ops/attention.py block_counts`).  A stack
    that mixes sliding and full layers gives both pairs, `_local` and
    `_global`, each for one layer of its kind.  Empty where the forward
    does not take the splash kernel."""
    shape = dict(mesh.shape) if mesh is not None else {}
    sp = shape.get("sp", 1)
    if (cfg.attn_impl == "ring" and sp > 1) or not _splash_applies(
        cfg, segment_ids.shape[1], sp
    ):
        return {}

    def counts(window):
        return splash_block_counts(
            segment_ids,
            cfg.num_heads // cfg.num_kv_heads,
            window,
            cfg.attn_logit_softcap,
            sp=sp,
            row_shards=(shape.get("dp", 1) * shape.get("fsdp", 1)
                        * shape.get("ep", 1)),
        )

    if cfg.layer_is_sliding is None:
        run, causal = counts(cfg.sliding_window)
        return {"attn_blocks_run": run, "attn_blocks_causal": causal}
    out = {}
    for kind, window in (("local", cfg.sliding_window), ("global", None)):
        if (kind == "local") in cfg.layer_is_sliding:
            run, causal = counts(window)
            out[f"attn_blocks_run_{kind}"] = run
            out[f"attn_blocks_causal_{kind}"] = causal
    return out


def _layer_forward(
    cfg: TransformerConfig,
    mesh: Optional[Mesh],
    lp: Params,  # this layer's params (no leading L axis)
    x: jax.Array,  # [B, T, D]
    cos: jax.Array,
    sin: jax.Array,
    seg: jax.Array,  # [B, T] segment ids
    pos: jax.Array,  # [B, T] positions
    mask: Optional[jax.Array],  # [B, 1, T, T] — naive path only
    sliding: Optional[bool] = None,  # STATIC: this layer's kind, where a
    # stack mixes sliding and full layers and knows each layer's statically;
    # None = the config's one window (or none) for every layer
):
    """One decoder block (cache-free; the generation paths below thread
    their own cache through the same _qkv/_ffn primitives)."""
    if is_retention(cfg):
        x, aux, _ = _retention_layer(
            cfg, lp, x, cos, sin, seg, name_outputs=True
        )
        return x, aux
    dtype = x.dtype
    by_kind = sliding is not None
    window = cfg.sliding_window if sliding in (None, True) else None
    # a layer kind of its own applies the rotary embedding itself, under
    # its own scope (afmoe: sliding layers only)
    rope_here = by_kind and cfg.pos_emb == "rope" and (
        sliding or cfg.rope_layers == "all"
    )
    q, k, v = _attn_inputs(
        cfg, lp, x, None if by_kind else cos, None if by_kind else sin, dtype
    )
    with jax.named_scope("attn"):
        with jax.named_scope(
            "attn_local" if sliding else "attn_global"
        ) if by_kind else contextlib.nullcontext():
            if rope_here:
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            if mask is not None:
                attn_out = attention(q, k, v, mask, cfg.attn_logit_softcap)
            else:
                attn_out = segment_attention(
                    q,
                    k,
                    v,
                    seg,
                    pos,
                    sliding_window=window,
                    logit_softcap=cfg.attn_logit_softcap,
                    impl="ring" if cfg.attn_impl == "ring" else "splash",
                    mesh=mesh,
                )
        attn_out = jax.ad_checkpoint.checkpoint_name(attn_out, "attn_out")
    return _attn_out_and_ffn(
        cfg, lp, x, attn_out, dtype, name_outputs=True, valid=seg >= 0
    )


def _remat_checkpoint_kwargs(cfg: TransformerConfig) -> dict:
    """jax.checkpoint kwargs for the config's remat rung.  Applied around
    one layer (layer_group_size == 1) or one unrolled group of layers — the
    policy composes per checkpoint boundary either way."""
    if cfg.remat_policy == "dots":
        return dict(
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    if cfg.remat_policy == "save_attn":
        # keep the tagged attention outputs (checkpoint_name in
        # _layer_forward): the backward pass recomputes projections and
        # MLP but not the attention kernel — ~50 MB/layer at 16k tokens,
        # the selective policy that still fits 16G v5e
        return dict(
            policy=jax.checkpoint_policies.save_only_these_names("attn_out")
        )
    if cfg.remat_policy == "save_mlp":
        # keep the tagged MLP outputs instead (ROADMAP 3b probe): the
        # backward pass recomputes attention but not the MLP — the rung
        # between save_attn and full on the memory/recompute ladder
        return dict(
            policy=jax.checkpoint_policies.save_only_these_names("mlp_out")
        )
    if cfg.remat_policy == "carry_offload":
        # keep BOTH tagged outputs but park them in pinned host memory:
        # the residuals leave HBM entirely, trading the pressure that
        # kills save_attn compiles for PCIe traffic the backward can
        # overlap with recompute.  Requires a runtime with host memory
        # spaces (TPU); CPU test rigs may fail to lower — the bench
        # ladder records the per-rung compile outcome either way.
        return dict(
            policy=jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["attn_out", "mlp_out"],
                offload_src="device",
                offload_dst="pinned_host",
            )
        )
    if cfg.remat_policy == "full":
        return {}
    raise ValueError(
        f"unknown remat_policy {cfg.remat_policy!r}; use 'full', "
        "'save_attn', 'save_mlp', 'carry_offload', or 'dots'"
    )


def effective_scan_unroll(cfg: TransformerConfig) -> int:
    """The unroll factor the layer scan will actually use.

    `scan_unroll` must divide the OUTER scan length (num_layers /
    layer_group_size).  Non-divisors fall back to 1 — loudly: the silent
    fallback this replaces let a mistuned config quietly forfeit the
    unrolling win for whole rounds.  Engines record this value in train
    stats / bench JSON so the regression is visible in artifacts too."""
    if cfg.ffn_kinds is not None:
        # a scan a kind of block (`_kind_scan_plan`), each unrolled by the
        # largest divisor of its steps up to `scan_unroll`: the expert
        # layers' is the one reported
        _, _, n, period = _kind_scan_plan(cfg)[-1]
        return _steps_unroll(cfg, n // period)
    u = max(1, cfg.scan_unroll)
    n = cfg.num_layers // max(1, cfg.layer_group_size)
    if n % u:
        import warnings

        warnings.warn(
            f"scan_unroll={cfg.scan_unroll} does not divide the outer layer-"
            f"scan length {n} (num_layers={cfg.num_layers}, "
            f"layer_group_size={cfg.layer_group_size}); falling back to "
            "unroll=1 — pick a divisor to get the requested unrolling",
            stacklevel=2,
        )
        return 1
    return u


def _backbone(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,
    positions: jax.Array,
    segment_ids: jax.Array,
    mesh: Optional[Mesh] = None,
    inputs_embeds: Optional[jax.Array] = None,  # [B, T, D] (VLM merge)
    rope: Optional[tuple] = None,  # (cos, sin) override (mrope)
):
    """Layer scan -> (final-norm hidden [B, T, D], summed MoE aux loss; or,
    from a stack of gated experts at a share, its expert counters)."""
    if is_latent(cfg):
        raise NotImplementedError(
            "longcat_flash (latent attention in double layers around a "
            "shortcut expert layer) is built for the cache forwards only: "
            "the packed training forward of the double layer is not built"
        )
    if is_windowed(cfg):
        raise NotImplementedError(
            "mimo_v2 (full and sliding layers with their own kv heads, a "
            "sink in the sliding softmax) is built for the cache forwards "
            "only: the packed training forward is not built"
        )
    if cfg.lora_rank:
        # freeze everything but the adapters: XLA prunes the base bwd pass
        from areal_tpu.models.lora import freeze_base

        params = freeze_base(params, True)
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if inputs_embeds is not None:
            x = inputs_embeds.astype(dtype)
        else:
            x = _embed(params, cfg, input_ids, dtype, positions=positions)
        cos, sin = rope if rope is not None else rope_cos_sin(
            positions, cfg.head_dim_, cfg.rope_theta
        )

    if is_hybrid(cfg):
        # whole sequences, no cache: every Mamba block starts empty (a new
        # segment id resets it), attention is the dense masked product
        with jax.named_scope("embed"):
            mask = make_attention_mask(segment_ids, positions, None)

        def attend(j, q, k, v):
            with jax.named_scope("attn"):
                return attention(q, k, v, mask), None

        x, _, _, _ = _hybrid_traverse(
            params, cfg, x, segment_ids, attend, remat=cfg.remat
        )
        return x, jnp.zeros((), jnp.float32)

    B, T = input_ids.shape
    sp = mesh.shape["sp"] if mesh is not None else 1
    if cfg.ffn_kinds is not None:
        return _dense_moe_layers(
            params, cfg, x, cos, sin, segment_ids, positions, mesh
        )
    per_layer_window = (
        cfg.sliding_window is not None and cfg.layer_is_sliding is not None
    )
    # ring attention: K/V sequence-sharded over sp with rotating blocks —
    # the context-parallel regime (ops/attention.py ring_attention)
    use_ring = (
        cfg.attn_impl == "ring"
        and not per_layer_window
        and mesh is not None
        and mesh.shape.get("sp", 1) > 1
    )
    if cfg.attn_impl == "ring" and not use_ring:
        # requesting ring implies the O(T/sp) memory regime was wanted —
        # falling back silently would surprise at long context (trace-time
        # warning: fires once per compiled shape)
        import warnings

        reason = (
            "per-layer sliding windows (gemma2) are mask-based"
            if per_layer_window
            else "the mesh has no sp>1 axis"
        )
        warnings.warn(
            f"attn_impl='ring' requested but unused: {reason}; falling "
            "back to the splash/naive ladder",
            stacklevel=2,
        )
    retention = is_retention(cfg)
    use_splash = not use_ring and _splash_applies(cfg, T, sp)
    record_attention_impl(
        "retention" if retention
        else "ring" if use_ring else "splash" if use_splash else "einsum",
        T, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
    )
    # the splash/ring paths never materialise a mask; naive builds
    # [B,1,T,T] once.  With per-layer windows (gemma2) both variants are
    # built once and each scan step selects by the layer's flag.
    mask_win = None
    with jax.named_scope("embed"):
        if per_layer_window:
            mask = make_attention_mask(segment_ids, positions, None)
            mask_win = make_attention_mask(
                segment_ids, positions, cfg.sliding_window
            )
        elif use_splash or use_ring or retention:
            mask = None
        else:
            mask = make_attention_mask(
                segment_ids, positions, cfg.sliding_window
            )

    layer_fn = functools.partial(_layer_forward, cfg, mesh)
    ckpt_kwargs = _remat_checkpoint_kwargs(cfg) if cfg.remat else None

    G = max(1, cfg.layer_group_size)
    if cfg.num_layers % G:
        raise ValueError(
            f"layer_group_size={cfg.layer_group_size} must divide "
            f"num_layers={cfg.num_layers}: a trailing partial group would "
            "silently change the remat boundary — pick a divisor"
        )
    n_groups = cfg.num_layers // G

    def one_layer(lp, sliding, x):
        m = mask
        if mask_win is not None:
            m = jnp.where(sliding, mask_win, mask)
        return layer_fn(lp, x, cos, sin, segment_ids, positions, m)

    if G == 1:
        # classic single-level scan; the remat policy wraps each layer
        if ckpt_kwargs is not None:
            layer_fn = jax.checkpoint(layer_fn, **ckpt_kwargs)

        def scan_body(carry, xs):
            lp, sliding = xs
            x, aux_sum = carry
            x, aux = one_layer(lp, sliding, x)
            return (x, aux_sum + aux), None

        xs = (params["layers"], _layer_sliding_flags(cfg))
    else:
        # two-level scan: the outer scan runs n_groups steps, each an
        # unrolled chain of G layers behind ONE checkpoint at the group
        # boundary.  Only the inter-group activation is saved (everything
        # inside the group is recomputed under `full`, or kept per the
        # selective policy), so the backward scan-transpose carry holds
        # n_groups entries instead of num_layers — ~G× fewer
        # dynamic-update-slice carry writes.
        def group_fn(gp, gflags, x):
            aux = jnp.zeros((), jnp.float32)
            for i in range(G):
                lp = jax.tree_util.tree_map(lambda a, i=i: a[i], gp)
                x, a = one_layer(lp, gflags[i], x)
                aux = aux + a
            return x, aux

        if ckpt_kwargs is not None:
            group_fn = jax.checkpoint(group_fn, **ckpt_kwargs)

        def scan_body(carry, xs):
            gp, gflags = xs
            x, aux_sum = carry
            x, aux = group_fn(gp, gflags, x)
            return (x, aux_sum + aux), None

        xs = (
            jax.tree_util.tree_map(
                lambda a: a.reshape((n_groups, G) + a.shape[1:]),
                params["layers"],
            ),
            _layer_sliding_flags(cfg).reshape(n_groups, G),
        )

    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(
            scan_body,
            (x, jnp.zeros((), jnp.float32)),
            xs,
            unroll=effective_scan_unroll(cfg),
            _split_transpose=cfg.scan_split_transpose,
        )
    with jax.named_scope("final_norm"):
        return _norm(cfg, x, params, "final_norm"), aux


def _steps_unroll(cfg: TransformerConfig, steps: int) -> int:
    """The largest divisor of a scan's `steps` up to `cfg.scan_unroll`."""
    return max(
        u for u in range(1, max(1, cfg.scan_unroll) + 1) if steps % u == 0
    )


def _kind_scan_plan(cfg: TransformerConfig):
    """[(kind, first layer, layers, layers a scan step)] for the runs of a
    stack of gated experts behind leading dense layers: each run of one FFN
    kind is ONE `lax.scan` over its stacked parameters whose step holds the
    shortest period of the run's sliding / full pattern (a multiple of
    `layer_group_size`), so that inside a step every layer's kind of
    attention is static.  A pattern that does not repeat is one step."""
    G = max(1, cfg.layer_group_size)
    plan, first = [], 0
    for kind in ("dense", "moe"):
        n = cfg.ffn_kinds.count(kind)
        if not n:
            continue
        if n % G:
            raise ValueError(
                f"layer_group_size={G} must divide the {n} {kind} layers of "
                "this stack: a checkpoint does not span two kinds of block"
            )
        sliding = cfg.layer_is_sliding[first:first + n]
        period = next(
            p for p in range(G, n + 1, G)
            if n % p == 0 and all(sliding[i] == sliding[i % p] for i in range(n))
        )
        plan.append((kind, first, n, period))
        first += n
    return plan


def _dense_moe_layers(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,  # [B, T, D] embedded tokens
    cos: jax.Array,
    sin: jax.Array,
    segment_ids: jax.Array,
    positions: jax.Array,
    mesh: Optional[Mesh],
):
    """The layers of a stack of gated experts behind leading dense layers
    (`cfg.ffn_kinds`, afmoe) -> (final-norm hidden, expert counters int32
    [3]: assignments to held experts summed over the expert layers, the
    fullest held expert's rows, max over them, and the rows of the sorted
    buffers, summed).

    The two kinds of block have parameter trees of different shapes
    (`layers["dense"]`, `layers["moe"]`, each stacked over its own blocks),
    and a layer's attention is sliding or full by `cfg.layer_is_sliding`:
    each kind is a layer scan of its own (`_kind_scan_plan`) whose step
    unrolls one period of the pattern with the kinds static.  A sliding
    layer runs the splash kernel built on `LocalMask`, a full one the
    kernel built on `CausalMask`, both in this one program and both
    narrowed by the row's segment ids, and no [T, T] mask is built where
    splash applies.  Remat as the dense path has it: one `jax.checkpoint`
    under the config's policy around every `layer_group_size` layers."""
    T = x.shape[1]
    sp = mesh.shape["sp"] if mesh is not None else 1
    if cfg.attn_impl == "ring":
        import warnings

        warnings.warn(
            "attn_impl='ring' requested but unused: per-layer sliding "
            "windows take the splash/naive ladder",
            stacklevel=2,
        )
        cfg = cfg.replace(attn_impl="auto")
    use_splash = _splash_applies(cfg, T, sp)
    record_attention_impl(
        "splash" if use_splash else "einsum",
        T, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
    )
    masks = {True: None, False: None}
    if not use_splash:
        with jax.named_scope("embed"):
            masks = {
                sliding: make_attention_mask(
                    segment_ids, positions,
                    cfg.sliding_window if sliding else None,
                )
                for sliding in set(cfg.layer_is_sliding)
            }
    G = max(1, cfg.layer_group_size)
    ckpt_kwargs = _remat_checkpoint_kwargs(cfg) if cfg.remat else None

    def merged(counters, c):
        return jnp.stack([
            counters[0] + c[0], jnp.maximum(counters[1], c[1]),
            counters[2] + c[2],
        ])

    def group_fn(lps, x, *, kind, sliding):
        counters = jnp.zeros((3,), jnp.int32)
        for lp, s in zip(lps, sliding):
            x, c = _layer_forward(
                cfg, mesh, lp, x, cos, sin, segment_ids, positions,
                masks[s], sliding=s,
            )
            if kind == "moe":
                counters = merged(counters, c)
        return x, counters

    counters = jnp.zeros((3,), jnp.int32)
    with jax.named_scope("layers"):
        for kind, first, n, period in _kind_scan_plan(cfg):
            pattern = cfg.layer_is_sliding[first:first + period]

            def step(carry, sp_, kind=kind, pattern=pattern):
                x, counters = carry
                for g in range(0, len(pattern), G):
                    fn = functools.partial(
                        group_fn, kind=kind, sliding=pattern[g:g + G]
                    )
                    if ckpt_kwargs is not None:
                        fn = jax.checkpoint(fn, **ckpt_kwargs)
                    x, c = fn(
                        [jax.tree_util.tree_map(lambda a, i=i: a[i], sp_)
                         for i in range(g, g + G)],
                        x,
                    )
                    counters = merged(counters, c)
                return (x, counters), None

            steps = n // period
            (x, counters), _ = jax.lax.scan(
                step,
                (x, counters),
                jax.tree_util.tree_map(
                    lambda a: a.reshape((steps, period) + a.shape[1:]),
                    params["layers"][kind],
                ),
                unroll=_steps_unroll(cfg, steps),
                _split_transpose=cfg.scan_split_transpose,
            )
    with jax.named_scope("final_norm"):
        return _norm(cfg, x, params, "final_norm"), counters


def forward_hidden(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,  # int32 [B, T]
    positions: jax.Array,  # int32 [B, T]
    segment_ids: jax.Array,  # int32 [B, T], -1 = padding
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Backbone forward -> final-norm hidden states [B, T, D] (for value /
    reward heads, the role of the reference's critic models)."""
    x, _ = _backbone(params, cfg, input_ids, positions, segment_ids, mesh=mesh)
    return x


def forward(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,  # int32 [B, T]
    positions: jax.Array,  # int32 [B, T]
    segment_ids: jax.Array,  # int32 [B, T], -1 = padding
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Full forward -> logits [B, T, V] (in cfg.dtype; softmax-sensitive
    consumers should upcast)."""
    dtype = jnp.dtype(cfg.dtype)
    x = forward_hidden(params, cfg, input_ids, positions, segment_ids, mesh=mesh)
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x, dtype)


class LMOutput(NamedTuple):
    """Deferred language-model head: final-norm hidden states + head matrix.

    Train-path losses consume this instead of materialised logits so the
    [tokens, vocab] matrix (2.4 GB bf16 / 4.9 GB fp32 at 8k tokens on a 151k
    vocab — the round-1 OOM wall) only ever exists one chunk at a time inside
    `ops.functional.lm_logprobs_entropy`'s rematerialised scan.

    `aux_loss` carries the MoE load-balancing penalty (already scaled by
    cfg.moe_aux_coef; 0 for dense models) — losses fold it in per token.
    """

    hidden: jax.Array  # [B, T, D] in compute dtype
    head: jax.Array  # [D, V] in compute dtype
    aux_loss: Optional[jax.Array] = None  # scalar fp32
    # gemma2 final-logit tanh cap; consumers (ops.functional) must apply it
    # to every logits chunk.  Static python float, never a traced leaf.
    logit_softcap: Optional[float] = None
    # what the forward counted (int32 scalars by stat name: a stack of
    # experts at a share says how many rows its held experts took); the
    # train step adds them to its stats
    counters: Optional[Dict[str, jax.Array]] = None


def forward_lm(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,
    positions: jax.Array,
    segment_ids: jax.Array,
    mesh: Optional[Mesh] = None,
) -> LMOutput:
    """Backbone forward with a *deferred* LM head (see LMOutput)."""
    dtype = jnp.dtype(cfg.dtype)
    x, aux = _backbone(params, cfg, input_ids, positions, segment_ids, mesh=mesh)
    with jax.named_scope("lm_head"):
        head = params.get("lm_head")
        if head is None:
            head = params["embedding"].T
        if cfg.lora_rank:
            head = jax.lax.stop_gradient(head)
        head = head.astype(dtype)
    if cfg.ffn_kinds is not None:
        # gated experts at a share: no balancing loss is built; `aux` holds
        # the expert counters
        return LMOutput(
            hidden=x, head=head, logit_softcap=cfg.final_logit_softcap,
            counters={
                "expert_assignments_held": aux[0], "expert_load_max": aux[1],
                "expert_rows_buffered": aux[2],
            },
        )
    return LMOutput(
        hidden=x,
        head=head,
        aux_loss=aux * cfg.moe_aux_coef if cfg.num_experts > 0 else None,
        logit_softcap=cfg.final_logit_softcap,
    )


def forward_packed(params: Params, cfg: TransformerConfig, packed: Dict[str, jax.Array]):
    """Convenience wrapper over a packed dict (flat [T] buffers)."""
    ids = packed["input_ids"][None, :]
    pos = packed["positions"][None, :]
    seg = packed["segment_ids"][None, :]
    return forward(params, cfg, ids, pos, seg)[0]


# ---------------------------------------------------------------------------
# KV-cache forward paths (generation engine)
# ---------------------------------------------------------------------------
#
# The decode-time counterpart of the reference's native generation runtime
# (realhf/impl/model/nn/real_llm_generate.py KV-cache decode loop) and of the
# SGLang servers it normally delegates to.  Cache layout is layer-stacked to
# match the scan parameter layout:
#     k, v: [L, S, M, Hkv, hd]   (S = batch slots, M = max seq len)
# Both entry points are shape-static: prefill takes a padded prompt bucket,
# decode advances every slot by exactly one token.


def _proj(
    cfg: TransformerConfig,
    sub: Params,
    leaf: str,
    x: jax.Array,
    dtype,
    bias: Optional[str] = None,
):
    """x @ W (+ bias leaf if present, + LoRA delta when adapted)."""
    out = jnp.einsum("btd,dh->bth", x, sub[leaf].astype(dtype))
    if bias is not None and bias in sub:
        out = out + sub[bias].astype(dtype)
    if cfg.lora_rank:
        from areal_tpu.models.lora import lora_delta, lora_scale

        d = lora_delta(sub, leaf, x, dtype, lora_scale(cfg))
        if d is not None:
            out = out + d
    return out


def _qkv(cfg: TransformerConfig, lp: Params, h: jax.Array, dtype):
    q = _proj(cfg, lp["attn"], "wq", h, dtype)
    k = _proj(cfg, lp["attn"], "wk", h, dtype)
    v = _proj(cfg, lp["attn"], "wv", h, dtype)
    if cfg.qkv_bias:
        q = q + lp["attn"]["bq"].astype(dtype)
        k = k + lp["attn"]["bk"].astype(dtype)
        v = v + lp["attn"]["bv"].astype(dtype)
    B, T = h.shape[:2]
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim_)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim_)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim_)
    if cfg.qk_norm:
        q = _norm(cfg, q, lp["attn"], "q_norm")
        k = _norm(cfg, k, lp["attn"], "k_norm")
    if cfg.query_pre_attn_scalar is not None:
        # attention kernels scale scores by head_dim^-0.5; pre-scaling q
        # makes the net softmax scale query_pre_attn_scalar^-0.5 (gemma2)
        q = q * jnp.asarray(
            cfg.head_dim_**0.5 / cfg.query_pre_attn_scalar**0.5, q.dtype
        )
    return q, k, v


def _mlp(lp: Params, h: jax.Array, dtype, cfg: Optional[TransformerConfig] = None):
    act = jax.nn.silu if cfg is None else _act(cfg)
    if cfg is not None and not cfg.mlp_gated:
        # gpt2-style: up-project, activate, down-project.  _proj applies
        # bias leaves when present and LoRA deltas when adapted.
        up = _proj(cfg, lp["mlp"], "w_up", h, dtype, bias="b_up")
        return _proj(cfg, lp["mlp"], "w_down", act(up), dtype, bias="b_down")
    if cfg is not None and cfg.lora_rank:
        gate = _proj(cfg, lp["mlp"], "w_gate", h, dtype)
        up = _proj(cfg, lp["mlp"], "w_up", h, dtype)
        return _proj(cfg, lp["mlp"], "w_down", act(gate) * up, dtype)
    gate = jnp.einsum("btd,df->btf", h, lp["mlp"]["w_gate"].astype(dtype))
    up = jnp.einsum("btd,df->btf", h, lp["mlp"]["w_up"].astype(dtype))
    return jnp.einsum(
        "btf,fd->btd", act(gate) * up, lp["mlp"]["w_down"].astype(dtype)
    )


def kv_cache_partition_specs(cfg: TransformerConfig) -> Dict[str, P]:
    """What a slot of the serving cache holds, by the model's kind, and how
    it is sharded: the kv-head axis over "tp" (a latent row has none)."""
    if is_latent(cfg):
        return {"lat": P(None, None, None, None)}
    if is_windowed(cfg):
        from areal_tpu.models import windowed

        return windowed.cache_partition_specs()
    if is_retention(cfg):
        return {
            "s": P(None, None, "tp", None, None),
            "z": P(None, None, "tp", None),
        }
    kv = {
        "k": P(None, None, None, "tp", None),
        "v": P(None, None, None, "tp", None),
    }
    if is_hybrid(cfg):
        # the Mamba heads over "tp"; the window's channels mix x, B, C
        # (Mamba-1: the state's channels, last)
        s = (P(None, None, None, "tp") if cfg.ssm_kind == MAMBA1
             else P(None, None, "tp", None, None))
        return {**kv, "s": s, "c": P(None, None, None, None)}
    return kv


def init_kv_cache(
    cfg: TransformerConfig,
    n_slots: int,
    max_len: int,
    dtype: str = "bfloat16",
    shardings: Optional[Dict[str, Any]] = None,
) -> Dict[str, jax.Array]:
    """Softmax: columns `k`, `v` [L, S, M, Hkv, hd] in `dtype`.  Power
    retention: the state `s` [L, S, Hkv, F, hd] and its normaliser `z`
    [L, S, Hkv, F], always float32: sums of hundreds of terms that a
    narrower state would round away (`max_len` and `dtype` size nothing
    there).  A hybrid stack: `k`, `v` for its attention blocks only
    [n_attn, S, M, Hkv, hd], and for its Mamba blocks the state `s`
    [n_ssm, S, H, P, N] (Mamba-2) or [n_ssm, S, N, d_inner] (Mamba-1: the
    channels last, where the chip's lanes are), always float32, and the
    convolution window `c` [n_ssm, S, K - 1, conv_dim] in `dtype`.  Latent
    attention: the rows
    `lat` [sublayers, S, kv_lora_rank + qk_rope_head_dim, M] in `dtype`,
    one a position, no head axis, the positions LAST (`models/latent.py`).
    A windowed stack: `k`, `v` for its full layers [n_full, S, M, Hkv * ..]
    and, for its sliding layers, the rings `wk`, `wv` [n_sliding, S, W,
    Hkv_swa * ..] of W = `cfg.window_ring` positions and no `max_len` axis,
    the kv heads side by side in one row (`models/windowed.py`).
    With `shardings` each leaf is made in place on its devices: a pool of
    gigabytes is never held twice."""
    if is_latent(cfg):
        leaves = {"lat": (
            (cfg.attn_sublayers, n_slots, cfg.latent_row_dim, max_len),
            jnp.dtype(dtype),
        )}
    elif is_windowed(cfg):
        from areal_tpu.models import windowed

        leaves = windowed.cache_leaves(cfg, n_slots, max_len, dtype)
    elif is_hybrid(cfg):
        n_attn, n_ssm = cfg.n_kind(ATTN), cfg.n_kind(cfg.ssm_kind)
        shape = (n_attn, n_slots, max_len, cfg.num_kv_heads, cfg.head_dim_)
        state = (
            (cfg.ssm_state_size, cfg.mamba_d_inner)
            if cfg.ssm_kind == MAMBA1
            else (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size)
        )
        leaves = {
            "k": (shape, jnp.dtype(dtype)),
            "v": (shape, jnp.dtype(dtype)),
            "s": ((n_ssm, n_slots) + state, jnp.float32),
            "c": ((n_ssm, n_slots, cfg.conv_kernel - 1, cfg.mamba_conv_dim),
                  jnp.dtype(dtype)),
        }
    elif is_retention(cfg):
        F = retention_feature_dim(cfg.head_dim_, cfg.retention_degree)
        L, Hkv = cfg.num_layers, cfg.num_kv_heads
        leaves = {
            "s": ((L, n_slots, Hkv, F, cfg.head_dim_), jnp.float32),
            "z": ((L, n_slots, Hkv, F), jnp.float32),
        }
    else:
        shape = (
            cfg.num_layers, n_slots, max_len, cfg.num_kv_heads, cfg.head_dim_
        )
        leaves = {"k": (shape, jnp.dtype(dtype)), "v": (shape, jnp.dtype(dtype))}
    return {
        name: jnp.zeros(
            shape, dt, device=None if shardings is None else shardings[name]
        )
        for name, (shape, dt) in leaves.items()
    }


def _retention_cache_forward(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,  # [B, T, D] embedded tokens
    cos: jax.Array,
    sin: jax.Array,
    seg: jax.Array,  # [B, T]; < 0 = padding
    cache: Dict[str, jax.Array],
    read_rows: Optional[jax.Array] = None,  # int32 [B]; None = start empty
    write_rows: Optional[jax.Array] = None,  # int32 [B]
    block: Optional[tuple] = None,  # decode: STATIC (first row, rows)
    active: Optional[jax.Array] = None,  # decode: bool [B]
    ragged: bool = False,  # decode: the kernel of ops/retention_decode.py
):
    """The layer scan of every cache forward of the retention kind ->
    (final-norm hidden, new cache).  The pool rides the scan's CARRY and
    each layer touches only its own rows of it, in place: as a scanned
    input and output the pool would be sliced and stacked whole, layer by
    layer, and held twice.  Prefill starts empty and writes `write_rows`;
    continuation reads `read_rows` (a sibling's rows are its
    representative's: the fan-out copy) and writes `write_rows`; decode
    steps the contiguous `block` of rows: sliced out, stepped by
    `retention_step` and written back, or with `ragged` by the kernel that
    reads each live row of the pool once and writes it where it lay."""
    L = cfg.num_layers

    def layer(carry, xs):
        x, cs, cz = carry
        lp, l = xs
        state = None
        if block is not None and ragged:
            x, _, (cs, cz) = _retention_layer(
                cfg, lp, x, cos, sin, seg, state=RetentionState(cs, cz),
                decode=True, active=active, pool_at=(l, block[0]),
            )
            return (x, cs, cz), None
        if block is not None:
            lo, n = block
            tail_s, tail_z = cs.shape[2:], cz.shape[2:]
            state = RetentionState(
                jax.lax.dynamic_slice(
                    cs, (l, lo, 0, 0, 0), (1, n) + tail_s
                )[0],
                jax.lax.dynamic_slice(
                    cz, (l, lo, 0, 0), (1, n) + tail_z
                )[0],
            )
        elif read_rows is not None:
            with jax.named_scope("state_copy"):
                # the layer first, then its rows: one gather over both
                # axes ran four times slower on the chip (PR 27)
                state = RetentionState(
                    jnp.take(cs[l], read_rows, axis=0),
                    jnp.take(cz[l], read_rows, axis=0),
                )
        x, _, state = _retention_layer(
            cfg, lp, x, cos, sin, seg, state=state,
            decode=block is not None, active=active,
        )
        with jax.named_scope("retention"):
            if block is not None:
                cs = jax.lax.dynamic_update_slice(
                    cs, state.s[None], (l, lo, 0, 0, 0)
                )
                cz = jax.lax.dynamic_update_slice(
                    cz, state.z[None], (l, lo, 0, 0)
                )
            else:
                cs = cs.at[l, write_rows].set(state.s)
                cz = cz.at[l, write_rows].set(state.z)
        return (x, cs, cz), None

    with jax.named_scope("layers"):
        (x, cs, cz), _ = jax.lax.scan(
            layer,
            (x, cache["s"], cache["z"]),
            (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        )
    with jax.named_scope("final_norm"):
        x = _norm(cfg, x, params, "final_norm")
    return x, {"s": cs, "z": cz}


def _hybrid_state_io(
    read_rows: Optional[jax.Array],  # int32 [B]; None = start empty
    write_rows: Optional[jax.Array],  # int32 [B]
    block: Optional[tuple],  # decode: STATIC (first row, rows)
    where_it_lies: bool = False,  # decode: the kernel of ops/mamba1_decode.py
):
    """How the Mamba blocks of a cache forward reach the pool -> (state_in,
    state_out) of `_hybrid_traverse`.  The pool leaves are touched in
    place, one block's rows at a time.  Prefill starts empty and writes
    `write_rows`; continuation reads `read_rows` (a sibling's rows are its
    representative's: the fan-out copy) and writes `write_rows`; decode
    steps the contiguous `block` of rows: sliced out, stepped and written
    back, or, `where_it_lies`, the state leaf is handed to the block whole
    with the place of its rows (`StateAt`) and comes back stepped by the
    kernel, which reads each live row once and writes it where it lay (the
    window, a tenth of the bytes, is sliced and written back either way)."""

    def state_in(cache, j):
        cs, cc = cache["s"], cache["c"]
        if block is not None:
            lo, n = block
            state = StateAt(cs, j, lo) if where_it_lies else (
                jax.lax.dynamic_slice(
                    cs, (j, lo) + (0,) * (cs.ndim - 2),
                    (1, n) + cs.shape[2:])[0])
            return state, jax.lax.dynamic_slice(
                cc, (j, lo, 0, 0), (1, n) + cc.shape[2:])[0]
        if read_rows is None:
            return None, None
        with jax.named_scope("state_copy"):
            return (
                jnp.take(cs[j], read_rows, axis=0),
                jnp.take(cc[j], read_rows, axis=0),
            )

    def state_out(cache, j, state, window):
        cs, cc = cache["s"], cache["c"]
        with jax.named_scope("ssm"):
            if block is not None:
                lo = block[0]
                cs = state if where_it_lies else jax.lax.dynamic_update_slice(
                    cs, state[None], (j, lo) + (0,) * (cs.ndim - 2))
                cc = jax.lax.dynamic_update_slice(
                    cc, window[None].astype(cc.dtype), (j, lo, 0, 0))
            else:
                cs = cs.at[j, write_rows].set(state)
                cc = cc.at[j, write_rows].set(window.astype(cc.dtype))
        return {**cache, "s": cs, "c": cc}

    return state_in, state_out


def _write_columns(leaf, cols, where, **kw):
    """The new columns of a hybrid stack's attention blocks into the pool
    leaf `k` or `v` at `where` (rows, positions).  One block: one scatter
    over the leaf, as it always was.  Several: one scatter a block, because
    ONE scatter over several layers makes the chip's compiler lay the leaf
    out with the layers beside the head size and copy it whole, in and out
    of every decode pass (0.8 GB a leaf at 2 x 385 x 4,096; compiled for a
    described v5e, PR 52)."""
    if len(cols) == 1:
        return leaf.at[(slice(None),) + where].set(jnp.stack(cols), **kw)
    for j, c in enumerate(cols):
        leaf = leaf.at[(j,) + where].set(c, **kw)
    return leaf


def _hybrid_append_and_attend(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,  # [B, T, D] embedded tokens
    seg: jax.Array,  # [B, T]
    cache: Dict[str, jax.Array],
    mask: jax.Array,  # [B, 1, T, K]
    *,
    widx: jax.Array,  # int32 [B, T] write positions; M = the write drops
    rows: Optional[jax.Array],  # int32 [B] physical rows, None = the block
    slot_base: int,
    K: int,
    read_rows: Optional[jax.Array] = None,
    block: Optional[tuple] = None,
    active: Optional[jax.Array] = None,
    ragged: bool = False,  # decode: the state kernel (`_hybrid_state_io`)
):
    """Suffix prefill and decode of a hybrid stack -> (final-norm hidden,
    new cache, expert counters): an attention block attends its rows' first
    K cached columns plus the T new ones exactly as `_append_and_attend`'s
    dense layer does, and the new columns of all attention blocks are
    written by one scatter after the traversal; a Mamba block continues
    from its rows' state and window."""
    B = x.shape[0]
    dtype = x.dtype
    ck, cv = cache["k"], cache["v"]
    with jax.named_scope("embed"):
        hit = _new_column_hits(widx, K, ck.shape[2])

    def attend(j, q, k, v):
        k, v = k.astype(ck.dtype), v.astype(cv.dtype)
        with jax.named_scope("kv_write"):
            kw, vw = (
                _with_new_columns(
                    win.astype(dtype), new.astype(dtype), widx[:, 0], hit
                )
                for win, new in zip(
                    _cache_window(ck, cv, j, rows, slot_base, B, K), (k, v)
                )
            )
        with jax.named_scope("attn"):
            return attention(q, kw, vw, mask), (k, v)

    state_in, state_out = _hybrid_state_io(
        read_rows, rows if block is None else None, block, ragged
    )
    x, cache, kept, counters = _hybrid_traverse(
        params, cfg, x, seg, attend, cache, state_in, state_out,
        decode=block is not None, active=active,
    )
    slots = rows if rows is not None else slot_base + jnp.arange(B)
    cache = dict(cache)
    with jax.named_scope("kv_write"):
        for name, cols in zip(("k", "v"), zip(*kept)):
            cache[name] = _write_columns(
                cache[name], cols, (slots[:, None], widx), mode="drop")
    return x, cache, counters


def forward_decode_hybrid(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [B]
    lengths: jax.Array,  # [B]
    cache: Dict[str, jax.Array],
    key_window: Optional[int] = None,
    slot_base: int = 0,
    active: Optional[jax.Array] = None,
    ragged: bool = False,  # STATIC: the state kernel (ops/mamba1_decode.py)
    **_,  # what `SlotKind.decode` hands the kinds that read through a table
):
    """`forward_decode` of a hybrid stack -> (logits [B, V], new cache,
    expert counters int32 [2] of this pass).  The block's rows are stepped
    where they lie, contiguous from `slot_base` (the page table stays the
    identity for a kind with a recurrent state: one tier, nothing
    migrates).  `ragged` is the kind's own kernel: the Mamba-1 blocks step
    each live row's state in the pool; the attention blocks read their
    bucketed key window either way."""
    if ragged and cfg.ssm_kind != MAMBA1:
        raise ValueError(f"ragged_attn: {_NO_HEAD_DECAY_KERNEL}")
    B = tokens.shape[0]
    M = cache["k"].shape[2]
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens[:, None], dtype)
        key_pos = jnp.arange(K, dtype=jnp.int32)[None, :]
        mask = (key_pos <= lengths[:, None])[:, None, None, :]  # [B,1,1,K]
        widx = jnp.minimum(lengths, K - 1)
        if active is not None:
            widx = jnp.where(active, widx, M)
        widx = widx[:, None].astype(jnp.int32)
    x, cache, counters = _hybrid_append_and_attend(
        params, cfg, x, jnp.zeros((B, 1), jnp.int32), cache, mask,
        widx=widx, rows=None, slot_base=slot_base, K=K,
        block=(slot_base, B), active=active, ragged=ragged,
    )
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x[:, 0], dtype), cache, counters


def forward_prefill(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,  # [S, P] padded prompt bucket (may be 1 row)
    prompt_lens: jax.Array,  # [S]
    cache: Dict[str, jax.Array],
    slot_ids: jax.Array,  # int32 [S]: cache slot each row occupies
    inputs_embeds: Optional[jax.Array] = None,  # [S, P, D] (VLM merge)
    rope: Optional[tuple] = None,  # (cos, sin) override (mrope)
):
    """Prefill `input_ids` into cache slots `slot_ids` (arbitrary, possibly
    non-contiguous — batched admission fills whichever slots are free);
    returns (last-token logits [S, V], updated cache)."""
    if is_latent(cfg):
        from areal_tpu.models import latent

        return latent.forward_prefill(
            params, cfg, input_ids, prompt_lens, cache, slot_ids)
    if is_windowed(cfg):
        from areal_tpu.models import windowed

        return windowed.forward_prefill(
            params, cfg, input_ids, prompt_lens, cache, slot_ids)
    S, P = input_ids.shape
    dtype = jnp.dtype(cfg.dtype)
    # built once per program, before the layer scan: positions, masks, RoPE
    # tables and the embedding lookup
    with jax.named_scope("embed"):
        positions = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (S, P))
        valid = positions < prompt_lens[:, None]
        seg = jnp.where(valid, 0, -1)
        per_layer_window = (
            cfg.sliding_window is not None and cfg.layer_is_sliding is not None
        )
        mask = make_attention_mask(
            seg, positions, None if per_layer_window else cfg.sliding_window
        )
        mask_win = (
            make_attention_mask(seg, positions, cfg.sliding_window)
            if per_layer_window
            else None
        )
        if rope is not None:
            cos, sin = rope
        else:
            cos, sin = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
        if inputs_embeds is not None:
            x = inputs_embeds.astype(dtype)
        else:
            x = _embed(params, cfg, input_ids, dtype, positions=positions)

    if is_retention(cfg):
        x, cache = _retention_cache_forward(
            params, cfg, x, cos, sin, seg, cache, write_rows=slot_ids
        )
        return _last_token_logits(params, cfg, x, prompt_lens, dtype), cache

    kv_dtype = cache["k"].dtype
    if is_hybrid(cfg):
        def attend(j, q, k, v):
            with jax.named_scope("attn"):
                return attention(q, k, v, mask), (
                    k.astype(kv_dtype), v.astype(kv_dtype))

        state_in, state_out = _hybrid_state_io(None, slot_ids, None)
        x, cache, kept, _ = _hybrid_traverse(
            params, cfg, x, seg, attend, cache, state_in, state_out
        )
        cache = dict(cache)
        with jax.named_scope("kv_write"):
            for name, cols in zip(("k", "v"), zip(*kept)):
                cache[name] = _write_columns(
                    cache[name], cols, (slot_ids, slice(0, P)))
        return _last_token_logits(params, cfg, x, prompt_lens, dtype), cache

    def layer(x, xs):
        lp, sliding, _ = xs
        m = mask if mask_win is None else jnp.where(sliding, mask_win, mask)
        q, k, v = _attn_inputs(cfg, lp, x, cos, sin, dtype)
        with jax.named_scope("attn"):
            attn = attention(q, k, v, m, cfg.attn_logit_softcap)
        x, _ = _attn_out_and_ffn(cfg, lp, x, attn, dtype)
        return x, (k.astype(kv_dtype), v.astype(kv_dtype))

    x, new = _scan_cache_layers(params, cfg, layer, x)
    with jax.named_scope("kv_write"):
        cache = {
            name: cache[name].at[:, slot_ids, :P].set(cols)
            for name, cols in zip(("k", "v"), new)
        }
    # logits only at each row's final real token
    return _last_token_logits(params, cfg, x, prompt_lens, dtype), cache


def forward_prefill_cached(
    params: Params,
    cfg: TransformerConfig,
    input_ids: jax.Array,  # [S, P] padded SUFFIX tokens
    starts: jax.Array,  # int32 [S]: cache position where the suffix begins
    suffix_lens: jax.Array,  # int32 [S]: real suffix tokens per row
    cache: Dict[str, jax.Array],
    slot_ids: jax.Array,  # int32 [S]
    copy_src: Optional[jax.Array] = None,  # int32 [S]: prefix-KV source row
    copy_block: int = 0,  # STATIC bucketed copy length (0 = no fan-out)
    key_window: Optional[int] = None,  # STATIC bucketed attended span
):
    """Prefill only a SUFFIX of each row, attending over the slot's retained
    KV prefix [0, starts) plus the causal suffix — the engine's KV prefix
    reuse (VERDICT r3 #3: the counterpart of the radix-cache reuse the
    reference gets from SGLang, areal/core/remote_inf_engine.py:404-413).
    Returns (last-token logits [S, V], updated cache).

    Group fan-out (ISSUE 2): with `copy_src`/`copy_block`, each row's
    prefix K/V [0, copy_block) is first copied from `copy_src[row]` into
    its own slot — ONE batched gather/scatter over the cache pytree
    (ops/kv_copy.py) fused into the same program, so GRPO siblings ride
    their representative's prefix without an extra dispatch.  Rows that
    reuse their OWN retained prefix pass copy_src == slot_ids (an identity
    self-copy); copy_block rides the prompt-bucket ladder so the program
    count stays bounded.  The caller guarantees every source row's
    [0, starts[row]) span is valid BEFORE this call (fresh representatives
    prefill first; retained representatives cap the share at their lcp).

    Cost is O(P * K) attention over the attended span K (`key_window`, a
    bucketed bound on the deepest row's start + suffix — M when omitted)
    instead of O(P^2) within the prompt — the right trade when P (new
    tokens) << the retained prefix, and the window keeps short sequences
    in a large cache from paying O(M).  Fresh admissions keep using
    `forward_prefill`."""
    if is_latent(cfg):
        from areal_tpu.models import latent

        return latent.forward_prefill_cached(
            params, cfg, input_ids, starts, suffix_lens, cache, slot_ids,
            copy_src=copy_src, copy_block=copy_block, key_window=key_window,
        )
    if is_windowed(cfg):
        from areal_tpu.models import windowed

        return windowed.forward_prefill_cached(
            params, cfg, input_ids, starts, suffix_lens, cache, slot_ids,
            copy_src=copy_src, copy_block=copy_block, key_window=key_window,
        )
    S, P = input_ids.shape
    if is_retention(cfg):
        # a state has no columns: each row continues from the END state of
        # `copy_src` (itself, or the representative whose prefix it shares),
        # so `starts` must be that row's whole retained length
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope("embed"):
            offs = jnp.arange(P, dtype=jnp.int32)
            positions = starts[:, None] + offs[None, :]
            cos, sin = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
            x = _embed(params, cfg, input_ids, dtype, positions=positions)
            seg = jnp.where(offs[None, :] < suffix_lens[:, None], 0, -1)
        x, cache = _retention_cache_forward(
            params, cfg, x, cos, sin, seg, cache,
            read_rows=slot_ids if copy_src is None else copy_src,
            write_rows=slot_ids,
        )
        return _last_token_logits(params, cfg, x, suffix_lens, dtype), cache
    M = cache["k"].shape[2]
    if copy_block and copy_src is not None:
        from areal_tpu.ops.kv_copy import copy_kv_prefix

        # the columns alone: a hybrid stack's state is read through
        # `copy_src` where it is used
        cache = {**cache, **copy_kv_prefix(
            {name: cache[name] for name in ("k", "v")},
            copy_src, slot_ids, copy_block,
        )}
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        offs = jnp.arange(P, dtype=jnp.int32)
        positions = starts[:, None] + offs[None, :]  # [S, P] global positions
        cos, sin = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
        x = _embed(params, cfg, input_ids, dtype, positions=positions)
        key_pos = jnp.arange(K, dtype=jnp.int32)
        # q at global position g attends cache positions <= g; padding rows
        # (offs >= suffix_lens) produce garbage that is never read
        per_layer_window = (
            cfg.sliding_window is not None and cfg.layer_is_sliding is not None
        )
        mask = (key_pos[None, None, :] <= positions[:, :, None])[:, None]  # [S,1,P,M]
        mask_win = None
        if cfg.sliding_window is not None:
            win = mask & (
                key_pos[None, None, :] > positions[:, :, None] - cfg.sliding_window
            )[:, None]
            if per_layer_window:
                mask_win = win
            else:
                mask = win


    if is_hybrid(cfg):
        # the columns of [0, starts) came with the copy above; the state
        # and window each row continues from are the END state of
        # `copy_src` (itself, or the representative whose prefix it
        # shares), so `starts` must be that row's whole retained length
        seg = jnp.where(offs[None, :] < suffix_lens[:, None], 0, -1)
        x, cache, _ = _hybrid_append_and_attend(
            params, cfg, x, seg, cache, mask,
            widx=jnp.where(seg >= 0, positions, M), rows=slot_ids,
            slot_base=0, K=K,
            read_rows=slot_ids if copy_src is None else copy_src,
        )
        return _last_token_logits(params, cfg, x, suffix_lens, dtype), cache

    # the write is full-range (a position past M drops), attention never
    # reads past the window the caller bounded
    x, cache = _append_and_attend(
        params, cfg, x, cos, sin, cache, mask, mask_win,
        widx=positions, rows=slot_ids, slot_base=0, K=K,
    )
    return _last_token_logits(params, cfg, x, suffix_lens, dtype), cache


def causal_window(q_pos: jax.Array, key_pos: jax.Array, window=None):
    """bool [..., T, K]: the query at cache position `q_pos[..., t]` attends
    `key_pos[k]` at or before it (inclusive: its own column is written
    first) and, with `window`, fewer than `window` positions back."""
    keep = key_pos <= q_pos[..., None]
    if window is not None:
        keep = keep & (key_pos > q_pos[..., None] - window)
    return keep


def _last_token_logits(params: Params, cfg: TransformerConfig, x, lens, dtype):
    """Logits only at each row's final real token: x [S, P, D] -> [S, V]."""
    with jax.named_scope("lm_head"):
        idx = jnp.maximum(lens - 1, 0)
        last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        return _head_logits(params, cfg, last, dtype)


def _scan_cache_layers(params: Params, cfg: TransformerConfig, layer, x, carry=()):
    """Scan `layer` over the stacked weights, the sliding flags and the
    layer index, then the final norm -> (hidden, what the scan stacked)
    or, with a `carry` beside the hidden state, (hidden, the carry).

    The stacked cache `[L, S, M, Hkv, hd]` is never an input or an output
    of this scan: as one, `lax.scan` slices every layer's whole slab out of
    it and stacks it back, a read and a write of the whole cache a pass to
    append one column (58% of a decode token on the chip; PERF.md, PR 28).
    A dense layer reads its window from the closed-over cache by its index
    and hands back only its new columns; the kernel of the ragged path,
    which appends in place, gets the cache through the scan's carry."""
    xs = (
        params["layers"],
        _layer_sliding_flags(cfg),
        jnp.arange(cfg.num_layers, dtype=jnp.int32),
    )
    with jax.named_scope("layers"):
        if carry:
            (x, *out), _ = jax.lax.scan(layer, (x, *carry), xs)
        else:
            x, out = jax.lax.scan(layer, x, xs)
    with jax.named_scope("final_norm"):
        x = _norm(cfg, x, params, "final_norm")
    return x, out


def _cache_window(ck, cv, l, rows, slot_base: int, B: int, K: int):
    """Layer `l`'s K and V for a dispatched block, read straight from the
    stacked caches [L, S, M, Hkv, hd] -> two [B, K, Hkv, hd]: only the
    block's rows (contiguous from `slot_base`, or through the page table
    `rows`) and only the attended columns [0, K).  No layer's slab and no
    row of M columns is ever taken out whole.

    Through the page table it is a loop of one slice a row, which is what
    the chip's compiler makes of a gather with slices this large, but
    written out: handed the gather `ck[l, rows, :K]`, it chose at some K
    (256 and 2048 of 2048 at 2 kv heads; 128 and 256 of 1024 at 8) another
    layout for the gather's operand and copied the WHOLE cache into it
    every pass (compiled for a described v5e; PERF.md, PR 28)."""
    size = (1, 1, K) + ck.shape[3:]
    if rows is None:
        block = (1, B) + size[2:]
        return tuple(
            jax.lax.dynamic_slice(c, (l, slot_base, 0, 0, 0), block)[0]
            for c in (ck, cv)
        )

    def row(i, out):
        return tuple(
            jax.lax.dynamic_update_slice(
                o, jax.lax.dynamic_slice(c, (l, rows[i], 0, 0, 0), size)[0],
                (i, 0, 0, 0),
            )
            for o, c in zip(out, (ck, cv))
        )

    empty = jnp.zeros((B,) + size[2:], ck.dtype)
    return jax.lax.fori_loop(0, B, row, (empty, empty))


def _new_column_hits(widx, K: int, M: int):
    """Where this call's new columns fall in the attended window: bool
    [B, K], True at window index `widx[b, 0] + j` for each written column
    j.  `widx` [B, T] are the write positions: contiguous from the first,
    M where the write drops, and the written ones lead (every caller's
    are: a decode step's one column, a verify's `n_write`, a suffix)."""
    j = jnp.arange(K, dtype=jnp.int32)[None, :] - widx[:, :1]
    n = jnp.sum(widx < M, axis=1, keepdims=True)
    return (j >= 0) & (j < n)


def _with_new_columns(win, new, at, hit):
    """The window a layer attends: `win` [B, K, Hkv, hd] as the cache holds
    it, with this call's columns `new` [B, T, Hkv, hd], which the cache
    does not hold yet, at their own indices (`at[b] + j` wherever `hit`).
    The softmax then runs over the K positions it would see had the
    columns been written first, in the same order."""
    K, T = win.shape[1], new.shape[1]
    if T > 1:
        # a row's columns are contiguous: one slice a row out of the padded
        # run, not a gather per column
        run = jnp.pad(new, ((0, 0), (K, K), (0, 0), (0, 0)))
        new = jax.vmap(
            lambda r, a: jax.lax.dynamic_slice_in_dim(r, K - a, K, axis=0)
        )(run, jnp.clip(at, 0, K))
    return jnp.where(hit[:, :, None, None], new, win)


def _append_and_attend(
    params: Params,
    cfg: TransformerConfig,
    x: jax.Array,  # [B, T, D] embedded tokens
    cos: jax.Array,
    sin: jax.Array,
    cache: Dict[str, jax.Array],
    mask: jax.Array,  # [B, 1, T, K]
    mask_win: Optional[jax.Array],  # the sliding layers' mask, or None
    *,
    widx: jax.Array,  # int32 [B, T] write positions; M = the write drops
    rows: Optional[jax.Array],  # int32 [B] physical rows, None = the block
    slot_base: int,
    K: int,
    ragged: Optional[dict] = None,  # the fused kernel's arguments
):
    """The layer scan of suffix prefill, decode and verify -> (final-norm
    hidden, new cache): every layer attends its rows' first K cached
    columns plus the T new ones, and the new columns of ALL layers are
    written into the cache by one scatter after the scan."""
    B = x.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    ck, cv = cache["k"], cache["v"]
    L, S = ck.shape[:2]

    if ragged is not None:
        # fused ragged kernel: append write + per-slot paged read + exact
        # dense-order softmax in ONE program over the grid (bit-identical
        # to write, window, attention below: ops/ragged_decode.py pins the
        # exactness argument); the write is inside the kernel, so all of
        # it is `attn`.  The kernel appends in place, so the cache rides
        # the carry, and it indexes rows, so the layers are laid end to
        # end: layer l's row r is row l * S + r.
        def kernel_layer(carry, xs):
            x, fk, fv = carry
            lp, sliding, l = xs
            m = mask if mask_win is None else jnp.where(sliding, mask_win, mask)
            q, k, v = _attn_inputs(cfg, lp, x, cos, sin, dtype)
            with jax.named_scope("attn"):
                attn, fk, fv = ragged_paged_attention(
                    q, k.astype(fk.dtype), v.astype(fv.dtype), fk, fv,
                    rows + l * S, ragged["lengths"], widx, m[:, 0],
                    key_window=K, page_size=ragged["page_size"],
                    logit_softcap=cfg.attn_logit_softcap,
                    mesh=ragged["mesh"],
                )
            x, _ = _attn_out_and_ffn(cfg, lp, x, attn, dtype)
            return (x, fk, fv), None

        flat = (L * S,) + ck.shape[2:]
        x, (fk, fv) = _scan_cache_layers(
            params, cfg, kernel_layer, x,
            carry=(ck.reshape(flat), cv.reshape(flat)),
        )
        return x, {"k": fk.reshape(ck.shape), "v": fv.reshape(cv.shape)}

    with jax.named_scope("embed"):
        hit = _new_column_hits(widx, K, ck.shape[2])

    def layer(x, xs):
        lp, sliding, l = xs
        m = mask if mask_win is None else jnp.where(sliding, mask_win, mask)
        q, k, v = _attn_inputs(cfg, lp, x, cos, sin, dtype)
        # through the cache's dtype, as a column read back from it would be
        k, v = k.astype(ck.dtype), v.astype(cv.dtype)
        with jax.named_scope("kv_write"):
            kw, vw = (
                _with_new_columns(
                    win.astype(dtype), new.astype(dtype), widx[:, 0], hit
                )
                for win, new in zip(
                    _cache_window(ck, cv, l, rows, slot_base, B, K), (k, v)
                )
            )
        with jax.named_scope("attn"):
            attn = attention(q, kw, vw, m, cfg.attn_logit_softcap)
        x, _ = _attn_out_and_ffn(cfg, lp, x, attn, dtype)
        return x, (k, v)

    x, new = _scan_cache_layers(params, cfg, layer, x)  # [L, B, T, Hkv, hd]
    slots = rows if rows is not None else slot_base + jnp.arange(B)
    with jax.named_scope("kv_write"):
        cache = {
            name: c.at[:, slots[:, None], widx].set(cols, mode="drop")
            for name, c, cols in zip(("k", "v"), (ck, cv), new)
        }
    return x, cache


def forward_decode(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [B] last generated token per slot in the block
    lengths: jax.Array,  # [B] current sequence length (cache fill) per slot
    cache: Dict[str, jax.Array],
    rope_positions: Optional[jax.Array] = None,  # [B] logical rope position
    key_window: Optional[int] = None,  # STATIC bucketed attended span
    slot_base: int = 0,  # STATIC first cache row of the dispatched block
    active: Optional[jax.Array] = None,  # bool [B]; False drops the KV write
    rows: Optional[jax.Array] = None,  # int32 [B] physical rows (page table)
    ragged: bool = False,  # STATIC: fused ragged paged-attention kernel
    page_size: int = 0,  # STATIC page granularity for the ragged path
    mesh: Optional[Mesh] = None,  # tp>1 shard_map wrap for the kernel
):
    """One decode step for a block of `B` slots; returns (logits [B, V],
    new cache).  The new token's K/V is written at cache position
    `lengths[s]`.  Rows are contiguous from `slot_base` by default; when
    `rows` is given (ISSUE 16 paged pool) each logical slot reads and
    writes THROUGH its page-table row instead — same program shape (rows
    is traced data), so remapping a slot's physical row costs zero new
    compilations and, with an identity table, zero numeric difference.

    `key_window` bounds attention, masks, and the cache write to the first
    K cache columns: decode FLOPs and HBM reads then track the occupied
    span, not the configured `max_seq_len` ceiling (ISSUE 5 — the decode
    analogue of `forward_prefill_cached`'s bucketed window).  K is STATIC
    and must come from a bucket ladder; the caller guarantees
    K >= max(lengths of active slots) + steps for the whole fused chunk.
    `slot_base`/`B` carve a length-cohort tier out of the slot grid — one
    dispatch per tier keeps a long outlier from inflating K for everyone.

    `active` masks the cache write per slot (out-of-window scatter drop):
    idle slots riding a tier dispatch would otherwise clamp their garbage
    write into column K-1, which may sit INSIDE a freed slot's retained
    prefix when K is windowed (full-width decode never had the hazard —
    the M-1 clamp was always past any retained frontier).

    `rope_positions` separates the rotary position from the cache index:
    VLM slots compress an image's placeholder run into a small mrope extent,
    so post-image text continues at a logical position < cache length (for
    equal (t,h,w) text positions, sectioned mrope equals standard rope, so
    decode needs only the scalar)."""
    return slot_kind(cfg).decode(
        params, cfg, tokens, lengths, cache, rope_positions=rope_positions,
        key_window=key_window, slot_base=slot_base, active=active, rows=rows,
        ragged=ragged, page_size=page_size, mesh=mesh,
    )[:2]


def _decode_columns(
    params, cfg, tokens, lengths, cache, *, rope_positions, key_window,
    slot_base, active, rows, ragged, page_size, mesh,
):
    M = cache["k"].shape[2]
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        rp = lengths if rope_positions is None else rope_positions
        positions = rp[:, None].astype(jnp.int32)  # [B, 1]
        cos, sin = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
        x = _embed(params, cfg, tokens[:, None], dtype, positions=positions)
        # attend to cache positions 0..lengths (inclusive: self just written)
        key_pos = jnp.arange(K, dtype=jnp.int32)[None, :]
        per_layer_window = (
            cfg.sliding_window is not None and cfg.layer_is_sliding is not None
        )
        attn_mask = (key_pos <= lengths[:, None])[:, None, None, :]  # [B,1,1,K]
        mask_win = None
        if cfg.sliding_window is not None:
            # window over CACHE indices, not rope positions (they diverge on
            # VLM slots)
            win = attn_mask & (
                key_pos > lengths[:, None] - cfg.sliding_window
            )[:, None, None, :]
            if per_layer_window:
                mask_win = win
            else:
                attn_mask = win
        # clamp: a slot past its cache end (freed host-side mid-chunk, still
        # advancing in the fused decode scan) overwrites the window's last
        # column with garbage instead of stalling the whole grid (VERDICT r3
        # weak #3); inactive slots drop the write entirely (index M is
        # out-of-bounds -> scatter mode="drop")
        widx = jnp.minimum(lengths, K - 1)
        if active is not None:
            widx = jnp.where(active, widx, M)
        widx = widx[:, None].astype(jnp.int32)

    x, cache = _append_and_attend(
        params, cfg, x, cos, sin, cache, attn_mask, mask_win,
        widx=widx, rows=rows, slot_base=slot_base, K=K,
        ragged=dict(lengths=lengths, page_size=page_size, mesh=mesh)
        if ragged and rows is not None else None,
    )
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x[:, 0], dtype), cache, ()


def _decode_state(
    params, cfg, tokens, lengths, cache, *, rope_positions, slot_base, active,
    ragged, **_,
):
    # no window, a state has no columns.  `ragged` is the kind's own kernel
    # (ops/retention_decode.py)
    B = tokens.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        rp = lengths if rope_positions is None else rope_positions
        positions = rp[:, None].astype(jnp.int32)
        cos, sin = rope_cos_sin(positions, cfg.head_dim_, cfg.rope_theta)
        x = _embed(params, cfg, tokens[:, None], dtype, positions=positions)
    x, cache = _retention_cache_forward(
        params, cfg, x, cos, sin, jnp.zeros((B, 1), jnp.int32), cache,
        block=(slot_base, B), active=active, ragged=ragged,
    )
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x[:, 0], dtype), cache, ()


def forward_verify(
    params: Params,
    cfg: TransformerConfig,
    tokens: jax.Array,  # [B, T] committed last token + T-1 draft tokens
    lengths: jax.Array,  # [B] current sequence length (cache fill) per slot
    cache: Dict[str, jax.Array],
    rope_positions: Optional[jax.Array] = None,  # [B] logical rope position
    key_window: Optional[int] = None,  # STATIC bucketed attended span
    slot_base: int = 0,  # STATIC first cache row of the dispatched block
    active: Optional[jax.Array] = None,  # bool [B]; False drops ALL KV writes
    n_write: Optional[jax.Array] = None,  # int32 [B] valid input positions
    rows: Optional[jax.Array] = None,  # int32 [B] physical rows (page table)
    ragged: bool = False,  # STATIC: fused ragged paged-attention kernel
    page_size: int = 0,  # STATIC page granularity for the ragged path
    mesh: Optional[Mesh] = None,  # tp>1 shard_map wrap for the kernel
):
    """Speculative-decode verification: score T input positions per slot of
    a contiguous tier block in ONE dispatch — the decode analogue of
    `forward_prefill_cached` (ISSUE 12).  Row b's inputs are its committed
    pending token followed by T-1 prompt-lookup draft tokens; their K/V
    land at cache positions lengths[b] .. lengths[b]+T-1 and the returned
    logits [B, T, V] give, at each position j, the model's distribution for
    the token at sequence position lengths[b]+j+1 — exactly what T
    sequential `forward_decode` steps would have computed had every draft
    been the sampled token.  The caller samples each position under the
    counter-keyed PRNG and accepts the leading run of agreeing drafts.

    Write-side hazard (same class as decode's idle-slot clamp): position j
    of row b scatter-drops its K/V write (index M, mode="drop") unless the
    row is `active` AND j < n_write[b] — padding positions of a short draft
    and idle slots riding the tier dispatch must never write, because a
    clamped write at K-1 can land inside a freed slot's retained prefix
    when K is windowed.  Writes for positions the caller later REJECTS do
    land here (acceptance needs these very logits) but sit strictly above
    the accepted frontier; the engine zeroes them post-acceptance
    (`_verify_chunk`) so no rejected draft's K/V outlives its dispatch.

    The caller guarantees K >= max(lengths of active slots) + T so no
    active in-budget slot ever clamps."""
    B, T = tokens.shape
    why_not = slot_kind(cfg).lacks.get("verify")
    if why_not:
        raise ValueError(f"spec_decode (forward_verify): {why_not}")
    M = cache["k"].shape[2]
    K = min(key_window, M) if key_window else M
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        rp = lengths if rope_positions is None else rope_positions
        offs = jnp.arange(T, dtype=jnp.int32)
        rope_pos = rp[:, None].astype(jnp.int32) + offs[None, :]  # [B, T]
        positions = lengths[:, None].astype(jnp.int32) + offs[None, :]  # cache idx
        cos, sin = rope_cos_sin(rope_pos, cfg.head_dim_, cfg.rope_theta)
        x = _embed(params, cfg, tokens, dtype, positions=rope_pos)
        key_pos = jnp.arange(K, dtype=jnp.int32)
        per_layer_window = (
            cfg.sliding_window is not None and cfg.layer_is_sliding is not None
        )
        # q at cache position g attends cache positions <= g (inclusive: its
        # own K/V was just written) — same mask family as forward_prefill_cached
        attn_mask = (key_pos[None, None, :] <= positions[:, :, None])[:, None]
        mask_win = None
        if cfg.sliding_window is not None:
            # window over CACHE indices, not rope positions (VLM divergence)
            win = attn_mask & (
                key_pos[None, None, :] > positions[:, :, None] - cfg.sliding_window
            )[:, None]
            if per_layer_window:
                mask_win = win
            else:
                attn_mask = win
        widx = jnp.minimum(positions, K - 1)
        keep = offs[None, :] < (
            jnp.full((B,), T, jnp.int32) if n_write is None else n_write
        )[:, None]
        if active is not None:
            keep = keep & active[:, None]
        widx = jnp.where(keep, widx, M)  # out-of-bounds -> scatter drop

    # the same scan as decode with T columns a row; on the ragged path the
    # same fused kernel with a T-wide query tile, so draft verification
    # rides the paged read for free (ISSUE 19)
    x, cache = _append_and_attend(
        params, cfg, x, cos, sin, cache, attn_mask, mask_win,
        widx=widx, rows=rows, slot_base=slot_base, K=K,
        ragged=dict(lengths=lengths, page_size=page_size, mesh=mesh)
        if ragged and rows is not None else None,
    )
    with jax.named_scope("lm_head"):
        return _head_logits(params, cfg, x, dtype), cache  # [B, T, V]


# ---------------------------------------------------------------------------
# Slot kinds: what a slot of the serving cache holds, and what follows from it
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SlotKind:
    """One row of the table the generation engine and the cache forwards
    both read; nothing else asks the model's family these questions.

    `holds`: "kv" (columns of keys and values, one a position), "state" (a
    recurrent state of fixed size, reusable only at the length it was taken
    at), both (a hybrid Mamba stack, of either recurrence: Mamba-2's state a
    head, Mamba-1's a channel and state column), "latent" (one latent row a
    position and attention sublayer: columns like keys and values), or "kv"
    beside
    "window" (columns for the full layers, a ring of the last positions for
    the sliding ones: reusable like a state, at the length it was taken at).
    `lacks`: capability -> why the kind has none, a sentence an error ends
    in.  Capabilities: "generate" (any cache forward), "verify" (the
    speculative program), "host_tier", "handoff" (export and import of a
    request's cache), "tiers" (more than one length cohort), "tp", "ep",
    "vision", "window" (a decode step bounded by a key window).
    `decode`: one step of a block of slots, ONE signature for every kind:
    (params, cfg, tokens [B], lengths [B], cache, *, rope_positions,
    key_window, slot_base, active, rows, ragged, page_size, mesh) -> (logits
    [B, V], new cache, counters int32 by `counters`, () where the kind counts
    nothing).  A kind takes what it has a use for (`**_` the rest): only K/V
    columns are read through a page table (`rows`), under a mesh, by
    `page_size`; the others' rows are stepped where they lie, contiguous
    from `slot_base` (one tier, the identity table).
    `counters`: names of what a decode pass counts, in `decode`'s order.
    `kernel_refusal(cfg, cache, max_seq_len, kv_dtype, tp)`: why the kind's
    decode kernel cannot serve this pool in this process, or "".  Five kinds
    have one, each for its own layout: `ops/ragged_decode.py` (columns),
    `ops/retention_decode.py` (state), `ops/latent_decode.py` (latent),
    `ops/windowed_decode.py` (windowed: the full layers' columns; the rings
    are read whole), `ops/mamba1_decode.py` (hybrid: the selective scan's
    state leaf; the attention blocks' columns are read by key window, and a
    stack of Mamba-2 blocks is refused: its decay is one number a head).
    `admit_tokens(cfg, max_seq_len)`: the most padded tokens one prefill
    dispatch takes, None for no bound."""

    name: str
    holds: frozenset
    decode: Callable
    kernel_refusal: Callable[..., str]
    lacks: Mapping[str, str] = dataclasses.field(default_factory=dict)
    counters: Tuple[str, ...] = ()
    admit_tokens: Callable[..., Optional[int]] = lambda cfg, max_seq_len: None


def _columns_kernel_refusal(cfg, cache, max_seq_len, kv_dtype, tp):
    return kernel_refusal(max_seq_len, cfg.num_kv_heads, cfg.head_dim_,
                          jnp.dtype(kv_dtype).itemsize, tp)


def _state_kernel_refusal(cfg, cache, max_seq_len, kv_dtype, tp):
    # imported here: a process that trains never loads the kernel's module
    from areal_tpu.ops.retention_decode import retention_refusal

    return retention_refusal(cfg.head_dim_, cache["s"].dtype.itemsize, tp)


def _latent_kernel_refusal(cfg, cache, max_seq_len, kv_dtype, tp):
    from areal_tpu.ops.latent_decode import latent_refusal

    return latent_refusal(cfg.latent_row_dim, cfg.kv_lora_rank, max_seq_len,
                          jnp.dtype(kv_dtype).itemsize)


def _hybrid_kernel_refusal(cfg, cache, max_seq_len, kv_dtype, tp):
    # the state leaf's own shape and dtype decide: [n_ssm, S, N, d_inner]
    if cfg.ssm_kind != MAMBA1:
        return _NO_HEAD_DECAY_KERNEL
    from areal_tpu.ops.mamba1_decode import mamba1_refusal

    s = cache["s"]
    return mamba1_refusal(s.shape[-1], s.shape[-2], s.dtype.itemsize, tp)


_EXPERT_SHARES = (
    "the exchange between expert shares is not built (a share of an "
    "expert-parallel deployment is a configuration's experts_held)"
)
_STATE = ("not built for a model whose slot holds a recurrent state (power "
          "retention; a hybrid Mamba stack, of either recurrence): ")
_NO_POSITION = _STATE + (
    "a state has no position to window, page out or cut back to")
_STATE_LACKS = {
    "verify": _STATE + "a rejected draft cannot be taken out of a state",
    "host_tier": _NO_POSITION, "tiers": _NO_POSITION, "vision": _NO_POSITION,
    "handoff": _STATE + "the wire format carries columns of keys and values",
}
_NO_HEAD_DECAY_KERNEL = (
    "no kernel steps a state with a decay a head (a hybrid stack of "
    "Mamba-2 blocks): the state kernel is the selective scan's "
    "(ops/mamba1_decode.py)")
_ONE_DEVICE = (
    _STATE + "a hybrid stack runs on one device here; " + _EXPERT_SHARES)
_LATENT = ("not built for a model whose slot holds latent rows (latent "
           "attention): ")

COLUMNS_KIND = SlotKind(
    "columns", frozenset({"kv"}), _decode_columns, _columns_kernel_refusal)
STATE_KIND = SlotKind(
    "state", frozenset({"state"}), _decode_state, _state_kernel_refusal,
    # nothing to window: one decode program
    lacks={**_STATE_LACKS, "window": _NO_POSITION},
)
HYBRID_KIND = SlotKind(
    "hybrid", frozenset({"kv", "state"}), forward_decode_hybrid,
    _hybrid_kernel_refusal,
    lacks={**_STATE_LACKS, "tp": _ONE_DEVICE, "ep": _ONE_DEVICE},
    counters=("expert_assignments_held", "experts_touched"),
    # Mamba-2: sixteen chunks of the recurrence (2,048 at the published
    # chunk of 128).  The chunked form builds [rows, heads, chunk, chunk]
    # float32 weights a Mamba block, and the first fill of a large grid
    # (every slot at once) does not fit beside the weights; at 32 chunks one
    # admission step in five runs stalled for up to a second, at 16 none in
    # seventeen runs (PERF.md, PR 32).  Mamba-1: its sequence form carries
    # the state and builds no array of a chunk; what grows with a dispatch
    # is u, dt and y a token in float32 (`ops/mamba1.py admit_tokens`:
    # 4,096 tokens at 5,120 channels)
    admit_tokens=lambda cfg, max_seq_len: (
        mamba1_admit_tokens(cfg.mamba_d_inner) if cfg.ssm_kind == MAMBA1
        else 16 * cfg.mamba_chunk),
)
# refused whole, before any weight is drawn or read: the cache forwards would
# generate through a path that ignores what they lack
GATED_EXPERTS_KIND = SlotKind(
    "gated_experts", frozenset({"kv"}), _decode_columns,
    _columns_kernel_refusal,
    lacks={"generate": (
        "this engine does not generate for a stack of gated experts behind "
        "leading dense layers (afmoe): the cache forwards lack the attention "
        "output gate and rotary embedding on sliding layers only (a window "
        "in the cache and gated experts in the decode programs are built "
        "for mimo_v2: models/windowed.py)")},
)


@functools.lru_cache(maxsize=None)
def _latent_kind() -> SlotKind:
    from areal_tpu.models import latent  # which imports this module

    return SlotKind(
        "latent", frozenset({"latent"}), latent.forward_decode,
        _latent_kernel_refusal,
        lacks={
            "verify": _LATENT + "the verify program reads keys and values "
            "by head, and rejected drafts' latent rows are not taken back",
            "host_tier": _LATENT + "the host tier reads keys and values by "
            "head",
            "tiers": _LATENT + "a decode step reads its block of rows where "
            "they lie (the latent kernel takes one query a slot, one tier)",
            "tp": _LATENT + "latent attention under tp is not built",
            "ep": _LATENT + _EXPERT_SHARES,
        },
        counters=latent.DECODE_COUNTERS,
        # one row of `max_seq_len` tokens' worth: the activations that fit
        # beside the weights of a model whose cache makes contexts this
        # long servable
        admit_tokens=lambda cfg, max_seq_len: max_seq_len,
    )


_WINDOW = ("not built for a model whose slot holds columns for its full "
           "layers beside a ring of the window for its sliding ones: ")


@functools.lru_cache(maxsize=None)
def _windowed_kind() -> SlotKind:
    from areal_tpu.models import windowed  # which imports this module
    from areal_tpu.ops.windowed_decode import windowed_refusal

    return SlotKind(
        "windowed", frozenset({"kv", "window"}), windowed.forward_decode,
        windowed_refusal,
        lacks={
            "verify": _WINDOW + "a rejected draft's entries cannot be taken "
            "back out of a ring, which has overwritten what they replaced",
            "host_tier": _WINDOW + "a ring has no prefix to page out; the "
            "host tier reads columns by position",
            "handoff": _WINDOW + "the wire format carries columns of keys "
            "and values",
            "tiers": _WINDOW + "a decode step reads its block of slots "
            "where they lie (one tier, nothing migrates)",
            "tp": _WINDOW + "two head layouts under tp are not built",
            "ep": _WINDOW + _EXPERT_SHARES,
        },
        counters=windowed.DECODE_COUNTERS,
        # one row of `max_seq_len` tokens' worth a prefill dispatch: the
        # activations that fit beside the weights and the pool
        admit_tokens=lambda cfg, max_seq_len: max_seq_len,
    )


def slot_kind(cfg: TransformerConfig) -> SlotKind:
    """The row of the table for a model configuration."""
    if cfg.ffn_kinds is not None:
        return GATED_EXPERTS_KIND
    if is_latent(cfg):
        return _latent_kind()
    if is_windowed(cfg):
        return _windowed_kind()
    if is_retention(cfg):
        return STATE_KIND
    if is_hybrid(cfg):
        return HYBRID_KIND
    return COLUMNS_KIND


# ---------------------------------------------------------------------------
# Init & partitioning
# ---------------------------------------------------------------------------


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Params:
    """Random init (fan-in scaled normal), master dtype cfg.param_dtype."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, F, V, L = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size, cfg.num_layers
    Hq, Hkv = cfg.q_size, cfg.kv_size
    keys = jax.random.split(rng, 8)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(pdt)

    if is_hybrid(cfg):
        return _init_hybrid_params(cfg, rng, dense)
    if cfg.ffn_kinds is not None:
        return _init_dense_moe_params(cfg, rng, dense)
    if is_latent(cfg):
        from areal_tpu.models import latent

        return latent.init_params(cfg, rng, dense)
    if is_windowed(cfg):
        from areal_tpu.models import windowed

        return windowed.init_params(cfg, rng, dense)
    # unit-offset (gemma) norms store zero-centered weights: zeros==identity
    norm_one = jnp.zeros if cfg.norm_unit_offset else jnp.ones
    layers = {
        "attn": {
            "wq": dense(keys[0], (L, D, Hq), D),
            "wk": dense(keys[1], (L, D, Hkv), D),
            "wv": dense(keys[2], (L, D, Hkv), D),
            "wo": dense(keys[3], (L, Hq, D), Hq),
        },
        "input_norm": norm_one((L, D), pdt),
        "post_attn_norm": norm_one((L, D), pdt),
    }
    if cfg.sandwich_norms:
        layers["sandwich_attn_norm"] = norm_one((L, D), pdt)
        layers["sandwich_ffn_norm"] = norm_one((L, D), pdt)
    if cfg.num_experts > 0:
        E = cfg.num_experts
        Fm = cfg.moe_intermediate_size or F
        layers["moe"] = {
            "router": dense(jax.random.fold_in(keys[4], 7), (L, D, E), D),
            "w_gate": dense(keys[4], (L, E, D, Fm), D),
            "w_up": dense(keys[5], (L, E, D, Fm), D),
            "w_down": dense(keys[6], (L, E, Fm, D), Fm),
        }
    elif not cfg.mlp_gated:
        layers["mlp"] = {
            "w_up": dense(keys[5], (L, D, F), D),
            "w_down": dense(keys[6], (L, F, D), F),
        }
        if cfg.mlp_bias:
            layers["mlp"]["b_up"] = jnp.zeros((L, F), pdt)
            layers["mlp"]["b_down"] = jnp.zeros((L, D), pdt)
    else:
        layers["mlp"] = {
            "w_gate": dense(keys[4], (L, D, F), D),
            "w_up": dense(keys[5], (L, D, F), D),
            "w_down": dense(keys[6], (L, F, D), F),
        }
    if cfg.attn_output_bias:
        layers["attn"]["bo"] = jnp.zeros((L, D), pdt)
    if cfg.norm_type == "layernorm":
        for nm in list(layers):
            if nm.endswith("_norm"):
                layers[nm + "_b"] = jnp.zeros((L, D), pdt)
    if cfg.qkv_bias:
        layers["attn"]["bq"] = jnp.zeros((L, Hq), pdt)
        layers["attn"]["bk"] = jnp.zeros((L, Hkv), pdt)
        layers["attn"]["bv"] = jnp.zeros((L, Hkv), pdt)
    if cfg.qk_norm:
        layers["attn"]["q_norm"] = norm_one((L, cfg.head_dim_), pdt)
        layers["attn"]["k_norm"] = norm_one((L, cfg.head_dim_), pdt)
    if is_retention(cfg):
        # the gate: hidden -> one scalar a kv head, no bias
        layers["attn"]["wg"] = dense(
            jax.random.fold_in(keys[3], 1), (L, D, cfg.num_kv_heads), D
        )
    params: Params = {
        "embedding": dense(keys[7], (V, D), D),
        "layers": layers,
        "final_norm": norm_one((D,), pdt),
    }
    if cfg.norm_type == "layernorm":
        params["final_norm_b"] = jnp.zeros((D,), pdt)
    if cfg.pos_emb == "learned":
        params["pos_embedding"] = dense(
            jax.random.fold_in(keys[7], 2),
            (cfg.max_position_embeddings, D),
            D,
        )
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(jax.random.fold_in(keys[7], 1), (D, V), D)
    return params


def _init_hybrid_params(cfg: TransformerConfig, rng: jax.Array, dense) -> Params:
    """A hybrid stack's parameters, stacked per kind: `layers[kind]` holds
    that kind's blocks with a leading [n_kind] axis, in the pattern's
    order.  dt_bias, A_log and D as Mamba-2 initialises them (dt
    log-uniform in [time_step_min, time_step_max], floored, through the
    inverse softplus; A uniform in [1, 16]; D one), and as Mamba-1 does
    (the same dt a channel; A = 1..N over a channel's state columns; D
    one); the router's selection bias zero, as before any load balancing
    has moved it."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, V = cfg.hidden_size, cfg.vocab_size
    keys = iter(jax.random.split(rng, 24))
    layers: Params = {}

    def dt_bias(key, shape):
        """softplus(dt_bias) == dt, log-uniform in [time_step_min,
        time_step_max], floored."""
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.maximum(
            jnp.exp(
                u * (np.log(cfg.time_step_max) - np.log(cfg.time_step_min))
                + np.log(cfg.time_step_min)
            ),
            cfg.time_step_floor,
        )
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32)

    n = cfg.n_kind(MAMBA)
    if n:
        H, d_in, cd = cfg.mamba_num_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
        mamba_dt_bias = dt_bias(next(keys), (n, H))
        layers[MAMBA] = {
            "input_norm": jnp.ones((n, D), pdt),
            "w_in": dense(next(keys), (n, D, d_in + cd + H), D),
            "conv_w": dense(next(keys), (n, cfg.conv_kernel, cd), cfg.conv_kernel),
            "conv_b": (0.1 * jax.random.normal(next(keys), (n, cd))).astype(pdt),
            "dt_bias": mamba_dt_bias,
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (n, H), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((n, H), jnp.float32),
            "gate_norm": jnp.ones((n, d_in), pdt),
            "w_out": dense(next(keys), (n, d_in, D), d_in),
        }
    n = cfg.n_kind(ATTN)
    if n:
        Hq, Hkv = cfg.q_size, cfg.kv_size
        layers[ATTN] = {
            "input_norm": jnp.ones((n, D), pdt),
            "attn": {
                "wq": dense(next(keys), (n, D, Hq), D),
                "wk": dense(next(keys), (n, D, Hkv), D),
                "wv": dense(next(keys), (n, D, Hkv), D),
                "wo": dense(next(keys), (n, Hq, D), Hq),
            },
        }
    n = cfg.n_kind(MOE)
    if n:
        lo, hi = cfg.held_range
        E, Lt = cfg.num_experts, cfg.moe_latent_size
        Fm, Fs = cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
        layers[MOE] = {
            "input_norm": jnp.ones((n, D), pdt),
            "router": dense(next(keys), (n, D, E), D),
            "router_bias": jnp.zeros((n, E), jnp.float32),
            "w_l1": dense(next(keys), (n, D, Lt), D),
            "w_l2": dense(next(keys), (n, Lt, D), Lt),
            # the experts held here, ids [lo, hi) of E
            "w1": dense(next(keys), (n, hi - lo, Lt, Fm), Lt),
            "w2": dense(next(keys), (n, hi - lo, Fm, Lt), Fm),
            "ws1": dense(next(keys), (n, D, Fs), D),
            "ws2": dense(next(keys), (n, Fs, D), Fs),
        }
    # jamba's two kinds draw from keys of their own: nemotron_h's weights
    # stay what a seed gave them before these kinds existed
    keys1 = iter(jax.random.split(jax.random.fold_in(rng, 1), 12))
    n = cfg.n_kind(MAMBA1)
    if n:
        d_in, N, R = cfg.mamba_d_inner, cfg.ssm_state_size, cfg.mamba_dt_rank
        mamba1_dt_bias = dt_bias(next(keys1), (n, d_in))
        layers[MAMBA1] = {
            "input_norm": jnp.ones((n, D), pdt),
            "w_in": dense(next(keys1), (n, D, 2 * d_in), D),
            "conv_w": dense(
                next(keys1), (n, cfg.conv_kernel, d_in), cfg.conv_kernel),
            "conv_b": (0.1 * jax.random.normal(next(keys1), (n, d_in))).astype(pdt),
            "w_x": dense(next(keys1), (n, d_in, R + 2 * N), d_in),
            "dt_norm": jnp.ones((n, R), pdt),
            "b_norm": jnp.ones((n, N), pdt),
            "c_norm": jnp.ones((n, N), pdt),
            "w_dt": dense(next(keys1), (n, R, d_in), R),
            "dt_bias": mamba1_dt_bias,
            # A = 1..N a channel (Mamba-1's own start)
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (n, d_in, N)),
            "D": jnp.ones((n, d_in), jnp.float32),
            "w_out": dense(next(keys1), (n, d_in, D), d_in),
        }
    n = cfg.n_kind(FFN)
    if n:
        F = cfg.intermediate_size
        layers[FFN] = {
            "input_norm": jnp.ones((n, D), pdt),
            "mlp": {
                "w_gate": dense(next(keys1), (n, D, F), D),
                "w_up": dense(next(keys1), (n, D, F), D),
                "w_down": dense(next(keys1), (n, F, D), F),
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


def _init_dense_moe_params(cfg: TransformerConfig, rng: jax.Array, dense) -> Params:
    """A stack of gated experts behind leading dense layers (afmoe):
    `layers["dense"]` and `layers["moe"]`, each with a leading axis over
    its own blocks in the stack's order.  Both kinds hold the attention
    (q/k norm, the output gate `wg`) and four norms; a dense block a gated
    MLP of `intermediate_size`, an expert block the router over ALL
    experts, its selection bias (float32, zero: a buffer that load
    balancing moves and no gradient does), the experts held here and one
    shared expert."""
    pdt = jnp.dtype(cfg.param_dtype)
    D, V, F = cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size
    Hq, Hkv, hd = cfg.q_size, cfg.kv_size, cfg.head_dim_
    keys = iter(jax.random.split(rng, 32))

    def block(n):
        return {
            "attn": {
                "wq": dense(next(keys), (n, D, Hq), D),
                "wk": dense(next(keys), (n, D, Hkv), D),
                "wv": dense(next(keys), (n, D, Hkv), D),
                "wo": dense(next(keys), (n, Hq, D), Hq),
                "wg": dense(next(keys), (n, D, Hq), D),
                "q_norm": jnp.ones((n, hd), pdt),
                "k_norm": jnp.ones((n, hd), pdt),
            },
            "input_norm": jnp.ones((n, D), pdt),
            "sandwich_attn_norm": jnp.ones((n, D), pdt),
            "post_attn_norm": jnp.ones((n, D), pdt),
            "sandwich_ffn_norm": jnp.ones((n, D), pdt),
        }

    layers: Params = {}
    n = cfg.ffn_kinds.count("dense")
    if n:
        layers["dense"] = {**block(n), "mlp": {
            "w_gate": dense(next(keys), (n, D, F), D),
            "w_up": dense(next(keys), (n, D, F), D),
            "w_down": dense(next(keys), (n, F, D), F),
        }}
    n = cfg.ffn_kinds.count("moe")
    lo, hi = cfg.held_range
    Fm, Fs = cfg.moe_intermediate_size, cfg.moe_shared_intermediate_size
    layers["moe"] = {**block(n), "moe": {
        "router": dense(next(keys), (n, D, cfg.num_experts), D),
        "router_bias": jnp.zeros((n, cfg.num_experts), jnp.float32),
        # the experts held here, ids [lo, hi) of num_experts
        "w_gate": dense(next(keys), (n, hi - lo, D, Fm), D),
        "w_up": dense(next(keys), (n, hi - lo, D, Fm), D),
        "w_down": dense(next(keys), (n, hi - lo, Fm, D), Fm),
        "ws_gate": dense(next(keys), (n, D, Fs), D),
        "ws_up": dense(next(keys), (n, D, Fs), D),
        "ws_down": dense(next(keys), (n, Fs, D), Fs),
    }}
    params: Params = {
        "embedding": dense(next(keys), (V, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), pdt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (D, V), D)
    return params


def _dense_moe_partition_specs(cfg: TransformerConfig, vocab_axis) -> Params:
    """The dense path's layout for what both kinds of block share
    (attention and dense MLP column/row-split over tp, the other axis over
    fsdp); the held experts over "ep" with fsdp on the model axis inside
    each; router and its bias whole."""
    def block():
        return {
            "attn": {
                "wq": P(None, "fsdp", "tp"), "wk": P(None, "fsdp", "tp"),
                "wv": P(None, "fsdp", "tp"), "wo": P(None, "tp", "fsdp"),
                "wg": P(None, "fsdp", "tp"),
                "q_norm": P(None, None), "k_norm": P(None, None),
            },
            "input_norm": P(None, "fsdp"),
            "sandwich_attn_norm": P(None, "fsdp"),
            "post_attn_norm": P(None, "fsdp"),
            "sandwich_ffn_norm": P(None, "fsdp"),
        }

    layers: Params = {}
    if "dense" in cfg.ffn_kinds:
        layers["dense"] = {**block(), "mlp": {
            "w_gate": P(None, "fsdp", "tp"), "w_up": P(None, "fsdp", "tp"),
            "w_down": P(None, "tp", "fsdp"),
        }}
    layers["moe"] = {**block(), "moe": {
        "router": P(None, None, None), "router_bias": P(None, None),
        "w_gate": P(None, "ep", "fsdp", None),
        "w_up": P(None, "ep", "fsdp", None),
        "w_down": P(None, "ep", None, "fsdp"),
        "ws_gate": P(None, "fsdp", "tp"), "ws_up": P(None, "fsdp", "tp"),
        "ws_down": P(None, "tp", "fsdp"),
    }}
    specs: Params = {
        "embedding": P(vocab_axis, "fsdp"),
        "layers": layers,
        "final_norm": P("fsdp"),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("fsdp", vocab_axis)
    return specs


def _hybrid_partition_specs(cfg: TransformerConfig, vocab_axis) -> Params:
    """A hybrid stack on a serving mesh: the held experts over "ep", the
    vocabulary over "tp", every mixer whole (with two key-value heads there
    is little to divide; the engine refuses tp > 1 for this kind)."""
    layers: Params = {}
    rep2, rep3 = P(None, None), P(None, None, None)
    if MAMBA in cfg.layer_kinds:
        layers[MAMBA] = {
            "input_norm": rep2, "w_in": rep3, "conv_w": rep3, "conv_b": rep2,
            "dt_bias": rep2, "A_log": rep2, "D": rep2, "gate_norm": rep2,
            "w_out": rep3,
        }
    if ATTN in cfg.layer_kinds:
        layers[ATTN] = {
            "input_norm": rep2,
            "attn": {"wq": rep3, "wk": rep3, "wv": rep3, "wo": rep3},
        }
    if MAMBA1 in cfg.layer_kinds:
        layers[MAMBA1] = {
            "input_norm": rep2, "w_in": rep3, "conv_w": rep3, "conv_b": rep2,
            "w_x": rep3, "dt_norm": rep2, "b_norm": rep2, "c_norm": rep2,
            "w_dt": rep3, "dt_bias": rep2, "A_log": rep3, "D": rep2,
            "w_out": rep3,
        }
    if FFN in cfg.layer_kinds:
        layers[FFN] = {
            "input_norm": rep2,
            "mlp": {"w_gate": rep3, "w_up": rep3, "w_down": rep3},
        }
    if MOE in cfg.layer_kinds:
        layers[MOE] = {
            "input_norm": rep2, "router": rep3, "router_bias": rep2,
            "w_l1": rep3, "w_l2": rep3,
            "w1": P(None, "ep", None, None), "w2": P(None, "ep", None, None),
            "ws1": rep3, "ws2": rep3,
        }
    specs: Params = {
        "embedding": P(vocab_axis, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, vocab_axis)
    return specs


def param_partition_specs(cfg: TransformerConfig, tp: int = 0) -> Params:
    """PartitionSpecs over mesh axes ("fsdp", "tp").

    Layout follows the megatron/GSPMD convention the reference realises with
    DTensor TP plans (areal/utils/fsdp/parallel.py:10-18) and in-repo
    Column/RowParallelLinear (realhf .../tensor_parallel/modules.py:737,885):
    qkv & mlp-in column-split over tp, attn-out & mlp-down row-split; the
    other axis is ZeRO-sharded over fsdp.  Vocab-parallel embedding/head.

    Pass the mesh's `tp` size to drop the vocab sharding when the vocab
    is not divisible (odd test vocabs; real vocabs are multiples of 128).
    """
    vocab_axis = "tp" if (tp == 0 or cfg.vocab_size % max(tp, 1) == 0) else None
    if is_hybrid(cfg):
        return _hybrid_partition_specs(cfg, vocab_axis)
    if cfg.ffn_kinds is not None:
        return _dense_moe_partition_specs(cfg, vocab_axis)
    if is_latent(cfg):
        from areal_tpu.models import latent

        return latent.partition_specs(cfg, vocab_axis)
    if is_windowed(cfg):
        from areal_tpu.models import windowed

        return windowed.partition_specs(cfg, vocab_axis)
    attn = {
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
    }
    if cfg.qkv_bias:
        attn.update(bq=P(None, "tp"), bk=P(None, "tp"), bv=P(None, "tp"))
    if cfg.attn_output_bias:
        attn["bo"] = P(None, "fsdp")
    if cfg.qk_norm:
        attn.update(q_norm=P(None, None), k_norm=P(None, None))
    if is_retention(cfg):
        attn["wg"] = P(None, "fsdp", "tp")  # with the kv heads
    if cfg.num_experts > 0:
        # experts over ep, megatron column/row split inside each expert —
        # the reference's EP x ETP layout (alloc_mode.py:80-117)
        ffn = {
            "moe": {
                "router": P(None, "fsdp", None),
                "w_gate": P(None, "ep", "fsdp", "tp"),
                "w_up": P(None, "ep", "fsdp", "tp"),
                "w_down": P(None, "ep", "tp", "fsdp"),
            }
        }
    elif not cfg.mlp_gated:
        ffn = {
            "mlp": {
                "w_up": P(None, "fsdp", "tp"),
                "w_down": P(None, "tp", "fsdp"),
            }
        }
        if cfg.mlp_bias:
            ffn["mlp"]["b_up"] = P(None, "tp")
            ffn["mlp"]["b_down"] = P(None, "fsdp")
    else:
        ffn = {
            "mlp": {
                "w_gate": P(None, "fsdp", "tp"),
                "w_up": P(None, "fsdp", "tp"),
                "w_down": P(None, "tp", "fsdp"),
            }
        }
    if cfg.lora_rank:
        # adapters: A follows the base weight's input sharding, B its
        # output (column/row) split; the rank dim stays whole
        from areal_tpu.models.lora import TARGET_MAP

        row_split = {"wo", "w_down"}
        for tgt in cfg.lora_targets:
            sub_name, leaf = TARGET_MAP[tgt]
            sub = attn if sub_name == "attn" else ffn.get("mlp")
            if sub is None or leaf not in sub:
                continue
            if leaf in row_split:
                sub[f"{leaf}_lora_a"] = P(None, "tp", None)
                sub[f"{leaf}_lora_b"] = P(None, None, "fsdp")
            else:
                sub[f"{leaf}_lora_a"] = P(None, "fsdp", None)
                sub[f"{leaf}_lora_b"] = P(None, None, "tp")
    layer_specs = {
        "attn": attn,
        **ffn,
        "input_norm": P(None, "fsdp"),
        "post_attn_norm": P(None, "fsdp"),
    }
    if cfg.sandwich_norms:
        layer_specs["sandwich_attn_norm"] = P(None, "fsdp")
        layer_specs["sandwich_ffn_norm"] = P(None, "fsdp")
    if cfg.norm_type == "layernorm":
        for nm in [n for n in layer_specs if n.endswith("_norm")]:
            layer_specs[nm + "_b"] = P(None, "fsdp")
    specs: Params = {
        "embedding": P(vocab_axis, "fsdp"),
        "layers": layer_specs,
        "final_norm": P("fsdp"),
    }
    if cfg.norm_type == "layernorm":
        specs["final_norm_b"] = P("fsdp")
    if cfg.pos_emb == "learned":
        specs["pos_embedding"] = P(None, "fsdp")
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("fsdp", vocab_axis)
    return specs


def param_count(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
