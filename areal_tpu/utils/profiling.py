"""Device profiling + FLOPs/MFU accounting.

Capability counterpart of the reference's monitoring stack
(realhf/base/monitor.py:404-678 kineto CUDA kernel-time categorisation,
realhf/base/flops_counter.py): on TPU the device timeline comes from
`jax.profiler` (xplane traces viewable in TensorBoard/Perfetto) and FLOPs
from the analytic transformer model below, folded into per-step MFU that
the train engine reports with every batch.
"""

import contextlib
from typing import Optional

import jax

from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.utils.runtime import cpu_requested

# peak bf16 TFLOP/s by `device_kind` prefix.  Source: Google Cloud TPU
# documentation, the per-generation system architecture pages ("TPU v5e":
# 197 TFLOP/s bf16 per chip; v4 275; v5p 459; v6e 918).
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}

# The closed vocabulary of `jax.named_scope` names in this package: every
# HLO operation's `op_name` carries the scopes it was written under
# (`jit(_decode_chunk)/layers/while/body/kv_write/...`), so a profile names
# the device's time by layer and not by the number XLA gave a fusion.  JAX
# itself wraps a scope in `jvp(...)` / `transpose(jvp(...))` in a gradient
# and adds `checkpoint` / `rematted_computation` for a remat forward;
# readers tell the passes apart by those words.  tests/test_scopes.py pins
# that no other name is used and where each one appears.
SCOPES = (
    ("embed", "built once, before the layer scan: embedding lookup, positions, RoPE tables, masks"),
    ("layers", "the layer stack: the lax.scan and everything inside it"),
    ("attn_qkv", "in layers: input norm, q/k/v projections, bias, q/k norm, RoPE"),
    ("kv_write", "in layers: a layer's window of the cache, with the call's new columns in it, as it feeds attention; after the layer scan: the one write of all layers' new K/V into the cache"),
    ("attn", "in layers: the attention core (splash, ragged kernel, or dense scores and values)"),
    ("attn_local", "in attn, a stack that mixes sliding and full layers (afmoe; mimo_v2's cache forwards): a sliding layer's attention core (splash under the window's LocalMask, with the sinks where the family has them; over a slot's ring in a suffix or a decode step), and afmoe's rotary embedding on sliding layers only"),
    ("attn_global", "in attn, the same stacks: a full layer's attention core (splash under CausalMask; in mimo_v2's suffix and decode steps the copy of the block's key window out of the pool and the two products over it)"),
    ("attn_gate", "in layers, gated attention (afmoe): sigmoid of the gate projection of the input-normed stream, times the attention output, before attn_out"),
    ("retention", "in layers, power-retention models (in place of attn + kv_write): scores, the state's update and read-out, the state's write into the pool"),
    ("state_copy", "in layers, recurrent-state models (power retention, a hybrid stack's Mamba blocks of either recurrence): reading the state (and convolution window) a suffix row starts from, its own or (group fan-out) its representative's"),
    ("ssm", "in layers, a hybrid stack's Mamba block (Mamba-2: nemotron_h; Mamba-1: jamba): pre-norm, in/out projections, the gated norm or (Mamba-1) the step-size / B / C projections with their norms and the SiLU gate, and the state's write into the pool"),
    ("ssm_conv", "in ssm: the causal depthwise convolution and its window"),
    ("ssm_scan", "in ssm: the state-space recurrence (Mamba-2's chunked SSD form, Mamba-1's selective scan), in chunks (prefill) or one step (decode)"),
    ("attn_out", "in layers: output projection and the residual add"),
    ("mlp", "in layers: post-attention norm, gate/up/down, residual add"),
    ("moe", "in layers: the same place for a mixture of experts (routing + experts); a hybrid stack's latent expert block whole"),
    ("moe_router", "in moe, experts at a share (latent, or gated at the model's width): sigmoid scores, top-k by score + bias, the sort of assignments by held expert, the counters"),
    ("moe_latent", "in moe, latent experts: the two latent projections"),
    ("moe_experts", "in moe, experts at a share: gather by expert, the grouped products over the held experts (two for latent experts, three for gated ones), the weighted sum back"),
    ("moe_shared", "in moe, experts at a share: the shared expert at the model's width"),
    ("moe_identity", "in moe, identity (zero-compute) experts: the summed weights of a token's identity choices times the token itself"),
    ("mla_q", "in layers, latent attention: the query's two projections through its normed latent, the rotary embedding on its rope dims, and (over cached rows) the fold of W_kvb's key half into the query"),
    ("mla_kv", "in layers, latent attention: the down-projection to the latent row, its norm and scale, the rotary embedding on the shared key"),
    ("mla_attn", "in layers, latent attention: scores, softmax and the weighted sum over a slot's latent rows (absorbed: a decode step's paged kernel or its copy of the window, a suffix), or the expansion of a fresh prompt's own rows and its blocked causal attention"),
    ("mla_out", "in layers, latent attention: W_kvb's value half after the weighted sum, and the output projection"),
    ("latent_write", "in layers, after the last layer: the chunk's latent rows of every sublayer into the pool, a block a row"),
    ("ffn_dense", "in layers, a double layer's two dense gated FFNs (longcat_flash); the leading layers' dense gated FFN (mimo_v2); a hybrid stack's dense gated FFN block, one behind every mixer (jamba)"),
    ("window_write", "in layers, after the last layer (mimo_v2): the chunk's last positions into every sliding layer's ring, at their position mod the ring's length"),
    ("window_copy", "a sibling's copy of its representative's rings, whole, before a suffix dispatch's layers (mimo_v2; beside kv_copy of the full layers' columns)"),
    ("kv_copy", "cross-slot prefix fan-out and host-tier gather/scatter of the cache"),
    ("final_norm", "the last norm"),
    ("lm_head", "the vocabulary projection (in training only the head's transpose/cast: the product is in xent)"),
    ("xent", "chunked log-softmax / gather over the vocabulary and its hand-written backward"),
    ("sampler", "temperature, top-k/top-p, the draw, the chosen token's log-prob"),
    ("loss", "the training loss and its statistics"),
    ("optimizer", "gradient norm and clip, optimizer update, parameter apply"),
    ("advantages", "GAE over padded rows"),
)
SCOPE_NAMES = tuple(name for name, _ in SCOPES)


def device_peak_tflops(device=None) -> Optional[float]:
    """Peak of the device in the table above.  `None` on an explicit CPU
    run (`JAX_PLATFORMS=cpu`), which reports no utilisation; a TPU the
    table does not know raises — a missing MFU must not pass for a
    measured one."""
    device = device or jax.devices()[0]
    kind = device.device_kind
    for k in sorted(PEAK_TFLOPS, key=len, reverse=True):
        if kind.startswith(k):
            return PEAK_TFLOPS[k]
    if device.platform == "cpu" and cpu_requested():
        return None
    raise ValueError(
        f"no peak TFLOP/s for device kind {kind!r} (platform "
        f"{device.platform!r}): add it to utils/profiling.py PEAK_TFLOPS "
        "from the Google Cloud TPU system architecture pages"
    )


def param_count(cfg: TransformerConfig) -> int:
    """Analytic parameter count of the dense/MoE transformer."""
    D, F, V, L = (
        cfg.hidden_size,
        cfg.intermediate_size,
        cfg.vocab_size,
        cfg.num_layers,
    )
    attn = D * (cfg.q_size + 2 * cfg.kv_size) + cfg.q_size * D
    embed = V * D * (1 if cfg.tie_word_embeddings else 2)
    if cfg.ffn_kinds is not None:
        # gated experts at a share behind leading dense layers: the experts
        # HELD, the router over all, one shared expert, the output gate
        lo, hi = cfg.held_range
        attn += D * cfg.q_size
        moe = (3 * D * ((hi - lo) * cfg.moe_intermediate_size
                        + cfg.moe_shared_intermediate_size)
               + D * cfg.num_experts + cfg.num_experts)
        n_dense = cfg.leading_dense_layers
        norms = 4 * D + 2 * cfg.head_dim_
        return (n_dense * (attn + 3 * D * F + norms)
                + (L - n_dense) * (attn + moe + norms) + embed + D)
    if cfg.num_experts > 0:
        Fm = cfg.moe_intermediate_size or F
        ffn = cfg.num_experts * 3 * D * Fm + D * cfg.num_experts
    else:
        ffn = 3 * D * F
    return L * (attn + ffn + 2 * D) + embed + D


def train_flops_per_token(cfg: TransformerConfig, ctx_len: int) -> float:
    """fwd+bwd FLOPs per trained token: the standard 6P matmul estimate
    (active params only for MoE) plus causal attention's 6*L*D_attn*ctx
    term, which dominates at long context."""
    P = param_count(cfg)
    if cfg.ffn_kinds is not None:
        # of the held experts a token reaches k * held / all on average;
        # a sliding layer attends at most its window
        lo, hi = cfg.held_range
        idle = (hi - lo) * (1 - cfg.num_experts_per_tok / cfg.num_experts)
        P -= (cfg.ffn_kinds.count("moe") * idle
              * 3 * cfg.hidden_size * cfg.moe_intermediate_size)
        attn = 6 * cfg.q_size * sum(
            min(ctx_len / 2, cfg.sliding_window) if s else ctx_len / 2
            for s in cfg.layer_is_sliding
        )
        return 6.0 * P + 2.0 * attn
    if cfg.num_experts > 0:
        Fm = cfg.moe_intermediate_size or cfg.intermediate_size
        dense_share = cfg.num_experts_per_tok * 3 * cfg.hidden_size * Fm
        all_experts = cfg.num_experts * 3 * cfg.hidden_size * Fm
        P = P - cfg.num_layers * (all_experts - dense_share)
    attn = 6 * cfg.num_layers * cfg.q_size * ctx_len / 2  # causal half
    return 6.0 * P + 2.0 * attn  # qk^T and pv matmuls, fwd+bwd


def mfu(
    tokens_per_sec: float,
    cfg: TransformerConfig,
    ctx_len: int,
    n_chips: int = 1,
    peak_tflops: Optional[float] = None,
) -> Optional[float]:
    peak = peak_tflops or device_peak_tflops()
    if not peak:
        return None
    achieved = tokens_per_sec * train_flops_per_token(cfg, ctx_len) / 1e12
    return achieved / (peak * n_chips)


@contextlib.contextmanager
def profile_trace(log_dir: Optional[str]):
    """jax.profiler device trace scope; no-op when log_dir is falsy.  View
    with TensorBoard's profile plugin or Perfetto.  While it is open the
    `areal/<name>` host spans (utils/telemetry.py `span`) land on host lines
    of the same trace and the device's operations carry the SCOPES above
    (docs/observability.md, "Device profile")."""
    if not log_dir:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield
