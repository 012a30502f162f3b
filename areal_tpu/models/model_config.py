"""Model architecture config.

One dataclass covers the decoder-only families the reference supports via its
per-arch HF converters (reference: realhf/api/from_hf/{llama,qwen2,qwen3,
mistral,gemma,gpt2,mixtral}.py and lite's AutoModelForCausalLM path,
areal/engine/base_hf_engine.py:46): llama/mistral (no qkv bias, untied),
qwen2 (qkv bias), qwen3 (qk-norm, explicit head_dim), gemma/gemma2 (scaled
embeddings, zero-centred norms, sandwich norms, logit softcaps, alternating
sliding/full layers), gpt2 (LayerNorm+bias, learned positions, non-gated
gelu MLP, fused-qkv checkpoints).  MoE fields cover the mixtral/qwen3-moe
family.

TPU-first: the config is a frozen, hashable pytree-static object so it can be
closed over by `jax.jit` without retracing.
"""

import json
import os
from dataclasses import dataclass, replace
from typing import Optional


@dataclass(frozen=True)
class VisionConfig:
    """ViT vision tower (Qwen2-VL family shape: patchified pixels in,
    spatially-merged embeddings at the text width out)."""

    patch_size: int = 14
    temporal_patch_size: int = 2
    in_channels: int = 3
    hidden_size: int = 1280
    intermediate_size: int = 5120
    num_layers: int = 32
    num_heads: int = 16
    spatial_merge_size: int = 2  # 2x2 patches -> one embedding
    out_hidden_size: int = 4096  # text model width
    rms_norm_eps: float = 1e-6
    # Qwen2.5-VL windowed attention: blocks NOT in fullatt_block_indexes
    # attend only within window_size x window_size pixel tiles of their
    # image.  window_size == 0 means full attention in every block
    # (Qwen2-VL behavior).
    window_size: int = 0
    fullatt_block_indexes: tuple = ()

    @property
    def patch_dim(self) -> int:
        return self.in_channels * self.temporal_patch_size * self.patch_size**2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def replace(self, **kw) -> "VisionConfig":
        return replace(self, **kw)


# families `from_hf` builds (gemma3+ is refused by name further down)
KNOWN_MODEL_TYPES = frozenset({
    "llama", "mistral", "qwen2", "qwen3", "qwen3_moe", "mixtral", "gemma",
    "gemma2", "gpt2", "qwen2_vl", "qwen2_5_vl", "brumby", "nemotron_h",
    "afmoe", "longcat_flash", "mimo_v2", "jamba",
})

# block kinds of a heterogeneous stack (`TransformerConfig.layer_kinds`), by
# the letters of nemotron_h's `hybrid_override_pattern`: each block is ONE
# mixer under one pre-norm and one residual.  `jamba` adds two: the Mamba-1
# mixer ("S": the selective scan, `ops/mamba1.py`) and a dense gated FFN
# ("-", the letter nemotron_h's own alphabet has for it); a Jamba LAYER is
# two blocks, its mixer and then its FFN
MAMBA, MOE, ATTN, MAMBA1, FFN = "M", "E", "*", "S", "-"
LAYER_KINDS = (MAMBA, MOE, ATTN, MAMBA1, FFN)
# the kinds whose slot holds a recurrent state and a convolution window
SSM_KINDS = (MAMBA, MAMBA1)


def _experts_share(n_held: int, share: Optional[dict]) -> tuple:
    """(routed experts in all, the ids [lo, hi) held here or None for all)
    from the count of experts a config file holds and its `experts_held`
    {"first": id, "of": routed experts in all}."""
    if share is None:
        return n_held, None
    lo, n_experts = int(share["first"]), int(share["of"])
    if not 0 <= lo <= lo + n_held <= n_experts:
        raise ValueError(
            f"experts_held {share}: {n_held} experts from {lo} do not lie "
            f"in {n_experts}"
        )
    return n_experts, (lo, lo + n_held) if n_held != n_experts else None


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # defaults to hidden_size // num_heads
    max_position_embeddings: int = 32768
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    qkv_bias: bool = False  # qwen2
    qk_norm: bool = False  # qwen3
    attn_logit_softcap: Optional[float] = None  # gemma2
    sliding_window: Optional[int] = None
    # per-layer attention kinds (gemma2/3 alternate sliding/full): tuple of
    # bools, True = this layer uses the sliding window.  None = uniform
    # (every layer slides iff sliding_window is set, the mistral behavior).
    layer_is_sliding: Optional[tuple] = None
    # which layers get the rotary embedding: "all", or "sliding" (afmoe: a
    # full-attention layer carries no positional encoding)
    rope_layers: str = "all"  # all | sliding
    # the attention output times sigmoid(W_g input_norm(x)) elementwise
    # before the output projection (afmoe's `gate_proj`)
    attn_gate: bool = False
    # what a layer attends with: "softmax" over keys and values kept per
    # position, or "power_retention" (ops/power_retention.py): weights
    # ((q . k) / sqrt(d))^degree under a per-kv-head scalar gate, summarised
    # by a float32 state of fixed size per kv head — a slot of the serving
    # cache is then that state and not columns (brumby)
    # or "latent" (longcat_flash; DeepSeek's MLA): keys and values of all
    # heads expanded from ONE normed latent row a position, beside one
    # rotary key shared by the heads; a slot of the serving cache then holds
    # that row (`latent_row_dim` values, no head axis) a position and
    # attention sublayer
    # or "windowed" (mimo_v2): softmax layers of two kinds in one stack,
    # each with its own kv heads and rotary base: full layers keep every
    # position's key and value, sliding layers the last `sliding_window`
    # (a slot of the serving cache holds columns for the one kind and a
    # ring of the window for the other; `models/windowed.py`)
    attn_kind: str = "softmax"  # softmax | power_retention | latent | windowed
    # windowed: the sliding layers' kv heads and rotary base (the full
    # layers' are `num_kv_heads`, `rope_theta`), the share of a head's
    # leading dims that rotate, a constant on the values, and which kind's
    # softmax carries a learned sink (one scalar a query head that takes
    # mass and adds no value).  A value narrower than its key is
    # `v_head_dim`
    swa_num_kv_heads: int = 0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    attn_value_scale: float = 1.0
    sink_sliding: bool = False
    sink_full: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the normed latents times sqrt(hidden / rank)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    retention_degree: int = 2
    retention_chunk: int = 128  # tokens per chunk of the chunked form

    # a heterogeneous stack (nemotron_h): the kind of every block, a tuple
    # of LAYER_KINDS letters; None = every block is attention + FFN.  The
    # parameters are then stacked per kind and a slot of the serving cache
    # holds BOTH the recurrent state of every Mamba block and the K/V
    # columns of every attention block
    layer_kinds: Optional[tuple] = None
    # Mamba-2 (ops/mamba2.py): d_inner = mamba_num_heads * mamba_head_dim
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    mamba_n_groups: int = 1  # B/C groups; head h reads group h // (H / G)
    conv_kernel: int = 4
    mamba_chunk: int = 128  # tokens per chunk of the chunked (SSD) form
    # Mamba-1 (ops/mamba1.py; 0 = the stack has none): d_inner =
    # mamba_expand * hidden_size, a state of [ssm_state_size, d_inner] a
    # block, the step size a channel out of a projection of mamba_dt_rank
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    # jamba's own keys for where its layer kinds lie (attn_layer_period,
    # attn_layer_offset, expert_layer_period, expert_layer_offset); None =
    # another family
    jamba_layer_rule: Optional[tuple] = None
    # how dt_bias is drawn (Mamba-2's own initialisation; init_params)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4

    # gemma-family structure knobs (reference keeps a gemma converter,
    # realhf/api/from_hf/gemma.py; defaults reproduce the llama family)
    hidden_act: str = "silu"  # silu | gelu_pytorch_tanh | gelu
    scale_embeddings: bool = False  # multiply embeds by sqrt(hidden_size)
    norm_unit_offset: bool = False  # RMSNorm weight stored zero-centered
    sandwich_norms: bool = False  # gemma2: extra norms on attn/ffn outputs
    final_logit_softcap: Optional[float] = None  # gemma2 lm-head tanh cap
    query_pre_attn_scalar: Optional[float] = None  # softmax scale = qpas^-0.5

    # gpt2-family structure knobs (reference: realhf/api/from_hf/gpt2.py)
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm (mean-centred + bias)
    pos_emb: str = "rope"  # rope | learned (wpe table added to embeds) | none
    mlp_gated: bool = True  # False: w_up -> act -> w_down (no gate branch)
    attn_output_bias: bool = False  # bias on the attention out-projection
    mlp_bias: bool = False  # biases on the MLP projections

    # MoE (mixtral / qwen3-moe); num_experts == 0 means dense
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: Optional[int] = None
    moe_capacity_factor: float = 1.25  # per-expert token budget multiplier
    moe_aux_coef: float = 0.01  # Switch load-balance loss coefficient
    # "dropless": exact HF Mixtral/Qwen3-MoE semantics — every routed token
    # reaches its expert (sort + lax.ragged_dot grouped GEMM).  "capacity":
    # GShard capacity-bounded dense dispatch (tokens beyond the per-expert
    # budget are dropped under routing imbalance; cheapest under ep
    # sharding).  HF-loaded checkpoints default to dropless so logits match
    # the source model regardless of batch size (ADVICE r3).
    moe_impl: str = "capacity"  # capacity | dropless
    # top-k weights divided by their sum (mixtral, released qwen-moe,
    # nemotron_h); False keeps the softmax probabilities as they are
    norm_topk_prob: bool = True
    # "softmax" over the router's logits, or (nemotron_h) "sigmoid" scores
    # chosen by score + a selection bias and weighted by the score alone
    router_kind: str = "softmax"  # softmax | sigmoid
    routed_scaling_factor: float = 1.0
    # latent experts: un-gated squared-ReLU MLPs in a space of this width
    # between two latent projections (None = experts at the model's width)
    moe_latent_size: Optional[int] = None
    moe_shared_intermediate_size: Optional[int] = None  # one shared expert
    # the experts THIS program holds, ids [lo, hi) of `num_experts` (None =
    # all): the router scores and chooses over all `num_experts`, and the
    # expert layer computes the part of the result its own experts give,
    # for the tokens routed to them (the weights normalised over every
    # chosen expert, wherever it lives).  What the others would add is left
    # out; no exchange between shares is simulated
    experts_held: Optional[tuple] = None
    # identity ("zero-compute") experts: router outputs `num_experts` ..
    # `num_experts + zero_expert_num` whose expert is the token itself, so a
    # choice among them costs no product (longcat_flash)
    zero_expert_num: int = 0
    # leading layers whose FFN is a dense gated MLP of `intermediate_size`
    # in a model whose other layers hold experts (afmoe).  The stack is
    # then two parameter trees, `layers["dense"]` and `layers["moe"]`
    leading_dense_layers: int = 0

    # LoRA (0 = off); targets use HF module names (models/lora.py TARGET_MAP)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ()

    # numerics
    dtype: str = "bfloat16"  # compute/activation dtype
    param_dtype: str = "float32"  # master weights
    remat: bool = True  # jax.checkpoint each layer (or layer group)
    # "full": recompute everything in backward (min HBM);
    # "dots": save matmul outputs, recompute elementwise only — trades HBM
    # for ~the forward matmul FLOPs of the backward recompute;
    # "save_attn"/"save_mlp": keep only the tagged attention/MLP outputs
    # (checkpoint_name in _layer_forward) — the selective rungs between;
    # "carry_offload": save the tagged attention AND MLP outputs but park
    # them in pinned host memory (save_and_offload_only_these_names) —
    # trades the HBM pressure that kills selective rungs at long context
    # for PCIe/host traffic the backward overlaps with recompute
    remat_policy: str = "full"  # full | dots | save_attn | save_mlp | carry_offload
    # two-level layer scan: the outer lax.scan runs num_layers /
    # layer_group_size steps, each step an unrolled chain of
    # layer_group_size layers wrapped in ONE jax.checkpoint at the group
    # boundary.  Only inter-group activations are saved (within-group ones
    # are recomputed), so the backward scan-transpose carry shrinks ~G× in
    # entry count — the sqrt-remat regime a per-layer checkpoint cannot
    # express.  Must divide num_layers (rejected loudly otherwise); 1
    # reproduces the classic per-layer scan exactly.
    layer_group_size: int = 1
    # outer-scan unroll factor: >1 trades compile time for less per-step
    # scan overhead (dynamic-update-slice carry traffic); must divide the
    # outer scan length (num_layers / layer_group_size) — non-divisors
    # warn loudly and fall back to 1 (models/transformer.py
    # effective_scan_unroll)
    scan_unroll: int = 1
    # lax.scan(_split_transpose=...): split the backward (transposed) layer
    # scan into two passes — XLA can then overlap the grad-accumulation
    # carry writes differently; measured per-hardware, off by default
    scan_split_transpose: bool = False
    # attention implementation: "auto" picks the Pallas splash kernel on TPU
    # when shapes allow and the naive einsum path elsewhere; "ring" shards
    # K/V along the sequence over the sp axis with rotating blocks — the
    # context-parallel regime for contexts too long for per-chip whole-K/V
    # (ops/attention.py ring_attention; falls back to auto — with a
    # warning — without an sp>1 mesh axis or with per-layer sliding
    # windows, which are mask-based)
    attn_impl: str = "auto"  # auto | splash | naive | ring

    # vision-language (None = text-only); Qwen2-VL-style mrope: the rope
    # frequency bands are split into (temporal, height, width) sections
    vision: Optional[VisionConfig] = None
    image_token_id: Optional[int] = None
    mrope_section: Optional[tuple] = None  # e.g. (16, 24, 24); sums to hd/2

    # bookkeeping
    hf_architecture: str = "LlamaForCausalLM"
    bos_token_id: Optional[int] = 1
    eos_token_id: Optional[int] = 2

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim_

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim_

    @property
    def held_range(self) -> tuple:
        return self.experts_held or (0, self.num_experts)

    @property
    def mamba_d_inner(self) -> int:
        if self.mamba_expand:
            return self.mamba_expand * self.hidden_size
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C (Mamba-2);
        the channels of u alone (Mamba-1)."""
        if self.mamba_expand:
            return self.mamba_d_inner
        return (
            self.mamba_d_inner + 2 * self.mamba_n_groups * self.ssm_state_size
        )

    @property
    def ffn_kinds(self) -> Optional[tuple]:
        """"dense" / "moe" for every layer of a stack of gated experts
        behind leading dense layers (sigmoid-routed, attention + FFN in
        every block: afmoe); None for every other model."""
        if (self.layer_kinds is not None or self.num_experts <= 0
                or self.router_kind != "sigmoid"
                or self.attn_kind == "windowed"):
            return None
        n = self.leading_dense_layers
        return ("dense",) * n + ("moe",) * (self.num_layers - n)

    @property
    def latent_row_dim(self) -> int:
        """Values of one latent row of the serving cache: the normed latent
        and the rotary key all heads share."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_sublayers(self) -> int:
        """Attention sublayers of the stack: two a layer where a layer is a
        double block (latent attention), else one."""
        return self.num_layers * (2 if self.attn_kind == "latent" else 1)

    @property
    def rotary_dim(self) -> int:
        """Leading dims of a head that rotate (`partial_rotary_factor`)."""
        return int(self.partial_rotary_factor * self.head_dim_)

    @property
    def window_ring(self) -> int:
        """Positions a sliding layer of a windowed stack keeps a slot: the
        window, rounded up to the eight rows the chip tiles by."""
        return -(-int(self.sliding_window or 0) // 8) * 8

    def n_kind(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds or () if k == kind)

    @property
    def ssm_kind(self) -> Optional[str]:
        """The one kind of recurrent block a heterogeneous stack has (a
        slot's state leaf has one shape), None without any."""
        return next((k for k in SSM_KINDS if k in (self.layer_kinds or ())), None)

    def replace(self, **kw) -> "TransformerConfig":
        return replace(self, **kw)

    # ------------------------------------------------------------------
    # HF interop
    # ------------------------------------------------------------------

    @classmethod
    def from_hf(cls, path_or_dict) -> "TransformerConfig":
        """Build from an HF `config.json` (path to a checkpoint dir, a json
        file, or an already-parsed dict)."""
        if isinstance(path_or_dict, dict):
            d = path_or_dict
        else:
            p = path_or_dict
            if os.path.isdir(p):
                p = os.path.join(p, "config.json")
            with open(p) as f:
                d = json.load(f)
        archs = d.get("architectures") or ["LlamaForCausalLM"]
        arch = archs[0]
        model_type = d.get("model_type") or (
            # the published file of this family states no model_type
            "longcat_flash" if d.get("attention_method") == "MLA"
            and "zero_expert_num" in d else "llama"
        )
        if model_type not in KNOWN_MODEL_TYPES and not model_type.startswith(
            "gemma"  # refused by name below
        ):
            # reading an unknown family as llama would run a softmax model
            # under its name without a word
            raise ValueError(
                f"unsupported model_type {model_type!r}: this runtime builds "
                f"{sorted(KNOWN_MODEL_TYPES)}"
            )
        if model_type == "nemotron_h":
            return cls._from_nemotron_h(d, arch)
        if model_type == "jamba":
            return cls._from_jamba(d, arch)
        if model_type == "afmoe":
            return cls._from_afmoe(d, arch)
        if model_type == "longcat_flash":
            return cls._from_longcat_flash(d, arch)
        if model_type == "mimo_v2":
            return cls._from_mimo_v2(d, arch)
        if model_type == "gpt2":
            # entirely different key names (n_embd/n_layer/...) and block
            # structure: LayerNorm, learned positions, fused-qkv Conv1D,
            # non-gated gelu MLP, biases throughout, always-tied head
            act = d.get("activation_function", "gelu_new")
            return cls(
                vocab_size=d["vocab_size"],
                hidden_size=d["n_embd"],
                intermediate_size=d.get("n_inner") or 4 * d["n_embd"],
                num_layers=d["n_layer"],
                num_heads=d["n_head"],
                num_kv_heads=d["n_head"],
                max_position_embeddings=d.get("n_positions", 1024),
                rms_norm_eps=float(d.get("layer_norm_epsilon", 1e-5)),
                tie_word_embeddings=True,
                qkv_bias=True,
                attn_output_bias=True,
                mlp_bias=True,
                mlp_gated=False,
                norm_type="layernorm",
                pos_emb="learned",
                # pass unknown activations through: _act raises loudly for
                # unsupported ones instead of silently running gelu
                hidden_act=(
                    "gelu_pytorch_tanh" if act in ("gelu_new", "gelu_pytorch_tanh")
                    else act
                ),
                hf_architecture=arch,
                bos_token_id=d.get("bos_token_id", 50256),
                eos_token_id=d.get("eos_token_id", 50256),
            )
        qkv_bias = bool(d.get("attention_bias", False))
        qk_norm = False
        if model_type == "qwen2":
            # qwen2 HF configs carry no attention_bias flag; bias is implied
            qkv_bias = d.get("attention_bias", True)
        if model_type in ("qwen3", "qwen3_moe", "brumby"):
            # brumby is Qwen3's block with power retention in every layer
            qkv_bias = bool(d.get("attention_bias", False))
            qk_norm = True
        gemma = model_type.startswith("gemma")
        if gemma and model_type not in ("gemma", "gemma2"):
            # gemma3+ adds qk-norm / local-rope / different layer_types
            # semantics — loading it with gemma1/2 structure would run but
            # silently produce wrong logits
            raise ValueError(
                f"unsupported gemma variant {model_type!r}: only gemma and "
                "gemma2 checkpoints are implemented"
            )
        num_layers = d["num_hidden_layers"]
        layer_is_sliding = None
        sliding_window = (
            d.get("sliding_window")
            if d.get("use_sliding_window", model_type == "mistral")
            else None
        )
        if model_type == "gemma2":
            # alternating local/global attention; HF encodes it as
            # layer_types, older configs imply sliding on even layers
            sliding_window = d.get("sliding_window")
            lt = d.get("layer_types")
            if lt is not None:
                layer_is_sliding = tuple(t == "sliding_attention" for t in lt)
            else:
                layer_is_sliding = tuple(
                    i % 2 == 0 for i in range(num_layers)
                )
            if sliding_window is None or not any(layer_is_sliding):
                # no layer actually slides: drop the window entirely so the
                # uniform-window (mistral) path can't window every layer
                layer_is_sliding = None
                sliding_window = None
        num_heads = d["num_attention_heads"]
        n_experts = d.get("num_local_experts", d.get("num_experts", 0)) or 0
        # mixtral always renormalises its top-k gates; qwen-moe says
        # (transformers' Qwen3MoeConfig defaults the key to false)
        norm_topk_prob = (
            bool(d.get("norm_topk_prob", False))
            if model_type.startswith("qwen") else True
        )
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        # activation key precedence per model type, matching transformers
        # >=4.57: Gemma2MLP reads config.hidden_activation (default tanh),
        # GemmaMLP reads config.hidden_act only (hidden_activation ignored,
        # legacy 'gelu' runs EXACT gelu), everything else reads hidden_act —
        # pinned by test_legacy_gemma_act_parity
        if model_type == "gemma2":
            hidden_act = d.get("hidden_activation") or "gelu_pytorch_tanh"
        elif gemma:
            hidden_act = d.get("hidden_act") or "gelu_pytorch_tanh"
        else:
            hidden_act = d.get("hidden_act") or "silu"
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 4 * d["hidden_size"]),
            num_layers=num_layers,
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim", 256 if gemma else None),
            max_position_embeddings=d.get("max_position_embeddings", 32768),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", gemma)),
            qkv_bias=qkv_bias,
            qk_norm=qk_norm,
            sliding_window=sliding_window,
            layer_is_sliding=layer_is_sliding,
            attn_kind=(
                "power_retention" if model_type == "brumby" else "softmax"
            ),
            # the published config carries neither: the family's description
            # (degree 2) and this runtime's choice of chunk
            retention_degree=int(d.get("retention_degree", 2)),
            retention_chunk=int(d.get("retention_chunk", 128)),
            hidden_act=hidden_act,
            scale_embeddings=gemma,
            norm_unit_offset=gemma,
            sandwich_norms=model_type == "gemma2",
            final_logit_softcap=(
                d.get("final_logit_softcapping")
                if model_type == "gemma2"
                else None
            ),
            attn_logit_softcap=(
                d.get("attn_logit_softcapping")
                if model_type == "gemma2"
                else None
            ),
            query_pre_attn_scalar=(
                float(d["query_pre_attn_scalar"])
                if d.get("query_pre_attn_scalar") is not None
                and model_type == "gemma2"
                else None
            ),
            num_experts=d.get("num_local_experts", d.get("num_experts", 0)) or 0,
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            moe_intermediate_size=d.get("moe_intermediate_size"),
            # real HF MoE checkpoints (mixtral/qwen3-moe) are dropless;
            # running them through the capacity path silently drops tokens
            # under routing imbalance and makes logits batch-size-dependent
            moe_impl="dropless" if n_experts > 0 else "capacity",
            norm_topk_prob=norm_topk_prob,
            hf_architecture=arch,
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
            # qwen2-VL-style vision config (this repo's saver emits the same
            # shape, so VLM checkpoints round-trip)
            vision=(
                VisionConfig(
                    patch_size=vd.get("patch_size", 14),
                    temporal_patch_size=vd.get("temporal_patch_size", 2),
                    in_channels=vd.get("in_channels", 3),
                    hidden_size=vd.get("hidden_size", 1280),
                    intermediate_size=vd.get("intermediate_size", 5120),
                    num_layers=vd.get("depth", vd.get("num_hidden_layers", 32)),
                    num_heads=vd.get("num_heads", 16),
                    spatial_merge_size=vd.get("spatial_merge_size", 2),
                    out_hidden_size=vd.get("out_hidden_size", d["hidden_size"]),
                    window_size=vd.get("window_size", 0) or 0,
                    fullatt_block_indexes=tuple(
                        vd.get("fullatt_block_indexes", ()) or ()
                    ),
                )
                if (vd := d.get("vision_config")) is not None
                else None
            ),
            image_token_id=d.get("image_token_id"),
            mrope_section=(
                tuple(d["rope_scaling"]["mrope_section"])
                if isinstance(d.get("rope_scaling"), dict)
                and d["rope_scaling"].get("mrope_section")
                else None
            ),
        )

    @classmethod
    def _from_nemotron_h(cls, d: dict, arch: str) -> "TransformerConfig":
        """`nemotron_h`: a stack of Mamba-2, attention and latent
        mixture-of-experts blocks by `hybrid_override_pattern`.  A share of
        an expert-parallel deployment says so with `experts_held`:
        {"first": id, "of": routed experts in all}; `n_routed_experts` then
        counts the experts held here."""
        pattern = d["hybrid_override_pattern"]
        L = d["num_hidden_layers"]
        bad = sorted(set(pattern) - set(LAYER_KINDS))
        if bad or len(pattern) != L:
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: {L} letters of "
                f"{LAYER_KINDS} wanted" + (f", not {bad}" if bad else "")
            )
        if d.get("n_group", 1) != 1 or d.get("topk_group", 1) != 1:
            raise ValueError(
                "nemotron_h with group-limited routing (n_group / "
                "topk_group > 1) is not implemented"
            )
        if d.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError(
                f"nemotron_h mlp_hidden_act {d['mlp_hidden_act']!r}: only "
                "relu2 (squared ReLU) is implemented"
            )
        for key in ("use_bias", "mlp_bias", "mamba_proj_bias",
                    "attention_bias"):
            if d.get(key, False):
                raise ValueError(f"nemotron_h with {key} is not implemented")
        if not d.get("use_conv_bias", True):
            raise ValueError("nemotron_h without a conv bias is not implemented")
        n_experts, held = _experts_share(
            d.get("n_routed_experts", 0) or 0, d.get("experts_held")
        )
        if MOE in pattern and (
            not n_experts or d.get("n_shared_experts", 1) != 1
        ):
            raise ValueError(
                "nemotron_h expert blocks need n_routed_experts and exactly "
                "one shared expert"
            )
        if MAMBA in pattern and (
            d["mamba_num_heads"] % d.get("n_groups", 1)
        ):
            raise ValueError("n_groups must divide mamba_num_heads")
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        num_heads = d["num_attention_heads"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 4 * d["hidden_size"]),
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim"),
            max_position_embeddings=d.get("max_position_embeddings", 32768),
            # kept for the round trip: the published modeling code builds
            # no rotary embedding (pos_emb "none")
            rope_theta=float(d.get("rope_theta", 10000.0)),
            pos_emb="none",
            rms_norm_eps=float(
                d.get("layer_norm_epsilon", d.get("norm_eps", 1e-5))
            ),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            layer_kinds=tuple(pattern),
            mamba_num_heads=d.get("mamba_num_heads", 0),
            mamba_head_dim=d.get("mamba_head_dim", 0),
            ssm_state_size=d.get("ssm_state_size", 0),
            mamba_n_groups=d.get("n_groups", 1),
            conv_kernel=d.get("conv_kernel", 4),
            mamba_chunk=d.get("chunk_size", 128),
            time_step_min=float(d.get("time_step_min", 0.001)),
            time_step_max=float(d.get("time_step_max", 0.1)),
            time_step_floor=float(d.get("time_step_floor", 1e-4)),
            hidden_act="relu2",
            num_experts=n_experts,
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            moe_intermediate_size=d.get("moe_intermediate_size"),
            moe_impl="dropless",
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            router_kind="sigmoid",
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            moe_latent_size=d.get("moe_latent_size"),
            moe_shared_intermediate_size=d.get(
                "moe_shared_expert_intermediate_size"
            ),
            experts_held=held,
            hf_architecture=arch,
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
        )

    @classmethod
    def _from_jamba(cls, d: dict, arch: str) -> "TransformerConfig":
        """`jamba`: every layer a mixer and then a dense gated FFN, each
        under its own pre-norm and residual; the mixer is attention where
        `l % attn_layer_period == attn_layer_offset`, else Mamba-1, with
        RMS norms on the step-size, B and C projections; no positional
        encoding.  Built as a heterogeneous stack of TWO blocks a layer.
        The family's expert variant (`num_experts` > 1 at
        `expert_layer_period` / `expert_layer_offset`) is refused."""
        if d.get("num_experts", 1) > 1:
            raise ValueError(
                f"jamba with num_experts {d['num_experts']} is not "
                "implemented: softmax-routed experts behind a Mamba-1 or "
                "attention mixer (expert_layer_period / expert_layer_offset)"
                " are not built; only the dense variant (num_experts 1) is"
            )
        if d.get("mamba_proj_bias", False):
            raise ValueError("jamba with mamba_proj_bias is not implemented")
        if not d.get("mamba_conv_bias", True):
            raise ValueError("jamba without a conv bias is not implemented")
        if d.get("sliding_window") is not None:
            raise ValueError("jamba with a sliding_window is not implemented")
        L = d["num_hidden_layers"]
        period, offset = d["attn_layer_period"], d["attn_layer_offset"]
        kinds = tuple(
            k for l in range(L)
            for k in (ATTN if l % period == offset else MAMBA1, FFN)
        )
        if not {ATTN, MAMBA1} <= set(kinds):
            raise ValueError(
                f"jamba attn_layer_period {period} / attn_layer_offset "
                f"{offset} over {L} layers: both attention and Mamba "
                "layers are wanted"
            )
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        D = d["hidden_size"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=D,
            intermediate_size=d["intermediate_size"],
            num_layers=len(kinds),  # blocks: two a published layer
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            max_position_embeddings=d.get("max_position_embeddings", 262144),
            pos_emb="none",
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            layer_kinds=kinds,
            # kept for the round trip (the layer rule and the expert keys,
            # which select nothing at num_experts 1)
            jamba_layer_rule=(
                period, offset, d.get("expert_layer_period", 2),
                d.get("expert_layer_offset", 1),
            ),
            mamba_expand=d.get("mamba_expand", 2),
            mamba_dt_rank=(
                -(-D // 16) if d.get("mamba_dt_rank", "auto") == "auto"
                else d["mamba_dt_rank"]
            ),
            ssm_state_size=d.get("mamba_d_state", 16),
            conv_kernel=d.get("mamba_d_conv", 4),
            hidden_act=d.get("hidden_act") or "silu",
            hf_architecture=(
                arch if d.get("architectures") else "JambaForCausalLM"),
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
        )

    def _to_jamba(self) -> dict:
        period, offset, e_period, e_offset = self.jamba_layer_rule
        return {
            "architectures": [self.hf_architecture],
            "model_type": "jamba",
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "num_hidden_layers": self.num_layers // 2,
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "attn_layer_period": period,
            "attn_layer_offset": offset,
            "expert_layer_period": e_period,
            "expert_layer_offset": e_offset,
            "num_experts": 1,
            "num_experts_per_tok": 1,
            "mamba_d_state": self.ssm_state_size,
            "mamba_d_conv": self.conv_kernel,
            "mamba_expand": self.mamba_expand,
            "mamba_dt_rank": self.mamba_dt_rank,
            "mamba_conv_bias": True,
            "mamba_proj_bias": False,
            "hidden_act": self.hidden_act,
            "max_position_embeddings": self.max_position_embeddings,
            "rms_norm_eps": self.rms_norm_eps,
            "sliding_window": None,
            "tie_word_embeddings": self.tie_word_embeddings,
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }

    @classmethod
    def _from_afmoe(cls, d: dict, arch: str) -> "TransformerConfig":
        """`afmoe` (Arcee Trinity): every block attention + FFN under four
        RMS norms; sliding-window layers with rotary embedding and full
        layers without, by `layer_types`; q/k norm; the attention output
        under a sigmoid gate; `num_dense_layers` leading dense blocks, then
        sigmoid-routed gated experts beside one shared expert; the
        embedding times sqrt(hidden) (`mup_enabled`).  A share of an
        expert-parallel deployment says so with `experts_held` as
        `nemotron_h` does; `num_experts` then counts the experts held."""
        L = d["num_hidden_layers"]
        lt = d.get("layer_types")
        if lt is None or len(lt) != L or set(lt) - {
            "sliding_attention", "full_attention"
        }:
            raise ValueError(
                f"afmoe layer_types {lt!r}: {L} of sliding_attention / "
                "full_attention wanted"
            )
        if d.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError(
                f"afmoe score_func {d['score_func']!r}: only sigmoid is "
                "implemented"
            )
        for key in ("n_group", "topk_group", "num_expert_groups",
                    "num_limited_groups"):
            if d.get(key, 1) != 1:
                raise ValueError(
                    f"afmoe with group-limited routing ({key} > 1) is not "
                    "implemented"
                )
        if d.get("rope_scaling") is not None:
            raise ValueError("afmoe with rope_scaling is not implemented")
        if d.get("num_shared_experts", 1) != 1:
            raise ValueError("afmoe needs exactly one shared expert")
        n_dense = int(d.get("num_dense_layers", 0))
        n_experts, held = _experts_share(
            d.get("num_experts", 0) or 0, d.get("experts_held")
        )
        if not n_experts or not 0 <= n_dense < L:
            raise ValueError(
                f"afmoe needs num_experts and num_dense_layers < {L} layers"
            )
        sliding = tuple(t == "sliding_attention" for t in lt)
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        num_heads = d["num_attention_heads"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim"),
            max_position_embeddings=d.get("max_position_embeddings", 131072),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rope_layers="sliding",
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-5)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            qk_norm=True,
            attn_gate=True,
            sliding_window=d["sliding_window"] if any(sliding) else None,
            layer_is_sliding=sliding,
            hidden_act=d.get("hidden_act") or "silu",
            scale_embeddings=bool(d.get("mup_enabled", False)),
            sandwich_norms=True,
            num_experts=n_experts,
            num_experts_per_tok=d.get("num_experts_per_tok", 8),
            moe_intermediate_size=d["moe_intermediate_size"],
            moe_shared_intermediate_size=d["moe_intermediate_size"],
            moe_impl="dropless",
            # the balancing loss (`load_balance_coeff`) is not built: the
            # cell and RL fine-tuning train with the bias held fixed
            moe_aux_coef=0.0,
            norm_topk_prob=bool(d.get("route_norm", True)),
            router_kind="sigmoid",
            routed_scaling_factor=float(d.get("route_scale", 1.0)),
            experts_held=held,
            leading_dense_layers=n_dense,
            hf_architecture=arch,
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
        )

    @classmethod
    def _from_longcat_flash(cls, d: dict, arch: str) -> "TransformerConfig":
        """`longcat_flash` (LongCat-Flash, the language model of
        LongCat-Flash-Omni), by its own keys: every layer two latent
        attention sublayers and two dense FFNs around ONE expert layer on a
        shortcut (`models/latent.py`); a softmax router over
        `n_routed_experts` + `zero_expert_num` outputs chosen by score + a
        bias and weighted by the score alone, times `routed_scaling_factor`;
        no shared expert.  `num_layers` counts the double layers.  A share
        of an expert-parallel deployment says so with `experts_held` as
        `nemotron_h` does."""
        if d.get("zero_expert_type", "identity") != "identity":
            raise ValueError(
                f"longcat_flash zero_expert_type {d['zero_expert_type']!r}: "
                "only identity experts are implemented"
            )
        if d.get("attention_method", "MLA") != "MLA":
            raise ValueError(
                f"longcat_flash attention_method {d['attention_method']!r}: "
                "only MLA is implemented"
            )
        if d.get("rope_scaling") is not None:
            raise ValueError("longcat_flash with rope_scaling is not implemented")
        if d.get("attention_bias", False):
            raise ValueError("longcat_flash with attention_bias is not implemented")
        n_experts, held = _experts_share(
            d["n_routed_experts"], d.get("experts_held")
        )
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        nope, rope = d["qk_nope_head_dim"], d["qk_rope_head_dim"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["ffn_hidden_size"],
            num_layers=d["num_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_attention_heads"],
            head_dim=nope + rope,
            max_position_embeddings=d.get("max_position_embeddings", 131072),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-5)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            attn_kind="latent",
            q_lora_rank=d["q_lora_rank"],
            kv_lora_rank=d["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=d["v_head_dim"],
            mla_scale_q_lora=bool(d.get("mla_scale_q_lora", False)),
            mla_scale_kv_lora=bool(d.get("mla_scale_kv_lora", False)),
            hidden_act=d.get("hidden_act") or "silu",
            num_experts=n_experts,
            num_experts_per_tok=d["moe_topk"],
            moe_intermediate_size=d["expert_ffn_hidden_size"],
            moe_impl="dropless",
            moe_aux_coef=0.0,
            norm_topk_prob=False,
            router_kind="softmax",
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            zero_expert_num=int(d.get("zero_expert_num", 0)),
            experts_held=held,
            hf_architecture=arch,
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
        )

    @classmethod
    def _from_mimo_v2(cls, d: dict, arch: str) -> "TransformerConfig":
        """`mimo_v2` (MiMo-V2-Flash, the language model of MiMo-V2.5), by its
        own keys: every block attention + FFN under two RMS norms; full
        layers (`hybrid_layer_pattern` 0) beside sliding ones (1), each
        kind with its own kv heads and rotary base, rotary embedding on the
        leading `partial_rotary_factor` of a head, a value narrower than
        its key times `attention_value_scale`, a learned sink in the
        softmax of the kinds that say so; `moe_layer_freq` 0 = a dense
        gated FFN, 1 = sigmoid-routed gated experts chosen by score + bias
        (`noaux_tc`), no shared expert.  A share of an expert-parallel
        deployment says so with `experts_held` as `nemotron_h` does;
        `n_routed_experts` then counts the experts held
        (`models/windowed.py`)."""
        L = d["num_hidden_layers"]
        pattern, freq = d.get("hybrid_layer_pattern"), d.get("moe_layer_freq")
        for key, got in (("hybrid_layer_pattern", pattern),
                         ("moe_layer_freq", freq)):
            if got is None or len(got) != L or set(got) - {0, 1}:
                raise ValueError(f"mimo_v2 {key} {got!r}: {L} of 0 / 1 wanted")
        n_dense = L - sum(freq)
        if list(freq) != [0] * n_dense + [1] * (L - n_dense):
            raise ValueError(
                f"mimo_v2 moe_layer_freq {freq!r}: dense layers anywhere but "
                "at the head of the stack are not implemented"
            )
        if d.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError(
                f"mimo_v2 scoring_func {d['scoring_func']!r}: only sigmoid "
                "is implemented"
            )
        for key in ("n_group", "topk_group"):
            if (d.get(key) or 1) != 1:
                raise ValueError(
                    f"mimo_v2 with group-limited routing ({key} > 1) is not "
                    "implemented"
                )
        scaling = d.get("rope_scaling") or {}
        if scaling.get("rope_type", scaling.get("type", "default")) != "default":
            raise ValueError("mimo_v2 with rope_scaling is not implemented")
        if d.get("attention_bias", False):
            raise ValueError("mimo_v2 with attention_bias is not implemented")
        if d.get("n_shared_experts"):
            raise ValueError("mimo_v2 with shared experts is not implemented")
        num_heads, hd = d["num_attention_heads"], d["head_dim"]
        for key, want in (("swa_num_attention_heads", num_heads),
                          ("swa_head_dim", hd),
                          ("swa_v_head_dim", d["v_head_dim"])):
            if d.get(key, want) != want:
                raise ValueError(
                    f"mimo_v2 {key} {d[key]!r}: sliding layers with other "
                    "query heads or widths than the full layers' are not "
                    "implemented"
                )
        window = d.get("sliding_window", d.get("sliding_window_size"))
        sliding = tuple(bool(t) for t in pattern)
        if any(sliding) and not window:
            raise ValueError("mimo_v2 sliding layers need a sliding_window")
        n_experts, held = _experts_share(
            d["n_routed_experts"], d.get("experts_held")
        )
        eos = d.get("eos_token_id", 2)
        if isinstance(eos, list):
            eos = eos[0]
        scale = d.get("routed_scaling_factor")
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=num_heads,
            num_kv_heads=d["num_key_value_heads"],
            head_dim=hd,
            v_head_dim=d["v_head_dim"],
            max_position_embeddings=d.get("max_position_embeddings", 1048576),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=float(d.get("layernorm_epsilon", 1e-5)),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            attn_kind="windowed",
            sliding_window=window if any(sliding) else None,
            layer_is_sliding=sliding,
            swa_num_kv_heads=d.get(
                "swa_num_key_value_heads", d["num_key_value_heads"]),
            swa_rope_theta=float(
                d.get("swa_rope_theta", d.get("rope_theta", 10000.0))),
            partial_rotary_factor=float(d.get("partial_rotary_factor", 1.0)),
            attn_value_scale=float(d.get("attention_value_scale") or 1.0),
            sink_sliding=bool(d.get("add_swa_attention_sink_bias", False)),
            sink_full=bool(d.get("add_full_attention_sink_bias", False)),
            hidden_act=d.get("hidden_act") or "silu",
            num_experts=n_experts,
            num_experts_per_tok=d["num_experts_per_tok"],
            moe_intermediate_size=d["moe_intermediate_size"],
            moe_impl="dropless",
            moe_aux_coef=0.0,
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            router_kind="sigmoid",
            routed_scaling_factor=1.0 if scale is None else float(scale),
            experts_held=held,
            leading_dense_layers=n_dense,
            hf_architecture=arch,
            bos_token_id=d.get("bos_token_id", 1),
            eos_token_id=eos,
        )

    def _to_mimo_v2(self) -> dict:
        lo, hi = self.held_range
        L, n_dense = self.num_layers, self.leading_dense_layers
        d = {
            "architectures": [self.hf_architecture],
            "model_type": "mimo_v2",
            "attention_bias": False,
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "moe_intermediate_size": self.moe_intermediate_size,
            "num_hidden_layers": L,
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "head_dim": self.head_dim_,
            "v_head_dim": self.v_head_dim,
            "swa_num_attention_heads": self.num_heads,
            "swa_num_key_value_heads": self.swa_num_kv_heads,
            "swa_head_dim": self.head_dim_,
            "swa_v_head_dim": self.v_head_dim,
            "hybrid_layer_pattern": [
                int(t) for t in self.layer_is_sliding or (False,) * L],
            "moe_layer_freq": [0] * n_dense + [1] * (L - n_dense),
            "sliding_window": self.sliding_window,
            "sliding_window_size": self.sliding_window,
            "rope_theta": self.rope_theta,
            "swa_rope_theta": self.swa_rope_theta,
            "partial_rotary_factor": self.partial_rotary_factor,
            "attention_value_scale": self.attn_value_scale,
            "add_swa_attention_sink_bias": self.sink_sliding,
            "add_full_attention_sink_bias": self.sink_full,
            "max_position_embeddings": self.max_position_embeddings,
            "layernorm_epsilon": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act,
            "n_routed_experts": hi - lo,
            "n_shared_experts": None,
            "num_experts_per_tok": self.num_experts_per_tok,
            "norm_topk_prob": self.norm_topk_prob,
            "scoring_func": "sigmoid",
            "topk_method": "noaux_tc",
            "n_group": 1,
            "topk_group": 1,
            "routed_scaling_factor": self.routed_scaling_factor,
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }
        if self.experts_held is not None:
            d["experts_held"] = {"first": lo, "of": self.num_experts}
        return d

    def _to_longcat_flash(self) -> dict:
        lo, hi = self.held_range
        d = {
            "architectures": [self.hf_architecture],
            "model_type": "longcat_flash",
            "attention_method": "MLA",
            "attention_bias": False,
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "ffn_hidden_size": self.intermediate_size,
            "expert_ffn_hidden_size": self.moe_intermediate_size,
            "num_layers": self.num_layers,
            # what the loaders of this repository's benchmark ask of a file
            "num_hidden_layers": self.num_layers,
            "num_attention_heads": self.num_heads,
            "q_lora_rank": self.q_lora_rank,
            "kv_lora_rank": self.kv_lora_rank,
            "qk_nope_head_dim": self.qk_nope_head_dim,
            "qk_rope_head_dim": self.qk_rope_head_dim,
            "v_head_dim": self.v_head_dim,
            "mla_scale_q_lora": self.mla_scale_q_lora,
            "mla_scale_kv_lora": self.mla_scale_kv_lora,
            "max_position_embeddings": self.max_position_embeddings,
            "rope_theta": self.rope_theta,
            "rms_norm_eps": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act,
            "n_routed_experts": hi - lo,
            "moe_topk": self.num_experts_per_tok,
            "routed_scaling_factor": self.routed_scaling_factor,
            "zero_expert_num": self.zero_expert_num,
            "zero_expert_type": "identity",
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }
        if self.experts_held is not None:
            d["experts_held"] = {"first": lo, "of": self.num_experts}
        return d

    def _to_afmoe(self) -> dict:
        lo, hi = self.held_range
        d = {
            "architectures": [self.hf_architecture],
            "model_type": "afmoe",
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "moe_intermediate_size": self.moe_intermediate_size,
            "num_hidden_layers": self.num_layers,
            "num_dense_layers": self.leading_dense_layers,
            "layer_types": [
                "sliding_attention" if s else "full_attention"
                for s in self.layer_is_sliding
            ],
            "sliding_window": self.sliding_window,
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "head_dim": self.head_dim_,
            "max_position_embeddings": self.max_position_embeddings,
            "rope_theta": self.rope_theta,
            "rope_scaling": None,
            "rms_norm_eps": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act,
            "mup_enabled": self.scale_embeddings,
            "num_experts": hi - lo,
            "num_experts_per_tok": self.num_experts_per_tok,
            "num_shared_experts": 1,
            "score_func": "sigmoid",
            "route_norm": self.norm_topk_prob,
            "route_scale": self.routed_scaling_factor,
            "n_group": 1,
            "topk_group": 1,
            "num_expert_groups": 1,
            "num_limited_groups": 1,
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }
        if self.experts_held is not None:
            d["experts_held"] = {"first": lo, "of": self.num_experts}
        return d

    def _to_nemotron_h(self) -> dict:
        lo, hi = self.held_range
        d = {
            "architectures": [self.hf_architecture],
            "model_type": "nemotron_h",
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "num_hidden_layers": self.num_layers,
            "hybrid_override_pattern": "".join(self.layer_kinds),
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "head_dim": self.head_dim_,
            "max_position_embeddings": self.max_position_embeddings,
            "rope_theta": self.rope_theta,
            "layer_norm_epsilon": self.rms_norm_eps,
            "norm_eps": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "mamba_num_heads": self.mamba_num_heads,
            "mamba_head_dim": self.mamba_head_dim,
            "ssm_state_size": self.ssm_state_size,
            "n_groups": self.mamba_n_groups,
            "conv_kernel": self.conv_kernel,
            "chunk_size": self.mamba_chunk,
            "time_step_min": self.time_step_min,
            "time_step_max": self.time_step_max,
            "time_step_floor": self.time_step_floor,
            "mamba_hidden_act": "silu",
            "mlp_hidden_act": "relu2",
            "use_bias": False,
            "mlp_bias": False,
            "mamba_proj_bias": False,
            "attention_bias": False,
            "use_conv_bias": True,
            "n_routed_experts": hi - lo,
            "n_shared_experts": 1,
            "num_experts_per_tok": self.num_experts_per_tok,
            "moe_intermediate_size": self.moe_intermediate_size,
            "moe_latent_size": self.moe_latent_size,
            "moe_shared_expert_intermediate_size": (
                self.moe_shared_intermediate_size
            ),
            "norm_topk_prob": self.norm_topk_prob,
            "routed_scaling_factor": self.routed_scaling_factor,
            "n_group": 1,
            "topk_group": 1,
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }
        if self.experts_held is not None:
            d["experts_held"] = {"first": lo, "of": self.num_experts}
        return d

    def to_hf_dict(self) -> dict:
        """Emit an HF-compatible config dict (for saving checkpoints that
        inference servers / transformers can load back)."""
        arch = self.hf_architecture
        if self.jamba_layer_rule is not None:
            return self._to_jamba()
        if self.layer_kinds is not None:
            return self._to_nemotron_h()
        if self.ffn_kinds is not None:
            return self._to_afmoe()
        if self.attn_kind == "latent":
            return self._to_longcat_flash()
        if self.attn_kind == "windowed":
            return self._to_mimo_v2()
        if arch == "GPT2LMHeadModel":
            return {
                "architectures": [arch],
                "model_type": "gpt2",
                "vocab_size": self.vocab_size,
                "n_embd": self.hidden_size,
                "n_inner": self.intermediate_size,
                "n_layer": self.num_layers,
                "n_head": self.num_heads,
                "n_positions": self.max_position_embeddings,
                "n_ctx": self.max_position_embeddings,
                "layer_norm_epsilon": self.rms_norm_eps,
                "activation_function": (
                    "gelu_new" if self.hidden_act == "gelu_pytorch_tanh"
                    else self.hidden_act
                ),
                "tie_word_embeddings": True,
                "torch_dtype": "bfloat16",
                "bos_token_id": self.bos_token_id,
                "eos_token_id": self.eos_token_id,
            }
        model_type = {
            "LlamaForCausalLM": "llama",
            "Qwen2ForCausalLM": "qwen2",
            "Qwen3ForCausalLM": "qwen3",
            "MistralForCausalLM": "mistral",
            "Qwen3MoeForCausalLM": "qwen3_moe",
            "MixtralForCausalLM": "mixtral",
            "GemmaForCausalLM": "gemma",
            "Gemma2ForCausalLM": "gemma2",
        }.get(arch, "llama")
        if self.attn_kind == "power_retention":
            model_type = "brumby"
        d = {
            "architectures": [arch],
            "model_type": model_type,
            "vocab_size": self.vocab_size,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "num_hidden_layers": self.num_layers,
            "num_attention_heads": self.num_heads,
            "num_key_value_heads": self.num_kv_heads,
            "max_position_embeddings": self.max_position_embeddings,
            "rope_theta": self.rope_theta,
            "rms_norm_eps": self.rms_norm_eps,
            "tie_word_embeddings": self.tie_word_embeddings,
            "hidden_act": self.hidden_act,
            "torch_dtype": "bfloat16",
            "bos_token_id": self.bos_token_id,
            "eos_token_id": self.eos_token_id,
        }
        if self.head_dim is not None:
            d["head_dim"] = self.head_dim
        if model_type in (
            "qwen2", "qwen3", "mistral", "llama", "qwen3_moe", "brumby"
        ):
            d["attention_bias"] = self.qkv_bias
        if model_type == "brumby":
            d["retention_degree"] = self.retention_degree
            d["retention_chunk"] = self.retention_chunk
        if model_type.startswith("gemma"):
            # transformers' gemma configs read hidden_activation
            d["hidden_activation"] = self.hidden_act
            d["attention_bias"] = self.qkv_bias
        if model_type == "gemma2":
            if self.query_pre_attn_scalar is not None:
                d["query_pre_attn_scalar"] = self.query_pre_attn_scalar
            if self.attn_logit_softcap is not None:
                d["attn_logit_softcapping"] = self.attn_logit_softcap
            if self.final_logit_softcap is not None:
                d["final_logit_softcapping"] = self.final_logit_softcap
            if self.sliding_window is not None:
                d["sliding_window"] = self.sliding_window
            if self.layer_is_sliding is not None:
                d["layer_types"] = [
                    "sliding_attention" if s else "full_attention"
                    for s in self.layer_is_sliding
                ]
        if self.num_experts > 0:
            key = "num_local_experts" if model_type == "mixtral" else "num_experts"
            d[key] = self.num_experts
            d["num_experts_per_tok"] = self.num_experts_per_tok
            d["norm_topk_prob"] = self.norm_topk_prob
            if self.moe_intermediate_size is not None:
                d["moe_intermediate_size"] = self.moe_intermediate_size
        if self.sliding_window is not None and model_type != "gemma2":
            d["sliding_window"] = self.sliding_window
            d["use_sliding_window"] = True
        if self.vision is not None:
            v = self.vision
            d["vision_config"] = {
                "patch_size": v.patch_size,
                "temporal_patch_size": v.temporal_patch_size,
                "in_channels": v.in_channels,
                "hidden_size": v.hidden_size,
                "intermediate_size": v.intermediate_size,
                "depth": v.num_layers,
                "num_heads": v.num_heads,
                "spatial_merge_size": v.spatial_merge_size,
                "out_hidden_size": v.out_hidden_size,
            }
            if v.window_size:
                d["vision_config"]["window_size"] = v.window_size
                d["vision_config"]["fullatt_block_indexes"] = list(
                    v.fullatt_block_indexes
                )
            if self.image_token_id is not None:
                d["image_token_id"] = self.image_token_id
            if self.mrope_section is not None:
                d["rope_scaling"] = {
                    "type": "mrope",
                    "mrope_section": list(self.mrope_section),
                }
        return d


# Handy presets for tests / benchmarks ------------------------------------

def tiny_config(**kw) -> TransformerConfig:
    base = dict(
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        max_position_embeddings=512,
        remat=False,
        dtype="float32",
    )
    base.update(kw)
    return TransformerConfig(**base)


def qwen25_1p5b() -> TransformerConfig:
    """Qwen2.5-1.5B shapes — the reference's small benchmark model class
    (BASELINE.md: 1.5B R1-Distill)."""
    return TransformerConfig(
        vocab_size=151936,
        hidden_size=1536,
        intermediate_size=8960,
        num_layers=28,
        num_heads=12,
        num_kv_heads=2,
        max_position_embeddings=32768,
        rope_theta=1000000.0,
        tie_word_embeddings=True,
        qkv_bias=True,
        hf_architecture="Qwen2ForCausalLM",
    )


def qwen2_0p6b_ctx() -> TransformerConfig:
    """Qwen2-class ~0.6B with head_dim 128 (splash-eligible): the largest
    shape whose 32k-context train step fits a 16G v5e chip — the on-chip
    long-context evidence model (VERDICT r2 #8).  Qwen2.5-0.5B itself has
    head_dim 64, which the splash kernel cannot tile."""
    return TransformerConfig(
        vocab_size=151936,
        hidden_size=1024,
        intermediate_size=5504,
        num_layers=24,
        num_heads=8,
        num_kv_heads=2,
        max_position_embeddings=32768,
        rope_theta=1000000.0,
        tie_word_embeddings=True,
        qkv_bias=True,
        hf_architecture="Qwen2ForCausalLM",
    )


def qwen25_7b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        max_position_embeddings=32768,
        rope_theta=1000000.0,
        qkv_bias=True,
        hf_architecture="Qwen2ForCausalLM",
    )
