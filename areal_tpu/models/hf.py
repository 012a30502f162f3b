"""HF checkpoint <-> param-pytree conversion.

Capability counterpart of the reference's HF interop: lite loads via
transformers AutoModelForCausalLM (areal/engine/base_hf_engine.py:46) and
saves full state dicts (areal/engine/fsdp_engine.py:228-254); legacy keeps
per-arch name maps (realhf/api/from_hf/{llama,qwen2,qwen3,mistral}.py).

TPU-first: weights stream shard-by-shard from safetensors into numpy buffers
stacked over the layer axis (our scan layout), never materialising a torch
model.  Saving emits HF-format safetensors + config.json so any HF-ecosystem
inference server (and our generation engine) can reload them — this is the
"disk" weight-update path (reference: fsdp_engine.py:403-425).
"""

import json
import os
import re
import shutil
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.utils import logging

logger = logging.getLogger("models.hf")

_LAYER_RE = re.compile(r"model\.layers\.(\d+)\.(.+)")

# our (path-in-layer, transpose?) for each HF per-layer suffix
_LAYER_MAP = {
    "self_attn.q_proj.weight": (("attn", "wq"), True),
    "self_attn.k_proj.weight": (("attn", "wk"), True),
    "self_attn.v_proj.weight": (("attn", "wv"), True),
    "self_attn.o_proj.weight": (("attn", "wo"), True),
    "self_attn.q_proj.bias": (("attn", "bq"), False),
    "self_attn.k_proj.bias": (("attn", "bk"), False),
    "self_attn.v_proj.bias": (("attn", "bv"), False),
    "self_attn.q_norm.weight": (("attn", "q_norm"), False),
    "self_attn.k_norm.weight": (("attn", "k_norm"), False),
    # power retention's gate (brumby): hidden -> one scalar a kv head.  The
    # name is an assumption, the published checkpoint was not at hand
    "self_attn.g_proj.weight": (("attn", "wg"), True),
    "mlp.gate_proj.weight": (("mlp", "w_gate"), True),
    "mlp.up_proj.weight": (("mlp", "w_up"), True),
    "mlp.down_proj.weight": (("mlp", "w_down"), True),
    "input_layernorm.weight": (("input_norm",), False),
    "post_attention_layernorm.weight": (("post_attn_norm",), False),
}


def layer_name_map(cfg: TransformerConfig) -> Dict[str, Tuple[Tuple[str, ...], bool]]:
    """Per-layer HF-name map for a config.  The gemma2 sandwich layout
    renames the norms: its post_attention_layernorm normalises the attention
    OUTPUT (our sandwich_attn_norm) while pre_feedforward_layernorm is the
    pre-FFN norm every other family calls post_attention_layernorm.

    gpt2 is its own dialect: Conv1D weights already store [in, out] (no
    transpose), LayerNorms carry biases, the MLP is non-gated, and the
    fused attn.c_attn qkv is handled separately in state_to_params."""
    if cfg.hf_architecture == "GPT2LMHeadModel":
        return {
            "ln_1.weight": (("input_norm",), False),
            "ln_1.bias": (("input_norm_b",), False),
            "attn.c_proj.weight": (("attn", "wo"), False),
            "attn.c_proj.bias": (("attn", "bo"), False),
            "ln_2.weight": (("post_attn_norm",), False),
            "ln_2.bias": (("post_attn_norm_b",), False),
            "mlp.c_fc.weight": (("mlp", "w_up"), False),
            "mlp.c_fc.bias": (("mlp", "b_up"), False),
            "mlp.c_proj.weight": (("mlp", "w_down"), False),
            "mlp.c_proj.bias": (("mlp", "b_down"), False),
        }
    m = dict(_LAYER_MAP)
    if cfg.sandwich_norms:
        m["post_attention_layernorm.weight"] = (("sandwich_attn_norm",), False)
        m["pre_feedforward_layernorm.weight"] = (("post_attn_norm",), False)
        m["post_feedforward_layernorm.weight"] = (("sandwich_ffn_norm",), False)
    return m

# vision tower (models/vision.py tree) <-> "visual."-prefixed names in the
# REAL Qwen2.5-VL checkpoint convention (RMSNorm norm1/norm2, biased
# qkv/proj + gated mlp, merger.ln_q + merger.mlp.{0,2}); weights store
# [in, out], HF linears [out, in].  patch_embed.proj is a Conv3d
# [D, C, tps, ps, ps] reshaped to the tower's [patch_dim, D] matmul.
_VISION_RE = re.compile(r"visual\.blocks\.(\d+)\.(.+)")
_VISION_LAYER_MAP = {
    "norm1.weight": (("input_norm",), False),
    "attn.qkv.weight": (("wqkv",), True),
    "attn.qkv.bias": (("b_qkv",), False),
    "attn.proj.weight": (("wo",), True),
    "attn.proj.bias": (("b_o",), False),
    "norm2.weight": (("post_attn_norm",), False),
    "mlp.up_proj.weight": (("w_up",), True),
    "mlp.up_proj.bias": (("b_up",), False),
    "mlp.gate_proj.weight": (("w_gate",), True),
    "mlp.gate_proj.bias": (("b_gate",), False),
    "mlp.down_proj.weight": (("w_down",), True),
    "mlp.down_proj.bias": (("b_down",), False),
}
# MoE per-layer names: qwen-MoE (mlp.experts.N.*_proj + mlp.gate router)
# and mixtral (block_sparse_moe.experts.N.w{1,2,3} + block_sparse_moe.gate)
_MOE_EXPERT_RE = re.compile(
    r"(?:mlp|block_sparse_moe)\.experts\.(\d+)\.(gate_proj|up_proj|down_proj|w1|w2|w3)\.weight"
)
_MOE_ROUTER_NAMES = ("mlp.gate.weight", "block_sparse_moe.gate.weight")
_MOE_LEAF = {
    "gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down",
    "w1": "w_gate", "w3": "w_up", "w2": "w_down",
}

# read-only aliases: this repo's pre-r3 checkpoints used short mlp names
_VISION_LAYER_ALIASES = {
    "mlp.up.weight": (("w_up",), True),
    "mlp.gate.weight": (("w_gate",), True),
    "mlp.down.weight": (("w_down",), True),
}
_VISION_TOP_MAP = {  # name -> (key, transpose)
    "visual.merger.ln_q.weight": ("merger_norm", False),
    "visual.merger.mlp.0.weight": ("merger_fc1", True),
    "visual.merger.mlp.0.bias": ("merger_fc1_b", False),
    "visual.merger.mlp.2.weight": ("merger_fc2", True),
    "visual.merger.mlp.2.bias": ("merger_fc2_b", False),
}
_VISION_TOP_ALIASES = {
    "visual.patch_embed.weight": ("patch_embed", False),
    "visual.merger.ln.weight": ("merger_norm", False),
    "visual.merger.fc1.weight": ("merger_fc1", True),
    "visual.merger.fc2.weight": ("merger_fc2", True),
}


# nemotron_h (a hybrid stack, parameters stacked per block kind): names
# under `backbone.layers.<i>.`, by the kind of block i -> (leaf path in
# `layers[kind]`, transpose?).  Linears are [out, in] there, [in, out] here.
# The published checkpoint was not at hand: the names follow the published
# `NemotronH` modeling code's module names (configs list them as assumed).
_NEMOTRON_RE = re.compile(r"backbone\.layers\.(\d+)\.(.+)")
_NEMOTRON_EXPERT_RE = re.compile(
    r"mixer\.experts\.(\d+)\.(up_proj|down_proj)\.weight"
)
_NEMOTRON_MAP = {
    "M": {
        "norm.weight": (("input_norm",), False),
        "mixer.in_proj.weight": (("w_in",), True),
        "mixer.conv1d.bias": (("conv_b",), False),
        "mixer.A_log": (("A_log",), False),
        "mixer.D": (("D",), False),
        "mixer.dt_bias": (("dt_bias",), False),
        "mixer.norm.weight": (("gate_norm",), False),
        "mixer.out_proj.weight": (("w_out",), True),
    },
    "*": {
        "norm.weight": (("input_norm",), False),
        "mixer.q_proj.weight": (("attn", "wq"), True),
        "mixer.k_proj.weight": (("attn", "wk"), True),
        "mixer.v_proj.weight": (("attn", "wv"), True),
        "mixer.o_proj.weight": (("attn", "wo"), True),
    },
    "E": {
        "norm.weight": (("input_norm",), False),
        "mixer.gate.weight": (("router",), True),
        "mixer.gate.e_score_correction_bias": (("router_bias",), False),
        "mixer.fc1_latent_proj.weight": (("w_l1",), True),
        "mixer.fc2_latent_proj.weight": (("w_l2",), True),
        "mixer.shared_experts.up_proj.weight": (("ws1",), True),
        "mixer.shared_experts.down_proj.weight": (("ws2",), True),
    },
}
_NEMOTRON_EXPERT_LEAF = {"up_proj": ("w1",), "down_proj": ("w2",)}
# float32 whatever the load dtype: the recurrence's own parameters
_NEMOTRON_F32 = {("A_log",), ("D",), ("dt_bias",), ("router_bias",)}


def _kind_index(kinds):
    """Block i -> (its kind, its index among the blocks of that kind)."""
    seen: Dict[str, int] = {}
    out = []
    for kind in kinds:
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = out[-1][1] + 1
    return out, seen


# afmoe (gated experts behind leading dense layers, parameters stacked per
# FFN kind): names under `model.layers.<i>.`, by the kind of block i.  The
# published checkpoint was not at hand: the names follow the published
# `afmoe` modeling code's module names (configs list them as assumed).
_AFMOE_EXPERT_RE = re.compile(
    r"mlp\.experts\.(\d+)\.(gate_proj|up_proj|down_proj)\.weight"
)
_AFMOE_BLOCK = {
    "self_attn.q_proj.weight": (("attn", "wq"), True),
    "self_attn.k_proj.weight": (("attn", "wk"), True),
    "self_attn.v_proj.weight": (("attn", "wv"), True),
    "self_attn.o_proj.weight": (("attn", "wo"), True),
    "self_attn.gate_proj.weight": (("attn", "wg"), True),
    "self_attn.q_norm.weight": (("attn", "q_norm"), False),
    "self_attn.k_norm.weight": (("attn", "k_norm"), False),
    "input_layernorm.weight": (("input_norm",), False),
    "post_attention_layernorm.weight": (("sandwich_attn_norm",), False),
    "pre_mlp_layernorm.weight": (("post_attn_norm",), False),
    "post_mlp_layernorm.weight": (("sandwich_ffn_norm",), False),
}
_AFMOE_MAP = {
    "dense": {
        **_AFMOE_BLOCK,
        "mlp.gate_proj.weight": (("mlp", "w_gate"), True),
        "mlp.up_proj.weight": (("mlp", "w_up"), True),
        "mlp.down_proj.weight": (("mlp", "w_down"), True),
    },
    "moe": {
        **_AFMOE_BLOCK,
        "mlp.router.gate.weight": (("moe", "router"), True),
        "mlp.expert_bias": (("moe", "router_bias"), False),
        "mlp.shared_experts.gate_proj.weight": (("moe", "ws_gate"), True),
        "mlp.shared_experts.up_proj.weight": (("moe", "ws_up"), True),
        "mlp.shared_experts.down_proj.weight": (("moe", "ws_down"), True),
    },
}

# what `_kinds_to_params` / `_kinds_state` need to know of a family whose
# parameters are stacked per block kind
_DIALECTS = {
    "nemotron_h": dict(
        layer_re=_NEMOTRON_RE, layer_fmt="backbone.layers.{}.",
        maps=_NEMOTRON_MAP, expert_kind="E", expert_re=_NEMOTRON_EXPERT_RE,
        expert_fmt="mixer.experts.{}.{}.weight",
        expert_leaf=_NEMOTRON_EXPERT_LEAF, f32=_NEMOTRON_F32,
        embedding="backbone.embeddings.weight",
        final_norm="backbone.norm_f.weight",
    ),
    "afmoe": dict(
        layer_re=_LAYER_RE, layer_fmt="model.layers.{}.",
        maps=_AFMOE_MAP, expert_kind="moe", expert_re=_AFMOE_EXPERT_RE,
        expert_fmt="mlp.experts.{}.{}.weight",
        expert_leaf={"gate_proj": ("moe", "w_gate"),
                     "up_proj": ("moe", "w_up"),
                     "down_proj": ("moe", "w_down")},
        f32={("moe", "router_bias")},
        embedding="model.embed_tokens.weight", final_norm="model.norm.weight",
    ),
}


# longcat_flash (latent attention in double layers, `models/latent.py`):
# names under `model.layers.<l>.`, `{}` the sublayer 0 / 1; leaves carry
# [L, 2, ...], the expert layer's [L, ...].  The published checkpoint was
# not at hand: the names follow the publisher's module names as the issue
# that asked for the family lists them (unchecked), and the rotary columns
# of `q_b_proj` / `kv_a_proj_with_mqa` are taken as they come (the
# publisher pairs them interleaved, this runtime by halves: a checkpoint
# needs them permuted, which waits for one to check against).
_LONGCAT_SUB = {
    "self_attn.{}.q_a_proj.weight": (("attn", "wq_a"), True),
    "self_attn.{}.q_a_layernorm.weight": (("attn", "q_norm"), False),
    "self_attn.{}.q_b_proj.weight": (("attn", "wq_b"), False),  # [out, in]
    "self_attn.{}.kv_a_proj_with_mqa.weight": (("attn", "wkv_a"), True),
    "self_attn.{}.kv_a_layernorm.weight": (("attn", "kv_norm"), False),
    "self_attn.{}.kv_b_proj.weight": (("attn", "wkv_b"), False),  # [out, in]
    "self_attn.{}.o_proj.weight": (("attn", "wo"), True),
    "mlps.{}.gate_proj.weight": (("mlp", "w_gate"), True),
    "mlps.{}.up_proj.weight": (("mlp", "w_up"), True),
    "mlps.{}.down_proj.weight": (("mlp", "w_down"), True),
    "input_layernorm.{}.weight": (("input_norm",), False),
    "post_attention_layernorm.{}.weight": (("post_attn_norm",), False),
}
_LONGCAT_LAYER = {
    "mlp.router.classifier.weight": (("moe", "router"), True),
    "mlp.router.e_score_correction_bias": (("moe", "router_bias"), False),
}
_LONGCAT_EXPERT = {"gate_proj": "w_gate", "up_proj": "w_up",
                   "down_proj": "w_down"}
_LONGCAT_EXPERT_FMT = "mlp.experts.{}.{}.weight"


def longcat_name_map(cfg: TransformerConfig):
    """{checkpoint name: (path under `layers`, index into the stacked leaf,
    transpose)} for every weight of the stack this share holds."""
    lo, hi = cfg.held_range
    out = {}
    for l in range(cfg.num_layers):
        prefix = f"model.layers.{l}."
        for i in (0, 1):
            for fmt, (path, t) in _LONGCAT_SUB.items():
                out[prefix + fmt.format(i)] = (path, (l, i), t)
        for suffix, (path, t) in _LONGCAT_LAYER.items():
            out[prefix + suffix] = (path, (l,), t)
        for e in range(lo, hi):
            for hf_leaf, leaf in _LONGCAT_EXPERT.items():
                out[prefix + _LONGCAT_EXPERT_FMT.format(e, hf_leaf)] = (
                    ("moe", leaf), (l, e - lo), True)
    return out


def _longcat_to_params(items, cfg: TransformerConfig, np_dtype):
    names = longcat_name_map(cfg)
    lead = {1: (cfg.num_layers,), 2: (cfg.num_layers, 2)}
    held = cfg.held_range[1] - cfg.held_range[0]
    params: Dict[str, Any] = {"layers": {}}
    left = set(names)
    for name, arr in items:
        if name in names:
            path, index, transpose = names[name]
            arr = arr.T if transpose else arr
            try:
                buf = _get_nested(params["layers"], path)
            except KeyError:
                expert = path[0] == "moe" and len(index) == 2
                shape = (cfg.num_layers, held) if expert else lead[len(index)]
                dt = np.float32 if path[-1] == "router_bias" else np_dtype
                buf = np.zeros(shape + arr.shape, dt)
                _set_nested(params["layers"], path, buf)
            buf[index] = arr
            left.discard(name)
        elif name == "model.embed_tokens.weight":
            params["embedding"] = arr[: cfg.vocab_size].astype(np_dtype)
        elif name == "model.norm.weight":
            params["final_norm"] = arr.astype(np_dtype)
        elif name == "lm_head.weight":
            params["lm_head"] = arr[: cfg.vocab_size].T.astype(np_dtype)
        else:
            # a deeper layer or an expert another share holds, among others
            logger.warning("skipping unmapped weight %s", name)
    missing = sorted(left) + [
        k for k in ("embedding", "final_norm", "lm_head") if k not in params]
    if missing:
        raise ValueError(
            f"incomplete weights: {len(missing)} missing, first {missing[0]}")
    return params


def _longcat_state(params, cfg: TransformerConfig):
    yield "model.embed_tokens.weight", np.asarray(params["embedding"])
    for name, (path, index, transpose) in longcat_name_map(cfg).items():
        arr = np.asarray(_get_nested(params["layers"], path)[index])
        yield name, arr.T if transpose else arr
    yield "model.norm.weight", np.asarray(params["final_norm"])
    yield "lm_head.weight", np.asarray(params["lm_head"]).T


# mimo_v2 (`models/windowed.py`): read as a llama checkpoint its leaves would
# land in one stack with one head layout
_MIMO_NO_CHECKPOINT = (
    "mimo_v2 (full and sliding layers stacked by kind, a fused qkv "
    "projection): the publisher's parameter names are not mapped; the "
    "family runs on weights drawn or handed over in memory"
)


# jamba (two blocks of the hybrid stack a published layer): read through
# nemotron_h's names nothing of it would be found
_JAMBA_NO_CHECKPOINT = (
    "jamba (a Mamba-1 or attention mixer and a dense FFN a layer, stacked "
    "by block kind): the publisher's parameter names are not mapped; the "
    "family runs on weights drawn or handed over in memory"
)


def _dialect(cfg: TransformerConfig):
    """-> (the dialect, the kind of every block) of a family whose
    parameters are stacked per kind; (None, None) for every other."""
    if cfg.jamba_layer_rule is not None:
        raise NotImplementedError(_JAMBA_NO_CHECKPOINT)
    if cfg.layer_kinds is not None:
        return _DIALECTS["nemotron_h"], cfg.layer_kinds
    if cfg.ffn_kinds is not None:
        return _DIALECTS["afmoe"], cfg.ffn_kinds
    return None, None


def _kinds_to_params(items, cfg: TransformerConfig, np_dtype):
    dia, kinds = _dialect(cfg)
    where, counts = _kind_index(kinds)
    lo, hi = cfg.held_range
    expert_paths = set(dia["expert_leaf"].values())
    params: Dict[str, Any] = {"layers": {k: {} for k in counts}}
    filled: Dict[Tuple, int] = {}

    def put(kind, j, path, arr, index=None, n_inner=None):
        tree = params["layers"][kind]
        dt = np.float32 if path in dia["f32"] else np_dtype
        try:
            buf = _get_nested(tree, path)
        except KeyError:
            shape = arr.shape if n_inner is None else (n_inner, *arr.shape)
            buf = np.zeros((counts[kind], *shape), dt)
            _set_nested(tree, path, buf)
        if index is None:
            buf[j] = arr
        else:
            buf[j, index] = arr
        filled[(kind, path)] = filled.get((kind, path), 0) + 1

    for name, arr in items:
        m = dia["layer_re"].match(name)
        if m:
            i, suffix = int(m.group(1)), m.group(2)
            if i >= len(where):
                continue  # a deeper block than this (cut) stack holds
            kind, j = where[i]
            entry = dia["maps"][kind].get(suffix)
            em = (dia["expert_re"].fullmatch(suffix)
                  if kind == dia["expert_kind"] else None)
            if entry is not None:
                path, transpose = entry
                put(kind, j, path, arr.T if transpose else arr)
            elif kind == "M" and suffix == "mixer.conv1d.weight":
                # Conv1d [channels, 1, K] -> taps [K, channels]
                put(kind, j, ("conv_w",), arr[:, 0, :].T)
            elif em:
                e = int(em.group(1))
                if lo <= e < hi:  # the experts this share holds
                    put(kind, j, dia["expert_leaf"][em.group(2)],
                        arr.T, index=e - lo, n_inner=hi - lo)
            else:
                logger.warning("skipping unmapped weight %s", name)
        elif name == dia["embedding"]:
            params["embedding"] = arr[: cfg.vocab_size].astype(np_dtype)
        elif name == dia["final_norm"]:
            params["final_norm"] = arr.astype(np_dtype)
        elif name == "lm_head.weight":
            params["lm_head"] = arr[: cfg.vocab_size].T.astype(np_dtype)
        else:
            logger.warning("skipping unmapped weight %s", name)
    for kind, n in counts.items():
        leaves = [p for p, _ in dia["maps"][kind].values()]
        if kind == "M":
            leaves.append(("conv_w",))
        if kind == dia["expert_kind"]:
            leaves += sorted(expert_paths)
        for path in leaves:
            want = n * (hi - lo) if path in expert_paths else n
            got = filled.get((kind, path), 0)
            if got != want:
                raise ValueError(
                    f"incomplete weights: layers.{kind}.{'.'.join(path)} "
                    f"filled for {got}/{want} slots"
                )
    for req in ("embedding", "final_norm", "lm_head"):
        if req not in params:
            raise ValueError(f"checkpoint missing {req}")
    return params


def _kinds_state(params, cfg: TransformerConfig):
    dia, kinds = _dialect(cfg)
    where, _ = _kind_index(kinds)
    lo, _ = cfg.held_range
    yield dia["embedding"], np.asarray(params["embedding"])
    for i, (kind, j) in enumerate(where):
        prefix = dia["layer_fmt"].format(i)
        tree = params["layers"][kind]
        for suffix, (path, transpose) in dia["maps"][kind].items():
            arr = np.asarray(_get_nested(tree, path)[j])
            yield prefix + suffix, arr.T if transpose else arr
        if kind == "M":
            yield (prefix + "mixer.conv1d.weight",
                   np.asarray(tree["conv_w"][j]).T[:, None, :])
        if kind == dia["expert_kind"]:
            for hf_leaf, path in dia["expert_leaf"].items():
                buf = np.asarray(_get_nested(tree, path)[j])
                for e in range(buf.shape[0]):
                    yield (prefix + dia["expert_fmt"].format(lo + e, hf_leaf),
                           buf[e].T)
    yield dia["final_norm"], np.asarray(params["final_norm"])
    yield "lm_head.weight", np.asarray(params["lm_head"]).T


def _set_nested(tree: Dict, path: Tuple[str, ...], value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _get_nested(tree: Dict, path: Tuple[str, ...]):
    for p in path:
        tree = tree[p]
    return tree


def iter_safetensors(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (name, numpy array) over all safetensors shards in a dir."""
    from safetensors import safe_open

    if os.path.isfile(path):
        files = [path]
    else:
        files = sorted(
            os.path.join(path, f)
            for f in os.listdir(path)
            if f.endswith(".safetensors")
        )
    if not files:
        raise FileNotFoundError(f"no .safetensors under {path}")
    for f in files:
        with safe_open(f, framework="np") as sf:
            for name in sf.keys():
                yield name, sf.get_tensor(name)


def state_to_params(
    items: Iterator[Tuple[str, np.ndarray]],
    cfg: TransformerConfig,
    dtype: str = "float32",
) -> Dict[str, Any]:
    """HF-named (name, array) pairs -> scan-stacked param pytree, with
    completeness validation.  Shared by checkpoint loading and the
    streamed weight-update path (gen/server.py /update_weights_chunk)."""
    L = cfg.num_layers
    np_dtype = np.dtype(dtype)
    if _dialect(cfg)[0] is not None:
        return _kinds_to_params(items, cfg, np_dtype)
    if cfg.attn_kind == "latent":
        return _longcat_to_params(items, cfg, np_dtype)
    if cfg.attn_kind == "windowed":
        raise NotImplementedError(_MIMO_NO_CHECKPOINT)
    lmap = layer_name_map(cfg)
    params: Dict[str, Any] = {"layers": {}}
    fill_count: Dict[Tuple[str, ...], int] = {}
    # expected writes per path: L for dense leaves, L*E for expert stacks
    fill_expected: Dict[Tuple[str, ...], int] = {}

    def layer_buf(path_in_layer: Tuple[str, ...], shape):
        try:
            return _get_nested(params["layers"], path_in_layer)
        except KeyError:
            buf = np.zeros((L, *shape), dtype=np_dtype)
            _set_nested(params["layers"], path_in_layer, buf)
            return buf

    Lv = cfg.vision.num_layers if cfg.vision is not None else 0
    vision: Dict[str, Any] = {"layers": {}}
    vision_fill: Dict[Tuple[str, ...], int] = {}

    def vision_layer_buf(path_in_layer: Tuple[str, ...], shape):
        try:
            return _get_nested(vision["layers"], path_in_layer)
        except KeyError:
            buf = np.zeros((Lv, *shape), dtype=np_dtype)
            _set_nested(vision["layers"], path_in_layer, buf)
            return buf

    gpt2 = cfg.hf_architecture == "GPT2LMHeadModel"
    D = cfg.hidden_size
    seen_head = False
    for name, arr in items:
        arr = np.asarray(arr)  # bf16 arrives as ml_dtypes.bfloat16; the cast-on-assignment into the stacked buffers handles it
        if gpt2:
            # gpt2 dialect: transformer.{wte,wpe,h.N.*,ln_f}; Conv1D
            # weights are already [in, out]
            if name.startswith("transformer."):
                name = name[len("transformer."):]
            if name.endswith((".attn.bias", ".attn.masked_bias")):
                continue  # causal-mask buffers, not weights (c_attn.bias
                # is a real weight and does NOT match the leading dot)
            if name == "wte.weight":
                name = "model.embed_tokens.weight"
            elif name == "wpe.weight":
                params["pos_embedding"] = arr.astype(np_dtype)
                continue
            elif name == "ln_f.weight":
                name = "model.norm.weight"
            elif name == "ln_f.bias":
                params["final_norm_b"] = arr.astype(np_dtype)
                continue
            elif name.startswith("h."):
                name = "model.layers." + name[len("h."):]
            gm = _LAYER_RE.match(name)
            if gm and gm.group(2) in ("attn.c_attn.weight", "attn.c_attn.bias"):
                # fused qkv: split [D, 3D] columns (or [3D] bias) into q/k/v
                idx = int(gm.group(1))
                leaves = (
                    ("attn", "wq"), ("attn", "wk"), ("attn", "wv")
                ) if gm.group(2).endswith("weight") else (
                    ("attn", "bq"), ("attn", "bk"), ("attn", "bv")
                )
                for j, path_in_layer in enumerate(leaves):
                    part = arr[..., j * D:(j + 1) * D]
                    buf = layer_buf(path_in_layer, part.shape)
                    buf[idx] = part
                    fill_count[path_in_layer] = (
                        fill_count.get(path_in_layer, 0) + 1
                    )
                continue
        # newer transformers nest the decoder/tower under model.*
        if name.startswith("model.language_model."):
            name = "model." + name[len("model.language_model."):]
        elif name.startswith("model.visual."):
            name = name[len("model."):]
        if name.startswith("visual."):
            if cfg.vision is None:
                logger.warning("skipping vision weight %s (text-only config)", name)
                continue
            vm = _VISION_RE.match(name)
            if vm:
                idx, suffix = int(vm.group(1)), vm.group(2)
                entry = _VISION_LAYER_MAP.get(suffix) or _VISION_LAYER_ALIASES.get(suffix)
                if entry is None:
                    logger.warning("skipping unmapped weight %s", name)
                    continue
                path_in_layer, transpose = entry
                if transpose:
                    arr = arr.T
                buf = vision_layer_buf(path_in_layer, arr.shape)
                buf[idx] = arr  # assignment casts; no intermediate copy
                vision_fill[path_in_layer] = vision_fill.get(path_in_layer, 0) + 1
            elif name == "visual.patch_embed.proj.weight":
                # Conv3d [D, C, tps, ps, ps] -> matmul [patch_dim, D]
                vision["patch_embed"] = (
                    arr.reshape(arr.shape[0], -1).T.astype(np_dtype)
                )
            elif name in _VISION_TOP_MAP or name in _VISION_TOP_ALIASES:
                key, transpose = (
                    _VISION_TOP_MAP.get(name) or _VISION_TOP_ALIASES[name]
                )
                vision[key] = (arr.T if transpose else arr).astype(np_dtype)
            else:
                logger.warning("skipping unmapped weight %s", name)
            continue
        m = _LAYER_RE.match(name)
        if m:
            idx, suffix = int(m.group(1)), m.group(2)
            if suffix in lmap:
                path_in_layer, transpose = lmap[suffix]
                if transpose:
                    arr = arr.T
                buf = layer_buf(path_in_layer, arr.shape)
                buf[idx] = arr  # assignment casts; no intermediate copy
                fill_count[path_in_layer] = fill_count.get(path_in_layer, 0) + 1
                continue
            if cfg.num_experts > 0:
                em = _MOE_EXPERT_RE.fullmatch(suffix)
                if em:
                    e, leaf = int(em.group(1)), _MOE_LEAF[em.group(2)]
                    path_in_layer = ("moe", leaf)
                    buf = layer_buf(
                        path_in_layer, (cfg.num_experts, *arr.T.shape)
                    )
                    buf[idx, e] = arr.T
                    fill_count[path_in_layer] = (
                        fill_count.get(path_in_layer, 0) + 1
                    )
                    fill_expected[path_in_layer] = L * cfg.num_experts
                    continue
                if suffix in _MOE_ROUTER_NAMES:
                    # HF router Linear [E, D] -> ours [D, E]
                    path_in_layer = ("moe", "router")
                    buf = layer_buf(path_in_layer, arr.T.shape)
                    buf[idx] = arr.T
                    fill_count[path_in_layer] = (
                        fill_count.get(path_in_layer, 0) + 1
                    )
                    continue
            logger.warning("skipping unmapped weight %s", name)
        elif name == "model.embed_tokens.weight":
            params["embedding"] = arr.astype(np_dtype)
        elif name == "model.norm.weight":
            params["final_norm"] = arr.astype(np_dtype)
        elif name == "lm_head.weight":
            params["lm_head"] = arr.T.astype(np_dtype)
            seen_head = True
        else:
            logger.warning("skipping unmapped weight %s", name)
    for path_in_layer, n in fill_count.items():
        want = fill_expected.get(path_in_layer, L)
        if n != want:
            raise ValueError(
                f"incomplete weights: {'.'.join(path_in_layer)} filled for "
                f"{n}/{want} slots"
            )
    required = ["embedding", "final_norm"]
    if cfg.pos_emb == "learned":
        required.append("pos_embedding")
    if cfg.norm_type == "layernorm":
        required.append("final_norm_b")
    for req in required:
        if req not in params:
            raise ValueError(f"checkpoint missing {req}")
    if cfg.attn_kind == "power_retention" and "wg" not in params[
        "layers"
    ].get("attn", {}):
        # without it every gate would read 1/2 and nothing would say so
        raise ValueError(
            "power-retention config but the checkpoint has no "
            "self_attn.g_proj.weight (the gate)"
        )
    if cfg.tie_word_embeddings and seen_head:
        del params["lm_head"]
    if not cfg.tie_word_embeddings and not seen_head:
        raise ValueError("untied config but checkpoint has no lm_head.weight")
    if vision_fill or "patch_embed" in vision:
        problems = [
            f"{'.'.join(p)} filled {n}/{Lv} layers"
            for p, n in vision_fill.items()
            if n != Lv
        ] + [
            f"missing visual {req}"
            for req in ("patch_embed", "merger_norm", "merger_fc1", "merger_fc2")
            if req not in vision
        ]
        if problems:
            # unmappable tower (e.g. Qwen2-VL's LayerNorm/fc1-fc2 blocks vs
            # this tree's RMSNorm/gated layout): degrade to a text-only
            # load — the text weights are still valuable — instead of
            # failing the whole checkpoint.  (Unmapped EXTRA visual leaves
            # alone are not fatal: the tower loads if its own tree filled.)
            logger.warning(
                "visual.* tree unmappable (%s); loading TEXT-ONLY — the "
                "vision tower will be randomly initialised",
                "; ".join(problems),
            )
        else:
            params["vision"] = vision
    return params


def load_hf_params(
    path: str,
    cfg: Optional[TransformerConfig] = None,
    dtype: str = "float32",
) -> Tuple[Dict[str, Any], TransformerConfig]:
    """Load an HF checkpoint dir into the scan-stacked param pytree."""
    if cfg is None:
        cfg = TransformerConfig.from_hf(path)
    return state_to_params(iter_safetensors(path), cfg, dtype), cfg


def _gpt2_state(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Iterator[Tuple[str, np.ndarray]]:
    """gpt2-dialect emission: transformer.* names, re-fused c_attn qkv."""
    pre = "transformer."
    yield pre + "wte.weight", np.asarray(params["embedding"])
    yield pre + "wpe.weight", np.asarray(params["pos_embedding"])
    layers = params["layers"]
    lmap = layer_name_map(cfg)
    for i in range(cfg.num_layers):
        p = f"{pre}h.{i}."
        attn = layers["attn"]
        yield p + "attn.c_attn.weight", np.concatenate(
            [np.asarray(attn[leaf][i]) for leaf in ("wq", "wk", "wv")], axis=1
        )
        yield p + "attn.c_attn.bias", np.concatenate(
            [np.asarray(attn[leaf][i]) for leaf in ("bq", "bk", "bv")]
        )
        for suffix, (path_in_layer, _t) in lmap.items():
            yield p + suffix, np.asarray(_get_nested(layers, path_in_layer)[i])
    yield pre + "ln_f.weight", np.asarray(params["final_norm"])
    yield pre + "ln_f.bias", np.asarray(params["final_norm_b"])


def params_to_hf_state(
    params: Dict[str, Any], cfg: TransformerConfig
) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield HF-named (name, array) pairs from the stacked pytree."""
    if cfg.hf_architecture == "GPT2LMHeadModel":
        yield from _gpt2_state(params, cfg)
        return
    if _dialect(cfg)[0] is not None:
        yield from _kinds_state(params, cfg)
        return
    if cfg.attn_kind == "latent":
        yield from _longcat_state(params, cfg)
        return
    if cfg.attn_kind == "windowed":
        raise NotImplementedError(_MIMO_NO_CHECKPOINT)
    yield "model.embed_tokens.weight", np.asarray(params["embedding"])
    layers = params["layers"]
    mixtral = cfg.hf_architecture == "MixtralForCausalLM"
    moe_prefix = "block_sparse_moe" if mixtral else "mlp"
    moe_names = (
        {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
        if mixtral
        else {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    )
    lmap = layer_name_map(cfg)
    for i in range(cfg.num_layers):
        prefix = f"model.layers.{i}."
        for suffix, (path_in_layer, transpose) in lmap.items():
            try:
                buf = _get_nested(layers, path_in_layer)
            except KeyError:
                continue
            arr = np.asarray(buf[i])
            if transpose:
                arr = arr.T
            yield prefix + suffix, arr
        if "moe" in layers:
            moe = layers["moe"]
            yield (
                f"{prefix}{moe_prefix}.gate.weight",
                np.asarray(moe["router"][i]).T,
            )
            for leaf, hf_leaf in moe_names.items():
                buf = np.asarray(moe[leaf][i])  # [E, D, F] / [E, F, D]
                for e in range(cfg.num_experts):
                    yield (
                        f"{prefix}{moe_prefix}.experts.{e}.{hf_leaf}.weight",
                        buf[e].T,
                    )
    yield "model.norm.weight", np.asarray(params["final_norm"])
    if "lm_head" in params:
        yield "lm_head.weight", np.asarray(params["lm_head"]).T
    elif not cfg.tie_word_embeddings:
        raise ValueError("untied config but params have no lm_head")
    if "vision" in params and cfg.vision is not None:
        vision = params["vision"]
        vc = cfg.vision
        # [patch_dim, D] matmul -> Conv3d [D, C, tps, ps, ps] (the real
        # Qwen2.5-VL layout, so transformers can load our checkpoints)
        yield (
            "visual.patch_embed.proj.weight",
            np.ascontiguousarray(np.asarray(vision["patch_embed"]).T).reshape(
                vc.hidden_size,
                vc.in_channels,
                vc.temporal_patch_size,
                vc.patch_size,
                vc.patch_size,
            ),
        )
        for name, (key, transpose) in _VISION_TOP_MAP.items():
            if key not in vision:
                continue  # pre-r3 trees carry no merger biases
            arr = np.asarray(vision[key])
            yield name, arr.T if transpose else arr
        for i in range(cfg.vision.num_layers):
            for suffix, (path_in_layer, transpose) in _VISION_LAYER_MAP.items():
                try:
                    buf = _get_nested(vision["layers"], path_in_layer)
                except KeyError:
                    continue  # pre-r3 trees carry no block biases
                arr = np.asarray(buf[i])
                yield (
                    f"visual.blocks.{i}.{suffix}",
                    arr.T if transpose else arr,
                )


def save_hf_checkpoint(
    params: Dict[str, Any],
    cfg: TransformerConfig,
    out_dir: str,
    save_dtype: str = "bfloat16",
    max_shard_bytes: int = 4 * 1024**3,
    tokenizer_src: Optional[str] = None,
) -> None:
    """Write an HF-format checkpoint dir (config.json + sharded safetensors
    + weight index), castable to bf16 for serving."""
    import ml_dtypes
    from safetensors.numpy import save_file

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg.to_hf_dict(), f, indent=2)

    target = np.dtype(ml_dtypes.bfloat16) if save_dtype == "bfloat16" else np.dtype(
        save_dtype
    )
    shards: List[Dict[str, np.ndarray]] = [{}]
    sizes = [0]
    weight_map: Dict[str, str] = {}
    for name, arr in params_to_hf_state(params, cfg):
        # np.asarray over a jax array may be stride-permuted (XLA layout) and
        # transposes are views; safetensors serializes the raw buffer, so the
        # array must be C-contiguous.
        arr = np.ascontiguousarray(arr.astype(target))
        if sizes[-1] + arr.nbytes > max_shard_bytes and shards[-1]:
            shards.append({})
            sizes.append(0)
        shards[-1][name] = arr
        sizes[-1] += arr.nbytes
    n = len(shards)
    for i, shard in enumerate(shards):
        fname = (
            "model.safetensors"
            if n == 1
            else f"model-{i + 1:05d}-of-{n:05d}.safetensors"
        )
        save_file(shard, os.path.join(out_dir, fname))
        for name in shard:
            weight_map[name] = fname
    if n > 1:
        with open(os.path.join(out_dir, "model.safetensors.index.json"), "w") as f:
            json.dump(
                {"metadata": {"total_size": sum(sizes)}, "weight_map": weight_map},
                f,
            )
    if tokenizer_src and os.path.isdir(tokenizer_src):
        for fname in (
            "tokenizer.json",
            "tokenizer_config.json",
            "vocab.json",
            "merges.txt",
            "special_tokens_map.json",
            "generation_config.json",
        ):
            src = os.path.join(tokenizer_src, fname)
            if os.path.exists(src):
                shutil.copy(src, os.path.join(out_dir, fname))
