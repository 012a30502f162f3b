"""Structured configuration for every subsystem.

Capability counterpart of the reference's `areal/api/cli_args.py` (1314 LoC of
dataclasses + OmegaConf/Hydra loading).  Re-designed without OmegaConf: a plain
dataclass tree plus a small recursive YAML/dot-list merge (`load_expr_config`),
which covers the reference's `cli_args.py:1247-1310` behavior (YAML file +
`a.b.c=value` command-line overrides).
"""

import argparse
import dataclasses
import os
from dataclasses import dataclass, field, fields, is_dataclass
from typing import Any, Dict, List, Optional, Tuple, Type, TypeVar, Union, get_args, get_origin

import yaml

T = TypeVar("T")


# ---------------------------------------------------------------------------
# Generation
# ---------------------------------------------------------------------------


@dataclass
class GenerationHyperparameters:
    """Per-request sampling config (reference: cli_args.py GenerationHyperparameters)."""

    n_samples: int = 1
    max_new_tokens: int = 256
    min_new_tokens: int = 0
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    greedy: bool = False
    stop_token_ids: List[int] = field(default_factory=list)
    stop: List[str] = field(default_factory=list)
    frequency_penalty: float = 0.0

    def new(self, **kwargs) -> "GenerationHyperparameters":
        return dataclasses.replace(self, **kwargs)


# ---------------------------------------------------------------------------
# Optimizer / train engine
# ---------------------------------------------------------------------------


@dataclass
class OptimizerConfig:
    type: str = "adamw"
    lr: float = 2e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    min_lr_ratio: float = 0.0
    lr_scheduler_type: str = "constant"  # constant | linear | cosine
    warmup_steps_proportion: float = 0.001
    gradient_clipping: float = 1.0
    # Offload optimizer state to host memory between steps (TPU HBM relief).
    offload: bool = False


@dataclass
class MeshConfig:
    """How a train engine lays its chips out as a jax.sharding.Mesh.

    Normally derived from the allocation expression; explicit here for tests
    and single-engine runs.
    """

    data_parallel_size: int = 1
    fsdp_parallel_size: int = 1
    sequence_parallel_size: int = 1
    tensor_parallel_size: int = 1
    expert_parallel_size: int = 1

    @property
    def world_size(self) -> int:
        return (
            self.data_parallel_size
            * self.fsdp_parallel_size
            * self.sequence_parallel_size
            * self.tensor_parallel_size
        )


@dataclass
class TrainEngineConfig:
    experiment_name: str = ""
    trial_name: str = ""
    path: str = ""  # HF model path or name
    init_from_scratch: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"  # master copy / optimizer dtype
    disable_dropout: bool = True
    gradient_checkpointing: bool = True
    # "full" recomputes layers in backward (min HBM); "dots" keeps matmul
    # outputs (faster when HBM allows — v5p-class chips); "save_attn"/
    # "save_mlp" keep only the tagged attention/MLP outputs;
    # "carry_offload" keeps both tags but parks them in pinned host memory
    # (models/model_config.py TransformerConfig.remat_policy)
    remat_policy: str = "full"
    # two-level layer scan (models/transformer.py): the outer scan runs
    # num_layers/G steps, each an unrolled chain of G layers behind ONE
    # remat boundary — saved carries shrink ~G×.  Must divide the model
    # depth (rejected loudly); 1 = the classic per-layer scan
    layer_group_size: int = 1
    # outer-scan unroll: >1 cuts per-step scan overhead (~2% throughput at
    # 4 on v5e 1.5B) for more compile time/live buffers; must divide the
    # outer scan length (num_layers / layer_group_size) — non-divisors
    # warn loudly and fall back to 1; the effective value rides train stats
    scan_unroll: int = 1
    # fused LM-head vocab chunk width (ops/fused_xent.py), rounded up to a
    # multiple of 128; 0 = 8192.  Passed through the loss partial
    lm_head_chunk: int = 0
    mb_spec: "MicroBatchSpec" = field(default_factory=lambda: MicroBatchSpec())
    optimizer: Optional[OptimizerConfig] = field(default_factory=OptimizerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    pad_to_maximum: bool = False
    # Sequence-length bucketing for packed batches: powers-of-two multiples of
    # this quantum; avoids XLA recompilation storms on variable-length data.
    pack_length_quantum: int = 512
    max_pack_length: int = 32768
    # forwarded onto the model config at initialize: "auto" picks the
    # splash kernel when shapes allow; "ring" turns an sp>1 mesh axis
    # (alloc `s`/`c` dims) into ring attention — K/V sequence-sharded
    # context parallelism (ops/attention.py ring_attention)
    attn_impl: str = "auto"  # auto | splash | naive | ring
    # Defer the per-step stats fetch so consecutive train steps pipeline on
    # the device (the fetch otherwise serialises the trainer on dispatch
    # latency).  train_batch then returns a
    # PendingTrainStats mapping that materialises on first read; per-step
    # step_time/tflops/mfu keys are omitted (no sync point to measure them).
    async_stats: bool = False
    lora: "LoRAConfig" = field(default_factory=lambda: LoRAConfig())


@dataclass
class LoRAConfig:
    enabled: bool = False
    rank: int = 8
    alpha: float = 16.0
    target_modules: List[str] = field(
        default_factory=lambda: ["q_proj", "k_proj", "v_proj", "o_proj"]
    )


@dataclass
class MicroBatchSpec:
    """Micro-batch splitting spec (reference: cli_args.py MicroBatchSpec)."""

    n_mbs: int = 1
    max_tokens_per_mb: int = 0  # 0 = unlimited; else balanced FFD packing
    granularity: int = 1


# ---------------------------------------------------------------------------
# PPO / algorithm configs
# ---------------------------------------------------------------------------


@dataclass
class NormConfig:
    mean_level: Optional[str] = "group"  # batch | group | none/null
    std_level: Optional[str] = "group"
    group_size: int = 1
    eps: float = 1e-5


@dataclass
class PPOActorConfig(TrainEngineConfig):
    group_size: int = 1  # answers per prompt (GRPO group)
    ppo_n_minibatches: int = 4
    eps_clip: float = 0.2
    eps_clip_higher: Optional[float] = None  # asymmetric clipping (DAPO)
    c_clip: Optional[float] = None  # dual clip
    temperature: float = 1.0
    # rewards
    group_reward_norm: bool = False
    # full-control reward normalization (lite_ppo group-mean/batch-std,
    # dr.grpo group-mean/no-std); overrides group_reward_norm when set
    reward_norm: Optional[NormConfig] = None
    reward_scaling: float = 1.0
    reward_bias: float = 0.0
    reward_clip: float = 20.0
    overlong_reward_penalty: bool = False
    overlong_tokens: int = 0
    overlong_penalty_factor: float = 0.0
    # generation budget the overlong penalty is measured against (DAPO);
    # must equal the rollout's gconfig.max_new_tokens
    max_new_tokens: int = 0
    mask_no_eos_with_zero: bool = False
    # KL & advantages
    kl_ctl: float = 0.0
    kl_estimator: str = "k1"  # k1 | k2 | k3
    discount: float = 1.0
    gae_lambda: float = 1.0
    adv_norm: Optional[NormConfig] = field(default_factory=NormConfig)
    # decoupled PPO
    recompute_logprob: bool = True
    use_decoupled_loss: bool = True
    behav_imp_weight_cap: Optional[float] = None
    # dynamic sampling (reject groups with identical rewards)
    dynamic_sampling: bool = False
    log_agent_stats: bool = False
    log_agent_stats_keys: List[str] = field(default_factory=list)


@dataclass
class PPOCriticConfig(TrainEngineConfig):
    value_eps_clip: float = 0.2
    ppo_n_minibatches: int = 4
    mask_no_eos_with_zero: bool = False


# ---------------------------------------------------------------------------
# Inference engine / rollout
# ---------------------------------------------------------------------------


@dataclass
class InferenceEngineConfig:
    experiment_name: str = ""
    trial_name: str = ""
    max_concurrent_rollouts: Optional[int] = None
    queue_size: Optional[int] = None
    consumer_batch_size: int = 1
    max_head_offpolicyness: int = 0  # max staleness η
    check_trajectory_format: bool = False
    schedule_policy: str = "round_robin"  # round_robin | least_requests
    setup_timeout: float = 120.0
    request_timeout: float = 3600.0
    request_retries: int = 3
    pause_grace_period: float = 0.0
    cleanup_timeout: float = 120.0
    # trajectory failover (ISSUE 11): how many times one trajectory may be
    # resubmitted to a different server after a backend failure before it
    # is declared lost, and how long a failed server is excluded from
    # re-placement
    failover_retries: int = 3
    failover_cooldown: float = 30.0


@dataclass
class GenServerConfig:
    """Config for the JAX generation server (counterpart of SGLangConfig)."""

    model_path: str = ""
    dtype: str = "bfloat16"
    max_seqs: int = 64  # continuous-batching slots
    prefill_chunk: int = 512
    max_context_len: int = 8192
    page_size: int = 128
    mesh: MeshConfig = field(default_factory=MeshConfig)
    host: str = "127.0.0.1"
    port: int = 0  # 0 = pick free port
    enable_metrics: bool = True
    random_seed: int = 1
    # KV cache dtype; bf16 default, fp8-style int8 quantization optional later.
    kv_dtype: str = "bfloat16"
    # Tiered decode (ISSUE 5): decode attention reads a bucketed key window
    # over the occupied span instead of the full max_context_len ceiling.
    decode_window: bool = True
    # Number of length-cohort slot tiers (1 = single cohort).  >1 splits the
    # slot grid into contiguous blocks with ascending length ceilings so a
    # long rollout does not inflate the short cohort's attended window;
    # explicit layouts override via decode_tier_lens/decode_tier_slots
    # (parallel lists: per-tier length ceiling / slot count).
    decode_tiers: int = 1
    decode_tier_lens: List[int] = field(default_factory=list)
    decode_tier_slots: List[int] = field(default_factory=list)
    # Self-speculative decoding (ISSUE 12): prompt-lookup drafts verified in
    # one dispatch per tier; the emitted streams are bit-identical to plain
    # decode at any temperature (counter-keyed sampling), so this is purely
    # a throughput knob.  spec_ladder lists the static draft-length rungs
    # (must match the checked-in signature budget's spec_rungs accounting);
    # spec_draft_len > 0 pins D instead of adapting.
    spec_decode: bool = False
    spec_ladder: List[int] = field(default_factory=list)
    spec_draft_len: int = 0
    # Disaggregated-fleet role (ISSUE 17): prefill | decode | both.  The
    # launcher must plumb this through --role or every server comes up
    # colocated and the router's role pools stay empty.
    role: str = "both"
    # Host-DRAM overflow tier for evicted retained prefixes (ISSUE 16);
    # --role decode implies it server-side, but launchers should set it
    # explicitly so the capacity flag below is honored.
    host_offload: bool = False
    host_cache_mb: int = 64
    # Paged decode attention (ISSUE 19): one fused Pallas kernel dispatch
    # covers the whole slot grid and reads each slot's occupied pages
    # through the KV page table, instead of a dispatch a tier that copies
    # the key window out of the cache first; output streams are
    # bit-identical either way.  None (unset): the engine takes the kernel
    # wherever it applies (a slot of K/V columns, the window inside the
    # kernel's VMEM budget) and the copy path elsewhere.  True requires it:
    # the server refuses to start where it does not apply.  False is the
    # copy path.
    ragged_attn: Optional[bool] = None

    @staticmethod
    def build_cmd(
        config: "GenServerConfig",
        host: str,
        port: int,
        dist_init_addr: Optional[str] = None,
    ) -> str:
        """Shell command launching a generation server (reference:
        SGLangConfig.build_cmd); flags match gen/server.py's argparse —
        launchers must use this instead of hand-building the command."""
        import sys

        args = [
            sys.executable, "-m", "areal_tpu.gen.server",
            f"--model-path={config.model_path}",
            f"--n-slots={config.max_seqs}",
            f"--max-seq-len={config.max_context_len}",
            f"--tp={max(1, config.mesh.tensor_parallel_size)}",
            f"--ep={max(1, config.mesh.expert_parallel_size)}",
        ]
        if config.role != "both":
            args.append(f"--role={config.role}")
        if config.host_offload:
            args.append("--host-offload")
            args.append(f"--host-cache-mb={config.host_cache_mb}")
        if not config.decode_window:
            args.append("--no-decode-window")
        if config.decode_tiers > 1:
            args.append(f"--decode-tiers={config.decode_tiers}")
        if config.decode_tier_lens:
            args.append(
                "--decode-tier-lens="
                + ",".join(str(x) for x in config.decode_tier_lens)
            )
            args.append(
                "--decode-tier-slots="
                + ",".join(str(x) for x in config.decode_tier_slots)
            )
        if config.spec_decode:
            args.append("--spec-decode")
            if config.spec_ladder:
                args.append(
                    "--spec-ladder="
                    + ",".join(str(x) for x in config.spec_ladder)
                )
            if config.spec_draft_len:
                args.append(f"--spec-draft-len={config.spec_draft_len}")
        if config.ragged_attn is not None:
            args.append(
                "--ragged-attn" if config.ragged_attn else "--no-ragged-attn"
            )
        if port:
            args.append(f"--port={port}")
        return " ".join(args)


# ---------------------------------------------------------------------------
# Infra: saver / evaluator / recover / stats / name_resolve / launcher
# ---------------------------------------------------------------------------


@dataclass
class TimerConfig:
    freq_epochs: Optional[int] = None
    freq_steps: Optional[int] = None
    freq_secs: Optional[int] = None


@dataclass
class SaverConfig(TimerConfig):
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""


@dataclass
class EvaluatorConfig(TimerConfig):
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""


@dataclass
class RecoverConfig(TimerConfig):
    mode: str = "disabled"  # disabled | auto | fault | resume
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""
    retries: int = 3


@dataclass
class StatsLoggerConfig:
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""
    wandb: Dict[str, Any] = field(default_factory=dict)
    tensorboard_dir: Optional[str] = None


@dataclass
class NameResolveConfig:
    # http = first-party TTL'd KV service (utils/kv_store.py), the
    # distributed-fleet backend (etcd3-lease semantics without etcd)
    type: str = "memory"  # memory | nfs | http
    nfs_record_root: str = "/tmp/areal_tpu/name_resolve"
    http_addr: str = "localhost:18999"
    etcd3_addr: str = "localhost:2379"  # legacy field; etcd3 -> use http


@dataclass
class ClusterSpecConfig:
    name_resolve: NameResolveConfig = field(default_factory=NameResolveConfig)
    cluster_name: str = "local"
    fileroot: str = "/tmp/areal_tpu/experiments"
    n_nodes: int = 1
    n_accelerators_per_node: int = 8


@dataclass
class LauncherConfig:
    inference_server_cpus_per_accelerator: int = 4
    inference_server_mem_per_accelerator: int = 32768
    trainer_cpus_per_accelerator: int = 4
    trainer_mem_per_accelerator: int = 32768
    inference_server_env_vars: str = ""
    trainer_env_vars: str = ""
    trainer_port: int = 27009


@dataclass
class DatasetConfig:
    path: str = ""
    type: str = ""
    batch_size: int = 1
    shuffle: bool = True
    pin_memory: bool = False
    num_workers: int = 2
    drop_last: bool = True
    max_length: Optional[int] = None


# ---------------------------------------------------------------------------
# Experiment-level configs
# ---------------------------------------------------------------------------


@dataclass
class BaseExperimentConfig:
    experiment_name: str = "my-exp"
    trial_name: str = "my-trial"
    cluster: ClusterSpecConfig = field(default_factory=ClusterSpecConfig)
    allocation_mode: str = ""
    seed: int = 1
    total_train_epochs: int = 1
    total_train_steps: Optional[int] = None
    tokenizer_path: str = ""
    train_dataset: DatasetConfig = field(default_factory=DatasetConfig)
    valid_dataset: Optional[DatasetConfig] = None
    saver: SaverConfig = field(default_factory=SaverConfig)
    checkpointer: SaverConfig = field(default_factory=SaverConfig)
    evaluator: EvaluatorConfig = field(default_factory=EvaluatorConfig)
    recover: RecoverConfig = field(default_factory=RecoverConfig)
    stats_logger: StatsLoggerConfig = field(default_factory=StatsLoggerConfig)
    launcher: LauncherConfig = field(default_factory=LauncherConfig)


@dataclass
class SFTConfig(BaseExperimentConfig):
    model: TrainEngineConfig = field(default_factory=TrainEngineConfig)


@dataclass
class RWConfig(BaseExperimentConfig):
    model: TrainEngineConfig = field(default_factory=TrainEngineConfig)


@dataclass
class GRPOConfig(BaseExperimentConfig):
    async_training: bool = True
    # trainer -> inference weight sync: "disk" (shared-fs snapshot, the
    # simple correct default) or "transfer" (HTTP chunk streaming straight
    # into server memory — no shared filesystem, lower latency at scale)
    weight_update_mode: str = "disk"
    # transfer mode only: commit staged weights WITHOUT aborting in-flight
    # generation (swap_weights_live — requests keep decoding across the
    # publish, per-token versions record the transition).  Default ON: the
    # abort-and-resume choreography measured below sync in round 4 (a
    # record since deleted; not re-measured on today's code) while the live
    # commit keeps the pipeline saturated; set False to reproduce the
    # reference's abort-only behavior (SGLang cannot hot-swap mid-request)
    weight_update_live_commit: bool = True
    # (n_sequences, seq_len) pack signatures to AOT-compile before step 0
    # (PPOActor.warm_shapes): varying rollout lengths otherwise trigger XLA
    # compiles INSIDE the training loop the first time each signature lands
    warm_pack_shapes: List[List[int]] = field(default_factory=list)
    gconfig: GenerationHyperparameters = field(
        default_factory=GenerationHyperparameters
    )
    rollout: InferenceEngineConfig = field(default_factory=InferenceEngineConfig)
    gen_server: GenServerConfig = field(default_factory=GenServerConfig)
    actor: PPOActorConfig = field(default_factory=PPOActorConfig)
    ref: Optional[TrainEngineConfig] = None
    # rollout episode pattern: "rlvr" (single-turn) or "multi_turn"
    # (retry-with-feedback, reference workflow/multi_turn.py)
    workflow: str = "rlvr"
    max_turns: int = 3
    turn_discount: float = 0.9


@dataclass
class PPOConfig(GRPOConfig):
    critic: PPOCriticConfig = field(default_factory=PPOCriticConfig)


# ---------------------------------------------------------------------------
# Loading: YAML + dot-list overrides (no OmegaConf)
# ---------------------------------------------------------------------------


def _from_dict(
    cls: Type[T],
    data: Dict[str, Any],
    path: str = "",
    ignore_unknown_top: bool = False,
) -> T:
    if data is None:
        data = {}
    if not isinstance(data, dict):
        raise ValueError(f"config node {path or '<root>'} must be a mapping")
    kwargs = {}
    fld_map = {f.name: f for f in fields(cls)}
    for key, value in data.items():
        if key not in fld_map:
            if ignore_unknown_top and not path:
                # launchers parse experiment configs only for THEIR fields
                # (gen_server, allocation_mode, ...); example-specific
                # top-level sections (e.g. PPOConfig's `critic`) must not
                # fail the launch — the entry point re-parses strictly
                continue
            raise ValueError(f"unknown config key {path + key!r} for {cls.__name__}")
        kwargs[key] = _coerce(fld_map[key].type, value, path + key + ".")
    return cls(**kwargs)


def _unwrap_optional(tp):
    origin = get_origin(tp)
    if origin is Union:
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0], True
    return tp, False


def _coerce(tp, value, path):
    if isinstance(tp, str):
        # string annotations from `from __future__` or forward refs
        tp = _resolve_annotation(tp)
    tp, optional = _unwrap_optional(tp)
    if value is None:
        return None
    if is_dataclass(tp) and isinstance(value, dict):
        return _from_dict(tp, value, path)
    if is_dataclass(tp) and isinstance(value, tp):
        return value
    origin = get_origin(tp)
    if origin in (list, List):
        (etp,) = get_args(tp) or (Any,)
        return [_coerce(etp, v, path) for v in value]
    if origin in (dict, Dict):
        return dict(value)
    if tp is bool and isinstance(value, str):
        return value.lower() in ("1", "true", "yes", "on")
    if tp in (int, float, str) and not isinstance(value, tp):
        return tp(value)
    return value


_ANNOT_CACHE: Dict[str, Any] = {}


def _resolve_annotation(name: str):
    if name in _ANNOT_CACHE:
        return _ANNOT_CACHE[name]
    ns = dict(globals())
    import typing

    ns.update(vars(typing))
    try:
        tp = eval(name, ns)  # noqa: S307 — annotations from this module only
    except Exception:
        tp = Any
    _ANNOT_CACHE[name] = tp
    return tp


def to_dict(cfg) -> Dict[str, Any]:
    if is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in fields(cfg)}
    if isinstance(cfg, list):
        return [to_dict(v) for v in cfg]
    if isinstance(cfg, dict):
        return {k: to_dict(v) for k, v in cfg.items()}
    return cfg


def _apply_dotlist(data: Dict[str, Any], overrides: List[str]):
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override {item!r} must look like a.b.c=value")
        key, _, raw = item.partition("=")
        node = data
        parts = key.strip().split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
            if not isinstance(node, dict):
                raise ValueError(f"cannot override through non-mapping at {p!r}")
        node[parts[-1]] = yaml.safe_load(raw) if raw != "" else None


def load_expr_config(
    argv: List[str],
    config_cls: Type[T],
    ignore_unknown_top: bool = False,
) -> Tuple[T, str]:
    """Parse `--config path.yaml key=value ...` into a config dataclass.

    Counterpart of the reference's `load_expr_config` (cli_args.py:1280).
    Returns (config, config_file_path).  `ignore_unknown_top` skips unknown
    TOP-LEVEL yaml sections (for launchers, which parse experiment configs
    only for the fields they own); nested typos still fail loudly.
    """
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default=None)
    args, overrides = parser.parse_known_args(argv)
    bad = [o for o in overrides if o.startswith("--")]
    if bad:
        raise ValueError(
            f"unrecognized flags {bad}; overrides use dotted form a.b.c=value"
        )
    data: Dict[str, Any] = {}
    if args.config:
        with open(args.config) as f:
            data = yaml.safe_load(f) or {}
    _apply_dotlist(data, overrides)
    cfg = _from_dict(config_cls, data, ignore_unknown_top=ignore_unknown_top)
    # propagate experiment/trial names into nested configs that carry them
    for f in fields(cfg):
        sub = getattr(cfg, f.name)
        if is_dataclass(sub) and hasattr(sub, "experiment_name"):
            if getattr(sub, "experiment_name", None) in ("", None):
                sub.experiment_name = cfg.experiment_name
            if getattr(sub, "trial_name", None) in ("", None):
                sub.trial_name = cfg.trial_name
        if is_dataclass(sub) and hasattr(sub, "fileroot"):
            if getattr(sub, "fileroot", None) in ("", None):
                sub.fileroot = cfg.cluster.fileroot
    # select the name_resolve backend for this process: the env override
    # (set by multi-host launchers for every spawned process) wins over the
    # config; both route through utils.name_resolve.reconfigure
    if hasattr(cfg, "cluster"):
        from areal_tpu.utils import name_resolve as _nr

        _nr.reconfigure_from_env(cfg.cluster.name_resolve)
    return cfg, args.config or ""


def save_config(cfg, path: str):
    if os.path.dirname(path):
        os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        yaml.safe_dump(to_dict(cfg), f, sort_keys=False)
