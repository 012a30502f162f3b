"""Dataclasses crossing process / engine boundaries.

Capability counterpart of the reference's `areal/api/io_struct.py` (ModelRequest
:21, ModelResponse :47, WeightUpdateMeta :105, ParamSpec :93, SaveLoadMeta :197,
FinetuneSpec :77, StepInfo :215, RolloutStat).  torch-free: sizes are computed
with numpy dtypes and the weight-update channel is TPU-native ("disk" via a
shared filesystem + version handshake, or "transfer" via host RPC push).
"""

import os
import uuid
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, List, Literal, Optional, Tuple

import numpy as np

from areal_tpu.api.config import GenerationHyperparameters

if TYPE_CHECKING:
    from areal_tpu.api.alloc import AllocationMode


@dataclass
class ModelRequest:
    """One generation request travelling client -> inference server."""

    rid: str = field(default_factory=lambda: str(uuid.uuid4()))
    input_ids: List[int] = field(default_factory=list)
    gconfig: GenerationHyperparameters = field(
        default_factory=GenerationHyperparameters
    )
    metadata: Dict[str, Any] = field(default_factory=dict)
    tokenizer: Any = None
    image_data: Optional[List[Any]] = None
    processor: Any = None
    # native VLM serving wire format (gen/server.py): pre-patchified pixels
    # + per-image patch grids, the AutoProcessor's output layout
    pixel_values: Optional[Any] = None  # np [N, patch_dim]
    image_grid_thw: Optional[Any] = None  # np [n_img, 3]
    # group fan-out (gen/engine.py): GRPO siblings over one prompt share a
    # group_id + expected size so the router keeps them on one replica and
    # the engine clusters them for cross-slot KV prefix sharing
    group_id: str = ""
    group_n: int = 0
    # telemetry (utils/telemetry.py): trajectory-lifecycle trace id, carried
    # on the wire and echoed in the response meta; survives the interruption
    # loop's resubmissions because copy() preserves it
    trace_id: str = ""

    def copy(self) -> "ModelRequest":
        return ModelRequest(
            rid=self.rid,
            input_ids=list(self.input_ids),
            gconfig=self.gconfig.new(),
            metadata=dict(self.metadata),
            tokenizer=self.tokenizer,
            image_data=list(self.image_data) if self.image_data is not None else None,
            processor=self.processor,
            pixel_values=self.pixel_values,
            image_grid_thw=self.image_grid_thw,
            group_id=self.group_id,
            group_n=self.group_n,
            trace_id=self.trace_id,
        )


@dataclass
class ModelResponse:
    """Generation result; `output_versions` carries the weight version that
    produced each output token — the raw signal for staleness accounting and
    the decoupled-PPO behavior policy (reference: io_struct.py:47-75)."""

    input_tokens: List[int] = field(default_factory=list)
    output_tokens: List[int] = field(default_factory=list)
    output_logprobs: List[float] = field(default_factory=list)
    output_versions: List[int] = field(default_factory=list)
    stop_reason: Literal["length", "stop", "interrupt", "abort"] = "stop"
    tokenizer: Any = None
    input_images: List[Any] = field(default_factory=list)
    processor: Any = None
    # timing stats
    latency: float = float("inf")
    ttft: float = float("inf")
    itl: List[float] = field(default_factory=list)

    @property
    def input_len(self) -> int:
        return len(self.input_tokens)

    @property
    def output_len(self) -> int:
        return len(self.output_tokens)


@dataclass
class FinetuneSpec:
    total_train_epochs: int
    dataset_size: int
    train_batch_size: int

    def __post_init__(self):
        if self.train_batch_size <= 0:
            raise ValueError(f"train_batch_size={self.train_batch_size} must be > 0")
        if self.dataset_size < self.train_batch_size:
            raise ValueError(
                f"dataset_size={self.dataset_size} < train_batch_size="
                f"{self.train_batch_size}: zero steps per epoch (drop_last)"
            )

    @property
    def total_train_steps(self) -> int:
        return self.total_train_epochs * self.steps_per_epoch

    @property
    def steps_per_epoch(self) -> int:
        return self.dataset_size // self.train_batch_size


@dataclass
class ParamSpec:
    name: str
    shape: Tuple[int, ...]
    dtype: str

    @property
    def size(self) -> int:
        """Parameter bytes."""
        return int(np.dtype(self.dtype).itemsize * int(np.prod(self.shape)))


@dataclass
class WeightUpdateMeta:
    """How fresh trainer weights reach inference servers.

    - "disk": trainer writes a safetensors/tensorstore snapshot under `path`
      and publishes a version timestamp in name_resolve; servers reload from
      the shared filesystem (reference disk path: fsdp_engine.py:403-425).
    - "transfer": trainer pushes host-gathered shards over HTTP chunks
      directly into server HBM (TPU-native replacement of the reference's
      NCCL broadcast group, fsdp_engine.py:298-401).
    """

    type: Literal["disk", "transfer"] = "disk"
    path: Optional[str] = None
    alloc_mode: Optional["AllocationMode"] = None
    chunk_mb: int = 256
    use_lora: bool = False
    # transfer commits only: swap without aborting in-flight generation
    # (GenEngine.swap_weights_live semantics — requests keep decoding, the
    # policy transition is recorded in per-token versions).  Default ON —
    # abort-and-resume measured below sync in round 4 (not re-measured
    # since); False reproduces the
    # reference's abort-only choreography.
    live_commit: bool = True
    # identify the trial for the name_resolve version handshake
    experiment_name: str = ""
    trial_name: str = ""
    # disk updates only: pin the exact version the servers must load.
    # None (the default, normal training) lets each server resolve the
    # newest v{N} snapshot itself; recovery replays set it so rejoining
    # servers are forced to the RECOVERED version even when a newer,
    # never-trained-on snapshot survived the crash on disk.
    version: Optional[int] = None

    @classmethod
    def from_disk(
        cls,
        experiment_name: str,
        trial_name: str,
        file_root: str,
        name: str = "default",
        use_lora: bool = False,
    ) -> "WeightUpdateMeta":
        path = os.path.join(
            file_root,
            "checkpoints",
            experiment_name,
            trial_name,
            name,
            "weight_update",
        )
        return cls(
            type="disk",
            path=path,
            use_lora=use_lora,
            experiment_name=experiment_name,
            trial_name=trial_name,
        )

    @classmethod
    def from_transfer(
        cls,
        experiment_name: str = "",
        trial_name: str = "",
        alloc_mode: Optional["AllocationMode"] = None,
        chunk_mb: int = 256,
        live_commit: bool = True,
    ) -> "WeightUpdateMeta":
        return cls(
            type="transfer",
            alloc_mode=alloc_mode,
            chunk_mb=chunk_mb,
            live_commit=live_commit,
            experiment_name=experiment_name,
            trial_name=trial_name,
        )


@dataclass
class SaveLoadMeta:
    path: str
    weight_format: str = "safetensors"  # safetensors | tensorstore
    with_optim: bool = False
    tokenizer: Any = None
    processor: Any = None
    base_model_path: Optional[str] = None


@dataclass
class RolloutStat:
    submitted: int = 0
    accepted: int = 0
    running: int = 0
    # rollouts that settled without acceptance (should_accept veto, episode
    # failure, or trajectory lost to fleet failure) — tracked explicitly so
    # the ledger invariant submitted == accepted + rejected + running is
    # checkable at every transition
    rejected: int = 0


@dataclass
class StepInfo:
    epoch: int
    epoch_step: int
    global_step: int
    steps_per_epoch: int

    def next(self) -> "StepInfo":
        last = self.epoch_step == self.steps_per_epoch - 1
        return StepInfo(
            epoch=self.epoch + int(last),
            epoch_step=0 if last else self.epoch_step + 1,
            global_step=self.global_step + 1,
            steps_per_epoch=self.steps_per_epoch,
        )


@dataclass
class HttpRequest:
    endpoint: str
    payload: Dict[str, Any]
    method: str = "POST"


@dataclass
class HttpGenerationResult:
    output_tokens: List[int]
    output_logprobs: List[float]
    stop_reason: str
    version: int = -1
    # prompt tokens served from the server's radix/paged prefix cache
    # (warm-started failover resubmits report nonzero here)
    cache_hit_tokens: int = 0


@dataclass
class WeightUpdateRequests:
    requests: List[HttpRequest] = field(default_factory=list)
