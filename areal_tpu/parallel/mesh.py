"""Device-mesh construction and sharding helpers.

TPU-native counterpart of the reference's process-group plumbing: what FSDP2
DeviceMesh setup (areal/utils/fsdp/parallel.py:87), Megatron 5-D initialization
(areal/engine/megatron_engine.py:176-237) and the legacy ParallelGrid
(realhf/base/topology.py:369) achieve with explicit NCCL groups is here a
single `jax.sharding.Mesh` over axes (dp, fsdp, sp, tp); GSPMD derives every
collective from PartitionSpecs, so there is no group bookkeeping to port.

Axis semantics:
- dp: pure data parallel (replicated params, sharded batch rows)
- fsdp: ZeRO-style — params/optimizer sharded here AND batch rows sharded
  (the reference's dp axis under FSDP2 plays both roles too)
- sp: sequence dimension of activations (Ulysses/CP-equivalent; GSPMD
  inserts the head/seq all-to-alls the reference hand-writes in
  areal/utils/ulysses.py)
- tp: tensor parallel (megatron column/row split via the model's specs)
- ep: expert parallel (MoE expert dim; the reference's
  expert_parallel_size, alloc_mode.py:80-117 / megatron EP groups) — the
  ep axis also carries batch rows when dense layers run, so ep chips are
  never idle outside MoE blocks
"""

from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.api.alloc import ParallelStrategy
from areal_tpu.utils import logging

logger = logging.getLogger("parallel.mesh")

MeshAxes = ("dp", "fsdp", "ep", "sp", "tp")


def build_mesh(
    dp: int = 1,
    fsdp: int = 1,
    sp: int = 1,
    tp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build the 5-axis mesh. Axis order puts tp innermost so tensor-parallel
    collectives ride the fastest ICI links."""
    if devices is None:
        devices = jax.devices()
    need = dp * fsdp * sp * tp * ep
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    if len(devices) > need:
        # a mesh smaller than the host leaves chips idle: say which were
        # taken, so "everything on the first chip" is never silent
        logger.info(
            f"mesh takes {need} of {len(devices)} devices: "
            f"{[d.id for d in devices[:need]]} "
            f"({devices[0].device_kind}); the others stay idle"
        )
    dev = np.asarray(devices[:need]).reshape(dp, fsdp, ep, sp, tp)
    return Mesh(dev, MeshAxes)


def mesh_from_alloc(
    strategy: ParallelStrategy, devices: Optional[Sequence[Any]] = None
) -> Mesh:
    """Map an allocation-DSL ParallelStrategy onto mesh axes.

    The DSL's context/sequence parallel sizes both land on the `sp` axis
    (they are the same axis on TPU: shard the sequence dim, let GSPMD insert
    gathers); pipeline parallel is intentionally not an axis — GSPMD+ICI
    covers TPU slices without PP (SURVEY.md §7).
    """
    if strategy.pipeline_parallel_size > 1:
        raise NotImplementedError(
            "pipeline parallelism is not a TPU mesh axis; use fsdp/tp/sp"
        )
    sp = strategy.sequence_parallel_size * strategy.context_parallel_size
    return build_mesh(
        dp=strategy.data_parallel_size,
        fsdp=strategy.fsdp_parallel_size,
        sp=sp,
        tp=strategy.tensor_parallel_size,
        ep=strategy.expert_parallel_size,
        devices=devices,
    )


def batch_spec(per_token: bool = True) -> P:
    """PartitionSpec for [R, L(, ...)] batch arrays: rows over
    (dp, fsdp, ep) — ep chips carry rows through the dense layers and
    exchange tokens for expert compute inside the MoE block — sequence
    over sp."""
    if per_token:
        return P(("dp", "fsdp", "ep"), "sp")
    return P(("dp", "fsdp", "ep"))


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_pytree(mesh: Mesh, tree: Any, specs: Any) -> Any:
    """Host pytree -> sharded device pytree (specs mirrors tree).

    Single-process: plain device_put.  Multi-process: every process holds
    the identical host values and contributes its local shards via
    make_array_from_callback (device_put cannot target non-addressable
    devices)."""
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs
        )

    def _make(x, s):
        sharding = NamedSharding(mesh, s)
        if isinstance(x, jax.Array):
            # already a device array (e.g. the trainer's params in a
            # colocated publish): reshard device-to-device — np.asarray
            # would gather through the host (and raise outright on
            # non-addressable shards)
            return jax.device_put(x, sharding)
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx, x=x: x[idx]
        )

    return jax.tree_util.tree_map(_make, tree, specs)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
