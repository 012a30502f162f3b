"""Multi-process (multi-host) runtime plumbing.

TPU-native counterpart of the reference's process-group bring-up and
cross-rank data movement:

- `init_distributed` plays the role of torch `init_process_group` +
  platform backend selection (areal/engine/fsdp_engine.py:112
  create_process_group, areal/platforms/*.communication_backend): one
  `jax.distributed.initialize` call and every chip on every host joins a
  single global device list; GSPMD collectives ride ICI within a slice and
  DCN across hosts with no further group bookkeeping.
- `broadcast_pytree` is the host-side data plane the reference builds from
  NCCL broadcast + two-phase shape handshakes (areal/utils/data.py:915-1007
  broadcast_tensor_container, core/dist_rollout.py:99-146): arbitrary
  pytrees move head -> all via two device broadcasts (length, payload).
- `make_global_batch` turns a replicated host batch into jax Arrays laid
  out over a multi-process mesh (the role of DTensor construction under
  FSDP2): each process contributes exactly the shards it owns.

Env contract (set by the launcher, one process per host):
  AREAL_COORDINATOR   host:port of process 0 (jax.distributed coordinator)
  AREAL_NUM_PROCESSES total process count
  AREAL_PROCESS_ID    this process's rank
"""

import os
import pickle
from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.utils import logging

logger = logging.getLogger("distributed")

_INITIALIZED = False


def multi_process_requested() -> bool:
    return int(os.environ.get("AREAL_NUM_PROCESSES", "1")) > 1


def init_distributed(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Join the global JAX runtime.  No-op when single-process (the common
    dev path) or when already initialized.  Arguments default to the
    AREAL_* env contract above."""
    global _INITIALIZED
    if _INITIALIZED:
        return
    num_processes = num_processes or int(os.environ.get("AREAL_NUM_PROCESSES", "1"))
    if num_processes <= 1:
        return
    coordinator = coordinator or os.environ["AREAL_COORDINATOR"]
    process_id = (
        process_id
        if process_id is not None
        else int(os.environ["AREAL_PROCESS_ID"])
    )
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        # XLA:CPU has no cross-process collectives of its own ("Multiprocess
        # computations aren't implemented on the CPU backend"); the gloo
        # TCP backend provides them.  Must be configured BEFORE the backend
        # initializes — and only for explicit CPU runs (the multi-process
        # CPU tests): TPU runs use ICI/DCN and must not see this.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True
    logger.info(
        f"joined distributed runtime: process {process_id}/{num_processes}, "
        f"{len(jax.local_devices())} local / {len(jax.devices())} global devices"
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_head() -> bool:
    return jax.process_index() == 0


# ---------------------------------------------------------------------------
# Host-side data plane
# ---------------------------------------------------------------------------


def broadcast_pytree(obj: Any, is_source: Optional[bool] = None) -> Any:
    """Broadcast an arbitrary picklable pytree from the head process to all.

    Two-phase (length then payload) because `broadcast_one_to_all` needs
    identical shapes on every process and only the head knows the batch's
    — the same reason the reference's tensor-container broadcast sends
    metadata before data (areal/utils/data.py:948-1007).
    """
    from jax.experimental import multihost_utils

    if jax.process_count() == 1:
        return obj
    if is_source is None:
        is_source = is_head()
    payload = (
        np.frombuffer(pickle.dumps(obj), np.uint8)
        if is_source
        else np.zeros((0,), np.uint8)
    )
    n = multihost_utils.broadcast_one_to_all(
        np.array([payload.size], np.int64), is_source=is_source
    )
    buf = np.zeros((int(n[0]),), np.uint8)
    if is_source:
        buf[:] = payload
    buf = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
    # broadcast_one_to_all implements the broadcast as a psum behind a
    # source flag, which PROMOTES the dtype on some backends (uint8 ->
    # float); the values stay exact (<= 255) but bytes() of the promoted
    # buffer would reinterpret float words as pickle opcodes — cast back
    # before decoding
    return pickle.loads(np.asarray(buf).astype(np.uint8).tobytes())


def make_global_batch(
    mesh: Mesh, spec_for: Dict[str, P], host_batch: Dict[str, np.ndarray]
) -> Dict[str, jax.Array]:
    """Replicated host batch -> global device arrays over a (possibly
    multi-process) mesh.  Every process must hold the identical host batch
    (use `broadcast_pytree` first); each contributes its local shards."""
    out = {}
    for k, v in host_batch.items():
        sharding = NamedSharding(mesh, spec_for[k])
        out[k] = jax.make_array_from_callback(
            v.shape, sharding, lambda idx, v=v: v[idx]
        )
    return out


def fetch_replicated(tree: Any) -> Any:
    """device_get for outputs that are replicated over the mesh (stats,
    losses): safe in multi-process because every process holds a full
    replica as an addressable shard.  All leaves go through ONE batched
    device_get (async copies issued together) — per-leaf np.asarray would
    pay a blocking device-to-host round-trip each."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    local = [
        x.addressable_data(0) if isinstance(x, jax.Array) else x for x in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, jax.device_get(local))
