"""Multi-process worker: 2-process x 4-CPU-device distributed train step.

Spawned by tests/test_multiprocess.py (and __graft_entry__.dryrun_multiprocess)
with AREAL_COORDINATOR / AREAL_NUM_PROCESSES / AREAL_PROCESS_ID set — the
same env contract a real multi-host launcher uses.  Mirrors the reference's
torchrun-driven distributed tests (areal/tests/torchrun/run_fsdp_ulysses_
forward.py): fabricate the runtime, run real collective work, print results
for the parent to compare.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import jax  # noqa: E402


import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from areal_tpu.api.config import (  # noqa: E402
    MeshConfig,
    MicroBatchSpec,
    NormConfig,
    OptimizerConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec  # noqa: E402
from areal_tpu.core.dist_rollout import DistRolloutCoordinator  # noqa: E402
from areal_tpu.engine.ppo import JaxPPOActor  # noqa: E402
from areal_tpu.models.model_config import tiny_config  # noqa: E402
from areal_tpu.parallel import distributed  # noqa: E402


class _FakeRollout:
    """Stands in for the inference engine on the head process."""

    def __init__(self, batch):
        self._batch = batch
        self.calls = 0

    def rollout_batch(self, data, **kw):
        self.calls += 1
        return self._batch


def main():
    distributed.init_distributed()
    pid = jax.process_index()
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    model_cfg = tiny_config(
        vocab_size=128,
        hidden_size=64,
        num_heads=4,
        num_kv_heads=2,
        qkv_bias=True,
        dtype="float32",
        hf_architecture="Qwen2ForCausalLM",
    )
    cfg = PPOActorConfig(
        experiment_name="mp",
        trial_name="mp",
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        gradient_checkpointing=False,
        mesh=MeshConfig(
            data_parallel_size=2, fsdp_parallel_size=2, tensor_parallel_size=2
        ),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
        pack_length_quantum=64,
        max_pack_length=256,
        group_size=2,
        ppo_n_minibatches=1,
        use_decoupled_loss=True,
        adv_norm=NormConfig(mean_level="group", std_level="group", group_size=2),
    )
    actor = JaxPPOActor(cfg, model_config=model_cfg)
    actor.initialize(ft_spec=FinetuneSpec(1, 64, 4))

    # head-only rollout: only process 0 "contacts the server"; the batch
    # reaches everyone via the coordinator broadcast
    rng = np.random.default_rng(7)
    B, L = 8, 48
    head_batch = None
    if distributed.is_head():
        lens = rng.integers(24, L, B)
        am = np.zeros((B, L), bool)
        lm = np.zeros((B, L), np.float32)
        for i, n in enumerate(lens):
            am[i, :n] = True
            lm[i, n // 2 : n] = 1.0
        head_batch = {
            "input_ids": rng.integers(0, 128, (B, L)).astype(np.int32) * am,
            "attention_mask": am,
            "loss_mask": lm,
            "logprobs": (rng.normal(-1, 0.1, (B, L)) * am).astype(np.float32),
            "rewards": rng.integers(0, 2, B).astype(np.float32),
            "versions": np.zeros((B, L), np.int32),
        }
    fake = _FakeRollout(head_batch)
    coord = DistRolloutCoordinator(fake)
    batch = coord.rollout_batch([{}] * B)
    assert fake.calls == (1 if pid == 0 else 0)

    # exercises the multi-process forward path (row-sharded output must be
    # replicated before the host reads it)
    batch["prox_logp"] = actor.compute_logp(batch)
    actor.compute_advantages(batch)
    for step in range(2):
        stats = actor.ppo_update(batch)
        print(
            f"RESULT proc={pid} step={step} "
            f"loss={stats[0]['loss']:.6f} gn={stats[0]['grad_norm']:.6f}",
            flush=True,
        )
    print(f"DONE proc={pid}", flush=True)


if __name__ == "__main__":
    main()
