"""Killable recover trainer: the subprocess half of tests/test_recover_e2e.py.

A miniature but REAL async training loop (tiny JaxLMEngine + RemoteJaxEngine
executor against the parent's FakeGenServer) wearing the full ISSUE-15
recovery harness: config-fingerprinted RecoverHandler, per-step atomic
generation dumps, disk weight publishes, fault points.  The parent SIGKILLs
it mid-run (via `kill_trainer_at_step` or `AREAL_FAULT_POINTS=recover_mid_
dump...`), relaunches it with AREAL_RUN_ID incremented, and asserts step
continuity + ledger invariants + the stitched lifecycle trace from the
artifacts this process leaves behind.

Env contract (all paths under the parent's tmpdir):
  AREAL_FAKE_SERVER_ADDR  host:port of the parent-owned fake gen server
  AREAL_RUN_ID            0 for the first launch, +1 per relaunch
  RECOVER_FILEROOT        RecoverConfig.fileroot (checkpoints + recover/)
  RECOVER_STEPS           total global steps the run should reach
  RECOVER_KILL_AT_STEP    optional: SIGKILL self at the END of this step
  AREAL_FAULT_POINTS      optional: e.g. "recover_mid_dump@2:kill"
  RECOVER_STEPS_LOG       steps.jsonl appended one line per completed step
  RECOVER_EVENTS_PATH     telemetry events JSONL, rewritten every step so
                          it survives the SIGKILL
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=1"
    ).strip()

import jax  # noqa: E402


import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from areal_tpu.api.config import (  # noqa: E402
    GenerationHyperparameters,
    InferenceEngineConfig,
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    RecoverConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import (  # noqa: E402
    FinetuneSpec,
    StepInfo,
    WeightUpdateMeta,
)
from areal_tpu.engine.jax_remote import RemoteJaxEngine  # noqa: E402
from areal_tpu.engine.sft import JaxLMEngine  # noqa: E402
from areal_tpu.models.model_config import tiny_config  # noqa: E402
from areal_tpu.utils import telemetry  # noqa: E402
from areal_tpu.utils.dataloader import StatefulDataLoader  # noqa: E402
from areal_tpu.utils.faults import fault_point, kill_trainer_at_step  # noqa: E402
from areal_tpu.utils.recover import (  # noqa: E402
    RecoverHandler,
    check_if_recover,
    config_fingerprint,
)
from areal_tpu.workflow.rlvr import RLVRWorkflow  # noqa: E402

BATCH_SIZE = 4


def _reward(prompt, completion, prompt_ids, completion_ids, **kw):
    return float(len(completion_ids))


def main():
    telemetry.set_enabled(True)
    run_id = int(os.environ.get("AREAL_RUN_ID", 0))
    fileroot = os.environ["RECOVER_FILEROOT"]
    total_steps = int(os.environ["RECOVER_STEPS"])
    kill_at = int(os.environ.get("RECOVER_KILL_AT_STEP", -1))
    steps_log = os.environ["RECOVER_STEPS_LOG"]
    events_path = os.environ["RECOVER_EVENTS_PATH"]

    engine = JaxLMEngine(
        TrainEngineConfig(
            experiment_name="recover-e2e", trial_name="t",
            init_from_scratch=True, dtype="float32",
            gradient_checkpointing=False, mesh=MeshConfig(),
            mb_spec=MicroBatchSpec(), pack_length_quantum=16,
            optimizer=OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0),
        ),
        model_config=tiny_config(vocab_size=128, qkv_bias=True,
                                 hf_architecture="Qwen2ForCausalLM"),
    )
    engine.initialize(ft_spec=FinetuneSpec(1, 64, BATCH_SIZE))

    client = RemoteJaxEngine(InferenceEngineConfig(
        experiment_name="recover-e2e", trial_name="t",
        consumer_batch_size=BATCH_SIZE,
        max_concurrent_rollouts=BATCH_SIZE * 2,
        max_head_offpolicyness=4,
        request_timeout=30,
    ))
    client.initialize(addr=os.environ["AREAL_FAKE_SERVER_ADDR"])

    meta = WeightUpdateMeta.from_disk("recover-e2e", "t", fileroot)
    dataset = [{"input_ids": [i % 32], "query_id": str(i)} for i in range(64)]
    dataloader = StatefulDataLoader(dataset, batch_size=BATCH_SIZE, seed=0)
    workflow = RLVRWorkflow(
        reward_fn=_reward,
        gconfig=GenerationHyperparameters(max_new_tokens=8),
    )

    rcfg = RecoverConfig(mode="fault", experiment_name="recover-e2e",
                         trial_name="t", fileroot=fileroot)
    recover = RecoverHandler(rcfg, fingerprint=config_fingerprint(
        {"model": "tiny128", "batch_size": BATCH_SIZE, "lr": 1e-2}
    ))
    start_step = 0
    if check_if_recover(rcfg, run_id=run_id):
        info = recover.load(
            engine,
            dataloader=dataloader,
            inference_engine=client,
            weight_update_meta=meta,
        )
        if info is not None:
            start_step = info.recover_start.global_step

    if kill_at >= start_step:
        kill_trainer_at_step(kill_at, start_step)

    try:
        for global_step in range(start_step, total_steps):
            batch = client.prepare_batch(dataloader, workflow=workflow)
            engine.train_lm({
                "input_ids": np.asarray(batch["input_ids"]),
                "attention_mask": np.asarray(batch["attention_mask"]),
                "loss_mask": np.asarray(batch["loss_mask"], np.float32),
            })
            version = global_step + 1
            engine.set_version(version)
            engine.update_weights(meta)
            client.update_weights(meta)
            client.set_version(version)

            step_info = StepInfo(
                epoch=0, epoch_step=global_step, global_step=global_step,
                steps_per_epoch=total_steps,
            )
            recover.dump(engine, step_info, dataloader=dataloader,
                         inference_engine=client)

            stat = client.executor.staleness_manager.get_stats()
            line = {
                "run_id": run_id,
                "global_step": global_step,
                "version": version,
                "ledger": {
                    "submitted": stat.submitted, "accepted": stat.accepted,
                    "rejected": stat.rejected, "running": stat.running,
                },
                "ledger_ok": (
                    stat.submitted
                    == stat.accepted + stat.rejected + stat.running
                    and stat.running >= 0
                ),
            }
            with open(steps_log, "a") as f:
                f.write(json.dumps(line) + "\n")
                f.flush()
                os.fsync(f.fileno())
            # rewrite (not append) the full ring each step: the file must be
            # intact at whatever step the SIGKILL lands
            telemetry.EVENTS.dump_jsonl(events_path)
            print(f"run{run_id} step {global_step} done", flush=True)
            fault_point("train_step")
    finally:
        client.destroy()
    print(f"DONE run{run_id}", flush=True)


if __name__ == "__main__":
    main()
