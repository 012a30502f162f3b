"""End-to-end GRPO slice: real generation server + RemoteJaxEngine client +
async prepare_batch + PPO actor + DISK weight sync, for multiple steps on a
tiny model (the reference's test_examples.py smoke, without subprocesses).

Also validates the example config parses into GRPOConfig."""

import asyncio
import os
import threading
import time

import numpy as np
import pytest
from aiohttp import web

from areal_tpu.api.config import (
    GRPOConfig,
    GenerationHyperparameters,
    InferenceEngineConfig,
    MeshConfig,
    MicroBatchSpec,
    NormConfig,
    OptimizerConfig,
    PPOActorConfig,
    load_expr_config,
)
from areal_tpu.api.io_struct import FinetuneSpec, WeightUpdateMeta
from areal_tpu.engine.jax_remote import RemoteJaxEngine
from areal_tpu.engine.ppo import JaxPPOActor
from areal_tpu.gen.engine import GenEngine
from areal_tpu.gen.server import GenServer
from areal_tpu.models import init_params
from areal_tpu.models.hf import save_hf_checkpoint
from areal_tpu.models.model_config import tiny_config
from areal_tpu.utils import network
from areal_tpu.utils.dataloader import StatefulDataLoader
from areal_tpu.workflow.rlvr import RLVRWorkflow

CFG = tiny_config(vocab_size=89, qkv_bias=True, hf_architecture="Qwen2ForCausalLM",
                  eos_token_id=None)


def _token7_reward(prompt, completion, prompt_ids, completion_ids, **kw):
    """Module-level: reward fns run in a process pool and must pickle."""
    return float(7 in completion_ids)


def test_example_config_parses():
    cfg, _ = load_expr_config(
        ["--config", "examples/math/gsm8k_grpo.yaml", "actor.optimizer.lr=2e-6"],
        GRPOConfig,
    )
    assert cfg.actor.optimizer.lr == 2e-6
    assert cfg.gconfig.n_samples == 4
    assert cfg.actor.experiment_name == cfg.experiment_name  # propagated


def test_grpo_end_to_end_with_disk_weight_sync(tmp_path):
    import jax

    # initial checkpoint on disk; BOTH sides load it
    ckpt0 = tmp_path / "init"
    params = init_params(CFG, jax.random.PRNGKey(0))
    save_hf_checkpoint(params, CFG, str(ckpt0), save_dtype="float32")

    engine = GenEngine(CFG.replace(dtype="float32"), model_path=str(ckpt0),
                       n_slots=4, max_seq_len=96, prompt_bucket=16,
                       decode_chunk=4)
    server = GenServer(engine)
    server.start()
    port = network.find_free_port()
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.TCPSite(runner, "127.0.0.1", port).start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    import urllib.request

    for _ in range(100):
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=1)
            break
        except Exception:
            time.sleep(0.1)

    rollout = RemoteJaxEngine(InferenceEngineConfig(
        experiment_name="e2e", trial_name="t", consumer_batch_size=4,
        max_concurrent_rollouts=8, request_timeout=60,
        max_head_offpolicyness=2,
    ))
    rollout.initialize(addr=f"127.0.0.1:{port}")

    actor = JaxPPOActor(
        PPOActorConfig(
            experiment_name="e2e", trial_name="t", path=str(ckpt0),
            dtype="float32", gradient_checkpointing=False,
            mesh=MeshConfig(), mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
            pack_length_quantum=32, max_pack_length=96,
            group_size=2, ppo_n_minibatches=1,
            use_decoupled_loss=True, recompute_logprob=True,
            adv_norm=NormConfig(mean_level="group", std_level="group", group_size=2),
        ),
    )
    actor.initialize(ft_spec=FinetuneSpec(1, 16, 4))

    from areal_tpu.api.reward import prewarm_reward_pool

    prewarm_reward_pool()
    # reward: 1 if completion contains token 7
    wf = RLVRWorkflow(
        reward_fn=_token7_reward,
        gconfig=GenerationHyperparameters(n_samples=2, max_new_tokens=8),
    )
    rng = np.random.default_rng(0)
    dataset = [{"input_ids": rng.integers(0, 89, 5).tolist(),
                "query_id": str(i)} for i in range(16)]
    dataloader = StatefulDataLoader(dataset, batch_size=4, seed=0)
    weight_dir = tmp_path / "updates"

    try:
        for step in range(3):
            batch = rollout.prepare_batch(dataloader, workflow=wf)
            assert batch["input_ids"].shape[0] >= 4
            assert "rewards" in batch and "versions" in batch

            batch["prox_logp"] = actor.compute_logp(batch)
            actor.compute_advantages(batch)
            stats = actor.ppo_update(batch)
            assert np.isfinite(stats[-1]["loss"])

            # disk weight sync: trainer dumps, server reloads, versions bump
            meta = WeightUpdateMeta(
                type="disk", path=str(weight_dir),
                experiment_name="e2e", trial_name="t",
            )
            rollout.pause()
            actor.set_version(step + 1)
            actor.update_weights(meta)
            rollout.update_weights(meta)
            rollout.set_version(step + 1)
            rollout.resume()
            assert engine.version >= 1
        # staleness accounting let 3 consumer batches through
        assert rollout.get_version() == 3
    finally:
        rollout.destroy()
        server.shutdown.set()
        loop.call_soon_threadsafe(loop.stop)


def test_grpo_transfer_weight_sync(tmp_path):
    """Transfer (non-disk) weight sync: trainer streams bf16 chunks over
    /update_weights_chunk and commits (VERDICT round-1 next-step #4).
    Reports both paths' update latency."""
    import jax

    from areal_tpu.utils import name_resolve, names

    ckpt0 = tmp_path / "init"
    params = init_params(CFG, jax.random.PRNGKey(0))
    save_hf_checkpoint(params, CFG, str(ckpt0), save_dtype="float32")

    engine = GenEngine(CFG.replace(dtype="float32"), model_path=str(ckpt0),
                       n_slots=4, max_seq_len=96, prompt_bucket=16,
                       decode_chunk=4)
    server = GenServer(engine)
    server.start()
    port = network.find_free_port()
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.TCPSite(runner, "127.0.0.1", port).start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    import urllib.request

    for _ in range(100):
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=1)
            break
        except Exception:
            time.sleep(0.1)

    # register for trainer-side discovery (the launcher's job in real runs)
    name_resolve.add(
        names.gen_server("e2e-tr", "t", "0"), f"127.0.0.1:{port}", replace=True
    )

    actor = JaxPPOActor(
        PPOActorConfig(
            experiment_name="e2e-tr", trial_name="t", path=str(ckpt0),
            dtype="float32", gradient_checkpointing=False,
            mesh=MeshConfig(), mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
            pack_length_quantum=32, max_pack_length=96,
            group_size=2, ppo_n_minibatches=1,
        ),
    )
    actor.initialize(ft_spec=FinetuneSpec(1, 16, 4))

    try:
        # --- transfer path: chunk small enough to force multi-part arrays
        meta_t = WeightUpdateMeta.from_transfer("e2e-tr", "t", chunk_mb=1,
                                        live_commit=False)
        actor.set_version(1)
        t0 = time.perf_counter()
        actor.update_weights(meta_t)
        dt_transfer = time.perf_counter() - t0
        assert engine.version == 1

        # server now runs the trainer's weights: greedy outputs must match a
        # local engine fed the same params (round-trip integrity)
        local = GenEngine(CFG.replace(dtype="float32"),
                          params=actor._host_params(), n_slots=1,
                          max_seq_len=96, prompt_bucket=16)
        from areal_tpu.gen.engine import GenRequest

        prompt = [3, 1, 4, 1, 5]
        r_local = GenRequest(rid="l", input_ids=list(prompt),
                             max_new_tokens=6, temperature=0.0)
        local.generate_blocking([r_local])
        import json
        import urllib.request as rq

        req = rq.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({
                "rid": "r", "input_ids": prompt,
                "sampling_params": {"max_new_tokens": 6, "temperature": 0.0},
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        remote = json.loads(rq.urlopen(req, timeout=60).read())
        assert remote["output_tokens"] == r_local.output_tokens

        # --- disk path for latency comparison (versioned atomic dirs)
        weight_dir = tmp_path / "updates"
        weight_dir.mkdir()
        meta_d = WeightUpdateMeta(type="disk", path=str(weight_dir),
                                  experiment_name="e2e-tr", trial_name="t")
        actor.set_version(2)
        t0 = time.perf_counter()
        actor.update_weights(meta_d)
        dt_disk_write = time.perf_counter() - t0
        assert (weight_dir / "v2").is_dir()
        v = engine.load_weights(path=str(weight_dir), version=2)
        assert v == 2
        print(f"update latency: transfer={dt_transfer*1e3:.0f}ms "
              f"disk_write={dt_disk_write*1e3:.0f}ms")
    finally:
        server.shutdown.set()
        loop.call_soon_threadsafe(loop.stop)


def test_staged_weight_sync_splits_push_from_commit(tmp_path):
    """stage_weights streams chunks while the server is un-paused and does
    NOT swap weights; the later update_weights commit is the only part
    that needs the pause window."""
    import urllib.request

    import jax

    from areal_tpu.utils import name_resolve, names

    ckpt0 = tmp_path / "init"
    params = init_params(CFG, jax.random.PRNGKey(0))
    save_hf_checkpoint(params, CFG, str(ckpt0), save_dtype="float32")
    engine = GenEngine(CFG.replace(dtype="float32"), model_path=str(ckpt0),
                       n_slots=4, max_seq_len=96, prompt_bucket=16)
    server = GenServer(engine)
    server.start()
    port = network.find_free_port()
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.app())
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.TCPSite(runner, "127.0.0.1", port).start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    for _ in range(100):
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=1)
            break
        except Exception:
            time.sleep(0.1)
    name_resolve.add(
        names.gen_server("e2e-st", "t", "0"), f"127.0.0.1:{port}", replace=True
    )
    actor = JaxPPOActor(
        PPOActorConfig(
            experiment_name="e2e-st", trial_name="t", path=str(ckpt0),
            dtype="float32", gradient_checkpointing=False,
            mesh=MeshConfig(), mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=5e-3, warmup_steps_proportion=0.0),
            pack_length_quantum=32, max_pack_length=96,
            group_size=2, ppo_n_minibatches=1,
        ),
    )
    actor.initialize(ft_spec=FinetuneSpec(1, 16, 4))
    try:
        meta = WeightUpdateMeta.from_transfer("e2e-st", "t", chunk_mb=1,
                                      live_commit=False)
        actor.set_version(1)
        actor.stage_weights(meta)
        # staged but NOT swapped: server still serves version 0 un-paused.
        # Staging now goes all the way to DEVICE (the standby tree), so the
        # later commit is a pointer swap — the chunk buffer is already
        # drained by the `prepare` message.
        assert engine.version == 0
        assert engine.has_standby and engine.staged_version == 1
        assert not server._chunk_buf
        assert not server.paused.is_set()
        t0 = time.perf_counter()
        actor.update_weights(meta)  # commit only
        commit_s = time.perf_counter() - t0
        assert engine.version == 1
        assert not engine.has_standby  # consumed by the commit
        assert engine.last_pause_s <= commit_s
        # staged state is single-use: a second update re-pushes
        actor.set_version(2)
        actor.update_weights(meta)
        assert engine.version == 2
        print(f"staged commit: {commit_s*1e3:.0f}ms")

        # disk path staging: snapshot written before publish
        weight_dir = tmp_path / "updates"
        weight_dir.mkdir()
        meta_d = WeightUpdateMeta(type="disk", path=str(weight_dir),
                                  experiment_name="e2e-st", trial_name="t")
        actor.set_version(3)
        actor.stage_weights(meta_d)
        assert (weight_dir / "v3").is_dir()
        key = names.update_weights_from_disk("e2e-st", "t", 3)
        try:
            name_resolve.get(key)
            raise AssertionError("version published before update_weights")
        except name_resolve.NameEntryNotFoundError:
            pass
        actor.update_weights(meta_d)
        assert name_resolve.get(key)
    finally:
        server.shutdown.set()
        loop.call_soon_threadsafe(loop.stop)
