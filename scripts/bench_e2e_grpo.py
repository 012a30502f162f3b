"""End-to-end GRPO benchmark: async vs sync, trajectories/sec/chip.

VERDICT r3 next-step #1 (second half) — THE system's primary metric
(BASELINE.md: "async GRPO trajectories/sec/chip").  The REAL loop runs
on the chip: generation engine + rollout workflows + reward pool + PPO
trainer + per-step weight publish, in two modes over the same workload:

- **sync**: rollout_batch (generate-all, then train, then publish) — the
  classic alternating loop;
- **async**: WorkflowExecutor.prepare_batch keeps the rollout pipeline
  saturated under the staleness gate (max_head_offpolicyness) while the
  trainer consumes; weight publishes interrupt generation mid-flight and
  clients resume with accumulated tokens (the interruptible-generation
  machinery, blog/AReaL_v0_3.md:203-207).

Single-chip regime: trainer and serving engine share the chip in one
process (0.6B model — both fit), weights hand over in memory.  The async
win measured here comes from pipeline overlap (host-side scheduling,
reward computation, batch assembly, straggler absorption), not from
disaggregated hardware — the multi-host deployment adds that on top.

Prints ONE JSON line:
  {"sync": {...}, "async": {...},
   "async_over_sync_trajs_per_sec": R, "pause_window_s": {...}}
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.obs.trace import dist_summary  # noqa: E402 (stdlib-only)


class _LatencyRecorder:
    """Collects per-request client latencies (ModelResponse.latency /
    .ttft) across a measured mode so the bench reports p50/p99
    distributions instead of single-number means (ISSUE 14)."""

    def __init__(self):
        self.samples = []
        self._mark = 0

    def reset(self):
        self.samples = []
        self._mark = 0

    def mark(self):
        # Warmup boundary: prefer samples completed after this point.  The
        # pre-mark ones stay as a fallback — prepare_batch keeps batches in
        # flight, so a short smoke run can consume only episodes whose
        # generation finished during warmup, and a destructive reset here
        # would leave the measured window with zero samples.
        self._mark = len(self.samples)

    def record(self, resp):
        self.samples.append((
            float(resp.latency),
            float(resp.ttft),
            int(resp.output_len),
        ))

    def summary(self):
        post = self.samples[self._mark:]
        use = post or self.samples
        if not use:
            return None
        e2e = [s[0] for s in use if s[0] != float("inf")]
        ttft = [s[1] for s in use if s[1] != float("inf")]
        itl = [
            (lat - tf) / (n - 1)
            for lat, tf, n in use
            if lat != float("inf") and tf != float("inf") and n > 1
        ]
        return {
            "n": len(use),
            "includes_warmup": not post,
            "e2e_s": dist_summary(e2e),
            "ttft_s": dist_summary(ttft),
            "inter_token_s": dist_summary(itl),
        }


class _RecordingEngine:
    """Transparent engine proxy: forwards everything, taps agenerate."""

    def __init__(self, inner, recorder):
        self._inner = inner
        self._recorder = recorder

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def agenerate(self, req):
        resp = await self._inner.agenerate(req)
        self._recorder.record(resp)
        return resp


class _RecordingWorkflow:
    """Workflow wrapper interposing the recording engine.  Works for
    every transport x mode combination because both WorkflowExecutor
    and rollout_batch drive episodes through
    ``workflow.arun_episode(engine, data)``."""

    def __init__(self, inner, recorder):
        self._inner = inner
        self._recorder = recorder

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def arun_episode(self, engine, data):
        return await self._inner.arun_episode(
            _RecordingEngine(engine, self._recorder), data)


def _reward_any_even(prompt, completions, prompt_ids, completion_ids, **kw):
    """Module-level so the reward process pool can pickle it."""
    return float(any(t % 2 == 0 for t in completion_ids))


def _reward_mt(prompt, completions, prompt_ids, completion_ids, **kw):
    """Multi-turn grader: ~1/3 of turns "solve" the task, so episodes span
    1..max_turns turns — the variable-horizon agentic regime."""
    return float(sum(completion_ids) % 3 == 0)


class _FakeTokenizer:
    """Just enough surface for MultiTurnWorkflow on synthetic token data."""

    def decode(self, tokens):
        return " ".join(str(t) for t in tokens)

    def encode(self, text, add_special_tokens=False):
        return [3] * 6  # fixed-size feedback suffix

    def apply_chat_template(self, messages, add_generation_prompt=True,
                            tokenize=True):
        raise NotImplementedError("bench feeds raw input_ids")


def _make_parts(model_scale: str, n_slots: int, max_seq_len: int,
                group_size: int, batch_norm: bool = False,
                serving_engine: bool = True, share_prefix: bool = True,
                layer_group_size: int = 1, remat_policy: str = "full",
                lm_head_chunk: int = 0, num_layers: int = 0):
    import jax

    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.colocated import ColocatedEngine
    from areal_tpu.engine.ppo import JaxPPOActor
    from areal_tpu.models.model_config import qwen2_0p6b_ctx, tiny_config

    if model_scale == "0p6b":
        cfg = qwen2_0p6b_ctx()
    else:  # tiny smoke mode for CPU validation
        cfg = tiny_config(vocab_size=512, qkv_bias=True,
                          hf_architecture="Qwen2ForCausalLM")
    cfg = cfg.replace(eos_token_id=None)
    if num_layers:
        # depth override so the two-level scan A/B can group tiny (2-layer
        # default) models: --num-layers 4 --layer-group-size 4
        cfg = cfg.replace(num_layers=num_layers)

    actor = JaxPPOActor(
        PPOActorConfig(
            experiment_name="e2e-bench", trial_name="b",
            init_from_scratch=True,
            dtype="bfloat16" if model_scale == "0p6b" else "float32",
            param_dtype="bfloat16" if model_scale == "0p6b" else "float32",
            gradient_checkpointing=True,
            remat_policy=remat_policy,
            layer_group_size=layer_group_size,
            lm_head_chunk=lm_head_chunk,
            mesh=MeshConfig(),
            mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=1e-6, warmup_steps_proportion=0.0),
            pack_length_quantum=256,
            max_pack_length=max_seq_len,
            group_size=group_size,
            ppo_n_minibatches=1,
            use_decoupled_loss=True,
            recompute_logprob=True,
            async_stats=True,
            adv_norm=(
                # multi-turn episodes yield ONE trajectory each: normalise
                # over the batch, not fixed-size groups
                NormConfig(mean_level="batch", std_level="batch")
                if batch_norm
                else NormConfig(mean_level="group", std_level="group",
                                group_size=group_size)
            ),
        ),
        model_config=cfg.replace(
            dtype="bfloat16" if model_scale == "0p6b" else "float32",
            param_dtype="bfloat16" if model_scale == "0p6b" else "float32",
        ),
    )
    actor.initialize(ft_spec=FinetuneSpec(1, 4096, 8))

    if not serving_engine:  # remote transport builds its own GenServer
        return actor, None, cfg
    serving = ColocatedEngine(
        cfg.replace(
            dtype="bfloat16" if model_scale == "0p6b" else "float32",
            param_dtype="bfloat16" if model_scale == "0p6b" else "float32",
            remat=False,
        ),
        params=actor._export_params(),
        n_slots=n_slots,
        max_seq_len=max_seq_len,
        prompt_bucket=128,
        decode_chunk=8,
        share_prefix=share_prefix,
    )
    return actor, serving, cfg


def _make_remote_parts(args, actor, cfg):
    """The REAL fleet slice on one chip: a GenServer over HTTP (in-process
    aiohttp thread — two OS processes cannot share the TPU) driven by
    RemoteJaxEngine, with weight publishes streamed as binary chunks +
    device-staged + committed over /update_weights_chunk — the transfer
    choreography the disaggregated deployment uses
    (VERDICT r4 #2: the fleet path had integration tests but no
    trajectories/sec figure)."""
    import asyncio
    import threading

    from aiohttp import web

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.gen.server import GenServer
    from areal_tpu.utils import network

    dtype = "bfloat16" if args.model == "0p6b" else "float32"
    engine = GenEngine(
        cfg.replace(dtype=dtype, param_dtype=dtype, remat=False),
        params=actor._export_params(),
        n_slots=args.n_slots,
        max_seq_len=args.max_seq_len,
        prompt_bucket=128,
        decode_chunk=8,
        share_prefix=args.share_prefix == "on",
    )
    server = GenServer(engine)
    server.start()
    port = network.find_free_port()
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    import urllib.request

    deadline = time.perf_counter() + 30
    while time.perf_counter() < deadline:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=1
            )
            break
        except Exception:
            time.sleep(0.1)
    else:
        raise RuntimeError("bench GenServer did not come up")

    addr = f"127.0.0.1:{port}"
    os.environ["AREAL_LLM_SERVER_ADDRS"] = addr

    def stop():
        server.shutdown.set()
        # park the device-worker before interpreter teardown starts
        # dismantling XLA under its feet (C++ abort at exit otherwise)
        server.worker.join(timeout=10)
        loop.call_soon_threadsafe(loop.stop)

    return engine, server, addr, stop


def _measure_loop(mode: str, actor, get_batch, publish, steps: int,
                  warmup: int, label: str = "", recorder=None):
    """The shared timed region of every transport x mode combination:
    rollout -> train -> version bump -> publish, with warmup reset and the
    same stats dict — so the colocated/remote A/B can never silently
    measure different things."""
    trajs = tokens = span_trajs = 0
    pauses = []
    rewards = []
    step_stats = []  # per-step PendingTrainStats, materialised after flush
    t_start = None
    if recorder is not None:
        recorder.reset()
    for step in range(warmup + steps):
        if step == warmup:
            import jax

            jax.block_until_ready(actor.params)
            trajs = tokens = span_trajs = 0
            pauses = []
            rewards = []
            if recorder is not None:
                recorder.mark()  # warmup requests must not skew p99s
            t_start = time.perf_counter()
        batch = get_batch()
        trajs += int(np.asarray(batch["attention_mask"]).shape[0])
        tokens += _batch_tokens(batch)
        span_trajs += _version_span_trajectories(batch)
        rewards.append(float(np.asarray(batch["rewards"]).mean()))
        step_stats.append(_train_consume(actor, batch))
        pauses.append(publish())
        print(f"{label}{mode} step {step}: trajs={trajs} tokens={tokens}",
              file=sys.stderr, flush=True)
    import jax

    actor.flush_stats()
    jax.block_until_ready(actor.params)
    wall = time.perf_counter() - t_start
    latency = recorder.summary() if recorder is not None else None
    # per-step training trajectory INCLUDING warmup steps (every step moves
    # the params, so this is the full optimisation path) — the CI two-level-
    # scan A/B gates on these being identical across layer_group_size
    # values.  Group-centred advantages make the step-0 PG loss exactly 0
    # regardless of params, so entropy/new_logp (which see the real forward
    # pass) ride along as the non-degenerate signal.
    def _traj(key):
        return [round(sum(float(st[key]) for st in step), 8)
                for step in step_stats]
    return {
        "loss_trajectory": _traj("loss"),
        "entropy_trajectory": _traj("entropy"),
        "new_logp_trajectory": _traj("new_logp"),
        "latency": latency,
        "steps": steps,
        "trajectories": trajs,
        # trajectories generated across a weight publish: their output
        # tokens carry more than one policy version
        "version_span_trajectories": span_trajs,
        "effective_tokens": tokens,
        "wall_s": round(wall, 2),
        "trajs_per_sec_per_chip": round(trajs / wall, 3),
        "effective_tokens_per_sec_per_chip": round(tokens / wall, 1),
        "pause_window_s_mean": round(float(np.mean(pauses)), 3),
        # the quality half's raw signal (meaningful for --dataset
        # gsm8k-synth, where the reward is the real math grader)
        "reward_mean": round(float(np.mean(rewards)), 4),
    }


def run_mode_remote(mode: str, actor, client, server_engine, meta, workflow,
                    dataset, batch_size: int, steps: int, warmup: int = 1,
                    recorder=None):
    """Fleet-path counterpart of run_mode: rollouts over HTTP via the
    client's executor, publishes via the trainer's stage+commit transfer
    choreography (live or abort per meta.live_commit)."""
    from areal_tpu.utils.dataloader import StatefulDataLoader

    dataloader = StatefulDataLoader(dataset, batch_size=batch_size, seed=0)
    data_iter = iter(np.random.default_rng(1).permutation(len(dataset)))

    def get_batch():
        if mode == "async":
            return client.prepare_batch(dataloader, workflow=workflow)
        items = [dataset[int(next(data_iter)) % len(dataset)]
                 for _ in range(batch_size)]
        return client.rollout_batch(items, workflow=workflow)

    state = {"version": server_engine.version}

    def publish():
        # the fleet publish: stream + device-stage while generation keeps
        # running, then commit (live = no abort; abort mode exercises the
        # interruption-resume storm)
        state["version"] += 1
        actor.set_version(state["version"])
        actor.stage_weights(meta)
        actor.update_weights(meta)
        client.set_version(state["version"])
        return float(server_engine.last_pause_s)

    return _measure_loop(mode, actor, get_batch, publish, steps, warmup,
                         label="remote ", recorder=recorder)


def run_recoverable(args, actor, client, workflow, dataset):
    """Crash-safe loop (ISSUE 15): per-step atomic recover generations +
    disk weight publishes, resumable across SIGKILL via AREAL_RUN_ID —
    the launchers' relaunch contract, runnable standalone in CI.  Each
    completed step appends one line to ``{recover_dir}/steps.jsonl``
    ({run_id, global_step, version, ledger, ledger_ok}) and rewrites
    ``events_run{run_id}.jsonl``, so a kill at ANY instant leaves enough
    evidence to gate step continuity and ledger invariants on."""
    from areal_tpu.api.config import RecoverConfig
    from areal_tpu.api.io_struct import StepInfo, WeightUpdateMeta
    from areal_tpu.utils import telemetry
    from areal_tpu.utils.dataloader import StatefulDataLoader
    from areal_tpu.utils.faults import (
        arm_fault_point,
        fault_point,
        kill_trainer_at_step,
    )
    from areal_tpu.utils.recover import (
        RecoverHandler,
        check_if_recover,
        config_fingerprint,
    )
    from areal_tpu.utils.shutdown import PreemptionGuard, preempt_exit

    # SIGTERM/SIGINT -> force-dump + RESUME_EXIT_CODE at the step boundary
    guard = PreemptionGuard().install()
    run_id = int(os.environ.get("AREAL_RUN_ID", 0))
    os.makedirs(args.recover_dir, exist_ok=True)
    meta = WeightUpdateMeta.from_disk("e2e-bench", "recover", args.recover_dir)
    rcfg = RecoverConfig(mode="fault", experiment_name="e2e-bench",
                         trial_name="recover", fileroot=args.recover_dir)
    recover = RecoverHandler(rcfg, fingerprint=config_fingerprint({
        "model": args.model, "batch_size": args.batch_size,
        "group_size": args.group_size, "workflow": args.workflow,
        "max_new_tokens": args.max_new_tokens,
    }))
    dataloader = StatefulDataLoader(dataset, batch_size=args.batch_size,
                                    seed=0)
    start_step = 0
    if check_if_recover(rcfg, run_id=run_id):
        info = recover.load(actor, dataloader=dataloader,
                            inference_engine=client,
                            weight_update_meta=meta)
        if info is not None:
            start_step = info.recover_start.global_step
            print(f"recovered: resuming run {run_id} at step {start_step}",
                  file=sys.stderr, flush=True)
    if args.kill_at_step >= start_step:
        kill_trainer_at_step(args.kill_at_step, start_step)
    if args.kill_mid_dump_at_step >= start_step:
        arm_fault_point("recover_mid_dump",
                        at_hit=args.kill_mid_dump_at_step - start_step + 1)

    steps_log = os.path.join(args.recover_dir, "steps.jsonl")
    events_path = os.path.join(args.recover_dir,
                               f"events_run{run_id}.jsonl")
    for global_step in range(start_step, args.steps):
        batch = client.prepare_batch(dataloader, workflow=workflow)
        _train_consume(actor, batch)
        version = global_step + 1
        actor.set_version(version)
        actor.update_weights(meta)  # disk: self-stages snapshot v{version}
        client.update_weights(meta)
        client.set_version(version)
        step_info = StepInfo(epoch=0, epoch_step=global_step,
                             global_step=global_step,
                             steps_per_epoch=args.steps)
        recover.dump(actor, step_info, dataloader=dataloader,
                     inference_engine=client)
        stat = client.executor.staleness_manager.get_stats()
        line = {
            "run_id": run_id,
            "global_step": global_step,
            "version": version,
            "ledger": {
                "submitted": int(stat.submitted),
                "accepted": int(stat.accepted),
                "rejected": int(stat.rejected),
                "running": int(stat.running),
            },
            "ledger_ok": (
                stat.submitted == stat.accepted + stat.rejected + stat.running
                and stat.running >= 0
            ),
        }
        with open(steps_log, "a") as f:
            f.write(json.dumps(line) + "\n")
            f.flush()
            os.fsync(f.fileno())
        if telemetry.is_enabled():
            # rewrite the full ring each step: intact at whatever step the
            # kill lands
            telemetry.EVENTS.dump_jsonl(events_path)
        print(f"recover run{run_id} step {global_step} done "
              f"(version {version})", file=sys.stderr, flush=True)
        if guard.requested:
            # the step just dumped is the resume point: zero steps lost
            preempt_exit(recover, actor, step_info,
                         rollout_engines=(client,),
                         dump_kwargs={"dataloader": dataloader,
                                      "inference_engine": client})
        fault_point("train_step")
    return {
        "run_id": run_id,
        "start_step": start_step,
        "steps_completed": args.steps - start_step,
        "steps_jsonl": steps_log,
        "events_jsonl": events_path,
    }


def _train_consume(actor, batch):
    batch["prox_logp"] = actor.compute_logp(batch)
    actor.compute_advantages(batch)
    stats = actor.ppo_update(batch)
    return stats


def _batch_tokens(batch) -> int:
    return int(np.asarray(batch["attention_mask"]).sum())


def _version_span_trajectories(batch) -> int:
    """Rows whose generated tokens (version >= 0; prompt tokens are -1)
    were sampled under more than one weight version."""
    v = np.asarray(batch["versions"])
    gen = v >= 0
    lo = np.where(gen, v, np.iinfo(v.dtype).max).min(axis=-1)
    hi = np.where(gen, v, -1).max(axis=-1)
    return int((gen.any(axis=-1) & (lo < hi)).sum())


def plan_warm_shapes(args, dataset, actor):
    """Dry-run the packer over sampled step batches to enumerate the
    (rows, row_len) signatures the loop will hit, so warm_shapes can
    AOT-compile them before the timed region (varying rollout lengths
    otherwise recompile INSIDE the loop, which sank the first
    heterogeneous-length run).

    The packing parameters (quantum, max length, rows multiple) are DERIVED
    from the live actor so the planned signatures match what
    `_prepare_rows` (engine/jax_train.py) actually compiles."""
    from areal_tpu.utils.data import pack_into_rows
    from areal_tpu.utils.datapack import round_up_to_bucket

    quantum = actor.config.pack_length_quantum
    max_len = actor.config.max_pack_length
    dp = (actor.mesh.shape["dp"] * actor.mesh.shape["fsdp"]
          * actor.mesh.shape.get("ep", 1))
    rows_multiple = actor.config.mb_spec.n_mbs * dp
    rng = np.random.default_rng(7)
    fb = len(_FakeTokenizer().encode(""))  # feedback suffix length
    shapes = set()
    for _ in range(8 if args.workflow == "rlvr" else 32):
        idx = rng.choice(len(dataset), args.batch_size, replace=False)
        lens = []
        for i in idx:
            if args.workflow == "multi_turn":
                # one trajectory per episode; length grows per retry turn
                t = int(rng.integers(1, args.max_turns + 1))
                lens.append(args.prompt_len + t * args.max_new_tokens
                            + (t - 1) * fb)
            else:
                budget = dataset[int(i)].get("max_new_tokens",
                                             args.max_new_tokens)
                lens.extend([args.prompt_len + budget] * args.group_size)
        row_len = round_up_to_bucket(max(lens), quantum, max_len)
        mask = np.zeros((len(lens), max(lens)), bool)
        for r, n in enumerate(lens):
            mask[r, :n] = True
        rp = pack_into_rows({"attention_mask": mask}, row_len,
                            rows_multiple=rows_multiple,
                            rows_bucket_pow2=True)
        shapes.add((rp.n_rows, row_len))
    return sorted(shapes)


def run_mode(mode: str, actor, serving, workflow, dataset, batch_size: int,
             steps: int, warmup: int = 1, interrupt_publish: bool = False,
             recorder=None):
    """-> {trajs_per_sec, effective_tokens_per_sec, steps, pause_s_mean}"""
    from areal_tpu.api.config import InferenceEngineConfig
    from areal_tpu.core.executor import WorkflowExecutor
    from areal_tpu.utils.dataloader import StatefulDataLoader

    executor = None
    if mode == "async":
        executor = WorkflowExecutor(
            InferenceEngineConfig(
                experiment_name="e2e-bench", trial_name="b",
                consumer_batch_size=batch_size,
                max_concurrent_rollouts=batch_size * 2,
                max_head_offpolicyness=4,
                request_timeout=600,
            ),
            serving,
        )
        executor.initialize()
        dataloader = StatefulDataLoader(dataset, batch_size=batch_size, seed=0)

    data_iter = iter(np.random.default_rng(1).permutation(len(dataset)))

    def get_batch():
        if mode == "async":
            return executor.prepare_batch(dataloader, workflow=workflow)
        items = [dataset[int(next(data_iter)) % len(dataset)]
                 for _ in range(batch_size)]
        return serving.rollout_batch(items, workflow=workflow)

    state = {"version": serving.get_version()}

    def publish():
        # device-to-device handoff: both sides share the chip, so the
        # publish never touches the host (export_device_params); the
        # executor reads the new version via serving.get_version()
        state["version"] += 1
        actor.set_version(state["version"])
        return serving.update_weights_in_memory(
            actor.export_device_params(), state["version"],
            interrupt=interrupt_publish,
        )

    try:
        return _measure_loop(mode, actor, get_batch, publish, steps, warmup,
                             recorder=recorder)
    finally:
        if executor is not None:
            executor.destroy()


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="0p6b", choices=["0p6b", "tiny"])
    p.add_argument("--steps", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--group-size", type=int, default=2)
    p.add_argument("--n-slots", type=int, default=16)
    p.add_argument("--max-seq-len", type=int, default=512)
    p.add_argument("--prompt-len", type=int, default=64)
    p.add_argument("--max-new-tokens", type=int, default=128)
    p.add_argument("--modes", default="sync,async")
    p.add_argument("--layer-group-size", type=int, default=1,
                   help="two-level layer scan: layers per remat group "
                   "(TrainEngineConfig.layer_group_size); must divide the "
                   "model depth, 1 = classic per-layer scan")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots", "save_attn", "save_mlp",
                            "carry_offload"],
                   help="per-group remat rung "
                   "(TrainEngineConfig.remat_policy)")
    p.add_argument("--lm-head-chunk", type=int, default=0,
                   help="fused LM-head vocab chunk width "
                   "(TrainEngineConfig.lm_head_chunk); 0 = 8192")
    p.add_argument("--num-layers", type=int, default=0,
                   help="model depth override (0 = model default) — lets "
                   "the tiny 2-layer CPU config run grouped-scan A/Bs at "
                   "--layer-group-size 4")
    p.add_argument("--warmup", type=int, default=1,
                   help="untimed leading steps; interrupt-publish runs want "
                        "2 so the first post-publish abort storm (whose "
                        "burst admission compiles NEW suffix-prefill "
                        "signatures) stays outside the timed region")
    p.add_argument("--workflow", default="rlvr",
                   choices=["rlvr", "multi_turn"],
                   help="multi_turn = retry-until-correct agentic episodes "
                        "(variable turn count; exercises KV prefix reuse)")
    p.add_argument("--max-turns", type=int, default=3)
    p.add_argument("--len-jitter", type=float, default=0.0,
                   help=">0 gives each prompt a log-uniform generation "
                        "budget in [max_new/(1+j), max_new] — length "
                        "variance a la real math workloads")
    p.add_argument("--publish-mode", default="live",
                   choices=["live", "interrupt", "abort"],
                   help="live = non-aborting swap_weights_live (the "
                        "default everywhere since r5); interrupt/abort "
                        "(synonyms) = abort-and-resume for A/B comparison")
    p.add_argument("--share-prefix", default="on", choices=["on", "off"],
                   help="off = pre-fan-out admission (per-slot retained "
                        "reuse only) for A/B regression runs")
    p.add_argument("--transport", default="colocated",
                   choices=["colocated", "remote"],
                   help="colocated = in-process ColocatedEngine handoff; "
                        "remote = REAL GenServer over HTTP + RemoteJaxEngine "
                        "+ transfer-mode weight publish (the fleet slice)")
    p.add_argument("--chaos", action="store_true",
                   help="mount a seeded FaultProxy (utils/faults.py) "
                        "between the client and the gen server: HTTP 500s, "
                        "latency spikes, and mid-request disconnects replay "
                        "deterministically from --chaos-seed; reports "
                        "goodput + trajectory-loss fraction under fire. "
                        "Requires --transport remote and async-only --modes")
    p.add_argument("--recover-dir", default="",
                   help="run the crash-safe recoverable loop (ISSUE 15) "
                        "instead of the timed A/B: per-step atomic recover "
                        "generations + disk weight publishes under this "
                        "dir, resumable across SIGKILL via AREAL_RUN_ID. "
                        "Requires --transport remote and async-only --modes")
    p.add_argument("--kill-at-step", type=int, default=-1,
                   help="with --recover-dir: SIGKILL self (no flush) at the "
                        "END of this global step — the trainer-kill chaos "
                        "fault (utils/faults.py kill_trainer_at_step)")
    p.add_argument("--kill-mid-dump-at-step", type=int, default=-1,
                   help="with --recover-dir: SIGKILL self INSIDE this "
                        "step's recover dump, between the staging fsync "
                        "and the atomic rename (fault point "
                        "recover_mid_dump) — the torn-checkpoint case")
    p.add_argument("--chaos-seed", type=int, default=0,
                   help="one integer reproduces the exact injected-failure "
                        "sequence (FaultPlan.generate)")
    p.add_argument("--chaos-rate", type=float, default=0.15,
                   help="per-call fault probability in the generated plan")
    p.add_argument("--telemetry-dir", default="",
                   help="enable unified telemetry (utils/telemetry.py) and "
                        "dump events.jsonl + trace.json (Perfetto) + "
                        "metrics.json registry snapshots here; also starts "
                        "a trainer-side /metrics endpoint")
    p.add_argument("--xla-profile-dir", default="",
                   help="wrap the measured mode loop in a jax.profiler "
                        "trace (utils/profiling.py profile_trace)")
    p.add_argument("--dataset", default="random",
                   choices=["random", "gsm8k-synth"],
                   help="random = synthetic token prompts (throughput "
                        "measurement); gsm8k-synth = the synthetic GSM8K "
                        "generator + WordTokenizer + the REAL "
                        "gsm8k_reward_fn (dataset/gsm8k_synth.py) — the "
                        "quality-half workload, learnable rewards included")
    args = p.parse_args()
    interrupt_publish = args.publish_mode in ("interrupt", "abort")
    if args.dataset == "gsm8k-synth" and args.workflow != "rlvr":
        p.error("--dataset gsm8k-synth runs the RLVR workflow (its reward "
                "parses \\boxed{} answers, not multi-turn feedback)")
    if args.chaos:
        if args.transport != "remote":
            p.error("--chaos requires --transport remote (faults are "
                    "injected at the HTTP boundary)")
        if any(m != "async" for m in args.modes.split(",")):
            p.error("--chaos runs async modes only: a sync rollout_batch "
                    "waits for its exact batch, so one lost trajectory "
                    "hangs the step; prepare_batch keeps consuming")
    if args.recover_dir:
        if args.transport != "remote":
            p.error("--recover-dir requires --transport remote (the fleet "
                    "slice: gen server rejoin + pinned disk reload is the "
                    "machinery under test)")
        if any(m != "async" for m in args.modes.split(",")):
            p.error("--recover-dir runs async modes only (the recover "
                    "harness snapshots the executor's staleness ledger)")
        if args.chaos:
            p.error("--recover-dir and --chaos are separate harnesses; "
                    "run them in separate invocations")
    elif args.kill_at_step >= 0 or args.kill_mid_dump_at_step >= 0:
        p.error("--kill-at-step/--kill-mid-dump-at-step require "
                "--recover-dir")
    if args.workflow == "multi_turn" and args.len_jitter > 0:
        # MultiTurnWorkflow generates with its fixed gconfig budget; per-item
        # budgets would be ignored and the result JSON would claim a
        # jittered regime that never ran.  Turn variance already provides
        # the length distribution in this mode.
        p.error("--len-jitter is not supported with --workflow multi_turn")

    import jax

    from areal_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()

    from areal_tpu.utils import telemetry

    train_metrics_port = None
    if args.telemetry_dir:
        # enable BEFORE any engine/workflow is built so lifecycle events
        # from warmup onward land in the log
        os.makedirs(args.telemetry_dir, exist_ok=True)
        telemetry.set_enabled(True)
        _, train_metrics_port = telemetry.start_metrics_server(telemetry.TRAIN)
        print(f"trainer /metrics on :{train_metrics_port}",
              file=sys.stderr, flush=True)
    elif args.recover_dir:
        # the recover harness's step-continuity gate consumes the stitched
        # lifecycle log, so events must flow even without --telemetry-dir
        telemetry.set_enabled(True)

    from areal_tpu.api.config import GenerationHyperparameters
    from areal_tpu.api.reward import prewarm_reward_pool
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    actor, serving, cfg = _make_parts(
        args.model, args.n_slots, args.max_seq_len, args.group_size,
        batch_norm=args.workflow == "multi_turn",
        serving_engine=args.transport == "colocated",
        share_prefix=args.share_prefix == "on",
        layer_group_size=args.layer_group_size,
        remat_policy=args.remat_policy,
        lm_head_chunk=args.lm_head_chunk,
        num_layers=args.num_layers,
    )
    client = server_engine = stop_server = meta = None
    chaos_plan = chaos_proxy = None
    if args.transport == "remote":
        from areal_tpu.api.config import InferenceEngineConfig
        from areal_tpu.api.io_struct import WeightUpdateMeta
        from areal_tpu.engine.jax_remote import RemoteJaxEngine

        server_engine, _server, addr, stop_server = _make_remote_parts(
            args, actor, cfg
        )
        client_addr = addr
        if args.chaos:
            from areal_tpu.utils.faults import FaultPlan, FaultProxy

            # generate() excludes "hang" by default — a held request would
            # stall the run for the full client timeout, which measures the
            # timeout constant, not the failover machinery
            chaos_plan = FaultPlan.generate(
                seed=args.chaos_seed,
                n_calls=args.batch_size * (args.warmup + args.steps) * 8,
                rate=args.chaos_rate,
            )
            chaos_proxy = FaultProxy(addr, chaos_plan)
            client_addr = chaos_proxy.start()
            # the client talks through the proxy; the trainer's transfer
            # publish goes straight to the real server via
            # AREAL_LLM_SERVER_ADDRS (set in _make_remote_parts), so weight
            # chunks are not subject to generation-path faults
            print(f"chaos proxy on {client_addr} -> {addr} "
                  f"(seed={args.chaos_seed}, {len(chaos_plan.plan)} faults "
                  f"planned)", file=sys.stderr, flush=True)
        client = RemoteJaxEngine(InferenceEngineConfig(
            experiment_name="e2e-bench", trial_name="b",
            consumer_batch_size=args.batch_size,
            max_concurrent_rollouts=args.batch_size * 2,
            max_head_offpolicyness=4,
            request_timeout=600,
        ))
        client.initialize(addr=client_addr)
        meta = WeightUpdateMeta.from_transfer(
            "e2e-bench", "b", chunk_mb=64,
            live_commit=not interrupt_publish,
        )
    prewarm_reward_pool()
    if args.workflow == "multi_turn":
        from areal_tpu.workflow.multi_turn import MultiTurnWorkflow

        workflow = MultiTurnWorkflow(
            reward_fn=_reward_mt,
            gconfig=GenerationHyperparameters(
                n_samples=1,
                max_new_tokens=args.max_new_tokens,
                temperature=1.0,
            ),
            tokenizer=_FakeTokenizer(),
            max_turns=args.max_turns,
        )
    elif args.dataset == "gsm8k-synth":
        # the quality-half workload (dataset/gsm8k_synth.py): real word
        # problems through the closed-vocabulary tokenizer, scored by the
        # REAL math reward — rewards are learnable, not coin flips
        from areal_tpu.dataset.gsm8k_synth import (
            WordTokenizer,
            generate_problems,
        )
        from areal_tpu.reward.math_parser import gsm8k_reward_fn

        synth_tok = WordTokenizer()
        assert len(synth_tok) <= cfg.vocab_size, (
            f"model vocab {cfg.vocab_size} < tokenizer {len(synth_tok)}"
        )
        workflow = RLVRWorkflow(
            reward_fn=gsm8k_reward_fn,
            gconfig=GenerationHyperparameters(
                n_samples=args.group_size,
                max_new_tokens=args.max_new_tokens,
                temperature=1.0,
            ),
            tokenizer=synth_tok,
        )
    else:
        workflow = RLVRWorkflow(
            reward_fn=_reward_any_even,
            gconfig=GenerationHyperparameters(
                n_samples=args.group_size,
                max_new_tokens=args.max_new_tokens,
                temperature=1.0,
            ),
        )
    # per-request latency distributions (TTFT / inter-token / e2e) come
    # from a transparent workflow wrapper; transport-agnostic because
    # every episode path funnels through workflow.arun_episode
    recorder = _LatencyRecorder()
    workflow = _RecordingWorkflow(workflow, recorder)
    rng = np.random.default_rng(0)
    dataset = []
    if args.dataset == "gsm8k-synth":
        for prob in generate_problems(256, seed=0):
            dataset.append({
                "input_ids": synth_tok.apply_chat_template(
                    prob["messages"], add_generation_prompt=True
                ),
                "query_id": prob["query_id"],
                "answer": prob["answer"],
            })
        # warm-shape planning sizes rows from args.prompt_len; cover the
        # longest generated problem so the packer's signatures match
        args.prompt_len = max(len(d["input_ids"]) for d in dataset)
    else:
        for i in range(256):
            item = {
                "input_ids": rng.integers(0, cfg.vocab_size,
                                          args.prompt_len).tolist(),
                "query_id": str(i),
            }
            if args.len_jitter > 0:
                # realistic length variance (the reference's math workloads
                # span 1k-31k generated tokens): log-uniform budgets in
                # [max_new/(1+j), max_new].  Sync pays the straggler tail
                # every step; async absorbs it — this is the regime the
                # async design targets.
                lo = args.max_new_tokens / (1.0 + args.len_jitter)
                item["max_new_tokens"] = int(np.exp(
                    rng.uniform(np.log(lo), np.log(args.max_new_tokens))
                ))
            dataset.append(item)
    shapes = plan_warm_shapes(args, dataset, actor)
    print(f"warming {len(shapes)} pack signatures: {shapes}",
          file=sys.stderr, flush=True)
    t_warm = time.perf_counter()
    actor.warm_shapes(shapes)
    warm_s = round(time.perf_counter() - t_warm, 1)
    print(f"warm done in {warm_s}s", file=sys.stderr, flush=True)

    result = {
        "model": args.model,
        "workflow": args.workflow,
        "transport": args.transport,
        "dataset": args.dataset,
        "device_kind": jax.devices()[0].device_kind,
        "batch_size": args.batch_size,
        "group_size": args.group_size,
        "max_new_tokens": args.max_new_tokens,
        "len_jitter": args.len_jitter,
        "publish_mode": args.publish_mode,
        "share_prefix": args.share_prefix,
        # the scan shape actually compiled (ISSUE 20): group size from the
        # post-replace model config, unroll after the loud divisor fallback
        "layer_group_size": int(max(1, actor.model_config.layer_group_size)),
        "effective_scan_unroll": int(
            getattr(actor, "_effective_scan_unroll", 1)),
        "remat_policy": args.remat_policy,
        "lm_head_chunk": args.lm_head_chunk,
        "num_layers": int(actor.model_config.num_layers),
        "warm_shapes": [list(s) for s in shapes],
        "warm_s": warm_s,
    }
    try:
        from contextlib import nullcontext

        prof_ctx = nullcontext()
        if args.xla_profile_dir:
            from areal_tpu.utils.profiling import profile_trace

            prof_ctx = profile_trace(args.xla_profile_dir)
            result["xla_profile_dir"] = args.xla_profile_dir
        with prof_ctx:
            if args.recover_dir:
                result["recover"] = run_recoverable(
                    args, actor, client, workflow, dataset
                )
            else:
                for mode in args.modes.split(","):
                    if args.transport == "remote":
                        result[mode] = run_mode_remote(
                            mode, actor, client, server_engine, meta,
                            workflow, dataset, args.batch_size, args.steps,
                            warmup=args.warmup, recorder=recorder,
                        )
                    else:
                        result[mode] = run_mode(
                            mode, actor, serving, workflow, dataset,
                            args.batch_size, args.steps, warmup=args.warmup,
                            interrupt_publish=interrupt_publish,
                            recorder=recorder,
                        )
        if "sync" in result and "async" in result:
            result["async_over_sync_trajs_per_sec"] = round(
                result["async"]["trajs_per_sec_per_chip"]
                / result["sync"]["trajs_per_sec_per_chip"], 3,
            )
        st = (server_engine if args.transport == "remote"
              else serving.engine).stats
        total_prefill = (st["prefill_tokens"] + st["suffix_tokens"]
                         + st["reused_tokens"] + st["shared_tokens"])
        if args.workflow == "multi_turn":
            # later turns re-prefill only the suffix when the engine still
            # holds the episode's KV prefix (gen/kv_pool.py radix index)
            result["kv_reuse"] = {
                "prefill_tokens": int(st["prefill_tokens"]),
                "suffix_tokens": int(st["suffix_tokens"]),
                "reused_tokens": int(st["reused_tokens"]),
                "reused_fraction": round(
                    st["reused_tokens"] / max(total_prefill, 1), 3
                ),
            }
        if args.group_size > 1:
            # group fan-out prefill: siblings of each GRPO group ride the
            # representative's prefix KV (gen/engine.py cluster fan-out)
            result["shared_prefill"] = {
                "prefill_tokens": int(st["prefill_tokens"]),
                "suffix_tokens": int(st["suffix_tokens"]),
                "shared_tokens": int(st["shared_tokens"]),
                "copy_calls": int(st["copy_calls"]),
                "shared_fraction": round(
                    st["shared_tokens"] / max(total_prefill, 1), 3
                ),
            }
        if args.chaos:
            st = client.executor.staleness_manager.get_stats()
            lost = int(client.executor.lost_trajectories)
            result["chaos"] = {
                "seed": args.chaos_seed,
                "rate": args.chaos_rate,
                "plan_size": len(chaos_plan.plan),
                # the replayable record: same seed -> same sequence
                "injected": [list(t) for t in chaos_plan.injected_log()],
                "lost_trajectories": lost,
                "submitted": int(st.submitted),
                "trajectory_loss_fraction": round(
                    lost / max(1, st.submitted), 4
                ),
            }
        if args.telemetry_dir:
            events_path = os.path.join(args.telemetry_dir, "events.jsonl")
            trace_path = os.path.join(args.telemetry_dir, "trace.json")
            snap_path = os.path.join(args.telemetry_dir, "metrics.json")
            n_events = telemetry.EVENTS.dump_jsonl(events_path)
            telemetry.EVENTS.dump_chrome_trace(trace_path)
            with open(snap_path, "w") as f:
                json.dump({
                    "gen": telemetry.GEN.snapshot(),
                    "train": telemetry.TRAIN.snapshot(),
                    "router": telemetry.ROUTER.snapshot(),
                }, f, indent=2, default=str)
            result["telemetry"] = {
                "dir": args.telemetry_dir,
                "events_jsonl": events_path,
                "chrome_trace": trace_path,
                "metrics_snapshot": snap_path,
                "n_events": n_events,
                "dropped_events": telemetry.EVENTS.dropped,
                "trainer_metrics_port": train_metrics_port,
            }
        # the result line must survive teardown hiccups (stale request
        # callbacks etc.) — print FIRST, clean up after
        print(json.dumps(result))
        sys.stdout.flush()
    finally:
        try:
            if client is not None:
                client.destroy()
            if chaos_proxy is not None:
                chaos_proxy.stop()
            if stop_server is not None:
                stop_server()
            if serving is not None:
                serving.destroy()
        except Exception as e:  # noqa: BLE001 — teardown only
            print(f"teardown: {str(e)[:120]}", file=sys.stderr)


if __name__ == "__main__":
    main()
