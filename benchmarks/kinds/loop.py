"""Kind `loop`: the whole product on one chip.  `WorkflowExecutor` with the
staleness gate, the RLVR workflow, the reward pool, `JaxPPOActor` train and
a live in-memory publish every step, trainer and serving engine colocated
(`scripts/bench_e2e_grpo.py run_mode("async")`, copied: its warm-up reset,
its `block_until_ready` at both ends, its pack signatures compiled ahead).
Every seed of the copied loop (dataset, loader, shape plan) comes from
`--seed`; the trainer draws its own initial weights (`PRNGKey(0)`).
"""

import hashlib
import time

import numpy as np


def make_actor(model_cfg, a, max_seq_len, group_size, dtype):
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.engine.ppo import JaxPPOActor

    return JaxPPOActor(
        PPOActorConfig(
            experiment_name="bench-loop", trial_name="b",
            init_from_scratch=True, dtype=dtype, param_dtype=dtype,
            gradient_checkpointing=True, remat_policy=a["remat_policy"],
            layer_group_size=int(a["layer_group_size"]),
            mesh=MeshConfig(), mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=float(a["lr"]),
                                      warmup_steps_proportion=0.0),
            pack_length_quantum=int(a["pack_length_quantum"]),
            max_pack_length=max_seq_len, group_size=group_size,
            ppo_n_minibatches=1, use_decoupled_loss=True,
            recompute_logprob=True, async_stats=True,
            adv_norm=NormConfig(mean_level="group", std_level="group",
                                group_size=group_size),
        ),
        model_config=model_cfg.replace(dtype=dtype, param_dtype=dtype),
    )


def plan_pack_shapes(actor, dataset, tr, seed):
    """Every (n_sequences, seq_len) signature the packer can produce for a
    step's batch (`bench_e2e_grpo.plan_warm_shapes`, copied): dry-run the
    repo's packer over many sampled step batches."""
    from areal_tpu.utils.data import pack_into_rows
    from areal_tpu.utils.datapack import round_up_to_bucket

    quantum = actor.config.pack_length_quantum
    max_len = actor.config.max_pack_length
    rng = np.random.default_rng([int(tr["size_seed"]), 13])
    P = int(tr["prompt_len"]["value"])
    shapes = set()
    for _ in range(256):
        idx = rng.choice(len(dataset), int(tr["batch_prompts"]), replace=False)
        lens = []
        for i in idx:
            lens.extend([P + dataset[int(i)]["max_new_tokens"]]
                        * int(tr["group_size"]))
        row_len = round_up_to_bucket(max(lens), quantum, max_len)
        mask = np.zeros((len(lens), max(lens)), bool)
        for r, n in enumerate(lens):
            mask[r, :n] = True
        rp = pack_into_rows({"attention_mask": mask}, row_len,
                            rows_multiple=1, rows_bucket_pow2=True)
        shapes.add((rp.n_rows, row_len))
    return sorted(shapes)


def warm_advantage_shapes(actor, dataset, tr, vocab):
    """`compute_advantages` jits over the padded [trajectories, longest]
    batch, so every distinct longest length is a program of its own.  The
    lengths a step can have are prompt + one of the dataset's budgets: run
    each once on a made-up batch (what `warm_shapes` does for one shape)."""
    P = int(tr["prompt_len"]["value"])
    n = int(tr["batch_prompts"]) * int(tr["group_size"])
    rng = np.random.default_rng(0)
    for L in sorted({P + d["max_new_tokens"] for d in dataset}):
        loss_mask = np.zeros((n, L), np.float32)
        loss_mask[:, P:] = 1.0
        logp = rng.normal(-1.0, 0.1, (n, L)).astype(np.float32)
        actor.compute_advantages({
            "input_ids": rng.integers(0, vocab, (n, L)).astype(np.int32),
            "attention_mask": np.ones((n, L), bool),
            "loss_mask": loss_mask,
            "logprobs": logp,
            "prox_logp": logp.copy(),
            "rewards": (np.arange(n) % 2).astype(np.float32),
            "versions": np.zeros((n, L), np.int32),
        })


def check_batch(actor, hf, chk, batch, rehearsal):
    """Behaviour log-probs the engine returned for sampled tokens (in the
    first batch, all generated under the initial weights) against the
    float32 reference fed the trainer's parameters, which the serving copy
    was made from."""
    from benchmarks.lib import reference

    k = min(int(chk["requests"]), batch["input_ids"].shape[0])
    ids = np.asarray(batch["input_ids"])[:k]
    attn = np.asarray(batch["attention_mask"])[:k].astype(bool)
    gen = (np.asarray(batch["loss_mask"])[:k] > 0) & attn
    v = np.asarray(batch["versions"])[:k]
    if (v[gen] != 0).any():
        return False, {"n": 0, "why": "first batch not all of version 0"}
    got = np.asarray(batch["logprobs"])[:k, 1:]
    want = np.asarray(reference.next_token_logprobs(actor.params, hf, ids))
    tol_mean, tol_max = ((1e-4, 2e-3) if rehearsal
                         else (chk["tol_mean"], chk["tol_max"]))
    return reference.compare_logprobs(got, want, gen[:, 1:], tol_mean, tol_max)


def _stop_reward_pool():
    """Stop the program's reward processes and wait for them."""
    from areal_tpu.api import reward

    pool = reward._pool
    procs = list(getattr(pool, "_processes", {}).values()) if pool else []
    reward.shutdown_reward_pool()
    for p in procs:
        p.join(10)
        if p.is_alive():
            p.terminate()
            p.join(5)


def run(cell, hf, bench):
    import jax

    from areal_tpu.api.config import (
        GenerationHyperparameters,
        InferenceEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.api.reward import prewarm_reward_pool
    from areal_tpu.core.executor import WorkflowExecutor
    from areal_tpu.engine.colocated import ColocatedEngine
    from areal_tpu.gen.engine import GenRequest
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.utils.dataloader import StatefulDataLoader
    from areal_tpu.workflow.rlvr import RLVRWorkflow
    from benchmarks.lib import engine_warm, reference, rewards, traffic as tg

    tr, e, a = cell["traffic"], dict(cell["engine"]), dict(cell["actor"])
    dtype = a["dtype"]
    if bench.rehearsal:
        e.update(n_slots=tr["n_slots"], max_seq_len=tr["max_seq_len"],
                 kv_dtype="float32")
        a.update(pack_length_quantum=tr["pack_length_quantum"])
        dtype = "float32"
    seed = int(bench.args.seed)
    G, B = int(tr["group_size"]), int(tr["batch_prompts"])
    model_cfg = TransformerConfig.from_hf(hf).replace(eos_token_id=None)

    t0 = time.perf_counter()
    actor = make_actor(model_cfg, a, int(e["max_seq_len"]), G, dtype)
    actor.initialize(ft_spec=FinetuneSpec(1, 4096, 8))
    serving = ColocatedEngine(
        model_cfg.replace(dtype=dtype, param_dtype=dtype, remat=False),
        params=actor.export_device_params(),
        n_slots=int(e["n_slots"]), max_seq_len=int(e["max_seq_len"]),
        prompt_bucket=int(e["prompt_bucket"]),
        decode_chunk=int(e["decode_chunk"]), share_prefix=True,
        seed=seed & 0x7FFFFFFF,
        **({"kv_dtype": e["kv_dtype"]} if "kv_dtype" in e else {}),
    )
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    prewarm_reward_pool()
    pool_s = time.perf_counter() - t0
    workflow = RLVRWorkflow(
        reward_fn=rewards.mostly_even,
        gconfig=GenerationHyperparameters(
            n_samples=G, max_new_tokens=int(tr["max_new_tokens"]),
            temperature=float(tr["temperature"])),
    )
    dataset = tg.loop_dataset(tr, hf["vocab_size"], seed)

    t0 = time.perf_counter()
    shapes = plan_pack_shapes(actor, dataset, tr, seed)
    actor.warm_shapes(shapes)
    warm_advantage_shapes(actor, dataset, tr, hf["vocab_size"])
    warm_train_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    P = int(tr["prompt_len"]["value"])
    plan = engine_warm.warm(
        serving.engine, GenRequest, hf["vocab_size"], seed, [P], G,
        P + int(tr["max_new_tokens"]), int(tr["warm_max_admit"]),
        float(tr["temperature"]))
    warm_engine_s = time.perf_counter() - t0

    executor = WorkflowExecutor(
        InferenceEngineConfig(
            experiment_name="bench-loop", trial_name="b",
            consumer_batch_size=B,
            max_concurrent_rollouts=int(tr["max_concurrent_rollouts"]),
            max_head_offpolicyness=int(tr["max_head_offpolicyness"]),
            request_timeout=600,
        ),
        serving,
    )
    executor.initialize()
    dataloader = StatefulDataLoader(dataset, batch_size=B,
                                    seed=int(tr["loader_seed"]))
    state = {"version": serving.get_version(), "seen": set(), "dups": 0,
             "gate_breaks": 0, "beyond_gate": 0, "oldest": 0,
             "version_errors": 0, "span_trajs": 0}
    max_stale = int(tr["max_head_offpolicyness"])

    def check_gate():
        """The staleness gate's own invariant, from its ledger: rollouts
        accepted or running never exceed (max_head_offpolicyness + version
        + 1) consumer batches.  (It bounds how far generation runs ahead,
        not the age of a single trajectory: episodes finish out of order.)"""
        st = executor.staleness_manager.get_stats()
        if st.accepted + st.running > (max_stale + serving.get_version() + 1) * B:
            state["gate_breaks"] += 1

    def account(batch):
        """Count this batch's trajectories, duplicates and stale ones."""
        ids = np.asarray(batch["input_ids"])
        attn = np.asarray(batch["attention_mask"]).astype(bool)
        v = np.asarray(batch["versions"])
        gen = (v >= 0) & attn
        for r in range(ids.shape[0]):
            h = hashlib.sha1(ids[r][attn[r]].tobytes()).digest()
            if h in state["seen"]:
                state["dups"] += 1
            state["seen"].add(h)
            if gen[r].any():
                lo, hi = v[r][gen[r]].min(), v[r][gen[r]].max()
                state["oldest"] = max(state["oldest"], int(state["version"] - lo))
                if state["version"] - lo > max_stale:
                    state["beyond_gate"] += 1
                if lo < hi:
                    state["span_trajs"] += 1
        return ids.shape[0], int(attn.sum())

    def one_step(first=False):
        with bench.spans.span("prepare_batch"):
            batch = executor.prepare_batch(dataloader, workflow=workflow)
        check_gate()
        ref = check_batch(actor, hf, cell["check"], batch, bench.rehearsal) \
            if first else None
        n_traj, n_tok = account(batch)
        with bench.spans.span("train_consume"):
            batch["prox_logp"] = actor.compute_logp(batch)
            actor.compute_advantages(batch)
            stats = actor.ppo_update(batch)
            jax.block_until_ready(actor.params)
        with bench.spans.span("publish"):
            state["version"] += 1
            actor.set_version(state["version"])
            pause = serving.update_weights_in_memory(
                actor.export_device_params(), state["version"])
            bench.spans.value("publish_pause_s", pause)
        if serving.get_version() != state["version"]:
            state["version_errors"] += 1
        return n_traj, n_tok, stats, ref

    try:
        ok_ref, ref_report = True, None
        t_ramp = time.perf_counter()
        for i in range(int(tr["ramp_steps"])):
            _, _, _, ref = one_step(first=(i == 0))
            if ref is not None:
                ok_ref, ref_report = ref
        actor.flush_stats()
        jax.block_until_ready(actor.params)
        ramp_state = {k: state[k] for k in ("dups", "gate_breaks",
                                            "version_errors")}
        bench.diag(phase="setup", init_s=init_s, pool_s=pool_s,
                   warm_train_s=warm_train_s, warm_engine_s=warm_engine_s,
                   ramp_s=time.perf_counter() - t_ramp, pack_shapes=shapes,
                   engine_plan=plan, reference=ref_report,
                   ramp_compiles=bench.compiles.snapshot())

        seconds = bench.window_seconds(cell)
        stats0 = dict(serving.engine.stats)
        trajs = tokens = steps = 0
        all_stats = []
        state["span_trajs"] = 0
        t_open = bench.open_window()
        t_last = t_open
        while t_last - t_open < seconds:
            n_traj, n_tok, st, _ = one_step()
            trajs += n_traj
            tokens += n_tok
            steps += 1
            all_stats.append(st)
            t_last = time.perf_counter()
        window_s = bench.close_window(t_last)
        actor.flush_stats()
        counters = {k: serving.engine.stats[k] - stats0.get(k, 0)
                    for k in serving.engine.stats
                    if isinstance(serving.engine.stats[k], (int, float))}
    finally:
        try:
            executor.destroy()
            serving.destroy()
        finally:
            _stop_reward_pool()

    losses = [sum(float(s["loss"]) for s in st) for st in all_stats]
    gnorms = [float(st[-1]["grad_norm"]) for st in all_stats]
    finite = bool(np.isfinite(losses).all() and np.isfinite(gnorms).all())
    moving = len(set(losses)) > 1 and len(set(gnorms)) > 1
    failed = state["dups"] + state["gate_breaks"]
    return {
        "correct": (ok_ref and finite and moving and failed == 0
                    and state["version_errors"] == 0
                    and trajs == steps * B * G),
        "attempted": trajs,
        "failed": failed,
        "metrics": {
            "loop_trained_tokens_per_s": (tokens / window_s, "tokens/s"),
        },
        "counts": {"steps": steps, "trajectories": trajs, "tokens": tokens},
        "counters": counters,
        "work": {},
        "compared": reference.compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "duplicates": state["dups"],
                   "gate_breaks": state["gate_breaks"],
                   "older_than_gate_at_consumption": state["beyond_gate"],
                   "oldest_at_consumption": state["oldest"],
                   "version_errors": state["version_errors"],
                   "ramp": ramp_state, "final_version": state["version"],
                   "version_span_trajectories": state["span_trajs"],
                   "loss_first_last": [losses[0], losses[-1]] if losses else None,
                   "grad_norm_first_last": [gnorms[0], gnorms[-1]] if gnorms else None,
                   "finite": finite, "moving": moving,
                   "engine_counters": counters},
    }
