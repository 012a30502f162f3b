"""Kind `train`: PPO/GRPO train steps of `JaxPPOActor` on packed rows.

The actor is built as `bench.make_actor` / `chip_smoke.py` build it (copied:
bf16 params and optimizer, gradient checkpointing, GRPO decoupled loss, one
minibatch, deferred stats), with the workload file's `actor` settings.  The
trainer draws its own initial weights (`engine/jax_train.py` uses
`PRNGKey(0)`); `--seed` makes the batches.
"""

import time

import numpy as np


def make_actor(model_cfg, traffic, a):
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.engine.ppo import JaxPPOActor

    row_len = int(traffic["row_len"])
    cfg = PPOActorConfig(
        experiment_name="bench", trial_name="bench", init_from_scratch=True,
        dtype=a["dtype"], param_dtype=a["param_dtype"],
        gradient_checkpointing=True,
        remat_policy=a["remat_policy"],
        layer_group_size=int(a["layer_group_size"]),
        scan_unroll=int(a["scan_unroll"]),
        mesh=MeshConfig(), mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=float(a["lr"]),
                                  warmup_steps_proportion=0.0),
        pack_length_quantum=row_len, max_pack_length=row_len,
        group_size=1, ppo_n_minibatches=1,
        use_decoupled_loss=bool(a["use_decoupled_loss"]),
        async_stats=True,
        adv_norm=NormConfig(mean_level="batch", std_level="batch"),
    )
    return JaxPPOActor(cfg, model_config=model_cfg)


def check_logprobs(actor, hf, chk, batch, got_all, rehearsal):
    """The actor's recomputed log-probs (`got_all`, its `compute_logp` over
    a whole packed batch) against the float32 reference fed the actor's own
    parameters, on the first `tokens` tokens of the `sequences` longest
    sequences of that batch: causal attention makes a prefix's log-probs
    independent of what follows, and packing must not change them."""
    from benchmarks.lib import reference

    lens = batch["attention_mask"].sum(-1)
    pick = np.argsort(-lens, kind="stable")[: int(chk["sequences"])]
    T = int(min(int(chk["tokens"]), lens[pick].min()))
    ids = batch["input_ids"][pick, :T]
    got = np.asarray(got_all)[pick, : T - 1]
    want = np.asarray(reference.next_token_logprobs(actor.params, hf, ids))
    # tolerance: the program computes in bfloat16 (8 bits of mantissa) and
    # the reference in float32; what that costs over 28 layers was measured
    # on the chip (PERF.md, Findings) and the tolerance is about three
    # times it.  A path in float8 or int8 has 16 times the rounding step
    # and lands far outside.  A float32 rehearsal agrees much more closely.
    tol_mean, tol_max = ((1e-4, 1e-3) if rehearsal
                         else (chk["tol_mean"], chk["tol_max"]))
    return reference.compare_logprobs(got, want, np.ones_like(got, bool),
                                      tol_mean, tol_max)


def run(cell, hf, bench):
    import jax

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.models.model_config import TransformerConfig
    from benchmarks.lib import flops, reference, traffic as tg

    tr, a = cell["traffic"], dict(cell["actor"])
    if bench.rehearsal:
        a.update(dtype="float32", param_dtype="float32", scan_unroll=1)
    model_cfg = TransformerConfig.from_hf(hf)
    t0 = time.perf_counter()
    actor = make_actor(model_cfg, tr, a)
    actor.initialize(ft_spec=FinetuneSpec(1, 1024, 8))
    init_s = time.perf_counter() - t0
    batches = tg.train_batches(tr, hf["vocab_size"], bench.args.seed)
    # as the real loop does: the proximal log-probs are the actor's own
    # recomputation; the behaviour policy's differ from them a little
    t0 = time.perf_counter()
    noise = np.random.default_rng([int(bench.args.seed), 9])
    for k, b in enumerate(batches):
        b["prox_logp"] = np.asarray(actor.compute_logp(b))
        if k == 0:
            logp0 = b["prox_logp"]
        b["logprobs"] = (b["prox_logp"] + noise.normal(
            0, 0.02, b["prox_logp"].shape).astype(np.float32)) * b["attention_mask"]
        actor.compute_advantages(b)
    logp_s = time.perf_counter() - t0
    real_tokens = int(batches[0]["attention_mask"].sum())
    seq_lens = batches[0]["attention_mask"].sum(-1).tolist()
    t0 = time.perf_counter()
    ok_ref, ref_report = check_logprobs(
        actor, hf, cell["check"], batches[0], logp0, bench.rehearsal)
    check_s = time.perf_counter() - t0

    # warm-up: the first step compiles (or loads) the one step program
    t0 = time.perf_counter()
    for i in range(2):
        actor.ppo_update(batches[i % len(batches)])
        jax.block_until_ready(actor.params)
    warm_s = time.perf_counter() - t0
    actor.flush_stats()
    bench.diag(phase="setup", init_s=init_s, logp_s=logp_s, check_s=check_s, warm_s=warm_s,
               tokens_per_step=real_tokens, sequences=len(seq_lens),
               padded_tokens=int(tr["rows"]) * int(tr["row_len"]),
               reference=ref_report,
               attention=str(actor.attention_impls()))

    seconds = bench.window_seconds(cell)
    stats, steps = [], 0
    t_open = bench.open_window()
    t_last = t_open
    while t_last - t_open < seconds:
        with bench.spans.span("ppo_update"):
            stats.append(actor.ppo_update(batches[steps % len(batches)]))
            jax.block_until_ready(actor.params)
        steps += 1
        t_last = time.perf_counter()
    window_s = bench.close_window(t_last)
    actor.flush_stats()

    losses = [sum(float(s["loss"]) for s in st) for st in stats]
    gnorms = [float(st[-1]["grad_norm"]) for st in stats]
    bad = [i for i, (l, g) in enumerate(zip(losses, gnorms))
           if not (np.isfinite(l) and np.isfinite(g))]
    moving = len(set(losses)) > 1 and len(set(gnorms)) > 1
    H, _, hd = reference.hf_shape(hf)
    attn_flops = (flops.causal_attention_flops(seq_lens, H, hd)
                  * hf["num_hidden_layers"] * steps)
    return {
        "correct": ok_ref and not bad and moving,
        "attempted": steps,
        "failed": len(bad),
        "metrics": {
            "train_tokens_per_s": (real_tokens * steps / window_s, "tokens/s"),
        },
        "counts": {"steps": steps},
        "work": {"attention_flops": attn_flops},
        "compared": reference.compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "loss_first_last": [losses[0], losses[-1]] if losses else None,
                   "grad_norm_first_last": [gnorms[0], gnorms[-1]] if gnorms else None,
                   "moving": moving, "non_finite_steps": bad,
                   "padding_share": 1 - real_tokens / (int(tr["rows"]) * int(tr["row_len"]))},
    }
