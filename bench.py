"""Benchmark: trainer effective token throughput on one real TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N}

Workload: Qwen2.5-1.5B shapes (the reference's small benchmark model class,
BASELINE.md "1.5B R1-Distill"), bf16 params/optimizer, GRPO decoupled-loss
train step over packed rows — the same fused scan step the real training
loop runs, measured steady-state.  Attention runs the Pallas splash kernel
(areal_tpu/ops/attention.py); the LM head is the chunked rematerialised
scan (ops/functional.py lm_logprobs_entropy), so the workload scales until
HBM is full instead of dying on a [tokens, vocab] fp32 materialisation.

Baseline (vs_baseline denominator): the reference's *effective trainer
throughput per chip* derived from its published numbers (BASELINE.md):
1.5B async run, 1000 PPO steps in 14.8 h on 128 H800s, benchmark workload
512 prompts x 16 samples with ~8k mean tokens per trajectory
=> 512*16*8192 tokens / 53.3 s / 128 chips ~= 9.8k tokens/sec/chip.
This is an estimate (the reference publishes wall-clock, not tok/s/chip);
it is held fixed across rounds so the trend is comparable.

Extra fields: mfu (model-flops 6PT / peak), step_ms, tokens_per_step, the
lm_head_chunk sweep, the 16k- and 32k-context variants and the serving
probe.  A phase that fails fails the run, and so does a missing TPU: no
number is printed from a CPU and none is copied from an older record.

Env knobs: BENCH_PROFILE=/path -> writes a jax.profiler trace of 2 steps
(equivalent to --xla-profile-dir).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

BASELINE_TOKENS_PER_SEC_PER_CHIP = 9800.0

MODEL = "qwen25_1p5b"
WARMUP_STEPS = 4
MEASURE_STEPS = 5

def _peak_tflops():
    import jax

    from areal_tpu.utils.profiling import device_peak_tflops

    return device_peak_tflops(), jax.devices()[0].device_kind


def _make_batch(rng, n_rows, row_len, vocab, seqs_per_row=2):
    """`seqs_per_row` packed sequences per row, loss on the latter 75%."""
    seq_len = row_len // seqs_per_row
    B = n_rows * seqs_per_row
    ids = rng.integers(0, vocab, (B, seq_len)).astype(np.int32)
    mask = np.ones((B, seq_len), bool)
    prompt = seq_len // 4
    loss_mask = np.zeros((B, seq_len), np.float32)
    loss_mask[:, prompt:] = 1.0
    return {
        "input_ids": ids,
        "attention_mask": mask,
        "loss_mask": loss_mask,
        "logprobs": rng.normal(-1.0, 0.1, (B, seq_len)).astype(np.float32),
        "rewards": rng.integers(0, 2, B).astype(np.float32),
        "versions": np.zeros((B, seq_len), np.int32),
    }


def make_actor(model_cfg, row_len, n_mbs=1, group_size=2,
               remat_policy="save_attn", layer_group_size=1, lm_head_chunk=0,
               mesh=None):
    """The PPO actor every train-step measurement (and chip_smoke.py) runs:
    bf16 params and optimizer, GRPO decoupled loss, packed rows."""
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.engine.ppo import JaxPPOActor

    cfg = PPOActorConfig(
        experiment_name="bench",
        trial_name="bench",
        init_from_scratch=True,
        dtype="bfloat16",
        # bf16 master+optimizer: a 1.5B fp32 AdamW state does not fit one
        # 16G chip; throughput is what's measured here
        param_dtype="bfloat16",
        gradient_checkpointing=True,
        # selective remat: keep attention outputs (the backward recomputes
        # projections/MLP but not the attention kernel) — fits v5e HBM and
        # buys ~1% over full remat; the ladder falls back to "full" if the
        # borderline fit flakes
        remat_policy=remat_policy,
        # two-level scan (ISSUE 20): >1 groups this many layers behind one
        # remat boundary per outer-scan step — the backward scan-transpose
        # carry shrinks ~G×; must divide the model depth
        layer_group_size=layer_group_size,
        # fused LM-head vocab chunk (0 = env default 8192); the sweep
        # below records the neighbouring widths
        lm_head_chunk=lm_head_chunk,
        # unroll 4 outer-scan steps per iteration: less per-step carry
        # traffic (~2% on v5e); 7+ runs out of HBM.  With grouping the
        # outer length is depth/G — non-divisors would loudly fall back
        # to 1, so grouped rungs pin unroll=1 instead
        scan_unroll=4 if layer_group_size == 1 else 1,
        mesh=mesh or MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=n_mbs),
        optimizer=OptimizerConfig(lr=1e-5, warmup_steps_proportion=0.0),
        pack_length_quantum=row_len,
        max_pack_length=row_len,
        group_size=group_size,
        ppo_n_minibatches=1,
        use_decoupled_loss=True,
        # deferred stats fetch: steps pipeline on the device instead of
        # serialising on per-step scalar readback (the real train loop runs
        # the same way and flushes at its logging boundary)
        async_stats=True,
        adv_norm=NormConfig(
            mean_level="group", std_level="group", group_size=group_size
        ),
    )
    return JaxPPOActor(cfg, model_config=model_cfg)


def _run(model_cfg, model_name, n_rows, row_len, n_mbs=1, seqs_per_row=2,
         group_size=2, remat_policy="save_attn", layer_group_size=1,
         lm_head_chunk=0):
    actor = make_actor(
        model_cfg, row_len, n_mbs=n_mbs, group_size=group_size,
        remat_policy=remat_policy, layer_group_size=layer_group_size,
        lm_head_chunk=lm_head_chunk,
    )
    try:
        return _run_on_actor(
            actor, model_cfg, model_name, n_rows, row_len, seqs_per_row
        )
    finally:
        # a failed attempt must free its params/optimizer, or every later
        # (smaller) ladder entry inherits a nearly-full chip and OOMs too
        actor.destroy()


def _run_on_actor(actor, model_cfg, model_name, n_rows, row_len, seqs_per_row):
    import jax

    from areal_tpu.api.io_struct import FinetuneSpec

    actor.initialize(ft_spec=FinetuneSpec(1, 1024, 8))

    rng = np.random.default_rng(0)
    batch = _make_batch(
        rng, n_rows, row_len, model_cfg.vocab_size, seqs_per_row=seqs_per_row
    )
    batch["prox_logp"] = batch["logprobs"].copy()
    actor.compute_advantages(batch)

    tokens_per_step = int(batch["attention_mask"].sum())
    for _ in range(WARMUP_STEPS):
        actor.ppo_update(batch)
    jax.block_until_ready(actor.params)

    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        from areal_tpu.utils.profiling import profile_trace

        with profile_trace(profile_dir):
            actor.ppo_update(batch)
            actor.ppo_update(batch)
            jax.block_until_ready(actor.params)

    # two measurement windows, best wins
    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            actor.ppo_update(batch)
        jax.block_until_ready(actor.params)
        dt = min(dt, (time.perf_counter() - t0) / MEASURE_STEPS)

    tok_per_sec = tokens_per_step / dt
    result = {
        "metric": f"grpo_train_step_throughput_{model_name}_bf16_ctx{row_len}",
        "value": round(tok_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(tok_per_sec / BASELINE_TOKENS_PER_SEC_PER_CHIP, 3),
        "step_ms": round(dt * 1e3, 1),
        "tokens_per_step": tokens_per_step,
    }
    peak, kind = _peak_tflops()
    from areal_tpu.utils.profiling import param_count

    model_tflops = tokens_per_step * 6 * param_count(model_cfg) / dt / 1e12
    result["model_tflops_per_sec"] = round(model_tflops, 1)
    result["device_kind"] = kind
    result["mfu"] = round(model_tflops / peak, 3)
    # scan shape actually in effect (ISSUE 20 satellite: the silent unroll
    # fallback is now recorded, not guessed) — the engine computed these at
    # initialize() from the post-replace model config
    result["layer_group_size"] = int(
        max(1, actor.model_config.layer_group_size))
    result["effective_scan_unroll"] = int(
        getattr(actor, "_effective_scan_unroll", 1))
    result["lm_head_chunk"] = int(getattr(actor.config, "lm_head_chunk", 0))
    return result


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--xla-profile-dir",
        default=os.environ.get("BENCH_PROFILE", ""),
        help="write a jax.profiler trace of 2 warm steps here "
        "(utils/profiling.py profile_trace; BENCH_PROFILE env is the "
        "legacy spelling)",
    )
    args = p.parse_args()
    if args.xla_profile_dir:
        # _run_on_actor reads the env knob at its capture point
        os.environ["BENCH_PROFILE"] = args.xla_profile_dir

    import jax

    from areal_tpu.models.model_config import qwen25_1p5b
    from areal_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        # a rate from a CPU run is not a measurement of this system
        sys.exit(
            f"bench.py needs a TPU; JAX came up on "
            f"{jax.devices()[0].platform!r} and no rate is printed"
        )

    # best-throughput workload first (probed on v5e: 8 rows beats 12 —
    # larger batches hit HBM pressure); smaller fallbacks for smaller chips.
    # The two-level scan rungs (ISSUE 20) lead: 28 layers / G=4 = 7 outer
    # steps, one remat boundary per group, backward scan-transpose carry
    # ~G× smaller — the ROADMAP 3b plateau was carry-bound, so the grouped
    # rungs are the headline candidates and the proven G=1 rungs the net
    ladder = [
        # carry_offload parks the per-group saved activations in pinned
        # host DRAM between forward and backward — the HBM-relief rung
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "carry_offload", 4),
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "full", 4),
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "full", 2),
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "save_attn", 1),
        # ROADMAP 3b plateau probe: keep MLP intermediates instead of the
        # attention outputs — the intermediate memory/recompute rung
        # between save_attn and full, aimed at the backward-scan carry
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "save_mlp", 1),
        (qwen25_1p5b(), "qwen25_1p5b", 8, 2048, 1, "full", 1),
        (qwen25_1p5b(), "qwen25_1p5b", 4, 2048, 1, "full", 1),
        (qwen25_1p5b(), "qwen25_1p5b", 2, 2048, 1, "full", 1),
        (qwen25_1p5b().replace(num_layers=14), "qwen25_1p5b_half_depth", 2,
         2048, 1, "full", 1),
    ]
    result = None
    last_err = None
    attempts = []  # which ladder rung produced the headline, and what
    # ran out of memory on the way there
    for model_cfg, name, n_rows, row_len, n_mbs, policy, lgs in ladder:
        rung = f"{name} x{n_rows}x{row_len} remat={policy} G={lgs}"
        try:
            result = _run(model_cfg, name, n_rows, row_len, n_mbs,
                          remat_policy=policy, layer_group_size=lgs)
        except Exception as e:  # noqa: BLE001 — ladder fall-through
            # only a program that does not fit moves on to the next
            # (smaller) rung; any other failure fails the run
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            last_err = e
            attempts.append(
                {"rung": rung, "ok": False, "error_tail": str(e)[-200:]}
            )
            print(f"bench: {rung} does not fit, trying the next rung",
                  file=sys.stderr)
            continue
        attempts.append({"rung": rung, "ok": True})
        result["remat_policy"] = policy
        result["n_rows"] = n_rows
        headline_rung = (model_cfg, name, n_rows, row_len, n_mbs, policy, lgs)
        break
    if result is None:
        raise last_err
    result["attempts"] = attempts
    result["lm_head_impl"] = os.environ.get("AREAL_LM_HEAD_IMPL", "fused")

    # fused LM-head vocab-chunk sweep (ISSUE 20 satellite): the chunk width
    # was a buried env default (8192); now that it's a plumbed knob, record
    # the neighbouring widths on the headline workload so the default is
    # re-justified by data each round.  BENCH_CHUNK_SWEEP=0 skips.
    if os.environ.get("BENCH_CHUNK_SWEEP", "1") != "0":
        sweep = {}
        m_cfg, name, n_rows, row_len, n_mbs, policy, lgs = headline_rung
        for chunk in (4096, 16384):
            r = _run(m_cfg, name, n_rows, row_len, n_mbs,
                     remat_policy=policy, layer_group_size=lgs,
                     lm_head_chunk=chunk)
            sweep[str(chunk)] = {"tokens_per_sec": r["value"],
                                 "step_ms": r["step_ms"]}
        result["lm_head_chunk_sweep"] = sweep
    if args.xla_profile_dir:
        result["xla_profile_dir"] = args.xla_profile_dir

    # ctx-scaling variant: one 16k-token sequence per row — evidence the
    # splash path holds at long context (no O(T^2) mask materialisation)
    long_res = _run(
        qwen25_1p5b(), "qwen25_1p5b", 1, 16384, 1, seqs_per_row=1,
        group_size=1, remat_policy="full",
    )
    result["ctx16k_tokens_per_sec"] = long_res["value"]
    result["ctx16k_step_ms"] = long_res["step_ms"]

    # 32k-context on-chip evidence (VERDICT r2 #8): the 1.5B state doesn't
    # leave room for 32k activations on 16G, so the Qwen2-class ~0.6B
    # (head_dim 128, splash-eligible) carries the long-context train step
    from areal_tpu.models.model_config import qwen2_0p6b_ctx

    long32 = _run(
        qwen2_0p6b_ctx(), "qwen2_0p6b", 1, 32768, 1, seqs_per_row=1,
        group_size=1, remat_policy="full",
    )
    result["ctx32k_0p6b_tokens_per_sec"] = long32["value"]
    result["ctx32k_0p6b_step_ms"] = long32["step_ms"]

    # serving-side probe (VERDICT r3 #1): decode throughput with a busy
    # 64-slot grid + the multi-turn KV-prefix-reuse gain, on the same chip.
    # BENCH_SERVING=0 skips (the full curve is scripts/bench_serving.py's;
    # the e2e async-vs-sync loop is scripts/bench_e2e_grpo.py's).
    if os.environ.get("BENCH_SERVING", "1") != "0":
        result.update(_serving_probe())

    print(json.dumps(result))


def _serving_probe():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    import bench_serving as bs

    cfg, params = bs.serving_model_setup()
    decode = bs.bench_decode(cfg, params, [64], max_seq_len=512,
                             gen_tokens=128, prompt_len=64)
    # prefill-dominated turns (the agentic shape where reuse matters) at
    # 512-token turns x 4 on growing transcripts; tiny-turn workloads are
    # decode-bound and measure ~1.0x regardless
    mt = bs.bench_multi_turn(cfg, params, n_convs=8, turns=4,
                             turn_prompt=512, turn_gen=32, max_seq_len=4096)
    out = {}
    if "64" in decode and "tokens_per_sec" in decode["64"]:
        out["serving_decode_tok_s_64slots"] = decode["64"]["tokens_per_sec"]
        # ISSUE 5 window accounting: fraction of the cache width decode
        # actually attended (1.0 would mean the full ceiling is paid)
        out["serving_decode_attended_fraction"] = decode["64"].get(
            "decode_attended_fraction"
        )
        # latency distributions (ISSUE 14): BENCH carries p50/p99 curves,
        # not single-run means
        lat = decode["64"].get("latency") or {}
        for stat, key in (("ttft", "ttft_s"), ("e2e", "e2e_s"),
                          ("itl", "inter_token_s")):
            d = lat.get(key)
            if d:
                out[f"serving_decode_{stat}_p50_s"] = round(d["p50"], 4)
                out[f"serving_decode_{stat}_p99_s"] = round(d["p99"], 4)
    out["serving_multiturn_kv_reuse_speedup"] = mt["speedup"]
    out["serving_multiturn_prefill_tokens_saved_frac"] = round(
        mt["reuse"]["reused_tokens"]
        / max(1, mt["cold"]["prefill_tokens"]), 3,
    )
    # speculative decode (ISSUE 12): acceptance rate + on/off speedup on
    # the repetition-heavy workload, tracked alongside the decode curve
    spec = bs.bench_spec_decode_ab(cfg, params, n_slots=8, gen_tokens=128)
    out["serving_spec_acceptance_rate"] = spec["on"]["spec_acceptance_rate"]
    out["serving_spec_decode_speedup"] = spec["spec_over_plain_tok_s"]
    # ragged paged-decode kernel (ISSUE 19): dispatch collapse + tok/s
    # ratio on the mixed-length workload, with the stream-parity bit
    # riding along (False would mean the kernel broke bit-identity)
    ragged = bs.bench_ragged_ab(cfg, params, n_slots=8, gen_tokens=96)
    for regime in ("mixed", "repetition"):
        r = ragged[regime]
        out[f"serving_ragged_speedup_{regime}"] = r["ragged_over_dense_tok_s"]
        out[f"serving_ragged_dispatch_reduction_{regime}"] = (
            r["dispatch_reduction"]
        )
        out[f"serving_ragged_bit_identical_{regime}"] = (
            r["streams_bit_identical"]
        )
    return out


if __name__ == "__main__":
    main()
