"""Serving-side benchmark: decode throughput, prefill cost, KV-reuse gain.

VERDICT r3 next-step #1 (first half): the generation engine — the biggest
piece of new TPU-native machinery — gets measured on the real chip.
Prints ONE JSON line:

  {"decode": {"<n_slots>": {"tokens_per_sec": ..., "wall_s": ...}, ...},
   "prefill": {"bucket_<P>": {"tokens_per_sec": ..., "ms": ...}, ...},
   "multi_turn": {"reuse": {...}, "cold": {...}, "speedup": ...},
   "device_kind": ...}

Workloads (Qwen2.5-1.5B shapes, bf16, random weights — serving throughput
does not depend on weight values):
- decode: fill every slot, generate to a fixed budget, steady-state
  delivered tokens/sec vs slot count (the tokens/s-vs-n_slots curve of
  VERDICT weak #5);
- prefill: one bucketed admission per prompt-length bucket, tokens/sec
  through the prefill program;
- multi-turn: T-turn conversations where each turn extends the last
  transcript — KV prefix reuse vs cold engine (VERDICT #3's gain,
  quantified).

Match: the reference benchmarks its serving side through SGLang's
reported throughput (blog/AReaL_v0_3.md); this engine is ours, so it gets
its own figure.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.obs.trace import dist_summary  # noqa: E402


def serving_model_setup(model: str = "qwen25_1p5b"):
    """The canonical serving-bench model: Qwen2.5-1.5B shapes, bf16,
    random weights.  Shared with bench.py's quick probe so the headline
    serving numbers and SERVING_BENCH_r{N}.json can never desynchronise.
    `model="tiny"` is the CPU smoke mode: wall-clock is meaningless there,
    but the token-accounting signals (reused/shared fractions) are
    workload arithmetic and carry over exactly."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import qwen25_1p5b, tiny_config

    if model == "tiny":
        cfg = tiny_config(vocab_size=512, qkv_bias=True,
                          hf_architecture="Qwen2ForCausalLM",
                          eos_token_id=None)
    else:
        cfg = qwen25_1p5b().replace(
            dtype="bfloat16", param_dtype="bfloat16", remat=False,
            eos_token_id=None,
        )
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _reset_stats(eng):
    for k in eng.stats:
        eng.stats[k] = 0


def _engine(cfg, params, n_slots, max_seq_len, kv_reuse=True, decode_chunk=8,
            **kw):
    from areal_tpu.gen.engine import GenEngine

    return GenEngine(
        cfg, params=params, n_slots=n_slots, max_seq_len=max_seq_len,
        prompt_bucket=128, decode_chunk=decode_chunk, kv_reuse=kv_reuse,
        **kw,
    )


def bench_decode(cfg, params, slot_counts, max_seq_len=512, gen_tokens=128,
                 prompt_len=64, spec_decode=False, draft_len=0):
    """Steady-state decode tokens/sec with every slot busy."""
    from areal_tpu.gen.engine import GenRequest

    rng = np.random.default_rng(0)
    out = {}
    for n_slots in slot_counts:
        try:
            eng = _engine(cfg, params, n_slots, max_seq_len, kv_reuse=False,
                          spec_decode=spec_decode,
                          spec_draft_len=draft_len or None)
            # warmup: compile prefill + decode
            reqs = [
                GenRequest(rid=f"w{i}",
                           input_ids=rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                           max_new_tokens=8, temperature=1.0)
                for i in range(n_slots)
            ]
            eng.generate_blocking(reqs)
            _reset_stats(eng)  # warmup compiles must not skew counters
            # measured run: fixed budget per slot, no stop tokens
            reqs = [
                GenRequest(rid=f"m{i}",
                           input_ids=rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                           max_new_tokens=gen_tokens, temperature=1.0)
                for i in range(n_slots)
            ]
            for r in reqs:
                eng.submit(r)
            eng.step()  # admission (prefill) outside the decode timing
            t0 = time.perf_counter()
            delivered = 0
            while any(not r.stop_reason for r in reqs):
                delivered += eng.step()
            dt = time.perf_counter() - t0
            # per-request latency triple off the GenRequest perf_counter
            # stamps (submit -> first delivered token -> finish); the
            # admission step above sits inside TTFT, as it does for a
            # real client
            ttfts = [r.first_token_ts - r.submit_ts for r in reqs
                     if r.first_token_ts > 0.0]
            e2es = [r.finish_ts - r.submit_ts for r in reqs
                    if r.finish_ts > 0.0]
            itls = [
                (r.finish_ts - r.first_token_ts)
                / max(1, len(r.output_tokens) - 1)
                for r in reqs
                if r.finish_ts > 0.0 and r.first_token_ts > 0.0
                and len(r.output_tokens) > 1
            ]
            out[str(n_slots)] = {
                "tokens_per_sec": round(delivered / dt, 1),
                "wall_s": round(dt, 2),
                "latency": {
                    "ttft_s": dist_summary(ttfts),
                    "e2e_s": dist_summary(e2es),
                    "inter_token_s": dist_summary(itls),
                },
                "decode_calls": eng.stats["decode_calls"],
                # attended span / ceiling (ISSUE 5 window accounting):
                # decode reads this fraction of the configured cache width
                "decode_attended_fraction": round(
                    eng.decode_attended_fraction(), 4
                ),
                # speculative-decode accounting (ISSUE 12): all zero when
                # --spec-decode is off
                "verify_calls": eng.stats["verify_calls"],
                "spec_draft_tokens": eng.stats["spec_drafted"],
                "spec_accepted_tokens": eng.stats["spec_accepted"],
                "spec_acceptance_rate": round(
                    eng.stats["spec_accepted"]
                    / max(1, eng.stats["spec_drafted"]), 4
                ),
            }
            print(f"decode n_slots={n_slots}: {out[str(n_slots)]}",
                  file=sys.stderr, flush=True)
            del eng
        except Exception as e:  # noqa: BLE001 — record and continue the curve
            out[str(n_slots)] = {"error": str(e)[:200]}
            print(f"decode n_slots={n_slots} failed: {str(e)[:120]}",
                  file=sys.stderr, flush=True)
    return out


def bench_prefill(cfg, params, buckets=(128, 512, 1024), rows=8,
                  max_seq_len=2048):
    """Prefill throughput per prompt bucket: one bucketed admission of
    `rows` prompts, tokens/sec through the prefill program."""
    from areal_tpu.gen.engine import GenRequest

    rng = np.random.default_rng(1)
    eng = _engine(cfg, params, rows, max_seq_len, kv_reuse=False)
    out = {}
    for bucket in buckets:
        plen = bucket - 1  # stay inside the bucket
        for warm in (True, False):
            reqs = [
                GenRequest(rid=f"p{bucket}_{warm}_{i}",
                           input_ids=rng.integers(0, cfg.vocab_size, plen).tolist(),
                           max_new_tokens=1, temperature=1.0)
                for i in range(rows)
            ]
            for r in reqs:
                eng.submit(r)
            t0 = time.perf_counter()
            eng.step()
            dt = time.perf_counter() - t0
            while any(not r.stop_reason for r in reqs):
                eng.step()
        out[f"bucket_{bucket}"] = {
            "tokens_per_sec": round(rows * plen / dt, 1),
            "ms": round(dt * 1e3, 1),
        }
        print(f"prefill bucket={bucket}: {out[f'bucket_{bucket}']}",
              file=sys.stderr, flush=True)
    return out


def bench_multi_turn(cfg, params, n_convs=8, turns=4, turn_prompt=64,
                     turn_gen=32, max_seq_len=1024):
    """T-turn conversations: each turn replays the transcript + new user
    tokens.  Reuse engine vs cold engine, wall-clock + prefill-token
    accounting."""
    from areal_tpu.gen.engine import GenRequest

    out = {}
    for mode in ("reuse", "cold"):
        rng = np.random.default_rng(2)  # identical workload both modes
        eng = _engine(cfg, params, n_convs, max_seq_len,
                      kv_reuse=(mode == "reuse"))
        # compile EVERY program the timed loop will hit by replaying ALL
        # `turns` rounds of the real shapes: growing transcripts cross a
        # new pow-2 prefill bucket as late as the final turn, plus the
        # suffix-prefill program reuse mode enters from turn 2, plus
        # decode.  A partial warmup leaks a compile into the timed region
        # and swamps the ~seconds workload.
        warm_tr = [[1] * turn_prompt for _ in range(n_convs)]
        for _ in range(turns):
            wreqs = [
                GenRequest(rid=f"w{i}", input_ids=list(warm_tr[i]),
                           max_new_tokens=turn_gen, temperature=1.0)
                for i in range(n_convs)
            ]
            for r in wreqs:
                eng.submit(r)
            while any(not r.stop_reason for r in wreqs):
                eng.step()
            for i, r in enumerate(wreqs):
                warm_tr[i] = (
                    warm_tr[i] + r.output_tokens + [2] * turn_prompt
                )
        _reset_stats(eng)  # warmup must not skew the token accounting
        eng.retained_len[:] = 0  # nor seed a reusable prefix
        transcripts = [
            rng.integers(0, cfg.vocab_size, turn_prompt).tolist()
            for _ in range(n_convs)
        ]
        t0 = time.perf_counter()
        for turn in range(turns):
            reqs = [
                GenRequest(rid=f"c{i}", input_ids=list(transcripts[i]),
                           max_new_tokens=turn_gen, temperature=1.0)
                for i in range(n_convs)
            ]
            for r in reqs:
                eng.submit(r)
            while any(not r.stop_reason for r in reqs):
                eng.step()
            for i, r in enumerate(reqs):
                transcripts[i] = (
                    transcripts[i] + r.output_tokens
                    + rng.integers(0, cfg.vocab_size, turn_prompt).tolist()
                )
        dt = time.perf_counter() - t0
        out[mode] = {
            "wall_s": round(dt, 2),
            "prefill_tokens": eng.stats["prefill_tokens"],
            "suffix_tokens": eng.stats["suffix_tokens"],
            "reused_tokens": eng.stats["reused_tokens"],
        }
        print(f"multi_turn {mode}: {out[mode]}", file=sys.stderr, flush=True)
        del eng
    out["speedup"] = round(out["cold"]["wall_s"] / out["reuse"]["wall_s"], 3)
    return out


def bench_group_fanout(cfg, params, group_size=8, n_groups=6, prompt_len=256,
                       gen_tokens=16, max_seq_len=1024):
    """GRPO-shaped admission: `n_groups` groups of `group_size` requests
    over ONE prompt each (distinct prompts across groups).  Share engine
    (group fan-out prefill) vs no-share engine over the identical workload;
    reports wall clock plus the hardware-independent signal —
    `shared_prefill_fraction`: the fraction of grouped prompt tokens that
    were NEVER recomputed (fanned out from the representative's KV).

    A third pass (`share_host`) reruns the share workload with the
    host-DRAM overflow tier enabled and retained prefixes spilling between
    groups; its streams must be bit-identical to the device-only share
    pass — cache placement (device row, page remap, host round trip) is
    invisible to the counter-keyed sampler."""
    from areal_tpu.gen.engine import GenRequest

    out = {"group_size": group_size, "n_groups": n_groups,
           "prompt_len": prompt_len}
    streams = {}  # mode -> [[output_tokens per sibling] per group]
    mode_kw = {
        "share": dict(share_prefix=True),
        "noshare": dict(share_prefix=False),
        "share_host": dict(share_prefix=True, host_offload=True,
                           host_cache_mb=32, host_min_tokens=16),
    }
    for mode in ("share", "noshare", "share_host"):
        rng = np.random.default_rng(5)  # identical workload all modes
        eng = _engine(cfg, params, group_size, max_seq_len,
                      **mode_kw[mode])

        def run_group(prompt, tag):
            reqs = [
                GenRequest(rid=f"{tag}-{i}", input_ids=list(prompt),
                           max_new_tokens=gen_tokens, temperature=1.0,
                           group_id=tag, group_n=group_size)
                for i in range(group_size)
            ]
            eng.submit_batch(reqs)
            while any(not r.stop_reason for r in reqs):
                eng.step()
            return reqs

        # warmup compiles every program the timed loop hits (prefill
        # bucket, fan-out copy, sibling suffix bucket, decode)
        run_group([1] * prompt_len, "warm")
        _reset_stats(eng)
        eng.retained_len[:] = 0  # no cross-group retained carryover
        t0 = time.perf_counter()
        for g in range(n_groups):
            # mode-independent tag: stream keys derive from the rid, so
            # the share/noshare identity check needs identical rids
            done = run_group(
                rng.integers(0, cfg.vocab_size, prompt_len).tolist(),
                f"g{g}",
            )
            streams.setdefault(mode, []).append(
                [r.output_tokens for r in done]
            )
        dt = time.perf_counter() - t0
        st = eng.stats
        total = (st["prefill_tokens"] + st["suffix_tokens"]
                 + st["reused_tokens"] + st["shared_tokens"])
        out[mode] = {
            "wall_s": round(dt, 2),
            "prefill_tokens": st["prefill_tokens"],
            "suffix_tokens": st["suffix_tokens"],
            "shared_tokens": st["shared_tokens"],
            "copy_calls": st["copy_calls"],
            "shared_prefill_fraction": round(
                st["shared_tokens"] / max(total, 1), 4
            ),
        }
        if mode == "share_host":
            out[mode]["prefix_cache_host_swaps"] = st[
                "prefix_cache_host_swaps"
            ]
            out[mode]["prefix_cache_evictions"] = st[
                "prefix_cache_evictions"
            ]
        print(f"group_fanout {mode}: {out[mode]}", file=sys.stderr,
              flush=True)
        del eng
    out["shared_prefill_fraction"] = out["share"]["shared_prefill_fraction"]
    # the host tier must be invisible to the counter-keyed sampler: the
    # share workload rerun under spill pressure emits the exact streams
    out["streams_bit_identical"] = streams["share"] == streams["share_host"]
    out["speedup"] = round(
        out["noshare"]["wall_s"] / max(out["share"]["wall_s"], 1e-9), 3
    )
    return out


def bench_decode_ceiling_ab(cfg, params, n_slots=16, ceilings=(4096, 16384),
                            prompt_len=64, gen_tokens=128, tiers=1,
                            window=True):
    """ISSUE 5 acceptance A/B: the SAME decode workload under different
    `max_seq_len` ceilings.  Before the bucketed key window, decode
    attention read the full ceiling width every step, so tokens/s degraded
    as the ceiling grew even though the workload never used the headroom;
    with tiered/windowed decode the large-ceiling number should land
    within ~10% of the small-ceiling one.  Reports per-ceiling tokens/s,
    `decode_attended_fraction`, and the large/small throughput ratio."""
    from areal_tpu.gen.engine import GenRequest

    out = {"n_slots": n_slots, "prompt_len": prompt_len,
           "gen_tokens": gen_tokens, "decode_window": window,
           "decode_tiers": tiers}
    per = {}
    for ceiling in ceilings:
        rng = np.random.default_rng(7)  # identical workload per ceiling
        try:
            eng = _engine(cfg, params, n_slots, ceiling, kv_reuse=False,
                          decode_window=window, decode_tiers=tiers)
            warm = [
                GenRequest(rid=f"w{i}",
                           input_ids=rng.integers(0, cfg.vocab_size,
                                                  prompt_len).tolist(),
                           max_new_tokens=8, temperature=1.0)
                for i in range(n_slots)
            ]
            eng.generate_blocking(warm)
            _reset_stats(eng)
            reqs = [
                GenRequest(rid=f"m{i}",
                           input_ids=rng.integers(0, cfg.vocab_size,
                                                  prompt_len).tolist(),
                           max_new_tokens=gen_tokens, temperature=1.0)
                for i in range(n_slots)
            ]
            for r in reqs:
                eng.submit(r)
            eng.step()  # admission (prefill) outside the decode timing
            t0 = time.perf_counter()
            delivered = 0
            while any(not r.stop_reason for r in reqs):
                delivered += eng.step()
            dt = time.perf_counter() - t0
            per[str(ceiling)] = {
                "tokens_per_sec": round(delivered / dt, 1),
                "wall_s": round(dt, 2),
                "decode_attended_fraction": round(
                    eng.decode_attended_fraction(), 4
                ),
            }
            print(f"ceiling_ab max_seq_len={ceiling}: {per[str(ceiling)]}",
                  file=sys.stderr, flush=True)
            del eng
        except Exception as e:  # noqa: BLE001 — record and continue the A/B
            per[str(ceiling)] = {"error": str(e)[:200]}
            print(f"ceiling_ab max_seq_len={ceiling} failed: {str(e)[:120]}",
                  file=sys.stderr, flush=True)
    out["by_ceiling"] = per
    lo, hi = str(min(ceilings)), str(max(ceilings))
    if "tokens_per_sec" in per.get(lo, {}) and "tokens_per_sec" in per.get(hi, {}):
        # >= 0.9 is the acceptance bar: the large ceiling costs <= 10%
        out["large_over_small_tok_s"] = round(
            per[hi]["tokens_per_sec"] / max(per[lo]["tokens_per_sec"], 1e-9),
            3,
        )
    return out


def _repetition_params(cfg, params):
    """Repetition-heavy synthetic regime (ISSUE 12): zeroing the attention
    output projection makes greedy next-token a pure function of the
    current token, so every stream settles into a short cycle — the
    deterministic stand-in for math-style restatement / code-identifier
    loops that the prompt-lookup drafter feeds on.  Engine-side cost per
    dispatch is unchanged (serving throughput does not depend on weight
    values), so the spec-on/off A/B stays fair while guaranteeing
    draftable streams."""
    import jax.numpy as jnp

    out = dict(params)
    out["layers"] = dict(params["layers"])
    out["layers"]["attn"] = dict(params["layers"]["attn"])
    out["layers"]["attn"]["wo"] = jnp.zeros_like(
        params["layers"]["attn"]["wo"]
    )
    return out


def bench_spec_decode_ab(cfg, params, n_slots=8, prompt_len=64,
                         gen_tokens=128, max_seq_len=512, draft_len=31):
    """ISSUE 12 acceptance A/B: the SAME repetition-heavy greedy workload
    with speculative decoding off vs on.  Spec-off pays one sequential
    model call per token; spec-on verifies D+1 positions in one batched
    dispatch, so accepted drafts collapse dispatches.  Reports per-arm
    tokens/s, draft/accept counters, the bit-identical-stream check (the
    correctness contract rides along with the perf number), and the
    on/off throughput ratio — acceptance bar: >= 1.4x on the CPU rig,
    target >= 2x on real chips (ROADMAP 3b).

    Prompts are the model's OWN prior greedy output (an untimed setup
    rollout from one seed token per slot) — the continuation-of-own-output
    shape that self-speculation targets.  Random tiled prompts would hide
    the win behind each stream's cycle-entry transient: until a cycle has
    repeated once inside visible history the drafter has nothing to look
    up, and in a mixed batch the already-drafting slots drag the still-
    transient ones through verify dispatches at one token each."""
    from areal_tpu.gen.engine import GenRequest

    rep_params = _repetition_params(cfg, params)
    out = {"n_slots": n_slots, "prompt_len": prompt_len,
           "gen_tokens": gen_tokens, "draft_len": draft_len}
    rng = np.random.default_rng(9)
    seeds = rng.integers(0, cfg.vocab_size, n_slots).tolist()
    seed_eng = _engine(cfg, rep_params, n_slots, max_seq_len, kv_reuse=False)
    seed_reqs = [
        GenRequest(rid=f"s{i}", input_ids=[int(s)],
                   max_new_tokens=prompt_len - 1, temperature=0.0)
        for i, s in enumerate(seeds)
    ]
    seed_eng.generate_blocking(seed_reqs)
    prompts = [[int(s)] + list(r.output_tokens)
               for s, r in zip(seeds, seed_reqs)]
    del seed_eng
    streams = {}
    for mode in ("off", "on"):
        kw = (dict(spec_decode=True, spec_draft_len=draft_len or None)
              if mode == "on" else {})
        eng = _engine(cfg, rep_params, n_slots, max_seq_len, kv_reuse=False,
                      **kw)
        # full-length warmup: the timed loop crosses the same key-window
        # buckets, so every decode/verify program compiles here
        warm = [
            GenRequest(rid=f"w{i}", input_ids=list(p),
                       max_new_tokens=gen_tokens, temperature=0.0)
            for i, p in enumerate(prompts)
        ]
        eng.generate_blocking(warm)
        _reset_stats(eng)
        eng.retained_len[:] = 0
        reqs = [
            GenRequest(rid=f"m{i}", input_ids=list(p),
                       max_new_tokens=gen_tokens, temperature=0.0)
            for i, p in enumerate(prompts)
        ]
        for r in reqs:
            eng.submit(r)
        eng.step()  # admission (prefill) outside the decode timing
        t0 = time.perf_counter()
        delivered = 0
        while any(not r.stop_reason for r in reqs):
            delivered += eng.step()
        dt = time.perf_counter() - t0
        streams[mode] = [tuple(r.output_tokens) for r in reqs]
        drafted = eng.stats["spec_drafted"]
        accepted = eng.stats["spec_accepted"]
        out[mode] = {
            "tokens_per_sec": round(delivered / dt, 1),
            "wall_s": round(dt, 2),
            "decode_calls": eng.stats["decode_calls"],
            "verify_calls": eng.stats["verify_calls"],
            "spec_draft_tokens": drafted,
            "spec_accepted_tokens": accepted,
            "spec_acceptance_rate": round(accepted / max(1, drafted), 4),
        }
        print(f"spec_ab {mode}: {out[mode]}", file=sys.stderr, flush=True)
        del eng
    out["streams_bit_identical"] = streams["on"] == streams["off"]
    out["spec_over_plain_tok_s"] = round(
        out["on"]["tokens_per_sec"] / max(out["off"]["tokens_per_sec"], 1e-9),
        3,
    )
    return out


def bench_ragged_ab(cfg, params, n_slots=8, gen_tokens=96, max_seq_len=512,
                    draft_len=31):
    """ISSUE 19 acceptance A/B: the SAME workload through the dense tiered
    decode path and the collapsed ragged-kernel path, on two regimes:

      - mixed:      mixed-length random prompts, greedy, spec off — the
                    ragged-span case (per-slot paged gather vs the dense
                    tier ceiling), one grid-wide dispatch per step vs one
                    per active tier.
      - repetition: repetition-heavy continuation-of-own-output prompts
                    with speculative decoding on — verification rides the
                    SAME kernel (T = D+1 query positions), so the per-tier
                    verify fan-out collapses too.

    The correctness contract rides along with the perf number: token AND
    logprob streams must be bit-identical across arms, and the acceptance
    bar is a strict decode+verify dispatch-count reduction at equal
    streams.  On the CPU rig the kernel runs in Pallas interpret mode —
    dispatch counts, attended-page accounting, and bit-identity all
    transfer to real chips, wall-clock ratios do NOT (interpret-mode
    per-dispatch overhead dominates; see docs/perf.md Round 13)."""
    from areal_tpu.gen.engine import GenRequest

    out = {"n_slots": n_slots, "gen_tokens": gen_tokens,
           "interpret_caveat": (
               "CPU run: kernel in Pallas interpret mode; dispatch counts "
               "and bit-identity transfer to chips, wall-clock does not")}
    rng = np.random.default_rng(17)

    mixed_prompts = [
        rng.integers(0, cfg.vocab_size, int(n)).tolist()
        for n in rng.integers(16, 257, n_slots)
    ]
    rep_params = _repetition_params(cfg, params)
    seeds = rng.integers(0, cfg.vocab_size, n_slots).tolist()
    seed_eng = _engine(cfg, rep_params, n_slots, max_seq_len, kv_reuse=False)
    seed_reqs = [
        GenRequest(rid=f"s{i}", input_ids=[int(s)], max_new_tokens=63,
                   temperature=0.0)
        for i, s in enumerate(seeds)
    ]
    seed_eng.generate_blocking(seed_reqs)
    rep_prompts = [[int(s)] + list(r.output_tokens)
                   for s, r in zip(seeds, seed_reqs)]
    del seed_eng

    regimes = {
        "mixed": dict(params=params, prompts=mixed_prompts, kw={}),
        "repetition": dict(
            params=rep_params, prompts=rep_prompts,
            kw=dict(spec_decode=True, spec_draft_len=draft_len or None)),
    }
    for name, regime in regimes.items():
        streams, res = {}, {}
        for mode in ("dense", "ragged"):
            eng = _engine(cfg, regime["params"], n_slots, max_seq_len,
                          kv_reuse=False, decode_tiers=2,
                          ragged_attn=(mode == "ragged"), **regime["kw"])
            warm = [
                GenRequest(rid=f"w{i}", input_ids=list(p),
                           max_new_tokens=gen_tokens, temperature=0.0)
                for i, p in enumerate(regime["prompts"])
            ]
            eng.generate_blocking(warm)
            _reset_stats(eng)
            eng.retained_len[:] = 0
            reqs = [
                GenRequest(rid=f"m{i}", input_ids=list(p),
                           max_new_tokens=gen_tokens, temperature=0.0)
                for i, p in enumerate(regime["prompts"])
            ]
            for r in reqs:
                eng.submit(r)
            eng.step()  # admission (prefill) outside the decode timing
            t0 = time.perf_counter()
            delivered = 0
            while any(not r.stop_reason for r in reqs):
                delivered += eng.step()
            dt = time.perf_counter() - t0
            streams[mode] = [(tuple(r.output_tokens),
                              tuple(r.output_logprobs)) for r in reqs]
            res[mode] = {
                "tokens_per_sec": round(delivered / dt, 1),
                "wall_s": round(dt, 2),
                "decode_calls": eng.stats["decode_calls"],
                "verify_calls": eng.stats["verify_calls"],
                "ragged_dispatches": eng.stats["ragged_dispatches"],
                "ragged_attended_pages": eng.stats["ragged_attended_pages"],
            }
            print(f"ragged_ab {name}/{mode}: {res[mode]}", file=sys.stderr,
                  flush=True)
            del eng
        res["streams_bit_identical"] = streams["dense"] == streams["ragged"]
        dn, rg = res["dense"], res["ragged"]
        res["dispatches_dense"] = dn["decode_calls"] + dn["verify_calls"]
        res["dispatches_ragged"] = rg["decode_calls"] + rg["verify_calls"]
        res["dispatch_reduction"] = round(
            1 - res["dispatches_ragged"] / max(1, res["dispatches_dense"]), 4)
        res["ragged_over_dense_tok_s"] = round(
            rg["tokens_per_sec"] / max(dn["tokens_per_sec"], 1e-9), 3)
        out[name] = res
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--slots", default="8,32,64,128,256")
    p.add_argument("--skip-decode", action="store_true")
    p.add_argument("--skip-prefill", action="store_true")
    p.add_argument("--skip-multi-turn", action="store_true")
    p.add_argument("--skip-group", action="store_true")
    p.add_argument("--skip-ceiling-ab", action="store_true")
    # tiered-decode ceiling A/B knobs (ISSUE 5 acceptance: large ceiling
    # within 10% of small on the same workload)
    p.add_argument("--ab-slots", type=int, default=16)
    p.add_argument("--ab-ceilings", default="4096,16384")
    p.add_argument("--ab-prompt", type=int, default=64)
    p.add_argument("--ab-gen", type=int, default=128)
    p.add_argument("--ab-tiers", type=int, default=1)
    p.add_argument("--no-decode-window", action="store_true",
                   help="A/B with the window disabled (reproduces the "
                        "pre-ISSUE-5 ceiling-bound decode)")
    # speculative decode knobs (ISSUE 12)
    p.add_argument("--spec-decode", action="store_true",
                   help="run the decode curve with self-speculative "
                        "decoding (prompt-lookup drafts) enabled")
    p.add_argument("--draft-len", type=int, default=31,
                   help="pin the draft length D (0 = adaptive ladder); the "
                        "A/B wants D comfortably above the decode chunk so "
                        "one verify dispatch commits more than one chunk")
    p.add_argument("--ab-spec", action="store_true",
                   help="spec-on/off A/B on the repetition-heavy workload "
                        "(ISSUE 12 acceptance: >= 1.4x decode tok/s on CPU)")
    p.add_argument("--spec-slots", type=int, default=8)
    p.add_argument("--spec-gen", type=int, default=128)
    # ragged paged-decode kernel A/B (ISSUE 19 acceptance)
    p.add_argument("--ab-ragged", action="store_true",
                   help="ragged-vs-dense decode A/B on the mixed-length "
                        "and repetition workloads (ISSUE 19 acceptance: "
                        "bit-identical streams, strictly fewer "
                        "decode+verify dispatches; CPU numbers run the "
                        "kernel in Pallas interpret mode)")
    p.add_argument("--ragged-slots", type=int, default=8)
    p.add_argument("--ragged-gen", type=int, default=96)
    # group fan-out regime knobs (GRPO-shaped grouped admission)
    p.add_argument("--group-size", type=int, default=8)
    p.add_argument("--group-prompt", type=int, default=256)
    p.add_argument("--n-groups", type=int, default=6)
    # multi-turn regime knobs — the published figures are reproduced with:
    #   decode-dominated floor: --turn-prompt 64  --turns 3 --mt-max-seq-len 1024
    #   prefill-dominated:      --turn-prompt 512 --turns 4 --mt-max-seq-len 4096
    p.add_argument("--turn-prompt", type=int, default=512)
    p.add_argument("--turns", type=int, default=4)
    p.add_argument("--turn-gen", type=int, default=32)
    p.add_argument("--mt-max-seq-len", type=int, default=4096)
    p.add_argument("--model", default="qwen25_1p5b",
                   choices=["qwen25_1p5b", "tiny"],
                   help="tiny = CPU smoke mode (token accounting only)")
    p.add_argument("--telemetry-dir", default="",
                   help="enable unified telemetry (utils/telemetry.py) and "
                        "dump events.jsonl + the gen registry snapshot here")
    args = p.parse_args()

    from areal_tpu.utils import telemetry

    if args.telemetry_dir:
        os.makedirs(args.telemetry_dir, exist_ok=True)
        telemetry.set_enabled(True)

    import jax

    from areal_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()

    cfg, params = serving_model_setup(args.model)
    result = {"model": args.model, "device_kind": jax.devices()[0].device_kind}
    if not args.skip_decode:
        result["decode"] = bench_decode(
            cfg, params, [int(s) for s in args.slots.split(",")],
            spec_decode=args.spec_decode, draft_len=args.draft_len,
        )
    if not args.skip_prefill:
        result["prefill"] = bench_prefill(cfg, params)
    if not args.skip_multi_turn:
        result["multi_turn"] = bench_multi_turn(
            cfg, params, turns=args.turns, turn_prompt=args.turn_prompt,
            turn_gen=args.turn_gen, max_seq_len=args.mt_max_seq_len,
        )
    if not args.skip_group and args.group_size > 1:
        result["grouped"] = bench_group_fanout(
            cfg, params, group_size=args.group_size,
            n_groups=args.n_groups, prompt_len=args.group_prompt,
        )
    if args.ab_spec:
        result["spec_ab"] = bench_spec_decode_ab(
            cfg, params, n_slots=args.spec_slots,
            gen_tokens=args.spec_gen, draft_len=args.draft_len,
        )
    if args.ab_ragged:
        result["ragged_ab"] = bench_ragged_ab(
            cfg, params, n_slots=args.ragged_slots,
            gen_tokens=args.ragged_gen, draft_len=args.draft_len,
        )
    if not args.skip_ceiling_ab:
        result["decode_ceiling_ab"] = bench_decode_ceiling_ab(
            cfg, params, n_slots=args.ab_slots,
            ceilings=tuple(int(c) for c in args.ab_ceilings.split(",")),
            prompt_len=args.ab_prompt, gen_tokens=args.ab_gen,
            tiers=args.ab_tiers, window=not args.no_decode_window,
        )
    if args.telemetry_dir:
        events_path = os.path.join(args.telemetry_dir, "events.jsonl")
        snap_path = os.path.join(args.telemetry_dir, "metrics.json")
        n_events = telemetry.EVENTS.dump_jsonl(events_path)
        with open(snap_path, "w") as f:
            json.dump({"gen": telemetry.GEN.snapshot()}, f, indent=2,
                      default=str)
        result["telemetry"] = {
            "dir": args.telemetry_dir,
            "events_jsonl": events_path,
            "metrics_snapshot": snap_path,
            "n_events": n_events,
        }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
