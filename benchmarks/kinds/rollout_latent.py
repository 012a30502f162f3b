"""Kind `rollout_latent`: kind `rollout`'s closed loop for a configuration of
the `longcat_flash` family (LongCat-Flash, the language model of
LongCat-Flash-Omni): every layer two latent-attention sublayers and two
dense FFNs around one expert layer on a shortcut, of whose routed experts
this program holds a share (`experts_held`) beside identity experts every
share computes alike, checked against the configuration's own plain
reference (`bench.reference`, `lib/reference_longcat_flash.py`).

`ClosedLoop` and `build_engine` are `kinds/rollout.py`'s as they are (as
`kinds/rollout_ref.py` and `kinds/rollout_hybrid.py` take them); `run` and
`check_requests` are repeated here for the same reason as there.  What a
reader of the benchmark needs to know of this kind:

- the first thing `run` does is to see that the model built from the
  configuration's file IS latent attention over the file's latent widths
  around the file's experts.  A program that does not know the family
  cannot build it (`from_hf` raises: the run ends at once, exit code 1);
  one that builds something else under the name is stopped with exit code 4
  and no result;
- before anything is timed the engine's pool is looked at: every slot holds,
  for every position, one row of `kv_lora_rank + qk_rope_head_dim` values in
  each of the 2 x `num_layers` attention sublayers, in the dtype the cell's
  files state (the workload's `engine.kv_dtype` where it states one, a
  control's; else the configuration's `bench.cache_dtype`), and one scratch
  row.  A narrower, a wider (head-expanded) or a shorter pool is another
  deployment, not a faster one: exit code 5;
- the weights are the program's own draw from `--seed` but for two things
  (`trained_like_draw`): the norm weights of the two latents are drawn at
  sqrt(rank / hidden), so that the scaled latents have unit variance as a
  trained network's do (with them at one, attention is nearly an arg-max
  and rounding is amplified layer by layer until the comparison can tell
  nothing); and the router's selection bias is drawn small and non-zero
  (normal, 0.04 of an even router's probability), so that choosing by
  p + b and weighting by p differ and the choice still follows the token;
  every other weight is the program's own draw from `--seed`;
- `rollout_tokens_per_s` follows the held experts a pass touches (0.42 % an
  expert-pass: 25.2 to 28.7 of 64 by run), and those follow the run's
  PROMPTS: a random network's first attention sublayer puts out ten times
  the embedding, so a token's stream is mostly its prompt's average, the
  members of a group route alike, and ten groups a window do not average
  that out.  Drawing the weights from `size_seed` changed nothing (0.97 %
  of spread over six seeds against 0.89 % over eight: my chip runs, PR 44,
  `PERF.md` section 6), so they stay with `--seed`;
- a group's ONE prompt is prefilled once (expanded attention, a block of
  queries at a time) and its latent rows are copied to the seven siblings,
  which compute their last prompt token on the copy (absorbed attention);
  decode reads every live slot's rows absorbed.  So both attention forms
  and both expert regimes (64-128 rows an expert in a prefill, under one in
  a decode pass) run in one window;
- the cell's `trace_seconds` is the whole measured window: every group
  holds a budget of the top eighth and the ramp starts all the groups in
  flight at once, so the window's admissions all come in its last third
  (after the first group's longest member ends); a shorter traced window
  holds decode chunks only and the inherited `rollout_shared_prefill_pct`
  has nothing to read there;
- the engine's pool is freed (not its parameters) before the float32
  reference runs, one sequence and one sub-block at a time;
- the reference routes for itself: a routing choice that flips on rounding
  is part of what the log-probs differ by;
- `correct`: the log-probs the engine returned for the sampled tokens of
  `check.requests` finished requests (prefill, the copy, then decode through
  the latent cache) against the reference's full forward, mean |d| under
  `check.tol_mean`; and every request of the window finished at its budget
  with one log-prob a token.

The cell's `work` hands the configuration's name to `lib/latent_work.py`;
`counters` gain `expert_slots` (decode passes x expert layers x experts
held: what `experts_touched` is a share of).
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader

# the published keys shrunk for the CPU rehearsal (on top of run.py's
# REHEARSAL_HF, which knows only a dense decoder's keys)
REHEARSAL_HF = {
    "num_layers": 2, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "experts_held": {"first": 2, "of": 8}, "zero_expert_num": 4,
    "moe_topk": 3,
}


def trained_like_draw(params, hf, seed):
    """The program's own draw from `--seed`, but for two things a trained
    network has and a random one lacks:

    - the norm weights of the two latents at sqrt(rank / hidden), so that a
      normed latent times its weight times the model's `mla_scale_*` factor
      sqrt(hidden / rank) has unit variance (`bench.assumed.
      latent_norm_draw`).  With every norm weight one the factors 2 and 3.46
      give attention scores a spread of 5.8 where a softmax decoder's have
      one: attention is then nearly an arg-max, one rounding that flips it
      changes a head's whole output, and the log-probs of the bfloat16
      program leave the float32 reference's by 0.4-0.7 in prefill and
      decode alike, at 1k and at 4k positions, where the same program with
      the factors off reads 0.011-0.013 (my chip run, PR 44, `PERF.md`
      section 6): the comparison could tell nothing;
    - `router_bias` normal at the assumed spread (`bench.assumed.
      e_score_correction_bias`): 0.04 of an even router's probability
      1 / outputs, which is what the 0.02 the other two mixture-of-experts
      configurations draw is to a sigmoid's 1 / 2.  At 0.02 itself, fifteen
      times a softmax's typical probability over 768 outputs, every token
      chose the twelve largest biases (my chip run, PR 44: 3 % of the held
      experts touched a pass)."""
    import jax
    import jax.numpy as jnp

    assumed = hf["bench"]["assumed"]
    layers = dict(params["layers"])
    moe, attn = dict(layers["moe"]), dict(layers["attn"])
    spread = assumed["e_score_correction_bias"]["spread"]
    moe["router_bias"] = spread / moe["router_bias"].shape[-1] * jax.random.normal(
        jax.random.fold_in(seed, 0x1d), moe["router_bias"].shape, jnp.float32)
    if assumed["latent_norm_draw"]["unit_variance"]:
        D = hf["hidden_size"]
        for leaf, rank, on in (
                ("q_norm", hf["q_lora_rank"], hf.get("mla_scale_q_lora")),
                ("kv_norm", hf["kv_lora_rank"], hf.get("mla_scale_kv_lora"))):
            if on:
                attn[leaf] = (attn[leaf].astype(jnp.float32)
                              * (rank / D) ** 0.5).astype(attn[leaf].dtype)
    return {**params, "layers": {**layers, "moe": moe, "attn": attn}}


def model_as_stated(cfg, hf):
    """'' if the model the program built is the file's, else what differs."""
    want = {
        "attn_kind": "latent",
        "num_layers": hf["num_layers"],
        "num_heads": hf["num_attention_heads"],
        "q_lora_rank": hf["q_lora_rank"],
        "kv_lora_rank": hf["kv_lora_rank"],
        "qk_nope_head_dim": hf["qk_nope_head_dim"],
        "qk_rope_head_dim": hf["qk_rope_head_dim"],
        "v_head_dim": hf["v_head_dim"],
        "intermediate_size": hf["ffn_hidden_size"],
        "moe_intermediate_size": hf["expert_ffn_hidden_size"],
        "num_experts": (hf.get("experts_held") or {}).get(
            "of", hf["n_routed_experts"]),
        "zero_expert_num": hf["zero_expert_num"],
        "num_experts_per_tok": hf["moe_topk"],
    }
    got = {k: getattr(cfg, k, None) for k in want}
    if got != want:
        return f"built {got}, the file states {want}"
    lo, hi = cfg.held_range
    if hi - lo != hf["n_routed_experts"]:
        return f"{hi - lo} experts held, not {hf['n_routed_experts']}"
    return ""


def pool_as_stated(cache, hf, e, n_slots, max_seq_len):
    """'' if the engine's pool is what the cell's files state, else what
    differs: latent rows only, `lib/latent_work.py cache_bytes_per_token`
    a position at the stated dtype, `max_seq_len` positions a slot, one
    scratch row."""
    import jax.numpy as jnp

    from benchmarks.lib import latent_work as lw

    want_dt = jnp.dtype(e.get("kv_dtype", hf["bench"]["cache_dtype"]))
    if any(jnp.dtype(a.dtype) != want_dt for a in cache.values()):
        return (f"pool leaves {({k: str(a.dtype) for k, a in cache.items()})}"
                f", not {want_dt}")
    got = sum(int(a.nbytes) for a in cache.values())
    want = ((n_slots + 1) * max_seq_len
            * lw.cache_values_per_token(hf) * want_dt.itemsize)
    if got != want:
        return (f"pool holds {got} bytes in leaves "
                f"{({k: tuple(a.shape) for k, a in cache.items()})}, not "
                f"{lw.cache_values_per_token(hf)} values a position for "
                f"{n_slots} + 1 rows of {max_seq_len} ({want} bytes)")
    return ""


def check_requests(reference, eng_params, hf, chk, finished, rehearsal):
    """The log-prob the engine returned for each sampled token (prefill or
    the copy of a shared prompt's rows, then decode through the latent
    cache) against the float32 reference's for the same prefix, on a few
    finished requests spread over the lengths."""
    from benchmarks.lib.reference import compare_logprobs

    done = sorted((r for r in finished if r.stop_reason == "length"
                   and len(r.output_tokens) >= 2
                   and len(r.output_logprobs) == len(r.output_tokens)),
                  key=lambda r: len(r.input_ids) + len(r.output_tokens))
    k = int(chk["requests"])
    if len(done) < k:
        return False, {"n": 0, "why": f"only {len(done)} finished requests"}
    pick = [done[int((i + 0.5) * len(done) / k)] for i in range(k)]
    cap = int(chk["max_tokens"])
    T = min(cap, max(len(r.input_ids) + len(r.output_tokens) for r in pick))
    ids = np.zeros((k, T), np.int32)
    got = np.zeros((k, T - 1), np.float32)
    mask = np.zeros((k, T - 1), bool)
    for i, r in enumerate(pick):
        seq = (list(r.input_ids) + list(r.output_tokens))[:T]
        ids[i, : len(seq)] = seq
        P = len(r.input_ids)
        n_out = len(seq) - P
        # output token j sits at position P + j; its log-prob is predicted
        # at position P + j - 1
        got[i, P - 1: P - 1 + n_out] = r.output_logprobs[:n_out]
        mask[i, P - 1: P - 1 + n_out] = True
    want = np.asarray(reference.next_token_logprobs(eng_params, hf, ids))
    # tolerance: bfloat16 weights, activations and latent rows, the router's
    # scores and every softmax in float32, against the float32 reference;
    # the readings it lies between are in the workload's `check.why`
    tol_mean = 1e-4 if rehearsal else chk["tol_mean"]
    ok, rep = compare_logprobs(got, want, mask, tol_mean, float("inf"))
    del rep["tol_max"]
    rep["lengths"] = [len(r.input_ids) + len(r.output_tokens) for r in pick]
    rep["cache_hit_tokens"] = [int(r.cache_hit_tokens) for r in pick]
    return ok, rep


def run(cell, hf, bench):
    from areal_tpu.models.model_config import TransformerConfig

    if bench.rehearsal:
        hf = {**hf, **REHEARSAL_HF}
    model_cfg = TransformerConfig.from_hf(hf)
    wrong = model_as_stated(model_cfg, hf)
    if wrong:
        print(f"benchmark: {wrong}; configuration {hf['bench']['name']!r} "
              "states latent attention in double layers; no result",
              file=sys.stderr, flush=True)
        sys.exit(4)

    import jax

    from areal_tpu.gen.engine import GenRequest
    from areal_tpu.models import init_params
    from benchmarks.lib import device, engine_warm, stats, traffic as tg
    from benchmarks.lib.reference import compared

    root = bench.args.bench_root
    rollout = loader._load_module("kinds", "rollout", root)
    reference = loader._load_module("lib", hf["bench"]["reference"], root)

    tr, e = cell["traffic"], dict(cell["engine"])
    dtype = hf["bench"]["dtype"]
    if bench.rehearsal:
        # float32 throughout, the pool too unless the file states one (a
        # control's): the rehearsal checks the comparison itself (positions,
        # masks), which then has to be exact
        e = {"kv_dtype": "float32", **e, "n_slots": tr["n_slots"],
             "max_seq_len": tr["max_seq_len"], "dtype": "float32"}
        dtype = "float32"
    else:
        e.setdefault("kv_dtype", hf["bench"]["cache_dtype"])
    model_cfg = model_cfg.replace(
        dtype=dtype, param_dtype=dtype, remat=False, eos_token_id=None)
    t0 = time.perf_counter()
    seed = device.jax_seed(bench.args.seed)
    params = jax.jit(
        lambda k: trained_like_draw(init_params(model_cfg, k), hf, k))(seed)
    jax.block_until_ready(params)
    eng = rollout.build_engine(model_cfg, params, e, bench.args.seed)
    init_s = time.perf_counter() - t0
    n_slots, max_seq_len = int(e["n_slots"]), int(e["max_seq_len"])
    wrong = pool_as_stated(eng.cache, hf, e, n_slots, max_seq_len)
    if wrong:
        print(f"benchmark: {wrong}; no result", file=sys.stderr, flush=True)
        sys.exit(5)

    def make_groups(cycle):
        return tg.rollout_groups(tr, hf["vocab_size"],
                                 [int(bench.args.seed), cycle])

    loop = rollout.ClosedLoop(eng, make_groups, int(tr["groups_in_flight"]),
                              float(tr["temperature"]))
    t0 = time.perf_counter()
    plan = engine_warm.warm_closed_loop(
        eng, GenRequest, hf["vocab_size"], bench.args.seed, tr, loop.groups)
    warm_s = time.perf_counter() - t0
    warm_compiles = bench.compiles.snapshot()

    # the ramp fills the empty engine and takes a fixed number of engine
    # steps, so that a run that compiles opens its window in the same state
    t0 = time.perf_counter()
    loop.run(until_steps=int(tr["ramp_steps"]))
    ramp_s = time.perf_counter() - t0
    ramp_done = len(loop.finished)
    bench.diag(phase="setup", init_s=init_s, warm_s=warm_s, ramp_s=ramp_s,
               plan=plan, warm_compiles=warm_compiles,
               ramp_finished=ramp_done,
               pool_bytes=sum(int(a.nbytes) for a in eng.cache.values()),
               ramp_compiles=bench.compiles.snapshot())

    seconds = bench.window_seconds(cell)
    stats0 = dict(eng.stats)
    t_open = bench.open_window()
    delivered = loop.run(until_s=t_open + seconds, spans=bench.spans)
    window_s = bench.close_window()
    counters = {k: eng.stats[k] - stats0.get(k, 0) for k in eng.stats
                if isinstance(eng.stats[k], (int, float))}
    lo, hi = model_cfg.held_range
    counters["expert_slots"] = (
        counters.get("decode_passes", 0) * model_cfg.num_layers * (hi - lo))
    in_window = loop.finished[ramp_done:]
    # the engine's own peak: the reference that follows has another
    peak_at_close = device.memory_peak_bytes()
    # stops what is in flight and gives the pool's memory back; the
    # parameters stay for the reference
    eng.release_memory(drop_params=False)

    tpot = rollout.tpot_ms(in_window, t_open)
    budget_of = loop.budget_of
    bad = [r.rid for r in in_window
           if r.stop_reason != "length"
           or len(r.output_tokens) != budget_of[r.rid]
           or len(r.output_logprobs) != len(r.output_tokens)]
    ok_ref, ref_report = check_requests(
        reference, eng.params, hf, cell["check"], loop.finished,
        bench.rehearsal)
    dispatches = (counters.get("decode_calls", 0)
                  + counters.get("prefill_calls", 0)
                  + counters.get("suffix_calls", 0))
    return {
        "correct": ok_ref and not bad and bool(in_window),
        "attempted": len(in_window),
        "failed": len(bad),
        "metrics": {
            "rollout_tokens_per_s": (delivered / window_s, "tokens/s"),
        },
        "counts": {"dispatches": dispatches, "output_tokens": delivered,
                   "requests": len(in_window)},
        "counters": counters,
        # what the byte functions of lib/latent_work.py are given
        "work": {"n_slots": n_slots, "config": hf["bench"]["name"]},
        "compared": compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "bad_requests": bad[:8],
                   "tpot_ms": stats.dist_summary(tpot),
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "memory_peak_bytes_at_window_close": peak_at_close,
                   "decode_path": "latent pool (one row a position and "
                                  "sublayer), absorbed attention through "
                                  "the paged kernel ops/latent_decode.py "
                                  "where ragged_dispatches counts it, else "
                                  "windowed decode programs",
                   "counters": counters},
    }
