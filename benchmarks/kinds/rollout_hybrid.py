"""Kind `rollout_hybrid`: kind `rollout`'s closed loop for a configuration
of the `nemotron_h` family: a stack of Mamba-2, attention and latent
mixture-of-experts blocks, of whose routed experts this program holds a
share (`experts_held`), checked against the configuration's own plain
reference (`bench.reference`, `lib/reference_nemotron_h.py`).

`ClosedLoop` and `build_engine` are `kinds/rollout.py`'s as they are (as
`kinds/rollout_ref.py` takes them); `run` and `check_requests` are repeated
here for the same reason as there.  What differs:

- the first thing `run` does is to see that the model built from the
  configuration's file IS a stack of the file's block kinds holding the
  file's experts.  A program that does not know the family cannot build it
  (`from_hf` raises: the run ends at once, exit code 1); one that builds
  something else under the name is stopped with exit code 4 and no result;
- a program whose pool does not hold, for every slot, the float32 state
  and the window of every Mamba block and the K/V rows of every attention
  block at the stated sizes is stopped with exit code 5 before anything is
  timed: a narrower state is another deployment, not a faster one;
- the weights are drawn as a network of this family starts out
  (`trained_like_draw`: unit-variance embedding, projections back into the
  residual stream at 1 / sqrt(88) as `rescale_prenorm_residual` has them):
  with every block's output as large as the stream it is added to, one
  routing choice that flips on rounding moves the log-probs more than a
  bfloat16 state would, and the comparison could tell nothing (PERF.md,
  PR 32); and the router's selection bias is drawn small and non-zero, so
  that choosing by score + bias and weighting by score differ;
- the engine's state pool is freed (not its parameters) before the float32
  reference runs;
- the reference routes for itself: a routing choice that flips on rounding
  is part of what the log-probs differ by (how many flip, and what that
  costs, was measured once: PERF.md, PR 32);
- `correct` holds TWO comparisons with the reference, because the log-probs
  do not see how the state is kept (a bfloat16 state pool reads inside the
  float32 pool's range there): the log-probs of finished requests
  (`check.tol_mean`; a bfloat16 router fails it), and the recurrent STATE
  the program left in the pool for live sequences against the reference's
  own recurrence over the same ids (`check.tol_state`; a bfloat16 pool, or
  a float32 pool updated in bfloat16, fails it).

The cell's `work` hands `n_slots` and the configuration's name to
`lib/hybrid_work.py`; `counters` gain `expert_slots` (decode passes x
expert blocks x experts held: what `experts_touched` is a share of).  The
slots live in a pass are the engine's own `tokens_delivered` over
`decode_passes`, as in the other rollout cells.
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader

# the published keys shrunk for the CPU rehearsal (on top of run.py's
# REHEARSAL_HF, which knows only a dense decoder's keys)
REHEARSAL_HF = {
    "num_hidden_layers": 5, "hybrid_override_pattern": "ME*ME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "chunk_size": 8, "n_routed_experts": 4,
    "experts_held": {"first": 0, "of": 8}, "num_experts_per_tok": 3,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96,
}


def trained_like_draw(params, hf, seed):
    """The drawn weights as a network of this family starts out, so that a
    block is a small update of the residual stream and rounding is not
    amplified block by block (`bench.assumed.residual_draw`): the embedding
    at unit variance, and every block's projection back into the residual
    stream (Mamba `w_out`, attention `wo`, the latent `w_l2`, the shared
    expert's `ws2`) at 1 / sqrt(published depth) of the fan-in scale, which
    is what the configuration's `rescale_prenorm_residual: true` does to
    out projections.  And `router_bias` normal at the assumed spread
    (`bench.assumed.e_score_correction_bias`).  `dt_bias`, `A_log` and `D`
    stay as Mamba-2 initialises them (`init_params`): the state's precision
    is held by comparing the state itself (`check_states`), not by a draw
    that would carry it into the log-probs (PERF.md, PR 32)."""
    import jax
    import jax.numpy as jnp

    assumed = hf["bench"]["assumed"]
    f32 = jnp.float32
    down = f32(1.0 / np.sqrt(assumed["residual_draw"]["value"]["depth"]))
    D = int(hf["hidden_size"])

    def scaled(a, by):
        return (a.astype(f32) * by).astype(a.dtype)

    layers = {k: dict(v) for k, v in params["layers"].items()}
    layers["M"]["w_out"] = scaled(layers["M"]["w_out"], down)
    layers["*"]["attn"] = {**layers["*"]["attn"],
                           "wo": scaled(layers["*"]["attn"]["wo"], down)}
    moe = layers["E"]
    moe["w_l2"], moe["ws2"] = scaled(moe["w_l2"], down), scaled(moe["ws2"], down)
    std = float(assumed["e_score_correction_bias"]["value"]["std"])
    moe["router_bias"] = std * jax.random.normal(
        jax.random.fold_in(seed, 32), moe["router_bias"].shape, f32)
    return {**params, "layers": layers,
            "embedding": scaled(params["embedding"], jnp.sqrt(f32(D)))}


def built_as_stated(model_cfg, hf):
    """'' if the program's model is the file's stack holding the file's
    experts, else what differs."""
    kinds = getattr(model_cfg, "layer_kinds", None)
    if kinds is None or "".join(kinds) != hf["hybrid_override_pattern"]:
        return (f"block kinds {kinds!r}, not the pattern "
                f"{hf['hybrid_override_pattern']!r}")
    share = hf.get("experts_held") or {"first": 0, "of": hf["n_routed_experts"]}
    want = (share["first"], share["first"] + hf["n_routed_experts"])
    got = tuple(getattr(model_cfg, "held_range", ()))
    if got != want or model_cfg.num_experts != share["of"]:
        return (f"experts {got} of {getattr(model_cfg, 'num_experts', None)} "
                f"held, not {want} of {share['of']}")
    return ""


def pool_as_stated(cache, hf, n_slots, max_seq_len):
    """'' if the engine's pool is what the configuration states, else what
    differs: `s` in `bench.state_dtype` and `c`, `lib/hybrid_work.py
    state_bytes_per_slot` a slot together; `k`, `v` at `kv_bytes_per_token`
    a position; every leaf with one scratch row."""
    from benchmarks.lib import hybrid_work as hw

    if sorted(cache) != ["c", "k", "s", "v"]:
        return f"pool leaves {sorted(cache)}, not ['c', 'k', 's', 'v']"
    want_dt = np.dtype(hf["bench"]["state_dtype"])
    if np.dtype(cache["s"].dtype) != want_dt:
        return f"pool leaf 's' is {cache['s'].dtype}, not {want_dt}"
    rows = {int(a.shape[1]) for a in cache.values()}
    if len(rows) != 1 or min(rows) < n_slots:
        return f"pool rows {sorted(rows)} for {n_slots} slots"
    n = min(rows)
    state = int(cache["s"].nbytes) + int(cache["c"].nbytes)
    if state != n * hw.state_bytes_per_slot(hf):
        return (f"state and windows hold {state} bytes, not "
                f"{hw.state_bytes_per_slot(hf)} a slot in {n} rows")
    kv = int(cache["k"].nbytes) + int(cache["v"].nbytes)
    if kv != n * max_seq_len * hw.kv_bytes_per_token(hf):
        return (f"keys and values hold {kv} bytes, not "
                f"{hw.kv_bytes_per_token(hf)} a position")
    return ""


def pooled_states(eng, k):
    """Before the pool is freed: for the k live slots whose state holds the
    most tokens, (the ids the state has taken in, the slot's state of
    every Mamba block [n_ssm, H, P, N] as float32 on the host).  Between
    two engine steps a live slot's cache holds its first `lengths` tokens
    (the pending sampled token is not in it yet)."""
    live = sorted((s for s, r in enumerate(eng.slot_req) if r is not None),
                  key=lambda s: -int(eng.lengths[s]))[:k]
    return [(np.array(eng.seq_tokens[s, : int(eng.lengths[s])], np.int32),
             np.asarray(eng.cache["s"][:, eng.pool.row(s)], np.float32))
            for s in live]


def check_states(reference, eng_params, hf, chk, pooled, rehearsal):
    """The recurrent state the program left in the pool for a few live
    sequences (prefill or the fan-out copy, then hundreds of decode steps)
    against the state of the float32 reference's own recurrence over the
    same ids: each head's |difference| over |reference|, averaged over the
    quarter of a block's heads that remember longest and over the slots.
    The limit holds the FIRST block's reading: its input is the embedding
    itself, with no routing choice upstream, so what it reads is the
    bfloat16 activations against how the state is kept and summed.  The
    later blocks' readings are reported."""
    if len(pooled) < int(chk["state_slots"]):
        return False, {"n": 0, "why": f"only {len(pooled)} live slots"}
    lens = [len(ids) for ids, _ in pooled]
    ids = np.zeros((len(pooled), max(lens)), np.int32)
    for i, (seq, _) in enumerate(pooled):
        ids[i, : len(seq)] = seq
    want = []
    reference.hidden_states(eng_params, hf, ids, want, lens)
    slow, every = [], []
    for j, w in enumerate(want):
        err = reference.state_error(np.stack([s[j] for _, s in pooled]), w)
        slow.append(float(err[:, reference.slow_heads(eng_params, j)].mean()))
        every.append(float(err.mean()))
    tol = 1e-4 if rehearsal else chk["tol_state"]
    ok = bool(np.isfinite(slow).all() and slow[0] <= tol)
    return ok, {"n": len(pooled), "lengths": lens, "tol_state": tol,
                "slow_heads_rel_err": slow, "all_heads_rel_err": every}


def check_requests(reference, eng_params, hf, chk, finished, rehearsal):
    """The log-prob the engine returned for each sampled token (prefill, the
    fan-out copy of state, window and K/V, then decode through the cache)
    against the float32 reference's full forward pass of the same ids, on a
    few finished requests spread over the lengths, four a pass of the
    reference."""
    done = sorted((r for r in finished if r.stop_reason == "length"
                   and len(r.output_tokens) >= 2),
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
    want = np.concatenate([
        np.asarray(reference.next_token_logprobs(eng_params, hf, ids[i: i + 4]))
        for i in range(0, k, 4)])
    # tolerance: bfloat16 weights, activations, convolution window and K/V,
    # the recurrent state float32 and summed in float32, the router's
    # scores float32, against the float32 reference, which routes for
    # itself.  The readings that set the limit (the stated program over its
    # seeds; a bfloat16 router) are in PERF.md, Findings of PR 32, and
    # beside the limit in the workload file.  No limit on the largest
    # difference: it is one routing choice that flips on rounding at one
    # position, the same in the program and in every control.
    tol_mean = 1e-4 if rehearsal else chk["tol_mean"]
    ok, rep = reference.compare_logprobs(got, want, mask, tol_mean,
                                         float("inf"))
    del rep["tol_max"]
    rep["lengths"] = [len(r.input_ids) + len(r.output_tokens) for r in pick]
    rep["cache_hit_tokens"] = [int(r.cache_hit_tokens) for r in pick]
    return ok, rep


def run(cell, hf, bench):
    from areal_tpu.models.model_config import TransformerConfig

    if bench.rehearsal:
        # float32 throughout, the window and the K/V rows too
        hf = {**hf, **REHEARSAL_HF,
              "bench": {**hf["bench"], "dtype": "float32"}}
    # a program that does not know the family raises here: no result
    model_cfg = TransformerConfig.from_hf(hf)
    wrong = built_as_stated(model_cfg, hf)
    if wrong:
        print(f"benchmark: this program builds {wrong} from configuration "
              f"{hf['bench']['name']!r}; no result", file=sys.stderr, flush=True)
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
        # float32 throughout: the rehearsal checks the comparison itself
        # (positions, masks), which then has to be exact
        e.update(n_slots=tr["n_slots"], max_seq_len=tr["max_seq_len"],
                 dtype="float32", kv_dtype="float32")
        dtype = "float32"
    model_cfg = model_cfg.replace(
        dtype=dtype, param_dtype=dtype, remat=False, eos_token_id=None)
    t0 = time.perf_counter()
    key = device.jax_seed(bench.args.seed)
    params = jax.jit(
        lambda k: trained_like_draw(init_params(model_cfg, k), hf, k))(key)
    jax.block_until_ready(params)
    eng = rollout.build_engine(model_cfg, params, e, bench.args.seed)
    init_s = time.perf_counter() - t0
    n_slots = int(e["n_slots"])
    wrong = pool_as_stated(eng.cache, hf, n_slots, int(e["max_seq_len"]))
    if wrong:
        print(f"benchmark: {wrong}; configuration {hf['bench']['name']!r} "
              f"states a {hf['bench']['state_dtype']} state; no result",
              file=sys.stderr, flush=True)
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
    counters["expert_slots"] = (
        counters.get("decode_passes", 0)
        * hf["hybrid_override_pattern"].count("E") * hf["n_routed_experts"])
    in_window = loop.finished[ramp_done:]
    # the engine's own peak: the reference that follows has another
    peak_at_close = device.memory_peak_bytes()
    # the state comparison wants `state_slots` live sequences.  A group is
    # replaced only when its last member ends, so a small grid (the
    # rehearsal's) can be nearly empty after a step: step on, outside the
    # window, until that many are live (on the chip 50-128 always are)
    k_live = int(cell["check"]["state_slots"])
    for _ in range(64):
        if sum(r is not None for r in eng.slot_req) >= k_live:
            break
        loop.run(until_steps=1)
    pooled = pooled_states(eng, k_live)
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
    ok_state, state_report = check_states(
        reference, eng.params, hf, cell["check"], pooled, bench.rehearsal)
    dispatches = (counters.get("decode_calls", 0)
                  + counters.get("prefill_calls", 0)
                  + counters.get("suffix_calls", 0))
    return {
        "correct": ok_ref and ok_state and not bad and bool(in_window),
        "attempted": len(in_window),
        "failed": len(bad),
        "metrics": {
            "rollout_tokens_per_s": (delivered / window_s, "tokens/s"),
        },
        "counts": {"dispatches": dispatches, "output_tokens": delivered,
                   "requests": len(in_window)},
        "counters": counters,
        # what the byte functions of lib/hybrid_work.py are given
        "work": {"n_slots": n_slots, "config": hf["bench"]["name"]},
        "compared": {
            **compared(ref_report),
            **({"state_rel_err": {
                "value": state_report["slow_heads_rel_err"][0],
                "limit": state_report["tol_state"]}}
               if "tol_state" in state_report else {})},
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "state": state_report, "state_ok": ok_state,
                   "bad_requests": bad[:8],
                   "tpot_ms": stats.dist_summary(tpot),
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "memory_peak_bytes_at_window_close": peak_at_close,
                   "decode_path": "hybrid pool (state + window + K/V), "
                                  "windowed decode programs",
                   "counters": counters},
    }
