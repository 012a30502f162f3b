"""Kind `rollout_swa`: kind `rollout`'s closed loop for a configuration of
the `mimo_v2` family (MiMo-V2-Flash, the language model of MiMo-V2.5): full
attention layers that keep every position's key and value beside sliding
layers that keep a window, each kind with its own kv heads and rotary base,
a learned sink in the sliding softmax, a dense FFN in the leading layer and
sigmoid-routed gated experts in the others, of which this program holds a
share (`experts_held`), checked against the configuration's own plain
reference (`bench.reference`, `lib/reference_mimo_v2.py`).

`ClosedLoop` and `build_engine` are `kinds/rollout.py`'s as they are (as
`kinds/rollout_latent.py` takes them); `run` and `check_requests` are
repeated here for the same reason as there.  What a reader of the benchmark
needs to know of this kind:

- the first thing `run` does is to see that the model built from the
  configuration's file IS the file's: the two head layouts, the widths, the
  window, the two rotary bases, the value scale, the sink, the experts.  A
  program that does not know the family cannot build it (`from_hf` raises:
  the run ends at once, exit code 1); one that builds something else under
  the name is stopped with exit code 4 and no result;
- before anything is timed the engine's pool is looked at: every slot
  holds, for every position, one key and one value column in each FULL
  layer and, in each SLIDING layer, `sliding_window` positions (rounded up
  to eight) and no more, in the dtype the cell's files state (the workload's
  `engine.kv_dtype` where it states one, a control's; else the
  configuration's `bench.cache_dtype`), and one scratch row
  (`lib/swa_work.py pool_bytes`).  A pool that holds `max_seq_len` columns
  for a sliding layer, or a narrower one, is another deployment, not a
  faster one: exit code 5;
- the weights are the program's own draw from `--seed` but for four things
  (`trained_like_draw`; the embedding's scale and the held experts' centred
  router columns are there): the sinks of the sliding layers are drawn
  normal(`bench.assumed.sink_draw`), so that a sink takes a share of the
  softmax's mass a comparison can see (the program's initial zero takes
  under half a percent of a full window's), and the router's selection
  bias is drawn small and non-zero (`bench.assumed.e_score_correction_
  bias`), so that choosing by s + b and weighting by s differ;
- a group's ONE prompt is prefilled once into the representative's slot
  (the splash kernel under a causal or a local mask, the sinks passed in)
  up to its last token but one; the columns of the full layers and the
  rings of the sliding layers are copied to the seven siblings, and all
  eight compute the last prompt token on their copy; decode reads every
  live slot's columns by its block's bucket and its rings whole.  So the
  prompt, suffix and decode forms of both attention kinds and both expert
  regimes (hundreds of rows an expert in a prefill, about two in a decode
  pass) run in one window;
- the cell's `trace_seconds` is the whole measured window: every group
  holds a budget of the top eighth and the ramp starts all the groups in
  flight at once, so the window's admissions come after the first group's
  longest member ends; a shorter traced window holds decode chunks only and
  the inherited `rollout_shared_prefill_pct` has nothing to read there;
- the engine's pool is freed (not its parameters) before the float32
  reference runs, one sequence and one layer at a time;
- the reference routes for itself: a routing choice that flips on rounding
  is part of what the log-probs differ by;
- `correct`: the log-probs the engine returned for the sampled tokens of
  `check.requests` finished requests, the one on the LONGEST prompt among
  them (`check.long_prompt` positions at least), against the reference's
  full forward, mean |d| under `check.tol_mean`; and every request of the
  window finished at its budget with one log-prob a token.

The cell's `work` hands the configuration's name to `lib/swa_work.py`; the
counters are the engine's own (`expert_slots` among them).
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader

# the published keys shrunk for the CPU rehearsal (on top of run.py's
# REHEARSAL_HF, which knows only a dense decoder's keys), the ratios kept:
# a key of 24 beside a value of 16, 2 full + 5 sliding layers, a window of
# 8, 4 of 16 experts held
REHEARSAL_HF = {
    "num_hidden_layers": 7, "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "num_attention_heads": 8,
    "swa_num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "swa_head_dim": 24,
    "v_head_dim": 16, "swa_v_head_dim": 16, "sliding_window": 8,
    "sliding_window_size": 8, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "experts_held": {"first": 4, "of": 16},
    "num_experts_per_tok": 4,
}


def trained_like_draw(params, hf, seed):
    """The program's own draw from `--seed`, but for what a trained
    network has and a random one lacks:

    - the sliding layers' sinks normal(mean, std) of `bench.assumed.
      sink_draw`: the program starts them at zero, where exp(0) = 1 beside
      the hundred and more a full window's keys sum to is under half a
      percent of the mass, and a forward that left the sink out would read
      the same to the comparison's tolerance;
    - `router_bias` normal at `bench.assumed.e_score_correction_bias.std`:
      a hundredth of a sigmoid score's spread, non-zero so that selection
      (by score + bias) and weight (by score) differ.  The top 8 of 256 are
      a tail: at a tenth of the spread (the 0.02 the other sigmoid-routed
      configurations draw) an expert's share of the rows moves by a fifth,
      the held experts' share of all rows follows the draw, and
      `rollout_tokens_per_s` with it (2.3 % of spread over six seeds: my
      chip runs, PR 49); a trained model's bias is what EVENS the load;
    - the embedding times `bench.assumed.embedding_draw.scale`: the
      program draws its rows at norm one, under what a random first layer's
      attention adds (the average of a few hundred values), so the stream
      of a token is mostly its context's and the eight members of a group
      route alike; a window then holds about eight independent routings,
      the held experts a pass touches follow the run's PROMPTS, and
      `rollout_tokens_per_s` with them (1,869.8 and 1,848.8 tokens/s at
      50.5 and 53.9 % of the held experts touched: my chip runs, PR 49).
      At eight times that a token's own row outweighs its context's average
      from the shortest prompt on, as a trained network's does, and every
      slot routes for itself;
    - the HELD experts' router columns brought to one norm (the root mean
      square of all the layer's columns) and centred on their mean, and
      their biases centred (`bench.assumed.router_held_draw`): for every
      token the held experts' logits then sum to zero, so the share of the
      rows that this chip's experts take is the even `held / of` to first
      order whatever the seed, which is what a balanced router gives an
      expert-parallel rank.  As drawn, the share follows the seed (97.6 to
      101.9 assignments a pass where even is 100), the held experts a pass
      touches follow the share (56.2 to 57.9) and `rollout_tokens_per_s`
      them, by 0.5 % for every expert touched; centred, 101.6 to 103.6
      and 57.3 to 57.8 on six seeds, and what the rate still spreads by
      (0.35 to 0.48 %) is run-to-run (my chip runs, PR 49)."""
    import jax
    import jax.numpy as jnp

    assumed = hf["bench"]["assumed"]
    layers = dict(params["layers"])
    moe = dict(layers["moe"])
    bias = assumed["e_score_correction_bias"]["std"] * (
        jax.random.normal(jax.random.fold_in(seed, 0x1d),
                          moe["router_bias"].shape, jnp.float32))
    lo = int((hf.get("experts_held") or {}).get("first", 0))
    hi = lo + int(hf["n_routed_experts"])
    router = moe["router"].astype(jnp.float32)  # [layers, D, experts]
    norm = jnp.sqrt(jnp.mean(jnp.sum(router ** 2, axis=1), axis=-1))
    held = router[..., lo:hi]
    held = held / jnp.linalg.norm(held, axis=1, keepdims=True)
    held = held - jnp.mean(held, axis=-1, keepdims=True)
    # ONE factor for all the held columns, so that their sum stays zero
    held = held * (norm / jnp.sqrt(jnp.mean(
        jnp.sum(held ** 2, axis=1), axis=-1)))[:, None, None]
    moe["router"] = router.at[..., lo:hi].set(held).astype(
        moe["router"].dtype)
    moe["router_bias"] = bias.at[..., lo:hi].add(
        -jnp.mean(bias[..., lo:hi], axis=-1, keepdims=True))
    layers["moe"] = moe
    draw = assumed["sink_draw"]
    for i, kind in enumerate(("full", "sliding")):
        if "sink" in layers.get(kind, {}):
            shape = layers[kind]["sink"].shape
            layers[kind] = {**layers[kind], "sink": draw["mean"] + draw["std"]
                            * jax.random.normal(
                                jax.random.fold_in(seed, 0x51 + i), shape,
                                jnp.float32)}
    scale = assumed["embedding_draw"]["scale"]
    embedding = (params["embedding"].astype(jnp.float32) * scale).astype(
        params["embedding"].dtype)
    return {**params, "embedding": embedding, "layers": layers}


def model_as_stated(cfg, hf):
    """'' if the model the program built is the file's, else what differs."""
    want = {
        "attn_kind": "windowed",
        "num_layers": hf["num_hidden_layers"],
        "num_heads": hf["num_attention_heads"],
        "num_kv_heads": hf["num_key_value_heads"],
        "swa_num_kv_heads": hf["swa_num_key_value_heads"],
        "head_dim": hf["head_dim"],
        "v_head_dim": hf["v_head_dim"],
        "sliding_window": hf["sliding_window"],
        "layer_is_sliding": tuple(bool(t) for t in hf["hybrid_layer_pattern"]),
        "leading_dense_layers": hf["moe_layer_freq"].count(0),
        "rope_theta": float(hf["rope_theta"]),
        "swa_rope_theta": float(hf["swa_rope_theta"]),
        "partial_rotary_factor": float(hf["partial_rotary_factor"]),
        "attn_value_scale": float(hf["attention_value_scale"]),
        "sink_sliding": bool(hf["add_swa_attention_sink_bias"]),
        "sink_full": bool(hf["add_full_attention_sink_bias"]),
        "intermediate_size": hf["intermediate_size"],
        "moe_intermediate_size": hf["moe_intermediate_size"],
        "num_experts": (hf.get("experts_held") or {}).get(
            "of", hf["n_routed_experts"]),
        "num_experts_per_tok": hf["num_experts_per_tok"],
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
    differs: `lib/swa_work.py pool_bytes` at the stated dtype (columns for
    the full layers, a ring of the window for the sliding ones), one
    scratch row, and no leaf of a sliding layer with a `max_seq_len`
    axis."""
    import jax.numpy as jnp

    from benchmarks.lib import swa_work as sw

    want_dt = jnp.dtype(e.get("kv_dtype", hf["bench"]["cache_dtype"]))
    if any(jnp.dtype(a.dtype) != want_dt for a in cache.values()):
        return (f"pool leaves {({k: str(a.dtype) for k, a in cache.items()})}"
                f", not {want_dt}")
    got = sum(int(a.nbytes) for a in cache.values())
    want = sw.pool_bytes(hf, n_slots + 1, max_seq_len, want_dt.itemsize)
    if got != want:
        return (f"pool holds {got} bytes in leaves "
                f"{({k: tuple(a.shape) for k, a in cache.items()})}, not "
                f"{want}: columns for {sw.kinds(hf)[0]} full layers and a "
                f"ring of {sw.ring_positions(hf)} positions for "
                f"{sw.kinds(hf)[1]} sliding ones, {n_slots} + 1 rows of "
                f"{max_seq_len}")
    return ""


def check_requests(reference, eng_params, hf, chk, finished, rehearsal):
    """The log-prob the engine returned for each sampled token (prefill or
    the copy of a shared prompt's columns and rings, then decode through
    both) against the float32 reference's for the same prefix, on a few
    finished requests spread over the lengths, the last of them the one on
    the longest prompt (`long_prompt` positions at least, or no result)."""
    from benchmarks.lib.reference import compare_logprobs

    done = sorted((r for r in finished if r.stop_reason == "length"
                   and len(r.output_tokens) >= 2
                   and len(r.output_logprobs) == len(r.output_tokens)),
                  key=lambda r: len(r.input_ids) + len(r.output_tokens))
    k = int(chk["requests"])
    if len(done) < k:
        return False, {"n": 0, "why": f"only {len(done)} finished requests"}
    longest = max(done, key=lambda r: len(r.input_ids))
    if not rehearsal and len(longest.input_ids) < int(chk["long_prompt"]):
        return False, {"n": 0, "why": "no finished request on a prompt of "
                       f"{chk['long_prompt']} positions"}
    rest = [r for r in done if r is not longest]
    pick = [rest[int((i + 0.5) * len(rest) / (k - 1))]
            for i in range(k - 1)] + [longest]
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
    # tolerance: bfloat16 weights, activations, columns and rings, the
    # router's scores and every softmax in float32, against the float32
    # reference;
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
              "states full and sliding layers by kind; no result",
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
        # what the byte functions of lib/swa_work.py are given
        "work": {"n_slots": n_slots, "config": hf["bench"]["name"]},
        "compared": compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "bad_requests": bad[:8],
                   "tpot_ms": stats.dist_summary(tpot),
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "memory_peak_bytes_at_window_close": peak_at_close,
                   "decode_path": "columns by position for the full "
                                  "layers, a ring of the window for the "
                                  "sliding ones; the full layers through "
                                  "the paged kernel ops/windowed_decode.py "
                                  "where ragged_dispatches counts it, the "
                                  "rings through XLA",
                   "counters": counters},
    }
