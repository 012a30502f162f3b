"""Kind `rollout_ssm`: kind `rollout`'s closed loop for a configuration of
the `jamba` family (dense variant): a Mamba-1 or attention mixer and then a
dense gated FFN a layer, served WHOLE through the hybrid slot, checked
against the configuration's own plain reference (`bench.reference`,
`lib/reference_jamba.py`).

`ClosedLoop` and `build_engine` are `kinds/rollout.py`'s as they are and
`check_requests` is `kinds/rollout_hybrid.py`'s (the log-probs of finished
requests against the reference's full forward); `run` follows that kind's,
phase for phase.  What differs:

- the first thing `run` does is to see that the model built from the
  configuration's file IS the file's stack: two blocks a published layer,
  attention where the family's rule puts it, Mamba-1 at the file's widths.
  A program that does not know the family cannot build it (`from_hf`
  raises: the run ends at once, exit code 1); one that builds something
  else under the name is stopped with exit code 4 and no result;
- a program whose pool does not hold, for every slot, the float32 state
  and the window of every Mamba layer and the K/V rows of every attention
  layer at the stated sizes (`lib/jamba_work.py`) is stopped with exit code
  5 before anything is timed: a narrower state is another deployment, not
  a faster one;
- the weights are drawn as a network starts out (`trained_like_draw`:
  projections back into the residual stream at 1 / sqrt(56 blocks), the
  tied embedding as drawn); the recurrence's own parameters stay as Mamba-1
  initialises them (`init_params`: A = 1..16 over a channel's columns, a
  log-uniform step in [0.001, 0.1], D one), so that a state carries
  hundreds of positions;
- `correct` holds TWO comparisons with the reference: the log-probs of
  finished requests (`check.tol_mean`), and the recurrent STATE the program
  left in the pool for live sequences against the reference's own
  recurrence over the same ids, on the channels that remember longest
  (`check.tol_state`), as `rollout_hybrid` has it and for its reason: the
  log-probs of a random network do not see how a slow state is kept.

The cell's `work` hands the configuration's name to `lib/jamba_work.py`;
the rows a pass steps are the engine's own `state_rows_stepped`.
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader

# the published keys shrunk for the CPU rehearsal (on top of run.py's
# REHEARSAL_HF, which knows only a dense decoder's keys), with the
# published ratios: d_inner 2 x hidden, rank hidden / 16, one kv head
REHEARSAL_HF = {
    "num_hidden_layers": 6, "attn_layer_period": 6, "attn_layer_offset": 3,
    "mamba_dt_rank": 4, "num_key_value_heads": 1,
}


def trained_like_draw(params, hf):
    """The drawn weights as a network starts out, so that a block is a small
    update of the residual stream and rounding is not amplified block by
    block (`bench.assumed.residual_draw`): every block's projection back
    into the residual stream (Mamba `w_out`, attention `wo`, the FFN's
    `w_down`) at 1 / sqrt(blocks) of the fan-in scale.  The embedding stays
    as drawn (rows of norm 1): it is the head too, and at unit variance a
    token's own logit would be the hidden size and every sample certain."""
    import jax.numpy as jnp

    draw = hf["bench"]["assumed"]["residual_draw"]["value"]
    f32 = jnp.float32
    down = f32(1.0 / np.sqrt(draw["depth"]))

    def scaled(a, by):
        return (a.astype(f32) * by).astype(a.dtype)

    layers = {k: dict(v) for k, v in params["layers"].items()}
    layers["S"]["w_out"] = scaled(layers["S"]["w_out"], down)
    layers["*"]["attn"] = {**layers["*"]["attn"],
                           "wo": scaled(layers["*"]["attn"]["wo"], down)}
    layers["-"]["mlp"] = {**layers["-"]["mlp"], "w_down": scaled(
        layers["-"]["mlp"]["w_down"], down)}
    return {**params, "layers": layers}


def built_as_stated(model_cfg, hf):
    """'' if the program's model is the file's stack, else what differs."""
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    want = tuple(k for l in range(hf["num_hidden_layers"])
                 for k in ("*" if l % period == offset else "S", "-"))
    kinds = getattr(model_cfg, "layer_kinds", None)
    if kinds is None or tuple(kinds) != want:
        return f"block kinds {kinds!r}, not {''.join(want)!r}"
    got = (getattr(model_cfg, "mamba_d_inner", None), model_cfg.ssm_state_size,
           getattr(model_cfg, "mamba_dt_rank", None), model_cfg.conv_kernel)
    stated = (hf["mamba_expand"] * hf["hidden_size"], hf["mamba_d_state"],
              hf["mamba_dt_rank"], hf["mamba_d_conv"])
    if got != stated:
        return f"Mamba widths {got}, not {stated}"
    if getattr(model_cfg, "num_experts", 0) > 0:
        return f"{model_cfg.num_experts} experts in a dense configuration"
    return ""


def pool_as_stated(cache, hf, n_slots, max_seq_len, kv_dtype):
    """'' if the engine's pool is what the configuration states, else what
    differs: `s` in `bench.state_dtype` at [16, d_inner] a Mamba layer, the
    window `c` and the columns `k`, `v` in `kv_dtype` (the cell's `engine`
    block may state one, as the server's `--kv-dtype` does; else
    `bench.dtype`) at `lib/jamba_work.py`'s sizes; every leaf with one
    scratch row."""
    import jax.numpy as jnp

    from benchmarks.lib import jamba_work as jw

    if sorted(cache) != ["c", "k", "s", "v"]:
        return f"pool leaves {sorted(cache)}, not ['c', 'k', 's', 'v']"
    want_dt = np.dtype(hf["bench"]["state_dtype"])
    if np.dtype(cache["s"].dtype) != want_dt:
        return f"pool leaf 's' is {cache['s'].dtype}, not {want_dt}"
    rows = {int(a.shape[1]) for a in cache.values()}
    if len(rows) != 1 or min(rows) < n_slots:
        return f"pool rows {sorted(rows)} for {n_slots} slots"
    n, d = min(rows), jw.dims(hf)
    n_mamba, n_attn = jw.n_layers(hf)
    item = jnp.dtype(kv_dtype).itemsize
    state = int(cache["s"].nbytes) + int(cache["c"].nbytes)
    per_slot = n_mamba * d["d_in"] * (
        d["N"] * want_dt.itemsize + (d["K"] - 1) * item)
    if state != n * per_slot:
        return (f"state and windows hold {state} bytes, not {per_slot} a "
                f"slot in {n} rows")
    kv = int(cache["k"].nbytes) + int(cache["v"].nbytes)
    per_token = n_attn * 2 * d["kv"] * item
    if kv != n * max_seq_len * per_token:
        return f"keys and values hold {kv} bytes, not {per_token} a position"
    return ""


def pooled_states(eng, k):
    """Before the pool is freed: for the k live slots whose state holds the
    most tokens, (the ids the state has taken in, the slot's state of
    every Mamba layer [n_ssm, d_inner, N] as float32 on the host: the
    pool keeps the channels last, the reference the columns).  Between two
    engine steps a live slot's cache holds its first `lengths` tokens."""
    live = sorted((s for s, r in enumerate(eng.slot_req) if r is not None),
                  key=lambda s: -int(eng.lengths[s]))[:k]
    return [(np.array(eng.seq_tokens[s, : int(eng.lengths[s])], np.int32),
             np.asarray(eng.cache["s"][:, eng.pool.row(s)], np.float32
                        ).swapaxes(-1, -2))
            for s in live]


def check_states(reference, eng_params, hf, chk, pooled, rehearsal):
    """The recurrent state the program left in the pool for a few live
    sequences (prefill or the fan-out copy, then hundreds of decode steps)
    against the state of the float32 reference's own recurrence over the
    same ids: each channel's |difference| over |reference|, averaged over
    the quarter of a layer's channels that remember longest and over the
    slots.  The limit holds the FIRST layer's reading (its input is the
    embedding itself); the later layers' readings are reported."""
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
        slow.append(float(err[:, reference.slow_channels(eng_params, j)].mean()))
        every.append(float(err.mean()))
    tol = 1e-4 if rehearsal else chk["tol_state"]
    ok = bool(np.isfinite(slow).all() and slow[0] <= tol)
    return ok, {"n": len(pooled), "lengths": lens, "tol_state": tol,
                "slow_channels_rel_err": slow, "all_channels_rel_err": every}


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
    hybrid = loader._load_module("kinds", "rollout_hybrid", root)
    reference = loader._load_module("lib", hf["bench"]["reference"], root)

    tr, e = cell["traffic"], dict(cell["engine"])
    dtype = hf["bench"]["dtype"]
    if bench.rehearsal:
        # float32 throughout: the rehearsal checks the comparison itself
        # (positions, masks), which then has to be exact
        # (a control's `kv_dtype` stands)
        e = {"kv_dtype": "float32", **e, "n_slots": tr["n_slots"],
             "max_seq_len": tr["max_seq_len"], "dtype": "float32"}
        dtype = "float32"
    model_cfg = model_cfg.replace(
        dtype=dtype, param_dtype=dtype, remat=False, eos_token_id=None)
    t0 = time.perf_counter()
    key = device.jax_seed(bench.args.seed)
    params = jax.jit(
        lambda k: trained_like_draw(init_params(model_cfg, k), hf))(key)
    jax.block_until_ready(params)
    eng = rollout.build_engine(model_cfg, params, e, bench.args.seed)
    init_s = time.perf_counter() - t0
    n_slots = int(e["n_slots"])
    wrong = pool_as_stated(eng.cache, hf, n_slots, int(e["max_seq_len"]),
                           e.get("kv_dtype", dtype))
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
    ok_ref, ref_report = hybrid.check_requests(
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
        # what the byte functions of lib/jamba_work.py are given
        "work": {"n_slots": n_slots, "config": hf["bench"]["name"]},
        "compared": {
            **compared(ref_report),
            **({"state_rel_err": {
                "value": state_report["slow_channels_rel_err"][0],
                "limit": state_report["tol_state"]}}
               if "tol_state" in state_report else {})},
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "state": state_report, "state_ok": ok_state,
                   "bad_requests": bad[:8],
                   "tpot_ms": stats.dist_summary(tpot),
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "memory_peak_bytes_at_window_close": peak_at_close,
                   "decode_path": "hybrid pool (Mamba-1 state + window + "
                                  "K/V), windowed decode programs",
                   "counters": counters},
    }
