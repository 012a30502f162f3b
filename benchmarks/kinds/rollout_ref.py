"""Kind `rollout_ref`: kind `rollout`'s closed loop for a configuration
that names its own plain reference (`bench.reference`, a module of `lib/`).

`kinds/rollout.py check_requests` imports `lib.reference`, the softmax
decoder, and cannot be told otherwise; until a `benchmark` issue lets it
choose by `bench.reference`, this file takes `ClosedLoop` and
`build_engine` from it as they are and repeats only `run` and
`check_requests`.  What differs: the reference module is the
configuration's; the engine's state pool is freed (not its parameters)
before the float32 reference runs, because weights + pool + one upcast
layer + the [B, H, T, T] weights of the quadratic form do not fit a chip
together; and the first thing `run` does is to see that the model built
from the configuration carries the power-retention kind.  A program that
does not know the kind would build a softmax model under the
configuration's name and serve it: it is stopped here, at once, with exit
code 4 and no result.  A program whose state pool is not of the
configuration's `bench.state_dtype` and size is stopped the same way, exit
code 5, before anything is timed: a narrower pool is another deployment,
not a faster one.

The weights are random, and a random bias-free gate reads 1/2: its argument
W_g h is symmetric about 0, so E[log g] <= -ln 2 whatever W_g is, and a
state then remembers two or three tokens.  A trained model's gates sit near
1 and its state sums hundreds of terms, which is what a float32 state is
for and what a comparison of log-probs has to be able to see.  So
`trained_like_gates` gives the residual stream a small common component for
the gate to read (the configuration's `bench.assumed.gate_draw`): memories
of a hundred to thousands of tokens, layer by layer.
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader


def trained_like_gates(params, hf):
    """The drawn weights with gates near 1, by `bench.assumed.gate_draw`:
    the embedding at unit variance plus `offset` in every coordinate, and
    `gain / hidden_size` added to every entry of each layer's W_g, so that
    W_g h reads `gain` times the mean of the normed residual (its share of
    the all-ones direction: `offset` over the residual's RMS, which grows
    from 1 with depth) plus the drawn W_g's unit noise."""
    import jax.numpy as jnp

    draw = hf["bench"]["assumed"]["gate_draw"]["value"]
    D = int(hf["hidden_size"])
    emb, wg = params["embedding"], params["layers"]["attn"]["wg"]
    f32 = jnp.float32
    out = dict(params)
    out["embedding"] = (
        emb.astype(f32) * jnp.sqrt(f32(D)) + f32(draw["offset"])
    ).astype(emb.dtype)
    layers = dict(params["layers"])
    layers["attn"] = dict(layers["attn"])
    layers["attn"]["wg"] = (
        wg.astype(f32) + f32(draw["gain"]) / D).astype(wg.dtype)
    out["layers"] = layers
    return out


def pool_as_stated(cache, hf, n_slots):
    """'' if the engine's state pool is what the configuration states
    (`bench.state_dtype`, `lib/retention_work.py state_bytes_per_slot` a
    slot and one scratch row), else what differs."""
    from benchmarks.lib import retention_work as rw

    want_dt = np.dtype(hf["bench"]["state_dtype"])
    want = rw.state_bytes_per_slot(hf)
    if sorted(cache) != ["s", "z"]:
        return f"pool leaves {sorted(cache)}, not ['s', 'z']"
    for name, a in cache.items():
        if np.dtype(a.dtype) != want_dt:
            return f"pool leaf {name!r} is {a.dtype}, not {want_dt}"
    rows = {int(a.shape[1]) for a in cache.values()}
    got = sum(int(a.nbytes) for a in cache.values())
    if len(rows) != 1 or min(rows) < n_slots or got != want * min(rows):
        return (f"pool holds {got} bytes in {sorted(rows)} rows, not "
                f"{want} bytes a slot for {n_slots} slots")
    return ""


def check_requests(reference, eng_params, hf, chk, finished, rehearsal):
    """The log-prob the engine returned for each sampled token (prefill,
    the fan-out copy of a state, then decode through the state) against
    the float32 reference's for the same prefix, on a few finished requests
    spread over the lengths."""
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
    gate_log = []
    want = np.asarray(
        reference.next_token_logprobs(eng_params, hf, ids, gate_log))
    # tolerance: bfloat16 weights, activations and operands of the retention
    # products, the state and every sum into it float32, against the
    # float32 quadratic reference, with the gates drawn near 1.  Readings on
    # the chip (PERF.md, Findings of PR 27): the program, mean |d| 0.0068-
    # 0.0073 and max 0.025-0.035 over sixteen seeds; a bfloat16 POOL (the
    # nearest precision below the stated one; sums still float32) 0.0191-
    # 0.0197 and 0.163-0.184 on two seeds; the sums in bfloat16 as well
    # 0.0218 and 0.155.  Both limits lie between the readings, about 1.7
    # times the program's and 0.6 of the control's (the maximum, which is
    # heavy-tailed, 2.3 times and a half), and either catches the control.
    tol_mean, tol_max = ((1e-4, 2e-3) if rehearsal
                         else (chk["tol_mean"], chk["tol_max"]))
    ok, rep = reference.compare_logprobs(got, want, mask, tol_mean, tol_max)
    rep["lengths"] = [len(r.input_ids) + len(r.output_tokens) for r in pick]
    rep["cache_hit_tokens"] = [int(r.cache_hit_tokens) for r in pick]
    # tokens a layer's state remembers: 1 / -E[log g]
    rep["gate_memory_tokens"] = [round(-1.0 / g, 1) for g in gate_log]
    return ok, rep


def run(cell, hf, bench):
    from areal_tpu.models.model_config import TransformerConfig

    model_cfg = TransformerConfig.from_hf(hf)
    if getattr(model_cfg, "attn_kind", "softmax") != "power_retention":
        print("benchmark: this program builds "
              f"{getattr(model_cfg, 'attn_kind', 'a softmax model')!r} from "
              f"configuration {hf['bench']['name']!r}, not power retention; "
              "no result", file=sys.stderr, flush=True)
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
                 dtype="float32")
        dtype = "float32"
    model_cfg = model_cfg.replace(
        dtype=dtype, param_dtype=dtype, remat=False, eos_token_id=None)
    t0 = time.perf_counter()
    params = jax.jit(
        lambda k: trained_like_gates(init_params(model_cfg, k), hf))(
        device.jax_seed(bench.args.seed))
    jax.block_until_ready(params)
    eng = rollout.build_engine(model_cfg, params, e, bench.args.seed)
    init_s = time.perf_counter() - t0
    wrong = pool_as_stated(eng.cache, hf, int(e["n_slots"]))
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
    n_slots = int(e["n_slots"])
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
        # what the byte functions of lib/retention_work.py are given
        "work": {"n_slots": n_slots, "config": hf["bench"]["name"]},
        "compared": compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "bad_requests": bad[:8],
                   "tpot_ms": stats.dist_summary(tpot),
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "decode_path": "retention state pool, one program",
                   "counters": counters},
    }
