"""Kind `rollout`: an in-process `GenEngine`, built as `gen/server.py`
builds it by default, under a closed loop of GRPO groups.

One thread submits and steps (as the server's worker thread does); a
finished group is replaced at once, so `groups_in_flight * group_size`
requests are always in flight.  Weights are drawn on the device from
`--seed` in one jitted call; no checkpoint is written, no HTTP server runs.
"""

import time

import numpy as np


def build_engine(model_cfg, params, e, seed):
    """`gen/server.py main()`'s construction with its argument defaults,
    plus the cell's slot grid.  Only what a workload's `engine` block
    states is passed (`ragged_attn`: the server's `--ragged-attn` /
    `--no-ragged-attn`; `kv_dtype`); every other option is left to the
    constructor's own default, as the server leaves it: the decode window
    on, one tier, no speculative decode, no host offload, and for
    `ragged_attn` `None`, which the engine resolves from what it can
    observe (the paged kernel wherever its gate admits it)."""
    from areal_tpu.gen.engine import GenEngine

    return GenEngine(
        model_cfg.replace(dtype=e.get("dtype", "bfloat16")), params=params,
        n_slots=int(e["n_slots"]), max_seq_len=int(e["max_seq_len"]),
        tp=1, ep=1, seed=int(seed) & 0x7FFFFFFF,
        **({"ragged_attn": bool(e["ragged_attn"])} if "ragged_attn" in e
           else {}),
        **({"kv_dtype": e["kv_dtype"]} if "kv_dtype" in e else {}),
    )


# the engine's totals of its step phases (`GenEngine.stats`)
STEP_PHASES = tuple(f"t_step_{p}_s" for p in
                    ("admit", "sync", "dispatch", "fetch", "deliver"))


class ClosedLoop:
    """Keeps `in_flight` groups submitted; counts what finishes."""

    def __init__(self, eng, make_groups, in_flight, temperature):
        from areal_tpu.gen.engine import GenRequest

        self.eng, self.make_groups, self.temp = eng, make_groups, temperature
        self.groups = make_groups(0)
        self.budget_of = {}
        self.Req = GenRequest
        self.next = 0
        self.steps = 0
        self.left = {}
        self.finished = []
        self.owed = in_flight
        self.step_log = [()]
        # [(engine step, first group, groups)] of every admission pass
        self.passes = []

    def _done(self, req):
        self.finished.append(req)
        gid = req.group_id
        self.left[gid] -= 1
        if self.left[gid] == 0:
            del self.left[gid]
            self.owed += 1

    def pump(self):
        if self.owed:
            self.passes.append((self.steps, self.next, self.owed))
        while self.owed:
            cycle, k = divmod(self.next, len(self.groups))
            if k == 0 and cycle:
                # the same sizes again, with other tokens
                self.groups = self.make_groups(cycle)
            g = self.groups[k]
            gid = f"g{self.next}"
            n = len(g["budgets"])
            self.left[gid] = n
            self.budget_of.update(
                {f"{gid}-{i}": int(b) for i, b in enumerate(g["budgets"])})
            self.eng.submit_batch([
                self.Req(rid=f"{gid}-{i}", input_ids=list(g["prompt"]),
                         max_new_tokens=int(b), temperature=self.temp,
                         group_id=gid, group_n=n, on_done=self._done)
                for i, b in enumerate(g["budgets"])
            ])
            self.next += 1
            self.owed -= 1

    def run(self, until_s=None, until_steps=None, spans=None):
        """Step the engine until `until_s` (a `perf_counter` time) or for
        `until_steps` steps; -> tokens its `step()` delivered.  Each step's
        time is kept for `step_report` (this call's steps only)."""
        delivered, t_stop = 0, until_s or float("inf")
        n_stop = self.steps + (until_steps or 1 << 60)
        # the first row is the phase totals the first step starts from
        log = self.step_log = [
            (0.0, 0.0, *map(self.eng.stats.__getitem__, STEP_PHASES))]
        t = time.perf_counter()
        while t < t_stop and self.steps < n_stop:
            self.pump()
            cpu = time.thread_time()
            if spans is None:
                delivered += self.eng.step()
            else:
                with spans.span("engine_step"):
                    delivered += self.eng.step()
            self.steps += 1
            t, t_was = time.perf_counter(), t
            log.append((t - t_was, time.thread_time() - cpu,
                        *map(self.eng.stats.__getitem__, STEP_PHASES)))
        return delivered

    def step_report(self, plan=None):
        """`checks` entries that place a run that reads low: the steps of the
        last `run` on the host's clock (pump included), and the six slowest
        as [ms, index, ms of this thread's CPU time, the engine phase that
        took most of the step and its ms].  A step that waited (for the
        device, the runtime, or a core) has little CPU time; one that
        computed (Python, a collection) has nearly all of it.  With the
        warm-up's `plan`: the admission passes this loop made that the plan
        did not hold (`engine_warm.unplanned_passes`; [] is what a plan by
        reach promises)."""
        from benchmarks.lib import engine_warm, stats

        log = self.step_log
        ms = [row[0] * 1e3 for row in log[1:]]
        slowest = []
        for i in sorted(range(len(ms)), key=ms.__getitem__, reverse=True)[:6]:
            spent = {k[len("t_step_"):-len("_s")]: (a - b) * 1e3
                     for k, a, b in zip(STEP_PHASES, log[i + 1][2:], log[i][2:])}
            phase = max(spent, key=spent.get)
            slowest.append([round(ms[i], 1), i, round(log[i + 1][1] * 1e3, 1),
                            phase, round(spent[phase], 1)])
        report = {"step_ms": stats.dist_summary(ms), "slowest_steps": slowest}
        if plan is not None:
            report["admission_passes"] = len(self.passes)
            report["unplanned_passes"] = engine_warm.unplanned_passes(
                plan, self.passes, [len(g["prompt"]) for g in self.groups],
                self.eng.prompt_bucket, self.eng.max_seq_len)[:8]
        return report


def tpot_ms(requests, t_open):
    """Time per output token after the first, in ms, of each request BORN
    in the window: its first token came at or after `t_open` (a
    `perf_counter` time, the clock of the engine's stamps) and it finished
    (the callers hand over what finished before the close).  A request that
    the ramp admitted during set-up, through whatever the ramp's first fill
    dispatched, is not in the list; nor is one still running at the close."""
    return [
        (r.finish_ts - r.first_token_ts) / (len(r.output_tokens) - 1) * 1e3
        for r in requests
        if r.first_token_ts >= t_open and r.finish_ts > r.first_token_ts
        and len(r.output_tokens) > 1
    ]


def check_requests(eng_params, hf, chk, finished, rehearsal):
    """The log-prob the engine returned for each sampled token (prefill,
    then decode through the cache) against the float32 reference's for the
    same prefix, on a few finished requests spread over the lengths."""
    from benchmarks.lib import reference

    # a request without one log-prob a token is counted as failed by the
    # caller; there is nothing of it to compare
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
    # tolerance: bfloat16 weights, activations and KV cache against the
    # float32 reference; measured on the chip (PERF.md, Findings), set to
    # about three times that.  float8/int8 weights or cache would miss it
    # by an order of magnitude.
    tol_mean, tol_max = ((1e-4, 2e-3) if rehearsal
                         else (chk["tol_mean"], chk["tol_max"]))
    ok, rep = reference.compare_logprobs(got, want, mask, tol_mean, tol_max)
    rep["lengths"] = [len(r.input_ids) + len(r.output_tokens) for r in pick]
    return ok, rep


def run(cell, hf, bench):
    import jax

    from areal_tpu.gen.engine import GenRequest
    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from benchmarks.lib import (
        device, engine_warm, reference, stats, traffic as tg)

    tr, e = cell["traffic"], dict(cell["engine"])
    dtype = "bfloat16"
    if bench.rehearsal:
        # float32 throughout, the cache too unless the file states one (a
        # control's): the rehearsal checks the comparison itself (positions,
        # masks), which then has to be exact
        e = {"kv_dtype": "float32", **e, "n_slots": tr["n_slots"],
             "max_seq_len": tr["max_seq_len"], "dtype": "float32"}
        dtype = "float32"
    model_cfg = TransformerConfig.from_hf(hf).replace(
        dtype=dtype, param_dtype=dtype, remat=False, eos_token_id=None)
    t0 = time.perf_counter()
    params = jax.jit(lambda k: init_params(model_cfg, k))(
        device.jax_seed(bench.args.seed))
    jax.block_until_ready(params)
    eng = build_engine(model_cfg, params, e, bench.args.seed)
    init_s = time.perf_counter() - t0

    def make_groups(cycle):
        return tg.rollout_groups(tr, hf["vocab_size"],
                                 [int(bench.args.seed), cycle])

    loop = ClosedLoop(eng, make_groups, int(tr["groups_in_flight"]),
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
               plan=plan,
               warm_compiles=warm_compiles, ramp_finished=ramp_done,
               ragged=bool(getattr(eng, "_ragged_ok", False)),
               ramp_compiles=bench.compiles.snapshot())

    seconds = bench.window_seconds(cell)
    stats0 = dict(eng.stats)
    t_open = bench.open_window()
    delivered = loop.run(until_s=t_open + seconds, spans=bench.spans)
    window_s = bench.close_window()
    counters = {k: eng.stats[k] - stats0.get(k, 0) for k in eng.stats
                if isinstance(eng.stats[k], (int, float))}
    in_window = loop.finished[ramp_done:]
    eng.abort_all("abort")

    tpot = tpot_ms(in_window, t_open)
    budget_of = loop.budget_of
    bad = [r.rid for r in in_window
           if r.stop_reason != "length"
           or len(r.output_tokens) != budget_of[r.rid]
           or len(r.output_logprobs) != len(r.output_tokens)]
    ok_ref, ref_report = check_requests(
        eng.params, hf, cell["check"], loop.finished, bench.rehearsal)
    dispatches = (counters.get("decode_calls", 0) + counters.get("prefill_calls", 0)
                  + counters.get("suffix_calls", 0) + counters.get("verify_calls", 0))
    return {
        "correct": ok_ref and not bad and bool(tpot),
        "attempted": len(in_window),
        "failed": len(bad),
        # the time per output token is no end-to-end metric of this closed
        # loop (PERF.md section 2, PR 41): its p95 is in `checks.tpot_ms`
        "metrics": {
            "rollout_tokens_per_s": (delivered / window_s, "tokens/s"),
        },
        "counts": {"dispatches": dispatches, "output_tokens": delivered,
                   "requests": len(in_window)},
        "counters": counters,
        "work": {},
        "compared": reference.compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "bad_requests": bad[:8], "tpot_ms": stats.dist_summary(tpot),
                   # admission's share of the window's wall clock (its
                   # prefill dispatches, each a synchronous fetch, on the
                   # host's clock): the p95 above ranks with it run by run
                   "admit_share": counters.get("t_step_admit_s", 0.0) / window_s,
                   **loop.step_report(plan),
                   "groups_submitted": loop.next,
                   "decode_path": "ragged" if getattr(eng, "_ragged_ok", False)
                   else "dense tiered",
                   "counters": counters},
    }
