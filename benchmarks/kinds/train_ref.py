"""Kind `train_ref`: kind `train`'s run (PPO/GRPO train steps of
`JaxPPOActor` on packed rows, `make_actor` taken from `kinds/train.py` as
it is) for a configuration that names its own plain reference
(`bench.reference`, a module of `lib/`) and whose train step counts what
its experts did.  `kinds/train.py` imports `lib.reference`, the dense
decoder, and counts attention as causal in every layer; until a `benchmark`
issue lets it choose, `run` is repeated here.  What differs:

- the first thing `run` does is to see that the model built from the
  configuration's file IS the file's stack: a program that does not know
  the family cannot build it (`from_hf` raises: exit code 1 at once); one
  that builds something else under the name is stopped with exit code 4;
- the router's selection bias is drawn small and non-zero
  (`bench.assumed.expert_bias`), so that choosing by score + bias and
  weighting by score differ;
- `correct` compares the actor's recomputed log-probs with the reference on
  TWO spans of the longest sequences: their first `check.tokens` tokens, and
  as many again starting `check.tokens` past the sliding window, where a
  query's window no longer reaches the sequence's start: a sliding layer
  that attends too far, or a full layer given a rotary embedding, fails
  there and not on the first span;
- `work.attention_flops` is counted BY MASK and `work.expert_flops` from
  the step's own counter (`lib/afmoe_work.py`); the step's counters are
  handed back: `expert_assignments_held` and `expert_slots` (steps x expert
  layers x experts held), `expert_load_max` and `expert_load_mean` (summed
  over steps: the fullest held expert's rows, and a held expert's mean rows
  a layer), `attn_blocks_run` and `attn_blocks_static` (splash blocks run
  and blocks the static masks hold, over all layers by their kind).
"""

import sys
import time

import numpy as np

from benchmarks.lib import loader

# the published keys shrunk for the CPU rehearsal (on top of run.py's
# REHEARSAL_HF, which knows only a dense decoder's keys)
REHEARSAL_HF = {
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "sliding_window": 32, "moe_intermediate_size": 48, "num_experts": 4,
    "experts_held": {"first": 0, "of": 8}, "num_experts_per_tok": 2,
}
REHEARSAL_TOKENS = 16


def built_as_stated(model_cfg, hf):
    """'' if the program's model is the file's stack holding the file's
    experts, else what differs."""
    n_dense = int(hf["num_dense_layers"])
    L = int(hf["num_hidden_layers"])
    kinds = getattr(model_cfg, "ffn_kinds", None)
    if kinds != ("dense",) * n_dense + ("moe",) * (L - n_dense):
        return f"FFN kinds {kinds!r}, not {n_dense} dense then experts"
    sliding = tuple(t == "sliding_attention" for t in hf["layer_types"])
    if tuple(model_cfg.layer_is_sliding) != sliding:
        return f"sliding layers {model_cfg.layer_is_sliding!r}, not {sliding!r}"
    share = hf.get("experts_held") or {"first": 0, "of": hf["num_experts"]}
    want = (share["first"], share["first"] + hf["num_experts"])
    if tuple(model_cfg.held_range) != want or model_cfg.num_experts != share["of"]:
        return (f"experts {tuple(model_cfg.held_range)} of "
                f"{model_cfg.num_experts} held, not {want} of {share['of']}")
    return ""


def draw_expert_bias(actor, hf, seed):
    """The router's selection bias of every expert layer, normal at the
    assumed spread, in place of the zeros the trainer starts from."""
    import jax

    moe = actor.params["layers"]["moe"]["moe"]
    std = float(hf["bench"]["assumed"]["expert_bias"]["value"]["std"])
    bias = std * jax.random.normal(
        jax.random.PRNGKey(int(seed) % (2 ** 31)), moe["router_bias"].shape,
        moe["router_bias"].dtype)
    moe["router_bias"] = jax.device_put(bias, moe["router_bias"].sharding)


def check_spans(hf, chk, lens, rehearsal):
    """-> (T, [(lo, hi)]): the reference runs the first T tokens of each
    picked sequence; log-probs are compared at predictor positions [lo, hi)
    of each span: the first n tokens, and n more starting n past the
    window.  A sequence too short for the second span gives the first."""
    n = REHEARSAL_TOKENS if rehearsal else int(chk["tokens"])
    W = int(hf["sliding_window"])
    shortest = int(min(lens))
    if shortest < W + 2 * n:
        T = min(n, shortest)
        return T, [(0, T - 1)]
    return W + 2 * n, [(0, n), (W + n, W + 2 * n - 1)]


def check_logprobs(reference, actor, hf, chk, batch, got_all, rehearsal):
    """The actor's recomputed log-probs (`got_all`, its `compute_logp` over
    a whole packed batch) against the float32 reference fed the actor's own
    parameters, on two spans of the `sequences` longest sequences of that
    batch: causal attention makes a prefix's log-probs independent of what
    follows, and packing must not change them."""
    lens = batch["attention_mask"].sum(-1)
    pick = np.argsort(-lens, kind="stable")[: int(chk["sequences"])]
    T, spans = check_spans(hf, chk, lens[pick], rehearsal)
    ids = batch["input_ids"][pick, :T]
    got = np.asarray(got_all)[pick, : T - 1]
    want = np.asarray(reference.next_token_logprobs(actor.params, hf, ids))
    mask = np.zeros(got.shape, bool)
    for lo, hi in spans:
        mask[:, lo:hi] = True
    # tolerance: the program computes in bfloat16 (8 bits of mantissa) and
    # the reference in float32, and the reference routes for itself, so a
    # routing choice that flips on rounding is part of the difference; what
    # both cost over the stack was measured on the chip (PERF.md, Findings,
    # PR 42) and the limits stand in the workload file's `check` with their
    # reason.  A float32 rehearsal agrees much more closely.
    tol_mean, tol_max = ((1e-4, 1e-3) if rehearsal
                         else (chk["tol_mean"], chk["tol_max"]))
    ok, rep = reference.compare_logprobs(got, want, mask, tol_mean, tol_max)
    rep["spans"] = spans
    rep["by_span"] = [
        float(np.abs(got[:, lo:hi] - want[:, lo:hi]).mean()) for lo, hi in spans]
    return ok, rep


def run(cell, hf, bench):
    import jax

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.models.model_config import TransformerConfig
    from benchmarks.lib import afmoe_work, traffic as tg

    root = bench.args.bench_root
    train = loader._load_module("kinds", "train", root)
    reference = loader._load_module("lib", hf["bench"]["reference"], root)
    tr, a = cell["traffic"], dict(cell["actor"])
    if bench.rehearsal:
        hf = {**hf, **REHEARSAL_HF}
        a.update(dtype="float32", param_dtype="float32", scan_unroll=1)
    model_cfg = TransformerConfig.from_hf(hf)
    why = built_as_stated(model_cfg, hf)
    if why:
        print(f"the program did not build the configuration's model: {why}",
              file=sys.stderr, flush=True)
        sys.exit(4)
    t0 = time.perf_counter()
    actor = train.make_actor(model_cfg, tr, a)
    actor.initialize(ft_spec=FinetuneSpec(1, 1024, 8))
    draw_expert_bias(actor, hf, bench.args.seed)
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
        reference, actor, hf, cell["check"], batches[0], logp0, bench.rehearsal)
    check_s = time.perf_counter() - t0

    # warm-up: the first step compiles (or loads) the one step program
    t0 = time.perf_counter()
    for i in range(2):
        actor.ppo_update(batches[i % len(batches)])
        jax.block_until_ready(actor.params)
    warm_s = time.perf_counter() - t0
    actor.flush_stats()
    bench.diag(phase="setup", init_s=init_s, logp_s=logp_s, check_s=check_s,
               warm_s=warm_s, tokens_per_step=real_tokens,
               sequences=len(seq_lens), seq_lens=seq_lens,
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

    def total(key):
        return sum(float(s.get(key, 0.0)) for st in stats for s in st)

    sliding = [t == "sliding_attention" for t in hf["layer_types"]]
    n_local, n_global = sum(sliding), len(sliding) - sum(sliding)
    n_moe = int(hf["num_hidden_layers"]) - int(hf["num_dense_layers"])
    held = int(hf["num_experts"])
    rows = total("expert_assignments_held")
    counters = {
        "expert_assignments_held": rows,
        "expert_slots": steps * n_moe * held,
        "expert_load_max": total("expert_load_max"),
        "expert_load_mean": rows / (n_moe * held),
        "attn_blocks_run": (n_local * total("attn_blocks_run_local")
                            + n_global * total("attn_blocks_run_global")),
        "attn_blocks_static": (n_local * total("attn_blocks_causal_local")
                               + n_global * total("attn_blocks_causal_global")),
    }
    return {
        "correct": ok_ref and not bad and moving,
        "attempted": steps,
        "failed": len(bad),
        "metrics": {
            "train_tokens_per_s": (real_tokens * steps / window_s, "tokens/s"),
        },
        "counts": {"steps": steps},
        "counters": counters,
        "work": {
            "attention_flops": afmoe_work.attention_flops(seq_lens, hf) * steps,
            "expert_flops": afmoe_work.expert_flops(rows, hf),
        },
        "compared": reference.compared(ref_report),
        "checks": {"reference": ref_report, "reference_ok": ok_ref,
                   "loss_first_last": [losses[0], losses[-1]] if losses else None,
                   "grad_norm_first_last": [gnorms[0], gnorms[-1]] if gnorms else None,
                   "moving": moving, "non_finite_steps": bad,
                   "counters": counters,
                   "padding_share": 1 - real_tokens / (int(tr["rows"]) * int(tr["row_len"]))},
    }
