"""Warm every program a `GenEngine` will use for a cell's traffic, through
its public `submit_batch` / `step` only.

The engine compiles one program per shape signature, and keeps the set
logarithmic by bucketing: prompt and key-window lengths to power-of-two
multiples of `prompt_bucket`, admission rows to powers of two.  So the
signatures a closed loop of GRPO groups can reach are few, and this plan
walks them with throw-away requests:

- fresh prefill: k single prompts at once, k = 1, 2, 4, ... (rows) up to
  what one admission pass takes, for a prompt length in every length bucket
  the traffic has;
- shared-prefix admission: groups whose siblings number 1, 2, 4, ... (rows
  of the suffix program; past one group's siblings, several whole groups
  admitted in one pass), for every pair of (copied-span bucket, key-window
  bucket) the traffic's prompt lengths produce;
- retained-prefix reuse: k prompts finished and then sent again, k = 1, 2,
  4, ...: a sibling that arrives after another of its group has finished
  inherits that slot's retained prompt (suffix program without a copy);
- decode: one short request started at a length just under every
  key-window bucket from the shortest prompt up to the longest sequence.

Each program costs about 0.7 s of every later run's set-up even when the
persistent cache holds it (tracing and lowering are not cached; measured,
PR 23), so the plan is kept to what the traffic can reach.

What the plan assumes of the engine is only the bucketing rule (copied
below); if the engine changes its rule, the window's compile count shows it.
"""

import numpy as np


def bucket(n, quantum, max_len):
    b = quantum
    while b < n:
        b *= 2
    return min(b, max_len)


def _drain(eng, reqs, max_steps=4000):
    for _ in range(max_steps):
        if all(r.stop_reason for r in reqs):
            return
        eng.step()
    raise RuntimeError("engine warm-up: requests did not finish")


def _pow2_upto(n):
    out, k = [], 1
    while k < n:
        out.append(k)
        k *= 2
    out.append(k)
    return out


def plan(n_slots, quantum, max_len, chunk, prompt_lens, group_size,
         max_total, max_admit):
    """-> {"prompt_lens", "fresh_rows", "sibling_rounds", "reuse_rows",
    "decode_starts"}.

    `prompt_lens` are the lengths the traffic really has; one stands for
    all that share its (length bucket, bucket of length - 1).  `max_admit`
    is the most requests one admission pass takes (`admit_rows`): rows are
    warmed up to the power of two that holds it.
    """
    reps = {}
    for L in sorted(set(int(x) for x in prompt_lens)):
        key = (bucket(L, quantum, max_len), bucket(max(L - 1, 1), quantum, max_len))
        reps.setdefault(key, L)
    g = max(group_size, 1)
    fresh_rows = [k for k in _pow2_upto(max_admit) if k <= max(n_slots // g, 1)]
    rounds = []  # each a list of group sizes (members) submitted together
    if g > 1:
        for R in _pow2_upto(max_admit):
            sizes, left = [], min(R, (n_slots // g) * (g - 1))
            while left > 0:
                take = min(g - 1, left)
                sizes.append(take + 1)
                left -= take
            if sum(sizes) <= n_slots:
                rounds.append(sizes)
    reuse_rows = _pow2_upto(max(max_admit // 2, 1))
    starts = []
    K = bucket(min(reps.values()) + chunk, quantum, max_len)
    top = bucket(max_total + chunk, quantum, max_len)
    while True:
        starts.append(max(8, K - 2 * chunk))
        if K >= top:
            break
        K *= 2
    return {"prompt_lens": sorted(reps.values()), "fresh_rows": fresh_rows,
            "sibling_rounds": rounds, "reuse_rows": reuse_rows,
            "decode_starts": starts}


def admit_rows(traffic, n_slots):
    """The most requests one admission pass of a closed loop takes, from
    the traffic's own parameters: min(`n_slots`, `groups_in_flight` x
    `group_size`).  A closed loop replaces a group when its last member
    ends, and budgets are clipped at `output_len.hi`, so groups that were
    admitted together and hold a clipped budget END together: one pass then
    takes two, four, ... groups, and the first fill of the empty engine
    takes every slot.  Warmed that far, no admission shape is left to the
    ramp or the window (`rollout_window_compiles` says whether a cell's plan
    holds).  A file that states `warm_max_admit` is taken at its word (the
    cells from before PR 41, whose windows their smaller plans do cover)."""
    if "warm_max_admit" in traffic:
        return int(traffic["warm_max_admit"])
    return min(int(n_slots),
               int(traffic["groups_in_flight"]) * int(traffic["group_size"]))


def warm(eng, Request, vocab, seed, prompt_lens, group_size, max_total,
         max_admit, temperature=1.0):
    """Run the plan.  `Request` is the engine's request type (`GenRequest`),
    built by keyword."""

    def make_request(rid, input_ids, max_new_tokens, temperature, group_id,
                     group_n):
        return Request(rid=rid, input_ids=input_ids,
                       max_new_tokens=max_new_tokens, temperature=temperature,
                       group_id=group_id, group_n=group_n)

    rng = np.random.default_rng([int(seed), 11])
    p = plan(eng.n_slots, eng.prompt_bucket, eng.max_seq_len, eng.decode_chunk,
             prompt_lens, group_size, max_total, max_admit)
    n = 0

    def ids(L):
        return rng.integers(0, vocab, L).tolist()

    def go(reqs):
        eng.submit_batch(reqs)
        _drain(eng, reqs)

    for L in p["prompt_lens"]:
        for k in p["fresh_rows"]:
            n += 1
            prompts = [ids(L) for _ in range(k)]
            go([make_request(f"wf{n}-{i}", prompts[i], 1, temperature, "", 0)
                for i in range(k)])
            if k in p["reuse_rows"]:
                # the same prompts again: each matches the prefix its first
                # copy left in a freed slot
                go([make_request(f"wr{n}-{i}", prompts[i], 1, temperature,
                                 "", 0) for i in range(k)])
        for sizes in p["sibling_rounds"]:
            n += 1
            reqs = []
            for gi, m in enumerate(sizes):
                prompt = ids(L)
                reqs += [make_request(f"ws{n}-{gi}-{i}", prompt, 1, temperature,
                                      f"ws{n}-{gi}", m) for i in range(m)]
            go(reqs)
    for L in p["decode_starts"]:
        n += 1
        go([make_request(f"wd{n}", ids(L), eng.decode_chunk + 2, temperature,
                         "", 0)])
    return p
