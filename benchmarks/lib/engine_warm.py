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
PR 23), and every round RUNS its prefills, so a plan covers what its cell's
traffic reaches, no less and no more.  There are two plans:

- `plan`, the walk above over rows 1, 2, 4, ... up to `admit_rows`: for a
  cell whose file states `warm_max_admit` (the cells from before PR 41 and
  the loop, whose feed is no list of groups) and for a closed loop that
  keeps more requests submitted than it has slots (`queues`);
- `plan_by_reach` (PR 55), for a closed loop of GRPO groups that states no
  such key and always has a slot for every member it submits.  The work of
  such a cell is fixed by its `size_seed`, budgets are exact (no request
  stops early), and an engine step gives every live request `decode_chunk`
  tokens: so WHICH groups one admission pass takes is arithmetic
  (`closed_loop_passes`), and the plan is the list of the passes the loop
  makes over `HORIZON_CYCLES` turns of its work list, each kept once by the
  length buckets of its groups in order.  `warm_closed_loop` sends each
  pass as the loop would, whole groups together with other tokens, and the
  engine batches it as it will batch the real one: whatever rule it has
  for rows, buckets and windows of a mixed pass, the programs it lowers are
  the ones the window needs.  The first fill of the empty engine happens in
  the ramp, inside `setup_s`: it is left to the ramp
  (`plan["left_to_ramp"]`) unless the loop makes that pass again.

What the plans assume of the engine is the bucketing rule (copied below)
and, for the second, that a request of budget b admitted in step t ends in
step t + ceil((b - 1) / decode_chunk) - 1; if the engine changes either,
`checks.unplanned_passes` and the window's compile count show it.
"""

import math

import numpy as np


def bucket(n, quantum, max_len):
    b = quantum
    while b < n:
        b *= 2
    return min(b, max_len)


def length_key(L, quantum, max_len):
    """Prompt lengths that share this share their programs: the bucket of
    the length (the key window) and of the length less one (the span a
    sibling copies, a representative prefills)."""
    return bucket(L, quantum, max_len), bucket(max(L - 1, 1), quantum, max_len)


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
        reps.setdefault(length_key(L, quantum, max_len), L)
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
    return {"prompt_lens": sorted(reps.values()), "fresh_rows": fresh_rows,
            "sibling_rounds": rounds, "reuse_rows": reuse_rows,
            "decode_starts": _decode_starts(min(reps.values()), quantum,
                                            max_len, chunk, max_total)}


def _decode_starts(shortest, quantum, max_len, chunk, max_total):
    """A length just under every key-window bucket from the shortest
    prompt's up to the longest sequence's."""
    starts = []
    K = bucket(shortest + chunk, quantum, max_len)
    top = bucket(max_total + chunk, quantum, max_len)
    while True:
        starts.append(max(8, K - 2 * chunk))
        if K >= top:
            break
        K *= 2
    return starts


# how many turns of its work list a closed loop is followed for: eight times
# and more what a window of 40 s reaches today (`rollout_ssm_dense_4k` 1,186
# engine steps where a window takes about 130, `rollout_swa_moe_16k` 7,404
# for 290; my chip runs, PR 55), and past 8 the list of passes hardly grows
# (34 -> 36 at 16 turns, 19 -> 19)
HORIZON_CYCLES = 8


def closed_loop_passes(top_budgets, in_flight, chunk, n_groups):
    """The admission passes of a closed loop that keeps `in_flight` groups
    submitted and replaces a group when its last member ends, up to the
    pass that submits group `n_groups`: [(engine step, first group,
    groups)].  `top_budgets[g % len]` is the largest budget of group g.  A
    request admitted in step t has 1 + chunk tokens after it, so a group
    admitted in step t is replaced in step t + ceil((top - 1) / chunk)."""
    due = {0: int(in_flight)}
    out, nxt = [], 0
    while due and nxt < n_groups:
        t = min(due)
        k = due.pop(t)
        out.append((t, nxt, k))
        for g in range(nxt, nxt + k):
            top = int(top_budgets[g % len(top_budgets)])
            end = t + max(1, math.ceil((top - 1) / chunk))
            due[end] = due.get(end, 0) + 1
        nxt += k
    return out


def plan_by_reach(prompt_lens, top_budgets, in_flight, quantum, max_len,
                  chunk, max_total, cycles=HORIZON_CYCLES):
    """-> {"passes", "left_to_ramp", "decode_starts", "horizon"}.

    `prompt_lens[g]` and `top_budgets[g]` are the work list's (group g's
    prompt length and largest budget; the list repeats).  A pass is the
    list of its groups' prompt lengths in the order they are submitted,
    one length standing for all that share its (length bucket, bucket of
    length - 1); two passes with the same list are one."""
    G = len(prompt_lens)
    reps = {}

    def rep(g):
        L = int(prompt_lens[g % G])
        return reps.setdefault(length_key(L, quantum, max_len), L)

    made = closed_loop_passes(top_budgets, in_flight, chunk, cycles * G)
    seen = {}  # pass -> times made
    for _, first, k in made:
        p = tuple(rep(g) for g in range(first, first + k))
        seen[p] = seen.get(p, 0) + 1
    fill = tuple(rep(g) for g in range(made[0][2]))
    ramp_only = seen[fill] == 1
    return {
        "passes": [list(p) for p in seen if not (ramp_only and p == fill)],
        "left_to_ramp": [list(fill)] if ramp_only else [],
        "decode_starts": _decode_starts(min(reps.values()), quantum, max_len,
                                        chunk, max_total),
        "horizon": {"cycles": cycles, "groups": made[-1][1] + made[-1][2],
                    "steps": made[-1][0], "passes": len(made)},
    }


def unplanned_passes(p, made, prompt_lens, quantum, max_len):
    """The passes a closed loop really made (`made`: [(step, first group,
    groups)], `kinds/rollout.py ClosedLoop.passes`) that plan `p` does not
    hold, as [step, [prompt lengths]]; [] for a plan by stated rows."""
    if "passes" not in p:
        return []

    def keys(lens):
        return tuple(length_key(L, quantum, max_len) for L in lens)

    held = {keys(x) for x in p["passes"] + p["left_to_ramp"]}
    G = len(prompt_lens)
    out = []
    for step, first, k in made:
        lens = [int(prompt_lens[g % G]) for g in range(first, first + k)]
        if keys(lens) not in held:
            out.append([step, lens])
    return out


def warm(eng, Request, vocab, seed, prompt_lens, group_size, max_total,
         max_admit, temperature=1.0):
    """Run `plan` (stated rows).  `Request` is the engine's request type
    (`GenRequest`), built by keyword."""
    p = plan(eng.n_slots, eng.prompt_bucket, eng.max_seq_len, eng.decode_chunk,
             prompt_lens, group_size, max_total, max_admit)
    return _run(eng, Request, vocab, seed, p, group_size, temperature)


def admit_rows(traffic, n_slots):
    """The most requests one admission pass takes under `plan`'s walk: the
    file's `warm_max_admit`, taken at its word (the cells from before PR 41,
    whose windows their smaller plans do cover), else min(`n_slots`,
    `groups_in_flight` x `group_size`): every slot or every request in
    flight, whichever is fewer."""
    if "warm_max_admit" in traffic:
        return int(traffic["warm_max_admit"])
    return min(int(n_slots),
               int(traffic["groups_in_flight"]) * int(traffic["group_size"]))


def queues(traffic, n_slots):
    """Whether the closed loop keeps more requests submitted than the grid
    has slots (`rollout_decode`: 96 over 64).  Members of a group then wait
    for slots and are admitted one by one, beside whoever else waits: which
    rows share a pass is the engine's queue discipline, not arithmetic on
    the work list, and `plan`'s walk over rows is what covers it."""
    return (int(traffic["groups_in_flight"]) * int(traffic["group_size"])
            > int(n_slots))


def warm_closed_loop(eng, Request, vocab, seed, traffic, groups):
    """The warm-up of a closed loop over `groups` (`traffic.rollout_groups`'
    work list): `plan`'s walk up to `admit_rows` where the file states
    `warm_max_admit` or the loop queues, else what the loop reaches
    (`plan_by_reach`)."""
    max_total = traffic["prompt_len"]["hi"] + traffic["output_len"]["hi"]
    lens = [len(g["prompt"]) for g in groups]
    if "warm_max_admit" in traffic or queues(traffic, eng.n_slots):
        return warm(eng, Request, vocab, seed, lens, traffic["group_size"],
                    max_total, admit_rows(traffic, eng.n_slots),
                    traffic["temperature"])
    p = plan_by_reach(lens, [max(g["budgets"]) for g in groups],
                      int(traffic["groups_in_flight"]), eng.prompt_bucket,
                      eng.max_seq_len, eng.decode_chunk, max_total)
    return _run(eng, Request, vocab, seed, p, traffic["group_size"],
                traffic["temperature"])


def _run(eng, Request, vocab, seed, p, group_size, temperature):
    """Send what plan `p` holds, with throw-away tokens; -> p."""
    rng = np.random.default_rng([int(seed), 11])
    n = 0

    def ids(L):
        return rng.integers(0, vocab, L).tolist()

    def request(rid, input_ids, max_new_tokens=1, group_id="", group_n=0):
        return Request(rid=rid, input_ids=input_ids,
                       max_new_tokens=max_new_tokens, temperature=temperature,
                       group_id=group_id, group_n=group_n)

    def group(tag, L, m):
        prompt = ids(L)
        return [request(f"{tag}-{i}", prompt, group_id=tag, group_n=m)
                for i in range(m)]

    def go(reqs):
        eng.submit_batch(reqs)
        _drain(eng, reqs)

    for lens in p.get("passes", []):
        n += 1
        go([r for gi, L in enumerate(lens)
            for r in group(f"wp{n}-{gi}", L, group_size)])
    for L in p.get("prompt_lens", []):
        for k in p["fresh_rows"]:
            n += 1
            prompts = [ids(L) for _ in range(k)]
            go([request(f"wf{n}-{i}", prompts[i]) for i in range(k)])
            if k in p["reuse_rows"]:
                # the same prompts again: each matches the prefix its first
                # copy left in a freed slot
                go([request(f"wr{n}-{i}", prompts[i]) for i in range(k)])
        for sizes in p["sibling_rounds"]:
            n += 1
            go([r for gi, m in enumerate(sizes)
                for r in group(f"ws{n}-{gi}", L, m)])
    for L in p["decode_starts"]:
        n += 1
        go([request(f"wd{n}", ids(L), max_new_tokens=eng.decode_chunk + 2)])
    return p
