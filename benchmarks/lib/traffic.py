"""One general traffic generator, driven by a cell's `traffic` parameters.

Every seed gets the SAME sizes (drawn once, by stratified quantiles, from the
distributions the workload file names, and put in an order fixed by the
file's `size_seed`) with other token ids.  Where order cannot change the
work (which row a packed sequence lands in) the seed permutes it; where it
can, it is fixed: which groups a closed loop reaches within its window
(two seeds that reached other groups differed by 3.7% in tokens/s while two
runs of one order differed by 0.4%), which sibling of a group has which
budget (siblings are admitted one by one as slots free, so their order
decides who starts first: the 95th percentile of TPOT followed the seed,
72.8 to 78.1 ms, and repeated to 0.1 ms for one seed), which prompts share a
step of the loop (my chip runs 2-4, PR 23).

Length distributions (`{"dist": ...}`):
    {"dist": "uniform", "lo": a, "hi": b}                 whole numbers a..b
    {"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}
    {"dist": "fixed", "value": v}
"""

import math
from statistics import NormalDist

import numpy as np


def quantile(spec, u):
    """The u-quantile (0 < u < 1) of a length distribution, a whole number."""
    d = spec["dist"]
    if d == "fixed":
        return int(spec["value"])
    if d == "uniform":
        lo, hi = int(spec["lo"]), int(spec["hi"])
        return min(hi, lo + int(u * (hi - lo + 1)))
    if d == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf(u))
        return int(min(spec["hi"], max(spec["lo"], round(x))))
    raise ValueError(f"unknown length distribution {d!r}")


def stratified(spec, n):
    """n lengths, one from the middle of each of n equal-probability strata."""
    return [quantile(spec, (i + 0.5) / n) for i in range(n)]


def _rng(seed, stream):
    """`seed` is a whole number or a list of them (a seed and a cycle)."""
    parts = list(seed) if isinstance(seed, (list, tuple)) else [seed]
    return np.random.default_rng([int(x) for x in parts] + [int(stream)])


# ---------------------------------------------------------------------------
# rollout: GRPO groups for a closed loop
# ---------------------------------------------------------------------------


def rollout_groups(traffic, vocab, seed):
    """-> list of groups, each {"prompt": [ids], "budgets": [n]*group_size}.

    `n_groups` groups: prompt lengths are the stratified quantiles of
    `prompt_len`; the `n_groups * group_size` output budgets are the
    stratified quantiles of `output_len`, dealt so that every group holds
    one budget from each of `group_size` bands (each group is about the
    same amount of work).  `size_seed` fixes all of that: which group gets
    which prompt length and which budget of a band, the order of the groups
    and of the budgets within a group.  The seed draws the token ids.
    """
    G, n = int(traffic["n_groups"]), int(traffic["group_size"])
    sizes = np.random.default_rng(int(traffic["size_seed"]))
    rng = _rng(seed, 1)
    plens = np.array(stratified(traffic["prompt_len"], G))
    budgets = np.array(stratified(traffic["output_len"], G * n)).reshape(n, G)
    for band in budgets:  # band b holds the b-th n-tile of the budgets
        sizes.shuffle(band)
    sizes.shuffle(plens)
    groups = []
    for g in range(G):
        b = budgets[:, g].copy()
        sizes.shuffle(b)
        groups.append({
            "prompt": rng.integers(0, vocab, int(plens[g])).tolist(),
            "budgets": [int(x) for x in b],
        })
    return groups


# ---------------------------------------------------------------------------
# train: packed PPO batches
# ---------------------------------------------------------------------------


def _pack_rows_needed(lens, row_len):
    """First-fit-decreasing row count (the arithmetic of the repo's
    `pack_into_rows`, copied so the yardstick does not move with it)."""
    space = []
    for n in sorted(lens, reverse=True):
        for r in range(len(space)):
            if space[r] >= n:
                space[r] -= n
                break
        else:
            space.append(row_len - n)
    return len(space)


def train_sequence_lengths(traffic):
    """The fixed multiset of (prompt, response) lengths of ONE batch: take
    stratified sequences in a fixed interleaved order until the next one
    would need more than `rows` rows of `row_len`."""
    rows, row_len = int(traffic["rows"]), int(traffic["row_len"])
    n = int(traffic["candidates"])
    p = stratified(traffic["prompt_len"], n)
    r = stratified(traffic["response_len"], n)
    # a fixed permutation decorrelates prompt and response strata
    order = np.random.default_rng(int(traffic["size_seed"]))
    p = [p[i] for i in order.permutation(n)]
    r = [r[i] for i in order.permutation(n)]
    pairs, lens = [], []
    for pi, ri in zip(p, r):
        ri = max(1, min(ri, row_len - pi))
        if _pack_rows_needed(lens + [pi + ri], row_len) > rows:
            continue
        pairs.append((pi, ri))
        lens.append(pi + ri)
    return pairs


def train_batches(traffic, vocab, seed):
    """-> `pool` padded PPO batches (dicts of numpy arrays) that the repo's
    packer puts into exactly `rows` rows of `row_len`; same lengths for
    every seed and every batch of the pool, other order and token ids."""
    pairs = train_sequence_lengths(traffic)
    row_len = int(traffic["row_len"])
    out = []
    for k in range(int(traffic["pool"])):
        rng = _rng(seed, 100 + k)
        order = rng.permutation(len(pairs))
        B = len(pairs)
        ids = np.zeros((B, row_len), np.int32)
        mask = np.zeros((B, row_len), bool)
        loss_mask = np.zeros((B, row_len), np.float32)
        for b, j in enumerate(order):
            pl, rl = pairs[j]
            n = pl + rl
            ids[b, :n] = rng.integers(0, vocab, n)
            mask[b, :n] = True
            loss_mask[b, pl:n] = 1.0
        out.append({
            "input_ids": ids,
            "attention_mask": mask,
            "loss_mask": loss_mask,
            "logprobs": (rng.normal(-1.0, 0.1, (B, row_len)).astype(np.float32)
                         * mask),
            # half the sequences rewarded, always: a batch of equal rewards
            # has zero advantages and trains nothing
            "rewards": rng.permutation(
                (np.arange(B) % 2).astype(np.float32)),
            "versions": np.zeros((B, row_len), np.int32),
        })
    return out


# ---------------------------------------------------------------------------
# loop: a prompt dataset with per-prompt output budgets
# ---------------------------------------------------------------------------


def loop_dataset(traffic, vocab, seed):
    """-> list of dataset items {"input_ids", "query_id", "max_new_tokens"}."""
    n = int(traffic["dataset_size"])
    rng = _rng(seed, 2)
    plens = stratified(traffic["prompt_len"], n)
    budgets = np.array(stratified(traffic["output_len"], n))
    # which item has which budget is fixed by the file, like the loader's
    # order: the seed draws the tokens
    np.random.default_rng(int(traffic["size_seed"])).shuffle(budgets)
    return [
        {
            "input_ids": rng.integers(0, vocab, plens[i]).tolist(),
            "query_id": str(i),
            "max_new_tokens": int(budgets[i]),
        }
        for i in range(n)
    ]
