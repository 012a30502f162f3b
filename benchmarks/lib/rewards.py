"""Reward functions for the loop cells.  Importable by name
(`benchmarks.lib.rewards`), because the program's reward pool runs them in
spawned processes."""


def mostly_even(prompt, completions, prompt_ids, completion_ids, **kw):
    """1.0 when more than half of the sampled token ids are even: close to
    a coin flip for random weights, so groups have mixed rewards and the
    advantages are not all zero."""
    n = len(completion_ids)
    return float(sum(1 for t in completion_ids if t % 2 == 0) * 2 > n)
