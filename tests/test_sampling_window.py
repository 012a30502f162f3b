"""The sampler builds its top-k/top-p candidate window only when a live
row asked for one (gen/sampling.py), and draws what it always drew.

The oracle below is the sampler as it stood before that rule: it sorted
the whole vocabulary for every row of every pass.  Tokens and log-probs
of every row anybody reads must come out bit-identical.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.gen import sampling
from areal_tpu.gen.sampling import (
    NEG_INF,
    TOPK_WINDOW,
    sample_tokens,
    sample_tokens_keyed,
)
from tests.fixtures import sorts_outside_conditionals

# --- the oracle: always builds the window --------------------------------


def _oracle_front(logits, temperature, top_k, top_p):
    S, V = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    safe_temp = jnp.where(greedy, 1.0, temperature)
    scaled = logits / safe_temp[:, None]
    window = min(TOPK_WINDOW, V)
    win_logits, win_idx = jax.lax.top_k(scaled, window)
    ranks = jnp.arange(window)[None, :]
    k = jnp.where(top_k <= 0, window, jnp.minimum(top_k, window))
    keep = ranks < k[:, None]
    win_probs = jax.nn.softmax(win_logits, axis=-1)
    cum = jnp.cumsum(win_probs, axis=-1)
    keep &= (cum - win_probs) < top_p[:, None]
    keep |= ranks == 0
    masked = jnp.where(keep, win_logits, NEG_INF)
    return scaled, masked, win_idx, greedy


def _oracle_back(scaled, masked, win_idx, greedy, top_k, top_p, draw_win, draw_full):
    choice = draw_win(masked)
    sampled = jnp.take_along_axis(win_idx, choice[:, None], axis=-1)[:, 0]
    unrestricted = (top_k <= 0) & (top_p >= 1.0)
    full_sampled = jax.lax.cond(
        jnp.any(unrestricted), lambda: draw_full(scaled), lambda: sampled
    )
    sampled = jnp.where(unrestricted, full_sampled, sampled)
    tokens = jnp.where(greedy, win_idx[:, 0], sampled)
    logz = jax.nn.logsumexp(scaled, axis=-1)
    tok_logit = jnp.take_along_axis(scaled, tokens[:, None], axis=-1)[:, 0]
    return tokens, tok_logit - logz


@jax.jit
def oracle_tokens(logits, rng, temperature, top_k, top_p):
    front = _oracle_front(logits, temperature, top_k, top_p)
    rng_win, rng_full = jax.random.split(rng)
    return _oracle_back(
        *front, top_k, top_p,
        lambda m: jax.random.categorical(rng_win, m, axis=-1),
        lambda s: jax.random.categorical(rng_full, s, axis=-1),
    )


@jax.jit
def oracle_tokens_keyed(logits, keys, temperature, top_k, top_p):
    front = _oracle_front(logits, temperature, top_k, top_p)
    split2 = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    rng_win, rng_full = split2[:, 0], split2[:, 1]
    return _oracle_back(
        *front, top_k, top_p,
        lambda m: jax.vmap(jax.random.categorical)(rng_win, m),
        lambda s: jax.vmap(jax.random.categorical)(rng_full, s),
    )


# --- batches -------------------------------------------------------------

S = 8
# (temperature, top_k, top_p) per row
UNRESTRICTED = [(1.0, 0, 1.0)] * S
GREEDY = [(0.0, 0, 1.0)] * 4 + [(0.0, 5, 0.9)] * 4
ROLLOUT = [(1.0, 0, 1.0), (0.0, 0, 1.0), (0.7, 0, 1.0), (1.3, 0, 1.0)] * 2
RESTRICTED = [(1.0, 50, 1.0), (1.0, 0, 0.95), (0.7, 5, 0.5), (1.0, 1, 1.0),
              (1.0, 0, 0.0), (1.2, 200, 0.99), (0.5, 3, 1.0), (1.0, 0, 0.3)]
MIXED = [(1.0, 0, 1.0)] * 5 + [(0.0, 0, 1.0), (1.0, 0, 0.9), (0.8, 0, 1.0)]
BATCHES = {
    "unrestricted": UNRESTRICTED,
    "greedy": GREEDY,
    "rollout": ROLLOUT,
    "restricted": RESTRICTED,
    "mixed": MIXED,
}


def _params(rows):
    t, k, p = zip(*rows)
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def _logits(seed, rows, V, ties):
    x = jax.random.normal(jax.random.PRNGKey(1000 + seed), (rows, V), jnp.float32)
    x = x * 3.0
    if ties:
        # the maximum of every row stands at several columns: greedy must
        # take the first, as the window's rank 0 did
        top = jnp.max(x, axis=-1, keepdims=True) + 1.0
        cols = jnp.asarray([V - 3, V // 2, 7])
        x = x.at[:, cols].set(jnp.broadcast_to(top, (rows, 3)))
    return x


def _same(got, want, rows=slice(None)):
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[rows], np.asarray(w)[rows])


SAMPLERS = {
    "batch_key": (
        jax.jit(sample_tokens), oracle_tokens,
        lambda seed, rows: jax.random.PRNGKey(seed),
    ),
    "row_keys": (
        jax.jit(sample_tokens_keyed), oracle_tokens_keyed,
        lambda seed, rows: jax.random.split(jax.random.PRNGKey(seed), rows),
    ),
}


@pytest.mark.parametrize("ties", [False, True], ids=["plain", "ties"])
@pytest.mark.parametrize("V", [50, 256, 151936])
@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("sampler", list(SAMPLERS))
def test_streams_are_the_oracles(sampler, batch, V, ties):
    new, old, key = SAMPLERS[sampler]
    temp, tk, tp = _params(BATCHES[batch])
    for seed in range(2 if V > 1000 else 4):
        logits = _logits(seed, S, V, ties)
        k = key(seed, S)
        _same(new(logits, k, temp, tk, tp), old(logits, k, temp, tk, tp))


@pytest.mark.parametrize("sampler", list(SAMPLERS))
def test_rows_that_are_not_live_do_not_change_the_live_ones(sampler):
    """Restricted rows outside `live` get some token nobody reads; the
    live rows' tokens and log-probs are the oracle's."""
    new, old, key = SAMPLERS[sampler]
    temp, tk, tp = _params(MIXED)
    live = jnp.asarray([r[1] == 0 and r[2] >= 1.0 for r in MIXED])
    for seed in range(4):
        logits = _logits(seed, S, 256, False)
        k = key(seed, S)
        got = new(logits, k, temp, tk, tp, live)
        _same(got, old(logits, k, temp, tk, tp), np.asarray(live))
        assert np.all((np.asarray(got[0]) >= 0) & (np.asarray(got[0]) < 256))
        # with the restricted row live, every row is the oracle's again
        _same(new(logits, k, temp, tk, tp, jnp.ones(S, bool)),
              old(logits, k, temp, tk, tp))


# --- the window is not built unless asked for ----------------------------


def _eqns(jaxpr, under_cond=False):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, under_cond
        inner = under_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


@pytest.mark.parametrize("sampler", ["batch_key", "row_keys"])
def test_top_k_sits_under_a_cond(sampler):
    fn = sample_tokens if sampler == "batch_key" else sample_tokens_keyed
    temp, tk, tp = _params(RESTRICTED)
    logits = _logits(0, S, 256, False)
    key = SAMPLERS[sampler][2](0, S)
    jaxpr = jax.make_jaxpr(fn)(logits, key, temp, tk, tp, jnp.ones(S, bool))
    found = [(n, c) for n, c in _eqns(jaxpr.jaxpr) if n in ("top_k", "sort")]
    assert found and all(c for _, c in found), found
    # ... and in the compiled program no sort runs outside a branch; the
    # oracle's does
    args = (logits, key, temp, tk, tp)
    text = jax.jit(fn).lower(*args, jnp.ones(S, bool)).compile().as_text()
    assert " conditional(" in text
    assert not sorts_outside_conditionals(text)
    assert sorts_outside_conditionals(
        SAMPLERS[sampler][1].lower(*args).compile().as_text())


@pytest.mark.parametrize("case,live,built", [
    ("rollout", None, False),
    ("greedy", None, False),
    ("restricted", None, True),
    ("mixed", None, True),
    # the one restricted row of MIXED is row 6
    ("mixed", [True] * 6 + [False, True], False),
    ("mixed", [False] * 6 + [True, False], True),
    # restricted but greedy rows take the argmax
    ("greedy", [True] * S, False),
])
def test_window_is_built_only_for_a_live_restricted_row(monkeypatch, case, live, built):
    calls = []
    real = sampling._masked_window

    def spy(scaled, top_k, top_p):
        jax.debug.callback(lambda: calls.append(1))
        return real(scaled, top_k, top_p)

    monkeypatch.setattr(sampling, "_masked_window", spy)
    temp, tk, tp = _params(BATCHES[case])
    logits = _logits(0, S, 256, False)
    live = None if live is None else jnp.asarray(live)
    for fn, key in ((sample_tokens, jax.random.PRNGKey(0)),
                    (sample_tokens_keyed,
                     jax.random.split(jax.random.PRNGKey(0), S))):
        calls.clear()
        # a function of its own: the jit cache is keyed by the function
        out = jax.jit(lambda *a, fn=fn: fn(*a))(logits, key, temp, tk, tp, live)
        jax.block_until_ready(out)
        jax.effects_barrier()
        assert bool(calls) == built


# --- the engine: which passes built the window ---------------------------


@pytest.fixture(scope="module")
def tiny():
    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config

    cfg = tiny_config(vocab_size=97, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("kw", [
    {},
    {"decode_tiers": 2},
    {"spec_decode": True, "spec_draft_len": 2},
    {"ragged_attn": True},
], ids=["plain", "tiered", "spec", "ragged"])
def test_engine_counts_the_passes_that_built_the_window(tiny, kw):
    """`stats["sampler_window_passes"]` is the sampler's predicate on the
    host: nothing for rollout parameters, every pass while a `top_p=0.9`
    request is live, nothing again once it has finished, although its slot
    keeps the parameters."""
    from areal_tpu.gen.engine import GenEngine, GenRequest

    cfg, params = tiny
    eng = GenEngine(cfg, params=params, n_slots=4, max_seq_len=256,
                    prompt_bucket=16, kv_dtype="float32", seed=3, **kw)
    rng = np.random.default_rng(5)

    def run(tag, rows):
        before = dict(eng.stats)
        reqs = [
            GenRequest(rid=f"{tag}{i}", input_ids=rng.integers(0, 97, 9).tolist(),
                       max_new_tokens=n, temperature=t, top_k=k, top_p=p)
            for i, (n, t, k, p) in enumerate(rows)
        ]
        eng.generate_blocking(reqs)
        assert all(len(r.output_tokens) == n for r, (n, *_) in zip(reqs, rows))
        return tuple(eng.stats[k] - before[k]
                     for k in ("sampler_window_passes", "decode_passes"))

    # what the benchmark's cells send, and a greedy request: restricted
    # parameters on a greedy slot ask for no window either
    window, passes = run("a", [(12, 1.0, 0, 1.0), (12, 0.0, 5, 0.9), (7, 0.7, 0, 1.0)])
    assert passes > 0 and window == 0
    window, passes = run("b", [(12, 1.0, 0, 0.9)])
    assert passes > 0 and window == passes
    window, passes = run("c", [(12, 1.0, 40, 1.0), (12, 1.0, 0, 1.0)])
    assert 0 < window <= passes
    # every slot is free and says top_p 0.9; most still do while d runs
    run("e", [(3, 1.0, 0, 0.9)] * 4)
    assert (eng.top_p[:4] < 1.0).all() and not any(eng.slot_req)
    window, passes = run("d", [(12, 1.0, 0, 1.0)])
    assert (eng.top_p[:4] < 1.0).any()
    assert passes > 0 and window == 0
