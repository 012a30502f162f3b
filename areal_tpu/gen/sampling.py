"""Batched token sampling, shape-static for the decode jit.

Counterpart of the sampling the reference delegates to SGLang/vLLM servers
(temperature / top-k / top-p / greedy, areal/api/cli_args.py
GenerationHyperparameters).  Per-slot parameters are arrays so one compiled
step serves heterogeneous requests.  Unrestricted slots (top_k<=0 and
top_p>=1) sample from the full-vocab categorical so the behavior
distribution exactly matches the reported full-vocab log-softmax logprobs
(the PPO importance ratios depend on this agreement); restricted slots run
top-k/top-p inside a static `TOPK_WINDOW`-wide candidate window
(lax.top_k), exact whenever the nucleus fits the window; greedy slots take
the argmax.

No window unless a live slot is restricted: `lax.top_k` over the whole
vocabulary is a sort on the chip and was the largest single operation of a
decoded token, so the window is built under a `lax.cond` on "some live row
is restricted and not greedy".  RL rollouts (temperature 1, top_k 0,
top_p 1) never take that branch; one live `top_p=0.95` request takes it for
the whole dispatch, at the cost it always had.  The engine counts such
passes in `GenEngine.stats["sampler_window_passes"]`.
"""

from typing import Optional

import jax
import jax.numpy as jnp

TOPK_WINDOW = 64
NEG_INF = -1e30


def _masked_window(
    scaled: jax.Array,  # [S, V] fp32, temperature-scaled
    top_k: jax.Array,  # [S] int32; 0 = disabled
    top_p: jax.Array,  # [S]; 1.0 = disabled
):
    """Take the static candidate window and apply top-k/top-p.  Returns
    (masked window logits [S, W], window idx [S, W])."""
    # candidate window (static shape; clamped for tiny vocabularies —
    # lax.top_k rejects k > V)
    window = min(TOPK_WINDOW, scaled.shape[1])
    win_logits, win_idx = jax.lax.top_k(scaled, window)  # [S, W]
    ranks = jnp.arange(window)[None, :]
    # top-k mask (0 = off)
    k = jnp.where(top_k <= 0, window, jnp.minimum(top_k, window))
    keep = ranks < k[:, None]
    # top-p mask over the window distribution
    win_probs = jax.nn.softmax(win_logits, axis=-1)
    cum = jnp.cumsum(win_probs, axis=-1)
    keep &= (cum - win_probs) < top_p[:, None]  # keep first token exceeding p
    keep |= ranks == 0  # top_p=0 must mean near-greedy, never mask everything
    return jnp.where(keep, win_logits, NEG_INF), win_idx


def _token_logprob(scaled: jax.Array, tokens: jax.Array) -> jax.Array:
    logz = jax.nn.logsumexp(scaled, axis=-1)
    tok_logit = jnp.take_along_axis(scaled, tokens[:, None], axis=-1)[:, 0]
    return tok_logit - logz


def _sample(logits, temperature, top_k, top_p, live, draw, rng_win, rng_full):
    """Both samplers; `draw(keys, logits)` is the categorical draw, with
    one key for the batch or one a row.  Each of the two draws sits under
    a `lax.cond` on whether a row can use its result, so the work of a
    pass follows the parameters of the slots in it: the window (and its
    sort) only when a live row is restricted and samples, the
    full-vocabulary draw only when a row is unrestricted."""
    greedy = temperature <= 0.0
    safe_temp = jnp.where(greedy, 1.0, temperature)
    scaled = logits.astype(jnp.float32) / safe_temp[:, None]
    unrestricted = (top_k <= 0) & (top_p >= 1.0)
    wants_window = ~unrestricted & ~greedy
    if live is not None:
        wants_window &= live

    def from_window():
        masked, win_idx = _masked_window(scaled, top_k, top_p)
        choice = draw(rng_win, masked)  # [S] window index
        return jnp.take_along_axis(win_idx, choice[:, None], axis=-1)[:, 0]

    sampled = jax.lax.cond(
        jnp.any(wants_window),
        from_window,
        lambda: jnp.zeros(scaled.shape[:1], jnp.int32),
    )
    # unrestricted slots: full-vocab categorical (behavior == reported
    # logprobs); skipped entirely when every slot is restricted
    full_sampled = jax.lax.cond(
        jnp.any(unrestricted),
        lambda: draw(rng_full, scaled),
        lambda: sampled,
    )
    sampled = jnp.where(unrestricted, full_sampled, sampled)
    # first maximum, which is also the window's rank 0
    tokens = jnp.where(greedy, jnp.argmax(scaled, axis=-1), sampled)
    return tokens, _token_logprob(scaled, tokens)


def sample_tokens(
    logits: jax.Array,  # [S, V] fp32
    rng: jax.Array,
    temperature: jax.Array,  # [S]; 0 = greedy
    top_k: jax.Array,  # [S] int32; 0 = disabled
    top_p: jax.Array,  # [S]; 1.0 = disabled
    live: Optional[jax.Array] = None,  # [S] bool; None = every row
):
    """Returns (tokens [S], logprobs [S]) — logprob of the sampled token
    under the *unmodified* (temperature-scaled) distribution, matching what
    inference servers report and what decoupled PPO consumes.  Rows outside
    `live` still get a token, but never cause the window to be built."""
    return _sample(
        logits, temperature, top_k, top_p, live,
        jax.random.categorical, *jax.random.split(rng),
    )


def sample_tokens_keyed(
    logits: jax.Array,  # [S, V] fp32
    keys: jax.Array,  # [S] per-slot PRNG keys (vmapped leading axis)
    temperature: jax.Array,  # [S]; 0 = greedy
    top_k: jax.Array,  # [S] int32; 0 = disabled
    top_p: jax.Array,  # [S]; 1.0 = disabled
    live: Optional[jax.Array] = None,  # [S] bool; None = every row
):
    """`sample_tokens` with one independent PRNG key PER ROW.

    The batch-keyed sampler draws its noise as one [S, ...] tensor, so a
    row's draw depends on the batch SHAPE — splitting the slot grid into
    length-cohort tiers (ISSUE 5) would change every slot's stream.  Keyed
    per row (the engine derives key = fold(decode_key, stream_id, position)
    — a counter-based scheme), a slot's tokens are a function of its own
    (key, logits) only, so any partitioning of slots into decode dispatches
    yields identical streams: the tiered-vs-untiered parity contract.  (The
    conds reduce over the dispatch, but only decide whether a draw no row
    reads is computed: the values drawn stay row-wise.)"""
    split2 = jax.vmap(lambda k: jax.random.split(k, 2))(keys)  # [S, 2, ...]
    return _sample(
        logits, temperature, top_k, top_p, live,
        jax.vmap(jax.random.categorical), split2[:, 0], split2[:, 1],
    )
