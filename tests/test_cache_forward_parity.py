"""The four cache-path forwards against the full forward, token by token.

`forward_prefill`, `forward_prefill_cached`, `forward_decode` and
`forward_verify` share their layer body with each other
(`_attn_inputs`, `_attn_out_and_ffn`, `_cache_window`, `_scan_cache_layers`,
`_last_token_logits` in models/transformer.py) but not with `forward`: what
they return for a position must be what the full forward over the whole
sequence returns there, in float32 to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config
from areal_tpu.models.transformer import (
    forward,
    forward_decode,
    forward_prefill,
    forward_prefill_cached,
    forward_verify,
    init_kv_cache,
)

SLOTS, M, P = 4, 32, 16
LENS = (11, 16, 7)  # prompt lengths of the three rows
ROWS = (2, 0, 3)  # the cache slots they occupy, not in order
TOL = dict(rtol=2e-5, atol=2e-5)

CONFIGS = {
    "qwen2_bias": dict(qkv_bias=True, hf_architecture="Qwen2ForCausalLM"),
    "qwen3_qk_norm": dict(qk_norm=True, hf_architecture="Qwen3ForCausalLM"),
    "tied_sliding": dict(tie_word_embeddings=True, sliding_window=6),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg = tiny_config(vocab_size=61, dtype="float32", eos_token_id=None,
                      **CONFIGS[request.param])
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 61, (len(LENS), M)).astype(np.int32)
    pos = np.broadcast_to(np.arange(M, dtype=np.int32), ids.shape)
    full = np.asarray(forward(params, cfg, jnp.asarray(ids), jnp.asarray(pos),
                              jnp.zeros_like(ids)))  # [3, M, V], causal
    return cfg, params, ids, full


def _prefilled(cfg, params, ids, lens):
    cache = init_kv_cache(cfg, SLOTS, M, "float32")
    padded = np.where(np.arange(P)[None] < np.asarray(lens)[:, None],
                      ids[:, :P], 0)
    return forward_prefill(params, cfg, jnp.asarray(padded),
                           jnp.asarray(lens, jnp.int32), cache,
                           jnp.asarray(ROWS, jnp.int32))


def test_prefill_gives_the_last_prompt_token_s_logits(model):
    cfg, params, ids, full = model
    logits, _ = _prefilled(cfg, params, ids, LENS)
    for r, n in enumerate(LENS):
        np.testing.assert_allclose(logits[r], full[r, n - 1], **TOL)


def test_suffix_prefill_over_a_retained_prefix(model):
    cfg, params, ids, full = model
    starts = (5, 9, 3)  # each row keeps a prefix and prefills the rest
    _, cache = _prefilled(cfg, params, ids, starts)
    suffix_lens = [n - s for n, s in zip(LENS, starts)]
    suffix = np.zeros((len(LENS), 8), np.int32)
    for r, (s, n) in enumerate(zip(starts, suffix_lens)):
        suffix[r, :n] = ids[r, s:s + n]
    logits, _ = forward_prefill_cached(
        params, cfg, jnp.asarray(suffix), jnp.asarray(starts, jnp.int32),
        jnp.asarray(suffix_lens, jnp.int32), cache,
        jnp.asarray(ROWS, jnp.int32), key_window=24)
    for r, n in enumerate(LENS):
        np.testing.assert_allclose(logits[r], full[r, n - 1], **TOL)


@pytest.mark.parametrize("paged", [False, True])
def test_decode_steps_follow_the_full_forward(model, paged):
    """Three decode steps through the page table (`rows`) or over the
    contiguous block: each step's logits are the full forward's at that
    position."""
    cfg, params, ids, full = model
    _, cache = _prefilled(cfg, params, ids, LENS)
    table = np.full(SLOTS, 1, np.int32)  # slot 1 is nobody's: idle row
    order = ROWS if paged else range(SLOTS)
    row_of = {slot: r for r, slot in enumerate(ROWS)}
    lengths = np.asarray([LENS[row_of[s]] if s in row_of else 0
                          for s in order], np.int32)
    active = np.asarray([s in row_of for s in order])
    kw = {}
    if paged:
        table[:len(ROWS)] = ROWS
        kw["rows"] = jnp.asarray(table[:len(ROWS)])
    for step in range(3):
        toks = np.asarray([ids[row_of[s], lengths[i]] if s in row_of else 0
                           for i, s in enumerate(order)], np.int32)
        logits, cache = forward_decode(
            params, cfg, jnp.asarray(toks), jnp.asarray(lengths), cache,
            key_window=24, active=jnp.asarray(active), **kw)
        for i, s in enumerate(order):
            if s in row_of:
                np.testing.assert_allclose(
                    logits[i], full[row_of[s], lengths[i]], **TOL)
        lengths = lengths + active.astype(np.int32)


def test_verify_scores_a_run_of_positions_at_once(model):
    cfg, params, ids, full = model
    T = 4
    _, cache = _prefilled(cfg, params, ids, LENS)
    row_of = {slot: r for r, slot in enumerate(ROWS)}
    lengths = np.asarray([LENS[row_of[s]] if s in row_of else 0
                          for s in range(SLOTS)], np.int32)
    toks = np.zeros((SLOTS, T), np.int32)
    for s, r in row_of.items():
        toks[s] = ids[r, lengths[s]:lengths[s] + T]
    active = np.asarray([s in row_of for s in range(SLOTS)])
    logits, _ = forward_verify(
        params, cfg, jnp.asarray(toks), jnp.asarray(lengths), cache,
        key_window=24, active=jnp.asarray(active),
        n_write=jnp.full((SLOTS,), T, jnp.int32))
    for s, r in row_of.items():
        np.testing.assert_allclose(
            logits[s], full[r, lengths[s]:lengths[s] + T], **TOL)
