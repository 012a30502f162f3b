"""The four cache-path forwards against the full forward, token by token.

`forward_prefill`, `forward_prefill_cached`, `forward_decode` and
`forward_verify` share their layer body with each other
(`_attn_inputs`, `_attn_out_and_ffn`, `_append_and_attend`,
`_scan_cache_layers`, `_last_token_logits` in models/transformer.py) but not
with `forward`: what they return for a position must be what the full
forward over the whole sequence returns there, in float32 to rounding.

They also share the cache's route: the stacked cache `[L, S, M, Hkv, hd]`
is neither a scanned input nor a stacked output of the layer scan (as one
it is sliced and stacked back whole, layer by layer: PERF.md, PR 28).  A
layer reads its window `[l, rows, :K]` from the stacked array, the call's
new columns are put into that window at their own indices, and one scatter
after the scan writes them.  The second half of this file pins that route
in the jaxpr and the write's edge cases: what lands, where, and that every
other element of the cache comes back bit for bit."""

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


# ---------------------------------------------------------------------------
# The write: what lands where, and nothing else
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def written(model):
    """A cache full of noise with the three rows prefilled into it, and the
    K/V every position of every row should hold (a fresh prefill of the
    whole rows, position by position what any later write must equal)."""
    cfg, params, ids, _ = model
    rng = np.random.default_rng(11)
    shape = init_kv_cache(cfg, SLOTS, M, "float32")["k"].shape
    noise = {n: jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for n in ("k", "v")}
    padded = np.where(np.arange(P)[None] < np.asarray(LENS)[:, None],
                      ids[:, :P], 0)
    _, cache = forward_prefill(
        params, cfg, jnp.asarray(padded), jnp.asarray(LENS, jnp.int32),
        noise, jnp.asarray(ROWS, jnp.int32))
    _, ref = forward_prefill(
        params, cfg, jnp.asarray(ids), jnp.full((len(LENS),), M, jnp.int32),
        init_kv_cache(cfg, SLOTS, M, "float32"), jnp.asarray(ROWS, jnp.int32))
    return noise, cache, ref


def _only_these_columns_changed(before, after, ref, columns, garbage=()):
    """`columns`: {(slot, position)} that must now hold the reference K/V
    (`garbage`: must have changed, to anything); every other element of
    the cache must be the very bits it was."""
    for name in ("k", "v"):
        b, a, r = (np.asarray(c[name]) for c in (before, after, ref))
        touched = np.zeros(b.shape[1:3], bool)
        for slot, pos in columns:
            np.testing.assert_allclose(a[:, slot, pos], r[:, slot, pos], **TOL)
            touched[slot, pos] = True
        for slot, pos in garbage:
            assert not np.array_equal(a[:, slot, pos], b[:, slot, pos])
            touched[slot, pos] = True
        same = np.broadcast_to(~touched[None, :, :, None, None], b.shape)
        np.testing.assert_array_equal(a[same], b[same])


def test_fresh_prefill_writes_its_rows_first_columns_only(model, written):
    cfg, params, ids, _ = model
    noise, cache, ref = written
    columns = {(slot, pos) for slot, n in zip(ROWS, LENS) for pos in range(n)}
    padding = {(slot, pos) for slot, n in zip(ROWS, LENS)
               for pos in range(n, P)}
    _only_these_columns_changed(noise, cache, ref, columns, padding)


@pytest.mark.parametrize("key_window", [None, 24, 18])
def test_suffix_prefill_scatters_by_position(model, written, key_window):
    """Each row keeps [0, start) and prefills 8 columns from there (the
    suffix bucket: padding columns are written too, above the frontier);
    the window may be the whole row or a bucket under M."""
    cfg, params, ids, full = model
    _, cache, ref = written
    starts, width = (5, 9, 3), 8
    suffix_lens = [min(n - s, width) for n, s in zip(LENS, starts)]
    suffix = np.zeros((len(LENS), width), np.int32)
    for r, (s, n) in enumerate(zip(starts, suffix_lens)):
        suffix[r, :n] = ids[r, s:s + n]
    logits, after = forward_prefill_cached(
        params, cfg, jnp.asarray(suffix), jnp.asarray(starts, jnp.int32),
        jnp.asarray(suffix_lens, jnp.int32), cache,
        jnp.asarray(ROWS, jnp.int32), key_window=key_window)
    for r, (s, n) in enumerate(zip(starts, suffix_lens)):
        np.testing.assert_allclose(logits[r], full[r, s + n - 1], **TOL)
    real = {(slot, s + j) for slot, s, n in zip(ROWS, starts, suffix_lens)
            for j in range(n)}
    padding = {(slot, s + j) for slot, s, n in zip(ROWS, starts, suffix_lens)
               for j in range(n, width)}
    _only_these_columns_changed(cache, after, ref, real, padding)


DECODE_CASES = {
    # name: (key_window, page table or None, inactive slots, {slot: length})
    "plain": (None, None, (1,), {}),
    "windowed": (20, None, (1,), {}),
    "inactive_slot_keeps_its_columns": (24, None, (1, 0), {}),
    "clamped_at_the_window_s_end": (24, None, (1,), {3: 29}),
    "permuted_page_table": (24, (3, 1, 0, 2), (1,), {}),
    "permuted_and_inactive": (20, (2, 3, 1, 0), (1, 3), {}),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_writes_one_column_a_live_slot(model, written, case):
    """Logical slot i of the block sits in physical row `table[i]`.  A live
    slot's new column lands at its length (clamped to the window's last
    column when it ran past it: garbage, in its own row); an inactive
    slot's write is dropped and its retained columns are untouched."""
    cfg, params, ids, full = model
    _, cache, ref = written
    key_window, table, inactive, over = DECODE_CASES[case]
    K = key_window or M
    table = tuple(range(SLOTS)) if table is None else table
    row_of = {slot: r for r, slot in enumerate(ROWS)}
    lengths = np.asarray(
        [over.get(s, LENS[row_of[s]] if s in row_of else 0) for s in table],
        np.int32)
    active = np.asarray([s not in inactive for s in table])
    toks = np.asarray(
        [ids[row_of[s], min(n, M - 1)] if s in row_of else 0
         for s, n in zip(table, lengths)], np.int32)
    kw = {} if table == tuple(range(SLOTS)) else {
        "rows": jnp.asarray(table, jnp.int32)}
    logits, after = forward_decode(
        params, cfg, jnp.asarray(toks), jnp.asarray(lengths), cache,
        key_window=key_window, active=jnp.asarray(active), **kw)
    columns, garbage = set(), set()
    for i, s in enumerate(table):
        if not active[i]:
            continue
        if s in over:
            garbage.add((s, K - 1))
            continue
        columns.add((s, int(lengths[i])))
        np.testing.assert_allclose(
            logits[i], full[row_of[s], lengths[i]], **TOL)
    _only_these_columns_changed(cache, after, ref, columns, garbage)


VERIFY_CASES = {
    # name: (key_window, page table or None, {slot: n_write}, inactive)
    "all_positions": (24, None, {}, (1,)),
    "short_drafts": (24, None, {2: 2, 0: 1, 3: 3}, (1,)),
    "short_drafts_paged": (None, (3, 1, 0, 2), {2: 1, 0: 3}, (1,)),
    "inactive_row_with_drafts": (24, None, {2: 2}, (1, 3)),
}


@pytest.mark.parametrize("case", sorted(VERIFY_CASES))
def test_verify_writes_only_the_positions_it_was_given(model, written, case):
    """Of a row's T input positions the first `n_write` are written and
    scored; the rest (a short draft's padding, an inactive row) drop."""
    cfg, params, ids, full = model
    _, cache, ref = written
    T = 4
    key_window, table, short, inactive = VERIFY_CASES[case]
    table = tuple(range(SLOTS)) if table is None else table
    row_of = {slot: r for r, slot in enumerate(ROWS)}
    lengths = np.asarray([LENS[row_of[s]] if s in row_of else 0
                          for s in table], np.int32)
    n_write = np.asarray([short.get(s, T) for s in table], np.int32)
    active = np.asarray([s not in inactive for s in table])
    toks = np.zeros((SLOTS, T), np.int32)
    for i, s in enumerate(table):
        if s in row_of:
            toks[i] = ids[row_of[s], lengths[i]:lengths[i] + T]
    kw = {} if table == tuple(range(SLOTS)) else {
        "rows": jnp.asarray(table, jnp.int32)}
    logits, after = forward_verify(
        params, cfg, jnp.asarray(toks), jnp.asarray(lengths), cache,
        key_window=key_window, active=jnp.asarray(active),
        n_write=jnp.asarray(n_write), **kw)
    columns = set()
    for i, s in enumerate(table):
        if not active[i]:
            continue
        n = int(n_write[i])
        columns |= {(s, int(lengths[i]) + j) for j in range(n)}
        np.testing.assert_allclose(
            logits[i, :n], full[row_of[s], lengths[i]:lengths[i] + n], **TOL)
    _only_these_columns_changed(cache, after, ref, columns)


# ---------------------------------------------------------------------------
# The route: the cache is no input and no output of a layer scan
# ---------------------------------------------------------------------------


def _scans(jaxpr):
    """Every `scan` equation of a jaxpr, those inside other equations'
    sub-jaxprs too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


def _assert_no_scan_moves_the_cache(jaxpr, cache_shape):
    """No scan takes the stacked cache, or anything of a layer's slab's
    shape, as a scanned input or gives it as a stacked output (the carry
    and the closed-over constants are free to hold it: those are not
    sliced and stacked)."""
    banned = {tuple(cache_shape), tuple(cache_shape[1:])}
    n = 0
    for eqn in _scans(jaxpr):
        n += 1
        consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
        body = eqn.params["jaxpr"].jaxpr
        moved = (list(eqn.invars[consts + carry:]) + list(eqn.outvars[carry:])
                 + list(body.invars[consts + carry:]) + list(body.outvars[carry:]))
        for v in moved:
            assert tuple(v.aval.shape) not in banned, (v.aval, eqn.params["length"])
    assert n, "no scan found: the test looks at nothing"


def _forward_jaxpr(name, cfg, params, cache):
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    live = jnp.ones((SLOTS,), bool)
    return jax.make_jaxpr({
        "forward_prefill": lambda c: forward_prefill(
            params, cfg, i32(3, P), i32(3) + 5, c, i32(3)),
        "forward_prefill_cached": lambda c: forward_prefill_cached(
            params, cfg, i32(3, 8), i32(3) + 4, i32(3) + 3, c, i32(3),
            copy_src=i32(3), copy_block=16, key_window=24),
        "forward_decode": lambda c: forward_decode(
            params, cfg, i32(SLOTS), i32(SLOTS) + 9, c, key_window=24,
            active=live, rows=i32(SLOTS)),
        "forward_verify": lambda c: forward_verify(
            params, cfg, i32(SLOTS, 4), i32(SLOTS) + 9, c, key_window=24,
            active=live, n_write=i32(SLOTS) + 2, rows=i32(SLOTS)),
    }[name])(cache)


@pytest.mark.parametrize("forward_name", [
    "forward_prefill", "forward_prefill_cached", "forward_decode",
    "forward_verify"])
def test_no_layer_scan_takes_or_gives_the_cache(model, forward_name):
    cfg, params, _, _ = model
    cache = init_kv_cache(cfg, SLOTS, M, "float32")
    jaxpr = _forward_jaxpr(forward_name, cfg, params, cache)
    _assert_no_scan_moves_the_cache(jaxpr.jaxpr, cache["k"].shape)


@pytest.mark.parametrize("ragged", [False, True])
def test_no_scan_of_the_engine_s_decode_chunk_takes_or_gives_the_cache(
        model, ragged):
    """The engine's fused chunk is a scan over steps around the layer scan:
    the cache rides the outer carry and no inner scan slices it.  On the
    ragged path the kernel appends in place, so the cache rides the layer
    scan's carry too, its layers laid end to end."""
    from areal_tpu.gen.engine import GenEngine, GenRequest

    cfg, params, _, _ = model
    engine = GenEngine(cfg, params=params, n_slots=SLOTS, max_seq_len=64,
                       prompt_bucket=16, decode_chunk=4, ragged_attn=ragged)
    seen = []
    decode_fn = engine._decode_fn

    def traced(*args):
        seen.append((decode_fn.trace(*args).jaxpr, args[1]["k"].shape))
        return decode_fn(*args)

    engine._decode_fn = traced
    engine.generate_blocking([GenRequest(
        rid="r", input_ids=list(range(3, 12)), max_new_tokens=6,
        temperature=1.0)])
    assert seen
    for jaxpr, cache_shape in seen:
        _assert_no_scan_moves_the_cache(jaxpr.jaxpr, cache_shape)
        flat = (cache_shape[0] * cache_shape[1],) + tuple(cache_shape[2:])
        _assert_no_scan_moves_the_cache(jaxpr.jaxpr, (1,) + flat)
