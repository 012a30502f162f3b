"""Disaggregated prefill/decode KV handoff (ISSUE 17) — engine-level
exactness and protocol tests.

The contract under test: a request's page set serialized on one engine
(`export_request_kv` -> wire format) and imported on ANOTHER engine
(`import_request_kv` -> host-tier entry, swap-in re-scatter) continues
the token stream bit-identically — tokens AND logprobs — to the same
two-leg split served by a single engine.  The counter-keyed sampler
makes the stream a pure function of (stream_id, position), so the only
thing the transfer may change is *where* the tail runs, never *what* it
emits.  The colocated control is the same two-leg split on one engine
(not a one-shot run): decode-vs-suffix XLA programs may legitimately
differ in the last ulp at the handoff boundary, and this suite pins the
transfer, not boundary numerics.
"""

import os

import numpy as np
import pytest

from areal_tpu.gen import kv_pool
from areal_tpu.gen.engine import GenRequest
from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config
from tests.engine_attrs import build_engine


@pytest.fixture(scope="module", autouse=True)
def _debug_locks():
    old = os.environ.get("AREAL_DEBUG_LOCKS")
    os.environ["AREAL_DEBUG_LOCKS"] = "1"
    yield
    if old is None:
        os.environ.pop("AREAL_DEBUG_LOCKS", None)
    else:
        os.environ["AREAL_DEBUG_LOCKS"] = old


@pytest.fixture(scope="module")
def setup(_debug_locks):
    import jax

    cfg = tiny_config(vocab_size=97, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    base = dict(n_slots=2, max_seq_len=128, prompt_bucket=16,
                kv_dtype="float32", reuse_min_tokens=4)
    base.update(kw)
    return build_engine(cfg, params, **base)


def _leg(eng, ids, n, *, stream_id, temp):
    r = GenRequest(rid=f"leg-{stream_id}-{len(ids)}", input_ids=list(ids),
                   max_new_tokens=n, temperature=temp, top_p=0.9,
                   stream_id=stream_id)
    eng.generate_blocking([r])
    return r


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_wire_round_trip_bit_exact(setup):
    """encode -> decode must reproduce every KV array byte-for-byte (same
    dtype, shape, bytes) plus tokens/valid_len/version — the wire is a
    host-side re-encoding, never a numeric conversion."""
    cfg, params = setup
    eng = _engine(cfg, params)
    rng = np.random.default_rng(40)
    prompt = rng.integers(0, 97, 24).tolist()
    r = _leg(eng, prompt, 1, stream_id=7, temp=0.0)
    entry = eng.export_request_kv(prompt + r.output_tokens)
    assert entry is not None
    assert eng.stats["kv_handoff_exports"] == 1

    doc = kv_pool.wire_encode_entry(entry)
    assert doc["nbytes"] > 0
    back = kv_pool.wire_decode_entry(doc)
    assert list(back["tokens"]) == list(entry["tokens"])
    assert back["valid_len"] == entry["valid_len"]
    assert back["version"] == entry["version"]
    assert set(back["kv"]) == set(entry["kv"])
    for k, a in entry["kv"].items():
        b = back["kv"][k]
        src = np.asarray(a)
        assert b.dtype == src.dtype and b.shape == src.shape
        assert src.tobytes() == np.asarray(b).tobytes()


def test_export_unknown_prefix_returns_none(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    assert eng.export_request_kv([11, 13, 17, 19] * 8) is None
    assert eng.stats["kv_handoff_failures"] >= 1


def test_import_without_host_tier_refused(setup):
    cfg, params = setup
    src = _engine(cfg, params)
    rng = np.random.default_rng(41)
    prompt = rng.integers(0, 97, 20).tolist()
    r = _leg(src, prompt, 1, stream_id=5, temp=0.0)
    entry = src.export_request_kv(prompt + r.output_tokens)
    dst = _engine(cfg, params)  # no host_offload: nowhere to install
    assert dst.import_request_kv(entry) is False
    assert dst.stats["kv_handoff_failures"] >= 1


# ---------------------------------------------------------------------------
# cross-engine continuation exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("leg1_n,temp", [(1, 0.0), (1, 1.0), (5, 1.0)])
def test_handoff_continuation_bit_identical(setup, leg1_n, temp):
    """The tentpole pin: leg 1 on a 'prefill' engine, export/import, leg 2
    on a 'decode' engine — versus the SAME two-leg split on one engine.
    Greedy and sampled, including a mid-generation handoff (leg 1 longer
    than one token).  Tokens and logprobs must match exactly."""
    cfg, params = setup
    rng = np.random.default_rng(42 + leg1_n)
    prompt = rng.integers(0, 97, 27).tolist()
    total, sid = 8, 90 + leg1_n

    # colocated control: both legs on one engine
    ctl = _engine(cfg, params)
    c1 = _leg(ctl, prompt, leg1_n, stream_id=sid, temp=temp)
    assert len(c1.output_tokens) == leg1_n
    ctl_ids = prompt + c1.output_tokens
    c2 = _leg(ctl, ctl_ids, total - leg1_n, stream_id=sid, temp=temp)
    assert c2.cache_hit_tokens > 0  # warm continuation, not a cold prefill

    # disaggregated: leg 1 on A, wire transfer, leg 2 on B
    ea = _engine(cfg, params)
    eb = _engine(cfg, params, host_offload=True, host_cache_mb=8,
                 host_min_tokens=8)
    a1 = _leg(ea, prompt, leg1_n, stream_id=sid, temp=temp)
    assert a1.output_tokens == c1.output_tokens
    assert a1.output_logprobs == c1.output_logprobs
    full_ids = prompt + a1.output_tokens
    doc = kv_pool.wire_encode_entry(ea.export_request_kv(full_ids))
    assert eb.import_request_kv(kv_pool.wire_decode_entry(doc)) is True
    assert eb.stats["kv_handoff_imports"] == 1
    b2 = _leg(eb, full_ids, total - leg1_n, stream_id=sid, temp=temp)

    # the import was admitted as a warm-cache hit on the decode engine
    assert b2.cache_hit_tokens > 0
    assert eb.stats["prefix_cache_host_swaps"] >= 1
    assert b2.output_tokens == c2.output_tokens
    assert b2.output_logprobs == c2.output_logprobs


def test_handoff_stream_without_pin_still_exact_same_allocation(setup):
    """Engine-allocated stream ids are written back to the request — the
    server surfaces them so leg 2 can pin what leg 1 drew.  Two engines
    seeded identically allocate the same first id, and the pinned
    continuation reproduces the unpinned engine's stream."""
    cfg, params = setup
    rng = np.random.default_rng(44)
    prompt = rng.integers(0, 97, 21).tolist()

    one = _engine(cfg, params)
    solo = GenRequest(rid="solo", input_ids=list(prompt), max_new_tokens=6,
                      temperature=1.0, top_p=0.9)
    one.generate_blocking([solo])
    assert solo.stream_id > 0  # allocation written back

    ea = _engine(cfg, params)
    eb = _engine(cfg, params, host_offload=True, host_cache_mb=8,
                 host_min_tokens=8)
    a1 = GenRequest(rid="a1", input_ids=list(prompt), max_new_tokens=1,
                    temperature=1.0, top_p=0.9)
    ea.generate_blocking([a1])
    assert a1.stream_id == solo.stream_id
    full = prompt + a1.output_tokens
    eb.import_request_kv(ea.export_request_kv(full))
    b2 = _leg(eb, full, 5, stream_id=a1.stream_id, temp=1.0)
    assert a1.output_tokens + b2.output_tokens == solo.output_tokens


# ---------------------------------------------------------------------------
# page-granular partial prefix hits (satellite)
# ---------------------------------------------------------------------------


def test_partial_hit_page_floored_accounting(setup):
    """A request whose device match loses its donor slot to a longer match
    — and that can't ride the batch's cluster fan-out, because the
    winners cluster among their own declared group — still inherits the
    donor prefix up to a page (prompt-bucket) boundary: counted in
    prefix_cache_partial_hits, credited page-floored in
    cache_hit_tokens, and bit-identical to a cold run."""
    cfg, params = setup
    rng = np.random.default_rng(45)
    base = rng.integers(0, 97, 37).tolist()

    eng = _engine(cfg, params, n_slots=4)
    r0 = _leg(eng, base, 4, stream_id=11, temp=0.0)
    transcript = base + r0.output_tokens
    # one batch: the declared group's two continuations cluster together
    # (one wins r0's slot in place, the sibling fans out from it), and
    # the groupless loser — divergent at 37 — finds its only donor
    # claimed, so it copy-shares 32 tokens (37 floored to the 16-page
    # grid) instead of cold-prefilling
    w1 = GenRequest(rid="w1", input_ids=transcript + [1],
                    max_new_tokens=4, temperature=0.0, stream_id=12,
                    group_id="gw", group_n=2)
    w2 = GenRequest(rid="w2", input_ids=transcript + [2],
                    max_new_tokens=4, temperature=0.0, stream_id=14,
                    group_id="gw", group_n=2)
    loser = GenRequest(rid="l", input_ids=base[:37] + [7, 8, 9],
                       max_new_tokens=4, temperature=0.0, stream_id=13)
    eng.generate_blocking([w1, w2, loser])
    assert eng.stats["prefix_cache_partial_hits"] == 1
    assert loser.cache_hit_tokens == 32  # 37 floored to the 16-page grid

    cold = _engine(cfg, params, n_slots=4)
    ref = GenRequest(rid="ref", input_ids=base[:37] + [7, 8, 9],
                     max_new_tokens=4, temperature=0.0, stream_id=13)
    cold.generate_blocking([ref])
    assert loser.output_tokens == ref.output_tokens
    assert cold.stats["prefix_cache_partial_hits"] == 0
    eng.pool.check_page_table()


# ---------------------------------------------------------------------------
# tp parity (satellite): the handoff is sharding-invariant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temp", [0.0, 1.0])
def test_tp2_handoff_stream_parity(setup, temp):
    """tp=2 vs tp=1, each serving the same two-leg handoff: token streams
    must be bit-identical across shardings (logprobs to float tolerance,
    matching the existing tp-parity pin) — the exported page set is
    gathered/scattered per-shard but represents the same prefix."""
    cfg, params = setup
    rng = np.random.default_rng(46)
    prompt = rng.integers(0, 97, 18).tolist()
    streams = {}
    for tp in (1, 2):
        ea = _engine(cfg, params, tp=tp)
        eb = _engine(cfg, params, tp=tp, host_offload=True,
                     host_cache_mb=8, host_min_tokens=8)
        a1 = _leg(ea, prompt, 1, stream_id=33, temp=temp)
        full = prompt + a1.output_tokens
        doc = kv_pool.wire_encode_entry(ea.export_request_kv(full))
        assert eb.import_request_kv(kv_pool.wire_decode_entry(doc))
        b2 = _leg(eb, full, 5, stream_id=33, temp=temp)
        streams[tp] = (a1.output_tokens + b2.output_tokens,
                       a1.output_logprobs + b2.output_logprobs)
    toks1, lp1 = streams[1]
    toks2, lp2 = streams[2]
    assert toks1 == toks2
    np.testing.assert_allclose(lp2, lp1, rtol=1e-4, atol=1e-4)
