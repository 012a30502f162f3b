"""`GenEngine` with a hybrid stack (`nemotron_h`): a slot of the pool holds
the recurrent state and convolution window of every Mamba block AND the K/V
columns of every attention block.  Toy config on the CPU (pattern `ME*ME`,
hidden 64, 8 experts top-3 with 4 held, state 16, float32, seeded random
weights); log-probs are compared with the benchmark's plain float32
reference (`benchmarks/lib/reference_nemotron_h.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from tests.engine_attrs import build_engine
from tests.test_hybrid_model import CFG, HF, _params, ref

COUNTERS = ("state_copies", "state_copy_bytes", "state_reuse_dropped",
            "sibling_reprefills", "expert_assignments_held", "experts_touched")
# a slot's share of the pool: 2 Mamba blocks x (8 x 8 x 16 state + 3 x 128
# window) x 4 bytes, and 1 attention block x (k, v) x 2 heads x 16 x 4 bytes
STATE_BYTES = 2 * (8 * 8 * 16 + 3 * 128) * 4
KV_TOKEN_BYTES = 2 * 2 * 16 * 4


@pytest.fixture(scope="module")
def params():
    return _params()


def _engine(params, **kw):
    kw = {"n_slots": 6, "max_seq_len": 128, "prompt_bucket": 16, "seed": 1,
          "decode_chunk": 4, "kv_dtype": "float32", **kw}
    return build_engine(CFG, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 128, n).tolist()


def _req(rid, prompt, new, **kw):
    return GenRequest(rid=rid, input_ids=list(prompt), max_new_tokens=new,
                      temperature=1.0, **kw)


def _reference_error(params, req):
    """Largest |engine log-prob - reference log-prob| over the request's
    sampled tokens."""
    seq = np.asarray([list(req.input_ids) + list(req.output_tokens)], np.int32)
    want = np.asarray(ref.next_token_logprobs(params, HF, seq))[0]
    P = len(req.input_ids)
    got = np.asarray(req.output_logprobs)
    assert len(got) == len(req.output_tokens) == req.max_new_tokens
    return float(np.abs(got - want[P - 1: P - 1 + len(got)]).max())


def _delta(eng, before):
    return {k: eng.stats[k] - before[k] for k in eng.stats
            if isinstance(eng.stats[k], (int, float))}


def test_a_slot_holds_state_window_and_columns(params):
    eng = _engine(params)
    assert set(eng.cache) == {"k", "v", "s", "c"}
    assert eng.cache["k"].shape == (1, 7, 128, 2, 16)  # one `*` block
    assert eng.cache["s"].shape == (2, 7, 8, 8, 16)  # two `M` blocks
    assert eng.cache["c"].shape == (2, 7, 3, 128)
    assert eng.cache["s"].dtype == jnp.float32
    assert eng._state_bytes == STATE_BYTES
    assert eng._kv_token_bytes == KV_TOKEN_BYTES
    # the columns are windowed as a dense model's; one tier
    assert eng.decode_window and eng.n_tiers == 1
    for k in COUNTERS:
        assert eng.stats[k] == 0


@pytest.mark.parametrize("asked", ["bfloat16", "float32"])
def test_the_state_is_float32_whatever_the_cache_dtype(asked):
    from areal_tpu.models.transformer import init_kv_cache

    cache = init_kv_cache(CFG, 3, 64, dtype=asked)
    assert cache["s"].dtype == jnp.float32
    assert {cache[n].dtype for n in "kvc"} == {jnp.dtype(asked)}


@pytest.mark.parametrize("call", ["export_request_kv", "import_request_kv"])
def test_kv_handoff_is_refused_at_the_call(params, call):
    eng = _engine(params)
    arg = _prompt(0, 40) if call == "export_request_kv" else {"tokens": []}
    with pytest.raises(ValueError, match=call):
        getattr(eng, call)(arg)


@pytest.fixture(scope="module")
def grouped(params):
    """A group of 8 over 6 slots plus a single prompt: six members are
    admitted together (one prefill of the shared span, five copies), two
    come late."""
    eng = _engine(params)
    prompt = _prompt(1, 37)
    group = [_req(f"g-{i}", prompt, 9 + i, group_id="g", group_n=8)
             for i in range(8)]
    single = _req("s", _prompt(2, 21), 8)
    before = dict(eng.stats)
    eng.generate_blocking(group + [single])
    return eng, group, single, _delta(eng, before)


def test_group_fan_out_copies_state_window_and_columns(grouped):
    _, group, _, d = grouped
    assert d["state_copies"] == 5
    # the state and windows whole, and the K/V columns of the copied span
    # (36 shared tokens in the bucket of 64)
    assert d["state_copy_bytes"] == 5 * (STATE_BYTES + 64 * KV_TOKEN_BYTES)
    assert d["shared_tokens"] == 5 * 36
    assert d["copy_calls"] == 1  # the column copy, fused into the suffix
    assert [r.cache_hit_tokens for r in group[:6]] == [0, 36, 36, 36, 36, 36]


@pytest.mark.parametrize("which", range(9))
def test_every_request_of_the_group_gives_reference_logprobs(
        grouped, params, which):
    _, group, single, _ = grouped
    assert _reference_error(params, (group + [single])[which]) < 5e-5


def test_a_sibling_admitted_late_prefills_its_prompt_again(grouped):
    _, group, _, d = grouped
    assert d["sibling_reprefills"] == 2
    assert [r.cache_hit_tokens for r in group[6:]] == [0, 0]
    assert d["prefill_tokens"] == 36 + 37 + 37 + 21
    assert d["suffix_tokens"] == 6  # the last prompt token of each member


def test_decode_counts_what_the_held_experts_saw(grouped):
    _, _, _, d = grouped
    # 2 expert blocks x 4 held experts a pass at most
    assert 0 < d["experts_touched"] <= d["decode_passes"] * 2 * 4
    # top-3 of 8 with half of them held: about 1.5 a live token a block
    assert 0 < d["expert_assignments_held"] <= d["decode_passes"] * 6 * 3 * 2


def test_the_next_turn_continues_from_the_whole_retained_slot(params):
    eng = _engine(params)
    first = _req("t1", _prompt(3, 30), 5)
    eng.generate_blocking([first])
    assert int(eng.retained_len.max()) == 34
    turn2 = list(first.input_ids) + list(first.output_tokens) + _prompt(4, 11)
    before = dict(eng.stats)
    second = _req("t2", turn2, 6)
    eng.generate_blocking([second])
    d = _delta(eng, before)
    assert d["reused_tokens"] == 34 and d["suffix_tokens"] == len(turn2) - 34
    assert d["prefill_calls"] == 0 and d["state_reuse_dropped"] == 0
    assert d["state_copies"] == 0  # its own state, nobody else's
    assert second.cache_hit_tokens == 34
    assert _reference_error(params, second) < 5e-5


def test_a_partial_match_of_a_retained_state_is_dropped(params):
    eng = _engine(params)
    first = _req("p1", _prompt(5, 30), 5)
    eng.generate_blocking([first])
    branch = list(first.input_ids)[:25] + _prompt(6, 9)
    before = dict(eng.stats)
    other = _req("p2", branch, 3)
    eng.generate_blocking([other])
    d = _delta(eng, before)
    # the K/V columns could be cut back to 25; the state beside them cannot
    assert d["state_reuse_dropped"] == 1 and d["reused_tokens"] == 0
    assert d["prefill_tokens"] == len(branch) and other.cache_hit_tokens == 0
    assert _reference_error(params, other) < 5e-5


def test_a_stop_inside_a_chunk_leaves_the_pool_consistent(params):
    eng = _engine(params, n_slots=2)
    req = _req("m", _prompt(7, 30), 3)  # ends on the second step of a chunk
    eng.generate_blocking([req])
    assert int(eng.retained_len.max()) == 0  # the state ran past the host
    assert _reference_error(params, req) < 5e-5
    # whoever takes the slot next starts from nothing of it
    after = [_req(f"n{i}", _prompt(20 + i, 17 + i), 6) for i in range(3)]
    eng.generate_blocking(after)
    for r in after:
        assert _reference_error(params, r) < 5e-5


def test_abort_and_resubmit_gives_reference_logprobs(params):
    eng = _engine(params, abort_reserve_s=0.0)
    req = _req("a", _prompt(9, 26), 12)
    eng.submit(req)
    eng.step()
    assert eng.abort_all("abort") == 1 and req.stop_reason == "abort"
    again = _req("a2", list(req.input_ids) + list(req.output_tokens), 7)
    eng.generate_blocking([again])
    assert _reference_error(params, again) < 5e-5
    assert all(r is None for r in eng.slot_req)


def test_a_large_first_fill_goes_in_several_dispatches(params):
    """More padded tokens than one prefill dispatch of a recurrent-state
    model takes: the rows go in order, siblings before the representative
    they start from, and nobody's result changes."""
    eng = _engine(params)
    assert eng._state_admit_tokens == 16 * eng.model_config.mamba_chunk
    eng._state_admit_tokens = 32  # two rows of a 16-token suffix bucket
    prompt = _prompt(11, 21)
    group = [_req(f"h-{i}", prompt, 5, group_id="h", group_n=6)
             for i in range(6)]
    before = dict(eng.stats)
    eng.generate_blocking(group)
    d = _delta(eng, before)
    assert d["suffix_calls"] == 3 and d["state_copies"] == 5
    for r in group:
        assert _reference_error(params, r) < 5e-5
    # whole prompts too: three fresh rows of a 32-token bucket, one a call
    singles = [_req(f"f-{i}", _prompt(30 + i, 20), 4) for i in range(3)]
    before = dict(eng.stats)
    eng.generate_blocking(singles)
    assert _delta(eng, before)["prefill_calls"] == 3
    for r in singles:
        assert _reference_error(params, r) < 5e-5


def test_engine_logprobs_equal_the_packed_forward_s(grouped, params):
    """What the trainer recomputes for a rollout is the packed forward of
    the same model: it gives the log-probs the engine returned."""
    from areal_tpu.models import transformer as tf

    _, group, _, _ = grouped
    r = group[3]
    seq = np.asarray(list(r.input_ids) + list(r.output_tokens), np.int32)
    T = len(seq)
    logits = tf.forward(
        params, CFG, jnp.asarray(seq[None]),
        jnp.arange(T, dtype=jnp.int32)[None], jnp.zeros((1, T), jnp.int32))[0]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    P = len(r.input_ids)
    want = np.asarray(logp[np.arange(P - 1, T - 1), seq[P:]])
    np.testing.assert_allclose(r.output_logprobs, want, atol=5e-5)
