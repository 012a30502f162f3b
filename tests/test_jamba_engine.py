"""`GenEngine` with a hybrid stack of the `jamba` family on its normal path:
a slot of the pool holds the float32 state [16, d_inner] and the
convolution window of every Mamba-1 block AND the K/V columns of the
attention blocks.  The toy config of `tests/test_jamba_model.py` on the
CPU; log-probs are compared with the benchmark's plain float32 reference
(`benchmarks/lib/reference_jamba.py`).  Nobody says `ragged_attn`: every
engine here but `grouped_plain` steps its states through the kernel of
`ops/mamba1_decode.py`, interpreted."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models.transformer import HYBRID_KIND, slot_kind
from tests.engine_attrs import build_engine
from tests.test_hybrid_engine import _delta, _prompt, _req
from tests.test_jamba_model import CFG, HF, TOL, _params, ref

COUNTERS = ("state_copies", "state_copy_bytes", "state_reuse_dropped",
            "sibling_reprefills", "state_rows_stepped")
# a slot's share of the pool: 10 Mamba blocks x (16 x 128 state + 3 x 128
# window) x 4 bytes, and 2 attention blocks x (k, v) x 1 head x 16 x 4 bytes
STATE_BYTES = 10 * (16 * 128 + 3 * 128) * 4
KV_TOKEN_BYTES = 2 * 2 * 16 * 4


@pytest.fixture(scope="module")
def params():
    return _params()


def _engine(params, **kw):
    kw = {"n_slots": 6, "max_seq_len": 256, "prompt_bucket": 16, "seed": 1,
          "decode_chunk": 4, "kv_dtype": "float32", **kw}
    return build_engine(CFG, params, **kw)


def _reference_error(params, req):
    """Largest |engine log-prob - reference log-prob| over the request's
    sampled tokens."""
    seq = np.asarray([list(req.input_ids) + list(req.output_tokens)], np.int32)
    want = np.asarray(ref.next_token_logprobs(params, HF, seq))[0]
    P = len(req.input_ids)
    got = np.asarray(req.output_logprobs)
    assert len(got) == len(req.output_tokens) == req.max_new_tokens
    return float(np.abs(got - want[P - 1: P - 1 + len(got)]).max())


def test_the_family_takes_the_hybrid_slot_on_the_normal_path(params):
    assert slot_kind(CFG) is HYBRID_KIND
    eng = _engine(params)
    assert set(eng.cache) == {"k", "v", "s", "c"}
    assert eng.cache["k"].shape == (2, 7, 256, 1, 16)  # two `*` blocks
    assert eng.cache["s"].shape == (10, 7, 16, 128)  # ten `S` blocks
    assert eng.cache["c"].shape == (10, 7, 3, 128)
    assert eng.cache["s"].dtype == jnp.float32
    assert eng._state_bytes == STATE_BYTES
    assert eng._kv_token_bytes == KV_TOKEN_BYTES
    assert eng.decode_window and eng.n_tiers == 1
    # nobody said: the kind's kernel serves this pool, and every decode
    # dispatch is the collapsed one
    assert eng.ragged_attn and eng._ragged_ok
    assert not _engine(params, ragged_attn=False)._ragged_ok
    # what ITS sequence form bounds a prefill dispatch by, not Mamba-2's
    # sixteen chunks
    assert eng._state_admit_tokens == 1 << 17  # 256 MiB / (12 B x 128)
    for k in COUNTERS:
        assert eng.stats[k] == 0


@pytest.mark.parametrize("call", ["export_request_kv", "import_request_kv"])
def test_kv_handoff_is_refused_at_the_call(params, call):
    eng = _engine(params)
    arg = _prompt(0, 40) if call == "export_request_kv" else {"tokens": []}
    with pytest.raises(ValueError, match="a hybrid Mamba stack"):
        getattr(eng, call)(arg)


def _grouped(params, **kw):
    eng = _engine(params, **kw)
    prompt = _prompt(1, 141)
    group = [_req(f"g-{i}", prompt, 9 + i, group_id="g", group_n=8)
             for i in range(8)]
    single = _req("s", _prompt(2, 21), 8)
    before = dict(eng.stats)
    eng.generate_blocking(group + [single])
    return eng, group, single, _delta(eng, before)


@pytest.fixture(scope="module")
def grouped(params):
    """A group of 8 over 6 slots, on a prompt past two chunks of the
    sequence form, plus a single prompt: six members are admitted together
    (ONE prefill of the shared span, five copies), two come late."""
    return _grouped(params)


@pytest.fixture(scope="module")
def grouped_plain(params):
    """The same requests with every state sliced out of the pool, stepped
    by `selective_step` and written back."""
    return _grouped(params, ragged_attn=False)


def test_group_fan_out_is_one_prefill_and_copies_of_the_state(grouped):
    _, group, _, d = grouped
    assert d["state_copies"] == 5
    # the state and windows whole, and the K/V columns of the copied span
    # (140 shared tokens in the bucket of 256)
    assert d["state_copy_bytes"] == 5 * (STATE_BYTES + 256 * KV_TOKEN_BYTES)
    assert d["shared_tokens"] == 5 * 140
    assert [r.cache_hit_tokens for r in group[:6]] == [0] + [140] * 5


@pytest.mark.parametrize("which", range(9))
def test_every_request_of_the_group_gives_reference_logprobs(
        grouped, params, which):
    _, group, single, _ = grouped
    assert _reference_error(params, (group + [single])[which]) < TOL


def test_the_kernel_s_streams_are_the_plain_path_s(grouped, grouped_plain):
    """Token for token, and the log-probs to float32 rounding; the plain
    path dispatches a tier at a time, the kernel the collapsed grid."""
    (_, group, single, d), (_, group_p, single_p, dp) = grouped, grouped_plain
    for r, rp in zip(group + [single], group_p + [single_p]):
        assert r.output_tokens == rp.output_tokens
        np.testing.assert_allclose(
            r.output_logprobs, rp.output_logprobs, atol=1e-5, rtol=0)
    assert d["ragged_dispatches"] == d["decode_calls"] > 0
    assert dp["ragged_dispatches"] == 0 < dp["decode_calls"]
    assert d["decode_passes"] == dp["decode_passes"]
    assert d["decode_attended_cols"] == dp["decode_attended_cols"]


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_a_decode_pass_steps_the_rows_its_path_moves(
        grouped, grouped_plain, path):
    """The kernel steps the live rows of the dispatch's snapshot and leaves
    the others where they lie; the plain path reads and rewrites the six
    slots of the one tier, live or not."""
    eng, _, _, d = grouped if path == "kernel" else grouped_plain
    whole = d["decode_passes"] * eng.n_slots
    assert 0 < d["tokens_delivered"] <= d["state_rows_stepped"] <= whole
    # the run ends on fewer requests than slots: idle rows in its last passes
    assert (d["state_rows_stepped"] == whole) == (path == "plain")


def test_groups_admitted_together_take_one_suffix_program_a_window(params):
    """Two groups of unlike prompts in ONE admission pass (a closed loop's
    clipped budgets end groups together): the suffix dispatch holds the
    short group's siblings beside the long group's representative, so the
    span its siblings share and the window of the longest row differ.  The
    copy takes the window's bucket: one program a window, whatever the mix,
    and what each request samples is the reference's."""
    eng = _engine(params)
    seen = []
    suffix = eng._suffix_prefill_fn

    def recorded(*args):
        seen.append(args[-2:])
        return suffix(*args)

    eng._suffix_prefill_fn = recorded
    short, long = _prompt(5, 21), _prompt(6, 141)
    reqs = [_req(f"a-{i}", short, 5, group_id="a", group_n=3)
            for i in range(3)]
    reqs += [_req(f"b-{i}", long, 5, group_id="b", group_n=3)
             for i in range(3)]
    eng.generate_blocking(reqs)
    assert seen and all(copy in (0, window) for copy, window in seen), seen
    # a dispatch that mixed them: siblings of 20 shared tokens copy 256
    assert (256, 256) in seen and eng.stats["state_copies"] == 4
    for r in reqs:
        assert _reference_error(params, r) < TOL


def test_a_sibling_admitted_late_prefills_its_prompt_again(grouped):
    _, group, _, d = grouped
    assert d["sibling_reprefills"] == 2
    assert [r.cache_hit_tokens for r in group[6:]] == [0, 0]


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
    assert second.cache_hit_tokens == 34
    assert _reference_error(params, second) < TOL


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
    assert _reference_error(params, other) < TOL


def test_a_stop_inside_a_chunk_leaves_the_pool_consistent(params):
    eng = _engine(params, n_slots=2)
    req = _req("m", _prompt(7, 30), 3)  # ends on the second step of a chunk
    eng.generate_blocking([req])
    assert int(eng.retained_len.max()) == 0  # the state ran past the host
    assert _reference_error(params, req) < TOL
    after = [_req(f"n{i}", _prompt(20 + i, 17 + i), 6) for i in range(3)]
    eng.generate_blocking(after)
    for r in after:
        assert _reference_error(params, r) < TOL


def test_a_live_weight_swap_keeps_the_pool(params):
    """`swap_weights_live` between two turns: the retained state, window
    and columns stay where they are, and the next turn continues from them
    under the new weights' version."""
    eng = _engine(params)
    first = _req("w1", _prompt(8, 30), 5)
    eng.generate_blocking([first])
    pool_before = jax.tree_util.tree_map(np.asarray, eng.cache)
    eng.swap_weights_live(params, version=3)
    for name, a in pool_before.items():
        np.testing.assert_array_equal(np.asarray(eng.cache[name]), a)
    turn2 = list(first.input_ids) + list(first.output_tokens) + _prompt(9, 7)
    second = _req("w2", turn2, 4)
    eng.generate_blocking([second])
    assert _reference_error(params, second) < TOL


def test_a_large_first_fill_goes_in_several_dispatches(params):
    eng = _engine(params)
    eng._state_admit_tokens = 32  # two rows of a 16-token suffix bucket
    prompt = _prompt(11, 21)
    group = [_req(f"h-{i}", prompt, 5, group_id="h", group_n=6)
             for i in range(6)]
    before = dict(eng.stats)
    eng.generate_blocking(group)
    d = _delta(eng, before)
    assert d["suffix_calls"] == 3 and d["state_copies"] == 5
    for r in group:
        assert _reference_error(params, r) < TOL


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
    np.testing.assert_allclose(r.output_logprobs, want, atol=TOL)


@pytest.mark.parametrize("option, kw", [
    ("spec_decode", {"spec_decode": True}),
    ("host_offload", {"host_offload": True}),
    ("decode_tiers", {"decode_tiers": 2}),
    ("tp=2", {"tp": 2}),
    ("ep=2", {"ep": 2}),
], ids=["spec_decode", "host_offload", "decode_tiers", "tp", "ep"])
def test_what_the_hybrid_slot_lacks_is_refused_by_name(option, kw):
    """Before any weight is drawn, in the words that hold for both
    recurrences: the family brings no capability the kind did not have."""
    from areal_tpu.gen.engine import GenEngine

    with pytest.raises(ValueError,
                       match=f"{option}.*a hybrid Mamba stack, of either"):
        GenEngine(CFG, n_slots=6, max_seq_len=128, prompt_bucket=16, **kw)
