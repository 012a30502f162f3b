"""`GenEngine` with latent attention (`longcat_flash`): a slot of the pool
holds one latent row a position and attention sublayer, reused, copied and
exported by column as keys and values are.  `tests/test_longcat_model.py`'s
toy config on the CPU; log-probs are compared with the benchmark's plain
float32 reference (`benchmarks/lib/reference_longcat_flash.py`)."""

import jax
import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from tests.engine_attrs import build_engine
from tests.test_longcat_model import CFG, HF, _params, ref

COUNTERS = ("expert_assignments", "identity_assignments",
            "expert_assignments_held", "experts_touched", "latent_rows_read")
# a position's share of the pool: 4 sublayers x (32 + 8) float32 values
TOKEN_BYTES = 4 * 40 * 4


@pytest.fixture(scope="module")
def params():
    return _params()


def _engine(params, **kw):
    kw = {"n_slots": 6, "max_seq_len": 128, "prompt_bucket": 16, "seed": 1,
          "decode_chunk": 4, "kv_dtype": "float32", **kw}
    return build_engine(CFG, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _req(rid, prompt, new, **kw):
    return GenRequest(rid=rid, input_ids=list(prompt), max_new_tokens=new,
                      temperature=1.0, **kw)


def _run(eng, reqs, max_steps=400):
    eng.submit_batch(reqs)
    for _ in range(max_steps):
        if all(r.stop_reason for r in reqs):
            return
        eng.step()
    raise AssertionError("requests did not finish")


def _reference_error(params, req):
    """Largest |engine log-prob - reference log-prob| over the request's
    sampled tokens."""
    seq = np.asarray([list(req.input_ids) + list(req.output_tokens)], np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.next_token_logprobs(params, HF, seq))[0]
    P = len(req.input_ids)
    got = np.asarray(req.output_logprobs)
    assert len(got) == len(req.output_tokens) == req.max_new_tokens
    return float(np.abs(got - want[P - 1: P - 1 + len(got)]).max())


def test_a_slot_holds_latent_rows(params):
    eng = _engine(params)
    assert set(eng.cache) == {"lat"}
    assert eng.cache["lat"].shape == (4, 7, 40, 128)  # positions last
    assert eng._latent and eng._columns and not eng._state
    assert (eng._kv_token_bytes, eng._state_bytes) == (TOKEN_BYTES, 0)
    # the rows are windowed as a dense model's columns; one tier; the
    # latent kernel (`ops/latent_decode.py`, interpreted here), without a
    # word
    assert eng.decode_window and eng.n_tiers == 1 and eng.ragged_attn
    # one row of max_seq_len tokens a prefill dispatch
    assert eng._state_admit_tokens == 128
    for k in COUNTERS:
        assert eng.stats[k] == 0


@pytest.fixture(scope="module")
def grouped(params):
    """Two GRPO groups of four over 37- and 21-token prompts, then a late
    sibling of the first group after its members freed their slots."""
    eng = _engine(params, n_slots=8)
    groups = []
    for g, n in enumerate((37, 21)):
        prompt = _prompt(g, n)
        groups.append([_req(f"g{g}-{i}", prompt, 6 + 3 * i, group_id=f"g{g}",
                            group_n=4) for i in range(4)])
    before = dict(eng.stats)
    _run(eng, groups[0] + groups[1])
    stats = {k: eng.stats[k] - before[k] for k in eng.stats
             if isinstance(eng.stats[k], (int, float))}
    late = _req("g0-late", groups[0][0].input_ids, 5)
    _run(eng, [late])
    return eng, groups[0] + groups[1] + [late], stats


def test_a_group_s_prompt_is_prefilled_once_and_its_columns_copied(grouped):
    eng, reqs, stats = grouped
    # one representative a group through the fresh program, the six siblings
    # through the suffix program after one fused copy
    assert stats["prefill_tokens"] == 37 + 21
    assert stats["shared_tokens"] == 3 * 36 + 3 * 20
    assert stats["copy_calls"] >= 1 and stats["sibling_reprefills"] == 0
    assert [r.cache_hit_tokens for r in reqs[:8]] == [0, 36, 36, 36, 0, 20, 20, 20]
    # the late sibling reuses a freed slot's retained rows by column
    assert reqs[8].cache_hit_tokens == 36 and eng.stats["reused_tokens"] >= 36


@pytest.mark.parametrize("which", range(9))
def test_every_request_gives_reference_logprobs(grouped, params, which):
    """Prefill, the copy of a shared prompt's rows, suffix prefill on
    retained rows, and decode through the latent cache."""
    assert _reference_error(params, grouped[1][which]) < 2e-5


def test_decode_counts_assignments_and_rows_read(grouped):
    _, _, stats = grouped
    passes = stats["decode_passes"]
    assert passes > 0
    # every live slot of a pass: 3 choices in each of 2 expert layers
    assert stats["expert_assignments"] % 6 == 0
    slot_passes = stats["expert_assignments"] // 6
    assert stats["tokens_delivered"] <= slot_passes <= 8 * passes
    assert 0 < stats["identity_assignments"] < stats["expert_assignments"]
    assert (0 < stats["expert_assignments_held"]
            <= stats["expert_assignments"] - stats["identity_assignments"])
    assert 0 < stats["experts_touched"] <= 2 * 4 * passes
    # at least the shortest prompt's rows, in 4 sublayers, for every slot-pass
    assert stats["latent_rows_read"] >= slot_passes * 4 * 21


def _serve_groups(params, **kw):
    """Two groups of three over shared prompts of 37 and 21 tokens, outputs
    of 5 to 13 tokens -> (engine, requests)."""
    eng = _engine(params, n_slots=8, **kw)
    reqs = [_req(f"k{g}-{i}", _prompt(40 + g, n), 5 + 4 * i, group_id=f"k{g}",
                 group_n=3) for g, n in enumerate((37, 21)) for i in range(3)]
    _run(eng, reqs)
    return eng, reqs


def test_the_latent_kernel_serves_what_the_copy_path_serves(params):
    """`ragged_attn=None` resolves to the latent kernel (interpreted here),
    `False` keeps the copy of the window: the same tokens, the same
    log-probs to float32 rounding, and every decode dispatch of the kernel
    path counted as a paged one."""
    kernel, got = _serve_groups(params)
    copy, want = _serve_groups(params, ragged_attn=False)
    assert kernel.ragged_attn and not copy.ragged_attn
    for g, w in zip(got, want):
        assert g.output_tokens == w.output_tokens
        np.testing.assert_allclose(
            g.output_logprobs, w.output_logprobs, atol=2e-5, rtol=0)
        assert _reference_error(params, g) < 2e-5
    ks, cs = kernel.stats, copy.stats
    assert ks["ragged_dispatches"] == ks["decode_calls"] == cs["decode_calls"] > 0
    assert cs["ragged_dispatches"] == cs["ragged_attended_pages"] == 0
    # pages of `prompt_bucket` positions, by each slot's length: fewer
    # columns than the copy path's windows by bucket
    assert 0 < ks["ragged_attended_pages"] * 16 == ks["decode_attended_cols"]
    assert ks["decode_attended_cols"] < cs["decode_attended_cols"]
    assert ks["decode_ceiling_cols"] == cs["decode_ceiling_cols"]
    # what live slots did: `experts_touched` alone also counts where the
    # rows of INACTIVE slots were routed, and those rows differ (the kernel
    # returns zeros there, the copy path attends whatever the slot holds)
    for k in COUNTERS + ("decode_passes", "tokens_delivered"):
        assert k == "experts_touched" or ks[k] == cs[k], k


def test_ragged_attn_true_is_the_same_engine_as_none(params):
    assert _engine(params, ragged_attn=True).ragged_attn


@pytest.mark.parametrize("kw,sentence", [
    # a 1-byte pool (the benchmark's float8 control) takes the copy path
    ({"kv_dtype": "float8_e4m3fn"}, "2- or 4-byte rows"),
    ({"max_seq_len": 1040}, "do not divide"),
])
def test_a_pool_the_latent_kernel_does_not_read_takes_the_copy_path(
        params, kw, sentence):
    for said in ({}, {"ragged_attn": False}):
        assert not _engine(params, **kw, **said).ragged_attn
    with pytest.raises(ValueError, match=f"ragged_attn requested.*{sentence}"):
        _engine(params, **kw, ragged_attn=True)


def test_a_backend_without_the_kernel_takes_the_copy_path(params, monkeypatch):
    """Neither a TPU nor an explicit CPU run: `None` serves through the copy
    path without a word, `True` raises with the backend's sentence."""
    from areal_tpu.ops import latent_decode

    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but the process did not ask")

    monkeypatch.setattr(latent_decode, "_interpret_mode", neither)
    assert not _engine(params).ragged_attn
    with pytest.raises(ValueError, match="ragged_attn requested.*came up on"):
        _engine(params, ragged_attn=True)


def test_the_next_turn_continues_on_the_retained_rows(params):
    eng = _engine(params)
    first = _req("t1", _prompt(5, 30), 9)
    _run(eng, [first])
    turn = list(first.input_ids) + list(first.output_tokens) + _prompt(6, 11)
    second = _req("t2", turn, 7)
    before = eng.stats["reused_tokens"]
    _run(eng, [second])
    # all of the first turn but its last sampled token is in the slot
    assert second.cache_hit_tokens == eng.stats["reused_tokens"] - before >= 30
    assert _reference_error(params, second) < 2e-5


def test_abort_and_resubmit_gives_reference_logprobs(params):
    eng = _engine(params)
    req = _req("a", _prompt(8, 26), 40)
    eng.submit_batch([req])
    for _ in range(3):
        eng.step()
    eng.abort_all("abort")
    assert req.stop_reason == "abort" and 0 < len(req.output_tokens) < 40
    again = _req("a2", list(req.input_ids) + list(req.output_tokens), 8)
    _run(eng, [again])
    assert again.cache_hit_tokens >= 16
    assert _reference_error(params, again) < 2e-5


def test_a_large_first_fill_goes_one_row_a_dispatch(params):
    """Four fresh prompts admitted in one pass go in four prefill
    dispatches of one row (`_state_admit_tokens`: one row of max_seq_len,
    whatever the prompt's bucket)."""
    eng = _engine(params, max_seq_len=64, n_slots=4)
    reqs = [_req(f"f{i}", _prompt(20 + i, 40), 4) for i in range(4)]
    _run(eng, reqs)
    assert eng.stats["prefill_calls"] == 4
    for r in reqs:
        assert _reference_error(params, r) < 2e-5


def test_siblings_go_eight_rows_a_suffix_dispatch(params):
    """Three groups of six admitted in one pass: fifteen siblings, whose
    fan-out copy gathers every row's shared span at once, go in two suffix
    dispatches of eight rows (a row weighs max_seq_len / 8; the second is
    filled up to eight with scratch rows: one program a shape), and the
    three prompts in three fresh dispatches of one row."""
    eng = _engine(params, n_slots=18, group_hold_s=0.0)
    reqs = [_req(f"s{g}-{i}", _prompt(30 + g, 21), 3, group_id=f"s{g}",
                 group_n=6) for g in range(3) for i in range(6)]
    _run(eng, reqs)
    assert eng.stats["shared_tokens"] == 15 * 20
    assert eng.stats["suffix_calls"] == 2 and eng.stats["prefill_calls"] == 3
    for r in reqs[::5]:
        assert _reference_error(params, r) < 2e-5


def test_a_retained_prefix_is_exported_by_column(params):
    eng = _engine(params)
    req = _req("e", _prompt(9, 40), 4)
    _run(eng, [req])
    entry = eng.export_request_kv(list(req.input_ids) + [7])
    assert entry is not None and entry["valid_len"] >= 39
    assert set(entry["kv"]) == {"lat"}
    block = entry["block"]
    assert entry["kv"]["lat"].shape == (4, 40, block)  # [sublayers, row, block]
    slot = next(s for s in range(eng.n_slots)
                if eng.retained_len[s] >= entry["valid_len"])
    np.testing.assert_array_equal(
        entry["kv"]["lat"], np.asarray(eng.cache["lat"][:, slot, :, :block]))
    # the host tier, which an import lands in, is refused for the kind
    assert not eng.import_request_kv(entry)


def test_a_live_swap_serves_the_new_weights(params):
    eng = _engine(params)
    _run(eng, [_req("w", _prompt(10, 24), 4)])
    other = _params(seed=3)
    eng.swap_weights_live(other, version=1)
    after = _req("w2", _prompt(11, 24), 6)  # no row of the old weights reused
    _run(eng, [after])
    assert after.output_versions == [1] * 6
    assert _reference_error(other, after) < 2e-5
