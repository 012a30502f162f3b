"""`GenEngine` with a power-retention model: a slot of the pool is one
recurrent state of fixed size, not columns of keys and values.  Tiny config
on the CPU (head_dim 16, 4 q / 2 kv heads, 2 layers, float32, seeded random
weights); log-probs are compared with the benchmark's plain float32
reference (`benchmarks/lib/reference_power_retention.py`)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.models import transformer as tf
from areal_tpu.models.model_config import TransformerConfig
from tests.engine_attrs import build_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmarks.lib import reference_power_retention as ref  # noqa: E402

HF = {
    "model_type": "brumby", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu",
}
CFG = TransformerConfig.from_hf(HF).replace(
    dtype="float32", param_dtype="float32", remat=False, retention_chunk=8,
    eos_token_id=None)
COUNTERS = ("state_copies", "state_copy_bytes", "state_reuse_dropped",
            "sibling_reprefills")


@pytest.fixture(scope="module")
def params():
    return tf.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw = {"n_slots": 6, "max_seq_len": 128, "prompt_bucket": 16, "seed": 1,
          "decode_chunk": 4, **kw}
    return build_engine(CFG, params, **kw)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 512, n).tolist()


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


def test_a_slot_is_a_state_and_the_pool_exists_once(params):
    eng = _engine(params)
    assert set(eng.cache) == {"s", "z"}
    F = 16 * 17 // 2
    assert eng.cache["s"].shape == (2, 7, 2, F, 16)  # slots + scratch row
    assert eng.cache["s"].dtype == jnp.float32
    assert not eng.decode_window and eng.n_tiers == 1
    for k in COUNTERS:
        assert eng.stats[k] == 0


@pytest.mark.parametrize("asked", ["bfloat16", "float32"])
def test_the_state_pool_is_float32_whatever_the_cache_dtype(asked):
    """`dtype` sizes key and value columns; a state sums hundreds of terms
    and is float32 always: there is no option that narrows it."""
    from areal_tpu.models.transformer import init_kv_cache

    cache = init_kv_cache(CFG, 3, 64, dtype=asked)
    assert {a.dtype for a in cache.values()} == {jnp.dtype("float32")}


@pytest.mark.parametrize("said", [None, True])
def test_ragged_attn_resolves_to_the_state_kernel(params, said):
    """A slot that holds a state and nothing else: nobody said, or `True`,
    is the kernel that steps the pool in place (`ops/retention_decode.py`,
    interpreted here)."""
    eng = _engine(params, ragged_attn=said)
    assert eng.ragged_attn and eng._ragged_ok


def test_ragged_attn_false_keeps_retention_step(params):
    eng = _engine(params, ragged_attn=False)
    assert not eng.ragged_attn and not eng._ragged_ok


def test_a_backend_without_the_kernel_keeps_retention_step(params, monkeypatch):
    """Neither a TPU nor an explicit CPU run: `None` serves through
    `retention_step` without a word, `True` raises with the backend's
    sentence."""
    from areal_tpu.ops import retention_decode

    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but the process did not ask")

    monkeypatch.setattr(retention_decode, "_interpret_mode", neither)
    assert not _engine(params).ragged_attn
    with pytest.raises(ValueError, match="ragged_attn requested.*came up on"):
        _engine(params, ragged_attn=True)


def test_a_state_beside_columns_still_refuses_ragged_attn_by_name():
    """A hybrid slot of Mamba-2 blocks (a state with a decay a head beside
    K/V columns) has no kernel: `True` is refused at construction with
    `kernel_refusal`'s sentence, and nobody's word takes the plain path."""
    from tests.test_hybrid_model import CFG as HYBRID, _params

    kw = {"n_slots": 3, "max_seq_len": 64, "prompt_bucket": 16,
          "kv_dtype": "float32"}
    hybrid = _params()
    with pytest.raises(ValueError, match="ragged_attn.*a state with a decay a head"):
        GenEngine(HYBRID, params=hybrid, ragged_attn=True, **kw)
    assert not GenEngine(HYBRID, params=hybrid, **kw).ragged_attn


def _serve_two(params, **kw):
    """One engine, two requests of different lengths in six slots (four
    stay idle), the second admitted a chunk late."""
    eng = _engine(params, **kw)
    reqs = [_req("a", _prompt(11, 23), 14), _req("b", _prompt(12, 40), 9)]
    eng.submit(reqs[0])
    eng.step()
    eng.generate_blocking([reqs[1]])
    while not all(r.stop_reason for r in reqs):
        eng.step()
    return eng, reqs


def test_the_state_kernel_serves_what_retention_step_serves(params):
    """`ragged_attn=None` against `ragged_attn=False`: the same tokens (one
    seed, one sampler), their log-probs within the benchmark cell's
    tolerance (to float32 rounding here), and every decode dispatch of the
    kernel's engine the collapsed one."""
    plain, want = _serve_two(params, ragged_attn=False)
    kernel, got = _serve_two(params)
    assert kernel.ragged_attn and not plain.ragged_attn
    for g, w in zip(got, want):
        assert g.output_tokens == w.output_tokens
        np.testing.assert_allclose(
            g.output_logprobs, w.output_logprobs, atol=2e-5, rtol=0)
        assert _reference_error(params, g) < 5e-5
    ks, ps = kernel.stats, plain.stats
    assert ks["ragged_dispatches"] == ks["decode_calls"] == ps["decode_calls"] > 0
    assert ps["ragged_dispatches"] == 0
    # a state has no pages: nothing is attended by length or windowed
    assert ks["ragged_attended_pages"] == 0
    assert ks["decode_attended_cols"] == ks["decode_ceiling_cols"]
    for k in COUNTERS + ("decode_passes", "tokens_delivered",
                         "decode_ceiling_cols", "decode_attended_cols"):
        assert ks[k] == ps[k], k


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


def test_group_fan_out_copies_a_state_and_not_a_prefix(grouped):
    eng, group, _, d = grouped
    assert d["state_copies"] == 5
    per_slot = sum(int(a.nbytes) // 7 for a in eng.cache.values())
    assert d["state_copy_bytes"] == 5 * per_slot
    assert per_slot == 2 * 2 * (16 * 17 // 2) * 17 * 4
    assert d["shared_tokens"] == 5 * 36  # the shared span, five siblings
    assert d["copy_calls"] == 0  # no column copy program for this kind
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
    # the shared span once, two late siblings and the single prompt in full
    assert d["prefill_tokens"] == 36 + 37 + 37 + 21
    assert d["suffix_tokens"] == 6  # the last prompt token of each member


def test_siblings_get_the_first_token_logits_of_separate_prefills(grouped,
                                                                  params):
    """The first sampled token of each sibling carries the log-prob the
    reference gives it after the whole prompt: the copied state is the
    representative's, to rounding."""
    _, group, _, _ = grouped
    prompt = np.asarray([group[0].input_ids], np.int32)
    x = ref.hidden_states(params, HF, prompt)[0, -1]
    logp = jax.nn.log_softmax(x @ params["lm_head"])
    for r in group:
        assert abs(float(logp[r.output_tokens[0]]) - r.output_logprobs[0]) < 2e-5


def test_the_next_turn_continues_from_the_whole_retained_state(params):
    eng = _engine(params)
    # 1 token from the prefill + one whole chunk of 4: the state ends where
    # the host's count does
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
    assert int(eng.retained_len.max()) == 34
    # shares 25 tokens of the retained 34: a state cannot be cut back
    branch = list(first.input_ids)[:25] + _prompt(6, 9)
    before = dict(eng.stats)
    other = _req("p2", branch, 3)
    eng.generate_blocking([other])
    d = _delta(eng, before)
    assert d["state_reuse_dropped"] == 1 and d["reused_tokens"] == 0
    assert d["prefill_tokens"] == len(branch) and other.cache_hit_tokens == 0
    assert _reference_error(params, other) < 5e-5


def test_a_stop_inside_a_chunk_retains_nothing(params):
    eng = _engine(params)
    req = _req("m", _prompt(7, 30), 3)  # ends on the second step of a chunk
    eng.generate_blocking([req])
    assert int(eng.retained_len.max()) == 0
    assert _reference_error(params, req) < 5e-5


def test_a_live_weight_swap_continues_from_the_kept_state(params):
    """`swap_weights_live` mid-generation: the tokens after it are sampled
    under the new weights FROM the state the old ones built, which is what
    one step of the new model on that state gives."""
    eng = _engine(params, n_slots=2)
    new = jax.tree_util.tree_map(
        lambda a: a * 1.05 if a.ndim > 2 else a, params)
    req = _req("w", _prompt(8, 19), 13)
    eng.submit(req)
    eng.step()  # prefill (1 token) + one chunk of 4
    assert len(req.output_tokens) == 5
    state_before = jax.tree_util.tree_map(np.asarray, eng.cache)
    version = eng.swap_weights_live(new)
    np.testing.assert_array_equal(np.asarray(eng.cache["s"]),
                                  state_before["s"])
    eng.generate_blocking([])
    while not req.stop_reason:
        eng.step()
    assert req.output_versions == [0] * 5 + [version] * 8
    # the fifth token goes in under the new weights, on the old state
    slot = 0
    logits, _ = tf.forward_decode(
        new, CFG, jnp.asarray([req.output_tokens[4], 0]),
        jnp.asarray([19 + 4, 0]),
        {k: jnp.asarray(v[:, :2]) for k, v in state_before.items()},
        slot_base=slot, active=jnp.asarray([True, False]))
    want = jax.nn.log_softmax(logits[0])[req.output_tokens[5]]
    assert abs(float(want) - req.output_logprobs[5]) < 2e-5
    # and before the swap the stream is the old model's
    head = _req("w-head", req.input_ids, 5)
    head.output_tokens = req.output_tokens[:5]
    head.output_logprobs = req.output_logprobs[:5]
    assert _reference_error(params, head) < 5e-5


def test_a_large_first_fill_is_one_dispatch_as_before(params):
    """Power retention's prefill is not split by padded tokens (a hybrid
    stack's is, `GenEngine._state_admit_tokens`): six whole prompts of a
    32-token bucket go in ONE fresh dispatch, and a group's five siblings
    in one suffix dispatch behind one shared-span dispatch."""
    eng = _engine(params)
    assert eng._state_admit_tokens is None
    singles = [_req(f"f-{i}", _prompt(30 + i, 20), 4) for i in range(6)]
    before = dict(eng.stats)
    eng.generate_blocking(singles)
    d = _delta(eng, before)
    assert d["prefill_calls"] == 1 and d["suffix_calls"] == 0
    group = [_req(f"g-{i}", _prompt(40, 37), 4, group_id="g", group_n=6)
             for i in range(6)]
    before = dict(eng.stats)
    eng.generate_blocking(group)
    d = _delta(eng, before)
    assert d["prefill_calls"] == 1 and d["suffix_calls"] == 1
    for r in singles + group:
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


def test_engine_logprobs_equal_the_trainer_s_recomputed_ones(grouped, params):
    """The importance ratio of the decoupled loss is exp(trainer log-prob -
    engine log-prob) for the same tokens: the trainer's packed forward
    (chunked form, segment resets) must give what the engine gave (prefill,
    state copy, decode steps)."""
    from areal_tpu.ops.functional import lm_logprobs_entropy

    _, group, single, _ = grouped
    reqs = group[:3] + [single]
    seqs = [list(r.input_ids) + list(r.output_tokens) for r in reqs]
    ids = np.concatenate(seqs).astype(np.int32)
    pos = np.concatenate([np.arange(len(s)) for s in seqs]).astype(np.int32)
    seg = np.concatenate(
        [np.full(len(s), i) for i, s in enumerate(seqs)]).astype(np.int32)
    out = tf.forward_lm(params, CFG, ids[None], pos[None], seg[None])
    logp, _, _ = lm_logprobs_entropy(out, jnp.roll(ids, -1)[None])
    logp, lo = np.asarray(logp[0]), 0
    for r, s in zip(reqs, seqs):
        P, n = len(r.input_ids), len(r.output_tokens)
        got = logp[lo + P - 1: lo + P - 1 + n]
        np.testing.assert_allclose(got, r.output_logprobs, atol=5e-5)
        lo += len(s)


def test_the_counters_are_on_the_metrics_surface():
    import json

    with open(os.path.join(REPO, "tests/data/metrics_schema.json")) as f:
        schema = json.dumps(json.load(f))
    with open(os.path.join(REPO, "docs/observability.md")) as f:
        doc = f.read()
    for k in COUNTERS:
        assert f"areal_gen_{k}_total" in schema, k
        assert f"`{k}`" in doc, k
