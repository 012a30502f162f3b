"""Self-speculative decoding (ISSUE 12): prompt-lookup drafts verified in
one batched dispatch per tier, with BIT-IDENTICAL output streams to plain
decode — greedy AND sampled, tiered AND untiered, across mid-generation
tier migration, group fan-out, interrupt/resume, and a live weight publish.

The exactness contract: verification samples every draft position under the
same position-keyed PRNG plain decode would use, and the first mismatching
position's sample IS the non-speculative token — so speculation only changes
how many dispatches the stream costs, never its contents.  Also covers the
drafter/controller units, the rejected-draft KV-zeroing invariant, and the
(tier, K, D) compile-signature soak against the checked-in C6 budget."""

import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from areal_tpu.gen.spec import SpecController, propose_draft
from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config
from tests.engine_attrs import build_engine


@pytest.fixture(scope="module")
def setup():
    import jax

    cfg = tiny_config(vocab_size=97, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    base = dict(n_slots=4, max_seq_len=256, prompt_bucket=16,
                kv_dtype="float32", reuse_min_tokens=4, seed=3)
    base.update(kw)
    return build_engine(cfg, params, **base)


def _run(eng, reqs):
    eng.generate_blocking(reqs)
    return [(tuple(r.output_tokens), tuple(r.output_logprobs), r.stop_reason)
            for r in reqs]


def _rep_prompt(rng, seg_len, total):
    """Repetitive prompt: a random segment tiled — prompt lookup hits."""
    seg = rng.integers(0, 97, seg_len).tolist()
    return (seg * (total // seg_len + 1))[:total]


def _rep_reqs(rng, temperature):
    """Mixed lengths/budgets over repetitive prompts (drafts get proposed
    AND sometimes accepted), plus one non-repetitive request (drafts rare:
    the D=0 fall-through to the plain decode program stays exercised)."""
    specs = [(4, 12, 10, 1.0), (6, 24, 30, 0.9), (3, 9, 12, 1.0)]
    reqs = [
        GenRequest(rid=f"r{i}", input_ids=_rep_prompt(rng, s, n),
                   max_new_tokens=m, temperature=temperature, top_p=tp)
        for i, (s, n, m, tp) in enumerate(specs)
    ]
    reqs.append(GenRequest(rid="r3", input_ids=rng.integers(0, 97, 40).tolist(),
                           max_new_tokens=9, temperature=temperature))
    return reqs


# ---------------------------------------------------------------------------
# drafter + controller units
# ---------------------------------------------------------------------------


def test_propose_draft_rightmost_longest_ngram():
    h = [1, 2, 3, 9, 1, 2, 3, 5, 1, 2, 3]
    # longest suffix n-gram with an earlier occurrence is [1,2,3]; the
    # RIGHTMOST prior occurrence starts at 4, so the draft continues from 7
    d = propose_draft(np.array(h), 4)
    assert d.tolist() == [5, 1, 2, 3]
    # deterministic
    assert propose_draft(np.array(h), 4).tolist() == d.tolist()
    # max_draft truncates
    assert propose_draft(np.array(h), 2).tolist() == [5, 1]
    # on a short cycle, the overall-rightmost match cannot fill the draft;
    # the drafter steps back to the rightmost occurrence that can
    cyc = [1, 2, 3] * 4
    assert propose_draft(np.array(cyc), 6).tolist() == [1, 2, 3, 1, 2, 3]


def test_propose_draft_falls_back_to_shorter_ngrams():
    # trigram suffix [4,2,5] never recurs; bigram [2,5] doesn't either;
    # unigram [5] does (index 1) -> draft continues with what followed it
    h = [9, 5, 7, 4, 2, 5]
    assert propose_draft(np.array(h), 3).tolist() == [7, 4, 2]


def test_propose_draft_empty_and_degenerate():
    assert propose_draft(np.array([], np.int32), 4).size == 0
    assert propose_draft(np.array([7]), 4).size == 0  # nothing precedes
    assert propose_draft(np.array([1, 2, 3, 4, 5]), 4).size == 0  # no repeat
    assert propose_draft(np.array([7, 7, 7]), 0).size == 0  # D=0 pinned
    assert propose_draft(np.array([7, 7]), 3).tolist() == [7]


def test_spec_controller_ladder_selection():
    c = SpecController(ladder=(0, 3, 7), probe_every=4)
    assert c.draft_len(0) == 7  # optimistic start, no signal yet
    for _ in range(8):
        c.record(0, 7, 6)  # high acceptance
    assert c.draft_len(0) == 7
    assert c.acceptance_rate(0) == pytest.approx(6 / 7)

    mid = SpecController(ladder=(0, 3, 7), probe_every=4)
    for _ in range(8):
        mid.record(0, 7, 2)  # 0.2 <= rate < 0.5 -> bottom nonzero rung
    assert mid.draft_len(0) == 3

    cold = SpecController(ladder=(0, 3, 7), probe_every=4)
    for _ in range(8):
        cold.record(0, 7, 0)
    picks = [cold.draft_len(0) for _ in range(8)]
    assert 0 in picks  # parked on plain decode...
    assert 3 in picks  # ...but probes at the cadence so it can re-climb
    assert cold.acceptance_rate(0) == 0.0
    # per-tier isolation: tier 1 has no history, stays optimistic
    assert cold.draft_len(1) == 7


def test_spec_controller_validates_ladder():
    with pytest.raises(ValueError):
        SpecController(ladder=(0,))
    with pytest.raises(ValueError):
        SpecController(ladder=(-1, 3))


# ---------------------------------------------------------------------------
# bit-identical stream parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 1.0])
@pytest.mark.parametrize("layout", [dict(decode_tiers=1),
                                    dict(decode_tiers=2)])
def test_spec_on_matches_spec_off(setup, temperature, layout):
    """The core ISSUE 12 contract: the same workload with speculation on
    yields the token streams AND logprobs of the spec-off engine, bit for
    bit, greedy and sampled, untiered and tiered."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    plain = _run(_engine(cfg, params, **layout), _rep_reqs(rng, temperature))
    rng = np.random.default_rng(11)
    eng = _engine(cfg, params, spec_decode=True, **layout)
    spec = _run(eng, _rep_reqs(rng, temperature))
    assert spec == plain
    # speculation actually ran: drafts were proposed and verified
    assert eng.stats["verify_calls"] > 0
    assert eng.stats["spec_drafted"] > 0


def _cyclic_params(params):
    """Zeroing the attention output projection makes greedy next-token a
    pure function of the current token: every stream settles into a short
    cycle the prompt-lookup drafter locks onto (guaranteed drafts AND
    acceptances, weight-value-independent engine cost)."""
    import jax.numpy as jnp

    cyc = dict(params)
    cyc["layers"] = dict(params["layers"])
    cyc["layers"]["attn"] = dict(params["layers"]["attn"])
    cyc["layers"]["attn"]["wo"] = jnp.zeros_like(params["layers"]["attn"]["wo"])
    return cyc


def test_spec_accepts_drafts_on_cyclic_stream(setup):
    """On a cyclic greedy stream acceptance must be substantial and the
    stream must still equal the spec-off rollout."""
    cfg, params = setup
    cyc = _cyclic_params(params)

    def reqs():
        return [GenRequest(rid="cyc", input_ids=[5, 9, 13],
                           max_new_tokens=48, temperature=0.0)]

    plain = _run(_engine(cfg, cyc), reqs())
    eng = _engine(cfg, cyc, spec_decode=True)
    spec = _run(eng, reqs())
    assert spec == plain
    assert eng.stats["spec_accepted"] > 0
    rate = eng.stats["spec_accepted"] / eng.stats["spec_drafted"]
    assert rate > 0.5, eng.stats
    # accepted tokens shrink the dispatch count: 48 tokens in well under
    # 48 - accepted model calls would be ideal; at minimum the chunked
    # decode+verify call count stays below one call per token
    calls = eng.stats["decode_calls"] + eng.stats["verify_calls"]
    assert calls < 48


def test_spec_migration_parity(setup):
    """A request that migrates between length cohorts mid-generation under
    speculation still matches the spec-off untiered stream bit for bit —
    migration copies the whole retained row, never a rejected draft's KV."""
    cfg, params = setup

    def reqs_for(rng):
        blockers = [
            GenRequest(rid=f"b{i}", input_ids=_rep_prompt(rng, 7, 30),
                       max_new_tokens=40, temperature=1.0)
            for i in range(2)
        ]
        mover = GenRequest(rid="mover", input_ids=_rep_prompt(rng, 8, 40),
                           max_new_tokens=60, temperature=1.0)
        return blockers + [mover]

    tiered = _engine(cfg, params, decode_tier_lens=[64, 256],
                     decode_tier_slots=[2, 2], decode_chunk=4,
                     spec_decode=True)
    rng = np.random.default_rng(21)
    t_out = _run(tiered, reqs_for(rng))
    assert tiered.stats["tier_migrations"] >= 1, tiered.stats
    assert tiered.stats["spec_drafted"] > 0

    untiered = _engine(cfg, params, decode_tiers=1, decode_chunk=4)
    rng = np.random.default_rng(21)
    u_out = _run(untiered, reqs_for(rng))
    assert t_out == u_out


def test_spec_group_fanout_parity(setup):
    """GRPO fan-out under speculation: every sibling rides the shared
    prefix (one prefill + one copy) and emits the solo greedy stream.
    Cyclic params + a small chunk guarantee speculation genuinely runs on
    the siblings (a big first chunk would finish the budget before any
    generated token could seed a draft)."""
    cfg, params = setup
    cyc = _cyclic_params(params)
    rng = np.random.default_rng(4)
    prompt = _rep_prompt(rng, 6, 24)

    solo = _engine(cfg, cyc, decode_chunk=2)
    ref = GenRequest(rid="ref", input_ids=list(prompt), max_new_tokens=12,
                     temperature=0.0)
    solo.generate_blocking([ref])

    eng = _engine(cfg, cyc, decode_tiers=2, decode_chunk=2, spec_decode=True)
    reqs = [
        GenRequest(rid=f"G-{i}", input_ids=list(prompt), max_new_tokens=12,
                   temperature=0.0, group_id="G", group_n=4)
        for i in range(4)
    ]
    eng.generate_blocking(reqs)
    for r in reqs:
        assert r.output_tokens == ref.output_tokens, r.rid
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["copy_calls"] == 1
    assert eng.stats["spec_drafted"] > 0


def test_spec_interrupt_resume_parity(setup):
    """Interrupt (abort at a weight-publish boundary) then client resume:
    the spec engine's pre-abort tokens plus its resumed continuation equal
    the spec-off engine's under the identical cut — the suffix prefill must
    never absorb a rejected draft's KV."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    prompt = _rep_prompt(rng, 5, 20)

    spec = _engine(cfg, params, spec_decode=True, decode_chunk=2)
    r1 = GenRequest(rid="i", input_ids=list(prompt), max_new_tokens=12,
                    temperature=1.0)
    spec.submit(r1)
    while len(r1.output_tokens) < 3:
        spec.step(chunk=2)
    spec.abort_all("abort")
    cut = len(r1.output_tokens)
    assert cut > 0 and r1.stop_reason == "abort"
    r1b = GenRequest(rid="i", input_ids=prompt + r1.output_tokens,
                     max_new_tokens=12 - cut, temperature=1.0)
    spec.generate_blocking([r1b])
    assert spec.stats["suffix_calls"] >= 1  # resume reused the prefix

    plain = _engine(cfg, params, decode_chunk=1)
    r2 = GenRequest(rid="i", input_ids=list(prompt), max_new_tokens=12,
                    temperature=1.0)
    plain.submit(r2)
    while len(r2.output_tokens) < cut:  # land on the same cut, exactly
        plain.step(chunk=1)
    plain.abort_all("abort")
    assert len(r2.output_tokens) == cut
    r2b = GenRequest(rid="i", input_ids=prompt + r2.output_tokens,
                     max_new_tokens=12 - cut, temperature=1.0)
    plain.generate_blocking([r2b])

    assert r1.output_tokens + r1b.output_tokens \
        == r2.output_tokens + r2b.output_tokens
    assert r1.output_logprobs + r1b.output_logprobs \
        == r2.output_logprobs + r2b.output_logprobs


def test_spec_live_publish_parity(setup):
    """swap_weights_live mid-generation with speculation: no abort, the
    stream keeps decoding under the new policy, and tokens/logprobs/
    versions all match the spec-off engine publishing at the same token."""
    import jax

    cfg, params = setup
    new_params = init_params(cfg, jax.random.PRNGKey(123))
    rng = np.random.default_rng(17)
    prompt = _rep_prompt(rng, 6, 24)

    def run(spec_on):
        eng = _engine(cfg, params, spec_decode=spec_on,
                      decode_chunk=4 if spec_on else 1)
        r = GenRequest(rid="lp", input_ids=list(prompt), max_new_tokens=16,
                       temperature=1.0)
        eng.submit(r)
        # the spec engine publishes wherever its chunk boundary lands (it
        # may overshoot 4 tokens on an accepted draft run); the plain
        # engine then steps 1 token at a time to the identical cut
        target = 4 if spec_on else run.cut
        while len(r.output_tokens) < target:
            eng.step(chunk=eng.decode_chunk)
        if spec_on:
            run.cut = len(r.output_tokens)
        assert len(r.output_tokens) == run.cut
        eng.swap_weights_live(new_params)
        assert not r.stop_reason  # still in flight — publish aborted nothing
        while not r.stop_reason:
            eng.step(chunk=eng.decode_chunk)
        return r

    run.cut = None
    r_spec = run(True)
    r_plain = run(False)
    assert r_spec.output_tokens == r_plain.output_tokens
    assert r_spec.output_logprobs == r_plain.output_logprobs
    assert r_spec.output_versions == r_plain.output_versions
    assert set(r_spec.output_versions) == {0, 1}  # both policies contributed


# ---------------------------------------------------------------------------
# rejected-draft KV hygiene + compile-signature soak
# ---------------------------------------------------------------------------


def test_rejected_draft_kv_never_persists(setup):
    """Auditable KV hygiene: at every step boundary, cache rows at or above
    a live slot's frontier are all-zero — a rejected draft's K/V never
    outlives the verify dispatch that wrote it (it would otherwise be
    silently attended by every later chunk, retained prefix, or migration
    copy of that row).  The prompt is bucket-aligned (16 = prompt_bucket)
    so prefill writes no pad rows and the audit is exact: any nonzero row
    past the frontier can only have come from a decode/verify write."""
    cfg, params = setup
    eng = _engine(cfg, params, spec_decode=True, decode_chunk=4)
    rng = np.random.default_rng(5)
    # temperature 1.0 over a small vocab: sampled continuations repeat
    # earlier tokens often enough to trigger drafts, and those drafts are
    # then almost never what the sampler emits — exactly the rejection
    # traffic this audit needs
    req = GenRequest(rid="kv", input_ids=_rep_prompt(rng, 5, 16),
                     max_new_tokens=96, temperature=1.0)
    eng.submit(req)
    while not req.stop_reason:
        eng.step(chunk=4)
        s = next((i for i in range(eng.n_slots) if eng.slot_req[i] is req),
                 None)
        if s is None:
            continue
        frontier = int(eng.lengths[s])
        for name in ("k", "v"):
            tail = np.asarray(eng.cache[name])[:, s, frontier:]
            assert not np.any(tail), (
                f"{name}-cache rows >= frontier {frontier} are nonzero "
                f"after a verify dispatch (rejected draft KV leaked)"
            )
    # the invariant was actually exercised: some drafts were rejected
    assert eng.stats["spec_drafted"] > eng.stats["spec_accepted"]


def test_spec_compile_signature_soak(setup):
    """Steady-state spec traffic stays on the (tier, K bucket, D rung)
    program lattice: zero new decode/prefill programs after warmup and the
    verify-program count within the checked-in C6 budget for the
    spec_decode_soak reference config (ISSUE 9 discipline extended)."""
    from tests.test_tiered_decode import _signature_budget

    cfg, params = setup
    eng = _engine(cfg, params, decode_tiers=2, decode_chunk=4,
                  spec_decode=True)
    rng = np.random.default_rng(31)

    def wave(tag):
        reqs = []
        for i, (n, m) in enumerate([(8, 10), (20, 25), (40, 40), (60, 30)]):
            ids = (_rep_prompt(rng, max(2, n // 4), n) if i % 2 == 0
                   else rng.integers(0, 97, n).tolist())
            reqs.append(GenRequest(rid=f"{tag}{i}", input_ids=ids,
                                   max_new_tokens=m, temperature=1.0))
        eng.generate_blocking(reqs)

    wave("warm0")
    wave("warm1")
    sizes = {
        "decode": eng._decode_fn._cache_size(),
        "prefill": eng._prefill_fn._cache_size(),
    }
    for w in range(3):
        wave(f"soak{w}")
    # decode/prefill mint nothing new; verify may legitimately mint a
    # not-yet-seen rung (the controller adapts) but never leaves the budget
    assert eng._decode_fn._cache_size() == sizes["decode"]
    assert eng._prefill_fn._cache_size() == sizes["prefill"]
    assert eng.stats["verify_calls"] > 0

    ref = _signature_budget("spec_decode_soak")
    assert ref["config"] == {"n_slots": 4, "max_seq_len": 256,
                             "prompt_bucket": 16, "decode_tiers": 2,
                             "spec_rungs": 2}
    assert eng._verify_fn._cache_size() <= ref["budgets"]["verify"]
    assert eng._decode_fn._cache_size() <= ref["budgets"]["decode"]
    assert eng._prefill_fn._cache_size() <= ref["budgets"]["prefill"]
