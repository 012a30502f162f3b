"""Tiered decode: bucketed key-window attention over length-cohort slot
blocks (ISSUE 5).  Decode must pay for the occupied span, not the
`max_seq_len` ceiling — while producing BIT-IDENTICAL token streams to the
untiered/unwindowed path at a fixed seed (counter-keyed sampling makes the
streams partition-invariant).  Covers: greedy + sampled parity across tier
layouts, window-on vs window-off parity, a mid-generation tier migration,
a group fan-out sibling landing in a tier, the compile-signature soak
(steady state stays on the K/tier bucket ladder), device-resident decode
state (no per-chunk re-uploads), admission cohort placement, and the
attended-fraction accounting."""

import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest, plan_decode_tiers
from areal_tpu.models import forward, init_params
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


def _greedy_reference(cfg, params, prompt, n_new):
    seq = list(prompt)
    out = []
    for _ in range(n_new):
        L = len(seq)
        ids = np.asarray(seq, np.int32)[None]
        pos = np.arange(L, dtype=np.int32)[None]
        seg = np.zeros((1, L), np.int32)
        logits = np.asarray(forward(params, cfg, ids, pos, seg))[0, -1]
        tok = int(np.argmax(logits))
        out.append(tok)
        seq.append(tok)
    return out


def _run(eng, reqs):
    eng.generate_blocking(reqs)
    return [(tuple(r.output_tokens), r.stop_reason) for r in reqs]


def _signature_budget(name):
    """Reference entry from the checked-in C6 signature budget (ISSUE 9)."""
    import json
    import os

    from areal_tpu.analysis.jit_signatures import BUDGET_PATH

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, BUDGET_PATH)) as f:
        return json.load(f)["reference_configs"][name]


def _mixed_reqs(cfg, rng, temperature):
    return [
        GenRequest(rid=f"r{i}", input_ids=rng.integers(0, 97, n).tolist(),
                   max_new_tokens=m, temperature=temperature, top_p=tp)
        for i, (n, m, tp) in enumerate(
            [(10, 6, 1.0), (24, 30, 0.9), (7, 12, 1.0), (40, 9, 1.0)]
        )
    ]


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_tiered_matches_untiered(setup, temperature):
    """The same mixed-length workload through 1, 2, and 4 tiers (and an
    explicit uneven layout) yields identical per-request token streams —
    the ISSUE 5 bit-parity contract at fixed seed, greedy AND sampled."""
    cfg, params = setup
    layouts = [
        dict(decode_tiers=1),
        dict(decode_tiers=2),
        dict(decode_tiers=3),
        dict(decode_tier_lens=[64, 256], decode_tier_slots=[3, 1]),
    ]
    outs = []
    for kw in layouts:
        rng = np.random.default_rng(11)
        eng = _engine(cfg, params, **kw)
        outs.append(_run(eng, _mixed_reqs(cfg, rng, temperature)))
    for got in outs[1:]:
        assert got == outs[0]


def test_windowed_matches_full_width(setup):
    """decode_window=True (bucketed K) vs decode_window=False (legacy
    full-M attention): identical token streams — the masked columns beyond
    the window contribute exactly zero."""
    cfg, params = setup
    outs = []
    for window in (True, False):
        rng = np.random.default_rng(12)
        eng = _engine(cfg, params, decode_window=window)
        outs.append(_run(eng, _mixed_reqs(cfg, rng, 1.0)))
    assert outs[0] == outs[1]
    # and the windowed engine really attended less than the ceiling
    eng = _engine(cfg, params)
    _run(eng, [GenRequest(rid="w", input_ids=list(range(1, 9)),
                          max_new_tokens=8, temperature=0.0)])
    assert eng.decode_attended_fraction() < 0.5


def test_greedy_group_fanout_sibling_lands_in_tier(setup):
    """A GRPO group fanned out across a length-cohort tier still emits the
    solo greedy rollout per sibling, with the cluster prefix shared (one
    fresh prefill + one copy), tiering composing with ISSUE 2."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 97, 24).tolist()
    ref = _greedy_reference(cfg, params, prompt, 6)
    eng = _engine(cfg, params, decode_tiers=2)
    reqs = [
        GenRequest(rid=f"G-{i}", input_ids=list(prompt), max_new_tokens=6,
                   temperature=0.0, group_id="G", group_n=4)
        for i in range(4)
    ]
    eng.generate_blocking(reqs)
    for r in reqs:
        assert r.output_tokens == ref, r.rid
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["copy_calls"] == 1
    assert eng.stats["shared_tokens"] == 3 * (len(prompt) - 1)


def test_mid_generation_tier_migration_parity(setup):
    """A long-budget request forced into the short cohort (its tier full)
    migrates mid-generation once a roomier slot frees — device-side
    cache-row copy — and its token stream still matches the untiered
    engine's bit for bit."""
    cfg, params = setup

    def reqs_for(rng):
        # two short-lived long-budget requests claim the long tier; the
        # third (also long-budget) must take a short-tier slot and later
        # outgrow the 64-token cohort ceiling
        blockers = [
            GenRequest(rid=f"b{i}",
                       input_ids=rng.integers(0, 97, 30).tolist(),
                       max_new_tokens=40, temperature=1.0)
            for i in range(2)
        ]
        mover = GenRequest(rid="mover",
                           input_ids=rng.integers(0, 97, 40).tolist(),
                           max_new_tokens=60, temperature=1.0)
        return blockers + [mover]

    tiered = _engine(cfg, params, decode_tier_lens=[64, 256],
                     decode_tier_slots=[2, 2], decode_chunk=4)
    rng = np.random.default_rng(21)
    t_reqs = reqs_for(rng)
    t_out = _run(tiered, t_reqs)
    assert tiered.stats["tier_migrations"] >= 1, tiered.stats

    untiered = _engine(cfg, params, decode_tiers=1, decode_chunk=4)
    rng = np.random.default_rng(21)
    u_out = _run(untiered, reqs_for(rng))
    assert t_out == u_out


def test_compile_signature_soak_stays_on_ladder(setup):
    """Steady-state mixed-length traffic mints ZERO new decode programs
    once the K/tier bucket ladder is warm — the jit-cache-counting pin for
    the ISSUE 5 shape discipline."""
    cfg, params = setup
    eng = _engine(cfg, params, decode_tiers=2, decode_chunk=4)
    rng = np.random.default_rng(31)

    def wave(tag):
        reqs = [
            GenRequest(rid=f"{tag}{i}",
                       input_ids=rng.integers(0, 97, n).tolist(),
                       max_new_tokens=m, temperature=1.0)
            for i, (n, m) in enumerate(
                [(8, 10), (20, 25), (40, 40), (60, 30)]
            )
        ]
        eng.generate_blocking(reqs)

    # two warm rounds: the second covers re-admission over post-decode
    # cache buffers (their sharding signature differs from the cold
    # device_put the very first prefill saw)
    wave("warm0")
    wave("warm1")
    sizes = {
        "decode": eng._decode_fn._cache_size(),
        "prefill": eng._prefill_fn._cache_size(),
    }
    for w in range(3):
        wave(f"soak{w}")
    assert eng._decode_fn._cache_size() == sizes["decode"]
    assert eng._prefill_fn._cache_size() == sizes["prefill"]

    # ISSUE 9: the checked-in signature budget is the authoritative
    # ceiling for this reference config — observed program counts must
    # stay within it, and the config must match what the budget assumed
    # (regenerate with `python scripts/lint.py --write-budget`).
    ref = _signature_budget("tiered_decode_soak")
    assert ref["config"] == {"n_slots": 4, "max_seq_len": 256,
                             "prompt_bucket": 16, "decode_tiers": 2}
    assert eng._decode_fn._cache_size() <= ref["budgets"]["decode"]
    assert eng._prefill_fn._cache_size() <= ref["budgets"]["prefill"]


def test_device_resident_state_between_chunks(setup):
    """Steady-state decode chains device arrays chunk to chunk: the host
    re-uploads state only when admission/free/migration dirties it, never
    per dispatch (the C2 host-upload discipline, runtime-verified)."""
    cfg, params = setup
    eng = _engine(cfg, params, n_slots=2, decode_chunk=4)
    req = GenRequest(rid="long", input_ids=list(range(1, 9)),
                     max_new_tokens=64, temperature=1.0)
    eng.generate_blocking([req])
    assert eng.stats["decode_calls"] >= 10
    # one sync after admission; the free at the end dirties but is never
    # re-uploaded (no further decode) — steady chunks upload nothing
    assert eng.stats["state_syncs"] <= 2, eng.stats


def test_admission_places_by_length_cohort(setup):
    """Budget-based placement: short-budget requests land in the short
    cohort, long-budget in the long one (occupancy observed mid-flight)."""
    cfg, params = setup
    eng = _engine(cfg, params, decode_tier_lens=[64, 256],
                  decode_tier_slots=[2, 2])
    short = [
        GenRequest(rid=f"s{i}", input_ids=list(range(1, 11)),
                   max_new_tokens=8, temperature=1.0)
        for i in range(2)
    ]
    long_ = [
        GenRequest(rid=f"l{i}", input_ids=list(range(1, 41)),
                   max_new_tokens=120, temperature=1.0)
        for i in range(2)
    ]
    for r in short + long_:
        eng.submit(r)
    eng._admit()  # placement observed before decode can finish anything
    assert eng.tier_occupancy() == [2, 2]
    # short cohort slots are exactly the first block
    assert all(
        eng.slot_req[s] is not None and eng.slot_req[s].rid.startswith("s")
        for s in range(2)
    )
    eng.generate_blocking(short + long_)


def test_plan_decode_tiers_layouts():
    lens, slots = plan_decode_tiers(64, 16384, 3, 128)
    assert lens == [4096, 8192, 16384]
    assert slots == [32, 16, 16]
    assert sum(slots) == 64
    lens, slots = plan_decode_tiers(8, 2048, 1, 128)
    assert (lens, slots) == ([2048], [8])
    with pytest.raises(ValueError):
        plan_decode_tiers(2, 2048, 4, 128)


def test_tier_layout_validation(setup):
    cfg, params = setup
    with pytest.raises(ValueError):
        _engine(cfg, params, decode_tier_lens=[64, 256],
                decode_tier_slots=[2, 3])  # sums to 5 != 4
    with pytest.raises(ValueError):
        _engine(cfg, params, decode_tier_lens=[256, 64],
                decode_tier_slots=[2, 2])  # ceilings must ascend
    with pytest.raises(ValueError):
        _engine(cfg, params, decode_tier_lens=[64, 256])  # lens without slots
