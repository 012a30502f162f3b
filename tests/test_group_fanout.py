"""Group fan-out prefill: cross-slot KV prefix sharing for GRPO groups
(ISSUE 2).  A group of `group_size` requests over one prompt must pay ONE
prefill of the shared prefix — the representative's — with the siblings
receiving it via a device-side cache copy and suffix-prefilling only their
remainder.  Covers greedy parity, sampling independence, the token
accounting identity (shared + suffix + cold + reused == total), abort-storm
x live-publish composition, the no-regression guarantee vs unclustered
admission, steady-state compile-signature stability, and the r5 advice
fixes (reservation off-by-one, holdback abort safety, match-window cap)."""

import os
import time

import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from areal_tpu.models import forward, init_params
from areal_tpu.models.model_config import tiny_config
from tests.engine_attrs import build_engine


@pytest.fixture(scope="module", autouse=True)
def _debug_locks():
    """Abort-storm x live-publish composition runs with the runtime lock
    assertions armed (areal-lint C1 acceptance): annotation drift raises
    LockDisciplineError instead of racing silently."""
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
    base = dict(n_slots=8, max_seq_len=128, prompt_bucket=16,
                kv_dtype="float32", reuse_min_tokens=4)
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


def _group(prompt, n, gid, max_new=6, temperature=0.0, counts=None):
    reqs = []
    for i in range(n):
        r = GenRequest(rid=f"{gid}-{i}", input_ids=list(prompt),
                       max_new_tokens=max_new, temperature=temperature,
                       group_id=gid, group_n=n)
        if counts is not None:
            counts[r.rid] = 0
            r.on_done = lambda rr: counts.__setitem__(
                rr.rid, counts[rr.rid] + 1
            )
        reqs.append(r)
    return reqs


def _acct_total(eng):
    st = eng.stats
    return (st["prefill_tokens"] + st["suffix_tokens"]
            + st["reused_tokens"] + st["shared_tokens"])


def test_group_fanout_greedy_matches_solo(setup):
    """Every sibling of a greedy GRPO group emits exactly the solo greedy
    rollout, while only the representative prefills the shared prefix."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 97, 24).tolist()
    ref = _greedy_reference(cfg, params, prompt, 6)
    eng = _engine(cfg, params)
    reqs = _group(prompt, 4, "G")
    eng.generate_blocking(reqs)
    for r in reqs:
        assert r.output_tokens == ref, r.rid
    # one fresh prefill (the representative), one fan-out copy, and the
    # 3 siblings rode the shared prefix: len-1 tokens each never recomputed
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["prefill_tokens"] == len(prompt)
    assert eng.stats["copy_calls"] == 1
    assert eng.stats["shared_tokens"] == 3 * (len(prompt) - 1)
    assert _acct_total(eng) == 4 * len(prompt)


def test_group_fanout_sampling_stays_independent(setup):
    """Siblings share prefix K/V, not randomness: a stochastic group must
    still diversify (per-row categorical draws in the suffix batch)."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 97, 20).tolist()
    eng = _engine(cfg, params)
    reqs = _group(prompt, 6, "S", max_new=10, temperature=1.0)
    eng.generate_blocking(reqs)
    outs = {tuple(r.output_tokens) for r in reqs}
    assert len(outs) > 1
    assert all(np.isfinite(r.output_logprobs).all() for r in reqs)
    assert eng.stats["shared_tokens"] == 5 * (len(prompt) - 1)


def test_shared_accounting_identity_mixed_workload(setup):
    """The fast tier-1 accounting invariant: over a mixed workload (GRPO
    group + multi-turn retained reuse + distinct cold prompts), every
    admitted prompt token is counted exactly once as cold (prefill),
    suffix, retained-reused, or shared."""
    cfg, params = setup
    rng = np.random.default_rng(2)
    eng = _engine(cfg, params)
    admitted_tokens = 0

    # 1) a GRPO group
    p1 = rng.integers(0, 97, 20).tolist()
    g = _group(p1, 4, "A", max_new=4)
    eng.generate_blocking(g)
    admitted_tokens += 4 * len(p1)
    # 2) a multi-turn extension of one transcript (retained reuse)
    turn2 = p1 + g[0].output_tokens + rng.integers(0, 97, 5).tolist()
    r2 = GenRequest(rid="t2", input_ids=turn2, max_new_tokens=4,
                    temperature=0.0)
    eng.generate_blocking([r2])
    admitted_tokens += len(turn2)
    assert eng.stats["reused_tokens"] > 0  # the retained path engaged
    # 3) distinct cold prompts
    cold = [GenRequest(rid=f"c{i}",
                       input_ids=rng.integers(0, 97, 12).tolist(),
                       max_new_tokens=3, temperature=0.0) for i in range(3)]
    eng.generate_blocking(cold)
    admitted_tokens += 3 * 12
    assert _acct_total(eng) == admitted_tokens, eng.stats


def test_clustered_admission_admits_no_fewer_than_unclustered(setup):
    """Regression guard: clustering changes HOW prompts prefill, never
    whether they admit.  The same burst over share and no-share engines
    must admit the same number of requests on the first pass and complete
    identically under greedy decoding."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    p_a = rng.integers(0, 97, 18).tolist()
    p_b = rng.integers(0, 97, 14).tolist()
    singles = [rng.integers(0, 97, 10).tolist() for _ in range(2)]

    def burst():
        reqs = _group(p_a, 3, "A", max_new=4) + _group(p_b, 3, "B", max_new=4)
        reqs += [GenRequest(rid=f"s{i}", input_ids=list(p),
                            max_new_tokens=4, temperature=0.0)
                 for i, p in enumerate(singles)]
        return reqs

    admitted = {}
    outputs = {}
    for share in (True, False):
        eng = _engine(cfg, params, share_prefix=share)
        reqs = burst()
        for r in reqs:
            eng.submit(r)
        # the group hold may park a pass; give it the TTL then count
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            eng.step(chunk=1)
            if sum(r is not None for r in eng.slot_req) == len(reqs):
                break
        admitted[share] = sum(r is not None for r in eng.slot_req)
        eng.generate_blocking(reqs)  # drain
        outputs[share] = [tuple(r.output_tokens) for r in reqs]
    assert admitted[True] >= admitted[False]
    assert outputs[True] == outputs[False]


def test_group_fanout_under_abort_storm_and_live_publish(setup):
    """The composition case the tentpole must survive: a group decodes,
    a LIVE weight publish lands mid-flight (no abort — versions transition
    per token), then an abort storm hits and every sibling resubmits with
    accumulated tokens.  Siblings keep their own retained prefixes, no
    request sees a second terminal callback, and per-token output_versions
    stay monotonic."""
    import jax

    cfg, params = setup
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 97, 24).tolist()
    eng = _engine(cfg, params, n_slots=4)
    counts: dict = {}
    reqs = _group(prompt, 4, "W", max_new=24, counts=counts)
    for r in reqs:
        eng.submit(r)
    while any(len(r.output_tokens) < 4 for r in reqs):
        eng.step(chunk=2)
    # live publish: nobody dies, decoding continues under the new policy
    new_params = init_params(cfg, jax.random.PRNGKey(42))
    eng.swap_weights_live(new_params, version=1)
    assert all(not r.stop_reason for r in reqs)
    while any(len(r.output_tokens) < 8 for r in reqs):
        eng.step(chunk=2)
    # abort storm
    eng.abort_all("abort")
    assert all(r.stop_reason == "abort" for r in reqs)
    assert all(counts[r.rid] == 1 for r in reqs)
    reused_before = eng.stats["reused_tokens"]
    resubs = []
    for r in reqs:
        rr = GenRequest(rid=r.rid, input_ids=r.input_ids + r.output_tokens,
                        max_new_tokens=24 - len(r.output_tokens),
                        temperature=0.0, group_id="W", group_n=4)
        counts[("re", rr.rid)] = 0
        rr.on_done = lambda x, k=("re", rr.rid): counts.__setitem__(
            k, counts[k] + 1
        )
        resubs.append(rr)
    eng.submit_batch(resubs)
    eng.generate_blocking(resubs)
    # every sibling found ITS retained prefix (prompt + its own tokens) —
    # the storm never collapsed the group onto one reserved slot
    assert eng.stats["reused_tokens"] - reused_before >= sum(
        len(r.input_ids) for r in reqs
    )
    # exactly one terminal callback per request object
    assert all(counts[r.rid] == 1 for r in reqs)
    assert all(counts[("re", rr.rid)] == 1 for rr in resubs)
    # versions never decrease along any trajectory
    for r, rr in zip(reqs, resubs):
        versions = r.output_versions + rr.output_versions
        assert all(a <= b for a, b in zip(versions, versions[1:])), versions
        assert versions[0] == 0 and versions[-1] == 1


def test_no_new_compile_signatures_in_steady_state(setup):
    """Acceptance: shared-prefix admission must not mint XLA programs
    mid-loop.  After a warmup over the bucket ladder, further mixed-length
    group workloads add ZERO entries to the prefill / suffix-prefill jit
    caches (the fan-out copy is fused into the suffix program with
    bucketed copy lengths, so it shares the same cache)."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    eng = _engine(cfg, params, n_slots=8, max_seq_len=256)

    def run_groups(lens, sizes):
        for n, g in zip(lens, sizes):
            prompt = rng.integers(0, 97, n).tolist()
            reqs = _group(prompt, g, f"g{n}-{g}", max_new=2)
            eng.generate_blocking(reqs)

    # warmup: hit every (rows, prompt-bucket, copy-block, key-window)
    # signature the steady state will use — the ladder is log-bounded, so
    # covering it is a handful of groups (33 sits just past the 32 bucket
    # boundary: copy-block 32 but key-window 64)
    run_groups([25, 20, 60, 17, 44, 33], [5, 3, 2, 5, 3, 5])
    sizes = {
        "prefill": eng._prefill_fn._cache_size(),
        "suffix": eng._suffix_prefill_fn._cache_size(),
    }
    # steady state: different lengths and group sizes, same bucket ladder
    run_groups([33, 25, 60, 17, 44], [5, 3, 2, 5, 3])
    run_groups([19, 47, 30], [4, 2, 5])
    assert eng._prefill_fn._cache_size() == sizes["prefill"]
    assert eng._suffix_prefill_fn._cache_size() == sizes["suffix"]

    # ISSUE 9: cross-check against the checked-in C6 signature budget —
    # the static ladder proof and this runtime soak must agree
    # (regenerate with `python scripts/lint.py --write-budget`).
    import json

    from areal_tpu.analysis.jit_signatures import BUDGET_PATH

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, BUDGET_PATH)) as f:
        ref = json.load(f)["reference_configs"]["group_fanout_soak"]
    assert ref["config"] == {"n_slots": 8, "max_seq_len": 256,
                             "prompt_bucket": 16, "decode_tiers": 1}
    assert eng._prefill_fn._cache_size() <= ref["budgets"]["prefill"]
    assert (eng._suffix_prefill_fn._cache_size()
            <= ref["budgets"]["suffix_prefill"])


def test_abort_reservation_strictly_greater_threshold(setup):
    """ADVICE r5: a slot whose retained_len == reuse_min_tokens must NOT be
    reserved by abort_all (its owner's resubmission could never be the only
    claimant for the full TTL); strictly longer prefixes still reserve."""
    cfg, params = setup
    rng = np.random.default_rng(6)
    eng = _engine(cfg, params, n_slots=2, reuse_min_tokens=8,
                  abort_reserve_s=30.0)
    # slot at exactly the threshold: prompt 7 + 1 generated = lengths 8
    r1 = GenRequest(rid="eq", input_ids=rng.integers(0, 97, 7).tolist(),
                    max_new_tokens=8, temperature=0.0)
    eng.submit(r1)
    while len(r1.output_tokens) < 1:
        eng.step(chunk=1)
    s_eq = next(s for s in range(2) if eng.slot_req[s] is r1)
    assert int(eng.lengths[s_eq]) == 8
    eng.abort_all("abort")
    assert eng._reserved_until[s_eq] == 0.0  # NOT reserved at equality
    # strictly above the threshold: reserved
    r2 = GenRequest(rid="gt", input_ids=rng.integers(0, 97, 16).tolist(),
                    max_new_tokens=8, temperature=0.0)
    eng.submit(r2)
    while len(r2.output_tokens) < 2:
        eng.step(chunk=1)
    s_gt = next(s for s in range(2) if eng.slot_req[s] is r2)
    eng.abort_all("abort")
    assert eng._reserved_until[s_gt] > time.monotonic()


def test_abort_during_admit_pass_never_resurrects_holdback(setup):
    """ADVICE r5: an abort_all landing mid-_admit must not let the pass
    write drained-but-unadmitted requests back into _holdback behind their
    terminal callback — the abort generation counter finishes them with
    'abort' instead, exactly once."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    eng = _engine(cfg, params, n_slots=2)
    counts: dict = {}
    reqs = []
    for i in range(6):  # > n_slots so some must be held back
        r = GenRequest(rid=f"h{i}",
                       input_ids=rng.integers(0, 97, 10).tolist(),
                       max_new_tokens=4, temperature=0.0)
        counts[r.rid] = 0
        r.on_done = lambda rr: counts.__setitem__(rr.rid, counts[rr.rid] + 1)
        reqs.append(r)
        eng.submit(r)
    orig = eng._plan_clusters

    def aborting_plan(entries, matched):
        # fire the abort in the window between the intake swap and the
        # holdback write-back — the race the generation counter closes
        eng.abort_all("abort")
        return orig(entries, matched)

    eng._plan_clusters = aborting_plan
    eng.step()
    eng._plan_clusters = orig
    # nothing lingers in holdback unfinished, and nobody ever gets a
    # second terminal callback (guarded field: read under the lock, which
    # the armed AREAL_DEBUG_LOCKS assertions enforce even for tests)
    with eng._lock:
        assert not eng._holdback
    for r in reqs:
        assert counts[r.rid] <= 1, r.rid
        if r.stop_reason == "abort":
            assert counts[r.rid] == 1
    # the engine still serves cleanly afterwards
    fresh = GenRequest(rid="after", input_ids=rng.integers(0, 97, 8).tolist(),
                       max_new_tokens=3, temperature=0.0)
    eng.generate_blocking([fresh])
    assert fresh.stop_reason == "length"


def test_group_hold_admits_partial_group_after_ttl(setup):
    """A declared group missing members is parked only for group_hold_s;
    the partial group then admits (a finished sibling never resubmits)."""
    cfg, params = setup
    rng = np.random.default_rng(8)
    eng = _engine(cfg, params, group_hold_s=0.15)
    prompt = rng.integers(0, 97, 16).tolist()
    partial = _group(prompt, 4, "P", max_new=3)[:2]  # 2 of a declared 4
    for r in partial:
        eng.submit(r)
    eng.step()
    assert all(r is None for r in eng.slot_req[: eng.n_slots])  # held
    # generous: under six loaded workers the first programs compile slowly
    deadline = time.monotonic() + 60
    while any(not r.stop_reason for r in partial):
        eng.step()
        assert time.monotonic() < deadline
    # the two that did arrive still clustered with each other
    assert eng.stats["shared_tokens"] == len(prompt) - 1


def test_strict_reload_zeroes_shared_prefixes_like_retained(setup):
    """retain_kv_on_reload=False: after a live publish, neither retained
    nor fan-out-shared prefixes may seed reuse, and kv_version reflects
    that no pre-swap KV survives."""
    import jax

    cfg, params = setup
    rng = np.random.default_rng(9)
    eng = _engine(cfg, params, retain_kv_on_reload=False)
    prompt = rng.integers(0, 97, 20).tolist()
    reqs = _group(prompt, 4, "Z", max_new=3)
    eng.generate_blocking(reqs)
    assert eng.stats["shared_tokens"] > 0
    assert eng.retained_len.max() > 0
    eng.swap_weights_live(init_params(cfg, jax.random.PRNGKey(11)), version=1)
    assert eng.retained_len.max() == 0
    assert (eng.kv_version == 1).all()
    # an identical prompt now pays a fresh representative prefill (no
    # suffix against pre-swap KV) — only in-group sharing, under the new
    # policy, remains
    suffix_before = eng.stats["reused_tokens"]
    reqs2 = _group(prompt, 2, "Z2", max_new=3)
    eng.generate_blocking(reqs2)
    assert eng.stats["reused_tokens"] == suffix_before
    assert (eng.kv_version == 1).all()


def test_match_window_caps_lcp_scan(setup):
    """The global lcp scan is bounded by match_window, not the (larger)
    drain window — requests beyond the cap still admit, just without the
    retained-prefix match."""
    cfg, params = setup
    rng = np.random.default_rng(10)
    eng = _engine(cfg, params, n_slots=4, match_window=2,
                  admission_window=16)
    # seed a retained prefix
    p = rng.integers(0, 97, 16).tolist()
    r0 = GenRequest(rid="seed", input_ids=p, max_new_tokens=2,
                    temperature=0.0)
    eng.generate_blocking([r0])
    assert eng.retained_len.max() > 0
    # a burst where the retained-matching candidate sits BEYOND the cap
    others = [GenRequest(rid=f"o{i}",
                         input_ids=rng.integers(0, 97, 8).tolist(),
                         max_new_tokens=2, temperature=0.0)
              for i in range(2)]
    resume = GenRequest(rid="seed", input_ids=p + r0.output_tokens,
                        max_new_tokens=2, temperature=0.0)
    for r in others + [resume]:
        eng.submit(r)
    eng.generate_blocking(others + [resume])
    # all complete regardless of whether the match was scanned
    assert all(r.stop_reason for r in others + [resume])
