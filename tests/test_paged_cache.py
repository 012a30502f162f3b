"""Paged KV cache + host-DRAM overflow tier (ISSUE 16) — engine-level
parity and behavior tests.

The contract under test: routing every prefix mechanism through the
radix/paged pool — including spilling retained prefixes to host DRAM and
swapping them back on a hit — changes NOTHING about the emitted token
streams.  Tokens AND logprobs must be bit-identical to an engine that
never evicts, across greedy and sampled decoding, including a host-swap
round trip of a mid-generation (interrupted) prefix.
"""

import os

import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from areal_tpu.models import forward, init_params
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


def _greedy_reference(cfg, params, prompt, n_new):
    seq = list(prompt)
    out = []
    for _ in range(n_new):
        ids = np.asarray(seq, np.int32)[None]
        pos = np.arange(len(seq), dtype=np.int32)[None]
        seg = np.zeros((1, len(seq)), np.int32)
        logits = np.asarray(forward(params, cfg, ids, pos, seg))[0, -1]
        tok = int(np.argmax(logits))
        out.append(tok)
        seq.append(tok)
    return out


def _run_workload(eng, reqs):
    """Submit request batches sequentially; returns the finished requests."""
    done = []
    for batch in reqs:
        rs = [
            GenRequest(rid=r["rid"], input_ids=list(r["ids"]),
                       max_new_tokens=r["n"],
                       temperature=r.get("temp", 0.0))
            for r in batch
        ]
        eng.generate_blocking(rs)
        done.extend(rs)
    return done


def _fillers(rng, count, n=4, length=20):
    return [
        {"rid": f"fill-{i}", "ids": rng.integers(0, 97, length).tolist(),
         "n": n}
        for i in range(count)
    ]


def test_host_swap_round_trip_is_bit_identical(setup):
    """A retained prefix forced through host DRAM (spill on slot pressure,
    swap back on a radix hit) must leave the multi-turn continuation
    bit-identical — tokens and logprobs — to an engine with enough slots
    to keep it device-resident."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    turn1 = rng.integers(0, 97, 24).tolist()
    fills = _fillers(np.random.default_rng(22), 2)

    def workload(transcript_holder):
        # [turn1] -> [2 fillers overwrite both slots] -> [turn2]
        yield [{"rid": "t1", "ids": turn1, "n": 6}]
        yield fills
        yield [{"rid": "t2", "ids": transcript_holder[0], "n": 6}]

    # reference: 4 slots, no host tier — turn1's prefix stays on device
    ref_eng = _engine(cfg, params, n_slots=4)
    r1 = GenRequest(rid="t1", input_ids=list(turn1), max_new_tokens=6,
                    temperature=0.0)
    ref_eng.generate_blocking([r1])
    transcript = turn1 + r1.output_tokens + rng.integers(0, 97, 4).tolist()
    ref_done = _run_workload(ref_eng, [fills,
                                       [{"rid": "t2", "ids": transcript,
                                         "n": 6}]])
    ref_t2 = ref_done[-1]
    assert ref_eng.stats["prefix_cache_host_swaps"] == 0

    # paged: 2 slots + host tier — the fillers evict turn1's prefix to
    # host DRAM; turn2's radix hit swaps it back in
    eng = _engine(cfg, params, n_slots=2, host_offload=True,
                  host_cache_mb=8, host_min_tokens=8)
    h1 = GenRequest(rid="t1", input_ids=list(turn1), max_new_tokens=6,
                    temperature=0.0)
    eng.generate_blocking([h1])
    assert h1.output_tokens == r1.output_tokens
    done = _run_workload(eng, [fills, [{"rid": "t2", "ids": transcript,
                                        "n": 6}]])
    t2 = done[-1]

    assert eng.stats["prefix_cache_host_swaps"] >= 2  # spill + swap-in
    assert eng.stats["suffix_calls"] >= 1  # warm start, not a cold prefill
    assert t2.output_tokens == ref_t2.output_tokens
    assert t2.output_logprobs == ref_t2.output_logprobs
    assert t2.cache_hit_tokens >= len(turn1)
    eng.pool.check_page_table()


def test_host_swap_mid_generation_interrupt_resume(setup):
    """The acceptance case: an INTERRUPTED generation's accumulated prefix
    survives a full spill/swap-in round trip and resumes to exactly the
    uninterrupted greedy rollout."""
    cfg, params = setup
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 97, 16).tolist()
    eng = _engine(cfg, params, n_slots=2, host_offload=True,
                  host_cache_mb=8, host_min_tokens=8)

    r1 = GenRequest(rid="i", input_ids=list(prompt), max_new_tokens=10,
                    temperature=0.0)
    eng.submit(r1)
    while len(r1.output_tokens) < 3:
        eng.step(chunk=2)
    eng.abort_all("abort")  # mid-generation: prefix retained in-slot
    got = len(r1.output_tokens)
    assert got >= 3 and r1.stop_reason == "abort"

    # slot pressure pushes the interrupted prefix through host DRAM
    _run_workload(eng, [_fillers(np.random.default_rng(24), 2)])
    assert eng.stats["prefix_cache_host_swaps"] >= 1

    resumed = GenRequest(rid="i", input_ids=prompt + r1.output_tokens,
                         max_new_tokens=10 - got, temperature=0.0)
    eng.generate_blocking([resumed])
    assert eng.stats["prefix_cache_host_swaps"] >= 2  # ...and back in
    ref = _greedy_reference(cfg, params, prompt, 10)
    assert r1.output_tokens + resumed.output_tokens == ref


def test_sampled_streams_invariant_to_host_tier(setup):
    """Counter-keyed sampling: the SAME workload, sampled at temperature
    1.0, must emit identical streams whether prefixes ride device
    residency or a host round trip — stream keys depend on (stream_id,
    position), never on cache placement."""
    cfg, params = setup
    rng = np.random.default_rng(25)
    turn1 = rng.integers(0, 97, 24).tolist()
    fills = _fillers(np.random.default_rng(26), 2)

    outs = []
    for kw in (
        dict(n_slots=4),
        dict(n_slots=2, host_offload=True, host_cache_mb=8,
             host_min_tokens=8),
    ):
        eng = _engine(cfg, params, **kw)
        r1 = GenRequest(rid="s1", input_ids=list(turn1), max_new_tokens=6,
                        temperature=1.0, top_p=0.9)
        eng.generate_blocking([r1])
        transcript = turn1 + r1.output_tokens
        done = _run_workload(eng, [fills, [{"rid": "s2",
                                            "ids": transcript, "n": 6,
                                            "temp": 1.0}]])
        outs.append((r1, done[-1], eng))
    (a1, a2, ref_eng), (b1, b2, host_eng) = outs
    assert host_eng.stats["prefix_cache_host_swaps"] >= 2
    assert ref_eng.stats["prefix_cache_host_swaps"] == 0
    assert a1.output_tokens == b1.output_tokens
    assert a2.output_tokens == b2.output_tokens
    assert a2.output_logprobs == b2.output_logprobs


def test_host_swap_mints_no_new_decode_programs(setup):
    """Static-shape discipline: spill/swap-in traffic may compile its own
    bucketed gather/scatter programs, but the decode program family must
    not grow — a swapped-in row is read through the same page table as
    any other."""
    cfg, params = setup
    rng = np.random.default_rng(27)
    eng = _engine(cfg, params, n_slots=2, host_offload=True,
                  host_cache_mb=8, host_min_tokens=8)
    # init compiled the whole gather ladder; counted as growth from here,
    # because jax.jit keeps ONE cache per wrapped function and options, so
    # the absolute count holds every engine this process has built
    g0 = eng._host_gather_fn._cache_size()
    warm = rng.integers(0, 97, 24).tolist()
    # n=12 walks the decode frontier across the 32- AND 64-column key
    # windows, then ONE full evict/swap-in cycle warms the swap-in aval
    # family (scatter-output cache) — the same one-time warmup the tiered
    # soaks grant cold device_put arrays.  Steady state starts here.
    _run_workload(eng, [[{"rid": "w", "ids": warm, "n": 12}]])
    _run_workload(eng, [_fillers(np.random.default_rng(30), 2)])
    _run_workload(eng, [[{"rid": "w0", "ids": warm + [1, 2, 3], "n": 4}]])
    assert eng.stats["prefix_cache_host_swaps"] >= 2
    baseline = eng._decode_fn._cache_size()
    for i in range(1, 4):  # repeated evict/swap-in churn
        _run_workload(eng, [_fillers(np.random.default_rng(30 + i), 2)])
        _run_workload(
            eng, [[{"rid": f"w{i}", "ids": warm + [1, 2, 3], "n": 4}]]
        )
    assert eng.stats["prefix_cache_host_swaps"] >= 6
    assert eng._decode_fn._cache_size() == baseline
    # ...and the whole family stays within the C6 decode budget
    # (tiers * ladder(16, 128) = 4 programs at this config)
    assert eng._decode_fn._cache_size() <= 4
    # the host transfer programs themselves stay on the bucket ladder
    # that init warmed
    assert eng._host_gather_fn._cache_size() == g0


def test_cold_start_swap_in_mints_nothing(setup):
    """ISSUE 17 satellite (the PR 16 cold-start caveat): engine init now
    pre-compiles the whole gather/scatter bucket ladder AND leaves the
    cache scatter-produced (out_shardings pins its aval), so the soak
    starts COLD — no warm evict/swap-in round granted — and the first
    real spill/swap-in/handoff cycle must mint zero programs anywhere."""
    cfg, params = setup
    rng = np.random.default_rng(33)
    eng = _engine(cfg, params, n_slots=2, host_offload=True,
                  host_cache_mb=8, host_min_tokens=8)
    # the gather/scatter jit caches are shared by every engine of the
    # process (one per wrapped function and options): empty them and warm
    # again, so the count is this engine's ladder whatever ran before
    eng._host_gather_fn.clear_cache()
    eng._host_scatter_fn.clear_cache()
    eng._warmup_host_tier()
    g0 = eng._host_gather_fn._cache_size()
    s0 = eng._host_scatter_fn._cache_size()
    assert g0 == s0 == len({16, 32, 64, 128})  # full ladder, compiled cold
    warm = rng.integers(0, 97, 24).tolist()
    _run_workload(eng, [[{"rid": "w", "ids": warm, "n": 12}]])
    assert eng.stats["prefix_cache_host_swaps"] == 0  # still cold
    baseline = eng._decode_fn._cache_size()
    for i in range(3):  # evict/swap-in churn starts HERE, from cold
        _run_workload(eng, [_fillers(np.random.default_rng(34 + i), 2)])
        _run_workload(
            eng, [[{"rid": f"w{i}", "ids": warm + [1, 2, 3], "n": 4}]]
        )
    assert eng.stats["prefix_cache_host_swaps"] >= 4
    assert eng._decode_fn._cache_size() == baseline
    assert eng._host_gather_fn._cache_size() == g0
    assert eng._host_scatter_fn._cache_size() == s0


def test_prefix_cache_stats_accounting(setup):
    """hits/misses/evictions line up with the admission composition, and
    the hit-rate helper reflects them."""
    cfg, params = setup
    eng = _engine(cfg, params, n_slots=4)
    rng = np.random.default_rng(28)
    prompt = rng.integers(0, 97, 20).tolist()
    _run_workload(eng, [[{"rid": "a", "ids": prompt, "n": 4}]])
    assert eng.stats["prefix_cache_misses"] == 1
    assert eng.stats["prefix_cache_hits"] == 0
    assert eng.prefix_cache_hit_rate() == 0.0
    # multi-turn continuation: a device radix hit
    done = _run_workload(eng, [[{"rid": "a2",
                                 "ids": prompt + [5, 6, 7, 8, 9], "n": 4}]])
    assert eng.stats["prefix_cache_hits"] == 1
    assert eng.prefix_cache_hit_rate() == 0.5
    assert done[0].cache_hit_tokens >= len(prompt) - 1
    # an unrelated prompt overwriting a retained slot is an eviction
    before = eng.stats["prefix_cache_evictions"]
    _run_workload(eng, [_fillers(np.random.default_rng(29), 4)])
    assert eng.stats["prefix_cache_evictions"] >= before + 1


def test_migration_keeps_page_table_permutation(setup):
    """Tier migration is a page-table remap: after a tiered run with
    migrations the table must still be a permutation (no aliased or
    leaked cache rows) and retained prefixes must still match."""
    cfg, params = setup
    eng = _engine(cfg, params, n_slots=4, max_seq_len=128,
                  decode_tiers=2)
    rng = np.random.default_rng(31)
    reqs = [
        {"rid": f"m{i}", "ids": rng.integers(0, 97, 6).tolist(), "n": 40}
        for i in range(4)
    ]
    _run_workload(eng, [reqs])
    eng.pool.check_page_table()
    # at least one retained prefix is findable through the radix
    assert any(
        eng.pool.device_tokens(s) is not None for s in range(eng.n_slots)
    )
