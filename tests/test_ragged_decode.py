"""Ragged paged-decode attention kernel (ISSUE 19) — bit-exactness and
collapse tests.

The contract under test: routing decode AND speculative verification
through the fused Pallas kernel (`ops/ragged_decode.py`, per-slot paged
KV gather + fused append + exact dense-order softmax) changes NOTHING
about the emitted streams — tokens and logprobs bit-identical to the
dense tiered path at any temperature — while the per-tier dispatch
fan-out collapses to ONE program per step.  Covers: kernel-vs-dense unit
parity (dtypes, softcap, tail page, verify tile with dropped positions),
engine-level stream parity (greedy + sampled x spec on/off), dispatch
collapse, mid-generation migration parity, a host-DRAM round trip, a
cross-engine disagg handoff,
rejected-draft KV hygiene through the kernel's fused writes, and the
compile-signature soak against the checked-in `ragged_decode` budget.

The dense references here are JITTED: XLA strength-reduces `x / const`
to `x * (1/const)` under jit (and the Pallas interpreter matches that),
so only jit-vs-jit comparison is meaningful — every engine path is
jitted anyway.
"""

import functools

import numpy as np
import pytest

from areal_tpu.gen.engine import GenRequest
from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config
from areal_tpu.ops.attention import naive_attention
from areal_tpu.ops.ragged_decode import ragged_paged_attention, ragged_supported
from tests.engine_attrs import build_engine
from tests.test_spec_decode import _rep_prompt
from tests.test_tiered_decode import _signature_budget


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


def _mixed_reqs(rng, temperature, repetitive=False):
    """Mixed lengths/budgets; repetitive prompts when spec drafting should
    actually fire (prompt-lookup n-gram hits)."""
    specs = [(10, 6, 1.0), (24, 30, 0.9), (7, 12, 1.0), (40, 9, 1.0)]
    reqs = []
    for i, (n, m, tp) in enumerate(specs):
        ids = (_rep_prompt(rng, max(2, n // 4), n) if repetitive and i % 2
               else rng.integers(0, 97, n).tolist())
        reqs.append(GenRequest(rid=f"r{i}", input_ids=ids, max_new_tokens=m,
                               temperature=temperature, top_p=tp))
    return reqs


# ---------------------------------------------------------------------------
# kernel unit parity (vs the JITTED dense set->take->attention sequence)
# ---------------------------------------------------------------------------


def _oracle(q, k_new, v_new, ck, cv, rows, widx, mask, *, K, softcap):
    """The dense path's exact op order from forward_decode/forward_verify:
    scatter-append (drop at index M), row gather, bucketed
    naive_attention."""
    import jax.numpy as jnp

    ck = ck.at[rows[:, None], widx].set(k_new.astype(ck.dtype), mode="drop")
    cv = cv.at[rows[:, None], widx].set(v_new.astype(cv.dtype), mode="drop")
    ckr = jnp.take(ck, rows, axis=0)[:, :K].astype(q.dtype)
    cvr = jnp.take(cv, rows, axis=0)[:, :K].astype(q.dtype)
    out = naive_attention(q, ckr, cvr, mask[:, None], softcap)
    return out, ck, cv


def _case(seed, *, B=4, T=1, K=32, page=16, M=64, Hq=4, Hkv=2, hd=8,
          qdtype="float32", kvdtype="float32", softcap=None):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    S = B + 1
    lengths = rng.integers(0, K - T, B).astype(np.int32)
    rows = rng.permutation(S)[:B].astype(np.int32)
    ck = rng.standard_normal((S, M, Hkv, hd)).astype(kvdtype)
    cv = rng.standard_normal((S, M, Hkv, hd)).astype(kvdtype)
    q = rng.standard_normal((B, T, Hq, hd)).astype(qdtype)
    # pre-cast through the cache dtype — the dense path's write-then-read
    # round trip, reproduced by the caller (models/transformer.py)
    k_new = rng.standard_normal((B, T, Hkv, hd)).astype(qdtype).astype(kvdtype)
    v_new = rng.standard_normal((B, T, Hkv, hd)).astype(qdtype).astype(kvdtype)
    # verify-style widx: position len+t, with the tile's tail positions
    # dropped for one slot (a short draft's padding) via the M sentinel
    widx = lengths[:, None] + np.arange(T, dtype=np.int32)[None, :]
    if T > 1:
        widx[0, -1] = M  # dropped padding position
    key_pos = np.arange(K, dtype=np.int32)
    mask = (key_pos[None, None, :]
            <= (lengths[:, None] + np.arange(T, dtype=np.int32)[None, :])[
                :, :, None])

    kern = jax.jit(functools.partial(
        ragged_paged_attention, key_window=K, page_size=page,
        logit_softcap=softcap,
    ))
    ref = jax.jit(functools.partial(_oracle, K=K, softcap=softcap))
    args = (jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(rows),
            jnp.asarray(lengths), jnp.asarray(widx), jnp.asarray(mask))
    got = kern(*args)
    want = ref(args[0], args[1], args[2], args[3], args[4], args[5],
               args[7], args[8])
    return got, want


@pytest.mark.parametrize("case", [
    dict(),                                      # f32, page-aligned K
    dict(softcap=30.0),                          # softcapped logits
    dict(K=40, page=16),                         # static tail page
    dict(qdtype="bfloat16", kvdtype="bfloat16"),  # low-precision
    dict(qdtype="float32", kvdtype="bfloat16"),  # mixed compute/cache
    dict(T=4, K=48),                             # verify tile + dropped pos
    # the loop's head counts (Qwen3-0.6B: 16 q / 8 kv): four pairs of
    # 16-bit heads behind one strided word load each, B = 5 slots so the
    # two window buffers change hands on an odd grid too
    dict(qdtype="bfloat16", kvdtype="bfloat16", Hq=16, Hkv=8, B=5),
    dict(Hq=16, Hkv=8, T=3, K=48),               # ... and its verify tile
])
def test_kernel_matches_dense_bitwise(case):
    """Kernel output AND in-place cache writes equal the dense sequence
    bit-for-bit — including the masked tail, the softcap, non-page-aligned
    K, low/mixed precision, and a wide verify tile with a scatter-dropped
    padding position."""
    got, want = _case(7, **case)
    for g, w, name in zip(got, want, ("out", "ck", "cv")):
        g, w = np.asarray(g), np.asarray(w)
        assert g.tobytes() == w.tobytes(), (
            f"{name} diverges: max|d|={np.abs(g.astype(np.float64) - w.astype(np.float64)).max()}"
        )


def test_ragged_supported_gate():
    """The VMEM gate: small windows fit, a window whose 2*K*Hkv*hd scratch
    exceeds the budget does not; tp shards the kv heads down."""
    assert ragged_supported(256, 2, 64, 4)
    assert not ragged_supported(1 << 20, 8, 128, 4)
    # tp=8 divides the per-shard scratch by 8 — the same window fits again
    assert ragged_supported(4096, 8, 128, 4, tp=8) or not ragged_supported(
        4096, 1, 128, 4
    )


# ---------------------------------------------------------------------------
# engine-level stream parity + dispatch collapse
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_ragged_matches_dense_streams(setup, temperature):
    """The same mixed-length workload through the dense tiered path and
    the collapsed ragged path emits identical token AND logprob streams —
    greedy and sampled, spec decode off and on — while the ragged engine
    issues strictly fewer decode+verify dispatches (the tier fan-out is
    gone)."""
    cfg, params = setup
    for spec in (False, True):
        outs, engs = [], []
        for ragged in (False, True):
            rng = np.random.default_rng(11)
            eng = _engine(cfg, params, decode_tiers=2, spec_decode=spec,
                          ragged_attn=ragged)
            outs.append(_run(eng, _mixed_reqs(rng, temperature, spec)))
            engs.append(eng)
        assert outs[0] == outs[1], f"stream diverged (spec={spec})"
        dense, ragged = engs
        assert ragged._ragged_ok
        assert ragged.stats["ragged_dispatches"] > 0
        assert ragged.stats["ragged_attended_pages"] > 0
        assert dense.stats["ragged_dispatches"] == 0
        # dispatch collapse: equal streams, strictly fewer programs run
        assert (ragged.stats["decode_calls"] + ragged.stats["verify_calls"]
                < dense.stats["decode_calls"] + dense.stats["verify_calls"])


def test_ragged_migration_parity(setup):
    """A mid-generation tier migration (device-side cache-row remap) under
    the ragged kernel still matches the untiered dense engine bit for bit
    — the kernel reads through the page table, so a remap is invisible to
    it."""
    cfg, params = setup

    def reqs_for(rng):
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

    ragged = _engine(cfg, params, decode_tier_lens=[64, 256],
                     decode_tier_slots=[2, 2], decode_chunk=4,
                     ragged_attn=True)
    rng = np.random.default_rng(21)
    r_out = _run(ragged, reqs_for(rng))
    assert ragged.stats["tier_migrations"] >= 1, ragged.stats
    assert ragged.stats["ragged_dispatches"] > 0

    dense = _engine(cfg, params, decode_tiers=1, decode_chunk=4)
    rng = np.random.default_rng(21)
    d_out = _run(dense, reqs_for(rng))
    assert r_out == d_out


def test_ragged_host_roundtrip_parity(setup):
    """A retained prefix spilled to host DRAM and swapped back continues
    its stream bit-identically under the ragged kernel — counter-keyed
    sampling depends on (stream, position), never on cache placement or
    the attention kernel."""
    cfg, params = setup
    rng = np.random.default_rng(25)
    turn1 = rng.integers(0, 97, 24).tolist()
    fills = [
        {"rid": f"fill-{i}",
         "ids": np.random.default_rng(26 + i).integers(0, 97, 20).tolist(),
         "n": 4}
        for i in range(2)
    ]

    outs = []
    for ragged in (False, True):
        eng = _engine(cfg, params, n_slots=2, max_seq_len=128,
                      host_offload=True, host_cache_mb=8,
                      host_min_tokens=8, ragged_attn=ragged)
        r1 = GenRequest(rid="t1", input_ids=list(turn1), max_new_tokens=6,
                        temperature=1.0, top_p=0.9)
        eng.generate_blocking([r1])
        transcript = turn1 + r1.output_tokens
        batches = [fills, [{"rid": "t2", "ids": transcript, "n": 6,
                            "temp": 1.0}]]
        done = []
        for batch in batches:
            rs = [GenRequest(rid=r["rid"], input_ids=list(r["ids"]),
                             max_new_tokens=r["n"],
                             temperature=r.get("temp", 0.0))
                  for r in batch]
            eng.generate_blocking(rs)
            done.extend(rs)
        assert eng.stats["prefix_cache_host_swaps"] >= 2, eng.stats
        outs.append((r1.output_tokens, done[-1].output_tokens,
                     done[-1].output_logprobs))
    assert outs[0] == outs[1]


def test_ragged_disagg_handoff_parity(setup):
    """A disagg handoff under the ragged kernel — leg 1 on a 'prefill'
    engine, wire export/import, leg 2 on a 'decode' engine — continues
    the stream bit-identically to the DENSE colocated control: the wire
    carries pages, the kernel reads through the page table, and counter-
    keyed sampling never sees the boundary (or the kernel swap)."""
    from areal_tpu.gen import kv_pool

    cfg, params = setup
    rng = np.random.default_rng(47)
    prompt = rng.integers(0, 97, 27).tolist()
    leg1_n, total, sid = 3, 9, 77

    def leg(eng, ids, n):
        r = GenRequest(rid=f"leg-{len(ids)}", input_ids=list(ids),
                       max_new_tokens=n, temperature=1.0, top_p=0.9,
                       stream_id=sid)
        eng.generate_blocking([r])
        return r

    # dense colocated control: both legs on one engine
    ctl = _engine(cfg, params, n_slots=2, max_seq_len=128)
    c1 = leg(ctl, prompt, leg1_n)
    c2 = leg(ctl, prompt + c1.output_tokens, total - leg1_n)

    # ragged disaggregated: leg 1 on A, wire transfer, leg 2 on B
    ea = _engine(cfg, params, n_slots=2, max_seq_len=128, ragged_attn=True)
    eb = _engine(cfg, params, n_slots=2, max_seq_len=128, ragged_attn=True,
                 host_offload=True, host_cache_mb=8, host_min_tokens=8)
    a1 = leg(ea, prompt, leg1_n)
    assert (a1.output_tokens, a1.output_logprobs) == (
        c1.output_tokens, c1.output_logprobs)
    full = prompt + a1.output_tokens
    doc = kv_pool.wire_encode_entry(ea.export_request_kv(full))
    assert eb.import_request_kv(kv_pool.wire_decode_entry(doc)) is True
    b2 = leg(eb, full, total - leg1_n)
    assert b2.cache_hit_tokens > 0  # warm continuation, not a cold prefill
    assert eb.stats["ragged_dispatches"] > 0
    assert (b2.output_tokens, b2.output_logprobs) == (
        c2.output_tokens, c2.output_logprobs)


def test_ragged_rejected_draft_kv_never_persists(setup):
    """KV hygiene through the kernel's FUSED writes: the verify dispatch
    appends draft K/V inside the kernel, and the engine's rejected-draft
    zeroing must still leave every cache row at or above a live slot's
    frontier all-zero at each step boundary."""
    cfg, params = setup
    eng = _engine(cfg, params, spec_decode=True, decode_chunk=4,
                  ragged_attn=True)
    rng = np.random.default_rng(5)
    req = GenRequest(rid="kv", input_ids=_rep_prompt(rng, 5, 16),
                     max_new_tokens=96, temperature=1.0)
    eng.submit(req)
    while not req.stop_reason:
        eng.step(chunk=4)
        s = next((i for i in range(eng.n_slots) if eng.slot_req[i] is req),
                 None)
        if s is None:
            continue
        row = eng.pool.row(s)
        frontier = int(eng.lengths[s])
        for name in ("k", "v"):
            tail = np.asarray(eng.cache[name])[:, row, frontier:]
            assert not np.any(tail), (
                f"{name}-cache rows >= frontier {frontier} nonzero after a "
                f"ragged verify dispatch (rejected draft KV leaked)"
            )
    assert eng.stats["spec_drafted"] > eng.stats["spec_accepted"]
    assert eng.stats["ragged_dispatches"] > 0


def test_ragged_compile_signature_soak(setup):
    """Steady-state ragged traffic stays on the (K bucket, D rung)
    lattice: ONE program family for the whole grid (no tier axis), zero
    mints after warmup, and the decode+verify program count within the
    checked-in `ragged_decode` budget."""
    cfg, params = setup
    eng = _engine(cfg, params, decode_tiers=2, decode_chunk=4,
                  spec_decode=True, ragged_attn=True)
    rng = np.random.default_rng(31)

    def wave(tag):
        reqs = []
        for i, (n, m) in enumerate([(8, 10), (20, 25), (40, 40), (60, 30)]):
            ids = (_rep_prompt(rng, max(2, n // 4), n) if i % 2 == 0
                   else rng.integers(0, 97, n).tolist())
            reqs.append(GenRequest(rid=f"{tag}{i}", input_ids=ids,
                                   max_new_tokens=m, temperature=1.0))
        eng.generate_blocking(reqs)

    # deterministic ladder sweep FIRST: the collapsed grid keys its K
    # bucket on the max span over ALL active slots, so which rung a
    # random wave first crosses is acceptance-dependent — saturate the
    # whole reachable (K bucket x {decode, D rung}) lattice up front by
    # walking one request per rung (random content = plain decode;
    # repetitive = drafting verify, whose span crosses every lower rung
    # as it grows), then mixed waves for the grid-packing interactions
    for L in (8, 24, 56, 120, 200):
        for rep in (False, True):
            ids = (_rep_prompt(rng, 4, L) if rep
                   else rng.integers(0, 97, L).tolist())
            eng.generate_blocking([GenRequest(
                rid=f"sweep{L}{'r' if rep else 'd'}", input_ids=ids,
                max_new_tokens=min(40, 250 - L), temperature=1.0,
            )])
    wave("warm0")
    wave("warm1")
    sizes = {
        "decode": eng._decode_fn._cache_size(),
        "verify": eng._verify_fn._cache_size(),
        "prefill": eng._prefill_fn._cache_size(),
    }
    for w in range(3):
        wave(f"soak{w}")
    assert eng._decode_fn._cache_size() == sizes["decode"]
    assert eng._prefill_fn._cache_size() == sizes["prefill"]
    assert eng.stats["ragged_dispatches"] > 0

    ref = _signature_budget("ragged_decode_soak")
    assert ref["config"] == {"n_slots": 4, "max_seq_len": 256,
                             "prompt_bucket": 16, "decode_tiers": 2,
                             "spec_rungs": 2, "ragged": 1}
    # the collapsed family: decode programs (one per K bucket) + verify
    # programs (one per K bucket x nonzero D rung), tier factor gone
    assert (eng._decode_fn._cache_size() + eng._verify_fn._cache_size()
            <= ref["budgets"]["ragged_decode"])
