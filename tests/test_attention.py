"""Splash-attention parity vs the naive segment-masked reference.

Runs the Pallas kernels in interpret mode on the virtual 8-device CPU mesh
(tests can't see real chips; tests/test_tpu_compile.py compiles the kernel
for a described v5e).  Covers the packed-segment mask semantics, GQA grouping,
sliding windows, gradients, and the shard_map path with a sequence-sharded
query (the Ulysses-regime long-context configuration, review rounds 1 and 5).
A single packed row runs under a block mask narrowed by its segment ids: held
here to the bits of the static mask's kernel, forward and all three gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import attention as attn_mod
from areal_tpu.ops.attention import (
    make_attention_mask,
    naive_attention,
    segment_attention,
)
from areal_tpu.parallel import build_mesh


@pytest.fixture(autouse=True)
def _interpret_mode():
    attn_mod.INTERPRET = True
    yield
    attn_mod.INTERPRET = False


def _packed_inputs(rng, B, T, Hq, Hkv, hd, n_segs=3):
    q = jnp.asarray(rng.normal(size=(B, T, Hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, hd)), jnp.float32)
    seg = np.full((B, T), -1, np.int32)
    pos = np.zeros((B, T), np.int32)
    for b in range(B):
        bounds = sorted(rng.choice(np.arange(32, T - 32), n_segs - 1, replace=False))
        start = 0
        for s, end in enumerate(list(bounds) + [T - 16]):  # leave tail padding
            seg[b, start:end] = s
            pos[b, start:end] = np.arange(end - start)
            start = end
    return q, k, v, jnp.asarray(seg), jnp.asarray(pos)


def _naive(q, k, v, seg, pos, window=None, softcap=None):
    mask = make_attention_mask(seg, pos, window)
    return naive_attention(q, k, v, mask, softcap)


def test_splash_matches_naive_packed_segments():
    rng = np.random.default_rng(0)
    q, k, v, seg, pos = _packed_inputs(rng, B=2, T=256, Hq=4, Hkv=2, hd=128)
    out = segment_attention(q, k, v, seg, pos, impl="splash")
    ref = _naive(q, k, v, seg, pos)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4


def test_splash_non_pow2_extent_picks_dividing_block():
    """A 768-token packed row is 128-aligned but NOT divisible by the
    default 512 query block; the kernel builder must step down to 384
    instead of crashing (regression: heterogeneous-length GRPO rollouts
    quantized to 768-token rows killed the train step)."""
    rng = np.random.default_rng(3)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=768, Hq=2, Hkv=1, hd=128)
    out = segment_attention(q, k, v, seg, pos, impl="splash")
    ref = _naive(q, k, v, seg, pos)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4


def test_splash_sliding_window():
    rng = np.random.default_rng(1)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=256, Hq=2, Hkv=1, hd=128, n_segs=2)
    out = segment_attention(q, k, v, seg, pos, sliding_window=64, impl="splash")
    ref = _naive(q, k, v, seg, pos, window=64)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4


def test_splash_gradients_match():
    rng = np.random.default_rng(2)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=256, Hq=4, Hkv=2, hd=128)
    w = jnp.asarray((np.asarray(seg) >= 0)[..., None, None], jnp.float32)

    def loss(impl):
        def f(q, k, v):
            o = segment_attention(q, k, v, seg, pos, impl=impl)
            return ((o * w) ** 2).sum()

        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gs = loss("splash")
    gn = loss("naive")
    for a, b in zip(gs, gn):
        denom = np.abs(np.asarray(b)).max() + 1e-9
        assert np.abs(np.asarray(a) - np.asarray(b)).max() / denom < 1e-3


def test_sharded_splash_matches_naive():
    """dp2 x sp2 x tp2 mesh: q-sequence sharded, kv whole, kv heads over tp."""
    mesh = build_mesh(dp=2, fsdp=1, sp=2, tp=2)
    rng = np.random.default_rng(3)
    q, k, v, seg, pos = _packed_inputs(rng, B=4, T=256, Hq=4, Hkv=2, hd=128)

    @jax.jit
    def sharded(q, k, v, seg, pos):
        return segment_attention(q, k, v, seg, pos, impl="splash", mesh=mesh)

    with mesh:
        out = sharded(q, k, v, seg, pos)
    ref = _naive(q, k, v, seg, pos)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4


def _rows(T, lens):
    """Segment ids [B, T] of rows holding sequences of `lens[b]`, ids
    rising from 0, the tail padding (-1)."""
    seg = np.full((len(lens), T), -1, np.int32)
    for b, row in enumerate(lens):
        start = 0
        for s, n in enumerate(row):
            seg[b, start:start + n] = s
            start += n
    return seg


def _draw(seg, Hq, Hkv, seed=0):
    """q, k, v and a cotangent for rows of segment ids `seg`, heads of 128."""
    rng = np.random.default_rng(seed)
    B, T = seg.shape
    return tuple(
        jnp.asarray(rng.normal(size=(B, T, H, 128)), jnp.float32)
        for H in (Hq, Hkv, Hkv, Hq)
    )


def _fwd_and_grads(inputs, seg, window=None, mesh=None):
    """(out, dq, dk, dv) of the splash path."""
    seg = jnp.asarray(seg)

    @jax.jit
    def f(q, k, v, do):
        out, vjp = jax.vjp(
            lambda q, k, v: segment_attention(
                q, k, v, seg, None, sliding_window=window, impl="splash",
                mesh=mesh,
            ),
            q, k, v,
        )
        return (out,) + vjp(do)

    return [np.asarray(x) for x in f(*inputs)]


# T = 640 is 128-aligned and divisible by no larger block: five blocks of 128
NARROWED_CASES = {
    # (lens per row, Hq, Hkv, sliding window, blocks run, blocks causal)
    "two_segments_padded_tail": ([[300, 250]], 4, 2, None, 11, 15),
    "eight_rows_of_2_to_4": (
        [[200, 300], [128, 128, 128, 128], [400, 100, 100], [639, 1],
         [250, 250], [100, 200, 300], [320, 320], [90, 90, 90, 90]],
        4, 2, None, 120, 120,  # more rows than one keep the static mask
    ),
    "one_segment": ([[640]], 4, 2, None, 15, 15),
    "all_padding": ([[]], 4, 2, None, 15, 15),
    "boundary_on_block_edge": ([[256, 384]], 4, 2, None, 9, 15),
    "heads_12_2": ([[300, 250]], 12, 2, None, 11, 15),
    "heads_16_8": ([[130, 250, 200]], 16, 8, None, 10, 15),
    "sliding_window_shrunk_grid": ([[256, 300]], 2, 1, 100, 8, 9),
}


@pytest.mark.parametrize("case", sorted(NARROWED_CASES))
def test_narrowed_kernel_bit_equal_to_static(case, monkeypatch):
    """Forward AND dq, dk, dv under the narrowed block mask against the
    kernel as it ran before (the static mask under `jax.vmap`): the same
    bits on every real position (on padding too: finite, and in fact
    equal), and `block_counts` says what ran."""
    lens, Hq, Hkv, window, run, causal = NARROWED_CASES[case]
    seg = _rows(640, lens)
    inputs = _draw(seg, Hq, Hkv)
    got = _fwd_and_grads(inputs, seg, window)
    counts = attn_mod.block_counts(jnp.asarray(seg), Hq // Hkv, window)
    assert tuple(int(c) for c in counts) == (run, causal)
    monkeypatch.setattr(attn_mod, "_narrows", lambda rows: False)
    want = _fwd_and_grads(inputs, seg, window)
    real = seg >= 0
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert np.isfinite(a).all(), name
        np.testing.assert_array_equal(a[real], b[real], err_msg=name)


def test_sharded_narrowed_kernel_bit_equal_to_static(monkeypatch):
    """dp2 x sp2 x tp2: each device holds ONE row's query shard, so the
    shard_map body narrows too, from its shard of the mask infos and of the
    query segment ids; same bits as the static mask, forward and gradients."""
    mesh = build_mesh(dp=2, fsdp=1, sp=2, tp=2)
    seg = _rows(1280, [[600, 500], [256, 512, 300]])
    inputs = _draw(seg, 4, 2)
    with mesh:
        got = _fwd_and_grads(inputs, seg, mesh=mesh)
        monkeypatch.setattr(attn_mod, "_narrows", lambda rows: False)
        want = _fwd_and_grads(inputs, seg, mesh=mesh)
    real = seg >= 0
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(a[real], b[real], err_msg=name)
    q, k, v, _ = inputs
    pos = jnp.broadcast_to(jnp.arange(1280), seg.shape)
    ref = np.asarray(_naive(q, k, v, jnp.asarray(seg), pos))
    assert np.abs(got[0] - ref)[real].max() < 1e-4


@pytest.mark.parametrize("lens,run", [([8682, 7442], 288), ([7442, 8682], 290)])
def test_block_overlap_at_train_16k_lengths(lens, run):
    """`train_16k`'s row (T 16,384, blocks of 512): 288 of the 528 causal
    blocks hold a same-sequence pair (290 with the shorter trace first).
    No kernel runs."""
    seg = jnp.asarray(_rows(16384, [lens]))
    overlap = np.asarray(attn_mod.block_overlap(seg[0], seg[0], 512, 512))
    causal = np.tril(np.ones((32, 32), bool))
    assert causal.sum() == 528 and (overlap & causal).sum() == run
    # the 240 left out are the rectangle "query in the second trace, key in
    # the first"; the padded tail keeps every block of its own trace
    first_end, second_start = lens[0] // 512, -(-lens[0] // 512)
    assert not overlap[second_start:, :first_end].any()
    assert tuple(int(c) for c in attn_mod.block_counts(seg, 6)) == (run, 528)


@pytest.mark.parametrize("window", [None, 200])
def test_skipped_steps_name_the_next_running_block(window):
    """`data_next` of a step the narrowing took out is that of the next
    step that runs, in the order each kernel walks its grid, so the
    pipeline fetches nothing for it; steps that run keep theirs."""
    kernel = attn_mod._make_kernel(640, 2, window, None, 1, interpret=True)
    seg = jnp.asarray(_rows(640, [[256, 300]])[0])
    narrowed = attn_mod._narrowed(kernel, seg, seg)
    for name, dkv in (("fwd_mask_info", False), ("dq_mask_info", False),
                      ("dkv_mask_info", True)):
        was, now = getattr(kernel, name), getattr(narrowed, name)
        run = np.asarray(now.block_mask)[0] > 0
        assert run.sum() < (np.asarray(was.block_mask) > 0).sum()
        nxt, old = np.asarray(now.data_next)[0], np.asarray(was.data_next)[0]
        if dkv:  # walked kv block by kv block, q fastest
            run, nxt, old = run.T, nxt.T, old.T
        steps = [(a, b) for a in range(run.shape[0]) for b in range(run.shape[1])]
        running = [s for s in steps if run[s]]
        for n, s in enumerate(steps):
            later = [r for r in running if r >= s] or running[:1]
            assert nxt[s] == old[later[0]], (name, s)


def test_auto_impl_cpu_is_naive():
    attn_mod.INTERPRET = False
    rng = np.random.default_rng(4)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=256, Hq=2, Hkv=2, hd=128)
    out = segment_attention(q, k, v, seg, pos, impl="auto")
    ref = _naive(q, k, v, seg, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_ring_matches_naive():
    """dp2 x sp4 mesh: K/V sequence-sharded and rotated via ppermute; the
    online-softmax accumulation matches the full naive oracle on packed
    segments with padding."""
    mesh = build_mesh(dp=2, fsdp=1, sp=4, tp=1)
    rng = np.random.default_rng(5)
    q, k, v, seg, pos = _packed_inputs(rng, B=2, T=256, Hq=4, Hkv=2, hd=32)

    @jax.jit
    def ring(q, k, v, seg, pos):
        return segment_attention(q, k, v, seg, pos, impl="ring", mesh=mesh)

    with mesh:
        out = ring(q, k, v, seg, pos)
    ref = _naive(q, k, v, seg, pos)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4
    # padding rows produce exact zeros (no valid key anywhere)
    assert np.abs(np.asarray(out)[~valid]).max() == 0.0


def test_ring_sliding_window_and_tp():
    mesh = build_mesh(dp=1, fsdp=2, sp=2, tp=2)
    rng = np.random.default_rng(6)
    q, k, v, seg, pos = _packed_inputs(rng, B=2, T=128, Hq=4, Hkv=2, hd=16)

    @jax.jit
    def ring(q, k, v, seg, pos):
        return segment_attention(
            q, k, v, seg, pos, impl="ring", mesh=mesh, sliding_window=24
        )

    with mesh:
        out = ring(q, k, v, seg, pos)
    ref = _naive(q, k, v, seg, pos, window=24)
    valid = np.asarray(seg) >= 0
    err = np.abs(np.asarray(out) - np.asarray(ref))[valid].max()
    assert err < 1e-4


def test_ring_gradients_match_naive():
    mesh = build_mesh(dp=1, fsdp=1, sp=4, tp=2)
    rng = np.random.default_rng(7)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=128, Hq=4, Hkv=2, hd=16)
    # cotangent only on valid positions: the naive oracle's padding rows
    # attend uniformly (softmax over an all-MASK_VALUE row) while ring
    # emits exact zeros there — a deliberate behavioural difference
    valid = (np.asarray(seg) >= 0)[..., None, None]
    ct = jnp.asarray(rng.normal(size=q.shape) * valid, jnp.float32)

    def loss_ring(q, k, v):
        out = segment_attention(q, k, v, seg, pos, impl="ring", mesh=mesh)
        return jnp.sum(out * ct)

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, seg, pos) * ct)

    with mesh:
        gs = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gn):
        denom = np.abs(np.asarray(b)).max() + 1e-9
        assert np.abs(np.asarray(a) - np.asarray(b)).max() / denom < 1e-3


def test_ring_without_sp_falls_back():
    rng = np.random.default_rng(8)
    q, k, v, seg, pos = _packed_inputs(rng, B=1, T=128, Hq=2, Hkv=2, hd=16)
    out = segment_attention(q, k, v, seg, pos, impl="ring", mesh=None)
    ref = _naive(q, k, v, seg, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
