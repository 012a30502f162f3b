"""Splash-attention parity vs the naive segment-masked reference.

Runs the Pallas kernels in interpret mode on the virtual 8-device CPU mesh
(tests can't see real chips; tests/test_tpu_compile.py compiles the kernel
for a described v5e).  Covers the packed-segment mask semantics, GQA grouping,
sliding windows, gradients, and the shard_map path with a sequence-sharded
query (the Ulysses-regime long-context configuration, review rounds 1 and 5).
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
