"""The state kernel of a power-retention decode step
(`ops/retention_decode.py`), interpreted on the CPU, against
`ops/power_retention.py retention_step` on the block sliced out of the pool:
tiny widths (head_dim 16: F = 136 is one product of 128 feature rows and a
tail of 8), a pool with a scratch row, the layer a traced scalar."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.ops import retention_decode
from areal_tpu.ops.power_retention import RetentionState, phi, retention_step
from areal_tpu.ops.retention_decode import (
    retention_decode_state,
    retention_decode_step,
    retention_refusal,
)

L, SLOTS, HKV, D = 3, 7, 2, 16  # six slots and the scratch row
F = D * (D + 1) // 2
LAYER = 1


def _pool(seed):
    rng = np.random.default_rng(seed)
    s = rng.standard_normal((L, SLOTS, HKV, F, D)).astype(np.float32)
    z = np.abs(rng.standard_normal((L, SLOTS, HKV, F))).astype(np.float32)
    return jnp.asarray(s), jnp.asarray(z)


def _token(seed, n, G, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal((n, HKV * G, D)), dtype),
        jnp.asarray(rng.standard_normal((n, HKV, D)), dtype),
        jnp.asarray(rng.standard_normal((n, HKV, D)), dtype),
        jnp.asarray(-rng.uniform(0.0, 0.3, (n, HKV)), jnp.float32),
    )


def _step_on_kernel(base):
    return jax.jit(
        lambda q, k, v, lg, s, z, active, layer: retention_decode_step(
            q, k, v, lg, s, z, active, layer=layer, slot_base=base
        )
    )


def _step_on_slices(q, k, v, lg, s, z, active, base):
    """The plain path: slice the block, `retention_step`, write it back."""
    n = q.shape[0]
    y, st = retention_step(
        q, k, v, lg,
        RetentionState(s[LAYER, base:base + n], z[LAYER, base:base + n]),
        active=active,
    )
    return (y, s.at[LAYER, base:base + n].set(st.s),
            z.at[LAYER, base:base + n].set(st.z))


ACTIVE = {
    "all": [1, 1, 1, 1],
    "idle-first": [0, 1, 0, 1],
    "idle-ends": [0, 0, 1, 0],
    "idle-last": [1, 1, 0, 0],
}


@pytest.mark.parametrize("G", [1, 5])
@pytest.mark.parametrize("base", [0, 2])
@pytest.mark.parametrize("which", list(ACTIVE))
def test_the_kernel_steps_what_retention_step_steps(G, base, which):
    """`y` and the stepped state to float32 rounding, for a group of one
    and of five query heads, a block that starts at row 0 and past it, live
    and idle slots mixed; an idle slot gives zeros and keeps its state, and
    every other layer and row (the scratch row with them) theirs, TO THE
    BIT."""
    active = np.array(ACTIVE[which], bool)
    n = len(active)
    s, z = _pool(1)
    q, k, v, lg = _token(2, n, G)
    y, s1, z1 = _step_on_kernel(base)(
        q, k, v, lg, s, z, jnp.asarray(active), jnp.int32(LAYER))
    yr, sr, zr = _step_on_slices(q, k, v, lg, s, z, jnp.asarray(active), base)
    np.testing.assert_allclose(
        np.asarray(y)[active], np.asarray(yr)[active], atol=2e-5, rtol=2e-5)
    assert not np.asarray(y)[~active].any()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(sr), atol=2e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(zr), atol=2e-6, rtol=0)
    stepped = np.zeros((L, SLOTS), bool)
    stepped[LAYER, base:base + n] = active
    for was, now in ((s, s1), (z, z1)):
        np.testing.assert_array_equal(
            np.asarray(now)[~stepped], np.asarray(was)[~stepped])
        assert (np.asarray(now)[stepped] != np.asarray(was)[stepped]).any()


def test_nobody_live_leaves_the_whole_pool_to_the_bit():
    s, z = _pool(3)
    q, k, v, lg = _token(4, 4, 5)
    y, s1, z1 = _step_on_kernel(1)(
        q, k, v, lg, s, z, jnp.zeros((4,), bool), jnp.int32(LAYER))
    assert not np.asarray(y).any()
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(z))


def test_a_chunk_of_eight_steps_equals_eight_single_steps():
    """The engine's decode chunk is a scan of passes, each a scan of layers
    with the pool in its carry: eight passes in one program give what eight
    programs of one pass give, and what `retention_step` gives."""
    n, G, base, steps = 4, 5, 1, 8
    active = jnp.asarray([True, False, True, True])
    s, z = _pool(5)
    toks = [_token(10 + t, n, G) for t in range(steps)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *toks)

    def one_pass(carry, tok):
        def layer(carry, l):
            s, z = carry
            y, s, z = retention_decode_step(
                *tok, s, z, active, layer=l, slot_base=base)
            return (s, z), y

        return jax.lax.scan(layer, carry, jnp.arange(L, dtype=jnp.int32))

    (s8, z8), y8 = jax.jit(
        lambda s, z: jax.lax.scan(one_pass, (s, z), stacked))(s, z)
    single = jax.jit(one_pass)
    s1, z1, sr, zr = s, z, s, z
    for t, tok in enumerate(toks):
        (s1, z1), y1 = single((s1, z1), tok)
        np.testing.assert_array_equal(np.asarray(y8[t]), np.asarray(y1))
        for l in range(L):
            blk = RetentionState(sr[l, base:base + n], zr[l, base:base + n])
            yr, st = retention_step(*tok, blk, active=active)
            sr = sr.at[l, base:base + n].set(st.s)
            zr = zr.at[l, base:base + n].set(st.z)
            live = np.asarray(active)
            np.testing.assert_allclose(
                np.asarray(y1[l])[live], np.asarray(yr)[live],
                atol=1e-3, rtol=1e-3)  # a quotient of sums that cancel
    np.testing.assert_array_equal(np.asarray(s8), np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(z8), np.asarray(z1))
    np.testing.assert_allclose(np.asarray(s8), np.asarray(sr), atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(z8), np.asarray(zr), atol=2e-5, rtol=0)


def test_sixteen_bit_features_read_the_float32_state_whole():
    """A bfloat16 model's features: the read-out splits each row of the
    state into three 16-bit parts, so the state enters at float32 (the
    plain path's product rounds it to 16 bits on the chip), and the outer
    product of 16-bit `phi(k)` and `v` is exact."""
    n, G, base = 4, 5, 2
    live = np.array([1, 0, 1, 1], bool)
    s, _ = _pool(6)
    q, k, v, lg = _token(7, n, G, jnp.bfloat16)
    pq, pk = phi(q.reshape(n, HKV, G, D)), phi(k)
    assert pq.dtype == jnp.bfloat16
    g = jnp.exp(lg)
    num, s1 = jax.jit(lambda s, layer: retention_decode_state(
        jnp.concatenate([pq, pk[:, :, None]], axis=2), v, g, s,
        jnp.asarray(live), layer=layer, slot_base=base))(s, jnp.int32(LAYER))
    f32 = np.float32
    blk = np.asarray(s)[LAYER, base:base + n]
    want = np.asarray(g)[..., None, None] * np.einsum(
        "bkgf,bkfd->bkgd", np.asarray(pq, f32), blk)
    np.testing.assert_allclose(
        np.asarray(num)[live], want[live], rtol=1e-5, atol=1e-4)
    assert not np.asarray(num)[~live].any()
    stepped = (np.asarray(g)[..., None, None] * blk
               + np.asarray(pk, f32)[..., None]
               * np.asarray(v, f32)[:, :, None, :])
    np.testing.assert_allclose(
        np.asarray(s1)[LAYER, base:base + n][live], stepped[live],
        rtol=0, atol=1e-6)


@pytest.mark.parametrize("args,sentence", [
    ((128, 2), "steps a float32 state, not a state of [8256, 128] x 2 byte"),
    ((128, 4, 2), "tp=2 shards the state pool by kv head"),
])
def test_what_the_kernel_refuses_whatever_the_backend(args, sentence):
    assert sentence in retention_refusal(*args)


def test_an_explicit_cpu_run_interprets_any_width():
    assert retention_refusal(16, 4) == retention_refusal(128, 4) == ""
    assert retention_refusal(256, 4) == ""


@pytest.mark.parametrize("head_dim,sentence", [
    (128, ""),
    (64, "does not tile a state of [2080, 64] x 4 byte(s) a head"),
    (256, "does not fit the retention kernel's VMEM budget of 64 MiB"),
])
def test_what_the_chip_s_kernel_compiler_takes(head_dim, sentence, monkeypatch):
    monkeypatch.setattr(retention_decode, "_interpret_mode", lambda _: False)
    said = retention_refusal(head_dim, 4)
    assert said == "" if not sentence else sentence in said


def test_a_backend_nobody_asked_for_refuses_with_its_sentence(monkeypatch):
    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but the process did not ask")

    monkeypatch.setattr(retention_decode, "_interpret_mode", neither)
    assert "came up on 'gpu'" in retention_refusal(128, 4)
