"""`ops/windowed_decode.py`: the paged decode kernel over a full layer's
columns of the `windowed` slot kind, interpreted on the CPU at toy widths
with the published ratios (8 query heads over 2 kv heads, keys of 24 beside
values of 16, tiles of 32 positions of a pool of 128), against a plain
float32 softmax and against the copy path of `models/windowed.py
_attend_decode` (`ragged=False`: eight slots' windows sliced out of the
pool, two products and one softmax over them)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import windowed
from areal_tpu.ops import windowed_decode
from areal_tpu.ops.windowed_decode import (
    windowed_decode_attention,
    windowed_refusal,
)

H, HKV, DQ, DV, M, TK = 8, 2, 24, 16, 128, 32
G = H // HKV
SCALE = DQ ** -0.5
VALUE_SCALE = 0.707


def _case(lengths, dtype=jnp.float32, slots=None, seed=0, pool_dtype=None):
    """Queries, new columns and a pool of `slots` (default: one a length,
    plus two) random slots in 3 full layers, the values times the value
    scale as `_project` leaves them."""
    B = len(lengths)
    S = slots or B + 2
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, H, DQ), jnp.float32).astype(dtype)
    kn = jax.random.normal(keys[1], (B, HKV * DQ), jnp.float32).astype(dtype)
    vn = (VALUE_SCALE * jax.random.normal(
        keys[2], (B, HKV * DV), jnp.float32)).astype(dtype)
    pk = jax.random.normal(keys[3], (3, S, M, HKV * DQ), jnp.float32)
    pv = VALUE_SCALE * jax.random.normal(
        keys[4], (3, S, M, HKV * DV), jnp.float32)
    pool_dtype = pool_dtype or dtype
    return q, kn, vn, pk.astype(pool_dtype), pv.astype(pool_dtype)


def _oracle(q, kn, vn, pk, pv, starts, j, slot_base):
    """Plain float32 softmax, a kv head at a time, over a slot's columns
    below `starts` and the new column."""
    f32 = jnp.float32
    out = []
    for b in range(q.shape[0]):
        n = int(starts[b])
        keys = jnp.concatenate(
            [pk[j, slot_base + b, :n].astype(f32), kn[b][None].astype(f32)]
        ).reshape(n + 1, HKV, DQ)
        vals = jnp.concatenate(
            [pv[j, slot_base + b, :n].astype(f32), vn[b][None].astype(f32)]
        ).reshape(n + 1, HKV, DV)
        p = jax.nn.softmax(jnp.einsum(
            "hgd,khd->hgk", q[b].astype(f32).reshape(HKV, G, DQ), keys,
            precision="highest") * SCALE, axis=-1)
        out.append(jnp.einsum(
            "hgk,khv->hgv", p, vals, precision="highest").reshape(H, DV))
    return np.asarray(jnp.stack(out))


def _kernel(q, kn, vn, pk, pv, starts, live, j=1, slot_base=0, block=TK):
    return np.asarray(windowed_decode_attention(
        q, kn, vn, pk, pv, jnp.asarray(starts, jnp.int32), jnp.asarray(live),
        j=j, slot_base=slot_base, scale=SCALE, block=block,
    ).astype(jnp.float32))


# a slot of length 0 (its new column alone) and of 1, one under / at / one
# over a tile's edge, several tiles, and the window's clamp
# (`forward_decode`: min(lengths, K - 1)), mixed in one block
RAGGED = [0, 1, TK - 1, TK, TK + 1, 3 * TK + 5, M - 1]


@pytest.mark.parametrize("slot_base", [0, 2])
@pytest.mark.parametrize("j", [0, 2])
def test_ragged_lengths_give_the_softmax_over_each_slot_s_columns(j, slot_base):
    case = _case(RAGGED)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(*case, RAGGED, live, j=j, slot_base=slot_base)
    np.testing.assert_allclose(
        got, _oracle(*case, RAGGED, j, slot_base), atol=2e-6, rtol=2e-6)


def test_a_slot_of_length_zero_attends_its_new_column_alone():
    """Probability one on the column the pool does not hold yet: the output
    is that column's value, each head its kv head's part, whatever the pool
    holds."""
    q, kn, vn, pk, pv = _case([0, 0, 0])
    got = _kernel(q, kn, vn, pk * jnp.nan, pv * jnp.nan, [0, 0, 0],
                  [True, True, True])
    want = np.repeat(np.asarray(vn).reshape(3, HKV, 1, DV), G, axis=2)
    np.testing.assert_allclose(got, want.reshape(3, H, DV), atol=1e-6)


@pytest.mark.parametrize("live", [
    [True, False, True, False, True, True],
    [False, True, True, True, True, False],  # the first and the last
    [False, False, False, True, False, False],
    [False] * 6,
])
def test_inactive_slots_give_zeros_and_nothing_past_a_length_is_read(live):
    """The pool holds NaN wherever the kernel has no business: every column
    of an inactive slot, every tile past a live slot's last one, every other
    layer and every slot outside the block."""
    lengths = [40, 0, 100, 64, 7, 0]
    live = np.array(live)
    q, kn, vn, pk, pv = _case(lengths, slots=9)
    j, base = 2, 1
    ok = np.zeros(pk.shape[:3] + (1,), bool)
    for b, (n, on) in enumerate(zip(lengths, live)):
        if on:
            ok[j, base + b, : -(-n // TK) * TK] = True
    got = _kernel(
        q, kn, vn, jnp.where(ok, pk, jnp.nan), jnp.where(ok, pv, jnp.nan),
        lengths, live, j=j, slot_base=base)
    assert (got[~live] == 0).all()
    want = _oracle(q, kn, vn, pk, pv, lengths, j, base)
    np.testing.assert_allclose(got[live], want[live], atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("block", [TK, 64, M])
def test_the_tile_s_width_does_not_change_the_result(block):
    case = _case(RAGGED, seed=3)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(*case, RAGGED, live, block=block)
    np.testing.assert_allclose(
        got, _oracle(*case, RAGGED, 1, 0), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("dtype,pool_dtype,tol", [
    (jnp.bfloat16, jnp.bfloat16, 2e-2),  # the cell's: widened in the tile
    (jnp.bfloat16, jnp.float32, 2e-2),  # a 4-byte pool narrowed in the tile
    (jnp.float32, jnp.bfloat16, 2e-6),
])
def test_2_and_4_byte_pools_under_float32_statistics(dtype, pool_dtype, tol):
    """The probabilities are narrowed to the compute dtype before the
    weighted sum, as the copy path narrows them, so kernel and oracle agree
    to that dtype's rounding of the output."""
    case = _case(RAGGED, dtype=dtype, pool_dtype=pool_dtype, seed=5)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(*case, RAGGED, live)
    np.testing.assert_allclose(
        got, _oracle(*case, RAGGED, 1, 0), atol=tol, rtol=tol)


def test_the_pool_is_only_read():
    q, kn, vn, pk, pv = _case(RAGGED, seed=7)
    before = np.asarray(pk).copy(), np.asarray(pv).copy()
    fn = jax.jit(lambda *a: windowed_decode_attention(
        *a, jnp.asarray(RAGGED, jnp.int32), jnp.ones(len(RAGGED), bool),
        j=0, slot_base=1, scale=SCALE, block=TK))
    fn(q, kn, vn, pk, pv).block_until_ready()
    assert np.array_equal(np.asarray(pk), before[0])
    assert np.array_equal(np.asarray(pv), before[1])


@pytest.mark.parametrize("slot_base,B", [(0, 5), (1, 16)])
def test_attend_decode_on_the_kernel_equals_its_copy_path(
        slot_base, B, monkeypatch):
    """`_attend_decode`'s full-layer branch both ways: a block of 5 slots
    (one group of the copy path) and of 16 (two groups of eight) out of a
    pool of 17, some inactive; a sliding layer takes no notice of
    `ragged`."""
    monkeypatch.setattr(windowed_decode, "BLOCK", TK)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q = jax.random.normal(keys[0], (B, 1, H, DQ))
    k = jax.random.normal(keys[1], (B, 1, HKV * DQ))
    v = VALUE_SCALE * jax.random.normal(keys[2], (B, 1, HKV * DV))
    pk = jax.random.normal(keys[3], (3, 17, M, HKV * DQ))
    pv = VALUE_SCALE * jax.random.normal(keys[4], (3, 17, M, HKV * DV))
    live = jax.random.bernoulli(keys[5], 0.7, (B,)).at[0].set(True)
    starts = jnp.asarray(
        ([0, TK, 50, M - 1, 77] * 4)[:B], jnp.int32)
    at = {"K": M, "starts": starts, "slot_base": slot_base, "live": live}
    outs = [
        np.asarray(windowed._attend_decode(
            q, k, v, pk, pv, 2, {**at, "ragged": ragged}, None, None, SCALE,
            HKV))
        for ragged in (False, True)
    ]
    keep = np.asarray(live)
    np.testing.assert_allclose(
        outs[1][keep], outs[0][keep], atol=2e-6, rtol=2e-6)
    assert (outs[1][~keep] == 0).all()
    ring = [
        np.asarray(windowed._attend_decode(
            q, k, v, pk[:, :, :8], pv[:, :, :8], 2, {**at, "ragged": ragged},
            8, jnp.ones((H,)), SCALE, HKV))
        for ragged in (False, True)
    ]
    assert np.array_equal(ring[0], ring[1])


def _pool(M=16384, rk=768, rv=512, dtype=jnp.bfloat16, sink_full=False):
    """A configuration and a pool as `windowed_refusal` looks at them."""
    leaf = lambda r: jax.ShapeDtypeStruct((2, 65, M, r), dtype)  # noqa: E731
    return (types.SimpleNamespace(sink_full=sink_full),
            {"k": leaf(rk), "v": leaf(rv)}, M, dtype, 1)


def test_a_pool_the_kernel_does_not_read_is_refused_by_name(monkeypatch):
    """A 1-byte pool (the benchmark's float8 control), a sink on the full
    layers and a length the tiles do not divide, whatever the backend; on a
    chip, where the kernel is lowered and not interpreted, rows the compiler
    does not tile; a backend that is neither a TPU nor an explicit CPU
    run."""
    toy = dict(M=128, rk=HKV * DQ, rv=HKV * DV, dtype=jnp.float32)
    assert windowed_refusal(*_pool(**toy)) == ""  # interpreted: this suite's
    assert windowed_refusal(*_pool()) == ""
    assert "2- or 4-byte columns" in windowed_refusal(
        *_pool(dtype=jnp.float8_e4m3fn))
    assert "add_full_attention_sink_bias" in windowed_refusal(
        *_pool(sink_full=True))
    assert "do not divide" in windowed_refusal(*_pool(M=2000))
    monkeypatch.setattr(windowed_decode, "_interpret_mode", lambda _: False)
    assert windowed_refusal(*_pool()) == ""
    assert windowed_refusal(*_pool(dtype=jnp.float32)) == ""
    assert windowed_refusal(*_pool(M=256)) == ""
    for pool in (toy, dict(rk=4 * 144), dict(rv=4 * 80), dict(M=72)):
        assert "does not tile" in windowed_refusal(*_pool(**pool)), pool

    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but nobody asked for it")

    monkeypatch.setattr(windowed_decode, "_interpret_mode", neither)
    assert "came up on 'gpu'" in windowed_refusal(*_pool())
