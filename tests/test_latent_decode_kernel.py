"""`ops/latent_decode.py`: the paged decode kernel over the latent pool,
interpreted on the CPU at toy widths (4 heads, rows of 32 + 8, tiles of 32
positions of a pool of 128), against the copy path of `models/latent.py
absorbed_attend` (`ragged=False`: the block's window sliced out of the pool,
two products and one softmax over it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import latent
from areal_tpu.ops import latent_decode
from areal_tpu.ops.latent_decode import latent_decode_attention, latent_refusal
from tests.test_longcat_model import CFG, _params

H, R, C, M, TK = CFG.num_heads, CFG.latent_row_dim, CFG.kv_lora_rank, 128, 32
SCALE = CFG.head_dim_ ** -0.5


def _case(lengths, dtype=jnp.float32, slots=None, seed=0, pool_dtype=None):
    """Queries, new rows and a pool of `slots` (default: one a length, plus
    two) random slots in 4 sublayers."""
    B = len(lengths)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, R), jnp.float32).astype(dtype)
    new = jax.random.normal(keys[1], (B, R), jnp.float32).astype(dtype)
    lat = jax.random.normal(keys[2], (4, slots or B + 2, R, M), jnp.float32)
    return q, new, lat.astype(pool_dtype or dtype)


def _oracle(q, new, lat, starts, j, slot_base):
    """Plain softmax over a slot's rows below `starts` and the new row."""
    out = []
    for b in range(q.shape[0]):
        n = int(starts[b])
        rows = jnp.concatenate(
            [lat[j, slot_base + b, :, :n].T.astype(jnp.float32),
             new[b][None].astype(jnp.float32)])  # [n + 1, R]
        p = jax.nn.softmax(
            jnp.einsum("hr,kr->hk", q[b].astype(jnp.float32), rows,
                       precision="highest") * SCALE, axis=-1)
        out.append(jnp.einsum("hk,kc->hc", p, rows[:, :C], precision="highest"))
    return np.asarray(jnp.stack(out))


def _kernel(q, new, lat, starts, live, j=1, slot_base=0, block=TK):
    return np.asarray(latent_decode_attention(
        q, new, lat, jnp.asarray(starts, jnp.int32), jnp.asarray(live),
        j=j, slot_base=slot_base, kv_lora_rank=C, scale=SCALE, block=block,
    ).astype(jnp.float32))


# a slot of length 0, one under / at / one over a tile's edge, several
# tiles, and the window's clamp (`forward_decode`: min(lengths, K - 1))
RAGGED = [0, TK - 1, TK, TK + 1, 3 * TK + 5, M - 1, 1]


@pytest.mark.parametrize("slot_base", [0, 2])
@pytest.mark.parametrize("j", [0, 3])
def test_ragged_lengths_give_the_softmax_over_each_slot_s_rows(j, slot_base):
    q, new, lat = _case(RAGGED)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(q, new, lat, RAGGED, live, j=j, slot_base=slot_base)
    np.testing.assert_allclose(
        got, _oracle(q, new, lat, RAGGED, j, slot_base), atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("live", [
    [True, False, True, False, True, True],
    [False, True, True, True, True, False],  # the first and the last
    [False, False, False, True, False, False],
    [False] * 6,
])
def test_inactive_slots_give_zeros_and_nothing_past_a_length_is_read(live):
    """The pool holds NaN wherever the kernel has no business: every row of
    an inactive slot, every tile past a live slot's last one, every other
    sublayer and every slot outside the block."""
    lengths = [40, 0, 100, 64, 7, 0]
    live = np.array(live)
    q, new, lat = _case(lengths, slots=9)
    j, base = 2, 1
    ok = np.zeros(lat.shape, bool)
    for b, (n, on) in enumerate(zip(lengths, live)):
        if on:
            ok[j, base + b, :, : -(-n // TK) * TK] = True
    got = _kernel(
        q, new, jnp.where(ok, lat, jnp.nan), lengths, live, j=j, slot_base=base)
    assert (got[~live] == 0).all()
    want = _oracle(q, new, lat, lengths, j, base)
    np.testing.assert_allclose(got[live], want[live], atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("block", [TK, 64, M])
def test_the_tile_s_width_does_not_change_the_result(block):
    q, new, lat = _case(RAGGED, seed=3)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(q, new, lat, RAGGED, live, block=block)
    np.testing.assert_allclose(
        got, _oracle(q, new, lat, RAGGED, 1, 0), atol=2e-6, rtol=2e-6)


def test_a_16_bit_pool_is_widened_in_the_tile():
    """bfloat16 queries over a bfloat16 pool: float32 statistics, the
    probabilities narrowed before the weighted sum as the copy path narrows
    them, so the two agree to bfloat16's rounding of the output."""
    q, new, lat = _case(RAGGED, dtype=jnp.bfloat16, seed=5)
    live = np.ones(len(RAGGED), bool)
    got = _kernel(q, new, lat, RAGGED, live)
    np.testing.assert_allclose(
        got, _oracle(q, new, lat, RAGGED, 1, 0), atol=2e-2, rtol=2e-2)


def test_the_pool_is_only_read():
    q, new, lat = _case(RAGGED, seed=7)
    before = np.asarray(lat).copy()
    fn = jax.jit(lambda q, new, lat: latent_decode_attention(
        q, new, lat, jnp.asarray(RAGGED, jnp.int32),
        jnp.ones(len(RAGGED), bool), j=0, slot_base=1, kv_lora_rank=C,
        scale=SCALE, block=TK))
    fn(q, new, lat).block_until_ready()
    assert np.array_equal(np.asarray(lat), before)


@pytest.mark.parametrize("slot_base", [0, 2])
def test_absorbed_attend_on_the_kernel_equals_its_copy_path(slot_base, monkeypatch):
    """`absorbed_attend`'s decode branch both ways, `W_kvb` folded in before
    and applied after: a block of 5 slots of a pool of 8, one inactive."""
    monkeypatch.setattr(latent_decode, "BLOCK", TK)
    ap = jax.tree_util.tree_map(
        lambda a: a[0, 1], _params()["layers"]["attn"])
    B = 5
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    nope, rope = CFG.qk_nope_head_dim, CFG.qk_rope_head_dim
    q_nope = jax.random.normal(keys[0], (B, 1, H, nope))
    q_rope = jax.random.normal(keys[1], (B, 1, H, rope))
    row = jax.random.normal(keys[2], (B, 1, R))
    lat = jax.random.normal(keys[3], (4, 8, R, M))
    live = jnp.array([True, True, False, True, True])
    at = {"K": M, "starts": jnp.array([0, TK, 50, M - 1, 77], jnp.int32),
          "slot_base": slot_base, "live": live}
    outs = [
        np.asarray(latent.absorbed_attend(
            CFG, ap, q_nope, q_rope, row, lat, 3, {**at, "ragged": ragged}))
        for ragged in (False, True)
    ]
    keep = np.asarray(live)
    np.testing.assert_allclose(
        outs[1][keep], outs[0][keep], atol=2e-6, rtol=2e-6)
    assert (outs[1][~keep] == 0).all()


def test_a_pool_the_kernel_does_not_read_is_refused_by_name(monkeypatch):
    """A 1-byte pool (the benchmark's float8 control) and a length the
    tiles do not divide, whatever the backend; on a chip, where the kernel
    is lowered and not interpreted, rows the compiler does not tile; a
    backend that is neither a TPU nor an explicit CPU run."""
    assert latent_refusal(40, 32, 128, 4) == ""  # interpreted: this suite's
    assert latent_refusal(576, 512, 8192, 2) == ""
    assert "2- or 4-byte rows" in latent_refusal(576, 512, 8192, 1)
    assert "do not divide" in latent_refusal(576, 512, 2000, 2)
    monkeypatch.setattr(latent_decode, "_interpret_mode", lambda _: False)
    assert latent_refusal(576, 512, 8192, 2) == ""
    assert latent_refusal(576, 512, 8192, 4) == ""
    assert latent_refusal(576, 512, 256, 2) == ""
    for pool in ((40, 32, 8192, 2), (576, 500, 8192, 2), (576, 512, 64, 2)):
        assert "does not tile" in latent_refusal(*pool), pool

    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but nobody asked for it")

    monkeypatch.setattr(latent_decode, "_interpret_mode", neither)
    assert "came up on 'gpu'" in latent_refusal(576, 512, 8192, 2)
