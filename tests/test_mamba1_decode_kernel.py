"""The state kernel of a selective-scan decode step
(`ops/mamba1_decode.py`), interpreted on the CPU, against `ops/mamba1.py
selective_step` on the block sliced out of the pool: tiny widths (24
channels: less than a lane tile, which the interpreter takes), blocks of
more and of fewer slots than a tile of eight, a pool with a scratch row,
the layer a traced scalar."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from areal_tpu.models import transformer as tf
from areal_tpu.ops import mamba1_decode
from areal_tpu.ops.mamba1 import selective_step
from areal_tpu.ops.mamba1_decode import mamba1_refusal, selective_decode_step
from tests import test_hybrid_model, test_jamba_model

L, SLOTS, N, C = 3, 13, 16, 24  # twelve slots and the scratch row
LAYER = 1


def _pool(seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((L, SLOTS, N, C)).astype(np.float32))


def _token(seed, n):
    """u, dt, A, B, C, D of one token a slot, as `_mamba1_block` has them."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return (
        jnp.asarray(rng.standard_normal((n, C)), f32),
        jnp.asarray(rng.uniform(0.01, 0.5, (n, C)), f32),
        -jnp.asarray(rng.uniform(0.5, 4.0, (C, N)), f32),
        jnp.asarray(rng.standard_normal((n, N)), f32),
        jnp.asarray(rng.standard_normal((n, N)), f32),
        jnp.asarray(rng.standard_normal((C,)), f32),
    )


def _step_on_kernel(base):
    return jax.jit(lambda tok, s, active, layer: selective_decode_step(
        *tok, s, active, layer=layer, slot_base=base))


def _step_on_slices(tok, s, active, base, layer=LAYER):
    """The plain path: slice the block, `selective_step`, write it back."""
    n = tok[0].shape[0]
    y, blk = selective_step(*tok, s[layer, base:base + n], active=active)
    return y, s.at[layer, base:base + n].set(blk)


ACTIVE = {
    "all": [1] * 10,
    "idle-first": [0, 1, 0, 1, 1, 0, 0, 0, 1, 1],
    "one-tile-idle": [0] * 8 + [1, 0],
    "idle-last": [1, 1, 0, 1, 0, 0, 0, 0, 0, 0],
    "one": [0, 0, 0, 1],
}


@pytest.mark.parametrize("base", [0, 2])
@pytest.mark.parametrize("which", list(ACTIVE))
def test_the_kernel_steps_what_selective_step_steps(base, which):
    """`y` and the stepped state to float32 rounding, for a block that
    starts at row 0 and past it, live and idle slots mixed, a tile of eight
    with nobody live in it; an idle slot gives zeros and keeps its state,
    and every other layer and row (the scratch row with them) theirs, TO
    THE BIT."""
    active = np.array(ACTIVE[which], bool)
    n = len(active)
    s, tok = _pool(1), _token(2, n)
    y, s1 = _step_on_kernel(base)(
        tok, s, jnp.asarray(active), jnp.int32(LAYER))
    yr, sr = _step_on_slices(tok, s, jnp.asarray(active), base)
    assert y.dtype == tok[0].dtype and s1.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(y)[active], np.asarray(yr)[active], atol=1e-5, rtol=1e-5)
    assert not np.asarray(y)[~active].any()
    np.testing.assert_allclose(np.asarray(s1), np.asarray(sr), atol=2e-6, rtol=0)
    stepped = np.zeros((L, SLOTS), bool)
    stepped[LAYER, base:base + n] = active
    np.testing.assert_array_equal(
        np.asarray(s1)[~stepped], np.asarray(s)[~stepped])
    assert (np.asarray(s1)[stepped] != np.asarray(s)[stepped]).all(axis=(1, 2)).all()


def test_nobody_live_leaves_the_whole_leaf_to_the_bit():
    s, tok = _pool(3), _token(4, 10)
    y, s1 = _step_on_kernel(1)(
        tok, s, jnp.zeros((10,), bool), jnp.int32(LAYER))
    assert not np.asarray(y).any()
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s))


def test_sixteen_bit_inputs_step_a_float32_state():
    """A bfloat16 model's u, B and C: widened exactly, the state and every
    product float32, `y` back in u's dtype as `selective_step` gives it."""
    active = jnp.asarray([1, 0, 1, 1, 1, 1, 0, 1, 1], bool)
    s = _pool(5)
    u, dt, A, Bm, Cm, D = _token(6, 9)
    bf16 = jnp.bfloat16
    tok = (u.astype(bf16), dt, A, Bm.astype(bf16), Cm.astype(bf16), D)
    y, s1 = _step_on_kernel(2)(tok, s, active, jnp.int32(LAYER))
    yr, sr = _step_on_slices(tok, s, active, 2)
    assert y.dtype == bf16
    live = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(y, np.float32)[live], np.asarray(yr, np.float32)[live],
        atol=0.05, rtol=0.01)  # one rounding to 8 bits of two float32 sums
    np.testing.assert_allclose(np.asarray(s1), np.asarray(sr), atol=2e-6, rtol=0)


def test_a_chunk_of_eight_passes_equals_eight_single_passes():
    """The engine's decode chunk is a scan of passes, each a scan of layers
    with the leaf in its carry (`_hybrid_plan`'s runs): eight passes in one
    program give what eight programs of one pass give, to the bit, and what
    `selective_step` gives."""
    n, base, steps = 10, 1, 8
    active = jnp.asarray(ACTIVE["idle-first"], bool)
    s = _pool(7)
    toks = [_token(10 + t, n) for t in range(steps)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *toks)

    def one_pass(s, tok):
        def layer(s, l):
            y, s = selective_decode_step(
                *tok, s, active, layer=l, slot_base=base)
            return s, y

        return jax.lax.scan(layer, s, jnp.arange(L, dtype=jnp.int32))

    s8, y8 = jax.jit(lambda s: jax.lax.scan(one_pass, s, stacked))(s)
    single = jax.jit(one_pass)
    s1, sr = s, s
    live = np.asarray(active)
    for t, tok in enumerate(toks):
        s1, y1 = single(s1, tok)
        np.testing.assert_array_equal(np.asarray(y8[t]), np.asarray(y1))
        for l in range(L):
            yr, sr = _step_on_slices(tok, sr, active, base, layer=l)
            np.testing.assert_allclose(
                np.asarray(y1[l])[live], np.asarray(yr)[live],
                atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(s8), np.asarray(s1))
    np.testing.assert_allclose(np.asarray(s8), np.asarray(sr), atol=2e-5, rtol=0)


@pytest.mark.parametrize("args,sentence", [
    ((5120, 16, 2), "steps a float32 state, not a state of [16, 5120] x 2 byte"),
    ((5120, 16, 4, 2), "tp=2 shards the state pool"),
])
def test_what_the_kernel_refuses_whatever_the_backend(args, sentence):
    assert sentence in mamba1_refusal(*args)


def test_an_explicit_cpu_run_interprets_any_width():
    assert mamba1_refusal(24, 16, 4) == mamba1_refusal(5120, 16, 4) == ""
    assert mamba1_refusal(1 << 20, 16, 4) == ""


@pytest.mark.parametrize("d_inner,d_state,sentence", [
    (5120, 16, ""),
    (5184, 16, "does not tile a state of [16, 5184] x 4 byte(s) a layer"),
    (5120, 12, "does not tile a state of [12, 5120] x 4 byte(s) a layer"),
    (1 << 16, 16, "does not fit the selective-scan kernel's VMEM budget of 16 MiB"),
])
def test_what_the_chip_s_kernel_compiler_takes(
        d_inner, d_state, sentence, monkeypatch):
    monkeypatch.setattr(mamba1_decode, "_interpret_mode", lambda _: False)
    said = mamba1_refusal(d_inner, d_state, 4)
    assert said == "" if not sentence else sentence in said


def test_a_backend_nobody_asked_for_refuses_with_its_sentence(monkeypatch):
    def neither(_):
        raise RuntimeError("JAX came up on 'gpu' but the process did not ask")

    monkeypatch.setattr(mamba1_decode, "_interpret_mode", neither)
    assert "came up on 'gpu'" in mamba1_refusal(5120, 16, 4)


def test_the_hybrid_kind_answers_from_the_pool_s_own_leaf():
    """`SlotKind.kernel_refusal` of the hybrid kind: the selective scan's
    float32 leaf has the kernel, a narrower leaf and tp do not, and a stack
    of Mamba-2 blocks is refused for its recurrence, whatever its pool."""
    refusal = tf.HYBRID_KIND.kernel_refusal
    cfg = test_jamba_model.CFG
    cache = tf.init_kv_cache(cfg, 3, 32, "float32")
    assert refusal(cfg, cache, 32, "float32", 1) == ""
    assert "tp=2 shards the state pool" in refusal(cfg, cache, 32, "float32", 2)
    narrow = {**cache, "s": cache["s"].astype(jnp.bfloat16)}
    assert "steps a float32 state, not a state of [16, 128] x 2 byte" in (
        refusal(cfg, narrow, 32, "float32", 1))
    other = test_hybrid_model.CFG
    said = refusal(other, tf.init_kv_cache(other, 3, 32, "float32"), 32,
                   "float32", 1)
    assert "no kernel steps a state with a decay a head" in said
    with pytest.raises(ValueError, match="ragged_attn: no kernel steps a state"):
        tf.forward_decode(
            None, other, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            {}, ragged=True)


# sha256 of the decode chunk's lowered text (locations stripped, symbols
# renamed in order of appearance) of the two kinds that share this PR's
# code and must not move, as the PARENT of PR 53 lowers them: the Mamba-2
# hybrid toy (`forward_decode_hybrid`, `_hybrid_traverse`,
# `_hybrid_state_io`) and the power-retention toy (`_count_passes`'s
# sibling; its kernel).  A PR that means to change either program replaces
# its digest and says so.
PARENT_PROGRAMS = {
    "hybrid": "4d79b3158e645478e05ee96a7e1fc5e414c7d0e64eec37b9ccd64fab19b65e21",
    "state": "1ce79be6a306ea3560fbcd619143968b321e20d989fa8ce866aa86cb8aef19b1",
}


def _decode_chunk_digest(kind):
    import hashlib
    import re

    from areal_tpu.gen.engine import GenEngine
    from tests.test_retention_engine import CFG as STATE_CFG

    cfg, params = {
        "hybrid": lambda: (test_hybrid_model.CFG, test_hybrid_model._params()),
        "state": lambda: (STATE_CFG, tf.init_params(
            STATE_CFG, jax.random.PRNGKey(0))),
    }[kind]()
    eng = GenEngine(cfg, params=params, n_slots=6, max_seq_len=128,
                    prompt_bucket=16, decode_chunk=4, kv_dtype="float32")
    S = eng.n_slots + 1
    zeros = lambda dt: jnp.zeros((S,), dt)  # noqa: E731
    text = eng._decode_fn.lower(
        eng.params, eng.cache, zeros(jnp.int32), zeros(jnp.int32),
        zeros(jnp.int32), zeros(jnp.int32), zeros(bool), zeros(jnp.float32),
        zeros(jnp.float32), zeros(jnp.int32), eng._decode_key,
        jnp.arange(S, dtype=jnp.int32), 4, 0, 6,
        32 if eng.decode_window else 128, eng.ragged_attn,
    ).as_text()
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = re.sub(r"^#loc.*$", "", text, flags=re.M)
    names = {}
    text = re.sub(r"@[A-Za-z_][\w.]*",
                  lambda m: names.setdefault(m.group(0), f"@s{len(names)}"),
                  text)
    return eng, hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("kind", list(PARENT_PROGRAMS))
def test_the_kinds_beside_it_lower_the_decode_chunk_they_lowered(kind):
    eng, digest = _decode_chunk_digest(kind)
    # the Mamba-2 stack is refused the kernel; power retention has its own
    assert eng.ragged_attn == (kind == "state")
    assert digest == PARENT_PROGRAMS[kind]
