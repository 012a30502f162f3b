"""The main path's Pallas kernels, compiled for a described TPU v5e.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (`on-chip-measurement` guide, section 2): what
Mosaic refuses at real widths it refuses in this file, on the CPU, before
any chip time is spent.  Interpret mode cannot show that — the ragged
decode kernel passed every interpret-mode test and was refused for its
matmul layout.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU's library, and every xdist worker imports
every test file), the compiles happen in the test's own process, and all of
them live in this one file so one worker keeps the library.
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Qwen2.5-1.5B attention widths (models/model_config.py qwen25_1p5b)
HQ, HKV, HD = 12, 2, 128


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out.  And compile
    # as the program runs on the chip: conftest.py raises the matmul
    # precision for the CPU numerics tests, which asks the kernels for a
    # multi-pass product of bfloat16 inputs that no TPU program asks for.
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _layer_copies(text, stacks, floor=16 << 20):
    """[(operation, bytes it writes)] of the compiled text's stand-alone
    copies of a part of one of `stacks` (stacked parameter leaves): in a
    computation that is no fusion's own (the entry, a loop's body, a
    branch) a `slice`, or a fusion whose called computation holds nothing
    but `parameter`, `slice`, `bitcast` and `tuple`, that reads a stack (an
    array of a stack's leading dimension, element count and type: the
    stored leaf or a view of it) and writes more than `floor` bytes.  A
    layer's weight that reaches its product through such an operation is
    written and read once more in every pass; the same slice INSIDE the
    product's fusion (`wo`'s) is an operand read where it lies."""
    names = {"bfloat16": "bf16", "float32": "f32"}
    itemsize = {names[str(x.dtype)]: x.dtype.itemsize for x in stacks}
    stacks = {(names[str(x.dtype)], x.shape[0], x.size) for x in stacks}

    def arrays(shapes):
        """(type, leading dimension, elements) of every array named."""
        dims = [(dt, [int(d) for d in dims.split(",") if d] or [1])
                for dt, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]", shapes)]
        return [(dt, d[0], math.prod(d)) for dt, d in dims]

    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([.\w\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            op = re.match(
                r"\s+(?:ROOT )?%?(\S+) = (\S.*?) ([a-z\-]+)\((.*)", line)
            if op:
                bodies[name].append(op.groups())
    slices_of_a_stack = {
        name for name, ops in bodies.items()
        if {"slice"} <= {op for _, _, op, _ in ops}
        <= {"parameter", "slice", "bitcast", "tuple"}
        and all(set(arrays(shape)) <= stacks
                for _, shape, op, _ in ops if op == "parameter")
    }
    found = []
    for name, ops in bodies.items():
        if "fused_computation" in name or "fusion" in name:
            continue
        shape_of = {out: shape for out, shape, _, _ in ops}
        for out, shape, op, rest in ops:
            if op == "slice":
                read = shape_of.get(rest.split(")")[0].lstrip("%"), "")
                if not set(arrays(read)) & stacks:
                    continue
            elif op != "fusion" or re.search(
                    r"calls=%?([.\w\-]+)", rest).group(1) not in slices_of_a_stack:
                continue
            size = sum(itemsize[dt] * n for dt, _, n in arrays(shape))
            if size > floor:
                found.append((out, size))
    return found


@pytest.mark.parametrize("rows,T", [(8, 2048), (1, 16384), (1, 4096)])
def test_splash_forward_backward_compiles(one_chip, rows, T):
    """The train step's attention at 8 x 2048 and 1 x 16384, forward and
    backward, as `segment_attention` builds it for one device: a single row
    under the block mask narrowed by its (traced) segment ids, so the three
    kernels take their mask infos as computed operands; more rows under the
    static mask."""
    from areal_tpu.ops import attention

    assert attention._narrows(rows) == (rows == 1)
    kernel = attention._make_kernel(T, HQ // HKV, None, None, 1)

    def loss(q, k, v, seg):
        out = attention._splash_call(kernel, q, k, v, seg, HQ // HKV)
        return out.astype(jnp.float32).sum()

    bf16 = jnp.bfloat16
    args = (
        _shape(one_chip, (rows, T, HQ, HD), bf16),
        _shape(one_chip, (rows, T, HKV, HD), bf16),
        _shape(one_chip, (rows, T, HKV, HD), bf16),
        _shape(one_chip, (rows, T), jnp.int32),
    )
    text = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(*args).compile().as_text()
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("T", [1, 4])
@pytest.mark.parametrize("K", [512, 2048])
def test_ragged_decode_compiles(one_chip, T, K):
    """Ragged paged decode (T=1) and fused verification (T=4) over a
    64-slot grid at page 128, bf16 cache, updated in place."""
    from areal_tpu.ops.ragged_decode import ragged_paged_attention

    B, M = 64, 2048
    bf16 = jnp.bfloat16
    fn = jax.jit(
        functools.partial(
            ragged_paged_attention, key_window=K, page_size=128,
            interpret=False,
        ),
        donate_argnums=(3, 4),
    )
    args = (
        _shape(one_chip, (B, T, HQ, HD), bf16),  # q
        _shape(one_chip, (B, T, HKV, HD), bf16),  # k_new
        _shape(one_chip, (B, T, HKV, HD), bf16),  # v_new
        _shape(one_chip, (B + 1, M, HKV, HD), bf16),  # cache k
        _shape(one_chip, (B + 1, M, HKV, HD), bf16),  # cache v
        _shape(one_chip, (B,), jnp.int32),  # rows
        _shape(one_chip, (B,), jnp.int32),  # lengths
        _shape(one_chip, (B, T), jnp.int32),  # widx
        _shape(one_chip, (B, T, K), jnp.bool_),  # mask
    )
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the cache is appended to in place: no second copy of it on the device
    cache_bytes = 2 * (B + 1) * M * HKV * HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 4


def _compile_decode_chunk(one_chip, monkeypatch, ragged, K, sample,
                          cfg=None, S=65, M=2048):
    """A fused chunk of 8 decode steps at `rollout_decode`'s size
    (Qwen2.5-1.5B, 64 slots + the scratch row x 2048, through the page
    table) or at another configuration's, the cache donated;
    `sample(logits, lengths, active)` gives the next tokens.  Returns
    (compiled, cfg, S, M)."""
    import dataclasses

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import qwen25_1p5b
    from areal_tpu.models.transformer import forward_decode, init_kv_cache
    from areal_tpu.ops import ragged_decode

    # JAX_PLATFORMS=cpu would interpret the kernel: compile the real one
    monkeypatch.setattr(ragged_decode, "_interpret_mode", lambda _: False)
    cfg = dataclasses.replace(
        cfg or qwen25_1p5b(), dtype="bfloat16", param_dtype="bfloat16")
    B = S - 1

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, S, M, "bfloat16")))

    def chunk(params, cache, tokens, lengths, active, rows):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache = forward_decode(
                params, cfg, tok, ln, cache, key_window=K, active=active,
                rows=rows, ragged=ragged, page_size=128)
            tok = sample(logits, ln, active).astype(jnp.int32)
            return (cache, tok, ln + 1), tok

        (cache, _, _), toks = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return toks, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_), i32
    ).compile()
    return compiled, cfg, S, M


@pytest.mark.parametrize("ragged,K", [(False, 256), (False, 2048), (True, 512)])
def test_decode_chunk_leaves_the_cache_where_it_is(one_chip, ragged, K, monkeypatch):
    """A fused chunk of decode steps at `rollout_decode`'s size: the donated
    cache is appended to in place, and no operation of the compiled program
    takes a layer's slab `[S, M, Hkv, hd]` out of it or builds a second
    stacked cache: as `lax.scan` did while the cache was its input and
    output, and as the compiler did at these two windows (and not at 512 or
    1024) when the window was read as one gather `ck[l, rows, :K]`, by
    copying the whole cache into another layout every pass (PERF.md,
    PR 28).  On the ragged path the kernel gets the cache through the
    scan's carry, flattened."""
    import re

    compiled, cfg, S, M = _compile_decode_chunk(
        one_chip, monkeypatch, ragged, K,
        lambda logits, ln, active: jnp.argmax(logits, -1))
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == ragged
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim_
    for moved in ((S, M, Hkv, hd), (L, S, M, Hkv, hd)):
        shape = re.escape("bf16[" + ",".join(map(str, moved)) + "]")
        assert not re.search(rf"= {shape}\S* (copy|copy-start)\(", text)
    # a slab is the window itself when the window is the whole row
    if K < M:
        assert not re.search(rf"= {re.escape(f'bf16[{S},{M},{Hkv},{hd}]')}", text)
    cache_bytes = 2 * L * S * M * Hkv * hd * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 8


@pytest.mark.parametrize("K", [256, 512])
def test_the_loop_s_decode_chunk_holds_the_kernel(one_chip, K, monkeypatch):
    """The decode chunk `grpo_async_loop` runs since the engine's default
    takes the kernel: Qwen3-0.6B (16 q / 8 kv heads of 128, q/k norm: four
    pairs of 16-bit heads a strided word load each), 32 slots + the
    scratch row x 1024, through the page table, at the two key windows
    its traced run holds.  The kernel is in the program, and the cache
    stays where it is: temporaries under a quarter of it."""
    import json
    import os

    from areal_tpu.models.model_config import TransformerConfig

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs", "qwen3-0.6b.json")
    with open(path) as f:
        cfg = TransformerConfig.from_hf(json.load(f))
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.qk_norm) == (16, 8, True)
    compiled, cfg, S, M = _compile_decode_chunk(
        one_chip, monkeypatch, True, K,
        lambda logits, ln, active: jnp.argmax(logits, -1), cfg=cfg, S=33,
        M=1024)
    assert "tpu_custom_call" in compiled.as_text()
    cache_bytes = 2 * cfg.num_layers * S * M * cfg.num_kv_heads * cfg.head_dim_ * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 4


def test_decode_chunk_sorts_only_under_a_conditional(one_chip, monkeypatch):
    """The same chunk with the engine's sampler (row keys, per-slot
    parameters, the active mask as `live`): `lax.top_k` over the whole
    vocabulary is a sort of `f32[64, 151936]` on the chip, 41% of a decoded
    token while it ran on every pass (PERF.md, PR 31).  It stays in the
    program, for a slot that asks for top-k/top-p, inside the branch of a
    conditional; and the conditional's operands bring no second copy of
    the logits."""
    import re

    from areal_tpu.gen.sampling import sample_tokens_keyed
    from tests.fixtures import HLO_SORT, sorts_outside_conditionals

    def sample(logits, ln, active):
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            jax.random.PRNGKey(0), ln)
        # parameters the compiler cannot fold: they follow the inputs
        temp = jnp.where(active, 1.0, 0.7)
        top_k = jnp.where(ln > 5, 0, 40).astype(jnp.int32)
        top_p = jnp.where(ln > 7, 1.0, 0.95)
        return sample_tokens_keyed(
            logits.astype(jnp.float32), keys, temp, top_k, top_p,
            live=active)[0]

    compiled, cfg, _, _ = _compile_decode_chunk(
        one_chip, monkeypatch, False, 512, sample)
    text = compiled.as_text()
    assert " conditional(" in text
    # the sort is there ...
    assert HLO_SORT.search(text)
    # ... and nowhere it would run for a slot grid that asked for none
    assert not sorts_outside_conditionals(text)
    logits = re.escape(f"f32[64,{cfg.vocab_size}]")
    assert not re.search(rf"= {logits}\S* (copy|copy-start)\(", text)


# ---------------------------------------------------------------------------
# the hybrid stack of `rollout_hybrid_moe` (nemotron3-super-120b as the
# benchmark cuts it) at its real size: 128 slots + the scratch row x 2048
# ---------------------------------------------------------------------------

HYBRID_SLOTS, HYBRID_LEN = 129, 2048


def _hybrid_shapes(one_chip):
    import os

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import init_kv_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TransformerConfig.from_hf(os.path.join(
        repo, "benchmarks/configs/nemotron3-super-120b.json")).replace(
        dtype="bfloat16", param_dtype="bfloat16", remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, HYBRID_SLOTS, HYBRID_LEN, "bfloat16")))
    return cfg, params, cache


def _no_copy_of_a_pool_leaf_or_the_experts(compiled, cache):
    """The pool is aliased and appended to in place; neither a pool leaf,
    nor one Mamba block's slab of the state, nor the stacked routed experts
    (or one block's 1.4 GB of them, as a slice handed to the grouped-matmul
    kernel was) is copied."""
    import re

    text = compiled.as_text()
    S = HYBRID_SLOTS
    for moved in (f"f32[5,{S},128,64,128]", f"f32[{S},128,64,128]",
                  f"bf16[1,{S},{HYBRID_LEN},2,128]",
                  "bf16[5,128,1024,2688]", "bf16[5,128,2688,1024]",
                  "bf16[640,1024,2688]", "bf16[640,2688,1024]"):
        assert not re.search(
            rf"= {re.escape(moved)}\S* (copy|copy-start)\(", text), moved
    # one block's experts cut out of the stack (a fusion of a slice)
    for moved in ("bf16[128,1024,2688]", "bf16[128,2688,1024]"):
        assert not re.search(rf"= {re.escape(moved)}", text), moved
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    # the grouped products of the held experts are the chip's kernel, not
    # the dense fallback that computes every expert for every row
    assert "tpu_custom_call" in text
    return mem


def test_hybrid_decode_chunk_steps_the_pool_in_place(one_chip):
    """A fused chunk of 8 decode passes of the eleven-block stack at the
    widest key window: state, window and K/V are stepped where they lie."""
    from areal_tpu.models.transformer import forward_decode_hybrid

    cfg, params, cache = _hybrid_shapes(one_chip)
    B = HYBRID_SLOTS

    def chunk(params, cache, tokens, lengths, active):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache, counts = forward_decode_hybrid(
                params, cfg, tok, ln, cache, key_window=HYBRID_LEN,
                slot_base=0, active=active)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tok, ln + 1), (tok, counts)

        (cache, _, _), out = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return out, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_)).compile()
    mem = _no_copy_of_a_pool_leaf_or_the_experts(compiled, cache)
    assert mem.temp_size_in_bytes < 1 << 30


def test_hybrid_fresh_prefill_fits_beside_weights_and_pool(one_chip):
    """The largest fresh prefill one dispatch takes (2048 padded tokens:
    `GenEngine._state_admit_tokens`), 4 rows x 512."""
    from areal_tpu.models.transformer import forward_prefill

    cfg, params, cache = _hybrid_shapes(one_chip)
    rows = _shape(one_chip, (4,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, n, slots: forward_prefill(p, cfg, ids, n, c, slots),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (4, 512), jnp.int32), rows,
            rows).compile()
    mem = _no_copy_of_a_pool_leaf_or_the_experts(compiled, cache)
    assert mem.temp_size_in_bytes < 1 << 30


def test_hybrid_suffix_prefill_with_the_fan_out_copy_fits(one_chip):
    """Sixteen siblings' last prompt token after the copy of a 512-column
    prefix, each continuing from its representative's state and window."""
    from areal_tpu.models.transformer import forward_prefill_cached

    cfg, params, cache = _hybrid_shapes(one_chip)
    rows = _shape(one_chip, (16,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, st, n, slots, src: forward_prefill_cached(
            p, cfg, ids, st, n, c, slots, copy_src=src, copy_block=512,
            key_window=512),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (16, 128), jnp.int32), rows, rows,
            rows, rows).compile()
    mem = _no_copy_of_a_pool_leaf_or_the_experts(compiled, cache)
    assert mem.temp_size_in_bytes < 1 << 30


def test_windowed_splash_compiles_beside_the_causal_one(one_chip):
    """A stack that mixes sliding and full layers (afmoe) holds BOTH static
    masks in one program: one row of 16,384 under `LocalMask` (window 2,048)
    and under `CausalMask`, each narrowed by the row's segment ids, forward
    and backward."""
    from areal_tpu.ops import attention

    T = 16384
    local = attention._make_kernel(T, 32 // 4, 2048, None, 1)
    causal = attention._make_kernel(T, 32 // 4, None, None, 1)
    assert local is not causal

    def loss(q, k, v, seg):
        a = attention._splash_call(local, q, k, v, seg, 8)
        b = attention._splash_call(causal, a, k, v, seg, 8)
        return b.astype(jnp.float32).sum()

    bf16 = jnp.bfloat16
    args = (
        _shape(one_chip, (1, T, 32, 128), bf16),
        _shape(one_chip, (1, T, 4, 128), bf16),
        _shape(one_chip, (1, T, 4, 128), bf16),
        _shape(one_chip, (1, T), jnp.int32),
    )
    text = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(*args).compile().as_text()
    )
    # two masks x (forward, dq, dkv)
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


def test_gated_experts_at_a_share_compile_with_their_transposes(one_chip):
    """`models/moe.py gated_moe_ffn` at the benchmark's widths (16,384
    tokens, top-8 of 128 with 16 held, 2,048 -> 1,024), forward and
    backward, on its blocks of 24,576 rows (1.5 x the even share of
    16,384): the three grouped products, their transposes by the rows and
    by the experts' weights, and the sums by token are the chip's
    grouped-matmul kernel, not the fallback that computes every group for
    every row; nothing is scattered, nothing is chosen by a conditional,
    and no buffer of N x k = 131,072 rows by a layer width is left in the
    program: what runs is sized by the block."""
    from areal_tpu.models import moe
    from areal_tpu.models.model_config import TransformerConfig

    cfg = TransformerConfig(
        hidden_size=2048, num_experts=128, num_experts_per_tok=8,
        experts_held=(0, 16), moe_intermediate_size=1024,
        moe_shared_intermediate_size=1024, router_kind="sigmoid",
        routed_scaling_factor=2.826, dtype="bfloat16",
    )
    bf16, N, D, F = jnp.bfloat16, 16384, 2048, 1024
    lp = {
        "router": _shape(one_chip, (D, 128), bf16),
        "router_bias": _shape(one_chip, (128,), jnp.float32),
        "w_gate": _shape(one_chip, (16, D, F), bf16),
        "w_up": _shape(one_chip, (16, D, F), bf16),
        "w_down": _shape(one_chip, (16, F, D), bf16),
        "ws_gate": _shape(one_chip, (D, F), bf16),
        "ws_up": _shape(one_chip, (D, F), bf16),
        "ws_down": _shape(one_chip, (F, D), bf16),
    }
    rows = moe.held_row_block(N, 8, 16, 128)
    assert rows == 24576

    def loss(lp, x):
        out, _ = moe.gated_moe_ffn(cfg, lp, x, bf16)
        return jnp.square(out.astype(jnp.float32)).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(lp, _shape(one_chip, (1, N, D), bf16)).compile().as_text()
    )
    # forward 3 products + the sum by token; backward gate and up again,
    # 2 + 1 by the rows, 3 by the weights, the sum by token
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 4 + 9
    assert len(re.findall(r" while\(", text)) == 2
    assert not re.search(r" conditional\(", text)
    assert not re.search(r" scatter\(", text)
    assert "[131072,2048]" not in text and "[131072,1024]" not in text
    assert f"bf16[{rows},2048]" in text and f"bf16[{rows},1024]" in text


# what `latent_moe_ffn` lowered to at the commit before `gated_moe_ffn` got
# a routed part of its own (bbc63e1; the two lowered texts were equal letter
# for letter):
# the operations that make the dispatch, and how many operations in all
HYBRID_BLOCK_OPS = {
    "chlo.ragged_dot": 2, "stablehlo.sort": 1, "stablehlo.gather": 3,
    "stablehlo.scatter": 2, "chlo.top_k": 1, "stablehlo.dot_general": 6,
    "stablehlo.compare": 16, "stablehlo.select": 8,
}
HYBRID_BLOCK_OPS_IN_ALL = 196


def test_the_hybrid_s_expert_block_is_the_program_it_was(one_chip):
    """`latent_moe_ffn` shares the router and the sort with `gated_moe_ffn`
    and nothing else: a decode pass of the hybrid cell (128 rows, top-22 of
    512 with 128 held, block 2 of the five expert blocks' stack) lowers to
    the operations it had: two grouped products, the sort (one function,
    called for the assignments and for the way back), three gathers,
    `bincount`'s scatter-add over 2,816 rows, and no conditional: a switch
    in every decode program would only add set-up to the dearest cell."""
    from areal_tpu.models import moe

    cfg, params, _ = _hybrid_shapes(one_chip)
    stack = params["layers"]["E"]
    lp = {k: v if k in ("w1", "w2") else _shape(one_chip, v.shape[1:], v.dtype)
          for k, v in stack.items()}

    def block(lp, h):
        return moe.latent_moe_ffn(cfg, {**lp, "block": 2}, h, jnp.bfloat16)

    lowered = jax.jit(block).lower(
        lp, _shape(one_chip, (128, 1, cfg.hidden_size), jnp.bfloat16))
    ops = re.findall(r"= \"?(stablehlo\.[a-z_]+|chlo\.[a-z_]+)", lowered.as_text())
    count = {name: ops.count(name) for name in set(ops)}
    assert count.get("stablehlo.case", 0) + count.get("stablehlo.if", 0) == 0
    assert {k: count.get(k, 0) for k in HYBRID_BLOCK_OPS} == HYBRID_BLOCK_OPS
    assert len(ops) == HYBRID_BLOCK_OPS_IN_ALL
    text = lowered.compile().as_text()
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 2
    assert not re.search(r" conditional\(", text)


# the cell rollout_latent_8k: 40 slots + the scratch row x 8,192 positions
LATENT_SLOTS, LATENT_LEN = 41, 8192


def _latent_shapes(one_chip):
    import os

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import init_kv_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TransformerConfig.from_hf(os.path.join(
        repo, "benchmarks/configs/longcat-flash-omni.json")).replace(
        dtype="bfloat16", param_dtype="bfloat16", remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, LATENT_SLOTS, LATENT_LEN, "bfloat16")))
    return cfg, params, cache


def _latent_pool_and_experts_stay_where_they_are(compiled, params, cache):
    """The latent pool is aliased and written a block a row in place: it is
    never copied or laid out anew (a scatter over slot and position made
    the compiler do that, there and back, in every program), and neither
    the stacked routed experts nor one layer's 1.2 GB of them are copied
    out for the grouped products; no sublayer of a stacked projection is
    copied out on its way to its product (`wq_b`'s eight were, 302 MB a
    decode pass, until `models/latent.py by_head`), but `wkv_b`'s: a head's
    rows are cut into a key and a value half, and 134 MB a pass still go
    through a fusion of slices."""
    text = compiled.as_text()
    S, M = LATENT_SLOTS, LATENT_LEN
    attn = dict(params["layers"]["attn"])
    del attn["wkv_b"]
    assert not _layer_copies(text, jax.tree.leaves(attn))
    for moved in (f"bf16[8,{S},576,{M}]", "bf16[4,16,6144,2048]",
                  "bf16[4,16,2048,6144]", "bf16[64,6144,2048]",
                  "bf16[64,2048,6144]", "bf16[16,6144,2048]",
                  "bf16[16,2048,6144]"):
        assert not re.search(
            rf"= {re.escape(moved)}\S* (copy|copy-start)\(", text), moved
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool == 41 * 8192 * 9216
    assert "tpu_custom_call" in text  # the chip's grouped-matmul kernel
    return mem


@pytest.mark.parametrize("ragged", [True, False], ids=["kernel", "copy"])
def test_latent_decode_chunk_reads_the_pool_where_it_lies(
        one_chip, monkeypatch, ragged):
    """A fused chunk of 8 decode passes of the four double layers at the
    widest key window, beside 10.35 GB of weights and 3.10 GB of pool, the
    rows written in place.  On the paged kernel (`ops/latent_decode.py`,
    steered here to be lowered and not interpreted: the backend is the CPU)
    no sublayer's window is sliced or copied out of the pool and no product
    runs over a window: one kernel call a sublayer and pass reads the rows
    where they lie.  On the copy path (`ragged_attn=False`, a pool the
    kernel does not read) the windows are read one sublayer at a time (the
    barrier in `models/latent.py absorbed_attend`: eight of them held at
    once did not fit)."""
    from areal_tpu.models import latent
    from areal_tpu.ops import latent_decode

    monkeypatch.setattr(latent_decode, "_interpret_mode", lambda _: False)
    cfg, params, cache = _latent_shapes(one_chip)
    B = LATENT_SLOTS

    def chunk(params, cache, tokens, lengths, active):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache, counts = latent.forward_decode(
                params, cfg, tok, ln, cache, key_window=LATENT_LEN,
                slot_base=0, active=active, ragged=ragged)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tok, ln + 1), (tok, counts)

        (cache, _, _), out = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return out, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_)).compile()
    mem = _latent_pool_and_experts_stay_where_they_are(compiled, params, cache)
    text = compiled.as_text()
    # a sublayer's window out of the pool, and the scores over it
    windows = [
        len(re.findall(rf"= {re.escape(shape)}\S* [a-z\-]+\(", text))
        for shape in (f"bf16[{B},576,{LATENT_LEN}]",
                      f"f32[{B},{cfg.num_heads},{LATENT_LEN}]")
    ]
    kernels = len(re.findall(
        r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*latent_decode", text))
    if ragged:
        assert windows == [0, 0] and kernels == cfg.attn_sublayers == 8
        # 118 MB (377 while every sublayer's `wq_b` was copied out a pass)
        assert mem.temp_size_in_bytes < 1 << 27
    else:
        assert min(windows) >= 8 and kernels == 0
        # 538 MB (803 with the copies of `wq_b`; 1.24 GB in PR 44)
        assert mem.temp_size_in_bytes < 5 << 27


def test_latent_fresh_prefill_of_a_whole_row_fits(one_chip, monkeypatch):
    """The largest fresh prefill one dispatch takes (one row of 8,192
    tokens: `GenEngine._state_admit_tokens`), as the chip runs it: the
    expanded attention through the splash kernel with a head's query and
    key 192 wide beside a value of 128 (steered here: the backend is the
    CPU), the dense FFNs a block of tokens at a time.  What is left of the
    chip beside weights and pool is 3.2 GB."""
    from areal_tpu.models import latent
    from areal_tpu.models.transformer import forward_prefill

    monkeypatch.setattr(latent, "_splash_applies", lambda T: T >= 256)
    cfg, params, cache = _latent_shapes(one_chip)
    rows = _shape(one_chip, (1,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, n, slots: forward_prefill(p, cfg, ids, n, c, slots),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (1, LATENT_LEN), jnp.int32), rows,
            rows).compile()
    mem = _latent_pool_and_experts_stay_where_they_are(compiled, params, cache)
    assert "splash" in compiled.as_text()
    assert mem.temp_size_in_bytes < 9 << 28  # 1.98 GB when written


# ---------------------------------------------------------------------------
# mimo-v2.5 as `rollout_swa_moe_16k` cuts it, at its real size: 64 slots + the
# scratch row of 16,384 positions' columns (2 full layers) and rings of 128
# (5 sliding layers)
# ---------------------------------------------------------------------------

MIMO_SLOTS, MIMO_LEN = 65, 16384


def _mimo_shapes(one_chip):
    import os

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import init_kv_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TransformerConfig.from_hf(os.path.join(
        repo, "benchmarks/configs/mimo-v2.5.json")).replace(
        dtype="bfloat16", param_dtype="bfloat16", remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, MIMO_SLOTS, MIMO_LEN, "bfloat16")))
    return cfg, params, cache


def _mimo_projections(params):
    return jax.tree.leaves(
        [params["layers"][kind] for kind in ("full", "sliding")])


def _mimo_pool_and_weights_stay_where_they_are(compiled, params, cache):
    """The pool is aliased and written in place, a scatter a layer: no leaf
    of it is copied or laid out anew (one scatter over all layers, a head
    axis of 192, one row's products at a time each made the compiler do
    that), and neither the stacked experts nor the stacked projections are
    copied out, whole or a layer at a time (every layer's `wq`, `wk` and
    `wv` were, 822 MB a decode pass, until `models/windowed.py by_head`)."""
    text = compiled.as_text()
    S, M = MIMO_SLOTS, MIMO_LEN
    assert not _layer_copies(text, _mimo_projections(params))
    for moved in (f"bf16[2,{S},{M},768]", f"bf16[2,{S},{M},512]",
                  f"bf16[5,{S},128,1536]", f"bf16[5,{S},128,1024]",
                  "bf16[6,16,4096,2048]", "bf16[6,16,2048,4096]",
                  "bf16[96,4096,2048]", "bf16[96,2048,4096]",
                  "bf16[5,12288,4096]", "bf16[2,12288,4096]"):
        assert not re.search(
            rf"= {re.escape(moved)}\S* (copy|copy-start|transpose)\(", text), moved
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool == 65 * (16384 * 5120 + 3276800)
    assert "tpu_custom_call" in text  # the chip's grouped-matmul kernel
    return mem


@pytest.mark.parametrize("ragged", [True, False], ids=["kernel", "copy"])
def test_mimo_decode_chunk_steps_columns_and_rings_in_place(
        one_chip, monkeypatch, ragged):
    """A fused chunk of 8 decode passes of the seven layers at the widest
    key window, beside 6.86 GB of weights and 5.67 GB of pool; a sliding
    layer reads its block's rings, nothing of the pool moves.  On the paged
    kernel (`ops/windowed_decode.py`, steered here to be lowered and not
    interpreted: the backend is the CPU) no full layer's window is sliced
    or copied out of the pool and no product runs over one: one kernel
    call a full layer and pass reads the columns where they lie.  On the
    copy path (`ragged_attn=False`, a pool the kernel does not read) a full
    layer copies eight slots' windows at a time (64 slots' are 2.7 GB a
    layer)."""
    from areal_tpu.models import windowed
    from areal_tpu.ops import windowed_decode

    monkeypatch.setattr(windowed_decode, "_interpret_mode", lambda _: False)
    cfg, params, cache = _mimo_shapes(one_chip)
    B = MIMO_SLOTS - 1

    def chunk(params, cache, tokens, lengths, active):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache, counts = windowed.forward_decode(
                params, cfg, tok, ln, cache, key_window=MIMO_LEN,
                slot_base=0, active=active, ragged=ragged)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tok, ln + 1), (tok, counts)

        (cache, _, _), out = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return out, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_)).compile()
    mem = _mimo_pool_and_weights_stay_where_they_are(compiled, params, cache)
    text = compiled.as_text()
    # a window of eight slots, never of all sixty-four; the scores over it
    windows = [
        len(re.findall(rf"= {re.escape(shape)}\S* [a-z\-]+\(", text))
        for shape in (f"bf16[8,{MIMO_LEN},768]", f"bf16[8,{MIMO_LEN},512]",
                      f"f32[8,{cfg.num_heads},1,{MIMO_LEN}]")
    ]
    kernels = len(re.findall(
        r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*windowed_decode", text))
    assert not re.search(rf"bf16\[{B},{MIMO_LEN},768\]", text)
    # temporaries: 22 / 18 MB (612 / 738 while the layers' projections were
    # copied out of their stacks in every pass)
    assert mem.temp_size_in_bytes < 1 << 26
    if ragged:
        assert windows == [0, 0, 0] and kernels == 2  # the full layers
    else:
        assert min(windows) >= 1 and kernels == 0


def test_mimo_suffix_with_the_fan_out_copy_fits(one_chip):
    """Eight rows of one token behind a copy of 16,384 columns and the
    rings: the rows' windows sliced out once and attended as one batch."""
    from areal_tpu.models.transformer import forward_prefill_cached

    cfg, params, cache = _mimo_shapes(one_chip)
    rows = _shape(one_chip, (8,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, st, n, slots, src: forward_prefill_cached(
            p, cfg, ids, st, n, c, slots, copy_src=src, copy_block=MIMO_LEN,
            key_window=MIMO_LEN),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (8, 128), jnp.int32), rows, rows,
            rows, rows).compile()
    mem = _mimo_pool_and_weights_stay_where_they_are(compiled, params, cache)
    assert mem.temp_size_in_bytes < 9 << 28  # 1.84 GB when written


def test_mimo_fresh_prefill_of_a_whole_row_fits(one_chip, monkeypatch):
    """The largest fresh prefill one dispatch takes (one row of 16,384
    tokens), as the chip runs it: both attention kinds through the splash
    kernel (steered here: the backend is the CPU) with a query and key 192
    wide beside a value of 128, sixteen or eight queries a kv head, the
    sliding layers under a local mask with their sinks; the dense FFN a
    block of tokens at a time.  That it compiles says it fits beside
    weights and pool (2.97 GB under pressure, 4.88 GB when left room)."""
    from areal_tpu.models import windowed
    from areal_tpu.models.transformer import forward_prefill

    monkeypatch.setattr(windowed, "_splash_applies", lambda T: T >= 256)
    cfg, params, cache = _mimo_shapes(one_chip)
    rows = _shape(one_chip, (1,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, n, slots: forward_prefill(p, cfg, ids, n, c, slots),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (1, MIMO_LEN), jnp.int32), rows,
            rows).compile()
    text = compiled.as_text()
    assert text.count("splash") >= 7  # a kernel a layer
    assert not _layer_copies(text, _mimo_projections(params))
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool


# ---------------------------------------------------------------------------
# the hybrid stack of `rollout_ssm_dense_4k` (jamba2-3b, whole) at its real
# size: 384 slots + the scratch row x 4096, 26 Mamba-1 and 2 attention layers
# ---------------------------------------------------------------------------

JAMBA_SLOTS, JAMBA_LEN = 385, 4096


def _jamba_shapes(one_chip):
    import os

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import init_kv_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TransformerConfig.from_hf(os.path.join(
        repo, "benchmarks/configs/jamba2-3b.json")).replace(
        dtype="bfloat16", param_dtype="bfloat16", remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, JAMBA_SLOTS, JAMBA_LEN, "bfloat16")))
    return cfg, params, cache


def _no_copy_of_the_state_or_the_columns(compiled, cache):
    """The pool is aliased; the float32 state (3.28 GB), one layer's slab of
    it, and the two attention layers' columns (0.8 GB a leaf: ONE scatter
    over both layers had them laid out anew and copied in and out of every
    decode pass) stay where they are.  The window leaf `c` is re-laid once a
    program (0.3 GB: the compiler's own layout for a second-minor axis of
    3), as nemotron_h's is."""
    text = compiled.as_text()
    S, M = JAMBA_SLOTS, JAMBA_LEN
    for moved in (f"f32[26,{S},16,5120]", f"f32[{S},16,5120]",
                  f"f32[1,{S},16,5120]", f"bf16[2,{S},{M},1,128]",
                  f"bf16[1,{S},{M},1,128]", f"bf16[{S},{M},1,128]"):
        assert not re.search(
            rf"= {re.escape(moved)}\S* (copy|copy-start)\(", text), moved
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    return mem


@pytest.mark.parametrize("ragged", [True, False], ids=["kernel", "slices"])
def test_jamba_decode_chunk_steps_the_pool_in_place(
        one_chip, monkeypatch, ragged):
    """A fused chunk of 8 decode passes of the whole model (56 blocks, of
    which the program holds the runs' periods once: it compiles in seconds)
    at the widest key window, beside 6.06 GB of weights and 5.20 GB of pool.
    On the state kernel (`ops/mamba1_decode.py`, steered here to be lowered
    and not interpreted: the backend is the CPU) each of the three runs'
    scans holds ONE call of it, under `layers/.../ssm/ssm_scan` where the
    benchmark's metrics look for it, the state leaf is its operand and its
    result, and no block of states exists as a value of its own: none is
    sliced out, stepped by a fusion or read a second time.  On
    `selective_step` (`ragged_attn=False`) the block is."""
    from areal_tpu.models.transformer import forward_decode_hybrid
    from areal_tpu.ops import mamba1_decode

    monkeypatch.setattr(mamba1_decode, "_interpret_mode", lambda _: False)
    cfg, params, cache = _jamba_shapes(one_chip)
    B = JAMBA_SLOTS

    def chunk(params, cache, tokens, lengths, active):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache, _ = forward_decode_hybrid(
                params, cfg, tok, ln, cache, key_window=JAMBA_LEN,
                slot_base=0, active=active, ragged=ragged)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tok, ln + 1), tok

        (cache, _, _), out = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return out, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_)).compile()
    mem = _no_copy_of_the_state_or_the_columns(compiled, cache)
    assert mem.temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    # three runs and four blocks of their own, not 56 blocks: the scan over
    # the layers is there, inside the scan over the passes
    assert text.count("while(") >= 4
    kernels = re.findall(
        r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*mamba1_decode[^\n]*", text)
    # the block's states as a value of their own: sliced out, or stepped
    blocks = re.findall(rf"= f32\[(?:1,)?{B},16,5120\]", text)
    if ragged:
        assert len(kernels) == 3 and not blocks
        for call in kernels:
            where = re.search(r'op_name="([^"]*)"', call).group(1)
            assert re.search(r"/layers/.*/ssm/ssm_scan/mamba1_decode", where)
    else:
        assert not kernels and blocks


@pytest.mark.parametrize("rows, P", [(2, 2048), (32, 128)])
def test_jamba_fresh_prefill_fits_beside_weights_and_pool(one_chip, rows, P):
    """The largest fresh prefills one dispatch takes (4,096 padded tokens:
    `ops/mamba1.py admit_tokens`): the sequence form carries the state, so
    no [T, 16, 5120] array of a prompt exists."""
    from areal_tpu.models.transformer import forward_prefill

    cfg, params, cache = _jamba_shapes(one_chip)
    r = _shape(one_chip, (rows,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, n, slots: forward_prefill(p, cfg, ids, n, c, slots),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (rows, P), jnp.int32), r,
            r).compile()
    mem = _no_copy_of_the_state_or_the_columns(compiled, cache)
    assert mem.temp_size_in_bytes < 3 << 30
    assert f"f32[{rows},{P},16,5120]" not in compiled.as_text()


def test_jamba_suffix_prefill_with_the_fan_out_copy_fits(one_chip):
    """32 siblings' last prompt token after the copy of a 2,048-column
    prefix, each continuing from its representative's state and window."""
    from areal_tpu.models.transformer import forward_prefill_cached

    cfg, params, cache = _jamba_shapes(one_chip)
    rows = _shape(one_chip, (32,), jnp.int32)
    compiled = jax.jit(
        lambda p, c, ids, st, n, slots, src: forward_prefill_cached(
            p, cfg, ids, st, n, c, slots, copy_src=src, copy_block=2048,
            key_window=2048),
        donate_argnums=(1,),
    ).lower(params, cache, _shape(one_chip, (32, 128), jnp.int32), rows, rows,
            rows, rows).compile()
    mem = _no_copy_of_the_state_or_the_columns(compiled, cache)
    assert mem.temp_size_in_bytes < 3 << 30


# ---------------------------------------------------------------------------
# power retention of `rollout_retention` (brumby-14b as the benchmark cuts
# it) at its real size: 16 slots + the scratch row of [8256, 128] states
# ---------------------------------------------------------------------------

RETENTION_SLOTS = 17


@pytest.mark.parametrize("ragged", [True, False], ids=["kernel", "slices"])
def test_retention_decode_chunk_steps_the_pool_in_place(
        one_chip, monkeypatch, ragged):
    """A fused chunk of 8 decode passes of the eight layers beside 6.84 GB
    of weights and 4.79 GB of float32 state.  On the state kernel
    (`ops/retention_decode.py`, steered here to be lowered and not
    interpreted: the backend is the CPU) the layer scan's body holds ONE
    call of it, the pool is its operand and its result (aliased: no block
    of states is sliced out of the pool or written back into it), and no
    product reads the states a second time.  On `retention_step`
    (`ragged_attn=False`) the block is sliced, read by the read-out's
    product and by the update, and written back."""
    import os

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import TransformerConfig
    from areal_tpu.models.transformer import forward_decode, init_kv_cache
    from areal_tpu.ops import retention_decode

    monkeypatch.setattr(retention_decode, "_interpret_mode", lambda _: False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = TransformerConfig.from_hf(os.path.join(
        repo, "benchmarks/configs/brumby-14b.json")).replace(
        dtype="bfloat16", param_dtype="bfloat16", remat=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, RETENTION_SLOTS, 1024, "bfloat16")))
    B = RETENTION_SLOTS - 1

    def chunk(params, cache, tokens, lengths, active):
        def step(carry, _):
            cache, tok, ln = carry
            logits, cache = forward_decode(
                params, cfg, tok, ln, cache, slot_base=0, active=active,
                ragged=ragged)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            return (cache, tok, ln + 1), tok

        (cache, _, _), out = jax.lax.scan(
            step, (cache, tokens, lengths), None, length=8)
        return out, cache

    i32 = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, cache, i32, i32, _shape(one_chip, (B,), jnp.bool_)).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= pool == 8 * 17 * 8 * 8256 * 129 * 4
    state = re.escape(f"f32[8,{RETENTION_SLOTS},8,8256,128]")
    assert not re.search(rf"= {state}\S* (copy|copy-start)\(", text)
    # the normaliser's leaf keeps its layout through both scans: re-laid
    # ({3,0,2,1}, which one feature map over [q; k] brought about) its
    # update is a strided copy, 8 % of the device's time in the cell
    assert not re.search(
        rf"f32\[8,{RETENTION_SLOTS},8,8256\]\{{(?!3,2,1,0)", text)
    kernels = re.findall(
        r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*retention_decode", text)
    # the block's states as a value of their own: sliced out, or stepped
    blocks = re.findall(rf"= f32\[(?:1,)?{B},8,8256,128\]", text)
    # the read-out's product over the states (a convolution on the chip)
    second_reads = re.findall(r"bkgf,bkfd->bkgd", text)
    if ragged:
        assert len(kernels) == 1 and not blocks and not second_reads
    else:
        assert not kernels and blocks and second_reads
    # 0.60 / 0.59 GB when written: the projections' weights laid out anew
    # once a chunk (0.5 GB), nothing of the state
    assert mem.temp_size_in_bytes < 1 << 30


def test_a_train_process_loads_nothing_of_the_state_kernel():
    """What a train cell imports: the model and the train engine, in a
    fresh interpreter.  The state kernels' modules are loaded where the
    decode branch is traced and by the generation engine, never by these."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import areal_tpu.models.transformer, areal_tpu.engine.jax_train\n"
        "bad = [m for m in sys.modules\n"
        "       if m.endswith(('retention_decode', 'mamba1_decode'))]\n"
        "assert not bad, bad\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=repo,
    )
    assert done.returncode == 0, done.stderr[-2000:]
