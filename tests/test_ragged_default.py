"""What `GenEngine(ragged_attn=None)` resolves to.

Nobody said: the engine takes the paged attention kernel wherever it
applies, from what it can observe at construction (what a slot holds, the
window against the kernel's VMEM gate, the cache dtype, the backend), and
the copy path elsewhere, without raising.  `True` still requires the
kernel, `False` is still the copy path, and the streams are the same
either way.  The launcher's flag follows: unset, `--ragged-attn`,
`--no-ragged-attn` (the server's own parser: `tests/test_chip_smoke.py`
starts it both ways).
"""

import jax
import numpy as np
import pytest

from areal_tpu.api.config import GenServerConfig
from areal_tpu.engine.colocated import ColocatedEngine
from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.models import init_params
from areal_tpu.models.model_config import tiny_config


def _dense():
    cfg = tiny_config(vocab_size=97, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _retention():
    from tests.test_retention_engine import CFG

    return CFG, init_params(CFG, jax.random.PRNGKey(0)), {}


def _hybrid():
    from tests.test_hybrid_model import CFG, _params

    return CFG, _params(), {"kv_dtype": "float32"}


def _past_the_gate():
    # the loop's attention widths (8 kv heads of 128) at `rollout_long`'s
    # window: 2 x 16,384 x 8 x 128 x 2 B = 67 MB of scratch against 8 MB
    cfg = tiny_config(vocab_size=64, hidden_size=1024, intermediate_size=64,
                      num_layers=1, num_heads=8, num_kv_heads=8,
                      max_position_embeddings=16384, eos_token_id=None,
                      dtype="bfloat16", param_dtype="bfloat16")
    assert cfg.head_dim_ == 128
    return (cfg, init_params(cfg, jax.random.PRNGKey(0)),
            {"n_slots": 1, "max_seq_len": 16384, "prompt_bucket": 128,
             "kv_dtype": "bfloat16"})


def _build(cfg, params, **kw):
    kw = {"n_slots": 4, "max_seq_len": 128, "prompt_bucket": 16, "seed": 3,
          "decode_chunk": 4, **kw}
    return GenEngine(cfg, params=params, **kw)


def test_nobody_said_takes_the_kernel_for_a_dense_model_under_the_gate():
    cfg, params = _dense()
    eng = _build(cfg, params, kv_dtype="float32")
    assert eng.ragged_attn and eng._ragged_ok
    reqs = [GenRequest(rid="a", input_ids=list(range(5, 25)),
                       max_new_tokens=6, temperature=1.0)]
    eng.generate_blocking(reqs)
    assert eng.stats["ragged_dispatches"] > 0
    assert eng.stats["ragged_dispatches"] == (
        eng.stats["decode_calls"] + eng.stats["verify_calls"])


def test_nobody_said_takes_the_state_kernel_for_a_state_alone():
    """Power retention (`brumby-14b`'s kind): a slot holds a state and
    nothing else, and the argument left out or `True` resolves to the
    kernel that steps the pool in place (`ops/retention_decode.py`);
    `False` keeps `retention_step`."""
    cfg, params, kw = _retention()
    for said in (None, True):
        extra = {} if said is None else {"ragged_attn": said}
        eng = _build(cfg, params, **kw, **extra)
        assert eng.ragged_attn and eng._ragged_ok
        del eng
    eng = _build(cfg, params, **kw, ragged_attn=False)
    assert not eng.ragged_attn and not eng._ragged_ok


@pytest.mark.parametrize("kind", [_hybrid, _past_the_gate],
                         ids=["hybrid", "past_the_gate"])
def test_nobody_said_takes_the_copy_path_where_the_kernel_does_not_apply(kind):
    """A hybrid stack (a state beside K/V columns) and a window past the
    VMEM gate build with the argument left out and with `False`, on the
    path they had; `True` is refused, by name."""
    cfg, params, kw = kind()
    for said in (None, False):
        extra = {} if said is None else {"ragged_attn": said}
        eng = _build(cfg, params, **kw, **extra)
        assert not eng.ragged_attn and not eng._ragged_ok
        del eng
    with pytest.raises(ValueError, match="ragged_attn"):
        _build(cfg, params, **kw, ragged_attn=True)


def test_a_cache_dtype_the_kernel_cannot_split_takes_the_copy_path():
    cfg, params = _dense()
    eng = _build(cfg, params, kv_dtype="float8_e4m3fn")
    assert not eng._ragged_ok
    with pytest.raises(ValueError, match="ragged_attn requested"):
        _build(cfg, params, kv_dtype="float8_e4m3fn", ragged_attn=True)


def test_heads_the_tpu_s_compiler_does_not_tile_take_the_copy_path(
        monkeypatch):
    """Where the kernel is lowered and not interpreted (a TPU) it takes
    heads of 128, a power of two of them a shard, two or more of a 16-bit
    cache (what compiled for a described v5e and what did not: Qwen2.5-0.5B
    has heads of 64, Qwen2.5-1.5B under tp=2 one 16-bit head a shard).
    The interpreter takes any widths, which is what the CPU suite runs."""
    from areal_tpu.ops import ragged_decode
    from areal_tpu.ops.ragged_decode import kernel_refusal

    assert kernel_refusal(128, 2, 16, 4) == ""  # interpreted: this suite's
    assert "not 3 kv head(s)" in kernel_refusal(128, 3, 128, 2)
    assert "of 1 byte(s)" in kernel_refusal(128, 2, 128, 1)
    assert "VMEM budget" in kernel_refusal(16384, 8, 128, 2)
    monkeypatch.setattr(ragged_decode, "_interpret_mode", lambda _: False)
    for heads in ((8, 128, 2), (2, 128, 2), (16, 128, 2), (1, 128, 4),
                  (8, 128, 4), (8, 128, 2, 4)):
        assert kernel_refusal(1024, *heads) == "", heads
    for heads in ((2, 64, 2), (8, 256, 2), (1, 128, 2), (2, 128, 2, 2),
                  (6, 128, 2), (3, 128, 4), (4, 64, 4)):
        assert "does not tile" in kernel_refusal(1024, *heads), heads
    cfg, params = _dense()  # heads of 16
    assert not _build(cfg, params, kv_dtype="float32")._ragged_ok
    with pytest.raises(ValueError, match="does not tile"):
        _build(cfg, params, kv_dtype="float32", ragged_attn=True)


def test_a_backend_without_the_kernel_takes_the_copy_path(monkeypatch):
    """Neither a TPU nor an explicit CPU run: `None` does not raise."""
    from areal_tpu.ops import ragged_decode

    def no_kernel(_):
        raise RuntimeError("JAX came up on 'gpu' but nobody asked for it")

    monkeypatch.setattr(ragged_decode, "_interpret_mode", no_kernel)
    cfg, params = _dense()
    assert not _build(cfg, params, kv_dtype="float32")._ragged_ok
    with pytest.raises(ValueError, match="nobody asked"):
        _build(cfg, params, kv_dtype="float32", ragged_attn=True)


def test_false_is_still_the_copy_path():
    cfg, params = _dense()
    eng = _build(cfg, params, kv_dtype="float32", ragged_attn=False)
    assert not eng.ragged_attn and not eng._ragged_ok
    eng.generate_blocking([GenRequest(
        rid="a", input_ids=list(range(5, 25)), max_new_tokens=6,
        temperature=1.0)])
    assert eng.stats["ragged_dispatches"] == 0
    assert eng.stats["decode_calls"] > 0


def _loop_engine(cfg, params, **kw):
    """The serving engine as `benchmarks/kinds/loop.py` builds it (the
    cell's rehearsal sizes): no `ragged_attn` argument."""
    return ColocatedEngine(
        cfg.replace(remat=False), params=params, n_slots=8, max_seq_len=128,
        prompt_bucket=16, decode_chunk=8, share_prefix=True, seed=11,
        kv_dtype="float32", **kw)


def _groups(temperature):
    rng = np.random.default_rng(5)
    reqs = []
    for g in range(3):
        prompt = rng.integers(0, 97, 24).tolist()
        for i in range(4):
            reqs.append(GenRequest(
                rid=f"g{g}-{i}", input_ids=list(prompt),
                max_new_tokens=int(rng.integers(8, 40)),
                temperature=temperature, group_id=f"g{g}", group_n=4))
    return reqs


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_the_loop_s_engine_runs_the_kernel_and_its_streams_do_not_change(
        temperature):
    """12 requests in 3 groups over 8 slots, prefix fan-out on, chunks of
    8: every decode dispatch is the kernel's, and tokens and log-probs are
    those of the same run on the copy path."""
    cfg, params = _dense()
    outs = {}
    for said in (None, False):
        extra = {} if said is None else {"ragged_attn": said}
        eng = _loop_engine(cfg, params, **extra).engine
        assert eng._ragged_ok == (said is None)
        reqs = _groups(temperature)
        eng.generate_blocking(reqs)
        outs[said] = [(tuple(r.output_tokens), tuple(r.output_logprobs),
                       r.stop_reason) for r in reqs]
        calls = eng.stats["decode_calls"] + eng.stats["verify_calls"]
        assert calls > 0 and eng.stats["shared_tokens"] > 0
        assert eng.stats["ragged_dispatches"] == (calls if said is None else 0)
    assert outs[None] == outs[False]


@pytest.mark.parametrize("said,flag", [
    (None, None), (True, "--ragged-attn"), (False, "--no-ragged-attn")])
def test_the_launcher_passes_on_what_was_said(said, flag):
    cmd = GenServerConfig.build_cmd(
        GenServerConfig(model_path="/m", ragged_attn=said), "h", 1234)
    assert ("ragged-attn" in cmd) == (flag is not None)
    if flag:
        assert flag in cmd.split()
