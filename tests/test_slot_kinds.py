"""The table of slot kinds (`models/transformer.py SlotKind`) against the
engine that reads it: every option built on what a kind lacks is refused by
its name, the constructor takes only what somebody sets, and the decode
program of each kind carries the counters row the table names.  Toy
configurations on the CPU, one a kind, from the kinds' own test files."""

import ast
import inspect
import os

import jax
import jax.numpy as jnp
import pytest

from areal_tpu.gen.engine import GenEngine
from areal_tpu.models import init_params
from areal_tpu.models.model_config import VisionConfig, tiny_config
from areal_tpu.models.transformer import slot_kind
from tests import (
    test_afmoe_model,
    test_hybrid_model,
    test_longcat_model,
    test_mimo_v2,
)
from tests.test_retention_engine import CFG as STATE_CFG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DENSE_CFG = tiny_config(vocab_size=97, qkv_bias=True,
                        hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
# kind -> (toy configuration, what the refusal says a slot of it holds)
KINDS = {
    "columns": (DENSE_CFG, None),
    "state": (STATE_CFG, "recurrent state"),
    "hybrid": (test_hybrid_model.CFG, "recurrent state"),
    "latent": (test_longcat_model.CFG, "latent rows"),
    "windowed": (test_mimo_v2._cfg(), "ring of the window"),
    "gated_experts": (test_afmoe_model.CFG, "does not generate.*afmoe"),
}
# the option as its message spells it, how a caller turns it on, the
# capability it is built on
OPTIONS = [
    ("model_config", {}, "generate"),
    ("spec_decode", {"spec_decode": True}, "verify"),
    ("host_offload", {"host_offload": True}, "host_tier"),
    ("decode_tiers", {"decode_tiers": 2}, "tiers"),
    ("decode_tiers", {"decode_tier_lens": [64, 128],
                      "decode_tier_slots": [3, 3]}, "tiers"),
    ("tp=2", {"tp": 2}, "tp"),
    ("ep=2", {"ep": 2}, "ep"),
    ("a vision tower", {"vision": VisionConfig()}, "vision"),
]
REFUSALS = [
    pytest.param(kind, option, kw, id=f"{kind}-{'-'.join(kw) or option}")
    for kind, (cfg, _) in KINDS.items()
    for option, kw, capability in OPTIONS
    if capability in slot_kind(cfg).lacks
]


def test_the_table_has_a_row_for_every_kind_and_only_the_dense_lacks_nothing():
    assert {k: slot_kind(cfg).name for k, (cfg, _) in KINDS.items()} == {
        k: k for k in KINDS}
    assert [k for k, (cfg, _) in KINDS.items() if not slot_kind(cfg).lacks] == [
        "columns"]
    assert len(REFUSALS) == 25


@pytest.mark.parametrize("kind,option,kw", REFUSALS)
def test_an_option_built_on_what_the_kind_lacks_is_refused_by_name(
        kind, option, kw):
    """Before any weight is drawn: no parameters are given, none are made."""
    cfg, holds = KINDS[kind]
    kw = dict(kw)
    if "vision" in kw:
        cfg = cfg.replace(vision=kw.pop("vision"))
    with pytest.raises(ValueError, match=f"{option}.*{holds}"):
        GenEngine(cfg, n_slots=6, max_seq_len=128, prompt_bucket=16, **kw)


def test_a_kernel_asked_of_a_pool_it_cannot_step_is_refused_with_its_sentence():
    """`ragged_attn=True` is no static capability of a kind: whether a pool
    has a decode kernel is `SlotKind.kernel_refusal`'s to say, from the
    pool it is shown.  A hybrid stack of Mamba-2 blocks has none (its decay
    is one number a head); the selective scan's float32 leaf has
    (`tests/test_jamba_engine.py` runs it)."""
    cfg = KINDS["hybrid"][0]
    assert "paged_kernel" not in slot_kind(cfg).lacks
    with pytest.raises(
            ValueError, match="ragged_attn requested but no kernel steps a "
            "state with a decay a head"):
        GenEngine(cfg, params=test_hybrid_model._params(), n_slots=6,
                  max_seq_len=128, prompt_bucket=16, ragged_attn=True)


# deployment settings: nobody in the tree spells them, a deployment does
DEPLOYMENT = {
    "devices": "which chips the mesh takes, handed through "
               "ColocatedEngine(**gen_kwargs)",
}
CALLERS = ("areal_tpu", "scripts", "examples", "benchmarks/kinds",
           "chip_smoke.py")


def _keywords_somebody_passes():
    """Every keyword of a call of the engine or of its facade, and of the
    dictionaries such a call is given (`dict(...)`, a literal's string
    keys), in the files that build engines."""
    names = set()
    paths = []
    for entry in CALLERS:
        full = os.path.join(REPO, entry)
        if full.endswith(".py"):
            paths.append(full)
        for root, _, files in os.walk(full):
            paths += [os.path.join(root, f) for f in files if f.endswith(".py")]
    for path in paths:
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                callee = getattr(node.func, "id", getattr(node.func, "attr", ""))
                if callee in ("GenEngine", "ColocatedEngine", "dict"):
                    names |= {k.arg for k in node.keywords if k.arg}
            elif isinstance(node, ast.Dict):
                names |= {k.value for k in node.keys
                          if isinstance(k, ast.Constant) and isinstance(k.value, str)}
    return names


def test_the_constructor_takes_only_what_somebody_sets():
    taken = list(inspect.signature(GenEngine.__init__).parameters)[2:]
    assert len(taken) + 1 == 23  # and the configuration, by position
    passed = _keywords_somebody_passes()
    nobody = [p for p in taken if p not in passed and p not in DEPLOYMENT]
    assert not nobody, (
        f"{nobody}: no entry point, launcher, example, script or benchmark "
        "kind passes these; a value nobody sets is a constant of "
        "gen/engine.py (an attribute, where a test needs another)")
    assert set(DEPLOYMENT) <= set(taken)


@pytest.mark.parametrize(
    "kind", ["columns", "state", "hybrid", "latent", "windowed"])
def test_the_decode_program_carries_the_counters_row_the_table_names(kind):
    """Lowered for the CPU, a grid-wide block and one of fewer slots than
    the kind has counters: two rows (tokens, log-probs) of a block's width,
    and a third of at least the counters' where the kind counts anything."""
    cfg = KINDS[kind][0]
    params = {
        "hybrid": test_hybrid_model._params, "latent": test_longcat_model._params,
        "windowed": lambda: test_mimo_v2._params(cfg),
    }.get(kind, lambda: init_params(cfg, jax.random.PRNGKey(0)))()
    eng = GenEngine(cfg, params=params, n_slots=6, max_seq_len=128,
                    prompt_bucket=16, decode_chunk=4, kv_dtype="float32")
    counters = slot_kind(cfg).counters
    assert eng._pass_counters == counters
    assert set(counters) <= set(eng.stats)
    assert bool(counters) == (kind in ("hybrid", "latent", "windowed"))
    S = eng.n_slots + 1
    zeros = lambda dt: jnp.zeros((S,), dt)  # noqa: E731
    for base, size in ((0, 6), (2, 2)):
        lowered = eng._decode_fn.lower(
            eng.params, eng.cache, zeros(jnp.int32), zeros(jnp.int32),
            zeros(jnp.int32), zeros(jnp.int32), zeros(bool),
            zeros(jnp.float32), zeros(jnp.float32), zeros(jnp.int32),
            eng._decode_key, jnp.arange(S, dtype=jnp.int32),
            4, base, size, 32 if eng.decode_window else 128, eng.ragged_attn,
        )
        rows = 3 if counters else 2
        width = max(size, len(counters))
        assert lowered.out_info[0].shape == (rows, 4, width)
        main = next(l for l in lowered.as_text().splitlines()
                    if "func.func public @main" in l)
        assert f"tensor<{rows}x4x{width}xf32>" in main
