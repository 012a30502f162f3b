"""JaxTrainEngine on an 8-virtual-device CPU mesh.

Ports the reference's engine test strategy (areal/tests/test_train_engine.py,
test_fsdp_engine_nccl.py, torchrun/run_fsdp_ulysses_forward.py): training
reduces the loss, forward logprobs match an unsharded reference, and results
are invariant to the mesh layout (dp/fsdp/tp/sp splits)."""

import functools

import numpy as np
import pytest

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec, SaveLoadMeta
from areal_tpu.engine.jax_train import JaxTrainEngine
from areal_tpu.models.model_config import tiny_config
from areal_tpu.ops import sft_loss_fn
from areal_tpu.utils.data import pack_into_rows, unpack_rows


MODEL_CFG = tiny_config(vocab_size=128, qkv_bias=True, hf_architecture="Qwen2ForCausalLM")


def _engine(mesh: MeshConfig, n_mbs: int = 1, lr: float = 1e-2) -> JaxTrainEngine:
    cfg = TrainEngineConfig(
        experiment_name="t",
        trial_name="t",
        init_from_scratch=True,
        dtype="float32",
        gradient_checkpointing=False,
        mesh=mesh,
        mb_spec=MicroBatchSpec(n_mbs=n_mbs),
        optimizer=OptimizerConfig(lr=lr, warmup_steps_proportion=0.0, weight_decay=0.0),
        pack_length_quantum=16,
    )
    eng = JaxTrainEngine(cfg, model_config=MODEL_CFG)
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 8))
    return eng


def _batch(rng, B=8, L=12):
    lens = rng.integers(4, L + 1, B)
    mask = np.arange(L)[None, :] < lens[:, None]
    ids = rng.integers(0, MODEL_CFG.vocab_size, (B, L)) * mask
    loss_mask = mask.copy()
    # exclude each sequence's last valid token (no next-token target)
    loss_mask[np.arange(B), lens - 1] = False
    return {
        "input_ids": ids.astype(np.int32),
        "attention_mask": mask,
        "loss_mask": loss_mask.astype(np.float32),
    }


def _weight(batch):
    return float(np.sum(batch["loss_mask"]))


def test_row_packing_roundtrip():
    rng = np.random.default_rng(0)
    b = _batch(rng)
    rp = pack_into_rows(b, row_len=16, rows_multiple=4)
    assert rp.data["input_ids"].shape[0] % 4 == 0
    # every sequence's tokens appear exactly once
    out = unpack_rows(rp, rp.data["input_ids"], 8, 12)
    np.testing.assert_array_equal(out * b["attention_mask"], b["input_ids"])


def test_train_loss_decreases():
    rng = np.random.default_rng(1)
    eng = _engine(MeshConfig(data_parallel_size=2, fsdp_parallel_size=2,
                             tensor_parallel_size=2))
    batch = _batch(rng)
    losses = []
    for _ in range(8):
        stats = eng.train_batch(batch, sft_loss_fn, _weight)
        losses.append(stats["loss"])
    assert losses[-1] < losses[0] * 0.7, losses
    assert stats["grad_norm"] > 0
    assert stats["lr"] > 0


def test_train_loss_decreases_gpt2_and_gemma2():
    """The trainer's grad path covers the non-llama structures: gpt2
    (LayerNorm biases, learned positions, non-gated MLP) and gemma2
    (sandwich norms, softcaps -> chunked head fallback) on a sharded mesh."""
    for kw in (
        dict(hf_architecture="GPT2LMHeadModel", norm_type="layernorm",
             pos_emb="learned", mlp_gated=False, qkv_bias=True,
             attn_output_bias=True, mlp_bias=True, num_kv_heads=4,
             hidden_act="gelu_pytorch_tanh", tie_word_embeddings=True),
        dict(hf_architecture="Gemma2ForCausalLM", sandwich_norms=True,
             norm_unit_offset=True, scale_embeddings=True,
             hidden_act="gelu_pytorch_tanh", attn_logit_softcap=50.0,
             final_logit_softcap=30.0, sliding_window=8,
             layer_is_sliding=(True, False), tie_word_embeddings=True),
    ):
        mc = tiny_config(vocab_size=128, **kw)
        cfg = TrainEngineConfig(
            experiment_name="t", trial_name="t", init_from_scratch=True,
            dtype="float32", gradient_checkpointing=False,
            mesh=MeshConfig(data_parallel_size=2, fsdp_parallel_size=2,
                            tensor_parallel_size=2),
            mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0,
                                      weight_decay=0.0),
            pack_length_quantum=16,
        )
        eng = JaxTrainEngine(cfg, model_config=mc)
        eng.initialize(ft_spec=FinetuneSpec(1, 64, 8))
        rng = np.random.default_rng(2)
        batch = _batch(rng)
        losses = [
            eng.train_batch(batch, sft_loss_fn, _weight)["loss"]
            for _ in range(8)
        ]
        assert losses[-1] < losses[0] * 0.7, (kw["hf_architecture"], losses)
        eng.destroy()


def test_train_step_ring_attention_matches_naive():
    """attn_impl=ring (K/V sequence-sharded, rotating blocks) reproduces the
    naive-attention loss through the full train step on a dp2 x sp2 x tp2
    mesh — context parallelism as a drop-in numerics-preserving switch."""
    losses = {}
    for impl in ("naive", "ring"):
        mc = tiny_config(vocab_size=128, qkv_bias=True,
                         hf_architecture="Qwen2ForCausalLM", attn_impl=impl)
        cfg = TrainEngineConfig(
            experiment_name="t", trial_name="t", init_from_scratch=True,
            dtype="float32", gradient_checkpointing=True,
            mesh=MeshConfig(data_parallel_size=2, sequence_parallel_size=2,
                            tensor_parallel_size=2),
            mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0,
                                      weight_decay=0.0),
            pack_length_quantum=16,
        )
        eng = JaxTrainEngine(cfg, model_config=mc)
        eng.initialize(ft_spec=FinetuneSpec(1, 64, 8))
        rng = np.random.default_rng(3)
        batch = _batch(rng)
        losses[impl] = [
            eng.train_batch(batch, sft_loss_fn, _weight)["loss"]
            for _ in range(2)
        ]
        eng.destroy()
    np.testing.assert_allclose(losses["ring"], losses["naive"], rtol=2e-4)


def test_forward_matches_unsharded():
    rng = np.random.default_rng(2)
    batch = _batch(rng)
    ref_eng = _engine(MeshConfig())
    ref = ref_eng.forward(batch)
    for mesh in (
        MeshConfig(data_parallel_size=2, fsdp_parallel_size=2, tensor_parallel_size=2),
        MeshConfig(fsdp_parallel_size=2, sequence_parallel_size=2,
                   tensor_parallel_size=2),
        MeshConfig(data_parallel_size=8),
    ):
        eng = _engine(mesh)
        got = eng.forward(batch)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_train_invariant_to_microbatching():
    """Global loss-weight normalisation: the update must not depend on the
    micro-batch split (reference invariant of fsdp_engine.py:499-606)."""
    rng = np.random.default_rng(3)
    batch = _batch(rng)
    stats1 = _engine(MeshConfig(), n_mbs=1).train_batch(batch, sft_loss_fn, _weight)
    stats4 = _engine(MeshConfig(), n_mbs=4).train_batch(batch, sft_loss_fn, _weight)
    np.testing.assert_allclose(stats1["loss"], stats4["loss"], rtol=1e-4)
    np.testing.assert_allclose(stats1["grad_norm"], stats4["grad_norm"], rtol=1e-3)


def test_train_invariant_to_mesh():
    rng = np.random.default_rng(4)
    batch = _batch(rng)

    def run(mesh):
        eng = _engine(mesh)
        for _ in range(3):
            stats = eng.train_batch(batch, sft_loss_fn, _weight)
        return stats, eng.forward(batch)

    stats_ref, logp_ref = run(MeshConfig())
    stats_dist, logp_dist = run(
        MeshConfig(data_parallel_size=2, fsdp_parallel_size=2, tensor_parallel_size=2)
    )
    np.testing.assert_allclose(stats_dist["loss"], stats_ref["loss"], rtol=1e-3)
    np.testing.assert_allclose(logp_dist, logp_ref, rtol=2e-3, atol=2e-3)


def test_eval_batch_and_version():
    rng = np.random.default_rng(5)
    eng = _engine(MeshConfig())
    batch = _batch(rng)
    out = eng.eval_batch(batch, sft_loss_fn, _weight)
    assert out["loss"] > 0
    eng.set_version(3)
    assert eng.get_version() == 3


def test_save_load_roundtrip(tmp_path):
    rng = np.random.default_rng(6)
    eng = _engine(MeshConfig(fsdp_parallel_size=2))
    batch = _batch(rng)
    eng.train_batch(batch, sft_loss_fn, _weight)
    logp_before = eng.forward(batch)
    eng.save(SaveLoadMeta(path=str(tmp_path / "ck"), with_optim=True))

    eng2 = _engine(MeshConfig(fsdp_parallel_size=2))
    eng2.load(SaveLoadMeta(path=str(tmp_path / "ck"), with_optim=True))
    logp_after = eng2.forward(batch)
    np.testing.assert_allclose(logp_after, logp_before, rtol=1e-4, atol=1e-4)
    assert eng2.step_count == eng.step_count
    # loaded engine keeps training identically to the original
    s1 = eng.train_batch(batch, sft_loss_fn, _weight)
    s2 = eng2.train_batch(batch, sft_loss_fn, _weight)
    np.testing.assert_allclose(s1["loss"], s2["loss"], rtol=1e-4)


def test_async_stats_pipeline_matches_sync():
    """async_stats defers the fetch; numbers must equal the sync path and
    the tracker commit must happen at materialisation, not dispatch."""
    from areal_tpu.utils import stats as stats_mod

    rng = np.random.default_rng(7)
    batch = _batch(rng)
    mesh = MeshConfig(data_parallel_size=2, fsdp_parallel_size=2,
                      tensor_parallel_size=2)

    sync_eng = _engine(mesh)
    async_eng = _engine(mesh)
    async_eng.config.async_stats = True

    sync_losses, pendings = [], []
    for _ in range(4):
        sync_losses.append(sync_eng.train_batch(batch, sft_loss_fn, _weight)["loss"])
        pendings.append(async_eng.train_batch(batch, sft_loss_fn, _weight))
    for p in pendings:
        assert isinstance(p, stats_mod.PendingTrainStats)
        assert p._result is None  # not yet materialised
    async_losses = [p["loss"] for p in pendings]  # read -> materialise
    np.testing.assert_allclose(async_losses, sync_losses, rtol=1e-5)
    # async mode omits per-step wall-clock-derived keys (no sync point)
    assert "step_time" not in pendings[0].materialize()
    assert pendings[0]["total_loss_weight"] == _weight(batch)
    # finalizers registered via .then run once, at materialisation
    seen = []
    p = async_eng.train_batch(batch, sft_loss_fn, _weight)
    p.then(lambda st: (seen.append(True), st)[1])
    assert not seen
    _ = p["loss"]
    assert seen == [True]


def test_learned_pos_clamp_applies_on_checkpoint_route(tmp_path):
    """The common route — gpt2 checkpoint given via cfg.path with
    model_config=None — only learns pos_emb=='learned' from the loaded
    config, so the max_pack_length clamp must run after the checkpoint
    resolves (r4 advisor: the guard previously ran before load_hf_params
    and silently skipped, training overflow positions on the last wpe row)."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.hf import save_hf_checkpoint

    mc = tiny_config(
        vocab_size=128, hf_architecture="GPT2LMHeadModel",
        norm_type="layernorm", pos_emb="learned", mlp_gated=False,
        qkv_bias=True, attn_output_bias=True, mlp_bias=True, num_kv_heads=4,
        hidden_act="gelu_pytorch_tanh", tie_word_embeddings=True,
        max_position_embeddings=32,
    )
    ckpt = tmp_path / "gpt2"
    save_hf_checkpoint(init_params(mc, jax.random.PRNGKey(0)), mc, str(ckpt),
                       save_dtype="float32")
    cfg = TrainEngineConfig(
        experiment_name="t", trial_name="t", path=str(ckpt),
        dtype="float32", gradient_checkpointing=False,
        mesh=MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=1e-2, warmup_steps_proportion=0.0,
                                  weight_decay=0.0),
        pack_length_quantum=16, max_pack_length=4096,
    )
    eng = JaxTrainEngine(cfg, model_config=None)
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 8))
    assert eng.config.max_pack_length == 32
    eng.destroy()


def test_train_stats_count_attention_blocks(monkeypatch):
    """`attn_blocks_run` / `attn_blocks_causal` in the stats `train_batch`
    returns: one packed row of 640 (five blocks of 128) holding sequences of
    300 and 250 runs 11 of its 15 causal blocks (a layer, a kv head); a
    forward that does not take the splash kernel reports neither."""
    from areal_tpu.ops import attention as attn_mod

    def engine_and_batch():
        cfg = TrainEngineConfig(
            experiment_name="t", trial_name="t", init_from_scratch=True,
            dtype="float32", gradient_checkpointing=False, mesh=MeshConfig(),
            mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            pack_length_quantum=640, max_pack_length=640,
        )
        eng = JaxTrainEngine(cfg, model_config=tiny_config(
            vocab_size=128, hidden_size=256, num_heads=2, num_kv_heads=1,
            num_layers=1, max_position_embeddings=1024,
        ))
        eng.initialize(ft_spec=FinetuneSpec(1, 64, 8))
        lens = np.array([300, 250])
        mask = np.arange(300)[None, :] < lens[:, None]
        rng = np.random.default_rng(0)
        return eng, {
            "input_ids": (rng.integers(0, 128, mask.shape) * mask).astype(np.int32),
            "attention_mask": mask,
            "loss_mask": mask.astype(np.float32),
        }

    eng, batch = engine_and_batch()  # CPU: the einsum path
    stats = eng.train_batch(batch, sft_loss_fn, _weight)
    assert "attn_blocks_run" not in stats and "attn_blocks_causal" not in stats
    monkeypatch.setattr(attn_mod, "INTERPRET", True)
    eng, batch = engine_and_batch()
    stats = eng.train_batch(batch, sft_loss_fn, _weight)
    assert eng.attention_impls()[(640, 2, 1, 128)] == "splash"
    assert (stats["attn_blocks_run"], stats["attn_blocks_causal"]) == (11.0, 15.0)
    assert np.isfinite(stats["loss"]) and stats["grad_norm"] > 0
