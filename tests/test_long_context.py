"""Long-context discipline tests (VERDICT round-1 next-step #5).

- Train-side length bucketing must bound the number of compiled programs:
  arbitrary batch lengths land in power-of-two-of-quantum buckets, so a
  32k-max run compiles O(log) step programs, not one per length.
- The generation engine must serve a 32k-token cache at tiny hidden size
  (the capability the reference gets from SGLang's 32k serving; long-context
  speed on the chip is the `train_16k` cell's to state, PERF.md).
"""

import numpy as np

from areal_tpu.api.config import (
    MeshConfig,
    MicroBatchSpec,
    OptimizerConfig,
    TrainEngineConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec
from areal_tpu.engine.jax_train import JaxTrainEngine
from areal_tpu.models.model_config import tiny_config
from areal_tpu.ops import sft_loss_fn
from areal_tpu.utils.datapack import round_up_to_bucket


def test_bucket_ladder_is_logarithmic():
    quantum, max_len = 512, 32768
    buckets = {round_up_to_bucket(n, quantum, max_len) for n in range(1, max_len + 1, 97)}
    assert buckets == {512, 1024, 2048, 4096, 8192, 16384, 32768}


def _batch(rng, n_seqs, max_len):
    lens = rng.integers(max_len // 4, max_len, n_seqs)
    L = int(lens.max())
    am = np.zeros((n_seqs, L), bool)
    for i, n in enumerate(lens):
        am[i, :n] = True
    ids = rng.integers(0, 128, (n_seqs, L)).astype(np.int32) * am
    return {
        "input_ids": ids,
        "attention_mask": am,
        "loss_mask": am.astype(np.float32),
    }


def test_no_recompilation_storm_across_batch_lengths():
    """Twelve batches of random lengths must reuse a handful of compiled
    step programs (cache keyed on bucketed row_len)."""
    eng = JaxTrainEngine(
        TrainEngineConfig(
            experiment_name="lc", trial_name="t", init_from_scratch=True,
            dtype="float32", param_dtype="float32",
            gradient_checkpointing=False, mesh=MeshConfig(),
            mb_spec=MicroBatchSpec(n_mbs=1),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            pack_length_quantum=64, max_pack_length=1024,
        ),
        model_config=tiny_config(vocab_size=128),
    )
    eng.initialize(ft_spec=FinetuneSpec(1, 64, 4))
    rng = np.random.default_rng(0)
    for _ in range(12):
        n_seqs = int(rng.integers(2, 6))
        eng.train_batch(
            _batch(rng, n_seqs, int(rng.integers(40, 900))),
            sft_loss_fn,
            lambda b: float(np.sum(b["loss_mask"])),
        )
    # buckets possible: 64,128,256,512,1024 (x row-count variations is
    # absorbed by rows_multiple padding) — well under one-per-batch
    assert len(eng._train_step_cache) <= 5, len(eng._train_step_cache)


def test_gen_engine_32k_cache():
    """A 32k-slot KV cache serves and respects the length stop at tiny
    hidden size; prompt buckets stay power-of-two."""
    import jax

    from areal_tpu.gen.engine import GenEngine, GenRequest
    from areal_tpu.models import init_params

    cfg = tiny_config(
        vocab_size=64, hidden_size=16, intermediate_size=32, num_layers=1,
        num_heads=2, num_kv_heads=1, max_position_embeddings=32768,
        eos_token_id=None,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = GenEngine(cfg, params=params, n_slots=2, max_seq_len=32768,
                       prompt_bucket=256, decode_chunk=8)
    rng = np.random.default_rng(0)
    # a ~31.5k prompt (the reference benchmark's generation regime is 31k
    # of 32k ctx) with a short completion budget
    long_prompt = rng.integers(0, 64, 31500).tolist()
    req = GenRequest(rid="long", input_ids=long_prompt, max_new_tokens=8,
                     temperature=0.0)
    engine.generate_blocking([req])
    assert len(req.output_tokens) == 8
    assert req.stop_reason == "length"
    # and a request that would overflow the cache is rejected up front
    too_long = GenRequest(rid="over", input_ids=rng.integers(0, 64, 32768).tolist(),
                          max_new_tokens=8)
    engine.submit(too_long)
    assert too_long.stop_reason == "length"
