import json
import os
import shutil

import numpy as np
import pytest

from benchmarks.lib import loader


def test_finds_a_cell_s_files_by_name():
    c = loader.load_cell("train_2k")
    assert c["name"] == "train_2k" and c["kind"] == "train"
    hf = loader.load_config(c["config"])
    assert hf["hidden_size"] == 1536 and hf["bench"]["reduced"] == []
    names = [m["name"] for m in loader.load_layer_metrics("train_2k")]
    assert "splash_roofline" in names and "loop_publish_pause_ms" not in names
    assert all(callable(m["read"]) for m in loader.load_layer_metrics("train_2k"))
    with pytest.raises(loader.BenchFileError):
        loader.load_cell("no_such_cell")


def test_a_new_cell_config_metric_and_reader_are_only_new_files(tmp_path):
    """What a later PR does: add files, edit none.  A throw-away benchmark
    directory gets a cell, a configuration, a per-layer metric with a reader
    of its own and one with a reader the harness ships."""
    root = tmp_path / "benchmarks"
    for d in ("workloads", "configs", "layer_metrics", "readers"):
        (root / d).mkdir(parents=True)
    shutil.copy(os.path.join(loader.BENCH_ROOT, "configs", "qwen3-0.6b.json"),
                root / "configs" / "other-model.json")
    (root / "workloads" / "train_4k.other.json").write_text(json.dumps({
        "config": "other-model", "kind": "train", "chips": 1, "why": "x",
        "traffic": {"rows": 4, "row_len": 4096}}))
    base = {"unit": "ms", "layer": "model", "moves": "train_tokens_per_s",
            "cells": ["train_4k.other"]}
    (root / "layer_metrics" / "mine.json").write_text(json.dumps(
        {**base, "name": "mine", "reader": "my_reader"}))
    (root / "layer_metrics" / "theirs.json").write_text(json.dumps(
        {**base, "name": "theirs", "reader": "device_busy", "per": "steps"}))
    (root / "readers" / "my_reader.py").write_text(
        "def read(ctx, spec):\n    return 42.0\n")
    cell = loader.load_cell("train_4k.other", str(root))
    assert loader.load_config(cell["config"], str(root))["hidden_size"] == 1024
    metrics = loader.load_layer_metrics("train_4k.other", str(root))
    assert [m["name"] for m in metrics] == ["mine", "theirs"]
    assert metrics[0]["read"]({}, {}) == 42.0
    assert callable(loader.load_kind(cell["kind"], str(root)))
    assert loader.load_layer_metrics("train_2k", str(root)) == []


def _bench_with(tmp_path, end_to_end, metrics):
    """A throw-away `BENCHMARK.json` + `benchmarks/layer_metrics/`."""
    root = tmp_path / "benchmarks"
    (root / "layer_metrics").mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"end_to_end": end_to_end}))
    for m in metrics:
        (root / "layer_metrics" / (m["name"] + ".json")).write_text(json.dumps(
            {"unit": "ms", "layer": "model", "reader": "device_busy",
             "per": "steps", **m}))
    return str(root)


E2E = [{"name": "a_per_s", "workloads": ["c1", "c2"]},
       {"name": "b_per_s", "workloads": ["c3"]},
       {"name": "setup_s"}]


@pytest.mark.parametrize("cell, want", [
    ("c1", ["everywhere", "listed", "shared_a"]),
    ("c2", ["everywhere", "shared_a"]),
    ("c3", ["everywhere", "shared_b"]),
    # a cell BENCHMARK.json does not know yet reports only what every cell does
    ("c4", ["everywhere"]),
])
def test_a_metric_without_cells_reaches_the_cells_that_report_its_moves(
        tmp_path, cell, want):
    root = _bench_with(tmp_path, E2E, [
        {"name": "shared_a", "moves": "a_per_s"},
        {"name": "shared_b", "moves": "b_per_s"},
        {"name": "everywhere", "moves": "setup_s"},
        # an explicit list still wins: c2 reports a_per_s and is not in it
        {"name": "listed", "moves": "a_per_s", "cells": ["c1"]},
    ])
    assert [m["name"] for m in loader.load_layer_metrics(cell, root)] == want
    assert loader.end_to_end_metrics("c1", root) == ["a_per_s", "setup_s"]


@pytest.mark.parametrize("metric, complaint", [
    ({"name": "m", "moves": "no_such_per_s"}, "no end-to-end metric"),
    ({"name": "m", "moves": "no_such_per_s", "cells": ["c1"]},
     "no end-to-end metric"),
])
def test_an_unknown_moves_is_refused_before_a_run(tmp_path, metric, complaint):
    root = _bench_with(tmp_path, E2E, [metric])
    with pytest.raises(loader.BenchFileError, match=complaint):
        loader.load_layer_metrics("c1", root)


def test_a_metric_without_cells_needs_a_benchmark_json(tmp_path):
    root = _bench_with(tmp_path, E2E, [{"name": "m", "moves": "a_per_s"}])
    os.remove(tmp_path / "BENCHMARK.json")
    with pytest.raises(loader.BenchFileError, match="no BENCHMARK.json"):
        loader.load_layer_metrics("c1", root)


def test_an_unknown_reader_is_refused_before_a_run(tmp_path):
    root = tmp_path / "benchmarks"
    (root / "layer_metrics").mkdir(parents=True)
    (root / "layer_metrics" / "m.json").write_text(json.dumps({
        "name": "m", "unit": "ms", "layer": "model", "moves": "x",
        "cells": ["c"], "reader": "guess_it"}))
    with pytest.raises(loader.BenchFileError, match="unknown reader"):
        loader.load_layer_metrics("c", str(root))
    (root / "layer_metrics" / "m.json").write_text(json.dumps({"name": "m"}))
    with pytest.raises(loader.BenchFileError, match="lacks"):
        loader.load_layer_metrics("c", str(root))


@pytest.mark.parametrize("qk_norm", [False, True])
def test_reference_forward_equals_the_program_s_forward(qk_norm):
    """The plain float32 reference against `models/transformer.py forward`
    on `tiny_config`, Qwen2 flavour (qkv bias) and Qwen3 flavour (q/k norm)."""
    import jax
    import jax.numpy as jnp

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config
    from areal_tpu.models.transformer import forward
    from benchmarks.lib import reference

    cfg = tiny_config(qkv_bias=not qk_norm, qk_norm=qk_norm,
                      tie_word_embeddings=not qk_norm)
    params = init_params(cfg, jax.random.PRNGKey(3))
    # biases and norm weights start at 0 / 1: move them so they are tested
    key = jax.random.PRNGKey(4)
    for name in ("bq", "bk", "bv", "q_norm", "k_norm"):
        if name in params["layers"]["attn"]:
            key, k = jax.random.split(key)
            leaf = params["layers"]["attn"][name]
            params["layers"]["attn"][name] = leaf + 0.3 * jax.random.normal(
                k, leaf.shape, leaf.dtype)
    hf = {"num_attention_heads": cfg.num_heads,
          "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
          "hidden_size": cfg.hidden_size, "num_hidden_layers": cfg.num_layers,
          "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
          "model_type": "qwen3" if qk_norm else "qwen2"}
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 33)).astype(np.int32)
    pos = np.broadcast_to(np.arange(33, dtype=np.int32), ids.shape)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, cfg, jnp.asarray(ids), jnp.asarray(pos),
                         jnp.zeros_like(ids))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = np.take_along_axis(np.asarray(logp)[:, :-1], ids[:, 1:, None], -1)[..., 0]
    got = np.asarray(reference.next_token_logprobs(params, hf, ids))
    ok, rep = reference.compare_logprobs(got, want, np.ones_like(got, bool),
                                         1e-5, 1e-4)
    assert ok, rep
    # and a wrong model is told apart: a perturbed weight fails the check
    params["layers"]["mlp"]["w_up"] = params["layers"]["mlp"]["w_up"] * 1.05
    bad = np.asarray(reference.next_token_logprobs(params, hf, ids))
    assert not reference.compare_logprobs(bad, want, np.ones_like(got, bool),
                                          1e-5, 1e-4)[0]
