import json
import os

import numpy as np
import pytest

from benchmarks.lib import flops, loader, stats, traffic as tg
from benchmarks.lib import engine_warm


def cell(name):
    return loader.load_cell(name)


@pytest.mark.parametrize("name,gen", [
    ("rollout_decode", lambda t, s: tg.rollout_groups(t, 1000, s)),
    ("grpo_async_loop", lambda t, s: tg.loop_dataset(t, 1000, s)),
])
def test_same_seed_same_requests_other_seed_other_order(name, gen):
    t = cell(name)["traffic"]
    big = 3_000_000_007  # the driver's seeds pass 2**31
    assert gen(t, big) == gen(t, big)
    assert gen(t, big) != gen(t, big + 1)


def test_rollout_sizes_and_their_order_are_the_same_for_every_seed():
    t = cell("rollout_decode")["traffic"]
    a, b = tg.rollout_groups(t, 1000, 1), tg.rollout_groups(t, 1000, [2, 1])
    sizes = lambda gs: [(len(g["prompt"]), g["budgets"])  # noqa: E731
                        for g in gs]
    assert sizes(a) == sizes(b)
    assert [g["prompt"] for g in a] != [g["prompt"] for g in b]
    d1 = tg.loop_dataset(cell("grpo_async_loop")["traffic"], 1000, 1)
    d2 = tg.loop_dataset(cell("grpo_async_loop")["traffic"], 1000, 2)
    assert [x["max_new_tokens"] for x in d1] == [x["max_new_tokens"] for x in d2]
    lo, hi = t["output_len"]["lo"], t["output_len"]["hi"]
    assert all(lo <= x <= hi for g in a for x in g["budgets"])
    assert all(len(g["budgets"]) == t["group_size"] for g in a)
    # every group holds one budget from each band: similar work per group
    tot = [sum(g["budgets"]) for g in a]
    assert max(tot) < 2.5 * min(tot)


def test_train_batches_pack_into_exactly_the_rows_asked_for():
    from areal_tpu.utils.data import pack_into_rows

    t = cell("train_2k")["traffic"]
    a = tg.train_batches(t, 1000, 5)
    b = tg.train_batches(t, 1000, 6)
    assert len(a) == t["pool"]
    for batch in a + b:
        rp = pack_into_rows(batch, t["row_len"], rows_bucket_pow2=True)
        assert rp.n_rows == t["rows"]
    lens = lambda x: sorted(x["attention_mask"].sum(-1).tolist())  # noqa: E731
    assert lens(a[0]) == lens(a[1]) == lens(b[0])
    assert not np.array_equal(a[0]["input_ids"], b[0]["input_ids"])
    assert np.array_equal(a[0]["input_ids"],
                          tg.train_batches(t, 1000, 5)[0]["input_ids"])
    filled = a[0]["attention_mask"].sum() / (t["rows"] * t["row_len"])
    assert 0.85 < filled <= 1.0
    assert set(a[0]["rewards"].tolist()) == {0.0, 1.0}


def test_quantiles_respect_their_bounds():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "lo": 64, "hi": 1024}
    xs = tg.stratified(spec, 101)
    assert xs == sorted(xs) and xs[0] >= 64 and xs[-1] <= 1024
    assert xs[50] == 256
    u = tg.stratified({"dist": "uniform", "lo": 3, "hi": 5}, 300)
    assert set(u) == {3, 4, 5}
    with pytest.raises(ValueError):
        tg.quantile({"dist": "zipf"}, 0.5)


def test_attention_flops_against_a_hand_count():
    # one head of size 4, one segment of 3 tokens, forward: QK^T has 3*3
    # scores of 2*4 flops, PV the same, half of each under the causal mask
    assert flops.causal_attention_flops([3], 1, 4, backward=False) == 2 * 4 * 9
    # backward: four products of that size; segments add, heads multiply
    assert flops.causal_attention_flops([3, 2], 2, 4) == 6 * 2 * 4 * (9 + 4)


def test_param_count_and_kv_bytes_of_the_published_configs():
    q25 = loader.load_config("qwen2.5-1.5b")
    q3 = loader.load_config("qwen3-0.6b")
    assert flops.dense_param_count(q25) == 1_543_714_304
    assert flops.kv_bytes_per_token(q25) == 28_672
    assert flops.kv_bytes_per_token(q3) == 114_688
    assert flops.dense_param_count(q3) == 596_049_920


def test_percentiles():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.dist_summary([]) == {"n": 0}
    assert stats.iqr_share([10, 10, 10, 10, 10, 11]) == pytest.approx(0.025)


def test_engine_warm_plan_covers_the_rollout_cell():
    t = cell("rollout_decode")["traffic"]
    lens = [len(g["prompt"]) for g in tg.rollout_groups(t, 1000, [7, 0])]
    p = engine_warm.plan(64, 128, 2048, 8, lens, 8, 384 + 1024,
                         t["warm_max_admit"])
    # the traffic's lengths fall into two (length, length - 1) bucket pairs
    assert p["prompt_lens"] == [130, 258]
    assert p["fresh_rows"] == [1, 2, 4, 8]
    assert [sum(m - 1 for m in r) for r in p["sibling_rounds"]] == [1, 2, 4, 8]
    assert p["reuse_rows"] == [1, 2, 4]
    # one start under every key-window bucket from the shortest prompt's up
    assert p["decode_starts"] == [240, 496, 1008, 2032]
    # lengths on a bucket's edge get a representative of their own
    edge = engine_warm.plan(64, 128, 2048, 8, [128, 129, 200], 8, 1408, 8)
    assert edge["prompt_lens"] == [128, 129, 200]


def test_benchmark_json_agrees_with_the_files():
    root = os.path.dirname(loader.BENCH_ROOT)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        c = loader.load_cell(w["name"])
        assert (c["config"], c["chips"]) == (w["config"], w["chips"])
        loader.load_kind(c["kind"])
        hf = loader.load_config(w["config"])
        conf = next(x for x in bench["configs"] if x["name"] == w["config"])
        assert conf["source"] == hf["bench"]["source"]
        assert conf["reduced"] == hf["bench"]["reduced"]
        assert os.path.isfile(os.path.join(root, conf["file"]))
        names = {m["name"] for m in loader.load_layer_metrics(w["name"])}
        want = {m["name"] for m in bench["per_layer"] if w["name"] in m["workloads"]}
        assert names == want
        assert "setup_s" in loader.end_to_end_metrics(w["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        spec = json.load(open(os.path.join(
            loader.BENCH_ROOT, "layer_metrics", m["name"] + ".json")))
        for k in ("unit", "layer", "moves", "source"):
            assert spec[k] == m[k], (m["name"], k)
