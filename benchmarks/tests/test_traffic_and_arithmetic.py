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


def _plan_of(name):
    c = cell(name)
    t, e = c["traffic"], c["engine"]
    lens = [len(g["prompt"]) for g in tg.rollout_groups(t, 1000, [7, 0])]
    return t, e, engine_warm.plan(
        e["n_slots"], 128, e["max_seq_len"], 8, lens, t["group_size"],
        t["prompt_len"]["hi"] + t["output_len"]["hi"],
        engine_warm.admit_rows(t, e["n_slots"]))


def test_engine_warm_plan_covers_the_rollout_cell():
    t, e, p = _plan_of("rollout_decode")
    # the traffic's lengths fall into two (length, length - 1) bucket pairs
    assert p["prompt_lens"] == [130, 258]
    # one representative a group: at most n_slots / group_size fresh rows
    assert p["fresh_rows"] == [1, 2, 4, 8]
    # one start under every key-window bucket from the shortest prompt's up
    assert p["decode_starts"] == [240, 496, 1008, 2032]
    # lengths on a bucket's edge get a representative of their own
    edge = engine_warm.plan(64, 128, 2048, 8, [128, 129, 200], 8, 1408, 8)
    assert edge["prompt_lens"] == [128, 129, 200]


@pytest.mark.parametrize("name, sibling_rows, reuse_rows", [
    # groups whose longest member holds the clipped budget end TOGETHER, so
    # one admission pass replaces two, four or (the first fill) eight groups
    # of 8: suffix rows up to every slot at once
    ("rollout_decode", [1, 2, 4, 8, 16, 32, 56], [1, 2, 4, 8]),
    # these two keep the plan they were measured with
    ("rollout_retention", [1, 2, 4, 8], [1, 2]),
    ("rollout_hybrid_moe", [1, 2, 4, 8], [1, 2, 4]),
])
def test_engine_warm_plan_rows_follow_the_traffic(name, sibling_rows,
                                                  reuse_rows):
    t, e, p = _plan_of(name)
    g = t["group_size"]
    assert [sum(m - 1 for m in r) for r in p["sibling_rounds"]] == sibling_rows
    # a round's groups fit the slot grid, members of a group number at most g
    assert all(sum(r) <= e["n_slots"] and max(r) <= g
               for r in p["sibling_rounds"])
    # reuse rows are warmed beside a fresh dispatch of as many rows
    assert [k for k in p["reuse_rows"] if k in p["fresh_rows"]] == reuse_rows


@pytest.mark.parametrize("traffic, n_slots, rows", [
    # every request in flight, or every slot, whichever is fewer
    ({"groups_in_flight": 12, "group_size": 8}, 64, 64),
    ({"groups_in_flight": 3, "group_size": 4}, 64, 12),
    # a file that states the rows is taken at its word
    ({"groups_in_flight": 12, "group_size": 8, "warm_max_admit": 8}, 64, 8),
])
def test_admit_rows_come_from_the_traffic(traffic, n_slots, rows):
    assert engine_warm.admit_rows(traffic, n_slots) == rows


def test_rollout_decode_warms_up_to_a_pass_that_fills_every_slot():
    """The cell states no `warm_max_admit`: the rows are what its traffic's
    own parameters give, every slot or every request in flight."""
    c = cell("rollout_decode")
    t, e = c["traffic"], c["engine"]
    assert "warm_max_admit" not in t and "warm_max_admit" not in c["rehearsal"]
    assert engine_warm.admit_rows(t, e["n_slots"]) == 64
    # the cell runs the server's default engine: it states no decode path,
    # and says so where the driver reads why the cell exists
    assert "ragged_attn" not in e
    bench = json.load(open(os.path.join(
        os.path.dirname(loader.BENCH_ROOT), "BENCHMARK.json")))
    why = next(w["why"] for w in bench["workloads"]
               if w["name"] == "rollout_decode")
    assert why == c["why"] and "default engine" in why and len(why) <= 200


class _Req:
    def __init__(self, first, finish, n):
        self.first_token_ts, self.finish_ts = first, finish
        self.output_tokens = [0] * n


@pytest.mark.parametrize("first, finish, n, want", [
    (10.0, 12.0, 101, [20.0]),   # born and finished inside: 2 s over 100
    (9.99, 12.0, 101, []),       # first token during the ramp: out
    (10.0, 0.0, 50, []),         # straddles the close: never finished
    (0.0, 0.0, 0, []),           # still queued at the close
    (11.0, 11.5, 1, []),         # one token has no time per further token
])
def test_tpot_takes_requests_born_in_the_window(first, finish, n, want):
    rollout = loader._load_module("kinds", "rollout", loader.BENCH_ROOT)
    got = rollout.tpot_ms([_Req(first, finish, n)], t_open=10.0)
    assert got == pytest.approx(want)


class _Eng:
    """An engine whose step k takes k ms of its fetch phase."""

    def __init__(self):
        self.stats = {f"t_step_{p}_s": 0.0 for p in
                      ("admit", "sync", "dispatch", "fetch", "deliver")}
        self.k = 0

    def step(self):
        self.k += 1
        self.stats["t_step_fetch_s"] += self.k * 1e-3
        self.stats["t_step_admit_s"] += 1e-4
        return 3


def test_closed_loop_places_its_slowest_steps():
    """One record for the three rollout kinds, kept by the loop they all
    drive: no wrapper on the engine."""
    rollout = loader._load_module("kinds", "rollout", loader.BENCH_ROOT)
    loop = rollout.ClosedLoop.__new__(rollout.ClosedLoop)
    loop.eng, loop.steps, loop.owed = _Eng(), 0, 0
    assert loop.run(until_steps=4) == 12
    assert loop.run(until_steps=5) == 15     # a report is of the last run
    rep = loop.step_report()
    assert rep["step_ms"]["n"] == 5
    assert len(rep["slowest_steps"]) == 5
    for ms, i, cpu_ms, phase, phase_ms in rep["slowest_steps"]:
        assert 0 <= i < 5 and ms >= 0 and cpu_ms >= 0
        assert (phase, phase_ms) == ("fetch", pytest.approx(5 + i, abs=0.1))


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
        mine = loader.end_to_end_metrics(w["name"])
        assert "setup_s" in mine
        # no `workloads`: every cell that reports the metric's `moves`
        want = {m["name"] for m in bench["per_layer"]
                if w["name"] in m.get("workloads", [w["name"]])
                and m["moves"] in mine}
        assert names == want
    assert len(bench["per_layer"]) <= 128  # the contract's cap
    files = os.listdir(os.path.join(loader.BENCH_ROOT, "layer_metrics"))
    # one file an entry, one entry a file
    assert sorted(files) == sorted(m["name"] + ".json" for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        spec = json.load(open(os.path.join(
            loader.BENCH_ROOT, "layer_metrics", m["name"] + ".json")))
        for k in ("unit", "layer", "moves", "source"):
            assert spec[k] == m[k], (m["name"], k)
        assert spec.get("cells") == m.get("workloads"), m["name"]


def test_no_two_metric_files_differ_in_name_and_cells_alone():
    """A cell joins a metric by its `moves` (no `cells`) or by the file's
    list; a copy under another name is what filled `per_layer`.  The copies
    known today are listed, so that no NEW one is added: three are opened by
    name by a tier-1 test outside the benchmark's directories, fifteen came
    with the cells of PRs 32-52 (one reader and scope under another name and
    `cells` list).  Merging them renames entries of `BENCHMARK.json`: a
    `benchmark` issue of its own (PERF.md, section 7)."""
    d = os.path.join(loader.BENCH_ROOT, "layer_metrics")
    seen = {}
    for fn in sorted(os.listdir(d)):
        spec = json.load(open(os.path.join(d, fn)))
        key = json.dumps({k: v for k, v in spec.items()
                          if k not in ("name", "cells")}, sort_keys=True)
        seen.setdefault(key, []).append(spec["name"])
    copies = sorted(n for names in seen.values() if len(names) > 1
                    for n in sorted(names, key=len)[1:])
    assert copies == [
        "rollout_attn_ms_per_token.mamba1",
        "rollout_expert_tokens_per_expert.hybrid",
        "rollout_experts_touched_pct.hybrid",
        "rollout_experts_touched_pct.latent",
        "rollout_ffn_dense_ms_per_token.latent",
        "rollout_ffn_dense_ms_per_token.mamba1",
        "rollout_live_slots_per_pass.latent",
        "rollout_live_slots_per_pass.mamba1",
        "rollout_live_slots_per_pass.retention",
        "rollout_live_slots_per_pass.swa",
        "rollout_moe_experts_ms_per_token.hybrid",
        "rollout_moe_experts_ms_per_token.latent",
        "rollout_moe_ms_per_token.hybrid",
        "rollout_moe_ms_per_token.latent",
        "rollout_ssm_ms_per_token.mamba1",
        "rollout_state_copy_ms_per_token.mamba1",
        "train_pack_ms_per_step.16k",
        "train_update_dispatch_ms_per_step.16k"]


# ---------------------------------------------------------------------------
# a warm plan covers what its cell's traffic reaches, no less and no more
# ---------------------------------------------------------------------------


def _closed_loop_by_request(groups, in_flight, chunk, steps):
    """A closed loop stepped request by request, as `GenEngine.step` gives
    tokens (one with the prefill, then `chunk` a step) and `ClosedLoop`
    replaces a group (when its last member has ended, before the next
    step): [(step, first group, groups)].  Written apart from
    `engine_warm.closed_loop_passes`, which works on a group's top budget."""
    live, made, nxt, owed = {}, [], 0, in_flight
    for t in range(steps):
        if owed:
            made.append((t, nxt, owed))
            for g in range(nxt, nxt + owed):
                live[g] = [[0, b] for b in groups[g % len(groups)]["budgets"]]
            nxt, owed = nxt + owed, 0
            admitted = {g for g in live if g >= made[-1][1]}
        else:
            admitted = set()
        for g in list(live):
            for r in live[g]:
                r[0] += chunk + (1 if g in admitted else 0)
            if all(have >= want for have, want in live[g]):
                del live[g]
                owed += 1
    return made


@pytest.mark.parametrize("name, old_rounds, steps", [
    # the plan by stated rows would walk 5 length buckets x (6 fresh + 6
    # reuse + 10 sibling rounds) + 5 decode starts; 6 x (4 + 4 + 7) + 6.
    # `steps`: eight windows of today's engine (130 and 290 steps in 40 s)
    ("rollout_ssm_dense_4k", 115, 1000),
    ("rollout_swa_moe_16k", 96, 2400),
])
def test_plan_by_reach_holds_every_pass_of_the_closed_loop(name, old_rounds,
                                                           steps):
    c = cell(name)
    t, e = c["traffic"], c["engine"]
    assert "warm_max_admit" not in t and not engine_warm.queues(t, e["n_slots"])
    groups = tg.rollout_groups(t, 1000, [7, 0])
    lens = [len(g["prompt"]) for g in groups]
    top = [max(g["budgets"]) for g in groups]
    max_total = t["prompt_len"]["hi"] + t["output_len"]["hi"]
    p = engine_warm.plan_by_reach(lens, top, t["groups_in_flight"], 128,
                                  e["max_seq_len"], 8, max_total)
    # every pass a loop stepped request by request makes is in the plan,
    # the first fill in what is left to the ramp
    made = _closed_loop_by_request(groups, t["groups_in_flight"], 8, steps)
    assert made[0] == (0, 0, t["groups_in_flight"]) and len(made) > 100
    assert made[-1][1] < p["horizon"]["groups"]
    assert engine_warm.unplanned_passes(
        p, made, lens, 128, e["max_seq_len"]) == []
    assert p["left_to_ramp"] == [p["left_to_ramp"][0]]
    assert len(p["left_to_ramp"][0]) == t["groups_in_flight"]
    # a pass the loop does not make is named
    odd = [(3, 0, 5)]
    assert engine_warm.unplanned_passes(p, odd, lens, 128, e["max_seq_len"]) \
        == [[3, lens[:5]]]
    # smaller than the walk over stated rows, in rounds sent and in the
    # prompt tokens they prefill
    old = engine_warm.plan(
        e["n_slots"], 128, e["max_seq_len"], 8, lens, t["group_size"],
        max_total, engine_warm.admit_rows(t, e["n_slots"]))
    rounds = len(old["prompt_lens"]) * (
        len(old["fresh_rows"]) + len(old["sibling_rounds"])
        + sum(k in old["reuse_rows"] for k in old["fresh_rows"])
    ) + len(old["decode_starts"])
    assert rounds == old_rounds
    assert len(p["passes"]) + len(p["decode_starts"]) < rounds / 2
    old_tokens = sum(L * (2 * sum(old["fresh_rows"])
                          + sum(len(r) for r in old["sibling_rounds"]))
                     for L in old["prompt_lens"])
    assert sum(map(sum, p["passes"])) < old_tokens / 3
    assert p["decode_starts"] == old["decode_starts"]


def test_closed_loop_passes_are_arithmetic_on_the_top_budgets():
    # two groups in flight, budgets of 9 and 17 at a chunk of 8: the first
    # is replaced after one step, the second after two
    made = engine_warm.closed_loop_passes([9, 17], 2, 8, 6)
    assert made[:3] == [(0, 0, 2), (1, 2, 1), (2, 3, 2)]
    groups = [{"budgets": [9, 3]}, {"budgets": [2, 17]}]
    assert made == _closed_loop_by_request(groups, 2, 8, 20)[:len(made)]


class _WarmEng:
    """An engine that finishes what it is given at once and keeps the
    batches it was sent."""

    n_slots, prompt_bucket, max_seq_len, decode_chunk = 64, 128, 2048, 8

    def __init__(self, n_slots=64):
        self.n_slots, self.sent = n_slots, []

    def submit_batch(self, reqs):
        self.sent.append(reqs)
        for r in reqs:
            r.stop_reason = "length"

    def step(self):
        raise AssertionError("nothing is left to step")


class _WarmReq:
    stop_reason = ""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _warm(traffic, n_slots):
    eng = _WarmEng(n_slots)
    groups = tg.rollout_groups(traffic, 1000, [7, 0])
    return eng, engine_warm.warm_closed_loop(eng, _WarmReq, 1000, 7, traffic,
                                             groups)


def test_a_stated_warm_max_admit_still_wins_and_a_queue_keeps_its_rows():
    t = {**cell("rollout_swa_moe_16k")["traffic"], "n_groups": 16}
    # by reach: every pass sent as whole groups on one prompt each
    eng, p = _warm(t, 64)
    assert set(p) == {"passes", "left_to_ramp", "decode_starts", "horizon"}
    assert len(eng.sent) == len(p["passes"]) + len(p["decode_starts"])
    for lens, reqs in zip(p["passes"], eng.sent):
        assert len(reqs) == len(lens) * t["group_size"]
        by_group = {}
        for r in reqs:
            assert r.max_new_tokens == 1 and r.group_n == t["group_size"]
            by_group.setdefault(r.group_id, []).append(r.input_ids)
        assert [len(v[0]) for v in by_group.values()] == lens
        assert all(v.count(v[0]) == len(v) for v in by_group.values())
    # the file's word stands, to the letter of the plan by rows
    eng, p = _warm({**t, "warm_max_admit": 8}, 64)
    assert p == engine_warm.plan(
        64, 128, 2048, 8, [len(g["prompt"]) for g in
                           tg.rollout_groups(t, 1000, [7, 0])],
        t["group_size"], t["prompt_len"]["hi"] + t["output_len"]["hi"], 8)
    assert p["sibling_rounds"][-1] == [8, 2] and "passes" not in p
    # more requests in flight than slots: members wait for slots one by
    # one, which no arithmetic on the work list places
    eng, p = _warm(t, 32)
    assert "passes" not in p and p["fresh_rows"] == [1, 2, 4]
    assert engine_warm.unplanned_passes(p, [(0, 0, 8)], [300] * 16, 128, 2048) == []


def test_build_engine_leaves_to_the_constructor_what_no_file_states():
    """The nine options `build_engine` spelt at their defaults until PR 55
    are the constructor's own defaults, so the engine built is the same."""
    import inspect

    from areal_tpu.gen.engine import GenEngine

    d = {k: v.default for k, v in
         inspect.signature(GenEngine.__init__).parameters.items()}
    assert {k: d[k] for k in (
        "decode_window", "decode_tiers", "decode_tier_lens",
        "decode_tier_slots", "spec_decode", "spec_ladder", "spec_draft_len",
        "host_offload", "host_cache_mb")} == {
        "decode_window": True, "decode_tiers": 1, "decode_tier_lens": None,
        "decode_tier_slots": None, "spec_decode": False, "spec_ladder": None,
        "spec_draft_len": None, "host_offload": False, "host_cache_mb": 64}
    src = inspect.getsource(
        loader._load_module("kinds", "rollout", loader.BENCH_ROOT).build_engine)
    call = src[src.index("return GenEngine("):]
    assert not any(k in call for k in ("decode_window", "decode_tier",
                                       "spec_", "host_offload", "host_cache"))
