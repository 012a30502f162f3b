"""Unified telemetry (ISSUE 10): registry/exposition units, the trajectory
event log + Chrome-trace export, and the three Prometheus /metrics surfaces
(gen server, router, trainer endpoint) scraped over real HTTP.

The metric-name sets served by each surface are pinned in
tests/data/metrics_schema.json — a missing name is a silent observability
regression even when nothing else fails."""

import json
import os
import urllib.request

import numpy as np
import pytest

from areal_tpu.utils import telemetry
from areal_tpu.utils.telemetry import (
    EventLog,
    Histogram,
    Registry,
    parse_prometheus_text,
    trace_key,
)

SCHEMA_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "metrics_schema.json")


@pytest.fixture()
def enabled():
    """Enable telemetry for one test; restore flag + event log after."""
    was = telemetry.is_enabled()
    telemetry.set_enabled(True)
    telemetry.EVENTS.clear()
    yield
    telemetry.set_enabled(was)
    telemetry.EVENTS.clear()


def _type_lines(text: str):
    """{metric_name} declared via '# TYPE' — the schema unit (histograms
    expand to _bucket/_sum/_count sample names)."""
    out = {}
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            out[name] = kind
    return out


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------


def test_trace_key_stable_nonnegative_int64():
    k1 = trace_key("traj-0")
    assert k1 == trace_key("traj-0")  # deterministic across calls
    assert k1 != trace_key("traj-1")
    assert 0 <= k1 < 2**63
    assert isinstance(k1, int)
    # survives an int64 round-trip (how it rides inside batches)
    assert int(np.int64(k1)) == k1


def test_registry_render_parse_roundtrip():
    reg = Registry("t1")
    reg.counter("reqs_total", "requests").inc(3)
    reg.counter("reqs_total").inc(2, server="a")
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render_prometheus()
    parsed = parse_prometheus_text(text)
    assert parsed["areal_t1_reqs_total"][""] == 3
    assert parsed["areal_t1_reqs_total"]['{server="a"}'] == 2
    assert parsed["areal_t1_depth"][""] == 7
    # cumulative buckets + +Inf + sum/count
    b = parsed["areal_t1_lat_seconds_bucket"]
    assert b['{le="0.1"}'] == 1
    assert b['{le="1"}'] == 2
    assert b['{le="+Inf"}'] == 3
    assert parsed["areal_t1_lat_seconds_count"][""] == 3
    assert parsed["areal_t1_lat_seconds_sum"][""] == pytest.approx(5.55)
    kinds = _type_lines(text)
    assert kinds["areal_t1_reqs_total"] == "counter"
    assert kinds["areal_t1_depth"] == "gauge"
    assert kinds["areal_t1_lat_seconds"] == "histogram"


def test_registry_get_or_create_and_kind_conflict():
    reg = Registry("t2")
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    # already-prefixed names are not double-prefixed
    assert reg.counter("areal_custom_total").name == "areal_custom_total"


def test_collector_errors_do_not_fail_scrape():
    reg = Registry("t3")
    reg.add_collector(lambda: 1 / 0)
    ok = {"n": 0}

    def good():
        ok["n"] += 1
        reg.gauge("fine").set(1)

    reg.add_collector(good)
    text = reg.render_prometheus()
    assert "areal_t3_fine 1" in text
    assert reg.collector_errors == 1 and ok["n"] == 1


def test_histogram_staleness_buckets():
    h = Histogram("s", "", buckets=telemetry.STALENESS_BUCKETS)
    for v in (0, 0, 1, 5, 100):
        h.observe(v)
    samples = {(s, lab.get("le")): v for s, lab, v in h.samples()}
    assert samples[("_bucket", "0")] == 2
    assert samples[("_bucket", "1")] == 3
    assert samples[("_bucket", "6")] == 4
    assert samples[("_bucket", "+Inf")] == 5
    assert samples[("_count", None)] == 5


def test_event_log_disabled_is_noop():
    telemetry.set_enabled(False)
    log = EventLog(capacity=4)
    log.emit("submit", trace_id="t")
    assert len(log) == 0


def test_event_log_bounded_with_dropped_count(enabled):
    log = EventLog(capacity=4)
    for i in range(7):
        log.emit("e", trace_id=f"t{i}", idx=i)
    assert len(log) == 4
    assert log.dropped == 3
    evs = log.snapshot()
    assert [e["idx"] for e in evs] == [3, 4, 5, 6]  # oldest fell off
    assert all(e["trace_key"] == trace_key(e["trace_id"]) for e in evs)


def test_event_log_jsonl_and_chrome_trace(enabled, tmp_path):
    log = EventLog(capacity=64)
    log.emit("rollout_submit", trace_id="tr-1", input_len=8)
    log.emit("decode_chunk", tier=0, latency_s=0.25, trace_ids=["tr-1"])
    log.emit("gen_done", trace_id="tr-1", latency_s=1.0)
    jl = tmp_path / "events.jsonl"
    assert log.dump_jsonl(str(jl)) == 3
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["rollout_submit", "decode_chunk",
                                          "gen_done"]
    trace = log.to_chrome_trace()
    by_name = {}
    for ev in trace["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    assert by_name["process_name"][0]["ph"] == "M"
    assert by_name["rollout_submit"][0]["ph"] == "i"  # instant
    done = by_name["gen_done"][0]
    assert done["ph"] == "X" and done["dur"] == pytest.approx(1e6)
    assert done["tid"] == trace_key("tr-1") % (2**31)
    ct = tmp_path / "trace.json"
    assert log.dump_chrome_trace(str(ct)) == 3
    json.loads(ct.read_text())  # valid JSON on disk


def test_publish_train_stats_mirrors_scalars(enabled):
    reg = telemetry.TRAIN
    before = reg.snapshot().get("areal_train_steps_total", 0)
    telemetry.publish_train_stats({
        "loss": 0.5, "grad_norm": 1.25, "step_time": 0.1,
        "total_loss_weight": 128.0, "not_a_number": object(),
    })
    snap = reg.snapshot()
    assert snap["areal_train_steps_total"] == before + 1
    assert snap["areal_train_step_loss"] == 0.5
    assert snap["areal_train_step_grad_norm"] == 1.25


# ---------------------------------------------------------------------------
# staleness manager export + capacity formula
# ---------------------------------------------------------------------------


def test_staleness_capacity_formula_and_metrics_export():
    from areal_tpu.core.staleness import StalenessManager

    bs, eta = 4, 2
    m = StalenessManager(max_concurrent_rollouts=64, consumer_batch_size=bs,
                         max_staleness=eta)
    reg = Registry("stale_t")
    m.register_metrics(reg)

    version = 0
    # churn through submit/accept/reject and check the invariant at every
    # step: accepted + running <= (eta + version + 1) * bs
    rng = np.random.default_rng(0)
    for step in range(200):
        cap = m.get_capacity(version)
        if cap > 0:
            m.on_rollout_submitted()
        else:
            st = m.get_stats()
            if st.running:
                (m.on_rollout_accepted if rng.integers(2)
                 else m.on_rollout_rejected)()
            else:
                version += 1  # trainer consumed a batch
        st = m.get_stats()
        assert st.accepted + st.running <= (eta + version + 1) * bs, (
            step, st, version
        )
    snap = reg.snapshot()
    st = m.get_stats()
    assert snap["areal_stale_t_rollout_submitted"] == st.submitted
    assert snap["areal_stale_t_rollout_running"] == st.running
    assert snap["areal_stale_t_rollout_accepted"] == st.accepted


# ---------------------------------------------------------------------------
# the three HTTP surfaces
# ---------------------------------------------------------------------------


def _scrape(addr_or_url: str):
    url = (addr_or_url if addr_or_url.startswith("http")
           else f"http://{addr_or_url}/metrics?format=prometheus")
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200
        return resp.read().decode()


@pytest.fixture(scope="module")
def gen_server():
    import jax

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config

    from tests.test_gen_server_integration import _boot_server

    cfg = tiny_config(vocab_size=89, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = GenEngine(cfg, params=params, n_slots=4, max_seq_len=96,
                       prompt_bucket=16)
    server, addr, stop = _boot_server(engine)
    yield engine, server, addr
    stop()


def _generate(addr, rid, n_new=4):
    req = urllib.request.Request(
        f"http://{addr}/generate",
        data=json.dumps({
            "rid": rid,
            "input_ids": [5, 6, 7],
            "sampling_params": {"max_new_tokens": n_new,
                                "temperature": 0.0},
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_gen_server_prometheus_and_json_coexist(gen_server):
    engine, server, addr = gen_server
    _generate(addr, "m-0")
    # default stays the legacy JSON dict
    with urllib.request.urlopen(f"http://{addr}/metrics", timeout=10) as r:
        legacy = json.loads(r.read())
    assert "decode_steps" in legacy and "prefill_tokens" in legacy
    # Prometheus by query param and by Accept header
    text = _scrape(addr)
    parsed = parse_prometheus_text(text)
    assert parsed["areal_gen_prefill_tokens_total"][""] > 0
    assert "areal_gen_pause_window_seconds" in _type_lines(text)
    req = urllib.request.Request(f"http://{addr}/metrics",
                                 headers={"Accept": "text/plain"})
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.read().decode().startswith("# ")


def test_gen_server_counters_never_decrease(gen_server):
    _, _, addr = gen_server
    before = parse_prometheus_text(_scrape(addr))
    for i in range(3):
        _generate(addr, f"mono-{i}")
    after = parse_prometheus_text(_scrape(addr))
    checked = 0
    for name, series in before.items():
        if not name.endswith("_total"):
            continue
        for labels, v in series.items():
            assert after[name][labels] >= v, (name, labels)
            checked += 1
    assert checked > 5
    # activity moved the generation counters
    assert (after["areal_gen_tokens_generated_total"][""]
            > before["areal_gen_tokens_generated_total"][""])


def test_gen_server_json_metrics_survive_missing_stats_key(gen_server):
    """Satellite 1: a stats-key rename must degrade the counter to 0, not
    500 the whole scrape."""
    engine, _, addr = gen_server
    removed = engine.stats.pop("reservations_lapsed")
    try:
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=10) as r:
            assert r.status == 200
            legacy = json.loads(r.read())
        assert legacy["reservations_lapsed"] == 0
        # the Prometheus side mirrors the dict generically: still 200
        assert "areal_gen_prefill_calls_total" in _scrape(addr)
    finally:
        engine.stats["reservations_lapsed"] = removed


def test_gen_server_spec_decode_telemetry(enabled):
    """Spec decode (ISSUE 12): draft/accept counters, the per-tier
    acceptance-rate gauge, and spec_verify lifecycle spans all ride the
    gen surface when speculative decoding is live."""
    import jax

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config

    from tests.test_gen_server_integration import _boot_server

    cfg = tiny_config(vocab_size=89, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = GenEngine(cfg, params=params, n_slots=4, max_seq_len=128,
                       prompt_bucket=16, spec_decode=True, spec_draft_len=3)
    _, addr, stop = _boot_server(engine)
    try:
        req = urllib.request.Request(
            f"http://{addr}/generate",
            data=json.dumps({
                "rid": "spec-tel-0",
                # The drafter (gen/spec.py propose_draft) matches the
                # history's suffix INCLUDING the pending token, which the
                # model chose; and the engine drafts once per dispatch, so
                # 12 tokens at decode_chunk 8 are two chances.  A periodic
                # prompt hits only if a random-weight model happens to
                # continue the period (it does not on this jaxlib: it
                # answers 23, which the prompt never held, and nothing was
                # drafted).  A prompt that holds every token of the
                # vocabulary makes the first lookup hit whatever the model
                # answers.
                "input_ids": list(range(cfg.vocab_size)),
                "sampling_params": {"max_new_tokens": 12,
                                    "temperature": 0.0},
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert len(out["output_tokens"]) == 12
        assert engine.stats["spec_drafted"] > 0
        assert engine.stats["verify_calls"] > 0
        parsed = parse_prometheus_text(_scrape(addr))
        assert parsed["areal_gen_spec_drafted_total"][""] > 0
        assert parsed["areal_gen_verify_calls_total"][""] > 0
        rate = parsed["areal_gen_spec_acceptance_rate"]
        assert "" in rate  # lifetime rate
        assert any(lab.startswith('{tier=') for lab in rate)
        # the legacy JSON dict carries the same accounting
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=10) as r:
            legacy = json.loads(r.read())
        assert legacy["spec_drafted"] == engine.stats["spec_drafted"]
        assert 0.0 <= legacy["spec_acceptance_rate"] <= 1.0
        # every verify dispatch leaves a spec_verify lifecycle span
        evs = [e for e in telemetry.EVENTS.snapshot()
               if e["event"] == "spec_verify"]
        assert evs, "no spec_verify lifecycle events recorded"
        assert evs[0]["drafted"] >= 1
        assert "latency_s" in evs[0] and "tier" in evs[0]
    finally:
        stop()


@pytest.fixture()
def router_addr():
    from areal_tpu.gen.router import Router, RouterConfig

    from tests.fake_server import FakeGenServer
    from tests.test_router import RouterHarness

    backends = [FakeGenServer(completion=[1, 2]) for _ in range(2)]
    addrs = [s.start() for s in backends]
    router = Router(RouterConfig(train_batch_size=2, schedule_policy="round_robin"),
                    addresses=addrs)
    h = RouterHarness(router)
    yield h.start()
    h.stop()
    for s in backends:
        s.stop()


def test_router_prometheus_exposition(router_addr):
    addr = router_addr
    # route traffic + take a lease so every ledger field is non-trivial
    req = urllib.request.Request(
        f"http://{addr}/generate",
        data=json.dumps({"rid": "r0", "input_ids": [1, 2, 3],
                         "sampling_params": {"max_new_tokens": 4}}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
    alloc = urllib.request.Request(
        f"http://{addr}/allocate_request", data=b"{}",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(alloc, timeout=10) as r:
        assert json.loads(r.read())["staled"] is False
    # JSON default unchanged
    with urllib.request.urlopen(f"http://{addr}/metrics", timeout=10) as r:
        legacy = json.loads(r.read())
    assert sum(legacy["requests_routed"].values()) == 1
    assert legacy["running"] == 1
    text = _scrape(addr)
    parsed = parse_prometheus_text(text)
    assert sum(parsed["areal_router_requests_routed_total"].values()) == 1
    assert parsed["areal_router_rollout_running"][""] == 1
    # capacity = (0 + 0 + 1) * 2 - 1 lease
    assert parsed["areal_router_admission_capacity"][""] == 1


def test_trainer_metrics_endpoint(enabled):
    reg = Registry("train_ep")
    reg.counter("steps_total", "steps").inc(3)
    reg.histogram("staleness_at_consumption", "s",
                  buckets=telemetry.STALENESS_BUCKETS).observe(1)
    srv, port = telemetry.start_metrics_server(reg)
    try:
        text = _scrape(f"http://127.0.0.1:{port}/metrics")
        parsed = parse_prometheus_text(text)
        assert parsed["areal_train_ep_steps_total"][""] == 3
        assert (parsed["areal_train_ep_staleness_at_consumption_count"][""]
                == 1)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics?format=json", timeout=10
        ) as r:
            snap = json.loads(r.read())
        assert snap["areal_train_ep_steps_total"] == 3
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10
        ) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.shutdown()


def test_metrics_schema_pinned(gen_server, router_addr, enabled):
    """Every name in tests/data/metrics_schema.json must be served by its
    surface — renames/deletions break dashboards silently otherwise."""
    with open(SCHEMA_PATH) as f:
        schema = json.load(f)
    _, _, gaddr = gen_server
    _generate(gaddr, "schema-0")
    # touch every router ledger so the labeled series exist in this process
    req = urllib.request.Request(
        f"http://{router_addr}/generate",
        data=json.dumps({"rid": "schema-r", "input_ids": [1, 2, 3],
                         "sampling_params": {"max_new_tokens": 4}}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        assert r.status == 200
    telemetry.publish_train_stats({"loss": 0.1, "grad_norm": 1.0,
                                   "step_time": 0.01,
                                   "total_loss_weight": 8.0})
    srv, port = telemetry.start_metrics_server(telemetry.TRAIN)
    try:
        surfaces = {
            "gen": _type_lines(_scrape(gaddr)),
            "router": _type_lines(_scrape(router_addr)),
            "train": _type_lines(_scrape(f"http://127.0.0.1:{port}/metrics")),
        }
    finally:
        srv.shutdown()
    for surface, pinned in schema.items():
        served = surfaces[surface]
        missing = [n for n in pinned if n not in served]
        assert not missing, f"{surface} /metrics lost {missing}"
        assert all(n.startswith("areal_") for n in served)


def test_router_backend_state_gauge_tracks_breaker():
    """ISSUE 11: areal_router_backend_state must expose the circuit-breaker
    code per backend (0=closed, 2=open) so dashboards can see a dead fleet
    member.  Runs after the exposition test above: a labeled scrape leaves
    per-server samples in the shared ROUTER registry, which would skew that
    test's exact-sum assertions if scraped earlier."""
    import time

    from areal_tpu.gen.router import Router, RouterConfig

    from tests.fake_server import FakeGenServer
    from tests.test_router import RouterHarness

    backends = [FakeGenServer(completion=[1, 2]) for _ in range(2)]
    addrs = [s.start() for s in backends]
    router = Router(
        RouterConfig(
            schedule_policy="round_robin",
            health_check_interval=0.1,
            health_failure_threshold=1,
            health_probe_timeout=0.5,
        ),
        addresses=addrs,
    )
    h = RouterHarness(router)
    raddr = h.start()
    try:
        backends[0].stop()
        deadline = time.monotonic() + 10
        text = ""
        while time.monotonic() < deadline:
            text = _scrape(raddr)
            if f'areal_router_backend_state{{server="{addrs[0]}"}} 2' in text:
                break
            time.sleep(0.05)
        assert f'areal_router_backend_state{{server="{addrs[0]}"}} 2' in text
        assert f'areal_router_backend_state{{server="{addrs[1]}"}} 0' in text
        parsed = parse_prometheus_text(text)
        assert "areal_router_failovers_total" in parsed
        assert "areal_publish_partial_failures_total" in parsed
    finally:
        h.stop()
        backends[1].stop()


# ---------------------------------------------------------------------------
# lifecycle events through the live server
# ---------------------------------------------------------------------------


def test_trace_id_rides_the_wire_and_events_join(gen_server, enabled):
    import asyncio

    from areal_tpu.api.config import (
        GenerationHyperparameters,
        InferenceEngineConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_remote import RemoteJaxEngine

    _, _, addr = gen_server
    client = RemoteJaxEngine(InferenceEngineConfig(
        experiment_name="tt", trial_name="t", consumer_batch_size=2,
        max_concurrent_rollouts=8, request_timeout=30,
        max_head_offpolicyness=100,
    ))
    client.initialize(addr=addr)
    try:
        resp = asyncio.run(client.agenerate(ModelRequest(
            rid="wire-1", trace_id="wire-1", input_ids=[5, 6, 7],
            gconfig=GenerationHyperparameters(max_new_tokens=4, greedy=True),
        )))
        assert len(resp.output_tokens) == 4
    finally:
        client.destroy()
    evs = telemetry.EVENTS.snapshot()
    mine = [e for e in evs if e.get("trace_id") == "wire-1"]
    names = [e["event"] for e in mine]
    # client-side submit + completion spans...
    assert "rollout_submit" in names and "gen_done" in names
    # ...joined with SERVER-side admission/prefill spans via the wire id
    assert "admission" in names and "prefill" in names
    prefill = next(e for e in mine if e["event"] == "prefill")
    assert prefill["total_tokens"] >= 3
    assert prefill["cold_tokens"] + prefill["inherited_tokens"] == (
        prefill["total_tokens"]
    )
    done = next(e for e in mine if e["event"] == "gen_done")
    assert done["output_len"] == 4 and done["attempts"] == 1
    # decode chunks carry the trace id in their per-tier id lists
    chunks = [e for e in evs if e["event"] == "decode_chunk"]
    assert any("wire-1" in e.get("trace_ids", ()) for e in chunks)


# ---------------------------------------------------------------------------
# paired clocks + drop accounting (ISSUE 14 satellites)
# ---------------------------------------------------------------------------


def test_events_carry_paired_clocks_and_pid(enabled):
    """Every event records wall ts (cross-process joins), a perf_counter
    mono stamp (NTP-immune single-process decomposition), and the
    emitting pid so the analyzer knows when mono is comparable."""
    telemetry.emit("rollout_submit", trace_id="clk-1", input_len=4)
    telemetry.emit("gen_done", trace_id="clk-1", latency_s=0.1)
    evs = [e for e in telemetry.EVENTS.snapshot()
           if e.get("trace_id") == "clk-1"]
    assert len(evs) == 2
    for e in evs:
        assert e["pid"] == os.getpid()
        assert e["mono"] > 0 and e["ts"] > 0
    assert evs[1]["mono"] >= evs[0]["mono"]
    assert evs[1]["ts"] >= evs[0]["ts"]


def test_dump_jsonl_meta_trailer_records_drops(enabled, tmp_path):
    """A ring that overflowed must say so in the dump itself — the
    telemetry_meta trailer is what marks the log lossy for the trace
    analyzer (a lossless dump carries no trailer)."""
    log = EventLog(capacity=2)
    log.emit("e", trace_id="t0")
    jl = tmp_path / "lossless.jsonl"
    assert log.dump_jsonl(str(jl)) == 1
    assert "telemetry_meta" not in jl.read_text()

    for i in range(5):
        log.emit("e", trace_id=f"t{i}")
    jl2 = tmp_path / "lossy.jsonl"
    n = log.dump_jsonl(str(jl2))
    lines = [json.loads(ln) for ln in jl2.read_text().splitlines()]
    assert n == len(lines) == 3  # 2 events + the trailer
    meta = lines[-1]
    assert meta["event"] == "telemetry_meta"
    assert meta["dropped_events"] == log.dropped == 4
    assert meta["capacity"] == 2


def test_events_dropped_total_on_all_three_surfaces(enabled):
    """areal_telemetry_events_dropped_total mirrors EVENTS.dropped on the
    gen, router, AND train registries (scrape-time collector), so any
    surface can alarm on lifecycle-evidence loss."""
    name = "areal_telemetry_events_dropped_total"
    before = telemetry.EVENTS.dropped
    try:
        telemetry.EVENTS.dropped = before + 7
        for reg in (telemetry.GEN, telemetry.ROUTER, telemetry.TRAIN):
            snap = reg.snapshot()
            assert snap[name] == before + 7, reg.namespace
            parsed = parse_prometheus_text(reg.render_prometheus())
            assert parsed[name][""] == before + 7
    finally:
        telemetry.EVENTS.dropped = before


def test_partial_failure_counter_registered_eagerly():
    """Regression (ISSUE 18 / C9 metric-contract): the control-plane
    fanout partial-failure counter must be a module-level pinned metric —
    the lazy per-failure construction left it off the scrape surface
    until the first failure, unverifiable by the schema pin."""
    text = telemetry.TRAIN.render_prometheus()
    assert "areal_train_publish_partial_failures_total" in text
    # get-or-create resolves to the same eagerly-registered instance
    assert telemetry.PUBLISH_PARTIAL_FAILURES is telemetry.TRAIN.counter(
        "publish_partial_failures_total"
    )
