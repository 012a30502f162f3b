"""`GenEngine`'s in-flight ledger: `t_starved_s`, `t_admit_fetch_s` and
`t_between_steps_s` of `GenEngine.stats` (docs/observability.md, the
inventory).  Toy engines on the CPU, one a dispatch path: the collapsed
grid-wide dispatch, a dispatch a tier, a verify dispatch, and a slot that is
one state (whose shared span is prefilled by a program nobody downloads)."""

import json
import os
import time

import jax
import pytest

from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.gen.server import GenServer
from areal_tpu.models import init_params
from areal_tpu.utils import telemetry
from tests.test_slot_kinds import DENSE_CFG, STATE_CFG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = ("t_starved_s", "t_admit_fetch_s", "t_between_steps_s")
# engine -> (toy configuration, what the constructor is told)
ENGINES = {
    "grid": (DENSE_CFG, {}),
    "tiers": (DENSE_CFG, {"ragged_attn": False, "decode_tiers": 2}),
    "verify": (DENSE_CFG, {"ragged_attn": False, "spec_decode": True,
                           "spec_draft_len": 2}),
    "state": (STATE_CFG, {}),
}
# what lies between two spans of a step (a span's own entry and exit), which
# no phase total covers and an open interval does: a dozen seams a step
SEAMS_S = 100e-6
PROMPT = list(range(3, 3 + 21))
LONG = list(range(3, 70)) + list(range(3, 30))  # the roomier tier's


def _requests(tag, new=12):
    group = [GenRequest(rid=f"{tag}-{i}", input_ids=list(PROMPT),
                        max_new_tokens=new, temperature=1.0, group_id=tag,
                        group_n=2) for i in range(2)]
    return group + [GenRequest(rid=f"{tag}-long", input_ids=list(LONG),
                               max_new_tokens=new, temperature=1.0)]


_built, _at_birth = {}, {}


def _engine(name):
    """Built once a path and warmed, so that no test times a compile."""
    if name not in _built:
        cfg, kw = ENGINES[name]
        eng = GenEngine(cfg, params=init_params(cfg, jax.random.PRNGKey(0)),
                        n_slots=4, max_seq_len=128, prompt_bucket=16,
                        decode_chunk=4, **kw)
        _at_birth[name] = {k: eng.stats.get(k) for k in KEYS}
        for tag in ("warm-a", "warm-b"):  # fresh, then the reuse paths
            eng.generate_blocking(_requests(tag))
        _built[name] = eng
    return _built[name]


@pytest.fixture(params=list(ENGINES))
def engine(request):
    return _engine(request.param)


def _drive(eng, reqs, between=0.0):
    """Submit and step to the end; -> (what the stats gained, wall seconds
    from the first step's entry to the last one's return)."""
    assert eng._starved_since is None and eng._step_returned is None
    s0 = dict(eng.stats)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    steps = 0
    while eng.active_count():
        steps += bool(eng.step())
        assert steps < 500
        if between and eng.active_count():
            time.sleep(between)
    wall = time.perf_counter() - t0
    assert all(r.stop_reason for r in reqs)
    return {k: eng.stats[k] - s0[k] for k in s0}, wall


def _host_phases(d):
    """The phases an interval without queued work can lie in."""
    return (d["t_step_admit_s"] - d["t_admit_fetch_s"] + d["t_step_sync_s"]
            + d["t_step_dispatch_s"] + d["t_step_deliver_s"]
            + d["t_between_steps_s"])


@pytest.mark.parametrize("key", KEYS)
@pytest.mark.parametrize("name", list(ENGINES))
def test_the_key_is_in_stats_from_construction(name, key):
    engine = _engine(name)
    assert _at_birth[name][key] == 0.0
    assert isinstance(engine.stats[key], float)


def test_starved_lies_in_the_host_phases_and_admit_fetch_in_admit(engine):
    d, wall = _drive(engine, _requests("lies"))
    assert d["engine_steps"] >= 3 and d["admitted"] == 3
    assert 0.0 < d["t_admit_fetch_s"] <= d["t_step_admit_s"]
    assert 0.0 < d["t_starved_s"]
    assert d["t_starved_s"] <= _host_phases(d) + SEAMS_S * d["engine_steps"]
    # to the letter: an interval opens after a download's span has closed
    # and closes before the next one opens
    assert d["t_starved_s"] <= wall - d["t_step_fetch_s"] - d["t_admit_fetch_s"]
    assert d["t_between_steps_s"] > 0.0


def test_every_launch_is_counted_and_landed(engine):
    n0 = engine._n_launched
    d, _ = _drive(engine, _requests("count"))
    launches = (d["prefill_calls"] + d["suffix_calls"] + d["decode_calls"]
                + d["verify_calls"])
    assert engine._n_launched - n0 == launches
    assert engine._n_landed == engine._n_launched
    if engine.model_config is STATE_CFG:
        # the shared span's prefill is launched and never downloaded
        assert d["state_copies"] >= 1
    if engine.spec_decode:
        assert d["verify_calls"] >= 1


def test_a_sleep_between_steps_is_the_callers_time_and_starves_the_device(
        engine):
    d, _ = _drive(engine, _requests("sleep", new=24), between=0.02)
    slept = 0.02 * (d["engine_steps"] - 1)
    assert d["engine_steps"] >= 5
    assert d["t_between_steps_s"] >= slept
    assert d["t_starved_s"] >= slept
    assert d["t_starved_s"] <= _host_phases(d) + SEAMS_S * d["engine_steps"]


@pytest.mark.parametrize("idle_steps", [0, 3], ids=["unstepped", "stepped"])
def test_an_engine_without_requests_is_idle_not_starved(engine, idle_steps):
    """Left alone for 0.2 s and then fed: whether its caller steps it
    meanwhile (`gen/server.py`) or not (`ColocatedEngine`), none of the
    0.2 s is counted."""
    _drive(engine, _requests(f"before-{idle_steps}"))
    assert engine._starved_since is None and engine._step_returned is None
    s0 = dict(engine.stats)
    for _ in range(idle_steps):
        assert engine.step() == 0
        time.sleep(0.2 / idle_steps)
    if not idle_steps:
        time.sleep(0.2)
    assert [engine.stats[k] - s0[k] for k in KEYS] == [0.0, 0.0, 0.0]
    d, wall = _drive(engine, _requests(f"after-{idle_steps}"))
    assert d["t_starved_s"] <= wall - d["t_step_fetch_s"] - d["t_admit_fetch_s"]
    assert d["t_between_steps_s"] <= wall


def test_abort_all_leaves_no_open_interval(engine):
    reqs = _requests("abort", new=40)
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert engine._starved_since is not None
    assert engine._step_returned is not None
    s0 = dict(engine.stats)
    assert engine.abort_all("abort") == 3
    assert engine._starved_since is None and engine._step_returned is None
    time.sleep(0.05)
    assert engine.step() == 0
    assert [engine.stats[k] - s0[k] for k in KEYS] == [0.0, 0.0, 0.0]


def test_a_dispatch_that_raises_leaves_no_open_interval(engine, monkeypatch):
    reqs = _requests("raise", new=40)
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert engine._starved_since is not None

    def refuse(*a, **kw):
        raise RuntimeError("no such program")

    with monkeypatch.context() as m:
        m.setattr(engine, "_decode_fn", refuse)
        m.setattr(engine, "_verify_fn", refuse)
        with pytest.raises(RuntimeError, match="no such program"):
            engine.step()
    assert engine._starved_since is None and engine._step_returned is None
    assert engine._dev_state is None
    # the engine goes on from its host mirrors, and the ledger with it
    while engine.active_count():
        engine.step()
    assert all(r.stop_reason == "length" for r in reqs)
    assert engine._n_landed == engine._n_launched


def test_a_weight_swap_drops_the_open_interval(engine):
    reqs = _requests("swap", new=16)
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert engine._starved_since is not None
    s0 = dict(engine.stats)
    time.sleep(0.05)
    engine.swap_weights_live(engine.params)
    assert engine._starved_since is None and engine._step_returned is None
    while engine.active_count():
        engine.step()
    # the 50 ms before the swap were the pause's, which `publish_swap` times
    assert engine.stats["t_between_steps_s"] - s0["t_between_steps_s"] < 0.05


def test_only_the_newest_launch_landing_opens_an_interval():
    """Two tiers, two dispatches a step: the first download returns while the
    second program is queued."""
    eng = _engine("tiers")
    seen = []
    landed = eng._landed

    def watch(launch):
        landed(launch)
        seen.append((launch, eng._n_launched, eng._starved_since is not None))

    eng._landed = watch
    try:
        _drive(eng, _requests("two"))
    finally:
        del eng._landed
    assert any(launch < newest for launch, newest, _ in seen)
    assert all(opened == (launch == newest) for launch, newest, opened in seen)


@pytest.mark.parametrize("key", KEYS)
def test_the_key_is_on_the_metrics_surface_and_in_the_documents(key):
    engine = _engine("grid")
    server = GenServer(engine)  # its collector samples `engine.stats`
    assert server.engine is engine
    served = telemetry.parse_prometheus_text(telemetry.GEN.render_prometheus())
    assert served[f"areal_gen_{key}_total"][""] == engine.stats[key] > 0.0
    with open(os.path.join(REPO, "tests/data/metrics_schema.json")) as f:
        assert f"areal_gen_{key}_total" in json.load(f)["gen"]
    with open(os.path.join(REPO, "docs/observability.md")) as f:
        assert f"`{key}`" in f.read()
