"""The one totals table behind every `areal/` host span and host counter
(`utils/telemetry.py span / count / totals / session_totals`), its view on
the three /metrics surfaces, the executor's gate counters that feed it, and
the benchmark's reader of it (`benchmarks/readers/program_total_per.py`)."""

import asyncio
import json
import os
import re
import sys
import threading
import time

import pytest

from areal_tpu.api.config import GenerationHyperparameters, InferenceEngineConfig
from areal_tpu.utils import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.lib import loader  # noqa: E402


@pytest.fixture()
def table(monkeypatch):
    """The process-wide table, emptied for one test (and put back after)."""
    monkeypatch.setattr(telemetry, "_totals", {})
    monkeypatch.setattr(telemetry, "_session", {})
    monkeypatch.setattr(telemetry, "_session_open", False)
    return telemetry


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------


def test_table_totals_a_span_and_a_counter_from_three_threads(table):
    """The program's three threads (trainer, serving, the runner's event
    loop) add to one table: no update may be lost."""
    n, nap = 40, 0.001

    def work():
        for _ in range(n):
            with telemetry.span("threaded"):
                time.sleep(nap)
        for _ in range(50 * n):
            telemetry.count("threaded_items", 3)

    threads = [threading.Thread(target=work) for _ in range(3)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    got = telemetry.totals()
    assert got["n_threaded"] == 3 * n
    assert got["threaded_items"] == 3 * 50 * n * 3
    assert got["t_threaded_s"] >= 3 * n * nap


def test_overlapping_awaited_spans_each_keep_their_own_start(table):
    async def one():
        with telemetry.span("awaited"):
            await asyncio.sleep(0.03)

    async def three():
        await asyncio.gather(one(), one(), one())

    t0 = time.perf_counter()
    asyncio.run(three())
    wall = time.perf_counter() - t0
    got = telemetry.totals()
    assert got["n_awaited"] == 3
    # three overlapping spans of 30 ms: their total is three times the wall
    assert got["t_awaited_s"] >= 0.09
    assert got["t_awaited_s"] > 1.5 * wall or wall > 0.09


def test_a_span_has_one_sink_and_survives_an_exception(table):
    """Given `totals=` (the engine's step phases) the seconds go there and
    nowhere else: no lock on the serving thread, no second export."""
    mine = {}
    with pytest.raises(KeyError):
        with telemetry.span("mine", mine):
            raise KeyError("x")
    assert mine["t_mine_s"] >= 0.0 and "n_mine" not in mine
    assert telemetry.totals() == {}
    with pytest.raises(KeyError):
        with telemetry.span("shared"):
            raise KeyError("x")
    got = telemetry.totals()
    assert got["n_shared"] == 1 and got["t_shared_s"] >= 0.0


def test_a_count_inside_a_span_is_added_when_the_span_ends(table):
    """So that the span's seconds, its call and its counts reach the same
    tables together: ratios between them cannot mix two moments."""
    with telemetry.span("outer"):
        telemetry.count("outer_items", 2)
        with telemetry.span("inner"):
            telemetry.count("inner_items", 3)
            assert telemetry.totals() == {}
        got = telemetry.totals()
        assert got["inner_items"] == 3 and got["n_inner"] == 1
        assert "outer_items" not in got
        telemetry.count("outer_items", 5)
    assert telemetry.totals()["outer_items"] == 7
    # a span's counts survive the exception that ends it
    with pytest.raises(KeyError):
        with telemetry.span("broken"):
            telemetry.count("broken_items")
            raise KeyError("x")
    assert telemetry.totals()["broken_items"] == 1


def test_counts_of_overlapping_awaited_spans_stay_with_their_own(table):
    seen = {}

    async def one(i):
        with telemetry.span("task"):
            telemetry.count(f"task_{i}_before")
            await asyncio.sleep(0.01 * (3 - i))
            telemetry.count(f"task_{i}_after")
        seen[i] = telemetry.totals()

    async def three():
        await asyncio.gather(one(0), one(1), one(2))

    asyncio.run(three())
    # task 2 ends first: its counts are in, the others' are still pending
    assert seen[2]["task_2_before"] == seen[2]["task_2_after"] == 1
    assert "task_0_before" not in seen[2] and "task_1_before" not in seen[2]
    got = telemetry.totals()
    assert got["n_task"] == 3
    assert all(got[f"task_{i}_{w}"] == 1 for i in range(3)
               for w in ("before", "after"))


def test_count_carries_caller_measured_seconds(table):
    telemetry.count("t_measured_s", 0.25)
    telemetry.count("t_measured_s", 0.5)
    telemetry.count("plain")
    assert telemetry.totals() == {"t_measured_s": 0.75, "plain": 1}


def test_totals_is_a_copy(table):
    telemetry.count("copied")
    telemetry.totals()["copied"] = 99
    assert telemetry.totals()["copied"] == 1


# ---------------------------------------------------------------------------
# the profiler session's totals
# ---------------------------------------------------------------------------


SESSIONS_SCRIPT = """
import json, sys, time
import jax.profiler
from areal_tpu.utils import telemetry

opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
snaps = {}
with telemetry.span("phase"):
    pass
telemetry.count("items", 5)
snaps["before"] = telemetry.session_totals()

with telemetry.span("begun_before"):
    telemetry.count("begun_before_items", 4)
    jax.profiler.start_trace(sys.argv[1] + "/s1", profiler_options=opts)
    time.sleep(0.002)
for _ in range(3):
    with telemetry.span("phase"):
        time.sleep(0.001)
telemetry.count("items", 2)
snaps["inside"] = telemetry.session_totals()
with telemetry.span("ends_after"):
    telemetry.count("ends_after_items", 6)
    jax.profiler.stop_trace()
snaps["closed"] = telemetry.session_totals()

with telemetry.span("phase"):
    pass
telemetry.count("items", 7)
snaps["after"] = telemetry.session_totals()

jax.profiler.start_trace(sys.argv[1] + "/s2", profiler_options=opts)
telemetry.count("items", 1)
snaps["second"] = telemetry.session_totals()
jax.profiler.stop_trace()
snaps["cumulative"] = telemetry.totals()
print("SNAPS " + json.dumps(snaps))
"""


@pytest.fixture(scope="module")
def sessions(tmp_path_factory):
    """Two real profiler sessions on the CPU backend with spans and counts
    before, across the start of, inside, across the end of, between and
    after them; -> the snapshots of `session_totals()` along the way.  In a
    process of its own: a profiler session is process-wide state, and a test
    worker that has held one goes on to compile hundreds of programs for
    other files."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c", SESSIONS_SCRIPT,
         str(tmp_path_factory.mktemp("sessions"))],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("SNAPS ")][-1]
    return json.loads(line[len("SNAPS "):])


def test_session_totals_is_empty_before_a_profiler_session(sessions):
    assert sessions["before"] == {}


def test_session_totals_counts_only_what_ended_inside(sessions):
    inside = sessions["inside"]
    assert inside["n_phase"] == 3 and inside["items"] == 2
    assert inside["t_phase_s"] >= 0.003


def test_a_span_belongs_whole_to_the_session_in_which_it_ends(sessions):
    """With what its body counted, whenever that was: its ratios never mix
    the two sides of a session's edge, and consecutive sessions count every
    span once, so a mean length is not biased towards short spans."""
    inside, closed = sessions["inside"], sessions["closed"]
    assert inside["n_begun_before"] == 1 and inside["begun_before_items"] == 4
    assert inside["t_begun_before_s"] >= 0.002
    assert "n_ends_after" not in closed and "ends_after_items" not in closed
    assert sessions["cumulative"]["n_ends_after"] == 1
    assert sessions["cumulative"]["ends_after_items"] == 6


def test_session_totals_is_frozen_once_the_session_closed(sessions):
    assert sessions["closed"] == sessions["inside"]
    assert sessions["after"] == sessions["inside"]


def test_the_next_session_starts_afresh(sessions):
    assert sessions["second"] == {"items": 1}


def test_the_cumulative_table_counts_everything(sessions):
    assert sessions["cumulative"]["n_phase"] == 5
    assert sessions["cumulative"]["items"] == 15


def test_a_late_reader_of_the_profilers_flag_cannot_lose_a_begun_table(
        table, monkeypatch):
    """The flag is read under the table's lock: a thread that read "no
    session" and was preempted before it could act used to clear the open
    mark after another thread had begun the new table, and the next caller
    then began it again, losing the first adds."""
    flag = {"on": False}

    class Annotation:
        @staticmethod
        def is_enabled():
            # whoever reads the flag holds the lock
            assert telemetry._totals_lock.locked()
            return flag["on"]

    monkeypatch.setattr(telemetry, "_annotation", Annotation)
    telemetry.count("early")
    flag["on"] = True
    telemetry.count("first")
    telemetry.count("second")
    assert telemetry.session_totals() == {"first": 1, "second": 1}
    flag["on"] = False
    telemetry.count("late")
    assert telemetry.session_totals() == {"first": 1, "second": 1}
    assert telemetry.totals() == {"early": 1, "first": 1, "second": 1, "late": 1}


# ---------------------------------------------------------------------------
# /metrics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("surface", ["GEN", "ROUTER", "TRAIN"])
def test_metrics_text_carries_the_table(table, surface):
    with telemetry.span("scraped"):
        time.sleep(0.001)
    telemetry.count("t_gate_blocked_s", 0.5)
    telemetry.count("wait_running_sum", 4)
    text = getattr(telemetry, surface).render_prometheus()
    got = telemetry.parse_prometheus_text(text)
    assert "# TYPE areal_span_seconds_total counter" in text
    seconds = got["areal_span_seconds_total"]
    assert seconds['{span="scraped"}'] == pytest.approx(
        telemetry.totals()["t_scraped_s"])
    assert seconds['{span="gate_blocked"}'] == 0.5
    assert got["areal_span_calls_total"]['{span="scraped"}'] == 1
    assert "# TYPE areal_count_total counter" in text
    assert got["areal_count_total"]['{name="wait_running_sum"}'] == 4


# ---------------------------------------------------------------------------
# the benchmark's reader
# ---------------------------------------------------------------------------

HAND_BUILT = {"t_rollout_wait_s": 6.0, "n_rollout_wait": 8,
              "t_gate_blocked_s": 1.5, "trajectories_consumed": 0}
CTX = {"window_s": 12.0, "counts": {"steps": 4, "none": 0}}


@pytest.mark.parametrize("spec, want", [
    # seconds over another entry of the table, read in ms
    ({"total": "t_rollout_wait_s", "per_total": "n_rollout_wait"}, 750.0),
    # a share: scale 100
    ({"total": "t_gate_blocked_s", "per_total": "t_rollout_wait_s",
      "scale": 100.0}, 25.0),
    # over a count the kind reports
    ({"total": "t_rollout_wait_s", "per": "steps"}, 1500.0),
    # over the window's seconds when the file names neither
    ({"total": "t_rollout_wait_s", "scale": 1.0}, 0.5),
    # zero divisors and a missing total: nothing to read
    ({"total": "t_rollout_wait_s", "per_total": "trajectories_consumed"}, None),
    ({"total": "t_rollout_wait_s", "per_total": "no_such_key"}, None),
    ({"total": "t_rollout_wait_s", "per": "none"}, None),
    ({"total": "no_such_total", "per_total": "n_rollout_wait"}, None),
])
def test_program_total_per_on_a_hand_built_table(monkeypatch, spec, want):
    monkeypatch.setattr(telemetry, "session_totals", lambda: dict(HAND_BUILT))
    got = loader.load_reader("program_total_per")(CTX, spec)
    assert got == (pytest.approx(want) if want is not None else None)


def test_program_total_per_reads_nothing_from_a_program_without_the_table(
        monkeypatch):
    """The parent commit's `telemetry` has no `session_totals`."""
    monkeypatch.delattr(telemetry, "session_totals")
    read = loader.load_reader("program_total_per")
    assert read(CTX, {"total": "t_rollout_wait_s",
                      "per_total": "n_rollout_wait"}) is None


def _program_source():
    for root, _, files in os.walk(os.path.join(REPO, "areal_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    yield f.read()


def _table_keys():
    """Every key the program writes into the table, from its source: a span
    without a `totals` argument, and every `telemetry.count`."""
    spans, counts = set(), set()
    for text in _program_source():
        spans |= set(re.findall(r'telemetry\.span\(\s*"(\w+)"\s*\)', text))
        counts |= set(re.findall(r'telemetry\.count\(\s*"(\w+)"', text))
    return spans, counts


SPANS, COUNTS = _table_keys()
TABLE_KEYS = {f"t_{s}_s" for s in SPANS} | {f"n_{s}" for s in SPANS} | COUNTS


def test_the_table_holds_what_the_inventory_was_made_from():
    assert SPANS == {"rollout_wait", "episode", "generate", "reward", "logp",
                     "advantages", "pack", "update", "export_params"}
    assert COUNTS == {"wait_running_sum", "t_gate_blocked_s",
                      "trajectories_consumed", "t_ready_wait_s"}


NEW_METRICS = [
    "loop_episodes_running_at_wait", "loop_gate_blocked_pct",
    "loop_episode_ms", "loop_episode_generate_ms", "loop_episode_reward_ms",
    "loop_ready_wait_ms_per_trajectory", "loop_train_logp_ms_per_step",
    "loop_train_advantages_ms_per_step", "loop_train_pack_ms_per_step",
    "loop_train_update_dispatch_ms_per_step", "loop_publish_export_ms",
    "train_pack_ms_per_step", "train_update_dispatch_ms_per_step",
    "loop_step_admit_ms", "loop_step_sync_ms", "loop_step_dispatch_ms",
    "loop_step_fetch_ms", "loop_step_deliver_ms", "loop_queue_wait_ms",
    "loop_live_slots_per_pass", "rollout_live_slots_per_pass",
    "rollout_step_starved_ms", "rollout_admit_device_wait_ms",
    "rollout_between_steps_ms", "loop_step_starved_ms",
]


def _metric_files():
    """The files of `NEW_METRICS` and, while they exist, their copies for
    other cells (`<name>.<suffix>.json`, the same reader): a benchmark issue
    that merges a copy into its file deletes a case here, no more."""
    d = os.path.join(loader.BENCH_ROOT, "layer_metrics")
    stems = sorted(f[:-len(".json")] for f in os.listdir(d)
                   if f.endswith(".json"))
    return [s for s in stems if s.split(".")[0] in NEW_METRICS]


@pytest.fixture(scope="module")
def benchmark_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


LOADER_SCRIPT = """
import json, sys
from benchmarks.lib import loader
out = {}
for cell in json.load(open("BENCHMARK.json"))["workloads"]:
    loader.load_cell(cell["name"])
    out[cell["name"]] = [{k: v for k, v in m.items() if k != "read"}
                         for m in loader.load_layer_metrics(cell["name"])]
print("LOADED " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def loaded_metrics():
    """What the harness's loader makes of every cell's metric files, each
    reader resolved.  In a process of its own: loading the reader
    `device_time_in_scope` registers a `jax.monitoring` listener that asks the
    client for its live executables on EVERY later compile and pins them
    (`benchmarks/readers/kept_executables.py`); a test worker that carried
    it went on to crash inside XLA:CPU compiles of other files."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-c", LOADER_SCRIPT], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("LOADED ")][-1]
    return json.loads(line[len("LOADED "):])


@pytest.mark.parametrize("name", _metric_files())
def test_new_metric_file_agrees_with_the_benchmark(
        benchmark_json, loaded_metrics, name):
    """By the loader's rule (`benchmarks/lib/loader.py load_layer_metrics`):
    a file that lists `cells` is for those cells and its entry lists them as
    `workloads`; a file without belongs to every cell of its `moves` metric,
    and its entry lists none."""
    path = os.path.join(loader.BENCH_ROOT, "layer_metrics", f"{name}.json")
    with open(path) as f:
        spec = json.load(f)
    assert spec["name"] == name
    # `counter_per` and `program_total_per` register nothing when loaded
    assert spec["reader"] in ("counter_per", "program_total_per")
    assert callable(loader.load_reader(spec["reader"]))
    moved = [m for m in benchmark_json["end_to_end"]
             if m["name"] == spec["moves"]]
    assert len(moved) == 1
    every = moved[0].get("workloads") or [
        w["name"] for w in benchmark_json["workloads"]]
    cells = spec.get("cells", every)
    assert cells and set(cells) <= set(every)
    assert [c for c in loaded_metrics if spec in loaded_metrics[c]] == [
        w["name"] for w in benchmark_json["workloads"] if w["name"] in cells]
    entry = [m for m in benchmark_json["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert entry[0].get("workloads") == spec.get("cells")
    for key in ("unit", "layer", "moves", "source"):
        assert entry[0][key] == spec[key]
    assert entry[0]["source"] in ("program_span", "program_counter")
    # the table's keys are the program's: a span or counter that exists
    if spec["reader"] == "program_total_per":
        for key in (spec["total"], spec.get("per_total")):
            assert key is None or key in TABLE_KEYS, key


def test_every_key_of_the_table_is_read_by_a_metric_file():
    """A span or counter nobody reads does not stay: its generic export
    (`areal_span_*_total{span=}`, `areal_count_total{name=}`) is every
    key's, so it is no reader."""
    read = set()
    for name in _metric_files():
        path = os.path.join(loader.BENCH_ROOT, "layer_metrics", f"{name}.json")
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "program_total_per":
            read |= {spec["total"], spec.get("per_total")}
    unread = {s for s in SPANS if not {f"t_{s}_s", f"n_{s}"} & read}
    unread |= COUNTS - read
    assert not unread, unread


def _inventory_rows():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        doc = f.read()
    table = doc[doc.index("| span or counter | where |"):]
    rows = []
    for line in table.splitlines()[2:]:
        if not line.startswith("|"):
            break
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        assert len(cells) == 4, line
        rows.append((cells[0], cells[3]))
    return rows


INVENTORY = _inventory_rows()


def _names(metric_pattern):
    """`loop_step_*_ms[.x/.y]` -> a regular expression over metric names."""
    base, _, optional = metric_pattern.partition("[")
    pat = re.escape(base).replace(r"\*", r"\w+")
    if optional:
        alts = "|".join(re.escape(a) for a in optional.rstrip("]").split("/"))
        pat += f"(?:{alts})?"
    return re.compile(pat + r"\Z")


@pytest.mark.parametrize("what, read_by", INVENTORY,
                         ids=[w.split("`")[1] for w, _ in INVENTORY])
def test_every_row_of_the_inventory_has_a_reader(benchmark_json, what, read_by):
    """A benchmark metric that exists, or a section of the documents that
    describes an operator's use.  The generic exports every key of the table
    gets are no reader."""
    metrics = {m["name"] for m in benchmark_json["per_layer"]}
    named = [t for t in re.findall(r"`([^`]+)`", read_by)
             if not t.startswith(("areal_span_", "areal_count_"))]
    found = [t for t in named if any(_names(t).match(m) for m in metrics)]
    sections = re.findall(r"\]\((?:[\w.]+)?#[\w-]+\)", read_by)
    assert found or sections, (what, read_by)


def test_every_key_of_the_table_is_in_the_inventory():
    listed = " ".join(what for what, _ in INVENTORY)
    missing = [k for k in sorted(SPANS | COUNTS) if f"`{k}`" not in listed]
    assert not missing, missing


# ---------------------------------------------------------------------------
# the executor's gate counters, on the fake engine
# ---------------------------------------------------------------------------


def _reward_len(prompt, completion, prompt_ids, completion_ids, **kw):
    return float(len(completion_ids))


def _prepare_batches(addr, n_batches, **cfg_kwargs):
    from areal_tpu.engine.jax_remote import RemoteJaxEngine
    from areal_tpu.utils.dataloader import StatefulDataLoader
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    cfg = InferenceEngineConfig(
        experiment_name="e", trial_name="t", request_timeout=10, **cfg_kwargs)
    eng = RemoteJaxEngine(cfg)
    eng.initialize(addr=addr)
    try:
        wf = RLVRWorkflow(
            reward_fn=_reward_len,
            gconfig=GenerationHyperparameters(max_new_tokens=8, n_samples=2),
        )
        dl = StatefulDataLoader(
            [{"input_ids": [i + 1]} for i in range(64)],
            batch_size=cfg.consumer_batch_size)
        for _ in range(n_batches):
            batch = eng.prepare_batch(dl, workflow=wf)
            assert batch["input_ids"].shape[0] == 2 * cfg.consumer_batch_size
            eng.set_version(eng.get_version() + 1)
    finally:
        eng.destroy()
    return telemetry.totals()


@pytest.fixture()
def fake_server():
    from tests.fake_server import FakeGenServer

    s = FakeGenServer(completion=list(range(100, 110)), chunk_size=1024)
    addr = s.start()
    yield addr
    s.stop()


def test_gate_counters_add_up(table, fake_server):
    got = _prepare_batches(fake_server, 4, consumer_batch_size=2,
                           max_concurrent_rollouts=16,
                           max_head_offpolicyness=2)
    assert got["trajectories_consumed"] == 4 * 2
    # one span a call, whether it returned a batch or timed out
    assert got["n_rollout_wait"] >= 4
    assert 0 <= got["wait_running_sum"] <= 16 * got["n_rollout_wait"]
    assert 0.0 <= got["t_gate_blocked_s"] <= got["t_rollout_wait_s"]
    assert got["t_ready_wait_s"] >= 0.0
    # an episode is its generation and then its rewards, one per sample
    # (an episode cancelled when the engine went closed its span early)
    assert got["n_episode"] >= got["trajectories_consumed"]
    assert got["n_generate"] >= got["trajectories_consumed"]
    assert got["n_reward"] >= 2 * got["trajectories_consumed"]
    assert got["t_episode_s"] >= got["t_reward_s"]
    assert set(got) <= TABLE_KEYS


def test_gate_blocked_time_is_counted_when_the_gate_holds_inputs(
        table, fake_server):
    """One rollout at a time and a batch of two: the second input waits on
    the gate while the first runs, inside `rollout_wait`."""
    got = _prepare_batches(fake_server, 2, consumer_batch_size=2,
                           max_concurrent_rollouts=1,
                           max_head_offpolicyness=4)
    assert got["trajectories_consumed"] == 4
    assert got["wait_running_sum"] <= 1 * got["n_rollout_wait"]
    assert 0.0 < got["t_gate_blocked_s"] <= got["t_rollout_wait_s"]


def test_enable_rollout_tracing_is_gone():
    assert not hasattr(InferenceEngineConfig(), "enable_rollout_tracing")
