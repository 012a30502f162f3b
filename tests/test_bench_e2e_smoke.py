"""CPU smoke for the primary-metric instrument (VERDICT r6 #7): the
scripts/bench_e2e_grpo.py subprocess must produce a well-formed result
JSON on the REAL fleet slice (--transport remote: GenServer over HTTP +
RemoteJaxEngine + transfer-mode publish) in BOTH publish modes, so the
bench cannot rot silently between on-chip runs.

Tiny model, 2 measured steps each — speed is the `grpo_async_loop`
cell's to state (PERF.md); this only proves the instrument still runs
end-to-end.  The abort-mode run doubles as the gsm8k-synth dataset path
(the satellite importer for dataset/gsm8k_synth.py), exercising the real
math reward through the rollout loop."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "scripts", "bench_e2e_grpo.py")

_COMMON = [
    "--model", "tiny",
    "--transport", "remote",
    "--modes", "async",
    "--steps", "2",
    "--warmup", "1",
    "--batch-size", "4",
    "--group-size", "2",
    "--n-slots", "8",
    "--max-seq-len", "256",
    "--max-new-tokens", "32",
]


def _run_bench(extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, BENCH] + _COMMON + extra,
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the result is the last stdout line that parses as a JSON object
    for line in reversed(proc.stdout.strip().split("\n")):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    pytest.fail(f"no JSON result line in stdout: {proc.stdout[-500:]}")


def test_remote_live_publish_smoke():
    out = _run_bench(["--publish-mode", "live",
                      "--prompt-len", "32"])
    assert out["transport"] == "remote" and out["publish_mode"] == "live"
    a = out["async"]
    assert a["steps"] == 2 and a["trajectories"] > 0
    assert a["trajs_per_sec_per_chip"] > 0
    # live commit: the pause window is a pointer swap, not a placement
    assert a["pause_window_s_mean"] < 1.0
    # group fan-out accounting rode along (group_size 2)
    assert out["shared_prefill"]["shared_tokens"] > 0


@pytest.mark.slow
def test_remote_interrupt_publish_smoke():
    """ISSUE 12 satellite: the remote/interrupt combination had never run
    in the suite (remote+live and remote+abort are covered below) — the
    `stale_from`-marked e2e BENCH fields kept being carried forward on
    that gap.  `interrupt` publishes over the HTTP fleet slice abort
    in-flight requests and clients resume with their accumulated tokens,
    so the bench must complete and report sane throughput/fan-out
    accounting under that storm."""
    out = _run_bench(["--publish-mode", "interrupt",
                      "--prompt-len", "32"])
    assert out["transport"] == "remote"
    assert out["publish_mode"] == "interrupt"
    a = out["async"]
    assert a["steps"] == 2 and a["trajectories"] > 0
    assert a["trajs_per_sec_per_chip"] > 0
    # group fan-out accounting rode along (group_size 2), and the
    # interrupt/resume churn keeps the token split self-consistent
    sp = out["shared_prefill"]
    assert sp["shared_tokens"] > 0
    assert sp["suffix_tokens"] >= 0 and sp["prefill_tokens"] > 0


@pytest.fixture(scope="module")
def abort_run(tmp_path_factory):
    """One abort-mode bench run shared by the smoke + lifecycle tests
    (the subprocess is the expensive part; --telemetry-dir rides along)."""
    tdir = tmp_path_factory.mktemp("telemetry")
    out = _run_bench(["--publish-mode", "abort",
                      "--dataset", "gsm8k-synth",
                      "--telemetry-dir", str(tdir)])
    return out, tdir


@pytest.mark.slow
def test_remote_chaos_smoke():
    """ISSUE 11: the chaos instrument itself — a seeded FaultProxy between
    client and server — must complete the run and report the replayable
    injected-fault log plus the trajectory-loss fraction.  Slow-marked:
    the fast kill-one-of-two chaos acceptance lives in test_chaos_e2e.py;
    this proves the bench-side harness (CI chaos-smoke runs it too)."""
    out = _run_bench(["--publish-mode", "live",
                      "--prompt-len", "32",
                      "--chaos", "--chaos-seed", "5", "--chaos-rate", "0.3"])
    chaos = out["chaos"]
    assert chaos["seed"] == 5
    assert chaos["plan_size"] > 0
    assert chaos["injected"], "rate=0.3 must inject on an exercised call"
    # every injected record is (endpoint, call_index, kind)
    assert all(ep.startswith("/") and isinstance(i, int) and kind
               for ep, i, kind in chaos["injected"])
    assert 0.0 <= chaos["trajectory_loss_fraction"] <= 1.0
    # goodput under fire: the run still made progress
    assert out["async"]["trajectories"] > 0
    assert out["async"]["trajs_per_sec_per_chip"] > 0


def test_remote_abort_publish_gsm8k_synth_smoke(abort_run):
    out, _ = abort_run
    assert out["publish_mode"] == "abort"
    assert out["dataset"] == "gsm8k-synth"
    a = out["async"]
    assert a["steps"] == 2 and a["trajectories"] > 0
    # the real math reward ran (a from-scratch tiny model scores ~0, but
    # the field must exist and be a finite fraction)
    assert 0.0 <= a["reward_mean"] <= 1.0


def test_trajectory_lifecycle_reconstructs_from_jsonl(abort_run):
    """ISSUE 10 acceptance: one full trajectory lifecycle — submit ->
    admission -> prefill -> decode -> (interrupt -> resume at the abort
    publish) -> reward -> trainer consumption with staleness — must be
    reconstructable purely from the JSONL event log."""
    out, tdir = abort_run
    tele = out["telemetry"]
    assert tele["n_events"] > 0
    events_path = tele["events_jsonl"]
    assert os.path.exists(events_path)
    with open(events_path) as f:
        evs = [json.loads(line) for line in f]
    assert len(evs) == tele["n_events"]

    by_trace = {}
    for e in evs:
        if "trace_id" in e:
            by_trace.setdefault(e["trace_id"], []).append(e)
    consumed = {e["trace_key"]: e for e in evs
                if e["event"] == "train_consume"
                and e.get("trace_key") is not None}

    # at least one trajectory shows the FULL chain, in timestamp order,
    # ending in a trainer consumption joined via trace_key
    full = []
    for tid, tes in by_trace.items():
        names = [e["event"] for e in tes]
        if not {"rollout_submit", "admission", "prefill", "gen_done",
                "reward"} <= set(names):
            continue
        order = [names.index(n) for n in
                 ("rollout_submit", "admission", "prefill", "gen_done",
                  "reward")]
        assert order == sorted(order), (tid, names)
        tk = tes[0]["trace_key"]
        if tk in consumed:
            full.append((tid, tes, consumed[tk]))
    assert full, "no trajectory with a complete, trainer-joined lifecycle"
    tid, tes, tc = full[0]
    # prefill token split is self-consistent
    pf = next(e for e in tes if e["event"] == "prefill")
    assert pf["cold_tokens"] + pf["inherited_tokens"] == pf["total_tokens"]
    # consumption evidence carries the staleness measurement
    assert tc["staleness"] >= 0
    assert tc["consumed_version"] >= tc["behavior_version"]
    # decode made progress on some traced request (chunk events carry the
    # per-tier active trace-id lists)
    chunks = [e for e in evs if e["event"] == "decode_chunk"]
    traced_in_chunks = {t for e in chunks for t in e.get("trace_ids", ())}
    assert traced_in_chunks & set(by_trace)

    # abort-mode publishes interrupt in-flight requests; every interrupted
    # trace must show a later resume or re-admission (the pause/interrupt
    # evidence ROADMAP item 4 asks for)
    interrupted = {t: es for t, es in by_trace.items()
                   if any(e["event"] == "interrupt" for e in es)}
    assert interrupted, "abort publish produced no interrupt spans"
    for t, es in interrupted.items():
        it = min(e["ts"] for e in es if e["event"] == "interrupt")
        assert any(e["ts"] >= it and e["event"] in ("resume", "admission")
                   for e in es), t

    # sidecar artifacts: Chrome trace + metrics snapshot with the two
    # evidence histograms populated
    trace = json.load(open(tele["chrome_trace"]))
    phases = {e["ph"] for e in trace["traceEvents"]}
    assert "X" in phases and "i" in phases
    metrics = json.load(open(tele["metrics_snapshot"]))
    assert metrics["gen"]["areal_gen_pause_window_seconds_count"]["_"] >= 1
    assert (metrics["train"]
            ["areal_train_staleness_at_consumption_count"]["_"] >= 1)


def test_slo_report_reconstructs_recorded_run(abort_run):
    """ISSUE 14 acceptance: from one recorded e2e run the analyzer must
    produce an SLO report that is complete (zero dropped events, no
    orphan spans) and satisfies the accounting identity — the per-stage
    sums agree with each trajectory's client-measured end-to-end — plus
    the satellite latency percentiles in the bench JSON itself."""
    from areal_tpu.obs.slo import build_report, render_markdown

    out, _ = abort_run
    report = build_report(out["telemetry"]["events_jsonl"], run_id="smoke")
    comp = report["completeness"]
    assert comp["complete"], comp
    assert comp["dropped_events"] == 0
    acct = report["accounting"]
    assert acct["ok"], acct
    assert acct["checked"] > 0
    assert report["trajectories"]["closed"] > 0
    assert report["e2e_s"]["count"] > 0
    # real server spans in the log -> a true decomposition, not opaque
    assert "decode" in report["stages"]
    assert "admission_wait" in report["stages"]
    # abort publishes leave interrupt windows; staleness evidence joined
    assert report["staleness"] is not None
    md = render_markdown(report)
    assert "complete: **True**" in md and "stage:decode" in md

    # satellite: the bench JSON now carries client-side p50/p99 latency
    lat = out["async"]["latency"]
    assert lat["n"] > 0
    assert lat["e2e_s"]["count"] == lat["n"]
    assert 0 < lat["e2e_s"]["p50"] <= lat["e2e_s"]["p99"]
    assert lat["ttft_s"] is not None
    assert lat["ttft_s"]["p50"] <= lat["e2e_s"]["p99"]
