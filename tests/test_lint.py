"""areal-lint (ISSUE 3 + 9 + 18): fixture coverage for all ten
checkers, the mutation acceptance cases (fixture AND real code: deleted
locks, reordered acquisitions, off-ladder statics, double-free, renamed
wire keys, dropped schema metrics, broken config chains), the
signature-budget math cross-checks, the suppression-hygiene rules, the
AREAL_DEBUG_LOCKS runtime assertions, the CLI output formats, and the
tier-1 repo-clean gate."""

import asyncio
import json
import os
import threading

import pytest

from areal_tpu.analysis.async_blocking import check_async_blocking
from areal_tpu.analysis.core import (
    SourceFile,
    load_files,
    run_suite,
    suppression_hygiene,
    unsuppressed,
)
from areal_tpu.analysis.dead_modules import check_dead_modules
from areal_tpu.analysis.host_sync import check_host_sync
from areal_tpu.analysis.jit_signatures import (
    BUDGET_PATH,
    budget_drift,
    check_jit_signatures,
    compute_budgets,
    ladder_values,
    pow2_row_counts,
)
from areal_tpu.analysis.lock_discipline import check_lock_discipline
from areal_tpu.analysis.lock_order import check_lock_order
from areal_tpu.analysis.lockcheck import LockDisciplineError, lock_guarded
from areal_tpu.analysis.typestate import check_typestate
from areal_tpu.analysis.wire_contracts import (
    WireContracts,
    check_config_plumbing,
    check_payload_contracts,
    check_telemetry_contracts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "data", "lint")


def _fixture(name: str) -> SourceFile:
    return SourceFile.from_path(
        os.path.join(FIXTURES, name + ".py"), rel=name
    )


@pytest.fixture(scope="module")
def repo_findings():
    return run_suite(REPO)


@pytest.fixture(scope="module")
def repo_files():
    return load_files(REPO)


# ------------------------------- C1 ---------------------------------


def test_lock_positive_fixture_flags_every_violation():
    findings = check_lock_discipline(_fixture("lock_pos"))
    assert all(f.rule == "unlocked-field" for f in findings)
    # one finding per VIOLATION-marked line, nothing else
    src = open(os.path.join(FIXTURES, "lock_pos.py")).read()
    expected = {
        i + 1
        for i, line in enumerate(src.split("\n"))
        if "VIOLATION" in line
    }
    assert {f.line for f in findings} == expected


def test_lock_negative_fixture_is_clean():
    assert check_lock_discipline(_fixture("lock_neg")) == []


def test_deleting_with_lock_is_caught_in_fixture():
    """Acceptance: stripping `with self._lock:` from the clean fixture
    must produce findings for the now-unguarded accesses."""
    src = open(os.path.join(FIXTURES, "lock_neg.py")).read()
    assert "with self._lock:" in src
    mutated = src.replace("async with self._lock:", "if True:").replace(
        "with self._lock:", "if True:"
    )
    sf = SourceFile("lock_neg_mutated", mutated, rel="lock_neg_mutated")
    assert sf.tree is not None, sf.error
    findings = check_lock_discipline(sf)
    assert findings, "removing the lock guard went undetected"
    assert {f.rule for f in findings} == {"unlocked-field"}
    assert any("_queue" in f.message for f in findings)


def test_deleting_with_lock_is_caught_in_real_engine():
    """Acceptance: the same mutation against the REAL gen engine — every
    `with self._lock:` becomes a no-op block — must trip C1 on the
    engine's declared guarded fields."""
    path = os.path.join(REPO, "areal_tpu", "gen", "engine.py")
    src = open(path).read()
    assert src.count("with self._lock:") >= 5
    mutated = src.replace("with self._lock:", "if True:")
    findings = check_lock_discipline(
        SourceFile("engine_mutated", mutated, rel="engine_mutated")
    )
    hit_fields = {
        field
        for f in findings
        for field in ("_holdback", "_abort_gen")
        if field in f.message
    }
    assert hit_fields == {"_holdback", "_abort_gen"}, findings


def test_holds_annotation_requires_the_named_lock():
    src = (
        "import threading\n"
        "class C:\n"
        "    _GUARDED_FIELDS = {'_x': '_lock'}\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._x = 0\n"
        "    def wrong(self):  # holds: _other_lock\n"
        "        return self._x\n"
    )
    findings = check_lock_discipline(SourceFile("inline", src, rel="inline"))
    assert len(findings) == 1 and findings[0].line == 8


# ------------------------------- C2 ---------------------------------


def test_hostsync_positive_fixture():
    findings = check_host_sync(_fixture("hostsync_pos"))
    rules = sorted(f.rule for f in findings)
    assert rules == [
        "host-item",
        "host-sync",
        "host-sync",
        "host-sync",
        "host-upload",
        "unbucketed-shape",
        "unbucketed-shape",
    ]


def test_hostsync_negative_fixture_is_clean():
    assert check_host_sync(_fixture("hostsync_neg")) == []


def test_hostsync_only_applies_to_hot_files():
    src = open(os.path.join(FIXTURES, "hostsync_pos.py")).read()
    cold = src.replace("# areal-lint: hot-path", "")
    assert check_host_sync(SourceFile("cold", cold, rel="cold")) == []


# ------------------------------- C3 ---------------------------------


def test_async_positive_fixture():
    findings = check_async_blocking(_fixture("async_pos"))
    src = open(os.path.join(FIXTURES, "async_pos.py")).read()
    expected = {
        i + 1
        for i, line in enumerate(src.split("\n"))
        if "VIOLATION" in line
    }
    assert {f.line for f in findings} == expected


def test_async_negative_fixture_is_clean():
    assert check_async_blocking(_fixture("async_neg")) == []


# ------------------------------- C4 ---------------------------------


def test_dead_modules_fixture_tree():
    root = os.path.join(FIXTURES, "deadmod_tree")
    findings = check_dead_modules(root, load_files(root), package="myproj")
    by_mod = {f.path: f for f in findings}
    # flagged: the test-only module, the internal cycle, the suppressed
    # library surface — and nothing that a root actually reaches
    assert set(by_mod) == {
        "myproj/dead.py",
        "myproj/cycle_a.py",
        "myproj/cycle_b.py",
        "myproj/vendored.py",
    }
    assert not by_mod["myproj/dead.py"].suppressed  # test import ≠ alive
    assert by_mod["myproj/vendored.py"].suppressed
    assert "downstream" in by_mod["myproj/vendored.py"].suppress_reason


def test_gsm8k_synth_has_a_real_importer(repo_findings):
    """The satellite fix: dataset/gsm8k_synth.py is alive via the
    bench_e2e_grpo --dataset gsm8k-synth path, not via suppression."""
    synth = [
        f for f in repo_findings if "gsm8k_synth" in f.path
    ]
    assert synth == [], synth


# --------------------------- suppressions ----------------------------


def test_suppression_without_reason_is_flagged():
    src = "x = 1  # areal-lint: disable=host-sync\n"
    findings = suppression_hygiene(SourceFile("s", src, rel="s"))
    assert [f.rule for f in findings] == ["bad-suppression"]


def test_suppression_with_unknown_rule_is_flagged():
    src = "x = 1  # areal-lint: disable=no-such-rule because reasons\n"
    findings = suppression_hygiene(SourceFile("s", src, rel="s"))
    assert [f.rule for f in findings] == ["bad-suppression"]


def test_every_repo_suppression_carries_a_reason(repo_findings):
    for f in repo_findings:
        if f.suppressed:
            assert len(f.suppress_reason) > 10, f.render()


# ------------------------- runtime assertions ------------------------


def _make_guarded_class():
    @lock_guarded
    class Box:
        _GUARDED_FIELDS = {"_items": "_lock"}

        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def locked_append(self, x):
            with self._lock:
                self._items.append(x)

        def _append_holding(self, x):  # holds: _lock
            self._items.append(x)

        def locked_via_helper(self, x):
            with self._lock:
                self._append_holding(x)

        def unlocked_read(self):
            return self._items

    return Box


def test_runtime_guards_off_by_default(monkeypatch):
    monkeypatch.delenv("AREAL_DEBUG_LOCKS", raising=False)
    box = _make_guarded_class()()
    assert box.unlocked_read() == []  # no checking, no overhead


def test_runtime_guards_catch_unlocked_access(monkeypatch):
    monkeypatch.setenv("AREAL_DEBUG_LOCKS", "1")
    box = _make_guarded_class()()
    box.locked_append(1)
    box.locked_via_helper(2)  # holds:-style callee under the caller's lock
    with box._lock:
        assert box._items == [1, 2]
    with pytest.raises(LockDisciplineError):
        box.unlocked_read()
    with pytest.raises(LockDisciplineError):
        box._items = []


def test_runtime_guards_other_thread_cannot_satisfy(monkeypatch):
    monkeypatch.setenv("AREAL_DEBUG_LOCKS", "1")
    box = _make_guarded_class()()
    box._lock.acquire()  # main thread holds
    errors = []

    def probe():
        try:
            box.unlocked_read()
        except LockDisciplineError as e:
            errors.append(e)

    t = threading.Thread(target=probe)
    t.start()
    t.join()
    box._lock.release()
    assert len(errors) == 1


def test_runtime_guards_asyncio_flavor(monkeypatch):
    monkeypatch.setenv("AREAL_DEBUG_LOCKS", "1")

    @lock_guarded
    class Gate:
        _GUARDED_FIELDS = {"_running": "_lock"}

        def __init__(self):
            self._lock = asyncio.Lock()
            self._running = {}

        async def grant(self, k):
            async with self._lock:
                self._running[k] = 1

        def bare(self):
            return self._running

    async def run():
        g = Gate()
        await g.grant("a")
        with pytest.raises(LockDisciplineError):
            g.bare()  # nobody holds the lock: caught
        async with g._lock:
            assert g.bare() == {"a": 1}

    asyncio.run(run())


def test_gen_engine_annotations_match_runtime(monkeypatch):
    """The real engine's _GUARDED_FIELDS registry, exercised dynamically:
    direct unlocked access to a guarded field raises, the engine's own
    (lock-disciplined) paths pass — the same property the whole
    test_gen_engine module validates with the env flag on."""
    monkeypatch.setenv("AREAL_DEBUG_LOCKS", "1")
    import jax

    from areal_tpu.gen.engine import GenEngine, GenRequest
    from areal_tpu.models.model_config import tiny_config

    cfg = tiny_config(vocab_size=61, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    eng = GenEngine(cfg, n_slots=2, max_seq_len=64, prompt_bucket=16,
                    seed=0)
    assert type(eng).__name__.endswith("LockChecked")
    with pytest.raises(LockDisciplineError):
        _ = eng._holdback
    with pytest.raises(LockDisciplineError):
        eng._abort_gen += 1
    req = GenRequest(rid="r", input_ids=[1, 2, 3], max_new_tokens=4,
                     temperature=0.0)
    eng.generate_blocking([req])  # submit -> admit -> decode under guards
    assert req.stop_reason
    assert eng.abort_all() == 0  # abort path touches both guarded fields
    with eng._lock:
        assert eng._holdback == []


# ------------------------------- C5 ---------------------------------


def _violation_lines(name: str) -> set:
    src = open(os.path.join(FIXTURES, name + ".py")).read()
    return {
        i + 1
        for i, line in enumerate(src.split("\n"))
        if "# VIOLATION" in line
    }


def test_lockorder_positive_fixture():
    sf = _fixture("lockorder_pos")
    findings = check_lock_order({"lockorder_pos": sf})
    assert {f.line for f in findings} == _violation_lines("lockorder_pos")
    rules = {f.rule for f in findings}
    assert rules == {"lock-order", "blocking-under-lock", "atomicity-split"}


def test_lockorder_negative_fixture_is_clean():
    sf = _fixture("lockorder_neg")
    assert check_lock_order({"lockorder_neg": sf}) == []


def test_lock_reorder_is_caught_in_fixture():
    """Acceptance: inverting the declared `_flush -> _state` nesting in
    the clean fixture closes a cycle against the declaration."""
    src = open(os.path.join(FIXTURES, "lockorder_neg.py")).read()
    mutated = (
        src.replace("with self._flush:", "with self.__tmp__:")
        .replace("with self._state:", "with self._flush:")
        .replace("with self.__tmp__:", "with self._state:")
    )
    sf = SourceFile("m", mutated, rel="m")
    assert sf.tree is not None, sf.error
    findings = check_lock_order({"m": sf})
    assert any(
        f.rule == "lock-order" and "cycle" in f.message for f in findings
    ), findings


def test_lock_reorder_is_caught_in_real_router():
    """Acceptance: the same inversion against the REAL router — its
    `# lock-order: _flush_lock -> _lock` declaration makes the swapped
    nesting in _flush_and_update a cycle."""
    path = os.path.join(REPO, "areal_tpu", "gen", "router.py")
    src = open(path).read()
    assert "async with self._flush_lock:" in src
    mutated = (
        src.replace("async with self._flush_lock:", "async with self.__t__:")
        .replace("async with self._lock:", "async with self._flush_lock:")
        .replace("async with self.__t__:", "async with self._lock:")
    )
    sf = SourceFile("router_mutated", mutated, rel="router_mutated")
    assert sf.tree is not None, sf.error
    findings = check_lock_order({"router_mutated": sf})
    assert any(
        f.rule == "lock-order" and "cycle" in f.message for f in findings
    ), findings
    # the unmutated router is clean under the same single-file analysis
    clean = SourceFile(path, src, rel="router.py")
    assert check_lock_order({"router.py": clean}) == []


def test_holdback_overwrite_is_caught_in_real_engine():
    """Acceptance: reverting the _admit fix (merge -> blind overwrite of
    the guarded _holdback) re-trips the atomicity-split rule."""
    path = os.path.join(REPO, "areal_tpu", "gen", "engine.py")
    src = open(path).read()
    assert "self._holdback = leftover + self._holdback" in src
    mutated = src.replace(
        "self._holdback = leftover + self._holdback",
        "self._holdback = leftover",
    )
    findings = check_lock_order(
        {"engine.py": SourceFile("m", mutated, rel="engine.py")}
    )
    assert any(
        f.rule == "atomicity-split" and "_holdback" in f.message
        for f in findings
    ), findings
    clean = SourceFile(path, src, rel="engine.py")
    assert (
        check_lock_order({"engine.py": clean}) == []
    ), "unmutated engine must be C5-clean"


# ------------------------------- C6 ---------------------------------


def test_jitsig_positive_fixture():
    sf = _fixture("jitsig_pos")
    findings = check_jit_signatures({"jitsig_pos": sf})
    assert {f.line for f in findings} == _violation_lines("jitsig_pos")
    assert {f.rule for f in findings} == {"off-ladder-static"}


def test_jitsig_negative_fixture_is_clean():
    sf = _fixture("jitsig_neg")
    assert check_jit_signatures({"jitsig_neg": sf}) == []


def test_jitsig_only_applies_to_hot_files():
    src = open(os.path.join(FIXTURES, "jitsig_pos.py")).read()
    cold = src.replace("# areal-lint: hot-path", "#")
    sf = SourceFile("cold", cold, rel="cold")
    assert check_jit_signatures({"cold": sf}) == []


def test_off_ladder_keywindow_is_caught_in_real_engine():
    """Acceptance: an out-of-ladder key_window literal in the REAL decode
    dispatch is caught — the soak tests' runtime assertion, as a static
    proof."""
    path = os.path.join(REPO, "areal_tpu", "gen", "engine.py")
    src = open(path).read()
    anchor = (
        "key_window = round_up_to_bucket(\n"
        "                            span + n, self.prompt_bucket, M\n"
        "                        )"
    )
    assert anchor in src, "decode key_window bucketing moved; update test"
    mutated = src.replace(anchor, "key_window = 100")
    findings = check_jit_signatures(
        {"engine.py": SourceFile("m", mutated, rel="engine.py")}
    )
    assert any(
        f.rule == "off-ladder-static" and "key_window" in f.message
        for f in findings
    ), findings
    clean = SourceFile(path, src, rel="engine.py")
    assert check_jit_signatures({"engine.py": clean}) == []


def test_ladder_mirror_matches_runtime_bucketing():
    """The pure-python budget math must equal the runtime ladder exactly:
    the image of round_up_to_bucket over every feasible length is the
    enumerated ladder, and row padding counts match the pow2 rule."""
    from areal_tpu.utils.datapack import round_up_to_bucket

    for q, m in ((16, 256), (32, 256), (128, 2048)):
        image = {round_up_to_bucket(n, q, m) for n in range(1, m + 1)}
        assert image == set(ladder_values(q, m)), (q, m)
    for slots in (1, 2, 4, 8, 64):
        pads = {1 << max(0, (k - 1)).bit_length() for k in range(1, slots + 1)}
        assert len(pads) == pow2_row_counts(slots), slots


def test_signature_budget_is_fresh(repo_findings):
    """The checked-in budget matches the ladder math (the same condition
    `signature-budget-stale` enforces), and tampering is detected."""
    with open(os.path.join(REPO, BUDGET_PATH)) as f:
        doc = json.load(f)
    assert budget_drift(doc) == []
    ref = doc["reference_configs"]["tiered_decode_soak"]
    assert ref["budgets"] == compute_budgets(ref["config"])
    tampered = json.loads(json.dumps(doc))
    tampered["reference_configs"]["tiered_decode_soak"]["budgets"][
        "decode"
    ] += 1
    assert budget_drift(tampered) != []


# ------------------------------- C7 ---------------------------------


def test_typestate_positive_fixture():
    sf = _fixture("typestate_pos")
    findings = check_typestate({"typestate_pos": sf})
    assert {f.line for f in findings} == _violation_lines("typestate_pos")
    assert {f.rule for f in findings} == {
        "slot-double-free",
        "slot-lifecycle",
        "retained-unversioned",
    }


def test_typestate_negative_fixture_is_clean():
    sf = _fixture("typestate_neg")
    assert check_typestate({"typestate_neg": sf}) == []


def test_double_free_is_caught_in_real_engine():
    """Acceptance: turning _free's retained-prefix settle into a second
    `slot_req[s] = None` is a double-free of a retained cache row — the
    exact hazard the radix-refactor must not introduce."""
    path = os.path.join(REPO, "areal_tpu", "gen", "engine.py")
    src = open(path).read()
    # at _free's depth of indentation (abort_all settles it too, deeper)
    anchor = (
        "\n            self.retained_len[s] = self._retained_after(s)\n"
    )
    assert src.count(anchor) == 1, "update the _free mutation anchor"
    mutated = src.replace(anchor, "\n            self.slot_req[s] = None\n")
    findings = check_typestate(
        {"engine.py": SourceFile("m", mutated, rel="engine.py")}
    )
    assert any(f.rule == "slot-double-free" for f in findings), findings
    clean = SourceFile(path, src, rel="engine.py")
    assert check_typestate({"engine.py": clean}) == []


# ------------------------------- CLI ---------------------------------


def _load_cli():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "areal_lint_cli", os.path.join(REPO, "scripts", "lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_sarif_and_fingerprints(repo_findings):
    cli = _load_cli()
    active = unsuppressed(repo_findings)
    # fingerprints are line-drift-stable: same (path, rule, message)
    # hashes equal regardless of the line attribute
    for f in repo_findings[:5]:
        moved = type(f)(f.rule, f.path, f.line + 40, f.message)
        assert cli.fingerprint(f) == cli.fingerprint(moved)
    sarif = cli.to_sarif(repo_findings)
    assert sarif["version"] == "2.1.0"
    results = sarif["runs"][0]["results"]
    assert len(results) == len(repo_findings)
    for r in results:
        loc = r["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] >= 1
        assert r["partialFingerprints"]["arealLint/v1"]
    assert active == []  # and the repo itself stays SARIF-empty


def test_cli_baseline_roundtrip(tmp_path):
    """--write-baseline then --baseline suppresses exactly the recorded
    findings; a new finding still fails --check."""
    cli = _load_cli()
    from areal_tpu.analysis.core import Finding

    known = Finding("lock-order", "pkg/a.py", 10, "cycle via _lock")
    new = Finding("lock-order", "pkg/a.py", 20, "cycle via _other")
    baseline = {"fingerprints": [cli.fingerprint(known)]}
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(baseline))
    loaded = set(json.loads(bl.read_text())["fingerprints"])
    assert cli.fingerprint(known) in loaded
    assert cli.fingerprint(new) not in loaded


def test_cli_write_budget_is_idempotent(tmp_path):
    cli = _load_cli()
    doc = cli.render_budget_doc(cli.REFERENCE_CONFIGS)
    with open(os.path.join(REPO, BUDGET_PATH)) as f:
        checked_in = json.load(f)
    assert doc == checked_in, (
        "signature_budget.json is stale — run "
        "`python scripts/lint.py --write-budget`"
    )


def test_cli_explain_prints_wire_checker_catalog(capsys):
    """`--explain C8|C9|C10` prints the catalog entry and exits 0 without
    running the suite (ISSUE 18 satellite)."""
    cli = _load_cli()
    for checker, rule in (
        ("C8", "payload-contract"),
        ("C9", "metric-contract"),
        ("C10", "config-plumbing"),
    ):
        assert cli.main(["--explain", checker]) == 0
        out = capsys.readouterr().out
        assert rule in out
        assert "wire_contracts.json" in out


# ------------------------------ the gate -----------------------------


def test_repo_clean(repo_findings):
    """Tier-1 gate: zero unsuppressed findings on the real tree — the
    same condition as `python scripts/lint.py --check`."""
    active = unsuppressed(repo_findings)
    assert active == [], "\n" + "\n".join(f.render() for f in active)


# ----------------------- C8/C9/C10 (ISSUE 18) ------------------------


def _wire_doc(apps, echo=True):
    response = {"y": {"required": True}}
    if echo:
        response["echo"] = {}
    return {
        "endpoints": {
            "ping": {
                "path": "/ping",
                "app": "gen",
                "request": {"x": {"required": True}, "opt": {}},
                "response": response,
            }
        },
        "apps": apps,
    }


def test_wire_payload_negative_fixture_is_clean():
    sf = _fixture("wire_neg")
    wc = WireContracts(_wire_doc({"wire_neg": "gen"}))
    assert check_payload_contracts({sf.rel: sf}, contracts=wc) == []


def test_wire_payload_positive_fixture_flags_every_drift_class():
    sf = _fixture("wire_pos")
    wc = WireContracts(_wire_doc({"wire_pos": "gen"}, echo=False))
    findings = check_payload_contracts({sf.rel: sf}, contracts=wc)
    msgs = [f.message for f in findings]
    assert sum(f.rule == "payload-silent-default" for f in findings) == 1
    assert any("'ghost'" in m for m in msgs)  # read no producer writes
    assert any("'bogus'" in m for m in msgs)  # write not in contract
    assert any("'zzz'" in m for m in msgs)  # response read no one writes
    assert any("omits required key 'x'" in m for m in msgs)
    assert len(findings) == 5, "\n".join(f.render() for f in findings)


def test_renaming_wire_key_is_caught_in_fixture():
    """Acceptance: renaming the produced key in the CLEAN fixture must
    produce both an unknown-write and a missing-required finding."""
    src = open(os.path.join(FIXTURES, "wire_neg.py")).read()
    assert 'json={"x": 1, "opt": "o"}' in src
    mutated = src.replace('json={"x": 1, "opt": "o"}',
                          'json={"x_new": 1, "opt": "o"}')
    sf = SourceFile("wire_neg_mut", mutated, rel="wire_neg_mut")
    wc = WireContracts(_wire_doc({"wire_neg_mut": "gen"}))
    findings = check_payload_contracts({sf.rel: sf}, contracts=wc)
    msgs = [f.message for f in findings]
    assert any("'x_new'" in m for m in msgs)
    assert any("omits required key 'x'" in m for m in msgs)


def test_renaming_real_fake_server_key_is_caught(repo_files):
    """Acceptance (real code): renaming output_versions in the fake
    server — the exact PR-17 drift class this checker exists for."""
    src = open(os.path.join(REPO, "tests", "fake_server.py")).read()
    assert '"output_versions"' in src
    mutated = src.replace('"output_versions"', '"output_versionz"')
    sf = SourceFile("fake_server_mut", mutated,
                    rel=os.path.join("tests", "fake_server.py"))
    findings = check_payload_contracts(repo_files, REPO, fake_server=sf)
    active = [f for f in findings if not f.suppressed]
    assert any("output_versionz" in f.message for f in active)
    assert any("omits required key 'output_versions'" in f.message
               for f in active)


def test_metric_event_negative_fixture_is_clean():
    sf = _fixture("metric_neg")
    wc = WireContracts({"events": {"names": [{"name": "ev_done"}]}})
    findings = check_telemetry_contracts(
        {sf.rel: sf}, contracts=wc,
        schema={"gen": ["areal_gen_good_total"]},
        trace_sf=_fixture("event_trace"),
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_metric_event_positive_fixture_flags_all():
    sf = _fixture("metric_pos")
    wc = WireContracts({"events": {"names": [
        {"name": "ev_unparsed"}, {"name": "ev_never"},
    ]}})
    findings = check_telemetry_contracts(
        {sf.rel: sf}, contracts=wc,
        schema={"gen": ["areal_gen_orphan_total"]},
        trace_sf=_fixture("event_trace"),
    )
    msgs = [f.message for f in findings]
    # metric side: unpinned static name, dynamic name, schema orphan
    assert any("'bad_total'" in m for m in msgs)
    assert any("dynamically-named" in m for m in msgs)
    assert any("orphaned schema entry" in m for m in msgs)
    # event side: undeclared emit, emitted-but-never-parsed,
    # declared-but-never-emitted/consumed (both directions of ev_never),
    # parsed-but-undeclared ghost in the trace fixture
    assert any("'ghost_ev'" in m for m in msgs)
    assert any("'ev_unparsed' is emitted but" in m for m in msgs)
    assert any("'ev_never' is declared but nothing emits" in m for m in msgs)
    assert any("'ev_never' is declared but obs/trace.py never" in m
               for m in msgs)
    assert any("parses event 'ev_done'" in m for m in msgs)
    assert len(findings) == 8, "\n".join(f.render() for f in findings)


def test_dropping_real_metric_from_schema_is_caught(repo_files):
    """Acceptance (real code): removing a pinned metric the code still
    constructs must flag the construction site."""
    with open(os.path.join(REPO, "tests", "data",
                           "metrics_schema.json")) as fh:
        schema = json.load(fh)
    schema = {
        surface: [n for n in names if n != "areal_train_recover_total"]
        for surface, names in schema.items()
    }
    findings = check_telemetry_contracts(repo_files, REPO, schema=schema)
    assert any(
        f.rule == "metric-contract" and "areal_train_recover_total"
        in f.message and not f.suppressed
        for f in findings
    )


def test_orphan_schema_metric_is_caught(repo_files):
    with open(os.path.join(REPO, "tests", "data",
                           "metrics_schema.json")) as fh:
        schema = json.load(fh)
    schema["train"] = schema["train"] + ["areal_train_ghost_metric"]
    findings = check_telemetry_contracts(repo_files, REPO, schema=schema)
    assert any(
        "orphaned schema entry" in f.message
        and "areal_train_ghost_metric" in f.message
        for f in findings
    )


CFG_DOC = {
    "config_chains": {
        "files": {
            "config": "cfgchain_cfg",
            "server": "cfgchain_srv",
            "engine": "cfgchain_eng",
            "config_class": "TinyServerConfig",
            "build_cmd": "build_cmd",
            "engine_class": "TinyEngine",
        },
        "chains": [
            {"field": "depth", "flag": "--depth", "engine_kwarg": "depth"},
            {"field": "width", "flag": "--width", "engine_kwarg": "width"},
        ],
    }
}


def _cfg_files(server_fixture="cfgchain_srv"):
    files = {
        "cfgchain_cfg": _fixture("cfgchain_cfg"),
        "cfgchain_eng": _fixture("cfgchain_eng"),
    }
    files["cfgchain_srv"] = SourceFile.from_path(
        os.path.join(FIXTURES, server_fixture + ".py"), rel="cfgchain_srv"
    )
    return files


def test_config_chain_negative_fixture_is_clean():
    findings = check_config_plumbing(
        _cfg_files(), contracts=WireContracts(CFG_DOC)
    )
    assert findings == [], "\n".join(f.render() for f in findings)


def test_config_chain_positive_fixture_flags_every_break():
    findings = check_config_plumbing(
        _cfg_files("cfgchain_srv_pos"), contracts=WireContracts(CFG_DOC)
    )
    msgs = [f.message for f in findings]
    assert any("argparse has no '--width'" in m for m in msgs)
    assert any("never passes 'width'" in m for m in msgs)
    assert any("'--extra' is not covered" in m for m in msgs)
    assert any("does not accept it" in m for m in msgs)  # build vs argparse
    assert len(findings) == 4, "\n".join(f.render() for f in findings)


def test_train_config_chains_are_clean(repo_files):
    """The ISSUE 20 train chains (layer_group_size / remat_policy /
    scan_unroll / lm_head_chunk) hold on the real tree."""
    from areal_tpu.analysis.wire_contracts import check_train_config_plumbing

    findings = check_train_config_plumbing(repo_files, REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_breaking_real_train_chain_flag_is_caught(repo_files):
    """Acceptance (real code): renaming the bench's --layer-group-size
    flag breaks the declared train chain."""
    from areal_tpu.analysis.wire_contracts import check_train_config_plumbing

    rel = os.path.join("scripts", "bench_e2e_grpo.py")
    src = open(os.path.join(REPO, rel)).read()
    assert '"--layer-group-size"' in src
    mutated = src.replace('"--layer-group-size"', '"--layer-groupsize"')
    files = dict(repo_files)
    files[rel] = SourceFile("bench_mut", mutated, rel=rel)
    findings = check_train_config_plumbing(files, REPO)
    msgs = [f.message for f in findings if not f.suppressed]
    assert any("argparse has no '--layer-group-size'" in m for m in msgs)


def test_unread_train_chain_flag_is_caught(repo_files):
    """Acceptance (real code): a train-chain flag whose `args.<dest>` read
    disappears is parsed-but-dropped."""
    from areal_tpu.analysis.wire_contracts import check_train_config_plumbing

    rel = os.path.join("scripts", "bench_e2e_grpo.py")
    src = open(os.path.join(REPO, rel)).read()
    assert "args.lm_head_chunk" in src
    mutated = src.replace("args.lm_head_chunk", "args.lm_head_chunk_gone")
    files = dict(repo_files)
    files[rel] = SourceFile("bench_mut2", mutated, rel=rel)
    findings = check_train_config_plumbing(files, REPO)
    msgs = [f.message for f in findings if not f.suppressed]
    assert any("`args.lm_head_chunk` is never read" in m for m in msgs)


def test_dropped_model_replace_plumbing_is_caught(repo_files):
    """Acceptance (real code): the engine's model-config replace() losing
    the layer_group_size kwarg severs the chain to the backbone."""
    from areal_tpu.analysis.wire_contracts import check_train_config_plumbing

    rel = os.path.join("areal_tpu", "engine", "jax_train.py")
    src = open(os.path.join(REPO, rel)).read()
    assert "layer_group_size=" in src
    mutated = src.replace("layer_group_size=", "layer_group_size_x=")
    files = dict(repo_files)
    files[rel] = SourceFile("engine_mut", mutated, rel=rel)
    findings = check_train_config_plumbing(files, REPO)
    msgs = [f.message for f in findings if not f.suppressed]
    assert any("never plumbs 'layer_group_size'" in m for m in msgs)


def test_breaking_real_config_chain_is_caught(repo_files):
    """Acceptance (real code): renaming a gen/server.py argparse flag out
    from under its GenServerConfig chain."""
    path = os.path.join(REPO, "areal_tpu", "gen", "server.py")
    src = open(path).read()
    assert '"--host-cache-mb"' in src
    mutated = src.replace('"--host-cache-mb"', '"--host-cachemb"')
    rel = os.path.join("areal_tpu", "gen", "server.py")
    files = dict(repo_files)
    files[rel] = SourceFile("server_mut", mutated, rel=rel)
    findings = check_config_plumbing(files, REPO)
    msgs = [f.message for f in findings if not f.suppressed]
    assert any("argparse has no '--host-cache-mb'" in m for m in msgs)
    assert any("'--host-cachemb'" in m for m in msgs)  # now uncovered
