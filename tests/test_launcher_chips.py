"""One process per chip: what the local launcher gives each child.

A chip belongs to one process at a time.  The launcher is the only place
that knows the whole allocation, so it is the one that hands every child
its own chips (the environment libtpu reads at load time), passes `--tp`
to the servers, and — never importing JAX itself — leaves the device to
its children.  On an explicit CPU run each child gets as many virtual
devices as it was allotted chips, which is what the four-virtual-device
rehearsal of `chip_smoke.py --four-chips` leans on.
"""

import logging
import os
import subprocess
import sys

import pytest

from areal_tpu.api.alloc import AllocationMode
from areal_tpu.launcher import local

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("expr,n_servers,per_server,n_train", [
    ("jax:d1+jax:d1", 1, 1, 1),
    ("jax:d1t2+jax:f2", 1, 2, 2),  # the four-chip host of chip_smoke.py
    ("jax:d2+jax:d1f2", 2, 1, 2),
    ("jax:d2t2+jax:f2t2", 2, 2, 4),
])
def test_plan_gives_every_child_its_own_chips(expr, n_servers, per_server,
                                               n_train):
    servers, trainer = local.plan_chips(AllocationMode.from_str(expr))
    assert [len(s) for s in servers] == [per_server] * n_servers
    assert len(trainer) == n_train
    owned = [c for s in servers for c in s] + trainer
    assert len(set(owned)) == len(owned), f"a chip was given twice: {owned}"
    assert sorted(owned) == list(range(len(owned)))


def test_chip_env_names_visible_chips_and_bounds(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = local.chip_env([2, 3])
    assert env == {
        "TPU_VISIBLE_CHIPS": "2,3",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,2,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    assert local.chip_env([0])["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    with pytest.raises(ValueError, match="cannot own 3 chips"):
        local.chip_env([0, 1, 2])
    # an explicit CPU run: as many virtual devices as chips, whatever
    # count the parent itself was started with
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8 --foo=1")
    flags = local.chip_env([2, 3])["XLA_FLAGS"].split()
    assert flags == ["--foo=1", "--xla_force_host_platform_device_count=2"]


def _launcher(tmp_path, monkeypatch, allocation):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        "experiment_name: chips\ntrial_name: t0\n"
        f"cluster:\n  fileroot: {tmp_path}\n"
        f"allocation_mode: \"{allocation}\"\n"
        "gen_server:\n  model_path: /nowhere\n  max_seqs: 4\n"
        "  max_context_len: 64\n"
    )
    launcher = local.LocalLauncher("entry.py", ["--config", str(cfg)])
    spawned = []

    def fake_spawn(cmd, env=None, tag=""):
        spawned.append((tag, cmd, env or {}))

    monkeypatch.setattr(launcher, "_spawn", fake_spawn)
    return launcher, spawned


def test_children_get_disjoint_chips_and_tp(tmp_path, monkeypatch):
    """What `run()` starts for a tp=2 server beside an fsdp=2 trainer on a
    four-chip host: disjoint chips, `--tp 2` on the server command line."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launcher, spawned = _launcher(tmp_path, monkeypatch, "jax:d1t2+jax:f2")
    alloc = AllocationMode.from_str(launcher.config.allocation_mode)
    server_chips, trainer_chips = local.plan_chips(alloc)
    addrs = launcher.start_gen_servers(server_chips, tp=alloc.gen.tp_size)
    launcher.start_trainer(addrs, run_id=0, chips=trainer_chips)
    (s_tag, s_cmd, s_env), (t_tag, t_cmd, t_env) = spawned
    assert s_tag == "gen_server_0" and t_tag == "trainer_run0"
    assert s_cmd[s_cmd.index("--tp") + 1] == "2"
    assert s_env["TPU_VISIBLE_CHIPS"] == "0,1"
    assert t_env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert not set(s_env["TPU_VISIBLE_CHIPS"].split(",")) & set(
        t_env["TPU_VISIBLE_CHIPS"].split(","))
    for env in (s_env, t_env):
        assert "device_count=2" in env["XLA_FLAGS"]
    assert t_env["AREAL_LLM_SERVER_ADDRS"] == addrs[0]


def test_children_inherit_cache_and_checkout(tmp_path, monkeypatch):
    """`_spawn` itself: the child compiles into the launcher's cache
    directory and imports this checkout from wherever its entry lives."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    launcher, _ = _launcher(tmp_path, monkeypatch, "jax:d1+jax:d1")
    monkeypatch.undo()
    seen = {}

    class FakePopen:
        def __init__(self, cmd, env=None, **kw):
            seen.update(env)

    monkeypatch.setattr(subprocess, "Popen", FakePopen)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    launcher._spawn(["true"], env={"X": "1"}, tag="child")
    assert seen["JAX_COMPILATION_CACHE_DIR"] == os.path.join(
        REPO, ".jax_cache")
    assert seen["PYTHONPATH"].split(os.pathsep)[0] == REPO
    assert seen["X"] == "1"


def test_launcher_stays_off_jax():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys, areal_tpu.launcher.local; "
         "print('jax' in sys.modules or 'jaxlib' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.stdout.strip() == "False", r.stdout + r.stderr


def test_default_mesh_says_which_device_it_took():
    """A one-device mesh on a host with more devices (eight virtual ones
    here) logs the device it took instead of silently using the first."""
    import jax

    from areal_tpu.parallel import build_mesh

    records = []

    class Grab(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Grab()
    log = logging.getLogger("areal_tpu")
    log.addHandler(handler)
    try:
        four = jax.devices()[:4]
        mesh = build_mesh(devices=four)
        assert mesh.devices.size == 1
        assert any("mesh takes 1 of 4 devices: [0]" in m for m in records), \
            records
        records.clear()
        build_mesh(fsdp=2, tp=2, devices=four)
        assert not any("mesh takes" in m for m in records)
    finally:
        log.removeHandler(handler)
