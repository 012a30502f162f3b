"""`chip_smoke.py` on a machine without a chip, and the compile-cache rule.

The smoke script is the proof that the system starts on the TPU, so the
one thing a CPU test can hold it to is that it never claims so here; the
cache helper is held to its two cases: leave `JAX_COMPILATION_CACHE_DIR`
alone when set, one fixed path inside the checkout when not.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, cwd=REPO, script=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout,
    )


def _ok_lines(stdout):
    """The final `{"ok": true, "device": ...}` line, if any (phase records
    carry "ok" too, and a "phase")."""
    records = [json.loads(line) for line in stdout.splitlines()
               if line.startswith("{")]
    return [r for r in records if r.get("ok") is True and "phase" not in r]


def test_refuses_without_a_tpu():
    """With JAX on the CPU the script exits non-zero and prints no ok
    line — whatever phase it would have reached."""
    r = _run([])
    assert r.returncode != 0, r.stdout
    assert not _ok_lines(r.stdout), r.stdout
    assert "needs a TPU" in r.stderr, r.stderr


def test_refuses_alone_in_a_directory(tmp_path):
    """A copy of the script without the rest of the repo fails too."""
    import shutil

    alone = tmp_path / "chip_smoke.py"
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    r = _run([], cwd=str(tmp_path), script=str(alone),
             env_extra={"PYTHONPATH": ""})
    assert r.returncode != 0, r.stdout
    assert not _ok_lines(r.stdout), r.stdout


def test_rehearsal_needs_an_explicit_cpu_run():
    r = _run(["--cpu-rehearsal"], env_extra={"JAX_PLATFORMS": ""})
    assert r.returncode != 0
    assert not _ok_lines(r.stdout), r.stdout


@pytest.mark.parametrize("set_dir", [True, False])
def test_compile_cache_placement(tmp_path, set_dir):
    """Set: nothing is configured in code and the variable stands.  Unset:
    `<checkout>/.jax_cache`, the same path from every process."""
    code = (
        "import os, jax\n"
        "from areal_tpu.utils.runtime import enable_compile_cache\n"
        "before = jax.config.jax_compilation_cache_dir\n"
        "d = enable_compile_cache()\n"
        "print(repr((before, d, jax.config.jax_compilation_cache_dir, "
        "os.environ.get('JAX_COMPILATION_CACHE_DIR'))))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outside = str(tmp_path / "cache")
    if set_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    seen = set()
    for cwd in (REPO, str(tmp_path)):
        r = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        seen.add(eval(r.stdout.strip()))  # noqa: S307 — our own repr
    assert len(seen) == 1, seen  # no pid, time or cwd in the path
    before, used, configured, env_after = seen.pop()
    if set_dir:
        # JAX read the variable itself; the helper set nothing
        assert before == used == configured == env_after == outside
    else:
        assert before is None and env_after is None
        assert used == configured == os.path.join(REPO, ".jax_cache")


def test_kernel_backend_rule(monkeypatch):
    """Interpret mode is asked for, never inferred: a flag, or an explicit
    JAX_PLATFORMS=cpu; a CPU that nobody asked for raises."""
    from areal_tpu.utils import runtime

    assert runtime.kernel_backend(True) == "interpret"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.kernel_backend(False) == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="did not ask for it"):
        runtime.kernel_backend(False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError):
        runtime.kernel_backend(False)


def test_unknown_tpu_kind_is_an_error(monkeypatch):
    """No default for a device the tables do not know: peak FLOP/s
    (utils/profiling.py) and HBM size (api/presets.py) both raise and name
    their table; an explicit CPU run reports no utilisation."""
    from types import SimpleNamespace

    from areal_tpu.api import presets
    from areal_tpu.utils import profiling

    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert profiling.device_peak_tflops(cpu) is None
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert profiling.device_peak_tflops(v5e) == 197.0
    unknown = SimpleNamespace(platform="tpu", device_kind="TPU v9 mega")
    with pytest.raises(ValueError, match="PEAK_TFLOPS"):
        profiling.device_peak_tflops(unknown)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="PEAK_TFLOPS"):
        profiling.device_peak_tflops(cpu)
    with pytest.raises(ValueError, match="HBM_BYTES"):
        presets.search_allocation(8, 1.5e9, device_kind="TPU v9 mega")
    assert "default" not in presets.HBM_BYTES


def test_ragged_attn_that_cannot_be_honoured_raises():
    """A requested kernel that does not fit is an error at engine init,
    not a warning and the dense path."""
    import jax

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config
    from areal_tpu.ops import ragged_decode

    cfg = tiny_config(vocab_size=97)
    params = init_params(cfg, jax.random.PRNGKey(0))
    budget = ragged_decode.RAGGED_VMEM_BYTES
    ragged_decode.RAGGED_VMEM_BYTES = 1024
    try:
        with pytest.raises(ValueError, match="ragged_attn requested"):
            GenEngine(cfg, params=params, n_slots=2, max_seq_len=128,
                      prompt_bucket=16, kv_dtype="float32", ragged_attn=True)
    finally:
        ragged_decode.RAGGED_VMEM_BYTES = budget


@pytest.mark.parametrize("four", [False, True], ids=["one-chip", "four-chips"])
def test_cpu_rehearsal_passes_and_never_says_ok(four, tmp_path):
    """The whole control flow at toy sizes: trainer, server (dense, then
    ragged with equal tokens), async loop; with `--four-chips` the
    fsdp=2 x tp=2 steps against one device and the real launcher with a
    `--tp 2` server beside an `fsdp=2` trainer, each child on its own two
    of four virtual devices.  A rehearsal passes without ever printing the
    ok line."""
    args = ["--cpu-rehearsal"] + (["--four-chips"] if four else [])
    r = _run(args, timeout=600,
             env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert not _ok_lines(r.stdout), r.stdout
    records = [json.loads(l) for l in r.stdout.splitlines()
               if l.startswith("{")]
    phases = {rec.get("phase"): rec for rec in records}
    last = records[-1]
    assert last["rehearsal"] and last["passed"] and last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    if four:
        assert last["device"]["count"] == 4
        mesh = phases["mesh_fsdp2_tp2"]
        assert sum(mesh["collectives"].values()) > 0
        launcher = phases["launcher_disaggregated"]
        assert launcher["steps"] >= 2
        assert launcher["server_versions"][-1] >= 2
        assert "servers [[0, 1]] (tp=2), trainer [2, 3]" in launcher["chip_plan"]
        assert launcher["server_device"]["count"] == 2
    else:
        assert phases["trainer"]["attention"] == "einsum"
        assert phases["server_ragged"]["tokens_equal_dense"]
        assert phases["server_dense"]["version"] == [0, 1]
        assert phases["async_loop"]["version_span_trajectories"] >= 1
