"""Local launcher: one host, generation servers + trainer as subprocesses.

Behavioral counterpart of the reference's `LocalLauncher`
(areal/launcher/local.py:81): parse the allocation expression, start the
LLM servers (here `areal_tpu.gen.server`), register/discover addresses via
name_resolve env plumbing, start the trainer entrypoint, babysit everything,
and relaunch the whole run on failure (auto-recover loop,
RECOVER_TIME_INTERVAL) up to `recover.retries` times with AREAL_RUN_ID
incremented so `check_if_recover` (utils/recover.py) resumes from the dump.

Two relaunch classes (ISSUE 15):
- crash (any unexpected rc): consumes one of `recover.retries`, waits out
  RECOVER_TIME_INTERVAL — the dump on disk is whatever the dying process
  last committed;
- preemption (rc == RESUME_EXIT_CODE, utils/shutdown.py): the trainer
  announced an orderly retreat with a known-good force-dump, so the
  relaunch is immediate and does NOT burn a crash retry.

Either way AREAL_RUN_ID increments per launch, so run artifacts
(events_run{N}.jsonl, logs) never collide and `check_if_recover`'s
``fault`` mode sees a relaunch.

Every child owns its chips.  A chip belongs to one process at a time, so
the allocation expression is also the chip plan: generation server `i`
takes the next `gen_instance_size` chips, the trainer the
`train_world_size` after them, and each child is started with the
environment libtpu reads for its visible chips and process bounds
(`chip_env`).  The launcher itself never imports JAX.

Usage:
    python -m areal_tpu.launcher.local entry.py --config cfg.yaml [k=v ...]
"""

import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

from areal_tpu.api.alloc import AllocationMode
from areal_tpu.api.config import GRPOConfig, load_expr_config
from areal_tpu.utils import logging, name_resolve, names, network
from areal_tpu.utils.runtime import (
    COMPILE_CACHE_ENV,
    REPO_ROOT,
    compile_cache_dir,
    cpu_requested,
)
from areal_tpu.utils.shutdown import RESUME_EXIT_CODE

logger = logging.getLogger("launcher.local")

RECOVER_TIME_INTERVAL = 10.0
# brief pause before a preemption relaunch: lets sockets/ports settle
# without hot-spinning if the entry exits with the resume code instantly
RESUME_RELAUNCH_DELAY = 1.0


# libtpu's TPU_CHIPS_PER_PROCESS_BOUNDS for a process that owns n chips of
# one host (x,y,z; a v5e host is a 2x2 or 2x4 tray)
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_env(chips: List[int]) -> Dict[str, str]:
    """Environment that makes a child process see exactly `chips`.

    libtpu reads TPU_VISIBLE_CHIPS and the two bounds at load time; with
    TPU_CHIPS_PER_PROCESS_BOUNDS naming a subset of the host it also lets
    several processes load it side by side, each on its own chips.  On an
    explicit CPU run (`JAX_PLATFORMS=cpu`) the child instead gets as many
    virtual host devices as it was allotted chips, so a rehearsal sees the
    same per-process device counts."""
    if len(chips) not in _PROCESS_BOUNDS:
        raise ValueError(
            f"a process cannot own {len(chips)} chips of one host: "
            f"use one of {sorted(_PROCESS_BOUNDS)}"
        )
    env = {
        "TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _PROCESS_BOUNDS[len(chips)],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }
    if cpu_requested():
        flags = [
            f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={len(chips)}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def plan_chips(alloc: AllocationMode) -> Tuple[List[List[int]], List[int]]:
    """(chips of each generation server, chips of the trainer): disjoint,
    in allocation order."""
    n_servers = max(1, alloc.gen.dp_size) if alloc.gen is not None else 1
    per_server = max(1, alloc.gen_instance_size)
    servers = [
        list(range(i * per_server, (i + 1) * per_server))
        for i in range(n_servers)
    ]
    first = n_servers * per_server
    trainer = list(range(first, first + max(1, alloc.train_world_size)))
    return servers, trainer


class LocalLauncher:
    def __init__(self, entry: str, config_args: List[str]):
        self.entry = entry
        self.config_args = config_args
        self.config, _ = load_expr_config(config_args, GRPOConfig, ignore_unknown_top=True)
        self.procs: List[subprocess.Popen] = []
        self.server_addrs: List[str] = []

    # ------------------------------------------------------------------

    def _spawn(self, cmd: List[str], env: Optional[Dict[str, str]] = None,
               tag: str = "") -> subprocess.Popen:
        full_env = dict(os.environ)
        # children compile into the launcher's cache directory, and import
        # this checkout whatever directory their entry script lives in
        full_env.setdefault(COMPILE_CACHE_ENV, compile_cache_dir())
        # a child that dies in native code (the TPU runtime) says where
        full_env.setdefault("PYTHONFAULTHANDLER", "1")
        full_env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO_ROOT, full_env.get("PYTHONPATH")) if p
        )
        if env:
            full_env.update(env)
        log_dir = os.path.join(
            self.config.cluster.fileroot,
            self.config.experiment_name,
            self.config.trial_name,
            "logs",
        )
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir, f"{tag}.log")
        log_f = open(log_path, "a")
        logger.info(f"spawn [{tag}]: {' '.join(cmd)} (log: {log_path})")
        p = subprocess.Popen(
            cmd, env=full_env, stdout=log_f, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.procs.append(p)
        return p

    def gen_server_cmd(self, idx: int, port: int, tp: int) -> List[str]:
        return [
            sys.executable, "-m", "areal_tpu.gen.server",
            "--model-path", self.config.gen_server.model_path,
            "--port", str(port),
            "--n-slots", str(self.config.gen_server.max_seqs),
            "--max-seq-len", str(self.config.gen_server.max_context_len),
            "--tp", str(tp),
            "--experiment-name", self.config.experiment_name,
            "--trial-name", self.config.trial_name,
            "--server-idx", str(idx),
        ]

    def start_gen_servers(
        self, server_chips: List[Optional[List[int]]], tp: int = 1
    ) -> List[str]:
        """One server per entry; an entry is the chips that server owns
        (None: no allocation was given, the child sees what it finds)."""
        addrs = []
        for idx, chips in enumerate(server_chips):
            port = network.find_free_port()
            self._spawn(
                self.gen_server_cmd(idx, port, tp),
                env=chip_env(chips) if chips is not None else None,
                tag=f"gen_server_{idx}",
            )
            addrs.append(f"127.0.0.1:{port}")
        return addrs

    def start_trainer(
        self, server_addrs: List[str], run_id: int,
        chips: Optional[List[int]] = None,
    ) -> subprocess.Popen:
        env = {
            "AREAL_LLM_SERVER_ADDRS": ",".join(server_addrs),
            "AREAL_RUN_ID": str(run_id),
        }
        if chips is not None:
            env.update(chip_env(chips))
        cmd = [sys.executable, self.entry, *self.config_args]
        return self._spawn(cmd, env=env, tag=f"trainer_run{run_id}")

    def stop_all(self):
        for p in self.procs:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGTERM)
                except ProcessLookupError:
                    pass
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except ProcessLookupError:
                    pass
        self.procs.clear()

    # ------------------------------------------------------------------

    def run(self) -> int:
        server_chips: List[Optional[List[int]]] = [None]
        trainer_chips: Optional[List[int]] = None
        gen_tp = 1
        if self.config.allocation_mode:
            alloc = AllocationMode.from_str(self.config.allocation_mode)
            server_chips, trainer_chips = plan_chips(alloc)
            if alloc.gen is not None:
                gen_tp = alloc.gen.tp_size
            logger.info(
                f"chip plan: servers {server_chips} (tp={gen_tp}), "
                f"trainer {trainer_chips}"
            )
        else:
            logger.warning(
                "no allocation_mode: children are given no chips of their "
                "own and will contend for whatever device they find"
            )

        retries = max(1, self.config.recover.retries)
        run_id = int(os.environ.get("AREAL_RUN_ID", 0))
        failures = 0  # crash relaunches consumed; preemptions don't count
        rc = 1
        try:
            while True:
                self.server_addrs = self.start_gen_servers(
                    server_chips, tp=gen_tp
                )
                trainer = self.start_trainer(
                    self.server_addrs, run_id, chips=trainer_chips
                )
                rc = self._babysit(trainer)
                self.stop_all()
                if rc == 0:
                    logger.info("trainer finished successfully")
                    return 0
                if self.config.recover.mode == "disabled":
                    return rc
                run_id += 1
                if rc == RESUME_EXIT_CODE:
                    # orderly preemption retreat (utils/shutdown.py): the
                    # dump is known-good — relaunch now, keep the retry
                    # budget for real crashes
                    logger.warning(
                        f"trainer preempted (rc={rc}); relaunching "
                        f"immediately (run {run_id})"
                    )
                    time.sleep(RESUME_RELAUNCH_DELAY)
                    continue
                failures += 1
                if failures < retries and self.config.recover.mode in (
                        "auto", "fault"):
                    logger.warning(
                        f"trainer exited rc={rc}; relaunching (run {run_id}) "
                        f"in {RECOVER_TIME_INTERVAL}s "
                        f"[crash {failures}/{retries}]"
                    )
                    time.sleep(RECOVER_TIME_INTERVAL)
                else:
                    break
            return rc
        finally:
            self.stop_all()

    def _babysit(self, trainer: subprocess.Popen) -> int:
        """Wait for the trainer; if any gen server dies first, fail the run."""
        while True:
            rc = trainer.poll()
            if rc is not None:
                return rc
            for p in self.procs:
                if p is not trainer and p.poll() is not None:
                    logger.error(
                        f"a generation server (pid {p.pid}) exited with "
                        f"{p.returncode}; failing the run"
                    )
                    return 1
            time.sleep(1.0)


def main():
    if len(sys.argv) < 2:
        print(__doc__)
        sys.exit(2)
    entry, args = sys.argv[1], sys.argv[2:]
    launcher = LocalLauncher(entry, args)
    sys.exit(launcher.run())


if __name__ == "__main__":
    main()
