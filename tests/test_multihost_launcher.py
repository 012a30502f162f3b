"""Multi-host launcher test: fabricate a 2-"host" run on one machine.

The launcher's remote_shell is swapped for a local shell (the reference
fabricates clusters the same way, realhf/base/testing.py), everything else
is the real path: NFS name_resolve rendezvous, gen-server registration +
discovery, per-host trainer processes joining one jax.distributed runtime,
babysitting, and clean shutdown.
"""

import os
import sys
import textwrap

import yaml

from areal_tpu.launcher.multihost import MultiHostLauncher, local_shell

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENTRY = textwrap.dedent(
    """
    import os, sys, time, urllib.request

    sys.path.insert(0, {repo!r})
    from areal_tpu.api.config import GRPOConfig, load_expr_config
    from areal_tpu.parallel import distributed
    from areal_tpu.utils import name_resolve, names

    import jax

    cfg, _ = load_expr_config(sys.argv[1:], GRPOConfig)
    distributed.init_distributed()
    assert jax.process_count() == 2, jax.process_count()

    # discover the generation server through the shared store and probe it
    key = names.gen_servers(cfg.experiment_name, cfg.trial_name)
    deadline = time.monotonic() + 60
    addrs = []
    while time.monotonic() < deadline and not addrs:
        addrs = sorted(name_resolve.get_subtree(key))
        time.sleep(0.25)
    assert addrs, "no gen servers registered"
    health = urllib.request.urlopen(
        f"http://{{addrs[0]}}/health", timeout=10
    ).read()
    print("TRAINER OK", jax.process_index(), addrs[0], flush=True)
    """
)


def test_two_host_launch(tmp_path):
    nr_root = str(tmp_path / "name_resolve")
    fileroot = str(tmp_path / "experiments")
    cfg_path = tmp_path / "cfg.yaml"
    cfg_path.write_text(
        yaml.safe_dump(
            {
                "experiment_name": "mh",
                "trial_name": "t0",
                "cluster": {
                    "fileroot": fileroot,
                    "name_resolve": {"type": "nfs", "nfs_record_root": nr_root},
                },
                "gen_server": {"max_seqs": 2, "max_context_len": 128},
                "recover": {"mode": "disabled", "retries": 1},
            }
        )
    )
    entry_path = tmp_path / "entry.py"
    entry_path.write_text(ENTRY.format(repo=REPO))

    def test_shell(host, cmd, env, workdir):
        env = {
            **env,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        }
        return local_shell(host, cmd, env, workdir)

    launcher = MultiHostLauncher(
        entry=str(entry_path),
        config_args=["--config", str(cfg_path)],
        gen_hosts=["hostA"],
        train_hosts=["hostA", "hostB"],
        remote_shell=test_shell,
        workdir=REPO,
        coordinator_host="127.0.0.1",
    )
    rc = launcher.run()
    assert rc == 0, rc

    log_dir = os.path.join(fileroot, "mh", "t0", "logs")
    logs = {f: open(os.path.join(log_dir, f)).read() for f in os.listdir(log_dir)}
    trainer_out = "".join(v for k, v in logs.items() if k.startswith("trainer"))
    assert "TRAINER OK 0" in trainer_out, logs
    assert "TRAINER OK 1" in trainer_out, logs
