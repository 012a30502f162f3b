#!/usr/bin/env python3
"""Bring-up proof: the train / serve / publish path on one TPU v5e chip.

    python chip_smoke.py                  # one chip: what the driver runs
    python chip_smoke.py --four-chips     # fsdp=2 x tp=2 + the launcher path
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal   # tiny, no chip

One chip, three phases at fixed sizes (no ladder, no retry, no fallback):

1. trainer — `JaxPPOActor` on Qwen2.5-1.5B, 8 packed rows x 2048, bf16
   params and optimizer, remat `full`: `compute_advantages`, then a few
   `ppo_update` steps.  Loss and grad-norm must be finite and move, and the
   compiled step must hold the splash `tpu_custom_call`.
2. server — the real `python -m areal_tpu.gen.server --model-path ...` over
   HTTP on a Qwen2.5-1.5B checkpoint this script wrote: `/health`,
   concurrent greedy `/generate`, `/pause_generation` +
   `/continue_generation`, `/update_weights_from_disk` from a second
   checkpoint (version advances, greedy output changes), `/metrics`, under
   `--no-ragged-attn` (the copy path).  Then the same prompts with the flag
   left out: the server's default must run the paged kernel and the tokens
   must equal the copy path's.
3. async loop — `scripts/bench_e2e_grpo.py` colocated, `qwen2_0p6b_ctx`,
   async mode: rollout through the workflow executor and reward pool,
   train, live weight publish, and at least one trajectory generated
   across a publish.

A chip belongs to one process at a time, so this parent never imports JAX:
it starts one child after another, relays the JSON line each phase prints
(phase, seconds, compile seconds, steps or requests, peak bytes) and the
device the children found.  The LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only if every phase passed on a TPU.  Anything else — no
accelerator, a failed phase, a directory without the rest of the repo —
exits non-zero without it.  `--cpu-rehearsal` runs the same control flow at
toy sizes on the CPU and can never print that line.

Nothing comes from a file git would not commit: checkpoints, tokenizer and
data are made here from `--seed` into `.chip_smoke_work/` (emptied first),
child logs go to `chiprun_out/chip_smoke/`.
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")
LOGS = os.path.join(HERE, "chiprun_out", "chip_smoke")

# fixed sizes; "tiny" is the CPU rehearsal of the same control flow
SIZES = {
    "real": dict(
        model="qwen25_1p5b", rows=8, row_len=2048, train_steps=4,
        slots=64, max_seq_len=2048, prompt_lens=(24, 57, 96, 130, 41, 200),
        new_tokens=12,
        e2e=["--model", "0p6b", "--steps", "5", "--batch-size", "8",
             "--group-size", "2", "--n-slots", "16", "--max-seq-len", "512",
             "--prompt-len", "64", "--max-new-tokens", "192"],
        launcher=dict(slots=16, max_seq_len=1024, batch=4, n_samples=2,
                      new_tokens=64, steps=3, quantum=256, dtype="bfloat16"),
    ),
    "tiny": dict(
        model="tiny", rows=2, row_len=256, train_steps=3,
        slots=4, max_seq_len=256, prompt_lens=(9, 20, 33, 14),
        new_tokens=8,
        e2e=["--model", "tiny", "--steps", "4", "--batch-size", "4",
             "--group-size", "2", "--n-slots", "8", "--max-seq-len", "256",
             "--prompt-len", "16", "--max-new-tokens", "48"],
        launcher=dict(slots=4, max_seq_len=256, batch=4, n_samples=2,
                      new_tokens=16, steps=2, quantum=64, dtype="float32"),
    ),
}


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(record):
    print(json.dumps(record), flush=True)


# ---------------------------------------------------------------------------
# children: each is one process that may hold the chip
# ---------------------------------------------------------------------------


def _model_config(name):
    from areal_tpu.models import model_config as mc

    if name == "tiny":
        return mc.tiny_config(
            vocab_size=384, qkv_bias=True, hf_architecture="Qwen2ForCausalLM"
        )
    return getattr(mc, name)()


def _open_device(rehearsal):
    """First thing a child does: find the device, refuse the wrong one,
    and say what it is."""
    import jax

    from areal_tpu.utils.runtime import (
        cpu_requested,
        device_report,
        enable_compile_cache,
    )

    dev = device_report()
    if rehearsal:
        check(dev["platform"] == "cpu" and cpu_requested(),
              "--cpu-rehearsal needs JAX_PLATFORMS=cpu")
    else:
        check(dev["platform"] == "tpu",
              f"chip_smoke.py needs a TPU; JAX found {dev['platform']!r}")
    cache = enable_compile_cache()
    emit({"phase": "device", "device": dev, "compile_cache": cache,
          "jax": jax.__version__})
    return dev


def _make_actor(model_cfg, row_len, mesh=None):
    """The PPO actor of the trainer phases: bf16 params and optimizer (a
    1.5B fp32 AdamW state does not fit one 16 GB chip), full remat, GRPO
    decoupled loss, packed rows."""
    from areal_tpu.api.config import (
        MeshConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.engine.ppo import JaxPPOActor

    cfg = PPOActorConfig(
        experiment_name="chip_smoke",
        trial_name="chip_smoke",
        init_from_scratch=True,
        dtype="bfloat16",
        param_dtype="bfloat16",
        gradient_checkpointing=True,
        remat_policy="full",
        layer_group_size=1,
        scan_unroll=4,
        mesh=mesh or MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(lr=1e-5, warmup_steps_proportion=0.0),
        pack_length_quantum=row_len,
        max_pack_length=row_len,
        group_size=2,
        ppo_n_minibatches=1,
        use_decoupled_loss=True,
        # deferred stats fetch, as the real train loop runs
        async_stats=True,
        adv_norm=NormConfig(mean_level="group", std_level="group",
                            group_size=2),
    )
    return JaxPPOActor(cfg, model_config=model_cfg)


def _make_batch(rng, n_rows, row_len, vocab):
    """Two packed sequences a row, loss on the latter 75% of each."""
    import numpy as np

    seq_len = row_len // 2
    B = n_rows * 2
    loss_mask = np.zeros((B, seq_len), np.float32)
    loss_mask[:, seq_len // 4:] = 1.0
    return {
        "input_ids": rng.integers(0, vocab, (B, seq_len)).astype(np.int32),
        "attention_mask": np.ones((B, seq_len), bool),
        "loss_mask": loss_mask,
        "logprobs": rng.normal(-1.0, 0.1, (B, seq_len)).astype(np.float32),
        "rewards": rng.integers(0, 2, B).astype(np.float32),
        "versions": np.zeros((B, seq_len), np.int32),
    }


def _train_steps(size, seed, mesh=None, steps=None):
    """Build the actor, take `steps` ppo_update steps on one fixed batch;
    -> (actor, record)."""
    import jax
    import numpy as np

    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.native import available as native_available

    cfg = _model_config(size["model"])
    t0 = time.perf_counter()
    actor = _make_actor(cfg, size["row_len"], mesh=mesh)
    actor.initialize(ft_spec=FinetuneSpec(1, 1024, 8))
    init_s = time.perf_counter() - t0
    batch = _make_batch(
        np.random.default_rng(seed), size["rows"], size["row_len"],
        cfg.vocab_size,
    )
    batch["prox_logp"] = batch["logprobs"].copy()
    actor.compute_advantages(batch)
    step_s, losses, grad_norms = [], [], []
    for _ in range(steps or size["train_steps"]):
        t = time.perf_counter()
        stats = actor.ppo_update(batch)
        jax.block_until_ready(actor.params)
        step_s.append(time.perf_counter() - t)
        losses.append(sum(float(s["loss"]) for s in stats))
        grad_norms.append(float(stats[-1]["grad_norm"]))
    check(all(np.isfinite(losses)) and all(np.isfinite(grad_norms)),
          f"non-finite loss/grad-norm: {losses} {grad_norms}")
    check(len(set(losses)) > 1 and len(set(grad_norms)) > 1,
          f"loss/grad-norm did not move: {losses} {grad_norms}")
    warm = sorted(step_s[1:])[len(step_s[1:]) // 2]
    key = (size["row_len"], cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
    return actor, {
        "init_seconds": round(init_s, 2),
        "steps": len(step_s),
        "step_seconds": [round(s, 3) for s in step_s],
        # the first step compiles; the rest do not
        "compile_seconds": round(step_s[0] - warm, 2),
        "tokens_per_step": int(batch["attention_mask"].sum()),
        "loss": losses,
        "grad_norm": grad_norms,
        "attention": actor.attention_impls().get(key),
        "native_packer": bool(native_available()),
    }


def child_trainer(size, seed, rehearsal):
    from areal_tpu.utils.runtime import device_report

    t0 = time.perf_counter()
    _open_device(rehearsal)
    actor, rec = _train_steps(size, seed)
    if rehearsal:
        check(rec["attention"] == "einsum",
              f"an explicit CPU run takes the einsum, got {rec['attention']}")
    else:
        check(rec["attention"] == "splash",
              f"train step attention is {rec['attention']!r}, not splash")
        t = time.perf_counter()
        hlo = actor.train_step_hlo()
        rec["hlo_recompile_seconds"] = round(time.perf_counter() - t, 2)
        rec["splash_custom_calls"] = hlo.count("tpu_custom_call")
        check(rec["splash_custom_calls"] > 0,
              "compiled train step holds no tpu_custom_call")
    dev = device_report()
    emit({"phase": "trainer", "ok": True,
          "seconds": round(time.perf_counter() - t0, 2), **rec,
          "peak_bytes_in_use": dev["peak_bytes_in_use"], "device": dev})


def child_checkpoints(size, seed, four):
    """CPU-only child: random checkpoints (two seeds) the server loads; for
    the launcher path also a tokenizer and a data file."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.hf import save_hf_checkpoint

    t0 = time.perf_counter()
    dtype = "bfloat16" if size["model"] != "tiny" else "float32"
    cfg = _model_config(size["model"]).replace(param_dtype=dtype)
    if four:
        # the launcher path reads a tokenizer and a dataset too
        from tests.fixtures import make_gsm8k_jsonl, make_tiny_tokenizer

        tok = make_tiny_tokenizer(os.path.join(WORK, "ckpt_a"))
        cfg = cfg.replace(eos_token_id=tok.eos_token_id)
        make_gsm8k_jsonl(os.path.join(WORK, "train.jsonl"), n=64)
    seeds = {"ckpt_a": seed} if four else {"ckpt_a": seed, "ckpt_b": seed + 1}
    for name, s in seeds.items():
        params = init_params(cfg, jax.random.PRNGKey(s))
        save_hf_checkpoint(params, cfg, os.path.join(WORK, name),
                           save_dtype=dtype)
        del params
    emit({"phase": "checkpoints", "ok": True,
          "seconds": round(time.perf_counter() - t0, 2)})


def child_e2e(size, seed, rehearsal):
    """The colocated async loop through scripts/bench_e2e_grpo.py's own
    main(); its one JSON line is captured and checked."""
    import contextlib
    import io

    from areal_tpu.utils.runtime import device_report

    t0 = time.perf_counter()
    _open_device(rehearsal)
    sys.path.insert(0, os.path.join(HERE, "scripts"))
    import bench_e2e_grpo as e2e

    sys.argv = ["bench_e2e_grpo.py", "--modes", "async", "--warmup", "0",
                *size["e2e"]]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        e2e.main()
    result = json.loads(buf.getvalue().strip().splitlines()[-1])
    loop = result["async"]
    check(loop["steps"] >= 3, f"async loop took {loop['steps']} steps")
    check(loop["trajectories"] > 0 and loop["effective_tokens"] > 0,
          "async loop consumed no trajectories")
    check(loop["version_span_trajectories"] >= 1,
          "no trajectory was generated across a weight publish")
    import math

    check(all(math.isfinite(x) for x in loop["loss_trajectory"]),
          f"non-finite loss: {loop['loss_trajectory']}")
    dev = device_report()
    emit({"phase": "async_loop", "ok": True,
          "seconds": round(time.perf_counter() - t0, 2),
          # pack signatures compiled ahead of the loop
          "compile_seconds": result["warm_s"],
          "steps": loop["steps"], "trajectories": loop["trajectories"],
          "version_span_trajectories": loop["version_span_trajectories"],
          "effective_tokens": loop["effective_tokens"],
          "loop_wall_seconds": loop["wall_s"],
          "publish_pause_seconds_mean": loop["pause_window_s_mean"],
          "loss": loop["loss_trajectory"],
          "peak_bytes_in_use": dev["peak_bytes_in_use"], "device": dev})


def child_mesh4(size, seed, rehearsal):
    """Four chips, one process: the same steps on an fsdp=2 x tp=2 mesh and
    on one device, from the same seed and batch."""
    import jax
    import numpy as np

    from areal_tpu.api.config import MeshConfig
    from areal_tpu.utils.runtime import device_report

    t0 = time.perf_counter()
    dev = _open_device(rehearsal)
    check(dev["count"] >= 4, f"needs 4 devices, found {dev['count']}")
    actor, sharded = _train_steps(
        size, seed, steps=3,
        mesh=MeshConfig(fsdp_parallel_size=2, tensor_parallel_size=2),
    )
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()[:4]
    ]
    hlo = actor.train_step_hlo()
    collectives = {
        op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                   "collective-permute")
    }
    check(sum(collectives.values()) > 0, "sharded step has no collectives")
    if not rehearsal:
        check(all(b for b in in_use) and min(in_use) > 0.5 * max(in_use),
              f"state is not spread over the four devices: {in_use}")
        check("tpu_custom_call" in hlo, "sharded step lost the splash kernel")
    actor.destroy()
    del actor
    actor, single = _train_steps(size, seed, steps=3)
    actor.destroy()
    diffs = [abs(a - b) for a, b in zip(sharded["loss"], single["loss"])]
    # bf16 parameters and activations: 8 mantissa bits
    tol = [2.0 ** -6 * max(1.0, abs(b)) for b in single["loss"]]
    check(all(d <= t for d, t in zip(diffs, tol)),
          f"sharded vs one-device losses differ: {sharded['loss']} vs "
          f"{single['loss']}")
    dev = device_report()
    emit({"phase": "mesh_fsdp2_tp2", "ok": True,
          "seconds": round(time.perf_counter() - t0, 2),
          "compile_seconds": sharded["compile_seconds"],
          "steps": 3, "sharded": sharded, "one_device": single,
          "loss_abs_diff": diffs, "collectives": collectives,
          "bytes_in_use_per_device": in_use,
          "peak_bytes_in_use": dev["peak_bytes_in_use"], "device": dev})


# ---------------------------------------------------------------------------
# parent: never imports JAX
# ---------------------------------------------------------------------------


class Parent:
    def __init__(self, args):
        self.args = args
        self.size_name = "tiny" if args.cpu_rehearsal else "real"
        self.size = SIZES[self.size_name]
        # a rehearsal's logs stay apart from a chip run's
        self.logs = LOGS + ("_rehearsal" if args.cpu_rehearsal else "")
        self.env = dict(os.environ)
        self.procs = []
        self.devices = []  # one report per child that held the device

    # ---- process plumbing ------------------------------------------------

    def child_cmd(self, name):
        cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
               "--seed", str(self.args.seed)]
        if self.args.cpu_rehearsal:
            cmd.append("--cpu-rehearsal")
        if self.args.four_chips:
            cmd.append("--four-chips")
        return cmd

    def spawn(self, cmd, log_name, env=None, stdout=None, text=None):
        """Start a process of its own group; stderr (and stdout unless it
        is piped to the parent) goes to chiprun_out/chip_smoke/<log_name>."""
        log = open(os.path.join(self.logs, log_name), "w")
        p = subprocess.Popen(
            cmd, cwd=HERE, env=env or self.env, stderr=log,
            stdout=stdout or log, text=text, start_new_session=True,
        )
        self.procs.append(p)
        return p

    def stop(self, p, grace=15):
        if p.poll() is None:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGTERM)
                p.wait(timeout=grace)
            except (ProcessLookupError, subprocess.TimeoutExpired):
                pass
        if p.poll() is None:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except ProcessLookupError:
                pass
            p.wait()

    def stop_all(self):
        for p in self.procs:
            self.stop(p)

    def run_child(self, name, on_device=None, env=None):
        """Run a child to its end, relaying its JSON lines; -> its phase
        record.  `on_device` fires once the child has said which device it
        holds (the earliest moment the parent knows a chip is there)."""
        p = self.spawn(self.child_cmd(name), f"{name}.log", env=env,
                       stdout=subprocess.PIPE, text=True)
        record = None
        for line in p.stdout:
            line = line.strip()
            if not line.startswith("{"):
                continue
            rec = json.loads(line)
            emit(rec)
            if rec.get("phase") == "device":
                if on_device:
                    on_device()
            elif rec.get("ok"):
                record = rec
        rc = p.wait()
        check(rc == 0 and record is not None,
              f"phase {name} failed (exit {rc}); see chiprun_out/chip_smoke/"
              f"{name}.log\n{_tail(os.path.join(self.logs, name + '.log'))}")
        if "device" in record:
            self.devices.append(record["device"])
        return record

    # ---- the server phase (the parent is the HTTP client) ----------------

    def server_phase(self, phase, extra, update_from=None, expect=None):
        size = self.size
        port = _free_port()
        cmd = [sys.executable, "-m", "areal_tpu.gen.server",
               "--model-path", os.path.join(WORK, "ckpt_a"),
               "--port", str(port), "--n-slots", str(size["slots"]),
               "--max-seq-len", str(size["max_seq_len"]), *extra]
        t0 = time.perf_counter()
        p = self.spawn(cmd, f"{phase}.log")
        base = f"http://127.0.0.1:{port}"
        try:
            _wait_health(base, p, timeout=900)
            load_s = time.perf_counter() - t0
            health = _get(base + "/health")
            check(health["status"] == "ok", f"/health says {health}")
            with open(os.path.join(WORK, "ckpt_a", "config.json")) as f:
                vocab = json.load(f)["vocab_size"]
            prompts = _prompts(self.args.seed, size["prompt_lens"], vocab)

            def generate_all():
                t = time.perf_counter()
                with ThreadPoolExecutor(len(prompts)) as pool:
                    out = list(pool.map(
                        lambda ids: _post(base + "/generate", {
                            "input_ids": ids,
                            "sampling_params": {
                                "max_new_tokens": size["new_tokens"],
                                "temperature": 0.0,
                            },
                        }, timeout=900),
                        prompts,
                    ))
                for r in out:
                    check(len(r["output_tokens"]) == size["new_tokens"]
                          and len(r["output_logprobs"]) == size["new_tokens"],
                          f"short /generate answer: {r}")
                    check(all(lp <= 0.0 and lp == lp
                              for lp in r["output_logprobs"]),
                          f"bad logprobs: {r['output_logprobs']}")
                return [r["output_tokens"] for r in out], \
                    time.perf_counter() - t

            cold_tokens, cold_s = generate_all()
            # warm round with a pause in the middle of it: the held
            # requests finish after /continue_generation, unchanged
            with ThreadPoolExecutor(1) as pool:
                warm = pool.submit(generate_all)
                time.sleep(0.05)
                _post(base + "/pause_generation", {})
                check(_get(base + "/health")["status"] == "paused",
                      "/pause_generation did not pause")
                _post(base + "/continue_generation", {})
                check(_get(base + "/health")["status"] == "ok",
                      "/continue_generation did not resume")
                warm_tokens, warm_s = warm.result()
            check(warm_tokens == cold_tokens,
                  "greedy output changed between two identical rounds")
            n_requests = 2 * len(prompts)
            metrics = _get(base + "/metrics")
            rec = {"load_seconds": round(load_s, 2),
                   "cold_round_seconds": round(cold_s, 2),
                   "warm_round_seconds": round(warm_s, 2),
                   "compile_seconds": round(cold_s - warm_s, 2)}
            if update_from:
                v0 = metrics["version"]
                t = time.perf_counter()
                ans = _post(base + "/update_weights_from_disk",
                            {"path": update_from}, timeout=900)
                rec["weight_update_seconds"] = round(time.perf_counter() - t, 2)
                metrics = _get(base + "/metrics")
                check(ans["ok"] and metrics["version"] > v0,
                      f"version did not advance: {v0} -> {metrics['version']}")
                new_tokens, _ = generate_all()
                n_requests += len(prompts)
                check(new_tokens != cold_tokens,
                      "greedy output did not change with the new weights")
                rec["version"] = [v0, metrics["version"]]
                metrics = _get(base + "/metrics")
            if expect is not None:
                check(metrics["ragged_dispatches"] > 0,
                      "a server left to its default dispatched no ragged kernel")
                same = [a == b for a, b in zip(cold_tokens, expect)]
                check(all(same),
                      f"ragged tokens differ from dense on prompts "
                      f"{[i for i, s in enumerate(same) if not s]}: "
                      f"{cold_tokens} vs {expect}")
                rec["ragged_dispatches"] = metrics["ragged_dispatches"]
                rec["tokens_equal_dense"] = True
            else:
                check(metrics["ragged_dispatches"] == 0,
                      "dense server dispatched the ragged kernel")
            dev = metrics["device"]
            if not self.args.cpu_rehearsal:
                check(dev["platform"] == "tpu",
                      f"server ran on {dev['platform']!r}")
            self.devices.append(dev)
            emit({"phase": phase, "ok": True,
                  "seconds": round(time.perf_counter() - t0, 2), **rec,
                  "requests": n_requests,
                  "tokens_generated": metrics["tokens_generated"],
                  "peak_bytes_in_use": dev["peak_bytes_in_use"],
                  "device": dev})
            return cold_tokens
        except SmokeFailure as e:
            raise SmokeFailure(
                f"{e}\n{_tail(os.path.join(self.logs, phase + '.log'))}")
        finally:
            self.stop(p)

    # ---- the launcher phase (four chips) ---------------------------------

    def launcher_phase(self):
        lz = self.size["launcher"]
        ckpt = os.path.join(WORK, "ckpt_a")
        fileroot = os.path.join(WORK, "exp")
        cfg_path = os.path.join(WORK, "launcher.yaml")
        with open(cfg_path, "w") as f:
            f.write(_LAUNCHER_YAML.format(
                ckpt=ckpt, fileroot=fileroot,
                data=os.path.join(WORK, "train.jsonl"), **lz))
        t0 = time.perf_counter()
        log_path = os.path.join(self.logs, "launcher.log")
        p = self.spawn(
            [sys.executable, "-m", "areal_tpu.launcher.local",
             os.path.join("examples", "math", "gsm8k_grpo.py"),
             "--config", cfg_path],
            "launcher.log",
        )
        logs_dir = os.path.join(fileroot, "chip-smoke", "t0", "logs")
        server_log = os.path.join(logs_dir, "gen_server_0.log")
        dev = None
        try:
            # the launcher stops its servers when the trainer ends: ask the
            # server which chips it holds while it is up, read the weight
            # versions it reached from its log afterwards
            while p.poll() is None:
                time.sleep(0.5)
                check(time.perf_counter() - t0 < 1500, "launcher path timed out")
                port = dev is None and _find_port(log_path)
                if port:
                    try:
                        dev = _get(f"http://127.0.0.1:{port}/metrics",
                                   timeout=5)["device"]
                    except (urllib.error.URLError, OSError, ValueError):
                        pass
            trainer_log = os.path.join(logs_dir, "trainer_run0.log")
            steps = _count(trainer_log, "done.")
            check(p.returncode == 0,
                  f"launcher exited {p.returncode}\n{_tail(log_path)}\n"
                  f"--- trainer\n{_tail(trainer_log)}\n"
                  f"--- server\n{_tail(server_log)}")
            check(steps >= 2, f"trainer logged {steps} finished steps")
            versions = _versions(server_log)
            check(versions and versions[-1] >= 2,
                  f"server weight version never advanced: {versions}")
            plan = _grep(log_path, "chip plan")
            check(dev is not None and dev["count"] == 2
                  or self.args.cpu_rehearsal,
                  f"the tp=2 server saw {dev}")
            emit({"phase": "launcher_disaggregated", "ok": True,
                  "seconds": round(time.perf_counter() - t0, 2),
                  "steps": steps, "server_versions": versions,
                  "chip_plan": plan, "server_device": dev,
                  "peak_bytes_in_use": dev and dev["peak_bytes_in_use"]})
        finally:
            self.stop(p, grace=30)
            for name in os.listdir(logs_dir) if os.path.isdir(logs_dir) else ():
                shutil.copy(os.path.join(logs_dir, name),
                            os.path.join(self.logs, "launcher_" + name))

    # ---- the run ----------------------------------------------------------

    def run(self):
        args = self.args
        if args.cpu_rehearsal:
            check(os.environ.get("JAX_PLATFORMS", "").strip() == "cpu",
                  "--cpu-rehearsal needs JAX_PLATFORMS=cpu in the environment")
            if args.four_chips:
                self.env["XLA_FLAGS"] = (
                    "--xla_force_host_platform_device_count=4")
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        os.makedirs(self.logs, exist_ok=True)
        t0 = time.perf_counter()
        ckpt = {}

        def start_checkpoints():
            # CPU-only work beside the child that holds the chip
            env = dict(self.env, JAX_PLATFORMS="cpu")
            ckpt["proc"] = self.spawn(
                self.child_cmd("checkpoints"), "checkpoints.log", env=env,
                stdout=subprocess.PIPE, text=True)

        def wait_checkpoints():
            out, _ = ckpt["proc"].communicate()
            for line in out.splitlines():
                if line.startswith("{"):
                    emit(json.loads(line))
            check(ckpt["proc"].returncode == 0,
                  "writing checkpoints failed\n"
                  + _tail(os.path.join(self.logs, "checkpoints.log")))

        if args.four_chips:
            self.run_child("mesh4", on_device=start_checkpoints)
            wait_checkpoints()
            self.launcher_phase()
        else:
            self.run_child("trainer", on_device=start_checkpoints)
            wait_checkpoints()
            dense = self.server_phase(
                "server_dense", ["--no-ragged-attn"],
                update_from=os.path.join(WORK, "ckpt_b"))
            # no flag: the server's own default must take the kernel
            self.server_phase("server_ragged", [], expect=dense)
            self.run_child("e2e")
        total = round(time.perf_counter() - t0, 2)
        check(self.devices, "no child reported a device")
        first = self.devices[0]
        want = 4 if args.four_chips else 1
        emit({"phase": "total", "seconds": total,
              "size": self.size_name,
              "devices_reported": len(self.devices)})
        if args.cpu_rehearsal:
            # a rehearsal proves the control flow and nothing about a chip
            emit({"ok": False, "rehearsal": True, "passed": True,
                  "device": {k: first[k] for k in ("platform", "kind", "count")}})
            return 0
        check(all(d["platform"] == "tpu" and d["kind"] == first["kind"]
                  for d in self.devices),
              f"children disagree on the device: {self.devices}")
        check(first["count"] == want,
              f"expected {want} chip(s), the first child saw {first['count']}")
        shutil.rmtree(WORK, ignore_errors=True)
        print(json.dumps({"ok": True, "device": {
            "platform": first["platform"], "kind": first["kind"],
            "count": first["count"]}}), flush=True)
        return 0


_LAUNCHER_YAML = """\
experiment_name: chip-smoke
trial_name: t0
seed: 1
total_train_epochs: 1
total_train_steps: {steps}
async_training: true
tokenizer_path: {ckpt}
cluster:
  fileroot: {fileroot}
allocation_mode: "jax:d1t2+jax:f2"
train_dataset:
  path: {data}
  type: gsm8k
  batch_size: {batch}
  max_length: 128
gconfig:
  n_samples: {n_samples}
  max_new_tokens: {new_tokens}
  temperature: 1.0
rollout:
  max_concurrent_rollouts: 16
  consumer_batch_size: {batch}
  max_head_offpolicyness: 2
  request_timeout: 900
gen_server:
  model_path: {ckpt}
  max_seqs: {slots}
  max_context_len: {max_seq_len}
actor:
  path: {ckpt}
  dtype: {dtype}
  param_dtype: {dtype}
  gradient_checkpointing: true
  group_size: {n_samples}
  ppo_n_minibatches: 1
  pack_length_quantum: {quantum}
  max_pack_length: {max_seq_len}
  mesh:
    fsdp_parallel_size: 2
  adv_norm:
    mean_level: group
    std_level: group
  optimizer:
    lr: 1.0e-6
    warmup_steps_proportion: 0.0
saver:
  freq_steps: null
checkpointer:
  freq_steps: null
evaluator:
  freq_steps: null
recover:
  mode: disabled
stats_logger:
  fileroot: {fileroot}
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _post(url, body, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _wait_health(base, proc, timeout):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        check(proc.poll() is None,
              f"the server exited with {proc.returncode} before /health")
        try:
            _get(base + "/health", timeout=2)
            return
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(0.5)
    raise SmokeFailure(f"no /health within {timeout}s")


def _prompts(seed, lens, vocab):
    import random

    rng = random.Random(seed)
    return [[rng.randrange(vocab) for _ in range(n)] for n in lens]


def _tail(path, n=30):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return f"(no {path})"


def _grep(path, needle):
    try:
        with open(path, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line.strip()
    except OSError:
        pass
    return None


def _count(path, needle):
    try:
        with open(path, errors="replace") as f:
            return sum(needle in line for line in f)
    except OSError:
        return 0


def _versions(server_log):
    """Weight versions a server logged (gen/server.py: "weights at
    version N")."""
    out = []
    try:
        with open(server_log, errors="replace") as f:
            for line in f:
                if "weights at version " in line:
                    out.append(int(line.rsplit("version ", 1)[1].split()[0]))
    except OSError:
        pass
    return out


def _find_port(path):
    line = _grep(path, "spawn [gen_server_0]")
    if not line or "--port" not in line:
        return None
    return int(line.split("--port", 1)[1].split()[0])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases (count 4)")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on an explicit CPU run; never prints ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        size = SIZES["tiny" if args.cpu_rehearsal else "real"]
        try:
            if args.child == "checkpoints":
                child_checkpoints(size, args.seed, args.four_chips)
            else:
                {"trainer": child_trainer, "e2e": child_e2e,
                 "mesh4": child_mesh4}[args.child](
                    size, args.seed, args.cpu_rehearsal)
        except SmokeFailure as e:
            print(f"chip_smoke[{args.child}]: {e}", file=sys.stderr)
            return 1
        return 0

    parent = Parent(args)
    try:
        return parent.run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        parent.stop_all()


if __name__ == "__main__":
    sys.exit(main())
