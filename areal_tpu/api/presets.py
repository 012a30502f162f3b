"""Experiment presets + automatic device-allocation heuristics.

Behavioral counterpart of the reference's experiment-preset layer
(realhf/experiments/common/common.py:627 auto device-mesh assignment,
realhf/api/quickstart/device_mesh.py:274 heuristic allocation): given a
model size and a chip budget, pick a sensible allocation expression and a
ready-to-edit config, so users start from `preset("gsm8k-grpo-1.5b")`
instead of a blank YAML.

The heuristics encode the TPU sizing rules the rest of the stack assumes:

- **tp** is chosen so one model replica's train state fits a chip's HBM
  (bf16 params + grads + AdamW moments ~ 8 bytes/param, plus ~25%
  activation headroom under remat);
- **fsdp** absorbs the remaining train chips (GSPMD ZeRO-3 over the fsdp
  axis is the default scale-out, mirroring the reference's FSDP engine);
- generation gets the larger chip share (async RL is generation-bound —
  the reference's benchmark splits ~3:1 gen:train);
- generation servers shard tp only as far as KV-cache+weights demand
  (serving needs ~2 bytes/param + KV, far less than training).
"""

import dataclasses
import math
from typing import Dict, Optional

from areal_tpu.api.alloc import AllocationMode

# per-chip usable HBM bytes (published capacity less ~2 GiB of runtime
# reserve), keyed by `device_kind` as JAX reports it.  Source: Google Cloud
# TPU documentation, system architecture pages (v5e 16 GB, v5p 95 GB,
# v4 32 GB).  A kind that is not here is an error, not a default.
HBM_BYTES = {
    "TPU v5 lite": 14 * 1024**3,
    "TPU v5p": 90 * 1024**3,
    "TPU v4": 28 * 1024**3,
}
# the chip the presets plan for unless the caller names another
DEFAULT_DEVICE_KIND = "TPU v5 lite"


def hbm_bytes_for(device_kind: str) -> int:
    if device_kind not in HBM_BYTES:
        raise ValueError(
            f"no HBM size for device kind {device_kind!r}: add it to "
            f"api/presets.py HBM_BYTES (known: {sorted(HBM_BYTES)}) or pass "
            "hbm_bytes"
        )
    return HBM_BYTES[device_kind]

TRAIN_BYTES_PER_PARAM = 8.0 * 1.25  # bf16 p+g + f32 moments, remat headroom
GEN_BYTES_PER_PARAM = 2.0 * 1.5  # bf16 weights + KV/activation headroom


def _pow2_at_least(x: float, cap: int) -> int:
    p = 1
    while p < x and p < cap:
        p *= 2
    return p


def _pow2_divisors(n: int):
    p = 1
    while p <= n:
        if n % p == 0:
            yield p
        p *= 2


def search_allocation(
    n_devices: int,
    n_params: float,
    ctx_len: int = 4096,
    gen_cost_ratio: float = 3.0,
    hbm_bytes: Optional[int] = None,
    device_kind: str = DEFAULT_DEVICE_KIND,
    hidden_size: Optional[float] = None,
    num_layers: Optional[float] = None,
    gen_concurrency: int = 32,
) -> Dict:
    """Enumerate-and-score allocation search (the depth of the reference's
    device-mesh search, realhf/api/quickstart/device_mesh.py:274, with a
    TPU cost model instead of GPU profiles).

    Every pow-2 split of chips into gen (dp x tp) and train
    (fsdp x sp x tp) is checked for HBM feasibility and scored by a
    throughput model:

    - trainer consumption ~ n_train scaled by a collective-overhead factor
      per doubling of tp/sp (intra-replica collectives ride ICI but still
      cost bandwidth);
    - generation supply ~ n_gen similarly scaled; the system rate is
      min(train_rate, gen_rate / gen_cost_ratio) — async RL is
      generation-bound, the reference benchmarks split chips ~3:1;
    - memory: train state bytes shard over (tp x fsdp), activation bytes
      (~ctx-linear under remat) over (tp x sp); serving weights AND the
      KV cache for `gen_concurrency` sequences of ctx_len shard over the
      serving tp.

    Returns {"expr", "score", "n_gen", "n_train", ...} for the best split.
    """
    if n_devices < 2:
        raise ValueError("async RL needs >= 2 chips (gen + train)")
    hbm = hbm_bytes or hbm_bytes_for(device_kind)
    # coarse dense-transformer shape: real models keep layers ~ hidden/128
    # (e.g. Qwen2.5-7B: 3584/28), so from n = 12*L*h^2 = 12*h^3/128:
    if hidden_size:
        hidden = hidden_size
        layers = num_layers or max(4.0, n_params / (12 * hidden * hidden))
    else:
        hidden = max(512.0, 128.0 * round((n_params * 128 / 12) ** (1 / 3) / 128))
        layers = num_layers or max(4.0, n_params / (12 * hidden * hidden))
    # per-token activation bytes under full remat: layer inputs + head
    act_bytes_per_token = 2.0 * hidden * (layers + 8)
    # per-token KV bytes (bf16 K+V, GQA kv width ~hidden/4)
    kv_bytes_per_token = 2.0 * 2.0 * layers * (hidden / 4)
    train_state = n_params * TRAIN_BYTES_PER_PARAM
    gen_state = n_params * GEN_BYTES_PER_PARAM

    def axis_eff(k: int, per_double: float) -> float:
        return 1.0 / (1.0 + per_double * math.log2(max(k, 1)))

    # KV cache for the concurrent-rollout budget shards over the serving tp
    # axis along with the weights
    gen_kv = gen_concurrency * ctx_len * kv_bytes_per_token
    best = None
    for gen_tp in _pow2_divisors(n_devices):
        if (gen_state + gen_kv) / gen_tp > hbm:
            continue
        for n_gen in range(gen_tp, n_devices, gen_tp):
            n_train = n_devices - n_gen
            gen_rate = n_gen * axis_eff(gen_tp, 0.10)
            for tp in _pow2_divisors(n_train):
                for sp in _pow2_divisors(n_train // tp):
                    fsdp = n_train // (tp * sp)
                    state_pc = train_state / (tp * fsdp)
                    act_pc = ctx_len * act_bytes_per_token / (tp * sp)
                    if state_pc + act_pc > hbm:
                        continue
                    train_rate = n_train * axis_eff(tp, 0.08) * axis_eff(sp, 0.05)
                    score = min(train_rate, gen_rate / gen_cost_ratio)
                    # prefer simpler meshes on ties (fewer sharded axes)
                    complexity = (tp > 1) + (sp > 1) + (gen_tp > 1)
                    key = (score, -complexity, n_gen)
                    if best is None or key > best["key"]:
                        gen = f"jax:d{n_gen // gen_tp}" + (
                            f"t{gen_tp}" if gen_tp > 1 else ""
                        )
                        train = "jax:" + (f"f{fsdp}" if fsdp > 1 else "d1") + (
                            f"s{sp}" if sp > 1 else ""
                        ) + (f"t{tp}" if tp > 1 else "")
                        best = {
                            "key": key,
                            "expr": f"{gen}+{train}",
                            "score": score,
                            "n_gen": n_gen,
                            "n_train": n_train,
                            "gen_tp": gen_tp,
                            "train_tp": tp,
                            "train_sp": sp,
                            "train_fsdp": fsdp,
                        }
    if best is None:
        raise ValueError(
            f"{n_devices} chips cannot host model {n_params / 1e9:.1f}B at "
            f"ctx {ctx_len} (train state {train_state / 1e9:.1f} GB)"
        )
    AllocationMode.from_str(best["expr"])  # validate against the real parser
    del best["key"]
    return best


def auto_allocation(
    n_devices: int,
    n_params: float,
    gen_fraction: float = 0.75,  # kept for API compat; the search owns the split
    hbm_bytes: Optional[int] = None,
    device_kind: str = DEFAULT_DEVICE_KIND,
    ctx_len: int = 4096,
) -> str:
    """Pick a disaggregated allocation expression for an async-RL run.

    Returns e.g. "jax:d6t2+jax:f2t2" — gen servers on the left of '+',
    trainer mesh on the right (api/alloc.py dialect)."""
    return search_allocation(
        n_devices,
        n_params,
        ctx_len=ctx_len,
        hbm_bytes=hbm_bytes,
        device_kind=device_kind,
    )["expr"]


# ---------------------------------------------------------------------------
# Named experiment presets
# ---------------------------------------------------------------------------


def _gsm8k_grpo(model_path: str, n_params: float, n_devices: int) -> Dict:
    """Config-dict preset mirroring examples/math/gsm8k_grpo.py + the
    reference's example YAMLs (examples/math/gsm8k_grpo.yaml)."""
    return {
        "experiment_name": "gsm8k-grpo",
        "trial_name": "trial0",
        "allocation_mode": auto_allocation(n_devices, n_params),
        "train_dataset": {
            "path": "openai/gsm8k",
            "type": "gsm8k",
            "batch_size": 8,
            "shuffle": True,
        },
        "actor": {
            "experiment_name": "gsm8k-grpo",
            "trial_name": "trial0",
            "path": model_path,
            "dtype": "bfloat16",
            "group_size": 8,
            "group_reward_norm": True,
            "use_decoupled_loss": True,
            "recompute_logprob": True,
            "ppo_n_minibatches": 2,
            "optimizer": {"lr": 1e-6, "lr_scheduler_type": "constant"},
        },
        "gconfig": {
            "max_new_tokens": 1024,
            "temperature": 1.0,
            "n_samples": 8,
        },
        "rollout": {
            "experiment_name": "gsm8k-grpo",
            "trial_name": "trial0",
            "max_concurrent_rollouts": 64,
            "max_head_offpolicyness": 4,
        },
        "gen_server": {"model_path": model_path, "max_context_len": 2048},
    }


_PRESETS = {
    "gsm8k-grpo-tiny": lambda: _gsm8k_grpo("", 5e6, 2),
    "gsm8k-grpo-1.5b": lambda: _gsm8k_grpo("Qwen/Qwen2.5-1.5B-Instruct", 1.54e9, 8),
    "gsm8k-grpo-7b": lambda: _gsm8k_grpo("Qwen/Qwen2.5-7B-Instruct", 7.6e9, 32),
}


def preset(name: str) -> Dict:
    """A ready-to-edit config dict (feed to load_expr_config via YAML dump,
    or use as overrides)."""
    if name not in _PRESETS:
        raise ValueError(f"unknown preset {name!r}; known: {sorted(_PRESETS)}")
    return _PRESETS[name]()


def list_presets():
    return sorted(_PRESETS)


def main():
    """Preset browser / allocation helper:

        python -m areal_tpu.api.presets                  # list names
        python -m areal_tpu.api.presets gsm8k-grpo-1.5b  # config as JSON
        python -m areal_tpu.api.presets --alloc 1.5e9 8  # just the
                                                         # allocation expr

    The JSON is the ready-to-edit config: dump to YAML and feed
    load_expr_config, or use as overrides."""
    import argparse
    import json

    p = argparse.ArgumentParser()
    p.add_argument("name", nargs="?", default="")
    p.add_argument(
        "--alloc",
        nargs=2,
        metavar=("N_PARAMS", "N_CHIPS"),
        help="print the auto allocation expression for a model size "
        "(params, float ok: 1.5e9) on a chip budget",
    )
    p.add_argument("--ctx-len", type=int, default=4096)
    args = p.parse_args()
    if args.alloc:
        n_params, n_devices = float(args.alloc[0]), int(args.alloc[1])
        print(auto_allocation(n_devices, n_params, ctx_len=args.ctx_len))
        return
    if not args.name:
        print("\n".join(list_presets()))
        return
    print(json.dumps(preset(args.name), indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
