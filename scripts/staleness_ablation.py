"""η-staleness ablation: decoupled PPO vs naive PPO under stale rollouts.

The acceptance test SURVEY.md §7 / BASELINE.md call for: reproduce the
*shape* of the reference's ablation (blog/AReaL_v0_3.md:231-244) — with the
decoupled objective, training quality stays near the zero-staleness oracle
for η ≤ 4, while naive PPO (ratio against the stale behaviour policy)
degrades as η grows.

Model-scale setup (VERDICT r2 #5): a real (WordPiece) tokenizer and a
4-layer / 256-hidden Qwen2-class transformer learn single-digit-sum
arithmetic ("Q: 3 + 4 = ?  A:") — GSM8K's shape at a size a scratch model
can learn.  A short SFT warm-start (mirroring RL-from-distilled practice)
gives the policy a format prior; GRPO then improves accuracy with rollouts
that are η policy versions stale: a FIFO holds the last η batches, so at
step t the trainer consumes the batch *generated by the policy of step
t-η* — exactly the off-policyness the async runtime's StalenessManager
admits — while generation always uses the newest policy.

Writes STALENESS_ABLATION.json (+ prints a summary table).  Usage:
    python scripts/staleness_ablation.py [--steps N] [--etas 0 1 2 4] [--out PATH]
"""

import argparse
import collections
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402


import jax.numpy as jnp  # noqa: E402

from areal_tpu.api.config import (  # noqa: E402
    MeshConfig,
    MicroBatchSpec,
    NormConfig,
    OptimizerConfig,
    PPOActorConfig,
)
from areal_tpu.api.io_struct import FinetuneSpec  # noqa: E402
from areal_tpu.engine.ppo import JaxPPOActor  # noqa: E402
from areal_tpu.models import forward  # noqa: E402
from areal_tpu.models.model_config import tiny_config  # noqa: E402
from areal_tpu.ops import sft_loss_fn  # noqa: E402

GROUP = 4
PROMPTS_PER_BATCH = 32  # x GROUP samples = 128 rows
GEN = 3  # enough for " 12" style answers


def _tokenizer(tmpdir: str):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
    from fixtures import make_tiny_tokenizer

    return make_tiny_tokenizer(tmpdir, vocab_size=256)


def _problems(rng: np.random.Generator, n: int):
    # sums capped at 9: single-token answers keep the reward surface sharp
    # for a scratch 3M model (the ablation is about OFF-POLICY dynamics,
    # not arithmetic depth)
    a = rng.integers(0, 10, n)
    b = np.array([rng.integers(0, 10 - x) for x in a])
    return a, b, a + b


def _encode_prompts(tok, a, b):
    return [tok.encode(f"Q: {x} + {y} = ? A:", add_special_tokens=False)
            for x, y in zip(a, b)]


class Task:
    """Tokenised arithmetic episodes + reward."""

    def __init__(self, tok, max_prompt: int, L: int):
        self.tok = tok
        self.max_prompt = max_prompt
        self.L = L

    def batch_prompts(self, rng):
        a, b, ans = _problems(rng, PROMPTS_PER_BATCH)
        enc = _encode_prompts(self.tok, a, b)
        return enc, np.repeat(ans, GROUP)

    def reward(self, completion_text: str, answer: int) -> float:
        digits = "".join(c for c in completion_text if c.isdigit() or c == " ")
        toks = digits.split()
        return 1.0 if toks and toks[0] == str(answer) else 0.0


def _model_cfg(vocab: int, L: int):
    return tiny_config(
        vocab_size=vocab,
        hidden_size=256,
        intermediate_size=704,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        qkv_bias=True,
        max_position_embeddings=2 * L,
        dtype="float32",
        eos_token_id=None,
        hf_architecture="Qwen2ForCausalLM",
    )


def _sample_batch(actor, mcfg, task, rng_np, key, temperature=1.0,
                  phases=None):
    """Generate GEN tokens per row; returns the trajectory dict with
    behaviour logprobs, rewards, and loss masks.

    `phases` = [(params, n_tokens, version), ...] models INTERRUPTED
    generation (VERDICT r3 weak #6's second axis): a weight update lands
    mid-trajectory, so the first tokens come from an older policy and the
    rest from a newer one — exactly what the real system's interruptible
    generation + resubmission produces (per-token versions included).
    Default: one phase under the current policy."""
    enc, answers = task.batch_prompts(rng_np)
    enc = [e[: task.max_prompt] for e in enc]
    plens = np.array([len(e) for e in enc]).repeat(GROUP)
    B = PROMPTS_PER_BATCH * GROUP
    L = task.L
    ids = np.zeros((B, L), np.int32)
    for i, e in enumerate(np.repeat(np.arange(PROMPTS_PER_BATCH), GROUP)):
        ids[i, : len(enc[e])] = enc[e]
    logps = np.zeros((B, L), np.float32)
    versions = np.zeros((B, L), np.int32)
    pos = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L))
    seg = np.zeros((B, L), np.int32)
    if phases is None:
        phases = [(actor.params, GEN, 0)]
    assert sum(n for _, n, _ in phases) == GEN

    @jax.jit
    def step_logits(params, ids):
        return forward(params, mcfg, ids, pos, seg)

    row = np.arange(B)
    t = 0
    for params, n_tokens, ver in phases:
        for _ in range(n_tokens):
            logits = np.asarray(step_logits(params, jnp.asarray(ids)))
            cur = logits[row, plens + t - 1] / temperature
            lsm = cur - (
                np.log(np.exp(cur - cur.max(-1, keepdims=True)).sum(-1, keepdims=True))
                + cur.max(-1, keepdims=True)
            )
            key, sub = jax.random.split(key)
            tok = np.asarray(jax.random.categorical(sub, jnp.asarray(cur), axis=-1))
            ids[row, plens + t] = tok
            logps[row, plens + t] = lsm[row, tok]
            versions[row, plens + t] = ver
            t += 1

    rewards = np.zeros(B, np.float32)
    for i in range(B):
        comp = task.tok.decode(ids[i, plens[i] : plens[i] + GEN].tolist())
        rewards[i] = task.reward(comp, int(answers[i]))
    mask = np.zeros((B, L), bool)
    loss_mask = np.zeros((B, L), np.float32)
    for i in range(B):
        mask[i, : plens[i] + GEN] = True
        loss_mask[i, plens[i] : plens[i] + GEN] = 1.0
    return {
        "input_ids": ids,
        "attention_mask": mask,
        "loss_mask": loss_mask,
        "logprobs": logps,
        "rewards": rewards,
        "versions": versions,
    }, key


def _sft_warmstart(actor, task, steps: int, rng_np):
    """Teach the answer format (and some arithmetic) supervised, so RL
    starts from a model whose samples earn nonzero reward — the ablation
    compares RL dynamics, not exploration from noise."""
    L = task.L
    for _ in range(steps):
        a, b, ans = _problems(rng_np, PROMPTS_PER_BATCH)
        rows, masks, lmask = [], [], []
        for x, y, z in zip(a, b, ans):
            e = task.tok.encode(
                f"Q: {x} + {y} = ? A: {z}", add_special_tokens=False
            )[:L]
            p = task.tok.encode(
                f"Q: {x} + {y} = ? A:", add_special_tokens=False
            )
            row = np.zeros(L, np.int32)
            row[: len(e)] = e
            m = np.zeros(L, bool)
            m[: len(e)] = True
            lm = np.zeros(L, np.float32)
            lm[len(p) : len(e)] = 1.0
            rows.append(row)
            masks.append(m)
            lmask.append(lm)
        batch = {
            "input_ids": np.stack(rows),
            "attention_mask": np.stack(masks),
            "loss_mask": np.stack(lmask),
        }
        actor.train_batch(
            batch, sft_loss_fn, lambda b: float(np.sum(b["loss_mask"]))
        )


def _task_setup(tok):
    max_prompt = max(
        len(e) for e in _encode_prompts(tok, *_problems(np.random.default_rng(0), 64)[:2])
    )
    L = max_prompt + GEN
    return Task(tok, max_prompt, L), _model_cfg(tok.vocab_size, L)


def _make_actor(task, mcfg, eta, decoupled, lr, steps):
    cfg = PPOActorConfig(
        experiment_name="abl",
        trial_name=f"eta{eta}-{'dec' if decoupled else 'naive'}",
        init_from_scratch=True,
        dtype="float32",
        param_dtype="float32",
        gradient_checkpointing=False,
        mesh=MeshConfig(),
        mb_spec=MicroBatchSpec(n_mbs=1),
        optimizer=OptimizerConfig(
            lr=lr, warmup_steps_proportion=0.0, weight_decay=0.0,
            gradient_clipping=1.0,
        ),
        pack_length_quantum=task.L,
        max_pack_length=2 * task.L,
        group_size=GROUP,
        ppo_n_minibatches=4,  # >1 magnifies off-policy damage, as in PPO lore
        eps_clip=0.2,
        use_decoupled_loss=decoupled,
        recompute_logprob=decoupled,
        behav_imp_weight_cap=5.0 if decoupled else None,
        adv_norm=NormConfig(mean_level="group", std_level="group", group_size=GROUP),
        temperature=1.0,
    )
    actor = JaxPPOActor(cfg, model_config=mcfg)
    actor.initialize(
        ft_spec=FinetuneSpec(1, steps * PROMPTS_PER_BATCH, PROMPTS_PER_BATCH)
    )
    return actor


def warmstart_params(tok, seed: int, sft_steps: int, sft_lr: float = 1e-3):
    """SFT-warm-started host params, computed ONCE per seed and shared by
    every (eta, objective) RL config — the ablation varies the RL dynamics,
    not the starting point."""
    import jax

    task, mcfg = _task_setup(tok)
    actor = _make_actor(task, mcfg, 0, True, sft_lr, sft_steps + 1)
    rng_np = np.random.default_rng(seed)
    _sft_warmstart(actor, task, sft_steps, rng_np)
    host = jax.tree_util.tree_map(np.asarray, actor.params)
    actor.destroy()
    return host


def run(eta: int, decoupled: bool, steps: int, tok, warm_params,
        seed: int = 0, lr: float = 1e-4, interrupt_every: int = 0):
    """Train with rollouts that are `eta` policy versions stale; returns the
    per-step reward curve (reward of the FRESH batch generated each step —
    i.e. current policy quality).

    `interrupt_every=k` (>0) adds the second ablation axis: every k-th
    training batch is generated INTERRUPTED — its first half comes from
    the policy η steps old, then a weight update lands mid-trajectory and
    the second half resumes under the current policy (mixed per-token
    versions, the real interruptible-generation regime).  The remaining
    batches stay uniformly η-stale via the FIFO."""
    import jax

    task, mcfg = _task_setup(tok)
    actor = _make_actor(task, mcfg, eta, decoupled, lr, steps)
    actor.params = jax.tree_util.tree_map(
        lambda s, w: jax.device_put(np.asarray(w), s.sharding),
        actor.params, warm_params,
    )
    rng_np = np.random.default_rng(seed + 1000)  # disjoint from SFT stream
    key = jax.random.PRNGKey(seed)
    fifo = collections.deque()
    params_hist = collections.deque(maxlen=eta + 1)  # device trees (tiny model)
    curve = []
    trained = 0
    for step in range(steps):
        # host copies: ppo_update donates the device params, which would
        # invalidate plain references held across steps
        params_hist.append(jax.tree_util.tree_map(np.asarray, actor.params))
        fresh, key = _sample_batch(actor, mcfg, task, rng_np, key)
        curve.append(float(fresh["rewards"].mean()))
        fifo.append(fresh)
        if len(fifo) <= eta:
            continue  # warmup: nothing old enough to train on yet
        if (
            interrupt_every > 0
            and eta > 0
            and trained % interrupt_every == 0
            and len(params_hist) > eta
        ):
            # interrupted batch replaces the FIFO one this step: phase A
            # under the η-old policy, phase B resumed under current
            fifo.popleft()
            old_params = params_hist[0]
            batch, key = _sample_batch(
                actor, mcfg, task, rng_np, key,
                phases=[(old_params, GEN // 2, step - eta),
                        (actor.params, GEN - GEN // 2, step)],
            )
            batch = dict(batch)
        else:
            batch = dict(fifo.popleft())  # generated by the policy η steps ago
        if decoupled:
            batch["prox_logp"] = actor.compute_logp(batch)
        actor.compute_advantages(batch)
        actor.ppo_update(batch)
        trained += 1
    actor.destroy()
    return curve


def main():
    import tempfile

    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--etas", type=int, nargs="+", default=[0, 1, 2, 4])
    p.add_argument("--interrupts", type=int, nargs="+", default=[0],
                   help="second axis (VERDICT r3 weak #6): 0 = pure FIFO "
                        "staleness; k>0 = every k-th training batch is "
                        "generated interrupted (first half by the eta-old "
                        "policy, second half resumed under current)")
    p.add_argument("--seeds", type=int, nargs="+", default=[0])
    p.add_argument("--lr", type=float, default=1e-4, help="RL learning rate")
    p.add_argument("--sft-lr", type=float, default=1e-3)
    p.add_argument("--sft-steps", type=int, default=1600)
    p.add_argument("--out", default="STALENESS_ABLATION.json")
    args = p.parse_args()

    with tempfile.TemporaryDirectory() as d:
        tok = _tokenizer(d)
        results = {
            "task": "single-digit-sum QA; reward=1 iff first emitted number "
                    "equals the answer",
            "setup": {
                "model": "4-layer/256-hidden Qwen2-class (qkv-bias, GQA), "
                         "WordPiece tokenizer vocab 256",
                "warmstart": f"{args.sft_steps} SFT steps (lr {args.sft_lr}) "
                             "shared across all RL configs per seed — RL "
                             "starts near the SFT plateau, the reference "
                             "ablation's regime (RL from distilled models)",
                "batch": f"{PROMPTS_PER_BATCH} prompts x {GROUP} GRPO samples",
                "optimizer": f"adamw rl-lr={args.lr}, 4 ppo minibatches per batch",
                "staleness": "FIFO: step t trains on the batch generated by "
                             "the policy from step t-eta",
                "steps": args.steps,
                "seeds": args.seeds,
            },
            "results": {},
        }
        warm = {
            s: warmstart_params(tok, s, args.sft_steps, args.sft_lr)
            for s in args.seeds
        }
        print("warm-start ready", flush=True)
        for eta in args.etas:
            for dec in (True, False):
                for intr in args.interrupts:
                    if intr > 0 and eta == 0:
                        continue  # interruption needs an older policy
                    name = f"eta{eta}_{'decoupled' if dec else 'naive'}"
                    if intr > 0:
                        name += f"_int{intr}"
                    curves = [
                        run(eta, dec, args.steps, tok, warm[s], seed=s,
                            lr=args.lr, interrupt_every=intr)
                        for s in args.seeds
                    ]
                    final = float(np.mean([np.mean(c[-20:]) for c in curves]))
                    results["results"][name] = {
                        "final_reward": final,
                        "curves": curves,
                    }
                    print(f"{name}: final reward {final:.3f}", flush=True)
                    with open(args.out, "w") as f:  # incremental: long run
                        json.dump(results, f, indent=2)

        print(f"\nwrote {args.out}")
        # the acceptance shape (blog/AReaL_v0_3.md ablation): at the largest
        # η, decoupled stays near the η=0 oracle while naive falls behind
        e = max(args.etas)
        res = results["results"]
        if e > 0 and "eta0_decoupled" in res:
            oracle = res["eta0_decoupled"]["final_reward"]
            dec = res[f"eta{e}_decoupled"]["final_reward"]
            naive = res[f"eta{e}_naive"]["final_reward"]
            print(
                f"shape check @ eta={e}: oracle={oracle:.3f} "
                f"decoupled={dec:.3f} naive={naive:.3f}"
            )


if __name__ == "__main__":
    main()
