"""PPO/GRPO actor.

Behavioral counterpart of the reference's `PPOActor`
(areal/engine/ppo/actor.py:25): compute_logp (:52), compute_advantages (:72 —
reward scale/clip/norm, KL-regularized token rewards, GAE, group
normalisation) and ppo_update (:166 — dynamic sampling, minibatch splitting,
stats).  TPU-first differences:

- GAE runs as the reverse `lax.scan` kernel (areal_tpu/ops/gae.py), jitted
  over the whole padded batch — replacing both the reference's CUDA `cugae`
  and its python fallback loop.
- Alignment convention: trajectories arrive token-aligned (arr[t] describes
  token t, the workflow/inference convention); losses consume
  predictor-aligned arrays (arr[t] describes token t+1).
  `compute_advantages` performs that shift ONCE, explicitly — everything it
  writes back (advantages, logprobs, prox_logp, loss_mask) is
  predictor-aligned, matching what `grpo_loss_fn` and `engine.forward`'s
  logprob hook produce.
"""

import functools
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from areal_tpu.api.config import NormConfig, PPOActorConfig
from areal_tpu.engine.jax_train import JaxTrainEngine
from areal_tpu.ops.functional import grpo_loss_fn
from areal_tpu.ops.gae import gae_padded
from areal_tpu.utils import logging, stats, telemetry
from areal_tpu.utils.data import Normalization, split_padded_tensor_dict_into_mb_list

# jitted once per (shape, gamma, lam): eager execution would pay a device
# round-trip per op
_gae_padded_jit = jax.jit(gae_padded, static_argnums=(3, 4))

logger = logging.getLogger("ppo.actor")


def _roll_back(arr: np.ndarray) -> np.ndarray:
    """token-aligned [B, L] -> predictor-aligned (arr[t] <- arr[t+1])."""
    return np.roll(arr, -1, axis=-1)


class PPOActor:
    """Algorithm layer over any TrainEngine (reference: actor.py:25)."""

    # batch keys forwarded into the jitted loss; recipe subclasses extend
    LOSS_KEYS = (
        "input_ids", "attention_mask", "loss_mask", "logprobs",
        "advantages", "prox_logp",
    )
    # sum-reduced loss stats normalised to per-token means after each step
    PER_TOKEN_STAT_KEYS = (
        "importance_weight", "approx_kl", "clip_ratio", "dual_clip_ratio",
        "behave_kl", "behave_imp_weight", "entropy", "new_logp", "old_logp",
        "moe_aux_loss",
    )

    def __init__(self, config: PPOActorConfig, engine):
        self.config = config
        self.engine = engine
        self._pending_stats: List[stats.PendingTrainStats] = []
        def make_norm(norm_cfg):
            if norm_cfg is None:
                return None
            # NormConfig.group_size overrides when set; default to the GRPO
            # group size so the common case needs no duplication
            return Normalization(
                mean_level=norm_cfg.mean_level,
                std_level=norm_cfg.std_level,
                group_size=(
                    norm_cfg.group_size
                    if norm_cfg.group_size > 1
                    else config.group_size
                ),
                eps=norm_cfg.eps,
            )

        self.adv_norm = make_norm(config.adv_norm)
        # explicit NormConfig wins: the recipe variants shape rewards
        # differently (dr.grpo removes the std division entirely, lite_ppo
        # uses group mean + batch std); group_reward_norm is the legacy
        # group/group switch
        self.reward_norm = make_norm(
            config.reward_norm
            or (NormConfig() if config.group_reward_norm else None)
        )

    # ------------------------------------------------------------------

    @telemetry.span("logp")
    def compute_logp(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        """Recompute current-policy logprobs (predictor-aligned [B, L]);
        the proximal policy of the decoupled objective."""
        return self.engine.forward(batch, post_hook=self._get_logp_hook())

    def _get_logp_hook(self):
        """The logp post-hook, built once — the jitted forward is keyed on
        the callable's identity, so compute_logp and warm_shapes must hand
        the engine the SAME object."""
        if not hasattr(self, "_logp_hook"):
            temp = self.config.temperature
            vchunk = getattr(self.config, "lm_head_chunk", 0) or None

            def hook(model_out, mb):
                import jax.numpy as jnp

                from areal_tpu.ops.functional import lm_logprobs_entropy

                labels = jnp.roll(mb["input_ids"], -1, axis=-1)
                logp, _, _ = lm_logprobs_entropy(
                    model_out, labels, temperature=temp, with_entropy=False,
                    vocab_chunk=vchunk,
                )
                return logp

            self._logp_hook = hook
        return self._logp_hook

    # ------------------------------------------------------------------

    @telemetry.span("advantages")
    def compute_advantages(self, batch: Dict[str, np.ndarray]) -> None:
        """In-place: add predictor-aligned advantages/logprobs/loss_mask
        (reference: actor.py:72-165)."""
        cfg = self.config
        mask_tok = batch["loss_mask"].astype(np.float32)  # token-aligned
        B, L = mask_tok.shape

        # ---- sequence-level reward shaping (reference: actor.py:80-118)
        rewards = batch["rewards"].astype(np.float32).copy()
        seq_lens_completion = mask_tok.sum(-1)
        if cfg.mask_no_eos_with_zero and "no_eos" in batch:
            rewards = np.where(batch["no_eos"].astype(bool), 0.0, rewards)
        if cfg.overlong_reward_penalty and cfg.overlong_tokens > 0:
            # DAPO soft length penalty measured against the *configured*
            # generation budget, not the batch's padded width (reference:
            # actor.py:84-89 uses max_new_tokens)
            if cfg.max_new_tokens <= 0:
                raise ValueError(
                    "overlong_reward_penalty requires max_new_tokens to be "
                    "set to the rollout's generation budget"
                )
            overflow = seq_lens_completion - (cfg.max_new_tokens - cfg.overlong_tokens)
            penalty = np.clip(
                overflow / cfg.overlong_tokens, 0.0, 1.0
            ) * cfg.overlong_penalty_factor
            rewards = rewards - penalty
        rewards = (rewards + cfg.reward_bias) * cfg.reward_scaling
        rewards = np.clip(rewards, -cfg.reward_clip, cfg.reward_clip)
        if self.reward_norm is not None:
            rewards = self.reward_norm(rewards[:, None])[:, 0]

        # ---- shift to predictor alignment
        mask = _roll_back(mask_tok)
        mask[:, -1] = 0.0
        prox_logp = batch.get("prox_logp")  # already predictor-aligned
        if prox_logp is not None and not cfg.use_decoupled_loss:
            # plain PPO with a recompute pass: the ratio must be taken
            # against the recomputed policy, so the recomputed logprobs
            # replace the inference engine's (reference: actor.py:103-106)
            old_logp = np.asarray(prox_logp, np.float32) * mask
        else:
            old_logp = _roll_back(batch["logprobs"].astype(np.float32)) * mask

        # ---- token rewards: KL penalty + terminal reward (actor.py:119-135)
        tok_rewards = np.zeros((B, L), np.float32)
        if cfg.kl_ctl > 0 and "ref_logp" in batch:
            ref = _roll_back(batch["ref_logp"].astype(np.float32)) * mask
            from areal_tpu.utils.data import KLEstimator

            kl = KLEstimator(cfg.kl_estimator)(old_logp, ref)
            tok_rewards -= cfg.kl_ctl * kl * mask
        # terminal reward at the last predictor position of each sequence
        idx = np.maximum(mask.shape[1] - 1 - np.argmax(mask[:, ::-1], axis=1), 0)
        has_completion = mask.sum(-1) > 0
        tok_rewards[np.arange(B), idx] += np.where(has_completion, rewards, 0.0)

        # ---- GAE (values default 0: GRPO / reward-to-go)
        # values are NOT rolled: the critic head's output at position t is
        # V(prefix through token t) = the state before emitting token t+1,
        # which is already predictor alignment — rolling would train the
        # critic one step shifted
        values = batch.get("values")
        values = (
            values.astype(np.float32) * mask
            if values is not None
            else np.zeros((B, L), np.float32)
        )
        adv, returns = _gae_padded_jit(
            tok_rewards, values, mask, cfg.discount, cfg.gae_lambda
        )
        adv, returns = jax.device_get((adv, returns))
        if self.adv_norm is not None:
            adv = self.adv_norm(adv, mask)

        batch["advantages"] = adv.astype(np.float32)
        batch["returns"] = returns.astype(np.float32)
        batch["logprobs"] = old_logp.astype(np.float32)
        batch["loss_mask"] = mask.astype(np.float32)
        batch["tot_rewards"] = rewards.astype(np.float32)
        if prox_logp is None and cfg.use_decoupled_loss:
            # without a recompute pass, proximal == behaviour policy
            batch["prox_logp"] = old_logp.astype(np.float32)

    # ------------------------------------------------------------------

    def _dynamic_filter(self, batch: Dict[str, np.ndarray]) -> Optional[np.ndarray]:
        """Drop groups whose rewards are all identical — zero advantage,
        zero gradient (reference: actor.py dynamic sampling)."""
        g = self.config.group_size
        r = batch["rewards"].astype(np.float32)
        B = r.shape[0]
        if g <= 1 or B % g != 0:
            return None
        groups = r.reshape(-1, g)
        keep_group = ~np.all(np.isclose(groups, groups[:, :1]), axis=1)
        keep = np.repeat(keep_group, g)
        if keep.all():
            return None
        if not keep.any():
            logger.warning("dynamic sampling rejected every group; keeping all")
            return None
        return np.nonzero(keep)[0]

    def ppo_update(self, batch: Dict[str, np.ndarray]) -> List[Dict[str, float]]:
        cfg = self.config
        if cfg.dynamic_sampling:
            keep = self._dynamic_filter(batch)
            if keep is not None:
                from areal_tpu.utils.data import select_rows

                batch = select_rows(batch, keep)

        # consumption evidence must be taken HERE, on the post-filter batch:
        # the LOSS_KEYS view below drops `versions`/`trace_keys`, so the
        # engine-level hook inside train_batch never sees them on this path
        if hasattr(self.engine, "_consume_telemetry"):
            batch = self.engine._consume_telemetry(batch)
        train_view = {k: batch[k] for k in self.LOSS_KEYS if k in batch}
        mbs = split_padded_tensor_dict_into_mb_list(
            train_view, n_mbs=cfg.ppo_n_minibatches
        )
        all_stats = []
        for mb in mbs.mbs:
            all_stats.append(self._train_one_mb(mb))
        return all_stats

    def flush_stats(self) -> None:
        """Materialise every deferred stats fetch (async_stats mode); call
        before reading the tracker/logging so commits are complete."""
        for st in self._pending_stats:
            st.materialize()
        self._pending_stats.clear()

    def warm_shapes(self, shapes) -> None:
        """Precompile the PPO step programs for packed-batch shape
        signatures, side-effect-free.

        RL rollout lengths vary step to step, so the packer's
        (rows, row_len) signature varies, and under jit each new signature
        is a fresh XLA compile that otherwise lands INSIDE the training
        loop (a torch-eager reference never sees this class of stall).
        The shape space is already log-bounded (pow-2 row buckets x the
        pack_length_quantum ladder, utils/data.py pack_into_rows); this
        walks it up front through the REAL packer + jit plumbing, so the
        compiled programs are exactly the ones the live loop will request.

        Compilation is AOT (`jit.lower(...).compile()` via the engine's
        precompile_* methods): nothing executes, nothing is donated, no
        state changes — warming is exactly free of side effects.

        shapes: iterable of (n_sequences, seq_len) pairs; each warms the
        signature the packer produces for n full rows of seq_len.
        n_sequences must respect the group-norm group size.
        """
        eng = self.engine
        rng = np.random.default_rng(0)
        # validate against the RESOLVED normalization groups (NormConfig
        # group_size defaults to 1 and is overridden by config.group_size
        # in __init__ — the raw config field is not what group_view asserts)
        g = 1
        for norm in (self.adv_norm, self.reward_norm):
            if norm is not None:
                g = max(g, norm.group_size)
        if not hasattr(self, "_loss_fn"):
            self._loss_fn = self._build_loss_fn()
        for n_seqs, seq_len in shapes:
            if n_seqs % g:
                raise ValueError(
                    f"warm shape n_sequences={n_seqs} must be divisible by "
                    f"the adv-norm group size {g}"
                )
            V = eng.model_config.vocab_size
            prompt = max(1, seq_len // 4)
            loss_mask = np.zeros((n_seqs, seq_len), np.float32)
            loss_mask[:, prompt:] = 1.0
            batch = {
                "input_ids": rng.integers(0, V, (n_seqs, seq_len)).astype(
                    np.int32),
                "attention_mask": np.ones((n_seqs, seq_len), bool),
                "loss_mask": loss_mask,
                "logprobs": rng.normal(-1.0, 0.1, (n_seqs, seq_len)).astype(
                    np.float32),
                "rewards": rng.integers(0, 2, n_seqs).astype(np.float32),
            }
            if self.config.recompute_logprob:
                eng.precompile_forward(batch,
                                       post_hook=self._get_logp_hook())
            # advantages run host/numpy-side (plus a tiny gae program):
            # executing them is cheap, touches no engine state, and yields
            # the exact key-set ppo_update's loss view needs
            batch["prox_logp"] = batch["logprobs"].copy()
            self.compute_advantages(batch)
            train_view = {k: batch[k] for k in self.LOSS_KEYS if k in batch}
            mbs = split_padded_tensor_dict_into_mb_list(
                train_view, n_mbs=self.config.ppo_n_minibatches
            )
            for mb in mbs.mbs:
                eng.precompile_train_batch(mb, self._loss_fn)

    def _build_loss_fn(self):
        """The cached grpo loss partial (built ONCE: the compiled step is
        keyed on the callable's identity)."""
        cfg = self.config
        return functools.partial(
            grpo_loss_fn,
            eps_clip=cfg.eps_clip,
            c_clip=cfg.c_clip,
            behav_imp_weight_cap=cfg.behav_imp_weight_cap,
            temperature=cfg.temperature,
            use_decoupled_loss=cfg.use_decoupled_loss,
            eps_clip_higher=cfg.eps_clip_higher,
            # plumbed fused-head chunk width (0/unset -> env default);
            # baked into the partial so the bench ladder's sweep value
            # reaches the compiled step, not just the config dataclass
            vocab_chunk=getattr(cfg, "lm_head_chunk", 0) or None,
        )

    def _train_one_mb(self, mb: Dict[str, np.ndarray]):
        """One train_batch + stat normalisation + tracker commit — shared
        with VLM/recipe actors so their stats cannot drift from the base.

        With `async_stats` the engine returns a PendingTrainStats; the
        normalisation/commit below runs when the stats materialise, so the
        next step's dispatch is never blocked on this one's scalars."""
        if not hasattr(self, "_loss_fn"):
            self._loss_fn = self._build_loss_fn()
        st = self.engine.train_batch(
            mb,
            self._loss_fn,
            loss_weight_fn=lambda b: float(np.sum(b["loss_mask"])),
        )
        if isinstance(st, stats.PendingTrainStats):
            st.then(self._finalize_mb_stats)
            # registered here (the one chokepoint) so flush_stats always
            # covers every pending fetch, whichever actor path dispatched it
            self._pending_stats.append(st)
            return st
        return self._finalize_mb_stats(st)

    def _finalize_mb_stats(self, st: Dict[str, float]) -> Dict[str, float]:
        n = max(st.pop("n_valid_tokens", 1.0), 1.0)
        for k in self.PER_TOKEN_STAT_KEYS:
            if k in st:
                st[k] = st[k] / n
        st["n_tokens"] = n
        with stats.DEFAULT_TRACKER.scope("ppo_actor"):
            stats.DEFAULT_TRACKER.scalar(**{
                k: v for k, v in st.items() if np.isscalar(v)
            })
        return st


class JaxPPOActor(JaxTrainEngine):
    """JaxTrainEngine + PPOActor algorithm surface, mirroring the
    reference's FSDPPPOActor (actor.py:278)."""

    def __init__(self, config: PPOActorConfig, model_config=None):
        super().__init__(config, model_config)
        self.actor = PPOActor(config, self)

    def compute_logp(self, batch: Dict[str, np.ndarray]) -> np.ndarray:
        return self.actor.compute_logp(batch)

    def compute_advantages(self, batch: Dict[str, np.ndarray]) -> None:
        self.actor.compute_advantages(batch)

    def ppo_update(self, batch: Dict[str, np.ndarray]) -> List[Dict[str, float]]:
        return self.actor.ppo_update(batch)

    def warm_shapes(self, shapes) -> None:
        self.actor.warm_shapes(shapes)

    def flush_stats(self) -> None:
        self.actor.flush_stats()
