"""Loss functions and token-level numerics, jax-native.

Capability counterpart of the reference's `areal/utils/functional.py`
(gather_logprobs :28, ppo_actor_loss_fn :171 with the decoupled objective,
dual clip) and `realhf/impl/model/utils/ppo_functional.py` (actor/critic
losses, reward shaping).  All reductions are masked *sums* plus explicit
weights so callers can normalise globally across micro-batches and dp ranks
(the reference's loss_weight_fn protocol, fsdp_engine.py:499-606); under a
single jit over the mesh a `jnp.sum` is already a global sum, no psum needed.

Softmax/log-softmax run in fp32 regardless of activation dtype (MXU-friendly
bf16 matmuls, fp32 numerics).
"""
# areal-lint: hot-path

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def masked_mean(x: jax.Array, mask: Optional[jax.Array], eps: float = 1e-8) -> jax.Array:
    if mask is None:
        return jnp.mean(x)
    mask = mask.astype(x.dtype)
    return jnp.sum(x * mask) / (jnp.sum(mask) + eps)


def masked_normalize(
    x: jax.Array,
    mask: Optional[jax.Array],
    unbiased: bool = False,
    eps: float = 1e-5,
) -> jax.Array:
    """Whiten x over masked entries (reference: ppo_functional masked_normalize)."""
    if mask is None:
        mask = jnp.ones_like(x)
    mask = mask.astype(x.dtype)
    n = jnp.sum(mask)
    mean = jnp.sum(x * mask) / jnp.maximum(n, 1.0)
    var = jnp.sum(jnp.square(x - mean) * mask) / jnp.maximum(
        n - (1.0 if unbiased else 0.0), 1.0
    )
    return (x - mean) * jax.lax.rsqrt(var + eps) * mask


def gather_logprobs(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """log p(labels) from logits [..., V]; fp32 log-softmax.

    (reference: areal/utils/functional.py:28-47 gather_logprobs)
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return picked - logz


def gather_logprobs_entropy(
    logits: jax.Array, labels: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(logprobs, entropy) in one pass (reference: functional.py:85-116)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    logp_all = logits - logz[..., None]
    entropy = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return picked - logz, entropy


def _chunk_len(n: int, target: int) -> int:
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def _clamped_entropy(logits: jax.Array, entropy_clamp: float) -> jax.Array:
    """Entropy of the policy renormalised over the top (1-entropy_clamp)
    fraction of the vocabulary — the bottom tail is masked out before the
    softmax (reference: recipe/AEnt/functional.py clamped_softmax_entropy,
    which removes the k = V*clamp smallest logits).  Token-space clamping
    keeps the entropy bonus from rewarding mass on junk tokens."""
    V = logits.shape[-1]
    keep = max(1, V - int(V * entropy_clamp))
    kth = jax.lax.top_k(logits, keep)[0][..., -1:]
    mask = logits >= kth
    neg_inf = jnp.finfo(logits.dtype).min
    clamped = jnp.where(mask, logits, neg_inf)
    logz = jax.nn.logsumexp(clamped, axis=-1)
    p = jax.nn.softmax(clamped, axis=-1)
    return logz - jnp.sum(jnp.where(mask, p * logits, 0.0), axis=-1)


def lm_logprobs_entropy(
    out,  # LMOutput (deferred head) or materialised logits [..., V]
    labels: jax.Array,  # int [...]
    temperature: float = 1.0,
    chunk: int = 1024,
    with_entropy: bool = True,
    entropy_clamp: float = 0.0,
    entropy_grad: bool = True,
    impl: Optional[str] = None,  # fused | chunked; None -> "fused"
    vocab_chunk: Optional[int] = None,  # fused-head chunk width
    # (TrainEngineConfig.lm_head_chunk, passed through the loss partials)
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(logprobs, entropy, argmax-correct) of `labels`, fp32 numerics.

    With an `LMOutput` the default "fused" impl runs the vocab-chunked
    online-softmax head with a hand-written VJP (ops/fused_xent.py): never
    holds [tokens, V] fp32 logits, accumulates dx in a [tokens, D] carry,
    writes each dW vocab slice once, and (with entropy_grad=False — the
    GRPO stats-only case) skips the entropy backward term entirely.  This
    is the TPU-side counterpart of the reference's vocab-parallel
    cross-entropy memory discipline (realhf .../tensor_parallel/
    modules.py:1180 vocab_parallel_cross_entropy).  "chunked" keeps the
    legacy rematerialised token-chunk scan (also used for entropy_clamp,
    which needs a per-token top-k over the full vocab row).
    """
    from areal_tpu.models.transformer import LMOutput

    inv_t = float(1.0 / temperature)
    if not isinstance(out, LMOutput):
        logits = out.astype(jnp.float32) * inv_t
        logp, ent = gather_logprobs_entropy(logits, labels)
        if entropy_clamp > 0:
            ent = _clamped_entropy(logits, entropy_clamp)
        corr = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        return logp, ent, corr

    shape = labels.shape
    if (
        impl in (None, "fused")
        and entropy_clamp == 0
        and getattr(out, "logit_softcap", None) is None
    ):
        from areal_tpu.ops.fused_xent import fused_logprobs_entropy

        D = out.hidden.shape[-1]
        lp, ent, corr = fused_logprobs_entropy(
            out.hidden.reshape(-1, D),
            out.head,
            labels.reshape(-1),
            temperature=temperature,
            vocab_chunk=int(vocab_chunk or 8192),
            with_entropy=with_entropy,
            entropy_grad=entropy_grad,
        )
        return lp.reshape(shape), ent.reshape(shape), corr.reshape(shape)
    D = out.hidden.shape[-1]
    h = out.hidden.reshape(-1, D)
    lab = labels.reshape(-1)
    N = h.shape[0]
    c = _chunk_len(N, chunk)
    hs = h.reshape(N // c, c, D)
    ls = lab.reshape(N // c, c)
    head = out.head

    cap = getattr(out, "logit_softcap", None)

    @jax.checkpoint
    def one_chunk(carry, xs):
        hc, lc = xs
        logits = (hc @ head).astype(jnp.float32)
        if cap:
            # gemma2 final-logit tanh cap is part of the model's output
            # distribution, applied before temperature
            logits = jnp.tanh(logits / cap) * cap
        logits = logits * inv_t
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        if with_entropy:
            if entropy_clamp > 0:
                ent = _clamped_entropy(logits, entropy_clamp)
            else:
                p = jax.nn.softmax(logits, axis=-1)
                ent = logz - jnp.sum(p * logits, axis=-1)
            corr = (jnp.argmax(logits, axis=-1) == lc).astype(jnp.float32)
        else:
            ent = jnp.zeros_like(logz)
            corr = jnp.zeros_like(logz)
        return carry, (picked - logz, ent, corr)

    with jax.named_scope("xent"):
        _, (lp, ent, corr) = jax.lax.scan(one_chunk, (), (hs, ls))
    return lp.reshape(shape), ent.reshape(shape), corr.reshape(shape)


def kl_estimate(
    logp: jax.Array, ref_logp: jax.Array, kind: str = "k1", clip: float = 20.0
) -> jax.Array:
    """Schulman k1/k2/k3 estimators of KL(pi || ref) per token
    (reference: areal/utils/data.py KLEstimator :1306)."""
    diff = jnp.clip(logp - ref_logp, -clip, clip)
    if kind == "k1":
        return diff
    if kind == "k2":
        return 0.5 * jnp.square(diff)
    if kind == "k3":
        return jnp.exp(-diff) - 1.0 + diff
    raise ValueError(f"unknown KL estimator {kind}")


# ---------------------------------------------------------------------------
# PPO / GRPO
# ---------------------------------------------------------------------------


def ppo_actor_loss_fn(
    logprobs: jax.Array,
    old_logprobs: jax.Array,
    advantages: jax.Array,
    eps_clip: float,
    loss_mask: jax.Array,
    c_clip: Optional[float] = None,
    proximal_logprobs: Optional[jax.Array] = None,
    behav_imp_weight_cap: Optional[float] = None,
    eps_clip_higher: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Decoupled-PPO actor loss (reference: areal/utils/functional.py:171-235).

    With `proximal_logprobs` (the recomputed policy at train time), the ratio
    is taken against the *proximal* policy and the sample is reweighted by the
    capped behaviour importance weight exp(prox - old) — the decoupled PPO
    objective that makes staleness η≤4 trainable (blog/AReaL_v0_3.md ablation).
    Returns (sum-reduced masked loss, stats dict of masked sums).
    """
    denorm_logprobs = proximal_logprobs if proximal_logprobs is not None else old_logprobs
    loss_mask = loss_mask.astype(jnp.float32)
    ratio = jnp.exp(logprobs - denorm_logprobs)
    clipped_ratio = jnp.clip(
        ratio,
        1.0 - eps_clip,
        1.0 + (eps_clip_higher if eps_clip_higher is not None else eps_clip),
    )
    pg_loss1 = -advantages * ratio
    pg_loss2 = -advantages * clipped_ratio
    clip_mask = pg_loss1 < pg_loss2
    pg_loss = jnp.maximum(pg_loss1, pg_loss2)
    if c_clip is not None:
        # dual clip: bound the loss for very negative advantages
        pg_loss3 = jnp.sign(advantages) * c_clip * advantages
        # mask marks positions where the dual clip actually takes effect
        dual_clip_mask = (advantages < 0) & (pg_loss3 < pg_loss)
        pg_loss = jnp.where(advantages < 0, jnp.minimum(pg_loss, pg_loss3), pg_loss)
    else:
        dual_clip_mask = jnp.zeros_like(clip_mask)
    if proximal_logprobs is not None:
        behav_kl = denorm_logprobs - old_logprobs
        behav_imp_weight = jnp.exp(behav_kl)
        if behav_imp_weight_cap is not None:
            behav_mask = (behav_imp_weight <= behav_imp_weight_cap) & (loss_mask > 0)
        else:
            behav_mask = loss_mask > 0
        behav_imp_weight = jnp.where(behav_mask, behav_imp_weight, 0.0)
        pg_loss = pg_loss * behav_imp_weight
        stat_behav_kl = jnp.sum(behav_kl * behav_mask)
        stat_behav_w = jnp.sum(behav_imp_weight * behav_mask)
    else:
        stat_behav_kl = jnp.zeros(())
        stat_behav_w = jnp.zeros(())
    loss = jnp.sum(pg_loss * loss_mask)
    stats = {
        "importance_weight": jnp.sum(ratio * loss_mask),
        "approx_kl": jnp.sum((logprobs - denorm_logprobs) * loss_mask),
        "clip_ratio": jnp.sum(clip_mask * loss_mask),
        "dual_clip_ratio": jnp.sum(dual_clip_mask * loss_mask),
        "behave_kl": stat_behav_kl,
        "behave_imp_weight": stat_behav_w,
        "n_valid_tokens": jnp.sum(loss_mask),
    }
    return loss, stats


def grpo_loss_fn(
    model_out,  # LMOutput or [T, V] packed logits
    batch: Dict[str, jax.Array],
    eps_clip: float,
    c_clip: Optional[float] = None,
    behav_imp_weight_cap: Optional[float] = None,
    temperature: float = 1.0,
    use_decoupled_loss: bool = True,
    entropy_coef: float = 0.0,
    eps_clip_higher: Optional[float] = None,
    vocab_chunk: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Packed GRPO/PPO policy loss over next-token logits
    (reference: areal/engine/ppo/actor.py:313-391 grpo_loss_fn).

    batch keys (flat [T]): input_ids, loss_mask, logprobs (behaviour),
    advantages, and optionally prox_logp.
    """
    labels = jnp.roll(batch["input_ids"], -1, axis=-1)
    loss_mask = batch["loss_mask"].astype(jnp.float32)
    logprobs, entropy, _ = lm_logprobs_entropy(
        model_out, labels, temperature=temperature,
        # entropy is a logged stat unless an entropy bonus actually trains
        # on it — skipping its backward term saves an elementwise pass over
        # every recomputed logits block
        entropy_grad=bool(entropy_coef),
        vocab_chunk=vocab_chunk,
    )
    old_logp = batch["logprobs"]
    prox = batch.get("prox_logp") if use_decoupled_loss else None
    loss, stats = ppo_actor_loss_fn(
        logprobs=logprobs,
        old_logprobs=old_logp,
        advantages=batch["advantages"],
        eps_clip=eps_clip,
        loss_mask=loss_mask,
        c_clip=c_clip,
        proximal_logprobs=prox,
        behav_imp_weight_cap=behav_imp_weight_cap,
        eps_clip_higher=eps_clip_higher,
    )
    if entropy_coef:
        loss = loss - entropy_coef * jnp.sum(entropy * loss_mask)
    aux = getattr(model_out, "aux_loss", None)
    if aux is not None:
        # MoE load-balance penalty, weighted per valid token so the global
        # loss normalisation leaves it as an average across micro-batches
        loss = loss + aux * jnp.sum(loss_mask)
        stats["moe_aux_loss"] = aux * jnp.sum(loss_mask)
    stats["entropy"] = jnp.sum(entropy * loss_mask)
    stats["new_logp"] = jnp.sum(logprobs * loss_mask)
    stats["old_logp"] = jnp.sum(old_logp * loss_mask)
    return loss, stats


def ppo_critic_loss_fn(
    values: jax.Array,
    old_values: jax.Array,
    returns: jax.Array,
    loss_mask: jax.Array,
    eps_clip_value: Optional[float] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Clipped value loss (reference: realhf .../ppo_functional.py critic_loss_fn)."""
    loss_mask = loss_mask.astype(jnp.float32)
    err = jnp.square(values - returns)
    if eps_clip_value is not None:
        clipped = old_values + jnp.clip(values - old_values, -eps_clip_value, eps_clip_value)
        err_clipped = jnp.square(clipped - returns)
        clip_mask = err_clipped > err
        err = jnp.maximum(err, err_clipped)
    else:
        clip_mask = jnp.zeros_like(err, dtype=bool)
    loss = 0.5 * jnp.sum(err * loss_mask)
    return loss, {
        "value_clip_ratio": jnp.sum(clip_mask * loss_mask),
        "n_valid_tokens": jnp.sum(loss_mask),
    }


# ---------------------------------------------------------------------------
# SFT / RW / DPO
# ---------------------------------------------------------------------------


def sft_loss_fn(
    model_out, batch: Dict[str, jax.Array],
    vocab_chunk: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Token cross-entropy over next-token targets, masked sum
    (reference: areal/engine/sft/lm_engine.py)."""
    labels = jnp.roll(batch["input_ids"], -1, axis=-1)
    mask = batch["loss_mask"].astype(jnp.float32)
    logprobs, _, correct = lm_logprobs_entropy(
        model_out, labels, entropy_grad=False, vocab_chunk=vocab_chunk
    )
    loss = -jnp.sum(logprobs * mask)
    aux = getattr(model_out, "aux_loss", None)
    if aux is not None:
        loss = loss + aux * jnp.sum(mask)
    return loss, {
        "loss_sum": loss,
        "n_valid_tokens": jnp.sum(mask),
        "correct_tokens": jnp.sum(correct * mask),
    }


def pairwise_reward_loss_fn(
    chosen_scores: jax.Array,
    rejected_scores: jax.Array,
    pair_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Bradley-Terry pairwise loss (reference: areal/engine/rw/rw_engine.py).
    `pair_mask` excludes filler pairs (dp-padding rows)."""
    margin = chosen_scores - rejected_scores
    if pair_mask is None:
        pair_mask = jnp.ones_like(margin)
    pair_mask = pair_mask.astype(jnp.float32)
    loss = -jnp.sum(jax.nn.log_sigmoid(margin) * pair_mask)
    return loss, {
        "acc": jnp.sum((margin > 0) * pair_mask),
        "margin": jnp.sum(margin * pair_mask),
        "n_pairs": jnp.sum(pair_mask),
    }


def dpo_loss_fn(
    policy_chosen_logp: jax.Array,
    policy_rejected_logp: jax.Array,
    ref_chosen_logp: jax.Array,
    ref_rejected_logp: jax.Array,
    beta: float = 0.1,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Direct preference optimization loss over sequence logprobs."""
    pi_ratio = policy_chosen_logp - policy_rejected_logp
    ref_ratio = ref_chosen_logp - ref_rejected_logp
    h = beta * (pi_ratio - ref_ratio)
    loss = -jnp.sum(jax.nn.log_sigmoid(h))
    return loss, {"acc": jnp.sum(h > 0), "n_pairs": jnp.asarray(h.size, jnp.float32)}
