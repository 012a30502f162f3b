"""Generalised Advantage Estimation over padded and packed sequences.

TPU-native counterpart of the reference's CUDA `cugae` kernel
(csrc/cugae/gae.cu:10-60 `gae_1d_nolp_misalign`) and lite's python GAE loop
(areal/engine/ppo/actor.py:136-151).  Instead of a hand-written backward CUDA
kernel, a single reverse `jax.lax.scan` runs the recurrence

    adv[t] = delta[t] + gamma * lam * (not boundary[t]) * adv[t+1]
    delta[t] = r[t] + gamma * V[t+1] * (not boundary[t]) - V[t]

across the whole (packed) buffer at once; sequence boundaries reset the
carry, which is exactly the cu_seqlens-misalignment handling of the CUDA
kernel, but shape-static and fusable by XLA.
"""
# areal-lint: hot-path

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def gae_padded(
    rewards: jax.Array,  # [B, L]
    values: jax.Array,  # [B, L]
    mask: jax.Array,  # [B, L] loss mask; holes allowed (multi-turn)
    gamma: float,
    lam: float,
) -> Tuple[jax.Array, jax.Array]:
    """GAE over [B, L] batches; bootstrap value after the last masked token
    is 0 (terminal).  Returns (advantages, returns) masked to 0 off-mask.

    Positions with mask 0 — trailing padding *and* interior holes such as
    multi-turn user tokens — are skipped exactly as the reference does
    (areal/engine/ppo/actor.py:146-151): the accumulated lastgaelam and the
    bootstrap value are frozen across them, so the recurrence connects each
    loss token directly to the next loss token with a single gamma*lam step.
    """

    def step(carry, xs):
        lastgaelam, nextvalues = carry
        r, v, m = xs
        delta = r + gamma * nextvalues - v
        newgaelam = delta + gamma * lam * lastgaelam
        lastgaelam = m * newgaelam + (1.0 - m) * lastgaelam
        nextvalues = m * v + (1.0 - m) * nextvalues
        return (lastgaelam, nextvalues), lastgaelam

    with jax.named_scope("advantages"):
        mask = mask.astype(jnp.float32)
        rewards = rewards.astype(jnp.float32) * mask
        values = values.astype(jnp.float32) * mask
        B = rewards.shape[0]
        # reverse scan over time, batched over B via transpose
        init = (jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.float32))
        _, adv_rev = jax.lax.scan(
            step, init, (rewards.T[::-1], values.T[::-1], mask.T[::-1])
        )
        adv = adv_rev[::-1].T * mask
        returns = adv + values
        return adv, returns * mask


def gae_segments(
    rewards: jax.Array,  # [T] packed
    values: jax.Array,  # [T]
    segment_ids: jax.Array,  # [T], -1 on filler
    gamma: float,
    lam: float,
    loss_mask: Optional[jax.Array] = None,  # [T]; holes allowed
) -> Tuple[jax.Array, jax.Array]:
    """GAE over a packed flat buffer; boundaries where segment id changes.

    Equivalent to cugae's `gae_1d_nolp_misalign` with per-sequence terminal
    bootstrap 0 (RLVR episodes end at the final token).  `loss_mask` holes
    inside a segment freeze the carry exactly as in `gae_padded`.
    """
    valid = segment_ids >= 0
    m = valid.astype(jnp.float32)
    if loss_mask is not None:
        m = m * loss_mask.astype(jnp.float32)
    rewards = rewards.astype(jnp.float32) * m
    values = values.astype(jnp.float32) * m
    # carry resets (to 0) at segment boundaries, scanning in reverse:
    # position t is a boundary start if segment_ids[t] != segment_ids[t+1]
    nxt_same = jnp.concatenate(
        [(segment_ids[1:] == segment_ids[:-1]) & valid[1:], jnp.zeros((1,), bool)]
    ).astype(jnp.float32)

    def step(carry, xs):
        lastgaelam, nextvalues = carry
        r, v, mm, same = xs
        lastgaelam = lastgaelam * same
        nextvalues = nextvalues * same
        delta = r + gamma * nextvalues - v
        newgaelam = delta + gamma * lam * lastgaelam
        lastgaelam = mm * newgaelam + (1.0 - mm) * lastgaelam
        nextvalues = mm * v + (1.0 - mm) * nextvalues
        return (lastgaelam, nextvalues), lastgaelam

    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    _, adv_rev = jax.lax.scan(
        step, init, (rewards[::-1], values[::-1], m[::-1], nxt_same[::-1])
    )
    adv = adv_rev[::-1] * m
    returns = adv + values
    return adv, returns * m


# ---------------------------------------------------------------------------
# Host-side numpy reference (used by tests and by host-side advantage calc)
# ---------------------------------------------------------------------------


def gae_numpy(
    rewards: np.ndarray, values: np.ndarray, lens: np.ndarray, gamma: float, lam: float
):
    """Straightforward per-sequence loop over a padded [B, L] batch."""
    B, L = rewards.shape
    adv = np.zeros_like(rewards, dtype=np.float64)
    for b in range(B):
        n = int(lens[b])
        carry = 0.0
        for t in reversed(range(n)):
            nxt = values[b, t + 1] if t + 1 < n else 0.0
            delta = rewards[b, t] + gamma * nxt - values[b, t]
            carry = delta + gamma * lam * carry
            adv[b, t] = carry
    ret = adv + np.where(
        np.arange(L)[None, :] < lens[:, None], values.astype(np.float64), 0.0
    )
    return adv, ret
