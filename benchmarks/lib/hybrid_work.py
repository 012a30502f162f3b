"""Bytes the decode passes of a `nemotron_h` stack (Mamba-2, attention and
latent mixture-of-experts blocks) have to move at least, from the
configuration's keys and the program's own counters.  A decode pass is one
forward of every slot by one token:

- every Mamba block's weights are read once, and every slot's recurrent
  state and convolution window are read once and written once (the new
  state is a function of all of the old one);
- every expert block's router, latent projections and shared expert are
  read once; a HELD routed expert's two matrices are read once for each
  pass in which the program's `experts_touched` counted it (an expert no
  token was routed to is not read);
- every attention block's weights, the final norm and the head are read
  once.

The embedding table is only gathered from (one row a slot); activations
and the attention blocks' K/V columns (which grow with the sequences) are
left out: a share of a roofline built on these errs low and cannot pass
100%.  Each `*_bytes(hf, work, counters)` is the total over a window:
`counters` are the engine's deltas (`decode_passes`, `experts_touched`),
`work["n_slots"]` the slots of the grid."""

import numpy as np


def _item(hf):
    return 2 if hf["bench"]["dtype"] == "bfloat16" else np.dtype(
        hf["bench"]["dtype"]).itemsize


def _count(hf, kind):
    return hf["hybrid_override_pattern"].count(kind)


def mamba_dims(hf):
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    G, N = hf["n_groups"], hf["ssm_state_size"]
    d_in = H * P
    return H, P, G, N, d_in, d_in + 2 * G * N


def mamba_block_params(hf):
    """in_proj (z | xBC | dt), conv taps and bias, dt_bias, A_log, D, the
    gated norm, out_proj, the block's pre-norm."""
    D, K = hf["hidden_size"], hf["conv_kernel"]
    H, _, _, _, d_in, cd = mamba_dims(hf)
    return (D * (d_in + cd + H) + K * cd + cd + 3 * H + d_in + d_in * D + D)


def attention_block_params(hf):
    D, hd = hf["hidden_size"], hf["head_dim"]
    q, kv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    return D * q + 2 * D * kv + q * D + D


def moe_fixed_params(hf):
    """What an expert block reads whatever the routing: router and its
    selection bias (over ALL routed experts), the two latent projections,
    the shared expert, the pre-norm."""
    D, Lt = hf["hidden_size"], hf["moe_latent_size"]
    E = (hf.get("experts_held") or {}).get("of", hf["n_routed_experts"])
    return (D * E + E + 2 * D * Lt
            + 2 * D * hf["moe_shared_expert_intermediate_size"] + D)


def expert_params(hf):
    """One routed expert: up and down in the latent space, no gate."""
    return 2 * hf["moe_latent_size"] * hf["moe_intermediate_size"]


def state_bytes_per_slot(hf):
    """One sequence's recurrent state (`bench.state_dtype`) and convolution
    window (`bench.dtype`) over all Mamba blocks."""
    H, P, _, N, _, cd = mamba_dims(hf)
    s_item = np.dtype(hf["bench"]["state_dtype"]).itemsize
    return _count(hf, "M") * (
        H * P * N * s_item + (hf["conv_kernel"] - 1) * cd * _item(hf))


def kv_bytes_per_token(hf):
    return (_count(hf, "*") * 2 * hf["num_key_value_heads"] * hf["head_dim"]
            * _item(hf))


def _passes(counters):
    return int((counters or {}).get("decode_passes", 0))


def ssm_bytes(hf, work, counters):
    """The Mamba blocks: weights once a pass, every slot's state and window
    read once and written once."""
    per_pass = (_count(hf, "M") * mamba_block_params(hf) * _item(hf)
                + 2 * int(work["n_slots"]) * state_bytes_per_slot(hf))
    return _passes(counters) * per_pass


def moe_bytes(hf, work, counters):
    """The expert blocks: the fixed part once a pass, a held expert's two
    matrices once for each pass that touched it."""
    fixed = _count(hf, "E") * moe_fixed_params(hf) * _item(hf)
    touched = int((counters or {}).get("experts_touched", 0))
    return _passes(counters) * fixed + touched * expert_params(hf) * _item(hf)


def decode_bytes(hf, work, counters):
    """Everything above, the attention blocks' weights, the final norm and
    the head."""
    rest = (_count(hf, "*") * attention_block_params(hf)
            + hf["hidden_size"] * (hf["vocab_size"] + 1)) * _item(hf)
    return (ssm_bytes(hf, work, counters) + moe_bytes(hf, work, counters)
            + _passes(counters) * rest)
