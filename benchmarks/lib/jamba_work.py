"""Bytes the decode passes of a `jamba` stack (a Mamba-1 or attention mixer
and then a dense gated FFN a layer; `num_experts` 1) have to move at least,
from the configuration's keys and the program's own counters.  A decode
pass is one forward of a block of slots by one token:

- every Mamba layer's mixer weights are read once, and the recurrent state
  and convolution window of every row the pass STEPS (the engine's
  `state_rows_stepped`: the LIVE rows where the state kernel
  `ops/mamba1_decode.py` serves the dispatch, since PR 53; the whole block
  on the plain path, which steps it where it lies) are read once and written
  once: the new state is a function of all of the old one;
- every other weight is read once: the attention layers' mixers, every
  layer's FFN and two norms, the final norm and the tied head.

The embedding is the head, read once as the head; activations and the
attention layers' K/V columns (which grow with the sequences) are left
out: a share of a roofline built on these errs low and cannot pass 100%.
Each `*_bytes(hf, work, counters)` is the total over a window: `counters`
are the engine's deltas (`decode_passes`, `state_rows_stepped`)."""

import numpy as np


def _item(hf):
    return 2 if hf["bench"]["dtype"] == "bfloat16" else np.dtype(
        hf["bench"]["dtype"]).itemsize


def dims(hf):
    D = hf["hidden_size"]
    return {"D": D, "d_in": hf["mamba_expand"] * D, "N": hf["mamba_d_state"],
            "R": hf["mamba_dt_rank"], "K": hf["mamba_d_conv"],
            "F": hf["intermediate_size"], "V": hf["vocab_size"],
            "q": D, "kv": hf["num_key_value_heads"] * (
                D // hf["num_attention_heads"])}


def n_layers(hf):
    """(Mamba layers, attention layers) by the family's rule."""
    L = hf["num_hidden_layers"]
    n_attn = sum(1 for l in range(L)
                 if l % hf["attn_layer_period"] == hf["attn_layer_offset"])
    return L - n_attn, n_attn


def mamba_mixer_params(hf):
    """in_proj (u | z), conv taps and bias, x_proj (r | B | C), dt_proj and
    its bias, A_log, D, out_proj, the three inner norms."""
    d = dims(hf)
    D, d_in, N, R, K = d["D"], d["d_in"], d["N"], d["R"], d["K"]
    return (D * 2 * d_in + K * d_in + d_in + d_in * (R + 2 * N)
            + R * d_in + d_in + d_in * N + d_in + d_in * D + R + 2 * N)


def attention_mixer_params(hf):
    d = dims(hf)
    return 2 * d["D"] * d["q"] + 2 * d["D"] * d["kv"]


def ffn_and_norms_params(hf):
    """A layer's dense gated FFN and its two pre-norms."""
    d = dims(hf)
    return 3 * d["D"] * d["F"] + 2 * d["D"]


def parameters(hf):
    """The whole model (the head is the embedding)."""
    d = dims(hf)
    n_m, n_a = n_layers(hf)
    return (n_m * mamba_mixer_params(hf) + n_a * attention_mixer_params(hf)
            + (n_m + n_a) * ffn_and_norms_params(hf)
            + d["V"] * d["D"] + d["D"])


def state_bytes_per_slot(hf):
    """One sequence's recurrent state (`bench.state_dtype`) and convolution
    window (`bench.dtype`) over all Mamba layers."""
    d = dims(hf)
    s_item = np.dtype(hf["bench"]["state_dtype"]).itemsize
    return n_layers(hf)[0] * (
        d["d_in"] * d["N"] * s_item + (d["K"] - 1) * d["d_in"] * _item(hf))


def kv_bytes_per_token(hf):
    return n_layers(hf)[1] * 2 * dims(hf)["kv"] * _item(hf)


def _passes(counters):
    return int((counters or {}).get("decode_passes", 0))


def ssm_bytes(hf, work, counters):
    """The Mamba mixers: weights once a pass, state and window of every row
    stepped read once and written once."""
    rows = int((counters or {}).get("state_rows_stepped", 0))
    return (_passes(counters) * n_layers(hf)[0] * mamba_mixer_params(hf)
            * _item(hf) + 2 * rows * state_bytes_per_slot(hf))


def decode_bytes(hf, work, counters):
    """That, and every other weight once a pass."""
    rest = parameters(hf) - n_layers(hf)[0] * mamba_mixer_params(hf)
    return ssm_bytes(hf, work, counters) + _passes(counters) * rest * _item(hf)
