"""Bytes a decode pass of a power-retention decoder has to move at least,
from the configuration's keys alone.  A pass is one forward of every slot of
the engine's block by one token: each layer's weights and the head are read
once, and every slot's state is read once and written once (the new state
is a function of all of the old one).  The embedding table is only gathered
from (one row a slot), and activations are left out: a share of a roofline
built on these errs low and cannot pass 100%."""

import numpy as np


def feature_dim(head_dim, degree=2):
    """Symmetric features of degree 2 of a head: d (d + 1) / 2."""
    if degree != 2:
        raise ValueError(f"degree {degree}: only 2 is counted here")
    return head_dim * (head_dim + 1) // 2


def _shape(hf):
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // H
    return H, Hkv, hd


def _degree(hf):
    a = hf.get("bench", {}).get("assumed", {})
    return int(a.get("retention_degree", {}).get("value", 2))


def state_bytes_per_slot(hf):
    """One sequence's state over all layers: per kv head S [F, d] and the
    normaliser z [F], in `bench.state_dtype`."""
    _, Hkv, hd = _shape(hf)
    F = feature_dim(hd, _degree(hf))
    item = np.dtype(hf["bench"]["state_dtype"]).itemsize
    return hf["num_hidden_layers"] * Hkv * F * (hd + 1) * item


def layer_param_count(hf):
    """One block: q, k, v, o, the gate (hidden -> kv heads), the per-head
    q/k norms, the SwiGLU MLP, the two norms."""
    D, Fm = hf["hidden_size"], hf["intermediate_size"]
    H, Hkv, hd = _shape(hf)
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D + D * Hkv + 2 * hd
    return attn + 3 * D * Fm + 2 * D


def weight_bytes_per_pass(hf):
    """Layer weights and the head (a tied head is the embedding, read whole),
    not an untied embedding table, in `bench.dtype`."""
    item = 2 if hf["bench"]["dtype"] == "bfloat16" else np.dtype(
        hf["bench"]["dtype"]).itemsize
    n = (hf["num_hidden_layers"] * layer_param_count(hf)
         + hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"])
    return n * item


def retention_state_bytes(hf, n_slots):
    """Every slot's state read once and written once."""
    return 2 * int(n_slots) * state_bytes_per_slot(hf)


def decode_pass_bytes(hf, n_slots):
    return weight_bytes_per_pass(hf) + retention_state_bytes(hf, n_slots)
