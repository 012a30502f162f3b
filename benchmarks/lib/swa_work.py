"""Bytes the decode passes of a `mimo_v2` stack (full and sliding attention
layers with their own kv heads, a dense FFN in the leading layer, gated
experts at a share in the others) have to move at least, from the
configuration's keys and the program's own counters.  A decode pass is one
forward of every live slot by one token:

- every layer's weights outside its routed experts are read once:
  attention (and a sliding layer's sinks), the dense FFN, two norms, the
  router and its selection bias; a HELD routed expert's three matrices are
  read once for each pass in which the program's `experts_touched` counted
  it (an expert no live slot was routed to is not read);
- the final norm and the head slice are read once;
- a full layer's attention reads one key and one value column for every
  position a live slot attends (`kv_columns_read`: summed over slots and
  passes by the program, for ONE full layer) and writes one a live slot;
- a sliding layer's attention reads a live slot's ring of `sliding_window`
  positions whole and writes one entry.  The live slots of the window's
  passes are at least its `tokens_delivered` (a slot that stopped inside a
  chunk is stepped and delivers nothing): a floor of a floor.

The embedding table is only gathered from (one row a slot) and activations
are left out: a share of a roofline built on these errs low and cannot
pass 100%.  Each `*_bytes(hf, work, counters)` is the total over a window:
`counters` are the engine's deltas.  `parameters_held`, `pool_bytes` and
the per-block counts are what the configuration's `bench.bytes` states."""

import numpy as np


def _item(hf, key="dtype"):
    name = hf["bench"][key]
    return 2 if name == "bfloat16" else np.dtype(name).itemsize


def kinds(hf):
    """(full layers, sliding layers, dense layers, expert layers)."""
    sliding = sum(hf["hybrid_layer_pattern"])
    moe = sum(hf["moe_layer_freq"])
    L = hf["num_hidden_layers"]
    return L - sliding, sliding, L - moe, moe


def attention_params(hf, sliding):
    """q, k, v, o of one layer (and a sliding layer's sinks)."""
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    dq, dv = hf["head_dim"], hf["v_head_dim"]
    hkv = hf["swa_num_key_value_heads" if sliding else "num_key_value_heads"]
    sink = hf["add_swa_attention_sink_bias" if sliding
              else "add_full_attention_sink_bias"]
    return D * H * dq + D * hkv * (dq + dv) + H * dv * D + (H if sink else 0)


def dense_ffn_params(hf):
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def router_params(hf):
    """The router over ALL its outputs and its selection bias."""
    routed = (hf.get("experts_held") or {}).get("of", hf["n_routed_experts"])
    return hf["hidden_size"] * routed + routed


def expert_params(hf):
    """One routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def fixed_params(hf):
    """Every layer outside its routed experts."""
    n_full, n_sliding, n_dense, n_moe = kinds(hf)
    return (n_full * attention_params(hf, False)
            + n_sliding * attention_params(hf, True)
            + n_dense * dense_ffn_params(hf) + n_moe * router_params(hf)
            + hf["num_hidden_layers"] * 2 * hf["hidden_size"])


def parameters_held(hf):
    """What `bench.bytes.parameters_held` states, from the keys."""
    return (fixed_params(hf)
            + kinds(hf)[3] * hf["n_routed_experts"] * expert_params(hf)
            + 2 * hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"])


def full_values_per_position(hf):
    """One full layer's key and value columns of one position."""
    return hf["num_key_value_heads"] * (hf["head_dim"] + hf["v_head_dim"])


def ring_positions(hf):
    """Positions a sliding layer keeps a slot: the window, rounded up to
    the eight rows the chip tiles by (`TransformerConfig.window_ring`)."""
    return -(-hf["sliding_window"] // 8) * 8


def ring_values_per_slot(hf):
    """One sliding layer's ring of one slot."""
    return (ring_positions(hf) * hf["swa_num_key_value_heads"]
            * (hf["head_dim"] + hf["v_head_dim"]))


def pool_bytes(hf, rows, max_seq_len, itemsize=None):
    """The engine's pool: `rows` slots (the spare one among them), columns
    by position for the full layers, a ring for the sliding ones."""
    n_full, n_sliding, _, _ = kinds(hf)
    item = _item(hf, "cache_dtype") if itemsize is None else itemsize
    return rows * item * (
        max_seq_len * n_full * full_values_per_position(hf)
        + n_sliding * ring_values_per_slot(hf))


def _count(counters, key):
    return int((counters or {}).get(key, 0))


def attn_global_bytes(hf, work, counters):
    """The columns the full layers' attention read: one key and one value
    column a position attended, slot, pass and full layer."""
    return (_count(counters, "kv_columns_read") * kinds(hf)[0]
            * full_values_per_position(hf) * _item(hf, "cache_dtype"))


def attn_local_bytes(hf, work, counters):
    """The rings the sliding layers' attention read: one a live slot, pass
    and sliding layer."""
    return (_count(counters, "tokens_delivered") * kinds(hf)[1]
            * ring_values_per_slot(hf) * _item(hf, "cache_dtype"))


def moe_bytes(hf, work, counters):
    """The touched experts' matrices, and every pass the routers."""
    return (_count(counters, "experts_touched") * expert_params(hf)
            + _count(counters, "decode_passes") * kinds(hf)[3]
            * router_params(hf)) * _item(hf)


def decode_bytes(hf, work, counters):
    """Everything above: the fixed weights and the head a pass, a touched
    expert's matrices, the columns and rings read, and one column or ring
    entry written a live slot and layer."""
    n_full, n_sliding, _, _ = kinds(hf)
    per_pass = (fixed_params(hf)
                + hf["hidden_size"] * (hf["vocab_size"] + 1)) * _item(hf)
    written = _count(counters, "tokens_delivered") * (
        n_full * full_values_per_position(hf)
        + n_sliding * ring_values_per_slot(hf) // ring_positions(hf)
    ) * _item(hf, "cache_dtype")
    return (_count(counters, "decode_passes") * per_pass
            + _count(counters, "experts_touched") * expert_params(hf) * _item(hf)
            + attn_global_bytes(hf, work, counters)
            + attn_local_bytes(hf, work, counters) + written)
