"""Bytes the decode passes of a `longcat_flash` stack (latent attention in
double layers around a shortcut expert layer) have to move at least, from
the configuration's keys and the program's own counters.  A decode pass is
one forward of every live slot by one token:

- every layer's weights outside its routed experts are read once: two
  latent-attention sublayers, two dense FFNs, four norms, the router and its
  selection bias; a HELD routed expert's three matrices are read once for
  each pass in which the program's `experts_touched` counted it (an expert
  no token was routed to is not read; an identity expert has no weights);
- the final norm and the head slice are read once;
- attention reads, in every sublayer, one latent row for every position a
  live slot attends (`latent_rows_read`: summed over slots, passes and
  sublayers by the program) and writes one row a live slot and sublayer.

The embedding table is only gathered from (one row a slot) and activations
are left out: a share of a roofline built on these errs low and cannot
pass 100%.  Each `*_bytes(hf, work, counters)` is the total over a window:
`counters` are the engine's deltas."""

import numpy as np


def _item(hf, key="dtype"):
    name = hf["bench"][key]
    return 2 if name == "bfloat16" else np.dtype(name).itemsize


def attention_sublayer_params(hf):
    """q_a, its norm, q_b, kv_a (latent + shared rotary key), its norm,
    kv_b (key and value halves), o."""
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    rq, rkv = hf["q_lora_rank"], hf["kv_lora_rank"]
    nope, rope, vd = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"])
    return (D * rq + rq + rq * H * (nope + rope) + D * (rkv + rope) + rkv
            + rkv * H * (nope + vd) + H * vd * D)


def dense_ffn_params(hf):
    return 3 * hf["hidden_size"] * hf["ffn_hidden_size"]


def router_params(hf):
    """The router over ALL its outputs (routed experts everywhere and the
    identity experts) and its selection bias."""
    routed = (hf.get("experts_held") or {}).get("of", hf["n_routed_experts"])
    outputs = routed + hf["zero_expert_num"]
    return hf["hidden_size"] * outputs + outputs


def expert_params(hf):
    """One routed expert: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["expert_ffn_hidden_size"]


def layer_fixed_params(hf):
    """A double layer outside its routed experts."""
    return (2 * attention_sublayer_params(hf) + 2 * dense_ffn_params(hf)
            + 4 * hf["hidden_size"] + router_params(hf))


def parameters_held(hf):
    """What `bench.bytes.parameters_held` states, from the keys."""
    return (hf["num_layers"] * (layer_fixed_params(hf)
                                + hf["n_routed_experts"] * expert_params(hf))
            + 2 * hf["vocab_size"] * hf["hidden_size"] + hf["hidden_size"])


def sublayers(hf):
    return 2 * hf["num_layers"]


def row_values(hf):
    """One latent row: the normed latent and the shared rotary key."""
    return hf["kv_lora_rank"] + hf["qk_rope_head_dim"]


def cache_values_per_token(hf):
    return sublayers(hf) * row_values(hf)


def cache_bytes_per_token(hf):
    return cache_values_per_token(hf) * _item(hf, "cache_dtype")


def _count(counters, key):
    return int((counters or {}).get(key, 0))


def mla_attn_bytes(hf, work, counters):
    """The latent rows attention read: one row a position attended, slot,
    pass and sublayer (`latent_rows_read` counts exactly those)."""
    return (_count(counters, "latent_rows_read") * row_values(hf)
            * _item(hf, "cache_dtype"))


def rows_written(hf, counters):
    """Rows the decode passes wrote: one a live slot, pass and sublayer.
    The live slots of a pass are its `expert_assignments` over `moe_topk`
    choices in each of `num_layers` expert layers."""
    slot_passes = _count(counters, "expert_assignments") // (
        hf["moe_topk"] * hf["num_layers"])
    return slot_passes * sublayers(hf)


def decode_bytes(hf, work, counters):
    """Everything above: the fixed weights and the head a pass, a touched
    expert's matrices, the rows read and the rows written."""
    per_pass = (hf["num_layers"] * layer_fixed_params(hf)
                + hf["hidden_size"] * (hf["vocab_size"] + 1)) * _item(hf)
    return (_count(counters, "decode_passes") * per_pass
            + _count(counters, "experts_touched") * expert_params(hf) * _item(hf)
            + mla_attn_bytes(hf, work, counters)
            + rows_written(hf, counters) * row_values(hf)
            * _item(hf, "cache_dtype"))
