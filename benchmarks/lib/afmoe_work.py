"""Operations the train step of an `afmoe` stack needs, from shapes and from
the program's own counters.  Recomputed work (remat, the flash backward's
second QK^T) is never counted, so a share of a roofline built on these
cannot be flattered by doing work twice.

Attention is counted BY MASK: a full layer attends, within a sequence of s
tokens, the s (s + 1) / 2 pairs (query i, key j <= i); a sliding layer only
those with i - window < j (`pairs`: the same rule as `ops/attention.py
make_attention_mask` / `_mask_for` and the reference's `sees`;
`tests/test_afmoe_model.py` holds the four to each other at the window's
edge).  A causal count for every layer would read the stack's seven sliding
layers 2.5 times too high at 16k.
"""


def pairs(s, window=None):
    """(query, key) pairs one causal layer attends within a sequence of `s`
    tokens, the diagonal included; with `window`, keys j > i - window only."""
    s = int(s)
    if window is None or s <= window:
        return s * (s + 1) // 2
    w = int(window)
    return w * s - w * (w - 1) // 2


def attention_flops(seg_lens, hf, backward=True):
    """Forward QK^T and PV, 2 * head_dim operations a pair a head each;
    backward four such products (dV, dP, dQ, dK): 12 * H * hd * pairs over
    the layers of `hf["layer_types"]`, each by its own mask."""
    H, hd = int(hf["num_attention_heads"]), int(hf["head_dim"])
    per = 12 if backward else 4
    total = 0
    for kind in hf["layer_types"][: int(hf["num_hidden_layers"])]:
        window = hf["sliding_window"] if kind == "sliding_attention" else None
        total += sum(pairs(s, window) for s in seg_lens)
    return per * H * hd * total


def expert_flops(rows, hf, backward=True):
    """The three grouped products of gated experts over `rows` (token,
    expert) assignments that reached a held expert (the program's counter
    `expert_assignments_held`, summed over layers and steps): gate, up and
    down are 2 * hidden * moe_intermediate operations a row each, and the
    backward pass twice that again: 18 * D * F * rows."""
    per = 18 if backward else 6
    return per * int(hf["hidden_size"]) * int(hf["moe_intermediate_size"]) * int(rows)
