"""Operations and bytes an algorithm needs, from shapes alone.  Recomputed
work (remat, the flash backward's second QK^T) is never counted, so a share
of the roofline built on these cannot be flattered by doing work twice."""


def causal_attention_flops(seg_lens, num_heads, head_dim, backward=True):
    """Causal self-attention over independent segments of `seg_lens` tokens.

    Forward: QK^T and PV, each 2*s*s*head_dim per head over the full square,
    half of it under the causal mask -> 2 * H * hd * s^2.  Backward needs
    four such products (dV, dP, dQ, dK) -> 4 * H * hd * s^2.  Together
    6 * H * hd * sum(s_i^2).
    """
    sq = sum(int(s) * int(s) for s in seg_lens)
    per = 6 if backward else 2
    return per * num_heads * head_dim * sq


def dense_param_count(hf):
    """Parameters of a dense Qwen-class decoder from its config.json keys
    (tied head counted once)."""
    D, F, L, V = (hf["hidden_size"], hf["intermediate_size"],
                  hf["num_hidden_layers"], hf["vocab_size"])
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or D // H
    attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    if hf.get("attention_bias", hf.get("model_type") == "qwen2"):
        attn += H * hd + 2 * Hkv * hd  # q, k, v biases
    if hf.get("model_type") in ("qwen3", "qwen3_moe"):
        attn += 2 * hd  # per-head q and k RMS norm weights
    mlp = 3 * D * F
    n = L * (attn + mlp + 2 * D) + D + V * D
    if not hf.get("tie_word_embeddings", False):
        n += V * D
    return n


def kv_bytes_per_token(hf, bytes_per_el=2):
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // H
    return 2 * hf["num_hidden_layers"] * Hkv * hd * bytes_per_el
