"""Offline test fixtures: tiny tokenizer + tiny HF checkpoint dir.

The reference builds fixtures the same way (realhf/tests/fixtures.py trains
a fresh WordPiece tokenizer and saves a cpu-sized model) because CI has no
network access.
"""

import json
import os
import re


CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "{{ message['role'] }}: {{ message['content'] }}\n"
    "{% endfor %}"
    "{% if add_generation_prompt %}assistant: {% endif %}"
)


def make_tiny_tokenizer(out_dir: str, vocab_size: int = 256):
    """Train a tiny byte-level BPE tokenizer on synthetic text and save it as
    a transformers PreTrainedTokenizerFast with a simple chat template."""
    from tokenizers import Tokenizer, models, pre_tokenizers, trainers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<|endoftext|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    corpus = [
        "What is 1 + 1? The answer is 2.",
        "Compute 3 * 4. #### 12",
        "Please reason step by step, and put your final answer within \\boxed{}.",
        "user assistant system: numbers 0 1 2 3 4 5 6 7 8 9 10 11 12 13",
    ] * 50
    tok.train_from_iterator(corpus, trainer)
    fast = PreTrainedTokenizerFast(
        tokenizer_object=tok,
        eos_token="<|endoftext|>",
        pad_token="<|endoftext|>",
    )
    fast.chat_template = CHAT_TEMPLATE
    os.makedirs(out_dir, exist_ok=True)
    fast.save_pretrained(out_dir)
    return fast


def make_tiny_ckpt(out_dir: str, vocab_size: int = 384, seed: int = 0):
    """Tiny Qwen2-style checkpoint dir (weights + config + tokenizer) that
    both the train engine and the generation server can load."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.hf import save_hf_checkpoint
    from areal_tpu.models.model_config import tiny_config

    tokenizer = make_tiny_tokenizer(out_dir, vocab_size=256)
    cfg = tiny_config(
        vocab_size=vocab_size,
        qkv_bias=True,
        hf_architecture="Qwen2ForCausalLM",
        eos_token_id=tokenizer.eos_token_id,
    )
    params = init_params(cfg, jax.random.PRNGKey(seed))
    save_hf_checkpoint(params, cfg, out_dir, save_dtype="float32")
    return cfg


def make_gsm8k_jsonl(path: str, n: int = 32):
    rows = [
        {
            "question": f"What is {i} + {i + 1}?",
            "answer": f"Adding gives {2 * i + 1}.\n#### {2 * i + 1}",
        }
        for i in range(n)
    ]
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


def make_tiny_vlm_ckpt(out_dir: str, vocab_size: int = 384, seed: int = 0):
    """Tiny Qwen2-VL-style checkpoint (text + vision tower + tokenizer)
    loadable by TransformerConfig.from_hf + the train/serving engines."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.hf import save_hf_checkpoint
    from areal_tpu.models.model_config import VisionConfig, tiny_config
    from areal_tpu.models.vision import init_vision_params

    tokenizer = make_tiny_tokenizer(out_dir, vocab_size=256)
    image_token_id = 251  # inside the tokenizer vocab, unused by text
    vcfg = VisionConfig(
        patch_size=2,
        temporal_patch_size=1,
        in_channels=3,
        hidden_size=16,
        intermediate_size=32,
        num_layers=1,
        num_heads=2,
        spatial_merge_size=2,
        out_hidden_size=64,
    )
    cfg = tiny_config(
        vocab_size=vocab_size,
        qkv_bias=True,
        hf_architecture="Qwen2VLForConditionalGeneration",
        eos_token_id=tokenizer.eos_token_id,
    ).replace(vision=vcfg, image_token_id=image_token_id,
              mrope_section=(2, 3, 3))
    params = init_params(cfg, jax.random.PRNGKey(seed))
    params["vision"] = init_vision_params(vcfg, jax.random.PRNGKey(seed + 1))
    save_hf_checkpoint(params, cfg, out_dir, save_dtype="float32")
    return cfg


def make_clevr_jsonl(path: str, cfg, n: int = 8, rng_seed: int = 0):
    """Pre-patchified CLEVR-count manifest rows: input_ids with placeholder
    runs, inline pixel patches, and the integer answer."""
    import json

    import numpy as np

    vcfg = cfg.vision
    rng = np.random.default_rng(rng_seed)
    n_placeholder = 4  # 4x4 patches / merge 2x2
    rows = []
    for i in range(n):
        ids = [5, 6 + (i % 7)] + [cfg.image_token_id] * n_placeholder + [20, 21]
        rows.append({
            "input_ids": ids,
            "messages": f"How many objects? (scene {i})",
            "answer": i % 5,
            "pixel_values": rng.normal(
                size=(16, vcfg.patch_dim)
            ).astype(np.float32).round(3).tolist(),
            "image_grid_thw": [[1, 4, 4]],
        })
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return path


# a sort or a top-k in a compiled program's text, however the backend spells it
HLO_SORT = re.compile(
    r" (sort|topk)\(|custom_call_target=\"[^\"]*(TopK|Sort)", re.I
)


def sorts_outside_conditionals(hlo_text: str):
    """Lines of a compiled program (`compiled.as_text()`) that sort or take
    a top-k and run whenever the program runs: reachable from ENTRY through
    calls, fusions and loop bodies, but not through a branch of a
    `conditional`."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif name is not None:
            comps[name].append(line)
    assert entry is not None, "no ENTRY computation in the text"
    found, seen, todo = [], set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps.get(comp, ()):
            if HLO_SORT.search(line):
                found.append(line.strip())
            if " conditional(" in line:
                continue  # its branches run only when chosen
            todo += [c for c in re.findall(r"%([\w.\-]+)", line) if c in comps]
    return found
