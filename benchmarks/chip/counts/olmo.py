"""Operations and bytes of OLMo-style models, from the published widths.

Model FLOPs count the matmuls and the attention a token needs once: 2 per
weight per token forward, 6 in training (forward and backward), and no
recompute.  Serving computes logits only where a token is produced (the last
prompt position and each decoded one); training at every position.
"""
from __future__ import annotations


import harness

_dims = harness.load_module("reference", "olmo").dims


def layer_weights(conf: dict) -> int:
    """Matmul weights of one block: q, k, v, o and the three SwiGLU matrices."""
    d, H, G, dh, ff, V, L = _dims(conf)
    return d * H * dh + 2 * d * G * dh + H * dh * d + 3 * d * ff


def attention_flops(conf: dict, queries: int, first_key_count: int) -> int:
    """QK^T and PV of ``queries`` consecutive positions, the first of which
    sees ``first_key_count`` keys (causal), summed over layers."""
    d, H, G, dh, ff, V, L = _dims(conf)
    keys = queries * first_key_count + queries * (queries - 1) // 2
    return L * 4 * H * dh * keys


def serve_flops(conf: dict, batch: int, prompt_len: int, new_tokens: int) -> int:
    """One static batch: prefill of ``prompt_len`` then ``new_tokens - 1``
    decode steps (the first token comes from the prefill's logits)."""
    d, H, G, dh, ff, V, L = _dims(conf)
    per_token = 2 * L * layer_weights(conf)
    prefill = prompt_len * per_token + attention_flops(conf, prompt_len, 1) + 2 * d * V
    decode = (new_tokens - 1) * (per_token + 2 * d * V) + attention_flops(
        conf, new_tokens - 1, prompt_len + 1)
    return batch * (prefill + decode)


def train_flops(conf: dict, batch: int, seq_len: int) -> int:
    """One optimizer step: forward and backward (x3) of every position."""
    d, H, G, dh, ff, V, L = _dims(conf)
    per_token = 2 * (L * layer_weights(conf) + d * V)
    return 3 * batch * (seq_len * per_token + attention_flops(conf, seq_len, 1))


def kernels(conf: dict, batch: int, prompt_len: int) -> dict:
    """The flash-attention kernel as the prefill calls it, once per layer:
    causal attention of ``prompt_len`` positions, q/k/v read and o written
    in bfloat16 once each."""
    d, H, G, dh, ff, V, L = _dims(conf)
    return {"flash_attention": {
        "calls": L,
        "flops": batch * attention_flops(conf, prompt_len, 1) // L,
        "bytes": 2 * batch * prompt_len * dh * (2 * H + 2 * G),
    }}
