"""Operations and bytes of RWKV-6 models, from the published widths.

Per token and layer: the time mix's five d x d projections and its decay LoRA
(rank ``decay_lora_rank``), the channel mix's three matrices, and the WKV recurrence of each
head (state read and decayed, the key-value outer product added, the
receptance read-out: 5 K V + 4 K operations per head).  Logits where a token
is produced.
"""
from __future__ import annotations

import harness

_dims = harness.load_module("reference", "rwkv6").dims


def layer_weights(conf: dict) -> int:
    d, H, K, ff, V, L = _dims(conf)
    return 6 * d * d + 2 * d * ff + 2 * d * conf["decay_lora_rank"]


def wkv_flops_per_token(conf: dict) -> int:
    d, H, K, ff, V, L = _dims(conf)
    return H * (5 * K * K + 4 * K)


def serve_flops(conf: dict, batch: int, prompt_len: int, new_tokens: int) -> int:
    d, H, K, ff, V, L = _dims(conf)
    per_token = L * (2 * layer_weights(conf) + wkv_flops_per_token(conf))
    return batch * ((prompt_len + new_tokens - 1) * per_token + new_tokens * 2 * d * V)


def kernels(conf: dict, batch: int, prompt_len: int) -> dict:
    """The WKV6 kernel as the prefill calls it, once per layer: r, k, v
    (bfloat16) and the log decay (float32) read, y (bfloat16) and the final
    state (float32) written, once each."""
    d, H, K, ff, V, L = _dims(conf)
    return {"wkv6": {
        "calls": L,
        "flops": batch * prompt_len * wkv_flops_per_token(conf),
        "bytes": batch * prompt_len * d * (2 + 2 + 2 + 4 + 2) + batch * H * K * K * 4,
    }}
