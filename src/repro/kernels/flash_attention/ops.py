"""Jit-ready wrapper: model layout in/out, kernel-or-oracle dispatch."""
from __future__ import annotations

import jax

from repro.kernels import config
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref


def supported(S_q: int, S_k: int, dh: int, block: int = 128) -> bool:
    bq = min(block, S_q)
    bk = min(block, S_k)
    return S_q % bq == 0 and S_k % bk == 0 and dh % 8 == 0


def attention(
    q: jax.Array,  # (B, Sq, H, dh) — model layout
    k: jax.Array,  # (B, Sk, G, dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    softcap: float = 0.0,
    block: int = 128,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if supported(q.shape[1], k.shape[1], q.shape[-1], block):
        out = flash_attention(
            qt, kt, vt,
            causal=causal, window=window, q_offset=q_offset, softcap=softcap,
            block_q=block, block_k=block, interpret=config.interpret_mode(),
        )
    else:
        config.fallback("flash_attention", f"Sq={q.shape[1]}, Sk={k.shape[1]}, "
                                           f"dh={q.shape[-1]}")
        out = attention_ref(
            qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
            softcap=softcap,
        )
    return out.transpose(0, 2, 1, 3)
