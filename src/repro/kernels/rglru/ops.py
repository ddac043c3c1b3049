"""Jit-ready RG-LRU scan wrapper: Pallas kernel or scan oracle."""
from __future__ import annotations

from repro.kernels import config
from repro.kernels.rglru.kernel import rglru_scan
from repro.kernels.rglru.ref import rglru_ref


def scan(a, b, *, chunk: int = 128, block_w: int = 128):
    B, S, W = a.shape
    ck, bw = min(chunk, S), min(block_w, W)
    if S % ck == 0 and W % bw == 0:
        return rglru_scan(a, b, chunk=ck, block_w=bw, interpret=config.interpret_mode())
    config.fallback("rglru", f"S={S}, W={W} with chunk {ck}, block {bw}")
    return rglru_ref(a, b)[0]
