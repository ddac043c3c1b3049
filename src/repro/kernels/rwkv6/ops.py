"""Jit-ready WKV6 wrapper: Pallas kernel or recurrence oracle."""
from __future__ import annotations

from repro.kernels import config
from repro.kernels.rwkv6.kernel import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref


def supported(S: int, chunk: int) -> bool:
    return S % min(chunk, S) == 0


def wkv(r, k, v, log_w, u, *, chunk: int = 32):
    S = r.shape[1]
    if supported(S, chunk):
        return wkv6(r, k, v, log_w, u, chunk=chunk, interpret=config.interpret_mode())
    config.fallback("wkv6", f"S={S} with chunk {chunk}")
    return wkv6_ref(r, k, v, log_w, u)
