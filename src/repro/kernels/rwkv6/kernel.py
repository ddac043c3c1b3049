"""WKV6 chunk-parallel scan as a Pallas TPU kernel.

TPU-native design: one grid cell per (batch*head, chunk) with the chunk
axis *sequential* ("arbitrary") so the state matrix persists in VMEM
scratch across chunks (held transposed, (V x K), so that its per-key
decay is a row broadcast) — zero HBM state traffic, versus the pure-XLA
chunked scan whose carried state round-trips HBM every chunk.  Within a
chunk everything is dense (L x L x K pairwise-decay einsum feeding the
MXU), the same algebra as models/rwkv.wkv_chunked; all decay exponents are
differences of cumulative log-decays, bounded above by 0 — no overflow.

Grid: (B*H, S/L)  —  ("parallel", "arbitrary").
Outputs: y (B*H, S, K) and the final state (B*H, K, V) (prefill needs it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    r_ref, k_ref, v_ref, lw_ref, u_ref,  # (1, L, K) x4, (1, 1, K)
    y_ref, fin_ref,  # (1, L, K), (1, V, K)
    state_scr,  # VMEM (V, K) f32: the state, transposed
    *,
    chunks: int,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def init():
        state_scr[...] = jnp.zeros_like(state_scr)

    rr = r_ref[0].astype(jnp.float32)  # (L, K)
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, K)
    L = rr.shape[0]

    # inclusive prefix sum over the chunk by log2(L) shifted adds (Mosaic
    # has no cumsum lowering)
    cum = lw  # (L, K)
    s = 1
    while s < L:
        cum = cum + jnp.concatenate([jnp.zeros((s, cum.shape[1]), cum.dtype), cum[:-s]], axis=0)
        s *= 2
    cum_ex = cum - lw
    # intra-chunk pairwise decays: exp(cum_ex[t] - cum[s]) for s < t
    D = cum_ex[:, None, :] - cum[None, :, :]  # (L, L, K)
    P = rr[:, None, :] * kk[None, :, :] * jnp.exp(jnp.minimum(D, 0.0))
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (L, L), 1
    )
    att = P.sum(-1) * tri.astype(jnp.float32)  # (L, L)
    y = jax.lax.dot_general(
        att, vv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # diagonal bonus term
    y += (rr * u * kk).sum(-1, keepdims=True) * vv
    # cross-chunk state contribution
    y += jax.lax.dot_general(
        rr * jnp.exp(cum_ex), state_scr[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )
    y_ref[0] = y.astype(y_ref.dtype)

    # state update, transposed: S'^T = S^T exp(cum_L) + sum_s v_s (exp(cum_L - cum_s) k_s)^T
    cum_last = cum[L - 1:]  # (1, K)
    decay_to_end = jnp.exp(cum_last - cum)  # (L, K)
    state_scr[...] = jnp.exp(cum_last) * state_scr[...] + jax.lax.dot_general(
        vv, (kk * decay_to_end), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ci == chunks - 1)
    def flush():
        fin_ref[0] = state_scr[...]


def wkv6(
    r: jax.Array,  # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,
    log_w: jax.Array,
    u: jax.Array,  # (H, K)
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    B, S, H, K = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    chunks = S // chunk
    grid = (B * H, chunks)

    def fold(a):  # (B,S,H,K) -> (B*H, S, K)
        return a.transpose(0, 2, 1, 3).reshape(B * H, S, K)

    rs, ks, vs, ws = map(fold, (r, k, v, log_w))

    y, fin = pl.pallas_call(
        functools.partial(_kernel, chunks=chunks, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            # u as (H, 1, K): a block's last two dims must equal the
            # array's (or be (8, 128)-aligned), which (1, K) of (H, K) is not
            pl.BlockSpec((1, 1, K), lambda b, c, _h=H: (b % _h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, K, K), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, K), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, K), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(rs, ks, vs, ws, u.reshape(H, 1, K))

    y = y.reshape(B, H, S, K).transpose(0, 2, 1, 3)
    fin = fin.reshape(B, H, K, K).swapaxes(-1, -2)
    return y, fin
