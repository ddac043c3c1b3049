"""Flash attention as a Pallas TPU kernel.

TPU-native design (not a CUDA port): the kv axis is the innermost
*sequential* ("arbitrary") grid dimension, so the online-softmax state
(m, l, acc) lives in VMEM scratch that persists across kv steps while the
MXU consumes (block_q x dh) @ (dh x block_k) tiles.  Block shapes default to
128 — the MXU systolic width — and dh is kept whole (a lane-dim multiple of
128 for every assigned arch).

Grid: (B * H, Sq / block_q, Sk / block_k)  —  ("parallel", "parallel",
"arbitrary").  GQA maps q-head h to kv-group h // (H // G) in the
BlockSpec index maps; KV blocks fully above the causal diagonal are
predicated off with pl.when (the TPU grid still visits them, but no MXU
work issues).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _kernel(
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref,
    m_scr, l_scr, acc_scr,  # scratch: (bq,1) f32, (bq,1) f32, (bq, dh) f32
    *,
    block_q: int,
    block_k: int,
    sk_blocks: int,
    causal: bool,
    window: int,
    q_offset: int,
    softcap: float,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q + q_offset
    k_start = ki * block_k

    # causal block skip: this kv block is entirely in the future
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window - block_q)

    @pl.when(run)
    def body():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, dh)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        m_safe = jnp.maximum(m_new, -1e30)  # fully-masked rows stay finite
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(jnp.maximum(m_prev, -1e30) - m_safe)
        l_new = l_scr[...][:, 0] * alpha + p.sum(axis=1)
        v = v_ref[0].astype(jnp.float32)  # (bk, dh)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new[:, None]
        l_scr[...] = l_new[:, None]

    @pl.when(ki == sk_blocks - 1)
    def flush():
        l = l_scr[...][:, 0]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, H, Sq, dh)
    k: jax.Array,  # (B, G, Sk, dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, dh = q.shape
    G, Sk = k.shape[1], k.shape[2]
    rep = H // G
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    sk_blocks = Sk // block_k
    grid = (B * H, Sq // block_q, sk_blocks)

    kernel = functools.partial(
        _kernel,
        block_q=block_q,
        block_k=block_k,
        sk_blocks=sk_blocks,
        causal=causal,
        window=window,
        q_offset=q_offset,
        softcap=softcap,
        scale=dh**-0.5,
    )
    qs = q.reshape(B * H, Sq, dh)
    ks = k.reshape(B * G, Sk, dh)
    vs = v.reshape(B * G, Sk, dh)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j, _rep=rep: (b // _rep, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j, _rep=rep: (b // _rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",  # the HLO custom call's name, and the profiler's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qs, ks, vs)
    return out.reshape(B, H, Sq, dh)
