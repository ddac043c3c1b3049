"""RG-LRU diagonal linear scan as a Pallas TPU kernel.

TPU-native design: a Blelloch-style *in-VMEM* log-depth scan inside each
time chunk (log2(L) vectorized passes over a VMEM-resident (L, bW) tile —
VPU work, no HBM), with the chunk axis sequential so the (bW,) carry state
never leaves VMEM scratch.  Compare the XLA ``associative_scan`` lowering,
which makes O(log S) full passes over the (B, S, W) array in HBM: the
kernel reads/writes each element exactly once.

Grid: (B, W/bW, S/L)  —  ("parallel", "parallel", "arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, y_ref, h_scr, *, chunk: int, chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)  # (L, bW)
    b = b_ref[0].astype(jnp.float32)
    L = a.shape[0]

    # inclusive scan of the affine maps h -> a*h + b within the chunk:
    # after the loop, A[t] = prod a_{0..t}, B[t] = h_t given h_{-1} = 0.
    A, Bv = a, b
    s = 1
    while s < L:
        A_sh = jnp.concatenate([jnp.ones((s, A.shape[1]), A.dtype), A[:-s]], axis=0)
        B_sh = jnp.concatenate([jnp.zeros((s, A.shape[1]), A.dtype), Bv[:-s]], axis=0)
        Bv = A * B_sh + Bv
        A = A * A_sh
        s *= 2

    h0 = h_scr[...][0]  # (bW,)
    y = Bv + A * h0[None, :]
    y_ref[0] = y.astype(y_ref.dtype)
    h_scr[...] = y[-1:][:]  # carry last value


def rglru_scan(
    a: jax.Array,  # (B, S, W)
    b: jax.Array,
    *,
    chunk: int = 128,
    block_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, W = a.shape
    chunk = min(chunk, S)
    block_w = min(block_w, W)
    assert S % chunk == 0 and W % block_w == 0, (S, W, chunk, block_w)
    chunks = S // chunk
    grid = (B, W // block_w, chunks)
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, chunks=chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi)),
            pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_w), lambda bi, wi, ci: (bi, ci, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
        name="rglru",  # the HLO custom call's name, and the profiler's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(a, b)
    return y
