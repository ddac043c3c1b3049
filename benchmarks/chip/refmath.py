"""Arithmetic the plain references share: matmuls at a stated precision, the
weights drawn from the seed, and per-slice norms of a parameter tree.

Nothing here imports the program.  ``mode`` is ``"f32"`` for the reference
(float32 at ``Precision.HIGHEST``, which a TPU otherwise rounds to bfloat16)
or ``"fp8"`` for the control: both operands of every matmul rounded to
float8_e4m3fn with one absmax scale per operand, then multiplied as above,
and their gradients rounded to float8_e5m2.

The rounding is worked out in float32 arithmetic, not as a round trip
through the narrow type: under ``jax.jit`` the TPU compiler drops a
``float32 -> float8 -> float32`` (or ``-> bfloat16 ->``) pair of converts as
a no-op, so such a round trip rounds nothing on the chip.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# (explicit mantissa bits, exponent of the smallest normal, largest finite)
E4M3FN = (3, -6, 448.0)
E5M2 = (2, -14, 57344.0)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also one above 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def quantize(x: jax.Array, fmt) -> jax.Array:
    """``x`` rounded to the nearest value of the float format ``fmt``
    (round half to even, subnormals kept, saturating), as float32.  Every
    step is exact in float32: the spacing is a power of two."""
    bits, min_exp, largest = fmt
    x = x.astype(jnp.float32)
    ax = jnp.abs(x)
    _, e = jnp.frexp(ax)  # ax = m * 2**e, 0.5 <= m < 1
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e, min_exp + 1) - 1 - bits)
    return jnp.sign(x) * jnp.minimum(jnp.round(ax / step) * step, largest)


def _round(x: jax.Array, fmt) -> jax.Array:
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / fmt[2]
    return quantize(x / scale, fmt) * scale


def store_fp8(x: jax.Array) -> jax.Array:
    """A parameter kept in float8_e4m3fn (one absmax scale), as float32."""
    return _round(x, E4M3FN)


@jax.custom_vjp
def fp8(x: jax.Array) -> jax.Array:
    """Round to float8_e4m3fn under one absmax scale; back in float32.  Its
    gradient is the incoming one rounded to float8_e5m2 the same way, as
    fp8 training keeps its gradients."""
    return _round(x, E4M3FN)


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (_round(g, E5M2),))


def mm(spec: str, a: jax.Array, b: jax.Array, mode: str) -> jax.Array:
    """``jnp.einsum(spec, a, b)`` in float32 at the reference's precision."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if mode == "fp8":
        a, b = fp8(a), fp8(b)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def normal(key: jax.Array, shape: Tuple[int, ...], fan_in: int, dtype) -> jax.Array:
    """N(0, 1/fan_in) entries, as a deployment's initialiser would draw them."""
    return (jax.random.normal(key, shape, jnp.float32) / np.sqrt(fan_in)).astype(dtype)


def layernorm(x: jax.Array, eps: float) -> jax.Array:
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


# ---------------------------------------------------------------------------
# Per-slice norms.  A leaf stacked over layers (under "groups") is one slice
# per layer: the gradient and the change are compared tensor by tensor, as a
# model with one leaf per layer would have them.
# ---------------------------------------------------------------------------

def is_stacked(path) -> bool:
    return any(getattr(k, "key", None) == "groups" for k in path)


def slice_names(tree) -> List[str]:
    names = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        base = jax.tree_util.keystr(path)
        if is_stacked(path):
            names += [f"{base}[{i}]" for i in range(leaf.shape[0])]
        else:
            names.append(base)
    return names


def slice_norms(tree) -> jax.Array:
    """Float32 norm of every slice, in ``slice_names`` order (traceable)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sq = jnp.square(leaf.astype(jnp.float32))
        if is_stacked(path):
            out.append(jnp.sqrt(sq.reshape(leaf.shape[0], -1).sum(-1)))
        else:
            out.append(jnp.sqrt(sq.sum())[None])
    return jnp.concatenate(out)


def worst_gap(got: np.ndarray, ref: np.ndarray, keep: np.ndarray = None) -> Tuple[float, int]:
    """Largest |got - ref| over slices, each against the larger of its own
    reference norm and the median slice's; returns (gap, slice index)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else keep
    floor = np.median(ref[keep])
    gap = np.where(keep, np.abs(got - ref) / np.maximum(ref, floor), 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i

