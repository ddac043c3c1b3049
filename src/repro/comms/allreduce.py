"""All-reduce strategies.

Contract of every public wrapper: the *leading dimension* of ``x`` indexes
replicas over the reduce axes (size == product of the reduce-axes sizes);
``x[i]`` is replica i's contribution.  The result has the same shape with
``out[i] = sum_j x[j]`` — i.e. after the call every replica's slot holds the
reduced value (standard all-reduce semantics, laid out as a global array so
the wrappers are jit-free-standing and testable).

Strategies:

* ``flat``         — one psum over all axes (XLA picks; baseline /
                     "CUDA-aware" analogue).
* ``hierarchical`` — reduce-scatter over the fast (intra-pod ICI) axes,
                     psum over the slow (cross-pod DCN) axis on 1/k shards,
                     all-gather back over the fast axes.  The paper's
                     "split the slow-tier traffic over every injecting
                     agent" optimization (§IV, Dup-Devptr).
* ``ring``         — explicit bidirectional ring via ppermute (reference
                     algorithm; exercises collective-permute in the HLO).

``*_inner`` variants are for use inside an existing shard_map body.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax

from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# --------------------------------------------------------------------------
# Inner (shard_map-body) building blocks.  x: this device's contribution.
# --------------------------------------------------------------------------

def allreduce_flat_inner(x: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    return jax.lax.psum(x, axes)


def allreduce_hier_inner(
    x: jax.Array, slow_axis: str, fast_axes: Tuple[str, ...], fast_size: int
) -> jax.Array:
    """RS(fast) -> psum(slow) on shards -> AG(fast)."""
    lead = x.shape[0]
    pad = (-lead) % fast_size
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    shard = x
    for a in fast_axes:
        shard = jax.lax.psum_scatter(shard, a, scatter_dimension=0, tiled=True)
    shard = jax.lax.psum(shard, slow_axis)
    out = shard
    for a in reversed(fast_axes):
        out = jax.lax.all_gather(out, a, axis=0, tiled=True)
    return out[:lead] if pad else out


def allreduce_ring_inner(x: jax.Array, axis: str, axis_size: int) -> jax.Array:
    """Ring reduce-scatter + ring all-gather via ppermute (2(k-1) steps)."""
    k = axis_size
    if k == 1:
        return x
    lead = x.shape[0]
    pad = (-lead) % k
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    chunks = jnp.reshape(x, (k, -1) + x.shape[1:])
    idx = jax.lax.axis_index(axis)
    perm_fwd = [(i, (i + 1) % k) for i in range(k)]

    # Reduce-scatter: after k-1 steps, device d owns the full sum of chunk
    # (d+1) mod k.  Each step: send current partial, add the local chunk for
    # the partial we receive.
    def rs_step(i, buf):
        recv = jax.lax.ppermute(buf, axis, perm_fwd)
        tgt = (idx - i - 1) % k  # chunk id the received partial corresponds to
        return recv + chunks[tgt]

    buf0 = chunks[idx]
    owned = jax.lax.fori_loop(0, k - 1, rs_step, buf0)  # sum of chunk (idx+1)%k
    own_id = (idx + 1) % k

    # All-gather the reduced chunks around the ring.
    def ag_step(i, carry):
        out, buf = carry
        buf = jax.lax.ppermute(buf, axis, perm_fwd)
        src = (own_id - i - 1) % k
        out = jax.lax.dynamic_update_index_in_dim(out, buf, src, 0)
        return out, buf

    out0 = jnp.zeros_like(chunks)
    out0 = jax.lax.dynamic_update_index_in_dim(out0, owned, own_id, 0)
    out, _ = jax.lax.fori_loop(0, k - 1, ag_step, (out0, owned))
    out = jnp.reshape(out, (k * out.shape[1],) + out.shape[2:])
    return out[:lead] if pad else out


# --------------------------------------------------------------------------
# Global-array wrappers.
# --------------------------------------------------------------------------

def _check_lead(x: jax.Array, k: int, who: str) -> None:
    if x.shape[0] != k:
        raise ValueError(
            f"{who}: leading dim {x.shape[0]} must equal #replicas {k} "
            f"(one contribution slice per device over the reduce axes)"
        )


def _mesh_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _squeeze_body(fn):
    """shard_map body adapter: local block (1, *payload) <-> payload."""

    @functools.wraps(fn)
    def body(x):
        return fn(x[0])[None]

    return body


def allreduce_flat(x: jax.Array, mesh: Mesh, axes: Sequence[str]) -> jax.Array:
    axes = tuple(axes)
    k = _mesh_size(mesh, axes)
    _check_lead(x, k, "allreduce_flat")
    spec = P(axes, *([None] * (x.ndim - 1)))
    fn = shard_map(
        _squeeze_body(functools.partial(allreduce_flat_inner, axes=axes)),
        mesh=mesh, in_specs=spec, out_specs=spec,
    )
    return fn(x)


def allreduce_hierarchical(
    x: jax.Array, mesh: Mesh, slow_axis: str, fast_axes: Sequence[str]
) -> jax.Array:
    fast_axes = tuple(fast_axes)
    all_axes = (slow_axis,) + fast_axes
    k = _mesh_size(mesh, all_axes)
    _check_lead(x, k, "allreduce_hierarchical")
    fast_size = _mesh_size(mesh, fast_axes)
    spec = P(all_axes, *([None] * (x.ndim - 1)))
    fn = shard_map(
        _squeeze_body(
            functools.partial(
                allreduce_hier_inner,
                slow_axis=slow_axis,
                fast_axes=fast_axes,
                fast_size=fast_size,
            )
        ),
        mesh=mesh, in_specs=spec, out_specs=spec,
    )
    return fn(x)


def allreduce_ring(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    k = mesh.shape[axis]
    _check_lead(x, k, "allreduce_ring")
    spec = P((axis,), *([None] * (x.ndim - 1)))
    fn = shard_map(
        _squeeze_body(
            functools.partial(allreduce_ring_inner, axis=axis, axis_size=k)
        ),
        mesh=mesh, in_specs=spec, out_specs=spec,
    )
    return fn(x)


def reduce_scatter(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Per-replica contributions (lead dim = axis size) -> each replica gets
    its 1/k shard of the sum.  Output shape: (k, payload/k)."""
    k = mesh.shape[axis]
    _check_lead(x, k, "reduce_scatter")

    def body(v):
        return jax.lax.psum_scatter(v[0], axis, scatter_dimension=0, tiled=True)[None]

    in_spec = P((axis,), *([None] * (x.ndim - 1)))
    out_spec = in_spec
    fn = shard_map(body, mesh=mesh, in_specs=in_spec, out_specs=out_spec)
    return fn(x)


def auto_allreduce_strategy(
    x: jax.Array,
    mesh: Mesh,
    slow_axis: str = "pod",
    fast_axes: Sequence[str] = ("data",),
) -> str:
    """Model-driven strategy pick for :func:`allreduce`.

    Consults :mod:`repro.comms.autotune` (event-engine schedule search
    against the active machine, closed-form planners as fallback) with this
    mesh's shape and the per-replica payload size.

    Cheap enough to call per collective: the first consultation for a
    (machine, mesh, payload-bucket) key lowers and simulates candidate
    schedules; every later one is a plan-cache probe (microseconds — see
    ``plan_cache_info`` and the planner_speed benchmark), so
    ``strategy="auto"`` is safe inside a serving or training step loop.
    """
    from repro.comms.autotune import select_allreduce_strategy

    if slow_axis not in mesh.shape:
        return "flat"
    bytes_per_chip = float(x.size // max(x.shape[0], 1)) * x.dtype.itemsize
    # only the participating axes: other mesh axes would inflate the modeled
    # per-pod chip count and price the wrong machine
    shape = {a: mesh.shape[a]
             for a in (slow_axis, *fast_axes) if a in mesh.shape}
    return select_allreduce_strategy(shape, bytes_per_chip)


def allreduce(
    x: jax.Array,
    mesh: Mesh,
    strategy: str = "flat",
    slow_axis: str = "pod",
    fast_axes: Sequence[str] = ("data",),
) -> jax.Array:
    """Strategy-dispatched all-reduce over (slow_axis, *fast_axes).

    ``strategy="auto"`` asks the performance models (schedule search with
    closed-form fallback, see :func:`auto_allreduce_strategy`)."""
    if strategy == "auto":
        strategy = auto_allreduce_strategy(x, mesh, slow_axis, fast_axes)
    if strategy == "flat" or slow_axis not in mesh.shape:
        axes = [a for a in (slow_axis, *fast_axes) if a in mesh.shape]
        return allreduce_flat(x, mesh, axes)
    if strategy == "hierarchical":
        return allreduce_hierarchical(x, mesh, slow_axis, tuple(fast_axes))
    if strategy == "ring":
        return allreduce_ring(x, mesh, fast_axes[0])
    raise ValueError(f"unknown allreduce strategy {strategy!r}")
