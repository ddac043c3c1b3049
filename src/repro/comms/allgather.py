"""All-gather helpers (FSDP parameter gathering path)."""
from __future__ import annotations

import jax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def all_gather_axis(x: jax.Array, mesh: Mesh, axis: str, dim: int = 0) -> jax.Array:
    """Gather an array sharded on ``axis`` along tensor dim ``dim``; output
    replicated over ``axis``.  The explicit form of the FSDP un-shard."""
    in_spec = P(*[axis if i == dim else None for i in range(x.ndim)])
    out_spec = P(*([None] * x.ndim))

    def body(v):
        return jax.lax.all_gather(v, axis, axis=dim, tiled=True)

    # all_gather output IS replicated over `axis`, but the static
    # varying-axes checker cannot infer that through all_gather.
    fn = shard_map(
        body, mesh=mesh, in_specs=in_spec, out_specs=out_spec, check_vma=False
    )
    return fn(x)
