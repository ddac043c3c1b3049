"""Multi-device correctness checks, run as a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (tests/test_comms.py).

Prints one `OK <name>` line per passing check; any exception fails the run.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.comms import (
    all_gather_axis,
    allreduce_flat,
    allreduce_hierarchical,
    allreduce_ring,
    alltoall_direct,
    alltoall_hierarchical,
    halo_exchange,
    reduce_scatter,
    ring_shift,
)
from repro.comms.overlap import chunked_collective
from repro.optim.compress import compressed_allreduce

ok = lambda name: print(f"OK {name}", flush=True)


def mesh2(a, b, names=("pod", "data")):
    return jax.make_mesh((a, b), names, axis_types=(AxisType.Auto,) * 2)


def main():
    assert len(jax.devices()) == 8, jax.devices()
    rng = np.random.default_rng(0)

    # ---- allreduce strategies agree ------------------------------------
    mesh = mesh2(2, 4, ("pod", "data"))
    x = jnp.asarray(rng.standard_normal((8, 16, 5)), jnp.float32)
    want = np.broadcast_to(np.asarray(x).sum(0, keepdims=True), x.shape)
    flat = allreduce_flat(x, mesh, ("pod", "data"))
    np.testing.assert_allclose(np.asarray(flat), want, rtol=1e-5, atol=1e-5)
    ok("allreduce_flat")
    hier = allreduce_hierarchical(x, mesh, "pod", ("data",))
    np.testing.assert_allclose(np.asarray(hier), want, rtol=1e-5, atol=1e-5)
    ok("allreduce_hierarchical")
    ring_mesh = mesh2(1, 8, ("pod", "data"))
    xr = jnp.asarray(rng.standard_normal((8, 24)), jnp.float32)
    ring = allreduce_ring(xr, ring_mesh, "data")
    np.testing.assert_allclose(
        np.asarray(ring), np.broadcast_to(np.asarray(xr).sum(0, keepdims=True), xr.shape),
        rtol=1e-5,
    )
    ok("allreduce_ring")

    # ---- reduce_scatter --------------------------------------------------
    rs = reduce_scatter(xr, ring_mesh, "data")
    full = np.asarray(xr).sum(0)
    for i in range(8):
        np.testing.assert_allclose(np.asarray(rs)[i], full[i * 3 : (i + 1) * 3], rtol=1e-5, atol=1e-5)
    ok("reduce_scatter")

    # ---- alltoall direct == hierarchical == transpose ---------------------
    mesh_a2a = mesh2(2, 4, ("outer", "inner"))
    k = 8
    blocks = jnp.asarray(rng.standard_normal((k, k, 3)), jnp.float32)
    direct = alltoall_direct(blocks, mesh_a2a, ("outer", "inner"))
    want_t = np.asarray(blocks).transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(direct), want_t, rtol=1e-5, atol=1e-5)
    ok("alltoall_direct")
    hier2 = alltoall_hierarchical(blocks, mesh_a2a, "outer", "inner")
    np.testing.assert_allclose(np.asarray(hier2), want_t, rtol=1e-5, atol=1e-5)
    ok("alltoall_hierarchical")

    # ---- p2p --------------------------------------------------------------
    shift = ring_shift(xr, ring_mesh, "data", 1)
    np.testing.assert_allclose(np.asarray(shift), np.roll(np.asarray(xr), 1, axis=0))
    ok("ring_shift")
    halo = halo_exchange(
        jnp.asarray(rng.standard_normal((8, 6, 2)), jnp.float32), ring_mesh, "data", 2
    )
    assert halo.shape == (8, 10, 2)
    ok("halo_exchange")

    # ---- all_gather -------------------------------------------------------
    g = all_gather_axis(xr, ring_mesh, "data", dim=0)
    np.testing.assert_allclose(np.asarray(g), np.asarray(xr))
    ok("all_gather_axis")

    # ---- compressed allreduce ≈ flat ---------------------------------------
    xc = jnp.asarray(rng.standard_normal((8, 2048)), jnp.float32)
    cr = compressed_allreduce(xc, mesh, "pod", ("data",))
    true = np.asarray(xc).sum(0)
    err = np.abs(np.asarray(cr)[0] - true)
    # per-pod quantization bound: scale/2 = max|RS-shard| / 254, x pods
    shard_max = np.abs(np.asarray(xc).reshape(2, 4, -1).sum(1)).max()
    assert err.max() <= 2 * shard_max / 254 + 1e-6, (err.max(), shard_max)
    ok("compressed_allreduce")

    # ---- chunked collective identity ----------------------------------------
    cc = chunked_collective(lambda p: allreduce_flat(p, mesh, ("pod", "data")), x, 2)
    np.testing.assert_allclose(np.asarray(cc), want, rtol=1e-5, atol=1e-5)
    ok("chunked_collective")

    # ---- sharded MoE == dense (high capacity) --------------------------------
    from repro.configs import smoke_config
    from repro.models import forward, init_params
    from repro.models.transformer import DistContext

    cfg = smoke_config("dbrx-132b")
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0), ep_shards=2)  # 4 experts x2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    ref, _ = forward(cfg, params, tokens)
    mesh_me = mesh2(1, 8, ("data", "model"))
    dist = DistContext(mesh=mesh_me, dp_axes=("data",), ep_shards=2)
    out, _ = jax.jit(lambda p, t: forward(cfg, p, t, dist=dist))(params, tokens)
    err = float(jnp.abs(out - ref).max())
    assert err < 0.08, err
    ok("moe_sharded_vs_dense")

    # chunked a2a strategy agrees too
    dist_c = dataclasses.replace(dist, moe_strategy="chunked", a2a_chunks=2)
    out_c, _ = jax.jit(lambda p, t: forward(cfg, p, t, dist=dist_c))(params, tokens)
    assert float(jnp.abs(out_c - ref).max()) < 0.08
    ok("moe_chunked_a2a")

    # ---- sharded train step == single-device train step ----------------------
    from repro.configs.base import RunConfig
    from repro.models.steps import train_step
    from repro.optim import init_state
    from repro.sharding import specs

    cfgl = smoke_config("llama3.2-1b")
    run = RunConfig(model=cfgl, n_microbatches=1, remat=False, warmup_steps=1,
                    total_steps=10, learning_rate=1e-3)
    p0 = init_params(cfgl, jax.random.PRNGKey(0))
    o0 = init_state(p0)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfgl.vocab_size)}
    p1, o1, m1 = train_step(cfgl, run, p0, o0, batch)

    mesh_t = mesh2(2, 4, ("data", "model"))
    dist_t = DistContext(mesh=mesh_t, dp_axes=("data",))
    p_sh = specs.param_shardings(p0, mesh_t)
    p0s = jax.device_put(p0, p_sh)
    o0s = init_state(p0s)
    p2, o2, m2 = jax.jit(lambda p, o, b: train_step(cfgl, run, p, o, b, dist=dist_t))(
        p0s, o0s, batch
    )
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2, (m1["loss"], m2["loss"])
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        p1, p2,
    )
    assert max(jax.tree_util.tree_leaves(d)) < 0.15
    ok("sharded_train_step_matches")

    # ---- elastic reshard: restore on a different mesh -------------------------
    import tempfile

    from repro.checkpoint import Checkpointer
    from repro.runtime.elastic import restore_on_mesh

    with tempfile.TemporaryDirectory() as td:
        ck = Checkpointer(td)
        ck.save(7, p2, block=True)
        mesh_new = mesh2(4, 2, ("data", "model"))
        p3 = restore_on_mesh(ck, 7, jax.tree.map(np.asarray, p2), mesh_new)
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - jnp.asarray(np.asarray(b), jnp.float32)))),
            p3, p2,
        )
        assert max(jax.tree_util.tree_leaves(d)) == 0.0
    ok("elastic_reshard")

    # ---- mid-run reshape continuity: shrink 8->4, grow 4->8 -------------------
    # Continue the run from (p2, o2) twice: a reference continuation on the
    # original 2x4 mesh, and an elastic one that shrinks to 4 devices for
    # step 2 then grows back to 8 for step 3 (checkpoint -> restore ->
    # reshard params AND optimizer state each time).  Global batch is held
    # constant, so both trajectories must track each other step for step —
    # the loss-continuity contract runtime/elastic promises.
    from repro.runtime.elastic import reshard_tree

    def submesh(a, b, names, devs):
        arr = np.array(devs).reshape(a, b)
        return jax.sharding.Mesh(arr, names, axis_types=(AxisType.Auto,) * 2)

    def step_on(dist_):
        return jax.jit(lambda p, o, b: train_step(cfgl, run, p, o, b, dist=dist_))

    batch2 = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfgl.vocab_size)}
    batch3 = {"tokens": jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0, cfgl.vocab_size)}
    pr, o_r, mr2 = step_on(dist_t)(p2, o2, batch2)
    pr, o_r, mr3 = step_on(dist_t)(pr, o_r, batch3)

    host = lambda t: jax.tree.map(np.asarray, t)
    with tempfile.TemporaryDirectory() as td:
        ck = Checkpointer(td)
        ck.save(1, {"params": p2, "opt": o2}, block=True)
        blob = ck.restore(1, host({"params": p2, "opt": o2}))
        mesh_small = submesh(2, 2, ("data", "model"), jax.devices()[:4])
        dist_s = DistContext(mesh=mesh_small, dp_axes=("data",))
        ps = reshard_tree(blob["params"],
                          specs.param_shardings(blob["params"], mesh_small))
        os_ = reshard_tree(blob["opt"],
                           specs.opt_shardings(blob["params"], mesh_small))
        ps, os_, ms2 = step_on(dist_s)(ps, os_, batch2)
        assert abs(float(mr2["loss"]) - float(ms2["loss"])) < 2e-2, (
            mr2["loss"], ms2["loss"])
        ok("elastic_shrink_continuity")

        ck.save(2, {"params": ps, "opt": os_}, block=True)
        blob2 = ck.restore(2, host({"params": ps, "opt": os_}))
        pg = reshard_tree(blob2["params"],
                          specs.param_shardings(blob2["params"], mesh_t))
        og = reshard_tree(blob2["opt"],
                          specs.opt_shardings(blob2["params"], mesh_t))
        pg, og, mg3 = step_on(dist_t)(pg, og, batch3)
        assert abs(float(mr3["loss"]) - float(mg3["loss"])) < 2e-2, (
            mr3["loss"], mg3["loss"])
        d = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            pg, pr,
        )
        assert max(jax.tree_util.tree_leaves(d)) < 0.15
        ok("elastic_grow_continuity")

    print("ALL_MULTIDEVICE_OK", flush=True)


if __name__ == "__main__":
    main()
