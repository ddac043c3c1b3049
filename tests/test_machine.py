"""MachineSpec/transport-tier registry tests (DESIGN.md §3).

The regression oracle: independent re-implementations of the pre-registry
cost formulas (straight from the paper's tables, the way the seed code
computed them) must match the registry-backed generic evaluators to within
1e-12 relative error, and the Fig-5 message-count crossovers must be
unchanged.  Plus the §VI loop: a machine fitted from (synthetic) ping-pong
measurements registers and is planned/autotuned end-to-end.
"""
import numpy as np
import pytest

from repro.core.benchmark import spec_from_measurements
from repro.core.machine import (
    MachineSpec,
    get_machine,
    machine_for,
    path_time,
    plan_costs,
    register_machine,
    registered_machines,
    simulate_strategies,
)
from repro.core.maxrate import MaxRateParams, multi_message_time
from repro.core.params import CopyDirection, Locality, TABLE_II, TABLE_III_BETA_N
from repro.core.planner import message_count_crossover, plan_messages
from repro.core.postal import paper_model
from repro.core.simulate import CollectiveProblem, simulate_all
from repro.core.topology import LASSEN, SUMMIT, TpuPodTopology

RTOL = 1e-12


# --------------------------------------------------------------------------
# Reference implementations: the seed's arithmetic, from the tables.
# --------------------------------------------------------------------------

def ref_gpudirect(machine, s, n, ppn_gpus, locality=Locality.OFF_NODE):
    m = paper_model(machine, "gpu", locality)
    p = m.params_for(s)
    params = MaxRateParams(p.alpha, p.beta, TABLE_III_BETA_N[machine]["gpu"])
    return float(multi_message_time(params, s, n, ppn_gpus))


def ref_three_step(machine, s, n, cores, ppn_gpus, dedup=1.0,
                   locality=Locality.OFF_NODE):
    total = s * n
    copy = total * dedup
    d2h = TABLE_II[machine]["on-socket"][CopyDirection.D2H].time(copy)
    h2d = TABLE_II[machine]["on-socket"][CopyDirection.H2D].time(copy)
    s_core = s / cores
    p = paper_model(machine, "cpu", locality).params_for(s_core)
    params = MaxRateParams(p.alpha, p.beta, TABLE_III_BETA_N[machine]["cpu"])
    send = float(multi_message_time(params, s_core, n, cores * ppn_gpus))
    return float(d2h) + send + float(h2d)


def ref_extra_msg(machine, topo, s, n, split):
    c = topo.cores_per_gpu
    total = s * n
    d2h = float(TABLE_II[machine]["on-socket"][CopyDirection.D2H].time(total))
    h2d = float(TABLE_II[machine]["on-socket"][CopyDirection.H2D].time(total))
    pn = paper_model(machine, "cpu", Locality.ON_NODE).params_for(total / c)
    on_node = MaxRateParams(pn.alpha, pn.beta, TABLE_III_BETA_N[machine]["cpu"])
    redist = float(multi_message_time(on_node, total / c, c - 1, topo.cpu_cores_per_node))
    s_core = s / c
    n_core = n if not split else max(n / c, 1.0)
    po = paper_model(machine, "cpu", Locality.OFF_NODE).params_for(s_core)
    off = MaxRateParams(po.alpha, po.beta, TABLE_III_BETA_N[machine]["cpu"])
    send = float(multi_message_time(off, s_core, n_core, c * topo.gpus_per_node))
    return d2h + redist + send + redist + h2d


def ref_dup_devptr(machine, topo, s, n, split):
    c = topo.cores_per_gpu
    total = s * n
    t_d = TABLE_II[machine]["on-socket"][CopyDirection.D2H]
    t_h = TABLE_II[machine]["on-socket"][CopyDirection.H2D]
    d2h = c * t_d.time(0.0) + (t_d.time(total) - t_d.time(0.0))
    h2d = c * t_h.time(0.0) + (t_h.time(total) - t_h.time(0.0))
    s_core = s / c
    n_core = n if not split else max(n / c, 1.0)
    po = paper_model(machine, "cpu", Locality.OFF_NODE).params_for(s_core)
    off = MaxRateParams(po.alpha, po.beta, TABLE_III_BETA_N[machine]["cpu"])
    send = float(multi_message_time(off, s_core, n_core, c * topo.gpus_per_node))
    return float(d2h) + send + float(h2d)


SIZES = [8.0, 1024.0, 4096.0, 65536.0, float(2**20), float(2**24), 123456.0]
COUNTS = [1, 3, 10, 100, 1000]


# --------------------------------------------------------------------------
# Bit-for-bit (1e-12) equality of registry-backed costs vs the seed math.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("machine", ["summit", "lassen"])
def test_registry_gpudirect_matches_reference(machine):
    spec = get_machine(machine)
    g = int(spec.fact("gpus_per_node"))
    for s in SIZES:
        for n in COUNTS:
            ref = ref_gpudirect(machine, s, n, g)
            got = float(path_time(spec, "gpudirect", s, n, concurrency=g))
            assert got == pytest.approx(ref, rel=RTOL)


@pytest.mark.parametrize("machine", ["summit", "lassen"])
def test_registry_three_step_matches_reference(machine):
    spec = get_machine(machine)
    g = int(spec.fact("gpus_per_node"))
    c = int(spec.fact("cores_per_gpu"))
    for s in SIZES:
        for n in COUNTS:
            for cores in (1, c):
                for dd in (1.0, 0.5):
                    ref = ref_three_step(machine, s, n, cores, g, dd)
                    got = float(
                        path_time(spec, "three_step", s, n, lanes=cores,
                                  concurrency=g, dedup_factor=dd)
                    )
                    assert got == pytest.approx(ref, rel=RTOL)


@pytest.mark.parametrize("topo", [SUMMIT, LASSEN], ids=lambda t: t.machine)
@pytest.mark.parametrize("split", [False, True])
def test_registry_strategies_match_reference(topo, split):
    m = topo.machine
    for s in (8.0, 64.0, 4096.0, float(2**22)):
        p = CollectiveProblem(topo=topo, nodes=32, msg_bytes=s, split_messages=split)
        costs = simulate_all(p)
        n = p.n_msgs
        assert costs["cuda_aware"] == pytest.approx(
            ref_gpudirect(m, s, n, topo.gpus_per_node), rel=RTOL)
        assert costs["three_step"] == pytest.approx(
            ref_three_step(m, s, n, 1, topo.gpus_per_node), rel=RTOL)
        assert costs["extra_msg"] == pytest.approx(
            ref_extra_msg(m, topo, s, n, split), rel=RTOL)
        assert costs["dup_devptr"] == pytest.approx(
            ref_dup_devptr(m, topo, s, n, split), rel=RTOL)


def test_registry_tpu_strategies_match_reference():
    """TPU paths re-derived from the system constants (the seed formulas)."""
    topo = TpuPodTopology(pods=2)
    spec = machine_for(topo)
    sys = topo.system
    H, C, L = topo.hosts_per_pod, topo.chips_per_pod, sys.ici_links_per_chip
    dcn = MaxRateParams(sys.dcn_alpha, sys.dcn_beta_per_host, sys.dcn_beta_N_pod)

    def ici(nbytes, hops, links):
        a = sys.ici_alpha + sys.ici_hop_alpha * max(hops - 1, 0)
        return a + nbytes * sys.ici_beta / links

    for s in (4096.0, 262144.0, float(1 << 24)):
        for n in (1, 16, 256):
            got = simulate_strategies(spec, s, n)
            direct = float(multi_message_time(dcn, s, n, H))
            total = s * C * n
            gather = ici(total, topo.torus_x // 2, L)
            staged = gather + float(multi_message_time(dcn, total, 1, 1)) + gather
            rebucket = ici(s * n, 2, L)
            rail = float(multi_message_time(dcn, total / H, 1, H))
            multirail = rebucket + rail + rebucket
            assert got["direct"] == pytest.approx(direct, rel=RTOL)
            assert got["staged"] == pytest.approx(staged, rel=RTOL)
            assert got["multirail"] == pytest.approx(multirail, rel=RTOL)


# --------------------------------------------------------------------------
# Crossover invariance (paper Fig 5) and planner behaviour.
# --------------------------------------------------------------------------

def test_fig5_crossovers_unchanged():
    """The refactor's headline regression oracle: 3-step beats GPUDirect at
    ~10 messages on Summit, ~100 on Lassen (1 KiB messages)."""
    ns = message_count_crossover(SUMMIT, 1024)
    nl = message_count_crossover(LASSEN, 1024)
    assert ns is not None and ns <= 10
    assert nl is not None and 10 < nl <= 150


def test_crossover_matches_linear_scan():
    """Vectorized grid evaluation == the O(n) scan it replaced."""
    from repro.core.paths import gpudirect_time, three_step_time

    for topo in (SUMMIT, LASSEN):
        for s in (1024.0, 4096.0):
            got = message_count_crossover(topo, s, max_msgs=256)
            ref = None
            for n in range(1, 257):
                direct = float(gpudirect_time(topo.machine, s, n, topo.gpus_per_node))
                staged = float(three_step_time(topo.machine, s, n, 1, topo.gpus_per_node))
                if staged < direct:
                    ref = n
                    break
            assert got == ref


def test_no_machine_branching_in_generic_layers():
    """paths/simulate/planner must stay machine-agnostic: machine names may
    appear only as registry entries (machine.py) and data tables (params)."""
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / "core"
    for fname in ("paths.py", "simulate.py", "planner.py"):
        text = (root / fname).read_text()
        for name in ("summit", "lassen", "tpu_v5e", "gh200"):
            assert f'"{name}"' not in text and f"'{name}'" not in text, (
                f"{fname} hard-codes machine {name!r}"
            )


def test_builtin_registry_entries():
    names = registered_machines()
    for expected in ("summit", "lassen", "tpu_v5e", "gh200"):
        assert expected in names
    assert isinstance(get_machine("summit"), MachineSpec)


def test_gh200_like_spec_plans():
    """Extensibility proof: the tightly-coupled entry plans with the same
    generic machinery, and its near-free C2C copies move the staged-path
    crossover far below Summit's."""
    spec = get_machine("gh200")
    costs = plan_costs(spec, 65536.0, 32)
    assert set(costs) == {"gpudirect", "three_step_1core", "three_step_allcores"}
    assert all(v > 0 for v in costs.values())

    class _T:  # minimal topology carrying the registry name
        machine = "gh200"

    x = message_count_crossover(_T(), 1024.0, max_msgs=512)
    xs = message_count_crossover(SUMMIT, 1024.0, max_msgs=512)
    assert x is not None and xs is not None and x <= xs


# --------------------------------------------------------------------------
# spec_from_measurements: the §VI fit -> register -> plan loop.
# --------------------------------------------------------------------------

def _synth(model, sizes):
    return sizes, np.asarray(model.time(sizes), np.float64)


def test_spec_from_measurements_roundtrip_and_planning():
    """Fit a machine from synthetic ping-pong data generated by Summit's own
    tables; the fitted spec must reproduce Summit's planning decisions."""
    sizes = np.unique(np.logspace(0, 8, 64).astype(np.int64)).astype(np.float64)
    gpu = paper_model("summit", "gpu", Locality.OFF_NODE)
    cpu = paper_model("summit", "cpu", Locality.OFF_NODE)
    d2h = TABLE_II["summit"]["on-socket"][CopyDirection.D2H]
    h2d = TABLE_II["summit"]["on-socket"][CopyDirection.H2D]
    spec = spec_from_measurements(
        "fitted_summit_test",
        _synth(gpu, sizes),
        staged_net=_synth(cpu, sizes),
        copy_d2h=(sizes, d2h.time(sizes)),
        copy_h2d=(sizes, h2d.time(sizes)),
        direct_beta_N=TABLE_III_BETA_N["summit"]["gpu"],
        staged_beta_N=TABLE_III_BETA_N["summit"]["cpu"],
        injectors_per_node=6,
        lanes_per_injector=6,
        thresholds=(4096, 65536),
    )
    assert "fitted_summit_test" in registered_machines()
    assert get_machine("fitted_summit_test") is spec

    # fitted costs track the generating tables (noiseless fit)
    for s in (1024.0, 65536.0, float(2**20)):
        for n in (1, 32):
            fitted = float(path_time(spec, "gpudirect", s, n, concurrency=6))
            truth = ref_gpudirect("summit", s, n, 6)
            assert fitted == pytest.approx(truth, rel=0.05)

    # planner end-to-end: single message -> direct; many messages -> staged
    assert plan_messages(spec, 1024.0, 1).strategy == "gpudirect"
    assert plan_messages(spec, 1024.0, 64).strategy.startswith("three_step")

    # crossover machinery works on the fitted machine
    class _T:
        machine = "fitted_summit_test"

    x = message_count_crossover(_T(), 1024.0)
    assert x is not None and x <= 20  # Summit's true value is <= 10


def test_fitted_machine_flows_into_autotune():
    """comms/autotune consumes a fitted machine exactly like a built-in."""
    from repro.comms.autotune import (
        select_collective_strategy,
        select_transfer_path,
    )

    sizes = np.unique(np.logspace(0, 8, 48).astype(np.int64)).astype(np.float64)
    gpu = paper_model("summit", "gpu", Locality.OFF_NODE)
    cpu = paper_model("summit", "cpu", Locality.OFF_NODE)
    d2h = TABLE_II["summit"]["on-socket"][CopyDirection.D2H]
    h2d = TABLE_II["summit"]["on-socket"][CopyDirection.H2D]
    spec = spec_from_measurements(
        "fitted_autotune_test",
        _synth(gpu, sizes),
        staged_net=_synth(cpu, sizes),
        copy_d2h=(sizes, d2h.time(sizes)),
        copy_h2d=(sizes, h2d.time(sizes)),
        direct_beta_N=TABLE_III_BETA_N["summit"]["gpu"],
        staged_beta_N=TABLE_III_BETA_N["summit"]["cpu"],
        injectors_per_node=6,
        lanes_per_injector=6,
        thresholds=(4096, 65536),
    )
    # by name and by spec object
    assert select_transfer_path("fitted_autotune_test", 1024.0, 1) == "gpudirect"
    assert select_transfer_path(spec, 1024.0, 64).startswith("three_step")
    # §VI collective decision on the fitted machine (Summit semantics:
    # tiny Alltoallv -> extra_msg; huge -> dup_devptr)
    assert select_collective_strategy(spec, 8.0, 191, split_messages=True) == "extra_msg"
    assert select_collective_strategy(spec, float(2**22), 191, split_messages=True) == "dup_devptr"


def test_active_fitted_machine_does_not_break_mesh_selectors():
    """Regression: pointing the active machine at a GPU-family fitted spec
    must not crash the TPU-mesh selectors — they need the pod path family
    and fall back to the deployment default."""
    from repro.comms import autotune

    sizes = np.logspace(1, 7, 24)
    spec_from_measurements("fitted_active_test", (sizes, 2e-6 + sizes * 1e-10))
    old = autotune.set_active_machine("fitted_active_test")
    try:
        mesh = {"pod": 2, "data": 16, "model": 16}
        s = autotune.select_allreduce_strategy(mesh, 1e6)
        assert s in ("flat", "hierarchical")
        s2 = autotune.select_alltoall_strategy(mesh, 4096.0, n_msgs=64, crosses_pod=True)
        assert s2 in ("direct", "hierarchical")
        # while message-level selection DOES use the active fitted machine
        assert autotune.select_transfer_path(None, 4096.0, 4) == "gpudirect"
    finally:
        autotune.set_active_machine(old)


def test_direct_only_fit():
    """A fit with only the direct tier still registers and plans (single
    path), e.g. first-boot fitting on a machine without a staging path."""
    sizes = np.logspace(1, 7, 24)
    times = 2e-6 + sizes * 1e-10
    spec = spec_from_measurements(
        "fitted_direct_only", (sizes, times), register=False
    )
    assert list(spec.paths) == ["gpudirect"]
    costs = plan_costs(spec, 4096.0, 4)
    assert list(costs) == ["gpudirect"] and costs["gpudirect"] > 0
    assert "fitted_direct_only" not in registered_machines()


@pytest.mark.parametrize("bench", ["h2d", "d2h", "direct", "staged"])
def test_transfer_benchmarks_fit_postal(bench):
    """Each transfer benchmark yields one positive sample per size and a
    postal fit; on one device the ping-pongs go device 0 <-> device 0."""
    import jax

    from repro.core import benchmark as bm

    dev = jax.devices()[0]
    sizes = (1 << 10, 1 << 14)
    res = {
        "h2d": lambda: bm.bench_host_device_roundtrip(sizes),
        "d2h": lambda: bm.bench_device_to_host(sizes, samples=3),
        "direct": lambda: bm.bench_device_pingpong(dev, dev, sizes),
        "staged": lambda: bm.bench_staged_pingpong(dev, dev, sizes),
    }[bench]()
    assert list(res.sizes) == list(sizes) and len(res.times) == 2
    assert all(t > 0 for t in res.times)
    assert res.fitted.alpha >= 0 and np.isfinite(res.fitted.beta)
