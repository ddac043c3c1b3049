"""Live microbenchmarks + model fitting (the paper's measurement pipeline).

The transfer benchmarks time real transports of the devices JAX was given
(host<->device copies, device<->device sends) and fit postal models to
them.  Each sample is a host-clock time around work that ends in a
blocking wait; the device the samples came from is the caller's to record.

:func:`spec_from_measurements` closes the loop the paper draws in §VI:
measured tiers become a registered :class:`~repro.core.machine.MachineSpec`,
so a live-fitted machine plans (``repro.core.planner``) and autotunes
(``repro.comms.autotune``) exactly like the built-in table-driven entries.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.fitting import fit_postal, fit_transport_model
from repro.core.machine import (
    MachineSpec,
    TransportTier,
    gpu_family_paths,
    gpu_family_strategies,
    gpu_plan_variants,
    register_machine,
)
from repro.core.params import PostalParams
from repro.obs import drift as obs_drift


def _time_call(fn: Callable[[], None], min_time: float = 2e-3, max_reps: int = 200) -> float:
    """Paper §VI methodology: repeat until timer precision, min over trials."""
    trials = []
    for _ in range(3):
        # calibrate rep count
        t0 = time.perf_counter()
        fn()
        once = max(time.perf_counter() - t0, 1e-9)
        reps = int(min(max(min_time / once, 1), max_reps))
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        trials.append((time.perf_counter() - t0) / reps)
    return min(trials)


@dataclasses.dataclass
class BenchResult:
    sizes: List[int]
    times: List[float]
    fitted: PostalParams

    def csv_rows(self, name: str) -> List[str]:
        rows = [f"{name},{s},{t:.3e}" for s, t in zip(self.sizes, self.times)]
        rows.append(f"{name}_fit,alpha={self.fitted.alpha:.3e},beta={self.fitted.beta:.3e}")
        return rows


def bench_transfer(
    make_buffer: Callable[[int], object],
    transfer: Callable[[object], object],
    sizes: Sequence[int] = (1 << 10, 1 << 13, 1 << 16, 1 << 19, 1 << 22, 1 << 24),
) -> BenchResult:
    """Measure transfer(buffer_of_size) for each size and fit a postal model."""
    measured: List[float] = []
    szs: List[int] = []
    for s in sizes:
        buf = make_buffer(s)
        t = _time_call(lambda: transfer(buf))
        measured.append(t)
        szs.append(s)
    return BenchResult(sizes=szs, times=measured, fitted=fit_postal(szs, measured))


def bench_host_device_roundtrip(sizes: Sequence[int] = (1 << 12, 1 << 16, 1 << 20, 1 << 23)) -> BenchResult:
    """cudaMemcpyAsync analogue: host numpy -> jax device buffer."""
    import jax

    def make(s: int):
        return np.zeros(s, np.uint8)

    def put(buf):
        jax.device_put(buf).block_until_ready()

    return bench_transfer(make, put, sizes)


def bench_device_to_host(
    sizes: Sequence[int] = (1 << 12, 1 << 16, 1 << 20, 1 << 23), samples: int = 20
) -> BenchResult:
    """Device jax buffer -> host numpy: the min over ``samples`` copies.

    Not :func:`bench_transfer`'s repeat loop: a ``jax.Array`` keeps its host
    copy after the first read, so every sample reads a buffer of its own,
    put on the device outside the timed region.
    """
    import jax

    measured: List[float] = []
    for s in sizes:
        best = float("inf")
        for _ in range(samples):
            buf = jax.device_put(np.zeros(s, np.uint8)).block_until_ready()
            t0 = time.perf_counter()
            jax.device_get(buf)
            best = min(best, time.perf_counter() - t0)
        measured.append(best)
    return BenchResult(sizes=list(sizes), times=measured, fitted=fit_postal(sizes, measured))


def _pingpong(a, b, hop: Callable[[object, object], object],
              sizes: Sequence[int]) -> BenchResult:
    """One-way time between devices ``a`` and ``b``: half a round trip."""
    import jax

    def make(s: int):
        return [jax.device_put(np.zeros(s, np.uint8), a)]

    def there_and_back(held):
        held[0] = hop(hop(held[0], b), a).block_until_ready()

    rt = bench_transfer(make, there_and_back, sizes)
    one_way = [t / 2 for t in rt.times]
    return BenchResult(sizes=rt.sizes, times=one_way, fitted=fit_postal(rt.sizes, one_way))


def bench_device_pingpong(
    a, b, sizes: Sequence[int] = (1 << 12, 1 << 16, 1 << 20, 1 << 23)
) -> BenchResult:
    """Direct device-to-device sends (the GPUDirect analogue)."""
    import jax

    return _pingpong(a, b, lambda x, dev: jax.device_put(x, dev), sizes)


def bench_staged_pingpong(
    a, b, sizes: Sequence[int] = (1 << 12, 1 << 16, 1 << 20, 1 << 23)
) -> BenchResult:
    """Sends staged through host memory: device -> host, host -> device."""
    import jax

    return _pingpong(a, b, lambda x, dev: jax.device_put(np.asarray(x), dev), sizes)


# --------------------------------------------------------------------------
# Measurements -> registered machine (the paper's §VI loop, closed).
# --------------------------------------------------------------------------

Samples = Union["BenchResult", Tuple[Sequence[float], Sequence[float]]]


def _samples(data: Samples) -> Tuple[Sequence[float], Sequence[float]]:
    if isinstance(data, BenchResult):
        return data.sizes, data.times
    sizes, times = data
    return sizes, times


def spec_from_measurements(
    name: str,
    direct_net: Samples,
    *,
    staged_net: Optional[Samples] = None,
    copy_d2h: Optional[Samples] = None,
    copy_h2d: Optional[Samples] = None,
    placed_pairs: Optional[Dict[str, Samples]] = None,
    direct_beta_N: Optional[float] = None,
    staged_beta_N: Optional[float] = None,
    injectors_per_node: int = 1,
    lanes_per_injector: int = 1,
    thresholds=None,
    register: bool = True,
) -> MachineSpec:
    """Build (and by default register) a MachineSpec from measured tiers.

    * ``direct_net`` — ping-pong (size, time) samples of the direct
      device-to-device path (the GPUDirect analogue).
    * ``staged_net`` + ``copy_d2h``/``copy_h2d`` — the staging network tier
      and the host<->device copy tiers; when all three are present the spec
      also declares the 3-step family (``three_step``/``extra_msg``/
      ``dup_devptr``) and the Fig-5 crossover becomes measurable.
    * ``placed_pairs`` — locality-split ping-pong samples of the direct
      path, keyed by placement class (``"on-socket"``, ``"on-node"``,
      ``"off-node"``): pairs pinned on-socket, across sockets of one node,
      and across nodes.  Each class fits its own ``gpu_net:{class}`` tier,
      so :meth:`~repro.core.machine.MachineSpec.resolve_tier` picks the
      placement-correct model exactly as it does for the paper's Table-I
      localities — a degraded machine can be *fitted* per locality live,
      not just declared (ROADMAP item 5).
    * ``direct_beta_N``/``staged_beta_N`` — injection caps, e.g. from
      :func:`repro.core.fitting.fit_maxrate_beta_N` on a ppn sweep (NaN is
      treated as "cap never reached").
    * ``injectors_per_node``/``lanes_per_injector`` — shape facts: devices
      injecting per node, and staging lanes (CPU cores) per device.
    * ``thresholds`` — protocol switch points for the net tiers: a
      ``(short_max, eager_max)`` pair, ``"detect"``, or None (one segment);
      see :func:`repro.core.fitting.fit_transport_model`.

    The result plans and simulates through the exact code paths the
    built-in machines use — registry in, planner out.
    """
    def cap(v: Optional[float]) -> Optional[float]:
        return None if v is None or (isinstance(v, float) and np.isnan(v)) else v

    staged_family = staged_net is not None and copy_d2h is not None and copy_h2d is not None
    tiers: Dict[str, TransportTier] = {
        "gpu_net": TransportTier(
            name="gpu_net",
            model=fit_transport_model(*_samples(direct_net), thresholds=thresholds),
            beta_N=cap(direct_beta_N),
            width=injectors_per_node,
        ),
    }
    if staged_family:
        tiers["cpu_net"] = TransportTier(
            name="cpu_net",
            model=fit_transport_model(*_samples(staged_net), thresholds=thresholds),
            beta_N=cap(staged_beta_N),
            width=lanes_per_injector,
        )
        for tier_name, data in (("copy_d2h", copy_d2h), ("copy_h2d", copy_h2d)):
            tiers[tier_name] = TransportTier(
                name=tier_name,
                model=fit_transport_model(*_samples(data), thresholds=None),
                width=lanes_per_injector,
                serialize_alpha=True,
            )
    if placed_pairs:
        for loc_key, data in placed_pairs.items():
            tier_key = f"gpu_net:{loc_key}"
            tiers[tier_key] = TransportTier(
                name=tier_key,
                model=fit_transport_model(*_samples(data), thresholds=thresholds),
                beta_N=cap(direct_beta_N),
                width=injectors_per_node,
            )
    # fitted-vs-measured residuals per tier: every sample the fit consumed
    # becomes a drift record, so the fit quality itself is visible to
    # run.py --compare (a tier whose model stops matching its own samples
    # is the first sign of a bad protocol-threshold split)
    tier_samples = {"gpu_net": direct_net}
    if staged_family:
        tier_samples.update(
            cpu_net=staged_net, copy_d2h=copy_d2h, copy_h2d=copy_h2d
        )
    if placed_pairs:
        for loc_key, data in placed_pairs.items():
            tier_samples[f"gpu_net:{loc_key}"] = data
    for tier_name, data in tier_samples.items():
        tier = tiers[tier_name]
        for s, t in zip(*_samples(data)):
            obs_drift.record(
                name, tier_name, f"fit:{tier_name}", float(s),
                float(tier.time(float(s))), float(t),
            )
    paths = gpu_family_paths()
    strategies = gpu_family_strategies()
    variants = gpu_plan_variants()
    if not staged_family:
        paths = {"gpudirect": paths["gpudirect"]}
        strategies = {"cuda_aware": strategies["cuda_aware"]}
        variants = {"gpudirect": variants["gpudirect"]}
    spec = MachineSpec(
        name=name,
        tiers=tiers,
        paths=paths,
        strategies=strategies,
        plan_variants=variants,
        facts={
            "gpus_per_node": injectors_per_node,
            "cpu_cores_per_node": injectors_per_node * lanes_per_injector,
            "cores_per_gpu": lanes_per_injector,
            "injectors_per_node": injectors_per_node,
        },
        crossover_paths=("gpudirect", "three_step") if staged_family
        else ("gpudirect", "gpudirect"),
        description=f"fitted from measurements ({len(_samples(direct_net)[0])} "
                    f"direct-net samples)",
        provenance="fitted",
    )
    if register:
        register_machine(name, spec)
    return spec
