"""One run of one cell of the on-chip benchmark.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, makes the weights on the device from ``--seed``, compiles or loads from
the persistent cache the cell's own programs and warms them (set-up), then
measures for ``--seconds``.  With ``--trace 0`` the result holds the cell's
end-to-end metrics, taken with the profiler off; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window.  Then
the program's state is freed and ``correct`` is decided against the plain
reference.  The last line of standard output is the result as one JSON
object; the numbers compared are the last lines of standard error.

Exits with 2, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(run: harness.Run, driver, device_kind: str) -> tuple:
    """Reduce the window's trace; each metric's reader takes what it needs."""
    import trace_reduce

    trace = trace_reduce.reduce(str(run.trace_dir), driver.SPANS)
    view = SimpleNamespace(trace=trace, facts=driver.facts, peaks=harness.peaks(device_kind),
                           cell=run.cell)
    metrics = {}
    for m in run.cell.per_layer:
        value = harness.load_module("metrics", m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": trace.top_ops(10), "idle_gaps": trace.idle_by_span(10)}
    return metrics, breakdown, trace


def main(argv=None, devices=None) -> int:
    args = parse(argv)
    cell = harness.cell(args.workload)
    if devices is None:
        try:
            devices = harness.require_devices(cell.chips)
        except harness.NoChip as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 2
    print(f"[run] {cell.name} seed {args.seed} on {devices[0].device_kind} x{len(devices)}, "
          f"compile cache {harness.configure_jax()}", flush=True)
    compiles = harness.CompileCounter()
    from repro.obs import metrics as counters

    counters.enable()  # kernels.fallback.*, plan_cache.*: printed after the window
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), list(devices))
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(run)
    driver.setup()
    setup_s = time.perf_counter() - T0
    n0, hits0, s0 = compiles.count, compiles.hits, compiles.seconds
    driver.window()
    print(f"[run] set-up {setup_s:.3f} s: {n0} programs, {hits0} of them from the cache, "
          f"{s0:.3f} s building or loading them; {compiles.count - n0} programs built "
          f"in the window", flush=True)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": harness.memory_peak(devices)}
    breakdown = None
    if run.trace:
        metrics, breakdown, trace = per_layer(run, driver, kind)
        device.update(busy_s=trace.busy_s, window_s=trace.window_s)
    else:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    print(f"[run] counters {counters.summary_line(prefixes=['kernels.', 'plan_cache.'])}",
          flush=True)
    checks = driver.checks()
    harness.print_checks(checks)
    print(harness.result(all(c.ok for c in checks) and driver.failed == 0, driver.attempted,
                         driver.failed, metrics, device, checks, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
