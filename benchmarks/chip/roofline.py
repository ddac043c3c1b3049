"""A kernel's share of its roofline, from the trace and the shape counts."""
from __future__ import annotations

import sys


def share(view, kernel: str):
    """Percent; None where the trace holds no call of the kernel."""
    k = view.facts.get("kernels", {}).get(kernel)
    if k is None:
        return None
    ops = view.trace.ops(kernel=kernel)
    if not ops:
        return None
    calls = len(ops) / len(view.trace.chips)
    least = max(k["flops"] / view.peaks["bf16_flops_per_s"],
                k["bytes"] / view.peaks["hbm_bytes_per_s"])
    bound = "compute" if k["flops"] / view.peaks["bf16_flops_per_s"] >= \
        k["bytes"] / view.peaks["hbm_bytes_per_s"] else "memory"
    busy = sum(o.dur for o in ops) / len(view.trace.chips)
    print(f"[roofline] {kernel}: {calls:g} calls, {busy:.6f} s on the chip, bound by {bound}; "
          f"least time {least:.3e} s per call", file=sys.stderr, flush=True)
    return 100.0 * calls * least / busy
