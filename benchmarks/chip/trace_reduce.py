"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

    python benchmarks/chip/trace_reduce.py <trace dir or .xplane.pb> <span> [span ...]

What a TPU trace holds, as ``jax.profiler.ProfileData`` reads it:

- one plane ``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` has one event
  per executed HLO operation (named by its HLO text, ``%fusion.3 = bf16[..]
  fusion(..)``) and whose line ``XLA Modules`` has one event per program run;
- the plane ``/host:CPU``, whose thread lines hold the benchmark's
  ``jax.profiler.TraceAnnotation`` spans and one ``PJRT_LoadedExecutable_Execute``
  event per program dispatch.

The device clock and the host clock of one trace can differ by a millisecond.
The offset is taken from the dispatches: the n-th program run on a chip was
dispatched by the n-th ``PJRT_LoadedExecutable_Execute`` on the host, and no
run starts before its dispatch, so the smallest (run start - dispatch start)
is the offset.  Each run is then named by the benchmark span its dispatch fell
in, each op by its run, and each idle gap on a chip by the span the host was in.
The pairing needs the trace to hold only work dispatched inside it: a driver
drains the chip before it starts the profiler.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DISPATCH = "PJRT_LoadedExecutable_Execute"
OTHER = "host:other"
_HLO = re.compile(r"^%?(?P<name>[^ ]+) = (?P<type>\([^=]*?\)|[^ ]+) (?P<opcode>[a-z][a-z0-9_-]*)\(")


def kernel_name(name: str, opcode: str) -> Optional[str]:
    """The Pallas kernel's name (``pallas_call(name=...)``), which a named
    kernel's custom call takes as its op name (``wkv6.6``): the op name
    without its ``.N``.  None for other ops and unnamed custom calls."""
    base = name.rsplit(".", 1)[0]
    if opcode != "custom-call" or base == "custom-call" or base.startswith("_unknown_"):
        return None
    return base


@dataclasses.dataclass
class Op:
    name: str  # HLO instruction name, e.g. "fusion.3", "wkv6.6"
    opcode: str  # e.g. "fusion", "custom-call"
    result: str  # result type, e.g. "bf16[8,512,128]{...}"
    start: float  # seconds, host clock
    dur: float
    span: str  # benchmark span that dispatched its program

    @property
    def kernel(self) -> Optional[str]:
        return kernel_name(self.name, self.opcode)


@dataclasses.dataclass
class Module:
    name: str
    start: float
    dur: float
    span: str


@dataclasses.dataclass
class Chip:
    plane: str
    ops: List[Op]
    modules: List[Module]
    offset: Optional[float]  # device clock - host clock, s; None where unmatched
    busy_s: float = 0.0
    gaps: List[Tuple[float, float, str]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    spans: List[Tuple[str, float, float]]  # (name, start, end), host clock, seconds
    window: Tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        return sum(c.busy_s for c in self.chips) / max(len(self.chips), 1)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s[0] == name)

    def module_seconds(self, span: str) -> float:
        """Device seconds of the program runs dispatched in ``span``, per chip."""
        tot = sum(m.dur for c in self.chips for m in c.modules if m.span == span)
        return tot / max(len(self.chips), 1)

    def ops(self, span: Optional[str] = None, opcode: Optional[str] = None,
            kernel: Optional[str] = None) -> List[Op]:
        return [o for c in self.chips for o in c.ops
                if (span is None or o.span == span)
                and (opcode is None or o.opcode == opcode)
                and (kernel is None or o.kernel == kernel)]

    def top_ops(self, n: int = 10) -> List[List]:
        """The device ops that took most time (summed over runs), per chip:
        a named kernel by its name, any other op by opcode and result type."""
        agg: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for o in c.ops:
                agg[f"{o.span}:{o.kernel or o.opcode + ' ' + o.result.split('{')[0]}"] += o.dur
        k = max(len(self.chips), 1)
        return [[name, t / k] for name, t in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[List]:
        """Idle seconds on the chips, summed by what the host was doing, per chip."""
        agg: Dict[str, float] = defaultdict(float)
        cnt: Dict[str, int] = defaultdict(int)
        for c in self.chips:
            for a, b, span in c.gaps:
                agg[span] += b - a
                cnt[span] += 1
        k = max(len(self.chips), 1)
        rows = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{span} (x{cnt[span] // k})", t / k] for span, t in rows]


def find_xplane(path: str) -> str:
    if path.endswith(".xplane.pb"):
        return path
    hits = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return hits[-1]


def _parse_op(text: str) -> Tuple[str, str, str]:
    m = _HLO.match(text)
    if not m:
        name = text.split(" ")[0].lstrip("%")
        return name, name.split(".")[0], ""
    return m["name"], m["opcode"], m["type"]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


class _SpanIndex:
    """Which benchmark span covers a host time (spans do not nest)."""

    def __init__(self, spans: List[Tuple[str, float, float]]):
        self.spans = sorted(spans, key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t <= self.spans[i][2]:
            return self.spans[i][0]
        return OTHER

    def most_overlap(self, a: float, b: float) -> str:
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        best, best_t = OTHER, 0.0
        while i < len(self.spans) and self.spans[i][1] < b:
            name, s, e = self.spans[i]
            ov = min(b, e) - max(a, s)
            if ov > best_t:
                best, best_t = name, ov
            i += 1
        return best


def reduce(path: str, span_names: Iterable[str]) -> Trace:
    """Read the trace at ``path``; ``span_names`` are the benchmark's spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(path))
    names = set(span_names)
    spans: List[Tuple[str, float, float]] = []
    dispatches: List[float] = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        spans.append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name == DISPATCH:
                        dispatches.append(e.start_ns * 1e-9)
    spans.sort(key=lambda s: s[1])
    dispatches.sort()
    if not spans:
        raise ValueError(f"no benchmark span ({sorted(names)}) in {path}")
    index = _SpanIndex(spans)

    chips = []
    for plane in sorted(device_planes, key=lambda p: int(p.name.rsplit(":", 1)[1])):
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        mods = sorted(lines.get("XLA Modules", []), key=lambda e: e.start_ns)
        # Runs and dispatches pair up in order when the trace holds them all.
        offset = None
        if mods and len(mods) == len(dispatches):
            offset = min(m.start_ns * 1e-9 - d for m, d in zip(mods, dispatches))
        shift = offset or 0.0
        modules = [Module(m.name, m.start_ns * 1e-9 - shift, m.duration_ns * 1e-9,
                          index.at(dispatches[i]) if offset is not None
                          else index.at(m.start_ns * 1e-9 - shift))
                   for i, m in enumerate(mods)]
        starts = [m.start for m in modules]
        ops = []
        for e in lines.get("XLA Ops", []):
            start = e.start_ns * 1e-9 - shift
            i = bisect.bisect_right(starts, start) - 1
            span = modules[i].span if i >= 0 else OTHER
            name, opcode, result = _parse_op(e.name)
            ops.append(Op(name, opcode, result, start, e.duration_ns * 1e-9, span))
        chips.append(Chip(plane.name, ops, modules, offset))
    if not chips:
        raise ValueError(f"no TPU device plane in {path}")
    # from the first span to the end of the last span or of the last op:
    # work dispatched asynchronously runs on after its span has closed
    w0 = spans[0][1]
    w1 = max([s[2] for s in spans] + [o.start + o.dur for c in chips for o in c.ops])
    for chip in chips:
        busy = _union((max(o.start, w0), min(o.start + o.dur, w1))
                      for o in chip.ops if o.start + o.dur > w0 and o.start < w1)
        chip.busy_s = sum(b - a for a, b in busy)
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        chip.gaps = [(a, b, index.most_overlap(a, b))
                     for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return Trace(chips, spans, (w0, w1))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 2:
        print(__doc__)
        return 2
    t = reduce(argv[0], argv[1:])
    print(f"window {t.window_s:.6f} s, busy {t.busy_s:.6f} s, idle {100 * t.idle_share:.3f}%, "
          f"offsets {[c.offset for c in t.chips]}")
    for name, s in t.top_ops():
        print(f"  op   {s:.6f}  {name}")
    for name, s in t.idle_by_span():
        print(f"  idle {s:.6f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
