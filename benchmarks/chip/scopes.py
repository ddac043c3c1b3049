"""Device time by the names the program gives its work.

    python benchmarks/chip/scopes.py <trace dir or .xplane.pb>

The program names its work with ``jax.named_scope``: a root scope per
program (``decode``, ``prefill``, ``train_step``), ``layers`` around each
layer scan, and inside them ``attn`` (with ``kv_cache``), ``mlp``,
``time_mix`` (with ``wkv``), ``channel_mix``, and so on; its Pallas kernels
are named by ``pallas_call(name=...)``.  The compiler keeps a scope in each
HLO op's ``op_name``, and the TPU profiler copies it into the metadata of
that op's events as the stat ``tf_op``, beside ``program_id``:
``jit(...)/decode/layers/while/body/closed_call/attn/kv_cache/dynamic_update_slice``.
``jax.profiler.ProfileData`` does not expose event metadata, so this module
decodes the XSpace protobuf itself, from the public field numbers of
``xplane.proto``.

For each ``/device:TPU:<n>`` plane it reads the ``XLA Ops`` line (one event
per executed HLO op) and the ``XLA Modules`` line (one event per program
run, named ``<module>(<program id>)``).  An op's scope is its ``tf_op`` path
with the wrappers JAX adds stripped: ``jit(...)`` components go, ``jvp(x)``
and ``transpose(x)`` become ``x`` (a ``transpose`` marks the backward pass),
and the control-flow frames (``while``, ``body``, ``closed_call``, ...) and
the final primitive are dropped.  An op with no ``tf_op`` -- a copy the
compiler inserted -- takes the scope of the innermost ``while``,
``conditional`` or ``call`` op on the same chip whose interval holds it.
Control-flow ops are not summed: their time is their children's.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import trace_reduce

ROOTS = ("decode", "prefill", "train_step")
# scopes of one layer's parts; ops under ``layers`` and none of these are the
# scan's own work (stacking its outputs, carrying its state)
SUBLAYERS = ("attn", "xattn", "mlp", "moe", "time_mix", "channel_mix", "recurrent")
CONTROL = ("while", "conditional", "call")
# the coverage that counts only work named beyond the layer scan
PARTLESS = ("layers",)
# name-stack frames JAX adds for control flow and calls, not scopes
_FRAMES = {"while", "body", "cond", "closed_call", "core_call", "checkpoint", "remat",
           "rematted_computation", "scan", "shard_map", "custom_jvp_call", "custom_vjp_call"}
_WRAPPER = re.compile(r"^(jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")
_CALL = re.compile(r"^p?jit\(.*\)$")
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*$")


# -- the XSpace protobuf, from xplane.proto's field numbers -------------------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, s, b, m = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(name="xplane_fields.proto", package="xplane",
                                            syntax="proto3")

    def message(name, fields, oneof=(), nested=()):
        msg = fd.message_type.add(name=name)
        msg.nested_type.extend(nested)
        if oneof:
            msg.oneof_decl.add(name="value")
        for fname, number, ftype, label, tname in fields:
            f = msg.field.add(name=fname, number=number, type=ftype, label=label)
            if tname:
                f.type_name = f".xplane.{tname}"
            if number in oneof:
                f.oneof_index = 0

    def map_entry(name, value):
        e = descriptor_pb2.DescriptorProto(name=name)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=i64, label=one)
        e.field.add(name="value", number=2, type=m, label=one, type_name=f".xplane.{value}")
        return e

    message("XStat", [("metadata_id", 1, i64, one, None), ("double_value", 2, F.TYPE_DOUBLE, one, None),
                      ("uint64_value", 3, u64, one, None), ("int64_value", 4, i64, one, None),
                      ("str_value", 5, s, one, None), ("bytes_value", 6, b, one, None),
                      ("ref_value", 7, u64, one, None)], oneof=(2, 3, 4, 5, 6, 7))
    message("XEvent", [("metadata_id", 1, i64, one, None), ("offset_ps", 2, i64, one, None),
                       ("num_occurrences", 5, i64, one, None), ("duration_ps", 3, i64, one, None),
                       ("stats", 4, m, many, "XStat")], oneof=(2, 5))
    message("XLine", [("id", 1, i64, one, None), ("display_id", 10, i64, one, None),
                      ("name", 2, s, one, None), ("display_name", 11, s, one, None),
                      ("timestamp_ns", 3, i64, one, None), ("duration_ps", 9, i64, one, None),
                      ("events", 4, m, many, "XEvent")])
    message("XEventMetadata", [("id", 1, i64, one, None), ("name", 2, s, one, None),
                               ("display_name", 4, s, one, None), ("metadata", 3, b, one, None),
                               ("stats", 5, m, many, "XStat"), ("child_id", 6, i64, many, None)])
    message("XStatMetadata", [("id", 1, i64, one, None), ("name", 2, s, one, None),
                              ("description", 3, s, one, None)])
    message("XPlane", [("id", 1, i64, one, None), ("name", 2, s, one, None),
                       ("lines", 3, m, many, "XLine"),
                       ("event_metadata", 4, m, many, "XPlane.EventMetadataEntry"),
                       ("stat_metadata", 5, m, many, "XPlane.StatMetadataEntry"),
                       ("stats", 6, m, many, "XStat")],
            nested=(map_entry("EventMetadataEntry", "XEventMetadata"),
                    map_entry("StatMetadataEntry", "XStatMetadata")))
    message("XSpace", [("planes", 1, m, many, "XPlane"), ("errors", 2, s, many, None),
                       ("warnings", 3, s, many, None), ("hostnames", 4, s, many, None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xplane.XSpace"))


def read_xspace(path: str):
    space = _xspace_class()()
    with open(trace_reduce.find_xplane(path), "rb") as f:
        space.ParseFromString(f.read())
    return space


def _stat(stat):
    kind = stat.WhichOneof("value")
    return getattr(stat, kind) if kind else None


# -- scopes -------------------------------------------------------------------

def scope_of(tf_op: str) -> Tuple[Tuple[str, ...], bool]:
    """(named scopes outermost first, whether in the backward pass) of an
    op's ``tf_op`` (its HLO ``op_name``, with the profiler's ``:<type>``)."""
    path, _, kind = tf_op.rpartition(":")
    if not path or "/" in kind:
        path = tf_op
    names, backward = [], False
    for part in path.split("/")[:-1]:  # the last part is the primitive
        while True:
            m = _WRAPPER.match(part)
            if not m:
                break
            backward |= m[1] == "transpose"
            part = m[2]
        if _CALL.match(part) or part in _FRAMES or not _NAME.match(part):
            continue
        names.append(part)
    return tuple(names), backward


def _under(path: Sequence[str], scope: Sequence[str]) -> bool:
    """``scope`` appears in ``path`` in order (frames may lie between)."""
    it = iter(path)
    return all(name in it for name in scope)


@dataclasses.dataclass
class Op:
    start_ps: int
    dur_ps: int
    name: str  # HLO instruction name, e.g. "fusion.3", "wkv6.1"
    opcode: str
    result: str
    tf_op: str
    program: int
    path: Tuple[str, ...] = ()
    backward: bool = False
    inherited: bool = False  # took the scope of its enclosing control-flow op

    @property
    def control(self) -> bool:
        return self.opcode in CONTROL

    @property
    def kernel(self) -> Optional[str]:
        """The Pallas kernel's name, for a named kernel's custom call."""
        return trace_reduce.kernel_name(self.name, self.opcode)

    def named(self, skip: Sequence[str] = ()) -> bool:
        """Under a scope below the root that is not in ``skip``, or a named kernel."""
        return any(p not in skip for p in self.path[1:]) or self.kernel is not None


@dataclasses.dataclass
class Program:
    name: str
    runs: int = 0  # summed over chips
    root: Optional[str] = None


class Scopes:
    """The leaf ops of a trace's TPU planes with their scopes, and the runs
    of each program."""

    def __init__(self, space):
        self.chips = 0
        self.programs: Dict[int, Program] = {}
        self.ops: List[Op] = []  # leaf ops, every chip
        for plane in space.planes:
            if plane.name.startswith("/device:TPU:"):
                self.chips += 1
                self._plane(plane)
        firsts: Dict[int, collections.Counter] = collections.defaultdict(collections.Counter)
        for o in self.ops:
            if o.path:
                firsts[o.program][o.path[0]] += o.dur_ps
        for pid, prog in self.programs.items():
            roots = [r for r, _ in firsts[pid].most_common() if r in ROOTS]
            prog.root = roots[0] if roots else None
        # jax.checkpoint hoists a layer's loop-invariant work out of the scan
        # with a name relative to the layer: it still belongs to the program
        for o in self.ops:
            root = self.programs[o.program].root if o.program in self.programs else None
            if root and o.path[:1] != (root,):
                o.path = (root,) + o.path

    def _plane(self, plane) -> None:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            stats = {stat_names.get(s.metadata_id): _stat(s) for s in md.stats}
            meta[k] = (md.name, str(stats.get("tf_op") or ""), int(stats.get("program_id") or 0))
        ops: List[Op] = []
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            if line.name == "XLA Modules":
                for e in line.events:
                    name = meta[e.metadata_id][0]
                    m = re.match(r"^(.*)\((\d+)\)$", name)
                    pid = int(m[2]) if m else 0
                    prog = self.programs.setdefault(pid, Program(m[1] if m else name))
                    prog.runs += 1
            elif line.name == "XLA Ops":
                for e in line.events:
                    text, tf_op, pid = meta[e.metadata_id]
                    name, opcode, result = trace_reduce._parse_op(text)
                    path, backward = scope_of(tf_op) if tf_op else ((), False)
                    ops.append(Op(base + e.offset_ps, e.duration_ps, name, opcode, result,
                                  tf_op, pid, path, backward))
        # an op with no tf_op takes the scope of the innermost control-flow op
        # holding it: parents sort before the children that start with them
        ops.sort(key=lambda o: (o.start_ps, -o.dur_ps))
        open_: List[Op] = []
        for o in ops:
            while open_ and open_[-1].start_ps + open_[-1].dur_ps <= o.start_ps:
                open_.pop()
            if not o.tf_op and open_ and o.start_ps + o.dur_ps <= open_[-1].start_ps + open_[-1].dur_ps:
                parent = open_[-1]
                o.path, o.backward, o.inherited = parent.path, parent.backward, True
            if o.control:
                open_.append(o)
        self.ops.extend(o for o in ops if not o.control)

    # -- queries ---------------------------------------------------------------
    def select(self, root: str, *scope: str, outside: Iterable[str] = ()) -> List[Op]:
        """Leaf ops under ``root`` and then ``scope`` (in order, not
        necessarily adjacent), and under none of ``outside``."""
        outside = set(outside)
        programs = {pid for pid, p in self.programs.items() if p.root == root}
        return [o for o in self.ops
                if o.program in programs and o.path[:1] == (root,)
                and _under(o.path[1:], scope) and not outside.intersection(o.path)]

    def seconds(self, root: str, *scope: str, outside: Iterable[str] = ()) -> Optional[float]:
        """Device seconds under ``root``/``scope``, per chip and per run of
        the programs that hold such ops (of every program that carries
        ``root`` where none does); None where no program carries ``root``."""
        programs = {pid for pid, p in self.programs.items() if p.root == root}
        if not programs:
            return None
        ops = self.select(root, *scope, outside=outside)
        runs = sum(self.programs[pid].runs for pid in {o.program for o in ops} or programs)
        return 1e-12 * sum(o.dur_ps for o in ops) / runs

    def coverage(self, skip: Sequence[str] = ()) -> Optional[float]:
        """Share of leaf-op time, in programs with a root scope, that falls
        under a scope below the root not in ``skip``, or in a named kernel;
        None where no program has a root scope."""
        scoped = {pid for pid, p in self.programs.items() if p.root}
        ops = [o for o in self.ops if o.program in scoped]
        total = sum(o.dur_ps for o in ops)
        return sum(o.dur_ps for o in ops if o.named(skip)) / total if total else None

    # -- report ----------------------------------------------------------------
    def table(self, rows: int = 24) -> str:
        """Per program with a root scope: ms per run by scope, the named
        kernels, the coverage and the largest ops left unscoped."""
        out = []
        for pid, prog in sorted(self.programs.items(), key=lambda kv: kv[1].root or ""):
            if not prog.root:
                continue
            runs = prog.runs
            ops = [o for o in self.ops if o.program == pid]
            by_scope: Dict[str, int] = collections.Counter()
            kernels: Dict[str, int] = collections.Counter()
            for o in ops:
                by_scope["/".join(o.path) + (" (backward)" if o.backward else "")] += o.dur_ps
                if o.kernel:
                    kernels[o.kernel] += o.dur_ps
            total = max(sum(by_scope.values()), 1)
            below = sum(o.dur_ps for o in ops if o.named())
            parts = sum(o.dur_ps for o in ops if o.named(PARTLESS))
            out.append(f"[scopes] program {prog.name} (root {prog.root}): {runs} runs, "
                       f"{1e-9 * total / runs:.4f} ms of leaf ops a run, "
                       f"{100.0 * below / total:.2f}% under a scope below the root, "
                       f"{100.0 * parts / total:.2f}% beyond layers")
            for scope, ps in by_scope.most_common(rows):
                out.append(f"[scopes]   {1e-9 * ps / runs:10.4f} ms  {scope or '(none)'}")
            for kernel, ps in kernels.most_common():
                out.append(f"[scopes]   {1e-9 * ps / runs:10.4f} ms  kernel {kernel}")
            left = collections.Counter()
            for o in ops:
                if not o.named(PARTLESS):
                    left[f"{o.opcode} {o.result.split('{')[0]} {o.tf_op or '(no tf_op)'}"] += o.dur_ps
            for what, ps in left.most_common(6):
                out.append(f"[scopes]   layers or root only {1e-9 * ps / runs:10.4f} ms  {what}")
        if not out:
            out.append("[scopes] no program carries a root scope "
                       f"({', '.join(ROOTS)}): the program names nothing")
        return "\n".join(out)


@functools.lru_cache(maxsize=2)
def _load(path: str, stamp: Tuple[int, int]) -> Scopes:
    t0 = time.perf_counter()
    found = Scopes(read_xspace(path))
    lines = [found.table()]
    below, parts = found.coverage(), found.coverage(PARTLESS)
    if below is not None:
        lines.append(f"[scopes] coverage of the leaf-op time of the programs with a root scope: "
                     f"{100.0 * below:.3f}% under a scope below the root, {100.0 * parts:.3f}% "
                     f"beyond layers (a layer's part, embed, lm_head, loss, optimizer) or in a "
                     f"named kernel")
    lines.append(f"[scopes] read {path} ({stamp[1]} bytes) and reduced it in "
                 f"{time.perf_counter() - t0:.3f} s of host time")
    print("\n".join(lines), file=sys.stderr, flush=True)
    return found


def load(path: Optional[str] = None) -> Scopes:
    """The scopes of the trace at ``path`` (default: the traced run's),
    parsed once per process; the first parse prints its table to stderr."""
    if path is None:
        import harness

        path = str(harness.Run.trace_dir)
    found = trace_reduce.find_xplane(path)
    st = os.stat(found)
    return _load(found, (st.st_mtime_ns, st.st_size))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__)
        return 2
    load(argv[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
