"""The scope reader: on a trace built here, whose numbers are known, and on
``testdata/scoped_tiny.xplane.pb``, recorded on one TPU v5e chip by
``record_scoped_tiny.py`` (OLMo-1B's prefill and two decode steps, then an
RWKV6-1.6B prefill of one WKV chunk, both at two layers)."""
import re
from pathlib import Path

import pytest

import harness
import scopes
from scopes import _under

TRACE = Path(__file__).resolve().parent / "testdata" / "scoped_tiny.xplane.pb"


@pytest.mark.parametrize("tf_op,path,backward", [
    ("jit(<unknown>)/decode/layers/while/body/closed_call/attn/kv_cache/dynamic_update_slice:",
     ("decode", "layers", "attn", "kv_cache"), False),
    ("jit(<unknown>)/prefill/layers/while/body/closed_call/attn/kv_cache/jit(_pad)/pad",
     ("prefill", "layers", "attn", "kv_cache"), False),
    ("jit(f)/train_step/transpose(jvp(loss))/layers/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/bsd,dhk->bshk/dot_general:", ("train_step", "loss", "layers",
                                                             "attn"), True),
    ("jit(f)/train_step/jvp(loss)/jit(log_softmax)/exp:", ("train_step", "loss"), False),
    ("jit(<lambda>)/dot_general:", (), False),
])
def test_scope_of(tf_op, path, backward):
    assert scopes.scope_of(tf_op) == (path, backward)


def _space(modules, ops):
    """One chip's XSpace: ``modules`` [(name, program id, start, dur)],
    ``ops`` [(HLO text, tf_op, program id, start, dur)], times in ps."""
    space = scopes._xspace_class()()
    plane = space.planes.add(id=1, name="/device:TPU:0")
    for sid, name in ((1, "tf_op"), (2, "program_id")):
        plane.stat_metadata[sid].id = sid
        plane.stat_metadata[sid].name = name
    lines = {n: plane.lines.add(name=n, timestamp_ns=1000) for n in ("XLA Modules", "XLA Ops")}
    mid = 0
    for name, pid, start, dur in modules:
        mid += 1
        plane.event_metadata[mid].name = f"{name}({pid})"
        lines["XLA Modules"].events.add(metadata_id=mid, offset_ps=start, duration_ps=dur)
    for text, tf_op, pid, start, dur in ops:
        mid += 1
        md = plane.event_metadata[mid]
        md.name = text
        if tf_op:
            md.stats.add(metadata_id=1, str_value=tf_op)
        md.stats.add(metadata_id=2, uint64_value=pid)
        lines["XLA Ops"].events.add(metadata_id=mid, offset_ps=start, duration_ps=dur)
    return space


def _run(t0, pid=7):
    """One run of a decode step at ``t0``: a layer scan holding the cache
    write, an inserted copy and the MLP, then the head."""
    d = "jit(<unknown>)/decode"
    return [
        ("%while.1 = (s32[]) while(%tuple.1)", f"{d}/layers/while:", pid, t0, 100),
        ("%fusion.1 = bf16[4,8]{1,0} fusion(%p.1)",
         f"{d}/layers/while/body/closed_call/attn/kv_cache/dynamic_update_slice:", pid, t0 + 10, 20),
        ("%copy.1 = bf16[4,8]{1,0} copy(%p.2)", "", pid, t0 + 30, 30),
        ("%fusion.2 = bf16[4,8]{1,0} fusion(%p.3)",
         f"{d}/layers/while/body/closed_call/mlp/dot_general:", pid, t0 + 60, 30),
        ("%fusion.3 = f32[4,16]{1,0} fusion(%p.4)", f"{d}/lm_head/dot_general:", pid, t0 + 100, 10),
        ("%fusion.4 = f32[4]{0} fusion(%p.5)", f"{d}/iota:", pid, t0 + 110, 4),
    ]


@pytest.fixture(scope="module")
def built():
    ops = _run(0) + _run(1000) + [
        ("%_unknown_.1 = bf16[4]{0} custom-call(%p.9)", "jit(<lambda>)/argmax:", 9, 2000, 6)]
    mods = [("jit_decode", 7, 0, 114), ("jit_decode", 7, 1000, 114), ("jit__lambda", 9, 2000, 6)]
    return scopes.Scopes(_space(mods, ops))


def test_built_programs_and_roots(built):
    assert built.chips == 1
    assert {pid: (p.runs, p.root) for pid, p in built.programs.items()} == {
        7: (2, "decode"), 9: (1, None)}
    assert not any(o.control for o in built.ops)  # the while's time is its children's


def test_built_inserted_copy_takes_the_scan_scope(built):
    copies = [o for o in built.ops if o.opcode == "copy"]
    assert len(copies) == 2
    assert all(o.inherited and o.path == ("decode", "layers") for o in copies)


def test_built_seconds_per_run(built):
    assert built.seconds("decode", "layers", "attn", "kv_cache") == pytest.approx(20e-12)
    assert built.seconds("decode", "layers", outside=scopes.SUBLAYERS) == pytest.approx(30e-12)
    assert built.seconds("decode") == pytest.approx(94e-12)
    assert built.seconds("train_step", "optimizer") is None


def test_built_coverage(built):
    # of 94 a run, the iota (4) is the root's alone and the copy (30) the scan's
    assert built.coverage() == pytest.approx(90 / 94)
    assert built.coverage(scopes.PARTLESS) == pytest.approx(60 / 94)
    assert "layers or root only" in built.table() and "kernel" not in built.table()


def test_a_trace_without_scopes_reads_nothing():
    none = scopes.Scopes(_space([("jit__lambda", 9, 0, 6)], [
        ("%fusion.1 = f32[4]{0} fusion(%p.1)", "jit(<lambda>)/dot_general:", 9, 0, 6)]))
    assert none.seconds("decode", "layers") is None and none.coverage() is None
    assert "names nothing" in none.table()


# -- the recorded trace ----------------------------------------------------------

KV_CACHE = re.compile(r"/decode/layers/.*/attn/kv_cache/")
WKV = re.compile(r"/prefill/layers/.*/time_mix/wkv/")


@pytest.fixture(scope="module")
def tiny():
    return scopes.load(str(TRACE))


def test_tiny_programs(tiny):
    assert tiny.chips == 1
    roots = sorted(p.root for p in tiny.programs.values() if p.root)
    assert roots == ["decode", "prefill", "prefill"]  # OLMo decode, OLMo and RWKV prefill
    assert [p.runs for p in tiny.programs.values() if p.root == "decode"] == [2]


def test_tiny_every_leaf_op_is_scoped_or_takes_its_enclosing_scope(tiny):
    """On the chip an inserted copy inside a loop carries the loop's op_name;
    those without one sit at the program's top level, under its root alone."""
    for o in tiny.ops:
        root = tiny.programs[o.program].root
        if root is None:
            continue
        assert o.path[:1] == (root,)
        if o.tf_op:
            assert not o.inherited
        else:
            assert o.inherited and "layers" in o.path or o.path == (root,)


def _per_run(tiny, pattern):
    ops = [o for o in tiny.ops if pattern.search(o.tf_op)]
    runs = sum(tiny.programs[pid].runs for pid in {o.program for o in ops})
    return 1e-12 * sum(o.dur_ps for o in ops) / runs


def test_tiny_kv_cache_and_wkv_are_the_sums_of_their_ops(tiny):
    assert tiny.seconds("decode", "layers", "attn", "kv_cache") == pytest.approx(
        _per_run(tiny, KV_CACHE), rel=1e-12)
    wkv = tiny.seconds("prefill", "layers", "time_mix", "wkv")
    assert wkv > 0 and wkv == pytest.approx(_per_run(tiny, WKV), rel=1e-12)


def test_tiny_named_kernels_found_without_counts(tiny):
    kernels = {o.kernel: o for o in tiny.ops if o.kernel}
    assert set(kernels) == {"flash_attention", "wkv6"}
    assert _under(kernels["flash_attention"].path, ("prefill", "layers", "attn"))
    assert _under(kernels["wkv6"].path, ("prefill", "layers", "time_mix", "wkv"))


def test_tiny_metric_readers(tiny, monkeypatch):
    monkeypatch.setattr(harness.Run, "trace_dir", TRACE)

    def read(name):
        return harness.load_module("metrics", name).read(None)

    cache = tiny.seconds("decode", "layers", "attn", "kv_cache")
    scan = tiny.seconds("decode", "layers", outside=scopes.SUBLAYERS)
    assert read("decode_kv_cache_ms") == pytest.approx(1e3 * (cache + scan))
    assert read("prefill_wkv_ms") == pytest.approx(
        1e3 * tiny.seconds("prefill", "layers", "time_mix", "wkv"))
    assert read("train_optimizer_ms") is None  # no training step in this trace
