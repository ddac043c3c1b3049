"""The trace reduction, on a trace recorded on one TPU v5e chip: three rounds
of two small programs (a fused matmul and the flash-attention kernel) under
``serve.prefill``, each followed by a 2 ms host sleep under ``serve.readback``."""
from pathlib import Path

import pytest

import trace_reduce

TRACE = Path(__file__).resolve().parent / "testdata" / "serve_tiny.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce(str(TRACE), ["serve.prefill", "serve.readback"])


def test_structure(trace):
    assert len(trace.chips) == 1
    chip = trace.chips[0]
    assert len(chip.modules) == 6 and len(chip.ops) == 12
    assert [s[0] for s in trace.spans] == ["serve.prefill", "serve.readback"] * 3
    assert chip.offset is not None


def test_runs_fall_in_the_span_that_dispatched_them(trace):
    chip = trace.chips[0]
    assert {m.span for m in chip.modules} == {"serve.prefill"}
    spans = [s for s in trace.spans if s[0] == "serve.prefill"]
    for m in chip.modules:  # after the offset, each run starts inside a prefill span
        assert any(a <= m.start <= b for _, a, b in spans)


def test_busy_is_the_union_of_ops_in_the_window(trace):
    ops = sorted((o.start, o.start + o.dur) for o in trace.chips[0].ops)
    merged = []
    for a, b in ops:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    assert trace.busy_s == pytest.approx(sum(b - a for a, b in merged), rel=1e-9)
    assert 0 < trace.busy_s < trace.window_s
    gaps = sum(b - a for a, b, _ in trace.chips[0].gaps)
    assert gaps + trace.busy_s == pytest.approx(trace.window_s, rel=1e-9)


def test_idle_is_named_by_what_the_host_did(trace):
    rows = trace.idle_by_span()
    assert rows[0][0].startswith("serve.readback")
    assert rows[0][1] >= 3 * 0.002  # the three sleeps


def test_kernel_ops_by_opcode_and_shape(trace):
    kernel = trace.ops(opcode="custom-call")
    assert len(kernel) == 3 and all(o.dur > 0 and o.result.startswith("bf16[2,256,128]")
                                    for o in kernel)
    assert trace.ops(kernel="flash_attention") == []  # recorded before kernels had names
    assert trace.module_seconds("serve.prefill") > sum(o.dur for o in kernel)
    assert trace.top_ops(1)[0][0].startswith("serve.prefill:custom-call")


@pytest.mark.parametrize("text,want", [
    ("%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kLoop",
     ("fusion.3", "fusion", "bf16[8,128]{1,0}")),
    ("%closed_call.2 = (bf16[128,1024,64]{2,1,0}, f32[128,64,64]{2,1,0}) custom-call(bf16[1] %a)",
     ("closed_call.2", "custom-call", "(bf16[128,1024,64]{2,1,0}, f32[128,64,64]{2,1,0})")),
])
def test_parse_op(text, want):
    assert trace_reduce._parse_op(text) == want


def test_top_ops_name_a_named_kernel():
    """A custom call named by ``pallas_call(name=...)`` is labelled by that
    name; an unnamed one, and every other op, by opcode and result type."""
    def op(name, opcode, result, dur):
        return trace_reduce.Op(name, opcode, result, 0.0, dur, "serve.prefill")

    ops = [op("wkv6.6", "custom-call", "(bf16[64,32,64]{2,1,0}, f32[64,64,64]{2,1,0})", 3.0),
           op("wkv6.6", "custom-call", "(bf16[64,32,64]{2,1,0}, f32[64,64,64]{2,1,0})", 3.0),
           op("_unknown_.1", "custom-call", "bf16[2,256,128]{2,1,0}", 2.0),
           op("custom-call.3", "custom-call", "bf16[2,2048]{1,0}", 1.5),
           op("fusion.3", "fusion", "bf16[8,128]{1,0}", 1.0)]
    trace = trace_reduce.Trace([trace_reduce.Chip("/device:TPU:0", ops, [], None)], [], (0, 10))
    assert trace.top_ops() == [["serve.prefill:wkv6", 6.0],
                               ["serve.prefill:custom-call bf16[2,256,128]", 2.0],
                               ["serve.prefill:custom-call bf16[2,2048]", 1.5],
                               ["serve.prefill:fusion bf16[8,128]", 1.0]]
    assert [o.kernel for o in ops] == ["wkv6", "wkv6", None, None, None]
    assert trace.ops(kernel="wkv6") == ops[:2]


def test_union_merges_overlaps():
    assert trace_reduce._union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
