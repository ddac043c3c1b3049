"""Each per-layer metric's reader on the recorded trace (testdata/): a
number where the trace holds what it reads, nothing where it does not."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import trace_reduce

TRACE = Path(__file__).resolve().parent / "testdata" / "serve_tiny.xplane.pb"
PEAKS = harness.peaks("TPU v5 lite")
# the recorded kernel: causal flash attention, B*H = 2, S = 256, dh = 128
KERNEL = {"result_prefix": "bf16[2,256,128]", "calls": 1, "flops": 2 * 2 * 128 * 256 * 257,
          "bytes": 2 * 4 * 2 * 256 * 128}


@pytest.fixture(scope="module")
def view():
    trace = trace_reduce.reduce(
        str(TRACE), harness.load_module("drivers", "serve_static").Driver.SPANS)
    return SimpleNamespace(trace=trace, peaks=PEAKS, cell=None,
                           facts={"kernels": {"flash_attention": KERNEL},
                                  "traced_flops": 3 * KERNEL["flops"]})


def read(name, view):
    return harness.load_module("metrics", name).read(view)


def test_idle(view):
    for name in ("idle_pct.serve", "idle_pct.train"):
        assert read(name, view) == pytest.approx(100 * view.trace.idle_share)
        assert 0 < read(name, view) < 100


def test_roofline_share_is_a_share(view):
    share = read("flash_attention_roofline", view)
    least = max(KERNEL["flops"] / PEAKS["bf16_flops_per_s"],
                KERNEL["bytes"] / PEAKS["hbm_bytes_per_s"])
    busy = sum(o.dur for o in view.trace.ops(opcode="custom-call"))
    assert share == pytest.approx(100 * 3 * least / busy)
    assert 0 < share <= 100


def test_absent_work_reads_nothing(view):
    assert read("wkv6_roofline", view) is None  # no WKV6 kernel in this trace
    assert read("decode_step_ms", view) is None  # no decode step either


def test_mfu(view):
    want = 100 * view.facts["traced_flops"] / (view.trace.window_s * PEAKS["bf16_flops_per_s"])
    assert read("serve_mfu_pct", view) == pytest.approx(want)
