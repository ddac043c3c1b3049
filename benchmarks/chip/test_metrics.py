"""Each per-layer metric's reader on the recorded traces (testdata/): a
number where the trace holds what it reads, nothing where it does not."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import trace_reduce

HERE = Path(__file__).resolve().parent
TRACE = HERE / "testdata" / "serve_tiny.xplane.pb"
SCOPED = HERE / "testdata" / "scoped_tiny.xplane.pb"
PEAKS = harness.peaks("TPU v5 lite")
# the recorded kernel, unnamed (recorded before kernels had names): causal
# flash attention, B*H = 2, S = 256, dh = 128
KERNEL = {"calls": 1, "flops": 2 * 2 * 128 * 256 * 257, "bytes": 2 * 4 * 2 * 256 * 128}
# the named kernels of scoped_tiny (record_scoped_tiny.py, two layers): the
# configuration, the prefill's batch and length, and the result type by
# which roofline.py found each kernel before it found it by name
RECORDED = {"flash_attention": ("olmo-1b", 8, 128, "bf16[128,128,128]"),
            "wkv6": ("rwkv6-1.6b", 2, 32, "(bf16[64,32,64]")}


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


@pytest.fixture(scope="module")
def scoped():
    """The chips' ops of scoped_tiny.xplane.pb, which keeps no host plane
    (so no span names them)."""
    from jax.profiler import ProfileData

    chips = []
    for plane in ProfileData.from_file(str(SCOPED)).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [trace_reduce.Op(*trace_reduce._parse_op(e.name), e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9, trace_reduce.OTHER)
                   for line in plane.lines if line.name == "XLA Ops" for e in line.events]
            chips.append(trace_reduce.Chip(plane.name, ops, [], None))
    return trace_reduce.Trace(chips, [], (0.0, 1.0))


def by_shape(trace, kernel):
    return [o for o in trace.ops(opcode="custom-call") if o.result.startswith(RECORDED[kernel][3])]


@pytest.mark.parametrize("kernel", sorted(RECORDED))
def test_kernel_found_by_name_as_by_its_shape(scoped, kernel):
    ops = scoped.ops(kernel=kernel)
    assert len(ops) == 2 and ops == by_shape(scoped, kernel)  # two layers


def test_roofline_share_is_a_share(scoped):
    for kernel, (config, batch, prompt_len, _) in RECORDED.items():
        conf = dict(harness.load_json(HERE / "configs" / f"{config}.json"), num_hidden_layers=2)
        k = harness.load_module("counts", conf["reference"]).kernels(conf, batch, prompt_len)
        view = SimpleNamespace(trace=scoped, peaks=PEAKS, cell=None, facts={"kernels": k})
        share = read(f"{kernel}_roofline", view)
        least = max(k[kernel]["flops"] / PEAKS["bf16_flops_per_s"],
                    k[kernel]["bytes"] / PEAKS["hbm_bytes_per_s"])
        ops = by_shape(scoped, kernel)
        assert share == pytest.approx(100 * len(ops) * least / sum(o.dur for o in ops))
        assert 0 < share <= 100


def test_absent_work_reads_nothing(view):
    assert read("flash_attention_roofline", view) is None  # its kernel has no name
    assert read("wkv6_roofline", view) is None  # no WKV6 kernel in this trace
    assert read("decode_step_ms", view) is None  # no decode step either


def test_mfu(view):
    want = 100 * view.facts["traced_flops"] / (view.trace.window_s * PEAKS["bf16_flops_per_s"])
    assert read("serve_mfu_pct", view) == pytest.approx(want)
