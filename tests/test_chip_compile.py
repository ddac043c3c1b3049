"""Compile-only checks for one TPU v5e chip, at the models' real widths.

The chip is described (``v5e:2x2``) and not attached: nothing runs, but the
TPU compiler refuses here what it would refuse on the chip (block shapes
Mosaic cannot tile, programs that exceed the chip's memory).  The topology
is described inside a module fixture, never at import: only one process
at a time may load the TPU library, and each pytest worker imports every
test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.rglru.kernel import rglru_scan
from repro.kernels.rwkv6.kernel import wkv6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded in this process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled, name: str) -> bool:
    """A Mosaic kernel named ``name``: the custom call takes the kernel's
    name, which is also the op's name in a device profile."""
    text = compiled.as_text()
    return "tpu_custom_call" in text and re.search(
        rf"%{name}(\.\d+)? = .*custom-call\(", text) is not None


FA_WIDTHS = {
    # arch: (B, S, kwargs from the config)
    "llama3.2-1b": (1, 2048, {}),
    "gemma2-9b": (1, 4096, {"window": 4096, "softcap": 50.0}),
}


@pytest.mark.parametrize("arch", sorted(FA_WIDTHS))
def test_flash_attention_compiles(arch, one_chip):
    cfg = get_config(arch)
    B, S, kw = FA_WIDTHS[arch]
    H, G, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    fn = functools.partial(flash_attention, **kw)
    c = _compile(fn, one_chip, ((B, H, S, dh), jnp.bfloat16),
                 ((B, G, S, dh), jnp.bfloat16), ((B, G, S, dh), jnp.bfloat16))
    assert _has_kernel(c, "flash_attention")


def test_wkv6_compiles(one_chip):
    cfg = get_config("rwkv6-1.6b")
    K = cfg.rwkv_head_dim
    H = cfg.d_model // K
    B, S = 1, 2048
    fn = functools.partial(wkv6, chunk=cfg.wkv_chunk)
    x = ((B, S, H, K), jnp.float32)
    c = _compile(fn, one_chip, x, x, x, x, ((H, K), jnp.float32))
    assert _has_kernel(c, "wkv6")


def test_rglru_compiles(one_chip):
    W = get_config("recurrentgemma-9b").lru_width
    B, S = 1, 2048
    c = _compile(rglru_scan, one_chip, ((B, S, W), jnp.float32), ((B, S, W), jnp.float32))
    assert _has_kernel(c, "rglru")


def test_llama_prefill_compiles_with_flash_kernel(one_chip, monkeypatch):
    """serve.py's prefill at full width, kernels on, as the chip compiles it."""
    from repro.kernels import config as kernels
    from repro.models import decode as dec
    from repro.models import init_params

    # the process sees the CPU, where dispatch would choose interpret mode
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    cfg = get_config("llama3.2-1b")
    B, P, N = 8, 1024, 64
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0)),
    )
    tokens = jax.ShapeDtypeStruct((B, P), jnp.int32, sharding=one_chip)
    with kernels.use_pallas(True):
        c = jax.jit(functools.partial(dec.prefill, cfg, capacity=P + N)).lower(
            params, tokens).compile()
    assert _has_kernel(c, "flash_attention")


def _chip_harness():
    """The on-chip benchmark's ``harness`` module (cells and their files)."""
    import importlib.util
    import sys
    from pathlib import Path

    if "chip_harness" not in sys.modules:
        path = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "harness.py"
        spec = importlib.util.spec_from_file_location("chip_harness", path)
        sys.modules["chip_harness"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules["chip_harness"])
    return sys.modules["chip_harness"]


VIEWS = ("parameter", "get-tuple-element", "bitcast")


def _computations(hlo: str):
    """Instruction lines of each computation of a compiled module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head:
            name = head[1]
            comps[name] = []
        elif name and re.match(r"\s*(?:ROOT )?%", line):
            comps[name].append(line)
    return comps


def _buffers_of_shape(hlo: str, shape: str):
    """For each buffer of array type ``shape`` (e.g. ``bf16[16,48,1024,16,128]``)
    that an instruction outside a fusion makes: the opcodes that make values
    of that shape in it, a fusion's taken from its fused computation.  A
    parameter, tuple element or bitcast makes no buffer of its own."""
    comps = _computations(hlo)
    fused = {c for lines in comps.values() for line in lines
             for c in re.findall(r"kind=k\w+, calls=%([\w.-]+)", line)}
    made = re.compile(r"\s*(?:ROOT )?%\S+ = " + re.escape(shape) + r"\{[^}]*\} ([\w-]+)\(")

    def makers(lines):
        for line in lines:
            m = made.match(line)
            if not m or m[1] in VIEWS:
                continue
            if m[1] == "fusion":
                yield from makers(comps[re.search(r"calls=%([\w.-]+)", line)[1]])
            else:
                yield m[1]

    return [(ops, line.strip()[:160]) for name, lines in comps.items() if name not in fused
            for line in lines for ops in [tuple(makers([line]))] if ops]


def test_olmo_decode_step_writes_its_cache_in_place(one_chip):
    """The chat cell's donated decode step (OLMo-1B at the configuration
    file's widths, B=48, KV capacity 1024), as the chip compiles it: only
    an in-place dynamic-update-slice makes a buffer of the stacked cache's
    shape, no layer's slice of it is copied out for the attention, and the
    program's temporaries stay under 1 GiB (one copy of the stacked K is
    3 GiB)."""
    from repro.models import decode as dec
    from repro.models import init_params

    harness = _chip_harness()
    cell = harness.cell("olmo-1b.serve.chat")
    cfg = harness.program_config(cell.config)
    B = cell.traffic["batch"]
    capacity = cell.traffic["prompt_len"] + cell.traffic["new_tokens"]
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(functools.partial(dec.init_caches, cfg, B, capacity)))
    step = jax.jit(functools.partial(dec.decode_step, cfg), donate_argnums=(1,))
    c = step.lower(params, caches, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
                   jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    hlo = c.as_text()
    k = caches[0][0]["k"]
    assert k.shape == (16, B, capacity, 16, 128)
    stack = f"bf16[{','.join(map(str, k.shape))}]"
    made = _buffers_of_shape(hlo, stack)
    assert made and all(ops == ("dynamic-update-slice",) for ops, _ in made), made
    for layer in (k.shape[1:], (1,) + k.shape[1:]):
        slice_ = f"bf16[{','.join(map(str, layer))}]"
        assert not _buffers_of_shape(hlo, slice_), slice_
    assert c.memory_analysis().temp_size_in_bytes < 2 ** 30
