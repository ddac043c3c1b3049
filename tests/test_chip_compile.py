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
