"""Compile the Pallas kernels and phi3's decode step for a TPU v5e chip
that is described, not attached: the chip's compiler refuses here what
it would refuse on the chip (VMEM overflow, unaligned tiles, HBM).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler would otherwise log under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip cannot be read back
        # from the persistent cache: keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not compiled"


def test_flash_attention_compiles_phi3_width(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention_op

    qkv = _shape(one_chip, (1, 1024, 32, 96), jnp.bfloat16)
    _compile_kernel(lambda q, k, v: flash_attention_op(q, k, v, causal=True),
                    qkv, qkv, qkv)


def test_moe_gmm_compiles_granite_width(one_chip):
    from repro.kernels.moe_dispatch.ops import expert_ffn

    E, C, d, f = 32, 1280, 1024, 512
    buf = _shape(one_chip, (E, C, d), jnp.bfloat16)
    p = {"w1": _shape(one_chip, (E, d, f), jnp.bfloat16),
         "w3": _shape(one_chip, (E, d, f), jnp.bfloat16),
         "w2": _shape(one_chip, (E, f, d), jnp.bfloat16)}
    _compile_kernel(lambda b, p: expert_ffn(b, p, "swiglu", use_kernel=True),
                    buf, p)


def test_ssm_scan_compiles_falcon_mamba_width(one_chip):
    from repro.kernels.ssm_scan.ops import ssm_scan_op

    B, L, Di, N = 1, 2048, 8192, 16
    dA = _shape(one_chip, (B, L, Di, N), jnp.float32)
    C = _shape(one_chip, (B, L, N), jnp.float32)
    _compile_kernel(ssm_scan_op, dA, dA, C)


def test_phi3_decode_step_fits_one_chip(one_chip):
    """The served decode step at chip_smoke's shapes (4 slots, 1024
    positions) compiles and its arguments fit one chip."""
    from repro.configs import get_config
    from repro.models import model as MDL

    cfg = get_config("phi3-mini-3.8b")

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                            tree)

    params = place(jax.eval_shape(
        lambda: MDL.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(lambda: MDL.init_cache(cfg, 4, 1024)))
    batch = {"tokens": _shape(one_chip, (4, 1), jnp.int32),
             "cache_index": _shape(one_chip, (4,), jnp.int32)}
    compiled = jax.jit(
        lambda p, c, b: MDL.decode_step(p, cfg, c, b)).lower(
            params, cache, batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
