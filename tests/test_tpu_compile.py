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
    lane = MDL.lane_width(next(iter(one_chip.device_set)))
    cache = place(jax.eval_shape(lambda: MDL.init_cache(cfg, 4, 1024, lane)))
    batch = {"tokens": _shape(one_chip, (4, 1), jnp.int32),
             "cache_index": _shape(one_chip, (4,), jnp.int32)}
    compiled = jax.jit(
        lambda p, c, b: MDL.decode_step(p, cfg, c, b)).lower(
            params, cache, batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


def _holds_layer(dims, slots, T, KV) -> bool:
    """Whether a shape holds a whole layer's K or V: slots, T and KV
    among its dims, in any order and layout."""
    rest = list(dims)
    for d in (slots, T, KV):
        if d not in rest:
            return False
        rest.remove(d)
    return True


def _layer_sized_moves(hlo: str, slots: int, T: int, KV: int) -> list:
    """The ops of an optimized module that write a whole layer's or the
    whole cache's K/V: a copy or transpose whose result holds one, or a
    dynamic-update-slice or scatter whose UPDATE holds one (an in-place
    update's result is the whole buffer whatever it writes)."""
    import re

    define = re.compile(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* "
                        r"([\w-]+)\(([^)]*)\)")
    ops = []
    shapes = {}
    for line in hlo.splitlines():
        m = define.match(line)
        if m:
            name, dims, opcode, args = m.groups()
            shapes[name] = [int(d) for d in dims.split(",") if d]
            ops.append((name, opcode, re.findall(r"%(\S+?)(?:[,)\s]|$)",
                                                 args + ")")))
    update_arg = {"dynamic-update-slice": 1, "scatter": 2}
    moves = []
    for name, opcode, args in ops:
        if opcode in ("copy", "transpose"):
            dims = shapes[name]
        elif opcode in update_arg and len(args) > update_arg[opcode]:
            dims = shapes.get(args[update_arg[opcode]], [])
        else:
            continue
        if _holds_layer(dims, slots, T, KV):
            moves.append(f"{opcode} {name} {dims}")
    return moves


def test_phi3_step_programs_write_the_cache_in_place(one_chip):
    """phi3's decode (4 slots x 1024) and prefill (chunks of 32) programs,
    jitted as ``ContinuousBatcher`` jits them and with the cache sized for
    the described chip's lane width, write only the positions they
    produce: no op copies, transposes or writes back a whole layer's or
    the whole cache's K/V, and the donated cache is aliased to the
    output."""
    from repro.configs import get_config
    from repro.models import model as MDL
    from repro.serve.batcher import step_programs

    cfg = get_config("phi3-mini-3.8b")
    slots, T, chunk = 4, 1024, 32
    lane = MDL.lane_width(next(iter(one_chip.device_set)))
    assert lane == 128

    def place(tree):
        return jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype),
                            tree)

    params = place(jax.eval_shape(
        lambda: MDL.init_params(cfg, jax.random.PRNGKey(0))))
    cache = place(jax.eval_shape(
        lambda: MDL.init_cache(cfg, slots, T, lane)))
    assert cache["layers"]["k"].shape == (cfg.n_layers, slots,
                                          cfg.n_kv_heads, T, 128)
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    ints = lambda *s: _shape(one_chip, s, jnp.int32)  # noqa: E731
    decode_fn, prefill_fn = step_programs(cfg)
    for fn, batch in (
            (decode_fn, {"tokens": ints(slots, 1),
                         "cache_index": ints(slots)}),
            (prefill_fn, {"tokens": ints(slots, chunk),
                          "cache_index": ints(slots),
                          "count": ints(slots)})):
        compiled = fn.lower(params, cache, batch).compile()
        moves = _layer_sized_moves(compiled.as_text(), slots, T,
                                   cfg.n_kv_heads)
        assert not moves, moves
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes, (
            mem.alias_size_in_bytes, cache_bytes)
