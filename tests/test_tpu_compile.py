"""Compile the main path's kernels and the paged decode step for a TPU v5e
that is described, not attached: what the chip's compiler refuses (tiling,
unsupported primitives, VMEM or HBM overflow) fails here, on the CPU.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.  A compile passing here is not a chip run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels.abft_matmul import abft_matmul
from repro.kernels.thermal_stencil import thermal_stencil
from repro.models.model import Model
from repro.serve.engine import Engine

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


@pytest.mark.parametrize("m", [16, 92])
def test_thermal_stencil_red_black(one_chip, m):
    x = _spec((m, m), jnp.float32, one_chip)
    compiled = jax.jit(lambda T, P, d: thermal_stencil(
        T, P, d, g_lat=0.1, g_v_tamb=0.0, iters=4, phase=0,
        interpret=False)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_abft_matmul_qwen3_mlp(one_chip):
    """One qwen3-1.7b MLP matmul: 256 tokens x 2048 @ 2048 x 6144, int8."""
    m, k, n = 256, 2048, 6144
    compiled = jax.jit(lambda *a: abft_matmul(*a, interpret=False)).lower(
        _spec((m, k), jnp.int8, one_chip), _spec((k, n), jnp.int8, one_chip),
        _spec((m, n), jnp.uint32, one_chip),
        _spec((m, n), jnp.uint32, one_chip),
        _spec((33,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_paged_fused_decode_step_qwen3(one_chip):
    """The engine's paged fused step (K/V kept in the page pool) at
    qwen3-1.7b width, cut to 2 layers, with bf16 weights as served (float32
    weights would add their casts to the temporaries); 8 slots of 1024
    tokens."""
    model = Model(registry.get("qwen3-1.7b").replace(
        num_layers=2, param_dtype="bfloat16"))
    eng = Engine(model, None, batch_slots=8, max_len=1024,
                 prefill_chunk=256, paged=True, warmup=False)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    slots = _spec((8,), jnp.int32, one_chip)
    compiled = eng._fused.lower(
        on_chip(model.abstract_params()), on_chip(eng.mgr.pool),
        *on_chip(eng.mgr.tables()), _spec((8, 1), jnp.int32, one_chip),
        slots, slots, on_chip(eng.key)).compile()
    m = compiled.memory_analysis()
    # the pool is donated: the writes land in place (the device layout
    # pads the small pos_ids leaves, so aliased bytes >= nbytes)
    assert m.alias_size_in_bytes >= sum(
        x.nbytes for x in jax.tree_util.tree_leaves(eng.mgr.pool))
    # no buffer the size of the stack's logical K/V (2 layers x 8 slots x
    # 1024 positions x 4,096 B), which the gather/scatter step materialised
    assert m.temp_size_in_bytes < 2 * 8 * 1024 * 4096, m.temp_size_in_bytes
    _fits(compiled)


def test_paged_fused_decode_step_deepseek_v2(one_chip):
    """The engine's paged fused step for DeepSeek-V2 at published widths,
    cut to its dense layer and one expert layer holding 10 of the 160
    experts, bf16 weights as served, 16 slots of 2048 tokens: the latents
    stay in the page pool (donated, written in place) and the expert layer
    compiles its grouped matmuls."""
    model = Model(registry.get("deepseek-v2-236b").replace(
        num_layers=2, held_experts=10, param_dtype="bfloat16"))
    eng = Engine(model, None, batch_slots=16, max_len=2048,
                 prefill_chunk=256, paged=True, warmup=False)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    slots = _spec((16,), jnp.int32, one_chip)
    compiled = eng._fused.lower(
        on_chip(model.abstract_params()), on_chip(eng.mgr.pool),
        *on_chip(eng.mgr.tables()), _spec((16, 1), jnp.int32, one_chip),
        slots, slots, on_chip(eng.key)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        x.nbytes for x in jax.tree_util.tree_leaves(eng.mgr.pool))
    assert "ragged-dot" in compiled.as_text()
    _fits(compiled)
