"""Compile the Mosaic kernels, and the engine's decode segment, for a
described TPU v5e at qwen1.5-0.5b widths.

Nothing runs: the TPU compiler that ships with libtpu compiles each kernel
for a chip that is described, not attached, and refuses what the chip
would refuse (VMEM overruns, unaligned tiles) -- faults that interpret
mode cannot show.  Shapes are decode (M=8) and prefill (M=512) GEMMs over
the model's K/N widths (d_model 1024, d_ff 2816, fused qkv 3072), and the
SWAR units over activation-sized operands.

The topology is described inside a module fixture, never at import time:
only one process may hold libtpu, and every test worker imports this
file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import (autotune, mul4, muladd2, packed_matmul,
                           quant_matmul, simd_add)
from test_decode_inplace import (assert_no_cache_copies,
                                 assert_sort_only_in_branches,
                                 segment_lowered)

GEMMS = [(8, 1024, 2816), (8, 2816, 1024), (512, 1024, 3072),
         (512, 2816, 1024)]


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no libtpu / no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT-compiled TPU program cannot be read back without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", GEMMS)
def test_quant_matmul_compiles(one_chip, m, k, n):
    blk = autotune.default_block("quant_matmul")
    _compile(lambda x, w: quant_matmul.quant_matmul_acc(
        x, w, block=blk, interpret=False),
        one_chip, ((m, k), jnp.int8), ((k, n), jnp.int8))


@pytest.mark.parametrize("m,k,n", GEMMS)
def test_packed_w4_matmul_compiles(one_chip, m, k, n):
    blk = autotune.default_block("packed_w4_matmul")
    _compile(lambda x, w: packed_matmul.packed_w4_matmul_acc(
        x, w, block=blk, interpret=False),
        one_chip, ((m, k), jnp.int8), ((k, n // 2), jnp.int8))


@pytest.mark.parametrize("rows,cols", [(8, 1024), (512, 2816)])
def test_simd_add_compiles(one_chip, rows, cols):
    _compile(lambda x, y: simd_add.simd_add(
        [x] * 4, [y] * 4, lane_bits=8, interpret=False),
        one_chip, ((rows, cols), jnp.int8), ((rows, cols), jnp.int8))


@pytest.mark.parametrize("rows,cols", [(8, 1024), (512, 2816)])
def test_muladd2_compiles(one_chip, rows, cols):
    blk = autotune.default_block("muladd2")
    shape = ((8, rows, cols), jnp.int8)
    _compile(lambda a, b, c: muladd2.muladd2(a, b, c, block=blk,
                                             interpret=False),
             one_chip, shape, shape, shape)


@pytest.mark.parametrize("rows,cols", [(8, 1024), (512, 2816)])
def test_mul4_compiles(one_chip, rows, cols):
    blk = autotune.default_block("mul4")
    _compile(lambda a, b: mul4.mul4_full32(a, b, block=blk,
                                           interpret=False),
             one_chip, ((4, rows, cols), jnp.int8), ((rows, cols), jnp.int8))


@pytest.fixture(scope="module")
def qwen_segment(one_chip):
    """The engine segment at qwen1.5-0.5b widths, 8 slots x 1024
    positions, compiled once for the described v5e."""
    cfg = dataclasses.replace(configs.get_config("qwen1.5-0.5b"),
                              attn_q_chunk=256)
    return cfg, segment_lowered(cfg, 8, 1024, one_chip).compile()


def test_segment_loop_has_no_cache_copies(qwen_segment):
    """The step loop holds no copy or fresh buffer of the whole stacked
    cache and no layer-sized select (test_decode_inplace.py), and the
    segment's temp stays under 2.5 GB (4.9 GB when the cache was the
    layer scan's xs/ys)."""
    cfg, compiled = qwen_segment
    assert_no_cache_copies(compiled.as_text(), cfg, 8, 1024)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_segment_loop_sorts_only_in_a_branch(qwen_segment):
    """The TPU compiler keeps the sampler's conditional: the step loop
    runs the [8, 151936] sort only inside a branch, so an all-greedy
    step skips it."""
    _, compiled = qwen_segment
    assert_sort_only_in_branches(compiled.as_text())
