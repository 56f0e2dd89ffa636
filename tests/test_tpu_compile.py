"""The Pallas kernels of the pod path compiled for a TPU v5e at the widths
the benchmark serves them at, with no chip: the chip's compiler is installed
here and compiles for a described device.  It proves "compiles" (tiling,
VMEM), never "is right" or "is fast".  One file, and the topology only inside
a fixture: a worker that cannot describe it skips these tests and no other
(the `on-chip-measurement` guide, section 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_d_kv_cache_manager_tpu.ops import flash_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

H, HKV, DH, BLOCK = 32, 4, 128, 16  # the afmoe cell's attention widths


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("tq, tk, q_offset, window", (
    (12800, 12800, 0, None),  # a miss prefill's full layer
    (12800, 12800, 0, 2048),  # ... and its sliding layers
    (512, 12800, 12288, None),  # a hit prefill's full layer
    (512, 2560, 2048, 2048),  # ... its sliding layers: the window's blocks
))
def test_flash_kernel_compiles_at_the_served_shapes(one_chip, tq, tk,
                                                    q_offset, window):
    bf16 = jnp.bfloat16
    compile_for(one_chip, functools.partial(
        flash_pallas.flash_gqa_attention_pallas, q_offset=q_offset,
        window=window), ((1, tq, H, DH), bf16), ((1, tk, HKV, DH), bf16),
        ((1, tk, HKV, DH), bf16))


@pytest.mark.parametrize("slots, columns, windowed", (
    (65536, 832, False),  # the full layer's logical table
    (16384, 129, True),  # a sliding layer's window table
))
def test_paged_decode_kernel_compiles_heads_first(one_chip, slots, columns,
                                                  windowed):
    i32, B = jnp.int32, 64
    fn = functools.partial(paged_decode_attention_pallas, heads_first=True,
                           blocks_per_step=32)
    shapes = [((B, H, DH), jnp.bfloat16),
              ((slots, 2, HKV, BLOCK, DH), jnp.bfloat16),
              ((B, columns), i32), ((B,), i32)]
    if windowed:
        compile_for(one_chip, lambda q, kv, t, c, s: fn(q, kv, t, c, start=s),
                    *shapes, ((B,), i32))
    else:
        compile_for(one_chip, fn, *shapes)
