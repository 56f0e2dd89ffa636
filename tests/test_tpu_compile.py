"""The Pallas kernels of the pod path, and the `llama` model-step programs,
compiled for a TPU v5e at the widths the benchmark serves them at, with no
chip: the chip's compiler is installed here and compiles for a described
device.  It proves "compiles" (tiling, VMEM) and what the compiler's output
says of copies and temporaries, never "is right" or "is fast".  One file, and
the topology only inside a fixture: a worker that cannot describe it skips
these tests and no other (the `on-chip-measurement` guide, section 2).
"""

from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from llm_d_kv_cache_manager_tpu.models import lfm2moe, llama
from llm_d_kv_cache_manager_tpu.models import pod as pod_programs
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


def paged_kernels(hlo: str) -> set:
    """The compiled program's kernels that the trace reduction counts as the
    paged decode kernel (`reduce.op_time_per_program`: the name holds it)."""
    return set(re.findall(
        r"%(\S*paged_decode_attention_pallas\S*) = .*tpu_custom_call", hlo))


def test_paged_decode_kernel_compiles_for_the_llama_pool(one_chip):
    """Slots [2, block, Hkv, Dh] as `llama._scan_layers` merges them: the
    chat cell's pool of 24 layers x 3072 blocks, 32 sequences, 192 table
    columns, at the served blocks a step, the shared pass (its strided reads
    by KV head among what must compile) and the walk.  Both take the pool
    where it lies: the [block * Hkv, Dh] view of a slot is a bitcast, and
    what is copied and kept beside is the plan's integers, a group's query
    rows and the shared pass's float32 results, a few megabytes."""
    i32, B = jnp.int32, 32
    compiled = compile_for(
        one_chip, paged_decode_attention_pallas,
        ((B, 16, DH), jnp.bfloat16),
        ((24 * 3072, 2, BLOCK, 8, DH), jnp.bfloat16),
        ((B, 192), i32), ((B,), i32))
    hlo = compiled.as_text()
    assert len(paged_kernels(hlo)) == 2
    assert not re.search(rf"= bf16\[{24 * 3072},\S* copy\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# ------------------------------------- the llama programs and their KV pool

# benchmarks/configs/mistral-7b-v0.3-l8.json and internlm2-1.8b.json; the
# pools of benchmarks/traffic/docs-*.json and chat-sysprompt.json.
MISTRAL = llama.LlamaConfig(vocab_size=32768, d_model=4096, n_layers=8,
                            n_heads=32, n_kv_heads=8, d_ff=14336,
                            rope_theta=1e6)
INTERNLM2 = llama.LlamaConfig(vocab_size=92544, d_model=2048, n_layers=24,
                              n_heads=16, n_kv_heads=8, d_ff=8192,
                              rope_theta=1e6)

INSTRUCTION = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")


def pool_sized_moves(hlo: str, pool_shape: tuple) -> list:
    """The instructions of a compiled program that move the whole pool or
    one layer of it: a `copy` or a `dynamic-slice` with such a result, a
    `dynamic-update-slice` with such an update.  (A scatter or a slice update
    whose RESULT is the pool writes the carried buffer where it lies.)"""
    L, N, *slot = pool_shape
    moved = [[L, N] + slot, [L * N] + slot, [1, N] + slot, [N] + slot]
    rows = INSTRUCTION.findall(hlo)
    shapes = {name: [int(d) for d in dims.split(",") if d]
              for name, dims, _, _ in rows}
    found = []
    for name, _, op, operands in rows:
        if op == "dynamic-update-slice":
            what = shapes.get(operands.split(",")[1].strip().lstrip("%"))
        elif op in ("copy", "dynamic-slice"):
            what = shapes[name]
        else:
            continue
        if what in moved:
            found.append(f"{op} {name} {what}")
    return found


SERVED = (  # name, widths, pool blocks, tokens, table, static prefix
    ("miss_prefill_T8448", MISTRAL, 4096, (1, 8448), (1, 528), None),
    ("hit_prefill_P8192_S256", MISTRAL, 4096, (1, 256), (1, 528), 8192),
    ("hit_prefill_P2048_S512", INTERNLM2, 3072, (1, 512), (1, 160), 2048),
    ("decode_B32", INTERNLM2, 3072, (32,), (32, 192), None),
)


@pytest.mark.parametrize("name, cfg, blocks, tokens, table, prefix", SERVED,
                         ids=[case[0] for case in SERVED])
def test_llama_programs_update_a_donated_pool_in_place(
        one_chip, monkeypatch, name, cfg, blocks, tokens, table, prefix):
    """The cells' programs, donated as `benchmarks/harness/pod.py` donates
    them: the compiler leaves no copy, slice or temporary the size of the
    pool, nor of one layer of it a layer (`llama._scan_layers`)."""
    # The prefill's attention asks for the backend (llama._prefill_attention):
    # as on the chip, so the miss prefill holds the flash kernel.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    i32 = jnp.int32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def last(logits, kv):
        return logits[:, -1], kv

    if name.startswith("miss"):
        def program(p, t, kv, bt):
            return last(*llama.prefill_paged(p, t, kv, bt, cfg))
    elif name.startswith("hit"):
        def program(p, t, kv, bt):
            return last(*llama.prefill_continue(p, t, kv, bt, prefix, cfg))
    else:
        def program(p, t, kv, bt, n):
            return llama.decode_step(p, t, kv, bt, n, cfg)
    pool_shape = (cfg.n_layers, blocks, 2, cfg.block_size, cfg.n_kv_heads,
                  cfg.head_dim)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(functools.partial(llama.init_params, cfg=cfg),
                       jax.random.key(0)))
    args = [params, spec(tokens, i32), spec(pool_shape, jnp.bfloat16),
            spec(table, i32)]
    if name.startswith("decode"):
        args.append(spec(tokens, i32))
    compiled = jax.jit(program, donate_argnums=(2,)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()

    hlo = compiled.as_text()
    # The miss prefill holds the flash kernel and the decode step the paged
    # kernel (llama.decode_step's rule); the hit prefills are XLA's alone.
    assert ("tpu_custom_call" in hlo) == (not name.startswith("hit"))
    # the shared pass and the walk, once in the layer scan's body
    assert len(paged_kernels(hlo)) == (2 if name.startswith("decode") else 0)
    assert pool_sized_moves(hlo, pool_shape) == []
    pool_bytes = 2 * math.prod(pool_shape)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool handed back
    assert memory.temp_size_in_bytes < pool_bytes


# --------------------------- the lfm2moe programs, K/V and state pools donated

# benchmarks/configs/lfm2-8b-a1b-l13.json and benchmarks/traffic/chat-agents.json
LFM2 = lfm2moe.Lfm2MoeConfig(
    vocab_size=65536, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=7168,
    d_expert=1792, n_experts=32, top_k=4, n_dense_layers=1,
    layer_types=("conv",) + ("full_attention", "conv", "conv", "conv") * 3,
    state_slots=2048, state_stride_blocks=16)
LFM2_SHAPES = {"miss": (8704,), "hit": (8192, 512), "decode": (64,),
               "max_blocks": 576}
# Temporaries a program may take beside 11.0 GB of weights and pools on a
# 15.75-GB chip.  The miss prefill's are its 8704 tokens' activations (the
# expert layer's 34 816 sorted rows) and the hit's the batched expert
# product's [32, 512, 1792] float32 (0.28 GB); the hit's and the decode
# step's held a copy of each 537-MB layer pool (2.3 and 1.1 GB) while slots
# were [2, Hkv, 16, 64]: the compiler made the slot axis the minor one.
LFM2_TEMP_LIMIT = {"miss": 2.6e9, "hit": 0.4e9, "decode": 0.1e9}


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_lfm2moe_programs_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                      key):
    """The cell `lfm2moe-chat-agents`' three programs as `models/pod.py`
    jits them, pools donated: they compile for the v5e (the flash kernel and
    the paged kernel at head size 64 among them), hand the pools back where
    they lie, and no instruction copies a layer's pool."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: lfm2moe.init_params(jax.random.key(0), LFM2)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: lfm2moe.new_pool(LFM2, 16384)))

    class Shapes:  # what `example_args` reads of a pod
        window = None

        class state:
            spec = lfm2moe.cache_groups(LFM2)["state"]

    first, second = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        pod_programs.example_args(key, LFM2_SHAPES, Shapes, BLOCK))
    program = pod_programs.inner_programs(lfm2moe, LFM2, LFM2_SHAPES,
                                          False)[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the shared pass and the walk in each of the three attention layers
    assert len(paged_kernels(hlo)) == (6 if key == "decode" else 0)
    slot = "16384,16,8,128"
    assert not re.search(rf"= bf16\[{slot}\]\S* copy\(", hlo)
    memory = compiled.memory_analysis()
    pool_bytes = sum(2 * math.prod(a.shape) for a in jax.tree.leaves(pools))
    assert memory.alias_size_in_bytes >= pool_bytes  # the pools handed back
    assert memory.temp_size_in_bytes < LFM2_TEMP_LIMIT[key]
