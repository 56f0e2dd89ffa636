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
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from llm_d_kv_cache_manager_tpu.models import (
    afmoe, deepseekv32, glm4moelite, keyevl2, kv_cache_pool, lfm2moe, llama,
    nemotronh, phi4flash,
)
from llm_d_kv_cache_manager_tpu.models import pod as pod_programs
from llm_d_kv_cache_manager_tpu.ops import (
    flash_pallas, moe_decode_pallas, ssd_pallas,
)
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (
    latent_picked_prefill_pallas, latent_prefill_attention_pallas,
)
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)
from tests.helpers.jaxprs import equations

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
    """The walk over heads-first slots as `afmoe.decode_step` calls it: waves
    of `walk_wave`'s 16 blocks [2, Hkv, 16, 128], a KV head's keys of a wave
    [256, 128] with no re-layout, float32 operands, no shared pass."""
    i32, B = jnp.int32, 64
    fn = functools.partial(paged_decode_attention_pallas, heads_first=True,
                           mxu_native=False)
    shapes = [((B, H, DH), jnp.bfloat16),
              ((slots, 2, HKV, BLOCK, DH), jnp.bfloat16),
              ((B, columns), i32), ((B,), i32)]
    if windowed:
        compiled = compile_for(
            one_chip, lambda q, kv, t, c, s: fn(q, kv, t, c, start=s),
            *shapes, ((B,), i32))
    else:
        compiled = compile_for(one_chip, fn, *shapes)
    assert len(re.findall(r"= .*tpu_custom_call", compiled.as_text())) == 1


def paged_kernels(hlo: str) -> set:
    """The compiled program's kernels that the trace reduction counts as the
    paged decode kernel (`reduce.op_time_per_program`: the name holds it)."""
    return set(re.findall(
        r"%(\S*paged_decode_attention_pallas\S*) = .*tpu_custom_call", hlo))


WALKED = {  # name: sequences, table columns, query heads, the pool, packed
    # `llama._scan_layers` merges the chat cell's 24 layers x 3072 blocks
    "internlm2-1.8b": (32, 192, (16, DH), (24 * 3072, 2, BLOCK, 8, DH), False),
    # a layer's pool of `lfm2moe-chat-agents`, K and V side by side at 64
    "lfm2-8b-a1b-l13": (64, 576, (32, 64), (16384, BLOCK, 8, 2 * 64), True),
    # the full group of `phi4flash-reasoning-longgen`, ten pair-wise KV heads:
    # stored with a block's rows merged (ten is no multiple of the chip's
    # tile) and handed over with them apart, as `phi4flash._decode_attention`
    "phi-4-mini-flash-reasoning": (64, 416, (40, DH),
                                   (24576, 2, BLOCK * 10, DH), False),
    # the full group of `nemotron3nano-agents-reasoning`, two KV heads of 16
    # query heads each, a block's rows merged as `phi4flash`'s
    "nemotron-3-nano-30b-a3b-l9": (128, 736, (32, DH),
                                   (32768, 2, BLOCK * 2, DH), False),
}


@pytest.mark.parametrize("name", WALKED)
def test_paged_decode_kernel_compiles_for_the_walked_pools(one_chip, name):
    """The shared pass (its strided reads by KV head among what must compile)
    and the walk that copies a sequence's own blocks a wave at a time, at the
    three served slot layouts and the waves `walk_wave` gives them.  Both
    take the pool where it lies: the [block * Hkv, Dh] view of a slot is a
    bitcast, and what is copied and kept beside is the plan's integers, a
    group's query rows and the shared pass's float32 results, a few
    megabytes; the walk's two buffers of a wave are VMEM."""
    i32 = jnp.int32
    B, columns, heads, pool, packed = WALKED[name]

    def walked(q, kv, table, context_len):
        if kv.ndim == 4 and not packed:
            kv = kv.reshape(kv.shape[:2] + (BLOCK, -1, DH))
        return paged_decode_attention_pallas(q, kv, table, context_len,
                                             packed=packed)

    compiled = compile_for(
        one_chip, walked,
        ((B,) + heads, jnp.bfloat16), (pool, jnp.bfloat16),
        ((B, columns), i32), ((B,), i32))
    hlo = compiled.as_text()
    assert len(paged_kernels(hlo)) == 2
    assert not re.search(rf"= bf16\[{pool[0]},\S* copy\(", hlo)
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


def flash_kernels(hlo: str) -> set:
    """The compiled program's flash kernels by the name the trace reduction
    finds them under (`reduce.op_time_per_program`), less the instance
    number."""
    return {k.rsplit(".", 1)[0] for k in re.findall(
        r"%(flash_gqa_attention_pallas\S*) = .*tpu_custom_call", hlo)}


def gathered_prefix_moves(hlo: str, elements: int) -> list:
    """The instructions of a compiled hit prefill that make or move a copy of
    the cached prefix's K or V (``elements`` each, a layer): what the gather
    of the table's blocks, its select and the concatenation with the
    suffix's K/V lowered to, with the pads and re-layouts fused into them."""
    found = []
    for line in hlo.splitlines():
        row = INSTRUCTION.search(line)
        made_by = re.search(r'op_name="[^"]*/(\w+)"', line)
        if row and made_by and made_by.group(1) in (
                "gather", "select_n", "concatenate"):
            if math.prod(int(d) for d in row.group(2).split(",") if d) >= elements:
                found.append(f"{row.group(3)} {row.group(1)} [{row.group(2)}]")
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
    # Every program holds its kernel: a miss prefill the flash kernel, a hit
    # its continuation entry over the table's blocks where the pool holds
    # them (`llama.prefill_continue`), the decode step the paged kernel
    # (`llama.decode_step`'s rule).
    assert flash_kernels(hlo) == {
        "miss": {"flash_gqa_attention_pallas"},
        "hit": {"flash_gqa_attention_pallas_paged"},
        "decode": set()}[name.split("_")[0]]
    # the shared pass and the walk, once in the layer scan's body
    assert len(paged_kernels(hlo)) == (2 if name.startswith("decode") else 0)
    if prefix:  # 33.5 MB and 8.4 MB of K and V a layer that nothing copies
        assert gathered_prefix_moves(
            hlo, prefix * cfg.n_kv_heads * cfg.head_dim) == []
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
        window, decode_ahead = None, False

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


# ------------------- the phi4flash programs: full, window and state pools donated

# benchmarks/configs/phi-4-mini-flash-reasoning.json and
# benchmarks/traffic/reasoning-longgen.json
PHI4 = phi4flash.Phi4FlashConfig(
    vocab_size=200064, d_model=2560, n_layers=32, n_heads=40, n_kv_heads=20,
    d_ff=10240, window=512, d_state=16, d_conv=4, expand=2, dt_rank=160,
    window_slots=4608, window_store_blocks=64, state_slots=256,
    state_stride_blocks=32)
PHI4_SHAPES = {"miss": (1536,), "hit": (1024, 512), "decode": (64,),
               "max_blocks": 416}
PHI4_POOL_BLOCKS = 24576
# Temporaries a program may take beside 13.57 GB of weights and pools on a
# 15.75-GiB chip (16.9 GB): compiled here they read 0.33 / 0.18 / 0.14 GB.
# With slots [2, 16, 10, 128] the decode step held the window pool unpacked
# to the chip's tile (10 rows padded to 16: 4.5 GiB) and did not fit; with a
# view of the pool's rows apart around a prefill's scatter each prefill copied
# the pools it wrote (3.3 GB).
PHI4_TEMP_LIMIT = {"miss": 0.6e9, "hit": 0.4e9, "decode": 0.3e9}
HBM_BYTES = 15.75 * 2**30


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_phi4flash_programs_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                        key):
    """The cell `phi4flash-reasoning-longgen`'s three programs as
    `models/pod.py` jits them, the three groups' pools donated: they compile
    for the v5e (the flash kernel banded and whole, the paged kernel windowed
    and with its shared pass, at the pair-wise head size 128), fit the chip
    beside the weights, hand the pools back where they lie, and no
    instruction copies or re-lays-out a pool."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: phi4flash.init_params(jax.random.key(0), PHI4)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: phi4flash.new_pool(PHI4, PHI4_POOL_BLOCKS)))
    policy = phi4flash.cache_policy(PHI4)

    class Shapes:  # what `example_args` reads of a pod
        # a decode call's first argument is the pair: what the step before
        # served, where it lies on the device, and the integers
        decode_ahead = policy["decode_ahead"]

        class window:
            need = -(-(PHI4.window - 1) // BLOCK)
            width, store = need + 1, policy["window"]["store_blocks"]

        class state:
            spec = policy["specs"]["state"]

    first, second = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        pod_programs.example_args(key, PHI4_SHAPES, Shapes, BLOCK))
    if key == "decode":
        served, ints = first
        assert Shapes.decode_ahead and served.shape == (2, 64)
        assert ints.shape == (64, 2 + 1 + 33 + 2)
    else:
        assert first.shape == (1, PHI4_SHAPES[key][-1])
    program = pod_programs.inner_programs(phi4flash, PHI4, PHI4_SHAPES,
                                          False)[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the window layers' kernel in the first scan's body; the shared pass and
    # the walk of the full group in layer 17 and in the second scan's body
    assert len(paged_kernels(hlo)) == (5 if key == "decode" else 0)
    sizes = {a.shape[0] for a in jax.tree.leaves(pools)}
    assert sizes == {PHI4_POOL_BLOCKS, 8 * 4608, 9 * 256}
    for n in sizes:
        assert not re.search(rf"= \w+\[{n},[\d,]*\]\S* copy\(", hlo), n
    memory = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * math.prod(a.shape)
                     for a in jax.tree.leaves(pools))
    assert memory.alias_size_in_bytes >= pool_bytes  # the pools handed back
    assert memory.temp_size_in_bytes < PHI4_TEMP_LIMIT[key]
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < HBM_BYTES


# ------------------------------ the latent cache's kernels and programs

# benchmarks/configs/glm-4.7-flash-l5.json; the pool and the shapes of
# benchmarks/traffic/chat-repos.json
GLM = glm4moelite.Glm4MoeLiteConfig(
    vocab_size=154880, d_model=2048, n_layers=5, n_heads=20, q_rank=768,
    kv_rank=512, nope_dim=192, rope_dim=64, v_dim=256, d_ff=10240,
    d_expert=1536, n_experts=64, top_k=4)
GLM_SHAPES = {"miss": (16384,), "hit": (15872, 512), "decode": (64,),
              "max_blocks": 1056}
GLM_POOL_BLOCKS = 73728
# Temporaries beside 13.32 GB of weights and pool: compiled here they read
# 2.30 / 0.06 / 0.01 GB (a miss holds 16 384 positions' stream and a chunk's
# queries in both layouts).
GLM_TEMP_LIMIT = {"miss": 2.6e9, "hit": 0.2e9, "decode": 0.1e9}


def test_a_latent_slot_lies_in_the_pool_as_it_is_written(one_chip):
    """Slots [8, 1152] (two positions a row) are whole tiles: the pool takes
    its own bytes on the chip and a scatter writes it where it lies.  (As
    [16, 576] the compiler pads the lanes to 640 or makes the slot axis the
    minor one, and Mosaic refuses to slice it: PR 42.)"""
    spec = glm4moelite.cache_groups(GLM)["full"]
    shape = spec.layer_shape(8192)
    assert shape == (8192, 8, 1152)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (shape, jnp.bfloat16), ((32,), jnp.int32),
        ((32,) + shape[1:], jnp.bfloat16))]
    compiled = jax.jit(lambda pool, ids, new: pool.at[ids].set(new),
                       donate_argnums=(0,)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    nbytes = 2 * math.prod(shape)
    assert nbytes * spec.num_layers // 8192 == spec.block_nbytes
    assert compiled.memory_analysis().argument_size_in_bytes < 1.01 * nbytes
    assert "bf16[8192,8,1152]{2,1,0:" in compiled.as_text()


@pytest.mark.parametrize("tq, q_offset", ((512, 15872), (2048, 14336)))
def test_latent_prefill_kernel_compiles_at_the_served_shapes(one_chip, tq,
                                                             q_offset):
    """A hit's suffix over its cached prefix, and a chunk of a miss's
    queries: one kernel (the offset is data), the pool where it lies."""
    compiled = compile_for(
        one_chip, functools.partial(
            latent_prefill_attention_pallas, q_offset=q_offset,
            value_dim=512, scale=1 / 16,
            q_tile=glm4moelite.PREFILL_Q_TILE,
            blocks_per_step=glm4moelite.PREFILL_BLOCKS_PER_STEP),
        ((1, tq, 20, 576), jnp.bfloat16),
        ((GLM_POOL_BLOCKS, 8, 1152), jnp.bfloat16),
        ((1, 1024), jnp.int32))
    hlo = compiled.as_text()
    assert re.search(r"%latent_prefill_attention_pallas\S* = .*tpu_custom_call",
                     hlo)
    assert not re.search(rf"= bf16\[{GLM_POOL_BLOCKS},\S* copy\(", hlo)


def test_paged_decode_kernel_compiles_for_latent_slots(one_chip):
    """The shared pass and the walk in the latent form at the cell's shapes:
    20 heads (padded to whole sublanes) of 576 against a wave of blocks as one
    operand, the pool where it lies."""
    i32 = jnp.int32
    compiled = compile_for(
        one_chip, functools.partial(
            paged_decode_attention_pallas, latent=512, scale=1 / 16,
            walk_blocks_per_wave=glm4moelite.DECODE_BLOCKS_PER_WAVE,
            shared_blocks_per_step=glm4moelite.DECODE_BLOCKS_PER_WAVE),
        ((64, 20, 576), jnp.bfloat16),
        ((GLM_POOL_BLOCKS, 8, 1152), jnp.bfloat16), ((64, 1056), i32),
        ((64,), i32))
    hlo = compiled.as_text()
    assert len(paged_kernels(hlo)) == 2
    assert not re.search(rf"= bf16\[{GLM_POOL_BLOCKS},\S* copy\(", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_glm4moelite_programs_compile_at_the_cells_shapes(one_chip,
                                                          monkeypatch, key):
    """The cell `glm47flash-chat-repos`'s three programs as `models/pod.py`
    jits them, the pool of latent slots donated: they compile for the v5e
    (the latent prefill kernel a layer, the paged kernel's latent form with
    its shared pass), fit the chip beside the weights, hand the pool back
    where it lies, and no instruction copies or re-lays-out a layer of it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: glm4moelite.init_params(jax.random.key(0), GLM)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: glm4moelite.new_pool(GLM, GLM_POOL_BLOCKS)))

    class Shapes:  # what `example_args` reads of a pod: one group
        window = state = None
        decode_ahead = False

    first, second = jax.tree.map(
        spec, pod_programs.example_args(key, GLM_SHAPES, Shapes, BLOCK))
    assert first.shape == ((64, 2) if key == "decode"
                           else (1, GLM_SHAPES[key][-1]))
    program = pod_programs.inner_programs(glm4moelite, GLM, GLM_SHAPES,
                                          False)[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert len(paged_kernels(hlo)) == (2 * GLM.n_layers if key == "decode"
                                       else 0)
    assert len(set(re.findall(
        r"%(latent_prefill_attention_pallas\S*) = .*tpu_custom_call", hlo))
    ) == (0 if key == "decode" else GLM.n_layers)
    assert not re.search(rf"= \w+\[{GLM_POOL_BLOCKS},[\d,]*\]\S* copy\(", hlo)
    assert not pool_sized_moves(hlo, (GLM.n_layers, GLM_POOL_BLOCKS, 8, 1152))
    memory = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * math.prod(a.shape)
                     for a in jax.tree.leaves(pools))
    assert pool_bytes == GLM_POOL_BLOCKS * 92160
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool handed back
    assert memory.temp_size_in_bytes < GLM_TEMP_LIMIT[key]
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < HBM_BYTES


# --------------------- the selected cache's kernels and programs (PR 44)

# benchmarks/configs/keye-vl-2.0-30b-a3b-l4.json; the pool and the shapes of
# benchmarks/traffic/chat-longctx.json
KEYE = keyevl2.KeyeVl2Config(
    vocab_size=151936, d_model=2048, n_layers=4, n_heads=32, n_kv_heads=4,
    head_dim=128, index_heads=16, index_dim=64, index_topk=2048, d_expert=768,
    n_experts=128, top_k=8)
KEYE_SHAPES = {"miss": (32768,), "hit": (32256, 512), "decode": (24,),
               "max_blocks": 2080}
KEYE_POOL_BLOCKS = 51200
KEYE_SLOT = (17, 8, 128)
# the kept decode form's kernels (keyevl2._decode_attention)
KEYE_DECODE_KERNELS = {"sparse_decode_scores_pallas"}


def test_a_selected_slot_lies_in_the_pool_as_it_is_written(one_chip):
    """Slots [16 + 1, 8, 128] (a tile a position: 4 K heads' rows, then 4 V
    heads'; then a tile of the block's sixteen 64-lane selector keys, two a
    row) are whole tiles: the pool takes its own bytes on the chip, a scatter
    writes it where it lies, and a gather of single tiles reads it as it
    lies."""
    spec = keyevl2.cache_groups(KEYE)["full"]
    shape = spec.layer_shape(8192)
    assert shape == (8192,) + KEYE_SLOT
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (shape, jnp.bfloat16), ((32,), jnp.int32),
        ((32,) + shape[1:], jnp.bfloat16), ((24, 2048), jnp.int32))]

    def step(pool, ids, new, tiles):
        pool = pool.at[ids].set(new)
        return pool, kv_cache_pool.gather_picked_tiles(spec, pool, tiles)

    compiled = jax.jit(step, donate_argnums=(0,)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    nbytes = 2 * math.prod(shape)
    assert nbytes * spec.num_layers // 8192 == spec.block_nbytes == 139264
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 1.01 * nbytes
    assert memory.alias_size_in_bytes >= nbytes
    hlo = compiled.as_text()
    assert "bf16[8192,17,8,128]{3,2,1,0:" in hlo
    assert not re.search(r"= bf16\[8192,\S* copy\(", hlo)


def test_sparse_kernels_compile_at_the_served_shapes(one_chip):
    """A chunk's index scores over a whole table, the prefill kernel under
    the picks over the pool where it lies (a hit's suffix and a chunk of a
    miss's queries are one kernel: the offset is data), and a decode step's
    scores walked over each sequence's own table."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = ((KEYE_POOL_BLOCKS,) + KEYE_SLOT, bf16)
    compile_for(
        one_chip, lambda q, w, k, at: sparse.sparse_index_scores_pallas(
            q, w, k, q_offset=at),
        ((512, 16, 64), bf16), ((512, 16), f32), ((32768, 64), bf16),
        ((), i32))
    hlo = compile_for(
        one_chip, lambda q, p, t, m, at: sparse.sparse_prefill_attention_pallas(
            q, p, t, m, q_offset=at),
        ((1, 512, 32, 128), bf16), pool, ((1, 2048), i32),
        ((1, 512, 32768), jnp.bool_), ((), i32)).as_text()
    assert not re.search(rf"= bf16\[{KEYE_POOL_BLOCKS},\S* copy\(", hlo)
    hlo = compile_for(
        one_chip, functools.partial(sparse.sparse_decode_scores_pallas,
                                    selector_dim=64),
        ((24, 16, 64), bf16), ((24, 16), f32), pool, ((24, 2080), i32),
        ((24,), i32)).as_text()
    assert not re.search(rf"= bf16\[{KEYE_POOL_BLOCKS},\S* copy\(", hlo)


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_keyevl2_programs_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                      key):
    """The cell `keyevl2-chat-longctx`'s three programs as `models/pod.py`
    jits them, the pool of selected slots donated: they compile for the v5e,
    fit the chip beside 6.25 GB of weights and a 7.13-GB pool (a 32 768-token
    miss is the risk: its chunks make their own queries, and what it holds
    beside the pool the compiler trades against recomputing, down to 1.5 GB
    where the pool is larger), hand the pool back where it lies, and no
    instruction copies or re-lays-out a layer of it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: keyevl2.init_params(jax.random.key(0), KEYE)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: keyevl2.new_pool(KEYE, KEYE_POOL_BLOCKS)))

    class Shapes:  # what `example_args` reads of a pod: one group, and a
        # decode call that launches the step after its own (`decode_ahead`)
        window = state = None
        decode_ahead = True

    first, second = jax.tree.map(
        spec, pod_programs.example_args(key, KEYE_SHAPES, Shapes, BLOCK))
    assert jax.tree.map(lambda x: x.shape, first) == (
        ((2, 24), (24, 2)) if key == "decode"
        else (1, KEYE_SHAPES[key][-1]))
    program = pod_programs.inner_programs(keyevl2, KEYE, KEYE_SHAPES,
                                          False)[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    kernels = set(re.findall(r"%(sparse_\w+_pallas)\S* = .*tpu_custom_call",
                             hlo))
    assert kernels == (KEYE_DECODE_KERNELS if key == "decode" else {
        "sparse_index_scores_pallas", "sparse_prefill_attention_pallas"})
    assert not re.search(rf"= \w+\[{KEYE_POOL_BLOCKS},[\d,]*\]\S* copy\(", hlo)
    assert not pool_sized_moves(
        hlo, (KEYE.n_layers, KEYE_POOL_BLOCKS) + KEYE_SLOT)
    memory = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * math.prod(a.shape)
                     for a in jax.tree.leaves(pools))
    assert pool_bytes == KEYE_POOL_BLOCKS * 139264
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool handed back
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < HBM_BYTES


# ------------- the latent-selected cache's kernels and programs (PR 53)

# benchmarks/configs/deepseek-v3.2-exp-l5.json; the pool and the shapes of
# benchmarks/traffic/chat-longctx-shared.json
DSV32 = deepseekv32.DeepseekV32Config(
    vocab_size=16160, d_model=7168, n_layers=5, n_heads=128, q_rank=1536,
    kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, index_heads=64,
    index_dim=128, index_topk=2048, d_ff=18432, d_expert=2048, n_experts=256,
    held=(0, 16), top_k=8, n_group=8, topk_group=4, rope_original=4096)
DSV32_SHAPES = {"miss": (32768,), "hit": (32256, 512), "decode": (32,),
                "max_blocks": 2080}
DSV32_POOL_BLOCKS = 20480
DSV32_SLOT = (8, 1408)
# Temporaries beside 9.27 GB of weights and a 2.31-GB pool: compiled here
# they read 4.00 / 0.50 / 0.41 GB (a 32 768-token miss holds its stream
# twice and the stream's norm; its chunks make their own queries and add to
# their own piece of the stream).
DSV32_TEMP_LIMIT = {"miss": 4.3e9, "hit": 0.7e9, "decode": 0.6e9}


def test_a_latent_selected_slot_lies_in_the_pool_as_it_is_written(one_chip):
    """Slots [8, 1408] (a row two positions' latents, mirrored, 9 tiles, then
    their two selector keys, 2 tiles): the pool takes its own bytes on the
    chip, a prefill's scatter and a decode step's slice update write it where
    it lies, and the gathers of a table's keys and of picked rows read it as
    it lies (a gather whose slices START at the key lanes did not: it had the
    whole pool re-laid-out, slot axis minor)."""
    spec = deepseekv32.cache_groups(DSV32)["full"]
    shape = spec.layer_shape(DSV32_POOL_BLOCKS)
    assert shape == (DSV32_POOL_BLOCKS,) + DSV32_SLOT
    bf16, i32 = jnp.bfloat16, jnp.int32
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        (shape, bf16), ((1, 32), i32), ((1, 512, 576), bf16),
        ((1, 512, 128), bf16), ((32,), i32), ((32,), i32), ((32, 576), bf16),
        ((32, 128), bf16), ((1, 2048), i32), ((32, 2048), i32),
        ((32, 2048), jnp.bool_))]

    def step(pool, new, latent, key, ids, at, one, one_key, table, rows,
             second):
        pool = kv_cache_pool.write_blocks(spec, pool, new, latent, key)
        pool = kv_cache_pool.write_token(spec, pool, ids, at, one, one_key)
        return (pool, kv_cache_pool.gather_selector_keys(spec, pool, table),
                kv_cache_pool.gather_picked_latents(spec, pool, rows, second))

    compiled = jax.jit(step, donate_argnums=(0,)).trace(*args).lower(
        lowering_platforms=("tpu",)).compile()
    nbytes = 2 * math.prod(shape)
    assert nbytes * spec.num_layers // DSV32_POOL_BLOCKS \
        == spec.block_nbytes == 112640
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < 0.6e9  # the picked rows: 184 MB twice
    hlo = compiled.as_text()
    assert f"bf16[{DSV32_POOL_BLOCKS},8,1408]{{2,1,0:" in hlo
    assert not re.search(rf"= bf16\[{DSV32_POOL_BLOCKS},\S* copy\(", hlo)


def test_latent_selected_kernels_compile_at_the_served_shapes(one_chip):
    """A chunk's index scores at 64 heads of 128 over a whole table, the
    latent kernel under the picks over the pool where it lies at the tile of
    32 positions x 128 heads (a hit's suffix and a chunk of a miss's queries
    are one kernel: the offset is data), and a decode step's scores walked
    over each sequence's own table, the rows' key lanes copied alone."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = ((DSV32_POOL_BLOCKS,) + DSV32_SLOT, bf16)
    compile_for(
        one_chip, lambda q, w, k, at: sparse.sparse_index_scores_pallas(
            q, w, k, q_offset=at),
        ((512, 64, 128), bf16), ((512, 64), f32), ((32768, 128), bf16),
        ((), i32))
    hlo = compile_for(
        one_chip, lambda q, p, t, m, at: latent_picked_prefill_pallas(
            q, p, t, m, q_offset=at, value_dim=512, scale=0.135),
        ((1, 512, 128, 576), bf16), pool, ((1, 2048), i32),
        ((1, 512, 32768), jnp.bool_), ((), i32)).as_text()
    assert not re.search(rf"= bf16\[{DSV32_POOL_BLOCKS},\S* copy\(", hlo)
    hlo = compile_for(
        one_chip, functools.partial(sparse.latent_index_scores_pallas,
                                    latent_dim=576),
        ((32, 64, 128), bf16), ((32, 64), f32), pool, ((32, 2080), i32),
        ((32,), i32)).as_text()
    assert not re.search(rf"= bf16\[{DSV32_POOL_BLOCKS},\S* copy\(", hlo)


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_deepseekv32_programs_compile_at_the_cells_shapes(one_chip,
                                                          monkeypatch, key):
    """The cell `deepseekv32-chat-longctx-shared`'s three programs as
    `models/pod.py` jits them, the pool of latent-selected slots donated:
    they compile for the v5e, fit the chip beside 9.27 GB of weights and a
    2.31-GB pool (the 32 768-token miss is the risk), hand the pool back
    where it lies, and no instruction copies or re-lays-out a layer of it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: deepseekv32.init_params(jax.random.key(0), DSV32)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: deepseekv32.new_pool(DSV32, DSV32_POOL_BLOCKS)))

    class Shapes:  # what `example_args` reads of a pod: one group, and a
        # decode call that launches the step after its own (`decode_ahead`)
        window = state = None
        decode_ahead = True

    first, second = jax.tree.map(
        spec, pod_programs.example_args(key, DSV32_SHAPES, Shapes, BLOCK))
    program = pod_programs.inner_programs(deepseekv32, DSV32, DSV32_SHAPES,
                                          False)[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    kernels = set(re.findall(r"%(\w+_pallas)\S* = .*tpu_custom_call", hlo))
    # a decode step's 32 rows leave held experts untouched: its expert layers
    # are the kernel that copies the touched ones alone; a hit's 512 rows and
    # a miss's chunks of 1024 keep the einsum (`moe_serve.decode_kernel_serves`)
    assert kernels == ({"latent_index_scores_pallas", "moe_decode_pallas"}
                       if key == "decode" else {
        "sparse_index_scores_pallas", "latent_picked_prefill_pallas"})
    assert not re.search(
        rf"= \w+\[{DSV32_POOL_BLOCKS},[\d,]*\]\S* copy\(", hlo)
    assert not pool_sized_moves(
        hlo, (DSV32.n_layers, DSV32_POOL_BLOCKS) + DSV32_SLOT)
    memory = compiled.memory_analysis()
    pool_bytes = sum(a.dtype.itemsize * math.prod(a.shape)
                     for a in jax.tree.leaves(pools))
    assert pool_bytes == DSV32_POOL_BLOCKS * 112640
    assert memory.alias_size_in_bytes >= pool_bytes  # the pool handed back
    assert memory.temp_size_in_bytes < DSV32_TEMP_LIMIT[key]
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < HBM_BYTES


# -------------- the nemotronh programs: a state group heavier than the K/V pool

# benchmarks/configs/nemotron-3-nano-30b-a3b-l9.json and
# benchmarks/traffic/agents-reasoning.json
NEMO = nemotronh.NemotronHConfig(
    vocab_size=65536, d_model=2688, pattern="MEMEM*EME", n_heads=32,
    n_kv_heads=2, head_dim=128, mamba_heads=64, mamba_head_dim=64, n_groups=8,
    d_state=128, d_conv=4, chunk=128, d_expert=1856, d_shared=3712,
    n_experts=128, held=(0, 64), top_k=6, state_slots=452,
    state_stride_blocks=64)
NEMO_SHAPES = {"miss": (8704,), "hit": (8192, 512), "decode": (128,),
               "max_blocks": 736}
NEMO_POOL_BLOCKS = 32768
# Temporaries beside 6.33 GB of weights and 4.63 GB of pools (4.10 of them
# the state group's): compiled here they read 2.06 / 0.18 / 0.04 GB (a miss
# holds 8704 positions' projections of 10 304 lanes; a decode step that
# gathered its 128 sequences' states, 268 MB a Mamba-2 layer, read 1.65 GB:
# it advances them where they lie, `ssd_decode_step_pallas` on the pool
# aliased to its result).
NEMO_TEMP_LIMIT = {"miss": 2.4e9, "hit": 0.3e9, "decode": 0.2e9}
# kernels by the names the trace reduction finds them under: a call of the
# chunk scan a kept boundary a Mamba-2 layer (a miss keeps nine, a hit one)
NEMO_KERNELS = {
    "miss": {"flash_gqa_attention_pallas": 1, "ssd_chunk_scan_pallas": 36},
    "hit": {"flash_gqa_attention_pallas": 1, "ssd_chunk_scan_pallas": 4},
    "decode": {"paged_decode_attention_pallas": 2,
               "ssd_decode_step_pallas": 4},
}


@pytest.mark.parametrize("tokens", (512, 1024))
def test_ssd_kernel_compiles_at_the_served_shapes(one_chip, tokens):
    """The chunk scan of a hit's suffix and of a miss's stretch between two
    kept boundaries: a group's eight heads a grid step, x in bfloat16 with
    time in the lanes, the state float32 and resident over the chunks."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    compiled = compile_for(
        one_chip, functools.partial(ssd_pallas.ssd_chunk_scan_pallas,
                                    chunk=128),
        ((1, tokens, 64, 64), bf16), ((1, tokens, 64), f32), ((64,), f32),
        ((1, tokens, 8, 128), bf16), ((1, tokens, 8, 128), bf16),
        ((1, 64, 64, 128), f32))
    assert re.findall(r"%(ssd_chunk_scan_pallas)\S* = .*tpu_custom_call",
                      compiled.as_text())


def test_ssd_decode_kernel_compiles_and_advances_the_pool_in_place(one_chip):
    """A decode step's state update of one Mamba-2 layer at the cell's
    sizes: 128 rows over a pool of 452 slots [64, 64, 128] float32 (0.95
    GB), 8 groups.  One kernel under its own name, the pool donated and
    handed back where it lies: no instruction copies it and nothing of its
    size is a temporary.  And set-up pays for every equation of the traced
    call: the grid walks the rows, so 8 rows trace to what 128 do, and a
    slot's row blocks go a few an iteration of a rolled loop (179 equations
    at four; all 32 written out would be 1327)."""
    f32, i32 = jnp.float32, jnp.int32

    def shapes(rows, slots=452):
        return (((slots, 64, 64, 128), f32), ((rows,), i32), ((rows,), i32),
                ((rows, 64, 64), f32), ((rows, 64), f32), ((64,), f32),
                ((rows, 8, 128), f32), ((rows, 8, 128), f32))

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes(128)]
    compiled = jax.jit(
        ssd_pallas.ssd_decode_step_pallas, donate_argnums=(0,)).trace(
            *args).lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert len(re.findall(
        r"%(ssd_decode_step_pallas)\S* = .*tpu_custom_call", hlo)) == 1
    assert not re.search(r"= \w+\[452,[\d,]*\]\S* copy\(", hlo)
    memory = compiled.memory_analysis()
    pool_bytes = 452 * 64 * 64 * 128 * 4
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes // 64
    counts = {rows: equations(jax.make_jaxpr(
        ssd_pallas.ssd_decode_step_pallas)(
            *(jax.ShapeDtypeStruct(*s) for s in shapes(rows, 16))).jaxpr)
        for rows in (8, 32, 128)}
    assert len(set(counts.values())) == 1, counts
    assert counts[128] <= 256, counts


# a decode step's rows, the experts held, D, F, a gate matrix or none: the six
# expert configurations under benchmarks/configs/
MOE_DECODE = {
    "trinity-mini": (64, 128, 2048, 1024, True),
    "lfm2-8b-a1b": (64, 32, 2048, 1792, True),
    "glm-4.7-flash": (64, 64, 2048, 1536, True),
    "keye-vl-2.0": (24, 128, 2048, 768, True),
    "nemotron-3-nano": (128, 64, 2688, 1856, False),  # 14.5 lane tiles wide
    "deepseek-v3.2-exp": (32, 16, 7168, 2048, True),  # four tiles an expert
}


@pytest.mark.parametrize("name", MOE_DECODE)
def test_moe_decode_kernel_compiles_at_the_served_shapes(one_chip, name):
    """A decode step's expert layer as the kernel that copies the touched
    experts alone, at each expert configuration's widths: an expert's
    matrices whole where two buffers of them fit its VMEM share, tiles of
    the hidden width where they do not."""
    rows, held, D, F, gated = MOE_DECODE[name]
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    tile = moe_decode_pallas.hidden_tile(D, F, 2 + gated, 2)
    assert tile == (512 if name == "deepseek-v3.2-exp" else F)
    names = ("w_up", "w_down") + ("w_gate",) * gated

    def layer(x, weight, order, n, *matrices):
        return moe_decode_pallas.moe_decode_pallas(
            x, weight, dict(zip(names, matrices)), order, n)

    compiled = compile_for(
        one_chip, layer, ((rows, D), bf16), ((rows, held), f32),
        ((held,), i32), ((1,), i32), ((held, D, F), bf16),
        ((held, F, D), bf16), *((((held, D, F), bf16),) * gated))
    assert len(re.findall(r"%(moe_decode_pallas)\S* = .*tpu_custom_call",
                          compiled.as_text())) == 1


def test_flash_kernel_compiles_at_two_kv_heads(one_chip):
    """A hit prefill's 512 queries over 8704 keys, 16 query heads a KV head."""
    bf16 = jnp.bfloat16
    compile_for(one_chip, functools.partial(
        flash_pallas.flash_gqa_attention_pallas, q_offset=8192, window=None),
        ((1, 512, 32, DH), bf16), ((1, 8704, 2, DH), bf16),
        ((1, 8704, 2, DH), bf16))


@pytest.mark.parametrize("key", ("miss", "hit", "decode"))
def test_nemotronh_programs_compile_at_the_cells_shapes(one_chip, monkeypatch,
                                                        key):
    """The cell `nemotron3nano-agents-reasoning`'s three programs
    (`miss_prefill_T8704`, `hit_prefill_P8192_S512`, `decode_B128`) as
    `models/pod.py` jits them, both groups' pools donated: they compile for
    the v5e (the chunk scan, the flash kernel and the paged kernel with its
    shared pass at two KV heads), fit the chip beside the weights, hand the
    pools back where they lie, and the state group's arrays lie as they are
    written: the arguments weigh what the arrays weigh (a state [64, 64,
    128] float32 is whole tiles, a row of 3 x 6144 conv inputs whole lanes)
    and no instruction copies or re-lays-out one around a write."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: nemotronh.init_params(jax.random.key(0), NEMO)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: nemotronh.new_pool(NEMO, NEMO_POOL_BLOCKS)))
    policy = nemotronh.cache_policy(NEMO)

    class Shapes:  # what `example_args` reads of a pod
        window, decode_ahead = None, policy["decode_ahead"]

        class state:
            spec = policy["specs"]["state"]

    first, second = jax.tree.map(
        spec, pod_programs.example_args(key, NEMO_SHAPES, Shapes, BLOCK))
    if key == "decode":
        served, ints = first
        assert Shapes.decode_ahead and served.shape == (2, 128)
        assert ints.shape == (128, 2 + 2) and second.shape == (128, 736)
    else:
        assert first.shape == (1, NEMO_SHAPES[key][-1])
        assert second["state_write"].shape == (1, 9 if key == "miss" else 1)
    program = pod_programs.inner_programs(nemotronh, NEMO, NEMO_SHAPES,
                                          False)[key]
    assert program.__name__ == {
        "miss": "miss_prefill_T8704", "hit": "hit_prefill_P8192_S512",
        "decode": "decode_B128"}[key]
    compiled = program.trace(params, first, pools, second).lower(
        lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    kernels = re.findall(r"%(\w+_pallas)\S* = .*tpu_custom_call", hlo)
    assert {k: kernels.count(k) for k in set(kernels)} == NEMO_KERNELS[key]
    sizes = {a.shape[0] for a in jax.tree.leaves(pools)}
    assert sizes == {NEMO_POOL_BLOCKS, 452}
    for n in sizes:
        assert not re.search(rf"= \w+\[{n},[\d,]*\]\S* copy\(", hlo), n
    memory = compiled.memory_analysis()
    held = sum(a.dtype.itemsize * math.prod(a.shape)
               for a in jax.tree.leaves((params, pools)))
    pool_bytes = sum(a.dtype.itemsize * math.prod(a.shape)
                     for a in jax.tree.leaves(pools))
    assert pool_bytes == 452 * 8536064 + NEMO_POOL_BLOCKS * 16384
    # the arguments as they lie on the chip: the arrays' own bytes and the
    # call's integers, nothing padded to a tile
    assert held <= memory.argument_size_in_bytes < held + (4 << 20)
    assert memory.alias_size_in_bytes >= pool_bytes  # the pools handed back
    assert memory.temp_size_in_bytes < NEMO_TEMP_LIMIT[key]
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes
            ) < HBM_BYTES


# ---------------- the two-group pods, unmoved by the list of groups (PR 35)

# Read on commit 7f3671a (PR 34), where a pod had one group beside the full
# one: sha256 of str(jax.make_jaxpr(program)) of `lfm2moe`'s three programs
# (`afmoe`'s and `llama`'s are pinned in tests/test_lfm2moe_pod.py), and of
# every host-side array the two families' pods hand out over the scripted
# run below.  A PR that changes one on purpose reads its digest anew:
# `lfm2moe.decode.True` in PR 41 (the interpreted step holds the paged
# kernel, whose walk became a sequence a grid step with its own copies) and
# in PR 43 (a wave of the walk that is a run in the pool comes by one copy,
# and the step counts `run_blocks`) and in PR 52 (a step of `packed` slots is
# one operand, the shared pass brings a step that is a run by one copy, and
# the step counts `shared_run_blocks`).
AT_PR_34 = {
    "afmoe.tables": "66348e6e9d5f9ed8", "lfm2moe.tables": "a43cc9a2ce3fa258",
    "lfm2moe.miss.False": "6b66837c74b57be8",
    "lfm2moe.hit.False": "a0f712e977db715f",
    "lfm2moe.decode.False": "4855b16f775d4a8e",
    "lfm2moe.miss.True": "6b66837c74b57be8",
    "lfm2moe.hit.True": "a0f712e977db715f",
    "lfm2moe.decode.True": "d4b822376274fd6c",
}


def host_tables(module, cfg) -> str:
    """Five prompts of six blocks with two of an answer each through a pool of
    40, four decode steps each, every second sequence ended, then a hit:
    everything the pod hands out or keeps on the host, digested."""
    pod = pod_programs.Pod("p", module, cfg, 40)
    seen = hashlib.sha256()

    def note(x):
        for leaf in jax.tree.leaves(x):
            a = np.asarray(leaf)
            seen.update(str(a.dtype).encode() + str(a.shape).encode()
                        + a.tobytes())

    chains = []
    for doc in range(5):
        hashes = [1000 * doc + i for i in range(6)]
        found = pod.cached_prefix(hashes[:4])
        ids, evicted = pod.alloc(6)
        note((found, ids, evicted))
        pod.hold(ids, +1)
        own, more = pod.alloc(2)
        pod.hold(own, +1)
        note((own, more, pod.tables("miss", np.asarray(ids)[None])))
        for h, b in zip(hashes, ids):
            pod.cached[h] = b
        table = np.zeros((1, 9), np.int32)
        table[0, :8] = ids + own
        for ctx in (97, 98, 112, 113):
            note(pod.tables("decode", table, context_len=np.asarray([ctx])))
        chains.append((hashes, ids, own))
        if doc % 2:  # every second sequence ends; the others stay live
            pod.hold(ids + own, -1)
            pod.free.extend(own)
    hashes, ids, own = chains[1]
    found = pod.cached_prefix(hashes[:4])
    note(found)
    if len(found) == 4:
        pod.touch(hashes[:4])
        more, evicted = pod.alloc(2)
        note((more, evicted, pod.tables(
            "hit", np.asarray(found + more)[None], prefix_blocks=4)))
    for group in pod.groups:
        note((group.slot_of, group.block_of, group.stamp,
              sorted(group.counts.items())))
    note((pod.refs, pod.hashed, pod.asked, sorted(pod.cached.items())))
    return seen.hexdigest()[:16]


def test_afmoe_and_lfm2moe_programs_and_host_tables_are_what_they_were():
    got = {}
    acfg = afmoe.AfmoeConfig(dtype="float32", vocab_size=128, window_slots=24,
                             window_store_blocks=4)
    lcfg = lfm2moe.Lfm2MoeConfig(
        dtype="float32", vocab_size=128,
        layer_types=("conv", "full_attention", "conv", "conv"),
        state_slots=24, state_stride_blocks=2)
    got["afmoe.tables"] = host_tables(afmoe, acfg)
    got["lfm2moe.tables"] = host_tables(lfm2moe, lcfg)
    shapes = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
    params = jax.eval_shape(
        lambda: lfm2moe.init_params(jax.random.key(0), lcfg))
    pod = pod_programs.Pod("p", lfm2moe, lcfg, 40)
    for interpret in (False, True):
        programs = pod_programs.inner_programs(lfm2moe, lcfg, shapes, interpret)
        for key, fn in programs.items():
            a, b = pod_programs.example_args(key, shapes, pod, 16)
            text = str(jax.make_jaxpr(fn)(params, a, pod.kv.arrays, b))
            got[f"lfm2moe.{key}.{interpret}"] = hashlib.sha256(
                text.encode()).hexdigest()[:16]
    assert got == AT_PR_34
