"""Pallas TPU paged-decode attention kernel.

The XLA version (ops/paged_attention.py) gathers every table block into
a dense [B, T, Hkv, D] tensor before attending — the whole context's
KV crosses HBM twice (pool -> gathered copy -> compute reads).  This
kernel is the TPU analogue of vLLM's paged-attention CUDA kernel: the
block table rides in as a scalar-prefetch operand, each grid step's
``index_map`` points straight at that sequence's next pool block, and
Pallas's pipeline DMAs exactly the referenced blocks HBM->VMEM
(double-buffered) while the MXU works on the previous one.  Past the
context length the index map pins to the last valid block — an
unchanged index skips the redundant DMA — and the flash accumulators
(f32, VMEM scratch) carry the online softmax across grid steps.

Contract matches ops/paged_attention.py::paged_attention; equivalence
is pinned by tests/test_paged_decode_pallas.py (interpret mode on CPU,
compiled on TPU via bench paths).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# Default pool blocks fetched per grid step: amortizes per-step
# pipeline overhead (528 one-block steps left the MXU mostly idle)
# while each block still arrives through its own independently-
# pipelined DMA.  LlamaConfig.decode_blocks_per_step overrides it and
# nothing in the repo sets that; models/afmoe.py passes its own 32.
BLOCKS_PER_STEP = 4


def _decode_kernel(
    table_ref,  # SMEM [B, max_blocks] int32 (scalar prefetch)
    ctx_ref,  # SMEM [B] int32 (scalar prefetch)
    *rest,  # [start ref (SMEM [B]) if windowed,] q ref (VMEM [1, H, D]),
    # blocks_per_step kv refs, out ref, then scratch
    block_size: int,
    groups: int,
    scale: float,
    blocks_per_step: int,
    mxu_native: bool,
    windowed: bool = False,
    heads_first: bool = False,
):
    start_ref = rest[0] if windowed else None
    q_ref, *rest = rest[1:] if windowed else rest
    kv_refs = rest[:blocks_per_step]
    out_ref = rest[blocks_per_step]
    m_ref, l_ref, acc_ref = rest[blocks_per_step + 1 :]

    b = pl.program_id(0)
    j = pl.program_id(1)
    n_steps = pl.num_programs(1)
    ctx = ctx_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    H = q_ref.shape[1]
    D = q_ref.shape[2]
    Hkv = kv_refs[0].shape[2 if heads_first else 3]
    # mxu_native: feed the dots bf16 operands with f32 accumulation (the
    # MXU's native mode) instead of upcasting K/V after the DMA — saves
    # the VPU cast and halves the operands' VMEM footprint.  Softmax
    # statistics and accumulators stay f32 either way.
    compute_dtype = q_ref.dtype if mxu_native else jnp.float32
    q = q_ref[0].astype(jnp.float32) * scale  # [H, D]
    qb = q.reshape(Hkv, groups, D).astype(compute_dtype)

    def attend(kb, vb, first, width):
        """One online-softmax update over the keys at positions
        first .. first+width-1; kb, vb: [Hkv, width, D]."""
        s = jax.lax.dot_general(
            qb,
            kb,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, width]
        s = s.reshape(H, width)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, width), 1)
        seen = col < ctx - first
        if windowed:
            # Positions before the window's first (all in the table's
            # first block) are masked like those past ctx.
            seen &= col >= start_ref[b] - first
        s = jnp.where(seen, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [H, width] f32
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pb = p.reshape(Hkv, groups, width).astype(compute_dtype)
        o = jax.lax.dot_general(
            pb,
            vb,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hkv, G, D]
        acc_ref[...] = acc_ref[...] * correction + o.reshape(H, D)

    if heads_first:
        # Slots are [2, Hkv, bs, D]: the step's blocks side by side are one
        # [Hkv, P*bs, D] operand, so a step is one update (P*bs = 128 keys
        # at 8 blocks a step) and not P small ones.
        first = j * blocks_per_step * block_size

        @pl.when(first < ctx)
        def _attend_step():
            kb = jnp.concatenate([r[0, 0] for r in kv_refs], axis=1)
            vb = jnp.concatenate([r[0, 1] for r in kv_refs], axis=1)
            attend(
                kb.astype(compute_dtype),
                vb.astype(compute_dtype),
                first,
                blocks_per_step * block_size,
            )

    else:
        for i, kv_ref in enumerate(kv_refs):
            # Valid positions in sub-block i: [(j*P+i)*bs, ctx).
            first = (j * blocks_per_step + i) * block_size

            @pl.when(first < ctx)
            def _attend(kv_ref=kv_ref, first=first):
                # [bs, Hkv, D] -> [Hkv, bs, D]
                kb = kv_ref[0, 0].astype(compute_dtype).transpose(1, 0, 2)
                vb = kv_ref[0, 1].astype(compute_dtype).transpose(1, 0, 2)
                attend(kb, vb, first, block_size)

    @pl.when(j == n_steps - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "blocks_per_step", "mxu_native", "heads_first"
    ),
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,
    kv_layer: jnp.ndarray,
    block_table: jnp.ndarray,
    context_len: jnp.ndarray,
    *,
    interpret: bool = False,
    blocks_per_step: int = BLOCKS_PER_STEP,
    mxu_native: bool = False,
    start: jnp.ndarray | None = None,
    heads_first: bool = False,
) -> jnp.ndarray:
    """q: [B, H, D]; kv_layer: [num_blocks, 2, bs, Hkv, D]
    (``heads_first``: [num_blocks, 2, Hkv, bs, D], read without the
    transpose in VMEM);
    block_table: [B, max_blocks] int32; context_len: [B] int32.
    ``start`` ([B] int32, window layers): the first position of the
    table a sequence still sees, as in ``paged_attention``; without it
    the kernel is the one it was.  Returns [B, H, D] in q.dtype.

    ``mxu_native=True`` keeps the attention dots in the input dtype
    (bf16 operands, f32 accumulation) instead of upcasting K/V to f32 in
    VMEM.  No caller outside the tests sets it; not timed on a chip.
    """
    B, H, D = q.shape
    if heads_first:
        _, _, Hkv, block_size, _ = kv_layer.shape
    else:
        _, _, block_size, Hkv, _ = kv_layer.shape
    groups = H // Hkv
    max_blocks = block_table.shape[1]
    P_STEP = blocks_per_step
    n_steps = -(-max_blocks // P_STEP)
    if max_blocks % P_STEP:
        # Pad table columns; pads resolve to the last valid block and
        # are masked by context_len in the kernel.
        block_table = jnp.pad(
            block_table,
            ((0, 0), (0, n_steps * P_STEP - max_blocks)),
        )

    def kv_index(i):
        # Sub-block i of step j; past-context steps revisit the last
        # valid block (an unchanged index skips the DMA).
        def index(b, j, table_ref, ctx_ref, *_):
            jc = jnp.minimum(
                j * P_STEP + i,
                jnp.maximum((ctx_ref[b] - 1) // block_size, 0),
            )
            return (table_ref[b, jc], 0, 0, 0, 0)

        return index

    windowed = start is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + windowed,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec(
                (1, H, D),
                lambda b, j, *_: (b, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ]
        + [
            pl.BlockSpec(
                (1,) + kv_layer.shape[1:],
                kv_index(i),
                memory_space=pltpu.VMEM,
            )
            for i in range(P_STEP)
        ],
        out_specs=pl.BlockSpec(
            (1, H, D),
            lambda b, j, *_: (b, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_size=block_size,
        groups=groups,
        scale=D**-0.5,
        blocks_per_step=P_STEP,
        mxu_native=mxu_native,
        windowed=windowed,
        heads_first=heads_first,
    )
    scalars = [block_table, context_len] + [
        a for a in (start,) if a is not None
    ]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in scalars), q, *([kv_layer] * P_STEP))
