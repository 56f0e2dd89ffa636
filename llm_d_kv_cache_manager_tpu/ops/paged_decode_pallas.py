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

A grid step takes ``blocks_per_step`` pool blocks, each through its own
pipelined DMA, and makes ONE online-softmax update over all their keys.
How the step's operand is assembled is read from the slot layout
(``heads_first``, as the pool's ``KVGroupSpec`` states it), the algorithm
is one: slots [2, Hkv, bs, D] (models/afmoe.py) side by side are one
[Hkv, P*bs, D] operand; slots [2, bs, Hkv, D] (models/llama.py) are taken
as they lie, [bs*Hkv, D] rows against every query head with the other KV
heads' columns masked (K and V pass the MXU once either way, and nothing
is re-laid-out in VMEM).  ``packed`` slots [bs*Hkv, 2*D] (models/lfm2moe.py,
head size 64) are the second form with a position's K in the lower half of a
row's lanes and its V in the upper: the pool's minor axis is then 128 wide, as
the chip lays arrays out (with 64 the compiler makes the slot axis the minor
one and re-lays-out the whole pool around every step), one operand is both K
and V, the query comes padded with zeros over V's lanes and the output is read
from them.

Contract matches ops/paged_attention.py::paged_attention; equivalence
is pinned by tests/test_paged_decode_pallas.py (interpret mode on CPU);
tests/test_tpu_compile.py compiles both forms for the v5e at the served
shapes, and llama.decode_step / afmoe.decode_step serve through it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# What the measurement chose for slots [2, bs, Hkv, D] (PERF.md section 6,
# PR 32; the cell internlm2-chat-sysprompt: B 32, 192 table columns, Hkv 8,
# D 128): pool blocks a grid step, each through its own pipelined DMA, and
# the products' operands in the query's type (bfloat16 in serving) with
# float32 accumulation.  models/afmoe.py passes its own for its slots.
BLOCKS_PER_STEP = 32
MXU_NATIVE = True


def serves(interpret: bool) -> bool:
    """The one rule by which a model's decode step takes this kernel: where
    the program is compiled for the TPU or asked to be interpreted (the CPU
    tests); elsewhere the XLA gather (ops/paged_attention.py)."""
    return interpret or jax.default_backend() == "tpu"


def _decode_kernel(
    table_ref,  # SMEM [B, max_blocks] int32 (scalar prefetch)
    ctx_ref,  # SMEM [B] int32 (scalar prefetch)
    *rest,  # more scalar prefetch (below), q ref (VMEM [1, H, D]),
    # blocks_per_step kv refs, out ref, then scratch
    block_size: int,
    groups: int,
    scale: float,
    blocks_per_step: int,
    mxu_native: bool,
    windowed: bool = False,
    heads_first: bool = False,
    packed: bool = False,
):
    # Scalar prefetch after the context: [start (SMEM [B]) if windowed,]
    # [the last block (SMEM [B]) unless heads_first: the index maps' own.]
    start_ref = rest[0] if windowed else None
    q_ref, *rest = rest[windowed + (not heads_first) :]
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
    Hkv = H // groups
    # mxu_native: feed the dots bf16 operands with f32 accumulation (the
    # MXU's native mode) instead of upcasting K/V after the DMA — saves
    # the VPU cast and halves the operands' VMEM footprint.  Softmax
    # statistics and accumulators stay f32 either way.
    compute_dtype = q_ref.dtype if mxu_native else jnp.float32
    q = q_ref[0].astype(jnp.float32) * scale  # [H, D]
    if heads_first:
        qb = q.reshape(Hkv, groups, D).astype(compute_dtype)

    def softmax_update(s, seen):
        """The online-softmax statistics over one step's scores s [H, width]
        (f32) of which ``seen`` are visible: returns the weights p (f32)
        and the factor the accumulator shrinks by."""
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [H, width] f32
        correction = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * correction + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        return p, correction

    def in_context(position, first):
        """Which of the step's positions (an iota, counted from the step's
        first) the sequence sees."""
        seen = position < ctx - first
        if windowed:
            # Positions before the window's first (all in the table's
            # first block) are masked like those past ctx.
            seen &= position >= start_ref[b] - first
        return seen

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
        p, correction = softmax_update(s, in_context(col, first))
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
        # Slots are [2, bs, Hkv, D], handed in as [2, bs*Hkv, D] (merging
        # the two moves nothing): a block's K is one [bs*Hkv, D] operand
        # whose row r is position r // Hkv of KV head r % Hkv.  Every
        # query head is multiplied against every row and the rows of other
        # KV heads are masked before the softmax: the step is one update
        # over P*bs*Hkv columns, with no re-layout of K or V in VMEM.
        # (Hkv times the products' FLOPs on an MXU that H query rows leave
        # idle; each K and V row passes it once, as in any other form.
        # Measured against a transpose a step and a transpose a block:
        # PERF.md section 6, PR 32.)
        rows = block_size * Hkv
        width = blocks_per_step * rows
        if packed:  # K and V side by side in the lanes: one operand is both

            def key(r):
                return r[0]

            value = key
        else:

            def key(r):
                return r[0, 0]

            def value(r):
                return r[0, 1]

        first = j * blocks_per_step * block_size

        @pl.when(first < ctx)
        def _attend_step():
            qc = q.astype(compute_dtype)
            s = jnp.concatenate(
                [
                    jax.lax.dot_general(
                        qc,
                        key(r).astype(compute_dtype),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    for r in kv_refs
                ],
                axis=1,
            )  # [H, width]
            col = jax.lax.broadcasted_iota(jnp.int32, (H, width), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (H, width), 0)
            own = jax.lax.rem(col, Hkv) == jax.lax.div(row, groups)
            p, correction = softmax_update(
                s, own & in_context(jax.lax.div(col, Hkv), first)
            )
            p = p.astype(compute_dtype)
            o = sum(
                jax.lax.dot_general(
                    p[:, i * rows : (i + 1) * rows],
                    value(r).astype(compute_dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for i, r in enumerate(kv_refs)
            )  # [H, D]
            acc_ref[...] = acc_ref[...] * correction + o

    @pl.when(j == n_steps - 1)
    def _finalize():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "blocks_per_step", "mxu_native", "heads_first", "packed"
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
    mxu_native: bool = MXU_NATIVE,
    start: jnp.ndarray | None = None,
    heads_first: bool = False,
    packed: bool = False,
) -> jnp.ndarray:
    """q: [B, H, D]; kv_layer: [num_blocks, 2, bs, Hkv, D], or
    ``heads_first``: [num_blocks, 2, Hkv, bs, D], or ``packed``:
    [num_blocks, bs, Hkv, 2*D] (the module's head says how a step's operand
    is made of each);
    block_table: [B, max_blocks] int32; context_len: [B] int32.
    ``start`` ([B] int32, window layers): the first position of the
    table a sequence still sees, as in ``paged_attention``; without it
    the kernel is the one it was.  Returns [B, H, D] in q.dtype.

    ``mxu_native=True`` keeps the attention dots in the input dtype
    (bf16 operands, f32 accumulation) instead of upcasting K/V to f32 in
    VMEM.  The default serves ``llama`` (the same time on the chip as
    float32 operands and the same ``decode_logit_rel_err``: PERF.md
    section 6, PR 32); models/afmoe.py passes False, as it was measured.
    """
    B, H, D = q.shape
    scale = D**-0.5
    if packed:
        if heads_first:
            raise ValueError("packed slots are rows of positions, not heads")
        _, block_size, Hkv, _ = kv_layer.shape
        # zeros over V's lanes: the scores are q.k, the output's upper half p.v
        q = jnp.concatenate((q, jnp.zeros_like(q)), axis=-1)
    elif heads_first:
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

    scalars = [block_table, context_len] + [
        a for a in (start,) if a is not None
    ]
    if not heads_first:
        kv_layer = kv_layer.reshape(
            kv_layer.shape[: 1 if packed else 2]
            + (block_size * Hkv, q.shape[2])
        )
        # The sequence's last valid block, once for all index maps: the
        # scalar core runs every operand's map twice a grid step, and a
        # division in each was a tenth of the kernel's time.  (The
        # heads-first maps still divide: ROADMAP.)
        scalars.append(jnp.maximum((context_len - 1) // block_size, 0))
    zeros = (0,) * (kv_layer.ndim - 1)

    def kv_index(i):
        # Sub-block i of step j; past-context steps revisit the last
        # valid block (an unchanged index skips the DMA).
        def index(b, j, table_ref, ctx_ref, *more):
            if heads_first:
                jc = jnp.minimum(
                    j * P_STEP + i,
                    jnp.maximum((ctx_ref[b] - 1) // block_size, 0),
                )
            else:
                jc = jnp.minimum(j * P_STEP + i, more[-1][b])
            return (table_ref[b, jc],) + zeros

        return index

    windowed = start is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec(
                (1, H, q.shape[2]),
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
            (1, H, q.shape[2]),
            lambda b, j, *_: (b, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, q.shape[2]), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        block_size=block_size,
        groups=groups,
        scale=scale,
        blocks_per_step=P_STEP,
        mxu_native=mxu_native,
        windowed=windowed,
        heads_first=heads_first,
        packed=packed,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in scalars), q, *([kv_layer] * P_STEP))
    return out[..., D:] if packed else out
