"""Pallas TPU paged-decode attention kernel.

The XLA version (ops/paged_attention.py) gathers every table block into
a dense [B, T, Hkv, D] tensor before attending — the whole context's
KV crosses HBM twice (pool -> gathered copy -> compute reads).  This
kernel is the TPU analogue of vLLM's paged-attention CUDA kernel: the
block table rides in as a scalar-prefetch operand, the pool's blocks are
brought HBM->VMEM where the table says they lie while the MXU works on the
ones before, and the flash accumulators (f32, VMEM scratch) carry the
online softmax.  It has two forms, read from the slot layout
(``heads_first``, ``packed``: as the pool's ``KVGroupSpec`` states it) and
from ``start``.

**The shared pass and the walk** (slots [2, bs, Hkv, D], models/llama.py,
models/phi4flash.py's full group and models/nemotronh.py; ``packed`` slots,
models/lfm2moe.py).  A step's blocks are taken as they lie in the buffer they
were copied into, ONE operand: [P, bs*Hkv, D] = [P*bs*Hkv, D] rows (a block's
rows are whole tiles: nothing is re-laid-out in VMEM) against every query
head in one product, the other KV heads' columns masked, one online-softmax
update over the step's width and one product for the weighted values
(``_attend_rows``; K and V pass the MXU once either way).  Until PR 52 a step
was a list of blocks, a product and a piece of the scores each, the pieces
concatenated along the lanes: at 2 KV heads a block is 32 rows, sixteen
32-lane pieces at offsets that are no tile's (PERF.md section 6, PR 52).
``packed`` slots [bs*Hkv, 2*D] (head size
64) are that form with a position's K in the lower half of a row's lanes and
its V in the upper: the pool's minor axis is then 128 wide, as the chip lays
arrays out (with 64 the compiler makes the slot axis the minor one and
re-lays-out the whole pool around every step), one operand is both K and V,
the query comes padded with zeros over V's lanes and the output is read from
them.

Live sequences whose tables begin with the same run of full blocks (a system
prompt that `Pod.cached_prefix` gave them all) are found from the table
itself, once a decode step (``shared_prefix_plan``: data, never shapes).  The
shared pass brings each block of such a run from HBM once for up to
``SHARED_SEQUENCES`` sequences and multiplies it against all their query rows
together (``_shared_kernel``).  Then the walk (``_walk_kernel``): a grid step
is a sequence, which brings its own blocks, from the end of its run to its
last block in context, out of the pool by copies of its own
(``pltpu.make_async_copy``, a wave of blocks at a time into one of
``WALK_BUFFERS`` VMEM buffers, the waves behind on their way while this one
is multiplied, in a rolled loop), its online softmax resumed from what the
pass left (running maximum, sum, weighted values, float32): the same
attention over the same keys in the same types, the float32 sums in another
order.  A sequence copies as many
blocks as it has in context past its run and none past the context (a block
an operand of the pipeline, 32 a grid step, read ≈1.4 times the blocks in
chat-sysprompt at ≈55 % of the bandwidth: PERF.md section 6, PR 41), and
near its end it starts the next sequence's first waves into the free buffers,
so that no sequence begins by waiting for a copy with nothing to hide it.
**A run** is a wave of a walk, all of it in context, whose blocks lie one
after another in the pool in the table's order (column c + 1 names the block
after column c's: what `Pod.alloc` deals out of a fresh pool, so a document
prefilled once and asked again lies in runs).  The plan finds the runs from
the table, once a decode step (``runs``: data, never a shape or a layout's
name), and the walk brings a run by ONE copy of the wave's bytes and one
wait; any other wave, a sequence's last partial one among them, by a copy a
block.  Starting a copy and waiting for it are trips of the same scalar core
that issues the products, ≈50 cycles each whatever the copy carries, so a
slot of 18 KB held the walk by the number of its copies (PERF.md section 6,
PR 43): the same bytes into the same places by fewer descriptors.  The
shared pass brings its steps the same way (``_bring`` is both kernels'): a
step of ``SHARED_BLOCKS_PER_STEP`` columns that lies wholly inside its
group's run and whose blocks ascend is a run too (the plan's ``shared`` says
which, and counts them: ``shared_run_blocks``), as a prompt that one miss
prefilled out of a fresh pool lies; its last partial step, and a step with a
break, come by a copy a block, in the same rolled loop (PR 52).
A sequence that shares nothing walks its whole table, and a table where
nobody shares costs the set-finding and the shared pass's one empty step.

**Heads-first slots** [2, Hkv, bs, D] (``heads_first``: models/afmoe.py, its
full layers and, with a ``start``, its window layers) are walked too, the
copies, waves, runs and the stream across sequences the same (a copy does
not know a slot's shape); the products are their own (``_attend_heads``): a
wave is multiplied a KV head at a time, that head's keys of the wave
``buf[:, 0, h]`` being [P, bs, D] = [P*bs, D] with no re-layout (a block's
[bs, D] is whole tiles) against the head's own query rows, one online-softmax
update a wave over [H, P*bs] scores with no other head's columns to mask.
They go through no shared pass (its products for this layout wait for traffic
that shares a document: ROADMAP), and a window layer's table, which begins at
the block that holds the window's first position ``start``, shares nothing by
nature (a ``start`` hides part of a prefix from each sequence on its own):
their plan is ``shared_prefix_plan``'s with ``min_sequences`` None, the runs
alone, and the walk hides what lies before ``start`` like what lies past the
context.

**The grid of tables by steps** is what is left of the kernel's first form,
for the window layers of slots [2, bs, Hkv, D] (models/phi4flash.py: rows,
as above, with the positions before ``start`` hidden).  A grid step takes
``blocks_per_step`` pool blocks of one sequence, each an operand whose
``index_map`` points at the sequence's next block, so that Pallas's pipeline
DMAs exactly the referenced blocks (double-buffered), and makes ONE
online-softmax update over all their keys.  Past the context length the index
map pins to the last valid block: an unchanged index skips the redundant
DMA.  (Moving them to the walk is a change of its own, judged on
`phi4flash-reasoning-longgen`: ROADMAP.)

**Latent slots** (``latent``: models/glm4moelite.py; ``KVGroupSpec``'s latent
kind).  A position holds no K and V per head but one vector that is key and
value at once: every query head scores over all its lanes and takes the first
``latent`` of them, weighted, as its output.  A slot is [bs/2, 2*W], row r the
positions r and r + bs/2 as [value_r | rest_r | rest_r+ | value_r+]
(``kv_cache_pool.pack_latent_blocks``: W = 576 alone is no whole number of
128-lane tiles).  The same shared pass and walk, a wave's blocks as ONE
[P*bs/2, 2*W] operand as they lie: the rows' first positions are scored by
the queries laid over the lanes [value | rest | 0], their second positions by
the queries as [0 | rest | value] over the lanes from ``latent`` on (both
slices start on a tile), one online-softmax update over both, and the output
is the weights against the first and the last ``latent`` lanes: a block is
read once for both products (``_attend_latent``).

Contract matches ops/paged_attention.py::paged_attention; equivalence
is pinned by tests/test_paged_decode_pallas.py (interpret mode on CPU), the
walk's copies and what it traces to by tests/test_paged_decode_walk.py;
tests/test_tpu_compile.py compiles every form for the v5e at the served
shapes, and the decode steps of models/llama.py, afmoe.py (one plan a group
of slots: the full layer's and the four window layers'), lfm2moe.py,
glm4moelite.py, nemotronh.py (two KV heads of sixteen query heads each) and
phi4flash.py serve through it (the last with four query
heads a pair-wise KV head of twice the model's head size, the window layers
by ``start`` and the full group by one plan for the eight layers that read
it).
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# The products' operands in the query's type (bfloat16 in serving) with
# float32 accumulation: what the measurement chose for slots [2, bs, Hkv, D]
# (PERF.md section 6, PR 32).  models/afmoe.py passes its own for its slots.
MXU_NATIVE = True
# The shared pass's own, read on the chip at both cells' shapes (kernel alone,
# a decode step's layers; PERF.md section 6, PR 34): sequences a group (their
# query rows go through a block together: 8 x 16 heads = 128 rows in
# internlm2-chat-sysprompt, 8 x 32 = 256 in lfm2moe-chat-agents, where the
# sets are of about eight) and blocks a step (the scores of a step are
# [sequences * H, blocks * bs * Hkv] float32: 1 and 2 MB; 8 / 16 / 32 blocks
# read 6.96 / 6.55 / 6.53 ms and 3.73 / 3.51 / 3.57 ms).
# A step is ONE operand since PR 52, its blocks as they lie in the buffer, and
# a step that is a run in the pool comes by one copy (PERF.md section 6,
# PR 52; hack/paged_decode_alone.py, kernel alone, one layer, ms the shared
# pass + the walk).  At `nemotron3nano-agents-reasoning`'s shapes (128
# sequences of 32 heads over 2 KV heads, 32-row blocks; 8 prompts of 512
# blocks, 16 sequences on each: 16 groups x 32 steps), a step a list of
# blocks before: 1.538 + 0.919; with copies and no products 0.240 + 0.368,
# products and no copies 1.371 + 0.805: the sixteen products a step and their
# 32-lane pieces held it, not the 8192 descriptors.  One operand: 0.755 +
# 0.627 with a copy a block, **0.486 + 0.625** with runs (copies alone
# 0.244 + 0.363, products alone 0.444 + 0.524); runs under the list of
# blocks 1.475 + 0.896.  Where a block's rows were whole 128-lane tiles
# already the two forms read the same: `lfm2moe-chat-agents` (128-row blocks,
# `packed`) 0.610 + 0.198 -> 0.553 + 0.198 (0.687 with a copy a block: the
# runs carry it), `internlm2-chat-sysprompt` 0.075 + 0.138 -> 0.074 + 0.139
# (0.1367 -> 0.1373 once a wait's descriptor no longer reads the table a
# block: 0.1382 -> 0.1408 before), `phi4flash-reasoning-longgen` (160-row
# blocks) whole 4.648 -> 4.570.
SHARED_SEQUENCES = 8
SHARED_BLOCKS_PER_STEP = 16
# The walk's own (PERF.md section 6, PR 41): blocks a wave, as many as make
# WALK_WAVE_BYTES and WALK_WAVE_BLOCKS at most, and the buffers a wave is
# copied into (WALK_BUFFERS - 1 waves are on their way while one is
# multiplied).  Read on the chip at the three cells' shapes, kernel alone
# (shared pass and walk) over a decode step's layers, ms, in the order
# chat-sysprompt (slots of 64 KB, 16 query rows) / chat-agents (32 KB, 32) /
# reasoning-longgen (80 KB, 40): the walk before, 32 / 64 / 32 blocks a grid
# step as operands, 6.63-6.71 / 3.53-3.60 / 69.3-69.4; two buffers of 4 / 8 /
# 16 / 24 / 32 blocks 7.80 / 6.26 / 5.66-5.71 / 5.58-5.61 / 5.73, 3.83 / 3.44 /
# 3.26-3.33 / 3.28-3.31 / 3.22, 78.7 / 71.8 / 68.9-69.1 / 68.8-68.9 / 68.7;
# three buffers of 8 / 12 / 16 / 24 blocks 6.14 / 5.64 / 5.40 / 5.43, 3.54 /
# 3.44 / 3.37 / 3.28, 73.2 / 70.4 / 69.1 / 68.4; four of 8 6.22 / 3.60 / 73.1.
# With copies and no products 5.47, products and no copies 3.91, neither 2.27
# (chat-sysprompt, two buffers of 16): the copies hold the walk, and a third
# buffer keeps them coming across a sequence's end.  An online-softmax update
# every 8 blocks of a wave, so that no product is made past the context, read
# 6.11-6.15 / 3.55 / 72.6: the last wave's masked products cost less than the
# updates.  A wave's products are unrolled in the loop's body, which set-up
# pays for: a body of 24 blocks traced in 0.8 s on the chip's host where the
# walk before took 0.4, so 16 serves (its copies are a rolled loop: unrolled
# under `pl.when`, 16 of them traced 0.9 s).
# Read again with runs (PR 43, kernel alone, one latent layer at chat-repos'
# shapes, ms at waves of 32 / 64 / 128 blocks): tables where 992 of a
# sequence's ≈1040 blocks ascend 2.02 / 1.97 / 2.02 (a copy a block: 3.88 at
# 64), tables in no order 4.27 / 3.84 / 3.65 (3.94).  The `llama` forms'
# tables hold few whole ascending waves (chat-sysprompt 1 % of its own
# blocks after the first round) and their readings above stand.
WALK_WAVE_BYTES = 2 << 20
WALK_WAVE_BLOCKS = 16
WALK_BUFFERS = 3


def serves(interpret: bool) -> bool:
    """The one rule by which a model's decode step takes this kernel: where
    the program is compiled for the TPU or asked to be interpreted (the CPU
    tests); elsewhere the XLA gather (ops/paged_attention.py).
    models/llama.py sends its prefills to the flash kernel by it too."""
    return interpret or jax.default_backend() == "tpu"


def _softmax_update(s, seen, m_ref, l_ref):
    """The online-softmax statistics over one step's scores s [rows, width]
    (f32) of which ``seen`` are visible (None: the hidden ones are at
    NEG_INF already): returns the weights p (f32) and the factor the
    accumulator shrinks by."""
    if seen is not None:
        s = jnp.where(seen, s, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # [rows, width] f32
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * correction + jnp.sum(
        p, axis=1, keepdims=True
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    return p, correction


def _other_heads(shape, heads: int, groups: int):
    """NEG_INF where scores [R, n*Hkv] are a query row's against a row of
    another KV head than its own, 0 where of its own (`_own_head`): added to
    a step's scores."""
    return jnp.where(_own_head(shape, heads, groups), 0.0, NEG_INF)


def _own_head(shape, heads: int, groups: int):
    """Where scores [R, n*Hkv] are a query row's (row r: query head
    r % heads) against a row of its own KV head (column c: KV head
    c % Hkv)."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if shape[0] != heads:
        row = jax.lax.rem(row, heads)
    return jax.lax.rem(col, heads // groups) == jax.lax.div(row, groups)


def _attend_rows(q, wave, other, seen, m_ref, l_ref, acc_ref, *, packed: bool):
    """One online-softmax update over a step's blocks of slots [2, bs, Hkv, D]
    as they lie in the buffer, ``wave``: [P, 2, bs*Hkv, D] (merging a slot's
    two axes moves nothing).  The step's keys ``wave[:, 0]`` are
    [P, bs*Hkv, D], whose leading axes collapse to [P*bs*Hkv, D] with no
    re-layout (a block's rows are whole tiles at every served shape: 32, 128,
    160 rows), row r of them position r // Hkv of the step, of KV head
    r % Hkv; ``packed``: ``wave`` is [P, bs*Hkv, 2*D], one operand both K and
    V.  Every query row (q: [R, D] in the products' type) is multiplied
    against every row of the keys in ONE product; the scores [R, P*bs*Hkv]
    against other KV heads' rows (``other``: NEG_INF under them, 0 under a
    row's own, `_other_heads`) and against the rows from ``seen`` on, which
    the query rows do not see (the step's positions past the context, or past
    a shared run's end), are at NEG_INF before the softmax; ONE product
    weighs the values.  Nothing is concatenated along the keys and no weight
    is sliced back a block at a time (as `_attend_heads` has it for its
    slots; the list of blocks this took before is the grid form's alone,
    `_decode_kernel`).  (Hkv times the products' FLOPs on an MXU that few
    query rows leave idle; each K and V row passes it once, as in any other
    form.  Measured against a transpose a step and a transpose a block:
    PERF.md section 6, PR 32; one product against a product a block: PR 52.)
    The walk hands in one sequence's heads, the shared pass those of a whole
    group."""
    width = wave.shape[-1]
    if packed:  # K and V side by side in the lanes: one operand is both
        keys = values = wave[...].reshape(-1, width).astype(q.dtype)
    else:
        keys = wave[:, 0].reshape(-1, width).astype(q.dtype)
        values = wave[:, 1].reshape(-1, width).astype(q.dtype)
    s = jax.lax.dot_general(
        q, keys, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [R, P*bs*Hkv]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    p, correction = _softmax_update(
        s + other + jnp.where(row < seen, 0.0, NEG_INF), None, m_ref, l_ref)
    o = jax.lax.dot_general(
        p.astype(q.dtype), values, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [R, D]
    acc_ref[...] = acc_ref[...] * correction + o


def _attend_heads(q, wave, seen, m_ref, l_ref, acc_ref):
    """One online-softmax update over a wave of heads-first slots as they
    lie, ``wave``: [P, 2, Hkv, bs, D].  A KV head's keys ``wave[:, 0, h]`` are
    [P, bs, D], whose leading axes collapse to [P*bs, D] with no re-layout (a
    block's [bs, D] is whole tiles), against that head's own query rows
    (q: [H, D] in the products' type, head h's ``groups`` rows together):
    the scores are [H, P*bs], column c the wave's position c, ``seen`` where
    the sequence sees it.  No other head's columns to mask, nothing
    concatenated along the keys."""
    P, _, Hkv, bs, D = wave.shape
    groups = q.shape[0] // Hkv

    def of_head(x, h):
        return x[h * groups : (h + 1) * groups]

    def slab(i, h):
        return wave[:, i, h].reshape(P * bs, D).astype(q.dtype)

    s = jnp.concatenate(
        [
            jax.lax.dot_general(
                of_head(q, h), slab(0, h), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(Hkv)
        ],
        axis=0,
    )  # [H, P*bs]
    p, correction = _softmax_update(s, seen, m_ref, l_ref)
    p = p.astype(q.dtype)
    o = jnp.concatenate(
        [
            jax.lax.dot_general(
                of_head(p, h), slab(1, h), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for h in range(Hkv)
        ],
        axis=0,
    )  # [H, D]
    acc_ref[...] = acc_ref[...] * correction + o


def _attend_latent(q, slab, hide, m_ref, l_ref, acc_ref, *, value: int):
    """One online-softmax update over a step's latent blocks as they lie:
    slab [n, 2*W], a row two positions ([value | rest | rest+ | value+]);
    q = (the query rows [R, 2*W - value] laid out for a row's first position,
    and for its second).  ``hide(i, scores)`` puts what the query rows do not
    see of the rows' first (i = 0) or second (i = 1) positions at NEG_INF.
    Two products score the slab, two weigh it; each reads lanes that start
    on a tile."""
    dims = (((1,), (1,)), ((), ()))
    keys = (slab[:, : slab.shape[1] - value], slab[:, value:])
    s = [
        hide(i, jax.lax.dot_general(
            x, k.astype(x.dtype), dims, preferred_element_type=jnp.float32))
        for i, (x, k) in enumerate(zip(q, keys))
    ]  # [R, n] each
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(
        m_prev,
        jnp.maximum(jnp.max(s[0], axis=1, keepdims=True),
                    jnp.max(s[1], axis=1, keepdims=True)),
    )
    p = [jnp.exp(x - m_new) for x in s]
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * correction + (
        jnp.sum(p[0], axis=1, keepdims=True)
        + jnp.sum(p[1], axis=1, keepdims=True)
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    o = sum(
        jax.lax.dot_general(
            w.astype(q[0].dtype),
            v.astype(q[0].dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        for w, v in zip(p, (slab[:, :value], slab[:, slab.shape[1] - value:]))
    )  # [R, value]
    acc_ref[...] = acc_ref[...] * correction + o


def latent_query_layouts(q, value: int):
    """Latent queries [B, ..., W] over the lanes of a slot row's first
    position and of its second, [value | rest | 0] and [0 | rest | value]:
    [B, 2, ..., 2*W - value]."""
    zeros = jnp.zeros_like(q[..., value:])
    return jnp.stack(
        (
            jnp.concatenate((q, zeros), axis=-1),
            jnp.concatenate((zeros, q[..., value:], q[..., :value]), axis=-1),
        ),
        axis=1,
    )


def _latent_queries(q_refs, scale: float, compute_dtype):
    """The two layouts of the query rows ([.., 2, H, 2*W - value] each ref),
    scaled, the refs' rows one under the other."""
    return tuple(
        (jnp.concatenate(
            [r[0, i].astype(jnp.float32) for r in q_refs], axis=0
        ) * scale).astype(compute_dtype)
        for i in range(2)
    )


def _bring(act: str, table_ref, row, column, count, is_run, kv_hbm, target,
           sem):
    """Start, or wait for (``act``), the copies that bring ``count`` blocks,
    those row ``row`` of the table names from ``column`` on, out of the pool
    into the first places of ``target`` ([P, a slot]: a wave of the walk, a
    step of the shared pass): where the plan found them to be a run
    (``is_run``: all P, one after another in the pool) by ONE copy of P slots,
    else by a copy a block, in a rolled loop: one body to trace, lower and
    compile whatever P is, and as many trips of the scalar core as there are
    descriptors.  The flag the start read decides the wait, and a wait's
    descriptor stands for its size alone: each names the first block, so that
    waiting reads the table once and not once a block (the walk of tables
    with few runs is held by these trips: `internlm2-chat-sysprompt`, PERF.md
    section 6, PR 52).  A pool of fewer blocks than P holds no run, and no
    slice of P blocks could be taken of it."""
    P = target.shape[0]
    starts = act == "start"
    act = operator.methodcaller(act)
    if kv_hbm.shape[0] >= P:

        @pl.when(is_run & (count > 0))
        def _run():
            act(pltpu.make_async_copy(
                kv_hbm.at[pl.ds(table_ref[row, column], P)], target, sem))

        count = jnp.where(is_run, 0, count)

    def one(i, _):
        act(pltpu.make_async_copy(
            kv_hbm.at[pl.ds(
                table_ref[row, column + i if starts else column], 1)],
            target.at[pl.ds(i, 1)],
            sem,
        ))

    jax.lax.fori_loop(0, count, one, None)


def _decode_kernel(
    table_ref,  # SMEM [B, max_blocks] int32 (scalar prefetch)
    ctx_ref,  # SMEM [B] int32 (scalar prefetch)
    start_ref,  # SMEM [B]: the first position of the table a sequence sees
    last_ref,  # SMEM [B]: its last block in context (the index maps' own)
    q_ref,  # VMEM [1, H, D]
    *rest,  # blocks_per_step kv refs, out ref, scratch
    block_size: int,
    groups: int,
    scale: float,
    blocks_per_step: int,
    mxu_native: bool,
):
    """The grid of tables by steps (window layers of slots [2, bs, Hkv, D]):
    a grid step takes ``blocks_per_step`` pool blocks of one sequence, each
    an operand with its own pipelined DMA, a block's K one [bs*Hkv, D]
    operand against every query head, the other KV heads' columns and the
    positions the sequence does not see at NEG_INF."""
    del last_ref
    kv_refs = rest[:blocks_per_step]
    out_ref, m_ref, l_ref, acc_ref = rest[blocks_per_step:]

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
    Hkv = H // groups
    # mxu_native: feed the dots bf16 operands with f32 accumulation (the
    # MXU's native mode) instead of upcasting K/V after the DMA — saves
    # the VPU cast and halves the operands' VMEM footprint.  Softmax
    # statistics and accumulators stay f32 either way.
    compute_dtype = q_ref.dtype if mxu_native else jnp.float32
    q = q_ref[0].astype(jnp.float32) * scale  # [H, D]

    def in_context(position, first):
        """Which of the step's positions (an iota, counted from the step's
        first) the sequence sees: those before the window's first (all in
        the table's first block) are masked like those past ctx."""
        return (position < ctx - first) & (position >= start_ref[b] - first)

    first = j * blocks_per_step * block_size

    @pl.when(first < ctx)
    def _attend_step():
        shape = (H, block_size * Hkv)
        own = _own_head(shape, H, groups)
        position = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, shape, 1), Hkv
        )

        def scores(i, r):
            s = jax.lax.dot_general(
                x, r[0, 0].astype(x.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            seen = in_context(position, first + i * block_size)
            return jnp.where(own & seen, s, NEG_INF)

        # The step's blocks are separate operands here (each its own
        # pipelined DMA), so this form alone multiplies a block at a time:
        # the pieces of the scores side by side for one update, and the
        # weights sliced back a block at a time.
        x = q.astype(compute_dtype)
        s = jnp.concatenate(
            [scores(i, r) for i, r in enumerate(kv_refs)], axis=1)
        p, correction = _softmax_update(s, None, m_ref, l_ref)
        p = p.astype(x.dtype)
        o = sum(
            jax.lax.dot_general(
                p[:, i * shape[1] : (i + 1) * shape[1]],
                r[0, 1].astype(x.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            for i, r in enumerate(kv_refs)
        )  # [H, D]
        acc_ref[...] = acc_ref[...] * correction + o

    @pl.when(j == n_steps - 1)
    def _finalize():
        _normalised(out_ref, l_ref, acc_ref)


def _normalised(out_ref, l_ref, acc_ref):
    l = l_ref[:, :1]
    out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
    out_ref[0] = out.astype(out_ref.dtype)


def _walk_kernel(
    table_ref,  # SMEM [B, max_blocks] int32 (scalar prefetch)
    ctx_ref,  # SMEM [B]
    last_ref,  # SMEM [B]: each sequence's last block in context
    place_ref,  # SMEM [B]: its place in the shared pass's results
    skip_ref,  # SMEM [B]: its shared run, the first block of its own
    runs_ref,  # SMEM [B, waves]: which waves of its walk are runs
    *rest,  # [start (SMEM [B]) if windowed,] q (VMEM [1, H, D]; latent:
    # [1, 2, H, 2*W - value]), the pool where it lies, [what the shared pass
    # left of this sequence: m0, l0, acc0,] out, scratch
    block_size: int,
    groups: int,
    scale: float,
    mxu_native: bool,
    packed: bool,
    latent: int | None = None,
    heads_first: bool = False,
    windowed: bool = False,
):
    """The walk of each sequence's own blocks: a grid step is a sequence.  It
    brings the blocks from the end of the sequence's shared run to its last
    block in context, a wave at a time (``buf``: [buffers, blocks a wave, a
    slot]) into one of the buffers, the waves behind this one on their way
    while it is multiplied.  A wave that the plan found to be a run (its
    blocks lie one after another in the pool, in the table's order:
    ``runs_ref``) comes by one copy of the whole wave, any other by a copy a
    block, none past the sequence's last: every block of its own is brought
    once either way.  The waves of a call are one
    stream, in the sequences' order: a wave asks for the one buffers - 1
    behind it, which near a sequence's end is the first of the next
    sequence's (scratch lives across grid steps, and the grid is sequential),
    so that a sequence does not begin by waiting for a copy with nothing to
    hide it.  ``wave_ref`` counts the waves since the call began: a wave's
    buffer is its number's remainder.

    Heads-first slots [2, Hkv, bs, D] are multiplied a KV head at a time
    (``_attend_heads``); with ``windowed`` the table begins at the block that
    holds the window's first position, ``start``, and what lies before it is
    hidden like what lies past the context.  A call that nothing resumes (no
    shared pass came before it: the plan's every ``skip`` is 0) starts every
    sequence's softmax anew."""
    del place_ref  # the index maps' own
    *ins, out_ref, m_ref, l_ref, acc_ref, buf, sem, wave_ref = rest
    start_ref = ins.pop(0) if windowed else None
    q_ref, kv_hbm, *resumed = ins
    N, P = buf.shape[:2]
    b = pl.program_id(0)
    B = pl.num_programs(0)
    H = q_ref.shape[-2]
    Hkv = H // groups
    ctx = ctx_ref[b]

    def own(seq):
        """A sequence's first own block and how many it has (one at least:
        a run ends before the block of the write position)."""
        return skip_ref[seq], last_ref[seq] + 1 - skip_ref[seq]

    def bring(act, seq, j, slot, count):
        """Wave j of a sequence, its first ``count`` blocks, into a buffer."""
        _bring(act, table_ref, seq, skip_ref[seq] + j * P, count,
               runs_ref[seq, j] == 1, kv_hbm, buf.at[slot], sem.at[slot])

    def start(seq, j, number):
        """Ask for wave j of a sequence, the call's wave ``number``: a run by
        one copy, any other wave by a copy a block, none past the sequence's
        last and none behind the last sequence."""
        at = jnp.minimum(seq, B - 1)
        _, n = own(at)
        count = jnp.where(seq < B, jnp.minimum(n - j * P, P), 0)
        bring("start", at, j, jax.lax.rem(number, N), count)

    def behind(seq, j):
        """The wave behind wave j of a sequence in the call's stream."""
        _, n = own(jnp.minimum(seq, B - 1))
        closes = (j + 1) * P >= n
        return jnp.where(closes, seq + 1, seq), jnp.where(closes, 0, j + 1)

    def stream(seq, j):
        """The ``N - 1`` waves behind wave j of a sequence, nearest first."""
        waves = []
        for _ in range(1, N):
            seq, j = behind(seq, j)
            waves.append((seq, j))
        return waves

    @pl.when(b == 0)
    def _once():
        # Places of a buffer that no copy has written yet are multiplied
        # under weights of zero: they must hold numbers.
        buf[...] = jnp.zeros_like(buf)
        wave_ref[0] = 0
        # the call's first waves, but the last of those wave 0 has behind it
        # (rolled: `start` is traced here once however many buffers)
        def first_waves(number, at):
            start(*at, number)
            return behind(*at)

        jax.lax.fori_loop(0, N - 1, first_waves, (jnp.int32(0), jnp.int32(0)))

    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if len(resumed) == 3:
        resumes = skip_ref[b] > 0

        @pl.when(resumes)
        def _resume():
            for ref, left in zip((m_ref, l_ref, acc_ref), resumed):
                ref[...] = left[...]

        pl.when(jnp.logical_not(resumes))(_init)
    else:
        _init()

    compute_dtype = q_ref.dtype if mxu_native else jnp.float32
    if not isinstance(latent, int):
        q = (q_ref[0].astype(jnp.float32) * scale).astype(compute_dtype)
        if heads_first:  # column c of a wave's scores is the wave's position c
            position = jax.lax.broadcasted_iota(
                jnp.int32, (H, P * block_size), 1)
        else:
            # column c of a wave's scores is the wave's position c // Hkv
            # under KV head c % Hkv (a block is bs * Hkv rows)
            other = _other_heads((H, P * block_size * Hkv), H, groups)
    else:
        q = _latent_queries([q_ref], scale, compute_dtype)
        half = block_size // 2  # rows a block: column c is row c % half of
        col = jax.lax.broadcasted_iota(jnp.int32, (H, P * half), 1)
        # block c // half; its first position, counted from the wave's first
        position = col + jax.lax.div(col, half) * half
    first, n = own(b)
    waves = (n + P - 1) // P
    before = wave_ref[0]

    def wave(j, _):
        number = before + j
        slot = jax.lax.rem(number, N)
        start(*stream(b, j)[-1], number + N - 1)
        bring("wait", b, j, slot, jnp.minimum(n - j * P, P))
        at = (first + j * P) * block_size

        if heads_first:
            seen = position < ctx - at
            if windowed:
                seen &= position >= start_ref[b] - at
            _attend_heads(q, buf.at[slot], seen, m_ref, l_ref, acc_ref)
        elif not isinstance(latent, int):
            # blocks past the sequence's last lie past its context too
            _attend_rows(q, buf.at[slot], other, (ctx - at) * Hkv,
                         m_ref, l_ref, acc_ref, packed=packed)
        else:
            _attend_latent(
                q, buf[slot].reshape(P * half, -1),
                lambda i, s: jnp.where(
                    position + i * half < ctx - at, s, NEG_INF),
                m_ref, l_ref, acc_ref, value=latent,
            )

    jax.lax.fori_loop(0, waves, wave, None)
    wave_ref[0] = before + waves
    _normalised(out_ref, l_ref, acc_ref)


def _shared_kernel(
    table_ref,  # SMEM [B, max_blocks] int32 (scalar prefetch)
    row_ref,  # SMEM [C]: each group's table row (its first member's),
    run_ref,  # SMEM [C]: its run, in blocks,
    members_ref,  # SMEM [C*G] (the index maps' own)
    step_runs_ref,  # SMEM [C, steps]: and which steps of its run are runs
    *rest,  # a q ref a member, the pool (HBM); out: m, l, acc; scratch
    groups: int,
    scale: float,
    blocks_per_step: int,
    sequences: int,
    mxu_native: bool,
    packed: bool,
    latent: int | None = None,
):
    """The shared-prefix pass: a grid step is a group, whose sequences all
    have the same run of blocks at the head of their tables.  It brings the
    run in ``blocks_per_step`` blocks at a time into one of two buffers (the
    next blocks arrive while these are multiplied), a step that the plan
    found to be a run in the pool (``step_runs_ref``) by one copy of the
    step's slots and one wait, any other, the last partial one among them, by
    a copy a block, none past the run's end: every block of the run is
    brought once either way.  It makes one online-softmax update of all the
    group's query rows over a step.  The statistics and the weighted values
    are left unnormalised in the output blocks: the walk resumes each
    sequence's softmax from them."""
    del members_ref
    q_refs = rest[:sequences]
    kv_hbm = rest[sequences]
    m_ref, l_ref, acc_ref, buf, sem, *other = rest[sequences + 1 :]
    P = blocks_per_step
    g = pl.program_id(0)
    row, run = row_ref[g], run_ref[g]
    H = q_refs[0].shape[-2]

    @pl.when(g == 0)
    def _once():
        # Places of a buffer that no copy has written yet are multiplied
        # under weights of zero: they must hold numbers.
        buf[...] = jnp.zeros_like(buf)
        # NEG_INF under the other KV heads' rows, added to a step's scores:
        # made once a call, where the walk's few rows make theirs from iotas.
        if not isinstance(latent, int):
            other[0][...] = _other_heads(other[0].shape, H, groups)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def bring(act, step, half):
        _bring(act, table_ref, row, step * P, jnp.minimum(run - step * P, P),
               step_runs_ref[g, step] == 1, kv_hbm, buf.at[half],
               sem.at[half])

    @pl.when(run > 0)
    def _first():
        bring("start", 0, 0)

    compute_dtype = q_refs[0].dtype if mxu_native else jnp.float32
    rows = buf.shape[-2]  # a block's: column c of a step is of block c // rows
    if not isinstance(latent, int):
        q = jnp.concatenate([r[0] for r in q_refs], axis=0)  # [G*H, D]
        q = (q.astype(jnp.float32) * scale).astype(compute_dtype)
    else:
        q = _latent_queries(q_refs, scale, compute_dtype)
        block = jax.lax.div(
            jax.lax.broadcasted_iota(
                jnp.int32, (sequences * H, P * rows), 1), rows)
    n_steps = (run + P - 1) // P

    def step(j, _):
        half = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_steps)
        def _next():
            bring("start", j + 1, 1 - half)

        bring("wait", j, half)
        # Every position of the run lies before every member's own: only
        # the blocks past the run's end, in its last step, are hidden whole.
        count = run - j * P
        if not isinstance(latent, int):
            _attend_rows(q, buf.at[half], other[0][...], count * rows,
                         m_ref, l_ref, acc_ref, packed=packed)
        else:  # one KV "head": only the blocks past the run's end hide
            _attend_latent(
                q, buf[half].reshape(P * rows, -1),
                lambda i, s: jnp.where(block < count, s, NEG_INF),
                m_ref, l_ref, acc_ref, value=latent,
            )

    jax.lax.fori_loop(0, n_steps, step, None)


def shared_prefix_plan(
    block_table: jnp.ndarray,
    context_len: jnp.ndarray,
    *,
    block_size: int,
    blocks_per_wave: int,
    min_sequences: int | None = 2,
    shared_blocks_per_step: int = SHARED_BLOCKS_PER_STEP,
) -> dict:
    """Which sequences' tables begin with the same run of full blocks, the
    groups the shared pass takes them in, and where each sequence's walk
    begins.  All of it is data (int32 arrays of shapes fixed by the table's),
    found once a decode step: every layer sees the same table, shifted.

    A sequence's leader is the first row with the same first block; its own
    run is the count of leading columns equal to the leader's that lie wholly
    before its write position ((j+1)*bs <= context_len - 1); a set's run is
    the least of its members'.  A set goes through the shared pass in groups
    of up to ``SHARED_SEQUENCES`` in row order; a sequence whose group has
    fewer than ``min_sequences`` members (alone in it, as the default has
    it), or in a set whose run is 0 (idle slots on the scratch block among
    them), walks its whole table.  With ``min_sequences`` None nobody shares
    and no sets are looked for: the plan of a call with no shared pass
    (heads-first slots; a window layer's table, whose ``start`` hides part of
    a prefix from each sequence on its own), which has no ``shared`` and no
    ``shared_steps``.  The walk takes a sequence's own blocks
    ``blocks_per_wave`` at a time (`walk_wave` of the pool, where the caller
    names no other), and a wave all in context whose blocks lie one after
    another in the pool (each column's id the one before's + 1, as an
    allocator deals a fresh pool out) is a run, which one copy brings; the
    shared pass takes a group's run ``shared_blocks_per_step`` at a time
    (the call's, where it names another than the module's), and a step all
    inside the run whose blocks lie so is a run too.
    Keys: ``shared`` (each group's table row, run and members, and which
    steps of its run are runs, [C, steps a table]) and ``walk`` (each
    sequence's place in the shared pass's results, the first block of
    its own: its run, 0 where it shares nothing, and which of its waves are
    runs, [B, waves a table]): the two kernels' scalar prefetch after the
    table; ``shared_steps``: the shared pass's grid; ``read_blocks`` (what
    the two read: each group's run once and every sequence's rest, which is
    what the walk copies), ``run_blocks`` (what of it the walk brings by
    runs), ``shared_run_blocks`` (what of it the shared pass brings by runs)
    and ``walked_blocks`` (what a walk of every table reads):
    `attention_read_counts` hands the four to a model's decode step."""
    i32 = jnp.int32
    ctx = context_len.astype(i32)
    if min_sequences is None:
        slot = skip = jnp.zeros(ctx.shape, i32)
    else:
        slot, skip, heads, groups = _shared_sets(
            block_table, ctx, block_size, min_sequences)

    # The walk: every sequence's rest, its write position's block at least.
    blocks = jnp.maximum(ctx - 1, 0) // block_size + 1
    skip = skip.astype(i32)
    plan = {"walk": (slot, skip, _whole_runs(
        block_table, skip, blocks, blocks_per_wave))}
    shared_blocks = by_shared_runs = jnp.zeros((), i32)
    if min_sequences is not None:
        row, run, members = (a.astype(i32) for a in groups)
        step_runs = _whole_runs(
            block_table[row], jnp.zeros_like(run), run, shared_blocks_per_step)
        plan["shared"] = (row, run, members, step_runs)
        # a grid of no step at all is not asked of the compiler: one step
        # that reads nothing where nobody shares
        plan["shared_steps"] = jnp.maximum(jnp.sum(heads), 1).astype(i32)
        shared_blocks = jnp.sum(run)
        by_shared_runs = jnp.sum(step_runs) * shared_blocks_per_step
    return {
        **plan,
        "read_blocks": (shared_blocks + jnp.sum(blocks - skip)).astype(i32),
        "run_blocks": (
            jnp.sum(plan["walk"][2]) * blocks_per_wave).astype(i32),
        "shared_run_blocks": by_shared_runs.astype(i32),
        "walked_blocks": jnp.sum(blocks).astype(i32),
    }


# The plan's counts as a decode step hands them back (`pools["attention_read"]`)
# and as the span `attention.read` names them (models/pod.py).
READ_COUNTS = ("read_blocks", "walked_blocks", "run_blocks",
               "shared_run_blocks")


def attention_read_counts(plan: dict) -> jnp.ndarray:
    """`READ_COUNTS` of a plan, [4] int32."""
    return jnp.stack([plan[name] for name in READ_COUNTS])


def _whole_runs(table, first, blocks, P: int):
    """Which steps of P columns of each row of ``table``, counted from the
    row's column ``first``, are runs ([rows, steps a table] int32): step w is
    columns first + w*P .. + P - 1, a run where the last of them is before
    the row's column ``blocks`` and no step from one of them to the next
    breaks the ascent."""
    i32 = jnp.int32
    M = table.shape[1]
    start = first[:, None] + jnp.arange(-(-M // P), dtype=i32)[None] * P
    column = jnp.arange(M - 1, dtype=i32)  # the step from it to the next
    steps = (column >= start[..., None]) & (column < start[..., None] + P - 1)
    breaks = table[:, 1:] != table[:, :-1] + 1
    return ((start + P <= blocks[:, None]) & ~jnp.any(
        breaks[:, None] & steps, axis=2)).astype(i32)


def _shared_sets(block_table, ctx, block_size: int, min_sequences: int):
    """`shared_prefix_plan`'s sets: each sequence's place in the shared
    pass's results and its run, which sequences head a group, and the
    groups' (table row, run, members)."""
    i32 = jnp.int32
    B, M = block_table.shape
    G = SHARED_SEQUENCES
    rows = jnp.arange(B, dtype=i32)
    leader = jnp.argmax(
        block_table[:, :1] == block_table[None, :, 0], axis=1
    ).astype(i32)
    whole = (jnp.arange(1, M + 1, dtype=i32) * block_size)[None] < ctx[:, None]
    agree = (block_table == block_table[leader]) & whole
    own_run = jnp.sum(jnp.cumprod(agree.astype(i32), axis=1), axis=1)
    mates = leader[:, None] == leader[None, :]  # [B, B]: of one set
    run = jnp.min(jnp.where(mates, own_run[None, :], M), axis=1)
    rank = jnp.sum(mates & (rows[None, :] < rows[:, None]), axis=1)
    size = jnp.sum(mates, axis=1)
    place = rank % G  # within its group
    shares = (run > 0) & (
        jnp.minimum(G, size - (rank - place)) > min_sequences - 1)
    skip = jnp.where(shares, run, 0)
    heads = shares & (place == 0)
    # Groups are numbered in the row order of their first members.
    first_member = jnp.argmax(
        mates & (rank[None, :] == (rank - place)[:, None]), axis=1
    )
    group = jnp.where(shares, (jnp.cumsum(heads) - 1)[first_member], 0)
    slot = jnp.where(shares, group * G + place, 0).astype(i32)

    C = max(B // 2, 1)  # groups there can be: each has two members or more
    is_head = heads[None, :] & (group[None, :] == jnp.arange(C)[:, None])
    group_row = jnp.sum(jnp.where(is_head, rows[None, :], 0), axis=1)
    group_run = jnp.sum(jnp.where(is_head, run[None, :], 0), axis=1)
    holds = (shares[None, :]
             & (slot[None, :] == jnp.arange(C * G)[:, None]))  # [C*G, B]
    members = jnp.where(
        jnp.any(holds, axis=1),
        jnp.sum(jnp.where(holds, rows[None, :], 0), axis=1),
        jnp.repeat(group_row, G),  # an empty place asks again for the first
    )
    return slot, skip, heads, (group_row, group_run, members)


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "blocks_per_step", "mxu_native", "heads_first", "packed",
        "shared_blocks_per_step", "walk_blocks_per_wave", "latent", "scale",
    ),
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,
    kv_layer: jnp.ndarray,
    block_table: jnp.ndarray,
    context_len: jnp.ndarray,
    *,
    interpret: bool = False,
    blocks_per_step: int | None = None,
    mxu_native: bool = MXU_NATIVE,
    start: jnp.ndarray | None = None,
    heads_first: bool = False,
    packed: bool = False,
    plan: dict | None = None,
    shared_blocks_per_step: int = SHARED_BLOCKS_PER_STEP,
    walk_blocks_per_wave: int | None = None,
    latent: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """q: [B, H, D]; kv_layer: [num_blocks, 2, bs, Hkv, D], or
    ``heads_first``: [num_blocks, 2, Hkv, bs, D], or ``packed``:
    [num_blocks, bs, Hkv, 2*D] (the module's head says how a step's operand
    is made of each);
    block_table: [B, max_blocks] int32; context_len: [B] int32.
    ``start`` ([B] int32, window layers): the first position of the
    table a sequence still sees, as in ``paged_attention``.  Returns
    [B, H, D] in q.dtype.

    Every layout is walked: each sequence's own blocks are copied
    ``walk_blocks_per_wave`` at a time (``walk_wave``'s where not given: the
    tests' small tables ask for small waves), a wave that is a run in the
    pool by one copy.  Without ``heads_first`` and ``start``, runs of blocks
    that several tables begin with are first read once for the sequences that
    share them (the shared pass); heads-first slots, with a ``start`` or
    without, share nothing.  ``shared_prefix_plan``, which a model's decode
    step makes once for all the layers that see a table, for the same wave
    (with ``min_sequences`` None for a call that shares nothing), and hands
    in as ``plan``, is made here when it is not.  What is left of the grid of
    tables by steps serves a ``start`` over slots [2, bs, Hkv, D]
    (models/phi4flash.py's window layers): ``blocks_per_step`` blocks a grid
    step, which the caller states.

    ``mxu_native=True`` keeps the attention dots in the input dtype
    (bf16 operands, f32 accumulation) instead of upcasting K/V to f32 in
    VMEM.  The default serves ``llama`` (the same time on the chip as
    float32 operands and the same ``decode_logit_rel_err``: PERF.md
    section 6, PR 32); models/afmoe.py passes False, as it was measured.

    ``latent`` (the value's width): q is [B, H, W] in the latent space and
    kv_layer [num_blocks, bs/2, 2*W], a latent group's slots; returns
    [B, H, latent].  ``scale`` is the scores' (the query's width ** -0.5
    where not given: a latent query is wider than the head it stands for).
    """
    B, H, D = q.shape
    if scale is None:
        scale = D**-0.5
    if isinstance(latent, int):
        if heads_first or packed or not isinstance(start, type(None)):
            raise ValueError("latent slots are walked, whole contexts only")
        _, half, width = kv_layer.shape
        if width != 2 * D or not 0 < latent <= D:
            raise ValueError("a latent slot is two positions of q's width a row")
        # whole sublanes of heads (a block of the resumed state is a
        # sequence's heads): rows of zeros, whose output is dropped
        q = latent_query_layouts(
            jnp.pad(q, ((0, 0), (0, -H % 8), (0, 0))), latent)
        return _shared_pass_and_walk(
            q, kv_layer, block_table, context_len, plan, [],
            block_size=2 * half, groups=q.shape[-2], scale=scale,
            shared_blocks_per_step=shared_blocks_per_step,
            blocks_per_wave=walk_blocks_per_wave, mxu_native=mxu_native,
            packed=False, latent=latent, heads_first=False,
            interpret=interpret,
        )[:, :H]
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
    window = [a for a in (start,) if a is not None]
    if not heads_first:
        kv_layer = kv_layer.reshape(
            kv_layer.shape[: 1 if packed else 2]
            + (block_size * Hkv, q.shape[2])
        )
    # The shared pass and the walk; the walk alone for heads-first slots and
    # their window layers' tables.
    if heads_first or len(window) == 0:
        out = _shared_pass_and_walk(
            q, kv_layer, block_table, context_len, plan, window,
            block_size=block_size, groups=groups, scale=scale,
            shared_blocks_per_step=shared_blocks_per_step,
            blocks_per_wave=walk_blocks_per_wave, mxu_native=mxu_native,
            packed=packed, heads_first=heads_first, interpret=interpret,
        )
        return out[..., D:] if packed else out

    # The grid of tables by steps: a window layer of slots [2, bs, Hkv, D].
    if packed:
        raise ValueError("packed slots are walked, not stepped through")
    if blocks_per_step is None:
        raise ValueError("the grid of tables by steps: blocks_per_step")
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

    # The sequence's last valid block, once for all index maps: the scalar
    # core runs every operand's map twice a grid step, and a division in
    # each was a tenth of the kernel's time.
    scalars = [block_table, context_len, start,
               _last_block(context_len, block_size)]
    zeros = (0,) * (kv_layer.ndim - 1)
    kv_block = (1,) + kv_layer.shape[1:]

    def kv_index(i):
        # Sub-block i of step j; past-context steps revisit the last
        # valid block (an unchanged index skips the DMA).
        def index(b, j, table_ref, ctx_ref, start_ref, last_ref):
            return (table_ref[b, jnp.minimum(j * P_STEP + i, last_ref[b])],
                    ) + zeros

        return index

    def of_sequence(b, j, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((1, H, D), of_sequence, memory_space=pltpu.VMEM),
        ]
        + [
            pl.BlockSpec(kv_block, kv_index(i), memory_space=pltpu.VMEM)
            for i in range(P_STEP)
        ],
        out_specs=pl.BlockSpec(
            (1, H, D), of_sequence, memory_space=pltpu.VMEM
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
        scale=scale,
        blocks_per_step=P_STEP,
        mxu_native=mxu_native,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        *(a.astype(jnp.int32) for a in scalars),
        q,
        *([kv_layer] * P_STEP),
    )


def _last_block(context_len, block_size: int):
    return jnp.maximum((context_len - 1) // block_size, 0)


def walk_wave(kv_layer) -> int:
    """Blocks a wave of the walk of a pool [blocks, *slot], from what a slot
    weighs (the readings are beside the constants)."""
    slot_bytes = kv_layer.dtype.itemsize * math.prod(kv_layer.shape[1:])
    return min(max(WALK_WAVE_BYTES // slot_bytes, 1), WALK_WAVE_BLOCKS)


def _shared_pass_and_walk(q, kv_layer, block_table, context_len, plan, window,
                          *, block_size, shared_blocks_per_step,
                          blocks_per_wave, interpret, heads_first, **statics):
    """The shared pass over the plan's groups, then every sequence's walk of
    its own blocks, resumed from what the pass left: one sequence a grid
    step, the pool handed in where it lies.  A plan with no ``shared``
    (`shared_prefix_plan`'s ``min_sequences`` None) is walked with no pass
    before it: the only kind for heads-first slots, whose pass has no products
    yet, and for a ``window`` ([start] or []), which shares nothing."""
    B, H = q.shape[0], q.shape[-2]
    # what a head keeps of a block: the query's own lanes, or a latent's value
    Dq = statics.get("latent") or q.shape[-1]
    q_block, q_zeros = (1,) + q.shape[1:], (0,) * (q.ndim - 1)
    if blocks_per_wave is None:
        blocks_per_wave = walk_wave(kv_layer)
    windowed = len(window) == 1
    walk_only = heads_first or windowed
    if not isinstance(plan, dict):
        plan = shared_prefix_plan(
            block_table, context_len, block_size=block_size,
            blocks_per_wave=blocks_per_wave,
            shared_blocks_per_step=shared_blocks_per_step,
            **({"min_sequences": None} if walk_only else {}),
        )
    place, skip, runs = plan["walk"]
    columns = block_table.shape[1]
    if place.shape != (B,) or runs.shape != (B, -(-columns // blocks_per_wave)):
        raise ValueError("the plan was made for another table or wave")
    resumed = []
    if "shared" in plan:
        if walk_only:
            raise ValueError("heads-first slots and window starts share "
                             "nothing: a plan of min_sequences None")
        resumed = _shared_pass(
            q, kv_layer, block_table, plan,
            blocks_per_step=shared_blocks_per_step, interpret=interpret,
            **statics,
        )
    def of_sequence(b, *_):
        return (b, 0, 0)

    def q_of_sequence(b, *_):
        return (b,) + q_zeros

    def of_place(b, table_ref, ctx_ref, last_ref, place_ref, *_):
        return (place_ref[b], 0)

    scalars = (block_table, context_len,
               _last_block(context_len, block_size), place, skip, runs,
               *window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(B,),
        in_specs=[
            pl.BlockSpec(q_block, q_of_sequence, memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        + [
            pl.BlockSpec((H, a.shape[1]), of_place, memory_space=pltpu.VMEM)
            for a in resumed
        ],
        out_specs=pl.BlockSpec(
            (1, H, Dq), of_sequence, memory_space=pltpu.VMEM
        ),
        scratch_shapes=[
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, Dq), jnp.float32),
            # a wave's blocks as they lie in the pool: a run's one target
            pltpu.VMEM((WALK_BUFFERS, blocks_per_wave) + kv_layer.shape[1:],
                       kv_layer.dtype),
            pltpu.SemaphoreType.DMA((WALK_BUFFERS,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _walk_kernel, block_size=block_size, heads_first=heads_first,
            windowed=windowed, **statics),
        out_shape=jax.ShapeDtypeStruct((B, H, Dq), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(*(a.astype(jnp.int32) for a in scalars), q, kv_layer, *resumed)


def _shared_pass(q, kv_layer, block_table, plan, *, blocks_per_step,
                 interpret, **statics):
    """The shared pass over the plan's groups: the running maximum, the sum
    and the weighted values (float32, unnormalised) of each group member's
    heads over its group's run, as rows ``slot * H + head`` of three arrays
    ([.., 128], [.., 128], [.., D]).  Rows of places no sequence holds are
    never read."""
    H = q.shape[-2]
    Dq = statics.get("latent") or q.shape[-1]
    G = SHARED_SEQUENCES
    members, step_runs = plan["shared"][2:]
    C = members.shape[0] // G
    if step_runs.shape != (C, -(-block_table.shape[1] // blocks_per_step)):
        raise ValueError("the plan was made for another table or shared step")

    def q_index(i):
        def index(g, table_ref, row_ref, run_ref, members_ref, *_):
            return (members_ref[g * G + i],) + (0,) * (q.ndim - 1)

        return index

    def of_group(g, *_):
        return (g, 0)

    widths = (128, 128, Dq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(plan["shared"]),
        grid=(plan["shared_steps"],),
        in_specs=[
            pl.BlockSpec((1,) + q.shape[1:], q_index(i),
                         memory_space=pltpu.VMEM)
            for i in range(G)
        ]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((G * H, width), of_group, memory_space=pltpu.VMEM)
            for width in widths
        ],
        scratch_shapes=[
            # a step's blocks as they lie in the pool: a run's one target
            pltpu.VMEM((2, blocks_per_step) + kv_layer.shape[1:],
                       kv_layer.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        # the other KV heads' columns of a step's scores (a latent has none)
        + ([] if isinstance(statics.get("latent"), int) else [pltpu.VMEM(
            (G * H, blocks_per_step * kv_layer.shape[-2]), jnp.float32)]),
    )
    return pl.pallas_call(
        functools.partial(
            _shared_kernel, blocks_per_step=blocks_per_step, sequences=G,
            **statics,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((C * G * H, width), jnp.float32)
            for width in widths
        ],
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        *plan["shared"],
        *([q] * G),
        kv_layer,
    )
