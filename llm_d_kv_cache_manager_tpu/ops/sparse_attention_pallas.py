"""Learned sparse attention over the paged pool: the indexer's scores, the
exact pick of the best ``K`` positions a query, and attention over the picked
positions only (models/keyevl2.py).

An indexer scores every cached position for every query,
``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))`` over ``HI`` small heads
against ONE selector key a position, and attention sees the ``K`` positions
with the largest score (ties to the earlier position; all of them while fewer
than ``K`` exist).  The pool's slot (``KVGroupSpec``'s selected kind) is
[block + t, 2*Hkv, Dh]: a tile a position (its K heads' rows, then its V
heads'), then the block's selector keys.

- ``sparse_index_scores_pallas``: a chunk of queries against the selector keys of a
  whole table, a tile of [queries, positions] a grid step: ``HI`` products
  against the same keys, summed under their weights in float32.  What a query
  may not see (a later position) is ``-inf``.
- ``kth_largest`` / ``topk_mask``: the exact pick, in XLA.  The ``K``-th
  largest score of a row is found by bisection over the floats' ordered bits
  (32 counting passes, no sort); equal scores at the threshold are admitted
  in position order until ``K`` are picked, which is `lax.top_k`'s rule.
- ``sparse_prefill_attention_pallas``: a prefill's attention under the picks,
  over the table's blocks **where they lie** (no gather of a cached prefix;
  a miss is the same kernel from position 0, a chunk of queries at a time, so
  no ``[T, T]`` array exists at any length).  ops/flash_pallas.py's
  ``_paged_kernel`` with one more operand: a step's [tile, positions] piece of
  the picks, as an additive bias (0 picked, ``NEG_INF`` not), copied beside the
  step's blocks; causality is the picks' (nothing later is ever picked).
- ``sparse_decode_scores_pallas``: a decode step's ``I``, one query a
  sequence over its own table's selector keys, read from the pool where they
  lie, a wave of blocks at a time (a wave that is a run in the pool by one
  copy).
- ``picked_tiles``: a row's picks as rows of the pool seen a tile a row, in
  position order, by counting and small products (XLA): what a decode step
  hands a gather.
- ``latent_index_scores_pallas`` / ``picked_latent_rows``: the same two over
  a pool whose slot is a latent vector and a selector key a position
  (``KVGroupSpec``'s latent-selected kind, models/deepseekv32.py): the walk
  copies a row's last lanes only, its two positions' keys, and a pick is a
  row of the pool and which half of it.

Products in the serving type with float32 sums, as everywhere on this path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
INT_MIN = -(2**31)
# The index-score kernel's tile: 256 queries against 1024 positions is 1 MB
# of float32 scores, and the HI products of a tile share one copy of the keys.
SCORE_Q_TILE = 256
SCORE_KEY_TILE = 1024
# The prefill kernel's tile of query positions (all heads keep their state)
# and pool blocks a step (64 x 16 = 1024 positions).
PREFILL_Q_TILE = 256
PREFILL_BLOCKS_PER_STEP = 64
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


# ------------------------------------------------------------ the exact pick


def ordered_key(x):
    """float32 -> int32 whose order is the floats' (``-0.0`` as ``0.0``)."""
    bits = lax.bitcast_convert_type(
        jnp.where(x == 0, 0.0, x).astype(jnp.float32), jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def kth_largest(key, k: int):
    """key: [..., L] int32 -> [...] the ``k``-th largest value of each row
    (the least int32 where a row has fewer than ``k`` elements), by bisection:
    the largest t with ``count(key >= t) >= k``, built bit by bit from the
    sign down.  32 counting passes over the rows, and nothing is sorted."""

    def enough(t):
        return jnp.sum(key >= t[..., None], axis=-1, dtype=jnp.int32) >= k

    lead = key.shape[:-1]
    zero = jnp.zeros(lead, jnp.int32)
    thr = jnp.where(enough(zero), zero, jnp.full(lead, INT_MIN, jnp.int32))

    def bit(i, thr):
        cand = thr | (jnp.int32(1) << (30 - i))
        return jnp.where(enough(cand), cand, thr)

    return lax.fori_loop(0, 31, bit, thr)


def _lane_ranks(mask):
    """mask: [..., L] bool -> (the inclusive count of true entries within
    each row of 128 lanes, float32 [..., R, 128]; the rows' totals, int32
    [..., R]).  One product against a triangle: every number it carries is a
    count of at most 128, exact in the serving type."""
    L = mask.shape[-1]
    pad = [(0, 0)] * (mask.ndim - 1) + [(0, -L % 128)]
    lanes = jnp.pad(mask, pad).reshape(mask.shape[:-1] + (-1, 128))
    tri = jnp.triu(jnp.ones((128, 128), jnp.bfloat16))
    rank = jnp.einsum("...l,lm->...m", lanes.astype(jnp.bfloat16), tri,
                      preferred_element_type=jnp.float32)
    return rank, rank[..., -1].astype(jnp.int32)


def topk_mask(scores, k: int):
    """scores: [..., L] float32, ``-inf`` what a row may not see -> bool
    [..., L]: the ``k`` positions with the largest score of each row, equal
    scores to the earlier position; every position it may see where those are
    fewer than ``k``.  Exact (what `lax.top_k` picks)."""
    L = scores.shape[-1]
    key = ordered_key(scores)
    thr = kth_largest(key, k)[..., None]
    above, equal = key > thr, key == thr
    need = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    # equal scores are admitted in position order: their running count, by
    # lanes of 128 (a product) and the rows' offsets (a short sum)
    rank, count = _lane_ranks(equal)
    before = jnp.cumsum(count, axis=-1) - count
    admitted = (rank.astype(jnp.int32) + before[..., None]).reshape(
        scores.shape[:-1] + (-1,))[..., :L] <= need
    return (above | (equal & admitted)) & (scores > -jnp.inf)


def picked_tiles(picked, block_table, k: int, block_size: int,
                 slot_tiles: int):
    """picked: [B, L] bool, at most ``k`` true a row; block_table: [B, n]
    int32, n * block_size >= L -> (tiles [B, k] int32: the picked positions,
    in order, as rows of a pool of selected slots seen a tile a row, ``slot *
    slot_tiles + position in block``; positions [B, k] int32; which of the k
    are picks [B, k] bool; the rest name tile 0 of the table's first block).
    No scatter, no sort and no gather of single numbers, each of which costs
    the chip a step an element: ranks within rows of 128 lanes
    (`_lane_ranks`), the rows' offsets by a short cumulative sum; an output
    slot finds its row by counting offsets, its lane by counting ranks, and
    its block's slot by a product of its row's one-hot against the table, a
    byte at a time (a byte is exact in the serving type)."""
    B, n = block_table.shape
    if 128 % block_size:
        raise ValueError("a row of 128 lanes holds whole blocks")
    per = 128 // block_size  # blocks a row of lanes
    rank, count = _lane_ranks(picked)  # [B, R, 128], [B, R]
    R = count.shape[-1]
    upto = jnp.cumsum(count, axis=-1)
    slot = jnp.arange(k, dtype=jnp.int32)
    earlier = upto[:, None, :] <= slot[None, :, None]  # [B, k, R]
    row = jnp.minimum(jnp.sum(earlier, axis=-1, dtype=jnp.int32), R - 1)
    before = jnp.sum(jnp.where(earlier, count[:, None, :], 0), axis=-1)
    valid = slot[None] < upto[:, -1:]
    own = (row[:, :, None] == jnp.arange(R)[None, None, :]).astype(jnp.bfloat16)
    ranks = jnp.einsum("bkr,brl->bkl", own, rank.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    lane = jnp.sum(ranks < (slot[None] - before + 1)[:, :, None], axis=-1,
                   dtype=jnp.int32)
    lane = jnp.where(valid, lane, 0)
    row = jnp.where(valid, row, 0)
    # the table by rows of lanes, and the slot of (row, lane // block_size)
    table = jnp.pad(block_table.astype(jnp.int32),
                    ((0, 0), (0, R * per - n))).reshape(B, R, per)
    block = jnp.zeros((B, k, per), jnp.int32)
    for shift in range(0, 32, 8):
        byte = ((table >> shift) & 255).astype(jnp.bfloat16)
        block += jnp.einsum("bkr,brc->bkc", own, byte,
                            preferred_element_type=jnp.float32
                            ).astype(jnp.int32) << shift
    block = jnp.sum(jnp.where(
        (lane // block_size)[:, :, None] == jnp.arange(per), block, 0), axis=-1)
    block = jnp.where(valid, block, block_table[:, :1])
    return (block * slot_tiles + lane % block_size, row * 128 + lane, valid)


def picked_latent_rows(picked, block_table, k: int, block_size: int):
    """``picked_tiles`` for a pool of latent-selected slots ([slots,
    block_size / 2, ..], a row two positions): -> (rows [B, k] int32: the
    picked positions, in order, as rows of the pool seen a row a line, ``slot
    * block_size / 2 + position % (block_size / 2)``; second [B, k] bool: the
    position is its row's second; positions [B, k] int32; which of the k are
    picks [B, k] bool)."""
    half = block_size // 2
    at, positions, valid = picked_tiles(picked, block_table, k, block_size,
                                        block_size)
    inside = at % block_size
    return (at // block_size * half + inside % half, inside >= half,
            positions, valid)


# ---------------------------------------------------------- the index scores


def _score_kernel(offset_ref, q_ref, w_ref, k_ref, out_ref, *, tq, tk):
    qi, ki = pl.program_id(0), pl.program_id(1)
    q_start = offset_ref[0] + qi * tq

    @pl.when(ki * tk > q_start + tq - 1)
    def _later():  # every position of the tile lies after every query
        out_ref[...] = jnp.full_like(out_ref, -jnp.inf)

    @pl.when(ki * tk <= q_start + tq - 1)
    def _scores():
        keys = k_ref[...]
        acc = jnp.zeros(out_ref.shape, jnp.float32)
        for j in range(q_ref.shape[0]):
            s = lax.dot_general(
                q_ref[j], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [tq, tk]
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        row = lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        seen = ki * tk + col <= q_start + row
        out_ref[...] = jnp.where(seen, acc, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("q_tile", "key_tile",
                                             "interpret"))
def sparse_index_scores_pallas(q, w, keys, *, q_offset, q_tile: int = SCORE_Q_TILE,
                        key_tile: int = SCORE_KEY_TILE,
                        interpret: bool = False):
    """``I(t, s) = sum_j w_j(t) relu(q_j(t) . keys(s))`` for the queries at
    positions ``q_offset ..`` (data) over positions 0 .. L-1, ``-inf`` where
    s > t.  q: [Tq, HI, dI] and keys: [L, dI] in the serving type; w: [Tq, HI]
    float32.  Returns [Tq, L] float32."""
    Tq, HI, dI = q.shape
    L = keys.shape[0]
    tq = min(q_tile, -(-Tq // 8) * 8)
    tk = min(key_tile, -(-L // 128) * 128)
    qp, kp = (-Tq) % tq, (-L) % tk
    q = jnp.pad(q, ((0, qp), (0, 0), (0, 0))).swapaxes(0, 1)  # [HI, Tq, dI]
    w = jnp.pad(w.astype(jnp.float32), ((0, qp), (0, 0)))
    keys = jnp.pad(keys, ((0, kp), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_score_kernel, tq=tq, tk=tk),
        out_shape=jax.ShapeDtypeStruct((Tq + qp, L + kp), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=((Tq + qp) // tq, (L + kp) // tk),
            in_specs=[
                pl.BlockSpec((HI, tq, dI), lambda qi, ki, *_: (0, qi, 0)),
                pl.BlockSpec((tq, HI), lambda qi, ki, *_: (qi, 0)),
                pl.BlockSpec((tk, dI), lambda qi, ki, *_: (ki, 0)),
            ],
            out_specs=pl.BlockSpec((tq, tk), lambda qi, ki, *_: (qi, ki)),
        ),
        interpret=interpret,
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), q, w, keys)
    return out[:Tq, :L]


# ------------------------------------------- a prefill's attention, as picked


def _attend(q, k, v, bias, m_ref, l_ref, acc_ref):
    """One step of the online softmax of one head: q [tq, Dh] (scaled) and
    k, v [width, Dh] in the serving type, bias [tq, width] float32 (0 where
    the row picked the position, NEG_INF where not); the running maximum,
    sum (both lane-replicated [tq, 128]) and weighted values [tq, Dh],
    float32, updated in place."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) + bias
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * correction + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _prefill_kernel(
    table_ref,  # SMEM [B, n_blocks] int32 (scalar prefetch)
    offset_ref,  # SMEM [1] int32: the position of the first query
    q_ref,  # VMEM [1, Hkv, G, tq, Dh]
    pool_ref,  # HBM [slots, bs + t, 2*Hkv, Dh]: the pool where it lies
    bias_ref,  # HBM [B, Tq, L] float32: 0 picked, NEG_INF not
    out_ref,  # VMEM [1, Hkv, G, tq, Dh]
    buf,  # VMEM [2, P, bs, 2*Hkv, Dh]: two steps' tiles as they lie
    bias_buf,  # VMEM [2, tq, P*bs] float32
    sem,  # DMA [2, 2]: the blocks' and the picks', a buffer each
    kv_ref,  # VMEM [P*bs*2*Hkv, Dh] f32: a step's rows, widened
    acc_ref,  # VMEM [Hkv, G, tq, Dh] f32
    m_ref, l_ref,  # VMEM [Hkv, G, tq, 128] f32
    *,
    scale: float,
):
    b, qi = pl.program_id(0), pl.program_id(1)
    _, Hkv, G, tq, Dh = q_ref.shape
    P, bs, rows = buf.shape[1], buf.shape[2], buf.shape[3]
    width = P * bs
    q_start = offset_ref[0] + qi * tq
    n_steps = pl.cdiv(q_start + tq, width)  # nothing later is ever picked

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(j, i, half):
        return pltpu.make_async_copy(
            pool_ref.at[table_ref[b, j * P + i], pl.ds(0, bs)],
            buf.at[half, i], sem.at[0, half])

    def bias_copy(j, half):
        return pltpu.make_async_copy(
            bias_ref.at[b, pl.ds(qi * tq, tq), pl.ds(j * width, width)],
            bias_buf.at[half], sem.at[1, half])

    def start(j, half):
        bias_copy(j, half).start()

        def one(i, _):
            copies(j, i, half).start()
            return 0

        lax.fori_loop(0, P, one, 0)

    start(0, 0)

    def step(j, _):
        half = lax.rem(j, 2)

        @pl.when(j + 1 < n_steps)
        def _next():
            start(j + 1, 1 - half)

        bias_copy(j, half).wait()

        def one(i, _):
            copies(j, i, half).wait()
            return 0

        lax.fori_loop(0, P, one, 0)
        # rows as in a slot, a position's K heads then its V heads; float32,
        # because a strided read of one head's rows is of 32-bit rows only
        kv_ref[...] = buf[half].reshape(width * rows, Dh).astype(jnp.float32)
        bias = bias_buf[half]

        def head(hg, _):
            h, g = lax.div(hg, G), lax.rem(hg, G)
            q = (q_ref[0, h, g].astype(jnp.float32) * scale).astype(
                buf.dtype)
            k = kv_ref[pl.ds(h, width, stride=rows), :].astype(buf.dtype)
            v = kv_ref[pl.ds(Hkv + h, width, stride=rows), :].astype(
                buf.dtype)
            _attend(q, k, v, bias, m_ref.at[h, g], l_ref.at[h, g],
                    acc_ref.at[h, g])
            return 0

        lax.fori_loop(0, Hkv * G, head, 0)
        return 0

    lax.fori_loop(0, n_steps, step, 0)
    out_ref[0] = (acc_ref[...] / l_ref[:, :, :, :1]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_tile", "blocks_per_step",
                                             "interpret"))
def sparse_prefill_attention_pallas(
    q, kv_pool, block_table, picked, *, q_offset,
    q_tile: int = PREFILL_Q_TILE,
    blocks_per_step: int = PREFILL_BLOCKS_PER_STEP,
    interpret: bool = False,
):
    """GQA attention of the queries at positions ``q_offset ..`` (data) over
    the positions each picked, in the pool's blocks where they lie.
    q: [B, Tq, H, Dh]; kv_pool: [slots, bs + t, 2*Hkv, Dh], one layer's pool
    of a selected group (``KVGroupSpec.layer_shape``); block_table: [B, n]
    int32, the slots that hold positions 0 .. n*bs - 1 >= q_offset + Tq - 1,
    the queries' own K/V among them (the caller writes them first); picked:
    [B, Tq, n*bs] bool, what each query attends over (nothing after its own
    position, and something).  Only the table's blocks up to a tile's last
    position are read, each once a tile.  Returns [B, Tq, H, Dh] in
    q.dtype."""
    B, Tq, H, Dh = q.shape
    rows = kv_pool.shape[2]
    Hkv = rows // 2
    G = H // Hkv
    n = block_table.shape[1]
    bs = picked.shape[-1] // n
    tq = min(q_tile, -(-Tq // 8) * 8)
    P = min(blocks_per_step, n)
    qp, bp = (-Tq) % tq, (-n) % P
    qt = jnp.pad(q, ((0, 0), (0, qp), (0, 0), (0, 0)))
    qt = qt.reshape(B, Tq + qp, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)
    bias = jnp.where(picked, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.pad(bias, ((0, 0), (0, qp), (0, bp * bs)),
                   constant_values=NEG_INF)
    table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, bp)),
                    mode="edge")
    tile = pl.BlockSpec((1, Hkv, G, tq, Dh),
                        lambda b, qi, *_: (b, 0, 0, qi, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=Dh**-0.5),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, (Tq + qp) // tq),
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, P, bs, rows, Dh), kv_pool.dtype),
                pltpu.VMEM((2, tq, P * bs), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((P * bs * rows, Dh), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, Dh), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, 128), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, 128), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(table, jnp.asarray(q_offset, jnp.int32).reshape(1), qt, kv_pool, bias)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq + qp, H, Dh)
    return out[:, :Tq]


# ------------------------------------------- a decode step's scores, walked

# Blocks a wave of the score kernel's walk: their key tiles (2 KB each at the
# published sizes) are one operand of a wave's product.
SCORE_WAVE_BLOCKS = 64


def _walk_scores_kernel(
    table_ref,  # SMEM [B, n] int32 (scalar prefetch)
    runs_ref,  # SMEM [B, n / W] int32: a wave's blocks lie one after another
    count_ref,  # SMEM [B] int32: waves that hold a position of the context
    q_ref,  # VMEM [1, per*HI, lanes]: the heads' queries, a lane group a copy
    w_ref,  # VMEM [1, per*HI, 1] float32
    pool_ref,  # HBM [slots, *slot]
    out_ref,  # VMEM [1, per, n * R]
    buf,  # VMEM [2, W, *a slot's keys]: R rows of `lanes` a block in all
    sem,  # DMA [2]
    *,
    keys_at: tuple,
    heads: int,
):
    """``keys_at``: where a slot's selector keys lie, the index of a slot's
    piece after its number: the tiles behind the positions' of a selected
    slot, the last lanes of every row of a latent-selected one."""
    b = pl.program_id(0)
    W, lanes = buf.shape[1], buf.shape[-1]
    R = math.prod(buf.shape[2:-1])
    per = q_ref.shape[1] // heads
    n_waves = count_ref[b]

    def whole(j, slot):
        return pltpu.make_async_copy(
            pool_ref.at[(pl.ds(table_ref[b, j * W], W),) + keys_at],
            buf.at[slot], sem.at[slot])

    def single(j, i, slot):
        return pltpu.make_async_copy(
            pool_ref.at[(table_ref[b, j * W + i],) + keys_at],
            buf.at[slot, i], sem.at[slot])

    def each(j, slot, what):
        @pl.when(runs_ref[b, j] == 1)
        def _run():
            what(whole(j, slot))

        @pl.when(runs_ref[b, j] == 0)
        def _blocks():
            def one(i, _):
                what(single(j, i, slot))
                return 0

            lax.fori_loop(0, W, one, 0)

    @pl.when(n_waves > 0)
    def _first():
        each(0, 0, lambda copy: copy.start())

    q, w = q_ref[0], w_ref[0]

    def wave(j, _):
        slot = lax.rem(j, 2)

        @pl.when(j + 1 < n_waves)
        def _next():
            each(j + 1, 1 - slot, lambda copy: copy.start())

        each(j, slot, lambda copy: copy.wait())
        keys = buf[slot].reshape(W * R, lanes)
        s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = (jnp.maximum(s, 0.0) * w).reshape(per, heads, W * R).sum(axis=1)
        out_ref[0, :, pl.ds(pl.multiple_of(j * W * R, W * R), W * R)] = s
        return 0

    lax.fori_loop(0, n_waves, wave, 0)


def _walked_scores(q, w, kv_pool, block_table, context_len, *, keys_at,
                   keys_shape, block_size, wave_blocks, interpret):
    """The walk both pools' decode scores share: ``keys_shape`` is a slot's
    keys as ``keys_at`` cuts them out, R rows of ``per`` positions side by
    side; row r of lane group g holds position ``g * R + r`` of its block."""
    B, HI, dI = q.shape
    N, bs = kv_pool.shape[0], block_size
    lanes = keys_shape[-1]
    per, R = lanes // dI, math.prod(keys_shape[:-1])
    n = block_table.shape[1]
    W = min(wave_blocks, n)
    pad = (-n) % W
    table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, pad)),
                    mode="edge")
    waves = table.reshape(B, -1, W)
    runs = (jnp.all(waves == waves[:, :, :1] + jnp.arange(W), axis=-1)
            & (waves[:, :, 0] + W <= N)).astype(jnp.int32)
    count = -(-context_len.astype(jnp.int32) // (W * bs))
    # the heads' queries once a lane group: row g * HI + j holds q_j in the
    # lanes of a row's g-th position, so one product scores a row's positions
    eye = jnp.eye(per, dtype=q.dtype)
    q2 = jnp.einsum("gh,bjd->bgjhd", eye, q).reshape(B, per * HI, lanes)
    w2 = jnp.tile(w.astype(jnp.float32), (1, per))[..., None]
    out = pl.pallas_call(
        functools.partial(_walk_scores_kernel, keys_at=keys_at, heads=HI),
        out_shape=jax.ShapeDtypeStruct((B, per, (n + pad) * R), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, per * HI, lanes), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, per * HI, 1), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, per, (n + pad) * R),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, W) + tuple(keys_shape),
                                       kv_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        interpret=interpret,
    )(table, runs, count, q2, w2, kv_pool)
    # [B, per, blocks, R] -> positions in order: block, lane group, row
    scores = out.reshape(B, per, n + pad, R).transpose(0, 2, 1, 3).reshape(
        B, -1)[:, :n * bs]
    seen = jnp.arange(n * bs)[None, :] < context_len[:, None]
    return jnp.where(seen, scores, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("selector_dim", "wave_blocks",
                                             "interpret"))
def sparse_decode_scores_pallas(q, w, kv_pool, block_table, context_len, *,
                                selector_dim: int,
                                wave_blocks: int = SCORE_WAVE_BLOCKS,
                                interpret: bool = False):
    """``I`` of one query a sequence over its own table's selector keys, read
    from the pool where they lie, a wave of blocks at a time; a wave whose
    blocks lie one after another in the pool (as a fresh pool's allocator
    deals a prompt's blocks out) comes by ONE copy, a tile a block, the rest
    by a copy a block.  q: [B, HI, dI] in the serving type; w: [B, HI]
    float32; kv_pool: [slots, bs + t, rows, Dh]; block_table: [B, n] int32;
    context_len: [B].  Returns [B, n * bs] float32, ``-inf`` past the
    context."""
    dI = q.shape[-1]
    N, slot_tiles, rows, Dh = kv_pool.shape
    t = slot_tiles * dI // (rows * Dh + dI)  # bs + t tiles, bs * dI = t * tile
    bs = slot_tiles - t
    return _walked_scores(
        q, w, kv_pool, block_table, context_len, keys_at=(pl.ds(bs, t),),
        keys_shape=(t, rows, Dh), block_size=bs, wave_blocks=wave_blocks,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("latent_dim", "wave_blocks",
                                             "interpret"))
def latent_index_scores_pallas(q, w, kv_pool, block_table, context_len, *,
                               latent_dim: int,
                               wave_blocks: int = SCORE_WAVE_BLOCKS,
                               interpret: bool = False):
    """``sparse_decode_scores_pallas`` over a pool of latent-selected slots:
    kv_pool [slots, bs / 2, 2 * latent_dim + 2 * dI], a row its two
    positions' latents and then their two selector keys, of which the walk
    copies the keys alone (a wave that is a run in the pool by one copy of
    the rows' last lanes).  q: [B, HI, dI] in the serving type; w: [B, HI]
    float32; block_table: [B, n] int32; context_len: [B].  Returns
    [B, n * bs] float32, ``-inf`` past the context."""
    dI = q.shape[-1]
    _, half, width = kv_pool.shape
    if width != 2 * latent_dim + 2 * dI:
        raise ValueError("a latent-selected row is two latents and two keys")
    return _walked_scores(
        q, w, kv_pool, block_table, context_len,
        keys_at=(slice(None), pl.ds(2 * latent_dim, 2 * dI)),
        keys_shape=(half, 2 * dI), block_size=2 * half,
        wave_blocks=wave_blocks, interpret=interpret)
