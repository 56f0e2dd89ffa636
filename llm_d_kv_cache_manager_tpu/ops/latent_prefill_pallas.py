"""Pallas TPU prefill attention in the latent space, over the paged pool.

Latent attention (models/glm4moelite.py) caches one vector a position a
layer, key and value at once: every query head scores over all its ``W``
lanes and takes its first ``value_dim`` lanes, weighted, as the output (the
per-head keys and values are products of that vector, folded into the query
and applied to the output outside).  A prefill writes its positions' vectors
into the pool first and then attends here, over the table's blocks **where
they lie**: the cached prefix of a hit is neither gathered nor up-projected
to heads (15 872 positions x 20 heads x 448 would be 285 MB a layer), and a
miss is the same kernel from position 0, so no ``[T, T]`` scores exist at any
length and no VMEM bound on the context either (ops/flash_pallas.py's
``_flash_kernel`` stages a head's whole K and V: 8192 positions at head
size 256).

A grid step is a tile of ``q_tile`` query positions, all heads: its rows
(position-major) keep their online-softmax state (float32) in VMEM while the
blocks stream past, ``blocks_per_step`` at a time, each block by a copy of
its own into one of two buffers (the next step's arrive while this one's are
multiplied), as ops/flash_pallas.py's ``_paged_kernel`` and
ops/paged_decode_pallas.py's shared pass do.  A step's blocks are ONE operand
as they lie, [P*bs/2, 2*W], a row two positions
(``kv_cache_pool.pack_latent_blocks``), scored and weighed by
``paged_decode_pallas._attend_latent``: products in the serving type,
float32 sums.  Steps wholly before the tile's first position take no mask;
from there to its last position the causal mask.  The pool crosses HBM once
a tile.

``latent_picked_prefill_pallas`` (at the foot) is the same walk for a cache
whose queries see picked positions only (models/deepseekv32.py): one more
operand, the picks as an additive bias, over a pool whose rows carry the
positions' selector keys behind their latents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    NEG_INF,
    _attend_latent,
    _latent_queries,
    latent_query_layouts,
)

# Query positions a tile and pool blocks a step (512 keys), where the caller
# states none: 64 x 20 heads = 1280 rows against 512 keys is 2.6 MB of
# float32 scores; the tile's state, its query rows in both layouts and two
# steps' blocks come to about 18 MB of VMEM.
Q_TILE = 64
BLOCKS_PER_STEP = 32
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _prefill_kernel(
    table_ref,  # SMEM [B, n_blocks] int32 (scalar prefetch)
    offset_ref,  # SMEM [1] int32: the position of the first query
    q_ref,  # VMEM [1, 2, tq*H, 2*W - value]: the rows' two layouts
    pool_ref,  # HBM [slots, bs/2, 2*W]: the pool where it lies
    out_ref,  # VMEM [1, tq*H, value]
    buf,  # VMEM [2, P, 1, bs/2, 2*W]: two steps' blocks as they lie
    sem,  # DMA [2]
    m_ref, l_ref, acc_ref,  # VMEM [tq*H, 128], [tq*H, 128], [tq*H, value] f32
    *,
    q_tile: int,
    heads: int,
    value: int,
    scale: float,
):
    b, qi = pl.program_id(0), pl.program_id(1)
    P, half = buf.shape[1], buf.shape[3]
    width = P * 2 * half  # positions a step
    n_blocks = table_ref.shape[1]
    q_start = offset_ref[0] + qi * q_tile  # the position of the tile's first rows

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    n_clear = q_start // width  # steps every row sees whole
    n_steps = pl.cdiv(q_start + q_tile, width)

    def copy(j, i, slot):
        # past the table's end its last block again, as if it lay after
        # every query's own position: the causal mask hides it
        return pltpu.make_async_copy(
            pool_ref.at[
                pl.ds(table_ref[b, jnp.minimum(j * P + i, n_blocks - 1)], 1)
            ],
            buf.at[slot, i],
            sem.at[slot],
        )

    def start(j, slot):
        def one(i, _):
            copy(j, i, slot).start()
            return 0

        jax.lax.fori_loop(0, P, one, 0)

    q = _latent_queries([q_ref], scale, q_ref.dtype)
    start(0, 0)

    def step(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_steps)
        def _next():
            start(j + 1, 1 - slot)

        def one(i, _):
            copy(j, i, slot).wait()
            return 0

        jax.lax.fori_loop(0, P, one, 0)
        slab = buf[slot].reshape(P * half, -1)

        def causal(i, s):
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # column c is row c % half of block c // half: its first
            # position (i = 0) or its second
            key = j * width + col + jax.lax.div(col, half) * half + i * half
            return jnp.where(key <= q_start + jax.lax.div(row, heads), s,
                             NEG_INF)

        def attend(hide):
            _attend_latent(q, slab, hide, m_ref, l_ref, acc_ref, value=value)

        @pl.when(j < n_clear)
        def _clear():
            attend(lambda i, s: s)

        @pl.when(j >= n_clear)
        def _own():
            attend(causal)

        return 0

    jax.lax.fori_loop(0, n_steps, step, 0)
    out_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("value_dim", "scale", "q_tile", "blocks_per_step",
                     "interpret"),
)
def latent_prefill_attention_pallas(
    q: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    *,
    q_offset,
    value_dim: int,
    scale: float,
    q_tile: int = Q_TILE,
    blocks_per_step: int = BLOCKS_PER_STEP,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal attention in the latent space of the queries at positions
    ``q_offset ..`` (data: a long prefill calls one compiled kernel a chunk
    of its queries) over the pool's blocks where they lie.
    q: [B, Tq, H, W], each head's query in the latent space; kv_pool:
    [slots, bs/2, 2*W], one layer's pool of a latent group
    (``KVGroupSpec.layer_shape``); block_table: [B, n] int32, the slots that
    hold positions 0 .. n*bs - 1 >= q_offset + Tq - 1 (the caller's to
    see to: the offset is data), the queries' own latents among them (the
    caller writes them first).  Only the table's
    blocks are read, each once a tile; they hold numbers.  Returns
    [B, Tq, H, value_dim] in q.dtype: sum over the positions a query sees of
    softmax(q . latent * scale) times the latent's first ``value_dim``
    lanes."""
    B, Tq, H, W = q.shape
    _, half, width = kv_pool.shape
    if width != 2 * W or not 0 < value_dim <= W:
        raise ValueError("a latent slot is two positions of q's width a row")
    tq = min(q_tile, -(-Tq // 8) * 8)
    pad = (-Tq) % tq
    q = latent_query_layouts(
        jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))), value_dim
    ).reshape(B, 2, (Tq + pad) * H, 2 * W - value_dim)
    rows = tq * H
    kernel = functools.partial(
        _prefill_kernel, q_tile=tq, heads=H, value=value_dim, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, (Tq + pad) * H, value_dim),
                                       q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, (Tq + pad) // tq),
            in_specs=[
                pl.BlockSpec((1, 2, rows, q.shape[-1]),
                             lambda b, qi, *_: (b, 0, qi, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, value_dim),
                                   lambda b, qi, *_: (b, qi, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, blocks_per_step, 1, half, width),
                           kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        q,
        kv_pool,
    )
    return out.reshape(B, Tq + pad, H, value_dim)[:, :Tq]


# ------------------------------------- under the picks of a learned selector

# Learned sparse attention over a latent cache (models/deepseekv32.py): a
# query attends over the positions an indexer picked for it, which arrive as
# one more operand, an additive bias (0 picked, NEG_INF not), as
# ops/sparse_attention_pallas.py's prefill kernel gave `flash_pallas`'s one.
# The pool's slot is the latent-selected kind's, [bs/2, 2*W + 2*dI]: a step
# copies the rows' first 2*W lanes, which are the latent kind's rows.
#
# The tile at 128 heads (the published sizes: W 576, value 512): a tile's
# rows are positions x heads, and 128 positions x 128 heads in the latent
# space would be 37.7 MB of float32 state alone.  32 positions are 4096 rows
# (`glm-4.7-flash-l5`'s tile of 128 x 20 heads is 2560): the rows' two
# layouts 10.5 MB in the serving type, their state 12 MB, a step's scores and
# weights over 32 blocks 16 MB.  Read on the chip, kernel alone at the cell's
# shapes (512 queries over 32 768 positions, one layer, 4.7 TFLOP computed; my
# chip run, PR 53; ms a call): tiles of 8 / 16 / 32 positions at 32 blocks a
# step 34.7 / 31.5 / 29.9 (76 % of the chip's bfloat16 peak); 16 positions at
# 64 blocks 31.4.
#
# The latent space against the per-head form (the published code takes the
# per-head form for a prefill: 192 + 128 lanes a head where the latent space
# has 576 + 512, but the cache holds latents, so a hit sends all its 32 768
# positions up through W_uk and W_uv again).  Same shapes, same call
# (hack/deepseekv32_alone.py; my chip run, PR 53, second session; ms a layer):
# this kernel 30.0; the per-head form in plain XLA under the same picks 75.2
# (its scores cross HBM); its parts where a kernel exists: the up-projection
# alone 15.9 in XLA, attention alone 8.8 and 14.7 in JAX's Pallas flash kernel
# at head sizes 128 and 256 over K and V already up-projected and without the
# picks (192 / 128 lies between: about 10.5), so 26-27 with a kernel this repo
# does not have, for 2.7 GB of K and V a layer where weights and pool hold
# 11.7 of the chip's 15.75 GB.
# The latent form is kept: a tenth slower than the best the per-head form
# could reach with today's parts, in place, no temporaries.  A per-head kernel
# that up-projects a step's latents in VMEM is ROADMAP R-M6 (c).
PICKED_Q_TILE = 32
PICKED_BLOCKS_PER_STEP = 32
PICKED_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _picked_prefill_kernel(
    table_ref,  # SMEM [B, n_blocks] int32 (scalar prefetch)
    offset_ref,  # SMEM [1] int32: the position of the first query
    q_ref,  # VMEM [1, 2, tq*H, 2*W - value]: the rows' two layouts
    pool_ref,  # HBM [slots, bs/2, 2*W + 2*dI]: the pool where it lies
    bias_ref,  # HBM [B, 2, Tq, n*bs/2] float32: 0 picked, NEG_INF not; [:, i]
    # column c the i-th position of row c % (bs/2) of block c // (bs/2)
    out_ref,  # VMEM [1, tq*H, value]
    buf,  # VMEM [2, P, 1, bs/2, 2*W]: two steps' latent rows as they lie
    bias_buf,  # VMEM [2, 2, tq, P*bs/2] float32
    sem,  # DMA [2, 2]: the blocks' and the picks', a buffer each
    m_ref, l_ref, acc_ref,  # VMEM [tq*H, 128], [tq*H, 128], [tq*H, value] f32
    *,
    q_tile: int,
    heads: int,
    value: int,
    scale: float,
):
    b, qi = pl.program_id(0), pl.program_id(1)
    P, half, lanes = buf.shape[1], buf.shape[3], buf.shape[4]
    width = P * 2 * half  # positions a step
    q_start = offset_ref[0] + qi * q_tile
    # nothing later is ever picked (and a padded tile ends with the table)
    n_steps = jnp.minimum(pl.cdiv(q_start + q_tile, width),
                          table_ref.shape[1] // P)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copy(j, i, slot):
        return pltpu.make_async_copy(
            pool_ref.at[pl.ds(table_ref[b, j * P + i], 1), :,
                        pl.ds(0, lanes)],
            buf.at[slot, i], sem.at[0, slot])

    def bias_copy(j, slot):
        return pltpu.make_async_copy(
            bias_ref.at[b, :, pl.ds(qi * q_tile, q_tile),
                        pl.ds(j * P * half, P * half)],
            bias_buf.at[slot], sem.at[1, slot])

    def start(j, slot):
        bias_copy(j, slot).start()

        def one(i, _):
            copy(j, i, slot).start()
            return 0

        jax.lax.fori_loop(0, P, one, 0)

    q = _latent_queries([q_ref], scale, q_ref.dtype)
    start(0, 0)

    def step(j, _):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_steps)
        def _next():
            start(j + 1, 1 - slot)

        bias_copy(j, slot).wait()

        def one(i, _):
            copy(j, i, slot).wait()
            return 0

        jax.lax.fori_loop(0, P, one, 0)
        slab = buf[slot].reshape(P * half, lanes)

        def picked(i, s):  # a position's bias under each of its heads' rows
            bias = bias_buf[slot, i]  # [tq, P*half]
            return (s.reshape(q_tile, heads, P * half)
                    + bias[:, None, :]).reshape(s.shape)

        _attend_latent(q, slab, picked, m_ref, l_ref, acc_ref, value=value)
        return 0

    jax.lax.fori_loop(0, n_steps, step, 0)
    out_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("value_dim", "scale", "interpret"),
)
def latent_picked_prefill_pallas(
    q: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    picked: jnp.ndarray,
    *,
    q_offset,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention in the latent space of the queries at positions ``q_offset
    ..`` (data) over the positions each picked, in the pool's blocks where
    they lie.  q: [B, Tq, H, W]; kv_pool: [slots, bs/2, 2*W + 2*dI], one
    layer's pool of a latent-selected group (``KVGroupSpec.layer_shape``);
    block_table: [B, n] int32, the slots that hold positions 0 .. n*bs - 1 >=
    q_offset + Tq - 1, the queries' own among them (the caller writes them
    first); picked: [B, Tq, n*bs] bool, what each query attends over (nothing
    after its own position, and something).  Only the table's blocks up to a
    tile's last position are read, each once a tile.  Returns
    [B, Tq, H, value_dim] in q.dtype."""
    B, Tq, H, W = q.shape
    _, half, width = kv_pool.shape
    n = block_table.shape[1]
    if width < 2 * W or picked.shape[-1] != n * 2 * half \
            or not 0 < value_dim <= W:
        raise ValueError("a latent-selected row is two positions of q's "
                         "width and their keys, and a pick names a position")
    tq = min(PICKED_Q_TILE, -(-Tq // 8) * 8)
    P = min(PICKED_BLOCKS_PER_STEP, n)
    qp, bp = (-Tq) % tq, (-n) % P
    q = latent_query_layouts(
        jnp.pad(q, ((0, 0), (0, qp), (0, 0), (0, 0))), value_dim
    ).reshape(B, 2, (Tq + qp) * H, 2 * W - value_dim)
    # the picks by a row's first and second position: [B, 2, Tq, n * half]
    bias = jnp.where(picked, 0.0, NEG_INF).astype(jnp.float32).reshape(
        B, Tq, n, 2, half).transpose(0, 3, 1, 2, 4).reshape(B, 2, Tq, n * half)
    bias = jnp.pad(bias, ((0, 0), (0, 0), (0, qp), (0, bp * half)),
                   constant_values=NEG_INF)
    table = jnp.pad(block_table.astype(jnp.int32), ((0, 0), (0, bp)),
                    mode="edge")
    rows = tq * H
    kernel = functools.partial(
        _picked_prefill_kernel, q_tile=tq, heads=H, value=value_dim,
        scale=scale)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, (Tq + qp) * H, value_dim),
                                       q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, (Tq + qp) // tq),
            in_specs=[
                pl.BlockSpec((1, 2, rows, q.shape[-1]),
                             lambda b, qi, *_: (b, 0, qi, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, value_dim),
                                   lambda b, qi, *_: (b, qi, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((2, P, 1, half, 2 * W), kv_pool.dtype),
                pltpu.VMEM((2, 2, tq, P * half), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PICKED_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(
        table,
        jnp.asarray(q_offset, jnp.int32).reshape(1),
        q,
        kv_pool,
        bias,
    )
    return out.reshape(B, Tq + qp, H, value_dim)[:, :Tq]
