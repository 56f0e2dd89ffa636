"""Mamba-2's selective scan, chunk-wise (the state-space dual form of
arXiv:2405.21060), as a Pallas TPU kernel, with the same products as
``jax.numpy`` einsums and the recurrence they equal beside it; and one
position of that recurrence over the slots of a pool, as a second kernel.

The recurrence, a head h of P lanes that reads group ``g = h // (H / G)`` of
the input's G groups, its state ``S[h]`` a matrix [P, N] in float32:

    S_t[h] = exp(d_t[h] A[h]) S_{t-1}[h] + d_t[h] x_t[h] (x) B_t[g]
    y_t[h] = S_t[h] . C_t[g]

(``ssd_step``, one position; ``ssd_recurrence``, a ``lax.scan`` of it.)  Over
a chunk of Q positions with ``cum_t = sum_{r <= t} d_r A`` counted from the
chunk's first position, and ``S_in`` the state before it:

    Y_intra = ((C B^T) * L) . (d x),   L[t, s] = exp(cum_t - cum_s), t >= s
    Y_inter[t] = exp(cum_t) C_t . S_in
    S_out = exp(cum_Q) S_in + sum_s exp(cum_Q - cum_s) d_s x_s (x) B_s

which is the recurrence written out: products of [Q, Q], [Q, N] and [Q, P]
matrices, which the MXU takes, where the recurrence is ~2 P N vector
operations a head a position with the state crossing memory.  Nothing of size
T x H x P x N is made: the state is carried from chunk to chunk (the kernel
keeps a head group's in VMEM across the chunks of a sequence) and only the
last is handed back.  A caller that wants the state at a position inside the
sequence ends a call there (models/nemotronh.py: a call a kept boundary).

``ssd_chunk_scan_pallas``: a grid step is one group's H / G heads over one
chunk.  Inside it time lies in the lanes: x and y are handed over as
[.., P, T], so a head's decays are a row, every product is one the MXU takes
as it lies (``x^T . M^T``, ``S . C^T``, ``x^T . B``), and the chunk's 128
positions fill the 128 lanes.  The cumulative sums are made outside, by XLA,
in float32 (a [T, H] array), and handed over twice, as rows and as columns:
``L^T[s, t]`` needs ``cum_s`` down the sublanes and ``cum_t`` along the
lanes.  The products within a chunk (``C B^T`` and ``. (d x)``) take their
operands in x's type with float32 sums, as the flash kernel's do; the state's
two products (``S . C^T``, ``x^T . B``) are float32 whatever x is, so that a
state carried over thousands of positions is rounded nowhere.

``ssd_decode_step_pallas`` is a decode step: ``ssd_step`` for every sequence
on the slot of a pool that a table names, written to the slot another table
names, the pool aliased to its result.  A grid step is a sequence's whole
slot, [H P, N] with N in the lanes as it lies (2 MB at the model's sizes),
brought and written back by the pipeline through index maps that read the
tables, so the state crosses memory once in and once out and nothing else a
step reads is a fiftieth of it: ``d x`` and y are rows [.., H P / 128, 128], a
head's decay is handed over along the 128 lanes, B and C are rows, and no
operand has a minor dimension of 1 (padded 128-fold in memory, it held a
first form of this kernel at 2.44 ms a layer; PERF.md, PR 49).  Inside, a
block of 128 rows at a time, float32 on the VPU in ``ssd_step``'s order (on
the chip the pool and y came out bit for bit what the loop over sequences it
replaced gave): ``d x`` comes down the sublanes by a transpose of its row
broadcast, and ``S . C`` is the product with C's row summed along the lanes.
On the chip at the model's sizes (128 sequences of a pool of 452 slots, one
layer; PERF.md, PR 51) it reads 0.856 ms alone and 0.823 in the cell's step,
beside 0.843 for a copy through the same blocks (537 MB at 637 GB/s), where
the loop read 1.56-1.74 alone and 1.07 + 0.32 of gaps in the step.  The same
with y as a product on the MXU (``C . S^T``, ``HIGHEST``), the outer product
as one (``x^T . B``) or y by a transpose and a sum down the sublanes read the
same 0.86, both as products 1.52, and a group's 256 KB a grid step 1.03-1.14.

``ssd_chunk_scan`` is the same, chunk by chunk under a ``lax.scan``, for where
no TPU compiles the kernel (the pairing ``paged_decode_pallas`` /
``paged_attention`` has); tests/test_ssd_scan.py holds the three to each
other.  On the chip at the model's sizes (64 heads of 64, 8 groups, N 128,
bfloat16; PERF.md, PR 49) a call over 512 positions is 0.048 ms with the
kernel (the kernel 0.026; x turned to [.., P, T], y turned back, the sums and
their two layouts 0.021 as XLA's own fusions, which the kernel's name in a
device trace does not cover) against 0.470 ms as einsums; a prompt's 8704
positions in nine calls 1.59 against 9.51.
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
HI = lax.Precision.HIGHEST


def ssd_step(s, x, dt, a, b, c):
    """One position of the recurrence for each sequence.  s: [B, H, P, N]
    float32; x: [B, H, P]; dt: [B, H] float32 (after its softplus); a: [H]
    float32 (negative); b, c: [B, G, N].  Returns (s_t, y_t [B, H, P]
    float32)."""
    f32 = jnp.float32
    B, H, P, N = s.shape
    G = b.shape[1]
    sg = s.reshape(B, G, H // G, P, N)
    decay = jnp.exp(dt * a).reshape(B, G, H // G, 1, 1)
    dx = (dt[..., None] * x.astype(f32)).reshape(B, G, H // G, P, 1)
    sg = decay * sg + dx * b.astype(f32)[:, :, None, None, :]
    y = jnp.sum(sg * c.astype(f32)[:, :, None, None, :], axis=-1)
    return sg.reshape(B, H, P, N), y.reshape(B, H, P)


def ssd_recurrence(x, dt, a, b, c, s0):
    """The scan a position at a time.  x: [B, T, H, P]; dt: [B, T, H]; a:
    [H]; b, c: [B, T, G, N]; s0: [B, H, P, N] float32.  Returns (y [B, T, H,
    P] float32, the state after the last position)."""

    def step(s, xs):
        return ssd_step(s, *xs[:2], a, *xs[2:])

    s, y = lax.scan(step, s0.astype(jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt.astype(jnp.float32), b, c)))
    return jnp.moveaxis(y, 0, 1), s


def _chunk_cum(dt, a, chunk: int):
    """d A summed from each chunk's first position: [B, T, H] float32."""
    B, T, H = dt.shape
    da = (dt.astype(jnp.float32) * a).reshape(B, T // chunk, chunk, H)
    return jnp.cumsum(da, axis=2).reshape(B, T, H)


def ssd_chunk_scan(x, dt, a, b, c, s0, chunk: int):
    """The chunk-wise form as einsums, a ``lax.scan`` over chunks with the
    state its carry.  Shapes as ``ssd_recurrence``, T whole chunks.  Returns
    (y [B, T, H, P] float32, the state after the last position)."""
    f32 = jnp.float32
    B, T, H, P = x.shape
    G, N, Q = b.shape[2], b.shape[3], chunk
    if T % Q:
        raise ValueError("the chunk scan takes whole chunks")
    nc, hg = T // Q, H // G
    mm = x.dtype
    cum = _chunk_cum(dt, a, Q).reshape(B, nc, Q, G, hg)
    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(
        B, nc, Q, G, hg, P)
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]  # [t, s]

    def one(s, xs):
        cum, xdt, bm, cm = xs  # [B, Q, G, hg], [B, Q, G, hg, P], [B, Q, G, N]
        cb = jnp.einsum("btgn,bsgn->bgts", cm, bm, preferred_element_type=f32)
        ct = jnp.moveaxis(cum, 1, -1)  # [B, G, hg, Q]
        decay = jnp.exp(jnp.where(
            seen, ct[..., :, None] - ct[..., None, :], NEG_INF))
        m = (cb[:, :, None] * decay).astype(mm)  # [B, G, hg, t, s]
        y = jnp.einsum("bghts,bsghp->btghp", m, xdt.astype(mm),
                       preferred_element_type=f32)
        sg = s.reshape(B, G, hg, P, N)
        y += jnp.exp(cum)[..., None] * jnp.einsum(
            "btgn,bghpn->btghp", cm.astype(f32), sg, precision=HI)
        last = cum[:, -1]  # [B, G, hg]
        left = jnp.exp(last[:, None] - cum)[..., None] * xdt
        sg = jnp.exp(last)[..., None, None] * sg + jnp.einsum(
            "bsghp,bsgn->bghpn", left, bm.astype(f32), precision=HI)
        return sg.reshape(B, H, P, N), y

    s, y = lax.scan(one, s0.astype(f32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (
            cum, xdt, b.reshape(B, nc, Q, G, N), c.reshape(B, nc, Q, G, N))))
    return jnp.moveaxis(y, 0, 1).reshape(B, T, H, P), s


def _ssd_kernel(tot_ref, etot_ref, x_ref, dt_ref, row_ref, col_ref, b_ref,
                c_ref, s0_ref, y_ref, s_ref, state, *, heads: int,
                chunks: int):
    """One group's heads over one chunk.  tot_ref, etot_ref: SMEM [B * H *
    chunks] float32 (scalar prefetch), a chunk's whole sum of d A a head and
    its exponential (a factor of the whole state is a scalar: the chip
    broadcasts one from SMEM, not from a vector's corner); x_ref: [1, 1, hg,
    P, Q] (time in the lanes); dt_ref, row_ref: [1, 1, hg, Q], d and its
    cumulative sum with A; col_ref: [1, 1, Q, hg], the same sums down the
    sublanes; b_ref, c_ref: [1, 1, Q, N]; s0_ref, s_ref: [1, 1, hg, P, N];
    y_ref as x_ref, float32; state: VMEM [hg, P, N] float32, the carry."""
    f32 = jnp.float32
    ci = pl.program_id(2)
    first = ((pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
             * heads)

    @pl.when(ci == 0)
    def _():
        state[...] = s0_ref[0, 0]

    bm, cm = b_ref[0, 0], c_ref[0, 0]  # [Q, N]
    Q = bm.shape[0]
    mm = x_ref.dtype
    nt = (((1,), (1,)), ((), ()))  # a . b^T
    # cb^T[s, t] = B_s . C_t, shared by the group's heads
    cbt = lax.dot_general(bm, cm, nt, preferred_element_type=f32)
    seen = (lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
            >= lax.broadcasted_iota(jnp.int32, (Q, Q), 0))  # t >= s
    bm32, cm32 = bm.astype(f32), cm.astype(f32)
    for h in range(heads):
        row = row_ref[0, 0, h:h + 1, :]  # [1, Q]: cum_t along the lanes
        col = col_ref[0, 0, :, h:h + 1]  # [Q, 1]: cum_s down the sublanes
        mt = (cbt * jnp.exp(jnp.where(seen, row - col, NEG_INF))).astype(mm)
        xdt = x_ref[0, 0, h].astype(f32) * dt_ref[0, 0, h:h + 1, :]  # [P, Q]
        s_in = state[h]
        y = jnp.dot(xdt.astype(mm), mt, preferred_element_type=f32)
        y += jnp.exp(row) * lax.dot_general(s_in, cm32, nt,
                                            preferred_element_type=f32)
        y_ref[0, 0, h] = y
        at = (first + h) * chunks + ci
        state[h] = etot_ref[at] * s_in + jnp.dot(
            xdt * jnp.exp(tot_ref[at] - row), bm32,
            preferred_element_type=f32)

    @pl.when(ci == chunks - 1)
    def _():
        s_ref[0, 0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_pallas(x, dt, a, b, c, s0, chunk: int,
                          interpret: bool = False):
    """The chunk-wise form as one kernel: grid (sequence, group, chunk), the
    chunks innermost with a group's state resident in VMEM across them.
    Shapes as ``ssd_recurrence``, T whole chunks.  Returns (y [B, T, H, P]
    float32, the state after the last position)."""
    f32 = jnp.float32
    B, T, H, P = x.shape
    G, N, Q = b.shape[2], b.shape[3], chunk
    if T % Q:
        raise ValueError("the chunk scan takes whole chunks")
    nc, hg = T // Q, H // G
    cum = _chunk_cum(dt, a, Q)
    rows = [jnp.moveaxis(v.astype(f32), 1, 2).reshape(B, G, hg, T)
            for v in (dt, cum)]
    total = rows[1][..., Q - 1::Q].reshape(-1)  # [B * H * chunks]

    def tile(block, at):
        return pl.BlockSpec(block, lambda i, g, ci, *_: at(i, g, ci),
                            memory_space=pltpu.VMEM)

    head_tile = tile((1, 1, hg, P, Q), lambda i, g, ci: (i, g, 0, 0, ci))
    row_tile = tile((1, 1, hg, Q), lambda i, g, ci: (i, g, 0, ci))
    group_tile = tile((1, 1, Q, N), lambda i, g, ci: (i, g, ci, 0))
    state_tile = tile((1, 1, hg, P, N), lambda i, g, ci: (i, g, 0, 0, 0))
    y, s = pl.pallas_call(
        functools.partial(_ssd_kernel, heads=hg, chunks=nc),
        out_shape=(jax.ShapeDtypeStruct((B, G, hg, P, T), f32),
                   jax.ShapeDtypeStruct((B, G, hg, P, N), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, G, nc),
            in_specs=[
                head_tile, row_tile, row_tile,
                tile((1, 1, Q, hg), lambda i, g, ci: (i, g, ci, 0)),
                group_tile, group_tile, state_tile,
            ],
            out_specs=(head_tile, state_tile),
            scratch_shapes=[pltpu.VMEM((hg, P, N), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(
        total, jnp.exp(total),
        jnp.transpose(x, (0, 2, 3, 1)).reshape(B, G, hg, P, T),
        *rows,
        cum.reshape(B, T, G, hg).transpose(0, 2, 1, 3),
        jnp.moveaxis(b, 1, 2), jnp.moveaxis(c, 1, 2),
        s0.astype(f32).reshape(B, G, hg, P, N),
    )
    return (jnp.transpose(y.reshape(B, H, P, T), (0, 3, 1, 2)),
            s.reshape(B, H, P, N))


# ------------------------------------------------------------ a decode step

LANES = 128  # a row block of the decode kernel: a transpose's square side
BLOCKS_AN_ITERATION = 4
# a 2-MB slot in and out, each twice for the pipeline, and a block's values
SLOT_VMEM_BYTES = 48 * 1024 * 1024


def _ssd_decode_kernel(read_ref, write_ref, dx_ref, decay_ref, b_ref, c_ref,
                       s_ref, o_ref, y_ref, *, head_rows: int,
                       group_rows: int):
    """One sequence's slot, a row block at a time.  read_ref, write_ref: SMEM
    [B] (scalar prefetch; the index maps read them); s_ref, o_ref: [1, H P,
    N], a head's P rows after the last's; dx_ref, y_ref: [1, blocks, rows], a
    block's ``d x`` and y along the lanes; decay_ref: [1, H, N], a head's
    decay along its row; b_ref, c_ref: [1, G, N]."""
    N = s_ref.shape[-1]
    blocks, rows = dx_ref.shape[1:]
    heads = rows // head_rows

    def block(i):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        g = i * rows // group_rows
        decay = jnp.concatenate(
            [jnp.broadcast_to(decay_ref[0, pl.ds(i * heads + h, 1), :],
                              (head_rows, N)) for h in range(heads)], axis=0)
        # d x down the sublanes: its row, broadcast, turned
        dx = jnp.broadcast_to(dx_ref[0, pl.ds(i, 1), :], (N, rows)).T
        s = decay * s_ref[0, at, :] + dx * b_ref[0, pl.ds(g, 1), :]
        o_ref[0, at, :] = s
        y_ref[0, pl.ds(i, 1), :] = jnp.sum(
            s * c_ref[0, pl.ds(g, 1), :], axis=1).reshape(1, rows)

    # Some blocks an iteration, written out: the scheduler lays a block's
    # loads and stores beside its neighbours' arithmetic.  A layer at the
    # model's sizes, ms by blocks an iteration (PERF.md, PR 51): 1 1.166, 2
    # 1.081, 4 0.856, 8 0.854, all 32 0.853, beside a copy's 0.843 through
    # the same blocks; the kernel alone then takes 0.03 / 0.04 / 0.11 / 0.21 /
    # 0.67 s to trace and lower, four is within half a per cent of the best,
    # and set-up pays for every equation (tests/test_tpu_compile.py).
    several = math.gcd(blocks, BLOCKS_AN_ITERATION)

    def some(k, carry):
        for j in range(several):
            block(k * several + j)
        return carry

    lax.fori_loop(0, blocks // several, some, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_step_pallas(pool, read, write, x, dt, a, b, c,
                           interpret: bool = False):
    """`ssd_step` on the slots of a pool, where they lie: row i's state is
    slot ``read[i]`` and goes, advanced, into slot ``write[i]``.  pool:
    [slots, H, P, N] float32, aliased to the pool returned (a slot no row
    writes is not touched); read, write: [B] int32; x: [B, H, P]; dt: [B, H]
    float32; a: [H]; b, c: [B, G, N].  Returns (pool, y [B, H, P] float32).

    The grid walks the sequences, and the state's index maps read the tables:
    the pipeline brings row i + 1's slot while row i's is advanced and row i
    - 1's written back.  So no row may write a slot that a row whose result
    is used reads; a row may write the slot it reads itself.  models/pod.py
    keeps that: under `decode_ahead` a sequence alternates between two slots
    that are its own (`StateGroup._alternate`), without it a sequence inside
    a block reads and writes its block's own.  The engine's idle rows share
    one block, hence one pair of slots, whose content nobody reads."""
    f32 = jnp.float32
    slots, H, P, N = pool.shape
    B, G = b.shape[:2]
    group_rows = H // G * P
    rows = math.gcd(group_rows, LANES)
    if rows % P:
        raise ValueError(f"heads of {P} rows fill no row block of {rows}")
    blocks = H * P // rows
    dx = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(B, blocks, rows)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], (B, H, N))

    def tile(block):
        return pl.BlockSpec(block, lambda i, *_: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    def slot(table):
        return pl.BlockSpec(
            (1, H * P, N), lambda i, *tables: (tables[table][i], 0, 0),
            memory_space=pltpu.VMEM)

    out, y = pl.pallas_call(
        functools.partial(_ssd_decode_kernel, head_rows=P,
                          group_rows=group_rows),
        out_shape=(jax.ShapeDtypeStruct((slots, H * P, N), f32),
                   jax.ShapeDtypeStruct((B, blocks, rows), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[tile((1, blocks, rows)), tile((1, H, N)),
                      tile((1, G, N)), tile((1, G, N)), slot(0)],
            out_specs=(slot(1), tile((1, blocks, rows))),
        ),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SLOT_VMEM_BYTES),
        interpret=interpret,
    )(read, write, dx, decay, b.astype(f32), c.astype(f32),
      pool.reshape(slots, H * P, N))
    return out.reshape(pool.shape), y.reshape(B, H, P)
