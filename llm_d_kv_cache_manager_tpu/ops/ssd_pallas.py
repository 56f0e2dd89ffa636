"""Mamba-2's selective scan, chunk-wise (the state-space dual form of
arXiv:2405.21060), as a Pallas TPU kernel, with the same products as
``jax.numpy`` einsums and the recurrence they equal beside it.

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
