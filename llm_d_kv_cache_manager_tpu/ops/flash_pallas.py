"""Pallas TPU flash-attention prefill kernel.

The scan-based op (ops/flash_attention.py) expresses flash attention as
XLA loops; this kernel owns the schedule instead: one grid program per
(batch, head, q-tile) computes its output tile with an online-softmax
``fori_loop`` over K/V chunks resident in VMEM, f32 accumulators in
VMEM scratch, every tile contraction on the MXU
(``preferred_element_type=f32``), and the causal upper triangle never
read — the loop's trip count stops at the tile's last visible chunk
(q_offset + (qi+1)*q_block), so continuation suffixes (short q over a
long cached prefix) do only the work the mask allows.  With a static
``window`` the loop also starts at the band's first chunk.

Layout: TPU block specs need the tiled axes last, so the wrapper runs
in [B, H, T, D] (transposing at the boundary; XLA fuses these into the
surrounding ops).  Grid order puts q-tiles innermost so the same
head's K/V block stays resident in VMEM across its q-tiles.

Same contract as ``flash_gqa_attention`` for static ``q_offset``;
equivalence is pinned by tests/test_flash_attention.py (interpret mode
on CPU, compiled on TPU).  The model routes long-sequence inference
here on TPU and falls back to the scan op elsewhere
(models/llama.py::_prefill_attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The kernel stages one kv-head's full K and V in VMEM (~16 MB/core,
# shared with q/out tiles, f32 scratch, and pipeline double-buffering).
# Above this K+V footprint, callers should use the scan-based op, which
# streams K/V from HBM at any length.
VMEM_KV_BUDGET_BYTES = 8 * 1024 * 1024


def fits_vmem(kv_seq_len: int, head_dim: int, dtype_bytes: int = 2) -> bool:
    """True if a [kv_seq_len, head_dim] K+V pair fits the kernel's
    VMEM staging budget."""
    return 2 * kv_seq_len * head_dim * dtype_bytes <= VMEM_KV_BUDGET_BYTES


def _flash_kernel(
    q_ref,  # [1, 1, q_block, D]
    k_ref,  # [1, 1, Tk_pad, D]
    v_ref,  # [1, 1, Tk_pad, D]
    out_ref,  # [1, 1, q_block, D]
    acc_ref,  # VMEM [q_block, D] f32
    m_ref,  # VMEM [q_block, 128] f32 (lane-replicated row max)
    l_ref,  # VMEM [q_block, 128] f32 (lane-replicated row sum)
    *,
    q_offset: int,
    kv_len: int,
    q_block: int,
    kv_chunk: int,
    scale: float,
    window: int | None,
):
    qi = pl.program_id(2)
    q_start = q_offset + qi * q_block  # absolute position of q row 0

    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # [q_block, D]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # Last chunk any row of this tile may see (causal): position
    # q_start + q_block - 1, clamped to the real kv length.
    last = jnp.minimum(q_start + q_block, kv_len)
    n_chunks = pl.cdiv(last, kv_chunk)

    row = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 1)

    def chunk_body(ci, _):
        k_start = ci * kv_chunk
        k = k_ref[0, 0, pl.ds(k_start, kv_chunk), :]  # [kv_chunk, D]
        v = v_ref[0, 0, pl.ds(k_start, kv_chunk), :]

        s = jax.lax.dot_general(
            q,
            k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, kv_chunk]

        q_pos = q_start + row
        k_pos = k_start + col
        mask = (k_pos <= q_pos) & (k_pos < kv_len)
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # [q_block, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # masked entries underflow to 0
        correction = jnp.exp(m_prev - m_new)  # [q_block, 1]

        l_ref[...] = l_ref[...] * correction + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
            p,
            v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return 0

    if window is None:
        jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    else:
        # The band's first chunk: the tile's first row sees nothing
        # before q_start - window + 1.  A later row may find a whole
        # chunk masked before its own band begins; what that adds
        # (exp(0) per column) is wiped by ``correction`` = 0 at the
        # row's first visible chunk, which every row has (its diagonal).
        first = jnp.maximum(q_start - (window - 1), 0) // kv_chunk
        jax.lax.fori_loop(first, n_chunks, chunk_body, 0)

    l = l_ref[:, :1]
    out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)  # pad rows: 0 not NaN
    out_ref[0, 0, :, :] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("q_offset", "q_block", "kv_chunk", "interpret", "window"),
)
def flash_gqa_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_offset: int = 0,
    q_block: int = 256,
    kv_chunk: int = 512,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Causal GQA flash attention.  q: [B, Tq, H, D]; k/v:
    [B, Tk, Hkv, D]; ``q_offset`` shifts q positions (continuation).
    ``window`` (static): row i also sees only keys j > i - window, and
    the K/V chunks before the band are never read; None (the default)
    is plain causal attention and lowers as it did before the argument
    existed.  Returns [B, Tq, H, D] in q.dtype."""
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    groups = H // Hkv

    q_block = min(q_block, max(Tq, 8))
    kv_chunk = min(kv_chunk, Tk)
    q_pad = (-Tq) % q_block
    k_pad = (-Tk) % kv_chunk

    # Kernel layout: [B, H(kv), T, D] — tiled axes last.
    qt = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    kt = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    vt = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    nq = (Tq + q_pad) // q_block

    kernel = functools.partial(
        _flash_kernel,
        q_offset=q_offset,
        kv_len=Tk,
        q_block=q_block,
        kv_chunk=kv_chunk,
        scale=D**-0.5,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec(
                (1, 1, q_block, D),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, Tk + k_pad, D),
                lambda b, h, qi, g=groups: (b, h // g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, Tk + k_pad, D),
                lambda b, h, qi, g=groups: (b, h // g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, q_block, D),
            lambda b, h, qi: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, D), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if q_pad:
        out = out[:, :Tq]
    return out
