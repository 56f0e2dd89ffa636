"""Pallas flash-attention PARTIALS for the ring (mask-aware steps).

One ring-attention step computes a local Tq x Tk attention product
whose mask is known per step (ops/ring_attention.py):

* striped layout — every step is a causal band over LOCAL rows,
  ``key_row <= query_row + offset`` with offset 0 or -1;
* contiguous layout — a step is fully visible, diagonal (causal), or
  fully masked.

The einsum body computes the full product and ``where()``-masks it, so
half the MXU work of a causal step is discarded.  This kernel instead
returns an UNNORMALIZED partial — accumulator plus the online-softmax
residuals (row max ``m``, row sum ``l``) — and stops its K/V trip
count at the causal diagonal, so a causal step does only the visible
half.  Ring steps merge partials with the standard log-sum-exp
combine (``merge_partials``) and normalize once at the end; the
flash-decoding decomposition, applied across ring steps.

Kernel idioms (VMEM scratch accumulators, lane-replicated m/l rows,
MXU dot_generals, tiled-axes-last layout) follow
ops/flash_pallas.py::_flash_kernel, which pins the same math for the
single-device prefill path.  Exactness vs the einsum ring body is
pinned by tests/test_llama_model.py (test_flash_ring_matches_dense_
both_layouts and friends; interpret mode on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Lane width of the m/l outputs.  The VMEM scratch stays at the native
# 128 lanes (flash_pallas.py idiom), but only lane 0 carries data — as
# HBM OUTPUTS a 128-wide copy would cost 2x the acc payload's traffic
# per ring step, so the store narrows to 8 lanes (16x less) and the
# wrapper slices lane 0.
ML_LANES = 8


def _partial_kernel(
    q_ref,  # [1, 1, q_block, D]
    k_ref,  # [1, 1, Tk_pad, D]
    v_ref,  # [1, 1, Tk_pad, D]
    acc_ref,  # out [1, 1, q_block, D] f32
    m_ref,  # out [1, 1, q_block, ML_LANES] f32
    l_ref,  # out [1, 1, q_block, ML_LANES] f32
    acc_scratch,  # VMEM [q_block, D] f32
    m_scratch,  # VMEM [q_block, 128] f32
    l_scratch,  # VMEM [q_block, 128] f32
    *,
    causal_offset: Optional[int],
    kv_len: int,
    q_block: int,
    kv_chunk: int,
    scale: float,
):
    qi = pl.program_id(2)
    q_start = qi * q_block  # LOCAL row of this tile's first query

    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale

    acc_scratch[...] = jnp.zeros_like(acc_scratch)
    m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
    l_scratch[...] = jnp.zeros_like(l_scratch)

    if causal_offset is None:
        n_chunks = pl.cdiv(kv_len, kv_chunk)
    else:
        # Last key any row of this tile may see:
        # q_start + q_block - 1 + causal_offset.
        last = jnp.clip(
            q_start + q_block + causal_offset, 0, kv_len
        )
        n_chunks = pl.cdiv(last, kv_chunk)

    row = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 1)

    def chunk_body(ci, _):
        k_start = ci * kv_chunk
        k = k_ref[0, 0, pl.ds(k_start, kv_chunk), :]
        v = v_ref[0, 0, pl.ds(k_start, kv_chunk), :]

        s = jax.lax.dot_general(
            q,
            k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [q_block, kv_chunk]

        k_pos = k_start + col
        mask = k_pos < kv_len  # zero out the kv_chunk padding
        if causal_offset is not None:
            q_pos = q_start + row
            mask &= k_pos <= q_pos + causal_offset
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Fully-masked rows keep m at NEG_INF; the guard keeps exp()
        # away from the sentinel (same idiom as the einsum ring body).
        m_safe = jnp.maximum(m_new, 0.5 * NEG_INF)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        correction = jnp.exp(
            jnp.maximum(m_prev, 0.5 * NEG_INF) - m_safe
        )

        l_scratch[...] = l_scratch[...] * correction + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_scratch[...] = jnp.broadcast_to(m_new, m_scratch.shape)
        acc_scratch[...] = acc_scratch[...] * correction + (
            jax.lax.dot_general(
                p,
                v.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)

    acc_ref[0, 0, :, :] = acc_scratch[...]
    m_ref[0, 0, :, :] = m_scratch[:, :ML_LANES]
    l_ref[0, 0, :, :] = l_scratch[:, :ML_LANES]


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal_offset", "q_block", "kv_chunk", "interpret"
    ),
)
def flash_partial(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal_offset: Optional[int] = 0,
    q_block: int = 256,
    kv_chunk: int = 512,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unnormalized GQA flash partial over one K/V chunk.

    q: [B, Tq, H, D]; k/v: [B, Tk, Hkv, D].
    ``causal_offset``: keys visible to LOCAL row a are b <= a + offset
    (0: diagonal included; -1: strictly below — the striped ring's
    behind-me step).  ``None``: fully visible (no mask).
    Returns f32 ``(acc [B, Tq, H, D], m [B, Tq, H], l [B, Tq, H])``
    such that ``acc / l`` is the softmax output of this chunk alone
    and ``(m, l)`` merge across chunks via :func:`merge_partials`.
    """
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    groups = H // Hkv

    q_block = min(q_block, max(Tq, 8))
    kv_chunk = min(kv_chunk, Tk)
    q_pad = (-Tq) % q_block
    k_pad = (-Tk) % kv_chunk

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
    Tq_pad = Tq + q_pad

    kernel = functools.partial(
        _partial_kernel,
        causal_offset=causal_offset,
        kv_len=Tk,
        q_block=q_block,
        kv_chunk=kv_chunk,
        scale=D**-0.5,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, Tk + k_pad, D),
        lambda b, h, qi, g=groups: (b, h // g, 0, 0),
        memory_space=pltpu.VMEM,
    )
    # Under shard_map with check_vma, outputs must declare how they
    # vary over the mesh — same as the inputs (the ring body runs
    # per-shard).
    vma = jax.typeof(q).vma
    acc, m, l = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Tq_pad, D), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct(
                (B, H, Tq_pad, ML_LANES), jnp.float32, vma=vma
            ),
            jax.ShapeDtypeStruct(
                (B, H, Tq_pad, ML_LANES), jnp.float32, vma=vma
            ),
        ),
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec(
                (1, 1, q_block, D),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            kv_spec,
            kv_spec,
        ],
        out_specs=(
            pl.BlockSpec(
                (1, 1, q_block, D),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, q_block, ML_LANES),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, q_block, ML_LANES),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, D), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    acc = acc.transpose(0, 2, 1, 3)  # [B, Tq_pad, H, D]
    m = m[..., 0].transpose(0, 2, 1)  # [B, Tq_pad, H]
    l = l[..., 0].transpose(0, 2, 1)
    if q_pad:
        acc, m, l = acc[:, :Tq], m[:, :Tq], l[:, :Tq]
    return acc, m, l


def neutral_partial(
    q: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The identity element of :func:`merge_partials` (a fully-masked
    step): zero accumulator, NEG_INF max, zero sum.  Derived from q so
    the values carry shard_map's varying manual axes."""
    acc = jnp.zeros_like(q, dtype=jnp.float32)
    zero = jnp.zeros_like(q[..., 0], dtype=jnp.float32)
    return acc, zero + NEG_INF, zero


def merge_partials(
    state: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    update: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Log-sum-exp combine of two unnormalized partials (flash-
    decoding merge).  Both are ``(acc, m, l)`` with acc [..., D] and
    m/l [...]; associative, identity :func:`neutral_partial`."""
    acc_a, m_a, l_a = state
    acc_b, m_b, l_b = update
    m_new = jnp.maximum(m_a, m_b)
    m_safe = jnp.maximum(m_new, 0.5 * NEG_INF)
    s_a = jnp.exp(jnp.maximum(m_a, 0.5 * NEG_INF) - m_safe)
    s_b = jnp.exp(jnp.maximum(m_b, 0.5 * NEG_INF) - m_safe)
    return (
        acc_a * s_a[..., None] + acc_b * s_b[..., None],
        m_new,
        l_a * s_a + l_b * s_b,
    )


def normalize_partial(
    acc: jnp.ndarray, l: jnp.ndarray, dtype
) -> jnp.ndarray:
    """Final softmax division; fully-masked rows yield 0, not NaN."""
    return (acc / jnp.maximum(l, 1e-20)[..., None]).astype(dtype)
