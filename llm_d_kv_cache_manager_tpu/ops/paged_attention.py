"""Paged decode attention over the block-table KV layout.

Decode-time attention where K/V live in the paged pool
(``models/kv_cache_pool.py`` layout: ``[num_blocks, 2, block_size,
Hkv, D]`` per layer) and each sequence names its blocks via a block
table.  The gather + attention is one jitted function: XLA emits a
dynamic-gather from HBM followed by MXU contractions, no host round
trip — the TPU analogue of vLLM's paged-attention CUDA kernel.

Static shapes: block tables are padded to ``max_blocks`` and masked by
``context_len`` so the compiled program is reused across requests.
"""

from __future__ import annotations

import jax.numpy as jnp

NEG_INF = -1e30


def paged_attention(
    q: jnp.ndarray,
    kv_layer: jnp.ndarray,
    block_table: jnp.ndarray,
    context_len: jnp.ndarray,
    start: jnp.ndarray | None = None,
    heads_first: bool = False,
) -> jnp.ndarray:
    """q: [B, H, D]; kv_layer: [num_blocks, 2, block_size, Hkv, D];
    block_table: [B, max_blocks] int32 (pad with any valid id);
    context_len: [B] int32.  ``start`` ([B] int32, window layers): the
    first position of the table a sequence still sees; positions before
    it are masked like those past ``context_len``.  ``heads_first``: the
    pool's slots are [2, Hkv, block_size, D].  Returns [B, H, D]."""
    B, H, D = q.shape
    if heads_first:
        _, _, Hkv, block_size, _ = kv_layer.shape
    else:
        _, _, block_size, Hkv, _ = kv_layer.shape
    groups = H // Hkv
    max_blocks = block_table.shape[1]
    T = max_blocks * block_size

    # [B, max_blocks, 2, block_size, Hkv, D] -> [B, T, Hkv, D] x2
    gathered = jnp.take(kv_layer, block_table, axis=0)
    if heads_first:
        gathered = gathered.transpose(0, 1, 2, 4, 3, 5)
    k = gathered[:, :, 0].reshape(B, T, Hkv, D)
    v = gathered[:, :, 1].reshape(B, T, Hkv, D)

    qf = q.astype(jnp.float32).reshape(B, Hkv, groups, D) * (D**-0.5)
    scores = jnp.einsum("bhgd,bkhd->bhgk", qf, k.astype(jnp.float32))
    mask = jnp.arange(T)[None, :] < context_len[:, None]  # [B, T]
    if start is not None:
        mask &= jnp.arange(T)[None, :] >= start[:, None]
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)

    weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
    weights = weights / weights.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bkhd->bhgd", weights, v.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)
