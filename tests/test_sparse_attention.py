"""ops/sparse_attention_pallas.py at a small size on the CPU: the index-score
kernels of a prefill and of a decode step and the prefill's attention kernel,
interpreted against dense products and masked softmaxes over the same pool of
selected slots (`KVGroupSpec`'s selected kind).  The exact pick has its tests
in tests/test_keyevl2_pod.py."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import kv_cache_pool as kp
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse

H, HKV, DH, BS, DI, N = 4, 2, 16, 16, 8, 12
SPEC = kp.KVGroupSpec(1, BS, HKV, DH, "float32", selector_dim=DI)


def normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def pool_of(rng, batch, blocks):
    """A pool of selected slots, each sequence's K/V/keys of `blocks` blocks
    scattered to slots of its own in no order, and what was scattered."""
    L = blocks * BS
    k, v, ki = (normal(rng, batch, L, HKV, DH), normal(rng, batch, L, HKV, DH),
                normal(rng, batch, L, DI))
    table = jnp.asarray(np.stack([
        b * N + rng.permutation(N)[:blocks] for b in range(batch)]), jnp.int32)
    pool = jnp.zeros((batch * N, BS + 2, 2 * HKV, DH), jnp.float32)
    return kp.write_blocks(SPEC, pool, table, k, v, ki), table, k, v, ki


def dense(q, k, v, picked):
    """softmax over the picked positions alone: q [B, Tq, H, DH], k and v
    [B, L, HKV, DH], picked [B, Tq, L]."""
    B, Tq = q.shape[:2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(B, Tq, HKV, H // HKV, DH),
                   k) * DH**-0.5
    p = jax.nn.softmax(jnp.where(picked[:, None, None], s, -jnp.inf), -1)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, Tq, H, DH)


@pytest.mark.parametrize("offset, tq, q_tile, key_tile", (
    (48, 40, 16, 128), (0, 96, 256, 1024), (80, 16, 8, 128)))
def test_index_scores_are_the_weighted_relu_products(offset, tq, q_tile,
                                                     key_tile):
    rng = np.random.default_rng(offset)
    L = 96 if key_tile == 1024 else 200  # one tile of keys, or two
    q, w, keys = normal(rng, tq, 4, DI), normal(rng, tq, 4), normal(rng, L, DI)
    got = sparse.sparse_index_scores_pallas(
        q, w, keys, q_offset=offset, q_tile=q_tile, key_tile=key_tile,
        interpret=True)
    want = jnp.einsum("tj,tjs->ts", w, jax.nn.relu(
        jnp.einsum("tjd,sd->tjs", q, keys)))
    seen = jnp.arange(L)[None, :] <= offset + jnp.arange(tq)[:, None]
    np.testing.assert_allclose(got, jnp.where(seen, want, -jnp.inf),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("offset, tq, q_tile, step", (
    (48, 40, 16, 2),  # a hit: a prefix of 3 blocks, tiles that end mid-step
    (0, 96, 32, 4),  # a miss from position 0; the table padded to the step
    (80, 16, 256, 64),  # one tile, one step
))
def test_prefill_attends_over_the_picked_positions_where_they_lie(
        offset, tq, q_tile, step):
    rng = np.random.default_rng(tq)
    blocks = 6
    pool, table, k, v, _ = pool_of(rng, 2, blocks)
    q = normal(rng, 2, tq, H, DH)
    scores = normal(rng, 2, tq, blocks * BS)
    seen = (jnp.arange(blocks * BS)[None, None, :]
            <= offset + jnp.arange(tq)[None, :, None])
    picked = sparse.topk_mask(jnp.where(seen, scores, -jnp.inf), 8)
    got = sparse.sparse_prefill_attention_pallas(
        q, pool, table, picked, q_offset=offset, q_tile=q_tile,
        blocks_per_step=step, interpret=True)
    np.testing.assert_allclose(got, dense(q, k, v, picked), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("wave", (2, 4, 64))
def test_decode_scores_walk_the_tables_selector_keys(wave):
    """One query a sequence over its own table: a table that is one run of
    the pool (every whole wave one copy), one in no order (a copy a block),
    one that is a run and then not; contexts that end mid-block, mid-wave and
    within the first block; `-inf` past the context."""
    rng = np.random.default_rng(wave)
    blocks, HI = 9, 4
    pool = normal(rng, 40, BS + 2, 2 * HKV, DH)
    table = np.stack([np.arange(blocks) + 2,
                      rng.permutation(np.arange(12, 30))[:blocks],
                      np.r_[np.arange(30, 34),
                            rng.permutation(np.arange(34, 40))[:5]]])
    ki = normal(rng, 3, blocks * BS, DI)
    zeros = jnp.zeros((3, blocks * BS, HKV, DH))
    pool = kp.write_blocks(SPEC, pool, jnp.asarray(table, jnp.int32), zeros,
                           zeros, ki)
    q, w = normal(rng, 3, HI, DI), normal(rng, 3, HI)
    ctx = jnp.asarray([blocks * BS, 70, 5])
    got = sparse.sparse_decode_scores_pallas(
        q, w, pool, jnp.asarray(table, jnp.int32), ctx, selector_dim=DI,
        wave_blocks=wave, interpret=True)
    want = jnp.einsum("bj,bjs->bs", w, jax.nn.relu(
        jnp.einsum("bjd,bsd->bjs", q, ki)))
    seen = jnp.arange(blocks * BS)[None] < ctx[:, None]
    np.testing.assert_allclose(got, jnp.where(seen, want, -jnp.inf),
                               rtol=1e-5, atol=1e-5)
