"""Latent attention's cache and kernels: `KVGroupSpec`'s latent kind (one
vector a position a layer, key and value at once), the slot's layout, the
paged decode kernel's latent form (shared pass and walk) and the paged latent
prefill kernel, each in `interpret` mode against a dense masked softmax over
the unpacked latents."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVCachePool,
    KVCachePoolConfig,
    KVGroupSpec,
    pack_latent_blocks,
    unpack_latent_blocks,
    write_blocks,
)
from llm_d_kv_cache_manager_tpu.offload.spec import TPUOffloadSpec
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (
    latent_prefill_attention_pallas,
)
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
    shared_prefix_plan,
)

BS, W, VALUE = 16, 48, 32  # a latent of 32 value lanes and 16 more key lanes
SCALE = 24.0**-0.5


def spec(layers=5, latent=576, value=512, block=16):
    return KVGroupSpec(layers, block, 1, latent, "bfloat16",
                       latent_dim=latent, value_dim=value)


# ------------------------------------------------------------------ the spec


def test_a_latent_slot_is_one_vector_a_position():
    s = spec()
    assert s.block_nbytes == 5 * 16 * 576 * 2 == 92160
    assert s.read_nbytes == s.block_nbytes
    assert s.layer_shape(7) == (7, 8, 1152)
    # 5.6 % of what 20 heads of K (256) and V (256) would take
    per_head = KVGroupSpec(5, 16, 20, 256).block_nbytes
    assert round(100 * s.block_nbytes / per_head, 1) == 5.6
    shared = spec(layers=1).read_nbytes
    assert KVGroupSpec(1, 16, 1, 576, latent_dim=576, value_dim=512,
                       readers=3).read_nbytes == 3 * shared


@pytest.mark.parametrize("bad", (
    dict(num_kv_heads=2), dict(head_dim=64), dict(block_size=15),
    dict(value_dim=None), dict(value_dim=577), dict(state_shape=(4, 4)),
))
def test_a_latent_spec_refuses_what_it_cannot_hold(bad):
    args = dict(num_layers=5, block_size=16, num_kv_heads=1, head_dim=576,
                latent_dim=576, value_dim=512)
    KVGroupSpec(**args)
    with pytest.raises(ValueError):
        KVGroupSpec(**{**args, **bad})


def test_the_slots_layout_is_two_positions_a_row_mirrored():
    x = jnp.arange(2 * 32 * W, dtype=jnp.float32).reshape(2, 32, W)
    slots = pack_latent_blocks(x, BS, VALUE)
    assert slots.shape == (2, 2, BS // 2, 2 * W)
    r, rest = 3, W - VALUE
    row = np.asarray(slots[1, 1, r])
    a, b = np.asarray(x[1, BS + r]), np.asarray(x[1, BS + r + BS // 2])
    np.testing.assert_array_equal(row[:VALUE], a[:VALUE])
    np.testing.assert_array_equal(row[VALUE:W], a[VALUE:])
    np.testing.assert_array_equal(row[W:W + rest], b[VALUE:])
    np.testing.assert_array_equal(row[W + rest:], b[:VALUE])
    np.testing.assert_array_equal(unpack_latent_blocks(slots, VALUE), x)


def test_scatter_writes_only_the_named_slots():
    s = spec(layers=1, latent=W, value=VALUE)
    pool = jnp.full(s.layer_shape(6), 7, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2 * BS, W)
                          ).astype(jnp.bfloat16)
    out = write_blocks(s, pool, jnp.asarray([[4, 1]]), x)
    got = unpack_latent_blocks(out, VALUE)
    np.testing.assert_array_equal(got[4 * BS:5 * BS], x[0, :BS])
    np.testing.assert_array_equal(got[BS:2 * BS], x[0, BS:])
    for untouched in (0, 2, 3, 5):
        assert (np.asarray(out[untouched], np.float32) == 7).all()


def test_a_pool_and_the_offload_file_weigh_what_the_slot_does(tmp_path):
    """Everything that prices a block from `pool.block_nbytes` is right by
    construction for a pool of latent slots: the pool's own bytes a block,
    what a gather brings to the host, and the offload spec's file of
    `blocks_per_file` of them."""
    pool = KVCachePool(KVCachePoolConfig(
        num_layers=5, num_blocks=4, block_size=16, num_kv_heads=1,
        head_dim=576, latent_dim=576, value_dim=512))
    assert pool.kv.shape == (5, 4, 8, 1152)
    assert pool.block_nbytes == pool.kv.nbytes // 4 == spec().block_nbytes
    block = np.arange(5 * 8 * 1152, dtype=np.float32).reshape(5, 8, 1152) % 251
    pool.write_block(2, block)
    host = pool.gather_to_host([2, 0])
    assert host.nbytes == 2 * pool.block_nbytes
    np.testing.assert_array_equal(host[:, 0].astype(np.float32), block)
    offload = TPUOffloadSpec(str(tmp_path), "glm", device_block_size=16,
                             offloaded_block_size=64)
    assert offload.blocks_per_file * pool.block_nbytes == 4 * 92160


# --------------------------------------------------------- the decode kernel


def dense_decode(q, latents, ctx):
    """softmax(q . latent / scale) over each sequence's context, the value
    the first VALUE lanes: [B, H, VALUE] float32."""
    out = []
    for b in range(q.shape[0]):
        x = latents[b][: int(ctx[b])].astype(jnp.float32)
        s = jnp.einsum("hw,tw->ht", q[b].astype(jnp.float32), x) * SCALE
        out.append(jax.nn.softmax(s, -1) @ x[:, :VALUE])
    return jnp.stack(out)


def decode_case(ctx, shared_blocks=0, H=5, num_blocks=64, max_blocks=8):
    kq, kx = jax.random.split(jax.random.PRNGKey(1))
    B = len(ctx)
    q = jax.random.normal(kq, (B, H, W)).astype(jnp.bfloat16)
    x = jax.random.normal(kx, (num_blocks * BS, W)).astype(jnp.bfloat16)
    pool = pack_latent_blocks(x, BS, VALUE)  # [num_blocks, 8, 2W]
    ids = np.random.default_rng(0).permutation(np.arange(1, num_blocks))
    table, used = [], shared_blocks
    for b in range(B):
        n = -(-int(ctx[b]) // BS)
        own = list(ids[used:used + n - shared_blocks])
        used += len(own)
        row = list(ids[:shared_blocks]) + own
        table.append(row + [0] * (max_blocks - len(row)))
    table = np.asarray(table, np.int32)
    latents = [x.reshape(num_blocks, BS, W)[table[b]].reshape(-1, W)
               for b in range(B)]
    return q, pool, jnp.asarray(table), jnp.asarray(ctx, jnp.int32), latents


def close(got, ref, tol=0.03):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("ctx, wave", (
    ([64], 4), ([61, 33], 2), ([1, 17, 128], 4), ([97, 112, 9], 3),
    ([128, 120], 16),
))
def test_latent_decode_matches_a_dense_softmax(ctx, wave):
    q, pool, table, ctx_arr, latents = decode_case(ctx)
    got = paged_decode_attention_pallas(
        q, pool, table, ctx_arr, latent=VALUE, scale=SCALE,
        walk_blocks_per_wave=wave, interpret=True)
    assert got.shape == (len(ctx), q.shape[1], VALUE)
    close(got, dense_decode(q, latents, ctx))


@pytest.mark.parametrize("ctx, shared", (
    ([70, 90, 50], 3), ([40, 128, 33, 64, 100], 2),
))
def test_latent_decode_with_a_shared_prefix(ctx, shared):
    """Tables that begin with the same blocks: the shared pass reads the run
    once for the sequences of the set, and the walk resumes from it."""
    q, pool, table, ctx_arr, latents = decode_case(ctx, shared_blocks=shared)
    plan = shared_prefix_plan(table, ctx_arr, block_size=BS,
                              blocks_per_wave=2, shared_blocks_per_step=2)
    assert int(plan["read_blocks"]) < int(plan["walked_blocks"])
    got = paged_decode_attention_pallas(
        q, pool, table, ctx_arr, latent=VALUE, scale=SCALE, plan=plan,
        walk_blocks_per_wave=2, shared_blocks_per_step=2, interpret=True)
    close(got, dense_decode(q, latents, ctx))


@pytest.mark.parametrize("least, shares", ((4, False), (3, True), (2, True)))
def test_latent_sets_under_the_least_size_are_walked(least, shares):
    """`min_sequences` (models/glm4moelite.py asks for 4): three sequences
    over one run go through the shared pass only where three are enough;
    where not, each walks its whole table, and the result is the same."""
    ctx = [70, 90, 50]
    q, pool, table, ctx_arr, latents = decode_case(ctx, shared_blocks=3)
    plan = shared_prefix_plan(table, ctx_arr, block_size=BS,
                              blocks_per_wave=2, shared_blocks_per_step=2,
                              min_sequences=least)
    walked = int(plan["walked_blocks"])
    assert int(plan["read_blocks"]) == (walked - 2 * 3 if shares else walked)
    got = paged_decode_attention_pallas(
        q, pool, table, ctx_arr, latent=VALUE, scale=SCALE, plan=plan,
        walk_blocks_per_wave=2, shared_blocks_per_step=2, interpret=True)
    close(got, dense_decode(q, latents, ctx))


def test_latent_decode_refuses_the_other_forms_arguments():
    q, pool, table, ctx_arr, _ = decode_case([40])
    with pytest.raises(ValueError):
        paged_decode_attention_pallas(q, pool, table, ctx_arr, latent=VALUE,
                                      start=ctx_arr, interpret=True)
    with pytest.raises(ValueError):
        paged_decode_attention_pallas(q[..., :VALUE], pool, table, ctx_arr,
                                      latent=VALUE, interpret=True)


# -------------------------------------------------------- the prefill kernel


def dense_prefill(q, latents, q_offset):
    """Causal softmax of the queries at positions q_offset.. over the
    latents: [Tq, H, VALUE] float32."""
    x = latents.astype(jnp.float32)
    s = jnp.einsum("qhw,tw->hqt", q.astype(jnp.float32), x) * SCALE
    seen = (jnp.arange(x.shape[0])[None, :]
            <= q_offset + jnp.arange(q.shape[0])[:, None])
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    return jnp.einsum("hqt,tv->qhv", p, x[:, :VALUE])


@pytest.mark.parametrize("tq, q_offset, tile, step", (
    (32, 0, 16, 2),  # a miss: every query's own position the last it sees
    (32, 64, 16, 2),  # a hit over four blocks of prefix
    (48, 32, 16, 4),  # steps wider than a tile; a table that ends in a step
    (16, 80, 8, 1),
    (40, 16, 16, 2),  # queries that do not fill the last tile
))
def test_latent_prefill_matches_a_dense_causal_softmax(tq, q_offset, tile,
                                                       step):
    H, num_blocks = 3, 32
    kq, kx = jax.random.split(jax.random.PRNGKey(2))
    q = jax.random.normal(kq, (2, tq, H, W)).astype(jnp.bfloat16)
    x = jax.random.normal(kx, (num_blocks * BS, W)).astype(jnp.bfloat16)
    pool = pack_latent_blocks(x, BS, VALUE)
    n = -(-(q_offset + tq) // BS)
    table = np.random.default_rng(3).permutation(num_blocks)[:2 * n]
    table = table.reshape(2, n).astype(np.int32)
    got = latent_prefill_attention_pallas(
        q, pool, jnp.asarray(table), q_offset=q_offset, value_dim=VALUE,
        scale=SCALE, q_tile=tile, blocks_per_step=step, interpret=True)
    assert got.shape == (2, tq, H, VALUE)
    blocks = x.reshape(num_blocks, BS, W)
    for b in range(2):
        close(got[b], dense_prefill(q[b], blocks[table[b]].reshape(-1, W),
                                    q_offset))
