"""The seven K/V slot layouts behind `KVGroupSpec`: whatever a family's model
step writes through `kv_cache_pool`'s operations it reads back through them,
layout by layout, and a decode step's one-position write gives the slot a
prefill's scatter of the same block would."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import kv_cache_pool
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import KVGroupSpec
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

BLOCK, HKV, DH, SLOTS = 16, 2, 16, 5
SPECS = {
    "plain": KVGroupSpec(1, BLOCK, HKV, DH, "float32"),
    "heads_first": KVGroupSpec(1, BLOCK, HKV, DH, "float32",
                               heads_first=True),
    "packed": KVGroupSpec(1, BLOCK, HKV, DH, "float32", packed=True),
    "rows": KVGroupSpec(1, BLOCK, HKV, DH, "float32", rows=True),
    "latent": KVGroupSpec(1, BLOCK, 1, 24, "float32", latent_dim=24,
                          value_dim=16),
    "selected": KVGroupSpec(1, BLOCK, HKV, DH, "float32", selector_dim=8),
    "latent_selected": KVGroupSpec(1, BLOCK, 1, 24, "float32", latent_dim=24,
                                   value_dim=16, selector_dim=8, selected=4),
}
IDS = jnp.asarray([[3, 1]])  # two blocks of one sequence, out of order


def parts_of(spec: KVGroupSpec, seed: int = 0) -> tuple:
    """What a prefill of two blocks hands `write_blocks` for this layout."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=(1, 2 * BLOCK) + shape),
                           jnp.float32)

    if spec.layout == "latent":
        return (normal(spec.latent_dim),)
    if spec.layout == "latent_selected":
        return (normal(spec.latent_dim), normal(spec.selector_dim))
    kv = (normal(spec.num_kv_heads, spec.head_dim),
          normal(spec.num_kv_heads, spec.head_dim))
    if spec.layout == "selected":
        return kv + (normal(spec.selector_dim),)
    return kv


def written(spec: KVGroupSpec, parts: tuple):
    pool = jnp.zeros(spec.layer_shape(SLOTS), jnp.dtype(spec.dtype))
    return kv_cache_pool.write_blocks(spec, pool, IDS, *parts)


def read_back(spec: KVGroupSpec, pool) -> tuple:
    """The parts again, by the layout's own reader."""
    if spec.layout == "latent":
        return (kv_cache_pool.unpack_latent_blocks(pool[IDS],
                                                   spec.value_dim),)
    if spec.layout == "latent_selected":
        # every position's row and half, in table order
        at = jnp.arange(BLOCK)
        rows = (IDS[..., None] * (BLOCK // 2) + at % (BLOCK // 2)).reshape(1, -1)
        second = jnp.tile(at >= BLOCK // 2, 2)[None]
        whole = kv_cache_pool.unpack_latent_blocks(  # the keys left aside
            pool[IDS][..., :2 * spec.latent_dim], spec.value_dim)
        picked = kv_cache_pool.gather_picked_latents(spec, pool, rows, second)
        np.testing.assert_array_equal(whole, picked)
        return (whole, kv_cache_pool.gather_selector_keys(spec, pool, IDS))
    if spec.layout == "selected":
        # every position's tile, in table order
        tiles = (IDS[..., None] * spec.slot_tiles
                 + jnp.arange(BLOCK)).reshape(1, -1)
        return kv_cache_pool.gather_picked_tiles(spec, pool, tiles) + (
            kv_cache_pool.gather_selector_keys(spec, pool, IDS),)
    return kv_cache_pool.gather_prefix(spec, pool, IDS, jnp.float32)


@pytest.mark.parametrize("layout", SPECS)
def test_blocks_written_are_read_back_and_a_token_lands_where_they_do(layout):
    spec = SPECS[layout]
    assert spec.layout == layout
    parts = parts_of(spec)
    pool = written(spec, parts)
    assert pool.shape == spec.layer_shape(SLOTS)
    untouched = jnp.asarray([0, 2, 4])
    assert not np.asarray(pool[untouched]).any()
    for got, want in zip(read_back(spec, pool), parts, strict=True):
        np.testing.assert_array_equal(got, want)
    # position by position, a decode step's write makes the same slots
    got = jnp.zeros_like(pool)
    for pos in range(2 * BLOCK):
        got = kv_cache_pool.write_token(
            spec, got, IDS[:, pos // BLOCK], jnp.asarray([pos % BLOCK]),
            *(a[:, pos] for a in parts))
    np.testing.assert_array_equal(got, pool)
    # and one write moves one position only: position 5 of slot 3
    token = [a[:, 8] for a in parts_of(spec, seed=1)]
    other = kv_cache_pool.write_token(spec, pool, jnp.asarray([3]),
                                      jnp.asarray([5]), *token)
    for got, new, old in zip(read_back(spec, other), token, parts,
                             strict=True):
        np.testing.assert_array_equal(got[0, 5], new[0])
        np.testing.assert_array_equal(np.delete(got, 5, axis=1),
                                      np.delete(old, 5, axis=1))


@pytest.mark.parametrize("layout", ("plain", "heads_first", "packed", "rows"))
def test_both_decode_readers_see_the_slots_through_the_specs_view(layout):
    """`decode_view` hands the XLA gather and the paged kernel the same K and
    V: both give the dense softmax over what `gather_prefix` reads."""
    spec = SPECS[layout]
    parts = parts_of(spec)
    pool = written(spec, parts)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2 * HKV, DH)), jnp.float32)
    ctx = jnp.asarray([27])
    k, v = (np.asarray(a)[0, :27] for a in parts)  # [27, Hkv, Dh]
    s = np.einsum("hgd,khd->hgk", np.asarray(q)[0].reshape(HKV, 2, DH),
                  k) * DH**-0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hgk,khd->hgd", p / p.sum(-1, keepdims=True),
                     v).reshape(2 * HKV, DH)
    view, names = kv_cache_pool.decode_view(spec, pool, kernel=False)
    np.testing.assert_allclose(
        paged_attention(q, view, IDS, ctx, **names)[0], want, atol=1e-5)
    view, names = kv_cache_pool.decode_view(spec, pool, kernel=True)
    np.testing.assert_allclose(
        paged_decode_attention_pallas(q, view, IDS, ctx, interpret=True,
                                      **names)[0], want, atol=1e-5)


@pytest.mark.parametrize("two", (
    dict(heads_first=True, packed=True),
    dict(packed=True, rows=True),
    dict(rows=True, state_shape=(2, 8)),
    dict(heads_first=True, selector_dim=8),
))
def test_a_spec_that_names_two_layouts_is_refused(two):
    with pytest.raises(ValueError, match="one way|selected slot"):
        KVGroupSpec(1, BLOCK, HKV, DH, **two)


def test_a_reader_a_layout_does_not_have_is_refused():
    latent, selected = SPECS["latent"], SPECS["selected"]
    pool = jnp.zeros(latent.layer_shape(SLOTS))
    with pytest.raises(ValueError, match="no such reader"):
        kv_cache_pool.decode_view(latent, pool, kernel=False)
    with pytest.raises(ValueError, match="not gathered"):
        kv_cache_pool.gather_prefix(latent, pool, IDS, jnp.float32)
    with pytest.raises(ValueError, match="no such reader"):
        kv_cache_pool.decode_view(
            selected, jnp.zeros(selected.layer_shape(SLOTS)), kernel=True)
    assert kv_cache_pool.decode_view(latent, pool, kernel=True)[1] == {
        "latent": latent.value_dim}
    state = KVGroupSpec(1, BLOCK, 0, 0, state_shape=(2, 8))
    assert state.layout == "state"
    with pytest.raises(ValueError, match="no position"):
        kv_cache_pool.write_token(state, jnp.zeros(state.layer_shape(SLOTS)),
                                  jnp.asarray([0]), jnp.asarray([0]))
