"""Flash (blockwise) attention vs the dense reference implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.attention import causal_gqa_attention
from llm_d_kv_cache_manager_tpu.ops.flash_attention import flash_gqa_attention


def _qkv(key, B, Tq, Tk, H, Hkv, D):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Tq, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, Tk, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, Tk, Hkv, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("q_block,kv_block", [(8, 8), (16, 4), (64, 64)])
def test_matches_dense_causal(q_block, kv_block):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 24, 24, 4, 2, 8)
    dense = causal_gqa_attention(q, k, v)
    flash = flash_gqa_attention(q, k, v, q_block=q_block, kv_block=kv_block)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_matches_dense_with_q_offset():
    """Continuation shape: short q attending over a longer key axis."""
    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 8, 40, 4, 4, 8)
    dense = causal_gqa_attention(q, k, v, q_offset=32)
    flash = flash_gqa_attention(q, k, v, q_offset=32, q_block=4, kv_block=8)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_matches_dense_with_kv_len():
    q, k, v = _qkv(jax.random.PRNGKey(2), 3, 12, 16, 6, 2, 4)
    kv_len = jnp.asarray([16, 9, 3])
    dense = causal_gqa_attention(q, k, v, kv_len=kv_len)
    flash = flash_gqa_attention(q, k, v, kv_len=kv_len, q_block=4, kv_block=4)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_non_divisible_lengths_padded_internally():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 13, 19, 2, 1, 8)
    dense = causal_gqa_attention(q, k, v)
    flash = flash_gqa_attention(q, k, v, q_block=8, kv_block=8)
    np.testing.assert_allclose(
        np.asarray(flash), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_jit_and_bf16():
    q, k, v = _qkv(jax.random.PRNGKey(4), 1, 32, 32, 4, 2, 8)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    fn = jax.jit(
        lambda q, k, v: flash_gqa_attention(q, k, v, q_block=16, kv_block=16)
    )
    out = fn(q, k, v)
    assert out.dtype == jnp.bfloat16
    dense = causal_gqa_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(dense, np.float32),
        rtol=3e-2,
        atol=3e-2,
    )


# ----------------------- pallas kernel (interpret) ------------------------

from llm_d_kv_cache_manager_tpu.ops.flash_pallas import (  # noqa: E402
    flash_gqa_attention_pallas,
)


@pytest.mark.parametrize(
    "B,Tq,Tk,H,Hkv,D,q_offset",
    [
        (1, 512, 512, 4, 2, 64, 0),  # square causal, GQA
        (2, 256, 1280, 8, 4, 64, 1024),  # continuation
        (1, 300, 300, 4, 4, 128, 0),  # Tq not a q_block multiple
        (1, 128, 896, 4, 2, 64, 768),  # Tk not a kv_chunk multiple
    ],
)
def test_pallas_matches_dense(B, Tq, Tk, H, Hkv, D, q_offset):
    """The TPU kernel in interpreter mode vs the dense reference; the
    same code compiles on-chip (tests/test_tpu_compile.py, and every
    miss prefill of the benchmark runs it)."""
    q, k, v = _qkv(jax.random.PRNGKey(7), B, Tq, Tk, H, Hkv, D)
    q = q.astype(jnp.bfloat16)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)
    dense = causal_gqa_attention(q, k, v, q_offset=q_offset)
    got = flash_gqa_attention_pallas(
        q, k, v, q_offset=q_offset, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(dense, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_pallas_pad_rows_are_finite():
    """Padded q rows (Tq % q_block != 0) must come back 0, not NaN —
    q_block=32 forces real padding (40 -> 64) and the padded rows'
    l==0 guard."""
    q, k, v = _qkv(jax.random.PRNGKey(8), 1, 40, 40, 2, 2, 64)
    got = flash_gqa_attention_pallas(
        q.astype(jnp.bfloat16),
        k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16),
        q_block=32,
        interpret=True,
    )
    assert got.shape == q.shape
    assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
    dense = causal_gqa_attention(
        q.astype(jnp.bfloat16),
        k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16),
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(dense, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_vmem_gate():
    from llm_d_kv_cache_manager_tpu.ops.flash_pallas import fits_vmem

    assert fits_vmem(8448, 128)  # the bench shape
    assert not fits_vmem(32768, 128)  # long-context falls back to scan


# ------------- the continuation entry: the table's blocks where they lie ----

from llm_d_kv_cache_manager_tpu.ops.flash_pallas import (  # noqa: E402
    flash_gqa_attention_pallas_paged,
)

BLOCK = 16


def _pooled(seed, B, Tq, prefix, H, Hkv, D, dtype, pool_blocks=256):
    """q, k, v of ``prefix + Tq`` positions, and a pool that holds k and v
    in the blocks a scattered table names and NaN in every other block."""
    q, k, v = _qkv(jax.random.PRNGKey(seed), B, Tq, prefix + Tq, H, Hkv, D)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    nb = -(-(prefix + Tq) // BLOCK)  # the last block's tail: zeros, unseen
    table = np.random.default_rng(seed).permutation(pool_blocks)[: B * nb]
    table = table.reshape(B, nb).astype(np.int32)
    pool = np.full((pool_blocks, 2, BLOCK, Hkv, D), np.nan, np.float32)
    for half, a in enumerate((k, v)):
        held = np.zeros((B, nb * BLOCK, Hkv, D), np.float32)
        held[:, : prefix + Tq] = np.asarray(a, np.float32)
        pool[table, half] = held.reshape(B, nb, BLOCK, Hkv, D)
    return q, k, v, jnp.asarray(pool, dtype), jnp.asarray(table)


@pytest.mark.parametrize(
    "B,Tq,prefix,H,Hkv,D",
    [
        (1, 256, 1024, 8, 2, 128),  # docs-shared's 256 over 8192, cut; groups of 4
        (1, 512, 512, 4, 2, 128),  # chat-sysprompt's 512 over 2048, cut; of 2
        (1, 256, 1024 + 48, 8, 2, 128),  # a prefix that ends inside a chunk of 512
        (2, 64, 576, 4, 4, 128),  # two tables, no grouping
        (1, 40, 560, 4, 2, 128),  # 40 query rows: a tile padded to 8s
    ],
)
def test_paged_entry_matches_dense(B, Tq, prefix, H, Hkv, D):
    """The continuation entry in interpreter mode against the dense
    reference over the same keys, float32 (the order of the sums is all
    that differs).  The table's blocks lie scattered through a pool whose
    other blocks hold NaN: only the table's may be read."""
    q, k, v, pool, table = _pooled(11, B, Tq, prefix, H, Hkv, D, jnp.float32)
    got = flash_gqa_attention_pallas_paged(
        q, pool, table, q_offset=prefix, interpret=True
    )
    dense = causal_gqa_attention(q, k, v, q_offset=prefix)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(dense), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("Tq,prefix,H", [(256, 1024, 8), (512, 512, 4)])
def test_paged_entry_is_the_flash_kernel_over_a_gathered_prefix(Tq, prefix, H):
    """In the serving type the entry gives what ``flash_gqa_attention_pallas``
    gives over the gathered K/V: float32 scores, softmax state and
    accumulators in both, rounded once to bfloat16."""
    q, k, v, pool, table = _pooled(12, 1, Tq, prefix, H, 2, 128, jnp.bfloat16)
    got = flash_gqa_attention_pallas_paged(
        q, pool, table, q_offset=prefix, interpret=True
    )
    flash = flash_gqa_attention_pallas(
        q, k, v, q_offset=prefix, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(flash, np.float32),
        rtol=0.01, atol=0.01,
    )


def test_paged_entry_refuses_a_table_that_ends_before_the_queries():
    q, _, _, pool, table = _pooled(13, 1, 64, 512, 4, 2, 128, jnp.float32)
    with pytest.raises(ValueError, match="table ends before"):
        flash_gqa_attention_pallas_paged(
            q, pool, table[:, :-1], q_offset=512, interpret=True
        )


@pytest.mark.parametrize(
    "block_size, Hkv, D, H, fits, in_budget",
    (
        (16, 8, 128, 32, True, True),  # Mistral's slots, a tile of 256 positions
        (16, 8, 128, 16, True, True),  # InternLM2's, a tile of 512
        (16, 4, 128, 28, True, True),  # seven query heads a KV head
        (16, 32, 128, 32, False, False),  # 32 KV heads: a step's K/V are 4x the room
        (16, 8, 256, 32, False, False),  # heads of 256 likewise
        (16, 4, 256, 16, False, True),  # and no head of 256 or 64 lowers,
        (16, 8, 64, 32, False, True),  # whatever room there is
        (48, 8, 128, 32, False, True),  # blocks that make up no step
    ),
)
def test_paged_entry_states_what_it_has_room_for(
    block_size, Hkv, D, H, fits, in_budget
):
    from llm_d_kv_cache_manager_tpu.ops import flash_pallas

    assert flash_pallas.fits_paged(block_size, Hkv, D, H) == fits
    need = flash_pallas.paged_vmem_bytes(Hkv, D, H)
    assert (need <= flash_pallas.PAGED_VMEM_BUDGET_BYTES) == in_budget


def test_paged_entry_refuses_slots_it_has_no_room_for():
    q = jnp.zeros((1, 48, 4, 128), jnp.float32)
    pool = jnp.zeros((4, 2, 48, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="fits_paged"):
        flash_gqa_attention_pallas_paged(
            q, pool, jnp.zeros((1, 2), jnp.int32), q_offset=48, interpret=True
        )
