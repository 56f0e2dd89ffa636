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
