"""Pallas paged-decode kernel vs the XLA gather implementation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)


def make_case(key, B, H, Hkv, D, num_blocks, bs, max_blocks, ctx):
    kq, kkv, kt = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32).astype(jnp.bfloat16)
    kv = jax.random.normal(
        kkv, (num_blocks, 2, bs, Hkv, D), jnp.float32
    ).astype(jnp.bfloat16)
    # Unique pool blocks per sequence, pad slots point at block 0.
    tables = []
    used = 1
    for b in range(B):
        n = -(-int(ctx[b]) // bs)
        ids = list(range(used, used + n))
        used += n
        tables.append(ids + [0] * (max_blocks - n))
    table = jnp.asarray(tables, jnp.int32)
    return q, kv, table, jnp.asarray(ctx, jnp.int32)


@pytest.mark.parametrize(
    "B,H,Hkv,D,max_blocks,ctx",
    [
        (1, 8, 4, 64, 8, [64]),  # exact block multiple
        (2, 8, 2, 64, 8, [61, 33]),  # ragged contexts
        (3, 4, 4, 128, 8, [16, 7, 128]),  # MHA, tiny and full contexts
        (2, 8, 4, 64, 7, [97, 112]),  # max_blocks % BLOCKS_PER_STEP != 0
    ],
)
def test_matches_xla_gather(B, H, Hkv, D, max_blocks, ctx):
    bs = 16
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(0), B, H, Hkv, D, 64, bs, max_blocks, ctx
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )


@pytest.mark.parametrize("blocks_per_step", [1, 2, 8])
def test_blocks_per_step_variants_match(blocks_per_step):
    """The tile size (LlamaConfig.decode_blocks_per_step) must be
    correctness-neutral at every value (ragged contexts +
    non-divisible tables)."""
    bs = 16
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(2), 2, 8, 4, 64, 64, bs, 7, [97, 33]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr,
        interpret=True,
        blocks_per_step=blocks_per_step,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_mxu_native_variant_matches():
    """The bf16-operand (mxu_native) dot path must agree with the f32
    upcast path within bf16 tolerance (neither is timed on a chip)."""
    bs = 16
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(3), 2, 8, 4, 64, 64, bs, 7, [97, 33]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True, mxu_native=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )


def test_context_one_token():
    """ctx=1: only the first slot of the first block is visible."""
    bs = 16
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(1), 1, 4, 2, 64, 16, bs, 4, [1]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )
