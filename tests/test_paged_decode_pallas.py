"""Pallas paged-decode kernel vs the XLA gather implementation: slots
[2, block, Hkv, D] (the `llama` pool's), many blocks a grid step as one
online-softmax update.  (Heads-first slots and window starts:
tests/test_afmoe_pod.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    BLOCKS_PER_STEP,
    paged_decode_attention_pallas,
)

BS = 16


def make_case(key, B, H, Hkv, D, num_blocks, max_blocks, ctx, permute=False):
    kq, kkv, kt = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32).astype(jnp.bfloat16)
    kv = jax.random.normal(
        kkv, (num_blocks, 2, BS, Hkv, D), jnp.float32
    ).astype(jnp.bfloat16)
    # Unique pool blocks per sequence (in order, or drawn in no order);
    # pad slots point at block 0.
    ids = np.arange(1, num_blocks)
    if permute:
        ids = np.asarray(jax.random.permutation(kt, ids))
    tables = []
    used = 0
    for b in range(B):
        n = -(-int(ctx[b]) // BS)
        tables.append(list(ids[used : used + n]) + [0] * (max_blocks - n))
        used += n
    table = jnp.asarray(tables, jnp.int32)
    return q, kv, table, jnp.asarray(ctx, jnp.int32)


def close(got, ref):
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        rtol=0.05,
        atol=0.05,
    )


CASES = {
    # name: (B, H, Hkv, D, max_blocks, blocks a step, contexts)
    "exact_block_multiple": (1, 8, 4, 64, 8, 4, [64]),
    "ragged_inside_a_block": (2, 8, 2, 64, 8, 4, [61, 33]),
    "mha_tiny_and_table_full": (3, 4, 4, 128, 8, 4, [16, 7, 128]),
    "columns_not_a_multiple_of_the_step": (2, 8, 4, 64, 7, 4, [97, 112]),
    # The chat cell's heads (internlm2-1.8b: 16 query, 8 KV, 128 wide).
    # Contexts end inside a block, inside a grid step (block 5 of 0..7 in
    # the second step of 4), on a step's edge, and at the table's last
    # column.
    "chat_heads_ragged": (4, 16, 8, 128, 12, 4, [83, 96, 128, 192]),
    "chat_heads_one_token": (2, 16, 8, 128, 12, 4, [1, 17]),
    "chat_heads_columns_not_a_multiple": (2, 16, 8, 128, 11, 4, [176, 70]),
    # The served blocks a step, two steps, the second partly past the end.
    "chat_heads_served_step": (1, 16, 8, 128, 2 * BLOCKS_PER_STEP - 3,
                               BLOCKS_PER_STEP, [BS * BLOCKS_PER_STEP + 40]),
}


@pytest.mark.parametrize("name", CASES)
def test_matches_xla_gather(name):
    B, H, Hkv, D, max_blocks, step, ctx = CASES[name]
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(0), B, H, Hkv, D, 64, max_blocks, ctx
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True, blocks_per_step=step
    )
    close(got, ref)


@pytest.mark.parametrize("layer", (0, 2))
def test_merged_pool_with_a_layer_offset(layer):
    """`llama._scan_layers` hands the kernel the pool of every layer as
    `L * N` slots and a table shifted by `layer * N`: block ids in no
    order, and the answer is that of the layer's own slice."""
    L, N, max_blocks = 3, 24, 9
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(4), 2, 16, 8, 128, L * N, max_blocks, [133, 90],
        permute=True,
    )
    table = table % N
    got = paged_decode_attention_pallas(
        q, kv, table + layer * N, ctx_arr, interpret=True, blocks_per_step=4
    )
    close(got, paged_attention(q, kv[layer * N : (layer + 1) * N], table,
                               ctx_arr))


@pytest.mark.parametrize("blocks_per_step", [1, 2, 8])
def test_blocks_per_step_variants_match(blocks_per_step):
    """The blocks a grid step takes (a static argument; `BLOCKS_PER_STEP`
    serves) must be correctness-neutral at every value (ragged contexts +
    non-divisible tables)."""
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(2), 2, 8, 4, 64, 64, 7, [97, 33]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr,
        interpret=True,
        blocks_per_step=blocks_per_step,
    )
    close(got, ref)


@pytest.mark.parametrize("mxu_native", (True, False))
def test_mxu_native_variants_match(mxu_native):
    """Operands of the query's type (bf16; what serves, `MXU_NATIVE`) and
    operands widened to f32 in VMEM agree within bf16 tolerance."""
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(3), 2, 8, 4, 64, 64, 7, [97, 33]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True, mxu_native=mxu_native,
        blocks_per_step=4,
    )
    close(got, ref)


def test_context_one_token():
    """ctx=1: only the first slot of the first block is visible."""
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(1), 1, 4, 2, 64, 16, 4, [1]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True
    )
    close(got, ref)


def test_other_heads_rows_do_not_leak():
    """Every query head is multiplied against every KV head's rows; only
    its own may weigh.  One KV head's values are made huge: the query
    heads of the others must not see them."""
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(5), 1, 8, 4, 128, 16, 4, [50]
    )
    loud = kv.at[:, 1, :, 2].set(1000.0)  # V of KV head 2
    got = paged_decode_attention_pallas(
        q, loud, table, ctx_arr, interpret=True, blocks_per_step=2
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    groups = 2
    others = np.asarray([h for h in range(8) if h // groups != 2])
    close(got[:, others], ref[:, others])
    assert float(jnp.min(got[:, 2 * groups : 3 * groups])) > 900.0
