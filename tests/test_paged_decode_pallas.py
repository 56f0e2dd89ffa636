"""Pallas paged-decode kernel vs the XLA gather implementation: slots
[2, block, Hkv, D] (the `llama` pool's; `phi4flash`'s with four query heads a
KV head), `packed` ones (`lfm2moe`'s) and heads-first ones (`afmoe`'s, which
go through no shared pass), the shared pass and the walk that copies each
sequence's own blocks, a wave as one online-softmax update.
(Window starts: tests/test_paged_decode_walk.py and tests/test_afmoe_pod.py
for the walk over heads-first slots, tests/test_phi4flash_pod.py for the grid
of tables by steps.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
    shared_prefix_plan,
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


SERVED_WAVE = paged_decode_pallas.walk_wave(
    jax.ShapeDtypeStruct((64, 2, BS, 8, 128), jnp.bfloat16))

CASES = {
    # name: (B, H, Hkv, D, max_blocks, blocks a wave, contexts)
    "exact_block_multiple": (1, 8, 4, 64, 8, 4, [64]),
    "ragged_inside_a_block": (2, 8, 2, 64, 8, 4, [61, 33]),
    "mha_tiny_and_table_full": (3, 4, 4, 128, 8, 4, [16, 7, 128]),
    "columns_not_a_multiple_of_the_step": (2, 8, 4, 64, 7, 4, [97, 112]),
    # The chat cell's heads (internlm2-1.8b: 16 query, 8 KV, 128 wide).
    # Contexts end inside a block, inside a wave (block 5 of 0..7 in the
    # second wave of 4), on a wave's edge, and at the table's last column.
    "chat_heads_ragged": (4, 16, 8, 128, 12, 4, [83, 96, 128, 192]),
    "chat_heads_one_token": (2, 16, 8, 128, 12, 4, [1, 17]),
    "chat_heads_columns_not_a_multiple": (2, 16, 8, 128, 11, 4, [176, 70]),
    # The served blocks a wave (None: `walk_wave` of the slot's bytes), two
    # waves, the second partly past the end.
    "chat_heads_served_wave": (1, 16, 8, 128, 2 * SERVED_WAVE - 3, None,
                               [BS * SERVED_WAVE + 40]),
}


@pytest.mark.parametrize("name", CASES)
def test_matches_xla_gather(name):
    B, H, Hkv, D, max_blocks, wave, ctx = CASES[name]
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(0), B, H, Hkv, D, 64, max_blocks, ctx
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr, interpret=True, walk_blocks_per_wave=wave
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
        q, kv, table + layer * N, ctx_arr, interpret=True,
        walk_blocks_per_wave=4,
    )
    close(got, paged_attention(q, kv[layer * N : (layer + 1) * N], table,
                               ctx_arr))


@pytest.mark.parametrize("blocks_per_wave", [1, 2, 8])
def test_blocks_per_wave_variants_match(blocks_per_wave):
    """The blocks a wave of the walk takes (a static argument; `walk_wave`
    of the slot's bytes serves) must be correctness-neutral at every value
    (ragged contexts + non-divisible tables)."""
    q, kv, table, ctx_arr = make_case(
        jax.random.PRNGKey(2), 2, 8, 4, 64, 64, 7, [97, 33]
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    got = paged_decode_attention_pallas(
        q, kv, table, ctx_arr,
        interpret=True,
        walk_blocks_per_wave=blocks_per_wave,
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
        walk_blocks_per_wave=4,
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
        q, loud, table, ctx_arr, interpret=True, walk_blocks_per_wave=2
    )
    ref = paged_attention(q, kv, table, ctx_arr)
    groups = 2
    others = np.asarray([h for h in range(8) if h // groups != 2])
    close(got[:, others], ref[:, others])
    assert float(jnp.min(got[:, 2 * groups : 3 * groups])) > 900.0


# ------------------------------------------------------ the shared-prefix pass

SCRATCH = 0  # the block an idle slot's table names in every column


def make_shared_case(key, H, Hkv, D, max_blocks, prompts, sequences,
                     packed=False, layers=1, layer=0, heads_first=False):
    """Tables as `Pod.cached_prefix` makes them: the sequences of one prompt
    have its blocks' ids at the head of their rows and blocks of their own
    behind.  prompts: blocks of each shared prompt; sequences: (prompt or
    None, context length), or None for an idle slot (context 1, every column
    the scratch block).  Ids are drawn in no order; with `layers` the pool is
    the merged one of `llama._scan_layers` and the table is `layer`'s."""
    kq, kkv, kt = jax.random.split(key, 3)
    B = len(sequences)
    own = [0 if s is None else -(-s[1] // BS) - (prompts[s[0]] if s[0]
           is not None else 0) for s in sequences]
    N = 1 + sum(prompts) + sum(own)
    ids = list(1 + np.asarray(jax.random.permutation(kt, N - 1)))
    shared = [[ids.pop() for _ in range(n)] for n in prompts]
    table, ctx = [], []
    for s, n in zip(sequences, own):
        head = [] if s is None or s[0] is None else shared[s[0]]
        row = head + [ids.pop() for _ in range(n)]
        table.append(row + [SCRATCH] * (max_blocks - len(row)))
        ctx.append(1 if s is None else s[1])
    q = jax.random.normal(kq, (B, H, D), jnp.float32).astype(jnp.bfloat16)
    kv = jax.random.normal(
        kkv, (layers * N, 2, BS, Hkv, D), jnp.float32).astype(jnp.bfloat16)
    table = jnp.asarray(table, jnp.int32)
    ctx = jnp.asarray(ctx, jnp.int32)
    ref = paged_attention(q, kv[layer * N:(layer + 1) * N], table, ctx)
    if packed:  # K in the lower half of a row's lanes, V in the upper
        kv = jnp.concatenate((kv[:, 0], kv[:, 1]), axis=-1)
    if heads_first:  # a block's positions under each KV head
        kv = kv.transpose(0, 1, 3, 2, 4)
    return q, kv, table + layer * N, ctx, ref


SHARED_CASES = {
    # name: (prompts' blocks, sequences, blocks a wave of the walk and a step
    # of the shared pass, layers and layer)
    # Sets of 3 and 2 in no slot order, a sequence on a prompt of its own
    # with nobody to share it, one with no prompt, an idle slot.
    "two_uneven_sets_a_loner_and_an_idle_slot": (
        [6, 4, 5], [(0, 130), (1, 100), None, (0, 97), (2, 90), (1, 70),
                    (None, 40), (0, 113)], 4, 2, 1, 0),
    # The first member's context ends in block 5, the others' in 7 and 9.
    "contexts_end_in_different_blocks": (
        [5], [(0, 88), (0, 125), (0, 150)], 2, 2, 1, 0),
    # 7 shared blocks, 4 a step of the shared pass: its second step holds 3.
    "run_not_a_multiple_of_the_shared_step": (
        [7], [(0, 160), (0, 129)], 4, 4, 1, 0),
    # The second sharer writes position 64, the first of the block behind
    # the run of 4; the third's context ends on the run's last position, so
    # that block is its write position's and the set's run is 3.
    "write_position_in_the_block_after_the_run": (
        [4], [(0, 100), (0, 65)], 4, 2, 1, 0),
    "write_position_inside_the_prompts_last_block": (
        [4], [(0, 100), (0, 65), (0, 64)], 4, 2, 1, 0),
    # Ten on one prompt: a group of eight and one of two; nine on another:
    # eight, and the ninth walks alone.
    "sets_larger_than_a_group": (
        [3, 2], [(0, 50 + i) for i in range(10)]
        + [(1, 40 + 3 * i) for i in range(9)], 4, 2, 1, 0),
    "nobody_shares": ([], [(None, 70), (None, 33), None], 4, 2, 1, 0),
    "merged_pool_with_a_layer_offset": (
        [5], [(0, 100), (None, 50), (0, 90)], 4, 2, 3, 2),
    # The walk that copies its own blocks (PR 41).  Contexts that fill their
    # last block: of the sharers' own blocks, of a wave (2 own blocks of 2 a
    # wave), and of a sequence with no prompt.
    "contexts_end_on_a_block_boundary": (
        [3], [(0, 80), (0, 96), (None, 64), (0, 112)], 2, 2, 1, 0),
    # One block a sequence: a sharer's only own block and lone sequences of a
    # token, of a few and of a block, one wave each and the next sequence's
    # asked for while it is multiplied.
    "contexts_of_a_single_block": (
        [2], [(None, 1), (0, 40), (None, 9), (0, 33), (None, 16)], 4, 2, 1, 0),
    # The run is the whole table but the last block: the walk copies one
    # block, resumed from the shared pass; the third has two of its own.
    "shared_run_is_all_but_the_last_block": (
        [11], [(0, 180), (0, 177), (0, 192)], 2, 4, 1, 0),
    # Idle slots first, between and last: each copies the scratch block once,
    # and the first wave of the sequence behind one is asked for by it.
    "idle_slots_on_the_scratch_block": (
        [4], [None, (0, 90), None, None, (0, 120), (None, 37), None],
        2, 2, 1, 0),
    # No first block in common: the shared pass's one empty step, and every
    # table walked whole, 1 to 11 blocks in waves of 4.
    "nobody_shares_ragged": (
        [], [(None, 176), (None, 16), (None, 65), (None, 129), (None, 3)],
        4, 2, 1, 0),
    "every_sequence_in_one_group": (
        [5], [(0, 81 + 13 * i) for i in range(8)], 4, 2, 1, 0),
    # 7, 5 and 6 blocks of their own in waves of 2 (three waves and four: the
    # buffers change hands inside a sequence and between two), 9 with no
    # prompt, and one wave.
    "more_own_blocks_than_two_waves": (
        [3], [(0, 160), (0, 128), (None, 140), (0, 139), (0, 50)], 2, 2, 1, 0),
}

# The slot layouts the walk serves: (H, Hkv, D), `packed` or not.
# `llama`: internlm2-1.8b's heads; `packed`: K and V side by side in the lanes
# at head size 64 (models/lfm2moe.py); `pairwise`: four query heads a KV head
# (models/phi4flash.py's differential attention); `heads_first`: slots
# [2, Hkv, bs, D] (models/afmoe.py), which no shared pass serves: the
# sequences of a prompt each walk its blocks.
LAYOUTS = {"llama": (16, 8, 128), "packed": (8, 4, 64), "pairwise": (8, 2, 128),
           "heads_first": (8, 2, 128)}
# Rows a block as two more cells serve them, a step of which is one operand
# (PR 52): `nemotron-3-nano-30b-a3b`'s 2 KV heads of 32 (32-row blocks) and
# `phi-4-mini-flash-reasoning`'s pair-wise 10 of 40 (160-row).
SERVED_ROWS = {"two_kv_heads_of_32": (32, 2, 128),
               "pairwise_10_of_40": (40, 10, 128)}


def shared_case(name, slots, key=7):
    prompts, sequences, wave, shared_step, layers, layer = SHARED_CASES[name]
    H, Hkv, D = {**LAYOUTS, **SERVED_ROWS}[slots]
    q, kv, table, ctx, ref = make_shared_case(
        jax.random.PRNGKey(key), H, Hkv, D, 12, prompts, sequences,
        packed=slots == "packed", layers=layers, layer=layer,
        heads_first=slots == "heads_first")
    statics = dict(interpret=True, walk_blocks_per_wave=wave,
                   shared_blocks_per_step=shared_step,
                   packed=slots == "packed", heads_first=slots == "heads_first")
    return (q, kv, table, ctx), statics, ref


@pytest.mark.parametrize("name, slots", [
    (name, slots) for slots in LAYOUTS for name in SHARED_CASES] + [
    # the shared pass and the walk at the served rows: a run's last partial
    # step hidden, groups of three and two beside loners, waves past the end
    (name, slots) for slots in SERVED_ROWS for name in (
        "run_not_a_multiple_of_the_shared_step",
        "two_uneven_sets_a_loner_and_an_idle_slot",
        "more_own_blocks_than_two_waves")])
def test_shared_prefix_pass_matches_xla_gather(name, slots):
    args, statics, ref = shared_case(name, slots)
    close(paged_decode_attention_pallas(*args, **statics), ref)


@pytest.mark.parametrize("least", (3, 4, 9))
@pytest.mark.parametrize("name", (
    "two_uneven_sets_a_loner_and_an_idle_slot", "sets_larger_than_a_group"))
def test_groups_under_the_least_size_are_walked(name, least):
    """`min_sequences`: a group of fewer members goes through no shared pass.
    Its sequences walk their whole tables, the groups left are numbered from
    0, the counts say what is read, and the kernels give what they gave."""
    (q, kv, table, ctx), statics, ref = shared_case(name, "llama")
    steps = dict(blocks_per_wave=statics["walk_blocks_per_wave"],
                 shared_blocks_per_step=statics["shared_blocks_per_step"])
    every = shared_prefix_plan(table, ctx, block_size=BS, **steps)
    plan = shared_prefix_plan(table, ctx, block_size=BS, min_sequences=least,
                              **steps)
    place, skip = (np.asarray(a) for a in every["walk"][:2])
    group = np.where(skip > 0, place // 8, -1)
    size = np.asarray([np.sum(group == g) if g >= 0 else 0 for g in group])
    kept = size >= least
    got_place, got_skip = (np.asarray(a) for a in plan["walk"][:2])
    assert list(got_skip) == list(np.where(kept, skip, 0))
    groups = sorted(set(group[kept]))
    assert list(got_place[kept]) == [
        8 * groups.index(g) + p % 8 for g, p in zip(group[kept], place[kept])]
    assert int(plan["shared_steps"]) == max(len(groups), 1)
    runs = sum(int(skip[group == g][0]) for g in groups)
    blocks = [-(-c // BS) for c in np.asarray(ctx)]
    assert int(plan["read_blocks"]) == runs + sum(blocks) - sum(got_skip)
    assert int(plan["walked_blocks"]) == sum(blocks)
    close(paged_decode_attention_pallas(q, kv, table, ctx, plan=plan,
                                        **statics), ref)


def test_the_plan_finds_the_sets_and_counts_what_is_read():
    """What `shared_prefix_plan` hands the kernels for the first case's
    tables: who resumes from which place of the shared pass's results and
    where each walk begins, the shared pass's grid, and the step's blocks
    read against a walk of every table."""
    prompts, sequences, _, _, _, _ = SHARED_CASES[
        "two_uneven_sets_a_loner_and_an_idle_slot"]
    _, _, table, ctx, _ = make_shared_case(
        jax.random.PRNGKey(7), 16, 8, 128, 12, prompts, sequences)
    plan = shared_prefix_plan(table, ctx, block_size=BS, blocks_per_wave=4)
    place, skip, runs = (np.asarray(a) for a in plan["walk"])
    assert runs.shape == (8, 3) and not runs.any()  # ids drawn in no order
    # prompt 0: rows 0, 3, 7 (group 0, run 6); prompt 1: rows 1, 5 (group 1,
    # run 4); rows 2 (idle), 4 (alone on its prompt) and 6 share nothing.
    assert list(place) == [0, 8, 0, 1, 0, 9, 0, 2]
    assert list(skip) == [6, 4, 0, 6, 0, 4, 0, 6]
    blocks = [-(-c // BS) for c in np.asarray(ctx)]
    row, run, members, step_runs = (np.asarray(a) for a in plan["shared"])
    assert step_runs.shape == (4, 1) and not step_runs.any()
    assert int(plan["shared_run_blocks"]) == 0
    assert int(plan["shared_steps"]) == 2
    assert list(row[:2]) == [0, 1] and list(run[:2]) == [6, 4]
    assert list(members[:16]) == [0, 3, 7, 0, 0, 0, 0, 0,
                                  1, 5, 1, 1, 1, 1, 1, 1]
    assert int(plan["read_blocks"]) == 6 + 4 + sum(blocks) - sum(skip)
    assert int(plan["walked_blocks"]) == sum(blocks)
