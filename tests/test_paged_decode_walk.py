"""The paged kernel's walk of each sequence's own blocks (PR 41): what it
copies, counted as the interpreted kernel runs (a wave whose blocks lie one
after another in the pool by one copy, PR 43; heads-first slots and a window
layer's `start`, which share nothing, PR 45; a step of the shared pass by one
copy too, PR 52), and what it traces to, which is set-up's time.  (Against
the XLA gather: tests/test_paged_decode_pallas.py.)
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from llm_d_kv_cache_manager_tpu.models import glm4moelite
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
    shared_prefix_plan,
)
from tests.helpers.jaxprs import equations
from tests.test_latent_attention import SCALE, VALUE, W
from tests.test_paged_decode_pallas import (
    BS,
    LAYOUTS,
    SERVED_ROWS,
    close,
    shared_case,
)


class CountedCopy:
    """`pltpu.make_async_copy`'s descriptor, its `start` noted with the pool
    block it names and how many blocks it carries as the interpreted kernel
    runs it: a host callback beside the copy, inside whatever `pl.when` and
    loop the kernel put it in."""

    started: list = []
    waited: list = []  # how many blocks each wait was for

    def __init__(self, copy, block, blocks):
        self.copy, self.block, self.blocks = copy, block, blocks

    def start(self):
        jax.debug.callback(
            lambda block: CountedCopy.started.append((int(block), self.blocks)),
            self.block)
        self.copy.start()

    def wait(self):
        jax.debug.callback(lambda: CountedCopy.waited.append(self.blocks))
        self.copy.wait()


def counted_kernel(monkeypatch, name: str) -> list:
    """One of the module's two kernels with its copies counted (the other's
    are not)."""
    make, kernel = pltpu.make_async_copy, getattr(paged_decode_pallas, name)

    def counted(src, dst, sem):
        # the pool blocks a descriptor names: its source's slice of the pool
        blocks = src.transforms[0].indices[0]
        return CountedCopy(make(src, dst, sem), blocks.start, blocks.size)

    def kernel_counted(*refs, **statics):
        with monkeypatch.context() as m:
            m.setattr(pltpu, "make_async_copy", counted)
            return kernel(*refs, **statics)

    monkeypatch.setattr(paged_decode_pallas, name, kernel_counted)
    CountedCopy.started, CountedCopy.waited = [], []
    return CountedCopy.started


@pytest.fixture
def counted_walk(monkeypatch):
    return counted_kernel(monkeypatch, "_walk_kernel")


@pytest.fixture
def counted_shared(monkeypatch):
    return counted_kernel(monkeypatch, "_shared_kernel")


def plan_of(slots, table, ctx, wave, shared_step=2) -> dict:
    """The plan a call over such slots makes: heads-first ones share
    nothing."""
    least = {"min_sequences": None} if slots == "heads_first" else {}
    return shared_prefix_plan(table, ctx, block_size=BS, blocks_per_wave=wave,
                              shared_blocks_per_step=shared_step, **least)


@pytest.mark.parametrize("slots", LAYOUTS)
@pytest.mark.parametrize("name", (
    "two_uneven_sets_a_loner_and_an_idle_slot",
    "idle_slots_on_the_scratch_block",
    "more_own_blocks_than_two_waves",
    "contexts_of_a_single_block",
    "nobody_shares_ragged",
))
def test_the_walk_copies_each_block_in_context_past_the_runs_once(
        counted_walk, name, slots):
    """Blocks copied / blocks in context past the runs = 1: the walk brings
    each of a sequence's blocks from the end of its shared run to its last
    block in context once, in the table's order, and none for a column past
    the context (the walk before it multiplied whole steps of 32)."""
    args, statics, ref = shared_case(name, slots)
    _, _, table, ctx = args
    plan = plan_of(slots, table, ctx, statics["walk_blocks_per_wave"],
                   statics["shared_blocks_per_step"])
    own = [block for copy in expected_copies(
        table, ctx, plan, statics["walk_blocks_per_wave"]) for block in copy]
    # not through the jit's cache: the counted kernel must be traced
    got = paged_decode_attention_pallas.__wrapped__(*args, **statics)
    jax.effects_barrier()
    close(got, ref)
    assert brought(counted_walk) == own
    runs = int(np.sum(np.asarray(plan.get("shared", (0, 0))[1])))
    assert len(own) == int(plan["read_blocks"]) - runs


def brought(copies) -> list:
    """The pool blocks a list of counted descriptors names, in their order."""
    return [block + i for block, blocks in copies for i in range(blocks)]


def expected_copies(table, ctx, plan, wave) -> list:
    """What the walk should ask for, read from the table alone: a sequence's
    own blocks (from its run's end to its last in context) a wave at a time,
    a whole wave whose ids ascend by one as one copy, any other a copy a
    block; the sequences and their waves in order.  Each copy is the list of
    the blocks it carries."""
    table, skip = np.asarray(table), np.asarray(plan["walk"][1])
    copies = []
    for b, c in enumerate(np.asarray(ctx)):
        copies += copies_of(table[b, skip[b]:-(-int(c) // BS)], wave)
    return copies


def copies_of(blocks, wave) -> list:
    """The copies that bring ``blocks`` (ids in a table row's order) a wave
    at a time: a whole wave whose ids ascend by one as one copy, any other a
    copy a block."""
    copies = []
    for at in range(0, len(blocks), wave):
        ids = [int(i) for i in blocks[at:at + wave]]
        if len(ids) == wave and ids == list(range(ids[0], ids[0] + wave)):
            copies.append(ids)
        else:
            copies.extend([i] for i in ids)
    return copies


# ------------------------------------------------ a wave that is a run in the pool

WAVE = 4
# name: each sequence's (shared blocks, own blocks in context, blocks the table
# holds behind them) and how its own ids lie in the pool.  A fresh pool's
# allocator deals ascending ids (`Pod.alloc`), a free list used as a stack
# descending ones.
RUN_CASES = {
    # 2 whole waves and 2 loose blocks; 2 whole waves; less than a wave
    "all_ascending": ([(0, 10, 0), (0, 8, 0), (0, 3, 0)], "ascending"),
    "in_no_order": ([(0, 10, 0), (0, 8, 0), (0, 3, 0)], "no_order"),
    "one_break_inside_each_wave": ([(0, 10, 0), (0, 8, 0)], "broken"),
    "descending_ids": ([(0, 10, 0), (0, 8, 0)], "descending"),
    # the ids go on ascending past the context, which ends inside a wave
    "a_run_crosses_the_last_block": ([(0, 6, 4), (0, 9, 3)], "ascending"),
    # two sequences begin with the same 3 blocks: their walks' waves are
    # columns 3..6, 7..10 of tables of 12
    "a_run_starts_at_a_shared_runs_end": ([(3, 8, 0), (3, 5, 1)], "ascending"),
}
RUN_LAYOUTS = {**LAYOUTS, "latent": (5, 1, W)}
# The same, and the ids of the prompt the sequences share: the shared pass
# takes it 2 blocks a step, the last step of 7 a single block.
SHARED_RUN_CASES = {
    "a_prompt_dealt_of_a_fresh_pool": (
        [(7, 3, 0), (7, 5, 0), (0, 4, 0), (7, 1, 2)], "ascending",
        [1, 2, 3, 4, 5, 6, 7]),
    "one_break_inside_a_step": (  # 4, 3: the second step of four
        [(7, 3, 0), (7, 5, 0), (7, 1, 2)], "ascending", [1, 2, 4, 3, 5, 6, 7]),
    "a_prompt_in_no_order": (
        [(7, 3, 0), (7, 5, 0), (7, 1, 2)], "no_order", [6, 2, 7, 4, 1, 3, 5]),
    # two prompts, the second's run of 4 two whole steps
    "two_prompts": (
        [(7, 2, 0), (4, 3, 0), (7, 4, 0), (4, 5, 1)], "ascending",
        [1, 2, 3, 4, 5, 6, 7]),
}
SHARED_RUN_LAYOUTS = {
    **{k: v for k, v in RUN_LAYOUTS.items() if k != "heads_first"},
    "two_kv_heads_of_32": SERVED_ROWS["two_kv_heads_of_32"]}


def run_case(name, slots):
    """(q, pool, table, ctx), the call's static arguments, and the same
    content with the pool's blocks permuted into no order and the table
    naming them there."""
    sequences, order, *prompt = {**RUN_CASES, **SHARED_RUN_CASES}[name]
    prompt = prompt[0] if prompt else [1, 2, 3]
    H, Hkv, D = {**RUN_LAYOUTS, **SHARED_RUN_LAYOUTS}[slots]
    rng = np.random.default_rng(43)
    columns = 12
    N = 1 + len(prompt) + sum(own + more for _, own, more in sequences)
    free = iter(range(1 + len(prompt), N))
    table, ctx = [], []
    for shared, own, more in sequences:
        ids = [next(free) for _ in range(own + more)]
        if order == "descending":
            ids = ids[::-1]
        elif order == "no_order":
            ids = list(rng.permutation(ids))
        elif order == "broken":  # a swap inside each wave
            for at in range(1, len(ids) - 1, WAVE):
                ids[at], ids[at + 1] = ids[at + 1], ids[at]
        # the second of two prompts is the first's last blocks
        row = prompt[len(prompt) - shared:] + ids
        table.append(row + [0] * (columns - len(row)))
        ctx.append((shared + own) * BS - int(rng.integers(0, BS)))
    table, ctx = np.asarray(table, np.int32), jnp.asarray(ctx, jnp.int32)
    kq, kkv = jax.random.split(jax.random.PRNGKey(43))
    shape = {"packed": (N, BS, Hkv, 2 * D), "latent": (N, BS // 2, 2 * W),
             "heads_first": (N, 2, Hkv, BS, D)}.get(slots, (N, 2, BS, Hkv, D))
    pool = jax.random.normal(kkv, shape, jnp.float32).astype(jnp.bfloat16)
    q = jax.random.normal(kq, (len(ctx), H, D), jnp.float32).astype(jnp.bfloat16)
    statics = dict(interpret=True, walk_blocks_per_wave=WAVE,
                   shared_blocks_per_step=2, packed=slots == "packed",
                   heads_first=slots == "heads_first")
    if slots == "latent":
        statics.update(latent=VALUE, scale=SCALE)
    to = np.concatenate(([0], 1 + rng.permutation(N - 1)))  # where a block goes
    scattered = jnp.zeros_like(pool).at[to].set(pool)
    return ((q, pool, jnp.asarray(table), ctx), statics,
            (q, scattered, jnp.asarray(to[table], jnp.int32), ctx))


@pytest.mark.parametrize("slots", RUN_LAYOUTS)
@pytest.mark.parametrize("name", RUN_CASES)
def test_a_wave_that_is_a_run_in_the_pool_comes_by_one_copy(
        counted_walk, name, slots):
    """Copies issued = whole ascending waves + loose blocks: a wave of the
    walk whose blocks are all in context and lie one after another in the
    pool, in the table's order, is one descriptor of a wave's blocks, any
    other wave a descriptor a block; every block in context past the shared
    runs is brought once, none past the context; the plan's `run_blocks` is
    what came by runs; and the output is, bit for bit, that of the same
    content lying in no order."""
    args, statics, in_no_order = run_case(name, slots)
    _, _, table, ctx = args
    plan = plan_of(slots, table, ctx, WAVE)
    want = expected_copies(table, ctx, plan, WAVE)
    got = paged_decode_attention_pallas.__wrapped__(*args, plan=plan, **statics)
    jax.effects_barrier()
    copies = list(counted_walk)
    assert copies == [(ids[0], len(ids)) for ids in want]
    by_runs = sum(blocks for _, blocks in copies if blocks > 1)
    assert int(plan["run_blocks"]) == by_runs <= int(plan["read_blocks"])
    assert (by_runs > 0) == (RUN_CASES[name][1] == "ascending")
    counted_walk.clear()
    scattered = paged_decode_attention_pallas.__wrapped__(
        *in_no_order, **statics)
    jax.effects_barrier()
    assert len(counted_walk) == len(brought(copies))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(scattered, np.float32))


# ------------------------------------------------ a shared step that is a run

@pytest.mark.parametrize("name, slots", [
    # every layout over a prompt that lies in runs; the other tables at two
    # (a file is one worker's in tier-1: seconds here are the run's)
    (name, slots) for name in SHARED_RUN_CASES for slots in (
        SHARED_RUN_LAYOUTS if name == "a_prompt_dealt_of_a_fresh_pool"
        else ("packed", "two_kv_heads_of_32"))])
def test_a_shared_step_that_is_a_run_comes_by_one_copy(
        counted_shared, name, slots):
    """Copies the shared pass issues = whole ascending steps + loose blocks: a
    step of a group's run that lies wholly inside it, its blocks one after
    another in the pool in the table's order, is one descriptor of a step's
    blocks and one wait, any other step (one with a break, the run's last
    partial one) a descriptor and a wait a block; every block of every
    group's run is brought once either way, none past the run's end; the
    plan's `shared_run_blocks` is what came by runs; and the output is that
    of the XLA gather and, bit for bit, that of the same content lying in no
    order."""
    args, statics, in_no_order = run_case(name, slots)
    q, pool, table, ctx = args
    step = statics["shared_blocks_per_step"]
    plan = plan_of(slots, table, ctx, WAVE, step)
    row, run, _, step_runs = (np.asarray(a) for a in plan["shared"])
    prompts = [np.asarray(table)[row[g], :run[g]]
               for g in range(int(plan["shared_steps"]))]
    want = [ids for prompt in prompts for ids in copies_of(prompt, step)]
    got = paged_decode_attention_pallas.__wrapped__(*args, plan=plan, **statics)
    jax.effects_barrier()
    copies = list(counted_shared)
    assert copies == [(ids[0], len(ids)) for ids in want]
    assert CountedCopy.waited == [blocks for _, blocks in copies]
    assert brought(copies) == [int(i) for prompt in prompts for i in prompt]
    by_runs = sum(blocks for _, blocks in copies if blocks > 1)
    assert int(plan["shared_run_blocks"]) == by_runs == step * step_runs.sum()
    assert by_runs == {"a_prompt_dealt_of_a_fresh_pool": 6, "two_prompts": 10,
                       "one_break_inside_a_step": 4}.get(name, 0)
    assert int(plan["read_blocks"]) >= sum(len(p) for p in prompts) >= by_runs
    if slots != "latent":
        kv = pool if slots != "packed" else jnp.stack(
            jnp.split(pool, 2, axis=-1), axis=1)
        close(got, paged_attention(q, kv, table, ctx))
    counted_shared.clear()
    scattered = paged_decode_attention_pallas.__wrapped__(
        *in_no_order, **statics)
    jax.effects_barrier()
    # (two blocks of a prompt may come to lie side by side by chance)
    assert len(brought(counted_shared)) == len(brought(copies))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(scattered, np.float32))


# ------------------------------------------------ a window layer's table

@pytest.mark.parametrize("order", ("runs", "no_order"))
def test_a_window_tables_walk_hides_what_lies_before_its_start(
        counted_walk, order):
    """Heads-first slots with a `start` (models/afmoe.py's window layers): the
    table begins at the block that holds the window's first position, so the
    walk copies every block of it in context, a run by one copy, and what the
    first block holds before `start` is hidden like what lies past the
    context: `paged_attention(..., start=, heads_first=True)` to 1e-5 in
    float32, whole waves and a partial one, a start of 0 and a context of
    one block among them."""
    rng = np.random.default_rng(45)
    H, Hkv, D, columns = 8, 2, 16, 11
    ctx = jnp.asarray([10 * BS + 5, 8 * BS, 3 * BS + 1, 7], jnp.int32)
    start = jnp.asarray([9, 0, BS - 1, 3], jnp.int32)
    N = 1 + len(ctx) * columns
    ids = np.arange(1, N).reshape(len(ctx), columns)
    if order == "no_order":
        ids = rng.permutation(ids.ravel()).reshape(ids.shape)
    table = jnp.asarray(ids, jnp.int32)
    pool = jnp.asarray(rng.normal(size=(N, 2, Hkv, BS, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(len(ctx), H, D)), jnp.float32)
    plan = plan_of("heads_first", table, ctx, WAVE)
    got = paged_decode_attention_pallas.__wrapped__(
        q, pool, table, ctx, start=start, heads_first=True, plan=plan,
        walk_blocks_per_wave=WAVE, interpret=True, mxu_native=False)
    jax.effects_barrier()
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(paged_attention(q, pool, table, ctx, start=start,
                                   heads_first=True)),
        rtol=1e-5, atol=1e-5)
    want = expected_copies(table, ctx, plan, WAVE)
    assert list(counted_walk) == [(ids[0], len(ids)) for ids in want]
    whole = [-(-int(c) // BS) // WAVE * WAVE for c in ctx]  # 8, 8, 4, 0 blocks
    assert int(plan["run_blocks"]) == (sum(whole) if order == "runs" else 0)
    with pytest.raises(ValueError, match="share nothing"):
        paged_decode_attention_pallas(
            q, pool, table, ctx, start=start, heads_first=True, interpret=True,
            plan=shared_prefix_plan(table, ctx, block_size=BS,
                                    blocks_per_wave=WAVE),
            walk_blocks_per_wave=WAVE)


# ------------------------------------------------ what the walk costs set-up

# The served shapes' heads (`internlm2-1.8b`, `lfm2-8b-a1b-l13`,
# `phi-4-mini-flash-reasoning` pair-wise), at the waves `walk_wave` gives them,
# and `glm-4.7-flash`'s latent form (20 heads over slots [8, 1152]) at its
# family's waves of 64.
SERVED_HEADS = {"llama": (16, 8, 128), "packed": (32, 8, 64),
                "pairwise": (40, 10, 128), "latent": (20, 1, 576),
                "two_kv_heads": (32, 2, 128),  # `nemotron-3-nano-30b-a3b`
                "heads_first": (32, 4, 128), "heads_first_window": (32, 4, 128)}
# sha256 of the traced call's text at 8 sequences of 48 columns.  They stood
# from commit 81cfceb (PR 44), through the walk's learning heads-first slots
# and a `start` (PR 45), until PR 52 changed the shared pass and the walk for
# these layouts on purpose (a step is one operand; a shared step that is a run
# comes by one copy; both kernels' copies are `_bring`'s) and read them anew,
# with the fifth beside them, and measured their cells (PERF.md section 6).
# A PR that changes the kernels for them on purpose does the same.
TEXT_AT_PR_52 = {"llama": "ab27662ee73a62aa", "packed": "5a261fe9528c6da1",
                 "pairwise": "3a65b6367bff6a5d", "latent": "767839128531f238",
                 "two_kv_heads": "55d4d6de63cffd28"}


@pytest.mark.parametrize("slots", SERVED_HEADS)
def test_the_walk_traces_to_the_same_kernel_whatever_the_table(slots):
    """Set-up pays for every equation of the traced call (tracing, lowering,
    the kernel's compile): nothing in the plan, the shared pass or the walk
    is unrolled over a table's columns, its waves or its sequences, so a
    48-column table of 8 sequences traces to as many equations as a
    192-column one of 32, with the run's one copy and one wait in it.  (A
    wave's products are unrolled: `walk_wave` caps them.)  Heads-first slots,
    with a window's `start` and without, are the walk alone: one kernel."""
    H, Hkv, D = SERVED_HEADS[slots]
    heads_first = slots.startswith("heads_first")
    pool = {"packed": (64, BS, Hkv, 2 * D), "latent": (64, BS // 2, 2 * D)}.get(
        slots, (64, 2, Hkv, BS, D) if heads_first else (64, 2, BS, Hkv, D))
    statics = {"packed": slots == "packed", "heads_first": heads_first}
    if slots == "latent":
        statics = dict(
            latent=512, scale=D**-0.5,
            walk_blocks_per_wave=glm4moelite.DECODE_BLOCKS_PER_WAVE,
            shared_blocks_per_step=glm4moelite.DECODE_BLOCKS_PER_WAVE)

    texts = {}

    def traced(B, columns):
        spec = jax.ShapeDtypeStruct
        start = spec((B,), jnp.int32) if slots.endswith("window") else None
        jaxpr = jax.make_jaxpr(
            lambda q, kv, table, ctx, start: paged_decode_attention_pallas(
                q, kv, table, ctx, start=start, **statics))(
            spec((B, H, D), jnp.bfloat16), spec(pool, jnp.bfloat16),
            spec((B, columns), jnp.int32), spec((B,), jnp.int32), start)
        texts[B, columns] = str(jaxpr)
        assert texts[B, columns].count("pallas_call") == 2 - heads_first
        return equations(jaxpr.jaxpr)

    counts = {shape: traced(*shape)
              for shape in ((8, 48), (8, 192), (32, 48), (32, 192))}
    assert len(set(counts.values())) == 1, counts
    if slots in TEXT_AT_PR_52:
        assert hashlib.sha256(texts[8, 48].encode()).hexdigest()[:16] == (
            TEXT_AT_PR_52[slots])
    wave = paged_decode_pallas.walk_wave(
        jax.ShapeDtypeStruct(pool, jnp.bfloat16))
    assert wave <= paged_decode_pallas.WALK_WAVE_BLOCKS
