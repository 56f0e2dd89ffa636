"""The paged kernel's walk of each sequence's own blocks (PR 41): what it
copies, counted as the interpreted kernel runs, and what it traces to, which
is set-up's time.  (Against the XLA gather: tests/test_paged_decode_pallas.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
    shared_prefix_plan,
)
from tests.test_paged_decode_pallas import BS, LAYOUTS, close, shared_case


class CountedCopy:
    """`pltpu.make_async_copy`'s descriptor, its `start` noted with the pool
    block it names as the interpreted kernel runs it: a host callback beside
    the copy, inside whatever `pl.when` and loop the kernel put it in."""

    started: list = []

    def __init__(self, copy, block):
        self.copy, self.block = copy, block

    def start(self):
        jax.debug.callback(
            lambda block: CountedCopy.started.append(int(block)), self.block)
        self.copy.start()

    def wait(self):
        self.copy.wait()


@pytest.fixture
def counted_walk(monkeypatch):
    """The walk's kernel with its copies counted (the shared pass's are not)."""
    make, walk = pltpu.make_async_copy, paged_decode_pallas._walk_kernel

    def counted(src, dst, sem):
        # the pool block a descriptor names: where its source's slice begins
        return CountedCopy(make(src, dst, sem),
                           src.transforms[0].indices[0].start)

    def walk_counted(*refs, **statics):
        with monkeypatch.context() as m:
            m.setattr(pltpu, "make_async_copy", counted)
            return walk(*refs, **statics)

    monkeypatch.setattr(paged_decode_pallas, "_walk_kernel", walk_counted)
    CountedCopy.started = []
    return CountedCopy.started


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
    """Blocks copied / blocks in context past the runs = 1: the walk starts
    one copy for each of a sequence's blocks from the end of its shared run
    to its last block in context, in the table's order, and none for a column
    past the context (the walk before it multiplied whole steps of 32)."""
    args, statics, ref = shared_case(name, slots)
    _, _, table, ctx = args
    plan = shared_prefix_plan(table, ctx, block_size=BS)
    skip = np.asarray(plan["walk"][1])
    own = [int(block) for b, c in enumerate(np.asarray(ctx))
           for block in np.asarray(table)[b, skip[b]:-(-int(c) // BS)]]
    # not through the jit's cache: the counted kernel must be traced
    got = paged_decode_attention_pallas.__wrapped__(*args, **statics)
    jax.effects_barrier()
    close(got, ref)
    assert counted_walk == own
    runs = int(np.sum(np.asarray(plan["shared"][1])))
    assert len(own) == int(plan["read_blocks"]) - runs


# ------------------------------------------------ what the walk costs set-up

# The served shapes' heads (`internlm2-1.8b`, `lfm2-8b-a1b-l13`,
# `phi-4-mini-flash-reasoning` pair-wise), at the waves `walk_wave` gives them.
SERVED_HEADS = {"llama": (16, 8, 128), "packed": (32, 8, 64),
                "pairwise": (40, 10, 128)}


def equations(jaxpr) -> int:
    """The equations of a jaxpr, those of every jaxpr among their parameters
    (a jit's, a kernel's body, a loop's, a `pl.when`'s branches) counted in."""
    def inner(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield from inner(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for v in value:
                yield from inner(v)

    return sum(1 + sum(equations(j) for v in eqn.params.values()
                       for j in inner(v)) for eqn in jaxpr.eqns)


@pytest.mark.parametrize("slots", SERVED_HEADS)
def test_the_walk_traces_to_the_same_kernel_whatever_the_table(slots):
    """Set-up pays for every equation of the traced call (tracing, lowering,
    the kernel's compile): nothing in the plan, the shared pass or the walk
    is unrolled over a table's columns, its waves or its sequences, so a
    48-column table of 8 sequences traces to as many equations as a
    192-column one of 32.  (A wave's own copies and products are unrolled:
    `walk_wave` caps them.)"""
    H, Hkv, D = SERVED_HEADS[slots]
    pool = (64, BS, Hkv, 2 * D) if slots == "packed" else (64, 2, BS, Hkv, D)

    def traced(B, columns):
        spec = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(lambda *a: paged_decode_attention_pallas(
            *a, packed=slots == "packed"))(
            spec((B, H, D), jnp.bfloat16), spec(pool, jnp.bfloat16),
            spec((B, columns), jnp.int32), spec((B,), jnp.int32))
        assert "pallas_call" in str(jaxpr)
        return equations(jaxpr.jaxpr)

    counts = {shape: traced(*shape)
              for shape in ((8, 48), (8, 192), (32, 48), (32, 192))}
    assert len(set(counts.values())) == 1, counts
    wave = paged_decode_pallas.walk_wave(2 * BS * Hkv * D * 2)
    assert wave <= paged_decode_pallas.WALK_WAVE_BLOCKS
