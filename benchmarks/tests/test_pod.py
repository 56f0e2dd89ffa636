"""CPU tests of the pod's cache manager and its three programs
(`harness/pod.py`) at a tiny size: `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, pod

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "tiny")
CFG = run.load(TINY, "configs", "tiny")


def new_pod(blocks: int, cfg: dict = CFG):
    program = family.program(cfg)
    return pod.Pod("p", program, program.from_published(cfg, 16), blocks)


def store(p, hashes):
    """What `Fleet.account` and `commit` do for a miss: (ids, evicted)."""
    ids, evicted = p.alloc(len(hashes))
    p.cached.update(zip(hashes, ids))
    return ids, evicted


def test_alloc_never_hands_out_a_block_a_live_sequence_references():
    p = new_pod(8)
    live, _ = store(p, [1, 2, 3])
    p.hold(live, +1)
    store(p, [4, 5, 6, 7, 8])
    ids, evicted = p.alloc(5)  # the free list is empty: all five are evicted
    assert not set(ids) & set(live) and sorted(evicted) == [4, 5, 6, 7, 8]
    assert p.cached_prefix([1, 2, 3]) == live
    with pytest.raises(RuntimeError, match="exhausted by live sequences"):
        p.alloc(1)
    p.hold(live, -1)
    assert p.alloc(3) == (live, [1, 2, 3])


def test_least_recently_used_blocks_go_first_and_come_back_as_evicted():
    p = new_pod(6)
    first, none = store(p, [10, 11, 12, 13, 14, 15])
    assert none == [] and first == list(range(6))  # the free list, in order
    p.touch([10, 11])  # used again: now the newest
    ids, evicted = p.alloc(3)
    assert evicted == [12, 13, 14] and ids == first[2:5]
    assert list(p.cached) == [15, 10, 11]


def test_cached_prefix_stops_at_the_first_hole():
    p = new_pod(8)
    ids, _ = store(p, [1, 2, 3, 4])
    assert p.cached_prefix([1, 2, 3, 4, 5]) == ids
    del p.cached[3]
    assert p.cached_prefix([1, 2, 3, 4]) == ids[:2]
    assert p.cached_prefix([9, 1, 2]) == []


@functools.cache
def drive(name: str) -> list:
    """A miss prefill, the hit prefill of the same prompt's second half and a
    decode step on one pod of the family `name`; what each served."""
    cfg = {**CFG, "family": name}
    program = family.program(cfg)
    model = program.from_published(cfg, 16)
    shapes = {"miss": (64,), "hit": (32, 32), "decode": (2,)}
    programs = pod.jit_programs(program, model, shapes, interpret=True)
    assert {k: f.__wrapped__.__name__ for k, f in programs.items()} == {
        "miss": "miss_prefill_T64", "hit": "hit_prefill_P32_S32",
        "decode": "decode_B2"}
    params = family.reference(cfg).make_weights(cfg, 5)
    p = pod.Pod("p", program, model, 8)
    assert len(jax.tree.leaves(p.kv)) == (2 if name == "two" else 1)
    tokens = np.arange(1, 65, dtype=np.int32)[None]
    table = np.arange(4, dtype=np.int32)[None]
    calls = (("miss", (tokens, table)),
             ("hit", (tokens[:, 32:], table)),
             ("decode", (np.array([7, 9], np.int32),
                         np.array([[0, 1, 2, 3, 4], [0, 1, 2, 3, 5]], np.int32),
                         np.array([65, 65], np.int32))))
    served = []
    for key, (ids, bt, *more) in calls:
        before = p.kv
        shape = jax.tree.map(lambda a: (a.shape, a.dtype), before)
        out, *_, p.kv = programs[key](params, ids, before, bt, *more)
        assert jax.tree.map(lambda a: (a.shape, a.dtype), p.kv) == shape
        assert all(a.is_deleted() for a in jax.tree.leaves(before))
        served.append(np.asarray(out))
    return served


@pytest.mark.parametrize("name", ("llama", "two"))
def test_the_three_programs_donate_the_pool_and_return_it_whole(
        name, second_family):
    """`two` keeps K and V in pools of their own: every leaf of the pool is
    donated and comes back with its shape."""
    miss, hit, _ = drive(name)
    assert np.array_equal(miss[0], hit[0])  # the same prompt, the same token


def test_a_two_leaf_pool_serves_what_the_one_array_serves(second_family):
    for one, two in zip(drive("llama"), drive("two")):
        np.testing.assert_array_equal(one[0], two[0])
        np.testing.assert_allclose(one[1], two[1], rtol=1e-2)


def test_a_family_may_bring_its_own_cache_manager(second_family):
    cfg = {**CFG, "family": "two"}
    program = family.program(cfg)
    fleet = engine.Fleet(program, program.from_published(cfg, 16), None,
                         {"pods": 2, "pool_blocks": 4}, {}, engine.Records(),
                         interpret=True)
    fleet.shutdown()
    assert [type(p) for p in fleet.pods] == [program.Pod] * 2
    assert program.Pod is not pod.Pod and fleet.programs == {}
