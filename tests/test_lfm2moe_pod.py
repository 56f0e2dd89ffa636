"""The `lfm2moe` family on the pod path (models/lfm2moe.py) and the pod's cache
with a state group (models/pod.py), at a small size on the CPU: seven layers
(a leading dense conv layer, then conv and attention layers with experts),
hidden 64, head size 16, 8 experts top-2 and no shared one, block 16, a
snapshot every second block.

The comparisons run the program in float32, where it has to repeat the plain
reference to rounding (2e-4 of the largest logit: the sums run in another
order); that the serving precision stays near it is the chip check's business
(benchmarks/harness/family_lfm2moe.py), and the last test here shows the
comparison would catch a lower precision.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import afmoe, lfm2moe, llama
from llm_d_kv_cache_manager_tpu.models.pod import (
    Pod, example_args, inner_programs, jit_programs,
)
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

BLOCK, VOCAB = 16, 128
C, A = lfm2moe.CONV, lfm2moe.FULL
CFG = lfm2moe.Lfm2MoeConfig(
    dtype="float32", vocab_size=VOCAB, layer_types=(C, A, C, C, A, C, C),
    state_slots=24, state_stride_blocks=2)
PARAMS = lfm2moe.init_params(jax.random.key(0), CFG)
STEPS = {
    "miss": jax.jit(functools.partial(lfm2moe.prefill_paged, cfg=CFG)),
    "hit": jax.jit(functools.partial(lfm2moe.prefill_continue, cfg=CFG),
                   static_argnames=("prefix_len",)),
    "decode": jax.jit(functools.partial(lfm2moe.decode_step, cfg=CFG)),
}


def tokens_of(n: int, *key: int) -> np.ndarray:
    return np.random.default_rng([7, *key]).integers(1, VOCAB, n)


def hashes_of(tokens) -> list[int]:
    """Chained block hashes, as the benchmark's engine makes them."""
    out, parent = [], b"root"
    data, width = np.asarray(tokens, "<i8").tobytes(), 8 * BLOCK
    for i in range(0, len(data) - len(data) % width, width):
        parent = hashlib.sha256(parent + data[i:i + width]).digest()
        out.append(int.from_bytes(parent[-8:], "big"))
    return out


_reference = jax.jit(functools.partial(lfm2moe.reference_logits, cfg=CFG))


@functools.cache
def reference(tokens: tuple) -> np.ndarray:
    """Logits [T, V] of the whole sequence (causal: row t is what a step that
    was fed token t has to give)."""
    return np.asarray(_reference(PARAMS, jnp.asarray(tokens)))


class Engine:
    """What the benchmark's engine does around a pod, call for call
    (`Fleet.account`, `run_chat.admit`, `commit`, `finish`), with the model
    steps run directly so that a test sees whole rows of logits."""

    def __init__(self, pool_blocks: int = 48, cfg=CFG, steps=STEPS,
                 params=PARAMS) -> None:
        self.cfg, self.steps, self.params = cfg, steps, params
        self.pod = Pod("pod-0", lfm2moe, cfg, pool_blocks)
        self.removed: list[int] = []  # every hash an alloc gave back

    def prefill(self, tokens, n_prefix: int, own: int = 0) -> dict:
        pod, hashes = self.pod, hashes_of(tokens)
        cached = pod.cached_prefix(hashes[:n_prefix]) if n_prefix else []
        hit = bool(n_prefix) and len(cached) == n_prefix
        first_new = n_prefix if hit else 0
        pod.touch(hashes[:first_new])
        pod.hold(cached[:first_new], +1)
        new_ids, evicted = pod.alloc(len(hashes) - first_new)
        pod.hold(cached[:first_new], -1)
        blocks = cached[:first_new] + new_ids
        pod.hold(blocks, +1)
        own_ids, more = pod.alloc(own)
        pod.hold(own_ids, +1)
        tables = pod.tables("hit" if hit else "miss",
                            np.asarray(blocks, np.int32)[None],
                            prefix_blocks=first_new)
        self.poison()
        ids = jnp.asarray(tokens[first_new * BLOCK:], jnp.int32)[None]
        if hit:
            logits, arrays = self.steps["hit"](
                self.params, ids, pod.kv.arrays, tables,
                prefix_len=first_new * BLOCK)
        else:
            logits, arrays = self.steps["miss"](self.params, ids,
                                                pod.kv.arrays, tables)
        arrays.pop("load")
        pod.kv.arrays = arrays
        for h, bid in zip(hashes[first_new:], blocks[first_new:]):
            pod.cached[h] = bid
        self.removed += evicted + more
        return dict(hit=hit, cached=len(cached), blocks=blocks + own_ids,
                    own=own_ids, evicted=evicted + more, hashes=hashes,
                    row=np.asarray(logits[0, 0]), tokens=list(tokens))

    def decode(self, seqs: list[dict]) -> np.ndarray:
        """One step for the sequences given (each dict of `prefill`, its
        `tokens` grown by the token to feed); returns logits [B, V]."""
        width = max(len(s["blocks"]) for s in seqs)
        table = np.zeros((len(seqs), width), np.int32)
        for i, s in enumerate(seqs):
            table[i, :len(s["blocks"])] = s["blocks"]
        ctx = np.asarray([len(s["tokens"]) for s in seqs], np.int32)
        cur = np.asarray([s["tokens"][-1] for s in seqs], np.int32)
        tables = self.pod.tables("decode", table, context_len=ctx)
        self.poison(keep=tables["state"][:, 0])
        logits, arrays = self.steps["decode"](
            self.params, cur, self.pod.kv.arrays, tables, ctx)
        arrays.pop("load")
        self.pod.kv.arrays = arrays
        return np.asarray(logits)

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])

    def poison(self, keep=()) -> None:
        """NaN into every state slot that is free, or held by a block that
        is neither cached nor about to be read: a step that read a state the
        rules do not keep would show it."""
        group, pod = self.pod.state, self.pod
        block = np.maximum(group.block_of, 0)
        dead = (group.block_of < 0) | ~pod.hashed[block]
        dead[np.asarray(keep, np.int64)] = False
        slots = jnp.asarray(np.flatnonzero(dead))
        pod.kv.arrays["state"] = [a.at[slots].set(jnp.nan)
                                  for a in pod.kv.arrays["state"]]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


def test_prefill_repeats_the_reference_and_keeps_the_stride_boundaries():
    """A miss of six blocks, stride 2: snapshots of blocks 1, 3, 5 (the last
    is both a stride boundary and the call's end), each the conv layers'
    (z_{t-1}, z_t) at the block's last position, which the reference's own
    z gives."""
    eng = Engine()
    seq = eng.prefill(tokens_of(96, 1), 0)
    close(seq["row"], reference(tuple(seq["tokens"]))[-1])
    group = eng.pod.state
    have = [int(group.slot_of[b] >= 0) for b in seq["blocks"]]
    assert have == [0, 1, 0, 1, 0, 1]
    # the first conv layer's z from the weights, by hand
    lp = PARAMS["layers"][0]
    x = np.asarray(PARAMS["embed"])[np.asarray(seq["tokens"])]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + CFG.rms_eps) * np.asarray(
        lp["ln_op"])
    bcu = np.einsum("td,dce->tce", h, np.asarray(lp["w_in"]))
    z = bcu[:, 0] * bcu[:, 2]
    for i in (1, 3, 5):
        slot = group.slot_of[seq["blocks"][i]]
        end = (i + 1) * BLOCK - 1
        close(np.asarray(eng.pod.kv.arrays["state"][0][slot]),
              z[end - 1:end + 1], 1e-5)


@pytest.mark.parametrize("prefix_blocks, resumed", ((4, 4), (3, 0), (5, 0)))
def test_continue_from_a_snapshot_repeats_the_references_full_pass(
        prefix_blocks, resumed):
    """A six-block prompt is stored (snapshots after blocks 1, 3, 5).  A
    prompt that shares its first four blocks continues from the snapshot of
    block 3 and gives the reference's logits.  One that shares three, or
    five, ends one block before or after a kept boundary: `cached_prefix`
    falls back to two, or four, blocks, which is not the whole prefix, so the
    engine recomputes it all: it is never continued from another block's
    state."""
    eng = Engine()
    doc = tokens_of(96, 1)
    eng.prefill(doc, 0)
    turn = tokens_of(32, 2)
    second = eng.prefill(np.concatenate((doc[:prefix_blocks * BLOCK], turn)),
                         prefix_blocks)
    assert second["hit"] == bool(resumed)
    assert second["cached"] == (resumed or prefix_blocks - 1)
    close(second["row"], reference(tuple(second["tokens"]))[-1])
    counts = eng.pod.state.counts
    assert counts["resume_short_blocks"] == (0 if resumed else 1)
    assert counts["asked_blocks"] == prefix_blocks


def test_decode_over_forty_steps_repeats_the_reference():
    """A miss and a hit on its first four blocks, admitted at different
    positions (96 and 97 .. against 80 ..), decoded side by side for 44
    steps: every sequence crosses two block edges and a stride boundary, and
    every row of logits is the reference's.  Before each step every state
    slot the rules do not keep holds NaN."""
    eng = Engine()
    doc = tokens_of(64, 1)
    first = eng.prefill(np.concatenate((doc, tokens_of(32, 3))), 4, own=3)
    second = eng.prefill(np.concatenate((doc, tokens_of(16, 2))), 4, own=3)
    assert not first["hit"] and second["hit"]
    assert second["blocks"][:4] == first["blocks"][:4]
    seqs = [first, second]
    for s in seqs:
        s["tokens"].append(int(np.argmax(s["row"])))
        s["rows"] = []
    first["rows"].append(eng.decode([first])[0])  # one step ahead
    first["tokens"].append(int(np.argmax(first["rows"][-1])))
    for _ in range(44):
        logits = eng.decode(seqs)
        assert np.isfinite(logits).all()
        for s, row in zip(seqs, logits):
            s["rows"].append(row)
            s["tokens"].append(int(np.argmax(row)))
    for s in seqs:
        n = len(s["rows"])
        close(np.stack(s["rows"]), reference(tuple(s["tokens"]))[-n - 1:-1])
    group = eng.pod.state
    assert group.counts["resume_short_blocks"] == 0
    assert group.counts["released"] >= 2  # own blocks behind the rolling two
    # a live sequence holds its current block's slot and the one before
    for s in seqs:
        at = (len(s["tokens"]) - 2) // BLOCK
        held = [int(group.slot_of[b] >= 0) for b in s["own"]]
        own_at = at - (len(s["blocks"]) - len(s["own"]))
        assert held[own_at] == 1 and sum(held) <= 2


def test_the_three_programs_serve_the_reference_tokens_and_report_spans():
    """`jit_programs` over a pod with a state group: every shape compiles at
    the first call of any, the tokens served are the reference's, and the
    spans carry what the state group did."""
    shapes = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(lfm2moe, CFG, shapes, interpret=False)
    pod = Pod("pod-0", lfm2moe, CFG, 40)
    doc = tokens_of(64, 1)
    prompts = [np.concatenate((doc, tokens_of(32, 3))),
               np.concatenate((doc, tokens_of(32, 2)))]
    ids, _ = pod.alloc(6)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        before = jax.tree.leaves(pod.kv.arrays)
        out, row, kv = programs["miss"](
            PARAMS, prompts[0][None], pod.kv, np.asarray(ids)[None])
        assert kv is pod.kv and all(a.is_deleted() for a in before)
        assert int(np.asarray(out)[0, 0]) == reference(
            tuple(prompts[0]))[-1].argmax()
        compiled = len(compiles)
        assert compiled >= 3
        pod.cached.update(zip(hashes_of(prompts[0]), ids))
        more, _ = pod.alloc(2)
        out, row, kv = programs["hit"](
            PARAMS, prompts[1][None, 64:], pod.kv,
            np.asarray(ids[:4] + more)[None])
        close(np.asarray(row), reference(tuple(prompts[1]))[-1])
        table = np.zeros((2, 9), np.int32)
        table[0, :6], table[1, :6] = ids, ids[:4] + more
        nxt = np.asarray([reference(tuple(p))[-1].argmax() for p in prompts])
        own, _ = pod.alloc(2)
        table[0, 6], table[1, 6] = own
        out, kv = programs["decode"](PARAMS, nxt, pod.kv, table,
                                     np.asarray([97, 97]))
        assert len(compiles) == compiled  # nothing compiled after the first call
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[-1].argmax() for s in seqs]
        out, kv = programs["decode"](PARAMS, np.asarray(out)[0].astype(int),
                                     pod.kv, table, np.asarray([98, 98]))
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    spans = [r for r in rows if r["span"] is not None]
    assert {r["span"] for r in spans} == {
        "kvpool.state", "state.read", "moe.expert_load", "pod.compile",
        "pod.counts_read", "pod.pack", "pod.launch.miss", "pod.launch.hit",
        "pod.launch.decode"}  # the call's own parts: tests/test_pod_step_spans.py
    assert {r["trace"] for r in rows if r["span"] is None} == {"pod.step"}
    state = [r["attrs"] for r in spans if r["span"] == "kvpool.state"]
    assert [a["taken"] for a in state] == [3, 1, 2, 0]
    assert all(a["calls"] == 1 and a["resume_short_blocks"] == 0
               for a in state)
    read = [r["attrs"] for r in spans if r["span"] == "state.read"][-1]
    per = lfm2moe.cache_groups(CFG)
    assert read == {
        "state_slots_live": 6, "blocks_live": 10,
        "state_bytes": 2 * 2 * per["state"].block_nbytes,
        "kv_bytes": 14 * per["full"].block_nbytes}
    load = [r["attrs"] for r in spans if r["span"] == "moe.expert_load"]
    assert len(load) == 6 and all(
        a["experts_held"] == 8 and 1 <= a["experts_touched"] <= 4
        for a in load)


def test_a_decode_step_counts_the_blocks_its_attention_reads():
    """Two sequences on one cached prompt, through `jit_programs` with the
    paged kernel (interpreted): the step's `attention.read` span, read at the
    next decode call, carries what the kernel's plan counted on the device,
    and that is what the pod's own tables say: the four shared blocks once
    and each sequence's other three, against seven a sequence."""
    shapes = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(lfm2moe, CFG, shapes, interpret=True)
    pod = Pod("pod-0", lfm2moe, CFG, 40)
    doc = tokens_of(64, 1)
    prompts = [np.concatenate((doc, tokens_of(32, 3))),
               np.concatenate((doc, tokens_of(32, 2)))]
    ids, _ = pod.alloc(6)
    programs["miss"](PARAMS, prompts[0][None], pod.kv, np.asarray(ids)[None])
    pod.cached.update(zip(hashes_of(prompts[0]), ids))
    more, _ = pod.alloc(2)
    programs["hit"](PARAMS, prompts[1][None, 64:], pod.kv,
                    np.asarray(ids[:4] + more)[None])
    own, _ = pod.alloc(2)
    table = np.zeros((2, 9), np.int32)
    table[0, :7], table[1, :7] = ids + own[:1], ids[:4] + more + own[1:]
    nxt = np.asarray([reference(tuple(p))[-1].argmax() for p in prompts])
    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        out, kv = programs["decode"](PARAMS, nxt, pod.kv, table,
                                     np.asarray([97, 97]))
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[-1].argmax() for s in seqs]
        assert "attention_read" not in pod.kv.arrays  # no part of the pools
        programs["decode"](PARAMS, np.asarray(out)[0].astype(int), pod.kv,
                           table, np.asarray([98, 98]))
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    read = [r["attrs"] for r in rows if r["span"] == "attention.read"]
    shared = sum(a == b for a, b in zip(*table)) - 2  # not the empty columns
    assert shared == 4 and dropped == 0
    # a tiny pool's waves of 16 hold no table of nine columns: no run
    assert read == [{"read_blocks": shared + 2 * (7 - shared),
                     "walked_blocks": 2 * 7, "run_blocks": 0,
                     "shared_run_blocks": 0}]


def test_pallas_decode_in_the_step_agrees_with_the_gather():
    """The paged kernel (interpreted here) at this family's head size, in
    the step, against the XLA gather and the reference."""
    eng = Engine()
    seq = eng.prefill(tokens_of(96, 9), 0, own=1)
    seq["tokens"].append(int(np.argmax(seq["row"])))
    tables = eng.pod.tables("decode", np.asarray(seq["blocks"], np.int32)[None],
                            context_len=np.asarray([97]))
    args = (PARAMS, jnp.asarray(seq["tokens"][-1:]), eng.pod.kv.arrays, tables,
            jnp.asarray([97]))
    one, _ = lfm2moe.decode_step(*args, CFG)
    two, _ = lfm2moe.decode_step(*args, CFG, interpret=True)
    close(np.asarray(two), np.asarray(one), 1e-5)
    close(np.asarray(one[0]), reference(tuple(seq["tokens"]))[-1])


@pytest.mark.parametrize("blocks_per_wave", (2, 4))
def test_paged_kernel_over_packed_slots_is_the_gather(blocks_per_wave):
    """Slots [block, Hkv, 2 Dh], K in the lower half of a row and V in the
    upper: the kernel's output (the query padded over V's lanes, the result
    read from them) against the XLA gather over the slots unpacked, with a
    table whose columns the blocks a wave of the walk do not divide."""
    from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
    from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
        paged_decode_attention_pallas,
    )

    rng = np.random.default_rng(8)
    pool = jnp.asarray(rng.normal(size=(12, 16, 2, 32)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    table = jnp.asarray(rng.permutation(12)[:9].reshape(3, 3), jnp.int32)
    ctx = jnp.asarray([40, 17, 33])
    got = paged_decode_attention_pallas(
        q, pool, table, ctx, packed=True,
        walk_blocks_per_wave=blocks_per_wave, interpret=True)
    want = paged_attention(
        q, jnp.stack((pool[..., :16], pool[..., 16:]), axis=1), table, ctx)
    close(np.asarray(got), np.asarray(want), 1e-5)
    with pytest.raises(ValueError, match="packed"):
        paged_decode_attention_pallas(q, pool, table, ctx, packed=True,
                                      heads_first=True, interpret=True)


# ---------------------------------------------------------- the state group


def store(pod, hashes, n_prefix=0):
    """What the engine does for a request that misses: ask, alloc, commit."""
    pod.cached_prefix(hashes[:n_prefix]) if n_prefix else None
    ids, evicted = pod.alloc(len(hashes))
    pod.tables("miss", np.asarray(ids)[None])
    pod.cached.update(zip(hashes, ids))
    return ids, evicted


@pytest.mark.parametrize("n, served", (
    (1, 0), (2, 2), (3, 2), (4, 4), (5, 4), (6, 4), (7, 7)))
def test_hit_rule_wants_the_snapshot_at_the_prefixs_end(n, served):
    """A seven-block miss, stride 2, keeps the state after blocks 1, 3, 5
    (stride) and 6 (the call's end).  A prefix is a hit at those lengths;
    any other falls back to the longest that is, and the blocks given up are
    counted."""
    pod = Pod("p", lfm2moe, CFG, 40)
    long = list(range(100, 107))
    ids, _ = store(pod, long)
    if n == 6:
        pod.state.drop(ids[5])
    assert pod.cached_prefix(long[:n]) == ids[:served]
    assert pod.state.counts["resume_short_blocks"] == n - served
    assert pod.state.counts["asked_blocks"] == n


def test_rolling_slots_never_alias_a_cached_snapshot():
    """The first decode step after a prefill reads the prefill's end snapshot
    and writes the sequence's own block's slot; the snapshot a later hit
    resumes from is the same array before and after 20 steps."""
    eng = Engine()
    seq = eng.prefill(tokens_of(64, 5), 0, own=2)
    group = eng.pod.state
    end = int(group.slot_of[seq["blocks"][3]])
    kept = [np.asarray(a[end]) for a in eng.pod.kv.arrays["state"]]
    seq["tokens"].append(int(np.argmax(seq["row"])))
    for step in range(20):
        tables_before = group.slot_of.copy()
        row = eng.decode([seq])[0]
        seq["tokens"].append(int(np.argmax(row)))
        assert group.slot_of[seq["blocks"][3]] == end == tables_before[
            seq["blocks"][3]]
    for a, want in zip(eng.pod.kv.arrays["state"], kept):
        assert np.array_equal(np.asarray(a[end]), want)
    again = eng.prefill(np.concatenate((tokens_of(64, 5), tokens_of(16, 6))), 4)
    assert again["hit"]
    close(again["row"], reference(tuple(again["tokens"]))[-1])


def test_released_slots_go_never_asked_first_and_take_the_chains_tail():
    """Eight state slots, stride 2.  A document of four blocks is asked for,
    then turns nobody asks for stream through as hits on it: each takes one
    slot (its end).  When none is free the group reuses the turns' slots,
    oldest first, evicting each turn's blocks from the full group too (the
    hashes ride in `alloc`'s list); the document's own two snapshots, the
    coldest of all but asked for, stay, and it stays a hit."""
    cfg = dataclasses.replace(CFG, state_slots=8)
    pod = Pod("p", lfm2moe, cfg, 64)
    doc = [1, 2, 3, 4]
    ids, _ = store(pod, doc + [50], n_prefix=4)  # snapshots: 1, 3, 4 (end)
    assert [int(pod.state.slot_of[b] >= 0) for b in ids] == [0, 1, 0, 1, 1]
    gone = []
    for turn in range(8):
        hit = pod.cached_prefix(doc)
        assert hit == ids[:4]
        pod.touch(doc)
        new, evicted = pod.alloc(1)
        pod.tables("hit", np.asarray(hit + new)[None], prefix_blocks=4)
        pod.cached[60 + turn] = new[0]
        gone += evicted
    # 8 slots: 2 of the document, 6 turns' ends; the 7th and 8th turn and the
    # first reuse push out the oldest never-asked ends
    assert gone == [50, 60]
    assert pod.state.counts["reclaimed"] == 3
    new, evicted = pod.alloc(1)
    assert evicted == [61]  # evicted at the last table build, published now
    assert pod.state.counts["resume_short_blocks"] == 0
    assert all(h in pod.cached for h in doc) and 50 not in pod.cached
    # asked ones go only when nothing else is left, and take their tail along
    gone = []
    for _ in range(2):
        live, evicted = pod.alloc(8)
        pod.hold(live, +1)
        pod.tables("miss", np.asarray(live)[None])  # 4 stride snapshots
        gone.append(evicted)
    gone.append(pod.alloc(1)[1])
    assert gone == [[], [62, 63, 64, 65], [66, 67, 2, 3, 4]]
    assert pod.cached_prefix(doc) == [] and 1 in pod.cached


def test_a_live_sequences_state_is_never_reused_and_exhaustion_is_an_error():
    cfg = dataclasses.replace(CFG, state_slots=2)
    pod = Pod("p", lfm2moe, cfg, 16)
    ids, _ = store(pod, [1, 2, 3, 4])  # snapshots after blocks 1 and 3
    pod.hold(ids, +1)
    more, _ = pod.alloc(2)
    with pytest.raises(RuntimeError, match="state group exhausted"):
        pod.tables("miss", np.asarray(more)[None])
    pod.hold(ids, -1)
    pod.tables("miss", np.asarray(more)[None])
    assert pod.alloc(1)[1] == [2, 3, 4]  # the boundary block and its tail


def test_a_decode_step_from_a_position_no_step_wrote_is_still_handed_a_slot():
    """The benchmark's set-up requests start `done` tokens into an answer:
    the block of position p - 1 is then an own block no step has written."""
    eng = Engine()
    seq = eng.prefill(tokens_of(32, 8), 0, own=2)
    table = np.asarray(seq["blocks"], np.int32)[None]
    tables = eng.pod.tables("decode", table, context_len=np.asarray([32 + 21]))
    read, write = tables["state"][0]
    assert read == write == eng.pod.state.slot_of[seq["own"][1]] >= 0
    tables = eng.pod.tables("decode", table, context_len=np.asarray([32 + 17]))
    assert list(tables["state"][0]) == [
        eng.pod.state.slot_of[b] for b in seq["own"]]


def test_a_pod_without_a_state_group_is_the_pod_it_was():
    assert lfm2moe.cache_policy(dataclasses.replace(
        CFG, layer_types=(A, A)))["state"] is None
    pod = Pod("p", afmoe, afmoe.AfmoeConfig(), 8)
    assert pod.state is None and pod.groups == [pod.window]
    table = np.asarray(pod.alloc(2)[0], np.int32)[None]
    assert set(pod.tables("miss", table)) == {"full", "window"}


# ------------------------------------------------- geometry and configuration


def test_block_bytes_come_from_one_spec_per_group():
    """At the published widths and the cell's cut: a full-group slot is
    96 KB (three attention layers, 8 KV heads of 64), a state slot 80 KB
    (ten conv layers, two inputs of 2048) whatever the block."""
    cfg = dataclasses.replace(
        CFG, d_model=2048, n_heads=32, n_kv_heads=8, dtype="bfloat16",
        layer_types=(C, A, C, C, C, A, C, C, C, A, C, C, C))
    groups = lfm2moe.cache_groups(cfg)
    assert cfg.head_dim == 64
    assert groups["full"].block_nbytes == 96 * 1024
    assert groups["state"].block_nbytes == 80 * 1024
    assert dataclasses.replace(groups["state"],
                               block_size=64).block_nbytes == 80 * 1024
    assert groups["state"].layer_shape(7) == (7, 2, 2048)
    assert groups["state"].snapshot_blocks(0, 6) == [1, 3, 5]
    assert dataclasses.replace(groups["state"], stride_blocks=16
                               ).snapshot_blocks(512, 32) == [527, 543]
    pools = lfm2moe.new_pool(CFG, 10)
    assert [a.shape for a in pools["full"]] == [(10, 16, 2, 32)] * 2  # packed
    assert [a.shape for a in pools["state"]] == [(24, 2, 64)] * 5


def published(**over) -> dict:
    cfg = dict(
        vocab_size=VOCAB, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=128, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
        num_hidden_layers=7, layer_types=list(CFG.layer_types), conv_L_cache=3,
        conv_bias=False, rope_theta=1000000, norm_eps=1e-5,
        norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True,
        torch_dtype="float32",
        serving={"state_slots": 24, "state_stride_blocks": 2})
    return {**cfg, **over}


def test_from_published_reads_the_keys_and_refuses_what_is_not_implemented():
    assert lfm2moe.from_published(published(), 16) == CFG
    for key, value in (("conv_bias", True), ("conv_L_cache", 4),
                       ("use_expert_bias", False), ("num_hidden_layers", 6),
                       ("layer_types", ["conv"] * 6 + ["sliding_attention"]),
                       ("hidden_size", 66)):
        with pytest.raises(ValueError):
            lfm2moe.from_published(published(**{key: value}), 16)


def test_router_normalises_with_the_published_epsilon():
    lp = PARAMS["layers"][2]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(16, CFG.d_model)),
                    jnp.float32)
    from llm_d_kv_cache_manager_tpu.models import moe_serve

    picked, w = moe_serve.route(h, lp["router"], lp["route_bias"], 2, True, 1.0,
                                lfm2moe.ROUTE_NORM_EPS)
    scores = np.asarray(jax.nn.sigmoid(h @ lp["router"]))
    chosen = np.take_along_axis(scores, np.asarray(picked), 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / (chosen.sum(1, keepdims=True) + 1e-6),
        rtol=1e-6)
    assert (np.asarray(w).sum(1) < 1).all()


# ------------------------------------------------ a lower precision would show


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_a_float8_pass_fails_the_tolerance_the_decode_comparison_holds():
    """The decode comparison above holds the program to 2e-4 of the largest
    logit.  The same equations with every product's operands rounded through
    float8 (one scale a tensor) are two orders further off, and bfloat16
    operands one: the comparison would catch either."""
    tokens = tokens_of(96, 11)
    want = reference(tuple(tokens))

    def rounded(dtype):
        def q(a):
            scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
            return (a / scale).astype(dtype).astype(jnp.float32) * scale

        params = jax.tree.map(
            lambda a: q(a) if a.ndim > 1 else a, PARAMS)
        return np.asarray(lfm2moe.reference_logits(params, tokens, CFG))

    err8 = _rel(rounded(jnp.float8_e4m3fn), want)
    assert err8 > 100 * 2e-4
    eng = Engine()
    seq = eng.prefill(tokens, 0)
    assert _rel(seq["row"], want[-1]) < 2e-4 < err8


# ------------------------------------- the other families' programs, unmoved

# sha256 of str(jax.make_jaxpr(program)) at the sizes below, read on commit
# f1ec187 (PR 32), before this family existed.  `models/moe_serve.py` now
# holds the router and the expert products `models/afmoe.py` had, and
# `models/pod.py` a third kind of table: the `afmoe` and `llama` programs
# must trace to the text they had.  A PR that changes one of those programs
# on purpose reads its digest anew: `llama.decode.True` was read anew in PR 34
# (the paged kernel finds the shared prefixes from the table, reads each once
# in a shared pass and walks the rest) and in PR 41 (the walk is a sequence a
# grid step and copies its own blocks; the plan lists sequences, not steps)
# and in PR 43 (the plan flags the waves that are runs in the pool and the
# walk brings such a wave by one copy) and in PR 52 (a step of `rows` slots
# is one operand, and the shared pass brings a step that is a run by one copy:
# the plan flags those too and counts `shared_run_blocks`);
# `afmoe.decode.True` was read anew in PR 45 (heads-first slots and their
# window layers' tables are walked: a plan of the runs a group of slots, the
# step counts `attention_read`) and in PR 52 (the walk's copies are
# `_bring`'s, the shared pass's too, and `attention_read` has a fourth count);
# `llama.decode.False` stands, because off the TPU and uninterpreted the step
# keeps the XLA gather and makes no plan, and so do the other five `afmoe.*`
# and the four `llama.miss/hit.*`.
TEXT_AT_PR_32 = {
    "afmoe.miss.False": "e6f04f9f13e0aa70", "afmoe.hit.False": "83fd523e8393f5b3",
    "afmoe.decode.False": "50748779141b4cb4",
    "afmoe.miss.True": "e6f04f9f13e0aa70", "afmoe.hit.True": "83fd523e8393f5b3",
    "afmoe.decode.True": "19c2b8838b605cef",
    "llama.miss.False": "90c7da6d6fb5ae9d", "llama.hit.False": "364331e0e1475faa",
    "llama.decode.False": "53c6a3b76efc093d",
    "llama.miss.True": "90c7da6d6fb5ae9d", "llama.hit.True": "364331e0e1475faa",
    "llama.decode.True": "a9d7f3002d789864",
}


def _texts(interpret: bool) -> dict:
    out = {}
    cfg = afmoe.AfmoeConfig(dtype="float32", vocab_size=128, window_slots=24,
                            window_store_blocks=4)
    params = jax.eval_shape(lambda: afmoe.init_params(jax.random.key(0), cfg))
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    pod = Pod("p", afmoe, cfg, 40)
    for key, fn in inner_programs(afmoe, cfg, shapes, interpret).items():
        a, b = example_args(key, shapes, pod, 16)
        out[f"afmoe.{key}.{interpret}"] = jax.make_jaxpr(fn)(
            params, a, pod.kv.arrays, b)
    lcfg = llama.LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                             n_kv_heads=2, d_ff=128, dtype="float32")
    lp = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), lcfg))
    pool, i32 = jnp.zeros((2, 12, 2, 16, 2, 16)), jnp.int32
    out[f"llama.miss.{interpret}"] = jax.make_jaxpr(
        lambda p, t, kv, bt: llama.prefill_paged(
            p, t, kv, bt, lcfg, interpret=interpret))(
        lp, jnp.zeros((1, 96), i32), pool, jnp.zeros((1, 6), i32))
    out[f"llama.hit.{interpret}"] = jax.make_jaxpr(
        lambda p, t, kv, bt: llama.prefill_continue(
            p, t, kv, bt, 64, lcfg, interpret=interpret))(
        lp, jnp.zeros((1, 32), i32), pool, jnp.zeros((1, 6), i32))
    out[f"llama.decode.{interpret}"] = jax.make_jaxpr(
        lambda p, t, kv, bt, n: llama.decode_step(
            p, t, kv, bt, n, lcfg, interpret=interpret))(
        lp, jnp.zeros((2,), i32), pool, jnp.zeros((2, 6), i32),
        jnp.ones((2,), i32))
    return out


@pytest.mark.parametrize("interpret", (False, True), ids=("xla", "interpret"))
def test_afmoe_and_llama_programs_lower_to_the_text_they_had(interpret):
    got = {name: hashlib.sha256(str(text).encode()).hexdigest()[:16]
           for name, text in _texts(interpret).items()}
    assert got == {k: v for k, v in TEXT_AT_PR_32.items() if k in got}
