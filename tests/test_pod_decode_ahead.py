"""A pod that launches the decode step after its own (`cache_policy`'s
`decode_ahead`, models/pod.py `jit_programs.run_ahead`) beside a window group,
a state group, or both, against the same pod without the key: both are driven
through one schedule of decode calls, finishes and admissions, as the
benchmark's `run_chat` makes them, and have to serve the same.

The families at the small sizes of their own tests: `afmoe` (a window group),
`lfm2moe` (a state group), `phi4flash` (both), `nemotronh` (a state group
whose slot is a matrix a head, advanced where it lies in the pool by
`ssd_decode_step_pallas`), float32, kernels interpreted.
"""

from __future__ import annotations

import hashlib
from collections import Counter

import jax
import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import program_spans
from llm_d_kv_cache_manager_tpu.models import (
    afmoe, lfm2moe, nemotronh, phi4flash,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

BLOCK, VOCAB, ROWS, PREFIX = 16, 128, 3, 4
SHAPES = {"miss": (96,), "hit": (64, 32), "decode": (ROWS,), "max_blocks": 9}
C, A = lfm2moe.CONV, lfm2moe.FULL


def family(name: str, slots: int):
    """(module, configuration) with `slots` in each group beside the full."""
    if name == "window":
        return afmoe, afmoe.AfmoeConfig(
            dtype="float32", vocab_size=VOCAB, window_slots=slots,
            window_store_blocks=4)
    if name == "state":
        return lfm2moe, lfm2moe.Lfm2MoeConfig(
            dtype="float32", vocab_size=VOCAB, layer_types=(C, A, C, C),
            state_slots=slots, state_stride_blocks=2)
    if name == "matrix":  # a state group whose slot is a matrix a head
        return nemotronh, nemotronh.NemotronHConfig(
            dtype="float32", vocab_size=VOCAB, pattern="MEM*E", held=(0, 4),
            state_slots=slots, state_stride_blocks=2)
    return phi4flash, phi4flash.Phi4FlashConfig(
        dtype="float32", vocab_size=VOCAB, window=32, window_slots=slots,
        window_store_blocks=6, state_slots=slots, state_stride_blocks=2)


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


def schedule() -> list[list[tuple]]:
    """Per row, its requests in turn: (document, answer length, calls the row
    stays idle before the request is admitted).  A sequence of `n` tokens
    out ends `n - 2` positions behind a block boundary, and the lengths
    2 .. 17 put an end at every offset; the long ones cross two boundaries;
    lengths of 2 and 3 put events on neighbouring calls; every row goes on
    through the other rows' events."""
    return [
        [(0, 5, 0), (1, 2, 0), (0, 8, 1), (2, 11, 0), (1, 14, 0), (0, 17, 0)],
        [(0, 3, 0), (1, 6, 0), (2, 2, 0), (0, 9, 2), (1, 12, 0), (2, 15, 0)],
        [(1, 40, 0), (2, 4, 0), (0, 7, 0), (1, 10, 1), (2, 13, 0), (0, 16, 0)],
    ]


def drive(name: str, ahead: bool, slots: int, pool_blocks: int, patch) -> dict:
    """The schedule through a pod of the family `name`, with or without
    `decode_ahead`: what each decode call served, what lay in every live
    row's state slot after it, the cached set after it, what the pod gave
    back as evicted, and the spans."""
    module, cfg = family(name, slots)
    policy = {**module.cache_policy(cfg), "decode_ahead": ahead}
    if not ahead:
        del policy["decode_ahead"]  # the pod every chat cell has
    patch.setattr(module, "cache_policy", lambda cfg: policy)
    params = module.init_params(jax.random.key(0), cfg)
    programs = jit_programs(module, cfg, SHAPES, interpret=True)
    pod = Pod("pod-0", module, cfg, pool_blocks)
    assert pod.decode_ahead == ahead
    scratch = pod.alloc(1)[0][0]
    pod.hold([scratch], +1)
    table = np.full((ROWS, SHAPES["max_blocks"]), scratch, np.int32)
    ctx, cur = np.ones(ROWS, np.int32), np.zeros(ROWS, np.int32)
    live: list = [None] * ROWS
    todo = schedule()
    waiting = [[row, *todo[row].pop(0)] for row in range(ROWS)]
    seen = dict(served=[], state=[], cached=[], events=[], offsets=set(),
                idle=0, stored=[], removed=[], untruthful=[])
    indexed: set = set()

    def publish(stored, removed):
        """The index by the events an engine publishes."""
        for h in removed:
            if h not in indexed:
                seen["untruthful"].append(h)
            indexed.discard(h)
        indexed.update(stored)
        seen["stored"] += stored
        seen["removed"] += removed

    def finish(row):
        req = live[row]
        pod.hold(req["blocks"], -1)
        pod.free.extend(req["own"])
        table[row], ctx[row], live[row] = scratch, 1, None
        seen["offsets"].add(int(req["end"] % BLOCK))
        if todo[row]:
            waiting.append([row, *todo[row].pop(0)])

    def admit(row, doc, n_out):
        tokens = np.concatenate((tokens_of(64, doc),
                                 tokens_of(32, doc, row, n_out)))
        hashes = hashes_of(tokens)
        cached = pod.cached_prefix(hashes[:PREFIX])
        first = PREFIX if len(cached) == PREFIX else 0
        pod.touch(hashes[:first])
        pod.hold(cached[:first], +1)
        new, evicted = pod.alloc(len(hashes) - first)
        pod.hold(cached[:first], -1)
        blocks = cached[:first] + new
        pod.hold(blocks, +1)
        own, more = pod.alloc(-(-(n_out - 1) // BLOCK))
        pod.hold(own, +1)
        out, _, kv = programs["hit" if first else "miss"](
            params, tokens[None, first * BLOCK:], pod.kv,
            np.asarray(blocks)[None])
        assert kv is pod.kv
        for h, bid in zip(hashes[first:], blocks[first:]):
            pod.cached[h] = bid
        publish(hashes[first:], evicted + more)
        table[row, :len(blocks) + len(own)] = blocks + own
        cur[row], ctx[row] = np.asarray(out)[0, 0], len(tokens) + 1
        live[row] = dict(blocks=blocks + own, own=own, left=n_out - 1,
                         end=len(tokens) + n_out - 2)

    def state_of(row) -> list:
        """What lies in the slot that holds the row's state."""
        if pod.state is None:
            return []
        slot = pod.state.slot_of[table[row, (ctx[row] - 2) // BLOCK]]
        assert slot >= 0
        # one array a layer, or layer i's slot s at i * slots + s
        return [np.asarray(a)[slot::slots] for a in pod.kv.arrays["state"]]

    def step():
        out, kv = programs["decode"](params, cur.copy(), pod.kv, table.copy(),
                                     ctx.copy())
        assert kv is pod.kv
        toks, tops = np.asarray(out)
        on = np.asarray([r is not None for r in live])
        seen["idle"] += int((~on).sum())
        seen["served"].append((on, toks[on].copy(), tops[on].copy()))
        cur[on], ctx[on] = toks[on], ctx[on] + 1
        seen["state"].append([state_of(row) for row in np.flatnonzero(on)])
        seen["cached"].append(frozenset(pod.cached))
        ended = 0
        for row in np.flatnonzero(on):
            live[row]["left"] -= 1
            if not live[row]["left"]:
                finish(row)
                ended += 1
        seen["events"].append(ended)

    TRACER.configure(sample_rate=1.0, ring_size=4096)
    try:
        calls = 0
        while waiting or any(live):
            ready = [w for w in waiting if not w[3]][:1]
            for row, doc, n_out, _ in ready:  # one admission a call
                waiting.remove(ready[0])
                admit(row, doc, n_out)
            for w in waiting:
                w[3] = max(w[3] - 1, 0)
            step()
            calls += 1
            assert calls < 200
        publish([], pod.alloc(0)[1])  # what a reuse evicted since
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    assert not dropped
    seen.update(indexed=indexed, cached_at_end=set(pod.cached), pod=pod,
                rows=rows, spans=decode_spans(rows))
    return seen


def decode_spans(rows) -> list[list]:
    """The spans of each decode call, (name, attributes), in order."""
    roots = [r for r in rows if r["span"] is None
             and r["attrs"]["kind"] == "decode"]
    return [[(s["span"], s["attrs"]) for s in rows
             if s["span"] and s["trace_id"] == r["trace_id"]] for r in roots]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * max(np.abs(want).max(), 1e-30))


GROUP_SPANS = ("kvpool.window", "kv.read", "kvpool.state", "state.read",
               "attention.read", "moe.expert_load", "pod.pack",
               "pod.counts_read")
# of the reads, what is the step's alone (the slots in use are the pod's)
STEP_READS = ("full_blocks", "window_blocks", "uniform_blocks", "state_bytes",
              "kv_bytes", "full_read_blocks", "read_blocks", "walked_blocks")


def same_service(got: dict, want: dict, tol: float) -> None:
    """Call for call: the same rows live, the same tokens, the same logits,
    the same state behind every live row, the same spans a served step."""
    assert len(got["served"]) == len(want["served"])
    for (on, toks, tops), (won, wtoks, wtops) in zip(got["served"],
                                                      want["served"]):
        assert (on == won).all() and (toks == wtoks).all()
        close(tops, wtops, tol)
    for rows, wrows in zip(got["state"], want["state"]):
        for parts, wparts in zip(rows, wrows):
            assert len(parts) == len(wparts)
            for a, b in zip(parts, wparts):
                close(a, b, tol)
    for call, wcall in zip(got["spans"], want["spans"]):
        assert (Counter(n for n, _ in call if n in GROUP_SPANS)
                == Counter(n for n, _ in wcall if n in GROUP_SPANS))
        reads = [[(n, k, a[k]) for n, a in c for k in STEP_READS if k in a]
                 for c in (call, wcall)]
        assert reads[0] == reads[1]


def launches(seen: dict) -> tuple[list, list]:
    """Per decode call: whether its step had been launched ahead, and the
    `ahead` marks of the steps it launched."""
    packs = [[a.get("ahead") for n, a in c if n == "pod.pack"][0]
             for c in seen["spans"]]
    return packs, [[a.get("ahead", 0) for n, a in c
                    if n == "pod.launch.decode"] for c in seen["spans"]]


@pytest.mark.parametrize("name", ("window", "state", "both", "matrix"))
def test_a_pod_that_launches_ahead_serves_what_one_that_does_not(
        name, monkeypatch):
    """Slots to spare in the groups, a full group that evicts: tokens,
    logits, the state behind every live row, the cached set after every call
    and the hashes given back as evicted are the plain pod's, and a served
    step is counted once."""
    got = drive(name, True, 256, 40, monkeypatch)
    want = drive(name, False, 256, 40, monkeypatch)
    same_service(got, want, 1e-6)
    assert got["cached"] == want["cached"]
    assert got["removed"] == want["removed"] and want["removed"]
    assert got["stored"] == want["stored"]
    assert not got["untruthful"] and got["indexed"] == got["cached_at_end"]
    # the schedule is what it says it is
    assert got["offsets"] == set(range(BLOCK)) and got["idle"] >= 3
    events = np.asarray(got["events"]) > 0
    assert (events[1:] & events[:-1]).any()  # on neighbouring calls
    # ... and the pod did launch ahead: a call that goes on takes the step
    # launched for it and launches the next; a call behind an event launches
    # its own alone, and the step launched for it is dropped
    taken, launched = launches(got)
    assert sum(taken) >= 10 and all(
        marks == ([1] if took else [0, 1] if 1 in marks else [0])
        for took, marks in zip(taken, launched))
    dropped = sum(1 in before and not took
                  for before, took in zip(launched[:-1], taken[1:]))
    assert dropped >= 5
    plain, only = launches(want)
    assert set(plain) == {None} and all(m == [0] for m in only)
    # the engage counter, as benchmarks/metrics/decode_ahead_share.*.json
    # read it: decode calls whose step had been launched ahead, of all
    for cell in ("reasoning", "longctx"):
        read = run.load(run.BENCH, "metrics", f"decode_ahead_share.{cell}")
        assert read["moves"] == "itl_p50_s" and read["better"] == "higher"
        assert program_spans.read(
            read["read"], got["rows"], float("-inf"), float("inf")
        ) == sum(taken) / len(taken)
        assert not program_spans.read(
            read["read"], want["rows"], float("-inf"), float("inf"))


@pytest.mark.parametrize("name", ("window", "state", "both"))
def test_where_the_groups_slots_force_reuse_no_eviction_is_lost(
        name, monkeypatch):
    """Few slots in the groups: a step launched ahead takes its slots a call
    early, so what is reused when may differ from the plain pod's, and what
    a prefill finds cached with it; the service does not (tokens, logits and
    state to rounding: a hit and a miss sum in another order), and the index
    stays truthful: every hash the pod dropped was given back, none twice."""
    slots = {"window": 20, "state": 20, "both": 26}[name]
    got = drive(name, True, slots, 40, monkeypatch)
    want = drive(name, False, slots, 40, monkeypatch)
    same_service(got, want, 2e-4)
    for seen in (got, want):
        assert not seen["untruthful"]
        assert seen["indexed"] == seen["cached_at_end"]
        reused = sum(g.counts["reclaimed"] for g in seen["pod"].groups)
        assert reused > 0  # the groups were short of slots


def test_a_state_groups_sequence_alternates_between_two_slots(monkeypatch):
    """The rule itself, on the host: a step reads the slot the last served
    step wrote and writes the other; tables made for a step that is not
    served change nothing the next tables depend on; the step that enters a
    block writes the spare of the one before, and the first step inside
    takes the old block's slot for its spare unless a hash keeps it."""
    module, cfg = family("state", 24)
    policy = {**module.cache_policy(cfg), "decode_ahead": True}
    monkeypatch.setattr(module, "cache_policy", lambda cfg: policy)
    pod = Pod("pod-0", module, cfg, 40)
    state = pod.state
    ids, _ = pod.alloc(4)
    pod.hold(ids, +1)
    pod.cached[11], pod.cached[12] = ids[0], ids[1]  # the prompt's two blocks
    table = np.asarray([ids], np.int32)
    pod.tables("miss", table[:, :2])

    def made(ctx):
        return state.decode_slots(table, np.asarray([ctx]))["state"][0]

    def served(ctx):
        return pod.tables("decode", table, context_len=np.asarray([ctx]))[
            "state"][0]

    snapshot = state.slot_of[ids[1]]
    first = served(33)  # enters the third block from the hashed second
    assert first[0] == snapshot and first[1] == state.slot_of[ids[2]]
    assert state.slot_of[ids[1]] == snapshot  # a hash keeps it
    a = made(34)
    assert a[0] == first[1] and a[1] not in (first[1], snapshot)
    assert (made(34) == a).all() and (made(34) == a).all()  # not served
    assert (served(34) == a).all()
    b = served(35)
    assert (b == a[::-1]).all()  # the two exchanged
    for ctx in range(36, 49):
        pair = served(ctx)
        assert sorted(pair) == sorted(a) and pair[0] != pair[1]
    last = pair
    live = len(state.block_of) - len(state.free)
    enter = made(49)  # the fourth block takes the spare
    assert enter[0] == last[1] and enter[1] == last[0]
    assert (made(49) == enter).all()
    assert state.slot_of[ids[2]] == last[1]  # the state to go on from
    assert (served(49) == enter).all() and (served(49) == enter).all()
    assert state.slot_of[ids[2]] == enter[0]  # until the sequence moves on
    assert (made(50) == enter[::-1]).all()
    assert state.slot_of[ids[2]] < 0  # no hash: its slot is the spare now
    assert (served(50) == enter[::-1]).all()
    assert len(state.block_of) - len(state.free) == live
    pod.hold(ids, -1)  # the sequence ends: its own blocks' slots go back
    assert len(state.block_of) - len(state.free) == 1  # the snapshot
    assert (state.spare_of < 0).all()


@pytest.mark.parametrize("module,name", ((afmoe, "window"), (lfm2moe, "state")))
def test_decode_ahead_stands_beside_a_window_or_a_state_group(
        module, name, monkeypatch):
    """`Pod.__init__` refused the key beside a group until PR 47."""
    cfg = family(name, 24)[1]
    policy = {**module.cache_policy(cfg), "decode_ahead": True}
    monkeypatch.setattr(module, "cache_policy", lambda cfg: policy)
    pod = Pod("pod-0", module, cfg, 40)
    assert pod.decode_ahead and len(pod.groups) == 1


def test_the_families_that_say_the_key():
    """`phi4flash`, `nemotronh` and `keyevl2` (long generations, long
    contexts: few events a step); the 64-slot chat families do not
    (models/pod.py)."""
    from llm_d_kv_cache_manager_tpu.models import glm4moelite, keyevl2

    says = {m.__name__.rsplit(".", 1)[1]: bool(
        m.cache_policy(c).get("decode_ahead")) for m, c in (
        (phi4flash, phi4flash.Phi4FlashConfig()),
        (nemotronh, nemotronh.NemotronHConfig()),
        (keyevl2, keyevl2.KeyeVl2Config()),
        (afmoe, afmoe.AfmoeConfig()),
        (lfm2moe, lfm2moe.Lfm2MoeConfig()),
        (glm4moelite, glm4moelite.Glm4MoeLiteConfig()))}
    assert says == {"phi4flash": True, "nemotronh": True, "keyevl2": True,
                    "afmoe": False,
                    "lfm2moe": False, "glm4moelite": False}


# ------------------------------------ the pods that do not launch ahead (PR 47)

# Read on commit 5e27e03 (PR 45's tree, the parent of PR 47): everything the
# pods of `trinitymini-chat-longdocs` (a window group) and
# `lfm2moe-chat-agents` (a state group) hand out or keep on the host over the
# scripted run below, at the cells' own sizes, digested.  Without the key a
# pod builds the tables it built before the groups learned to make a step's
# tables a call early.
AT_PR_46 = {"chat-longdocs": "33a249abc4f197fe",
            "chat-agents": "4ed9a62ee93fdac6"}
CELLS = {
    # benchmarks/configs/trinity-mini-l5.json, benchmarks/traffic/chat-longdocs.json
    "chat-longdocs": (afmoe, dict(window=2048, window_slots=16384,
                                  window_store_blocks=160),
                      65536, 64, 12288),
    # benchmarks/configs/lfm2-8b-a1b-l13.json, benchmarks/traffic/chat-agents.json
    "chat-agents": (lfm2moe, dict(
        layer_types=(C, A, C, C, C, A, C, C, C, A, C, C, C), state_slots=2048,
        state_stride_blocks=16), 16384, 8, 8192),
}


def recorded_tables(cell: str, steps: int = 120) -> str:
    """64 clients over the cell's shared prompts with turns of 512 tokens and
    answers of 64 to 512, each starting part of the way into its first
    answer, as `traffic.chat_clients` deals them; a decode call a step, a
    sequence that ends gives its row to the client's next request (a hit
    where the prompt is cached) before the next call, as `run_chat` does."""
    module, sizes, pool_blocks, systems, system_tokens = CELLS[cell]
    cfg = {afmoe: afmoe.AfmoeConfig, lfm2moe: lfm2moe.Lfm2MoeConfig}[module](
        **sizes)

    class Program:  # what a `Pod` asks of a family: no pool is made
        cache_policy = staticmethod(module.cache_policy)
        new_pool = staticmethod(lambda model, blocks: {})

    pod, seen = Pod("p", Program, cfg, pool_blocks), hashlib.sha256()
    assert not pod.decode_ahead

    def note(x):
        for leaf in jax.tree.leaves(x):
            a = np.asarray(leaf)
            seen.update(str(a.dtype).encode() + str(a.shape).encode()
                        + a.tobytes())

    rows, pre, turn = 64, system_tokens // BLOCK, 512 // BLOCK
    rng = np.random.default_rng(47)
    scratch = pod.alloc(1)[0][0]
    pod.hold([scratch], +1)
    table = np.full((rows, pre + turn + 32), scratch, np.int32)
    ctx, left, live = np.ones(rows, np.int32), np.zeros(rows, int), {}
    rounds = [0] * rows

    def admit(row, opening=False):
        r, system = rounds[row], (row + rounds[row]) % systems
        rounds[row] += 1
        n_out = int(rng.integers(64, 513))
        hashes = [10**6 * (system + 1) + i for i in range(pre)] + [
            10**9 * (row + 1) + 10**4 * r + i for i in range(turn)]
        cached = pod.cached_prefix(hashes[:pre])
        first = pre if len(cached) == pre else 0
        pod.touch(hashes[:first])
        pod.hold(cached[:first], +1)
        new, evicted = pod.alloc(len(hashes) - first)
        pod.hold(cached[:first], -1)
        blocks = cached[:first] + new
        pod.hold(blocks, +1)
        own, more = pod.alloc(-(-(n_out - 1) // BLOCK))
        pod.hold(own, +1)
        note((cached, new, evicted, own, more, pod.tables(
            "hit" if first else "miss", np.asarray(blocks, np.int32)[None],
            prefix_blocks=first)))
        for h, bid in zip(hashes[first:], blocks[first:]):
            pod.cached[h] = bid
        table[row, :len(blocks) + len(own)] = blocks + own
        done = (n_out - 1) * (2 * row + 1) // (2 * rows) if opening else 0
        ctx[row], left[row] = len(hashes) * BLOCK + 1 + done, n_out - 1 - done
        live[row] = (blocks + own, own)

    for row in range(rows):
        admit(row, opening=True)
    waiting, hits = [], 0
    for _ in range(steps):
        if waiting:
            hits += 1
            admit(waiting.pop(0))
        note(pod.tables("decode", table.copy(), context_len=ctx.copy()))
        for row in list(live):
            ctx[row] += 1
            left[row] -= 1
            if left[row] <= 0:
                blocks, own = live.pop(row)
                pod.hold(blocks, -1)
                pod.free.extend(own)
                table[row], ctx[row] = scratch, 1
                waiting.append(row)
    for group in pod.groups:
        note((group.slot_of, group.block_of, group.stamp,
              sorted(group.counts.items())))
    note((pod.refs, pod.hashed, pod.asked, sorted(pod.cached.items()),
          pod.unpublished))
    assert hits >= 15  # sequences ended and their rows were taken again
    return seen.hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_without_the_key_the_groups_tables_are_what_they_were(cell):
    assert recorded_tables(cell) == AT_PR_46[cell]
