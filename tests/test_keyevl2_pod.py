"""The `keyevl2` family on the pod path (models/keyevl2.py: learned sparse
attention, an indexer that picks the best ``K`` cached positions a query, a
selector key cached beside K and V, softmax-routed experts) and the pod's
cache with its one group of the selected kind (models/pod.py), at a small size
on the CPU: three layers, hidden 64, 4 heads over 2 KV heads of 16, an indexer
of 4 heads of 8 that picks 8 positions, 8 experts top-2, block 16.

The comparisons run the program in float32, where it has to repeat the plain
reference (benchmarks/harness/family_keyevl2.py: `I` as a whole causal array,
`lax.top_k`, a dense softmax under the picks' mask) to rounding, logits AND
picked sets; that the serving precision stays near it is the chip check's
business.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmarks.harness import family_keyevl2
from llm_d_kv_cache_manager_tpu.models import keyevl2, kv_cache_pool, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVCachePool,
    KVCachePoolConfig,
    KVGroupSpec,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse

BLOCK, VOCAB, TOPK = 16, 128, 8
PUBLISHED = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=128,
    hidden_act="silu", hidden_size=2048, intermediate_size=6144,
    max_position_embeddings=262144, max_window_layers=48, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=768, norm_topk_prob=True,
    num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
    num_hidden_layers=4, num_key_value_heads=4, num_local_experts=128,
    rms_norm_eps=1e-06,
    rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 2048},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=151936, torch_dtype="bfloat16")
# the reference's view of the small configuration: the published keys
TINY = {**PUBLISHED, "head_dim": 16, "hidden_size": 64,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "num_key_value_heads": 2, "num_local_experts": 8, "vocab_size": VOCAB,
        "sa_config": {**PUBLISHED["sa_config"], "indexer_head_dim": 8,
                      "indexer_num_heads": 4, "topk": TOPK},
        "torch_dtype": "float32"}
CFG = keyevl2.from_published(TINY, BLOCK)
PARAMS = family_keyevl2.make_weights(TINY, 5)
STEPS = {
    "miss": jax.jit(functools.partial(keyevl2.prefill_paged, cfg=CFG)),
    "hit": jax.jit(functools.partial(keyevl2.prefill_continue, cfg=CFG),
                   static_argnames=("prefix_len",)),
    "decode": jax.jit(functools.partial(keyevl2.decode_step, cfg=CFG)),
}


def tokens_of(n: int, *key: int) -> np.ndarray:
    return np.random.default_rng([13, *key]).integers(1, VOCAB, n)


def hashes_of(tokens) -> list[int]:
    """Chained block hashes, as the benchmark's engine makes them."""
    out, parent = [], b"root"
    data, width = np.asarray(tokens, "<i8").tobytes(), 8 * BLOCK
    for i in range(0, len(data) - len(data) % width, width):
        parent = hashlib.sha256(parent + data[i:i + width]).digest()
        out.append(int.from_bytes(parent[-8:], "big"))
    return out


@functools.cache
def reference(tokens: tuple):
    """(logits [T, V], each layer's picked sets, bool [T, T]) of the whole
    sequence by the plain reference."""
    picks: list = []
    logits = family_keyevl2.forward_logits(PARAMS, TINY, np.asarray(tokens),
                                           len(tokens), picks=picks)
    return np.asarray(logits), [np.asarray(p) for p in picks]


class Engine:
    """What the benchmark's engine does around a pod, call for call, with the
    model steps run directly so that a test sees whole rows of logits."""

    def __init__(self, pool_blocks: int = 40) -> None:
        self.pod = Pod("pod-0", keyevl2, CFG, pool_blocks)
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
        table = pod.tables("hit" if hit else "miss",
                           np.asarray(blocks, np.int32)[None],
                           prefix_blocks=first_new)
        ids = jnp.asarray(tokens[first_new * BLOCK:], jnp.int32)[None]
        if hit:
            logits, arrays = STEPS["hit"](PARAMS, ids, pod.kv.arrays, table,
                                          prefix_len=first_new * BLOCK)
        else:
            logits, arrays = STEPS["miss"](PARAMS, ids, pod.kv.arrays, table)
        arrays.pop("load")
        pod.kv.arrays = arrays
        for h, bid in zip(hashes[first_new:], blocks[first_new:]):
            pod.cached[h] = bid
        self.removed += evicted + more
        return dict(hit=hit, blocks=blocks + own_ids, own=own_ids,
                    evicted=evicted + more, hashes=hashes,
                    row=np.asarray(logits[0, 0]), tokens=list(tokens))

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("prefix_blocks", (5, 0))
def test_miss_hit_and_decode_repeat_the_reference_logits_and_picks(
        prefix_blocks):
    """A miss of 96 positions; a hit of 32 on a cached prefix of 80 (over
    `topk`: every query picks 8 of 80 and more) or of 0 blocks... of 16 (under
    it: the first queries see fewer than 8 and take all); then 24 decode steps
    of two sequences side by side through the pool, across a block's end.
    Every layer's picked sets are the reference's, and the logits to float32
    rounding."""
    prefix = 80 if prefix_blocks else 16
    doc, turn = tokens_of(prefix, 1), tokens_of(32, 2)
    first = np.concatenate((doc, tokens_of(96 - prefix, 3)))
    second = np.concatenate((doc, turn))
    pool = keyevl2.new_pool(CFG, 24)
    rng = np.random.default_rng(prefix)
    ids = rng.permutation(24)
    nb = prefix // BLOCK
    t1 = np.asarray(ids[:9], np.int32)  # 6 blocks of the miss, three to grow
    t2 = np.concatenate((t1[:nb], ids[9:9 + 9 - nb])).astype(np.int32)
    taps: list = []
    logits, pools = keyevl2.prefill_paged(
        PARAMS, jnp.asarray(first)[None], pool, jnp.asarray(t1[None, :6]),
        CFG, taps=taps)
    want, picks = reference(tuple(first))
    close(np.asarray(logits[0, 0]), want[-1])
    assert len(taps) == CFG.n_layers
    for got, ref in zip(taps, picks):
        np.testing.assert_array_equal(np.asarray(got[0]), ref)
    taps = []
    n2 = (prefix + 32) // BLOCK
    logits2, pools = keyevl2.prefill_continue(
        PARAMS, jnp.asarray(turn)[None], {"full": pools["full"]},
        jnp.asarray(t2[None, :n2]), prefix, CFG, taps=taps)
    want2, picks2 = reference(tuple(second))
    close(np.asarray(logits2[0, 0]), want2[-1])
    for got, ref in zip(taps, picks2):
        np.testing.assert_array_equal(np.asarray(got[0]), ref[prefix:])
        # a query with fewer than `topk` positions before it takes them all
        assert (np.asarray(got[0]).sum(-1)
                == np.minimum(prefix + 1 + np.arange(32), TOPK)).all()
    seqs = [list(first) + [int(want[-1].argmax())],
            list(second) + [int(want2[-1].argmax())]]
    table = np.stack((t1, t2))
    kv, rows = {"full": pools["full"]}, [[], []]
    tapped = []
    for _ in range(24):
        taps = []
        ctx = np.asarray([len(s) for s in seqs], np.int32)
        out, kv = keyevl2.decode_step(
            PARAMS, jnp.asarray([s[-1] for s in seqs]), kv,
            jnp.asarray(table), jnp.asarray(ctx), CFG, taps=taps)
        load = np.asarray(kv.pop("load"))
        tapped.append(taps)
        for s, r, row in zip(seqs, rows, np.asarray(out)):
            r.append(row)
            s.append(int(row.argmax()))
    assert load.shape == (CFG.n_layers, 2) and (load[:, 0] <= 4).all()
    for b, (s, r) in enumerate(zip(seqs, rows)):
        want, picks = reference(tuple(s))
        close(np.stack(r), want[-25:-1])
        for step, taps in enumerate(tapped):
            t = len(s) - 25 + step  # the position this step's query stands at
            for (at, ok), ref in zip(taps, picks):
                got = np.zeros(len(s), bool)
                got[np.asarray(at[b])[np.asarray(ok[b])]] = True
                np.testing.assert_array_equal(got, ref[t])


def test_a_long_prefill_attends_by_chunks_and_spans(monkeypatch):
    """Past `ATTN_CHUNK_TOKENS` a prefill's attention is one loop of kernel
    calls a span of `ATTN_SPAN_TOKENS` (the offset is data, the table cut at
    the span's end), and logits and picks are what one call gives."""
    tokens = tokens_of(96, 5)
    pool = keyevl2.new_pool(CFG, 8)
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    monkeypatch.setattr(keyevl2, "ATTN_CHUNK_TOKENS", 16)
    monkeypatch.setattr(keyevl2, "ATTN_SPAN_TOKENS", 48)
    taps: list = []
    chunked, pools = keyevl2.prefill_paged(
        PARAMS, jnp.asarray(tokens)[None], pool, table, CFG, taps=taps)
    want, picks = reference(tuple(tokens))
    close(np.asarray(chunked[0, 0]), want[-1])
    for got, ref in zip(taps, picks):
        np.testing.assert_array_equal(np.asarray(got[0]), ref)
    assert (np.asarray(pools["full"][0][0]) == 0).all()  # slot 0 not named


def test_the_three_programs_serve_the_reference_tokens_and_record_the_read():
    """`jit_programs`: every shape compiles at the first call of any; each
    call donates the pools and hands the handle back; the tokens served are
    the reference's; a decode call of this one-group pod says what the step
    reads of the cache: selector keys of every live position, K and V of the
    picked ones, and what reading every position's K and V would be."""
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(keyevl2, CFG, shapes, interpret=True)
    pod = Pod("pod-0", keyevl2, CFG, 40)
    assert pod.groups == [] and pod.protect_asked
    doc = tokens_of(80, 1)
    prompts = [np.concatenate((doc, tokens_of(16, 3))),
               np.concatenate((doc, tokens_of(32, 2)))]
    ids, _ = pod.alloc(6)
    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        before = jax.tree.leaves(pod.kv.arrays)
        out, row, kv = programs["miss"](
            PARAMS, prompts[0][None], pod.kv, np.asarray(ids)[None])
        assert kv is pod.kv and all(a.is_deleted() for a in before)
        assert int(np.asarray(out)[0, 0]) == reference(
            tuple(prompts[0]))[0][-1].argmax()
        more, _ = pod.alloc(2)
        out, row, kv = programs["hit"](
            PARAMS, prompts[1][None, 80:], pod.kv,
            np.asarray(ids[:5] + more)[None])
        want = reference(tuple(prompts[1]))[0][-1]
        assert int(np.asarray(out)[0, 0]) == want.argmax()
        close(np.asarray(row), want)
        table = np.zeros((2, 9), np.int32)
        table[0, :6], table[1, :7] = ids, ids[:5] + more
        nxt = np.asarray([reference(tuple(p))[0][-1].argmax()
                          for p in prompts])
        own, _ = pod.alloc(2)
        table[0, 6], table[1, 7] = own
        for _ in range(2):
            out, kv = programs["decode"](
                PARAMS, nxt, pod.kv, table, np.asarray([97, 113]))
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[0][-1].argmax() for s in seqs]
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    spans = [r for r in rows if r["span"] is not None]
    assert {"kv.read", "moe.expert_load", "pod.compile", "pod.counts_read",
            "pod.pack", "pod.launch.miss", "pod.launch.hit",
            "pod.launch.decode"} == {r["span"] for r in spans}
    read = [r["attrs"] for r in spans if r["span"] == "kv.read"]
    layers, item = CFG.n_layers, 4
    key, kv_bytes = 8 * item * layers, 2 * 2 * 16 * item * layers
    assert len(read) == 2 and read[-1] == {
        "full_blocks": 7 + 8, "index_bytes": (97 + 113) * key,
        "picked_bytes": 2 * TOPK * kv_bytes,
        "sparse_bytes": (97 + 113) * key + 2 * TOPK * kv_bytes,
        "dense_bytes": (97 + 113) * kv_bytes,
        "step_bytes": (97 + 113) * key + 2 * TOPK * kv_bytes
        + CFG.decode_weight_nbytes}
    load = [r["attrs"] for r in spans if r["span"] == "moe.expert_load"]
    assert len(load) == layers and all(
        a["experts_held"] == 8 and 1 <= a["experts_touched"] <= 4
        and a["mean_tokens"] == 0.5 for a in load)


def chat(ahead: bool, patch) -> tuple[list, list]:
    """An engine's loop over `jit_programs` as the benchmark's `run_chat`
    makes it (a step's tokens read before the next call, and fed to it):
    three steps, the second row's sequence ends, a step with that row idle,
    a hit admitted in its place, three more.  Returns what each decode call
    served and the `pod.step` rows.  Without `ahead` the policy says no
    `decode_ahead`: the pod every other family has."""
    if not ahead:
        policy = keyevl2.cache_policy(CFG)
        del policy["decode_ahead"]
        patch.setattr(keyevl2, "cache_policy", lambda cfg: policy)
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(keyevl2, CFG, shapes, interpret=True)
    pod = Pod("pod-0", keyevl2, CFG, 40)
    assert pod.decode_ahead == ahead
    doc = tokens_of(80, 1)
    prompts = [np.concatenate((doc, tokens_of(16, 3))),
               np.concatenate((doc, tokens_of(32, 2))),
               np.concatenate((doc, tokens_of(32, 4)))]
    ids, _ = pod.alloc(6)
    scratch, served = pod.alloc(1)[0][0], []
    table = np.full((2, 9), scratch, np.int32)
    cur, ctx = np.zeros(2, np.int32), np.ones(2, np.int32)

    def admit(slot, prompt, blocks, program, first=0):
        out, _, _ = programs[program](
            PARAMS, prompt[None, first:], pod.kv, np.asarray(blocks)[None])
        own, _ = pod.alloc(1)
        table[slot, :len(blocks) + 1] = blocks + own
        cur[slot], ctx[slot] = np.asarray(out)[0, 0], len(prompt) + 1

    def step():
        out, kv = programs["decode"](PARAMS, cur.copy(), pod.kv, table.copy(),
                                     ctx.copy())
        assert kv is pod.kv
        toks, tops = np.asarray(out)
        served.append((toks.copy(), tops.copy(), ctx > 1))
        live = ctx > 1
        cur[live], ctx[live] = toks[live], ctx[live] + 1

    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        admit(0, prompts[0], ids, "miss")
        admit(1, prompts[1], ids[:5] + pod.alloc(2)[0], "hit", 80)
        for _ in range(3):
            step()
        table[1], ctx[1] = scratch, 1  # the second sequence ends
        step()
        admit(1, prompts[2], ids[:5] + pod.alloc(2)[0], "hit", 80)
        for _ in range(3):
            step()
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    assert not dropped
    return served, rows


def test_a_decode_call_that_goes_on_is_handed_the_step_launched_ahead(
        monkeypatch):
    """`decode_ahead`: what is served is what a pod without it serves, step
    for step; a call launches the step after its own only where it goes on
    from the call before, takes the step launched ahead only where it goes
    on in turn, and a step not taken (the row that ended, the admission)
    changes nothing a live sequence reads."""
    got, rows = chat(True, monkeypatch)
    want, plain = chat(False, monkeypatch)
    assert len(got) == len(want) == 7
    for (toks, tops, live), (wtoks, wtops, wlive) in zip(got, want):
        assert (live == wlive).all() and (toks[live] == wtoks[live]).all()
        close(tops[live], wtops[live], 1e-6)
    # ... and the reference's: the first row's seven tokens after its prompt's
    seq = tuple(np.concatenate((tokens_of(80, 1), tokens_of(16, 3))))
    seq += (int(reference(seq)[0][-1].argmax()),)
    for toks, _, _ in got:
        assert int(toks[0]) == reference(seq)[0][-1].argmax()
        seq += (int(toks[0]),)

    def decode_calls(rows):
        roots = [r for r in rows if r["span"] is None
                 and r["attrs"]["kind"] == "decode"]
        return [[s for s in rows if s["span"] and s["trace_id"] == r["trace_id"]]
                for r in roots]

    calls = decode_calls(rows)
    packs = [[s["attrs"] for s in c if s["span"] == "pod.pack"][0] for c in calls]
    launches = [[s["attrs"].get("ahead", 0) for s in c
                 if s["span"] == "pod.launch.decode"] for c in calls]
    # first step; goes on: its own and one ahead; handed that one, launches
    # the next; a row changed: its own (the one ahead is dropped); after the
    # admission as at first
    assert [a["ahead"] for a in packs] == [0, 0, 1, 0, 0, 0, 1]
    assert launches == [[0], [0, 1], [1], [0], [0], [0, 1], [1]]
    assert all("ahead" not in a for c in decode_calls(plain) for s in c
               for a in [s["attrs"]] if s["span"] in ("pod.pack",
                                                      "pod.launch.decode"))
    assert [len([s for s in c if s["span"] == "pod.launch.decode"])
            for c in decode_calls(plain)] == [1] * 7


def test_bfloat16_serving_stays_near_the_reference():
    """The serving type end to end at the small size: the program in
    bfloat16 against the float32 reference of the same (bfloat16-valued)
    weights.  With `topk` at the prompt's length every position is picked and
    the distance is the products' rounding; with 8 picks of up to 96 a
    position that swaps at the 8th score carries an eighth of a query's
    attention, so the distance is larger and says little (at the published
    sizes a pick is one of 2048: the chip check's business)."""
    tokens = tokens_of(96, 6)
    for topk, limit in ((96, 0.05), (TOPK, 0.5)):
        tiny = {**TINY, "torch_dtype": "bfloat16",
                "sa_config": {**TINY["sa_config"], "topk": topk}}
        cfg = dataclasses.replace(CFG, dtype="bfloat16", index_topk=topk)
        params = family_keyevl2.make_weights(tiny, 6)
        logits, pools = keyevl2.prefill_paged(
            params, jnp.asarray(tokens)[None], keyevl2.new_pool(cfg, 8),
            jnp.arange(1, 7, dtype=jnp.int32)[None], cfg)
        want = np.asarray(
            family_keyevl2.forward_logits(params, tiny, tokens, 1))[0]
        got = np.asarray(logits[0, 0], np.float32)
        assert np.linalg.norm(got - want) / np.linalg.norm(want) < limit
        assert pools["full"][0].dtype == jnp.bfloat16


# ------------------------------------------------------------- the selection


def _planted(rng, rows, n, seen):
    """Scores with few distinct values (ties everywhere, at the threshold
    too), zeros of both signs, and -inf past each row's `seen`."""
    x = rng.integers(-3, 4, (rows, n)).astype(np.float32) / 2
    x[x == 0] *= rng.choice([1.0, -1.0], (x == 0).sum())
    x[np.arange(n)[None] >= np.asarray(seen)[:, None]] = -np.inf
    return x


@pytest.mark.parametrize("k", (8, 40, 150, 300))
def test_the_pick_is_lax_top_k_with_planted_ties_and_short_rows(k):
    """`topk_mask` (bisection over the ordered bits, ties admitted in
    position order) against `lax.top_k` over rows full of equal scores and
    rows that see fewer positions than `k`; and `picked_tiles` names those
    picks, in position order, as tiles of the slots a table gives them."""
    rng = np.random.default_rng(k)
    seen = [200, 150, 9, 1, 77]
    x = _planted(rng, 5, 200, seen)
    best, at = lax.top_k(jnp.asarray(x), min(k, 200))
    want = np.zeros(x.shape, bool)
    for r in range(5):
        want[r, np.asarray(at[r])[np.asarray(best[r]) > -np.inf]] = True
    assert (want.sum(-1) == np.minimum(seen, k)).all()
    picked = sparse.topk_mask(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(picked), want)
    # 13 blocks of 16 hold 200 positions; slot ids past a byte and past two
    table = rng.permutation(70000)[:5 * 13].reshape(5, 13).astype(np.int32)
    tiles, where, ok = sparse.picked_tiles(picked, jnp.asarray(table), k,
                                           BLOCK, BLOCK + 2)
    tiles, where, ok = (np.asarray(a) for a in (tiles, where, ok))
    for r in range(5):
        n = min(seen[r], k)
        assert ok[r].sum() == n and ok[r, :n].all()
        np.testing.assert_array_equal(where[r, :n], np.nonzero(want[r])[0])
        np.testing.assert_array_equal(
            tiles[r, :n], table[r, where[r, :n] // BLOCK] * (BLOCK + 2)
            + where[r, :n] % BLOCK)
        assert (tiles[r, n:] == table[r, 0] * (BLOCK + 2)).all()


def test_the_kth_largest_by_bisection_is_the_sorted_rows():
    rng = np.random.default_rng(3)
    x = np.concatenate((rng.normal(size=(4, 300)) * 10.0 ** rng.integers(
        -30, 30, (4, 300)), _planted(rng, 2, 300, [300, 300])))
    x = jnp.asarray(x, jnp.float32)
    for k in (1, 2, 17, 300):
        thr = sparse.kth_largest(sparse.ordered_key(x), k)
        want = sparse.ordered_key(jnp.sort(x, axis=-1)[:, -k])
        np.testing.assert_array_equal(np.asarray(thr), np.asarray(want))
    assert (np.asarray(sparse.kth_largest(sparse.ordered_key(x), 301))
            == -2**31).all()  # fewer than k: everything is picked


# ------------------------------------------------------ the router's two kinds


def test_route_softmax_is_a_plain_softmax_top_k():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(20, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    picked, w = moe_serve.route(h, router, None, 2, True, 1.0,
                                scores="softmax")
    s = jax.nn.softmax(jnp.dot(h, router, precision=lax.Precision.HIGHEST))
    best, at = lax.top_k(s, 2)
    np.testing.assert_array_equal(np.asarray(picked), np.asarray(at))
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        best / best.sum(-1, keepdims=True)), rtol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 1)
    with pytest.raises(ValueError, match="scores"):
        moe_serve.route(h, router, None, 2, True, 1.0, scores="tanh")
    # the sigmoid kind is what it was: the same jaxpr with and without the
    # argument
    bias = jnp.zeros(8)
    a, b = (str(jax.make_jaxpr(f)(h, router, bias)) for f in (
        lambda h, r, b: moe_serve.route(h, r, b, 2, True, 1.5),
        lambda h, r, b: moe_serve.route(h, r, b, 2, True, 1.5,
                                        scores="sigmoid")))
    assert a == b and "logistic" in a and "exp" not in a.replace("expand", "")


# ------------------------------------------------------------ cache and pod


def test_block_bytes_and_pool_shapes_come_from_the_selected_spec():
    cfg = keyevl2.from_published(PUBLISHED, BLOCK)
    spec = keyevl2.cache_groups(cfg)["full"]
    assert (spec.selector_dim, spec.selected, spec.num_kv_heads,
            spec.selector_tiles) == (64, 2048, 4, 1)
    # K and V 2 x 4 x 128 and the selector's key 64, bfloat16, 16 positions,
    # 4 layers: 139 264 B a block, 8704 B a token
    assert spec.block_nbytes == spec.read_nbytes == 139264 == 16 * 8704
    assert spec.layer_shape(51200) == (51200, 17, 8, 128)
    small = keyevl2.new_pool(CFG, 12)
    assert list(small) == ["full"] and len(small["full"]) == CFG.n_layers
    assert small["full"][0].shape == (12, 16 + 2, 4, 16)
    assert sum(a.nbytes for a in small["full"]) == 12 * keyevl2.cache_groups(
        CFG)["full"].block_nbytes
    # what a decode step reads of the weights: all but the embedding
    # (ISSUE 44: 0.31 B of head + 4 x 625.4 M: 5.63 GB)
    assert round(cfg.decode_weight_nbytes / 1e9, 2) == 5.63
    policy = keyevl2.cache_policy(cfg)
    assert policy["step_weight_nbytes"] == cfg.decode_weight_nbytes
    assert "window" not in policy and "state" not in policy
    with pytest.raises(ValueError, match="selected slot"):
        KVGroupSpec(4, 16, 4, 128, selector_dim=48)  # no whole tiles
    # a latent vector AND a selector key is a kind of its own (PR 53)
    assert KVGroupSpec(4, 16, 1, 128, selector_dim=64, latent_dim=128,
                       value_dim=64).layout == "latent_selected"
    with pytest.raises(ValueError, match="exclude each other"):
        KVGroupSpec(4, 16, 4, 128, selector_dim=64, packed=True)


def test_a_uniform_pool_and_the_offload_spec_price_both_parts(tmp_path):
    """`KVCachePool.block_nbytes`, the stacked array's own bytes and the
    offload manager's file size all follow the spec: K/V and selector key."""
    from llm_d_kv_cache_manager_tpu.offload.spec import (
        TPUOffloadConnector,
        TPUOffloadSpec,
    )

    pool = KVCachePool(KVCachePoolConfig(
        num_layers=4, num_blocks=6, block_size=16, num_kv_heads=4,
        head_dim=128, selector_dim=64))
    assert pool.block_nbytes == 139264
    assert pool.kv.shape == (4, 6, 17, 8, 128) and pool.kv.nbytes == 6 * 139264
    assert pool.gather_block_major([1, 4]).nbytes == 2 * 139264
    connector = TPUOffloadConnector(
        TPUOffloadSpec(shared_storage_path=str(tmp_path), model_name="keye",
                       device_block_size=16, offloaded_block_size=64,
                       threads_per_chip=2), pool)
    try:
        assert connector.get_manager().full_file_nbytes == 4 * 139264
    finally:
        connector.close()


def test_a_decode_write_lands_where_the_scatter_would_put_it():
    """`write_token` patches one position's tile and its selector key's
    lanes; over a whole block it is what `write_blocks` writes."""
    rng = np.random.default_rng(2)
    k, v = (jnp.asarray(rng.normal(size=(1, 2 * BLOCK, 2, 16)), jnp.float32)
            for _ in range(2))
    ki = jnp.asarray(rng.normal(size=(1, 2 * BLOCK, 8)), jnp.float32)
    pool = jnp.zeros((4, BLOCK + 2, 4, 16), jnp.float32)
    spec = keyevl2.cache_groups(CFG)["full"]
    want = kv_cache_pool.write_blocks(spec, pool, jnp.asarray([[3, 1]]),
                                      k, v, ki)
    got = pool
    for pos in range(2 * BLOCK):
        got = kv_cache_pool.write_token(
            spec, got, jnp.asarray([[3, 1][pos // BLOCK]]),
            jnp.asarray([pos % BLOCK]), k[:, pos], v[:, pos], ki[:, pos])
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        kv_cache_pool.unpack_selector_keys(want[jnp.asarray([3, 1]), BLOCK:],
                                           8), ki[0])


@pytest.mark.parametrize("key, value", (
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("attention_bias", True),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4}),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("sa_config", None),
    ("sa_config", {**PUBLISHED["sa_config"], "indexer_num_kv_heads": 2}),
    ("num_local_experts", 64),
))
def test_from_published_refuses_what_the_equations_do_not_cover(key, value):
    cfg = keyevl2.from_published(PUBLISHED, BLOCK)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.index_heads,
            cfg.index_dim, cfg.index_topk, cfg.n_experts, cfg.top_k,
            cfg.n_layers) == (32, 4, 128, 16, 64, 2048, 128, 8, 4)
    assert cfg.rope_theta == 1e7 and cfg.rms_eps == 1e-6
    with pytest.raises(ValueError, match="indexer_num_kv_heads" if value
                       and key == "sa_config" else key.split("_")[0]):
        keyevl2.from_published({**PUBLISHED, key: value}, BLOCK)


def test_the_weights_pytrees_of_reference_and_program_are_one():
    shapes = jax.eval_shape(
        lambda: keyevl2.init_params(jax.random.key(0), CFG))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), PARAMS) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    # the indexer's heads vote against each other, and no norm is constant
    assert all((np.asarray(lp["w_w"]) > 0).any(0).all()
               and (np.asarray(lp["w_w"]) < 0).any(0).all()
               for lp in PARAMS["layers"])
    assert all(np.asarray(lp[k], np.float32).std() > 0.04
               for lp in PARAMS["layers"]
               for k in ("ln_in", "ln_post", "q_norm", "k_norm", "ki_norm",
                         "ki_bias"))


def test_the_pod_with_a_selected_group_caches_evicts_and_publishes_as_any():
    """`cached_prefix`, eviction and what `alloc` hands back for
    `BlockRemoved`, as for a K/V group: a block of 16 tokens is a block, and
    its selector keys share its slot, hash and fate."""
    eng = Engine(pool_blocks=12)
    a = eng.prefill(tokens_of(64, 7), 4)  # 4 blocks, asked (a miss)
    eng.finish(a)
    assert eng.pod.cached_prefix(a["hashes"]) == a["blocks"]
    assert eng.pod.cached_prefix(a["hashes"][:2]) == a["blocks"][:2]
    b = eng.prefill(tokens_of(64, 8), 0)  # never asked for
    eng.finish(b)
    again = eng.prefill(np.concatenate((tokens_of(64, 7), tokens_of(16, 9))), 4)
    assert again["hit"] and again["blocks"][:4] == a["blocks"]
    close(again["row"], reference(tuple(again["tokens"]))[0][-1])
    eng.finish(again)
    assert eng.removed == []
    # 12 blocks hold 4 + 4 + 1: five more push out the never-asked first,
    # least recently used first, and hand their hashes back
    c = eng.prefill(tokens_of(80, 10), 0)
    assert c["evicted"] == b["hashes"][:2] == eng.removed
    assert eng.pod.cached_prefix(b["hashes"]) == []
    assert eng.pod.cached_prefix(a["hashes"]) == a["blocks"]
    assert len(set(c["blocks"]) & set(a["blocks"])) == 0
    with pytest.raises(RuntimeError, match="exhausted"):
        eng.pod.alloc(12)
