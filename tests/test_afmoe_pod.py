"""The `afmoe` family on the pod path (models/afmoe.py) and the pod's cache
with two groups of slots (models/pod.py), at a small size on the CPU: five
layers of the published pattern (a leading dense layer, sliding x3 + full with
experts), hidden 64, 8 experts top-2 and a shared one, window 32, block 16.

The comparisons run the program in float32, where it has to repeat the plain
reference to rounding; that the serving precision stays near it is the chip
check's business (benchmarks/harness/family_afmoe.py).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.kvcache.indexer import Indexer, IndexerConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import IndexConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.events import (
    BlockRemoved, BlockStored, EventBatch,
)
from llm_d_kv_cache_manager_tpu.kvevents.pool import Message, Pool, PoolConfig
from llm_d_kv_cache_manager_tpu.models import (
    afmoe, kv_cache_pool, layers, llama,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER
from llm_d_kv_cache_manager_tpu.ops import flash_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)
from llm_d_kv_cache_manager_tpu.tokenization.pool import TokenizationPoolConfig
from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import Encoding

BLOCK, VOCAB = 16, 128
CFG = afmoe.AfmoeConfig(
    dtype="float32", vocab_size=VOCAB, window_slots=24, window_store_blocks=4)
PARAMS = afmoe.init_params(jax.random.key(0), CFG)
STEPS = {
    "miss": jax.jit(functools.partial(afmoe.prefill_paged, cfg=CFG)),
    "hit": jax.jit(functools.partial(afmoe.prefill_continue, cfg=CFG),
                   static_argnames=("prefix_len",)),
    "decode": jax.jit(functools.partial(afmoe.decode_step, cfg=CFG)),
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


_reference = jax.jit(functools.partial(afmoe.reference_logits, cfg=CFG))


@functools.cache
def reference(tokens: tuple) -> np.ndarray:
    """Logits [T, V] of the whole sequence (causal: row t is what a step that
    was fed token t has to give)."""
    return np.asarray(_reference(PARAMS, jnp.asarray(tokens)))


class Engine:
    """What the benchmark's engine does around a pod, call for call
    (`Fleet.account`, `run_chat.admit`, `commit`, `finish`), with the model
    steps run directly so that a test sees whole rows of logits."""

    def __init__(self, pool_blocks: int = 40, cfg=CFG, steps=STEPS,
                 params=PARAMS) -> None:
        self.cfg, self.steps, self.params = cfg, steps, params
        self.pod = Pod("pod-0", afmoe, cfg, pool_blocks)
        self.removed: list[int] = []  # every hash an alloc gave back

    def prefill(self, tokens, n_prefix: int, own: int = 0, nan=False) -> dict:
        """One request: (hit, blocks, last row of logits, ...)."""
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
        own_ids, more = self.own(own, nan)
        if nan:
            self.fresh(new_ids)
        table = np.asarray(blocks, np.int32)[None]
        tables = pod.tables("hit" if hit else "miss", table,
                            prefix_blocks=first_new)
        if nan:
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
        return dict(hit=hit, blocks=blocks + own_ids, own=own_ids,
                    evicted=evicted + more, hashes=hashes,
                    row=np.asarray(logits[0, 0]), tokens=list(tokens))

    def decode(self, seqs: list[dict], nan=False) -> np.ndarray:
        """One step for the sequences given (each dict of `prefill`, its
        `tokens` grown by the token to feed); returns logits [B, V]."""
        width = max(len(s["blocks"]) for s in seqs)
        table = np.zeros((len(seqs), width), np.int32)
        for i, s in enumerate(seqs):
            table[i, :len(s["blocks"])] = s["blocks"]
        ctx = np.asarray([len(s["tokens"]) for s in seqs], np.int32)
        cur = np.asarray([s["tokens"][-1] for s in seqs], np.int32)
        tables = self.pod.tables("decode", table, context_len=ctx)
        if nan:
            self.poison()
        logits, arrays = self.steps["decode"](
            self.params, cur, self.pod.kv.arrays, tables, ctx)
        self.load = np.asarray(arrays.pop("load"))
        self.pod.kv.arrays = arrays
        return np.asarray(logits)

    def own(self, n: int, nan=False):
        ids, evicted = self.pod.alloc(n)
        self.pod.hold(ids, +1)
        if nan:
            self.fresh(ids)
        return ids, evicted

    def fresh(self, ids) -> None:
        """A slot that is taken again holds old K/V, not NaN: positions of a
        live block that lie past the context are masked, not unread."""
        slots = self.pod.window.slot_of[np.asarray(ids, np.int64)]
        slots = jnp.asarray(slots[slots >= 0])
        self.pod.kv.arrays["window"] = [a.at[slots].set(0.0)
                                        for a in self.pod.kv.arrays["window"]]

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])

    def poison(self) -> None:
        """NaN into every window slot that is free or released: a step that
        read anything outside a live window would show it."""
        group, pod = self.pod.window, self.pod
        block = np.maximum(group.block_of, 0)
        released = (group.block_of < 0) | (pod.refs[block] == 0) | (
            ~pod.hashed[block] & (group.stamp < group.live_tick))
        slots = jnp.asarray(np.flatnonzero(released))
        pod.kv.arrays["window"] = [a.at[slots].set(jnp.nan)
                                   for a in pod.kv.arrays["window"]]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("nan", (False, True),
                         ids=("plain", "released-slots-hold-nan"))
def test_prefill_continue_and_decode_repeat_the_reference(nan):
    """A miss, then a hit on its first five blocks with a new suffix, then
    both sequences decoded side by side past a window's length; every row of
    logits against the reference's full forward pass.  With `nan`, every
    window slot outside a live window is overwritten before each step."""
    eng = Engine()
    doc, turn = tokens_of(80, 1), tokens_of(32, 2)
    first = eng.prefill(np.concatenate((doc, tokens_of(16, 3))), 5, own=3,
                        nan=nan)
    assert not first["hit"]
    close(first["row"], reference(tuple(first["tokens"]))[-1])
    second = eng.prefill(np.concatenate((doc, turn)), 5, own=3, nan=nan)
    assert second["hit"] and second["blocks"][:5] == first["blocks"][:5]
    close(second["row"], reference(tuple(second["tokens"]))[-1])
    seqs = [first, second]
    for s in seqs:
        s["tokens"].append(int(np.argmax(s["row"])))
        s["rows"] = []
    for _ in range(40):
        logits = eng.decode(seqs, nan=nan)
        assert np.isfinite(logits).all()
        for s, row in zip(seqs, logits):
            s["rows"].append(row)
            s["tokens"].append(int(np.argmax(row)))
    for s in seqs:
        close(np.stack(s["rows"]), reference(tuple(s["tokens"]))[-41:-1])
    assert eng.pod.window.counts["half_hits"] == 0


def test_a_sequence_decoded_past_two_windows_gives_its_old_slots_back():
    """80 steps after a 32-token prompt: the window (32) passes three times.
    Six window slots for seven blocks: the slots of the sequence's own blocks
    that fell out of its window are reused for the blocks ahead."""
    cfg = dataclasses.replace(CFG, window_slots=6)
    eng = Engine(pool_blocks=16, cfg=cfg, steps={
        "miss": jax.jit(functools.partial(afmoe.prefill_paged, cfg=cfg)),
        "decode": jax.jit(functools.partial(afmoe.decode_step, cfg=cfg))})
    seq = eng.prefill(tokens_of(32, 4), 0)
    seq["tokens"].append(int(np.argmax(seq["row"])))
    rows = []
    for _ in range(80):
        if len(seq["tokens"]) > len(seq["blocks"]) * BLOCK:
            own, evicted = eng.own(1, nan=True)
            assert evicted == []  # a sequence's own blocks carry no hash
            seq["blocks"] += own
            seq["own"] += own
        rows.append(eng.decode([seq], nan=True)[0])
        seq["tokens"].append(int(np.argmax(rows[-1])))
    close(np.stack(rows), reference(tuple(seq["tokens"]))[-81:-1])
    group = eng.pod.window
    assert len(seq["blocks"]) == 7 and group.counts["reclaimed"] > 0
    assert group.slot_of[seq["own"][0]] < 0 <= group.slot_of[seq["own"][-1]]


def test_the_three_programs_serve_the_reference_tokens_and_keep_their_names():
    """`jit_programs`: every shape compiles at the first call of any; each
    call donates the pools and hands the handle back; the tokens served are
    the reference's; spans carry what the window group did."""
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(afmoe, CFG, shapes, interpret=False)
    pod = Pod("pod-0", afmoe, CFG, 40)
    doc = tokens_of(80, 1)
    prompts = [np.concatenate((doc, tokens_of(16, 3))),
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
        more, _ = pod.alloc(2)
        out, row, kv = programs["hit"](
            PARAMS, prompts[1][None, 80:], pod.kv,
            np.asarray(ids[:5] + more)[None])
        want = reference(tuple(prompts[1]))[-1]
        assert int(np.asarray(out)[0, 0]) == want.argmax()
        close(np.asarray(row), want)
        table = np.zeros((2, 9), np.int32)
        table[0, :6], table[1, :7] = ids, ids[:5] + more
        nxt = np.asarray([reference(tuple(p))[-1].argmax() for p in prompts])
        own, _ = pod.alloc(2)
        table[0, 6], table[1, 7] = own
        for _ in range(2):
            out, kv = programs["decode"](
                PARAMS, nxt, pod.kv, table, np.asarray([97, 113]))
        assert len(compiles) == compiled  # nothing compiled after the first call
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[-1].argmax() for s in seqs]
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    spans = [r for r in rows if r["span"] is not None]
    names = {r["span"] for r in spans}
    assert {"kvpool.window", "kv.read", "moe.expert_load", "pod.compile",
            "pod.counts_read", "pod.pack", "pod.launch.miss", "pod.launch.hit",
            "pod.launch.decode"} == names
    assert {r["trace"] for r in rows if r["span"] is None} == {"pod.step"}
    window = [r["attrs"] for r in spans if r["span"] == "kvpool.window"]
    assert sum(a["taken"] for a in window) == 4 + 2 + 2 and all(
        a["half_hits"] == 0 for a in window)
    read = [r["attrs"] for r in spans if r["span"] == "kv.read"][-1]
    assert read == {"full_blocks": 7 + 8, "window_blocks": 3 + 3,
                    "uniform_blocks": 7 + 8}
    load = [r["attrs"] for r in spans if r["span"] == "moe.expert_load"]
    assert len(load) == 4 and all(
        a["experts_held"] == 8 and 1 <= a["experts_touched"] <= 4
        and a["mean_tokens"] == 0.5 and 1 <= a["max_tokens"] <= 2
        for a in load)


# --------------------------------------------------------- the expert layer


def test_router_weights_sum_to_route_scale_and_bias_enters_selection_only():
    lp = PARAMS["layers"][2]
    h = jnp.asarray(np.random.default_rng(3).normal(size=(64, CFG.d_model)),
                    jnp.float32)
    picked, w = afmoe.route(h, lp, CFG)
    np.testing.assert_allclose(np.asarray(w).sum(1), CFG.route_scale, rtol=1e-5)
    scores = jax.nn.sigmoid(h @ lp["router"])
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(picked), 1)
    np.testing.assert_allclose(
        np.asarray(w), chosen / chosen.sum(1, keepdims=True) * CFG.route_scale,
        rtol=1e-5)
    skew = dict(lp, route_bias=jnp.zeros(8).at[5].set(10.0))
    assert (np.asarray(afmoe.route(h, skew, CFG)[0]) == 5).any(axis=1).all()
    assert not (np.asarray(picked) == 5).any(axis=1).all()


def test_no_token_is_dropped_under_a_routing_skewed_onto_one_expert():
    """Every token picks expert 5 first: 64 picks on one expert of eight,
    eight times an even share.  Each token still gets both its experts."""
    lp = dict(PARAMS["layers"][3])
    lp["route_bias"] = jnp.zeros(8).at[5].set(10.0)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(64, CFG.d_model)),
                    jnp.float32)
    picked, w = afmoe.route(h, lp, CFG)
    out, sizes = afmoe.routed_experts(h, picked, w, lp["experts"], CFG)
    assert int(sizes[5]) == 64 and int(sizes.sum()) == 128

    def expert(e, x):
        ep = jax.tree.map(lambda a: a[e], lp["experts"])
        return (jax.nn.silu(x @ ep["w_gate"]) * (x @ ep["w_up"])) @ ep["w_down"]

    want = sum(np.asarray(w)[:, j:j + 1] * np.stack(
        [np.asarray(expert(int(e), h[t])) for t, e in
         enumerate(np.asarray(picked)[:, j])]) for j in range(2))
    close(np.asarray(out), want, 1e-5)


def test_few_tokens_through_every_expert_is_the_sorted_product():
    """A decode step's tokens (no more than experts) go through every expert
    under the routing's mask; a prefill's are sorted by expert.  One sum."""
    lp = PARAMS["layers"][2]
    h = jnp.asarray(np.random.default_rng(12).normal(size=(16, CFG.d_model)),
                    jnp.float32)
    picked, w = afmoe.route(h, lp, CFG)
    few = [afmoe.routed_experts(h[i:i + 8], picked[i:i + 8], w[i:i + 8],
                                lp["experts"], CFG) for i in (0, 8)]
    many, sizes = afmoe.routed_experts(h, picked, w, lp["experts"], CFG)
    close(np.concatenate([np.asarray(o) for o, _ in few]), np.asarray(many),
          1e-5)
    assert np.array_equal(np.asarray(few[0][1] + few[1][1]), np.asarray(sizes))


def test_expert_layer_in_chunks_is_the_layer_whole(monkeypatch):
    lp = PARAMS["layers"][1]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 96, CFG.d_model)),
                    jnp.float32)
    whole, sizes = afmoe._moe(h, lp, CFG, False)
    monkeypatch.setattr(afmoe, "MOE_CHUNK_TOKENS", 32)
    parts, sizes3 = afmoe._moe(h, lp, CFG, False)
    close(np.asarray(parts), np.asarray(whole), 1e-6)
    assert np.array_equal(np.asarray(sizes), np.asarray(sizes3))


# ------------------------------------------------------------- the kernels


@pytest.mark.parametrize("q_offset", (0, 64))
@pytest.mark.parametrize("window", (None, 24, 40))
def test_flash_kernel_with_a_window_is_the_masked_product(window, q_offset):
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 64 + q_offset, 2, 16)), jnp.float32)
            for _ in range(2))
    got = flash_pallas.flash_gqa_attention_pallas(
        q, k, v, q_offset=q_offset, q_block=16, kv_chunk=16, window=window,
        interpret=True)
    close(np.asarray(got),
          np.asarray(layers.dense_attention(q, k, v, q_offset, window)), 1e-5)


def test_flash_kernel_without_a_window_lowers_as_it_did():
    """The window is a static argument: with none, no operation of the
    kernel's body changes (the llama programs compile to what they were)."""
    q = jnp.zeros((1, 32, 4, 16)), jnp.zeros((1, 32, 2, 16))

    def body(**kw):
        return str(jax.make_jaxpr(functools.partial(
            flash_pallas.flash_gqa_attention_pallas, q_block=16, kv_chunk=16,
            interpret=True, **kw))(q[0], q[1], q[1]))

    assert body() == body(window=None) != body(window=8)


@pytest.mark.parametrize("heads_first", (False, True))
@pytest.mark.parametrize("kernel", ("gather", "pallas"))
def test_paged_decode_attention_reads_from_the_window_start(kernel,
                                                            heads_first):
    rng = np.random.default_rng(8)
    pool = jnp.asarray(rng.normal(size=(12, 2, 16, 2, 16)), jnp.float32)
    stored = pool.transpose(0, 1, 3, 2, 4) if heads_first else pool
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    table = jnp.asarray(rng.permutation(12)[:9].reshape(3, 3), jnp.int32)
    ctx, start = jnp.asarray([40, 17, 33]), jnp.asarray([9, 0, 15])
    if kernel == "gather":
        got = paged_attention(q, stored, table, ctx, start=start,
                              heads_first=heads_first)
    else:
        # heads-first slots are walked, two blocks a wave; the others go
        # through the grid of tables by steps, two blocks a step
        got = paged_decode_attention_pallas(
            q, stored, table, ctx, start=start, heads_first=heads_first,
            blocks_per_step=2, walk_blocks_per_wave=2, interpret=True)
    for b in range(3):
        kv = pool[table[b]]  # [3, 2, 16, Hkv, D]
        k = kv[:, 0].reshape(48, 2, 16)[int(start[b]):int(ctx[b])]
        v = kv[:, 1].reshape(48, 2, 16)[int(start[b]):int(ctx[b])]
        s = jnp.einsum("hgd,thd->hgt", q[b].reshape(2, 2, 16), k) / 4.0
        want = jnp.einsum("hgt,thd->hgd", jax.nn.softmax(s, -1), v)
        close(np.asarray(got[b]), np.asarray(want.reshape(4, 16)), 1e-5)


def test_pallas_decode_in_the_step_agrees_with_the_gather():
    eng = Engine()
    seq = eng.prefill(tokens_of(96, 9), 0, own=1)
    seq["tokens"].append(int(np.argmax(seq["row"])))
    tables = eng.pod.tables("decode", np.asarray(seq["blocks"], np.int32)[None],
                            context_len=np.asarray([97]))
    args = (PARAMS, jnp.asarray(seq["tokens"][-1:]), eng.pod.kv.arrays, tables,
            jnp.asarray([97]))
    one, _ = afmoe.decode_step(*args, CFG)
    two, _ = afmoe.decode_step(*args, CFG, interpret=True)
    close(np.asarray(two), np.asarray(one), 1e-5)
    close(np.asarray(one[0]), reference(tuple(seq["tokens"]))[-1])


def test_bfloat16_serving_stays_near_the_reference():
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 or a.size > 8 else a,
        PARAMS)
    params = jax.tree.map(lambda a: a, params)
    for lp in params["layers"]:
        if "route_bias" in lp:
            lp["route_bias"] = lp["route_bias"].astype(jnp.float32)
    pod = Pod("p", afmoe, cfg, 8)
    toks = tokens_of(96, 10)
    ids, _ = pod.alloc(6)
    logits, _ = afmoe.prefill_paged(
        params, jnp.asarray(toks)[None], pod.kv.arrays,
        pod.tables("miss", np.asarray(ids)[None]), cfg)
    want = np.asarray(afmoe.reference_logits(params, toks, cfg))[-1]
    err = np.linalg.norm(np.asarray(logits[0, 0]) - want) / np.linalg.norm(want)
    assert logits.dtype == jnp.float32 and err < 0.08


# ----------------------------------------------------- the cache's geometry


def test_block_bytes_come_from_one_spec_per_layer_kind():
    """Trinity-Mini's kinds at the published widths: a full-group slot is
    32 KB (one layer of five kept), a window-group slot 128 KB (four)."""
    cfg = dataclasses.replace(CFG, n_kv_heads=4, head_dim=128, dtype="bfloat16")
    groups = afmoe.cache_groups(cfg)
    assert groups["full"].block_nbytes == 32 * 1024
    assert groups["window"].block_nbytes == 128 * 1024
    assert groups["window"].window_blocks == 2  # ceil(31 / 16)
    assert dataclasses.replace(groups["window"],
                               window=2048).window_blocks == 128
    with pytest.raises(ValueError):
        groups["full"].window_blocks
    pools = afmoe.new_pool(CFG, 10)
    assert [a.shape for a in pools["full"]] == [(10, 2, 2, 16, 16)]  # heads first
    assert [a.shape for a in pools["window"]] == [(24, 2, 2, 16, 16)] * 4


def test_the_uniform_pool_reads_its_bytes_and_shape_from_the_same_spec():
    config = kv_cache_pool.KVCachePoolConfig(3, 5, 16, 2, 8, "bfloat16")
    pool = kv_cache_pool.KVCachePool(config)
    assert pool.block_nbytes == config.spec.block_nbytes == 3 * 2 * 16 * 2 * 8 * 2
    assert pool.kv.shape == (3,) + config.spec.layer_shape(5)
    k = jnp.arange(2 * 32 * 2 * 8, dtype=jnp.float32).reshape(2, 32, 2, 8)
    layer = kv_cache_pool.scatter_kv_blocks(
        jnp.zeros(config.spec.layer_shape(5)), k, -k,
        jnp.asarray([[4, 1], [0, 3]]), 16)
    assert np.array_equal(np.asarray(layer[1, 0], np.float32), np.asarray(k[0, 16:]))
    assert np.array_equal(np.asarray(layer[0, 1], np.float32), np.asarray(-k[1, :16]))
    heads = kv_cache_pool.scatter_kv_blocks(
        jnp.zeros(dataclasses.replace(config.spec, heads_first=True)
                  .layer_shape(5)), k, -k, jnp.asarray([[4, 1], [0, 3]]), 16,
        heads_first=True)
    assert np.array_equal(np.asarray(heads.transpose(0, 1, 3, 2, 4)),
                          np.asarray(layer))
    assert llama.scatter_kv_blocks is kv_cache_pool.scatter_kv_blocks


def published(**over) -> dict:
    cfg = dict(
        vocab_size=VOCAB, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, num_dense_layers=1, num_hidden_layers=5,
        layer_types=list(CFG.layer_types), sliding_window=32, rope_theta=10000,
        rms_norm_eps=1e-5, route_norm=True, route_scale=2.826,
        score_func="sigmoid", mup_enabled=True, n_group=1, topk_group=1,
        hidden_act="silu", tie_word_embeddings=False, rope_scaling=None,
        torch_dtype="float32",
        serving={"window_slots": 24, "window_store_blocks": 4})
    return {**cfg, **over}


def test_from_published_reads_the_keys_and_refuses_what_is_not_implemented():
    assert afmoe.from_published(published(), 16) == CFG
    for key, value in (("score_func", "softmax"), ("n_group", 2),
                       ("tie_word_embeddings", True), ("num_hidden_layers", 4)):
        with pytest.raises(ValueError):
            afmoe.from_published(published(**{key: value}), 16)
    assert afmoe.cache_policy(dataclasses.replace(
        CFG, layer_types=(afmoe.FULL,) * 2))["window"] is None


# ------------------------------------------------- the cache's two groups


def store(pod, hashes, n_prefix=0):
    """What the engine does for a request that misses: ask, alloc, commit."""
    pod.cached_prefix(hashes[:n_prefix]) if n_prefix else None
    ids, evicted = pod.alloc(len(hashes))
    pod.tables("miss", np.asarray(ids)[None])
    pod.cached.update(zip(hashes, ids))
    return ids, evicted


@pytest.mark.parametrize("n", (1, 2, 3, 5, 8))
def test_hit_rule_wants_the_last_two_window_slots_or_all_if_fewer(n):
    """Window 32, block 16: a query at a block boundary reads 31 positions
    back, two blocks.  An eight-block miss stores window K/V for its last
    four, so a prefix of it is a hit from six blocks on; a short prompt, whose
    blocks all hold a slot, at every length."""
    pod = Pod("p", afmoe, CFG, 40)
    long, short = list(range(100, 108)), list(range(200, 203))
    ids, _ = store(pod, long)
    ids3, _ = store(pod, short)
    assert pod.cached_prefix(long[:n]) == (ids[:n] if n >= 6 else [])
    assert pod.window.counts["half_hits"] == (n < 6)
    if n <= 3:
        assert pod.cached_prefix(short[:n]) == ids3[:n]


@pytest.mark.parametrize("missing, servable", ((7, 7), (6, 6), (5, 8)))
def test_one_trailing_window_slot_missing_refuses_the_prefixes_that_need_it(
        missing, servable):
    pod = Pod("p", afmoe, CFG, 40)
    long = list(range(100, 108))
    ids, _ = store(pod, long)
    pod.window.drop(ids[missing])
    assert pod.cached_prefix(long) == ids[:servable]
    assert pod.window.counts["half_hits"] == (servable < 8)
    assert pod.cached_prefix(long[:6]) == (ids[:6] if missing > 5 else [])


def test_released_slots_go_coldest_first_and_never_asked_before_asked():
    """Twelve window slots.  A document of four blocks is asked for (a miss
    that names its hashes), then suffixes nobody asks for stream through: the
    window group reuses the suffixes' slots, oldest first, and the document's
    prefix stays a hit although it is the coldest of all."""
    cfg = dataclasses.replace(CFG, window_slots=12)
    pod = Pod("p", afmoe, cfg, 64)
    doc = [1, 2, 3, 4]
    ids, _ = store(pod, doc + [50, 51], n_prefix=4)
    assert pod.asked[ids].tolist() == [True] * 4 + [False] * 2
    gone = []
    for turn in range(6):
        hit = pod.cached_prefix(doc)
        assert hit == ids[:4]
        pod.touch(doc)
        new, evicted = pod.alloc(2)
        pod.tables("hit", np.asarray(hit + new)[None], prefix_blocks=4)
        pod.cached.update(zip((60 + 2 * turn, 61 + 2 * turn), new))
        gone += evicted
    assert gone == [50, 51, 60, 61]  # oldest suffix first, whole tails
    assert pod.window.counts["half_hits"] == 0 and all(h in pod.cached for h in doc)
    # asked ones go only when nothing else is left, and take their tail along
    gone = []
    for _ in range(3):
        live, evicted = pod.alloc(4)
        pod.hold(live, +1)
        gone.append(sorted(evicted))
    assert gone == [[62, 63, 64, 65], [66, 67, 68, 69], [3, 4, 70, 71]]
    assert pod.cached_prefix(doc) == [] and pod.cached_prefix(doc[:2]) == []


def test_full_group_reuses_never_asked_blocks_first_only_when_asked_to(
        monkeypatch):
    for protect, want in ((True, [50, 51]), (False, [1, 2])):
        policy = {**afmoe.cache_policy(CFG), "protect_asked": protect}
        monkeypatch.setattr(afmoe, "cache_policy", lambda cfg: policy)
        pod = Pod("p", afmoe, CFG, 6)
        store(pod, [1, 2, 3, 4, 50, 51], n_prefix=4)
        assert pod.alloc(2)[1] == want


def test_a_live_window_is_never_reused_and_exhaustion_is_an_error():
    cfg = dataclasses.replace(CFG, window_slots=4)
    pod = Pod("p", afmoe, cfg, 16)
    ids, _ = store(pod, [1, 2, 3, 4])
    pod.hold(ids, +1)
    with pytest.raises(RuntimeError, match="window group exhausted"):
        pod.alloc(1)
    pod.hold(ids, -1)
    _, evicted = pod.alloc(1)
    assert evicted == [1, 2, 3, 4]  # the block and the tail of its chain


class WordTokenizer:
    def type(self) -> str:
        return "test-word"

    def encode(self, prompt, model_name, add_special_tokens):
        words = prompt.split(" ")
        return Encoding(tokens=[int(w) for w in words],
                        offsets=[(0, 0)] * len(words))


def test_coupled_eviction_publishes_what_the_index_needs():
    """Through the real `kvevents.Pool` and `Indexer`.  A ten-block prompt is
    stored (window slots for its last four blocks), then unrelated blocks take
    the window group's slots.  The window group must reuse the slot of block
    6; the pod evicts blocks 6..9 from the full group too and the hashes ride
    in `alloc`'s list.  Published as `BlockRemoved`, they bring the prompt's
    score down to six blocks: what the index says is held is what can still
    be served."""
    cfg = dataclasses.replace(CFG, window_slots=8)
    pod = Pod("pod-0", afmoe, cfg, 64)
    indexer = Indexer(
        IndexerConfig(
            token_processor_config=TokenProcessorConfig(block_size=BLOCK),
            kvblock_index_config=IndexConfig(),
            tokenizers_pool_config=TokenizationPoolConfig()),
        tokenizer=WordTokenizer())
    indexer.run()
    events = Pool(indexer.kv_block_index, indexer.token_processor,
                  PoolConfig(concurrency=1))
    events.start()

    def publish(*batch):
        events.add_task(Message(
            topic="kv@pod-0@m", pod_identifier="pod-0", model_name="m",
            payload=EventBatch(ts=time.time(), events=list(batch)).encode()))
        events.drain()

    def score(tokens) -> float:
        text = " ".join(str(t) for t in tokens)
        return indexer.get_pod_scores(text, "m", ["pod-0"]).get("pod-0", 0)

    try:
        prompt = tokens_of(160, 11)
        hashes = hashes_of(prompt)
        ids, _ = store(pod, hashes)
        publish(BlockStored(block_hashes=hashes, parent_block_hash=None,
                            token_ids=prompt.tolist(), block_size=BLOCK,
                            medium="hbm"))
        full = score(prompt)
        assert full > 0 and len(pod.cached_prefix(hashes)) == 10
        assert store(pod, list(range(900, 904)))[1] == []  # the free slots
        other, evicted = store(pod, [904])  # none free: the coldest is reused
        assert evicted == hashes[6:]
        assert pod.window.counts["reclaimed"] == 4
        publish(BlockRemoved(block_hashes=evicted, medium="hbm"))
        assert score(prompt) == pytest.approx(full * 6 / 10)
        assert len(pod.cached_prefix(hashes)) == 0  # six blocks, no window slot
        assert pod.cached_prefix(hashes[:6]) == [] and pod.window.counts["half_hits"] == 1
        assert all(h in pod.cached for h in hashes[:6])
        assert sorted(pod.free[-4:]) == sorted(ids[6:])
    finally:
        events.shutdown()
        indexer.shutdown()


# ------------------------------------- one group: the benchmark's plain pod


class PlainPod:
    """`benchmarks/harness/pod.py`'s rule, written out: the yardstick a
    one-group pod of the package has to repeat on any stream."""

    def __init__(self, blocks: int) -> None:
        from collections import OrderedDict, defaultdict

        self.free = list(range(blocks - 1, -1, -1))
        self.cached, self.refs = OrderedDict(), defaultdict(int)

    def cached_prefix(self, hashes):
        ids = []
        for h in hashes:
            if h not in self.cached:
                break
            ids.append(self.cached[h])
        return ids

    def touch(self, hashes):
        for h in hashes:
            self.cached.move_to_end(h)

    def alloc(self, n):
        ids, evicted = [], []
        while len(ids) < n and self.free:
            ids.append(self.free.pop())
        if len(ids) < n:
            for h, bid in list(self.cached.items()):
                if self.refs[bid]:
                    continue
                del self.cached[h]
                evicted.append(h)
                ids.append(bid)
                if len(ids) == n:
                    break
        if len(ids) < n:
            raise RuntimeError("exhausted by live sequences")
        return ids, evicted

    def hold(self, ids, by):
        for bid in ids:
            self.refs[bid] += by


class OneGroup:
    """A family whose cache has one kind of state, as `llama`'s."""

    @staticmethod
    def new_pool(model, blocks):
        return jnp.zeros((blocks, 2))


@pytest.mark.parametrize("seed", range(4))
def test_one_group_pod_is_the_plain_pod_on_a_recorded_stream(seed):
    """A stream of requests as the engine makes them (documents re-asked with
    new suffixes, live sequences that pin blocks and let go later, a partly
    cached prompt stored again): ids, hits and evictions equal, call by
    call."""
    rng = np.random.default_rng(seed)
    mine, plain = Pod("p", OneGroup, None, 48), PlainPod(48)
    assert mine.window is None and not mine.protect_asked
    docs = [[1000 * d + i for i in range(6)] for d in range(6)]
    live, unique, hits, evictions = [], 10 ** 6, 0, 0
    for step in range(300):
        doc = docs[rng.integers(len(docs))]
        n_pre = int(rng.integers(0, 7))
        hashes = doc[:n_pre] + list(range(unique, unique + int(rng.integers(1, 5))))
        unique += 10
        got = []
        for pod in (mine, plain):
            cached = pod.cached_prefix(hashes[:n_pre]) if n_pre else []
            hit = bool(n_pre) and len(cached) == n_pre
            first = n_pre if hit else 0
            pod.touch(hashes[:first])
            pod.hold(cached[:first], +1)
            try:
                ids, evicted = pod.alloc(len(hashes) - first)
            except RuntimeError:
                ids, evicted = None, None
            pod.hold(cached[:first], -1)
            if ids is not None:
                for h, bid in zip(hashes[first:], ids):
                    pod.cached[h] = bid
            got.append((hit, cached, ids, evicted))
        assert got[0] == got[1]
        hits += got[0][0]
        evictions += len(got[0][3] or ())
        if got[0][2] is not None and len(live) < 4 and rng.random() < 0.4:
            blocks = got[0][1][:n_pre if got[0][0] else 0] + got[0][2]
            for pod in (mine, plain):
                pod.hold(blocks, +1)
            live.append(blocks)
        if live and rng.random() < 0.35:
            blocks = live.pop(int(rng.integers(len(live))))
            for pod in (mine, plain):
                pod.hold(blocks, -1)
        assert list(mine.cached.items()) == list(plain.cached.items())
        assert mine.free == plain.free
    assert hits > 5 and evictions > 50


def test_a_dropped_pod_frees_its_pools_without_the_collector():
    """The benchmark freezes the collector's view when a window opens, so a
    pod held in a reference cycle would keep 4 GB of pools until the process
    ends (a second run in one process then finds no room)."""
    import gc
    import weakref

    gc.disable()
    try:
        pod = Pod("p", afmoe, CFG, 8)
        store(pod, [1, 2, 3])
        leaves = [weakref.ref(a) for a in jax.tree.leaves(pod.kv.arrays)]
        gone = weakref.ref(pod)
        del pod
        assert gone() is None and all(ref() is None for ref in leaves)
    finally:
        gc.enable()
