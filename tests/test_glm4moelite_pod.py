"""The `glm4moelite` family on the pod path (models/glm4moelite.py: latent
attention whose cache is one vector a position a layer, sparse experts with a
shared one) and the pod's cache with its one group of the latent kind
(models/pod.py), at a small size on the CPU: a leading dense layer and two
expert layers, hidden 64, 4 heads over a latent of 32 + 8, 8 experts top-2 and
a shared one, block 16.

The comparisons run the program in float32, where it has to repeat the plain
reference (the published per-head form) to rounding; that the serving
precision stays near it is the chip check's business
(benchmarks/harness/family_glm4moelite.py).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import glm4moelite, kv_cache_pool
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

BLOCK, VOCAB = 16, 128
CFG = glm4moelite.Glm4MoeLiteConfig(dtype="float32", vocab_size=VOCAB)
PARAMS = glm4moelite.init_params(jax.random.key(0), CFG)
STEPS = {
    "miss": jax.jit(functools.partial(glm4moelite.prefill_paged, cfg=CFG)),
    "hit": jax.jit(functools.partial(glm4moelite.prefill_continue, cfg=CFG),
                   static_argnames=("prefix_len",)),
    "decode": jax.jit(functools.partial(glm4moelite.decode_step, cfg=CFG)),
}
PUBLISHED = dict(
    attention_bias=False, hidden_act="silu", hidden_size=2048,
    intermediate_size=10240, max_position_embeddings=202752,
    model_type="glm4_moe_lite", moe_intermediate_size=1536,
    topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
    n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
    routed_scaling_factor=1.8, num_experts_per_tok=4, first_k_dense_replace=1,
    num_hidden_layers=5, num_key_value_heads=20, num_nextn_predict_layers=1,
    partial_rotary_factor=1, rms_norm_eps=1e-05, rope_scaling=None,
    rope_theta=1000000, tie_word_embeddings=False, q_lora_rank=768,
    kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
    v_head_dim=256, vocab_size=154880, torch_dtype="bfloat16")


def tokens_of(n: int, *key: int) -> np.ndarray:
    return np.random.default_rng([11, *key]).integers(1, VOCAB, n)


def hashes_of(tokens) -> list[int]:
    """Chained block hashes, as the benchmark's engine makes them."""
    out, parent = [], b"root"
    data, width = np.asarray(tokens, "<i8").tobytes(), 8 * BLOCK
    for i in range(0, len(data) - len(data) % width, width):
        parent = hashlib.sha256(parent + data[i:i + width]).digest()
        out.append(int.from_bytes(parent[-8:], "big"))
    return out


_reference = jax.jit(functools.partial(glm4moelite.reference_logits, cfg=CFG))


@functools.cache
def reference(tokens: tuple) -> np.ndarray:
    """Logits [T, V] of the whole sequence (causal: row t is what a step that
    was fed token t has to give)."""
    return np.asarray(_reference(PARAMS, jnp.asarray(tokens)))


class Engine:
    """What the benchmark's engine does around a pod, call for call
    (`Fleet.account`, `run_chat.admit`, `commit`, `finish`), with the model
    steps run directly so that a test sees whole rows of logits."""

    def __init__(self, pool_blocks: int = 40) -> None:
        self.pod = Pod("pod-0", glm4moelite, CFG, pool_blocks)
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

    def decode(self, seqs: list[dict]) -> np.ndarray:
        width = max(len(s["blocks"]) for s in seqs)
        table = np.zeros((len(seqs), width), np.int32)
        for i, s in enumerate(seqs):
            table[i, :len(s["blocks"])] = s["blocks"]
        ctx = np.asarray([len(s["tokens"]) for s in seqs], np.int32)
        cur = np.asarray([s["tokens"][-1] for s in seqs], np.int32)
        table = self.pod.tables("decode", table, context_len=ctx)
        logits, arrays = STEPS["decode"](PARAMS, cur, self.pod.kv.arrays,
                                         table, ctx)
        self.load = np.asarray(arrays.pop("load"))
        self.read = np.asarray(arrays.pop("attention_read"))
        self.pod.kv.arrays = arrays
        return np.asarray(logits)

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("prefix_blocks", (5, 2))
def test_prefill_continue_and_decode_repeat_the_reference(prefix_blocks):
    """A miss, then a hit on its first blocks with a new suffix (at two
    prefix lengths), then both sequences decoded side by side for 24 steps
    through the pod's pool, across a block's end and a row's two halves; every
    row of logits against the reference's full forward pass in the per-head
    form."""
    eng = Engine()
    n = prefix_blocks * BLOCK
    doc, turn = tokens_of(n, 1), tokens_of(32, 2)
    first = eng.prefill(np.concatenate((doc, tokens_of(16, 3))),
                        prefix_blocks, own=2)
    assert not first["hit"]
    close(first["row"], reference(tuple(first["tokens"]))[-1])
    second = eng.prefill(np.concatenate((doc, turn)), prefix_blocks, own=2)
    assert second["hit"]
    assert second["blocks"][:prefix_blocks] == first["blocks"][:prefix_blocks]
    close(second["row"], reference(tuple(second["tokens"]))[-1])
    seqs = [first, second]
    for s in seqs:
        s["tokens"].append(int(np.argmax(s["row"])))
        s["rows"] = []
    for _ in range(24):
        logits = eng.decode(seqs)
        assert np.isfinite(logits).all()
        for s, row in zip(seqs, logits):
            s["rows"].append(row)
            s["tokens"].append(int(np.argmax(row)))
    for s in seqs:
        close(np.stack(s["rows"]), reference(tuple(s["tokens"]))[-25:-1])
    # the two tables begin with the same blocks, and two are too few for the
    # shared pass (`SHARED_MIN_SEQUENCES`): each walks its whole table
    read, walked, by_runs, by_shared_runs = eng.read
    # no table holds a wave of 64
    assert read == walked and by_runs == by_shared_runs == 0
    assert eng.load.shape == (2, 2) and (eng.load[:, 0] <= 4).all()


def test_a_prefix_that_enough_sequences_share_is_read_once():
    """A miss and three hits on its first blocks, decoded side by side across
    a block's end: with `SHARED_MIN_SEQUENCES` tables beginning with the same
    blocks the shared pass reads the run once and each walk resumes from it;
    every row of logits is the reference's."""
    assert glm4moelite.SHARED_MIN_SEQUENCES == 4
    eng = Engine()
    doc = tokens_of(5 * BLOCK, 1)
    seqs = [eng.prefill(
        np.concatenate((doc, tokens_of(16 + 16 * (i % 2), 3 + i))), 5, own=1)
        for i in range(4)]
    assert [s["hit"] for s in seqs] == [False, True, True, True]
    for s in seqs:
        s["tokens"].append(int(np.argmax(s["row"])))
        s["rows"] = []
    for _ in range(6):
        for s, row in zip(seqs, eng.decode(seqs)):
            s["rows"].append(row)
            s["tokens"].append(int(np.argmax(row)))
        read, walked, by_runs, by_shared_runs = eng.read
        assert read == walked - 3 * 5 and by_runs == by_shared_runs == 0
    for s in seqs:
        close(np.stack(s["rows"]), reference(tuple(s["tokens"]))[-7:-1])


def test_a_decode_step_counts_the_blocks_its_walk_brings_by_runs(monkeypatch):
    """`attention_read`'s third count, through the pod's own allocator: a
    fresh pool deals its blocks out ascending, so with waves of two (the
    family's 64 hold no table of a test) every whole wave of a sequence is a
    run, a last wave of one block is not, and the rows are the reference's
    all the same."""
    monkeypatch.setattr(glm4moelite, "DECODE_BLOCKS_PER_WAVE", 2)
    monkeypatch.setitem(STEPS, "decode", jax.jit(
        functools.partial(glm4moelite.decode_step, cfg=CFG)))
    eng = Engine()
    seqs = [eng.prefill(tokens_of(16 * n, n), 0, own=1) for n in (5, 4)]
    assert [s["blocks"] for s in seqs] == [[0, 1, 2, 3, 4, 5],
                                           [6, 7, 8, 9, 10]]
    for s in seqs:
        s["tokens"].append(int(np.argmax(s["row"])))
    for s, row in zip(seqs, eng.decode(seqs)):
        close(row, reference(tuple(s["tokens"]))[-1])
    # (0, 1), (2, 3), (4, 5); (6, 7), (8, 9) and block 10 alone
    assert list(eng.read) == [11, 11, 10, 0]  # and nobody shares


def test_a_long_prefill_attends_a_chunk_of_queries_at_a_time(monkeypatch):
    """Past `ATTN_CHUNK_TOKENS` a prefill's attention is one kernel call a
    chunk of its queries (the offset is data), and the rows are what one call
    gives."""
    tokens = tokens_of(96, 5)
    pool = glm4moelite.new_pool(CFG, 8)
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    whole, _ = glm4moelite.prefill_paged(
        PARAMS, jnp.asarray(tokens)[None], pool, table, CFG)
    monkeypatch.setattr(glm4moelite, "ATTN_CHUNK_TOKENS", 32)
    chunked, pools = glm4moelite.prefill_paged(
        PARAMS, jnp.asarray(tokens)[None], pool, table, CFG)
    close(np.asarray(chunked[0, 0]), np.asarray(whole[0, 0]), tol=1e-5)
    close(np.asarray(chunked[0, 0]), reference(tuple(tokens))[-1])
    assert (np.asarray(pools["full"][0][0]) == 0).all()  # slot 0 not named


def test_the_latent_identity_on_its_own():
    """`q~ . c` against `qn . kn`, and `o~ . W_uv` against `sum p v`, to
    float32 rounding: what lets a step score and weigh the cached vector
    without ever making a head's keys and values."""
    rng = np.random.default_rng(0)
    H, Rkv, dn, dv, T = 4, 32, 16, 16, 24
    hi = jax.lax.Precision.HIGHEST
    w_kvb = jnp.asarray(rng.normal(size=(Rkv, H, dn + dv)), jnp.float32)
    qn = jnp.asarray(rng.normal(size=(H, dn)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(T, Rkv)), jnp.float32)
    kv = jnp.einsum("tr,rhk->thk", c, w_kvb, precision=hi)
    per_head = jnp.einsum("hn,thn->ht", qn, kv[..., :dn], precision=hi)
    folded = jnp.einsum("hn,rhn->hr", qn, w_kvb[..., :dn], precision=hi)
    latent = jnp.einsum("hr,tr->ht", folded, c, precision=hi)
    close(np.asarray(latent), np.asarray(per_head), tol=1e-5)
    p = jax.nn.softmax(per_head, -1)
    out = jnp.einsum("ht,thv->hv", p, kv[..., dn:], precision=hi)
    weighed = jnp.einsum("ht,tr->hr", p, c, precision=hi)
    close(np.asarray(jnp.einsum("hr,rhv->hv", weighed, w_kvb[..., dn:],
                                precision=hi)), np.asarray(out), tol=1e-5)


def test_rope_pairs_neighbouring_lanes_and_keeps_scores_relative():
    """Lanes (2i, 2i + 1) turn together, position 0 turns nothing, and a
    score depends on the distance alone."""
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 4, 8)),
                    jnp.float32)
    at = lambda p: glm4moelite._rope(x, jnp.full((1, 4), p), 1e4)
    np.testing.assert_allclose(at(0), x, atol=1e-6)
    turned = np.asarray(at(3))
    pairs = lambda a: np.asarray(a).reshape(1, 4, 4, 2)
    np.testing.assert_allclose(np.linalg.norm(pairs(turned), axis=-1),
                               np.linalg.norm(pairs(x), axis=-1), rtol=1e-5)
    assert not np.allclose(turned, np.asarray(x))
    score = lambda a, b: float(jnp.sum(at(a)[0, 0] * at(b)[0, 1]))
    assert abs(score(7, 2) - score(12, 7)) < 1e-4


def test_the_three_programs_serve_the_reference_tokens_and_record_the_read():
    """`jit_programs`: every shape compiles at the first call of any; each
    call donates the pools and hands the handle back; the tokens served are
    the reference's; a decode call of this one-group pod says what the step
    reads of the latent cache and what share of its bytes that is."""
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(glm4moelite, CFG, shapes, interpret=True)
    pod = Pod("pod-0", glm4moelite, CFG, 40)
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
            tuple(prompts[0]))[-1].argmax()
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
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[-1].argmax() for s in seqs]
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0, ring_size=64)
    spans = [r for r in rows if r["span"] is not None]
    assert {"kv.read", "moe.expert_load", "attention.read", "pod.compile",
            "pod.counts_read", "pod.pack", "pod.launch.miss", "pod.launch.hit",
            "pod.launch.decode"} == {r["span"] for r in spans}
    spec = glm4moelite.cache_groups(CFG)["full"]
    read = [r["attrs"] for r in spans if r["span"] == "kv.read"]
    assert len(read) == 2 and read[-1] == {
        "full_blocks": 7 + 8, "latent_bytes": 15 * spec.read_nbytes,
        "step_bytes": 15 * spec.read_nbytes + CFG.decode_weight_nbytes}
    walked = [r["attrs"] for r in spans if r["span"] == "attention.read"]
    # a pair over one run is walked whole (`SHARED_MIN_SEQUENCES`)
    assert walked == [{"read_blocks": 7 + 8, "walked_blocks": 7 + 8,
                       "run_blocks": 0, "shared_run_blocks": 0}]
    load = [r["attrs"] for r in spans if r["span"] == "moe.expert_load"]
    assert len(load) == 2 and all(
        a["experts_held"] == 8 and 1 <= a["experts_touched"] <= 4
        and a["mean_tokens"] == 0.5 for a in load)


def test_bfloat16_serving_stays_near_the_reference():
    """The serving type end to end at the small size: the program in
    bfloat16 against the float32 reference of the same (bfloat16-valued)
    weights."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = glm4moelite.init_params(jax.random.key(0), cfg)
    tokens = tokens_of(96, 6)
    pool = glm4moelite.new_pool(cfg, 8)
    logits, pools = glm4moelite.prefill_paged(
        params, jnp.asarray(tokens)[None], pool,
        jnp.arange(1, 7, dtype=jnp.int32)[None], cfg)
    want = np.asarray(glm4moelite.reference_logits(
        params, jnp.asarray(tokens), cfg))[-1]
    got = np.asarray(logits[0, 0], np.float32)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.05
    assert pools["full"][0].dtype == jnp.bfloat16


# ------------------------------------------------------------ cache and pod


def test_block_bytes_and_pool_shapes_come_from_the_latent_spec():
    cfg = glm4moelite.from_published(PUBLISHED, BLOCK)
    spec = glm4moelite.cache_groups(cfg)["full"]
    assert (spec.latent_dim, spec.value_dim, spec.num_kv_heads) == (576, 512, 1)
    assert spec.block_nbytes == spec.read_nbytes == 92160
    assert spec.layer_shape(73728) == (73728, 8, 1152)
    small = glm4moelite.new_pool(CFG, 12)
    assert list(small) == ["full"] and len(small["full"]) == CFG.n_layers
    assert small["full"][0].shape == (12, 8, 2 * CFG.latent_dim)
    assert sum(a.nbytes for a in small["full"]) == 12 * glm4moelite.cache_groups(
        CFG)["full"].block_nbytes
    # what a decode step reads of the weights: all but the embedding
    # (ISSUE 42: 0.63 + 0.17 + 4 x 1.27 = 5.89 GB)
    assert round(cfg.decode_weight_nbytes / 1e9, 2) == 5.89
    policy = glm4moelite.cache_policy(cfg)
    assert policy["step_weight_nbytes"] == cfg.decode_weight_nbytes
    assert "window" not in policy and "state" not in policy


def test_a_decode_write_lands_where_the_scatter_would_put_it():
    """`write_token` into a row's first or second half, mirrored, is what
    `write_blocks` writes for that position; nothing else moves."""
    width = CFG.latent_dim
    rng = np.random.default_rng(2)
    latents = jnp.asarray(rng.normal(size=(1, 2 * BLOCK, width)), jnp.float32)
    pool = jnp.zeros((4, BLOCK // 2, 2 * width), jnp.float32)
    spec = glm4moelite.cache_groups(CFG)["full"]
    want = kv_cache_pool.write_blocks(spec, pool, jnp.asarray([[3, 1]]),
                                      latents)
    got = pool
    for pos in range(2 * BLOCK):
        got = kv_cache_pool.write_token(
            spec, got, jnp.asarray([[3, 1][pos // BLOCK]]),
            jnp.asarray([pos % BLOCK]), latents[:, pos])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("key, value", (
    ("n_group", 2), ("topk_group", 2), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("q_lora_rank", None), ("hidden_act", "gelu"),
    ("topk_method", "greedy"), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("partial_rotary_factor", 0.5),
    ("num_key_value_heads", 4), ("qk_rope_head_dim", 63),
))
def test_from_published_refuses_what_the_equations_do_not_cover(key, value):
    cfg = glm4moelite.from_published(PUBLISHED, BLOCK)
    assert (cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
            cfg.v_dim, cfg.n_experts, cfg.top_k, cfg.n_layers) == (
        20, 768, 512, 192, 64, 256, 64, 4, 5)
    assert cfg.route_scale == 1.8 and cfg.rope_theta == 1e6
    with pytest.raises(ValueError, match=key):
        glm4moelite.from_published({**PUBLISHED, key: value}, BLOCK)


def test_the_pod_with_a_latent_group_caches_evicts_and_publishes_as_any():
    """`cached_prefix`, eviction and what `alloc` hands back for
    `BlockRemoved`, as for a K/V group: a block of 16 tokens is a block."""
    eng = Engine(pool_blocks=12)
    a = eng.prefill(tokens_of(64, 7), 4)  # 4 blocks, asked (a miss)
    eng.finish(a)
    assert eng.pod.cached_prefix(a["hashes"]) == a["blocks"]
    assert eng.pod.cached_prefix(a["hashes"][:2]) == a["blocks"][:2]
    b = eng.prefill(tokens_of(64, 8), 0)  # never asked for
    eng.finish(b)
    again = eng.prefill(np.concatenate((tokens_of(64, 7), tokens_of(16, 9))), 4)
    assert again["hit"] and again["blocks"][:4] == a["blocks"]
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
