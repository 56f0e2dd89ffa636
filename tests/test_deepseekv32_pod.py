"""The `deepseekv32` family on the pod path (models/deepseekv32.py: latent
attention under a learned selection, the selector's key in the latent's slot,
group-limited experts of which the chip holds a share, a rotation rescaled by
YaRN) and the pod's cache with its one group of the latent-selected kind
(models/pod.py), at a small size on the CPU: three layers (one dense), hidden
64, 4 heads over a latent of 32 + 8, an indexer of 4 heads of 16 that picks 8
positions, 8 experts in 4 groups of which 2 are kept, top-2, experts 2-5 held,
block 16.

The comparisons run the program in float32, where it has to repeat the plain
reference (benchmarks/harness/family_deepseekv32.py: the per-head form, `I` as
a whole causal array, `lax.top_k`, a dense softmax under the picks' mask) to
rounding, logits AND picked sets; that the serving precision stays near it is
the chip check's business.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import family_deepseekv32
from llm_d_kv_cache_manager_tpu.models import (
    deepseekv32, kv_cache_pool, layers, moe_serve,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (
    latent_picked_prefill_pallas, latent_prefill_attention_pallas,
)

BLOCK, VOCAB, TOPK = 16, 128, 8
PUBLISHED = dict(
    attention_bias=False, ep_size=1, first_k_dense_replace=3,
    hidden_act="silu", hidden_size=7168, index_head_dim=128, index_n_heads=64,
    index_topk=2048, intermediate_size=18432, kv_lora_rank=512,
    max_position_embeddings=163840, model_type="deepseek_v32",
    moe_intermediate_size=2048, moe_layer_freq=1, n_group=8,
    n_routed_experts=256, n_shared_experts=1, norm_topk_prob=True,
    num_attention_heads=128, num_experts_per_tok=8, num_hidden_layers=61,
    num_key_value_heads=128, num_nextn_predict_layers=0, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-06,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"},
    rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
    tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
    v_head_dim=128, vocab_size=129280, torch_dtype="bfloat16")
# the reference's view of the small configuration: the published keys, with
# the chip's share as the benchmark's file states it
TINY = {**PUBLISHED, "first_k_dense_replace": 1, "hidden_size": 64,
        "index_head_dim": 16, "index_n_heads": 4, "index_topk": TOPK,
        "intermediate_size": 128, "kv_lora_rank": 32,
        "moe_intermediate_size": 32, "n_group": 4, "n_routed_experts": 4,
        "num_attention_heads": 4, "num_experts_per_tok": 2,
        "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "topk_group": 2,
        "v_head_dim": 16, "vocab_size": VOCAB, "torch_dtype": "float32",
        "rope_scaling": {**PUBLISHED["rope_scaling"],
                         "original_max_position_embeddings": 64},
        "published": {"n_routed_experts": 8}, "held": {"experts_first": 2}}
CFG = deepseekv32.from_published(TINY, BLOCK)
PARAMS = family_deepseekv32.make_weights(TINY, 5)
STEPS = {
    "miss": jax.jit(functools.partial(deepseekv32.prefill_paged, cfg=CFG)),
    "hit": jax.jit(functools.partial(deepseekv32.prefill_continue, cfg=CFG),
                   static_argnames=("prefix_len",)),
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
    logits = family_deepseekv32.forward_logits(
        PARAMS, TINY, np.asarray(tokens), len(tokens), picks=picks)
    return np.asarray(logits), [np.asarray(p) for p in picks]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


def test_the_configuration_reads_the_published_keys_and_refuses_the_rest():
    assert (CFG.n_experts, CFG.held, CFG.experts_held) == (8, (2, 4), 4)
    assert (CFG.n_group, CFG.topk_group, CFG.top_k) == (4, 2, 2)
    assert CFG.latent_dim == 40 and CFG.index_topk == TOPK
    m = 0.1 * math.log(40) + 1
    assert CFG.score_scale == pytest.approx(24 ** -0.5 * m * m)
    spec = deepseekv32.cache_groups(CFG)["full"]
    assert spec.layout == "latent_selected" and spec.selected == TOPK
    assert spec.layer_shape(7) == (7, 8, 2 * 40 + 2 * 16)
    assert spec.block_nbytes == 3 * 16 * (40 + 16) * 4
    big = deepseekv32.from_published(
        {**PUBLISHED, "n_routed_experts": 16, "published":
         {"n_routed_experts": 256}, "held": {"experts_first": 0}}, 16)
    assert big.score_scale == pytest.approx(192 ** -0.5 * 1.3688879 ** 2)
    assert deepseekv32.cache_groups(big)["full"].block_nbytes == 61 * 16 * 1408
    for key, bad in (("num_nextn_predict_layers", 1), ("scoring_func", "softmax"),
                     ("topk_method", "greedy"), ("q_lora_rank", None),
                     ("rope_scaling", None), ("n_group", 3),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="deepseekv32"):
            deepseekv32.from_published({**TINY, key: bad}, BLOCK)
    with pytest.raises(ValueError, match="past the router's"):
        deepseekv32.from_published(
            {**TINY, "held": {"experts_first": 6}}, BLOCK)


def test_yarn_frequencies_at_the_published_numbers():
    """`low` and `high` by hand: dr 64, theta 10000, 4096 positions, 32 turns
    and 1: floor(64 ln(4096 / (64 pi)) / (2 ln 1e4)) = floor(10.47) = 10 and
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23."""
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000))
    assert (math.floor(low), math.ceil(high)) == (10, 23)
    f = np.asarray(layers.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)  # kept
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)  # / factor
    r = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(f[11:23], plain[11:23] * (1 - r + r / 40),
                               rtol=1e-5)
    np.testing.assert_allclose(  # the benchmark's own text of the same
        np.asarray(family_deepseekv32.yarn_inv_freq(
            family_deepseekv32.sizes({**PUBLISHED, "n_routed_experts": 16}))),
        f, rtol=1e-6)


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("prefix_blocks", (5, 1))
def test_miss_hit_and_decode_repeat_the_reference_logits_and_picks(
        prefix_blocks):
    """A miss of 96 positions; a hit of 32 on a cached prefix of 80 (over
    `topk`) or of 16; then 20 decode steps of two sequences side by side
    through the pool, across a block's end.  Every layer's picked sets are
    the reference's, and the logits to float32 rounding."""
    prefix = prefix_blocks * BLOCK
    doc, turn = tokens_of(prefix, 1), tokens_of(32, 2)
    first = np.concatenate((doc, tokens_of(96 - prefix, 3)))
    second = np.concatenate((doc, turn))
    pool = deepseekv32.new_pool(CFG, 24)
    ids = np.random.default_rng(prefix).permutation(24)
    nb = prefix // BLOCK
    t1 = np.asarray(ids[:9], np.int32)  # 6 blocks of the miss, three to grow
    t2 = np.concatenate((t1[:nb], ids[9:9 + 9 - nb])).astype(np.int32)
    taps: list = []
    logits, pools = deepseekv32.prefill_paged(
        PARAMS, jnp.asarray(first)[None], pool, jnp.asarray(t1[None, :6]),
        CFG, taps=taps)
    want, picks = reference(tuple(first))
    close(np.asarray(logits[0, 0]), want[-1])
    assert len(taps) == CFG.n_layers
    for got, ref in zip(taps, picks):
        np.testing.assert_array_equal(np.asarray(got[0]), ref)
    taps = []
    n2 = (prefix + 32) // BLOCK
    logits2, pools = deepseekv32.prefill_continue(
        PARAMS, jnp.asarray(turn)[None], {"full": pools["full"]},
        jnp.asarray(t2[None, :n2]), prefix, CFG, taps=taps)
    want2, picks2 = reference(tuple(second))
    close(np.asarray(logits2[0, 0]), want2[-1])
    for got, ref in zip(taps, picks2):
        np.testing.assert_array_equal(np.asarray(got[0]), ref[prefix:])
        assert (np.asarray(got[0]).sum(-1)
                == np.minimum(prefix + 1 + np.arange(32), TOPK)).all()
    seqs = [list(first) + [int(want[-1].argmax())],
            list(second) + [int(want2[-1].argmax())]]
    table = np.stack((t1, t2))
    kv, rows, tapped = {"full": pools["full"]}, [[], []], []
    for _ in range(20):
        taps = []
        ctx = np.asarray([len(s) for s in seqs], np.int32)
        out, kv = deepseekv32.decode_step(
            PARAMS, jnp.asarray([s[-1] for s in seqs]), kv,
            jnp.asarray(table), jnp.asarray(ctx), CFG, taps=taps)
        load = np.asarray(kv.pop("load"))
        tapped.append(taps)
        for s, r, row in zip(seqs, rows, np.asarray(out)):
            r.append(row)
            s.append(int(row.argmax()))
    # the expert layers' counts: held experts touched, the most picks of
    # one, all picks (2 a token), those that fell on experts 2-5
    assert load.shape == (2, 4) and (load[:, 2] == 4).all()
    assert (load[:, 3] <= 4).all() and (load[:, 0] <= load[:, 3]).all()
    for b, (s, r) in enumerate(zip(seqs, rows)):
        want, picks = reference(tuple(s))
        close(np.stack(r), want[-21:-1])
        for step, taps in enumerate(tapped):
            t = len(s) - 21 + step  # the position this step's query stands at
            for (at, ok), ref in zip(taps, picks):
                got = np.zeros(len(s), bool)
                got[np.asarray(at[b])[np.asarray(ok[b])]] = True
                np.testing.assert_array_equal(got, ref[t])


def test_a_long_prefill_attends_and_feeds_forward_by_chunks(monkeypatch):
    """Past `ATTN_CHUNK_TOKENS` a prefill's attention is one loop of kernel
    calls (the offset is data), past `FF_CHUNK_TOKENS` its feed-forward one
    of chunks, and logits and picks are what one call gives."""
    tokens = tokens_of(96, 5)
    pool = deepseekv32.new_pool(CFG, 8)
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    monkeypatch.setattr(deepseekv32, "ATTN_CHUNK_TOKENS", 16)
    monkeypatch.setattr(deepseekv32, "FF_CHUNK_TOKENS", 32)
    taps: list = []
    chunked, pools = deepseekv32.prefill_paged(
        PARAMS, jnp.asarray(tokens)[None], pool, table, CFG, taps=taps)
    want, picks = reference(tuple(tokens))
    close(np.asarray(chunked[0, 0]), want[-1])
    for got, ref in zip(taps, picks):
        np.testing.assert_array_equal(np.asarray(got[0]), ref)
    assert (np.asarray(pools["full"][0][0]) == 0).all()  # slot 0 not named


def test_a_context_under_topk_is_the_unselected_latent_form():
    """With fewer positions than `topk` every query picks all it may see: the
    kernel under the picks gives what the latent kernel without selection
    gives over the same latents (`glm4moelite`'s), and the model the logits
    of a reference that never selects."""
    rng = np.random.default_rng(7)
    spec = deepseekv32.cache_groups(CFG)["full"]
    W, V = CFG.latent_dim, CFG.kv_rank
    latent = jnp.asarray(rng.normal(size=(1, 48, W)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(1, 48, CFG.index_dim)), jnp.float32)
    table = jnp.asarray([[4, 1, 6]])
    pool = kv_cache_pool.write_blocks(
        spec, jnp.zeros(spec.layer_shape(8), jnp.float32), table, latent, keys)
    q = jnp.asarray(rng.normal(size=(1, 32, 4, W)), jnp.float32)
    causal = (jnp.arange(48)[None, :] <= 16 + jnp.arange(32)[:, None])[None]
    got = latent_picked_prefill_pallas(
        q, pool, table, causal, q_offset=16, value_dim=V, scale=0.2,
        interpret=True)
    plain = kv_cache_pool.write_blocks(
        dataclasses.replace(spec, selector_dim=None, selected=None),
        jnp.zeros((8, 8, 2 * W), jnp.float32), table, latent)
    want = latent_prefill_attention_pallas(
        q, plain, table, q_offset=16, value_dim=V, scale=0.2, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the model: `topk` 64 over 48 positions, and a reference that picks all
    tokens = tokens_of(48, 9)
    cfg = dataclasses.replace(CFG, index_topk=64)
    taps: list = []
    logits, _ = deepseekv32.prefill_paged(
        PARAMS, jnp.asarray(tokens)[None], deepseekv32.new_pool(cfg, 8),
        jnp.arange(1, 4, dtype=jnp.int32)[None], cfg, taps=taps)
    assert all((np.asarray(t[0]) == np.tril(np.ones((48, 48), bool))).all()
               for t in taps)
    want = np.asarray(family_deepseekv32.forward_logits(
        PARAMS, {**TINY, "index_topk": 10**6}, tokens, 1))[0]
    close(np.asarray(logits[0, 0]), want)
    # and `reference_logits`, the package's copy, is the benchmark's
    mine = np.asarray(deepseekv32.reference_logits(PARAMS, tokens_of(96, 5),
                                                   CFG))
    close(mine, reference(tuple(tokens_of(96, 5)))[0])


@pytest.mark.parametrize("offset, tq, tile, step", (
    (48, 40, 16, 2),  # a hit: a prefix of 3 blocks, tiles that end mid-step
    (0, 96, 32, 4),  # a miss from position 0; the table padded to the step
    (80, 16, 8, 1),  # a block a step
))
def test_the_picked_kernel_walks_tiles_and_steps(monkeypatch, offset, tq,
                                                 tile, step):
    """The kernel's tile and its blocks a step are constants sized for 128
    heads; made small here, a call takes several tiles of queries and several
    steps of blocks (two buffers, a last step that is part padding), and
    gives the softmax over each query's picked positions alone."""
    from llm_d_kv_cache_manager_tpu.ops import latent_prefill_pallas as kernel
    monkeypatch.setattr(kernel, "PICKED_Q_TILE", tile)
    monkeypatch.setattr(kernel, "PICKED_BLOCKS_PER_STEP", step)
    rng = np.random.default_rng(tq)
    spec = deepseekv32.cache_groups(CFG)["full"]
    W, V, blocks = CFG.latent_dim, CFG.kv_rank, 6
    L = blocks * BLOCK
    latent = jnp.asarray(rng.normal(size=(2, L, W)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(2, L, CFG.index_dim)), jnp.float32)
    table = jnp.asarray(np.stack([b * 12 + rng.permutation(12)[:blocks]
                                  for b in range(2)]), jnp.int32)
    pool = kv_cache_pool.write_blocks(
        spec, jnp.zeros(spec.layer_shape(24), jnp.float32), table, latent, keys)
    q = jnp.asarray(rng.normal(size=(2, tq, 4, W)), jnp.float32)
    seen = jnp.arange(L)[None, None, :] <= offset + jnp.arange(tq)[None, :, None]
    scores = jnp.asarray(rng.normal(size=(2, tq, L)), jnp.float32)
    picked = sparse.topk_mask(jnp.where(seen, scores, -jnp.inf), TOPK)
    got = latent_picked_prefill_pallas(
        q, pool, table, picked, q_offset=offset, value_dim=V, scale=0.2,
        interpret=True)
    s = jnp.einsum("bqhw,bkw->bhqk", q, latent) * 0.2
    p = jax.nn.softmax(jnp.where(picked[:, None], s, -jnp.inf), -1)
    want = jnp.einsum("bhqk,bkv->bqhv", p, latent[..., :V])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips that hold experts 0-1, 2-3, 4-5 and 6-7 of one expert
    layer: their partial sums, the shared expert counted once, are the layer
    of a chip that holds all eight, in the program and in the reference."""
    whole = {**TINY, "n_routed_experts": 8, "held": {"experts_first": 0}}
    full = family_deepseekv32.make_weights(whole, 3)
    lp = full["layers"][1]
    cfg = deepseekv32.from_published(whole, BLOCK)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, 64)),
                    jnp.float32)
    uncut, load = deepseekv32._ff_block(x, lp, cfg, False)
    assert int(load[2]) == int(load[3]) == 48  # every pick falls here
    parts, held_picks = [], 0
    for first in range(0, 8, 2):
        share = {**lp, "experts": jax.tree.map(lambda a: a[first:first + 2],
                                               lp["experts"])}
        out, load = deepseekv32._ff_block(
            x, share, dataclasses.replace(cfg, held=(first, 2)), False)
        parts.append(out - x)
        held_picks += int(load[3])
        assert int(load[2]) == 48
    assert held_picks == 48
    shared = layers.swiglu(
        layers.rms_norm(x, lp["ln_post"], cfg.rms_eps), lp["shared"])
    np.testing.assert_allclose(sum(parts) - 3 * shared, uncut - x, atol=2e-5)
    # the reference, whole sequences: the last layer's shares around one stream
    tokens = tokens_of(32, 4)
    z = tuple(sorted(family_deepseekv32.sizes(whole).items()))
    stream = jnp.take(full["embed"], jnp.asarray(tokens), axis=0)
    stream = jnp.pad(stream, ((0, 224), (0, 0)))
    want = family_deepseekv32._feed_forward(stream, lp, z, None) - stream
    got = 0
    for first in range(0, 8, 2):
        cut = {**whole, "n_routed_experts": 2, "published":
               {"n_routed_experts": 8}, "held": {"experts_first": first}}
        share = {**lp, "experts": jax.tree.map(lambda a: a[first:first + 2],
                                               lp["experts"])}
        zc = tuple(sorted(family_deepseekv32.sizes(cut).items()))
        got = got + family_deepseekv32._feed_forward(stream, share, zc,
                                                     None) - stream
    shared = family_deepseekv32._swiglu_sliced(
        family_deepseekv32._norm(stream, lp["ln_post"], 1e-6), lp["shared"],
        None, 32)
    np.testing.assert_allclose((got - 3 * shared)[:32], want[:32], atol=2e-5)


# --------------------------------------------------------- through the pod


def test_the_three_programs_serve_the_reference_tokens_and_record_the_read():
    """`jit_programs`: every shape compiles at the first call of any; each
    call donates the pools and hands the handle back; the tokens served are
    the reference's; a hit over a shared prefix serves what a miss of the
    same prompt serves; a decode call of this one-group pod says what the
    step reads of the cache: selector keys of every live position, the
    latents of the picked ones, and what reading every position's latent
    would be; the expert layers' counts carry the picks that fell here."""
    shapes = {"miss": (96,), "hit": (80, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(deepseekv32, CFG, shapes, interpret=True)
    pod = Pod("pod-0", deepseekv32, CFG, 40)
    assert pod.groups == [] and pod.protect_asked and pod.decode_ahead
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
        # the same prompt as a miss, over blocks of its own: the same row
        fresh, _ = pod.alloc(6)
        _, again, _ = programs["miss"](
            PARAMS, prompts[1][None, :96], pod.kv, np.asarray(fresh)[None])
        close(np.asarray(again),
              reference(tuple(prompts[1][:96]))[0][-1])
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
    n_layers, item = CFG.n_layers, 4
    key, latent = 16 * item * n_layers, 40 * item * n_layers
    assert len(read) == 2 and read[-1] == {
        "full_blocks": 7 + 8, "index_bytes": (97 + 113) * key,
        "picked_bytes": 2 * TOPK * latent,
        "sparse_bytes": (97 + 113) * key + 2 * TOPK * latent,
        "dense_bytes": (97 + 113) * latent,
        "step_bytes": (97 + 113) * key + 2 * TOPK * latent
        + CFG.decode_weight_nbytes}
    load = [r["attrs"] for r in spans if r["span"] == "moe.expert_load"]
    assert len(load) == 2 and all(
        a["experts_held"] == 4 and a["picks"] == 4
        and 0 <= a["picks_held"] <= 4 and a["experts_touched"] <= a["picks_held"]
        and a["mean_tokens"] == 0.5 for a in load)


def test_a_hit_over_a_shared_prefix_serves_what_a_miss_serves():
    """Two prompts over one document through the pod's own cache: the second
    is a hit on the document's five blocks, and its logits are those of the
    same prompt served as a miss by a pod that never saw the document."""
    doc, turn = tokens_of(80, 1), tokens_of(32, 8)
    prompt = np.concatenate((doc, turn))

    def serve(pod, tokens, n_prefix):
        hashes = hashes_of(tokens)
        cached = pod.cached_prefix(hashes[:n_prefix]) if n_prefix else []
        hit = bool(n_prefix) and len(cached) == n_prefix
        first = n_prefix if hit else 0
        new, _ = pod.alloc(len(hashes) - first)
        blocks = cached[:first] + new
        table = pod.tables("hit" if hit else "miss",
                           np.asarray(blocks, np.int32)[None],
                           prefix_blocks=first)
        ids = jnp.asarray(tokens[first * BLOCK:], jnp.int32)[None]
        if hit:
            logits, arrays = STEPS["hit"](PARAMS, ids, pod.kv.arrays, table,
                                          prefix_len=first * BLOCK)
        else:
            logits, arrays = STEPS["miss"](PARAMS, ids, pod.kv.arrays, table)
        arrays.pop("load")
        pod.kv.arrays = arrays
        for h, bid in zip(hashes[first:], blocks[first:]):
            pod.cached[h] = bid
        return hit, np.asarray(logits[0, 0])

    warm = Pod("pod-0", deepseekv32, CFG, 40)
    assert serve(warm, np.concatenate((doc, tokens_of(16, 3))), 5)[0] is False
    hit, got = serve(warm, prompt, 5)
    cold = Pod("pod-1", deepseekv32, CFG, 40)
    miss, want = serve(cold, prompt, 5)
    assert hit and not miss
    close(got, want, 1e-5)
    close(got, reference(tuple(prompt))[0][-1])


def test_bfloat16_serving_stays_near_the_reference():
    """The serving type end to end at the small size: the program in
    bfloat16 against the float32 reference of the same (bfloat16-valued)
    weights, with `topk` at the prompt's length (every position picked: the
    distance is the products' rounding)."""
    tokens = tokens_of(96, 6)
    tiny = {**TINY, "torch_dtype": "bfloat16", "index_topk": 96}
    cfg = dataclasses.replace(CFG, dtype="bfloat16", index_topk=96)
    params = family_deepseekv32.make_weights(tiny, 6)
    logits, pools = deepseekv32.prefill_paged(
        params, jnp.asarray(tokens)[None], deepseekv32.new_pool(cfg, 8),
        jnp.arange(1, 7, dtype=jnp.int32)[None], cfg)
    want = np.asarray(
        family_deepseekv32.forward_logits(params, tiny, tokens, 1))[0]
    got = np.asarray(logits[0, 0], np.float32)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.05
    assert pools["full"][0].dtype == jnp.bfloat16


# ------------------------------------------------------------- the kernels


def test_the_walked_scores_over_latent_slots_are_the_dense_scores():
    """`latent_index_scores_pallas` over tables of runs and of scattered
    blocks against the scores written out, and `picked_latent_rows` names the
    picks as rows of the pool and their halves."""
    rng = np.random.default_rng(11)
    spec = deepseekv32.cache_groups(CFG)["full"]
    HI, dI, W = CFG.index_heads, CFG.index_dim, CFG.latent_dim
    T = 6 * BLOCK
    latent = jnp.asarray(rng.normal(size=(2, T, W)), jnp.float32)
    keys = jnp.asarray(rng.normal(size=(2, T, dI)), jnp.float32)
    table = jnp.asarray([[3, 4, 5, 6, 7, 8], [20, 2, 11, 9, 30, 14]])
    pool = kv_cache_pool.write_blocks(
        spec, jnp.zeros(spec.layer_shape(32), jnp.float32), table, latent, keys)
    q = jnp.asarray(rng.normal(size=(2, HI, dI)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, HI)), jnp.float32)
    ctx = jnp.asarray([T - 3, 41])
    got = sparse.latent_index_scores_pallas(
        q, w, pool, table, ctx, latent_dim=W, wave_blocks=2, interpret=True)
    want = np.einsum("bj,bjt->bt", w, np.maximum(
        np.einsum("bjd,btd->bjt", q, keys), 0))
    seen = np.arange(T)[None] < np.asarray(ctx)[:, None]
    np.testing.assert_allclose(np.where(seen, got, 0), np.where(seen, want, 0),
                               atol=1e-4)
    assert np.isneginf(np.asarray(got)[~seen]).all()
    np.testing.assert_array_equal(
        kv_cache_pool.gather_selector_keys(spec, pool, table), keys)
    picked = sparse.topk_mask(got, TOPK)
    rows, second, at, ok = sparse.picked_latent_rows(picked, table, TOPK, BLOCK)
    assert np.asarray(ok).all()
    for b in range(2):
        np.testing.assert_array_equal(np.asarray(at[b]),
                                      np.nonzero(np.asarray(picked[b]))[0])
    inside = np.asarray(at) % BLOCK
    np.testing.assert_array_equal(
        np.asarray(rows), np.take_along_axis(
            np.asarray(table), np.asarray(at) // BLOCK, 1) * 8 + inside % 8)
    np.testing.assert_array_equal(np.asarray(second), inside >= 8)
    np.testing.assert_array_equal(
        kv_cache_pool.gather_picked_latents(spec, pool, rows, second),
        np.take_along_axis(np.asarray(latent), np.asarray(at)[..., None], 1))


# --------------------------------------------------- the router's group limit


def test_route_with_groups_is_the_loop_written_out_by_hand():
    """8 groups of 4 of 32 experts, the best 3 groups by the sum of their two
    largest `s + b`, the 5 largest `s + b` within them; weights the picked
    sigmoid scores over their sum + eps, times the scale: token by token in
    numpy."""
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 32)) / 8, jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=32), jnp.float32)
    picked, w = moe_serve.route(h, router, bias, 5, True, 2.5, 1e-20,
                                n_group=8, topk_group=3)
    s = 1 / (1 + np.exp(-np.asarray(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST), np.float64)))
    for t in range(40):
        choose = s[t] + np.asarray(bias, np.float64)
        score = [np.sort(choose[g * 4:(g + 1) * 4])[-2:].sum()
                 for g in range(8)]
        groups = sorted(np.argsort(score)[-3:])
        inside = [e for g in groups for e in range(g * 4, (g + 1) * 4)]
        want = sorted(inside, key=lambda e: -choose[e])[:5]
        assert sorted(np.asarray(picked[t]).tolist()) == sorted(want)
        assert {e // 4 for e in np.asarray(picked[t]).tolist()} <= set(groups)
        weights = s[t][np.asarray(picked[t])]
        np.testing.assert_allclose(np.asarray(w[t]),
                                   weights / (weights.sum() + 1e-20) * 2.5,
                                   rtol=1e-5)
    # some token's unlimited top-5 reaches into a group the limit leaves out
    free, _ = moe_serve.route(h, router, bias, 5, True, 2.5, 1e-20)
    assert (np.sort(np.asarray(free), -1)
            != np.sort(np.asarray(picked), -1)).any()
    for bad in ({"n_group": 5}, {"n_group": 8, "topk_group": 9},
                {"n_group": 8, "topk_group": 0}):
        with pytest.raises(ValueError, match="groups"):
            moe_serve.route(h, router, bias, 5, True, 2.5, **bad)


def test_route_with_one_group_is_todays_route_to_the_bit():
    """`n_group` 1 takes no branch: the same jaxpr as a call that never
    heard of groups, so every other family's programs trace to what they
    were; and all groups kept picks what no limit picks."""
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(24, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=16), jnp.float32)
    a, b = (str(jax.make_jaxpr(f)(h, router, bias)) for f in (
        lambda h, r, b: moe_serve.route(h, r, b, 4, True, 1.8, 1e-20),
        lambda h, r, b: moe_serve.route(h, r, b, 4, True, 1.8, 1e-20,
                                        n_group=1, topk_group=1)))
    assert a == b and "scatter" not in a
    plain = moe_serve.route(h, router, bias, 4, True, 1.8, 1e-20)
    every = moe_serve.route(h, router, bias, 4, True, 1.8, 1e-20, n_group=4,
                            topk_group=4)
    for got, want in zip(every, plain):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
