"""Flagship model tests: dense vs paged serving-path equivalence, ring
attention vs dense attention, and the sharded train step.

Runs on the virtual 8-device CPU platform (conftest.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.ops.attention import causal_gqa_attention
from llm_d_kv_cache_manager_tpu.ops.ring_attention import ring_attention
from llm_d_kv_cache_manager_tpu.parallel.mesh import MeshPlan, make_mesh

CFG = llama.LlamaConfig(
    vocab_size=128,
    d_model=64,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=128,
    block_size=4,
    dtype="float32",
)


# Heads of 128: the only ones the flash kernel's continuation entry reads
# where the pool holds them (`flash_pallas.fits_paged`).
WIDE = dataclasses.replace(CFG, d_model=4 * 128)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def wide_params():
    return llama.init_params(jax.random.PRNGKey(0), WIDE)


def test_forward_shapes(params):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 128)
    logits = llama.forward(params, tokens, CFG)
    assert logits.shape == (2, 12, 128)
    assert bool(jnp.isfinite(logits).all())


def test_paged_prefill_matches_dense(params):
    B, T = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 128)
    nb = T // CFG.block_size
    kv_pool = jnp.zeros(
        (CFG.n_layers, 16, 2, CFG.block_size, CFG.n_kv_heads, CFG.head_dim),
        jnp.float32,
    )
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    paged_logits, kv_pool = llama.prefill_paged(
        params, tokens, kv_pool, table, CFG
    )
    dense_logits = llama.forward(params, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(paged_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )
    assert float(jnp.abs(kv_pool).sum()) > 0  # blocks were written


def test_prefill_continue_matches_dense(params):
    """Prefill a prefix, then continue with the suffix from the cached
    pool; suffix logits must match one dense pass over the whole
    prompt, and the suffix blocks must land in the pool."""
    B, T, P = 2, 12, 8
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, T), 0, 128)
    nb = T // CFG.block_size + 1  # one spare block for the decode step
    kv_pool = jnp.zeros(
        (CFG.n_layers, 16, 2, CFG.block_size, CFG.n_kv_heads, CFG.head_dim),
        jnp.float32,
    )
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    _, kv_pool = llama.prefill_paged(
        params, tokens[:, :P], kv_pool, table[:, : P // CFG.block_size], CFG
    )
    cont_logits, kv_pool = llama.prefill_continue(
        params, tokens[:, P:], kv_pool, table, P, CFG
    )
    dense_logits = llama.forward(params, tokens, CFG)[:, P:]
    np.testing.assert_allclose(
        np.asarray(cont_logits), np.asarray(dense_logits), rtol=2e-4, atol=2e-4
    )
    # Decode on top of the continued pool agrees with dense too.
    next_tok = jnp.argmax(cont_logits[:, -1], -1)
    ctx = jnp.full((B,), T + 1, jnp.int32)
    dec_logits, _ = llama.decode_step(
        params, next_tok, kv_pool, table, ctx, CFG
    )
    seq = jnp.concatenate([tokens, next_tok[:, None]], axis=1)
    dense_last = llama.forward(params, seq, CFG)[:, -1]
    np.testing.assert_allclose(
        np.asarray(dec_logits), np.asarray(dense_last), rtol=2e-4, atol=2e-4
    )


def test_paged_decode_matches_dense(params):
    """Prefill a prompt, decode a few tokens, check each decode logit
    equals the dense forward over the growing sequence."""
    B, T = 2, 8
    max_blocks = 4
    rng = jax.random.PRNGKey(3)
    tokens = jax.random.randint(rng, (B, T), 0, 128)
    kv_pool = jnp.zeros(
        (CFG.n_layers, 32, 2, CFG.block_size, CFG.n_kv_heads, CFG.head_dim),
        jnp.float32,
    )
    table = jnp.arange(B * max_blocks, dtype=jnp.int32).reshape(B, max_blocks)
    logits, kv_pool = llama.prefill_paged(
        params, tokens, kv_pool, table[:, : T // CFG.block_size], CFG
    )

    seq = tokens
    for step in range(3):
        next_tok = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits, -1)
        seq = jnp.concatenate([seq, next_tok[:, None]], axis=1)
        ctx = jnp.full((B,), seq.shape[1], jnp.int32)
        logits, kv_pool = llama.decode_step(
            params, next_tok, kv_pool, table, ctx, CFG
        )
        dense = llama.forward(params, seq, CFG)[:, -1]
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(dense), rtol=2e-4, atol=2e-4
        )


def test_prefill_chunked_matches_full(params):
    """Chunked prefill (bounded-memory long-prompt path, one compiled
    chunk step with dynamic q_offset) must write the same pool and
    produce the same last-position logits as the one-shot paged
    prefill, and decode must continue off its pool exactly."""
    B, T, C = 2, 32, 8
    tokens = jax.random.randint(
        jax.random.PRNGKey(15), (B, T), 0, CFG.vocab_size
    )
    nb = T // CFG.block_size
    pool = jnp.zeros(
        (
            CFG.n_layers,
            B * nb + B,
            2,
            CFG.block_size,
            CFG.n_kv_heads,
            CFG.head_dim,
        ),
        jnp.float32,
    )
    table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)

    full_logits, full_pool = llama.prefill_paged(
        params, tokens, pool, table, CFG
    )
    chunk_last, chunk_pool = llama.prefill_chunked(
        params, tokens, jnp.zeros_like(pool), table, CFG, chunk_tokens=C
    )
    np.testing.assert_allclose(
        np.asarray(chunk_last),
        np.asarray(full_logits[:, -1]),
        rtol=2e-4,
        atol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(chunk_pool), np.asarray(full_pool), rtol=2e-4, atol=2e-4
    )

    # Decode continues off the chunked pool exactly as off the dense
    # forward (the serving handoff).
    extra = jnp.arange(B, dtype=jnp.int32)[:, None] + B * nb
    table_d = jnp.concatenate([table, extra], axis=1)
    nxt = jnp.argmax(chunk_last, -1)
    seq = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    ctx = jnp.full((B,), T + 1, jnp.int32)
    logits, _ = llama.decode_step(
        params, nxt, chunk_pool, table_d, ctx, CFG
    )
    dense = llama.forward(params, seq, CFG)[:, -1]
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dense), rtol=2e-4, atol=2e-4
    )

    # Ragged lengths: prompts padded up to a chunk multiple must get
    # their logits at the TRUE last position, never a pad position —
    # and sequences ending in different chunks both resolve.
    seq_len = jnp.asarray([T - C - 3, T - 1], jnp.int32)
    ragged_last, _ = llama.prefill_chunked(
        params,
        tokens,
        jnp.zeros_like(pool),
        table,
        CFG,
        chunk_tokens=C,
        seq_len=seq_len,
    )
    for b in range(B):
        expect = llama.forward(
            params, tokens[b : b + 1, : int(seq_len[b])], CFG
        )[0, -1]
        np.testing.assert_allclose(
            np.asarray(ragged_last[b]),
            np.asarray(expect),
            rtol=2e-4,
            atol=2e-4,
            err_msg=f"sequence {b}",
        )


# ------------------------------------------------- the pool, carried in place
#
# The four paged programs carry the pool through their layer scan and
# write only the slots they name (llama._scan_layers).  What they must
# still compute is what the scan over (layers, pool) as xs -> ys
# computed: the logits of a dense pass, the new K/V in the table's
# slots of every layer, and every other slot of every layer untouched.

SEQ_T = 16  # tokens a sequence of the cases below (and one to decode)


def _dense_pass(params, cfg):
    """Two sequences of SEQ_T + 1 tokens with the logits [B, T, V] and
    every layer's K and V [L, B, T, Hkv, Dh] of a dense pass over them,
    by a plain loop over the layers: no pool, no scan."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(21), (2, SEQ_T + 1), 0, cfg.vocab_size
    )
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = jnp.take(params["embed"], tokens, axis=0)
    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = llama._rms_norm(x, lp["ln1"])
        q, k, v = llama._qkv(h, lp, positions, cfg.rope_theta)
        attn = causal_gqa_attention(q, k, v)
        x = x + jnp.einsum("bthk,hkd->btd", attn, lp["wo"])
        x = x + llama._mlp(llama._rms_norm(x, lp["ln2"]), lp)
        ks.append(k)
        vs.append(v)
    return (
        tokens,
        np.asarray(llama._logits(x, params)),
        np.asarray(jnp.stack(ks)),
        np.asarray(jnp.stack(vs)),
    )


@pytest.fixture(scope="module")
def dense_pass(params):
    return _dense_pass(params, CFG)


@pytest.fixture(scope="module")
def wide_dense_pass(wide_params):
    return _dense_pass(wide_params, WIDE)


def _write_positions(pool, ks, vs, table, first, last):
    """``pool`` (numpy, in place) with the K/V of positions
    [first[b], last[b]) of sequence b in the slots ``table`` names; the
    written mask is returned beside it."""
    written = np.zeros(pool.shape, bool)
    for b in range(table.shape[0]):
        for pos in range(first[b], last[b]):
            block, at = table[b, pos // CFG.block_size], pos % CFG.block_size
            pool[:, block, 0, at] = ks[:, b, pos]
            pool[:, block, 1, at] = vs[:, b, pos]
            written[:, block, :, at] = True
    return written


PAGED_CASES = [
    (program, attention, donate)
    for program, attention in (
        ("prefill_paged", "auto"),
        ("prefill_continue", "auto"),  # on the CPU: the gathered prefix
        ("prefill_continue", "interpreted"),  # the flash kernel's paged entry
        ("prefill_chunked", "auto"),
        ("decode_step", "auto"),  # on the CPU: the XLA gather
        ("decode_step", "interpreted"),  # the paged kernel, by the rule
    )
    for donate in (False, True)
]


@pytest.mark.parametrize(
    "program, attention, donate",
    PAGED_CASES,
    ids=["-".join((p, a, "donated" if d else "kept")) for p, a, d in PAGED_CASES],
)
def test_paged_programs_update_the_pool_in_place(
    request, program, attention, donate
):
    # The hit through the kernel's entry at a head size the entry reads.
    paged_hit = (program, attention) == ("prefill_continue", "interpreted")
    base = WIDE if paged_hit else CFG
    params = request.getfixturevalue("wide_params" if paged_hit else "params")
    tokens, dense, ks, vs = request.getfixturevalue(
        "wide_dense_pass" if paged_hit else "dense_pass"
    )
    B, T, P, bs = 2, SEQ_T, SEQ_T // 2, CFG.block_size
    pool_blocks = 24
    rng = np.random.default_rng(22)
    # Noise everywhere: a slot that the program should not touch shows
    # it if it does, in every layer.
    before = rng.standard_normal(
        (CFG.n_layers, pool_blocks, 2, bs, CFG.n_kv_heads, base.head_dim)
    ).astype(np.float32)
    # Block ids in no order, so that a layer offset applied to the
    # wrong operand cannot go unseen.
    table = rng.permutation(pool_blocks)[: B * (T // bs + 1)].reshape(
        B, T // bs + 1
    ).astype(np.int32)
    zero, whole = np.zeros(B, int), np.full(B, T)

    if program == "prefill_paged":
        args = (tokens[:, :T], table[:, : T // bs])
        call = lambda p, t, kv, bt: llama.prefill_paged(p, t, kv, bt, CFG)
        first, last, want = zero, whole, dense[:, :T]
    elif program == "prefill_chunked":
        args = (tokens[:, :T], table[:, : T // bs])
        call = lambda p, t, kv, bt: llama.prefill_chunked(
            p, t, kv, bt, CFG, chunk_tokens=P
        )
        first, last, want = zero, whole, dense[:, T - 1]
    elif program == "prefill_continue":
        _write_positions(before, ks, vs, table, zero, np.full(B, P))
        args = (tokens[:, P:T], table[:, : T // bs])
        # Interpreted, under a key bound that the 16 positions pass: the
        # kernel reads the table's blocks where the pool holds them.
        cfg = dataclasses.replace(
            base, flash_attention_min_len=T if paged_hit else 1024
        )
        call = lambda p, t, kv, bt: llama.prefill_continue(
            p, t, kv, bt, P, cfg, interpret=paged_hit
        )
        first, last, want = np.full(B, P), whole, dense[:, P:T]
    else:  # decode_step, ragged: sequence 1 is three tokens behind
        pos = np.array([T, T - 3])
        _write_positions(before, ks, vs, table, zero, pos)
        args = (
            tokens[np.arange(B), pos],
            table,
            jnp.asarray(pos + 1, jnp.int32),
        )
        call = lambda p, t, kv, bt, n: llama.decode_step(
            p, t, kv, bt, n, CFG, interpret=attention == "interpreted"
        )
        first, last, want = pos, pos + 1, dense[np.arange(B), pos]

    after = before.copy()
    written = _write_positions(after, ks, vs, table, first, last)
    kv_in = jnp.asarray(before)
    if paged_hit:
        traced = str(jax.make_jaxpr(call)(params, args[0], kv_in, *args[1:]))
        assert "flash_gqa_attention_pallas_paged" in traced
    logits, kv_out = jax.jit(call, donate_argnums=(2,) if donate else ())(
        params, args[0], kv_in, *args[1:]
    )
    np.testing.assert_allclose(np.asarray(logits), want, rtol=2e-4, atol=2e-4)
    kv_out = np.asarray(kv_out)
    assert kv_out.shape == before.shape and kv_out.dtype == before.dtype
    np.testing.assert_allclose(
        kv_out[written], after[written], rtol=1e-5, atol=1e-5
    )
    # Every slot that was not named, of every layer, bit for bit.
    np.testing.assert_array_equal(kv_out[~written], before[~written])
    if not donate:  # the caller's pool is still the caller's
        np.testing.assert_array_equal(np.asarray(kv_in), before)
    if program == "prefill_chunked":  # ... and against prefill_paged
        _, paged = llama.prefill_paged(
            params, *args[:1], jnp.asarray(before), *args[1:], CFG
        )
        np.testing.assert_allclose(
            kv_out, np.asarray(paged), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize(
    "decode_attention, interpret, backend, kernel",
    (
        ("auto", False, "cpu", False),  # here: the XLA gather
        ("auto", False, "tpu", True),  # compiled for the chip: the kernel
        ("auto", True, "cpu", True),  # asked to be interpreted: the kernel
        ("gather", False, "tpu", False),  # the one caller that asks
        ("gather", True, "cpu", False),  # (__graft_entry__'s tp decode)
    ),
)
def test_decode_attention_is_one_rule(
    params, monkeypatch, decode_attention, interpret, backend, kernel
):
    """`decode_step` takes the paged kernel where it is compiled for the
    TPU or interpreted, and the gather elsewhere or when asked."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = dataclasses.replace(CFG, decode_attention=decode_attention)
    pool = jnp.zeros(
        (CFG.n_layers, 8, 2, CFG.block_size, CFG.n_kv_heads, CFG.head_dim)
    )
    traced = jax.make_jaxpr(
        lambda p, kv: llama.decode_step(
            p, jnp.zeros((2,), jnp.int32), kv,
            jnp.arange(8, dtype=jnp.int32).reshape(2, 4),
            jnp.asarray([5, 9], jnp.int32), cfg, interpret=interpret,
        )
    )(params, pool)
    assert ("pallas_call" in str(traced)) == kernel


@pytest.mark.parametrize(
    "keys_bound, interpret, backend, kernel",
    (
        (16, False, "cpu", False),  # here: the gathered prefix, dense or scanned
        (16, False, "tpu", True),  # compiled for the chip: the kernel
        (16, True, "cpu", True),  # asked to be interpreted: the kernel
        (17, True, "cpu", False),  # fewer keys than the bound: one dense product
        (17, False, "tpu", False),
    ),
)
def test_hit_attention_is_one_rule(
    wide_params, monkeypatch, keys_bound, interpret, backend, kernel
):
    """`prefill_continue` attends in the flash kernel's continuation entry,
    over the table's blocks where the pool holds them, at or past
    `flash_attention_min_len` keys (prefix and suffix together: 8 + 8 here,
    however few the queries) where it is compiled for the TPU or
    interpreted; elsewhere over a gathered copy of the prefix."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = dataclasses.replace(WIDE, flash_attention_min_len=keys_bound)
    pool = jnp.zeros(
        (cfg.n_layers, 8, 2, cfg.block_size, cfg.n_kv_heads, cfg.head_dim)
    )
    traced = str(jax.make_jaxpr(
        lambda p, kv: llama.prefill_continue(
            p, jnp.zeros((1, 8), jnp.int32), kv,
            jnp.arange(4, dtype=jnp.int32)[None], 8, cfg,
            interpret=interpret,
        )
    )(wide_params, pool))
    assert ("flash_gqa_attention_pallas_paged" in traced) == kernel
    assert ("pallas_call" in traced) == kernel
    assert ("gather" in traced.split("scan[", 1)[1]) != kernel  # in the layers


@pytest.mark.parametrize("backend, interpret", (("tpu", False), ("cpu", True)))
@pytest.mark.parametrize("slots", ("blocks_of_6", "heads_of_16"))
def test_hit_gathers_for_slots_the_entry_has_no_room_for(
    request, monkeypatch, slots, backend, interpret
):
    """Where `flash_pallas.fits_paged` refuses the pool's slots (blocks of 6
    positions, which make up no step of the entry; heads of 16, which fill
    no lane tile), a hit past the key bound attends as it did before the
    entry: over the gathered prefix, through `_prefill_attention`'s rule
    (the flash kernel over resident K/V where a kernel serves), and gives
    the dense pass's rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if slots == "blocks_of_6":
        cfg = dataclasses.replace(WIDE, block_size=6)
        params = request.getfixturevalue("wide_params")
    else:
        cfg, params = CFG, request.getfixturevalue("params")
    bs = cfg.block_size
    cfg = dataclasses.replace(cfg, flash_attention_min_len=2 * bs)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, 2 * bs), 0, 128)
    pool = jnp.zeros((cfg.n_layers, 4, 2, bs, cfg.n_kv_heads, cfg.head_dim))
    table = jnp.asarray([[2, 0]], jnp.int32)
    hit = lambda p, t, kv: llama.prefill_continue(
        p, t, kv, table, bs, cfg, interpret=interpret
    )
    traced = str(jax.make_jaxpr(hit)(params, tokens[:, bs:], pool))
    assert "flash_gqa_attention_pallas_paged" not in traced
    assert "pallas_call" in traced  # the kernel over the gathered K/V
    assert "gather" in traced.split("scan[", 1)[1]
    if interpret:
        _, pool = llama.prefill_paged(
            params, tokens[:, :bs], pool, table[:, :1], cfg
        )
        got, _ = hit(params, tokens[:, bs:], pool)
        want = llama.forward(params, tokens, cfg, use_flash=False)[:, bs:]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )


def test_decode_attention_refuses_an_unknown_path(params):
    cfg = dataclasses.replace(CFG, decode_attention="pallas")
    with pytest.raises(ValueError, match="decode_attention"):
        llama.decode_step(params, None, None, None, None, cfg)


def test_ring_attention_matches_dense():
    mesh = make_mesh(MeshPlan(dp=2, sp=4))
    B, T, H, D = 2, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, D), jnp.float32)
    ring = ring_attention(q, k, v, mesh)
    dense = causal_gqa_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_ring_attention_gqa_heads():
    mesh = make_mesh(MeshPlan(dp=1, sp=4), devices=jax.devices()[:4])
    B, T, H, Hkv, D = 1, 8, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    ring = ring_attention(q, k, v, mesh, batch_axis=None)
    dense = causal_gqa_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_ring_attention_8way_long_sequence():
    """Full 8-device ring (sp=8): seven ppermute rotations, longer
    sequence than the ring width so each chunk carries several
    positions — the long-context prefill configuration."""
    mesh = make_mesh(MeshPlan(dp=1, sp=8))
    B, T, H, Hkv, D = 1, 128, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    ring = ring_attention(q, k, v, mesh, batch_axis=None)
    dense = causal_gqa_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_stripe_unstripe_roundtrip():
    from llm_d_kv_cache_manager_tpu.ops.ring_attention import (
        stripe,
        unstripe,
    )

    x = jnp.arange(2 * 24 * 3).reshape(2, 24, 3)
    for ring in (2, 4, 8):
        y = unstripe(stripe(x, ring), ring)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    # The layout really interleaves: chunk 0 of a ring-4 stripe holds
    # tokens 0, 4, 8, ...
    s = stripe(x, 4)
    np.testing.assert_array_equal(
        np.asarray(s[:, : 24 // 4]), np.asarray(x[:, ::4])
    )


def test_striped_ring_matches_dense():
    """The load-balanced layout must stay exact: stripe -> ring ->
    unstripe equals dense causal attention (8-way ring, GQA heads)."""
    mesh = make_mesh(MeshPlan(dp=1, sp=8))
    B, T, H, Hkv, D = 1, 64, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    ring = ring_attention(
        q, k, v, mesh, batch_axis=None, striped=True
    )
    dense = causal_gqa_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), rtol=1e-5, atol=1e-5
    )


def test_flash_ring_matches_dense_both_layouts():
    """The mask-aware flash body (ops/ring_flash_pallas.py, interpret
    mode on CPU) must be exact in BOTH layouts: its per-step partials
    stop at the causal diagonal (striped) or skip fully-masked steps
    (contiguous), and the log-sum-exp merge reassembles the full
    softmax."""
    mesh = make_mesh(MeshPlan(dp=1, sp=8))
    B, T, H, Hkv, D = 1, 64, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    dense = causal_gqa_attention(q, k, v)
    for striped in (False, True):
        ring = ring_attention(
            q, k, v, mesh,
            batch_axis=None,
            striped=striped,
            impl="flash",
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(ring),
            np.asarray(dense),
            rtol=1e-5,
            atol=1e-5,
            err_msg=f"striped={striped}",
        )


def test_flash_partial_merge_is_flash_attention():
    """Splitting K/V in two, computing flash partials, and merging must
    equal one full-softmax pass (the flash-decoding identity the ring
    steps rely on)."""
    from llm_d_kv_cache_manager_tpu.ops.ring_flash_pallas import (
        flash_partial,
        merge_partials,
        neutral_partial,
        normalize_partial,
    )

    B, T, H, Hkv, D = 1, 32, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, Hkv, D), jnp.float32)
    half = T // 2
    state = merge_partials(
        neutral_partial(q),
        flash_partial(
            q, k[:, :half], v[:, :half],
            causal_offset=None, interpret=True,
        ),
    )
    state = merge_partials(
        state,
        flash_partial(
            q, k[:, half:], v[:, half:],
            causal_offset=None, interpret=True,
        ),
    )
    acc, _, l = state
    merged = normalize_partial(acc, l, q.dtype)
    full = flash_partial(q, k, v, causal_offset=None, interpret=True)
    expected = normalize_partial(full[0], full[2], q.dtype)
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_forward_striped_flash_ring_matches_dense():
    """forward(sp_mesh=..., ring_striped=True, ring_impl="flash") —
    the VERDICT-r4 'striped is unreachable from the model' gap — must
    match the plain dense forward: stripe at entry, balanced flash
    ring per layer, unstripe before logits."""
    cfg = llama.LlamaConfig(
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=176,
        dtype="float32",
    )
    params = llama.init_params(jax.random.PRNGKey(23), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(24), (2, 32), 0, 256)
    mesh = make_mesh(MeshPlan(dp=1, sp=8))
    base = llama.forward(params, tokens, cfg)
    for kwargs in (
        dict(ring_striped=True),
        dict(ring_striped=True, ring_impl="flash", ring_interpret=True),
    ):
        out = llama.forward(params, tokens, cfg, sp_mesh=mesh, **kwargs)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(base),
            rtol=5e-4,
            atol=5e-4,
            err_msg=str(kwargs),
        )


def test_ring_attention_bf16_serving_dtype():
    """bf16 inputs (the serving dtype): accumulators are f32 inside, so
    the ring must agree with a dense f32 reference within bf16
    round-off."""
    mesh = make_mesh(MeshPlan(dp=2, sp=4))
    B, T, H, D = 2, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q32 = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k32 = jax.random.normal(ks[1], (B, T, H, D), jnp.float32)
    v32 = jax.random.normal(ks[2], (B, T, H, D), jnp.float32)
    ring = ring_attention(
        q32.astype(jnp.bfloat16),
        k32.astype(jnp.bfloat16),
        v32.astype(jnp.bfloat16),
        mesh,
    )
    assert ring.dtype == jnp.bfloat16
    dense = causal_gqa_attention(q32, k32, v32)
    np.testing.assert_allclose(
        np.asarray(ring, np.float32),
        np.asarray(dense),
        rtol=0.05,
        atol=0.05,
    )


def test_forward_sp_mesh_matches_dense(params):
    """The wired long-context path: forward(sp_mesh=...) runs every
    layer's attention as a ring over sp and must agree with the plain
    dense forward."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshPlan(dp=2, sp=4))
    B, T = 2, 32
    tokens = jax.random.randint(jax.random.PRNGKey(9), (B, T), 0, CFG.vocab_size)
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P("dp", "sp"))
    )
    ring_logits = jax.jit(
        lambda p, t: llama.forward(p, t, CFG, sp_mesh=mesh)
    )(params, tokens_sharded)
    dense = llama.forward(params, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(ring_logits),
        np.asarray(dense),
        rtol=2e-4,
        atol=2e-4,
    )


def test_forward_sp_tp_mesh_matches_dense(params):
    """tp x sp composition: params head-sharded over tp, sequence over
    sp — the ring runs per head-shard (no per-layer all-gather of
    q/k/v) and must still match the dense forward."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshPlan(dp=1, tp=2, sp=2), jax.devices()[:4])
    pspecs = llama.param_pspecs(CFG)
    sharded = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(10), (2, 32), 0, CFG.vocab_size
    )
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P(None, "sp"))
    )
    ring_logits = jax.jit(
        lambda p, t: llama.forward(p, t, CFG, sp_mesh=mesh)
    )(sharded, tokens_sharded)
    dense = llama.forward(params, tokens, CFG)
    np.testing.assert_allclose(
        np.asarray(ring_logits),
        np.asarray(dense),
        rtol=2e-4,
        atol=2e-4,
    )


def test_forward_sp_tp_mesh_flash_striped_matches_dense(params):
    """The tp x sp composition must hold for the mask-aware flash body
    too: per head-shard the partial kernel sees H/tp q-heads and
    Hkv/tp kv-heads (GQA group count preserved), the striped layout
    rides the same sp sharding, and the result still matches dense."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshPlan(dp=1, tp=2, sp=2), jax.devices()[:4])
    pspecs = llama.param_pspecs(CFG)
    sharded = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(10), (2, 32), 0, CFG.vocab_size
    )
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P(None, "sp"))
    )
    dense = llama.forward(params, tokens, CFG)
    for striped in (False, True):
        ring_logits = jax.jit(
            lambda p, t, s=striped: llama.forward(
                p,
                t,
                CFG,
                sp_mesh=mesh,
                ring_striped=s,
                ring_impl="flash",
                ring_interpret=True,
            )
        )(sharded, tokens_sharded)
        np.testing.assert_allclose(
            np.asarray(ring_logits),
            np.asarray(dense),
            rtol=2e-4,
            atol=2e-4,
            err_msg=f"striped={striped}",
        )


def test_sharded_train_step_params_stay_finite(params):
    """Regression: under combined sp x tp sharding, the old
    slice-to-[B, T-1] loss made XLA pad the short sequence shard and
    the padded-lane softmax backward wrote NaN into the target token's
    embedding row — invisible to the loss (computed pre-update).  Every
    post-step param must be finite, and the sharded loss must equal the
    unsharded one."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(MeshPlan(dp=1, tp=2, sp=2), jax.devices()[:4])
    pspecs = llama.param_pspecs(CFG)
    sharded = jax.device_put(
        params, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    )
    optimizer = llama.make_optimizer()
    opt_state = optimizer.init(sharded)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, CFG.vocab_size)
    tokens_sharded = jax.device_put(
        tokens, NamedSharding(mesh, P("dp", "sp"))
    )
    step = jax.jit(
        lambda p, o, t: llama.train_step(p, o, t, CFG, optimizer)
    )
    new_params, _, loss = step(sharded, opt_state, tokens_sharded)
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    unsharded_loss = llama.loss_fn(params, tokens, CFG)
    np.testing.assert_allclose(
        float(loss), float(unsharded_loss), rtol=1e-5
    )


def test_train_step_runs_and_improves(params):
    optimizer = llama.make_optimizer(1e-2)
    p = jax.tree.map(lambda x: x, params)
    opt_state = optimizer.init(p)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 16), 0, 128)
    first = None
    for _ in range(5):
        p, opt_state, loss = llama.train_step(
            p, opt_state, tokens, CFG, optimizer
        )
        first = first if first is not None else float(loss)
    assert float(loss) < first
