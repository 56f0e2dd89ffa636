"""The `phi4flash` family on the pod path (models/phi4flash.py) and the pod's
cache with three groups (models/pod.py: full, window and state), at a small
size on the CPU: eight layers (two Mamba/window pairs, a Mamba and a full
attention layer, one memory-unit/cross pair), hidden 64, head size 16 (pairs of
32), window 32, block 16, a snapshot every second block.

The comparisons run the program in float32, where it has to repeat the plain
reference to rounding (2e-4 of the largest logit: the sums run in another
order); that the serving precision stays near it is the chip check's business
(benchmarks/harness/family_phi4flash.py).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import afmoe, layers, phi4flash
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

BLOCK, VOCAB = 16, 128
CFG = phi4flash.Phi4FlashConfig(
    dtype="float32", vocab_size=VOCAB, window=32, window_slots=32,
    window_store_blocks=6, state_slots=24, state_stride_blocks=2)
PARAMS = phi4flash.init_params(jax.random.key(0), CFG)
PUBLISHED = {
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_norm_eps": 1e-5, "mb_per_layer": 2, "num_attention_heads": 4,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "sliding_window": 32,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": VOCAB, "torch_dtype": "float32", "mamba_d_state": 4,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "serving": {"window_slots": 32, "window_store_blocks": 6,
                "state_slots": 24, "state_stride_blocks": 2},
}


def steps_of(cfg, interpret=False):
    return {
        "miss": jax.jit(functools.partial(phi4flash.prefill_paged, cfg=cfg,
                                          interpret=interpret)),
        "hit": jax.jit(functools.partial(phi4flash.prefill_continue, cfg=cfg,
                                         interpret=interpret),
                       static_argnames=("prefix_len",)),
        "decode": jax.jit(functools.partial(phi4flash.decode_step, cfg=cfg,
                                            interpret=interpret)),
    }


STEPS = steps_of(CFG)


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


_reference = jax.jit(functools.partial(phi4flash.reference_logits, cfg=CFG))


@functools.cache
def reference(tokens: tuple) -> np.ndarray:
    """Logits [T, V] of the whole sequence (causal: row t is what a step that
    was fed token t has to give)."""
    return np.asarray(_reference(PARAMS, jnp.asarray(tokens)))


class Engine:
    """What the benchmark's engine does around a pod, call for call
    (`Fleet.account`, `run_chat.admit`, `commit`, `finish`), with the model
    steps run directly so that a test sees whole rows of logits."""

    def __init__(self, pool_blocks: int = 64, cfg=CFG, steps=STEPS) -> None:
        self.cfg, self.steps = cfg, steps
        self.pod = Pod("pod-0", phi4flash, cfg, pool_blocks)
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
                PARAMS, ids, pod.kv.arrays, tables,
                prefix_len=first_new * BLOCK)
        else:
            logits, arrays = self.steps["miss"](PARAMS, ids, pod.kv.arrays,
                                                tables)
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
            PARAMS, cur, self.pod.kv.arrays, tables, ctx)
        arrays.pop("attention_read", None)
        self.pod.kv.arrays = arrays
        return np.asarray(logits)

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])

    def poison(self, keep=()) -> None:
        """NaN into every state slot that is free, or held by a block that
        is neither cached nor about to be read: a step that read a state the
        rules do not keep would show it.  (K/V slots are not poisoned: a
        position past the context is masked by a weight of zero, and zero
        times NaN is NaN.)"""
        pod, base = self.pod, self.cfg.state_slots
        block = np.maximum(pod.state.block_of, 0)
        dead = (pod.state.block_of < 0) | ~pod.hashed[block]
        dead[np.asarray(keep, np.int64)] = False
        layers = np.arange(self.cfg.n_front + 1)[:, None] * base
        slots = jnp.asarray((layers + np.flatnonzero(dead)[None]).ravel())
        pod.kv.arrays["state"] = [a.at[slots].set(jnp.nan)
                                  for a in pod.kv.arrays["state"]]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("blocks", (2, 6))
def test_miss_prefill_gives_the_references_last_row(blocks):
    """The lower decoder runs on the last position alone (layers 6 and 7
    here) and the row is the reference's, which runs every layer over every
    position; snapshots stand after every second block and the call's last,
    window slots under the trailing `window_store_blocks`."""
    eng = Engine()
    seq = eng.prefill(tokens_of(blocks * BLOCK, 1), 0)
    close(seq["row"], reference(tuple(seq["tokens"]))[-1])
    pod = eng.pod
    assert [int(pod.state.slot_of[b] >= 0) for b in seq["blocks"]] == [
        0, 1, 0, 1, 0, 1][:blocks]
    assert all(pod.window.slot_of[b] >= 0 for b in seq["blocks"])


def test_prefill_keeps_the_first_mamba_layers_state_as_the_equations_give_it():
    eng = Engine()
    seq = eng.prefill(tokens_of(64, 1), 0)
    lp = jax.tree.map(lambda a: np.asarray(a[0], np.float64),
                      PARAMS["front"]["a"])
    x = np.asarray(PARAMS["embed"], np.float64)[np.asarray(seq["tokens"])]
    mean = x.mean(-1, keepdims=True)
    h = (x - mean) / np.sqrt(((x - mean) ** 2).mean(-1, keepdims=True)
                             + CFG.ln_eps) * lp["ln_in"]["w"] + lp["ln_in"]["b"]
    u = (h @ lp["w_in"])[:, :CFG.d_inner]
    up = np.concatenate((np.zeros((3, u.shape[1])), u))
    conv = sum(lp["conv_k"][:, j] * up[j:j + len(u)] for j in range(4))
    conv = conv + lp["conv_b"]
    c = conv / (1 + np.exp(-conv))
    dbc = c @ lp["w_x"]
    R, N = CFG.dt_rank, CFG.d_state
    delta = np.log1p(np.exp(dbc[:, :R] @ lp["w_dt"] + lp["b_dt"]))
    s = np.zeros((N, u.shape[1]))
    for i in (1, 3):
        slot = eng.pod.state.slot_of[seq["blocks"][i]]
        end = (i + 1) * BLOCK
        for t in range(end - 2 * BLOCK if i == 3 else 0, end):
            s = (np.exp(delta[t][None] * -np.exp(lp["a_log"])) * s
                 + (delta[t] * c[t])[None] * dbc[t, R:R + N][:, None])
        conv_pool, ssm_pool = eng.pod.kv.arrays["state"]
        close(np.asarray(ssm_pool[slot]), s, 1e-4)
        close(np.asarray(conv_pool[slot]).reshape(3, -1), u[end - 3:end], 1e-5)


@pytest.mark.parametrize("prefix_blocks, resumed", ((4, 4), (3, 0), (5, 0)))
def test_continue_from_a_snapshot_repeats_the_references_full_pass(
        prefix_blocks, resumed):
    """A six-block prompt is stored (snapshots after blocks 1, 3, 5).  A
    prompt that shares its first four blocks continues from the snapshot of
    block 3 (and the window slots of blocks 2 and 3) and gives the
    reference's logits.  One that shares three, or five, ends beside a kept
    boundary: `cached_prefix` falls back to the last kept one (two, or four,
    blocks), which is not the whole prefix, so the engine recomputes it all,
    and `resume_short_blocks` counts the block given up."""
    eng = Engine()
    doc = tokens_of(96, 1)
    eng.prefill(doc, 0)
    turn = tokens_of(32, 2)
    second = eng.prefill(np.concatenate((doc[:prefix_blocks * BLOCK], turn)),
                         prefix_blocks)
    assert second["hit"] == bool(resumed)
    assert second["cached"] == (resumed or prefix_blocks - 1)
    close(second["row"], reference(tuple(second["tokens"]))[-1])
    assert eng.pod.state.counts["resume_short_blocks"] == (0 if resumed else 1)
    assert eng.pod.window.counts["half_hits"] == 0


@pytest.mark.parametrize("interpret", (False, True), ids=("xla", "interpret"))
def test_decode_over_forty_steps_repeats_the_reference(interpret):
    """Two sequences of one shared prompt, 44 steps through the cache: across
    block boundaries (positions 96, 112, 128), past the window's edge (a
    window slot is released), past a stride boundary, each step's logits
    against the reference's row of the whole sequence.  Interpreted, the step
    takes the paged kernel and its shared pass over the full group."""
    eng = Engine(steps=steps_of(CFG, interpret) if interpret else STEPS)
    doc = tokens_of(64, 1)
    first = eng.prefill(np.concatenate((doc, tokens_of(32, 2))), 0, own=3)
    second = eng.prefill(np.concatenate((doc, tokens_of(16, 3))), 4, own=4)
    assert second["hit"]
    seqs = [first, second]
    for s in seqs:
        s["tokens"].append(int(reference(tuple(s["tokens"]))[-1].argmax()))
    rows = []
    for _ in range(44):
        logits = eng.decode(seqs)
        rows.append(logits)
        for s, row in zip(seqs, logits):
            s["tokens"].append(int(row.argmax()))
    for i, s in enumerate(seqs):
        want = reference(tuple(s["tokens"][:-1]))
        for t, row in enumerate(rows):
            close(row[i], want[len(want) - 44 + t])
    assert eng.pod.window.counts["released"] > 0
    assert eng.pod.state.counts["released"] > 0


def test_pairwise_heads_are_the_four_products_as_written():
    """`_queries`, `_keys_values` and `_attn_out` around one grouped attention
    of four query heads a pair-wise KV head, against Att(q1, k1, v1),
    Att(q1, k1, v2), Att(q2, k2, v1), Att(q2, k2, v2) by dense softmax."""
    cfg = dataclasses.replace(CFG, n_heads=8, n_kv_heads=4, d_model=128)
    lp = jax.tree.map(
        lambda a: a[0],
        phi4flash.init_params(jax.random.key(3), cfg)["front"]["b"])
    T, H, Hkv, d = 24, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = jax.random.normal(jax.random.key(4), (1, T, cfg.d_model), jnp.float32)
    lam0 = float(cfg.lam0(5))
    k, v = phi4flash._keys_values(h, lp, cfg)
    attn = layers.dense_attention(phi4flash._queries(h, lp, cfg), k, v, 0, None)
    got = np.asarray(phi4flash._attn_out(attn, lp, lam0))[0]

    hp = jax.lax.Precision.HIGHEST
    def heads(w, b, n):
        return (jnp.einsum("td,de->te", h[0], w, precision=hp) + b).reshape(T, n, d)

    q = heads(lp["wq"], lp["bq"], H)
    kk, vv = heads(lp["wk"], lp["bk"], Hkv), heads(lp["wv"], lp["bv"], Hkv)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def att(q, k, v):
        k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
        s = jnp.einsum("qhk,thk->hqt", q, k, precision=hp) * d**-0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=hp)

    q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], kk[:, 0::2], kk[:, 1::2]
    v1, v2 = vv[:, 0::2], vv[:, 1::2]
    o1 = jnp.concatenate((att(q1, k1, v1), att(q1, k1, v2)), -1)
    o2 = jnp.concatenate((att(q2, k2, v1), att(q2, k2, v2)), -1)
    lam = (jnp.exp(jnp.sum(lp["lam"][0] * lp["lam"][1]))
           - jnp.exp(jnp.sum(lp["lam"][2] * lp["lam"][3])) + lam0)
    diff = o1 - lam * o2
    diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True) + 1e-5)
    diff = (diff * lp["sub_norm"] * (1 - lam0)).reshape(T, H * d)
    want = jnp.einsum("te,ed->td", diff, lp["wo"], precision=hp) + lp["bo"]
    assert k.shape == (1, T, Hkv // 2, 2 * d)
    close(got, np.asarray(want), 1e-5)


def test_the_three_programs_serve_the_reference_tokens_and_report_spans():
    """`jit_programs`: every shape compiles at the first call of any; a decode
    call packs both groups' integers into one host argument and records both
    groups' spans, with the full group's readers."""
    shapes = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(phi4flash, CFG, shapes, interpret=True)
    pod = Pod("pod-0", phi4flash, CFG, 40)
    doc = tokens_of(64, 1)
    prompts = [np.concatenate((doc, tokens_of(32, 3))),
               np.concatenate((doc, tokens_of(32, 2)))]
    ids, _ = pod.alloc(6)
    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        out, row, kv = programs["miss"](
            PARAMS, prompts[0][None], pod.kv, np.asarray(ids)[None])
        assert kv is pod.kv
        close(np.asarray(row), reference(tuple(prompts[0]))[-1])
        more, _ = pod.alloc(2)
        out, row, kv = programs["hit"](
            PARAMS, prompts[1][None, 64:], pod.kv,
            np.asarray(ids[:4] + more)[None])
        close(np.asarray(row), reference(tuple(prompts[1]))[-1])
        table = np.zeros((2, 9), np.int32)
        table[0, :6], table[1, :6] = ids, ids[:4] + more
        own, _ = pod.alloc(2)
        table[0, 6], table[1, 6] = own
        nxt = np.asarray([reference(tuple(p))[-1].argmax() for p in prompts])
        for _ in range(2):
            out, kv = programs["decode"](
                PARAMS, nxt, pod.kv, table, np.asarray([97, 97]))
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        assert [int(t) for t in np.asarray(out)[0]] == [
            reference(s)[-1].argmax() for s in seqs]
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0)
    assert not dropped
    spans = {}
    for r in rows:
        if r.get("span"):
            spans.setdefault(r["span"], []).append(r.get("attrs", {}))
    assert {r["trace"] for r in rows if r["span"] is None} == {"pod.step"}
    assert len(spans["kvpool.window"]) == len(spans["kvpool.state"]) == 4
    assert len(spans["pod.compile"]) == 3 and len(spans["pod.pack"]) == 2
    assert [len(spans[f"pod.launch.{kind}"])
            for kind in ("miss", "hit", "decode")] == [1, 1, 2]
    assert [a["arrays"] for a in spans["pod.counts_read"]] == [1]
    read = spans["kv.read"][-1]
    assert read["full_readers"] == 2 == phi4flash.cache_groups(
        CFG)["full"].num_readers
    assert read["full_read_blocks"] == 2 * read["full_blocks"] == 2 * 14
    assert read["window_blocks"] == 2 * 3
    state = spans["state.read"][-1]
    groups = phi4flash.cache_groups(CFG)
    assert state["state_bytes"] == 2 * 2 * groups["state"].block_nbytes
    assert state["kv_bytes"] == (14 * 2 * groups["full"].block_nbytes
                                 + 6 * groups["window"].block_nbytes)
    # the second decode call read what the first counted on the device: the
    # shared prompt's four blocks once, each sequence's other three
    assert spans["attention.read"][-1] == {"read_blocks": 4 + 2 * 3,
                                           "walked_blocks": 14,
                                           "run_blocks": 0,
                                           "shared_run_blocks": 0}


# ---------------------------------------------------- the cache's three groups


def stored(eng: Engine, n: int = 6, key: int = 1) -> dict:
    """A prompt of n blocks, stored and no longer referenced."""
    seq = eng.prefill(tokens_of(n * BLOCK, key), 0)
    eng.finish(seq)
    return seq


@pytest.mark.parametrize("case, asked, served, short, half", (
    ("whole", 6, 6, 0, 0),
    ("whole", 4, 4, 0, 0),
    ("full short", 6, 4, 0, 0),  # block 4's hash is gone: four found, four served
    ("window short", 4, 2, 2, 1),  # block 3 holds no window slot
    ("snapshot missing", 4, 2, 2, 0),  # block 3 holds no snapshot
    ("window short", 6, 6, 0, 0),  # ... which a longer prefix does not need
    ("both short", 6, 0, 6, 1),  # no boundary is left that both groups admit
))
def test_hit_rule_is_met_by_all_three_groups_at_one_length(
        case, asked, served, short, half):
    """Six blocks stored: snapshots after blocks 1, 3, 5, window slots under
    all six, the window rule wants a prefix's last two."""
    eng = Engine()
    seq = stored(eng)
    pod, blocks = eng.pod, seq["blocks"]
    if case == "full short":
        del pod.cached[seq["hashes"][4]]
    if case in ("window short", "both short"):
        pod.window.drop(blocks[3])
    if case == "snapshot missing":
        pod.state.drop(blocks[3])
    if case == "both short":
        pod.window.drop(blocks[5])
        pod.window.drop(blocks[0])
    ids = pod.cached_prefix(seq["hashes"][:asked])
    assert ids == blocks[:served]
    found = 4 if case == "full short" else asked
    assert pod.state.counts["asked_blocks"] == asked
    assert pod.state.counts["resume_short_blocks"] == (
        found - served if case == "full short" else short)
    assert pod.window.counts["half_hits"] == half


@pytest.mark.parametrize("group", ("window", "state"))
def test_a_reused_slot_takes_the_chains_tail_out_of_the_full_group(group):
    """Where the window group, or the state group, reuses the slot of a block
    that is still cached, the chain's tail from there leaves the full group
    too, once, and its hashes ride in `alloc`'s list: the index hears
    `BlockRemoved` for exactly what is no longer servable."""
    cfg = dataclasses.replace(
        CFG, **({"window_slots": 14} if group == "window"
                else {"state_slots": 7}))
    eng = Engine(cfg=cfg, steps=steps_of(cfg))
    first = stored(eng, key=1)
    stored(eng, key=2)
    assert eng.removed == []  # 12 window slots, 6 snapshots: all fit
    third = stored(eng, key=3)  # a prefill's slots are taken at its tables ...
    assert third["evicted"] == []
    fourth = stored(eng, key=4)  # ... what they evicted rides in the next list
    pod = eng.pod
    gone = [h for h in first["hashes"] if h not in pod.cached]
    assert gone and gone == first["hashes"][-len(gone):]  # a tail, no hole
    assert set(gone) <= set(fourth["evicted"])
    assert len(eng.removed) == len(set(eng.removed))  # each hash once
    kept = len(first["hashes"]) - len(gone)
    want = pod.cached_prefix(first["hashes"])
    assert len(want) <= kept and all(h in pod.cached for h in third["hashes"])


def test_a_lazy_window_group_gives_an_answers_blocks_no_slot_until_they_are_read():
    """An answer's blocks are handed out at admission; with `lazy` they take
    window slots as decode steps reach them, and give them back behind the
    window."""
    eng = Engine()
    seq = eng.prefill(tokens_of(64, 1), 0, own=4)
    group = eng.pod.window
    assert all(group.slot_of[b] < 0 for b in seq["own"])
    taken = group.counts["taken"]
    seq["tokens"].append(int(seq["row"].argmax()))
    eng.decode([seq])
    assert group.slot_of[seq["own"][0]] >= 0 > group.slot_of[seq["own"][1]]
    assert group.counts["taken"] == taken + 1


def test_a_live_sequences_slots_are_never_reused_and_exhaustion_is_an_error():
    cfg = dataclasses.replace(CFG, window_slots=6)
    eng = Engine(cfg=cfg, steps=steps_of(cfg))
    eng.prefill(tokens_of(96, 1), 0)  # live: its six blocks hold six slots
    with pytest.raises(RuntimeError, match="window group exhausted"):
        eng.prefill(tokens_of(96, 2), 0)


def test_one_group_alone_leaves_the_pod_what_it_was():
    """`afmoe`'s pod has a window group and no other; the list of groups is
    that one, and its slots are taken at `alloc` as they were."""
    cfg = afmoe.AfmoeConfig(dtype="float32", vocab_size=VOCAB, window_slots=24,
                            window_store_blocks=4)
    pod = Pod("p", afmoe, cfg, 40)
    assert pod.groups == [pod.window] and pod.state is None
    assert not pod.window.lazy and pod.specs["full"].readers is None
    ids, _ = pod.alloc(6)
    assert [int(pod.window.slot_of[b] >= 0) for b in ids] == [0, 0, 1, 1, 1, 1]


def test_block_bytes_and_pool_shapes_come_from_one_spec_per_group():
    groups = phi4flash.cache_groups(CFG)
    full, window, state = groups["full"], groups["window"], groups["state"]
    pair_bytes = 2 * BLOCK * 1 * 32 * 4  # K and V, one pair of heads, float32
    assert full.block_nbytes == pair_bytes and full.num_readers == 2
    assert window.block_nbytes == 2 * pair_bytes and window.num_readers == 2
    Di, N = CFG.d_inner, CFG.d_state
    assert state.state_parts == (((3 * Di,), "float32"), ((N, Di), "float32"))
    assert state.block_nbytes == 3 * (3 * Di + N * Di) * 4
    pools = phi4flash.new_pool(CFG, 40)
    assert [a.shape for a in pools["full"]] == [(40, 2, BLOCK, 32)]
    assert [a.shape for a in pools["window"]] == [(2 * 32, 2, BLOCK, 32)]
    assert [(a.shape, a.dtype) for a in pools["state"]] == [
        ((3 * 24, 3 * Di), jnp.float32), ((3 * 24, N, Di), jnp.float32)]
    serving = dataclasses.replace(CFG, dtype="bfloat16")
    assert phi4flash.cache_groups(serving)["state"].block_nbytes == 3 * (
        3 * Di * 2 + N * Di * 4)
    assert full.read_nbytes == 2 * pair_bytes  # one layer's K/V, two readers
    assert window.read_nbytes == window.block_nbytes
    policy = phi4flash.cache_policy(CFG)
    assert policy["specs"] == groups and policy["window"]["lazy"]


def test_from_published_reads_the_keys():
    assert phi4flash.from_published(PUBLISHED, BLOCK) == CFG


@pytest.mark.parametrize("key, value, match", (
    ("mb_per_layer", 4, "mb_per_layer"),
    ("num_hidden_layers", 10, "multiple of 4"),
    ("hidden_act", "gelu", "hidden_act"),
    ("mlp_bias", True, "mlp_bias"),
    ("lm_head_bias", True, "lm_head_bias"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
    ("num_key_value_heads", 4, "pairs the heads"),
))
def test_from_published_refuses_what_is_not_implemented(key, value, match):
    with pytest.raises(ValueError, match=match):
        phi4flash.from_published({**PUBLISHED, key: value}, BLOCK)


def test_a_float8_pass_fails_the_tolerance_the_comparisons_hold():
    tokens = tokens_of(96, 9)
    want = reference(tuple(tokens))

    def q(a):
        scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    low = np.asarray(phi4flash.reference_logits(
        jax.tree.map(lambda a: q(a) if a.ndim > 1 else a, PARAMS), tokens, CFG))
    err8 = np.linalg.norm(low - want) / np.linalg.norm(want)
    seq = Engine().prefill(tokens, 0)
    err = np.linalg.norm(seq["row"] - want[-1]) / np.linalg.norm(want[-1])
    assert err < 2e-4 < 100 * 2e-4 < err8
