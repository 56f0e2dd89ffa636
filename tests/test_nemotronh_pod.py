"""The `nemotronh` family on the pod path (models/nemotronh.py) and the pod's
cache with a state group whose slot is a matrix a head (models/pod.py), at a
small size on the CPU: seven layers ``MEM*EME`` (three Mamba-2, three expert
layers of which this chip holds experts 0-3 of 8, one attention layer), hidden
64, four Mamba-2 heads of 8 with a state [8, 16] each in two groups, block 16,
a chunk of the scan 32 positions, a snapshot every second block.

The comparisons run the program in float32, where it has to repeat the plain
reference to rounding (2e-4 of the largest logit: the chunk form, the paged
kernels and the batched experts sum in another order); that the serving
precision stays near it is the chip check's business
(benchmarks/harness/family_nemotronh.py).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import moe_serve, nemotronh
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER
from llm_d_kv_cache_manager_tpu.ops import ssd_pallas

BLOCK, VOCAB = 16, 128
CFG = nemotronh.NemotronHConfig(
    dtype="float32", vocab_size=VOCAB, pattern="MEM*EME", held=(0, 4),
    state_slots=24, state_stride_blocks=2)
PARAMS = nemotronh.init_params(jax.random.key(0), CFG)
PUBLISHED = {
    "attention_bias": False, "chunk_size": 32, "conv_kernel": 4,
    "head_dim": 16, "hidden_size": 64, "hybrid_override_pattern": "MEM*EME",
    "intermediate_size": 32, "layer_norm_epsilon": 1e-5, "mamba_head_dim": 8,
    "mamba_hidden_act": "silu", "mamba_num_heads": 4, "mamba_proj_bias": False,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "n_group": 1, "n_groups": 2, "n_routed_experts": 4, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 7,
    "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
    "ssm_state_size": 16, "tie_word_embeddings": False, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "vocab_size": VOCAB,
    "torch_dtype": "float32", "published": {"n_routed_experts": 8},
    "held": {"experts_first": 0},
    "serving": {"state_slots": 24, "state_stride_blocks": 2},
}


def steps_of(cfg, interpret=False):
    return {
        "miss": jax.jit(functools.partial(nemotronh.prefill_paged, cfg=cfg,
                                          interpret=interpret)),
        "hit": jax.jit(functools.partial(nemotronh.prefill_continue, cfg=cfg,
                                         interpret=interpret),
                       static_argnames=("prefix_len",)),
        "decode": jax.jit(functools.partial(nemotronh.decode_step, cfg=cfg,
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


_reference = jax.jit(functools.partial(nemotronh.reference_logits, cfg=CFG))


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
        self.pod = Pod("pod-0", nemotronh, cfg, pool_blocks)
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
        arrays.pop("load", None)
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
        self.slots = tables["state"]
        self.poison(keep=tables["state"][:, 0])
        logits, arrays = self.steps["decode"](
            PARAMS, cur, self.pod.kv.arrays, tables, ctx)
        self.load = np.asarray(arrays.pop("load"))
        arrays.pop("attention_read", None)
        self.pod.kv.arrays = arrays
        return np.asarray(logits)

    def finish(self, seq: dict) -> None:
        self.pod.hold(seq["blocks"], -1)
        self.pod.free.extend(seq["own"])

    def poison(self, keep=()) -> None:
        """NaN into every state slot that is free, or held by a block that
        is neither cached nor about to be read: a step that read a state the
        rules do not keep would show it."""
        pod = self.pod
        block = np.maximum(pod.state.block_of, 0)
        dead = (pod.state.block_of < 0) | ~pod.hashed[block]
        dead[np.asarray(keep, np.int64)] = False
        slots = jnp.asarray(np.flatnonzero(dead))
        pod.kv.arrays["state"] = [a.at[slots].set(jnp.nan)
                                  for a in pod.kv.arrays["state"]]


def close(got, want, tol=2e-4):
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


# ------------------------------------------------- the model step, end to end


@pytest.mark.parametrize("blocks", (2, 5, 6))
def test_miss_prefill_gives_the_references_last_row(blocks):
    """Whole chunks (2 and 6 blocks) and a prompt that ends inside one (5
    blocks: its last 16 positions run through the one-position recurrence);
    snapshots stand after every second block and the call's last."""
    eng = Engine()
    seq = eng.prefill(tokens_of(blocks * BLOCK, 1), 0)
    close(seq["row"], reference(tuple(seq["tokens"]))[-1])
    pod = eng.pod
    assert [int(pod.state.slot_of[b] >= 0) for b in seq["blocks"]] == [
        0, 1, 0, 1, int(blocks == 5), 1][:blocks]


def test_prefill_keeps_the_first_layers_state_as_the_equations_give_it():
    """Layer 0 by hand in float64: the conv inputs and the matrices a head
    after blocks 1 and 3."""
    eng = Engine()
    seq = eng.prefill(tokens_of(64, 1), 0)
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), PARAMS["layers"][0])
    x = np.asarray(PARAMS["embed"], np.float64)[np.asarray(seq["tokens"])]
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + CFG.rms_eps) * lp["ln"]
    Di, C = CFG.d_inner, CFG.conv_dim
    H, P, G, N = CFG.mamba_heads, CFG.mamba_head_dim, CFG.n_groups, CFG.d_state
    zud = h @ lp["w_in"]
    u, dt = zud[:, Di:Di + C], zud[:, Di + C:]
    up = np.concatenate((np.zeros((3, C)), u))
    conv = sum(lp["conv_k"][:, j] * up[j:j + len(u)] for j in range(4))
    conv = conv + lp["conv_b"]
    c = conv / (1 + np.exp(-conv))
    xs = c[:, :Di].reshape(-1, H, P)
    bm = c[:, Di:Di + G * N].reshape(-1, G, N)
    d = np.log1p(np.exp(dt + lp["dt_bias"]))
    a = -np.exp(lp["a_log"])
    s = np.zeros((H, P, N))
    conv_pool, ssm_pool = eng.pod.kv.arrays["state"][:2]
    for i in (1, 3):
        slot = eng.pod.state.slot_of[seq["blocks"][i]]
        end = (i + 1) * BLOCK
        for t in range(end - 2 * BLOCK, end):
            for head in range(H):
                s[head] = (np.exp(d[t, head] * a[head]) * s[head]
                           + d[t, head] * np.outer(xs[t, head],
                                                   bm[t, head // (H // G)]))
        close(np.asarray(ssm_pool[slot]), s, 1e-5)
        close(np.asarray(conv_pool[slot]).reshape(3, -1), u[end - 3:end], 1e-5)


@pytest.mark.parametrize("prefix_blocks, resumed", ((4, 4), (3, 0), (5, 0)))
def test_continue_from_a_snapshot_repeats_the_references_full_pass(
        prefix_blocks, resumed):
    """A six-block prompt is stored (snapshots after blocks 1, 3, 5).  A
    prompt that shares its first four blocks continues from the snapshot of
    block 3 and gives the reference's logits.  One that shares three, or
    five, ends beside a kept boundary: `cached_prefix` falls back to the last
    kept one, which is not the whole prefix, so the engine recomputes it all,
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


@pytest.mark.parametrize("turn", (32, 16), ids=("whole chunk", "ragged"))
def test_a_snapshot_at_a_stride_boundary_resumes_to_the_misss_logits(turn):
    """The same prompt as a miss and as a hit resumed from the snapshot its
    prefix left at block 3: one row of logits (to the order of the sums: the
    hit's chunks start at the boundary), the same state at the prompt's end.
    A turn of 16 tokens is no whole chunk and runs through the recurrence."""
    doc, ask = tokens_of(64, 1), tokens_of(turn, 2)
    prompt = np.concatenate((doc, ask))
    miss = Engine()
    first = miss.prefill(prompt, 0)
    hit = Engine()
    hit.prefill(np.concatenate((doc, tokens_of(32, 3))), 0)
    second = hit.prefill(prompt, 4)
    assert second["hit"] and not first["hit"]
    close(second["row"], first["row"], 1e-5)
    close(second["row"], reference(tuple(prompt))[-1])
    for eng, seq in ((miss, first), (hit, second)):
        assert eng.pod.state.slot_of[seq["blocks"][-1]] >= 0
    ends = [np.asarray(a[eng.pod.state.slot_of[seq["blocks"][-1]]])
            for eng, seq in ((miss, first), (hit, second))
            for a in eng.pod.kv.arrays["state"]]
    for got, want in zip(ends[len(ends) // 2:], ends[:len(ends) // 2]):
        close(got, want, 1e-5)


@pytest.mark.parametrize("kernel", (False, True), ids=("einsums", "kernel"))
@pytest.mark.parametrize("ends, resumed", (
    ((32, 64, 96), False),  # whole chunks, a call a kept boundary
    ((32, 80), False),  # ... a call of a chunk and half a chunk
    ((16,), True),  # less than a chunk: the recurrence alone
    ((64, 112), True),  # from a snapshot, a ragged end
))
def test_the_scan_by_calls_is_the_recurrence(kernel, ends, resumed):
    """`_scan`: the chunk form up to each kept boundary and the one-position
    recurrence over what is left of a call, against the recurrence over all:
    the outputs and the state at every boundary."""
    H, P, G, N = CFG.mamba_heads, CFG.mamba_head_dim, CFG.n_groups, CFG.d_state
    T = ends[-1]
    k = jax.random.split(jax.random.key(len(ends) + T), 6)
    x = jax.random.normal(k[0], (2, T, H, P))
    d = jax.nn.softplus(jax.random.normal(k[1], (2, T, H)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.7))
    bm, cm = (jax.random.normal(kk, (2, T, G, N)) for kk in k[3:5])
    s0 = (jax.random.normal(k[5], (2, H, P, N)) if resumed
          else jnp.zeros((2, H, P, N)))
    y, kept = nemotronh._scan(x, d, a, bm, cm, s0, ends, CFG,
                              interpret=kernel)
    want_y, _ = ssd_pallas.ssd_recurrence(x, d, a, bm, cm, s0)
    close(np.asarray(y), np.asarray(want_y), 2e-5)
    assert kept.shape == (2, len(ends), H, P, N)
    for i, end in enumerate(ends):
        _, want = ssd_pallas.ssd_recurrence(
            x[:, :end], d[:, :end], a, bm[:, :end], cm[:, :end], s0)
        close(np.asarray(kept[:, i]), np.asarray(want), 2e-5)


@pytest.mark.parametrize("interpret", (False, True), ids=("xla", "interpret"))
def test_decode_over_forty_steps_repeats_the_reference(interpret):
    """Two sequences of one shared prompt, 44 steps through the cache: across
    block boundaries (positions 96, 112, 128) and a stride boundary, each
    step's logits against the reference's row of the whole sequence.  The
    policy says `decode_ahead`, so no step writes the slot it reads.
    Interpreted, the step takes the paged kernel and its shared pass."""
    eng = Engine(steps=steps_of(CFG, interpret) if interpret else STEPS)
    doc = tokens_of(64, 1)
    first = eng.prefill(np.concatenate((doc, tokens_of(32, 2))), 0, own=3)
    second = eng.prefill(np.concatenate((doc, tokens_of(16, 3))), 4, own=4)
    assert second["hit"]
    seqs = [first, second]
    for s in seqs:
        s["tokens"].append(int(reference(tuple(s["tokens"]))[-1].argmax()))
    rows, pairs = [], []
    for _ in range(44):
        logits = eng.decode(seqs)
        rows.append(logits)
        pairs.append(eng.slots.copy())
        for s, row in zip(seqs, logits):
            s["tokens"].append(int(row.argmax()))
    for i, s in enumerate(seqs):
        want = reference(tuple(s["tokens"][:-1]))
        for t, row in enumerate(rows):
            close(row[i], want[len(want) - 44 + t])
    pairs = np.asarray(pairs)  # [step, sequence, (read, written)]
    assert (pairs[..., 0] != pairs[..., 1]).all()
    assert (pairs[1:, :, 0] == pairs[:-1, :, 1]).all()  # reads the last write
    assert eng.pod.state.counts["released"] > 0
    # the step's counts: three expert layers, 2 tokens x 2 picks each
    assert eng.load.shape == (3, 4) and (eng.load[:, 2] == 4).all()
    assert (eng.load[:, 3] <= 4).all() and (eng.load[:, 0] <= 4).all()


def test_the_three_programs_serve_the_reference_tokens_and_report_spans():
    """`jit_programs`: every shape compiles at the first call of any; a decode
    call packs the state group's integers into its one host argument and
    records the group's spans; the expert layers' counts are read a step
    later, with the picks and the picks that fell on a held expert."""
    shapes = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
    programs = jit_programs(nemotronh, CFG, shapes, interpret=True)
    pod = Pod("pod-0", nemotronh, CFG, 40)
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
        seqs = [tuple(p) + (int(t),) for p, t in zip(prompts, nxt)]
        served = []
        for step in range(3):
            out, kv = programs["decode"](
                PARAMS, nxt, pod.kv, table, np.asarray([97, 97]) + step)
            nxt = np.asarray(out)[0].astype(np.int32)
            served.append(nxt)
            want = [reference(s)[-1].argmax() for s in seqs]
            assert [int(t) for t in nxt] == want
            seqs = [s + (int(t),) for s, t in zip(seqs, nxt)]
        rows, dropped = TRACER.recorder.export()
    finally:
        TRACER.configure(sample_rate=0.0)
    assert not dropped
    spans = {}
    for r in rows:
        if r.get("span"):
            spans.setdefault(r["span"], []).append(r.get("attrs", {}))
    assert {r["trace"] for r in rows if r["span"] is None} == {"pod.step"}
    assert len(spans["kvpool.state"]) == 5 and "kvpool.window" not in spans
    assert len(spans["pod.compile"]) == 3 and len(spans["pod.pack"]) == 3
    # the second and third call go on from the call before: each takes the
    # step launched for it and launches the next
    assert [a["ahead"] for a in spans["pod.pack"]] == [0, 0, 1]
    assert [a.get("ahead", 0) for a in spans["pod.launch.decode"]] == [
        0, 0, 1, 1]
    # a prefill records the snapshots it took: blocks 1, 3, 5, then 5
    assert [a["taken"] for a in spans["kvpool.state"][:2]] == [3, 1]
    state = spans["state.read"][-1]
    groups = nemotronh.cache_groups(CFG)
    assert state["state_bytes"] == 2 * 2 * groups["state"].block_nbytes
    assert state["kv_bytes"] == 14 * groups["full"].block_nbytes
    loads = spans["moe.expert_load"]
    assert len(loads) == 2 * 3 and {a["layer"] for a in loads} == {0, 1, 2}
    for a in loads:
        assert a["experts_held"] == 4 and a["picks"] == 2 * CFG.top_k
        assert 0 <= a["picks_held"] <= a["picks"]
        assert a["experts_touched"] <= min(4, a["picks_held"])
        assert a["mean_tokens"] == 2 * CFG.top_k / CFG.n_experts
    assert spans["attention.read"][-1] == {"read_blocks": 4 + 2 * 3,
                                           "walked_blocks": 14,
                                           "run_blocks": 0,
                                           "shared_run_blocks": 0}


def test_a_step_launched_ahead_and_not_taken_leaves_the_state_it_read():
    """`decode_ahead` beside the matrix state: a call that goes on launches
    the step after its own, which writes the sequence's OTHER slot; when the
    next call does not go on (another token is fed than the one served, as
    after an admission), the step is dropped, the call's own step reads the
    state that was left alone, and serves the reference's token."""
    shapes = {"miss": (96,), "decode": (1,), "max_blocks": 9}
    programs = jit_programs(nemotronh, CFG, shapes, interpret=True)
    pod = Pod("pod-0", nemotronh, CFG, 40)
    assert pod.decode_ahead
    prompt = tokens_of(96, 5)
    ids, _ = pod.alloc(6)
    own, _ = pod.alloc(2)
    programs["miss"](PARAMS, prompt[None], pod.kv, np.asarray(ids)[None])
    table = np.asarray([ids + own + [0]], np.int32)
    seq = tuple(prompt) + (int(reference(tuple(prompt))[-1].argmax()),)
    for _ in range(3):  # the third call takes a step launched ahead
        out, _ = programs["decode"](PARAMS, np.asarray([seq[-1]]), pod.kv,
                                    table, np.asarray([len(seq)]))
        assert int(np.asarray(out)[0, 0]) == reference(seq)[-1].argmax()
        seq += (int(np.asarray(out)[0, 0]),)
    assert pod.last_decode[2] is not None  # a step is launched for the next
    other = (seq[-1] + 1) % VOCAB or 1  # not the token that step was fed
    seq = seq[:-1] + (other,)
    out, _ = programs["decode"](PARAMS, np.asarray([other]), pod.kv, table,
                                np.asarray([len(seq)]))
    want = reference(seq)[-1]
    assert int(np.asarray(out)[0, 0]) == want.argmax()
    close(np.asarray(out)[1, 0], want.max())


def test_no_decode_step_writes_a_slot_that_a_live_row_of_it_reads():
    """What `ssd_decode_step_pallas` stands on: it brings row b + 1's slot
    while row b's is advanced and row b - 1's written back, so no row of a
    step may write what a live row of the same step reads.  Six decode slots
    over shared prompts, requests ending and admitted for 300 steps, the
    idle rows on the engine's scratch block, a call that goes on handing the
    next its tables as `jit_programs.run_ahead` does: in every table a `Pod`
    makes under `decode_ahead`, the step's own and the one launched ahead,
    the live rows' read slots and all rows' written slots are disjoint, the
    live rows write a slot each, and the idle rows share one pair."""

    class Program:  # the cache's rules alone: no model, no pools
        cache_policy = staticmethod(nemotronh.cache_policy)
        new_pool = staticmethod(lambda cfg, blocks: {})

    slots, columns = 6, 12
    pod = Pod("pod-0", Program, dataclasses.replace(CFG, state_slots=34), 96)
    assert pod.decode_ahead
    rng = np.random.default_rng(50)
    prompts = [tokens_of(64, 40 + i) for i in range(2)]
    scratch = pod.alloc(1)[0][0]
    pod.hold([scratch], +1)
    table = np.full((slots, columns), scratch, np.int32)
    ctx, live = np.ones(slots, np.int32), [None] * slots
    seen = {"steps": 0, "idle": 0, "ahead": 0}

    def admit(slot):
        tokens = np.concatenate((prompts[rng.integers(2)],
                                 tokens_of(16, 60 + int(rng.integers(1000)))))
        hashes, n_out = hashes_of(tokens), int(rng.integers(3, 70))
        cached = pod.cached_prefix(hashes[:4])
        first = 4 if len(cached) == 4 else 0
        pod.touch(hashes[:first])
        pod.hold(cached[:first], +1)
        new, _ = pod.alloc(len(hashes) - first)
        pod.hold(cached[:first], -1)
        blocks = cached[:first] + new
        pod.hold(blocks, +1)
        own, _ = pod.alloc(-(-n_out // BLOCK))
        pod.hold(own, +1)
        pod.tables("hit" if first else "miss",
                   np.asarray(blocks, np.int32)[None], prefix_blocks=first)
        for h, bid in zip(hashes[first:], blocks[first:]):
            pod.cached[h] = bid
        live[slot] = dict(blocks=blocks + own, own=own, left=n_out)
        table[slot] = scratch
        table[slot, :len(blocks + own)] = blocks + own
        ctx[slot] = len(tokens) + 1

    def check(pairs):
        rows = np.asarray([r is not None for r in live])
        reads, writes = pairs[rows, 0], pairs[:, 1]
        assert not set(reads) & set(writes)
        assert len(set(pairs[rows, 1])) == rows.sum()
        assert len({tuple(p) for p in pairs[~rows]}) <= 1
        seen["idle"] += int((~rows).sum())

    went_on, handed = False, None
    for step in range(300):
        for slot in range(slots):  # an admission a step, some slots idle
            if live[slot] is None and rng.random() < 0.3:
                admit(slot)
                went_on = False
                break
        if went_on:
            own, handed = pod.tables(
                "decode", table.copy(), context_len=ctx.copy(), made=handed,
                ahead=np.minimum(ctx + 1, columns * BLOCK))
            check(handed["state"])
            seen["ahead"] += 1
        else:
            own, handed = pod.tables("decode", table.copy(),
                                     context_len=ctx.copy()), None
        check(own["state"])
        seen["steps"] += 1
        went_on = True
        for slot, req in enumerate(live):
            if req is None:
                continue
            ctx[slot] += 1
            req["left"] -= 1
            if req["left"] <= 0:
                pod.hold(req["blocks"], -1)
                pod.free.extend(req["own"])
                table[slot], ctx[slot], live[slot] = scratch, 1, None
                went_on = False
    assert seen["steps"] == 300 and seen["idle"] > 100 and seen["ahead"] > 100
    assert pod.state.counts["released"] > 0


# ------------------------------------------------- the chip's share of experts


def _expert_layer(cfg, params):
    layer = cfg.pattern.index(nemotronh.EXPERTS)
    return params["layers"][layer]


def test_the_two_halves_and_the_shared_expert_once_are_the_uncut_layer():
    """`model-configs` section 4's test: the expert layer with the experts
    0 .. E/2 - 1 held, plus the layer with the other half held, the shared
    expert counted once, is what the uncut reference gives for the whole
    layer; and each half is what the reference gives when handed that
    share."""
    whole = dataclasses.replace(CFG, held=(0, 8))
    params = nemotronh.init_params(jax.random.key(1), whole)
    lp = _expert_layer(whole, params)
    h = jax.random.normal(jax.random.key(2), (1, 24, CFG.d_model))
    shared = nemotronh._relu2(h, lp["shared"])
    parts, counts = [], []
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, held=(first, 4))
        half = {**lp, "experts": jax.tree.map(
            lambda a: a[first:first + 4], lp["experts"])}
        out, load = nemotronh._moe(h, half, cfg, False)
        parts.append(out - shared)
        counts.append(np.asarray(load))
    uncut, load = nemotronh._moe(h, lp, whole, False)
    close(np.asarray(parts[0] + parts[1] + shared), np.asarray(uncut), 1e-5)
    assert counts[0][2] == counts[1][2] == 24 * CFG.top_k == load[2] == load[3]
    assert counts[0][3] + counts[1][3] == 24 * CFG.top_k
    assert 0 < counts[0][3] < 24 * CFG.top_k  # both halves are picked from
    # the plain pass over the whole layer, every expert by a mask
    f32 = jnp.float32
    s = jax.nn.sigmoid(h[0] @ lp["router"])
    _, picked = jax.lax.top_k(s + lp["route_bias"], CFG.top_k)
    w = s * jnp.zeros_like(s).at[jnp.arange(24)[:, None], picked].set(1)
    w = w / (w.sum(-1, keepdims=True) + nemotronh.ROUTE_NORM_EPS) * 2.5
    want = shared[0]
    for e in range(8):
        up = jax.nn.relu(h[0] @ lp["experts"]["w_up"][e].astype(f32))
        want = want + w[:, e:e + 1] * ((up * up) @ lp["experts"]["w_down"][e])
    close(np.asarray(uncut[0]), np.asarray(want), 1e-5)


@pytest.mark.parametrize("batched", (True, False), ids=("batched", "sorted"))
def test_both_forms_sum_the_held_picks_only(batched):
    """`moe_serve.routed_experts` with a share: picks outside the range add
    nothing in either form, and the counts end with the picks outside."""
    E, D, F, n, k = 8, 16, 12, 10, 3
    keys = jax.random.split(jax.random.key(0), 5)
    h = jax.random.normal(keys[0], (n, D))
    up = jax.random.normal(keys[1], (E, D, F))
    down = jax.random.normal(keys[2], (E, F, D))
    picked, w = moe_serve.route(h, jax.random.normal(keys[3], (D, E)),
                                0.1 * jax.random.normal(keys[4], (E,)), k,
                                True, 2.5, 1e-20)
    for first, count in ((0, 4), (4, 4), (2, 3), (0, 8)):
        want = jnp.zeros((n, D))
        for e in range(first, first + count):
            we = jnp.sum(jnp.where(picked == e, w, 0), -1)[:, None]
            want += we * (jnp.square(jax.nn.relu(h @ up[e])) @ down[e])
        out, sizes = moe_serve.routed_experts(
            h, picked, w, {"w_up": up[first:first + count],
                           "w_down": down[first:first + count]}, E, batched,
            held=(first, count))
        close(np.asarray(out), np.asarray(want), 1e-5)
        inside = (picked >= first) & (picked < first + count)
        assert sizes.shape == (count + 1,) and int(sizes.sum()) == n * k
        assert int(sizes[-1]) == int((~inside).sum())
        assert [int(c) for c in sizes[:-1]] == [
            int((picked == e).sum()) for e in range(first, first + count)]


@pytest.mark.parametrize("batched", (True, False), ids=("batched", "sorted"))
def test_every_expert_held_is_a_gated_familys_layer_bit_for_bit(batched):
    """`held` = all reproduces what `routed_experts` gives a gated family
    (`afmoe`, `lfm2moe`, `glm4moelite`, `keyevl2`: three matrices an expert,
    `silu`) without the key, to the bit, and the counts but for the last."""
    E, D, F, n, k = 8, 16, 12, 20, 2
    keys = jax.random.split(jax.random.key(3), 6)
    h = jax.random.normal(keys[0], (n, D))
    experts = {"w_gate": jax.random.normal(keys[1], (E, D, F)),
               "w_up": jax.random.normal(keys[2], (E, D, F)),
               "w_down": jax.random.normal(keys[3], (E, F, D))}
    picked, w = moe_serve.route(h, jax.random.normal(keys[4], (D, E)), None, k,
                                True, 1.0)
    want, sizes = moe_serve.routed_experts(h, picked, w, experts, E, batched)
    got, held = moe_serve.routed_experts(h, picked, w, experts, E, batched,
                                         held=(0, E))
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(np.asarray(held[:-1]), np.asarray(sizes))
    assert int(held[-1]) == 0


# ---------------------------------------------------- the cache's two groups


def stored(eng: Engine, n: int = 6, key: int = 1) -> dict:
    """A prompt of n blocks, stored and no longer referenced."""
    seq = eng.prefill(tokens_of(n * BLOCK, key), 0)
    eng.finish(seq)
    return seq


def test_a_reused_snapshot_takes_the_chains_tail_out_of_the_full_group():
    """Seven state slots: where the group reuses the slot of a boundary whose
    block is still cached, the chain's tail from there leaves the full group
    too, and its hashes ride in `alloc`'s list."""
    cfg = dataclasses.replace(CFG, state_slots=7)
    eng = Engine(cfg=cfg, steps=steps_of(cfg))
    first = stored(eng, key=1)
    stored(eng, key=2)
    assert eng.removed == []  # six snapshots fit
    third = stored(eng, key=3)  # a prefill's slots are taken at its tables ...
    assert third["evicted"] == []
    fourth = stored(eng, key=4)  # ... what they evicted rides in the next list
    pod = eng.pod
    gone = [h for h in first["hashes"] if h not in pod.cached]
    assert gone and gone == first["hashes"][-len(gone):]  # a tail, no hole
    assert set(gone) <= set(fourth["evicted"])
    assert len(eng.removed) == len(set(eng.removed))  # each hash once


def test_live_sequences_hold_their_slots_and_exhaustion_is_an_error():
    cfg = dataclasses.replace(CFG, state_slots=4)
    eng = Engine(cfg=cfg, steps=steps_of(cfg))
    eng.prefill(tokens_of(96, 1), 0)  # live: snapshots after blocks 1, 3, 5
    with pytest.raises(RuntimeError, match="state group exhausted"):
        eng.prefill(tokens_of(96, 2), 0)


def test_block_bytes_and_pool_shapes_come_from_one_spec_per_group():
    groups = nemotronh.cache_groups(CFG)
    full, state = groups["full"], groups["state"]
    assert full.layout == "rows" and full.num_layers == 1
    assert full.block_nbytes == 2 * BLOCK * 2 * 16 * 4  # K and V, float32
    C = CFG.conv_dim
    assert C == 32 + 2 * 2 * 16 and CFG.d_inner == 32
    assert state.state_parts == (((3 * C,), "float32"),
                                 ((4, 8, 16), "float32"))
    assert state.block_nbytes == 3 * (3 * C + 4 * 8 * 16) * 4
    pools = nemotronh.new_pool(CFG, 40)
    assert [a.shape for a in pools["full"]] == [(40, 2, BLOCK * 2, 16)]
    assert [(a.shape, a.dtype) for a in pools["state"]] == 3 * [
        ((24, 3 * C), jnp.float32), ((24, 4, 8, 16), jnp.float32)]
    serving = dataclasses.replace(CFG, dtype="bfloat16")
    assert nemotronh.cache_groups(serving)["state"].block_nbytes == 3 * (
        3 * C * 2 + 4 * 8 * 16 * 4)
    policy = nemotronh.cache_policy(CFG)
    assert policy["specs"] == groups and policy["decode_ahead"]
    assert policy["protect_asked"] and policy["state"] == {"slots": 24}
    # at the published sizes a slot is 8 536 064 bytes, 533 blocks of K/V
    big = nemotronh.cache_groups(nemotronh.NemotronHConfig(
        pattern="MEMEM*EME", n_heads=32, n_kv_heads=2, head_dim=128,
        mamba_heads=64, mamba_head_dim=64, n_groups=8, d_state=128))
    assert big["state"].block_nbytes == 8536064
    assert big["full"].block_nbytes == 16384
    assert big["state"].block_nbytes // big["full"].block_nbytes == 521


def test_from_published_reads_the_keys():
    assert nemotronh.from_published(PUBLISHED, BLOCK) == CFG
    whole = {k: v for k, v in PUBLISHED.items()
             if k not in ("published", "held")}
    assert nemotronh.from_published(whole, BLOCK) == dataclasses.replace(
        CFG, n_experts=4, held=(0, 4))


@pytest.mark.parametrize("key, value, match", (
    ("n_group", 2, "n_group"),
    ("topk_group", 2, "topk_group"),
    ("n_shared_experts", 2, "n_shared_experts"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hybrid_override_pattern", "MEM*EM-", "mixers"),
    ("num_hidden_layers", 8, "does not name"),
    ("chunk_size", 24, "whole blocks"),
    ("serving", {"state_slots": 24, "state_stride_blocks": 3}, "whole chunk"),
    ("held", {"experts_first": 6}, "past the router's"),
))
def test_from_published_refuses_what_is_not_implemented(key, value, match):
    with pytest.raises(ValueError, match=match):
        nemotronh.from_published({**PUBLISHED, key: value}, BLOCK)


def test_a_float8_pass_fails_the_tolerance_the_comparisons_hold():
    tokens = tokens_of(96, 9)
    want = reference(tuple(tokens))

    def q(a):
        scale = jnp.max(jnp.abs(a)) / 448.0 + 1e-30
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    low = np.asarray(nemotronh.reference_logits(
        jax.tree.map(lambda a: q(a) if a.ndim > 1 else a, PARAMS), tokens, CFG))
    err8 = np.linalg.norm(low - want) / np.linalg.norm(want)
    seq = Engine().prefill(tokens, 0)
    err = np.linalg.norm(seq["row"] - want[-1]) / np.linalg.norm(want[-1])
    assert err < 2e-4 < 100 * 2e-4 < err8
