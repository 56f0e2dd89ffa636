"""What a `pod.step` trace records of the program call itself
(`models/pod.py`'s `jit_programs`): `pod.compile`, `pod.pack`,
`pod.launch.<kind>`, `pod.counts_read` and the root's attributes, for the three
families the pod cache serves, each at a tiny size on the CPU.

One scripted run a family, traced at rate 1.0 (`script`):

    0 miss      compiles all three programs
    1 hit
    2 decode    the first step: the table goes to the device, no counts yet
    3 decode    the same table
    4 decode    a row changed
    5 hit       a prefill between two decode steps
    6 decode
    7 decode

then, with the tracer off, a miss and two decode steps on a second pod.  That
the tokens served are the reference's is the family files' business
(`tests/test_<family>_pod.py`).
"""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from llm_d_kv_cache_manager_tpu.models import afmoe, lfm2moe, phi4flash
from llm_d_kv_cache_manager_tpu.models import pod as pod_programs
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

VOCAB = 128
SHAPES = {"miss": (96,), "hit": (64, 32), "decode": (2,), "max_blocks": 9}
NAMES = {"miss": "miss_prefill_T96", "hit": "hit_prefill_P64_S32",
         "decode": "decode_B2"}
KINDS = ("miss", "hit", "decode", "decode", "decode", "hit", "decode", "decode")
# (module, configuration, interpret, what a decode step counts on the device);
# the paged kernel, which counts `attention_read`, runs interpreted here
FAMILIES = {
    "afmoe": (afmoe, afmoe.AfmoeConfig(
        dtype="float32", vocab_size=VOCAB, window_slots=24,
        window_store_blocks=4), False, {"load"}),
    "lfm2moe": (lfm2moe, lfm2moe.Lfm2MoeConfig(
        dtype="float32", vocab_size=VOCAB,
        layer_types=("conv", "full_attention", "conv", "conv"),
        state_slots=24, state_stride_blocks=2), True,
        {"load", "attention_read"}),
    "phi4flash": (phi4flash, phi4flash.Phi4FlashConfig(
        dtype="float32", vocab_size=VOCAB, window=32, window_slots=32,
        window_store_blocks=6, state_slots=24, state_stride_blocks=2), True,
        {"attention_read"}),
}


def tokens_of(n: int, *key: int) -> np.ndarray:
    return np.random.default_rng([7, *key]).integers(1, VOCAB, n)


@functools.cache
def script(family: str) -> SimpleNamespace:
    """The run of the module's head; what it exported, what each traced
    decode step handed `Pod.keep_load`, and what the untraced part left."""
    module, cfg, interpret, _ = FAMILIES[family]
    params = module.init_params(jax.random.key(0), cfg)
    programs = pod_programs.jit_programs(module, cfg, SHAPES, interpret)
    pod = pod_programs.Pod("pod-0", module, cfg, 40)
    doc = tokens_of(64, 1)
    prompts = [np.concatenate((doc, tokens_of(32, k))) for k in (3, 2, 4)]
    kept, copies = [], []
    keep_load, to_host = pod_programs.Pod.keep_load, ArrayImpl.copy_to_host_async

    def spy_keep(self, counted, tokens):
        kept.append({k: np.asarray(v).copy() for k, v in counted.items()})
        keep_load(self, counted, tokens)

    def spy_copy(self):
        copies.append(self.shape)
        to_host(self)

    def decode(pod, table, ctx):
        out, _ = programs["decode"](params, np.asarray([5, 9]), pod.kv, table,
                                    np.asarray([ctx, ctx]))
        np.asarray(out)  # the engine reads a step's tokens before the next

    patch = pytest.MonkeyPatch()
    patch.setattr(pod_programs.Pod, "keep_load", spy_keep)
    TRACER.configure(sample_rate=1.0, ring_size=64)
    try:
        ids, _ = pod.alloc(6)
        programs["miss"](params, prompts[0][None], pod.kv, np.asarray(ids)[None])
        pod.cached.update(zip(range(100, 106), ids))
        more, _ = pod.alloc(2)
        programs["hit"](params, prompts[1][None, 64:], pod.kv,
                        np.asarray(ids[:4] + more)[None])
        own, _ = pod.alloc(3)
        table = np.zeros((2, 9), np.int32)
        table[0, :7], table[1, :7] = ids + own[:1], ids[:4] + more + own[1:2]
        decode(pod, table, 97)
        decode(pod, table, 98)
        table[1, 7] = own[2]  # a row changed: what an admission or a finish does
        decode(pod, table, 99)
        again, _ = pod.alloc(2)
        programs["hit"](params, prompts[2][None, 64:], pod.kv,
                        np.asarray(ids[:4] + again)[None])
        decode(pod, table, 100)
        decode(pod, table, 101)
        rows, dropped = TRACER.recorder.export()
        TRACER.configure(sample_rate=0.0)  # the ring, and what it holds, stay
        patch.setattr(ArrayImpl, "copy_to_host_async", spy_copy)
        quiet = pod_programs.Pod("pod-1", module, cfg, 40)
        ids, _ = quiet.alloc(6)
        programs["miss"](params, prompts[0][None], quiet.kv,
                         np.asarray(ids)[None])
        table = np.zeros((2, 9), np.int32)
        table[:, :7] = ids + quiet.alloc(1)[0]
        decode(quiet, table, 97)
        decode(quiet, table, 98)
        after, _ = TRACER.recorder.export()
    finally:
        patch.undo()
        TRACER.configure(sample_rate=0.0, ring_size=64)
        TRACER.reset()
    roots = [r for r in rows if r["span"] is None]
    spans = [[s for s in rows if s["span"] and s["trace_id"] == r["trace_id"]]
             for r in roots]
    return SimpleNamespace(
        roots=roots, spans=spans, dropped=dropped, kept=kept, quiet=quiet,
        untraced_rows=after, untraced_kept=len(kept), untraced_copies=copies)


def named(spans: list, name: str) -> list:
    return [s for s in spans if s["span"] == name]


family = pytest.mark.parametrize("family", sorted(FAMILIES))


@family
def test_every_call_is_a_pod_step_trace_that_says_what_it_ran(family):
    run = script(family)
    assert run.dropped == 0
    assert [r["trace"] for r in run.roots] == ["pod.step"] * len(KINDS)
    assert [r["attrs"]["kind"] for r in run.roots] == list(KINDS)
    assert [r["attrs"]["program"] for r in run.roots] == [
        NAMES[k] for k in KINDS]
    for kind, spans in zip(KINDS, run.spans):
        launches = [s for s in spans if s["span"].startswith("pod.launch.")]
        assert [s["span"] for s in launches] == [f"pod.launch.{kind}"]
        assert launches[0]["attrs"]["program"] == NAMES[kind]
        assert bool(named(spans, "pod.pack")) == (kind == "decode")


@family
def test_the_first_call_compiles_every_program_and_no_later_call_any(family):
    run = script(family)
    assert [r["attrs"]["compiled"] for r in run.roots] == [3] + [0] * 7
    first = named(run.spans[0], "pod.compile")
    assert sorted(s["attrs"]["program"] for s in first) == sorted(NAMES.values())
    assert all(s["end"] > s["start"] for s in first)
    assert not any(named(spans, "pod.compile") for spans in run.spans[1:])


@family
def test_a_calls_parts_lie_inside_the_root_one_after_another(family):
    """The table builds, `pod.counts_read`, `pod.pack`, the compiles and the
    launch: each inside the root's interval, none overlapping another, so
    their sum is no more than the root's length."""
    run = script(family)
    counts = FAMILIES[family][3]
    for i, (kind, root, spans) in enumerate(zip(KINDS, run.roots, run.spans)):
        assert all(root["start"] <= s["start"] <= s["end"] <= root["end"]
                   for s in spans)
        ordered = sorted(spans, key=lambda s: (s["start"], s["end"]))
        assert all(a["end"] <= b["start"] for a, b in zip(ordered, ordered[1:]))
        assert sum(s["end"] - s["start"] for s in spans) \
            <= root["end"] - root["start"]
        # from the second decode step on, the read of the step before's counts
        read = named(spans, "pod.counts_read")
        assert len(read) == (kind == "decode" and KINDS[:i].count("decode") > 0)
        for s in read:
            assert s["attrs"]["arrays"] == len(counts)
            assert s["attrs"]["bytes"] == sum(
                a.nbytes for a in run.kept[0].values())
            assert s["end"] <= named(spans, "pod.pack")[0]["start"]
        if kind == "decode":
            assert named(spans, "pod.pack")[0]["end"] \
                <= named(spans, "pod.launch.decode")[0]["start"]


@family
def test_the_table_is_sent_on_the_first_step_and_after_a_row_changed(family):
    run = script(family)
    packs = [named(spans, "pod.pack")[0]["attrs"]
             for kind, spans in zip(KINDS, run.spans) if kind == "decode"]
    assert [a["table_sent"] for a in packs] == [1, 0, 1, 0, 0]
    assert [a["h2d_bytes"] for a in packs] == [2 * 9 * 4, 0, 2 * 9 * 4, 0, 0]
    assert all(a["calls"] == 1 for a in packs)


@family
def test_a_decode_launch_stamps_its_period_where_no_prefill_stood_between(family):
    run = script(family)
    steps = [(root, named(spans, "pod.launch.decode")[0])
             for kind, root, spans in zip(KINDS, run.roots, run.spans)
             if kind == "decode"]
    assert [s["attrs"]["after_decode"] for _, s in steps] == [0, 1, 1, 0, 1]
    for (before, _), (root, s) in zip(steps, steps[1:]):
        if not s["attrs"]["after_decode"]:
            assert "since_prev_launch_s" not in s["attrs"]
            continue
        period = s["attrs"]["since_prev_launch_s"]
        # from launch to launch: no longer than from the call before's start
        # to this launch's, no shorter than from that call's end to this
        # call's start
        assert root["start"] - before["end"] < period \
            <= s["start"] - before["start"]
    assert "since_prev_launch_s" not in steps[0][1]["attrs"]
    prefills = [s for spans in run.spans for s in spans
                if s["span"] in ("pod.launch.hit", "pod.launch.miss")]
    assert len(prefills) == 3 and all(
        set(s["attrs"]) == {"program"} for s in prefills)


@family
def test_a_steps_counts_are_read_at_the_next_call_with_their_values(family):
    """What `Pod.keep_load` was handed at step n is what the spans of call
    n + 1 carry: read one step late, as before `pod.counts_read`."""
    module, cfg, _, counts = FAMILIES[family]
    run = script(family)
    decodes = [spans for kind, spans in zip(KINDS, run.spans) if kind == "decode"]
    assert len(run.kept) == 5 and all(set(k) == counts for k in run.kept)
    assert not named(decodes[0], "moe.expert_load")
    assert not named(decodes[0], "attention.read")
    for kept, spans in zip(run.kept, decodes[1:]):
        if "attention_read" in counts:
            read, walked, by_runs, by_shared_runs = kept["attention_read"]
            assert [s["attrs"] for s in named(spans, "attention.read")] == [
                {"read_blocks": int(read), "walked_blocks": int(walked),
                 "run_blocks": int(by_runs),
                 "shared_run_blocks": int(by_shared_runs)}]
            assert 0 <= by_runs + by_shared_runs <= read
        if "load" in counts:
            assert [s["attrs"] for s in named(spans, "moe.expert_load")] == [
                {"layer": layer, "experts_held": cfg.n_experts,
                 "experts_touched": int(touched), "max_tokens": int(most),
                 "mean_tokens": 2 * cfg.top_k / cfg.n_experts}
                for layer, (touched, most) in enumerate(kept["load"])]


@family
def test_an_untraced_call_records_nothing_and_asks_for_no_host_copy(family):
    run = script(family)
    assert len([r for r in run.untraced_rows if r["span"] is None]) == len(KINDS)
    assert run.untraced_kept == 5 and run.untraced_copies == []
    assert run.quiet.pending_load is None
    assert run.quiet.last_launch[0] == "decode"
