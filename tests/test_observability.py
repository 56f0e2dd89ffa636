"""Unit + concurrency tests for obs/: tracer, spans, traceparent
parsing, the flight recorder's three retention tiers, and the kvlint
gate over the package.  Uses private Tracer instances (not the global
TRACER) so tests never leak sampling state into each other.
"""

from __future__ import annotations

import subprocess
import sys
import threading
import time

import pytest

from llm_d_kv_cache_manager_tpu.obs.recorder import FlightRecorder
from llm_d_kv_cache_manager_tpu.obs.trace import (
    Tracer,
    TracerConfig,
    current_trace,
    format_traceparent,
    parse_traceparent,
    span as obs_span,
    use_trace,
)

SAMPLED_TP = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
UNSAMPLED_TP = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-00"


def make_tracer(**overrides) -> Tracer:
    config = TracerConfig(sample_rate=1.0)
    for key, value in overrides.items():
        setattr(config, key, value)
    return Tracer(config)


class TestTraceparent:
    def test_parse_valid_sampled(self):
        parsed = parse_traceparent(SAMPLED_TP)
        assert parsed is not None
        assert parsed.trace_id == "ab" * 16
        assert parsed.span_id == "cd" * 8
        assert parsed.sampled

    def test_parse_valid_unsampled(self):
        parsed = parse_traceparent(UNSAMPLED_TP)
        assert parsed is not None and not parsed.sampled

    def test_parse_is_case_insensitive_and_strips(self):
        parsed = parse_traceparent("  " + SAMPLED_TP.upper() + " ")
        assert parsed is not None and parsed.trace_id == "ab" * 16

    def test_parse_accepts_future_version_with_suffix_fields(self):
        """W3C forward compatibility: higher versions parse by their
        first four fields, ignoring any suffix fields."""
        header = "01-" + "ab" * 16 + "-" + "cd" * 8 + "-01-extrafield"
        parsed = parse_traceparent(header)
        assert parsed == ("ab" * 16, "cd" * 8, True)

    def test_parse_rejects_version_00_with_suffix(self):
        assert parse_traceparent(SAMPLED_TP + "-extrafield") is None

    @pytest.mark.parametrize(
        "header",
        [
            None,
            "",
            "garbage",
            "00-" + "ab" * 16 + "-" + "cd" * 8,  # missing flags
            "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
            "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # forbidden ver
            "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",  # zero trace id
            "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # zero span id
        ],
    )
    def test_parse_rejects(self, header):
        assert parse_traceparent(header) is None

    def test_format_roundtrip(self):
        header = format_traceparent("ab" * 16, "cd" * 8, sampled=True)
        parsed = parse_traceparent(header)
        assert parsed == ("ab" * 16, "cd" * 8, True)


class TestSampling:
    def test_rate_zero_drops_and_counts(self):
        tracer = make_tracer(sample_rate=0.0)
        assert tracer.start_trace("t") is None
        stats = tracer.stats()
        assert stats["traces_unsampled"] == 1
        assert stats["traces_sampled"] == 0

    def test_rate_one_samples(self):
        tracer = make_tracer()
        assert tracer.start_trace("t") is not None

    def test_sampled_traceparent_forces_at_rate_zero(self):
        tracer = make_tracer(sample_rate=0.0)
        trace = tracer.start_trace("t", traceparent=SAMPLED_TP)
        assert trace is not None
        assert trace.trace_id == "ab" * 16
        assert trace.parent_span_id == "cd" * 8

    def test_unsampled_traceparent_does_not_force(self):
        tracer = make_tracer(sample_rate=0.0)
        assert tracer.start_trace("t", traceparent=UNSAMPLED_TP) is None

    def test_force_flag(self):
        tracer = make_tracer(sample_rate=0.0)
        assert tracer.start_trace("t", force=True) is not None

    def test_configure_live_tunes_rate(self):
        tracer = make_tracer(sample_rate=0.0)
        tracer.configure(sample_rate=1.0)
        assert tracer.start_trace("t") is not None
        with pytest.raises(TypeError):
            tracer.configure(ring_capacity=5)

    def test_configure_ring_size_rebuilds_the_recorder(self):
        tracer = make_tracer()
        tracer.start_trace("before").finish()
        old = tracer.recorder
        tracer.configure(ring_size=5)
        assert tracer.recorder is not old
        assert tracer.recorder.ring_size == 5
        assert tracer.recorder.export() == ([], 0)
        assert tracer.stats()["ring_size"] == 5


class TestTraceSpans:
    def test_span_timing_parents_and_attrs(self):
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        with use_trace(trace):
            with obs_span("tokenize") as s:
                s.set_attr("tokens", 7)
                time.sleep(0.005)
            with obs_span("tokenize.encode", parent="tokenize"):
                pass
        trace.finish()
        view = trace.to_dict()
        assert view["status"] == "ok"
        assert [s["stage"] for s in view["stages"]] == ["tokenize"]
        spans = {s["name"]: s for s in view["spans"]}
        assert spans["tokenize"]["attributes"] == {"tokens": 7}
        assert spans["tokenize"]["duration_ms"] >= 5.0
        assert spans["tokenize.encode"]["parent"] == "tokenize"

    def test_untraced_span_is_null(self):
        assert current_trace() is None
        with obs_span("anything") as s:
            s.set_attr("ignored", 1)  # must not raise

    def test_add_completed_explicit_interval(self):
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        start = time.perf_counter() - 0.05
        trace.add_completed("queue_wait", start)
        trace.finish()
        (stage,) = trace.stage_breakdown()
        assert stage["stage"] == "queue_wait"
        assert stage["duration_ms"] >= 50.0

    def test_span_exception_marks_error(self):
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        with pytest.raises(RuntimeError):
            with use_trace(trace), obs_span("boom"):
                raise RuntimeError("nope")
        trace.finish()
        (span,) = trace.to_dict()["spans"]
        assert span["status"] == "error"
        assert "nope" in span["attributes"]["error"]

    def test_set_error_routes_to_errored_reservoir(self):
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        trace.set_error("poison pill")
        trace.finish()
        assert trace.status == "error"
        assert tracer.recorder.errored() == [trace]

    def test_finish_is_idempotent(self):
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        trace.finish()
        first = trace.duration_s
        trace.finish()
        assert trace.duration_s == first
        assert tracer.recorder.stats()["recorded"] == 1

    def test_finish_feeds_stage_histogram(self):
        from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

        def histogram_count(stage):
            for metric in METRICS.stage_latency.collect():
                for sample in metric.samples:
                    if (
                        sample.name.endswith("_count")
                        and sample.labels.get("stage") == stage
                    ):
                        return sample.value
            return 0.0

        before = histogram_count("uniquestage")
        tracer = make_tracer()
        trace = tracer.start_trace("req")
        with use_trace(trace), obs_span("uniquestage"):
            pass
        trace.finish()
        assert histogram_count("uniquestage") == before + 1

    def test_use_trace_restores_context(self):
        tracer = make_tracer()
        outer = tracer.start_trace("outer")
        inner = tracer.start_trace("inner")
        with use_trace(outer):
            with use_trace(inner):
                assert current_trace() is inner
            assert current_trace() is outer
        assert current_trace() is None


class TestFlightRecorder:
    def test_ring_eviction(self):
        tracer = make_tracer(ring_size=4)
        traces = []
        for i in range(10):
            trace = tracer.start_trace(f"t{i}")
            trace.finish()
            traces.append(trace)
        stats = tracer.recorder.stats()
        assert stats["ring_occupancy"] == 4
        assert stats["recorded"] == 10
        recent = tracer.recorder.recent()
        assert [t.name for t in recent] == ["t9", "t8", "t7", "t6"]
        # Evicted and never slow/errored: unresolvable.
        assert tracer.recorder.get(traces[0].trace_id) is None

    def test_slow_promotion_survives_ring_eviction(self):
        tracer = make_tracer(ring_size=2, slow_threshold_ms=0.0)
        slow_trace = tracer.start_trace("slow")
        time.sleep(0.002)
        slow_trace.finish()
        for i in range(5):
            tracer.start_trace(f"f{i}").finish()
        # Rolled out of the ring, still resolvable via the reservoir.
        assert tracer.recorder.get(slow_trace.trace_id) is slow_trace
        assert slow_trace in tracer.recorder.slow()

    def test_slow_reservoir_keeps_slowest(self):
        recorder = FlightRecorder(
            ring_size=64, slow_keep=2, slow_threshold_ms=0.0
        )

        class Stub:
            def __init__(self, trace_id, duration_s):
                self.trace_id = trace_id
                self.duration_s = duration_s
                self.status = "ok"

        for trace_id, duration in (
            ("a", 0.010), ("b", 0.030), ("c", 0.020), ("d", 0.001),
        ):
            recorder.record(Stub(trace_id, duration))
        assert [t.trace_id for t in recorder.slow()] == ["b", "c"]

    def test_threshold_gates_promotion(self):
        tracer = make_tracer(slow_threshold_ms=10_000.0)
        tracer.start_trace("fast").finish()
        assert tracer.recorder.stats()["slow_retained"] == 0

    def test_clear(self):
        tracer = make_tracer()
        tracer.start_trace("t").finish()
        tracer.reset()
        stats = tracer.stats()
        assert stats["recorded"] == 0
        assert stats["ring_occupancy"] == 0
        assert stats["traces_sampled"] == 0


class TestConcurrency:
    def test_parallel_traced_requests_no_lost_or_duplicated_ids(self):
        """Acceptance gate: the flight-recorder ring under parallel
        traced requests — every trace retrievable, every id unique."""
        tracer = make_tracer(ring_size=1024)
        n_threads, per_thread = 16, 25
        errors = []
        barrier = threading.Barrier(n_threads)

        def worker(worker_index):
            try:
                barrier.wait(timeout=10)
                for i in range(per_thread):
                    trace = tracer.start_trace(
                        f"w{worker_index}.{i}"
                    )
                    with use_trace(trace):
                        with obs_span("stage_a"):
                            pass
                        with obs_span("stage_b"):
                            assert current_trace() is trace
                    trace.finish()
            except Exception as exc:  # pragma: no cover - surfaced below
                errors.append(exc)

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_threads)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=30)
        assert not errors
        total = n_threads * per_thread
        recent = tracer.recorder.recent(limit=total)
        ids = [t.trace_id for t in recent]
        assert len(ids) == total
        assert len(set(ids)) == total
        stats = tracer.recorder.stats()
        assert stats["recorded"] == total
        assert tracer.stats()["traces_sampled"] == total
        # Every trace got both spans (none torn by concurrency).
        for trace in recent:
            assert len(trace.to_dict()["spans"]) == 2

    def test_cross_thread_span_append(self):
        """Spans appended from a worker thread land on the same trace
        (the tokenization-pool propagation contract)."""
        tracer = make_tracer()
        trace = tracer.start_trace("req")

        def worker():
            trace.add_completed(
                "queue_wait", time.perf_counter() - 0.001
            )
            with trace.span("encode", parent="tokenize"):
                pass

        thread = threading.Thread(target=worker)
        thread.start()
        thread.join(timeout=10)
        trace.finish()
        assert len(trace.to_dict()["spans"]) == 2


# ---- the program traces itself: library calls, real intervals, export ----

MODEL = "obs-model"
BLOCK = 4
TOP_LEVEL = {
    "tokenize", "memo_check", "hash_blocks", "index_lookup", "score",
    "bookkeeping",
}


class _WordTokenizer:
    """'tN' -> N."""

    def type(self) -> str:
        return "word"

    def encode(self, prompt, model_name, add_special_tokens=True):
        from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import (
            Encoding,
        )

        tokens, offsets, pos = [], [], 0
        for word in prompt.split(" "):
            tokens.append(int(word[1:]))
            offsets.append((pos, pos + len(word)))
            pos += len(word) + 1
        return Encoding(tokens, offsets)


def _prompt(tokens) -> str:
    return " ".join(f"t{t}" for t in tokens)


@pytest.fixture
def global_tracer():
    """The process tracer at rate 1.0 with an empty ring; its sampling
    state and ring geometry are put back afterwards."""
    from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

    rate, ring = TRACER.config.sample_rate, TRACER.config.ring_size
    TRACER.configure(sample_rate=1.0, ring_size=64)
    TRACER.reset()
    yield TRACER
    TRACER.configure(sample_rate=rate, ring_size=ring)
    TRACER.reset()


def _make_indexer(prefix_chunk_bytes=None):
    from llm_d_kv_cache_manager_tpu.kvcache.indexer import (
        Indexer,
        IndexerConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import PodEntry
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
        TokenProcessorConfig,
    )
    from llm_d_kv_cache_manager_tpu.tokenization.pool import (
        TokenizationPoolConfig,
    )
    from llm_d_kv_cache_manager_tpu.tokenization.prefixstore.lru_store import (  # noqa: E501
        LRUStoreConfig,
    )

    ix = Indexer(
        IndexerConfig(
            prefix_store_config=(
                LRUStoreConfig(block_size=prefix_chunk_bytes)
                if prefix_chunk_bytes else LRUStoreConfig()),
            token_processor_config=TokenProcessorConfig(block_size=BLOCK),
            tokenizers_pool_config=TokenizationPoolConfig(
                workers=1, model_name=MODEL
            ),
            lookup_chunk_size=2,  # several chunks in a 12-block walk
            cache_stats=False,
        ),
        tokenizer=_WordTokenizer(),
    )
    ix.run()
    tokens = [7 + i for i in range(BLOCK * 12)]
    keys = ix.token_processor.tokens_to_kv_block_keys(0, tokens, MODEL)
    ix.kv_block_index.add(keys, keys, [PodEntry("pod-a", "hbm")])
    ix.kv_block_index.add(keys[:5], keys[:5], [PodEntry("pod-b", "hbm")])
    ix.prompt = _prompt(tokens)
    return ix


@pytest.fixture
def indexer():
    ix = _make_indexer()
    yield ix
    ix.shutdown()


def _by_trace(rows):
    traces = {}
    for row in rows:
        traces.setdefault(row["trace_id"], []).append(row)
    return list(traces.values())


class TestLibraryCallsTraceThemselves:
    def test_one_trace_of_real_intervals_with_counts(
        self, global_tracer, indexer
    ):
        scores = indexer.get_pod_scores(indexer.prompt, MODEL)
        assert scores == {"pod-a": 12.0, "pod-b": 5.0}
        rows, dropped = global_tracer.recorder.export()
        assert dropped == 0
        (trace,) = _by_trace(rows)
        root, spans = trace[0], trace[1:]
        assert (root["trace"], root["span"], root["status"]) == (
            "indexer.score", None, "ok")
        top = sorted((r for r in spans if r["parent"] is None),
                     key=lambda r: r["start"])
        assert {r["span"] for r in top} == TOP_LEVEL
        # Inside the root, and no overlap between siblings.
        assert root["start"] <= top[0]["start"]
        assert top[-1]["end"] <= root["end"]
        for before, after in zip(top, top[1:]):
            assert before["start"] <= before["end"] <= after["start"]
        for child in (r for r in spans if r["parent"] == "tokenize"):
            (tokenize,) = [r for r in top if r["span"] == "tokenize"]
            assert tokenize["start"] <= child["start"]
            assert child["end"] <= tokenize["end"]
        # One span per chunk of the walk, counts taken where the work is.
        by = {}
        for r in top:
            by.setdefault(r["span"], []).append(r["attrs"])
        assert by["tokenize"] == [{"tokens": BLOCK * 12}]
        assert by["memo_check"] == [{"memo": "miss"}]
        assert len(by["hash_blocks"]) == len(by["score"]) > 1
        assert sum(a["block_keys"] for a in by["hash_blocks"]) == 12
        assert sum(a["memo_blocks"] for a in by["hash_blocks"]) == 0
        assert sum(a["keys_hit"] for a in by["index_lookup"]) == 12
        assert by["score"][-1] == {"pods": 2}  # sampled: no provenance

    def test_traced_and_untraced_agree_on_scores_and_memo(
        self, global_tracer, indexer
    ):
        """A traced request takes the untraced path: the second
        identical prompt is a memo hit under a trace, as without one."""
        pods = ["pod-a", "pod-b", "pod-c"]
        other = _prompt(t + 1000 for t in range(BLOCK * 12))
        prompts = (indexer.prompt, indexer.prompt, other, indexer.prompt)

        def drive(ix):
            """(scores, index lookups made) per call."""
            calls = []
            real = ix.kv_block_index.lookup_chain
            ix.kv_block_index.lookup_chain = (
                lambda keys: calls[-1][1].append(len(keys)) or real(keys))
            for prompt in prompts:
                calls.append((None, []))
                scores = ix.get_pod_scores(prompt, MODEL, pods)
                calls[-1] = (scores, calls[-1][1])
            return calls

        traced = drive(indexer)
        rows, _ = global_tracer.recorder.export()
        memo = [[r["attrs"]["memo"] for r in t if r["span"] == "memo_check"]
                for t in _by_trace(rows)]
        assert memo == [["miss"], ["hit"], ["miss"], ["hit"]]
        walked = [bool({r["span"] for r in t} & {"hash_blocks", "score"})
                  for t in _by_trace(rows)]
        assert walked == [True, False, True, False]

        global_tracer.configure(sample_rate=0.0)
        twin = _make_indexer()
        try:
            untraced = drive(twin)
        finally:
            twin.shutdown()
        assert traced == untraced
        assert traced[1] == (
            {"pod-a": 12.0, "pod-b": 5.0, "pod-c": 0.0}, [])

    def test_keys_served_by_the_prefix_store_are_counted_as_memo_blocks(
        self, global_tracer
    ):
        """A prompt that extends a stored one is served its tokens and
        block keys by the prefix store (16-byte text chunks here): the
        walk hashes nothing, and `hash_blocks` says so."""
        ix = _make_indexer(prefix_chunk_bytes=16)
        try:
            ix.get_pod_scores(ix.prompt, MODEL)
            longer = ix.prompt + " " + _prompt(range(900, 900 + BLOCK))
            ix.get_pod_scores(longer, MODEL)
        finally:
            ix.shutdown()
        rows, _ = global_tracer.recorder.export()
        first, second = ([r["attrs"] for r in t if r["span"] == "hash_blocks"]
                         for t in _by_trace(rows))
        assert sum(a["memo_blocks"] for a in first) == 0
        assert sum(a["block_keys"] for a in first) == 12
        assert second == [{"block_keys": 11, "memo_blocks": 11}]

    def test_under_a_callers_trace_nothing_is_started(
        self, global_tracer, indexer
    ):
        outer = global_tracer.start_trace("api.layer")
        with use_trace(outer):
            indexer.get_pod_scores(indexer.prompt, MODEL)
        assert global_tracer.recorder.export() == ([], 0)
        outer.finish()
        rows, _ = global_tracer.recorder.export()
        assert {r["trace"] for r in rows} == {"api.layer"}
        assert TOP_LEVEL <= {r["span"] for r in rows}

    def test_only_a_forced_trace_carries_provenance(
        self, global_tracer, indexer
    ):
        forced = global_tracer.start_trace("asked.for", force=True)
        assert forced.forced
        with use_trace(forced):
            indexer.get_pod_scores(indexer.prompt, MODEL)
        forced.finish()
        last_score = [s for s in forced.to_dict()["spans"]
                      if s["name"] == "score"][-1]
        assert last_score["attributes"]["provenance"]["pod-b"] == {
            "blocks_matched": 5, "break_index": 5}
        assert not global_tracer.start_trace("drawn").forced

    def test_rate_zero_allocates_no_trace(
        self, global_tracer, indexer, monkeypatch
    ):
        from llm_d_kv_cache_manager_tpu.obs import trace as trace_module

        global_tracer.configure(sample_rate=0.0)
        made = []
        real_init = trace_module.Trace.__init__
        monkeypatch.setattr(
            trace_module.Trace, "__init__",
            lambda self, *a, **kw: made.append(1) or real_init(
                self, *a, **kw))
        before = global_tracer.stats()["traces_unsampled"]
        assert indexer.get_pod_scores(indexer.prompt, MODEL)
        assert made == []
        assert global_tracer.stats()["traces_unsampled"] == before + 1
        assert global_tracer.recorder.export() == ([], 0)

    def test_a_failing_call_finishes_its_trace_errored(
        self, global_tracer, indexer
    ):
        def boom(*args, **kwargs):
            raise RuntimeError("tokenizer down")

        indexer.tokenization_pool.tokenize_with_keys = boom
        with pytest.raises(RuntimeError):
            indexer.get_pod_scores(indexer.prompt, MODEL)
        (trace,) = global_tracer.recorder.errored()
        assert trace.name == "indexer.score" and trace.status == "error"
        assert current_trace() is None

    def test_stages_view_and_histogram_sum_a_stage_per_trace(
        self, global_tracer, indexer
    ):
        from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

        def count(stage):
            return sum(
                sample.value
                for metric in METRICS.stage_latency.collect()
                for sample in metric.samples
                if sample.name.endswith("_count")
                and sample.labels.get("stage") == stage)

        before = count("hash_blocks")
        indexer.get_pod_scores(indexer.prompt, MODEL)
        (trace,) = global_tracer.recorder.recent()
        view = trace.to_dict()
        names = [s["stage"] for s in view["stages"]]
        assert sorted(names) == sorted(TOP_LEVEL)  # one entry per stage
        chunks = [s["duration_ms"] for s in view["spans"]
                  if s["name"] == "hash_blocks"]
        assert len(chunks) > 1
        (summed,) = [s["duration_ms"] for s in view["stages"]
                     if s["stage"] == "hash_blocks"]
        assert summed == pytest.approx(sum(chunks))
        assert sum(s["duration_ms"] for s in view["stages"]) <= (
            view["duration_ms"])
        assert count("hash_blocks") == before + 1


class TestEventPlaneSpans:
    def test_lockfree_predecode_yields_a_decode_span(self, global_tracer):
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
            InMemoryIndex,
        )
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
            InMemoryIndexConfig,
        )
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (  # noqa: E501
            ChunkedTokenDatabase,
            TokenProcessorConfig,
        )
        from llm_d_kv_cache_manager_tpu.kvevents.events import (
            BlockStored,
            EventBatch,
        )
        from llm_d_kv_cache_manager_tpu.kvevents.pool import (
            Message,
            Pool,
            PoolConfig,
        )

        index = InMemoryIndex(InMemoryIndexConfig())
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=BLOCK))
        pool = Pool(index, db, PoolConfig(concurrency=1))
        assert pool._lockfree_decode  # the default path
        pool.start()
        try:
            payload = EventBatch(ts=1.0, events=[BlockStored(
                block_hashes=[11, 12], parent_block_hash=None,
                token_ids=list(range(BLOCK * 2)), block_size=BLOCK,
                medium="hbm")]).encode()
            pool.add_task(Message(
                topic=f"kv@pod-a@{MODEL}", payload=payload,
                pod_identifier="pod-a", model_name=MODEL))
            pool.drain()
        finally:
            pool.shutdown()
        rows, dropped = global_tracer.recorder.export()
        assert dropped == 0
        (trace,) = _by_trace(rows)
        root, spans = trace[0], sorted(trace[1:], key=lambda r: r["start"])
        assert root["trace"] == "kvevents.message" and root["status"] == "ok"
        assert [r["span"] for r in spans] == [
            "kvevents.decode", "kvevents.queue_wait", "kvevents.apply",
            "kvevents.flush"]
        assert root["start"] <= spans[0]["start"]
        assert spans[-1]["end"] <= root["end"]
        for before, after in zip(spans, spans[1:]):
            assert before["start"] <= before["end"] <= after["start"]
        assert spans[0]["attrs"] == {"events": 1}
        assert spans[2]["attrs"] == {"applied": 1}
        assert spans[3]["attrs"] == {"adds": 1}
        assert pool.stage_stats()["decode_msgs"] == 1  # /healthz still fed


class TestExport:
    def test_export_returns_every_span_and_counts_what_the_ring_lost(self):
        tracer = make_tracer(ring_size=3)
        for i in range(3):
            trace = tracer.start_trace(f"req-{i}")
            trace.set_attr("i", i)
            with use_trace(trace):
                with obs_span("a") as s:
                    s.set_attr("n", i)
                with obs_span("a.child", parent="a"):
                    pass
            trace.finish()
        rows, dropped = tracer.recorder.export()
        assert dropped == 0 and len(rows) == 9
        assert [r["span"] for r in rows[:3]] == [None, "a", "a.child"]
        assert [r["trace"] for r in rows[::3]] == ["req-0", "req-1", "req-2"]
        root, a, child = rows[3:6]
        assert root["attrs"] == {"i": 1} and a["attrs"] == {"n": 1}
        assert child["parent"] == "a" and a["parent"] is None
        assert root["trace_id"] == a["trace_id"] == child["trace_id"]
        assert root["start"] <= a["start"] <= a["end"] <= child["start"]
        assert child["end"] <= root["end"]
        assert set(a) == {"trace_id", "trace", "span", "parent", "start",
                          "end", "status", "attrs"}
        # Asking again returns the same; the ring wrapping is reported.
        assert tracer.recorder.export() == (rows, 0)
        for i in range(2):
            tracer.start_trace(f"late-{i}").finish()
        rows, dropped = tracer.recorder.export()
        assert dropped == 2
        assert [r["trace"] for r in rows if r["span"] is None] == [
            "req-2", "late-0", "late-1"]

    def test_errored_and_slow_traces_outlive_the_ring_in_the_export(self):
        tracer = make_tracer(ring_size=1, slow_threshold_ms=0.0)
        first = tracer.start_trace("first")
        first.set_error("boom")
        first.finish()
        tracer.start_trace("second").finish()
        rows, dropped = tracer.recorder.export()
        assert dropped == 0
        assert [(r["trace"], r["status"]) for r in rows] == [
            ("second", "ok"), ("first", "error")]

    def test_an_open_trace_is_not_exported(self):
        tracer = make_tracer()
        tracer.start_trace("open")
        assert tracer.recorder.export() == ([], 0)


class TestKvlintGate:
    def test_obs_package_is_kvlint_clean_without_baseline(self):
        """Acceptance gate: kvlint over obs/ with zero baseline
        entries.  --no-baseline means a future violation cannot hide
        behind a grandfathered entry."""
        result = subprocess.run(
            [
                sys.executable,
                "-m",
                "hack.kvlint",
                "llm_d_kv_cache_manager_tpu/obs",
                "--no-baseline",
            ],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert result.returncode == 0, result.stdout + result.stderr
