"""Scheduler-plugin adapter + TTL subscriber lifecycle + pod reconciler.

Mirrors the reference's scorer-plugin behavior
(examples/kv_cache_aware_scorer) and reconciler predicates
(examples/kv_events/pod_reconciler), with the fleet simulated by
injected index entries and a fake k8s API server.
"""

import http.server
import json
import threading
import time

import pytest

from llm_d_kv_cache_manager_tpu.kvcache.indexer import Indexer, IndexerConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import PodEntry
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
    EMPTY_BLOCK_HASH,
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.pod_reconciler import (
    PodReconciler,
    PodReconcilerConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.subscriber_manager import (
    SubscriberManager,
)
from llm_d_kv_cache_manager_tpu.scheduler import (
    ChatCompletionsBody,
    ChatMessage,
    CompletionsBody,
    LLMRequest,
    Pod,
    PrecisePrefixCacheScorer,
    PrecisePrefixCacheScorerConfig,
)
from llm_d_kv_cache_manager_tpu.tokenization.pool import (
    TokenizationPoolConfig,
)
from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import (
    LocalFastTokenizer,
)
from llm_d_kv_cache_manager_tpu.utils.ttl_cache import TTLCache
from tests.helpers.tiny_tokenizer import (
    build_transformers_tokenizer,
    save_tokenizer_json,
)

MODEL = "test-model"
PROMPT = "the quick brown fox jumps over the lazy dog"


class TestTTLCache:
    def test_set_get_expire(self):
        evicted = []
        cache = TTLCache(0.15, on_evict=lambda k, v: evicted.append(k))
        cache.set("a", 1)
        assert cache.get("a") == 1
        time.sleep(0.2)
        assert cache.get("a") is None
        assert evicted == ["a"]

    def test_set_refreshes_deadline(self):
        cache = TTLCache(0.2)
        cache.set("a", 1)
        time.sleep(0.12)
        cache.set("a", 2)
        time.sleep(0.12)
        assert cache.get("a") == 2

    def test_sweep_and_delete(self):
        evicted = []
        cache = TTLCache(0.05, on_evict=lambda k, v: evicted.append(k))
        cache.set("a", 1)
        cache.set("b", 2, ttl_seconds=60)
        time.sleep(0.1)
        assert cache.sweep() == 1
        assert evicted == ["a"]
        # Explicit delete does not fire on_evict.
        assert cache.delete("b")
        assert evicted == ["a"]


@pytest.fixture()
def scorer(tmp_path):
    tokenizer_dir = save_tokenizer_json(str(tmp_path), MODEL)
    indexer = Indexer(
        IndexerConfig(
            token_processor_config=TokenProcessorConfig(block_size=4),
            tokenizers_pool_config=TokenizationPoolConfig(
                workers=2, model_name=MODEL
            ),
        ),
        tokenizer=LocalFastTokenizer(tokenizer_dir),
    )
    indexer.chat_processor.register_tokenizer(
        MODEL, build_transformers_tokenizer()
    )
    scorer = PrecisePrefixCacheScorer(
        PrecisePrefixCacheScorerConfig(
            discover_pods=False,  # no live fleet in unit tests
        ),
        indexer=indexer,
    )
    yield scorer
    scorer.shutdown()


def seed(scorer, prompt, address, truncate=None):
    indexer = scorer.indexer
    tokens = indexer.tokenization_pool.tokenize(prompt, MODEL, None)
    keys = indexer.token_processor.tokens_to_kv_block_keys(
        EMPTY_BLOCK_HASH, tokens, MODEL
    )
    if truncate:
        keys = keys[:truncate]
    indexer.kv_block_index.add(keys, keys, [PodEntry(address, "hbm")])


class TestPrecisePrefixCacheScorer:
    def test_completions_scoring_normalized(self, scorer):
        seed(scorer, PROMPT, "10.0.0.1")
        seed(scorer, PROMPT, "10.0.0.2", truncate=1)
        pods = [
            Pod("ns/pod-a", "10.0.0.1"),
            Pod("ns/pod-b", "10.0.0.2"),
            Pod("ns/pod-c", "10.0.0.3"),
        ]
        request = LLMRequest(
            target_model=MODEL, completions=CompletionsBody(prompt=PROMPT)
        )
        scores = scorer.score(request, pods)
        assert scores[pods[0]] == 1.0
        assert 0 < scores[pods[1]] < 1.0
        assert scores[pods[2]] == 0.0

    def test_chat_completions_scoring(self, scorer):
        body = ChatCompletionsBody(
            messages=[ChatMessage("user", "hello world")]
        )
        rendered = scorer.indexer.chat_processor.apply_chat_template(
            MODEL,
            __import__(
                "llm_d_kv_cache_manager_tpu.preprocessing.chat_templating",
                fromlist=["ApplyChatTemplateRequest"],
            ).ApplyChatTemplateRequest(
                conversation=[{"role": "user", "content": "hello world"}]
            ),
        )
        # Seed the index with the rendered prompt's block chain.
        tokens = scorer.indexer.tokenization_pool.tokenize(
            rendered, MODEL, None
        )
        keys = scorer.indexer.token_processor.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, tokens, MODEL
        )
        scorer.indexer.kv_block_index.add(
            keys, keys, [PodEntry("10.0.0.9", "hbm")]
        )
        pods = [Pod("ns/pod-x", "10.0.0.9")]
        scores = scorer.score(
            LLMRequest(target_model=MODEL, chat_completions=body), pods
        )
        assert scores[pods[0]] == 1.0

    def test_nil_request_and_empty_body(self, scorer):
        pods = [Pod("ns/pod-a", "10.0.0.1")]
        assert scorer.score(None, pods) == {}
        # No body -> error swallowed, empty result.
        assert scorer.score(LLMRequest(target_model=MODEL), pods) == {}

    def test_cold_index_scores_zero(self, scorer):
        pods = [Pod("ns/pod-a", "10.0.0.1")]
        request = LLMRequest(
            target_model=MODEL, completions=CompletionsBody(prompt=PROMPT)
        )
        assert scorer.score(request, pods) == {pods[0]: 0.0}


class TestSubscriberTTLLifecycle:
    def test_unseen_pods_age_out(self):
        removed = []

        class FakeManager:
            def ensure_subscriber(self, pod, endpoint):
                return True

            def remove_subscriber(self, pod):
                removed.append(pod)
                return True

        cache = TTLCache(
            0.15, on_evict=lambda pod, _: FakeManager().remove_subscriber(pod)
        )
        cache.set("ns/pod-a", "10.0.0.1")
        time.sleep(0.2)
        cache.sweep()
        assert removed == ["ns/pod-a"]


class TestPurgeOnExpiry:
    def test_expired_pod_purged_from_index(self, tmp_path):
        """With purge_index_on_expiry, a pod whose subscription ages
        out also loses its index entries (stale claims stop attracting
        traffic); other pods' entries survive."""
        tokenizer_dir = save_tokenizer_json(str(tmp_path), MODEL)
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size=4),
                tokenizers_pool_config=TokenizationPoolConfig(
                    workers=1, model_name=MODEL
                ),
            ),
            tokenizer=LocalFastTokenizer(tokenizer_dir),
        )
        scorer = PrecisePrefixCacheScorer(
            PrecisePrefixCacheScorerConfig(
                indexer_config=IndexerConfig(),
                subscription_ttl_seconds=0.1,
                purge_index_on_expiry=True,
            ),
            indexer=indexer,
        )
        try:
            from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
                PodEntry,
            )

            indexer.kv_block_index.add(
                [0x51, 0x52],
                [0x61, 0x62],
                [PodEntry("10.0.0.1", "hbm"), PodEntry("10.0.0.2", "hbm")],
            )
            scorer._subscriptions.set("ns/pod-a", "10.0.0.1")
            time.sleep(0.2)
            scorer._subscriptions.sweep()
            # The purge runs on its own thread (the expiry callback
            # must not stall scoring): wait for it, bounded.
            deadline = time.monotonic() + 5.0
            while True:
                found = indexer.kv_block_index.lookup([0x61, 0x62])
                survivors = {
                    p.pod_identifier
                    for pods in found.values()
                    for p in pods
                }
                if survivors == {"10.0.0.2"} or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            assert survivors == {"10.0.0.2"}
        finally:
            scorer.shutdown()


class TestDiscoveryTopicFilter:
    def test_discovered_subscriber_matches_engine_topics(self, tmp_path):
        """The plugin subscribes under the scheduler's namespaced pod
        name, but engines publish under their own id — the "kv@" filter
        must bridge the two (regression: a per-pod-identity filter
        silently drops every event)."""
        import time as _time

        from llm_d_kv_cache_manager_tpu.kvevents.publisher import Publisher

        tokenizer_dir = save_tokenizer_json(str(tmp_path), MODEL)
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size=4),
                tokenizers_pool_config=TokenizationPoolConfig(
                    workers=1, model_name=MODEL
                ),
            ),
            tokenizer=LocalFastTokenizer(tokenizer_dir),
        )
        # Bind to port 0 so the OS picks a free port (fixed ports flake
        # under parallel test runs); the scorer then dials that port.
        publisher = Publisher(
            "tcp://127.0.0.1:0",
            pod_identifier="127.0.0.1",  # engine id != "ns/pod-a"
            model_name=MODEL,
            bind=True,
        )
        scorer = PrecisePrefixCacheScorer(
            PrecisePrefixCacheScorerConfig(
                discover_pods=True, pod_socket_port=publisher.port
            ),
            indexer=indexer,
        )
        pods = [Pod("ns/pod-a", "127.0.0.1")]
        request = LLMRequest(
            target_model=MODEL, completions=CompletionsBody(prompt=PROMPT)
        )
        try:
            assert scorer.score(request, pods)[pods[0]] == 0.0
            _time.sleep(1.0)  # slow joiner
            from llm_d_kv_cache_manager_tpu.kvevents.events import (
                BlockStored,
            )

            tokens = indexer.tokenization_pool.tokenize(PROMPT, MODEL, None)
            publisher.publish(
                *[
                    BlockStored(
                        block_hashes=[0x7000 + i],
                        parent_block_hash=0x7000 + i - 1 if i else None,
                        token_ids=tokens[i * 4:(i + 1) * 4],
                        block_size=4,
                        lora_id=None,
                        medium="hbm",
                    )
                    for i in range(len(tokens) // 4)
                ]
            )
            deadline = _time.time() + 10
            score = 0.0
            while _time.time() < deadline and score == 0.0:
                score = scorer.score(request, pods)[pods[0]]
                _time.sleep(0.2)
            assert score == 1.0
            publisher.close()
        finally:
            scorer.shutdown()


# ----------------------------- pod reconciler -----------------------------


def make_pod(name, ip="10.1.0.1", phase="Running", ready=True, rv="1"):
    return {
        "kind": "Pod",
        "metadata": {
            "name": name,
            "namespace": "llm-d",
            "resourceVersion": rv,
            "labels": {"llm-d.ai/inferenceServing": "true"},
        },
        "status": {
            "phase": phase,
            "podIP": ip,
            "conditions": [
                {"type": "Ready", "status": "True" if ready else "False"}
            ],
        },
    }


class FakeKubeHandler(http.server.BaseHTTPRequestHandler):
    pods = []
    watch_events = []

    def log_message(self, *args):
        pass

    def do_GET(self):
        if "watch=true" in self.path:
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            for event in self.watch_events:
                self.wfile.write(json.dumps(event).encode() + b"\n")
            return
        body = json.dumps(
            {
                "kind": "PodList",
                "metadata": {"resourceVersion": "10"},
                "items": self.pods,
            }
        ).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


@pytest.fixture()
def fake_kube():
    server = http.server.ThreadingHTTPServer(
        ("127.0.0.1", 0), FakeKubeHandler
    )
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    thread.join(timeout=5)


class RecordingManager(SubscriberManager):
    """Real manager against unroutable endpoints; records calls."""

    def __init__(self):
        super().__init__(sink=lambda message: None)
        self.calls = []

    def ensure_subscriber(self, pod, endpoint, topic_filter=None):
        self.calls.append(("ensure", pod, endpoint))
        return super().ensure_subscriber(pod, endpoint, topic_filter)

    def remove_subscriber(self, pod):
        self.calls.append(("remove", pod))
        return super().remove_subscriber(pod)


class TestPodReconciler:
    def test_predicates(self):
        assert PodReconciler.should_subscribe(make_pod("a"))
        assert not PodReconciler.should_subscribe(
            make_pod("a", phase="Pending")
        )
        assert not PodReconciler.should_subscribe(make_pod("a", ip=""))
        assert not PodReconciler.should_subscribe(
            make_pod("a", ready=False)
        )

    def test_list_watch_converges_subscribers(self, fake_kube):
        FakeKubeHandler.pods = [
            make_pod("pod-a", ip="10.1.0.1"),
            make_pod("pod-b", ip="10.1.0.2", ready=False),
        ]
        FakeKubeHandler.watch_events = [
            {"type": "ADDED", "object": make_pod("pod-c", ip="10.1.0.3")},
            {"type": "DELETED", "object": make_pod("pod-a")},
        ]
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        reconciler.run_once()
        assert manager.active_pods() == ["llm-d/pod-c"]
        assert (
            "ensure",
            "llm-d/pod-a",
            "tcp://10.1.0.1:5557",
        ) in manager.calls
        manager.shutdown()

    def test_resync_removes_stale_only_reconciler_owned(self, fake_kube):
        FakeKubeHandler.pods = [make_pod("pod-a")]
        FakeKubeHandler.watch_events = []
        manager = RecordingManager()
        # Pre-existing subscribers: one reconciler-shaped, one manual.
        manager.ensure_subscriber("llm-d/ghost", "tcp://10.9.9.9:5557")
        manager.ensure_subscriber("local-subscriber", "tcp://10.9.9.8:5557")
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        reconciler.run_once()
        assert manager.active_pods() == ["llm-d/pod-a", "local-subscriber"]
        manager.shutdown()

    def test_endpoint_ipv6_brackets(self):
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(api_server="http://127.0.0.1:1", token="t"),
        )
        pod = make_pod("pod-a", ip="fd00::1")
        assert reconciler._endpoint(pod) == "tcp://[fd00::1]:5557"
        manager.shutdown()

    def test_watch_requests_server_side_timeout(self, fake_kube):
        """The watch must carry timeoutSeconds so the API server ends the
        stream periodically — the liveness bound against half-open TCP
        connections that would otherwise block the loop forever."""
        FakeKubeHandler.pods = []
        FakeKubeHandler.watch_events = []
        seen_paths = []
        original = FakeKubeHandler.do_GET

        def spy(handler):
            seen_paths.append(handler.path)
            original(handler)

        FakeKubeHandler.do_GET = spy
        try:
            manager = RecordingManager()
            reconciler = PodReconciler(
                manager,
                PodReconcilerConfig(
                    namespace="llm-d",
                    api_server=fake_kube,
                    token="t",
                    watch_timeout_seconds=123,
                ),
            )
            reconciler.run_once()
            watch_paths = [p for p in seen_paths if "watch=true" in p]
            assert watch_paths and "timeoutSeconds=123" in watch_paths[0]
            manager.shutdown()
        finally:
            FakeKubeHandler.do_GET = original

    def test_read_timeout_is_a_normal_stream_end(self):
        """A dead (half-open) stream raises TimeoutError mid-iteration;
        run_once must swallow it and return so the loop re-lists."""
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(api_server="http://127.0.0.1:1", token="t"),
        )

        class DeadStreamClient:
            def list_pods(self):
                return {"metadata": {"resourceVersion": "1"}, "items": []}

            def watch_pods(self, resource_version):
                yield {
                    "type": "ADDED",
                    "object": make_pod("pod-a", ip="10.0.0.1"),
                }
                raise TimeoutError("read timed out")

        reconciler.client = DeadStreamClient()
        reconciler.run_once()  # must not raise
        assert manager.active_pods() == ["llm-d/pod-a"]
        manager.shutdown()


class TestReconcilerChaos:
    """Garbled watch events must not abort the watch: type-confused
    lines are skipped per-event (kvevents-pool poison philosophy) and
    later valid events still converge the subscriber set."""

    def test_garbage_events_skipped_valid_ones_applied(self, fake_kube):
        FakeKubeHandler.pods = []
        FakeKubeHandler.watch_events = [
            42,  # not an object
            "nope",
            [1, 2, 3],
            {"type": "ADDED", "object": "not-a-pod"},
            {"type": "ADDED", "object": {"status": "confused"}},
            {"type": 7, "object": {}},
            {"type": "ADDED", "object": make_pod("pod-z", ip="10.1.0.9")},
        ]
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        reconciler.run_once()
        # The single valid event at the end of the garbled stream landed.
        assert manager.active_pods() == ["llm-d/pod-z"]
        manager.shutdown()

    def test_poison_pod_in_list_does_not_wedge_resync(self, fake_kube):
        """A malformed pod in the LIST response (run_once re-lists
        first, every cycle) must be skipped per-item — otherwise the
        reconciler wedges for as long as the bad item exists."""
        FakeKubeHandler.pods = [
            42,
            {"metadata": {"name": "bad"}, "status": "confused"},
            # Dict pod whose metadata itself is type-confused: the key
            # computation runs OUTSIDE the per-item try (seen-marking),
            # so _pod_key must tolerate these rather than raise and
            # abort the whole resync.
            {"metadata": None, "status": {"phase": "Running"}},
            {"metadata": "nope", "status": {"phase": "Running"}},
            {"metadata": [1, 2], "status": {"phase": "Running"}},
            make_pod("pod-good", ip="10.1.0.7"),
        ]
        FakeKubeHandler.watch_events = []
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        reconciler.run_once()
        assert manager.active_pods() == ["llm-d/pod-good"]
        manager.shutdown()

    def test_malformed_list_response_does_not_raise(self, fake_kube):
        """Go serializes an empty slice as null ({"items": null}); a
        proxy may mangle worse.  reconcile_list must tolerate a
        type-confused items/metadata field — run_once re-lists first
        every cycle, so raising here wedges the reconciler for as long
        as the response shape persists."""
        manager = RecordingManager()
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        for bad_list in (
            {"items": None, "metadata": None},
            {"items": "nope", "metadata": "nope"},
            {"items": 42, "metadata": {"resourceVersion": 7}},
            {},
            None,
            "garbage",
        ):
            version = reconciler.reconcile_list(bad_list)
            assert isinstance(version, str)
        manager.shutdown()

    def test_failed_reconcile_does_not_prune_existing_subscriber(
        self, fake_kube
    ):
        """A pod PRESENT in the list whose reconcile raises (transient
        failure, type confusion) keeps its existing subscription — the
        stale-prune must only remove pods absent from the response."""
        FakeKubeHandler.pods = [
            {
                "metadata": {"namespace": "llm-d", "name": "flaky"},
                "status": "confused",  # reconcile raises on this
            },
        ]
        FakeKubeHandler.watch_events = []
        manager = RecordingManager()
        manager.ensure_subscriber("llm-d/flaky", "tcp://10.3.0.1:5557")
        reconciler = PodReconciler(
            manager,
            PodReconcilerConfig(
                namespace="llm-d", api_server=fake_kube, token="t"
            ),
        )
        reconciler.run_once()
        assert manager.active_pods() == ["llm-d/flaky"]
        manager.shutdown()
