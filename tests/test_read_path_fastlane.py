"""Read-path fast lane: memoization, early exit, sharding, batching.

The fast lane (docs/performance.md) restructures the scoring read path
— memoized block keys from the prefix store, chunked early-exit
hashing/lookup, lock-striped index shards, batched kvevents applies —
under ONE invariant: scores must be bit-identical to the straight-line
path.  These tests pin that invariant property-style, plus the
correctness of each layer's machinery.
"""

import random

import pytest

from llm_d_kv_cache_manager_tpu.kvcache.indexer import (
    DEFAULT_LOOKUP_CHUNK,
    Indexer,
    IndexerConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.cbor_canonical import (
    encode_chunk_payload,
    encode_hash_payload,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.cost_aware import (
    CostAwareMemoryIndex,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
    InMemoryIndex,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
    CostAwareIndexConfig,
    IndexConfig,
    InMemoryIndexConfig,
    PodEntry,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
    EMPTY_BLOCK_HASH,
    ChunkedTokenDatabase,
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.scorer import (
    LongestPrefixScorer,
)
from llm_d_kv_cache_manager_tpu.kvevents.events import (
    BlockRemoved,
    BlockStored,
    EventBatch,
)
from llm_d_kv_cache_manager_tpu.kvevents.pool import (
    Message,
    Pool,
    PoolConfig,
)
from llm_d_kv_cache_manager_tpu.tokenization.prefixstore.lru_store import (
    LRUStoreConfig,
    LRUTokenStore,
)
from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import Encoding

POD_A = PodEntry("pod-a", "hbm")
POD_B = PodEntry("pod-b", "host")
POD_C = PodEntry("pod-c", "hbm")


class WordTokenizer:
    """Deterministic test tokenizer: 't<id>' words -> stable ids with
    exact byte offsets (what the prefix store needs)."""

    def type(self) -> str:
        return "test-word"

    def encode(self, prompt, model_name, add_special_tokens):
        tokens, offsets, pos = [], [], 0
        for word in prompt.split(" "):
            tokens.append(int(word[1:]) if word and word[0] == "t" else 0)
            offsets.append((pos, pos + len(word)))
            pos += len(word) + 1
        return Encoding(tokens=tokens, offsets=offsets)


def words(tokens):
    return " ".join(f"t{t}" for t in tokens)


# ---------------------------------------------------------------- hashing


class TestChunkPayloadEncoder:
    def test_matches_generic_encoder_randomized(self):
        rng = random.Random(7)
        boundary = [0, 1, 23, 24, 255, 256, 65535, 65536, 2**32 - 1,
                    2**32, 2**64 - 1]
        for trial in range(200):
            parent = rng.choice(boundary + [rng.getrandbits(64)])
            n = rng.randrange(0, 48)
            tokens = [
                rng.choice(boundary + [rng.randrange(0, 200_000)])
                for _ in range(n)
            ]
            fast = bytes(encode_chunk_payload(parent, tokens))
            generic = encode_hash_payload(parent, tokens, None)
            assert fast == generic, (trial, parent, tokens)

    def test_rejects_oversized_ints_like_generic(self):
        with pytest.raises(ValueError):
            encode_chunk_payload(2**64, [1])


class TestExtendBlockKeys:
    @pytest.mark.parametrize("use_native", [False, True])
    @pytest.mark.parametrize("block_size", [2, 4, 16])
    @pytest.mark.parametrize("seed", ["", "fleet-seed"])
    def test_resume_bit_identical_to_fresh(
        self, use_native, block_size, seed
    ):
        """Property: extend_block_keys off any full-block split point
        reproduces the fresh full-chain hash bit for bit."""
        db = ChunkedTokenDatabase(
            TokenProcessorConfig(block_size=block_size, hash_seed=seed),
            use_native=use_native,
        )
        rng = random.Random(block_size * 1000 + len(seed))
        for model in ("model-a", "model-b"):
            tokens = [rng.randrange(0, 70_000) for _ in range(
                rng.randrange(block_size, 40 * block_size))]
            fresh = db.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, model
            )
            for _ in range(6):
                cut_blocks = rng.randrange(0, len(fresh) + 1)
                prefix = fresh[:cut_blocks]
                parent = prefix[-1] if prefix else EMPTY_BLOCK_HASH
                resumed = prefix + db.extend_block_keys(
                    parent, tokens[cut_blocks * block_size:], model
                )
                assert resumed == fresh, (model, cut_blocks)

    def test_key_space_distinguishes_configs(self):
        a = ChunkedTokenDatabase(TokenProcessorConfig(block_size=16))
        b = ChunkedTokenDatabase(TokenProcessorConfig(block_size=32))
        c = ChunkedTokenDatabase(
            TokenProcessorConfig(block_size=16, hash_seed="x")
        )
        assert a.key_space != b.key_space
        assert a.key_space != c.key_space
        assert a.key_space == ChunkedTokenDatabase(
            TokenProcessorConfig(block_size=16)
        ).key_space


# ------------------------------------------------------ prefix-store memo


class TestPrefixStoreBlockKeyMemo:
    def _store_with(self, tokens, model="m", chunk_bytes=32):
        store = LRUTokenStore(LRUStoreConfig(block_size=chunk_bytes))
        prompt = words(tokens)
        enc = WordTokenizer().encode(prompt, model, True)
        store.add_tokenization(prompt, enc.tokens, enc.offsets, model)
        return store, prompt

    def test_attach_then_probe_returns_keys(self):
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        tokens = list(range(100, 164))
        store, prompt = self._store_with(tokens)
        keys = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, tokens, "m")
        written = store.attach_block_keys(
            prompt, "m", db.key_space, keys, tokens
        )
        assert written > 0
        probe = store.probe(prompt, "m", db.key_space)
        assert probe.blocks > 0
        assert probe.blocks <= len(probe.tokens) // 4
        # The memoized keys ARE the chain prefix, bit for bit.
        assert list(probe.keys) == keys[: probe.blocks]

    def test_probe_without_key_space_skips_memo(self):
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        tokens = list(range(64))
        store, prompt = self._store_with(tokens)
        keys = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, tokens, "m")
        store.attach_block_keys(prompt, "m", db.key_space, keys, tokens)
        probe = store.probe(prompt, "m")
        assert probe.blocks == 0 and probe.keys == ()
        assert probe.tokens  # token resolution unaffected

    def test_key_spaces_never_alias(self):
        """Keys attached under one (seed, block size) space must not
        serve another: a config change re-hashes, never replays."""
        db16 = ChunkedTokenDatabase(TokenProcessorConfig(block_size=16))
        db4 = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        tokens = list(range(64))
        store, prompt = self._store_with(tokens)
        keys16 = db16.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, tokens, "m")
        store.attach_block_keys(
            prompt, "m", db16.key_space, keys16, tokens
        )
        probe4 = store.probe(prompt, "m", db4.key_space)
        assert probe4.blocks == 0 and probe4.keys == ()
        probe16 = store.probe(prompt, "m", db16.key_space)
        assert list(probe16.keys) == keys16[: probe16.blocks]

    def test_longer_prompt_resumes_from_deepest_record(self):
        """A grown conversation probes back the old prefix's keys: only
        the suffix still needs hashing — the memoization contract."""
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        base = list(range(200, 264))
        store, base_prompt = self._store_with(base)
        base_keys = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, base, "m")
        store.attach_block_keys(
            base_prompt, "m", db.key_space, base_keys, base
        )

        grown = base + list(range(500, 532))
        grown_prompt = words(grown)
        enc = WordTokenizer().encode(grown_prompt, "m", True)
        # A full re-tokenization installs fresh chunk tuples, so the
        # old anchors no longer validate — memo is (conservatively)
        # rejected until the next attach, which is exactly what the
        # indexer does after re-hashing.
        store.add_tokenization(grown_prompt, enc.tokens, enc.offsets, "m")
        rejected = store.probe(grown_prompt, "m", db.key_space)
        assert rejected.blocks == 0
        grown_keys = db.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, rejected.tokens, "m"
        )
        store.attach_block_keys(
            grown_prompt, "m", db.key_space, grown_keys, rejected.tokens
        )

        probe = store.probe(grown_prompt, "m", db.key_space)
        assert probe.blocks > 0
        # Resume off the memo and compare against a fresh full chain.
        full = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, probe.tokens, "m")
        resumed = list(probe.keys) + db.extend_block_keys(
            probe.keys[-1], probe.tokens[probe.blocks * 4:], "m"
        )
        assert resumed == full

    def test_stale_record_rejected_when_token_split_changes(self):
        """A later tokenization of a longer prompt can overwrite a
        shared chunk's token tuple with a DIFFERENT boundary split
        (add_tokenization assigns straddling tokens to the later
        chunk).  A memo record attached under the old split must then
        be rejected — serving its keys against the new token stream
        would silently change scores vs the straight path."""
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        store = LRUTokenStore(LRUStoreConfig(block_size=8))
        prompt = "abcdefgh" * 4  # 4 chunks of 8 bytes

        # Tokenization 1: two 4-byte tokens per chunk.
        tokens_a = list(range(100, 108))
        offsets_a = [(i * 4, (i + 1) * 4) for i in range(8)]
        store.add_tokenization(prompt, tokens_a, offsets_a, "m")
        keys_a = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, tokens_a, "m")
        assert store.attach_block_keys(
            prompt, "m", db.key_space, keys_a, tokens_a
        )
        probe = store.probe(prompt, "m", db.key_space)
        assert probe.blocks > 0  # record served while split matches

        # Tokenization 2: same bytes, different split (8 one-byte
        # tokens then 4-byte tokens) — overwrites the shared chunks.
        tokens_b = list(range(500, 508)) + list(range(600, 606))
        offsets_b = [(i, i + 1) for i in range(8)] + [
            (8 + i * 4, 8 + (i + 1) * 4) for i in range(6)
        ]
        store.add_tokenization(prompt, tokens_b, offsets_b, "m")

        probe2 = store.probe(prompt, "m", db.key_space)
        # The stale record must NOT pair keys_a with tokens_b.
        assert probe2.blocks == 0 and probe2.keys == ()
        assert probe2.tokens[: len(tokens_b)] == tokens_b[
            : len(probe2.tokens)
        ]


# ----------------------------------------------------- incremental scorer


class TestIncrementalScorer:
    WEIGHTS = {"hbm": 1.0, "host": 0.8, "shared_storage": 0.5}

    def _random_case(self, rng):
        n_keys = rng.randrange(0, 24)
        keys = list(range(1, n_keys + 1))
        pods = ["pod-a", "pod-b", "pod-c"]
        tiers = list(self.WEIGHTS) + ["unknown-tier"]
        key_to_pods = {}
        for key in keys:
            if rng.random() < 0.15:
                continue  # missing key
            entries = [
                PodEntry(rng.choice(pods), rng.choice(tiers))
                for _ in range(rng.randrange(0, 4))
            ]
            key_to_pods[key] = entries
        return keys, key_to_pods

    def test_chunked_advance_equals_score(self):
        scorer = LongestPrefixScorer(self.WEIGHTS)
        rng = random.Random(11)
        for trial in range(300):
            keys, key_to_pods = self._random_case(rng)
            expected = scorer.score(keys, key_to_pods)
            chain = scorer.begin()
            position = 0
            while position < len(keys):
                step = rng.randrange(1, 6)
                chunk = keys[position:position + step]
                pods_per_key = [key_to_pods.get(k, ()) for k in chunk]
                if not scorer.advance(chain, pods_per_key):
                    break
                position += step
            assert chain.scores == expected, trial

    def test_advance_with_filter_equals_filtered_score(self):
        """Filtering inside advance ≡ filtering before score (what the
        legacy lookup did)."""
        scorer = LongestPrefixScorer(self.WEIGHTS)
        rng = random.Random(13)
        for trial in range(200):
            keys, key_to_pods = self._random_case(rng)
            pod_set = set(rng.sample(["pod-a", "pod-b", "pod-c"],
                                     rng.randrange(0, 4)))
            filtered = {
                k: [e for e in v if e.pod_identifier in pod_set]
                for k, v in key_to_pods.items()
            }
            filtered = {k: v for k, v in filtered.items() if v}
            expected = scorer.score(keys, filtered)
            chain = scorer.begin()
            scorer.advance(
                chain,
                [key_to_pods.get(k, ()) for k in keys],
                pod_set or None,
            )
            if pod_set:
                assert chain.scores == expected, trial

    def test_advance_reports_dead_chain(self):
        scorer = LongestPrefixScorer(self.WEIGHTS)
        chain = scorer.begin()
        assert scorer.advance(chain, [[POD_A], [POD_A]])
        assert chain.alive
        assert not scorer.advance(chain, [[POD_B]])  # disjoint pod
        assert not chain.alive
        # Feeding more after death stays dead and changes nothing.
        scores_before = dict(chain.scores)
        assert not scorer.advance(chain, [[POD_A]])
        assert chain.scores == scores_before

    def test_resolve_cache_invalidates_on_new_snapshot(self):
        """The identity-keyed weight cache must never serve a mutated
        pod set: a new snapshot tuple resolves fresh."""
        scorer = LongestPrefixScorer(self.WEIGHTS)
        index = InMemoryIndex(InMemoryIndexConfig(size=64))
        index.add([1], [1], [POD_A])
        first = index.lookup_chain([1])
        chain = scorer.begin()
        scorer.advance(chain, first)
        assert chain.scores == {"pod-a": 1.0}
        index.add([1], [1], [POD_B])  # mutates -> new snapshot
        second = index.lookup_chain([1])
        chain2 = scorer.begin()
        scorer.advance(chain2, second)
        assert chain2.scores == {"pod-a": 1.0, "pod-b": 0.8}


# ----------------------------------------------------- sharded index


class TestShardedIndex:
    def test_lookup_chain_stops_at_missing_key(self):
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        index.add([1, 2], [1, 2], [POD_A])
        index.add([9], [9], [POD_A])
        chain = index.lookup_chain([1, 2, 5, 9])
        assert len(chain) == 2
        assert [set(c) for c in chain] == [{POD_A}, {POD_A}]

    def test_lookup_chain_stops_at_empty_pod_cache(self):
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        index.add([1, 2, 3], [1, 2, 3], [POD_A])
        index._shard(2).get(2).remove_all([POD_A])
        assert len(index.lookup_chain([1, 2, 3])) == 1

    def test_lookup_chain_default_adapter_on_cost_aware(self):
        """Backends without an override answer lookup_chain through
        the dict-based default — same truncation semantics."""
        index = CostAwareMemoryIndex(CostAwareIndexConfig())
        index.add([1, 2], [1, 2], [POD_A])
        index.add([9], [9], [POD_B])
        chain = index.lookup_chain([1, 2, 5, 9])
        assert len(chain) == 2

    @pytest.mark.parametrize("src_shards,dst_shards", [(1, 8), (8, 1),
                                                       (4, 8)])
    def test_cross_shard_dump_restore(self, src_shards, dst_shards):
        """A dump from one shard layout restores into any other: keys
        re-shard by value, lookups agree."""
        source = InMemoryIndex(
            InMemoryIndexConfig(size=10_000, shards=src_shards)
        )
        rng = random.Random(5)
        keys = [rng.getrandbits(64) for _ in range(200)]
        for i, key in enumerate(keys):
            source.add(
                [key ^ 0xABCD], [key],
                [POD_A if i % 2 else POD_B, POD_C][: 1 + i % 2],
            )
        block_entries, engine_map = source.dump_entries()
        assert len(block_entries) == len(keys)

        restored = InMemoryIndex(
            InMemoryIndexConfig(size=10_000, shards=dst_shards)
        )
        count = restored.restore_entries(block_entries, engine_map)
        assert count == len(keys)
        for key in keys:
            assert restored.lookup([key]) == source.lookup([key])
            assert restored.get_request_key(key ^ 0xABCD) == key

    def test_cross_shard_purge_pod(self):
        index = InMemoryIndex(InMemoryIndexConfig(size=10_000, shards=8))
        rng = random.Random(6)
        keys = [rng.getrandbits(64) for _ in range(300)]
        solo, shared = [], []
        for key in keys:
            if key % 3 == 0:
                index.add([key], [key], [POD_A])
                solo.append(key)
            else:
                index.add([key], [key], [POD_A, POD_B])
                shared.append(key)
        removed = index.purge_pod("pod-a")
        assert removed == len(keys)
        # Keys held only by the purged pod vanish entirely (an empty
        # pod set would break other pods' chains at lookup)...
        for key in solo:
            assert index.lookup([key]) == {}
        # ...while co-held keys keep the surviving pod.
        for key in shared:
            assert index.lookup([key]) == {key: [POD_B]}

    def test_shard_count_rounds_to_power_of_two(self):
        assert len(InMemoryIndex(
            InMemoryIndexConfig(shards=3))._shards) == 4
        assert len(InMemoryIndex(
            InMemoryIndexConfig(shards=8))._shards) == 8
        assert len(InMemoryIndex(
            InMemoryIndexConfig(shards=0))._shards) == 1

    def test_filtered_lookup_skips_copy_only_when_covered(self):
        index = InMemoryIndex(InMemoryIndexConfig(size=100))
        index.add([1], [1], [POD_A, POD_B])
        # Filter covers everything -> both entries back.
        assert set(index.lookup([1], {"pod-a", "pod-b"})[1]) == {
            POD_A, POD_B,
        }
        # Filter drops one -> filtered copy.
        assert index.lookup([1], {"pod-a"}) == {1: [POD_A]}
        # Filter drops all -> key absent (not an empty list).
        assert index.lookup([1], {"pod-z"}) == {}


# ----------------------------------------------------- batched kvevents


def _stored_message(pod, seq, engine_base, tokens, block_size=4,
                    parent=None, model="m"):
    event = BlockStored(
        block_hashes=[engine_base + i for i in range(
            len(tokens) // block_size)],
        parent_block_hash=parent,
        token_ids=tokens,
        block_size=block_size,
        medium="hbm",
    )
    batch = EventBatch(ts=1.0, events=[event])
    return Message(
        topic=f"kv@{pod}@{model}",
        payload=batch.encode(),
        pod_identifier=pod,
        model_name=model,
        seq=seq,
    )


class TestBatchedEventApply:
    @pytest.mark.parametrize("backend", ["in_memory", "cost_aware"])
    def test_batched_apply_equals_sequential(self, backend):
        """Flooding the pool before start forces multi-message batches;
        the applied state must equal a one-message-at-a-time pool's."""
        def build(batch_size):
            db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
            if backend == "in_memory":
                index = InMemoryIndex(InMemoryIndexConfig(size=100_000))
            else:
                index = CostAwareMemoryIndex(CostAwareIndexConfig())
            pool = Pool(index, db, PoolConfig(
                concurrency=2, apply_batch_size=batch_size))
            return index, pool

        results = []
        for batch_size in (1, 16):
            index, pool = build(batch_size)
            rng = random.Random(3)
            for pod_i in range(4):
                pod = f"pod-{pod_i}"
                for seq in range(12):
                    tokens = [rng.randrange(0, 5000) for _ in range(16)]
                    pool.add_task(_stored_message(
                        pod, seq, (pod_i + 1) * 10_000 + seq * 100, tokens))
            pool.start()
            pool.drain()
            pool.shutdown()
            results.append(index)

        sequential, batched = results
        s_entries, s_map = sequential.dump_entries()
        b_entries, b_map = batched.dump_entries()
        assert dict(s_map) == dict(b_map)
        assert {k: set(v) for k, v in s_entries} == {
            k: set(v) for k, v in b_entries
        }

    def test_add_then_evict_in_one_batch_stays_evicted(self):
        """The eviction barrier: an add and its evict drained in the
        same batch must apply in order."""
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        pool = Pool(index, db, PoolConfig(
            concurrency=1, apply_batch_size=64))
        tokens = list(range(8))
        pool.add_task(_stored_message("pod-x", 0, 500, tokens))
        removed = BlockRemoved(block_hashes=[500, 501], medium="hbm")
        pool.add_task(Message(
            topic="kv@pod-x@m",
            payload=EventBatch(ts=2.0, events=[removed]).encode(),
            pod_identifier="pod-x",
            model_name="m",
            seq=1,
        ))
        pool.start()
        pool.drain()
        pool.shutdown()
        request_keys = db.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, tokens, "m")
        for key in request_keys:
            assert index.lookup([key]) == {}

    def test_parent_chain_resolves_within_one_batch(self):
        """Eager engine-map publication: a child event whose parent
        arrived in the SAME drained batch still chains correctly."""
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        pool = Pool(index, db, PoolConfig(
            concurrency=1, apply_batch_size=64))
        pool.add_task(_stored_message("pod-y", 0, 700, list(range(4))))
        pool.add_task(_stored_message(
            "pod-y", 1, 701, list(range(4, 8)), parent=700))
        pool.start()
        pool.drain()
        pool.shutdown()
        full = db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH,
                                          list(range(8)), "m")
        assert index.get_request_key(701) == full[1]
        assert index.lookup([full[1]]) != {}

    def test_flush_failure_never_journals_orphaned_adds(self):
        """A failed add flush must drop the deferred journal records
        with it: a later flush journaling admissions the index never
        held would corrupt warm restarts."""
        from llm_d_kv_cache_manager_tpu.kvevents.pool import _BatchApplier

        class ExplodingIndex(InMemoryIndex):
            def __init__(self):
                super().__init__(InMemoryIndexConfig(size=100))
                self.explode = True

            def add_entries_batch(self, items):
                if self.explode:
                    raise RuntimeError("backend down")
                super().add_entries_batch(items)

        class RecordingJournal:
            def __init__(self):
                self.adds = []

            def record_add(self, *args):
                self.adds.append(args)

        journal = RecordingJournal()
        index = ExplodingIndex()
        applier = _BatchApplier(index, journal)
        applier.add("pod-a", 0, [1], [1], [POD_A])
        with pytest.raises(RuntimeError):
            applier.flush()
        # The failed batch's records died with it; a later successful
        # flush journals only ITS adds.
        index.explode = False
        applier.add("pod-a", 1, [2], [2], [POD_A])
        applier.flush()
        assert [args[1] for args in journal.adds] == [1]  # seq 1 only
        assert index.lookup([2]) == {2: [POD_A]}

    def test_barrier_flush_failure_errors_earlier_message_traces(self):
        """A mid-batch eviction-barrier flush failure discards EARLIER
        messages' deferred admissions; their traces must finish errored
        — an "ok" trace for admissions that never landed would hide the
        loss from the flight recorder."""
        from llm_d_kv_cache_manager_tpu.obs.trace import (
            Tracer,
            TracerConfig,
        )

        class ExplodingIndex(InMemoryIndex):
            def __init__(self):
                super().__init__(InMemoryIndexConfig(size=1000))
                self.explode = True

            def add_entries_batch(self, items):
                if self.explode:
                    self.explode = False
                    raise RuntimeError("backend down")
                super().add_entries_batch(items)

        tracer = Tracer(TracerConfig(sample_rate=1.0))
        stored_trace = tracer.start_trace("kvevents.message", force=True)
        removed_trace = tracer.start_trace("kvevents.message", force=True)
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        index = ExplodingIndex()
        pool = Pool(index, db, PoolConfig(
            concurrency=1, apply_batch_size=64))
        stored = _stored_message("pod-x", 0, 500, list(range(4)))
        stored.trace = stored_trace
        removed = BlockRemoved(block_hashes=[500], medium="hbm")
        pool.add_task(stored)
        pool.add_task(Message(
            topic="kv@pod-x@m",
            payload=EventBatch(ts=2.0, events=[removed]).encode(),
            pod_identifier="pod-x",
            model_name="m",
            seq=1,
            trace=removed_trace,
        ))
        pool.start()
        pool.drain()
        # The stored message's add was discarded by the failed barrier
        # flush: its trace is errored, NOT ok, and the worker survived
        # (drain returned).
        assert stored_trace.status == "error"
        assert removed_trace.status == "error"
        later = _stored_message("pod-x", 2, 600, list(range(4, 8)))
        pool.add_task(later)
        pool.drain()
        pool.shutdown()
        keys = db.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, list(range(4, 8)), "m")
        assert index.lookup([keys[0]]) != {}

    def test_worker_survives_exception_outside_message_guards(self):
        """An exception escaping the per-message guards (here: the
        batch-size histogram observe) must not kill the shard worker —
        a dead worker silently sheds every later event for its pods."""
        from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        pool = Pool(index, db, PoolConfig(concurrency=1))
        original = METRICS.kvevents_batch_size.observe
        calls = {"n": 0}

        def observe_once_broken(value):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("metrics backend down")
            original(value)

        METRICS.kvevents_batch_size.observe = observe_once_broken
        try:
            pool.add_task(_stored_message("pod-w", 0, 800, list(range(4))))
            pool.start()
            pool.drain()  # first batch dropped, worker alive
            pool.add_task(_stored_message("pod-w", 1, 810, list(range(4))))
            pool.drain()
            pool.shutdown()
        finally:
            METRICS.kvevents_batch_size.observe = original
        keys = db.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, list(range(4)), "m")
        assert index.lookup([keys[0]]) != {}

    def test_batch_size_histogram_observed(self):
        from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

        def histogram_count():
            total = 0.0
            for metric in METRICS.kvevents_batch_size.collect():
                for sample in metric.samples:
                    if sample.name.endswith("_count"):
                        total += sample.value
            return total

        before = histogram_count()
        db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=4))
        index = InMemoryIndex(InMemoryIndexConfig(size=1000))
        pool = Pool(index, db, PoolConfig(concurrency=1))
        pool.add_task(_stored_message("pod-z", 0, 900, list(range(4))))
        pool.start()
        pool.drain()
        pool.shutdown()
        assert histogram_count() > before


# ----------------------------------------------------- end-to-end parity


def make_indexer(fast, block_size=16, shards=8, chunk=8):
    indexer = Indexer(
        IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size=block_size
            ),
            kvblock_index_config=IndexConfig(
                in_memory_config=InMemoryIndexConfig(
                    size=200_000, shards=shards
                )
            ),
            read_path_fast_lane=fast,
            lookup_chunk_size=chunk,
        ),
        tokenizer=WordTokenizer(),
    )
    indexer.run()
    return indexer


class TestFullGeometryWork:
    """What one cold scoring call does at the benchmark's full geometry
    (8192 + 256 tokens = 528 blocks of 16, the default lookup chunk),
    counted, not timed: a lost early exit, a chain hashed twice or a
    round per block shows here on any machine.  On the chip the same
    call is timed by the cell mistral7b-docs-shared
    (router_score_p50_s, router_index_lookup_p50_s, router_rank_p50_s)."""

    TOKENS = 8192 + 256
    BLOCKS = TOKENS // 16

    @pytest.mark.parametrize(
        "held_from, rounds, keys_walked, score",
        [
            # Held whole: chunks of 32, 64, 128, 256, then the last 48.
            (0, 5, 528, 528.0),
            # Dead at block 0 (every later block held): one chunk, out.
            (1, 1, DEFAULT_LOOKUP_CHUNK, None),
        ],
    )
    def test_rounds_and_keys_of_one_cold_call(
        self, held_from, rounds, keys_walked, score
    ):
        indexer = make_indexer(True, chunk=DEFAULT_LOOKUP_CHUNK)
        try:
            rng = random.Random(31)
            tokens = [rng.randrange(1, 60_000) for _ in range(self.TOKENS)]
            keys = indexer.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            assert len(keys) == self.BLOCKS
            indexer.kv_block_index.add(
                keys[held_from:], keys[held_from:], [POD_A]
            )
            hashed, looked_up = [], []
            processor, index = indexer.token_processor, indexer.kv_block_index
            extend, lookup = processor.extend_block_keys, index.lookup_chain

            def counting_extend(parent, suffix, model):
                out = extend(parent, suffix, model)
                hashed.append(len(out))
                return out

            def counting_lookup(chain):
                looked_up.append(len(chain))
                return lookup(chain)

            processor.extend_block_keys = counting_extend
            index.lookup_chain = counting_lookup
            scores = indexer.get_pod_scores(words(tokens), "m")
            assert scores == ({} if score is None else {"pod-a": score})
            assert len(looked_up) == rounds
            assert sum(looked_up) == sum(hashed) == keys_walked
        finally:
            indexer.shutdown()


class TestFastLaneParity:
    """Acceptance: get_pod_scores with the fast lane ≡ with it
    disabled, across multi-turn growth, tier mixes, pod filters, and
    broken chains."""

    def test_multi_turn_and_randomized_parity(self):
        fast = make_indexer(True)
        straight = make_indexer(False)
        pods = [f"pod-{i}" for i in range(4)]
        try:
            rng = random.Random(42)
            base = [rng.randrange(1, 60_000) for _ in range(800)]
            convo = list(base)
            for _ in range(5):  # seed both indexes, then grow
                for ix in (fast, straight):
                    keys = ix.token_processor.tokens_to_kv_block_keys(
                        EMPTY_BLOCK_HASH, convo, "m"
                    )
                    ix.kv_block_index.add(
                        keys, keys, [PodEntry("pod-0", "hbm")]
                    )
                    ix.kv_block_index.add(
                        keys[: len(keys) // 2], keys[: len(keys) // 2],
                        [PodEntry("pod-1", "host")],
                    )
                prompt = words(convo)
                for flt in (None, pods, pods[:2], ["pod-404"]):
                    # First pass: both cold (full tokenizer run).
                    a = fast.get_pod_scores(prompt, "m", flt)
                    b = straight.get_pod_scores(prompt, "m", flt)
                    assert a == b, (len(convo), flt, a, b)
                    # Warm pass: both sides now serve tokens from the
                    # prefix store (which covers only full text chunks
                    # — a pre-existing fast-path property, identical
                    # for both lanes) and the fast side adds memoized
                    # keys.  Warm-vs-warm must still agree exactly.
                    a2 = fast.get_pod_scores(prompt, "m", flt)
                    b2 = straight.get_pod_scores(prompt, "m", flt)
                    assert a2 == b2, (len(convo), flt, a2, b2)
                convo.extend(
                    rng.randrange(1, 60_000) for _ in range(48)
                )

            # Randomized partial/broken chains.
            for trial in range(25):
                t2 = [rng.randrange(1, 60_000)
                      for _ in range(rng.randrange(0, 400))]
                prompt = words(t2) if t2 else "t1"
                cut = rng.random()
                tier = rng.choice(["hbm", "host", "cpu", "weird"])
                pod = rng.choice(pods)
                for ix in (fast, straight):
                    keys = ix.token_processor.tokens_to_kv_block_keys(
                        EMPTY_BLOCK_HASH, t2, "m"
                    )
                    if keys:
                        c = max(1, int(cut * len(keys)))
                        ix.kv_block_index.add(
                            keys[:c], keys[:c], [PodEntry(pod, tier)]
                        )
                flt = rng.choice([None, pods, pods[:2]])
                a = fast.get_pod_scores(prompt, "m", flt)
                b = straight.get_pod_scores(prompt, "m", flt)
                assert a == b, (trial, a, b)
        finally:
            fast.shutdown()
            straight.shutdown()

    def test_empty_prompt_and_subblock_prompt(self):
        fast = make_indexer(True)
        try:
            assert fast.get_pod_scores("t1 t2", "m") == {}  # < one block
        finally:
            fast.shutdown()

    def test_env_knob_disables_fast_lane(self, monkeypatch):
        monkeypatch.setenv("READ_PATH_FAST_LANE", "0")
        indexer = Indexer(IndexerConfig(), tokenizer=WordTokenizer())
        assert indexer._fast_lane is False
        monkeypatch.setenv("READ_PATH_FAST_LANE", "1")
        indexer = Indexer(IndexerConfig(), tokenizer=WordTokenizer())
        assert indexer._fast_lane is True
        monkeypatch.delenv("READ_PATH_FAST_LANE")
        indexer = Indexer(IndexerConfig(), tokenizer=WordTokenizer())
        assert indexer._fast_lane is True
        # Explicit config wins over env.
        monkeypatch.setenv("READ_PATH_FAST_LANE", "1")
        indexer = Indexer(
            IndexerConfig(read_path_fast_lane=False),
            tokenizer=WordTokenizer(),
        )
        assert indexer._fast_lane is False

    def test_protocol_only_processor_falls_back_to_straight_path(self):
        """A custom TokenProcessor implementing only the Protocol
        (tokens_to_kv_block_keys) must still work: the fast lane needs
        block_size/extend_block_keys, so the Indexer silently takes
        the straight path instead of crashing."""

        class MinimalProcessor:
            def __init__(self):
                self._db = ChunkedTokenDatabase(
                    TokenProcessorConfig(block_size=16)
                )

            def tokens_to_kv_block_keys(self, parent, tokens, model):
                return self._db.tokens_to_kv_block_keys(
                    parent, tokens, model
                )

        indexer = Indexer(
            IndexerConfig(read_path_fast_lane=True),
            token_processor=MinimalProcessor(),
            tokenizer=WordTokenizer(),
        )
        indexer.run()
        try:
            assert indexer._fast_lane is False
            tokens = list(range(100, 164))
            keys = indexer.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            indexer.kv_block_index.add(keys, keys, [POD_A])
            scores = indexer.get_pod_scores(words(tokens), "m")
            assert scores == {"pod-a": float(len(keys))}
        finally:
            indexer.shutdown()

    def test_explain_matches_fast_lane_scores(self):
        """The explain surface (straight path) must report the same
        scores the fast lane routes on."""
        fast = make_indexer(True)
        try:
            rng = random.Random(9)
            tokens = [rng.randrange(1, 60_000) for _ in range(320)]
            keys = fast.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            fast.kv_block_index.add(keys, keys, [PodEntry("pod-0", "hbm")])
            prompt = words(tokens)
            fast.get_pod_scores(prompt, "m")  # warm the prefix store
            # Warm on both surfaces: the same token stream feeds the
            # fast lane and the explain (straight) path.
            scores = fast.get_pod_scores(prompt, "m")
            explained, _ = fast.get_pod_scores_explained(prompt, "m")
            assert scores == explained
        finally:
            fast.shutdown()


# ----------------------------------------------------- request score memo


class TestScoreMemo:
    """The request score memo: an exact-prompt repeat serves memoized
    scores when the index's per-shard version vector (and the served
    token count) is unchanged — and ONLY then, so scores stay
    bit-identical to a fresh walk through every mutation."""

    def test_memo_serves_without_walking_and_invalidates_on_mutation(
        self,
    ):
        indexer = make_indexer(True)
        straight = make_indexer(False)
        try:
            assert indexer._score_memo is not None
            rng = random.Random(11)
            tokens = [rng.randrange(1, 60_000) for _ in range(320)]
            keys = indexer.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            for ix in (indexer, straight):
                ix.kv_block_index.add(keys, keys, [POD_A])
                ix.kv_block_index.add(keys[:10], keys[:10], [POD_B])
            prompt = words(tokens)
            # Cold vs cold, then warm vs warm: the prefix store serves
            # full text chunks only, so a warm pass may score slightly
            # fewer blocks than the cold one — identically on BOTH
            # lanes (pre-existing fast-path property).
            first = indexer.get_pod_scores(prompt, "m")  # cold fill
            assert first == straight.get_pod_scores(prompt, "m")
            warm = indexer.get_pod_scores(prompt, "m")  # warm re-fill
            assert warm == straight.get_pod_scores(prompt, "m")

            # Prove the next repeat is a memo hit: a walk would have to
            # call lookup_chain, so booby-trap it.
            inner = indexer.kv_block_index

            def bomb(chain):  # pragma: no cover - must not run
                raise AssertionError("memo miss: lookup_chain called")

            original = inner.lookup_chain
            inner.lookup_chain = bomb
            try:
                hit = indexer.get_pod_scores(prompt, "m")
            finally:
                inner.lookup_chain = original
            assert hit == warm
            # The served dict is the caller's to mutate.
            hit["pod-a"] = -1.0
            assert indexer.get_pod_scores(prompt, "m") == warm

            # Every mutation class invalidates: add, evict, purge,
            # restore.  After each, fast scores == a straight indexer
            # driven through the same mutations.
            def both(op):
                for ix in (indexer, straight):
                    op(ix.kv_block_index)

            both(lambda ix: ix.add(keys[:4], keys[:4], [POD_C]))
            a = indexer.get_pod_scores(prompt, "m")
            assert a == straight.get_pod_scores(prompt, "m")
            assert a != warm

            both(lambda ix: ix.evict(keys[0], [POD_C]))
            assert indexer.get_pod_scores(
                prompt, "m"
            ) == straight.get_pod_scores(prompt, "m")

            both(lambda ix: ix.purge_pod("pod-b"))
            b = indexer.get_pod_scores(prompt, "m")
            assert b == straight.get_pod_scores(prompt, "m")

            dump = indexer.kv_block_index.dump_entries()
            both(lambda ix: ix.restore_entries(*dump))
            assert indexer.get_pod_scores(
                prompt, "m"
            ) == straight.get_pod_scores(prompt, "m")
        finally:
            indexer.shutdown()
            straight.shutdown()

    def test_memo_respects_pod_filter_keying(self):
        indexer = make_indexer(True)
        straight = make_indexer(False)
        try:
            tokens = list(range(1, 161))
            keys = indexer.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            for ix in (indexer, straight):
                ix.kv_block_index.add(keys, keys, [POD_A])
                ix.kv_block_index.add(keys[:3], keys[:3], [POD_B])
            prompt = words(tokens)
            for flt in (None, ["pod-a"], ["pod-b"], ["pod-a", "pod-b"]):
                for _ in range(3):  # cold, warm fill, memo hit
                    assert indexer.get_pod_scores(
                        prompt, "m", flt
                    ) == straight.get_pod_scores(prompt, "m", flt), flt
        finally:
            indexer.shutdown()
            straight.shutdown()

    def test_memo_hit_refreshes_chain_recency(self):
        """A memo hit must leave the same LRU recency the elided walk
        would have: chain keys it serves stay MRU, so index capacity
        pressure evicts colder keys first."""
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size=16),
                kvblock_index_config=IndexConfig(
                    in_memory_config=InMemoryIndexConfig(
                        size=10, shards=1
                    )
                ),
                read_path_fast_lane=True,
            ),
            tokenizer=WordTokenizer(),
        )
        indexer.run()
        try:
            index = indexer.kv_block_index
            tokens = list(range(1, 65))  # 4 blocks
            chain = indexer.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            index.add(chain, chain, [POD_A])
            fillers = [10_000 + i for i in range(6)]
            for key in fillers:
                index.add([key], [key], [POD_B])
            prompt = words(tokens)
            expected = {"pod-a": float(len(chain))}
            assert indexer.get_pod_scores(prompt, "m") == expected
            assert indexer.get_pod_scores(prompt, "m") == expected  # fill

            # Make the chain the LRU victim-to-be WITHOUT mutating the
            # index (recency is not score-relevant, so no version bump),
            # then serve from the memo — the hit must re-touch the chain.
            index.touch_chain(fillers)
            assert indexer.get_pod_scores(prompt, "m") == expected  # hit

            # Capacity pressure: three new keys evict three fillers,
            # never the just-served chain.
            for key in (20_001, 20_002, 20_003):
                index.add([key], [key], [POD_C])
            assert indexer.get_pod_scores(prompt, "m") == expected
        finally:
            indexer.shutdown()

    def test_env_knob_and_config_disable_memo(self, monkeypatch):
        monkeypatch.setenv("READ_PATH_SCORE_MEMO", "0")
        indexer = Indexer(IndexerConfig(), tokenizer=WordTokenizer())
        assert indexer._score_memo is None
        monkeypatch.setenv("READ_PATH_SCORE_MEMO", "64")
        indexer = Indexer(IndexerConfig(), tokenizer=WordTokenizer())
        assert indexer._score_memo is not None
        assert indexer._score_memo.capacity == 64
        monkeypatch.delenv("READ_PATH_SCORE_MEMO")
        indexer = Indexer(
            IndexerConfig(score_memo_size=0), tokenizer=WordTokenizer()
        )
        assert indexer._score_memo is None
        # The straight path never builds one.
        indexer = Indexer(
            IndexerConfig(read_path_fast_lane=False),
            tokenizer=WordTokenizer(),
        )
        assert indexer._score_memo is None

    def test_memo_requires_version_vector_surface(self):
        """Backends without the optimistic-validation surface
        (version_vector/touch_chain) silently run without the memo."""
        indexer = Indexer(
            IndexerConfig(
                kvblock_index_config=IndexConfig(
                    in_memory_config=None,
                    cost_aware_config=CostAwareIndexConfig(
                        max_cost_bytes=10_000_000
                    ),
                ),
                read_path_fast_lane=True,
            ),
            tokenizer=WordTokenizer(),
        )
        assert indexer._score_memo is None
        # The instrumented wrapper passes the surface through.
        instrumented = Indexer(
            IndexerConfig(
                kvblock_index_config=IndexConfig(enable_metrics=True),
                read_path_fast_lane=True,
            ),
            tokenizer=WordTokenizer(),
        )
        assert instrumented._score_memo is not None
        instrumented.run()
        try:
            tokens = list(range(1, 33))
            keys = instrumented.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens, "m"
            )
            instrumented.kv_block_index.add(keys, keys, [POD_A])
            prompt = words(tokens)
            expected = {"pod-a": float(len(keys))}
            for _ in range(3):
                assert instrumented.get_pod_scores(prompt, "m") == expected
        finally:
            instrumented.shutdown()

    def test_memo_invalidates_on_count_preserving_token_resplit(self):
        """A prefix-store chunk overwritten with a different token
        split of the SAME text (an overlapping prompt's
        add_tokenization; BPE boundaries depend on following context)
        can change the served token VALUES while preserving their
        count.  The memo must invalidate on token content, not count —
        serving the stale scores would break fast≡straight parity with
        the index unmutated."""
        fast = make_indexer(True)
        straight = make_indexer(False)
        try:
            assert fast._score_memo is not None
            tokens_a = list(range(1000, 1320))
            prompt = words(tokens_a)
            keys_a = fast.token_processor.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, tokens_a, "m"
            )
            for ix in (fast, straight):
                ix.kv_block_index.add(keys_a, keys_a, [POD_A])
            assert fast.get_pod_scores(prompt, "m") == {
                "pod-a": float(len(keys_a))
            }  # cold walk; warms the prefix store
            # Warm repeats serve the store's (possibly truncated)
            # stream; the second equals the first warm call via the
            # memo.
            warm = fast.get_pod_scores(prompt, "m")
            assert warm["pod-a"] > 0
            assert fast.get_pod_scores(prompt, "m") == warm  # memo hit

            # Same text, same token COUNT, different token values.
            words_list = prompt.split(" ")
            offsets, pos = [], 0
            for word in words_list:
                offsets.append((pos, pos + len(word)))
                pos += len(word) + 1
            tokens_b = [t + 500_000 for t in tokens_a]
            for ix in (fast, straight):
                ix.prefix_store.add_tokenization(
                    prompt, tokens_b, offsets, "m"
                )
                served = ix.tokenization_pool.tokenize(prompt, "m")
                assert served == tokens_b[: len(served)]  # B, same count
                assert served

            # Index untouched (version vector unchanged): only the
            # token check can reject the memo entry.
            a = fast.get_pod_scores(prompt, "m")
            b = straight.get_pod_scores(prompt, "m")
            assert a == b
            assert a != warm  # stale memo scores would be `warm`
        finally:
            fast.shutdown()
            straight.shutdown()


class TestVersionVector:
    """Per-shard mutation counters: score-relevant mutations bump, pure
    reads and recency touches do not."""

    @pytest.mark.parametrize("shards", [1, 8])
    def test_mutations_bump_reads_do_not(self, shards):
        index = InMemoryIndex(
            InMemoryIndexConfig(size=1000, shards=shards)
        )
        v0 = index.version_vector()
        assert v0 == tuple([0] * len(index._shards))

        index.add([1, 2, 3], [1, 2, 3], [POD_A])
        v1 = index.version_vector()
        assert v1 != v0

        index.lookup([1, 2, 3], None)
        index.lookup_chain((1, 2, 3))
        index.touch_chain([1, 2, 3])
        index.dump_entries()
        assert index.version_vector() == v1

        index.evict(1, [POD_A])
        v2 = index.version_vector()
        assert v2 != v1

        index.add_mappings([9], [9])  # engine map only: not score-relevant
        assert index.version_vector() == v2

        index.add_entries_batch([((9,), [POD_B])])
        v3 = index.version_vector()
        assert v3 != v2

        index.purge_pod("pod-b")
        v4 = index.version_vector()
        assert v4 != v3

        dump = index.dump_entries()
        index.restore_entries(*dump)
        assert index.version_vector() != v4
