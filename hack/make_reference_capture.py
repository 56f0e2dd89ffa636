"""Deterministic generator for the pinned what-if reference capture.

``tests/testdata/whatif_reference.cbor`` is the capture
``obs.whatif.reference_ab`` replays (shards=1 vs shards=8 A/B); its
deterministic headlines are held to the oracle beside it,
``tests/testdata/WHATIF_r01.json``, by ``tests/test_whatif.py`` and
``hack/whatif_smoke.py``, which also composes its storms from it.  It
must be BYTE-STABLE across machines and package versions, so this
generator:

* drives a REAL stack (indexer + kvevents pool + flight recorder) with
  a seeded workload — recorded score maps and the canonical state
  section are measured truth, not hand-written fixtures;
* then rewrites the nondeterministic envelope: record timestamps
  become a seeded bursty schedule over a ~60 s virtual window, and the
  header gets the PINNED fingerprint/knobs below (the live fingerprint
  hashes the package version, which would churn the artifact every
  release; what-if loads with ``allow_mismatch=True`` by design).

Everything else (global seq order, payload bytes, score maps, state)
is already deterministic: ingress is single-threaded, block hashing is
FNV-64a over canonical CBOR, and the pool fully drains before every
score.  ``tests/test_whatif.py::test_reference_capture_is_current``
rebuilds the bytes and compares against the checked-in file, so a
drift in ANY of those layers fails CI with this script as the fix.

Run: ``python hack/make_reference_capture.py`` (writes the artifact
in place).
"""

import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TOKENIZERS_PARALLELISM", "false")

BLOCK = 4
MODEL = "whatif-ref"
SEED = 20260806
PODS = 3
ROUNDS = 24
# Pinned header identity — survives version bumps by construction.
FINGERPRINT = "whatif-reference-v1"
KNOBS = [["BLOCK_SIZE", str(BLOCK)], ["MODEL_NAME", MODEL]]
# Virtual origin: 2026-01-01T00:00:00Z in microseconds.
T0_US = 1_767_225_600_000_000

OUTPUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests",
    "testdata",
    "whatif_reference.cbor",
)


def _drive(recorder) -> bytes:
    """Seeded mixed workload against a fresh stack; returns the live
    artifact bytes (real score maps + canonical state)."""
    from llm_d_kv_cache_manager_tpu.kvcache.indexer import (
        Indexer,
        IndexerConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
        TokenProcessorConfig,
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
    from llm_d_kv_cache_manager_tpu.obs.replay import (
        _ReplayTokenizer,
        render_prompt,
    )

    indexer = Indexer(
        IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size=BLOCK, hash_seed=""
            ),
            cache_stats=False,
        ),
        tokenizer=_ReplayTokenizer(),
        capture_recorder=recorder,
    )
    indexer.run()
    pool = Pool(
        indexer.kv_block_index,
        indexer.token_processor,
        PoolConfig(concurrency=2, max_queue_depth=1 << 30),
        capture=recorder,
    )
    pool.start()
    rng = random.Random(SEED)
    seqs = {}

    def send(pod, payload):
        seqs[pod] = seqs.get(pod, 0) + 1
        pool.add_task(
            Message(
                topic=f"kv@{pod}@{MODEL}",
                payload=payload,
                pod_identifier=pod,
                model_name=MODEL,
                seq=seqs[pod],
            )
        )

    def stored(hashes, tokens, parent=None, medium="hbm"):
        return EventBatch(
            ts=1.0,
            events=[
                BlockStored(
                    block_hashes=list(hashes),
                    parent_block_hash=parent,
                    token_ids=list(tokens),
                    block_size=BLOCK,
                    medium=medium,
                )
            ],
        ).encode()

    try:
        convo = []
        for round_i in range(ROUNDS):
            convo.extend(
                rng.randrange(1, 30_000) for _ in range(BLOCK * 3)
            )
            for pod_i in range(PODS):
                if rng.random() < 0.25:
                    continue
                pod = f"pod-{pod_i}"
                claimed = rng.randrange(1, len(convo) // BLOCK + 1)
                medium = "host" if rng.random() < 0.3 else "hbm"
                send(
                    pod,
                    stored(
                        [
                            90_000 + round_i * 500 + pod_i * 100 + b
                            for b in range(claimed)
                        ],
                        convo[: claimed * BLOCK],
                        medium=medium,
                    ),
                )
                if rng.random() < 0.35:
                    private_hash = 800_000 + pod_i * 1_000 + round_i
                    send(
                        pod,
                        stored(
                            [private_hash],
                            [
                                40_000
                                + pod_i * 5_000
                                + round_i * BLOCK
                                + j
                                + 1
                                for j in range(BLOCK)
                            ],
                        ),
                    )
                    if rng.random() < 0.5:
                        send(
                            pod,
                            EventBatch(
                                ts=0.0,
                                events=[
                                    BlockRemoved(
                                        block_hashes=[private_hash]
                                    )
                                ],
                            ).encode(),
                        )
            # Every admitted write visible before the round's scores —
            # what replay AND what-if's unbounded-drain mode reproduce.
            pool.drain()
            hit_prompt = render_prompt(convo)
            pod_filter = (
                [f"pod-{i}" for i in range(PODS)]
                if rng.random() < 0.5
                else None
            )
            for _ in range(rng.randrange(2, 5)):
                indexer.get_pod_scores(hit_prompt, MODEL, pod_filter)
            # Cold prompts keep the measured hit rate honestly < 1.
            miss_tokens = [
                900_000 + round_i * 100 + j for j in range(BLOCK * 2)
            ]
            indexer.get_pod_scores(
                render_prompt(miss_tokens), MODEL, None
            )
        pool.drain()
        return recorder.dump_bytes(index=indexer.kv_block_index)
    finally:
        pool.shutdown()
        indexer.shutdown()


def _schedule(count: int) -> list:
    """Seeded bursty offsets (microseconds from T0): bursts of 5-20
    records 2-15 ms apart, separated by 0.5-4 s idle gaps — the shape
    time compression turns into arrival pressure."""
    rng = random.Random(SEED + 1)
    offsets = []
    t = 0
    remaining_in_burst = 0
    for _ in range(count):
        if remaining_in_burst == 0:
            remaining_in_burst = rng.randrange(5, 21)
            t += rng.randrange(500_000, 4_000_001)
        else:
            t += rng.randrange(2_000, 15_001)
        remaining_in_burst -= 1
        offsets.append(t)
    return offsets


def build_reference_capture() -> bytes:
    """The full pipeline: drive, re-stamp, pin the header.  Importable
    so the staleness test rebuilds and compares bytes."""
    from llm_d_kv_cache_manager_tpu.obs.capture import (
        CaptureConfig,
        InputCaptureRecorder,
        encode_capture,
        load_artifact,
    )

    recorder = InputCaptureRecorder(
        CaptureConfig(window_s=3600.0, max_bytes=32 << 20),
        meta={
            "block_size": BLOCK,
            "hash_seed": "",
            "model": MODEL,
        },
    )
    art = load_artifact(_drive(recorder))
    records = art["records"]
    offsets = _schedule(len(records))
    for record, offset in zip(records, offsets):
        record[2] = T0_US + offset
    meta = dict(art["meta"])
    meta["generator"] = "hack/make_reference_capture.py"
    meta["seed"] = str(SEED)
    return encode_capture(
        records,
        fingerprint=FINGERPRINT,
        knobs=KNOBS,
        created_us=T0_US,
        window_s=3600,
        max_bytes=0,
        truncated=[],
        meta=meta,
        state=art["state"],
    )


def main() -> int:
    payload = build_reference_capture()
    with open(OUTPUT, "wb") as handle:
        handle.write(payload)
    print(f"wrote {OUTPUT} ({len(payload)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
