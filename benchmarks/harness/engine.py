"""The engine the benchmark drives around a pod, and the spans around each layer.

What is the benchmark's, here: the records (spans, counters, series), the word
tokenizer and the engine's own block hashes, `Fleet` (route, account, commit:
a copy of the sound parts of `bench.py`'s `FleetRouter` and `publish_events`)
and the two load loops (`run_stream`, `run_chat`).  What is the program's:
the pod's cache manager and its three compiled programs (`harness/pod.py`
until ROADMAP D4 moves them into the package) over the family's model step,
which `Fleet` is handed as a module found by the configuration's `family`
(`harness/family.py`); no model module is imported here.  The system under
test is what these call: `Indexer`, `kvevents.Pool`, the family's model step
and the kernels below it.
"""

from __future__ import annotations

import contextlib
import hashlib
import time
from collections import defaultdict

import jax
import numpy as np

from llm_d_kv_cache_manager_tpu.kvcache.indexer import Indexer, IndexerConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import IndexConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.events import (
    BlockRemoved, BlockStored, EventBatch,
)
from llm_d_kv_cache_manager_tpu.kvevents.pool import Message, Pool, PoolConfig
from llm_d_kv_cache_manager_tpu.tokenization.pool import TokenizationPoolConfig
from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import Encoding

from .pod import Pod, jit_programs

MODEL_NAME = "bench/model"
BLOCK = 16  # tokens per K/V block: the index's block size


class Records:
    """What a run observed: spans (name, start, end) on the host clock,
    counters, and series of per-request or per-step values.  With
    `annotate`, each span is also written into the profiler's trace."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.spans: list[tuple[str, float, float]] = []
        self.counters: dict[str, float] = defaultdict(float)
        self.series: dict[str, list[float]] = defaultdict(list)
        self.recording = False  # set when the window opens

    @contextlib.contextmanager
    def span(self, name: str):
        note = (jax.profiler.TraceAnnotation("bench:" + name)
                if self.annotate and self.recording
                else contextlib.nullcontext())
        start = time.perf_counter()
        with note:
            yield
        if self.recording:
            self.spans.append((name, start, time.perf_counter()))

    def count(self, name: str, by: float = 1) -> None:
        if self.recording:
            self.counters[name] += by

    def add(self, name: str, value: float) -> None:
        if self.recording:
            self.series[name].append(value)


class WordTokenizer:
    """Whitespace tokenizer: the word `t<id>` is token <id>."""

    def type(self) -> str:
        return "bench-word"

    def encode(self, prompt: str, model_name: str, add_special_tokens: bool):
        words = prompt.split(" ")
        lens = np.fromiter(map(len, words), np.int64, len(words))
        ends = np.cumsum(lens + 1) - 1
        return Encoding(tokens=[int(w[1:]) for w in words],
                        offsets=list(zip((ends - lens).tolist(), ends.tolist())))


def prompt_text(tokens) -> str:
    return " ".join(f"t{t}" for t in tokens)


def block_hash_chain(tokens) -> list[int]:
    """The engine's own chained block hashes, as vLLM makes them."""
    hashes, parent = [], b"root"
    data, width = np.asarray(tokens, "<i8").tobytes(), 8 * BLOCK
    for i in range(0, len(data) - len(data) % width, width):
        parent = hashlib.sha256(parent + data[i:i + width]).digest()
        hashes.append(int.from_bytes(parent[-8:], "big"))
    return hashes


class Fleet:
    """Pods on one chip behind precise routing: the real `Indexer` scores,
    the real `kvevents.Pool` feeds its index."""

    def __init__(self, program, model, params, traffic: dict, shapes: dict,
                 rec: Records, interpret: bool) -> None:
        """`program`: the family's program module (`family.program(cfg)`);
        `model`: its configuration object (`program.from_published`)."""
        self.params, self.rec = params, rec
        new_pod = getattr(program, "Pod", Pod)
        self.pods = [new_pod(f"pod-{i}", program, model, traffic["pool_blocks"])
                     for i in range(traffic["pods"])]
        self.by_name = {p.name: p for p in self.pods}
        self.programs = getattr(program, "jit_programs", jit_programs)(
            program, model, shapes, interpret)
        self.shapes = shapes
        self.log: list[dict] = []  # every request since the pools were empty
        self._rr = 0
        self.indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(block_size=BLOCK),
                kvblock_index_config=IndexConfig(),
                tokenizers_pool_config=TokenizationPoolConfig()),
            tokenizer=WordTokenizer())
        self.indexer.run()
        self.events = Pool(self.indexer.kv_block_index,
                           self.indexer.token_processor, PoolConfig(concurrency=2))
        self.events.start()

    def shutdown(self) -> None:
        self.events.shutdown()
        self.indexer.shutdown()

    def route(self, text: str, hashes, n_prefix: int) -> Pod:
        """The pod with the best score; round-robin where nobody scores.
        Counts whether a pod that holds the prefix got the request."""
        with self.rec.span("route"):
            with self.rec.span("route.score"):
                scores = self.indexer.get_pod_scores(
                    text, MODEL_NAME, [p.name for p in self.pods])
            if scores and max(scores.values()) > 0:
                pod = self.by_name[max(scores.items(), key=lambda kv: kv[1])[0]]
            else:
                pod = self.pods[self._rr % len(self.pods)]
                self._rr += 1
            holders = [p for p in self.pods
                       if n_prefix and len(p.cached_prefix(hashes[:n_prefix]))
                       == n_prefix]
            if holders:
                self.rec.count("held_somewhere")
                self.rec.count("routed_to_holder", pod in holders)
        return pod

    def account(self, pod: Pod, hashes, n_prefix: int):
        """Engine-side hit check and allocation: a hit is the whole shared
        prefix (one compiled suffix shape), anything less a miss.  Returns
        (hit, first_new, block_ids, evicted)."""
        with self.rec.span("account"):
            cached = pod.cached_prefix(hashes[:n_prefix]) if n_prefix else []
            hit = bool(n_prefix) and len(cached) == n_prefix
            first_new = n_prefix if hit else 0
            pod.touch(hashes[:first_new])
            pod.hold(cached[:first_new], +1)
            new_ids, evicted = pod.alloc(len(hashes) - first_new)
            pod.hold(cached[:first_new], -1)
        return hit, first_new, cached[:first_new] + new_ids, evicted

    def prefill(self, pod: Pod, tokens, block_ids, hit: bool, first_new: int):
        """Dispatch one prefill and read its token back: (token, logit, row)."""
        with self.rec.span("dispatch"):
            program = self.programs["hit" if hit else "miss"]
            ids = np.asarray(tokens[first_new * BLOCK:], np.int32)[None]
            table = np.asarray(block_ids, np.int32)[None]
            out, row, pod.kv = program(self.params, ids, pod.kv, table)
        with self.rec.span("readback"):
            out = np.asarray(out)
        return int(out[0, 0]), float(out[1, 0]), row

    def commit(self, pod: Pod, tokens, hashes, first_new, block_ids, evicted):
        """Register the blocks just written, then tell the index: the
        BlockRemoved and BlockStored events an engine publishes, through the
        codec and the event pool, drained before the next request."""
        for h, bid in zip(hashes[first_new:], block_ids[first_new:]):
            pod.cached[h] = bid
        with self.rec.span("publish_events"):
            events = []
            if evicted:
                events.append(BlockRemoved(block_hashes=list(evicted), medium="hbm"))
            if first_new < len(hashes):
                events.append(BlockStored(
                    block_hashes=list(hashes[first_new:]),
                    parent_block_hash=hashes[first_new - 1] if first_new else None,
                    token_ids=tokens[first_new * BLOCK:len(hashes) * BLOCK].tolist(),
                    block_size=BLOCK, medium="hbm"))
            if events:
                self.events.add_task(Message(
                    topic=f"kv@{pod.name}@{MODEL_NAME}",
                    payload=EventBatch(ts=time.time(), events=events).encode(),
                    pod_identifier=pod.name, model_name=MODEL_NAME))
                self.events.drain()
        self.rec.count("blocks_stored", len(hashes) - first_new)
        self.rec.count("blocks_removed", len(evicted))

    def serve_prefill(self, req: dict, due: float, free_at: float) -> None:
        """One request through router, engine, model step and event plane;
        fills in the request's record.  `free_at`: when the engine came free."""
        rec = self.rec
        picked = time.perf_counter()
        tokens, n_prefix = req["tokens"], req["prefix_blocks"]
        hashes = block_hash_chain(tokens)
        pod = self.route(req["text"], hashes, n_prefix)
        hit, first_new, block_ids, evicted = self.account(pod, hashes, n_prefix)
        token, top, row = self.prefill(pod, tokens, block_ids, hit, first_new)
        first = time.perf_counter()
        self.commit(pod, tokens, hashes, first_new, block_ids, evicted)
        rec.add("ttft", first - due)
        rec.add("queue_wait", max(0.0, free_at - due))
        rec.add("gen_late", picked - max(due, free_at))
        rec.count("prompt_tokens", len(tokens))
        rec.count("cached_tokens", first_new * BLOCK)
        req.update(pod=pod.name, hit=hit, cached_blocks=first_new,
                   evicted=len(evicted), hashes=hashes, out=[token], top=[top],
                   row=row, in_window=rec.recording)
        self.log.append(req)

    def fill(self, pod: Pod, tokens) -> None:
        """Bring a pod's bookkeeping and the index to a full pool from
        made-up blocks, without running the model: no request reads them."""
        hashes = block_hash_chain(tokens)
        _, _, block_ids, evicted = self.account(pod, hashes, 0)
        self.commit(pod, tokens, hashes, 0, block_ids, evicted)
        self.log.append(dict(pod=pod.name, hashes=hashes, prefix_blocks=0,
                             hit=False, cached_blocks=0, evicted=len(evicted),
                             in_window=False))


def wait_until(due: float) -> None:
    while True:
        left = due - time.perf_counter()
        if left <= 0:
            return
        if left > 0.002:
            time.sleep(left - 0.001)


def run_stream(fleet: Fleet, requests: list[dict], t0: float, seconds: float):
    """One request in service at a time.  A request with a `due` offset is
    sent then, whether or not the engine is free (open loop); one without is
    sent when the reply before it has come (closed loop).  Ends with the
    window; a backlog that runs dry is a fault of the traffic file."""
    rec, end = fleet.rec, t0 + seconds
    free_at = t0
    for req in requests:
        paced = req["due"] is not None
        due = t0 + req["due"] if paced else free_at
        if due >= end:
            return
        if paced:
            with rec.span("wait_for_arrival"):
                wait_until(due)
        rec.count("backlog_at_end", time.perf_counter() > end)
        fleet.serve_prefill(req, due, free_at)
        free_at = time.perf_counter()
        rec.count("attempted")
        rec.count("completed_tokens", len(req["tokens"]) + 1)
    if requests and requests[-1]["due"] is None:
        raise RuntimeError("the backlog ran dry before the window closed")
    with rec.span("wait_for_arrival"):
        wait_until(end)


def run_chat(fleet: Fleet, clients: list, seconds: float, open_window,
             warm_steps: int = 2) -> None:
    """Closed loop of clients, each bound to a decode slot of the one pod.
    A client sends its next request the moment its reply ends; one waiting
    prefill runs between two decode steps.  The first request of each client
    is admitted during set-up at a staggered position (its `done` tokens
    count as generated before the window), so the window opens on a full
    batch in steady state."""
    rec, pod = fleet.rec, fleet.pods[0]
    B, max_blocks = fleet.shapes["decode"][0], fleet.shapes["max_blocks"]
    scratch = pod.alloc(1)[0][0]  # where idle slots write
    pod.hold([scratch], +1)
    table = np.full((B, max_blocks), scratch, np.int32)
    ctx, cur = np.ones(B, np.int32), np.zeros(B, np.int32)
    live: list = [None] * B  # slot -> request in decode
    waiting = [(i, next(c), None) for i, c in enumerate(clients)]

    def finish(slot, now):
        req = live[slot]
        pod.hold(req["blocks"], -1)
        pod.free.extend(req["own"])
        table[slot], ctx[slot], live[slot] = scratch, 1, None
        rec.count("attempted")
        rec.count("completed_tokens", len(req["tokens"]) + req["n_out"])
        req["finished"] = rec.recording
        waiting.append((slot, next(clients[slot]), now))

    def admit(slot, req, sent):
        tokens, n_pre = req["tokens"], req["prefix_blocks"]
        hashes = block_hash_chain(tokens)
        fleet.route(req["text"], hashes, n_pre)
        hit, first_new, block_ids, evicted = fleet.account(pod, hashes, n_pre)
        pod.hold(block_ids, +1)
        # the prefill gives the first token; each later one is a decode step
        own, more = pod.alloc(-(-(req["n_out"] - 1) // BLOCK))
        pod.hold(own, +1)
        token, top, row = fleet.prefill(pod, tokens, block_ids, hit, first_new)
        first = time.perf_counter()
        fleet.commit(pod, tokens, hashes, first_new, block_ids, evicted + more)
        if sent is not None:
            rec.add("first_token", first - sent)
        rec.count("prompt_tokens", len(tokens))
        rec.count("cached_tokens", first_new * BLOCK)
        req.update(pod=pod.name, hit=hit, cached_blocks=first_new, hashes=hashes,
                   evicted=len(evicted) + len(more), blocks=block_ids + own,
                   own=own, out=[token], top=[top], row=row, last=first,
                   in_window=rec.recording)
        fleet.log.append(req)
        table[slot, :len(req["blocks"])] = req["blocks"]
        ctx[slot] = len(tokens) + 1 + req["done"]
        cur[slot], live[slot] = token, req
        if 1 + req["done"] >= req["n_out"]:
            finish(slot, first)

    def step():
        with rec.span("dispatch"):
            out, pod.kv = fleet.programs["decode"](
                fleet.params, cur.copy(), pod.kv, table.copy(), ctx.copy())
        with rec.span("readback"):
            toks, tops = np.asarray(out)
        now = time.perf_counter()
        active = [r for r in live if r is not None]
        n_pre = active[0]["prefix_blocks"] if active else 0
        rec.count("decode_steps")
        rec.count("decode_live_seqs", len(active))
        rec.count("generated_tokens", len(active))
        rec.count("decode_live_blocks", sum(
            -(-int(ctx[s]) // BLOCK) - n_pre for s, r in enumerate(live) if r)
            + n_pre * len({r["system"] for r in active}))
        for slot, req in enumerate(live):
            if req is None:
                continue
            req["out"].append(int(toks[slot]))  # whole numbers, exact in float32
            req["top"].append(float(tops[slot]))
            rec.add("itl", now - req["last"])
            req["last"] = now
            cur[slot] = toks[slot]
            ctx[slot] += 1
            if len(req["out"]) + req["done"] >= req["n_out"]:
                finish(slot, now)

    while waiting:  # set-up: every client's first request
        admit(*waiting.pop(0))
    for _ in range(warm_steps):
        step()
    end = open_window() + seconds
    while time.perf_counter() < end:
        if waiting:
            admit(*waiting.pop(0))
        step()
