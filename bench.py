"""Fleet-routing benchmark on real hardware (driver contract).

Reproduces the reference's headline experiment shape (BASELINE.md §1-2:
N pods, long shared prefix + short unique question, precise KV-aware
routing vs baseline scheduling) as a single-host simulation in which the
*prefill compute is real*: every request of the two anchored headline
runs executes the flagship Llama model on the default JAX device — the
TPU chip, or the CPU only when ``KVTPU_BENCH_PLATFORM=cpu`` asks for it
(CI smoke geometry).  A run that finds no TPU otherwise fails.

- 4 simulated pods, each with its own paged KV pool (models/
  kv_cache_pool.py geometry) and a vLLM-style local prefix cache.
- Workload: 8 prefix groups x 6 requests, 8192-token shared prefix +
  256-token unique suffix, shuffled arrival order (fixed seed).
- Write path is the real one: each prefill publishes BlockStored
  batches through the msgpack codec + sharded event pool into the
  in-memory index (kvevents/).
- Read path is the real one: the precise scheduler calls
  Indexer.get_pod_scores (tokenize -> chained block hashes -> index
  lookup -> tier-weighted longest-prefix score) and routes argmax.
- Load model: open-loop Poisson arrivals, each pod a FIFO server on a
  virtual clock (the reference's headline regime — QPS-loaded fleets
  where misrouting queues prefills, BASELINE.md §1-2).  Service times
  are the *real measured* on-device prefill times: a pod with the
  prefix cached runs ``prefill_continue`` over the 256-token suffix
  only; a miss runs ``prefill_paged`` over all 8448 tokens.
- TTFT per request = routing + queue wait + service.

Three layers of output (full artifact in a results file, compact
headline on stdout — see the driver-contract emit section; reference
benchmarking/73-capacity regime):

1. **Headline** (real compute per request): p50-TTFT speedup of
   precise routing over round-robin at 70% of ideal capacity — the
   BASELINE.json north star (>= 3x at >= 60% hit rate), so
   ``vs_baseline`` = speedup / 3.0.
2. **Matrix** (detail.matrix): 5 strategies (precise / estimated /
   load / random / round_robin, per the reference's strategy tables,
   benchmarking/73-capacity/README.md:241-419) x a QPS ladder x >= 3
   arrival seeds on the same virtual clock with the measured service
   times; p50+p90 TTFT, mean queue depth, hit rate.  The precise
   strategy runs the full real indexer read+write path per request.
   Three workload regimes: "steady" (the ladder), "churn" (pods hold
   barely one group's working set, constant eviction), and "restart"
   (scheduler-local routing history wiped mid-run — the index, rebuilt
   continuously from engine events, survives; precise holds its hit
   rate where history-only routing pays a cold restart).
3. **Compute** (detail.mfu / detail.kernels): prefill tok/s and MFU of
   the real on-device prefill, plus compiled-mode timings of the
   Pallas kernels vs their XLA counterparts at serving shapes, with a
   bench-time equality assert (the decode winner is routed into
   models/llama.py via LlamaConfig.decode_attention).

Operational contract: one stderr progress line per phase (a timed-out
run's tail shows where the time went), the persistent XLA compilation
cache where parallel/compile_cache.py puts it, and a soft wall-clock
budget (``KVTPU_BENCH_BUDGET_S``, default 1500 s — deliberately under
plausible driver timeouts) past which optional layers are truncated —
flagged in the JSON — so the headline always prints inside the driver's
timeout.

Stdout contract (the driver captures only the LAST ~2 KB): a compact
(< 1.5 KB) headline JSON as the FINAL line; the full matrix/micro/kernel
detail goes to ``bench_results.json`` (``KVTPU_BENCH_RESULTS_PATH``
overrides) — see ``emit_result``.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_T_START = time.monotonic()


def _env_float(name: str, default: float) -> float:
    """A malformed knob must not crash before main()'s parseable-error
    machinery exists; fall back to the default, loudly."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        print(
            f"[bench] ignoring malformed {name}={raw!r}; "
            f"using {default}",
            file=sys.stderr,
        )
        return default


# Soft wall-clock budget: the driver runs `python bench.py` under its
# own (unknown) timeout; a bench that overruns records rc=124 and NO
# metric.  Degrade instead: past the budget, optional layers are
# truncated/skipped (marked in the JSON) and the headline still prints.
_BUDGET_S = _env_float("KVTPU_BENCH_BUDGET_S", 1500.0)


def _elapsed() -> float:
    return time.monotonic() - _T_START


def _over_budget(reserve_s: float = 0.0) -> bool:
    return _elapsed() + reserve_s > _BUDGET_S


def _progress(phase: str) -> None:
    """One stderr line per phase: a timed-out run's tail shows exactly
    where the time went instead of a bare platform warning."""
    print(
        f"[bench +{_elapsed():7.1f}s] {phase}",
        file=sys.stderr,
        flush=True,
    )


# ---------------- driver-contract emit (tail-survivable stdout) --------
#
# r5 post-mortem: the driver captures only the LAST ~2 KB of stdout, and
# the old single-line emit carried the full matrix/micro detail — the
# artifact was clipped to unparseable garbage and the round recorded no
# metric.  The contract now: full detail goes to a results FILE; stdout
# carries a compact headline JSON as the FINAL line, hard-bounded well
# under the capture window.

HEADLINE_MAX_BYTES = 1400  # < 1.5 KB with margin for the driver's tail


def _round_floats(obj, digits=4):
    """Round every float in a compact block: full-precision doubles
    (~18 chars each) are what blow the headline budget, and the full
    values live in the results file anyway.  Not applied to
    indexer_restart — the driver-contract test pins that block equal
    to the detail artifact."""
    if isinstance(obj, float):
        return round(obj, digits)
    if isinstance(obj, dict):
        return {k: _round_floats(v, digits) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_round_floats(v, digits) for v in obj]
    return obj


def _results_file_path() -> str:
    return os.environ.get("KVTPU_BENCH_RESULTS_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_results.json"
    )


def _write_results_file(full: dict) -> Optional[str]:
    """Atomic (tmp+rename) write of the full artifact; None on failure
    — the compact headline still prints, flagging the lost detail."""
    path = _results_file_path()
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as handle:
            json.dump(full, handle)
        os.replace(tmp, path)
        return path
    except OSError as exc:
        print(
            f"[bench] results file write failed: {exc}", file=sys.stderr
        )
        try:
            if os.path.exists(tmp):
                os.unlink(tmp)
        except OSError:
            pass
        return None


def emit_result(full: dict) -> None:
    """Write the full artifact to the results file; print the compact
    headline as the process's last stdout line.  The headline repeats
    only what the driver needs: metric, value, device, the scoring-RPC
    percentiles, and the indexer_restart cold/warm comparison."""
    results_path = _write_results_file(full)
    detail = full.get("detail", {})
    read_path = detail.get("read_path") or {}
    read_path_compact = None
    if read_path and "warm_multi_turn" in read_path:
        read_path_compact = {
            "warm_sps": read_path["warm_multi_turn"].get("scores_per_sec"),
            "warm_p50_us": read_path["warm_multi_turn"].get("p50_us"),
            "warm_no_memo_sps": (
                read_path.get("warm_multi_turn_no_memo", {}).get(
                    "scores_per_sec"
                )
            ),
            "cold_sps": read_path["cold"].get("scores_per_sec"),
            "mixed_sps": read_path["mixed"].get("scores_per_sec"),
            "warm_speedup_vs_off": read_path.get("warm_speedup_vs_off"),
            "parity": read_path.get("parity"),
            # The other profiler cells (event_storm.profiler_ab,
            # replica_scaleout.fanout_profile) stay detail-only: the
            # compact line sits within ~100 bytes of the shed loop's
            # budget in full tiny runs, and one representative
            # overhead number is what the driver needs at a glance.
            "prof_overhead": (
                read_path.get("profiler_ab") or {}
            ).get("overhead"),
            # The capture_ab cells (read_path AND event_storm) stay
            # detail-only: the compact line sits within ~100 bytes of
            # the shed budget in full tiny runs, and adding one more
            # field here shed indexer_restart off the line (the
            # driver-contract test pins that block's presence).
        }
    cache_analytics = detail.get("cache_analytics") or {}
    cache_analytics_compact = None
    if cache_analytics and "ledger_truth" in cache_analytics:
        truth = cache_analytics.get("ledger_truth") or {}
        audit = cache_analytics.get("audit_plane") or {}
        overhead = cache_analytics.get("overhead") or {}
        cache_analytics_compact = {
            "ledger_hit_rate": truth.get("ledger_hit_rate"),
            "ground_truth": truth.get("ground_truth_hit_rate"),
            "within_2pct": truth.get("within_2pct"),
            "divergence_detected": audit.get("detected_within_one_cycle"),
            "detected_ratio": audit.get("detected_ratio"),
            "overhead_pct": overhead.get("overhead_pct"),
            "within_3pct": overhead.get("within_3pct"),
            "parity": overhead.get("parity"),
        }
    tiered_churn = detail.get("tiered_churn") or {}
    tiered_churn_compact = None
    if tiered_churn and "eviction_ab" in tiered_churn:
        ab = tiered_churn.get("eviction_ab") or {}
        col = tiered_churn.get("compute_or_load") or {}
        tiered_churn_compact = {
            "hit_lru": ab.get("hit_rate_lru"),
            "hit_pred": ab.get("hit_rate_predictive"),
            "beats_lru": ab.get("beats_lru"),
            "parity": ab.get("policy_off_parity"),
            "ttft_load_s": col.get("ttft_load_s"),
            "ttft_recompute_s": col.get("ttft_recompute_s"),
            "ttft_hybrid_s": col.get("ttft_hybrid_s"),
            "hybrid_ok": col.get("hybrid_le_min_pure"),
            "advice": (col.get("advice") or {}).get("action"),
        }
    scaleout_warmup = detail.get("scaleout_warmup") or {}
    scaleout_warmup_compact = None
    if scaleout_warmup and "arms" in scaleout_warmup:
        # Keys terse (p90 = [transfer_aware, route_to_holder,
        # round_robin] post-join p90 TTFT); full names live in
        # detail.scaleout_warmup.
        arms = scaleout_warmup.get("arms") or {}
        ta = arms.get("transfer_aware") or {}
        scaleout_warmup_compact = {
            "p90": [
                (arms.get(a) or {}).get("p90_ttft_post_join_s")
                for a in (
                    "transfer_aware",
                    "route_to_holder",
                    "round_robin",
                )
            ],
            "beats_rth": scaleout_warmup.get(
                "ttft_p90_beats_route_to_holder"
            ),
            "beats_rr": scaleout_warmup.get(
                "ttft_p90_beats_round_robin"
            ),
            "cold_ratio": scaleout_warmup.get("cold_pod_hit_ratio"),
            "cold_ok": scaleout_warmup.get(
                "cold_pod_warm_within_envelope"
            ),
            "env_s": ta.get("warmup_envelope_s"),
            "parity": (scaleout_warmup.get("parity") or {}).get(
                "parity"
            ),
        }
    host_offload = detail.get("host_offload") or {}
    # The regime pre-computes its compact block (bench_host_offload
    # "headline"); pass it through untouched.
    host_offload_compact = host_offload.get("headline")
    event_storm = detail.get("event_storm") or {}
    event_storm_compact = None
    if event_storm and "n_pods" in event_storm:
        gap = event_storm.get("gap_storm") or {}
        fairness = event_storm.get("fairness") or {}
        consolidated = event_storm.get("consolidated_pollers_1") or {}
        poller_scaling = event_storm.get("poller_scaling") or {}
        replica_local = event_storm.get("replica_local") or {}
        # Headline bytes are a hard driver budget (the shed loop below
        # drops whole blocks when the line overflows), so field names
        # here are terse: stage_us = [decode, apply] µs/msg,
        # p4_ratio = pollers-4-vs-1 non-inversion guard, ri_scaling =
        # replica-local 1→3 process scaling.  Full names live in the
        # results file (detail.event_storm).
        event_storm_compact = {
            "n_pods": event_storm.get("n_pods"),
            "apply_sps": consolidated.get("apply_msgs_per_sec"),
            "stage_us": [
                consolidated.get("decode_us_per_msg"),
                consolidated.get("apply_us_per_msg"),
            ],
            "p4_ratio": poller_scaling.get("ratio_4_vs_1"),
            "ri_scaling": replica_local.get("scaling_1_to_3"),
            "fairness_ok": fairness.get("property_holds"),
            "gap_s": gap.get("recovery_wall_s"),
            "consistency": gap.get("post_resync_consistency"),
        }
    replica_scaleout = detail.get("replica_scaleout") or {}
    replica_scaleout_compact = None
    if replica_scaleout and "cluster_3_replicas" in replica_scaleout:
        failover = replica_scaleout.get("failover") or {}
        replica_scaleout_compact = {
            "single_sps": replica_scaleout["single"].get(
                "scores_per_sec"
            ),
            "cluster1_sps": replica_scaleout["cluster_1_replica"].get(
                "scores_per_sec"
            ),
            "cluster3_sps": replica_scaleout["cluster_3_replicas"].get(
                "scores_per_sec"
            ),
            "parity": replica_scaleout.get("parity"),
            "pre_kill_hit": failover.get("pre_kill_hit_rate"),
            "post_kill_hit": failover.get("post_kill_hit_rate"),
            "dip": failover.get("dip"),
            "within_envelope": failover.get("within_envelope"),
            "slo_state": (failover.get("slo_envelope") or {}).get(
                "state"
            ),
            "trace_overhead": (
                replica_scaleout.get("trace_ab") or {}
            ).get("overhead"),
            # Pipelined read-path A/B (RTT-injected): 3-replica warm
            # multi-turn scores/sec with overlap+pipelining armed, and
            # its p99 as a multiple of the injected RTT.
            "pipelined_sps": (
                (replica_scaleout.get("pipelined_ab") or {}).get(
                    "pipelined_warm"
                )
                or {}
            ).get("scores_per_sec"),
            "p99_rtt": (
                replica_scaleout.get("pipelined_ab") or {}
            ).get("p99_rtt_ratio"),
        }
    compact = {
        "metric": full["metric"],
        "value": full["value"],
        "unit": full.get("unit"),
        "vs_baseline": full.get("vs_baseline"),
        "device": detail.get("device"),
        "routing_precise_us": _round_floats(
            detail.get("routing_precise_us")
        ),
        "read_path": _round_floats(read_path_compact),
        "cache_analytics": _round_floats(cache_analytics_compact),
        "tiered_churn": _round_floats(tiered_churn_compact),
        "scaleout_warmup": _round_floats(scaleout_warmup_compact),
        "host_offload": _round_floats(host_offload_compact),
        "event_storm": _round_floats(event_storm_compact),
        # Passed through un-rounded: the driver-contract test pins
        # this block equal to the detail artifact.
        "indexer_restart": detail.get("indexer_restart"),
        "replica_scaleout": _round_floats(replica_scaleout_compact),
        "elapsed_s": detail.get("elapsed_s"),
        "results": results_path or "WRITE FAILED (stderr has why)",
    }
    line = json.dumps(compact)
    # Belt and braces: every field above is small by construction, but
    # the budget is a hard driver contract — shed optional fields
    # before ever printing an oversized last line.
    # Shed order: newest/nice-to-have blocks first.  replica_scaleout
    # and scaleout_warmup go before indexer_restart — the driver-
    # contract test pins indexer_restart's presence on the full tiny
    # run, and the line only fits it after two sheds.
    for key in (
        "replica_scaleout",
        "scaleout_warmup",
        "indexer_restart",
        "event_storm",
        "host_offload",
        "tiered_churn",
        "cache_analytics",
        "read_path",
        "routing_precise_us",
        "results",
    ):
        if len(line) <= HEADLINE_MAX_BYTES:
            break
        compact.pop(key, None)
        line = json.dumps(compact)
    print(line, flush=True)

import zmq

from llm_d_kv_cache_manager_tpu.kvcache.indexer import Indexer, IndexerConfig
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import InMemoryIndex
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
    IndexConfig,
    InMemoryIndexConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.token_processor import (
    EMPTY_BLOCK_HASH,
    ChunkedTokenDatabase,
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.events import (
    BlockRemoved,
    BlockStored,
    EventBatch,
)
from llm_d_kv_cache_manager_tpu.kvevents.pool import Message, Pool, PoolConfig
from llm_d_kv_cache_manager_tpu.metrics.collector import counter_total
from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVCachePool,
    KVCachePoolConfig,
)
from llm_d_kv_cache_manager_tpu.native.engine import (
    JobStatus as OffloadJobStatus,
)
from llm_d_kv_cache_manager_tpu.offload.spec import (
    TPUOffloadConnector,
    TPUOffloadSpec,
)
from llm_d_kv_cache_manager_tpu.offload.worker import (
    group_blocks_per_file,
    host_dtype,
)
from llm_d_kv_cache_manager_tpu.parallel.compile_cache import (
    configure_compile_cache,
)
from llm_d_kv_cache_manager_tpu.tokenization.pool import (
    TokenizationPoolConfig,
)
from llm_d_kv_cache_manager_tpu.tokenization.tokenizers import Encoding

MODEL_NAME = "bench/llama"
NUM_PODS = 4
NUM_GROUPS = 8
REQS_PER_GROUP = 6
PREFIX_TOKENS = 8192  # benchmark 1's 8k shared system prompt
SUFFIX_TOKENS = 256
BLOCK_SIZE = 16
TOTAL_TOKENS = PREFIX_TOKENS + SUFFIX_TOKENS

# ~0.75B params + 8k prefix (flash-attention prefill): enough compute
# that prefill — the thing routing saves — dominates the sub-ms routing
# overhead, as in the reference's fleet where an 8k prefill on a 70B
# model takes seconds (BASELINE.md §1).
CFG = llama.LlamaConfig(
    vocab_size=16384,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    n_kv_heads=8,
    d_ff=5632,
    block_size=BLOCK_SIZE,
    dtype="bfloat16",
)
POOL_BLOCKS = 1536  # per pod: holds 2 groups' working set (precise
# routing assigns NUM_GROUPS/NUM_PODS = 2 groups per pod); reuse evicts
# Churn regime: barely one group's working set (512 prefix blocks +
# 6 requests x 16 suffix blocks = 608), so the allocator wraps and
# evicts constantly.
CHURN_POOL_BLOCKS = 640

# Matrix axes (reference benchmarking/73-capacity: strategy tables over
# a QPS ladder).  Fractions are of the fleet's ideal-routing capacity.
STRATEGIES = ("precise", "estimated", "load", "random", "round_robin")
QPS_FRACTIONS = (0.5, 0.6, 0.7, 0.8, 0.9)
ARRIVAL_SEEDS = (7, 11, 13)

if os.environ.get("KVTPU_BENCH_TINY"):
    # Smoke-run geometry (CI / CPU): same code paths, minutes -> seconds.
    NUM_GROUPS, REQS_PER_GROUP = 4, 4
    PREFIX_TOKENS, SUFFIX_TOKENS = 512, 64
    TOTAL_TOKENS = PREFIX_TOKENS + SUFFIX_TOKENS
    CFG = llama.LlamaConfig(
        vocab_size=2048,
        d_model=256,
        n_layers=2,
        n_heads=8,
        n_kv_heads=4,
        d_ff=704,
        block_size=BLOCK_SIZE,
        dtype="float32",
    )
    POOL_BLOCKS = 160
    CHURN_POOL_BLOCKS = 52  # one tiny group = 32 prefix + 4x4 suffix
    ARRIVAL_SEEDS = (7, 11)


class WordTokenizer:
    """Deterministic whitespace tokenizer (ASCII words -> stable ids)."""

    def type(self) -> str:
        return "bench-word"

    def encode(
        self, prompt: str, model_name: str, add_special_tokens: bool
    ) -> Encoding:
        tokens: List[int] = []
        offsets: List[Tuple[int, int]] = []
        pos = 0
        for word in prompt.split(" "):
            tokens.append(int(word[1:]) if word[0] == "t" else 0)
            offsets.append((pos, pos + len(word)))
            pos += len(word) + 1
        return Encoding(tokens=tokens, offsets=offsets)


def make_prompts(rng: random.Random) -> List[Tuple[int, str, List[int]]]:
    """(group, prompt text, token ids) per request, shuffled arrival."""
    group_prefixes = [
        [rng.randrange(1, CFG.vocab_size) for _ in range(PREFIX_TOKENS)]
        for _ in range(NUM_GROUPS)
    ]
    requests = []
    for group in range(NUM_GROUPS):
        for _ in range(REQS_PER_GROUP):
            suffix = [
                rng.randrange(1, CFG.vocab_size) for _ in range(SUFFIX_TOKENS)
            ]
            tokens = group_prefixes[group] + suffix
            text = " ".join(f"t{t}" for t in tokens)
            requests.append((group, text, tokens))
    rng.shuffle(requests)
    return requests


class SimPod:
    """One simulated serving pod: paged pool + local prefix cache.

    ``with_kv=False`` (matrix runs) keeps the block-allocator and
    prefix-cache bookkeeping but skips the ~1.6 GB device pool — the
    virtual-clock runs never touch the device.  ``device`` commits the
    pool (and the caller commits ``params``) to one chip of several;
    None leaves both on the default device.  ``cfg`` defaults to the
    bench model."""

    def __init__(
        self,
        name: str,
        params=None,
        with_kv: bool = True,
        pool_blocks: int = None,
        cfg: Optional[llama.LlamaConfig] = None,
        device: Optional[jax.Device] = None,
    ) -> None:
        cfg = cfg or CFG
        self.pool_blocks = pool_blocks or POOL_BLOCKS
        self.name = name
        self.params = params
        self.kv = None
        if with_kv:
            self.kv = jnp.zeros(
                (
                    cfg.n_layers,
                    self.pool_blocks,
                    2,
                    cfg.block_size,
                    cfg.n_kv_heads,
                    cfg.head_dim,
                ),
                jnp.bfloat16,
                device=device,
            )
        self._next_block = 0
        # Engine-side prefix cache: chained block hash -> pool block id,
        # plus the reverse map so reuse evicts the old resident.
        self.cached: Dict[int, int] = {}
        self._block_owner: Dict[int, int] = {}
        # Optional eviction journal (tiered_churn parity cell): when a
        # list is attached, alloc() appends every evicted hash in order.
        self.evict_log: Optional[List[int]] = None

    def alloc(self, n: int) -> Tuple[List[int], List[int]]:
        """Bump-allocate n blocks; returns (ids, evicted block hashes).
        Like a real engine, reusing a block evicts whatever prefix block
        lived there — callers must publish the eviction."""
        ids = [
            (self._next_block + i) % self.pool_blocks for i in range(n)
        ]
        self._next_block = (self._next_block + n) % self.pool_blocks
        evicted: List[int] = []
        for bid in ids:
            old = self._block_owner.pop(bid, None)
            if old is not None and self.cached.get(old) == bid:
                del self.cached[old]
                evicted.append(old)
        if self.evict_log is not None:
            self.evict_log.extend(evicted)
        return ids, evicted

    def cached_prefix_blocks(self, block_hashes: Sequence[int]) -> List[int]:
        """Pool ids of the longest cached consecutive prefix."""
        ids: List[int] = []
        for h in block_hashes:
            if h not in self.cached:
                break
            ids.append(self.cached[h])
        return ids


class TieredFleetPolicy:
    """Shared policy state for a tiered_churn predictive run: ONE
    ledger + PolicyFeed across the fleet (the engine-chain analogue of
    the indexer-side wiring — the PolicyFeed contract is key-space
    agnostic, and here the pods' own block-hash chains feed it)."""

    def __init__(self) -> None:
        from llm_d_kv_cache_manager_tpu.analytics.ledger import (
            CacheStatsLedger,
            LedgerConfig,
        )
        from llm_d_kv_cache_manager_tpu.tiering import PolicyFeed

        self.ledger = CacheStatsLedger(LedgerConfig(sample_rate=1.0))
        self.feed = PolicyFeed(ledger=self.ledger)

    def close(self) -> None:
        self.ledger.close()


class TieredSimPod(SimPod):
    """SimPod + the predictive tiering policy at the engine edge.

    Reuse-aware **admission + protection** (the TinyLFU-flavored rule
    from docs/tiering.md): the pod protects one incumbent prefix
    family's blocks from eviction; a challenger family is admitted
    into the cache (registered + advertised) only when the PolicyFeed
    predicts its reuse strictly better (2x shorter expected next use)
    than the incumbent's — otherwise it is served **transiently**:
    blocks are allocated from the unprotected region and never
    registered, so the incumbent's working set survives churn and the
    index is never told about blocks the pod won't keep.

    ``tiering=None`` is the parity oracle: every code path delegates
    to the pristine SimPod behavior, bit-identically (asserted by the
    bench's tiered_churn parity cell).
    """

    # Fraction of the pool the incumbent may pin; the rest stays a
    # churn region so transient requests always progress.
    PROTECT_FRACTION = 0.85

    def __init__(self, *args, tiering: Optional[TieredFleetPolicy] = None,
                 **kw) -> None:
        super().__init__(*args, **kw)
        self.tiering = tiering
        self.protected_ids: set = set()
        self.protected_family: Optional[int] = None
        # Decisions for the in-flight request (prepare_request ->
        # alloc -> commit ride the same virtual-clock step).
        self.register_current = True
        self._pending_protect: Optional[int] = None
        self._protect_cap = int(self.pool_blocks * self.PROTECT_FRACTION)

    # -- per-request policy hooks (called by _fleet_step/commit) --------

    def prepare_request(self, hashes: Sequence[int]) -> None:
        """Record the arrival, then decide admission/protection for
        this request BEFORE account() allocates."""
        if self.tiering is None:
            return
        ledger, feed = self.tiering.ledger, self.tiering.feed
        family = ledger.family_key(hashes, len(hashes))
        matched = len(self.cached_prefix_blocks(hashes))
        ledger.record(family, MODEL_NAME, len(hashes), matched)
        feed.observe_chain(hashes, family)
        self.register_current = True
        self._pending_protect = None
        if self.protected_family is None:
            # No incumbent: this family takes the seat (protection
            # lands on its block ids at commit).
            self._pending_protect = family
        elif family == self.protected_family:
            if matched == 0:
                # Defensive (protected blocks cannot normally be
                # evicted): rebuild protection from this request.
                self.protected_ids.clear()
                self._pending_protect = family
        else:
            challenger = feed.prediction(family)
            incumbent = feed.prediction(self.protected_family)
            now = time.monotonic()
            swap = (
                challenger is not None
                and (
                    incumbent is None
                    or challenger.expected_next_use_s(now) * 2.0
                    < incumbent.expected_next_use_s(now)
                )
            )
            if swap:
                self.protected_ids.clear()
                self._pending_protect = family
            else:
                # Transient service: the incumbent's working set is
                # worth more than caching this request.
                self.register_current = False

    def commit_blocks(self, hashes: Sequence[int],
                      block_ids: Sequence[int]) -> None:
        """Post-registration hook: pin the just-admitted family's
        blocks (up to the protect cap)."""
        if self.tiering is None or self._pending_protect is None:
            return
        self.protected_family = self._pending_protect
        self._pending_protect = None
        room = self._protect_cap - len(self.protected_ids)
        if room > 0:
            self.protected_ids.update(block_ids[:room])

    def alloc(self, n: int) -> Tuple[List[int], List[int]]:
        if self.tiering is None or not self.protected_ids:
            return super().alloc(n)
        # Ring allocation skipping protected ids.  A transient request
        # larger than the unprotected region reuses ids WITHIN itself
        # (real engines serve an over-sized transient request by
        # recycling its own scratch blocks); such requests are never
        # registered, so no stale cache mappings can form.
        ids: List[int] = []
        evicted: List[int] = []
        cursor = self._next_block
        scanned = 0
        while len(ids) < n:
            bid = cursor % self.pool_blocks
            cursor += 1
            scanned += 1
            if bid in self.protected_ids:
                continue
            ids.append(bid)
            old = self._block_owner.pop(bid, None)
            if old is not None and self.cached.get(old) == bid:
                del self.cached[old]
                evicted.append(old)
            if scanned >= self.pool_blocks:
                scanned = 0  # wrapped: continue into duplicates
        self._next_block = cursor % self.pool_blocks
        if self.evict_log is not None:
            self.evict_log.extend(evicted)
        return ids, evicted


def block_hash_chain(tokens: Sequence[int]) -> List[int]:
    """vLLM-style chained block hashes (the engine's own hash config;
    the indexer absorbs any scheme via the engineKey->requestKey map)."""
    import hashlib

    hashes: List[int] = []
    parent = b"root"
    for i in range(0, len(tokens) - len(tokens) % BLOCK_SIZE, BLOCK_SIZE):
        chunk = tokens[i : i + BLOCK_SIZE]
        digest = hashlib.sha256(
            parent + np.asarray(chunk, np.int64).tobytes()
        ).digest()
        hashes.append(int.from_bytes(digest[-8:], "big"))
        parent = digest
    return hashes


def publish_events(
    event_pool: Pool,
    pod: SimPod,
    tokens: Sequence[int],
    block_hashes: Sequence[int],
    first_new: int,
    evicted: Sequence[int],
) -> None:
    """Publish this request's BlockRemoved (pool-block reuse) and
    BlockStored events in order, as the engine would."""
    events = []
    if evicted:
        events.append(BlockRemoved(block_hashes=list(evicted), medium="hbm"))
    if first_new < len(block_hashes):
        events.append(
            BlockStored(
                block_hashes=list(block_hashes[first_new:]),
                parent_block_hash=(
                    block_hashes[first_new - 1] if first_new > 0 else None
                ),
                token_ids=list(tokens[first_new * BLOCK_SIZE :]),
                block_size=BLOCK_SIZE,
                medium="hbm",
            )
        )
    if not events:
        return
    batch = EventBatch(ts=time.time(), events=events)
    event_pool.add_task(
        Message(
            topic=f"kv@{pod.name}@{MODEL_NAME}",
            payload=batch.encode(),
            pod_identifier=pod.name,
            model_name=MODEL_NAME,
        )
    )


class EstimatedScorer:
    """Scheduler-side prefix-affinity approximation (the reference's
    "estimated" strategy, benchmarking/73-capacity/README.md:241-246):
    scores pods by the scheduler's OWN routing history — no engine
    events, so it is blind to evictions and to blocks cached by other
    routes.  The gap between this and "precise" is the product's value
    proposition."""

    def __init__(self, capacity_per_pod: int = 200_000) -> None:
        self.capacity = capacity_per_pod
        self._assumed: Dict[str, Dict[int, None]] = {}

    def pick(self, pod_names: Sequence[str], hashes: Sequence[int]):
        """Pod with the longest assumed consecutive prefix, or None."""
        best, best_len = None, 0
        for name in pod_names:
            assumed = self._assumed.get(name)
            if not assumed:
                continue
            n = 0
            for h in hashes:
                if h not in assumed:
                    break
                n += 1
            if n > best_len:
                best, best_len = name, n
        return best

    def record(self, pod_name: str, hashes: Sequence[int]) -> None:
        assumed = self._assumed.setdefault(pod_name, {})
        for h in hashes:
            assumed.pop(h, None)  # re-insert at LRU tail
            assumed[h] = None
        while len(assumed) > self.capacity:
            assumed.pop(next(iter(assumed)))


class FleetRouter:
    """Routing + engine-cache accounting shared by the real-compute
    headline runs and the virtual-clock matrix cells.  ONE semantics,
    measured two ways — were these duplicated, a fix to one path would
    silently make the headline and the matrix measure different caches.

    Strategies: "precise" runs the real indexer read+write path
    (routing wall time charged to TTFT); "estimated" routes from
    scheduler-local affinity; "load" to the least-backlogged pod;
    "random"/"round_robin" blind.
    """

    def __init__(
        self,
        strategy: str,
        with_kv: bool,
        params=None,
        seed: int = 0,
        pool_blocks: int = None,
        journal=None,
        cache_stats_ledger=None,
        exact_tokenize: bool = False,
        pod_factory=None,
        index_factory=None,
    ) -> None:
        self.strategy = strategy
        # pod_factory(name) lets a regime substitute policy-aware pods
        # (tiered_churn); None keeps the plain SimPod fleet.
        if pod_factory is None:
            def pod_factory(name):
                return SimPod(
                    name, params, with_kv=with_kv, pool_blocks=pool_blocks
                )
        self.pods = [pod_factory(f"pod-{i}") for i in range(NUM_PODS)]
        self.pod_by_name = {p.name: p for p in self.pods}
        self.pod_free_at: Dict[str, float] = {
            p.name: 0.0 for p in self.pods
        }
        self.completions: Dict[str, List[float]] = {
            p.name: [] for p in self.pods
        }
        self._rr = 0
        self._rng = random.Random(31_000 + seed)
        self.indexer = None
        self.event_pool = None
        self.estimated = None
        if strategy == "precise":
            tokenization_config = TokenizationPoolConfig()
            if exact_tokenize:
                # The cache_analytics regime validates the ledger's
                # per-request block counts against engine-side ground
                # truth, so the prefix store's coverage-truncated warm
                # tokenization (which serves slightly fewer tokens than
                # the full prompt) must be off: a ratio above 1.0 makes
                # the fast path unreachable.
                tokenization_config = TokenizationPoolConfig(
                    min_prefix_overlap_ratio=1.01
                )
            self.indexer = Indexer(
                IndexerConfig(
                    token_processor_config=TokenProcessorConfig(
                        block_size=BLOCK_SIZE
                    ),
                    kvblock_index_config=IndexConfig(),
                    tokenizers_pool_config=tokenization_config,
                    cache_stats=cache_stats_ledger is not None,
                ),
                tokenizer=WordTokenizer(),
                cache_stats_ledger=cache_stats_ledger,
                # index_factory() lets a regime substitute a remote
                # backend (replica_scaleout: cluster RemoteIndex); None
                # keeps the config-built in-memory index.
                kv_block_index=(
                    index_factory() if index_factory is not None else None
                ),
            )
            self.indexer.run()
            self.event_pool = Pool(
                self.indexer.kv_block_index,
                self.indexer.token_processor,
                PoolConfig(concurrency=2),
                journal=journal,
            )
            self.event_pool.start()
            # Zero-score fallback affinity (see route()); the index
            # score always overrides it when positive.
            self.estimated = EstimatedScorer()
        elif strategy == "estimated":
            self.estimated = EstimatedScorer()

    def shutdown(self) -> None:
        if self.event_pool is not None:
            self.event_pool.shutdown()
        if self.indexer is not None:
            self.indexer.shutdown()

    def _next_rr(self) -> SimPod:
        pod = self.pods[self._rr % NUM_PODS]
        self._rr += 1
        return pod

    def _affinity(self, hashes: Sequence[int]) -> SimPod:
        """Routing-history affinity (where this prefix last went);
        round-robin for groups never routed before."""
        name = self.estimated.pick([p.name for p in self.pods], hashes)
        return self.pod_by_name[name] if name else self._next_rr()

    def route(
        self, text: str, hashes: Sequence[int]
    ) -> Tuple[SimPod, float]:
        """Pick a pod; returns (pod, routing seconds charged to TTFT)."""
        if self.strategy == "precise":
            t0 = time.perf_counter()
            scores = self.indexer.get_pod_scores(
                text, MODEL_NAME, [p.name for p in self.pods]
            )
            routing_seconds = time.perf_counter() - t0
            if scores and max(scores.values()) > 0:
                pod = self.pod_by_name[
                    max(scores.items(), key=lambda kv: kv[1])[0]
                ]
            else:
                # Zero-score fallback: routing-history affinity, then
                # round-robin for genuinely cold groups.  Under pool
                # churn a prefix's blocks come and go; pure-rr fallback
                # scatters a group across pods (each miss lands
                # somewhere new, evicting yet another group), while
                # affinity keeps the group pinned so its next request
                # can hit whatever survived.  This mirrors llm-d's
                # scorer composition: the precise score breaks ties
                # ABOVE a stable affinity baseline, not above noise.
                pod = self._affinity(hashes)
            return pod, routing_seconds
        if self.strategy == "estimated":
            return self._affinity(hashes), 0.0
        if self.strategy == "load":
            return (
                min(self.pods, key=lambda p: self.pod_free_at[p.name]),
                0.0,
            )
        if self.strategy == "random":
            return self._rng.choice(self.pods), 0.0
        return self._next_rr(), 0.0

    @staticmethod
    def account(
        pod: SimPod,
        hashes: Sequence[int],
        n_prefix_blocks: Optional[int] = None,
    ) -> Tuple[bool, int, List[int], List[int]]:
        """Engine-side hit check + allocation.  Suffix blocks never
        repeat across requests, so a hit is exactly the shared prefix
        (``n_prefix_blocks``, the bench workload's by default);
        partial-prefix hits count as misses (single compiled suffix
        shape).  Returns (hit, first_new, block_ids, evicted)."""
        if n_prefix_blocks is None:
            n_prefix_blocks = PREFIX_TOKENS // BLOCK_SIZE
        cached_ids = pod.cached_prefix_blocks(hashes)
        if len(cached_ids) >= n_prefix_blocks:
            new_ids, evicted = pod.alloc(len(hashes) - n_prefix_blocks)
            return (
                True,
                n_prefix_blocks,
                cached_ids[:n_prefix_blocks] + new_ids,
                evicted,
            )
        new_ids, evicted = pod.alloc(len(hashes))
        return False, 0, new_ids, evicted

    def commit(
        self,
        pod: SimPod,
        tokens: Sequence[int],
        hashes: Sequence[int],
        first_new: int,
        block_ids: Sequence[int],
        evicted: Sequence[int],
    ) -> None:
        """Register ONLY newly-written blocks: re-registering a hit
        prefix would resurrect hashes that alloc() just evicted when
        the allocator wrapped into the cached prefix region, mapping
        them to blocks that now hold suffix KV.  Then feed whichever
        learning mechanism the strategy uses."""
        if not getattr(pod, "register_current", True):
            # Tiering admission control declined this request: the
            # blocks were transient scratch — no cache registration and
            # no BlockStored advertisement (the index must never claim
            # blocks the pod won't keep); evictions still publish.
            first_new = len(hashes)
        else:
            for h, bid in zip(hashes[first_new:], block_ids[first_new:]):
                pod.cached[h] = bid
                pod._block_owner[bid] = h
            protect = getattr(pod, "commit_blocks", None)
            if protect is not None:
                protect(hashes, block_ids)
        if self.event_pool is not None:
            publish_events(
                self.event_pool, pod, tokens, hashes, first_new, evicted
            )
            self.event_pool.drain()  # index learns before next arrival
        if self.estimated is not None:
            # Both the estimated strategy and precise's zero-score
            # fallback learn from routing history.
            self.estimated.record(pod.name, hashes)


def run_fleet_virtual(
    strategy: str,
    requests,
    hashes_list: Sequence[Sequence[int]],
    arrivals: Sequence[float],
    t_miss: float,
    t_hit: float,
    seed: int,
    pool_blocks: int = None,
    reset_history_at: Optional[int] = None,
    cache_stats_ledger=None,
    exact_tokenize: bool = False,
    pod_factory=None,
) -> Tuple[List[float], float, float, List[float]]:
    """One matrix cell: the request stream under ``strategy`` on the
    virtual clock, service times taken from the measured on-device
    prefill costs.  Returns (TTFTs, hit rate, mean queue depth,
    per-request routing seconds).

    ``reset_history_at``: request index at which the scheduler
    "restarts" — scheduler-local routing history is wiped, while the
    indexer (a separate service continuously fed by engine events)
    survives.  The reference architecture's core pitch: cache truth
    lives in the shared index, not in any scheduler's memory.
    """
    fleet = FleetRouter(
        strategy,
        with_kv=False,
        seed=seed,
        pool_blocks=pool_blocks,
        cache_stats_ledger=cache_stats_ledger,
        exact_tokenize=exact_tokenize,
        pod_factory=pod_factory,
    )
    ttfts: List[float] = []
    depths: List[int] = []
    routings: List[float] = []
    hits = 0
    try:
        for i, (request, hashes, arrival) in enumerate(
            zip(requests, hashes_list, arrivals)
        ):
            if i == reset_history_at and fleet.estimated is not None:
                fleet.estimated = EstimatedScorer()
            ttft, hit, depth, routing_seconds = _fleet_step(
                fleet, request, hashes, arrival, t_miss, t_hit
            )
            ttfts.append(ttft)
            hits += hit
            depths.append(depth)
            routings.append(routing_seconds)
    finally:
        fleet.shutdown()
    return ttfts, hits / len(requests), float(np.mean(depths)), routings


def _fleet_step(
    fleet: FleetRouter,
    request,
    hashes: Sequence[int],
    arrival: float,
    t_miss: float,
    t_hit: float,
) -> Tuple[float, bool, int, float]:
    """One request through route -> account -> FIFO queue -> commit on
    the virtual clock; returns (ttft, hit, queue depth at arrival,
    routing seconds).  Shared by the matrix cells and the
    indexer_restart regime — one semantics, per the FleetRouter
    contract."""
    group, text, tokens = request
    pod, routing_seconds = fleet.route(text, hashes)
    prepare = getattr(pod, "prepare_request", None)
    if prepare is not None:
        # Tiering policy hook (TieredSimPod): record the arrival and
        # decide admission/protection before account() allocates.
        prepare(hashes)
    hit, first_new, block_ids, evicted = fleet.account(pod, hashes)
    service_seconds = t_hit if hit else t_miss
    depth = sum(1 for c in fleet.completions[pod.name] if c > arrival)
    queue_start = max(arrival, fleet.pod_free_at[pod.name])
    done = queue_start + service_seconds
    fleet.pod_free_at[pod.name] = done
    fleet.completions[pod.name].append(done)
    fleet.commit(pod, tokens, hashes, first_new, block_ids, evicted)
    return (
        routing_seconds + (queue_start - arrival) + service_seconds,
        hit,
        depth,
        routing_seconds,
    )


def bench_indexer_restart(
    requests, hashes_list, t_miss: float, t_hit: float,
    ideal_service: float,
) -> dict:
    """Cold vs warm-recovered routing across an INDEXER restart.

    The ``restart`` matrix workload already prices losing scheduler
    history while the index survives; this regime prices losing the
    INDEX itself.  First half of the stream runs precise routing with
    the persistence journal tapped in and a snapshot published at the
    cut; then the indexer "restarts" — fresh Indexer, fresh index —
    while the engine pods keep their caches (pods did not restart).
    The second half runs twice from identical pod state: cold (empty
    index, the status quo before persistence/) and warm (snapshot +
    journal-tail recovery).  Device-free: only hit rates are compared,
    so no service-time measurement is needed.
    """
    import copy
    import tempfile

    from llm_d_kv_cache_manager_tpu.persistence import (
        PersistenceConfig,
        PersistenceManager,
        recover,
    )

    n = len(requests)
    half = n // 2
    qps = 0.7 * NUM_PODS / ideal_service
    arrivals = poisson_arrivals(qps, n, ARRIVAL_SEEDS[0])
    out: dict = {}
    with tempfile.TemporaryDirectory() as pdir:
        config = PersistenceConfig(directory=pdir)
        manager = PersistenceManager(config)
        fleet = FleetRouter(
            "precise", with_kv=False, seed=0, journal=manager.journal
        )
        try:
            for i in range(half):
                _fleet_step(
                    fleet, requests[i], hashes_list[i], arrivals[i],
                    t_miss, t_hit,
                )
            manager.snapshot(fleet.indexer.kv_block_index)
            saved_pods = copy.deepcopy(fleet.pods)
        finally:
            fleet.shutdown()
            manager.close()

        report = None
        for mode in ("cold", "warm"):
            restarted = FleetRouter("precise", with_kv=False, seed=0)
            # Engine pods survive an indexer restart: transplant their
            # caches; the queue clocks restart at zero.
            restarted.pods = copy.deepcopy(saved_pods)
            restarted.pod_by_name = {p.name: p for p in restarted.pods}
            restarted.pod_free_at = {p.name: 0.0 for p in restarted.pods}
            restarted.completions = {p.name: [] for p in restarted.pods}
            if mode == "warm":
                report = recover(
                    restarted.indexer.kv_block_index, config
                )
            hits = 0
            try:
                for i in range(half, n):
                    _, hit, _, _ = _fleet_step(
                        restarted, requests[i], hashes_list[i],
                        arrivals[i], t_miss, t_hit,
                    )
                    hits += hit
            finally:
                restarted.shutdown()
            out[f"{mode}_hit_rate"] = round(hits / (n - half), 3)
        out["recovered_block_keys"] = report.block_keys_restored
        out["replayed_records"] = report.records_replayed
    return out


def maybe_bench_indexer_restart(
    requests, hashes_list, t_miss, t_hit, ideal_service
) -> dict:
    """bench_indexer_restart under the degrade contract (headline
    reserve), one helper for both emit paths like maybe_bench_micro."""
    if _over_budget(reserve_s=60.0):
        return {"truncated": True}
    _progress("indexer_restart: cold vs warm-recovered routing")
    return bench_indexer_restart(
        requests, hashes_list, t_miss, t_hit, ideal_service
    )


def measure_readback_rtt() -> float:
    """Host->device->host round-trip floor for a trivial readback.

    TTFT sampling ends with an on-device argmax read back to the host;
    subtracting this floor leaves the prefill compute.  On a TPU host
    the floor is small beside a prefill (chip_smoke.py prints it as
    ``readback_floor_s``), and ``block_until_ready`` does wait there, so
    whether to keep the subtraction is ROADMAP S0's call."""
    probe = jnp.arange(8, dtype=jnp.int32)
    int(jnp.sum(probe))  # drain any queued work
    samples = []
    for _ in range(5):
        t0 = time.perf_counter()
        int(jnp.sum(probe))
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def jit_prefills(
    cfg: llama.LlamaConfig, prefix_tokens: int, interpret: bool = False
):
    """The pod's two jitted prefill steps, (miss, hit): full-prompt
    ``prefill_paged`` and ``prefill_continue`` over a cached
    ``prefix_tokens`` prefix.  The pool is donated: each pod's ~1.6 GB
    kv array is updated in place instead of copied per request (halves
    transient HBM, keeps the copy out of every TTFT sample)."""
    prefill_full = jax.jit(
        lambda p, t, kv, bt: llama.prefill_paged(
            p, t, kv, bt, cfg, interpret=interpret
        ),
        donate_argnums=(2,),
    )
    prefill_suffix = jax.jit(
        lambda p, t, kv, bt: llama.prefill_continue(
            p, t, kv, bt, prefix_tokens, cfg, interpret=interpret
        ),
        donate_argnums=(2,),
    )
    return prefill_full, prefill_suffix


def run_fleet(
    scheduler: str,
    requests,
    params,
    prefill_full,
    prefill_suffix,
    arrivals: Sequence[float],
    readback_rtt: float = 0.0,
) -> Tuple[List[float], float, List[float]]:
    """Run the request stream under one scheduler; returns (TTFTs, hit
    rate, per-request routing seconds).  A fresh indexer + event pool +
    pods per run.

    Open-loop load model (the reference's headline regime —
    BASELINE.md §1: Poisson arrivals at fixed QPS against N pods, where
    misrouting makes prefill queues pile up): requests *arrive* at
    ``arrivals[i]`` on a virtual clock; each pod is a FIFO server.  The
    prefill itself runs for real on the device and its measured wall
    time is the service time; queueing is then
    ``start = max(arrival, pod_free_at)`` and
    ``TTFT = routing + (start - arrival) + service``."""
    fleet = FleetRouter(scheduler, with_kv=True, params=params)
    ttfts: List[float] = []
    routings: List[float] = []
    hits = 0
    try:
        for (group, text, tokens), arrival in zip(requests, arrivals):
            hashes = block_hash_chain(tokens)
            pod, routing_seconds = fleet.route(text, hashes)
            routings.append(routing_seconds)
            hit, first_new, block_ids, evicted = fleet.account(
                pod, hashes
            )
            hits += hit
            token_arr = np.asarray(tokens, np.int32)
            table = jnp.asarray([block_ids], jnp.int32)
            service_start = time.perf_counter()
            if hit:
                logits, pod.kv = prefill_suffix(
                    pod.params,
                    jnp.asarray(token_arr[None, PREFIX_TOKENS:]),
                    pod.kv,
                    table,
                )
            else:
                logits, pod.kv = prefill_full(
                    pod.params, jnp.asarray(token_arr[None]), pod.kv, table
                )
            # Service ends when the first sampled token reaches the host
            # (the same on-device argmax + readback both paths).
            int(jnp.argmax(logits[0, -1]))
            service_seconds = max(
                time.perf_counter() - service_start - readback_rtt, 1e-4
            )
            queue_start = max(arrival, fleet.pod_free_at[pod.name])
            fleet.pod_free_at[pod.name] = queue_start + service_seconds
            ttfts.append(
                routing_seconds
                + (queue_start - arrival)
                + service_seconds
            )
            fleet.commit(
                pod, tokens, hashes, first_new, block_ids, evicted
            )
    finally:
        fleet.shutdown()
    return ttfts, hits / len(requests), routings


# ---------------- compute layers (detail.mfu / detail.kernels) ----------

TIMING_CHAIN_STEPS = 24

# The Pallas decode kernel is routed over the XLA gather only when it
# wins by at least this factor at every measured serving shape — a
# within-noise margin (r4: 1.09x) must not flip the default.
DECODE_ROUTE_MIN_SPEEDUP = 1.3


def time_chained(op, operand, readback_rtt: float = 0.0,
                 steps: int = TIMING_CHAIN_STEPS) -> float:
    """Compiled per-call latency of a sub-millisecond op.

    Chains ``steps`` data-dependent calls inside ONE jitted scan (the
    1e-30-scaled feedback keeps the value numerically unchanged while
    defeating constant folding), reads back once, subtracts the
    measured readback floor, divides — so host dispatch of each call
    stays out of the per-call number.  Whether a plain
    ``block_until_ready`` loop replaces this is ROADMAP S0's call.
    """
    def chain(x):
        def body(xc, _):
            out = op(xc)
            return xc + (1e-30 * out).astype(xc.dtype), None
        xf, _ = jax.lax.scan(body, x, None, length=steps)
        return xf

    chained = jax.jit(chain)
    float(jnp.sum(chained(operand)))  # compile + warm
    best = float("inf")
    for _ in range(3):  # min-of-3 bounds the RTT jitter contribution
        t0 = time.perf_counter()
        float(jnp.sum(chained(operand)))
        best = min(best, time.perf_counter() - t0)
    return max(best - readback_rtt, 1e-6) / steps


def max_rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6))


def bench_kernels(readback_rtt: float) -> dict:
    """detail.kernels: Pallas vs XLA compiled at serving shapes.

    Equality is asserted at bench time (a wrong-but-fast kernel must
    fail the bench, not win it); the decode winner is routed into the
    headline runs via ``LlamaConfig.decode_attention``.
    """
    if os.environ.get("KVTPU_BENCH_PLATFORM") == "cpu":
        # The Pallas kernels compile for the TPU only; main() has
        # already refused every other way of ending up without one.
        return {"skipped": "KVTPU_BENCH_PLATFORM=cpu"}
    from llm_d_kv_cache_manager_tpu.ops import flash_pallas
    from llm_d_kv_cache_manager_tpu.ops.attention import (
        causal_gqa_attention,
    )
    from llm_d_kv_cache_manager_tpu.ops.flash_attention import (
        flash_gqa_attention,
    )
    from llm_d_kv_cache_manager_tpu.ops.paged_attention import (
        paged_attention,
    )
    from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
        paged_decode_attention_pallas,
    )

    H, Hkv, Dh = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    nblocks = TOTAL_TOKENS // BLOCK_SIZE
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    kv_layer = jax.random.normal(
        k1, (POOL_BLOCKS, 2, BLOCK_SIZE, Hkv, Dh), jnp.bfloat16
    )

    def decode_operands(B):
        q = jax.random.normal(k2, (B, H, Dh), jnp.bfloat16)
        table = jnp.asarray(
            np.stack(
                [
                    np.random.RandomState(7 + i).permutation(
                        POOL_BLOCKS
                    )[:nblocks]
                    for i in range(B)
                ]
            ),
            jnp.int32,
        )
        ctx = jnp.full((B,), TOTAL_TOKENS, jnp.int32)
        return q, table, ctx

    # Decode is sub-ms per call: long chains lift the measurement well
    # above the readback jitter.  Sweep the kernel's blocks-per-
    # step tile at the primary shape (r3 review: BLOCKS_PER_STEP=4 was
    # tuned by anecdote); every candidate must pass the equality gate
    # before it may win.
    B_PRIMARY, B_WIDE = 4, 16  # the fleet's and a loaded serving batch
    q, table, ctx = decode_operands(B_PRIMARY)
    xla_out = paged_attention(q, kv_layer, table, ctx)
    sweep = {}
    best_p, t_decode_pallas, decode_err = None, float("inf"), 1.0
    for blocks_per_step in (2, 4, 8):
        err = max_rel_err(
            paged_decode_attention_pallas(
                q, kv_layer, table, ctx,
                blocks_per_step=blocks_per_step,
            ),
            xla_out,
        )
        assert err < 0.05, (
            f"paged-decode Pallas (P={blocks_per_step}) diverges from "
            f"XLA: max rel err {err:.4f}"
        )
        t = time_chained(
            lambda qq, p=blocks_per_step: paged_decode_attention_pallas(
                qq, kv_layer, table, ctx, blocks_per_step=p
            ),
            q,
            readback_rtt,
            steps=96,
        )
        sweep[f"P{blocks_per_step}_us"] = round(t * 1e6, 1)
        if t < t_decode_pallas:
            best_p, t_decode_pallas, decode_err = blocks_per_step, t, err
    # bf16-operand (mxu_native) dot variant at the winning tile: skips
    # the f32 upcast of K/V in VMEM.  Purely an optional speed variant:
    # failing the equality gate makes it INELIGIBLE (noted in the
    # sweep), never a bench abort — unlike the P-sweep asserts above,
    # which gate the default kernel's correctness.
    mxu_native = False
    t_pallas_f32, err_f32 = t_decode_pallas, decode_err
    err = max_rel_err(
        paged_decode_attention_pallas(
            q, kv_layer, table, ctx,
            blocks_per_step=best_p, mxu_native=True,
        ),
        xla_out,
    )
    if err < 0.05:
        t = time_chained(
            lambda qq: paged_decode_attention_pallas(
                qq, kv_layer, table, ctx,
                blocks_per_step=best_p, mxu_native=True,
            ),
            q,
            readback_rtt,
            steps=96,
        )
        sweep[f"P{best_p}_bf16_us"] = round(t * 1e6, 1)
        if t < t_decode_pallas:
            mxu_native, t_decode_pallas, decode_err = True, t, err
    else:
        sweep[f"P{best_p}_bf16_us"] = f"ineligible: rel err {err:.4f}"
    t_decode_xla = time_chained(
        lambda qq: paged_attention(qq, kv_layer, table, ctx),
        q,
        readback_rtt,
        steps=96,
    )

    # Second serving shape: the B=4 winner config re-measured at a
    # loaded batch, so the routing decision holds across shapes
    # instead of being a one-point anecdote.
    q_w, table_w, ctx_w = decode_operands(B_WIDE)
    xla_out_w = paged_attention(q_w, kv_layer, table_w, ctx_w)
    err_w = max_rel_err(
        paged_decode_attention_pallas(
            q_w, kv_layer, table_w, ctx_w,
            blocks_per_step=best_p, mxu_native=mxu_native,
        ),
        xla_out_w,
    )
    if mxu_native and err_w >= 0.05:
        # The optional bf16-operand variant must hold at EVERY shape;
        # diverging here demotes it (ineligible, never a bench abort —
        # same policy as the primary-shape gate) and reverts the
        # primary timing to the f32-upcast winner.
        sweep[f"P{best_p}_bf16_wide"] = (
            f"ineligible at B={B_WIDE}: rel err {err_w:.4f}"
        )
        mxu_native = False
        t_decode_pallas, decode_err = t_pallas_f32, err_f32
        err_w = max_rel_err(
            paged_decode_attention_pallas(
                q_w, kv_layer, table_w, ctx_w,
                blocks_per_step=best_p, mxu_native=False,
            ),
            xla_out_w,
        )
    assert err_w < 0.05, (
        f"paged-decode Pallas diverges at B={B_WIDE}: {err_w:.4f}"
    )
    t_pallas_w = time_chained(
        lambda qq: paged_decode_attention_pallas(
            qq, kv_layer, table_w, ctx_w,
            blocks_per_step=best_p, mxu_native=mxu_native,
        ),
        q_w,
        readback_rtt,
        steps=96,
    )
    t_xla_w = time_chained(
        lambda qq: paged_attention(qq, kv_layer, table_w, ctx_w),
        q_w,
        readback_rtt,
        steps=96,
    )

    # Routing rule (r4 verdict: a 1.09x margin is within noise of not
    # mattering): the Pallas kernel is routed only when it beats the
    # XLA gather by >= DECODE_ROUTE_MIN_SPEEDUP at EVERY measured
    # serving shape; otherwise the gather is the honest default.
    speedups = (
        t_decode_xla / t_decode_pallas,
        t_xla_w / t_pallas_w,
    )
    decode_winner = (
        "pallas"
        if min(speedups) >= DECODE_ROUTE_MIN_SPEEDUP
        else "gather"
    )

    # detail.kernels.ring: per-ring-step cost, einsum body vs the
    # mask-aware flash partial (ops/ring_flash_pallas.py).  A single
    # chip cannot run a real multi-device ring, but the ring's
    # wall-clock is R x (per-step body + overlapped permute), so the
    # step bodies ARE the comparison: the striped layout's win is
    # exactly flash_causal_step vs einsum_step on every device at
    # every step.
    from llm_d_kv_cache_manager_tpu.ops.ring_flash_pallas import (
        flash_partial,
        normalize_partial,
    )

    RING = 4  # a 4-chip pod-slice ring over the 8k prefill
    T_local = PREFIX_TOKENS // RING
    qr = jax.random.normal(k2, (1, T_local, H, Dh), jnp.bfloat16)
    kr = jax.random.normal(k3, (1, T_local, Hkv, Dh), jnp.bfloat16)
    vr = jax.random.normal(k1, (1, T_local, Hkv, Dh), jnp.bfloat16)

    def einsum_step(qq):
        """One ring step in the einsum body (diagonal/causal step):
        the dense op the ring's where()-masked einsum path pays per
        step regardless of the mask (ops/attention.py — the SAME math
        _ring_attention_local inlines, via the shared helper so the
        reference cannot drift)."""
        return causal_gqa_attention(qq, kr, vr)

    # Equality gate first: the flash causal partial must agree with
    # the einsum body's softmax before its time may count.
    acc, _, l = flash_partial(qr, kr, vr, causal_offset=0)
    ring_err = max_rel_err(
        normalize_partial(acc, l, qr.dtype), einsum_step(qr)
    )
    assert ring_err < 0.05, (
        f"ring flash partial diverges from einsum body: {ring_err:.4f}"
    )

    t_ring_einsum = time_chained(einsum_step, qr, readback_rtt, steps=8)
    t_ring_flash_causal = time_chained(
        lambda qq: flash_partial(qq, kr, vr, causal_offset=0)[0].astype(
            qq.dtype
        ),
        qr,
        readback_rtt,
        steps=8,
    )
    t_ring_flash_full = time_chained(
        lambda qq: flash_partial(
            qq, kr, vr, causal_offset=None
        )[0].astype(qq.dtype),
        qr,
        readback_rtt,
        steps=8,
    )

    Tq = PREFIX_TOKENS  # the 8k shared-prefix prefill shape
    qp = jax.random.normal(k3, (1, Tq, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(k1, (1, Tq, Hkv, Dh), jnp.bfloat16)
    vp = jax.random.normal(k2, (1, Tq, Hkv, Dh), jnp.bfloat16)
    flash_err = max_rel_err(
        flash_pallas.flash_gqa_attention_pallas(qp, kp, vp),
        flash_gqa_attention(qp, kp, vp),
    )
    assert flash_err < 0.05, (
        f"flash-prefill Pallas/XLA diverge: max rel err {flash_err:.4f}"
    )
    t_flash_pallas = time_chained(
        lambda qq: flash_pallas.flash_gqa_attention_pallas(qq, kp, vp),
        qp,
        readback_rtt,
    )
    t_flash_xla = time_chained(
        lambda qq: flash_gqa_attention(qq, kp, vp), qp, readback_rtt
    )
    return {
        "paged_decode": {
            "shape": f"B={B_PRIMARY} ctx={TOTAL_TOKENS} blocks={nblocks}",
            "pallas_us": round(t_decode_pallas * 1e6, 1),
            "xla_gather_us": round(t_decode_xla * 1e6, 1),
            "speedup_pallas": round(t_decode_xla / t_decode_pallas, 2),
            "wide_shape": f"B={B_WIDE} ctx={TOTAL_TOKENS}",
            "wide_pallas_us": round(t_pallas_w * 1e6, 1),
            "wide_xla_gather_us": round(t_xla_w * 1e6, 1),
            "wide_speedup_pallas": round(t_xla_w / t_pallas_w, 2),
            "max_rel_err": round(decode_err, 5),
            "winner": decode_winner,
            "route_rule": (
                f"pallas iff speedup >= {DECODE_ROUTE_MIN_SPEEDUP} at "
                "every measured shape"
            ),
            "blocks_per_step_sweep": sweep,
            "blocks_per_step": best_p,
            "mxu_native": mxu_native,
        },
        "flash_prefill": {
            "shape": f"B=1 T={Tq} H={H} D={Dh}",
            "pallas_ms": round(t_flash_pallas * 1e3, 2),
            "xla_scan_ms": round(t_flash_xla * 1e3, 2),
            "speedup_pallas": round(t_flash_xla / t_flash_pallas, 2),
            "max_rel_err": round(flash_err, 5),
        },
        "ring": {
            # Ring wall-clock ~= R x per-step body (permutes overlap),
            # so the step bodies carry the comparison: a striped flash
            # ring costs ~R x causal_step on every device; the einsum
            # ring costs ~R x einsum_step.
            "shape": (
                f"ring={RING} T_local={T_local} H={H} "
                f"Hkv={Hkv} D={Dh}"
            ),
            "einsum_step_ms": round(t_ring_einsum * 1e3, 2),
            "flash_causal_step_ms": round(
                t_ring_flash_causal * 1e3, 2
            ),
            "flash_full_step_ms": round(t_ring_flash_full * 1e3, 2),
            "striped_flash_vs_einsum": round(
                t_ring_einsum / t_ring_flash_causal, 2
            ),
            "max_rel_err": round(ring_err, 5),
        },
    }


def model_prefill_flops(T: int) -> float:
    """Matmul FLOPs of one dense prefill forward (causal-halved attn)."""
    D, H, Hkv, Dh, F, L, V = (
        CFG.d_model,
        CFG.n_heads,
        CFG.n_kv_heads,
        CFG.head_dim,
        CFG.d_ff,
        CFG.n_layers,
        CFG.vocab_size,
    )
    per_layer = (
        2 * T * D * (H * Dh + 2 * Hkv * Dh)  # qkv projections
        + 2 * T * T * H * Dh  # QK^T + AV, x2 flops, /2 causal
        + 2 * T * H * Dh * D  # output projection
        + 2 * T * 3 * D * F  # gate/up/down
    )
    return float(L * per_layer + 2 * T * D * V)  # + logits head


# device_kind substrings -> peak dense bf16 TFLOP/s per chip (public
# figures; v5p before v5 so the substring match is unambiguous).
PEAK_BF16_TFLOPS = (
    ("v6", 918.0),
    ("trillium", 918.0),
    ("v5p", 459.0),
    ("v5", 197.0),  # v5e / v5 lite
    ("v4", 275.0),
)


def peak_bf16_tflops(device_kind: str) -> float:
    """Peak of a TPU ``device_kind``; a device that is not in the table
    is an error, not a default."""
    kind = device_kind.lower()
    for tag, tflops in PEAK_BF16_TFLOPS:
        if tag in kind:
            return tflops
    raise KeyError(
        f"device_kind {device_kind!r} is not in PEAK_BF16_TFLOPS; add "
        "its published peak before reporting a utilisation on it"
    )


def bench_mfu(t_miss: float) -> dict:
    """detail.mfu: measured full-prefill throughput vs chip peak (no
    peak, and so no utilisation, on the explicit CPU platform)."""
    device = jax.devices()[0]
    peak = (
        peak_bf16_tflops(device.device_kind)
        if device.platform == "tpu"
        else None
    )
    flops = model_prefill_flops(TOTAL_TOKENS)
    achieved_tflops = flops / t_miss / 1e12
    return {
        "prefill_tokens": TOTAL_TOKENS,
        "prefill_tok_s": round(TOTAL_TOKENS / t_miss, 1),
        "model_flops_per_prefill": flops,
        "achieved_tflops": round(achieved_tflops, 2),
        "device_kind": device.device_kind,
        "peak_bf16_tflops": peak,
        "mfu": round(achieved_tflops / peak, 4) if peak else None,
    }


def warmup_indexes(requests) -> set:
    """Each group's FIRST arrival: an unavoidable cold miss under ANY
    scheduler (the reference's harness likewise excludes warmup)."""
    seen: set = set()
    warm: set = set()
    for i, (group, _, _) in enumerate(requests):
        if group not in seen:
            seen.add(group)
            warm.add(i)
    return warm


def poisson_arrivals(qps: float, n: int, seed: int) -> List[float]:
    arrival_rng = random.Random(seed)
    clock, out = 0.0, []
    for _ in range(n):
        clock += arrival_rng.expovariate(qps)
        out.append(clock)
    return out


def _matrix_cell(
    strategy,
    qps_frac,
    qps,
    requests,
    hashes_list,
    t_miss,
    t_hit,
    warmup,
    workload="steady",
    pool_blocks=None,
    reset_history_at=None,
) -> dict:
    """One (strategy, qps, workload) cell aggregated over the arrival
    seeds; per-seed values reported raw (no averaging away the spread
    the r3 review called out)."""
    p50s, p90s, depths, hit_rates = [], [], [], []
    for seed in ARRIVAL_SEEDS:
        arrivals = poisson_arrivals(qps, len(requests), seed)
        ttfts, hit_rate, depth, _ = run_fleet_virtual(
            strategy,
            requests,
            hashes_list,
            arrivals,
            t_miss,
            t_hit,
            seed,
            pool_blocks=pool_blocks,
            reset_history_at=reset_history_at,
        )
        steady = [t for i, t in enumerate(ttfts) if i not in warmup]
        p50s.append(round(float(np.percentile(steady, 50)), 4))
        p90s.append(round(float(np.percentile(steady, 90)), 4))
        depths.append(round(depth, 2))
        hit_rates.append(round(hit_rate, 3))
    return {
        "strategy": strategy,
        "workload": workload,
        "qps_frac": qps_frac,
        "qps": round(qps, 2),
        "p50_ttft_s": p50s,
        "p90_ttft_s": p90s,
        "mean_queue_depth": depths,
        "hit_rate": hit_rates,
    }


def run_matrix(
    requests,
    hashes_list,
    t_miss: float,
    t_hit: float,
    ideal_service: float,
    warmup: set,
) -> Tuple[List[dict], bool]:
    """detail.matrix: strategies x QPS ladder x arrival seeds on the
    virtual clock, plus a pool-churn regime at the headline QPS.

    Returns (cells, truncated): past the soft budget the remaining
    cells are dropped and flagged rather than overrunning the driver's
    timeout with the headline unreported."""
    cells: List[dict] = []

    def _out_of_time() -> bool:
        return _over_budget(reserve_s=30.0)

    for frac in QPS_FRACTIONS:
        qps = frac * NUM_PODS / ideal_service
        for strategy in STRATEGIES:
            if _out_of_time():
                return cells, True
            cells.append(
                _matrix_cell(
                    strategy, frac, qps, requests, hashes_list,
                    t_miss, t_hit, warmup,
                )
            )
    # Churn regime: pods hold barely one group's working set, so the
    # allocator wraps and evicts constantly.  This is where "precise"
    # earns its name: BlockRemoved events keep the index truthful about
    # what each pod still holds, while the estimated scorer keeps
    # routing to pods that already evicted the prefix (the reference's
    # precise-vs-estimated gap, benchmarking/73-capacity).
    qps = 0.7 * NUM_PODS / ideal_service
    for strategy in STRATEGIES:
        if _out_of_time():
            return cells, True
        cells.append(
            _matrix_cell(
                strategy, 0.7, qps, requests, hashes_list,
                t_miss, t_hit, warmup,
                workload="churn",
                pool_blocks=CHURN_POOL_BLOCKS,
            )
        )
    # Restart regime: the scheduler loses its routing history halfway
    # through (replica restart / failover).  The index — a separate
    # service continuously rebuilt from engine events — survives, so
    # "precise" recovers instantly while history-only routing pays a
    # cold restart.  This is the architecture's core pitch measured.
    # Only the history-bearing strategies: for load/random/rr the
    # reset is a no-op and the cells would duplicate the steady rows.
    for strategy in ("precise", "estimated"):
        if _out_of_time():
            return cells, True
        cells.append(
            _matrix_cell(
                strategy, 0.7, qps, requests, hashes_list,
                t_miss, t_hit, warmup,
                workload="restart",
                reset_history_at=len(requests) // 2,
            )
        )
    return cells, False


def init_device() -> jax.Device:
    """Initialise JAX once, in this process, and return device 0.

    A chip belongs to one process at a time, so nothing probes it from
    a child.  No TPU is an error — a benchmark number must name the
    device it ran on — unless ``KVTPU_BENCH_PLATFORM=cpu`` asked for the
    CPU explicitly (CI smoke geometry, bench-logic checks)."""
    platform = os.environ.get("KVTPU_BENCH_PLATFORM")
    if platform:
        jax.config.update("jax_platforms", platform)
    configure_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and platform != "cpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU (platform {device.platform!r}); "
            "set KVTPU_BENCH_PLATFORM=cpu to run on the CPU on purpose"
        )
    return device


def make_workload() -> Tuple[list, set, List[List[int]]]:
    """The ONE workload of the headline runs, the matrix and the
    device-free regimes: seeded prompts, warmup (first arrival per
    group), per-request hash chains."""
    requests = make_prompts(random.Random(0))
    warmup_idx = warmup_indexes(requests)
    hashes_list = [block_hash_chain(tokens) for _, _, tokens in requests]
    return requests, warmup_idx, hashes_list


def ideal_service_time(
    t_miss: float, t_hit: float, n_requests: int
) -> float:
    """Mean service time under IDEAL routing: the first request per
    group misses, every other hits.  Shared by the headline and every
    virtual-clock regime, so they all run at the same effective QPS
    fraction."""
    miss_fraction = NUM_GROUPS / n_requests
    return miss_fraction * t_miss + (1 - miss_fraction) * t_hit


def measure_routing_micro(
    requests, hashes_list, warmup: set
) -> List[float]:
    """Steady-state scoring-RPC latency samples (tokenize -> chained
    hashes -> index lookup -> tier-weighted score), device-free.

    One precise pass of the SAME fleet loop the matrix cells run
    (run_fleet_virtual — one semantics, per the FleetRouter contract);
    the virtual clock is irrelevant here, so arrivals are all zero and
    the service times are placeholders."""
    _, _, _, routings = run_fleet_virtual(
        "precise",
        requests,
        hashes_list,
        [0.0] * len(requests),
        t_miss=1.0,
        t_hit=1.0,
        seed=0,
    )
    return [r for i, r in enumerate(routings) if i not in warmup]


def bench_micro() -> dict:
    """detail.micro: index + tokenization-path microbenches (reference
    tests/profiling/kv_cache_index/index_benchmark_test.go:97-197 and
    the tokenization make-bench) — device-free, so they are always
    emittable, chip or no chip."""
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
        ChunkedTokenDatabase,
        EMPTY_BLOCK_HASH,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
        InMemoryIndex,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
        InMemoryIndexConfig,
        PodEntry,
    )

    rng = random.Random(97)
    # Token->key chain: the per-request hashing cost at the headline's
    # prompt length.
    db = ChunkedTokenDatabase(TokenProcessorConfig(block_size=BLOCK_SIZE))
    tokens = [rng.randrange(1, 16384) for _ in range(TOTAL_TOKENS)]
    db.tokens_to_kv_block_keys(EMPTY_BLOCK_HASH, tokens, MODEL_NAME)  # warm
    reps, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < 0.5:
        keys = db.tokens_to_kv_block_keys(
            EMPTY_BLOCK_HASH, tokens, MODEL_NAME
        )
        reps += 1
    hash_elapsed = time.perf_counter() - t0
    # Index add + chain lookup at the reference microbench scale.
    # Fixtures (key lists, PodEntry objects) are built OUTSIDE the
    # timed region so the number measures the index, not allocation
    # of throwaway arguments (Go microbench fixture-setup discipline).
    n_keys = 10_000
    index = InMemoryIndex(InMemoryIndexConfig(size=n_keys * 2))
    idx_keys = [rng.getrandbits(64) for _ in range(n_keys)]
    key_lists = [[key] for key in idx_keys]
    pod_entries = [
        [PodEntry(f"pod-{i}", "hbm")] for i in range(NUM_PODS)
    ]
    t0 = time.perf_counter()
    for i, key_list in enumerate(key_lists):
        index.add(key_list, key_list, pod_entries[i % NUM_PODS])
    add_elapsed = time.perf_counter() - t0
    chain = len(keys)
    lookups, t0 = 0, time.perf_counter()
    for offset in range(0, n_keys - chain, chain):
        index.lookup(idx_keys[offset:offset + chain], None)
        lookups += 1
    lookup_elapsed = time.perf_counter() - t0
    return {
        "hash_chain_tok_s": round(reps * TOTAL_TOKENS / hash_elapsed, 0),
        "index_add_us_per_key": round(1e6 * add_elapsed / n_keys, 2),
        "index_lookup_us_per_chain": round(
            1e6 * lookup_elapsed / max(lookups, 1), 1
        ),
        "index_keys": n_keys,
        "chain_len": chain,
    }


def maybe_bench_micro(context: str) -> dict:
    """bench_micro under the degrade contract: skipped + marked past
    the budget.  One helper for both emit paths so the sentinel shape
    and reserve stay in lockstep."""
    if _over_budget(reserve_s=60.0):
        return {"truncated": True}
    _progress(f"{context}: index/tokenization microbenches")
    return bench_micro()


READ_PATH_CELL_S = _env_float("KVTPU_BENCH_READPATH_S", 1.2)
ANALYTICS_CELL_S = _env_float("KVTPU_BENCH_ANALYTICS_S", 1.2)


def bench_read_path(cell_seconds: Optional[float] = None) -> dict:
    """detail.read_path regime: per-request scoring throughput/latency
    through the REAL indexer read path (tokenize -> hash -> lookup ->
    score), device-free.

    Three workloads: "warm_multi_turn" (a conversation whose growing
    prefix is resident on two pods — the memoized-suffix-hashing case),
    "cold" (8k prompts the index has never seen — the early-exit case),
    and "mixed" (alternating).  Each also runs with the fast lane OFF
    (READ_PATH_FAST_LANE semantics via IndexerConfig) — the straight
    pre-fast-lane path over the same data — and a parity check asserts
    identical scores both ways, because the fast lane must never change
    routing decisions (docs/performance.md)."""
    cell_s = READ_PATH_CELL_S if cell_seconds is None else cell_seconds
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import PodEntry

    rng = random.Random(171)
    pods = [f"pod-{i}" for i in range(NUM_PODS)]

    def new_indexer(fast: bool, score_memo: bool = True) -> Indexer:
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size=BLOCK_SIZE
                ),
                kvblock_index_config=IndexConfig(),
                read_path_fast_lane=fast,
                score_memo_size=None if score_memo else 0,
            ),
            tokenizer=WordTokenizer(),
        )
        indexer.run()
        return indexer

    # One conversation: an 8k base prefix plus 8 turns of 256-token
    # suffixes.  Scoring request t sees the whole conversation so far.
    convo = [rng.randrange(1, 16384) for _ in range(PREFIX_TOKENS)]
    turns: List[str] = []
    for _ in range(8):
        convo.extend(
            rng.randrange(1, 16384) for _ in range(SUFFIX_TOKENS)
        )
        turns.append(" ".join(f"t{t}" for t in convo))
    cold_prompts = [
        " ".join(
            f"t{rng.randrange(1, 16384)}" for _ in range(PREFIX_TOKENS)
        )
        for _ in range(24)
    ]
    mixed = [p for pair in zip(turns * 3, cold_prompts) for p in pair]

    def seed(indexer: Indexer) -> None:
        keys = indexer.token_processor.tokens_to_kv_block_keys(
            0, convo, MODEL_NAME
        )
        indexer.kv_block_index.add(keys, keys, [PodEntry("pod-0", "hbm")])
        indexer.kv_block_index.add(keys, keys, [PodEntry("pod-1", "host")])

    def run_cell(indexer: Indexer, prompts: List[str]) -> dict:
        # One warm pass populates the tokenization prefix store, so the
        # cell measures steady-state scoring, not first-touch encodes.
        for prompt in prompts:
            indexer.get_pod_scores(prompt, MODEL_NAME, pods)
        latencies: List[float] = []
        deadline = time.perf_counter() + cell_s
        i = 0
        while time.perf_counter() < deadline:
            prompt = prompts[i % len(prompts)]
            t0 = time.perf_counter()
            indexer.get_pod_scores(prompt, MODEL_NAME, pods)
            latencies.append(time.perf_counter() - t0)
            i += 1
        total = sum(latencies)
        return {
            "scores_per_sec": (
                round(len(latencies) / total, 1) if total else 0.0
            ),
            "p50_us": round(float(np.percentile(latencies, 50)) * 1e6, 1),
            "p99_us": round(float(np.percentile(latencies, 99)) * 1e6, 1),
            "requests": len(latencies),
        }

    fast = new_indexer(True)
    off = new_indexer(False)
    # Three lanes: the full fast lane (score memo included — the
    # steady-state production path), the fast lane without the score
    # memo (isolates incremental hashing + early exit; also the honest
    # "cold" lane, since the memo would turn the repeating cold prompt
    # set into exact-repeat hits), and the straight pre-fast-lane path.
    no_memo = new_indexer(True, score_memo=False)
    try:
        seed(fast)
        seed(off)
        seed(no_memo)
        parity_ok = True
        for prompt in turns[:3] + cold_prompts[:2] + [turns[-1]]:
            # Two passes, compared ACROSS lanes per pass: the warm
            # (second) pass serves prefix-store-truncated tokens —
            # identically on every lane — so cold-vs-warm would
            # spuriously differ, while each pass must agree across
            # lanes (the memoized lane serves pass 3+ from the score
            # memo; one extra repeat pins that too).
            for _ in range(2):
                on_scores = fast.get_pod_scores(prompt, MODEL_NAME, pods)
                off_scores = off.get_pod_scores(prompt, MODEL_NAME, pods)
                no_memo_scores = no_memo.get_pod_scores(
                    prompt, MODEL_NAME, pods
                )
                if not (on_scores == off_scores == no_memo_scores):
                    parity_ok = False
            if fast.get_pod_scores(prompt, MODEL_NAME, pods) != off_scores:
                parity_ok = False
        result = {
            "warm_multi_turn": run_cell(fast, turns),
            "warm_multi_turn_no_memo": run_cell(no_memo, turns),
            "cold": run_cell(no_memo, cold_prompts),
            "mixed": run_cell(fast, mixed),
            "warm_multi_turn_fastlane_off": run_cell(off, turns),
            "cold_fastlane_off": run_cell(off, cold_prompts),
            "parity": "ok" if parity_ok else "MISMATCH",
            "cell_seconds": cell_s,
            "block_size": BLOCK_SIZE,
            "prefix_tokens": PREFIX_TOKENS,
        }
        warm_on = result["warm_multi_turn"]["scores_per_sec"]
        warm_off = result["warm_multi_turn_fastlane_off"]["scores_per_sec"]
        result["warm_speedup_vs_off"] = (
            round(warm_on / warm_off, 2) if warm_off else None
        )

        # ---- profiler A/B: the always-on sampling profiler's cost to
        # the warm-multi-turn headline at its DEFAULT rate
        # (obs/profiler.py; docs/observability.md).  The profiler adds
        # zero instructions to application threads — its only cost is
        # the sampler thread competing for the GIL — so the bound is a
        # whole-process claim, measured the same alternating best-of
        # way as the trace A/B.
        from llm_d_kv_cache_manager_tpu.obs.profiler import (
            ProfilerConfig,
            SamplingProfiler,
        )

        prof = SamplingProfiler(ProfilerConfig())  # shipped default hz
        best = {True: 0.0, False: 0.0}
        # Best-of-4 with alternating order, exactly like the cluster
        # trace A/B: the signal (a sampler thread's GIL share) is well
        # under run-to-run scheduler noise at shorter settings.
        for ab_round in range(4):
            order = (True, False) if ab_round % 2 == 0 else (False, True)
            for prof_on in order:
                if prof_on:
                    prof.start()
                else:
                    prof.close()
                best[prof_on] = max(
                    best[prof_on],
                    run_cell(fast, turns)["scores_per_sec"],
                )
        top_self = prof.top(8)
        prof.close()
        overhead = (
            max(0.0, (best[False] - best[True]) / best[False])
            if best[False]
            else 0.0
        )
        result["profiler_ab"] = {
            "hz": prof.config.hz,
            "profiler_on_sps": best[True],
            "profiler_off_sps": best[False],
            "overhead": round(overhead, 4),
            "bound": PROFILE_OVERHEAD_BOUND,
            "within_bound": overhead <= PROFILE_OVERHEAD_BOUND,
            "top_self": top_self,
        }

        # ---- capture A/B: the always-on input flight recorder's cost
        # to the warm-multi-turn headline (obs/capture.py; ISSUE 15's
        # ≤3% acceptance bound).  The recorder's hot-path work is one
        # lock hop + a tuple append per scored request (token lists
        # ride by reference, serialization is dump-time only), so the
        # A/B is measured the same alternating best-of-4 way as the
        # profiler's — the signal is well under scheduler noise at
        # shorter settings.
        from llm_d_kv_cache_manager_tpu.obs.capture import (
            CaptureConfig,
            InputCaptureRecorder,
        )

        # Shipped-default config (same reasoning as the event_storm
        # cell: the bound is a claim about production settings).
        recorder = InputCaptureRecorder(CaptureConfig())
        best = {True: 0.0, False: 0.0}
        # Best-of-6 (vs the profiler's 4): the recorder's true cost is
        # ~1% — a single scheduler hiccup on the off side at best-of-4
        # could still read past the 3% bound.
        for ab_round in range(6):
            order = (True, False) if ab_round % 2 == 0 else (False, True)
            for cap_on in order:
                fast.set_capture(recorder if cap_on else None)
                best[cap_on] = max(
                    best[cap_on],
                    run_cell(fast, turns)["scores_per_sec"],
                )
        fast.set_capture(None)
        ring = recorder.status()["sources"]["scores"]
        overhead = (
            max(0.0, (best[False] - best[True]) / best[False])
            if best[False]
            else 0.0
        )
        result["capture_ab"] = {
            "capture_on_sps": best[True],
            "capture_off_sps": best[False],
            "overhead": round(overhead, 4),
            "bound": CAPTURE_OVERHEAD_BOUND,
            "within_bound": overhead <= CAPTURE_OVERHEAD_BOUND,
            "recorded": ring["appended"],
            "ring_bytes": ring["bytes"],
        }
        return result
    finally:
        fast.shutdown()
        off.shutdown()
        no_memo.shutdown()


def maybe_bench_read_path(context: str) -> dict:
    """bench_read_path under the degrade contract (headline first)."""
    if _over_budget(reserve_s=45.0):
        return {"truncated": True}
    _progress(f"{context}: read_path scoring regime")
    return bench_read_path()


# ------------- replica_scaleout: clustered-indexer regime ---------------


SCALEOUT_CELL_S = _env_float("KVTPU_BENCH_SCALEOUT_S", 1.0)
# Synthetic per-RPC round-trip injected into the pipelined A/B cell's
# transports: the in-process transport is so cheap that overlapping
# it never pays (adaptive arming correctly stays sequential), so the
# cell that prices the OVERLAP itself needs a realistic wire cost.
# 2ms ~ cross-zone gRPC hop; large enough that the fixed per-request
# tokenize/hash/score work doesn't drown the RPC share the A/B is
# measuring.  0 skips the cell.
SCALEOUT_RTT_S = _env_float("KVTPU_BENCH_SCALEOUT_RTT_S", 0.002)
# The pinned failover degradation envelope (docs/replication.md): the
# post-kill hit rate over the measurement window may dip at most this
# far below the pre-kill window — the follower's standby slice is warm,
# so the only lost state is whatever hadn't synced at the kill.
SCALEOUT_DIP_ENVELOPE = 0.15
# Untraced-path budget for the fleet observability plane (ISSUE 13):
# trace plumbing + per-replica rpc accounting may cost at most this
# fraction of clustered scores/sec when no request is traced.
TRACE_OVERHEAD_BOUND = 0.03
# Pinned ceiling for the always-on sampling profiler's cost to a hot
# headline at its DEFAULT rate (obs/profiler.py; the read_path and
# event_storm profiler_ab cells assert it).
PROFILE_OVERHEAD_BOUND = 0.03
# Pinned ceiling for the always-on input flight recorder's cost to
# the same two headlines (obs/capture.py; the read_path and
# event_storm capture_ab cells assert it — the ISSUE 15 acceptance
# bound for capture-on overhead).
CAPTURE_OVERHEAD_BOUND = 0.03


def bench_replica_scaleout(
    requests, hashes_list, t_miss: float, t_hit: float,
    ideal_service: float, cell_seconds: Optional[float] = None,
) -> dict:
    """detail.replica_scaleout regime (docs/replication.md): the
    indexer as an N-replica service, extending ``indexer_restart`` —
    that regime prices losing the whole index; this one prices losing
    ONE replica of it.

    Cell 1 (scores/sec): per-request scoring throughput through the
    REAL read path against a single-process in-memory index, a
    1-replica cluster (pure RPC-hop overhead), and a 3-replica cluster
    (in-process replicas over the local transport), with an exact
    score-parity check across all three — the cluster must never
    change a routing decision (the same oracle the parity tests pin).

    Cell 2 (failover dip): the fleet stream runs precise routing with
    the 3-replica cluster (replication followers syncing); halfway, one
    replica is KILLED mid-traffic.  Engine pods keep their caches —
    only the index slice moves — so the hit-rate dip between the
    pre-kill and post-kill windows is the cost of failover, asserted
    inside the pinned envelope.
    """
    import tempfile

    from llm_d_kv_cache_manager_tpu.cluster import LocalCluster
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import PodEntry

    cell_s = SCALEOUT_CELL_S if cell_seconds is None else cell_seconds
    rng = random.Random(733)
    pods = [f"pod-{i}" for i in range(NUM_PODS)]
    out: dict = {"dip_envelope": SCALEOUT_DIP_ENVELOPE}

    # ---- cell 1: multi-replica scores/sec + parity -------------------
    def new_indexer(
        index=None,
        pipeline_depth=None,
        score_memo=0,
        exact_tokenize=False,
    ) -> Indexer:
        # exact_tokenize (the cache_analytics precedent): a ratio
        # above 1.0 makes the prefix store's serve path unreachable,
        # so warm repeats re-walk the chain in chunks instead of
        # collapsing to one pre-hashed slice — the pipelined A/B
        # prices the chunked drive, which the serve path would mask.
        tokenization_config = (
            TokenizationPoolConfig(min_prefix_overlap_ratio=1.01)
            if exact_tokenize
            else TokenizationPoolConfig()
        )
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size=BLOCK_SIZE
                ),
                kvblock_index_config=IndexConfig(),
                tokenizers_pool_config=tokenization_config,
                score_memo_size=score_memo,
                cache_stats=False,
                pipeline_depth=pipeline_depth,
            ),
            tokenizer=WordTokenizer(),
            kv_block_index=index,
        )
        indexer.run()
        return indexer

    convo = [rng.randrange(1, 16384) for _ in range(PREFIX_TOKENS)]
    prompts: List[str] = []
    for _ in range(6):
        convo.extend(
            rng.randrange(1, 16384) for _ in range(SUFFIX_TOKENS)
        )
        prompts.append(" ".join(f"t{t}" for t in convo))

    def seed_index(indexer: Indexer) -> None:
        keys = indexer.token_processor.tokens_to_kv_block_keys(
            0, convo, MODEL_NAME
        )
        indexer.kv_block_index.add(keys, keys, [PodEntry("pod-0", "hbm")])
        indexer.kv_block_index.add(keys, keys, [PodEntry("pod-1", "host")])

    def run_cell(indexer: Indexer) -> dict:
        for prompt in prompts:  # steady-state warmup
            indexer.get_pod_scores(prompt, MODEL_NAME, pods)
        latencies: List[float] = []
        deadline = time.perf_counter() + cell_s
        i = 0
        while time.perf_counter() < deadline:
            t0 = time.perf_counter()
            indexer.get_pod_scores(
                prompts[i % len(prompts)], MODEL_NAME, pods
            )
            latencies.append(time.perf_counter() - t0)
            i += 1
        total = sum(latencies)
        return {
            "scores_per_sec": (
                round(len(latencies) / total, 1) if total else 0.0
            ),
            "p50_us": round(float(np.percentile(latencies, 50)) * 1e6, 1),
            "p99_us": round(float(np.percentile(latencies, 99)) * 1e6, 1),
            "requests": len(latencies),
        }

    cluster3 = LocalCluster()
    cluster1 = LocalCluster(replica_ids=("solo",))
    single = new_indexer()
    over3 = new_indexer(cluster3.remote_index)
    over1 = new_indexer(cluster1.remote_index)
    try:
        for indexer in (single, over3, over1):
            seed_index(indexer)
        parity_ok = True
        for prompt in prompts:
            want = single.get_pod_scores(prompt, MODEL_NAME, pods)
            if (
                over3.get_pod_scores(prompt, MODEL_NAME, pods) != want
                or over1.get_pod_scores(prompt, MODEL_NAME, pods) != want
            ):
                parity_ok = False
        out["single"] = run_cell(single)
        out["cluster_1_replica"] = run_cell(over1)
        out["cluster_3_replicas"] = run_cell(over3)
        out["parity"] = "ok" if parity_ok else "MISMATCH"
        out["cell_seconds"] = cell_s

        # ---- trace A/B: untraced-path cost of the observability
        # plane.  Side A runs the default plane (trace plumbing +
        # per-replica rpc accounting armed; requests untraced); side B
        # strips it wholesale — router trace checks, tallies, and the
        # replica span piggyback all off, i.e. the pre-plane frame
        # shape.  Best-of-4 with alternating order damps scheduler and
        # warm-cache bias (the signal is a few µs per RPC); the pinned
        # bound is TRACE_OVERHEAD_BOUND.
        def set_plane(on: bool) -> None:
            cluster3.remote_index.trace_rpcs = on
            cluster3.remote_index.rpc_accounting = on
            for replica in cluster3.replicas.values():
                replica.trace_piggyback = on

        best = {True: 0.0, False: 0.0}
        for ab_round in range(4):
            order = (True, False) if ab_round % 2 == 0 else (False, True)
            for plane_on in order:
                set_plane(plane_on)
                best[plane_on] = max(
                    best[plane_on],
                    run_cell(over3)["scores_per_sec"],
                )
        set_plane(True)
        overhead = (
            max(0.0, (best[False] - best[True]) / best[False])
            if best[False]
            else 0.0
        )
        out["trace_ab"] = {
            "plane_on_sps": best[True],
            "plane_off_sps": best[False],
            "overhead": round(overhead, 4),
            "bound": TRACE_OVERHEAD_BOUND,
            "within_bound": overhead <= TRACE_OVERHEAD_BOUND,
        }

        # ---- fan-out profile: a continuous-profiler capture of the
        # 3-replica scoring drive (obs/profiler.py), the live "before"
        # for ROADMAP item 3 — the share of wall time inside
        # cluster/remote_index.py IS the sequential owner/chunk
        # fan-out the pipelining work must erase, and the rpc
        # critical-path counters ride along so the A/B has exact
        # owner-RPC depths next to the stack shares.
        from llm_d_kv_cache_manager_tpu.obs.profiler import (
            ProfilerConfig as _ProfCfg,
            SamplingProfiler as _Prof,
        )

        fan_hz = 199.0  # dense: the cell is short and sampler-only
        fan_prof = _Prof(_ProfCfg(hz=fan_hz))
        fan_prof.start()
        fan_cell = run_cell(over3)
        fan_prof.close()
        fan_total = 0
        fan_in_remote = 0
        for line in fan_prof.collapsed().splitlines():
            stack, _, count_text = line.rpartition(" ")
            if not stack.startswith("main;"):
                # The drive (and the in-process replica RPCs under
                # it) runs on the bench main thread; idle pool
                # threads would only dilute the share.
                continue
            count = int(count_text)
            fan_total += count
            if "cluster/remote_index.py" in stack:
                fan_in_remote += count
        out["fanout_profile"] = {
            "hz": fan_hz,
            "scores_per_sec": fan_cell["scores_per_sec"],
            "samples": fan_total,
            "remote_index_share": (
                round(fan_in_remote / fan_total, 4)
                if fan_total
                else None
            ),
            "top_self": fan_prof.top(10),
            "critical_path": cluster3.remote_index.rpc_stats()[
                "critical_path"
            ],
        }
    finally:
        single.shutdown()
        over3.shutdown()
        over1.shutdown()
        cluster3.close()
        cluster1.close()

    # ---- pipelined A/B: read-path fan-out pipelining ------------------
    # (docs/replication.md "Pipelined read path").  The cells above run
    # in-process transports whose whole "RPC" is cheaper than a thread
    # handoff, so adaptive arming correctly keeps them sequential; this
    # cell injects a realistic per-call RTT and runs the same warm
    # multi-turn workload through the sequential parity oracle
    # (fanout_workers=0 + pipeline_depth=0) and the overlapped +
    # pipelined drive (defaults, arming forced) on twin 3-replica
    # clusters: scores asserted identical, warm throughput asserted
    # >= 2x, pipelined warm p99 reported as a multiple of the RTT.  A
    # cold cell (unique single-shot prompts, index misses) prices the
    # speculation overhead, and a memo cell pins memo-hit repeats at
    # ~single-process rates with ZERO lookup RPC rounds.  Profiler
    # captures around both warm cells give the before/after
    # main-thread remote_index.py wall share (ROADMAP item 3's
    # acceptance: the sequential fan-out share must shrink).
    class _RttTransport:
        """Transport decorator charging one synthetic RTT per call."""

        def __init__(self, inner, rtt_s: float) -> None:
            self._inner = inner
            self._rtt_s = rtt_s
            self.supports_deadline = getattr(
                inner, "supports_deadline", False
            )

        def call(self, method, args):
            time.sleep(self._rtt_s)
            return self._inner.call(method, args)

        def call_ex(self, method, args, traceparent=None):
            time.sleep(self._rtt_s)
            return self._inner.call_ex(
                method, args, traceparent=traceparent
            )

        def call_vv(self, method, args, traceparent=None, timeout=None):
            time.sleep(self._rtt_s)
            return self._inner.call_vv(
                method, args, traceparent=traceparent, timeout=timeout
            )

    def _main_remote_share(prof) -> Optional[float]:
        # Main-thread wall share inside cluster/remote_index.py: the
        # sequential drive blocks THERE (transport waits under _call);
        # the pipelined drive blocks in the indexer's handle.result()
        # while pool threads do the waiting, so the share collapsing
        # is exactly the pipelining landing.
        total = hits = 0
        for line in prof.collapsed().splitlines():
            stack, _, count_text = line.rpartition(" ")
            if not stack.startswith("main;"):
                continue
            count = int(count_text)
            total += count
            if "cluster/remote_index.py" in stack:
                hits += count
        return round(hits / total, 4) if total else None

    rtt_s = SCALEOUT_RTT_S
    if rtt_s > 0.0:
        wrap = lambda _rid, t: _RttTransport(t, rtt_s)  # noqa: E731
        seq_cluster = LocalCluster(
            fanout_workers=0, transport_wrap=wrap
        )
        pipe_cluster = LocalCluster(
            overlap_min_rpc_s=0.0, transport_wrap=wrap
        )
        seq_ix = new_indexer(
            seq_cluster.remote_index,
            pipeline_depth=0,
            exact_tokenize=True,
        )
        pipe_ix = new_indexer(
            pipe_cluster.remote_index, exact_tokenize=True
        )
        memo_pipe = new_indexer(
            pipe_cluster.remote_index,
            score_memo=256,
            exact_tokenize=True,
        )
        memo_single = new_indexer(score_memo=256, exact_tokenize=True)
        try:
            for indexer in (seq_ix, pipe_ix, memo_single):
                seed_index(indexer)
            ab_parity = True
            for prompt in prompts:
                if seq_ix.get_pod_scores(
                    prompt, MODEL_NAME, pods
                ) != pipe_ix.get_pod_scores(prompt, MODEL_NAME, pods):
                    ab_parity = False

            prof_before = _Prof(_ProfCfg(hz=fan_hz))
            prof_before.start()
            seq_warm = run_cell(seq_ix)
            prof_before.close()
            prof_after = _Prof(_ProfCfg(hz=fan_hz))
            prof_after.start()
            pipe_warm = run_cell(pipe_ix)
            prof_after.close()
            before_share = _main_remote_share(prof_before)
            after_share = _main_remote_share(prof_after)
            speedup = (
                round(
                    pipe_warm["scores_per_sec"]
                    / seq_warm["scores_per_sec"],
                    2,
                )
                if seq_warm["scores_per_sec"]
                else None
            )

            # Cold: unique prompts, every chain misses at block 0 —
            # prices tokenize + first-chunk fan-out + the speculation
            # a dead chain drops on the floor.
            cold_rng = random.Random(401)
            cold_pool = [
                " ".join(
                    f"c{cold_rng.randrange(1, 1 << 30)}"
                    for _ in range(128)
                )
                for _ in range(320)
            ]

            def run_cold(indexer, cold_prompts) -> dict:
                latencies: List[float] = []
                for prompt in cold_prompts:
                    t0 = time.perf_counter()
                    indexer.get_pod_scores(prompt, MODEL_NAME, pods)
                    latencies.append(time.perf_counter() - t0)
                total = sum(latencies)
                return {
                    "scores_per_sec": (
                        round(len(latencies) / total, 1)
                        if total
                        else 0.0
                    ),
                    "p99_us": round(
                        float(np.percentile(latencies, 99)) * 1e6, 1
                    ),
                    "requests": len(latencies),
                }

            seq_cold = run_cold(seq_ix, cold_pool[:160])
            pipe_cold = run_cold(pipe_ix, cold_pool[160:])

            # Memo: repeats of one warm prompt must hit the memo (0
            # lookup RPC rounds — touch_chain recency refreshes ride
            # the off-thread pool) at ~the single-process memo rate.
            def run_repeat(indexer, seconds: float) -> dict:
                repeat_prompt = prompts[-1]
                for _ in range(3):  # populate + validate the memo
                    indexer.get_pod_scores(
                        repeat_prompt, MODEL_NAME, pods
                    )
                count = 0
                t0 = time.perf_counter()
                deadline = t0 + seconds
                while time.perf_counter() < deadline:
                    indexer.get_pod_scores(
                        repeat_prompt, MODEL_NAME, pods
                    )
                    count += 1
                elapsed = time.perf_counter() - t0
                return {
                    "scores_per_sec": (
                        round(count / elapsed, 1) if elapsed else 0.0
                    ),
                    "requests": count,
                }

            memo_parity = memo_pipe.get_pod_scores(
                prompts[-1], MODEL_NAME, pods
            ) == memo_single.get_pod_scores(prompts[-1], MODEL_NAME, pods)
            # Converge the memo first: request 1 stores a sentinel
            # vector (nothing piggybacked yet), request 2 recomputes
            # against the now-real vector, request 3+ hit.  Only THEN
            # pin zero lookup rounds.
            for _ in range(3):
                memo_pipe.get_pod_scores(prompts[-1], MODEL_NAME, pods)
            rounds_before = pipe_cluster.remote_index.rpc_stats()[
                "critical_path"
            ]["lookup_calls"]
            memo_pipe_cell = run_repeat(memo_pipe, cell_s / 2)
            hit_rounds = (
                pipe_cluster.remote_index.rpc_stats()["critical_path"][
                    "lookup_calls"
                ]
                - rounds_before
            )
            memo_single_cell = run_repeat(memo_single, cell_s / 2)

            pipe_stats = pipe_cluster.remote_index.rpc_stats()
            out["pipelined_ab"] = {
                "rtt_us": round(rtt_s * 1e6, 1),
                "parity": "ok" if ab_parity else "MISMATCH",
                "sequential_warm": seq_warm,
                "pipelined_warm": pipe_warm,
                "speedup_warm": speedup,
                "speedup_ok": (
                    speedup is not None and speedup >= 2.0
                ),
                "p99_rtt_ratio": round(
                    pipe_warm["p99_us"] / (rtt_s * 1e6), 2
                ),
                "sequential_cold": seq_cold,
                "pipelined_cold": pipe_cold,
                "memo_warm": {
                    "pipelined_sps": memo_pipe_cell["scores_per_sec"],
                    "single_sps": memo_single_cell["scores_per_sec"],
                    "ratio": (
                        round(
                            memo_pipe_cell["scores_per_sec"]
                            / memo_single_cell["scores_per_sec"],
                            3,
                        )
                        if memo_single_cell["scores_per_sec"]
                        else None
                    ),
                    "hit_lookup_rounds": hit_rounds,
                    "hit_rounds_ok": hit_rounds == 0,
                    "parity": memo_parity,
                },
                "profile": {
                    "hz": fan_hz,
                    "before_share": before_share,
                    "after_share": after_share,
                    "improved": (
                        before_share is not None
                        and after_share is not None
                        and after_share < before_share
                    ),
                },
                "rpc": pipe_stats["critical_path"],
                "fanout": pipe_stats["fanout"],
            }
        finally:
            seq_ix.shutdown()
            pipe_ix.shutdown()
            memo_pipe.shutdown()
            memo_single.shutdown()
            seq_cluster.close()
            pipe_cluster.close()

    # ---- cell 2: failover hit-rate dip --------------------------------
    n = len(requests)
    half = n // 2
    window = max(1, half // 2)
    qps = 0.7 * NUM_PODS / ideal_service
    arrivals = poisson_arrivals(qps, n, ARRIVAL_SEEDS[0])
    with tempfile.TemporaryDirectory() as root:
        cluster = LocalCluster(journal_root=root)
        fleet = FleetRouter(
            "precise",
            with_kv=False,
            seed=0,
            index_factory=lambda: cluster.remote_index,
        )
        try:
            from llm_d_kv_cache_manager_tpu.obs.slo import (
                SloEngine,
                SloSpec,
                envelope_violations,
            )

            pre_hits = 0
            for i in range(half):
                _, hit, _, _ = _fleet_step(
                    fleet, requests[i], hashes_list[i], arrivals[i],
                    t_miss, t_hit,
                )
                if i >= half - window:
                    pre_hits += hit
            # Let the event plane and the standby followers catch up,
            # then kill the replica owning the FIRST request's chain —
            # guaranteed to hold live slice state.
            fleet.event_pool.drain()
            while cluster.sync_followers():
                pass  # drain bounded polls until every journal is dry
            ring_before = cluster.membership.ring()
            victim = ring_before.owner(hashes_list[0][0])
            # Direct slice-coverage probe: the fleet hit rate can mask
            # index loss behind the router's affinity fallback, so also
            # ask the cluster for the victim's own resident keys after
            # the kill — a warm follower answers ~all of them.
            victim_dump, _ = cluster.replicas[victim].index.dump_entries()
            owned_sample = [
                key
                for key, _ in victim_dump
                if ring_before.owner(key) == victim
            ][:500]
            # Declarative degradation envelope (docs/observability.md):
            # the PR-10 "dip <= 0.15" one-off pin expressed as SLIs the
            # SLO engine evaluates — post-kill hit rate bounded by
            # (pre-kill rate - envelope), replica deaths and failovers
            # bounded by the single planned kill.  The chaos cell then
            # asserts the PUBLISHED envelope, not ad-hoc numbers.
            pre_rate = round(pre_hits / window, 3)
            slo_hits = {"good": 0.0, "total": 0.0}
            slo = SloEngine(window_fast_s=3600.0, window_slow_s=7200.0)
            slo.register(
                SloSpec(
                    "hit_rate",
                    kind="ratio",
                    objective=max(0.0, min(1.0, pre_rate)),
                    degraded_bound=max(
                        0.0, pre_rate - SCALEOUT_DIP_ENVELOPE
                    ),
                    description=(
                        "post-kill fleet hit rate vs the pre-kill "
                        "baseline"
                    ),
                ),
                lambda: (slo_hits["good"], slo_hits["total"]),
            )
            slo.register(
                SloSpec(
                    "replicas_dead",
                    kind="gauge",
                    objective=0.0,
                    degraded_bound=1.0,
                ),
                lambda: (
                    float(
                        len(cluster.membership.members())
                        - len(cluster.membership.alive())
                    ),
                    0.0,
                ),
            )
            slo.register(
                SloSpec(
                    "failovers",
                    kind="rate",
                    objective=0.0,
                    degraded_bound=1.0,
                ),
                lambda: (
                    float(cluster.membership.failover_count()),
                    0.0,
                ),
            )
            t_base = time.time()
            slo.sample(now=t_base)
            pre_state = slo.evaluate(now=t_base)["state"]
            cluster.kill(victim)
            coverage = None
            if owned_sample:
                served = cluster.remote_index.lookup(owned_sample)
                coverage = round(len(served) / len(owned_sample), 3)
            post_hits = 0
            for i in range(half, half + window):
                _, hit, _, _ = _fleet_step(
                    fleet, requests[i], hashes_list[i], arrivals[i],
                    t_miss, t_hit,
                )
                post_hits += hit
                slo_hits["good"] += hit
                slo_hits["total"] += 1
            slo.sample(now=t_base + 1.0)
            envelope = slo.evaluate(now=t_base + 1.0)
            violations = envelope_violations(envelope)
            post_rate = round(post_hits / window, 3)
            dip = round(max(0.0, pre_rate - post_rate), 3)
            out["failover"] = {
                "pre_kill_hit_rate": pre_rate,
                "post_kill_hit_rate": post_rate,
                "dip": dip,
                "within_envelope": dip <= SCALEOUT_DIP_ENVELOPE,
                "slo_envelope": {
                    "pre_state": pre_state,
                    "state": envelope["state"],
                    "hit_rate_value": envelope["slis"]["hit_rate"][
                        "value"
                    ],
                    "hit_rate_bound": envelope["slis"]["hit_rate"][
                        "degraded_bound"
                    ],
                    "violations": violations,
                    "ok": pre_state == "healthy" and not violations,
                },
                "slice_coverage_post_kill": coverage,
                "slice_keys_sampled": len(owned_sample),
                "coverage_ok": (
                    coverage is None
                    or coverage >= 1.0 - SCALEOUT_DIP_ENVELOPE
                ),
                "killed_replica": victim,
                "failovers": cluster.membership.failover_count(),
                "window_requests": window,
            }
        finally:
            fleet.shutdown()
            cluster.close()
    return out


def maybe_bench_replica_scaleout(
    requests, hashes_list, t_miss, t_hit, ideal_service
) -> dict:
    """bench_replica_scaleout under the degrade contract."""
    if _over_budget(reserve_s=50.0):
        return {"truncated": True}
    _progress(
        "replica_scaleout: clustered scores/sec + failover dip"
    )
    return bench_replica_scaleout(
        requests, hashes_list, t_miss, t_hit, ideal_service
    )


# ------------- cache_analytics: ledger-truth + audit-plane regime -------


def bench_cache_analytics(
    t_miss: float, t_hit: float, cell_seconds: Optional[float] = None
) -> dict:
    """detail.cache_analytics regime (docs/observability.md), three
    cells, all device-free (``t_miss``/``t_hit``: this run's measured
    service times, which place the virtual clock's arrival rate):

    1. **ledger truth** — the churn workload (pool barely holds one
       group's working set) through the REAL precise read+write path
       with the hit-attribution ledger attached; the ledger's reported
       hit rate must land within ±2% of the bench's engine-side ground
       truth (account() on the routed pod).  The ledger classifies hit
       = best pod covered the full 512-block shared prefix
       (hit_blocks), exactly the engine's own criterion; tokenization
       runs exact (no prefix-store truncation) so block counts align.
    2. **audit plane** — a synthetic 2-pod index built through the
       event pool, with a planted 5% divergence (one pod's inventory
       loses 5% of its blocks → the index's claims become phantoms);
       one auditor cycle must detect the pod, the ratio, and leave the
       clean pod clean.
    3. **overhead A/B** — the warm multi-turn scoring loop with
       analytics on (sample rate 1.0) vs off over identical data;
       the acceptance bar is on-overhead <= 3% (and bit-identical
       scores, asserted here as parity).
    """
    from llm_d_kv_cache_manager_tpu.analytics.auditor import (
        AuditorConfig,
        IndexAuditor,
    )
    from llm_d_kv_cache_manager_tpu.analytics.ledger import (
        CacheStatsLedger,
        LedgerConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import PodEntry
    from llm_d_kv_cache_manager_tpu.kvevents.resync import (
        CallableInventorySource,
        InventoryBlock,
        PodInventory,
    )

    cell_s = (
        ANALYTICS_CELL_S if cell_seconds is None else cell_seconds
    )
    result: dict = {}

    # -- cell 1: ledger hit rate vs engine-side ground truth (churn) --
    rng = random.Random(8080)
    requests = make_prompts(rng)
    hashes_list = [block_hash_chain(tokens) for _, _, tokens in requests]
    n_prefix_blocks = PREFIX_TOKENS // BLOCK_SIZE
    ledger = CacheStatsLedger(
        LedgerConfig(sample_rate=1.0, hit_blocks=n_prefix_blocks)
    )
    ideal = ideal_service_time(t_miss, t_hit, len(requests))
    qps = 0.7 * NUM_PODS / ideal
    arrivals = poisson_arrivals(qps, len(requests), ARRIVAL_SEEDS[0])
    _, ground_truth, _, _ = run_fleet_virtual(
        "precise",
        requests,
        hashes_list,
        arrivals,
        t_miss,
        t_hit,
        ARRIVAL_SEEDS[0],
        pool_blocks=CHURN_POOL_BLOCKS,
        cache_stats_ledger=ledger,
        exact_tokenize=True,
    )
    snapshot = ledger.snapshot()
    totals = snapshot["totals"]
    recorded = totals["recorded"]
    ledger_hit_rate = totals["hits"] / recorded if recorded else 0.0
    delta = abs(ledger_hit_rate - ground_truth)
    result["ledger_truth"] = {
        "workload": "churn",
        "requests": len(requests),
        "recorded": recorded,
        "ground_truth_hit_rate": round(ground_truth, 4),
        "ledger_hit_rate": round(ledger_hit_rate, 4),
        "delta": round(delta, 4),
        "within_2pct": delta <= 0.02,
        "partials": totals["partials"],
        "families_tracked": snapshot["families_tracked"],
        "window_1m": {
            key: snapshot["windows"]["1m"][key]
            for key in ("requests", "hits", "hit_rate")
        },
    }

    # -- cell 2: planted divergence through the audit plane --
    audit_indexer = Indexer(
        IndexerConfig(
            token_processor_config=TokenProcessorConfig(
                block_size=BLOCK_SIZE
            ),
            cache_stats=False,
        ),
        tokenizer=WordTokenizer(),
    )
    audit_pool = Pool(
        audit_indexer.kv_block_index,
        audit_indexer.token_processor,
        PoolConfig(concurrency=2),
    )
    audit_pool.start()
    try:
        blocks_per_pod = 400
        planted_fraction = 0.05
        truth: Dict[str, List[InventoryBlock]] = {}
        plant_rng = random.Random(5050)
        for pod_index in range(2):
            pod = f"audit-pod-{pod_index}"
            tokens = [
                plant_rng.randrange(1, CFG.vocab_size)
                for _ in range(blocks_per_pod * BLOCK_SIZE)
            ]
            hashes = block_hash_chain(tokens)
            batch = EventBatch(
                ts=time.time(),
                events=[
                    BlockStored(
                        block_hashes=list(hashes),
                        parent_block_hash=None,
                        token_ids=list(tokens),
                        block_size=BLOCK_SIZE,
                        medium="hbm",
                    )
                ],
            )
            audit_pool.add_task(
                Message(
                    topic=f"kv@{pod}@{MODEL_NAME}",
                    payload=batch.encode(),
                    pod_identifier=pod,
                    model_name=MODEL_NAME,
                )
            )
            truth[pod] = [
                InventoryBlock(
                    block_hashes=list(hashes),
                    token_ids=list(tokens),
                    block_size=BLOCK_SIZE,
                    medium="hbm",
                )
            ]
        audit_pool.drain()

        # Plant: audit-pod-0's engine "forgot" the last 5% of its
        # blocks — the index now carries that many phantom claims.
        planted = int(blocks_per_pod * planted_fraction)
        kept = blocks_per_pod - planted
        victim = truth["audit-pod-0"][0]
        victim.block_hashes = victim.block_hashes[:kept]
        victim.token_ids = victim.token_ids[: kept * BLOCK_SIZE]

        def fetch(pod: str) -> Optional[PodInventory]:
            if pod not in truth:
                return None
            return PodInventory(
                pod_identifier=pod,
                model_name=MODEL_NAME,
                blocks=truth[pod],
            )

        auditor = IndexAuditor(
            audit_indexer.kv_block_index,
            audit_indexer.token_processor,
            CallableInventorySource(fetch),
            AuditorConfig(interval_s=0.0),
        )
        cycle_start = time.perf_counter()
        reports = {r.pod: r for r in auditor.run_cycle()}
        cycle_s = time.perf_counter() - cycle_start
        divergent = reports.get("audit-pod-0")
        clean = reports.get("audit-pod-1")
        expected_ratio = planted / blocks_per_pod
        result["audit_plane"] = {
            "blocks_per_pod": blocks_per_pod,
            "planted_ratio": expected_ratio,
            "detected_ratio": (
                round(divergent.divergence_ratio, 4) if divergent else None
            ),
            "detected_phantom": divergent.phantom if divergent else None,
            "detected_outcome": divergent.outcome if divergent else None,
            "clean_pod_ratio": (
                round(clean.divergence_ratio, 4) if clean else None
            ),
            "cycle_s": round(cycle_s, 4),
            "detected_within_one_cycle": bool(
                divergent
                and divergent.outcome == "divergent"
                and abs(divergent.divergence_ratio - expected_ratio) < 0.01
                and clean
                and clean.outcome == "clean"
            ),
        }
    finally:
        audit_pool.shutdown()
        audit_indexer.shutdown()

    # -- cell 3: scoring-path overhead, analytics on vs off --
    overhead_rng = random.Random(909)
    convo = [
        overhead_rng.randrange(1, 16384) for _ in range(PREFIX_TOKENS)
    ]
    turns: List[str] = []
    for _ in range(8):
        convo.extend(
            overhead_rng.randrange(1, 16384) for _ in range(SUFFIX_TOKENS)
        )
        turns.append(" ".join(f"t{t}" for t in convo))

    def scoring_indexer(analytics_on: bool, memo: bool) -> Indexer:
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size=BLOCK_SIZE
                ),
                cache_stats=False,
                score_memo_size=None if memo else 0,
            ),
            tokenizer=WordTokenizer(),
            cache_stats_ledger=(
                CacheStatsLedger(LedgerConfig(sample_rate=1.0))
                if analytics_on
                else None
            ),
        )
        indexer.run()
        keys = indexer.token_processor.tokens_to_kv_block_keys(
            0, convo, MODEL_NAME
        )
        indexer.kv_block_index.add(
            keys, keys, [PodEntry("pod-0", "hbm")]
        )
        indexer.kv_block_index.add(
            keys, keys, [PodEntry("pod-1", "host")]
        )
        return indexer

    pods = [f"pod-{i}" for i in range(NUM_PODS)]

    def scoring_cell(indexer: Indexer) -> float:
        for prompt in turns:  # warm pass
            indexer.get_pod_scores(prompt, MODEL_NAME, pods)
        count = 0
        deadline = time.perf_counter() + cell_s
        start = time.perf_counter()
        while time.perf_counter() < deadline:
            indexer.get_pod_scores(
                turns[count % len(turns)], MODEL_NAME, pods
            )
            count += 1
        return count / (time.perf_counter() - start)

    def overhead_ab(memo: bool) -> dict:
        on = scoring_indexer(True, memo)
        off = scoring_indexer(False, memo)
        try:
            parity_ok = all(
                on.get_pod_scores(prompt, MODEL_NAME, pods)
                == off.get_pod_scores(prompt, MODEL_NAME, pods)
                for prompt in turns[:3]
            )
            # Interleaved rounds with alternating order and best-of
            # aggregation: shared-host scheduler noise dwarfs the
            # ~1% signal, and best-of keeps each side's least-
            # disturbed cell.
            sps_on, sps_off = 0.0, 0.0
            for round_index in range(4):
                if round_index % 2:
                    sps_off = max(sps_off, scoring_cell(off))
                    sps_on = max(sps_on, scoring_cell(on))
                else:
                    sps_on = max(sps_on, scoring_cell(on))
                    sps_off = max(sps_off, scoring_cell(off))
            pct = (
                round((1.0 - sps_on / sps_off) * 100.0, 2)
                if sps_off
                else None
            )
            return {
                "scores_per_sec_on": round(sps_on, 1),
                "scores_per_sec_off": round(sps_off, 1),
                "overhead_pct": pct,
                "parity": "ok" if parity_ok else "MISMATCH",
            }
        finally:
            on.shutdown()
            off.shutdown()

    # The acceptance A/B runs the scoring WALK (multi-turn warm, score
    # memo off): production conversations extend every turn, so the
    # walk is the path each new request pays — the memo serves only
    # exact repeats of an already-scored prompt against an unchanged
    # index.  That adversarial repeat path (microseconds total, where
    # the ledger's fixed ~6us cost is proportionally large) is reported
    # alongside, unbounded, as repeat_overhead.
    walk = overhead_ab(memo=False)
    repeat = overhead_ab(memo=True)
    walk_pct = walk["overhead_pct"]
    result["overhead"] = {
        "walk": walk,
        "repeat": repeat,
        "overhead_pct": walk_pct,
        "within_3pct": walk_pct is not None and walk_pct <= 3.0,
        "parity": (
            "ok"
            if walk["parity"] == "ok" and repeat["parity"] == "ok"
            else "MISMATCH"
        ),
        "cell_seconds": cell_s,
    }
    return result


def maybe_bench_cache_analytics(
    context: str, t_miss: float, t_hit: float
) -> dict:
    """bench_cache_analytics under the degrade contract."""
    if _over_budget(reserve_s=60.0):
        return {"truncated": True}
    _progress(f"{context}: cache_analytics regime")
    try:
        return bench_cache_analytics(t_miss, t_hit)
    except Exception as exc:  # noqa: BLE001 — optional layer
        detail = f"{type(exc).__name__}: {exc}"
        _progress(f"cache_analytics failed: {detail}")
        return {"error": detail[:300]}


# ---------------- tiered_churn: predictive tiering regime --------------

# Assumed offload-path constants (labeled calibrated, never measured):
# a transfer RTT floor for scaleout_warmup's pricing advisor, and a
# host<->storage streaming bandwidth for the synthetic load
# observations fed to both advisors' estimators.
CAL_READBACK_S = _env_float("KVTPU_BENCH_CAL_READBACK_S", 0.065)
CAL_HOST_BW_BYTES_S = _env_float("KVTPU_BENCH_HOST_BW_GBPS", 5.0) * 1e9


def _tiered_churn_run(pod_factory, seed: int, t_miss: float, t_hit: float):
    """One churn-workload run (the r05 regime's exact geometry: same
    prompts, same pool, same QPS) under the given pod factory; returns
    (hit_rate, per-pod eviction logs)."""
    rng = random.Random(9090)
    requests = make_prompts(rng)
    hashes_list = [block_hash_chain(tokens) for _, _, tokens in requests]
    ideal = ideal_service_time(t_miss, t_hit, len(requests))
    qps = 0.7 * NUM_PODS / ideal
    arrivals = poisson_arrivals(qps, len(requests), seed)
    logs: Dict[str, List[int]] = {}

    def factory(name):
        pod = pod_factory(name)
        pod.evict_log = logs.setdefault(name, [])
        return pod

    _, hit_rate, _, _ = run_fleet_virtual(
        "precise",
        requests,
        hashes_list,
        arrivals,
        t_miss,
        t_hit,
        seed,
        pool_blocks=CHURN_POOL_BLOCKS,
        pod_factory=factory,
    )
    return hit_rate, logs


def bench_tiered_churn(
    t_miss: float, t_hit: float, readback_rtt: float
) -> dict:
    """detail.tiered_churn regime (docs/tiering.md), device-free
    (``t_miss``/``t_hit``: this run's measured service times):

    1. **eviction-policy A/B** — the r05 churn workload through the
       real precise read+write path twice in one run: the LRU/ring
       baseline (today's eviction order) vs TieredSimPod driving the
       real PolicyFeed + ledger (reuse-aware protection/admission).
       The predictive arm must beat the baseline hit rate (r05
       stalled at 0.375 — the headroom ROADMAP item 4 names).
    2. **policy-off parity** — TieredSimPod with tiering=None must
       reproduce the baseline's hit rate AND per-pod eviction order
       bit-identically (the escape hatch is the oracle).
    3. **compute-or-load** — TTFT for a fully-offloaded shared prefix
       under pure-load vs pure-recompute vs hybrid overlap, priced by
       the real ComputeOrLoadAdvisor fed with this run's measured
       readback floor; hybrid must be <= the best pure arm within
       noise.
    """
    from llm_d_kv_cache_manager_tpu.tiering import (
        AdvisorConfig,
        ComputeOrLoadAdvisor,
    )

    result: dict = {}
    seed = ARRIVAL_SEEDS[0]

    # -- cells 1+2: eviction-policy A/B + parity, one run each arm --
    baseline_hit, baseline_logs = _tiered_churn_run(
        lambda name: SimPod(name, with_kv=False,
                            pool_blocks=CHURN_POOL_BLOCKS),
        seed, t_miss, t_hit,
    )
    parity_hit, parity_logs = _tiered_churn_run(
        lambda name: TieredSimPod(name, with_kv=False,
                                  pool_blocks=CHURN_POOL_BLOCKS,
                                  tiering=None),
        seed, t_miss, t_hit,
    )
    policy = TieredFleetPolicy()
    try:
        predictive_hit, _ = _tiered_churn_run(
            lambda name: TieredSimPod(name, with_kv=False,
                                      pool_blocks=CHURN_POOL_BLOCKS,
                                      tiering=policy),
            seed, t_miss, t_hit,
        )
    finally:
        policy.close()
    parity_ok = (
        parity_hit == baseline_hit and parity_logs == baseline_logs
    )
    result["eviction_ab"] = {
        "workload": "churn",
        "pool_blocks": CHURN_POOL_BLOCKS,
        "hit_rate_lru": round(baseline_hit, 4),
        "hit_rate_predictive": round(predictive_hit, 4),
        "beats_lru": predictive_hit > baseline_hit,
        "policy_off_parity": parity_ok,
        "evictions_lru": sum(len(v) for v in baseline_logs.values()),
    }

    # -- cell 3: compute-or-load TTFT (single offloaded-prefix point) --
    n_prefix_blocks = PREFIX_TOKENS // BLOCK_SIZE
    # Per-block KV bytes of the bench model (bf16 = 2 bytes).
    bytes_per_block = (
        2 * CFG.n_layers * CFG.block_size * CFG.n_kv_heads
        * CFG.head_dim * 2
    )
    prefill_rate = TOTAL_TOKENS / t_miss
    advisor = ComputeOrLoadAdvisor(
        AdvisorConfig(
            bytes_per_block=bytes_per_block,
            block_tokens=BLOCK_SIZE,
            prefill_tokens_per_s=prefill_rate,
            rtt_floor_s=readback_rtt,
        )
    )
    # Synthetic load observations at the calibrated bandwidth — the
    # shape the offload worker's rtt_observer would feed live.
    for nbytes in (1 << 20, 8 << 20, 64 << 20):
        advisor.observe_load(
            nbytes, readback_rtt + nbytes / CAL_HOST_BW_BYTES_S
        )
    advice = advisor.advise(n_prefix_blocks)
    suffix_s = SUFFIX_TOKENS / prefill_rate
    ttft_load = advice.load_s + suffix_s
    ttft_recompute = (PREFIX_TOKENS + SUFFIX_TOKENS) / prefill_rate
    hybrid_core = (
        advice.hybrid_s
        if advice.hybrid_s is not None
        else min(advice.load_s, advice.recompute_s)
    )
    ttft_hybrid = hybrid_core + suffix_s
    best_pure = min(ttft_load, ttft_recompute)
    result["compute_or_load"] = {
        "prefix_blocks": n_prefix_blocks,
        "prefix_bytes": n_prefix_blocks * bytes_per_block,
        "rtt_floor_s": round(readback_rtt, 4),
        "host_bw_bytes_s": CAL_HOST_BW_BYTES_S,
        "prefill_tokens_per_s": round(prefill_rate, 1),
        "ttft_load_s": round(ttft_load, 4),
        "ttft_recompute_s": round(ttft_recompute, 4),
        "ttft_hybrid_s": round(ttft_hybrid, 4),
        "hybrid_le_min_pure": ttft_hybrid <= best_pure * 1.001 + 1e-9,
        "advice": advice.to_dict(),
    }
    return result


def maybe_bench_tiered_churn(
    context: str, t_miss: float, t_hit: float, readback_rtt: float
) -> dict:
    """bench_tiered_churn under the degrade contract."""
    if _over_budget(reserve_s=60.0):
        return {"truncated": True}
    _progress(f"{context}: tiered_churn regime (eviction A/B)")
    try:
        return bench_tiered_churn(t_miss, t_hit, readback_rtt)
    except Exception as exc:  # noqa: BLE001 — optional layer
        detail = f"{type(exc).__name__}: {exc}"
        _progress(f"tiered_churn failed: {detail}")
        return {"error": detail[:300]}


# ---------------- scaleout_warmup: KV-transfer planning regime ---------

# Arrival rate as a fraction of the ORIGINAL fleet's ideal capacity:
# high enough that the pre-join pods queue (scale-out is worth doing),
# low enough that the post-join fleet can drain.
SCALEOUT_QPS_FRACTION = 0.95
# LOAD_BLEND coefficient for the transfer-aware arm: queue depth folds
# into routing so the freshly-warmed pod actually receives traffic.
SCALEOUT_LOAD_BLEND = 0.2
# Holder queue depth at which the planner starts pricing transfers:
# genuine overload under the saturating arrival rate, not the ambient
# 2-3 deep queue every pod carries at 0.95 utilization.
SCALEOUT_LOAD_THRESHOLD = 6.0
# Pod bring-up (weights load, server start) before a joining pod is
# routable, every arm alike.  Warm-up transfers stream during this
# window — "instant-warm" means the envelope hides inside init, so
# the pod's first routable request is already a prefix hit.
SCALEOUT_INIT_S = 1.0


def _scaleout_engine_advisor(t_miss: float):
    """Transfer-pricing advisor fed the calibrated offload-path
    costs (same constants as tiered_churn's compute-or-load cell)."""
    from llm_d_kv_cache_manager_tpu.tiering import (
        AdvisorConfig,
        ComputeOrLoadAdvisor,
    )

    bytes_per_block = (
        2 * CFG.n_layers * CFG.block_size * CFG.n_kv_heads
        * CFG.head_dim * 2
    )
    advisor = ComputeOrLoadAdvisor(
        AdvisorConfig(
            bytes_per_block=bytes_per_block,
            block_tokens=BLOCK_SIZE,
            prefill_tokens_per_s=TOTAL_TOKENS / t_miss,
            rtt_floor_s=CAL_READBACK_S,
        )
    )
    for nbytes in (1 << 20, 8 << 20, 64 << 20):
        advisor.observe_load(
            nbytes, CAL_READBACK_S + nbytes / CAL_HOST_BW_BYTES_S
        )
        advisor.observe_store(nbytes, nbytes / CAL_HOST_BW_BYTES_S)
    return advisor


def _scaleout_arm(
    arm: str,
    requests,
    hashes_list,
    arrivals,
    t_miss: float,
    t_hit: float,
    join_at: int,
    pool_blocks: int,
) -> dict:
    """One scale-out run: NUM_PODS pods serve the first half of the
    stream, then a cold pod joins at ``join_at``.

    Arms: ``round_robin`` (blind), ``route_to_holder`` (precise index
    routing, today's behavior — the new pod scores zero on every hot
    prefix and never absorbs load), ``transfer_aware`` (precise +
    TransferEngine: instant-warm the new pod with hot families via
    real KVEvents, blend queue depth into routing, and execute priced
    transfer directives mid-stream — a transferred request pays the
    fetch before decoding, a real cost the virtual clock charges).
    """
    from llm_d_kv_cache_manager_tpu.analytics.ledger import (
        CacheStatsLedger,
        LedgerConfig,
    )
    from llm_d_kv_cache_manager_tpu.transfer import (
        TransferConfig,
        TransferEngine,
    )
    from llm_d_kv_cache_manager_tpu.transfer.planner import (
        DONE as PLAN_DONE,
    )

    n_prefix_blocks = PREFIX_TOKENS // BLOCK_SIZE
    pods = [
        SimPod(f"pod-{i}", with_kv=False, pool_blocks=pool_blocks)
        for i in range(NUM_PODS)
    ]
    pod_by_name = {p.name: p for p in pods}
    pod_free_at = {p.name: 0.0 for p in pods}
    rr = 0
    new_pod_name = f"pod-{NUM_PODS}"
    indexer = event_pool = engine = ledger = None
    if arm != "round_robin":
        if arm == "transfer_aware":
            ledger = CacheStatsLedger(LedgerConfig(sample_rate=1.0))
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size=BLOCK_SIZE
                ),
                kvblock_index_config=IndexConfig(),
                cache_stats=ledger is not None,
                load_blend=(
                    SCALEOUT_LOAD_BLEND
                    if arm == "transfer_aware"
                    else 0.0
                ),
            ),
            tokenizer=WordTokenizer(),
            cache_stats_ledger=ledger,
        )
        indexer.run()
        event_pool = Pool(
            indexer.kv_block_index,
            indexer.token_processor,
            PoolConfig(concurrency=2),
        )
        event_pool.start()
    if arm == "transfer_aware":
        # The new pod's pool holds pool_blocks // prefix-blocks
        # families; warm one fewer so suffix churn has headroom.
        warm_families = max(1, pool_blocks // n_prefix_blocks - 1)
        engine = TransferEngine(
            advisor=_scaleout_engine_advisor(t_miss),
            ledger=ledger,
            config=TransferConfig(
                load_threshold=SCALEOUT_LOAD_THRESHOLD,
                min_blocks=2,
                warmup_families=warm_families,
                warmup_moves=warm_families,
            ),
        )
        indexer.set_transfer_engine(engine)
        engine.attach_executor(
            indexer.kv_block_index, event_pool, MODEL_NAME,
            start_warmup=False,
        )

    # request-key -> engine-hash map per group prefix, so executed
    # plans (which carry index keys) can be mirrored into the virtual
    # pods' engine caches — the sim's stand-in for moving bytes.
    rk_to_engine: Dict[int, int] = {}
    seen_groups: set = set()
    records: List[Tuple[int, float, float, str, bool]] = []
    warmup_moves = 0
    warmup_envelope_s = 0.0
    new_pod_ready: Optional[float] = None

    def engine_copy(dst: SimPod, engine_hashes, src) -> int:
        """Engine-side byte movement: replicate src's cached prefix
        into dst (index-side events were already published by the
        executor); dst's alloc-evictions publish like live traffic."""
        src_ids = (
            src.cached_prefix_blocks(engine_hashes)
            if src is not None
            else []
        )
        n = len(src_ids)
        if n == 0:
            return 0
        ids, evicted = dst.alloc(n)
        for h, bid in zip(engine_hashes[:n], ids):
            dst.cached[h] = bid
            dst._block_owner[bid] = h
        if evicted and event_pool is not None:
            batch = EventBatch(
                ts=time.time(),
                events=[
                    BlockRemoved(
                        block_hashes=list(evicted), medium="hbm"
                    )
                ],
            )
            event_pool.add_task(
                Message(
                    topic=f"kv@{dst.name}@{MODEL_NAME}",
                    payload=batch.encode(),
                    pod_identifier=dst.name,
                    model_name=MODEL_NAME,
                )
            )
        return n

    try:
        for i, (request, hashes, arrival) in enumerate(
            zip(requests, hashes_list, arrivals)
        ):
            group, text, tokens = request
            if i == join_at:
                # -- scale-out event: a cold pod joins ---------------
                new_pod = SimPod(
                    new_pod_name, with_kv=False, pool_blocks=pool_blocks
                )
                pods.append(new_pod)
                pod_by_name[new_pod_name] = new_pod
                pod_free_at[new_pod_name] = arrival
                if engine is not None:
                    engine.register_cold_pod(new_pod_name)
                    plans = engine.warmup.queued_plans()
                    while engine.run_warmup_cycle():
                        pass
                    event_pool.drain()
                    for plan in plans:
                        if (
                            plan.state != PLAN_DONE
                            or plan.target_pod != new_pod_name
                        ):
                            continue
                        engine_hashes = [
                            rk_to_engine[k]
                            for k in plan.block_keys
                            if k in rk_to_engine
                        ]
                        copied = engine_copy(
                            new_pod,
                            engine_hashes,
                            pod_by_name.get(plan.source_pod),
                        )
                        if copied:
                            warmup_moves += 1
                            warmup_envelope_s += (
                                plan.est_transfer_s or 0.0
                            )
                    event_pool.drain()
                # Warm-up bytes stream during pod bring-up; the pod is
                # routable once BOTH finish.  The published SLO
                # envelope is the warm-up transient itself.
                new_pod_ready = arrival + max(
                    SCALEOUT_INIT_S, warmup_envelope_s
                )
                pod_free_at[new_pod_name] = new_pod_ready
            if indexer is not None and group not in seen_groups:
                seen_groups.add(group)
                prefix_keys = (
                    indexer.token_processor.tokens_to_kv_block_keys(
                        0, tokens[:PREFIX_TOKENS], MODEL_NAME
                    )
                )
                for rk, eh in zip(prefix_keys, hashes):
                    rk_to_engine[rk] = eh

            # -- route ----------------------------------------------
            routable = [
                p
                for p in pods
                if p.name != new_pod_name
                or (new_pod_ready is not None and arrival >= new_pod_ready)
            ]
            names = [p.name for p in routable]
            directive = None
            routing_s = 0.0
            if arm == "round_robin":
                pod = routable[rr % len(routable)]
                rr += 1
            else:
                t0 = time.perf_counter()
                if arm == "transfer_aware":
                    # Queue depth in request-equivalents from each
                    # pod's backlog — the warm-up envelope shows up
                    # here too, so the blend doesn't pile requests
                    # onto a pod still receiving its warm-up bytes.
                    loads = {
                        name: max(0.0, pod_free_at[name] - arrival)
                        / t_hit
                        for name in names
                    }
                    scores, directive = (
                        indexer.get_pod_scores_planned(
                            text, MODEL_NAME, names, pod_loads=loads
                        )
                    )
                else:
                    scores = indexer.get_pod_scores(
                        text, MODEL_NAME, names
                    )
                routing_s = time.perf_counter() - t0
                if scores and max(scores.values()) > 0:
                    pod = pod_by_name[
                        max(scores.items(), key=lambda kv: kv[1])[0]
                    ]
                else:
                    pod = routable[rr % len(routable)]
                    rr += 1

            # -- execute a priced transfer directive ----------------
            fetch_s = 0.0
            if (
                directive
                and directive.get("planned")
                and directive["target_pod"] in pod_by_name
            ):
                plan = engine.planner.get(directive["plan_id"])
                if plan is not None and engine.executor.execute(plan):
                    event_pool.drain()
                    dst = pod_by_name[directive["target_pod"]]
                    copied = engine_copy(
                        dst,
                        list(hashes[: directive["blocks"]]),
                        pod_by_name.get(directive["source_pod"]),
                    )
                    if copied:
                        event_pool.drain()
                        pod = dst
                        # The target fetches before decoding.
                        fetch_s = directive.get("est_transfer_s") or 0.0

            # -- serve on the virtual clock -------------------------
            hit, first_new, block_ids, evicted = FleetRouter.account(
                pod, hashes
            )
            service = (t_hit if hit else t_miss) + fetch_s
            queue_start = max(arrival, pod_free_at[pod.name])
            done = queue_start + service
            pod_free_at[pod.name] = done
            for h, bid in zip(
                hashes[first_new:], block_ids[first_new:]
            ):
                pod.cached[h] = bid
                pod._block_owner[bid] = h
            if event_pool is not None:
                publish_events(
                    event_pool, pod, tokens, hashes, first_new, evicted
                )
                event_pool.drain()
            records.append(
                (
                    i,
                    arrival,
                    routing_s + (queue_start - arrival) + service,
                    pod.name,
                    hit,
                )
            )
    finally:
        if engine is not None:
            engine.close()
        if event_pool is not None:
            event_pool.shutdown()
        if indexer is not None:
            indexer.shutdown()

    pre = [r for r in records if r[0] < join_at]
    post = [r for r in records if r[0] >= join_at]
    new_pod_post = [r for r in post if r[3] == new_pod_name]
    veteran_post = [r for r in post if r[3] != new_pod_name]
    # "Within the published envelope": the cold pod's hit rate is
    # judged from the moment it becomes routable (init + warm-up
    # transient both behind it).
    settled = [
        r
        for r in new_pod_post
        if new_pod_ready is None or r[1] >= new_pod_ready
    ]
    out = {
        "p90_ttft_pre_join_s": (
            round(float(np.percentile([r[2] for r in pre], 90)), 4)
            if pre
            else None
        ),
        "p90_ttft_post_join_s": (
            round(float(np.percentile([r[2] for r in post], 90)), 4)
            if post
            else None
        ),
        "hit_rate_post_join": (
            round(sum(r[4] for r in post) / len(post), 4)
            if post
            else None
        ),
        "fleet_warm_hit_rate": (
            round(
                sum(r[4] for r in veteran_post) / len(veteran_post), 4
            )
            if veteran_post
            else None
        ),
        "new_pod_requests": len(new_pod_post),
        "new_pod_hit_rate": (
            round(sum(r[4] for r in settled) / len(settled), 4)
            if settled
            else None
        ),
    }
    if arm == "transfer_aware":
        out["warmup"] = {
            "moves": warmup_moves,
            "envelope_s": round(warmup_envelope_s, 4),
            "planner_outcomes": engine.planner.stats()["outcomes"],
            "executor": engine.executor.stats(),
        }
    return out


def _scaleout_parity_cell(requests, hashes_list) -> dict:
    """Planner-off parity: an indexer with the transfer plane attached
    but unused on the plain scoring path (blend off, no pod_loads, no
    planned variant) must return scores bit-identical to a pristine
    indexer fed the same events."""
    from llm_d_kv_cache_manager_tpu.tiering import ComputeOrLoadAdvisor
    from llm_d_kv_cache_manager_tpu.transfer import (
        TransferConfig,
        TransferEngine,
    )

    sample = list(zip(requests, hashes_list))[: min(6, len(requests))]
    names = [f"pod-{i}" for i in range(NUM_PODS)]

    def build(with_transfer: bool):
        indexer = Indexer(
            IndexerConfig(
                token_processor_config=TokenProcessorConfig(
                    block_size=BLOCK_SIZE
                ),
                kvblock_index_config=IndexConfig(),
                load_blend=0.0,
            ),
            tokenizer=WordTokenizer(),
        )
        indexer.run()
        pool = Pool(
            indexer.kv_block_index,
            indexer.token_processor,
            PoolConfig(concurrency=2),
        )
        pool.start()
        engine = None
        if with_transfer:
            engine = TransferEngine(
                advisor=ComputeOrLoadAdvisor(),
                config=TransferConfig(),
            )
            indexer.set_transfer_engine(engine)
            engine.attach_executor(
                indexer.kv_block_index, pool, MODEL_NAME,
                start_warmup=False,
            )
        return indexer, pool, engine

    plain = build(False)
    planned = build(True)
    try:
        for j, ((_group, _text, tokens), hashes) in enumerate(sample):
            batch = EventBatch(
                ts=1.0,
                events=[
                    BlockStored(
                        block_hashes=list(hashes),
                        parent_block_hash=None,
                        token_ids=list(
                            tokens[: len(hashes) * BLOCK_SIZE]
                        ),
                        block_size=BLOCK_SIZE,
                        medium="hbm",
                    )
                ],
            )
            for _indexer, pool, _engine in (plain, planned):
                pool.add_task(
                    Message(
                        topic=f"kv@pod-{j % NUM_PODS}@{MODEL_NAME}",
                        payload=batch.encode(),
                        pod_identifier=f"pod-{j % NUM_PODS}",
                        model_name=MODEL_NAME,
                    )
                )
                pool.drain()
        parity_ok = all(
            plain[0].get_pod_scores(text, MODEL_NAME, names)
            == planned[0].get_pod_scores(text, MODEL_NAME, names)
            for (_g, text, _t), _h in sample
        )
    finally:
        for indexer, pool, engine in (plain, planned):
            if engine is not None:
                engine.close()
            pool.shutdown()
            indexer.shutdown()
    return {
        "parity": "ok" if parity_ok else "MISMATCH",
        "prompts": len(sample),
    }


def bench_scaleout_warmup(t_miss: float, t_hit: float) -> dict:
    """detail.scaleout_warmup regime (docs/transfer.md), device-free
    (``t_miss``/``t_hit``: this run's measured service times):

    1. **scale-out A/B/C** — the grouped-prefix stream at 0.95 of the
       original fleet's ideal capacity; a cold pod joins mid-stream.
       transfer-aware (instant-warm + load-blended routing + priced
       directives) vs route-to-holder (today's precise routing) vs
       round-robin, on post-join p90 TTFT and the cold pod's hit rate
       relative to the warm fleet, with the warm-up transient
       published as an SLO envelope.
    2. **planner-off parity** — the transfer plane attached but unused
       must leave plain scores bit-identical (the oracle).
    """
    rng = random.Random(2121)
    base = make_prompts(rng)
    base_hashes = [block_hash_chain(tokens) for _, _, tokens in base]
    # 0.95 of the original fleet's HIT-dominated capacity: the best
    # any routing can do with warm caches is t_hit per request, so the
    # veterans run saturated and the only path to queue relief is
    # making the new pod useful.
    qps = SCALEOUT_QPS_FRACTION * NUM_PODS / t_hit
    # Replay the grouped stream until the virtual span comfortably
    # exceeds the rho=0.95 queueing time-constant (~t_hit/(1-rho)):
    # shorter runs measure the warm-up transient, not the relief.
    span_s = 4.0 * t_hit / (1.0 - SCALEOUT_QPS_FRACTION)
    reps = max(3, -(-int(span_s * qps) // len(base)))
    requests = base * reps
    hashes_list = base_hashes * reps
    n_prefix_blocks = PREFIX_TOKENS // BLOCK_SIZE
    # Per-pod capacity that BINDS (~half the family set + suffix
    # headroom): with free capacity everywhere, route-to-holder never
    # pays for ignoring the new pod and the regime measures nothing.
    pool_blocks = min(
        POOL_BLOCKS,
        n_prefix_blocks * max(2, NUM_GROUPS // 2)
        + n_prefix_blocks // 2,
    )
    join_at = len(requests) // 3
    # Scale-out transients are noisy at rho ~= 1: median p90 across
    # arrival seeds, same discipline as the headline.
    per_seed = {}
    for seed in ARRIVAL_SEEDS:
        arrivals = poisson_arrivals(qps, len(requests), seed)
        per_seed[seed] = {
            arm: _scaleout_arm(
                arm, requests, hashes_list, arrivals, t_miss, t_hit,
                join_at, pool_blocks,
            )
            for arm in (
                "round_robin", "route_to_holder", "transfer_aware"
            )
        }

    def _median(values):
        vals = sorted(v for v in values if v is not None)
        return vals[len(vals) // 2] if vals else None

    arms = {}
    for arm in ("round_robin", "route_to_holder", "transfer_aware"):
        runs = [per_seed[seed][arm] for seed in ARRIVAL_SEEDS]
        arms[arm] = {
            key: _median([r.get(key) for r in runs])
            for key in (
                "p90_ttft_pre_join_s",
                "p90_ttft_post_join_s",
                "hit_rate_post_join",
                "fleet_warm_hit_rate",
                "new_pod_hit_rate",
            )
        }
        arms[arm]["per_seed"] = {
            str(seed): per_seed[seed][arm] for seed in ARRIVAL_SEEDS
        }
        if arm == "transfer_aware":
            arms[arm]["warmup_envelope_s"] = _median(
                [(r.get("warmup") or {}).get("envelope_s") for r in runs]
            )
    ta = arms["transfer_aware"]
    p90_ta = ta.get("p90_ttft_post_join_s")
    p90_rth = arms["route_to_holder"].get("p90_ttft_post_join_s")
    p90_rr = arms["round_robin"].get("p90_ttft_post_join_s")
    cold_ratio = None
    if ta.get("new_pod_hit_rate") is not None and ta.get(
        "fleet_warm_hit_rate"
    ):
        cold_ratio = round(
            ta["new_pod_hit_rate"] / ta["fleet_warm_hit_rate"], 4
        )
    return {
        "workload": {
            "requests": len(requests),
            "reps": reps,
            "join_at": join_at,
            "qps_fraction": SCALEOUT_QPS_FRACTION,
            "pool_blocks": pool_blocks,
            "load_blend": SCALEOUT_LOAD_BLEND,
            "load_threshold": SCALEOUT_LOAD_THRESHOLD,
        },
        "arms": arms,
        "ttft_p90_beats_route_to_holder": (
            p90_ta is not None
            and p90_rth is not None
            and p90_ta < p90_rth
        ),
        "ttft_p90_beats_round_robin": (
            p90_ta is not None
            and p90_rr is not None
            and p90_ta < p90_rr
        ),
        "cold_pod_hit_ratio": cold_ratio,
        "cold_pod_warm_within_envelope": (
            cold_ratio is not None and cold_ratio >= 0.8
        ),
        "parity": _scaleout_parity_cell(requests, hashes_list),
    }


def maybe_bench_scaleout_warmup(
    context: str, t_miss: float, t_hit: float
) -> dict:
    """bench_scaleout_warmup under the degrade contract."""
    if _over_budget(reserve_s=60.0):
        return {"truncated": True}
    _progress(f"{context}: scaleout_warmup regime (transfer A/B/C)")
    try:
        return bench_scaleout_warmup(t_miss, t_hit)
    except Exception as exc:  # noqa: BLE001 — optional layer
        detail = f"{type(exc).__name__}: {exc}"
        _progress(f"scaleout_warmup failed: {detail}")
        return {"error": detail[:300]}


# ---------------- host_offload: staging-engine data-plane regime -------

# A compact but real KV geometry: 64 KiB per block across layers, so a
# 32-block transfer moves 2 MiB through the actual gather -> staging ->
# file path without dominating the CPU smoke budget.
HO_POOL_BLOCKS = 32
HO_BLOCKS_PER_FILE = 4
HO_LANES_SWEEP = (1, 2, 4)


def _ho_pool_config() -> KVCachePoolConfig:
    return KVCachePoolConfig(
        num_layers=4,
        num_blocks=HO_POOL_BLOCKS,
        block_size=BLOCK_SIZE,
        num_kv_heads=4,
        head_dim=64,
        dtype="bfloat16",
    )


def _ho_fill(pool: KVCachePool, block_ids, seed: int):
    rng = np.random.default_rng(seed)
    c = pool.config
    for block_id in block_ids:
        pool.write_block(
            block_id,
            rng.standard_normal(
                (c.num_layers, 2, c.block_size, c.num_kv_heads, c.head_dim)
            ).astype(host_dtype(c.dtype)),
        )


def _ho_roundtrip(
    device, root: str, lanes: int, rank: int, seed: int
) -> dict:
    """One chip's store + load round trip through the offload
    connector (staged when lanes > 0, the one-shot oracle at 0);
    returns wall times, bytes, and a parity verdict."""
    pool = KVCachePool(
        _ho_pool_config(),
        sharding=jax.sharding.SingleDeviceSharding(device),
    )
    spec = TPUOffloadSpec(
        shared_storage_path=root,
        model_name="bench/offload",
        device_block_size=BLOCK_SIZE,
        offloaded_block_size=BLOCK_SIZE * HO_BLOCKS_PER_FILE,
        threads_per_chip=4,
        staging_lanes=lanes,
        rank=rank,  # each chip writes its own shard tree
    )
    connector = TPUOffloadConnector(spec, pool)
    try:
        half = HO_POOL_BLOCKS // 2
        block_ids = list(range(half))
        _ho_fill(pool, block_ids, seed)
        file_hashes = [
            0x1000 + seed * 0x100 + i
            for i in range(half // HO_BLOCKS_PER_FILE)
        ]
        groups = group_blocks_per_file(
            file_hashes, block_ids, HO_BLOCKS_PER_FILE
        )
        nbytes = half * pool.block_nbytes

        t0 = time.perf_counter()
        connector.store_handler.transfer_async(1, groups)
        store_ok = (
            connector.store_handler.wait(1) == OffloadJobStatus.SUCCEEDED
        )
        store_s = time.perf_counter() - t0

        target_ids = list(range(half, 2 * half))
        t0 = time.perf_counter()
        connector.load_handler.transfer_async(
            2,
            group_blocks_per_file(
                file_hashes, target_ids, HO_BLOCKS_PER_FILE
            ),
        )
        load_ok = (
            connector.load_handler.wait(2) == OffloadJobStatus.SUCCEEDED
        )
        load_s = time.perf_counter() - t0
        parity = store_ok and load_ok and bool(
            np.array_equal(
                pool.gather_to_host(block_ids),
                pool.gather_to_host(target_ids),
            )
        )
        return {
            "store_s": store_s,
            "load_s": load_s,
            "nbytes": nbytes,
            "parity": parity,
        }
    finally:
        connector.close()


def bench_host_offload(t_miss: float) -> dict:
    """detail.host_offload regime (docs/host-offload.md):

    1. **staging A/B** — the same store+load round trip through the
       one-shot oracle (lanes=0) and the staged pipeline (lanes=2),
       bytes verified both ways;
    2. **lanes sweep x chips** — every local device runs its own
       staged round trip concurrently (per-chip trees, rank-sharded),
       swept over lanes-per-chip: the MULTICHIP per-chip I/O scaling
       cell;
    3. **TTFT** — offload-hit (measured staged load) vs recompute vs
       advisor-hybrid, with the advisor's estimator fed by the REAL
       transfers this regime just ran, not simulated RTTs.
    """
    from llm_d_kv_cache_manager_tpu.tiering import (
        AdvisorConfig,
        ComputeOrLoadAdvisor,
    )

    result: dict = {}
    root = tempfile.mkdtemp(prefix="kvtpu-bench-offload-")
    devices = jax.local_devices()
    try:
        # -- cell 1: staged vs one-shot A/B on chip 0 --
        oneshot = _ho_roundtrip(
            devices[0], os.path.join(root, "oneshot"), 0, 0, seed=1
        )
        staged = _ho_roundtrip(
            devices[0], os.path.join(root, "staged"), 2, 0, seed=1
        )
        nbytes = staged["nbytes"]

        def _mbps(cell, key):
            seconds = max(cell[key], 1e-9)
            return round(cell["nbytes"] / seconds / 1e6, 1)

        result["staging_ab"] = {
            "payload_mb": round(nbytes / 1e6, 2),
            "oneshot_store_mbps": _mbps(oneshot, "store_s"),
            "staged_store_mbps": _mbps(staged, "store_s"),
            "oneshot_load_mbps": _mbps(oneshot, "load_s"),
            "staged_load_mbps": _mbps(staged, "load_s"),
            "parity": oneshot["parity"] and staged["parity"],
        }

        # -- cell 2: MULTICHIP lanes-per-chip sweep --
        # Untimed warmup round trip per chip first: each device's
        # first gather/scatter pays XLA compilation, which would
        # otherwise be billed entirely to the sweep's first lane
        # count.
        warm_threads = [
            threading.Thread(
                target=_ho_roundtrip,
                args=(d, os.path.join(root, "warm"), 1, i, 99),
            )
            for i, d in enumerate(devices)
        ]
        for thread in warm_threads:
            thread.start()
        for thread in warm_threads:
            thread.join()
        sweep = []
        for lanes in HO_LANES_SWEEP:
            lane_root = os.path.join(root, f"lanes_{lanes}")
            cells = [None] * len(devices)

            def run_chip(idx, device, lane_count=lanes, out=cells,
                         base=lane_root):
                out[idx] = _ho_roundtrip(
                    device, base, lane_count, idx, seed=2 + idx
                )

            threads = [
                threading.Thread(target=run_chip, args=(i, d))
                for i, d in enumerate(devices)
            ]
            wall0 = time.perf_counter()
            for thread in threads:
                thread.start()
            for thread in threads:
                thread.join()
            wall = time.perf_counter() - wall0
            total_bytes = sum(c["nbytes"] for c in cells) * 2  # both ways
            sweep.append(
                {
                    "lanes_per_chip": lanes,
                    "chips": len(devices),
                    "wall_s": round(wall, 4),
                    "aggregate_mbps": round(
                        total_bytes / max(wall, 1e-9) / 1e6, 1
                    ),
                    "parity": all(c["parity"] for c in cells),
                }
            )
        best = max(sweep, key=lambda c: c["aggregate_mbps"])
        result["multichip_lanes_sweep"] = {
            "cells": sweep,
            "best_lanes": best["lanes_per_chip"],
            "best_aggregate_mbps": best["aggregate_mbps"],
        }

        # -- cell 3: TTFT offload-hit vs recompute vs advisor-hybrid --
        ho_cfg = _ho_pool_config()
        pool_bytes_per_block = (
            ho_cfg.num_layers
            * 2
            * ho_cfg.block_size
            * ho_cfg.num_kv_heads
            * ho_cfg.head_dim
            * jnp.dtype(ho_cfg.dtype).itemsize
        )
        prefix_blocks = HO_POOL_BLOCKS // 2
        measured_load_s = staged["load_s"]
        prefill_rate = TOTAL_TOKENS / t_miss
        advisor = ComputeOrLoadAdvisor(
            AdvisorConfig(
                bytes_per_block=pool_bytes_per_block,
                block_tokens=BLOCK_SIZE,
                prefill_tokens_per_s=prefill_rate,
            )
        )
        # Feed the estimator with THIS regime's measured transfers.
        advisor.observe_load(nbytes, staged["load_s"])
        advisor.observe_load(oneshot["nbytes"], oneshot["load_s"])
        advisor.observe_store(nbytes, staged["store_s"])
        advice = advisor.advise(prefix_blocks)
        suffix_s = SUFFIX_TOKENS / prefill_rate
        ttft_hit = measured_load_s + suffix_s
        ttft_recompute = (
            prefix_blocks * BLOCK_SIZE + SUFFIX_TOKENS
        ) / prefill_rate
        hybrid_core = (
            advice.hybrid_s
            if advice.hybrid_s is not None
            else min(advice.load_s, advice.recompute_s)
        )
        ttft_hybrid = hybrid_core + suffix_s
        result["ttft"] = {
            "prefix_blocks": prefix_blocks,
            "prefix_bytes": prefix_blocks * pool_bytes_per_block,
            "rtt_source": "measured_staging_path",
            "prefill_tokens_per_s": round(prefill_rate, 1),
            "prefill_source": (
                "measured" if t_miss and t_miss > 0 else "calibrated"
            ),
            "ttft_offload_hit_s": round(ttft_hit, 4),
            "ttft_recompute_s": round(ttft_recompute, 4),
            "ttft_hybrid_s": round(ttft_hybrid, 4),
            "advice": advice.to_dict(),
            "advisor_rtt": advisor.stats()["rtt"],
        }
        # The compact headline block the driver sees (emit_result).
        result["headline"] = {
            "staged_store_mbps": result["staging_ab"]["staged_store_mbps"],
            "staged_load_mbps": result["staging_ab"]["staged_load_mbps"],
            "parity": result["staging_ab"]["parity"],
            "chips": len(devices),
            "best_lanes": best["lanes_per_chip"],
            "best_aggregate_mbps": best["aggregate_mbps"],
            "ttft_hit_s": result["ttft"]["ttft_offload_hit_s"],
            "ttft_recompute_s": result["ttft"]["ttft_recompute_s"],
            "ttft_hybrid_s": result["ttft"]["ttft_hybrid_s"],
            "advice": advice.action,
        }
        return result
    finally:
        shutil.rmtree(root, ignore_errors=True)


def maybe_bench_host_offload(context: str, t_miss: float) -> dict:
    """bench_host_offload under the degrade contract."""
    if _over_budget(reserve_s=90.0):
        return {"truncated": True}
    _progress(f"{context}: host_offload regime (staging data plane)")
    try:
        return bench_host_offload(t_miss)
    except Exception as exc:  # noqa: BLE001 — optional layer
        detail = f"{type(exc).__name__}: {exc}"
        _progress(f"host_offload failed: {detail}")
        return {"error": detail[:300]}


# ---------------- event_storm: fleet-scale event-plane regime ----------

_STORM_TINY = bool(os.environ.get("KVTPU_BENCH_TINY"))
STORM_PODS = int(
    os.environ.get(
        "KVTPU_BENCH_STORM_PODS", "64" if _STORM_TINY else "1000"
    )
)
STORM_PUBLISH_S = _env_float(
    "KVTPU_BENCH_STORM_S", 1.0 if _STORM_TINY else 3.0
)
STORM_BLOCK_SIZE = 16
# Offered load for the throughput cells, msgs/s across the whole
# fleet.  Must exceed the apply capacity of every cell so each one is
# measured at saturation (sustained capacity), not at whatever rate
# the load generator happened to reach.
STORM_RATE = _env_float("KVTPU_BENCH_STORM_RATE", 6000.0)


def _hist_stats(hist) -> tuple:
    """(sum, count) of an unlabeled prometheus histogram."""
    total = count = 0.0
    for metric in hist.collect():
        for sample in metric.samples:
            if sample.name.endswith("_sum"):
                total = sample.value
            elif sample.name.endswith("_count"):
                count = sample.value
    return total, count


def _pod_labeled_totals(counter, pods) -> dict:
    """pod -> value for a pod-labeled counter, 0.0 when never touched."""
    wanted = set(pods)
    out = {pod: 0.0 for pod in wanted}
    for metric in counter.collect():
        for sample in metric.samples:
            if sample.name.endswith("_total"):
                pod = sample.labels.get("pod")
                if pod in wanted:
                    out[pod] = sample.value
    return out


def _event_plane_threads() -> int:
    """Threads belonging to the event plane: pollers (consolidated),
    legacy per-pod subscriber threads (baseline), pool workers, and the
    resync worker."""
    prefixes = ("kvtpu-evplane-", "kvtpu-events-", "kvtpu-zmq-")
    return sum(
        1
        for t in threading.enumerate()
        if any(t.name.startswith(p) for p in prefixes)
    )


class _StormFleet:
    """N simulated publishers over inproc: raw PUB sockets + per-pod
    seq counters, sending pre-encoded payloads so the publish side
    never bottlenecks the measurement (the apply path is the subject).
    """

    def __init__(self, context, n_pods: int, run_id: str) -> None:
        import struct as _struct

        self._struct = _struct
        self.context = context
        self.pods = [f"storm-{run_id}-{i}" for i in range(n_pods)]
        self.endpoints = {
            pod: f"inproc://{pod}" for pod in self.pods
        }
        self.socks = {}
        for pod in self.pods:
            sock = context.socket(zmq.PUB)
            sock.setsockopt(zmq.LINGER, 0)
            sock.bind(self.endpoints[pod])
            self.socks[pod] = sock
        self.topics = {
            pod: f"kv@{pod}@{MODEL_NAME}".encode() for pod in self.pods
        }
        self.seq = {pod: 0 for pod in self.pods}
        # One shared payload: distinct engine keys per pod are not
        # needed for the throughput cells (shared blocks across pods
        # are realistic), and the apply-side token hashing dominates
        # regardless.
        tokens = list(range(2 * STORM_BLOCK_SIZE))
        self.payload = EventBatch(
            ts=0.0,
            events=[
                BlockStored(
                    block_hashes=[0xBEEF, 0xCAFE],
                    parent_block_hash=None,
                    token_ids=tokens,
                    block_size=STORM_BLOCK_SIZE,
                )
            ],
        ).encode()

    def publish_raw(self, pod: str, payload=None) -> None:
        self.seq[pod] += 1
        self.socks[pod].send_multipart(
            [
                self.topics[pod],
                self._struct.pack(">Q", self.seq[pod]),
                payload if payload is not None else self.payload,
            ]
        )

    def skip_seq(self, pod: str, count: int) -> None:
        self.seq[pod] += count

    def close(self) -> None:
        for sock in self.socks.values():
            sock.close()


def _storm_pool(index=None, start=True, **kw):
    index = index or InMemoryIndex(InMemoryIndexConfig(size=2_000_000))
    db = ChunkedTokenDatabase(
        TokenProcessorConfig(block_size=STORM_BLOCK_SIZE)
    )
    pool = Pool(index, db, PoolConfig(**kw))
    if start:
        pool.start()
    return pool, index, db


def _wait_join(fleet, pods, seen, deadline_s: float = 60.0) -> int:
    """Publish warmup rounds until every pod's subscription is live
    (PUB/SUB is lossy pre-join); returns pods joined."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline and len(seen) < len(pods):
        for pod in pods:
            if pod not in seen:
                fleet.publish_raw(pod)
        time.sleep(0.05)
    return len(seen)


# Standalone publisher process for the throughput cells.  Publishing
# must happen OUTSIDE the measured process: in production the
# publishers are remote vLLM pods, and an in-process load generator
# shares the GIL with the subscription layer under test — under the
# thread-per-pod baseline its 1000+ threads starve the generator until
# offered load collapses to whatever the baseline can absorb, and the
# A/B degenerates to comparing publish rates.  SNDHWM=0 so a saturated
# cell backs up into the publisher's buffers instead of dropping
# (drops would read as forced seq gaps and poison the gap metrics).
_STORM_PUBLISHER_SRC = r"""
import json, os, struct, sys, time
import zmq

spec = json.load(open(sys.argv[1]))
go_path = sys.argv[2]
endpoints = spec["endpoints"]
topics = {pod: t.encode() for pod, t in spec["topics"].items()}
payload = bytes.fromhex(spec["payload_hex"])
rate = float(spec["rate"])
deadline = time.monotonic() + float(spec["duration"])

ctx = zmq.Context()
ctx.set(zmq.MAX_SOCKETS, max(4096, 2 * len(endpoints)))
socks = {}
for pod, endpoint in endpoints.items():
    s = ctx.socket(zmq.PUB)
    s.setsockopt(zmq.LINGER, 0)
    s.setsockopt(zmq.SNDHWM, 0)
    s.bind(endpoint)
    socks[pod] = s
seq = {pod: 0 for pod in endpoints}
pods = list(endpoints)
pass_s = len(pods) / rate if rate else 0.0
# Warmup: one gentle pass per 0.5s until the parent (having seen a
# message from every pod) drops the go-file — joining at full offered
# load would saturate a slow cell before its fleet ever finished
# subscribing.  Then publish at the saturation rate.
while time.monotonic() < deadline:
    go = os.path.exists(go_path)
    t0 = time.monotonic()
    for pod in pods:
        seq[pod] += 1
        socks[pod].send_multipart(
            [topics[pod], struct.pack(">Q", seq[pod]), payload]
        )
    sleep_s = (pass_s if go else 0.5) - (time.monotonic() - t0)
    if sleep_s > 0:
        time.sleep(sleep_s)
for s in socks.values():
    s.close()
ctx.term()
"""


def _spawn_storm_publisher(
    workdir: str,
    endpoints: Dict[str, str],
    payload: bytes,
    rate: float,
    duration: float,
) -> Tuple[subprocess.Popen, str]:
    spec = {
        "endpoints": endpoints,
        "topics": {
            pod: f"kv@{pod}@{MODEL_NAME}" for pod in endpoints
        },
        "payload_hex": payload.hex(),
        "rate": rate,
        "duration": duration,
    }
    src_path = os.path.join(workdir, "publisher.py")
    spec_path = os.path.join(workdir, "spec.json")
    go_path = os.path.join(workdir, "go")
    with open(src_path, "w") as f:
        f.write(_STORM_PUBLISHER_SRC)
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    proc = subprocess.Popen(
        [sys.executable, src_path, spec_path, go_path],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    return proc, go_path


def _storm_throughput_cell(
    pods, endpoints, payload, attach, detach, publish_s: float
) -> dict:
    """One apply-throughput cell: attach subscriptions for `pods`,
    spawn the external publisher at STORM_RATE (above every cell's
    capacity), wait for join, and measure APPLY completions inside a
    `publish_s` window — the sustained ingest capacity with the
    subscription layer's own overhead (poller vs 1000 threads) on the
    same CPUs.  The backlog left in sockets dies with detach (LINGER
    0); the pool's own backlog is drained after the measurement, not
    counted: folding an unbounded drain tail into the rate made the
    number depend on backlog luck, not capacity.

    The cell also reports the decode-vs-apply stage split
    (µs/message inside the window, from ``Pool.stage_stats``) so the
    bottleneck is attributable straight from the BENCH artifact."""
    from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

    pool, _index, _db = _storm_pool(concurrency=4)
    seen = set()
    seen_lock = threading.Lock()

    def sink(message):
        with seen_lock:
            seen.add(message.pod_identifier)
        pool.add_task(message)

    def sink_batch(messages):
        with seen_lock:
            for message in messages:
                seen.add(message.pod_identifier)
        pool.add_tasks(messages)

    attach(sink, sink_batch)
    workdir = tempfile.mkdtemp(prefix="kvtpu-storm-pub-")
    proc = None
    detached = False
    try:
        proc, go_path = _spawn_storm_publisher(
            workdir,
            endpoints,
            payload,
            STORM_RATE,
            duration=150.0 + publish_s,
        )
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and len(seen) < len(pods):
            time.sleep(0.05)
        joined = len(seen)
        # Full join reached (at gentle warmup load): release the
        # saturation rate, give the backlog a moment to build, then
        # measure the steady state.
        with open(go_path, "w"):
            pass
        time.sleep(1.0)
        drained_before, _ = _hist_stats(METRICS.kvevents_batch_size)
        dropped_before = counter_total(METRICS.kvevents_dropped)
        stages_before = pool.stage_stats()
        threads = _event_plane_threads()

        t0 = time.perf_counter()
        time.sleep(publish_s)
        elapsed = time.perf_counter() - t0
        drained_after, _ = _hist_stats(METRICS.kvevents_batch_size)
        stages_after = pool.stage_stats()
        applied = drained_after - drained_before
        # Detach BEFORE draining the pool backlog: the subscription
        # layer's overhead belongs in the window, not in the cleanup.
        detach()
        detached = True
        proc.terminate()
        proc.wait(timeout=30)
        pool.drain()

        def stage_us(stage):
            msgs = (
                stages_after[f"{stage}_msgs"]
                - stages_before[f"{stage}_msgs"]
            )
            if not msgs:
                return None
            seconds = (
                stages_after[f"{stage}_s"] - stages_before[f"{stage}_s"]
            )
            return round(seconds / msgs * 1e6, 1)

        return {
            "pods": len(pods),
            "pods_joined": joined,
            "offered_msgs_per_sec": STORM_RATE,
            "applied_msgs_in_window": int(applied),
            "apply_msgs_per_sec": round(applied / elapsed, 1),
            "decode_us_per_msg": stage_us("decode"),
            "apply_us_per_msg": stage_us("apply"),
            "dropped": int(
                counter_total(METRICS.kvevents_dropped) - dropped_before
            ),
            "event_plane_threads": threads,
            "window_s": round(elapsed, 2),
        }
    finally:
        if not detached:
            detach()
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        pool.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)


# Offered load for the replica-local ingestion cells.  Must exceed the
# AGGREGATE capacity of the largest replica set so scaling is measured
# at saturation — with the fast lane a single ingestor can absorb the
# default storm rate, which would clamp every cell to the offered load
# and read as "no scaling".
STORM_RI_RATE = _env_float("KVTPU_BENCH_STORM_RI_RATE", 24000.0)

# One replica-local ingestor as its own PROCESS (own GIL, own poller
# pool + kvevents pool + index slice — the deployment shape of
# CLUSTER_LOCAL_INGEST).  Subscribes to its pod slice, reports joins,
# waits for the go-file, measures applies inside the window, writes a
# result JSON.  Spawned by _storm_replica_local_cell.
_STORM_INGESTOR_SRC = r"""
import json, os, sys, threading, time

spec = json.load(open(sys.argv[1]))
sys.path.insert(0, spec["repo_root"])
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import zmq

from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
    ChunkedTokenDatabase,
    TokenProcessorConfig,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
    InMemoryIndex,
)
from llm_d_kv_cache_manager_tpu.kvcache.kvblock.index import (
    InMemoryIndexConfig,
)
from llm_d_kv_cache_manager_tpu.kvevents.pool import Pool, PoolConfig
from llm_d_kv_cache_manager_tpu.kvevents.poller import (
    ChannelConfig,
    PollerPool,
    PollerPoolConfig,
)
from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

endpoints = spec["endpoints"]
context = zmq.Context()
context.set(zmq.MAX_SOCKETS, max(1024, 2 * len(endpoints) + 64))
index = InMemoryIndex(InMemoryIndexConfig(size=2_000_000))
db = ChunkedTokenDatabase(
    TokenProcessorConfig(block_size=int(spec["block_size"]))
)
pool = Pool(index, db, PoolConfig(concurrency=int(spec["concurrency"])))
pool.start()
seen = set()
lock = threading.Lock()


def sink(message):
    with lock:
        seen.add(message.pod_identifier)
    pool.add_task(message)


def sink_batch(messages):
    with lock:
        for message in messages:
            seen.add(message.pod_identifier)
    pool.add_tasks(messages)


ppool = PollerPool(
    context=context,
    config=PollerPoolConfig(pollers=1, poll_interval_ms=20),
)
for pod, endpoint in endpoints.items():
    ppool.attach(
        ChannelConfig(endpoint=endpoint, pod_identifier=pod),
        sink,
        sink_batch=sink_batch,
    )

deadline = time.monotonic() + float(spec["join_timeout_s"])
while time.monotonic() < deadline and len(seen) < len(endpoints):
    time.sleep(0.05)
with open(spec["joined_path"], "w") as f:
    f.write(str(len(seen)))
deadline = time.monotonic() + 150
while time.monotonic() < deadline and not os.path.exists(spec["go_path"]):
    time.sleep(0.02)
time.sleep(1.0)


def hist_sum(hist):
    total = 0.0
    for metric in hist.collect():
        for sample in metric.samples:
            if sample.name.endswith("_sum"):
                total = sample.value
    return total


before = hist_sum(METRICS.kvevents_batch_size)
t0 = time.perf_counter()
time.sleep(float(spec["window_s"]))
elapsed = time.perf_counter() - t0
applied = hist_sum(METRICS.kvevents_batch_size) - before
with open(spec["result_path"], "w") as f:
    json.dump(
        {
            "pods": len(endpoints),
            "pods_joined": len(seen),
            "applied_msgs_in_window": int(applied),
            "window_s": round(elapsed, 2),
            "apply_msgs_per_sec": round(applied / elapsed, 1),
        },
        f,
    )
ppool.shutdown()
pool.shutdown()
context.term()
"""


def _storm_replica_local_cell(
    fleet, storm_endpoints: Dict[str, str], window: float
) -> dict:
    """Replica-local ingestion scaling: the same 1000-pod fleet
    ingested by 1 vs 3 ingestor PROCESSES (each its own GIL), the pod
    set sliced by the production rendezvous slicer
    (``cluster.ingest.pod_owner``).  Offered load (STORM_RI_RATE) sits
    above the aggregate capacity of the largest set so every cell is
    measured at saturation; the aggregate apply rate across replicas
    is the headline, ``scaling_1_to_3`` the claim.  ``cpu_count``
    rides along because process-level scaling is physically bounded by
    the cores available to the bench box."""
    from llm_d_kv_cache_manager_tpu.cluster.ingest import pod_owner
    from llm_d_kv_cache_manager_tpu.cluster.ring import HashRing

    repo_root = os.path.dirname(os.path.abspath(__file__))
    result: dict = {
        "offered_msgs_per_sec": STORM_RI_RATE,
        "cpu_count": os.cpu_count(),
    }
    for n_replicas in (1, 3):
        _progress(
            f"event_storm: replica-local ingestion, {n_replicas} replicas"
        )
        ring = HashRing([f"ingest-{i}" for i in range(n_replicas)])
        slices: Dict[str, Dict[str, str]] = {r: {} for r in ring.members}
        for pod, endpoint in storm_endpoints.items():
            slices[pod_owner(ring, pod)][pod] = endpoint
        workdir = tempfile.mkdtemp(prefix="kvtpu-storm-ri-")
        ingestors = []
        publisher = None
        try:
            go_path = os.path.join(workdir, "go")
            src_path = os.path.join(workdir, "ingestor.py")
            with open(src_path, "w") as f:
                f.write(_STORM_INGESTOR_SRC)
            joined_paths = []
            result_paths = []
            for replica_id in ring.members:
                spec = {
                    "repo_root": repo_root,
                    "endpoints": slices[replica_id],
                    "block_size": STORM_BLOCK_SIZE,
                    "concurrency": 4,
                    "window_s": window,
                    "join_timeout_s": 120.0,
                    "go_path": go_path,
                    "joined_path": os.path.join(
                        workdir, f"{replica_id}.joined"
                    ),
                    "result_path": os.path.join(
                        workdir, f"{replica_id}.json"
                    ),
                }
                joined_paths.append(spec["joined_path"])
                result_paths.append(spec["result_path"])
                spec_path = os.path.join(workdir, f"{replica_id}.spec")
                with open(spec_path, "w") as f:
                    json.dump(spec, f)
                ingestors.append(
                    subprocess.Popen(
                        [sys.executable, src_path, spec_path],
                        stdout=subprocess.DEVNULL,
                        stderr=subprocess.DEVNULL,
                    )
                )
            publisher, _pub_go = _spawn_storm_publisher(
                workdir,
                storm_endpoints,
                fleet.payload,
                STORM_RI_RATE,
                duration=200.0 + window,
            )
            # _spawn_storm_publisher hardcodes its go file inside
            # workdir — the same go_path the ingestor specs point at,
            # so one touch releases saturation AND the measurement.
            deadline = time.monotonic() + 130.0
            while time.monotonic() < deadline and not all(
                os.path.exists(p) for p in joined_paths
            ):
                time.sleep(0.1)
            with open(go_path, "w"):
                pass
            deadline = time.monotonic() + 60.0 + window
            for proc in ingestors:
                remaining = max(1.0, deadline - time.monotonic())
                try:
                    proc.wait(timeout=remaining)
                except subprocess.TimeoutExpired:
                    proc.kill()
            per_replica = []
            for path in result_paths:
                try:
                    with open(path) as f:
                        per_replica.append(json.load(f))
                except (OSError, ValueError):
                    per_replica.append(None)
            rates = [
                cell["apply_msgs_per_sec"]
                for cell in per_replica
                if cell
            ]
            result[f"replicas_{n_replicas}"] = {
                "per_replica": per_replica,
                "aggregate_apply_msgs_per_sec": round(sum(rates), 1),
                "pods_joined": sum(
                    cell["pods_joined"] for cell in per_replica if cell
                ),
            }
        finally:
            for proc in ingestors:
                if proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=10)
            if publisher is not None and publisher.poll() is None:
                publisher.terminate()
                try:
                    publisher.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    publisher.kill()
            shutil.rmtree(workdir, ignore_errors=True)
    agg1 = result["replicas_1"]["aggregate_apply_msgs_per_sec"]
    agg3 = result["replicas_3"]["aggregate_apply_msgs_per_sec"]
    result["scaling_1_to_3"] = round(agg3 / agg1, 2) if agg1 else None
    return result


def bench_event_storm(
    n_pods: Optional[int] = None, publish_s: Optional[float] = None
) -> dict:
    """detail.event_storm regime (docs/event-plane.md): the full
    subscribe -> demux -> shard-lane -> batched-apply path at fleet
    scale, device-free.

    Cells: consolidated poller (pollers=1 and pollers=4) vs the legacy
    thread-per-pod baseline at equal publish load (apply throughput +
    event-plane thread count); per-pod flow control on vs off under a
    deliberately chatty pod (fairness: an under-budget pod must never
    be shed); and a forced 10%-gap storm with inventory resync
    (gap-recovery wall time, per-pod staleness window, post-resync
    index consistency vs the publishers' ground truth)."""
    from llm_d_kv_cache_manager_tpu.kvevents.poller import (
        ChannelConfig,
        PollerPool,
        PollerPoolConfig,
    )
    from llm_d_kv_cache_manager_tpu.kvevents.resync import (
        CallableInventorySource,
        InventoryBlock,
        PodInventory,
        ResyncConfig,
        ResyncManager,
    )
    from llm_d_kv_cache_manager_tpu.kvevents.zmq_subscriber import (
        ZMQSubscriber,
        ZMQSubscriberConfig,
    )
    from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

    n = STORM_PODS if n_pods is None else n_pods
    window = STORM_PUBLISH_S if publish_s is None else publish_s
    run_id = uuid.uuid4().hex[:8]
    # Dedicated context: the fleet needs ~2N sockets and libzmq's
    # default max_sockets is 1023 — at N=1000 most SUB opens would
    # fail (and surface only as endless reconnect backoff).
    context = zmq.Context(2)
    context.set(zmq.MAX_SOCKETS, max(4096, 4 * n))
    fleet = _StormFleet(context, n, run_id)
    # The throughput cells subscribe over ipc to an EXTERNAL publisher
    # process (see _STORM_PUBLISHER_SRC); the inproc fleet above feeds
    # the gap/fairness logic cells, where publish volume is tiny.
    ipc_dir = tempfile.mkdtemp(prefix="kvtpu-storm-ipc-")
    storm_endpoints = {
        pod: f"ipc://{ipc_dir}/p{i}" for i, pod in enumerate(fleet.pods)
    }
    result: dict = {
        "n_pods": n,
        "publish_seconds": window,
        "block_size": STORM_BLOCK_SIZE,
        "offered_rate_msgs_per_sec": STORM_RATE,
    }
    try:
        # -- consolidated poller cells --------------------------------
        for pollers in (1, 4):
            _progress(
                f"event_storm: consolidated pollers={pollers}, N={n}"
            )
            ppool = PollerPool(
                context=context,
                config=PollerPoolConfig(
                    pollers=pollers, poll_interval_ms=20
                ),
            )
            channels = []

            def attach(sink, sink_batch, ppool=ppool, channels=channels):
                for pod in fleet.pods:
                    channels.append(
                        ppool.attach(
                            ChannelConfig(
                                endpoint=storm_endpoints[pod],
                                pod_identifier=pod,
                            ),
                            sink,
                            sink_batch=sink_batch,
                        )
                    )

            def detach(ppool=ppool, channels=channels):
                for channel in channels:
                    ppool.detach(channel)
                ppool.shutdown()

            cell = _storm_throughput_cell(
                fleet.pods,
                storm_endpoints,
                fleet.payload,
                attach,
                detach,
                window,
            )
            # The headline thread claim: the event plane is
            # pollers + pool workers, independent of N.
            cell["thread_ceiling"] = pollers + 4
            cell["thread_ceiling_ok"] = (
                cell["event_plane_threads"] <= cell["thread_ceiling"]
            )
            result[f"consolidated_pollers_{pollers}"] = cell

        # -- legacy thread-per-pod baseline ---------------------------
        _progress(f"event_storm: thread-per-pod baseline, N={n}")
        subscribers = []

        def attach_baseline(sink, _sink_batch):
            # The legacy subscriber has no batched sink — that IS the
            # baseline being measured.
            for pod in fleet.pods:
                sub = ZMQSubscriber(
                    ZMQSubscriberConfig(
                        endpoint=storm_endpoints[pod],
                        pod_identifier=pod,
                    ),
                    sink,
                    context=context,
                )
                sub.start()
                subscribers.append(sub)

        def detach_baseline():
            for sub in subscribers:
                sub._stop.set()
            for sub in subscribers:
                sub.stop()

        baseline = _storm_throughput_cell(
            fleet.pods,
            storm_endpoints,
            fleet.payload,
            attach_baseline,
            detach_baseline,
            window,
        )
        result["baseline_thread_per_pod"] = baseline
        consolidated = result["consolidated_pollers_1"]
        result["speedup_vs_thread_baseline"] = (
            round(
                consolidated["apply_msgs_per_sec"]
                / baseline["apply_msgs_per_sec"],
                2,
            )
            if baseline["apply_msgs_per_sec"]
            else None
        )

        # Non-inversion regression guard (BENCH_r06: pollers=4 applied
        # 324 msg/s vs 519 at pollers=1 — the O(lanes) shed scan under
        # the shard lock convoyed pollers against workers).  Apply rate
        # must be monotone-ish in pollers: a 0.85 tolerance absorbs
        # scheduler noise at saturation (the seed inversion sat at
        # 0.62x, far below it).
        r1 = consolidated["apply_msgs_per_sec"]
        r4 = result["consolidated_pollers_4"]["apply_msgs_per_sec"]
        result["poller_scaling"] = {
            "pollers_1_sps": r1,
            "pollers_4_sps": r4,
            "ratio_4_vs_1": round(r4 / r1, 3) if r1 else None,
            "monotone_tolerance": 0.85,
            "monotone_ok": bool(r1 and r4 >= 0.85 * r1),
        }

        # -- fairness: per-pod budget on vs off ------------------------
        result["fairness"] = _storm_fairness_cells(
            context, fleet, run_id
        )

        # -- forced gap storm + resync --------------------------------
        result["gap_storm"] = _storm_gap_cell(
            context,
            fleet,
            METRICS,
            CallableInventorySource,
            InventoryBlock,
            PodInventory,
            ResyncConfig,
            ResyncManager,
        )

        # -- replica-local ingestion scaling --------------------------
        result["replica_local"] = _storm_replica_local_cell(
            fleet, storm_endpoints, window
        )

        # -- profiler A/B on the apply path ---------------------------
        result["profiler_ab"] = _storm_profiler_ab(fleet.payload)

        # -- capture A/B on the apply path ----------------------------
        result["capture_ab"] = _storm_capture_ab(fleet.payload)
        return result
    finally:
        fleet.close()
        context.term()
        shutil.rmtree(ipc_dir, ignore_errors=True)


def _storm_profiler_ab(payload: bytes, rounds: int = 2) -> dict:
    """Profiler on-vs-off A/B on the decode+apply hot path
    (obs/profiler.py at its DEFAULT rate; docs/observability.md).

    In-process by design: the subject is the sampler thread's cost to
    the apply loop, and sockets would re-introduce the publisher-side
    noise the external-process cells exist to avoid.  Pre-built
    messages ride the batched sink (``add_tasks``: lock-free
    pre-decode + one shard round trip, the production poller shape)
    and the pool is drained to empty; apply rate = messages / wall.
    Alternating best-of damps scheduler bias, as in the trace A/B.
    """
    from llm_d_kv_cache_manager_tpu.obs.profiler import (
        ProfilerConfig,
        SamplingProfiler,
    )

    n_msgs = 4000
    n_pods = 16

    def one_side() -> float:
        pool, _index, _db = _storm_pool(concurrency=4)
        messages = [
            Message(
                topic=f"kv@ab-{i % n_pods}@{MODEL_NAME}",
                payload=payload,
                pod_identifier=f"ab-{i % n_pods}",
                model_name=MODEL_NAME,
                seq=i // n_pods + 1,
            )
            for i in range(n_msgs)
        ]
        t0 = time.perf_counter()
        for start in range(0, n_msgs, 64):
            pool.add_tasks(messages[start:start + 64])
        pool.drain()
        elapsed = time.perf_counter() - t0
        pool.shutdown()
        return round(n_msgs / elapsed, 1) if elapsed else 0.0

    prof = SamplingProfiler(ProfilerConfig())  # shipped default hz
    best = {True: 0.0, False: 0.0}
    for ab_round in range(rounds):
        order = (True, False) if ab_round % 2 == 0 else (False, True)
        for prof_on in order:
            if prof_on:
                prof.start()
            else:
                prof.close()
            best[prof_on] = max(best[prof_on], one_side())
    prof.close()
    overhead = (
        max(0.0, (best[False] - best[True]) / best[False])
        if best[False]
        else 0.0
    )
    return {
        "hz": prof.config.hz,
        "n_msgs": n_msgs,
        "profiler_on_msgs_per_sec": best[True],
        "profiler_off_msgs_per_sec": best[False],
        "overhead": round(overhead, 4),
        "bound": PROFILE_OVERHEAD_BOUND,
        "within_bound": overhead <= PROFILE_OVERHEAD_BOUND,
    }


def _storm_capture_ab(payload: bytes, rounds: int = 5) -> dict:
    """Input-flight-recorder on-vs-off A/B on the decode+apply hot
    path (obs/capture.py; ISSUE 15's ≤3% acceptance bound) — the same
    in-process batched-sink shape as ``_storm_profiler_ab``, with the
    capture tap (payload stash + compact ring append per message in
    ``Pool.add_tasks``) attached on one side.  Longer runs and more
    best-of rounds than the profiler cell: the tap's true cost
    (~0.5µs/msg against a ~25µs/msg all-in-process apply) sits near
    this container class's run-to-run noise floor."""
    from llm_d_kv_cache_manager_tpu.obs.capture import (
        CaptureConfig,
        InputCaptureRecorder,
    )

    n_msgs = 8000
    n_pods = 16

    def one_burst(pool) -> float:
        messages = [
            Message(
                topic=f"kv@cab-{i % n_pods}@{MODEL_NAME}",
                payload=payload,
                pod_identifier=f"cab-{i % n_pods}",
                model_name=MODEL_NAME,
                seq=i // n_pods + 1,
            )
            for i in range(n_msgs)
        ]
        t0 = time.perf_counter()
        for start in range(0, n_msgs, 64):
            pool.add_tasks(messages[start:start + 64])
        pool.drain()
        elapsed = time.perf_counter() - t0
        return round(n_msgs / elapsed, 1) if elapsed else 0.0

    # Shipped-default config: the bound is a claim about production
    # settings, and an oversized ring just measures gc scans of its
    # own retained objects instead of the tap.
    recorder = InputCaptureRecorder(CaptureConfig())
    # One WARM pool per side, reused across rounds: per-run pool
    # construction (worker-thread startup, cold shard caches) costs
    # more run-to-run variance than the tap itself.
    pool_off, _index_off, _db_off = _storm_pool(concurrency=4)
    pool_on, _index_on, _db_on = _storm_pool(concurrency=4)
    pool_on.set_capture(recorder)
    best = {True: 0.0, False: 0.0}
    try:
        one_burst(pool_off)  # warmup both sides
        one_burst(pool_on)
        for ab_round in range(rounds):
            order = (
                (True, False) if ab_round % 2 == 0 else (False, True)
            )
            for cap_on in order:
                best[cap_on] = max(
                    best[cap_on],
                    one_burst(pool_on if cap_on else pool_off),
                )
    finally:
        pool_off.shutdown()
        pool_on.shutdown()
    ring = recorder.status()["sources"]["kvevents"]
    overhead = (
        max(0.0, (best[False] - best[True]) / best[False])
        if best[False]
        else 0.0
    )
    return {
        "n_msgs": n_msgs,
        "capture_on_msgs_per_sec": best[True],
        "capture_off_msgs_per_sec": best[False],
        "overhead": round(overhead, 4),
        "bound": CAPTURE_OVERHEAD_BOUND,
        "within_bound": overhead <= CAPTURE_OVERHEAD_BOUND,
        "recorded": ring["appended"],
        "ring_bytes": ring["bytes"],
    }


def _storm_fairness_cells(context, fleet, run_id: str) -> dict:
    """Deterministic fairness A/B at the pool layer: 8 quiet pods
    enqueue 5 messages each (well under the effective budget,
    64 // 9 = 7), then one chatty pod bursts 2000 into the same shard
    of an unstarted pool (so the backlog is real, as in a storm).  With
    per-pod flow control ON the chatty pod pays for its own flood and
    no quiet message may be shed; OFF (legacy global FIFO, drop-oldest)
    the quiet pods — whose messages are the oldest — are shed first:
    exactly the starvation mode the lanes exist to kill."""
    from llm_d_kv_cache_manager_tpu.metrics.collector import METRICS

    chatty = "storm-fair-chatty"
    quiet = [f"storm-fair-quiet-{i}" for i in range(8)]
    payload = fleet.payload
    cells = {}
    for mode, per_pod in (("budget_on", True), ("budget_off", False)):
        _progress(f"event_storm: fairness {mode}")
        # Enqueue-only (never started): the cell measures shedding
        # against a standing backlog, the storm's worst case.
        pool, _index, _db = _storm_pool(
            start=False,
            concurrency=1,
            max_queue_depth=64,
            per_pod_flow_control=per_pod,
        )
        shed_before = _pod_labeled_totals(
            METRICS.kvevents_pod_shed, [chatty] + quiet
        )

        def enqueue(pod, i):
            pool.add_task(
                Message(
                    topic=f"kv@{pod}@{MODEL_NAME}",
                    payload=payload,
                    pod_identifier=pod,
                    model_name=MODEL_NAME,
                    seq=i,
                )
            )

        for i in range(5):
            for pod in quiet:
                enqueue(pod, i)
        for i in range(2000):
            enqueue(chatty, i)
        shed_after = _pod_labeled_totals(
            METRICS.kvevents_pod_shed, [chatty] + quiet
        )
        quiet_shed = sum(shed_after[p] - shed_before[p] for p in quiet)
        quiet_queued = sum(
            depth
            for q in pool._queues
            for pod, depth in q.lane_depths().items()
            if pod in quiet
        )
        cells[mode] = {
            "chatty_shed": int(shed_after[chatty] - shed_before[chatty]),
            "quiet_shed": int(quiet_shed),
            "quiet_queued": quiet_queued,
        }
        pool.start()
        pool.drain()
        pool.shutdown()
    cells["property_holds"] = (
        cells["budget_on"]["quiet_shed"] == 0
        and cells["budget_on"]["quiet_queued"] == 40
    )
    return cells


def _storm_gap_cell(
    context,
    fleet,
    METRICS,
    CallableInventorySource,
    InventoryBlock,
    PodInventory,
    ResyncConfig,
    ResyncManager,
) -> dict:
    """Force seq gaps on 10% of the fleet and measure the resync loop:
    recovery wall time, staleness window, post-resync consistency."""
    from llm_d_kv_cache_manager_tpu.kvevents.poller import (
        ChannelConfig,
        PollerPool,
        PollerPoolConfig,
    )

    _progress("event_storm: 10% gap storm + resync")
    rng = random.Random(7)
    gap_pods = fleet.pods[: max(1, len(fleet.pods) // 10)]
    pool, index, db = _storm_pool(concurrency=4)

    # Ground truth: each pod "stores" one private 2-block chain; the
    # inventory source serves it back on resync.
    truth = {}
    for pod in fleet.pods:
        base = rng.randrange(1, 1 << 30)
        tokens = [
            (base + j) % 30000 + 1 for j in range(2 * STORM_BLOCK_SIZE)
        ]
        truth[pod] = InventoryBlock(
            block_hashes=[base * 2 + 1, base * 2 + 2],
            token_ids=tokens,
            block_size=STORM_BLOCK_SIZE,
            medium="hbm",
        )

    source = CallableInventorySource(
        lambda pod: PodInventory(
            pod_identifier=pod,
            model_name=MODEL_NAME,
            blocks=[truth[pod]],
        )
    )
    resync = ResyncManager(
        pool, source, ResyncConfig(apply_timeout_s=60.0)
    )
    resync.start()

    seen = set()
    seen_lock = threading.Lock()

    def sink(message):
        with seen_lock:
            seen.add(message.pod_identifier)
        pool.add_task(message)

    ppool = PollerPool(
        context=context,
        config=PollerPoolConfig(pollers=1, poll_interval_ms=10),
    )
    manager_channels = {
        pod: ppool.attach(
            ChannelConfig(
                endpoint=fleet.endpoints[pod], pod_identifier=pod
            ),
            sink,
            on_gap=resync.gap_listener,
        )
        for pod in fleet.pods
    }
    try:
        _wait_join(fleet, fleet.pods, seen)
        # Phase 1: every pod stores its ground-truth chain.
        for pod in fleet.pods:
            block = truth[pod]
            fleet.publish_raw(
                pod,
                EventBatch(
                    ts=0.0,
                    events=[
                        BlockStored(
                            block_hashes=list(block.block_hashes),
                            parent_block_hash=None,
                            token_ids=list(block.token_ids),
                            block_size=block.block_size,
                            medium="hbm",
                        )
                    ],
                ).encode(),
            )
        time.sleep(0.5)
        pool.drain()

        staleness_sum0, staleness_n0 = _hist_stats(
            METRICS.kvevents_resync_staleness
        )
        # Phase 2: force a gap on 10% of pods (skip 5 seqs, then one
        # live message so the tracker sees the jump).
        t0 = time.perf_counter()
        for pod in gap_pods:
            fleet.skip_seq(pod, 5)
            fleet.publish_raw(pod)
        # Recovery = every forced gap DETECTED (resync attempted) and
        # the suspect set drained again — not just "no suspects yet".
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            stats = resync.stats()
            outcomes = stats["resyncs_ok"] + stats["resyncs_failed"]
            if outcomes >= len(gap_pods) and not stats["suspect"]:
                break
            time.sleep(0.05)
        recovery_s = time.perf_counter() - t0
        stats = resync.stats()
        staleness_sum1, staleness_n1 = _hist_stats(
            METRICS.kvevents_resync_staleness
        )
        resynced = int(staleness_n1 - staleness_n0)

        # Post-resync consistency: every gapped pod's ground-truth
        # chain must be claimed by exactly that pod again.
        consistent = 0
        for pod in gap_pods:
            keys = db.tokens_to_kv_block_keys(
                EMPTY_BLOCK_HASH, truth[pod].token_ids, MODEL_NAME
            )
            found = index.lookup(keys)
            if set(found) == set(keys) and all(
                any(e.pod_identifier == pod for e in entries)
                for entries in found.values()
            ):
                consistent += 1
        return {
            "gap_pods": len(gap_pods),
            "resynced": resynced,
            "resyncs_failed": stats["resyncs_failed"],
            "still_suspect": len(stats["suspect"]),
            "recovery_wall_s": round(recovery_s, 3),
            "staleness_mean_s": (
                round(
                    (staleness_sum1 - staleness_sum0) / resynced, 4
                )
                if resynced
                else None
            ),
            "post_resync_consistency": (
                round(consistent / len(gap_pods), 4) if gap_pods else None
            ),
        }
    finally:
        for channel in manager_channels.values():
            ppool.detach(channel)
        ppool.shutdown()
        resync.close()
        pool.shutdown()


def maybe_bench_event_storm(context: str) -> dict:
    """bench_event_storm under the degrade contract."""
    if _over_budget(reserve_s=90.0):
        return {"truncated": True}
    _progress(f"{context}: event_storm fleet regime (N={STORM_PODS})")
    try:
        return bench_event_storm()
    except Exception as exc:  # noqa: BLE001 — optional layer
        logger_exc = f"{type(exc).__name__}: {exc}"
        _progress(f"event_storm failed: {logger_exc}")
        return {"error": logger_exc[:300]}


def _routing_percentiles(samples: Sequence[float]) -> Optional[dict]:
    if not samples:
        return None
    return {
        "p50": round(float(np.percentile(samples, 50)) * 1e6, 1),
        "p99": round(float(np.percentile(samples, 99)) * 1e6, 1),
    }


def main() -> None:
    device = init_device()
    _progress(
        f"device ready ({device.platform}, {device.device_kind}, "
        f"{jax.device_count()} device(s)); init params"
    )
    requests, warmup_idx, hashes_list = make_workload()
    # One jitted program: eager, every op of the init compiles on its
    # own (~100 s at this width on the chip host against ~30 s).
    params = jax.jit(lambda key: llama.init_params(key, CFG))(
        jax.random.PRNGKey(0)
    )

    prefill_full, prefill_suffix = jit_prefills(CFG, PREFIX_TOKENS)
    # Warm both shapes so compile time stays out of the TTFT samples,
    # and measure per-path service times to place the arrival rate.
    _progress("compile + warm prefill shapes")
    warm = SimPod("warm", params)
    full_ids, _ = warm.alloc(TOTAL_TOKENS // BLOCK_SIZE)
    tok = jnp.zeros((1, TOTAL_TOKENS), jnp.int32)
    t_miss = t_hit = float("inf")
    readback_rtt = 0.0
    for _ in range(2):  # second pass = compiled, warm path
        t0 = time.perf_counter()
        logits, warm.kv = prefill_full(
            params, tok, warm.kv, jnp.asarray([full_ids], jnp.int32)
        )
        int(jnp.argmax(logits[0, -1]))
        t_miss = min(t_miss, time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits, warm.kv = prefill_suffix(
            params,
            tok[:, PREFIX_TOKENS:],
            warm.kv,
            jnp.asarray([full_ids], jnp.int32),
        )
        int(jnp.argmax(logits[0, -1]))
        t_hit = min(t_hit, time.perf_counter() - t0)
        readback_rtt = measure_readback_rtt()
    t_miss = max(t_miss - readback_rtt, 1e-4)
    t_hit = max(t_hit - readback_rtt, 1e-4)

    # detail.kernels: compiled Pallas-vs-XLA at serving shapes, and the
    # decode winner routed into the headline via decode_attention.
    _progress("detail.kernels: Pallas-vs-XLA sweep")
    kernels = bench_kernels(readback_rtt)
    decode_winner = kernels.get("paged_decode", {}).get("winner")
    if decode_winner:
        CFG.decode_attention = decode_winner
        CFG.decode_blocks_per_step = kernels["paged_decode"][
            "blocks_per_step"
        ]
        CFG.decode_mxu_native = kernels["paged_decode"]["mxu_native"]

    # Secondary metric: decode throughput over the warm pod's full
    # 8448-token context (the reference's output-tok/s axis; decode
    # attention is whichever kernel detail.kernels just measured ahead).
    decode_tok_s = None
    decode_truncated = True
    if not _over_budget(reserve_s=120.0):
        decode_truncated = False
        _progress("decode throughput")
        decode = jax.jit(
            lambda p, t, kv, bt, cl: llama.decode_step(
                p, t, kv, bt, cl, CFG
            ),
            donate_argnums=(2,),
        )
        table = jnp.asarray([full_ids], jnp.int32)
        ctx = jnp.asarray([TOTAL_TOKENS], jnp.int32)
        step_tok = jnp.zeros((1,), jnp.int32)
        logits, warm.kv = decode(params, step_tok, warm.kv, table, ctx)
        int(jnp.argmax(logits[0]))  # compile + drain
        decode_steps = 16
        t0 = time.perf_counter()
        for _ in range(decode_steps):
            logits, warm.kv = decode(params, step_tok, warm.kv, table, ctx)
        int(jnp.argmax(logits[0]))
        decode_elapsed = max(
            time.perf_counter() - t0 - readback_rtt, 1e-4
        )
        decode_tok_s = round(decode_steps / decode_elapsed, 1)
        del logits
    del warm

    # detail.mfu: full-prefill throughput vs chip peak.
    mfu = bench_mfu(t_miss)

    # Arrival rate: 70% of the fleet's capacity under *ideal* routing
    # (first request per group misses, the rest hit).  A well-routed
    # fleet is comfortably stable there; a hit-blind scheduler's
    # effective service time is ~t_miss, pushing it past saturation so
    # prefill queues build — the reference's headline mechanism
    # (BASELINE.md §1-2: TTFT seconds-vs-minutes at the same QPS).
    ideal_service = ideal_service_time(t_miss, t_hit, len(requests))
    qps = 0.7 * NUM_PODS / ideal_service

    # Headline: REAL on-device compute per request, across arrival
    # seeds — one Poisson draw has ~±10-20% noise (burned r2->r3), so
    # the reported value is the median seed and the spread is explicit.
    per_seed: List[dict] = []
    routing_samples: List[float] = []
    headline_truncated = False
    for seed in ARRIVAL_SEEDS:
        if per_seed and _over_budget(reserve_s=180.0):
            # ~1 headline seed costs 2 fleet runs of real prefills;
            # report the seeds measured rather than record nothing.
            headline_truncated = True
            _progress(
                f"budget: stopping headline after {len(per_seed)} seed(s)"
            )
            break
        _progress(f"headline seed {seed}: real-compute fleet runs")
        arrivals = poisson_arrivals(qps, len(requests), seed)
        rr_ttfts, rr_hit, _ = run_fleet(
            "round_robin", requests, params, prefill_full,
            prefill_suffix, arrivals, readback_rtt,
        )
        pr_ttfts, pr_hit, pr_routings = run_fleet(
            "precise", requests, params, prefill_full, prefill_suffix,
            arrivals, readback_rtt,
        )
        # Steady-state only, matching the TTFT percentiles below: the
        # warmup requests route against a cold index (cheap lookups,
        # first-call setup) and would bias the scoring-RPC stats.
        routing_samples.extend(
            r for i, r in enumerate(pr_routings) if i not in warmup_idx
        )
        rr_steady = [
            t for i, t in enumerate(rr_ttfts) if i not in warmup_idx
        ]
        pr_steady = [
            t for i, t in enumerate(pr_ttfts) if i not in warmup_idx
        ]
        p50_rr = float(np.percentile(rr_steady, 50))
        p50_pr = float(np.percentile(pr_steady, 50))
        per_seed.append(
            {
                "seed": seed,
                "speedup": round(p50_rr / p50_pr, 3) if p50_pr else 0.0,
                "p50_ttft_precise_s": round(p50_pr, 5),
                "p50_ttft_round_robin_s": round(p50_rr, 5),
                "hit_rate_precise": round(pr_hit, 3),
                "hit_rate_round_robin": round(rr_hit, 3),
            }
        )
    by_speedup = sorted(per_seed, key=lambda s: s["speedup"])
    # Lower-middle for even seed counts: a conservative headline, never
    # the max masquerading as the median.
    median = by_speedup[(len(by_speedup) - 1) // 2]
    speedup = median["speedup"]

    # detail.micro: device-free index/tokenization microbenches —
    # optional like every detail layer per the degrade contract.
    micro = maybe_bench_micro("detail.micro")

    # detail.read_path: scoring-path throughput regime (fast lane on
    # vs off + parity), device-free.
    read_path = maybe_bench_read_path("detail.read_path")

    # detail.cache_analytics: hit-attribution ledger vs ground truth,
    # planted index divergence through the audit plane, analytics
    # overhead A/B — device-free.
    cache_analytics = maybe_bench_cache_analytics(
        "detail.cache_analytics", t_miss, t_hit
    )

    # detail.tiered_churn: predictive-eviction A/B on the churn
    # workload + compute-or-load TTFT (docs/tiering.md), device-free
    # except for the measured readback floor.
    tiered_churn = maybe_bench_tiered_churn(
        "detail.tiered_churn", t_miss, t_hit, readback_rtt
    )

    # detail.scaleout_warmup: KV-transfer planning A/B/C — instant-warm
    # scale-out + load-blended routing + priced transfer directives vs
    # route-to-holder vs round-robin (docs/transfer.md), device-free.
    scaleout_warmup = maybe_bench_scaleout_warmup(
        "detail.scaleout_warmup", t_miss, t_hit
    )

    # detail.host_offload: the staging-engine data plane — staged vs
    # one-shot A/B, the MULTICHIP lanes-per-chip sweep, and TTFT
    # offload-hit vs recompute vs advisor-hybrid priced from the
    # measured transfers (docs/host-offload.md).
    host_offload = maybe_bench_host_offload("detail.host_offload", t_miss)

    # detail.event_storm: fleet-scale event-plane regime (consolidated
    # poller vs thread-per-pod, per-pod fairness, gap->resync),
    # device-free.
    event_storm = maybe_bench_event_storm("detail.event_storm")

    # Persistence regime: cold vs warm-recovered routing across an
    # indexer restart (uses the measured service times).
    indexer_restart = maybe_bench_indexer_restart(
        requests, hashes_list, t_miss, t_hit, ideal_service
    )

    # detail.replica_scaleout: the indexer as an N-replica cluster —
    # multi-replica scores/sec + parity + the failover hit-rate dip
    # (docs/replication.md), device-free.
    replica_scaleout = maybe_bench_replica_scaleout(
        requests, hashes_list, t_miss, t_hit, ideal_service
    )

    # detail.matrix: 5 strategies x QPS ladder x seeds, virtual clock.
    _progress("detail.matrix: virtual-clock strategy ladder")
    matrix, matrix_truncated = run_matrix(
        requests, hashes_list, t_miss, t_hit, ideal_service, warmup_idx
    )
    _progress("emit")

    emit_result(
        {
            "metric": "p50_ttft_speedup_precise_vs_round_robin",
            "value": speedup,
            "unit": "x",
            "vs_baseline": round(speedup / 3.0, 3),
            "detail": {
                "p50_ttft_precise_s": median["p50_ttft_precise_s"],
                "p50_ttft_round_robin_s": median[
                    "p50_ttft_round_robin_s"
                ],
                "prefix_cache_hit_rate_precise": median[
                    "hit_rate_precise"
                ],
                "prefix_cache_hit_rate_round_robin": median[
                    "hit_rate_round_robin"
                ],
                "headline_seeds": per_seed,
                "speedup_spread": {
                    "min": by_speedup[0]["speedup"],
                    "median": speedup,
                    "max": by_speedup[-1]["speedup"],
                },
                "qps": round(qps, 2),
                # The scoring RPC's own cost (reference: index
                # microbench axis): tokenize -> hash -> lookup ->
                # score per request, inside the precise runs.
                "routing_precise_us": _routing_percentiles(
                    routing_samples
                ),
                "micro": micro,
                "read_path": read_path,
                "cache_analytics": cache_analytics,
                "tiered_churn": tiered_churn,
                "scaleout_warmup": scaleout_warmup,
                "host_offload": host_offload,
                "event_storm": event_storm,
                "indexer_restart": indexer_restart,
                "replica_scaleout": replica_scaleout,
                "service_times": "measured",
                "service_miss_s": round(t_miss, 4),
                "service_hit_s": round(t_hit, 4),
                "readback_rtt_s": round(readback_rtt, 4),
                "decode_tok_s_per_seq": decode_tok_s,
                "decode_attention": CFG.decode_attention,
                "device": jax.devices()[0].platform,
                "requests": len(requests),
                "elapsed_s": round(_elapsed(), 1),
                "budget_s": _BUDGET_S,
                "headline_seeds_truncated": headline_truncated,
                "decode_truncated": decode_truncated,
                "matrix_truncated": matrix_truncated,
                "matrix": matrix,
                "mfu": mfu,
                "kernels": kernels,
            },
        }
    )


if __name__ == "__main__":
    main()
