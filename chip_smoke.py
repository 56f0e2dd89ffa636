"""The quickest proof that the pod path still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process, at
a published configuration and the geometry of the benchmark's cell
``mistral7b-docs-shared``, both read from that cell's files under
``benchmarks/`` (``configs/mistral-7b-v0.3-l8.json``: Mistral-7B widths,
8 of 32 layers; 8192 + 256 tokens, block 16, 4 pods x 4096 blocks =
2.1 GB a pod), with random weights made from a seed.

1. **fleet** — the benchmark's ``harness.engine.Fleet`` (its ``route``
   / ``account`` / ``prefill`` / ``commit``, used as they are):
   ``Indexer.get_pod_scores`` routes 2 prefix groups x 3 requests (2
   misses, then 4 hits) to the pods' paged pools; misses run
   ``llama.prefill_paged``, hits ``llama.prefill_continue``; every
   request publishes its KVEvents through the msgpack codec and
   ``kvevents.Pool`` into the index;
2. **reference** — paged prefill against the dense forward on a small
   input (the repo's own equivalence reference);
3. **decode** — ``llama.decode_step`` x 8 on one sequence, the compiled
   Pallas kernel (what serves on the chip) against the XLA gather at the
   same context, both donating the pool;
4. **flash_bound** — the Pallas flash kernel compiled and run at the
   longest context ``flash_pallas.fits_vmem`` admits;
5. **offload** — ``TPUOffloadConnector``: store one group's blocks,
   zero them, load them back, bit-identical, through the pinned-host
   staging lanes and the native I/O engine built from ``native/src/``
   in the run;
6. with four or more chips, **four_chips** — the fleet with pod *i*'s
   pool and a replica of the parameters committed to chip *i* after
   ``Fleet`` built them, and ``__graft_entry__``'s sharded checks
   (tp-sharded paged decode, ring prefill with the flash body compiled,
   per-chip staged offload) on the real devices.

It checks what comes out, not only that it runs; any failed check or
raised exception ends the process with a non-zero code and no result
line.  No TPU is such a failure: there is no CPU mode in ``main()``.
The phases are plain functions of a model configuration and a
geometry, and tests/test_chip_smoke.py calls them at a tiny size with
``interpret=True``.

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import __graft_entry__ as graft
from benchmarks import run as benchmark
from benchmarks.harness import costs, engine, program_llama, traffic
from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVCachePool,
    KVCachePoolConfig,
)
from llm_d_kv_cache_manager_tpu.native import build as native_build
from llm_d_kv_cache_manager_tpu.native.engine import JobStatus
from llm_d_kv_cache_manager_tpu.offload.spec import (
    TPUOffloadConnector,
    TPUOffloadSpec,
)
from llm_d_kv_cache_manager_tpu.offload.worker import group_blocks_per_file
from llm_d_kv_cache_manager_tpu.ops import flash_pallas
from llm_d_kv_cache_manager_tpu.ops.flash_attention import (
    flash_gqa_attention,
)
from llm_d_kv_cache_manager_tpu.parallel.compile_cache import (
    configure_compile_cache,
)

# Two paths that compute the same logits in bf16 with different
# accumulation orders (Pallas vs XLA attention, cached vs recomputed
# K/V) must agree to this share of the largest reference logit (the
# benchmark's `prefill_logits_rel_err` limit is the same 0.05).
BF16_REL_TOL = 0.05

# The benchmark cell whose configuration and geometry this run takes.
CELL = "mistral7b-docs-shared"

# The seed of the one-chip fleet's requests.  `logits_agree` asks two paths
# for one first choice, and these are random weights: the reference's best two
# logits often lie closer (0.1-0.9 % of the largest, at some step of the
# decode phase, under seeds 0, 1 and 3) than two roundings of one sum do
# (1.0-1.7 %).  Under this seed none of the nine compared rows holds a pair
# closer than 1.8 % (PERF.md section 6, PR 36; ROADMAP T2 asks for a rule).
FLEET_SEED = 2

# Files of the offload round trip hold this many device blocks.
OFFLOAD_BLOCKS_PER_FILE = 4


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(condition: bool, what: str) -> None:
    if not condition:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """The traffic of one smoke run: documents of ``prefix_tokens``
    asked with a fresh ``suffix_tokens`` question, as the cell's."""

    prefix_tokens: int
    suffix_tokens: int
    pool_blocks: int
    pods: int = 4
    n_groups: int = 2
    reqs_per_group: int = 3
    decode_steps: int = 8
    reference_tokens: int = 1024

    @property
    def total_tokens(self) -> int:
        return self.prefix_tokens + self.suffix_tokens

    def traffic(self) -> dict:
        """As a traffic file of the cell's kind, for ``Fleet`` and
        ``harness.traffic.shapes``."""
        return {
            "kind": "paced_sessions",
            "pods": self.pods,
            "pool_blocks": self.pool_blocks,
            "doc_tokens": self.prefix_tokens,
            "question_tokens": self.suffix_tokens,
        }


def full_setup() -> Tuple[llama.LlamaConfig, Geometry]:
    """The model and the geometry of ``CELL``, from its files."""
    cell = benchmark.load(benchmark.BENCH, "cells", CELL)
    cfg = benchmark.load(benchmark.BENCH, "configs", cell["config"])
    tr = benchmark.load(benchmark.BENCH, "traffic", cell["traffic"])
    check(tr["kind"] == "paced_sessions", f"{CELL}: traffic kind {tr['kind']}")
    return program_llama.from_published(cfg, engine.BLOCK), Geometry(
        prefix_tokens=tr["doc_tokens"],
        suffix_tokens=tr["question_tokens"],
        pool_blocks=tr["pool_blocks"],
        pods=tr["pods"],
    )


def max_rel_err(got, want) -> float:
    """Largest difference over the largest reference value."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def peak_hbm_gb() -> Optional[float]:
    """The default device's high-water mark of memory so far, where the
    backend reports one (the TPU does, the CPU does not)."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats["peak_bytes_in_use"] / 1e9


def say(phase: str, **fields) -> None:
    """One stdout line per phase result."""
    fields["peak_hbm_gb"] = peak_hbm_gb()
    body = " ".join(
        f"{key}={value:.4f}" if isinstance(value, float) else f"{key}={value}"
        for key, value in fields.items()
    )
    print(f"[chip_smoke] {phase}: {body}", flush=True)


def logits_agree(got: np.ndarray, want: np.ndarray, what: str) -> float:
    """Finite, within BF16_REL_TOL of the reference, same argmax;
    returns the error."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{what}: shape {got.shape} vs {want.shape}")
    check(
        bool(np.isfinite(got).all() and np.isfinite(want).all()),
        f"{what}: non-finite logits",
    )
    err = max_rel_err(got, want)
    check(err < BF16_REL_TOL, f"{what}: max rel err {err:.4f} >= {BF16_REL_TOL}")
    check(
        int(np.argmax(got)) == int(np.argmax(want)),
        f"{what}: argmax {int(np.argmax(got))} vs {int(np.argmax(want))} "
        f"(max rel err {err:.4f})",
    )
    return err


def timed(fn, *args):
    """(result, seconds) of one call, waited for on the device.  That
    ``block_until_ready`` does wait is phase_block_until_ready's check;
    nothing is read back here, because the first use of each small
    eager op compiles it, inside the time."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def compile_timed(jitted, *args, want_mosaic: Optional[bool] = None):
    """Lower and compile ``jitted`` for ``args``; returns (executable,
    compile seconds).  ``want_mosaic`` asserts that the lowered program
    does (or does not) hold a Mosaic custom call, i.e. which attention
    kernel the trace really took."""
    lowered = jitted.lower(*args)
    if want_mosaic is not None:
        has_mosaic = "tpu_custom_call" in lowered.as_text()
        check(
            has_mosaic == want_mosaic,
            f"lowered program: Mosaic call present={has_mosaic}, "
            f"expected {want_mosaic}",
        )
    t0 = time.perf_counter()
    executable = lowered.compile()
    return executable, time.perf_counter() - t0


def alloc_spare(pod, n_blocks: int) -> List[int]:
    """``n_blocks`` of the pod's pool that no cached prefix lives in."""
    block_ids, evicted = pod.alloc(n_blocks)
    check(not evicted, f"{pod.name}: pool too small, evicted {len(evicted)}")
    return block_ids


def make_requests(
    cfg: llama.LlamaConfig, geom: Geometry, seed: int
) -> List[Tuple[int, str, np.ndarray]]:
    """(group, prompt text, tokens): every group's first request, then
    every group's second, ... so precise routing sees ``n_groups``
    misses and then only hits."""
    rng = np.random.default_rng(seed)

    def draw(n: int) -> np.ndarray:
        return rng.integers(1, cfg.vocab_size, n, dtype=np.int64)

    prefixes = [draw(geom.prefix_tokens) for _ in range(geom.n_groups)]
    requests = []
    for _ in range(geom.reqs_per_group):
        for group in range(geom.n_groups):
            tokens = np.concatenate((prefixes[group], draw(geom.suffix_tokens)))
            requests.append((group, engine.prompt_text(tokens.tolist()), tokens))
    return requests


@dataclasses.dataclass
class FleetResult:
    fleet: engine.Fleet
    params: Dict[str, object]  # pod -> the parameters on that pod's device
    holder: Dict[int, str]  # group -> pod that holds its prefix
    last_hit: dict  # the last hit request: pod, tokens, block ids, logits

    def prefill(self, pod, tokens, block_ids, hit: bool, first_new: int):
        """``Fleet.prefill`` with the parameters that live where the
        pod's pool does (``Fleet`` itself keeps one set for all pods)."""
        self.fleet.params = self.params[pod.name]
        return self.fleet.prefill(pod, tokens, block_ids, hit, first_new)


def phase_fleet(
    cfg: llama.LlamaConfig,
    geom: Geometry,
    params,
    *,
    interpret: bool = False,
    devices: Optional[Sequence[jax.Device]] = None,
    seed: int = 0,
) -> FleetResult:
    """Route, prefill and publish ``geom``'s requests through the
    benchmark's precise fleet.  ``devices``: pod *i*'s pool and a
    replica of the parameters are committed to ``devices[i]`` after
    ``Fleet`` built them (one chip per pod); None keeps every pod on the
    default device, as the benchmark's cells do."""
    check(
        cfg.block_size == engine.BLOCK,
        f"the fleet's indexer hashes {engine.BLOCK}-token blocks",
    )
    n_prefix_blocks = geom.prefix_tokens // cfg.block_size
    requests = make_requests(cfg, geom, seed)
    records = engine.Records()
    records.recording = True  # the counters of `route` are read below
    tr = geom.traffic()
    fleet = engine.Fleet(
        program_llama, cfg, params, tr, traffic.shapes(tr), records, interpret
    )
    pod_names = [pod.name for pod in fleet.pods]
    pod_params = dict.fromkeys(pod_names, params)
    compile_s = {"miss": 0.0, "hit": 0.0}
    if devices is not None:
        for pod, device in zip(fleet.pods, devices):
            pod.kv = jax.device_put(pod.kv, device)
            pod_params[pod.name] = jax.device_put(params, device)
    else:
        # One device: compile both programs ahead, apart from the run
        # time, and look at what they lowered to: the flash kernel in the
        # miss, its continuation entry in the hit.
        pod = fleet.pods[0]
        table = np.zeros((1, geom.total_tokens // cfg.block_size), np.int32)
        for key, n_new, want_mosaic in (
            ("miss", geom.total_tokens, not interpret),
            ("hit", geom.suffix_tokens, not interpret),
        ):
            fleet.programs[key], compile_s[key] = compile_timed(
                fleet.programs[key],
                params,
                np.zeros((1, n_new), np.int32),
                pod.kv,
                table,
                want_mosaic=want_mosaic,
            )
    result = FleetResult(fleet, pod_params, holder={}, last_hit={})

    holder = result.holder
    run_s = {"miss": [], "hit": []}
    worked_on = set()
    for group, text, tokens in requests:
        hashes = engine.block_hash_chain(tokens)
        scores = fleet.indexer.get_pod_scores(text, engine.MODEL_NAME, pod_names)
        pod = fleet.route(text, hashes, n_prefix_blocks)
        if group in holder:
            # Requests 2..n of a group: the index learned the holder
            # from the events that pod published, and routes to it.
            check(
                scores.get(holder[group], 0.0) > 0
                and scores[holder[group]] == max(scores.values()),
                f"group {group}: holder {holder[group]} not the top "
                f"score in {scores}",
            )
            check(
                pod.name == holder[group],
                f"group {group}: routed to {pod.name}, holder is "
                f"{holder[group]}",
            )
        else:
            check(
                not scores or max(scores.values()) == 0,
                f"group {group}: first request scored {scores}",
            )
            holder[group] = pod.name
        hit, first_new, block_ids, evicted = fleet.account(
            pod, hashes, n_prefix_blocks
        )
        check(hit == (first_new > 0), "hit without a cached prefix")
        t0 = time.perf_counter()
        _, _, row = result.prefill(pod, tokens, block_ids, hit, first_new)
        run_s["hit" if hit else "miss"].append(time.perf_counter() - t0)
        worked_on |= row.devices()
        last = np.asarray(row, np.float32)
        check(
            last.shape == (cfg.vocab_size,) and bool(np.isfinite(last).all()),
            f"group {group}: logits {last.shape}, finite="
            f"{bool(np.isfinite(last).all())}",
        )
        if hit:
            result.last_hit = {
                "pod": pod,
                "tokens": tokens,
                "block_ids": block_ids,
                "logits": last,
            }
        fleet.commit(pod, tokens, hashes, first_new, block_ids, evicted)

    n_miss, n_hit = len(run_s["miss"]), len(run_s["hit"])
    check(
        n_miss == geom.n_groups
        and n_hit == geom.n_groups * (geom.reqs_per_group - 1),
        f"{n_miss} misses and {n_hit} hits",
    )
    held = records.counters["held_somewhere"]
    check(
        held == n_hit and records.counters["routed_to_holder"] == held,
        f"the fleet counted {held} held prefixes and "
        f"{records.counters['routed_to_holder']} routed to a holder",
    )
    if devices is not None:
        # (Here each pod's first call of each program also compiled it
        # for that pod's chip, so first_*_s hold the compile.)
        check(
            len(worked_on) == min(len(devices), geom.n_groups),
            f"work ran on {sorted(str(d) for d in worked_on)}",
        )
    say(
        "fleet",
        pods=len(fleet.pods),
        devices=len(worked_on),
        misses=n_miss,
        hits=n_hit,
        miss_compile_s=compile_s["miss"],
        hit_compile_s=compile_s["hit"],
        miss_run_s=min(run_s["miss"]),
        hit_run_s=min(run_s["hit"]),
        first_miss_s=run_s["miss"][0],
        first_hit_s=run_s["hit"][0],
    )
    return result


def phase_hit_vs_miss(
    cfg: llama.LlamaConfig, geom: Geometry, result: FleetResult
) -> None:
    """The last hit's logits (suffix over the cached prefix) against the
    miss path over the same tokens, on a pod that never saw them."""
    hit = result.last_hit
    other = next(
        pod
        for pod in result.fleet.pods
        if pod.name not in result.holder.values()
    )
    block_ids = alloc_spare(other, geom.total_tokens // cfg.block_size)
    _, _, row = result.prefill(other, hit["tokens"], block_ids, False, 0)
    err = logits_agree(hit["logits"], np.asarray(row), "hit path vs miss path")
    say("hit_vs_miss", max_rel_err=err, tol=BF16_REL_TOL)


def phase_block_until_ready(
    cfg: llama.LlamaConfig, geom: Geometry, result: FleetResult
) -> bool:
    """Whether ``jax.block_until_ready`` waits for the device here: the
    time to enqueue one miss prefill, the time until it reports ready,
    and what a host readback still costs after that.  Every time this
    script prints rests on the answer, so ``main()`` requires it."""
    pod = result.last_hit["pod"]
    miss = result.fleet.programs["miss"]
    block_ids = alloc_spare(pod, geom.total_tokens // cfg.block_size)
    tokens = np.zeros((1, geom.total_tokens), np.int32)
    table = np.asarray([block_ids], np.int32)
    for _ in range(2):  # the first pass compiles the readback's own ops
        t0 = time.perf_counter()
        _, row, pod.kv = miss(result.params[pod.name], tokens, pod.kv, table)
        enqueue_s = time.perf_counter() - t0
        jax.block_until_ready(row)
        ready_s = time.perf_counter() - t0
        int(jnp.argmax(row))
        readback_s = time.perf_counter() - t0 - ready_s
    waits = bool(ready_s > 4 * enqueue_s and readback_s < 0.25 * ready_s)
    say(
        "block_until_ready",
        enqueue_s=enqueue_s,
        ready_s=ready_s,
        readback_after_ready_s=readback_s,
        waits=waits,
    )
    return waits


def phase_reference(
    cfg: llama.LlamaConfig, geom: Geometry, params, *, interpret: bool = False
) -> None:
    """Paged prefill (the Pallas flash kernel on the chip) against the
    dense forward — what tests/test_llama_model.py compares against —
    on one small input at full width."""
    T = geom.reference_tokens
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (1, T), 1, cfg.vocab_size
    )
    n_blocks = T // cfg.block_size
    kv = jnp.zeros(
        (cfg.n_layers, n_blocks, 2, cfg.block_size, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16,
    )
    table = jnp.arange(n_blocks, dtype=jnp.int32)[None]
    paged, paged_compile_s = compile_timed(
        jax.jit(
            lambda p, t, kv, bt: llama.prefill_paged(
                p, t, kv, bt, cfg, interpret=interpret
            )[0][0, -1]
        ),
        params,
        tokens,
        kv,
        table,
        want_mosaic=not interpret,
    )
    dense, dense_compile_s = compile_timed(
        jax.jit(
            lambda p, t: llama.forward(p, t, cfg, use_flash=False)[0, -1]
        ),
        params,
        tokens,
        want_mosaic=False,
    )
    got, paged_s = timed(paged, params, tokens, kv, table)
    want, dense_s = timed(dense, params, tokens)
    err = logits_agree(got, want, "paged prefill vs dense forward")
    say(
        "reference",
        tokens=T,
        compile_s=paged_compile_s + dense_compile_s,
        run_s=paged_s + dense_s,
        max_rel_err=err,
        tol=BF16_REL_TOL,
    )


def phase_decode(
    cfg: llama.LlamaConfig,
    geom: Geometry,
    result: FleetResult,
    *,
    interpret: bool = False,
) -> None:
    """``decode_steps`` greedy steps on the last hit's sequence: the
    Pallas paged-decode kernel (``llama.decode_step``'s rule: compiled
    for the TPU, or interpreted) against the XLA gather
    (``decode_attention="gather"``), both from the same pool at every
    step.  Both programs donate the pool, as the benchmark's pod does, so
    neither step's time holds a pool copy: the gather's step hands its
    pool to the kernel's, which writes the same slot again before it
    attends."""
    hit = result.last_hit
    pod, params = hit["pod"], result.params[hit["pod"].name]
    spare = alloc_spare(pod, -(-geom.decode_steps // cfg.block_size))
    table = jnp.asarray([list(hit["block_ids"]) + spare], jnp.int32)

    def jit_decode(decode_attention: str):
        decode_cfg = dataclasses.replace(
            cfg, decode_attention=decode_attention
        )
        return jax.jit(
            lambda p, t, kv, bt, cl: llama.decode_step(
                p, t, kv, bt, cl, decode_cfg, interpret=interpret
            ),
            donate_argnums=(2,),
        )

    token = jnp.asarray([int(np.argmax(hit["logits"]))], jnp.int32)
    ctx = jnp.asarray([geom.total_tokens + 1], jnp.int32)
    pallas, pallas_compile_s = compile_timed(
        jit_decode("auto"),
        params, token, pod.kv, table, ctx,
        want_mosaic=not interpret,
    )
    gather, gather_compile_s = compile_timed(
        jit_decode("gather"),
        params, token, pod.kv, table, ctx,
        want_mosaic=False,
    )
    pallas_s, gather_s, worst = [], [], 0.0
    for step in range(geom.decode_steps):
        (want, kv), seconds = timed(gather, params, token, pod.kv, table, ctx)
        gather_s.append(seconds)
        (got, pod.kv), seconds = timed(pallas, params, token, kv, table, ctx)
        pallas_s.append(seconds)
        worst = max(
            worst,
            logits_agree(
                np.asarray(got[0]),
                np.asarray(want[0]),
                f"decode step {step}: pallas vs gather",
            ),
        )
        token = jnp.argmax(got, axis=-1).astype(jnp.int32)
        ctx = ctx + 1
    say(
        "decode",
        steps=geom.decode_steps,
        context=geom.total_tokens + 1,
        pallas_compile_s=pallas_compile_s,
        gather_compile_s=gather_compile_s,
        pallas_step_s=min(pallas_s),
        gather_step_s=min(gather_s),
        max_rel_err=worst,
        tol=BF16_REL_TOL,
    )


def flash_bound_tokens(cfg: llama.LlamaConfig, kv_chunk: int = 512) -> int:
    """The longest context, in whole K/V chunks, that
    ``flash_pallas.fits_vmem`` admits for ``cfg``'s heads."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    tokens = kv_chunk
    while flash_pallas.fits_vmem(tokens + kv_chunk, cfg.head_dim, itemsize):
        tokens += kv_chunk
    return tokens


def phase_flash_bound(
    cfg: llama.LlamaConfig, tokens: int, *, interpret: bool = False
) -> None:
    """The Pallas flash kernel at ``tokens`` of context — in ``main()``
    the bound ``fits_vmem`` states, so that what the router admits is
    what Mosaic accepts on this chip — against the XLA scan op."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    check(
        flash_pallas.fits_vmem(tokens, cfg.head_dim, itemsize),
        f"fits_vmem refuses {tokens} tokens",
    )
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    dtype = jnp.dtype(cfg.dtype)
    q = jax.random.normal(keys[0], (1, tokens, cfg.n_heads, cfg.head_dim), dtype)
    k = jax.random.normal(keys[1], (1, tokens, cfg.n_kv_heads, cfg.head_dim), dtype)
    v = jax.random.normal(keys[2], (1, tokens, cfg.n_kv_heads, cfg.head_dim), dtype)
    pallas, compile_s = compile_timed(
        jax.jit(
            lambda q, k, v: flash_pallas.flash_gqa_attention_pallas(
                q, k, v, interpret=interpret
            )
        ),
        q, k, v,
        want_mosaic=not interpret,
    )
    got, run_s = timed(pallas, q, k, v)
    want = flash_gqa_attention(q, k, v)
    err = max_rel_err(got, want)
    check(
        bool(jnp.isfinite(got.astype(jnp.float32)).all()) and err < BF16_REL_TOL,
        f"flash kernel at {tokens} tokens: max rel err {err:.4f}",
    )
    say(
        "flash_bound",
        tokens=tokens,
        head_dim=cfg.head_dim,
        compile_s=compile_s,
        run_s=run_s,
        max_rel_err=err,
    )


def phase_offload(
    cfg: llama.LlamaConfig,
    geom: Geometry,
    result: FleetResult,
    *,
    require_native: bool = True,
) -> None:
    """Store one group's prefix blocks from the pod's pool through the
    staged connector, zero them on the device, load them back:
    bit-identical, pinned path still on, native engine in use."""
    hit = result.last_hit
    pod = hit["pod"]
    n_prefix_blocks = geom.prefix_tokens // cfg.block_size
    block_ids = list(hit["block_ids"][:n_prefix_blocks])
    pool = KVCachePool(
        KVCachePoolConfig(
            num_layers=cfg.n_layers,
            num_blocks=geom.pool_blocks,
            block_size=cfg.block_size,
            num_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim,
        )
    )
    # The pod's own K/V, not the pool's fresh zeros; the pool is its
    # only holder until the phase hands it back.
    pool.kv, pod.kv = pod.kv, None
    root = tempfile.mkdtemp(prefix="kvtpu-chip-smoke-")
    connector = TPUOffloadConnector(
        TPUOffloadSpec(
            shared_storage_path=root,
            model_name="chip-smoke/llama",
            device_block_size=cfg.block_size,
            offloaded_block_size=cfg.block_size * OFFLOAD_BLOCKS_PER_FILE,
            staging_lanes=2,
        ),
        pool,
    )
    try:
        check(
            connector.engine.is_native or not require_native,
            "the offload engine is the Python one: the native library "
            "built from native/src/ did not load",
        )
        check(
            pool.pinned_host and connector.staging.uses_pinned,
            "no pinned_host staging on this device",
        )
        n_files = -(-len(block_ids) // OFFLOAD_BLOCKS_PER_FILE)
        groups = group_blocks_per_file(
            [0xC0DE0000 + i for i in range(n_files)],
            block_ids,
            OFFLOAD_BLOCKS_PER_FILE,
        )
        before = pool.gather_to_host(block_ids)
        check(bool(np.any(before != 0)), "the group's blocks hold no K/V")

        t0 = time.perf_counter()
        connector.store_handler.transfer_async(1, groups)
        check(
            connector.store_handler.wait(1) == JobStatus.SUCCEEDED,
            "staged store failed",
        )
        store_s = time.perf_counter() - t0
        peak_after_store = peak_hbm_gb()

        pool.kv = pool.kv.at[:, jnp.asarray(block_ids)].set(0)
        check(
            not np.any(pool.gather_to_host(block_ids) != 0),
            "blocks not zeroed before the load",
        )

        t0 = time.perf_counter()
        connector.load_handler.transfer_async(2, groups)
        check(
            connector.load_handler.wait(2) == JobStatus.SUCCEEDED,
            "staged load failed",
        )
        load_s = time.perf_counter() - t0
        peak_after_load = peak_hbm_gb()

        after = pool.gather_to_host(block_ids)
        check(
            before.tobytes() == after.tobytes(),
            "offload round trip is not bit-identical",
        )
        check(
            pool.pinned_host and connector.staging.uses_pinned,
            "the pinned_host path switched itself off during the run",
        )
        pod.kv = pool.kv
        say(
            "offload",
            engine="native" if connector.engine.is_native else "python",
            uses_pinned=connector.staging.uses_pinned,
            blocks=len(block_ids),
            files=n_files,
            mbytes=before.nbytes / 1e6,
            store_s=store_s,
            load_s=load_s,
            bit_identical=True,
            peak_after_store_gb=peak_after_store,
            peak_after_load_gb=peak_after_load,
        )
    finally:
        connector.close()
        shutil.rmtree(root, ignore_errors=True)


def phase_four_chips(
    cfg: llama.LlamaConfig, geom: Geometry, params, *, interpret: bool = False
) -> None:
    """One pod per chip behind the router, then the sharded checks of
    ``__graft_entry__`` on the same four chips."""
    devices = jax.devices()[:4]
    spread = dataclasses.replace(geom, n_groups=4, reqs_per_group=2)
    t0 = time.perf_counter()
    result = phase_fleet(
        cfg, spread, params, interpret=interpret, devices=devices, seed=1
    )
    result.fleet.shutdown()
    fleet_s = time.perf_counter() - t0
    del result
    t0 = time.perf_counter()
    graft._dryrun_inproc(4, ring_interpret=interpret)
    say(
        "four_chips",
        fleet_s=fleet_s,
        sharded_checks_s=time.perf_counter() - t0,
        chips=[str(d) for d in devices],
    )


def cache_entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def main() -> None:
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"chip_smoke: JAX found no TPU (platform {device.platform!r}, "
            f"{device.device_kind}); nothing was run"
        )
    # An unknown chip is an error here, before any number is printed
    # for it, not a None in a report.
    peak = costs.peaks(device.device_kind)["bf16_flops"] / 1e12
    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)
    print(
        f"[chip_smoke] device: platform={device.platform} "
        f"kind={device.device_kind!r} count={jax.device_count()} "
        f"peak_bf16_tflops={peak} jax={jax.__version__}",
        flush=True,
    )
    say("compile_cache", dir=cache_dir, entries_before=entries_before)

    t0 = time.perf_counter()
    library = native_build.build(force=True)
    check(library is not None, "no C++ compiler to build native/src/ with")
    say("native_build", library=os.path.basename(library),
        build_s=time.perf_counter() - t0)

    cfg, geom = full_setup()
    say("setup", cell=CELL, layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, tokens=geom.total_tokens, pods=geom.pods,
        pool_blocks=geom.pool_blocks)
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(llama.init_params, cfg=cfg))(
        jax.random.PRNGKey(0)
    )
    jax.block_until_ready(params)
    say("init_params", seconds=time.perf_counter() - t0)

    phase_reference(cfg, geom, params)
    phase_flash_bound(cfg, flash_bound_tokens(cfg))
    result = phase_fleet(cfg, geom, params, seed=FLEET_SEED)
    try:
        phase_hit_vs_miss(cfg, geom, result)
        check(
            phase_block_until_ready(cfg, geom, result),
            "block_until_ready came back before the device was done: "
            "the times above are enqueue times",
        )
        # Decode and offload keep one pool; the other pods' 2.1 GB
        # each go back to the chip first.
        keep = result.last_hit["pod"]
        for pod in result.fleet.pods:
            if pod is not keep:
                pod.kv = None
        phase_decode(cfg, geom, result)
        phase_offload(cfg, geom, result)
    finally:
        result.fleet.shutdown()
    del result

    if jax.device_count() >= 4:
        phase_four_chips(cfg, geom, params)
    else:
        say("four_chips", skipped=f"{jax.device_count()} chip(s) on this host")

    say("compile_cache", dir=cache_dir, entries_before=entries_before,
        entries_after=cache_entries(cache_dir))
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device.platform,
                    "kind": device.device_kind,
                    "count": jax.device_count(),
                },
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
