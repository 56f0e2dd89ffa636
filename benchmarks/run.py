"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up (set-up), measures for `--seconds` on the real clock, checks
what the window served against the plain reference, and prints as its last line
the contract's JSON object.  `--trace 1` profiles a window of at most
TRACE_SECONDS and reports the per-layer metrics instead.  Fails, with no
result line, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (ROOT,) if p not in sys.path]
TRACE_SECONDS = 12.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load(root: str, kind: str, name: str) -> dict:
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


def read_thread_entries() -> None:
    """Read every thread's entries under /proc/self/task once, as the window
    opens.  Measured, not understood (PERF.md section 2): one process in eight
    otherwise runs its whole window with +1.1 ms on every dispatch and +1.3 ms
    on every completion (8 of 66 runs on the chip's sandboxed host); none of 56
    runs that made these reads did."""
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return
    for tid in tids:
        for entry in ("comm", "stat", "status"):
            try:
                with open(f"/proc/self/task/{tid}/{entry}") as f:
                    f.read()
            except OSError:
                pass


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = BENCH, on_cpu: bool = False, overrides: dict | None = None,
             control: bool = False) -> dict:
    """The whole of one run; returns the result object.  `on_cpu` (the tests'
    tiny sizes only) skips the look for a chip; `overrides` replaces traffic
    parameters (the rate sweep); `control` adds the float8 control's numbers
    (benchmarks/tests/control_run.py).  Beside the contract's keys the result
    carries "extra": every metric's value, the numbers compared, the counters."""
    import jax
    import jax.monitoring

    from benchmarks.harness import check, costs, engine, reduce, reference, traffic
    from llm_d_kv_cache_manager_tpu.models import llama
    from llm_d_kv_cache_manager_tpu.parallel.compile_cache import (
        configure_compile_cache,
    )

    cell = load(root, "cells", workload)
    cfg = load(root, "configs", cell["config"])
    tr = {**load(root, "traffic", cell["traffic"]), **(overrides or {})}
    specs = {name: load(root, "metrics", name) for name in cell["metrics"]}
    devices = jax.devices()
    chip = devices[0].platform == "tpu"
    if not on_cpu and (not chip or len(devices) < cell["chips"]):
        sys.exit(f"{workload} needs {cell['chips']} TPU chip(s); JAX found "
                 f"{len(devices)} x {devices[0].platform}")
    peak = costs.peaks(devices[0].device_kind) if chip else None
    if chip:
        configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: name == COMPILE_EVENT and compiles.append(name))

    tracing = trace and chip
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    rec = engine.Records(annotate=tracing)
    model = llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), block_size=engine.BLOCK,
        dtype=cfg["torch_dtype"])
    shapes = traffic.shapes(tr)
    fleet = engine.Fleet(model, reference.make_weights(cfg, seed), tr, shapes,
                         rec, interpret=not chip)
    trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    state = {}

    def open_window() -> float:
        if tracing:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            state["span"] = jax.profiler.TraceAnnotation("bench:window")
            state["span"].__enter__()
        state["compiles"] = len(compiles)
        gc.collect()
        gc.freeze()  # what set-up built is not walked again inside the window
        read_thread_entries()
        rec.recording = True
        state["t0"] = time.perf_counter()
        return state["t0"]

    ctx = SimpleNamespace(cfg=cfg, traffic=tr, seed=seed, seconds=seconds,
                          vocab=cfg["vocab_size"], fleet=fleet,
                          open_window=open_window)
    try:
        traffic.run(ctx)
        window_s = time.perf_counter() - state["t0"]
        rec.recording = False
        in_window = len(compiles) - state["compiles"]
        if tracing:
            state["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()
    finally:
        fleet.shutdown()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    picked = check.fetch(check.sample(
        [r for r in fleet.log if r["in_window"]], tr["check_sample"], seed))
    numbers = check.against_cache_model(fleet.log, tr["pool_blocks"])
    numbers["misrouted"] = (rec.counters["held_somewhere"]
                            - rec.counters["routed_to_holder"])
    numbers["compiles_in_window"] = in_window
    for r in fleet.log:
        r.pop("row", None)
    fleet.pods.clear()
    fleet.programs.clear()
    fleet.params = None
    gc.collect()
    numbers.update(check.against_reference(cfg, seed, picked))
    correct = bool(picked) and check.verdict(numbers, cell["limits"])

    pd_trace = None
    if tracing:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        pd_trace = reduce.Trace(found[0])
        device["busy_s"], device["window_s"] = pd_trace.busy_s, pd_trace.window_s
    env = {"cfg": cfg, "shapes": shapes, "peaks": peak}
    values = {name: reduce.read_metric(spec["read"], rec, pd_trace, window_s, env)
              for name, spec in specs.items()}
    values["setup_s"] = state["t0"] - T_START
    units = {**{n: s["unit"] for n, s in specs.items()}, "setup_s": "s"}
    per_layer = {n for n, s in specs.items() if "layer" in s}
    result = {"correct": correct, "attempted": int(rec.counters["attempted"]),
              "failed": 0,
              "metrics": {n: {"value": v, "unit": units[n]}
                          for n, v in values.items()
                          if v is not None and (n in per_layer) == bool(trace)},
              "device": device}
    if pd_trace is not None:
        result["breakdown"] = pd_trace.breakdown()
    result["extra"] = {"values": values, "numbers": numbers, "window_s": window_s,
                       "counters": dict(rec.counters)}
    by_span: dict[str, list] = {}
    for name, start, stop in rec.spans:
        by_span.setdefault(name, []).append(stop - start)
    print("spans p50/mean ms:", {n: (round(1e3 * reduce.percentile(v, 50), 3),
                                     round(1e3 * sum(v) / len(v), 3))
                                 for n, v in by_span.items()}, flush=True)
    if control:
        result["extra"]["control"] = check.against_reference(cfg, seed, picked,
                                                             "fp8")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args()
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    del result["extra"]  # the last line holds the contract's keys and no others
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
