"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up (set-up), measures for `--seconds` on the real clock, checks
what the window served against the plain reference, and prints as its last line
the contract's JSON object.  `--trace 1` profiles a window of at most
TRACE_SECONDS with the program's own tracer (`obs/trace.py`) at rate 1.0, and
reports the per-layer metrics instead; `--trace 0` runs with that tracer off.
Fails, with no result line, where JAX finds no TPU or fewer chips than the
cell asks for.
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
TRACE_RING = 1 << 16  # traces kept: set-up's and a window's are some hundreds
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
             control: bool = False, tracer_rate: float | None = None) -> dict:
    """The whole of one run; returns the result object.  `on_cpu` (the tests'
    tiny sizes only) skips the look for a chip; `overrides` replaces traffic
    parameters (the rate sweep); `control` adds the float8 control's numbers
    (benchmarks/tests/control_run.py); `tracer_rate` is the program's tracer's
    sample rate where it is not 1.0 with `trace` and 0.0 without
    (benchmarks/tests/traced_run.py).  Beside the contract's keys the result
    carries "extra": every metric's value, the numbers compared, the counters,
    and what the traced run read them from."""
    import jax
    import jax.monitoring

    from benchmarks.harness import (
        check, costs, engine, family, program_spans, reduce, traffic,
    )
    from llm_d_kv_cache_manager_tpu.obs.trace import TRACER
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
    if tracer_rate is None:
        tracer_rate = float(trace)
    TRACER.configure(sample_rate=tracer_rate, ring_size=TRACE_RING)
    rec = engine.Records(annotate=tracing)
    program = family.program(cfg)
    shapes = traffic.shapes(tr)
    fleet = engine.Fleet(program, program.from_published(cfg, engine.BLOCK),
                         family.reference(cfg).make_weights(cfg, seed), tr,
                         shapes, rec, interpret=not chip)
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

    pd_trace = placed = clock = None
    rows, dropped = TRACER.recorder.export()
    if dropped:
        sys.exit(f"{dropped} traces of the program were dropped: the ring "
                 f"of {TRACE_RING} is too small for this window")
    if tracing:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        with open(os.path.join(os.path.dirname(found[0]), "program_spans.jsonl"),
                  "w") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)
        pd_trace = reduce.Trace(found[0])
        device["busy_s"], device["window_s"] = pd_trace.busy_s, pd_trace.window_s
        clock = program_spans.clock_offset(rec.spans, pd_trace.host)
        print("clock offset, its spread, pairs:", clock, flush=True)
        if clock[1] <= program_spans.CLOCK_SPREAD_LIMIT_S:
            placed = program_spans.place(rows, clock[0])
    env = {"cfg": cfg, "shapes": shapes, "peaks": peak, "rows": rows,
           "window": (state["t0"], state["t0"] + window_s)}
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
        result["breakdown"] = pd_trace.breakdown(placed=placed)
    result["extra"] = {"values": values, "numbers": numbers, "window_s": window_s,
                       "counters": dict(rec.counters), "spans": rec.spans,
                       "rows": rows, "window": env["window"], "trace": pd_trace,
                       "clock": clock, "placed": placed}
    by_span: dict[str, list] = {}
    for name, start, stop in rec.spans:
        by_span.setdefault(name, []).append(stop - start)
    print("spans p50/mean/max ms:",
          {n: tuple(round(1e3 * x, 3) for x in (
              reduce.percentile(v, 50), sum(v) / len(v), max(v)))
           for n, v in by_span.items()}, flush=True)
    if control:
        result["extra"]["control"] = check.against_reference(cfg, seed, picked,
                                                             "fp8")
    result["checks"] = {name: {"value": value, "limit": cell["limits"][name]}
                        for name, value in numbers.items()}
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    a = ap.parse_args()
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    del result["extra"]  # the contract's keys, then the numbers compared
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
