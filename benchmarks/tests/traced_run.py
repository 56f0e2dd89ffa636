"""Read, on the chip, what the program's own spans say about a cell, and what
recording them costs.

    python3 benchmarks/tests/traced_run.py <cell> <seconds> <seed> <rate> [<rate> ...]

Runs the cell's traced window (`run_cell(..., trace=True, tracer_rate=<rate>)`:
profiler on, at most `run.TRACE_SECONDS`) once per tracer sample rate, in turn,
in one process.  `run_cell` exports every span of the window (a dropped trace
fails the run) and writes them to `.bench_trace/<cell>/program_spans.jsonl`
beside the `.xplane.pb`; a run at rate 1.0 is what `run.py --trace 1` does, and
prints one JSON line with

- every `program_span` / `program_attr` metric under `benchmarks/metrics/` that
  finds something to read in this cell, and the p50 of every other span name;
- the benchmark's outside spans `route.score` and `publish_events` (p50), the
  yardstick the stage spans are checked against and the overhead is read on;
- `checks`: per request, the router's stage spans summed over the outside
  `route.score` span, and the event plane's over `publish_events`; how many
  program spans lie outside the benchmark span that called them;
- on a chip: the clock offset between `perf_counter` and the profiler and its
  spread (the run fails above 0.2 ms), and the whole of `idle_gaps_inner`
  (`run.py`'s `breakdown` carries its ten largest).

A run at rate 0.0 prints the outside spans alone.  Runs at a rate between the
two (0.5) give the overhead, profiler on on both sides: after the last run one
line compares each call's outside span when the tracer drew it with the same
call's when it passed it by (`split`, `paired_overhead`).  Runs at 1.0 against
runs at 0.0 cannot tell: between the windows of one process `route.score`
drifts by more than the tracer costs (1.53 to 1.80 ms untraced, PERF.md section
6).  The process's first run is left out: whatever its rate, its
`tokenize.encode` read 1 ms above the later runs' (4.48 against 3.35 ms), so
start with one run to spare, e.g. `0 0.5 0.5 0.5 0.5`.  The numbers of PERF.md
section 6 (PR 25) are these lines.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402
from benchmarks.harness import program_spans, reduce  # noqa: E402

OUTSIDE = {"indexer.score": "route.score", "kvevents.message": "publish_events"}
STAGES = {"indexer.score": ("tokenize", "hash_blocks", "index_lookup", "score"),
          "kvevents.message": ("kvevents.queue_wait", "kvevents.decode",
                               "kvevents.apply")}


def program_metrics(root: str, rows, t0: float, t1: float) -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)["read"]
        if spec["from"] in ("program_span", "program_attr"):
            value = program_spans.read(spec, rows, t0, t1)
            if value is not None:
                out[os.path.basename(path)[:-5]] = value
    return out


def consistency(traces, rec_spans) -> dict:
    """Per trace of the window (`program_spans.by_trace`), against the
    benchmark span that called it: the stage spans' sum over the outside span,
    and whether every span lies inside it."""
    checks = {}
    for trace_name, outside in OUTSIDE.items():
        calls = sorted((a, b) for n, a, b in rec_spans if n == outside)
        shares, astray, total = [], 0, 0
        for root, spans in traces:
            if root["trace"] != trace_name:
                continue
            inside = [c for c in calls if c[0] <= root["start"] < c[1]]
            if not inside:
                astray += 1 + len(spans)
                continue
            a, b = inside[0]
            total += 1 + len(spans)
            astray += sum(not (a <= s["start"] and s["end"] <= b)
                          for s in [root, *spans])
            stages = sum(s["end"] - s["start"] for s in spans
                         if s["span"] in STAGES[trace_name])
            shares.append(stages / (b - a))
        if shares:
            checks[outside] = {
                "traces": len(shares), "spans": total, "spans_outside": astray,
                "stage_sum_over_outside_p50": statistics.median(shares),
                "stage_sum_over_outside_min": min(shares),
                "stage_sum_over_outside_max": max(shares)}
    return checks


def split(traces, rec_spans) -> dict:
    """Each outside span of the window in order, as [seconds, drawn]: whether
    the tracer drew the call (a trace of the program began inside the span) or
    passed it by."""
    out = {}
    for trace_name, outside in OUTSIDE.items():
        began = [root["start"] for root, _ in traces
                 if root["trace"] == trace_name]
        out[outside] = [[b - a, any(a <= t < b for t in began)]
                        for n, a, b in rec_spans if n == outside]
    return out


def paired_overhead(calls: list[list]) -> dict | None:
    """Traced against untraced, call by call.  `calls`: one list per run, of
    that run's outside spans in order as [seconds, drawn].  One seed gives every
    run the same requests in the same order, so the i-th call of each run is the
    same work: its traced and its untraced readings (medians over the runs
    that have them) are compared with each other, and the result is the median
    over calls.  Hits, misses and first re-asks differ severalfold, so medians
    of the two groups as wholes would compare their mixes."""
    ratios, diffs, base = [], [], []
    for same in zip(*calls):
        on = [v for v, drawn in same if drawn]
        off = [v for v, drawn in same if not drawn]
        if on and off:
            on, off = statistics.median(on), statistics.median(off)
            ratios.append(on / off)
            diffs.append(on - off)
            base.append(off)
    if not ratios:
        return None
    return {"ratio": statistics.median(ratios), "diff_s": statistics.median(diffs),
            "untraced_s": statistics.median(base), "calls": len(ratios),
            "runs": len(calls)}


def other_spans(traces, rows, t0: float, t1: float) -> dict:
    """p50 per trace of every span name, and of each trace's own duration."""
    names = {(root["trace"], s["span"]) for root, spans in traces for s in spans}
    out = {f"{trace}:{name}": program_spans.read(
        {"from": "program_span", "trace": trace, "name": name, "reduce": "p50"},
        rows, t0, t1) for trace, name in sorted(names)}
    for trace in sorted({t for t, _ in names}):
        out[f"{trace}:(trace)"] = program_spans.read(
            {"from": "program_span", "trace": trace, "trace_duration": True,
             "reduce": "p50"}, rows, t0, t1)
    return out


def placed_inside(placed, host_spans) -> dict:
    """After the clock offset: how many of the window's program traces lie
    inside the profiler's own record of the benchmark span that called them,
    and by how much the farthest one sticks out."""
    inside = worst = count = 0
    for name, a, b, depth in placed:
        if depth != 2 or b <= 0.0:
            continue
        calls = [(x, y) for n, x, y in host_spans if n == OUTSIDE.get(name)]
        if not calls:
            continue
        x, y = min(calls, key=lambda c: abs(c[0] - a))
        count += 1
        inside += x <= a and b <= y
        worst = max(worst, x - a, b - y)
    return {"traces_placed": count, "traces_inside_their_bench_span": inside,
            "farthest_outside_s": worst}


def traced_run(cell: str, seed: int, seconds: float, rate: float, *,
               root: str = run.BENCH, on_cpu: bool = False) -> dict:
    """One traced window at one tracer rate; the printed line's object."""
    result = run.run_cell(cell, seed, seconds, True, root=root, on_cpu=on_cpu,
                          tracer_rate=rate)
    extra = result["extra"]
    spans, rows, (t0, t1) = extra["spans"], extra["rows"], extra["window"]
    line = {"cell": cell, "seed": seed, "rate": rate, "correct": result["correct"],
            "device": result["device"],
            "outside": {name: reduce.reduce_values(
                [b - a for n, a, b in spans if n == name], "p50")
                for name in OUTSIDE.values()}}
    if not rate or not spans:
        return line
    traces = program_spans.by_trace(rows, t0, t1)
    line["traces"] = len(traces)
    line["metrics"] = program_metrics(root, rows, t0, t1)
    line["spans_p50"] = other_spans(traces, rows, t0, t1)
    line["checks"] = consistency(traces, spans)
    if rate < 1.0:
        line["split"] = split(traces, spans)
    if extra["clock"] and rate == 1.0:
        offset, spread, pairs = extra["clock"]
        line["clock"] = {"offset_s": offset, "spread_s": spread, "pairs": pairs}
        if extra["placed"] is None:
            sys.exit(f"the two clocks' offset spreads by {spread * 1e3:.3f} ms")
        line["clock"].update(placed_inside(extra["placed"], extra["trace"].host))
        line["idle_gaps"] = result["breakdown"]["idle_gaps"]
        line["idle_gaps_inner"] = program_spans.idle_gaps_inner(
            extra["trace"], extra["placed"])
    return line


def main(argv: list[str]) -> None:
    cell, seconds, seed = argv[0], float(argv[1]), int(argv[2])
    runs = []
    for rate in map(float, argv[3:]):
        line = traced_run(cell, seed, seconds, rate)
        print(json.dumps(line), flush=True)
        runs.append(line)

    overhead = {}
    for name in OUTSIDE.values():
        paired = paired_overhead([line["split"][name] for line in runs[1:]
                                  if "split" in line])
        if paired:
            overhead[name] = paired
    if overhead:
        print(json.dumps({"cell": cell, "overhead": overhead}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
