"""Read, on the chip, what the program's tracer costs a decode cell's gap
between tokens, with the JAX profiler off on both sides.

    python3 benchmarks/tests/tracer_cost.py <cell> <seconds> <seed> <pairs>

Runs the cell's untraced window (`run_cell(..., trace=False, tracer_rate=r)`)
in pairs, one process for all: a run with the tracer off (0.0) and one with it
at 1.0 on the same seed, the next pair on the next seed with the order turned
round.  One run to spare comes first and is left out of the comparison (a
process's first run compiles or loads every program, and has read
`itl_p50_s` 1.4 % high: PERF.md section 7).  `traced_run.py` cannot give
this number: it pairs the router's outside spans call by call within a window,
and the decode cells' cost lies in `pod.step`, once a step.

Prints one JSON line a run ({"seed", "rate", "correct", "itl_p50_s"}, and at
rate 1.0 every `program_span` / `program_attr` metric that finds something to
read: `pod_decode_period_s` there is the program's own reading of the gap
between two decode launches with the profiler off), then one line with each
pair's `itl_p50_s` on over off and their median.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run  # noqa: E402
from benchmarks.tests.traced_run import program_metrics  # noqa: E402


def one_run(cell: str, seed: int, seconds: float, rate: float, *,
            root: str = run.BENCH, on_cpu: bool = False) -> dict:
    result = run.run_cell(cell, seed, seconds, False, root=root, on_cpu=on_cpu,
                          tracer_rate=rate)
    extra = result["extra"]
    line = {"cell": cell, "seed": seed, "rate": rate,
            "correct": result["correct"],
            "itl_p50_s": extra["values"].get("itl_p50_s")}
    if rate:
        line["metrics"] = program_metrics(root, extra["rows"], *extra["window"])
    return line


def ratios(lines: list[dict]) -> dict:
    """`itl_p50_s` with the tracer on over off, pair by pair (the two runs of
    a seed), and the median over pairs."""
    by_seed: dict[int, dict] = {}
    for line in lines:
        by_seed.setdefault(line["seed"], {})[bool(line["rate"])] = line["itl_p50_s"]
    pairs = [both[True] / both[False] for both in by_seed.values()
             if len(both) == 2]
    return {"pairs": len(pairs), "on_over_off": pairs,
            "median": statistics.median(pairs) if pairs else None}


def main(argv: list[str]) -> None:
    cell, seconds, seed, pairs = argv[0], float(argv[1]), int(argv[2]), int(argv[3])
    print(json.dumps({"spare": one_run(cell, seed, seconds, 0.0)}), flush=True)
    lines = []
    for i in range(pairs):
        for rate in ((0.0, 1.0), (1.0, 0.0))[i % 2]:
            lines.append(one_run(cell, seed + 1 + i, seconds, rate))
            print(json.dumps(lines[-1]), flush=True)
    print(json.dumps({"cell": cell, "itl_p50_s": ratios(lines)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
