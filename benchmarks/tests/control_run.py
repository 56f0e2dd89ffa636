"""Read, on the chip, the numbers that decide `correct` for sound runs of the
program and for the float8 control, several seeds in one process.

    python3 benchmarks/tests/control_run.py <cell> <seconds> <seed> [<seed> ...]

Prints one JSON line per seed: {"seed", "program": {...}, "control": {...}};
a seed written c<seed> also gets the control, the others the program alone.
The limits in `benchmarks/cells/<cell>.json` were set from these lines
(PERF.md section 2).  `sweep` in place of a cell name sweeps the open-loop rate
instead:  control_run.py sweep <cell> <seconds> <rate> [<rate> ...]
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.run import run_cell  # noqa: E402


def main(argv: list[str]) -> None:
    if argv[0] == "sweep":
        cell, seconds = argv[1], float(argv[2])
        for rate in map(float, argv[3:]):
            x = run_cell(cell, 7, seconds, False,
                         overrides={"rate_rps": rate})["extra"]
            print(json.dumps({
                "rate_rps": rate, "sent": x["counters"]["attempted"],
                "backlog_at_end": x["counters"]["backlog_at_end"],
                "drain_s": x["window_s"] - seconds,
                **{k: x["values"][k] for k in (
                    "ttft_p50_s", "ttft_p95_s", "queue_wait_p50_s")}}), flush=True)
        return
    cell, seconds = argv[0], float(argv[1])
    for word in argv[2:]:
        seed = int(word.lstrip("c"))
        r = run_cell(cell, seed, seconds, False, control=word.startswith("c"))
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "program": r["extra"]["numbers"],
                          "control": r["extra"].get("control")}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
