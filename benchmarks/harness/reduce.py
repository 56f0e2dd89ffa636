"""From what a run observed to its metrics: percentiles, spans, counters, and
the profiler's trace (`.xplane.pb`, read with `jax.profiler.ProfileData` alone).

A metric is a data file, `benchmarks/metrics/<name>.json`, whose "read" says
where its number comes from:

  {"from": "series",  "name": "ttft", "reduce": "p50"}
  {"from": "span",    "name": "route.score", "reduce": "p50"}
  {"from": "counter", "num": "cached_tokens", "den": "prompt_tokens"}
  {"from": "rate",    "num": "completed_tokens"}          (over the window)
  {"from": "device",  "program": "hit_prefill_P\\d+_S\\d+", "reduce": "p50"}
  {"from": "roofline", "program": "miss_prefill_T\\d+",
   "op": "flash_gqa_attention_pallas", "cost": "flash_prefill_min_s"}
  {"from": "program_span", "trace": "indexer.score", "name": "tokenize",
   "reduce": "p50"}                    (the program's own spans: program_spans.py)
  {"from": "program_attr", "name": "hash_blocks", "num": "memo_blocks",
   "den": "block_keys"}

A reader that finds nothing to read returns None and the metric is left out.
A roofline's `cost` is a function of `costs.py` or of the configuration's
family module (`costs.cost`).

The trace, as the TPU runtime writes it: one plane "/device:TPU:<n>" per chip,
whose line "XLA Modules" holds one event per run of a jitted program (named
"jit_<function>(<fingerprint>)") and whose line "XLA Ops" holds the operations
inside, each named by its HLO line ("%fusion.111 = bf16[...] fusion(...)", a
Pallas kernel as "%<kernel name>.<n> = ... custom-call(...)"); the host's planes "/host:*" hold the benchmark's spans as events named
"bench:<span>".  All lines share one clock, in nanoseconds.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict

import numpy as np

# operations that only hold others (a scan's loop): their time is their parts'
CONTAINERS = re.compile(r"^(while|conditional|cond|call)[.\d]*$")


def op_name(event: str) -> str:
    """An operation's event carries its whole HLO line,
    "%fusion.111 = bf16[...] fusion(...)": the name is what stands before " = "."""
    return event.split(" = ", 1)[0].lstrip("%")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]."""
    ordered = sorted(values)
    return ordered[max(0, int(-(-len(ordered) * q // 100)) - 1)]


def reduce_values(values, how: str):
    if not len(values):
        return None
    if how == "mean":
        return statistics.fmean(values)
    if how == "sum":
        return float(sum(values))
    return float(percentile(values, float(how[1:])))


def _union(starts, ends):
    """Merged, sorted intervals of a set of [start, end)."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts)
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    first = np.concatenate(([True], s[1:] > reach[:-1]))
    last = np.concatenate((first[1:], [True]))
    return s[first], reach[last]


def _covered(s, e, upto):
    """Length of the merged set (s, e) that lies before each time in `upto`."""
    upto = np.asarray(upto, float)
    if not len(s):
        return np.zeros_like(upto)
    total = np.concatenate(([0.0], np.cumsum(e - s)))
    i = np.searchsorted(s, upto, "right")
    over = np.where(i > 0, np.maximum(0.0, e[np.maximum(i, 1) - 1] - upto), 0.0)
    return total[i] - over


class Trace:
    """Device programs and operations, and the host's spans, of one trace.
    Times are seconds from the start of the span `bench:window`."""

    def __init__(self, path: str) -> None:
        from jax.profiler import ProfileData

        self.programs, self.ops, self.host = [], [], []
        chips: dict[str, list] = defaultdict(list)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            device = plane.name.startswith("/device:TPU:")
            for line in plane.lines:
                if device and line.name == "XLA Modules":
                    self.programs += [
                        (re.sub(r"^jit_|\(.*$", "", ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
                elif device and line.name == "XLA Ops":
                    rows = [(op_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in line.events]
                    rows = [r for r in rows if not CONTAINERS.match(r[0])]
                    self.ops += rows
                    chips[plane.name] += rows
                elif plane.name.startswith("/host:"):
                    self.host += [
                        (ev.name[6:], ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name.startswith("bench:")]
        window = [(a, b) for n, a, b in self.host if n == "window"]
        if not window or not self.ops:
            raise RuntimeError("the trace holds no bench:window span or no "
                               "device operation")
        t0, t1 = window[0]

        def clip(rows):
            return sorted(((n, (max(a, t0) - t0) / 1e9, (min(b, t1) - t0) / 1e9)
                           for n, a, b in rows if b > t0 and a < t1),
                          key=lambda r: r[1])

        self.window_s = (t1 - t0) / 1e9
        self.programs, self.ops = clip(self.programs), clip(self.ops)
        self.host = clip(r for r in self.host if r[0] != "window")
        per_chip = [sum(e - s for s, e in zip(*_union(*zip(*[r[1:] for r in rows]))))
                    for rows in map(clip, chips.values()) if rows]
        self.busy_s = sum(per_chip) / len(per_chip)

    def program_times(self, pattern: str) -> list[float]:
        want = re.compile(pattern)
        return [b - a for n, a, b in self.programs if want.fullmatch(n)]

    def op_time_per_program(self, program: str, op: str) -> list[float]:
        """Summed time of the operations whose name holds `op`, for each run
        of a program matching `program`."""
        want = re.compile(program)
        runs = [(a, b) for n, a, b in self.programs if want.fullmatch(n)]
        if not runs:
            return []
        starts = np.array([a for a, _ in runs])
        sums = np.zeros(len(runs))
        for n, a, b in self.ops:
            if op in n:
                i = int(np.searchsorted(starts, a, "right")) - 1
                if i >= 0 and a < runs[i][1]:
                    sums[i] += b - a
        return [s for s in sums if s > 0]

    def breakdown(self, top: int = 10, placed=None) -> dict:
        """The device operations with most time, as <program>:<op>, and the
        idle time by the span the host was in: inside a program with nothing
        running ("in_program"), in one of the benchmark's outermost spans, or
        in none ("other").  With `placed`, the program's own spans on this
        trace's clock (`program_spans.place`), also `idle_gaps_inner`: the same
        idle time by the innermost span, the benchmark's or the program's."""
        starts = np.array([a for _, a, _ in self.programs])
        ends = np.array([b for _, _, b in self.programs])
        by_op: dict[str, float] = defaultdict(float)
        for n, a, b in self.ops:
            i = int(np.searchsorted(starts, a, "right")) - 1
            inside = i >= 0 and a < ends[i]
            by_op[f"{self.programs[i][0] if inside else '-'}:{n}"] += b - a
        busy = _union([a for _, a, _ in self.ops], [b for _, _, b in self.ops])
        held = _union(np.concatenate((busy[0], starts)),
                      np.concatenate((busy[1], ends)))

        def idle(cover, a, b):
            return (b - a) - float(_covered(*cover, b) - _covered(*cover, a))

        gaps: dict[str, float] = defaultdict(float)
        gaps["in_program"] = idle(busy, 0.0, self.window_s) \
            - idle(held, 0.0, self.window_s)
        outer = [r for r in self.host if "." not in r[0]]
        for n, a, b in outer:
            gaps[n] += idle(held, a, b)
        gaps["other"] = idle(held, 0.0, self.window_s) \
            - sum(v for k, v in gaps.items() if k != "in_program")

        def rank(times):
            return [[k, v] for k, v in
                    sorted(times.items(), key=lambda kv: -kv[1])[:top]]

        out = {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}
        if placed is not None:
            from .program_spans import idle_gaps_inner

            out["idle_gaps_inner"] = idle_gaps_inner(self, placed)[:top]
        return out


def read_metric(read: dict, rec, trace, window_s: float, env: dict):
    """One metric's number from a run, or None where there is nothing to read.
    `env`: configuration, shapes and peaks for a roofline's cost function; the
    program's exported span rows and the window's bounds on `perf_counter`."""
    kind = read["from"]
    if kind in ("program_span", "program_attr"):
        from .program_spans import read as read_rows

        return read_rows(read, env["rows"], *env["window"])
    if kind == "series":
        return reduce_values(rec.series.get(read["name"], ()), read["reduce"])
    if kind == "span":
        return reduce_values([b - a for n, a, b in rec.spans if n == read["name"]],
                             read["reduce"])
    if kind == "counter":
        den = rec.counters.get(read["den"], 0)
        return rec.counters.get(read["num"], 0) / den if den else None
    if kind == "rate":
        done = rec.counters.get(read["num"], 0)
        return done / window_s if done else None
    if trace is None:
        return None
    if kind == "device":
        return reduce_values(trace.program_times(read["program"]), read["reduce"])
    if kind == "roofline":
        from . import costs

        took = (trace.op_time_per_program(read["program"], read["op"])
                if read.get("op") else trace.program_times(read["program"]))
        if not took:
            return None
        least = costs.cost(read["cost"], env["cfg"])(
            env["cfg"], env["shapes"], rec.counters, env["peaks"])
        return 100.0 * least / statistics.median(took)
    raise ValueError(f"unknown metric source {kind!r}")
