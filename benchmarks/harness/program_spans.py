"""The program's own spans as a source of metrics, and their place on the
device trace's clock.

`obs/trace.py` records what the router read path and the event plane did as
spans on `time.perf_counter`; `TRACER.recorder.export()` returns them as flat
rows (one root row per trace, `span` None, then one per span).  Two readers
turn rows into a metric's number, next to the six of `reduce.read_metric`:

  {"from": "program_span", "trace": "indexer.score", "name": "tokenize",
   "reduce": "p50"}
  {"from": "program_span", "trace": "kvevents.message", "trace_duration": true,
   "reduce": "p50"}
  {"from": "program_attr", "name": "hash_blocks", "num": "memo_blocks",
   "den": "block_keys"}

`program_span` sums the spans of that name within one trace (a stage that ran
in chunks is one number per request), leaves out a trace in which the stage did
not run, and reduces over the traces that began inside the window.
`program_attr` is the ratio of two attributes summed over those spans.  Both
return None where there is nothing to read: a program without these spans, a
tracer at rate 0.

One clock: the benchmark writes each of its own spans twice, into
`Records.spans` on `perf_counter` and into the profiler's `/host:` plane.  The
median of (profiler start - perf_counter start) over those pairs is the offset
that places the program's spans on the device trace's timeline
(`clock_offset`, `place`); `idle_gaps_inner` then splits the device's idle time
by the innermost span, the benchmark's or the program's, that covers it.

`run.py --trace 1` runs the program's tracer at rate 1.0, exports the rows
after the window and hands them to `reduce.read_metric`, which sends the two
kinds here; `Trace.breakdown` carries `idle_gaps_inner`.
"""

from __future__ import annotations

import statistics
from collections import defaultdict

import numpy as np

from .reduce import _covered, _union, reduce_values

CLOCK_SPREAD_LIMIT_S = 0.2e-3


def by_trace(rows: list[dict], t0: float = float("-inf"),
             t1: float = float("inf")) -> list[tuple[dict, list[dict]]]:
    """(root row, span rows) of each finished trace that began in [t0, t1)."""
    spans: dict[str, list[dict]] = defaultdict(list)
    roots = []
    for row in rows:
        if row["span"] is None:
            roots.append(row)
        else:
            spans[row["trace_id"]].append(row)
    return [(root, spans[root["trace_id"]]) for root in roots
            if root["end"] is not None and t0 <= root["start"] < t1]


def read(read: dict, rows: list[dict], t0: float, t1: float):
    """A `program_span` or `program_attr` metric's number from exported rows,
    over the traces that began inside the window [t0, t1) on `perf_counter`."""
    traces = [(root, spans) for root, spans in by_trace(rows, t0, t1)
              if read.get("trace") in (None, root["trace"])]
    if read["from"] == "program_attr":
        hit = [s["attrs"] for _, spans in traces for s in spans
               if s["span"] == read["name"]]
        den = sum(a.get(read["den"], 0) for a in hit)
        return sum(a.get(read["num"], 0) for a in hit) / den if den else None
    if read["from"] != "program_span":
        raise ValueError(f"not a reader of program spans: {read['from']!r}")
    if read.get("trace_duration"):
        return reduce_values([root["end"] - root["start"] for root, _ in traces],
                             read["reduce"])
    sums = []
    for _, spans in traces:
        took = [s["end"] - s["start"] for s in spans if s["span"] == read["name"]]
        if took:
            sums.append(sum(took))
    return reduce_values(sums, read["reduce"])


def clock_offset(rec_spans, host_spans) -> tuple[float, float, int]:
    """(offset, spread, pairs): what to add to a `perf_counter` time to stand
    on the clock of `host_spans`.  Both lists hold (name, start, end) of the
    benchmark's spans, one on each clock; spans pair up by name in order of
    start, and a name that one side has more of is left out.  The offset is the
    median difference of the starts; the spread is the distance between the
    differences' quartiles, which one pair torn apart by a context switch
    between its two stamps does not move."""
    ours, theirs = defaultdict(list), defaultdict(list)
    for name, start, _ in rec_spans:
        ours[name].append(start)
    for name, start, _ in host_spans:
        theirs[name].append(start)
    diffs = []
    for name, starts in ours.items():
        if len(starts) == len(theirs.get(name, ())):
            diffs += [b - a for a, b in zip(sorted(starts), sorted(theirs[name]))]
    if len(diffs) < 4:
        raise RuntimeError("fewer than four spans were recorded on both clocks")
    q1, _, q3 = statistics.quantiles(diffs, n=4)
    return statistics.median(diffs), q3 - q1, len(diffs)


def place(rows: list[dict], offset: float) -> list[tuple[str, float, float, int]]:
    """The program's finished spans as (label, start, end, depth) on the
    trace's clock: a trace's own interval under its name at depth 2, its
    top-level spans at 3, their children at 4 (the benchmark's outer spans are
    depth 0, its dotted ones 1: `idle_gaps_inner`)."""
    out = []
    for root, spans in by_trace(rows):
        out.append((root["trace"], root["start"] + offset, root["end"] + offset, 2))
        out += [(s["span"], s["start"] + offset, s["end"] + offset,
                 3 if s["parent"] is None else 4) for s in spans]
    return out


def idle_gaps_inner(trace, placed) -> list[list]:
    """The idle time of `Trace.breakdown()`'s `idle_gaps`, attributed to the
    innermost span that covers it.  `<outer>/<inner>` is idle time inside the
    benchmark's outermost span <outer> and, within it, inside the program's or
    the benchmark's span <inner> and nothing deeper: `route/tokenize.encode`,
    `route/route.score` (the outside span's self time: inside it, in no span
    of the program), `publish_events/kvevents.flush`.  A bare `<outer>` is time
    in that span and no other; "in_program" and "other" are what they are in
    `idle_gaps`.  `placed`: the program's spans from `place`.  Where spans of
    one depth overlap (two threads), the one that began last takes the time."""
    starts = np.array([a for _, a, _ in trace.programs])
    ends = np.array([b for _, _, b in trace.programs])
    busy = _union([a for _, a, _ in trace.ops], [b for _, _, b in trace.ops])
    held = _union(np.concatenate((busy[0], starts)), np.concatenate((busy[1], ends)))

    def idle(cover, a, b):
        return (b - a) - float(_covered(*cover, b) - _covered(*cover, a))

    w = trace.window_s
    spans = [(n, a, b, 1 if "." in n else 0) for n, a, b in trace.host]
    spans += [(n, max(a, 0.0), min(b, w), d) for n, a, b, d in placed
              if b > 0.0 and a < w]
    edges = sorted({0.0, w, *(t for _, a, b, _ in spans for t in (a, b))})
    opening = sorted(range(len(spans)), key=lambda i: spans[i][1])
    gaps: dict[str, float] = defaultdict(float)
    active: list[int] = []
    k = 0
    for a, b in zip(edges, edges[1:]):
        while k < len(opening) and spans[opening[k]][1] <= a:
            active.append(opening[k])
            k += 1
        active = [i for i in active if spans[i][2] > a]
        gap = idle(held, a, b)
        if not active:
            gaps["other"] += gap
            continue
        inner = max(active, key=lambda i: (spans[i][3], spans[i][1]))
        outer = [spans[i][0] for i in active if spans[i][3] == 0]
        name = spans[inner][0]
        gaps[name if spans[inner][3] == 0 or not outer
             else f"{outer[-1]}/{name}"] += gap
    gaps["in_program"] = idle(busy, 0.0, w) - idle(held, 0.0, w)
    return [[key, v] for key, v in sorted(gaps.items(), key=lambda kv: -kv[1])]
