"""CPU tests of the readers of the program's own spans
(`harness/program_spans.py`) and of the script that drives them
(`tests/traced_run.py`): `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import glob
import json
import os
import shutil

import pytest

from benchmarks import run
from benchmarks.harness import program_spans, reduce
from benchmarks.tests import traced_run
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
NEW = {
    "router_tokenize_p50_s.ttft": ("tokenize", "ttft_p50_s"),
    "router_hash_blocks_p50_s.ttft": ("hash_blocks", "ttft_p50_s"),
    "router_index_lookup_p50_s": ("index_lookup", "ttft_p50_s"),
    "router_rank_p50_s": ("score", "ttft_p50_s"),
    "router_memo_block_share": ("hash_blocks", "ttft_p50_s"),
    "router_tokenize_p50_s.tput": ("tokenize", "tok_s"),
    "router_hash_blocks_p50_s.tput": ("hash_blocks", "tok_s"),
    "events_queue_wait_p50_s": ("kvevents.queue_wait", "tok_s"),
    "events_decode_p50_s": ("kvevents.decode", "tok_s"),
    "events_apply_p50_s": ("kvevents.apply", "tok_s"),
}


def trace_rows(tid, name, start, spans, end=None):
    """Export rows of one trace: spans as (name, start, end[, attrs[, parent]])."""
    ident = {"trace_id": tid, "trace": name}
    last = max([start] + [s[2] for s in spans]) if end is None else end
    rows = [dict(ident, span=None, parent=None, start=start, end=last,
                 status="ok", attrs={})]
    for s in spans:
        rows.append(dict(ident, span=s[0], parent=s[4] if len(s) > 4 else None,
                         start=s[1], end=s[2], status="ok",
                         attrs=s[3] if len(s) > 3 else {}))
    return rows


ROWS = (
    trace_rows("a", "indexer.score", 9.0, [("hash_blocks", 9.0, 9.5)])  # before
    + trace_rows("b", "indexer.score", 10.0, [
        ("tokenize", 10.0, 10.4), ("hash_blocks", 10.4, 10.5, {
            "block_keys": 32, "memo_blocks": 32}),
        ("index_lookup", 10.5, 10.6), ("hash_blocks", 10.6, 10.9, {
            "block_keys": 8, "memo_blocks": 0})])
    + trace_rows("c", "indexer.score", 11.0, [
        ("tokenize", 11.0, 11.1), ("hash_blocks", 11.1, 11.2, {
            "block_keys": 10, "memo_blocks": 0})])
    + trace_rows("d", "indexer.score", 12.0, [("tokenize", 12.0, 12.3)], end=12.5)
    + trace_rows("e", "kvevents.message", 12.6, [("hash_blocks", 12.6, 14.0)])
    + [dict(trace_id="f", trace="indexer.score", span=None, parent=None,
            start=12.7, end=None, status="in_flight", attrs={})]
)


def span_read(name, reduce_="p50", **more):
    return {"from": "program_span", "trace": "indexer.score", "name": name,
            "reduce": reduce_, **more}


def test_span_reader_sums_within_a_trace_before_the_percentile():
    # b: 0.1 + 0.3 in two chunks; c: 0.1; d ran no such stage and is left out;
    # a began before the window, e is another kind of trace, f never finished.
    read = program_spans.read
    assert read(span_read("hash_blocks", "p50"), ROWS, 10.0, 13.0) \
        == pytest.approx(0.1)
    assert read(span_read("hash_blocks", "p95"), ROWS, 10.0, 13.0) \
        == pytest.approx(0.4)
    assert read(span_read("hash_blocks", "mean"), ROWS, 9.0, 13.0) \
        == pytest.approx((0.5 + 0.4 + 0.1) / 3)
    assert read(span_read("tokenize", "sum"), ROWS, 10.0, 13.0) \
        == pytest.approx(0.4 + 0.1 + 0.3)
    assert read(span_read("score"), ROWS, 10.0, 13.0) is None
    assert read(span_read("hash_blocks"), [], 10.0, 13.0) is None


def test_span_reader_reads_a_traces_own_duration():
    read = {"from": "program_span", "trace": "indexer.score",
            "trace_duration": True, "reduce": "sum"}
    assert program_spans.read(read, ROWS, 10.0, 13.0) \
        == pytest.approx(0.9 + 0.2 + 0.5)


def test_attr_reader_is_the_ratio_of_the_summed_attributes():
    read = {"from": "program_attr", "trace": "indexer.score",
            "name": "hash_blocks", "num": "memo_blocks", "den": "block_keys"}
    assert program_spans.read(read, ROWS, 10.0, 13.0) == pytest.approx(32 / 50)
    assert program_spans.read({**read, "name": "tokenize"}, ROWS, 10.0, 13.0) is None
    with pytest.raises(ValueError):
        program_spans.read({"from": "span", "name": "x"}, ROWS, 10.0, 13.0)


def test_clock_offset_from_spans_recorded_on_both_clocks():
    """The host plane's clock runs 1234.5 s behind `perf_counter` here; every
    pair is a few microseconds apart, one is torn by 3 ms."""
    ours, theirs = [], []
    for i in range(40):
        for j, name in enumerate(("route", "dispatch", "route.score")):
            start = 5000.0 + i * 0.2 + j * 0.01
            late = 2e-6 + 1e-6 * ((i + j) % 5) + (3e-3 if (i, j) == (7, 1) else 0)
            ours.append((name, start, start + 0.005))
            theirs.append((name, start - 1234.5 + late, start - 1234.5 + 0.005))
    ours.append(("account", 4999.0, 4999.1))  # open before the profiler was on
    offset, spread, pairs = program_spans.clock_offset(ours, theirs[::-1])
    assert pairs == 120
    assert offset == pytest.approx(-1234.5 + 4e-6, abs=1.5e-6)
    assert 0 < spread < 5e-6 < program_spans.CLOCK_SPREAD_LIMIT_S
    placed = program_spans.place(ROWS, offset)
    assert ("indexer.score", pytest.approx(10.0 + offset),
            pytest.approx(10.9 + offset), 2) in placed
    assert all(d == 3 for n, _, _, d in placed if n == "tokenize")
    with pytest.raises(RuntimeError):
        program_spans.clock_offset(ours[:3], theirs[:2])


@pytest.mark.parametrize("name", ("docs-shared", "chat-sysprompt"))
def test_idle_gaps_inner_splits_the_same_idle_time(name):
    """On the two recorded v5e traces, with program spans made up inside the
    benchmark's `route.score` spans: the split sums to the idle time, and what
    lies under `route` is what `idle_gaps` gives `route`."""
    t = reduce.Trace(os.path.join(HERE, "data", name + ".xplane.pb"))
    outer = dict(t.breakdown()["idle_gaps"])
    rows = []
    for i, (n, a, b) in enumerate(r for r in t.host if r[0] == "route.score"):
        third = (b - a) / 3
        rows += trace_rows(str(i), "indexer.score", a + 0.01 * third, [
            ("tokenize", a + 0.02 * third, a + third),
            ("tokenize.encode", a + 0.1 * third, a + 0.9 * third, {}, "tokenize"),
            ("hash_blocks", a + third, a + 2 * third)], end=b - 0.01 * third)
    inner = dict(program_spans.idle_gaps_inner(t, program_spans.place(rows, 0.0)))
    assert sum(inner.values()) == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert sum(v for k, v in inner.items() if k.split("/")[0] == "route") \
        == pytest.approx(outer["route"], rel=1e-9)
    for key in set(outer) - {"route"}:
        assert inner[key] == pytest.approx(outer[key], rel=1e-9, abs=1e-12)
    assert {"route/tokenize.encode", "route/tokenize", "route/hash_blocks",
            "route/indexer.score", "route/route.score"} <= set(inner)
    assert inner["route/tokenize.encode"] > inner["route/tokenize"] > 0
    # With no program spans it is `idle_gaps`, the dotted spans named apart.
    bare = dict(program_spans.idle_gaps_inner(t, []))
    assert bare["route"] + bare["route/route.score"] \
        == pytest.approx(outer["route"], rel=1e-9)


def test_the_ten_metric_files_read_what_the_issue_names():
    for name, (span, moves) in NEW.items():
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == moves and spec["read"]["name"] == span
        assert spec["layer"] == ("event plane" if name.startswith("events_")
                                 else "router read path")
        share = name == "router_memo_block_share"
        assert spec["read"]["from"] == ("program_attr" if share else "program_span")
        assert spec["source"] == ("program_counter" if share else "program_span")
        assert (spec["unit"], spec["better"]) == (
            ("share", "higher") if share else ("s", "lower"))


@pytest.fixture
def tracer_restored():
    rate, ring = TRACER.config.sample_rate, TRACER.config.ring_size
    yield
    TRACER.configure(sample_rate=rate, ring_size=ring)
    TRACER.reset()


@pytest.mark.parametrize("cell", ("tiny-docs-shared", "tiny-docs-unique"))
def test_a_tiny_cell_prints_the_host_metrics_and_no_device_metric(
        cell, tmp_path, tracer_restored):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), root / "metrics")
    line = traced_run.traced_run(cell, 2**31 + 5, 1.0, 1.0, root=str(root),
                                 on_cpu=True)
    json.dumps(line)
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert not {"clock", "idle_gaps", "idle_gaps_inner"} & set(line)
    assert set(line["metrics"]) == set(NEW)
    assert all(v > 0 for n, v in line["metrics"].items()
               if n != "router_memo_block_share")
    assert 0 <= line["metrics"]["router_memo_block_share"] <= 1
    assert line["metrics"]["router_tokenize_p50_s.ttft"] \
        == line["metrics"]["router_tokenize_p50_s.tput"] \
        == line["spans_p50"]["indexer.score:tokenize"]
    checks = line["checks"]
    assert set(checks) == {"route.score", "publish_events"}
    for check in checks.values():
        assert check["spans_outside"] == 0 and check["traces"] > 0
        assert 0 < check["stage_sum_over_outside_max"] <= 1
    assert line["spans_p50"]["kvevents.message:kvevents.flush"] > 0
    assert not glob.glob(os.path.join(run.ROOT, ".bench_trace", cell, "**",
                                      "program_spans.jsonl"), recursive=True)
    off = traced_run.traced_run(cell, 2**31 + 5, 1.0, 0.0, root=str(root),
                                on_cpu=True)
    assert set(off) == {"cell", "seed", "rate", "correct", "device", "outside"}
    assert off["outside"]["route.score"] > 0
    half = traced_run.traced_run(cell, 2**31 + 5, 1.0, 0.5, root=str(root),
                                 on_cpu=True)
    for name, calls in half["split"].items():
        assert calls and all(v > 0 for v, _ in calls)
        assert sum(drawn for _, drawn in calls) == half["checks"].get(
            name, {"traces": 0})["traces"]


def test_overhead_is_paired_call_by_call_across_runs():
    """Three runs of the same four calls (a miss of 7 ms, hits of 1.5 ms); the
    tracer costs 50 us where it drew the call.  The groups' medians would say
    anything the mix says; the pairing says 50 us."""
    cost = [7e-3, 1.5e-3, 1.5e-3, 1.6e-3]
    drew = [(1, 0, 0, 1), (0, 1, 0, 0), (0, 0, 0, 1)]
    calls = [[[c + 50e-6 * d, bool(d)] for c, d in zip(cost, run)]
             for run in drew]
    out = traced_run.paired_overhead(calls)
    assert out["calls"] == 3 and out["runs"] == 3  # call 2 was never drawn
    assert out["diff_s"] == pytest.approx(50e-6)
    assert out["ratio"] == pytest.approx(1 + 50e-6 / 1.5e-3, rel=0.05)
    assert traced_run.paired_overhead([[[1.0, False]]]) is None
