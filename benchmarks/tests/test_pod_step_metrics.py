"""CPU tests of the five metric files that read a `pod.step` trace's own parts
(`pod.launch.decode`, `pod.pack`, `pod.counts_read`; the package's
`models/pod.py`), through `harness/program_spans.py` and the two scripts that
print them (`tests/traced_run.py`, `tests/tracer_cost.py`), on the tiny cells
under `tests/data/`.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import os
import shutil

import pytest

from benchmarks import run
from benchmarks.harness import program_spans, reduce
from benchmarks.tests import traced_run, tracer_cost
from llm_d_kv_cache_manager_tpu.obs.trace import TRACER

HERE = os.path.dirname(os.path.abspath(__file__))
# name: (unit, better, source, reader, span, (num, den) of an attribute ratio)
FILES = {
    "pod_decode_launch_p50_s": ("s", "lower", "program_span", "program_span",
                                "pod.launch.decode", None),
    "pod_decode_pack_p50_s": ("s", "lower", "program_span", "program_span",
                              "pod.pack", None),
    "pod_counts_read_p50_s": ("s", "lower", "program_span", "program_span",
                              "pod.counts_read", None),
    "pod_table_resend_share": ("share", "lower", "program_counter",
                               "program_attr", "pod.pack",
                               ("table_sent", "calls")),
    "pod_decode_period_s": ("s", "lower", "program_span", "program_attr",
                            "pod.launch.decode",
                            ("since_prev_launch_s", "after_decode")),
}
CELLS = {"afmoe": "tiny-afmoe-chat", "lfm2moe": "tiny-lfm2moe-agents",
         "phi4flash": "tiny-phi4flash-reasoning"}
each_file = pytest.mark.parametrize("name", sorted(FILES))
# the check wants one request of the window's to compare: the slowest tiny cell
# admits three in 1.5 s on an idle CPU, and tier-1 runs beside five other workers
WINDOW_S = 4.0


def bench_dir(tmp_path_factory, data: str) -> str:
    """A benchmark directory of a tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp(f"bench-{data}")
    shutil.copytree(os.path.join(HERE, "data", data), path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


@pytest.fixture(scope="module")
def tracer_restored():
    rate, ring = TRACER.config.sample_rate, TRACER.config.ring_size
    yield
    TRACER.configure(sample_rate=rate, ring_size=ring)
    TRACER.reset()


@pytest.fixture(scope="module")
def pod_window(tmp_path_factory, tracer_restored):
    """(rows, t0, t1) of a traced window of the tiny `lfm2moe` cell."""
    root = bench_dir(tmp_path_factory, "lfm2moe")
    result = run.run_cell(CELLS["lfm2moe"], 2**31 + 37, WINDOW_S, True,
                          root=root, on_cpu=True)
    assert result["correct"]
    return (result["extra"]["rows"], *result["extra"]["window"])


@pytest.fixture(scope="module")
def llama_window(tmp_path_factory, tracer_restored):
    """The same of the tiny `llama` cell, whose pod is `harness/pod.py`."""
    root = bench_dir(tmp_path_factory, "tiny")
    result = run.run_cell("tiny-docs-shared", 2**31 + 37, 1.0, True, root=root,
                          on_cpu=True)
    return (result["extra"]["rows"], *result["extra"]["window"])


@each_file
def test_the_file_reads_what_the_issue_names(name):
    unit, better, source, reader, span, ratio = FILES[name]
    spec = run.load(run.BENCH, "metrics", name)
    assert (spec["unit"], spec["better"], spec["source"]) == (unit, better, source)
    assert (spec["layer"], spec["moves"]) == ("pod cache", "itl_p50_s")
    read = spec["read"]
    assert (read["from"], read["trace"], read["name"]) == (reader, "pod.step", span)
    if ratio:
        assert (read["num"], read["den"]) == ratio
    else:
        assert read["reduce"] == "p50"
    assert set(spec) == {"unit", "better", "source", "layer", "moves", "read"}


@each_file
def test_the_file_reads_its_number_from_a_tiny_pods_rows(name, pod_window):
    rows, t0, t1 = pod_window
    _, _, _, reader, span, ratio = FILES[name]
    value = program_spans.read(run.load(run.BENCH, "metrics", name)["read"],
                               rows, t0, t1)
    steps = [[s for s in spans if s["span"] == span]
             for root, spans in program_spans.by_trace(rows, t0, t1)
             if root["trace"] == "pod.step"]
    hit = [s[0] for s in steps if s]  # one such span a decode call, or none
    assert len(hit) > 3 and all(len(s) <= 1 for s in steps)
    if ratio:
        num, den = ratio
        assert value == pytest.approx(
            sum(s["attrs"].get(num, 0) for s in hit)
            / sum(s["attrs"][den] for s in hit))
    else:
        assert value == pytest.approx(reduce.reduce_values(
            [s["end"] - s["start"] for s in hit], "p50"))
    assert (0 < value <= 1) if name == "pod_table_resend_share" else value > 0


@each_file
def test_the_file_finds_nothing_to_read_on_the_llama_path(name, llama_window):
    rows, t0, t1 = llama_window
    assert {r["trace"] for r in rows} >= {"indexer.score"}
    assert program_spans.read(run.load(run.BENCH, "metrics", name)["read"],
                              rows, t0, t1) is None


@pytest.mark.parametrize("family", sorted(CELLS))
def test_traced_run_prints_the_five_in_a_pod_cell(family, tmp_path_factory,
                                                  tracer_restored):
    """`traced_run.py`'s line at rate 1.0 carries the five; the period is
    longer than the launch it is stamped on; set-up's compiles lie before the
    window."""
    line = traced_run.traced_run(CELLS[family], 2**31 + 41, WINDOW_S, 1.0,
                                 root=bench_dir(tmp_path_factory, family),
                                 on_cpu=True)
    assert line["correct"] and set(FILES) <= set(line["metrics"])
    assert line["spans_p50"]["pod.step:pod.launch.decode"] \
        == line["metrics"]["pod_decode_launch_p50_s"]
    assert line["metrics"]["pod_decode_period_s"] \
        > line["metrics"]["pod_decode_launch_p50_s"]
    assert "pod.step:pod.compile" not in line["spans_p50"]


def test_tracer_cost_pairs_an_untraced_window_on_and_off(tmp_path_factory,
                                                         tracer_restored):
    """`tracer_cost.py`: the profiler off on both sides; at rate 1.0 the line
    carries the five, at 0.0 there is nothing to read; runs pair up by seed."""
    root = bench_dir(tmp_path_factory, "lfm2moe")
    on, off = (tracer_cost.one_run(CELLS["lfm2moe"], 2**31 + 43, WINDOW_S, rate,
                                   root=root, on_cpu=True)
               for rate in (1.0, 0.0))
    assert on["correct"] and set(FILES) <= set(on["metrics"])
    assert "metrics" not in off and on["itl_p50_s"] > 0 < off["itl_p50_s"]
    ratio = on["itl_p50_s"] / off["itl_p50_s"]
    assert tracer_cost.ratios([on, off, {**on, "seed": 5}]) == {
        "pairs": 1, "on_over_off": [ratio], "median": ratio}
