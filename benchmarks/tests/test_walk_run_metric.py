"""CPU tests of the metric file `walk_run_block_share.repos` (PR 43): what the
paged decode kernel's walk brings by runs (a wave whose blocks lie one after
another in the pool, one copy) of the blocks it reads, from the attribute
`run_blocks` of the span `attention.read`, through `harness/program_spans.py`
and `tests/traced_run.py`, on the tiny cell under `tests/data/glm4moelite/`.
`python -m pytest benchmarks/tests`."""

from __future__ import annotations

import pytest

from benchmarks import run
from benchmarks.harness import program_spans
from benchmarks.tests import traced_run
from benchmarks.tests.test_pod_step_metrics import (  # noqa: F401
    WINDOW_S, bench_dir, llama_window, tracer_restored,
)
from llm_d_kv_cache_manager_tpu.models import glm4moelite

NAME = "walk_run_block_share.repos"
CELL = "tiny-glm4moelite-repos"


@pytest.fixture(scope="module")
def line(tmp_path_factory, tracer_restored):  # noqa: F811
    """`traced_run.py`'s line of the tiny cell at rate 1.0 with its rows, the
    walk at waves of two blocks: the family's 64 hold no table of 6 + 1
    blocks, and a run is a whole wave."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(glm4moelite, "DECODE_BLOCKS_PER_WAVE", 2)
        return traced_run.traced_run(
            CELL, 2**31 + 43, WINDOW_S, 1.0,
            root=bench_dir(tmp_path_factory, "glm4moelite"), on_cpu=True)


def test_the_file_reads_what_the_issue_names():
    spec = run.load(run.BENCH, "metrics", NAME)
    assert spec == {
        "unit": "share", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "itl_p50_s",
        "read": {"from": "program_attr", "trace": "pod.step",
                 "name": "attention.read", "num": "run_blocks",
                 "den": "read_blocks"}}


def test_the_traced_run_reads_it_from_the_pods_spans(line):
    """The allocator of a fresh pool deals ascending ids, so a tiny cell's
    prompts lie in runs: the share is above none and, with every last wave
    of one block loose, under all."""
    assert line["correct"] and 0 < line["metrics"][NAME] < 1
    assert line["metrics"]["attention_read_share.repos"] > 0


def test_the_file_finds_nothing_to_read_on_the_llama_path(llama_window):  # noqa: F811
    rows, t0, t1 = llama_window
    assert program_spans.read(run.load(run.BENCH, "metrics", NAME)["read"],
                              rows, t0, t1) is None
