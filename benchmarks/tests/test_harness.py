"""CPU tests of the harness at tiny sizes: `python -m pytest benchmarks/tests`.

They drive `run.run_cell`, the code path of `benchmarks/run.py`, with the look
for a chip skipped (`on_cpu`), on the cells under `tests/data/tiny/`.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import (
    check, costs, engine, family, family_llama, reduce, traffic,
)

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny")
CELLS = ("tiny-docs-shared", "tiny-docs-unique", "tiny-chat-sysprompt")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cells with the real metric files."""
    path = tmp_path_factory.mktemp("bench")
    shutil.copytree(TINY, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def load(root, kind, name):
    return run.load(root, kind, name)


@pytest.mark.parametrize("trace", (False, True))
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_prints_the_contract(root, cell, trace):
    result = run.run_cell(cell, 2**31 + 11, 1.0, trace, root=root, on_cpu=True)
    result.pop("extra")
    assert set(result) == KEYS and result["correct"] and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    specs = {n: load(root, "metrics", n) for n in load(root, "cells", cell)["metrics"]}
    want = {n for n, s in specs.items() if ("layer" in s) == trace
            and s["read"]["from"] not in ("device", "roofline")}
    assert set(result["metrics"]) == want | (set() if trace else {"setup_s"})
    assert all(m["value"] > 0
               or n in ("queue_wait_p50_s", "router_memo_block_share")
               for n, m in result["metrics"].items())
    assert list(result)[-1] == "checks" and all(
        c["value"] <= c["limit"] for c in result["checks"].values())
    json.dumps(result)


def test_main_refuses_a_machine_without_the_chip(root):
    with pytest.raises(SystemExit) as e:
        run.run_cell(CELLS[0], 1, 1.0, False, root=root)
    assert e.value.code not in (0, None)


def shape_of(tr, seed):
    if tr["kind"] == "paced_sessions":
        setup, window = traffic.paced_sessions(tr, 512, seed, 2.0)
        reqs = setup + window
    elif tr["kind"] == "backlog":
        reqs = traffic.backlog(tr, 512, seed, 0.05)
    else:
        clients = traffic.chat_clients(tr, 512, seed)
        reqs = [next(c) for _ in range(3) for c in clients]
        return (sorted((r["cls"], len(r["tokens"]), r["n_out"]) for r in reqs),
                [r["tokens"].tolist() for r in reqs])
    return ([(r["cls"], len(r["tokens"]), r["prefix_blocks"], r["due"])
             for r in reqs], [r["tokens"].tolist() for r in reqs])


@pytest.mark.parametrize("name", ("docs-shared", "docs-unique", "chat-sysprompt"))
def test_two_seeds_give_the_same_shape_and_other_tokens(name):
    tr = load(TINY, "traffic", name)
    a, b, again = shape_of(tr, 5), shape_of(tr, 2**31 + 6), shape_of(tr, 5)
    assert a[0] == b[0] and a[1] != b[1] and a == again


def test_every_reask_of_a_live_document_hits():
    """The schedule keeps its live documents in the pools whatever the seed,
    so the work of a run does not depend on it."""
    tr = load(run.BENCH, "traffic", "docs-shared")
    for seed in (1, 2, 3):
        setup, window = traffic.paced_sessions(
            {**tr, "doc_tokens": 32, "question_tokens": 16}, 64, seed, 40.0)
        log, rr, home = [], 0, {}
        blocks = tr["doc_tokens"] // 16  # the real documents' size in blocks
        for r in setup + window:
            if r["cls"] == "new":
                home[r["doc"]], rr = f"pod-{rr % tr['pods']}", rr + 1
            doc = [hash(("d", r["doc"], i)) for i in range(blocks)]
            ask = [hash(("q", len(log), i))
                   for i in range(tr["question_tokens"] // 16)]
            log.append(dict(pod=home[r["doc"]], hashes=doc + ask,
                            prefix_blocks=blocks, hit=r["cls"] == "reask",
                            cached_blocks=blocks * (r["cls"] == "reask"),
                            evicted=None, in_window=True))
        assert check.against_cache_model(log, tr["pool_blocks"]) == {
            "accounting_mismatches": 0}


def test_cache_model_catches_a_wrong_hit():
    log = [dict(pod="p", hashes=[1, 2, 3], prefix_blocks=2, hit=False,
                cached_blocks=0, evicted=0, in_window=True),
           dict(pod="p", hashes=[1, 2, 4], prefix_blocks=2, hit=True,
                cached_blocks=2, evicted=0, in_window=True)]
    assert check.against_cache_model(log, 8)["accounting_mismatches"] == 0
    log[1].update(hit=False, cached_blocks=0)
    assert check.against_cache_model(log, 8)["accounting_mismatches"] == 1
    log[1].update(hit=True, cached_blocks=2, evicted=1)
    assert check.against_cache_model(log, 8)["accounting_mismatches"] == 1


@pytest.mark.parametrize("cell", ("tiny-docs-shared", "tiny-chat-sysprompt"))
def test_float8_control_fails_and_the_program_passes(root, cell, capsys):
    result = run.run_cell(cell, 3, 0.5, False, root=root, on_cpu=True,
                          control=True)
    limits = load(root, "cells", cell)["limits"]
    assert result["correct"]
    assert not check.verdict(result["extra"]["control"], limits)
    assert "FAILED" in capsys.readouterr().err


def test_an_altered_token_makes_the_run_incorrect(root, monkeypatch):
    sound = engine.Fleet.prefill

    def broken(self, *args):
        token, top, row = sound(self, *args)
        return (token + 1) % 512, top, row

    monkeypatch.setattr(engine.Fleet, "prefill", broken)
    result = run.run_cell(CELLS[1], 4, 0.5, False, root=root, on_cpu=True)
    assert result["correct"] is False
    assert result["extra"]["numbers"]["token_gap_max"] > 0.1


def test_reference_is_causal_and_padding_changes_nothing():
    cfg = load(TINY, "configs", "tiny")
    w = family_llama.make_weights(cfg, 1)
    ids = np.arange(1, 301) % 500 + 1
    full = np.asarray(family_llama.forward_logits(w, cfg, ids, 300))
    part = np.asarray(family_llama.forward_logits(w, cfg, ids[:200], 1))
    np.testing.assert_allclose(full[199], part[0], rtol=1e-4, atol=1e-5)


def test_unknown_device_has_no_peaks():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")


def test_percentile_is_nearest_rank():
    values = list(range(1, 201))
    assert reduce.percentile(values, 50) == 100
    assert reduce.percentile(values, 95) == 190
    assert reduce.reduce_values([], "p50") is None


def test_a_cell_is_added_with_files_only(root, tmp_path, second_family):
    """A new configuration of a second family, a traffic mix of an existing
    kind, a per-layer metric over an existing span, one over a span of the
    program's own and a roofline with a cost function of the family's: one new
    file each, plus the cell's, and no edit to a file that is there.  The
    family's two modules are `tests/data/two/*.py`, found beside the harness's
    own (`second_family`, conftest.py)."""
    new = tmp_path / "bench"
    shutil.copytree(root, new)
    cfg = {**load(root, "configs", "tiny"), "num_hidden_layers": 1,
           "family": "two"}
    tr = {**load(root, "traffic", "chat-sysprompt"), "turn_tokens": 48}
    layer = {"unit": "s", "better": "lower", "source": "program_span",
             "moves": "itl_p50_s"}
    account = {**layer, "layer": "pod engine",
               "read": {"from": "span", "name": "account", "reduce": "p95"}}
    bookkeeping = {**layer, "layer": "router read path", "read": {
        "from": "program_span", "trace": "indexer.score", "name": "bookkeeping",
        "reduce": "p50"}}
    roofline = {"unit": "%", "better": "higher", "source": "device_trace",
                "layer": "model step", "moves": "itl_p50_s", "read": {
                    "from": "roofline", "program": "miss_prefill_T\\d+",
                    "cost": "miss_prefill_min_s"}}
    cell = {**load(root, "cells", "tiny-chat-sysprompt"), "config": "one-layer",
            "traffic": "short-chat",
            "metrics": ["itl_p50_s", "account_p95_s", "router_bookkeeping_p50_s",
                        "miss_prefill_roofline"]}
    for kind, name, body in (("configs", "one-layer", cfg),
                             ("traffic", "short-chat", tr),
                             ("metrics", "account_p95_s", account),
                             ("metrics", "router_bookkeeping_p50_s", bookkeeping),
                             ("metrics", "miss_prefill_roofline", roofline),
                             ("cells", "one-layer-short", cell)):
        (new / kind / f"{name}.json").write_text(json.dumps(body))
    result = run.run_cell("one-layer-short", 8, 0.5, True, root=str(new),
                          on_cpu=True)
    # hit and miss prefills and decode steps all ran on the two-leaf pool
    assert result["correct"] and result["extra"]["counters"]["decode_steps"] > 0
    assert set(result["metrics"]) == {"account_p95_s", "router_bookkeeping_p50_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    # the roofline needs a device trace: on the recorded one, its cost function
    # is found in the family's module
    env = {"cfg": cfg, "shapes": {"miss": (8448,)},
           "peaks": costs.peaks("TPU v5 lite")}
    share = reduce.read_metric(roofline["read"], engine.Records(),
                               fixture("docs-shared"), 1.2, env)
    assert share == pytest.approx(
        100 * 2 * 8448 * (64 * 16 * 12 + 3 * 64 * 128) / 197e12
        / ((0.27577677 + 0.262382062) / 2), rel=1e-6)


def test_a_configuration_names_its_family():
    cfg = load(TINY, "configs", "tiny")
    assert family.reference(cfg) is family_llama
    assert all(hasattr(family.program(cfg), n) for n in family.NAMES["program"])
    for broken in ({k: v for k, v in cfg.items() if k != "family"},
                   {**cfg, "family": "no-such"}):
        with pytest.raises(KeyError):
            family.reference(broken)
        with pytest.raises(KeyError):
            family.program(broken)


def test_manifest_agrees_with_the_files():
    with open(os.path.join(os.path.dirname(run.BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    cells = {w["name"]: load(run.BENCH, "cells", w["name"])
             for w in manifest["workloads"]}
    for w in manifest["workloads"]:
        cell = cells[w["name"]]
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        load(run.BENCH, "traffic", cell["traffic"])
    for c in manifest["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        cfg = load(run.BENCH, "configs", c["name"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert family.reference(cfg) and family.program(cfg)  # both sides whole
    for name, m in metrics.items():
        if name == "setup_s":
            continue
        spec = load(run.BENCH, "metrics", name)
        assert all(spec[k] == m[k] for k in spec if k != "read"), name
        assert set(m["workloads"]) == {n for n, c in cells.items()
                                       if name in c["metrics"]}, name
    assert {n for c in cells.values() for n in c["metrics"]} <= set(metrics)


def fixture(name):
    return reduce.Trace(os.path.join(HERE, "data", name + ".xplane.pb"))


def test_trace_reduction_on_a_recorded_docs_shared_trace():
    """The first 1.2 s of a traced `mistral7b-docs-shared` window on a TPU
    v5e (PR 24), cut down by tests/trim_trace.py."""
    t = fixture("docs-shared")
    assert t.window_s == pytest.approx(1.2)
    assert t.busy_s == pytest.approx(0.679467148, rel=1e-6)
    assert t.program_times(r"miss_prefill_T\d+") == pytest.approx(
        [0.27577677, 0.262382062], rel=1e-6)
    assert t.program_times(r"hit_prefill_P\d+_S\d+") == pytest.approx(
        [0.035715503, 0.035714062, 0.035722586, 0.035719181], rel=1e-6)
    assert t.program_times(r"decode_B\d+") == []
    assert t.op_time_per_program(r"miss_prefill_T\d+", "flash_gqa_attention_pallas") \
        == pytest.approx([0.061737173, 0.061973202], rel=1e-6)
    b = t.breakdown()
    assert b["device_ops"][0] == [
        "miss_prefill_T8448:flash_gqa_attention_pallas.7",
        pytest.approx(0.123710375, rel=1e-6)]
    gaps = dict(b["idle_gaps"])
    assert gaps["wait_for_arrival"] == pytest.approx(0.453189304, rel=1e-6)
    assert gaps["route"] == pytest.approx(0.02786287, rel=1e-6)
    assert gaps["in_program"] == pytest.approx(0.001563016, rel=1e-4)
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s, rel=1e-9)


def test_trace_reduction_on_a_recorded_chat_trace():
    t = fixture("chat-sysprompt")
    assert t.busy_s == pytest.approx(0.481982283, rel=1e-6)
    assert t.program_times(r"decode_B\d+")[:2] == pytest.approx(
        [0.149893398, 0.149903507], rel=1e-6)
    assert t.program_times(r"hit_prefill_P\d+_S\d+") == pytest.approx(
        [0.069007577], rel=1e-6)
    gaps = dict(t.breakdown()["idle_gaps"])
    assert max(gaps, key=gaps.get) == "readback"


def test_roofline_reader_divides_least_time_by_measured():
    t = fixture("docs-shared")
    cfg = load(run.BENCH, "configs", "mistral-7b-v0.3-l8")
    env = {"cfg": cfg, "shapes": {"miss": (8448,)},
           "peaks": costs.peaks("TPU v5 lite")}
    spec = load(run.BENCH, "metrics", "flash_prefill_roofline")["read"]
    share = reduce.read_metric(spec, engine.Records(), t, 1.2, env)
    least = 8 * 2 * 8448**2 * 32 * 128 / 197e12
    assert share == pytest.approx(100 * least / 0.0618551875, rel=1e-6)
    assert 0 < share < 100


def test_reading_the_thread_entries_needs_no_proc(monkeypatch):
    run.read_thread_entries()  # whatever this host's /proc holds

    def gone(path):
        raise FileNotFoundError(path)
    monkeypatch.setattr(run.os, "listdir", gone)
    run.read_thread_entries()
