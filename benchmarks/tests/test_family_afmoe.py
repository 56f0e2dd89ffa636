"""CPU tests of the family `afmoe` in the harness: a family that brings its
own `Pod` and `jit_programs` (the package's, `models/pod.py`) through the
files-only path, on a tiny configuration under `tests/data/afmoe/`; and the
cases of `test_pod.py` that hold for any pod, against the package's.
`python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_afmoe
from benchmarks.tests import test_pod
from llm_d_kv_cache_manager_tpu.models import afmoe
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "afmoe")
CFG = run.load(DATA, "configs", "tiny-afmoe")
CELL = "tiny-afmoe-chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-afmoe")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_afmoe
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, afmoe.AfmoeConfig) and model.window == 32
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert [type(p) for p in fleet.pods] == [package_pod.Pod] * 2
    assert all(p.window is not None and p.protect_asked for p in fleet.pods)


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod through `closed_loop_chat`: hits and misses agree with the
    plain cache model, nothing compiles inside the window (the hit shape is
    first used there), and the traced run reads the new spans."""
    result = run.run_cell(CELL, 2**31 + 29, 1.5, trace, root=root, on_cpu=True)
    extra = result.pop("extra")
    assert result["correct"] and result["attempted"] > 0
    assert extra["counters"]["cached_tokens"] > 0  # hits were served
    specs = {n: run.load(root, "metrics", n)
             for n in run.load(root, "cells", CELL)["metrics"]}
    want = {n for n, s in specs.items() if ("layer" in s) == trace
            and s["read"]["from"] not in ("device", "roofline")}
    assert set(result["metrics"]) == want | (set() if trace else {"setup_s"})
    if trace:
        values = {n: m["value"] for n, m in result["metrics"].items()}
        assert values["window_half_hit_share"] == 0
        assert 0 < values["window_kv_read_share"] < 1
        assert 0 < values["moe_experts_touched_share"] <= 1
        assert values["moe_expert_load_max_over_mean"] >= 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_banded_and_padding_changes_nothing():
    weights = family_afmoe.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 300)
    whole = np.asarray(family_afmoe.forward_logits(weights, CFG, tokens, 300))
    head = np.asarray(family_afmoe.forward_logits(weights, CFG, tokens[:200], 8))
    np.testing.assert_allclose(head, whole[192:200], atol=2e-5)
    other = tokens.copy()
    other[:100] = 5  # outside every later window, but a full layer sees it
    moved = np.asarray(family_afmoe.forward_logits(weights, CFG, other, 1))
    assert np.abs(moved - whole[-1:]).max() > 1e-3


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations (float32 here, so nothing flips)."""
    import jax

    weights = family_afmoe.make_weights(CFG, 4)
    model = afmoe.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(lambda: afmoe.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_afmoe.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(afmoe.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    norms = [np.asarray(lp[k], np.float32) for lp in weights["layers"]
             for k in ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp",
                       "q_norm", "k_norm")]
    assert all(n.std() > 0.05 for n in norms)
    assert all(np.asarray(lp["route_bias"]).std() > 0.02
               for lp in weights["layers"][1:])


def test_counts_at_the_published_sizes():
    cfg = run.load(run.BENCH, "configs", "trinity-mini-l5")
    assert family_afmoe.param_count(cfg) == 4_241_534_720 + 0  # 8.48 GB
    assert family_afmoe.kv_token_bytes(cfg, "full") == 2048
    assert family_afmoe.kv_token_bytes(cfg, "window") == 4 * 2048
    assert family_afmoe.kv_block_bytes(cfg, 16) == 160 * 1024
    flops = family_afmoe.prefill_attention_flops
    H, Dh, T, W = 32, 128, 4096, 2048
    band = W * (W + 1) // 2 + (T - W) * W
    assert flops(cfg, T) == 4 * H * Dh * (T * (T + 1) // 2 + 4 * band)
    assert flops(cfg, T, T - 16) == 4 * H * Dh * (
        sum(range(T - 15, T + 1)) + 4 * 16 * W)
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * 64 * 815}
    shapes = {"hit": (12288, 512)}
    peak = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}
    step = family_afmoe.afmoe_decode_step_min_s(cfg, shapes, counters, peak)
    attn = family_afmoe.paged_decode_attention_min_s(cfg, shapes, counters, peak)
    assert 0.011 < step < 0.014 and 0.003 < attn < 0.004
    assert family_afmoe.flash_hit_prefill_min_s(cfg, shapes, counters, peak) == (
        flops(cfg, 12800, 12288) / 197e12)


@pytest.fixture
def packages_pod(monkeypatch):
    program = family.program(CFG)
    model = program.from_published(CFG, engine.BLOCK)
    monkeypatch.setattr(
        test_pod, "new_pod",
        lambda blocks, cfg=None: package_pod.Pod("p", program, model, blocks))


@pytest.mark.parametrize("case", (
    test_pod.test_alloc_never_hands_out_a_block_a_live_sequence_references,
    test_pod.test_least_recently_used_blocks_go_first_and_come_back_as_evicted,
    test_pod.test_cached_prefix_stops_at_the_first_hole),
    ids=lambda f: f.__name__)
def test_what_holds_for_any_pod_holds_for_the_packages(case, packages_pod):
    case()
