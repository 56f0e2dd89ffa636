"""CPU tests of the family `lfm2moe` in the harness: found by name, with the
package's `Pod` and `jit_programs` (`models/pod.py`, a state group beside the
K/V group) through the files-only path, on a tiny configuration under
`tests/data/lfm2moe/`.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_lfm2moe
from llm_d_kv_cache_manager_tpu.models import lfm2moe
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "lfm2moe")
CFG = run.load(DATA, "configs", "tiny-lfm2moe")
CELL = "tiny-lfm2moe-agents"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-lfm2moe")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_lfm2moe
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, lfm2moe.Lfm2MoeConfig) and model.head_dim == 16
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.state is not None and p.window is None and p.protect_asked
               for p in fleet.pods)
    with pytest.raises(ValueError, match="conv_bias"):
        program.from_published({**CFG, "conv_bias": True}, engine.BLOCK)


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod through `closed_loop_chat`: hits (each resumed from a
    snapshot) and misses agree with the plain cache model, nothing compiles
    inside the window, and the traced run reads the new spans."""
    result = run.run_cell(CELL, 2**31 + 29, 1.5, trace, root=root, on_cpu=True)
    extra = result.pop("extra")
    assert result["correct"] and result["attempted"] > 0
    assert extra["numbers"]["accounting_mismatches"] == 0
    assert extra["counters"]["cached_tokens"] > 0  # hits were served
    specs = {n: run.load(root, "metrics", n)
             for n in run.load(root, "cells", CELL)["metrics"]}
    want = {n for n, s in specs.items() if ("layer" in s) == trace
            and s["read"]["from"] not in ("device", "roofline")}
    assert set(result["metrics"]) == want | (set() if trace else {"setup_s"})
    if trace:
        values = {n: m["value"] for n, m in result["metrics"].items()}
        assert values["state_resume_short_share"] == 0
        assert 0 < values["state_slot_share"] < 1
        assert 0 < values["moe_experts_touched_share.agents"] <= 1
        assert values["moe_expert_load_max_over_mean.agents"] >= 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_lfm2moe.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 300)
    whole = np.asarray(family_lfm2moe.forward_logits(weights, CFG, tokens, 300))
    head = np.asarray(family_lfm2moe.forward_logits(weights, CFG, tokens[:200], 8))
    np.testing.assert_allclose(head, whole[192:200], atol=2e-5)
    other = tokens.copy()
    other[:100] = 5  # far behind every convolution, but attention sees it
    moved = np.asarray(family_lfm2moe.forward_logits(weights, CFG, other, 1))
    assert np.abs(moved - whole[-1:]).max() > 1e-3


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations (float32 here, so nothing flips)."""
    import jax

    weights = family_lfm2moe.make_weights(CFG, 4)
    model = lfm2moe.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(lambda: lfm2moe.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_lfm2moe.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(lfm2moe.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    norms = [np.asarray(lp[k], np.float32) for lp in weights["layers"]
             for k in ("ln_op", "ln_ff", "q_norm", "k_norm", "conv_k") if k in lp]
    assert all(n.std() > 0.05 for n in norms)
    assert all(np.asarray(lp["route_bias"]).std() > 0.02
               for lp in weights["layers"][1:])


def test_counts_at_the_published_sizes():
    cfg = run.load(run.BENCH, "configs", "lfm2-8b-a1b-l13")
    assert family_lfm2moe.param_count(cfg) == 4_606_249_728  # 9.21 GB
    assert family_lfm2moe.kv_token_bytes(cfg) == 6 * 1024
    assert family_lfm2moe.kv_block_bytes(cfg, 16) == 96 * 1024
    assert family_lfm2moe.state_slot_bytes(cfg) == 80 * 1024
    model = lfm2moe.from_published(cfg, 16)
    groups = lfm2moe.cache_groups(model)  # the program's own, the same bytes
    assert groups["full"].block_nbytes == 96 * 1024
    assert groups["state"].block_nbytes == 80 * 1024
    flops = family_lfm2moe.prefill_attention_flops
    H, Dh, T = 32, 64, 4096
    assert flops(cfg, T) == 3 * 4 * H * Dh * (T * (T + 1) // 2)
    assert flops(cfg, T, T - 16) == 3 * 4 * H * Dh * sum(range(T - 15, T + 1))
    shapes = {"hit": (8192, 512)}
    assert family_lfm2moe.lfm2moe_flash_hit_prefill_min_s(
        cfg, shapes, {}, PEAK) == flops(cfg, 8704, 8192) / 197e12


@pytest.mark.parametrize("grow", ("decode_live_blocks", "decode_live_seqs"))
def test_costs_are_positive_and_grow_with_what_they_count(grow):
    cfg = run.load(run.BENCH, "configs", "lfm2-8b-a1b-l13")
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * (8 * 512 + 64 * 40)}
    more = {**counters, grow: 2 * counters[grow]}
    step = family_lfm2moe.lfm2moe_decode_step_min_s
    attn = family_lfm2moe.lfm2moe_paged_decode_attention_min_s
    assert 0.011 < step(cfg, {}, counters, PEAK) < 0.013  # weights 9.2 GB
    assert 0.0007 < attn(cfg, {}, counters, PEAK) < 0.0009
    assert step(cfg, {}, more, PEAK) > step(cfg, {}, counters, PEAK)
    assert (attn(cfg, {}, more, PEAK) > attn(cfg, {}, counters, PEAK)) == (
        grow == "decode_live_blocks")
