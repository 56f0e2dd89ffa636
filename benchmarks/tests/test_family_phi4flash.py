"""CPU tests of the family `phi4flash` in the harness: found by name, with the
package's `Pod` and `jit_programs` (`models/pod.py`, a window group and a
state group beside the K/V group) through the files-only path, on a tiny
configuration under `tests/data/phi4flash/`.  `python -m pytest
benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_phi4flash
from llm_d_kv_cache_manager_tpu.models import phi4flash
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "phi4flash")
CFG = run.load(DATA, "configs", "tiny-phi4flash")
CELL = "tiny-phi4flash-reasoning"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-phi4flash")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_phi4flash
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, phi4flash.Phi4FlashConfig) and model.head_dim == 16
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.groups == [p.window, p.state] and p.window.lazy
               and p.specs["full"].readers == 2 and p.protect_asked
               for p in fleet.pods)
    with pytest.raises(ValueError, match="mlp_bias"):
        program.from_published({**CFG, "mlp_bias": True}, engine.BLOCK)


def test_the_real_cell_is_found_with_files_only():
    cell = run.load(run.BENCH, "cells", "phi4flash-reasoning-longgen")
    cfg = run.load(run.BENCH, "configs", cell["config"])
    tiny = run.load(DATA, "cells", CELL)
    assert cell["metrics"] == tiny["metrics"] and cfg["reduced"] == {}
    assert family.reference(cfg) is family_phi4flash
    lengths = run.load(run.BENCH, "traffic", cell["traffic"])["output_lengths"]
    assert sorted(set(lengths)) == [3072, 4096, 5120] and len(lengths) == 64
    assert sum(lengths) == 64 * 4096
    for name in cell["metrics"]:
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == "itl_p50_s" if "layer" in spec else name == "itl_p50_s"
        cost = spec["read"].get("cost")
        assert cost is None or callable(getattr(family_phi4flash, cost))


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's three-group pod through `closed_loop_chat`: hits (each resumed
    from a snapshot and the window's blocks) and misses agree with the plain
    cache model, nothing compiles inside the window, and the traced run's
    readers find their spans."""
    result = run.run_cell(CELL, 2**31 + 29, 2.0, trace, root=root, on_cpu=True)
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
        assert values["state_resume_short_share.reasoning"] == 0
        assert values["window_half_hit_share.reasoning"] == 0
        assert 0 < values["state_slot_share.reasoning"] < 1
        assert 0 < values["window_kv_read_share.reasoning"] < 1
        assert 0 < values["state_kv_bytes_ratio.reasoning"]
        assert 0 < values["attention_read_share.reasoning"] < 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_phi4flash.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 600)
    whole = np.asarray(family_phi4flash.forward_logits(weights, CFG, tokens, 600))
    assert whole.shape == (600, CFG["vocab_size"])
    head = np.asarray(family_phi4flash.forward_logits(weights, CFG, tokens[:300], 8))
    np.testing.assert_allclose(head, whole[292:300], atol=2e-5)
    rows = np.asarray(family_phi4flash.forward_logits(weights, CFG, tokens, 300))
    np.testing.assert_allclose(rows, whole[300:], atol=2e-5)  # over two head calls
    other = tokens.copy()
    other[:100] = 5  # behind every window, but the full layer and the scan see it
    moved = np.asarray(family_phi4flash.forward_logits(weights, CFG, other, 1))
    assert np.abs(moved - whole[-1:]).max() > 1e-3


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations."""
    import jax

    weights = family_phi4flash.make_weights(CFG, 4)
    model = phi4flash.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(lambda: phi4flash.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_phi4flash.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(phi4flash.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    assert [family_phi4flash.lam0_of(l) for l in (1, 5, 7)] == pytest.approx(
        [float(model.lam0(l)) for l in (1, 5, 7)])
    mamba = weights["front"]["a"]
    a_log = np.asarray(mamba["a_log"])
    assert np.allclose(np.exp(a_log[0, :, 0]), np.arange(1, 5))  # A = -(1..N)
    dt = np.log1p(np.exp(np.asarray(mamba["b_dt"])))
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1 and np.all(
        np.asarray(mamba["d_skip"]) == 1)
    for part in (mamba["ln_in"], weights["mid"]["b"]["ln_post"], weights["ln_f"]):
        assert np.asarray(part["w"], np.float32).std() > 0.05
        assert np.asarray(part["b"], np.float32).std() > 0.05
    assert np.asarray(weights["back"]["b"]["bq"], np.float32).std() > 0.05


def test_counts_at_the_published_sizes():
    cfg = run.load(run.BENCH, "configs", "phi-4-mini-flash-reasoning")
    c = family_phi4flash.layer_counts(cfg)
    D, F, Di = 2560, 10240, 5120
    assert c["common"] == 3 * D * F + 4 * D  # a SwiGLU 78.6 M and two norms
    assert c["mamba"] == (2 * D * Di + 4 * Di + Di + Di * 192 + 160 * Di + Di
                          + 16 * Di + Di + Di * D)  # 41.2 M
    assert c["attention"] - c["cross"] == 2 * (D * 20 * 64 + 20 * 64)
    assert c["gmu"] == 2 * D * Di
    assert family_phi4flash.layers(cfg) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert family_phi4flash.full_readers(cfg) == 8
    count = family_phi4flash.param_count(cfg)
    assert count == (200064 * D + 2 * D + 32 * c["common"] + 9 * c["mamba"]
                     + 9 * c["attention"] + 7 * c["cross"] + 7 * c["gmu"])
    assert round(count / 1e9, 2) == 3.85  # 7.70 GB at bfloat16
    assert family_phi4flash.kv_token_bytes(cfg) == 5 * 1024
    assert family_phi4flash.kv_block_bytes(cfg, 16) == 80 * 1024
    assert family_phi4flash.window_block_bytes(cfg, 16) == 640 * 1024
    assert family_phi4flash.state_slot_bytes(cfg) == 9 * (
        3 * Di * 2 + Di * 16 * 4)  # 3.2 MB: forty blocks of the full group
    model = phi4flash.from_published(cfg, 16)
    groups = phi4flash.cache_groups(model)  # the program's own, the same bytes
    assert groups["full"].block_nbytes == 80 * 1024
    assert groups["full"].num_readers == 8
    assert groups["window"].block_nbytes == 640 * 1024
    assert groups["state"].block_nbytes == family_phi4flash.state_slot_bytes(cfg)
    # a hit prefill by hand: 512 queries at positions 1024 .. 1535, each over
    # all before it in the full layer and over 512 in each of eight layers
    pair = 6 * 40 * 64
    assert family_phi4flash.attention_pair_flops(cfg) == pair
    flops = family_phi4flash.prefill_attention_flops
    assert flops(cfg, 1536, 1024) == pair * (
        sum(range(1025, 1537)) + 8 * 512 * 512)
    assert flops(cfg, 256) == pair * 9 * (256 * 257 // 2)  # all within the window
    shapes = {"hit": (1024, 512)}
    assert family_phi4flash.phi4flash_flash_hit_prefill_min_s(
        cfg, shapes, {}, PEAK) == flops(cfg, 1536, 1024) / 197e12


def test_a_decode_steps_least_bytes_on_a_hand_counted_case():
    """64 sequences of 8 prompts of 64 blocks, 186 blocks of their own each:
    weights 7.70 GB; the full group's 512 + 64 x 186 distinct blocks of 80 KB
    eight times; 512 positions of 40 KB in the window layers a sequence; 3.2 MB
    of state read and written a sequence; 45 KB of new K/V a sequence."""
    cfg = run.load(run.BENCH, "configs", "phi-4-mini-flash-reasoning")
    blocks = 8 * 64 + 64 * 186
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * blocks}
    kv = blocks * 80 * 1024 * 8 + 64 * 512 * 8 * 5 * 1024
    assert family_phi4flash._decode_kv_bytes(cfg, counters) == kv
    attn = family_phi4flash.phi4flash_paged_decode_attention_min_s
    step = family_phi4flash.phi4flash_decode_step_min_s
    assert attn(cfg, {}, counters, PEAK) == kv / 819e9
    state = 64 * 2 * family_phi4flash.state_slot_bytes(cfg)
    want = (family_phi4flash.param_bytes(cfg) + kv + state
            + 64 * 9 * 5 * 1024) / 819e9
    assert step(cfg, {}, counters, PEAK) == pytest.approx(want, rel=1e-12)
    assert 0.021 < want < 0.024  # 7.7 + 8.1 + 1.3 + 0.4 GB over 819 GB/s


@pytest.mark.parametrize("grow", ("decode_live_blocks", "decode_live_seqs"))
def test_costs_grow_with_what_they_count(grow):
    cfg = run.load(run.BENCH, "configs", "phi-4-mini-flash-reasoning")
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * (8 * 64 + 64 * 186)}
    more = {**counters, grow: 2 * counters[grow]}
    for cost in (family_phi4flash.phi4flash_decode_step_min_s,
                 family_phi4flash.phi4flash_paged_decode_attention_min_s):
        assert cost(cfg, {}, more, PEAK) > cost(cfg, {}, counters, PEAK)
