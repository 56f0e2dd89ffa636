"""CPU tests of the family `keyevl2` (Keye-VL-2.0-30B-A3B's language model)
in the harness: the package's pod with its one group of the selected kind
through the files-only path, on a tiny configuration under
`tests/data/keyevl2/`; the plain reference; and the least-work counts at the
published sizes.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_keyevl2
from benchmarks.tests import test_pod
from llm_d_kv_cache_manager_tpu.models import keyevl2
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "keyevl2")
CFG = run.load(DATA, "configs", "tiny-keyevl2")
CELL = "tiny-keyevl2-longctx"
REAL = "keyevl2-chat-longctx"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-keyevl2")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_keyevl2
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, keyevl2.KeyeVl2Config)
    assert (model.index_heads, model.index_dim, model.index_topk) == (4, 8, 8)
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.groups == [] and p.protect_asked
               and p.specs["full"].selector_dim == 8
               and p.specs["full"].selected == 8
               and p.step_weight_nbytes == model.decode_weight_nbytes
               for p in fleet.pods)
    with pytest.raises(ValueError, match="use_sliding_window"):
        program.from_published({**CFG, "use_sliding_window": True},
                               engine.BLOCK)


def test_the_real_cell_is_found_with_files_only():
    cell = run.load(run.BENCH, "cells", REAL)
    cfg = run.load(run.BENCH, "configs", cell["config"])
    tiny = run.load(DATA, "cells", CELL)
    assert cell["metrics"] == tiny["metrics"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert family.reference(cfg) is family_keyevl2
    tr = run.load(run.BENCH, "traffic", cell["traffic"])
    # ISSUE 44's table: as many output lengths as slots, so that every seed
    # deals the same work
    assert sorted(tr.pop("output_lengths")) == (
        [64] * 4 + [96] * 3 + [128] * 4 + [160] * 2 + [192] * 3 + [256] * 4
        + [320] + [384] * 2 + [512])
    assert tr == {"kind": "closed_loop_chat", "pods": 1, "slots": 24,
                  "system_prompts": 24, "system_tokens": 32256,
                  "turn_tokens": 512, "pool_blocks": 51200, "check_sample": 3}
    # 24 contexts of 2016 blocks, 24 live suffixes of 64, and 1280 spare
    assert 24 * 2016 + 24 * 64 + 1280 == tr["pool_blocks"]
    for name in cell["metrics"]:
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == "itl_p50_s" if "layer" in spec else name == "itl_p50_s"
        cost = spec["read"].get("cost")
        assert cost is None or callable(getattr(family_keyevl2, cost))
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", ())}
    assert listed == set(cell["metrics"])
    entry = bench["workloads"][-1]
    assert (entry["name"], entry["chips"], entry["why"]) == (
        REAL, 1, cell["why"])


def test_the_configuration_keeps_every_published_number_but_the_depth():
    """The catalog's `config` of the model, key for key: only
    `num_hidden_layers` differs, and it is what `reduced` names."""
    cfg = run.load(run.BENCH, "configs", "keye-vl-2.0-30b-a3b-l4")
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=262144, max_window_layers=48,
        mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        num_local_experts=128, rms_norm_eps=1e-06,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        rope_theta=10000000,
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048},
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    assert {k for k, v in published.items() if cfg[k] != v} == {
        "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 4
    assert {"vision_tower", "mrope_section", "precision_of_I"} <= set(
        cfg["departs"])
    assert "twelve pipeline stages" in cfg["deployment"]
    model = keyevl2.from_published(cfg, engine.BLOCK)
    assert model.n_layers == 4 and model.index_topk == 2048


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod with a selected group through `closed_loop_chat`: hits and
    misses agree with the plain cache model, nothing compiles inside the
    window, and the traced run's readers find their spans."""
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
        # contexts of ~100 positions, 8 picked: (8 x 100 + 8 x 64) / (64 x 100)
        assert 0.1 < values["picked_read_share.longctx"] < 0.3
        assert 0 < values["moe_experts_touched_share.longctx"] <= 1
        assert values["moe_expert_load_max_over_mean.longctx"] >= 1
        # answers of 4-12 tokens: some calls go on from the last, never all
        assert 0 < values["decode_ahead_share.longctx"] < 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_keyevl2.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 300)
    whole = np.asarray(family_keyevl2.forward_logits(weights, CFG, tokens, 300))
    head = np.asarray(family_keyevl2.forward_logits(weights, CFG, tokens[:200], 8))
    np.testing.assert_allclose(head, whole[192:200], atol=2e-5)
    picks: list = []
    family_keyevl2.forward_logits(weights, CFG, tokens, 1, picks=picks)
    assert len(picks) == 3 and all(
        (p.sum(-1) == np.minimum(np.arange(300) + 1, 8)).all()
        and not np.triu(np.asarray(p), 1).any() for p in picks)


def test_counts_at_the_published_sizes():
    """ISSUE 44's arithmetic: 18.87 M of attention and 2.26 M of indexer a
    layer, 625.4 M a layer, 3.124 B = 6.25 GB in all; 2176 B a token a layer,
    139 264 B a block over four layers; a hit hidden-dense 275 GFLOP a layer
    and its scores 34."""
    cfg = run.load(run.BENCH, "configs", "keye-vl-2.0-30b-a3b-l4")
    fam = family_keyevl2
    c = fam.layer_counts(cfg)
    assert round(c["attention"] / 1e6, 2) == 18.87
    assert round(c["indexer"] / 1e6, 2) == 2.26
    assert round((c["attention"] + c["indexer"] + c["norms"] + c["router"]
                  + 128 * c["expert"]) / 1e6, 1) == 625.4
    assert round(fam.param_count(cfg) / 1e9, 3) == 3.124
    assert round(fam.param_bytes(cfg) / 1e9, 2) == 6.25
    assert fam.kv_token_bytes(cfg) + fam.selector_token_bytes(cfg) == 4 * 2176
    assert fam.kv_block_bytes(cfg, 16) == 139264
    model = keyevl2.from_published(cfg, engine.BLOCK)
    assert keyevl2.cache_groups(model)["full"].block_nbytes == 139264
    # the program's own count of what a decode step reads of the weights: all
    # but the embedding
    assert model.decode_weight_nbytes == (
        fam.param_count(cfg) - cfg["vocab_size"] * cfg["hidden_size"]) * 2
    T, P = 32768, 32256
    hit = fam.prefill_attention_flops(cfg, T, P) / 4
    assert 0.98 < hit / (512 * 32768 * 32 * 128 * 4) < 1.0  # 275 GFLOP, causal
    assert 0.98 < fam.index_flops(cfg, fam._pairs(T, P)) / 4 / 34.4e9 < 1.0
    shapes = {"hit": (P, 512), "max_blocks": 2080}
    counters = {"decode_steps": 10, "decode_live_seqs": 240,
                "decode_live_blocks": 10 * 24 * 2064}
    step = fam.keyevl2_decode_step_min_s(cfg, shapes, counters, PEAK)
    scores = fam.keyevl2_sparse_decode_scores_min_s(cfg, shapes, counters,
                                                    PEAK)
    # ISSUE 44: 5.4 GB a step by least bytes, of which selector keys 0.40 and
    # picked K/V 0.40
    assert 0.0064 < step < 0.0068
    # the scores kernel is priced by what it does alone: the keys once
    assert scores == 24 * 2064 * 16 * 512 / 819e9
    assert 0.00049 < scores < 0.00050
    # reading every position's K and V would be 6.5 GB more
    assert 6.4e9 < 24 * 2064 * 16 * fam.kv_token_bytes(cfg) < 6.6e9
    hit_s = fam.keyevl2_sparse_hit_prefill_min_s(cfg, shapes, counters, PEAK)
    assert 0.0061 < hit_s < 0.0063  # 4 x (275 + 34) GFLOP at the peak


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
