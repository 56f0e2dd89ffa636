"""CPU tests of the family `deepseekv32` (DeepSeek-V3.2-Exp) in the harness:
the package's pod with its one group of the latent-selected kind through the
files-only path, on a tiny configuration under `tests/data/deepseekv32/` that
holds experts 2-5 of the 8 its router scores; the plain reference; and the
least-work counts at the published sizes.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_deepseekv32
from benchmarks.tests import test_pod
from llm_d_kv_cache_manager_tpu.models import deepseekv32
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "deepseekv32")
CFG = run.load(DATA, "configs", "tiny-deepseekv32")
CELL = "tiny-deepseekv32-longshared"
REAL = "deepseekv32-chat-longctx-shared"
REAL_CONFIG = "deepseek-v3.2-exp-l5"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-deepseekv32")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_deepseekv32
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, deepseekv32.DeepseekV32Config)
    assert (model.index_heads, model.index_dim, model.index_topk) == (4, 16, 8)
    assert (model.n_experts, model.held, model.experts_held) == (8, (2, 4), 4)
    assert (model.n_group, model.topk_group) == (4, 2)
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.groups == [] and p.protect_asked and p.decode_ahead
               and p.specs["full"].layout == "latent_selected"
               and p.specs["full"].selector_dim == 16
               and p.specs["full"].selected == 8
               and p.step_weight_nbytes == model.decode_weight_nbytes
               for p in fleet.pods)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        program.from_published({**CFG, "num_nextn_predict_layers": 1},
                               engine.BLOCK)


def test_the_real_cell_is_found_with_files_only():
    cell = run.load(run.BENCH, "cells", REAL)
    cfg = run.load(run.BENCH, "configs", cell["config"])
    tiny = run.load(DATA, "cells", CELL)
    assert cell["config"] == REAL_CONFIG and cell["metrics"] == tiny["metrics"]
    assert family.reference(cfg) is family_deepseekv32
    tr = run.load(run.BENCH, "traffic", cell["traffic"])
    # ISSUE 53: the multiset of chat-repos' first 32 (mean 192), as many as
    # slots, so that every seed deals the same work
    lengths = tr.pop("output_lengths")
    repos = run.load(run.BENCH, "traffic", "chat-repos")["output_lengths"]
    assert sorted(lengths) == sorted(repos[:32]) and sum(lengths) == 32 * 192
    assert tr == {"kind": "closed_loop_chat", "pods": 1, "slots": 32,
                  "system_prompts": 8, "system_tokens": 32256,
                  "turn_tokens": 512, "pool_blocks": 20480, "check_sample": 3}
    # 8 contexts of 2016 blocks, 32 live suffixes of 64, and 2304 spare
    assert 8 * 2016 + 32 * 64 + 2304 == tr["pool_blocks"]
    for name in cell["metrics"]:
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == "itl_p50_s" if "layer" in spec else name == "itl_p50_s"
        cost = spec["read"].get("cost")
        assert cost is None or callable(getattr(family_deepseekv32, cost))
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", ())}
    assert listed == set(cell["metrics"])
    (entry,) = [w for w in bench["workloads"] if w["name"] == REAL]
    assert (entry["config"], entry["traffic"], entry["chips"],
            entry["why"]) == (REAL_CONFIG, "chat-longctx-shared", 1,
                              cell["why"])
    assert len(cell["why"]) <= 200


def test_the_configuration_file_states_its_cut_and_its_source():
    """Every number of the catalog's row under its key, but for the keys
    `reduced` lists; no width among those; the published counts, the held
    share and the deployment beside them."""
    cfg = run.load(run.BENCH, "configs", REAL_CONFIG)
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=3,
        hidden_act="silu", hidden_size=7168, index_head_dim=128,
        index_n_heads=64, index_topk=2048, intermediate_size=18432,
        kv_lora_rank=512, max_position_embeddings=163840,
        model_type="deepseek_v32", moe_intermediate_size=2048,
        moe_layer_freq=1, n_group=8, n_routed_experts=256,
        n_shared_experts=1, norm_topk_prob=True, num_attention_heads=128,
        num_experts_per_tok=8, num_hidden_layers=61, num_key_value_heads=128,
        num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
        qk_rope_head_dim=64, rms_norm_eps=1e-06,
        rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                      "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096,
                      "type": "yarn"},
        rope_theta=10000, routed_scaling_factor=2.5, scoring_func="sigmoid",
        tie_word_embeddings=False, topk_group=4, topk_method="noaux_tc",
        v_head_dim=128, vocab_size=129280)
    reduced = {"num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert {k for k, v in published.items() if cfg[k] != v} == reduced
    assert set(cfg["reduced"]) == reduced
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    assert cfg["source"].endswith("DeepSeek-V3.2-Exp/blob/main/config.json")
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (
                5, 1, 16160, 0)
    assert cfg["published"] == {k: published[k] for k in reduced}
    assert cfg["held"] == {"experts_first": 0}
    assert 256 // cfg["n_routed_experts"] in (16, 32)  # chips a layer
    assert str(256 // cfg["n_routed_experts"]) in cfg["deployment"]
    assert {"yarn", "score_scale", "indexer", "selection", "cache_slot",
            "group_limited_routing", "rope_pairing"} <= set(cfg["assumed"])
    assert {"num_nextn_predict_layers", "float8", "selector_key_bfloat16",
            "latent_space_attention", "index_precision"} <= set(cfg["departs"])
    model = deepseekv32.from_published(cfg, engine.BLOCK)
    assert (model.n_layers, model.n_dense_layers, model.n_experts) == (5, 1, 256)
    assert model.held == (0, cfg["n_routed_experts"])
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    (entry,) = [c for c in bench["configs"] if c["name"] == REAL_CONFIG]
    assert set(entry["reduced"]) == reduced and entry["source"] == cfg["source"]


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod with a latent-selected group through `closed_loop_chat`,
    two clients a context: hits and misses agree with the plain cache model,
    nothing compiles inside the window, and the traced run's readers find
    their spans, the held picks among them."""
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
        # contexts of ~100 positions, 8 picked: (8 x 40 + 100 x 16) / (100 x 40)
        assert 0.4 < values["picked_read_share.longshared"] < 0.6
        assert 0 < values["moe_experts_touched_share.longshared"] <= 1
        assert values["moe_expert_load_max_over_mean.longshared"] >= 1
        assert 0 < values["moe_held_pick_share.longshared"] < 1
        # answers of 4-12 tokens: some calls go on from the last, never all
        assert 0 < values["decode_ahead_share.longshared"] < 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_deepseekv32.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 300)
    fam = family_deepseekv32
    whole = np.asarray(fam.forward_logits(weights, CFG, tokens, 300))
    head = np.asarray(fam.forward_logits(weights, CFG, tokens[:200], 8))
    np.testing.assert_allclose(head, whole[192:200], atol=2e-5)
    picks: list = []
    fam.forward_logits(weights, CFG, tokens, 1, picks=picks)
    assert len(picks) == 3 and all(
        (p.sum(-1) == np.minimum(np.arange(300) + 1, 8)).all()
        and not np.triu(np.asarray(p), 1).any() for p in picks)


def test_runs_and_last_blocks_change_no_logit(monkeypatch):
    """What makes the reference cheaper is no other computation: query blocks
    in runs against the keys up to a run's end, and the last layer for the
    last blocks alone, give the logits and the picked sets of one run over
    every key and every row."""
    import jax

    fam = family_deepseekv32
    weights = fam.make_weights(CFG, 6)
    tokens = np.random.default_rng(6).integers(1, CFG["vocab_size"], 1300)

    def logits(runs, n_last, picks=None):
        monkeypatch.setattr(fam, "RUNS", runs)  # read as it is traced
        monkeypatch.setattr(fam, "_attention", jax.jit(
            fam._attention.__wrapped__,
            static_argnames=("z", "quant", "first", "picks")))
        return np.asarray(fam.forward_logits(weights, CFG, tokens, n_last,
                                             picks=picks))

    whole_picks: list = []
    whole = logits(1, 1300, whole_picks)
    for runs, n_last in ((2, 300), (3, 1300)):
        np.testing.assert_allclose(logits(runs, n_last),
                                   whole[1300 - n_last:], atol=2e-5)
    picks: list = []
    logits(2, 1, picks)
    assert all((a == b).all() for a, b in zip(picks, whole_picks))
    monkeypatch.setattr(fam, "RUNS", 5)  # as the file has it
    assert [fam._runs(0, 6), fam._runs(3, 6), fam._runs(127, 130)] == [
        [(0, 3), (3, 6)], [(3, 6)], [(127, 130)]]
    assert fam._runs(0, 130) == [(0, 26), (26, 52), (52, 78), (78, 104),
                                 (104, 130)]


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations, both handed the same share of the experts."""
    import jax

    weights = family_deepseekv32.make_weights(CFG, 4)
    model = deepseekv32.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(
        lambda: deepseekv32.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    experts = weights["layers"][1]["experts"]
    assert experts["w_up"].shape[0] == 4 and "mlp" in weights["layers"][0]
    assert weights["layers"][1]["router"].shape == (64, 8)  # scores all eight
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_deepseekv32.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(deepseekv32.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    # the share is part of the result: with other ids the same weights give
    # other logits
    other = {**CFG, "held": {"experts_first": 4}}
    moved = np.asarray(family_deepseekv32.forward_logits(weights, other, tokens, 80))
    assert np.abs(moved - mine).max() > 1e-3
    for part in (weights["layers"][0]["ln_in"], weights["layers"][1]["ki_bias"],
                 weights["layers"][2]["kv_norm"], weights["ln_f"]):
        assert np.asarray(part, np.float32).std() > 0.05
    assert np.asarray(weights["layers"][1]["route_bias"]).std() > 0.02
    w_w = np.asarray(weights["layers"][0]["w_w"])
    assert (w_w > 0).any() and (w_w < 0).any()


def test_counts_at_the_published_sizes():
    """ISSUE 53's arithmetic: attention 187.1 M and the indexer 14.0 M a
    layer, a dense layer 597.4 M, an expert layer 201.1 + 1.8 + 44.0 + 16 x
    44.04 = 951.6 M, embedding and head 231.7 M: 4.635 B = 9.27 GB; the cache
    1408 B a token a layer, 112 640 B a block."""
    cfg = run.load(run.BENCH, "configs", REAL_CONFIG)
    fam = family_deepseekv32
    c = fam.layer_counts(cfg)
    held = cfg["n_routed_experts"]
    assert round(c["attention"] / 1e6, 1) == 187.1
    assert round(c["indexer"] / 1e6, 1) == 14.0
    every = c["attention"] + c["indexer"] + c["norms"]
    assert round((every + c["dense"]) / 1e6, 1) == 597.4
    assert c["expert"] == c["shared"] == 3 * 7168 * 2048
    assert c["router"] == 7168 * 256 + 256  # over all the published experts
    layer = every + c["router"] + c["shared"] + held * c["expert"]
    count = fam.param_count(cfg)
    assert count == (2 * 16160 * 7168 + 7168 + every + c["dense"] + 4 * layer)
    if held == 16:
        assert round(layer / 1e6, 1) == 951.6
        assert round(count / 1e9, 3) == 4.636
        assert round(fam.param_bytes(cfg) / 1e9, 2) == 9.27
    assert str(round(count / 1e9, 3))[:4] in cfg["deployment"]
    assert fam.latent_token_bytes(cfg) == 5 * 1152
    assert fam.selector_token_bytes(cfg) == 5 * 256
    assert fam.kv_block_bytes(cfg, 16) == 112640 == 16 * 5 * 1408
    model = deepseekv32.from_published(cfg, engine.BLOCK)
    assert deepseekv32.cache_groups(model)["full"].block_nbytes == 112640
    # the program's own count of what a decode step reads of the weights: all
    # but the embedding (the selection bias is float32: 4 x 256 x 2 B more)
    assert model.decode_weight_nbytes == (
        count - cfg["vocab_size"] * cfg["hidden_size"]) * 2 + 4 * 256 * 2
    # a hit by hand: 512 queries of 2048 picks each, 128 heads, 320 lanes of
    # scores and values in the per-head form, 5 layers
    T, P = 32768, 32256
    assert fam._picked_pairs(cfg, T, P) == 512 * 2048
    assert fam._picked_pairs(cfg, 4096) == 2049 * 2048 + sum(range(1, 2048))
    assert fam._picked_pairs(cfg, 100, 20) == sum(range(21, 101))
    assert fam.prefill_attention_flops(cfg, T, P) == (
        5 * 2 * 128 * 320 * 512 * 2048)
    assert fam.index_flops(cfg, 10) == 5 * 2 * 64 * 128 * 10
    shapes = {"hit": (P, 512), "max_blocks": 2080}
    hit_s = fam.deepseekv32_sparse_latent_hit_prefill_min_s(cfg, shapes, {},
                                                            PEAK)
    assert hit_s == 5 * 2 * 128 * 320 * 512 * 2048 / 197e12  # 2.2 ms
    assert hit_s > 32768 * 5 * 1152 / 819e9  # compute-bound: 0.23 ms of bytes


def test_a_decode_steps_least_bytes_on_a_hand_counted_case():
    """32 sequences in fours over 8 contexts of 2016 blocks, 48 blocks of
    their own each: every weight outside the routed experts (the embedding a
    row a sequence); of each expert layer's 16 held experts the 16 (1 - (1 -
    8/256)^32) = 10.2 that 32 sequences touch; the selector keys of the
    distinct live positions; 2048 latents a sequence; the new slots."""
    cfg = run.load(run.BENCH, "configs", REAL_CONFIG)
    fam = family_deepseekv32
    c = fam.layer_counts(cfg)
    held = cfg["n_routed_experts"]
    blocks = 8 * 2016 + 32 * 48
    counters = {"decode_steps": 10, "decode_live_seqs": 320,
                "decode_live_blocks": 10 * blocks}
    shapes = {"hit": (32256, 512), "max_blocks": 2080}
    touched = held * (1 - (1 - 8 / 256) ** 32)
    if held == 16:
        assert 10.1 < touched < 10.3
    every = c["attention"] + c["indexer"] + c["norms"]
    other = 2 * (16160 * 7168 + 7168 + 5 * every + c["dense"]
                 + 4 * (c["shared"] + c["router"]))
    experts = 2 * 4 * touched * c["expert"]
    keys = blocks * 16 * 5 * 256
    latents = 32 * 2048 * 5 * 1152
    want = (other + experts + 32 * 7168 * 2 + keys + latents
            + 32 * 5 * 1408) / 819e9
    got = fam.deepseekv32_decode_step_min_s(cfg, shapes, counters, PEAK)
    assert got == pytest.approx(want, rel=1e-12)
    if held == 16:
        assert round(other / 1e9, 2) == 3.40 and round(experts / 1e9, 2) == 3.60
        assert 0.0093 < want < 0.0096  # 3.40 + 3.60 + 0.36 + 0.38 GB
    # the selection path by least bytes: keys and picked latents
    assert 0.05 < (keys + latents) / (want * 819e9) < 0.12
    # the scores kernel is priced by what it does alone: the distinct keys
    # once, or every sequence's products over its own context
    scores = fam.deepseekv32_latent_index_scores_min_s(cfg, shapes, counters,
                                                       PEAK)
    assert scores == max(keys / 819e9,
                         5 * 2 * 64 * 128 * 32 * 32256 / 197e12)
    assert 0.0004 < scores < 0.0005
    # reading every live sequence's every latent would be 6.1 GB
    assert 6.0e9 < 32 * 33024 * 5 * 1152 < 6.2e9


@pytest.mark.parametrize("grow", ("decode_live_blocks", "decode_live_seqs"))
def test_costs_grow_with_what_they_count(grow):
    cfg = run.load(run.BENCH, "configs", REAL_CONFIG)
    fam = family_deepseekv32
    shapes = {"hit": (32256, 512), "max_blocks": 2080}
    counters = {"decode_steps": 10, "decode_live_seqs": 160,
                "decode_live_blocks": 10 * (8 * 2016 + 16 * 48)}
    more = {**counters, grow: 2 * counters[grow]}
    for cost in (fam.deepseekv32_decode_step_min_s,
                 fam.deepseekv32_latent_index_scores_min_s):
        assert cost(cfg, shapes, more, PEAK) > cost(cfg, shapes, counters, PEAK)


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
