"""CPU tests of the family `glm4moelite` (GLM-4.7-Flash) in the harness: the
package's pod with its one group of the latent kind through the files-only
path, on a tiny configuration under `tests/data/glm4moelite/`; the plain
reference in the published per-head form against the program's; and the
least-work counts at the published sizes.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_glm4moelite
from benchmarks.tests import test_pod
from llm_d_kv_cache_manager_tpu.models import glm4moelite
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "glm4moelite")
CFG = run.load(DATA, "configs", "tiny-glm4moelite")
CELL = "tiny-glm4moelite-repos"
REAL = "glm47flash-chat-repos"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-glm4moelite")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_glm4moelite
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, glm4moelite.Glm4MoeLiteConfig)
    assert model.latent_dim == 40
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.groups == [] and p.protect_asked
               and p.specs["full"].latent_dim == 40
               and p.step_weight_nbytes == model.decode_weight_nbytes
               for p in fleet.pods)
    with pytest.raises(ValueError, match="n_group"):
        program.from_published({**CFG, "n_group": 2}, engine.BLOCK)


def test_the_real_cell_is_found_with_files_only():
    cell = run.load(run.BENCH, "cells", REAL)
    cfg = run.load(run.BENCH, "configs", cell["config"])
    tiny = run.load(DATA, "cells", CELL)
    assert cell["metrics"] == tiny["metrics"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert family.reference(cfg) is family_glm4moelite
    tr = run.load(run.BENCH, "traffic", cell["traffic"])
    longdocs = run.load(run.BENCH, "traffic", "chat-longdocs")
    assert tr["output_lengths"] == longdocs["output_lengths"]  # letter for letter
    assert {k: tr[k] for k in ("kind", "pods", "slots", "system_prompts",
                               "system_tokens", "turn_tokens", "pool_blocks",
                               "check_sample")} == {
        "kind": "closed_loop_chat", "pods": 1, "slots": 64,
        "system_prompts": 64, "system_tokens": 15872, "turn_tokens": 512,
        "pool_blocks": 73728, "check_sample": 3}
    for name in cell["metrics"]:
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == "itl_p50_s" if "layer" in spec else name == "itl_p50_s"
        cost = spec["read"].get("cost")
        assert cost is None or callable(getattr(family_glm4moelite, cost))
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
              if REAL in m.get("workloads", ())}
    assert listed == set(cell["metrics"])


def test_the_configuration_keeps_every_published_number_but_the_depth():
    """The catalog's `config` of the model, key for key: only
    `num_hidden_layers` differs, and it is what `reduced` names."""
    cfg = run.load(run.BENCH, "configs", "glm-4.7-flash-l5")
    published = dict(
        attention_bias=False, hidden_act="silu", hidden_size=2048,
        intermediate_size=10240, max_position_embeddings=202752,
        model_type="glm4_moe_lite", moe_intermediate_size=1536,
        topk_method="noaux_tc", norm_topk_prob=True, num_attention_heads=20,
        n_group=1, topk_group=1, n_routed_experts=64, n_shared_experts=1,
        routed_scaling_factor=1.8, num_experts_per_tok=4,
        first_k_dense_replace=1, num_hidden_layers=47, num_key_value_heads=20,
        num_nextn_predict_layers=1, partial_rotary_factor=1,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=1000000,
        tie_word_embeddings=False, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        vocab_size=154880)
    assert {k for k, v in published.items() if cfg[k] != v} == {
        "num_hidden_layers"}
    assert cfg["num_hidden_layers"] == 5 and "num_nextn_predict_layers" in cfg["departs"]
    model = glm4moelite.from_published(cfg, engine.BLOCK)
    assert model.n_layers == 5 and model.latent_dim == 576


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod with a latent group through `closed_loop_chat`: hits and
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
        assert 0 < values["latent_read_share.repos"] < 1
        assert 0 < values["attention_read_share.repos"] <= 1
        assert 0 < values["moe_experts_touched_share.repos"] <= 1
        assert values["moe_expert_load_max_over_mean.repos"] >= 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_glm4moelite.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 300)
    whole = np.asarray(family_glm4moelite.forward_logits(weights, CFG, tokens, 300))
    head = np.asarray(family_glm4moelite.forward_logits(weights, CFG, tokens[:200], 8))
    np.testing.assert_allclose(head, whole[192:200], atol=2e-5)
    other = tokens.copy()
    other[:100] = 5  # every later position attends over it
    moved = np.asarray(family_glm4moelite.forward_logits(weights, CFG, other, 1))
    assert np.abs(moved - whole[-1:]).max() > 1e-3


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations in the per-head form (float32 here, so nothing
    flips)."""
    import jax

    weights = family_glm4moelite.make_weights(CFG, 4)
    model = glm4moelite.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(
        lambda: glm4moelite.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_glm4moelite.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(glm4moelite.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    norms = [np.asarray(lp[k], np.float32) for lp in weights["layers"]
             for k in ("ln_in", "ln_post", "q_norm", "kv_norm")]
    assert all(n.std() > 0.05 for n in norms)
    assert all(np.asarray(lp["route_bias"]).std() > 0.02
               for lp in weights["layers"][1:])


def test_counts_at_the_published_sizes():
    """ISSUE 42's arithmetic: 21.8 M of attention, 635.3 M an expert layer,
    3.26 B = 6.52 GB in all; 1152 B a token a layer, 92 160 B a block over
    five layers; a hit in the latent form 365 GFLOP a layer."""
    cfg = run.load(run.BENCH, "configs", "glm-4.7-flash-l5")
    fam = family_glm4moelite
    c = fam.layer_counts(cfg)
    assert round(c["attention"] / 1e6, 1) == 21.8
    assert round((c["attention"] + c["norms"] + 64 * c["expert"] + c["shared"]
                  + c["router"]) / 1e6, 1) == 635.3
    assert round(fam.param_count(cfg) / 1e9, 2) == 3.26
    assert round(fam.param_bytes(cfg) / 1e9, 2) == 6.52
    assert fam.kv_token_bytes(cfg) == 5 * 1152
    assert fam.kv_block_bytes(cfg, 16) == 92160
    model = glm4moelite.from_published(cfg, engine.BLOCK)
    assert glm4moelite.cache_groups(model)["full"].block_nbytes == 92160
    # the program's own count of what a decode step reads of the weights
    # (all but the embedding; it counts the selection bias as the float32
    # it is, 2 B more for each of 64 experts in 4 layers)
    assert model.decode_weight_nbytes == (
        fam.param_count(cfg) - cfg["vocab_size"] * cfg["hidden_size"]) * 2 \
        + 4 * 64 * 2
    T, P = 16384, 15872
    assert fam.prefill_attention_flops(cfg, T) == 5 * 2 * 20 * 512 * (
        T * (T + 1) // 2)
    hit = fam.latent_attention_flops(cfg, fam._pairs(T, P)) / 5
    assert 0.98 < hit / (512 * 16384 * 20 * 1088 * 2) < 1.0  # 365 GFLOP, causal
    shapes = {"hit": (P, 512), "max_blocks": 1056}
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * 64 * 1040}
    step = fam.glm4moelite_decode_step_min_s(cfg, shapes, counters, PEAK)
    attn = fam.glm4moelite_latent_decode_attention_min_s(cfg, shapes, counters,
                                                         PEAK)
    assert 0.0140 < step < 0.0150  # 12 GB at 819 GB/s
    assert attn == 64 * 1040 * 92160 / 819e9  # bandwidth-bound: 7.5 ms
    # 32 sequences over one prefix: the bytes once, the products 32 times
    shared = dict(counters, decode_live_seqs=320,
                  decode_live_blocks=10 * (992 + 32 * 48))
    assert fam.glm4moelite_latent_decode_attention_min_s(
        cfg, shapes, shared, PEAK) == fam.latent_attention_flops(
        cfg, 32 * P) / 197e12
    assert 0.0090 < fam.glm4moelite_latent_hit_prefill_min_s(
        cfg, shapes, counters, PEAK) < 0.0094  # 1.8 TFLOP at the peak


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
