"""CPU tests of the family `nemotronh` in the harness: found by name, with the
package's `Pod` and `jit_programs` (`models/pod.py`, a state group whose slot
is a matrix a head beside the K/V group) through the files-only path, on a
tiny configuration under `tests/data/nemotronh/` that holds experts 0-3 of the
8 its router scores.  `python -m pytest benchmarks/tests`."""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from benchmarks import run
from benchmarks.harness import engine, family, family_nemotronh
from llm_d_kv_cache_manager_tpu.models import nemotronh
from llm_d_kv_cache_manager_tpu.models import pod as package_pod

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data", "nemotronh")
CFG = run.load(DATA, "configs", "tiny-nemotronh")
CELL = "tiny-nemotronh-agentreason"
REAL = "nemotron3nano-agents-reasoning"
PEAK = {"hbm_bytes_s": 819e9, "bf16_flops": 197e12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark directory of the tiny cell with the real metric files."""
    path = tmp_path_factory.mktemp("bench-nemotronh")
    shutil.copytree(DATA, path, dirs_exist_ok=True)
    shutil.copytree(os.path.join(run.BENCH, "metrics"), path / "metrics")
    return str(path)


def test_the_family_is_found_by_name_and_brings_the_packages_pod():
    program = family.program(CFG)
    assert family.reference(CFG) is family_nemotronh
    assert program.Pod is package_pod.Pod
    assert program.jit_programs is package_pod.jit_programs
    model = program.from_published(CFG, engine.BLOCK)
    assert isinstance(model, nemotronh.NemotronHConfig)
    assert (model.n_experts, model.held, model.experts_held) == (8, (0, 4), 4)
    fleet = engine.Fleet(program, model, None, {"pods": 2, "pool_blocks": 8},
                         {}, engine.Records(), interpret=True)
    fleet.shutdown()
    assert all(p.groups == [p.state] and p.window is None and p.protect_asked
               and p.decode_ahead for p in fleet.pods)
    with pytest.raises(ValueError, match="n_group"):
        program.from_published({**CFG, "n_group": 4}, engine.BLOCK)


def test_the_real_cell_is_found_with_files_only():
    cell = run.load(run.BENCH, "cells", REAL)
    cfg = run.load(run.BENCH, "configs", cell["config"])
    tiny = run.load(DATA, "cells", CELL)
    assert cell["metrics"] == tiny["metrics"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "hybrid_override_pattern",
                                   "n_routed_experts", "vocab_size"}
    assert family.reference(cfg) is family_nemotronh
    tr = run.load(run.BENCH, "traffic", cell["traffic"])
    lengths = tr["output_lengths"]
    assert {n: lengths.count(n) for n in set(lengths)} == {
        1024: 32, 2048: 64, 3072: 32}
    assert len(lengths) == tr["slots"] == 128 and sum(lengths) == 128 * 2048
    assert (tr["system_prompts"], tr["system_tokens"], tr["turn_tokens"],
            tr["pool_blocks"], tr["check_sample"]) == (8, 8192, 512, 32768, 3)
    chunk = cfg["chunk_size"]  # every prefill of the cell is whole chunks
    assert not tr["system_tokens"] % chunk and not tr["turn_tokens"] % chunk
    assert not cfg["serving"]["state_stride_blocks"] * engine.BLOCK % chunk
    for name in cell["metrics"]:
        spec = run.load(run.BENCH, "metrics", name)
        assert spec["moves"] == "itl_p50_s" if "layer" in spec else name == "itl_p50_s"
        cost = spec["read"].get("cost")
        assert cost is None or callable(getattr(family_nemotronh, cost))


@pytest.mark.parametrize("trace", (False, True))
def test_the_cell_runs_through_the_harness_unchanged(root, trace):
    """`run.run_cell`, the code path of `benchmarks/run.py`, drives the
    package's pod through `closed_loop_chat`: hits (each resumed from a
    snapshot by the chunk scan) and misses agree with the plain cache model,
    nothing compiles inside the window, and the traced run's readers find
    their spans, the held picks among them."""
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
        assert values["state_resume_short_share.agentreason"] == 0
        assert 0 < values["state_slot_share.agentreason"] < 1
        assert 0 < values["state_kv_bytes_ratio.agentreason"]
        assert 0 < values["attention_read_share.agentreason"] < 1
        assert 0 < values["moe_held_pick_share.agentreason"] < 1
        assert 0 < values["moe_experts_touched_share.agentreason"] <= 1
        assert 0.5 < values["decode_ahead_share.agentreason"] < 1
    json.dumps(result)


def test_float8_control_fails_the_cells_limits(root):
    result = run.run_cell(CELL, 11, 1.0, False, root=root, on_cpu=True,
                          control=True)
    limits = run.load(root, "cells", CELL)["limits"]
    assert result["correct"]
    assert any(value > limits[name]
               for name, value in result["extra"]["control"].items())


def test_reference_is_causal_and_padding_changes_nothing():
    weights = family_nemotronh.make_weights(CFG, 3)
    tokens = np.random.default_rng(3).integers(1, CFG["vocab_size"], 600)
    whole = np.asarray(family_nemotronh.forward_logits(weights, CFG, tokens, 600))
    assert whole.shape == (600, CFG["vocab_size"])
    head = np.asarray(family_nemotronh.forward_logits(weights, CFG, tokens[:300], 8))
    np.testing.assert_allclose(head, whole[292:300], atol=2e-5)
    rows = np.asarray(family_nemotronh.forward_logits(weights, CFG, tokens, 300))
    np.testing.assert_allclose(rows, whole[300:], atol=2e-5)  # over two head calls
    other = tokens.copy()
    other[:100] = 5  # far behind, but the scan and the attention layer see it
    moved = np.asarray(family_nemotronh.forward_logits(weights, CFG, other, 1))
    assert np.abs(moved - whole[-1:]).max() > 1e-4


def test_the_benchmarks_weights_and_reference_are_the_programs():
    """The same pytree, and the same logits to rounding: two texts of one
    set of equations, both handed the same share of the experts."""
    import jax

    weights = family_nemotronh.make_weights(CFG, 4)
    model = nemotronh.from_published(CFG, engine.BLOCK)
    shapes = jax.eval_shape(lambda: nemotronh.init_params(jax.random.key(0), model))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), weights) == jax.tree.map(
        lambda a: (a.shape, a.dtype), shapes)
    experts = weights["layers"][1]["experts"]
    assert experts["w_up"].shape[0] == 4 and "w_gate" not in experts
    assert weights["layers"][1]["router"].shape == (64, 8)  # scores all eight
    tokens = np.random.default_rng(4).integers(1, CFG["vocab_size"], 80)
    mine = np.asarray(family_nemotronh.forward_logits(weights, CFG, tokens, 80))
    theirs = np.asarray(nemotronh.reference_logits(weights, tokens, model))
    np.testing.assert_allclose(mine, theirs, atol=2e-4 * np.abs(theirs).max())
    # the share is part of the result: with the other half's ids the same
    # weights give other logits
    other = {**CFG, "held": {"experts_first": 4}}
    moved = np.asarray(family_nemotronh.forward_logits(weights, other, tokens, 80))
    assert np.abs(moved - mine).max() > 1e-3
    mamba = weights["layers"][0]
    a = np.exp(np.asarray(mamba["a_log"]))
    assert 1 <= a.min() and a.max() <= 16 and np.all(
        np.asarray(mamba["d_skip"]) == 1)
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))
    assert 1e-3 <= dt.min() and dt.max() <= 1e-1 + 1e-6
    for part in (mamba["ln"], mamba["norm"], mamba["conv_b"], weights["ln_f"]):
        assert np.asarray(part, np.float32).std() > 0.05
    assert np.asarray(weights["layers"][1]["route_bias"]).std() > 0.02


def test_counts_at_the_published_sizes():
    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    c = family_nemotronh.layer_counts(cfg)
    D = 2688
    assert c["mamba"] == (D * (4096 + 6144 + 64) + 6144 * 5 + 3 * 64 + 4096
                          + 4096 * D)  # 38.74 M
    assert c["expert"] == 2 * D * 1856  # 9.978 M: two matrices, no gate
    assert c["shared"] == 2 * D * 3712 + D * 128 + 128  # the router scores 128
    assert c["attention"] == D * 128 * (2 * 32 + 2 * 2)  # 23.40 M
    assert family_nemotronh.layers(cfg) == {"M": 4, "E": 4, "*": 1}
    count = family_nemotronh.param_count(cfg)
    assert count == (2 * 65536 * D + D + 9 * D + 4 * c["mamba"]
                     + c["attention"] + 4 * (64 * c["expert"] + c["shared"]))
    assert round(count / 1e9, 3) == 3.166  # 6.33 GB at bfloat16
    assert family_nemotronh.param_bytes(cfg) == 2 * count
    assert family_nemotronh.kv_token_bytes(cfg) == 1024
    assert family_nemotronh.kv_block_bytes(cfg, 16) == 16 * 1024
    assert family_nemotronh.state_slot_bytes(cfg) == 4 * (
        3 * 6144 * 2 + 64 * 64 * 128 * 4) == 8536064
    model = nemotronh.from_published(cfg, 16)
    assert (model.n_experts, model.held, model.top_k) == (128, (0, 64), 6)
    groups = nemotronh.cache_groups(model)  # the program's own, the same bytes
    assert groups["full"].block_nbytes == 16 * 1024
    assert groups["state"].block_nbytes == family_nemotronh.state_slot_bytes(cfg)
    # the state group outweighs the pool: 452 slots against 32 768 blocks
    assert cfg["serving"]["state_slots"] == 452
    assert 452 * groups["state"].block_nbytes > 7 * 32768 * 16 * 1024
    # a hit prefill by hand: 512 queries at positions 8192 .. 8703, each over
    # all before it, 32 heads of 128, one layer
    flops = family_nemotronh.prefill_attention_flops
    assert flops(cfg, 8704, 8192) == 4 * 32 * 128 * sum(range(8193, 8705))
    shapes = {"hit": (8192, 512)}
    assert family_nemotronh.nemotronh_flash_hit_prefill_min_s(
        cfg, shapes, {}, PEAK) == flops(cfg, 8704, 8192) / 197e12
    # the chunk scan of a hit: 4 chunks x 4 layers; C B^T a group, then a head
    # the products with d x, with the carried state, and the state's update
    chunk = 2 * 128 * (8 * 128 * 128 + 64 * 128 * 64 + 2 * 64 * 128 * 64)
    assert family_nemotronh.ssd_chunk_flops(cfg) == chunk
    moved = 512 * ((2 * 4096 + 2 * 1024) * 2 + 4 * 64) + 2 * 64 * 64 * 128 * 4
    assert family_nemotronh.ssd_scan_bytes(cfg, 512) == moved
    least = family_nemotronh.nemotronh_ssd_hit_prefill_min_s(cfg, shapes, {}, PEAK)
    assert least == max(16 * chunk / 197e12, 4 * moved / 819e9)
    assert least == 4 * moved / 819e9  # bandwidth-bound: 72 us against 35 us


def test_a_decode_steps_least_bytes_on_a_hand_counted_case():
    """128 sequences of 8 prompts of 512 blocks, 96 blocks of their own each:
    every weight outside the routed experts and the head (0.87 GB, the
    embedding a row a sequence); of each expert layer's 64 held experts the
    64 (1 - (1 - 6/128)^128) = 63.86 that 128 sequences touch (5.10 GB); the
    full group's 4096 + 128 x 96 distinct blocks of 16 KB (0.27 GB); 8.54 MB
    of state read and written a sequence (2.19 GB); 1 KB of new K/V each."""
    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    c = family_nemotronh.layer_counts(cfg)
    blocks = 8 * 512 + 128 * 96
    counters = {"decode_steps": 10, "decode_live_seqs": 1280,
                "decode_live_blocks": 10 * blocks}
    kv = blocks * 16 * 1024
    assert family_nemotronh._decode_kv_bytes(cfg, counters) == kv
    attn = family_nemotronh.nemotronh_paged_decode_attention_min_s
    step = family_nemotronh.nemotronh_decode_step_min_s
    assert attn(cfg, {}, counters, PEAK) == kv / 819e9
    touched = 64 * (1 - (1 - 6 / 128) ** 128)
    assert 63.8 < touched < 63.9
    other = 2 * (65536 * 2688 + 10 * 2688 + 4 * c["mamba"] + c["attention"]
                 + 4 * c["shared"])
    assert round(other / 1e9, 2) == 0.87
    experts = 2 * 4 * touched * c["expert"]
    assert round(experts / 1e9, 2) == 5.10
    state = 128 * 2 * 8536064
    want = (other + experts + kv + state + 128 * 1024) / 819e9
    assert step(cfg, {}, counters, PEAK) == pytest.approx(want, rel=1e-12)
    assert 0.0102 < want < 0.0104  # 0.87 + 5.10 + 0.27 + 2.19 GB over 819 GB/s
    assert 0.86 < (experts + state) / (want * 819e9) < 0.88  # what is new here


@pytest.mark.parametrize("grow", ("decode_live_blocks", "decode_live_seqs"))
def test_costs_grow_with_what_they_count(grow):
    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    counters = {"decode_steps": 10, "decode_live_seqs": 640,
                "decode_live_blocks": 10 * (8 * 512 + 64 * 96)}
    more = {**counters, grow: 2 * counters[grow]}
    costs = [family_nemotronh.nemotronh_decode_step_min_s]
    if grow == "decode_live_blocks":
        costs.append(family_nemotronh.nemotronh_paged_decode_attention_min_s)
    for cost in costs:
        assert cost(cfg, {}, more, PEAK) > cost(cfg, {}, counters, PEAK)


def test_the_configuration_file_states_its_cut_and_its_source():
    """Every number of the catalog's row under its key, but for the keys
    `reduced` lists; no width among those; the published counts and the
    deployment beside them."""
    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    published = {
        "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "intermediate_size": 1856, "mamba_head_dim": 64,
        "mamba_num_heads": 64, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_groups": 8,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_key_value_heads": 2, "routed_scaling_factor": 2.5,
        "ssm_state_size": 128, "max_position_embeddings": 262144,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (
                9, "MEMEM*EME", 64, 65536)
    was = cfg["published"]
    assert (was["num_hidden_layers"], was["n_routed_experts"],
            was["vocab_size"]) == (52, 128, 131072)
    assert was["hybrid_override_pattern"][:9] == cfg["hybrid_override_pattern"]
    assert len(was["hybrid_override_pattern"]) == 52
    period = was["hybrid_override_pattern"][34:43]  # the pattern's 9-layer run
    assert sorted(period) == sorted(cfg["hybrid_override_pattern"])
    assert cfg["held"] == {"experts_first": 0}
    assert "two chips" in cfg["deployment"]
    assert {"no_position_encoding", "ssm_state_float32", "state_slots",
            "state_stride_blocks", "torch_dtype"} <= set(cfg["assumed"])
    assert {"residual_in_fp32", "chunked_scan",
            "rescale_prenorm_residual"} <= set(cfg["departs"])


def serve_on_the_host(state_slots: int, steps: int, seed: int = 2147490201):
    """The real cell's traffic through the package's `Pod` with no model
    behind it (a program whose `new_pool` makes nothing), call for call as
    `engine.run_chat` and `jit_programs.run_decode` make them, a decode call
    that goes on handing the next its tables: returns the state group."""
    from benchmarks.harness import traffic

    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    cfg = {**cfg, "serving": {**cfg["serving"], "state_slots": state_slots}}
    tr = run.load(run.BENCH, "traffic", "agents-reasoning")

    class Program:
        cache_policy = staticmethod(nemotronh.cache_policy)
        new_pool = staticmethod(lambda model, blocks: {})

    pod = package_pod.Pod("p", Program, nemotronh.from_published(cfg, 16),
                          tr["pool_blocks"])
    clients = traffic.chat_clients(tr, cfg["vocab_size"], seed)
    columns = traffic.shapes(tr)["max_blocks"]
    scratch = pod.alloc(1)[0][0]
    pod.hold([scratch], +1)
    table = np.full((tr["slots"], columns), scratch, np.int32)
    ctx, live = np.ones(tr["slots"], np.int32), [None] * tr["slots"]
    waiting = [(i, next(c)) for i, c in enumerate(clients)]
    went_on, handed, same = False, None, False

    def finish(slot):
        req = live[slot]
        pod.hold(req["blocks"], -1)
        pod.free.extend(req["own"])
        table[slot], ctx[slot], live[slot] = scratch, 1, None
        waiting.append((slot, next(clients[slot])))

    def admit(slot, req):
        hashes, n_pre = engine.block_hash_chain(req["tokens"]), req["prefix_blocks"]
        cached = pod.cached_prefix(hashes[:n_pre])
        first = n_pre if len(cached) == n_pre else 0
        pod.touch(hashes[:first])
        pod.hold(cached[:first], +1)
        new, _ = pod.alloc(len(hashes) - first)
        pod.hold(cached[:first], -1)
        blocks = cached[:first] + new
        pod.hold(blocks, +1)
        own, _ = pod.alloc(-(-(req["n_out"] - 1) // 16))
        pod.hold(own, +1)
        pod.tables("hit" if first else "miss",
                   np.asarray(blocks, np.int32)[None], prefix_blocks=first)
        for h, b in zip(hashes[first:], blocks[first:]):
            pod.cached[h] = b
        req.update(blocks=blocks + own, own=own,
                   left=req["n_out"] - 1 - req["done"])
        table[slot, :len(req["blocks"])] = req["blocks"]
        ctx[slot], live[slot] = len(req["tokens"]) + 1 + req["done"], req

    while waiting:
        admit(*waiting.pop(0))
    for _ in range(steps):
        if waiting:
            admit(*waiting.pop(0))
            went_on = same = False
        if went_on and same:  # the call goes on: its tables, and the next's
            _, handed = pod.tables(
                "decode", table.copy(), context_len=ctx.copy(), made=handed,
                ahead=np.minimum(ctx + 1, columns * 16))
        else:
            pod.tables("decode", table.copy(), context_len=ctx.copy())
            handed = None
        went_on = same = True
        for slot, req in enumerate(live):
            if req is not None:
                ctx[slot] += 1
                req["left"] -= 1
                if req["left"] <= 0:
                    finish(slot)
                    same = False
    return pod.state


def test_the_cells_state_slots_serve_its_traffic_on_the_host():
    """The need is 450 slots: 3 a live sequence (its prefill's end and two
    alternating slots) x 128, the 8 prompts' 8 stride boundaries, the scratch
    block's 2; the file's 452 is that and one rolling pair.  449 runs out, as
    the issue's 448 did at the 397th decode step of every 42-s run on the
    chip (PR 49)."""
    cfg = run.load(run.BENCH, "configs", "nemotron-3-nano-30b-a3b-l9")
    assert cfg["serving"]["state_slots"] == 3 * 128 + 8 * 8 + 2 + 2
    state = serve_on_the_host(450, 1500)
    assert state.counts["resume_short_blocks"] == 0
    assert state.counts["reclaimed"] > 0  # finished requests' end snapshots
    assert len(state.block_of) - len(state.free) == 450
    with pytest.raises(RuntimeError, match="state group exhausted"):
        serve_on_the_host(449, 1500)
