"""chip_smoke.py on the CPU: it refuses to run without a TPU, its phase
functions hold at a tiny size with the Pallas kernels interpreted, and
the compile cache goes where the one rule says."""

import dataclasses
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from benchmarks import run
from benchmarks.harness import engine, traffic
from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.parallel import compile_cache

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Same shape of model and traffic as the chip run, cut to seconds:
# bf16, GQA, a prompt long enough for the flash route, block 16.
TINY_CFG = llama.LlamaConfig(
    vocab_size=512,
    d_model=128,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    d_ff=256,
    block_size=16,
    dtype="bfloat16",
    flash_attention_min_len=128,
)
TINY_GEOMETRY = chip_smoke.Geometry(
    prefix_tokens=256,
    suffix_tokens=32,
    pool_blocks=64,
    decode_steps=3,
    reference_tokens=128,
)


def test_full_geometry_is_the_benchmark_cells():
    """Widths, tokens, block, pods and pool come from the files of the
    cell `mistral7b-docs-shared`."""
    cell = run.load(run.BENCH, "cells", chip_smoke.CELL)
    published = run.load(run.BENCH, "configs", cell["config"])
    tr = run.load(run.BENCH, "traffic", cell["traffic"])
    cfg, geom = chip_smoke.full_setup()
    assert cell["config"] == "mistral-7b-v0.3-l8"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.head_dim) == tuple(published[k] for k in (
                "hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "intermediate_size", "vocab_size",
                "head_dim"))
    assert cfg.block_size == engine.BLOCK == 16
    assert geom.traffic() == {key: tr[key] for key in geom.traffic()}
    assert traffic.shapes(geom.traffic()) == traffic.shapes(tr)


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=HERE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def params():
    return llama.init_params(jax.random.PRNGKey(0), TINY_CFG)


def test_single_chip_phases_hold_at_tiny_size(params):
    cfg, geom = TINY_CFG, TINY_GEOMETRY
    chip_smoke.phase_reference(cfg, geom, params, interpret=True)
    chip_smoke.phase_flash_bound(cfg, 512, interpret=True)
    result = chip_smoke.phase_fleet(cfg, geom, params, interpret=True)
    try:
        assert len(set(result.holder.values())) == geom.n_groups
        chip_smoke.phase_hit_vs_miss(cfg, geom, result)
        chip_smoke.phase_block_until_ready(cfg, geom, result)
        chip_smoke.phase_decode(cfg, geom, result, interpret=True)
        chip_smoke.phase_offload(cfg, geom, result)
    finally:
        result.fleet.shutdown()


def test_four_chip_fleet_puts_one_pod_on_each_device(params):
    spread = dataclasses.replace(
        TINY_GEOMETRY, n_groups=4, reqs_per_group=2
    )
    result = chip_smoke.phase_fleet(
        TINY_CFG,
        spread,
        params,
        interpret=True,
        devices=jax.devices()[:4],
        seed=1,
    )
    result.fleet.shutdown()
    assert len(set(result.holder.values())) == 4


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="argmax"):
        chip_smoke.logits_agree([1.0, 1.01], [1.01, 1.0], "swap")


def test_cache_helper_sets_nothing_when_the_variable_is_set(monkeypatch):
    writes = []
    monkeypatch.setattr(
        jax.config, "update", lambda *args: writes.append(args)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache.configure_compile_cache() == "/x"
    assert writes == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(HERE, ".xla_cache")
    assert compile_cache.configure_compile_cache() == fixed
    assert writes == [("jax_compilation_cache_dir", fixed)]
