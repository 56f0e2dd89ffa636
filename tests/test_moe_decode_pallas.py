"""A decode step's expert layer as the kernel that copies the touched experts
alone (`ops/moe_decode_pallas.py`, PR 54), interpreted: against
`moe_serve.routed_experts`'s batched einsum, which stays the plain form; the
order its grid visits the experts in; and the rule that sends a step's rows
to it, at the six expert configurations' shapes, with what a traced call
weighs (set-up's time).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models import (
    afmoe, deepseekv32, glm4moelite, keyevl2, lfm2moe, moe_serve, nemotronh,
)
from llm_d_kv_cache_manager_tpu.ops import moe_decode_pallas
from tests import test_tpu_compile as served
from tests.helpers.jaxprs import equations

D, F, E, K = 64, 256, 64, 2  # 64 experts scored, 2 picks a row


def layer(gated: bool, n: int, dtype, seed=0):
    rng = np.random.default_rng(seed)

    def matrix(*shape):
        return jnp.asarray(rng.normal(size=shape) * shape[-2] ** -0.5, dtype)

    experts = {"w_up": matrix(n, D, F), "w_down": matrix(n, F, D)}
    if gated:
        experts["w_gate"] = matrix(n, D, F)
    return experts


def step(rows: int, among, dtype, seed=1):
    """Rows, their picks dealt among the experts ``among`` (ids the router
    scored; each of them picked at least once) and the picks' weights."""
    rng = np.random.default_rng(seed)
    among = np.asarray(among)
    assert rows * K >= len(among)
    picked = among[(np.arange(rows * K) % len(among)).reshape(rows, K)]
    picked = rng.permuted(picked, axis=0)
    x = jnp.asarray(rng.normal(size=(rows, D)), dtype)
    w = jnp.asarray(rng.uniform(0.1, 1.0, size=(rows, K)), jnp.float32)
    return x, jnp.asarray(picked, jnp.int32), w


# gated, held, the experts picked among, rows, one tile of the hidden width
CASES = {
    "gated-all-touched": (True, None, range(E), 32, True),
    "gated-some-touched": (True, None, (1, 4, 6, 40, 63), 24, True),
    "gated-one-touched": (True, None, (5,), 32, True),
    "gated-one-row": (True, None, (0, 63), 1, True),
    "gated-128-rows": (True, None, range(E), 128, True),
    "gated-tiles-some": (True, None, (0, 2, 3, 47), 32, False),
    "gated-tiles-all": (True, None, range(E), 32, False),
    "gateless-all-touched": (False, None, range(E), 32, True),
    "gateless-some-touched": (False, None, (2, 3), 24, True),
    "gateless-tiles-some": (False, None, (0, 6, 7, 62, 63), 128, False),
    "held-all-touched": (True, (16, 8), range(E), 32, True),
    "held-some-touched": (True, (16, 8), (0, 17, 19, 23, 60), 24, True),
    "held-none-touched": (True, (16, 8), (0, 1, 40, 63), 24, True),
    "held-none-touched-tiles": (False, (32, 4), (0, 1, 2), 32, False),
    "held-last-touched-tiles": (True, (32, 4), (0, 35), 1, False),
    "gateless-held-some": (False, (0, 4), (1, 2, 6), 128, True),
    "serving-type-some": (True, None, (1, 4, 6, 40, 63), 32, True),
    "serving-type-held-tiles": (False, (16, 8), (0, 17, 20, 23, 60), 24,
                                False),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_batched_einsum(monkeypatch, case):
    """`routed_experts(batched=True)`: the interpreted kernel against the
    einsum (what an uninterpreted call off the TPU is), float32 to the order
    of a float32 sum, the serving type to its rounding; the picks per expert
    are the einsum's, whatever was touched."""
    gated, held, among, rows, whole = CASES[case]
    dtype = jnp.bfloat16 if case.startswith("serving") else jnp.float32
    if not whole:  # two tiles of 128 of the hidden width's 256
        monkeypatch.setattr(
            moe_decode_pallas, "TILE_VMEM_BYTES",
            2 * (2 + gated) * D * 128 * jnp.dtype(dtype).itemsize)
    n = E if held is None else held[1]
    assert moe_decode_pallas.hidden_tile(
        D, F, 2 + gated, jnp.dtype(dtype).itemsize) == (F if whole else 128)
    assert moe_serve.decode_kernel_serves(rows, K, E, F, True)
    assert not moe_serve.decode_kernel_serves(rows, K, E, F, False)
    experts = layer(gated, n, dtype)
    x, picked, w = step(rows, among, dtype)
    want, sizes = moe_serve.routed_experts(x, picked, w, experts, E, True,
                                           held)
    got, counted = moe_serve.routed_experts(x, picked, w, experts, E, True,
                                            held, interpret=True)
    assert got.shape == (rows, D) and got.dtype == jnp.float32
    np.testing.assert_array_equal(counted, sizes)
    first = 0 if held is None else held[0]
    touched = sorted({e - first for e in among if first <= e < first + n})
    assert [e for e in range(n) if int(sizes[e])] == touched
    if not touched:
        assert not np.asarray(got).any()
    scale = float(jnp.linalg.norm(want)) or 1.0
    off = float(jnp.linalg.norm(got - want)) / scale
    assert off < (2e-3 if dtype == jnp.bfloat16 else 1e-5), off


def visited(sizes, tiles: int) -> list:
    """The weight blocks (expert, tile) the grid names step after step, a
    block named again by the next step dropped: what the pipeline copies."""
    order, n = moe_decode_pallas.touched_order(jnp.asarray(sizes, jnp.int32))
    seen = []
    for e in range(len(sizes)):
        for f in range(tiles):
            block = tuple(int(v) for v in moe_decode_pallas.weight_block(
                e, f, order, n, tiles - 1))
            if not seen or seen[-1] != block:
                seen.append(block)
    return seen


@pytest.mark.parametrize("sizes", (
    (0, 0, 0, 0), (3, 0, 0, 0), (0, 0, 0, 9), (0, 2, 0, 5, 0, 0, 1, 0),
    (1, 1, 1, 1, 1, 1), (0, 4, 4, 0), tuple(range(16)),
    tuple(int(i % 3 == 0) for i in range(128)),
), ids=lambda s: f"{sum(v > 0 for v in s)}of{len(s)}")
@pytest.mark.parametrize("tiles", (1, 4))
def test_the_grid_visits_the_touched_experts_and_then_stays(sizes, tiles):
    """`touched_order`: the ids of the experts with a pick, ascending, then
    the last of them repeated; so the blocks the grid names are exactly the
    touched experts' tiles in the order they lie, and no other from there
    on (expert 0's last tile where nothing was touched: the one copy a call
    cannot be without)."""
    touched = [e for e, v in enumerate(sizes) if v]
    order, n = moe_decode_pallas.touched_order(jnp.asarray(sizes, jnp.int32))
    assert int(n[0]) == len(touched) and order.dtype == n.dtype == jnp.int32
    assert list(map(int, order)) == (touched + [touched[-1]] * (
        len(sizes) - len(touched)) if touched else [0] * len(sizes))
    assert visited(sizes, tiles) == (
        [(e, f) for e in touched for f in range(tiles)] or [(0, tiles - 1)])


# ------------------------------------------------- the rule, at the six shapes

AFMOE = afmoe.AfmoeConfig(  # benchmarks/configs/trinity-mini-l5.json
    vocab_size=200192, d_model=2048, n_heads=32, n_kv_heads=4,
    head_dim=128, d_ff=6144, d_expert=1024, n_experts=128, top_k=8)


def _expert_layer(module, cfg):
    params = jax.eval_shape(lambda: module.init_params(jax.random.key(0), cfg))
    return next(lp for lp in params["layers"] if "experts" in lp)


# family: its module, the configuration as the compile tests serve it, the
# function that holds its expert layer, a decode step's rows, the rows of a
# miss's chunk (a hit's suffix is 512 everywhere), and whether the rule sends
# a decode step to the kernel: where its rows leave held experts untouched
# under even routing and an expert's hidden width is whole lane tiles
# (`moe_serve.DECODE_KERNEL_MAX_SHARE`'s comment has the chip's readings)
SHAPES = {
    "afmoe": (afmoe, AFMOE, afmoe._moe, 64, 4096, True),
    "lfm2moe": (lfm2moe, served.LFM2, lfm2moe._moe, 64, 4352, False),
    "glm4moelite": (glm4moelite, served.GLM, glm4moelite._moe, 64, 4096,
                    True),
    "keyevl2": (keyevl2, served.KEYE, keyevl2._moe, 24, 4096, True),
    "nemotronh": (nemotronh, served.NEMO, nemotronh._moe, 128, 4352, False),
    "deepseekv32": (deepseekv32, served.DSV32, deepseekv32._ff_block, 32,
                    1024, True),
}
# What a traced call of the kernel may weigh, whatever the experts, the rows
# and the tiles: `touched_order` and the call with its body (set-up pays for
# every equation: tests/helpers/jaxprs.py).
KERNEL_CALL_EQUATIONS = 120


@pytest.mark.parametrize("family", SHAPES)
def test_the_rule_sends_a_decode_steps_rows_where_the_chip_said(family):
    """At the configuration's published widths (shapes alone, nothing is
    made): a decode step's rows trace to ONE call of the kernel a layer
    where the rule says so and the program is interpreted or compiled for
    the TPU, and to the einsum elsewhere; a 512-row hit suffix and a miss's
    chunk trace to what they were whatever `interpret` says; and the
    kernel's call weighs the same few equations at any shape."""
    module, cfg, block, decode_rows, miss_rows, kernel = SHAPES[family]
    lp = _expert_layer(module, cfg)
    d_model, width = lp["experts"]["w_up"].shape[1:]

    def serves(rows, interpret=True):
        return moe_serve.decode_kernel_serves(
            rows, cfg.top_k, cfg.n_experts, width, interpret)

    def traced(rows, interpret):
        h = jax.ShapeDtypeStruct((1, rows, d_model), jnp.float32)
        return jax.make_jaxpr(
            lambda h, lp: block(h, lp, cfg, interpret)[0])(h, lp)

    step, plain = traced(decode_rows, True), traced(decode_rows, False)
    assert "moe_decode_pallas" not in str(plain)
    assert str(step).count("name=moe_decode_pallas") == kernel
    assert serves(decode_rows) == kernel and not serves(decode_rows, False)
    for rows in (512, miss_rows):
        assert str(traced(rows, True)) == str(traced(rows, False))
        assert not serves(rows)
    if kernel:
        assert equations(step.jaxpr) - equations(plain.jaxpr) < (
            KERNEL_CALL_EQUATIONS)
    else:
        assert str(step) == str(plain)

    def call(rows, held):
        experts = {k: jax.ShapeDtypeStruct((held, *v.shape[1:]), v.dtype)
                   for k, v in lp["experts"].items()}
        s = jax.ShapeDtypeStruct
        return equations(jax.make_jaxpr(
            lambda x, w, experts, sizes: moe_decode_pallas.moe_decode_pallas(
                x, w, experts, *moe_decode_pallas.touched_order(sizes)))(
            s((rows, d_model), jnp.bfloat16), s((rows, held), jnp.float32),
            experts, s((held,), jnp.int32)).jaxpr)

    held = lp["experts"]["w_up"].shape[0]
    assert call(decode_rows, held) == call(8, 4) < KERNEL_CALL_EQUATIONS


SMALL = {  # each family's own small configuration, an expert a lane tile wide
    "afmoe": (afmoe, afmoe.AfmoeConfig, afmoe._moe),
    "lfm2moe": (lfm2moe, lfm2moe.Lfm2MoeConfig, lfm2moe._moe),
    "glm4moelite": (glm4moelite, glm4moelite.Glm4MoeLiteConfig,
                    glm4moelite._moe),
    "keyevl2": (keyevl2, keyevl2.KeyeVl2Config, keyevl2._moe),
    "nemotronh": (nemotronh, nemotronh.NemotronHConfig, nemotronh._moe),
    "deepseekv32": (deepseekv32, deepseekv32.DeepseekV32Config,
                    deepseekv32._ff_block),
}


@pytest.mark.parametrize("family", SMALL)
def test_a_familys_expert_layer_through_the_kernel_is_its_einsum(family):
    """The six call sites hand `interpret` on: two rows (a decode step's)
    through a family's expert layer, its own router, weights and shared
    expert, interpreted, hold the kernel's call and give what the einsum
    gives, float32, with the same counts."""
    module, config, block = SMALL[family]
    cfg = config(dtype="float32", d_expert=128)
    if hasattr(cfg, "held"):
        cfg = dataclasses.replace(cfg, held=(2, 4))
    params = module.init_params(jax.random.key(3), cfg)
    lp = next(lp for lp in params["layers"] if "experts" in lp)
    h = jax.random.normal(jax.random.key(4), (2, 1, cfg.d_model), jnp.float32)
    text = str(jax.make_jaxpr(lambda h: block(h, lp, cfg, True)[0])(h))
    assert text.count("name=moe_decode_pallas") == 1
    got, counted = block(h, lp, cfg, True)
    want, counts = block(h, lp, cfg, False)
    np.testing.assert_array_equal(counted, counts)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))
