"""A decode step's expert layer alone on the chip, a layer at each of the six
expert cells' shapes: `moe_serve.routed_experts`'s batched einsum against
`ops/moe_decode_pallas.py`'s kernel, at every held expert touched and at the
share of them the ledger's `moe_experts_touched_share.*` reads in the cell
(PR 53's lines).  Its readings are the table `moe_serve`'s rule cites
(PERF.md section 6, PR 54).

    chiprun -- python hack/moe_decode_alone.py [--out chiprun_out/pr54/alone.json]
    python hack/moe_decode_alone.py --tiny     (the script's own paths, CPU)

A form is `routed_experts(batched=True)` jitted alone with every operand an
argument: the seconds its trace, lowering and compile take, then ms a call
(the median of `CALLS` calls after a warm one, the launch in each) and ms a
layer where `LOOP` layers follow one another inside one call, each reading
the one before's result (no launch; the kernel's first copy and its result's
way out are in it, as they are in a step).  `--rows` reads both forms at
other numbers of rows, every expert touched: where the kernel's cap on a
step's rows comes from.  `--tiles` reads the kernel at other tiles of the
hidden width (`TILE_VMEM_BYTES`).  It fails where JAX finds no TPU: a CPU's
time is no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from llm_d_kv_cache_manager_tpu.models import moe_serve  # noqa: E402
from llm_d_kv_cache_manager_tpu.ops import moe_decode_pallas  # noqa: E402

CALLS, LOOP = 10, 8
# rows of a decode step, the experts the router scores, those held, picks a
# token, widths, a gate matrix or none; `share`: the ledger's
# moe_experts_touched_share of the cell (PR 53's lines)
REAL = {
    "deepseekv32-chat-longctx-shared": dict(
        rows=32, scored=256, held=16, k=8, D=7168, F=2048, gated=True,
        share=0.517),
    "keyevl2-chat-longctx": dict(
        rows=24, scored=128, held=128, k=8, D=2048, F=768, gated=True,
        share=0.770),
    "nemotron3nano-agents-reasoning": dict(
        rows=128, scored=128, held=64, k=6, D=2688, F=1856, gated=False,
        share=0.862),
    "trinitymini-chat-longdocs": dict(
        rows=64, scored=128, held=128, k=8, D=2048, F=1024, gated=True,
        share=0.889),
    "glm47flash-chat-repos": dict(
        rows=64, scored=64, held=64, k=4, D=2048, F=1536, gated=True,
        share=0.891),
    "lfm2moe-chat-agents": dict(
        rows=64, scored=32, held=32, k=4, D=2048, F=1792, gated=True,
        share=0.992),
}
TINY = {
    "tiny-held": dict(rows=8, scored=16, held=4, k=2, D=128, F=256,
                      gated=True, share=0.5),
    "tiny-gateless": dict(rows=8, scored=8, held=8, k=2, D=128, F=128,
                          gated=False, share=0.75),
}


def operands(shape, rows, dtype, seed=54):
    """A layer's held experts and a step's rows, made on the device."""
    E, D, F = shape["held"], shape["D"], shape["F"]

    def make(key):
        ks = jax.random.split(key, 4)
        experts = {
            "w_up": jax.random.normal(ks[0], (E, D, F), dtype) * D**-0.5,
            "w_down": jax.random.normal(ks[1], (E, F, D), dtype) * F**-0.5}
        if shape["gated"]:
            experts["w_gate"] = jax.random.normal(
                ks[2], (E, D, F), dtype) * D**-0.5
        return jax.random.normal(ks[3], (rows, D), dtype), experts

    return jax.jit(make)(jax.random.key(seed))


def picks(shape, rows, touched):
    """Picks [rows, k] and weights that touch exactly ``touched`` of the held
    experts, spread evenly; with a share held the rest fall outside.  The
    same for both forms."""
    held, k = shape["held"], shape["k"]
    rng = np.random.default_rng((54, rows, touched))
    ids = np.sort(rng.choice(held, size=touched, replace=False))
    outside = held if shape["scored"] > held else None
    picked = np.empty((rows, k), np.int32)
    for n in range(rows):
        for j in range(k):
            at = n * k + j
            picked[n, j] = ids[at % touched] if (
                outside is None or at < touched or at % 2) else outside
    w = rng.uniform(0.1, 1.0, size=(rows, k)).astype(np.float32)
    return jnp.asarray(picked), jnp.asarray(w)


def layer(shape, kernel: bool, interpret: bool):
    """`routed_experts`'s batched form as a function of its operands, the
    kernel or the einsum whatever `moe_serve`'s rule would say of the shape
    (the rule is held open or shut while the form is traced)."""
    held = None if shape["scored"] == shape["held"] else (0, shape["held"])

    def one(x, picked, w, experts):
        rule = moe_serve.decode_kernel_serves
        moe_serve.decode_kernel_serves = lambda *_: kernel
        try:
            return moe_serve.routed_experts(
                x, picked, w, experts, shape["scored"], True, held,
                interpret)[0]
        finally:
            moe_serve.decode_kernel_serves = rule

    def looped(x, picked, w, experts):
        def body(_, x):
            return x + (1e-3 * one(x, picked, w, experts)).astype(x.dtype)
        return lax.fori_loop(0, LOOP, body, x)

    return one, looped


def built(fn, *args):
    """(compiled, seconds of trace / lower / compile)."""
    t0 = time.perf_counter()
    traced = jax.jit(fn).trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    return compiled, (t1 - t0, t2 - t1, t3 - t2)


def median_ms(compiled, *args, calls=CALLS) -> float:
    jax.block_until_ready(compiled(*args))
    took = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        took.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(took))


def read(cell, shape, rows, touched, kernel, interpret, dtype, out, note=""):
    x, experts = operands(shape, rows, dtype)
    picked, w = picks(shape, rows, touched)
    one, looped = layer(shape, kernel, interpret)
    args = (x, picked, w, experts)
    compiled, build = built(one, *args)
    call_ms = median_ms(compiled, *args)
    loop, _ = built(looped, *args)
    layer_ms = median_ms(loop, *args) / LOOP
    n = 3 if shape["gated"] else 2
    nbytes = n * shape["D"] * shape["F"] * jnp.dtype(dtype).itemsize
    row = {
        "cell": cell, "form": "kernel" if kernel else "einsum", "rows": rows,
        "held": shape["held"], "touched": touched, "note": note,
        "call_ms": call_ms, "layer_ms": layer_ms,
        "trace_s": build[0], "lower_s": build[1], "compile_s": build[2],
        "GBps_touched": touched * nbytes / layer_ms / 1e6,
        "GBps_held": shape["held"] * nbytes / layer_ms / 1e6}
    out.append(row)
    print(f"{cell:32s} {row['form']:6s} rows {rows:4d} touched "
          f"{touched:3d}/{shape['held']:3d} {note:10s} call {call_ms:7.3f} ms"
          f"  layer {layer_ms:7.3f} ms  ({row['GBps_touched']:6.1f} GB/s of "
          f"the touched, {row['GBps_held']:6.1f} of the held)  trace/lower/"
          f"compile {build[0]:.2f}/{build[1]:.2f}/{build[2]:.2f} s",
          flush=True)
    return compiled(*args)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--cells", default=None, help="comma-separated")
    ap.add_argument("--rows", default="", help="other rows, e.g. 256,512")
    ap.add_argument("--tiles", default="",
                    help="other TILE_VMEM_BYTES in MiB, e.g. 24,90")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    shapes = TINY if a.tiny else REAL
    if not a.tiny and jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: a CPU's time is no device number")
    dtype = jnp.float32 if a.tiny else jnp.bfloat16
    out = []
    for cell in (a.cells.split(",") if a.cells else shapes):
        shape = shapes[cell]
        held, rows = shape["held"], shape["rows"]
        at_share = max(1, round(shape["share"] * held))
        print(f"# {cell}: the shapes' expected share "
              f"{moe_serve.touched_share(rows, shape['k'], shape['scored']):.3f}"
              f", the ledger's {shape['share']}",
              flush=True)
        want = read(cell, shape, rows, held, False, a.tiny, dtype, out)
        got = read(cell, shape, rows, held, True, a.tiny, dtype, out)
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        print(f"  the kernel off the einsum by {err:.2e} of its norm",
              flush=True)
        out[-1]["off_einsum"] = err
        for touched in sorted({at_share, max(1, held // 2)} - {held}):
            read(cell, shape, rows, touched, True, a.tiny, dtype, out)
        for n in (int(r) for r in a.rows.split(",") if r):
            read(cell, shape, n, held, False, a.tiny, dtype, out)
            read(cell, shape, n, held, True, a.tiny, dtype, out)
        budget = moe_decode_pallas.TILE_VMEM_BYTES
        for mib in (int(t) for t in a.tiles.split(",") if t):
            moe_decode_pallas.TILE_VMEM_BYTES = mib * 2**20
            try:
                tile = moe_decode_pallas.hidden_tile(
                    shape["D"], shape["F"], 2 + shape["gated"],
                    jnp.dtype(dtype).itemsize)
            except ValueError as e:  # a width that has no such tile
                print(f"  {mib} MiB: {e}", flush=True)
                continue
            for touched in sorted({at_share, held}):
                read(cell, shape, rows, touched, True, a.tiny, dtype, out,
                     note=f"tile {tile}")
        moe_decode_pallas.TILE_VMEM_BYTES = budget
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": str(jax.devices()[0]), "rows": out}, f,
                      indent=1)


if __name__ == "__main__":
    main()
