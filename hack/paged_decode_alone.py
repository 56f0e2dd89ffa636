"""The paged decode kernels alone on the chip, at a cell's shapes (PR 41's
method, PR 52's readings in PERF.md section 6): one attention layer's shared
pass and walk over tables as the cell's traffic leaves them, `LAYERS` calls
chained in one jit, the least of `ROUNDS` rounds of `CALLS` calls, in ms a
call; and what tracing, lowering and compiling one call took.

    chiprun -- python hack/paged_decode_alone.py [--cells agentreason,...]
        [--parent .chipcheck/parent] [--out chiprun_out/pr52/alone.json]

Forms, each a row of the output: the tree's kernels as they are (`both`), with
copies and no products (`copies`), with products and no copies (`products`),
with every step of the shared pass brought a block at a time (`no_shared_runs`:
the plan's step runs zeroed) and with a step multiplied a block at a time
(`list_products`: sixteen products, their scores side by side, sixteen more),
and a try at the products a KV head at a time (`by_kv_head`; `--check`
compares its walk with the tree's); with `--parent`, a checkout of another
commit, the same of its kernels beside; `--dealt`: tables whose answers'
blocks were dealt one at a time, so that the walk finds few runs.
A form a tree's module cannot take is left out.  It fails where JAX finds no
TPU: a CPU's time is no device number.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

LAYERS, CALLS, ROUNDS = 4, 10, 3
BS = 16
# name: sequences, (H, Hkv, D), packed, pool blocks, table columns, shared
# prompts x their blocks x sequences on each, a turn's blocks and the most
# blocks of an answer behind it
CELLS = {
    "agentreason": (128, (32, 2, 128), False, 32768, 736, (8, 512, 16), 32, 192),
    "reasoning": (64, (40, 10, 128), False, 24576, 416, (8, 64, 8), 32, 320),
    "sysprompt": (32, (16, 8, 128), False, 3072, 192, (4, 128, 8), 32, 32),
    "agents": (64, (32, 8, 64), True, 16384, 576, (8, 512, 8), 32, 32),
    # `--rehearse`: the script's own paths, interpreted on the CPU
    "tiny": (8, (8, 2, 128), False, 512, 64, (2, 20, 4), 16, 4),
    "tiny_packed": (8, (8, 4, 64), True, 512, 64, (2, 20, 4), 16, 4),
}
INTERPRET = DEALT = False


def load(root: str, name: str):
    """`ops/paged_decode_pallas.py` of the checkout at ``root`` as a module of
    its own."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(
        root, "llm_d_kv_cache_manager_tpu", "ops", "paged_decode_pallas.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def tables(cell: str, seed: int = 52):
    B, _, _, pool, columns, (prompts, shared, sharers), turn, decoded = (
        CELLS[cell])
    rng = np.random.default_rng(seed)
    free = iter(range(1, pool))
    heads = [[next(free) for _ in range(shared)] for _ in range(prompts)]
    own = [turn + int(round(decoded * b / max(B - 1, 1))) for b in range(B)]
    rng.shuffle(own)
    # (the harness deals a request its turn's blocks and all the blocks of
    # its answer when it admits it: they ascend, and the walk's whole waves
    # are runs; `--dealt` deals the answer's one at a time among the
    # sequences, as an engine that allocates while it decodes would)
    rows = [heads[b // sharers] + [next(free) for _ in range(
        turn if DEALT else own[b])] for b in range(B)]
    for k in range(turn, max(own) if DEALT else 0):
        for b in range(B):
            if k < own[b]:
                rows[b].append(next(free))
    ctx = [len(r) * BS - int(rng.integers(0, BS)) for r in rows]
    table = np.zeros((B, columns), np.int32)
    for b, r in enumerate(rows):
        table[b, :len(r)] = r
    return jnp.asarray(table), jnp.asarray(ctx, jnp.int32)


class NoCopy:
    def start(self):
        pass

    wait = start


def list_products(module):
    """`_attend_rows` over a wave's buffer a block at a time: the products as
    they were before a step was one operand."""

    def attend(q, wave, other, seen, m_ref, l_ref, acc_ref, *, packed):
        P, rows = wave.shape[0], wave.shape[-2]
        dot = jax.lax.dot_general

        def of(i, which):
            block = wave[i] if packed else wave[i, which]
            return block.astype(q.dtype)

        s = jnp.concatenate([
            dot(q, of(i, 0), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for i in range(P)], axis=1)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
        s = s + other + jnp.where(row < seen, 0.0, module.NEG_INF)
        p, correction = module._softmax_update(s, None, m_ref, l_ref)
        p = p.astype(q.dtype)
        o = sum(dot(p[:, i * rows:(i + 1) * rows], of(i, 1),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) for i in range(P))
        acc_ref[...] = acc_ref[...] * correction + o

    return attend


def by_kv_head(module, heads: int):
    """A try, for 2 KV heads of bfloat16 rows (not the tree's form): a tile
    packs rows 2k and 2k + 1 in one 32-bit word, which are a position's two
    KV heads, so a step's keys come apart into a head's [P*bs, D] each by a
    shift and a mask, and each head's query rows meet their own head's keys
    alone: half the scores, no other head's columns to hide.  The states are
    kept head-major here, which is a sequence's own order only in the walk:
    what the shared pass leaves is in another order than the walk resumes
    from, so this form's time is a reading and its output is not the
    attention (`--check` compares the walk alone).  ``heads``: the query
    heads a KV head."""

    def attend(q, wave, other, seen, m_ref, l_ref, acc_ref, *, packed):
        P, _, rows, D = wave.shape
        R, n = q.shape[0], P * rows // 2
        dot = jax.lax.dot_general

        def halves(x):
            w = pltpu.bitcast(x.reshape(P * rows, D), jnp.uint32)  # [n, D]
            return [pltpu.bitcast(part, jnp.float32).astype(q.dtype)
                    for part in (w << 16, w & jnp.uint32(0xFFFF0000))]

        k, v = halves(wave[:, 0]), halves(wave[:, 1])
        qh = [jnp.concatenate([q[at + h * heads:at + (h + 1) * heads]
                               for at in range(0, R, 2 * heads)])
              for h in range(2)]
        pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        past = jnp.where(pos < seen // 2, 0.0, module.NEG_INF)
        s = jnp.concatenate([
            dot(qh[h], k[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for h in range(2)]) + past
        p, correction = module._softmax_update(s, None, m_ref, l_ref)
        p = p.astype(q.dtype)
        o = jnp.concatenate([
            dot(p[h * R // 2:(h + 1) * R // 2], v[h],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            for h in range(2)])
        acc_ref[...] = acc_ref[...] * correction + o

    return attend


def forms(module, tree: str, heads: int):
    """name: (patches of the module, edit of the plan)."""
    none = lambda plan: plan  # noqa: E731
    out = {
        "both": ({}, none),
        "copies": ({"_attend_rows": lambda *a, **k: None}, none),
        "products": ({"make_async_copy": lambda *a: NoCopy()}, none),
    }
    if tree == "change":
        def no_shared_runs(plan):
            *group, step_runs = plan["shared"]
            return {**plan, "shared": (*group, jnp.zeros_like(step_runs))}

        out["no_shared_runs"] = ({}, no_shared_runs)
        out["list_products"] = ({"_attend_rows": list_products(module)}, none)
        out["list_products_no_shared_runs"] = (
            {"_attend_rows": list_products(module)}, no_shared_runs)
        out["by_kv_head"] = (
            {"_attend_rows": by_kv_head(module, heads)}, none)
    return out


def inputs(module, cell: str, **plan_options):
    """q, the pool, the table, the contexts, the plan, and the pool's shape
    as the kernels see it."""
    B, (H, Hkv, D), packed, pool, _, _, _, _ = CELLS[cell]
    table, ctx = tables(cell)
    kq, kkv = jax.random.split(jax.random.PRNGKey(52))
    q = jax.random.normal(kq, (B, H, D), jnp.float32).astype(jnp.bfloat16)
    shape = (pool, BS, Hkv, 2 * D) if packed else (pool, 2, BS, Hkv, D)
    kv = jax.random.normal(kkv, shape, jnp.bfloat16)
    view = (pool,) + ((BS * Hkv, 2 * D) if packed else (2, BS * Hkv, D))
    plan = module.shared_prefix_plan(
        table, ctx, block_size=BS, blocks_per_wave=module.walk_wave(
            jax.ShapeDtypeStruct(view, jnp.bfloat16)), **plan_options)
    return q, kv, table, ctx, plan, view


@contextlib.contextmanager
def patched(module, patches: dict):
    """The module's attributes (`make_async_copy`: the Pallas TPU module's)
    replaced while a form is traced."""
    homes = {k: pltpu if k == "make_async_copy" else module for k in patches}
    saved = {k: getattr(homes[k], k) for k in patches}
    try:
        for k, v in patches.items():
            setattr(homes[k], k, v)
        yield
    finally:
        for k, v in saved.items():
            setattr(homes[k], k, v)


def measure(module, tree: str, cell: str, only) -> list:
    B, (H, Hkv, D), packed, _, _, _, _, _ = CELLS[cell]
    q, kv, table, ctx, plan, view = inputs(module, cell)
    counts = {k: int(plan[k]) for k in plan if k.endswith("_blocks")}
    shared_blocks = int(jnp.sum(plan["shared"][1]))
    lines = []
    for name, (patches, edit) in forms(module, tree, H // Hkv).items():
        if only and name not in only:
            continue
        call = module.paged_decode_attention_pallas.__wrapped__

        def layers(q, kv, table, ctx, plan, shared_only):
            x = q
            for _ in range(LAYERS):
                if shared_only:
                    flat = kv.reshape(view)
                    xq = jnp.concatenate((x, jnp.zeros_like(x)), -1) if (
                        packed) else x
                    kw = dict(blocks_per_step=module.SHARED_BLOCKS_PER_STEP,
                              interpret=INTERPRET, groups=H // Hkv,
                              scale=D**-0.5,
                              mxu_native=True, packed=packed)
                    if tree == "parent":
                        kw["kv_block"] = (1,) + view[1:]
                    out = module._shared_pass(xq, flat, table, plan, **kw)[2]
                    out = out[:B * H].reshape(B, H, -1)[..., -D:]
                else:
                    out = call(x, kv, table, ctx, plan=plan, packed=packed,
                               interpret=INTERPRET)
                x = q + (out * 1e-3).astype(q.dtype)
            return x

        line = {"tree": tree, "cell": cell, "form": name, **counts,
                "shared_blocks": shared_blocks, "rehearsal": INTERPRET,
                "dealt": DEALT}
        try:
            for part, shared_only in (("whole", False), ("shared", True)):
                args = (q, kv, table, ctx, edit(plan))
                t0 = time.perf_counter()
                with patched(module, patches):
                    lowered = jax.jit(
                        lambda *a: layers(*a, shared_only)).lower(*args)
                t1 = time.perf_counter()
                fn = lowered.compile()
                t2 = time.perf_counter()
                fn(*args).block_until_ready()
                best = float("inf")
                for _ in range(ROUNDS):
                    t = time.perf_counter()
                    for _ in range(CALLS):
                        out = fn(*args)
                    out.block_until_ready()
                    best = min(best, time.perf_counter() - t)
                line[f"{part}_ms"] = best / CALLS / LAYERS * 1e3
                line[f"{part}_trace_lower_s"] = t1 - t0
                line[f"{part}_compile_s"] = t2 - t1
            line["walk_ms"] = line["whole_ms"] - line["shared_ms"]
        except Exception as e:  # a form this tree's module cannot take
            line["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                          for k, v in line.items()}), flush=True)
        lines.append(line)
    return lines


def check_by_kv_head(module, cell: str) -> None:
    """The walk alone (a plan in which nobody shares), by KV head against the
    tree's form: the largest difference of the outputs."""
    B, (H, Hkv, _), _, _, _, _, _, _ = CELLS[cell]
    q, kv, table, ctx, plan, _ = inputs(module, cell, min_sequences=B + 1)
    call = module.paged_decode_attention_pallas.__wrapped__
    outs = []
    for patches in ({}, {"_attend_rows": by_kv_head(module, H // Hkv)}):
        with patched(module, patches):
            outs.append(np.asarray(jax.jit(
                lambda q, kv, plan: call(q, kv, table, ctx, plan=plan,
                                         interpret=INTERPRET))(
                q, kv, plan), np.float32))
    print(json.dumps({"check": "by_kv_head", "cell": cell,
                      "max_abs_diff": float(np.max(np.abs(outs[0] - outs[1]))),
                      "max_abs": float(np.max(np.abs(outs[0])))}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="agentreason")
    ap.add_argument("--forms", default="")
    ap.add_argument("--parent", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--dealt", action="store_true")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="interpreted, for the tiny cells: no device number")
    args = ap.parse_args()
    global INTERPRET, DEALT, LAYERS, CALLS, ROUNDS
    DEALT = args.dealt
    if args.rehearse:
        INTERPRET, LAYERS, CALLS, ROUNDS = True, 1, 1, 1
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a CPU's time is no device number")
    jax.config.update("jax_enable_compilation_cache", False)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trees = [("change", load(here, "paged_change"))]
    if args.parent:
        trees.insert(0, ("parent", load(args.parent, "paged_parent")))
    only = set(filter(None, args.forms.split(",")))
    lines = []
    for cell in args.cells.split(","):
        if args.check:
            check_by_kv_head(trees[-1][1], cell)
        for tree, module in trees:
            lines += measure(module, tree, cell, only)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind,
                       "lines": lines}, f, indent=1)


if __name__ == "__main__":
    main()
