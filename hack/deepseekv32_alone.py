"""The `deepseekv32` family's attention alone on the chip, at the shapes of the
cell `deepseekv32-chat-longctx-shared` (PR 53's readings in PERF.md section 6
and beside the constants of `models/deepseekv32.py`): one layer's pieces of a
decode step and of a hit prefill, each jitted alone over a pool of
latent-selected slots, the median of `CALLS` calls after a warm-up, in ms a
call.

    chiprun -- python hack/deepseekv32_alone.py [--out chiprun_out/pr53/alone.json]
    python hack/deepseekv32_alone.py --tiny     (the script's own paths, CPU)

Decode (32 sequences in fours over 8 shared contexts of ~33 k): the walked
index scores; the exact pick and the picks' rows; the gather of the picked
rows with attention over them (the kept form), the same with two rows a piece
gathered, and the paged latent kernel over EVERY block of a latent pool of the
same tables (the control: no selection); `_decode_attention` whole.  Hit
prefill (512 queries over 32 768 positions): the index scores, the pick, the
latent kernel under the picks (the kept form), and the per-head form the
published code takes for a prefill, in plain XLA (`per_head`: the context's
latents up-projected through `W_uk` and `W_uv` a chunk of positions at a time,
scores of 192 lanes and values of 128 a head under the same picks), whole and
its up-projection alone; and what a kernel could make of the per-head products:
JAX's own Pallas flash kernel over already up-projected K and V at head sizes
of 128 and 256 (it takes no 192 / 128 and no picks: the two bracket the form).
It fails where JAX finds no TPU: a CPU's time is no device number.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from llm_d_kv_cache_manager_tpu.models import deepseekv32 as m  # noqa: E402
from llm_d_kv_cache_manager_tpu.models import kv_cache_pool as kp  # noqa: E402
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas  # noqa: E402
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse  # noqa: E402
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (  # noqa: E402
    latent_picked_prefill_pallas,
)

CALLS = 10
REAL = dict(
    cfg=m.DeepseekV32Config(
        vocab_size=16160, d_model=7168, n_layers=5, n_heads=128, q_rank=1536,
        kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128, index_heads=64,
        index_dim=128, index_topk=2048, d_ff=18432, d_expert=2048,
        n_experts=256, held=(0, 16), top_k=8, n_group=8, topk_group=4,
        rope_original=4096),
    pool_blocks=20480, seqs=32, systems=8, prefix_blocks=2016, columns=2080,
    own_blocks=(33, 64), suffix=512, per_head_chunk=2048,
    score_waves=(32, 128, 256))
TINY = dict(
    cfg=m.DeepseekV32Config(n_heads=4, index_topk=32),
    pool_blocks=96, seqs=4, systems=2, prefix_blocks=10, columns=16,
    own_blocks=(3, 5), suffix=32, per_head_chunk=64, score_waves=(2,))


def timed(name, fn, *args, rows):
    """The median of CALLS calls of a jitted ``fn``, after two warm calls."""
    fn = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    ms = 1e3 * float(np.median(took))
    rows.append({"what": name, "ms": ms, "min_ms": 1e3 * min(took),
                 "first_s": first})
    print(f"{name:58s} {ms:9.3f} ms  (least {1e3 * min(took):.3f}; "
          f"first call {first:.1f} s)", flush=True)
    return out


def tables_of(size, rng):
    """The cell's kind of table: each context's blocks one run in the pool
    (as a fresh pool's allocator deals a prompt's blocks out), shared by
    seqs / systems sequences, then each sequence's own blocks, a run too."""
    seqs, systems, pre = size["seqs"], size["systems"], size["prefix_blocks"]
    table = np.zeros((seqs, size["columns"]), np.int32)
    ctx = np.zeros(seqs, np.int32)
    nxt = systems * pre
    for b in range(seqs):
        own = int(rng.integers(*size["own_blocks"]))
        table[b, :pre] = (b % systems) * pre + np.arange(pre)
        table[b, pre:pre + own] = nxt + np.arange(own)
        table[b, pre + own:] = table[b, pre + own - 1]
        nxt += own
        ctx[b] = (pre + own) * 16 - int(rng.integers(0, 16))
    assert nxt <= size["pool_blocks"]
    return jnp.asarray(table), jnp.asarray(ctx)


def latents_of(pool, table, cfg):
    """The latents [L, W] of one sequence's table, from a latent-selected
    pool: the table's rows gathered, their keys left aside."""
    W = cfg.latent_dim
    return kp.unpack_latent_blocks(pool[table[0]][..., :2 * W], cfg.kv_rank)


def up_projected(pool, table, w_kvb, *, cfg):
    """Every cached position's K (no-position lanes) and V a head:
    [H, L, dn + dv] in the serving type."""
    c = latents_of(pool, table, cfg)[:, :cfg.kv_rank]
    return jnp.einsum("kr,rhd->hkd", c, w_kvb,
                      preferred_element_type=jnp.float32).astype(w_kvb.dtype)


def per_head_prefill(qn, qr, pool, table, picked, w_kvb, *, cfg, chunk):
    """The per-head form of a hit's attention, plain XLA: qn [S, H, dn] and
    qr [S, H, dr] (rotated) over the table's positions, `chunk` of them at a
    time: their latents up-projected through w_kvb [Rkv, H, dn + dv], scores
    of dn + dr lanes a head plus the picks' bias, an online softmax, values
    of dv lanes.  -> [S, H, dv]."""
    act, f32 = qn.dtype, jnp.float32
    S, H, dn = qn.shape
    V, dr, dv = cfg.kv_rank, cfg.rope_dim, cfg.v_dim
    lat = latents_of(pool, table, cfg)
    L = lat.shape[0]
    c = lat[:, :V].reshape(L // chunk, chunk, V)
    kr = lat[:, V:].reshape(L // chunk, chunk, dr)
    bias = jnp.where(picked[0], 0.0, sparse.NEG_INF).astype(f32).reshape(
        S, L // chunk, chunk).swapaxes(0, 1)

    def step(carry, x):
        m, l, acc = carry
        c, kr, bias = x
        kv = jnp.einsum("kr,rhd->hkd", c, w_kvb,
                        preferred_element_type=f32).astype(act)
        s = (jnp.einsum("qhd,hkd->hqk", qn, kv[..., :dn],
                        preferred_element_type=f32)
             + jnp.einsum("qhr,kr->hqk", qr, kr, preferred_element_type=f32)
             ) * cfg.score_scale + bias[None]
        m2 = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m2[..., None])
        fix = jnp.exp(m - m2)
        acc = acc * fix[..., None] + jnp.einsum(
            "hqk,hkd->hqd", p.astype(act), kv[..., dn:],
            preferred_element_type=f32)
        return (m2, l * fix + p.sum(-1), acc), None

    init = (jnp.full((H, S), sparse.NEG_INF, f32), jnp.zeros((H, S), f32),
            jnp.zeros((H, S, dv), f32))
    (m, l, acc), _ = jax.lax.scan(step, init, (c, kr, bias))
    return (acc / l[..., None]).swapaxes(0, 1).astype(act)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    size = TINY if a.tiny else REAL
    if not a.tiny and jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: a CPU's time is no device number")
    interpret = a.tiny
    cfg = size["cfg"]
    spec = m.cache_groups(cfg)["full"]
    rng = np.random.default_rng(53)
    key = jax.random.key(53)
    act = jnp.bfloat16
    B, H, W, V = size["seqs"], cfg.n_heads, cfg.latent_dim, cfg.kv_rank
    HI, dI, K = cfg.index_heads, cfg.index_dim, cfg.index_topk
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    pool = jax.random.normal(k1, spec.layer_shape(size["pool_blocks"]), act)
    table, ctx = tables_of(size, rng)
    rows: list = []

    # ---------------------------------------------------------------- decode
    q = jax.random.normal(k2, (B, H, W), act)
    qi = jax.random.normal(k3, (B, HI, dI), act)
    w = jax.random.normal(k4, (B, HI), jnp.float32)
    scores = timed(
        "decode: latent_index_scores_pallas",
        lambda qi, w, pool, table, ctx: sparse.latent_index_scores_pallas(
            qi, w, pool, table, ctx, latent_dim=W, interpret=interpret),
        qi, w, pool, table, ctx, rows=rows)

    for waves in size["score_waves"]:
        timed(f"decode: latent_index_scores_pallas, waves of {waves} blocks",
              lambda qi, w, pool, table, ctx: sparse.latent_index_scores_pallas(
                  qi, w, pool, table, ctx, latent_dim=W, wave_blocks=waves,
                  interpret=interpret), qi, w, pool, table, ctx, rows=rows)

    def pick(scores, table):
        return sparse.picked_latent_rows(
            sparse.topk_mask(scores, K), table, K, cfg.block_size)

    timed("decode: topk_mask alone", lambda s: sparse.topk_mask(s, K), scores,
          rows=rows)
    picked_rows, second, _, valid = timed(
        "decode: topk_mask + picked_latent_rows", pick, scores, table,
        rows=rows)

    def attend(latent, valid, q):
        s = jnp.einsum("bhw,bkw->bhk", q, latent,
                       preferred_element_type=jnp.float32) * cfg.score_scale
        p = jax.nn.softmax(jnp.where(valid[:, None], s, sparse.NEG_INF), -1)
        return jnp.einsum("bhk,bkv->bhv", p.astype(latent.dtype),
                          latent[..., :V], preferred_element_type=jnp.float32)

    timed("decode: gather a row a pick + attention (kept)",
          lambda pool, r, s, valid, q: attend(
              kp.gather_picked_latents(spec, pool, r, s), valid, q),
          pool, picked_rows, second, valid, q, rows=rows)
    timed("decode: gather a row a pick alone",
          lambda pool, r, s: kp.gather_picked_latents(spec, pool, r, s),
          pool, picked_rows, second, rows=rows)

    lines_of = pool.reshape(-1, pool.shape[-1])
    for mode in ("clip", "promise_in_bounds"):
        timed(f"decode: gather a row a pick alone, mode {mode}",
              lambda lines, r: lines.at[r].get(mode=mode), lines_of,
              picked_rows, rows=rows)
    # what a pool of 32-bit rows would give a gather (no such pool exists:
    # two packed rows of the serving type a row, four positions)
    words = jax.random.bits(k6, (lines_of.shape[0] // 2, pool.shape[-1]),
                            jnp.uint32)
    timed("decode: gather a 32-bit row a pick alone (no such pool)",
          lambda words, r: jnp.take(words, r // 2, axis=0), words,
          picked_rows, rows=rows)
    del words

    def two_rows(pool, r, s, valid, q):
        """Two rows a piece (a whole 32-bit sublane of the packed tile)."""
        pairs = jnp.take(pool.reshape(-1, 2, pool.shape[-1]), r // 2, axis=0)
        lines = jnp.where((r % 2 == 1)[..., None], pairs[:, :, 1], pairs[:, :, 0])
        latent = jnp.where(
            s[..., None],
            jnp.concatenate((lines[..., 2 * W - V:2 * W],
                             lines[..., W:2 * W - V]), -1), lines[..., :W])
        return attend(latent, valid, q)

    timed("decode: gather two rows a pick + attention",
          two_rows, pool, picked_rows, second, valid, q, rows=rows)

    latent_spec = kp.KVGroupSpec(cfg.n_layers, cfg.block_size, 1, W, "bfloat16",
                                 latent_dim=W, value_dim=V)
    latent_pool = jax.random.normal(
        k5, latent_spec.layer_shape(size["pool_blocks"]), act)

    def dense(q, pool, table, ctx):
        view, layout = kp.decode_view(latent_spec, pool, kernel=True)
        return paged_decode_pallas.paged_decode_attention_pallas(
            q, view, table, ctx, scale=cfg.score_scale, interpret=interpret,
            **layout)

    timed("decode: paged latent kernel over EVERY block (control)",
          dense, q, latent_pool, table, ctx, rows=rows)
    timed("decode: _decode_attention whole",
          lambda q, qi, w, pool, table, ctx: m._decode_attention(
              q, qi, w, pool, table, ctx, cfg, interpret, None),
          q, qi, w, pool, table, ctx, rows=rows)

    # ------------------------------------------------------------ hit prefill
    S = size["suffix"]
    n = size["prefix_blocks"] + S // 16
    n = min(n, size["columns"])
    first = n * 16 - S
    hit_table = table[:1, :n]
    hq = jax.random.normal(k6, (1, S, H, W), act)
    hqi = jax.random.normal(k7, (S, HI, dI), act)
    hw = jax.random.normal(k4, (S, HI), jnp.float32)
    keys = timed("hit: gather_selector_keys",
                 lambda pool, t: kp.gather_selector_keys(spec, pool, t),
                 pool, hit_table, rows=rows)
    hs = timed(
        "hit: sparse_index_scores_pallas",
        lambda qi, w, k: sparse.sparse_index_scores_pallas(
            qi, w, k, q_offset=jnp.int32(first), interpret=interpret),
        hqi, hw, keys[0], rows=rows)
    picked = timed("hit: topk_mask", lambda s: sparse.topk_mask(s, K),
                   hs[None], rows=rows)
    timed(
        "hit: latent_picked_prefill_pallas (kept)",
        lambda q, pool, t, p: latent_picked_prefill_pallas(
            q, pool, t, p, q_offset=jnp.int32(first), value_dim=V,
            scale=cfg.score_scale, interpret=interpret),
        hq, pool, hit_table, picked, rows=rows)

    # The per-head form: the same attention, as the published code computes a
    # prefill.  A head's query is its 128 + 64 lanes (no fold through W_uk);
    # the context's latents go up through W_uk and W_uv, which a hit has to
    # do again for all its 33 k positions (the cache holds latents).
    dn, dr, dv = cfg.nope_dim, cfg.rope_dim, cfg.v_dim
    w_kvb = jax.random.normal(k5, (V, H, dn + dv), act) * V**-0.5
    qn = jax.random.normal(k2, (S, H, dn), act)
    chunk = size["per_head_chunk"]
    up = functools.partial(up_projected, cfg=cfg)
    per_head = functools.partial(per_head_prefill, cfg=cfg, chunk=chunk)

    timed("hit: per-head form, the up-projection of the context alone",
          up, pool, hit_table, w_kvb, rows=rows)
    timed(f"hit: per-head form under the picks, XLA, chunks of {chunk}",
          per_head, qn, hq[0, ..., V:], pool, hit_table, picked, w_kvb,
          rows=rows)
    if not a.tiny:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes, flash_attention,
        )
        L = n * 16
        for d in (128, 256):  # K of 192 and V of 128 lie between the two
            fq = jax.random.normal(k2, (1, H, S, d), act)
            fk = jax.random.normal(k3, (1, H, L, d), act)
            fv = jax.random.normal(k4, (1, H, L, d), act)
            timed(f"hit: per-head attention alone, JAX's flash kernel, "
                  f"head size {d}, no picks",
                  functools.partial(
                      flash_attention, causal=False, sm_scale=cfg.score_scale,
                      block_sizes=BlockSizes(block_q=512, block_k_major=1024,
                                             block_k=1024, block_b=1)),
                  fq, fk, fv, rows=rows)
            del fq, fk, fv
    if a.tiny:  # the two forms agree: fold the per-head queries and compare
        f32 = jnp.float32
        folded = jnp.concatenate((jnp.einsum(
            "qhn,rhn->qhr", qn.astype(f32), w_kvb[..., :dn].astype(f32)),
            hq[0, ..., V:].astype(f32)), -1).astype(act)[None]
        mine = latent_picked_prefill_pallas(
            folded, pool, hit_table, picked, q_offset=jnp.int32(first),
            value_dim=V, scale=cfg.score_scale, interpret=True)
        mine = jnp.einsum("qhr,rhv->qhv", mine[0].astype(f32),
                          w_kvb[..., dn:].astype(f32))
        other = per_head(qn, hq[0, ..., V:], pool, hit_table, picked, w_kvb)
        err = float(jnp.max(jnp.abs(mine - other.astype(f32)))
                    / jnp.max(jnp.abs(mine)))
        print(f"per-head against latent form, largest difference over the "
              f"largest value: {err:.4f}", flush=True)
        assert err < 0.05, err
    if a.out:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f:
            json.dump({"device": jax.devices()[0].device_kind, "rows": rows},
                      f, indent=1)


if __name__ == "__main__":
    main()
