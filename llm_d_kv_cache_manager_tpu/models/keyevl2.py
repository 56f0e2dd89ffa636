"""The `keyevl2` family on the pod path (Keye-VL-2.0-30B-A3B's language
model): grouped-query attention that reads, for every query, only the ``K``
cached positions a learned indexer scores best; a selector key cached beside
K and V; softmax-routed sparse experts with no shared one; served through
paged prefill, prefix-continue and decode over a pool of selected slots.

The layer equations (shapes from the model's public ``config.json``; what is
marked + is from the family's published modelling code or the published
sparse-attention description and not from a key: each is listed under
``assumed`` in the benchmark's configuration file).  ``D`` hidden, ``H``
query heads, ``G`` KV heads, ``dh`` a head's size, ``HI`` / ``dI`` the
indexer's heads and their size (``sa_config.indexer_num_heads``,
``indexer_head_dim``), ``K`` = ``sa_config.topk``; ``RMS`` = RMSNorm with a
learned weight and ``rms_norm_eps``.

- ``x = E[tokens]``; after the last layer ``logits = RMS_out(x) . W_head``
  (untied).  Layer l, + pre-norm: ``a = x + Attn(h)``, ``h = RMS_in(x)``;
  ``x' = a + MoE(RMS_post(a))``.
- **Attention.**  ``q_j = rope(RMS_qn(h . W_q)_j)`` [H, dh],
  ``k_g = rope(RMS_kn(h . W_k)_g)``, ``v_g = (h . W_v)_g`` [G, dh] (+ a norm
  a head on q and k before the rotation; + rope pairs lanes by halves, all
  dh of them, ``rope_theta``; ``mrope_section`` turns its three sections by
  the one text position, which is this).  **Indexer**:
  ``qI_j = rope(h . W_qI)_j`` [HI, dI]; ``kI = rope(LN(h . W_kI))`` [dI],
  one a position (+ a LayerNorm with weight and bias on the key; + rope over
  all dI lanes, same theta); ``w = h . W_w`` [HI], float32.
  ``I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))``, s <= t.  ``S_t`` = the
  ``K`` positions s <= t with the largest ``I(t, s)``, ties to the earlier
  position; all of them while t < K (+ no position is forced in).
  ``o_j(t) = sum_{s in S_t} p v_g(s)``, ``p = softmax_{s in S_t}(q_j(t) .
  k_g(s) / sqrt(dh))``, g = j // (H / G); ``Attn = [o_0 | ... | o_(H-1)] .
  W_o``.  **The cache's slot, a position a layer: ``k`` and ``v`` after norm
  and rotation, and ``kI`` after norm and rotation**, in the serving type.
  ``sa_config.q_chunk_size`` / ``kv_chunk_size`` are the tiles in which the
  published code computes ``I`` (+; they change no result).
- **``MoE``**: ``s = softmax(h . W_r)`` in float32 over all experts; the
  ``top_k`` largest; ``w = s[picked] / sum s[picked]`` (``norm_topk_prob``);
  ``MoE = sum_e w_e SwiGLU_e(h)``; no shared expert, no bias, no scaling:
  ``moe_serve.route(..., scores="softmax")`` and ``routed_experts``, batched
  under the routing's mask for a decode step and sorted by expert
  (``lax.ragged_dot``) in chunks for a prefill; nothing is dropped.

**Selection is exact**: the ``K`` best by ``I``, `lax.top_k`'s tie rule.
What differs from the plain reference is the precision of ``I`` (operands in
the serving type, sums in float32): positions within rounding of the
``K``-th score may swap (benchmarks/configs/README-keyevl2.md has the
reading).

Each of the three programs writes ``k``, ``v`` and ``kI`` of its positions
into the pool first.  A prefill then attends a chunk of queries at a time
(ops/sparse_attention_pallas.py): the chunk's ``I`` over the whole table
(one kernel), each row's ``K``-th largest by bisection and the picks' mask
(XLA, exact), and the flash kernel under that mask over the pool's blocks
where they lie; a hit over its cached prefix with no gather, a miss the same
from position 0, so no ``[T, T]`` array exists.  A decode step scores one
query a sequence against its own table's selector keys where the pool holds
them (a kernel that walks the table), picks by the same bisection, and
attends over the picked positions' tiles, gathered a tile a row
(``_decode_attention``, with the readings of the forms it was chosen from);
no sequence reads anything for another, so a step's cost does not depend on
which clients sit over one context.

The cache has one group, ``"full"``, of ``KVGroupSpec``'s selected kind: a
logical block of 16 positions owns one slot, [16 + 1, 2 * G, dh] a layer at
the published sizes (a tile a position, then the block's selector keys):
2176 B a position a layer.  The pod (models/pod.py) is the plain one-group
prefix cache: the selector's keys share the block's slot, hash and fate.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.models import layers, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, gather_picked_tiles, gather_selector_keys, write_blocks,
    write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    embed, interpreted, logits, rms_norm,
)
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse

Params = Dict[str, Any]
MOE_CHUNK_TOKENS = moe_serve.MOE_CHUNK_TOKENS
LN_EPS = 1e-6  # the selector key's LayerNorm
# A prefill's attention runs over this many query positions at a time: their
# scores over a 32 768-position table are 67 MB of float32, and so is the
# picks' bias the flash kernel reads.
ATTN_CHUNK_TOKENS = 512
# Chunks of a long miss whose last positions lie within the same span of
# this many share one loop, over the table up to the span's end.  Compiled
# for the v5e at the cell's sizes (a 32 768-token miss beside 6.25 GB of
# weights and a pool of 51 200 blocks), arguments + temporaries are 16.75 GB
# with spans of 8192 and 18.23 GB with one loop over the whole table, which
# the chip's 15.75 GiB (16.91 GB) do not hold; beside a small pool, where
# both fit, a miss is 1.084 s a call by spans and 1.342 s by one loop, and
# compiles in 30.8 s against 13.6 (my chip run, PR 44).
ATTN_SPAN_TOKENS = 8192


@dataclass(frozen=True)
class KeyeVl2Config:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    index_heads: int = 4  # HI
    index_dim: int = 8  # dI
    index_topk: int = 8  # K
    d_expert: int = 32
    n_experts: int = 8
    top_k: int = 2
    rope_theta: float = 1e7
    rms_eps: float = 1e-6
    block_size: int = 16
    dtype: str = "bfloat16"

    @property
    def decode_weight_nbytes(self) -> int:
        """The bytes of the weights one decode step reads where every expert
        is touched: all but the embedding (of which a step looks up a row a
        sequence)."""
        D, H, G, dh = self.d_model, self.n_heads, self.n_kv_heads, self.head_dim
        attention = 2 * D * H * dh + 2 * D * G * dh + 2 * dh + 2 * D
        indexer = (D * self.index_heads * self.index_dim
                   + D * self.index_dim + 2 * self.index_dim
                   + D * self.index_heads)
        experts = self.n_experts * 3 * D * self.d_expert + D * self.n_experts
        count = (self.vocab_size * D + D
                 + self.n_layers * (attention + indexer + experts))
        return count * jnp.dtype(self.dtype).itemsize


def cache_groups(cfg: KeyeVl2Config) -> Dict[str, KVGroupSpec]:
    """What one slot of the one group holds; models/pod.py and `new_pool`
    read block bytes and shapes from here."""
    return {"full": KVGroupSpec(
        cfg.n_layers, cfg.block_size, cfg.n_kv_heads, cfg.head_dim, cfg.dtype,
        selector_dim=cfg.index_dim, selected=cfg.index_topk)}


def cache_policy(cfg: KeyeVl2Config) -> dict:
    """What models/pod.py needs to know of this family's cache: one group
    (the pod is the plain prefix cache), blocks that were asked for outlive
    those never asked, what a decode step reads beside the cache
    (`kv.read`'s `step_bytes`), and that a decode call launches the step
    after its own too (`decode_ahead`, models/pod.py's `jit_programs`: the
    host's 1.9 ms of a 15.5-ms step swung with the machine by more than the
    benchmark admits a cell under, PERF.md section 6)."""
    return {"specs": cache_groups(cfg), "protect_asked": True,
            "step_weight_nbytes": cfg.decode_weight_nbytes,
            "decode_ahead": True}


def new_pool(cfg: KeyeVl2Config, pool_blocks: int) -> dict:
    """The pod's pool as a pytree: one array a layer, each updated in place.
    (A step hands them back with one more leaf, `load`, that step's expert
    counts; it is not handed in again.)"""
    return layers.new_pool(cache_groups(cfg), {"full": pool_blocks})


def from_published(cfg: dict, block_size: int) -> KeyeVl2Config:
    """The program's configuration from the keys of the public
    ``config.json``.  What the equations at the head do not cover is an
    error, not a default, and nothing is guessed."""
    for key, want in (
        ("use_sliding_window", False),
        ("mlp_only_layers", []),
        ("decoder_sparse_step", 1),
        ("attention_bias", False),
        ("norm_topk_prob", True),
        ("tie_word_embeddings", False),
        ("hidden_act", "silu"),
    ):
        if cfg[key] != want:
            raise ValueError(f"keyevl2: {key}={cfg[key]!r} is not implemented")
    if (cfg["rope_scaling"] or {}).get("rope_type", "default") != "default":
        raise ValueError("keyevl2: rope_scaling other than rope_type "
                         "default is not implemented")
    sa = cfg.get("sa_config")
    if not sa:
        raise ValueError("keyevl2: a configuration without sa_config (the "
                         "indexer's shapes) is not implemented")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("keyevl2: the cache holds one selector key a "
                         "position: indexer_num_kv_heads must be 1")
    if cfg["num_local_experts"] != cfg["num_experts"]:
        raise ValueError("keyevl2: num_local_experts differs from "
                         "num_experts; every expert is held here")
    return KeyeVl2Config(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
    )


def init_params(rng: jax.Array, cfg: KeyeVl2Config) -> Params:
    """Seeded normal weights, fan-in scaled; norm weights are not constant,
    so that leaving one out of a step shows, and the indexer's head weights
    come out of both signs, so that heads vote against each other."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, G, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    HI, dI, E, Fe = cfg.index_heads, cfg.index_dim, cfg.n_experts, cfg.d_expert
    keys = iter(jax.random.split(rng, 32 * cfg.n_layers + 8))

    def w(shape, fan_in):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * fan_in**-0.5
        ).astype(dtype)

    def norm(n, mean=1.0):
        return (
            mean + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

    layers = [{
        "ln_in": norm(D), "ln_post": norm(D),
        "wq": w((D, H, dh), D), "wk": w((D, G, dh), D),
        "wv": w((D, G, dh), D), "wo": w((H, dh, D), H * dh),
        "q_norm": norm(dh), "k_norm": norm(dh),
        "w_qi": w((D, HI, dI), D), "w_ki": w((D, dI), D),
        "ki_norm": norm(dI), "ki_bias": norm(dI, 0.0),
        "w_w": w((D, HI), D),
        "router": w((D, E), D),
        "experts": {"w_gate": w((E, D, Fe), D), "w_up": w((E, D, Fe), D),
                    "w_down": w((E, Fe, D), Fe)},
    } for _ in range(cfg.n_layers)]
    return {"embed": w((cfg.vocab_size, D), D),
            "head": w((cfg.vocab_size, D), D), "ln_f": norm(D),
            "layers": layers}


# ------------------------------------------------------------ the model step


def _layer_norm(x, w, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + LN_EPS) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _rope(x, positions, theta):
    """x: float32 [B, T, n, d] or [B, T, d] with positions [B, T]: lane i
    turns with lane i + d/2 by ``pos * theta^(-2i/d)``."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs
    angles = angles.reshape(
        positions.shape + (1,) * (x.ndim - positions.ndim - 1) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), -1)


def _queries(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> q [B, T, H, dh] in the serving
    type.  Norm and rope run on the product's float32 sums, so q is rounded
    once."""
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"],
                   preferred_element_type=jnp.float32)
    return _rope(rms_norm(q, lp["q_norm"], cfg.rms_eps), positions,
                 cfg.rope_theta).astype(h.dtype)


def _index_queries(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> the indexer's queries
    [B, T, HI, dI] in the serving type and its heads' weights [B, T, HI]
    float32."""
    f32 = jnp.float32
    qi = jnp.einsum("btd,dhk->bthk", h, lp["w_qi"], preferred_element_type=f32)
    w = jnp.einsum("btd,dh->bth", h, lp["w_w"], preferred_element_type=f32)
    return _rope(qi, positions, cfg.rope_theta).astype(h.dtype), w


def _cached(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> what the cache holds of its
    positions: k and v [B, T, G, dh] and the selector's key [B, T, dI], in the
    serving type, after norm and rotation, each rounded once."""
    f32, act = jnp.float32, h.dtype
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"], preferred_element_type=f32)
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"], preferred_element_type=f32)
    ki = jnp.einsum("btd,dk->btk", h, lp["w_ki"], preferred_element_type=f32)
    k = _rope(rms_norm(k, lp["k_norm"], cfg.rms_eps), positions,
              cfg.rope_theta)
    ki = _rope(_layer_norm(ki, lp["ki_norm"], lp["ki_bias"]), positions,
               cfg.rope_theta)
    return k.astype(act), v.astype(act), ki.astype(act)


def _attn_out(o, lp):
    return jnp.einsum("bthk,hkd->btd", o.astype(lp["wo"].dtype), lp["wo"],
                      preferred_element_type=jnp.float32)


def _moe(h, lp, cfg, interpret):
    """h: [B, T, D] float32 -> (the routed experts' sum, float32; picks per
    expert [E]).  Batched under the routing's mask for at most as many
    tokens as experts (a decode step: 24 sequences pick 192 times among 128
    experts and touch 99 of them in the cell; the batched form there is the
    kernel that copies the touched experts alone,
    `moe_serve.decode_kernel_serves`), sorted by expert above (a prefill)."""
    act = lp["router"].dtype  # the serving type

    def chunk(rows):
        picked, w = moe_serve.route(rows, lp["router"], None, cfg.top_k,
                                    True, 1.0, scores="softmax")
        return moe_serve.routed_experts(
            rows.astype(act), picked, w, lp["experts"], cfg.n_experts,
            batched=picked.shape[0] <= cfg.n_experts, interpret=interpret)

    out, sizes = moe_serve.in_chunks(h, chunk, MOE_CHUNK_TOKENS)
    return out.reshape(h.shape), sizes


def _ff_block(x, lp, cfg, interpret):
    """a -> a + MoE(RMS_post(a)), and the layer's load."""
    y, sizes = _moe(rms_norm(x, lp["ln_post"], cfg.rms_eps), lp, cfg,
                    interpret)
    return x + y, jnp.stack((jnp.sum(sizes > 0), jnp.max(sizes)))


def _finish(x, params, cfg, full, loads):
    pools = {"full": full, "load": jnp.stack(loads).astype(jnp.int32)}
    return logits(x, params, cfg), pools


def _prefill_attention(h, lp, pool, table, first, cfg, interpret, taps):
    """``Attn`` of the positions ``first ..`` of h [B, T, D] (serving type)
    over the pool's blocks of ``table`` (which already hold these positions'
    slots), a chunk of queries at a time: [B, T, D] float32.  A chunk makes
    its own queries, so that a long miss holds no query of every position at
    once; chunks that end within the same ``ATTN_SPAN_TOKENS`` share one loop
    over the table up to that span's end, so that an early chunk of a long
    miss neither scores nor bisects over positions that lie after it.
    ``taps`` (a list, or None) is given the picks [B, T, positions]: the
    tests' window."""
    B, T, D = h.shape
    bs = cfg.block_size
    spec = cache_groups(cfg)["full"]
    interpret = interpreted(interpret)

    def attend(table, keys, h, at):
        positions = jnp.broadcast_to(at + jnp.arange(h.shape[1]), h.shape[:2])
        q = _queries(h, lp, positions, cfg)
        qi, w = _index_queries(h, lp, positions, cfg)
        scores = jnp.stack([
            sparse.sparse_index_scores_pallas(qi[b], w[b], keys[b], q_offset=at,
                                       interpret=interpret)
            for b in range(B)])  # [B, chunk, L]
        picked = sparse.topk_mask(scores, cfg.index_topk)
        o = sparse.sparse_prefill_attention_pallas(
            q, pool, table, picked, q_offset=at, interpret=interpret)
        out = _attn_out(o, lp)
        return (out, picked) if taps is not None else (out,)

    n = -(-T // ATTN_CHUNK_TOKENS)
    if T % n:
        n = 1
    chunk = T // n
    outs, picks = [], []
    # the chunks by the span their last position lies in
    for _, group in itertools.groupby(
            range(n), lambda i: -(-(first + (i + 1) * chunk)
                                  // ATTN_SPAN_TOKENS)):
        done, *_, last = 2 * list(group)
        count = last + 1 - done
        seen = table[:, :-(-(first + (last + 1) * chunk) // bs)]
        keys = gather_selector_keys(spec, pool, seen)  # [B, L, dI]
        hs = h[:, done * chunk:(done + count) * chunk]
        starts = first + chunk * (done + jnp.arange(count, dtype=jnp.int32))
        if count == 1:
            out, *picked = attend(seen, keys, hs, starts[0])
        else:
            out, *picked = lax.map(
                lambda c: attend(seen, keys, *c),
                (hs.reshape(B, count, chunk, D).swapaxes(0, 1), starts))
            out = out.swapaxes(0, 1).reshape(B, count * chunk, D)
            picked = [a.swapaxes(0, 1).reshape(B, count * chunk, -1)
                      for a in picked]
        outs.append(out)
        picks += [jnp.pad(a, ((0, 0), (0, 0),
                              (0, table.shape[1] * bs - a.shape[-1])))
                  for a in picked]
    if taps is not None:
        taps.append(jnp.concatenate(picks, axis=1))
    return jnp.concatenate(outs, axis=1)


def _prefill(params, tokens, pools, table, first, cfg, interpret, taps):
    """The positions ``first ..`` of a prompt over ``table`` ([B, blocks from
    position 0]); each layer writes its slots, then attends over the pool."""
    B, T = tokens.shape
    bs = cfg.block_size
    if first % bs or T % bs:
        raise ValueError("a prefill starts and ends on block boundaries")
    positions = jnp.broadcast_to(first + jnp.arange(T), (B, T))
    new = table[:, first // bs:(first + T) // bs]
    x = embed(params, tokens)
    spec = cache_groups(cfg)["full"]
    full, loads = list(pools["full"]), []
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["wq"].dtype)
        k, v, ki = _cached(h, lp, positions, cfg)
        full[l] = write_blocks(spec, full[l], new, k, v, ki)
        x = x + _prefill_attention(h, lp, full[l], table, first, cfg,
                                   interpret, taps)
        x, load = _ff_block(x, lp, cfg, interpret)
        loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, loads)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    cfg: KeyeVl2Config,
    interpret: bool = False,
    taps: list | None = None,
):
    """Prefill writing each layer's K/V and selector keys into the pool.
    tokens: [B, T], T a multiple of the block size; table: [B, T/block]
    logical blocks in chain order.  Returns (logits of the last position
    [B, 1, V], pools)."""
    return _prefill(params, tokens, pools, table, 0, cfg, interpret, taps)


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    prefix_len: int,
    cfg: KeyeVl2Config,
    interpret: bool = False,
    taps: list | None = None,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).  tokens:
    [B, S] suffix; table: [B, (prefix_len + S)/block], the prefix's blocks
    then the blocks to write; ``prefix_len`` is static.  The suffix picks
    and attends over the prefix where the pool holds it.  Returns (logits of
    the last position [B, 1, V], pools)."""
    return _prefill(params, tokens, pools, table, prefix_len, cfg, interpret,
                    taps)


def _decode_attention(q, qi, w, pool, table, context_len, cfg, interpret,
                      taps):
    """One query a sequence: its scores over its own table's selector keys
    (the walked kernel), the ``K`` best (bisection and the picks in position
    order: `topk_mask`, `picked_tiles`), those positions' tiles gathered from
    the pool a tile a row, and attention over them: [B, H, dh] float32.

    The form is the fastest of those read on the chip, kernel alone at the
    cell's shapes (24 sequences of ~33 k, one layer; milliseconds a call,
    ~0.3 of launch in each; my chip runs, PR 44, two calls).  Scores: walked 1.10 / 1.11, selector tiles
    gathered and scored in XLA 2.38 / 2.32.  The pick: bisection with
    `picked_tiles` 1.11 (the mask alone 0.64 / 0.61), `lax.top_k` 1.72 /
    1.69, bisection with a scatter 4.58 / 4.63.  Bringing the picks and
    attending: this gather of tiles 1.48 (2.02 where each pick's slot is
    first looked up in the table by a gather of single numbers), a kernel
    with a copy a position 2.92 / 2.98, the paged walk over EVERY block 3.24
    / 3.22 (with no pick to wait for).  Whole: this 2.09; walked + `lax.top_k`
    + gather 3.26; walked + `lax.top_k` + copies 4.22 / 4.18; all XLA 4.51 /
    4.46.  In the cell `itl_p50_s` 0.01548 s with this form (six seeds),
    0.02017 with `lax.top_k`, 0.02355 all XLA (a seed each)."""
    B, H, dh = q.shape
    G, K = cfg.n_kv_heads, cfg.index_topk
    spec = cache_groups(cfg)["full"]
    scores = sparse.sparse_decode_scores_pallas(
        qi, w, pool, table, context_len, selector_dim=cfg.index_dim,
        interpret=interpreted(interpret))
    tiles, at, picked = sparse.picked_tiles(
        sparse.topk_mask(scores, K), table, K, cfg.block_size,
        spec.slot_tiles)
    if taps is not None:
        taps.append((at, picked))
    k, v = gather_picked_tiles(spec, pool, tiles)  # [B, K, G, dh]
    s = jnp.einsum("bghd,bkgd->bghk", q.reshape(B, G, H // G, dh), k,
                   preferred_element_type=jnp.float32) * dh**-0.5
    p = jax.nn.softmax(jnp.where(picked[:, None, None], s, sparse.NEG_INF),
                       axis=-1)
    o = jnp.einsum("bghk,bkgd->bghd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, dh)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    context_len: jnp.ndarray,
    cfg: KeyeVl2Config,
    interpret: bool = False,
    taps: list | None = None,
):
    """One decode step.  tokens: [B]; context_len: [B], the current token
    included; table: [B, max_blocks] logical blocks.  Writes each sequence's
    new tile and selector key a layer, picks and attends over the paged pool,
    and returns (logits [B, V], pools)."""
    bs = cfg.block_size
    pos = context_len - 1
    x = embed(params, tokens)[:, None]  # [B, 1, D]
    at = pos % bs
    ids = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    spec = cache_groups(cfg)["full"]
    full, loads = list(pools["full"]), []
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["wq"].dtype)
        q = _queries(h, lp, pos[:, None], cfg)
        qi, w = _index_queries(h, lp, pos[:, None], cfg)
        k, v, ki = _cached(h, lp, pos[:, None], cfg)
        full[l] = write_token(spec, full[l], ids, at, k[:, 0], v[:, 0],
                              ki[:, 0])
        o = _decode_attention(q[:, 0], qi[:, 0], w[:, 0], full[l], table,
                              context_len, cfg, interpret, taps)
        x = x + _attn_out(o[:, None], lp)
        x, load = _ff_block(x, lp, cfg, interpret)
        loads.append(load)
    return _finish(x[:, 0], params, cfg, full, loads)
