"""The `deepseekv32` family on the pod path (DeepSeek-V3.2-Exp): latent
attention (MLA) whose queries read, of the latent cache, only the ``K``
positions a learned indexer scores best; the indexer's key cached in the
latent's slot; sparse experts picked within the best groups of them, of which
this chip holds a share; a rotation whose frequencies are rescaled (YaRN);
served through paged prefill, prefix-continue and decode over a pool of
latent-selected slots.

The layer equations (shapes from the model's public ``config.json``; what is
marked + is from the published modelling code and not from a key: each is
listed under ``assumed`` in the benchmark's configuration file).  ``D``
hidden, ``H`` heads, ``Rq`` / ``Rkv`` the query's and the cache's bottlenecks,
``dn`` / ``dr`` a head's key lanes without and with position, ``dv`` its value
lanes, ``HI`` / ``dI`` the indexer's heads and their size, ``K`` =
``index_topk``; ``RMS`` = RMSNorm with a learned weight and ``rms_norm_eps``.

- ``x = E[tokens]``; after the last layer ``logits = RMS_out(x) . W_head``
  (untied).  Layer l, + pre-norm: ``h = RMS_in(x)``, ``a = x + Attn(h)``,
  ``x' = a + FF_l(RMS_post(a))``.
- **Rotation** (YaRN, ``rope_scaling``): pair i of ``dr / 2`` turns by ``pos *
  f'_i``, ``f'`` the rescaled inverse frequencies (``layers.yarn_inv_freq``);
  cos and sin carry no factor (``mscale`` = ``mscale_all_dim``); + the score
  scale is ``(dn + dr)^(-1/2) m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``.
- **Attention**, as models/glm4moelite.py has it: ``cq = RMS_q(h . W_qa)``;
  ``q = cq . W_qb`` [H, dn + dr]; ``[c', kr'] = h . W_kva``; ``c = RMS_kv(c')``,
  ``kr = rope(kr')`` (+ lanes (2i, 2i + 1) turn together); ``kn_h = c .
  W_uk_h``, ``v_h = c . W_uv_h``; ``score_h(t, s) = (qn_h . kn_h(s) +
  rope(qr_h) . kr(s)) scale``, **softmax over ``S_t`` alone**; ``Attn = [o_0 |
  ... | o_(H-1)] . W_o``.
- **Indexer**: ``qI = cq . W_qI`` [HI, dI], from the query's normed
  bottleneck; ``kI = LN(h . W_kI)`` [dI], one a position (+ a LayerNorm with
  weight and bias, eps 1e-6); the first ``dr`` lanes of each ``qI_j`` and of
  ``kI`` turn with the same ``f'`` (+ lane i with lane i + dr/2 there), the
  others carry no position; ``w = h . W_w`` [HI], float32.  ``I(t, s) = sum_j
  w_j(t) relu(qI_j(t) . kI(s))``, s <= t (+ the published ``HI^(-1/2)`` and
  ``dI^(-1/2)`` are positive and common to all heads: left out).  ``S_t`` =
  the ``K`` positions s <= t with the largest ``I``, ties to the earlier; all
  of them while t < K; + nothing forced in; every layer picks anew.
- **The cache's slot**, a position a layer: ``[c | kr | kI]`` after norms and
  rotations, ``Rkv + dr + dI`` values in the serving type.
- **``FF_l``**, l < ``first_k_dense_replace``: SwiGLU of width
  ``intermediate_size``.  Else ``s = sigmoid(h . W_r)`` in float32 over all
  experts; ``n_group`` groups of neighbouring ids, a group's score the sum of
  its two largest ``s + b``; the ``topk_group`` best groups; the ``top_k``
  largest ``s + b`` within them; ``w = s[picked] / (sum s[picked] + 1e-20)
  routed_scaling_factor``; ``FF = sum_e w_e SwiGLU_e(h) + SwiGLU_shared(h)``;
  nothing dropped (``moe_serve.route`` with its groups, ``routed_experts``).
  *The chip's share.*  ``held = (first, count)``: the router, its bias and
  the group limit run over all experts; this chip computes the shared expert
  plus the part of the sum that the held experts give; ``w`` is normalised
  over all picks, absent ones included.  What the others would add is left
  out, by the reference too, which is handed the same ``held``.

**In the latent space** (what every step here computes; the plain reference at
the foot computes the per-head form): ``q~_h = W_uk_h . qn_h``; ``score = [q~_h
| rope(qr_h)] . [c | kr] scale``; ``o~_h = sum_s p c(s)``; ``o_h = o~_h .
W_uv_h``.  **Selection is exact**: the ``K`` best by ``I``, `lax.top_k`'s tie
rule; what differs from the plain reference is the precision of ``I``
(operands in the serving type, float32 sums), as models/keyevl2.py has it.

Each program writes its positions' slots first.  A prefill then attends a
chunk of queries at a time: the chunk's ``I`` over the table's selector keys
(``sparse_index_scores_pallas``), each row's ``K``-th largest by bisection
and the picks' mask (``topk_mask``, exact), and the latent kernel under that
mask over the pool's blocks where they lie (``latent_picked_prefill_pallas``);
a hit over its cached prefix with no gather, a miss the same from position 0
(in the latent space, where the published code takes the per-head form for a
prefill: both were read on the chip, the readings stand beside that kernel's
tile).
A decode step scores one query a sequence against its own table's keys where
the pool holds them (``latent_index_scores_pallas``, which copies a row's key
lanes alone), picks by the same bisection, and attends over the picked
positions' latents, gathered a row of the pool a pick (``_decode_attention``,
with the readings of the forms it was chosen from).

The cache has one group, ``"full"``, of ``KVGroupSpec``'s latent-selected
kind: a logical block of 16 positions owns one slot, [8, 2 x (Rkv + dr) + 2 x
dI] a layer, 1408 B a position a layer at the published sizes.  The pod
(models/pod.py) is the plain one-group prefix cache: the selector's keys share
the block's slot, hash and fate.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.models import layers, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, gather_picked_latents, gather_selector_keys, write_blocks,
    write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    embed, interpreted, logits, rms_norm, swiglu, yarn_inv_freq,
)
from llm_d_kv_cache_manager_tpu.ops import sparse_attention_pallas as sparse
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (
    latent_picked_prefill_pallas,
)

Params = Dict[str, Any]
HI = lax.Precision.HIGHEST
ROUTE_NORM_EPS = 1e-20
LN_EPS = 1e-6  # the selector key's LayerNorm
# A prefill's attention runs over this many query positions at a time: their
# scores over a 32 768-position table are 67 MB of float32, and so is the
# picks' bias the latent kernel reads; the chunk's heads in the latent space
# are 75 MB in the serving type.
ATTN_CHUNK_TOKENS = 512
# A feed-forward runs over at most this many tokens at a time: a 32 768-token
# miss through the dense layer's 18 432 lanes would be 2.4 GB of float32 a
# product.  Every chunk goes through the held experts in one batched product
# under the routing's mask (`moe_serve.routed_experts`): the form
# models/nemotronh.py reads faster up to 1024 tokens at a held share of its
# own (PERF.md section 6, PR 49), and the sorted form's rows are 8 picks a
# token of which 15 in 16 fall on other chips (0.94 GB of float32 for 4096
# tokens, twice: compiled for the v5e, PR 53).  A prefill's chunk touches
# every held expert and is the einsum; a decode step's 32 rows touch half of
# the 16, and its batched product is the kernel that copies those alone
# (1.04 ms a layer at 8 touched against 1.98; my chip run, PR 54;
# `moe_serve.decode_kernel_serves`).
FF_CHUNK_TOKENS = 1024


@dataclass(frozen=True)
class DeepseekV32Config:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_rank: int = 24  # Rq
    kv_rank: int = 32  # Rkv: the latent's value lanes
    nope_dim: int = 16  # dn
    rope_dim: int = 8  # dr
    v_dim: int = 16  # dv
    index_heads: int = 4  # HI
    index_dim: int = 16  # dI
    index_topk: int = 8  # K
    d_ff: int = 128  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's width; shared: n_shared times it
    n_experts: int = 8  # the router's width
    held: Tuple[int, int] = (0, 8)  # (first, count): the experts held here
    top_k: int = 2
    n_group: int = 4
    topk_group: int = 2
    n_shared: int = 1
    n_dense_layers: int = 1
    rope_theta: float = 1e4
    rope_factor: float = 40.0
    rope_original: int = 64  # original_max_position_embeddings
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    route_scale: float = 2.5
    block_size: int = 16
    dtype: str = "bfloat16"

    @property
    def latent_dim(self) -> int:
        """What the cache holds of a position a layer beside the selector's
        key: [c | kr]."""
        return self.kv_rank + self.rope_dim

    @property
    def experts_held(self) -> int:
        return self.held[1]

    @property
    def score_scale(self) -> float:
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    @property
    def decode_weight_nbytes(self) -> int:
        """The bytes of the weights one decode step reads where every held
        expert is touched: all but the embedding (of which a step looks up a
        row a sequence)."""
        D, H = self.d_model, self.n_heads
        attention = (D * self.q_rank + self.q_rank
                     + self.q_rank * H * (self.nope_dim + self.rope_dim)
                     + D * self.latent_dim + self.kv_rank
                     + self.kv_rank * H * (self.nope_dim + self.v_dim)
                     + H * self.v_dim * D + 2 * D)
        indexer = (self.q_rank * self.index_heads * self.index_dim
                   + D * self.index_dim + 2 * self.index_dim
                   + D * self.index_heads)
        expert = 3 * D * self.d_expert
        sparse_ff = (self.experts_held + self.n_shared) * expert \
            + D * self.n_experts + 2 * self.n_experts  # the bias is float32
        dense = 3 * D * self.d_ff
        count = (self.vocab_size * D + D
                 + self.n_layers * (attention + indexer)
                 + self.n_dense_layers * dense
                 + (self.n_layers - self.n_dense_layers) * sparse_ff)
        return count * jnp.dtype(self.dtype).itemsize


def cache_groups(cfg: DeepseekV32Config) -> Dict[str, KVGroupSpec]:
    """What one slot of the one group holds; models/pod.py and `new_pool`
    read block bytes and shapes from here."""
    return {"full": KVGroupSpec(
        cfg.n_layers, cfg.block_size, 1, cfg.latent_dim, cfg.dtype,
        latent_dim=cfg.latent_dim, value_dim=cfg.kv_rank,
        selector_dim=cfg.index_dim, selected=cfg.index_topk)}


def cache_policy(cfg: DeepseekV32Config) -> dict:
    """What models/pod.py needs to know of this family's cache: one group
    (the pod is the plain prefix cache), blocks that were asked for outlive
    those never asked, what a decode step reads beside the cache
    (`kv.read`'s `step_bytes`), and that a decode call launches the step
    after its own too (`decode_ahead`, models/pod.py's `jit_programs`), as
    the other long steps of this path do."""
    return {"specs": cache_groups(cfg), "protect_asked": True,
            "step_weight_nbytes": cfg.decode_weight_nbytes,
            "decode_ahead": True}


def new_pool(cfg: DeepseekV32Config, pool_blocks: int) -> dict:
    """The pod's pool as a pytree: one array a layer, each updated in place.
    (A step hands them back with one more leaf, `load`, that step's expert
    counts; it is not handed in again.)"""
    return layers.new_pool(cache_groups(cfg), {"full": pool_blocks})


def from_published(cfg: dict, block_size: int) -> DeepseekV32Config:
    """The program's configuration from the keys of the public
    ``config.json`` and, where the benchmark's file cuts it to one chip (the
    key states this chip's share: ``n_routed_experts`` is what is held, the
    router keeps the published width), its ``published`` and ``held`` groups.
    What the equations at the head do not cover is an error, not a default,
    and nothing is guessed."""
    for key, want in (
        ("attention_bias", False),
        ("hidden_act", "silu"),
        ("topk_method", "noaux_tc"),
        ("scoring_func", "sigmoid"),
        ("norm_topk_prob", True),
        ("tie_word_embeddings", False),
        ("moe_layer_freq", 1),
        ("num_nextn_predict_layers", 0),
    ):
        if cfg[key] != want:
            raise ValueError(
                f"deepseekv32: {key}={cfg[key]!r} is not implemented")
    if cfg["q_lora_rank"] is None:
        raise ValueError("deepseekv32: queries without a bottleneck "
                         "(q_lora_rank null) are not implemented")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("deepseekv32: the latent cache serves every head; "
                         "num_key_value_heads must equal num_attention_heads")
    if cfg["qk_rope_head_dim"] % 2 or (
            cfg["index_head_dim"] < cfg["qk_rope_head_dim"]):
        raise ValueError("deepseekv32: rope pairs lanes, and the indexer "
                         "turns its first qk_rope_head_dim lanes")
    scaling = cfg["rope_scaling"] or {}
    if scaling.get("type") != "yarn" or (
            scaling["mscale"] != scaling["mscale_all_dim"]):
        raise ValueError("deepseekv32: rope_scaling other than yarn with "
                         "mscale = mscale_all_dim is not implemented")
    held = cfg["n_routed_experts"]
    n_experts = cfg.get("published", {}).get("n_routed_experts", held)
    first = cfg.get("held", {}).get("experts_first", 0)
    if first + held > n_experts:
        raise ValueError("deepseekv32: the held experts lie past the router's")
    if n_experts % cfg["n_group"] or not (
            1 <= cfg["topk_group"] <= cfg["n_group"]) or (
            n_experts // cfg["n_group"] < 2):
        raise ValueError("deepseekv32: groups of at least two experts divide "
                         "the router's width, topk_group of them picked")
    return DeepseekV32Config(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"],
        index_heads=cfg["index_n_heads"],
        index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=n_experts,
        held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        n_shared=cfg["n_shared_experts"],
        n_dense_layers=cfg["first_k_dense_replace"],
        rope_theta=float(cfg["rope_theta"]),
        rope_factor=float(scaling["factor"]),
        rope_original=scaling["original_max_position_embeddings"],
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
    )


def init_params(rng: jax.Array, cfg: DeepseekV32Config) -> Params:
    """Seeded normal weights, fan-in scaled; norm weights and the selection
    bias are not constant, so that leaving one out of a step shows, and the
    indexer's head weights come out of both signs, so that heads vote against
    each other.  An expert layer's stacks hold the held experts only."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, E, Fe = cfg.d_model, cfg.n_heads, cfg.n_experts, cfg.d_expert
    HI_, dI = cfg.index_heads, cfg.index_dim
    keys = iter(jax.random.split(rng, 40 * cfg.n_layers + 8))

    def w(shape, fan_in):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * fan_in**-0.5
        ).astype(dtype)

    def norm(n, mean=1.0):
        return (
            mean + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

    def ff(width, lead=()):
        return {
            "w_gate": w(lead + (D, width), D),
            "w_up": w(lead + (D, width), D),
            "w_down": w(lead + (width, D), width),
        }

    layers_ = []
    for l in range(cfg.n_layers):
        lp = {
            "ln_in": norm(D),
            "ln_post": norm(D),
            "w_qa": w((D, cfg.q_rank), D),
            "q_norm": norm(cfg.q_rank),
            "w_qb": w((cfg.q_rank, H, cfg.nope_dim + cfg.rope_dim),
                      cfg.q_rank),
            "w_kva": w((D, cfg.latent_dim), D),
            "kv_norm": norm(cfg.kv_rank),
            "w_kvb": w((cfg.kv_rank, H, cfg.nope_dim + cfg.v_dim),
                       cfg.kv_rank),
            "wo": w((H, cfg.v_dim, D), H * cfg.v_dim),
            "w_qi": w((cfg.q_rank, HI_, dI), cfg.q_rank),
            "w_ki": w((D, dI), D),
            "ki_norm": norm(dI),
            "ki_bias": norm(dI, 0.0),
            "w_w": w((D, HI_), D),
        }
        if l < cfg.n_dense_layers:
            lp["mlp"] = ff(cfg.d_ff)
        else:
            lp["router"] = w((D, E), D)
            lp["route_bias"] = 0.05 * jax.random.normal(
                next(keys), (E,), jnp.float32
            )
            lp["shared"] = ff(cfg.n_shared * Fe)
            lp["experts"] = ff(Fe, (cfg.experts_held,))
        layers_.append(lp)
    return {
        "embed": w((cfg.vocab_size, D), D),
        "head": w((cfg.vocab_size, D), D),
        "ln_f": norm(D),
        "layers": layers_,
    }


# ------------------------------------------------------------ the model step


def _inv_freq(cfg):
    return yarn_inv_freq(cfg.rope_dim, cfg.rope_theta, cfg.rope_factor,
                         cfg.rope_original, cfg.rope_beta_fast,
                         cfg.rope_beta_slow)


def _angles(x, positions, cfg):
    """cos and sin of ``pos * f'``, shaped to x's [..., T, (n,) d]."""
    angles = positions[..., None].astype(jnp.float32) * _inv_freq(cfg)
    angles = angles.reshape(
        positions.shape + (1,) * (x.ndim - positions.ndim - 1)
        + (cfg.rope_dim // 2,))
    return jnp.cos(angles), jnp.sin(angles)


def _rope_pairs(x, positions, cfg):
    """x: float32 [..., T, n, dr] or [..., T, dr] with positions [..., T]:
    the lanes (2i, 2i + 1) turn together (attention's rotary lanes)."""
    cos, sin = _angles(x, positions, cfg)
    pairs = x.reshape(x.shape[:-1] + (cfg.rope_dim // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack((a * cos - b * sin, b * cos + a * sin),
                     axis=-1).reshape(x.shape)


def _rope_halves(x, positions, cfg):
    """x: float32 [..., T, n, dI] or [..., T, dI]: of its first dr lanes,
    lane i turns with lane i + dr/2; the others carry no position (the
    indexer's queries and key)."""
    cos, sin = _angles(x, positions, cfg)
    half = cfg.rope_dim // 2
    a, b, rest = (x[..., :half], x[..., half:cfg.rope_dim],
                  x[..., cfg.rope_dim:])
    return jnp.concatenate((a * cos - b * sin, b * cos + a * sin, rest), -1)


def _layer_norm(x, w, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + LN_EPS) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _cached(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> what the cache holds of its
    positions, each rounded once: the latent ``[RMS_kv(c') | rope(kr')]``
    [B, T, Rkv + dr] and the selector's key ``rope(LN(h . W_kI))``
    [B, T, dI]."""
    f32 = jnp.float32
    ckr = jnp.einsum("btd,dr->btr", h, lp["w_kva"], preferred_element_type=f32)
    c = rms_norm(ckr[..., :cfg.kv_rank], lp["kv_norm"], cfg.rms_eps)
    kr = _rope_pairs(ckr[..., cfg.kv_rank:], positions, cfg)
    ki = jnp.einsum("btd,dk->btk", h, lp["w_ki"], preferred_element_type=f32)
    ki = _rope_halves(_layer_norm(ki, lp["ki_norm"], lp["ki_bias"]),
                      positions, cfg)
    return (jnp.concatenate((c, kr), axis=-1).astype(h.dtype),
            ki.astype(h.dtype))


def _bottleneck(h, lp, cfg):
    """``cq = RMS_q(h . W_qa)`` [B, T, Rq] in the serving type: what the
    heads' queries and the indexer's are both made from."""
    cq = jnp.einsum("btd,dr->btr", h, lp["w_qa"],
                    preferred_element_type=jnp.float32)
    return rms_norm(cq, lp["q_norm"], cfg.rms_eps, h.dtype)


def _latent_query(cq, lp, positions, cfg):
    """cq: [B, T, Rq] -> each head's query in the latent space, ``[W_uk_h .
    qn_h | rope(qr_h)]``: [B, T, H, Rkv + dr] in the serving type (the
    scores' scale is the readers')."""
    f32, act = jnp.float32, cq.dtype
    q = jnp.einsum("btr,rhk->bthk", cq, lp["w_qb"], preferred_element_type=f32)
    qr = _rope_pairs(q[..., cfg.nope_dim:], positions, cfg)
    folded = jnp.einsum("bthn,rhn->bthr", q[..., :cfg.nope_dim].astype(act),
                        lp["w_kvb"][..., :cfg.nope_dim],
                        preferred_element_type=f32)
    return jnp.concatenate((folded, qr), axis=-1).astype(act)


def _index_queries(cq, h, lp, positions, cfg):
    """-> the indexer's queries [B, T, HI, dI] in the serving type (from the
    query's bottleneck) and its heads' weights [B, T, HI] float32 (from h)."""
    f32 = jnp.float32
    qi = jnp.einsum("btr,rhk->bthk", cq, lp["w_qi"], preferred_element_type=f32)
    w = jnp.einsum("btd,dh->bth", h, lp["w_w"], preferred_element_type=f32)
    return _rope_halves(qi, positions, cfg).astype(cq.dtype), w


def _attn_out(o_latent, lp, cfg):
    """``o~_h`` [B, T, H, Rkv] through ``W_uv`` and ``W_o``: [B, T, D]
    float32."""
    o = jnp.einsum("bthr,rhv->bthv", o_latent.astype(lp["wo"].dtype),
                   lp["w_kvb"][..., cfg.nope_dim:],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bthv,hvd->btd", o.astype(lp["wo"].dtype), lp["wo"],
                      preferred_element_type=jnp.float32)


def _in_chunks(x, chunk, limit: int):
    """``chunk`` ([n, D] float32 -> ([n, D] float32, counts)) over x
    [B, T, D], at most ``limit`` rows at a time where they divide evenly: ->
    ([B, T, D], the counts summed)."""
    out, counts = moe_serve.in_chunks(x, chunk, limit)
    return out.reshape(x.shape), counts


def _ff_block(x, lp, cfg, interpret):
    """a -> a + FF(RMS_post(a)), a chunk of tokens at a time (the norm too:
    a long miss holds no second stream), and the expert layer's counts: held
    experts with a pick, the most picks of one, all picks, the picks that
    fell on a held expert (None on a dense layer)."""
    act = lp["w_qa"].dtype  # the serving type

    def dense(rows):
        h = rms_norm(rows, lp["ln_post"], cfg.rms_eps, act)
        return swiglu(h, lp["mlp"]), jnp.zeros((), jnp.int32)

    def experts(rows):
        h = rms_norm(rows, lp["ln_post"], cfg.rms_eps)
        picked, w = moe_serve.route(
            h, lp["router"], lp["route_bias"], cfg.top_k, True,
            cfg.route_scale, ROUTE_NORM_EPS, n_group=cfg.n_group,
            topk_group=cfg.topk_group)
        out, sizes = moe_serve.routed_experts(
            h.astype(act), picked, w, lp["experts"], cfg.n_experts,
            batched=True, held=cfg.held, interpret=interpret)
        return swiglu(h.astype(act), lp["shared"]) + out, sizes

    if "mlp" in lp:
        return x + _in_chunks(x, dense, FF_CHUNK_TOKENS)[0], None
    y, sizes = _in_chunks(x, experts, FF_CHUNK_TOKENS)
    here = sizes[:-1]  # the last count: the picks that fell outside
    return x + y, jnp.stack((jnp.sum(here > 0), jnp.max(here), jnp.sum(sizes),
                             jnp.sum(here)))


def _finish(x, params, cfg, full, loads):
    pools = {"full": full, "load": jnp.stack(loads).astype(jnp.int32)}
    return logits(x, params, cfg), pools


def _prefill_attention(x, h, cq, lp, pool, table, first, cfg, interpret,
                       taps):
    """``x + Attn(h)`` for the positions ``first ..`` (x [B, T, D] float32;
    h its norm in the serving type, ``cq`` their queries' bottleneck) over
    the pool's blocks of ``table`` (which already hold these positions'
    slots), a chunk of queries at a time: [B, T, D] float32.  A chunk makes
    its own queries, scores them over the table's selector keys, picks,
    attends under the picks and adds to its piece of the stream (a long miss
    holds no second stream).  ``taps`` (a list, or None) is given the picks
    [B, T, positions]: the tests' window."""
    B, T, D = h.shape
    spec = cache_groups(cfg)["full"]
    interpret = interpreted(interpret)
    keys = gather_selector_keys(spec, pool, table)  # [B, L, dI]

    def attend(x, h, cq, at):
        positions = jnp.broadcast_to(at + jnp.arange(h.shape[1]), h.shape[:2])
        q = _latent_query(cq, lp, positions, cfg)
        qi, w = _index_queries(cq, h, lp, positions, cfg)
        scores = jnp.stack([
            sparse.sparse_index_scores_pallas(
                qi[b], w[b], keys[b], q_offset=at, interpret=interpret)
            for b in range(B)])  # [B, chunk, L]
        picked = sparse.topk_mask(scores, cfg.index_topk)
        o = latent_picked_prefill_pallas(
            q, pool, table, picked, q_offset=at, value_dim=cfg.kv_rank,
            scale=cfg.score_scale, interpret=interpret)
        out = x + _attn_out(o, lp, cfg)
        return (out, picked) if taps is not None else (out,)

    n = -(-T // ATTN_CHUNK_TOKENS)
    if T % n:
        n = 1
    if n == 1:
        out, *picked = attend(x, h, cq, jnp.int32(first))
    else:
        chunk = T // n
        starts = first + chunk * jnp.arange(n, dtype=jnp.int32)
        out, *picked = lax.map(
            lambda c: attend(*c),
            (x.reshape(B, n, chunk, D).swapaxes(0, 1),
             h.reshape(B, n, chunk, D).swapaxes(0, 1),
             cq.reshape(B, n, chunk, -1).swapaxes(0, 1), starts))
        out = out.swapaxes(0, 1).reshape(B, T, D)
        picked = [a.swapaxes(0, 1).reshape(B, T, -1) for a in picked]
    if taps is not None:
        taps.append(picked[0])
    return out


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
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["w_qa"].dtype)
        full[l] = write_blocks(spec, full[l], new,
                               *_cached(h, lp, positions, cfg))
        x = _prefill_attention(x, h, _bottleneck(h, lp, cfg), lp, full[l],
                               table, first, cfg, interpret, taps)
        x, load = _ff_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, loads)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    cfg: DeepseekV32Config,
    interpret: bool = False,
    taps: list | None = None,
):
    """Prefill writing each layer's latents and selector keys into the pool.
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
    cfg: DeepseekV32Config,
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
    (the walked kernel, which copies the rows' key lanes alone), the ``K``
    best (bisection and the picks in position order: `topk_mask`,
    `picked_latent_rows`), those positions' rows gathered from the pool a row
    a pick, the mirrored half turned back, and attention over them in the
    latent space: ``o~`` [B, H, Rkv] float32.

    The forms it was chosen from, read on the chip, kernel alone at the
    cell's shapes (32 sequences in fours over 8 contexts of ~33 k, 128 heads,
    one layer; ms a call, the median of ten, ~0.3 of launch in each; my chip
    runs, PR 53, three calls): the walked scores 1.50 / 1.51 / 1.50 (272 MB
    of keys; waves of 32 / 64 / 128 / 256 blocks 1.73 / 1.50 / 1.50 / 1.78);
    the pick 1.14 / 1.24 / 1.29 (the mask alone 0.69 / 0.74 / 0.76); the
    gather of a row a pick with attention over the rows 3.77 where the gather
    answers for rows outside the pool (`jnp.take`: the gather alone 3.76 /
    3.79) and **3.12** where the rows are promised in bounds, which is what
    `gather_picked_latents` does (the gather alone 2.29 / 2.35); two rows a
    pick (a whole 32-bit sublane of the packed tile) 10.8: the view
    re-lays-out the pool; rows of 32-bit words (no such pool) 3.65 / 3.70, so
    a row's width is not what holds the gather: 35 ns a row, 65 536 rows a
    layer.  The paged latent kernel over EVERY block of the same tables, with
    no pick to wait for, 5.00 / 4.94 / 5.03.  Whole: 4.88 / 4.84 with the
    first gather, **4.16** with the second; in the cell `itl_p50_s` 0.03415 s
    (a seed) and 0.03090 (six seeds)."""
    K = cfg.index_topk
    spec = cache_groups(cfg)["full"]
    scores = sparse.latent_index_scores_pallas(
        qi, w, pool, table, context_len, latent_dim=cfg.latent_dim,
        interpret=interpreted(interpret))
    rows, second, at, picked = sparse.picked_latent_rows(
        sparse.topk_mask(scores, K), table, K, cfg.block_size)
    if taps is not None:
        taps.append((at, picked))
    latent = gather_picked_latents(spec, pool, rows, second)  # [B, K, W]
    s = jnp.einsum("bhw,bkw->bhk", q, latent,
                   preferred_element_type=jnp.float32) * cfg.score_scale
    p = jax.nn.softmax(jnp.where(picked[:, None], s, sparse.NEG_INF), axis=-1)
    return jnp.einsum("bhk,bkv->bhv", p.astype(latent.dtype),
                      latent[..., :cfg.kv_rank],
                      preferred_element_type=jnp.float32)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    context_len: jnp.ndarray,
    cfg: DeepseekV32Config,
    interpret: bool = False,
    taps: list | None = None,
):
    """One decode step.  tokens: [B]; context_len: [B], the current token
    included; table: [B, max_blocks] logical blocks.  Writes each sequence's
    new latent and selector key a layer, picks and attends over the paged
    pool, and returns (logits [B, V], pools)."""
    bs = cfg.block_size
    pos = context_len - 1
    x = embed(params, tokens)[:, None]  # [B, 1, D]
    at = pos % bs
    ids = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    spec = cache_groups(cfg)["full"]
    full, loads = list(pools["full"]), []
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["w_qa"].dtype)
        cq = _bottleneck(h, lp, cfg)
        latent, ki = _cached(h, lp, pos[:, None], cfg)
        full[l] = write_token(spec, full[l], ids, at, latent[:, 0], ki[:, 0])
        q = _latent_query(cq, lp, pos[:, None], cfg)
        qi, w = _index_queries(cq, h, lp, pos[:, None], cfg)
        o = _decode_attention(q[:, 0], qi[:, 0], w[:, 0], full[l], table,
                              context_len, cfg, interpret, taps)
        x = x + _attn_out(o[:, None], lp, cfg)
        x, load = _ff_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, 0], params, cfg, full, loads)


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: DeepseekV32Config,
                     picks: list | None = None):
    """Logits [T, V] of one sequence by the equations at the head in their
    per-head form: float32, products at precision highest, keys and values
    made for every position and head, ``I`` as a whole causal array,
    `lax.top_k`, a dense softmax under the picks' mask, no cache, no kernels;
    every expert of ``params`` computed for every token and masked by the
    routing.  ``params`` stacks the experts ``cfg.held`` names of those the
    router scores; the picks outside that range add nothing.  ``picks`` (a
    list) is given each layer's picked sets, bool [T, T]."""
    f32 = jnp.float32
    p = jax.tree.map(lambda a: a.astype(f32), params)
    T = len(tokens)
    dn, dr = cfg.nope_dim, cfg.rope_dim
    first, count = cfg.held
    ang = jnp.arange(T, dtype=f32)[:, None] * _inv_freq(cfg)  # [T, dr/2]

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    def norm(x, w):
        return x * lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps) * w

    def turn(a, b):
        at = ang.reshape((T,) + (1,) * (a.ndim - 2) + (dr // 2,))
        return (a * jnp.cos(at) - b * jnp.sin(at),
                b * jnp.cos(at) + a * jnp.sin(at))

    def rope_pairs(x):  # [T, ..., dr]: lanes (2i, 2i + 1) turn together
        return jnp.stack(turn(x[..., 0::2], x[..., 1::2]), -1).reshape(x.shape)

    def rope_halves(x):  # [T, ..., dI]: of the first dr, i with i + dr/2
        return jnp.concatenate(
            turn(x[..., :dr // 2], x[..., dr // 2:dr]) + (x[..., dr:],), -1)

    def ff(h, w):
        return mm("tf,fd->td",
                  jax.nn.silu(mm("td,df->tf", h, w["w_gate"]))
                  * mm("td,df->tf", h, w["w_up"]), w["w_down"])

    x = jnp.take(p["embed"], jnp.asarray(tokens), axis=0)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    for lp in p["layers"]:
        h = norm(x, lp["ln_in"])
        cq = norm(mm("td,dr->tr", h, lp["w_qa"]), lp["q_norm"])
        q = mm("tr,rhk->thk", cq, lp["w_qb"])
        ckr = mm("td,dr->tr", h, lp["w_kva"])
        c = norm(ckr[:, :cfg.kv_rank], lp["kv_norm"])
        kr = rope_pairs(ckr[:, cfg.kv_rank:])  # one for all heads
        kv = mm("tr,rhk->thk", c, lp["w_kvb"])  # [T, H, dn + dv]
        qi = rope_halves(mm("tr,rhk->thk", cq, lp["w_qi"]))
        ki = mm("td,dk->tk", h, lp["w_ki"])
        mean = ki.mean(-1, keepdims=True)
        ki = rope_halves(
            (ki - mean) * lax.rsqrt(((ki - mean) ** 2).mean(-1, keepdims=True)
                                    + LN_EPS) * lp["ki_norm"] + lp["ki_bias"])
        index = mm("qj,qjt->qt", mm("td,dh->th", h, lp["w_w"]),
                   jax.nn.relu(mm("qjd,td->qjt", qi, ki)))
        best, where = lax.top_k(jnp.where(seen, index, -jnp.inf),
                                min(cfg.index_topk, T))
        picked = jnp.zeros((T, T), bool).at[
            jnp.arange(T)[:, None], where].set(best > -jnp.inf)
        if picks is not None:
            picks.append(picked)
        s = (mm("qhk,thk->hqt", q[..., :dn], kv[..., :dn])
             + mm("qhk,tk->hqt", rope_pairs(q[..., dn:]), kr)) * cfg.score_scale
        o = mm("hqt,thk->qhk",
               jax.nn.softmax(jnp.where(picked[None], s, -jnp.inf), -1),
               kv[..., dn:])
        x = x + mm("thk,hkd->td", o, lp["wo"])
        h = norm(x, lp["ln_post"])
        if "mlp" in lp:
            y = ff(h, lp["mlp"])
        else:
            s = jax.nn.sigmoid(mm("td,de->te", h, lp["router"]))
            choose = (s + lp["route_bias"]).reshape(T, cfg.n_group, -1)
            _, groups = lax.top_k(lax.top_k(choose, 2)[0].sum(-1),
                                  cfg.topk_group)
            kept = jnp.zeros((T, cfg.n_group), bool).at[
                jnp.arange(T)[:, None], groups].set(True)
            _, chosen = lax.top_k(jnp.where(
                kept[:, :, None], choose, -jnp.inf).reshape(T, -1), cfg.top_k)
            w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(1)
            w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
            w = w * cfg.route_scale
            y = ff(h, lp["shared"])
            for e in range(count):
                y = y + w[:, first + e:first + e + 1] * ff(
                    h, jax.tree.map(lambda a: a[e], lp["experts"]))
        x = x + y
    return mm("td,vd->tv", norm(x, p["ln_f"]), p["head"])
