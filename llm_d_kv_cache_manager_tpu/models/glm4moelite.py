"""The `glm4moelite` family on the pod path (GLM-4.7-Flash): latent attention
(MLA) whose cache is ONE vector a position a layer, sparse experts with a
shared one, served through paged prefill, prefix-continue and decode over a
pool of latent slots.

The layer equations (shapes from the model's public ``config.json``; what is
marked + is from the published ``glm4_moe_lite`` / latent-attention modelling
code and not from a key: each is listed under ``assumed`` in the benchmark's
configuration file).  ``D`` hidden, ``H`` heads, ``Rq`` / ``Rkv`` the query's
and the cache's bottlenecks (``q_lora_rank``, ``kv_lora_rank``), ``dn`` / ``dr``
a head's key lanes without and with position (``qk_nope_head_dim``,
``qk_rope_head_dim``), ``dv`` its value lanes; ``RMS`` = RMSNorm with a
learned weight and ``rms_norm_eps``.

- ``x = E[tokens]``; after the last layer ``logits = RMS_out(x) . W_head``
  (untied).  Layer l, + pre-norm: ``a = x + Attn(RMS_in(x))``;
  ``x' = a + FF_l(RMS_post(a))``.
- **Attention.**  ``cq = RMS_q(h . W_qa)`` [Rq] (+ a norm on each
  bottleneck); ``q = cq . W_qb`` [H, dn + dr], split ``qn_h`` [dn], ``qr_h``
  [dr].  ``[c', kr'] = h . W_kva`` [Rkv + dr]; ``c = RMS_kv(c')``,
  ``kr = rope(kr')``: one of each a position, and **the cache's slot is
  ``[c | kr]``, Rkv + dr values in the serving type, after the norm and after
  the rotation**.  ``W_kvb`` [Rkv, H, dn + dv] gives ``kn_h = c . W_uk_h``
  [dn] and ``v_h = c . W_uv_h`` [dv].  ``rope`` over all dr lanes of ``qr_h``
  and ``kr'`` (``partial_rotary_factor`` 1), ``rope_theta``, no scaling; + the
  lanes pair interleaved, (2i, 2i + 1) turning together by
  ``pos * theta^(-2i/dr)``, as the latent-attention code this family derives
  from.  ``score_h(t, s) = (qn_h(t) . kn_h(s) + rope(qr_h)(t) . kr(s)) /
  sqrt(dn + dr)``, causal softmax over s, ``o_h = sum_s p v_h(s)``,
  ``Attn = [o_0 | ... | o_(H-1)] . W_o``.
- **In the latent space** (what every step here computes; the plain reference
  at the foot and the benchmark's compute the per-head form):
  ``q~_h = W_uk_h . qn_h`` [Rkv];
  ``score = [q~_h | rope(qr_h)] . [c | kr] / sqrt(dn + dr)``;
  ``o~_h = sum_s p c(s)`` [Rkv]; ``o_h = o~_h . W_uv_h``.  Keys and values
  per head are never made: a prefill writes its positions' slots first and
  attends over the pool where it lies (ops/latent_prefill_pallas.py: a hit
  over its cached prefix with no gather, a miss the same kernel from position
  0, a chunk of queries at a time, so that no length needs a ``[T, T]`` array
  or a head's whole K and V in VMEM); a decode step writes one slot a
  sequence and attends through the paged kernel's latent form
  (ops/paged_decode_pallas.py: a block read once for scores and values).
  Products in the serving type with float32 sums.
- **``FF_l``**, l < ``first_k_dense_replace``: SwiGLU of width
  ``intermediate_size``.  Else ``s = sigmoid(h . W_r)`` in float32 over the
  experts; pick the ``top_k`` largest of ``s + b`` (+ ``b`` a selection bias a
  layer, in the selection only: ``topk_method`` ``noaux_tc``; ``n_group`` 1:
  no group limit); ``w = s[picked] / (sum s[picked] + 1e-20) x
  routed_scaling_factor`` (+ the 1e-20); ``FF = sum_e w_e SwiGLU_e(h) +
  SwiGLU_shared(h)``, each of width ``moe_intermediate_size``:
  ``moe_serve.route`` and ``routed_experts`` with this family's keys, batched
  under the routing's mask for a decode step and sorted by expert
  (``lax.ragged_dot``) in chunks for a prefill; nothing is dropped.

The cache has one group, ``"full"``, of ``KVGroupSpec``'s latent kind: a
logical block of 16 positions owns one slot, [8, 2 x (Rkv + dr)] a layer (two
positions a row: ``kv_cache_pool.pack_latent_blocks``), 1152 B a position a
layer at the published sizes against 20 480 B for K and V per head.  The pod
(models/pod.py) is the plain one-group prefix cache: its hashes, events and
index know nothing of what a slot holds.

``reference_logits`` is the plain float32 forward pass of the equations in
their per-head form: no cache, no kernels, no latent-space identity.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.models import layers, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, decode_view, write_blocks, write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    embed, interpreted, logits, rms_norm, swiglu,
)
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.latent_prefill_pallas import (
    latent_prefill_attention_pallas,
)
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

Params = Dict[str, Any]
HI = lax.Precision.HIGHEST
ROUTE_NORM_EPS = 1e-20
MOE_CHUNK_TOKENS = moe_serve.MOE_CHUNK_TOKENS
# A prefill's attention runs over at most this many query positions at a
# time: a position's heads in the latent space are H x (Rkv + dr) numbers
# (46 KB in float32 at the published sizes), 0.75 GB for 16 384 of them in
# one piece and several such pieces live at once.
ATTN_CHUNK_TOKENS = 2048
# The prefill kernel's tile of query positions and pool blocks a step, and
# the pool blocks a wave of the decode kernel's walk and a step of its shared
# pass (slots of 18 KB, a quarter of the `llama` slots': more of them make a
# wave).  Read on the chip, kernel alone at the cell's shapes (PERF.md
# section 6, PR 42): a hit's layer 2.93 / 2.74 ms at tiles of 64 / 128
# positions, 32 blocks a step (64 a step: 2.88 / 3.78); a decode step's
# layer 5.10 / 4.19 / 3.81 ms at waves of 16 / 32 / 64 blocks.  With a wave
# that is a run in the pool brought by one copy (PR 43; 15 of a 16k-token
# document's 16 waves are), waves of 32 / 64 / 128: 2.02 / 1.97 / 2.02 ms
# over the cell's kind of table, 4.27 / 3.84 / 3.65 over blocks in no order.
PREFILL_Q_TILE = 128
PREFILL_BLOCKS_PER_STEP = 32
DECODE_BLOCKS_PER_WAVE = 64
# How many live sequences have to begin with the same blocks before a decode
# step reads the run once for them (the kernel's shared pass) and not once a
# sequence (its walk).  Agents over repositories, one each, meet over a
# context two or three at a time, by chance and for a while.  With every
# such meeting taken (the plan's default, 2) a step's cost followed who had
# met whom: in the cell the 18 % of the blocks that pairs shared took 11 %
# off the step, a pair more or less 0.9 %, and `itl_p50_s` spread 1.0-1.7 %
# from seed to seed on one program; with 4 it spreads 0.2-0.3 % (PERF.md
# section 6, PR 42).  A prompt that many sequences begin with is read once,
# as in the other families.
SHARED_MIN_SEQUENCES = 4


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 3
    n_heads: int = 4
    q_rank: int = 24  # Rq
    kv_rank: int = 32  # Rkv: the latent's value lanes
    nope_dim: int = 16  # dn
    rope_dim: int = 8  # dr
    v_dim: int = 16  # dv
    d_ff: int = 128  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's width; shared: n_shared times it
    n_experts: int = 8
    top_k: int = 2
    n_shared: int = 1
    n_dense_layers: int = 1
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    route_scale: float = 1.8
    block_size: int = 16
    dtype: str = "bfloat16"

    @property
    def latent_dim(self) -> int:
        """What the cache holds of a position a layer: [c | kr]."""
        return self.kv_rank + self.rope_dim

    @property
    def score_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def decode_weight_nbytes(self) -> int:
        """The bytes of the weights one decode step reads where every expert
        is touched: all but the embedding (of which a step looks up a row a
        sequence)."""
        D, H = self.d_model, self.n_heads
        attention = (D * self.q_rank + self.q_rank
                     + self.q_rank * H * (self.nope_dim + self.rope_dim)
                     + D * self.latent_dim + self.kv_rank
                     + self.kv_rank * H * (self.nope_dim + self.v_dim)
                     + H * self.v_dim * D + 2 * D)
        expert = 3 * D * self.d_expert
        sparse = (self.n_experts + self.n_shared) * expert \
            + D * self.n_experts + 2 * self.n_experts  # the bias is float32
        dense = 3 * D * self.d_ff
        count = (self.vocab_size * D + D + self.n_layers * attention
                 + self.n_dense_layers * dense
                 + (self.n_layers - self.n_dense_layers) * sparse)
        return count * jnp.dtype(self.dtype).itemsize


def cache_groups(cfg: Glm4MoeLiteConfig) -> Dict[str, KVGroupSpec]:
    """What one slot of the one group holds; models/pod.py and `new_pool`
    read block bytes and shapes from here."""
    return {"full": KVGroupSpec(
        cfg.n_layers, cfg.block_size, 1, cfg.latent_dim, cfg.dtype,
        latent_dim=cfg.latent_dim, value_dim=cfg.kv_rank)}


def cache_policy(cfg: Glm4MoeLiteConfig) -> dict:
    """What models/pod.py needs to know of this family's cache: one group
    (the pod is the plain prefix cache), blocks that were asked for outlive
    those never asked, and what a decode step reads beside the cache, so
    that the pod can say what share of a step's bytes the cache is
    (`kv.read`)."""
    return {"specs": cache_groups(cfg), "protect_asked": True,
            "step_weight_nbytes": cfg.decode_weight_nbytes}


def new_pool(cfg: Glm4MoeLiteConfig, pool_blocks: int) -> dict:
    """The pod's pool as a pytree: one array a layer, each updated in place.
    (A step hands them back with more leaves, `load` and `attention_read`,
    that step's counts; they are not handed in again.)"""
    return layers.new_pool(cache_groups(cfg), {"full": pool_blocks})


def from_published(cfg: dict, block_size: int) -> Glm4MoeLiteConfig:
    """The program's configuration from the keys of the public
    ``config.json``.  What the equations at the head do not cover is an
    error, not a default, and nothing is guessed."""
    for key, want in (
        ("n_group", 1),
        ("topk_group", 1),
        ("rope_scaling", None),
        ("attention_bias", False),
        ("hidden_act", "silu"),
        ("topk_method", "noaux_tc"),
        ("norm_topk_prob", True),
        ("tie_word_embeddings", False),
        ("partial_rotary_factor", 1),
    ):
        if cfg[key] != want:
            raise ValueError(
                f"glm4moelite: {key}={cfg[key]!r} is not implemented")
    if cfg["q_lora_rank"] is None:
        raise ValueError("glm4moelite: queries without a bottleneck "
                         "(q_lora_rank null) are not implemented")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("glm4moelite: the latent cache serves every head; "
                         "num_key_value_heads must equal num_attention_heads")
    if cfg["qk_rope_head_dim"] % 2:
        raise ValueError("glm4moelite: rope pairs lanes: qk_rope_head_dim "
                         "must be even")
    return Glm4MoeLiteConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_shared=cfg["n_shared_experts"],
        n_dense_layers=cfg["first_k_dense_replace"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
    )


def init_params(rng: jax.Array, cfg: Glm4MoeLiteConfig) -> Params:
    """Seeded normal weights, fan-in scaled; norm weights and the selection
    bias are not constant, so that leaving one out of a step shows."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, E, Fe = cfg.d_model, cfg.n_heads, cfg.n_experts, cfg.d_expert
    keys = iter(jax.random.split(rng, 32 * cfg.n_layers + 8))

    def w(shape, fan_in):
        return (
            jax.random.normal(next(keys), shape, jnp.float32) * fan_in**-0.5
        ).astype(dtype)

    def norm(n):
        return (
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

    def swiglu(width, lead=()):
        return {
            "w_gate": w(lead + (D, width), D),
            "w_up": w(lead + (D, width), D),
            "w_down": w(lead + (width, D), width),
        }

    layers = []
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
        }
        if l < cfg.n_dense_layers:
            lp["mlp"] = swiglu(cfg.d_ff)
        else:
            lp["router"] = w((D, E), D)
            lp["route_bias"] = 0.05 * jax.random.normal(
                next(keys), (E,), jnp.float32
            )
            lp["shared"] = swiglu(cfg.n_shared * Fe)
            lp["experts"] = swiglu(Fe, (E,))
        layers.append(lp)
    return {
        "embed": w((cfg.vocab_size, D), D),
        "head": w((cfg.vocab_size, D), D),
        "ln_f": norm(D),
        "layers": layers,
    }


# ------------------------------------------------------------ the model step


def _rope(x, positions, theta):
    """x: [..., T, n, dr] or [..., T, dr] with positions [..., T]: the lanes
    (2i, 2i + 1) turn together by ``pos * theta^(-2i/dr)``."""
    dr = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dr // 2, dtype=jnp.float32) / (dr // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs
    angles = angles.reshape(
        positions.shape + (1,) * (x.ndim - positions.ndim - 1) + (dr // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dr // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack((a * cos - b * sin, b * cos + a * sin),
                     axis=-1).reshape(x.shape)


def _latent(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> the cache's slots of its
    positions, [B, T, Rkv + dr] in the serving type: ``[RMS_kv(c') |
    rope(kr')]``, rounded once."""
    ckr = jnp.einsum("btd,dr->btr", h, lp["w_kva"],
                     preferred_element_type=jnp.float32)
    c = rms_norm(ckr[..., :cfg.kv_rank], lp["kv_norm"], cfg.rms_eps)
    kr = _rope(ckr[..., cfg.kv_rank:], positions, cfg.rope_theta)
    return jnp.concatenate((c, kr), axis=-1).astype(h.dtype)


def _latent_query(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> each head's query in the latent
    space, ``[W_uk_h . qn_h | rope(qr_h)]``: [B, T, H, Rkv + dr] in the
    serving type (the scores' scale is the kernels')."""
    f32, act = jnp.float32, h.dtype
    cq = jnp.einsum("btd,dr->btr", h, lp["w_qa"], preferred_element_type=f32)
    cq = rms_norm(cq, lp["q_norm"], cfg.rms_eps, act)
    q = jnp.einsum("btr,rhk->bthk", cq, lp["w_qb"],
                   preferred_element_type=f32)
    qr = _rope(q[..., cfg.nope_dim:], positions, cfg.rope_theta)
    folded = jnp.einsum("bthn,rhn->bthr", q[..., :cfg.nope_dim].astype(act),
                        lp["w_kvb"][..., :cfg.nope_dim],
                        preferred_element_type=f32)
    return jnp.concatenate((folded, qr), axis=-1).astype(act)


def _attn_out(o_latent, lp, cfg):
    """What the kernels return, ``o~_h`` [B, T, H, Rkv], through ``W_uv`` and
    ``W_o``: [B, T, D] float32."""
    o = jnp.einsum("bthr,rhv->bthv", o_latent,
                   lp["w_kvb"][..., cfg.nope_dim:],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bthv,hvd->btd", o.astype(lp["wo"].dtype), lp["wo"],
                      preferred_element_type=jnp.float32)


def _moe(h, lp, cfg, interpret):
    """h: [B, T, D] float32 -> (Shared(h) + routed experts, float32; picks
    per expert [E]).  Batched under the routing's mask for at most as many
    tokens as experts (a decode step: 64 sequences pick 256 times among 64
    experts and touch 57 of them in the cell; the batched form there is the
    kernel that copies the touched experts alone,
    `moe_serve.decode_kernel_serves`), sorted by expert above (a prefill)."""
    act = lp["router"].dtype  # the serving type

    def chunk(rows):
        picked, w = moe_serve.route(
            rows, lp["router"], lp["route_bias"], cfg.top_k, True,
            cfg.route_scale, ROUTE_NORM_EPS)
        return moe_serve.routed_experts(
            rows.astype(act), picked, w, lp["experts"], cfg.n_experts,
            batched=picked.shape[0] <= cfg.n_experts, interpret=interpret)

    out, sizes = moe_serve.in_chunks(h, chunk, MOE_CHUNK_TOKENS)
    return swiglu(h.astype(act), lp["shared"]) + out.reshape(h.shape), sizes


def _ff_block(x, lp, cfg, interpret):
    """a -> a + FF(RMS_post(a)), and the expert layer's load (None on a
    dense layer)."""
    h = rms_norm(x, lp["ln_post"], cfg.rms_eps)
    if "mlp" in lp:
        return x + swiglu(h.astype(lp["mlp"]["w_up"].dtype), lp["mlp"]), None
    y, sizes = _moe(h, lp, cfg, interpret)
    return x + y, jnp.stack((jnp.sum(sizes > 0), jnp.max(sizes)))


def _finish(x, params, cfg, full, loads):
    pools = {"full": full, "load": jnp.stack(loads).astype(jnp.int32)}
    return logits(x, params, cfg), pools


def _prefill_attention(h, lp, pool, table, first, cfg, interpret):
    """``Attn`` of the positions ``first ..`` of h [B, T, D] (serving type)
    over the pool's blocks of ``table`` (which already hold these positions'
    slots), a chunk of queries at a time: [B, T, D] float32."""
    B, T, D = h.shape

    def attend(h, at):
        positions = jnp.broadcast_to(at + jnp.arange(h.shape[1]),
                                     h.shape[:2])
        o = latent_prefill_attention_pallas(
            _latent_query(h, lp, positions, cfg), pool, table, q_offset=at,
            value_dim=cfg.kv_rank, scale=cfg.score_scale,
            q_tile=PREFILL_Q_TILE, blocks_per_step=PREFILL_BLOCKS_PER_STEP,
            interpret=interpreted(interpret))
        return _attn_out(o, lp, cfg)

    n = -(-T // ATTN_CHUNK_TOKENS)
    if T % n:
        n = 1
    if n == 1:
        return attend(h, jnp.int32(first))
    chunks = h.reshape(B, n, T // n, D).swapaxes(0, 1)
    starts = first + (T // n) * jnp.arange(n, dtype=jnp.int32)
    out = lax.map(lambda c: attend(*c), (chunks, starts))
    return out.swapaxes(0, 1).reshape(B, T, D)


def _prefill(params, tokens, pools, table, first, cfg, interpret):
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
                               _latent(h, lp, positions, cfg))
        x = x + _prefill_attention(h, lp, full[l], table, first, cfg,
                                   interpret)
        x, load = _ff_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, loads)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    cfg: Glm4MoeLiteConfig,
    interpret: bool = False,
):
    """Prefill writing each layer's latents into the pool.  tokens: [B, T],
    T a multiple of the block size; table: [B, T/block] logical blocks in
    chain order.  Returns (logits of the last position [B, 1, V], pools)."""
    return _prefill(params, tokens, pools, table, 0, cfg, interpret)


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    prefix_len: int,
    cfg: Glm4MoeLiteConfig,
    interpret: bool = False,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).  tokens:
    [B, S] suffix; table: [B, (prefix_len + S)/block], the prefix's blocks
    then the blocks to write; ``prefix_len`` is static.  The suffix attends
    over the prefix where the pool holds it.  Returns (logits of the last
    position [B, 1, V], pools)."""
    return _prefill(params, tokens, pools, table, prefix_len, cfg, interpret)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    table: jnp.ndarray,
    context_len: jnp.ndarray,
    cfg: Glm4MoeLiteConfig,
    interpret: bool = False,
):
    """One decode step.  tokens: [B]; context_len: [B], the current token
    included; table: [B, max_blocks] logical blocks.  Writes each sequence's
    new slot a layer, attends in the latent space over the paged pool, and
    returns (logits [B, V], pools)."""
    bs = cfg.block_size
    pos = context_len - 1
    x = embed(params, tokens)[:, None]  # [B, 1, D]
    at = pos % bs
    ids = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    spec = cache_groups(cfg)["full"]
    full, loads = list(pools["full"]), []
    # Which sequences' tables begin with the same blocks, once for all
    # layers: every layer sees this table.
    plan = paged_decode_pallas.shared_prefix_plan(
        table, context_len, block_size=bs, min_sequences=SHARED_MIN_SEQUENCES,
        blocks_per_wave=DECODE_BLOCKS_PER_WAVE,
        shared_blocks_per_step=DECODE_BLOCKS_PER_WAVE)
    for l, lp in enumerate(params["layers"]):
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["w_qa"].dtype)
        full[l] = write_token(
            spec, full[l], ids, at, _latent(h, lp, pos[:, None], cfg)[:, 0])
        pool, layout = decode_view(spec, full[l], kernel=True)
        o = paged_decode_attention_pallas(
            _latent_query(h, lp, pos[:, None], cfg)[:, 0], pool, table,
            context_len, scale=cfg.score_scale,
            plan=plan, walk_blocks_per_wave=DECODE_BLOCKS_PER_WAVE,
            shared_blocks_per_step=DECODE_BLOCKS_PER_WAVE,
            interpret=interpreted(interpret), **layout)
        x = x + _attn_out(o[:, None], lp, cfg)
        x, load = _ff_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    logits, pools = _finish(x[:, 0], params, cfg, full, loads)
    pools["attention_read"] = paged_decode_pallas.attention_read_counts(plan)
    return logits, pools


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: Glm4MoeLiteConfig):
    """Logits [T, V] of one sequence by the equations at the head in their
    per-head form: float32, products at precision highest, keys and values
    made for every position and head, no cache, no kernels, every expert
    computed for every token and masked by the routing."""
    f32 = jnp.float32
    p = jax.tree.map(lambda a: a.astype(f32), params)
    T = len(tokens)
    dn, dr = cfg.nope_dim, cfg.rope_dim

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    def norm(x, w):
        return x * lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps) * w

    def rope(x):  # [T, ..., dr]: lanes (2i, 2i + 1) turn together
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, dr // 2, dtype=f32) / (dr // 2))
        ang = (jnp.arange(T, dtype=f32)[:, None] * freqs).reshape(
            (T,) + (1,) * (x.ndim - 2) + (dr // 2,))
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack((a * jnp.cos(ang) - b * jnp.sin(ang),
                          b * jnp.cos(ang) + a * jnp.sin(ang)),
                         -1).reshape(x.shape)

    def swiglu(h, w):
        return mm("tf,fd->td",
                  jax.nn.silu(mm("td,df->tf", h, w["w_gate"]))
                  * mm("td,df->tf", h, w["w_up"]), w["w_down"])

    x = jnp.take(p["embed"], jnp.asarray(tokens), axis=0)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    for lp in p["layers"]:
        h = norm(x, lp["ln_in"])
        q = mm("tr,rhk->thk", norm(mm("td,dr->tr", h, lp["w_qa"]),
                                   lp["q_norm"]), lp["w_qb"])
        ckr = mm("td,dr->tr", h, lp["w_kva"])
        c = norm(ckr[:, :cfg.kv_rank], lp["kv_norm"])
        kr = rope(ckr[:, cfg.kv_rank:])  # one for all heads
        kv = mm("tr,rhk->thk", c, lp["w_kvb"])  # [T, H, dn + dv]
        s = (mm("qhk,thk->hqt", q[..., :dn], kv[..., :dn])
             + mm("qhk,tk->hqt", rope(q[..., dn:]), kr)) * cfg.score_scale
        o = mm("hqt,thk->qhk",
               jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1),
               kv[..., dn:])
        x = x + mm("thk,hkd->td", o, lp["wo"])
        h = norm(x, lp["ln_post"])
        if "mlp" in lp:
            y = swiglu(h, lp["mlp"])
        else:
            s = jax.nn.sigmoid(mm("td,de->te", h, lp["router"]))
            _, picked = lax.top_k(s + lp["route_bias"], cfg.top_k)
            chosen = jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1)
            w = s * chosen
            w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
            w = w * cfg.route_scale
            y = swiglu(h, lp["shared"])
            for e in range(cfg.n_experts):
                y = y + w[:, e:e + 1] * swiglu(
                    h, jax.tree.map(lambda a: a[e], lp["experts"]))
        x = x + y
    return mm("td,vd->tv", norm(x, p["ln_f"]), p["head"])
