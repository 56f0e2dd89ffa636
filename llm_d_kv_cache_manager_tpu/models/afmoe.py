"""The `afmoe` family on the pod path: sparse experts with a shared expert,
window and full attention layers mixed, served through paged prefill,
prefix-continue and decode over a pool with two groups of slots.

The layer equations (shapes from the model's public ``config.json``; the six
points marked + are from the published ``afmoe`` modelling code and are listed
under ``assumed`` in the benchmark's configuration file):

- Embedding ``x = E[tokens]``; + ``x *= sqrt(hidden_size)`` (``mup_enabled``).
  Head untied: ``logits = RMSNorm(x) . W_head``.
- Layer l, + four norms: ``a = x + RMSNorm_post_attn(Attn(RMSNorm_in(x)))``;
  ``x' = a + RMSNorm_post_mlp(MLP(RMSNorm_pre_mlp(a)))``.
- ``Attn(h)``: q, k, v projections; + q and k RMS-normed over the head
  dimension with a learned weight; + RoPE on q, k only where the layer is a
  ``sliding_attention`` layer; scores ``q.k / sqrt(head_dim)``, causal, and on
  sliding layers also ``j > i - window``; + the output is gated,
  ``o = (softmax . v) * sigmoid(h . Wg)``, then ``o . Wo``.
- ``MLP``: a SwiGLU of width ``d_ff`` on the first ``n_dense_layers`` layers.
  Else ``s = sigmoid(h . Wr)`` in float32 over all experts; + selection
  ``top_k(s + b)`` with a per-expert bias ``b`` that enters selection only;
  weights ``w = s[sel]``, ``w /= sum(w)`` (``route_norm``), ``w *= route_scale``;
  ``MLP(h) = Shared(h) + sum_e w_e Expert_e(h)``, each a SwiGLU of width
  ``d_expert``.  Every chosen expert computes: a prefill's picks are sorted by
  expert and multiplied group by group (``lax.ragged_dot``), a decode step's
  few tokens go through every expert under the routing's mask; nothing is
  dropped and no capacity exists.

The cache has two groups of slots (``cache_groups``): a logical block owns a
slot of the full group (the full-attention layers' K/V of its 16 positions)
while it is cached, and a slot of the window group (the sliding layers') only
as long as the pod's retention rules keep it (models/pod.py).  A step is
handed both tables: ``tables["full"]`` in chain order over logical blocks,
``tables["window"]`` over window slots, and writes each layer's K/V into its
own group in place (the pools are per-layer arrays, donated by the caller).
A miss prefill stores the window layers' K/V of its trailing blocks only: as
many as the window table it is handed has columns.

``reference_logits`` is the plain float32 forward pass of the same equations:
no cache, no kernels, every expert by a mask.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.models import layers, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, decode_view, gather_prefix, write_blocks, write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    logits, prefill_attention, rms_norm, rope, swiglu,
)
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

Params = Dict[str, Any]
SLIDING, FULL = "sliding_attention", "full_attention"
HI = lax.Precision.HIGHEST
# An expert layer's prefill runs over at most this many tokens at a time.
MOE_CHUNK_TOKENS = moe_serve.MOE_CHUNK_TOKENS


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128  # the dense layers' SwiGLU width
    d_expert: int = 32  # each routed expert's width; shared: n_shared times it
    n_experts: int = 8
    top_k: int = 2
    n_shared: int = 1
    n_dense_layers: int = 1
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)
    window: int = 32
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    mup: bool = True
    block_size: int = 16
    dtype: str = "bfloat16"
    # The window group of the pod's cache: how many slots it has, and how
    # many trailing blocks of a miss prefill get one (which bounds how far
    # before the end of a stored sequence a later hit may start).
    window_slots: int = 64
    window_store_blocks: int = 4

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def slot_of_layer(self, layer: int) -> Tuple[str, int]:
        """(group, index within the group's per-layer pools) of a layer."""
        kind = "window" if self.layer_types[layer] == SLIDING else "full"
        same = SLIDING if kind == "window" else FULL
        return kind, sum(t == same for t in self.layer_types[:layer])


def cache_groups(cfg: AfmoeConfig) -> Dict[str, KVGroupSpec]:
    """What one slot of each group holds; models/pod.py and `new_pool` read
    block bytes and shapes from here."""
    counts = {
        kind: sum(t == name for t in cfg.layer_types)
        for kind, name in (("full", FULL), ("window", SLIDING))
    }
    return {
        kind: KVGroupSpec(
            counts[kind],
            cfg.block_size,
            cfg.n_kv_heads,
            cfg.head_dim,
            cfg.dtype,
            window=cfg.window if kind == "window" else None,
            heads_first=True,
        )
        for kind in ("full", "window")
    }


def cache_policy(cfg: AfmoeConfig) -> dict:
    """What models/pod.py needs to know of this family's cache: the second
    group of slots (None for a model without window layers: the pod is then
    the plain one-group prefix cache) and the order of reuse."""
    groups = cache_groups(cfg)
    window = groups["window"].num_layers and {
        "slots": cfg.window_slots,
        "store_blocks": cfg.window_store_blocks,
    }
    return {"specs": groups, "window": window or None, "protect_asked": True}


def new_pool(cfg: AfmoeConfig, pool_blocks: int) -> dict:
    """The pod's pools as a pytree: one array a layer, each updated in
    place.  (A step hands them back with one more leaf, `load`, the expert
    layers' counts of that step: [expert layer, (experts touched, most picks
    on one expert)]; it is not handed in again.)"""
    return layers.new_pool(
        cache_groups(cfg), {"full": pool_blocks, "window": cfg.window_slots})


def from_published(cfg: dict, block_size: int) -> AfmoeConfig:
    """The program's configuration from the keys of the public
    ``config.json`` and the configuration file's ``serving`` group.  What
    the module does not implement is an error, not a default."""
    for key, want in (
        ("score_func", "sigmoid"),
        ("n_group", 1),
        ("topk_group", 1),
        ("hidden_act", "silu"),
        ("tie_word_embeddings", False),
        ("rope_scaling", None),
    ):
        if cfg[key] != want:
            raise ValueError(f"afmoe: {key}={cfg[key]!r} is not implemented")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    serving = cfg["serving"]
    return AfmoeConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_shared=cfg["num_shared_experts"],
        n_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]),
        window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        route_norm=cfg["route_norm"],
        route_scale=float(cfg["route_scale"]),
        mup=cfg["mup_enabled"],
        block_size=block_size,
        dtype=cfg["torch_dtype"],
        window_slots=serving["window_slots"],
        window_store_blocks=serving["window_store_blocks"],
    )


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """Seeded normal weights, fan-in scaled; norm weights and the selection
    bias are not constant, so that leaving one out of a step shows."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Fe = cfg.n_experts, cfg.d_expert
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
            "ln_post_attn": norm(D),
            "ln_pre_mlp": norm(D),
            "ln_post_mlp": norm(D),
            "wq": w((D, H, Dh), D),
            "wk": w((D, Hkv, Dh), D),
            "wv": w((D, Hkv, Dh), D),
            "wg": w((D, H, Dh), D),
            "wo": w((H, Dh, D), H * Dh),
            "q_norm": norm(Dh),
            "k_norm": norm(Dh),
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


def _embed(params, tokens, cfg):
    """`layers.embed`'s float32 stream, scaled where the model says so."""
    x = layers.embed(params, tokens)
    return x * cfg.d_model**0.5 if cfg.mup else x


def _qkvg(h, lp, positions, cfg, sliding):
    """h: [B, T, D] in the serving type -> q and the output gate's
    pre-activation in float32, k and v in the cache's type.  Norm and RoPE
    run on the products' float32 sums, so k and v are rounded once, into the
    cache, and q not at all."""
    f32 = jnp.float32
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"], preferred_element_type=f32)
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"], preferred_element_type=f32)
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"], preferred_element_type=f32)
    g = jnp.einsum("btd,dhk->bthk", h, lp["wg"], preferred_element_type=f32)
    q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
    k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    if sliding:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k.astype(h.dtype), v.astype(h.dtype), g


def _attn_out(attn, g, lp):
    o = attn.astype(jnp.float32) * jax.nn.sigmoid(g)
    return jnp.einsum("bthk,hkd->btd", o.astype(lp["wo"].dtype), lp["wo"],
                      preferred_element_type=jnp.float32)


def route(h, lp, cfg):
    """h: [N, D] float32 -> (experts picked [N, k], their weights [N, k]
    float32): `moe_serve.route` with this family's keys."""
    return moe_serve.route(h, lp["router"], lp["route_bias"], cfg.top_k,
                           cfg.route_norm, cfg.route_scale)


def routed_experts(h, picked, w, experts, cfg, interpret: bool = False):
    """`moe_serve.routed_experts`, batched under the routing's mask for at
    most as many tokens as experts (a decode step), sorted by expert above (a
    prefill).  A step of 64 sequences picks 512 times among 128 experts:
    the grouped product (`lax.ragged_dot`) took 0.04 ms an expert touched,
    5.1 ms for 127, against 2.3 ms for all 128 in one batched product, 84 %
    of the chip's bandwidth (my chip run, PR 29).  Since PR 54 the batched
    form of a decode step is `ops/moe_decode_pallas.py`'s kernel, which
    copies the touched experts alone: 2.26 ms at all 128 and 2.04 at the
    114 a step of this cell touches, the einsum 2.44 (my chip run, PR 54;
    `moe_serve.decode_kernel_serves`)."""
    return moe_serve.routed_experts(h, picked, w, experts, cfg.n_experts,
                                    batched=picked.shape[0] <= cfg.n_experts,
                                    interpret=interpret)


def _moe(h, lp, cfg, interpret):
    """h: [B, T, D] float32 -> (Shared(h) + routed experts, float32; picks
    per expert [E])."""
    act = lp["router"].dtype  # the serving type

    def chunk(rows):
        picked, w = route(rows, lp, cfg)
        return routed_experts(rows.astype(act), picked, w, lp["experts"], cfg,
                              interpret)

    out, sizes = moe_serve.in_chunks(h, chunk, MOE_CHUNK_TOKENS)
    return swiglu(h.astype(act), lp["shared"]) + out.reshape(h.shape), sizes


def _mlp_block(x, lp, cfg, interpret):
    """a -> a + RMSNorm_post_mlp(MLP(RMSNorm_pre_mlp(a))), and the expert
    layer's load (None on a dense layer)."""
    h = rms_norm(x, lp["ln_pre_mlp"], cfg.rms_eps)
    if "mlp" in lp:
        y, load = swiglu(h.astype(lp["mlp"]["w_up"].dtype), lp["mlp"]), None
    else:
        y, sizes = _moe(h, lp, cfg, interpret)
        load = jnp.stack((jnp.sum(sizes > 0), jnp.max(sizes)))
    return x + rms_norm(y, lp["ln_post_mlp"], cfg.rms_eps), load


def _attn_block(x, attn, g, lp, cfg):
    return x + rms_norm(_attn_out(attn, g, lp), lp["ln_post_attn"],
                         cfg.rms_eps)


def _finish(x, params, cfg, full, win, loads):
    pools = {"full": full, "window": win,
             "load": jnp.stack(loads).astype(jnp.int32)}
    return logits(x, params, cfg), pools


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    cfg: AfmoeConfig,
    interpret: bool = False,
):
    """Prefill writing each layer's K/V into its group's pool.

    tokens: [B, T], T a multiple of the block size.  tables["full"]:
    [B, T/block] logical blocks in chain order; tables["window"]: [B, d]
    window slots of the trailing d blocks, the only ones whose window-layer
    K/V is stored.  Returns (logits of the last position [B, 1, V], pools).
    """
    B, T = tokens.shape
    bs = cfg.block_size
    stored = tables["window"].shape[1] * bs
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = _embed(params, tokens, cfg)
    specs = cache_groups(cfg)
    full, win, loads = list(pools["full"]), list(pools["window"]), []
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.slot_of_layer(l)
        sliding = kind == "window"
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["wq"].dtype)
        q, k, v, g = _qkvg(h, lp, positions, cfg, sliding)
        attn = prefill_attention(
            q, k, v, cfg, 0, cfg.window if sliding else None, interpret
        )
        x = _attn_block(x, attn, g, lp, cfg)
        if sliding:
            win[i] = write_blocks(
                specs[kind], win[i], tables["window"],
                k[:, T - stored:], v[:, T - stored:],
            )
        else:
            full[i] = write_blocks(specs[kind], full[i], tables["full"], k, v)
        x, load = _mlp_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, win, loads)


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    prefix_len: int,
    cfg: AfmoeConfig,
    interpret: bool = False,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).

    tokens: [B, S] suffix.  tables["full"]: [B, (prefix_len + S)/block], the
    prefix's blocks then the blocks to write.  tables["window"]:
    [B, n + S/block]: the window slots of the prefix's last n blocks
    (n = min(prefix blocks, ceil((window-1)/block)): all a window layer can
    see from the suffix) then the slots to write.  ``prefix_len`` is static.
    Returns (logits of the last position [B, 1, V], pools).
    """
    B, S = tokens.shape
    bs = cfg.block_size
    if prefix_len % bs or S % bs:
        raise ValueError("prefix_len and the suffix must be whole blocks")
    npre, nsuf = prefix_len // bs, S // bs
    nwin = tables["window"].shape[1] - nsuf
    positions = jnp.broadcast_to(prefix_len + jnp.arange(S), (B, S))
    x = _embed(params, tokens, cfg)
    specs = cache_groups(cfg)
    full, win, loads = list(pools["full"]), list(pools["window"]), []
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.slot_of_layer(l)
        sliding = kind == "window"
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["wq"].dtype)
        q, k, v, g = _qkvg(h, lp, positions, cfg, sliding)
        if sliding:
            pre_k, pre_v = gather_prefix(
                specs[kind], win[i], tables["window"][:, :nwin], k.dtype
            )
        else:
            pre_k, pre_v = gather_prefix(
                specs[kind], full[i], tables["full"][:, :npre], k.dtype
            )
        attn = prefill_attention(
            q,
            jnp.concatenate((pre_k, k), axis=1),
            jnp.concatenate((pre_v, v), axis=1),
            cfg,
            pre_k.shape[1],  # positions relative to the first key handed in
            cfg.window if sliding else None,
            interpret,
        )
        x = _attn_block(x, attn, g, lp, cfg)
        if sliding:
            win[i] = write_blocks(
                specs[kind], win[i], tables["window"][:, nwin:], k, v
            )
        else:
            full[i] = write_blocks(
                specs[kind], full[i], tables["full"][:, npre:npre + nsuf],
                k, v,
            )
        x, load = _mlp_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, win, loads)


def _decode_attention(spec, q, pool, table, context_len, start, interpret,
                      plan):
    """The paged kernel's walk, which copies each table block once, a run of
    them by one copy, where it serves (compiled for the TPU, or interpreted;
    `plan`: the runs of this table, `shared_prefix_plan`'s with nobody
    sharing); elsewhere the XLA gather."""
    pool, layout = decode_view(spec, pool, kernel=plan is not None)
    if plan is not None:
        return paged_decode_attention_pallas(
            q, pool, table, context_len, start=start, mxu_native=False,
            plan=plan, interpret=interpret, **layout,
        )
    return paged_attention(q, pool, table, context_len, start=start, **layout)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    context_len: jnp.ndarray,
    cfg: AfmoeConfig,
    interpret: bool = False,
):
    """One decode step over both groups.

    tokens: [B]; context_len: [B], the current token included.
    tables["full"]: [B, max_blocks] logical blocks.  tables["window"]:
    [B, n] window slots of the blocks from the one that holds position
    ``tables["first"]`` ([B], a block boundary at or before the window's
    first position) up to the current one; later columns are padding.
    Writes the new token's K/V into both groups, attends through each
    layer's own table, and returns (logits [B, V], pools).
    """
    B = tokens.shape[0]
    bs = cfg.block_size
    pos = context_len - 1
    first = tables["first"]
    x = _embed(params, tokens, cfg)[:, None]  # [B, 1, D]
    at = pos % bs
    full_id = jnp.take_along_axis(
        tables["full"], (pos // bs)[:, None], axis=1)[:, 0]
    win_id = jnp.take_along_axis(
        tables["window"], ((pos - first) // bs)[:, None], axis=1)[:, 0]
    win_ctx = context_len - first
    win_start = jnp.maximum(context_len - cfg.window, 0) - first
    specs = cache_groups(cfg)
    full, win, loads = list(pools["full"]), list(pools["window"]), []
    # Which waves of a table's walk are runs in the pool, once a group: its
    # layers all see the one table.
    plans = {}
    if paged_decode_pallas.serves(interpret):
        for kind, ctx in (("full", context_len), ("window", win_ctx)):
            if pools[kind]:  # a model may have no layer of a kind
                plans[kind] = paged_decode_pallas.shared_prefix_plan(
                    tables[kind], ctx, block_size=bs, min_sequences=None,
                    blocks_per_wave=paged_decode_pallas.walk_wave(
                        pools[kind][0]))
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.slot_of_layer(l)
        sliding = kind == "window"
        h = rms_norm(x, lp["ln_in"], cfg.rms_eps, lp["wq"].dtype)
        q, k, v, g = _qkvg(h, lp, pos[:, None], cfg, sliding)
        if sliding:
            win[i] = write_token(specs[kind], win[i], win_id, at,
                                 k[:, 0], v[:, 0])
            attn = _decode_attention(specs[kind], q[:, 0], win[i],
                                     tables["window"], win_ctx, win_start,
                                     interpret, plans.get(kind))
        else:
            full[i] = write_token(specs[kind], full[i], full_id, at,
                                  k[:, 0], v[:, 0])
            attn = _decode_attention(specs[kind], q[:, 0], full[i],
                                     tables["full"], context_len, None,
                                     interpret, plans.get(kind))
        x = _attn_block(x, attn[:, None], g, lp, cfg)
        x, load = _mlp_block(x, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    logits, pools = _finish(x[:, 0], params, cfg, full, win, loads)
    if plans:  # what the walks read: a group's plan by the layers that read it
        pools["attention_read"] = sum(
            len(pools[kind]) * paged_decode_pallas.attention_read_counts(plan)
            for kind, plan in plans.items())
    return logits, pools


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: AfmoeConfig):
    """Logits [T, V] of one sequence by the equations at the top: float32,
    products at precision highest, no cache, no kernels, no batching, every
    expert computed for every token and masked by the routing."""
    f32 = jnp.float32
    p = jax.tree.map(lambda a: a.astype(f32), params)
    T = len(tokens)

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    def norm(x, w):
        return x * lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps) * w

    def rope(x):
        Dh = x.shape[-1]
        freqs = cfg.rope_theta ** (
            -jnp.arange(0, Dh // 2, dtype=f32) / (Dh // 2))
        ang = jnp.arange(T, dtype=f32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = jnp.split(x, 2, -1)
        return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), -1)

    def swiglu(h, w):
        return mm("tf,fd->td",
                  jax.nn.silu(mm("td,df->tf", h, w["w_gate"]))
                  * mm("td,df->tf", h, w["w_up"]), w["w_down"])

    x = jnp.take(p["embed"], jnp.asarray(tokens), axis=0)
    if cfg.mup:
        x = x * cfg.d_model**0.5
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    for l, lp in enumerate(p["layers"]):
        sliding = cfg.layer_types[l] == SLIDING
        h = norm(x, lp["ln_in"])
        q = norm(mm("td,dhk->thk", h, lp["wq"]), lp["q_norm"])
        k = norm(mm("td,dhk->thk", h, lp["wk"]), lp["k_norm"])
        v = mm("td,dhk->thk", h, lp["wv"])
        if sliding:
            q, k = rope(q), rope(k)
        k, v = (jnp.repeat(a, cfg.n_heads // cfg.n_kv_heads, axis=1)
                for a in (k, v))
        s = mm("qhk,thk->hqt", q, k) * cfg.head_dim**-0.5
        seen = (j <= i) & ((j > i - cfg.window) if sliding else True)
        attn = mm("hqt,thk->qhk",
                  jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1), v)
        attn = attn * jax.nn.sigmoid(mm("td,dhk->thk", h, lp["wg"]))
        x = x + norm(mm("thk,hkd->td", attn, lp["wo"]), lp["ln_post_attn"])
        h = norm(x, lp["ln_pre_mlp"])
        if "mlp" in lp:
            y = swiglu(h, lp["mlp"])
        else:
            s = jax.nn.sigmoid(mm("td,de->te", h, lp["router"]))
            _, picked = lax.top_k(s + lp["route_bias"], cfg.top_k)
            chosen = jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1)
            w = s * chosen
            if cfg.route_norm:
                w = w / w.sum(-1, keepdims=True)
            w = w * cfg.route_scale
            y = swiglu(h, lp["shared"])
            for e in range(cfg.n_experts):
                y = y + w[:, e:e + 1] * swiglu(
                    h, jax.tree.map(lambda a: a[e], lp["experts"]))
        x = x + norm(y, lp["ln_post_mlp"])
    return mm("td,vd->tv", norm(x, p["ln_f"]), p["head"])
