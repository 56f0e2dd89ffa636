"""The `lfm2moe` family on the pod path: short-convolution layers mixed with
full attention layers, a router over sparse experts without a shared one,
served through paged prefill, prefix-continue and decode over a pool with a
K/V group and a group of convolution-state snapshots.

The layer equations (shapes from the model's public ``config.json``; the points
marked + are from the published ``lfm2_moe`` modelling code and are listed
under ``assumed`` in the benchmark's configuration file):

- ``x = E[tokens]``; after the last layer + ``logits = RMSNorm_out(x) . E^T``
  (+ the head is the embedding; + RMSNorm is ``w * x / rms(x)``, no ``1 + w``).
- Layer l, + pre-norm, two norms: ``a = x + Mixer_l(RMSNorm_op(x))``;
  ``x' = a + FF_l(RMSNorm_ff(a))``.
- ``Mixer`` on a ``conv`` layer (+ the order of the three products):
  ``[B, C, u] = h . W_in`` (D -> 3 D, no bias), ``z = B * u``,
  ``c_t = sum_{j=0..2} k[:, j] * z_{t-2+j}`` (depthwise, causal, ``z`` at
  negative positions zero, no bias), ``y = (C * c) . W_out``.  The state after
  position t is ``(z_{t-1}, z_t)``.
- ``Mixer`` on a ``full_attention`` layer: q, k, v projections without bias;
  + q and k RMS-normed over the head's values with a learned weight; RoPE
  (``rope_theta``) on q and k on every attention layer; causal softmax of
  ``q.k / sqrt(head size)``; ``. W_o``.  + head size = ``hidden_size /
  num_attention_heads``.
- ``FF`` on the first ``num_dense_layers`` layers: a SwiGLU of width
  ``intermediate_size``.  Else + ``s = sigmoid(h . W_r)`` in float32 over all
  experts; + selection ``top_k(s + b)``, the bias ``b`` in the selection only;
  + ``w = s[sel] / (sum s[sel] + 1e-6)`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``FF(h) = sum_e w_e Expert_e(h)``, each a SwiGLU
  of width ``moe_intermediate_size``.  No token is dropped, no capacity
  (``models/moe_serve.py``, shared with ``models/afmoe.py``).

The cache has two groups of slots (``cache_groups``).  A logical block owns a
slot of the *full* group (the attention layers' K/V of its 16 positions) while
it is cached, a position's K and V side by side in the last axis
(``KVGroupSpec.packed``: at this head size the chip's layout wants it so).  A
slot of the *state* group holds every conv layer's
``(z_{t-1}, z_t)`` after the last position of one logical block; the pod keeps
one only at the boundaries its rules name (models/pod.py), because a prefix
can be continued only where such a snapshot was kept.  A prefill is handed
``tables["state_write"]`` [B, n], the slots of the boundaries
``KVGroupSpec.snapshot_blocks`` lists for its blocks, and a continue also
``tables["state_read"]`` [B], the snapshot at its prefix's end; a decode step
``tables["state"]`` [B, 2]: the slot it reads each sequence's rolling state
from (the block of position p - 1) and the slot it writes it to (the block of
position p), the same slot but at a block's first position, so a finished
block's slot is its snapshot.  Pools are per-layer arrays, donated by the
caller and updated in place.

``z`` is rounded once, to the serving type, where it is made: the convolution
of a prefill and the state a decode step reads hold the same values.

``reference_logits`` is the plain float32 forward pass of the same equations:
no cache, no kernels, the convolution as three shifted products, every expert
by a mask.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llm_d_kv_cache_manager_tpu.models import layers, moe_serve
from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, decode_view, gather_prefix, write_blocks, write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    embed, prefill_attention, rms_norm, rope, swiglu,
)
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

Params = Dict[str, Any]
CONV, FULL = "conv", "full_attention"
HI = lax.Precision.HIGHEST
ROUTE_NORM_EPS = 1e-6  # + the published code's, under the picked scores' sum
# Up to this many tokens (a decode step, a hit prefill's suffix) go through
# every expert in one batched product under the routing's mask, more through
# `lax.ragged_dot`.  Read on the chip at the cell's sizes, 32 experts of width
# 1792, a layer: 64 tokens 1.03 ms batched / 1.90 ms grouped, 512 tokens
# 2.03 / 3.04 (my chip run, PR 33; PERF.md section 6).  The batched form
# stays the einsum here: 64 rows of 4 picks touch all 32 experts, and the
# kernel that skips an untouched expert's copies then read 1.060 ms against
# 1.042 (my chip run, PR 54; `moe_serve.decode_kernel_serves`).
BATCHED_EXPERTS_MAX_TOKENS = 512


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 128  # the dense layers' SwiGLU width
    d_expert: int = 32
    n_experts: int = 8
    top_k: int = 2
    n_dense_layers: int = 1
    layer_types: Tuple[str, ...] = (CONV, FULL, CONV, CONV)
    conv_taps: int = 3  # conv_L_cache: the state holds conv_taps - 1 inputs
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 1.0
    block_size: int = 16
    dtype: str = "bfloat16"
    # The state group of the pod's cache: how many slots it has, and every
    # how many blocks a prefill keeps a snapshot.
    state_slots: int = 32
    state_stride_blocks: int = 2

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def slot_of_layer(self, layer: int) -> Tuple[str, int]:
        """(group, index within the group's per-layer pools) of a layer."""
        same = self.layer_types[layer]
        kind = "state" if same == CONV else "full"
        return kind, sum(t == same for t in self.layer_types[:layer])


def cache_groups(cfg: Lfm2MoeConfig) -> Dict[str, KVGroupSpec]:
    """What one slot of each group holds; models/pod.py and `new_pool` read
    bytes and shapes from here."""
    layers = {kind: sum(t == name for t in cfg.layer_types)
              for kind, name in (("full", FULL), ("state", CONV))}
    return {
        "full": KVGroupSpec(layers["full"], cfg.block_size, cfg.n_kv_heads,
                            cfg.head_dim, cfg.dtype, packed=True),
        "state": KVGroupSpec(layers["state"], cfg.block_size, 0, 0, cfg.dtype,
                             state_shape=(cfg.conv_taps - 1, cfg.d_model),
                             stride_blocks=cfg.state_stride_blocks),
    }


def cache_policy(cfg: Lfm2MoeConfig) -> dict:
    """What models/pod.py needs to know of this family's cache: the state
    group (None for a model without conv layers: the pod is then the plain
    one-group prefix cache) and the order of reuse."""
    groups = cache_groups(cfg)
    state = groups["state"].num_layers and {"slots": cfg.state_slots}
    return {"specs": groups, "state": state or None, "protect_asked": True}


def new_pool(cfg: Lfm2MoeConfig, pool_blocks: int) -> dict:
    """The pod's pools as a pytree: one array a layer, each updated in
    place.  (A step hands them back with one more leaf, `load`, the expert
    layers' counts of that step, as `models/afmoe.py` does.)"""
    return layers.new_pool(
        cache_groups(cfg), {"full": pool_blocks, "state": cfg.state_slots})


def from_published(cfg: dict, block_size: int) -> Lfm2MoeConfig:
    """The program's configuration from the keys of the public
    ``config.json`` and the configuration file's ``serving`` group.  What
    the module does not implement is an error, not a default."""
    for key, want in (
        ("conv_bias", False),
        ("conv_L_cache", 3),
        ("use_expert_bias", True),
    ):
        if cfg[key] != want:
            raise ValueError(f"lfm2moe: {key}={cfg[key]!r} is not implemented")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    unknown = set(cfg["layer_types"]) - {CONV, FULL}
    if unknown:
        raise ValueError(f"lfm2moe: layer types {sorted(unknown)} are not "
                         "implemented")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size is not a whole number of heads")
    serving = cfg["serving"]
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        d_expert=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"]),
        conv_taps=cfg["conv_L_cache"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["norm_eps"]),
        route_norm=cfg["norm_topk_prob"],
        route_scale=float(cfg["routed_scaling_factor"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
        state_slots=serving["state_slots"],
        state_stride_blocks=serving["state_stride_blocks"],
    )


def init_params(rng: jax.Array, cfg: Lfm2MoeConfig) -> Params:
    """Seeded normal weights, fan-in scaled; norm weights, the convolution's
    taps and the selection bias are not constant, so that leaving one out of a
    step shows."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Fe = cfg.n_experts, cfg.d_expert
    keys = iter(jax.random.split(rng, 16 * cfg.n_layers + 4))

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
    for l, kind in enumerate(cfg.layer_types):
        lp = {"ln_op": norm(D), "ln_ff": norm(D)}
        if kind == CONV:
            lp["w_in"] = w((D, 3, D), D)
            lp["conv_k"] = w((D, cfg.conv_taps), cfg.conv_taps)
            lp["w_out"] = w((D, D), D)
        else:
            lp["wq"] = w((D, H, Dh), D)
            lp["wk"] = w((D, Hkv, Dh), D)
            lp["wv"] = w((D, Hkv, Dh), D)
            lp["wo"] = w((H, Dh, D), H * Dh)
            lp["q_norm"] = norm(Dh)
            lp["k_norm"] = norm(Dh)
        if l < cfg.n_dense_layers:
            lp["mlp"] = swiglu(cfg.d_ff)
        else:
            lp["router"] = w((D, E), D)
            lp["route_bias"] = 0.05 * jax.random.normal(
                next(keys), (E,), jnp.float32
            )
            lp["experts"] = swiglu(Fe, (E,))
        layers.append(lp)
    return {"embed": w((cfg.vocab_size, D), D), "ln_f": norm(D),
            "layers": layers}


# ------------------------------------------------------------ the model step


def _logits(x, params, cfg):
    """Final norm and the head, which is the embedding; float32 logits."""
    x = rms_norm(x, params["ln_f"], cfg.rms_eps, params["embed"].dtype)
    return jnp.einsum(
        "...d,vd->...v", x, params["embed"],
        preferred_element_type=jnp.float32,
    )


def _qkv(h, lp, positions, cfg):
    """h: [B, T, D] in the serving type -> q in float32, k and v in the
    cache's type; norm and RoPE on the products' float32 sums."""
    f32 = jnp.float32
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"], preferred_element_type=f32)
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"], preferred_element_type=f32)
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"], preferred_element_type=f32)
    q = rope(rms_norm(q, lp["q_norm"], cfg.rms_eps), positions,
              cfg.rope_theta)
    k = rope(rms_norm(k, lp["k_norm"], cfg.rms_eps), positions,
              cfg.rope_theta)
    return q, k.astype(h.dtype), v.astype(h.dtype)


def _attn_out(attn, lp):
    return jnp.einsum("bthk,hkd->btd", attn.astype(lp["wo"].dtype), lp["wo"],
                      preferred_element_type=jnp.float32)


def _decode_attention(spec, q, pool, table, context_len, interpret, plan):
    """The paged kernel where it serves (compiled for the TPU, or
    interpreted; `plan`: its `shared_prefix_plan` of this table); elsewhere
    the XLA gather."""
    pool, layout = decode_view(spec, pool, kernel=plan is not None)
    if plan is not None:
        return paged_decode_attention_pallas(
            q, pool, table, context_len, interpret=interpret, plan=plan,
            **layout,
        )
    return paged_attention(q, pool, table, context_len, **layout)


def _conv_in(h, lp):
    """h: [B, T, D] in the serving type -> (z = B * u rounded once to the
    serving type, the output gate C in float32)."""
    bcu = jnp.einsum("btd,dce->btce", h, lp["w_in"],
                     preferred_element_type=jnp.float32)
    return (bcu[:, :, 0] * bcu[:, :, 2]).astype(h.dtype), bcu[:, :, 1]


def _conv_mix(zs, gate, lp):
    """zs: the taps' inputs, oldest first, each [B, T, D] -> the mixer's
    output [B, T, D] float32: y = (C * sum_j k[:, j] z_j) . W_out."""
    k = lp["conv_k"].astype(jnp.float32)
    conv = sum(k[:, j] * z.astype(jnp.float32) for j, z in enumerate(zs))
    return jnp.einsum("btd,de->bte", (gate * conv).astype(lp["w_out"].dtype),
                      lp["w_out"], preferred_element_type=jnp.float32)


def _conv_prefill(h, lp, before, ends, pool, write):
    """A conv layer over a prefill's positions.  before: [B, taps - 1, D],
    the inputs of the positions before the first (zeros for a prompt's
    start, a snapshot for a continue); ends: the static positions whose state
    is kept; write: [B, len(ends)] slots.  Returns (y, pool)."""
    z, gate = _conv_in(h, lp)
    T, taps = z.shape[1], before.shape[1] + 1
    zp = jnp.concatenate((before.astype(z.dtype), z), axis=1)
    y = _conv_mix([zp[:, j:j + T] for j in range(taps)], gate, lp)
    # the state after position e: the taps - 1 inputs up to it, oldest first
    at = np.asarray(ends)[:, None] + 1 + np.arange(taps - 1)[None, :]
    snap = zp[:, at]  # [B, n, taps - 1, D]
    pool = pool.at[write.reshape(-1)].set(
        snap.reshape((-1,) + snap.shape[2:]).astype(pool.dtype))
    return y, pool


def _moe(h, lp, cfg, interpret):
    """h: [B, T, D] float32 -> (sum of each token's picked experts, float32;
    picks per expert [E])."""
    act = lp["router"].dtype  # the serving type

    def chunk(rows):
        picked, w = moe_serve.route(
            rows, lp["router"], lp["route_bias"], cfg.top_k, cfg.route_norm,
            cfg.route_scale, ROUTE_NORM_EPS)
        return moe_serve.routed_experts(
            rows.astype(act), picked, w, lp["experts"], cfg.n_experts,
            batched=rows.shape[0] <= BATCHED_EXPERTS_MAX_TOKENS,
            interpret=interpret)

    out, sizes = moe_serve.in_chunks(h, chunk)
    return out.reshape(h.shape), sizes


def _ff_block(x, lp, cfg, interpret):
    """a -> a + FF(RMSNorm_ff(a)), and the expert layer's load (None on a
    dense layer)."""
    h = rms_norm(x, lp["ln_ff"], cfg.rms_eps)
    if "mlp" in lp:
        return x + swiglu(h.astype(lp["mlp"]["w_up"].dtype), lp["mlp"]), None
    y, sizes = _moe(h, lp, cfg, interpret)
    return x + y, jnp.stack((jnp.sum(sizes > 0), jnp.max(sizes)))


def _finish(x, params, cfg, full, state, loads):
    pools = {"full": full, "state": state}
    if loads:
        pools["load"] = jnp.stack(loads).astype(jnp.int32)
    return _logits(x, params, cfg), pools


def _prefill(params, tokens, pools, tables, prefix_len, cfg, interpret):
    B, S = tokens.shape
    bs = cfg.block_size
    if prefix_len % bs or S % bs:
        raise ValueError("a prefill's prefix and tokens must be whole blocks")
    npre, nsuf = prefix_len // bs, S // bs
    specs = cache_groups(cfg)
    kept = specs["state"].snapshot_blocks(npre, nsuf)
    ends = [(i - npre + 1) * bs - 1 for i in kept]
    positions = jnp.broadcast_to(prefix_len + jnp.arange(S), (B, S))
    x = embed(params, tokens)
    full, state, loads = list(pools["full"]), list(pools["state"]), []
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.slot_of_layer(l)
        h = rms_norm(x, lp["ln_op"], cfg.rms_eps, lp["ln_op"].dtype)
        if kind == "state":
            before = (jnp.take(state[i], tables["state_read"], axis=0)
                      if npre else
                      jnp.zeros((B, cfg.conv_taps - 1, cfg.d_model),
                                state[i].dtype))
            y, state[i] = _conv_prefill(h, lp, before, ends, state[i],
                                        tables["state_write"])
        else:
            q, k, v = _qkv(h, lp, positions, cfg)
            keys, values = k, v
            if npre:
                pre_k, pre_v = gather_prefix(
                    specs["full"], full[i], tables["full"][:, :npre], k.dtype)
                keys = jnp.concatenate((pre_k, k), axis=1)
                values = jnp.concatenate((pre_v, v), axis=1)
            attn = prefill_attention(q, keys, values, cfg, prefix_len, None,
                                      interpret)
            y = _attn_out(attn, lp)
            full[i] = write_blocks(
                specs["full"], full[i], tables["full"][:, npre:npre + nsuf],
                k, v)
        x, load = _ff_block(x + y, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    return _finish(x[:, -1:], params, cfg, full, state, loads)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    cfg: Lfm2MoeConfig,
    interpret: bool = False,
):
    """Prefill writing each attention layer's K/V into the full group and the
    conv layers' state at the kept block boundaries into the state group.

    tokens: [B, T], T a multiple of the block size.  tables["full"]:
    [B, T/block] logical blocks in chain order; tables["state_write"]: [B, n]
    state slots of the blocks ``snapshot_blocks(0, T/block)`` names.
    Returns (logits of the last position [B, 1, V], pools).
    """
    return _prefill(params, tokens, pools, tables, 0, cfg, interpret)


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    prefix_len: int,
    cfg: Lfm2MoeConfig,
    interpret: bool = False,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).

    tokens: [B, S] suffix.  tables["full"]: [B, (prefix_len + S)/block], the
    prefix's blocks then the blocks to write.  tables["state_read"]: [B], the
    slot of the snapshot after the prefix's last block, which every conv
    layer starts from (it gathers no prefix); tables["state_write"]: [B, n]
    as in `prefill_paged`, for ``snapshot_blocks(prefix blocks, S/block)``.
    ``prefix_len`` is static.  Returns (logits of the last position
    [B, 1, V], pools).
    """
    if not prefix_len:
        raise ValueError("a continue has a prefix; a prompt's start is "
                         "`prefill_paged`'s")
    return _prefill(params, tokens, pools, tables, prefix_len, cfg, interpret)


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    context_len: jnp.ndarray,
    cfg: Lfm2MoeConfig,
    interpret: bool = False,
):
    """One decode step over both groups.

    tokens: [B]; context_len: [B], the current token included.
    tables["full"]: [B, max_blocks] logical blocks.  tables["state"]: [B, 2]:
    the state slot of the block that holds position p - 1 (read) and of the
    block that holds position p = context_len - 1 (written).  Writes the new
    token's K/V, shifts each sequence's conv state by one input, and returns
    (logits [B, V], pools).
    """
    bs = cfg.block_size
    pos = context_len - 1
    read, write = tables["state"][:, 0], tables["state"][:, 1]
    x = embed(params, tokens)[:, None]  # [B, 1, D]
    at = pos % bs
    full_id = jnp.take_along_axis(
        tables["full"], (pos // bs)[:, None], axis=1)[:, 0]
    spec = cache_groups(cfg)["full"]
    full, state, loads = list(pools["full"]), list(pools["state"]), []
    # Which sequences' tables begin with the same blocks, once for the
    # attention layers: all see this table.
    plan = None
    if paged_decode_pallas.serves(interpret):
        plan = paged_decode_pallas.shared_prefix_plan(
            tables["full"], context_len, block_size=bs,
            blocks_per_wave=paged_decode_pallas.walk_wave(full[0]))
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.slot_of_layer(l)
        h = rms_norm(x, lp["ln_op"], cfg.rms_eps, lp["ln_op"].dtype)
        if kind == "state":
            z, gate = _conv_in(h, lp)
            old = jnp.take(state[i], read, axis=0)  # [B, taps - 1, D]
            taps = old.shape[1] + 1
            zs = [old[:, j:j + 1] for j in range(taps - 1)] + [z]
            y = _conv_mix(zs, gate, lp)
            new = jnp.concatenate((old[:, 1:], z.astype(old.dtype)), axis=1)
            state[i] = state[i].at[write].set(new)
        else:
            q, k, v = _qkv(h, lp, pos[:, None], cfg)
            full[i] = write_token(spec, full[i], full_id, at, k[:, 0],
                                  v[:, 0])
            attn = _decode_attention(spec, q[:, 0], full[i], tables["full"],
                                     context_len, interpret, plan)
            y = _attn_out(attn[:, None], lp)
        x, load = _ff_block(x + y, lp, cfg, interpret)
        if load is not None:
            loads.append(load)
    logits, pools = _finish(x[:, 0], params, cfg, full, state, loads)
    if plan is not None:
        pools["attention_read"] = (
            paged_decode_pallas.attention_read_counts(plan))
    return logits, pools


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: Lfm2MoeConfig):
    """Logits [T, V] of one sequence by the equations at the top: float32,
    products at precision highest, no cache, no kernels, no batching, the
    convolution as shifted products, every expert computed for every token
    and masked by the routing."""
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
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    for l, lp in enumerate(p["layers"]):
        h = norm(x, lp["ln_op"])
        if cfg.layer_types[l] == CONV:
            bcu = mm("td,dce->tce", h, lp["w_in"])
            z = bcu[:, 0] * bcu[:, 2]
            taps = cfg.conv_taps
            zp = jnp.concatenate((jnp.zeros((taps - 1, z.shape[1]), f32), z))
            conv = sum(lp["conv_k"][:, t] * zp[t:t + T] for t in range(taps))
            y = mm("td,de->te", bcu[:, 1] * conv, lp["w_out"])
        else:
            q = rope(norm(mm("td,dhk->thk", h, lp["wq"]), lp["q_norm"]))
            k = rope(norm(mm("td,dhk->thk", h, lp["wk"]), lp["k_norm"]))
            v = mm("td,dhk->thk", h, lp["wv"])
            k, v = (jnp.repeat(a, cfg.n_heads // cfg.n_kv_heads, axis=1)
                    for a in (k, v))
            s = mm("qhk,thk->hqt", q, k) * cfg.head_dim**-0.5
            attn = mm("hqt,thk->qhk",
                      jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf),
                                     -1), v)
            y = mm("thk,hkd->td", attn, lp["wo"])
        x = x + y
        h = norm(x, lp["ln_ff"])
        if "mlp" in lp:
            y = swiglu(h, lp["mlp"])
        else:
            s = jax.nn.sigmoid(mm("td,de->te", h, lp["router"]))
            _, picked = lax.top_k(s + lp["route_bias"], cfg.top_k)
            chosen = jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1)
            w = s * chosen
            if cfg.route_norm:
                w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
            w = w * cfg.route_scale
            y = jnp.zeros_like(h)
            for e in range(cfg.n_experts):
                y = y + w[:, e:e + 1] * swiglu(
                    h, jax.tree.map(lambda a: a[e], lp["experts"]))
        x = x + y
    return mm("td,vd->tv", norm(x, p["ln_f"]), p["embed"])
