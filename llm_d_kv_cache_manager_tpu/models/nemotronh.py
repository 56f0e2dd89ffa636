"""The `nemotronh` family on the pod path: a hybrid whose every layer is ONE
mixer, Mamba-2 (a state that is a matrix a head), sparse squared-ReLU experts
with a shared one, or grouped attention without a position encoding, in the
order a pattern string names.  Served through paged prefill, prefix-continue
and decode over a pod cache of two groups: the attention layers' K/V, and the
Mamba-2 layers' state, which here outweighs the K/V by far.

The layer equations (sizes from the model's public ``config.json``; the points
marked + are from the published ``nemotron_h`` / ``mamba2`` modelling code and
the family's paper, arXiv:2504.03624, and are listed under ``assumed`` in the
benchmark's configuration file).  D = ``hidden_size``:

- ``x = E[tokens]``; layer l is one mixer, + pre-norm: ``x' = x +
  Mixer_l(RMSNorm(x))``, ``RMSNorm(x) = w * x / rms(x)`` with epsilon
  ``layer_norm_epsilon`` (+ no ``1 + w``); after the last layer ``logits =
  RMSNorm_f(x) . W_head`` (``tie_word_embeddings`` false).
  ``hybrid_override_pattern`` names the mixer: ``M``, ``E`` or ``*``.
- ``M``, Mamba-2 (H = ``mamba_num_heads``, P = ``mamba_head_dim``, Di = H P (+
  not ``expand`` x D), G = ``n_groups``, N = ``ssm_state_size``, C = Di + 2 G
  N).  ``[z | u | dt] = h . W_in`` (D -> Di + C + H, + in that order, no
  bias).  ``c_t = silu(sum_{j=0..3} k[:, j] * u_{t-3+j} + b_c)`` (depthwise
  causal over the C lanes, ``conv_kernel`` taps, with bias, ``u`` at negative
  positions zero).  ``[x_t | B_t | C_t] = c_t`` (Di -> [H, P]; G N -> [G, N]
  twice; + in that order); head h reads group ``g = h // (H / G)``.  ``d_t =
  softplus(dt_t + dt_bias)`` ([H]; + no clamp).  ``A = -exp(A_log)`` ([H], a
  scalar a head).  ``S_t[h] = exp(d_t[h] A[h]) S_{t-1}[h] + d_t[h] x_t[h] (x)
  B_t[g]`` (``S[h]`` is P x N, float32, ``S_{-1}`` = 0); ``y_t[h] = S_t[h] .
  C_t[g] + D[h] x_t[h]``.  + ``o_t = GroupRMSNorm(y_t * silu(z_t))``: the gate
  first, then an RMS norm over each of G groups of Di / G lanes with one
  learned weight of Di.  ``Mixer = o . W_out`` (Di -> D, no bias).  The state
  after position t is ``(u_{t-2}, u_{t-1}, u_t; S_t)``.
- ``E``, experts.  ``s = sigmoid(h . W_r)`` over all ``n_routed_experts``,
  float32; + the ``num_experts_per_tok`` experts are the top of ``s + b``, the
  bias ``b`` in the selection only (``n_group`` = ``topk_group`` = 1: group
  limiting is the identity); ``w = s[picked] / (sum + 1e-20)``
  (``norm_topk_prob``) x ``routed_scaling_factor``.  ``Expert_e(h) =
  (relu(h . U_e))^2 . V_e`` (``relu2``; + no gate matrix; no bias).  ``Mixer =
  sum_picked w_e Expert_e(h) + Shared(h)``, ``Shared`` the same form at width
  ``moe_shared_expert_intermediate_size``.
- ``*``, attention.  q/k/v/o without bias; causal softmax of ``q . k /
  sqrt(head_dim)``, ``num_attention_heads / num_key_value_heads`` query heads
  a KV head; + no rotary embedding and no other position encoding; no norm on
  q or k.

**Where the program departs from the equations as written** (the reference
below and the benchmark's do none of this):

- *The chip's share of an expert layer.*  ``held = (first, count)``: this
  chip holds that range of the layer's experts; the router scores all of them
  and picks as published, and the layer's result here is the shared expert
  plus the part of the sum that the held experts give
  (`moe_serve.routed_experts`).  What the other experts would add is left
  out, by the reference too, which is handed the same ``held``, and the
  partial sum goes on to the next layer.  No exchange, nothing that stands in
  for the other chips.
- *The scan chunk-wise.*  A prefill's scan is ops/ssd_pallas.py's chunk form
  (``chunk_size`` positions a chunk, products on the MXU, the state carried
  between chunks), mathematically the recurrence above; cumulative sums of
  ``d A`` and the state in float32.  One call of the kernel ends at each
  position whose state the pod keeps (``KVGroupSpec.snapshot_blocks``), so
  the state there is a call's result and nothing of size T x H x P x N is
  made; what of a call is no whole chunk (a suffix that ends inside one) runs
  through the one-position recurrence.  A decode step is that recurrence
  on the slots its tables name, where they lie in the pool
  (``ssd_decode_step_pallas``; in XLA, on the gathered slots, where no TPU
  compiles the kernel).
- *Rounding.*  ``u`` is rounded once, to the serving type, where it is made:
  the convolution of a prefill and the conv state a decode step reads hold
  the same values.  ``S`` is float32 everywhere and never rounded; ``c``,
  ``d``, ``y`` are float32 and rounded only as operands of a matrix product
  (the chunk form's ``x``, ``B``, ``C``).  The residual stream is float32
  (models/layers.py's ``embed``).

The cache (``cache_groups``): the *full* group holds the attention layers' K/V,
one slot a logical block; the *state* group, a slot, every Mamba-2 layer's
``(u_{t-2..t}; S_t)`` after the last position of a block: two arrays a layer,
the conv inputs in the serving type side by side in one row and the matrices
``[H, P, N]`` in float32 (N in the lanes).  At the published sizes a state
slot weighs what 533 blocks of K/V weigh: the state group's rules
(models/pod.py) decide the pod's memory.  Tables are lfm2moe's
(``state_read``, ``state_write``, ``state``).

``reference_logits`` is the plain float32 forward pass of the equations: no
cache, no kernels, the scan a position at a time, the convolution as four
shifted products, every held expert by a mask.
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
    embed, prefill_attention, rms_norm,
)
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas, ssd_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

Params = Dict[str, Any]
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
HI = lax.Precision.HIGHEST
ROUTE_NORM_EPS = 1e-20  # + the published code's, under the picked scores' sum
# Up to this many tokens (a decode step, a hit prefill's suffix) go through
# every held expert in one batched product under the routing's mask, more
# through `lax.ragged_dot` (`moe_serve.routed_experts` has the two).  Read on
# the chip at this layer's sizes, ms batched / sorted by rows: 128 1.75 /
# 16.1, 512 3.49 / 18.1, 1024 7.08 / 19.4 (PERF.md, PR 49): the sorted form's
# two products cost 16 ms before their first row, so the batched form holds
# as far as it was read.  It stays the einsum in a decode step too: the kernel
# that skips an untouched expert's copies read 2.09 ms at all 64 held and
# 1.85 at the 55 a step of the cell touches against the einsum's 1.80, and
# 2.4 ms a call more, because 1856 lanes are 14.5 tiles and `w_up` is
# re-laid-out for it (my chip run, PR 54; `moe_serve.decode_kernel_serves`).
BATCHED_EXPERTS_MAX_TOKENS = 1024


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 256
    d_model: int = 64
    pattern: str = "MEM*E"  # hybrid_override_pattern: a mixer a layer
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    mamba_heads: int = 4
    mamba_head_dim: int = 8
    n_groups: int = 2
    d_state: int = 16
    d_conv: int = 4
    chunk: int = 32  # positions a chunk of a prefill's scan
    d_expert: int = 32
    d_shared: int = 64
    n_experts: int = 8  # the router's width: every expert of a layer
    held: Tuple[int, int] = (0, 8)  # (first, count): the experts held here
    top_k: int = 2
    route_norm: bool = True
    route_scale: float = 2.5
    rms_eps: float = 1e-5
    block_size: int = 16
    dtype: str = "bfloat16"
    # The state group of the pod's cache: how many slots it has, and every
    # how many blocks a prefill keeps a snapshot.
    state_slots: int = 32
    state_stride_blocks: int = 2

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Lanes the convolution runs over: x, then B and C of every group."""
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def experts_held(self) -> int:
        return self.held[1]

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def index_of_layer(self, layer: int) -> int:
        """A layer's place among the layers of its kind."""
        return self.pattern[:layer].count(self.pattern[layer])


def cache_groups(cfg: NemotronHConfig) -> Dict[str, KVGroupSpec]:
    """What one slot of each group holds; models/pod.py and `new_pool` read
    bytes and shapes from here.  Two KV heads: a block's positions and heads
    as neighbouring rows (``rows``: as the last axis but one, two heads would
    be padded to the chip's tile of 8 or 16), which is also the layout the
    paged kernel's shared pass reads, and 16 sequences walk each shared
    prompt here."""
    return {
        "full": KVGroupSpec(cfg.count(ATTENTION), cfg.block_size,
                            cfg.n_kv_heads, cfg.head_dim, cfg.dtype,
                            rows=True),
        "state": KVGroupSpec(
            cfg.count(MAMBA), cfg.block_size, 0, 0, cfg.dtype,
            state_shape=((((cfg.d_conv - 1) * cfg.conv_dim,), cfg.dtype),
                         ((cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state),
                          "float32")),
            stride_blocks=cfg.state_stride_blocks),
    }


def cache_policy(cfg: NemotronHConfig) -> dict:
    """What models/pod.py needs to know of this family's cache: the state
    group, the order of reuse, and that a decode call launches the step after
    its own (`decode_ahead`: the family's deployments are long generations,
    thousands of steps between two of a sequence's events).  `_mamba_decode`
    writes the slot its table says, which under that key is never the one it
    reads (`pod.StateGroup._alternate`)."""
    return {
        "specs": cache_groups(cfg),
        "state": {"slots": cfg.state_slots},
        "protect_asked": True,
        "decode_ahead": True,
    }


def new_pool(cfg: NemotronHConfig, pool_blocks: int) -> dict:
    """The pod's pools as a pytree, one array a layer for K/V and two a Mamba-2
    layer (layer i's conv inputs at ``state[2 i]``, its matrices at ``state[2
    i + 1]``), each updated in place by the step that writes it."""
    groups = cache_groups(cfg)
    full, state = groups["full"], groups["state"]
    return {
        "full": [jnp.zeros(full.layer_shape(pool_blocks), jnp.dtype(full.dtype))
                 for _ in range(full.num_layers)],
        "state": [jnp.zeros((cfg.state_slots,) + shape, jnp.dtype(dtype))
                  for _ in range(state.num_layers)
                  for shape, dtype in state.state_parts],
    }


def from_published(cfg: dict, block_size: int) -> NemotronHConfig:
    """The program's configuration from the keys of the public
    ``config.json``, the configuration file's ``published`` counts (where a
    key states this chip's share: ``n_routed_experts`` is what is held, the
    router keeps the published width), its ``held`` group and its ``serving``
    group.  What the module does not implement is an error, not a default."""
    for key, want in (
        ("n_group", 1),
        ("topk_group", 1),
        ("n_shared_experts", 1),
        ("mlp_hidden_act", "relu2"),
        ("mamba_hidden_act", "silu"),
        ("use_conv_bias", True),
        ("mamba_proj_bias", False),
        ("mlp_bias", False),
        ("attention_bias", False),
        ("use_bias", False),
        ("tie_word_embeddings", False),
    ):
        if cfg[key] != want:
            raise ValueError(f"nemotronh: {key}={cfg[key]!r} is not implemented")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name "
                         "num_hidden_layers layers")
    unknown = set(pattern) - {MAMBA, EXPERTS, ATTENTION}
    if unknown:
        raise ValueError(f"nemotronh: mixers {sorted(unknown)} are not "
                         "implemented")
    if cfg["moe_intermediate_size"] != cfg["intermediate_size"]:
        raise ValueError("nemotronh: an expert's width is intermediate_size")
    serving = cfg["serving"]
    chunk, stride = cfg["chunk_size"], serving["state_stride_blocks"]
    if chunk % block_size or (stride * block_size) % chunk:
        raise ValueError("nemotronh: a chunk of the scan is whole blocks, and "
                         "a kept boundary a whole chunk")
    held = cfg["n_routed_experts"]
    n_experts = cfg.get("published", {}).get("n_routed_experts", held)
    first = cfg.get("held", {}).get("experts_first", 0)
    if first + held > n_experts:
        raise ValueError("nemotronh: the held experts lie past the router's")
    return NemotronHConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        pattern=pattern,
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        n_groups=cfg["n_groups"],
        d_state=cfg["ssm_state_size"],
        d_conv=cfg["conv_kernel"],
        chunk=chunk,
        d_expert=cfg["moe_intermediate_size"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        n_experts=n_experts,
        held=(first, held),
        top_k=cfg["num_experts_per_tok"],
        route_norm=cfg["norm_topk_prob"],
        route_scale=float(cfg["routed_scaling_factor"]),
        rms_eps=float(cfg["layer_norm_epsilon"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
        state_slots=serving["state_slots"],
        state_stride_blocks=stride,
    )


def init_params(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """Seeded weights that keep the recurrence where a trained model's is (+
    the published initialisation): ``A`` uniform in [1, 16] a head, ``D`` = 1,
    ``dt_bias`` the inverse softplus of values log-uniform in [1e-3, 1e-1];
    every matrix N(0, 1/fan-in); norm weights, the convolution's taps and bias
    and the selection bias are not constant, so that leaving one out of a step
    shows.  An expert layer's stacks hold the held experts only."""
    dtype = jnp.dtype(cfg.dtype)
    f32 = jnp.float32
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hm, Di, C = cfg.mamba_heads, cfg.d_inner, cfg.conv_dim
    E, Fe, Fs = cfg.n_experts, cfg.d_expert, cfg.d_shared
    keys = iter(jax.random.split(rng, 12 * cfg.n_layers + 4))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, f32)
                * fan_in**-0.5).astype(dtype)

    def norm(n):
        return (1.0 + 0.1 * jax.random.normal(next(keys), (n,), f32)
                ).astype(dtype)

    def relu2(width, lead=()):
        return {"w_up": w(lead + (D, width), D),
                "w_down": w(lead + (width, D), width)}

    out = []
    for kind in cfg.pattern:
        lp = {"ln": norm(D)}
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(
                next(keys), (Hm,), f32, np.log(1e-3), np.log(1e-1)))
            lp.update(
                w_in=w((D, Di + C + Hm), D),
                conv_k=w((C, cfg.d_conv), cfg.d_conv),
                conv_b=(0.1 * jax.random.normal(next(keys), (C,), f32)
                        ).astype(dtype),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.log(jax.random.uniform(
                    next(keys), (Hm,), f32, 1.0, 16.0)),
                d_skip=jnp.ones((Hm,), f32),
                norm=norm(Di), w_out=w((Di, D), Di))
        elif kind == EXPERTS:
            lp.update(
                router=w((D, E), D),
                route_bias=0.05 * jax.random.normal(next(keys), (E,), f32),
                experts=relu2(Fe, (cfg.experts_held,)), shared=relu2(Fs))
        else:
            lp.update(wq=w((D, H, Dh), D), wk=w((D, Hkv, Dh), D),
                      wv=w((D, Hkv, Dh), D), wo=w((H, Dh, D), H * Dh))
        out.append(lp)
    return {"embed": w((cfg.vocab_size, D), D), "ln_f": norm(D),
            "head": w((cfg.vocab_size, D), D), "layers": out}


# ------------------------------------------------------------ the model step

# -- Mamba-2


def _mamba_in(h, lp, cfg):
    """h: [B, T, D] in the serving type -> (the gate z [B, T, Di] float32, u
    [B, T, C] rounded once to the serving type, dt [B, T, H] float32 before
    its bias)."""
    zud = jnp.einsum("btd,de->bte", h, lp["w_in"],
                     preferred_element_type=jnp.float32)
    Di, C = cfg.d_inner, cfg.conv_dim
    return zud[..., :Di], zud[..., Di:Di + C].astype(h.dtype), zud[..., Di + C:]


def _mamba_ssm_in(taps, dt, lp, cfg):
    """taps: the convolution's inputs, oldest first, each [B, T, C]; dt as
    `_mamba_in` gives it -> (x [B, T, H, P], B_t and C_t [B, T, G, N], d
    [B, T, H]), float32: ``c = silu(sum_j k[:, j] u_j + b_c)`` split, and ``d =
    softplus(dt + dt_bias)``."""
    f32 = jnp.float32
    k = lp["conv_k"].astype(f32)
    c = jax.nn.silu(sum(k[:, j] * u.astype(f32) for j, u in enumerate(taps))
                    + lp["conv_b"].astype(f32))
    lead, Di, GN = c.shape[:2], cfg.d_inner, cfg.n_groups * cfg.d_state
    group = lead + (cfg.n_groups, cfg.d_state)
    return (c[..., :Di].reshape(lead + (cfg.mamba_heads, cfg.mamba_head_dim)),
            c[..., Di:Di + GN].reshape(group), c[..., Di + GN:].reshape(group),
            jax.nn.softplus(dt + lp["dt_bias"].astype(f32)))


def _mamba_out(y, x, z, lp, cfg):
    """y: the scan's output [B, T, H, P] float32 -> the mixer's: the ``D``
    term, the gate, the norm over each group's lanes, ``. W_out``."""
    f32 = jnp.float32
    y = y + lp["d_skip"].astype(f32)[:, None] * x
    lead = y.shape[:2]
    g = (y.reshape(lead + (-1,)) * jax.nn.silu(z)).reshape(
        lead + (cfg.n_groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_eps)
    o = g.reshape(lead + (-1,)) * lp["norm"].astype(f32)
    return jnp.einsum("bte,ed->btd", o.astype(lp["w_out"].dtype), lp["w_out"],
                      preferred_element_type=f32)


def _scan(x, d, a, bm, cm, s0, ends, cfg, interpret):
    """The scan over a prefill's positions, a call of the chunk form up to
    each of ``ends`` (static, rising, the last the prefill's own): (y [B, T,
    H, P] float32, the state at each end [B, len(ends), H, P, N])."""
    act = jnp.dtype(cfg.dtype)
    kernel = paged_decode_pallas.serves(interpret)

    def chunks(lo, hi, s):
        args = (x[:, lo:hi].astype(act), d[:, lo:hi], a,
                bm[:, lo:hi].astype(act), cm[:, lo:hi].astype(act), s)
        if kernel:
            return ssd_pallas.ssd_chunk_scan_pallas(
                *args, chunk=cfg.chunk, interpret=interpret)
        return ssd_pallas.ssd_chunk_scan(*args, cfg.chunk)

    ys, kept, start, s = [], [], 0, s0
    for end in ends:
        whole = start + (end - start) // cfg.chunk * cfg.chunk
        if whole > start:
            y, s = chunks(start, whole, s)
            ys.append(y)
        if end > whole:  # what is left of a call that ends inside a chunk
            y, s = ssd_pallas.ssd_recurrence(
                x[:, whole:end], d[:, whole:end], a, bm[:, whole:end],
                cm[:, whole:end], s)
            ys.append(y)
        kept.append(s)
        start = end
    return jnp.concatenate(ys, axis=1), jnp.stack(kept, axis=1)


def _mamba_prefill(h, lp, conv0, s0, ends, cfg, interpret):
    """A Mamba-2 layer over a prefill's positions.  conv0: [B, taps - 1, C],
    the inputs of the positions before the first, and s0: [B, H, P, N], the
    state there (zeros at a prompt's start, a snapshot for a continue); ends:
    the static positions after which the state is kept.  Returns (the mixer's
    output, the conv inputs at each end [B, n, (taps - 1) C], the matrices
    there [B, n, H, P, N])."""
    z, u, dt = _mamba_in(h, lp, cfg)
    T, taps = u.shape[1], cfg.d_conv
    up = jnp.concatenate((conv0.astype(u.dtype), u), axis=1)
    x, bm, cm, d = _mamba_ssm_in([up[:, j:j + T] for j in range(taps)], dt,
                                 lp, cfg)
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    y, states = _scan(x, d, a, bm, cm, s0, ends, cfg, interpret)
    # the inputs up to each end, oldest first
    at = np.asarray(ends)[:, None] + np.arange(taps - 1)[None, :]
    snap = up[:, at]  # [B, n, taps - 1, C]
    return (_mamba_out(y, x, z, lp, cfg),
            snap.reshape(snap.shape[:2] + (-1,)), states)


def _mamba_decode(h, lp, conv_pool, ssm_pool, read, write, cfg, interpret):
    """One position of a Mamba-2 layer for each sequence: the state of slot
    `read` advanced by one input into slot `write`.  h: [B, 1, D].  Returns
    (the mixer's output [B, 1, D], the pools).

    The matrices are advanced where they lie in the pool
    (`ssd_pallas.ssd_decode_step_pallas`: a slot is 2 MB here and crosses
    memory once in and once out, the next sequence's on its way while one is
    advanced) where the paged kernel serves too; elsewhere the plain form,
    the step on the gathered slots and a scatter back."""
    z, u, dt = _mamba_in(h, lp, cfg)
    B, taps = u.shape[0], cfg.d_conv
    old = jnp.take(conv_pool, read, axis=0).reshape(B, taps - 1, -1)
    x, bm, cm, d = _mamba_ssm_in(
        [old[:, j:j + 1] for j in range(taps - 1)] + [u], dt, lp, cfg)
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    step = (x[:, 0], d[:, 0], a, bm[:, 0], cm[:, 0])
    if paged_decode_pallas.serves(interpret):
        ssm_pool, y = ssd_pallas.ssd_decode_step_pallas(
            ssm_pool, read, write, *step, interpret=interpret)
    else:
        s, y = ssd_pallas.ssd_step(jnp.take(ssm_pool, read, axis=0), *step)
        ssm_pool = ssm_pool.at[write].set(s)
    conv_pool = conv_pool.at[write].set(jnp.concatenate(
        (old[:, 1:], u.astype(old.dtype)), axis=1).reshape(B, -1))
    return _mamba_out(y[:, None], x, z, lp, cfg), conv_pool, ssm_pool


# -- experts


def _relu2(x, w):
    """x in the serving type; what goes into the stream is float32."""
    f32 = jnp.float32
    up = jnp.einsum("...d,df->...f", x, w["w_up"], preferred_element_type=f32)
    return jnp.einsum("...f,fd->...d",
                      jnp.square(jax.nn.relu(up)).astype(x.dtype), w["w_down"],
                      preferred_element_type=f32)


def _moe(h, lp, cfg, interpret):
    """h: [B, T, D] float32 -> (the shared expert plus the held experts' part
    of each token's picked sum, float32; the layer's counts: held experts
    with a pick, the most picks of one, all picks, the picks that fell on a
    held expert)."""
    act = lp["router"].dtype  # the serving type

    def chunk(rows):
        picked, w = moe_serve.route(
            rows, lp["router"], lp["route_bias"], cfg.top_k, cfg.route_norm,
            cfg.route_scale, ROUTE_NORM_EPS)
        return moe_serve.routed_experts(
            rows.astype(act), picked, w, lp["experts"], cfg.n_experts,
            batched=rows.shape[0] <= BATCHED_EXPERTS_MAX_TOKENS,
            held=cfg.held, interpret=interpret)

    out, sizes = moe_serve.in_chunks(h, chunk)
    here = sizes[:-1]  # the last count: the picks that fell outside
    load = jnp.stack((jnp.sum(here > 0), jnp.max(here), jnp.sum(sizes),
                      jnp.sum(here)))
    return _relu2(h.astype(act), lp["shared"]) + out.reshape(h.shape), load


# -- attention


def _qkv(h, lp):
    """h: [B, T, D] in the serving type -> q in float32, k and v in the
    cache's type.  No norm, no position encoding."""
    f32 = jnp.float32
    q = jnp.einsum("btd,dhk->bthk", h, lp["wq"], preferred_element_type=f32)
    k = jnp.einsum("btd,dhk->bthk", h, lp["wk"], preferred_element_type=f32)
    v = jnp.einsum("btd,dhk->bthk", h, lp["wv"], preferred_element_type=f32)
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
            **layout)
    return paged_attention(q, pool, table, context_len, **layout)


def _finish(x, params, cfg, full, state, loads):
    pools = {"full": full, "state": state}
    if loads:
        pools["load"] = jnp.stack(loads).astype(jnp.int32)
    return layers.logits(x, params, cfg), pools


def _prefill(params, tokens, pools, tables, prefix_len, cfg, interpret):
    B, S = tokens.shape
    bs = cfg.block_size
    if prefix_len % bs or S % bs:
        raise ValueError("a prefill's prefix and tokens must be whole blocks")
    npre, nsuf = prefix_len // bs, S // bs
    specs = cache_groups(cfg)
    ends = [(i - npre + 1) * bs
            for i in specs["state"].snapshot_blocks(npre, nsuf)]
    x = embed(params, tokens)
    full, state, loads = list(pools["full"]), list(pools["state"]), []
    write = tables.get("state_write")
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.pattern[l], cfg.index_of_layer(l)
        h32 = rms_norm(x, lp["ln"], cfg.rms_eps)  # the router reads this
        h = h32.astype(lp["ln"].dtype)
        if kind == MAMBA:
            conv, ssm = state[2 * i], state[2 * i + 1]
            if npre:
                conv0 = jnp.take(conv, tables["state_read"], axis=0).reshape(
                    B, cfg.d_conv - 1, -1)
                s0 = jnp.take(ssm, tables["state_read"], axis=0)
            else:
                conv0 = jnp.zeros((B, cfg.d_conv - 1, cfg.conv_dim), conv.dtype)
                s0 = jnp.zeros((B,) + ssm.shape[1:], ssm.dtype)
            y, snap, states = _mamba_prefill(h, lp, conv0, s0, ends, cfg,
                                             interpret)
            state[2 * i] = conv.at[write.reshape(-1)].set(
                snap.reshape((-1,) + conv.shape[1:]).astype(conv.dtype))
            state[2 * i + 1] = ssm.at[write.reshape(-1)].set(
                states.reshape((-1,) + ssm.shape[1:]))
        elif kind == EXPERTS:
            y, load = _moe(h32, lp, cfg, interpret)
            loads.append(load)
        else:
            q, k, v = _qkv(h, lp)
            keys, values = k, v
            if npre:
                pre_k, pre_v = gather_prefix(
                    specs["full"], full[i], tables["full"][:, :npre], k.dtype)
                keys = jnp.concatenate((pre_k, k), axis=1)
                values = jnp.concatenate((pre_v, v), axis=1)
            y = _attn_out(prefill_attention(q, keys, values, cfg, prefix_len,
                                            None, interpret), lp)
            full[i] = write_blocks(
                specs["full"], full[i], tables["full"][:, npre:npre + nsuf],
                k, v)
        x = x + y
    return _finish(x[:, -1:], params, cfg, full, state, loads)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    cfg: NemotronHConfig,
    interpret: bool = False,
):
    """Prefill writing each attention layer's K/V into the full group and the
    Mamba-2 layers' state at the kept block boundaries into the state group.

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
    cfg: NemotronHConfig,
    interpret: bool = False,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).

    tokens: [B, S] suffix.  tables["full"]: [B, (prefix_len + S)/block], the
    prefix's blocks then the blocks to write.  tables["state_read"]: [B], the
    slot of the snapshot after the prefix's last block, which every Mamba-2
    layer resumes from (it gathers no prefix); tables["state_write"]: [B, n]
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
    cfg: NemotronHConfig,
    interpret: bool = False,
):
    """One decode step over both groups.

    tokens: [B]; context_len: [B], the current token included.
    tables["full"]: [B, max_blocks] logical blocks.  tables["state"]: [B, 2]:
    the state slot read and the one written (`pod.StateGroup`: the same slot
    inside a block, or under `decode_ahead` always another).  Writes the new
    token's K/V, advances every Mamba-2 layer's state by one position, and
    returns (logits [B, V], pools).
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
    if full and paged_decode_pallas.serves(interpret):
        plan = paged_decode_pallas.shared_prefix_plan(
            tables["full"], context_len, block_size=bs,
            blocks_per_wave=paged_decode_pallas.walk_wave(full[0]))
    for l, lp in enumerate(params["layers"]):
        kind, i = cfg.pattern[l], cfg.index_of_layer(l)
        h32 = rms_norm(x, lp["ln"], cfg.rms_eps)
        h = h32.astype(lp["ln"].dtype)
        if kind == MAMBA:
            y, state[2 * i], state[2 * i + 1] = _mamba_decode(
                h, lp, state[2 * i], state[2 * i + 1], read, write, cfg,
                interpret)
        elif kind == EXPERTS:
            y, load = _moe(h32, lp, cfg, interpret)
            loads.append(load)
        else:
            q, k, v = _qkv(h, lp)
            full[i] = write_token(spec, full[i], full_id, at, k[:, 0],
                                  v[:, 0])
            y = _attn_out(_decode_attention(
                spec, q[:, 0], full[i], tables["full"], context_len,
                interpret, plan)[:, None], lp)
        x = x + y
    logits, pools = _finish(x[:, 0], params, cfg, full, state, loads)
    if plan is not None:
        pools["attention_read"] = (
            paged_decode_pallas.attention_read_counts(plan))
    return logits, pools


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: NemotronHConfig):
    """Logits [T, V] of one sequence by the equations at the top: float32,
    products at precision highest, no cache, no kernels, no batching, the scan
    a position at a time, the convolution as shifted products, every expert
    of ``params`` computed for every token and masked by the routing.
    ``params`` stacks the experts ``cfg.held`` names of those the router
    scores; the picks outside that range add nothing."""
    f32 = jnp.float32
    p = jax.tree.map(lambda a: a.astype(f32), params)
    T = len(tokens)
    first, count = cfg.held
    H, P, G, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.d_state
    Di, taps = cfg.d_inner, cfg.d_conv

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    def norm(x, w):
        return x * lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps) * w

    def relu2(h, w):
        return mm("tf,fd->td",
                  jnp.square(jax.nn.relu(mm("td,df->tf", h, w["w_up"]))),
                  w["w_down"])

    def mamba(h, lp):
        zud = mm("td,de->te", h, lp["w_in"])
        z, u, dt = (zud[:, :Di], zud[:, Di:Di + cfg.conv_dim],
                    zud[:, Di + cfg.conv_dim:])
        up = jnp.concatenate((jnp.zeros((taps - 1, u.shape[1]), f32), u))
        c = jax.nn.silu(sum(lp["conv_k"][:, j] * up[j:j + T]
                            for j in range(taps)) + lp["conv_b"])
        x = c[:, :Di].reshape(T, H, P)
        bm = c[:, Di:Di + G * N].reshape(T, G, N)
        cm = c[:, Di + G * N:].reshape(T, G, N)
        d = jax.nn.softplus(dt + lp["dt_bias"])  # [T, H]
        a = -jnp.exp(lp["a_log"])

        def step(s, xs):
            x_t, d_t, b_t, c_t = xs
            b_h, c_h = (jnp.repeat(v, H // G, axis=0) for v in (b_t, c_t))
            s = (jnp.exp(d_t * a)[:, None, None] * s
                 + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
            return s, jnp.sum(s * c_h[:, None, :], axis=-1)

        _, y = lax.scan(step, jnp.zeros((H, P, N), f32), (x, d, bm, cm))
        y = y + lp["d_skip"][:, None] * x
        g = (y.reshape(T, Di) * jax.nn.silu(z)).reshape(T, G, Di // G)
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + cfg.rms_eps)
        return mm("te,ed->td", g.reshape(T, Di) * lp["norm"], lp["w_out"])

    def experts(h, lp):
        s = jax.nn.sigmoid(mm("td,de->te", h, lp["router"]))
        _, picked = lax.top_k(s + lp["route_bias"], cfg.top_k)
        w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1)
        if cfg.route_norm:
            w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
        w = w * cfg.route_scale
        y = relu2(h, lp["shared"])
        for e in range(count):
            y = y + w[:, first + e:first + e + 1] * relu2(
                h, jax.tree.map(lambda a: a[e], lp["experts"]))
        return y

    def attention(h, lp):
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        q = mm("td,dhk->thk", h, lp["wq"])
        k, v = (jnp.repeat(mm("td,dhk->thk", h, lp[n]),
                           cfg.n_heads // cfg.n_kv_heads, axis=1)
                for n in ("wk", "wv"))
        s = mm("qhk,thk->hqt", q, k) * cfg.head_dim**-0.5
        attn = mm("hqt,thk->qhk",
                  jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), -1),
                  v)
        return mm("thk,hkd->td", attn, lp["wo"])

    mixers = {MAMBA: mamba, EXPERTS: experts, ATTENTION: attention}
    x = jnp.take(p["embed"], jnp.asarray(tokens), axis=0)
    for kind, lp in zip(cfg.pattern, p["layers"]):
        x = x + mixers[kind](norm(x, lp["ln"]), lp)
    return mm("td,vd->tv", norm(x, p["ln_f"]), p["head"])
