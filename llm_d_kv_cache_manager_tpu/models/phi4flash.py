"""The `phi4flash` family on the pod path: a decoder-hybrid-decoder (SambaY).
State-space (Mamba-1) layers alternate with window attention in the first
half, one full attention layer follows whose K/V every later attention layer
reads (they keep none of their own), and gated memory units that reuse the
last Mamba layer's scan output stand between those.  Every attention is
differential.  Served through paged prefill, prefix-continue and decode over
a pod cache of three groups: full K/V (one layer), window K/V, and the Mamba
layers' state.

The layer equations (sizes from the model's public ``config.json``; the points
marked + are from the published ``phi4flash`` modelling code and the
architecture's paper, arXiv:2507.06607, and are listed under ``assumed`` in
the benchmark's configuration file).  L layers, L a multiple of 4; h = L/2:

- ``x = E[tokens]``; after the last layer ``logits = LN_out(x) . E^T``
  (``tie_word_embeddings``; + a final LayerNorm).  ``LN`` is LayerNorm with
  weight and bias, ``layer_norm_eps``.  + No position encoding anywhere.
- Layer l, + pre-norm, two norms: ``a = x + Mixer_l(LN_in(x))``;
  ``x' = a + FF(LN_post(a))``; ``FF(h) = (silu(g) * u) . W_down``,
  ``[g, u] = h . W_gu`` (no bias).
- ``Mixer`` = Mamba-1 on even layers l <= h (``mb_per_layer`` 2).  With Di =
  ``expand`` x D, N = ``d_state``, R = ``dt_rank``: ``[u, z] = h . W_in`` (+ no
  bias); ``c_t = silu(sum_{j=0..3} k[:, j] * u_{t-3+j} + b_c)`` (+ depthwise
  causal, ``d_conv`` = 4 taps, with bias, ``u`` at negative positions zero);
  ``[d, B_t, C_t] = c_t . W_x`` (Di -> R + 2 N, no bias); ``D_t = softplus(d .
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(D_t * A) * s_{t-1} + (D_t *
  c_t) (x) B_t`` (``s`` is Di x N, float32, ``s_{-1}`` = 0); ``m_t = s_t . C_t
  + D * c_t``; ``y = (m * silu(z)) . W_out`` (no bias).  The state after
  position t is ``(u_{t-2}, u_{t-1}, u_t; s_t)``.  Layer h also hands ``m`` on
  (its scan output with the ``D`` term, before its own gate).
- ``Mixer`` = gated memory unit on even layers l > h: ``y = (m * silu(h . W_1))
  . W_2`` (+ no bias), ``m`` layer h's at the same position.  It keeps nothing.
- ``Mixer`` = differential attention on odd layers (+; H query heads, Hkv KV
  heads of d = D / H; + q, k, v and out projections with bias).  By parity:
  ``q1 = q[0::2]``, ``q2 = q[1::2]``, ``k1 = k[0::2]``, ``k2 = k[1::2]``, ``v1 =
  v[0::2]``, ``v2 = v[1::2]``.  With ``Att(q, k, v)`` = causal softmax of
  ``q.k / sqrt(d)`` times ``v``, grouped two query heads a KV head: ``o1 =
  [Att(q1, k1, v1) | Att(q1, k1, v2)]``, ``o2 = [Att(q2, k2, v1) | Att(q2, k2,
  v2)]`` (H/2 heads of 2 d); ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  four learned vectors of d a layer, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; ``o =
  RMSNorm_2d(o1 - lam o2) (1 - lam0)`` (a learned weight of 2 d, epsilon 1e-5),
  reshaped to H x d, then ``. W_o + b_o``.  Odd l < h: keys within the last
  ``sliding_window`` positions (the query's own included).  Layer h + 1: every
  position; its K and V are the cross-decoder's.  Odd l > h + 1: queries only,
  over layer h + 1's K and V, with their own ``lam`` vectors and norm.

**Where the program departs from the equations as written** (the reference
below and the benchmark's do none of this):

- *Pair-wise heads.*  A position's K/V are stored as Hkv / 2 heads of 2 d,
  ``[K_2p | K_2p+1]`` and ``[V_2p | V_2p+1]``, and the kernels are given H
  query heads of 2 d whose other half is zero (``[q | 0]`` for the heads of
  ``q1``, ``[0 | q]`` for ``q2``), times sqrt 2 so that the kernels' ``(2
  d)^-1/2`` is the model's ``d^-1/2``.  Query heads 4p .. 4p + 3 are the two of
  ``q1`` and the two of ``q2`` that belong to pair p: grouped attention of four
  query heads a KV head, and one call returns ``o1`` (even heads) and ``o2``
  (odd heads) whole.  The same bytes of K/V, a head size the kernels serve
  (128 at the published sizes), no new kernel; the zero halves are multiplied.
- *The lower decoder on the last position only.*  Layers h + 2 .. L - 1 own no
  cache, and a prefill hands back only its last row of logits, so
  ``prefill_paged`` and ``prefill_continue`` run layers 0 .. h + 1 over every
  new position and the rest over the last position alone (with ``m`` of that
  position and layer h + 1's K/V of all): exact for that row, and a prefill is
  linear in its length but for layer h + 1.
- *The scan in chunks of a block.*  A prefill's selective scan is a
  ``lax.scan`` over blocks with the block's 16 positions unrolled inside, in
  float32, the state ``[N, Di]`` (Di in the lanes) its carry: the state at every
  block boundary is a carry, and those the pod keeps
  (``KVGroupSpec.snapshot_blocks``) are written to the state group.  Nothing of
  size T x Di x N is ever made.  A decode step is the one-position recurrence.
- *Rounding.*  ``u`` is rounded once, to the serving type, where it is made:
  the convolution of a prefill and the conv state a decode step reads hold the
  same values.  ``s`` is float32 everywhere and never rounded; ``c``, ``D_t``,
  ``m`` are float32 and rounded only as operands of the next matrix product.
  The residual stream is float32 (models/layers.py's ``embed``).
- *Stacked layers.*  The (Mamba, window) pairs and the (memory unit, cross)
  pairs are each one ``lax.scan`` over stacked weights (compile time), the
  pools their carry; a group's pool is one array over all its layers, layer i's
  slot s at ``i * slots + s`` (models/llama.py's ``_scan_layers``).

The cache (``cache_groups``): the *full* group holds layer h + 1's K/V, one
slot a logical block, read by 1 + (L/4 - 1) layers (``KVGroupSpec.readers``);
the *window* group the window layers' K/V of a block a slot; the *state* group,
a slot, every Mamba layer's ``(u_{t-2..t}; s_t)`` after the last position of a
block: two arrays a layer, the conv inputs in the serving type (side by side
in one row: three rows of Di would be padded to the chip's tile) and the
scan's state ``[N, Di]`` in float32.  models/pod.py keeps all three and serves a prefix only at a
length all three admit.  Tables are afmoe's (``window``, ``first``) and
lfm2moe's (``state_read``, ``state_write``, ``state``) side by side.

``reference_logits`` is the plain float32 forward pass of the equations: no
cache, no kernels, the scan a position at a time, the convolution as four
shifted products, the four ``Att`` products of a layer as written, every
layer over every position.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVGroupSpec, decode_view, gather_prefix, write_blocks, write_token,
)
from llm_d_kv_cache_manager_tpu.models.layers import (
    dense_attention, embed, prefill_attention,
)
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)

Params = Dict[str, Any]
HI = lax.Precision.HIGHEST
SUB_NORM_EPS = 1e-5  # + the published code's, in the norm behind the difference
# Pool blocks the paged decode kernel takes a grid step over a window layer's
# table (the full group's is walked in waves the kernel sizes itself, PR 41),
# read on the chip at the cell's shapes when both took it (64 sequences of
# 1.5-6.7 k over 8 shared prompts, a whole decode step): 8 / 16 / 32 / 64
# blocks gave 45.6 / 42.3 / 42.1 / 42.9 ms (my chip run, PR 35).
DECODE_BLOCKS_PER_STEP = 32


@dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_layers: int = 8
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 128
    window: int = 32
    d_state: int = 4
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4
    ln_eps: float = 1e-5
    block_size: int = 16
    dtype: str = "bfloat16"
    # The pod's cache beside the full group: slots of the window group and
    # how many trailing blocks of a miss prefill get one; slots of the state
    # group and every how many blocks a prefill keeps a snapshot.
    window_slots: int = 64
    window_store_blocks: int = 4
    state_slots: int = 32
    state_stride_blocks: int = 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_front(self) -> int:
        """(Mamba, window attention) pairs: layers 0 .. L/2 - 1."""
        return self.n_layers // 4

    @property
    def n_back(self) -> int:
        """(memory unit, cross attention) pairs: layers L/2 + 2 .. L - 1."""
        return self.n_layers // 4 - 1

    def lam0(self, layer) -> Any:
        """lam0 of an attention layer, from the layer's index."""
        return 0.8 - 0.6 * np.exp(-0.3 * np.asarray(layer, np.float32))


def cache_groups(cfg: Phi4FlashConfig) -> Dict[str, KVGroupSpec]:
    """What one slot of each group holds; models/pod.py and `new_pool` read
    bytes and shapes from here.  K/V slots are pair-wise: Hkv / 2 heads of
    twice the head size (the module's head)."""
    pairs, wide = cfg.n_kv_heads // 2, 2 * cfg.head_dim
    return {
        "full": KVGroupSpec(1, cfg.block_size, pairs, wide, cfg.dtype,
                            rows=True, readers=1 + cfg.n_back),
        "window": KVGroupSpec(cfg.n_front, cfg.block_size, pairs, wide,
                              cfg.dtype, window=cfg.window, rows=True),
        "state": KVGroupSpec(
            cfg.n_front + 1, cfg.block_size, 0, 0, cfg.dtype,
            state_shape=((((cfg.d_conv - 1) * cfg.d_inner,), cfg.dtype),
                         ((cfg.d_state, cfg.d_inner), "float32")),
            stride_blocks=cfg.state_stride_blocks),
    }


def cache_policy(cfg: Phi4FlashConfig) -> dict:
    """What models/pod.py needs to know of this family's cache: a window
    group and a state group beside the full one, each group's spec (the
    full group's says how many layers read it), the order of reuse, and
    that a decode call launches the step after its own (`decode_ahead`:
    the family's deployments are long generations, thousands of steps
    between two of a sequence's events, so a step launched ahead is nearly
    always taken).  `_mamba_decode` writes the slot its table says, which
    under that key is never the one it reads (`pod.StateGroup._alternate`)."""
    return {
        "specs": cache_groups(cfg),
        "window": {
            "slots": cfg.window_slots,
            "store_blocks": cfg.window_store_blocks,
            "lazy": True,
        },
        "state": {"slots": cfg.state_slots},
        "protect_asked": True,
        "decode_ahead": True,
    }


def new_pool(cfg: Phi4FlashConfig, pool_blocks: int) -> dict:
    """The pod's pools as a pytree: the full group's one layer; the window
    group's layers in one array (layer i's slot s at ``i * window_slots +
    s``); the state group's two arrays, each over all Mamba layers the same
    way.  All are carried through the layer scans and updated in place."""
    groups = cache_groups(cfg)
    full, window, state = groups["full"], groups["window"], groups["state"]
    slots = state.num_layers * cfg.state_slots
    return {
        "full": [jnp.zeros(full.layer_shape(pool_blocks), jnp.dtype(full.dtype))],
        "window": [jnp.zeros(
            window.layer_shape(window.num_layers * cfg.window_slots),
            jnp.dtype(window.dtype))],
        "state": [jnp.zeros((slots,) + shape, jnp.dtype(dtype))
                  for shape, dtype in state.state_parts],
    }


def from_published(cfg: dict, block_size: int) -> Phi4FlashConfig:
    """The program's configuration from the keys of the public
    ``config.json``, the Mamba sizes the configuration file states beside
    them (``mamba_*``: the published code's defaults) and its ``serving``
    group.  What the module does not implement is an error, not a default."""
    for key, want in (
        ("mb_per_layer", 2),
        ("hidden_act", "silu"),
        ("mlp_bias", False),
        ("lm_head_bias", False),
        ("tie_word_embeddings", True),
    ):
        if cfg[key] != want:
            raise ValueError(f"phi4flash: {key}={cfg[key]!r} is not implemented")
    if cfg["num_hidden_layers"] % 4 or cfg["num_hidden_layers"] < 8:
        raise ValueError("phi4flash: num_hidden_layers must be a multiple of 4 "
                         "(Mamba/window pairs, then memory-unit/cross pairs), "
                         "8 at least")
    if cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("hidden_size is not a whole number of heads")
    if cfg["num_key_value_heads"] % 2 or (
            cfg["num_attention_heads"] != 2 * cfg["num_key_value_heads"]):
        raise ValueError("phi4flash: differential attention pairs the heads: "
                         "an even number of KV heads, two query heads each")
    serving = cfg["serving"]
    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        window=cfg["sliding_window"],
        d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"],
        expand=cfg["mamba_expand"],
        dt_rank=cfg["mamba_dt_rank"],
        ln_eps=float(cfg["layer_norm_eps"]),
        block_size=block_size,
        dtype=cfg["torch_dtype"],
        window_slots=serving["window_slots"],
        window_store_blocks=serving["window_store_blocks"],
        state_slots=serving["state_slots"],
        state_stride_blocks=serving["state_stride_blocks"],
    )


def init_params(rng: jax.Array, cfg: Phi4FlashConfig) -> Params:
    """Seeded weights that keep the recurrence where a trained model's is (+
    the published initialisation): ``A_log = log(1..N)`` in every channel,
    ``D`` = 1, ``b_dt`` the inverse softplus of values log-uniform in [1e-3,
    1e-1], the ``lam`` vectors N(0, 0.1^2), LayerNorm weights 1 and biases 0
    plus a small perturbation so that a dropped bias shows, every matrix
    N(0, 1/fan-in).  ``front`` and ``back`` are stacked over their pairs: ``a``
    the pair's even layer, ``b`` its odd one.  Every matrix is two-dimensional
    (``[g | u]``, ``[u | z]`` and a projection's heads side by side in the
    columns): a stacked weight with a short axis before its last (``[D, 2,
    F]``) is laid out by tiles of that axis, and the layer scan then copied
    each layer's 105 MB out of the stack before multiplying (a third of a
    decode step; my chip run, PR 35)."""
    dtype = jnp.dtype(cfg.dtype)
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Di, N, R, F = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_ff
    f32 = jnp.float32

    def layer(key, kind):
        keys = iter(jax.random.split(key, 24))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, f32)
                    * fan_in**-0.5).astype(dtype)

        def small(shape, scale, mean=0.0):
            return (mean + scale * jax.random.normal(next(keys), shape, f32)
                    ).astype(dtype)

        def norm(n):
            return {"w": small((n,), 0.1, 1.0), "b": small((n,), 0.1)}

        lp = {"ln_in": norm(D), "ln_post": norm(D),
              "w_gu": w((D, 2 * F), D), "w_down": w((F, D), F)}
        if kind == "mamba":
            dt = jnp.exp(jax.random.uniform(
                next(keys), (Di,), f32, np.log(1e-3), np.log(1e-1)))
            lp.update(
                w_in=w((D, 2 * Di), D), conv_k=w((Di, cfg.d_conv), cfg.d_conv),
                conv_b=small((Di,), 0.1), w_x=w((Di, R + 2 * N), Di),
                w_dt=w((R, Di), R), b_dt=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=f32))[:, None], (N, Di)),
                d_skip=jnp.ones((Di,), f32), w_out=w((Di, D), Di))
        elif kind == "gmu":
            lp.update(w_1=w((D, Di), D), w_2=w((Di, D), Di))
        else:
            lp.update(wq=w((D, H * Dh), D), bq=small((H * Dh,), 0.1),
                      wo=w((H * Dh, D), H * Dh), bo=small((D,), 0.1),
                      lam=small((4, Dh), 0.1).astype(f32),
                      sub_norm=small((2 * Dh,), 0.1, 1.0))
            if kind == "attn":
                lp.update(wk=w((D, Hkv * Dh), D), bk=small((Hkv * Dh,), 0.1),
                          wv=w((D, Hkv * Dh), D), bv=small((Hkv * Dh,), 0.1))
        return lp

    def pairs(key, n, kinds):
        return {name: jax.vmap(lambda k: layer(k, kind))(
                    jax.random.split(jax.random.fold_in(key, i), n))
                for i, (name, kind) in enumerate(zip("ab", kinds))}

    k_front, k_mid, k_back, k_ends = jax.random.split(rng, 4)
    ends = iter(jax.random.split(k_ends, 3))
    return {
        "embed": (jax.random.normal(next(ends), (cfg.vocab_size, D), f32)
                  * D**-0.5).astype(dtype),
        "ln_f": {"w": (1.0 + 0.1 * jax.random.normal(next(ends), (D,), f32)
                       ).astype(dtype),
                 "b": (0.1 * jax.random.normal(next(ends), (D,), f32)
                       ).astype(dtype)},
        "front": pairs(k_front, cfg.n_front, ("mamba", "attn")),
        "mid": {"a": layer(jax.random.fold_in(k_mid, 0), "mamba"),
                "b": layer(jax.random.fold_in(k_mid, 1), "attn")},
        "back": pairs(k_back, cfg.n_back, ("gmu", "cross")),
    }


# ------------------------------------------------------------ the model step


def _layer_norm(x, p, eps, dtype=None):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    return (y * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
            ).astype(dtype or x.dtype)


def _logits(x, params, cfg):
    """Final LayerNorm and the head, which is the embedding; float32 logits."""
    x = _layer_norm(x, params["ln_f"], cfg.ln_eps, params["embed"].dtype)
    return jnp.einsum("...d,vd->...v", x, params["embed"],
                      preferred_element_type=jnp.float32)


def _mix_in(x, lp, cfg):
    return _layer_norm(x, lp["ln_in"], cfg.ln_eps, lp["w_down"].dtype)


def _ff(a, lp, cfg):
    """a -> a + FF(LN_post(a)); a and the result float32."""
    f32 = jnp.float32
    h = _layer_norm(a, lp["ln_post"], cfg.ln_eps, lp["w_down"].dtype)
    gu = jnp.einsum("...d,df->...f", h, lp["w_gu"], preferred_element_type=f32)
    F = gu.shape[-1] // 2
    hidden = (jax.nn.silu(gu[..., :F]) * gu[..., F:]).astype(h.dtype)
    return a + jnp.einsum("...f,fd->...d", hidden, lp["w_down"],
                          preferred_element_type=f32)


# -- Mamba


def _mamba_in(h, lp):
    """h: [B, T, D] in the serving type -> (u rounded once to the serving
    type, the gate z in float32), each [B, T, Di]."""
    uz = jnp.einsum("btd,de->bte", h, lp["w_in"],
                    preferred_element_type=jnp.float32)
    Di = uz.shape[-1] // 2
    return uz[..., :Di].astype(h.dtype), uz[..., Di:]


def _mamba_conv(taps, lp):
    """taps: the convolution's inputs, oldest first, each [B, T, Di] ->
    c = silu(sum_j k[:, j] u_j + b_c), float32."""
    k = lp["conv_k"].astype(jnp.float32)
    conv = sum(k[:, j] * u.astype(jnp.float32) for j, u in enumerate(taps))
    return jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32))


def _mamba_ssm_in(c, lp, cfg):
    """c: [B, T, Di] float32 -> (Delta [B, T, Di], B_t, C_t [B, T, N]),
    float32."""
    f32, act = jnp.float32, lp["w_x"].dtype
    R, N = cfg.dt_rank, cfg.d_state
    dbc = jnp.einsum("btd,dr->btr", c.astype(act), lp["w_x"],
                     preferred_element_type=f32)
    delta = jnp.einsum("btr,rd->btd", dbc[..., :R].astype(act), lp["w_dt"],
                       preferred_element_type=f32)
    return (jax.nn.softplus(delta + lp["b_dt"].astype(f32)),
            dbc[..., R:R + N], dbc[..., R + N:])


def _ssm_step(s, delta, c, b, cm, neg_a, d_skip):
    """One position of the recurrence.  s: [B, N, Di] float32; delta, c:
    [B, Di]; b, cm: [B, N].  Returns (s_t, m_t [B, Di])."""
    s = jnp.exp(delta[:, None, :] * neg_a) * s + (
        (delta * c)[:, None, :] * b[:, :, None])
    return s, jnp.sum(s * cm[:, :, None], axis=1) + d_skip * c


def _mamba_out(m, z, lp):
    return jnp.einsum("btd,de->bte",
                      (m * jax.nn.silu(z)).astype(lp["w_out"].dtype),
                      lp["w_out"], preferred_element_type=jnp.float32)


def _mamba_prefill(h, lp, conv0, s0, cfg):
    """A Mamba layer over a prefill's positions (whole blocks).  conv0:
    [B, d_conv - 1, Di], the inputs of the positions before the first, and
    s0: [B, N, Di], the state there (zeros at a prompt's start, a snapshot for
    a continue).  Returns (y, m [B, T, Di] float32, the padded inputs
    [B, d_conv - 1 + T, Di], the state after each block [B, T/block, N, Di])."""
    u, z = _mamba_in(h, lp)
    B, T, Di = u.shape
    taps, bs = cfg.d_conv, cfg.block_size
    up = jnp.concatenate((conv0.astype(u.dtype), u), axis=1)
    c = _mamba_conv([up[:, j:j + T] for j in range(taps)], lp)
    delta, bm, cm = _mamba_ssm_in(c, lp, cfg)
    neg_a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    d_skip = lp["d_skip"].astype(jnp.float32)

    def blocks(a):  # [B, T, n] -> [T/bs, bs, B, n]
        return a.reshape(B, T // bs, bs, -1).transpose(1, 2, 0, 3)

    def block(s, xs):
        ms = []
        for t in range(bs):  # the block's positions, unrolled
            s, m = _ssm_step(s, *(a[t] for a in xs), neg_a, d_skip)
            ms.append(m)
        return s, (jnp.stack(ms), s)

    _, (m, ends) = lax.scan(block, s0.astype(jnp.float32),
                            tuple(blocks(a) for a in (delta, c, bm, cm)))
    m = m.transpose(2, 0, 1, 3).reshape(B, T, Di)
    return _mamba_out(m, z, lp), m, up, ends.transpose(1, 0, 2, 3)


def _keep_state(conv_pool, ssm_pool, up, ends, kept, write, cfg):
    """Write the state after the blocks `kept` (static, counted from the
    call's first) into the slots `write` [B, len(kept)]."""
    bs, taps = cfg.block_size, cfg.d_conv
    at = (np.asarray(kept)[:, None] + 1) * bs + np.arange(taps - 1)[None, :]
    snap = up[:, at]  # [B, n, taps - 1, Di]: the inputs up to each block's end
    conv_pool = conv_pool.at[write.reshape(-1)].set(
        snap.reshape(-1, (taps - 1) * cfg.d_inner).astype(conv_pool.dtype))
    s = ends[:, np.asarray(kept)]
    ssm_pool = ssm_pool.at[write.reshape(-1)].set(
        s.reshape((-1,) + s.shape[2:]).astype(ssm_pool.dtype))
    return conv_pool, ssm_pool


def _mamba_decode(h, lp, conv_pool, ssm_pool, read, write, cfg):
    """One position of a Mamba layer for each sequence: the state of slot
    `read` advanced by one input into slot `write`.  h: [B, 1, D].  Returns
    (y, m [B, 1, Di], pools)."""
    u, z = _mamba_in(h, lp)
    old = jnp.take(conv_pool, read, axis=0).reshape(
        u.shape[0], cfg.d_conv - 1, -1)  # [B, taps - 1, Di]
    taps = [old[:, j:j + 1] for j in range(cfg.d_conv - 1)] + [u]
    c = _mamba_conv(taps, lp)
    delta, bm, cm = _mamba_ssm_in(c, lp, cfg)
    s, m = _ssm_step(jnp.take(ssm_pool, read, axis=0), delta[:, 0], c[:, 0],
                     bm[:, 0], cm[:, 0],
                     -jnp.exp(lp["a_log"].astype(jnp.float32)),
                     lp["d_skip"].astype(jnp.float32))
    conv_pool = conv_pool.at[write].set(jnp.concatenate(
        (old[:, 1:], u.astype(old.dtype)), axis=1).reshape(u.shape[0], -1))
    ssm_pool = ssm_pool.at[write].set(s)
    m = m[:, None]
    return _mamba_out(m, z, lp), m, conv_pool, ssm_pool


def _gmu(h, m, lp):
    """y = (m * silu(h . W_1)) . W_2; m float32, layer L/2's."""
    gate = jnp.einsum("btd,de->bte", h, lp["w_1"],
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bte,ed->btd", (m * jax.nn.silu(gate)).astype(h.dtype),
                      lp["w_2"], preferred_element_type=jnp.float32)


# -- differential attention, pair-wise


def _queries(h, lp, cfg):
    """h: [B, T, D] -> the kernels' queries [B, T, H, 2 d] float32: ``[q | 0]``
    on even heads, ``[0 | q]`` on odd ones, times sqrt 2."""
    q = jnp.einsum("btd,de->bte", h, lp["wq"],
                   preferred_element_type=jnp.float32) + lp["bq"].astype(
                       jnp.float32)
    q = q.reshape(q.shape[:2] + (cfg.n_heads, cfg.head_dim)) * 2.0**0.5
    even = (jnp.arange(q.shape[2]) % 2 == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate((jnp.where(even, q, zero), jnp.where(even, zero, q)),
                           axis=-1)


def _keys_values(h, lp, cfg):
    """h: [B, T, D] -> K and V pair-wise, [B, T, Hkv / 2, 2 d] in the cache's
    type: two neighbouring heads side by side (the projection's columns as
    they lie)."""
    f32 = jnp.float32
    k = jnp.einsum("btd,de->bte", h, lp["wk"], preferred_element_type=f32)
    v = jnp.einsum("btd,de->bte", h, lp["wv"], preferred_element_type=f32)
    k, v = k + lp["bk"].astype(f32), v + lp["bv"].astype(f32)
    pairs = k.shape[:2] + (cfg.n_kv_heads // 2, 2 * cfg.head_dim)
    return k.astype(h.dtype).reshape(pairs), v.astype(h.dtype).reshape(pairs)


def _attn_out(o, lp, lam0):
    """o: [B, T, H, 2 d], the kernels' output (even heads ``o1``, odd ``o2``)
    -> RMSNorm(o1 - lam o2) (1 - lam0), as H heads of d, . W_o + b_o."""
    f32 = jnp.float32
    o = o.astype(f32)
    lam = lp["lam"].astype(f32)
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
           + lam0)
    d = o[:, :, 0::2] - lam * o[:, :, 1::2]  # [B, T, H/2, 2 d]
    d = d * lax.rsqrt(jnp.mean(d * d, axis=-1, keepdims=True) + SUB_NORM_EPS)
    d = d * lp["sub_norm"].astype(f32) * (1.0 - lam0)
    d = d.reshape(d.shape[:2] + (-1,)).astype(lp["wo"].dtype)  # H heads of d
    return jnp.einsum("bte,ed->btd", d, lp["wo"],
                      preferred_element_type=f32) + lp["bo"].astype(f32)


def _decode_attention(spec, q, pool, table, context_len, start, interpret,
                      plan):
    """The paged kernel where it serves (compiled for the TPU, or
    interpreted; `plan`: its `shared_prefix_plan` of the full group's table,
    None on a window layer, whose `start` hides what lies before the
    window); elsewhere the XLA gather."""
    kernel = paged_decode_pallas.serves(interpret)
    pool, layout = decode_view(spec, pool, kernel)
    if kernel:
        return paged_decode_attention_pallas(
            q, pool, table, context_len, start=start,
            blocks_per_step=DECODE_BLOCKS_PER_STEP, interpret=interpret,
            plan=plan, **layout)
    return paged_attention(q, pool, table, context_len, start=start, **layout)


def _stacked(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _prefill(params, tokens, pools, tables, prefix_len, cfg, interpret):
    B, S = tokens.shape
    bs = cfg.block_size
    if prefix_len % bs or S % bs:
        raise ValueError("a prefill's prefix and tokens must be whole blocks")
    npre, nsuf = prefix_len // bs, S // bs
    specs = cache_groups(cfg)
    kept = specs["state"].snapshot_blocks(npre, nsuf)
    kept = [i - npre for i in kept]
    W, Sl = cfg.window_slots, cfg.state_slots
    # window slots: of the prefix's last blocks a window layer still sees,
    # then of the blocks to write (a miss prefill: its trailing ones only)
    nwin = tables["window"].shape[1] - nsuf if npre else 0
    (full,), (win,), (conv, ssm) = pools["full"], pools["window"], pools["state"]

    def mamba(x, lp, conv, ssm, base):
        h = _mix_in(x, lp, cfg)
        if npre:
            conv0 = jnp.take(conv, base + tables["state_read"],
                             axis=0).reshape(B, cfg.d_conv - 1, -1)
            s0 = jnp.take(ssm, base + tables["state_read"], axis=0)
        else:
            conv0 = jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), conv.dtype)
            s0 = jnp.zeros((B, cfg.d_state, cfg.d_inner), ssm.dtype)
        y, m, up, ends = _mamba_prefill(h, lp, conv0, s0, cfg)
        conv, ssm = _keep_state(conv, ssm, up, ends, kept,
                                base + tables["state_write"], cfg)
        return _ff(x + y, lp, cfg), m, conv, ssm

    def attention(x, lp, lam0, spec, pool, pre_ids, write_ids, window):
        h = _mix_in(x, lp, cfg)
        q = _queries(h, lp, cfg)
        k, v = _keys_values(h, lp, cfg)
        keys, values = k, v
        if pre_ids.shape[1]:
            pre_k, pre_v = gather_prefix(spec, pool, pre_ids, k.dtype)
            keys = jnp.concatenate((pre_k, k), axis=1)
            values = jnp.concatenate((pre_v, v), axis=1)
        attn = prefill_attention(q, keys, values, cfg, keys.shape[1] - S,
                                  window, interpret)
        n = write_ids.shape[1] * bs
        pool = write_blocks(spec, pool, write_ids, k[:, S - n:], v[:, S - n:])
        return _ff(x + _attn_out(attn, lp, lam0), lp, cfg), pool, keys, values

    def front(carry, xs):
        x, win, conv, ssm = carry
        lp, i, lam0 = xs
        x, _, conv, ssm = mamba(x, lp["a"], conv, ssm, i * Sl)
        x, win, _, _ = attention(
            x, lp["b"], lam0, specs["window"], win,
            i * W + tables["window"][:, :nwin],
            i * W + tables["window"][:, nwin:], cfg.window)
        return (x, win, conv, ssm), None

    x = embed(params, tokens)
    n = cfg.n_front
    (x, win, conv, ssm), _ = lax.scan(
        front, (x, win, conv, ssm),
        (params["front"], jnp.arange(n, dtype=jnp.int32),
         jnp.asarray(cfg.lam0(2 * np.arange(n) + 1))))
    x, m, conv, ssm = mamba(x, params["mid"]["a"], conv, ssm, n * Sl)
    x, full, keys, values = attention(
        x, params["mid"]["b"], cfg.lam0(2 * n + 1), specs["full"], full,
        tables["full"][:, :npre], tables["full"][:, npre:npre + nsuf], None)
    # the lower decoder: the last position alone (the module's head)
    x, m = x[:, -1:], m[:, -1:]

    def back(x, xs):
        lp, lam0 = xs
        x = _ff(x + _gmu(_mix_in(x, lp["a"], cfg), m, lp["a"]), lp["a"], cfg)
        q = _queries(_mix_in(x, lp["b"], cfg), lp["b"], cfg)
        attn = dense_attention(q, keys, values, keys.shape[1] - 1, None)
        return _ff(x + _attn_out(attn, lp["b"], lam0), lp["b"], cfg), None

    x, _ = lax.scan(
        back, x,
        (params["back"],
         jnp.asarray(cfg.lam0(2 * n + 3 + 2 * np.arange(cfg.n_back)))))
    pools = {"full": [full], "window": [win], "state": [conv, ssm]}
    return _logits(x, params, cfg), pools


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    cfg: Phi4FlashConfig,
    interpret: bool = False,
):
    """Prefill writing layer L/2 + 1's K/V into the full group, the window
    layers' K/V of the trailing blocks into the window group and the Mamba
    layers' state at the kept block boundaries into the state group.

    tokens: [B, T], T a multiple of the block size.  tables["full"]:
    [B, T/block] logical blocks in chain order; tables["window"]: [B, d]
    window slots of the trailing d blocks; tables["state_write"]: [B, n] state
    slots of the blocks ``snapshot_blocks(0, T/block)`` names.  Returns
    (logits of the last position [B, 1, V], pools).
    """
    return _prefill(params, tokens, pools, tables, 0, cfg, interpret)


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    pools: dict,
    tables: dict,
    prefix_len: int,
    cfg: Phi4FlashConfig,
    interpret: bool = False,
):
    """Prefill only the uncached suffix of a prompt (a prefix hit).

    tokens: [B, S] suffix.  tables["full"]: [B, (prefix_len + S)/block], the
    prefix's blocks then the blocks to write.  tables["window"]:
    [B, n + S/block]: the window slots of the prefix's last n blocks (n =
    min(prefix blocks, ceil((window - 1)/block))) then the slots to write.
    tables["state_read"]: [B], the slot of the snapshot after the prefix's
    last block, which every Mamba layer resumes from; tables["state_write"]:
    [B, n] as in `prefill_paged`, for ``snapshot_blocks(prefix blocks,
    S/block)``.  ``prefix_len`` is static.  Returns (logits of the last
    position [B, 1, V], pools).
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
    cfg: Phi4FlashConfig,
    interpret: bool = False,
):
    """One decode step over the three groups.

    tokens: [B]; context_len: [B], the current token included.
    tables["full"]: [B, max_blocks] logical blocks.  tables["window"]: [B, n]
    window slots of the blocks from the one that holds position
    ``tables["first"]`` up to the current one (`afmoe.decode_step`).
    tables["state"]: [B, 2], the state slot read and the one written
    (`lfm2moe.decode_step`).  Advances every Mamba layer's state by one
    position in place, writes the new token's K/V into the window group and
    (layer L/2 + 1's) into the full group, which that layer and every cross
    layer then read through one shared-prefix plan.  Returns (logits [B, V],
    pools).
    """
    bs = cfg.block_size
    W, Sl = cfg.window_slots, cfg.state_slots
    pos = context_len - 1
    at = pos % bs
    first = tables["first"]
    full_id = jnp.take_along_axis(
        tables["full"], (pos // bs)[:, None], axis=1)[:, 0]
    win_id = jnp.take_along_axis(
        tables["window"], ((pos - first) // bs)[:, None], axis=1)[:, 0]
    win_ctx = context_len - first
    win_start = jnp.maximum(context_len - cfg.window, 0) - first
    read, write = tables["state"][:, 0], tables["state"][:, 1]
    (full,), (win,), (conv, ssm) = pools["full"], pools["window"], pools["state"]
    specs = cache_groups(cfg)
    plan = None
    if paged_decode_pallas.serves(interpret):
        plan = paged_decode_pallas.shared_prefix_plan(
            tables["full"], context_len, block_size=bs,
            blocks_per_wave=paged_decode_pallas.walk_wave(full))

    def mamba(x, lp, conv, ssm, base):
        y, m, conv, ssm = _mamba_decode(_mix_in(x, lp, cfg), lp, conv, ssm,
                                        base + read, base + write, cfg)
        return _ff(x + y, lp, cfg), m, conv, ssm

    def front(carry, xs):
        x, win, conv, ssm = carry
        lp, i, lam0 = xs
        x, _, conv, ssm = mamba(x, lp["a"], conv, ssm, i * Sl)
        h = _mix_in(x, lp["b"], cfg)
        k, v = _keys_values(h, lp["b"], cfg)
        win = write_token(specs["window"], win, i * W + win_id, at, k[:, 0],
                          v[:, 0])
        attn = _decode_attention(specs["window"],
                                 _queries(h, lp["b"], cfg)[:, 0], win,
                                 i * W + tables["window"], win_ctx, win_start,
                                 interpret, None)
        x = _ff(x + _attn_out(attn[:, None], lp["b"], lam0), lp["b"], cfg)
        return (x, win, conv, ssm), None

    x = embed(params, tokens)[:, None]  # [B, 1, D]
    n = cfg.n_front
    (x, win, conv, ssm), _ = lax.scan(
        front, (x, win, conv, ssm),
        (params["front"], jnp.arange(n, dtype=jnp.int32),
         jnp.asarray(cfg.lam0(2 * np.arange(n) + 1))))
    x, m, conv, ssm = mamba(x, params["mid"]["a"], conv, ssm, n * Sl)
    lp = params["mid"]["b"]
    h = _mix_in(x, lp, cfg)
    k, v = _keys_values(h, lp, cfg)
    full = write_token(specs["full"], full, full_id, at, k[:, 0], v[:, 0])

    def over_full(x, h, lp, lam0):
        attn = _decode_attention(specs["full"], _queries(h, lp, cfg)[:, 0],
                                 full, tables["full"], context_len, None,
                                 interpret, plan)
        return _ff(x + _attn_out(attn[:, None], lp, lam0), lp, cfg)

    x = over_full(x, h, lp, cfg.lam0(2 * n + 1))

    def back(x, xs):
        lp, lam0 = xs
        x = _ff(x + _gmu(_mix_in(x, lp["a"], cfg), m, lp["a"]), lp["a"], cfg)
        return over_full(x, _mix_in(x, lp["b"], cfg), lp["b"], lam0), None

    x, _ = lax.scan(
        back, x,
        (params["back"],
         jnp.asarray(cfg.lam0(2 * n + 3 + 2 * np.arange(cfg.n_back)))))
    pools = {"full": [full], "window": [win], "state": [conv, ssm]}
    if plan is not None:
        pools["attention_read"] = (
            paged_decode_pallas.attention_read_counts(plan))
    return _logits(x[:, 0], params, cfg), pools


# ------------------------------------------------------ the plain reference


def reference_logits(params: Params, tokens, cfg: Phi4FlashConfig):
    """Logits [T, V] of one sequence by the equations at the top: float32,
    products at precision highest, no cache, no kernels, no batching, the scan
    a position at a time, the convolution as four shifted products, the four
    ``Att`` products of an attention layer each a dense masked softmax, every
    layer over every position."""
    f32 = jnp.float32
    p = jax.tree.map(lambda a: a.astype(f32), params)
    T = len(tokens)
    H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    R, N = cfg.dt_rank, cfg.d_state
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    def ln(x, q):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + cfg.ln_eps) * q["w"] + q["b"]

    def ff(a, lp):
        gu = mm("td,df->tf", ln(a, lp["ln_post"]), lp["w_gu"])
        return a + mm("tf,fd->td", jax.nn.silu(gu[:, :cfg.d_ff]) * gu[:, cfg.d_ff:],
                      lp["w_down"])

    def mamba(h, lp):
        uz = mm("td,de->te", h, lp["w_in"])
        u, z = uz[:, :cfg.d_inner], uz[:, cfg.d_inner:]
        taps = cfg.d_conv
        up = jnp.concatenate((jnp.zeros((taps - 1, u.shape[1]), f32), u))
        c = jax.nn.silu(sum(lp["conv_k"][:, t] * up[t:t + T]
                            for t in range(taps)) + lp["conv_b"])
        dbc = mm("td,dr->tr", c, lp["w_x"])
        delta = jax.nn.softplus(mm("tr,rd->td", dbc[:, :R], lp["w_dt"])
                                + lp["b_dt"])
        a = -jnp.exp(lp["a_log"])  # [N, Di]

        def step(s, xs):
            d_t, c_t, b_t, c_out = xs
            s = jnp.exp(d_t[None, :] * a) * s + (d_t * c_t)[None, :] * b_t[:, None]
            return s, jnp.sum(s * c_out[:, None], axis=0)

        _, sc = lax.scan(step, jnp.zeros_like(a),
                         (delta, c, dbc[:, R:R + N], dbc[:, R + N:]))
        m = sc + lp["d_skip"] * c
        return mm("td,de->te", m * jax.nn.silu(z), lp["w_out"]), m

    def att(q, k, v, window):
        """q: [T, h, d]; k, v: [T, h/2, d] -> [T, h, d], two heads a KV head."""
        k, v = (jnp.repeat(a, 2, axis=1) for a in (k, v))
        s = mm("qhk,thk->hqt", q, k) * Dh**-0.5
        seen = (j <= i) & ((j > i - window) if window else True)
        return mm("hqt,thk->qhk",
                  jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1), v)

    def attention(h, lp, l, k, v, window):
        q = (mm("td,de->te", h, lp["wq"]) + lp["bq"]).reshape(T, H, Dh)
        q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]
        v1, v2 = v[:, 0::2], v[:, 1::2]
        o1 = jnp.concatenate((att(q1, k1, v1, window), att(q1, k1, v2, window)),
                             -1)
        o2 = jnp.concatenate((att(q2, k2, v1, window), att(q2, k2, v2, window)),
                             -1)
        lam0 = float(cfg.lam0(l))
        lam = (jnp.exp(jnp.sum(lp["lam"][0] * lp["lam"][1]))
               - jnp.exp(jnp.sum(lp["lam"][2] * lp["lam"][3])) + lam0)
        d = o1 - lam * o2
        d = d * lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + SUB_NORM_EPS)
        d = (d * lp["sub_norm"] * (1.0 - lam0)).reshape(T, H * Dh)
        return mm("te,ed->td", d, lp["wo"]) + lp["bo"]

    def kv(h, lp):
        return ((mm("td,de->te", h, lp["wk"]) + lp["bk"]).reshape(T, Hkv, Dh),
                (mm("td,de->te", h, lp["wv"]) + lp["bv"]).reshape(T, Hkv, Dh))

    x = jnp.take(p["embed"], jnp.asarray(tokens), axis=0)
    half = cfg.n_layers // 2
    for l in range(cfg.n_layers):
        part = ("front" if l < half else "mid" if l < half + 2 else "back")
        lp = p[part]["ab"[l % 2]]
        if part != "mid":
            lp = _stacked(lp, (l if part == "front" else l - half - 2) // 2)
        h = ln(x, lp["ln_in"])
        if l % 2 == 0 and l <= half:
            y, m = mamba(h, lp)
        elif l % 2 == 0:
            y = mm("te,ed->td", m * jax.nn.silu(mm("td,de->te", h, lp["w_1"])),
                   lp["w_2"])
        elif l <= half + 1:
            k, v = kv(h, lp)  # layer L/2 + 1's stay for the layers below
            y = attention(h, lp, l, k, v, cfg.window if l < half else None)
        else:
            y = attention(h, lp, l, k, v, None)
        x = ff(x + y, lp)
    return mm("td,vd->tv", ln(x, p["ln_f"]), p["embed"])
