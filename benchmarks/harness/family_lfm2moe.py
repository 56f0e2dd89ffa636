"""The benchmark's side of the family `lfm2moe` (short-convolution and full
attention layers mixed, sparse experts without a shared one), found by the
configuration's `family` (`harness/family.py`): the plain reference, the seeded
weights, the control, and the least-work counts.  It imports nothing of the
program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-lfm2moe.md` writes down, in jax.numpy, float32,
matrix products at precision "highest", no kernels, no cache, no batching; the
convolution is three shifted products, attention runs over blocks of query
rows, and every expert is computed for every token and masked by the routing.
Weights stay in the type they are served in and are upcast where they are used
(an expert at a time), so that the reference fits beside them at 9 k tokens.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/lfm2moe.py` reads them: normal,
fan-in scaled, bfloat16-valued; the two norms a layer, the q/k norms and the
final norm are 1 + 0.1 N(0,1), the convolution's taps N(0,1)/sqrt(3) and the
selection bias 0.05 N(0,1), so that a step which leaves one of them out fails
the comparison.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's and the convolution's projections too) rounded through
float8_e4m3, one scale per tensor (per expert), the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per attention block: scores are [H, 256, T] f32
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
CONV = "conv"
ROUTE_NORM_EPS = 1e-6


def sizes(cfg: dict):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (D, H, cfg["num_key_value_heads"], D // H, cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts"], cfg["vocab_size"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def _layers(cfg: dict, kind: str) -> int:
    return sum((t == CONV) == (kind == "conv") for t in cfg["layer_types"])


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: a conv mixer (in, taps, out), an
    attention mixer (with the q/k norms), the two norms, a dense
    feed-forward, one expert, the router (with its selection bias)."""
    D, H, Hkv, Dh, F, Fe, E, _ = sizes(cfg)
    return {"conv": 3 * D * D + cfg["conv_L_cache"] * D + D * D,
            "attention": D * Dh * (2 * H + 2 * Hkv) + 2 * Dh, "norms": 2 * D,
            "dense": 3 * D * F, "expert": 3 * D * Fe, "router": D * E + E}


def param_count(cfg: dict, experts: float | None = None) -> float:
    """All parameters, the tied table once; with `experts`, that many of
    each expert layer's experts in place of all."""
    c, E = layer_counts(cfg), cfg["num_experts"]
    dense, L = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    return (cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + L * c["norms"] + _layers(cfg, "conv") * c["conv"]
            + _layers(cfg, "full") * c["attention"] + dense * c["dense"]
            + (L - dense) * ((E if experts is None else experts) * c["expert"]
                             + c["router"]))


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict) -> int:
    """K and V of one position over the attention layers."""
    _, _, Hkv, Dh, *_ = sizes(cfg)
    return _layers(cfg, "full") * 2 * Hkv * Dh * _itemsize(cfg)


def kv_block_bytes(cfg: dict, block: int) -> int:
    return block * kv_token_bytes(cfg)


def state_slot_bytes(cfg: dict) -> int:
    """The conv layers' state of one sequence: conv_L_cache - 1 inputs of
    hidden_size a layer, whatever the context."""
    return (_layers(cfg, "conv") * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"] * _itemsize(cfg))


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, the attention layers only: QK^T and PV over the causal pairs,
    4*H*Dh FLOPs a pair."""
    _, H, _, Dh, *_ = sizes(cfg)
    pairs = (T * (T + 1) - first * (first + 1)) // 2
    return _layers(cfg, "full") * 4 * H * Dh * pairs


# ------------------------------------------------------- least work, by step


def _decode_kv_bytes(cfg, counters) -> float:
    """K/V one decode step has to read: every distinct live block once (a
    shared system prompt's blocks once for all its sequences)."""
    from .engine import BLOCK

    return (counters["decode_live_blocks"] / counters["decode_steps"]
            * kv_block_bytes(cfg, BLOCK))


def lfm2moe_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: every weight outside the experts
    once, the tied table once (the head reads it whole); of each expert
    layer's E experts the E (1 - (1 - k/E)^B) that B sequences touch, an
    expectation under even routing and not a count; every distinct live K/V
    block once; each live sequence's conv state read and written; the new
    K/V written."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    seqs = counters["decode_live_seqs"] / counters["decode_steps"]
    touched = E * (1 - (1 - k / E) ** seqs)
    moved = (param_count(cfg, touched) * _itemsize(cfg)
             + _decode_kv_bytes(cfg, counters)
             + seqs * (2 * state_slot_bytes(cfg) + kv_token_bytes(cfg)))
    return moved / peak["hbm_bytes_s"]


def lfm2moe_paged_decode_attention_min_s(cfg, shapes, counters, peak) -> float:
    """The paged decode kernel over the attention layers of one step: the
    K/V of `_decode_kv_bytes` once.  Bandwidth-bound.  (A kernel that walks
    each sequence's table reads a shared prompt once a sequence.)"""
    return _decode_kv_bytes(cfg, counters) / peak["hbm_bytes_s"]


def lfm2moe_flash_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The flash kernel over the attention layers of one hit prefill: the
    suffix's queries over the cached prefix and itself.  Compute-bound."""
    prefix, suffix = shapes["hit"]
    return (prefill_attention_flops(cfg, prefix + suffix, prefix)
            / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/lfm2moe.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once)."""
    D, H, Hkv, Dh, F, Fe, E, V = sizes(cfg)
    taps = cfg["conv_L_cache"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draws(key):
        keys = iter(jax.random.split(key, 16))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, F32)
                    * fan_in ** -0.5).astype(dtype)

        def norm(n):
            return (1.0 + 0.1 * jax.random.normal(next(keys), (n,), F32)
                    ).astype(dtype)

        def swiglu(width, lead=()):
            return {"w_gate": w(lead + (D, width), D),
                    "w_up": w(lead + (D, width), D),
                    "w_down": w(lead + (width, D), width)}

        return keys, w, norm, swiglu

    @partial(jax.jit, static_argnames=("conv", "experts"))
    def layer(key, conv, experts):
        keys, w, norm, swiglu = draws(key)
        lp = {"ln_op": norm(D), "ln_ff": norm(D)}
        if conv:
            lp.update(w_in=w((D, 3, D), D), conv_k=w((D, taps), taps),
                      w_out=w((D, D), D))
        else:
            lp.update(wq=w((D, H, Dh), D), wk=w((D, Hkv, Dh), D),
                      wv=w((D, Hkv, Dh), D), wo=w((H, Dh, D), H * Dh),
                      q_norm=norm(Dh), k_norm=norm(Dh))
        if not experts:
            return {**lp, "mlp": swiglu(F)}
        return {**lp, "router": w((D, E), D),
                "route_bias": 0.05 * jax.random.normal(next(keys), (E,), F32),
                "experts": swiglu(Fe, (E,))}

    @jax.jit
    def ends(key):
        _, w, norm, _ = draws(key)
        return {"embed": w((V, D), D), "ln_f": norm(D)}

    key = key_of(seed)
    L = cfg["num_hidden_layers"]
    return {**ends(jax.random.fold_in(key, L)),
            "layers": [layer(jax.random.fold_in(key, l),
                             conv=cfg["layer_types"][l] == CONV,
                             experts=l >= cfg["num_dense_layers"])
                       for l in range(L)]}


# ------------------------------------------------------- the plain reference


def _round(x, quant):
    if quant is None:
        return x
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _round(a, quant), _round(b.astype(F32), quant),
                      precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    T, _, Dh = x.shape
    freqs = theta ** (-jnp.arange(0, Dh // 2, dtype=F32) / (Dh // 2))
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), -1)


def _swiglu(h, w, quant):
    gate = jax.nn.silu(_mm("td,df->tf", h, w["w_gate"], quant))
    return _mm("tf,fd->td", gate * _mm("td,df->tf", h, w["w_up"], quant),
               w["w_down"], quant)


def _conv_mixer(h, lp, quant):
    T = h.shape[0]
    bcu = _mm("td,dce->tce", h, lp["w_in"], quant)
    z = bcu[:, 0] * bcu[:, 2]
    k = lp["conv_k"].astype(F32)
    taps = k.shape[1]
    zp = jnp.concatenate((jnp.zeros((taps - 1, z.shape[1]), F32), z))
    conv = sum(k[:, j] * zp[j:j + T] for j in range(taps))
    return _mm("td,de->te", bcu[:, 1] * conv, lp["w_out"], quant)


def _attention_mixer(h, lp, theta, eps, quant):
    T = h.shape[0]
    H, Hkv = lp["wq"].shape[1], lp["wk"].shape[1]
    q = _rope(_norm(_mm("td,dhk->thk", h, lp["wq"], quant), lp["q_norm"], eps),
              theta)
    k = _rope(_norm(_mm("td,dhk->thk", h, lp["wk"], quant), lp["k_norm"], eps),
              theta)
    v = _mm("td,dhk->thk", h, lp["wv"], quant)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhk,thk->hqt", qi, k, precision=HI) * q.shape[-1] ** -0.5
        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        seen = jnp.arange(T)[None, :] <= at
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=HI)

    attn = jax.lax.map(rows, jnp.arange(T // Q_BLOCK)).reshape(q.shape)
    return _mm("thk,hkd->td", attn, lp["wo"], quant)


@partial(jax.jit, static_argnames=("theta", "eps", "top_k", "route_norm",
                                   "route_scale", "quant"))
def _layer(x, lp, theta, eps, top_k, route_norm, route_scale, quant):
    T = x.shape[0]
    h = _norm(x, lp["ln_op"], eps)
    x = x + (_conv_mixer(h, lp, quant) if "w_in" in lp
             else _attention_mixer(h, lp, theta, eps, quant))
    h = _norm(x, lp["ln_ff"], eps)
    if "mlp" in lp:
        return x + _swiglu(h, lp["mlp"], quant)
    s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], quant))
    _, picked = jax.lax.top_k(s + lp["route_bias"], top_k)
    w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1.0)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * route_scale

    def add(e, y):
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False), lp["experts"])
        col = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)
        return y + col * _swiglu(h, one, quant)

    return x + jax.lax.fori_loop(0, s.shape[1], add, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, embed, eps, quant):
    return _mm("td,vd->tv", _norm(x, ln_f, eps), embed, quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    eps = float(cfg["norm_eps"])
    for lp in weights["layers"]:
        x = _layer(x, lp, float(cfg["rope_theta"]), eps,
                   cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
                   float(cfg["routed_scaling_factor"]), quant)
    rows = min(n, -(-n_last // 64) * 64)  # few distinct shapes to compile
    return _head(x[n - rows:n], weights["ln_f"], weights["embed"], eps,
                 quant)[rows - n_last:]
