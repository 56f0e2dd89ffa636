"""The benchmark's side of the family `afmoe` (sparse experts with a shared
expert, window and full attention layers mixed), found by the configuration's
`family` (`harness/family.py`): the plain reference, the seeded weights, the
control, and the least-work counts.  It imports nothing of the program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-afmoe.md` writes down, in jax.numpy, float32,
matrix products at precision "highest", no kernels, no cache, no batching;
every expert is computed for every token and masked by the routing.  Weights
stay in the type they are served in and are upcast where they are used (an
expert at a time), so that the reference fits beside them at 13 k tokens.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/afmoe.py` reads them: normal,
fan-in scaled, bfloat16-valued; the four norms a layer, the q/k norms and the
final norm are 1 + 0.1 N(0,1) and the selection bias 0.05 N(0,1), so that a
step which leaves one of them out fails the comparison.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's too) rounded through float8_e4m3, one scale per tensor
(per expert), the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per attention block: scores are [H, 256, T] f32
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SLIDING = "sliding_attention"


def sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts"], cfg["vocab_size"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: attention (with the output gate and
    the q/k norms), the four norms, a dense feed-forward, one expert, the
    shared expert, the router (with its selection bias)."""
    D, H, Hkv, Dh, F, Fe, E, _ = sizes(cfg)
    return {"attention": D * Dh * (3 * H + 2 * Hkv) + 2 * Dh, "norms": 4 * D,
            "dense": 3 * D * F, "expert": 3 * D * Fe,
            "shared": 3 * D * Fe * cfg["num_shared_experts"],
            "router": D * E + E}


def param_count(cfg: dict) -> int:
    c, E = layer_counts(cfg), cfg["num_experts"]
    dense, L = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    every = c["attention"] + c["norms"]
    return (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + dense * (every + c["dense"])
            + (L - dense) * (every + E * c["expert"] + c["shared"] + c["router"]))


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict, kind: str | None = None) -> int:
    """K and V of one position over the layers of one kind (all if None)."""
    _, _, Hkv, Dh, *_ = sizes(cfg)
    layers = sum(kind is None or (t == SLIDING) == (kind == "window")
                 for t in cfg["layer_types"])
    return layers * 2 * Hkv * Dh * _itemsize(cfg)


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of K and V over all layers, in the served type (what one
    uniform table would hold a block)."""
    return block * kv_token_bytes(cfg)


def _pairs(T: int, window: int | None, first: int = 0) -> int:
    """Query-key pairs of causal attention for queries first..T-1."""
    if window is None:
        return (T * (T + 1) - first * (first + 1)) // 2
    return sum(min(i + 1, window) for i in range(first, T))


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, all layers: QK^T and PV over the pairs the mask leaves, 4*H*Dh
    FLOPs a pair; a sliding layer's pairs lie in its band."""
    _, H, _, Dh, *_ = sizes(cfg)
    return sum(4 * H * Dh * _pairs(T, cfg["sliding_window"]
                                   if t == SLIDING else None, first)
               for t in cfg["layer_types"])


# ------------------------------------------------------- least work, by step


def _decode_kv_bytes(cfg, shapes, counters) -> float:
    """K/V one decode step has to read: every distinct live block of the full
    layers once, and for each live sequence the window's positions of the
    sliding layers (contexts here are longer than the window)."""
    from .engine import BLOCK

    steps = counters["decode_steps"]
    context = sum(shapes["hit"])
    return (counters["decode_live_blocks"] / steps * BLOCK
            * kv_token_bytes(cfg, "full")
            + counters["decode_live_seqs"] / steps
            * min(context, cfg["sliding_window"]) * kv_token_bytes(cfg, "window"))


def afmoe_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: every weight outside the experts and
    the head once (of the embedding only the rows looked up); of each expert
    layer's E experts the E (1 - (1 - k/E)^B) that B sequences touch, which
    is an expectation under even routing and not a count; the K/V of
    `_decode_kv_bytes`; the new K/V written."""
    c, E, k = layer_counts(cfg), cfg["num_experts"], cfg["num_experts_per_tok"]
    dense, L = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    seqs = counters["decode_live_seqs"] / counters["decode_steps"]
    touched = E * (1 - (1 - k / E) ** seqs)
    weights = (cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
               + L * (c["attention"] + c["norms"]) + dense * c["dense"]
               + (L - dense) * (c["shared"] + c["router"] + touched * c["expert"]))
    moved = (weights * _itemsize(cfg) + seqs * cfg["hidden_size"] * _itemsize(cfg)
             + _decode_kv_bytes(cfg, shapes, counters)
             + seqs * kv_token_bytes(cfg))
    return moved / peak["hbm_bytes_s"]


def paged_decode_attention_min_s(cfg, shapes, counters, peak) -> float:
    """The paged decode kernel over all layers of one step: the K/V of
    `_decode_kv_bytes` once.  Bandwidth-bound."""
    return _decode_kv_bytes(cfg, shapes, counters) / peak["hbm_bytes_s"]


def flash_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The flash kernel over all layers of one hit prefill: the suffix's
    queries over the cached prefix and itself, banded on the sliding layers.
    Compute-bound."""
    prefix, suffix = shapes["hit"]
    return (prefill_attention_flops(cfg, prefix + suffix, prefix)
            / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/afmoe.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once)."""
    D, H, Hkv, Dh, F, Fe, E, V = sizes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draws(key):
        keys = iter(jax.random.split(key, 32))

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

    @partial(jax.jit, static_argnames=("experts",))
    def layer(key, experts):
        keys, w, norm, swiglu = draws(key)
        lp = {"ln_in": norm(D), "ln_post_attn": norm(D), "ln_pre_mlp": norm(D),
              "ln_post_mlp": norm(D), "wq": w((D, H, Dh), D),
              "wk": w((D, Hkv, Dh), D), "wv": w((D, Hkv, Dh), D),
              "wg": w((D, H, Dh), D), "wo": w((H, Dh, D), H * Dh),
              "q_norm": norm(Dh), "k_norm": norm(Dh)}
        if not experts:
            return {**lp, "mlp": swiglu(F)}
        return {**lp, "router": w((D, E), D),
                "route_bias": 0.05 * jax.random.normal(next(keys), (E,), F32),
                "shared": swiglu(cfg["num_shared_experts"] * Fe),
                "experts": swiglu(Fe, (E,))}

    @jax.jit
    def ends(key):
        _, w, norm, _ = draws(key)
        return {"embed": w((V, D), D), "head": w((V, D), D), "ln_f": norm(D)}

    key = key_of(seed)
    L = cfg["num_hidden_layers"]
    return {**ends(jax.random.fold_in(key, L)),
            "layers": [layer(jax.random.fold_in(key, l),
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


@partial(jax.jit, static_argnames=("sliding", "window", "theta", "eps", "top_k",
                                   "route_norm", "route_scale", "quant"))
def _layer(x, lp, sliding, window, theta, eps, top_k, route_norm, route_scale,
           quant):
    T = x.shape[0]
    H, Hkv = lp["wq"].shape[1], lp["wk"].shape[1]
    h = _norm(x, lp["ln_in"], eps)
    q = _norm(_mm("td,dhk->thk", h, lp["wq"], quant), lp["q_norm"], eps)
    k = _norm(_mm("td,dhk->thk", h, lp["wk"], quant), lp["k_norm"], eps)
    v = _mm("td,dhk->thk", h, lp["wv"], quant)
    if sliding:
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhk,thk->hqt", qi, k, precision=HI) * q.shape[-1] ** -0.5
        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        seen = jnp.arange(T)[None, :] <= at
        if sliding:
            seen &= jnp.arange(T)[None, :] > at - window
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=HI)

    attn = jax.lax.map(rows, jnp.arange(T // Q_BLOCK)).reshape(q.shape)
    attn = attn * jax.nn.sigmoid(_mm("td,dhk->thk", h, lp["wg"], quant))
    x = x + _norm(_mm("thk,hkd->td", attn, lp["wo"], quant),
                  lp["ln_post_attn"], eps)
    h = _norm(x, lp["ln_pre_mlp"], eps)
    if "mlp" in lp:
        y = _swiglu(h, lp["mlp"], quant)
    else:
        s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], quant))
        _, picked = jax.lax.top_k(s + lp["route_bias"], top_k)
        w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1.0)
        if route_norm:
            w = w / w.sum(-1, keepdims=True)
        w = w * route_scale

        def add(e, y):
            one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, e, keepdims=False), lp["experts"])
            col = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)
            return y + col * _swiglu(h, one, quant)

        y = jax.lax.fori_loop(0, s.shape[1], add,
                              _swiglu(h, lp["shared"], quant))
    return x + _norm(y, lp["ln_post_mlp"], eps)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    return _mm("td,vd->tv", _norm(x, ln_f, eps), head, quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    eps = float(cfg["rms_norm_eps"])
    for lp, kind in zip(weights["layers"], cfg["layer_types"]):
        x = _layer(x, lp, kind == SLIDING, cfg["sliding_window"],
                   float(cfg["rope_theta"]), eps, cfg["num_experts_per_tok"],
                   cfg["route_norm"], float(cfg["route_scale"]), quant)
    rows = min(n, -(-n_last // 64) * 64)  # few distinct shapes to compile
    return _head(x[n - rows:n], weights["ln_f"], weights["head"], eps,
                 quant)[rows - n_last:]
