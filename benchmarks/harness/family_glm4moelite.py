"""The benchmark's side of the family `glm4moelite` (GLM-4.7-Flash: latent
attention whose cache is one vector a position a layer, sparse experts with a
shared one), found by the configuration's `family` (`harness/family.py`): the
plain reference, the seeded weights, the control, and the least-work counts.
It imports nothing of the program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-glm4moelite.md` writes down, **in the published
per-head form**: every position's keys `kn_h = c . W_uk_h` and values
`v_h = c . W_uv_h` are made for every head, the rotary key is shared by the
heads, scores are `(qn.kn + rope(qr).kr) / sqrt(dn + dr)`.  No latent-space
identity (the program's `q~ = W_uk . qn` against the cached `c`), no cache,
no kernels, no batching; jax.numpy, float32, matrix products at precision
"highest"; every expert is computed for every token and masked by the
routing.  Weights stay in the type they are served in and are upcast where
they are used (an expert at a time), so that the reference fits beside them at
17 k tokens.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/glm4moelite.py` reads them:
normal, fan-in scaled, bfloat16-valued; the two norms a layer, the two
bottleneck norms and the final norm are 1 + 0.1 N(0,1) and the selection bias
0.05 N(0,1), so that a step which leaves one of them out fails the comparison.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's too) rounded through float8_e4m3, one scale per tensor
(per expert), the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .family_afmoe import _mm, _norm, _swiglu  # the plain pieces, as there
from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per attention block: scores are [H, 256, T] f32
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROUTE_NORM_EPS = 1e-20


def sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: attention (both bottlenecks with
    their norms, the up-projections, the output), the two norms, a dense
    feed-forward, one expert, the shared expert, the router (with its
    selection bias)."""
    D, H, Rq, Rkv, dn, dr, dv = sizes(cfg)
    E, Fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    return {"attention": (D * Rq + Rq + Rq * H * (dn + dr) + D * (Rkv + dr)
                          + Rkv + Rkv * H * (dn + dv) + H * dv * D),
            "norms": 2 * D, "dense": 3 * D * cfg["intermediate_size"],
            "expert": 3 * D * Fe,
            "shared": 3 * D * Fe * cfg["n_shared_experts"],
            "router": D * E + E}


def param_count(cfg: dict) -> int:
    c, E = layer_counts(cfg), cfg["n_routed_experts"]
    dense, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    every = c["attention"] + c["norms"]
    return (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + dense * (every + c["dense"])
            + (L - dense) * (every + E * c["expert"] + c["shared"] + c["router"]))


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict) -> int:
    """What the cache holds of one position over all layers: one vector of
    kv_lora_rank + qk_rope_head_dim numbers a layer, key and value at once."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * _itemsize(cfg))


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of the latent cache over all layers, in the served type."""
    return block * kv_token_bytes(cfg)


def _pairs(T: int, first: int = 0) -> int:
    """Query-key pairs of causal attention for queries first..T-1."""
    return (T * (T + 1) - first * (first + 1)) // 2


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, all layers, in the published per-head form, the least the
    algorithm needs: scores over dn + dr and values over dv, 2 FLOPs a
    multiply-add, for every head and pair the mask leaves."""
    _, H, _, _, dn, dr, dv = sizes(cfg)
    return cfg["num_hidden_layers"] * 2 * H * (dn + dr + dv) * _pairs(T, first)


def latent_attention_flops(cfg: dict, pairs: float) -> float:
    """Attention in the latent space over `pairs` query-key pairs, all layers
    and heads: scores over kv_lora_rank + qk_rope_head_dim lanes and values
    over kv_lora_rank, 2 FLOPs a multiply-add (1088 x 2 a head and pair at
    the published sizes).  What an implementation that never makes keys and
    values per head has to compute."""
    _, H, _, Rkv, _, dr, _ = sizes(cfg)
    return cfg["num_hidden_layers"] * 2 * H * (2 * Rkv + dr) * pairs


# ------------------------------------------------------- least work, by step


def _decode_latent_bytes(cfg, counters) -> float:
    """The cache one decode step has to read: every distinct live block once
    (the harness counts a prefix that live sequences share once)."""
    from .engine import BLOCK

    return (counters["decode_live_blocks"] / counters["decode_steps"]
            * kv_block_bytes(cfg, BLOCK))


def glm4moelite_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: every weight outside the experts
    and the head once (of the embedding only the rows looked up); of each
    expert layer's E experts the E (1 - (1 - k/E)^B) that B sequences touch,
    which is an expectation under even routing and not a count; the latent
    cache of the live contexts; the new slots written.  The same work
    whatever implements it."""
    c, E, k = layer_counts(cfg), cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    dense, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    seqs = counters["decode_live_seqs"] / counters["decode_steps"]
    touched = E * (1 - (1 - k / E) ** seqs)
    weights = (cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
               + L * (c["attention"] + c["norms"]) + dense * c["dense"]
               + (L - dense) * (c["shared"] + c["router"] + touched * c["expert"]))
    moved = (weights * _itemsize(cfg) + seqs * cfg["hidden_size"] * _itemsize(cfg)
             + _decode_latent_bytes(cfg, counters) + seqs * kv_token_bytes(cfg))
    return moved / peak["hbm_bytes_s"]


def glm4moelite_latent_decode_attention_min_s(cfg, shapes, counters, peak) -> float:
    """The paged decode kernel's latent form over all layers of one step: the
    larger of the live contexts' latent cache read once over the bandwidth
    (a shared prefix once) and the latent-space products of every live
    sequence over its own context over the peak (2 x 20 x 1088 a pair and
    layer at the published sizes)."""

    read = _decode_latent_bytes(cfg, counters)
    # pairs, from below: every live block is scored by one sequence at least,
    # and every live sequence scores its whole prefix, shared or not
    pairs = max(read / kv_token_bytes(cfg),
                counters["decode_live_seqs"] / counters["decode_steps"]
                * shapes["hit"][0])
    return max(read / peak["hbm_bytes_s"],
               latent_attention_flops(cfg, pairs) / peak["bf16_flops"])


def glm4moelite_latent_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The latent prefill kernel over all layers of one hit prefill: the
    suffix's queries over the cached prefix and itself, the larger of the
    prefix's and the suffix's latent cache read once over the bandwidth and
    the latent-space products over the peak (compute-bound by far)."""
    prefix, suffix = shapes["hit"]
    total = prefix + suffix
    return max(total * kv_token_bytes(cfg) / peak["hbm_bytes_s"],
               latent_attention_flops(cfg, _pairs(total, prefix))
               / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/glm4moelite.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once)."""
    D, H, Rq, Rkv, dn, dr, dv = sizes(cfg)
    E, Fe, V = (cfg["n_routed_experts"], cfg["moe_intermediate_size"],
                cfg["vocab_size"])
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
        lp = {"ln_in": norm(D), "ln_post": norm(D), "w_qa": w((D, Rq), D),
              "q_norm": norm(Rq), "w_qb": w((Rq, H, dn + dr), Rq),
              "w_kva": w((D, Rkv + dr), D), "kv_norm": norm(Rkv),
              "w_kvb": w((Rkv, H, dn + dv), Rkv),
              "wo": w((H, dv, D), H * dv)}
        if not experts:
            return {**lp, "mlp": swiglu(cfg["intermediate_size"])}
        return {**lp, "router": w((D, E), D),
                "route_bias": 0.05 * jax.random.normal(next(keys), (E,), F32),
                "shared": swiglu(cfg["n_shared_experts"] * Fe),
                "experts": swiglu(Fe, (E,))}

    @jax.jit
    def ends(key):
        _, w, norm, _ = draws(key)
        return {"embed": w((V, D), D), "head": w((V, D), D), "ln_f": norm(D)}

    key = key_of(seed)
    L = cfg["num_hidden_layers"]
    return {**ends(jax.random.fold_in(key, L)),
            "layers": [layer(jax.random.fold_in(key, l),
                             experts=l >= cfg["first_k_dense_replace"])
                       for l in range(L)]}


# ------------------------------------------------------- the plain reference


def _rope(x, theta):
    """x: [T, ..., dr]: the lanes (2i, 2i + 1) turn together by
    pos * theta^(-2i/dr)."""
    T, dr = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dr // 2, dtype=F32) / (dr // 2))
    ang = (jnp.arange(T, dtype=F32)[:, None] * freqs).reshape(
        (T,) + (1,) * (x.ndim - 2) + (dr // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack((a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)), -1).reshape(x.shape)


@partial(jax.jit, static_argnames=("rkv", "dn", "theta", "eps", "top_k",
                                   "route_scale", "quant"))
def _layer(x, lp, rkv, dn, theta, eps, top_k, route_scale, quant):
    T = x.shape[0]
    h = _norm(x, lp["ln_in"], eps)
    cq = _norm(_mm("td,dr->tr", h, lp["w_qa"], quant), lp["q_norm"], eps)
    q = _mm("tr,rhk->thk", cq, lp["w_qb"], quant)  # [T, H, dn + dr]
    ckr = _mm("td,dr->tr", h, lp["w_kva"], quant)
    c = _norm(ckr[:, :rkv], lp["kv_norm"], eps)
    kr = _rope(ckr[:, rkv:], theta)  # [T, dr]: one key for all heads
    kv = _mm("tr,rhk->thk", c, lp["w_kvb"], quant)  # [T, H, dn + dv]
    kn, v = kv[..., :dn], kv[..., dn:]
    qn, qr = q[..., :dn], _rope(q[..., dn:], theta)
    scale = q.shape[-1] ** -0.5

    def rows(i):
        a = jax.lax.dynamic_slice_in_dim(qn, i * Q_BLOCK, Q_BLOCK)
        b = jax.lax.dynamic_slice_in_dim(qr, i * Q_BLOCK, Q_BLOCK)
        s = (jnp.einsum("qhk,thk->hqt", a, kn, precision=HI)
             + jnp.einsum("qhk,tk->hqt", b, kr, precision=HI)) * scale
        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        seen = jnp.arange(T)[None, :] <= at
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=HI)

    attn = jax.lax.map(rows, jnp.arange(T // Q_BLOCK)).reshape(v.shape)
    x = x + _mm("thk,hkd->td", attn, lp["wo"], quant)
    h = _norm(x, lp["ln_post"], eps)
    if "mlp" in lp:
        return x + _swiglu(h, lp["mlp"], quant)
    s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], quant))
    _, picked = jax.lax.top_k(s + lp["route_bias"], top_k)
    w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1.0)
    w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS) * route_scale

    def add(e, y):
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False), lp["experts"])
        col = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)
        return y + col * _swiglu(h, one, quant)

    return x + jax.lax.fori_loop(0, s.shape[1], add,
                                 _swiglu(h, lp["shared"], quant))


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
    eps = float(cfg["rms_norm_eps"])
    for lp in weights["layers"]:
        x = _layer(x, lp, cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                   float(cfg["rope_theta"]), eps, cfg["num_experts_per_tok"],
                   float(cfg["routed_scaling_factor"]), quant)
    rows = min(n, -(-n_last // 64) * 64)  # few distinct shapes to compile
    return _head(x[n - rows:n], weights["ln_f"], weights["head"], eps,
                 quant)[rows - n_last:]
