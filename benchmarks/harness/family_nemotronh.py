"""The benchmark's side of the family `nemotronh` (one mixer a layer: Mamba-2
with a state that is a matrix a head, squared-ReLU experts with a shared one,
or grouped attention without a position encoding), found by the
configuration's `family` (`harness/family.py`): the plain reference, the seeded
weights, the control, and the least-work counts.  It imports nothing of the
program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-nemotronh.md` writes down, in jax.numpy, float32,
matrix products at precision "highest", no kernels, no cache, no batching: the
Mamba-2 scan is a `lax.scan` over positions, one matrix state a head at a time;
the convolution is four shifted products; attention runs over blocks of query
rows; every held expert is computed for every token and masked by the routing.
Weights stay in the type they are served in and are upcast where they are used
(an expert at a time), so that the reference fits beside them at 12 k tokens.

**The chip's share.**  The configuration's `n_routed_experts` is the number of
experts this chip HOLDS (`held.experts_first` the first of them) of the
`published.n_routed_experts` the router scores.  The reference is given the
same share: it routes over all, picks `num_experts_per_tok` as published, and
adds the held experts' terms only; what the others would add is left out, and
that partial sum goes on to the next layer.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/nemotronh.py` reads them: every
matrix N(0, 1/fan-in), bfloat16-valued; `A` uniform in [1, 16] a head, `D` = 1,
`dt_bias` the inverse softplus of values log-uniform in [`time_step_min`,
`time_step_max`] (+ the published initialisation, so that the recurrence decays
as a trained model's does); norm weights 1 + 0.1 N(0,1), the convolution's taps
N(0,1)/2 with a bias 0.1 N(0,1), the selection bias 0.05 N(0,1), so that a step
which leaves one of them out fails the comparison.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's and the Mamba-2 projections too) rounded through
float8_e4m3, one scale per tensor (per expert), the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per attention block: scores are [H, 256, T] f32
HEAD_ROWS = Q_BLOCK  # rows of logits a product of the head makes at a time
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
ROUTE_NORM_EPS = 1e-20


def sizes(cfg: dict) -> dict:
    """The widths by the names the equations use."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    held = cfg["n_routed_experts"]
    return dict(
        D=cfg["hidden_size"], V=cfg["vocab_size"], H=H, P=P, G=G, N=N,
        Di=H * P, C=H * P + 2 * G * N, taps=cfg["conv_kernel"],
        Ha=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], Fe=cfg["moe_intermediate_size"],
        Fs=cfg["moe_shared_expert_intermediate_size"], held=held,
        first=cfg.get("held", {}).get("experts_first", 0),
        E=cfg.get("published", {}).get("n_routed_experts", held),
        k=cfg["num_experts_per_tok"], Q=cfg["chunk_size"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def layers(cfg: dict) -> dict:
    """How many layers of each kind the pattern names."""
    pattern = cfg["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in (MAMBA, EXPERTS, ATTENTION)}


def layer_counts(cfg: dict) -> dict:
    """Parameters by part: a Mamba-2 mixer (in, taps and bias, dt bias, A, D,
    the gated norm, out), an attention mixer, one routed expert, what an
    expert layer has beside its routed experts (the shared expert, the router
    with its selection bias), a layer's norm."""
    z = sizes(cfg)
    D = z["D"]
    return {
        "mamba": (D * (z["Di"] + z["C"] + z["H"]) + z["C"] * (z["taps"] + 1)
                  + 3 * z["H"] + z["Di"] + z["Di"] * D),
        "attention": D * z["Dh"] * (2 * z["Ha"] + 2 * z["Hkv"]),
        "expert": 2 * D * z["Fe"],
        "shared": 2 * D * z["Fs"] + D * z["E"] + z["E"],
        "norm": D,
    }


def param_count(cfg: dict, experts: float | None = None,
                embedding: bool = True) -> float:
    """All parameters held here (the embedding and the untied head each
    once); with `experts`, that many of each expert layer's held experts in
    place of all; without `embedding`, what a decode step reads whole."""
    c, n, z = layer_counts(cfg), layers(cfg), sizes(cfg)
    held = z["held"] if experts is None else experts
    return ((1 + embedding) * z["V"] * z["D"] + z["D"]
            + cfg["num_hidden_layers"] * c["norm"] + n[MAMBA] * c["mamba"]
            + n[ATTENTION] * c["attention"]
            + n[EXPERTS] * (held * c["expert"] + c["shared"]))


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in (the recurrence's
    few float32 vectors counted in it too: under a ten-thousandth)."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict) -> int:
    """K and V of one position over the attention layers."""
    z = sizes(cfg)
    return layers(cfg)[ATTENTION] * 2 * z["Hkv"] * z["Dh"] * _itemsize(cfg)


def kv_block_bytes(cfg: dict, block: int) -> int:
    return block * kv_token_bytes(cfg)


def state_slot_bytes(cfg: dict) -> int:
    """The Mamba-2 layers' state of one sequence: `conv_kernel` - 1 inputs of
    C lanes in the serving type and H matrices P x N of float32 a layer,
    whatever the context."""
    z = sizes(cfg)
    return layers(cfg)[MAMBA] * ((z["taps"] - 1) * z["C"] * _itemsize(cfg)
                                 + z["H"] * z["P"] * z["N"] * 4)


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, the attention layers only: QK^T and PV over the causal pairs,
    4*H*Dh FLOPs a pair."""
    z = sizes(cfg)
    pairs = (T * (T + 1) - first * (first + 1)) // 2
    return layers(cfg)[ATTENTION] * 4 * z["Ha"] * z["Dh"] * pairs


def ssd_chunk_flops(cfg: dict) -> int:
    """The chunk form's products over one chunk of one Mamba-2 layer: C B^T
    a group (2 Q Q N), and a head the product with d x (2 Q Q P), the carried
    state's output (2 Q N P) and the state's update (2 Q P N)."""
    z = sizes(cfg)
    Q, H, P, G, N = z["Q"], z["H"], z["P"], z["G"], z["N"]
    return 2 * Q * (G * Q * N + H * Q * P + 2 * H * N * P)


def ssd_scan_bytes(cfg: dict, T: int) -> int:
    """What the scan of one Mamba-2 layer over T positions has to move: x, B
    and C in and y out in the serving type, d in float32, and one state in
    and out."""
    z = sizes(cfg)
    return (T * ((2 * z["Di"] + 2 * z["G"] * z["N"]) * _itemsize(cfg)
                 + 4 * z["H"]) + 2 * z["H"] * z["P"] * z["N"] * 4)


# ------------------------------------------------------- least work, by step


def _decode_kv_bytes(cfg, counters) -> float:
    """K/V one decode step has to read: every distinct live block once (a
    shared tool prompt's blocks once for all its sequences)."""
    from .engine import BLOCK

    return (counters["decode_live_blocks"] / counters["decode_steps"]
            * kv_block_bytes(cfg, BLOCK))


def nemotronh_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound, the same work whatever implements
    it: every weight outside the routed experts once and the head once (the
    embedding gives a row a sequence); of each expert layer's held experts
    the held (1 - (1 - k/E)^B) that B sequences touch, an expectation under
    even routing and not a count; every distinct live K/V block once; each
    live sequence's state read and written; the new K/V written."""
    z = sizes(cfg)
    seqs = counters["decode_live_seqs"] / counters["decode_steps"]
    touched = z["held"] * (1 - (1 - z["k"] / z["E"]) ** seqs)
    moved = (param_count(cfg, touched, embedding=False) * _itemsize(cfg)
             + _decode_kv_bytes(cfg, counters)
             + seqs * (2 * state_slot_bytes(cfg) + kv_token_bytes(cfg)))
    return moved / peak["hbm_bytes_s"]


def nemotronh_paged_decode_attention_min_s(cfg, shapes, counters, peak) -> float:
    """The paged decode kernel over the attention layers of one step: the
    K/V of `_decode_kv_bytes` once.  Bandwidth-bound."""
    return _decode_kv_bytes(cfg, counters) / peak["hbm_bytes_s"]


def nemotronh_flash_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The flash kernel over the attention layers of one hit prefill: the
    suffix's queries over the cached prefix and itself.  Compute-bound."""
    prefix, suffix = shapes["hit"]
    return (prefill_attention_flops(cfg, prefix + suffix, prefix)
            / peak["bf16_flops"])


def nemotronh_ssd_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The chunk scan over the Mamba-2 layers of one hit prefill: the larger
    of the chunk form's products over the suffix's chunks at the chip's
    bfloat16 peak and the scan's operands with one state in and out at its
    bandwidth (at the published sizes the second: 15 MB a layer against 1.7
    GFLOP)."""
    _, suffix = shapes["hit"]
    n = layers(cfg)[MAMBA]
    flops = n * -(-suffix // sizes(cfg)["Q"]) * ssd_chunk_flops(cfg)
    return max(flops / peak["bf16_flops"],
               n * ssd_scan_bytes(cfg, suffix) / peak["hbm_bytes_s"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/nemotronh.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once).  An
    expert layer's stacks hold the held experts only."""
    z = sizes(cfg)
    D, V = z["D"], z["V"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    dt_lo, dt_hi = (math.log(cfg.get("time_step_min", 1e-3)),
                    math.log(cfg.get("time_step_max", 1e-1)))

    def draws(key):
        keys = iter(jax.random.split(key, 16))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, F32)
                    * fan_in ** -0.5).astype(dtype)

        def norm(n):
            return (1.0 + 0.1 * jax.random.normal(next(keys), (n,), F32)
                    ).astype(dtype)

        def relu2(width, lead=()):
            return {"w_up": w(lead + (D, width), D),
                    "w_down": w(lead + (width, D), width)}

        return keys, w, norm, relu2

    @partial(jax.jit, static_argnames=("kind",))
    def layer(key, kind):
        keys, w, norm, relu2 = draws(key)
        lp = {"ln": norm(D)}
        if kind == MAMBA:
            dt = jnp.exp(jax.random.uniform(next(keys), (z["H"],), F32,
                                            dt_lo, dt_hi))
            return {**lp,
                    "w_in": w((D, z["Di"] + z["C"] + z["H"]), D),
                    "conv_k": w((z["C"], z["taps"]), z["taps"]),
                    "conv_b": (0.1 * jax.random.normal(
                        next(keys), (z["C"],), F32)).astype(dtype),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "a_log": jnp.log(jax.random.uniform(
                        next(keys), (z["H"],), F32, 1.0, 16.0)),
                    "d_skip": jnp.ones((z["H"],), F32),
                    "norm": norm(z["Di"]), "w_out": w((z["Di"], D), z["Di"])}
        if kind == EXPERTS:
            return {**lp, "router": w((D, z["E"]), D),
                    "route_bias": 0.05 * jax.random.normal(
                        next(keys), (z["E"],), F32),
                    "experts": relu2(z["Fe"], (z["held"],)),
                    "shared": relu2(z["Fs"])}
        return {**lp, "wq": w((D, z["Ha"], z["Dh"]), D),
                "wk": w((D, z["Hkv"], z["Dh"]), D),
                "wv": w((D, z["Hkv"], z["Dh"]), D),
                "wo": w((z["Ha"], z["Dh"], D), z["Ha"] * z["Dh"])}

    @jax.jit
    def ends(key):
        _, w, norm, _ = draws(key)
        return {"embed": w((V, D), D), "ln_f": norm(D), "head": w((V, D), D)}

    key = key_of(seed)
    pattern = cfg["hybrid_override_pattern"]
    return {**ends(jax.random.fold_in(key, len(pattern))),
            "layers": [layer(jax.random.fold_in(key, l), kind=kind)
                       for l, kind in enumerate(pattern)]}


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


def _relu2(h, w, quant):
    up = jax.nn.relu(_mm("td,df->tf", h, w["w_up"], quant))
    return _mm("tf,fd->td", up * up, w["w_down"], quant)


def _mamba_mixer(h, lp, z, eps, quant):
    T = h.shape[0]
    H, P, G, N, Di, C = z["H"], z["P"], z["G"], z["N"], z["Di"], z["C"]
    zud = _mm("td,de->te", h, lp["w_in"], quant)
    gate, u, dt = zud[:, :Di], zud[:, Di:Di + C], zud[:, Di + C:]
    k = lp["conv_k"].astype(F32)
    taps = k.shape[1]
    up = jnp.concatenate((jnp.zeros((taps - 1, C), F32), u))
    c = jax.nn.silu(sum(k[:, j] * up[j:j + T] for j in range(taps))
                    + lp["conv_b"].astype(F32))
    x = c[:, :Di].reshape(T, H, P)
    bm = c[:, Di:Di + G * N].reshape(T, G, N)
    cm = c[:, Di + G * N:].reshape(T, G, N)
    d = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))  # [T, H]
    a = -jnp.exp(lp["a_log"].astype(F32))

    def step(s, xs):
        """One position: S[h] = exp(d A) S[h] + d x (x) B[g]; y = S[h] . C[g]."""
        x_t, d_t, b_t, c_t = xs
        b_h, c_h = (jnp.repeat(v, H // G, axis=0) for v in (b_t, c_t))
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return s, jnp.sum(s * c_h[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, d, bm, cm))
    y = y + lp["d_skip"].astype(F32)[:, None] * x
    g = (y.reshape(T, Di) * jax.nn.silu(gate)).reshape(T, G, Di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return _mm("te,ed->td", g.reshape(T, Di) * lp["norm"].astype(F32),
               lp["w_out"], quant)


def _experts_mixer(h, lp, z, route_norm, route_scale, quant):
    T = h.shape[0]
    s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], quant))
    _, picked = jax.lax.top_k(s + lp["route_bias"], z["k"])
    w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1.0)
    if route_norm:
        w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    w = w * route_scale

    def add(e, y):  # the held expert e is the router's expert first + e
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False), lp["experts"])
        col = jax.lax.dynamic_slice_in_dim(w, z["first"] + e, 1, axis=1)
        return y + col * _relu2(h, one, quant)

    return jax.lax.fori_loop(0, z["held"], add,
                             _relu2(h, lp["shared"], quant))


def _attention_mixer(h, lp, quant):
    T = h.shape[0]
    H, Hkv = lp["wq"].shape[1], lp["wk"].shape[1]
    q = _mm("td,dhk->thk", h, lp["wq"], quant)
    k = _mm("td,dhk->thk", h, lp["wk"], quant)
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


@partial(jax.jit, static_argnames=("kind", "z", "eps", "route_norm",
                                   "route_scale", "quant"))
def _layer(x, lp, kind, z, eps, route_norm, route_scale, quant):
    z = dict(z)
    h = _norm(x, lp["ln"], eps)
    if kind == MAMBA:
        return x + _mamba_mixer(h, lp, z, eps, quant)
    if kind == EXPERTS:
        return x + _experts_mixer(h, lp, z, route_norm, route_scale, quant)
    return x + _attention_mixer(h, lp, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    return _mm("td,vd->tv", _norm(x, ln_f, eps), head, quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence, on
    the host: the head is multiplied `HEAD_ROWS` rows at a time."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    eps = float(cfg["layer_norm_epsilon"])
    z = tuple(sorted(sizes(cfg).items()))
    for kind, lp in zip(cfg["hybrid_override_pattern"], weights["layers"]):
        x = _layer(x, lp, kind, z, eps, cfg["norm_topk_prob"],
                   float(cfg["routed_scaling_factor"]), quant)
    out = [np.asarray(_head(
        jax.lax.dynamic_slice_in_dim(x, start, HEAD_ROWS), weights["ln_f"],
        weights["head"], eps, quant))[max(n - n_last - start, 0):n - start]
        for start in range((n - n_last) // HEAD_ROWS * HEAD_ROWS, n, HEAD_ROWS)]
    return np.concatenate(out)
