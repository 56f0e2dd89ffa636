"""The benchmark's side of the family `phi4flash` (a decoder-hybrid-decoder:
Mamba-1 layers alternating with differential window attention, one full
attention layer whose K/V every later attention layer reads, gated memory
units between those), found by the configuration's `family`
(`harness/family.py`): the plain reference, the seeded weights, the control,
and the least-work counts.  It imports nothing of the program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-phi4flash.md` writes down, in jax.numpy, float32,
matrix products at precision "highest", no kernels, no cache, no batching:
the selective scan is a `lax.scan` over positions, one state at a time; the
convolution is four shifted products; the four `Att` products of a
differential layer are each a dense masked softmax over blocks of query rows;
all layers run over all positions.  Weights stay in the type they are served
in and are upcast where they are used.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/phi4flash.py` reads them (the
(Mamba, window) pairs and the (memory unit, cross) pairs stacked, `a` the
pair's even layer and `b` its odd one): every matrix N(0, 1/fan-in),
bfloat16-valued; `A_log = log(1..N)` in every channel, `D` = 1, `b_dt` the
inverse softplus of values log-uniform in [1e-3, 1e-1] (+ the published
initialisation, so that the recurrence decays as a trained model's does);
the `lam` vectors N(0, 0.1^2); LayerNorm weights 1 + 0.1 N(0,1) and every bias
0.1 N(0,1), so that a step which leaves one out fails the comparison.

`quant="fp8"` is the control: the same pass with both operands of every
weight product rounded through float8_e4m3, one scale per tensor, the nearest
precision below the configuration's bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per attention block: scores are [H/2, 256, T] f32
HEAD_ROWS = Q_BLOCK  # rows of logits a product of the head makes at a time
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SUB_NORM_EPS = 1e-5


def sizes(cfg: dict):
    """(D, H, Hkv, d, F, V, Di, N, R, taps)."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return (D, H, cfg["num_key_value_heads"], D // H, cfg["intermediate_size"],
            cfg["vocab_size"], cfg["mamba_expand"] * D, cfg["mamba_d_state"],
            cfg["mamba_dt_rank"], cfg["mamba_d_conv"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def layers(cfg: dict) -> dict:
    """How many layers of each kind: L/4 (Mamba, window) pairs, one Mamba and
    one full attention layer, L/4 - 1 (memory unit, cross) pairs."""
    pairs = cfg["num_hidden_layers"] // 4
    return {"mamba": pairs + 1, "window": pairs, "full": 1, "gmu": pairs - 1,
            "cross": pairs - 1}


def full_readers(cfg: dict) -> int:
    """Layers that read the full group's K/V: the layer that writes it and
    every cross layer."""
    return 1 + layers(cfg)["cross"]


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: what every layer has (two LayerNorms
    and the SwiGLU) and each kind of mixer."""
    D, H, Hkv, d, F, _, Di, N, R, taps = sizes(cfg)
    cross = 2 * (D * H * d) + H * d + D + 4 * d + 2 * d  # q, out, lam, norm
    return {"common": 4 * D + 3 * D * F,
            "mamba": (2 * D * Di + Di * taps + Di + Di * (R + 2 * N) + R * Di
                      + Di + N * Di + Di + Di * D),
            "attention": cross + 2 * (D * Hkv * d + Hkv * d), "cross": cross,
            "gmu": 2 * D * Di}


def param_count(cfg: dict) -> int:
    """All parameters, the tied table once."""
    c, n = layer_counts(cfg), layers(cfg)
    return (cfg["vocab_size"] * cfg["hidden_size"] + 2 * cfg["hidden_size"]
            + cfg["num_hidden_layers"] * c["common"] + n["mamba"] * c["mamba"]
            + (n["window"] + n["full"]) * c["attention"]
            + n["cross"] * c["cross"] + n["gmu"] * c["gmu"])


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in (the recurrence's
    few float32 vectors counted in it too: under a thousandth)."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict) -> int:
    """K and V of one position in one attention layer (the full group holds
    one layer's)."""
    _, _, Hkv, d, *_ = sizes(cfg)
    return 2 * Hkv * d * _itemsize(cfg)


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of the full group."""
    return block * kv_token_bytes(cfg)


def window_block_bytes(cfg: dict, block: int) -> int:
    """One slot of the window group: a block's K/V in every window layer."""
    return layers(cfg)["window"] * block * kv_token_bytes(cfg)


def state_slot_bytes(cfg: dict) -> int:
    """The Mamba layers' state of one sequence: d_conv - 1 inputs of Di in
    the serving type and Di x N of float32 a layer, whatever the context."""
    *_, Di, N, _, taps = sizes(cfg)
    return layers(cfg)["mamba"] * ((taps - 1) * Di * _itemsize(cfg)
                                   + Di * N * 4)


def attention_pair_flops(cfg: dict) -> int:
    """FLOPs of one (query position, key position) pair in one differential
    layer, the least: each query head's score against its K head once (2 d)
    and its weights times two V heads (2 x 2 d)."""
    _, H, _, d, *_ = sizes(cfg)
    return 6 * H * d


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new: causal pairs in the full layer, pairs within the window in the
    window layers.  (The cross layers run on the last position alone in a
    prefill, and their one row is not counted.)"""
    w = cfg["sliding_window"]
    new = np.arange(first, T)
    pairs = int((new + 1).sum()) \
        + layers(cfg)["window"] * int(np.minimum(new + 1, w).sum())
    return attention_pair_flops(cfg) * pairs


# ------------------------------------------------------- least work, by step


def _decode_kv_bytes(cfg, counters) -> float:
    """K/V one decode step has to read: every distinct live block of the full
    group once a reader (a shared prompt's blocks once for all its sequences),
    and min(context, window) positions a live sequence in each window layer.
    The counters give no sequence's context: the mean live blocks a sequence
    stands in for it, which is no more."""
    from .engine import BLOCK

    steps = counters["decode_steps"]
    blocks, seqs = (counters["decode_live_blocks"] / steps,
                    counters["decode_live_seqs"] / steps)
    seen = min(cfg["sliding_window"], blocks * BLOCK / max(seqs, 1))
    return (blocks * kv_block_bytes(cfg, BLOCK) * full_readers(cfg)
            + seqs * seen * layers(cfg)["window"] * kv_token_bytes(cfg))


def phi4flash_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: every weight once with the tied
    table once; the K/V of `_decode_kv_bytes`; each live sequence's state
    read and written; the new K/V written (one full layer and the window
    layers)."""
    seqs = counters["decode_live_seqs"] / counters["decode_steps"]
    written = (1 + layers(cfg)["window"]) * kv_token_bytes(cfg)
    moved = (param_bytes(cfg) + _decode_kv_bytes(cfg, counters)
             + seqs * (2 * state_slot_bytes(cfg) + written))
    return moved / peak["hbm_bytes_s"]


def phi4flash_paged_decode_attention_min_s(cfg, shapes, counters, peak) -> float:
    """The paged decode kernel over every attention layer of one step: the
    K/V of `_decode_kv_bytes`.  Bandwidth-bound."""
    return _decode_kv_bytes(cfg, counters) / peak["hbm_bytes_s"]


def phi4flash_flash_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """The flash kernel over one hit prefill: the suffix's queries over the
    cached prefix and itself in the full layer, banded to the window in the
    window layers.  Compute-bound."""
    prefix, suffix = shapes["hit"]
    return (prefill_attention_flops(cfg, prefix + suffix, prefix)
            / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/phi4flash.py` reads, a jitted call a group of
    stacked layers on the device."""
    D, H, Hkv, d, F, V, Di, N, R, taps = sizes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    pairs = cfg["num_hidden_layers"] // 4

    def layer(key, kind):
        keys = iter(jax.random.split(key, 24))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, F32)
                    * fan_in ** -0.5).astype(dtype)

        def small(shape, scale, mean=0.0):
            return (mean + scale * jax.random.normal(next(keys), shape, F32)
                    ).astype(dtype)

        def norm(n):
            return {"w": small((n,), 0.1, 1.0), "b": small((n,), 0.1)}

        lp = {"ln_in": norm(D), "ln_post": norm(D),
              "w_gu": w((D, 2 * F), D), "w_down": w((F, D), F)}
        if kind == "mamba":
            dt = jnp.exp(jax.random.uniform(
                next(keys), (Di,), F32, math.log(1e-3), math.log(1e-1)))
            lp.update(
                w_in=w((D, 2 * Di), D), conv_k=w((Di, taps), taps),
                conv_b=small((Di,), 0.1), w_x=w((Di, R + 2 * N), Di),
                w_dt=w((R, Di), R), b_dt=dt + jnp.log(-jnp.expm1(-dt)),
                a_log=jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=F32))[:, None], (N, Di)),
                d_skip=jnp.ones((Di,), F32), w_out=w((Di, D), Di))
        elif kind == "gmu":
            lp.update(w_1=w((D, Di), D), w_2=w((Di, D), Di))
        else:
            lp.update(wq=w((D, H * d), D), bq=small((H * d,), 0.1),
                      wo=w((H * d, D), H * d), bo=small((D,), 0.1),
                      lam=small((4, d), 0.1).astype(F32),
                      sub_norm=small((2 * d,), 0.1, 1.0))
            if kind == "attn":
                lp.update(wk=w((D, Hkv * d), D), bk=small((Hkv * d,), 0.1),
                          wv=w((D, Hkv * d), D), bv=small((Hkv * d,), 0.1))
        return lp

    @partial(jax.jit, static_argnames=("kind", "n"))
    def stacked(key, kind, n):
        return jax.vmap(lambda k: layer(k, kind))(jax.random.split(key, n))

    @partial(jax.jit, static_argnames=("kind",))
    def one(key, kind):
        return layer(key, kind)

    @jax.jit
    def ends(key):
        k_e, k_w, k_b = jax.random.split(key, 3)
        return {"embed": (jax.random.normal(k_e, (V, D), F32)
                          * D ** -0.5).astype(dtype),
                "ln_f": {"w": (1.0 + 0.1 * jax.random.normal(k_w, (D,), F32)
                               ).astype(dtype),
                         "b": (0.1 * jax.random.normal(k_b, (D,), F32)
                               ).astype(dtype)}}

    key = key_of(seed)
    fold = partial(jax.random.fold_in, key)
    return {**ends(fold(0)),
            "front": {"a": stacked(fold(1), "mamba", pairs),
                      "b": stacked(fold(2), "attn", pairs)},
            "mid": {"a": one(fold(3), "mamba"), "b": one(fold(4), "attn")},
            "back": {"a": stacked(fold(5), "gmu", pairs - 1),
                     "b": stacked(fold(6), "cross", pairs - 1)}}


# ------------------------------------------------------- the plain reference


def _round(x, quant):
    if quant is None:
        return x
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _round(a, quant), _round(b.astype(F32), quant),
                      precision=HI)


def _ln(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["w"].astype(F32)
            + p["b"].astype(F32))


def _ff(a, lp, eps, quant):
    gu = _mm("td,df->tf", _ln(a, lp["ln_post"], eps), lp["w_gu"], quant)
    F = gu.shape[1] // 2  # [g | u]
    return a + _mm("tf,fd->td", jax.nn.silu(gu[:, :F]) * gu[:, F:],
                   lp["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _mamba_layer(x, lp, eps, quant):
    """Returns (x', m): m the scan's output with the D term, before the gate."""
    T = x.shape[0]
    h = _ln(x, lp["ln_in"], eps)
    uz = _mm("td,de->te", h, lp["w_in"], quant)
    u, z = uz[:, :uz.shape[1] // 2], uz[:, uz.shape[1] // 2:]  # [u | z]
    k = lp["conv_k"].astype(F32)
    taps = k.shape[1]
    up = jnp.concatenate((jnp.zeros((taps - 1, u.shape[1]), F32), u))
    c = jax.nn.silu(sum(k[:, j] * up[j:j + T] for j in range(taps))
                    + lp["conv_b"].astype(F32))
    N = lp["a_log"].shape[0]
    R = lp["w_dt"].shape[0]
    dbc = _mm("td,dr->tr", c, lp["w_x"], quant)
    delta = jax.nn.softplus(_mm("tr,rd->td", dbc[:, :R], lp["w_dt"], quant)
                            + lp["b_dt"].astype(F32))
    a = -jnp.exp(lp["a_log"].astype(F32))  # [N, Di]

    def step(s, xs):
        d_t, c_t, b_t, c_out = xs
        s = jnp.exp(d_t[None, :] * a) * s + (d_t * c_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_out[:, None], axis=0)

    _, sc = jax.lax.scan(step, jnp.zeros_like(a),
                         (delta, c, dbc[:, R:R + N], dbc[:, R + N:]))
    m = sc + lp["d_skip"].astype(F32) * c
    y = _mm("td,de->te", m * jax.nn.silu(z), lp["w_out"], quant)
    return _ff(x + y, lp, eps, quant), m


@partial(jax.jit, static_argnames=("eps", "quant"))
def _gmu_layer(x, m, lp, eps, quant):
    h = _ln(x, lp["ln_in"], eps)
    gate = jax.nn.silu(_mm("td,de->te", h, lp["w_1"], quant))
    return _ff(x + _mm("te,ed->td", m * gate, lp["w_2"], quant), lp, eps, quant)


@partial(jax.jit, static_argnames=("d", "eps", "quant"))
def _keys_values(x, lp, d, eps, quant):
    """K and V of a layer as [T, Hkv, d]: a projection's heads lie side by
    side in its columns."""
    h = _ln(x, lp["ln_in"], eps)
    k = _mm("td,de->te", h, lp["wk"], quant) + lp["bk"].astype(F32)
    v = _mm("td,de->te", h, lp["wv"], quant) + lp["bv"].astype(F32)
    return k.reshape(len(k), -1, d), v.reshape(len(v), -1, d)


@partial(jax.jit, static_argnames=("window", "eps", "quant"))
def _attention_layer(x, k, v, lp, lam0, window, eps, quant):
    """A differential attention layer over the keys and values it is handed
    (its own, or the full layer's): the four products as the equations write
    them, each a dense masked softmax over blocks of query rows."""
    T = x.shape[0]
    h = _ln(x, lp["ln_in"], eps)
    d = k.shape[-1]
    q = (_mm("td,de->te", h, lp["wq"], quant) + lp["bq"].astype(F32)
         ).reshape(T, -1, d)
    H = q.shape[1]

    def att(q, k, v):
        """q: [T, H/2, d]; k, v: [T, H/4, d], two query heads a KV head."""
        k, v = (jnp.repeat(a, 2, axis=1) for a in (k, v))

        def rows(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
            s = jnp.einsum("qhk,thk->hqt", qi, k, precision=HI) * d ** -0.5
            at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
            seen = jnp.arange(T)[None, :] <= at
            if window:
                seen &= jnp.arange(T)[None, :] > at - window
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thk->qhk", p, v, precision=HI)

        return jax.lax.map(rows, jnp.arange(T // Q_BLOCK)).reshape(q.shape)

    q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]
    v1, v2 = v[:, 0::2], v[:, 1::2]
    o1 = jnp.concatenate((att(q1, k1, v1), att(q1, k1, v2)), axis=-1)
    o2 = jnp.concatenate((att(q2, k2, v1), att(q2, k2, v2)), axis=-1)
    lam = lp["lam"].astype(F32)
    lam = (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
           + lam0)
    diff = o1 - lam * o2
    diff = diff * jax.lax.rsqrt(
        jnp.mean(diff * diff, -1, keepdims=True) + SUB_NORM_EPS)
    diff = (diff * lp["sub_norm"].astype(F32) * (1.0 - lam0)).reshape(T, H * d)
    y = _mm("te,ed->td", diff, lp["wo"], quant) + lp["bo"].astype(F32)
    return _ff(x + y, lp, eps, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, embed, eps, quant):
    return _mm("td,vd->tv", _ln(x, ln_f, eps), embed, quant)


def lam0_of(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence, on
    the host: the head is multiplied `HEAD_ROWS` rows at a time."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    eps = float(cfg["layer_norm_eps"])
    L, window = cfg["num_hidden_layers"], cfg["sliding_window"]
    d = cfg["hidden_size"] // cfg["num_attention_heads"]

    def of(part, role, i):
        return jax.tree.map(lambda a: a[i], weights[part][role])

    for l in range(0, L // 2, 2):
        x, _ = _mamba_layer(x, of("front", "a", l // 2), eps, quant)
        lp = of("front", "b", l // 2)
        k, v = _keys_values(x, lp, d, eps, quant)
        x = _attention_layer(x, k, v, lp, lam0_of(l + 1), window, eps, quant)
    x, m = _mamba_layer(x, weights["mid"]["a"], eps, quant)
    lp = weights["mid"]["b"]
    k, v = _keys_values(x, lp, d, eps, quant)  # the cross-decoder's from here on
    x = _attention_layer(x, k, v, lp, lam0_of(L // 2 + 1), None, eps, quant)
    for l in range(L // 2 + 2, L, 2):
        i = (l - L // 2 - 2) // 2
        x = _gmu_layer(x, m, of("back", "a", i), eps, quant)
        x = _attention_layer(x, k, v, of("back", "b", i), lam0_of(l + 1), None,
                             eps, quant)
    out = [np.asarray(_head(
        jax.lax.dynamic_slice_in_dim(x, start, HEAD_ROWS), weights["ln_f"],
        weights["embed"], eps, quant))[max(n - n_last - start, 0):n - start]
        for start in range((n - n_last) // HEAD_ROWS * HEAD_ROWS, n, HEAD_ROWS)]
    return np.concatenate(out)
