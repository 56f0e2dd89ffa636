"""The benchmark's side of the family `llama` (dense decoders with grouped-query
attention), found by the configuration's `family` (`harness/family.py`): the
plain reference, the seeded weights, the control, and the least-work counts.

The plain reference: the decoder's forward pass in jax.numpy, float32,
matrix products at precision "highest", no kernels, no cache, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from `make_weights`, the benchmark's own seeded initialiser,
which also hands the program its parameters (weights are an input, like the
prompts).  `cfg` is the configuration file's dictionary.

Departures from the published models, shared with the program
(`models/llama.py`): the output head is tied to the embedding, and the
RMS-norm epsilon is 1e-6.

`quant="fp8"` is the control of "How correct is decided": the same pass with
both operands of every weight product rounded through float8_e4m3 (one scale
per tensor), the nearest precision below the configurations' bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256  # query rows per attention block: scores are [H, 256, T] f32
EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def sizes(cfg: dict):
    H = cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], cfg["hidden_size"], H,
            cfg["num_key_value_heads"], cfg["hidden_size"] // H,
            cfg["intermediate_size"], cfg["vocab_size"])


def param_count(cfg: dict) -> int:
    L, D, H, Hkv, Dh, F, V = sizes(cfg)
    return V * D + D + L * (2 * D + D * Dh * (2 * H + 2 * Hkv) + 3 * D * F)


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * jnp.dtype(cfg["torch_dtype"]).itemsize


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of K and V over all layers, in the served type."""
    L, _, _, Hkv, Dh, _, _ = sizes(cfg)
    return L * 2 * block * Hkv * Dh * jnp.dtype(cfg["torch_dtype"]).itemsize


def prefill_attention_flops(cfg: dict, T: int) -> int:
    """Causal attention of one prefill of T tokens, all layers: QK^T and PV
    over the lower triangle, 2 * T^2 * H * Dh FLOPs a layer."""
    L, _, H, _, Dh, _, _ = sizes(cfg)
    return L * 2 * T * T * H * Dh


def key_of(seed: int) -> jax.Array:
    """Any whole seed (the driver's pass 2**31) to a PRNG key."""
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded normal weights, fan-in scaled, in the type they are served in,
    laid out as `models/llama.py` reads them.  One jitted call on the device."""
    L, D, H, Hkv, Dh, F, V = sizes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])

    def build(key):
        ks = jax.random.split(key, 8)

        def w(k, shape, fan_in):
            return (jax.random.normal(k, shape, jnp.float32)
                    * fan_in ** -0.5).astype(dtype)

        return {
            "embed": w(ks[0], (V, D), D),
            "layers": {
                "ln1": jnp.ones((L, D), dtype), "ln2": jnp.ones((L, D), dtype),
                "wq": w(ks[1], (L, D, H, Dh), D),
                "wk": w(ks[2], (L, D, Hkv, Dh), D),
                "wv": w(ks[3], (L, D, Hkv, Dh), D),
                "wo": w(ks[4], (L, H, Dh, D), H * Dh),
                "w_gate": w(ks[5], (L, D, F), D),
                "w_up": w(ks[6], (L, D, F), D),
                "w_down": w(ks[7], (L, F, D), F),
            },
            "ln_f": jnp.ones((D,), dtype),
        }

    return jax.jit(build)(key_of(seed))


def _round(x, quant):
    if quant is None:
        return x
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, quant):
    return jnp.einsum(spec, _round(a, quant), _round(b, quant), precision=HI)


def _norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * w


def _rope(x, theta):
    T, _, Dh = x.shape
    freqs = theta ** (-jnp.arange(0, Dh // 2, dtype=jnp.float32) / (Dh // 2))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate((x1 * cos - x2 * sin, x2 * cos + x1 * sin), -1)


@partial(jax.jit, static_argnames=("theta", "quant"))
def _layer(x, layers, i, theta, quant):
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(jnp.float32), layers)
    T = x.shape[0]
    H, Hkv = lp["wq"].shape[1], lp["wk"].shape[1]
    h = _norm(x, lp["ln1"])
    q = _rope(_mm("td,dhk->thk", h, lp["wq"], quant), theta)
    k = _rope(_mm("td,dhk->thk", h, lp["wk"], quant), theta)
    v = _mm("td,dhk->thk", h, lp["wv"], quant)
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))

    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhk,thk->hqt", qi, k, precision=HI) * q.shape[-1] ** -0.5
        seen = (jnp.arange(T)[None, :]
                <= i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=HI)

    attn = jax.lax.map(rows, jnp.arange(T // Q_BLOCK)).reshape(q.shape)
    x = x + _mm("thk,hkd->td", attn, lp["wo"], quant)
    h = _norm(x, lp["ln2"])
    gate = jax.nn.silu(_mm("td,df->tf", h, lp["w_gate"], quant))
    return x + _mm("tf,fd->td", gate * _mm("td,df->tf", h, lp["w_up"], quant),
                   lp["w_down"], quant)


@partial(jax.jit, static_argnames=("quant",))
def _head(x, ln_f, embed, quant):
    x = _norm(x, ln_f.astype(jnp.float32))
    return _mm("td,vd->tv", x, embed.astype(jnp.float32), quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, weights["layers"], i, float(cfg["rope_theta"]), quant)
    rows = min(n, -(-n_last // 64) * 64)  # few distinct shapes to compile
    return _head(x[n - rows:n], weights["ln_f"], weights["embed"],
                 quant)[rows - n_last:]
