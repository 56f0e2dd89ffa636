"""The benchmark's side of the family `keyevl2` (Keye-VL-2.0-30B-A3B's
language model: grouped-query attention that sees, a query, only the 2048
cached positions a learned indexer scores best; a selector key cached beside K
and V; 128 softmax-routed experts, top-8, no shared one), found by the
configuration's `family` (`harness/family.py`): the plain reference, the
seeded weights, the control, and the least-work counts.  It imports nothing of
the program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-keyevl2.md` writes down, **as published**: the
indexer's scores `I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))` as a whole
causal array, a block of queries at a time; `lax.top_k` over each row (its
ties go to the earlier position); a dense softmax over all positions under the
picks' mask.  No cache, no kernels, no batching, no threshold; jax.numpy,
float32, matrix products at precision "highest"; every expert is computed for
every token and masked by the routing.  Weights stay in the type they are
served in and are upcast where they are used (an expert at a time), so that
the reference fits beside them at 33 k tokens.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/keyevl2.py` reads them: normal,
fan-in scaled, bfloat16-valued; the norms' weights are 1 + 0.1 N(0,1) (the
selector key's LayerNorm bias 0.1 N(0,1)), so that a step which leaves one of
them out fails the comparison, and the indexer's head weights `W_w` come out
of both signs, so that heads vote against each other.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's and the indexer's too) rounded through float8_e4m3, one
scale per tensor (per expert), the nearest precision below the
configuration's bfloat16.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .family_afmoe import _mm, _norm, _swiglu  # the plain pieces, as there
from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per block: scores are [H, 256, T] float32
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
LN_EPS = 1e-6


def sizes(cfg: dict):
    sa = cfg["sa_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: attention (with the q/k norms), the
    indexer (queries, the key with its LayerNorm, the heads' weights), the two
    norms, one expert, the router."""
    D, H, G, dh, HI_, dI, _ = sizes(cfg)
    return {"attention": 2 * D * H * dh + 2 * D * G * dh + 2 * dh,
            "indexer": D * HI_ * dI + D * dI + 2 * dI + D * HI_,
            "norms": 2 * D, "expert": 3 * D * cfg["moe_intermediate_size"],
            "router": D * cfg["num_experts"]}


def param_count(cfg: dict) -> int:
    c = layer_counts(cfg)
    layer = (c["attention"] + c["indexer"] + c["norms"] + c["router"]
             + cfg["num_experts"] * c["expert"])
    return (2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
            + cfg["num_hidden_layers"] * layer)


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * _itemsize(cfg)


def kv_token_bytes(cfg: dict) -> int:
    """K and V per head of one position over all layers."""
    _, _, G, dh, _, _, _ = sizes(cfg)
    return cfg["num_hidden_layers"] * 2 * G * dh * _itemsize(cfg)


def selector_token_bytes(cfg: dict) -> int:
    """The selector's key of one position over all layers."""
    return (cfg["num_hidden_layers"] * cfg["sa_config"]["indexer_head_dim"]
            * _itemsize(cfg))


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of the cache over all layers, in the served type: K, V and
    the selector's key of each position."""
    return block * (kv_token_bytes(cfg) + selector_token_bytes(cfg))


def _pairs(T: int, first: int = 0) -> int:
    """Query-key pairs of causal attention for queries first..T-1."""
    return (T * (T + 1) - first * (first + 1)) // 2


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, all layers, as a kernel that hides what was not picked computes
    it: scores and values over dh, 2 FLOPs a multiply-add, for every head and
    pair causality leaves."""
    _, H, _, dh, _, _, _ = sizes(cfg)
    return cfg["num_hidden_layers"] * 4 * H * dh * _pairs(T, first)


def index_flops(cfg: dict, pairs: float) -> float:
    """The indexer's scores over `pairs` query-position pairs, all layers:
    HI heads of dI lanes, 2 FLOPs a multiply-add."""
    _, _, _, _, HI_, dI, _ = sizes(cfg)
    return cfg["num_hidden_layers"] * 2 * HI_ * dI * pairs


# ------------------------------------------------------- least work, by step


def _live(cfg, counters):
    """(live sequences, their positions in whole blocks, the positions each
    attends over: min(context, topk)), a decode step's means."""
    from .engine import BLOCK

    steps = counters["decode_steps"]
    seqs = counters["decode_live_seqs"] / steps
    positions = counters["decode_live_blocks"] / steps * BLOCK
    picked = seqs * min(positions / seqs, cfg["sa_config"]["topk"])
    return seqs, positions, picked


def keyevl2_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: every weight outside the experts and
    the head once (of the embedding only the rows looked up); of each layer's
    E experts the E (1 - (1 - k/E)^B) that B sequences touch, which is an
    expectation under even routing and not a count; the selector keys of the
    live contexts; K and V of the min(context, topk) positions a sequence
    attends over; the new slots written.  The same work whatever implements
    it: a form that reads every position's K/V reads low, not over 100 %."""
    c, E, k = layer_counts(cfg), cfg["num_experts"], cfg["num_experts_per_tok"]
    L = cfg["num_hidden_layers"]
    seqs, positions, picked = _live(cfg, counters)
    touched = E * (1 - (1 - k / E) ** seqs)
    weights = (cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
               + L * (c["attention"] + c["indexer"] + c["norms"] + c["router"]
                      + touched * c["expert"]))
    moved = (weights * _itemsize(cfg) + seqs * cfg["hidden_size"] * _itemsize(cfg)
             + positions * selector_token_bytes(cfg)
             + picked * kv_token_bytes(cfg) + seqs * kv_block_bytes(cfg, 1))
    return moved / peak["hbm_bytes_s"]


def keyevl2_sparse_decode_scores_min_s(cfg, shapes, counters, peak) -> float:
    """A decode step's selector scores over all layers, what the kernel
    `sparse_decode_scores_pallas` does and no more: the larger of the live
    contexts' selector keys read once over the bandwidth and the indexer's
    products over the peak.  (The pick, the gather of the picked tiles and
    attention over them are XLA fusions that no `op` names: their work is in
    `keyevl2_decode_step_min_s` and their time in the whole step's.)"""
    _, positions, _ = _live(cfg, counters)
    return max(positions * selector_token_bytes(cfg) / peak["hbm_bytes_s"],
               index_flops(cfg, positions) / peak["bf16_flops"])


def keyevl2_sparse_hit_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """A hit prefill's sparse attention over all layers: the suffix's queries
    over the cached prefix and itself, the larger of the positions' K/V and
    selector keys read once over the bandwidth and the indexer's scores plus
    attention's products as a kernel that hides what was not picked computes
    them over the peak (compute-bound by far)."""
    prefix, suffix = shapes["hit"]
    total = prefix + suffix
    pairs = _pairs(total, prefix)
    return max(kv_block_bytes(cfg, total) / peak["hbm_bytes_s"],
               (index_flops(cfg, pairs)
                + prefill_attention_flops(cfg, total, prefix))
               / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/keyevl2.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once)."""
    D, H, G, dh, HI_, dI, _ = sizes(cfg)
    E, Fe, V = cfg["num_experts"], cfg["moe_intermediate_size"], cfg["vocab_size"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draws(key):
        keys = iter(jax.random.split(key, 32))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, F32)
                    * fan_in ** -0.5).astype(dtype)

        def norm(n, mean=1.0):
            return (mean + 0.1 * jax.random.normal(next(keys), (n,), F32)
                    ).astype(dtype)

        return w, norm

    @jax.jit
    def layer(key):
        w, norm = draws(key)
        return {"ln_in": norm(D), "ln_post": norm(D),
                "wq": w((D, H, dh), D), "wk": w((D, G, dh), D),
                "wv": w((D, G, dh), D), "wo": w((H, dh, D), H * dh),
                "q_norm": norm(dh), "k_norm": norm(dh),
                "w_qi": w((D, HI_, dI), D), "w_ki": w((D, dI), D),
                "ki_norm": norm(dI), "ki_bias": norm(dI, 0.0),
                "w_w": w((D, HI_), D), "router": w((D, E), D),
                "experts": {"w_gate": w((E, D, Fe), D), "w_up": w((E, D, Fe), D),
                            "w_down": w((E, Fe, D), Fe)}}

    @jax.jit
    def ends(key):
        w, norm = draws(key)
        return {"embed": w((V, D), D), "head": w((V, D), D), "ln_f": norm(D)}

    key = key_of(seed)
    L = cfg["num_hidden_layers"]
    return {**ends(jax.random.fold_in(key, L)),
            "layers": [layer(jax.random.fold_in(key, l)) for l in range(L)]}


# ------------------------------------------------------- the plain reference


def _rope(x, theta):
    """x: [T, ..., d]: lane i turns with lane i + d/2 by
    pos * theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    ang = (jnp.arange(T, dtype=F32)[:, None] * freqs).reshape(
        (T,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate((x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)), -1)


@partial(jax.jit, static_argnames=("topk", "theta", "eps", "top_k", "quant",
                                   "picks"))
def _layer(x, lp, topk, theta, eps, top_k, quant, picks=False):
    T = x.shape[0]
    H, G = lp["wq"].shape[1], lp["wk"].shape[1]
    h = _norm(x, lp["ln_in"], eps)
    q = _rope(_norm(_mm("td,dhk->thk", h, lp["wq"], quant), lp["q_norm"], eps),
              theta)
    k = _rope(_norm(_mm("td,dhk->thk", h, lp["wk"], quant), lp["k_norm"], eps),
              theta)
    v = _mm("td,dhk->thk", h, lp["wv"], quant)
    k, v = (jnp.repeat(a, H // G, axis=1) for a in (k, v))
    qi = _rope(_mm("td,dhk->thk", h, lp["w_qi"], quant), theta)
    ki = _mm("td,dk->tk", h, lp["w_ki"], quant)
    mean = ki.mean(-1, keepdims=True)
    ki = ((ki - mean) * jax.lax.rsqrt(((ki - mean) ** 2).mean(-1, keepdims=True)
                                      + LN_EPS)
          * lp["ki_norm"].astype(F32) + lp["ki_bias"].astype(F32))
    ki = _rope(ki, theta)
    w = _mm("td,dh->th", h, lp["w_w"], quant)
    n_pick = min(topk, T)

    def rows(i):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK, Q_BLOCK)

        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        seen = jnp.arange(T)[None, :] <= at
        index = jnp.einsum("qj,qjt->qt", cut(w), jax.nn.relu(jnp.einsum(
            "qjd,td->qjt", cut(qi), ki, precision=HI)), precision=HI)
        best, where = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), n_pick)
        picked = jnp.zeros((Q_BLOCK, T), bool).at[
            jnp.arange(Q_BLOCK)[:, None], where].set(best > -jnp.inf)
        s = jnp.einsum("qhk,thk->hqt", cut(q), k, precision=HI) \
            * q.shape[-1] ** -0.5
        p = jax.nn.softmax(jnp.where(picked[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v, precision=HI), picked

    attn, picked = jax.lax.map(rows, jnp.arange(T // Q_BLOCK))
    x = x + _mm("thk,hkd->td", attn.reshape(v.shape), lp["wo"], quant)
    h = _norm(x, lp["ln_post"], eps)
    s = jax.nn.softmax(_mm("td,de->te", h, lp["router"], quant), axis=-1)
    _, chosen = jax.lax.top_k(s, top_k)
    w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], chosen].set(1.0)
    w = w / w.sum(-1, keepdims=True)

    def add(e, y):
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False), lp["experts"])
        col = jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1)
        return y + col * _swiglu(h, one, quant)

    x = x + jax.lax.fori_loop(0, s.shape[1], add, jnp.zeros_like(x))
    return (x, picked.reshape(T, T)) if picks else x


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    return _mm("td,vd->tv", _norm(x, ln_f, eps), head, quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None, picks: list | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence.
    `picks` (a list, the tests') is given each layer's picked sets, bool
    [T, T]: row t the positions query t attends over."""
    n = len(tokens)
    pad = -n % Q_BLOCK  # causal: padding behind the end touches nothing before
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    eps = float(cfg["rms_norm_eps"])
    for lp in weights["layers"]:
        out = _layer(x, lp, cfg["sa_config"]["topk"], float(cfg["rope_theta"]),
                     eps, cfg["num_experts_per_tok"], quant,
                     picks=picks is not None)
        if picks is not None:
            x, picked = out
            picks.append(picked[:n, :n])
        else:
            x = out
    rows = min(n, -(-n_last // 64) * 64)  # few distinct shapes to compile
    return _head(x[n - rows:n], weights["ln_f"], weights["head"], eps,
                 quant)[rows - n_last:]
