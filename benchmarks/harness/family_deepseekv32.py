"""The benchmark's side of the family `deepseekv32` (DeepSeek-V3.2-Exp: latent
attention whose queries see, of the latent cache, only the 2048 positions a
learned indexer scores best; the indexer's key cached in the latent's slot;
256 sigmoid-routed experts picked within the best 4 of 8 groups, one shared;
a rotation rescaled by YaRN), found by the configuration's `family`
(`harness/family.py`): the plain reference, the seeded weights, the control,
and the least-work counts.  It imports nothing of the program.

The plain reference is the forward pass of the layer equations that
`benchmarks/configs/README-deepseekv32.md` writes down, **in the published
per-head form**: every position's keys `kn_h = c . W_uk_h` and values `v_h = c
. W_uv_h` are made for every head, the rotary key is shared by the heads; the
indexer's scores `I(t, s) = sum_j w_j(t) relu(qI_j(t) . kI(s))` as a causal
array, a block of queries at a time; `lax.top_k` over each row (its ties go
to the earlier position); a dense softmax over the positions under the picks'
mask.  No latent-space identity, no cache, no kernels, no batching;
jax.numpy, float32, matrix products at precision "highest"; every held expert
is computed for every token and masked by the routing.  Weights stay in the
type they are served in and are upcast where they are used (a group of heads,
an expert, a slice of the dense layer's width at a time), so that the
reference fits beside them at 33 k tokens.

**What it leaves out is what no logit that is asked for feels** (a run of the
benchmark waits for three such passes; each saving is held against the pass
without it by
`test_family_deepseekv32.py::test_runs_and_last_blocks_change_no_logit`): a
block of queries is held against the positions up to the end of its run of
blocks and not against those behind it, which it may not see (`RUNS`); the
last layer is computed for the blocks of the positions whose logits are asked
for, every position being its key still; the picks' mask is read off
`top_k`'s k-th value and the last position it took at that value, with no
scatter, once a block of queries for all heads.  (My chip run, PR 53, a
33 k-token sample: a layer's attention 14.5 s -> 4.3, the pass 77 s -> 20.
Held experts over the tokens that picked them alone, gathered, read 0.70 s a
layer against 0.83: not taken.  The squares at three passes,
`Precision.HIGH`, moved the logits by 8.5 % of their norm, picks near the
k-th score changing sides: "highest" it stays.)

**The chip's share.**  The configuration's `n_routed_experts` is the number of
experts this chip HOLDS (`held.experts_first` the first of them) of the
`published.n_routed_experts` the router scores.  The reference is given the
same share: it routes over all, limits to the best groups and picks
`num_experts_per_tok` as published, and adds the held experts' terms only;
what the others would add is left out, and that partial sum goes on to the
next layer.

`make_weights` is the benchmark's own seeded initialiser and also hands the
program its parameters, laid out as `models/deepseekv32.py` reads them:
normal, fan-in scaled, bfloat16-valued; the norms' weights are 1 + 0.1 N(0,1)
(the selector key's LayerNorm bias 0.1 N(0,1)) and the selection bias 0.05
N(0,1), so that a step which leaves one of them out fails the comparison, and
the indexer's head weights `W_w` come out of both signs, so that heads vote
against each other.

`quant="fp8"` is the control: the same pass with both operands of every weight
product (the router's and the indexer's too) rounded through float8_e4m3, one
scale per tensor as it is used (per expert, per group of heads), the nearest
precision below the configuration's bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .family_afmoe import _mm, _norm  # the plain pieces, as there
from .family_llama import key_of  # any whole seed to a PRNG key

Q_BLOCK = 256  # query rows per block
PAD_BLOCKS = 2  # a sequence is padded to a whole number of these
RUNS = 5  # runs of query blocks, each held against the keys up to its end
HEAD_GROUP = 8  # heads at a time: scores are [8, 256, T] float32
FF_SLICE = 2048  # lanes of the dense layer's width at a time
HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
LN_EPS = 1e-6
ROUTE_NORM_EPS = 1e-20


def sizes(cfg: dict) -> dict:
    """The widths by the names the equations use."""
    held = cfg["n_routed_experts"]
    rs = cfg["rope_scaling"]
    return dict(
        D=cfg["hidden_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"], Rq=cfg["q_lora_rank"],
        Rkv=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        HI=cfg["index_n_heads"], dI=cfg["index_head_dim"],
        K=cfg["index_topk"], F=cfg["intermediate_size"],
        Fe=cfg["moe_intermediate_size"], shared=cfg["n_shared_experts"],
        held=held, first=cfg.get("held", {}).get("experts_first", 0),
        E=cfg.get("published", {}).get("n_routed_experts", held),
        k=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        topk_group=cfg["topk_group"], dense=cfg["first_k_dense_replace"],
        L=cfg["num_hidden_layers"], theta=float(cfg["rope_theta"]),
        factor=float(rs["factor"]),
        original=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        eps=float(cfg["rms_norm_eps"]),
        route_scale=float(cfg["routed_scaling_factor"]))


def _itemsize(cfg: dict) -> int:
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def score_scale(z: dict) -> float:
    """`(dn + dr)^(-1/2) m^2`, `m = 0.1 mscale_all_dim ln(factor) + 1`."""
    m = 0.1 * z["mscale_all_dim"] * math.log(z["factor"]) + 1.0
    return (z["dn"] + z["dr"]) ** -0.5 * m * m


def yarn_inv_freq(z: dict):
    """The dr / 2 inverse frequencies, rescaled: pair i turns by pos * f'_i,
    f_i = theta^(-2i/dr); `low` and `high` are the pairs that turn beta_fast
    and beta_slow times over the original context; r_i = clip((i - low) /
    (high - low), 0, 1); f'_i = f_i (1 - r_i) + (f_i / factor) r_i."""
    dr = z["dr"]

    def pair_of(turns):
        return dr * math.log(z["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(z["theta"]))

    low = max(math.floor(pair_of(z["beta_fast"])), 0)
    high = min(math.ceil(pair_of(z["beta_slow"])), dr // 2 - 1)
    i = jnp.arange(dr // 2, dtype=F32)
    f = z["theta"] ** (-i / (dr // 2))
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1.0 - r) + f / z["factor"] * r


def layer_counts(cfg: dict) -> dict:
    """Parameters of one layer by part: attention (both bottlenecks with
    their norms, the up-projections, the output), the indexer (queries from
    the bottleneck, the key with its LayerNorm, the heads' weights), the two
    norms, a dense feed-forward, one expert, the shared expert, the router
    (over all the published experts, with its selection bias)."""
    z = sizes(cfg)
    D, H = z["D"], z["H"]
    return {"attention": (D * z["Rq"] + z["Rq"]
                          + z["Rq"] * H * (z["dn"] + z["dr"])
                          + D * (z["Rkv"] + z["dr"]) + z["Rkv"]
                          + z["Rkv"] * H * (z["dn"] + z["dv"])
                          + H * z["dv"] * D),
            "indexer": (z["Rq"] * z["HI"] * z["dI"] + D * z["dI"]
                        + 2 * z["dI"] + D * z["HI"]),
            "norms": 2 * D, "dense": 3 * D * z["F"],
            "expert": 3 * D * z["Fe"],
            "shared": 3 * D * z["Fe"] * z["shared"],
            "router": D * z["E"] + z["E"]}


def param_count(cfg: dict, experts: float | None = None,
                embedding: bool = True) -> float:
    """All parameters held here (the embedding and the untied head each
    once); with `experts`, that many of each expert layer's held experts in
    place of all; without `embedding`, what a decode step reads whole."""
    c, z = layer_counts(cfg), sizes(cfg)
    held = z["held"] if experts is None else experts
    every = c["attention"] + c["indexer"] + c["norms"]
    return ((1 + embedding) * z["V"] * z["D"] + z["D"]
            + z["dense"] * (every + c["dense"])
            + (z["L"] - z["dense"]) * (every + held * c["expert"]
                                       + c["shared"] + c["router"]))


def param_bytes(cfg: dict) -> int:
    """The weights once, in the type they are served in."""
    return param_count(cfg) * _itemsize(cfg)


def latent_token_bytes(cfg: dict) -> int:
    """The latent vector of one position over all layers: kv_lora_rank +
    qk_rope_head_dim numbers a layer, key and value at once."""
    z = sizes(cfg)
    return z["L"] * (z["Rkv"] + z["dr"]) * _itemsize(cfg)


def selector_token_bytes(cfg: dict) -> int:
    """The selector's key of one position over all layers."""
    z = sizes(cfg)
    return z["L"] * z["dI"] * _itemsize(cfg)


def kv_block_bytes(cfg: dict, block: int) -> int:
    """One block of the cache over all layers, in the served type: the latent
    and the selector's key of each position."""
    return block * (latent_token_bytes(cfg) + selector_token_bytes(cfg))


def _picked_pairs(cfg: dict, T: int, first: int = 0) -> int:
    """Query-position pairs attention keeps for queries first..T-1: query t
    sees min(t + 1, index_topk) positions."""
    K = cfg["index_topk"]
    full = max(T - max(first, K - 1), 0)  # queries with K picks
    few = range(first + 1, min(T, K - 1) + 1)  # t + 1 for the others
    return full * K + sum(few)


def prefill_attention_flops(cfg: dict, T: int, first: int = 0) -> int:
    """Attention of one prefill over T positions of which the last T - first
    are new, all layers, the least the algorithm needs: the published
    per-head form (scores over dn + dr, values over dv, 2 FLOPs a
    multiply-add) for every head over the pairs the picks keep."""
    z = sizes(cfg)
    return (z["L"] * 2 * z["H"] * (z["dn"] + z["dr"] + z["dv"])
            * _picked_pairs(cfg, T, first))


def index_flops(cfg: dict, pairs: float) -> float:
    """The indexer's scores over `pairs` query-position pairs, all layers:
    HI heads of dI lanes, 2 FLOPs a multiply-add."""
    z = sizes(cfg)
    return z["L"] * 2 * z["HI"] * z["dI"] * pairs


# ------------------------------------------------------- least work, by step


def _live(cfg, shapes, counters):
    """A decode step's means: (live sequences; the distinct live positions,
    in whole blocks, a context that sequences share counted once; the
    positions the sequences attend over, min(context, topk) each, a context
    being its shared prefix at least)."""
    from .engine import BLOCK

    steps = counters["decode_steps"]
    seqs = counters["decode_live_seqs"] / steps
    positions = counters["decode_live_blocks"] / steps * BLOCK
    context = max(shapes["hit"][0], positions / max(seqs, 1e-9))
    return seqs, positions, seqs * min(context, cfg["index_topk"])


def deepseekv32_decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound, the same work whatever implements
    it: the head, attention, the indexer, the router, the shared expert and
    the dense layer whole (of the embedding only the rows looked up); of each
    expert layer's held experts the held (1 - (1 - k/E)^B) that B sequences
    touch, an expectation under even routing and not a count; the selector
    keys of the distinct live positions; the latents of the min(context,
    topk) positions a sequence attends over; the new slots written.  A form
    that reads every position's latent reads low, not over 100 %."""
    z = sizes(cfg)
    seqs, positions, picked = _live(cfg, shapes, counters)
    touched = z["held"] * (1 - (1 - z["k"] / z["E"]) ** seqs)
    moved = (param_count(cfg, touched, embedding=False) * _itemsize(cfg)
             + seqs * z["D"] * _itemsize(cfg)
             + positions * selector_token_bytes(cfg)
             + picked * latent_token_bytes(cfg)
             + seqs * kv_block_bytes(cfg, 1))
    return moved / peak["hbm_bytes_s"]


def deepseekv32_latent_index_scores_min_s(cfg, shapes, counters, peak) -> float:
    """A decode step's selector scores over all layers, what the kernel
    `latent_index_scores_pallas` does and no more: the larger of the distinct
    live positions' selector keys read once over the bandwidth and the
    indexer's products, every live sequence over its own context (its shared
    prefix at least), over the peak.  (The pick, the gather of the picked
    rows and attention over them are XLA fusions that no `op` names: their
    work is in `deepseekv32_decode_step_min_s` and their time in the whole
    step's.)"""
    seqs, positions, _ = _live(cfg, shapes, counters)
    pairs = max(positions, seqs * shapes["hit"][0])
    return max(positions * selector_token_bytes(cfg) / peak["hbm_bytes_s"],
               index_flops(cfg, pairs) / peak["bf16_flops"])


def deepseekv32_sparse_latent_hit_prefill_min_s(cfg, shapes, counters,
                                                peak) -> float:
    """A hit prefill's attention under the picks over all layers, what the
    kernel `latent_picked_prefill_pallas` has to do and no more: the larger
    of the positions' latents read once over the bandwidth and attention's
    products over the pairs the picks keep, in the cheaper per-head form,
    over the peak.  (A kernel that computes every causal pair in the latent
    space and hides what was not picked does 16 x (33 k / 2048) x 3.4 x
    (1088 / 320) the products: it reads low, not over 100 %.  The indexer's
    scores of a hit are `sparse_index_scores_pallas`'s, another `op`.)"""
    prefix, suffix = shapes["hit"]
    total = prefix + suffix
    return max(total * latent_token_bytes(cfg) / peak["hbm_bytes_s"],
               prefill_attention_flops(cfg, total, prefix)
               / peak["bf16_flops"])


# ------------------------------------------------------------ seeded weights


def make_weights(cfg: dict, seed: int) -> dict:
    """The pytree `models/deepseekv32.py` reads, a jitted call a layer on the
    device (one call for all would hold every float32 draw at once).  An
    expert layer's stacks hold the held experts only."""
    z = sizes(cfg)
    D, H, V = z["D"], z["H"], z["V"]
    dtype = jnp.dtype(cfg["torch_dtype"])

    def draws(key):
        keys = iter(jax.random.split(key, 40))

        def w(shape, fan_in):
            return (jax.random.normal(next(keys), shape, F32)
                    * fan_in ** -0.5).astype(dtype)

        def norm(n, mean=1.0):
            return (mean + 0.1 * jax.random.normal(next(keys), (n,), F32)
                    ).astype(dtype)

        def swiglu(width, lead=()):
            return {"w_gate": w(lead + (D, width), D),
                    "w_up": w(lead + (D, width), D),
                    "w_down": w(lead + (width, D), width)}

        return keys, w, norm, swiglu

    @partial(jax.jit, static_argnames=("experts",))
    def layer(key, experts):
        keys, w, norm, swiglu = draws(key)
        lp = {"ln_in": norm(D), "ln_post": norm(D),
              "w_qa": w((D, z["Rq"]), D), "q_norm": norm(z["Rq"]),
              "w_qb": w((z["Rq"], H, z["dn"] + z["dr"]), z["Rq"]),
              "w_kva": w((D, z["Rkv"] + z["dr"]), D),
              "kv_norm": norm(z["Rkv"]),
              "w_kvb": w((z["Rkv"], H, z["dn"] + z["dv"]), z["Rkv"]),
              "wo": w((H, z["dv"], D), H * z["dv"]),
              "w_qi": w((z["Rq"], z["HI"], z["dI"]), z["Rq"]),
              "w_ki": w((D, z["dI"]), D), "ki_norm": norm(z["dI"]),
              "ki_bias": norm(z["dI"], 0.0), "w_w": w((D, z["HI"]), D)}
        if not experts:
            return {**lp, "mlp": swiglu(z["F"])}
        return {**lp, "router": w((D, z["E"]), D),
                "route_bias": 0.05 * jax.random.normal(
                    next(keys), (z["E"],), F32),
                "shared": swiglu(z["shared"] * z["Fe"]),
                "experts": swiglu(z["Fe"], (z["held"],))}

    @jax.jit
    def ends(key):
        _, w, norm, _ = draws(key)
        return {"embed": w((V, D), D), "head": w((V, D), D), "ln_f": norm(D)}

    key = key_of(seed)
    return {**ends(jax.random.fold_in(key, z["L"])),
            "layers": [layer(jax.random.fold_in(key, l),
                             experts=l >= z["dense"])
                       for l in range(z["L"])]}


# ------------------------------------------------------- the plain reference


def _turn(a, b, ang):
    return (a * jnp.cos(ang) - b * jnp.sin(ang),
            b * jnp.cos(ang) + a * jnp.sin(ang))


def _angles(x, freqs, start):
    """x: [T, ..., d] at positions start ..: pos * f' shaped to x."""
    T = x.shape[0]
    pos = (start + jnp.arange(T)).astype(F32)
    return (pos[:, None] * freqs).reshape(
        (T,) + (1,) * (x.ndim - 2) + (freqs.shape[0],))


def _rope_pairs(x, freqs, start=0):
    """x: [T, ..., dr]: the lanes (2i, 2i + 1) turn together."""
    return jnp.stack(_turn(x[..., 0::2], x[..., 1::2],
                           _angles(x, freqs, start)), -1).reshape(x.shape)


def _rope_halves(x, freqs, start=0):
    """x: [T, ..., dI]: of its first dr lanes, lane i turns with lane i +
    dr/2; the others carry no position."""
    half = freqs.shape[0]
    return jnp.concatenate(
        _turn(x[..., :half], x[..., half:2 * half], _angles(x, freqs, start))
        + (x[..., 2 * half:],), -1)


def _cut(a, i):
    return jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK, Q_BLOCK)


def _group(w, g, size, axis):
    return jax.lax.dynamic_slice_in_dim(w, g * size, size, axis=axis)


def _runs(first: int, blocks: int) -> list[tuple[int, int]]:
    """The query blocks first .. blocks - 1 in at most RUNS runs of
    neighbours: a run's queries are held against the positions up to the
    run's own end and no further, since no query sees past itself.  (A run
    is a shape of its own to compile: a few blocks stay one run.)"""
    n = min(RUNS, -(-(blocks - first) // RUNS))
    edges = [first + (blocks - first) * j // n for j in range(n + 1)]
    return list(zip(edges[:-1], edges[1:]))


def _kept(index, seen, k):
    """Row by row, the k largest of `index` among `seen` (all of them where
    fewer are seen), ties to the earlier position: `lax.top_k`'s own set,
    as a mask and with no scatter.  What lies above the k-th value is in;
    of what equals it, the positions up to the last one `top_k` took
    (`top_k` puts the lower index of two equal values first)."""
    index = jnp.where(seen, index, -jnp.inf)
    best, where = jax.lax.top_k(index, min(k, index.shape[-1]))
    kth = best[:, -1:]
    last = jnp.where(best == kth, where, -1).max(-1, keepdims=True)
    at = jnp.arange(index.shape[-1])[None, :]
    return (index > kth) | ((index == kth) & (at <= last) & seen)


ATTENTION_PARTS = ("ln_in", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm",
                   "w_kvb", "wo", "w_qi", "w_ki", "ki_norm", "ki_bias", "w_w")


@partial(jax.jit, static_argnames=("z", "quant", "first", "picks"))
def _attention(x, lp, z, quant, first=0, picks=False):
    """(x + Attn(RMS_in(x))) of the query blocks `first` and after, every
    position of x being a key; with `picks` the picked sets of those rows
    too.  `lp`: the layer's ATTENTION_PARTS."""
    z = dict(z)
    T = x.shape[0]
    H, dn, rkv = z["H"], z["dn"], z["Rkv"]
    gh, gi = min(HEAD_GROUP, H), min(HEAD_GROUP, z["HI"])
    freqs, eps = yarn_inv_freq(z), z["eps"]
    h = _norm(x, lp["ln_in"], eps)
    ckr = _mm("td,dr->tr", h, lp["w_kva"], quant)
    c = _norm(ckr[:, :rkv], lp["kv_norm"], eps)
    kr = _rope_pairs(ckr[:, rkv:], freqs)  # [T, dr]: one key for all heads
    ki = _mm("td,dk->tk", h, lp["w_ki"], quant)
    mean = ki.mean(-1, keepdims=True)
    ki = _rope_halves(
        (ki - mean) * jax.lax.rsqrt(((ki - mean) ** 2).mean(-1, keepdims=True)
                                    + LN_EPS)
        * lp["ki_norm"].astype(F32) + lp["ki_bias"].astype(F32), freqs)
    start = first * Q_BLOCK  # the queries: rows start .. T - 1
    x, h = x[start:], h[start:]
    cq = _norm(_mm("td,dr->tr", h, lp["w_qa"], quant), lp["q_norm"], eps)
    w = _mm("td,dh->th", h, lp["w_w"], quant)
    runs = _runs(first, T // Q_BLOCK)

    def pick(i, keys):
        """Block i's rows of I over the first `keys` positions, a group of
        the indexer's heads at a time, and the K best of each row."""
        qi = _rope_halves(_mm("tr,rhk->thk", _cut(cq, i - first), lp["w_qi"],
                              quant), freqs, i * Q_BLOCK)
        wi = _cut(w, i - first)

        def heads(g, acc):
            s = jnp.einsum("qjd,td->qjt", _group(qi, g, gi, 1), ki[:keys],
                           precision=HI)
            return acc + jnp.einsum("qj,qjt->qt", _group(wi, g, gi, 1),
                                    jax.nn.relu(s), precision=HI)

        index = jax.lax.fori_loop(0, z["HI"] // gi, heads,
                                  jnp.zeros((Q_BLOCK, keys), F32))
        at = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        return _kept(index, jnp.arange(keys)[None, :] <= at, z["K"])

    masks = [jax.lax.map(partial(pick, keys=b * Q_BLOCK), jnp.arange(a, b))
             for a, b in runs]  # a run's: [blocks, Q_BLOCK, its keys]
    scale = score_scale(z)

    def heads(g, y):
        """A group of heads: their keys and values of every position, their
        rows of scores under the picks, their part of the output."""
        kv = _mm("tr,rhk->thk", c, _group(lp["w_kvb"], g, gh, 1), quant)
        q = _mm("tr,rhk->thk", cq, _group(lp["w_qb"], g, gh, 1), quant)
        kn, v = kv[..., :dn], kv[..., dn:]
        qn, qr = q[..., :dn], _rope_pairs(q[..., dn:], freqs, start)

        def rows(block, keys):
            i, mask = block
            s = (jnp.einsum("qhk,thk->hqt", _cut(qn, i), kn[:keys],
                            precision=HI)
                 + jnp.einsum("qhk,tk->hqt", _cut(qr, i), kr[:keys],
                              precision=HI)) * scale
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thk->qhk", p, v[:keys], precision=HI)

        attn = jnp.concatenate([
            jax.lax.map(partial(rows, keys=b * Q_BLOCK),
                        (jnp.arange(a - first, b - first), mask))
            for (a, b), mask in zip(runs, masks)]).reshape(qn.shape[:2] + (-1,))
        return y + _mm("thk,hkd->td", attn, _group(lp["wo"], g, gh, 0), quant)

    x = x + jax.lax.fori_loop(0, H // gh, heads, jnp.zeros_like(x))
    if picks:
        return x, jnp.concatenate([
            jnp.pad(mask, ((0, 0), (0, 0), (0, T - mask.shape[-1])))
            for mask in masks]).reshape(T - start, T)
    return x


def _swiglu_sliced(h, w, quant, width):
    """SwiGLU a slice of its width at a time (the dense layer's 18 432 lanes
    over 33 k rows would be 2.4 GB of float32 a product)."""
    n = -(-width // FF_SLICE)
    size = width // n if width % n == 0 else width
    n = width // size

    def part(g, y):
        gate = _mm("td,df->tf", h, _group(w["w_gate"], g, size, 1), quant)
        up = _mm("td,df->tf", h, _group(w["w_up"], g, size, 1), quant)
        return y + _mm("tf,fd->td", jax.nn.silu(gate) * up,
                       _group(w["w_down"], g, size, 0), quant)

    return jax.lax.fori_loop(0, n, part, jnp.zeros_like(h))


@partial(jax.jit, static_argnames=("z", "quant"))
def _feed_forward(x, lp, z, quant):
    """x + FF(RMS_post(x)): the dense layer, or the shared expert and the
    held experts' part of each token's picked sum."""
    z = dict(z)
    T = x.shape[0]
    h = _norm(x, lp["ln_post"], z["eps"])
    if "mlp" in lp:
        return x + _swiglu_sliced(h, lp["mlp"], quant, z["F"])
    s = jax.nn.sigmoid(_mm("td,de->te", h, lp["router"], quant))
    choose = (s + lp["route_bias"]).reshape(T, z["groups"], -1)
    _, groups = jax.lax.top_k(jax.lax.top_k(choose, 2)[0].sum(-1),
                              z["topk_group"])
    kept = jnp.zeros((T, z["groups"]), bool).at[
        jnp.arange(T)[:, None], groups].set(True)
    _, picked = jax.lax.top_k(
        jnp.where(kept[:, :, None], choose, -jnp.inf).reshape(T, -1), z["k"])
    w = s * jnp.zeros_like(s).at[jnp.arange(T)[:, None], picked].set(1.0)
    w = w / (w.sum(-1, keepdims=True) + ROUTE_NORM_EPS) * z["route_scale"]

    def add(e, y):  # the held expert e is the router's expert first + e
        one = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False), lp["experts"])
        col = jax.lax.dynamic_slice_in_dim(w, z["first"] + e, 1, axis=1)
        return y + col * _swiglu_sliced(h, one, quant, z["Fe"])

    shared = _swiglu_sliced(h, lp["shared"], quant, z["shared"] * z["Fe"])
    return x + jax.lax.fori_loop(0, z["held"], add, shared)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, ln_f, head, eps, quant):
    return _mm("td,vd->tv", _norm(x, ln_f, eps), head, quant)


def forward_logits(weights: dict, cfg: dict, tokens, n_last: int,
                   quant: str | None = None, picks: list | None = None):
    """Logits [n_last, V] of the last `n_last` positions of one sequence.
    The last layer is computed for the query blocks that hold those
    positions (every position is its key still), the layers before it for
    all.  `picks` (a list, the tests') is given each layer's picked sets,
    bool [T, T]: row t the positions query t attends over."""
    n = len(tokens)
    # causal: padding behind the end touches nothing before; to PAD_BLOCKS
    # blocks, so that a cell's prompts are few distinct shapes to compile
    pad = -n % (PAD_BLOCKS * Q_BLOCK)
    ids = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, pad))
    x = jnp.take(weights["embed"], ids, axis=0).astype(F32)
    z = tuple(sorted(sizes(cfg).items()))
    last = len(weights["layers"]) - 1
    first = 0
    for l, lp in enumerate(weights["layers"]):
        if l == last and picks is None:
            first = (n - n_last) // Q_BLOCK
        out = _attention(x, {k: lp[k] for k in ATTENTION_PARTS}, z, quant,
                         first=first, picks=picks is not None)
        if picks is not None:
            out, picked = out
            picks.append(picked[:n, :n])
        x = _feed_forward(out, lp, z, quant)
    end = n - first * Q_BLOCK
    return _head(x, weights["ln_f"], weights["head"],
                 float(cfg["rms_norm_eps"]), quant)[end - n_last:end]
