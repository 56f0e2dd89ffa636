"""The serve path's expert layer: the router, and the sum over each token's
picked experts without a capacity.  Shared by the families of two score
kinds: sigmoid scores an expert, with a selection bias (`models/afmoe.py`,
`models/lfm2moe.py`, `models/glm4moelite.py`, `models/nemotronh.py`; with a
limit to the best groups of experts, `models/deepseekv32.py`), and softmax
scores over all experts, with none (`models/keyevl2.py`).  (`models/moe.py` is the training
path's: softmax scores, a static capacity, tokens over it dropped.)

What differs between the families is an argument, and where an argument would
add an operation to a family's trace the branch is taken in Python, so that
each family's programs trace to what they were.  What does not differ is
decided here from a trace's shapes alone: whether a batched expert layer is
the einsum or the kernel that copies only the touched experts
(`decode_kernel_serves`, `ops/moe_decode_pallas.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.ops import moe_decode_pallas, paged_decode_pallas

HI = lax.Precision.HIGHEST
# An expert layer's prefill runs over at most this many tokens at a time.
MOE_CHUNK_TOKENS = 4096
# Where a batched expert layer is the kernel that copies the touched experts
# alone (ops/moe_decode_pallas.py): `decode_kernel_serves`.  Read on the chip,
# the kernel and the einsum alone, a layer at each expert cell's shapes, ms a
# layer where eight follow one another (hack/moe_decode_alone.py; my chip
# run, PR 54; PERF.md section 6):
#
#   rows held  D     F    einsum  kernel, every expert touched / some touched
#    32   16  7168  2048  1.975   1.983 / 1.041 at  8  (deepseekv32)
#    24  128  2048   768  1.719   1.711 / 1.339 at 99  (keyevl2)
#    64  128  2048  1024  2.436   2.264 / 2.036 at 114 (afmoe)
#    64   64  2048  1536  1.726   1.713 / 1.538 at 57  (glm4moelite)
#    64   32  2048  1792  1.042   1.060 / 0.590 at 16  (lfm2moe)
#   128   64  2688  1856  1.801   2.094 / 1.851 at 55  (nemotronh), and 2.4 ms
#         a call more: 1856 lanes are 14.5 tiles, and `w_up` is re-laid-out
#   256   16  7168  2048  2.473   2.125;   512 rows: 4.339 / 4.502
#
# So the kernel's time follows the experts touched (677-715 GB/s of them) and
# at every expert touched it is the einsum's within 2 % either way where the
# hidden width is whole lane tiles: it serves up to DECODE_KERNEL_MAX_TOKENS
# rows (read as far; at 512 the products hold it and nothing is skipped),
# whole lane tiles, and shapes that leave experts untouched: under even
# routing N rows touch 1 - (1 - k/E)^N of the experts (the share the
# benchmark prices a step by), 0.638 / 0.788 / 0.984 / 0.984 for the first
# four rows of the table, 0.9998 and 0.998 for the two where the kernel loses.
DECODE_KERNEL_MAX_TOKENS = 256
DECODE_KERNEL_MAX_SHARE = 0.99


def route(h, router, bias, top_k: int, norm: bool, scale: float,
          norm_eps: float | None = None, scores: str = "sigmoid",
          n_group: int = 1, topk_group: int = 1):
    """h: [N, D] float32 -> (experts picked [N, k], their weights [N, k]
    float32).  Scores in float32 at precision highest, ``sigmoid(h .
    router)`` an expert or, with ``scores="softmax"``, the softmax of
    ``h . router`` over all experts; the bias (None: the family has none)
    enters the selection only; with ``norm`` the picked scores are divided by
    their sum (plus ``norm_eps`` where a family's published code adds one),
    then multiplied by ``scale``.  With ``n_group`` > 1 the experts are that
    many groups of neighbouring ids, a group's score is the sum of its two
    largest ``s + bias``, and a token picks within its ``topk_group`` best
    groups only (ties to the lower group, as to the lower expert); with 1
    (every other family) the operations are what they were."""
    if scores not in ("sigmoid", "softmax"):
        raise ValueError(f"route: scores={scores!r} is not implemented")
    if n_group < 1 or router.shape[-1] % n_group or not (
            1 <= topk_group <= n_group):
        raise ValueError("route: groups divide the experts evenly, and a "
                         "token picks within 1 to n_group of them")
    s = jnp.dot(
        h.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=HI,
    )
    s = jax.nn.sigmoid(s) if scores == "sigmoid" else jax.nn.softmax(s, -1)
    choose = s if bias is None else s + bias
    if n_group > 1:
        grouped = choose.reshape(choose.shape[0], n_group, -1)
        best, _ = lax.top_k(grouped, 2)
        _, groups = lax.top_k(best.sum(-1), topk_group)
        kept = jnp.zeros((choose.shape[0], n_group), bool).at[
            jnp.arange(choose.shape[0])[:, None], groups].set(True)
        choose = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            choose.shape)
    _, picked = lax.top_k(choose, top_k)
    w = jnp.take_along_axis(s, picked, axis=1)
    if norm:
        total = jnp.sum(w, axis=1, keepdims=True)
        w = w / (total if norm_eps is None else total + norm_eps)
    return picked, w * scale


def _hidden(rows, experts, product):
    """An expert's hidden values, float32: ``silu(gate) * up`` where the
    family's experts have a gate matrix, ``relu(up)^2`` where they have none
    (two matrices an expert: models/nemotronh.py).  ``product(rows, w)`` is
    the form's product with a stack of expert matrices."""
    if "w_gate" not in experts:
        return jnp.square(jax.nn.relu(product(rows, experts["w_up"])))
    gate = product(rows, experts["w_gate"])
    up = product(rows, experts["w_up"])
    return jax.nn.silu(gate) * up


def touched_share(rows: int, top_k: int, n_experts: int) -> float:
    """The share of the experts that ``rows`` rows of ``top_k`` picks among
    ``n_experts`` touch under even routing: what the benchmark prices a
    step's expert weights by."""
    return 1 - (1 - top_k / n_experts) ** rows


def decode_kernel_serves(rows: int, top_k: int, n_experts: int, width: int,
                         interpret: bool) -> bool:
    """The one rule by which a batched expert layer is the kernel, from what
    a trace sees and nothing else: few rows, an expert's hidden ``width`` in
    whole lane tiles, picks that leave held experts untouched (the constants'
    comment has the readings), and a program compiled for the TPU or
    interpreted."""
    return (rows <= DECODE_KERNEL_MAX_TOKENS
            and width % moe_decode_pallas.LANES == 0
            and touched_share(rows, top_k, n_experts) <= (
                DECODE_KERNEL_MAX_SHARE)
            and paged_decode_pallas.serves(interpret))


def routed_experts(h, picked, w, experts, n_experts: int, batched: bool,
                   held: tuple | None = None, interpret: bool = False):
    """Sum over each token's picked experts of w_e Expert_e(h), without a
    capacity; h [N, D] in the serving type; returns ([N, D] float32, picks
    per expert).  Two ways, the caller's choice (each family's rule is read
    on the chip at its own sizes):

    - ``batched`` (a decode step's few tokens): every expert multiplies
      every token and the routing weights, zero for an expert not picked,
      mask the sum.  As one batched einsum its time does not depend on which
      experts a seed's router favours, and it reads every expert's weights:
      the plain form, and the one a hit's suffix keeps.  Where
      `decode_kernel_serves` says so (a decode step's rows at shapes that
      leave experts untouched, compiled for the TPU or ``interpret``-ed) the
      same sum is `moe_decode_pallas`: an expert a grid step, the touched
      ones first, so the weights of an expert no token picked are not
      copied and a step's time follows the experts its tokens touched;
    - else (a prefill): the N*k picks are sorted by expert and each expert
      multiplies its own rows (`lax.ragged_dot`).

    ``held=(first, count)``: this chip holds the experts ``first .. first +
    count - 1`` of the ``n_experts`` the router scored (``experts`` stacks
    those ``count``; the others lie on the chips that share the layer).  The
    sum is over the picks that fall in the range, the part of the layer's
    result that the held experts give: batched, the weight matrix has the
    held columns only; sorted, the picks outside sort behind the held
    experts' and are not multiplied.  The counts are then ``count + 1``: the
    picks of each held expert, and last the picks that fell outside.  No
    exchange and nothing that stands in for the other chips.  With ``held``
    None every expert is here and the operations are what they were."""
    N, k = picked.shape
    f32 = jnp.float32
    n_held = n_experts
    if held is not None:
        first, n_held = held
        inside = (picked >= first) & (picked < first + n_held)
        # an outside pick takes the id behind the last held expert
        picked = jnp.where(inside, picked - first, n_held)
        w = jnp.where(inside, w, 0.0)
    flat = picked.reshape(-1)
    sizes = jnp.bincount(
        flat, length=n_held + (held is not None)).astype(jnp.int32)
    if batched:
        weight = jnp.zeros((N, n_held), f32).at[
            jnp.arange(N)[:, None], picked].add(
                w, **({} if held is None else {"mode": "drop"}))
        if decode_kernel_serves(N, k, n_experts, experts["w_up"].shape[-1],
                                interpret):
            out = moe_decode_pallas.moe_decode_pallas(
                h, weight, experts,
                *moe_decode_pallas.touched_order(sizes[:n_held]), interpret)
            return out, sizes
        hidden = _hidden(h, experts, lambda x, m: jnp.einsum(
            "nd,edf->enf", x, m, preferred_element_type=f32)
        ) * weight.T[:, :, None]
        out = jnp.einsum("enf,efd->nd", hidden.astype(h.dtype),
                         experts["w_down"], preferred_element_type=f32)
        return out, sizes
    order = jnp.argsort(flat)
    rows = jnp.take(h, order // k, axis=0)  # [N*k, D], grouped by expert
    # with a share, the rows behind the held experts' belong to no group of
    # the products, and their weight is zero
    groups = sizes if held is None else sizes[:n_held]
    hidden = _hidden(rows, experts, lambda x, m: lax.ragged_dot(
        x, m, groups, preferred_element_type=f32)).astype(h.dtype)
    out = lax.ragged_dot(hidden, experts["w_down"], groups,
                         preferred_element_type=f32)
    if held is not None:
        out = jnp.where((jnp.arange(N * k) < N * k - sizes[n_held])[:, None],
                        out, 0.0)
    out = out * jnp.take(w.reshape(-1), order)[:, None]
    back = jnp.zeros_like(order).at[order].set(jnp.arange(N * k))
    return jnp.take(out, back, axis=0).reshape(N, k, -1).sum(axis=1), sizes


def in_chunks(h, chunk, limit: int = MOE_CHUNK_TOKENS):
    """``chunk`` ([n, D] float32 -> ([n, D] float32, picks per expert [E]))
    over h [B, T, D], at most ``limit`` rows at a time where they divide
    evenly: -> ([B * T, D], picks per expert summed)."""
    B, T, D = h.shape
    flat = h.reshape(B * T, D)
    n = -(-flat.shape[0] // limit)
    if flat.shape[0] % n:
        n = 1
    if n == 1:
        out, sizes = chunk(flat)
    else:
        out, sizes = lax.map(chunk, flat.reshape(n, -1, D))
        out, sizes = out.reshape(B * T, D), sizes.sum(axis=0)
    return out, sizes
