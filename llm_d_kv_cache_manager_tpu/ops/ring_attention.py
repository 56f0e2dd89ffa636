"""Ring attention: sequence-parallel causal attention over an ``sp``
mesh axis.

Long-context prefill shards the sequence across devices; each device
holds a contiguous chunk of Q/K/V.  K/V chunks rotate around the ring
via ``lax.ppermute`` (one ICI hop per step) while each device keeps an
online-softmax accumulator for its local queries — flash-attention
semantics distributed over the mesh, compute overlapping the permute.

This is the TPU-native answer to the reference's "long prompts stream
through chunked hashing" scope note (SURVEY §2.3): here long prompts
also *compute* in chunks, across chips.  Use under ``shard_map`` with
q/k/v sharded on the sequence axis, or via ``ring_attention`` which
wraps the shard_map given a mesh.

Performance note: contiguous chunking under causal masking is
load-imbalanced — device 0's queries are fully masked after one step
while the last device's stay visible every step.  ``striped=True``
selects the rebalanced layout (tokens interleave across devices via
``stripe``/``unstripe``; the causal mask becomes a near-uniform band
per step).  Two step bodies exist:

* ``impl="einsum"`` (the portable body; what ``"auto"`` picks off
  TPU): full Tq x Tk product + where() mask — balanced under striping
  but no FLOPs saved;
* ``impl="flash"``: each step runs the mask-aware Pallas partial
  (ops/ring_flash_pallas.py) whose K/V trip count stops at the causal
  diagonal, merged across steps by the flash-decoding combine.  With
  ``striped=True`` every step is a near-uniform causal band, so the
  layout's balance becomes ~half the per-step MXU work on every
  device.  Not measured on a chip: no cell of the benchmark enters
  this module (ROADMAP D8).

The model reaches both: ``forward(sp_mesh=..., ring_striped=True,
ring_impl="flash")`` runs the whole network in stripe order and
unstripes before the logits.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def resolve_auto_impl(
    mesh_platform: str,
    local_kv_tokens: int,
    head_dim: int,
    dtype_bytes: int,
    interpret: bool = False,
) -> str:
    """The step body ``impl="auto"`` resolves to, as a pure function.

    Flash is eligible only where the Pallas kernel will actually run
    (TPU mesh, or explicit interpret mode) AND the per-step K/V chunk
    fits the kernel's VMEM staging budget (``flash_pallas.fits_vmem``
    — the partial stages one kv-head's full local K and V chunk,
    ``2 * T_local * head_dim`` elements, in VMEM; past the budget the
    pallas_call fails to lower or silently spills).  Everything else
    falls back to the einsum body, which streams from HBM.  interpret
    mode is exempt from the bound: no real VMEM is allocated, and the
    flag is an explicit request to exercise the Pallas kernel.
    """
    from llm_d_kv_cache_manager_tpu.ops.flash_pallas import fits_vmem

    if interpret:
        return "flash"
    if mesh_platform != "tpu":
        return "einsum"
    if not fits_vmem(local_kv_tokens, head_dim, dtype_bytes):
        return "einsum"
    return "flash"


def _ring_driver(state, k, v, axis_name: str, accumulate):
    """Ring skeleton shared by both step bodies: K/V rotate around the
    ``axis_name`` ring via ppermute while ``accumulate(state, src,
    k_cur, v_cur)`` folds each chunk in; the last chunk accumulates
    outside the loop (no wasted final ppermute).  Keeping ONE driver
    means an overlap/permute change cannot silently apply to one body
    and not the other."""
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        state, k_cur, v_cur = carry
        src = (my_idx - i) % axis_size  # ring position k_cur came from
        state = accumulate(state, src, k_cur, v_cur)
        return (
            state,
            lax.ppermute(k_cur, axis_name, perm),
            lax.ppermute(v_cur, axis_name, perm),
        )

    state, k_last, v_last = lax.fori_loop(
        0, axis_size - 1, step, (state, k, v)
    )
    src_last = (my_idx - (axis_size - 1)) % axis_size
    return accumulate(state, src_last, k_last, v_last)


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    striped: bool = False,
) -> jnp.ndarray:
    """Per-device body. q/k/v: [B, T_local, H(kv), D]; causal over the
    global sequence.

    ``striped=False``: chunk i holds contiguous positions
    [i*T_local, (i+1)*T_local).  ``striped=True``: chunk i holds the
    interleaved stripe {t : t % R == i} in ascending order (see
    ``stripe``), so local row a on chunk c is global position a*R + c —
    the causal mask becomes the near-uniform band ``b <= a - (src >
    my_idx)`` and every device does almost equal work at every ring
    step (the contiguous layout leaves early chunks idle once their
    queries are past all rotated keys)."""
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    groups = H // Hkv
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32).reshape(B, Tq, Hkv, groups, D) * (D**-0.5)

    # Derive accumulators from qf so they carry shard_map's
    # varying-manual-axes type (a fresh jnp.zeros would not).
    o = jnp.zeros_like(qf)
    zero = jnp.zeros_like(qf[..., 0]).transpose(0, 2, 3, 1)  # [B,Hkv,g,Tq]
    m = zero + NEG_INF
    l = zero

    def accumulate(state, src, k_cur, v_cur):
        o, m, l = state

        scores = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qf, k_cur.astype(jnp.float32)
        )
        if striped:
            # Global positions: query a*R + my_idx vs key b*R + src;
            # b*R + src <= a*R + my_idx  <=>  b <= a - (src > my_idx).
            mask = jnp.arange(Tk)[None, :] <= (
                jnp.arange(Tq)[:, None]
                - (src > my_idx).astype(jnp.int32)
            )
        else:
            q_pos = my_idx * Tq + jnp.arange(Tq)[:, None]
            k_pos = src * Tk + jnp.arange(Tk)[None, :]
            mask = k_pos <= q_pos  # [Tq, Tk] causal, global positions
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)

        m_new = jnp.maximum(m, scores.max(axis=-1))
        # Keep exp() away from the -inf sentinel when a chunk is fully
        # masked (fresh accumulator, future chunk): scale becomes exp(0).
        m_safe = jnp.maximum(m_new, 0.5 * NEG_INF)
        scale = jnp.exp(jnp.maximum(m, 0.5 * NEG_INF) - m_safe)
        p = jnp.exp(scores - m_safe[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)

        l = l * scale + p.sum(axis=-1)
        o = o * scale.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
            "bhgqk,bkhd->bqhgd", p, v_cur.astype(jnp.float32)
        )
        return o, m_new, l

    o, m, l = _ring_driver((o, m, l), k, v, axis_name, accumulate)
    l = jnp.maximum(l, 1e-20)
    o = o / l.transpose(0, 3, 1, 2)[..., None]
    return o.reshape(B, Tq, H, D).astype(q.dtype)


def _ring_attention_local_flash(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    striped: bool = False,
    q_block: int = 256,
    kv_chunk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Mask-aware per-device body: each ring step runs the Pallas
    flash PARTIAL (ops/ring_flash_pallas.py) whose K/V trip count
    stops at the causal diagonal, so masked sub-tiles are never
    computed — where the einsum body spends a full Tq x Tk product
    per step and discards the masked half with where().

    Per-step work:

    * striped — every step is a near-uniform causal band (offset 0 or
      -1): every device does ~half the product at every step. This is
      where the striped layout's balance becomes FLOPs saved.
    * contiguous — steps are fully-visible (full product), diagonal
      (causal half), or fully-masked (skipped outright); per-step
      wall-clock is still set by the busiest device, which is why the
      striped layout is the one that converts balance into time.

    GQA note: the partial kernel indexes K/V heads by q_head //
    (H // Hkv), so q/k/v arrive exactly as _qkv produces them.
    """
    from llm_d_kv_cache_manager_tpu.ops.ring_flash_pallas import (
        flash_partial,
        merge_partials,
        neutral_partial,
        normalize_partial,
    )

    my_idx = lax.axis_index(axis_name)

    partial_kw = dict(
        q_block=q_block, kv_chunk=kv_chunk, interpret=interpret
    )

    def step_partial(src, k_cur, v_cur):
        operand = (q, k_cur, v_cur)
        if striped:
            # Keys from behind me in the ring sit one global position
            # later at equal local rows: offset -1.
            return lax.cond(
                src > my_idx,
                lambda a: flash_partial(
                    *a, causal_offset=-1, **partial_kw
                ),
                lambda a: flash_partial(
                    *a, causal_offset=0, **partial_kw
                ),
                operand,
            )
        # Contiguous: 0 = fully visible, 1 = diagonal, 2 = fully masked.
        case = (src >= my_idx).astype(jnp.int32) + (
            src > my_idx
        ).astype(jnp.int32)
        return lax.switch(
            case,
            [
                lambda a: flash_partial(
                    *a, causal_offset=None, **partial_kw
                ),
                lambda a: flash_partial(
                    *a, causal_offset=0, **partial_kw
                ),
                lambda a: neutral_partial(a[0]),
            ],
            operand,
        )

    def accumulate(state, src, k_cur, v_cur):
        return merge_partials(state, step_partial(src, k_cur, v_cur))

    acc, _, l = _ring_driver(
        neutral_partial(q), k, v, axis_name, accumulate
    )
    return normalize_partial(acc, l, q.dtype)


def stripe(x: jnp.ndarray, ring_size: int, axis: int = 1) -> jnp.ndarray:
    """Permute a sequence axis into the striped ring layout: global
    token t goes to chunk t % ring_size, slot t // ring_size — so that
    sharding the result contiguously over the ring gives each device an
    interleaved stripe.  Static permutation (trace-time indices)."""
    T = x.shape[axis]
    if T % ring_size:
        raise ValueError(f"sequence {T} not divisible by ring {ring_size}")
    idx = np.arange(T).reshape(T // ring_size, ring_size).T.reshape(-1)
    return jnp.take(x, jnp.asarray(idx), axis=axis)


def unstripe(x: jnp.ndarray, ring_size: int, axis: int = 1) -> jnp.ndarray:
    """Inverse of :func:`stripe` — which is itself a stripe with the
    complementary factor (the permutation t -> (t % R)*(T/R) + t//R is
    inverted by the same map with R' = T/R)."""
    T = x.shape[axis]
    if T % ring_size:
        raise ValueError(f"sequence {T} not divisible by ring {ring_size}")
    return stripe(x, T // ring_size, axis=axis)


def ring_attention_sharded(
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axis: Optional[str] = "dp",
    head_axis: Optional[str] = None,
    striped: bool = False,
    impl: str = "auto",
    interpret: bool = False,
):
    """The in-jit form: returns a callable ``(q, k, v) -> out`` over
    already-sharded [B, T, H(kv), D] arrays (T over ``axis_name``, B
    over ``batch_axis``).  Model code calls this inside its own jit —
    shard_map composes under jit; no device_put happens here.

    ``head_axis`` (e.g. ``"tp"``) shards the head dimension too — the
    tp×sp composition: each shard runs the ring over its own head
    slice (attention is head-independent; GQA group count is preserved
    since H and Hkv divide by the same degree).  Left None, heads are
    replicated over the mesh and tp-sharded inputs would be
    all-gathered per call.

    ``striped=True`` expects q/k/v already in the :func:`stripe` layout
    (and returns output in it — :func:`unstripe` after): the causal
    work balances across ring steps instead of concentrating on the
    last chunks.  RoPE/position embeddings must be applied BEFORE
    striping (or with striped position vectors) — positions are
    physical token indices, not stripe slots.

    ``impl``: ``"auto"`` picks ``"flash"`` on TPU and ``"einsum"``
    elsewhere; ``"einsum"`` is the portable full-product body;
    ``"flash"`` runs each step through the mask-aware Pallas partial
    (_ring_attention_local_flash) that skips masked sub-tiles — with
    ``striped=True`` this halves per-step MXU work.  ``interpret``
    runs the Pallas kernel in interpret mode (CPU tests)."""
    bspec = batch_axis if batch_axis else None
    spec = P(bspec, axis_name, head_axis, None)

    def build(resolved: str):
        check_vma = True
        if resolved == "flash":
            local = functools.partial(
                _ring_attention_local_flash,
                axis_name=axis_name,
                striped=striped,
                interpret=interpret,
            )
            # Pallas calls inside shard_map trip the vma checker (its
            # interpreter's internal slices don't pvary index
            # operands); JAX's own error message prescribes
            # check_vma=False.  Ring exactness is pinned by
            # tests/test_llama_model.py
            # (test_flash_ring_matches_dense_both_layouts) instead.
            check_vma = False
        elif resolved == "einsum":
            local = functools.partial(
                _ring_attention_local,
                axis_name=axis_name,
                striped=striped,
            )
        else:
            raise ValueError(f"unknown ring impl {resolved!r}")
        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=check_vma,
        )

    if impl != "auto":
        return build(impl)

    # "auto": the mask-aware Pallas body where the kernel will actually
    # run (the MESH's platform — a CPU debug mesh on a TPU host must
    # not dispatch pltpu onto CPU devices) AND where each device's K/V
    # chunk fits the kernel's VMEM staging budget (resolve_auto_impl;
    # the shape is only known at call/trace time, hence the dispatch
    # wrapper).  interpret=True is an explicit request to exercise the
    # Pallas kernel, so it forces flash — silently resolving to einsum
    # would drop the flag and fake the coverage the caller asked for.
    mesh_platform = next(iter(mesh.devices.flat)).platform
    ring = mesh.shape[axis_name]
    built = {}

    def dispatch(q, k, v):
        resolved = resolve_auto_impl(
            mesh_platform,
            local_kv_tokens=k.shape[1] // ring,
            head_dim=k.shape[-1],
            dtype_bytes=jnp.dtype(k.dtype).itemsize,
            interpret=interpret,
        )
        if resolved not in built:
            built[resolved] = build(resolved)
        return built[resolved](q, k, v)

    return dispatch


def ring_for_mesh(
    sp_mesh: Mesh,
    striped: bool = False,
    impl: str = "auto",
    interpret: bool = False,
):
    """Model-layer convenience: the sharded ring with the standard
    axis gating — batch rides ``dp`` and heads ride ``tp`` when those
    axes exist with degree > 1 (declaring tp-sharded heads replicated
    would all-gather them every layer).  One helper so every model
    family (llama, moe, ...) gates identically."""

    def axis_if_used(name):
        return (
            name
            if name in sp_mesh.axis_names and sp_mesh.shape[name] > 1
            else None
        )

    return ring_attention_sharded(
        sp_mesh,
        batch_axis=axis_if_used("dp"),
        head_axis=axis_if_used("tp"),
        striped=striped,
        impl=impl,
        interpret=interpret,
    )


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    batch_axis: Optional[str] = "dp",
    striped: bool = False,
    impl: str = "auto",
    interpret: bool = False,
) -> jnp.ndarray:
    """Eager convenience: place q/k/v ([B, T, H, D]; T sharded over
    ``axis_name``, B over ``batch_axis``) and run the ring.

    With ``striped=True`` the inputs/output are in PHYSICAL token order
    — this wrapper stripes them in, runs the balanced ring, and
    unstripes the output."""
    ring_size = mesh.shape[axis_name]
    if striped:
        q = stripe(q, ring_size)
        k = stripe(k, ring_size)
        v = stripe(v, ring_size)
    bspec = batch_axis if batch_axis else None
    spec = P(bspec, axis_name, None, None)
    fn = ring_attention_sharded(
        mesh,
        axis_name,
        batch_axis,
        striped=striped,
        impl=impl,
        interpret=interpret,
    )
    q = jax.device_put(q, NamedSharding(mesh, spec))
    k = jax.device_put(k, NamedSharding(mesh, spec))
    v = jax.device_put(v, NamedSharding(mesh, spec))
    out = fn(q, k, v)
    if striped:
        out = unstripe(out, ring_size)
    return out
