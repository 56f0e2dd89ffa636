"""Flagship model: Llama-family decoder, TPU-first.

The serving fleet in BASELINE.json runs Llama-3-8B on v5e; this module
is that model family in idiomatic JAX — pure-function params pytree,
``lax.scan`` over a stacked layer axis (one compiled layer body,
compiler-friendly control flow), bf16 matmuls with f32 softmax/norm
accumulation for the MXU, and PartitionSpecs over the canonical mesh
axes (parallel/mesh.py):

- params: layer axis over ``pp``, heads/ffn-hidden over ``tp``
- activations: batch over ``dp``, sequence over ``sp``
- serving KV state: the paged pool (models/kv_cache_pool.py), written
  by prefill and read at decode by the paged kernel
  (ops/paged_decode_pallas.py; off the TPU by ``paged_attention``) —
  the compute counterpart of the KV-block index the manager tracks fleet-wide.

Capabilities: dense forward (training / scoring), paged prefill +
decode (serving), ring-attention prefill for long context (ops/
ring_attention.py), and a full train step (optax AdamW) used by the
multi-chip dry run.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import scatter_kv_blocks
from llm_d_kv_cache_manager_tpu.ops.attention import causal_gqa_attention
from llm_d_kv_cache_manager_tpu.ops.flash_attention import flash_gqa_attention
from llm_d_kv_cache_manager_tpu.ops import flash_pallas
from llm_d_kv_cache_manager_tpu.ops import paged_decode_pallas
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import (
    paged_decode_attention_pallas,
)
from llm_d_kv_cache_manager_tpu.ops.paged_attention import paged_attention
from llm_d_kv_cache_manager_tpu.ops.ring_attention import (
    ring_for_mesh,
    stripe,
    unstripe,
)

Params = Dict[str, Any]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1408
    rope_theta: float = 500000.0
    block_size: int = 16  # paged-KV block, matches the index block size
    dtype: str = "bfloat16"
    # Key-axis length at/above which prefill attention switches from the
    # dense path to blockwise flash attention (O(tile) memory; the
    # long-context prefill path).  Static shapes make this a trace-time
    # choice.
    flash_attention_min_len: int = 1024
    # Decode attention over the paged pool.  "auto": the paged kernel
    # (ops/paged_decode_pallas.py) where the program is compiled for the
    # TPU or asked to be interpreted, the XLA gather elsewhere; the rule
    # models/afmoe.py has (ROADMAP D6, decided in PR 32 on the cell
    # internlm2-chat-sysprompt).  "gather" asks for the XLA gather
    # whatever the backend.  One caller needs it:
    # __graft_entry__._dryrun_tp_decode hands decode_step a pool sharded
    # over KV heads under plain jit, and a pallas_call is not partitioned
    # for it.  chip_smoke.py's decode phase uses it for the comparison.
    decode_attention: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_ff=14336,
        )


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    L, D, H, Hkv, Dh, F = (
        cfg.n_layers,
        cfg.d_model,
        cfg.n_heads,
        cfg.n_kv_heads,
        cfg.head_dim,
        cfg.d_ff,
    )
    keys = jax.random.split(rng, 8)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(
            dtype
        )

    return {
        "embed": norm_init(keys[0], (cfg.vocab_size, D), D),
        "layers": {
            "ln1": jnp.ones((L, D), dtype),
            "ln2": jnp.ones((L, D), dtype),
            "wq": norm_init(keys[1], (L, D, H, Dh), D),
            "wk": norm_init(keys[2], (L, D, Hkv, Dh), D),
            "wv": norm_init(keys[3], (L, D, Hkv, Dh), D),
            "wo": norm_init(keys[4], (L, H, Dh, D), H * Dh),
            "w_gate": norm_init(keys[5], (L, D, F), D),
            "w_up": norm_init(keys[6], (L, D, F), D),
            "w_down": norm_init(keys[7], (L, F, D), F),
        },
        "ln_f": jnp.ones((D,), dtype),
    }


def param_pspecs(cfg: LlamaConfig) -> Params:
    """PartitionSpec pytree matching init_params (axes: parallel/mesh)."""
    return {
        "embed": P(None, "tp"),
        "layers": {
            "ln1": P("pp", None),
            "ln2": P("pp", None),
            "wq": P("pp", None, "tp", None),
            "wk": P("pp", None, "tp", None),
            "wv": P("pp", None, "tp", None),
            "wo": P("pp", "tp", None, None),
            "w_gate": P("pp", None, "tp"),
            "w_up": P("pp", None, "tp"),
            "w_down": P("pp", "tp", None),
        },
        "ln_f": P(None),
    }


def _rms_norm(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (norm * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, T, H, D] (D even); positions: [B, T]."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
    ).astype(x.dtype)


def _mlp(x: jnp.ndarray, lp: Params) -> jnp.ndarray:
    gate = jnp.einsum("btd,df->btf", x, lp["w_gate"])
    up = jnp.einsum("btd,df->btf", x, lp["w_up"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("btf,fd->btd", hidden, lp["w_down"])


def _qkv(x: jnp.ndarray, lp: Params, positions: jnp.ndarray, theta: float):
    q = jnp.einsum("btd,dhk->bthk", x, lp["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, lp["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, lp["wv"])
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _logits(x: jnp.ndarray, params: Params) -> jnp.ndarray:
    """Shared epilogue: final norm + tied-embedding head, f32 logits for
    a stable softmax/loss."""
    x = _rms_norm(x, params["ln_f"])
    return jnp.einsum(
        "...d,vd->...v",
        x.astype(jnp.float32),
        params["embed"].astype(jnp.float32),
    )


def _prefill_attention(
    q, k, v, cfg: LlamaConfig, q_offset=0, use_flash=True, interpret=False
):
    """Dense under ``flash_attention_min_len`` keys, blockwise flash at
    or past it (static shapes make the switch a trace-time decision).

    Flash routing: on the TPU (or ``interpret=True``, the CPU tests of
    the TPU routing: ``paged_decode_pallas.serves``, one rule for the
    kernels of both steps) with a static ``q_offset`` and K/V within
    the kernel's VMEM bound, the Pallas kernel; beyond the bound (e.g.
    32k+ prompts), with a traced offset or off the TPU, the scan op
    streams K/V from HBM at any length.  The compile test and
    chip_smoke.py assert which one a lowered program holds.
    ``use_flash=False`` forces dense: neither flash op has a custom
    VJP, so under ``grad`` they keep the same O(Tq*Tk) residuals as
    dense while serializing the backward chunk-by-chunk — training
    paths should differentiate through the fused dense einsum instead.
    """
    if use_flash and k.shape[1] >= cfg.flash_attention_min_len:
        if (
            isinstance(q_offset, int)
            and paged_decode_pallas.serves(interpret)
            and flash_pallas.fits_vmem(
                k.shape[1], k.shape[-1], jnp.dtype(k.dtype).itemsize
            )
        ):
            return flash_pallas.flash_gqa_attention_pallas(
                q, k, v, q_offset=q_offset, interpret=interpret
            )
        return flash_gqa_attention(q, k, v, q_offset=q_offset)
    return causal_gqa_attention(q, k, v, q_offset=q_offset)


def forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    positions: Optional[jnp.ndarray] = None,
    use_flash: bool = True,
    sp_mesh=None,
    ring_striped: bool = False,
    ring_impl: str = "auto",
    ring_interpret: bool = False,
) -> jnp.ndarray:
    """Dense forward: tokens [B, T] -> logits [B, T, V].

    ``sp_mesh``: a Mesh with an ``sp`` axis routes attention through
    ring attention (ops/ring_attention.py) — the long-context prefill
    path: activations stay sequence-sharded over ``sp``, K/V chunks
    rotate over ICI, and only attention crosses devices.  Inference
    path (no custom VJP; train through the dense/flash route).  The
    ring's causal mask derives from each chunk's ring position, i.e.
    global positions 0..T-1 — custom ``positions`` are rejected rather
    than silently mismasked.

    ``ring_striped``: run the whole network in the striped (token-
    interleaved) sequence layout — tokens AND positions are striped at
    entry, every layer computes in stripe order (norms/MLP/logits are
    position-independent; RoPE gets the striped physical positions),
    attention runs the balanced striped ring, and the logits are
    unstriped at exit, so the returned contract is unchanged.
    ``ring_impl`` defaults to ``"auto"`` (the flash body on TPU, the
    portable einsum body elsewhere); ``"flash"`` forces the mask-aware
    Pallas partial that skips masked sub-tiles — with ``ring_striped``
    it halves per-step MXU work (ops/ring_flash_pallas.py).
    """
    B, T = tokens.shape
    if sp_mesh is not None and positions is not None:
        raise ValueError(
            "sp_mesh ring attention assumes default positions 0..T-1 "
            "(its causal mask is derived from ring chunk indices); "
            "custom positions would be RoPE-rotated but mis-masked"
        )
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    ring = None
    striped = False
    if sp_mesh is not None:
        striped = ring_striped and sp_mesh.shape["sp"] > 1
        if striped:
            ring_size = sp_mesh.shape["sp"]
            tokens = stripe(tokens, ring_size)
            # Positions stay PHYSICAL (RoPE rotates by true token
            # index); only their order is striped to match the tokens.
            positions = stripe(positions, ring_size)
        ring = ring_for_mesh(
            sp_mesh,
            striped=striped,
            impl=ring_impl,
            interpret=ring_interpret,
        )
    x = jnp.take(params["embed"], tokens, axis=0)

    def layer(x, lp):
        h = _rms_norm(x, lp["ln1"])
        q, k, v = _qkv(h, lp, positions, cfg.rope_theta)
        if ring is not None:
            attn = ring(q, k, v)
        else:
            attn = _prefill_attention(q, k, v, cfg, use_flash=use_flash)
        x = x + jnp.einsum("bthk,hkd->btd", attn, lp["wo"])
        x = x + _mlp(_rms_norm(x, lp["ln2"]), lp)
        return x, None

    x, _ = lax.scan(layer, x, params["layers"])
    if striped:
        x = unstripe(x, sp_mesh.shape["sp"])
    return _logits(x, params)


def _scan_layers(layer, x, params: Params, kv_pool: jnp.ndarray):
    """The layer scan of every paged program: ONE rolled layer body,
    with the KV pool as state that each layer updates in place.

    The pool is carried, never the scan's xs -> ys: handed in as xs the
    scan slices one layer out, the body's update makes a copy of it and
    the ys write it back — three passes over the whole pool a call,
    whatever the call touches.  Carried, the body writes only the slots
    it names and reads only the blocks it gathers, and where the caller
    donates the pool (``jax.jit(..., donate_argnums=(2,))``) the
    returned pool IS the argument's buffer; a caller that does not
    donate gets the same values through one copy the compiler makes.

    No layer of the pool is sliced out either (one slice a layer is one
    pool a call again): the pool is addressed as ``L * N`` slots, layer
    ``l``'s block ``b`` at ``l * N + b`` (merging the two leading axes
    moves nothing), and ``layer(x, slots, lp, base)`` adds
    ``base = l * N`` to the block ids and tables it uses, so
    ``scatter_kv_blocks``, ``paged_attention`` and the Pallas decode
    kernel take the merged pool as they take one layer's.  Returns
    (x, kv_pool in its own shape)."""
    L, N = kv_pool.shape[:2]

    def body(carry, inputs):
        lp, l = inputs
        return layer(*carry, lp, l * N), None

    (x, slots), _ = lax.scan(
        body,
        (x, kv_pool.reshape((L * N,) + kv_pool.shape[2:])),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)),
    )
    return x, slots.reshape(kv_pool.shape)


def prefill_paged(
    params: Params,
    tokens: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    cfg: LlamaConfig,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill writing per-layer K/V into the paged pool.

    tokens: [B, T] with T % block_size == 0 (pad; padding blocks may be
    overwritten — give padded sequences scratch block ids).
    kv_pool: [L, num_blocks, 2, block_size, Hkv, Dh] (KVCachePool.kv).
    block_table: [B, T/block_size] pool block ids for each sequence.
    ``interpret``: Pallas kernels in interpret mode (CPU tests).
    Returns (logits [B, T, V], new kv_pool).  The pool is carried
    through the layers and only the table's blocks are written
    (``_scan_layers``); donate it and the write is in place.
    """
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    x = jnp.take(params["embed"], tokens, axis=0)

    def layer(x, slots, lp, base):
        h = _rms_norm(x, lp["ln1"])
        q, k, v = _qkv(h, lp, positions, cfg.rope_theta)
        attn = _prefill_attention(q, k, v, cfg, interpret=interpret)
        x = x + jnp.einsum("bthk,hkd->btd", attn, lp["wo"])
        x = x + _mlp(_rms_norm(x, lp["ln2"]), lp)
        return x, scatter_kv_blocks(
            slots, k, v, base + block_table, cfg.block_size
        )

    x, kv_pool = _scan_layers(layer, x, params, kv_pool)
    return _logits(x, params), kv_pool


def _gathered_prefix_attention(q, k, v, slots, prefix_ids, cfg, interpret):
    """A continuation's attention over a copy of its prefix: the table's
    blocks gathered from the pool ([B, npre, 2, block, Hkv, Dh]), laid out
    as positions and joined with the suffix's own k, v.  The path of a
    prefix under the dense bound, of every hit off the TPU, and of slots
    the kernel's continuation entry has no room for
    (``flash_pallas.fits_paged``: heads of another size than 128, many KV
    heads, or a block that divides no step)."""
    B, npre = prefix_ids.shape
    prefix_len = npre * cfg.block_size
    pre = jnp.take(slots, prefix_ids, axis=0)
    pre = pre.transpose(0, 2, 1, 3, 4, 5).reshape(
        B, 2, prefix_len, k.shape[-2], k.shape[-1]
    )
    k_full = jnp.concatenate(
        (pre[:, 0].astype(k.dtype), k), axis=1
    )  # [B, prefix+Ts, Hkv, Dh]
    v_full = jnp.concatenate((pre[:, 1].astype(v.dtype), v), axis=1)
    return _prefill_attention(
        q, k_full, v_full, cfg, q_offset=prefix_len, interpret=interpret
    )


def prefill_continue(
    params: Params,
    tokens: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    prefix_len: int,
    cfg: LlamaConfig,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Prefill only the uncached suffix of a prompt (prefix-cache hit).

    The first ``prefix_len`` tokens' K/V already live in the pool (a
    prior request stored them, or the offload connector loaded them);
    this computes the suffix in one pass attending over the prefix's
    K/V and its own, and scatters the suffix blocks back.  At serving
    lengths on the TPU the flash kernel's continuation entry reads the
    table's blocks from the pool where they lie
    (``flash_pallas.flash_gqa_attention_pallas_paged``: no copy of the
    prefix is made); under ``flash_attention_min_len`` keys, off the
    TPU, or where the entry has no room for the pool's slots, the prefix
    is gathered (``_gathered_prefix_attention``).
    This is what turns an index hit into real TTFT savings — the
    compute analogue of vLLM's prefix-cache hit that the reference
    routes toward (SURVEY.md §6 north star).

    tokens: [B, Ts] suffix tokens, Ts % block_size == 0.
    block_table: [B, (prefix_len + Ts) / block_size] — prefix blocks
    first, then the blocks to write.  ``prefix_len`` is static
    (% block_size == 0); one compile per distinct padded prefix length.
    ``interpret``: Pallas kernels in interpret mode (CPU tests).
    Returns (suffix logits [B, Ts, V], new kv_pool).  The pool is
    carried through the layers: each reads its prefix blocks from it
    and writes only its suffix blocks (``_scan_layers``); donate it and
    the write is in place.
    """
    B, Ts = tokens.shape
    if prefix_len % cfg.block_size or Ts % cfg.block_size:
        raise ValueError("prefix_len and Ts must be block_size multiples")
    npre = prefix_len // cfg.block_size
    nsuf = Ts // cfg.block_size
    positions = jnp.broadcast_to(
        prefix_len + jnp.arange(Ts), (B, Ts)
    )
    x = jnp.take(params["embed"], tokens, axis=0)
    prefix_ids = block_table[:, :npre]  # [B, npre]
    suffix_ids = block_table[:, npre : npre + nsuf]
    # At or past the dense bound, where a kernel serves and has room for
    # these slots, the flash kernel's continuation entry reads the
    # table's blocks where the pool holds them; elsewhere the prefix is
    # gathered, for the dense product, the scan op or the flash kernel
    # over resident K/V (``_prefill_attention``'s rule).
    paged = (
        npre > 0
        and prefix_len + Ts >= cfg.flash_attention_min_len
        and paged_decode_pallas.serves(interpret)
        and flash_pallas.fits_paged(
            cfg.block_size, cfg.n_kv_heads, cfg.head_dim, cfg.n_heads,
            jnp.dtype(kv_pool.dtype).itemsize,
        )
    )

    def layer(x, slots, lp, base):
        h = _rms_norm(x, lp["ln1"])
        q, k, v = _qkv(h, lp, positions, cfg.rope_theta)
        if paged:
            # The suffix's own K/V first: the kernel then finds them in
            # the pool like the prefix's, behind one table.
            slots = scatter_kv_blocks(
                slots, k, v, base + suffix_ids, cfg.block_size
            )
            attn = flash_pallas.flash_gqa_attention_pallas_paged(
                q, slots, base + block_table[:, : npre + nsuf],
                q_offset=prefix_len, interpret=interpret,
            )
        else:
            attn = _gathered_prefix_attention(
                q, k, v, slots, base + prefix_ids, cfg, interpret
            )
        x = x + jnp.einsum("bthk,hkd->btd", attn, lp["wo"])
        x = x + _mlp(_rms_norm(x, lp["ln2"]), lp)
        if not paged:
            slots = scatter_kv_blocks(
                slots, k, v, base + suffix_ids, cfg.block_size
            )
        return x, slots

    x, kv_pool = _scan_layers(layer, x, params, kv_pool)
    return _logits(x, params), kv_pool


def prefill_chunked(
    params: Params,
    tokens: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    cfg: LlamaConfig,
    chunk_tokens: int = 2048,
    seq_len: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bounded-memory long-prompt prefill (vLLM's chunked prefill in
    the paged-pool design): the prompt is processed in fixed-size
    chunks, each writing its K/V blocks into the pool FIRST and then
    attending over everything written so far through the blockwise
    flash op — whose dynamic ``q_offset`` makes this ONE compiled
    chunk step regardless of prompt length, with runtime-skipped
    masked chunks.  Network activations are O(chunk) instead of O(T);
    the per-layer K/V gather still materializes the O(T) context
    (like ``_gathered_prefix_attention``) — what this bounds is the
    activation side, not the KV read.

    tokens: [B, T] with T % chunk_tokens == 0 and chunk_tokens %
    block_size == 0; block_table: [B, T / block_size].  ``seq_len``
    ([B], defaults to T everywhere): each sequence's TRUE length —
    prompts are padded up to a chunk multiple, and the returned
    logits are taken at position ``seq_len-1``, never at a pad
    position (pad tokens still run and write scratch blocks, but
    causality keeps them invisible to real positions).
    Returns (true-last-position logits [B, V], new kv_pool) — the
    serving contract (the next sampled token); intermediate
    positions' logits are not materialized.  The pool is carried
    through the chunks and, inside each, through the layers
    (``_scan_layers``): a chunk writes its own blocks and gathers the
    table's; donate the pool and the writes are in place.
    """
    B, T = tokens.shape
    C = chunk_tokens
    if T % C or C % cfg.block_size:
        raise ValueError(
            "chunk_tokens must divide T, and block_size must divide "
            f"chunk_tokens (T={T}, chunk={C}, block={cfg.block_size})"
        )
    n_chunks = T // C
    blocks_per_chunk = C // cfg.block_size
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    if seq_len is None:
        seq_len = jnp.full((B,), T, jnp.int32)
    # Clamped into range: an out-of-range length (caller forgot the
    # pad, off-by-one) must select a real position — otherwise no
    # chunk ever matches and the serving logits would silently come
    # from the zero-initialized carry.
    last_pos = jnp.clip(seq_len - 1, 0, T - 1)  # [B]

    def chunk_step(carry, i):
        kv_pool, last_h = carry
        start = i * C
        tok = lax.dynamic_slice_in_dim(tokens, start, C, axis=1)
        positions = jnp.broadcast_to(jnp.arange(C), (B, C)) + start
        x = jnp.take(params["embed"], tok, axis=0)
        chunk_ids = lax.dynamic_slice_in_dim(
            block_table, i * blocks_per_chunk, blocks_per_chunk, axis=1
        )

        def layer(x, slots, lp, base):
            h = _rms_norm(x, lp["ln1"])
            q, k, v = _qkv(h, lp, positions, cfg.rope_theta)
            # Scatter this chunk's K/V first: its keys then live in
            # the pool like every earlier chunk's, and ONE gathered
            # read serves the whole causal context.
            slots = scatter_kv_blocks(
                slots, k, v, base + chunk_ids, cfg.block_size
            )
            full = jnp.take(slots, base + block_table, axis=0)
            # [B, nb, 2, bs, Hkv, Dh] -> [B, T, Hkv, Dh] per half.
            k_full = full[:, :, 0].reshape(B, T, Hkv, Dh).astype(
                k.dtype
            )
            v_full = full[:, :, 1].reshape(B, T, Hkv, Dh).astype(
                v.dtype
            )
            # Causal mask with the chunk's dynamic offset hides every
            # pool position beyond the chunk's last token, including
            # blocks not written yet.
            attn = flash_gqa_attention(
                q, k_full, v_full, q_offset=start
            )
            x = x + jnp.einsum("bthk,hkd->btd", attn, lp["wo"])
            x = x + _mlp(_rms_norm(x, lp["ln2"]), lp)
            return x, slots

        x, kv_pool = _scan_layers(layer, x, params, kv_pool)
        # Pick each sequence's TRUE last hidden state when it falls in
        # this chunk (ragged lengths: pad positions must never produce
        # the serving logits).  Hidden state only — projecting every
        # chunk to [B, V] would run n_chunks vocab matmuls for
        # discarded outputs.
        in_chunk = last_pos // C == i  # [B]
        offset = jnp.clip(last_pos - start, 0, C - 1)
        picked = jnp.take_along_axis(
            x, offset[:, None, None].repeat(x.shape[-1], 2), axis=1
        )[:, 0]
        last_h = jnp.where(in_chunk[:, None], picked, last_h)
        return (kv_pool, last_h), None

    (kv_pool, last_h), _ = lax.scan(
        chunk_step,
        (kv_pool, jnp.zeros((B, cfg.d_model), jnp.dtype(cfg.dtype))),
        jnp.arange(n_chunks),
    )
    return _logits(last_h, params), kv_pool


def decode_step(
    params: Params,
    tokens: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    context_len: jnp.ndarray,
    cfg: LlamaConfig,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decode step over the paged pool.

    tokens: [B] current token ids; context_len: [B] length *including*
    the current token; block_table: [B, max_blocks].  Writes the new
    token's K/V into the pool slot, attends over the table, and returns
    (logits [B, V], new kv_pool).  ``interpret``: the Pallas decode
    kernel in interpret mode (CPU tests).  The pool is carried through
    the layers: each writes its one slot a sequence and attends through
    the table over the merged pool (``_scan_layers``); donate it and
    the write is in place.
    """
    if cfg.decode_attention not in ("auto", "gather"):
        raise ValueError(f"decode_attention: {cfg.decode_attention!r}")
    # The kernel reads each live block once where it lies; the gather
    # copies every table column, widened to float32 (LlamaConfig).
    use_kernel = cfg.decode_attention == "auto" and (
        paged_decode_pallas.serves(interpret)
    )
    B = tokens.shape[0]
    pos = context_len - 1  # [B]
    x = jnp.take(params["embed"], tokens, axis=0)  # [B, D]
    block_idx = pos // cfg.block_size
    slot = pos % cfg.block_size
    block_ids = jnp.take_along_axis(
        block_table, block_idx[:, None], axis=1
    )[:, 0]
    # Which sequences' tables begin with the same blocks, once for all
    # layers: each sees this table, shifted.
    plan = None
    if use_kernel:
        plan = paged_decode_pallas.shared_prefix_plan(
            block_table, context_len, block_size=cfg.block_size,
            # a layer's pool: `_scan_layers` merges the two leading axes
            blocks_per_wave=paged_decode_pallas.walk_wave(jax.ShapeDtypeStruct(
                kv_pool.shape[1:], kv_pool.dtype)),
        )

    def layer(x, slots, lp, base):
        h = _rms_norm(x, lp["ln1"])
        h3 = h[:, None]  # [B, 1, D]
        q, k, v = _qkv(h3, lp, pos[:, None], cfg.rope_theta)
        kv_new = jnp.stack((k[:, 0], v[:, 0]), axis=1)  # [B, 2, Hkv, Dh]
        slots = slots.at[base + block_ids, :, slot].set(
            kv_new.astype(slots.dtype)
        )
        table = base + block_table
        if use_kernel:
            attn = paged_decode_attention_pallas(
                q[:, 0], slots, table, context_len, interpret=interpret,
                plan=plan,
            )
        else:
            attn = paged_attention(q[:, 0], slots, table, context_len)
        x = x + jnp.einsum("bhk,hkd->bd", attn, lp["wo"])
        h2 = _rms_norm(x, lp["ln2"])[:, None]
        x = x + _mlp(h2, lp)[:, 0]
        return x, slots

    x, kv_pool = _scan_layers(layer, x, params, kv_pool)
    return _logits(x, params), kv_pool


# ---------------------------------------------------------------- training


def next_token_nll(
    logits: jnp.ndarray, tokens: jnp.ndarray
) -> jnp.ndarray:
    """Mean next-token cross entropy from full-length [B, T, V] logits.

    Shift-and-mask, not slice: ``tokens[:, :-1]`` inside jit makes an
    unevenly-sharded [B, T-1] intermediate when T is sharded over
    ``sp`` — XLA pads the short shard and the padded lanes' softmax
    backward emits NaN into the target-token embedding row (seen on
    sp x tp / sp x pp meshes).  Keeping every shape [B, T] and masking
    the final position avoids that; shared by the llama and MoE losses
    so the sharding-sensitive masking lives in one place.
    """
    B, T = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (jnp.arange(T) < T - 1).astype(nll.dtype)
    return (nll * mask).sum() / (B * (T - 1))


def loss_fn(
    params: Params, tokens: jnp.ndarray, cfg: LlamaConfig
) -> jnp.ndarray:
    """Next-token cross entropy over tokens [B, T] — identical to the
    sliced form (causality: logits for positions < T-1 cannot see token
    T-1), in the sharding-safe shape (see next_token_nll)."""
    logits = forward(params, tokens, cfg, use_flash=False)
    return next_token_nll(logits, tokens)


def make_optimizer(lr: float = 3e-4) -> optax.GradientTransformation:
    return optax.adamw(lr, b1=0.9, b2=0.95, weight_decay=0.1)


def train_step(
    params: Params,
    opt_state: Any,
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    optimizer: optax.GradientTransformation,
) -> Tuple[Params, Any, jnp.ndarray]:
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss
