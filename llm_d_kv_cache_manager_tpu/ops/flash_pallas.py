"""Pallas TPU flash-attention prefill kernel.

The scan-based op (ops/flash_attention.py) expresses flash attention as
XLA loops; this kernel owns the schedule instead: one grid program per
(batch, head, q-tile) computes its output tile with an online-softmax
``fori_loop`` over K/V chunks resident in VMEM, f32 accumulators in
VMEM scratch, every tile contraction on the MXU
(``preferred_element_type=f32``), and the causal upper triangle never
read — the loop's trip count stops at the tile's last visible chunk
(q_offset + (qi+1)*q_block), so continuation suffixes (short q over a
long cached prefix) do only the work the mask allows.  With a static
``window`` the loop also starts at the band's first chunk.

Layout: TPU block specs need the tiled axes last, so the wrapper runs
in [B, H, T, D] (transposing at the boundary; XLA fuses these into the
surrounding ops).  Grid order puts q-tiles innermost so the same
head's K/V block stays resident in VMEM across its q-tiles.

Same contract as ``flash_gqa_attention`` for static ``q_offset``;
equivalence is pinned by tests/test_flash_attention.py (interpret mode
on CPU, compiled on TPU).  The model routes long-sequence inference
here on TPU and falls back to the scan op elsewhere
(models/llama.py::_prefill_attention).

``flash_gqa_attention_pallas_paged`` is the entry of a continuation
over a cached prefix (models/llama.py::prefill_continue): the same
online softmax (``_online_softmax_update``), with K/V neither gathered
nor transposed beforehand.  The block table rides in as a
scalar-prefetch operand and the kernel copies the table's blocks out of
the pool itself, as ops/paged_decode_pallas.py's shared pass does.  A
slot [2, bs, Hkv, D] holds a position's KV heads as neighbouring rows,
so one KV head's keys are every Hkv-th row of it: they are read with
that stride from a float32 copy of the step's blocks in VMEM (the
products' operands are float32 here as in ``_flash_kernel``), each KV
head's against its own group's query rows alone (rows of every head
against every query head with the others masked, the decode kernel's
form, is Hkv times the products: free in a bandwidth-bound decode, not
in a prefill).  All heads of a query tile keep their softmax state in
VMEM while the blocks stream past, so K/V cross HBM once a tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# The kernel stages one kv-head's full K and V in VMEM (~16 MB/core,
# shared with q/out tiles, f32 scratch, and pipeline double-buffering).
# Above this K+V footprint, callers should use the scan-based op, which
# streams K/V from HBM at any length.
VMEM_KV_BUDGET_BYTES = 8 * 1024 * 1024


def fits_vmem(kv_seq_len: int, head_dim: int, dtype_bytes: int = 2) -> bool:
    """True if a [kv_seq_len, head_dim] K+V pair fits the kernel's
    VMEM staging budget."""
    return 2 * kv_seq_len * head_dim * dtype_bytes <= VMEM_KV_BUDGET_BYTES


def _online_softmax_update(q, k, v, hide, acc_ref, m_ref, l_ref):
    """One step of the online softmax, shared by the kernels of this file:
    the scores of q [rows, D] (float32, scaled) against the chunk's keys k
    [chunk, D], ``hide(scores)`` putting what a row does not see at NEG_INF
    (None: the rows see the whole chunk), then the running maximum m, sum l
    (both lane-replicated [rows, 128]) and weighted values acc [rows, D],
    all float32, updated in place."""
    s = jax.lax.dot_general(
        q,
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, chunk]
    if hide is not None:
        s = hide(s)

    m_prev = m_ref[:, :1]  # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # masked entries underflow to 0
    correction = jnp.exp(m_prev - m_new)  # [rows, 1]

    l_ref[...] = l_ref[...] * correction + jnp.sum(
        p, axis=1, keepdims=True
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        p,
        v.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _flash_kernel(
    q_ref,  # [1, 1, q_block, D]
    k_ref,  # [1, 1, Tk_pad, D]
    v_ref,  # [1, 1, Tk_pad, D]
    out_ref,  # [1, 1, q_block, D]
    acc_ref,  # VMEM [q_block, D] f32
    m_ref,  # VMEM [q_block, 128] f32 (lane-replicated row max)
    l_ref,  # VMEM [q_block, 128] f32 (lane-replicated row sum)
    *,
    q_offset: int,
    kv_len: int,
    q_block: int,
    kv_chunk: int,
    scale: float,
    window: int | None,
):
    qi = pl.program_id(2)
    q_start = q_offset + qi * q_block  # absolute position of q row 0

    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # [q_block, D]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # Last chunk any row of this tile may see (causal): position
    # q_start + q_block - 1, clamped to the real kv length.
    last = jnp.minimum(q_start + q_block, kv_len)
    n_chunks = pl.cdiv(last, kv_chunk)

    row = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_chunk), 1)

    def chunk_body(ci, _):
        k_start = ci * kv_chunk
        k = k_ref[0, 0, pl.ds(k_start, kv_chunk), :]  # [kv_chunk, D]
        v = v_ref[0, 0, pl.ds(k_start, kv_chunk), :]

        def hide(s):
            q_pos = q_start + row
            k_pos = k_start + col
            mask = (k_pos <= q_pos) & (k_pos < kv_len)
            if window is not None:
                mask &= k_pos > q_pos - window
            return jnp.where(mask, s, NEG_INF)

        _online_softmax_update(q, k, v, hide, acc_ref, m_ref, l_ref)
        return 0

    if window is None:
        jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
    else:
        # The band's first chunk: the tile's first row sees nothing
        # before q_start - window + 1.  A later row may find a whole
        # chunk masked before its own band begins; what that adds
        # (exp(0) per column) is wiped by ``correction`` = 0 at the
        # row's first visible chunk, which every row has (its diagonal).
        first = jnp.maximum(q_start - (window - 1), 0) // kv_chunk
        jax.lax.fori_loop(first, n_chunks, chunk_body, 0)

    l = l_ref[:, :1]
    out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)  # pad rows: 0 not NaN
    out_ref[0, 0, :, :] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("q_offset", "q_block", "kv_chunk", "interpret", "window"),
)
def flash_gqa_attention_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    q_offset: int = 0,
    q_block: int = 256,
    kv_chunk: int = 512,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Causal GQA flash attention.  q: [B, Tq, H, D]; k/v:
    [B, Tk, Hkv, D]; ``q_offset`` shifts q positions (continuation).
    ``window`` (static): row i also sees only keys j > i - window, and
    the K/V chunks before the band are never read; None (the default)
    is plain causal attention and lowers as it did before the argument
    existed.  Returns [B, Tq, H, D] in q.dtype."""
    B, Tq, H, D = q.shape
    _, Tk, Hkv, _ = k.shape
    groups = H // Hkv

    q_block = min(q_block, max(Tq, 8))
    kv_chunk = min(kv_chunk, Tk)
    q_pad = (-Tq) % q_block
    k_pad = (-Tk) % kv_chunk

    # Kernel layout: [B, H(kv), T, D] — tiled axes last.
    qt = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    kt = jnp.pad(k, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    vt = jnp.pad(v, ((0, 0), (0, k_pad), (0, 0), (0, 0))).transpose(
        0, 2, 1, 3
    )
    nq = (Tq + q_pad) // q_block

    kernel = functools.partial(
        _flash_kernel,
        q_offset=q_offset,
        kv_len=Tk,
        q_block=q_block,
        kv_chunk=kv_chunk,
        scale=D**-0.5,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec(
                (1, 1, q_block, D),
                lambda b, h, qi: (b, h, qi, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, Tk + k_pad, D),
                lambda b, h, qi, g=groups: (b, h // g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, Tk + k_pad, D),
                lambda b, h, qi, g=groups: (b, h // g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, q_block, D),
            lambda b, h, qi: (b, h, qi, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((q_block, D), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
            pltpu.VMEM((q_block, 128), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if q_pad:
        out = out[:, :Tq]
    return out


# The continuation entry below.  Positions of K/V a step over the cached
# prefix (one DMA a pool block, then one online-softmax update a query head:
# the state is rescaled once a step and head, so wider steps rescale less, and
# twice this width wants a third more VMEM for a few per cent; the readings are
# PERF.md's, section 6, PR 36), the query rows a tile keeps the softmax state
# of (all heads of the tile at once, so that a block crosses HBM once a tile),
# and the most VMEM the entry may ask for.  What it asks is counted from its
# shapes (``paged_vmem_bytes``: 40 and 43 MiB at 8 KV heads of 128 under 32
# and 16 query heads, of which the v5e's compiler takes between 28 and 32);
# shapes that count more than this do not fit (``fits_paged``) and their
# caller gathers the prefix instead.  The compiler's default is 16 MiB; a v5e
# or v6e core has 128, a v5p or 7x core 64, which this stays under.
PAGED_KV_CHUNK = 1024
PAGED_MAX_ROWS = 8192
PAGED_VMEM_BUDGET_BYTES = 56 * 1024 * 1024


def _paged_tile(n_heads: int) -> int:
    """The most positions a query tile holds: all heads of a tile keep their
    state, so as many as ``PAGED_MAX_ROWS`` rows allow, in whole sublanes,
    and no more than a step is wide."""
    return min(max(PAGED_MAX_ROWS // n_heads // 8 * 8, 8), PAGED_KV_CHUNK)


def paged_vmem_bytes(
    n_kv_heads: int, head_dim: int, n_heads: int, dtype_bytes: int = 2
) -> int:
    """The VMEM ``flash_gqa_attention_pallas_paged`` asks for at its widest
    tile: its scratch, its query and output tiles, and a step's values."""
    step = PAGED_KV_CHUNK * n_kv_heads * head_dim  # K's or V's numbers a step
    rows = _paged_tile(n_heads) * n_heads
    blocks = 2 * 2 * step * dtype_bytes  # two buffers of K and V as they lie
    widened = 2 * step * 4  # K and V in float32
    state = rows * (head_dim + 2 * 128) * 4  # acc, m and l of every head
    tiles = 2 * 2 * rows * head_dim * dtype_bytes  # q and out, two buffers each
    # in flight in one update: scores, probabilities and the mask's positions
    # of one head, that head's K and V
    update = (3 * _paged_tile(n_heads) + 2 * head_dim) * PAGED_KV_CHUNK * 4
    return blocks + widened + state + tiles + update


def fits_paged(
    block_size: int,
    n_kv_heads: int,
    head_dim: int,
    n_heads: int,
    dtype_bytes: int = 2,
) -> bool:
    """True if a pool of such slots can be read by the continuation entry:
    a head fills the 128 lanes (Mosaic lowers the strided read of one KV
    head's rows at no other size), whole blocks make a step, and the
    entry's VMEM is within its budget."""
    return (
        head_dim == 128
        and PAGED_KV_CHUNK % block_size == 0
        and paged_vmem_bytes(n_kv_heads, head_dim, n_heads, dtype_bytes)
        <= PAGED_VMEM_BUDGET_BYTES
    )


def _paged_kernel(
    table_ref,  # SMEM [B, n_blocks] int32 (scalar prefetch)
    q_ref,  # VMEM [1, Hkv, G, tq, D]
    pool_ref,  # HBM [slots, 2, bs*Hkv, D]: the pool where it lies
    out_ref,  # VMEM [1, Hkv, G, tq, D]
    buf,  # VMEM [2, P, 2, bs*Hkv, D]: two steps' blocks as they lie
    sem,  # DMA [2]
    kv_ref,  # VMEM [2, P*bs*Hkv, D] f32: the step's K and V, rows as in a slot
    acc_ref,  # VMEM [Hkv, G, tq, D] f32
    m_ref,  # VMEM [Hkv, G, tq, 128] f32 (lane-replicated row max)
    l_ref,  # VMEM [Hkv, G, tq, 128] f32 (lane-replicated row sum)
    *,
    q_offset: int,
    block_size: int,
    own_blocks: int,
    scale: float,
):
    """One query tile (``tq`` positions from ``q_offset + qi * tq``, every
    head) over the table's blocks.  A step's blocks come from the pool each by
    a copy of its own into one of two buffers (the next step's arrive while
    this one's are multiplied) and are widened to float32 as they lie (a row
    a position and KV head); each KV head's rows of them, read with the heads'
    stride, are one operand against the rows of that head's ``G`` query heads
    alone.  Steps that lie wholly before the tile's first position (the
    cached prefix) take ``P`` blocks and no mask; from there to the tile's
    last position the steps take ``own_blocks`` and the causal mask."""
    b, qi = pl.program_id(0), pl.program_id(1)
    _, Hkv, G, tq, D = q_ref.shape
    P, rows = buf.shape[1], buf.shape[3]
    n_blocks = table_ref.shape[1]
    q_start = q_offset + qi * tq  # absolute position of the tile's row 0

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    n_clear = q_start // (P * block_size)  # steps every row sees whole
    own_first = n_clear * P  # the first block of the steps under the mask
    own_width = own_blocks * block_size
    n_own = pl.cdiv(q_start + tq - own_first * block_size, own_width)

    def copy(first, i, half):
        # past the table's end its last block again: after every query's
        # own position, so the causal mask hides it
        return pltpu.make_async_copy(
            pool_ref.at[table_ref[b, jnp.minimum(first + i, n_blocks - 1)]],
            buf.at[half, i],
            sem.at[half],
        )

    # A loop on the chip, not in Python: a step's copies written out one by
    # one at each of their six places made the kernel a second to trace, in
    # every process that serves a hit.
    def start(first, count, half):
        def one(i, _):
            copy(first, i, half).start()
            return 0

        jax.lax.fori_loop(0, count, one, 0)

    def causal(first):
        """What hides, in the scores of a step from table column ``first``,
        the keys after a row's own position."""

        def hide(s):
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = first * block_size + col <= q_start + row
            return jnp.where(seen, s, NEG_INF)

        return hide

    def attend(first, count, half, hide):
        """One step over ``count`` blocks from table column ``first``, which
        lie in ``buf[half]`` once their copies have ended."""
        def one(i, _):
            copy(first, i, half).wait()
            return 0

        jax.lax.fori_loop(0, count, one, 0)
        for kv in range(2):
            kv_ref[kv, pl.ds(0, count * rows), :] = (
                buf[half, pl.ds(0, count), kv]
                .reshape(count * rows, D)
                .astype(jnp.float32)
            )
        width = count * block_size

        def head(hg, _):
            h, g = jax.lax.div(hg, G), jax.lax.rem(hg, G)
            q = q_ref[0, h, g].astype(jnp.float32) * scale  # [tq, D]
            k = kv_ref[0, pl.ds(h, width, stride=Hkv), :]  # [width, D]
            v = kv_ref[1, pl.ds(h, width, stride=Hkv), :]
            _online_softmax_update(
                q, k, v, hide, acc_ref.at[h, g], m_ref.at[h, g],
                l_ref.at[h, g],
            )
            return 0

        jax.lax.fori_loop(0, Hkv * G, head, 0)

    @pl.when(n_clear > 0)
    def _first_clear():
        start(0, P, 0)

    @pl.when(n_clear == 0)
    def _first_own():
        start(0, own_blocks, 0)

    def clear_step(j, _):
        half = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_clear)
        def _next_clear():
            start((j + 1) * P, P, 1 - half)

        @pl.when(j + 1 == n_clear)
        def _next_own():
            start(own_first, own_blocks, 1 - half)

        attend(j * P, P, half, None)
        return 0

    def own_step(j, _):
        half = jax.lax.rem(n_clear + j, 2)

        @pl.when(j + 1 < n_own)
        def _next_own():
            start(own_first + (j + 1) * own_blocks, own_blocks, 1 - half)

        first = own_first + j * own_blocks
        attend(first, own_blocks, half, causal(first))
        return 0

    jax.lax.fori_loop(0, n_clear, clear_step, 0)
    jax.lax.fori_loop(0, n_own, own_step, 0)

    l = l_ref[:, :, :, :1]
    out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_offset", "interpret"))
def flash_gqa_attention_pallas_paged(
    q: jnp.ndarray,
    kv_pool: jnp.ndarray,
    block_table: jnp.ndarray,
    *,
    q_offset: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal GQA flash attention of a continuation over the pool's blocks
    where they lie.  q: [B, Tq, H, D], the queries at positions
    ``q_offset ..`` (static); kv_pool: [slots, 2, bs, Hkv, D] (a slot as
    ``KVGroupSpec`` lays it out by default); block_table: [B, n] int32, the
    slots that hold positions 0 .. n*bs - 1 >= q_offset + Tq - 1, the
    queries' own K/V among them (the caller writes them first).  Only the
    table's blocks are read, each once a tile of ``PAGED_MAX_ROWS`` query
    rows; they hold numbers (a masked NaN would still poison its row).  The
    work of ``flash_gqa_attention_pallas`` over the gathered K/V: float32
    scores, softmax state and accumulators.  Returns [B, Tq, H, D] in
    q.dtype."""
    B, Tq, H, D = q.shape
    _, _, block_size, Hkv, _ = kv_pool.shape
    G = H // Hkv
    itemsize = jnp.dtype(kv_pool.dtype).itemsize
    if not fits_paged(block_size, Hkv, D, H, itemsize):
        raise ValueError(
            f"slots of {block_size} positions, {Hkv} KV heads of {D} under "
            f"{H} query heads do not fit the entry (fits_paged)"
        )
    if block_table.shape[1] * block_size < q_offset + Tq:
        raise ValueError("the table ends before the queries' positions")
    # all heads of a tile keep their state: as many positions as fit
    tq = min(-(-Tq // 8) * 8, _paged_tile(H))
    q_pad = (-Tq) % tq
    # under the mask a step is as wide as the tile is long, in whole blocks
    own = -(-tq // 128) * 128
    own_blocks = min(own, PAGED_KV_CHUNK) // block_size
    P = PAGED_KV_CHUNK // block_size
    qt = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    qt = qt.reshape(B, Tq + q_pad, Hkv, G, D).transpose(0, 2, 3, 1, 4)
    rows = block_size * Hkv
    kernel = functools.partial(
        _paged_kernel, q_offset=q_offset, block_size=block_size,
        own_blocks=own_blocks, scale=D**-0.5,
    )
    tile = pl.BlockSpec(
        (1, Hkv, G, tq, D),
        lambda b, qi, table_ref: (b, 0, 0, qi, 0),
        memory_space=pltpu.VMEM,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, (Tq + q_pad) // tq),
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, P, 2, rows, D), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((2, P * rows, D), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, D), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, 128), jnp.float32),
                pltpu.VMEM((Hkv, G, tq, 128), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=paged_vmem_bytes(Hkv, D, H, itemsize)
        ),
        interpret=interpret,
    )(
        block_table.astype(jnp.int32),
        qt,
        kv_pool.reshape(kv_pool.shape[:2] + (rows, D)),
    )
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq + q_pad, H, D)
    return out[:, :Tq] if q_pad else out
