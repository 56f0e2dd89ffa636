"""The paged K/V pools on the chip, and the ONE place a slot's layout is
written and read.  A pool is, a layer, one array ``[slots, *slot]``;
``KVGroupSpec`` says what a slot holds and ``KVGroupSpec.layout`` names how it
lies (the reasons stand beside the spec's fields):

  ``plain``        [2, block, Hkv, Dh]       `llama`, and ``KVCachePool`` below
                                             (the offload path's files)
  ``heads_first``  [2, Hkv, block, Dh]       `afmoe`: fewer than 8 KV heads
  ``packed``       [block, Hkv, 2 * Dh]      `lfm2moe`: heads of 64
  ``rows``         [2, block * Hkv, Dh]      `phi4flash`: 10 pair-wise heads
  ``latent``       [block / 2, 2 * W]        `glm4moelite`: a vector a position
  ``selected``     [block + t, 2 * Hkv, Dh]  `keyevl2`: a tile a position, then
                                             the block's selector keys
  ``latent_selected``  [block / 2, 2 * W + 2 * dI]  `deepseekv32`: the latent
                                             row, then its two selector keys

(and ``state``: a recurrent layer's state at a block's end, no K/V).  A family
names its layout once, in its ``cache_groups``, hands the group's spec to the
operations below (``write_blocks``, ``write_token``, ``gather_prefix``,
``decode_view``; for the kinds attended over where they lie
``unpack_latent_blocks``, ``gather_selector_keys``, ``gather_picked_tiles``,
``gather_picked_latents``) and never looks into a pool array.  A new layout
is a branch in each of these and in ``KVGroupSpec.layer_shape``, here and
nowhere else (the kernels in ops/ read a slot by the keywords ``decode_view``
gives, or by the lanes a family's spec names).

``KVCachePool`` stacks the layers, ``[num_layers, num_blocks, *slot]``: one
jitted gather/scatter moves a block batch across all layers in one XLA op and
one transfer, under a NamedSharding where one is passed.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclass(frozen=True)
class KVGroupSpec:
    """One group of layers that share a block table and a retention
    rule: what one slot of the group holds.  A dense model has one
    group (every layer, whole context); a model that mixes window and
    full attention layers has one group per kind, and ``window`` is the
    number of positions a layer of the group still reads (None: all).
    A slot need not be K/V: with ``state_shape`` it is, a layer, one array
    of that shape (a recurrent layer's state after the last position of a
    logical block), or several, each with its own type (``state_shape`` as
    ``((shape, dtype), ...)``: a state-space layer keeps its convolution's
    inputs in the serving type and its scan's state in float32); its bytes
    do not grow with the block, and ``stride_blocks`` says which block
    boundaries keep one (``snapshot_blocks``).  ``readers`` is how many
    layers read a slot where that is more than the ``num_layers`` whose K/V
    it holds (a cache that later layers attend over without one of their
    own).  With ``latent_dim`` a slot is no K and V per head but, a position
    a layer, ONE vector that is key and value at once (latent attention:
    every query head scores over all ``latent_dim`` lanes of it and takes
    its first ``value_dim`` as the value; ``num_kv_heads`` is 1 and
    ``head_dim`` the latent's width).  With ``selector_dim`` a slot holds, a
    position a layer, K and V per head AND one selector key of that many
    lanes (learned sparse attention: an indexer scores every cached position
    by its key and attention reads the best only), kept and evicted together.
    With both, a slot holds the latent vector and the selector key of each
    position (learned sparse attention over a latent cache).
    Block bytes, pool shapes and the scatter's geometry are read from here by
    the pool below, by the pod's cache (models/pod.py) and by each family's
    model step."""

    num_layers: int
    block_size: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"
    window: Optional[int] = None
    # A slot as [2, Hkv, block, Dh] instead of [2, block, Hkv, Dh]: the
    # chip's tiles then lie over (block, Dh) whole, so a model with fewer
    # than 8 KV heads neither pads its slots in VMEM nor has its pool
    # re-laid-out around a prefill's scatter.  The uniform pool keeps the
    # first form (the offload path's file layout is that one).
    heads_first: bool = False
    # A slot as [block, Hkv, 2 * Dh]: a position's K in the lower half of the
    # last axis and its V in the upper.  For a head size of 64, whose own last
    # axis is half of what the chip lays arrays out by: the compiler would
    # make the pool's slot axis the minor one, and every step that writes a
    # slot would re-lay-out the whole pool around it.
    packed: bool = False
    # A slot as [2, block * Hkv, Dh]: a position's heads as neighbouring rows
    # (the first form with its two middle axes merged, which moves nothing).
    # For a number of KV heads that is no multiple of 8 (10 pair-wise heads in
    # models/phi4flash.py): as the last axis but one, the chip pads it to its
    # tile (10 -> 16 rows) and re-lays-out the whole pool around every step.
    rows: bool = False
    state_shape: Optional[tuple] = None
    stride_blocks: Optional[int] = None
    readers: Optional[int] = None
    # The latent kind: a slot as [block / 2, 2 * latent_dim], row r the
    # positions r and r + block / 2 of the block, mirrored: [value_r | rest_r
    # | rest_r+ | value_r+] (``pack_latent_blocks``).  A position's
    # ``latent_dim`` lanes alone (576 = 4.5 x 128 in models/glm4moelite.py) are
    # no whole number of the chip's 128-lane tiles: as [block, 576] the
    # compiler either pads the pool to 640 lanes or makes its slot axis the
    # minor one, and Mosaic refuses to slice it; two positions a row are 9
    # tiles, both values start on a tile, and the pool lies as it is written
    # (compiled for the v5e, PR 42: tests/test_tpu_compile.py).
    latent_dim: Optional[int] = None
    value_dim: Optional[int] = None
    # The selected kind: a slot as [block + t, 2 * Hkv, Dh], a tile a
    # position (its K heads' rows, then its V heads'), then the t tiles that
    # hold the block's selector keys, ``Dh / selector_dim`` positions a row
    # (``pack_selected_blocks``).  A position's K and V are then ONE piece of
    # the pool (2 KB at 4 KV heads of 128 in bfloat16, a whole tile of the
    # chip's), which a step that reads picked positions only fetches by one
    # copy or one row of a gather; a key of 64 lanes alone is half a tile,
    # the trouble ``packed`` and the latent kind each solved their way.
    selector_dim: Optional[int] = None
    # how many positions a query reads of the group, the best by the
    # selector's score (None: every position the window or context admits)
    selected: Optional[int] = None
    # The latent-selected kind (``latent_dim`` AND ``selector_dim``): a slot
    # as [block / 2, 2 * latent_dim + 2 * selector_dim], row r the latent
    # kind's row of the positions r and r + block / 2 (mirrored, as above),
    # then their two selector keys, [key_r | key_r+].  A position's 704 lanes
    # (576 + 128 in models/deepseekv32.py) are 5.5 of the chip's 128-lane
    # tiles; two positions a row are 11: the latent part is the latent kind's
    # to the lane (its kernels read a row's first 9 tiles as they read a
    # latent slot: both values start on a tile), the keys are a row's last 2
    # tiles, which a copy that names lanes brings without the latents (a
    # decode step's scores read 256 B of a position's 1408), and a query's
    # picked positions come as rows of the pool seen a row a line
    # (``gather_picked_latents``: a row is the least piece of whole tiles
    # that holds a position's latent).  Keys in rows of their own, as the
    # selected kind has them, would make a slot 8 rows of 1152 lanes and 1 of
    # 2048: no one array.

    def __post_init__(self) -> None:
        if self.selector_dim is not None and self.latent_dim is not None:
            if self.selector_dim <= 0:
                raise ValueError("a selector key has lanes")
        elif self.selector_dim is not None:
            tile = 2 * self.num_kv_heads * self.head_dim
            if (self.state_shape is not None
                    or self.selector_dim <= 0
                    or self.head_dim % self.selector_dim
                    or (self.block_size * self.selector_dim) % tile):
                raise ValueError(
                    "a selected slot is K/V tiles and whole tiles of "
                    "selector keys: selector_dim divides head_dim, and a "
                    "block's keys fill tiles of 2 * num_kv_heads * head_dim")
        if self.latent_dim is not None and (
                self.num_kv_heads != 1 or self.head_dim != self.latent_dim
                or self.block_size % 2 or self.state_shape is not None
                or not 0 < (self.value_dim or 0) <= self.latent_dim):
            raise ValueError(
                "a latent slot is one vector a position: one KV head of "
                "latent_dim, value_dim of its lanes the value, an even block")
        named = [n for n in ("heads_first", "packed", "rows")
                 if getattr(self, n)]
        named += [n for n in ("latent_dim", "selector_dim", "state_shape")
                  if getattr(self, n) is not None]
        if named == ["latent_dim", "selector_dim"]:
            named = ["latent_selected"]  # one kind: both in a row
        if len(named) > 1:
            raise ValueError("a slot lies one way: " + ", ".join(named)
                             + " exclude each other")

    @property
    def layout(self) -> str:
        """How a slot lies, from the fields above (the module's head has the
        seven K/V layouts): what the operations below branch on."""
        if self.state_shape is not None:
            return "state"
        if self.latent_dim is not None:
            return "latent" if self.selector_dim is None else "latent_selected"
        if self.selector_dim is not None:
            return "selected"
        for name in ("heads_first", "packed", "rows"):
            if getattr(self, name):
                return name
        return "plain"

    @property
    def num_readers(self) -> int:
        """Layers that read a slot: those whose K/V it holds, unless
        ``readers`` says more."""
        return self.num_layers if self.readers is None else self.readers

    @property
    def read_nbytes(self) -> int:
        """Bytes of a slot that one step's layers read: the slot's bytes
        once for each reader of each layer it holds."""
        return self.block_nbytes * self.num_readers // self.num_layers

    @property
    def state_parts(self) -> tuple:
        """A state slot's arrays of one layer as ((shape, dtype), ...), a
        pool's being ``(slots,) + shape`` each: the one place
        ``block_nbytes``, ``layer_shape`` and a family's ``new_pool`` read
        them from."""
        if self.state_shape is None:
            raise ValueError("a K/V group's slot is not a state")
        if isinstance(self.state_shape[0], int):
            return ((tuple(self.state_shape), self.dtype),)
        return tuple((tuple(shape), dtype) for shape, dtype in self.state_shape)

    @property
    def block_nbytes(self) -> int:
        """Bytes of one slot: K and V of ``block_size`` positions over
        the group's layers, or the layers' states."""
        if self.state_shape is not None:
            return self.num_layers * sum(
                math.prod(shape) * jnp.dtype(dtype).itemsize
                for shape, dtype in self.state_parts
            )
        return (
            self.num_layers
            * self.block_size
            # one vector, or K and V, or K and V and the selector's key
            * ((1 if self.latent_dim else 2) * self.num_kv_heads
               * self.head_dim + (self.selector_dim or 0))
            * jnp.dtype(self.dtype).itemsize
        )

    @property
    def selector_tiles(self) -> int:
        """Tiles of [2 * Hkv, Dh] that hold a block's selector keys (the
        selected kind's)."""
        return (self.block_size * self.selector_dim
                // (2 * self.num_kv_heads * self.head_dim))

    @property
    def slot_tiles(self) -> int:
        """Tiles of a selected slot: a position each, then its keys'."""
        return self.block_size + self.selector_tiles

    def layer_shape(self, num_blocks: int) -> tuple:
        """One layer's share of a pool of ``num_blocks`` slots (a state in
        several arrays has a shape each: ``state_parts``)."""
        if self.state_shape is not None:
            ((shape, _),) = self.state_parts
            return (num_blocks,) + shape
        if self.latent_dim:
            return (num_blocks, self.block_size // 2,
                    2 * (self.latent_dim + (self.selector_dim or 0)))
        if self.selector_dim:
            return (num_blocks, self.slot_tiles, 2 * self.num_kv_heads,
                    self.head_dim)
        if self.packed:
            return (num_blocks, self.block_size, self.num_kv_heads,
                    2 * self.head_dim)
        if self.rows:
            return (num_blocks, 2, self.block_size * self.num_kv_heads,
                    self.head_dim)
        inner = (
            (self.num_kv_heads, self.block_size)
            if self.heads_first
            else (self.block_size, self.num_kv_heads)
        )
        return (num_blocks, 2) + inner + (self.head_dim,)

    def snapshot_blocks(self, first: int, count: int) -> list:
        """Of the blocks ``first .. first + count - 1`` of a chain that one
        prefill call writes, those whose end keeps a state slot: every
        block whose index + 1 is a multiple of ``stride_blocks``, and the
        call's last.  Static in a program's shapes, so the host that names
        the slots and the step that fills them read one list."""
        last = first + count - 1
        return [
            i
            for i in range(first, last + 1)
            if (i + 1) % self.stride_blocks == 0 or i == last
        ]

    @property
    def window_blocks(self) -> int:
        """Blocks before a block boundary that a query there reads:
        ceil((window - 1) / block_size).  A prefix is servable only
        where the group holds that many of its last blocks."""
        if self.window is None:
            raise ValueError("a group without a window reads every block")
        return -(-(self.window - 1) // self.block_size)


def _blocked(k, v, block_size, heads_first=False):
    """Per-token K/V ([B, T, Hkv, Dh] each) block by block: [B, T/block, 2,
    block, Hkv, Dh], or ``heads_first`` [B, T/block, 2, Hkv, block, Dh]."""
    B, T = k.shape[:2]
    kv = jnp.stack((k, v), axis=2)  # [B, T, 2, Hkv, Dh]
    return kv.reshape(
        B, T // block_size, block_size, 2, kv.shape[-2], kv.shape[-1]
    ).transpose((0, 1, 3, 4, 2, 5) if heads_first else (0, 1, 3, 2, 4, 5))


def scatter_kv_blocks(
    kv_layer, k, v, block_ids, block_size, heads_first=False
):
    """Write per-token K/V ([B, T, Hkv, Dh] each, T a multiple of
    ``block_size``) into the slots of one layer's pool
    (``KVGroupSpec.layer_shape``) named by ``block_ids``
    ([B, T/block_size]): the plain layout and ``heads_first``.  A family's
    model step reaches it through ``write_blocks``, which knows every layout;
    the `llama` programs call it on their merged pool.

    Only the named slots are written, so a pool that is carried (a
    scan's carry, or one array a layer as ``models/afmoe.py`` keeps
    them) and donated by the caller's ``jit`` is updated where it lies;
    handed in as a scan's xs and taken back as ys it is copied whole.
    The first axis may as well hold several layers' slots: the `llama`
    programs merge a pool's ``[L, N]`` into ``L * N`` and name layer
    ``l``'s block ``b`` as ``l * N + b`` (``llama._scan_layers``)."""
    kv = _blocked(k, v, block_size, heads_first)
    return kv_layer.at[block_ids.reshape(-1)].set(
        kv.reshape((-1,) + kv.shape[2:]).astype(kv_layer.dtype)
    )


def pack_latent_blocks(latent, block_size: int, value_dim: int):
    """Per-position latents [..., T, latent_dim] (T a multiple of
    ``block_size``) as the slots of a latent group
    (``KVGroupSpec.layer_shape``): [..., T/block_size, block_size/2,
    2*latent_dim], row r of a block its positions r and r + block_size/2 as
    [value_r | rest_r | rest_r+ | value_r+]."""
    *lead, T, _ = latent.shape
    x = latent.reshape(*lead, T // block_size, 2, block_size // 2, -1)
    a, b = x[..., 0, :, :], x[..., 1, :, :]
    return jnp.concatenate(
        (a, b[..., value_dim:], b[..., :value_dim]), axis=-1
    )


def unpack_latent_blocks(slots, value_dim: int):
    """``pack_latent_blocks`` undone: slots [..., n, block/2, 2*latent_dim]
    -> the positions' latents [..., n*block, latent_dim], in order."""
    *lead, n, half, width = slots.shape
    a, b = slots[..., : width // 2], slots[..., width // 2:]
    b = jnp.concatenate(
        (b[..., width // 2 - value_dim:], b[..., : width // 2 - value_dim]),
        axis=-1,
    )
    return jnp.stack((a, b), axis=-3).reshape(*lead, n * 2 * half, width // 2)


def pack_latent_selected_blocks(latent, key, block_size: int, value_dim: int):
    """Per-position latents [..., T, latent_dim] and selector keys
    [..., T, dI] as the slots of a latent-selected group: the latent kind's
    rows (``pack_latent_blocks``), each followed by its two positions' keys,
    [..., T/block_size, block_size/2, 2*latent_dim + 2*dI]."""
    *lead, T, dI = key.shape
    keys = key.reshape(*lead, T // block_size, 2, block_size // 2, dI)
    return jnp.concatenate(
        (pack_latent_blocks(latent, block_size, value_dim),
         keys[..., 0, :, :].astype(latent.dtype),
         keys[..., 1, :, :].astype(latent.dtype)), axis=-1)


def pack_selected_blocks(k, v, key, block_size: int):
    """Per-position K and V ([..., T, Hkv, Dh] each) and selector keys
    ([..., T, dI]), T a multiple of ``block_size``, as the slots of a
    selected group (``KVGroupSpec.layer_shape``): [..., T/block_size,
    block_size + t, 2*Hkv, Dh].  Tile p of a block is its position p (K
    heads' rows, then V heads'); the last t tiles are the block's keys as
    rows of Dh lanes, row r the positions r, r + R, r + 2R, ... side by side
    (R = block_size * dI / Dh rows in all)."""
    *lead, T, Hkv, Dh = k.shape
    dI = key.shape[-1]
    nb = T // block_size
    kv = jnp.concatenate((k, v), axis=-2).reshape(
        *lead, nb, block_size, 2 * Hkv, Dh)
    per = Dh // dI
    rows = key.reshape(*lead, nb, per, block_size // per, dI)
    rows = jnp.moveaxis(rows, -3, -2).reshape(*lead, nb, -1, 2 * Hkv, Dh)
    return jnp.concatenate((kv, rows.astype(kv.dtype)), axis=-3)


def unpack_selector_keys(tiles, selector_dim: int):
    """The selector keys of slots' key tiles ([..., n, t, 2*Hkv, Dh], the
    tiles after a slot's positions): [..., n*block, dI], in order."""
    *lead, n, t, rows, Dh = tiles.shape
    per = Dh // selector_dim
    keys = tiles.reshape(*lead, n, t * rows, per, selector_dim)
    return jnp.moveaxis(keys, -2, -3).reshape(*lead, -1, selector_dim)


# Each operation below takes the group's spec and branches on ``spec.layout``
# at trace time.  ``pool`` is one layer's array, or several layers' slots merged
# into its first axis (models/phi4flash.py); ids name slots of that axis.


def write_blocks(spec: KVGroupSpec, pool, block_ids, *parts):
    """Write a prefill's whole blocks into the slots ``block_ids``
    [B, T/block] of ``pool``.  ``parts``: per-token K and V [B, T, Hkv, Dh]
    each (T a multiple of the block size); for a latent group the latents
    [B, T, latent_dim]; for a selected group K, V and the selector keys
    [B, T, dI]; for a latent-selected group the latents and the keys.  Only
    the named slots are written (``scatter_kv_blocks``)."""
    bs, layout = spec.block_size, spec.layout
    if layout == "latent":
        slots = pack_latent_blocks(*parts, bs, spec.value_dim)
    elif layout == "latent_selected":
        slots = pack_latent_selected_blocks(*parts, bs, spec.value_dim)
    elif layout == "selected":
        slots = pack_selected_blocks(*parts, bs)
    elif layout == "packed":  # a reshape, no transpose
        slots = jnp.concatenate(parts, axis=-1).reshape((-1,) + pool.shape[1:])
    elif layout == "rows":
        # the plain slot with a block's positions and heads as rows.  (Through
        # a view of the pool with its rows apart the compiler re-laid-out the
        # whole pool around the scatter.)
        slots = _blocked(*parts, bs).reshape((-1,) + pool.shape[1:])
    else:
        return scatter_kv_blocks(pool, *parts, block_ids, bs,
                                 heads_first=layout == "heads_first")
    # (packed and rows have shaped their slots above, before the ids are
    # flattened, as those families' programs had it: for them this reshape
    # moves nothing)
    return pool.at[block_ids.reshape(-1)].set(
        slots.reshape((-1,) + pool.shape[1:]).astype(pool.dtype))


def gather_prefix(spec: KVGroupSpec, pool, ids, dtype):
    """The K and V of the slots ``ids`` [B, n], in order: [B, n * block, Hkv,
    Dh] each, in ``dtype``.  (The latent and the selected kind are attended
    over where they lie: their readers are further down.)"""
    layout = spec.layout
    pre = jnp.take(pool, ids, axis=0)  # [B, n, *slot]
    if layout == "packed":
        B, n, bs, Hkv, two = pre.shape
        pre = pre.reshape(B, n * bs, Hkv, two)
        return (pre[..., :two // 2].astype(dtype),
                pre[..., two // 2:].astype(dtype))
    if layout == "rows":
        B, n, _, rows, Dh = pre.shape
        Hkv = spec.num_kv_heads
        pre = pre.transpose(0, 2, 1, 3, 4).reshape(
            B, 2, n * rows // Hkv, Hkv, Dh)
    elif layout == "heads_first":
        B, n, _, Hkv, bs, Dh = pre.shape
        pre = pre.transpose(0, 2, 1, 4, 3, 5).reshape(B, 2, n * bs, Hkv, Dh)
    elif layout == "plain":
        B, n, _, bs, Hkv, Dh = pre.shape
        pre = pre.transpose(0, 2, 1, 3, 4, 5).reshape(B, 2, n * bs, Hkv, Dh)
    else:
        raise ValueError(f"a {layout} slot is not gathered as K and V")
    return pre[:, 0].astype(dtype), pre[:, 1].astype(dtype)


def _patched_slots(spec: KVGroupSpec, pool, ids, at, *parts):
    """The slots ``ids`` [B] of ``pool`` with position ``at[b]`` of slot b
    replaced by ``parts[b]``: what ``write_token`` puts back."""
    bs, layout = spec.block_size, spec.layout
    if layout in ("plain", "heads_first"):
        k, v = parts
        new = jnp.stack((k, v), axis=1)  # [B, 2, Hkv, Dh]
        slots = jnp.take(pool, ids, axis=0)  # [B, 2, Hkv, block, Dh]
        here = jnp.arange(bs)[None, :] == at[:, None]  # [B, block]
        if layout == "plain":  # [B, 2, block, Hkv, Dh]
            return jnp.where(here[:, None, :, None, None],
                             new[:, :, None].astype(pool.dtype), slots)
        return jnp.where(here[:, None, None, :, None],
                         new[:, :, :, None, :].astype(pool.dtype), slots)
    if layout == "packed":
        k, v = parts
        slots = jnp.take(pool, ids, axis=0)  # [B, block, Hkv, 2 Dh]
        new = jnp.concatenate((k, v), axis=-1).astype(pool.dtype)
        here = jnp.arange(bs)[None, :] == at[:, None]  # [B, block]
        return jnp.where(here[:, :, None, None], new[:, None], slots)
    if layout == "rows":
        k, v = parts
        slots = jnp.take(pool, ids, axis=0)  # [B, 2, block * Hkv, Dh]
        Hkv = spec.num_kv_heads
        new = jnp.tile(jnp.stack((k, v), axis=1).astype(pool.dtype),
                       (1, 1, bs, 1))  # row r: head r % Hkv
        here = jnp.arange(bs * Hkv)[None, :] // Hkv == at[:, None]
        return jnp.where(here[:, None, :, None], new, slots)
    if layout in ("latent", "latent_selected"):
        # position p of a block is the first half of row p if p is in the
        # block's first half, else the second half, mirrored, of row
        # p - block/2 (`pack_latent_blocks`); its selector key, where the
        # kind has one, the first or the second of the row's two
        new, value_dim = parts[0].astype(pool.dtype), spec.value_dim
        half, width = bs // 2, 2 * spec.latent_dim
        slots = jnp.take(pool, ids, axis=0)  # [B, block/2, 2 latent (+ 2 dI)]
        second = (at >= half)[:, None]
        zeros = jnp.zeros_like(new)
        row = jnp.where(
            second,
            jnp.concatenate((zeros, new[:, value_dim:], new[:, :value_dim]), -1),
            jnp.concatenate((new, zeros), -1))  # [B, 2 latent]
        lanes = (jnp.arange(width)[None, :] >= width // 2) == second
        if layout == "latent_selected":
            key = parts[1].astype(pool.dtype)
            dI = spec.selector_dim
            row = jnp.concatenate((row, key, key), -1)
            lanes = jnp.concatenate(
                (lanes, (jnp.arange(2 * dI)[None, :] >= dI) == second), -1)
        here = ((jnp.arange(half)[None, :] == (at % half)[:, None])[:, :, None]
                & lanes[:, None, :])
        return jnp.where(here, row[:, None, :], slots)
    if layout == "selected":
        k, v, ki = parts  # [B, Hkv, Dh] each and [B, dI]
        slots = jnp.take(pool, ids, axis=0)  # [B, block + t, 2 Hkv, Dh]
        here = jnp.arange(bs)[None, :] == at[:, None]  # [B, block]

        def slot_of(k, v, ki):
            """The slot of a block whose position p holds (k, v, ki)[:, p]."""
            return pack_selected_blocks(k, v, ki, bs)[:, 0]

        # the slot of a block that holds only this position, and where it is
        one = slot_of(jnp.where(here[:, :, None, None], k[:, None], 0),
                      jnp.where(here[:, :, None, None], v[:, None], 0),
                      jnp.where(here[:, :, None], ki[:, None], 0))
        mask = slot_of(*(jnp.broadcast_to(
            here.reshape(here.shape + (1,) * (a.ndim - 1)),
            here.shape + a.shape[1:]) for a in (k, v, ki)))
        return jnp.where(mask, one.astype(pool.dtype), slots)
    raise ValueError(f"a {layout} slot holds no position's K/V")


def write_token(spec: KVGroupSpec, pool, ids, at, *parts):
    """Position ``at[b]`` of slot ``ids[b]`` = ``parts[b]`` for each sequence
    of a decode step (``parts``: K and V [B, Hkv, Dh] each; the latent
    [B, latent_dim]; K, V and the selector key [B, dI]; the latent and the
    selector key), as whole slots: each sequence's current slot is read,
    patched at its position and put back by one slice update along the
    pool's first axis.  (A scatter or a slice update that addresses the
    position axis makes the compiler re-lay-out the whole pool around it,
    twice a layer.)  Idle rows share one scratch slot; what they leave there
    is read by nobody."""
    slots = _patched_slots(spec, pool, ids, at, *parts)

    def one(b, pool):
        return lax.dynamic_update_slice(
            pool, lax.dynamic_slice_in_dim(slots, b, 1, axis=0),
            (ids[b],) + (0,) * (pool.ndim - 1))

    return lax.fori_loop(0, ids.shape[0], one, pool)


def decode_view(spec: KVGroupSpec, pool, kernel: bool):
    """What a decode step's attention is told of the layout: (the pool as the
    reader takes it, the keywords that name the layout to it), the reader
    being ``paged_decode_attention_pallas`` (``kernel``) or the XLA gather
    ``paged_attention``.  Which a step takes is the family's to say."""
    layout = spec.layout
    if layout == "rows":
        # rows apart, [.., block, Hkv, Dh]: the kernel merges them again, and
        # the two reshapes together move nothing
        return pool.reshape(pool.shape[:2] + (
            spec.block_size, spec.num_kv_heads, spec.head_dim)), {}
    if layout == "packed" and not kernel:  # the gather reads K and V apart
        Dh = spec.head_dim
        return jnp.stack((pool[..., :Dh], pool[..., Dh:]), axis=1), {}
    if layout == "latent" and kernel:
        return pool, {"latent": spec.value_dim}
    if layout in ("plain", "heads_first", "packed"):
        return pool, {} if layout == "plain" else {layout: True}
    raise ValueError(f"{layout} slots have no such reader")


def gather_selector_keys(spec: KVGroupSpec, pool, table):
    """The selector keys of the positions ``table`` ([B, n]) names, in order:
    [B, n * block, dI].  Only the key tiles of the table's slots are read, of
    the pool with a tile a row (merging two leading axes moves nothing); of a
    latent-selected pool the table's slots whole, their rows' last lanes kept
    (a gather whose slices start at those lanes made the compiler re-lay-out
    the whole pool, slot axis minor, 6.9 GB for a 0.44-GB layer; compiled
    for the v5e, PR 53)."""
    if spec.layout == "latent_selected":
        B, n = table.shape
        half, dI = spec.block_size // 2, spec.selector_dim
        keys = pool.at[table.reshape(-1)].get(mode="promise_in_bounds")[
            ..., 2 * spec.latent_dim:]
        # [B * n, half, (first | second)] -> a block's first half, then its
        # second
        return keys.reshape(B, n, half, 2, dI).swapaxes(2, 3).reshape(
            B, n * 2 * half, dI)
    per, bs = spec.slot_tiles, spec.block_size
    at = table[..., None] * per + bs + jnp.arange(per - bs)
    tiles = pool.reshape((-1,) + pool.shape[2:])
    return unpack_selector_keys(jnp.take(tiles, at, axis=0),
                                spec.selector_dim)


def gather_picked_tiles(spec: KVGroupSpec, pool, tiles):
    """The K and V ([B, K, Hkv, Dh] each) of the positions whose tiles
    ``tiles`` [B, K] names, counted a tile a row (slot * ``slot_tiles`` +
    position)."""
    rows = jnp.take(pool.reshape((-1,) + pool.shape[2:]), tiles, axis=0)
    return rows[:, :, :spec.num_kv_heads], rows[:, :, spec.num_kv_heads:]


def gather_picked_latents(spec: KVGroupSpec, pool, rows, second):
    """The latents [B, K, latent_dim] of the positions that ``rows`` [B, K]
    (rows of a latent-selected pool seen a row a line: slot * block / 2 +
    position % (block / 2)) and ``second`` [B, K] (the position lies in its
    block's second half) name: a row is gathered whole and the mirrored half
    turned back.  The rows are the caller's promise (they come from a table's
    slots): a gather that has to answer for a row outside the pool fills it
    in a pass of its own, 3.79 ms against 2.29 for 32 x 2048 rows of 2816 B
    (my chip run, PR 53)."""
    W, value = spec.latent_dim, spec.value_dim
    lines = pool.reshape((-1, pool.shape[-1])).at[rows].get(
        mode="promise_in_bounds")
    return jnp.where(
        second[..., None],
        jnp.concatenate((lines[..., 2 * W - value:2 * W],
                         lines[..., W:2 * W - value]), axis=-1),
        lines[..., :W])


@dataclass
class KVCachePoolConfig:
    num_layers: int
    num_blocks: int
    block_size: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"
    # a pool of latent slots (``KVGroupSpec``'s latent kind): one KV head of
    # ``head_dim == latent_dim``, ``value_dim`` of its lanes the value
    latent_dim: Optional[int] = None
    value_dim: Optional[int] = None
    # a pool of selected slots (``KVGroupSpec``'s selected kind): K and V per
    # head and a selector key of ``selector_dim`` lanes a position
    selector_dim: Optional[int] = None

    @property
    def spec(self) -> KVGroupSpec:
        """The pool's one group: every layer, whole context."""
        return KVGroupSpec(
            self.num_layers,
            self.block_size,
            self.num_kv_heads,
            self.head_dim,
            self.dtype,
            latent_dim=self.latent_dim,
            value_dim=self.value_dim,
            selector_dim=self.selector_dim,
        )


@jax.jit
def _gather(kv: jax.Array, block_ids: jax.Array) -> jax.Array:
    return jnp.take(kv, block_ids, axis=1)


@jax.jit
def _scatter(kv: jax.Array, block_ids: jax.Array, blocks: jax.Array):
    return kv.at[:, block_ids].set(blocks)


# Donation variant used when the pool owns its array exclusively.
_scatter_donated = jax.jit(
    lambda kv, ids, blocks: kv.at[:, ids].set(blocks), donate_argnums=(0,)
)


@jax.jit
def _gather_block_major(kv: jax.Array, block_ids: jax.Array) -> jax.Array:
    """Gather + layer-major -> block-major transpose ON DEVICE: the
    staging engine's file layout is ``[n, L, 2, bs, h, d]``, and doing
    the moveaxis in XLA means the host-bound DMA already carries file
    bytes (no host-side ``np.ascontiguousarray`` re-layout copy)."""
    return jnp.moveaxis(jnp.take(kv, block_ids, axis=1), 1, 0)


def supports_pinned_host(device: jax.Device) -> bool:
    """Whether ``device`` exposes a pinned_host memory space (the TPU
    and CPU backends both do)."""
    return any(
        memory.kind == "pinned_host"
        for memory in device.addressable_memories()
    )


def _to_pinned_host(array: jax.Array) -> jax.Array:
    """Async transfer into the pinned_host space of the array's own
    device(s): same sharding, host memory kind."""
    return jax.device_put(
        array, array.sharding.with_memory_kind("pinned_host")
    )


class KVCachePool:
    def __init__(
        self,
        config: KVCachePoolConfig,
        sharding: Optional[jax.sharding.Sharding] = None,
    ) -> None:
        self.config = config
        shape = (config.num_layers,) + config.spec.layer_shape(
            config.num_blocks
        )
        dtype = jnp.dtype(config.dtype)
        if sharding is not None:
            self.kv = jax.device_put(jnp.zeros(shape, dtype), sharding)
        else:
            self.kv = jnp.zeros(shape, dtype)
        # Whether this pool's device exposes a pinned_host memory space
        # (the staging engine's fast-path gate).  Fixed at construction:
        # a pinned transfer that fails raises, it does not degrade.
        self.pinned_host = supports_pinned_host(
            next(iter(self.kv.devices()))
        )

    @property
    def block_nbytes(self) -> int:
        """Bytes of one block across all layers (the offload unit)."""
        return self.config.spec.block_nbytes

    def gather_to_host(self, block_ids: Sequence[int]) -> np.ndarray:
        """Pull blocks to host: one gather in HBM + one transfer.

        Uses the pinned_host memory space when the backend has one (TPU:
        DMA straight into pinned pages, the staging role CUDA pinned
        buffers play in the reference).  Returns
        ``[num_layers, n, 2, block_size, heads, dim]``.
        """
        ids = jnp.asarray(np.asarray(block_ids, dtype=np.int32))
        gathered = _gather(self.kv, ids)
        if self.pinned_host:
            gathered = _to_pinned_host(gathered)
        return np.asarray(jax.device_get(gathered))

    def stage_gather_pinned(self, block_ids: Sequence[int]) -> jax.Array:
        """Device gather+transpose, then an ASYNC DMA into pinned_host.

        Returns the pinned ``[n, L, 2, bs, h, d]`` array without
        forcing it, so the caller can overlap this slot's DMA with the
        previous slot's file I/O (the staging engine's double-buffered
        pipeline) and force only at submit time.  Raises when the
        backend has no pinned_host space — callers gate on
        :attr:`pinned_host` and use :meth:`gather_block_major` there.
        """
        if not self.pinned_host:
            raise RuntimeError("device exposes no pinned_host memory space")
        ids = jnp.asarray(np.asarray(block_ids, dtype=np.int32))
        return _to_pinned_host(_gather_block_major(self.kv, ids))

    def gather_block_major(self, block_ids: Sequence[int]) -> np.ndarray:
        """Block-major host gather ``[n, L, 2, bs, h, d]`` — the file
        byte layout, transposed on device (one copy fewer than
        :meth:`gather_to_host` + host moveaxis).  Pinned DMA when the
        backend supports it, plain transfer otherwise."""
        ids = jnp.asarray(np.asarray(block_ids, dtype=np.int32))
        gathered = _gather_block_major(self.kv, ids)
        if self.pinned_host:
            gathered = _to_pinned_host(gathered)
        return np.asarray(jax.device_get(gathered))

    def scatter_block_major(
        self, block_ids: Sequence[int], group: np.ndarray
    ) -> None:
        """Scatter a block-major ``[n, L, 2, bs, h, d]`` host group (the
        staging engine's slot/file layout) into the pool."""
        self.scatter_from_host(block_ids, np.moveaxis(group, 0, 1))

    def scatter_from_host(
        self,
        block_ids: Sequence[int],
        blocks: np.ndarray,
        donate: bool = False,
    ) -> None:
        """Upload a host block batch and scatter it into the pool.

        ``donate=True`` lets XLA reuse the old pool buffer (halves peak
        HBM) but deletes it — only safe when no external reference to
        ``self.kv`` exists (the serving loop holds one between steps,
        so the connector's async load path must keep the default).
        """
        ids = jnp.asarray(np.asarray(block_ids, dtype=np.int32))
        uploaded = jnp.asarray(blocks, dtype=self.kv.dtype)
        scatter = _scatter_donated if donate else _scatter
        self.kv = scatter(self.kv, ids, uploaded)

    def write_block(self, block_id: int, block: np.ndarray) -> None:
        """Test/demo helper: set one block's contents."""
        self.scatter_from_host([block_id], block[:, None])
