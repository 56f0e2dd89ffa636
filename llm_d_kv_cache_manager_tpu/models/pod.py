"""The pod's cache manager and its three compiled programs, generic over a
family's model step (the module that gives ``new_pool``, ``prefill_paged``,
``prefill_continue``, ``decode_step`` and, where the family's cache has more
than one kind of state, ``cache_policy``).

One group.  A logical block (one hash, ``block_size`` tokens) owns a slot of
the pool while it is cached; the allocator hands out free blocks first, then
least-recently-used cached blocks that no live sequence references, and gives
their hashes back as evicted (the engine publishes them as ``BlockRemoved``).
With no ``cache_policy`` this is all there is, rule for rule the benchmark's
``harness/pod.py``.  What a slot holds is the family's ``cache_groups`` spec's
business, not the pod's: where the one group is of the latent kind (one vector
a position a layer, key and value at once: models/glm4moelite.py) a block of
16 tokens is still a block, and the only thing the pod adds is that a decode
call says what the step reads of the group (``kv.read``: ``full_blocks``,
``latent_bytes`` from the spec's ``read_nbytes``, and ``step_bytes``, those
and the weights a step reads, which the policy's ``step_weight_nbytes``
states), since no window or state group is there to say it.  Where it is of
the selected kind (K and V per head and a selector key a position, of which
a query reads the keys of every position and K and V of the ``selected``
best: models/keyevl2.py) the call says that: ``index_bytes``,
``picked_bytes``, their sum ``sparse_bytes``, ``dense_bytes`` (what reading
every live position's K and V would be) and ``step_bytes``.

Two groups (a model that mixes window and full attention layers, after vLLM's
hybrid KV-cache manager).  The *full group* is the above: one slot per logical
block, K/V of the full-attention layers.  The *window group* has fewer slots,
each the sliding layers' K/V of one logical block, kept by these rules:

- every block handed out by ``alloc`` takes a window slot, except that of one
  call only the last ``store_blocks`` do (a miss prefill stores the window K/V
  of its trailing blocks only: the engine appends a call's blocks to the chain
  in the order they were handed out);
- a prefix of n blocks is a hit iff the full group holds all n and the window
  group holds the last ``ceil((window-1)/block_size)`` of them (all n if
  fewer): ``cached_prefix`` answers by that rule, and ``window_half_hits``
  counts the prefixes it had to refuse;
- a slot is *held* while its block is referenced by a live sequence and was in
  that sequence's window at the last decode step (or was written since), and
  while its block is referenced under a hash at all; every other slot is
  *released*: findable until it is reused;
- released slots are reused coldest first, those of blocks that no ask has
  named before those of asked ones (a hit warms and marks the blocks it used;
  a miss marks the blocks it stores under the hashes it asked for): a stream
  of never-asked suffixes cannot push out a prefix that is asked for again.
  ``protect_asked`` gives the full group the same order;
- **the index stays truthful without a new event**: where the window group
  reuses the slot of a block that is still cached, the pod evicts that block
  and the tail of every chain through it from the full group too, and their
  hashes ride in the list ``alloc`` returns, so ``BlockStored`` still means
  "servable from here" and ``BlockRemoved`` "no longer".

A state group (a model whose recurrent layers keep a state that is not
addressable by position: ``cache_policy`` names ``"state"``, in place of
``"window"`` or beside it).  A slot of it holds those layers' state after the last position
of one logical block, and a prefix of n blocks can be continued only where the
state after block n - 1 was kept:

- **which boundaries keep one**: every block whose index + 1 in its chain is
  a multiple of the group's ``stride_blocks`` and the last block of every
  prefill call (``KVGroupSpec.snapshot_blocks``: a miss prefill writes them, a
  hit prefill reads the one at its prefix's end and writes its own), and, for
  each live sequence, its current block and the one before: a decode step for
  the token at position p reads the slot of block ``(p - 1) // block`` and
  writes the slot of block ``p // block``, so a finished block's slot is its
  snapshot.  A sequence's own (unhashed) blocks behind those two give their
  slots back at once;
- **hit rule**: ``cached_prefix`` returns n blocks only if the full group
  holds all n and the state group the snapshot of block n - 1; else the
  longest such n, and ``resume_short_blocks`` counts what it gave up;
- released slots are reused coldest first, never-asked before asked, as the
  window group's, and **the index stays truthful at the boundaries that
  matter, without a new event**: where the group reuses the slot of a
  boundary whose block is still cached, the pod evicts the chain's tail from
  there and the hashes ride in ``alloc``'s list.  Between two kept boundaries
  the index over-counts a partial match by fewer than ``stride_blocks`` blocks
  (that much recompute, never a wrong pod): the event that would tell it
  ("state kept at block k") is ROADMAP R-M2's and not here;
- a decode step whose sequence starts at a position no step wrote the state
  of (the benchmark's set-up requests start ``done`` tokens into an answer) is
  still handed a slot: what it holds is whatever the slot last held.

Three groups (a model with window layers and recurrent layers:
``cache_policy`` names both, models/phi4flash.py).  ``Pod.groups`` lists the
groups beside the full one, and every rule above holds for each:

- **hit rule at one length**: ``cached_prefix`` returns n blocks only if the
  full group holds all n, the window group the last ``need`` of them and the
  state group the snapshot of block n - 1; else the longest n that every
  group admits (each group's ``admits`` answers for every prefix at once, so
  the search over the kept boundaries is one ``and``).  ``half_hits`` counts
  the whole prefixes the window rule alone refused, ``resume_short_blocks``
  what the length all groups agreed on gave up;
- **coupled eviction from either group**: a reused slot of a block that is
  still cached takes the chain's tail out of the full group and out of the
  other group, once, and the hashes ride in ``alloc``'s list;
- ``touch``, ``hold``, ``alloc`` and ``tables`` go through every group; a
  program call is handed the groups' tables side by side (``full``,
  ``window``, ``first``, ``state...``), a decode call's integers packed into
  one host argument whatever the number of groups;
- a window group may be ``lazy``: a block takes its slot when a program call
  first names it in a window table, not when ``alloc`` hands it out.  An
  answer of thousands of tokens is handed all its blocks at admission, and
  the last ``store_blocks`` of them would each hold a slot until the answer
  reaches them;
- ``specs`` (of the policy): the family's ``cache_groups``, which every
  group reads its window, its block and its bytes from.  Where the full
  group's spec names ``readers`` (later layers attend over one layer's K/V
  without a cache of their own) the spans price a block by them
  (``full_readers``, ``full_read_blocks``, ``kv_bytes``); nothing else in
  the pod depends on it.

Positions are never told to a pod; they are known at each program call (a
table is in chain order, decode brings ``context_len``), so ``jit_programs``
returns plain functions that build the groups' tables on the host, record
spans (``kvpool.window`` and ``kv.read``, ``kvpool.state`` and
``state.read``, each group its own; ``moe.expert_load``, ``attention.read``)
and call the inner compiled programs, which keep the names the trace
reduction looks for.  The rest of a call is spans too, each a real interval
stamped where the work happens: ``pod.counts_read`` (the read of the last
decode step's device counts), ``pod.pack`` (a decode call's packed argument
and its table on the device), ``pod.compile`` (one a program, on the call
that compiles) and ``pod.launch.decode`` / ``.hit`` / ``.miss`` (the compiled
call alone; a decode launch that follows a decode launch of the same pod
carries the period between the two).

A pod of one group may say ``decode_ahead`` in its policy
(models/keyevl2.py): a decode call that goes on from the call before it
launches the step after its own as well, and the next call is handed that
step (``jit_programs.run_ahead`` has the rule, and what a step that is not
taken leaves behind).  The groups beside the full one change their tables on
the host with every decode call, so a pod that has one refuses it.
"""

from __future__ import annotations

import time
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.obs.trace import root_trace, span

RESERVED = np.iinfo(np.int64).max  # a slot taken and not yet in any window
# What a decode step may hand back beside its pools: counts made on the device
# ("load": a row an expert layer; "attention_read": blocks read, blocks walked,
# blocks the walk brought by runs).
COUNTED = ("load", "attention_read")


def cache_policy(program, model) -> dict:
    """The family's `cache_policy(model)`; none means one group."""
    return getattr(program, "cache_policy", lambda model: {})(model)


class PodKV:
    """What a pod hands its programs as ``kv``: the arrays, and the pod whose
    host-side tables say where each layer kind reads and writes."""

    __slots__ = ("arrays", "_pod", "_table")

    def __init__(self, arrays, pod) -> None:
        # No cycle pod -> kv -> pod: a pod that is dropped frees its pools at
        # once, without waiting for (or being frozen out of) the collector.
        self.arrays, self._pod = arrays, weakref.ref(pod)
        self._table = (None, None)  # the last decode table, host and device

    def on_device(self, table) -> tuple[jax.Array, bool]:
        """The decode steps' logical table on the device, and whether this
        call sent it: again only when a row changed (an admission or a
        finish, not every step)."""
        table = np.asarray(table, np.int32)
        host, _ = self._table
        sent = (host is None or host.shape != table.shape
                or not np.array_equal(host, table))
        if sent:
            self._table = (table.copy(), jax.device_put(table))
        return self._table[1], sent

    @property
    def pod(self) -> "Pod":
        return self._pod()


class Cached:
    """hash -> block, least recently used first, as the engine writes it
    (``pod.cached[h] = block``).  Two segments: blocks no ask has named, and
    asked ones (only with ``protect_asked``; else all stay in the first).
    Keeps the reverse map that coupled evictions need."""

    def __init__(self, pod: "Pod") -> None:
        self.pod = weakref.proxy(pod)
        self.cold: OrderedDict[int, int] = OrderedDict()
        self.hot: OrderedDict[int, int] = OrderedDict()
        self.hash_of: dict[int, int] = {}

    def __contains__(self, h) -> bool:
        return h in self.cold or h in self.hot

    def __getitem__(self, h) -> int:
        return self.cold[h] if h in self.cold else self.hot[h]

    def __iter__(self):
        yield from self.cold
        yield from self.hot

    def items(self):
        yield from self.cold.items()
        yield from self.hot.items()

    def __setitem__(self, h, bid: int) -> None:
        segment = self.cold if h in self.cold else self.hot
        if h in segment:  # stored again elsewhere: its place in the order stays
            self._unhash(segment[h], h)
        else:
            asked = self.pod.protect_asked and h in self.pod.last_ask
            segment = self.hot if asked else self.cold
        segment[h] = bid
        self.hash_of[bid] = h
        self.pod.hashed[bid] = True
        self.pod.asked[bid] = segment is self.hot

    def __delitem__(self, h) -> None:
        self._unhash((self.cold if h in self.cold else self.hot).pop(h), h)

    def update(self, pairs) -> None:
        for h, bid in pairs:
            self[h] = bid

    def _unhash(self, bid: int, h) -> None:
        if self.hash_of.get(bid) == h:
            del self.hash_of[bid]
            self.pod.hashed[bid] = False

    def touch(self, h) -> None:
        """Most recently used; an asked block moves to the second segment."""
        if self.pod.protect_asked:
            bid = self.cold.pop(h, None)
            if bid is not None:
                self.hot[h] = bid
                self.pod.asked[bid] = True
                return
        (self.cold if h in self.cold else self.hot).move_to_end(h)

    def coldest_unreferenced(self, n: int) -> list:
        """Up to n (hash, block) no live sequence references, in the order
        they go: never-asked first, least recently used first."""
        out = []
        for segment in (self.cold, self.hot):
            for h, bid in segment.items():
                if len(out) == n:
                    return out
                if not self.pod.refs[bid]:
                    out.append((h, bid))
        return out


class SlotGroup:
    """A second group of slots beside the full group: which logical block
    holds which slot, when each was last used, and the chains the blocks
    stand in.  `WindowGroup` and `StateGroup` add their rules."""

    span, read_span = "", ""  # the spans `Pod.tables` records

    def __init__(self, pod: "Pod", slots: int, counts: tuple) -> None:
        self.pod = weakref.proxy(pod)
        self.slot_of = np.full(pod.pool_blocks, -1, np.int32)
        self.block_of = np.full(slots, -1, np.int32)
        self.stamp = np.zeros(slots, np.int64)  # tick it was last live
        self.free = list(range(slots - 1, -1, -1))
        self.tick = self.live_tick = 0
        self.parent: dict[int, int] = {}
        self.children: dict[int, set] = {}
        # over the pod's life; a span reports what was added since the last
        self.counts = dict.fromkeys(counts, 0)
        self.reported = dict(self.counts)

    # -- bookkeeping by block ------------------------------------------

    def drop(self, bid: int) -> None:
        slot = self.slot_of[bid]
        if slot >= 0:
            self.slot_of[bid], self.block_of[slot] = -1, -1
            self.free.append(int(slot))

    def forget(self, bid: int) -> None:
        """A block that is handed out again starts with no slot, no place in
        a chain and no ask to its name."""
        self.drop(bid)
        parent = self.parent.pop(bid, None)
        if parent is not None:
            self.children[parent].discard(bid)
        for child in self.children.pop(bid, ()):
            self.parent.pop(child, None)
        self.pod.asked[bid] = False

    def link(self, chain) -> None:
        for a, b in zip(chain[:-1], chain[1:]):
            a, b = int(a), int(b)
            if self.parent.get(b) != a:
                self.parent[b] = a
                self.children.setdefault(a, set()).add(b)

    def tail(self, bid: int) -> list:
        """The block and every block of a chain through it, behind it."""
        out, stack = [], [bid]
        while stack:
            out.append(stack.pop())
            stack.extend(self.children.get(out[-1], ()))
        return out

    def warm(self, ids) -> None:
        slots = self.slot_of[np.asarray(ids, np.int64)]
        self.stamp[slots[slots >= 0]] = self.tick

    def _assign(self, bid: int) -> None:
        slot = self.free.pop()
        self.slot_of[bid], self.block_of[slot] = slot, bid
        self.stamp[slot] = RESERVED
        self.counts["taken"] += 1

    def _unnamed(self, keep: np.ndarray) -> np.ndarray:
        """Per slot: its block is none of `keep` (the call at hand's)."""
        named = np.zeros(self.pod.pool_blocks, bool)
        named[keep.ravel()] = True
        return ~named[np.maximum(self.block_of, 0)]

    def _ensure(self, ids: np.ndarray, keep: np.ndarray) -> None:
        """The blocks of `ids` that hold no slot take one (a block just
        handed out has none), reclaiming none of `keep`'s.  What a reuse
        evicts from the full group waits for the next `alloc`."""
        missing = np.unique(ids[self.slot_of[ids] < 0])
        if len(missing) > len(self.free):
            self.pod.unpublished += self.reclaim(len(missing), keep)
        for bid in missing:
            self._assign(int(bid))

    def _reclaim(self, released: np.ndarray, target: int, what: str) -> list:
        """Free slots until `target` are free, of those `released` marks:
        never-asked before asked, coldest first; a cached block goes with
        its tail, out of the full group too.  Returns the hashes evicted."""
        pod = self.pod
        slots = np.flatnonzero((self.block_of >= 0) & released)
        order = slots[np.lexsort((self.stamp[slots],
                                  pod.asked[self.block_of[slots]]))]
        evicted = []
        for slot in order:
            if len(self.free) >= target:
                break
            bid = int(self.block_of[slot])
            if bid < 0:
                continue  # went with an earlier block's tail
            before = len(self.free)
            if pod.hashed[bid]:
                evicted += pod.evict_tail(bid)
            else:
                self.drop(bid)
            self.counts["reclaimed"] += len(self.free) - before
        if len(self.free) < target:
            raise RuntimeError(
                f"{pod.name}: {what} group exhausted by live sequences")
        return evicted


class WindowGroup(SlotGroup):
    """The sliding layers' K/V of one logical block a slot."""

    span, read_span = "kvpool.window", "kv.read"

    def __init__(self, pod: "Pod", slots: int, store_blocks: int,
                 lazy: bool = False) -> None:
        super().__init__(pod, slots,
                         ("taken", "released", "reclaimed", "half_hits"))
        self.spec = pod.specs["window"]
        self.window, self.block = self.spec.window, self.spec.block_size
        # blocks behind a boundary
        self.need = -(-(self.window - 1) // self.block)
        self.width = self.need + 1  # blocks a decode step's window can span
        self.store = store_blocks
        # `lazy`: a block takes its slot when a program call first names it in
        # a window table, not when `alloc` hands it out (an answer of
        # thousands of tokens is handed its blocks at once, and its last
        # `store` would each hold a slot until the answer reaches them).
        self.lazy = lazy

    # -- the rules ------------------------------------------------------

    def admits(self, ids: list) -> np.ndarray:
        """For each prefix of `ids` (cached in the full group, chain order),
        whether its last `need` blocks (all if fewer) hold a window slot."""
        have = self.slot_of[np.asarray(ids, np.int64)] >= 0
        n = np.arange(1, len(ids) + 1)
        run = n - np.maximum.accumulate(np.where(have, 0, n))
        return run >= np.minimum(n, self.need)

    def count_ask(self, own: np.ndarray, asked: int, m: int) -> None:
        """An ask of `asked` blocks, all found in the full group, that this
        group's rule alone refuses, is a half hit."""
        if len(own) and len(own) == asked and not own[-1]:
            self.counts["half_hits"] += 1

    def take(self, ids: list) -> list:
        """Slots for the blocks one `alloc` hands out (the last `store` of
        them; none yet where the group is `lazy`); returns the hashes a
        reuse evicted from the full group."""
        for bid in ids:
            self.forget(bid)
        want = [] if self.lazy else ids[-self.store:]
        evicted = self.reclaim(len(want)) if len(self.free) < len(want) else []
        for bid in want:
            self._assign(bid)
        return evicted

    def reclaim(self, target: int, keep=None) -> list:
        """Free slots until `target` are free: released ones, never-asked
        before asked, coldest first; a cached block goes with its tail.
        `keep`: blocks the call at hand names, which stay."""
        pod = self.pod
        block = np.maximum(self.block_of, 0)
        released = (pod.refs[block] == 0) | (
            ~pod.hashed[block] & (self.stamp < self.live_tick))
        if keep is not None:
            released &= self._unnamed(keep)
        return self._reclaim(released, target, "window")

    # -- one table a program call ----------------------------------------

    def _slots(self, ids: np.ndarray, what: str) -> np.ndarray:
        slots = self.slot_of[ids]
        if (slots < 0).any():
            raise RuntimeError(
                f"{self.pod.name}: {what} reads a block that holds no window "
                "slot (a prefix the window rule refuses, or blocks used out "
                "of the order they were handed out)")
        self.tick += 1
        self.stamp[slots] = self.tick
        return slots.astype(np.int32)

    def miss_tables(self, table: np.ndarray) -> dict:
        kept = min(table.shape[1], self.store)
        for row in table:
            self.link(row)
        if self.lazy:
            self._ensure(table[:, table.shape[1] - kept:], table)
        return {"full": table,
                "window": self._slots(table[:, table.shape[1] - kept:],
                                      "a miss prefill")}

    def hit_tables(self, table: np.ndarray, prefix_blocks: int) -> dict:
        seen = min(prefix_blocks, self.need)
        for row in table:
            self.link(row[max(prefix_blocks - 1, 0):])
        if self.lazy:
            self._ensure(table[:, prefix_blocks:], table)
        return {"full": table,
                "window": self._slots(table[:, prefix_blocks - seen:],
                                      "a hit prefill")}

    def decode_tables(self, table: np.ndarray, context_len: np.ndarray):
        """The window table of one decode step and what the step reads:
        (tables, {"full_blocks", "window_blocks", "uniform_blocks"})."""
        first = np.maximum(context_len - self.window, 0) // self.block
        current = (context_len - 1) // self.block
        cols = first[:, None] + np.arange(self.width)[None, :]
        ids = np.take_along_axis(
            table, np.minimum(cols, table.shape[1] - 1), axis=1)
        at = np.take_along_axis(table, current[:, None], axis=1)
        ids = np.where(cols <= current[:, None], ids, at)  # padding: current
        # A block that comes back into a window after its slot was reused
        # (the engine's scratch block, when a decode slot falls idle again)
        # takes one anew; what a reuse evicts waits for the next `alloc`.
        missing = self.slot_of[ids] < 0
        if missing.any():
            for bid in np.unique(ids[missing]):
                if not self.free:
                    self.pod.unpublished += self.reclaim(1)
                self._assign(int(bid))
        before = self.live_tick
        slots = self._slots(ids, "a decode step")
        self.live_tick = self.tick
        # live at the last decode step, in no window now
        self.counts["released"] += int((self.stamp == before).sum()) if before else 0
        window_blocks = int((current - first + 1).sum())
        whole = int((current + 1).sum())
        return ({"full": table, "window": slots,
                 "first": (first * self.block).astype(np.int32)},
                {"full_blocks": whole, "window_blocks": window_blocks,
                 "uniform_blocks": whole})


class StateGroup(SlotGroup):
    """The recurrent layers' state after the last position of one logical
    block a slot (the module's head has the rules)."""

    span, read_span = "kvpool.state", "state.read"

    def __init__(self, pod: "Pod", slots: int) -> None:
        super().__init__(pod, slots, ("taken", "released", "reclaimed",
                                      "resume_short_blocks", "asked_blocks"))
        self.spec = pod.specs["state"]
        self.block = self.spec.block_size
        # K/V bytes a step reads of a block of context (`state.read`)
        self.kv_read_nbytes = pod.specs["full"].read_nbytes

    def admits(self, ids: list) -> np.ndarray:
        """For each prefix of `ids` (cached in the full group, chain order),
        whether its last block holds a snapshot."""
        return self.slot_of[np.asarray(ids, np.int64)] >= 0

    def count_ask(self, own: np.ndarray, asked: int, m: int) -> None:
        """What an ask that found `len(own)` blocks in the full group gave
        up by resuming at `m` (the pod's one length over all its groups)."""
        self.counts["asked_blocks"] += asked
        self.counts["resume_short_blocks"] += len(own) - m

    def take(self, ids: list) -> list:
        """Blocks that `alloc` hands out start with no slot: which of them
        keep one is known when a program call brings their place in the
        chain."""
        for bid in ids:
            self.forget(bid)
        return []

    def reclaim(self, target: int, keep: np.ndarray) -> list:
        """Free slots until `target` are free, of blocks no live sequence
        references and the call at hand does not name (`keep`)."""
        block = np.maximum(self.block_of, 0)
        return self._reclaim((self.pod.refs[block] == 0) & self._unnamed(keep),
                             target, "state")

    def _slots(self, ids: np.ndarray) -> np.ndarray:
        """The slots of `ids`, stamped; a block without one takes one."""
        self._ensure(ids, ids)
        slots = self.slot_of[ids]
        self.tick += 1
        self.stamp[slots] = self.tick
        return slots.astype(np.int32)

    def _prefill_tables(self, table: np.ndarray, first: int) -> dict:
        kept = self.spec.snapshot_blocks(first, table.shape[1] - first)
        for row in table:
            self.link(row[max(first - 1, 0):])
        return {"full": table, "state_write": self._slots(table[:, kept])}

    def miss_tables(self, table: np.ndarray) -> dict:
        return self._prefill_tables(table, 0)

    def hit_tables(self, table: np.ndarray, prefix_blocks: int) -> dict:
        last = table[:, prefix_blocks - 1]
        if (self.slot_of[last] < 0).any():
            raise RuntimeError(
                f"{self.pod.name}: a hit prefill resumes from a block that "
                "holds no snapshot (a prefix the state rule refuses)")
        tables = self._prefill_tables(table, prefix_blocks)  # may reclaim
        read = self.slot_of[last]  # ... but never a block this table names
        self.stamp[read] = self.tick
        return {**tables, "state_read": read.astype(np.int32)}

    def decode_tables(self, table: np.ndarray, context_len: np.ndarray):
        """The state slots of one decode step, [B, (read, written)], and
        what the step reads."""
        pod, rows = self.pod, np.arange(len(table))
        pos = context_len - 1
        cur, prev = pos // self.block, np.maximum(pos - 1, 0) // self.block
        # A sequence that enters a new block: the block two back is no
        # longer one of its two; its own blocks carry no hash to keep it for.
        entered = np.flatnonzero((pos % self.block == 0) & (cur >= 2))
        old = table[entered, cur[entered] - 2]
        for bid in old[~pod.hashed[old] & (self.slot_of[old] >= 0)]:
            self.drop(int(bid))
            self.counts["released"] += 1
        ids = np.stack((table[rows, prev], table[rows, cur]), axis=1)
        slots = self._slots(ids)
        self.live_tick = self.tick
        live = context_len > 1
        blocks = int((cur[live] + 1).sum())
        return ({"full": table, "state": slots},
                {"state_slots_live": len(self.block_of) - len(self.free),
                 "blocks_live": pod.pool_blocks - len(pod.free),
                 "state_bytes": int(live.sum()) * 2 * self.spec.block_nbytes,
                 "kv_bytes": blocks * self.kv_read_nbytes})


class Pod:
    """One serving pod on the chip: its paged K/V pools and prefix cache.  The
    allocator never hands out a block a live sequence references."""

    def __init__(self, name: str, program, model, pool_blocks: int) -> None:
        self.name = name
        self.pool_blocks = pool_blocks
        self.kv = PodKV(program.new_pool(model, pool_blocks), self)
        self.free = list(range(pool_blocks - 1, -1, -1))
        self.refs = np.zeros(pool_blocks, np.int32)  # block -> live sequences
        self.asked = np.zeros(pool_blocks, bool)  # block -> named by an ask
        self.hashed = np.zeros(pool_blocks, bool)  # block -> cached under a hash
        policy = cache_policy(program, model)
        self.protect_asked = bool(policy.get("protect_asked"))
        self.last_ask: frozenset = frozenset()  # the last missed ask's hashes
        self.cached = Cached(self)
        self.unpublished: list[int] = []  # evicted outside `alloc`
        # the family's `cache_groups`: what a slot of each group holds, how
        # many layers read it; the groups and the spans read them from here
        self.specs = policy.get("specs") or {}
        self.step_weight_nbytes = policy.get("step_weight_nbytes", 0)
        self.window = (WindowGroup(self, **policy["window"])
                       if policy.get("window") else None)
        self.state = (StateGroup(self, **policy["state"])
                      if policy.get("state") else None)
        # the groups beside the full one; each keeps the chains (they are
        # told the same links), so any of them answers `tail`
        self.groups = [g for g in (self.window, self.state) if g is not None]
        self.pending_load = None  # (a decode step's device counts, its tokens)
        self.last_launch = None  # (kind, `perf_counter`) of the last program call
        # `jit_programs`' decode call launches the step after its own too,
        # and what the last such call left for the next (`run_ahead`)
        self.decode_ahead = bool(policy.get("decode_ahead"))
        self.last_decode = None
        if self.decode_ahead and self.groups:
            raise ValueError(
                f"{name}: decode_ahead is for a pod of one group (a window or "
                "state group's tables change on the host with every step)")

    def cached_prefix(self, hashes) -> list[int]:
        ids = []
        for h in hashes:
            if h not in self.cached:
                break
            ids.append(self.cached[h])
        if self.groups:
            # one length for all groups: the longest prefix each admits
            own = [g.admits(ids) for g in self.groups]
            both = np.flatnonzero(np.logical_and.reduce(own))
            m = int(both[-1]) + 1 if len(both) else 0
            for g, admitted in zip(self.groups, own):
                g.count_ask(admitted, len(hashes), m)
            ids = ids[:m]
        if len(ids) < len(hashes):
            self.last_ask = frozenset(hashes)
        return ids

    def touch(self, hashes) -> None:
        for h in hashes:
            self.cached.touch(h)
        if self.window is not None and len(hashes):
            self.window.warm([self.cached[h]
                              for h in hashes[-self.window.need:]])
        if self.state is not None and len(hashes):
            self.state.warm([self.cached[hashes[-1]]])

    def alloc(self, n: int) -> tuple[list[int], list[int]]:
        """n blocks no live sequence references; returns (ids, hashes evicted)."""
        ids, evicted = [], []
        while len(ids) < n and self.free:
            ids.append(self.free.pop())
        for h, bid in self.cached.coldest_unreferenced(n - len(ids)):
            del self.cached[h]
            evicted.append(h)
            ids.append(bid)
        if len(ids) < n:
            raise RuntimeError(f"{self.name}: pool exhausted by live sequences")
        if self.groups:
            for group in self.groups:
                evicted += group.take(ids)
            evicted += self.unpublished
            self.unpublished = []
        return ids, evicted

    def evict_tail(self, bid: int) -> list[int]:
        """Out of the full group: a block and every cached block behind it in
        a chain.  Returns their hashes; their ids go back to the free list."""
        out = []
        for x in self.groups[0].tail(bid):
            h = self.cached.hash_of.get(x)
            for group in self.groups:
                group.forget(x)
            if h is None:
                continue
            if self.refs[x]:  # a sequence holds its whole chain or none of it
                raise RuntimeError(f"{self.name}: block {x} is referenced "
                                   f"behind block {bid}, which is not")
            del self.cached[h]
            out.append(h)
            self.free.append(x)
        return out

    def hold(self, ids, by: int) -> None:
        ids = np.asarray(ids, np.int64)
        np.add.at(self.refs, ids, by)
        if by > 0 or not len(ids):
            return
        if self.groups:  # a sequence's own blocks: no hash to keep
            idle = ids[self.refs[ids] == 0]
            for bid in idle[~self.hashed[idle]]:
                for group in self.groups:
                    group.drop(bid)

    def tables(self, kind: str, table, context_len=None, prefix_blocks=0):
        """What a program call is handed as its table: the logical table
        alone with one group, every group's tables with more."""
        table = np.asarray(table, np.int32)
        if not self.groups:
            full = self.specs.get("full")
            if kind == "decode" and full is not None and (
                    full.latent_dim or full.selector_dim):
                # the full group alone: what the step reads of it
                live = np.asarray(context_len, np.int64)
                blocks = int(((live - 1) // full.block_size + 1).sum())
                with span("kv.read") as s:
                    s.set_attr("full_blocks", blocks)
                    if full.latent_dim:
                        read = blocks * full.read_nbytes
                        s.set_attr("latent_bytes", read)
                    else:
                        # every live position's selector key, and K and V of
                        # the positions a query picks; beside them what
                        # reading every live position's K and V would be
                        position = full.read_nbytes // full.block_size
                        key = (full.num_readers * full.selector_dim
                               * jnp.dtype(full.dtype).itemsize)
                        index = int(live.sum()) * key
                        picked = int(np.minimum(live, full.selected).sum()) \
                            * (position - key)
                        read = index + picked
                        s.set_attr("index_bytes", index)
                        s.set_attr("picked_bytes", picked)
                        s.set_attr("sparse_bytes", read)
                        s.set_attr("dense_bytes",
                                   int(live.sum()) * (position - key))
                    s.set_attr("step_bytes", read + self.step_weight_nbytes)
            return table
        tables, reads = {}, {}
        for group in self.groups:
            with span(group.span) as s:
                if kind == "decode":
                    more, reads[group.read_span] = group.decode_tables(
                        table, np.asarray(context_len, np.int64))
                else:
                    more = (group.miss_tables(table) if kind == "miss"
                            else group.hit_tables(table, prefix_blocks))
                tables.update(more)
                s.set_attr("calls", 1)
                for key, value in group.counts.items():
                    s.set_attr(key, value - group.reported[key])
                group.reported = dict(group.counts)
        full = self.specs.get("full")
        if full is not None and full.readers and "kv.read" in reads:
            # later layers attend over the full group's K/V without a cache
            # of their own: a block is priced by the layers that read it
            read = reads["kv.read"]
            read["full_readers"] = full.readers
            read["full_read_blocks"] = read["full_blocks"] * full.readers
            if "state.read" in reads:  # what the window layers read beside
                reads["state.read"]["kv_bytes"] += (
                    read["window_blocks"] * self.window.spec.read_nbytes)
        for name, read in reads.items():
            with span(name) as s:
                for key, value in read.items():
                    s.set_attr(key, value)
        return tables

    def report_load(self, model) -> None:
        """What the last decode step counted on the device, as spans: the
        blocks its attention read against a walk of every table, and the
        expert layers' loads.  They were sent on their way to the host when
        the step was launched (`keep_load`) and that step has long ended (its
        tokens were read back), so the read finds them there:
        `pod.counts_read` is what it waited all the same."""
        if self.pending_load is None:
            return
        counted, tokens = self.pending_load
        self.pending_load = None
        with span("pod.counts_read") as s:
            counted = {k: np.asarray(a) for k, a in counted.items()}
            s.set_attr("arrays", len(counted))
            s.set_attr("bytes", sum(a.nbytes for a in counted.values()))
        if "attention_read" in counted:
            read, walked, by_runs = counted["attention_read"]
            with span("attention.read") as s:
                s.set_attr("read_blocks", int(read))
                s.set_attr("walked_blocks", int(walked))
                s.set_attr("run_blocks", int(by_runs))
        for layer, (touched, most) in enumerate(counted.get("load", ())):
            with span("moe.expert_load") as s:
                s.set_attr("layer", layer)
                s.set_attr("experts_held", model.n_experts)
                s.set_attr("experts_touched", int(touched))
                s.set_attr("max_tokens", int(most))
                s.set_attr("mean_tokens",
                           tokens * model.top_k / model.n_experts)

    def keep_load(self, counted: dict, tokens: int) -> None:
        """A traced decode step's device counts, kept for the next call's
        `report_load` and started on their way to the host now, behind the
        step that makes them: a read that asks only then is a round trip to
        the device of its own (0.55–0.63 ms on a v5e: PERF.md section 6,
        PR 37), which an untraced step never makes."""
        for array in counted.values():
            array.copy_to_host_async()
        self.pending_load = (counted, tokens)


def inner_programs(program, model, shapes: dict, interpret: bool) -> dict:
    """The three steps as jitted functions over (params, tokens, pools,
    tables[, context_len]), named so that the trace reduction finds them
    (`miss_prefill_T..`, `hit_prefill_P.._S..`, `decode_B..`).  Each returns
    the greedy tokens with their logits as one array (one transfer to the
    host) and, for a prefill, the last position's row of logits; the pools
    are donated whole and updated in place."""

    policy = cache_policy(program, model)
    windowed, stateful = bool(policy.get("window")), bool(policy.get("state"))
    ahead = bool(policy.get("decode_ahead"))

    def served(logits):
        return jnp.stack((jnp.argmax(logits, -1).astype(jnp.float32),
                          jnp.max(logits, -1)))

    def last(logits, kv):
        return served(logits[:, -1]), logits[0, -1], kv

    def miss(p, t, kv, bt):
        return last(*program.prefill_paged(p, t, kv, bt, model,
                                           interpret=interpret))

    def hit(p, t, kv, bt):
        return last(*program.prefill_continue(
            p, t, kv, bt, shapes["hit"][0], model, interpret=interpret))

    def decode(p, ints, kv, table):
        """`ints` [B, 2 + (1 + window width) + 2] int32: each sequence's
        token, its context length and, with a window group, the position its
        window table starts at and that table's slots, and, with a state
        group, the state slot it reads and the one it writes, last.  One
        array, because every
        argument that comes from the host costs a transfer of its own (0.12 ms
        each on the chip's host; my chip run, PR 29); `table` stays on the
        device between the steps that do not change it.  With the policy's
        `decode_ahead` the first argument is a pair, what a step before
        served (`served`'s array, where it lies on the device) and the
        integers: a row whose token is -1 takes the token served there."""
        if ahead:
            before, ints = ints
            ints = ints.at[:, 0].set(jnp.where(
                ints[:, 0] < 0, before[0].astype(jnp.int32), ints[:, 0]))
        end = ints.shape[1] - 2 * stateful
        if ints.shape[1] == 2:
            tables = table
        else:
            tables = {"full": table}
            if windowed:
                tables.update(first=ints[:, 2], window=ints[:, 3:end])
            if stateful:
                tables["state"] = ints[:, end:]
        logits, kv = program.decode_step(p, ints[:, 0], kv, tables, ints[:, 1],
                                         model, interpret=interpret)
        return served(logits), kv

    inner = {}
    for fn, key, name in ((miss, "miss", "miss_prefill_T{}"),
                          (hit, "hit", "hit_prefill_P{}_S{}"),
                          (decode, "decode", "decode_B{}")):
        if key in shapes:
            fn.__name__ = fn.__qualname__ = name.format(*shapes[key])
            inner[key] = jax.jit(fn, donate_argnums=(2,))
    return inner


def example_args(key: str, shapes: dict, pod: "Pod", block: int) -> tuple:
    """What follows the parameters and the pools in a call of the step `key`,
    in its shapes: (tokens, tables) of a prefill, (ints, table) of a decode
    step; the tables name block 0 only and touch no state."""
    i32 = np.int32
    if key == "decode":
        B = shapes["decode"][0]
        width = (2 + (pod.window is not None and 1 + pod.window.width)
                 + 2 * (pod.state is not None))
        ints = np.ones((B, width), i32)
        if pod.decode_ahead:
            ints = (np.zeros((2, B), np.float32), ints)
        return ints, np.zeros((B, shapes["max_blocks"]), i32)
    tokens = sum(shapes[key])
    pre = shapes[key][0] // block if key == "hit" else 0
    return (np.zeros((1, tokens - pre * block), i32),
            _dry_tables(pod, key, np.zeros((1, tokens // block), i32),
                        prefix_blocks=pre))


def _launch_span(key: str):
    """The span around one compiled call.  Three literal names: a reader
    filters by trace and span name alone, so the kind is in the name, and
    `hack/kvlint` (KV007) holds each to its row of docs/observability.md."""
    if key == "decode":
        return span("pod.launch.decode")
    if key == "hit":
        return span("pod.launch.hit")
    return span("pod.launch.miss")


def jit_programs(program, model, shapes: dict, interpret: bool) -> dict:
    """The cell's steps as plain functions over a pod's `kv` handle.  Each
    builds its tables on the host (`Pod.tables`), then calls the inner
    compiled program (`inner_programs`).  All three are compiled at the first
    call of any (set-up), so that a shape first used inside a measured window
    does not compile there: a `pod.compile` span a program on that call, and
    none after it.

    Where the family's policy says `decode_ahead` (a pod of one group: its
    tables keep nothing on the host that a step not taken would have to give
    back), a decode call that goes on from the call before it (`run_ahead`)
    launches the step after its own as well, on the tokens its own step
    serves, which are on the device before the host has them; the next call,
    if it goes on in turn, is handed that step.  The host's share of a step
    (the launch, the tokens' way to the host and back) then lies beside the
    device's work and not between two steps."""
    block = model.block_size
    inner, compiled = inner_programs(program, model, shapes, interpret), {}

    def spec(x):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)

    def run(key, p, kv, first, second, traced, ahead=False):
        pod, fresh = kv.pod, 0
        if not compiled:
            for k, fn in inner.items():
                a, b = ((first, second) if k == key
                        else example_args(k, shapes, pod, block))
                with span("pod.compile") as s:
                    s.set_attr("program", fn.__name__)
                    compiled[k] = fn.lower(p, jax.tree.map(spec, a), kv.arrays,
                                           jax.tree.map(spec, b)).compile()
            fresh = len(compiled)
        # the period between two decode launches with no prefill between, as
        # the program itself reads it; kept on every call, so that a sample
        # rate under 1 still knows what the call before was
        name = inner[key].__name__
        last, now = pod.last_launch, time.perf_counter()
        pod.last_launch = (key, now)
        with _launch_span(key) as s:
            s.set_attr("program", name)
            if key == "decode":
                after = last is not None and last[0] == "decode"
                s.set_attr("after_decode", int(after))
                if after:
                    s.set_attr("since_prev_launch_s", now - last[1])
                if ahead:  # the step after the call's own
                    s.set_attr("ahead", 1)
            *out, kv.arrays = compiled[key](p, first, kv.arrays, second)
        if traced is not None:
            traced.set_attr("kind", key)
            traced.set_attr("program", name)
            traced.set_attr("compiled", fresh)
        # what a step counted on the device is no part of the pools: it is
        # not handed back in, so reading it later finds it alive
        counted = ({k: kv.arrays.pop(k) for k in COUNTED if k in kv.arrays}
                   if isinstance(kv.arrays, dict) else {})
        return (*out, kv), counted

    def prefill(key):
        prefix_blocks = shapes[key][0] // block if key == "hit" else 0

        def run_prefill(p, t, kv, bt):
            with root_trace("pod.step") as traced:
                tables = kv.pod.tables(key, bt, prefix_blocks=prefix_blocks)
                kv.pod.last_decode = None  # no decode call goes on from here
                return run(key, p, kv, np.asarray(t, np.int32), tables,
                           traced)[0]

        return run_prefill

    def follows(last, ints, sent) -> bool:
        """Whether the decode call of `ints` goes on from the call that left
        `last`: the same table (`sent`: it was not sent again), each context
        one longer, each token the one that call served.  (Reading what it
        served costs nothing where the engine has read it.)"""
        return (last is not None and not sent
                and np.array_equal(ints[:, 1], last[0][:, 1] + 1)
                and np.array_equal(ints[:, 0], np.asarray(last[1])[0]))

    def run_ahead(p, kv, ints, table, goes_on, traced):
        """A decode call of a `decode_ahead` pod: (what `run` returns of the
        call's own step).  `pod.last_decode` is what the last call left,
        (its integers, the array it served, the step it launched ahead or
        None), and None after a prefill.  This call goes on from that one
        (`goes_on`, `follows`) where the table is the same, every context is
        one longer and the tokens are those served: then the step launched
        ahead, if there is one, is this call's, and this call launches the
        next.  Where it does not go on (an admission, a finish, the first
        step) it launches its own step on the host's tokens and none ahead: a
        step launched ahead and not taken costs the device a step, so one is
        launched only where the last call shows that the engine is in the
        middle of its sequences.  Such a step has written, for each row, the
        position after the row's last in the table it was launched with:
        where the row goes on, the step that takes its place writes the same
        there; where it ended, the place is the ended sequence's own or the
        engine's scratch block, which whoever is handed the block next writes
        before reading it."""
        pod = kv.pod
        last, pod.last_decode = pod.last_decode, None
        if goes_on and last[2] is not None:
            out, counted = last[2]
        else:
            before = (last[1] if last is not None
                      else np.zeros((2, len(ints)), np.float32))
            out, counted = run("decode", p, kv, (before, ints), table, traced)
        ahead = None
        if goes_on:
            after = ints + np.asarray([0, 1], np.int32)
            after[:, 0] = -1  # the tokens `out` serves, where they lie
            ahead = run("decode", p, kv, (out[0], after), table, traced,
                        ahead=True)
        pod.last_decode = (ints, out[0], ahead)
        return out, counted

    def run_decode(p, t, kv, bt, n):
        pod = kv.pod
        with root_trace("pod.step") as traced:
            tables = pod.tables("decode", bt, context_len=n)
            if traced is not None:
                pod.report_load(model)
            with span("pod.pack") as s:
                ints = [t, n]
                if pod.window is not None:
                    ints += [tables["first"], *tables["window"].T]
                if pod.state is not None:
                    ints += [*tables["state"].T]
                ints = np.stack(ints, axis=1, dtype=np.int32)
                table, sent = kv.on_device(bt)
                s.set_attr("calls", 1)
                s.set_attr("table_sent", int(sent))
                s.set_attr("h2d_bytes", table.nbytes if sent else 0)
                if pod.decode_ahead:
                    goes_on = follows(pod.last_decode, ints, sent)
                    # the call's own step was launched by the call before
                    s.set_attr("ahead", int(
                        goes_on and pod.last_decode[2] is not None))
            if pod.decode_ahead:
                out, counted = run_ahead(p, kv, ints, table, goes_on, traced)
            else:
                out, counted = run("decode", p, kv, ints, table, traced)
            if traced is not None and counted:
                pod.keep_load(counted, len(t))
            return out

    return {key: run_decode if key == "decode" else prefill(key)
            for key in inner}


def _dry_tables(pod: Pod, kind: str, table, prefix_blocks=0):
    """A prefill's tables in the shapes `Pod.tables` would give, for
    compiling ahead."""
    if pod.window is None and pod.state is None:
        return table
    tables = {"full": table}
    if pod.state is not None:
        kept = pod.state.spec.snapshot_blocks(
            prefix_blocks, table.shape[1] - prefix_blocks)
        rows = np.zeros(table.shape[0], np.int32)
        tables["state_write"] = np.zeros((len(rows), len(kept)), np.int32)
        if kind == "hit":
            tables["state_read"] = rows
    group = pod.window
    if group is not None:
        kept = (min(table.shape[1], group.store) if kind == "miss" else
                table.shape[1] - prefix_blocks + min(prefix_blocks, group.need))
        tables["window"] = np.zeros((table.shape[0], kept), np.int32)
    return tables
