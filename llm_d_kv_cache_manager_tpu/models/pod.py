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

A family may say ``decode_ahead`` in its policy (models/keyevl2.py,
models/phi4flash.py): a decode call that goes on from the call before it
launches the step after its own as well, and the next call is handed that
step (``jit_programs.run_ahead`` has the rule, and what a step that is not
taken leaves behind).  Beside a window or a state group the step's tables
are made a call early too (``decode_slots``: a block takes its slot there;
the call that takes the step says that it is served, ``decode_tables``, and
records the groups' spans, once a served step), and nothing a step wrote may
have to be taken back:

- a window layer's K/V, as the full group's, is the same values at the same
  place when the step is run again, so the window group has no rule to add;
- a state slot is written in place of the state it held, so under the key a
  sequence **alternates between two slots**: a step reads the slot the last
  served step wrote (``slot_of`` its block) and writes the other
  (``spare_of``), and serving the step exchanges them.  A step that is run
  and not taken has written a slot nobody reads, and the step that takes its
  place reads and writes the same two.  They are the two slots a live
  sequence holds anyway (its current block's and the one before): the step
  that enters a block writes the spare, which is the block's slot from then
  on, and the first step inside the block takes the slot of the block before
  for its spare, unless a hash keeps that snapshot (a sequence's own blocks
  carry none).  Without the key a block has one slot and the tables are what
  they were;
- a slot that a reuse evicts on behalf of a step made ahead goes the way of
  every eviction outside ``alloc`` (``unpublished``, on the next ``alloc``'s
  list), taken or not.

**Who says the key.**  A call that does not go on (a sequence ended, one was
admitted) launches its own step alone, so an event costs two calls and the
share of calls served by a step launched ahead is about 1 − 2 × (events a
step); every step launched and not taken costs the device a whole step.
``keyevl2`` (24 slots, 191 tokens out on average: 0.126 events a step) reads
0.75, ``phi4flash`` (64 slots, 3072–5120 out: one event in 64 steps) ≈ 0.97.
``afmoe``, ``lfm2moe`` and ``glm4moelite`` do **not** say it: their
deployments' traffic as the benchmark has it (64 slots, 64–512 out, mean
192) ends a sequence every third step, the share would be 0.33–0.46, under
the half a median needs, and the steps not taken would cost a seventh to a
fifth of the chip (≈ 15 ms × 119 admissions in a 12-s window of
chat-longdocs; PERF.md section 7 has the counts).  They
wait for an engine that says which rows end, so that the step ahead can
leave those rows out (ROADMAP S12 i, D17: the key goes then).
"""

from __future__ import annotations

import time
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from llm_d_kv_cache_manager_tpu.obs.trace import root_trace, span
from llm_d_kv_cache_manager_tpu.ops.paged_decode_pallas import READ_COUNTS

RESERVED = np.iinfo(np.int64).max  # a slot taken and not yet in any window
# What a decode step may hand back beside its pools: counts made on the device
# ("load": a row an expert layer, the held experts with a pick and the most
# picks of one, then, where a chip holds a share of the layer's experts, all
# picks and those that fell on a held one; "attention_read": the plan's
# `READ_COUNTS`, blocks read, blocks walked, blocks the walk and the shared
# pass brought by runs).
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

    def holds(self, table) -> bool:
        """Whether the table on the device is this one: no row changed since
        the decode call that sent it."""
        host, _ = self._table
        return (host is not None and host.shape == np.shape(table)
                and np.array_equal(host, table))

    def on_device(self, table) -> tuple[jax.Array, bool]:
        """The decode steps' logical table on the device, and whether this
        call sent it: again only when a row changed (an admission or a
        finish, not every step)."""
        table = np.asarray(table, np.int32)
        sent = not self.holds(table)
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

    def _assign(self, bid: int, of: np.ndarray | None = None) -> None:
        """A free slot to the block: its slot, or what `of` maps (a state
        group's spare)."""
        slot = self.free.pop()
        (self.slot_of if of is None else of)[bid] = slot
        self.block_of[slot] = bid
        self.stamp[slot] = RESERVED
        self.counts["taken"] += 1

    def _stamp(self, slots: np.ndarray) -> np.ndarray:
        """One table a program call: its slots were live at this tick."""
        self.tick += 1
        self.stamp[slots] = self.tick
        return slots

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
        return slots.astype(np.int32)

    def miss_tables(self, table: np.ndarray) -> dict:
        kept = min(table.shape[1], self.store)
        for row in table:
            self.link(row)
        if self.lazy:
            self._ensure(table[:, table.shape[1] - kept:], table)
        return {"full": table,
                "window": self._stamp(self._slots(
                    table[:, table.shape[1] - kept:], "a miss prefill"))}

    def hit_tables(self, table: np.ndarray, prefix_blocks: int) -> dict:
        seen = min(prefix_blocks, self.need)
        for row in table:
            self.link(row[max(prefix_blocks - 1, 0):])
        if self.lazy:
            self._ensure(table[:, prefix_blocks:], table)
        return {"full": table,
                "window": self._stamp(self._slots(
                    table[:, prefix_blocks - seen:], "a hit prefill"))}

    def _span_of(self, context_len: np.ndarray) -> tuple:
        """The first and the last block of each sequence's window."""
        return (np.maximum(context_len - self.window, 0) // self.block,
                (context_len - 1) // self.block)

    def decode_slots(self, table: np.ndarray, context_len: np.ndarray) -> dict:
        """The window table of the decode step at `context_len`.  A block
        that enters a window takes its slot here; nothing says yet that the
        step is served (`decode_tables`), so a pod that launches ahead makes
        a step's table on the call before the one that takes it."""
        first, current = self._span_of(context_len)
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
        return {"full": table, "window": self._slots(ids, "a decode step"),
                "first": (first * self.block).astype(np.int32)}

    def decode_tables(self, table: np.ndarray, context_len: np.ndarray,
                      made: dict | None = None):
        """The window table of one served decode step (`made`: as the call
        before made it, `decode_slots`) and what the step reads: (tables,
        {"full_blocks", "window_blocks", "uniform_blocks"})."""
        tables = made or self.decode_slots(table, context_len)
        before = self.live_tick
        self._stamp(tables["window"])
        self.live_tick = self.tick
        # live at the last decode step, in no window now
        self.counts["released"] += int((self.stamp == before).sum()) if before else 0
        first, current = self._span_of(context_len)
        whole = int((current + 1).sum())
        return tables, {"full_blocks": whole,
                        "window_blocks": int((current - first + 1).sum()),
                        "uniform_blocks": whole}


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
        # a pod that launches ahead: the block's other slot (`_alternate`)
        self.spare_of = np.full(pod.pool_blocks, -1, np.int32)

    def drop(self, bid: int) -> None:
        super().drop(bid)
        slot = self.spare_of[bid]
        if slot >= 0:
            self.spare_of[bid], self.block_of[slot] = -1, -1
            self.free.append(int(slot))

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
        """The slots of `ids`; a block without one takes one."""
        self._ensure(ids, ids)
        return self.slot_of[ids].astype(np.int32)

    def _prefill_tables(self, table: np.ndarray, first: int) -> dict:
        kept = self.spec.snapshot_blocks(first, table.shape[1] - first)
        for row in table:
            self.link(row[max(first - 1, 0):])
        return {"full": table,
                "state_write": self._stamp(self._slots(table[:, kept]))}

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

    def _blocks_of(self, table: np.ndarray, context_len: np.ndarray) -> tuple:
        """Each sequence's current block's place in its chain, and the blocks
        [B, (read, written)] a decode step at `context_len` goes between."""
        rows, pos = np.arange(len(table)), context_len - 1
        cur, prev = pos // self.block, np.maximum(pos - 1, 0) // self.block
        return cur, np.stack((table[rows, prev], table[rows, cur]), axis=1)

    def decode_slots(self, table: np.ndarray, context_len: np.ndarray) -> dict:
        """The state slots of the decode step at `context_len`, [B, (read,
        written)].  A block without a slot takes one here; nothing says yet
        that the step is served (`decode_tables`)."""
        pod, pos = self.pod, context_len - 1
        cur, ids = self._blocks_of(table, context_len)
        # A sequence that enters a new block: the block two back is no
        # longer one of its two; its own blocks carry no hash to keep it for.
        entered = np.flatnonzero((pos % self.block == 0) & (cur >= 2))
        old = table[entered, cur[entered] - 2]
        for bid in old[~pod.hashed[old] & (self.slot_of[old] >= 0)]:
            self.drop(int(bid))
            self.counts["released"] += 1
        slots = (self._alternate(table, cur, ids) if pod.decode_ahead
                 else self._slots(ids))
        return {"full": table, "state": slots}

    def _alternate(self, table, cur, ids: np.ndarray) -> np.ndarray:
        """The slots of a pod that launches ahead.  A step that is run and
        not taken must leave the state its sequence goes on from as it was,
        so no step writes the slot it reads: a sequence alternates between
        two, `slot_of` its block holds the state after the last served step
        and `spare_of` is where the next step writes, whichever call
        launches it and however often (serving the step exchanges them:
        `decode_tables`).  They are the two slots a live sequence holds
        anyway.  The step that enters a block writes the spare of the block
        before, which is the new block's slot from then on; the first step
        inside the block finds no spare and takes the slot of the block
        before (the step that read it is served), unless a hash keeps that
        snapshot."""
        pod, inside = self.pod, ids[:, 0] == ids[:, 1]
        for before, block in ids[~inside]:
            slot = self.spare_of[before]
            if self.slot_of[block] < 0 and slot >= 0:
                self.spare_of[before] = -1
                self.slot_of[block], self.block_of[slot] = slot, block
        slots, bare = self._slots(ids), []
        for row in np.flatnonzero(inside & (self.spare_of[ids[:, 1]] < 0)):
            block = ids[row, 1]
            before = table[row, cur[row] - 1] if cur[row] else block
            slot = self.slot_of[before]
            if self.spare_of[block] >= 0 or block in bare:
                continue  # rows on one block: the engine's idle ones
            if before == block or slot < 0 or pod.hashed[before]:
                bare.append(block)
                continue
            self.slot_of[before] = -1
            self.spare_of[block], self.block_of[slot] = slot, block
            self.counts["released"] += 1
        if len(bare) > len(self.free):
            pod.unpublished += self.reclaim(len(bare), ids)
        for bid in bare:
            self._assign(int(bid), self.spare_of)
        slots[inside, 1] = self.spare_of[ids[inside, 1]]
        return slots

    def decode_tables(self, table: np.ndarray, context_len: np.ndarray,
                      made: dict | None = None):
        """The state slots of one served decode step (`made`: as the call
        before made them, `decode_slots`) and what the step reads."""
        pod = self.pod
        tables = made or self.decode_slots(table, context_len)
        slots = self._stamp(tables["state"])
        self.live_tick = self.tick
        if pod.decode_ahead:
            # served: inside a block, the slot the step wrote is the block's
            # and the one it read the spare
            _, ids = self._blocks_of(table, context_len)
            inside = ids[:, 0] == ids[:, 1]
            within = ids[inside, 1]
            self.slot_of[within], self.spare_of[within] = (slots[inside, 1],
                                                           slots[inside, 0])
        live = context_len > 1
        blocks = int(((context_len[live] - 1) // self.block + 1).sum())
        return (tables,
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

    def tables(self, kind: str, table, context_len=None, prefix_blocks=0,
               made=None, ahead=None):
        """What a program call is handed as its table: the logical table
        alone with one group, every group's tables with more.  A decode call
        of a pod that launches ahead (`jit_programs.run_ahead`) brings
        `made`, its step's tables where the call before made them, and
        `ahead`, the context lengths of the step it will launch after its
        own: it is handed (tables, that step's tables).  A group's span
        covers both, and what it says is of the call's own step alone, which
        is the one it serves."""
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
                    if full.selector_dim is None:
                        read = blocks * full.read_nbytes
                        s.set_attr("latent_bytes", read)
                    else:
                        # every live position's selector key, and K and V
                        # (or the latent vector) of the positions a query
                        # picks; beside them what reading every live
                        # position's would be
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
            return table if ahead is None else (table, table)
        tables, reads, after = {}, {}, {}
        for group in self.groups:
            with span(group.span) as s:
                if kind == "decode":
                    more, reads[group.read_span] = group.decode_tables(
                        table, np.asarray(context_len, np.int64), made)
                    if ahead is not None:
                        after.update(group.decode_slots(
                            table, np.asarray(ahead, np.int64)))
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
        return tables if ahead is None else (tables, after)

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
            with span("attention.read") as s:
                for name, blocks in zip(READ_COUNTS,
                                        counted["attention_read"]):
                    s.set_attr(name, int(blocks))
        held = getattr(model, "experts_held", None)  # a chip's share of them
        for layer, (touched, most, *picks) in enumerate(
                counted.get("load", ())):
            with span("moe.expert_load") as s:
                s.set_attr("layer", layer)
                s.set_attr("experts_held", held or model.n_experts)
                s.set_attr("experts_touched", int(touched))
                s.set_attr("max_tokens", int(most))
                s.set_attr("mean_tokens",
                           tokens * model.top_k / model.n_experts)
                if picks:  # the router's picks, and those that fell here
                    s.set_attr("picks", int(picks[0]))
                    s.set_attr("picks_held", int(picks[1]))

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

    Where the family's policy says `decode_ahead`, a decode call that goes
    on from the call before it (`run_ahead`) launches the step after its own
    as well, on the tokens its own step serves, which are on the device
    before the host has them, and with tables made now (`Pod.tables`'
    `ahead`); the next call, if it goes on in turn, is handed that step and
    its tables.  The host's share of a step (the groups' tables, the launch,
    the tokens' way to the host and back) then lies beside the device's work
    and not between two steps."""
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

    def follows(last, t, n, same: bool) -> bool:
        """Whether the decode call of tokens `t` at contexts `n` goes on from
        the call that left `last`: the same table (`same`: no row of it
        changed), each context one longer, each token the one that call
        served.  (Reading what it served costs nothing where the engine has
        read it.)"""
        return (last is not None and same
                and np.array_equal(n, last[0] + 1)
                and np.array_equal(t, np.asarray(last[1])[0]))

    def pack(pod, t, n, tables) -> np.ndarray:
        """A decode step's integers, a row a sequence (`inner_programs`'
        `decode` has the columns)."""
        ints = [t, n]
        if pod.window is not None:
            ints += [tables["first"], *tables["window"].T]
        if pod.state is not None:
            ints += [*tables["state"].T]
        return np.stack(ints, axis=1, dtype=np.int32)

    def run_ahead(p, kv, ints, table, last, after, traced):
        """A decode call of a `decode_ahead` pod: what `run` returns of the
        call's own step, and the step it launched ahead or None: (what `run`
        returns of that one, its tables).  `last` is what the last call left
        in `pod.last_decode`, (its contexts, the array it served,
        the step it launched ahead or None), and None after a prefill.
        This call goes on from that one (`follows`) where the table is the
        same, every context is one longer and the tokens are those served:
        then `ints` is None where a step launched ahead is this call's, and
        `after` is the next step, which this call launches: (its integers,
        the tokens -1, which is what the call's own step serves, where that
        lies on the device; its tables).  Where it does not go on
        (an admission, a finish, the first step) it launches its own step
        on the host's tokens and none ahead: a step launched ahead and not
        taken costs the device a step, so one is launched only where the
        last call shows that the engine is in the middle of its sequences.
        Such a step has written, for each row, the position after the row's
        last in the table it was launched with: where the row goes on, the
        step that takes its place writes the same there; where it ended,
        the place is the ended sequence's own or the engine's scratch
        block, which whoever is handed the block next writes before reading
        it.  The same holds of a window slot, which a block keeps from the
        call that first names it; a state group's slot is written in place
        of the state the step read, so such a pod's sequences alternate
        between two (`StateGroup._alternate`), and a step not taken has
        written the one that nobody reads."""
        if ints is None:
            out, counted, _ = last[2]
        else:
            before = (last[1] if last is not None
                      else np.zeros((2, len(ints)), np.float32))
            out, counted = run("decode", p, kv, (before, ints), table, traced)
        if after is None:
            return out, counted, None
        ints, tables = after
        return out, counted, (*run("decode", p, kv, (out[0], ints), table,
                                   traced, ahead=True), tables)

    def run_decode(p, t, kv, bt, n):
        pod = kv.pod
        with root_trace("pod.step") as traced:
            last, pod.last_decode = pod.last_decode, None
            goes_on = pod.decode_ahead and follows(last, t, n, kv.holds(bt))
            handed = last[2] if goes_on else None  # the call before launched
            after = None
            if goes_on:
                # a sequence that ends at its table's end goes no further
                n_after = np.minimum(np.asarray(n) + 1,
                                     np.shape(bt)[1] * block)
                tables, made = pod.tables(
                    "decode", bt, context_len=n,
                    made=handed and handed[2], ahead=n_after)
            else:
                tables = pod.tables("decode", bt, context_len=n)
            if traced is not None:
                pod.report_load(model)
            with span("pod.pack") as s:
                ints = None if handed else pack(pod, t, n, tables)
                if goes_on:
                    after = (pack(pod, np.full(len(n_after), -1), n_after,
                                  made), made)
                table, sent = kv.on_device(bt)
                s.set_attr("calls", 1)
                s.set_attr("table_sent", int(sent))
                s.set_attr("h2d_bytes", table.nbytes if sent else 0)
                if pod.decode_ahead:
                    # the call's own step was launched by the call before
                    s.set_attr("ahead", int(handed is not None))
            if pod.decode_ahead:
                out, counted, ahead = run_ahead(p, kv, ints, table, last,
                                                after, traced)
                pod.last_decode = (np.array(n), out[0], ahead)
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
