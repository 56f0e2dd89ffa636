"""The pod's cache manager and its three compiled programs, generic over the
family's program module (`harness/program_<family>.py`).

This is the part of the system that a model with two kinds of cache state has
to change, and it belongs in the package (ROADMAP D4; this PR may not write
there, PERF.md section 7).  Until it moves, a family that needs another manager
brings `Pod` and `jit_programs` in its program module and `engine.Fleet` takes
those.  The independent yardstick for the cache is `check.against_cache_model`.
"""

from __future__ import annotations

from collections import OrderedDict, defaultdict

import jax
import jax.numpy as jnp


class Pod:
    """One serving pod on the chip: its paged K/V pool and prefix cache.  The
    allocator never hands out a block a live sequence references: the free
    list first, then least-recently-used cached blocks that nobody references."""

    def __init__(self, name: str, program, model, pool_blocks: int):
        self.name = name
        self.pool_blocks = pool_blocks
        self.kv = program.new_pool(model, pool_blocks)  # a pytree of arrays
        self.free = list(range(pool_blocks - 1, -1, -1))
        self.cached: OrderedDict[int, int] = OrderedDict()  # hash -> block, LRU first
        self.refs: dict[int, int] = defaultdict(int)  # block -> live sequences

    def cached_prefix(self, hashes) -> list[int]:
        ids = []
        for h in hashes:
            if h not in self.cached:
                break
            ids.append(self.cached[h])
        return ids

    def touch(self, hashes) -> None:
        for h in hashes:
            self.cached.move_to_end(h)

    def alloc(self, n: int) -> tuple[list[int], list[int]]:
        """n blocks no live sequence references; returns (ids, hashes evicted)."""
        ids, evicted = [], []
        while len(ids) < n and self.free:
            ids.append(self.free.pop())
        if len(ids) < n:
            for h, bid in list(self.cached.items()):
                if self.refs[bid]:
                    continue
                del self.cached[h]
                evicted.append(h)
                ids.append(bid)
                if len(ids) == n:
                    break
        if len(ids) < n:
            raise RuntimeError(f"{self.name}: pool exhausted by live sequences")
        return ids, evicted

    def hold(self, ids, by: int) -> None:
        for bid in ids:
            self.refs[bid] += by


def jit_programs(program, model, shapes: dict, interpret: bool) -> dict:
    """The cell's compiled steps, named so that the trace reduction finds
    them.  Each returns the greedy tokens with their logits as one array (one
    transfer to the host) and, for a prefill, the last position's row of logits
    (kept on the device for the output check); the pool is donated whole."""

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

    def decode(p, t, kv, bt, n):
        logits, kv = program.decode_step(p, t, kv, bt, n, model,
                                         interpret=interpret)
        return served(logits), kv

    programs = {}
    for fn, key, name in ((miss, "miss", "miss_prefill_T{}"),
                          (hit, "hit", "hit_prefill_P{}_S{}"),
                          (decode, "decode", "decode_B{}")):
        if key in shapes:
            fn.__name__ = fn.__qualname__ = name.format(*shapes[key])
            programs[key] = jax.jit(fn, donate_argnums=(2,))
    return programs
