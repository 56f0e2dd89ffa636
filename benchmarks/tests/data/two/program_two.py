"""A second family's program side: K and V in pools of their own, so the
pod's pool is a pytree of two leaves, under a cache manager of its own."""
import jax.numpy as jnp

from benchmarks.harness import pod, program_llama
from benchmarks.harness.program_llama import from_published  # noqa: F401


class Pod(pod.Pod):
    """Stands where a family's own retention rules would: `engine.Fleet` takes
    a program module's `Pod` in place of the harness's."""


def new_pool(model, pool_blocks):
    kv = program_llama.new_pool(model, pool_blocks)
    return {"k": kv[:, :, 0], "v": kv[:, :, 1]}


def _step(fn):
    def step(params, tokens, pool, *rest, **kw):
        out, kv = fn(params, tokens, jnp.stack((pool["k"], pool["v"]), 2),
                     *rest, **kw)
        return out, {"k": kv[:, :, 0], "v": kv[:, :, 1]}
    return step


prefill_paged = _step(program_llama.prefill_paged)
prefill_continue = _step(program_llama.prefill_continue)
decode_step = _step(program_llama.decode_step)
