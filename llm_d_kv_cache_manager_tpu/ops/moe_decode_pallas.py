"""A decode step's expert layer as one Pallas kernel that copies only the
experts its tokens picked.

`models/moe_serve.py:routed_experts`'s batched form multiplies every held
expert through every token and lets the routing weights, zero for an expert
not picked, mask the sum: one product, and every expert's weights cross
memory whether a token picked it or not.  A decode step's few tokens leave
experts without a pick (a step of 32 tokens picks among 8 of the 16 experts
`deepseek-v3.2-exp-l5` holds; PERF.md section 6, PR 54), and an expert's
weights are all a decode step's expert layer moves.

``moe_decode_pallas`` is the same sum, an expert (and a tile of its hidden
width) a grid step, **in an order that puts the experts with a pick first**.
``touched_order`` makes the order in XLA from the picks per expert: the ids of
the experts with a pick, ascending, then the last of them repeated.  The
weights' index maps read it (scalar prefetch), so a grid step past the last
touched expert names the block the step before it held: the pipeline issues a
copy only where a block index changes, and the body does not run there
(``pl.when``).  What a step copies is then the touched experts' weights and no
more, in the order they lie.

Inside a grid step: the step's rows ``x`` [N, D] (resident) against the
expert's ``w_up`` (and ``w_gate``) tile [D, tf], float32; `moe_serve._hidden`'s
rule; times the expert's column of the routing weights, float32 (the column
is cut from the resident [N, E] weights under a lane mask: an operand a column
wide would be padded 128-fold in memory); cast to the serving type; against
the ``w_down`` tile [tf, D] into the result [N, D] float32, which stays in
VMEM for the whole grid.  Operands in the serving type and float32 sums, as
the batched einsum, in another order of summation over experts.

``hidden_tile`` cuts the hidden width so that two buffers of an expert's
tiles stay under ``TILE_VMEM_BYTES``: whole where that fits (every family but
`deepseekv32`, whose 7168 x 2048 matrices are 29 MB each: tiles of 512; 256
read 8 % slower and 1024 the same).

On the chip, a layer alone (hack/moe_decode_alone.py; PERF.md section 6, PR
54): the kernel's time follows the experts touched, 677-715 GB/s of their
bytes, and at every expert touched it is the einsum's to within 2 % at five
of the six expert cells' shapes (7 % faster at one).  Where it loses is a
hidden width that is no whole number of lane tiles (1856: XLA keeps such a
`w_up` with its other axis in the lanes and copies it for the kernel, 2.4 ms
a call) and shapes that touch every expert anyway; `models/moe_serve.py`'s
`decode_kernel_serves` keeps the einsum there.  In the cell
`deepseekv32-chat-longctx-shared` a step's four expert layers went from 4 x
1.98 ms to 4 x 1.04 and the token gap from 30.9 to 27.7 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
# What the pipeline's two buffers of an expert's weight tiles may take of
# VMEM_LIMIT_BYTES; the rows, the routing weights, the result and the hidden
# values' temporaries share the rest.
TILE_VMEM_BYTES = 64 * 1024 * 1024


def touched_order(sizes):
    """sizes: [E] picks per expert -> (order [E] int32, n_touched [1] int32):
    the ids of the experts with a pick, ascending, then the last of them
    repeated (expert 0 where none has one).  A running count and compares, no
    sort."""
    upto = jnp.cumsum(sizes > 0)  # the touched experts up to and with e
    n_touched = upto[-1]
    # the j-th touched expert is behind as many experts as have seen j or
    # fewer touched ones
    j = jnp.minimum(jnp.arange(sizes.shape[0]), n_touched - 1)
    order = jnp.sum(upto[None, :] <= j[:, None], axis=1)
    return order.astype(jnp.int32), n_touched.reshape(1).astype(jnp.int32)


def hidden_tile(D: int, F: int, matrices: int, itemsize: int) -> int:
    """The widest tile of an expert's hidden width F, whole or a divisor of F
    in whole lane tiles, of which two buffers of ``matrices`` tiles [D, tf]
    fit under TILE_VMEM_BYTES."""
    def fits(tf):
        return 2 * matrices * D * tf * itemsize <= TILE_VMEM_BYTES

    if fits(F):
        return F
    for n in range(2, F // LANES + 1):
        if F % (n * LANES) == 0 and fits(F // n):
            return F // n
    raise ValueError(f"no tile of a hidden width of {F} fits: D={D}")


def weight_block(e, f, order, n_touched, last: int):
    """(expert, tile) of the weights grid step (e, f) names: the e-th touched
    expert's f-th tile and, past the last touched expert, the block the step
    before held (its last tile), which brings no copy."""
    return order[e], jnp.where(e < n_touched[0], f, last)


def _kernel(order_ref, n_ref, x_ref, weight_ref, *refs, gated: bool):
    *w_refs, out_ref = refs
    e, f = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when((e == 0) & (f == 0))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(e < n_ref[0])
    def _expert():
        x = x_ref[...]
        up = jnp.dot(x, w_refs[-2][0], preferred_element_type=f32)
        if gated:
            gate = jnp.dot(x, w_refs[0][0], preferred_element_type=f32)
            hidden = jax.nn.silu(gate) * up
        else:
            hidden = jnp.square(jax.nn.relu(up))
        weight = weight_ref[...]
        here = jax.lax.broadcasted_iota(jnp.int32, weight.shape, 1) == (
            order_ref[e])
        column = jnp.sum(jnp.where(here, weight, 0.0), axis=1, keepdims=True)
        out_ref[...] += jnp.dot((hidden * column).astype(x.dtype),
                                w_refs[-1][0], preferred_element_type=f32)


def moe_decode_pallas(x, weight, experts, order, n_touched,
                      interpret: bool = False):
    """Sum over the experts of ``weight[:, e] Expert_e(x)``, the experts
    ``order[:n_touched]`` alone computed and copied.  x: [N, D] in the serving
    type; weight: [N, E] float32, zero where a token did not pick the expert;
    experts: ``w_up`` [E, D, F], ``w_down`` [E, F, D] and, where the family's
    experts have one, ``w_gate`` [E, D, F]; order, n_touched: `touched_order`
    of the picks per expert (an expert outside ``order[:n_touched]`` must have
    a zero column).  Returns [N, D] float32."""
    D, F = experts["w_up"].shape[1:]
    tile = hidden_tile(D, F, len(experts), experts["w_up"].dtype.itemsize)
    return _call(x, weight, experts, order, n_touched, tile=tile,
                 interpret=interpret)


# jitted, so that a program's expert layers, alike in every shape, are traced
# and lowered once
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _call(x, weight, experts, order, n_touched, *, tile: int,
          interpret: bool):
    N, D = x.shape
    E, _, F = experts["w_up"].shape
    gated = "w_gate" in experts
    names = ("w_gate", "w_up") if gated else ("w_up",)
    tf, last = tile, F // tile - 1

    def into(e, f, order, n):
        expert, at = weight_block(e, f, order, n, last)
        return expert, 0, at

    def out_of(e, f, order, n):
        expert, at = weight_block(e, f, order, n, last)
        return expert, at, 0

    def resident(shape):
        return pl.BlockSpec(shape, lambda e, f, *_: (0, 0))

    return pl.pallas_call(
        functools.partial(_kernel, gated=gated),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, F // tf),
            in_specs=[resident((N, D)), resident((N, E)),
                      *(pl.BlockSpec((1, D, tf), into) for _ in names),
                      pl.BlockSpec((1, tf, D), out_of)],
            out_specs=resident((N, D)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_decode_pallas",
    )(order, n_touched, x, weight, *(experts[k] for k in names),
      experts["w_down"])
