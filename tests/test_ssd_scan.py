"""Mamba-2's scan three ways (ops/ssd_pallas.py), at a small size on the CPU:
the Pallas kernel (interpret mode) against the same products as einsums under
a `lax.scan` over chunks, against the recurrence a position at a time; and a
decode step's kernel, which advances the slots of a pool where they lie,
against the recurrence's one position on the gathered slots.

In float32 the three are one set of equations in three orders of summation:
they agree to 2e-5 of the largest value (the chunk form multiplies decays that
the recurrence applies one by one).  With bfloat16 operands the products within
a chunk round where the recurrence does not, and the tolerance is a bfloat16
product's (1e-2); the state is float32 in all three.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.ops import ssd_pallas


def inputs(B, T, H, P, G, N, seed=0, dtype=jnp.float32, fresh=False):
    """x, d (after its softplus), A (negative), B, C and a state to resume
    from; d A spans 1e-3 .. ~2 a position, so that a chunk's decay runs from
    nearly none to nearly all."""
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (B, T, H, P)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (B, T, G, N)).astype(dtype),
            jax.random.normal(k[4], (B, T, G, N)).astype(dtype),
            jnp.zeros((B, H, P, N)) if fresh
            else jax.random.normal(k[5], (B, H, P, N)))


def close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=tol * float(jnp.abs(want).max()))


SHAPES = {  # B, T, H, P, G, N, chunk
    "a group a head": (2, 64, 2, 8, 2, 16, 16),
    "four heads a group": (1, 96, 8, 8, 2, 16, 32),
    "one chunk": (1, 32, 4, 16, 1, 8, 32),
    "lanes as published": (1, 256, 8, 64, 1, 128, 128),
}


@pytest.mark.parametrize("name", SHAPES)
@pytest.mark.parametrize("fresh", (False, True), ids=("resumed", "from zero"))
def test_kernel_einsums_and_recurrence_agree(name, fresh):
    *shape, chunk = SHAPES[name]
    args = inputs(*shape, seed=len(name), fresh=fresh)
    y0, s0 = ssd_pallas.ssd_recurrence(*args)
    y1, s1 = ssd_pallas.ssd_chunk_scan(*args, chunk=chunk)
    y2, s2 = ssd_pallas.ssd_chunk_scan_pallas(*args, chunk=chunk,
                                              interpret=True)
    for y, s in ((y1, s1), (y2, s2)):
        close(y, y0, 2e-5)
        close(s, s0, 2e-5)
    assert y2.shape == args[0].shape and y2.dtype == jnp.float32
    assert s2.shape == args[-1].shape and s2.dtype == jnp.float32


@pytest.mark.parametrize("form", ("einsums", "kernel"))
def test_two_calls_with_the_state_carried_are_one(form):
    """A call that ends at a kept boundary hands its state to the next: the
    chunks of a sequence may be split over calls anywhere between two."""
    x, d, a, b, c, s0 = inputs(1, 128, 4, 8, 2, 16, seed=3)

    def scan(lo, hi, s):
        part = (x[:, lo:hi], d[:, lo:hi], a, b[:, lo:hi], c[:, lo:hi], s)
        if form == "kernel":
            return ssd_pallas.ssd_chunk_scan_pallas(*part, chunk=32,
                                                    interpret=True)
        return ssd_pallas.ssd_chunk_scan(*part, chunk=32)

    whole_y, whole_s = scan(0, 128, s0)
    y1, s1 = scan(0, 96, s0)
    y2, s2 = scan(96, 128, s1)
    close(jnp.concatenate((y1, y2), axis=1), whole_y, 1e-6)
    close(s2, whole_s, 1e-6)


def test_a_step_is_the_recurrences_position():
    x, d, a, b, c, s0 = inputs(3, 5, 4, 8, 2, 16, seed=4)
    want_y, want_s = ssd_pallas.ssd_recurrence(x, d, a, b, c, s0)
    s, ys = s0, []
    for t in range(5):
        s, y = ssd_pallas.ssd_step(s, x[:, t], d[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    close(jnp.stack(ys, axis=1), want_y, 1e-6)
    close(s, want_s, 1e-6)
    # by hand, one head: S = exp(d A) S + d x (x) B[g]; y = S . C[g]
    h, g = 3, 1
    s1 = (np.exp(float(d[0, 0, h] * a[h])) * np.asarray(s0[0, h])
          + float(d[0, 0, h]) * np.outer(x[0, 0, h], b[0, 0, g]))
    close(s1 @ np.asarray(c[0, 0, g]), want_y[0, 0, h], 1e-5)


TABLES = {  # a step's (read, written) slots of a pool of 12, a row each
    # every row its own pair
    "own pairs": ((7, 2), (0, 9), (5, 4), (3, 11), (10, 1)),
    # the engine's idle rows share one block, hence one pair, whose content
    # nobody reads; a live row between them
    "idle rows on one pair": ((6, 8), (7, 2), (6, 8), (6, 8), (0, 9)),
}


@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("float32", "bfloat16"))
def test_the_decode_kernel_is_a_step_on_the_slots_its_tables_name(table,
                                                                  dtype):
    """`ssd_decode_step_pallas` (interpreted) against `ssd_step` on the
    gathered slots: the written slots and y to the step's own tolerance, the
    slots no row writes (the read ones among them) bit for bit.  The state is
    float32 whatever x, B and C are."""
    pairs = np.asarray(TABLES[table], np.int32)
    live = np.flatnonzero([(pairs == p).all(1).sum() == 1 for p in pairs])
    B, H, P, G, N, slots = len(pairs), 4, 8, 2, 16, 12
    x, d, a, b, c, _ = inputs(B, 1, H, P, G, N, seed=7, dtype=dtype)
    step = (x[:, 0], d[:, 0], a, b[:, 0], c[:, 0])
    pool = jax.random.normal(jax.random.key(8), (slots, H, P, N))
    before = np.asarray(pool)
    read, write = jnp.asarray(pairs[:, 0]), jnp.asarray(pairs[:, 1])
    want_s, want_y = ssd_pallas.ssd_step(pool[read], *step)
    got, y = ssd_pallas.ssd_decode_step_pallas(pool, read, write, *step,
                                               interpret=True)
    assert got.dtype == jnp.float32 and y.dtype == jnp.float32
    assert got.shape == pool.shape and y.shape == (B, H, P)
    close(y[live], want_y[live], 1e-6)
    close(got[write[live]], want_s[live], 1e-6)
    kept = np.setdiff1d(np.arange(slots), pairs[:, 1])
    assert set(pairs[:, 0]) <= set(kept)
    np.testing.assert_array_equal(np.asarray(got)[kept], before[kept])


def test_the_decode_kernel_refuses_heads_that_fill_no_row_block():
    x, d, a, b, c, _ = inputs(1, 1, 2, 24, 2, 16)
    with pytest.raises(ValueError, match="row block"):
        ssd_pallas.ssd_decode_step_pallas(
            jnp.zeros((2, 2, 24, 16)), jnp.zeros(1, jnp.int32),
            jnp.ones(1, jnp.int32), x[:, 0], d[:, 0], a, b[:, 0], c[:, 0],
            interpret=True)


@pytest.mark.parametrize("form", ("einsums", "kernel"))
def test_bfloat16_operands_round_the_products_and_never_the_state(form):
    args = inputs(1, 128, 4, 16, 2, 32, seed=5, dtype=jnp.bfloat16)
    y0, s0 = ssd_pallas.ssd_recurrence(*args)
    if form == "kernel":
        y, s = ssd_pallas.ssd_chunk_scan_pallas(*args, chunk=32, interpret=True)
    else:
        y, s = ssd_pallas.ssd_chunk_scan(*args, chunk=32)
    close(y, y0, 1e-2)
    close(s, s0, 1e-5)  # the state's two products are float32 whatever x is
    assert s.dtype == jnp.float32 and y.dtype == jnp.float32


def test_a_ragged_length_is_refused():
    args = inputs(1, 40, 2, 8, 1, 8)
    for scan in (ssd_pallas.ssd_chunk_scan, ssd_pallas.ssd_chunk_scan_pallas):
        with pytest.raises(ValueError, match="whole chunks"):
            scan(*args, chunk=16)


def test_nothing_of_the_size_of_every_positions_state_is_made():
    """T x H x P x N would be 2 MB here; the chunk form's largest value is a
    chunk's [H, Q, Q] decays and the state itself."""
    B, T, H, P, G, N, chunk = 1, 512, 8, 16, 2, 64, 32
    args = inputs(B, T, H, P, G, N)
    jaxpr = jax.make_jaxpr(
        lambda *a: ssd_pallas.ssd_chunk_scan(*a, chunk=chunk))(*args)
    largest = max(
        int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns for v in eqn.outvars)
    assert largest < T * H * P * N / 8
