"""The layer math the families on the pod path share: each function here
stood, with this body, in two or more of models/afmoe.py, lfm2moe.py,
phi4flash.py, glm4moelite.py and keyevl2.py.  A family file imports from here
and from models/kv_cache_pool.py, never from another family: a change to one
family's file moves no other family's numbers.  Nothing here is jitted or
named for a trace: a caller's program holds these operations as if its own
file wrote them.  What one family alone computes stays in its file;
models/llama.py keeps its own norm (a constant epsilon, ROADMAP D12).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from llm_d_kv_cache_manager_tpu.ops import flash_pallas

NEG_INF = -1e30
# Prefill attention below this key length is one dense masked product (XLA);
# at and above it the Pallas flash kernel, whose VMEM bound
# (flash_pallas.fits_vmem) is then the longest context a prefill takes.
FLASH_MIN_LEN = 1024


def rms_norm(x, w, eps, dtype=None):
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w.astype(jnp.float32)).astype(dtype or x.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """The ``dim / 2`` inverse frequencies of a rotation over ``dim`` lanes,
    rescaled as YaRN does: pair i turns by ``pos * f'_i``, ``f_i =
    theta^(-2i/dim)``; pairs that turn more than ``beta_fast`` times over the
    ``original`` context keep ``f_i``, those that turn fewer than
    ``beta_slow`` times take ``f_i / factor``, and between the two (pairs
    ``low`` to ``high``) a ramp mixes them.  float32 [dim / 2]."""
    def pair_of(turns):  # the pair that turns this often over `original`
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim // 2 - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freqs = theta ** (-i / (dim // 2))
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs * (1.0 - ramp) + freqs / factor * ramp


def rope(x, positions, theta):
    """x: [B, T, H, D] (D even); positions: [B, T].  (models/keyevl2.py keeps
    a body of its own: it turns keys without a head axis too, and shapes the
    angles before it takes their cosines, which is another program.)"""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x2 * cos + x1 * sin), axis=-1
    ).astype(x.dtype)


def embed(params, tokens):
    """The residual stream is float32 from here to the head: matrix products
    take their operands in the serving type, what they add to the stream is
    not rounded again.  (Under bfloat16 sums the expert selection of one token
    in twelve flipped at a near-tie in some layer of models/afmoe.py; the
    router now reads the stream's own float32 norm.)"""
    return jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)


def logits(x, params, cfg):
    """Final RMS norm and the untied head; float32 logits."""
    x = rms_norm(x, params["ln_f"], cfg.rms_eps, params["head"].dtype)
    return jnp.einsum(
        "...d,vd->...v", x, params["head"],
        preferred_element_type=jnp.float32,
    )


def swiglu(x, w):
    """x in the serving type; what goes into the stream is float32."""
    f32 = jnp.float32
    gate = jnp.einsum("...d,df->...f", x, w["w_gate"],
                      preferred_element_type=f32)
    up = jnp.einsum("...d,df->...f", x, w["w_up"], preferred_element_type=f32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", hidden, w["w_down"],
                      preferred_element_type=f32)


def interpreted(interpret: bool) -> bool:
    """For a family whose kernels are its only attention: interpreted where
    the program is not compiled for the TPU."""
    return interpret or jax.default_backend() != "tpu"


def dense_attention(q, k, v, q_offset, window):
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    qf = q.astype(jnp.float32).reshape(B, Tq, Hkv, H // Hkv, D) * D**-0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Tq)[:, None]
    k_pos = jnp.arange(Tk)[None, :]
    seen = k_pos <= q_pos
    if window is not None:
        seen &= k_pos > q_pos - window
    p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Tq, H, D).astype(q.dtype)


def prefill_attention(q, k, v, cfg, q_offset, window, interpret):
    """Causal attention of a prefill, banded where ``window`` is given: the
    Pallas flash kernel at serving lengths, one dense product below."""
    if k.shape[1] < FLASH_MIN_LEN:
        return dense_attention(q, k, v, q_offset, window)
    if not flash_pallas.fits_vmem(
        k.shape[1], k.shape[-1], jnp.dtype(k.dtype).itemsize
    ):
        raise ValueError(
            f"a prefill over {k.shape[1]} positions is past the flash "
            "kernel's VMEM bound"
        )
    return flash_pallas.flash_gqa_attention_pallas(
        q, k, v, q_offset=q_offset, window=window, interpret=interpret
    )


def new_pool(groups: dict, sizes: dict) -> dict:
    """A pod's pools as a pytree, one array a layer (each is updated in place
    by the step that writes it): ``groups`` a family's ``cache_groups(cfg)``,
    ``sizes`` the slots of each group."""
    return {
        kind: [
            jnp.zeros(spec.layer_shape(sizes[kind]), jnp.dtype(spec.dtype))
            for _ in range(spec.num_layers)
        ]
        for kind, spec in groups.items()
    }
