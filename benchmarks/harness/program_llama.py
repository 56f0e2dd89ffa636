"""The program's side of the family `llama`: dense decoders with grouped-query
attention through `models/llama.py`, one uniform paged K/V pool."""

from __future__ import annotations

import jax.numpy as jnp

from llm_d_kv_cache_manager_tpu.models import llama
from llm_d_kv_cache_manager_tpu.models.llama import (  # noqa: F401
    decode_step, prefill_continue, prefill_paged,
)


def from_published(cfg: dict, block_size: int) -> llama.LlamaConfig:
    """The program's configuration from the keys of the public `config.json`.
    `tie_word_embeddings` and `rms_norm_eps` cannot reach it (ROADMAP D12)."""
    return llama.LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), block_size=block_size,
        dtype=cfg["torch_dtype"])


def new_pool(model: llama.LlamaConfig, pool_blocks: int):
    """The pod's pool as a pytree of arrays: here the one array."""
    return jnp.zeros((model.n_layers, pool_blocks, 2, model.block_size,
                      model.n_kv_heads, model.head_dim), jnp.dtype(model.dtype))
