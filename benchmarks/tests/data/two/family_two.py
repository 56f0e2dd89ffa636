"""A second family's benchmark side: the llama reference and counts, and a
cost function of its own for a roofline no file of the harness knows."""
from benchmarks.harness.family_llama import (  # noqa: F401
    forward_logits, kv_block_bytes, make_weights, param_bytes,
    prefill_attention_flops, sizes,
)


def miss_prefill_min_s(cfg, shapes, counters, peak):
    """The matrix products of one miss prefill, without attention and head."""
    L, D, H, Hkv, Dh, F, V = sizes(cfg)
    T = shapes["miss"][0]
    return 2 * T * L * (D * Dh * (2 * H + 2 * Hkv) + 3 * D * F) / peak["bf16_flops"]
