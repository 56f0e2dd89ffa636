"""The least work an algorithm needs, from shapes, and the device's peaks.

A roofline share divides the least time the chip could take by the time a
kernel took, so the counts here are the least the algorithm needs, never what
the current program happens to move.
"""

from __future__ import annotations

from .reference import sizes

# Published peaks per chip, by `device_kind`.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """A device that is not in the table is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"device_kind {device_kind!r} has no published peaks in "
                       "benchmarks/harness/costs.py; add them with their source")
    return PEAKS[device_kind]


def param_count(cfg: dict) -> int:
    L, D, H, Hkv, Dh, F, V = sizes(cfg)
    return V * D + D + L * (2 * D + D * Dh * (2 * H + 2 * Hkv) + 3 * D * F)


def kv_block_bytes(cfg: dict, block: int = 16) -> int:
    """One block of K and V over all layers, in the served type (2 bytes)."""
    L, _, _, Hkv, Dh, _, _ = sizes(cfg)
    return L * 2 * block * Hkv * Dh * 2


def flash_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """Causal attention of one miss prefill, all layers: QK^T and PV over the
    lower triangle, 2 * T^2 * H * Dh FLOPs a layer.  Compute-bound: its
    bytes (Q, K, V, O once: 4*T*H*Dh*2 a layer at most) take far less."""
    L, _, H, _, Dh, _, _ = sizes(cfg)
    T = shapes["miss"][0]
    return L * 2 * T * T * H * Dh / peak["bf16_flops"]


def decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: the weights once in their stored
    type, every distinct live K/V block once (a shared system prompt's blocks
    once for all sequences), and the new K/V written."""
    steps = counters["decode_steps"]
    blocks = counters["decode_live_blocks"] / steps
    written = counters["decode_live_seqs"] / steps * kv_block_bytes(cfg, 1)
    return (2 * param_count(cfg) + blocks * kv_block_bytes(cfg) + written) \
        / peak["hbm_bytes_s"]


COSTS = {"flash_prefill_min_s": flash_prefill_min_s,
         "decode_step_min_s": decode_step_min_s}
