"""The least work an algorithm needs, from shapes, and the device's peaks.

A roofline share divides the least time the chip could take by the time a
kernel took, so the counts here are the least the algorithm needs, never what
the current program happens to move.  The counts that depend on the model come
from the configuration's family (`harness/family_<family>.py`); a cost
function that is not in `COSTS` is looked for there (`cost`), so a new kernel
brings its count in a new file.  The peaks stay here.
"""

from __future__ import annotations

from . import family
from .engine import BLOCK

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


def flash_prefill_min_s(cfg, shapes, counters, peak) -> float:
    """Causal attention of one miss prefill, all layers.  Compute-bound: its
    bytes (Q, K, V, O once: 4*T*H*Dh*2 a layer at most) take far less."""
    flops = family.reference(cfg).prefill_attention_flops(cfg, shapes["miss"][0])
    return flops / peak["bf16_flops"]


def decode_step_min_s(cfg, shapes, counters, peak) -> float:
    """One decode step, bandwidth-bound: the weights once in their stored
    type, every distinct live K/V block once (a shared system prompt's blocks
    once for all sequences), and the new K/V written."""
    fam = family.reference(cfg)
    steps = counters["decode_steps"]
    blocks = counters["decode_live_blocks"] / steps
    written = counters["decode_live_seqs"] / steps * fam.kv_block_bytes(cfg, 1)
    return (fam.param_bytes(cfg) + blocks * fam.kv_block_bytes(cfg, BLOCK) + written) \
        / peak["hbm_bytes_s"]


COSTS = {"flash_prefill_min_s": flash_prefill_min_s,
         "decode_step_min_s": decode_step_min_s}


def cost(name: str, cfg: dict):
    """A roofline's cost function `(cfg, shapes, counters, peak) -> seconds`:
    one of the generic ones here, else the configuration's family's."""
    return COSTS.get(name) or getattr(family.reference(cfg), name)
