"""The program's side of the family `nemotronh`: Mamba-2 layers (a state that
is a matrix a head), squared-ReLU experts of which this chip holds a share, and
grouped attention without a position encoding, one mixer a layer, through
`models/nemotronh.py`, and the package's pod cache with a state group
(`models/pod.py`), which `engine.Fleet` takes in place of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.nemotronh import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
