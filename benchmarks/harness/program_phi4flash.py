"""The program's side of the family `phi4flash`: Mamba-1 layers alternating
with differential window attention, one full attention layer whose K/V the
later attention layers read, gated memory units between those, through
`models/phi4flash.py`, and the package's pod cache with three groups of slots
(`models/pod.py`: full, window and state), which `engine.Fleet` takes in place
of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.phi4flash import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
