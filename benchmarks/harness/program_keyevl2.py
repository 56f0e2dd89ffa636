"""The program's side of the family `keyevl2` (Keye-VL-2.0-30B-A3B's language
model): learned sparse attention with a selector key cached beside K and V,
softmax-routed sparse experts, through `models/keyevl2.py`, and the package's
pod cache with its one group of the selected kind (`models/pod.py`), which
`engine.Fleet` takes in place of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.keyevl2 import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
