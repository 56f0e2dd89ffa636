"""The program's side of the family `afmoe`: sparse experts with a shared
expert, window and full attention layers mixed, through `models/afmoe.py`, and
the package's pod cache with two groups of slots (`models/pod.py`), which
`engine.Fleet` takes in place of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.afmoe import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
