"""The program's side of the family `lfm2moe`: short-convolution and full
attention layers mixed, sparse experts without a shared one, through
`models/lfm2moe.py`, and the package's pod cache with a state group
(`models/pod.py`), which `engine.Fleet` takes in place of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.lfm2moe import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
