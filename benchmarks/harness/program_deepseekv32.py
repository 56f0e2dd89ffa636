"""The program's side of the family `deepseekv32` (DeepSeek-V3.2-Exp): latent
attention under a learned selection, the selector's key cached in the latent's
slot, group-limited experts of which this chip holds a share, through
`models/deepseekv32.py`, and the package's pod cache with its one group of the
latent-selected kind (`models/pod.py`), which `engine.Fleet` takes in place of
`harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.deepseekv32 import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
