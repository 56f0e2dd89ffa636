"""The program's side of the family `glm4moelite` (GLM-4.7-Flash): latent
attention whose cache is one vector a position a layer, sparse experts with a
shared one, through `models/glm4moelite.py`, and the package's pod cache with
its one group of the latent kind (`models/pod.py`), which `engine.Fleet` takes
in place of `harness/pod.py`'s."""

from __future__ import annotations

from llm_d_kv_cache_manager_tpu.models.glm4moelite import (  # noqa: F401
    cache_policy, decode_step, from_published, new_pool, prefill_continue,
    prefill_paged,
)
from llm_d_kv_cache_manager_tpu.models.pod import Pod, jit_programs  # noqa: F401
