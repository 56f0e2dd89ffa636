"""A configuration names its model family, and the family's code is found by
that name, as a traffic kind's is (`traffic.py`):

  harness/family_<family>.py   the benchmark's side, which imports nothing of
      the program: `make_weights(cfg, seed)`, `forward_logits(weights, cfg,
      tokens, n_last, quant=None)` (the plain reference and its control), and
      the least-work counts the generic rooflines of `costs.py` ask for,
      `param_bytes(cfg)`, `kv_block_bytes(cfg, block)`,
      `prefill_attention_flops(cfg, T)`.  A cost function that `costs.COSTS`
      does not hold is looked for here too.
  harness/program_<family>.py  the program's side, the only place a model
      module is imported: `from_published(cfg, block_size)`,
      `new_pool(model, pool_blocks)`, `prefill_paged`, `prefill_continue`,
      `decode_step`; and, where the family needs another cache manager than
      `harness/pod.py`, its own `Pod` and `jit_programs`.

A configuration file without a `family` is an error, not a default.
"""

from __future__ import annotations

import importlib

NAMES = {"family": ("make_weights", "forward_logits", "param_bytes",
                    "kv_block_bytes", "prefill_attention_flops"),
         "program": ("from_published", "new_pool", "prefill_paged",
                     "prefill_continue", "decode_step")}


def _find(side: str, cfg: dict):
    if "family" not in cfg:
        raise KeyError("the configuration file names no `family`")
    where = f"harness/{side}_{cfg['family']}.py"
    try:
        module = importlib.import_module(f"{__package__}.{side}_{cfg['family']}")
    except ModuleNotFoundError as e:
        raise KeyError(f"family {cfg['family']!r} has no {where}") from e
    missing = [n for n in NAMES[side] if not hasattr(module, n)]
    if missing:
        raise KeyError(f"{where} lacks {', '.join(missing)}")
    return module


def reference(cfg: dict):
    return _find("family", cfg)


def program(cfg: dict):
    return _find("program", cfg)
