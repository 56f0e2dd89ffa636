"""What decides `correct`: the served tokens and logits against the plain
reference, and the engine's accounting against a plain prefix-cache model.

Every number compared is printed beside its limit, on standard error and
under "checks" in the result's line; the limits are the cell's
(`benchmarks/cells/<cell>.json`, "limits"), set from chip readings of sound
runs and of the float8 control (PERF.md section 2).
"""

from __future__ import annotations

import sys
from collections import OrderedDict

import jax
import numpy as np

from . import family


def sample(requests: list[dict], k: int, seed: int) -> list[dict]:
    """k of the requests the window admitted, drawn from the seed: those it
    finished first and the longest of them first, then turn about over hits
    and misses so that each path is in it.  Where the window finished fewer
    than k (a chat answer can outlast it), the ones furthest along stand in,
    with the tokens they were served so far."""
    rng = np.random.default_rng([seed, 9])
    own = [r for r in requests if not r.get("done")
           and (r.get("finished", True) or len(r["out"]) > 1)]
    order = [own[i] for i in rng.permutation(len(own))]
    order.sort(key=lambda r: (not r.get("finished", True), -len(r["out"])))
    by_class: dict[bool, list] = {}
    for r in order[1:]:
        by_class.setdefault(r["hit"], []).append(r)
    picked = order[:1]
    while len(picked) < k and any(by_class.values()):
        for rows in by_class.values():
            if rows and len(picked) < k:
                picked.append(rows.pop(0))
    return picked


def fetch(picked: list[dict]) -> list[dict]:
    """The sample's prompts, served tokens and logits, on the host, before
    the program's state is freed."""
    return [dict(tokens=np.asarray(r["tokens"]), out=list(r["out"]),
                 top=np.asarray(r["top"], np.float32),
                 row=np.asarray(jax.device_get(r["row"]), np.float32))
            for r in picked]


def against_reference(cfg: dict, seed: int, picked: list[dict],
                      quant: str | None = None) -> dict:
    """The reference over each sampled prompt with its served tokens.

    `token_gap_max`: the widest gap by which a served token's reference logit
    lies below the reference's best.  `prefill_logits_rel_err`: distance of the
    prefill's last row of logits from the reference's, over its norm.
    `decode_logit_rel_err`: the same for the served tokens' own logits over the
    decode steps.  With `quant`, the reference in that precision stands in the
    program's place (the control): its first choice, its row, its logits."""
    reference = family.reference(cfg)
    weights = reference.make_weights(cfg, seed)
    gap, row_d, row_n, dec_d, dec_n = 0.0, 0.0, 0.0, 0.0, 0.0
    for r in picked:
        n = len(r["out"])
        seq = np.concatenate((r["tokens"], np.asarray(r["out"][:-1], np.int64)))
        ref = np.asarray(reference.forward_logits(weights, cfg, seq, n))
        if quant is None:
            out, top, row = np.asarray(r["out"]), r["top"], r["row"]
        else:
            low = np.asarray(reference.forward_logits(weights, cfg, seq, n, quant))
            out, top, row = low.argmax(-1), low.max(-1), low[0]
        at = np.arange(n)
        gap = max(gap, float((ref.max(-1) - ref[at, out]).max()))
        row_d += float(((row - ref[0]) ** 2).sum())
        row_n += float((ref[0] ** 2).sum())
        dec_d += float(((top[1:] - ref[at, out][1:]) ** 2).sum())
        dec_n += float((ref[at, out][1:] ** 2).sum())
    numbers = {"token_gap_max": gap,
               "prefill_logits_rel_err": (row_d / row_n) ** 0.5}
    if dec_n:
        numbers["decode_logit_rel_err"] = (dec_d / dec_n) ** 0.5
    return numbers


def against_cache_model(log: list[dict], pool_blocks: int) -> dict:
    """A plain prefix cache per pod (least recently used out first) over the
    same stream, from empty pools.  Counts the window's requests whose hit,
    cached length or evictions differ from what the engine did.  Evictions are
    compared where no sequence outlives its request (no decode), since live
    sequences pin blocks the plain model knows nothing of."""
    pods: dict[str, OrderedDict] = {}
    plain = not any(r.get("own") for r in log)
    wrong = 0
    for r in log:
        cache = pods.setdefault(r["pod"], OrderedDict())
        n_pre, hashes = r["prefix_blocks"], r["hashes"]
        hit = bool(n_pre) and all(h in cache for h in hashes[:n_pre])
        for h in hashes[:n_pre] if hit else ():
            cache.move_to_end(h)
        for h in hashes[n_pre if hit else 0:]:
            cache[h] = True
        evicted = 0
        while plain and len(cache) > pool_blocks:
            cache.popitem(last=False)
            evicted += 1
        if r["in_window"] and (hit != r["hit"]
                               or (n_pre if hit else 0) != r["cached_blocks"]
                               or (plain and r["evicted"] is not None
                                   and evicted != r["evicted"])):
            wrong += 1
    return {"accounting_mismatches": wrong}


def verdict(numbers: dict, limits: dict) -> bool:
    """Prints each number beside its limit on standard error (a run's last
    lines there); true when all are within."""
    ok = True
    for name, value in numbers.items():
        limit = limits[name]
        within = value <= limit
        ok &= within
        print(f"check {name} = {value:.6g} (limit {limit:g}) "
              f"{'ok' if within else 'FAILED'}", file=sys.stderr, flush=True)
    return ok
