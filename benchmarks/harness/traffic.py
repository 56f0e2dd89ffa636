"""Traffic: one general generator per `kind`, driven by a data file.

A traffic file under `benchmarks/traffic/` names its `kind` and gives that
kind's parameters.  The file fixes the shape of a run: the sequence of request
classes, every length and every due time.  `--seed` chooses only what is asked:
token ids (hence block hashes and which pod holds what), which live document a
re-ask refers to, and which chat client gets which output length.  A kind that
is not here is looked for as `harness/traffic_<kind>.py` with the same
`run(ctx)` entry.

`run(ctx)` does the kind's set-up (filling pools and index to where the traffic
would have brought them), calls `ctx.open_window()` and drives the engine until
the window closes.
"""

from __future__ import annotations

import importlib

import numpy as np

from . import engine
from .engine import BLOCK


def _tokens(vocab: int, n: int, *key: int) -> np.ndarray:
    return np.random.default_rng(list(key)).integers(1, vocab, n, dtype=np.int64)


def _request(cls: str, tokens: np.ndarray, prefix_tokens: int, due=None, **more):
    return dict(cls=cls, tokens=tokens, text=engine.prompt_text(tokens.tolist()),
                prefix_blocks=prefix_tokens // BLOCK, due=due, **more)


def _fill_pools(ctx) -> None:
    """Every pod's pool full of blocks nobody will ask for, so that the first
    request of the window evicts as the last one does."""
    for i, pod in enumerate(ctx.fleet.pods):
        ctx.fleet.fill(pod, _tokens(ctx.vocab, pod.pool_blocks * BLOCK,
                                    ctx.seed, 3, i))


def paced_sessions(tr: dict, vocab: int, seed: int, seconds: float):
    """Documents asked `asks_per_doc` times: one new document, then
    asks_per_doc-1 re-asks, repeated, on an evenly paced open loop.  Documents
    enter in epochs of `epoch_docs`; the documents of epoch e get their k-th
    re-ask during epoch e+k, in an order the seed draws, so every cycle has one
    re-ask of each age and `epoch_docs * (asks_per_doc - 1)` documents are
    live.  Returns (set-up requests, window requests)."""
    per, lanes = tr["epoch_docs"], tr["asks_per_doc"] - 1
    cycle = 1 + lanes
    spacing = 1.0 / tr["rate_rps"]
    jitter = np.random.default_rng(tr["jitter_seed"])
    docs: dict[int, np.ndarray] = {}
    first_cycle = -per * lanes

    def cycle_requests(c: int, dues):
        e, i = divmod(c, per)
        out = []
        docs[c] = _tokens(vocab, tr["doc_tokens"], seed, 0, c + 2**20)
        asked = [c] + [
            per * (e - k) + int(np.random.default_rng(
                [seed, 1, e - k + 2**20, k]).permutation(per)[i])
            for k in range(1, lanes + 1)]
        for j, (doc, due) in enumerate(zip(asked, dues)):
            if doc < first_cycle:
                continue  # set-up: older documents were never made
            question = _tokens(vocab, tr["question_tokens"], seed, 2, c + 2**20, j)
            out.append(_request("new" if j == 0 else "reask",
                                np.concatenate((docs[doc], question)),
                                tr["doc_tokens"], due, doc=doc))
        return out

    setup = [r for c in range(first_cycle, 0)
             for r in cycle_requests(c, [None] * cycle)]
    n = int(seconds * tr["rate_rps"])
    dues = [(k + tr["jitter_frac"] * jitter.uniform(-1, 1)) * spacing
            for k in range(-(-n // cycle) * cycle)]
    window = [r for c in range(-(-n // cycle))
              for r in cycle_requests(c, dues[c * cycle:(c + 1) * cycle])]
    return setup, window[:n]


def run_paced_sessions(ctx) -> None:
    setup, window = paced_sessions(ctx.traffic, ctx.vocab, ctx.seed, ctx.seconds)
    _fill_pools(ctx)
    for req in setup:
        ctx.fleet.serve_prefill(req, 0.0, 0.0)
    engine.run_stream(ctx.fleet, window, ctx.open_window(), ctx.seconds)


def backlog(tr: dict, vocab: int, seed: int, seconds: float):
    """Prompts never seen before, as many as the window could take."""
    n = tr["warm_requests"] + int(seconds * tr["max_rps"]) + 1
    return [_request("new", _tokens(vocab, tr["prompt_tokens"], seed, 0, k), 0)
            for k in range(n)]


def run_backlog(ctx) -> None:
    requests = backlog(ctx.traffic, ctx.vocab, ctx.seed, ctx.seconds)
    warm = ctx.traffic["warm_requests"]
    _fill_pools(ctx)
    for req in requests[:warm]:
        ctx.fleet.serve_prefill(req, 0.0, 0.0)
    engine.run_stream(ctx.fleet, requests[warm:], ctx.open_window(), ctx.seconds)


def chat_clients(tr: dict, vocab: int, seed: int):
    """One endless iterator of requests per client: a shared system prompt
    (client + round, modulo their number) and a turn of its own.  The output
    lengths are the file's multiset, dealt anew to the clients each round by
    the seed.  A client's first request starts `done` tokens into its answer."""
    n, lengths = tr["slots"], tr["output_lengths"]
    systems = [_tokens(vocab, tr["system_tokens"], seed, 0, s)
               for s in range(tr["system_prompts"])]

    def client(i: int):
        r = 0
        while True:
            n_out = int(np.random.default_rng([seed, 1, r]).permutation(lengths)[i])
            s = (i + r) % len(systems)
            turn = _tokens(vocab, tr["turn_tokens"], seed, 2, i, r)
            yield _request("chat", np.concatenate((systems[s], turn)),
                           tr["system_tokens"], system=s, n_out=n_out,
                           done=(n_out - 1) * (2 * i + 1) // (2 * n) if r == 0 else 0)
            r += 1

    return [client(i) for i in range(n)]


def run_closed_loop_chat(ctx) -> None:
    engine.run_chat(ctx.fleet, chat_clients(ctx.traffic, ctx.vocab, ctx.seed),
                    ctx.seconds, ctx.open_window)


def shapes(tr: dict) -> dict:
    """The shapes the cell's traffic compiles, and no others."""
    kind = tr["kind"]
    if kind == "paced_sessions":
        return {"miss": (tr["doc_tokens"] + tr["question_tokens"],),
                "hit": (tr["doc_tokens"], tr["question_tokens"])}
    if kind == "backlog":
        return {"miss": (tr["prompt_tokens"],)}
    if kind == "closed_loop_chat":
        total = tr["system_tokens"] + tr["turn_tokens"]
        return {"miss": (total,), "hit": (tr["system_tokens"], tr["turn_tokens"]),
                "decode": (tr["slots"],),
                "max_blocks": -(-(total + max(tr["output_lengths"])) // BLOCK)}
    return importlib.import_module(f"{__package__}.traffic_{kind}").shapes(tr)


def run(ctx) -> None:
    kind = ctx.traffic["kind"]
    here = globals().get(f"run_{kind}")
    (here or importlib.import_module(f"{__package__}.traffic_{kind}").run)(ctx)
