"""Guards on bench.py's fleet-simulation semantics.

The headline number's meaning rests on these behaviors; a silent change
to any of them would alter what the benchmark measures without failing
anything.  All run on CPU with no device arrays (SimPod with_kv=False).
"""

import random

import pytest

import bench
from bench import (
    EstimatedScorer,
    FleetRouter,
    SimPod,
    block_hash_chain,
    poisson_arrivals,
    run_fleet_virtual,
    warmup_indexes,
)

BS = bench.BLOCK_SIZE


def prefix_tokens(n_blocks, seed=1):
    rng = random.Random(seed)
    return [rng.randrange(1, 1000) for _ in range(n_blocks * BS)]


class TestBlockHashChain:
    def test_deterministic_and_chained(self):
        tokens = prefix_tokens(4)
        a = block_hash_chain(tokens)
        b = block_hash_chain(tokens)
        assert a == b and len(a) == 4
        # A change in block 0 reflows every later hash (chaining).
        mutated = [tokens[0] + 1] + tokens[1:]
        c = block_hash_chain(mutated)
        assert all(x != y for x, y in zip(a, c))

    def test_partial_block_dropped(self):
        tokens = prefix_tokens(2) + [5]  # one dangling token
        assert len(block_hash_chain(tokens)) == 2


class TestSimPodAllocator:
    def test_wrap_evicts_and_reports(self):
        pod = SimPod("p", with_kv=False, pool_blocks=4)
        hashes = [10, 11, 12, 13]
        ids, evicted = pod.alloc(4)
        assert evicted == []
        for h, bid in zip(hashes, ids):
            pod.cached[h] = bid
            pod._block_owner[bid] = h
        # Wrapping reuses block 0 and 1: their hashes must be evicted.
        _, evicted = pod.alloc(2)
        assert set(evicted) == {10, 11}
        assert 10 not in pod.cached and 12 in pod.cached

    def test_cached_prefix_stops_at_first_miss(self):
        pod = SimPod("p", with_kv=False, pool_blocks=8)
        pod.cached = {1: 0, 2: 1, 4: 3}
        assert pod.cached_prefix_blocks([1, 2, 3, 4]) == [0, 1]


class TestEstimatedScorer:
    def test_longest_prefix_wins(self):
        scorer = EstimatedScorer()
        scorer.record("a", [1, 2])
        scorer.record("b", [1, 2, 3])
        assert scorer.pick(["a", "b"], [1, 2, 3, 4]) == "b"

    def test_unknown_prefix_returns_none(self):
        scorer = EstimatedScorer()
        scorer.record("a", [1])
        assert scorer.pick(["a"], [99]) is None

    def test_lru_cap(self):
        scorer = EstimatedScorer(capacity_per_pod=2)
        scorer.record("a", [1, 2, 3])  # 1 falls off
        assert scorer.pick(["a"], [1]) is None
        assert scorer.pick(["a"], [2]) == "a"


class TestFleetRouterSemantics:
    def _router(self, strategy, **kwargs):
        return FleetRouter(strategy, with_kv=False, **kwargs)

    def test_round_robin_cycles(self):
        fleet = self._router("round_robin")
        try:
            n = bench.NUM_PODS
            pods = [
                fleet.route("", [1])[0].name for _ in range(2 * n)
            ]
            assert pods[:n] == sorted(set(pods))
            assert pods[:n] == pods[n:]
        finally:
            fleet.shutdown()

    def test_load_routes_to_least_backlogged(self):
        fleet = self._router("load")
        try:
            for name in fleet.pod_free_at:
                fleet.pod_free_at[name] = 5.0
            fleet.pod_free_at["pod-2"] = 1.0
            assert fleet.route("", [1])[0].name == "pod-2"
        finally:
            fleet.shutdown()

    def test_account_register_commit_roundtrip(self):
        """A committed full-prefix request must hit on re-arrival, and
        the register-only-new-blocks invariant must hold: a hit commit
        never re-registers prefix hashes."""
        fleet = self._router("round_robin")
        try:
            pod = fleet.pods[0]
            n_pre = bench.PREFIX_TOKENS // BS
            tokens = prefix_tokens(n_pre + 2)
            hashes = block_hash_chain(tokens)
            hit, first_new, block_ids, evicted = fleet.account(pod, hashes)
            assert not hit and first_new == 0
            fleet.commit(pod, tokens, hashes, first_new, block_ids, evicted)
            hit2, first_new2, block_ids2, _ = fleet.account(pod, hashes)
            assert hit2 and first_new2 == n_pre
            assert block_ids2[:n_pre] == block_ids[:n_pre]
        finally:
            fleet.shutdown()

    def test_precise_learns_through_real_indexer(self):
        fleet = self._router("precise")
        try:
            pod = fleet.pods[2]
            n_pre = bench.PREFIX_TOKENS // BS
            tokens = prefix_tokens(n_pre + 1, seed=7)
            text = " ".join(f"t{t}" for t in tokens)
            hashes = block_hash_chain(tokens)
            _, first_new, block_ids, evicted = fleet.account(pod, hashes)
            fleet.commit(pod, tokens, hashes, first_new, block_ids, evicted)
            chosen, routing_s = fleet.route(text, hashes)
            assert chosen.name == pod.name
            assert routing_s > 0  # real measured indexer wall time
        finally:
            fleet.shutdown()

    def test_zero_score_fallback_is_sticky_affinity(self):
        fleet = self._router("precise")
        try:
            hashes = block_hash_chain(prefix_tokens(4, seed=9))
            first, _ = fleet.route("t1", hashes)
            # Nothing indexed: record routing history, then the same
            # prefix must go back to the same pod (no rr scatter).
            fleet.estimated.record(first.name, hashes)
            again, _ = fleet.route("t1", hashes)
            assert again.name == first.name
        finally:
            fleet.shutdown()


class TestVirtualClock:
    def test_queueing_builds_ttft(self):
        """Round-robin over NUM_PODS pods with simultaneous arrivals:
        the wrap-around request queues behind the busy pod AND hits its
        cached prefix (TTFT = wait + t_hit)."""
        n_pre = bench.PREFIX_TOKENS // BS
        tokens = prefix_tokens(n_pre + 1)
        n = bench.NUM_PODS + 1
        requests = [(0, "", tokens)] * n
        hashes_list = [block_hash_chain(tokens)] * n
        ttfts, hit_rate, depth, _ = run_fleet_virtual(
            "round_robin",
            requests,
            hashes_list,
            arrivals=[0.0] * n,
            t_miss=1.0,
            t_hit=0.1,
            seed=0,
        )
        assert ttfts[: bench.NUM_PODS] == pytest.approx(
            [1.0] * bench.NUM_PODS
        )
        assert ttfts[-1] == pytest.approx(1.0 + 0.1)
        assert depth > 0

    def test_restart_wipes_history_not_index(self):
        n_pre = bench.PREFIX_TOKENS // BS
        tokens = prefix_tokens(n_pre + 1, seed=3)
        text = " ".join(f"t{t}" for t in tokens)
        requests = [(0, text, tokens)] * 4
        hashes_list = [block_hash_chain(tokens)] * 4
        arrivals = [0.0, 10.0, 20.0, 30.0]
        # Precise: indexed state survives the reset -> 3 of 4 hit.
        ttfts, hit_rate, _, _ = run_fleet_virtual(
            "precise", requests, hashes_list, arrivals,
            t_miss=1.0, t_hit=0.1, seed=0, reset_history_at=2,
        )
        assert hit_rate == pytest.approx(0.75)
        # Estimated: history reset at 2 -> request 2 falls to rr and
        # can land on a cold pod; hit rate <= precise's.
        _, est_hit, _, _ = run_fleet_virtual(
            "estimated", requests, hashes_list, arrivals,
            t_miss=1.0, t_hit=0.1, seed=0, reset_history_at=2,
        )
        assert est_hit <= hit_rate


class TestHarness:
    def test_warmup_indexes_marks_first_arrivals(self):
        requests = [(1, "", []), (0, "", []), (1, "", []), (0, "", [])]
        assert warmup_indexes(requests) == {0, 1}

    def test_poisson_deterministic_per_seed(self):
        a = poisson_arrivals(10.0, 5, seed=3)
        b = poisson_arrivals(10.0, 5, seed=3)
        c = poisson_arrivals(10.0, 5, seed=4)
        assert a == b != c
        assert all(x < y for x, y in zip(a, a[1:]))


class TestDriverContract:
    """The driver runs `python bench.py` under an unknown timeout and
    captures only the LAST ~2 KB of stdout; these guards pin the
    degrade-don't-die behavior AND the tail-survivable emit contract
    end to end in a real subprocess (tiny geometry, the CPU asked for
    explicitly): compact headline (< 1.5 KB) as the final line, full
    detail in the results file — and no artifact at all when the run
    finds no TPU and was not told to use the CPU."""

    @staticmethod
    def _run(extra_env):
        """Returns (full_results_doc, compact_headline, stdout_lines,
        stderr) after asserting the emit contract's line layout."""
        import json
        import os
        import subprocess
        import sys
        import tempfile

        env = {
            k: v
            for k, v in os.environ.items()
            # Ambient knobs (an exported KVTPU_BENCH_BUDGET_S, say)
            # must not leak in and flip the truncation asserts.
            if not k.startswith("KVTPU_BENCH_")
        }
        results_path = os.path.join(
            tempfile.mkdtemp(prefix="kvtpu-bench-test-"),
            "results.json",
        )
        env.update(
            KVTPU_BENCH_PLATFORM="cpu",
            KVTPU_BENCH_TINY="1",
            KVTPU_BENCH_RESULTS_PATH=results_path,
            JAX_PLATFORMS="cpu",
        )
        env.update(extra_env)
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "bench.py"],
            cwd=here,
            env=env,
            capture_output=True,
            text=True,
            timeout=500,
        )
        assert proc.returncode == 0, proc.stderr[-1500:]
        lines = [
            line for line in proc.stdout.splitlines() if line.strip()
        ]
        # The final line is the compact headline and must survive the
        # driver's ~2 KB tail capture with margin.
        assert len(lines[-1].encode()) < 1536, len(lines[-1])
        compact = json.loads(lines[-1])
        assert compact["results"] == results_path
        with open(results_path) as handle:
            full = json.load(handle)
        # The compact line mirrors the full artifact's headline.
        assert compact["value"] == full["value"]
        return full, compact, lines, proc.stderr

    def test_full_tiny_run_emits_all_layers(self):
        # Malformed knobs ride along: they must fall back to defaults
        # (so this stays a FULL run) with a stderr note — asserting the
        # env-fallback contract without paying a third subprocess run.
        result, compact, lines, stderr = self._run(
            {"KVTPU_BENCH_BUDGET_S": "half-an-hour"}
        )
        detail = result["detail"]
        assert result["value"] > 0
        assert not detail["headline_seeds_truncated"]
        assert not detail["matrix_truncated"]
        assert not detail["decode_truncated"]
        assert len(detail["matrix"]) == 32  # 5x5 ladder + 5 churn + 2 restart
        assert detail["service_times"] == "measured"
        assert detail["routing_precise_us"]["p99"] > 0
        assert detail["micro"]["index_lookup_us_per_chain"] > 0
        assert "[bench +" in stderr  # phase progress lines
        assert detail["budget_s"] == 1500.0
        assert "ignoring malformed" in stderr
        # Persistence regime (acceptance): a warm-recovered index must
        # route at least as well as a cold restart, and the comparison
        # must ride the compact headline so the driver sees it.
        restart = compact["indexer_restart"]
        assert restart == detail["indexer_restart"]
        assert restart["warm_hit_rate"] >= restart["cold_hit_rate"]
        assert restart["recovered_block_keys"] > 0

    def test_tight_budget_degrades_not_dies(self):
        result, compact, _, _ = self._run({"KVTPU_BENCH_BUDGET_S": "1"})
        detail = result["detail"]
        # Headline still present and real; optional layers flagged.
        assert result["value"] > 0
        assert compact["value"] > 0
        assert len(detail["headline_seeds"]) >= 1
        assert detail["decode_truncated"]
        assert detail["matrix_truncated"]
        assert detail["decode_tok_s_per_seq"] is None

    def test_no_device_is_an_error_not_an_artifact(self, tmp_path):
        """A run that finds no TPU and was not asked to use the CPU
        exits non-zero, says why, and writes nothing: no headline line,
        no results file, no numbers from constants."""
        import os
        import subprocess
        import sys

        results_path = tmp_path / "results.json"
        env = {
            k: v
            for k, v in os.environ.items()
            if not k.startswith("KVTPU_BENCH_")
        }
        env.update(
            JAX_PLATFORMS="cpu",
            KVTPU_BENCH_TINY="1",
            KVTPU_BENCH_RESULTS_PATH=str(results_path),
        )
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "bench.py"],
            cwd=here,
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode != 0
        assert "no TPU" in proc.stderr
        assert proc.stdout.strip() == ""
        assert not results_path.exists()
