"""Perf-trend gate (hack/perf_trend.py; ISSUE 14 satellite).

The acceptance contract directly: the tool passes on the artifacts the
repo still keeps, fails on a synthetic regressed artifact,
parses every artifact shape the trajectory contains (parsed /
headline / compact), and skips errored runs as baselines.
"""

from __future__ import annotations

import json
import os

from hack.perf_trend import (
    evaluate,
    extract_headlines,
    load_trajectory,
    main,
)

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _write(tmp_path, name: str, artifact: dict) -> None:
    (tmp_path / name).write_text(json.dumps(artifact))


class TestExtraction:
    def test_parsed_shape(self):
        headlines = extract_headlines(
            {
                "n": 1,
                "rc": 0,
                "parsed": {
                    "metric": "p50_ttft_speedup_precise_vs_round_robin",
                    "value": 4.457,
                    "unit": "x",
                },
            }
        )
        assert headlines == {"ttft.speedup": 4.457}

    def test_headline_regime_shape(self):
        headlines = extract_headlines(
            {
                "n": 6,
                "rc": 0,
                "headline": {
                    "regime": "event_storm",
                    "apply_msgs_per_sec": 519.1,
                    "consistency": 1.0,
                },
            }
        )
        assert headlines == {
            "event_storm.apply_sps": 519.1,
            "event_storm.consistency": 1.0,
        }

    def test_compact_shape_with_blocks(self):
        headlines = extract_headlines(
            {
                "n": 7,
                "rc": 0,
                "compact": {
                    "metric": "p50_ttft_speedup_precise_vs_round_robin",
                    "value": 4.0,
                    "read_path": {
                        "warm_sps": 2800.0,
                        "cold_sps": 90.0,
                        "mixed_sps": 170.0,
                    },
                    "event_storm": {
                        "apply_sps": 6000.0,
                        "consistency": 1.0,
                    },
                    "replica_scaleout": {
                        "single_sps": 2500.0,
                        "cluster3_sps": 400.0,
                    },
                },
            }
        )
        assert headlines["ttft.speedup"] == 4.0
        assert headlines["read_path.warm_sps"] == 2800.0
        assert headlines["event_storm.apply_sps"] == 6000.0
        assert headlines["replica_scaleout.cluster3_sps"] == 400.0

    def test_full_regime_cells_shape(self):
        headlines = extract_headlines(
            {
                "rc": 0,
                "read_path": {
                    "warm_multi_turn": {"scores_per_sec": 2843.5}
                },
                "replica_scaleout": {
                    "single": {"scores_per_sec": 2000.0},
                    "cluster_3_replicas": {"scores_per_sec": 300.0},
                },
                "event_storm": {
                    "consolidated_pollers_1": {
                        "apply_msgs_per_sec": 519.1
                    },
                    "gap_storm": {"post_resync_consistency": 1.0},
                },
            }
        )
        assert headlines["read_path.warm_sps"] == 2843.5
        assert headlines["replica_scaleout.single_sps"] == 2000.0
        assert headlines["event_storm.apply_sps"] == 519.1
        assert headlines["event_storm.consistency"] == 1.0

    def test_errored_artifact_yields_nothing(self):
        assert (
            extract_headlines(
                {
                    "n": 4,
                    "rc": 0,
                    "parsed": {
                        "metric": "p50_ttft_speedup_precise",
                        "value": 0.0,
                        "error": "device unavailable",
                    },
                }
            )
            == {}
        )
        assert extract_headlines({"n": 9, "rc": 1}) == {}


class TestGate:
    def test_passes_on_real_trajectory(self):
        assert main(["--dir", REPO_ROOT]) == 0

    def test_trajectory_of_mixed_shapes_has_headlines(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "parsed": {
                    "metric": "p50_ttft_speedup_precise_vs_round_robin",
                    "value": 4.0,
                },
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "headline": {
                    "regime": "event_storm",
                    "apply_msgs_per_sec": 500.0,
                },
            },
        )
        runs = load_trajectory(str(tmp_path))
        assert [n for n, _, _ in runs] == [1, 2]
        measured = {
            key for _, _, headlines in runs for key in headlines
        }
        assert "ttft.speedup" in measured
        assert "event_storm.apply_sps" in measured

    def test_fails_on_synthetic_regression(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "headline": {
                    "regime": "event_storm",
                    "apply_msgs_per_sec": 500.0,
                },
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 400.0}},
            },
        )
        assert main(["--dir", str(tmp_path)]) == 1

    def test_within_threshold_passes(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 500.0}},
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 460.0}},
            },
        )
        assert main(["--dir", str(tmp_path)]) == 0

    def test_errored_run_never_baselines(self, tmp_path):
        # r2 is errored — the r3 value compares against r1, and a
        # regression vs r1 still fails even with the errored run in
        # between.
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 500.0}},
            },
        )
        _write(tmp_path, "BENCH_r02.json", {"n": 2, "rc": 1})
        _write(
            tmp_path,
            "BENCH_r03.json",
            {
                "n": 3,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 100.0}},
            },
        )
        assert main(["--dir", str(tmp_path)]) == 1

    def test_headline_absent_from_newest_not_compared(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"read_path": {"warm_sps": 9000.0}},
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 100.0}},
            },
        )
        assert main(["--dir", str(tmp_path)]) == 0

    def test_unreadable_artifact_skipped(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text("{not json")
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 100.0}},
            },
        )
        assert main(["--dir", str(tmp_path)]) == 0

    def test_empty_directory_passes(self, tmp_path):
        assert main(["--dir", str(tmp_path)]) == 0

    def test_custom_threshold(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 500.0}},
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 460.0}},
            },
        )
        # 8% drop: inside the default gate, outside a 5% one.
        assert main(["--dir", str(tmp_path)]) == 0
        assert (
            main(["--dir", str(tmp_path), "--threshold", "0.05"]) == 1
        )

    def test_table_marks_regression(self, tmp_path):
        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 500.0}},
            },
        )
        _write(
            tmp_path,
            "BENCH_r02.json",
            {
                "n": 2,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 100.0}},
            },
        )
        runs = load_trajectory(str(tmp_path))
        lines, regressions = evaluate(runs, 0.10)
        assert regressions and "event_storm.apply_sps" in regressions[0]
        assert any("REGRESSED" in line for line in lines)


class TestMultichipDisplay:
    """ISSUE 15 satellite: MULTICHIP_r*.json folded into the trend
    table — display-only, never gated."""

    def test_extracts_status_and_devices(self):
        from hack.perf_trend import extract_multichip

        assert extract_multichip(
            {"n_devices": 8, "rc": 0, "ok": True, "tail": ""}
        ) == {"status": "ok", "n_devices": 8}
        assert extract_multichip({"rc": 1, "tail": "boom"})["status"] == (
            "FAIL(rc=1)"
        )
        assert extract_multichip({"skipped": True})["status"] == "skipped"

    def test_extracts_numeric_throughput_fields(self):
        from hack.perf_trend import extract_multichip

        facts = extract_multichip(
            {
                "n_devices": 4,
                "rc": 0,
                "staged_mb_s": 123.4,
                "host_offload": {"lanes_best_mb_s": 456.0},
                "tail": "staged offload dry run ok on 4 chips",
            }
        )
        assert facts["staged_mb_s"] == 123.4
        assert facts["lanes_best_mb_s"] == 456.0
        assert facts["staged_offload"] == "ok"

    def test_display_lines_and_never_gated(self, tmp_path):
        from hack.perf_trend import (
            load_multichip_trajectory,
            main,
            multichip_lines,
        )

        _write(
            tmp_path,
            "BENCH_r01.json",
            {
                "n": 1,
                "rc": 0,
                "compact": {"event_storm": {"apply_sps": 500.0}},
            },
        )
        _write(
            tmp_path,
            "MULTICHIP_r01.json",
            {"n_devices": 8, "rc": 1, "tail": "exploded"},
        )
        _write(
            tmp_path,
            "MULTICHIP_r02.json",
            {"n_devices": 8, "rc": 0, "staged_mb_s": 99.5, "tail": ""},
        )
        runs = load_multichip_trajectory(str(tmp_path))
        assert [n for n, _, _ in runs] == [1, 2]
        lines = multichip_lines(runs)
        assert any("FAIL(rc=1)" in line for line in lines)
        assert any("staged_mb_s=99.500" in line for line in lines)
        # A failing MULTICHIP artifact never fails the gate.
        assert main(["--dir", str(tmp_path)]) == 0

    def test_every_multichip_run_carries_a_status(self, tmp_path):
        from hack.perf_trend import load_multichip_trajectory

        for n in range(1, 4):
            _write(
                tmp_path,
                f"MULTICHIP_r{n:02d}.json",
                {"n_devices": 8, "rc": 0, "ok": True, "tail": ""},
            )
        runs = load_multichip_trajectory(str(tmp_path))
        assert len(runs) == 3
        assert all("status" in facts for _, _, facts in runs)

    def test_unreadable_multichip_skipped(self, tmp_path):
        from hack.perf_trend import load_multichip_trajectory

        (tmp_path / "MULTICHIP_r01.json").write_text("{nope")
        assert load_multichip_trajectory(str(tmp_path)) == []


class TestWhatIfGate:
    """ISSUE 18 tentpole: WHATIF_r*.json capacity trajectory + the
    live reference A/B, gated like bench headlines."""

    def _whatif(self, n, hit_rate=0.75, parity=1.0):
        return {
            "run": n,
            "rc": 0,
            "headlines": {
                "whatif.hit_rate": hit_rate,
                "whatif.recorded_parity": parity,
                "whatif.ab_hit_parity": parity,
            },
        }

    def test_extract_shapes(self):
        from hack.perf_trend import extract_whatif

        assert extract_whatif(self._whatif(1))["whatif.hit_rate"] == 0.75
        assert extract_whatif({"rc": 1, "headlines": {"x": 1.0}}) == {}
        assert extract_whatif({"rc": 0, "headlines": "nope"}) == {}
        # Non-positive and non-numeric values never become baselines.
        assert (
            extract_whatif(
                {"rc": 0, "headlines": {"a": 0.0, "b": "x", "c": 2.0}}
            )
            == {"c": 2.0}
        )

    def test_real_trajectory_parses(self):
        from hack.perf_trend import load_whatif_trajectory

        runs = load_whatif_trajectory(REPO_ROOT)
        assert len(runs) >= 1
        assert "whatif.hit_rate" in runs[-1][2]
        assert runs[-1][2]["whatif.recorded_parity"] == 1.0

    def test_trajectory_regression_fails(self, tmp_path):
        _write(tmp_path, "WHATIF_r01.json", self._whatif(1, hit_rate=0.80))
        _write(tmp_path, "WHATIF_r02.json", self._whatif(2, hit_rate=0.40))
        assert (
            main(["--dir", str(tmp_path), "--skip-whatif"]) == 1
        )

    def test_trajectory_within_threshold_passes(self, tmp_path):
        _write(tmp_path, "WHATIF_r01.json", self._whatif(1, hit_rate=0.80))
        _write(tmp_path, "WHATIF_r02.json", self._whatif(2, hit_rate=0.75))
        assert (
            main(["--dir", str(tmp_path), "--skip-whatif"]) == 0
        )

    def test_no_artifacts_means_no_whatif_gate(self, tmp_path):
        from hack.perf_trend import whatif_evaluate

        assert whatif_evaluate([], 0.10, "/nope", False) == ([], [])

    def test_skip_live_still_gates_trajectory(self, tmp_path):
        from hack.perf_trend import load_whatif_trajectory, whatif_evaluate

        _write(tmp_path, "WHATIF_r01.json", self._whatif(1, hit_rate=0.80))
        _write(tmp_path, "WHATIF_r02.json", self._whatif(2, hit_rate=0.40))
        runs = load_whatif_trajectory(str(tmp_path))
        lines, regressions = whatif_evaluate(runs, 0.10, "/nope", True)
        assert any("--skip-whatif" in line for line in lines)
        assert regressions and "whatif.hit_rate" in regressions[0]

    def test_missing_reference_skips_live_cleanly(self, tmp_path):
        from hack.perf_trend import load_whatif_trajectory, whatif_evaluate

        _write(tmp_path, "WHATIF_r01.json", self._whatif(1))
        runs = load_whatif_trajectory(str(tmp_path))
        lines, regressions = whatif_evaluate(
            runs, 0.10, str(tmp_path / "nope.cbor"), False
        )
        assert any("no reference capture" in line for line in lines)
        assert regressions == []

    def test_live_check_fails_inflated_baseline(self, tmp_path):
        """A recorded baseline the live engine can no longer meet is
        a capacity regression — the exact planted case the smoke
        drives through the CLI, here in-process."""
        from hack.perf_trend import load_whatif_trajectory, whatif_evaluate

        reference = os.path.join(
            REPO_ROOT, "tests", "testdata", "whatif_reference.cbor"
        )
        _write(tmp_path, "WHATIF_r01.json", self._whatif(1, hit_rate=0.99))
        runs = load_whatif_trajectory(str(tmp_path))
        lines, regressions = whatif_evaluate(runs, 0.10, reference, False)
        assert any("live reference A/B" in line for line in lines)
        assert any("whatif.hit_rate (live)" in r for r in regressions)
        # The parity headlines match the planted artifact exactly, so
        # only the inflated one regresses.
        assert len(regressions) == 1
