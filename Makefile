# Developer entry points (reference: Makefile targets unit-test /
# e2e-test, .github/workflows/ci-pr-checks.yaml).

PYTHON ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all lint kvlint racefuzz-smoke lockorder-smoke test unit-test e2e-test examples obs-smoke slo-smoke profile-smoke events-smoke cachestats-smoke tiering-smoke transfer-smoke cluster-smoke offload-smoke replay-smoke whatif-smoke chip-smoke native native-race proto graft-check chart clean

all: native test

# Same invocation as CI's lint step (.github/workflows/ci.yaml); the
# flags also live in .flake8 so a bare `flake8` agrees.  The native
# format gate is HARD: real clang-format when installed, and always
# the portable subset checker (hack/check_native_format.py) — the
# same pair CI enforces.
lint:
	@if $(PYTHON) -c "import flake8" >/dev/null 2>&1; then \
		$(PYTHON) -m flake8 llm_d_kv_cache_manager_tpu tests examples \
			--max-line-length 100 --extend-ignore E203,W503; \
	else \
		echo "flake8 not installed; skipping python lint (CI runs it)"; \
	fi
	@if command -v clang-format >/dev/null 2>&1; then \
		clang-format --dry-run --Werror \
			llm_d_kv_cache_manager_tpu/native/src/*.cpp \
			llm_d_kv_cache_manager_tpu/native/src/*.hpp; \
	fi
	$(PYTHON) hack/check_native_format.py
	$(MAKE) kvlint

# Project-invariant static analysis (hack/kvlint, stdlib-only; see
# docs/static-analysis.md): per-file rules (lock discipline, tracer
# safety, canonical serialization, blocking-in-async, swallowed
# errors, shutdown discipline, split-lock atomicity, GIL-dependence)
# plus the whole-program pass (lock-order graph, contract-surface
# drift vs docs/) and the raceguard-manifest staleness pin — one
# invocation, same as CI and hooks/pre-commit.sh.
kvlint:
	$(PYTHON) -m hack.kvlint llm_d_kv_cache_manager_tpu --check-manifest

# Preemption-fuzzed storms under guarded-by runtime enforcement
# (hack/racefuzz.py; docs/static-analysis.md): two storms re-run with
# raceguard armed, sys.setswitchinterval(1e-6) and seeded yield
# injection at guarded-access/lock-acquire boundaries, plus the three
# planted defects that prove the harness can see what it claims.
# Bounded time, pinned seed — same invocation as CI's
# "Race-certification smoke" step.
racefuzz-smoke:
	$(PYTHON) -m hack.racefuzz --plant guarded-write --seed 1337
	$(PYTHON) -m hack.racefuzz --plant caller-locked --seed 1337
	$(PYTHON) -m hack.racefuzz --plant check-then-act --seed 1337
	$(PYTHON) -m hack.racefuzz --seed 1337 --time-budget 180 --storms \
		tests/test_concurrency.py::TestBackendStorm \
		tests/test_concurrency.py::TestShardedIndexStorm \
		tests/test_concurrency.py::TestClusterFanoutStorm

# Dynamic half of kvlint KV006 (same invocation as CI's "Lock-order
# watchdog smoke" step): the concurrency storms plus the watchdog unit
# suite with KVTPU_LOCK_ORDER_DEBUG=1, so every tracked lock —
# including ones constructed at import time — asserts the declared
# acquisition order while the storms hammer it (docs/static-analysis.md).
lockorder-smoke:
	KVTPU_LOCK_ORDER_DEBUG=1 $(PYTHON) -m pytest tests/test_concurrency.py tests/test_lockorder.py -q

test: unit-test

unit-test:
	$(PYTHON) -m pytest tests/ -x -q

e2e-test:
	$(PYTHON) -m pytest tests/test_indexer_e2e.py tests/test_zmq_integration.py tests/test_grpc_api.py tests/test_http_service.py tests/test_service_e2e.py tests/test_debug_surface.py -q

examples:
	bash hack/verify-examples.sh

# Tracing debug-surface smoke (same invocation as CI's
# "Observability smoke" step): booted service, traceparent round-trip,
# /debug/traces retrieval, explain=1, /healthz block.
obs-smoke:
	$(PYTHON) hack/verify_observability.py

# Fleet observability smoke (same invocation as CI's "SLO smoke"
# step): 3 strict-wire replicas behind a router service — a scored
# request stitches into ONE cross-replica trace (owner cluster.rpc
# spans + piggybacked replica-side sub-spans, stage sums ±5% of e2e),
# /debug/slo reports healthy under traffic then flags a bounded
# degradation when a replica is killed mid-traffic, with the envelope
# asserted via envelope_violations (docs/observability.md).
slo-smoke:
	$(CPU_ENV) $(PYTHON) hack/slo_smoke.py

# Incident capture & replay smoke (same invocation as CI's "Replay
# smoke" step): booted service under event + scoring traffic with the
# input flight recorder attached — a forced SLO violation writes one
# incident bundle (capture + traces + profile + timeline + slo +
# config fingerprint, listed at /debug/incidents), replaying the
# bundle's capture through a fresh stack reproduces every recorded
# score bit-identically and the final index state exactly, and a
# deliberately mutated capture reports a first-divergence point
# (docs/observability.md "Incident response runbook").
replay-smoke:
	$(CPU_ENV) $(PYTHON) hack/replay_smoke.py

# What-if engine smoke (same invocation as CI's "What-if smoke"
# step): composes a 4x pod-fanout storm from the pinned reference
# capture, proves the shards=1 vs shards=8 A/B deterministically
# agrees (and a flow-control-starved arm measurably sheds with a
# first SLO-divergence point), exercises GET /debug/whatif,
# GET /debug/incidents/<id> and POST /admin/whatif against a live
# bundle, and holds the live reference A/B to the recorded oracle
# tests/testdata/WHATIF_r01.json exactly (docs/observability.md
# "What-if engine").
whatif-smoke:
	$(CPU_ENV) $(PYTHON) hack/whatif_smoke.py

# Continuous-profiling smoke (same invocation as CI's "Profiling
# smoke" step): booted service under named-thread traffic — collapsed
# stacks attribute >=90% of samples to kvtpu-* roles, a planted
# two-thread lock fight is visible per lock name in
# /debug/profile?kind=locks AND kvtpu_lock_wait_seconds{lock}, the
# timeline shows the traffic ramp, and the PROFILE_HZ=0 /
# LOCK_CONTENTION_SAMPLE=0 off paths are verified zero-cost
# (docs/observability.md).
profile-smoke:
	$(CPU_ENV) $(PYTHON) hack/profile_smoke.py

# Cache-analytics smoke (same invocation as CI's "Cache analytics
# smoke" step): booted service with the hit-attribution ledger + an
# auditor over a controllable inventory — scored traffic lands in
# /debug/cachestats (totals, windows, family drill-down), a planted
# divergence is detected within one audit cycle, /healthz carries the
# analytics block, and the new metric families are on /metrics
# (docs/observability.md).
cachestats-smoke:
	$(CPU_ENV) $(PYTHON) hack/cachestats_smoke.py

# Tiering smoke (same invocation as CI's "Tiering smoke" step):
# booted service with the policy engine — traffic teaches the
# PolicyFeed, a forced demotion lands in /debug/tiering, /metrics AND
# the live score (1.0 -> 0.8/block), and the compute-or-load advice
# flips when the RTT estimator is inflated (docs/tiering.md).
tiering-smoke:
	$(CPU_ENV) $(PYTHON) hack/tiering_smoke.py

# Transfer smoke (same invocation as CI's "Transfer smoke" step):
# booted service with a TransferEngine — planned scoring yields a
# priced pod-to-pod directive, executing it publishes real KVEvents
# (the target's live score rises 0 -> full chain), and a cold pod
# registering for instant-warm gets the hot family pre-placed by the
# warm-up worker, all visible in /debug/transfer, /metrics and
# /healthz (docs/transfer.md).
transfer-smoke:
	$(CPU_ENV) $(PYTHON) hack/transfer_smoke.py

# Host-offload smoke (same invocation as CI's "Host-offload smoke"
# step): the staging engine moves real bytes — store->evict->load
# round trip bit-identical through the per-chip lanes, a demotion
# cycle pages group bytes hbm->host->shared_storage with the index
# tier AND the live score following each rung, and the advisor's
# read/write RTT estimators show measured transfers in /debug/tiering
# (docs/host-offload.md).
offload-smoke:
	$(CPU_ENV) $(PYTHON) hack/offload_smoke.py

# Cluster smoke (same invocation as CI's "Cluster smoke" step): 3
# in-process replicas + a router HTTP service over the RemoteIndex —
# event-plane traffic routed to slice owners, one replica killed
# mid-traffic, scores keep flowing, the journal-fed follower takes the
# slice over WARM (pre-kill scores reproduced exactly), failover
# visible in /debug/cluster and kvtpu_cluster_* (docs/replication.md).
cluster-smoke:
	$(CPU_ENV) $(PYTHON) hack/cluster_smoke.py

# Event-plane smoke (same invocation as CI's "Event-plane smoke"
# step): consolidated poller over ~64 inproc publishers — throughput
# floor, thread ceiling, zero cross-pod sheds under a chatty flood,
# forced gap -> resync, restart classification (docs/event-plane.md).
events-smoke:
	$(CPU_ENV) $(PYTHON) hack/events_smoke.py

# On a TPU host (one process per chip; from the sandbox, through the
# chip tool: `chiprun -- python chip_smoke.py`).  Fails when JAX finds
# no TPU.  Does the pod path start on the chip?  The benchmark is
# `python3 benchmarks/run.py --workload <cell> ...` (benchmarks/README.md).
chip-smoke:
	$(PYTHON) chip_smoke.py

# Render the serving-fleet chart: real helm when installed, the
# subset renderer otherwise (same sources, same output).
chart:
	@if command -v helm >/dev/null 2>&1; then \
		helm template kvtpu deploy/chart; \
	else \
		$(PYTHON) hack/render_chart.py deploy/chart; \
	fi

# Build the native C++ engine in-tree.
native:
	$(PYTHON) -m llm_d_kv_cache_manager_tpu.native.build

# ThreadSanitizer stress of the native engine (race detection the
# reference never wired up; SURVEY.md §5).
native-race:
	$(PYTHON) -m llm_d_kv_cache_manager_tpu.native.build --stress-tsan

# Regenerate protobuf message code (grpc wiring is hand-written,
# api/grpc_services.py).
proto:
	cd llm_d_kv_cache_manager_tpu/api && \
	protoc -I protos --python_out=. protos/indexer.proto protos/tokenizer.proto

# What the driver runs: single-chip compile check + virtual multi-chip
# (the multichip check on 8 virtual CPU devices, via CPU_ENV).
graft-check:
	$(PYTHON) -c "import __graft_entry__ as g; fn, args = g.entry(); import jax; jax.jit(fn)(*args); print('entry ok')"
	$(CPU_ENV) $(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip ok')"

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; true
	rm -rf .pytest_cache llm_d_kv_cache_manager_tpu/native/_build
