"""Staging-engine tests: parity vs the one-shot oracle, pinned vs plain slots,
lane backpressure (watchdog-armed), write-side RTT stamping, atomic
file layout, and the staged demotion target's real byte moves."""

import os
import threading
import time

import numpy as np
import pytest

from llm_d_kv_cache_manager_tpu.models.kv_cache_pool import (
    KVCachePool,
    KVCachePoolConfig,
)
from llm_d_kv_cache_manager_tpu.native.engine import JobStatus, _PythonEngine
from llm_d_kv_cache_manager_tpu.offload.host_tier import HostTierCache
from llm_d_kv_cache_manager_tpu.offload.spec import (
    TPUOffloadConnector,
    TPUOffloadSpec,
)
from llm_d_kv_cache_manager_tpu.offload.staging import StagingBudget
from llm_d_kv_cache_manager_tpu.offload.staging_engine import (
    StagingConfig,
    StagingEngine,
    StagingSaturated,
)
from llm_d_kv_cache_manager_tpu.offload.worker import (
    DeviceToStorageHandler,
    group_blocks_per_file,
    host_dtype,
)
from llm_d_kv_cache_manager_tpu.tiering.staged_target import (
    StagedDemotionTarget,
)

POOL_CONFIG = KVCachePoolConfig(
    num_layers=3,
    num_blocks=32,
    block_size=8,
    num_kv_heads=2,
    head_dim=16,
    dtype="bfloat16",
)


def make_connector(tmp_path, staging_lanes=0, pool=None, event_sink=None,
                   subdir="kv"):
    spec = TPUOffloadSpec(
        shared_storage_path=str(tmp_path / subdir),
        model_name="llama-3-8b",
        device_block_size=8,
        offloaded_block_size=16,  # 2 device blocks per file
        threads_per_chip=2,
        staging_lanes=staging_lanes,
    )
    pool = pool or KVCachePool(POOL_CONFIG)
    return TPUOffloadConnector(spec, pool, event_sink=event_sink), pool


def fill_pool_blocks(pool, block_ids, seed=0):
    rng = np.random.default_rng(seed)
    c = pool.config
    written = {}
    for block_id in block_ids:
        data = rng.standard_normal(
            (c.num_layers, 2, c.block_size, c.num_kv_heads, c.head_dim)
        ).astype(host_dtype(c.dtype))
        pool.write_block(block_id, data)
        written[block_id] = data
    return written


def read_tree(root):
    """{relative path: bytes} of every file under root."""
    out = {}
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as handle:
                out[os.path.relpath(path, root)] = handle.read()
    return out


class TestStagedParity:
    """Staged path ≡ one-shot path: same bytes on disk, same pool."""

    def test_disk_bytes_bit_identical(self, tmp_path):
        block_ids = [3, 4, 7, 9, 11]  # partial tail group included
        hashes = [0xA, 0xB, 0xC]
        pool = KVCachePool(POOL_CONFIG)
        fill_pool_blocks(pool, block_ids)

        oneshot, _ = make_connector(tmp_path, 0, pool=pool, subdir="one")
        staged, _ = make_connector(tmp_path, 2, pool=pool, subdir="two")
        assert staged.staging is not None
        groups = group_blocks_per_file(hashes, block_ids, 2)
        oneshot.store_handler.transfer_async(1, groups)
        staged.store_handler.transfer_async(1, groups)
        assert oneshot.store_handler.wait(1) == JobStatus.SUCCEEDED
        assert staged.store_handler.wait(1) == JobStatus.SUCCEEDED

        one = read_tree(str(tmp_path / "one"))
        two = read_tree(str(tmp_path / "two"))
        assert one.keys() == two.keys() and len(one) == 3
        for rel in one:
            assert one[rel] == two[rel], f"byte drift in {rel}"
        oneshot.close()
        staged.close()

    def test_scatter_bit_identical(self, tmp_path):
        block_ids = [1, 2, 5, 6, 8]
        hashes = [0x1, 0x2, 0x3]
        source = KVCachePool(POOL_CONFIG)
        fill_pool_blocks(source, block_ids)
        writer, _ = make_connector(tmp_path, 0, pool=source)
        writer.store_handler.transfer_async(
            1, group_blocks_per_file(hashes, block_ids, 2)
        )
        assert writer.store_handler.wait(1) == JobStatus.SUCCEEDED

        target_ids = [20, 21, 22, 23, 24]
        load_groups = group_blocks_per_file(hashes, target_ids, 2)
        pools = {}
        for lanes in (0, 2):
            pool = KVCachePool(POOL_CONFIG)
            reader, _ = make_connector(tmp_path, lanes, pool=pool)
            reader.load_handler.transfer_async(2, load_groups)
            assert reader.load_handler.wait(2) == JobStatus.SUCCEEDED
            pools[lanes] = pool.gather_to_host(target_ids)
            reader.close()
        np.testing.assert_array_equal(pools[0], pools[2])
        np.testing.assert_array_equal(
            pools[2], source.gather_to_host(block_ids)
        )
        writer.close()

    def test_polling_path_routes_staged_parent(self, tmp_path):
        events = []
        connector, pool = make_connector(
            tmp_path,
            2,
            event_sink=lambda hashes, medium: events.append(
                (tuple(hashes), medium)
            ),
        )
        fill_pool_blocks(pool, [0, 1])
        connector.store_handler.transfer_async(
            10, group_blocks_per_file([0xC], [0, 1], 2)
        )
        deadline = time.monotonic() + 10
        finished = []
        while time.monotonic() < deadline and not finished:
            finished = connector.get_finished()
            time.sleep(0.01)
        # The raw engine sub-job id must never surface — only the
        # parent the caller submitted.
        assert finished == [(10, JobStatus.SUCCEEDED)]
        assert events == [((0xC,), "shared_storage")]

        connector.load_handler.transfer_async(
            11, group_blocks_per_file([0xC], [5, 6], 2)
        )
        deadline = time.monotonic() + 10
        finished = []
        while time.monotonic() < deadline and not finished:
            finished = connector.get_finished()
            time.sleep(0.01)
        assert finished == [(11, JobStatus.SUCCEEDED)]
        np.testing.assert_array_equal(
            pool.gather_to_host([5, 6]), pool.gather_to_host([0, 1])
        )
        connector.close()

    def test_staged_load_missing_file_fails(self, tmp_path):
        connector, _ = make_connector(tmp_path, 2)
        connector.load_handler.transfer_async(
            20, group_blocks_per_file([0xDEAD], [1, 2], 2)
        )
        assert connector.load_handler.wait(20) == JobStatus.FAILED
        connector.close()

    def test_zero_group_staged_load_completes(self, tmp_path):
        connector, _ = make_connector(tmp_path, 1)
        connector.load_handler.transfer_async(30, [])
        assert connector.load_handler.wait(30) == JobStatus.SUCCEEDED
        connector.close()

    def test_staged_host_tier_hit_skips_file(self, tmp_path):
        """A host-cached group scatters immediately; only misses read
        files, and the RTT observer sees only the file bytes."""
        connector, pool = make_connector(tmp_path, 0)
        block_ids = [1, 2, 3, 4]
        fill_pool_blocks(pool, block_ids)
        connector.store_handler.transfer_async(
            1, group_blocks_per_file([0xA, 0xB], block_ids, 2)
        )
        assert connector.store_handler.wait(1) == JobStatus.SUCCEEDED

        from llm_d_kv_cache_manager_tpu.offload.worker import (
            StorageToDeviceHandler,
        )

        cache = HostTierCache(1 << 20)
        assert cache.put(0xA, pool.gather_block_major([1, 2]))
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(lanes_per_chip=1),
        )
        observed = []
        loader = StorageToDeviceHandler(
            pool,
            connector.engine,
            connector.file_mapper,
            host_cache=cache,
            rtt_observer=lambda nbytes, s: observed.append((nbytes, s)),
            staging=staging,
        )
        loader.transfer_async(
            5, group_blocks_per_file([0xA, 0xB], [20, 21, 22, 23], 2)
        )
        assert loader.wait(5) == JobStatus.SUCCEEDED
        np.testing.assert_array_equal(
            pool.gather_to_host([20, 21, 22, 23]),
            pool.gather_to_host(block_ids),
        )
        assert len(observed) == 1
        nbytes, seconds = observed[0]
        assert nbytes == 2 * pool.block_nbytes  # group 0xB only
        assert seconds > 0
        connector.close()


class TestPinnedAndPlainPaths:
    def test_plain_slots_when_pinned_forced_off(self, tmp_path):
        """use_pinned=None probes the pool; forcing False must keep
        the pipeline byte-correct through plain reusable slots."""
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(lanes_per_chip=1, use_pinned=False),
        )
        assert not staging.uses_pinned
        fill_pool_blocks(pool, [0, 1, 2])
        staging.store(
            1, group_blocks_per_file([0xA, 0xB], [0, 1, 2], 2)
        )
        assert staging.wait(1) == JobStatus.SUCCEEDED
        staging.job_stats(1)
        # Slot reuse across two groups must not corrupt the first
        # file (written before the slot was reused).
        path = connector.file_mapper.get_file_name(0xA)
        expected = pool.gather_block_major([0, 1])
        with open(path, "rb") as handle:
            on_disk = np.frombuffer(
                handle.read(), dtype=expected.dtype
            ).reshape(expected.shape)
        np.testing.assert_array_equal(on_disk, expected)
        connector.close()

    def test_pinned_path_survives_a_staged_store(self, tmp_path):
        """The CPU backend lists a pinned_host memory, so the auto
        probe takes the pinned path — and it must still be on after a
        store has actually used it (a transfer call the installed JAX
        lacks once flipped both flags off on the first gather, silently,
        with every test passing on the plain path)."""
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 1, pool=pool)
        assert pool.pinned_host and connector.staging.uses_pinned
        fill_pool_blocks(pool, [0, 1])
        connector.store_handler.transfer_async(
            1, group_blocks_per_file([0xA], [0, 1], 2)
        )
        assert connector.store_handler.wait(1) == JobStatus.SUCCEEDED
        assert pool.pinned_host and connector.staging.uses_pinned
        staged = pool.stage_gather_pinned([0, 1])
        assert staged.sharding.memory_kind == "pinned_host"
        np.testing.assert_array_equal(
            np.asarray(staged), pool.gather_block_major([0, 1])
        )
        connector.close()


class TestBackpressure:
    def test_lane_saturation_raises_not_deadlocks(self, tmp_path):
        connector, pool = make_connector(tmp_path, 0)
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(lanes_per_chip=1, lane_wait_s=0.2),
        )
        lane = staging._acquire_lane()
        t0 = time.monotonic()
        with pytest.raises(StagingSaturated):
            staging._acquire_lane()
        assert time.monotonic() - t0 < 5
        staging._release_lane(lane)
        # After release the lane is acquirable again.
        staging._release_lane(staging._acquire_lane())
        connector.close()

    def test_saturation_raise_completes_job_as_failed(self, tmp_path):
        """A StagingSaturated raise must not strand the job: it still
        completes (FAILED) so the handler's harvest releases budget
        and pending state, and the id becomes reusable."""
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(lanes_per_chip=1, lane_wait_s=0.2),
        )
        budget = StagingBudget(1 << 30)
        handler = DeviceToStorageHandler(
            pool,
            connector.engine,
            connector.file_mapper,
            staging_budget=budget,
            staging=staging,
        )
        fill_pool_blocks(pool, [0, 1])
        lane = staging._acquire_lane()  # wedge the only lane
        with pytest.raises(StagingSaturated):
            handler.transfer_async(
                7, group_blocks_per_file([0xE], [0, 1], 2)
            )
        # The job surfaced as FAILED and the harvest releases budget.
        assert handler.wait(7) == JobStatus.FAILED
        assert budget.in_flight_bytes == 0
        staging._release_lane(lane)
        # The id is reusable and the path is healthy again.
        handler.transfer_async(
            7, group_blocks_per_file([0xE], [0, 1], 2)
        )
        assert handler.wait(7) == JobStatus.SUCCEEDED
        connector.close()

    def test_concurrent_jobs_with_budget_no_deadlock(self, tmp_path):
        """Lane saturation + a tight StagingBudget together: every
        submitter completes (watchdog: the test fails by timeout
        assertion, not by hanging)."""
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(
                lanes_per_chip=1, slots_per_lane=1, lane_wait_s=30.0
            ),
        )
        # Budget fits ~2 concurrent jobs of 2 blocks each.
        budget = StagingBudget(4 * pool.block_nbytes)
        handler = DeviceToStorageHandler(
            pool,
            connector.engine,
            connector.file_mapper,
            staging_budget=budget,
            staging=staging,
        )
        fill_pool_blocks(pool, list(range(8)))
        errors = []
        done = []

        def submit(worker_idx):
            try:
                for j in range(3):
                    job_id = worker_idx * 100 + j
                    ids = [(worker_idx * 3 + j) * 2 % 8,
                           ((worker_idx * 3 + j) * 2 + 1) % 8]
                    handler.transfer_async(
                        job_id,
                        group_blocks_per_file([0x500 + job_id], ids, 2),
                    )
                    assert handler.wait(job_id) == JobStatus.SUCCEEDED
                done.append(worker_idx)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [
            threading.Thread(target=submit, args=(i,)) for i in range(3)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
        assert not errors, errors
        assert sorted(done) == [0, 1, 2]
        assert not any(t.is_alive() for t in threads), "deadlocked"
        assert budget.in_flight_bytes == 0
        connector.close()


class TestStoreRtt:
    def test_one_shot_store_stamps_observer(self, tmp_path):
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        observed = []
        handler = DeviceToStorageHandler(
            pool,
            connector.engine,
            connector.file_mapper,
            rtt_observer=lambda n, io_s, dev_s: observed.append(
                (n, io_s, dev_s)
            ),
        )
        fill_pool_blocks(pool, [0, 1])
        handler.transfer_async(
            1, group_blocks_per_file([0xE], [0, 1], 2)
        )
        assert handler.wait(1) == JobStatus.SUCCEEDED
        assert len(observed) == 1
        nbytes, io_s, dev_s = observed[0]
        assert nbytes == 2 * pool.block_nbytes
        assert io_s > 0
        assert dev_s is not None and dev_s > 0
        connector.close()

    def test_staged_store_stamps_observer(self, tmp_path):
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        staging = StagingEngine(
            pool, connector.engine, connector.file_mapper, 2,
            StagingConfig(lanes_per_chip=1),
        )
        observed = []
        handler = DeviceToStorageHandler(
            pool,
            connector.engine,
            connector.file_mapper,
            staging=staging,
            rtt_observer=lambda n, io_s, dev_s: observed.append(
                (n, io_s, dev_s)
            ),
        )
        fill_pool_blocks(pool, [0, 1, 2, 3])
        handler.transfer_async(
            1, group_blocks_per_file([0xA, 0xB], [0, 1, 2, 3], 2)
        )
        assert handler.wait(1) == JobStatus.SUCCEEDED
        assert len(observed) == 1
        nbytes, io_s, dev_s = observed[0]
        assert nbytes == 4 * pool.block_nbytes
        assert io_s > 0
        assert dev_s is not None and dev_s > 0
        connector.close()

    def test_advisor_store_estimator_fed(self):
        from llm_d_kv_cache_manager_tpu.tiering.advisor import (
            AdvisorConfig,
            ComputeOrLoadAdvisor,
        )

        advisor = ComputeOrLoadAdvisor(AdvisorConfig())
        assert advisor.estimate_store_s(1 << 20) is None
        advisor.observe_store(1 << 20, 0.1, 0.02)
        stats = advisor.stats()
        assert stats["rtt_store"]["observations"] == 1
        assert stats["store_device_observations"] == 1
        estimate = advisor.estimate_store_s(1 << 20)
        assert estimate is not None and estimate > 0.1


class TestAtomicity:
    """Satellite: a store killed between tmp-write and rename leaves
    no visible file, and lookup never trusts .tmp leftovers."""

    def test_kill_between_tmp_and_rename_leaves_no_visible_file(
        self, tmp_path, monkeypatch
    ):
        engine = _PythonEngine(n_threads=1)

        def dying_replace(src, dst):
            raise OSError("simulated kill between tmp write and rename")

        monkeypatch.setattr(os, "replace", dying_replace)
        path = str(tmp_path / "aa" / "bb" / "deadbeef.bin")
        buffer = np.arange(64, dtype=np.uint8)
        engine.store(1, [path], [buffer], skip_existing=True)
        assert engine.wait(1) == JobStatus.FAILED
        assert not os.path.exists(path), "torn store became visible"
        # The orphan tmp is allowed to exist (a killed process cannot
        # clean up) — but it must never match the block's real name.
        leftovers = [
            name
            for name in os.listdir(tmp_path / "aa" / "bb")
            if ".tmp." in name
        ]
        assert leftovers, "expected an orphan tmp artifact"
        engine.close()

    def test_lookup_rejects_tmp_leftovers(self, tmp_path):
        connector, pool = make_connector(tmp_path, 0)
        manager = connector.get_manager()
        fill_pool_blocks(pool, [0, 1])
        connector.store_handler.transfer_async(
            1, group_blocks_per_file([0x9], [0, 1], 2)
        )
        assert connector.store_handler.wait(1) == JobStatus.SUCCEEDED
        assert manager.lookup([0x9]) == 1

        # Plant an orphan tmp for a DIFFERENT hash, full-sized: the
        # scheduler must not count it (the real path does not exist).
        real = connector.file_mapper.get_file_name(0x9)
        orphan_dir = os.path.dirname(
            connector.file_mapper.get_file_name(0xBEEF)
        )
        os.makedirs(orphan_dir, exist_ok=True)
        with open(real, "rb") as handle:
            payload = handle.read()
        orphan = os.path.join(
            orphan_dir,
            os.path.basename(
                connector.file_mapper.get_file_name(0xBEEF)
            )
            + ".tmp.12345.67890",
        )
        with open(orphan, "wb") as handle:
            handle.write(payload)
        assert manager.lookup([0xBEEF]) == 0
        assert manager.lookup([0x9, 0xBEEF]) == 1

        # A truncated (torn) file at the REAL path is also rejected by
        # the full-file-size gate.
        torn = connector.file_mapper.get_file_name(0x77)
        os.makedirs(os.path.dirname(torn), exist_ok=True)
        with open(torn, "wb") as handle:
            handle.write(payload[: len(payload) // 2])
        assert manager.lookup([0x77]) == 0
        connector.close()


class TestStagedDemotionTarget:
    def _target(self, tmp_path, events=None):
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 2, pool=pool)
        cache = HostTierCache(1 << 22)
        observed = []
        target = StagedDemotionTarget(
            capacity_bytes=64 * pool.block_nbytes,
            pool=pool,
            file_mapper=connector.file_mapper,
            host_cache=cache,
            event_sink=(
                (lambda evts: events.extend(evts))
                if events is not None
                else None
            ),
            store_rtt_observer=lambda n, io_s, dev_s: observed.append(
                (n, io_s)
            ),
        )
        return target, pool, connector, cache, observed

    def test_demotions_move_real_bytes(self, tmp_path):
        events = []
        target, pool, connector, cache, observed = self._target(
            tmp_path, events
        )
        block_ids = [4, 5]
        fill_pool_blocks(pool, block_ids)
        expected = pool.gather_block_major(block_ids)
        target.register_pool_group(
            0xFACE,
            block_ids=block_ids,
            engine_hashes=[0x300, 0x301],
            token_ids=list(range(16)),
            block_size=8,
            now=time.monotonic() - 600,
        )

        # hbm -> host: the bytes must be readable from the host tier.
        assert target.demote(0xFACE, "host")
        cached = cache.get(0xFACE)
        assert cached is not None
        np.testing.assert_array_equal(cached, expected)
        assert [type(e).__name__ for e in events[:2]] == [
            "BlockStored",
            "BlockRemoved",
        ]
        assert events[0].medium == "host"

        # host -> shared_storage: the file must hold the bytes, the
        # host entry retires, the write cost is observed.
        events.clear()
        assert target.demote(0xFACE, "shared_storage")
        path = connector.file_mapper.get_file_name(0xFACE)
        with open(path, "rb") as handle:
            on_disk = np.frombuffer(
                handle.read(), dtype=expected.dtype
            ).reshape(expected.shape)
        np.testing.assert_array_equal(on_disk, expected)
        assert cache.get(0xFACE) is None
        assert events[0].medium == "shared_storage"
        assert observed and observed[0][0] == expected.nbytes
        assert target.tiers() == {"shared_storage": 1}

        # The demoted file round-trips through the load handler (the
        # destination-tier readback assertion).
        connector.load_handler.transfer_async(
            1, [(0xFACE, [20, 21])]
        )
        assert connector.load_handler.wait(1) == JobStatus.SUCCEEDED
        np.testing.assert_array_equal(
            pool.gather_to_host([20, 21]),
            pool.gather_to_host(block_ids),
        )
        connector.close()

    def test_storage_write_failure_keeps_tier(self, tmp_path, monkeypatch):
        target, pool, connector, cache, _ = self._target(tmp_path)
        block_ids = [1, 2]
        fill_pool_blocks(pool, block_ids)
        target.register_pool_group(
            0xB0B,
            block_ids=block_ids,
            engine_hashes=[0x1],
            token_ids=list(range(16)),
            now=time.monotonic() - 600,
        )
        assert target.demote(0xB0B, "host")

        from llm_d_kv_cache_manager_tpu.tiering import staged_target

        monkeypatch.setattr(
            staged_target, "store_file", lambda *a, **kw: False
        )
        assert not target.demote(0xB0B, "shared_storage")
        # Tier unchanged, bytes still host-resident.
        assert target.tiers() == {"host": 1}
        assert cache.get(0xB0B) is not None
        connector.close()

    def test_demotion_survives_concurrent_connector_polling(
        self, tmp_path
    ):
        """The serving loop polls connector.get_finished while the
        demotion thread moves a group down both rungs — the demotion
        must neither hang nor spuriously fail (harvest-race
        regression: the storage write is harvest-free by design)."""
        target, pool, connector, cache, _ = self._target(tmp_path)
        block_ids = [4, 5]
        fill_pool_blocks(pool, block_ids)
        target.register_pool_group(
            0xCAFE,
            block_ids=block_ids,
            engine_hashes=[0x2],
            token_ids=list(range(16)),
            now=time.monotonic() - 600,
        )
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                connector.get_finished()
                time.sleep(0.001)

        poller = threading.Thread(target=poll)
        poller.start()
        try:
            assert target.demote(0xCAFE, "host")
            assert target.demote(0xCAFE, "shared_storage")
        finally:
            stop.set()
            poller.join(timeout=10)
        assert not poller.is_alive()
        assert os.path.exists(
            connector.file_mapper.get_file_name(0xCAFE)
        )
        connector.close()

    def test_requires_host_cache(self, tmp_path):
        pool = KVCachePool(POOL_CONFIG)
        connector, _ = make_connector(tmp_path, 0, pool=pool)
        with pytest.raises(ValueError):
            StagedDemotionTarget(
                capacity_bytes=1024,
                pool=pool,
                file_mapper=connector.file_mapper,
                host_cache=None,
            )
        connector.close()
