"""Tests for the shared-memory process-pool sweep engine: BatchResult
(de)serialization through ``multiprocessing.shared_memory`` segments,
executor parity (serial/thread/process must be bit-identical), segment
lifecycle (no leaks after clean sweeps or worker crashes) and the warning
fallbacks."""
import os
import pickle
import secrets

import numpy as np
import pytest

from repro.core import batcheval
from repro.core import search as search_mod
from repro.core.batcheval import (Topology, batch_from_shm, batch_to_shm,
                                  enumerate_topologies,
                                  evaluate_specs_batch,
                                  evaluate_topology_grid, shm_unlink)
from repro.core.hardware import cloud, edge
from repro.core.ir import MappingSpec
from repro.core.search import (candidate_specs, cleanup_shm_segments,
                               parallel_map, search_many)
from repro.core.workload import attention, gemm_softmax

shm_required = pytest.mark.skipif(not search_mod._shm_usable(),
                                  reason="no working shared memory")

SHM_DIR = "/dev/shm"


def _segments():
    return set(os.listdir(SHM_DIR)) if os.path.isdir(SHM_DIR) else set()


def _small_jobs():
    """A mixed sweep over small spaces: scalar + front objectives plus a
    forced-randomized job (exercises the pickle wire next to the shm
    wire)."""
    jobs = [(gemm_softmax(256, 1024, 64), edge(), {"variants": [v]})
            for v in ("unfused", "fused_epilogue", "fused_std", "fused_dist")]
    jobs += [
        (gemm_softmax(256, 1024, 64), cloud(), {"objective": "pareto"}),
        (attention(256, 128, 256, 128), edge(), {"objective": "pareto3"}),
        (gemm_softmax(256, 1024, 64), edge(),
         {"mode": "randomized", "budget": 50, "seed": 3}),
        (gemm_softmax(384, 768, 96), edge(), {"divisor_tilings": True}),
    ]
    return jobs


# ------------------------------------------------------ shm serialization

@shm_required
def test_batch_shm_roundtrip_all_fields():
    """Every BatchResult channel — axes, str-dtype schedule, results,
    headroom + per-level headroom, breakdowns — survives the segment
    roundtrip bit-exactly, and unlinking is idempotent."""
    co, arch = gemm_softmax(512, 1024, 128), edge()
    br = evaluate_specs_batch(
        co, arch, Topology(variant="fused_dist"),
        [8, 4, 8], [2, 2, 1], [1, 1, 1],
        sp_cluster=[4, 2, 1], sp_core=[4, 1, 2],
        schedule=["sequential", "pipelined", "pipelined"],
        track_breakdown=True)
    ref = batch_to_shm(br, prefix="comettest")
    assert ref.shm_name.startswith("comettest")
    br2, shm = batch_from_shm(ref)
    try:
        assert br2.topo == br.topo
        for f in ("m_tiles", "k_tiles", "n_tiles", "sp_cluster", "sp_core",
                  "latency", "energy_pj", "valid", "headroom"):
            got, want = getattr(br2, f), getattr(br, f)
            assert got.dtype == want.dtype and np.array_equal(got, want), f
        assert np.array_equal(br2.schedule, br.schedule)
        assert sorted(br2.headroom_levels) == sorted(br.headroom_levels)
        for lvl, a in br.headroom_levels.items():
            assert np.array_equal(br2.headroom_levels[lvl], a)
        for d2, d in ((br2.lat_breakdown, br.lat_breakdown),
                      (br2.energy_breakdown, br.energy_breakdown)):
            assert sorted(d2) == sorted(d)
            for k in d:
                assert np.array_equal(np.asarray(d2[k]), np.asarray(d[k])), k
    finally:
        del br2
        shm.close()
        shm.unlink()
    assert not shm_unlink(ref.shm_name)       # already gone; no raise


@shm_required
def test_shm_ref_is_small_and_picklable():
    """The wire object is the ref, not the arrays: pickling it must cost
    bytes, not megabytes, while the segment holds the actual grid."""
    co, arch = gemm_softmax(512, 1024, 128), edge()
    cands = candidate_specs(co, arch)
    topo = enumerate_topologies(co, cands)[0]
    br = evaluate_topology_grid(co, arch, topo, cands)
    ref = batch_to_shm(br, prefix="comettest")
    try:
        wire = pickle.dumps(ref)
        array_bytes = sum(a.nbytes for a in
                          (br.m_tiles, br.latency, br.energy_pj))
        assert len(wire) < 4096 < array_bytes
        ref2 = pickle.loads(wire)
        br2, shm = batch_from_shm(ref2)
        assert np.array_equal(br2.latency, br.latency)
        del br2
        shm.close()
    finally:
        shm_unlink(ref.shm_name)


@shm_required
def test_shm_names_fit_posix_limits():
    """macOS caps shm names at 31 chars *including* the leading slash
    (PSHMNAMLEN); the default prefix and the sweep-scoped prefix format
    must both stay under it."""
    co, arch = gemm_softmax(256, 1024, 64), edge()
    br = evaluate_specs_batch(co, arch, Topology(variant="fused_dist"),
                              [1], [1], [1])
    ref = batch_to_shm(br)                       # default prefix
    try:
        assert 1 + len(ref.shm_name) <= 31
    finally:
        shm_unlink(ref.shm_name)
    # sweep prefix: "cm" + hex pid + "x" + 4 hex; batch_to_shm appends
    # "_" + 8 hex.  Even at pid_max (2^22) the name fits.
    worst = f"cm{4194304:x}x{'f' * 4}_{'f' * 8}"
    assert 1 + len(worst) <= 31


# ------------------------------------------------------- executor parity

@shm_required
def test_thread_process_serial_bitwise_parity():
    """The tentpole contract: identical jobs produce bit-identical
    results — specs, latency/energy floats, evaluated counts and whole
    Pareto fronts — no matter which executor ran them."""
    jobs = _small_jobs()
    runs = {}
    for ex in ("serial", "thread", "process"):
        batcheval.cache_clear()
        runs[ex] = search_many(jobs, executor=ex)
    for rs, rt, rp in zip(runs["serial"], runs["thread"], runs["process"]):
        assert rs.latency == rt.latency == rp.latency
        assert rs.energy_pj == rt.energy_pj == rp.energy_pj
        assert rs.best.spec == rt.best.spec == rp.best.spec
        assert rs.evaluated == rt.evaluated == rp.evaluated
        assert rs.valid == rt.valid == rp.valid
        assert rs.mode == rt.mode == rp.mode
        assert (rs.front is None) == (rp.front is None)
        if rs.front is not None:
            assert len(rs.front) == len(rp.front)
            for ps, pp in zip(rs.front, rp.front):
                assert ps[:-1] == pp[:-1]          # objective floats
                assert ps[-1] == pp[-1]            # the MappingSpec


@shm_required
def test_process_sweep_leaves_no_segments():
    before = _segments()
    res = search_many(_small_jobs(), executor="process")
    assert len(res) == len(_small_jobs())
    leaked = {n for n in _segments() - before if n.startswith("cm")}
    assert not leaked


# ------------------------------------------------------ segment lifecycle

@shm_required
def test_cleanup_shm_segments_reclaims_prefixed():
    """cleanup_shm_segments unlinks exactly the prefixed segments and
    reports them; foreign segments survive."""
    from multiprocessing import shared_memory

    prefix = f"comettest{secrets.token_hex(4)}"
    mine = [shared_memory.SharedMemory(name=f"{prefix}_{i}", create=True,
                                       size=64) for i in range(3)]
    other = shared_memory.SharedMemory(name=f"other{secrets.token_hex(4)}",
                                       create=True, size=64)
    for s in mine:
        s.close()
    try:
        removed = cleanup_shm_segments(prefix)
        assert sorted(removed) == sorted(f"{prefix}_{i}" for i in range(3))
        assert cleanup_shm_segments(prefix) == []       # idempotent
        assert other.name.lstrip("/") in _segments()
    finally:
        other.close()
        other.unlink()


@shm_required
def test_worker_crash_reclaims_orphans_and_finishes_serially(monkeypatch):
    """A worker that dies after creating a segment but before returning
    its ref must not leak: the sweep warns, finishes the jobs serially,
    and the prefix sweep reclaims the orphan."""
    from concurrent.futures.process import BrokenProcessPool
    from multiprocessing import shared_memory

    monkeypatch.setattr(secrets, "token_hex", lambda n: "fixedtok")
    prefix = f"cm{os.getpid():x}xfixedtok"
    orphan_name = f"{prefix}_orphan"

    class _CrashingPool:
        def __init__(self, max_workers=None, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def submit(self, fn, payload):
            # simulate the worker writing a grid segment, then dying
            # (submit runs once per chunk; the orphan only needs creating
            # once)
            try:
                seg = shared_memory.SharedMemory(name=orphan_name,
                                                 create=True, size=256)
                seg.close()
            except FileExistsError:
                pass

            class _F:
                @staticmethod
                def result():
                    raise BrokenProcessPool("worker died")

                @staticmethod
                def cancel():
                    return True

            return _F()

    monkeypatch.setattr(search_mod, "ProcessPoolExecutor", _CrashingPool)
    jobs = _small_jobs()[:3]
    with pytest.warns(RuntimeWarning, match="worker pool broke"):
        broken = search_many(jobs, executor="process")
    assert orphan_name not in _segments()               # orphan reclaimed
    ref = search_many(jobs, executor="serial")
    assert [r.latency for r in broken] == [r.latency for r in ref]
    assert [r.best.spec for r in broken] == [r.best.spec for r in ref]


@shm_required
def test_worker_killed_mid_shm_write_torn_segment_reclaimed(monkeypatch):
    """A worker SIGKILLed *mid-``batch_to_shm``* leaves a torn segment —
    created and half-filled with garbage, its ref never delivered.  The
    sweep must warn, finish the jobs serially with bit-identical
    results, and the prefix sweep must reclaim the torn segment (its
    contents are never parsed, so torn bytes cannot poison anything)."""
    from concurrent.futures.process import BrokenProcessPool
    from multiprocessing import shared_memory

    monkeypatch.setattr(secrets, "token_hex", lambda n: "tornsg")
    prefix = f"cm{os.getpid():x}xtornsg"
    torn_name = f"{prefix}_torn0001"

    class _KilledMidWritePool:
        def __init__(self, max_workers=None, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def submit(self, fn, payload):
            # the worker got as far as creating the segment and writing
            # part of the grid before the OOM-killer got it
            try:
                seg = shared_memory.SharedMemory(name=torn_name,
                                                 create=True, size=1024)
                seg.buf[:512] = bytes(range(256)) * 2
                seg.close()
            except FileExistsError:
                pass

            class _F:
                @staticmethod
                def result():
                    raise BrokenProcessPool("worker killed mid-write")

                @staticmethod
                def cancel():
                    return True

            return _F()

    monkeypatch.setattr(search_mod, "ProcessPoolExecutor",
                        _KilledMidWritePool)
    jobs = _small_jobs()[:4]
    with pytest.warns(RuntimeWarning, match="worker pool broke"):
        broken = search_many(jobs, executor="process")
    assert torn_name not in _segments()            # torn segment reclaimed
    ref = search_many(jobs, executor="serial")
    assert [r.latency for r in broken] == [r.latency for r in ref]
    assert [r.energy_pj for r in broken] == [r.energy_pj for r in ref]
    assert [r.best.spec for r in broken] == [r.best.spec for r in ref]


@shm_required
def test_cleanup_races_concurrent_healthy_sweep():
    """``cleanup_shm_segments`` for a dead sweep's prefix, looping
    concurrently with a live process sweep under its own prefix: the
    janitor reclaims exactly the stale segments, never touches the live
    sweep's, and the sweep's results stay bit-identical to serial."""
    import threading
    import time
    from multiprocessing import shared_memory

    stale_prefix = f"cmstale{secrets.token_hex(2)}"
    stale = [shared_memory.SharedMemory(name=f"{stale_prefix}_{i}",
                                        create=True, size=64)
             for i in range(4)]
    for s in stale:
        s.close()
    reclaimed, stop = [], threading.Event()

    def janitor():
        while not stop.is_set():
            reclaimed.extend(cleanup_shm_segments(stale_prefix))
            time.sleep(0.002)

    t = threading.Thread(target=janitor)
    t.start()
    jobs = _small_jobs()
    try:
        before = _segments()
        out = search_many(jobs, executor="process")
    finally:
        stop.set()
        t.join()
    assert sorted(reclaimed) == sorted(f"{stale_prefix}_{i}"
                                       for i in range(4))
    assert not [n for n in _segments() if n.startswith(stale_prefix)]
    # the healthy sweep leaked nothing and lost nothing to the janitor
    assert not {n for n in _segments() - before if n.startswith("cm")}
    ref = search_many(jobs, executor="serial")
    assert [r.latency for r in out] == [r.latency for r in ref]
    assert [r.best.spec for r in out] == [r.best.spec for r in ref]


# ---------------------------------------------------- warning fallbacks

def test_pool_unavailable_falls_back_to_threads_with_warning(monkeypatch):
    class _NoPool:
        def __init__(self, max_workers=None, **kw):
            raise OSError("no process pools here")

    monkeypatch.setattr(search_mod, "ProcessPoolExecutor", _NoPool)
    jobs = _small_jobs()[:3]
    with pytest.warns(RuntimeWarning, match="process pool unavailable"):
        out = search_many(jobs, executor="process")
    ref = search_many(jobs, executor="serial")
    assert [r.latency for r in out] == [r.latency for r in ref]


def test_parallel_map_pool_creation_failure_warns_and_runs_serial(monkeypatch):
    class _NoPool:
        def __init__(self, max_workers=None, **kw):
            raise OSError("no threads either")

    monkeypatch.setattr(search_mod, "ThreadPoolExecutor", _NoPool)
    with pytest.warns(RuntimeWarning, match="running serially"):
        out = parallel_map(lambda x: x * x, [1, 2, 3], executor="thread")
    assert out == [1, 4, 9]


def test_auto_executor_thresholds(monkeypatch):
    """'auto' stays on threads below PROCESS_MIN_JOBS and switches to the
    process pool at the threshold (when shared memory works)."""
    calls = []

    def _spy(jobs, *, max_workers, chunksize, chunking="size"):
        calls.append(len(jobs))
        return [search_mod._run_search_job(j) for j in jobs]

    monkeypatch.setattr(search_mod, "_search_many_process", _spy)
    co, arch = gemm_softmax(256, 1024, 64), edge()
    small = [(co, arch, {"variants": ["unfused"]})] * 2
    search_many(small)                       # auto, below threshold
    assert calls == []
    if not search_mod._shm_usable():
        pytest.skip("no working shared memory")
    big = [(co, arch, {"variants": ["unfused"]})] * search_mod.PROCESS_MIN_JOBS
    search_many(big)                         # auto, at threshold
    assert calls == [search_mod.PROCESS_MIN_JOBS]


@shm_required
def test_unknown_kwargs_rejected_identically_across_executors():
    """A typoed search kwarg must raise on the process path exactly as
    it does serially — the shm shortcut may not silently ignore it and
    return wrong-axes optima."""
    co, arch = gemm_softmax(256, 1024, 64), edge()
    jobs = [(co, arch, {"fanout": "pow2"})] * 3      # typo of 'fanouts'
    with pytest.raises(TypeError):
        search_many(jobs, executor="serial")
    with pytest.raises(TypeError):
        search_many(jobs, executor="process")


def test_make_chunks_size_aware_longest_first():
    """Size-aware chunk assignment deals jobs longest-first round-robin:
    the largest job opens the first chunk, every index appears exactly
    once, and 'contiguous' reproduces plain slicing."""
    from repro.core.search import _make_chunks, _norm_job

    arch = edge()
    small = gemm_softmax(256, 1024, 64)
    # candidate_list sizes are the (exact) size estimate, so the ranking
    # is fully deterministic
    def job(n_specs):
        return _norm_job((small, arch, {"candidate_list": [
            MappingSpec(variant="fused_dist", m_tiles=1 + i)
            for i in range(n_specs)]}))

    jobs = [job(2), job(5), job(1), job(9), job(3), job(4), job(7)]
    chunks = _make_chunks(jobs, 2, "size")
    flat = sorted(i for c in chunks for i, _j in c)
    assert flat == list(range(len(jobs)))          # a partition
    assert chunks[0][0][0] == 3                    # 9-spec job leads chunk 0
    # round-robin: second-largest (index 6, 7 specs) opens chunk 1
    assert chunks[1][0][0] == 6
    contig = _make_chunks(jobs, 2, "contiguous")
    assert [[i for i, _j in c] for c in contig] == [[0, 1], [2, 3], [4, 5], [6]]
    with pytest.raises(ValueError, match="chunking"):
        _make_chunks(jobs, 2, "random")


@shm_required
def test_size_aware_chunking_bit_identical_results():
    """chunking='size' must return the same ordered, bit-identical
    results as chunking='contiguous' and the serial path."""
    co, arch = gemm_softmax(256, 1024, 64), edge()
    variants = ["unfused", "fused_epilogue", "fused_std", "fused_dist"] * 2
    jobs = [(co, arch, {"variants": [v]}) for v in variants]
    serial = search_many(jobs, executor="serial")
    for mode in ("size", "contiguous"):
        out = search_many(jobs, executor="process", chunksize=3,
                          chunking=mode)
        assert [r.best.spec.variant for r in out] == variants
        assert all(a.latency == b.latency and a.best.spec == b.best.spec
                   and a.evaluated == b.evaluated
                   for a, b in zip(out, serial))


@shm_required
def test_chunked_scheduling_preserves_order():
    """Chunked job scheduling returns results in job order even when
    chunk sizes do not divide the job count."""
    co, arch = gemm_softmax(256, 1024, 64), edge()
    variants = ["unfused", "fused_epilogue", "fused_std", "fused_dist"] * 3
    jobs = [(co, arch, {"variants": [v]}) for v in variants]
    out = search_many(jobs, executor="process", chunksize=5)
    assert [r.best.spec.variant for r in out] == variants
    # chunksize=1 forces more chunks than the bounded submission window
    # holds, exercising the refill path
    out1 = search_many(jobs, executor="process", chunksize=1, max_workers=2)
    assert [r.best.spec.variant for r in out1] == variants
