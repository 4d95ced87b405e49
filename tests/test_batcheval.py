"""Tests for the vectorized batch map-space evaluation engine
(core/batcheval.py), the exhaustive search mode, the shared evaluation
caches and the parallel sweep driver."""
import dataclasses
import math
import random
import warnings

import numpy as np
import pytest

from repro.core import batcheval
from repro.core.batcheval import (Topology, co_signature,
                                  enumerate_topologies, evaluate_cached,
                                  evaluate_specs_batch,
                                  evaluate_topology_grid, pareto_merge)
from repro.core.hardware import cloud, edge
from repro.core.ir import MappingSpec, evaluate_mapping
from repro.core.search import (candidate_specs, parallel_map, search,
                               search_many, _sample)
from repro.core.workload import (attention, flash_attention, gemm_layernorm,
                                 gemm_softmax, ssd_chunk)

WORKLOADS = [
    ("gemm_softmax", gemm_softmax(512, 1024, 128)),
    ("gemm_layernorm", gemm_layernorm(512, 4096, 128)),
    ("attention_prefill", attention(1024, 256, 1024, 256)),
    ("attention_decode", attention(1, 128, 1024, 128)),
    ("flash_attention", flash_attention(2048, 256, 2048, 256)),
]
# Prime / non-divisible sizes: spatial fanouts never divide the dims, so
# every edge tile is a ceil-div residual (regression cover for the
# non-divisible fanout accounting fix).
PRIME_WORKLOADS = [
    ("gemm_softmax_prime", gemm_softmax(509, 769, 127)),
    ("attention_decode_prime", attention(1, 64, 769, 128)),
    ("attention_prefill_prime", attention(769, 127, 769, 127)),
]
ARCHS = [edge(), cloud()]


# -------------------------------------------------- vectorized equivalence

@pytest.mark.parametrize("wl_name,co", WORKLOADS,
                         ids=[n for n, _ in WORKLOADS])
@pytest.mark.parametrize("arch", ARCHS, ids=[a.name for a in ARCHS])
def test_batch_matches_tree_path(wl_name, co, arch):
    """Every grid point of every topology matches the per-spec
    build_tree -> validate_tree -> CostModel path to 1e-9 relative
    tolerance (they execute the same formulas, so in practice they are
    bit-identical), including validity."""
    cands = candidate_specs(co, arch)
    rng = random.Random(0)
    for topo in enumerate_topologies(co, cands):
        br = evaluate_topology_grid(co, arch, topo, cands)
        # sample a handful of points per topology to keep runtime down
        idxs = {rng.randrange(br.size) for _ in range(8)} | {0, br.size - 1}
        for i in idxs:
            spec = br.spec_at(i)
            try:
                r = evaluate_mapping(co, arch, spec)
            except (ValueError, KeyError):
                assert not br.valid[i]
                continue
            assert bool(br.valid[i]) == r.valid
            assert br.latency[i] == pytest.approx(r.latency, rel=1e-9)
            assert br.energy_pj[i] == pytest.approx(r.energy_pj, rel=1e-9)
            assert br.headroom[i] == pytest.approx(r.headroom, rel=1e-9)


@pytest.mark.parametrize("wl_name,co", PRIME_WORKLOADS,
                         ids=[n for n, _ in PRIME_WORKLOADS])
@pytest.mark.parametrize("arch", ARCHS, ids=[a.name for a in ARCHS])
def test_batch_matches_tree_path_prime_sizes(wl_name, co, arch):
    """Parity at prime dimension sizes: no spatial fanout divides the
    dims, so every tile is a ceil-div residual (edge) tile — the batched
    path must still match the per-spec tree path everywhere."""
    cands = candidate_specs(co, arch)
    rng = random.Random(1)
    for topo in enumerate_topologies(co, cands):
        br = evaluate_topology_grid(co, arch, topo, cands)
        idxs = {rng.randrange(br.size) for _ in range(8)} | {0, br.size - 1}
        for i in idxs:
            spec = br.spec_at(i)
            try:
                r = evaluate_mapping(co, arch, spec)
            except (ValueError, KeyError):
                assert not br.valid[i]
                continue
            assert bool(br.valid[i]) == r.valid
            assert br.latency[i] == pytest.approx(r.latency, rel=1e-9)
            assert br.energy_pj[i] == pytest.approx(r.energy_pj, rel=1e-9)


def test_spatial_and_schedule_axes_in_grid():
    """The SoA grid enumerates sp_cluster/sp_core and the schedule; the
    topology count no longer doubles on the schedule axis."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    cands = candidate_specs(co, arch)
    topos = enumerate_topologies(co, cands)
    # schedule folded into the grid: topologies = variants only (x gran)
    assert len(topos) == len(cands["variant"])
    assert all(t.schedule == "sequential" for t in topos)
    expect = (len(cands["m_tiles"]) * len(cands["k_tiles"])
              * len(cands["sp_cluster"]) * len(cands["sp_core"])
              * len(cands["schedule"]))
    assert batcheval.grid_size(co, cands) == expect
    br = evaluate_topology_grid(co, arch, topos[0], cands)
    assert br.size == expect
    assert set(np.unique(br.sp_cluster)) == set(cands["sp_cluster"])
    assert set(np.unique(br.sp_core)) == set(cands["sp_core"])
    assert set(np.unique(br.schedule)) == set(cands["schedule"])
    # candidate specs per space grew >= 4x over the m/k-only grid of PR 1
    legacy = len(cands["m_tiles"]) * len(cands["k_tiles"])
    assert expect >= 4 * legacy
    # and the spatial axes actually change results somewhere on the grid
    v = br.valid
    full = br.latency[v & (br.sp_cluster == max(cands["sp_cluster"]))]
    one = br.latency[v & (br.sp_cluster == 1)]
    assert full.size and one.size and not np.isclose(full.min(), one.min())


def test_grid_accepts_pr1_shaped_candidate_dicts():
    """Candidate dicts without the sp_*/schedule axes (the PR 1 API
    shape) pin the missing axes instead of raising KeyError."""
    co = gemm_softmax(256, 1024, 64)
    arch = edge()
    cands = {"m_tiles": [1, 2, 4], "k_tiles": [1, 2], "n_tiles": [1]}
    assert batcheval.grid_size(co, cands) == 6
    topo = Topology(variant="fused_dist")
    br = evaluate_topology_grid(co, arch, topo, cands)
    assert br.size == 6
    assert set(np.unique(br.sp_cluster)) == {0}          # auto fanout
    assert set(np.unique(br.schedule)) == {"sequential"}
    # rejected topology keeps the requested breakdown dicts (zeros)
    bad = evaluate_specs_batch(co, arch, Topology(variant="fa"),
                               [1], [1], [1], track_breakdown=True)
    assert not bad.valid.any()
    assert bad.lat_breakdown is not None
    assert bad.lat_breakdown_at(0)["gemm"] == 0.0
    # unknown schedule names are rejected up front, like the scalar path
    with pytest.raises(ValueError, match="bad schedule"):
        evaluate_specs_batch(co, arch, topo, [1], [1], [1],
                             schedule=["sequentail"])


def test_rejected_topology_arrays_are_independent():
    """Regression (satellite): the rejected-topology path used to alias
    ONE zeros buffer across latency, energy and every breakdown key —
    mutating any of them corrupted all of them."""
    co = gemm_softmax(256, 1024, 64)
    arch = edge()
    bad = evaluate_specs_batch(co, arch, Topology(variant="fa"),
                               [1, 2], [1, 1], [1, 1], track_breakdown=True)
    assert not bad.valid.any()
    bufs = [bad.latency, bad.energy_pj, bad.headroom,
            *bad.lat_breakdown.values(), *bad.energy_breakdown.values()]
    for i, a in enumerate(bufs):
        for b in bufs[i + 1:]:
            assert a is not b and not np.shares_memory(a, b)
    bad.lat_breakdown["gemm"][0] = 123.0
    assert bad.lat_breakdown["simd"][0] == 0.0
    assert bad.latency[0] == 0.0
    assert bad.energy_pj[0] == 0.0


def test_spec_spatial_fanouts_reach_scalar_builder():
    """sp_cluster/sp_core are honoured by the per-spec tree path too."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    base = MappingSpec(variant="fused_dist", m_tiles=8, k_tiles=2)
    narrow = dataclasses.replace(base, sp_cluster=1, sp_core=1)
    r_full = evaluate_mapping(co, arch, base)
    r_one = evaluate_mapping(co, arch, narrow)
    assert r_full.latency != r_one.latency
    # sp 0 (auto) == full arch fanout explicitly requested
    explicit = dataclasses.replace(base, sp_cluster=arch.num_clusters,
                                   sp_core=arch.cores_per_cluster)
    assert evaluate_mapping(co, arch, explicit).latency == r_full.latency


def test_batch_specs_parallel_arrays():
    """evaluate_specs_batch accepts explicit (m, k, n) candidate pairs
    (the autotune use case), not just meshgrids."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    topo = Topology(variant="fused_dist", schedule="sequential")
    m = [1, 2, 8, 64]
    k = [1, 4, 2, 8]
    br = evaluate_specs_batch(co, arch, topo, m, k, [1, 1, 1, 1])
    assert br.size == 4
    for i in range(4):
        r = evaluate_mapping(co, arch, br.spec_at(i))
        assert br.latency[i] == pytest.approx(r.latency, rel=1e-9)


# ------------------------------------------- exhaustive vs randomized

@pytest.mark.parametrize("wl_name,co", WORKLOADS,
                         ids=[n for n, _ in WORKLOADS])
@pytest.mark.parametrize("arch", ARCHS, ids=[a.name for a in ARCHS])
def test_exhaustive_no_worse_than_randomized(wl_name, co, arch):
    ex = search(co, arch, mode="exhaustive")
    assert ex.mode == "exhaustive"
    assert ex.best.valid
    for seed in (0, 1, 7):
        rd = search(co, arch, mode="randomized", budget=500, seed=seed)
        assert ex.latency <= rd.latency * (1 + 1e-12), \
            f"exhaustive worse than randomized seed={seed}"


def test_search_auto_picks_exhaustive_and_is_deterministic():
    co = gemm_softmax(512, 2048, 128)
    arch = cloud()
    r1 = search(co, arch)
    r2 = search(co, arch)
    assert r1.mode == "exhaustive" == r2.mode
    assert r1.latency == r2.latency
    assert r1.evaluated == r2.evaluated
    # full space covered: evaluated == topologies x grid
    cands = candidate_specs(co, arch)
    expect = (len(enumerate_topologies(co, cands))
              * batcheval.grid_size(co, cands))
    assert r1.evaluated == expect


def test_search_objectives():
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    lat = search(co, arch, objective="latency")
    en = search(co, arch, objective="energy")
    edp = search(co, arch, objective="edp")
    assert lat.latency <= en.latency * (1 + 1e-12)
    assert en.energy_pj <= lat.energy_pj * (1 + 1e-12)
    assert (edp.latency * edp.energy_pj
            <= lat.latency * lat.energy_pj * (1 + 1e-12))


def test_pareto_front_matches_bruteforce():
    """Vectorized skyline == O(n^2) dominance check on a real grid."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    cands = candidate_specs(co, arch)
    topo = enumerate_topologies(co, cands)[0]
    br = evaluate_topology_grid(co, arch, topo, cands)
    front = br.pareto_front()
    assert front.size > 0
    lat, en, valid = br.latency, br.energy_pj, br.valid
    fset = set(front.tolist())
    kept = [(lat[i], en[i]) for i in front]
    # ascending latency, strictly descending energy
    assert all(a[0] <= b[0] and a[1] > b[1] for a, b in zip(kept, kept[1:]))
    for i in front:
        assert valid[i]
        dominated = ((lat <= lat[i]) & (en <= en[i]) & valid
                     & ((lat < lat[i]) | (en < en[i])))
        assert not dominated.any(), f"front point {i} is dominated"
    # every non-front valid point is dominated by (or duplicates) the front
    for j in np.flatnonzero(valid):
        if j in fset:
            continue
        dom = ((lat <= lat[j]) & (en <= en[j]) & valid
               & (np.arange(br.size) != j))
        assert dom.any(), f"non-front point {j} is non-dominated"


def test_pareto_merge_skyline():
    pts = [(2.0, 5.0, "a"), (1.0, 9.0, "b"), (3.0, 1.0, "c"),
           (2.5, 5.0, "d"), (1.0, 9.0, "e"), (2.0, 4.0, "f")]
    out = pareto_merge(pts)
    assert [p[2] for p in out] == ["b", "f", "c"]


def test_search_pareto_objective():
    """objective='pareto': front endpoints match the scalar optima and
    SearchResult.best is the front's minimum-latency mapping."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    lat = search(co, arch, objective="latency")
    en = search(co, arch, objective="energy")
    pf = search(co, arch, objective="pareto")
    assert pf.mode == "exhaustive" and pf.front
    assert pf.front[0][0] == pytest.approx(lat.latency, rel=1e-12)
    assert pf.front[-1][1] == pytest.approx(en.energy_pj, rel=1e-12)
    assert pf.latency == pytest.approx(pf.front[0][0], rel=1e-12)
    assert pf.best.valid
    # scalar objectives keep front=None; randomized mode fills it too
    assert lat.front is None
    rd = search(co, arch, mode="randomized", budget=300, seed=0,
                objective="pareto")
    assert rd.front and all(a[0] < b[0] and a[1] > b[1]
                            for a, b in zip(rd.front, rd.front[1:]))


def test_batched_breakdown_matches_scalar_walk():
    """track_breakdown=True carries per-key latency/energy breakdowns
    through the SoA pass, matching the scalar tree walk per grid point."""
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    topo = Topology(variant="fused_dist")
    br = evaluate_specs_batch(
        co, arch, topo, [8, 4, 8], [2, 2, 1], [1, 1, 1],
        sp_cluster=[4, 2, 1], sp_core=[4, 1, 2],
        schedule=["sequential", "pipelined", "pipelined"],
        track_breakdown=True)
    assert br.lat_breakdown is not None
    for i in range(br.size):
        r = evaluate_mapping(co, arch, br.spec_at(i))
        bd = br.lat_breakdown_at(i)
        eb = br.energy_breakdown_at(i)
        for k, v in r.cost.lat_breakdown.items():
            assert bd[k] == pytest.approx(v, rel=1e-9, abs=1e-18)
        for k, v in r.cost.energy_breakdown.items():
            assert eb[k] == pytest.approx(v, rel=1e-9, abs=1e-12)
    # default path stays lean
    lean = evaluate_specs_batch(co, arch, topo, [8], [2], [1])
    assert lean.lat_breakdown is None
    with pytest.raises(ValueError):
        lean.lat_breakdown_at(0)


def test_exhaustive_falls_back_when_space_too_large():
    co = gemm_softmax(512, 1024, 128)
    arch = edge()
    r = search(co, arch, exhaustive_limit=10, budget=200, seed=0)
    assert r.mode == "randomized"


def test_generic_workload_exhaustive():
    co = ssd_chunk(S=2048, H=1, P=64, Dst=128, C=256)
    from repro.core.hardware import tpu_v5e
    arch = tpu_v5e((1, 1))
    r = search(co, arch)
    assert r.mode == "exhaustive"
    assert r.best.valid and r.latency > 0


# ----------------------------------------------------------------- caches

def test_grid_cache_hits():
    batcheval.cache_clear()
    co = gemm_softmax(256, 1024, 64)
    arch = edge()
    cands = candidate_specs(co, arch)
    topo = enumerate_topologies(co, cands)[0]
    br1 = evaluate_topology_grid(co, arch, topo, cands)
    info1 = batcheval.cache_info()["grid"]
    br2 = evaluate_topology_grid(co, arch, topo, cands)
    info2 = batcheval.cache_info()["grid"]
    assert info2["hits"] == info1["hits"] + 1
    assert br2 is br1          # same cached object
    # a different arch is a different cache line
    evaluate_topology_grid(co, cloud(), topo, cands)
    assert batcheval.cache_info()["grid"]["misses"] == info2["misses"] + 1


def test_spec_cache_hits_and_rejections():
    batcheval.cache_clear()
    co = gemm_softmax(256, 1024, 64)
    arch = edge()
    spec = MappingSpec(variant="fused_dist", m_tiles=8, k_tiles=2)
    r1 = evaluate_cached(co, arch, spec)
    h0 = batcheval.cache_info()["spec"]["hits"]
    r2 = evaluate_cached(co, arch, spec)
    assert batcheval.cache_info()["spec"]["hits"] == h0 + 1
    assert r1 == r2
    ref = evaluate_mapping(co, arch, spec)
    assert r1 == (ref.latency, ref.energy_pj, ref.valid, ref.headroom)
    # rejected specs (scalar path raises) cache as None both times
    bad = MappingSpec(variant="fa")    # wrong builder family
    assert evaluate_cached(co, arch, bad) is None
    assert evaluate_cached(co, arch, bad) is None


def test_arch_signature_busts_caches():
    """Regression: two Arch instances sharing a name but differing in a
    parameter (here GB bandwidth) must not reuse each other's cached
    results — keys use Arch.signature(), not arch.name."""
    batcheval.cache_clear()
    co = gemm_softmax(256, 1024, 64)
    a1 = edge()
    a2 = dataclasses.replace(
        a1, gb=dataclasses.replace(a1.gb, bandwidth=a1.gb.bandwidth / 4))
    assert a1.name == a2.name
    assert a1.signature() != a2.signature()

    cands = candidate_specs(co, a1)
    topo = enumerate_topologies(co, cands)[0]
    br1 = evaluate_topology_grid(co, a1, topo, cands)
    g = batcheval.cache_info()["grid"]
    br2 = evaluate_topology_grid(co, a2, topo, cands)
    g2 = batcheval.cache_info()["grid"]
    assert g2["misses"] == g["misses"] + 1   # miss, not a stale hit
    assert br2 is not br1
    assert float(br1.scores().min()) != float(br2.scores().min())

    spec = MappingSpec(variant="fused_dist", m_tiles=8, k_tiles=2)
    r1 = evaluate_cached(co, a1, spec)
    s = batcheval.cache_info()["spec"]
    r2 = evaluate_cached(co, a2, spec)
    s2 = batcheval.cache_info()["spec"]
    assert s2["misses"] == s["misses"] + 1
    assert r1 != r2


def test_arch_signature_memoized():
    """Regression (satellite): Arch.signature() is on the hot cache-key
    path — it must build the field tuple once per instance, and derived
    instances (dataclasses.replace) must not inherit a stale memo."""
    a = edge()
    s1 = a.signature()
    assert a.signature() is s1              # memoized object, not a rebuild
    b = dataclasses.replace(
        a, gb=dataclasses.replace(a.gb, bandwidth=a.gb.bandwidth * 2))
    assert b.signature() != s1              # fresh instance, fresh memo
    assert edge().signature() == s1         # equal params -> equal tuple
    # the memo attribute never leaks into dataclass equality
    assert a == edge()


def test_co_signature_distinguishes_shapes():
    assert co_signature(gemm_softmax(256, 1024, 64)) != \
        co_signature(gemm_softmax(256, 1024, 128))
    assert co_signature(gemm_softmax(256, 1024, 64)) == \
        co_signature(gemm_softmax(256, 1024, 64))


# ----------------------------------------------------------- sweep driver

def test_search_many_matches_serial_order():
    jobs = [(gemm_softmax(256, 1024, 128), edge(), {"variants": [v]})
            for v in ("unfused", "fused_epilogue", "fused_std", "fused_dist")]
    par = search_many(jobs)
    ser = search_many(jobs, executor="serial")
    assert [r.latency for r in par] == [r.latency for r in ser]
    assert [r.best.spec.variant for r in par] == \
        ["unfused", "fused_epilogue", "fused_std", "fused_dist"]


def _square(x):
    return x * x


def _boom(x):
    if x == 2:
        raise ValueError("boom")
    return x


def test_parallel_map_propagates_fn_exceptions():
    """Ordinary exceptions raised by fn are NOT swallowed by the broken-
    pool fallback — they propagate to the caller."""
    with pytest.raises(ValueError, match="boom"):
        parallel_map(_boom, [1, 2, 3], executor="thread")
    with pytest.raises(ValueError, match="boom"):
        parallel_map(_boom, [1, 2, 3], executor="serial")


def test_parallel_map_broken_pool_falls_back_serial(monkeypatch):
    """A pool that breaks mid-sweep (worker killed -> BrokenProcessPool
    out of pool.map) degrades to serial execution of the remaining items
    with a RuntimeWarning, instead of losing the whole sweep."""
    from concurrent.futures.process import BrokenProcessPool

    from repro.core import search as search_mod

    class _BreaksAfterOne:
        def __init__(self, max_workers=None, **kw):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def map(self, fn, items, chunksize=1):
            def gen():
                it = list(items)
                yield fn(it[0])
                raise BrokenProcessPool("worker died")
            return gen()

    monkeypatch.setattr(search_mod, "ProcessPoolExecutor", _BreaksAfterOne)
    with pytest.warns(RuntimeWarning, match="worker pool broke"):
        out = parallel_map(_square, [1, 2, 3, 4], executor="process")
    assert out == [1, 4, 9, 16]


# -------------------------------------------------- autotune integration

def test_autotune_uses_shared_engine():
    """Block selection routes through the shared search engine via the
    PlanCache (no local mini cost models, no per-process lru_cache) and
    still respects the kernel VMEM constraints."""
    import inspect

    from repro.kernels import autotune

    src = inspect.getsource(autotune)
    assert "get_plan_cache" in src             # PlanCache-resolved
    assert "candidate_list" in src             # shared candidates-mode search
    assert "lru_cache" not in src              # result caching = PlanCache
    assert "systolic_gemm_cycles" not in src   # the old mini-model hook
    bq, bk = autotune.attention_blocks(1024, 1024, 64)
    assert bq % 128 == 0 and bk % 128 == 0
    bm, bk2 = autotune.gemm_epilogue_blocks(512, 4096, 128)
    assert (bm * 4096 * 4 + bk2 * 4096 * 2 + bm * bk2 * 2
            + bm * 4096 * 2) * 2 <= autotune.VMEM_BUDGET
