"""Hybrid-cloud federation: DevicePool/PoolSet semantics, pool-aware
placement, residency + transfer accounting, batch spill, cache
invalidation on topology changes, cross-pool result parity, and the
checked-in reference calibration roundtrip.

The acceptance story this file pins (ISSUE 8): a query over a snapshot
resident only on pool B is planned onto B when the transfer cost
dominates and onto A when A's compute advantage dominates; batch spill
engages under per-pool capacity pressure; and per-ticket results are
``tobytes()``-identical across pools and to the pre-federation
single-pool path.
"""
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import planner as P
from repro.core import pools as PL
from repro.core import registry as R
from repro.core import runtime as RT
from repro.core.engines import DistributedEngine, LocalEngine
from repro.core.query import GraphPlatform, GraphQuery
from repro.core.service import GraphAnalyticsService
from repro.data import synthetic as S

N = 240


def _bits(v):
    """Recursive byte view of a result value (dict/tuple/array)."""
    if isinstance(v, dict):
        return tuple((k, _bits(v[k])) for k in sorted(v))
    if isinstance(v, (tuple, list)):
        return tuple(_bits(x) for x in v)
    return np.asarray(v).tobytes()


@pytest.fixture(scope="module")
def graph():
    src, dst = S.user_follow_graph(N, 4.0, seed=11)
    return G.build_coo(src, dst, N)


@pytest.fixture(scope="module")
def sym_graph():
    src, dst = S.user_follow_graph(N, 4.0, seed=11)
    keep = src != dst
    return G.build_coo(src[keep], dst[keep], N, symmetrize=True)


def _two_pools(link_bandwidth=PL.DEFAULT_LINK_BANDWIDTH,
               cloud_scale=1.0, **kw):
    return PL.PoolSet([
        PL.DevicePool("onprem", link_bandwidth=link_bandwidth, **kw),
        PL.DevicePool("cloud", link_bandwidth=link_bandwidth,
                      compute_scale=cloud_scale, **kw),
    ])


# ---------------------------------------------------------------------------
# DevicePool / PoolSet semantics
# ---------------------------------------------------------------------------

def test_devicepool_validates_fields():
    with pytest.raises(ValueError):
        PL.DevicePool("")
    with pytest.raises(ValueError):
        PL.DevicePool("p", link_bandwidth=0.0)
    with pytest.raises(ValueError):
        PL.DevicePool("p", compute_scale=0.0)
    with pytest.raises(ValueError):
        PL.DevicePool("p", capacity=-1)
    with pytest.raises(ValueError):
        PL.DevicePool("p", max_inflight=0)


def test_poolset_names_order_and_lookup():
    ps = _two_pools()
    assert ps.names() == ("onprem", "cloud")
    assert "cloud" in ps and "gpu" not in ps
    assert ps.default.name == "onprem"
    with pytest.raises(KeyError):
        ps.get("gpu")
    with pytest.raises(ValueError):
        PL.PoolSet([PL.DevicePool("a"), PL.DevicePool("a")])
    with pytest.raises(ValueError):
        PL.PoolSet([])


def test_poolset_trivial_only_for_one_unit_scale_healthy_pool():
    assert PL.single_pool().trivial
    assert not _two_pools().trivial
    assert not PL.single_pool(compute_scale=0.5).trivial
    ps = PL.single_pool()
    ps.set_health("default", False)
    assert not ps.trivial


def test_poolset_health_generation_bumps_only_on_change():
    ps = _two_pools()
    g0 = ps.generation
    ps.set_health("cloud", True)          # no-op: already healthy
    assert ps.generation == g0
    ps.set_health("cloud", False)
    assert ps.generation == g0 + 1
    assert ps.healthy_pools() == (ps.get("onprem"),)
    ps.set_health("cloud", True)
    assert ps.generation == g0 + 2


def test_default_pools_partitions_devices():
    fake = ("dev0", "dev1", "dev2", "dev3")
    ps = PL.default_pools(devices=fake)
    assert ps.get("onprem").devices == ("dev0", "dev1")
    assert ps.get("cloud").devices == ("dev2", "dev3")
    assert ps.get("onprem").n_chips == 2
    one = PL.default_pools(devices=("solo",))
    assert one.get("onprem").devices == one.get("cloud").devices


# ---------------------------------------------------------------------------
# Runtime primitives
# ---------------------------------------------------------------------------

def test_pool_gate_caps_and_release():
    gate = RT.PoolGate({"a": 1, "b": None})
    assert gate.try_acquire("a")
    assert not gate.try_acquire("a")      # at cap
    assert gate.try_acquire("b") and gate.try_acquire("b")  # unbounded
    assert gate.try_acquire(None)         # legacy plans always pass
    gate.release("a")
    assert gate.inflight("a") == 0
    assert gate.try_acquire("a")
    with pytest.raises(RuntimeError):
        gate.release("unknown")


def test_transfer_ledger_accumulates():
    led = RT.TransferLedger()
    led.record("cloud", 100)
    led.record("cloud", 50)
    assert led.bytes_for("cloud") == 150
    assert led.transfers_for("cloud") == 2
    assert led.snapshot() == {
        "cloud": {"transfer_bytes": 150, "transfers": 2}}


# ---------------------------------------------------------------------------
# Placement: both acceptance directions
# ---------------------------------------------------------------------------

def test_placement_follows_data_when_transfer_dominates(graph):
    """Snapshot resident only on pool B (cloud), slow link: the query
    must be planned onto B even though A is listed first."""
    svc = GraphAnalyticsService(pools=_two_pools(link_bandwidth=1.0))
    svc.add_graph("g", graph, pools=["cloud"])
    plan = svc.context("g").plan(GraphQuery("pagerank"))
    assert plan.pool == "cloud"
    assert plan.transfer_s == 0.0
    assert "resident" in plan.reason


def test_placement_follows_compute_when_transfer_is_cheap(graph):
    """Same residency-on-B setup, but now A (onprem) advertises a large
    compute advantage and the link is fast: the query moves to A and
    the plan carries the (tiny) transfer term."""
    ps = PL.PoolSet([
        PL.DevicePool("onprem", link_bandwidth=1e15, compute_scale=0.01),
        PL.DevicePool("cloud", link_bandwidth=1e15),
    ])
    svc = GraphAnalyticsService(pools=ps)
    svc.add_graph("g", graph, pools=["cloud"])
    plan = svc.context("g").plan(GraphQuery("pagerank"))
    assert plan.pool == "onprem"
    assert plan.transfer_s > 0.0
    assert plan.est_s is not None and np.isfinite(plan.est_s)


def test_trivial_poolset_reproduces_prepool_plans(graph):
    """The default single pool takes the legacy planning path exactly:
    ``pool=None``, same engine/variant/estimates as ``choose_plan``."""
    svc = GraphAnalyticsService()
    svc.add_graph("g", graph)
    q = GraphQuery("pagerank")
    plan = svc.context("g").plan(q)
    stats = svc.context("g").current_stats()
    legacy = P.choose_plan(stats, P.specs_for("pagerank", stats), 1)
    assert plan.pool is None
    assert plan.engine == legacy.engine
    assert plan.variant == legacy.variant
    assert P.plan_cost(plan) == P.plan_cost(legacy)


def test_pool_plans_price_scale_and_transfer(graph):
    """est_s must be compute_scale * engine_estimate + transfer, and
    plan_cost must report it (the admission/tier input)."""
    bw = 1e6
    ps = _two_pools(link_bandwidth=bw, cloud_scale=0.5)
    svc = GraphAnalyticsService(pools=ps)
    svc.add_graph("g", graph, pools=["onprem"])
    plan = svc.context("g").plan(GraphQuery("pagerank"))
    stats = svc.context("g").current_stats()
    spec = P.best_spec_for_engine(
        stats, P.specs_for("pagerank", stats), plan.engine)
    base = (P.estimate_local_cost(stats, spec) if plan.engine == "local"
            else P.estimate_dist_cost(stats, spec, 1))
    scale = 0.5 if plan.pool == "cloud" else 1.0
    transfer = 0.0 if plan.pool == "onprem" else stats.bytes_coo / bw
    assert plan.est_s == pytest.approx(scale * base + transfer)
    assert P.plan_cost(plan) == plan.est_s


# ---------------------------------------------------------------------------
# Residency, transfers, materialization
# ---------------------------------------------------------------------------

def test_execution_materializes_pool_and_charges_ledger(graph):
    ps = PL.PoolSet([
        PL.DevicePool("onprem", link_bandwidth=1e15),
        PL.DevicePool("cloud", link_bandwidth=1e15, compute_scale=0.01),
    ])
    svc = GraphAnalyticsService(pools=ps, cache_size=0)
    svc.add_graph("g", graph, pools=["onprem"])
    ctx = svc.context("g")
    plan = ctx.plan(GraphQuery("pagerank"))
    assert plan.pool == "cloud" and plan.transfer_s > 0
    gen0 = ctx.residency_generation
    svc.call("g", GraphQuery("pagerank"))
    pm = svc.metrics()["pools"]
    assert pm["cloud"]["transfers"] == 1
    assert pm["cloud"]["transfer_bytes"] == ctx.stats.bytes_coo
    assert "cloud" in ctx.residency
    assert ctx.residency_generation == gen0 + 1
    # second execution: the pool is now resident — no new transfer, and
    # the re-costed plan prices it as such
    svc.call("g", GraphQuery("pagerank"))
    assert svc.metrics()["pools"]["cloud"]["transfers"] == 1
    assert ctx.plan(GraphQuery("pagerank")).transfer_s == 0.0


def test_replica_names_merge_residency(graph):
    svc = GraphAnalyticsService(pools=_two_pools())
    c1 = svc.add_graph("a", graph, pools=["onprem"])
    c2 = svc.add_graph("b", graph, pools=["cloud"])
    assert c1 is c2                     # content-digest dedup
    assert c1.residency == frozenset({"onprem", "cloud"})


def test_remove_replica_shrinks_residency_and_invalidates_plans(graph):
    """The ISSUE-8 bugfix: cached plans that referenced a replica's
    pool must not survive ``remove_graph`` of that replica."""
    svc = GraphAnalyticsService(
        pools=_two_pools(link_bandwidth=1.0, cloud_scale=0.5))
    svc.add_graph("a", graph, pools=["onprem"])
    svc.add_graph("b", graph, pools=["cloud"])
    ctx = svc.context("a")
    q = GraphQuery("pagerank")
    plan = ctx.plan(q)
    assert plan.pool == "cloud"         # resident + compute advantage
    assert ctx.plan(q) is plan          # cached
    svc.remove_graph("b")               # the cloud replica goes away
    replan = ctx.plan(q)
    assert replan is not plan
    assert replan.pool == "onprem"      # 1 B/s link: transfer dominates
    assert ctx.residency == frozenset({"onprem"})


def test_pool_health_flip_invalidates_cached_plans(graph):
    svc = GraphAnalyticsService(
        pools=_two_pools(link_bandwidth=1.0, cloud_scale=0.5))
    svc.add_graph("g", graph)           # resident everywhere
    ctx = svc.context("g")
    q = GraphQuery("pagerank")
    assert ctx.plan(q).pool == "cloud"  # compute advantage, no transfer
    svc.set_pool_health("cloud", False)
    assert ctx.plan(q).pool == "onprem"
    svc.set_pool_health("cloud", True)
    assert ctx.plan(q).pool == "cloud"
    svc.set_pool_health("onprem", False)
    svc.set_pool_health("cloud", False)
    with pytest.raises(ValueError):     # nowhere healthy to place
        ctx.plan(GraphQuery("bfs", params={"sources": (0,)}))


def test_topology_change_rekeys_result_cache(graph):
    """A health flip must not replay results admitted under the old
    topology — but the re-executed answer is byte-identical."""
    svc = GraphAnalyticsService(pools=_two_pools())
    svc.add_graph("g", graph)
    q = GraphQuery("pagerank")
    r1 = svc.call("g", q)
    r2 = svc.call("g", q)
    assert r2.meta.get("cache") == "hit"
    svc.set_pool_health("cloud", False)
    r3 = svc.call("g", q)
    assert r3.meta.get("cache") != "hit"
    assert _bits(r1.value) == _bits(r3.value)


# ---------------------------------------------------------------------------
# Spill
# ---------------------------------------------------------------------------

def _batch_two_pool_service(graph, **pool_kw):
    svc = GraphAnalyticsService(
        pools=PL.PoolSet([
            PL.DevicePool("onprem", **pool_kw),
            PL.DevicePool("cloud", capacity=16),
        ]),
        interactive_threshold_s=0.0)    # everything lands in batch
    svc.add_graph("g", graph)
    return svc


def test_batch_spill_engages_under_capacity_pressure(graph):
    svc = _batch_two_pool_service(graph, capacity=1)
    ts = [svc.submit("g", GraphQuery("bfs", params={"sources": (i,)}))
          for i in range(4)]
    assert [t.pool for t in ts] == ["onprem", "cloud", "cloud", "cloud"]
    assert svc.stats["spilled"] == 3
    assert ts[1].tier == "batch"        # spill never changes the tier
    assert "spilled from onprem" in ts[1].plan.reason
    pm = svc.metrics()["pools"]
    assert pm["onprem"]["spilled_away"] == 3
    assert pm["onprem"]["queue_depths"]["local.batch"] == 1
    assert pm["cloud"]["queue_depths"]["local.batch"] == 3
    svc.drain()
    assert all(t.status == "done" for t in ts)
    vals = [_bits(svc.result(t).value) for t in ts]
    solo = GraphAnalyticsService()
    solo.add_graph("g", graph)
    for i, v in enumerate(vals):
        assert v == _bits(
            solo.call("g", GraphQuery("bfs", params={"sources": (i,)}))
            .value)


def test_spill_requires_residency(graph):
    """No resident alternative -> the ticket stays on its pool (spill
    sheds load, it never forces a transfer)."""
    svc = GraphAnalyticsService(
        pools=PL.PoolSet([
            PL.DevicePool("onprem", capacity=1, link_bandwidth=1.0),
            PL.DevicePool("cloud", capacity=16, link_bandwidth=1.0),
        ]),
        interactive_threshold_s=0.0)
    svc.add_graph("g", graph, pools=["onprem"])
    ts = [svc.submit("g", GraphQuery("bfs", params={"sources": (i,)}))
          for i in range(3)]
    assert [t.pool for t in ts] == ["onprem"] * 3
    assert svc.stats["spilled"] == 0


def test_spill_skips_unhealthy_pools(graph):
    svc = _batch_two_pool_service(graph, capacity=1)
    svc.set_pool_health("cloud", False)
    ts = [svc.submit("g", GraphQuery("bfs", params={"sources": (i,)}))
          for i in range(3)]
    assert [t.pool for t in ts] == ["onprem"] * 3
    assert svc.stats["spilled"] == 0


def test_concurrent_drain_matches_serial_with_spill(graph):
    def run(workers):
        svc = _batch_two_pool_service(graph, capacity=1)
        ts = [svc.submit("g", GraphQuery("bfs", params={"sources": (i,)}))
              for i in range(6)]
        svc.drain(workers=workers)
        return [_bits(svc.result(t).value) for t in ts]
    assert run(1) == run(4)


def test_pool_gate_limits_inflight(graph):
    """max_inflight=1 per pool: a 4-worker drain never runs two units
    on one pool at once (asserted via the gate's own accounting —
    release raising on over-release would catch an imbalance)."""
    svc = GraphAnalyticsService(
        pools=PL.PoolSet([
            PL.DevicePool("onprem", max_inflight=1),
            PL.DevicePool("cloud", max_inflight=1, capacity=16),
        ]),
        interactive_threshold_s=0.0, cache_size=0)
    svc.add_graph("g", graph)
    ts = [svc.submit("g", GraphQuery("pagerank",
                                     params={"max_iters": 5 + i}))
          for i in range(5)]
    svc.drain(workers=4)
    assert all(t.status == "done" for t in ts)
    pm = svc.metrics()["pools"]
    assert pm["onprem"]["inflight"] == 0
    assert pm["cloud"]["inflight"] == 0


# ---------------------------------------------------------------------------
# Cross-pool parity: every algorithm x variant
# ---------------------------------------------------------------------------

def _example_suite():
    return [(name, defn) for name, defn in R.items()
            if defn.example_params is not None]


def test_every_algorithm_and_variant_identical_across_pools(graph,
                                                            sym_graph):
    """The federation contract at the engine seam: a pool twin returns
    ``tobytes()``-identical values to the base engine and to the other
    pool's twin, for every registered algorithm and execution variant,
    on both engines."""
    pools = _two_pools().pools()
    engines = {
        False: (LocalEngine(graph), DistributedEngine(graph, n_data=4)),
        True: (LocalEngine(sym_graph),
               DistributedEngine(sym_graph, n_data=4)),
    }
    checked = 0
    for name, defn in _example_suite():
        params = dict(defn.example_params)
        for base in engines[defn.requires_symmetric]:
            if base.name not in defn.engines:
                continue
            variants = (None,) + tuple(sorted(defn.variants or ()))
            for var in variants:
                ref = _bits(base.run(name, params, variant=var).value)
                for pool in pools:
                    twin = base.for_pool(pool)
                    assert twin is not base
                    got = _bits(twin.run(name, params, variant=var).value)
                    assert got == ref, \
                        f"{name}/{var} differs on pool {pool.name}"
                checked += 1
    assert checked >= len(_example_suite())


def test_for_pool_twins_are_cached_and_share_nothing(graph):
    pools = _two_pools()
    eng = LocalEngine(graph)
    a = eng.for_pool(pools.get("onprem"))
    b = eng.for_pool(pools.get("cloud"))
    assert a is eng.for_pool(pools.get("onprem"))   # cached
    assert a is not b and a is not eng
    assert a.pool.name == "onprem" and b.pool.name == "cloud"
    assert set(eng.pool_twins()) == {"onprem", "cloud"}
    # a twin asked for its own pool is itself, not a twin-of-a-twin
    assert a.for_pool(pools.get("onprem")) is a


def test_service_results_identical_to_prepool_platform(graph):
    """End-to-end: the same queries through a two-pool service (each
    residency direction) and through the pre-federation single-pool
    platform return identical bytes."""
    queries = [GraphQuery("pagerank"),
               GraphQuery("bfs", params={"sources": (3,)}),
               GraphQuery("degree_stats")]
    plat = GraphPlatform(graph)
    for home in ("onprem", "cloud"):
        svc = GraphAnalyticsService(
            pools=_two_pools(link_bandwidth=1.0))
        svc.add_graph("g", graph, pools=[home])
        for q in queries:
            assert svc.context("g").plan(q).pool == home
            assert _bits(svc.call("g", q).value) == \
                _bits(plat.query(q).value)


# ---------------------------------------------------------------------------
# Metrics surface
# ---------------------------------------------------------------------------

def test_metrics_pools_section_shape(graph):
    svc = _batch_two_pool_service(graph, capacity=1)
    svc.submit("g", GraphQuery("pagerank"))
    m = svc.metrics()
    assert set(m["pools"]) == {"onprem", "cloud"}
    row = m["pools"]["onprem"]
    assert {"healthy", "capacity", "max_inflight", "inflight",
            "queue_depths", "transfer_bytes", "transfers",
            "spilled_away"} <= set(row)
    assert m["counters"]["spilled"] == 0
    # the aggregate engine.tier view is preserved for pre-pool callers
    assert m["queue_depths"]["local.batch"] == 1
    assert row["queue_depths"]["local.batch"] == 1


def test_trivial_pool_metrics_mirror_aggregate_depths(graph):
    svc = GraphAnalyticsService(interactive_threshold_s=0.0)
    svc.add_graph("g", graph)
    svc.submit("g", GraphQuery("pagerank"))
    m = svc.metrics()
    assert m["queue_depths"]["local.batch"] == 1
    assert m["pools"]["default"]["queue_depths"]["local.batch"] == 1


# ---------------------------------------------------------------------------
# Reference calibration roundtrip (the ROADMAP calibration residue)
# ---------------------------------------------------------------------------

def test_reference_profile_is_checked_in_and_autoloads(monkeypatch):
    import jax
    ref = P.CalibrationProfile.from_json(P.reference_profile_path())
    assert ref.source != "analytic-defaults"
    assert ref.algo_time_scale            # fitted, non-empty
    # the default is the reference only on the platform it was fitted on
    assert ref.platform == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert P.default_calibration() == ref
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    other = P.default_calibration()
    assert other == P.CalibrationProfile()
    assert other.source == "analytic-defaults"


def test_default_profile_resolves_on_first_use_not_at_import():
    import subprocess
    import sys
    code = ("import jax, repro.core.planner as P\n"
            "assert P._ACTIVE_PROFILE is None\n"
            "assert P.active_calibration().platform == "
            "jax.default_backend()\n"
            "print('LAZY_OK')")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert "LAZY_OK" in r.stdout, r.stderr[-2000:]


def test_load_reference_calibration_bumps_generation_and_applies():
    gen0 = P.calibration_generation()
    ref = P.load_reference_calibration()
    assert P.calibration_generation() == gen0 + 1
    assert P.active_calibration() is ref
    # live services follow the active profile's tier thresholds
    svc = GraphAnalyticsService()
    assert svc.interactive_threshold_s == ref.interactive_threshold_s
    P.set_calibration(None)
    assert P.calibration_generation() == gen0 + 2
    assert P.active_calibration().source == "analytic-defaults"


def test_reference_profile_roundtrips_through_json(tmp_path):
    ref = P.CalibrationProfile.from_json(P.reference_profile_path())
    out = tmp_path / "copy.json"
    ref.to_json(out)
    again = P.CalibrationProfile.from_json(out)
    assert again == ref
