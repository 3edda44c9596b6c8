"""Batched replay engine vs the scalar oracle: bit-exact equivalence.

The engine (core/replay_engine.py) must reproduce
``cluster_sim.replay_reject_rate`` EXACTLY — same event order, tie-breaks
and float semantics — on both its sweeps (the XLA int32 sweep on
integral-GB decisions, the float64 numpy sweep on fractional ones),
across trace seeds and policies, including
QoS-migration events and the all-local fallback path (tight pools force
it).  The engine-backed ``savings_analysis`` must agree with the
scalar-oracle search within search tolerance.
"""
import numpy as np
import pytest

from _fractional import shift_half
from repro.core import cluster_sim, replay_engine, traces
from repro.core.control_plane import ControlPlane, ControlPlaneConfig
from repro.core.pool_manager import PoolManager
from repro.core.predictors.models import (LatencySensitivityModel,
                                          UntouchedMemoryModel)

HORIZON = 4 * 86400
CFG = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                gb_per_core=4.75)


@pytest.fixture(scope="module")
def models():
    pop = traces.Population(seed=0)
    train = pop.sample_vms(500, HORIZON, seed=11)
    li = LatencySensitivityModel(pdm=0.05).fit(
        traces.pmu_matrix(train), traces.slowdowns(train, 182))
    hist = traces.build_history(train)
    um = UntouchedMemoryModel(0.05).fit(
        traces.metadata_features(train, hist),
        np.array([v.untouched for v in train]))
    return li, um, hist


def _world(seed: int, policy: str, models):
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(CFG, 0.8, HORIZON)
    vms = pop.sample_vms(n, HORIZON, seed=seed, start_id=10 ** 6)
    if policy == "pond":
        li, um, hist = models
        cp = ControlPlane(
            ControlPlaneConfig(li_threshold=0.05, um_quantile=0.05),
            li, um, PoolManager(pool_gb=4096, buffer_gb=64),
            history=dict(hist))
    else:
        cp = None
    decisions, _ = cluster_sim.policy_decisions(
        vms, policy, cp, static_pool_frac=0.25)
    return vms, decisions


# candidate frontier: hi-capacity, mid, tight-local, zero pool (forces the
# all-local fallback for every pooled VM), tight pool, infeasible
_SERVER = np.array([768.0, 200.0, 140.0, 250.0, 180.0, 60.0, 219.7, 0.0])
_POOL = np.array([6144.0, 300.0, 150.0, 0.0, 40.0, 6144.0, 83.3, 100.0])


@pytest.mark.parametrize("policy", ["static", "pond"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_engine_matches_scalar_oracle_exactly(seed, policy, models):
    vms, decisions = _world(seed, policy, models)
    if policy == "pond":
        # the trace must exercise QoS-migration events
        assert any(d.t_migrate is not None for d in decisions)
    assert any(d.pool_gb > 0 for d in decisions)
    eng = replay_engine.CompiledReplay(vms, decisions, CFG)
    oracle = np.array([
        cluster_sim.replay_reject_rate(vms, decisions, CFG, s, p)
        for s, p in zip(_SERVER, _POOL)])
    got_auto = eng.reject_rates(_SERVER, _POOL)
    assert got_auto.tolist() == oracle.tolist()
    shifted = shift_half(decisions)
    eng_np = replay_engine.CompiledReplay(vms, shifted, CFG)
    assert not eng_np._exact
    oracle_np = [cluster_sim.replay_reject_rate(vms, shifted, CFG, s, p)
                 for s, p in zip(_SERVER, _POOL)]
    got_np = eng_np.reject_rates(_SERVER, _POOL)
    assert got_np.tolist() == oracle_np


def test_reject_cap_preserves_feasibility_classification(models):
    vms, decisions = _world(3, "static", models)
    # fractional input: the numpy sweep, which compacts capped lanes
    eng = replay_engine.CompiledReplay(vms, shift_half(decisions), CFG)
    assert not eng._exact
    oracle = eng.reject_rates(_SERVER, _POOL)
    tol = float(oracle.min()) + 0.005
    cap = int(np.floor(tol * len(vms)))
    capped = eng.reject_rates(_SERVER, _POOL, reject_cap=cap)
    assert ((capped <= tol) == (oracle <= tol)).all()


def test_scalar_broadcast_and_single_candidate(models):
    vms, decisions = _world(4, "static", models)
    eng = replay_engine.CompiledReplay(vms, decisions, CFG)
    one = eng.reject_rates(250.0, 100.0)
    assert one.shape == (1,)
    assert one[0] == cluster_sim.replay_reject_rate(
        vms, decisions, CFG, 250.0, 100.0)


def test_search_min_batched_replicates_scalar_bisection(models):
    vms, decisions = _world(5, "static", models)
    eng = replay_engine.CompiledReplay(vms, decisions, CFG)
    big_pool = 768.0 * CFG.n_servers
    tol = float(eng.reject_rates(768.0, big_pool)[0]) + 0.005
    got = replay_engine.search_min_batched(
        lambda g: eng.reject_rates(g, big_pool) <= tol, 0.0, 768.0)
    want = cluster_sim._search_min(
        lambda g: cluster_sim.replay_reject_rate(
            vms, decisions, CFG, g, big_pool) <= tol, 0.0, 768.0)
    assert got == want          # bitwise: same probes, same outcomes


@pytest.mark.parametrize("policy", ["local", "static"])
def test_savings_analysis_matches_scalar_search(policy, models):
    vms, _ = _world(3, "static", models)
    r_eng = cluster_sim.savings_analysis(vms, CFG, policy,
                                         static_pool_frac=0.25)
    r_sc = cluster_sim.savings_analysis(vms, CFG, policy,
                                        static_pool_frac=0.25,
                                        use_engine=False)
    # server searches replicate the scalar bisection bit-for-bit
    assert r_eng.baseline_server_gb == r_sc.baseline_server_gb
    assert r_eng.server_gb == r_sc.server_gb
    # the pool search uses a different (batched, warm-started) probe
    # sequence, and reject rates are not perfectly monotone near the
    # boundary: both searches land on feasible points whose totals — and
    # hence savings — agree within the search tolerance
    assert abs(r_eng.pool_group_gb - r_sc.pool_group_gb) <= \
        0.15 * max(r_sc.pool_group_gb, 1.0) + 32.0
    assert abs(r_eng.savings - r_sc.savings) <= 0.02
    if policy == "local":
        # reject_rate for 'local' is the cores-bound floor r0
        assert r_eng.reject_rate == r_sc.reject_rate
    else:
        # the reported rate IS the oracle's rate at the solution
        decisions, _ = cluster_sim.policy_decisions(
            vms, policy, static_pool_frac=0.25)
        rr = cluster_sim.replay_reject_rate(
            vms, decisions, CFG, r_eng.server_gb, r_eng.pool_group_gb)
        assert rr == r_eng.reject_rate


def test_compiled_arrive_depart_matches_tuple_sort(models):
    vms, _ = _world(4, "static", models)
    times, kinds, vmidx = replay_engine.compiled_arrive_depart(vms)
    events = []
    for i, vm in enumerate(vms):
        events.append((vm.arrival, 0, i))
        events.append((vm.departure, 1, i))
    events.sort(key=lambda e: (e[0], e[1]))
    assert times.tolist() == [e[0] for e in events]
    assert kinds.tolist() == [e[1] for e in events]
    assert vmidx.tolist() == [e[2] for e in events]


def test_engine_stats_accumulate(models):
    vms, decisions = _world(3, "static", models)
    eng = replay_engine.CompiledReplay(vms, decisions, CFG)
    replay_engine.stats_reset()
    eng.reject_rates(np.array([200.0, 300.0]), np.array([100.0, 200.0]))
    s = replay_engine.stats_snapshot()
    assert s["sweeps"] == 1
    assert s["events"] == eng.n_events
    assert s["candidate_events"] > 0


def test_stale_migrate_after_departure_is_dropped(models):
    """A t_migrate past the VM's departure is a no-op in the scalar
    oracle; the slot-addressed XLA backend must not let it corrupt
    whichever VM reused the slot (regression: short-lived ingested VMs
    under the pond policy)."""
    pop = traces.Population(seed=0)
    base = pop.sample_vms(3, 100.0, seed=1)
    for vm, (arr, life, cores, mem) in zip(
            base, [(0.0, 10.0, 2, 8.0), (20.0, 100.0, 2, 8.0),
                   (35.0, 50.0, 2, 8.0)]):
        vm.arrival, vm.lifetime, vm.cores, vm.mem_gb = \
            arr, life, cores, mem
    decisions = [
        cluster_sim.VMDecision(4.0, 4.0, False, 30.0),   # after departure
        cluster_sim.VMDecision(4.0, 4.0, False, None),
        cluster_sim.VMDecision(4.0, 4.0, False, None)]
    cfg = cluster_sim.ClusterConfig(n_servers=1, pool_sockets=2,
                                    gb_per_core=4.75)
    eng = replay_engine.CompiledReplay(base, decisions, cfg)
    shifted = shift_half(decisions)
    eng_np = replay_engine.CompiledReplay(base, shifted, cfg)
    for s, p in ((16.0, 16.0), (12.0, 4.0), (8.0, 16.0)):
        want = cluster_sim.replay_reject_rate(base, decisions, cfg, s, p)
        want_np = cluster_sim.replay_reject_rate(base, shifted, cfg, s, p)
        got = eng.reject_rates(s, p)
        got_np = eng_np.reject_rates(s, p)
        assert got[0] == want and got_np[0] == want_np, (s, p)


# ------------------------------------------------------- trace batching ---
@pytest.fixture(scope="module")
def seed_batch(models):
    """Three compiled trace seeds (static policy) + their batch."""
    worlds = [_world(seed, "static", models) for seed in (3, 4, 5)]
    engines = [replay_engine.CompiledReplay(v, d, CFG)
               for v, d in worlds]
    return worlds, engines, replay_engine.CompiledReplayBatch(engines)


def test_batched_rows_match_single_trace_sweeps_bitwise(seed_batch):
    worlds, engines, batch = seed_batch
    got = batch.reject_rates(_SERVER, _POOL)
    want = np.stack([e.reject_rates(_SERVER, _POOL) for e in engines])
    assert got.shape == (len(engines), len(_SERVER))
    assert got.tolist() == want.tolist()
    # fractional input, the numpy sweep: same rows, K sweeps instead
    # of one
    frac = [replay_engine.CompiledReplay(v, shift_half(d), CFG)
            for v, d in worlds]
    got_np = replay_engine.CompiledReplayBatch(frac).reject_rates(
        _SERVER[:4], _POOL[:4])
    want_np = np.stack([e.reject_rates(_SERVER[:4], _POOL[:4])
                        for e in frac])
    assert got_np.tolist() == want_np.tolist()


def test_batched_per_trace_candidates_and_narrow_batches(seed_batch):
    _, engines, batch = seed_batch
    # per-trace candidate grids: row k prices its own (server, pool)
    per_s = np.stack([_SERVER + 8.0 * i for i in range(len(engines))])
    got = batch.reject_rates(per_s, _POOL)
    want = np.stack([e.reject_rates(per_s[i], _POOL)
                     for i, e in enumerate(engines)])
    assert got.tolist() == want.tolist()
    # narrow probe batches route through the small candidate buckets
    got1 = batch.reject_rates(250.0, 100.0)
    assert got1.shape == (len(engines), 1)
    for i, e in enumerate(engines):
        assert got1[i, 0] == e.reject_rates(250.0, 100.0)[0]


def test_batch_rejects_mismatched_cluster_shapes(models):
    vms, decisions = _world(3, "static", models)
    eng = replay_engine.CompiledReplay(vms, decisions, CFG)
    other_cfg = cluster_sim.ClusterConfig(n_servers=4, pool_sockets=8,
                                          gb_per_core=4.75)
    other = replay_engine.CompiledReplay(vms, decisions, other_cfg)
    with pytest.raises(ValueError, match="cluster shape"):
        replay_engine.CompiledReplayBatch([eng, other])
    with pytest.raises(ValueError):
        replay_engine.CompiledReplayBatch([])


def test_search_min_multi_replicates_scalar_bisection(seed_batch):
    worlds, engines, batch = seed_batch
    big_pool = 768.0 * CFG.n_servers
    tol = batch.reject_rates(768.0, big_pool)[:, 0] + 0.005
    got = replay_engine.search_min_multi(
        lambda g: batch.reject_rates(g, np.full_like(g, big_pool))
        <= tol[:, None], np.zeros(len(engines)),
        np.full(len(engines), 768.0))
    for i, (vms, decisions) in enumerate(worlds):
        want = cluster_sim._search_min(
            lambda g: cluster_sim.replay_reject_rate(
                vms, decisions, CFG, g, big_pool) <= tol[i], 0.0, 768.0)
        assert got[i] == want       # bitwise: same probes, same outcomes


def test_peak_pool_demand_bounds_required_pool(seed_batch):
    worlds, engines, batch = seed_batch
    for (vms, decisions), eng in zip(worlds, engines):
        peak = eng.peak_pool_demand()
        assert peak > 0.0
        # at pool >= peak the pool never binds: same rates as "infinite"
        big = 768.0 * CFG.n_servers
        assert eng.reject_rates(np.array([200.0]),
                                np.array([peak]))[0] == \
            eng.reject_rates(np.array([200.0]), np.array([big]))[0]


def test_savings_analysis_batched_matches_per_seed(models):
    vms_a, _ = _world(3, "static", models)
    vms_b, _ = _world(4, "static", models)
    batched = cluster_sim.savings_analysis_batched(
        [vms_a, vms_b], CFG, "static", static_pool_frac=0.25)
    singles = [cluster_sim.savings_analysis(v, CFG, "static",
                                            static_pool_frac=0.25)
               for v in (vms_a, vms_b)]
    for got, want in zip(batched, singles):
        # baseline server search replicates the scalar bisection
        assert got.baseline_server_gb == want.baseline_server_gb
        # pool probes differ (trajectory-free brackets) and reject rates
        # are not perfectly monotone near the boundary: totals — hence
        # savings — agree within search tolerance
        assert abs(got.savings - want.savings) <= 0.04
        assert got.reject_rate <= want.reject_rate + 0.006
    s = cluster_sim.summarize_savings(batched)
    assert s["n_seeds"] == 2
    assert s["savings_min"] <= s["savings_mean"] <= s["savings_max"]


def test_savings_analysis_batched_local_policy(models):
    vms_a, _ = _world(5, "static", models)
    cache: dict = {}
    res = cluster_sim.savings_analysis_batched(
        [vms_a], CFG, "local", cache=cache)
    single = cluster_sim.savings_analysis(vms_a, CFG, "local")
    assert res[0].server_gb == single.server_gb
    assert res[0].savings == 0.0
    assert "local_batch" in cache
