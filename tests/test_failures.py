"""Failure-domain chaos layer: compiled blast radius vs scalar oracle.

The contract under test: merging a :class:`FailureSchedule` into the
compiled event stream and resolving EMC blast radius + mitigation
inside the XLA scan (``sweep_core.build_fail_sweep``) is bit-exact
against the scalar oracle ``cluster_sim.replay_with_failures`` — both
mitigation policies, both state dtypes, fixture trace plus seeded
traces.
"""
import dataclasses

import numpy as np
import pytest

from _fractional import shift_half
from repro.core import (cluster_sim, replay_engine, sweep_core, topology,
                        traces)
from repro.runtime.fault import FailureSchedule

CFG = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                gb_per_core=4.0)
HORIZON = 86400
_SERVER = np.array([768.0, 200.0, 96.0])
_POOL = np.array([512.0, 300.0, 64.0])


def _trace(seed):
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(CFG, 0.8, HORIZON)
    vms = pop.sample_vms(n, HORIZON, seed=seed, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    return vms, dec


def _schedule(seed, mtbf_s=4 * 3600.0, cfg=CFG, horizon=HORIZON):
    return FailureSchedule.generate(horizon, cfg.n_groups, mtbf_s,
                                    1800.0, seed=seed)


_FIELDS = ("reject_rate", "affected", "killed", "remigrated",
           "lost_vm_minutes")


def _assert_same(a, b, ctx):
    for f in _FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), (ctx, f)


def _oracle(vms, dec, cfg, sched, server, pool, mitigation):
    """The scalar oracle ``cluster_sim.replay_with_failures``, one call
    per candidate, gathered into an ``AvailabilityResult``."""
    runs = [cluster_sim.replay_with_failures(vms, dec, cfg, float(s),
                                             float(p), sched, mitigation)
            for s, p in zip(server, pool)]
    return replay_engine.AvailabilityResult(
        reject_rate=np.array([r.reject_rate for r in runs]),
        affected=np.array([r.affected for r in runs]),
        killed=np.array([r.killed for r in runs]),
        remigrated=np.array([r.remigrated for r in runs]),
        lost_vm_minutes=np.array([r.lost_vm_minutes for r in runs]),
        n_failures=runs[0].n_failures,
        affected_per_failure=np.stack(
            [np.asarray(r.affected_per_failure) for r in runs], 1),
        mitigation=mitigation)


@pytest.mark.parametrize("mitigation", sweep_core.MITIGATIONS)
def test_fail_sweep_bit_exact_on_fixture(mitigation):
    vms = traces.load_trace_file(traces.fixture_trace_path())
    cfg = cluster_sim.ClusterConfig(n_servers=4, pool_sockets=4,
                                    gb_per_core=4.0)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    horizon = max(vm.departure for vm in vms)
    sched = _schedule(0, mtbf_s=horizon / 6, cfg=cfg, horizon=horizon)
    assert sched.n_failures > 0
    eng = replay_engine.CompiledReplay(vms, dec, cfg,
                                       failure_schedule=sched)
    server = np.array([768.0, 120.0, 30.0])
    pool = np.array([512.0, 64.0, 512.0])
    oracle = _oracle(vms, dec, cfg, sched, server, pool, mitigation)
    for dt in ("int32", "int16"):
        jx = eng.availability(server, pool, mitigation, state_dtype=dt)
        _assert_same(oracle, jx, (mitigation, dt))
        assert np.array_equal(oracle.affected_per_failure,
                              jx.affected_per_failure)


@pytest.mark.parametrize("seed", [3, 4, 5])
@pytest.mark.parametrize("mitigation", sweep_core.MITIGATIONS)
def test_fail_sweep_bit_exact_seeded(seed, mitigation):
    vms, dec = _trace(seed)
    sched = _schedule(seed)
    eng = replay_engine.CompiledReplay(vms, dec, CFG,
                                       failure_schedule=sched)
    oracle = _oracle(vms, dec, CFG, sched, _SERVER, _POOL, mitigation)
    jx = eng.availability(_SERVER, _POOL, mitigation)
    _assert_same(oracle, jx, (seed, mitigation))
    assert np.array_equal(oracle.affected_per_failure,
                          jx.affected_per_failure)
    # the schedule actually bites: failures touch pooled VMs somewhere
    assert int(np.asarray(oracle.affected).sum()) > 0


def test_failures_degrade_availability_not_happy_path():
    """reject_rates (plain sweep) ignores FAIL/RECOVER events;
    availability prices them: down domains grant no pool."""
    vms, dec = _trace(3)
    sched = _schedule(7, mtbf_s=2 * 3600.0)
    eng_f = replay_engine.CompiledReplay(vms, dec, CFG,
                                         failure_schedule=sched)
    eng_0 = replay_engine.CompiledReplay(vms, dec, CFG)
    # merged failure events are no-ops in the plain sweep
    assert eng_f.reject_rates(_SERVER, _POOL).tolist() == \
        eng_0.reject_rates(_SERVER, _POOL).tolist()
    av = eng_f.availability(_SERVER, _POOL, "kill")
    # the failure model changes admission outcomes (down domains grant
    # no pool; kills free capacity) — rates differ from the happy path
    assert np.asarray(av.reject_rate).tolist() != \
        eng_0.reject_rates(_SERVER, _POOL).tolist()
    assert av.n_failures == sched.n_failures
    assert av.affected_per_failure.shape == (sched.n_failures,
                                             len(_SERVER))
    assert (av.affected_per_failure.sum(0)
            == np.asarray(av.affected)).all()


def test_remigrate_beats_kill_on_lost_minutes_with_headroom():
    vms, dec = _trace(4)
    sched = _schedule(1)
    eng = replay_engine.CompiledReplay(vms, dec, CFG,
                                       failure_schedule=sched)
    server = np.array([768.0])      # generous local DRAM: all fits
    pool = np.array([512.0])
    rem = eng.availability(server, pool, "remigrate")
    kil = eng.availability(server, pool, "kill")
    assert int(rem.killed[0]) == 0
    assert int(rem.lost_vm_minutes[0]) == 0
    assert int(kil.killed[0]) == int(kil.affected[0])
    assert rem.remigration_success_rate[0] == 1.0


def test_batch_availability_matches_single_rows():
    engines = []
    for k, seed in enumerate([3, 4]):
        vms, dec = _trace(seed)
        engines.append(replay_engine.CompiledReplay(
            vms, dec, CFG, failure_schedule=_schedule(k)))
    batch = replay_engine.CompiledReplayBatch(engines)
    for mitigation in sweep_core.MITIGATIONS:
        br = batch.availability(_SERVER, _POOL, mitigation)
        for i, e in enumerate(engines):
            r = e.availability(_SERVER, _POOL, mitigation,
                               per_failure=False)
            for f in _FIELDS:
                assert np.array_equal(getattr(br, f)[i],
                                      getattr(r, f)), (mitigation, i, f)
            assert br.n_failures[i] == r.n_failures


@pytest.mark.parametrize("mitigation", sweep_core.MITIGATIONS)
def test_fractional_availability_prices_on_the_oracle(mitigation):
    """Non-integral decisions (0.5 GB shifted to the pool) cannot take
    the int32 failure sweep: the engine loops the scalar oracle, counts
    the fallback, and single and batched rows agree with it."""
    from repro.core import obs
    vms, dec = _trace(3)
    dec = shift_half(dec)
    sched = _schedule(3)
    eng = replay_engine.CompiledReplay(vms, dec, CFG,
                                       failure_schedule=sched)
    assert not eng._exact
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        got = eng.availability(_SERVER, _POOL, mitigation)
        rows = replay_engine.CompiledReplayBatch([eng]).availability(
            _SERVER, _POOL, mitigation)
    assert rec.metrics()["replay.backend_numpy"] == 2
    want = _oracle(vms, dec, CFG, sched, _SERVER, _POOL, mitigation)
    _assert_same(want, got, mitigation)
    assert np.array_equal(want.affected_per_failure,
                          got.affected_per_failure)
    for f in _FIELDS:
        assert np.array_equal(getattr(rows, f)[0], getattr(want, f)), f


def test_availability_requires_schedule():
    vms, dec = _trace(3)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    with pytest.raises(ValueError, match="failure_schedule"):
        eng.availability(_SERVER, _POOL)
    with pytest.raises(ValueError, match="mitigation"):
        sweep_core.build_fail_sweep(mitigation="nope")


# ----------------------------------------------- pool-manager blast radius --
def test_fail_emc_reconciles_pm_stats():
    """Regression: ``fail_emc`` used to wipe grants WITHOUT recording
    releases — ``assigns - releases`` leaked one release per affected
    host per failure and the revoked capacity vanished untracked."""
    from repro.core.pool_manager import PoolManager
    pm = PoolManager(64, num_emcs=2, slice_gb=1.0)
    for host in (0, 1, 2):
        assert pm.add_capacity(host, 8.0)
    assert pm.stats.assigns == 3
    assert pm.stats.outstanding() == 3
    # all three grants landed on EMC 0 (fill order); failing it must
    # count one FORCED release per affected host + tally the GB
    affected = pm.fail_emc(0)
    assert affected == [0, 1, 2]
    assert pm.stats.releases == 3
    assert pm.stats.outstanding() == 0          # ledger balances
    assert pm.stats.revoked_gb == 24.0
    assert pm.assigned_gb() == 0.0
    # the failed EMC's slices are reclaimable; voluntary releases keep
    # the ledger balanced alongside the forced ones
    assert pm.add_capacity(5, 4.0)
    pm.release_capacity(5)
    assert pm.stats.outstanding() == 0
    assert pm.stats.revoked_gb == 24.0          # voluntary != revoked
    # failing an EMC holding no grants affects nobody and moves nothing
    before = dataclasses.replace(pm.stats)
    assert pm.fail_emc(1) == []
    assert pm.stats == before


def test_fleet_pool_manager_pod_failure_is_isolated():
    """Whole-pod failure touches only that pod's members: sibling
    pods keep their grants, stats and free capacity untouched."""
    from repro.core.pool_manager import FleetPoolManager
    t = topology.partitioned(8, 4)              # pods {0..3}, {4..7}
    fpm = FleetPoolManager(t, 64.0)
    assert fpm.add_capacity(0, 8.0) == 0
    assert fpm.add_capacity(1, 4.0) == 0
    assert fpm.add_capacity(4, 8.0) == 1
    assert fpm.assigned_gb() == 20.0
    assert fpm.fail_pod(0) == [0, 1]
    assert fpm.pods[0].assigned_gb() == 0.0
    assert fpm.pods[0].stats.revoked_gb == 12.0
    assert fpm.pods[0].stats.outstanding() == 0
    # the sibling pod never saw the failure
    assert fpm.pods[1].assigned_gb() == 8.0
    assert fpm.pods[1].stats.revoked_gb == 0.0
    assert fpm.pods[1].stats.releases == 0
    assert fpm.host_pool_gb(4) == 8.0
    assert fpm.host_pool_gb(0) == 0.0


def test_fleet_pool_manager_first_reachable_pod_overflow():
    """Grants come from the FIRST reachable pod with room (the fleet
    engines' admission rule); a full first pod overflows to the next,
    and a host reaching no pod gets None (the all-local fallback)."""
    from repro.core.pool_manager import FleetPoolManager
    t = topology.overlapping(8, 4, 2)           # 2 pods, fanout 2
    fpm = FleetPoolManager(t, 16.0)
    assert fpm.add_capacity(0, 16.0) == 0       # fills pod 0
    assert fpm.add_capacity(1, 8.0) == 1        # overflow to pod 1
    assert fpm.add_capacity(2, 16.0) is None    # both pods short
    assert fpm.pod_free_gb().tolist() == [0.0, 8.0]
    fpm.release_capacity(0)
    # releases drain asynchronously (10-100 ms/GB offline path): the
    # capacity is back once the clock passes the drain window
    assert fpm.add_capacity(2, 16.0, now=0.0) is None
    assert fpm.add_capacity(2, 16.0, now=1e9) == 0
    # an orphan host (no reachable pod) can never draw pool
    orphans = topology.Topology("sparse", 4, 1, 1,
                                np.full((4, 1), -1, np.int32))
    fpm0 = FleetPoolManager(orphans, 64.0)
    assert fpm0.add_capacity(0, 1.0) is None
    assert fpm0.assigned_gb() == 0.0


def test_out_of_range_domain_rejected():
    vms, dec = _trace(3)
    bad = FailureSchedule(np.array([10.0]),
                          np.array([CFG.n_groups]),   # one past the end
                          np.array([False]))
    with pytest.raises(ValueError, match="domain"):
        replay_engine.CompiledReplay(vms, dec, CFG, failure_schedule=bad)
