"""Multi-pod fleet differential suite: compiled pod sweep vs the
scalar multi-pool oracle.

The contract under test: every fleet engine's ``reject_rates_fleet``
— one XLA/numpy event scan pricing a whole ``(server_gb, per-pod
capacities, topology)`` grid — is bit-exact (``==``, no tolerance)
against ``cluster_sim.replay_multi_pool`` across seeds, both sweep
paths, state dtypes and topology families, including the MIGRATE quirk
paths and the degenerate layouts (1 pod, zero-member pod, orphan
servers), and the 1-pod / partitioned lanes reproduce the existing
single-pool engines bitwise.

The path is chosen by the input: ``"jax"`` cases replay integral-GB
decisions (the XLA pod sweep), ``"numpy"`` cases the same decisions
with 0.5 GB more in the pool (the float64 numpy sweep).
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from _fractional import shift_half
from repro.core import cluster_sim, replay_engine, topology, traces

CFG = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                gb_per_core=4.75)
HORIZON = 2 * 86400
SEEDS = (3, 4, 5)
PATHS = ("numpy", "jax")


def _topologies():
    """The three ISSUE families plus the orphan degenerate, all at
    CFG.n_servers."""
    return [
        topology.partitioned(8, 4),
        topology.overlapping(8, 4, 2),
        topology.sparse(8, 4, 2, seed=1),
        topology.sparse(8, 3, 2, seed=2, allow_orphans=True),
    ]


def _lanes(topos):
    """A small grid crossing tight/ample DRAM with tight/ample pool
    budgets (every total split integrally per ``split_pool``)."""
    sgb, caps, lane_topos = [], [], []
    for server, total in ((200.0, 150.0), (200.0, 40.0),
                          (140.0, 300.0), (60.0, 6144.0)):
        for t in topos:
            sgb.append(server)
            caps.append(topology.split_pool(total, t.n_pods))
            lane_topos.append(t)
    return np.asarray(sgb), caps, lane_topos


_WORLDS: dict = {}


def _world(seed, migrate=False, path="jax"):
    """A trace and its decisions; ``path="numpy"`` makes them
    non-integral, so the engines take their numpy sweep."""
    key = (seed, migrate, path)
    if key in _WORLDS:
        return _WORLDS[key]
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(CFG, 0.8, HORIZON)
    vms = pop.sample_vms(n, HORIZON, seed=seed, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    if migrate:
        # graft deterministic QoS migrations onto a third of the
        # pooled VMs (mid-lifetime, so MIGRATE lands between ARRIVE
        # and DEPART) — exercises the oracle-quirk paths without a
        # fitted pond policy
        dec = [dataclasses.replace(
                   d, t_migrate=vm.arrival + 0.5 * vm.lifetime)
               if d.pool_gb > 0 and i % 3 == 0 else d
               for i, (vm, d) in enumerate(zip(vms, dec))]
    if path == "numpy":
        dec = shift_half(dec)
    _WORLDS[key] = (vms, dec)
    return vms, dec


_ORACLE_CACHE: dict = {}


def _oracle(seed, migrate, path, vms, dec, sgb, caps, lane_topos):
    key = (seed, migrate, path)
    if key not in _ORACLE_CACHE:
        _ORACLE_CACHE[key] = np.array([
            cluster_sim.replay_multi_pool(vms, dec, CFG, float(sgb[i]),
                                          lane_topos[i], caps[i])
            for i in range(len(sgb))])
    return _ORACLE_CACHE[key]


# ----------------------------------------------------- differential grid --
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("path", ("numpy", "jax"))
@pytest.mark.parametrize("state_dtype", ("int16", "int32"))
def test_fleet_grid_bit_exact(seed, path, state_dtype):
    vms, dec = _world(seed, path=path)
    topos = _topologies()
    sgb, caps, lane_topos = _lanes(topos)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    # integral static decisions take the XLA sweep; the numpy sweep
    # carries float64 state and leaves a forced packing unused
    assert eng._exact == (path == "jax")
    want = _oracle(seed, False, path, vms, dec, sgb, caps, lane_topos)
    got = eng.reject_rates_fleet(sgb, caps, lane_topos,
                                 state_dtype=state_dtype)
    assert (got == want).all(), (seed, path, state_dtype)
    # the grid actually discriminates: some lane rejects, some doesn't
    assert want.max() > 0.0 and want.min() < want.max()


@pytest.mark.parametrize("seed", SEEDS[:2])
@pytest.mark.parametrize("path", PATHS)
def test_fleet_migrate_paths_bit_exact(seed, path):
    """MIGRATE quirk coverage: pool returns to the recorded granting
    pod, fallback-placed VMs pay the server's FIRST listed pod, and
    pod-less (orphan) servers skip the pool update — bit-exact on a
    trace where a third of pooled VMs migrate mid-lifetime."""
    vms, dec = _world(seed, migrate=True, path=path)
    topos = _topologies()
    sgb, caps, lane_topos = _lanes(topos)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    assert eng._has_migrate           # the graft took
    assert eng._exact == (path == "jax")
    want = _oracle(seed, True, path, vms, dec, sgb, caps, lane_topos)
    got = eng.reject_rates_fleet(sgb, caps, lane_topos)
    assert (got == want).all(), (seed, path)
    if path == "jax":              # both packings on the quirk path
        got16 = eng.reject_rates_fleet(sgb, caps, lane_topos,
                                       state_dtype="int16")
        assert (got16 == want).all()


# ------------------------------------------------------ degenerate lanes --
@pytest.mark.parametrize("path", PATHS)
def test_single_pool_lane_matches_single_pool_engine(path):
    """1-pod degenerate: ``single_pool(n)`` must price bitwise like
    the existing engine at equal capacity — which means an n_groups==1
    config (the engine's ``pool_gb`` is PER GROUP)."""
    vms, dec = _world(3, path=path)
    cfg1 = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=16,
                                     gb_per_core=4.75)
    assert cfg1.n_groups == 1
    eng = replay_engine.CompiledReplay(vms, dec, cfg1)
    assert eng._exact == (path == "jax")
    one = topology.single_pool(8)
    for sgb, pgb in ((200.0, 300.0), (140.0, 150.0), (60.0, 6144.0)):
        base = eng.reject_rates(sgb, pgb)
        got = eng.reject_rates_fleet(sgb, float(pgb), one)
        assert (base == got).all(), (path, sgb, pgb)


@pytest.mark.parametrize("path", PATHS)
def test_partitioned_lane_matches_group_engine(path):
    """``partitioned(n, servers_per_group)`` with every pod at the
    per-group budget is exactly the existing multi-group engine."""
    vms, dec = _world(3, path=path)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    assert eng._exact == (path == "jax")
    assert CFG.n_groups == 2 and CFG.servers_per_group == 4
    part = topology.partitioned(8, 4)
    for sgb, pgb in ((200.0, 300.0), (140.0, 150.0), (60.0, 40.0)):
        base = eng.reject_rates(sgb, pgb)
        got = eng.reject_rates_fleet(sgb, np.array([pgb, pgb]), part)
        assert (base == got).all(), (path, sgb, pgb)


@pytest.mark.parametrize("path", PATHS)
def test_zero_member_pod_is_inert(path):
    """A pod no incidence row points at never grants: its capacity is
    dead weight, so rates match the same layout with that pod at 0."""
    vms, dec = _world(4, path=path)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    assert eng._exact == (path == "jax")
    # 2 pods but every server only reaches pod 0
    inc = np.zeros((8, 1), np.int32)
    t = topology.Topology("sparse", 8, 2, 1, inc)
    assert t.members(1) == []
    for dead_cap in (6144.0, 0.0):
        got = eng.reject_rates_fleet(
            200.0, np.array([150.0, dead_cap]), t)
        want = cluster_sim.replay_multi_pool(
            vms, dec, CFG, 200.0, t, np.array([150.0, dead_cap]))
        assert (got == want).all(), (path, dead_cap)
    lean = eng.reject_rates_fleet(200.0, np.array([150.0, 0.0]), t)
    fat = eng.reject_rates_fleet(200.0, np.array([150.0, 6144.0]), t)
    assert (lean == fat).all()


@pytest.mark.parametrize("path", PATHS)
def test_all_orphans_price_like_zero_pool(path):
    """Servers reaching no pod can only take the all-local fallback —
    an all-orphan topology must price bitwise like pool_gb == 0 on
    the single-pool engine."""
    vms, dec = _world(5, path=path)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    assert eng._exact == (path == "jax")
    orphans = topology.Topology("sparse", 8, 1, 1,
                                np.full((8, 1), -1, np.int32))
    for sgb in (200.0, 140.0, 768.0):
        base = eng.reject_rates(sgb, 0.0)
        got = eng.reject_rates_fleet(sgb, 6144.0, orphans)
        assert (base == got).all(), (path, sgb)


# -------------------------------------------------- engine-family parity --
@pytest.mark.parametrize("path", PATHS)
def test_stream_fleet_matches_monolithic(path):
    vms, dec = _world(3, path=path)
    topos = _topologies()
    sgb, caps, lane_topos = _lanes(topos)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=256)
    assert stream.n_shards > 1        # sharding actually engages
    assert eng._exact == stream._exact == (path == "jax")
    ref = eng.reject_rates_fleet(sgb, caps, lane_topos)
    got = stream.reject_rates_fleet(sgb, caps, lane_topos)
    assert (got == ref).all(), path
    # reject_cap is a lower-bound early exit, never an overcount
    capped = stream.reject_rates_fleet(sgb, caps, lane_topos,
                                       reject_cap=0)
    assert (capped <= ref).all()


@pytest.mark.parametrize("path", PATHS)
def test_batch_fleet_matches_engine_rows(path):
    worlds = [_world(s, path=path) for s in SEEDS[:2]]
    topos = _topologies()
    sgb, caps, lane_topos = _lanes(topos)
    engines = [replay_engine.CompiledReplay(v, d, CFG)
               for v, d in worlds]
    streams = [replay_engine.CompiledReplayStream(
                   v, d, CFG, max_events_per_shard=256)
               for v, d in worlds]
    expect = np.stack([e.reject_rates_fleet(sgb, caps, lane_topos)
                       for e in engines])
    batch = replay_engine.CompiledReplayBatch(engines)
    sbatch = replay_engine.CompiledReplayStreamBatch(streams)
    assert batch._exact == sbatch._exact == (path == "jax")
    for fleet in (batch, sbatch):
        got = fleet.reject_rates_fleet(sgb, caps, lane_topos)
        assert got.shape == expect.shape, type(fleet).__name__
        assert (got == expect).all(), (type(fleet).__name__, path)


@pytest.mark.slow
def test_fleet_large_grid_oracle_comparison():
    """CI's long-tail check: the full (quick=False) fig_topology
    topology set on a longer trace, every lane compared against the
    scalar oracle on both sweep paths — the large-grid version of the
    fast differential suite."""
    cfg = cluster_sim.ClusterConfig(n_servers=16, pool_sockets=8,
                                    gb_per_core=4.0)
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(cfg, 0.8, 4 * 86400)
    vms = pop.sample_vms(n, 4 * 86400, seed=11, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    topos = [topology.partitioned(16, 4), topology.partitioned(16, 8),
             topology.single_pool(16), topology.overlapping(16, 4, 2),
             topology.overlapping(16, 4, 3),
             topology.sparse(16, 6, 2, seed=8),
             topology.sparse(16, 4, 3, seed=9, allow_orphans=True)]
    sgb, caps, lane_topos = [], [], []
    for server in (256.0, 180.0, 128.0):
        for total in (100.0, 400.0, 1600.0):
            for t in topos:
                sgb.append(server)
                caps.append(topology.split_pool(total, t.n_pods))
                lane_topos.append(t)
    sgb = np.asarray(sgb)
    for path, d in (("numpy", shift_half(dec)), ("jax", dec)):
        eng = replay_engine.CompiledReplay(vms, d, cfg)
        assert eng._exact == (path == "jax")
        want = np.array([
            cluster_sim.replay_multi_pool(vms, d, cfg, float(sgb[i]),
                                          lane_topos[i], caps[i])
            for i in range(len(sgb))])
        got = eng.reject_rates_fleet(sgb, caps, lane_topos)
        assert (got == want).all(), path


# ------------------------------------------------------------ validation --
def test_fleet_rejects_mismatched_topology():
    vms, dec = _world(3)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    with pytest.raises(ValueError, match="n_servers|servers"):
        eng.reject_rates_fleet(200.0, 64.0, topology.partitioned(16, 4))


def test_fleet_rejects_bad_pod_capacity_shapes():
    vms, dec = _world(3)
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    part = topology.partitioned(8, 4)           # 2 pods
    with pytest.raises(ValueError, match="SHARED"):
        eng.reject_rates_fleet(200.0, np.array([1.0, 2.0, 3.0]), part)
    with pytest.raises(ValueError, match="pod capacities"):
        eng.reject_rates_fleet(
            200.0, [np.array([1.0, 2.0, 3.0])], part)
    with pytest.raises(ValueError, match="broadcast"):
        eng.reject_rates_fleet(np.array([1.0, 2.0, 3.0]), 64.0,
                               [part, part])


def test_oracle_rejects_mismatches():
    vms, dec = _world(3)
    with pytest.raises(ValueError, match="pod capacities"):
        cluster_sim.replay_multi_pool(
            vms, dec, CFG, 200.0, topology.partitioned(8, 4),
            np.array([1.0, 2.0, 3.0]))
    with pytest.raises(ValueError, match="servers"):
        cluster_sim.replay_multi_pool(
            vms, dec, CFG, 200.0, topology.partitioned(16, 4), 64.0)
