"""Compiled policy engine vs the scalar ControlPlane walk: bit-exact
decisions (local/pool/fully/t_migrate), misprediction accounting,
control-plane state mutation, the segment-op history percentiles, the
(tau, pdm, li-threshold) grid axis, and native SoA compilation in the
replay engine."""
import numpy as np
import pytest

from repro.core import cluster_sim, policy_engine, replay_engine, traces
from repro.core.control_plane import ControlPlane, ControlPlaneConfig
from repro.core.pool_manager import PoolManager
from repro.core.predictors.models import (LatencySensitivityModel,
                                          UntouchedMemoryModel)

HORIZON = 5 * 86400


@pytest.fixture(scope="module")
def world():
    pop = traces.Population(seed=0)
    train = pop.sample_vms(600, HORIZON, seed=1)
    li = LatencySensitivityModel(pdm=0.05).fit(
        traces.pmu_matrix(train), traces.slowdowns(train, 182))
    hist = traces.build_history(train)
    meta = traces.metadata_features(train, hist)
    ut = np.array([v.untouched for v in train])
    um = UntouchedMemoryModel(0.05).fit(meta, ut)
    return pop, li, um, hist, meta, ut


def _cp(li, um, hist, th=0.05):
    return ControlPlane(ControlPlaneConfig(li_threshold=th), li, um,
                        PoolManager(pool_gb=4096, buffer_gb=64),
                        history=dict(hist))


def _tuples_scalar(decisions):
    return [(d.local_gb, d.pool_gb, d.fully_pooled, d.t_migrate)
            for d in decisions]


def _tuples_soa(dec: policy_engine.PolicyDecisions):
    return [(float(l), float(p), bool(f),
             None if np.isnan(t) else float(t))
            for l, p, f, t in zip(dec.local_gb, dec.pool_gb,
                                  dec.fully_pooled, dec.t_migrate)]


# ------------------------------------------------- history percentiles ----
def _case_mixed(rng):
    """Today's case: 12 customers, half of them seeded."""
    n = 400
    history = {c: rng.random(rng.integers(0, 7)).tolist()
               for c in range(0, 12, 2)}       # some seeded, some not
    return rng.integers(0, 12, n), rng.random(n), history, n


def _case_ties(rng):
    """Many equal values: untouched rounded to 0.05, seeds too."""
    n = 500
    history = {c: (np.round(rng.random(rng.integers(0, 6)) / 0.05)
                   * 0.05).tolist() for c in range(8)}
    return (rng.integers(0, 8, n),
            np.round(rng.random(n) / 0.05) * 0.05, history, n)


def _case_long_seeds(rng):
    """Seeds longer than each customer's own VMs."""
    n = 60
    history = {c: rng.random(rng.integers(20, 40)).tolist()
               for c in range(10)}
    return rng.integers(0, 10, n), rng.random(n), history, n


def _case_tiny_customers(rng):
    """Customers with one or two VMs only, seeded with 0-2 values."""
    cust = np.repeat(np.arange(40), rng.integers(1, 3, 40))
    rng.shuffle(cust)
    history = {c: rng.random(rng.integers(0, 3)).tolist()
               for c in range(0, 40, 3)}
    return cust, rng.random(len(cust)), history, len(cust)


def _case_empty(rng):
    return (np.zeros(0, np.int64), np.zeros(0), {1: [0.2, 0.4, 0.6]}, 0)


def _case_one_large(rng):
    """One customer holds 3,000 of 3,500 VMs; every row is walked."""
    n = 3500
    cust = np.where(np.arange(n) < 3000, 7, rng.integers(0, 20, n))
    rng.shuffle(cust)
    history = {7: rng.random(5).tolist(), 3: rng.random(2).tolist()}
    return cust, rng.random(n), history, n


@pytest.mark.parametrize("case", [_case_mixed, _case_ties, _case_long_seeds,
                                  _case_tiny_customers, _case_empty,
                                  _case_one_large],
                         ids=lambda f: f.__name__[len("_case_"):])
def test_prefix_percentiles_match_np_percentile(case):
    """The rank-structure prefix percentiles replicate np.percentile
    (numpy's linear lerp incl. the gamma >= 0.5 branch) for every
    prefix of every customer's history, seeds included: the scalar
    walk, row by row."""
    customers, untouched, history, n = case(np.random.default_rng(0))
    n_hist, percs = policy_engine._prefix_percentiles(
        customers, untouched, history)
    assert n_hist.shape == (n,) and percs.shape == (n, 4)
    walk: dict[int, list] = {c: list(v) for c, v in history.items()}
    for i in range(n):
        c = int(customers[i])
        h = walk.setdefault(c, [])
        assert n_hist[i] == len(h)
        if len(h) < 3:
            assert percs[i].tolist() == [0.5] * 4
        else:
            ref = np.percentile(h, [80, 90, 95, 99])
            assert percs[i].tolist() == ref.tolist()
        h.append(float(untouched[i]))


def test_percentile_span_and_rows_counted(world):
    """Under a live recorder the Pond policy times its percentiles in
    ``policy.percentiles`` inside ``policy.decide``, and counts the rows
    answered from order statistics (history of 3 or more)."""
    from repro.core import obs
    pop, li, um, hist, *_ = world
    vms = pop.sample_vms(400, HORIZON, seed=5, start_id=10 ** 6)
    table = traces.vm_table(vms)
    n_hist, _ = policy_engine._prefix_percentiles(
        table.customer, table.untouched, dict(hist))
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        policy_engine.policy_decisions_compiled(
            vms, "pond", control_plane=_cp(li, um, hist), table=table)
    m = rec.metrics()
    assert m["policy.percentile_rows"] == int((n_hist >= 3).sum()) > 0
    assert m["span.policy.percentiles.count"] == 1
    spans = {sp["name"]: sp for sp in rec.spans()}
    inner, outer = spans["policy.percentiles"], spans["policy.decide"]
    assert outer["ts_ns"] <= inner["ts_ns"]
    assert inner["ts_ns"] + inner["dur_ns"] <= \
        outer["ts_ns"] + outer["dur_ns"]


def test_metadata_features_compiled_bitwise(world):
    pop, li, um, hist, *_ = world
    vms = pop.sample_vms(300, HORIZON, seed=4, start_id=10 ** 6)
    table = traces.vm_table(vms)
    # replay the scalar walk's growing history to build the reference
    cp = _cp(li, um, hist)
    rows = []
    for vm in vms:
        rows.append(traces.metadata_features([vm], cp.history)[0])
        cp.record_untouched(vm.customer, vm.untouched)
    _, percs = policy_engine._prefix_percentiles(
        table.customer, table.untouched, dict(hist))
    feat = policy_engine.metadata_features_compiled(table, percs)
    assert feat.dtype == np.float32
    assert np.array_equal(feat, np.stack(rows))


# ---------------------------------------------------- pipeline parity -----
@pytest.mark.parametrize("policy", ["local", "static", "pond"])
def test_compiled_bit_exact_vs_scalar_on_seeds(world, policy):
    """Acceptance: decision-for-decision equality (incl. t_migrate),
    misprediction rate, and identical control-plane end state across
    >=3 synthetic seeds for both replayable policies (+ local)."""
    pop, li, um, hist, *_ = world
    for seed in (2, 7, 11):
        vms = pop.sample_vms(700, HORIZON, seed=seed, start_id=10 ** 6)
        cpa = _cp(li, um, hist) if policy == "pond" else None
        cpb = _cp(li, um, hist) if policy == "pond" else None
        ds, ms = cluster_sim.policy_decisions(vms, policy, cpa,
                                              engine="scalar")
        dc, mc = cluster_sim.policy_decisions(vms, policy, cpb,
                                              as_arrays=True)
        assert _tuples_scalar(ds) == _tuples_soa(dc)
        assert ms == mc == dc.mispredictions
        if policy == "pond":
            assert any(np.isfinite(dc.t_migrate))    # migrations exist
            assert dc.fully_pooled.any()             # LI shortcut fires
            assert set(cpa.history) == set(cpb.history)
            for c in cpa.history:
                assert list(cpa.history[c]) == list(cpb.history[c])
            assert [(m.vm_id, m.at, m.pool_gb) for m in
                    cpa.mitigation.log] == \
                [(m.vm_id, m.at, m.pool_gb) for m in cpb.mitigation.log]
            assert cpa.monitor.checks == cpb.monitor.checks
            assert dc.n_mitigations == len(cpb.mitigation.log)


def test_compiled_bit_exact_on_fixture(world):
    pop, li, um, hist, *_ = world
    vms = traces.load_trace_file(traces.fixture_trace_path())
    for policy in ("static", "pond"):
        cpa = _cp(li, um, hist) if policy == "pond" else None
        cpb = _cp(li, um, hist) if policy == "pond" else None
        ds, ms = cluster_sim.policy_decisions(vms, policy, cpa,
                                              engine="scalar")
        dc, mc = cluster_sim.policy_decisions(vms, policy, cpb,
                                              as_arrays=True)
        assert _tuples_scalar(ds) == _tuples_soa(dc)
        assert ms == mc


def test_compiled_without_models_matches_scalar(world):
    """pond with li/um model gaps (None) keeps the scalar semantics:
    no LI shortcut without a model, all-sensitive monitor, zero pool
    without a UM model."""
    pop, li, um, hist, *_ = world
    vms = pop.sample_vms(200, HORIZON, seed=5, start_id=10 ** 6)
    for li_m, um_m in ((None, um), (li, None), (None, None)):
        cpa = _cp(li_m, um_m, hist)
        cpb = _cp(li_m, um_m, hist)
        ds, ms = cluster_sim.policy_decisions(vms, "pond", cpa,
                                              engine="scalar")
        dc, mc = cluster_sim.policy_decisions(vms, "pond", cpb,
                                              as_arrays=True)
        assert _tuples_scalar(ds) == _tuples_soa(dc)
        assert ms == mc


# ------------------------------------------------------------ grid axis ---
def test_grid_decisions_match_scalar_per_setting(world):
    """Every (tau, pdm, li-threshold) grid row equals a fresh scalar
    ControlPlane configured with that setting (numpy backend)."""
    pop, li, um, hist, meta, ut = world
    vms = pop.sample_vms(400, HORIZON, seed=6, start_id=10 ** 6)
    taus = (0.05, 0.3)
    um_models = policy_engine.fit_um_grid(meta, ut, taus)
    settings = policy_engine.make_grid(
        taus=taus, pdms=(0.02, 0.05), li_thresholds=(0.05,))
    assert len(settings) == 4
    grid = policy_engine.grid_decisions(
        [vms], settings, li, um_models, hist, backend="numpy")
    for s, row in zip(settings, grid):
        cp = _cp(li, um_models[s.tau], hist, th=s.li_threshold)
        ds, ms = cluster_sim.policy_decisions(
            vms, "pond", cp, pdm=s.pdm, engine="scalar")
        assert _tuples_scalar(ds) == _tuples_soa(row[0]), s.label
        assert ms == row[0].mispredictions


def test_grid_jax_backend_matches_numpy(world):
    pytest.importorskip("jax")
    pop, li, um, hist, meta, ut = world
    vms = pop.sample_vms(300, HORIZON, seed=8, start_id=10 ** 6)
    taus = (0.05, 0.2)
    um_models = policy_engine.fit_um_grid(meta, ut, taus)
    settings = policy_engine.make_grid(taus=taus,
                                       li_thresholds=(0.05, 0.5))
    g_np = policy_engine.grid_decisions([vms], settings, li, um_models,
                                        hist, backend="numpy")
    g_jx = policy_engine.grid_decisions([vms], settings, li, um_models,
                                        hist, backend="jax")
    for a, b in zip(g_np, g_jx):
        # GB-floored decisions absorb float32-order differences
        assert a[0].pool_gb.tolist() == b[0].pool_gb.tolist()
        assert a[0].fully_pooled.tolist() == b[0].fully_pooled.tolist()


def test_grid_fp_targets_resolve_thresholds(world):
    pop, li, um, hist, meta, ut = world
    vms = pop.sample_vms(200, HORIZON, seed=9, start_id=10 ** 6)
    pmu = traces.pmu_matrix(vms)
    slows = traces.slowdowns(vms, 182)
    settings = policy_engine.make_grid(
        taus=(0.05,), fp_targets=(0.005, 0.05), li_model=li, pmu=pmu,
        slowdowns=slows)
    assert [s.fp_target for s in settings] == [0.005, 0.05]
    # a looser FP budget admits at least as large a threshold
    assert settings[1].li_threshold >= settings[0].li_threshold
    with pytest.raises(ValueError, match="fp_targets"):
        policy_engine.make_grid(taus=(0.05,), fp_targets=(0.01,))


# -------------------------------------------- SoA -> replay integration ---
def test_soa_decisions_compile_natively(world):
    """PolicyDecisions feeds CompiledReplay/Stream directly and prices
    bit-identically to the materialized VMDecision list."""
    pop, li, um, hist, *_ = world
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                    gb_per_core=4.75)
    vms = pop.sample_vms(600, HORIZON, seed=2, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "pond",
                                          _cp(li, um, hist),
                                          as_arrays=True)
    assert isinstance(dec, policy_engine.PolicyDecisions)
    assert dec.n_migrations > 0
    server = np.array([768.0, 160.0, 60.0])
    pool = np.array([4096.0, 128.0, 0.0])
    r_soa = replay_engine.CompiledReplay(vms, dec, cfg).reject_rates(
        server, pool)
    r_list = replay_engine.CompiledReplay(
        vms, dec.as_vmdecisions(), cfg).reject_rates(server, pool)
    assert r_soa.tolist() == r_list.tolist()
    stream = replay_engine.CompiledReplayStream(
        vms, dec, cfg, max_events_per_shard=256)
    assert stream.n_shards > 1
    assert stream.reject_rates(server, pool).tolist() == r_soa.tolist()


def test_savings_analysis_accepts_precomputed_decisions(world):
    pop, li, um, hist, *_ = world
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                    gb_per_core=4.75)
    vms = pop.sample_vms(500, HORIZON, seed=3, start_id=10 ** 6)
    ref = cluster_sim.savings_analysis(vms, cfg, "static",
                                       static_pool_frac=0.2)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.2,
                                          as_arrays=True)
    inj = cluster_sim.savings_analysis(vms, cfg, "static",
                                       decisions=dec)
    assert (inj.server_gb, inj.pool_group_gb, inj.baseline_server_gb,
            inj.mispredictions) == \
        (ref.server_gb, ref.pool_group_gb, ref.baseline_server_gb,
         ref.mispredictions)
    # batched injection with a repeated trace shares the baseline
    rows = cluster_sim.savings_analysis_batched(
        [vms, vms], cfg, "static", decisions=[dec, dec])
    assert [r.server_gb for r in rows] == [ref.server_gb] * 2
    assert [r.baseline_server_gb for r in rows] == \
        [ref.baseline_server_gb] * 2
