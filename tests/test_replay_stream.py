"""Streaming replay (CompiledReplayStream) vs the monolithic engine:
bit-exact reject rates with peak event-tensor memory bounded by
``max_events_per_shard``, on the bundled fixture and on a >=100k-VM
synthetic trace — plus the int16 state-packing equivalence rules."""
import numpy as np
import pytest

from _fractional import shift_half
from repro.core import cluster_sim, replay_engine, traces

CFG = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                gb_per_core=4.75)


def _trace(seed=3, horizon=3 * 86400, policy="static", frac=0.25):
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(CFG, 0.8, horizon)
    vms = pop.sample_vms(n, horizon, seed=seed, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, policy,
                                          static_pool_frac=frac)
    return vms, dec


_SERVER = np.array([768.0, 200.0, 140.0, 60.0, 219.7, 0.0])
_POOL = np.array([6144.0, 300.0, 0.0, 6144.0, 83.3, 100.0])


def test_stream_bit_exact_on_fixture_all_backends():
    vms = traces.load_trace_file(traces.fixture_trace_path())
    cfg = cluster_sim.ClusterConfig(n_servers=4, pool_sockets=4,
                                    gb_per_core=4.0)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    eng = replay_engine.CompiledReplay(vms, dec, cfg)
    server = np.array([768.0, 120.0, 60.0, 30.0])
    pool = np.array([512.0, 64.0, 0.0, 512.0])
    mono = eng.reject_rates(server, pool)
    stream = replay_engine.CompiledReplayStream(
        vms, dec, cfg, max_events_per_shard=256)
    assert stream.reject_rates(server, pool).tolist() == mono.tolist()
    # the numpy sweeps, on fractional input, against the scalar oracle
    shifted = shift_half(dec)
    want = [cluster_sim.replay_reject_rate(vms, shifted, cfg, s, p)
            for s, p in zip(server, pool)]
    mono_np = replay_engine.CompiledReplay(vms, shifted, cfg)
    stream_np = replay_engine.CompiledReplayStream(
        vms, shifted, cfg, max_events_per_shard=256)
    assert not (mono_np._exact or stream_np._exact)
    assert mono_np.reject_rates(server, pool).tolist() == want
    assert stream_np.reject_rates(server, pool).tolist() == want


def test_stream_multi_shard_carry_matches_monolithic():
    vms, dec = _trace()
    mono = replay_engine.CompiledReplay(vms, dec, CFG).reject_rates(
        _SERVER, _POOL)
    shifted = shift_half(dec)
    mono_np = replay_engine.CompiledReplay(vms, shifted, CFG).reject_rates(
        _SERVER, _POOL)
    for budget in (256, 320):           # aligned and ragged shard splits
        stream = replay_engine.CompiledReplayStream(
            vms, dec, CFG, max_events_per_shard=budget)
        assert stream.n_shards > 1      # the carry actually threads
        assert stream.reject_rates(_SERVER,
                                   _POOL).tolist() == mono.tolist()
        stream_np = replay_engine.CompiledReplayStream(
            vms, shifted, CFG, max_events_per_shard=budget)
        assert stream_np.reject_rates(_SERVER, _POOL).tolist() \
            == mono_np.tolist()


def test_stream_chunked_construction_matches_monolithic():
    vms, dec = _trace()
    order = sorted(range(len(vms)), key=lambda i: vms[i].arrival)
    svms = [vms[i] for i in order]
    sdec = [dec[i] for i in order]
    mono = replay_engine.CompiledReplay(svms, sdec, CFG).reject_rates(
        _SERVER, _POOL)
    dmap = {id(v): d for v, d in zip(svms, sdec)}
    stream = replay_engine.CompiledReplayStream(
        iter([svms[i:i + 97] for i in range(0, len(svms), 97)]),
        None, CFG, max_events_per_shard=256,
        decide=lambda ch: [dmap[id(v)] for v in ch])
    assert stream.n_shards > 1
    assert stream.reject_rates(_SERVER, _POOL).tolist() == mono.tolist()
    # out-of-order chunks are rejected, not silently mis-replayed
    with pytest.raises(ValueError, match="non-decreasing"):
        replay_engine.CompiledReplayStream(
            iter([svms[100:], svms[:100]]), None, CFG,
            max_events_per_shard=256,
            decide=lambda ch: [dmap[id(v)] for v in ch])


def test_stream_chunked_soa_slice_decisions_match_monolithic():
    """Chunked ingestion fed by PolicyDecisions.slice (the compiled
    policy SoA, sliced at the running row offset — no VMDecision
    objects) replays bit-exactly like the monolithic engine."""
    vms, _ = _trace()
    order = sorted(range(len(vms)), key=lambda i: vms[i].arrival)
    svms = [vms[i] for i in order]
    dec, _ = cluster_sim.policy_decisions(svms, "static",
                                          static_pool_frac=0.25,
                                          as_arrays=True)
    mono = replay_engine.CompiledReplay(svms, dec, CFG).reject_rates(
        _SERVER, _POOL)
    off = [0]

    def decide(chunk):
        lo = off[0]
        off[0] += len(chunk)
        return dec.slice(lo, off[0])

    stream = replay_engine.CompiledReplayStream(
        iter([svms[i:i + 97] for i in range(0, len(svms), 97)]),
        None, CFG, max_events_per_shard=256, decide=decide)
    assert stream.n_shards > 1
    assert stream.reject_rates(_SERVER, _POOL).tolist() == mono.tolist()


def test_stream_100k_vm_trace_bit_exact_and_memory_bounded():
    """Acceptance: >=100k VMs, bit-exact vs monolithic, peak event
    tensor bounded by max_events_per_shard."""
    n = 100_000
    rng = np.random.default_rng(11)
    arrival = np.sort(rng.uniform(0, 30 * 86400, n)).round(3)
    life = rng.integers(1800, 86400, n).astype(float)
    cores = rng.choice([2, 4, 8], n, p=[.5, .3, .2])
    mem = cores * rng.choice([2, 4], n)
    pmu = np.zeros(traces.N_PMU_FEATURES, np.float32)
    vms = [traces.VM(i, 0, 0, 0, 0, int(cores[i]), float(mem[i]),
                     float(arrival[i]), float(life[i]), 0.5, 0.0, 0.0,
                     pmu)
           for i in range(n)]
    dec = [cluster_sim.VMDecision(
        v.mem_gb - float(np.floor(v.mem_gb * 0.25)),
        float(np.floor(v.mem_gb * 0.25)), False, None) for v in vms]
    cfg = cluster_sim.ClusterConfig(n_servers=112, pool_sockets=16,
                                    gb_per_core=4.75)
    server = np.array([768.0, 44.0, 30.0, 36.0])
    pool = np.array([6144.0, 512.0, 6144.0, 0.0])
    mono = replay_engine.CompiledReplay(vms, dec, cfg).reject_rates(
        server, pool)
    assert len(set(mono.tolist())) > 1     # memory actually binds
    budget = 32_768
    stream = replay_engine.CompiledReplayStream(
        vms, dec, cfg, max_events_per_shard=budget)
    assert stream.n_vms == n and stream.n_shards >= 6
    # THE memory bound: every per-sweep event tensor is one shard,
    # and non-256-multiple budgets floor rather than round past it
    assert stream.shard_pad_events <= budget
    small = replay_engine.CompiledReplayStream(
        vms[:500], dec[:500], cfg, max_events_per_shard=300)
    assert small.max_events_per_shard == 256
    assert small.shard_pad_events <= 300
    assert all(len(s["kind"]) == stream.shard_pad_events
               for s in stream._shards)
    assert stream.peak_shard_bytes == 6 * 4 * stream.shard_pad_events
    assert stream.reject_rates(server, pool).tolist() == mono.tolist()


def test_stream_reject_cap_preserves_feasibility():
    vms, dec = _trace()
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=256)
    tol = 0.02
    cap = int(tol * len(vms))
    full = stream.reject_rates(_SERVER, _POOL)
    capped = stream.reject_rates(_SERVER, _POOL, reject_cap=cap)
    assert ((full <= tol) == (capped <= tol)).all()
    # early-exited candidates report at or above the lower bound
    assert (capped[capped > tol] * len(vms) >= cap + 1).all()


def test_stream_fractional_decisions_match_oracle():
    vms, _ = _trace()
    dec = [cluster_sim.VMDecision(vm.mem_gb - 0.5, 0.5, False, None)
           for vm in vms]
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=256)
    assert not stream._exact               # auto-routes to numpy/float64
    got = stream.reject_rates(_SERVER[:3], _POOL[:3])
    want = [cluster_sim.replay_reject_rate(vms, dec, CFG, s, p)
            for s, p in zip(_SERVER[:3], _POOL[:3])]
    assert got.tolist() == want


def test_stream_peak_pool_demand_matches_monolithic():
    vms, dec = _trace()
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=256)
    assert stream.peak_pool_demand() == eng.peak_pool_demand()


# ------------------------------------------------------ int16 packing -----
def test_int16_matches_int32_near_boundary():
    """int16 state packing is bit-equivalent to int32 right up to the
    overflow-safety boundary, and the automatic pick flips to int32
    beyond it."""
    vms, dec = _trace()
    eng = replay_engine.CompiledReplay(vms, dec, CFG)
    safe = replay_engine._I16_SAFE
    pay_m, pay_p = eng._pay_mem_max, eng._pay_pool_max
    # capacities pinned AT the boundary (largest int16-eligible values)
    server = np.array([safe - pay_m, 200.0, 140.0, 60.0])
    pool = np.array([safe - pay_p, 300.0, 0.0, safe - pay_p])
    assert eng._pick_state_dtype(np.floor(server),
                                 np.floor(pool)) == "int16"
    i16 = eng.reject_rates(server, pool, state_dtype="int16")
    i32 = eng.reject_rates(server, pool, state_dtype="int32")
    oracle = [cluster_sim.replay_reject_rate(vms, dec, CFG, s, p)
              for s, p in zip(server, pool)]
    assert i16.tolist() == i32.tolist() == oracle
    # one GB past the boundary -> automatic int32 fallback
    assert eng._pick_state_dtype(
        np.floor(server + 1.0), np.floor(pool)) == "int32"
    assert eng._pick_state_dtype(
        np.floor(server), np.floor(pool + 1.0)) == "int32"
    assert eng._pick_state_dtype(
        np.array([-1.0]), np.array([0.0])) == "int32"
    # MIGRATE-bearing traces pack to int16 too: the oracle's
    # fallback-migrate quirk can only drive the used-pool carry
    # negative by the compiled migrate-event pool total, so bounding
    # that sum (plus payload headroom) within the int16 safety margin
    # keeps the packing bit-equivalent
    mig_dec = [cluster_sim.VMDecision(d.local_gb, d.pool_gb,
                                      d.fully_pooled, vms[i].arrival + 1.)
               for i, d in enumerate(dec)]
    eng_mig = replay_engine.CompiledReplay(vms, mig_dec, CFG)
    assert eng_mig._has_migrate
    assert eng_mig._mig_pool_sum + eng_mig._pay_pool_max <= safe
    assert eng_mig._pick_state_dtype(np.floor(server),
                                     np.floor(pool)) == "int16"
    # pool=0 lane: every placement falls back all-local, then every
    # migrate returns un-consumed pool — the deficit path int16 must
    # survive (carry goes negative by up to _mig_pool_sum)
    m16 = eng_mig.reject_rates(server, pool,
                               state_dtype="int16")
    m32 = eng_mig.reject_rates(server, pool,
                               state_dtype="int32")
    mig_oracle = [cluster_sim.replay_reject_rate(vms, mig_dec, CFG, s, p)
                  for s, p in zip(server, pool)]
    assert m16.tolist() == m32.tolist() == mig_oracle
    st_mig = replay_engine.CompiledReplayStream(
        vms, mig_dec, CFG, max_events_per_shard=512)
    assert st_mig._has_migrate
    assert st_mig._mig_pool_sum == eng_mig._mig_pool_sum
    assert st_mig._pick_state_dtype(np.floor(server),
                                    np.floor(pool)) == "int16"
    assert st_mig.reject_rates(server, pool,
                               state_dtype="int16").tolist() == mig_oracle
    # the stream shares the same rules
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=256)
    assert stream._pick_state_dtype(np.floor(server),
                                    np.floor(pool)) == "int16"
    s16 = stream.reject_rates(server, pool,
                              state_dtype="int16")
    s32 = stream.reject_rates(server, pool,
                              state_dtype="int32")
    assert s16.tolist() == s32.tolist() == oracle


def test_int16_migrate_pool_deficit_boundary():
    """The migrate-event pool total is the exact int16 gate: one VM
    past the deficit bound flips the automatic pick back to int32, and
    AT the bound the int16 replay (negative used-pool carry included)
    stays bit-equivalent to int32 and the scalar oracle."""
    safe = replay_engine._I16_SAFE
    pmu = np.zeros(traces.N_PMU_FEATURES, np.float32)

    def build(n_vms, pool_gb=750.0, mem_gb=800.0):
        vms = [traces.VM(i, 0, 0, 0, 0, 2, mem_gb, float(10 * i), 5.0,
                         0.5, 0.0, 0.0, pmu) for i in range(n_vms)]
        dec = [cluster_sim.VMDecision(mem_gb - pool_gb, pool_gb, False,
                                      vms[i].arrival + 1.0)
               for i in range(n_vms)]
        return vms, dec

    cfg = cluster_sim.ClusterConfig(n_servers=4, pool_sockets=8)
    server = np.array([900.0, 900.0])
    pool = np.array([800.0, 0.0])       # 0-pool lane: deficit path
    vms, dec = build(39)                # 39 * 750 + 750 == safe: eligible
    eng = replay_engine.CompiledReplay(vms, dec, cfg)
    assert eng._mig_pool_sum + eng._pay_pool_max == safe
    assert eng._pick_state_dtype(np.floor(server),
                                 np.floor(pool)) == "int16"
    i16 = eng.reject_rates(server, pool, state_dtype="int16")
    i32 = eng.reject_rates(server, pool, state_dtype="int32")
    oracle = [cluster_sim.replay_reject_rate(vms, dec, cfg, s, p)
              for s, p in zip(server, pool)]
    assert i16.tolist() == i32.tolist() == oracle
    stream = replay_engine.CompiledReplayStream(
        vms, dec, cfg, max_events_per_shard=256)
    assert stream._pick_state_dtype(np.floor(server),
                                    np.floor(pool)) == "int16"
    assert stream.reject_rates(server, pool,
                               state_dtype="int16").tolist() == oracle
    # one more migrating VM crosses the bound -> automatic int32
    vms40, dec40 = build(40)
    eng40 = replay_engine.CompiledReplay(vms40, dec40, cfg)
    assert eng40._mig_pool_sum + eng40._pay_pool_max > safe
    assert eng40._pick_state_dtype(np.floor(server),
                                   np.floor(pool)) == "int32"
    st40 = replay_engine.CompiledReplayStream(
        vms40, dec40, cfg, max_events_per_shard=256)
    assert st40._pick_state_dtype(np.floor(server),
                                  np.floor(pool)) == "int32"
    # out-of-window migrates are dropped at compile: they neither
    # count toward the bound nor flip the pick
    drop = [cluster_sim.VMDecision(d.local_gb, d.pool_gb, d.fully_pooled,
                                   vms40[i].departure + 1.0)
            for i, d in enumerate(dec40)]
    eng_drop = replay_engine.CompiledReplay(vms40, drop, cfg)
    assert not eng_drop._has_migrate and eng_drop._mig_pool_sum == 0.0
    assert eng_drop._pick_state_dtype(np.floor(server),
                                      np.floor(pool)) == "int16"


# --------------------------------------------------- search integration ---
def test_savings_analysis_streams_past_shard_budget():
    vms, _ = _trace(horizon=2 * 86400)
    mono = cluster_sim.savings_analysis(vms, CFG, "static",
                                        static_pool_frac=0.25)
    streamed = cluster_sim.savings_analysis(
        vms, CFG, "static", static_pool_frac=0.25,
        max_events_per_shard=256)
    # server bisections replicate the scalar probe sequence bitwise
    assert streamed.baseline_server_gb == mono.baseline_server_gb
    # the streamed optimum is a valid feasible provisioning point
    tol = streamed.reject_rate is not None
    assert tol and streamed.pool_group_gb <= \
        replay_engine.CompiledReplayStream(
            vms, cluster_sim.policy_decisions(
                vms, "static", static_pool_frac=0.25)[0], CFG,
            max_events_per_shard=256).peak_pool_demand() + 1e-9
    assert streamed.server_gb <= streamed.baseline_server_gb + 1e-9
    # the batched entry point streams through a CompiledReplayStreamBatch
    # running the SAME lockstep searches as the monolithic batch — every
    # probe is bit-exact, so the provisioning results match bitwise
    mono_rows = cluster_sim.savings_analysis_batched(
        [vms, vms], CFG, "static", static_pool_frac=0.25)
    cache: dict = {}
    rows = cluster_sim.savings_analysis_batched(
        [vms, vms], CFG, "static", static_pool_frac=0.25, cache=cache,
        max_events_per_shard=256)
    assert isinstance(cache["local_batch"],
                      replay_engine.CompiledReplayStreamBatch)
    for got, want in zip(rows, mono_rows):
        assert got.server_gb == want.server_gb
        assert got.pool_group_gb == want.pool_group_gb
        assert got.baseline_server_gb == want.baseline_server_gb
        assert got.reject_rate == want.reject_rate


# ------------------------------------------------ streaming trace batch ---
def test_stream_batch_bit_exact_vs_independent_streams():
    """K batched streams == K independent stream runs, bit-for-bit, on
    both backends and both forced state dtypes (the batched carry sweep
    reads the keyed jit cache, so int16 engages for batches too)."""
    vms, _ = _trace()
    streams, singles, decs = [], [], []
    for frac in (0.10, 0.25, 0.40):       # K=3 decision seeds, one trace
        dec, _ = cluster_sim.policy_decisions(vms, "static",
                                              static_pool_frac=frac)
        decs.append(dec)
        streams.append(replay_engine.CompiledReplayStream(
            vms, dec, CFG, max_events_per_shard=256))
        singles.append(streams[-1].reject_rates(_SERVER, _POOL))
    batch = replay_engine.CompiledReplayStreamBatch(streams)
    assert batch.n_shards > 1
    want = np.stack(singles)
    assert batch.reject_rates(_SERVER, _POOL).tolist() == want.tolist()
    # fractional input: K numpy stream sweeps, row for row
    shifted = [replay_engine.CompiledReplayStream(
        vms, shift_half(s_dec), CFG, max_events_per_shard=256)
        for s_dec in decs]
    want_np = np.stack([s.reject_rates(_SERVER, _POOL) for s in shifted])
    assert replay_engine.CompiledReplayStreamBatch(shifted).reject_rates(
        _SERVER, _POOL).tolist() == want_np.tolist()
    # int16-eligible candidate block: forced packings agree bitwise
    srv16 = np.array([768.0, 200.0, 140.0, 60.0])
    pool16 = np.array([2048.0, 300.0, 0.0, 2048.0])
    sq = np.broadcast_to(np.floor(srv16), (3, 4))
    pq = np.broadcast_to(np.floor(pool16), (3, 4))
    assert batch._pick_state_dtype(sq, pq) == "int16"
    i16 = batch.reject_rates(srv16, pool16, state_dtype="int16")
    i32 = batch.reject_rates(srv16, pool16, state_dtype="int32")
    want16 = np.stack([s.reject_rates(srv16, pool16) for s in streams])
    assert i16.tolist() == i32.tolist() == want16.tolist()
    # per-trace (K, n_cand) candidate grids work like the mono batch
    per = np.stack([_SERVER[:3], _SERVER[1:4], _SERVER[2:5]])
    perp = np.stack([_POOL[:3], _POOL[1:4], _POOL[2:5]])
    got = batch.reject_rates(per, perp)
    for i, s in enumerate(streams):
        assert got[i].tolist() == s.reject_rates(per[i],
                                                 perp[i]).tolist()


def test_stream_batch_fixture_and_memory_bound():
    vms = traces.load_trace_file(traces.fixture_trace_path())
    cfg = cluster_sim.ClusterConfig(n_servers=4, pool_sockets=4,
                                    gb_per_core=4.0)
    server = np.array([768.0, 120.0, 60.0, 30.0])
    pool = np.array([512.0, 64.0, 0.0, 512.0])
    streams, decs = [], []
    for frac in (0.15, 0.30):
        dec, _ = cluster_sim.policy_decisions(vms, "static",
                                              static_pool_frac=frac)
        decs.append(dec)
        streams.append(replay_engine.CompiledReplayStream(
            vms, dec, cfg, max_events_per_shard=256))
    batch = replay_engine.CompiledReplayStreamBatch(streams)
    want = np.stack([s.reject_rates(server, pool) for s in streams])
    assert batch.reject_rates(server, pool).tolist() == want.tolist()
    # fractional input: the numpy shard sweeps, row for row
    frac = [replay_engine.CompiledReplayStream(
        vms, shift_half(s_dec), cfg, max_events_per_shard=256)
        for s_dec in decs]
    want_np = np.stack([s.reject_rates(server, pool) for s in frac])
    assert replay_engine.CompiledReplayStreamBatch(frac).reject_rates(
        server, pool).tolist() == want_np.tolist()
    # THE memory bound: one stacked shard batch of K rows, set by the
    # shard budget — not by total event count
    assert batch.shard_pad_events <= 256
    assert batch.peak_shard_bytes == \
        batch.k * 6 * 4 * batch.shard_pad_events


@pytest.mark.slow
def test_stream_batch_100k_vm_trace_bit_exact():
    """Acceptance: >=100k VMs x K rows through the batched carry,
    bit-exact vs each independent stream, memory bounded."""
    n = 100_000
    rng = np.random.default_rng(11)
    arrival = np.sort(rng.uniform(0, 30 * 86400, n)).round(3)
    life = rng.integers(1800, 86400, n).astype(float)
    cores = rng.choice([2, 4, 8], n, p=[.5, .3, .2])
    mem = cores * rng.choice([2, 4], n)
    pmu = np.zeros(traces.N_PMU_FEATURES, np.float32)
    vms = [traces.VM(i, 0, 0, 0, 0, int(cores[i]), float(mem[i]),
                     float(arrival[i]), float(life[i]), 0.5, 0.0, 0.0,
                     pmu)
           for i in range(n)]
    cfg = cluster_sim.ClusterConfig(n_servers=112, pool_sockets=16,
                                    gb_per_core=4.75)
    server = np.array([768.0, 44.0, 30.0])
    pool = np.array([6144.0, 512.0, 0.0])
    budget = 32_768
    streams = []
    for frac in (0.15, 0.30):
        dec = [cluster_sim.VMDecision(
            v.mem_gb - float(np.floor(v.mem_gb * frac)),
            float(np.floor(v.mem_gb * frac)), False, None) for v in vms]
        streams.append(replay_engine.CompiledReplayStream(
            vms, dec, cfg, max_events_per_shard=budget))
    batch = replay_engine.CompiledReplayStreamBatch(streams)
    assert batch.n_shards >= 6
    assert batch.shard_pad_events <= budget
    assert batch.peak_shard_bytes == 2 * 6 * 4 * batch.shard_pad_events
    want = np.stack([s.reject_rates(server, pool) for s in streams])
    assert len(set(want.ravel().tolist())) > 1    # memory actually binds
    assert batch.reject_rates(server, pool).tolist() == want.tolist()


def test_stream_batch_lockstep_search_equivalence():
    """search_min_multi / pool_search_multi on a streaming batch land on
    the monolithic batch's exact results: every probe is bit-exact and
    the reject_cap early exit never flips a feasibility answer."""
    vms, _ = _trace(horizon=2 * 86400)
    decs = [cluster_sim.policy_decisions(vms, "static",
                                         static_pool_frac=f)[0]
            for f in (0.15, 0.30)]
    mono = replay_engine.CompiledReplayBatch(
        [replay_engine.CompiledReplay(vms, d, CFG) for d in decs])
    sb = replay_engine.CompiledReplayStreamBatch(
        [replay_engine.CompiledReplayStream(vms, d, CFG,
                                            max_events_per_shard=256)
         for d in decs])
    hi = CFG.cores_per_server * 12.0
    big_pool = hi * CFG.n_servers
    tol = mono.reject_rates(hi, big_pool)[:, 0] + 0.005
    cap = int(np.floor(tol * np.maximum(mono.n_vms, 1)).max())
    k = mono.k
    want_min = replay_engine.search_min_multi(
        lambda g: mono.reject_rates(g, np.full_like(g, big_pool))
        <= tol[:, None], np.zeros(k), np.full(k, hi))
    got_min = replay_engine.search_min_multi(
        lambda g: sb.reject_rates(g, np.full_like(g, big_pool),
                                  reject_cap=cap)
        <= tol[:, None], np.zeros(k), np.full(k, hi))
    assert got_min.tolist() == want_min.tolist()
    grids = np.linspace(want_min, np.full(k, hi * 0.8), 3, axis=1)
    want_pool = replay_engine.pool_search_multi(mono, grids, big_pool,
                                                tol)
    got_pool = replay_engine.pool_search_multi(sb, grids, big_pool, tol,
                                               reject_cap=cap)
    assert got_pool.tolist() == want_pool.tolist()
