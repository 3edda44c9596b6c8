"""What the sweeps really scan: ``sweep.lane_arrivals`` and its sibling
work counters at every plain sweep dispatch of the four engines,
``stream.steps_unscanned`` for the shards that window skipping and
``reject_cap`` exits leave, and the ``ingest.load`` span of
``traces.load_trace_file``.  None of them changes a result."""
import numpy as np
import pytest

from repro.core import cluster_sim, obs, replay_engine, traces

CFG = cluster_sim.ClusterConfig(n_servers=16, cores_per_server=32,
                                pool_sockets=8, gb_per_core=4.0)
SERVER = np.array([96.0, 160.0, 384.0])
POOL = np.array([0.0, 64.0, 2048.0])


@pytest.fixture(autouse=True)
def _no_ambient_recorder():
    prev = obs._ACTIVE
    obs.set_recorder(None)
    yield
    obs.set_recorder(prev)


def _trace(seed, days=2):
    vms = traces.Population(seed=0).sample_vms(
        cluster_sim.arrivals_for_util(CFG, 0.75, days * 86400.0),
        days * 86400.0, seed=seed)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.15)
    return vms, dec


def _engine(kind, seeds=(5, 6)):
    pairs = [_trace(s) for s in seeds]
    if kind == "replay":
        return replay_engine.CompiledReplay(*pairs[0], CFG), pairs[:1]
    if kind == "batch":
        return replay_engine.CompiledReplayBatch(
            [replay_engine.CompiledReplay(*p, CFG) for p in pairs]), pairs
    streams = [replay_engine.CompiledReplayStream(
        *p, CFG, max_events_per_shard=256) for p in pairs]
    if kind == "stream":
        return streams[0], pairs[:1]
    return replay_engine.CompiledReplayStreamBatch(streams), pairs


def _metrics(fn):
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        out = fn()
    return out, rec.metrics()


@pytest.mark.parametrize("kind", ["replay", "stream", "batch",
                                  "stream_batch"])
def test_whole_scans_count_every_arrival_on_every_lane(kind):
    eng, pairs = _engine(kind)
    kw = {} if kind in ("replay", "batch") else {"skip_windows": False}
    _, m = _metrics(lambda: eng.reject_rates(SERVER, POOL, **kw))
    lanes, k = len(SERVER), len(pairs)
    arrivals = sum(len(vms) for vms, _ in pairs)
    events = 2 * arrivals           # the static split migrates nothing
    dispatches = m["sweep.kernel.scan"] + m.get("sweep.kernel.pallas", 0)
    rows = {"replay": 2, "stream": 2, "batch": 1 + k,
            "stream_batch": 2 * k}[kind]
    assert m["sweep.lane_arrivals"] == arrivals * lanes
    assert m["sweep.fit_cells"] == arrivals * lanes * CFG.n_servers
    assert m["sweep.events_scanned"] == events
    assert m["sweep.carry_cells"] == dispatches * rows * lanes * (
        2 * CFG.n_servers + CFG.n_groups)
    assert m.get("stream.steps_unscanned", 0) == 0


def _calls(eng):
    """A round of streamed calls: large caps whose leading shards the
    reference lets every lane skip, small caps that every lane
    overruns early under a reject cap, and a whole scan."""
    return [eng.reject_rates([384.0, 352.0], [2048.0, 2048.0]),
            eng.reject_rates([24.0, 32.0], [0.0, 0.0], reject_cap=2),
            eng.reject_rates(SERVER, POOL)]


@pytest.mark.parametrize("kind", ["stream", "stream_batch"])
def test_scanned_and_unscanned_steps_cover_every_call(kind):
    eng, _ = _engine(kind)
    steps = getattr(eng, "shard_steps", None) or eng.shard_events
    off = _calls(eng)
    on, m = _metrics(lambda: _calls(eng))
    assert m["stream.shards_skipped"] > 0
    assert m["stream.reject_cap_exits"] > 0
    assert m["stream.steps_unscanned"] > 0
    assert m["sweep.steps"] + m["stream.steps_unscanned"] == \
        len(on) * sum(steps)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_streamed_search_counts_what_it_skips():
    # the lockstep search past the shard budget: every round is one
    # stream-batch call of one chunk, skipping where the reference
    # lets it, and the searched sizes are those of an untraced search
    vms_list = [_trace(s, days=4)[0] for s in (7, 8)]

    def search():
        return [(r.server_gb, r.pool_group_gb, r.reject_rate)
                for r in cluster_sim.savings_analysis_batched(
                    vms_list, CFG, "static", max_events_per_shard=256)]

    off = search()
    on, m = _metrics(search)
    assert on == off
    calls = m["span.stream_batch.reject_rates.count"]
    whole = 2 * max(len(vms) for vms in vms_list)   # no migrations
    assert m["stream.shards_skipped"] > 0
    assert m["sweep.steps"] + m["stream.steps_unscanned"] == calls * whole


def test_ingest_load_counts_each_file_once():
    path = traces.fixture_trace_path()
    plain = traces.load_trace_file(path)
    loaded, m = _metrics(lambda: [traces.load_trace_file(path)
                                  for _ in range(2)])
    assert m["span.ingest.load.count"] == 2
    assert m["ingest.rows"] == 2 * len(plain)
    assert [(v.arrival, v.lifetime, v.cores, v.mem_gb) for v in plain] \
        == [(v.arrival, v.lifetime, v.cores, v.mem_gb) for v in loaded[0]]
