"""Observability layer (``core/obs.py``): disabled-mode is a true
no-op (identity of results + overhead bound), span nesting/ordering
invariants, jit-cache counters match real ``get_*_sweep`` cache
behavior, and Chrome-trace JSON round-trips cleanly."""
import json
import time

import numpy as np
import pytest

from repro.core import cluster_sim, obs, replay_engine, sweep_core

try:
    import jax  # noqa: F401
    HAS_JAX = True
except Exception:                                    # pragma: no cover
    HAS_JAX = False


@pytest.fixture(autouse=True)
def _no_ambient_recorder():
    """Tests control the active recorder explicitly; never leak one."""
    prev = obs._ACTIVE
    obs.set_recorder(None)
    yield
    obs.set_recorder(prev)


def _small_engine(seed=0, n=250, horizon=3 * 86400.0):
    from benchmarks import common
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                    gb_per_core=4.0)
    vms = common.population().sample_vms(n, horizon, seed=seed)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.30)
    return replay_engine.CompiledReplay(vms, dec, cfg)


# ------------------------------------------------------------- recorder ----
def test_span_nesting_and_ordering():
    rec = obs.Recorder()
    with rec.span("outer"):
        with rec.span("inner", k=1):
            pass
        with rec.span("inner", k=2):
            pass
    spans = rec.spans()
    # inner spans finish (and are emitted) before outer
    assert [s["name"] for s in spans] == ["inner", "inner", "outer"]
    inner1, inner2, outer = spans
    assert inner1["depth"] == inner2["depth"] == 1
    assert outer["depth"] == 0
    # nesting: outer brackets both inners in time
    assert outer["ts_ns"] <= inner1["ts_ns"]
    assert (inner2["ts_ns"] + inner2["dur_ns"]
            <= outer["ts_ns"] + outer["dur_ns"])
    assert inner1["ts_ns"] + inner1["dur_ns"] <= inner2["ts_ns"]
    assert all(s["dur_ns"] >= 0 and s["ts_ns"] >= 0 for s in spans)
    assert inner1["args"] == {"k": 1} and inner2["args"] == {"k": 2}


def test_counters_and_metrics():
    rec = obs.Recorder()
    rec.count("x")
    rec.count("x", 4)
    rec.count("pad.events_used", 75)
    rec.count("pad.events_padded", 25)
    with rec.span("s"):
        pass
    m = rec.metrics()
    assert m["x"] == 5
    assert m["span.s.count"] == 1
    assert m["span.s.total_s"] >= 0.0
    assert m["pad.event_waste_ratio"] == 0.25


def test_event_cap_keeps_aggregates():
    rec = obs.Recorder(max_events=3)
    for _ in range(10):
        with rec.span("s"):
            pass
    assert len(rec.spans()) == 3
    m = rec.metrics()
    assert m["span.s.count"] == 10          # aggregates fold past cap
    assert m["obs.dropped_events"] == 7


def test_use_recorder_scoping():
    rec = obs.Recorder()
    assert not obs.enabled()
    with obs.use_recorder(rec):
        assert obs.get_recorder() is rec
        assert obs.enabled()
    assert not obs.enabled()
    assert obs.get_recorder().span("x") is obs._NULL_SPAN


def test_traced_decorator():
    calls = []

    @obs.traced("f.span")
    def f(a, b=1):
        calls.append((a, b))
        return a + b

    assert f(2, b=3) == 5                   # disabled: plain call
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        assert f(4) == 5
    assert calls == [(2, 3), (4, 1)]
    assert rec.metrics()["span.f.span.count"] == 1


# --------------------------------------------------- disabled-mode no-op --
def test_disabled_overhead_bound():
    """Null-recorder primitives on a 10k-event sweep's worth of call
    sites stay near-free: bounded vs the same loop doing real work.

    The bound is generous (10x a trivial arithmetic baseline) to stay
    robust on noisy CI runners — the point is catching an accidental
    allocation/formatting on the disabled path, not a microbenchmark.
    """
    n = 10_000
    rec = obs.get_recorder()
    assert rec is obs._NULL

    def instrumented():
        t0 = time.perf_counter()
        acc = 0
        for i in range(n):
            r = obs.get_recorder()
            with r.span("shard"):
                acc += i
            if r.enabled:
                r.count("pad.events_used", i)
        return time.perf_counter() - t0, acc

    def baseline():
        t0 = time.perf_counter()
        acc = 0
        for i in range(n):
            acc += i
        return time.perf_counter() - t0, acc

    instrumented()          # warm
    baseline()
    t_i = min(instrumented()[0] for _ in range(3))
    t_b = min(baseline()[0] for _ in range(3))
    assert instrumented()[1] == baseline()[1]
    assert t_i < max(10 * t_b, 0.05), (t_i, t_b)


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
def test_tracing_identity_of_results():
    """Engine results are bitwise identical with tracing on vs off."""
    eng = _small_engine()
    server = np.array([200.0, 260.0])
    pool = np.array([64.0, 128.0])
    off = eng.reject_rates(server, pool)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        on = eng.reject_rates(server, pool)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))
    assert rec.metrics()["span.replay.reject_rates.count"] == 1


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
@pytest.mark.parametrize("engine", ["replay", "stream"])
def test_auto_backend_numpy_fallback_is_counted(engine):
    """Every fallback to the numpy sweep (non-integral decisions) is
    counted, and nothing when the XLA sweep runs."""
    from benchmarks import common
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8)
    vms = common.population().sample_vms(200, 86400.0, seed=4)
    halves = [cluster_sim.VMDecision(vm.mem_gb - 0.5, 0.5, False, None)
              for vm in vms]
    whole, _ = cluster_sim.policy_decisions(vms, "static")

    def build(dec):
        if engine == "stream":
            return replay_engine.CompiledReplayStream(
                vms, dec, cfg, max_events_per_shard=256)
        return replay_engine.CompiledReplay(vms, dec, cfg)

    counts = []
    for dec in (whole, halves):
        rec = obs.Recorder()
        with obs.use_recorder(rec):
            build(dec).reject_rates([300.0], [64.0])
        counts.append(rec.metrics().get("replay.backend_numpy", 0))
    assert counts == [0, 1]


# --------------------------------------------------- jit-cache counters ---
@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
@pytest.mark.parametrize("state_dtype,batched", [
    ("int32", False), ("int32", True), ("int16", False)])
def test_jit_cache_counters_match_cache(state_dtype, batched):
    key = (state_dtype, False, batched)
    stem = f"jit.sweep.{state_dtype}.carry0.batched{int(batched)}"
    sweep_core._SWEEPS.pop(key, None)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        sweep_core.get_sweep(state_dtype=state_dtype, batched=batched)
        sweep_core.get_sweep(state_dtype=state_dtype, batched=batched)
    m = rec.metrics()
    assert m[stem + ".miss"] == 1
    assert m[stem + ".hit"] == 1
    assert m[f"span.{stem}.build.count"] == 1


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
def test_jit_fail_and_pod_cache_counters():
    sweep_core._FAIL_SWEEPS.pop(("int32", "kill", False, True), None)
    sweep_core._POD_SWEEPS.pop(("int32", False, False), None)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        sweep_core.get_fail_sweep(state_dtype="int32", mitigation="kill")
        sweep_core.get_fail_sweep(state_dtype="int32", mitigation="kill")
        sweep_core.get_pod_sweep(state_dtype="int32")
        sweep_core.get_pod_sweep(state_dtype="int32")
    m = rec.metrics()
    assert m["jit.fail.int32.kill.batched0.dist1.miss"] == 1
    assert m["jit.fail.int32.kill.batched0.dist1.hit"] == 1
    assert m["jit.pod.int32.carry0.batched0.miss"] == 1
    assert m["jit.pod.int32.carry0.batched0.hit"] == 1


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
def test_lowering_span_recorded_on_first_call():
    """The ``.lower`` span fires on the cache-missed sweep's first
    invocation (trace+compile), not on later calls."""
    sweep_core._SWEEPS.clear()    # engines pick the narrowest dtype
    eng = _small_engine(seed=1)
    server = np.array([220.0])
    pool = np.array([96.0])
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        eng.reject_rates(server, pool)
        eng.reject_rates(server, pool)
    m = rec.metrics()
    lowers = {k: v for k, v in m.items()
              if k.startswith("span.jit.sweep.") and k.endswith(
                  ".lower.count")}
    assert lowers and all(v == 1 for v in lowers.values()), m
    # every lowered sweep was a cache miss (some missed variants are
    # built but not invoked here, so misses can exceed lowers)
    misses = [v for k, v in m.items()
              if k.startswith("jit.sweep.") and k.endswith(".miss")]
    assert sum(misses) >= len(lowers)


# --------------------------------------------------- chrome trace export --
def test_chrome_trace_round_trip(tmp_path):
    rec = obs.Recorder()
    with rec.span("a"):
        with rec.span("b", shard=np.int64(3)):
            pass
    rec.count("jit.sweep.int32.carry0.batched0.hit", 2)
    out = tmp_path / "trace.json"
    rec.to_chrome_trace(str(out), manifest=obs.run_manifest())
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["a", "b"]    # sorted by start
    for e in evs:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert evs[0]["ts"] <= evs[1]["ts"]
    assert (doc["metadata"]["counters"]
            ["jit.sweep.int32.carry0.batched0.hit"] == 2)
    man = doc["metadata"]["manifest"]
    assert man["git_sha"] and man["timestamp"]


def test_run_manifest_fields():
    man = obs.run_manifest(extra_key="v")
    for k in ("timestamp", "unix_time", "git_sha", "python_version",
              "numpy_version", "jax_version", "backend", "device_kind",
              "n_devices"):
        assert k in man, k
    assert man["extra_key"] == "v"
    assert len(man["git_sha"]) in (7, 40) or man["git_sha"] == "unknown"


# ------------------------------------------------------- ingest counters --
def test_ingest_counters(tmp_path):
    from repro.core import traces
    p = traces.fixture_trace_path()
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        n = sum(len(v) for v in
                traces.iter_trace_chunks(p, chunk_vms=16))
    m = rec.metrics()
    assert m["ingest.vms"] == n
    assert m["ingest.rows"] == n
    assert m["ingest.chunks"] == (n + 15) // 16
    assert m["span.ingest.chunk.count"] >= m["ingest.chunks"]


def test_ingest_counters_identity():
    """Instrumented ingestion yields the identical VM stream."""
    from repro.core import traces
    p = traces.fixture_trace_path()
    plain = [v for c in traces.iter_trace_chunks(p, chunk_vms=16)
             for v in c]
    with obs.use_recorder(obs.Recorder()):
        traced = [v for c in traces.iter_trace_chunks(p, chunk_vms=16)
                  for v in c]
    assert [(v.vm_id, v.arrival, v.mem_gb) for v in plain] == \
        [(v.vm_id, v.arrival, v.mem_gb) for v in traced]


# ------------------------------------------- compute spans, sweep.steps ---
_SERVER = np.array([200.0, 260.0, 120.0, 300.0])
_POOL = np.array([64.0, 128.0, 0.0, 256.0])


def _n_chunks(n):
    return len(list(sweep_core.candidate_chunks(n)))


def _stream_batch(budget=256):
    engines = [_small_engine(seed=s, n=400) for s in (2, 3)]
    streams = [replay_engine.CompiledReplayStream(
        e._vms, e._decisions_src, e.cfg, max_events_per_shard=budget)
        for e in engines]
    return replay_engine.CompiledReplayStreamBatch(streams)


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
def test_batch_compute_spans_and_steps():
    """``batch.compute`` once per candidate chunk, ``batch.upload`` only
    when the stacked events build, ``sweep.steps`` the longest trace's
    events per chunk, ``replay.compile`` once per engine."""
    n = sweep_core.JAX_CHUNK + 4                   # two chunks
    server = np.linspace(150.0, 320.0, n)
    pool = np.linspace(0.0, 256.0, n)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        batch = replay_engine.CompiledReplayBatch(
            [_small_engine(seed=s, n=n_vms)
             for s, n_vms in ((0, 250), (1, 300))])
        batch.reject_rates(server, pool)
    second = obs.Recorder()
    with obs.use_recorder(second):
        batch.reject_rates(server, pool)
    chunks = _n_chunks(n)
    assert chunks == 2
    steps = int(batch.n_events.max()) * chunks
    m, m2 = rec.metrics(), second.metrics()
    assert m["span.replay.compile.count"] == 2
    assert m["span.batch.upload.count"] == 1
    assert "span.batch.upload.count" not in m2
    for got in (m, m2):
        assert got["span.batch.compute.count"] == chunks
        assert got["sweep.steps"] == steps
        assert got["span.batch.compute.total_s"] \
            <= got["span.batch.reject_rates.total_s"]


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
@pytest.mark.parametrize("family", ["plain", "fail", "pod"])
def test_replay_compute_spans_and_steps(family):
    """Every kernel family of the single-trace engine dispatches through
    its K=1 batch: one ``batch.compute`` per chunk and its trace's
    events as steps."""
    from repro.core import topology
    from repro.runtime.fault import FailureSchedule
    eng = _small_engine(seed=5)
    if family == "fail":
        sched = FailureSchedule.generate(3 * 86400.0, eng.cfg.n_groups,
                                         8 * 3600.0, 1800.0, seed=1)
        eng = replay_engine.CompiledReplay(eng._vms, eng._decisions_src,
                                           eng.cfg, failure_schedule=sched)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        if family == "plain":
            eng.reject_rates(_SERVER, _POOL)
        elif family == "fail":
            eng.availability(_SERVER, _POOL)
        else:
            eng.reject_rates_fleet(_SERVER, 128.0,
                                   topology.partitioned(8, 4))
    m = rec.metrics()
    assert m["span.batch.compute.count"] == _n_chunks(len(_SERVER))
    assert "span.replay.compute.count" not in m
    assert m["sweep.steps"] == eng.n_events * _n_chunks(len(_SERVER))


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
@pytest.mark.parametrize("case", ["whole", "reject_cap", "skipped"])
def test_stream_batch_steps_count_dispatched_shards(case):
    """``sweep.steps`` adds the longest trace's real events of each
    shard scan that ran: not the shards a ``reject_cap`` exit left
    undispatched, nor those a divergence window skipped."""
    batch = _stream_batch()
    assert batch.n_shards > 2
    if case == "whole":
        args = dict(server_gb=_SERVER, pool_gb=_POOL, skip_windows=False)
    elif case == "reject_cap":          # every lane rejects at once
        args = dict(server_gb=np.zeros(2), pool_gb=np.zeros(2),
                    reject_cap=0, skip_windows=False)
    else:               # capacities that cover the first shard's demand
        refs = [replay_engine._stream_reference(s) for s in batch.engines]
        args = dict(server_gb=np.full(2, max(r["max_srv"][0] for r in refs)),
                    pool_gb=np.full(2, max(r["max_pool"][0] for r in refs)))
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        batch.reject_rates(**args)
    m = rec.metrics()
    ran = m.get("span.stream.compute.count", 0)
    skipped = m.get("stream.shards_skipped", 0)
    assert skipped + ran <= batch.n_shards
    assert m.get("sweep.steps", 0) == \
        sum(batch.shard_steps[skipped:skipped + ran])
    if case == "whole":
        assert ran == batch.n_shards
        assert m["sweep.steps"] == int(batch.n_events.max())
    elif case == "reject_cap":
        assert m["stream.reject_cap_exits"] == 1 and ran < batch.n_shards
    else:
        assert skipped > 0 and ran > 0


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
def test_profiler_trace_holds_recorder_spans(tmp_path):
    """Under ``jax.profiler`` every recorder span is also a host-plane
    event of the same name and (near) the same duration."""
    from jax.profiler import ProfileData
    batch = replay_engine.CompiledReplayBatch(
        [_small_engine(seed=s) for s in (6, 7)])
    stream = _stream_batch()
    rec = obs.Recorder()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.use_recorder(rec):
            batch.reject_rates(_SERVER, _POOL)
            stream.reject_rates(_SERVER, _POOL, skip_windows=False)
    finally:
        jax.profiler.stop_trace()
    mine: dict = {}
    for sp in rec.spans():
        mine.setdefault(sp["name"], []).append(sp)
    assert {"batch.upload", "batch.compute", "stream.compute",
            "stream.upload"} <= set(mine)
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    native: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in mine:
                        native.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns))
    for name, spans in mine.items():
        got = sorted(native.get(name, []))
        assert len(got) == len(spans), name
        want = sorted(spans, key=lambda sp: sp["ts_ns"])
        for (_, dur), sp in zip(got, want):
            assert abs(dur - sp["dur_ns"]) <= max(
                0.05 * sp["dur_ns"], 100_000), (name, dur, sp["dur_ns"])


@pytest.mark.skipif(not HAS_JAX, reason="needs jax")
@pytest.mark.parametrize("engine", ["batch", "stream_batch"])
def test_results_identical_recorder_off_on_profiled(engine, tmp_path):
    """Rates are bit-identical with the recorder off, on, and on under
    an active profiler session."""
    if engine == "batch":
        eng = replay_engine.CompiledReplayBatch(
            [_small_engine(seed=s) for s in (8, 9)])
    else:
        eng = _stream_batch()
    off = eng.reject_rates(_SERVER, _POOL)
    with obs.use_recorder(obs.Recorder()):
        on = eng.reject_rates(_SERVER, _POOL)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.use_recorder(obs.Recorder()):
            profiled = eng.reject_rates(_SERVER, _POOL)
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(off, on) and np.array_equal(off, profiled)
