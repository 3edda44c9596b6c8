"""Multi-device sharded sweeps: bit-exactness vs the single-device
path, plus the divergence-window event skipping of the streaming
engines.

The sharded paths need >= 2 visible jax devices; on CPU-only hosts a
device pool only exists when ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` is exported before the first jax import.  The
canonical parity test therefore runs in a subprocess with the flag
forced; the in-process variants engage whenever the suite itself was
launched with a device pool (the CI multi-device step) and skip
otherwise.  Divergence-window tests need no devices and always run.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import cluster_sim, obs, replay_engine, traces
from repro.core.sweep_core import lane_shard_count, resolve_devices

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

CFG = cluster_sim.ClusterConfig(n_servers=8, cores_per_server=16,
                                pool_sockets=8, gb_per_core=4.75)
SGB = np.linspace(120.0, 400.0, 5)
PGB = np.linspace(0.0, 900.0, 5)


def _trace(seed, n=300, horizon=2 * 86400):
    vms = traces.Population(seed=0).sample_vms(n, horizon, seed=seed,
                                               start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.3)
    return vms, dec


def _streams(k=3, budget=256):
    return [replay_engine.CompiledReplayStream(
        *_trace(20 + i), CFG, max_events_per_shard=budget)
        for i in range(k)]


def _n_devices():
    import jax
    return len(jax.devices())


# ------------------------------------------------- subprocess parity --
# Forced 8-device pool; every engine family, both dtypes, even and
# uneven K % n_devices.  Kept deliberately small: each sharded variant
# costs one fresh XLA compile in the subprocess.
_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.core import cluster_sim, replay_engine, topology, traces

cfg = cluster_sim.ClusterConfig(n_servers=8, cores_per_server=16,
                                pool_sockets=8, gb_per_core=4.75)
sgb = np.linspace(120., 400., 5)
pgb = np.linspace(0., 900., 5)


def mk(seed):
    vms = traces.Population(seed=0).sample_vms(250, 2 * 86400,
                                               seed=seed,
                                               start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.3)
    return vms, dec


import jax
assert len(jax.devices()) == 8, jax.devices()

# stream batch: trace plan, even (K=3 on 3 devices) + uneven (K=3 on 2)
streams = [replay_engine.CompiledReplayStream(
    *mk(20 + i), cfg, max_events_per_shard=256) for i in range(3)]
sb = replay_engine.CompiledReplayStreamBatch(streams)
base = sb.reject_rates(sgb, pgb, skip_windows=False)
assert (base == sb.reject_rates(sgb, pgb, devices="all",
                                skip_windows=False)).all()
assert (base == sb.reject_rates(sgb, pgb, devices=2,
                                skip_windows=False)).all()
assert (base == sb.reject_rates(sgb, pgb, devices=2, skip_windows=False,
                                state_dtype="int16")).all()

# single stream: candidate-lane plan
s0 = streams[0].reject_rates(sgb, pgb, skip_windows=False)
assert (s0 == streams[0].reject_rates(sgb, pgb, devices="all",
                                      skip_windows=False)).all()

# monolithic batch: trace plan + int16
engines = [replay_engine.CompiledReplay(*mk(40 + i), cfg)
           for i in range(3)]
batch = replay_engine.CompiledReplayBatch(engines)
b0 = batch.reject_rates(sgb, pgb)
assert (b0 == batch.reject_rates(sgb, pgb, devices=2)).all()
assert (b0 == batch.reject_rates(sgb, pgb, devices=2,
                                 state_dtype="int16")).all()

# fleet (pod scan) through the stream batch
topo = topology.partitioned(cfg.n_servers, 4)
pods = [topology.split_pool(p, 2) for p in np.linspace(0., 600., 5)]
f0 = sb.reject_rates_fleet(sgb, pods, topo)
assert (f0 == sb.reject_rates_fleet(sgb, pods, topo,
                                    devices="all")).all()
print("OK")
"""


def test_sharded_bit_exact_on_forced_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)          # the script sets its own
    r = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0 and "OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]


# The same sharded engines with the sweep selected as on a TPU (the
# platform stubbed as ``test_sweep_pallas._stub_tpu`` does, the Pallas
# kernel run by its interpreter): each mesh variant wraps the kernel in
# ``shard_map`` and must match the single-device scan bit for bit.
_SUBPROC_PALLAS = r"""
import functools, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.core import cluster_sim, obs, replay_engine, sweep_core, traces

cfg = cluster_sim.ClusterConfig(n_servers=8, cores_per_server=16,
                                pool_sockets=8, gb_per_core=4.75)
sgb = np.linspace(120., 400., 5)
pgb = np.linspace(0., 900., 5)


def mk(seed):
    vms = traces.Population(seed=0).sample_vms(250, 2 * 86400,
                                               seed=seed,
                                               start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.3)
    return vms, dec


import jax
assert len(jax.devices()) == 4, jax.devices()
streams = [replay_engine.CompiledReplayStream(
    *mk(20 + i), cfg, max_events_per_shard=256) for i in range(3)]
engines = [replay_engine.CompiledReplay(*mk(40 + i), cfg)
           for i in range(3)]
# (engine, devices, state dtype)
if sys.argv[1] == "trace":    # K rows split over the mesh, K % n uneven
    calls = [(replay_engine.CompiledReplayStreamBatch(streams), 2, "int32"),
             (replay_engine.CompiledReplayStreamBatch(streams), 2, "int16"),
             (replay_engine.CompiledReplayBatch(engines), 2, "int32"),
             (replay_engine.CompiledReplayBatch(engines), 3, "int16")]
else:                         # the candidate lanes split over the mesh
    calls = [(streams[0], 4, "int32"), (engines[0], 4, "int16"),
             (replay_engine.CompiledReplayBatch(engines[:2]), 4, "int32")]


def rates(engine, **kw):
    if isinstance(engine, (replay_engine.CompiledReplayStream,
                           replay_engine.CompiledReplayStreamBatch)):
        kw["skip_windows"] = False
    return engine.reject_rates(sgb, pgb, **kw)


want = [rates(e, state_dtype=dt) for e, _, dt in calls]
sweep_core._on_tpu = lambda mesh: True
sweep_core.build_pallas_sweep = functools.partial(
    sweep_core.build_pallas_sweep, interpret=True)
sweep_core._SWEEPS.clear()
rec = obs.Recorder()
with obs.use_recorder(rec):
    got = [rates(e, devices=n, state_dtype=dt) for e, n, dt in calls]
for w, g in zip(want, got):
    assert w.tolist() == g.tolist(), (w, g)
m = rec.metrics()
dispatches = sum(v for k, v in m.items() if k.startswith("span.")
                 and k.endswith(".compute.count"))
assert m["sweep.kernel.pallas"] == dispatches >= len(calls), m
assert "sweep.kernel.scan" not in m, m
keys = sweep_core.jit_cache_keys()
assert keys and all(k[-2:] == (sys.argv[1], "pallas") for k in keys), keys
print("OK")
"""


@pytest.mark.parametrize("shard_axis", ["trace", "lane"])
def test_sharded_pallas_kernel_matches_scan(shard_axis):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)          # the script sets its own
    r = subprocess.run([sys.executable, "-c", _SUBPROC_PALLAS, shard_axis],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0 and "OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]


# -------------------------------------------- in-process (device pool) --
def test_resolve_devices_semantics():
    import jax
    n = _n_devices()
    assert resolve_devices(None) is None
    assert resolve_devices(1) is None                 # < 2 degrades
    if n >= 2:
        assert len(resolve_devices("all")) == n
        assert len(resolve_devices(2)) == 2
        assert resolve_devices(jax.devices()[:2]) is not None
    else:
        assert resolve_devices("all") is None
    with pytest.raises(ValueError):
        resolve_devices("some")


def test_lane_shard_count_divides_width():
    assert lane_shard_count(16, 8) == 8
    assert lane_shard_count(16, 5) == 4
    assert lane_shard_count(96, 7) == 6
    assert lane_shard_count(2, 8) == 2
    for w in (2, 4, 16, 32, 96):
        for n in range(1, 9):
            assert w % lane_shard_count(w, n) == 0


@pytest.mark.skipif(_n_devices() < 2,
                    reason="needs >= 2 jax devices (export XLA_FLAGS="
                           "--xla_force_host_platform_device_count=8)")
def test_stream_batch_sharded_in_process():
    streams = _streams()
    sb = replay_engine.CompiledReplayStreamBatch(streams)
    base = sb.reject_rates(SGB, PGB, skip_windows=False)
    dev = sb.reject_rates(SGB, PGB, devices="all", skip_windows=False)
    assert base.tolist() == dev.tolist()


# ---------------------------------------------- divergence windows --
def test_stream_skip_windows_bit_exact_and_fires():
    vms, dec = _trace(7, n=600, horizon=3 * 86400)
    stream = replay_engine.CompiledReplayStream(vms, dec, CFG,
                                                max_events_per_shard=256)
    assert stream.n_shards > 1
    # every candidate cap above the trace's per-shard peak needs, so
    # the reference proves whole shards can't bind (a min pool cap of
    # 0 GB would pin the windows shut — rejects bind immediately)
    gen_s, gen_p = SGB, np.linspace(150.0, 900.0, 5)
    mono = replay_engine.CompiledReplay(vms, dec, CFG).reject_rates(
        gen_s, gen_p)
    prev = obs.get_recorder()
    rec = obs.Recorder()
    obs.set_recorder(rec)
    try:
        skipped = stream.reject_rates(gen_s, gen_p)
    finally:
        obs.set_recorder(prev)
    full = stream.reject_rates(gen_s, gen_p, skip_windows=False)
    assert skipped.tolist() == full.tolist() == mono.tolist()
    # generous caps: the early shards cannot bind, so the reference
    # fast-forwards at least one of them
    assert rec.metrics().get("stream.shards_skipped", 0) > 0
    assert rec.metrics().get("stream.events_skipped", 0) > 0


def test_stream_skip_windows_tight_caps_bit_exact():
    # caps the trace saturates immediately: nothing is skippable, the
    # guarded path must still match the full scan
    vms, dec = _trace(9, n=500)
    stream = replay_engine.CompiledReplayStream(vms, dec, CFG,
                                                max_events_per_shard=256)
    tight_s, tight_p = [130.0], [10.0]
    assert stream.reject_rates(tight_s, tight_p).tolist() == \
        stream.reject_rates(tight_s, tight_p,
                            skip_windows=False).tolist()


def test_stream_skip_windows_int16_bit_exact():
    vms, dec = _trace(11, n=500)
    stream = replay_engine.CompiledReplayStream(vms, dec, CFG,
                                                max_events_per_shard=256)
    full = stream.reject_rates(SGB, PGB, skip_windows=False)
    assert stream.reject_rates(SGB, PGB,
                               state_dtype="int16").tolist() == \
        full.tolist()


def test_stream_batch_skip_windows_bit_exact():
    streams = _streams()
    sb = replay_engine.CompiledReplayStreamBatch(streams)
    full = sb.reject_rates(SGB, PGB, skip_windows=False)
    skipped = sb.reject_rates(SGB, PGB)
    per = np.stack([s.reject_rates(SGB, PGB, skip_windows=False)
                    for s in streams])
    assert skipped.tolist() == full.tolist() == per.tolist()
