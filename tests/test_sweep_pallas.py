"""The Pallas sweep kernel (``sweep_core.build_pallas_sweep``) against the
``lax.scan`` sweep it replaces on TPU, bit for bit, in Pallas's
interpreter on the CPU; and the selection of one or the other by the
platform the sweep runs on."""
import functools

import numpy as np
import pytest

from repro.core import cluster_sim, obs, replay_engine, sweep_core, traces
from repro.core.sweep_core import ARRIVE, DEPART, FAIL, MIGRATE, PAD

jax = pytest.importorskip("jax")
jnp = jax.numpy

N_SERVERS, CORES, SPG = 60, 16, 4         # 15 pool groups of 4 servers
S_PAD, G_PAD = 64, 16


def _trace(seed, n_vms):
    """Event columns ``(kind, slot, cores, local, pool, mem)`` of a trace
    with arrivals, departures, QoS migrations and FAIL/RECOVER events,
    padded with PAD events to a multiple of ``EVENT_PAD``."""
    rng = np.random.default_rng(seed)
    arr = rng.uniform(0, 1000, n_vms)
    life = rng.uniform(20, 400, n_vms)
    cores = rng.choice([2, 4, 8, 16], n_vms)
    mem = cores * 4
    pool = (mem * rng.choice([0, 0.25, 0.5], n_vms)).astype(int)
    mig = rng.random(n_vms) < 0.25
    n_fail = 6
    t = np.concatenate([arr, arr + life, (arr + life / 3)[mig],
                        rng.uniform(0, 1000, n_fail)])
    kind = np.concatenate([np.full(n_vms, ARRIVE), np.full(n_vms, DEPART),
                           np.full(mig.sum(), MIGRATE),
                           FAIL + np.arange(n_fail) % 2])  # FAIL, RECOVER
    vm = np.concatenate([np.arange(n_vms), np.arange(n_vms),
                         np.nonzero(mig)[0], np.zeros(n_fail, int)])
    order = np.lexsort((kind, t))
    kind, vm = kind[order], vm[order]
    slot, n_slots = sweep_core.assign_slots(kind, vm, n_vms)
    vmev = kind < FAIL
    cols = [kind, np.where(vmev, slot, 0)] + [
        np.where(vmev, a[vm], 0) for a in (cores, mem - pool, pool, mem)]
    e_pad = sweep_core.pad_up(len(kind), sweep_core.EVENT_PAD)
    out = [np.full(e_pad, PAD if j == 0 else 0, np.int32)
           for j in range(6)]
    for o, c in zip(out, cols):
        o[:len(c)] = c
    return out, n_slots


def _lanes(width, np_dt, seed):
    """Candidate capacities: lane 0 rejects (tiny servers), lane 1 has
    no pool, so every pooled VM falls back to all-local."""
    rng = np.random.default_rng(seed)
    sgb = rng.integers(90, 260, width)
    pgb = rng.integers(0, 200, width)
    sgb[0], pgb[1] = 8, 0
    return sgb.astype(np_dt), pgb.astype(np_dt)


def _stack(traces):
    e = max(len(t[0]) for t in traces)
    return tuple(np.stack([np.concatenate(
        [t[j], np.full(e - len(t[j]), PAD if j == 0 else 0, np.int32)])
        for t in traces]) for j in range(6))


def _shards(evs, n):
    """The event columns cut into ``n`` consecutive shards."""
    e = evs[0].shape[-1]
    cut = sweep_core.pad_up(-(-e // n), sweep_core.EVENT_PAD)
    return [tuple(c[..., i:i + cut] for c in evs) for i in range(0, e, cut)]


def _scan(state_dtype, with_carry, batched):
    base = sweep_core.build_sweep(state_dtype, with_carry)
    if batched:
        state_axis = 0 if with_carry else None
        base = jax.vmap(base, in_axes=((0,) * 6, None) + (state_axis,) * (
            5 if with_carry else 4) + (0, 0))
    return jax.jit(base)


CASES = [  # (variant, state dtype, candidate lanes)
    ("mono", "int32", 2), ("mono", "int16", 96), ("mono", "int16", 16),
    ("batched", "int32", 96), ("batched", "int16", 2),
    ("batched", "int32", 32),
    ("carry", "int32", 2), ("carry", "int16", 96), ("carry", "int32", 96),
    ("batched_carry", "int32", 96), ("batched_carry", "int16", 2),
    ("batched_carry", "int16", 96)]


@pytest.mark.parametrize("variant,state_dtype,width", CASES)
def test_pallas_sweep_matches_scan(variant, state_dtype, width):
    with_carry = variant.endswith("carry")
    batched = variant.startswith("batched")
    np_dt = sweep_core.state_np_dtype(state_dtype)
    k = 2 if batched else None
    traces = [_trace(seed, 520 - 40 * seed) for seed in range(k or 1)]
    n_slots = sweep_core.pad_up(max(n for _, n in traces),
                                sweep_core.SLOT_PAD)
    evs = _stack([t for t, _ in traces]) if batched else tuple(traces[0][0])
    # several event blocks, the last one partial
    assert evs[0].shape[-1] // sweep_core.EVENT_BLOCK >= 2
    assert evs[0].shape[-1] % sweep_core.EVENT_BLOCK
    caps = [_lanes(width, np_dt, seed) for seed in range(k or 1)]
    sgb, pgb = (np.stack(c) for c in zip(*caps)) if batched else caps[0]
    group_of = np.minimum(np.arange(S_PAD) // SPG, G_PAD - 1)
    group_of[N_SERVERS:] = 0
    group_of = group_of.astype(np.int32)
    init = sweep_core.init_state(width, N_SERVERS, CORES, S_PAD, G_PAD,
                                 n_slots, np_dt,
                                 k=k if with_carry else None)
    scan = _scan(state_dtype, with_carry, batched)
    kernel = jax.jit(sweep_core.build_pallas_sweep(
        state_dtype, with_carry, batched, interpret=True))
    if not with_carry:
        want = scan(evs, group_of, *init[:4], sgb, pgb)
        got = kernel(evs, group_of, *init[:4], sgb, pgb)
        assert got.dtype == want.dtype == jnp.int32
        assert np.array_equal(np.asarray(got), np.asarray(want))
        rej = np.asarray(want)
    else:
        want = got = tuple(jnp.asarray(a) for a in init)
        for shard in _shards(evs, 3):        # the carry threads shards
            want = scan(shard, group_of, *want, sgb, pgb)
            got = kernel(shard, group_of, *got, sgb, pgb)
            for w, g in zip(want, got):
                assert g.dtype == w.dtype and g.shape == w.shape
                assert np.array_equal(np.asarray(g), np.asarray(w))
        rej = np.asarray(want[4])
        # the pool-less lane placed pooled VMs all-local (odd slot values)
        assert (np.asarray(want[3])[..., 1] % 2 == 1).any()
    assert (rej[..., 0] > 0).all() and rej.shape[-1] == width


# ------------------------------------------------------------- selection --
def _engines(n_vms=400):
    cfg = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                    gb_per_core=4.75)
    pop = traces.Population(seed=0)
    vms = pop.sample_vms(n_vms, 2 * 86400, seed=3, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.3)
    return vms, dec, cfg


def _stub_tpu(monkeypatch):
    """``get_sweep`` as it selects on a TPU: the platform stubbed, the
    kernel run by Pallas's interpreter, and a cache of its own."""
    monkeypatch.setattr(sweep_core, "_on_tpu", lambda mesh: True)
    monkeypatch.setattr(sweep_core, "build_pallas_sweep", functools.partial(
        sweep_core.build_pallas_sweep, interpret=True))
    monkeypatch.setattr(sweep_core, "_SWEEPS", {})


@pytest.fixture
def on_tpu(monkeypatch):
    _stub_tpu(monkeypatch)


def _priced():
    vms, dec, cfg = _engines()
    server = np.array([60.0, 140.0, 200.0])
    pool = np.array([0.0, 40.0, 300.0])
    batch = replay_engine.CompiledReplayBatch(
        [replay_engine.CompiledReplay(vms, dec, cfg)] * 2)
    stream = replay_engine.CompiledReplayStreamBatch(
        [replay_engine.CompiledReplayStream(vms, dec, cfg,
                                            max_events_per_shard=256)] * 2)
    rec = obs.Recorder()
    with obs.use_recorder(rec):
        rates = (batch.reject_rates(server, pool),
                 stream.reject_rates(server, pool, skip_windows=False))
    return rates, rec.metrics()


def test_cpu_keeps_the_scan_and_tpu_selects_the_kernel(monkeypatch):
    """On the CPU every dispatch is the scan's; with the platform stubbed
    to a TPU every dispatch is the Pallas kernel's, with the same
    results and a ``.pallas1`` jit-cache stem."""
    (b_cpu, s_cpu), m = _priced()
    dispatches = m["span.batch.compute.count"] \
        + m["span.stream.compute.count"]
    assert m["sweep.kernel.scan"] == dispatches > 2
    assert "sweep.kernel.pallas" not in m
    assert not any("pallas" in k for k in m if k.startswith("jit.sweep."))
    assert not any("pallas" in key for key in sweep_core.jit_cache_keys())

    _stub_tpu(monkeypatch)
    (b_tpu, s_tpu), m = _priced()
    assert m["sweep.kernel.pallas"] == dispatches
    assert "sweep.kernel.scan" not in m
    assert m["jit.sweep.int16.carry0.batched1.pallas1.miss"] == 1
    assert m["jit.sweep.int16.carry1.batched1.pallas1.miss"] == 1
    assert all(key[-1] == "pallas" for key in sweep_core.jit_cache_keys())
    assert b_tpu.tolist() == b_cpu.tolist()
    assert s_tpu.tolist() == s_cpu.tolist()
    assert b_cpu.max() > 0


@pytest.mark.parametrize("state_dtype", ["int16", "int32"])
def test_pallas_carry_variant_donates_its_state(on_tpu, state_dtype):
    """The carry variant ``get_sweep`` hands the streamed engines donates
    the packed state and returns it in the engines' shapes and dtypes."""
    np_dt = sweep_core.state_np_dtype(state_dtype)
    (evs, n_slots), k, width = _trace(0, 200), 2, 4
    evs = _stack([evs] * k)
    n_slots = sweep_core.pad_up(n_slots, sweep_core.SLOT_PAD)
    init = sweep_core.init_state(width, N_SERVERS, CORES, S_PAD, G_PAD,
                                 n_slots, np_dt, k=k)
    sgb, pgb = (np.stack([c] * k) for c in _lanes(width, np_dt, 0))
    group_of = (np.arange(S_PAD) % N_SERVERS // SPG).astype(np.int32)
    sweep = sweep_core.get_sweep(state_dtype, with_carry=True, batched=True)
    text = sweep.lower(evs, group_of, *init, sgb, pgb).as_text()
    assert text.count("tf.aliasing_output") + text.count(
        "jax.buffer_donor") == 5
    out = sweep(evs, group_of, *(jnp.asarray(a) for a in init), sgb, pgb)
    for a, b in zip(out, init):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert out[4].dtype == jnp.int32


def test_cached_sweep_counts_times_and_lowers(monkeypatch):
    """One wrapper on a cached plain sweep: it counts every dispatch
    while a recorder is live (a sweep built untraced included), times
    the first call as the jit stem's ``.lower`` span, and still hands
    out the jitted function's ``lower``."""
    monkeypatch.setattr(sweep_core, "_SWEEPS", {})
    (evs, n_slots), width = _trace(1, 120), 4
    n_slots = sweep_core.pad_up(n_slots, sweep_core.SLOT_PAD)
    init = sweep_core.init_state(width, N_SERVERS, CORES, S_PAD, G_PAD,
                                 n_slots, np.int32)
    sgb, pgb = _lanes(width, np.int32, 0)
    group_of = (np.arange(S_PAD) % N_SERVERS // SPG).astype(np.int32)
    args = (tuple(evs), group_of, *init[:4], sgb, pgb)
    traced, untraced = obs.Recorder(), obs.Recorder()
    with obs.use_recorder(traced):
        sweep = sweep_core.get_sweep("int32")
        assert "sweep" in sweep.lower(*args).as_text()
        first = np.asarray(sweep(*args))
        assert sweep_core.get_sweep("int32") is sweep
        assert np.array_equal(np.asarray(sweep(*args)), first)
    m = traced.metrics()
    assert m["sweep.kernel.scan"] == 2
    assert m["span.jit.sweep.int32.carry0.batched0.lower.count"] == 1
    assert m["jit.sweep.int32.carry0.batched0.miss"] == 1
    sweep_core._SWEEPS.clear()
    plain = sweep_core.get_sweep("int32")        # built untraced
    plain(*args)
    with obs.use_recorder(untraced):
        assert np.array_equal(np.asarray(plain(*args)), first)
    m = untraced.metrics()
    assert m["sweep.kernel.scan"] == 1
    assert not any(k.endswith(".lower.count") for k in m)
