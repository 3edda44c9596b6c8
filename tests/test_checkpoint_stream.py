"""Checkpoint/resume for the streaming sweeps: kill-at-shard-k chaos.

The contract: a sweep killed after k shard sweeps (the
``CheckpointSpec.kill_after_shards`` chaos hook simulates preemption)
and resumed from its on-disk snapshot returns BIT-IDENTICAL rates to
the uninterrupted sweep — both sweep paths, both state dtypes, single
stream and batched.  The sweep is chosen by the input: ``"jax"`` cases
stream integral-GB decisions (the XLA carry sweep), ``"numpy"`` cases
the same decisions with 0.5 GB more in the pool (the float64 shard
sweep).
"""
import numpy as np
import pytest

from _fractional import shift_half
from repro.core import cluster_sim, replay_engine, traces

CFG = cluster_sim.ClusterConfig(n_servers=8, pool_sockets=8,
                                gb_per_core=4.0)
_SERVER = np.array([768.0, 200.0, 140.0, 96.0])
_POOL = np.array([512.0, 300.0, 100.0, 64.0])


def _stream(seed=3, horizon=2 * 86400, shard=256, sweep="jax"):
    pop = traces.Population(seed=0)
    n = cluster_sim.arrivals_for_util(CFG, 0.8, horizon)
    vms = pop.sample_vms(n, horizon, seed=seed, start_id=10 ** 6)
    dec, _ = cluster_sim.policy_decisions(vms, "static",
                                          static_pool_frac=0.25)
    if sweep == "numpy":              # non-integral: the numpy sweep
        dec = shift_half(dec)
    stream = replay_engine.CompiledReplayStream(
        vms, dec, CFG, max_events_per_shard=shard)
    assert stream._exact == (sweep == "jax")
    return stream


@pytest.mark.chaos
@pytest.mark.parametrize("sweep", ["jax", "numpy"])
@pytest.mark.parametrize("state_dtype", ["int32", "int16"])
def test_kill_at_shard_k_resume_bit_exact(tmp_path, sweep, state_dtype):
    # the numpy sweep carries float64 state: it leaves a forced
    # packing unused, and must resume bit-identically all the same
    stream = _stream(sweep=sweep)
    assert stream.n_shards >= 3
    baseline = stream.reject_rates(_SERVER, _POOL,
                                   state_dtype=state_dtype)
    path = str(tmp_path / "sweep.ckpt.npz")
    kill = replay_engine.CheckpointSpec(path, every_shards=1,
                                        kill_after_shards=2)
    with pytest.raises(replay_engine.SweepInterrupted):
        stream.reject_rates(_SERVER, _POOL, state_dtype=state_dtype,
                            checkpoint=kill)
    assert (tmp_path / "sweep.ckpt.npz").exists()
    resume = replay_engine.CheckpointSpec(path, every_shards=4,
                                          resume=True)
    rates = stream.reject_rates(_SERVER, _POOL, state_dtype=state_dtype,
                                checkpoint=resume)
    assert rates.tolist() == baseline.tolist()
    # a completed sweep removes its checkpoint
    assert not (tmp_path / "sweep.ckpt.npz").exists()


@pytest.mark.chaos
def test_kill_resume_mid_candidate_chunks(tmp_path):
    """Kill deep enough that whole candidate chunks completed before
    the interrupt: resumed counts for finished chunks come from the
    snapshot, not recomputation."""
    from repro.core import sweep_core
    stream = _stream()
    n_cand = sweep_core.JAX_CHUNK + 4    # forces two candidate chunks
    server = np.linspace(120.0, 760.0, n_cand)
    pool = np.full(n_cand, 300.0)
    baseline = stream.reject_rates(server, pool)
    path = str(tmp_path / "chunks.ckpt.npz")
    kill_at = stream.n_shards + 2    # chunk 0 done, chunk 1 underway
    with pytest.raises(replay_engine.SweepInterrupted):
        stream.reject_rates(
            server, pool,
            checkpoint=replay_engine.CheckpointSpec(
                path, every_shards=1, kill_after_shards=kill_at))
    rates = stream.reject_rates(
        server, pool,
        checkpoint=replay_engine.CheckpointSpec(path, resume=True))
    assert rates.tolist() == baseline.tolist()


@pytest.mark.chaos
@pytest.mark.parametrize("sweep", ["jax", "numpy"])
def test_batch_kill_resume_bit_exact(tmp_path, sweep):
    streams = [_stream(seed=s, sweep=sweep) for s in (3, 4)]
    batch = replay_engine.CompiledReplayStreamBatch(streams)
    baseline = batch.reject_rates(_SERVER, _POOL)
    path = str(tmp_path / "batch.ckpt.npz")
    with pytest.raises(replay_engine.SweepInterrupted):
        batch.reject_rates(
            _SERVER, _POOL,
            checkpoint=replay_engine.CheckpointSpec(
                path, every_shards=1, kill_after_shards=2))
    rates = batch.reject_rates(
        _SERVER, _POOL,
        checkpoint=replay_engine.CheckpointSpec(path, resume=True))
    assert rates.tolist() == baseline.tolist()


def test_fingerprint_mismatch_refuses_resume(tmp_path):
    stream = _stream()
    path = str(tmp_path / "fp.ckpt.npz")
    with pytest.raises(replay_engine.SweepInterrupted):
        stream.reject_rates(
            _SERVER, _POOL,
            checkpoint=replay_engine.CheckpointSpec(
                path, every_shards=1, kill_after_shards=1))
    with pytest.raises(ValueError, match="different sweep"):
        stream.reject_rates(
            _SERVER[:2], _POOL[:2],    # other candidates
            checkpoint=replay_engine.CheckpointSpec(path, resume=True))


def test_checkpoint_without_resume_is_plain_sweep(tmp_path):
    """A checkpointing sweep that runs to completion matches the plain
    sweep and leaves no checkpoint behind."""
    stream = _stream()
    baseline = stream.reject_rates(_SERVER, _POOL)
    path = str(tmp_path / "plain.ckpt.npz")
    rates = stream.reject_rates(
        _SERVER, _POOL,
        checkpoint=replay_engine.CheckpointSpec(path, every_shards=2))
    assert rates.tolist() == baseline.tolist()
    assert not (tmp_path / "plain.ckpt.npz").exists()


def test_invariant_guard_clean_on_healthy_sweep(tmp_path, monkeypatch):
    """POND_DEBUG_INVARIANTS=1 verifies carry + event tensors per shard
    without changing results on a healthy trace."""
    monkeypatch.setenv("POND_DEBUG_INVARIANTS", "1")
    stream, frac = _stream(), _stream(sweep="numpy")
    jx = stream.reject_rates(_SERVER, _POOL)
    nq = frac.reject_rates(_SERVER, _POOL)
    monkeypatch.delenv("POND_DEBUG_INVARIANTS")
    assert jx.tolist() == stream.reject_rates(_SERVER, _POOL).tolist()
    assert nq.tolist() == frac.reject_rates(_SERVER, _POOL).tolist()


def test_invariant_guard_catches_corrupt_events():
    from repro.core import sweep_core
    stream = _stream()
    stream._shards[0]["kind"][3] = 99
    with pytest.raises(sweep_core.SweepInvariantError,
                       match="kind out of range") as ei:
        stream._debug_check_events()
    assert ei.value.shard == 0 and ei.value.lane == 3
