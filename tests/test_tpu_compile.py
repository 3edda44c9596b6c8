"""Ahead-of-time compiles for a TPU v5e chip, at the main path's shapes.

Each test lowers one jitted kernel on ``ShapeDtypeStruct`` arguments
placed on a described (not attached) v5e chip and compiles it with the
TPU compiler, so a kernel the chip's compiler would refuse fails here
at no chip time.  Nothing runs: these tests say nothing about results
or speed.  The topology is described inside a module fixture (never at
import time), and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sweep_core
from repro.kernels.paged_attention.kernel import paged_attention_kernel

# the main path of ``chip_smoke.py``: 256 servers in 32 pool groups, K=2
# traces streamed in 65,536-event shards, peak concurrency ~2,100 VM
# slots, 32 candidate lanes (the pool-search bucket)
K, EVENTS, SERVERS, GROUPS, SLOTS, LANES = 2, 65536, 256, 32, 2112, 32
PODS, FANOUT = 32, 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("state_dtype", ["int32", "int16"])
def test_batched_carry_sweep_compiles(one_chip, state_dtype):
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    s = lambda shape, d=dt: _shape(one_chip, shape, d)     # noqa: E731
    evs = tuple(s((K, EVENTS), jnp.int32) for _ in range(6))
    sweep = sweep_core.get_sweep(state_dtype, with_carry=True,
                                 batched=True)
    compiled = sweep.lower(
        evs, s((SERVERS,), jnp.int32), s((K, LANES, SERVERS)),
        s((K, LANES, SERVERS)), s((K, LANES, GROUPS)),
        s((K, SLOTS, LANES)), s((K, LANES), jnp.int32), s((K, LANES)),
        s((K, LANES))).compile()
    fc, _, _, slots, rej = compiled.out_info
    assert fc.shape == (K, LANES, SERVERS) and fc.dtype == dt
    assert slots.shape == (K, SLOTS, LANES)
    assert rej.shape == (K, LANES) and rej.dtype == jnp.int32


def test_batched_pod_sweep_compiles(one_chip):
    s = lambda shape, d=jnp.int32: _shape(one_chip, shape, d)  # noqa: E731
    evs = tuple(s((K, EVENTS)) for _ in range(6))
    sweep = sweep_core.get_pod_sweep("int32", batched=True)
    compiled = sweep.lower(
        evs, s((LANES, SERVERS, FANOUT)), s((LANES, SERVERS)),
        s((LANES, SERVERS)), s((LANES, PODS)), s((SLOTS, LANES)),
        s((SLOTS, LANES)), s((K, LANES)), s((K, LANES, PODS))).compile()
    assert compiled.out_info.shape == (K, LANES)


def test_paged_attention_compiles(one_chip):
    # decode step: 8 sequences of up to 1,024 tokens in 16-token pages,
    # 32 query heads over 8 KV heads (GQA group 4), head_dim 128
    b, hq, hkv, d, page, n_pages, per_seq = 8, 32, 8, 128, 16, 512, 64
    s = lambda shape, dtype: _shape(one_chip, shape, dtype)  # noqa: E731
    fn = jax.jit(lambda q, kp, vp, tbl, lens: paged_attention_kernel(
        q, kp, vp, tbl, lens, scale=d ** -0.5))
    compiled = fn.lower(
        s((b, hq, d), jnp.bfloat16),
        s((hkv, n_pages, page, d), jnp.bfloat16),
        s((hkv, n_pages, page, d), jnp.bfloat16),
        s((b, per_seq), jnp.int32), s((b,), jnp.int32)).compile()
    assert compiled.out_info.shape == (b, hq, d)
    assert "tpu_custom_call" in compiled.as_text()


# the search cells' unstreamed batch: K=2 3-day traces, ~37k events each
SEARCH_EVENTS, SEARCH_LANES = 37120, 16


@pytest.mark.parametrize("state_dtype", ["int32", "int16"])
def test_pallas_sweep_compiles(one_chip, state_dtype):
    """The Pallas sweep, as ``get_sweep`` selects it on a TPU: the
    streamed carry variant at the main path's shapes and the shared-init
    batch at the search cells'."""
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    s = lambda shape, d=dt: _shape(one_chip, shape, d)     # noqa: E731
    carry = jax.jit(sweep_core.build_pallas_sweep(state_dtype, True, True),
                    donate_argnums=sweep_core._CARRY_ARGNUMS)
    compiled = carry.lower(
        tuple(s((K, EVENTS), jnp.int32) for _ in range(6)),
        s((SERVERS,), jnp.int32), s((K, LANES, SERVERS)),
        s((K, LANES, SERVERS)), s((K, LANES, GROUPS)),
        s((K, SLOTS, LANES)), s((K, LANES), jnp.int32), s((K, LANES)),
        s((K, LANES))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    fc, um, up, slots, rej = compiled.out_info
    assert fc.shape == um.shape == (K, LANES, SERVERS) and fc.dtype == dt
    assert up.shape == (K, LANES, GROUPS) and up.dtype == dt
    assert slots.shape == (K, SLOTS, LANES) and slots.dtype == dt
    assert rej.shape == (K, LANES) and rej.dtype == jnp.int32
    batch = jax.jit(sweep_core.build_pallas_sweep(state_dtype, False, True))
    compiled = batch.lower(
        tuple(s((K, SEARCH_EVENTS), jnp.int32) for _ in range(6)),
        s((SERVERS,), jnp.int32), s((SEARCH_LANES, SERVERS)),
        s((SEARCH_LANES, SERVERS)), s((SEARCH_LANES, GROUPS)),
        s((SLOTS, SEARCH_LANES)), s((K, SEARCH_LANES)),
        s((K, SEARCH_LANES))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (K, SEARCH_LANES)


def test_sharded_sweep_selects_pallas_on_tpu_mesh(topo):
    """A mesh of TPU devices selects the Pallas kernel inside the
    ``shard_map``: four traces, one per chip of the 2x2 host."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
        rows, rep = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
        k = 4

        def s(shape, d=jnp.int32, sh=rows):
            return jax.ShapeDtypeStruct(shape, d, sharding=sh)
        sweep = sweep_core.get_sweep("int32", with_carry=True, batched=True,
                                     mesh=mesh, shard_axis="trace")
        compiled = sweep.lower(
            tuple(s((k, EVENTS)) for _ in range(6)),
            s((SERVERS,), sh=rep), s((k, LANES, SERVERS)),
            s((k, LANES, SERVERS)), s((k, LANES, GROUPS)),
            s((k, SLOTS, LANES)), s((k, LANES)), s((k, LANES)),
            s((k, LANES))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert "tpu_custom_call" in compiled.as_text()
    assert any(key[-1] == "pallas" for key in sweep_core.jit_cache_keys())


@pytest.mark.parametrize("batched", [False, True])
def test_lane_sharded_sweep_selects_pallas_on_tpu_mesh(topo, batched):
    """The candidate-lane plan on the 2x2 host: events replicated, the
    lanes split four ways, the Pallas kernel inside the ``shard_map`` —
    the unbatched carried shard sweep and the shared-init batch a search
    of K=2 traces runs on four chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
        rep = NamedSharding(mesh, P())
        lead = (K,) if batched else ()
        lanes = NamedSharding(mesh, P(*(None,) * len(lead), "shard"))
        cols = NamedSharding(mesh, P(*(None,) * (len(lead) + 1), "shard"))

        def s(shape, sh, d=jnp.int32):
            return jax.ShapeDtypeStruct(shape, d, sharding=sh)
        sweep = sweep_core.get_sweep("int32", with_carry=not batched,
                                     batched=batched, mesh=mesh,
                                     shard_axis="lane")
        evs = tuple(s(lead + (EVENTS,), rep) for _ in range(6))
        if batched:        # the initial state has no trace axis
            init = (s((LANES, SERVERS), NamedSharding(mesh, P("shard"))),
                    s((LANES, SERVERS), NamedSharding(mesh, P("shard"))),
                    s((LANES, GROUPS), NamedSharding(mesh, P("shard"))),
                    s((SLOTS, LANES), NamedSharding(mesh, P(None, "shard"))))
        else:
            init = (s((LANES, SERVERS), lanes), s((LANES, SERVERS), lanes),
                    s((LANES, GROUPS), lanes), s((SLOTS, LANES), cols),
                    s((LANES,), lanes))
        compiled = sweep.lower(
            evs, s((SERVERS,), rep), *init, s(lead + (LANES,), lanes),
            s(lead + (LANES,), lanes)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    rej = out if batched else out[4]
    assert rej.shape == lead + (LANES,) and rej.dtype == jnp.int32
    assert any(key[-2:] == ("lane", "pallas")
               for key in sweep_core.jit_cache_keys())


def test_single_stream_lane_plan_compiles(topo):
    """What one stream runs with ``devices=4`` (``chip_smoke.py --chips
    4``'s lane plan): the batched carry sweep of a K=1 stream batch, its
    candidate lanes split four ways, events replicated."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
        rep = NamedSharding(mesh, P())
        lanes = NamedSharding(mesh, P(None, "shard"))
        slots = NamedSharding(mesh, P(None, None, "shard"))

        def s(shape, sh, d=jnp.int32):
            return jax.ShapeDtypeStruct(shape, d, sharding=sh)
        sweep = sweep_core.get_sweep("int32", with_carry=True, batched=True,
                                     mesh=mesh, shard_axis="lane")
        compiled = sweep.lower(
            tuple(s((1, EVENTS), rep) for _ in range(6)),
            s((SERVERS,), rep), s((1, LANES, SERVERS), lanes),
            s((1, LANES, SERVERS), lanes), s((1, LANES, GROUPS), lanes),
            s((1, SLOTS, LANES), slots), s((1, LANES), lanes),
            s((1, LANES), lanes), s((1, LANES), lanes)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    assert "tpu_custom_call" in compiled.as_text()
    rej = compiled.out_info[4]
    assert rej.shape == (1, LANES) and rej.dtype == jnp.int32
