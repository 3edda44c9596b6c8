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
