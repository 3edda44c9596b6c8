"""Shared sweep core for the event-compiled replay engines.

Every replay engine in ``core/replay_engine.py`` — ``CompiledReplay``
(one trace), ``CompiledReplayBatch`` (K traces, one vmapped scan),
``CompiledReplayStream`` (out-of-core shards, carried state) and
``CompiledReplayStreamBatch`` (K streams, batched carry) — prices
``(server_gb, pool_gb)`` candidates with the SAME integer event-step
kernel.  This module is that kernel plus everything the engines share
around it, so the engine classes stay thin orchestration layers:

* **The dtype-parametric event-step kernel** (:func:`build_sweep`):
  one ``lax.scan`` body covering arrivals (best-fit-by-cores with
  per-group pool checks and the all-local fallback), departures and
  QoS migrations, parametric over the packed state dtype (int32 or
  int16) and over whether the packed state is returned as a carry
  (the streaming variant) or consumed whole (the monolithic variant).

* **The same step as one Pallas TPU kernel** (:func:`build_pallas_sweep`):
  on TPU devices every variant runs a call's whole event loop in one
  kernel with the state in VMEM, bit for bit the scan's results; the
  scan stays the CPU path and the kernel's reference.

* **A single keyed jit cache** (:func:`get_sweep`): jitted sweeps are
  cached by ``(state_dtype, with_carry, batched)``.  This replaces the
  old ``_JAX_SWEEPS`` dict + ``_JAX_BATCH_SWEEP`` module globals —
  the batch global ignored the state dtype, so batched sweeps always
  ran int32 even when int16 packing applied (fixed here; regression
  test in ``tests/test_sweep_core.py``).  Carry variants are jitted
  with **donated carry arguments**: the shard-to-shard state buffers
  are reused in place on backends that support donation, so the carry
  stays device-resident instead of round-tripping through fresh
  allocations.

* **int16/int32 packing rules** (:func:`pick_state_dtype`): the carry
  packs to int16 — half the sweep's memory traffic — exactly when no
  intermediate can overflow: candidate capacity plus per-VM payload
  headroom within :data:`I16_SAFE`, the best-fit score sentinel above
  every free-cores value, packed slot values in range, and (for
  MIGRATE-bearing traces) the compiled migrate-event pool total
  bounding the fallback-migrate used-pool deficit.

* **Padding buckets** (:func:`bucket_width`, :func:`candidate_chunks`,
  :func:`pad_up`): candidate batches pad to fixed widths
  (2/4/16/32/96), event streams to multiples of 256, server/group
  columns to multiples of 16 and placement slots to multiples of 32,
  so XLA recompiles are rare.

* **Carry pack/unpack** (:func:`init_state`, :func:`lane_capacities`,
  :func:`quantize_capacities`, :func:`assign_slots`): building the
  packed all-free initial state (optionally with a leading trace
  axis for the batched engines), quantizing candidate capacities to
  the int sweep's domain, filling padded candidate lanes, and mapping
  VMs to reusable placement slots sized by peak concurrency.

* **Explicit device placement** (:func:`device_put`): shard event
  tensors and carry state are placed with ``jax.device_put`` so the
  identical code path runs on CPU, GPU or TPU — on accelerators the
  event shards upload one at a time and the carry never leaves the
  device, which is what keeps peak memory bounded by one shard
  (batch) regardless of trace length.

The kernel is bit-exact with respect to the scalar float64 oracle
(``cluster_sim.replay_reject_rate``) because every VM memory quantity
is an integral GB: admission tests like ``free_mem >= local_gb`` are
exactly ``used_mem + local_gb <= floor(server_gb)`` over int32 (see
``docs/replay_engine.md``).
"""
from __future__ import annotations

import os

import numpy as np

from repro.core import obs

ARRIVE, DEPART, MIGRATE = 0, 1, 2
PAD = 3               # no-op event kind used to pad the XLA event stream
FAIL, RECOVER = 4, 5  # failure-domain events (EMC/pod blast radius, §4.2);
# no-ops in the plain sweep, resolved in-scan by the failure sweep
# (:func:`build_fail_sweep`).  Sort AFTER same-time VM events: a VM
# departing at the instant of the failure has already left.
JAX_CHUNK = 96        # max candidate bucket per compiled sweep
BUCKETS = (2, 4, 16, 32, JAX_CHUNK)   # padded candidate widths (lazy
# compiles, one per width actually used; the small buckets matter for
# narrow probe batches — bracket checks and final-rate evaluations are
# fixed-cost-dominated per sweep, so padding 1-2 probes to 16 lanes
# would waste most of the sweep)
EVENT_PAD = 256       # event-stream pad granularity
EVENT_BLOCK = 512     # events per SMEM block of the Pallas sweep
LANE_PAD = 16         # server/group column pad granularity
SLOT_PAD = 32         # placement-slot pad granularity
I32_BIG = 1 << 30     # "infinite" capacity in the int32 sweep
I16_BIG = 1 << 14     # best-fit score sentinel in the int16 sweep
I16_SAFE = 30000      # int16 headroom bound: capacity + payload must fit


# --------------------------------------------------------------- jit cache --
_JAX_OK = None        # tri-state: None unknown, then True/False
_SWEEPS: dict = {}    # (state_dtype, with_carry, batched) -> jitted sweep


def _jit_key_name(family: str, state_dtype: str, **flags) -> str:
    """Counter-name stem for one jit-cache key, e.g.
    ``jit.sweep.int32.carry1.batched0`` — the cache accessors append
    ``.hit``/``.miss``; the keyed build/lower spans share the stem."""
    bits = [f"{k}{int(v)}" if isinstance(v, bool) else str(v)
            for k, v in flags.items()]
    return ".".join(["jit", family, state_dtype] + bits)


class _FirstCallTimer:
    """A cached jitted sweep: times its FIRST call — XLA tracing +
    lowering + compile all happen there — as a
    ``jit.<family>.<key>.lower`` span, and, given a ``counter``, counts
    every dispatch under it while a recorder is live.  One attribute
    hop; other attributes (``lower``, ...) are the jitted function's.
    The failure and pod sweeps get it only while a recorder is live
    (cache misses with tracing disabled store the bare jitted fn);
    the plain sweep always, as ``sweep.kernel.pallas`` /
    ``sweep.kernel.scan`` counts its dispatches whenever tracing is
    on, a sweep built while it was off included."""
    __slots__ = ("fn", "name", "counter", "_first")

    def __init__(self, fn, name, counter=None):
        self.fn = fn
        self.name = name
        self.counter = counter
        self._first = True

    def __call__(self, *args):
        if self.counter is not None:
            rec = obs.get_recorder()
            if rec.enabled:
                rec.count(self.counter)
        if self._first:
            self._first = False
            with obs.get_recorder().span(self.name):
                return self.fn(*args)
        return self.fn(*args)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def jax_importable() -> bool:
    global _JAX_OK
    if _JAX_OK is None:
        try:
            import jax                               # noqa: F401
            _JAX_OK = True
        except Exception:                            # pragma: no cover
            _JAX_OK = False
    return _JAX_OK


def build_sweep(state_dtype: str = "int32", with_carry: bool = False):
    """Build the (unjitted) integer event-sweep function.

    Because every VM memory quantity is an integral GB, admission tests
    like ``free_mem >= local_gb`` are equivalent to
    ``used_mem + local_gb <= floor(server_gb)`` over int32 — so the whole
    sweep runs in int32 under JAX's default x32 config and still matches
    the float64 oracle bit-for-bit.  Placement state lives in a
    ``(n_slots, C)`` array (VMs are mapped to reusable slots sized by
    peak concurrency, far smaller than n_vms) updated with leading-axis
    dynamic_update_slice so the scan carry stays in place.

    ``state_dtype="int16"`` packs the carry (free cores, used local GB,
    used pool GB, placement slots) to int16, halving the sweep's memory
    traffic.  The int16 sweep is bit-equivalent to int32 whenever no
    intermediate can overflow; callers must check
    :func:`pick_state_dtype` (capacity + per-VM payload headroom within
    :data:`I16_SAFE`) before selecting it.  Candidate events stay int32
    and are cast inside the body; the reject counters stay int32 (a
    trace can reject more than 2^15 VMs).

    ``with_carry=True`` returns the shard variant used by the streaming
    engines: it takes AND returns the full packed state, so consecutive
    time-windowed shards thread the carry.

    The returned function is pure over jax arrays: :func:`get_sweep`
    jits it directly, or vmaps it over a leading trace axis first
    (``batched=True``) so K traces price their candidate batches in ONE
    ``lax.scan``.
    """
    import jax.numpy as jnp
    from jax import lax
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    big = jnp.asarray(I16_BIG if state_dtype == "int16" else I32_BIG, dt)
    zero = jnp.asarray(0, dt)

    def body(carry, ev):
        fc, um, up, slots, rejects, sgb, pgb, group_of = carry
        kind, sl, c, l, p, m = ev
        c, l, p, m = (c.astype(dt), l.astype(dt), p.astype(dt),
                      m.astype(dt))
        is_arr, is_dep, is_mig = kind == ARRIVE, kind == DEPART, \
            kind == MIGRATE
        val = slots[sl]                              # (C,) packed s*2+mig
        has = val >= 0
        s_cur = jnp.where(has, val >> 1, 0)
        mg_cur = has & ((val & 1) == 1)
        cols = jnp.arange(fc.shape[1], dtype=jnp.int32)
        gcols = jnp.arange(up.shape[1], dtype=jnp.int32)
        # admission: best fit by cores among servers with local memory
        # room and group pool room (same mask as the scalar oracle)
        upg = up[:, group_of]
        ok = (fc >= c) & (um + l <= sgb[:, None]) & (upg + p <= pgb[:, None])
        score = jnp.where(ok, fc, big)
        s1 = jnp.argmin(score, 1).astype(jnp.int32)
        feas1 = jnp.take_along_axis(score, s1[:, None], 1)[:, 0] < big
        # pool short -> control-plane fallback: start the VM all-local
        ok2 = (fc >= c) & (um + m <= sgb[:, None])
        score2 = jnp.where(ok2, fc, big)
        s2 = jnp.argmin(score2, 1).astype(jnp.int32)
        feas2 = jnp.take_along_axis(score2, s2[:, None], 1)[:, 0] < big
        sel = jnp.where(feas1, s1, s2)
        place = feas1 | feas2
        s_aff = jnp.where(is_arr, sel, s_cur)
        act_arr = is_arr & place
        act_dep = is_dep & has
        um_s = jnp.take_along_axis(um, s_aff[:, None], 1)[:, 0]
        act_mig = is_mig & has & (um_s + p <= sgb)   # QoS: pool -> local
        oh = cols[None, :] == s_aff[:, None]
        dfc = jnp.where(act_dep, c, zero) - jnp.where(act_arr, c, zero)
        dum = (jnp.where(act_arr, jnp.where(feas1, l, m), zero)
               - jnp.where(act_dep, jnp.where(mg_cur, m, l), zero)
               + jnp.where(act_mig, p, zero))
        g_aff = group_of[s_aff]
        goh = gcols[None, :] == g_aff[:, None]
        dup = (jnp.where(act_arr & feas1, p, zero)
               - jnp.where(act_dep & ~mg_cur, p, zero)
               - jnp.where(act_mig, p, zero))
        fc = fc + oh * dfc[:, None]
        um = um + oh * dum[:, None]
        up = up + goh * dup[:, None]
        aval = jnp.where(place, sel * 2 + jnp.where(feas1, 0, 1), -1)
        new_val = jnp.where(is_arr, aval,
                            jnp.where(is_dep, -1,
                                      jnp.where(act_mig, val | 1, val)))
        slots = lax.dynamic_update_index_in_dim(
            slots, new_val.astype(slots.dtype), sl, 0)
        rejects = rejects + (is_arr & ~feas1 & ~feas2)
        return (fc, um, up, slots, rejects, sgb, pgb, group_of), None

    def sweep_carry(evs, group_of, fc0, um0, up0, slots0, rej0, sgb, pgb):
        init = (fc0, um0, up0, slots0, rej0, sgb, pgb, group_of)
        out, _ = lax.scan(body, init, evs)
        return out[0], out[1], out[2], out[3], out[4]

    def sweep(evs, group_of, fc0, um0, up0, slots0, sgb, pgb):
        init = (fc0, um0, up0, slots0,
                jnp.zeros(sgb.shape[0], jnp.int32), sgb, pgb, group_of)
        out, _ = lax.scan(body, init, evs)
        return out[4]

    return sweep_carry if with_carry else sweep


def build_pallas_sweep(state_dtype: str = "int32", with_carry: bool = False,
                       batched: bool = False, *, interpret: bool = False):
    """The sweep as one Pallas TPU kernel per call: :func:`build_sweep`'s
    step, bit for bit, with the whole event loop on the chip.

    :func:`build_sweep`'s ``lax.scan`` issues a dozen tiny XLA ops per
    event.  Here one kernel holds the packed state in VMEM from the
    first event to the last, in int32 whatever ``state_dtype`` is
    (:func:`pick_state_dtype` already rules out int16 overflow, so the
    results are identical):

    * **Layout.** Servers on sublanes, candidate lanes on the 128-wide
      lane axis, padded to a multiple of 128: free cores and used local
      GB ``(S, L)``, used pool GB per group ``(G, L)``, the slot array
      ``(n_slots, L)``, whose row at an event's slot lines up with the
      state's lanes.  The best fit is a min over sublanes, then the
      first server that reaches it (``jnp.argmin``'s tie-break).
    * **Group pool check.** A per-server view of the group pool use
      ``(S, L)``, updated through the mask ``group_of == g`` beside the
      per-group rows, so no step gathers.
    * **Events** stream through SMEM in blocks of :data:`EVENT_BLOCK`
      on the grid's last, sequential axis; the state loads at a trace's
      first block and is written out after its last.  PAD, FAIL and
      RECOVER events touch nothing (nor does the tail that pads the
      stream to whole blocks).
    * **Traces** are the grid's first axis: K rows of events and
      capacities, and either K carried states or one shared initial
      state.

    The returned function takes and returns exactly what
    :func:`get_sweep`'s ``(state_dtype, with_carry, batched)`` variant
    does, in the same dtypes and layouts; the transposes and casts at
    its boundary run once per call.  ``interpret=True`` runs the kernel
    in Pallas's interpreter (CPU tests).
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    big = state_sentinel(state_dtype)
    i32 = jnp.int32
    blk_n = EVENT_BLOCK

    def kernel(ev, sidx_ref, gidx_ref, sgb_ref, pgb_ref, fc_in, um_in,
               up_in, ups_in, slots_in, rej_in, fc, um, up, slots, rej, ups,
               *, shared):
        k0 = 0 if shared else pl.program_id(0)

        @pl.when(pl.program_id(1) == 0)
        def _load():
            for src, dst in ((fc_in, fc), (um_in, um), (up_in, up),
                             (ups_in, ups), (slots_in, slots),
                             (rej_in, rej)):
                pltpu.sync_copy(src.at[pl.ds(k0, 1)], dst)

        fc, um, up, ups, slots, rej = (r.at[0] for r in
                                       (fc, um, up, ups, slots, rej))
        sgb, pgb = sgb_ref[0], pgb_ref[0]
        # server and pool-group index of each state element, loaded once
        sidx, gidx = sidx_ref[...], gidx_ref[...]
        n_srv = sidx.shape[0]

        def best_fit(ok, f):
            score = jnp.where(ok, f, big)
            lo = jnp.min(score, axis=0, keepdims=True)
            first = jnp.min(jnp.where(score == lo, sidx, n_srv), axis=0,
                            keepdims=True)
            return first, lo < big

        def the(oh, a):            # a at each lane's server oh, or 0
            return jnp.sum(jnp.where(oh, a, 0), axis=0, keepdims=True)

        def add_pool(oh, dp):      # dp GB to the pool group of server oh
            g = the(oh, gidx)
            grows = lax.broadcasted_iota(i32, up.shape, 0)
            up[...] += jnp.where(grows == g, dp, 0)
            ups[...] += jnp.where(gidx == g, dp, 0)

        def step(i, carry):
            kind, sl = ev[i], ev[blk_n + i]
            c, l = ev[2 * blk_n + i], ev[3 * blk_n + i]
            p, m = ev[4 * blk_n + i], ev[5 * blk_n + i]
            row = slots.at[pl.ds(sl, 1), :]

            @pl.when(kind == ARRIVE)
            def _arrive():
                # u + l <= sgb as u <= sgb - l: one row, not the state
                f, u = fc[...], um[...]
                fits = f >= c
                s1, feas1 = best_fit(
                    fits & (u <= sgb - l) & (ups[...] <= pgb - p), f)
                # pool short -> control-plane fallback: all-local
                s2, feas2 = best_fit(fits & (u <= sgb - m), f)
                sel = jnp.where(feas1, s1, s2)
                place = feas1 | feas2
                oh = (sidx == sel) & place
                fc[...] = f - jnp.where(oh, c, 0)
                um[...] = u + jnp.where(oh, jnp.where(feas1, l, m), 0)
                add_pool(oh, jnp.where(place & feas1, p, 0))
                row[...] = jnp.where(place, sel * 2 + jnp.where(feas1, 0, 1),
                                     -1)
                rej[...] += jnp.where(place, 0, 1)

            @pl.when(kind == DEPART)
            def _depart():
                val = row[...]
                has = val >= 0
                mg = (val & 1) == 1
                oh = (sidx == (val >> 1)) & has
                fc[...] += jnp.where(oh, c, 0)
                um[...] -= jnp.where(oh, jnp.where(mg, m, l), 0)
                add_pool(oh, jnp.where(has & ~mg, -p, 0))
                row[...] = jnp.full(val.shape, -1, i32)

            @pl.when(kind == MIGRATE)            # QoS: pool -> local
            def _migrate():
                val = row[...]
                has = val >= 0
                oh = (sidx == (val >> 1)) & has
                u = um[...]
                act = has & (the(oh, u) <= sgb - p)
                um[...] = u + jnp.where(oh & act, p, 0)
                add_pool(oh & act, jnp.where(act, -p, 0))
                row[...] = jnp.where(act, val | 1, val)
            return carry

        lax.fori_loop(0, blk_n, step, 0)

    def call(evs, group_of, fc0, um0, up0, slots0, rej0, sgb, pgb):
        """Events ``(K, E)``, state with a leading axis of K or 1
        (shared), capacities ``(K, C)``; returns the K final states."""
        k, n_ev = evs[0].shape
        ks, c, s = fc0.shape
        g, n_slots = up0.shape[2], slots0.shape[1]
        width = pad_up(c, 128)
        n_blk = -(-n_ev // blk_n)
        ev = jnp.stack([e.astype(i32) for e in evs], 1)       # (K, 6, E)
        tail = jnp.broadcast_to(
            jnp.array([PAD, 0, 0, 0, 0, 0], i32)[None, :, None],
            (k, 6, n_blk * blk_n - n_ev))
        ev = jnp.concatenate([ev, tail], 2).reshape(k, 6, n_blk, blk_n)
        ev = ev.transpose(0, 2, 1, 3).reshape(-1)   # block-major, 1-D

        def pad_lanes(a, fill=0):       # int32, last axis padded to L
            return jnp.pad(a.astype(i32),
                           [(0, 0)] * (a.ndim - 1) + [(0, width - c)],
                           constant_values=fill)

        def lanes_last(a):              # (.., C, X) -> (.., X, L)
            return pad_lanes(jnp.swapaxes(a, -1, -2))

        state = (lanes_last(fc0), lanes_last(um0), lanes_last(up0),
                 lanes_last(up0[:, :, group_of]), pad_lanes(slots0, -1),
                 pad_lanes(rej0)[:, None])
        caps = [pad_lanes(a)[:, None] for a in (sgb, pgb)]
        sidx = lax.broadcasted_iota(i32, (s, width), 0)
        gidx = jnp.broadcast_to(group_of.astype(i32)[:, None], (s, width))

        def block(shape):
            return pl.BlockSpec((1,) + shape, lambda i, j: (i, 0, 0))
        shapes = ((s, width), (s, width), (g, width), (n_slots, width),
                  (1, width))
        rows = sum(n for n, _ in shapes)
        # double-buffered outputs, the pool view, capacities, server and
        # group indices
        vmem = 4 * width * (2 * rows + 5 * s + 32)
        whole = pl.BlockSpec((s, width), lambda i, j: (0, 0))
        out = pl.pallas_call(
            functools.partial(kernel, shared=ks == 1),
            grid=(k, n_blk),
            in_specs=[pl.BlockSpec((6 * blk_n,),
                                   lambda i, j: (i * n_blk + j,),
                                   memory_space=pltpu.SMEM),
                      whole, whole, block((1, width)), block((1, width))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 6,
            out_specs=[block(sh) for sh in shapes],
            out_shape=[jax.ShapeDtypeStruct((k,) + sh, i32)
                       for sh in shapes],
            scratch_shapes=[pltpu.VMEM((1, s, width), i32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=max(16 << 20, vmem + (4 << 20))),
            # a carried state is written back over the buffer it came in
            input_output_aliases={5: 0, 6: 1, 7: 2, 9: 3, 10: 4}
            if ks == k else {},
            interpret=interpret,
        )(ev, sidx, gidx, *caps, *state)
        fc, um, up, sl, rej = (a[..., :c] for a in out)
        return (jnp.swapaxes(fc, 1, 2).astype(dt),
                jnp.swapaxes(um, 1, 2).astype(dt),
                jnp.swapaxes(up, 1, 2).astype(dt), sl.astype(dt),
                rej[:, 0])

    def lead(*a):                      # a leading trace axis of 1
        return jax.tree.map(lambda x: x[None], a)

    if with_carry:
        def sweep_carry(evs, group_of, fc0, um0, up0, slots0, rej0, sgb,
                        pgb):
            state = (fc0, um0, up0, slots0, rej0, sgb, pgb)
            if batched:
                return call(evs, group_of, *state)
            out = call(*lead(evs), group_of, *lead(*state))
            return tuple(a[0] for a in out)
        return sweep_carry

    def sweep(evs, group_of, fc0, um0, up0, slots0, sgb, pgb):
        # the initial state is shared by every trace: one leading row
        init = lead(fc0, um0, up0, slots0)
        if not batched:
            evs, sgb, pgb = lead(evs, sgb, pgb)
        rej0 = jnp.zeros((1, sgb.shape[1]), i32)
        out = call(evs, group_of, *init, rej0, sgb, pgb)[4]
        return out if batched else out[0]
    return sweep


#: positions of the packed carry in the ``with_carry`` sweep signature
#: ``(evs, group_of, fc0, um0, up0, slots0, rej0, sgb, pgb)`` — donated
#: so the shard-to-shard state is reused in place (device-resident)
_CARRY_ARGNUMS = (2, 3, 4, 5, 6)


def get_sweep(state_dtype: str = "int32", *, with_carry: bool = False,
              batched: bool = False, mesh=None,
              shard_axis: str = "trace"):
    """Jitted sweep from the keyed cache, or None when jax is missing.

    ONE cache keyed by ``(state_dtype, with_carry, batched)`` serves
    every engine — compiled lazily, one jit per key actually used:

    * ``(dt, False, False)`` — monolithic single-trace sweep
      (``CompiledReplay``).
    * ``(dt, True, False)`` — shard sweep with carried state
      (``CompiledReplayStream``); carry args donated.
    * ``(dt, False, True)`` — vmapped over a leading trace axis with a
      SHARED all-free initial state (``CompiledReplayBatch``): per-trace
      event streams and candidate capacities, one scan with a batched
      carry for K traces.
    * ``(dt, True, True)`` — vmapped shard sweep with a PER-TRACE carry
      (``CompiledReplayStreamBatch``): K streams thread one batched
      carry shard-to-shard; carry args donated.

    With ``mesh`` set (a 1-D :func:`shard_mesh`), the (possibly
    vmapped) sweep is additionally wrapped in ``shard_map`` over the
    mesh's ``"shard"`` axis before jitting — partitioning either the
    leading trace axis (``shard_axis="trace"``: per-device slices of
    the K event rows, capacities and carry) or the candidate-lane axis
    (``shard_axis="lane"``: events replicated, state lanes split).
    Lanes and trace rows replay independently (the best-fit argmin
    runs over the never-sharded server axis), so sharded sweeps are
    bit-exact vs the single-device jit; sharded variants get their own
    cache keys (``(..., device_ids, axis)``).

    On TPU devices (the mesh's, or JAX's default device) every variant
    is the Pallas kernel of :func:`build_pallas_sweep` instead of the
    ``lax.scan``, with the same signature, results and donation; its
    keys end in ``"pallas"`` and its jit-cache stems in ``.pallas1``.
    Each dispatch counts ``sweep.kernel.pallas`` or ``sweep.kernel.scan``.
    """
    if not jax_importable():
        return None
    pallas = _on_tpu(mesh)
    if mesh is None:
        key = (state_dtype, with_carry, batched)
        flags = dict(carry=with_carry, batched=batched)
    else:
        key = (state_dtype, with_carry, batched, _mesh_key(mesh),
               shard_axis)
        flags = dict(carry=with_carry, batched=batched,
                     mesh=f"{shard_axis}{mesh.size}")
    if pallas:
        key += ("pallas",)
        flags["pallas"] = True
    fn = _SWEEPS.get(key)
    rec = obs.get_recorder()
    if fn is None:
        import jax
        stem = _jit_key_name("sweep", state_dtype, **flags)
        if rec.enabled:
            rec.count(stem + ".miss")
        with rec.span(stem + ".build"):
            if pallas:            # the kernel's grid holds the traces
                base = build_pallas_sweep(state_dtype, with_carry, batched)
            else:
                base = build_sweep(state_dtype, with_carry)
            if batched and with_carry and not pallas:
                base = jax.vmap(base, in_axes=((0, 0, 0, 0, 0, 0), None,
                                               0, 0, 0, 0, 0, 0, 0))
            elif batched and not pallas:
                base = jax.vmap(base,
                                in_axes=((0, 0, 0, 0, 0, 0), None,
                                         None, None, None, None, 0, 0))
            if mesh is not None:
                base = _shard(base, mesh, _plain_shard_specs(
                    jax.sharding.PartitionSpec, with_carry, batched,
                    shard_axis))
            fn = jax.jit(base, donate_argnums=_CARRY_ARGNUMS
                         if with_carry else ())
        fn = _FirstCallTimer(fn, stem + ".lower", "sweep.kernel."
                             + ("pallas" if pallas else "scan"))
        _SWEEPS[key] = fn
    elif rec.enabled:
        rec.count(_jit_key_name("sweep", state_dtype, **flags) + ".hit")
    return fn


def _on_tpu(mesh) -> bool:
    """Whether the sweep runs on TPU devices: the mesh's, or JAX's
    default device when there is no mesh."""
    import jax
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return dev.platform == "tpu"


def jit_cache_keys() -> list:
    """Keys compiled so far (introspection for tests/benchmarks)."""
    return sorted(_SWEEPS, key=repr)


# ------------------------------------------------------------ failure sweep --
_FAIL_SWEEPS: dict = {}   # (state_dtype, mitigation, batched, with_dist)

MITIGATIONS = ("remigrate", "kill")


def build_fail_sweep(state_dtype: str = "int32",
                     mitigation: str = "remigrate",
                     with_dist: bool = True):
    """Build the (unjitted) failure-aware event sweep.

    Same integer admission/departure/migration semantics as
    :func:`build_sweep`, plus the Pond §4.2 failure model resolved
    inside the scan step:

    * Events carry two extra int32 streams: ``x`` (the VM's departure
      minute at ARRIVE; the failure minute at FAIL) and ``dmn`` (the
      failure domain at FAIL/RECOVER, -1 otherwise).  One failure
      domain per EMC group.
    * While a domain is down (between its FAIL and RECOVER) its pool
      capacity is offline: arrivals needing pool slices there fail the
      pooled admission test and take the all-local fallback (or
      reject), per §4.3.
    * ``FAIL(d)``: every live VM holding pool slices in domain ``d``
      is affected (the blast-radius rule).  ``mitigation="kill"``
      terminates them all; ``mitigation="remigrate"`` pulls each
      server's affected pool into host-local DRAM when the server's
      free local memory covers its TOTAL affected pool demand
      (all-or-nothing per server — the host either absorbs its pooled
      pages or loses those VMs), killing the rest.  Either way the
      domain's EMC slices are lost: its used-pool column resets to 0.
    * Availability counters ride in the carry per candidate lane:
      VMs affected, VMs killed, VMs remigrated, and VM-minutes lost
      (``departure_minute - failure_minute`` summed over kills, int32).
      With ``with_dist=True`` the scan also emits the per-event
      affected count (zeros off FAIL events), giving the
      VMs-affected-per-failure distribution.

    The blast-radius step scans the whole ``(n_slots, C)`` placement
    array at EVERY event, so this kernel costs ~O(n_slots) more per
    event than the plain sweep — use :func:`get_sweep` when no failure
    events are present.  Bit-exact against the scalar oracle
    ``cluster_sim.replay_with_failures`` for integral-GB traces
    (``tests/test_failures.py``).
    """
    if mitigation not in MITIGATIONS:
        raise ValueError(f"mitigation must be one of {MITIGATIONS}")
    import jax.numpy as jnp
    from jax import lax
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    big = jnp.asarray(I16_BIG if state_dtype == "int16" else I32_BIG, dt)
    zero = jnp.asarray(0, dt)
    remigrate = mitigation == "remigrate"

    def body(carry, ev):
        (fc, um, up, slots, rejects, slot_c, slot_l, slot_p, slot_dep,
         dom_down, affected, killed, remig, lost_min,
         sgb, pgb, group_of) = carry
        kind, sl, c, l, p, m, x, dmn = ev            # all int32
        ci, li, pi = c, l, p                         # int32 bookkeeping
        c, l, p, m = (c.astype(dt), l.astype(dt), p.astype(dt),
                      m.astype(dt))
        is_arr, is_dep, is_mig = kind == ARRIVE, kind == DEPART, \
            kind == MIGRATE
        is_fail, is_rec = kind == FAIL, kind == RECOVER
        val = slots[sl]                              # (C,) packed s*2+mig
        has = val >= 0
        s_cur = jnp.where(has, val >> 1, 0)
        mg_cur = has & ((val & 1) == 1)
        cols = jnp.arange(fc.shape[1], dtype=jnp.int32)
        gcols = jnp.arange(up.shape[1], dtype=jnp.int32)
        # admission as the plain sweep, plus: a down domain has no EMC
        # slices to grant, so pool-bearing arrivals skip its servers
        upg = up[:, group_of]
        dom_ok = (pi == 0) | (dom_down[group_of] == 0)[None, :]
        ok = ((fc >= c) & (um + l <= sgb[:, None])
              & (upg + p <= pgb[:, None]) & dom_ok)
        score = jnp.where(ok, fc, big)
        s1 = jnp.argmin(score, 1).astype(jnp.int32)
        feas1 = jnp.take_along_axis(score, s1[:, None], 1)[:, 0] < big
        ok2 = (fc >= c) & (um + m <= sgb[:, None])
        score2 = jnp.where(ok2, fc, big)
        s2 = jnp.argmin(score2, 1).astype(jnp.int32)
        feas2 = jnp.take_along_axis(score2, s2[:, None], 1)[:, 0] < big
        sel = jnp.where(feas1, s1, s2)
        place = feas1 | feas2
        s_aff = jnp.where(is_arr, sel, s_cur)
        act_arr = is_arr & place
        act_dep = is_dep & has
        um_s = jnp.take_along_axis(um, s_aff[:, None], 1)[:, 0]
        act_mig = is_mig & has & (um_s + p <= sgb)   # QoS: pool -> local
        oh = cols[None, :] == s_aff[:, None]
        dfc = jnp.where(act_dep, c, zero) - jnp.where(act_arr, c, zero)
        dum = (jnp.where(act_arr, jnp.where(feas1, l, m), zero)
               - jnp.where(act_dep, jnp.where(mg_cur, m, l), zero)
               + jnp.where(act_mig, p, zero))
        g_aff = group_of[s_aff]
        goh = gcols[None, :] == g_aff[:, None]
        dup = (jnp.where(act_arr & feas1, p, zero)
               - jnp.where(act_dep & ~mg_cur, p, zero)
               - jnp.where(act_mig, p, zero))
        fc = fc + oh * dfc[:, None]
        um = um + oh * dum[:, None]
        up = up + goh * dup[:, None]
        aval = jnp.where(place, sel * 2 + jnp.where(feas1, 0, 1), -1)
        new_val = jnp.where(is_arr, aval,
                            jnp.where(is_dep, -1,
                                      jnp.where(act_mig, val | 1, val)))
        slots = lax.dynamic_update_index_in_dim(
            slots, new_val.astype(slots.dtype), sl, 0)
        rejects = rejects + (is_arr & ~feas1 & ~feas2)
        # ARRIVE records the slot's payload — shared across lanes (slot
        # assignment is host-side, identical in every lane; lanes where
        # the VM was rejected keep val < 0 and never read it)
        slot_c = lax.dynamic_update_index_in_dim(
            slot_c, jnp.where(is_arr, ci, slot_c[sl]), sl, 0)
        slot_l = lax.dynamic_update_index_in_dim(
            slot_l, jnp.where(is_arr, li, slot_l[sl]), sl, 0)
        slot_p = lax.dynamic_update_index_in_dim(
            slot_p, jnp.where(is_arr, pi, slot_p[sl]), sl, 0)
        slot_dep = lax.dynamic_update_index_in_dim(
            slot_dep, jnp.where(is_arr, x, slot_dep[sl]), sl, 0)
        # ------- blast radius: whole-slot-array step (no-op off FAIL) --
        live = slots >= 0                            # (n_slots, C)
        srv = jnp.where(live, (slots >> 1).astype(jnp.int32), 0)
        pooled = live & ((slots & 1) == 0) & (slot_p[:, None] > 0)
        aff = is_fail & pooled & (group_of[srv] == dmn)
        lanes = jnp.arange(fc.shape[0], dtype=jnp.int32)[None, :]
        if remigrate:
            # all-or-nothing per server: total affected pool demand on
            # the server must fit its free local memory (checked in
            # int32 — per-server sums can exceed the int16 domain)
            demand = jnp.zeros(fc.shape, jnp.int32).at[lanes, srv].add(
                jnp.where(aff, slot_p[:, None], 0))
            fits = (um.astype(jnp.int32) + demand
                    <= sgb.astype(jnp.int32)[:, None])
            rem_mask = aff & fits[lanes, srv]
            kill_mask = aff & ~fits[lanes, srv]
        else:
            rem_mask = jnp.zeros_like(aff)
            kill_mask = aff
        dfc_f = jnp.zeros(fc.shape, jnp.int32).at[lanes, srv].add(
            jnp.where(kill_mask, slot_c[:, None], 0))
        dum_f = (jnp.zeros(fc.shape, jnp.int32).at[lanes, srv].add(
            jnp.where(rem_mask, slot_p[:, None], 0))
            - jnp.zeros(fc.shape, jnp.int32).at[lanes, srv].add(
                jnp.where(kill_mask, slot_l[:, None], 0)))
        fc = fc + dfc_f.astype(dt)
        um = um + dum_f.astype(dt)
        # the failed domain loses every slice: used pool resets to 0
        # (its pool comes back EMPTY at RECOVER)
        up = jnp.where(is_fail & (gcols == dmn)[None, :], zero, up)
        slots = jnp.where(kill_mask, jnp.asarray(-1, slots.dtype),
                          jnp.where(rem_mask, slots | 1, slots))
        dom_down = jnp.where((is_fail | is_rec) & (gcols == dmn),
                             jnp.where(is_fail, 1, 0), dom_down)
        n_aff = jnp.sum(aff, 0, dtype=jnp.int32)     # (C,)
        affected = affected + n_aff
        killed = killed + jnp.sum(kill_mask, 0, dtype=jnp.int32)
        remig = remig + jnp.sum(rem_mask, 0, dtype=jnp.int32)
        lost_min = lost_min + jnp.sum(
            jnp.where(kill_mask,
                      jnp.maximum(slot_dep - x, 0)[:, None], 0),
            0, dtype=jnp.int32)
        new_carry = (fc, um, up, slots, rejects, slot_c, slot_l, slot_p,
                     slot_dep, dom_down, affected, killed, remig,
                     lost_min, sgb, pgb, group_of)
        return new_carry, (n_aff if with_dist else None)

    def sweep(evs, group_of, fc0, um0, up0, slots0,
              slot_c0, slot_l0, slot_p0, slot_dep0, dom0, sgb, pgb):
        zc = jnp.zeros(sgb.shape[0], jnp.int32)
        init = (fc0, um0, up0, slots0, zc, slot_c0, slot_l0, slot_p0,
                slot_dep0, dom0, zc, zc, zc, zc, sgb, pgb, group_of)
        out, ys = lax.scan(body, init, evs)
        return (out[4], out[10], out[11], out[12], out[13],
                ys if with_dist else None)

    return sweep


def get_fail_sweep(state_dtype: str = "int32",
                   mitigation: str = "remigrate", *,
                   batched: bool = False, with_dist: bool = True):
    """Jitted failure sweep from the keyed cache (None without jax).

    Keyed by ``(state_dtype, mitigation, batched, with_dist)``; the
    batched variant vmaps over a leading trace axis — per-trace event
    streams (each with its own merged failure schedule), per-trace
    packed state, shared group map — so K (trace, schedule) rows price
    their candidate batches in ONE scan (the
    ``benchmarks/fig_availability.py`` frontier pass).
    """
    if not jax_importable():
        return None
    key = (state_dtype, mitigation, batched, with_dist)
    fn = _FAIL_SWEEPS.get(key)
    rec = obs.get_recorder()
    if fn is None:
        import jax
        stem = _jit_key_name("fail", state_dtype, mitigation=mitigation,
                             batched=batched, dist=with_dist)
        if rec.enabled:
            rec.count(stem + ".miss")
        with rec.span(stem + ".build"):
            base = build_fail_sweep(state_dtype, mitigation, with_dist)
            if batched:
                base = jax.vmap(base, in_axes=((0,) * 8, None,
                                               0, 0, 0, 0, 0, 0, 0, 0, 0,
                                               0, 0))
            fn = jax.jit(base)
        if rec.enabled:
            fn = _FirstCallTimer(fn, stem + ".lower")
        _FAIL_SWEEPS[key] = fn
    elif rec.enabled:
        rec.count(_jit_key_name("fail", state_dtype,
                                mitigation=mitigation, batched=batched,
                                dist=with_dist) + ".hit")
    return fn


def init_fail_state(n_slots: int, g_pad: int,
                    k: int | None = None) -> tuple:
    """All-empty failure-sweep extras: per-slot payload records
    (cores, local GB, pool GB, departure minute — int32, shared across
    candidate lanes) and the per-domain down flags.  With ``k`` set,
    every array gains a leading trace axis (batched variant)."""
    out = (np.zeros(n_slots, np.int32), np.zeros(n_slots, np.int32),
           np.zeros(n_slots, np.int32), np.zeros(n_slots, np.int32),
           np.zeros(g_pad, np.int32))
    if k is None:
        return out
    return tuple(np.broadcast_to(a, (k,) + a.shape).copy() for a in out)


# --------------------------------------------------------------- pod sweep --
_POD_SWEEPS: dict = {}   # (state_dtype, with_carry, batched) -> jitted


def build_pod_sweep(state_dtype: str = "int32",
                    with_carry: bool = False):
    """Build the (unjitted) multi-pod fleet event sweep.

    The pod generalization of :func:`build_sweep`: the per-group
    used-pool row becomes a per-POD vector ``up (C, P)`` and the single
    ``group_of`` map becomes a PER-LANE incidence tensor
    ``inc (C, S, F)`` — row ``(ci, s)`` lists the pods server ``s`` can
    reach in lane ``ci``'s topology, in preference order, ``-1``
    padded (see ``core/topology.py``).  Candidate lanes therefore
    carry ``(server_gb, per-pod pool_gb, topology)`` triples: one scan
    prices a whole topology grid.

    Semantics (the contract ``cluster_sim.replay_multi_pool``
    replicates in float64, bit-exact on integral-GB traces):

    * ARRIVE admits a server when cores + local memory fit AND
      (``pool_gb == 0`` or SOME reachable pod has room for the WHOLE
      pool demand); best fit by cores, first min.  The granting pod is
      the FIRST listed pod with room on the chosen server; ``-1``
      (no grant) for pool-free VMs.  No pooled-admissible server ->
      the all-local fallback, else reject (§4.3 unchanged).
    * DEPART returns the local share to the server and the pool share
      to the RECORDED granting pod (nothing for migrated/fallback
      VMs, as the single-pool kernel).
    * MIGRATE keeps the oracle quirk verbatim — placed VM + local room
      triggers the move with no migrated-set check — returning pool to
      the recorded granting pod; a fallback-placed VM (no grant) pays
      the pool back to its server's FIRST listed pod, or skips the
      pool update entirely on a pod-less server (the local move still
      happens).  The per-pod used-pool can thus go NEGATIVE, bounded
      by the same ``mig_pool_sum`` deficit as the single-pool kernel.

    A second ``(n_slots, C)`` slot array carries the granting pod per
    placement (``-1`` none), extending the int16 packing rules by one
    bound: pod ids must stay below the int16 sentinel
    (:func:`pick_pod_state_dtype`).
    """
    import jax.numpy as jnp
    from jax import lax
    dt = jnp.int16 if state_dtype == "int16" else jnp.int32
    big = jnp.asarray(I16_BIG if state_dtype == "int16" else I32_BIG, dt)
    zero = jnp.asarray(0, dt)

    def body(carry, ev):
        fc, um, up, slots, pods, rejects, sgb, pgb, inc = carry
        kind, sl, c, l, p, m = ev
        pi = p                                       # int32 (shortcuts)
        c, l, p, m = (c.astype(dt), l.astype(dt), p.astype(dt),
                      m.astype(dt))
        is_arr, is_dep, is_mig = kind == ARRIVE, kind == DEPART, \
            kind == MIGRATE
        val = slots[sl]                              # (C,) packed s*2+mig
        has = val >= 0
        s_cur = jnp.where(has, val >> 1, 0)
        mg_cur = has & ((val & 1) == 1)
        podv = pods[sl].astype(jnp.int32)            # (C,) granting pod
        n_c, n_s = fc.shape
        n_f = inc.shape[2]
        cols = jnp.arange(n_s, dtype=jnp.int32)
        pcols = jnp.arange(up.shape[1], dtype=jnp.int32)
        # per-(lane, server, fanout) pod fit: gather each listed pod's
        # used pool + capacity; -1 padding entries never fit
        inc_flat = inc.reshape(n_c, n_s * n_f)
        valid = inc_flat >= 0
        idx = jnp.maximum(inc_flat, 0)
        upr = jnp.take_along_axis(up, idx, axis=1)
        pgr = jnp.take_along_axis(pgb, idx, axis=1)
        fits = (valid & (upr + p <= pgr)).reshape(n_c, n_s, n_f)
        pool_ok = (pi == 0) | fits.any(-1)           # (C, S)
        ok = (fc >= c) & (um + l <= sgb[:, None]) & pool_ok
        score = jnp.where(ok, fc, big)
        s1 = jnp.argmin(score, 1).astype(jnp.int32)
        feas1 = jnp.take_along_axis(score, s1[:, None], 1)[:, 0] < big
        # pool short -> control-plane fallback: start the VM all-local
        ok2 = (fc >= c) & (um + m <= sgb[:, None])
        score2 = jnp.where(ok2, fc, big)
        s2 = jnp.argmin(score2, 1).astype(jnp.int32)
        feas2 = jnp.take_along_axis(score2, s2[:, None], 1)[:, 0] < big
        sel = jnp.where(feas1, s1, s2)
        place = feas1 | feas2
        s_aff = jnp.where(is_arr, sel, s_cur)
        act_arr = is_arr & place
        act_dep = is_dep & has
        um_s = jnp.take_along_axis(um, s_aff[:, None], 1)[:, 0]
        act_mig = is_mig & has & (um_s + p <= sgb)   # QoS: pool -> local
        oh = cols[None, :] == s_aff[:, None]
        dfc = jnp.where(act_dep, c, zero) - jnp.where(act_arr, c, zero)
        dum = (jnp.where(act_arr, jnp.where(feas1, l, m), zero)
               - jnp.where(act_dep, jnp.where(mg_cur, m, l), zero)
               + jnp.where(act_mig, p, zero))
        fc = fc + oh * dfc[:, None]
        um = um + oh * dum[:, None]
        # granting pod: first listed pod with room on the chosen server
        # (argmax of bool = first True; masked off unless a pooled
        # admission actually happened)
        f_sel = jnp.argmax(fits, axis=-1).astype(jnp.int32)   # (C, S)
        pod_srv = jnp.take_along_axis(
            inc, f_sel[:, :, None], axis=2)[:, :, 0]          # (C, S)
        pod_arr = jnp.take_along_axis(pod_srv, sel[:, None], 1)[:, 0]
        arr_tgt = jnp.where(act_arr & feas1 & (pi > 0), pod_arr, -1)
        dep_tgt = jnp.where(act_dep & ~mg_cur, podv, -1)
        first_pod = jnp.take_along_axis(
            inc[:, :, 0], s_aff[:, None], 1)[:, 0]            # (C,)
        mig_tgt = jnp.where(act_mig,
                            jnp.where(podv >= 0, podv, first_pod), -1)
        up = (up
              + jnp.where(pcols[None, :] == arr_tgt[:, None], p, zero)
              - jnp.where(pcols[None, :] == dep_tgt[:, None], p, zero)
              - jnp.where(pcols[None, :] == mig_tgt[:, None], p, zero))
        aval = jnp.where(place, sel * 2 + jnp.where(feas1, 0, 1), -1)
        new_val = jnp.where(is_arr, aval,
                            jnp.where(is_dep, -1,
                                      jnp.where(act_mig, val | 1, val)))
        slots = lax.dynamic_update_index_in_dim(
            slots, new_val.astype(slots.dtype), sl, 0)
        new_pod = jnp.where(is_arr, arr_tgt,
                            jnp.where(is_dep, -1, podv))
        pods = lax.dynamic_update_index_in_dim(
            pods, new_pod.astype(pods.dtype), sl, 0)
        rejects = rejects + (is_arr & ~feas1 & ~feas2)
        return (fc, um, up, slots, pods, rejects, sgb, pgb, inc), None

    def sweep_carry(evs, inc, fc0, um0, up0, slots0, pods0, rej0,
                    sgb, pgb):
        init = (fc0, um0, up0, slots0, pods0, rej0, sgb, pgb, inc)
        out, _ = lax.scan(body, init, evs)
        return out[0], out[1], out[2], out[3], out[4], out[5]

    def sweep(evs, inc, fc0, um0, up0, slots0, pods0, sgb, pgb):
        init = (fc0, um0, up0, slots0, pods0,
                jnp.zeros(sgb.shape[0], jnp.int32), sgb, pgb, inc)
        out, _ = lax.scan(body, init, evs)
        return out[5]

    return sweep_carry if with_carry else sweep


#: packed-carry positions in the ``with_carry`` pod-sweep signature
#: ``(evs, inc, fc0, um0, up0, slots0, pods0, rej0, sgb, pgb)``
_POD_CARRY_ARGNUMS = (2, 3, 4, 5, 6, 7)


def get_pod_sweep(state_dtype: str = "int32", *,
                  with_carry: bool = False, batched: bool = False,
                  mesh=None):
    """Jitted pod sweep from the keyed cache (None without jax).

    Same four variants as :func:`get_sweep` — monolithic, carry
    (donated state), vmapped batch with shared init, vmapped batch
    with per-trace carry — keyed by ``(state_dtype, with_carry,
    batched)``.  The incidence tensor is shared across traces in the
    batched variants (one topology grid, K traces); candidate
    capacities stay per trace.

    ``mesh`` (batched variants only) wraps the vmapped sweep in
    ``shard_map`` over the leading trace axis, like
    :func:`get_sweep` with ``shard_axis="trace"`` — the fleet engines
    shard only the trace axis (the incidence tensor stays replicated).
    """
    if not jax_importable():
        return None
    if mesh is None:
        key = (state_dtype, with_carry, batched)
        flags = dict(carry=with_carry, batched=batched)
    else:
        key = (state_dtype, with_carry, batched, _mesh_key(mesh),
               "trace")
        flags = dict(carry=with_carry, batched=batched,
                     mesh=f"trace{mesh.size}")
    fn = _POD_SWEEPS.get(key)
    rec = obs.get_recorder()
    if fn is None:
        import jax
        stem = _jit_key_name("pod", state_dtype, **flags)
        if rec.enabled:
            rec.count(stem + ".miss")
        with rec.span(stem + ".build"):
            base = build_pod_sweep(state_dtype, with_carry)
            if batched and with_carry:
                base = jax.vmap(base, in_axes=((0, 0, 0, 0, 0, 0), None,
                                               0, 0, 0, 0, 0, 0, 0, 0))
            elif batched:
                base = jax.vmap(base, in_axes=((0, 0, 0, 0, 0, 0), None,
                                               None, None, None, None,
                                               None, 0, 0))
            if mesh is not None:
                base = _shard(base, mesh, _pod_shard_specs(
                    jax.sharding.PartitionSpec, with_carry))
            fn = jax.jit(base, donate_argnums=_POD_CARRY_ARGNUMS
                         if with_carry else ())
        if rec.enabled:
            fn = _FirstCallTimer(fn, stem + ".lower")
        _POD_SWEEPS[key] = fn
    elif rec.enabled:
        rec.count(_jit_key_name("pod", state_dtype, **flags) + ".hit")
    return fn


def pod_jit_cache_keys() -> list:
    """Pod-sweep keys compiled so far (introspection for tests)."""
    return sorted(_POD_SWEEPS, key=repr)


def pick_pod_state_dtype(cores_per_server: float, n_servers: int,
                         sgb_i: np.ndarray, pod_caps_i: np.ndarray,
                         pay_mem_max: float, pay_pool_max: float,
                         mig_pool_sum: float, n_pods: int) -> str:
    """int16/int32 packing rule for the pod sweep.

    The single-pool rules (:func:`pick_state_dtype`) applied with the
    per-pod capacity maxima standing in for the pool column — the
    fallback-migrate deficit bound holds per pod since every deficit
    subtraction lands on exactly one pod — plus one pod-axis bound:
    the granting-pod slot array stores pod ids, so ``n_pods`` must
    stay below the int16 sentinel.
    """
    if n_pods >= I16_BIG:
        return "int32"
    return pick_state_dtype(cores_per_server, n_servers, sgb_i,
                            np.asarray(pod_caps_i).ravel(),
                            pay_mem_max, pay_pool_max, mig_pool_sum)


def pod_lane_arrays(sgb_i: np.ndarray, pgb_i: np.ndarray,
                    inc: np.ndarray, lo: int, hi: int, width: int,
                    np_dt) -> tuple:
    """One candidate chunk's (server_gb, per-pod pool_gb, incidence)
    lane arrays, padded to ``width`` lanes by replicating the chunk's
    last candidate (same no-new-control-flow rule as
    :func:`lane_capacities`).  ``pgb_i`` is ``(n, P)``, ``inc`` is
    ``(n, s_pad, F)`` int32."""
    sgb = np.full(width, sgb_i[hi - 1], np_dt)
    sgb[:hi - lo] = sgb_i[lo:hi]
    pgb = np.repeat(pgb_i[hi - 1:hi], width, 0).astype(np_dt)
    pgb[:hi - lo] = pgb_i[lo:hi]
    incw = np.repeat(inc[hi - 1:hi], width, 0)
    incw[:hi - lo] = inc[lo:hi]
    return sgb, pgb, np.ascontiguousarray(incw, np.int32)


def init_pod_state(width: int, n_servers: int, cores_per_server: float,
                   s_pad: int, p_pad: int, n_slots: int, np_dt,
                   k: int | None = None) -> tuple:
    """Packed all-free initial pod-sweep state: the plain
    :func:`init_state` arrays with the used-pool row widened to the
    padded pod axis plus the granting-pod slot array (``-1`` = no
    grant).  With ``k`` set, every array gains a leading trace axis."""
    fc0, um0, _, slots0, rej0 = init_state(
        width, n_servers, cores_per_server, s_pad, 1, n_slots, np_dt)
    up0 = np.zeros((width, p_pad), np_dt)
    pods0 = np.full((n_slots, width), -1, np_dt)
    out = (fc0, um0, up0, slots0, pods0, rej0)
    if k is None:
        return out
    return tuple(np.broadcast_to(a, (k,) + a.shape).copy()
                 for a in out)


# --------------------------------------------------------- invariant guard --
class SweepInvariantError(RuntimeError):
    """A sweep invariant failed under ``POND_DEBUG_INVARIANTS=1``.

    Structured: ``what`` names the violated invariant, ``shard``/
    ``lane`` (and ``trace`` for batched sweeps) locate the first
    offending state entry.
    """

    def __init__(self, what: str, *, shard: int, lane: int,
                 trace: int | None = None, detail: str = ""):
        self.what, self.shard, self.lane, self.trace = \
            what, shard, lane, trace
        loc = f"shard {shard}, lane {lane}"
        if trace is not None:
            loc = f"shard {shard}, trace {trace}, lane {lane}"
        msg = f"sweep invariant violated: {what} at {loc}"
        super().__init__(msg + (f" ({detail})" if detail else ""))


def invariants_enabled() -> bool:
    """Opt-in debug mode: ``POND_DEBUG_INVARIANTS=1`` in the
    environment makes the streaming engines verify the packed carry
    and the event tensors after every shard (host round-trip per
    shard — debug cost, never on by default)."""
    return os.environ.get("POND_DEBUG_INVARIANTS", "") == "1"


def check_invariants(fc, um, up, *, n_servers: int,
                     cores_per_server: float, shard: int,
                     up_slack: float = 0.0) -> None:
    """Verify the packed carry after a shard (any backend's layout:
    ``(C, S)``/``(C, G)`` or batched ``(K, C, S)``/``(K, C, G)``).

    Checks, on the real server columns: free cores within
    ``[0, cores_per_server]`` (capacity conservation per server — used
    cores never negative, never above capacity), used local memory
    non-negative, used pool above ``-up_slack`` (the documented
    fallback-migrate deficit bound) and every entry finite.  Raises
    :class:`SweepInvariantError` naming the shard and the first
    offending (trace,) lane.
    """
    fc = np.asarray(fc, np.float64)[..., :n_servers]
    um = np.asarray(um, np.float64)[..., :n_servers]
    up = np.asarray(up, np.float64)

    def _raise(what, lane_mask, detail=""):
        first = np.argwhere(lane_mask)[0]
        trace = int(first[0]) if lane_mask.ndim == 2 else None
        lane = int(first[-1])
        raise SweepInvariantError(what, shard=shard, lane=lane,
                                  trace=trace, detail=detail)

    for name, a in (("free-cores", fc), ("used-local-GB", um),
                    ("used-pool-GB", up)):
        bad = ~np.isfinite(a)
        if bad.any():
            _raise(f"non-finite {name}", bad.any(-1))
    bad = (fc < 0) | (fc > cores_per_server)
    if bad.any():
        _raise("free cores outside [0, cores_per_server]", bad.any(-1),
               f"range [{fc.min()}, {fc.max()}]")
    if (um < 0).any():
        _raise("negative used local memory", (um < 0).any(-1),
               f"min {um.min()}")
    if (up < -up_slack - 1e-9).any():
        _raise("used pool below the migrate-deficit bound",
               (up < -up_slack - 1e-9).any(-1),
               f"min {up.min()} < -{up_slack}")


def check_event_tensors(shard: dict, shard_idx: int,
                        n_slots: int) -> None:
    """Verify one shard's event tensors (finite, kinds/slots/payloads
    in domain) under the invariant guard; ``lane`` in the raised error
    is the offending EVENT index within the shard."""
    def _raise(what, mask):
        raise SweepInvariantError(what, shard=shard_idx,
                                  lane=int(np.argwhere(mask)[0][-1]))

    kind = np.asarray(shard["kind"])
    bad = (kind < ARRIVE) | (kind > RECOVER)
    if bad.any():
        _raise("event kind out of range", bad)
    slot = np.asarray(shard["slot"])
    bad = (slot < 0) | (slot >= n_slots)
    if bad.any():
        _raise("event slot out of range", bad)
    for key in ("c", "l", "p", "m"):
        if key not in shard:
            continue
        a = np.asarray(shard[key], np.float64)
        if not np.isfinite(a).all():
            _raise(f"non-finite event payload {key!r}", ~np.isfinite(a))
        vm_ev = (kind == ARRIVE) | (kind == DEPART) | (kind == MIGRATE)
        if (vm_ev & (a < 0)).any():
            _raise(f"negative event payload {key!r}", vm_ev & (a < 0))


# ------------------------------------------------------------- state rules --
def state_np_dtype(state_dtype: str):
    """Host numpy dtype of the packed sweep state."""
    return np.int16 if state_dtype == "int16" else np.int32


def state_sentinel(state_dtype: str) -> int:
    """Best-fit score sentinel / "infinite" magnitude for the dtype."""
    return I16_BIG if state_dtype == "int16" else I32_BIG


def pick_state_dtype(cores_per_server: float, n_servers: int,
                     sgb_i: np.ndarray, pgb_i: np.ndarray,
                     pay_mem_max: float, pay_pool_max: float,
                     mig_pool_sum: float = 0.0) -> str:
    """``"int16"`` when every sweep intermediate provably fits int16.

    The admission tests compute at most ``capacity + one payload``
    (used mem is invariantly <= server_gb, used pool <= pool_gb), so
    int16 is bit-equivalent to int32 whenever the candidate maxima
    plus the per-VM payload maxima stay within :data:`I16_SAFE`, the
    best-fit score sentinel exceeds every free-cores value, and the
    packed slot values (server * 2 + 1) fit.  MIGRATE-bearing traces
    need one more bound: the oracle's fallback-migrate quirk returns
    pool a fallback-placed VM never consumed, driving the used-pool
    carry NEGATIVE — by at most the pool payload of each compiled
    MIGRATE event, so the total compiled migrate-event pool
    (``mig_pool_sum``) bounds the deficit.  When that sum plus the
    payload headroom fits :data:`I16_SAFE` too, migrate traces pack to
    int16 like any other; anything else falls back to int32
    automatically.
    """
    if (cores_per_server < I16_BIG
            and n_servers * 2 + 1 < I16_BIG
            and len(sgb_i) and sgb_i.min() >= 0 and pgb_i.min() >= 0
            and sgb_i.max() + pay_mem_max <= I16_SAFE
            and pgb_i.max() + pay_pool_max <= I16_SAFE
            and mig_pool_sum + pay_pool_max <= I16_SAFE):
        return "int16"
    return "int32"


def quantize_capacities(server_gb, pool_gb):
    """Floor + clip candidate capacities to the int sweep's domain.

    Integral quantities: flooring keeps every admission test identical
    to the float64 oracle; ±2^30 stands in for "infinite" probes.
    """
    sgb_i = np.clip(np.floor(server_gb), -I32_BIG, I32_BIG)
    pgb_i = np.clip(np.floor(pool_gb), -I32_BIG, I32_BIG)
    return sgb_i, pgb_i


# ---------------------------------------------------------------- padding --
def pad_up(n: int, granularity: int, minimum: int | None = None) -> int:
    """``n`` rounded up to a multiple of ``granularity`` (>= minimum)."""
    m = granularity if minimum is None else minimum
    return max(m, (n + granularity - 1) // granularity * granularity)


def bucket_width(k: int) -> int:
    """Padded candidate width for a k-candidate chunk (fixed buckets keep
    XLA recompiles rare; small buckets matter for narrow probe batches)."""
    for b in BUCKETS:
        if k <= b:
            return b
    return BUCKETS[-1]


def candidate_chunks(n: int):
    """Yield ``(lo, hi, width)`` candidate chunks of at most JAX_CHUNK,
    each padded to its bucket width.

    With tracing on, every chunk feeds the ``pad.cand_lanes_used`` /
    ``pad.cand_lanes_padded`` counters — the padding-waste ratio of the
    bucket scheme over the run's actual candidate batches.
    """
    rec = obs.get_recorder()
    for lo in range(0, n, JAX_CHUNK):
        hi = min(lo + JAX_CHUNK, n)
        width = bucket_width(hi - lo)
        if rec.enabled:
            rec.count("pad.cand_lanes_used", hi - lo)
            rec.count("pad.cand_lanes_padded", width - (hi - lo))
        yield lo, hi, width


def lane_capacities(sgb_i: np.ndarray, pgb_i: np.ndarray, lo: int,
                    hi: int, width: int, np_dt) -> tuple:
    """Candidate capacities for one chunk, padded to ``width`` lanes.

    Padding lanes replicate the chunk's last candidate (their results
    are discarded), so padded lanes never hit a different control-flow
    path.  Accepts 1-D ``(n,)`` (single trace) or 2-D ``(K, n)``
    (per-trace candidate grids) arrays.
    """
    if sgb_i.ndim == 1:
        sgb = np.full(width, sgb_i[hi - 1], np_dt)
        pgb = np.full(width, pgb_i[hi - 1], np_dt)
        sgb[:hi - lo] = sgb_i[lo:hi]
        pgb[:hi - lo] = pgb_i[lo:hi]
    else:
        sgb = np.repeat(sgb_i[:, hi - 1:hi], width, 1).astype(np_dt)
        pgb = np.repeat(pgb_i[:, hi - 1:hi], width, 1).astype(np_dt)
        sgb[:, :hi - lo] = sgb_i[:, lo:hi]
        pgb[:, :hi - lo] = pgb_i[:, lo:hi]
    return sgb, pgb


# ---------------------------------------------------- carry pack / unpack --
def init_state(width: int, n_servers: int, cores_per_server: float,
               s_pad: int, g_pad: int, n_slots: int, np_dt,
               k: int | None = None) -> tuple:
    """Packed all-free initial sweep state, as host numpy arrays.

    Returns ``(fc0, um0, up0, slots0, rej0)``: free cores per (lane,
    server) — padded server columns pinned to the negative sentinel so
    they never win a best-fit — used local GB, used pool GB per (lane,
    group), the slot array (-1 = empty) and the int32 reject counters.
    With ``k`` set, every array gains a leading trace axis (the
    per-trace carry of the batched streaming sweep).  Callers place the
    arrays with :func:`device_put`; the carry variants then donate them
    back to the sweep so the state stays device-resident.
    """
    neg = state_sentinel(
        "int16" if np_dt == np.int16 else "int32")
    fc0 = np.full((width, s_pad), -neg, np_dt)
    fc0[:, :n_servers] = np_dt(cores_per_server)
    um0 = np.zeros((width, s_pad), np_dt)
    up0 = np.zeros((width, g_pad), np_dt)
    slots0 = np.full((n_slots, width), -1, np_dt)
    rej0 = np.zeros(width, np.int32)
    if k is None:
        return fc0, um0, up0, slots0, rej0
    return tuple(np.broadcast_to(a, (k,) + a.shape).copy()
                 for a in (fc0, um0, up0, slots0, rej0))


def assign_slots(ev_kind, ev_vm, n_vms: int) -> tuple:
    """Map each event's VM to a reusable placement slot.

    Slots free on departure, so the per-candidate placement state is
    sized by PEAK CONCURRENCY rather than trace length.  Returns the
    per-event slot array and the raw slot count (pad with
    :func:`pad_up` / :data:`SLOT_PAD`).
    """
    slot_of = np.zeros(n_vms, np.int64)
    ev_slot = np.zeros(len(ev_kind), np.int64)
    free_slots: list[int] = []
    next_slot = 0
    for e in range(len(ev_kind)):
        v = ev_vm[e]
        kind = ev_kind[e]
        if kind == ARRIVE:
            if free_slots:
                slot_of[v] = free_slots.pop()
            else:
                slot_of[v] = next_slot
                next_slot += 1
        ev_slot[e] = slot_of[v]
        if kind == DEPART:
            free_slots.append(int(slot_of[v]))
    return ev_slot, next_slot


# -------------------------------------------------------------- placement --
def device_put(x, sharding=None):
    """Place a host array on jax's default device, explicitly.

    One shared entry point so every engine uploads event shards and
    carry state the same way: on CPU this is a no-copy wrap, on
    GPU/TPU an explicit host->device transfer — which, combined with
    the donated carry args of the carry sweeps, keeps the packed state
    device-resident across shards and peak device memory bounded by
    one shard (batch) plus the carry.

    ``sharding`` (a :func:`named_sharding`) places the array across a
    device mesh instead — sliced along the spec'd axis or replicated —
    so sharded sweeps receive inputs already laid out the way their
    ``shard_map`` expects (no resharding transfer inside the jit).

    With tracing on, the transfer volume feeds ``device_put.calls`` /
    ``device_put.bytes`` (host-side nbytes of the placed array).
    """
    import jax
    rec = obs.get_recorder()
    if rec.enabled:
        rec.count("device_put.calls")
        rec.count("device_put.bytes", int(getattr(x, "nbytes", 0)))
    if sharding is None:
        return jax.device_put(x)
    return jax.device_put(x, sharding)


# --------------------------------------------------------------- sharding --
_MESHES: dict = {}     # device-id tuple -> cached 1-D "shard"-axis Mesh


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axis types (``launch/mesh.py``
    re-exports it).  ``devices`` narrows the mesh to an explicit device
    list (default: all visible)."""
    import jax
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def resolve_devices(devices):
    """Normalize an engine ``devices=`` argument to a device list.

    ``None`` -> no sharding; ``"all"`` -> every visible jax device;
    an int -> the first n visible devices; a sequence of jax devices
    passes through.  Fewer than 2 resolved devices degrades to
    ``None`` (the single-device path), so ``devices="all"`` is safe on
    any host — on CPU-only machines, force a device pool with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    if devices is None or not jax_importable():
        return None
    import jax
    if isinstance(devices, str):
        if devices != "all":
            raise ValueError(
                f"devices={devices!r}: expected 'all', an int, a "
                "device sequence, or None")
        devs = list(jax.devices())
    elif isinstance(devices, int):
        devs = list(jax.devices())[:devices]
    else:
        devs = list(devices)
    return devs if len(devs) >= 2 else None


def shard_mesh(devs):
    """Cached 1-D mesh over ``devs`` with a single ``"shard"`` axis —
    the only mesh shape the sweep sharding uses (the batch axes being
    partitioned are 1-D)."""
    key = tuple(d.id for d in devs)
    mesh = _MESHES.get(key)
    if mesh is None:
        mesh = make_mesh((len(devs),), ("shard",), devices=devs)
        _MESHES[key] = mesh
    return mesh


def lane_shard_count(width: int, n_devices: int) -> int:
    """Largest device count <= ``n_devices`` evenly dividing a lane
    bucket — the lane axis must split evenly across the mesh."""
    n = max(1, min(n_devices, width))
    while width % n:
        n -= 1
    return n


def named_sharding(mesh, *spec):
    """``NamedSharding(mesh, PartitionSpec(*spec))`` — e.g.
    ``named_sharding(mesh, "shard")`` slices dim 0 across the mesh,
    ``named_sharding(mesh)`` replicates, ``named_sharding(mesh, None,
    "shard")`` slices dim 1."""
    import jax
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec))


def _mesh_key(mesh):
    return tuple(d.id for d in mesh.devices.flat)


def _shard(fn, mesh, specs):
    """``fn`` wrapped in ``jax.shard_map`` over ``mesh`` with the
    ``(in_specs, out_specs)`` pair ``specs``.  The partitioned rows and
    lanes replay independently, so there is nothing to replicate-check."""
    import jax
    in_specs, out_specs = specs
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _plain_shard_specs(P, with_carry: bool, batched: bool, axis: str):
    """``shard_map`` (in_specs, out_specs) for the plain sweep family.

    ``axis="trace"`` partitions the leading K axis of the event rows,
    candidate capacities and (carry variants) every state array; the
    shared-init batched variant keeps its trace-free initial state
    replicated.  ``axis="lane"`` replicates the event stream and
    splits the candidate-lane axis of the state — dim 0 of the lane
    arrays (dim 1 after a leading trace axis), dim 1 of the
    ``(n_slots, W)`` slot array (dim 2 batched).  Either way the
    sharded rows/lanes replay independently (the best-fit argmin runs
    over the never-sharded server axis), so results are bit-exact.
    """
    S, R = P("shard"), P()
    if axis == "trace":
        if not batched:
            raise ValueError("trace sharding requires batched=True")
        ev = (S,) * 6
        if with_carry:
            return (ev, R, S, S, S, S, S, S, S), (S, S, S, S, S)
        return (ev, R, R, R, R, R, S, S), S
    if axis != "lane":
        raise ValueError(f"unknown shard axis {axis!r}")
    ev = (R,) * 6
    if not batched:
        L, Ls = S, P(None, "shard")
        if with_carry:
            return (ev, R, L, L, L, Ls, L, L, L), (L, L, L, Ls, L)
        return (ev, R, L, L, L, Ls, L, L), L
    L, Ls = P(None, "shard"), P(None, None, "shard")
    if with_carry:
        return (ev, R, L, L, L, Ls, L, L, L), (L, L, L, Ls, L)
    # shared-init batched: the initial state has NO trace axis
    return (ev, R, S, S, S, P(None, "shard"), L, L), L


def _pod_shard_specs(P, with_carry: bool):
    """``shard_map`` specs for the batched pod sweeps, trace axis only
    (the incidence tensor stays replicated across devices)."""
    S, R = P("shard"), P()
    ev = (S,) * 6
    if with_carry:
        return (ev, R, S, S, S, S, S, S, S, S), (S, S, S, S, S, S)
    return (ev, R, R, R, R, R, R, S, S), S
