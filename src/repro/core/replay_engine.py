"""Vectorized event-compiled trace-replay engine (Pond Figs 3 & 21 hot path).

The feasibility searches behind ``savings_analysis`` ask the same question
hundreds of times: "does the trace schedule with <= tol rejections at
uniform (server_gb, pool_gb)?".  The scalar oracle
(``cluster_sim.replay_reject_rate``) answers one candidate per call and
rebuilds + re-sorts a Python tuple list of events every time, so a single
policy point costs ~100 full replays of pure-Python event handling.

This module splits that work into a compile phase and a batched sweep:

* **Compile once** — ``CompiledReplay`` turns a ``(vms, decisions)`` pair
  into flat NumPy event arrays (time, kind, vm index) sorted stably by
  ``(time, kind)`` exactly like the scalar oracle, plus per-VM payload
  vectors (cores, local_gb, pool_gb, fallback mem_gb).

* **Reference trajectories** — ``pool_search_batched`` brackets each
  server size's pool search with the engine's cached replay at
  (server_gb, infinite pool): its peak pool demand is always feasible
  and its reject count decides whether any pool size is.

* **XLA sweep (integral input)** — because every VM memory quantity is
  an integral GB, admission tests like ``free_mem >= local_gb`` are
  exactly ``used_mem + local_gb <= floor(server_gb)`` over int32, so the
  whole batched sweep compiles to one ``lax.scan`` (a Pallas kernel on
  a TPU) in JAX's default x32 mode and still matches the float64 oracle
  bit-for-bit.  Placement state lives in a slot array sized by PEAK
  CONCURRENCY (VM slots are reused after departure) and is updated with
  leading-axis dynamic slices, so the scan carry stays small and in
  place.  Event streams, servers and groups pad to fixed buckets so
  recompiles are rare.  This path needs jax: without it, integral input
  raises a ``RuntimeError`` rather than falling back.

* **numpy sweep (non-integral input)** — a decision or VM size that is
  not an integral GB (an ingested trace with fractional ``mem_gb``)
  would round in the int32 tests, so the engines pick a float64 sweep
  from the input instead; there is no switch.  The live batch carries
  placement state as a packed ``(n_live, n_servers + 1, 3)`` array
  (free cores / free local GB / free pool GB mirrored per server; the
  +1 column is an always-infeasible dummy absorbing ragged pool
  groups).  One fused ``>=``-compare + ``all`` answers cores, memory
  and pool admission for every (candidate, server) pair at once.  VMs
  whose arrival fast-pathed on every live candidate are tracked in a
  "clean" set so their departures skip migration/unplaced handling.
  Searches only need feasibility (rate <= tol), so they pass
  ``reject_cap``: candidates whose reject count exceeds the cap are
  compacted out mid-sweep (reported rate is the lower bound
  ``(cap + 1) / n``).  Each such sweep counts ``replay.backend_numpy``.

With ``reject_cap=None`` the sweep is semantically EXACT with respect to
the scalar oracle: same event order, same best-fit argmin tie-break
(first server achieving the minimum free cores), same float64 values,
same QoS-migration and all-local-fallback transitions.
``tests/test_replay_engine.py`` asserts bit-exact reject rates against
the oracle across trace seeds and policies.

``search_min_batched`` replicates the scalar bisection bit-for-bit by
pricing whole dyadic probe trees per sweep; ``pool_search_batched`` runs
all server-size points' pool searches in lockstep, bracketed for free by
each size's infinite-pool trajectory and warm-started from neighbors
(required pool is monotone non-increasing in server_gb).

* **Two sweep drivers** — ``CompiledReplay`` and ``CompiledReplayStream``
  compile one trace; the sweeps run in the two batch engines below.  A
  single engine's ``reject_rates``, ``availability`` and
  ``reject_rates_fleet`` price it as the one row of a K=1 batch (built
  on first use), so every dispatch of a family goes through one driver.

* **Trace batch axis** — ``CompiledReplayBatch`` stacks K compiled
  traces (synthetic seeds or ingested real traces, see
  ``core/traces.py``) into one ``(K, E_max)`` padded event tensor and
  prices every trace's candidate batch in a single vmapped ``lax.scan``
  — XLA turns the vmap into one scan with a batched carry, so a K-seed
  frontier costs one pass over the event axis instead of K.  Row ``k``
  is bit-exact vs ``engines[k]`` alone.  ``search_min_multi`` and
  ``pool_search_multi`` run the provisioning searches for all K traces
  in lockstep on top of it (one sweep per search round), which is what
  ``cluster_sim.savings_analysis_batched`` uses to report mean ± spread
  savings across a seed batch.  See ``docs/replay_engine.md``.

* **Streaming shards** — ``CompiledReplayStream`` prices traces whose
  padded event tensor would not fit memory: events compile into
  time-windowed shards of at most ``max_events_per_shard`` and the
  packed placement state threads from shard to shard as the scan
  carry, so N shards replay exactly like one monolithic sweep (reject
  rates bit-exact vs ``CompiledReplay``).  Chunked construction from
  ``traces.iter_trace_chunks`` keeps ingestion memory bounded too.
  Shard uploads are DOUBLE-BUFFERED: a background worker packs and
  ``device_put``s shard i+1 while shard i's scan runs (at most two
  shards' event tensors exist transiently; the measured overlap lands
  in ``stream.overlap_ratio``).  Divergence-window skipping
  (``skip_windows=True``, the default) fast-forwards the carry past
  shard prefixes where a cached infinite-capacity reference replay
  proves no candidate's caps can bind — whole shards are never
  scanned, bit-exactly.  Sweep state packs to int16 when server
  capacities permit (half the CPU memory traffic), with an automatic
  int32 fallback — every engine shares the
  ``sweep_core.pick_state_dtype`` overflow rules.

* **Streaming trace batch** — ``CompiledReplayStreamBatch`` composes
  the two axes: K streams replay through index-aligned padded shards,
  one vmapped ``lax.scan`` per shard with a PER-TRACE packed carry
  threaded shard-to-shard, so a K-seed Azure-scale study costs one
  pass over the shard axis instead of K — with peak event-tensor
  memory bounded by ONE stacked shard batch (two in the double-buffer
  window).  Row ``k`` is bit-exact vs running ``streams[k]`` alone.

* **Multi-device sharding** — every sweep entry point takes
  ``devices=`` (``"all"``, an int, a device list, or None): the
  trace-batch axis (or, when K < n_devices and for single traces, the
  candidate-lane axis) is partitioned across a 1-D
  ``jax.sharding.Mesh`` with ``shard_map`` inside the same jitted
  scans (the streaming batch splits lanes only for a single trace).
  The partitioned axes are embarrassingly parallel — no
  collectives — so sharded results are bit-exact (``==``) vs the
  single-device path; fewer than two resolved devices degrades to the
  unsharded sweep.  CPU-only hosts: export
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the
  first jax import.  See ``tests/test_device_shard.py`` and
  ``docs/replay_engine.md``.

The dtype-parametric event-step kernel, the keyed jit cache, the
int16/int32 packing rules, the padding buckets and the carry
pack/unpack + device-placement helpers all live in
``core/sweep_core.py`` — the engine classes here are thin
orchestration layers over that shared core (see
``docs/replay_engine.md`` for the layer diagram).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time

import numpy as np

from repro.core import obs, sweep_core
from repro.core import topology as topology_mod

# shared event/packing constants, re-exported for engine callers
ARRIVE, DEPART, MIGRATE = (sweep_core.ARRIVE, sweep_core.DEPART,
                           sweep_core.MIGRATE)
PAD = sweep_core.PAD  # no-op event kind padding the XLA event stream
FAIL, RECOVER = sweep_core.FAIL, sweep_core.RECOVER  # §4.2 domain events
_INF = np.inf
_I16_SAFE = sweep_core.I16_SAFE   # re-export: boundary tests pin it


def _on_device(exact: bool) -> bool:
    """The sweep path, chosen from the input: the XLA/Pallas sweep when
    every quantity is an integral GB (``exact``), else the float64 numpy
    sweep (the scalar oracle for availability).  Each fallback bumps the
    ``replay.backend_numpy`` counter, so a run that expects the device
    can see that a sweep never reached it.  Integral input needs jax:
    without it this raises instead of falling back."""
    if exact:
        if not sweep_core.jax_importable():
            raise RuntimeError(
                "integral-GB input is replayed by the jax sweep, and jax "
                "cannot be imported")
        return True
    obs.get_recorder().count("replay.backend_numpy")
    return False


# ----------------------------------------------------- decision ingest -----
def _decision_arrays(decisions, n: int):
    """``(local_gb, pool_gb, t_migrate)`` float64 arrays from either a
    ``VMDecision`` sequence or a struct-of-arrays object
    (``policy_engine.PolicyDecisions``) — the form the compiled policy
    pipeline emits, accepted natively so no per-VM decision objects are
    materialized on the hot path.  ``t_migrate`` uses NaN for "none".
    """
    if hasattr(decisions, "local_gb") \
            and not isinstance(decisions, (list, tuple)):
        local = np.asarray(decisions.local_gb, float)
        pool = np.asarray(decisions.pool_gb, float)
        t_mig = np.asarray(decisions.t_migrate, float)
        if not (len(local) == len(pool) == len(t_mig) == n):
            raise ValueError(
                f"decision arrays must align with the {n} VMs; got "
                f"lengths {(len(local), len(pool), len(t_mig))}")
        return local, pool, t_mig
    if len(decisions) != n:
        raise ValueError("decisions must align with vms")
    local = np.fromiter((float(d.local_gb) for d in decisions), float, n)
    pool = np.fromiter((float(d.pool_gb) for d in decisions), float, n)
    t_mig = np.fromiter(
        (np.nan if d.t_migrate is None else float(d.t_migrate)
         for d in decisions), float, n)
    return local, pool, t_mig


# ------------------------------------------------------------ statistics ---
@dataclasses.dataclass
class EngineStats:
    """Aggregate replay throughput across all engines since last reset."""
    sweeps: int = 0
    events: int = 0               # compiled trace length per sweep
    candidate_events: int = 0     # events x live batch width (work done)
    wall_s: float = 0.0

    @property
    def events_per_sec(self) -> float:
        return self.candidate_events / self.wall_s if self.wall_s else 0.0

    def as_dict(self) -> dict:
        return {"sweeps": self.sweeps, "events": self.events,
                "candidate_events": self.candidate_events,
                "wall_s": round(self.wall_s, 4),
                "events_per_sec": round(self.events_per_sec, 1)}


_STATS = EngineStats()


def stats_reset() -> None:
    global _STATS
    _STATS = EngineStats()


def stats_snapshot() -> dict:
    return _STATS.as_dict()


def _count_sweep(t0: float, events: int, candidate_events: int) -> None:
    """Fold one finished sweep, started at ``t0``, into ``_STATS``."""
    _STATS.sweeps += 1
    _STATS.events += events
    _STATS.candidate_events += candidate_events
    _STATS.wall_s += time.perf_counter() - t0


# --------------------------------------------------------------- compile ---
def compiled_arrive_depart(vms):
    """Arrival/departure events as sorted arrays ``(time, kind, vm_index)``.

    Build order and the stable ``(time, kind)`` sort replicate the scalar
    tuple-list construction, so downstream replays see the same sequence.
    """
    n = len(vms)
    times = np.empty(2 * n)
    times[0::2] = np.fromiter((vm.arrival for vm in vms), float, n)
    times[1::2] = np.fromiter((vm.departure for vm in vms), float, n)
    kinds = np.tile(np.array([ARRIVE, DEPART], np.int64), n)
    vmidx = np.repeat(np.arange(n, dtype=np.int64), 2)
    order = np.lexsort((kinds, times))          # stable, like list.sort
    return times[order], kinds[order], vmidx[order]


@dataclasses.dataclass
class _Trajectory:
    """One reference replay of the compiled trace at (server_gb,
    infinite pool): ``need_pool[e]`` is the least pool capacity keeping
    event ``e`` admissible on this path, ``total_rejects`` its reject
    count."""
    need_pool: np.ndarray         # (E,)
    total_rejects: int


@dataclasses.dataclass
class AvailabilityResult:
    """Failure-priced sweep outcome, per candidate (and per trace for
    the batched engines: every array gains a leading K axis).

    ``reject_rate`` includes the failure model (down domains grant no
    pool slices); the counters are totals over the schedule's FAIL
    events.  ``affected_per_failure`` is the per-failure distribution
    ``(n_failures, n_cand)`` (or a per-trace list for batches), None
    when not requested.
    """

    reject_rate: np.ndarray
    affected: np.ndarray
    killed: np.ndarray
    remigrated: np.ndarray
    lost_vm_minutes: np.ndarray
    n_failures: "int | np.ndarray"
    affected_per_failure: "np.ndarray | list | None"
    mitigation: str

    @property
    def remigration_success_rate(self) -> np.ndarray:
        """remigrated / affected, defined as 1.0 where nothing was
        affected (no failure touched a pooled VM)."""
        aff = np.asarray(self.affected, float)
        rem = np.asarray(self.remigrated, float)
        return np.where(aff > 0, rem / np.maximum(aff, 1), 1.0)


class CompiledReplay:
    """One ``(vms, decisions)`` pair compiled for batched replay sweeps."""

    @obs.traced("replay.compile")
    def __init__(self, vms, decisions, cfg, failure_schedule=None):
        self.cfg = cfg
        # references kept for the scalar-oracle availability fallback
        # (no copies, no materialization; the compiled arrays below are
        # the sweep's actual inputs)
        self._vms = vms
        self._decisions_src = decisions
        self.n_vms = n = len(vms)
        self.n_servers = n_srv = cfg.n_servers
        self.n_groups = cfg.n_groups
        self.group_of = np.arange(n_srv) // cfg.servers_per_group
        self.cores_per_server = float(cfg.cores_per_server)
        # group membership columns per server, padded with the dummy
        # column n_srv when the last group is short (ragged n_servers)
        spg_max = int(np.bincount(self.group_of).max())
        self._gcols = np.full((n_srv, spg_max), n_srv, np.int64)
        for s in range(n_srv):
            members = np.flatnonzero(self.group_of == self.group_of[s])
            self._gcols[s, :len(members)] = members

        # per-VM payloads: python floats for the loop, packed vectors for
        # the fused admission compare / state updates.  Decisions may be
        # a VMDecision list or a policy_engine.PolicyDecisions SoA —
        # the latter compiles without materializing per-VM objects.
        cores_a = np.fromiter((vm.cores for vm in vms), float, n)
        mem_a = np.fromiter((vm.mem_gb for vm in vms), float, n)
        local_a, pool_a, t_mig = _decision_arrays(decisions, n)
        self._cores = cores_a.tolist()
        self._mem = mem_a.tolist()
        self._local = local_a.tolist()
        self._pool = pool_a.tolist()
        self._vec3 = [np.array([c, l, p]) for c, l, p in
                      zip(self._cores, self._local, self._pool)]
        self._vec2 = [v[:2] for v in self._vec3]
        self._exact = bool(
            (cores_a == np.floor(cores_a)).all()
            and (mem_a == np.floor(mem_a)).all()
            and (local_a == np.floor(local_a)).all()
            and (pool_a == np.floor(pool_a)).all())
        # per-VM payload maxima: the int16 state-packing overflow check
        # bounds every admission intermediate by capacity + payload
        self._pay_mem_max = float(max(mem_a.max(initial=0.0),
                                      local_a.max(initial=0.0)))
        self._pay_pool_max = float(pool_a.max(initial=0.0))

        # events in the oracle's insertion order: per VM —
        # (arrival, ARRIVE), (t_migrate, MIGRATE)?, (departure, DEPART) —
        # then one stable lexsort by (time, kind).  MIGRATE events outside
        # [arrival, departure) are guaranteed no-ops in the scalar oracle
        # (the VM is not placed) and are dropped here: the XLA backend
        # addresses VMs by reusable slot, so a stale MIGRATE after
        # departure would otherwise hit whichever VM reused the slot.
        times = np.empty(3 * n)
        times[0::3] = np.fromiter((vm.arrival for vm in vms), float, n)
        t_mig = t_mig.copy()
        t_mig[(t_mig < times[0::3])
              | (t_mig >= np.fromiter((vm.departure for vm in vms),
                                      float, n))] = np.nan
        times[1::3] = t_mig
        mig_keep = ~np.isnan(t_mig)
        self._has_migrate = bool(mig_keep.any())
        # worst-case used-pool deficit of the oracle's fallback-migrate
        # quirk: bounds the negative side of the int16 pool carry
        # (see _pick_state_dtype)
        self._mig_pool_sum = float(pool_a[mig_keep].sum())
        dep_a = np.fromiter((vm.departure for vm in vms), float, n)
        times[2::3] = dep_a
        kinds = np.tile(np.array([ARRIVE, MIGRATE, DEPART], np.int64), n)
        vmidx = np.repeat(np.arange(n, dtype=np.int64), 3)
        keep = ~np.isnan(times)
        times, kinds, vmidx = times[keep], kinds[keep], vmidx[keep]
        # failure-domain events (Pond §4.2) merge into the same sorted
        # stream: FAIL/RECOVER kinds sort AFTER same-time VM events and
        # are no-ops in the plain sweep (reject_rates stays happy-path);
        # the failure sweep (availability()) resolves the blast radius
        doms = np.full(len(times), -1, np.int64)
        self.failure_schedule = failure_schedule
        if failure_schedule is not None and len(failure_schedule):
            if failure_schedule.max_domain() >= self.n_groups:
                raise ValueError(
                    f"failure domain {failure_schedule.max_domain()} out "
                    f"of range for {self.n_groups} pool groups")
            fk = np.where(failure_schedule.recovers,
                          sweep_core.RECOVER, sweep_core.FAIL)
            times = np.concatenate([times, failure_schedule.times])
            kinds = np.concatenate([kinds, fk])
            vmidx = np.concatenate(
                [vmidx, np.zeros(len(failure_schedule), np.int64)])
            doms = np.concatenate([doms, failure_schedule.domains])
        order = np.lexsort((kinds, times))
        self.ev_time = times[order]
        self._ev_kind = kinds[order].tolist()
        self._ev_vm = vmidx[order].tolist()
        self._ev_dom = doms[order]
        #: per-VM departure minute (int32): the availability metrics'
        #: VM-minutes-lost clock, quantized exactly like the oracle
        self._dep_min = np.floor(dep_a / 60.0).astype(np.int32)
        self.n_events = len(self._ev_kind)
        self._trajs: dict[float, _Trajectory] = {}
        self._jax_ev = None
        self._jax_ev_fail = None
        self._peak_pool = None
        self._batch = None

    def peak_pool_demand(self) -> float:
        """Cheap upper bound on the pool any candidate can ever need.

        Peak of the prefix sum of +pool_gb at arrival / -pool_gb at
        departure over the compiled event order: every group's actual
        usage is pointwise <= this naive concurrent demand (rejected and
        fallback VMs contribute 0, migrations only return pool early),
        so at pool_gb >= peak the pool never binds.  Used by
        ``pool_search_multi`` as a free feasible upper bracket in place
        of per-trace trajectory replays.
        """
        if self._peak_pool is None:
            kind = np.asarray(self._ev_kind)
            p = np.asarray(self._pool)[np.asarray(self._ev_vm)]
            delta = np.where(kind == ARRIVE, p,
                             np.where(kind == DEPART, -p, 0.0))
            self._peak_pool = float(np.cumsum(delta).max(initial=0.0))
        return self._peak_pool

    # ------------------------------------------------------ XLA compile --
    def _jax_events(self):
        """Slot-mapped, padded int32 host event arrays for the XLA
        sweep (the batch engines stack and upload them).

        VMs are assigned reusable slots (freed on departure), so the
        per-candidate placement state is sized by PEAK CONCURRENCY, not
        by trace length.  Events pad to a multiple of 256 with no-op
        events and servers/groups to multiples of 16, so the jitted
        sweep recompiles only when the padded shapes change.
        """
        if self._jax_ev is not None:
            return self._jax_ev
        n_ev, n_vms, n_srv = self.n_events, self.n_vms, self.n_servers
        ev_slot, next_slot = sweep_core.assign_slots(
            self._ev_kind, self._ev_vm, n_vms)
        n_slots = sweep_core.pad_up(next_slot, sweep_core.SLOT_PAD)
        e_pad = sweep_core.pad_up(n_ev, sweep_core.EVENT_PAD)
        s_pad = sweep_core.pad_up(n_srv, sweep_core.LANE_PAD)
        g_pad = sweep_core.pad_up(self.n_groups, sweep_core.LANE_PAD)

        def pad(vals, fill):
            out = np.full(e_pad, fill, np.int32)
            out[:n_ev] = vals
            return out

        rec = obs.get_recorder()
        if rec.enabled:
            rec.count("pad.events_used", n_ev)
            rec.count("pad.events_padded", e_pad - n_ev)
        vmx = np.asarray(self._ev_vm)
        evs = (pad(self._ev_kind, PAD), pad(ev_slot, 0),
               pad(np.asarray(self._cores, np.int32)[vmx], 0),
               pad(np.asarray(self._local, np.int32)[vmx], 0),
               pad(np.asarray(self._pool, np.int32)[vmx], 0),
               pad(np.asarray(self._mem, np.int32)[vmx], 0))
        group_np = np.zeros(s_pad, np.int32)
        group_np[:n_srv] = self.group_of
        self._jax_ev = (evs, group_np, n_slots, s_pad, g_pad)
        return self._jax_ev

    def _pick_state_dtype(self, sgb_i: np.ndarray,
                          pgb_i: np.ndarray) -> str:
        """``"int16"`` when every sweep intermediate provably fits int16
        (the shared ``sweep_core.pick_state_dtype`` rules, fed this
        engine's cluster shape, payload maxima and compiled
        migrate-event pool total ``_mig_pool_sum``)."""
        return sweep_core.pick_state_dtype(
            self.cores_per_server, self.n_servers, sgb_i, pgb_i,
            self._pay_mem_max, self._pay_pool_max, self._mig_pool_sum)

    def _jax_events_fail(self):
        """The plain event tensors plus the failure sweep's two extra
        int32 streams: ``x`` (departure minute at ARRIVE, failure minute
        at FAIL — the VM-minutes-lost clock) and ``dmn`` (the failure
        domain at FAIL/RECOVER, -1 otherwise)."""
        if self._jax_ev_fail is not None:
            return self._jax_ev_fail
        evs, group_of, n_slots, s_pad, g_pad = self._jax_events()
        e_pad = evs[0].shape[0]
        kind = np.asarray(self._ev_kind)
        x = np.zeros(e_pad, np.int32)
        dmn = np.full(e_pad, -1, np.int32)
        n_ev = self.n_events
        vmx = np.asarray(self._ev_vm)
        x[:n_ev] = np.where(
            kind == ARRIVE, self._dep_min[vmx],
            np.where(kind == FAIL,
                     np.floor(self.ev_time / 60.0).astype(np.int32), 0))
        dmn[:n_ev] = self._ev_dom
        evs8 = evs + (x, dmn)
        self._jax_ev_fail = (evs8, group_of, n_slots, s_pad, g_pad)
        return self._jax_ev_fail

    def _as_batch(self) -> "CompiledReplayBatch":
        """This engine as the one row of a :class:`CompiledReplayBatch`,
        built on first use: the batch's drivers run every XLA sweep of
        a single trace."""
        if self._batch is None:
            self._batch = CompiledReplayBatch([self])
        return self._batch

    @obs.traced("replay.availability")
    def availability(self, server_gb, pool_gb,
                     mitigation: str = "remigrate",
                     state_dtype: str | None = None,
                     per_failure: bool = True) -> "AvailabilityResult":
        """Price the merged failure schedule: reject rates WITH the
        §4.2 failure model, plus availability metrics, per candidate.

        Requires the engine to have been built with
        ``failure_schedule=``.  Broadcasting matches
        :meth:`reject_rates`.  ``mitigation`` picks the blast-radius
        policy (``"remigrate"`` pulls affected pool into host-local
        DRAM where the server's free memory allows, all-or-nothing per
        server; ``"kill"`` terminates every affected VM).  On integral
        input the failures resolve inside the same scan step
        (``sweep_core.build_fail_sweep``, run as row 0 of a K=1
        :class:`CompiledReplayBatch`); on non-integral input the
        engine loops the scalar blast-radius oracle
        ``cluster_sim.replay_with_failures`` — bit-exact either way
        (``tests/test_failures.py``).

        Returns an :class:`AvailabilityResult`; with
        ``per_failure=True`` it includes the ``(n_failures, n_cand)``
        VMs-affected-per-failure distribution.
        """
        if self.failure_schedule is None:
            raise ValueError(
                "availability() needs a failure_schedule= at compile "
                "time (see runtime.fault.FailureSchedule)")
        server_gb = np.atleast_1d(np.asarray(server_gb, float))
        pool_gb = np.atleast_1d(np.asarray(pool_gb, float))
        server_gb, pool_gb = np.broadcast_arrays(server_gb, pool_gb)
        if not _on_device(self._exact):
            return self._availability_oracle(server_gb, pool_gb,
                                             mitigation, per_failure)
        res = self._as_batch()._availability(
            server_gb[None], pool_gb[None], mitigation, state_dtype,
            per_failure)
        return dataclasses.replace(
            res, reject_rate=res.reject_rate[0], affected=res.affected[0],
            killed=res.killed[0], remigrated=res.remigrated[0],
            lost_vm_minutes=res.lost_vm_minutes[0],
            n_failures=int(res.n_failures[0]),
            affected_per_failure=res.affected_per_failure[0]
            if per_failure else None)

    def _availability_oracle(self, server_gb, pool_gb, mitigation,
                             per_failure):
        """Scalar-oracle sweep for non-integral decisions: one
        ``cluster_sim.replay_with_failures`` call per candidate."""
        from repro.core import cluster_sim  # deferred: cyclic at import
        t0 = time.perf_counter()
        decisions = (self._decisions_src.as_vmdecisions()
                     if hasattr(self._decisions_src, "as_vmdecisions")
                     else self._decisions_src)
        n0 = len(server_gb)
        out = {k: np.empty(n0, np.int64) for k in
               ("affected", "killed", "remig", "lost")}
        rates = np.empty(n0)
        dist = None
        for i in range(n0):
            r = cluster_sim.replay_with_failures(
                self._vms, decisions, self.cfg,
                float(server_gb[i]), float(pool_gb[i]),
                self.failure_schedule, mitigation)
            if per_failure and dist is None:
                dist = np.empty((r.n_failures, n0), np.int64)
            rates[i] = r.reject_rate
            out["affected"][i] = r.affected
            out["killed"][i] = r.killed
            out["remig"][i] = r.remigrated
            out["lost"][i] = r.lost_vm_minutes
            if per_failure:
                dist[:, i] = r.affected_per_failure
            n_failures = r.n_failures
        _count_sweep(t0, self.n_events, self.n_events * n0)
        return AvailabilityResult(
            reject_rate=rates, affected=out["affected"],
            killed=out["killed"], remigrated=out["remig"],
            lost_vm_minutes=out["lost"], n_failures=n_failures,
            affected_per_failure=dist, mitigation=mitigation)

    # --------------------------------------------- reference trajectories --
    def _trajectory(self, server_gb: float) -> _Trajectory:
        """Replay once at (server_gb, infinite pool), recording each
        event's pool threshold (lean Python loop; cached, so each
        trajectory is built one time per engine)."""
        key = float(server_gb)
        cached = self._trajs.get(key)
        if cached is not None:
            return cached
        n_srv, n_vms, n_ev = self.n_servers, self.n_vms, self.n_events
        group_of = self.group_of.tolist()
        cores_of, mem_of = self._cores, self._mem
        local_of, pool_of = self._local, self._pool
        ev_kind, ev_vm = self._ev_kind, self._ev_vm

        fc = [self.cores_per_server] * n_srv
        um = [0.0] * n_srv
        up = [0.0] * self.n_groups
        need_pool = np.zeros(n_ev)
        srv = np.full(n_vms, -1, np.int64)
        mig = np.zeros(n_vms, bool)
        live = [False] * n_vms
        rejects = 0

        for e in range(n_ev):
            v = ev_vm[e]
            kind = ev_kind[e]
            if kind == ARRIVE:
                c, l = cores_of[v], local_of[v]
                best, bv = -1, _INF
                for s in range(n_srv):          # best fit, first min
                    f = fc[s]
                    if f >= c and key - um[s] >= l and f < bv:
                        best, bv = s, f
                if best >= 0:
                    g = group_of[best]
                    p = pool_of[v]
                    fc[best] -= c
                    um[best] += l
                    up[g] += p
                    srv[v] = best
                    live[v] = True
                    need_pool[e] = up[g]
                    continue
                # pool can't help here (it is infinite on this path):
                # the oracle's all-local fallback
                m = mem_of[v]
                for s in range(n_srv):
                    f = fc[s]
                    if f >= c and key - um[s] >= m and f < bv:
                        best, bv = s, f
                if best >= 0:
                    fc[best] -= c
                    um[best] += m
                    srv[v] = best
                    live[v] = True
                    mig[v] = True               # departs as all-local
                    continue
                rejects += 1
            elif kind == DEPART:
                if not live[v]:
                    continue
                live[v] = False
                s = int(srv[v])
                fc[s] += cores_of[v]
                if mig[v]:
                    um[s] -= mem_of[v]          # pool already returned
                else:
                    um[s] -= local_of[v]
                    up[group_of[s]] -= pool_of[v]
            elif kind == MIGRATE and live[v]:   # pool -> local if the
                s = int(srv[v])                 # host has local room
                p = pool_of[v]
                # (oracle quirk: a fallback-placed VM can still be
                # "migrated" — it moves pool_gb mem->pool)
                if key - um[s] >= p:
                    um[s] += p
                    up[group_of[s]] -= p
                    mig[v] = True
        traj = _Trajectory(need_pool, rejects)
        self._trajs[key] = traj
        return traj

    # ------------------------------------------------------------- sweep --
    @obs.traced("replay.reject_rates")
    def reject_rates(self, server_gb, pool_gb,
                     reject_cap: int | None = None,
                     state_dtype: str | None = None,
                     devices=None) -> np.ndarray:
        """Reject fraction for each (server_gb, pool_gb) candidate.

        Accepts scalars or broadcastable 1-D arrays; one event sweep
        prices the whole batch, bit-exact vs the scalar oracle.  On
        integral-GB input the sweep is the XLA integer sweep, run as
        row 0 of a K=1 :class:`CompiledReplayBatch`; its carry packs to
        int16 when the candidate capacities (plus payload headroom)
        permit — half the memory traffic — and falls back to int32
        automatically; ``state_dtype`` ("int16"/"int32") forces one
        packing for tests.  ``devices`` shards its candidate-lane axis
        over a JAX device mesh (``"all"``, an int, or an explicit device
        list — see :func:`sweep_core.resolve_devices`), bit-exact vs
        single-device.  Non-integral input runs the float64 numpy sweep
        instead, which ignores both.  With ``reject_cap`` set, the
        numpy sweep drops candidates exceeding the cap mid-sweep and
        reports the lower bound ``(reject_cap + 1) / n_vms`` — only
        valid for feasibility tests against a tolerance below that
        bound (the XLA sweep always returns exact rates, which satisfy
        the same contract).

        Usage (price a 9-point frontier in one sweep)::

            eng = CompiledReplay(vms, decisions, cfg)
            rates = eng.reject_rates(np.linspace(200., 400., 9),
                                     np.linspace(0., 800., 9))
        """
        server_gb = np.atleast_1d(np.asarray(server_gb, float))
        pool_gb = np.atleast_1d(np.asarray(pool_gb, float))
        server_gb, pool_gb = np.broadcast_arrays(server_gb, pool_gb)
        if not self.n_events:
            return np.zeros(len(server_gb))
        if not _on_device(self._exact):
            return self._reject_rates_numpy(server_gb, pool_gb, reject_cap)
        return self._as_batch()._reject_rates(
            server_gb[None], pool_gb[None], state_dtype, devices)[0]

    def _reject_rates_numpy(self, server_gb, pool_gb,
                            reject_cap: int | None = None) -> np.ndarray:
        """The float64 sweep of non-integral input: every candidate
        replays from event 0 in one vectorized pass (see the module
        docstring's numpy sweep)."""
        t0 = time.perf_counter()
        n0 = len(server_gb)
        n_srv, n_vms, n_ev = self.n_servers, self.n_vms, self.n_events
        denom = max(n_vms, 1)
        if not n_ev:
            return np.zeros(n0)
        rates = np.empty(n0)
        if reject_cap is not None:      # default for dropped candidates
            rates[:] = (reject_cap + 1) / denom

        free = np.empty((n0, n_srv + 1, 3))
        free[:, :n_srv, 0] = self.cores_per_server
        free[:, :n_srv, 1] = server_gb[:, None]
        free[:, :n_srv, 2] = pool_gb[:, None]
        free[:, n_srv, :] = -_INF
        placed = np.full((n0, n_vms), -1, np.int32)
        migrated = np.zeros((n0, n_vms), bool)
        rejects = np.zeros(n0, np.int64)
        alive = np.arange(n0)
        cidx = np.arange(n0)
        clean: set = set()              # vms fast-pathed on every live row
        gcols = self._gcols
        vec3s, vec2s = self._vec3, self._vec2
        cores_of, mem_of = self._cores, self._mem
        local_of, pool_of = self._local, self._pool
        ev_kind, ev_vm = self._ev_kind, self._ev_vm
        cand_events = 0

        for e in range(n_ev):
            cand_events += len(alive)
            v = ev_vm[e]
            kind = ev_kind[e]
            if kind > MIGRATE:      # FAIL/RECOVER: happy-path no-ops
                continue            # (availability() prices them)
            if kind == DEPART:
                if v in clean:                   # all rows placed, none
                    s = placed[:, v]             # migrated
                    free[cidx, s, :2] += vec2s[v]
                    p = pool_of[v]
                    if p > 0.0:
                        free[cidx[:, None], gcols[s], 2] += p
                    placed[:, v] = -1
                    clean.discard(v)
                    continue
                s = placed[:, v]
                rows = cidx[s >= 0]
                if rows.size:
                    sv = s[rows]
                    mg = migrated[rows, v]
                    free[rows, sv, 0] += cores_of[v]
                    free[rows, sv, 1] += np.where(mg, mem_of[v],
                                                  local_of[v])
                    free[rows[:, None], gcols[sv], 2] += \
                        np.where(mg, 0.0, pool_of[v])[:, None]
                    migrated[rows, v] = False
                placed[:, v] = -1
                continue
            if kind == MIGRATE:
                # QoS mitigation: copy the pooled GBs back to local if the
                # host has room (§4.3); the VM then departs as all-local.
                p = pool_of[v]
                s = placed[:, v]
                rows = cidx[s >= 0]
                if rows.size:
                    sv = s[rows]
                    room = free[rows, sv, 1] >= p
                    rows, sv = rows[room], sv[room]
                    if rows.size:
                        free[rows, sv, 1] -= p
                        free[rows[:, None], gcols[sv], 2] += p
                        migrated[rows, v] = True
                        clean.discard(v)
                continue
            # ---- ARRIVE: best fit by cores among servers whose free local
            # memory fits; pool checked per group (same mask as the oracle,
            # fused into one packed compare).
            vec3 = vec3s[v]
            ok = (free >= vec3).all(-1)                  # (C, S+1)
            score = np.where(ok, free[:, :, 0], _INF)
            s = score.argmin(1)
            best = score[cidx, s]
            p = pool_of[v]
            if not np.isinf(best.max(initial=-_INF)):
                free[cidx, s, :2] -= vec2s[v]
                if p > 0.0:
                    free[cidx[:, None], gcols[s], 2] -= p
                placed[:, v] = s
                clean.add(v)
                continue
            infeas = np.isinf(best)
            rows = cidx[~infeas]
            if rows.size:
                sv = s[rows]
                free[rows, sv, :2] -= vec2s[v]
                if p > 0.0:
                    free[rows[:, None], gcols[sv], 2] -= p
                placed[rows, v] = sv
            # pool short -> control-plane fallback: start the VM all-local
            # (§4.3: VM starts never block on the pool)
            bad = cidx[infeas]
            c, m = cores_of[v], mem_of[v]
            sub = free[bad]                              # (B, S+1, 3)
            ok2 = (sub[:, :, 0] >= c) & (sub[:, :, 1] >= m)
            score2 = np.where(ok2, sub[:, :, 0], _INF)
            s2 = score2.argmin(1)
            inf2 = np.isinf(score2[np.arange(len(bad)), s2])
            rows2 = bad[~inf2]
            if rows2.size:
                sv2 = s2[~inf2]
                free[rows2, sv2, 0] -= c
                free[rows2, sv2, 1] -= m
                placed[rows2, v] = sv2
                migrated[rows2, v] = True    # departs as all-local
            rej = bad[inf2]
            if rej.size:
                rejects[rej] += 1
                if reject_cap is not None:
                    over = rejects > reject_cap
                    if over.any():           # compact decided candidates
                        keep = ~over
                        alive = alive[keep]
                        free = free[keep]
                        placed = placed[keep]
                        migrated = migrated[keep]
                        rejects = rejects[keep]
                        cidx = np.arange(len(alive))
                        if not len(alive):
                            break

        rates[alive] = rejects / denom
        _count_sweep(t0, n_ev, cand_events)
        return rates

    # ------------------------------------------------------------- fleet --
    def _fleet_events_np(self):
        """Slot-mapped numpy event arrays for the fleet sweep (cached):
        one shard dict shaped like a streaming shard, spanning the whole
        trace, float payloads (the numpy fleet sweep carries float64
        state, so non-integral decisions replay exactly too)."""
        if getattr(self, "_fleet_ev_np", None) is None:
            ev_slot, next_slot = sweep_core.assign_slots(
                self._ev_kind, self._ev_vm, self.n_vms)
            vmx = np.asarray(self._ev_vm)
            self._fleet_ev_np = {
                "kind": np.asarray(self._ev_kind, np.int32),
                "slot": np.asarray(ev_slot, np.int32),
                "c": np.asarray(self._cores)[vmx],
                "l": np.asarray(self._local)[vmx],
                "p": np.asarray(self._pool)[vmx],
                "m": np.asarray(self._mem)[vmx],
                "n_slots": int(next_slot),
            }
        return self._fleet_ev_np

    @obs.traced("replay.fleet")
    def reject_rates_fleet(self, server_gb, pod_gb, topology,
                           state_dtype: str | None = None) -> np.ndarray:
        """Reject fraction per ``(server_gb, pod capacities, topology)``
        fleet candidate — the multi-pod analog of :meth:`reject_rates`.
        (Traced as ``replay.fleet`` when a recorder is live.)

        ``topology`` is one ``core/topology.py`` Topology (shared) or a
        sequence of per-lane topologies (all at this engine's
        ``n_servers``); ``pod_gb`` broadcasts per
        :func:`_fleet_candidates` (scalar, shared per-pod array, or
        per-lane entries).  One event scan prices the whole grid,
        bit-exact against the scalar oracle
        ``cluster_sim.replay_multi_pool``: the XLA pod sweep (row 0 of a
        K=1 :class:`CompiledReplayBatch`) on integral-GB traces, the
        float64 numpy sweep otherwise.

        Usage (price a topology frontier at equal hardware)::

            caps = [topology.split_pool(960.0, t.n_pods) for t in topos]
            rates = eng.reject_rates_fleet(320.0, caps, topos)
        """
        sgb, caps, topos = _fleet_candidates(server_gb, pod_gb, topology)
        if topos[0].n_servers != self.n_servers:
            raise ValueError(
                f"topology covers {topos[0].n_servers} servers; engine "
                f"has {self.n_servers}")
        if not self.n_events:
            return np.zeros(len(sgb))
        if not _on_device(self._exact):
            return self._fleet_rates_numpy(sgb, caps, topos)
        return self._as_batch()._fleet_rates(sgb, caps, topos,
                                             state_dtype)[0]

    def _fleet_rates_numpy(self, sgb, caps, topos) -> np.ndarray:
        """The float64 fleet sweep over normalized lanes (see
        :func:`_np_fleet_sweep`)."""
        t0 = time.perf_counter()
        ev = self._fleet_events_np()
        state = _np_fleet_state(len(sgb), self.n_servers,
                                self.cores_per_server, sgb, caps,
                                ev["n_slots"])
        inc, _ = _fleet_incidence(topos, self.n_servers, self.n_servers)
        _np_fleet_sweep(ev, inc, *state)
        _count_sweep(t0, self.n_events, self.n_events * len(sgb))
        return state[-1] / max(self.n_vms, 1)


# ----------------------------------------------------------- fleet sweeps --
def _fleet_candidates(server_gb, pod_gb, topology):
    """Normalize a fleet candidate grid to per-lane arrays.

    A fleet candidate is a ``(server_gb, per-pod pool_gb, topology)``
    triple; all three broadcast to one lane axis:

    * ``server_gb`` — scalar or ``(n_cand,)``.
    * ``topology`` — one ``core/topology.py`` Topology (shared) or a
      sequence of ``n_cand`` (the topology-frontier axis).
    * ``pod_gb`` — a scalar (every pod of every lane), a 1-D array of
      SHARED per-pod capacities (length must equal every lane
      topology's pod count), or a sequence/2-D array of ``n_cand``
      per-lane entries (each a scalar or a per-pod array).

    Returns ``(sgb (n_cand,), pod_caps (n_cand, P_max), topos)``;
    capacity columns past a lane's pod count are 0 and inert (no
    incidence row points at them).
    """
    topos = list(topology) if isinstance(topology, (list, tuple)) \
        else [topology]
    sgb = np.atleast_1d(np.asarray(server_gb, float))
    if isinstance(pod_gb, np.ndarray) and pod_gb.ndim == 2:
        pod_gb = list(pod_gb)
    rows = len(pod_gb) if isinstance(pod_gb, (list, tuple)) else 1
    n0 = max(len(sgb), len(topos), rows)
    if len(sgb) == 1:
        sgb = np.repeat(sgb, n0)
    if len(topos) == 1:
        topos = topos * n0
    if isinstance(pod_gb, np.ndarray) and pod_gb.ndim == 1:
        for t in topos:
            if t.n_pods != len(pod_gb):
                raise ValueError(
                    "1-D pod_gb gives SHARED per-pod capacities; lane "
                    f"topology {t.describe()} has {t.n_pods} pods for "
                    f"{len(pod_gb)} capacities (pass a per-lane "
                    "sequence instead)")
        pod_gb = [pod_gb] * n0
    elif not isinstance(pod_gb, (list, tuple)):
        pod_gb = float(pod_gb)
    elif rows == 1 and n0 > 1:
        pod_gb = list(pod_gb) * n0
    if len(sgb) != n0 or len(topos) != n0 or (
            isinstance(pod_gb, list) and len(pod_gb) != n0):
        raise ValueError(
            "fleet candidates must broadcast to one lane count; got "
            f"{len(sgb)} server sizes, {len(topos)} topologies, "
            f"{rows} pod-capacity rows")
    n_srv = topos[0].n_servers
    for t in topos:
        if t.n_servers != n_srv:
            raise ValueError(
                "all lane topologies must share n_servers; got "
                f"{t.n_servers} vs {n_srv}")
    caps = topology_mod.pod_caps_matrix(pod_gb, topos)
    return sgb.astype(float), caps, topos


def _fleet_incidence(topos, n_servers: int, s_pad: int):
    """Stack per-lane incidence rows to one ``(n_cand, s_pad, F_max)``
    int32 tensor, ``-1`` filled (padded servers and narrower lanes
    reach no pod).  Returns ``(inc, p_max)``."""
    f_max = max((t.inc.shape[1] for t in topos), default=1)
    p_max = max((t.n_pods for t in topos), default=1)
    inc = np.full((len(topos), s_pad, f_max), -1, np.int32)
    for i, t in enumerate(topos):
        inc[i, :n_servers, :t.inc.shape[1]] = t.inc
    return inc, p_max


def _np_fleet_sweep(shard, inc, free, pool_free, placed, pod_of,
                    migrated, rejects):
    """Numpy fleet shard sweep over carried state (float64,
    oracle-ordered ops) — the multi-pod analog of
    :func:`_np_stream_sweep`.

    ``inc`` is the ``(C, S, F)`` per-lane incidence (``-1`` padded),
    ``free`` the ``(C, S, 2)`` free cores / free local GB, ``pool_free``
    the ``(C, P)`` per-pod free pool, ``placed``/``pod_of``/``migrated``
    the ``(C, n_slots)`` placement, granting-pod and migrated state —
    all mutated in place so consecutive shards continue one replay.
    Tracking FREE capacities keeps every float add/subtract in the
    scalar ``cluster_sim.replay_multi_pool`` order, so non-integral
    decisions stay bit-exact too.
    """
    kind, slot = shard["kind"], shard["slot"]
    cs, ls, ps, ms = shard["c"], shard["l"], shard["p"], shard["m"]
    cidx = np.arange(free.shape[0])
    valid = inc >= 0
    gidx = np.maximum(inc, 0)
    first_pod = inc[:, :, 0]                          # (C, S)
    for e in range(len(kind)):
        k = kind[e]
        if k >= PAD:                 # PAD and FAIL/RECOVER: no-ops here
            continue
        sl = slot[e]
        if k == DEPART:
            s = placed[:, sl]
            rows = cidx[s >= 0]
            if rows.size:
                sv = s[rows]
                mg = migrated[rows, sl]
                free[rows, sv, 0] += cs[e]
                free[rows, sv, 1] += np.where(mg, ms[e], ls[e])
                q = pod_of[rows, sl]
                back = ~mg & (q >= 0)
                if back.any():
                    pool_free[rows[back], q[back]] += ps[e]
                migrated[rows, sl] = False
            placed[:, sl] = -1
            pod_of[:, sl] = -1
            continue
        if k == MIGRATE:
            p = ps[e]
            s = placed[:, sl]
            rows = cidx[s >= 0]
            if rows.size:
                sv = s[rows]
                room = free[rows, sv, 1] >= p
                rows, sv = rows[room], sv[room]
                if rows.size:
                    free[rows, sv, 1] -= p
                    # pool returns to the granting pod; fallback VMs
                    # (no grant) pay their server's first listed pod,
                    # or skip the pool update on a pod-less server
                    q = pod_of[rows, sl]
                    tgt = np.where(q >= 0, q, first_pod[rows, sv])
                    back = tgt >= 0
                    if back.any():
                        pool_free[rows[back], tgt[back]] += p
                    migrated[rows, sl] = True
            continue
        # ARRIVE: best fit by cores among servers whose free local
        # memory fits and SOME reachable pod fits the whole pool demand
        c, l, p, m = cs[e], ls[e], ps[e], ms[e]
        okcm = (free[:, :, 0] >= c) & (free[:, :, 1] >= l)
        if p > 0.0:
            pf = pool_free[cidx[:, None, None], gidx]
            fits = valid & (pf >= p)
            ok = okcm & fits.any(-1)
        else:
            fits = None
            ok = okcm
        score = np.where(ok, free[:, :, 0], _INF)
        s = score.argmin(1)
        feas = ~np.isinf(score[cidx, s])
        rows = cidx[feas]
        if rows.size:
            sv = s[rows]
            free[rows, sv, 0] -= c
            free[rows, sv, 1] -= l
            if p > 0.0:
                f = fits[rows, sv].argmax(-1)   # first listed fitting pod
                q = inc[rows, sv, f]
                pool_free[rows, q] -= p
                pod_of[rows, sl] = q
            placed[rows, sl] = sv
        bad = cidx[~feas]
        if bad.size:
            # pool short -> control-plane fallback: start the VM all-local
            sub = free[bad]
            ok2 = (sub[:, :, 0] >= c) & (sub[:, :, 1] >= m)
            score2 = np.where(ok2, sub[:, :, 0], _INF)
            s2 = score2.argmin(1)
            inf2 = np.isinf(score2[np.arange(len(bad)), s2])
            rows2 = bad[~inf2]
            if rows2.size:
                sv2 = s2[~inf2]
                free[rows2, sv2, 0] -= c
                free[rows2, sv2, 1] -= m
                placed[rows2, sl] = sv2
                migrated[rows2, sl] = True       # departs as all-local
            rejects[bad[inf2]] += 1


def _np_fleet_state(n_cand: int, n_servers: int, cores_per_server,
                    sgb: np.ndarray, pod_caps: np.ndarray,
                    n_slots: int) -> tuple:
    """All-free numpy fleet carry: ``(free, pool_free, placed, pod_of,
    migrated, rejects)`` for :func:`_np_fleet_sweep`."""
    free = np.empty((n_cand, n_servers, 2))
    free[:, :, 0] = cores_per_server
    free[:, :, 1] = sgb[:, None]
    pool_free = pod_caps.astype(float).copy()
    placed = np.full((n_cand, n_slots), -1, np.int64)
    pod_of = np.full((n_cand, n_slots), -1, np.int64)
    migrated = np.zeros((n_cand, n_slots), bool)
    rejects = np.zeros(n_cand, np.int64)
    return free, pool_free, placed, pod_of, migrated, rejects


# ------------------------------------------------------------- streaming ---
def _np_stream_sweep(shard, gcols, free, placed, migrated, rejects):
    """Numpy shard sweep over carried state (float64, oracle-ordered ops).

    Vectorized over candidates like ``CompiledReplay``'s numpy sweep,
    but slot-indexed and carry-threaded: ``free`` is the packed
    ``(C, n_servers + 1, 3)`` free-capacity array (cores / local GB /
    mirrored group pool GB; the +1 dummy column absorbs ragged pool
    groups), ``placed``/``migrated`` are ``(C, n_slots)`` placement
    state, ``rejects`` the per-candidate counters — all mutated in
    place so consecutive shards continue one replay.  Tracking FREE
    capacities (not usage) keeps the float adds/subtracts in the scalar
    oracle's exact order, so non-integral decisions stay bit-exact too.
    """
    kind, slot = shard["kind"], shard["slot"]
    cs, ls, ps, ms = shard["c"], shard["l"], shard["p"], shard["m"]
    cidx = np.arange(free.shape[0])
    for e in range(len(kind)):
        k = kind[e]
        if k >= PAD:                 # PAD and FAIL/RECOVER: no-ops here
            continue
        sl = slot[e]
        if k == DEPART:
            s = placed[:, sl]
            rows = cidx[s >= 0]
            if rows.size:
                sv = s[rows]
                mg = migrated[rows, sl]
                free[rows, sv, 0] += cs[e]
                free[rows, sv, 1] += np.where(mg, ms[e], ls[e])
                free[rows[:, None], gcols[sv], 2] += \
                    np.where(mg, 0.0, ps[e])[:, None]
                migrated[rows, sl] = False
            placed[:, sl] = -1
            continue
        if k == MIGRATE:
            p = ps[e]
            s = placed[:, sl]
            rows = cidx[s >= 0]
            if rows.size:
                sv = s[rows]
                room = free[rows, sv, 1] >= p
                rows, sv = rows[room], sv[room]
                if rows.size:
                    free[rows, sv, 1] -= p
                    free[rows[:, None], gcols[sv], 2] += p
                    migrated[rows, sl] = True
            continue
        # ARRIVE: best fit by cores among servers whose free local memory
        # and group pool fit (same fused compare as the wave loop)
        vec3 = np.array([cs[e], ls[e], ps[e]])
        ok = (free >= vec3).all(-1)
        score = np.where(ok, free[:, :, 0], _INF)
        s = score.argmin(1)
        best = score[cidx, s]
        p = ps[e]
        feas = ~np.isinf(best)
        rows = cidx[feas]
        if rows.size:
            sv = s[rows]
            free[rows, sv, 0] -= cs[e]
            free[rows, sv, 1] -= ls[e]
            if p > 0.0:
                free[rows[:, None], gcols[sv], 2] -= p
            placed[rows, sl] = sv
        bad = cidx[~feas]
        if bad.size:
            # pool short -> control-plane fallback: start the VM all-local
            c, m = cs[e], ms[e]
            sub = free[bad]
            ok2 = (sub[:, :, 0] >= c) & (sub[:, :, 1] >= m)
            score2 = np.where(ok2, sub[:, :, 0], _INF)
            s2 = score2.argmin(1)
            inf2 = np.isinf(score2[np.arange(len(bad)), s2])
            rows2 = bad[~inf2]
            if rows2.size:
                sv2 = s2[~inf2]
                free[rows2, sv2, 0] -= c
                free[rows2, sv2, 1] -= m
                placed[rows2, sl] = sv2
                migrated[rows2, sl] = True       # departs as all-local
            rejects[bad[inf2]] += 1


# ------------------------------------------------- checkpoint / resume ----
class SweepInterrupted(RuntimeError):
    """A streaming sweep was killed by the chaos hook
    (``CheckpointSpec.kill_after_shards``) after writing its
    checkpoint.  Carries the checkpoint path and the number of shard
    sweeps completed before the kill."""

    def __init__(self, path: str, shards_done: int):
        self.path, self.shards_done = path, shards_done
        super().__init__(
            f"sweep interrupted after {shards_done} shard sweeps "
            f"(checkpoint at {path})")


@dataclasses.dataclass(frozen=True)
class CheckpointSpec:
    """Checkpoint/resume policy for the streaming sweeps.

    Passed as ``checkpoint=`` to
    :meth:`CompiledReplayStream.reject_rates` /
    :meth:`CompiledReplayStreamBatch.reject_rates`: every
    ``every_shards`` shard sweeps the engine snapshots the packed
    carry, the shard cursor and the candidate-chunk schedule position
    to ``path`` (one ``.npz``, written atomically: tmp file +
    ``os.replace``, so a kill mid-write never corrupts the previous
    snapshot).  With ``resume=True`` an existing checkpoint whose
    fingerprint matches the sweep (sweep path, state dtype, event/shard
    counts, candidate grid bytes, reject cap) is loaded first and the
    sweep fast-forwards — completed candidate chunks keep their
    counts, the current chunk restarts from the checkpointed shard
    with the restored carry.  Resumed results are BIT-IDENTICAL to an
    uninterrupted sweep (``tests/test_checkpoint_stream.py`` kills at
    shard k
    and proves it, both sweep paths, both state dtypes); a fingerprint
    mismatch raises ``ValueError`` rather than silently pricing a
    different sweep.

    ``kill_after_shards`` is the chaos hook: after that many shard
    sweeps the engine force-writes a snapshot and raises
    :class:`SweepInterrupted` (how the chaos tests and
    ``benchmarks/azure_e2e.py --kill-after`` simulate preemption).
    """

    path: str
    every_shards: int = 8
    resume: bool = False
    kill_after_shards: int | None = None


def _sweep_fingerprint(backend: str, dt_name: str, n_events, n_shards,
                       n_vms, reject_cap, server_gb, pool_gb) -> str:
    """Identity of one streaming sweep: resuming under any other
    configuration would silently produce wrong counts, so the
    checkpoint refuses to load when this differs."""
    h = hashlib.sha256()
    h.update(repr((backend, dt_name, np.asarray(n_events).tolist(),
                   np.asarray(n_shards).tolist(),
                   np.asarray(n_vms).tolist(), reject_cap)).encode())
    h.update(np.ascontiguousarray(np.asarray(server_gb, float)).tobytes())
    h.update(np.ascontiguousarray(np.asarray(pool_gb, float)).tobytes())
    return h.hexdigest()


class _CheckpointIO:
    """Snapshot cadence + atomic npz IO + the chaos kill hook for one
    streaming sweep (shared by the jax and numpy shard loops)."""

    def __init__(self, spec: CheckpointSpec, fingerprint: str):
        self.spec = spec
        self.fp = fingerprint
        self.shards_done = 0

    def load(self) -> dict | None:
        if not (self.spec.resume and os.path.exists(self.spec.path)):
            return None
        with obs.get_recorder().span("checkpoint.load"):
            with np.load(self.spec.path, allow_pickle=False) as z:
                state = {key: z[key] for key in z.files}
        got = str(state.pop("fingerprint"))
        if got != self.fp:
            raise ValueError(
                f"checkpoint {self.spec.path} belongs to a different "
                "sweep (sweep path/state dtype/trace/candidates/reject cap "
                "changed); delete it or rerun the original sweep")
        return state

    def save(self, state: dict) -> None:
        with obs.get_recorder().span("checkpoint.save"):
            tmp = self.spec.path + ".tmp.npz"
            np.savez(tmp, fingerprint=self.fp, **state)
            os.replace(tmp, self.spec.path)

    def tick(self, state_fn) -> None:
        """After each shard sweep: snapshot on cadence; then, if the
        chaos hook fires, force a snapshot and raise."""
        self.shards_done += 1
        kill = (self.spec.kill_after_shards is not None
                and self.shards_done >= self.spec.kill_after_shards)
        due = (self.spec.every_shards > 0
               and self.shards_done % self.spec.every_shards == 0)
        if due or kill:
            self.save(state_fn())
        if kill:
            raise SweepInterrupted(self.spec.path, self.shards_done)

    def done(self) -> None:
        """Completed sweeps delete their checkpoint: a later resume of
        a finished run recomputes from scratch instead of loading a
        stale cursor."""
        if os.path.exists(self.spec.path):
            os.remove(self.spec.path)


# ------------------------------------------- double-buffered uploads --
_UPLOAD_POOL = None


def _upload_pool():
    """Lazy single-worker executor for shard host-packing + uploads.

    One worker is enough: the pipeline only ever has shard i+1 in
    flight while shard i computes, and a single worker keeps uploads
    ordered.  The worker must never touch the obs recorder (it is
    single-threaded); jobs return wall timestamps and the main thread
    emits the ``stream.upload`` span via ``Recorder.add_span``.
    """
    global _UPLOAD_POOL
    if _UPLOAD_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _UPLOAD_POOL = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pond-upload")
    return _UPLOAD_POOL


def _upload_job(build, sharding=None):
    """Worker-side job: pack one shard's host tensors and start the
    device transfer.  Returns ``(device_arrays, t0_ns, t1_ns, nbytes)``
    so the caller can report the span from the engine thread.  The work
    runs inside a ``stream.upload`` profiler annotation, so a profiler
    trace shows each upload on the worker's own thread line."""
    import jax
    with jax.profiler.TraceAnnotation("stream.upload"):
        t0 = time.perf_counter_ns()
        arrs = build()
        nbytes = sum(int(a.nbytes) for a in arrs)
        if sharding is None:
            out = tuple(jax.device_put(a) for a in arrs)
        else:
            out = tuple(jax.device_put(a, sharding) for a in arrs)
        t1 = time.perf_counter_ns()
    return out, t0, t1, nbytes


def _count_out_devices(rec, out) -> None:
    """``sweep.out_devices.<n>``: one count per candidate chunk whose
    reject counters came back spread over ``n`` devices — how a run
    shows that ``devices=`` really partitioned the sweep."""
    if rec.enabled:
        rec.count(f"sweep.out_devices.{len(out.devices())}")


def _lane_shardings(mesh):
    """Placements of one lane-split chunk of the batched carry sweep,
    as ``(carry, capacities, events)``: the candidate-lane axis of the
    carry (dim 1; dim 2 of the slot array) and of the capacities split
    over ``mesh``, the events replicated.  All ``None`` without one."""
    if mesh is None:
        return (None,) * 5, None, None
    lane = sweep_core.named_sharding(mesh, None, "shard")
    slots = sweep_core.named_sharding(mesh, None, None, "shard")
    return ((lane, lane, lane, slots, lane), lane,
            sweep_core.named_sharding(mesh))


def _count_scanned(rec, lanes: int, arrivals: int, events: int,
                   n_servers: int, n_groups: int, carry_rows: int) -> None:
    """The work one plain sweep dispatch really scanned, whole or a
    shard of a skipping or capped stream: ``sweep.lane_arrivals``
    (arrivals summed over the call's traces, times its real candidate
    lanes), ``sweep.fit_cells`` (that times the servers each arrival's
    best fit compares), ``sweep.events_scanned`` (real events summed
    over the traces) and ``sweep.carry_cells`` (the per-server and
    per-group state entries the dispatch takes in and gives back:
    ``carry_rows`` lane sets of ``2 * n_servers + n_groups``).  Called
    only while a recorder is live, as its arguments may cost a count."""
    rec.count("sweep.lane_arrivals", arrivals * lanes)
    rec.count("sweep.fit_cells", arrivals * lanes * n_servers)
    rec.count("sweep.events_scanned", events)
    rec.count("sweep.carry_cells",
              carry_rows * lanes * (2 * n_servers + n_groups))


def _count_unscanned(rec, steps, first: int, end: int) -> None:
    """``stream.steps_unscanned``: the event steps (``steps`` per shard,
    counted as ``sweep.steps`` counts them) of one streamed candidate
    chunk that no dispatch scanned, because window skipping started it
    at shard ``first`` or a ``reject_cap`` exit stopped it before
    shard ``end``."""
    if rec.enabled:
        rec.count("stream.steps_unscanned",
                  int(sum(steps[:first])) + int(sum(steps[end:])))


# --------------------------------------------- divergence windows --
def _stream_reference(stream):
    """Infinite-capacity reference replay over a stream's shards.

    Replays the compiled event shards once with unbounded server/pool
    capacities — exactly the XLA kernel's semantics at ``sgb = pgb =
    inf`` (best-fit by free cores, first index on ties; cores-only
    rejects).  Produces, per shard, the maximum server/pool demand any
    admission or migration test could require (``max_srv`` /
    ``max_pool``) plus the full packed state at every shard boundary.

    A candidate lane whose capacities dominate a prefix of these maxima
    provably takes the identical action at every event of that prefix,
    so the sweep may start from the boundary snapshot instead — the
    divergence-window skip.  Cached on the stream; returns ``None``
    when the stream cannot support exact skipping (non-integral
    decisions or cores).
    """
    ref = getattr(stream, "_ref", None)
    if ref is not None:
        return ref if ref != "unusable" else None
    cps = float(stream.cores_per_server)
    if not (stream._exact and cps.is_integer()):
        stream._ref = "unusable"
        return None
    stream._ref = _reference_replay(stream, int(cps))
    return stream._ref


@obs.traced("stream.reference")
def _reference_replay(stream, cps: int) -> dict:
    """The replay behind :func:`_stream_reference` (host side, once
    per stream): per-shard demand maxima plus boundary snapshots."""
    big = 1 << 60
    n_srv = stream.n_servers
    group_of = np.asarray(stream.group_of, np.int64)
    fc = np.full(n_srv, cps, np.int64)
    um = np.zeros(n_srv, np.int64)
    up = np.zeros(stream.n_groups, np.int64)
    slots = np.full(stream._n_slots, -1, np.int64)
    rej = 0
    n = stream.n_shards
    max_srv = np.empty(n, np.int64)
    max_pool = np.empty(n, np.int64)
    snaps = [(fc.copy(), um.copy(), up.copy(), slots.copy(), rej)]
    for si, shard in enumerate(stream._shards):
        kinds = shard["kind"].tolist()
        sls = shard["slot"].tolist()
        cs = shard["c"].tolist()
        ls = shard["l"].tolist()
        ps = shard["p"].tolist()
        ms_ = shard["m"].tolist()
        ms = mp = -big                # event-free shards always skip
        for e, kind in enumerate(kinds):
            if kind == ARRIVE:
                c = int(cs[e])
                feas = fc >= c
                if feas.any():
                    b = int(np.argmin(np.where(feas, fc, big)))
                    g = group_of[b]
                    fc[b] -= c
                    um[b] += int(ls[e])
                    up[g] += int(ps[e])
                    slots[sls[e]] = b * 2
                    if um[b] > ms:
                        ms = int(um[b])
                    if up[g] > mp:
                        mp = int(up[g])
                else:
                    rej += 1
            elif kind == DEPART:
                val = int(slots[sls[e]])
                if val >= 0:
                    b = val >> 1
                    fc[b] += int(cs[e])
                    if val & 1:
                        um[b] -= int(ms_[e])
                    else:
                        um[b] -= int(ls[e])
                        up[group_of[b]] -= int(ps[e])
                    slots[sls[e]] = -1
            elif kind == MIGRATE:
                val = int(slots[sls[e]])
                if val >= 0:
                    b = val >> 1
                    p = int(ps[e])
                    um[b] += p
                    up[group_of[b]] -= p
                    slots[sls[e]] = val | 1
                    if um[b] > ms:
                        ms = int(um[b])
            # PAD (and FAIL/RECOVER, which the plain kernel ignores)
            # leave the state untouched
        max_srv[si] = ms
        max_pool[si] = mp
        snaps.append((fc.copy(), um.copy(), up.copy(), slots.copy(),
                      rej))
    return {"max_srv": max_srv, "max_pool": max_pool, "snaps": snaps}


def _skip_count(ref, min_sgb, min_pgb, n_shards):
    """Leading shards a chunk may skip: the longest prefix whose
    reference demand maxima every lane capacity in the chunk covers.
    A stream whose entire trace is skippable extends to ``n_shards``
    (trailing batch-alignment shards hold only no-op events)."""
    viol = (ref["max_srv"] > min_sgb) | (ref["max_pool"] > min_pgb)
    nz = np.flatnonzero(viol)
    return int(nz[0]) if nz.size else n_shards


def _carry_from_snap(snap, width, n_servers, n_groups, s_pad, g_pad,
                     n_slots, np_dt, dt_name):
    """Packed per-lane carry seeded from a reference boundary snapshot,
    broadcast across ``width`` candidate lanes (every non-diverged lane
    holds exactly the reference state).  Layout matches
    ``sweep_core.init_state``: padded server columns at the negative
    sentinel, padded slots at -1."""
    fc_r, um_r, up_r, slots_r, rej = snap
    fc0 = np.full((width, s_pad), -sweep_core.state_sentinel(dt_name),
                  np_dt)
    fc0[:, :n_servers] = fc_r
    um0 = np.zeros((width, s_pad), np_dt)
    um0[:, :n_servers] = um_r
    up0 = np.zeros((width, g_pad), np_dt)
    up0[:, :n_groups] = up_r
    slots0 = np.full((n_slots, width), -1, np_dt)
    slots0[:len(slots_r), :] = slots_r[:, None]
    rej0 = np.full(width, rej, np.int32)
    return fc0, um0, up0, slots0, rej0


def _pad_carry_rows(carry, k_pad, init_full):
    """Grow/shrink the leading (trace) axis of a resumed batched carry
    to ``k_pad`` rows — rows past the checkpointed count start from the
    plain init state (their events are all no-ops)."""
    k_have = np.asarray(carry[0]).shape[0]
    if k_have == k_pad:
        return carry
    return tuple(
        np.concatenate([np.asarray(a)[:k_pad], b[min(k_have, k_pad):]])
        for a, b in zip(carry, init_full))


class CompiledReplayStream:
    """Out-of-core replay: time-windowed event shards, carried state.

    Prices arbitrarily long traces with peak event-tensor memory set by
    ``max_events_per_shard``: events compile into fixed-size shards and
    the packed placement state (free cores, used local/pool GB, the
    slot array, reject counters) threads from shard to shard as the
    ``lax.scan`` carry, so N shards replay EXACTLY like one monolithic
    sweep — reject rates are bit-exact vs :class:`CompiledReplay` on
    any trace that fits both paths (asserted in
    ``tests/test_replay_stream.py``).  The carry packs to int16 when
    server capacities permit (automatic int32 fallback, same rules as
    the monolithic sweep); without jax (or with non-integral GB
    decisions) a numpy shard sweep carries the same state in float64.

    Two construction modes:

    * **in-memory** — drop-in for :class:`CompiledReplay` when only the
      padded event tensor (not the VM list) outgrows memory::

          stream = CompiledReplayStream(vms, decisions, cfg,
                                        max_events_per_shard=100_000)
          rates = stream.reject_rates([300.0, 350.0], [512.0, 256.0])

    * **chunked** — bounded-memory ingestion from an iterator of VM
      chunks (e.g. ``traces.iter_trace_chunks``); chunk arrivals must be
      non-decreasing across chunk boundaries, and ``decide`` maps each
      chunk to its per-VM decisions (default: all-local)::

          stream = CompiledReplayStream(
              traces.iter_trace_chunks("azure.csv.gz", chunk_vms=10**5),
              None, cfg, max_events_per_shard=250_000,
              decide=lambda chunk: cluster_sim.policy_decisions(
                  chunk, "static", static_pool_frac=0.15)[0])

    Chunk ingestion keeps compact per-event arrays (~40 host bytes per
    event), per-VM payload scalars (5 machine words per VM) and the
    pending-departure buffer; the heavyweight VM records (PMU vectors
    etc.) of a consumed chunk are dropped before the next chunk loads,
    and at most TWO shards' padded event tensors are ever materialized
    for the sweep (the one computing plus the one the double-buffer
    worker uploads) — that quantity is what ``max_events_per_shard``
    bounds.  ``scripts/fetch_azure_trace.py`` emits arrival-sorted
    trace files that stream through this path unchanged.
    """

    @obs.traced("stream.compile")
    def __init__(self, vms, decisions=None, cfg=None, *,
                 max_events_per_shard: int = 262_144, decide=None):
        if cfg is None:
            raise TypeError("CompiledReplayStream(vms, decisions, cfg): "
                            "cfg is required")
        if max_events_per_shard < 256:
            raise ValueError("max_events_per_shard must be >= 256")
        self.cfg = cfg
        # floored to a multiple of 256 (the shard pad granularity) so
        # the padded per-sweep tensor NEVER exceeds the stated budget
        self.max_events_per_shard = int(max_events_per_shard) // 256 * 256
        self.n_servers = n_srv = cfg.n_servers
        self.n_groups = cfg.n_groups
        self.group_of = np.arange(n_srv) // cfg.servers_per_group
        self.cores_per_server = float(cfg.cores_per_server)
        spg_max = int(np.bincount(self.group_of).max())
        self._gcols = np.full((n_srv, spg_max), n_srv, np.int64)
        for s in range(n_srv):
            members = np.flatnonzero(self.group_of == self.group_of[s])
            self._gcols[s, :len(members)] = members

        # ingest state
        self.n_vms = 0
        self._cores: list[float] = []
        self._local: list[float] = []
        self._pool: list[float] = []
        self._mem: list[float] = []
        self._exact = True
        self._pend_t: list[float] = []
        self._pend_k: list[int] = []
        self._pend_v: list[int] = []
        self._t_seen = -_INF          # latest arrival ingested
        self._t_flushed = -_INF       # events < this are already compiled
        self._slot_of: list[int] = []
        self._free_slots: list[int] = []
        self._next_slot = 0
        self._buf: dict[str, list] = {k: [] for k in
                                      ("kind", "slot", "c", "l", "p", "m")}
        self._shards: list[dict] = []
        self.n_events = 0
        self._pool_cum = 0.0
        self._peak_pool = 0.0
        self._pay_mem_max = 0.0
        self._pay_pool_max = 0.0
        self._has_migrate = False
        self._mig_pool_sum = 0.0      # compiled MIGRATE-event pool total
        self._batch = None

        it = iter(vms)
        first = next(it, None)
        if first is None:
            pass                                    # empty trace
        elif hasattr(first, "arrival"):             # flat VM list
            allvms = [first, *it]
            if decisions is not None and len(decisions) != len(allvms):
                raise ValueError("decisions must align with vms")
            self._ingest_chunk(allvms, decisions)
        else:                                       # iterator of chunks
            if decisions is not None:
                raise ValueError(
                    "pass decisions=None with a chunk iterator; supply a "
                    "decide(chunk) callback instead")
            for chunk in ([first] if first else []):
                self._ingest_chunk(chunk,
                                   decide(chunk) if decide else None)
            for chunk in it:
                if chunk:
                    self._ingest_chunk(chunk,
                                       decide(chunk) if decide else None)
        self._finish()

    # ------------------------------------------------------------ ingest --
    def _ingest_chunk(self, chunk, decisions) -> None:
        if decisions is not None:
            # list of VMDecision or a PolicyDecisions SoA, normalized
            # to arrays either way (NaN t_migrate = none)
            local_a, pool_a, tmig_a = _decision_arrays(decisions,
                                                       len(chunk))
        t_min = _INF
        for i, vm in enumerate(chunk):
            v = self.n_vms
            self.n_vms += 1
            c = float(vm.cores)
            m = float(vm.mem_gb)
            l = m if decisions is None else float(local_a[i])
            p = 0.0 if decisions is None else float(pool_a[i])
            t_mig = None
            if decisions is not None and not np.isnan(tmig_a[i]):
                t_mig = float(tmig_a[i])
            arrival = float(vm.arrival)
            dep = arrival + float(vm.lifetime)
            self._cores.append(c)
            self._local.append(l)
            self._pool.append(p)
            self._mem.append(m)
            self._slot_of.append(-1)
            self._exact = self._exact and c.is_integer() \
                and m.is_integer() and l.is_integer() and p.is_integer()
            self._pay_mem_max = max(self._pay_mem_max, m, l)
            self._pay_pool_max = max(self._pay_pool_max, p)
            t_min = min(t_min, arrival)
            self._t_seen = max(self._t_seen, arrival)
            self._pend_t.append(arrival)
            self._pend_k.append(ARRIVE)
            self._pend_v.append(v)
            # MIGRATE events outside [arrival, departure) are no-ops in
            # the oracle and are dropped, like the monolithic compile
            if t_mig is not None and arrival <= t_mig < dep:
                self._has_migrate = True
                self._pend_t.append(float(t_mig))
                self._pend_k.append(MIGRATE)
                self._pend_v.append(v)
            self._pend_t.append(dep)
            self._pend_k.append(DEPART)
            self._pend_v.append(v)
        if t_min < self._t_flushed:
            raise ValueError(
                f"chunk arrivals must be non-decreasing across chunks: "
                f"got {t_min:g} after events were compiled up to "
                f"{self._t_flushed:g} (sort the trace by arrival)")
        self._flush(self._t_seen)

    def _flush(self, t_max: float, final: bool = False) -> None:
        """Compile every pending event strictly before ``t_max`` (all of
        them when ``final``) in the monolithic (time, kind, vm) order."""
        if not self._pend_t:
            return
        t = np.asarray(self._pend_t)
        k = np.asarray(self._pend_k, np.int64)
        v = np.asarray(self._pend_v, np.int64)
        if final:
            take = np.ones(len(t), bool)
        else:
            take = t < t_max
            self._t_flushed = max(self._t_flushed, t_max)
        if not take.any():
            return
        ts, ks, vs = t[take], k[take], v[take]
        order = np.lexsort((vs, ks, ts))
        self._emit(ks[order].tolist(), vs[order].tolist())
        keep = ~take
        self._pend_t = t[keep].tolist()
        self._pend_k = k[keep].tolist()
        self._pend_v = v[keep].tolist()

    def _emit(self, kinds, vidx) -> None:
        buf = self._buf
        budget = self.max_events_per_shard
        for k, v in zip(kinds, vidx):
            if k == ARRIVE:
                if self._free_slots:
                    sl = self._free_slots.pop()
                else:
                    sl = self._next_slot
                    self._next_slot += 1
                self._slot_of[v] = sl
                self._pool_cum += self._pool[v]
                self._peak_pool = max(self._peak_pool, self._pool_cum)
            else:
                sl = self._slot_of[v]
                if k == DEPART:
                    self._free_slots.append(sl)
                    self._pool_cum -= self._pool[v]
                else:                         # MIGRATE (int16 pool bound)
                    self._mig_pool_sum += self._pool[v]
            buf["kind"].append(k)
            buf["slot"].append(sl)
            buf["c"].append(self._cores[v])
            buf["l"].append(self._local[v])
            buf["p"].append(self._pool[v])
            buf["m"].append(self._mem[v])
            self.n_events += 1
            if len(buf["kind"]) == budget:
                self._close_shard()

    def _close_shard(self) -> None:
        b = self._buf
        if not b["kind"]:
            return
        self._shards.append({
            "kind": np.asarray(b["kind"], np.int32),
            "slot": np.asarray(b["slot"], np.int32),
            "c": np.asarray(b["c"]), "l": np.asarray(b["l"]),
            "p": np.asarray(b["p"]), "m": np.asarray(b["m"])})
        for key in b:        # reset in place: _emit holds a reference
            b[key] = []

    def _finish(self) -> None:
        self._flush(_INF, final=True)
        self._close_shard()
        self.n_shards = len(self._shards)
        self._n_slots = sweep_core.pad_up(self._next_slot,
                                          sweep_core.SLOT_PAD)
        self._s_pad = sweep_core.pad_up(self.n_servers,
                                        sweep_core.LANE_PAD)
        self._g_pad = sweep_core.pad_up(self.n_groups,
                                        sweep_core.LANE_PAD)
        #: real (unpadded) events per shard: a shard scan's steps
        self.shard_events = [len(s["kind"]) for s in self._shards]
        self.shard_pad_events = sweep_core.pad_up(
            max(self.shard_events, default=0), sweep_core.EVENT_PAD)
        #: per-sweep device footprint of one shard's event tensor
        #: (6 int32 streams) — THE quantity max_events_per_shard bounds
        self.peak_shard_bytes = 6 * 4 * self.shard_pad_events
        rec = obs.get_recorder()
        if rec.enabled and self.n_shards:
            used = int(sum(len(s["kind"]) for s in self._shards))
            rec.count("pad.events_used", used)
            rec.count("pad.events_padded",
                      self.n_shards * self.shard_pad_events - used)
        for s in self._shards:           # pad in place, once
            n = len(s["kind"])
            pad = self.shard_pad_events - n
            if pad:
                s["kind"] = np.concatenate(
                    [s["kind"], np.full(pad, PAD, np.int32)])
                for key in ("slot",):
                    s[key] = np.concatenate(
                        [s[key], np.zeros(pad, np.int32)])
                for key in ("c", "l", "p", "m"):
                    s[key] = np.concatenate([s[key], np.zeros(pad)])
            if self._exact:
                # integral payloads: store int32 once so sweeps upload
                # without a per-call astype
                for key in ("c", "l", "p", "m"):
                    s[key] = s[key].astype(np.int32)
        group_np = np.zeros(self._s_pad, np.int32)
        group_np[:self.n_servers] = self.group_of
        self._group_np = group_np

    # -------------------------------------------------------------- query --
    def peak_pool_demand(self) -> float:
        """Naive concurrent pool demand peak over the compiled event
        order (same bound as ``CompiledReplay.peak_pool_demand``):
        feasible upper bracket for any pool search."""
        return float(self._peak_pool)

    # int16 state-packing rules are shared with the monolithic engine
    # (the check reads only cluster shape + payload maxima, which this
    # class mirrors attribute-for-attribute)
    _pick_state_dtype = CompiledReplay._pick_state_dtype

    def _as_batch(self) -> "CompiledReplayStreamBatch":
        """This stream as the one row of a
        :class:`CompiledReplayStreamBatch`, built on first use: the
        batch's drivers run every XLA sweep of a single stream."""
        if self._batch is None:
            self._batch = CompiledReplayStreamBatch([self])
        return self._batch

    @obs.traced("stream.reject_rates")
    def reject_rates(self, server_gb, pool_gb,
                     reject_cap: int | None = None,
                     state_dtype: str | None = None,
                     checkpoint: "CheckpointSpec | None" = None,
                     devices=None,
                     skip_windows: bool = True) -> np.ndarray:
        """Reject fraction per candidate, streamed shard by shard.

        Same contract and broadcasting as
        :meth:`CompiledReplay.reject_rates`; one pass over the shards
        prices the whole candidate batch, threading the packed state
        between shards, with peak event-tensor memory
        ``peak_shard_bytes`` (bounded by ``max_events_per_shard``; the
        double-buffered upload pipeline keeps at most TWO shards in
        flight, so transient peak is ``2 * peak_shard_bytes``).
        With ``reject_cap`` set the stream stops early once EVERY
        candidate exceeds the cap (each reported rate is then its exact
        count so far — a lower bound at or above
        ``(reject_cap + 1) / n_vms``, satisfying the same
        feasibility-test contract as the other engines).

        On integral-GB input the stream prices as row 0 of a K=1
        :class:`CompiledReplayStreamBatch`, which takes ``state_dtype``,
        ``checkpoint``, ``devices`` (here: the candidate-lane axis split
        across JAX devices, bit-exact vs single-device) and
        ``skip_windows`` as they are; see
        :meth:`CompiledReplayStreamBatch.reject_rates`.  Non-integral
        input runs a float64 numpy shard sweep over the same carry,
        which honours ``reject_cap`` and ``checkpoint`` (resumed
        results are bit-identical on both paths).  Under
        ``POND_DEBUG_INVARIANTS=1`` the carry is verified after every
        shard (``sweep_core.check_invariants``).

        Usage::

            stream = CompiledReplayStream(vms, decisions, cfg,
                                          max_events_per_shard=65_536)
            rates = stream.reject_rates(
                np.linspace(200., 400., 9), np.linspace(0., 800., 9))
        """
        server_gb = np.atleast_1d(np.asarray(server_gb, float))
        pool_gb = np.atleast_1d(np.asarray(pool_gb, float))
        server_gb, pool_gb = np.broadcast_arrays(server_gb, pool_gb)
        if not self.n_events:
            return np.zeros(len(server_gb))
        if not _on_device(self._exact):
            return self._sweep_numpy(server_gb, pool_gb, reject_cap,
                                     checkpoint)
        return self._as_batch()._reject_rates(
            server_gb[None], pool_gb[None], reject_cap, state_dtype,
            checkpoint, devices, skip_windows)[0]

    def _checkpoint_io(self, reject_cap, server_gb, pool_gb, spec):
        """The checkpoint of the numpy shard sweep, the one a single
        stream runs itself (its XLA sweeps checkpoint in the batch)."""
        if spec is None:
            return None, None
        io = _CheckpointIO(spec, _sweep_fingerprint(
            "numpy", "float64", self.n_events, self.n_shards, self.n_vms,
            reject_cap, server_gb, pool_gb))
        return io, io.load()

    def _debug_check_events(self) -> None:
        for si, shard in enumerate(self._shards):
            sweep_core.check_event_tensors(shard, si, self._n_slots)

    def _debug_check_carry(self, fc, um, up, si: int) -> None:
        sweep_core.check_invariants(
            np.asarray(fc), np.asarray(um), np.asarray(up),
            n_servers=self.n_servers,
            cores_per_server=self.cores_per_server, shard=si,
            up_slack=self._mig_pool_sum)

    @functools.cached_property
    def shard_arrivals(self) -> list:
        """Arrival events per shard: the best fits its scan makes.
        Counted on first use, which only a live recorder makes."""
        return [int(np.count_nonzero(s["kind"] == ARRIVE))
                for s in self._shards]

    def _sweep_numpy(self, server_gb, pool_gb, reject_cap, ckpt=None):
        t0 = time.perf_counter()
        n0 = len(server_gb)
        n_srv = self.n_servers
        free = np.empty((n0, n_srv + 1, 3))
        free[:, :n_srv, 0] = self.cores_per_server
        free[:, :n_srv, 1] = server_gb[:, None]
        free[:, :n_srv, 2] = pool_gb[:, None]
        free[:, n_srv, :] = -_INF
        placed = np.full((n0, self._n_slots), -1, np.int32)
        migrated = np.zeros((n0, self._n_slots), bool)
        rejects = np.zeros(n0, np.int64)
        cand_events = 0
        io, st = self._checkpoint_io(reject_cap, server_gb, pool_gb,
                                     ckpt)
        start_shard = 0
        if st is not None:
            free, placed, migrated = (st["free"], st["placed"],
                                      st["migrated"])
            rejects = st["rejects"]
            start_shard = int(st["shard_idx"])
            io.shards_done = int(st["shards_done"])
        debug = sweep_core.invariants_enabled()
        if debug:
            self._debug_check_events()
            # representative server per group: every member mirrors the
            # group's free pool, so column 2 of the first member IS it
            firsts = np.unique(self.group_of, return_index=True)[1]
        rec = obs.get_recorder()
        for si in range(start_shard, self.n_shards):
            shard = self._shards[si]
            with rec.span("stream.shard", shard=si, backend="numpy"):
                _np_stream_sweep(shard, self._gcols, free, placed,
                                 migrated, rejects)
            cand_events += len(shard["kind"]) * n0
            if debug:
                self._debug_check_carry(
                    free[:, :n_srv, 0],
                    server_gb[:, None] - free[:, :n_srv, 1],
                    pool_gb[:, None] - free[:, firsts, 2], si)
            if io is not None:
                io.tick(lambda: {
                    "shard_idx": si + 1, "free": free, "placed": placed,
                    "migrated": migrated, "rejects": rejects,
                    "shards_done": io.shards_done})
            if reject_cap is not None and (rejects > reject_cap).all():
                rec.count("stream.reject_cap_exits")
                break
        if io is not None:
            io.done()
        _count_sweep(t0, self.n_events, cand_events)
        return rejects / max(self.n_vms, 1)

    # ------------------------------------------------------------- fleet --
    @obs.traced("stream.fleet")
    def reject_rates_fleet(self, server_gb, pod_gb, topology,
                           reject_cap: int | None = None,
                           state_dtype: str | None = None) -> np.ndarray:
        """Fleet reject rates, streamed shard by shard.

        Same candidate contract as
        :meth:`CompiledReplay.reject_rates_fleet`; the pod carry (now
        including the per-pod used-pool matrix and the granting-pod
        slot array) threads between shards exactly like the single-pool
        streaming sweep — device-resident, as row 0 of a K=1
        :class:`CompiledReplayStreamBatch`, on integral-GB input; a
        float64 numpy shard sweep otherwise.  With ``reject_cap`` set
        the stream stops early once EVERY lane exceeds the cap (exact
        counts so far — the usual feasibility-test lower-bound
        contract).
        """
        sgb, caps, topos = _fleet_candidates(server_gb, pod_gb, topology)
        if topos[0].n_servers != self.n_servers:
            raise ValueError(
                f"topology covers {topos[0].n_servers} servers; stream "
                f"has {self.n_servers}")
        if not self.n_events:
            return np.zeros(len(sgb))
        if not _on_device(self._exact):
            return self._fleet_sweep_numpy(sgb, caps, topos, reject_cap)
        return self._as_batch()._fleet_rates(sgb, caps, topos, reject_cap,
                                             state_dtype)[0]

    def _fleet_sweep_numpy(self, sgb, caps, topos, reject_cap):
        t0 = time.perf_counter()
        n0 = len(sgb)
        inc, _ = _fleet_incidence(topos, self.n_servers, self.n_servers)
        state = _np_fleet_state(n0, self.n_servers, self.cores_per_server,
                                sgb, caps, self._n_slots)
        cand_events = 0
        rec = obs.get_recorder()
        for si in range(self.n_shards):
            shard = self._shards[si]
            with rec.span("stream.fleet.shard", shard=si,
                          backend="numpy"):
                _np_fleet_sweep(shard, inc, *state)
            cand_events += len(shard["kind"]) * n0
            if reject_cap is not None and (state[-1] > reject_cap).all():
                rec.count("stream.reject_cap_exits")
                break
        _count_sweep(t0, self.n_events, cand_events)
        return state[-1] / max(self.n_vms, 1)


# ----------------------------------------------------------- trace batch ---
def _validate_cluster_shape(engines, what: str):
    """One batch requires one cluster shape (the vmapped sweep shares
    the group map and state padding across rows)."""
    if not engines:
        raise ValueError(f"{what} needs >= 1 engine")
    e0 = engines[0]
    shape = (e0.n_servers, e0.n_groups, e0.cores_per_server)
    for e in engines[1:]:
        if (e.n_servers, e.n_groups, e.cores_per_server) != shape:
            raise ValueError(
                "all traces in a batch must share one cluster shape; "
                f"got {(e.n_servers, e.n_groups, e.cores_per_server)} "
                f"vs {shape}")


def _batch_pick_state_dtype(engines, sgb_i: np.ndarray,
                            pgb_i: np.ndarray) -> str:
    """int16 only when EVERY trace row packs safely: a vmapped sweep
    shares one state dtype across the batch, so any row that needs
    int32 (payload headroom, migrate-pool deficit) forces the whole
    batch to int32.  Bit-exactness is unaffected either way — int16 is
    only ever picked where it is provably equivalent."""
    if all(e._pick_state_dtype(sgb_i[i], pgb_i[i]) == "int16"
           for i, e in enumerate(engines)):
        return "int16"
    return "int32"


def _broadcast_candidates(k: int, server_gb, pool_gb):
    """Normalize candidates to float ``(K, n_cand)`` arrays: 1-D inputs
    are shared across traces, 2-D inputs give per-trace grids (the
    shape the lockstep searches need)."""
    s = np.atleast_1d(np.asarray(server_gb, float))
    p = np.atleast_1d(np.asarray(pool_gb, float))
    s, p = np.broadcast_arrays(s, p)
    if s.ndim == 1:
        s = np.broadcast_to(s, (k,) + s.shape)
        p = np.broadcast_to(p, (k,) + p.shape)
    if s.ndim != 2 or s.shape[0] != k:
        raise ValueError(
            f"candidates must be 1-D (shared) or ({k}, n_cand) "
            f"per-trace; got shape {s.shape}")
    return np.ascontiguousarray(s), np.ascontiguousarray(p)


class CompiledReplayBatch:
    """K compiled traces priced side by side in one padded event tensor.

    Stacks the per-trace slot-mapped event streams of K
    :class:`CompiledReplay` engines (same cluster shape required) into a
    ``(K, E_max)`` tensor — shorter traces pad with no-op events — and
    sweeps all traces' candidate batches in a single vmapped ``lax.scan``.
    Candidate capacities may be shared across traces (1-D) or per-trace
    (``(K, n_cand)``, the shape lockstep searches need).

    Bit-exactness contract: row ``k`` of :meth:`reject_rates` equals
    ``engines[k].reject_rates(...)`` bit-for-bit — padding events are
    no-ops and each candidate's int32 replay is independent of its batch
    neighbors (asserted in ``tests/test_replay_engine.py``).

    Usage::

        engines = [CompiledReplay(vms_k, dec_k, cfg) for ...]
        batch = CompiledReplayBatch(engines)
        rates = batch.reject_rates([200., 300.], [100., 100.])  # (K, 2)
    """

    def __init__(self, engines):
        _validate_cluster_shape(engines, "CompiledReplayBatch")
        e0 = engines[0]
        self.engines = list(engines)
        self.k = len(engines)
        self.n_servers = e0.n_servers
        self.cores_per_server = e0.cores_per_server
        self.n_vms = np.array([e.n_vms for e in engines], np.int64)
        self.n_events = np.array([e.n_events for e in engines], np.int64)
        self._exact = all(e._exact for e in engines)
        self._jax_batch = None
        self._jax_batch_fail = None
        self._jax_host = None
        self._jax_placed = None

    def _jax_batch_host(self):
        """Host-side (K, E_max) stacked int32 event columns + metadata;
        built once, shared by every device placement."""
        if self._jax_host is not None:
            return self._jax_host
        per = [e._jax_events() for e in self.engines]
        e_max = max(p[0][0].shape[0] for p in per)
        n_slots = max(p[2] for p in per)
        s_pad, g_pad = per[0][3], per[0][4]
        fills = (PAD, 0, 0, 0, 0, 0)     # kind pads with no-op events
        cols = []
        for j, fill in enumerate(fills):
            col = np.full((self.k, e_max), fill, np.int32)
            for i, p in enumerate(per):
                col[i, :p[0][j].shape[0]] = p[0][j]
            cols.append(col)
        self._jax_host = (cols, per[0][1], n_slots, s_pad, g_pad)
        return self._jax_host

    def _jax_batch_events(self):
        """Stack per-trace padded event streams to one (K, E_max) tensor."""
        if self._jax_batch is not None:
            return self._jax_batch
        with obs.get_recorder().span("batch.upload"):
            cols, group, n_slots, s_pad, g_pad = self._jax_batch_host()
            self._jax_batch = (
                tuple(sweep_core.device_put(c) for c in cols),
                sweep_core.device_put(group), n_slots, s_pad, g_pad)
        return self._jax_batch

    def _jax_batch_placed(self, mesh, k_pad, row_sharded):
        """Sharded placement of the stacked tensor: trace rows padded to
        ``k_pad`` with no-op events and row-sharded over ``mesh``
        (trace plan) or replicated (lane plan).  One placement is kept
        at a time, keyed by mesh + layout."""
        key = (mesh, k_pad, row_sharded)
        if self._jax_placed is not None and self._jax_placed[0] == key:
            return self._jax_placed[1]
        with obs.get_recorder().span("batch.upload"):
            cols, group, n_slots, s_pad, g_pad = self._jax_batch_host()
            fills = (PAD, 0, 0, 0, 0, 0)
            sh = (sweep_core.named_sharding(mesh, "shard") if row_sharded
                  else sweep_core.named_sharding(mesh))
            streams = []
            for col, fill in zip(cols, fills):
                if k_pad > self.k:
                    col = np.concatenate([col, np.full(
                        (k_pad - self.k, col.shape[1]), fill, np.int32)])
                streams.append(sweep_core.device_put(col, sh))
            data = (tuple(streams),
                    sweep_core.device_put(group,
                                          sweep_core.named_sharding(mesh)),
                    n_slots, s_pad, g_pad)
        self._jax_placed = (key, data)
        return data

    def _pick_state_dtype(self, sgb_i: np.ndarray,
                          pgb_i: np.ndarray) -> str:
        return _batch_pick_state_dtype(self.engines, sgb_i, pgb_i)

    @obs.traced("batch.reject_rates")
    def reject_rates(self, server_gb, pool_gb,
                     reject_cap: int | None = None,
                     state_dtype: str | None = None,
                     devices=None) -> np.ndarray:
        """Reject fraction per (trace, candidate): shape ``(K, n_cand)``.

        ``devices`` shards the vmapped sweep over a JAX device mesh
        (``"all"``, an int, or a device list): the K-trace axis when
        ``K >= n_devices`` (rows pad to a multiple of the mesh size
        with no-op traces), else the candidate-lane axis.  Bit-exact
        (==) vs single-device; ignored by the numpy sweep.

        ``server_gb``/``pool_gb`` broadcast like the single-trace API and
        additionally accept ``(K, n_cand)`` per-trace candidate grids.
        When every trace's decisions are integral GBs, all K traces
        price in ONE vmapped integer ``lax.scan``; otherwise each trace
        runs its float64 numpy sweep (same bit-exact rates, just K
        sweeps instead of one).

        The batched carry packs to int16 when every trace's capacities
        permit (the keyed ``sweep_core`` cache compiles one vmapped
        sweep per state dtype — the old module-global batch sweep was
        pinned to int32); ``state_dtype`` forces a packing for tests.
        ``reject_cap`` is accepted for engine interchangeability with
        the streaming batch: the monolithic vmapped sweep always
        returns exact rates (which satisfy the same feasibility-test
        contract), while the numpy sweeps take the cap.
        """
        server_gb, pool_gb = _broadcast_candidates(self.k, server_gb,
                                                   pool_gb)
        if not _on_device(self._exact):
            return np.stack([
                eng._reject_rates_numpy(server_gb[i], pool_gb[i],
                                        reject_cap)
                for i, eng in enumerate(self.engines)])
        return self._reject_rates(server_gb, pool_gb, state_dtype,
                                  devices)

    def _reject_rates(self, server_gb, pool_gb, state_dtype,
                      devices) -> np.ndarray:
        """The plain XLA driver over ``(K, n_cand)`` candidates: one
        vmapped sweep per candidate chunk."""
        n0 = server_gb.shape[1]
        t0 = time.perf_counter()
        rejects = np.empty((self.k, n0), np.int64)
        sgb_i, pgb_i = sweep_core.quantize_capacities(server_gb, pool_gb)
        dt_name = state_dtype or self._pick_state_dtype(sgb_i, pgb_i)
        np_dt = sweep_core.state_np_dtype(dt_name)
        devs = sweep_core.resolve_devices(devices)
        # trace plan: split the K rows over the mesh (pad K up to a
        # mesh-size multiple with no-op traces); a small batch on a big
        # mesh splits the candidate-lane axis instead
        plan = None
        k_pad = self.k
        tr_mesh = sh_row = None
        if devs is not None:
            plan = "trace" if self.k >= len(devs) else "lane"
        if plan == "trace":
            n_use = min(len(devs), self.k)
            tr_mesh = sweep_core.shard_mesh(devs[:n_use])
            sh_row = sweep_core.named_sharding(tr_mesh, "shard")
            k_pad = -(-self.k // n_use) * n_use
            evs, group_of, n_slots, s_pad, g_pad = \
                self._jax_batch_placed(tr_mesh, k_pad, True)
        else:
            evs, group_of, n_slots, s_pad, g_pad = \
                self._jax_batch_events()
        rec = obs.get_recorder()
        steps = int(self.n_events.max(initial=0))
        for lo, hi, width in sweep_core.candidate_chunks(n0):
            kc = hi - lo
            mesh = sh_state = sh_slot = sh_cap = None
            evs_m, group_m = evs, group_of
            if plan == "trace":
                mesh = tr_mesh
                sh_state = sweep_core.named_sharding(mesh)
                sh_slot = sh_state
                sh_cap = sh_row
            elif plan == "lane":
                n_lane = sweep_core.lane_shard_count(width, len(devs))
                if n_lane >= 2:
                    mesh = sweep_core.shard_mesh(devs[:n_lane])
                    sh_state = sweep_core.named_sharding(mesh, "shard")
                    sh_slot = sweep_core.named_sharding(mesh, None,
                                                        "shard")
                    sh_cap = sh_slot
                    evs_m, group_m = self._jax_batch_placed(
                        mesh, self.k, False)[:2]
            sweep = sweep_core.get_sweep(
                dt_name, batched=True, mesh=mesh,
                shard_axis="trace" if plan == "trace" else "lane")
            sgb, pgb = sweep_core.lane_capacities(sgb_i, pgb_i, lo, hi,
                                                  width, np_dt)
            if k_pad > self.k:      # no-op rows reuse the last real grid
                sgb = np.concatenate(
                    [sgb, np.repeat(sgb[-1:], k_pad - self.k, 0)])
                pgb = np.concatenate(
                    [pgb, np.repeat(pgb[-1:], k_pad - self.k, 0)])
            # the all-free initial state is SHARED across traces
            # (broadcast by the vmap), so no leading trace axis here
            fc0, um0, up0, slots0, _ = sweep_core.init_state(
                width, self.n_servers, self.cores_per_server, s_pad,
                g_pad, n_slots, np_dt)
            args = tuple(sweep_core.device_put(a, sh) for a, sh in zip(
                (fc0, um0, up0, slots0, sgb, pgb),
                (sh_state, sh_state, sh_state, sh_slot, sh_cap, sh_cap)))
            with rec.span("batch.compute"):
                out = sweep(evs_m, group_m, *args)
                rejects[:, lo:hi] = np.asarray(out)[:self.k, :kc]
            rec.count("sweep.steps", steps)
            if rec.enabled:
                # the initial state is shared: one lane set in, K out
                _count_scanned(rec, kc,
                               sum(e._ev_kind.count(ARRIVE)
                                   for e in self.engines),
                               int(self.n_events.sum()), self.n_servers,
                               self.engines[0].n_groups, 1 + self.k)
        rates = rejects / np.maximum(self.n_vms, 1)[:, None]
        _count_sweep(t0, steps, int(self.n_events.sum()) * n0)
        return rates

    # ------------------------------------------------------------- fleet --
    @obs.traced("batch.fleet")
    def reject_rates_fleet(self, server_gb, pod_gb, topology,
                           state_dtype: str | None = None,
                           devices=None) -> np.ndarray:
        """Fleet reject rates per (trace, candidate): ``(K, n_cand)``.

        The candidate grid — ``(server_gb, pod capacities, topology)``
        lanes per :func:`_fleet_candidates` — is SHARED across traces
        (one topology frontier, K traces), matching the batched pod
        sweep's shared incidence tensor.  Row ``k`` equals
        ``engines[k].reject_rates_fleet(...)`` bit-for-bit.
        ``devices`` shards the K-trace axis over a device mesh (rows
        pad with no-op traces), bit-exact vs single-device.
        """
        sgb, caps, topos = _fleet_candidates(server_gb, pod_gb, topology)
        if topos[0].n_servers != self.n_servers:
            raise ValueError(
                f"topology covers {topos[0].n_servers} servers; batch "
                f"has {self.n_servers}")
        if not _on_device(self._exact):
            return np.stack([eng._fleet_rates_numpy(sgb, caps, topos)
                             for eng in self.engines])
        return self._fleet_rates(sgb, caps, topos, state_dtype, devices)

    def _fleet_rates(self, sgb, caps, topos, state_dtype,
                     devices=None) -> np.ndarray:
        """The fleet XLA driver over normalized lanes (see
        :func:`_fleet_candidates`): one vmapped pod sweep per candidate
        chunk."""
        t0 = time.perf_counter()
        n0 = len(sgb)
        devs = sweep_core.resolve_devices(devices)
        mesh = sh_row = sh_rep = None
        k_pad = self.k
        if devs is not None:
            n_use = min(len(devs), self.k)
            if n_use >= 2:
                mesh = sweep_core.shard_mesh(devs[:n_use])
                sh_row = sweep_core.named_sharding(mesh, "shard")
                sh_rep = sweep_core.named_sharding(mesh)
                k_pad = -(-self.k // n_use) * n_use
        if mesh is not None:
            evs, _group_of, n_slots, s_pad, _g_pad = \
                self._jax_batch_placed(mesh, k_pad, True)
        else:
            evs, _group_of, n_slots, s_pad, _g_pad = \
                self._jax_batch_events()
        rejects = np.empty((self.k, n0), np.int64)
        inc, p_max = _fleet_incidence(topos, self.n_servers, s_pad)
        sgb_i, _ = sweep_core.quantize_capacities(sgb, np.zeros(n0))
        caps_i = np.clip(np.floor(caps), -sweep_core.I32_BIG,
                         sweep_core.I32_BIG)
        if state_dtype is not None:
            dt_name = state_dtype
        elif all(sweep_core.pick_pod_state_dtype(
                self.cores_per_server, self.n_servers, sgb_i, caps_i,
                e._pay_mem_max, e._pay_pool_max, e._mig_pool_sum,
                p_max) == "int16" for e in self.engines):
            dt_name = "int16"
        else:
            dt_name = "int32"
        np_dt = sweep_core.state_np_dtype(dt_name)
        p_pad = sweep_core.pad_up(p_max, sweep_core.LANE_PAD)
        pgb_i = np.zeros((n0, p_pad))
        pgb_i[:, :caps_i.shape[1]] = caps_i
        sweep = sweep_core.get_pod_sweep(dt_name, batched=True,
                                         mesh=mesh)
        rec = obs.get_recorder()
        steps = int(self.n_events.max(initial=0))
        for lo, hi, width in sweep_core.candidate_chunks(n0):
            kc = hi - lo
            sgb_w, pgb_w, inc_w = sweep_core.pod_lane_arrays(
                sgb_i, pgb_i, inc, lo, hi, width, np_dt)
            # shared init state (broadcast by the vmap), shared
            # incidence; capacities gain the per-trace leading axis
            fc0, um0, up0, slots0, pods0, _ = sweep_core.init_pod_state(
                width, self.n_servers, self.cores_per_server, s_pad,
                p_pad, n_slots, np_dt)
            args = tuple(sweep_core.device_put(a, sh_rep) for a in
                         (inc_w, fc0, um0, up0, slots0, pods0)) + tuple(
                sweep_core.device_put(
                    np.broadcast_to(a, (k_pad,) + a.shape).copy(), sh_row)
                for a in (sgb_w, pgb_w))
            with rec.span("batch.compute"):
                out = sweep(evs, *args)
                rejects[:, lo:hi] = np.asarray(out)[:self.k, :kc]
            rec.count("sweep.steps", steps)
        rates = rejects / np.maximum(self.n_vms, 1)[:, None]
        _count_sweep(t0, steps, int(self.n_events.sum()) * n0)
        return rates

    def _jax_batch_events_fail(self):
        """Stack the per-trace 8-stream failure event tensors (each
        trace's OWN merged schedule) to ``(K, E_max)``; padding events
        are no-ops (kind PAD, domain -1)."""
        if self._jax_batch_fail is not None:
            return self._jax_batch_fail
        with obs.get_recorder().span("batch.upload"):
            per = [e._jax_events_fail() for e in self.engines]
            e_max = max(p[0][0].shape[0] for p in per)
            n_slots = max(p[2] for p in per)
            s_pad, g_pad = per[0][3], per[0][4]
            fills = (PAD, 0, 0, 0, 0, 0, 0, -1)
            streams = []
            for j, fill in enumerate(fills):
                col = np.full((self.k, e_max), fill, np.int32)
                for i, p in enumerate(per):
                    col[i, :p[0][j].shape[0]] = p[0][j]
                streams.append(sweep_core.device_put(col))
        self._jax_batch_fail = (tuple(streams),
                                sweep_core.device_put(per[0][1]), n_slots,
                                s_pad, g_pad)
        return self._jax_batch_fail

    @obs.traced("batch.availability")
    def availability(self, server_gb, pool_gb,
                     mitigation: str = "remigrate",
                     state_dtype: str | None = None) -> AvailabilityResult:
        """Failure-priced sweep over all K (trace, schedule) rows at
        once: one vmapped scan per candidate chunk.

        Every engine must carry its own ``failure_schedule`` (rows may
        differ — e.g. one failure rate per row, the
        ``benchmarks/fig_availability.py`` frontier axis).  Returns an
        :class:`AvailabilityResult` whose arrays are ``(K, n_cand)``;
        ``n_failures`` is the per-trace ``(K,)`` count and the
        per-failure distribution is not materialized (schedules differ
        in length across rows — use the single-trace
        :meth:`CompiledReplay.availability` for it).  Row ``k`` is
        bit-exact vs ``engines[k].availability(...)``.
        """
        for i, e in enumerate(self.engines):
            if e.failure_schedule is None:
                raise ValueError(
                    f"engine {i} has no failure_schedule; the batched "
                    "availability sweep needs one per trace")
        server_gb, pool_gb = _broadcast_candidates(self.k, server_gb,
                                                   pool_gb)
        if not _on_device(self._exact):
            per = [eng._availability_oracle(server_gb[i], pool_gb[i],
                                            mitigation, False)
                   for i, eng in enumerate(self.engines)]
            return AvailabilityResult(
                reject_rate=np.stack([r.reject_rate for r in per]),
                affected=np.stack([r.affected for r in per]),
                killed=np.stack([r.killed for r in per]),
                remigrated=np.stack([r.remigrated for r in per]),
                lost_vm_minutes=np.stack([r.lost_vm_minutes
                                          for r in per]),
                n_failures=np.array([r.n_failures for r in per]),
                affected_per_failure=None, mitigation=mitigation)
        return self._availability(server_gb, pool_gb, mitigation,
                                  state_dtype, per_failure=False)

    def _availability(self, server_gb, pool_gb, mitigation, state_dtype,
                      per_failure: bool) -> AvailabilityResult:
        """The failure XLA driver over ``(K, n_cand)`` candidates.  With
        ``per_failure`` the result holds each trace's ``(n_failures,
        n_cand)`` VMs-affected-per-failure array, in a list."""
        t0 = time.perf_counter()
        n0 = server_gb.shape[1]
        evs, group_of, n_slots, s_pad, g_pad = \
            self._jax_batch_events_fail()
        sgb_i, pgb_i = sweep_core.quantize_capacities(server_gb, pool_gb)
        dt_name = state_dtype or self._pick_state_dtype(sgb_i, pgb_i)
        np_dt = sweep_core.state_np_dtype(dt_name)
        sweep = sweep_core.get_fail_sweep(dt_name, mitigation,
                                          batched=True,
                                          with_dist=per_failure)
        out = {key: np.empty((self.k, n0), np.int64) for key in
               ("rejects", "affected", "killed", "remig", "lost")}
        dist = fail_pos = None
        if per_failure:
            fail_pos = [np.flatnonzero(np.asarray(e._ev_kind) == FAIL)
                        for e in self.engines]
            dist = [np.empty((len(fp), n0), np.int64) for fp in fail_pos]
        rec = obs.get_recorder()
        steps = int(self.n_events.max(initial=0))
        for lo, hi, width in sweep_core.candidate_chunks(n0):
            kc = hi - lo
            sgb, pgb = sweep_core.lane_capacities(sgb_i, pgb_i, lo, hi,
                                                  width, np_dt)
            # unlike the plain batched sweep the initial state carries
            # a leading trace axis: the vmapped failure carry includes
            # per-trace slot payload records
            fc0, um0, up0, slots0, _ = sweep_core.init_state(
                width, self.n_servers, self.cores_per_server, s_pad,
                g_pad, n_slots, np_dt, k=self.k)
            fstate = sweep_core.init_fail_state(n_slots, g_pad,
                                                k=self.k)
            args = tuple(sweep_core.device_put(a) for a in
                         (fc0, um0, up0, slots0) + fstate + (sgb, pgb))
            with rec.span("batch.compute"):
                res = sweep(evs, group_of, *args)
                for key, a in zip(("rejects", "affected", "killed",
                                   "remig", "lost"), res[:5]):
                    out[key][:, lo:hi] = np.asarray(a)[:, :kc]
                if per_failure:
                    ys = np.asarray(res[5])
                    for i, fp in enumerate(fail_pos):
                        dist[i][:, lo:hi] = ys[i][fp, :kc]
            rec.count("sweep.steps", steps)
        _count_sweep(t0, steps, int(self.n_events.sum()) * n0)
        return AvailabilityResult(
            reject_rate=out["rejects"] / np.maximum(self.n_vms,
                                                    1)[:, None],
            affected=out["affected"], killed=out["killed"],
            remigrated=out["remig"], lost_vm_minutes=out["lost"],
            n_failures=np.array([e.failure_schedule.n_failures
                                 for e in self.engines]),
            affected_per_failure=dist, mitigation=mitigation)


# -------------------------------------------------- streaming trace batch ---
class CompiledReplayStreamBatch:
    """K streaming replays priced side by side, one vmapped scan per shard.

    Composes the trace-batch axis of :class:`CompiledReplayBatch` with
    the bounded-memory sharding of :class:`CompiledReplayStream`: the K
    streams' index-aligned padded shards stack into ONE ``(K, E_shard)``
    event tensor per shard index (streams built with one
    ``max_events_per_shard`` budget shard on the same event grid, so
    aligned shards cover comparable time windows; shorter streams pad
    with no-op events), and a PER-TRACE packed carry — free cores, used
    local/pool GB, slot array, reject counters, each with a leading
    trace axis — threads shard-to-shard through a single vmapped
    ``lax.scan``.  A K-seed Azure-scale sweep therefore costs one pass
    over the shard axis instead of K, while at most two stacked shard
    batches are ever materialized (shard i computing while shard i+1
    stacks + uploads on the double-buffer worker): steady-state
    event-tensor memory is
    ``peak_shard_bytes = K * 6 * 4 * shard_pad_events`` (transiently
    2x), set by the budget and trace count, independent of trace
    length.

    Bit-exactness contract: row ``k`` of :meth:`reject_rates` equals
    ``streams[k].reject_rates(...)`` — and hence the monolithic
    :class:`CompiledReplay` — bit-for-bit: padding events are no-ops
    and each (trace, candidate) lane replays independently of its batch
    neighbors (``tests/test_replay_stream.py`` asserts this on the
    fixture and a 100k-VM trace, both sweep paths and both state dtypes).
    The carry is placed with ``jax.device_put`` and donated back to the
    sweep, so it stays device-resident across shards (``chip_smoke.py``
    runs this path on a TPU).

    Usage (K seeds past the monolithic memory ceiling)::

        streams = [CompiledReplayStream(vms_k, dec_k, cfg,
                                        max_events_per_shard=250_000)
                   for ...]
        batch = CompiledReplayStreamBatch(streams)
        rates = batch.reject_rates([300., 350.], [512., 256.])  # (K, 2)

    ``cluster_sim.savings_analysis_batched`` builds this automatically
    once any trace of a batch runs past its ``max_events_per_shard``
    budget, so the lockstep provisioning searches
    (``search_min_multi``/``pool_search_multi``) stream transparently.
    """

    def __init__(self, streams):
        _validate_cluster_shape(streams, "CompiledReplayStreamBatch")
        s0 = streams[0]
        self.engines = list(streams)           # searches read .engines
        self.k = len(streams)
        self.n_servers = s0.n_servers
        self.n_groups = s0.n_groups
        self.cores_per_server = s0.cores_per_server
        self.n_vms = np.array([s.n_vms for s in streams], np.int64)
        self.n_events = np.array([s.n_events for s in streams], np.int64)
        self._exact = all(s._exact for s in streams)
        self.n_shards = max((s.n_shards for s in streams), default=0)
        self.shard_pad_events = max(
            (s.shard_pad_events for s in streams if s.n_shards), default=0)
        #: the longest trace's real events in each stacked shard: the
        #: steps of that shard's scan
        self.shard_steps = [
            max(s.shard_events[si] for s in streams if si < s.n_shards)
            for si in range(self.n_shards)]
        #: device footprint of ONE stacked shard batch (6 int32 streams
        #: x K traces) — THE quantity the composed engine bounds
        self.peak_shard_bytes = self.k * 6 * 4 * self.shard_pad_events
        self._n_slots = max(s._n_slots for s in streams)
        self._s_pad, self._g_pad = s0._s_pad, s0._g_pad
        self._group_np = s0._group_np

    def peak_pool_demand(self) -> np.ndarray:
        """Per-trace naive concurrent pool-demand peak (feasible upper
        bracket for the lockstep pool searches)."""
        return np.array([s.peak_pool_demand() for s in self.engines])

    def _pick_state_dtype(self, sgb_i: np.ndarray,
                          pgb_i: np.ndarray) -> str:
        return _batch_pick_state_dtype(self.engines, sgb_i, pgb_i)

    def _stacked_shard_host(self, si: int, k_pad: int):
        """Builder for one ``(k_pad, shard_pad_events)`` stacked int32
        event tensor — runs on the upload worker so host packing
        overlaps device compute.

        Built per sweep call per shard index — never cached — so at
        most two stacked shard batches (the one computing and the one
        uploading) exist at a time; rows of streams with fewer than
        ``si + 1`` shards, and device-padding rows past ``self.k``, are
        all no-ops.
        """
        e = self.shard_pad_events

        def build():
            cols = {key: np.zeros((k_pad, e), np.int32)
                    for key in ("slot", "c", "l", "p", "m")}
            cols["kind"] = np.full((k_pad, e), PAD, np.int32)
            for i, s in enumerate(self.engines):
                if si >= s.n_shards:
                    continue
                sh = s._shards[si]
                n = len(sh["kind"])
                for key, dst in cols.items():
                    dst[i, :n] = sh[key]
            return tuple(cols[key] for key in
                         ("kind", "slot", "c", "l", "p", "m"))

        return build

    def _carry_from_snaps(self, refs, boundary, width, k_pad, np_dt,
                          dt_name):
        """Stacked per-trace carry at a shard boundary: each real row
        holds its stream's reference snapshot (clamped to the stream's
        own shard count — trailing alignment shards are no-ops), and
        device-padding rows start from the plain init state."""
        rows = [_carry_from_snap(
            refs[i]["snaps"][min(boundary, s.n_shards)], width,
            self.n_servers, self.n_groups, self._s_pad, self._g_pad,
            self._n_slots, np_dt, dt_name)
            for i, s in enumerate(self.engines)]
        if k_pad > self.k:
            pad_row = sweep_core.init_state(
                width, self.n_servers, self.cores_per_server,
                self._s_pad, self._g_pad, self._n_slots, np_dt)
            rows.extend([pad_row] * (k_pad - self.k))
        return tuple(np.stack([r[j] for r in rows]) for j in range(5))

    @obs.traced("stream_batch.reject_rates")
    def reject_rates(self, server_gb, pool_gb,
                     reject_cap: int | None = None,
                     state_dtype: str | None = None,
                     checkpoint: "CheckpointSpec | None" = None,
                     devices=None,
                     skip_windows: bool = True) -> np.ndarray:
        """Reject fraction per (trace, candidate): shape ``(K, n_cand)``.

        Candidates broadcast like :meth:`CompiledReplayBatch.reject_rates`
        (1-D shared or ``(K, n_cand)`` per-trace grids).  One pass over
        the shard axis prices every trace's candidate batch, threading
        the batched carry between shards.  With ``reject_cap`` set the
        stream stops early once EVERY (trace, candidate) lane exceeds
        the cap — each reported rate is then its exact count so far, a
        lower bound satisfying the usual feasibility-test contract
        (callers must pass a cap covering every trace's tolerance, i.e.
        ``max_i floor(tol_i * n_vms_i)``).  Non-integral decisions loop
        the per-stream float64 shard sweeps instead — same bit-exact
        rates, K passes instead of one.

        ``devices`` shards the K-trace axis over a JAX device mesh
        (rows pad to a mesh-size multiple with no-op traces) or, for a
        single stream, its candidate-lane axis; bit-exact vs
        single-device either way.  Shard i+1's host stacking + upload always
        pipelines with shard i's scan (obs spans ``stream.upload`` /
        ``stream.compute``), so transient peak event memory is
        ``2 * peak_shard_bytes``.  ``skip_windows`` (default on) skips
        leading shards no (trace, candidate) lane can diverge on,
        seeding the carry from per-trace reference snapshots — bit-exact
        vs the unskipped sweep (without ``reject_cap``; with a cap both
        paths meet the same lower-bound contract).

        ``checkpoint`` snapshots the batched carry + cursors like the
        single-stream engine (resume is bit-identical and adapts across
        differing ``devices`` row padding); the numpy sweeps derive
        one per-stream spec per row (``<path>.k<i>``).
        ``POND_DEBUG_INVARIANTS=1`` verifies the per-trace carry after
        every shard.
        """
        server_gb, pool_gb = _broadcast_candidates(self.k, server_gb,
                                                   pool_gb)
        if not self.n_shards:
            return np.zeros((self.k, server_gb.shape[1]))
        if not _on_device(self._exact):
            return np.stack([
                s._sweep_numpy(server_gb[i], pool_gb[i], reject_cap,
                               None if checkpoint is None
                               else dataclasses.replace(
                                   checkpoint,
                                   path=f"{checkpoint.path}.k{i}"))
                for i, s in enumerate(self.engines)])
        return self._reject_rates(server_gb, pool_gb, reject_cap,
                                  state_dtype, checkpoint, devices,
                                  skip_windows)

    def _reject_rates(self, server_gb, pool_gb, reject_cap, state_dtype,
                      checkpoint, devices, skip_windows) -> np.ndarray:
        """The plain streaming XLA driver over ``(K, n_cand)``
        candidates: per candidate chunk, one vmapped carry sweep per
        shard."""
        t0 = time.perf_counter()
        rec = obs.get_recorder()
        n0 = server_gb.shape[1]
        sgb_i, pgb_i = sweep_core.quantize_capacities(server_gb, pool_gb)
        dt_name = state_dtype or self._pick_state_dtype(sgb_i, pgb_i)
        np_dt = sweep_core.state_np_dtype(dt_name)
        devs = sweep_core.resolve_devices(devices)
        mesh = sh_row = sh_rep = lane_devs = None
        k_pad = self.k
        if devs is not None:
            n_use = min(len(devs), self.k)
            if n_use >= 2:
                mesh = sweep_core.shard_mesh(devs[:n_use])
                sh_row = sweep_core.named_sharding(mesh, "shard")
                sh_rep = sweep_core.named_sharding(mesh)
                k_pad = -(-self.k // n_use) * n_use
            else:           # one trace: split its candidate lanes instead
                lane_devs = devs
        sh_carry, sh_cap, sh_ev = (sh_row,) * 5, sh_row, sh_row
        if lane_devs is None:
            sweep = sweep_core.get_sweep(dt_name, with_carry=True,
                                         batched=True, mesh=mesh,
                                         shard_axis="trace")
            group_j = sweep_core.device_put(self._group_np, sh_rep)
        refs = None
        if skip_windows and self._exact:
            refs = [_stream_reference(s) for s in self.engines]
            if not all(r is not None for r in refs):
                refs = None
        rejects = np.empty((self.k, n0), np.int64)
        cand_events = 0
        io = None
        start_chunk = start_shard = 0
        resumed = None
        if checkpoint is not None:
            io = _CheckpointIO(checkpoint, _sweep_fingerprint(
                "jax-batch", dt_name, self.n_events, self.n_shards,
                self.n_vms, reject_cap, server_gb, pool_gb))
            st = io.load()
            if st is not None:
                start_chunk, start_shard = (int(st["chunk_idx"]),
                                            int(st["shard_idx"]))
                rejects[:, :int(st["n_done"])] = st["rejects_done"]
                resumed = tuple(st[f"carry{j}"] for j in range(5))
                io.shards_done = int(st["shards_done"])
        debug = sweep_core.invariants_enabled()
        if debug:
            for s in self.engines:
                s._debug_check_events()
        pool = _upload_pool()
        for ci, (lo, hi, width) in enumerate(
                sweep_core.candidate_chunks(n0)):
            if ci < start_chunk:
                continue
            kc = hi - lo
            if lane_devs is not None:
                n_lane = sweep_core.lane_shard_count(width, len(lane_devs))
                mesh = (sweep_core.shard_mesh(lane_devs[:n_lane])
                        if n_lane >= 2 else None)
                sh_carry, sh_cap, sh_ev = _lane_shardings(mesh)
                sweep = sweep_core.get_sweep(dt_name, with_carry=True,
                                             batched=True, mesh=mesh,
                                             shard_axis="lane")
                group_j = sweep_core.device_put(self._group_np, sh_ev)
            sgb, pgb = sweep_core.lane_capacities(sgb_i, pgb_i, lo, hi,
                                                  width, np_dt)
            if k_pad > self.k:      # no-op rows reuse the last real grid
                sgb = np.concatenate(
                    [sgb, np.repeat(sgb[-1:], k_pad - self.k, 0)])
                pgb = np.concatenate(
                    [pgb, np.repeat(pgb[-1:], k_pad - self.k, 0)])
            if resumed is not None:
                carry0 = _pad_carry_rows(
                    resumed, k_pad, sweep_core.init_state(
                        width, self.n_servers, self.cores_per_server,
                        self._s_pad, self._g_pad, self._n_slots, np_dt,
                        k=k_pad))
                shard_from, resumed = start_shard, None
            elif refs is not None:
                # divergence window: skip shards no (trace, lane) pair
                # can diverge on, seeding per-trace boundary snapshots
                shard_from = min(
                    _skip_count(r, sgb_i[i, lo:hi].min(),
                                pgb_i[i, lo:hi].min(), self.n_shards)
                    for i, r in enumerate(refs))
                carry0 = self._carry_from_snaps(refs, shard_from, width,
                                                k_pad, np_dt, dt_name)
                if shard_from and rec.enabled:
                    rec.count("stream.shards_skipped", shard_from)
                    rec.count(
                        "stream.events_skipped",
                        shard_from * self.k * self.shard_pad_events
                        * width)
            else:
                # PER-TRACE carry (leading K axis), donated
                # shard-to-shard
                carry0 = sweep_core.init_state(
                    width, self.n_servers, self.cores_per_server,
                    self._s_pad, self._g_pad, self._n_slots, np_dt,
                    k=k_pad)
                shard_from = 0
            carry = tuple(sweep_core.device_put(a, sh)
                          for a, sh in zip(carry0, sh_carry))
            sgb_j = sweep_core.device_put(sgb, sh_cap)
            pgb_j = sweep_core.device_put(pgb, sh_cap)
            fut = None
            if shard_from < self.n_shards:
                fut = pool.submit(
                    _upload_job, self._stacked_shard_host(shard_from,
                                                          k_pad), sh_ev)
            end = self.n_shards
            for si in range(shard_from, self.n_shards):
                with rec.span("stream_batch.shard", shard=si, chunk=ci):
                    with rec.span("stream.upload_wait", shard=si):
                        evs, up0, up1, nbytes = fut.result()
                    if rec.enabled:
                        rec.add_span("stream.upload", up0, up1, shard=si)
                        rec.count("device_put.calls", 6)
                        rec.count("device_put.bytes", nbytes)
                    if si + 1 < self.n_shards:
                        # double buffering: stack + upload shard i+1
                        # while shard i's scan runs
                        fut = pool.submit(
                            _upload_job,
                            self._stacked_shard_host(si + 1, k_pad),
                            sh_ev)
                    with rec.span("stream.compute", shard=si):
                        carry = sweep(evs, group_j, *carry, sgb_j, pgb_j)
                        if rec.enabled:
                            carry[0].block_until_ready()
                    rec.count("sweep.steps", self.shard_steps[si])
                    if rec.enabled:
                        live = [s for s in self.engines if si < s.n_shards]
                        _count_scanned(
                            rec, kc,
                            sum(s.shard_arrivals[si] for s in live),
                            sum(s.shard_events[si] for s in live),
                            self.n_servers, self.n_groups, 2 * self.k)
                cand_events += self.k * self.shard_pad_events * width
                if debug:
                    sweep_core.check_invariants(
                        np.asarray(carry[0]), np.asarray(carry[1]),
                        np.asarray(carry[2]),
                        n_servers=self.n_servers,
                        cores_per_server=self.cores_per_server,
                        shard=si,
                        up_slack=max(s._mig_pool_sum
                                     for s in self.engines))
                if io is not None:
                    io.tick(lambda: {
                        "chunk_idx": ci, "shard_idx": si + 1,
                        "n_done": lo, "rejects_done": rejects[:, :lo],
                        "shards_done": io.shards_done,
                        **{f"carry{j}": np.asarray(c)
                           for j, c in enumerate(carry)}})
                if reject_cap is not None:
                    rej_now = np.asarray(carry[4])[:self.k, :kc]
                    if (rej_now > reject_cap).all():
                        rec.count("stream.reject_cap_exits")
                        end = si + 1
                        break               # every lane decided
            _count_unscanned(rec, self.shard_steps, shard_from, end)
            rejects[:, lo:hi] = np.asarray(carry[4])[:self.k, :kc]
            _count_out_devices(rec, carry[4])
        if io is not None:
            io.done()
        rates = rejects / np.maximum(self.n_vms, 1)[:, None]
        _count_sweep(t0, int(self.n_events.max(initial=0)), cand_events)
        return rates

    # ------------------------------------------------------------- fleet --
    @obs.traced("stream_batch.fleet")
    def reject_rates_fleet(self, server_gb, pod_gb, topology,
                           reject_cap: int | None = None,
                           state_dtype: str | None = None,
                           devices=None) -> np.ndarray:
        """Fleet reject rates per (trace, candidate): ``(K, n_cand)``,
        one vmapped pod scan per stacked shard.

        The fleet candidate grid is SHARED across traces (like
        :meth:`CompiledReplayBatch.reject_rates_fleet`); the per-trace
        pod carry threads shard-to-shard.  Row ``k`` equals
        ``streams[k].reject_rates_fleet(...)`` bit-for-bit; with
        ``reject_cap`` the stream stops once every (trace, candidate)
        lane exceeds the cap.  ``devices`` shards the K-trace axis over
        a device mesh (no-op padding rows), bit-exact vs single-device;
        shard uploads double-buffer with the scan like the plain path.
        """
        sgb, caps, topos = _fleet_candidates(server_gb, pod_gb, topology)
        if topos[0].n_servers != self.n_servers:
            raise ValueError(
                f"topology covers {topos[0].n_servers} servers; batch "
                f"has {self.n_servers}")
        if not self.n_shards:
            return np.zeros((self.k, len(sgb)))
        if not _on_device(self._exact):
            return np.stack([s._fleet_sweep_numpy(sgb, caps, topos,
                                                  reject_cap)
                             for s in self.engines])
        return self._fleet_rates(sgb, caps, topos, reject_cap,
                                 state_dtype, devices)

    def _fleet_rates(self, sgb, caps, topos, reject_cap, state_dtype,
                     devices=None) -> np.ndarray:
        """The streaming fleet XLA driver over normalized lanes: per
        candidate chunk, one vmapped pod carry sweep per shard."""
        t0 = time.perf_counter()
        n0 = len(sgb)
        rec = obs.get_recorder()
        rejects = np.empty((self.k, n0), np.int64)
        inc, p_max = _fleet_incidence(topos, self.n_servers, self._s_pad)
        sgb_i, _ = sweep_core.quantize_capacities(sgb, np.zeros(n0))
        caps_i = np.clip(np.floor(caps), -sweep_core.I32_BIG,
                         sweep_core.I32_BIG)
        if state_dtype is not None:
            dt_name = state_dtype
        elif all(sweep_core.pick_pod_state_dtype(
                self.cores_per_server, self.n_servers, sgb_i, caps_i,
                s._pay_mem_max, s._pay_pool_max, s._mig_pool_sum,
                p_max) == "int16" for s in self.engines):
            dt_name = "int16"
        else:
            dt_name = "int32"
        np_dt = sweep_core.state_np_dtype(dt_name)
        p_pad = sweep_core.pad_up(p_max, sweep_core.LANE_PAD)
        pgb_i = np.zeros((n0, p_pad))
        pgb_i[:, :caps_i.shape[1]] = caps_i
        devs = sweep_core.resolve_devices(devices)
        mesh = sh_row = sh_rep = None
        k_pad = self.k
        if devs is not None:
            n_use = min(len(devs), self.k)
            if n_use >= 2:
                mesh = sweep_core.shard_mesh(devs[:n_use])
                sh_row = sweep_core.named_sharding(mesh, "shard")
                sh_rep = sweep_core.named_sharding(mesh)
                k_pad = -(-self.k // n_use) * n_use
        sweep = sweep_core.get_pod_sweep(dt_name, with_carry=True,
                                         batched=True, mesh=mesh)
        cand_events = 0
        pool = _upload_pool()
        for lo, hi, width in sweep_core.candidate_chunks(n0):
            kc = hi - lo
            sgb_w, pgb_w, inc_w = sweep_core.pod_lane_arrays(
                sgb_i, pgb_i, inc, lo, hi, width, np_dt)
            # PER-TRACE carry (leading K axis), donated shard-to-shard;
            # the incidence tensor stays shared across traces
            carry = tuple(sweep_core.device_put(a, sh_row)
                          for a in sweep_core.init_pod_state(
                              width, self.n_servers,
                              self.cores_per_server, self._s_pad,
                              p_pad, self._n_slots, np_dt, k=k_pad))
            inc_j = sweep_core.device_put(inc_w, sh_rep)
            sgb_j = sweep_core.device_put(
                np.broadcast_to(sgb_w, (k_pad,) + sgb_w.shape).copy(),
                sh_row)
            pgb_j = sweep_core.device_put(
                np.broadcast_to(pgb_w, (k_pad,) + pgb_w.shape).copy(),
                sh_row)
            fut = pool.submit(_upload_job,
                              self._stacked_shard_host(0, k_pad), sh_row)
            end = self.n_shards
            for si in range(self.n_shards):
                with rec.span("stream_batch.fleet.shard", shard=si):
                    with rec.span("stream.upload_wait", shard=si):
                        evs, up0, up1, nbytes = fut.result()
                    if rec.enabled:
                        rec.add_span("stream.upload", up0, up1, shard=si)
                        rec.count("device_put.calls", 6)
                        rec.count("device_put.bytes", nbytes)
                    if si + 1 < self.n_shards:
                        fut = pool.submit(
                            _upload_job,
                            self._stacked_shard_host(si + 1, k_pad),
                            sh_row)
                    with rec.span("stream.compute", shard=si):
                        carry = sweep(evs, inc_j, *carry, sgb_j, pgb_j)
                        if rec.enabled:
                            carry[0].block_until_ready()
                    rec.count("sweep.steps", self.shard_steps[si])
                cand_events += self.k * self.shard_pad_events * width
                if reject_cap is not None:
                    rej_now = np.asarray(carry[5])[:self.k, :kc]
                    if (rej_now > reject_cap).all():
                        rec.count("stream.reject_cap_exits")
                        end = si + 1
                        break
            _count_unscanned(rec, self.shard_steps, 0, end)
            rejects[:, lo:hi] = np.asarray(carry[5])[:self.k, :kc]
        rates = rejects / np.maximum(self.n_vms, 1)[:, None]
        _count_sweep(t0, int(self.n_events.max(initial=0)), cand_events)
        return rates


# ---------------------------------------------------------------- search ---
def _dyadic_nodes(lo: float, hi: float, depth: int, nodes: list) -> None:
    """Append the depth-k tree of bisection midpoints of ``[lo, hi]``,
    computed with the same ``0.5 * (lo + hi)`` float arithmetic the
    scalar search uses (pre-order, so replays walk it bit-for-bit)."""
    m = 0.5 * (lo + hi)
    nodes.append(m)
    if depth > 1:
        _dyadic_nodes(lo, m, depth - 1, nodes)
        _dyadic_nodes(m, hi, depth - 1, nodes)


def search_min_batched(feasible, lo: float, hi: float,
                       tol_frac: float = 0.02, depth: int = 4) -> float:
    """Batched replica of the scalar ``cluster_sim._search_min`` bisection.

    Reject rates near the feasibility boundary are NOT perfectly monotone
    (placement cascades), so a different probe sequence can legitimately
    land on a different feasible point.  To keep results bit-identical to
    the scalar oracle search, each round evaluates the full depth-k tree
    of dyadic bisection midpoints (computed with the same ``0.5*(lo+hi)``
    float arithmetic the scalar uses) in ONE batched sweep — round 1 also
    prices ``hi`` itself — then walks the k bisection decisions locally.
    One sweep thus advances k sequential bisection steps.

    Usage (least feasible uniform server DRAM)::

        eng = CompiledReplay(vms, decisions, cfg)
        gb = search_min_batched(
            lambda g: eng.reject_rates(g, big_pool) <= tol, 0.0, 768.0)
    """
    nodes: list[float] = []
    first = True
    while (hi - lo) > tol_frac * max(hi, 1.0) or first:
        nodes.clear()
        _dyadic_nodes(lo, hi, depth, nodes)
        probes = nodes + [hi] if first else list(nodes)
        feas = np.asarray(feasible(np.array(probes)))
        if first:
            if not feas[-1]:
                return hi
            first = False
        fmap = dict(zip(probes, feas.tolist()))
        for _ in range(depth):
            if (hi - lo) <= tol_frac * max(hi, 1.0):
                break
            mid = 0.5 * (lo + hi)
            if fmap[mid]:
                hi = mid
            else:
                lo = mid
    return hi


def pool_search_batched(engine, server_grid: np.ndarray,
                        big_pool: float, tol: float, tol_frac: float = 0.02,
                        width: int = 12,
                        reject_cap: int | None = None) -> np.ndarray:
    """Minimum feasible pool_gb for EVERY server-size point, in lockstep.

    Replaces the per-point independent binary searches with a batched
    bracketing search.  The infinite-pool trajectory at each server size
    (already cached by the engine) supplies the starting bracket for
    free: its peak pool demand is always feasible (the replay never
    diverges from it), and its reject count decides outright whether the
    point is feasible at any pool size.  Each round then evaluates
    ``width`` interior points for every unconverged point in ONE sweep.
    Because the required pool is monotone (non-increasing) in server_gb,
    every round warm-starts each point's bracket from its neighbors:
    upper brackets propagate left-to-right (``min.accumulate`` over
    increasing server sizes) and lower brackets right-to-left.  Points
    infeasible even at ``big_pool`` return ``big_pool``.

    ``engine`` may also be a :class:`CompiledReplayStream` (the path
    ``savings_analysis`` takes past the shard budget): streams keep no
    Python reference trajectories, so the upper bracket comes from the
    vectorized ``peak_pool_demand`` prefix-sum bound instead (one extra
    sweep decides which grid points are infeasible outright), like the
    multi-trace search.

    Usage (pool frontier over a server-size grid)::

        grid = np.linspace(min_server, base_gb, 7)
        pool = pool_search_batched(eng, grid, big_pool=12288.0, tol=0.01)
    """
    server_grid = np.asarray(server_grid, float)
    n_pts = len(server_grid)
    denom = max(engine.n_vms, 1)
    lo = np.zeros(n_pts)
    hi = np.empty(n_pts)
    if isinstance(engine, CompiledReplayStream):
        hi[:] = min(float(big_pool), engine.peak_pool_demand())
        infeasible = engine.reject_rates(
            server_grid, hi, reject_cap=reject_cap) > tol
    else:
        infeasible = np.zeros(n_pts, bool)
        for i, sgb in enumerate(server_grid):
            traj = engine._trajectory(float(sgb))
            hi[i] = min(float(big_pool),
                        float(traj.need_pool.max(initial=0.0)))
            infeasible[i] = traj.total_rejects / denom > tol
    fracs = np.arange(1, width + 1) / (width + 1.0)
    while True:
        # neighbor warm start between FEASIBLE points only: an infeasible
        # point's (meaningless) brackets must not clamp its neighbors'
        prop_hi = np.minimum.accumulate(np.where(infeasible, _INF, hi))
        hi = np.where(infeasible, hi, np.minimum(hi, prop_hi))
        prop_lo = np.maximum.accumulate(
            np.where(infeasible, -_INF, lo)[::-1])[::-1]
        lo = np.where(infeasible, lo, np.maximum(lo, prop_lo))
        active = ~infeasible & ((hi - lo) > tol_frac * np.maximum(hi, 1.0))
        if not active.any():
            break
        ai = np.flatnonzero(active)
        grids = lo[ai, None] + (hi - lo)[ai, None] * fracs[None, :]
        r = engine.reject_rates(
            np.repeat(server_grid[ai], width), grids.ravel(),
            reject_cap=reject_cap).reshape(len(ai), width)
        f = r <= tol
        for j, i in enumerate(ai):
            row = f[j]
            if row.any():
                k = int(np.argmax(row))
                if k > 0:
                    lo[i] = grids[j, k - 1]
                hi[i] = grids[j, k]
            else:
                lo[i] = grids[j, -1]
    hi[infeasible] = big_pool
    return hi


# ------------------------------------------------- multi-trace searches ---
def search_min_multi(feasible, lo, hi, tol_frac: float = 0.02,
                     depth: int = 4) -> np.ndarray:
    """K independent ``_search_min`` bisections advanced in lockstep.

    Per-trace replica of :func:`search_min_batched`: each round builds
    every unconverged trace's depth-k dyadic probe tree (round 1 also
    prices each trace's ``hi``) and evaluates ALL trees in one call to
    ``feasible`` — with a :class:`CompiledReplayBatch` behind it, that is
    one vmapped event sweep per round instead of K.  Each trace's probe
    sequence (and thus its result) is bit-identical to running the
    scalar bisection on that trace alone.  Traces infeasible at ``hi``
    return ``hi``.

    ``feasible`` maps a ``(K, n_probes)`` capacity array to ``(K,
    n_probes)`` bools, e.g.::

        base_gb = search_min_multi(
            lambda g: batch.reject_rates(g, 0.0) <= tol[:, None],
            np.zeros(batch.k), np.full(batch.k, 768.0))
    """
    lo = np.array(lo, float)
    hi = np.array(hi, float)
    k = len(lo)
    n_nodes = 2 ** depth - 1
    done = np.zeros(k, bool)
    first = True
    while True:
        active = ~done & ((hi - lo) > tol_frac * np.maximum(hi, 1.0))
        if first:
            active = ~done
        if not active.any():
            break
        nodes = np.empty((k, n_nodes))
        for i in range(k):
            # converged rows re-price their frozen tree (uniform probe
            # width keeps the sweep one rectangular batch); their
            # brackets are no longer updated
            row: list[float] = []
            _dyadic_nodes(float(lo[i]), float(hi[i]), depth, row)
            nodes[i] = row
        probes = np.concatenate([nodes, hi[:, None]], 1) if first else nodes
        feas = np.asarray(feasible(probes))
        if first:
            done |= ~feas[:, -1]          # infeasible even at hi
            first = False
        for i in np.flatnonzero(active & ~done):
            fmap = dict(zip(probes[i].tolist(), feas[i].tolist()))
            for _ in range(depth):
                if (hi[i] - lo[i]) <= tol_frac * max(hi[i], 1.0):
                    break
                mid = 0.5 * (float(lo[i]) + float(hi[i]))
                if fmap[mid]:
                    hi[i] = mid
                else:
                    lo[i] = mid
    return hi


def pool_search_multi(batch, server_grids,
                      big_pool: float, tol, tol_frac: float = 0.02,
                      width: int = 4,
                      reject_cap: int | None = None) -> np.ndarray:
    """Minimum feasible pool_gb per (trace, server-size) point, lockstep.

    Multi-trace analogue of :func:`pool_search_batched`: one bracketing
    search over a ``(K, n_pts)`` server grid, evaluating ``width``
    interior points for every point of every trace in ONE vmapped sweep
    per round.  Brackets start at ``[0, peak_pool_demand]`` per trace —
    a vectorized prefix-sum bound that replaces the per-trace trajectory
    replays of the single-trace search — and warm-start from neighbors
    within each trace (required pool is monotone non-increasing in
    server_gb).  Points infeasible even at the upper bracket return
    ``big_pool``.

    ``batch`` may be a :class:`CompiledReplayBatch` or a
    :class:`CompiledReplayStreamBatch` — the search only needs
    ``reject_rates`` plus per-engine ``peak_pool_demand``, so the
    lockstep rounds stream transparently past a shard budget.
    ``reject_cap`` (cover every trace's tolerance: ``max_i
    floor(tol_i * n_i)``) lets the streaming batch stop a round's sweep
    early once every lane is decided; the monolithic batch returns
    exact rates regardless, so the probe sequence — and the result —
    is identical either way.
    """
    sg = np.asarray(server_grids, float)
    if sg.ndim != 2 or sg.shape[0] != batch.k:
        raise ValueError(f"server_grids must be (K={batch.k}, n_pts); "
                         f"got {sg.shape}")
    k, n_pts = sg.shape
    tol = np.asarray(tol, float).reshape(k, 1)
    lo = np.zeros((k, n_pts))
    peaks = np.array([min(float(big_pool), e.peak_pool_demand())
                      for e in batch.engines])
    hi = np.broadcast_to(peaks[:, None], (k, n_pts)).copy()
    infeasible = batch.reject_rates(sg, hi, reject_cap=reject_cap) > tol
    fracs = np.arange(1, width + 1) / (width + 1.0)
    while True:
        prop_hi = np.minimum.accumulate(
            np.where(infeasible, _INF, hi), axis=1)
        hi = np.where(infeasible, hi, np.minimum(hi, prop_hi))
        prop_lo = np.maximum.accumulate(
            np.where(infeasible, -_INF, lo)[:, ::-1], axis=1)[:, ::-1]
        lo = np.where(infeasible, lo, np.maximum(lo, prop_lo))
        active = ~infeasible & ((hi - lo) > tol_frac * np.maximum(hi, 1.0))
        if not active.any():
            break
        # converged points re-price their frozen bracket: the sweep needs
        # one rectangular (K, n_pts * width) candidate block per round
        grids = lo[..., None] + (hi - lo)[..., None] * fracs
        r = batch.reject_rates(
            np.repeat(sg, width, axis=1),
            grids.reshape(k, n_pts * width),
            reject_cap=reject_cap).reshape(k, n_pts, width)
        f = r <= tol[:, :, None]
        for i in range(k):
            for j in np.flatnonzero(active[i]):
                row = f[i, j]
                if row.any():
                    q = int(np.argmax(row))
                    if q > 0:
                        lo[i, j] = grids[i, j, q - 1]
                    hi[i, j] = grids[i, j, q]
                else:
                    lo[i, j] = grids[i, j, -1]
    hi[infeasible] = big_pool
    return hi
