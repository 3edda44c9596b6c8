"""Compiled policy engine: batched Pond prediction pipeline (§4.3–4.4).

``cluster_sim.policy_decisions`` used to walk every VM through the
scalar :class:`~repro.core.control_plane.ControlPlane` — a per-VM GBM
prediction, a per-VM ``np.percentile`` over the customer's untouched
history, and a per-VM QoS check.  With the replay side compiled
(``core/replay_engine.py``), that Python loop was the last hot path
between the trace loaders and the provisioning searches.  This module
vectorizes the entire decide→place→monitor→mitigate pipeline:

* **struct-of-arrays traces** — ``traces.vm_table`` compiles a VM list
  into column arrays once; every stage below reads whole columns.
* **history percentiles from one rank structure** — the per-customer
  untouched-memory history grows by one observation per VM
  (``record_untouched``), and the UM features need ``np.percentile`` of
  every PREFIX of that stream.  ``_prefix_percentiles`` ranks every
  customer's seed+append values with one sort, answers all prefixes'
  order statistics from one wavelet matrix over those ranks in
  O(M log M), then applies numpy's exact linear-interpolation lerp —
  including its ``gamma >= 0.5`` branch — so every feature is
  bit-identical to the scalar walk's ``np.percentile`` call.
* **batched model inference** — one ``predict_proba_batch`` call scores
  every VM's latency-sensitivity probability (bit-matching the per-VM
  ``p_sensitive(pmu[None])`` calls, see ``predictors/forest.py``) and
  one ``UntouchedMemoryModel.predict`` call prices every VM's untouched
  quantile (row-bitwise by construction, see ``predictors/gbm.py``).
* **vectorized QoS monitoring** — spill detection, sensitivity
  sampling and migration-time assignment (``t = arrival + 60``) are
  array ops; the control plane's monitor/mitigation state is updated to
  the same end state the scalar loop produces.

Bit-exactness contract: for the ``local``, ``static`` and ``pond``
policies, :func:`policy_decisions_compiled` reproduces the scalar
``cluster_sim.policy_decisions`` decision-for-decision — ``local_gb``,
``pool_gb``, ``fully_pooled``, ``t_migrate``, the misprediction rate
(accumulated in the scalar's float order) and the control plane's
post-run history/mitigation state — asserted across trace seeds in
``tests/test_policy_engine.py``.  The result is a
:class:`PolicyDecisions` struct-of-arrays that
``replay_engine.CompiledReplay`` (and the stream) compile natively, so
no per-VM ``VMDecision`` objects are materialized on the hot path.

On top of the single-policy pipeline, the **grid axis** prices many
policy settings at once: :func:`grid_decisions` evaluates a list of
:class:`PolicySetting` (tau, pdm, li-threshold / fp-target) against a
trace batch with the features and forest probabilities computed ONCE
and the tau axis priced in one vmapped multi-GBM call
(``gbm.predict_gbms_jax``); ``benchmarks/fig17_sensitivity.py`` feeds
the resulting decision grid straight into
``cluster_sim.savings_analysis_batched(decisions=...)`` to reproduce
the paper's model-error-sensitivity curves in a single run.

Usage::

    dec = policy_engine.policy_decisions_compiled(
        vms, "pond", control_plane=cp)          # PolicyDecisions (SoA)
    eng = replay_engine.CompiledReplay(vms, dec, cfg)

    settings = policy_engine.make_grid(taus=(0.05, 0.2), pdms=(0.05,),
                                       li_thresholds=(0.05, 0.5))
    grid = policy_engine.grid_decisions([vms], settings, li, um_models,
                                        history)   # [setting][trace]
"""
from __future__ import annotations

import dataclasses
import itertools
import math

import numpy as np

from repro.core import obs, qos, traces

#: quantiles of the customer history used as UM-model features
#: (``traces.metadata_features``)
_QS = (80.0, 90.0, 95.0, 99.0)
_PRIOR = 0.5          # no-history feature prior
_MIN_HIST_FEAT = 3    # metadata_features' hardcoded history floor
_MONITOR_DELAY = 60.0  # scalar loop samples QoS at arrival + 60s


# ------------------------------------------------------------- decisions ---
@dataclasses.dataclass
class PolicyDecisions:
    """Struct-of-arrays pendant of ``list[cluster_sim.VMDecision]``.

    ``t_migrate`` uses NaN for "no QoS migration".  The replay engine
    compiles this form natively (``CompiledReplay``/``Stream`` read the
    arrays directly); :meth:`as_vmdecisions` materializes the legacy
    object list for the scalar oracle path.
    """
    local_gb: np.ndarray      # (N,) float64
    pool_gb: np.ndarray       # (N,) float64
    fully_pooled: np.ndarray  # (N,) bool
    t_migrate: np.ndarray     # (N,) float64, NaN = none
    mispredictions: float = 0.0
    n_mitigations: int = 0

    def __len__(self) -> int:
        return len(self.local_gb)

    @property
    def n_migrations(self) -> int:
        """Number of compiled MIGRATE events this decision set emits."""
        return int(np.isfinite(self.t_migrate).sum())

    def slice(self, lo: int, hi: int) -> "PolicyDecisions":
        """Rows ``[lo, hi)`` as a new SoA (zero-copy numpy views).

        The chunked-ingestion path of the streaming replay engines
        takes a per-chunk ``decide(chunk)`` callback: with decisions
        precomputed once for the whole trace (one compiled policy
        pass), the callback just slices this SoA at the running row
        offset — no per-VM decision objects are ever materialized::

            dec, _ = cluster_sim.policy_decisions(vms, "pond", cp,
                                                  as_arrays=True)
            off = [0]
            def decide(chunk):
                lo = off[0]; off[0] += len(chunk)
                return dec.slice(lo, off[0])
            stream = replay_engine.CompiledReplayStream(
                traces.iter_trace_chunks(path), None, cfg,
                max_events_per_shard=250_000, decide=decide)

        Aggregate fields (``mispredictions``, ``n_mitigations``) are
        trace-level, not per-row, so the slice resets them to zero.
        """
        return PolicyDecisions(self.local_gb[lo:hi],
                               self.pool_gb[lo:hi],
                               self.fully_pooled[lo:hi],
                               self.t_migrate[lo:hi])

    def as_vmdecisions(self) -> list:
        """Materialize ``cluster_sim.VMDecision`` objects (off the hot
        path: the scalar oracle and legacy callers index them)."""
        from repro.core.cluster_sim import VMDecision
        return [VMDecision(float(l), float(p), bool(f),
                           None if math.isnan(t) else float(t))
                for l, p, f, t in zip(self.local_gb, self.pool_gb,
                                      self.fully_pooled, self.t_migrate)]


def decisions_from_list(decisions) -> PolicyDecisions:
    """Pack a ``VMDecision`` sequence into :class:`PolicyDecisions`."""
    n = len(decisions)
    return PolicyDecisions(
        np.fromiter((d.local_gb for d in decisions), float, n),
        np.fromiter((d.pool_gb for d in decisions), float, n),
        np.fromiter((d.fully_pooled for d in decisions), bool, n),
        np.fromiter((np.nan if d.t_migrate is None else d.t_migrate
                     for d in decisions), float, n))


# --------------------------------------------------- history percentiles ---
def _np_lerp(a: np.ndarray, b: np.ndarray, t: np.ndarray) -> np.ndarray:
    """numpy's percentile lerp, branch for branch: ``a + (b-a)*t`` but
    ``b - (b-a)*(1-t)`` when ``t >= 0.5`` (the rewrite numpy applies for
    monotonicity).  Replicating the branch keeps the vectorized
    percentiles bit-identical to ``np.percentile``."""
    d = b - a
    out = a + d * t
    hi = t >= 0.5
    if hi.any():
        out = np.where(hi, b - d * (1.0 - t), out)
    return out


def _rank_levels(ranks: np.ndarray) -> list[np.ndarray]:
    """A wavelet matrix over ``ranks``, (M,) non-negative int32 values.

    One level per bit of the largest value, the most significant first.
    A level keeps the running count of 0-bits over its entries, an
    (M + 1,) int32 array, then stably moves its 0-bit entries before its
    1-bit ones to give the next level's order.
    """
    levels = []
    cur = ranks
    for b in range(int(ranks.max()).bit_length() - 1, -1, -1):
        zero = (cur >> b) & 1 == 0
        zeros = np.zeros(len(cur) + 1, np.int32)
        np.cumsum(zero, out=zeros[1:])
        levels.append(zeros)
        cur = np.concatenate([cur[zero], cur[~zero]])
    return levels


def _kth_smallest(levels: list[np.ndarray], lo: np.ndarray,
                  hi: np.ndarray, k: np.ndarray) -> np.ndarray:
    """The ``k``-th smallest (from 0) of ``ranks[lo:hi]`` for every
    query at once, on :func:`_rank_levels`' matrix: one gather-and-select
    step per level narrows each query's range to the entries that share
    the answer's bits so far."""
    out = np.zeros(np.shape(k), np.int32)
    for b, zeros in zip(range(len(levels) - 1, -1, -1), levels):
        zlo, zhi = zeros[lo], zeros[hi]
        n0 = zhi - zlo                  # 0-bit entries in the range
        one = k >= n0
        lo = np.where(one, zeros[-1] + lo - zlo, zlo)
        hi = np.where(one, zeros[-1] + hi - zhi, zhi)
        k = np.where(one, k - n0, k)
        out |= one.astype(np.int32) << b
    return out


def _prefix_percentiles(customers: np.ndarray, untouched: np.ndarray,
                        history: dict | None,
                        qs=_QS) -> tuple[np.ndarray, np.ndarray]:
    """History length and feature percentiles for every VM of a trace.

    For VM ``i`` (trace order), the customer's history at decision time
    is its seeded sequence from ``history`` plus the ``untouched``
    observations of the customer's EARLIER VMs (the scalar loop appends
    via ``record_untouched`` after each decision).  Returns

    * ``n_hist``  — (N,) history length at decision time, and
    * ``percs``   — (N, len(qs)) float64, ``np.percentile(h, qs)``
      bit-for-bit where ``n_hist >= 3``, the 0.5 prior row elsewhere.

    Instead of re-sorting each prefix (the per-VM history walk), every
    customer's seed+append sequence is laid end to end in one array,
    whose values are ranked by one sort.  A wavelet matrix over the
    ranks (:func:`_rank_levels`) then answers the order statistics that
    numpy's linear interpolation needs, for every prefix at once
    (:func:`_kth_smallest`): O(M log M) for M values in all, whatever
    the customers' sizes.
    """
    cust = np.asarray(customers, np.int64)
    ut = np.asarray(untouched, float)
    n = len(cust)
    qf = np.asarray(qs, float) / 100.0
    percs = np.full((n, len(qf)), _PRIOR)
    n_hist = np.zeros(n, np.int64)
    if not n:
        return n_hist, percs
    hist = history or {}
    order = np.argsort(cust, kind="stable")
    uniq, counts = np.unique(cust[order], return_counts=True)
    seeds = [np.asarray(hist.get(int(c), ()), float) for c in uniq]
    ns = np.array([len(s) for s in seeds], np.int64)
    # every customer's sequence end to end, seed first, then its VMs in
    # trace order: customer u holds positions [start[u], start[u] + size[u])
    size = ns + counts
    start = np.cumsum(size) - size
    groups = np.split(order, np.cumsum(counts)[:-1])
    vals = np.concatenate([part for s, g in zip(seeds, groups)
                           for part in (s, ut[g])])
    u = np.repeat(np.arange(len(uniq)), counts)     # customer of order[i]
    nh = ns[u] + np.arange(n) - np.repeat(np.cumsum(counts) - counts,
                                          counts)
    n_hist[order] = nh
    rows = nh >= _MIN_HIST_FEAT
    if not rows.any():
        return n_hist, percs
    nj = nh[rows]
    vi = qf[None, :] * (nj[:, None] - 1)        # same op as np.percentile
    lo = np.floor(vi)
    gamma = vi - lo
    # each value's rank within its customer: sorted by (customer, value),
    # customer u's values fill its own positions, so the ranks need only
    # as many bits as the largest customer has values
    owner = np.repeat(np.arange(len(uniq)), size)
    by_value = np.lexsort((vals, owner))
    ranks = np.empty(len(vals), np.int32)
    ranks[by_value] = np.arange(len(vals), dtype=np.int32) \
        - start[owner].astype(np.int32)
    # ranks lo and lo + 1 of each VM's history [start[u], start[u] + nh)
    k = lo.astype(np.int32)[..., None] + np.arange(2, dtype=np.int32)
    first = start[u[rows]].astype(np.int32)[:, None, None]
    end = first + nj.astype(np.int32)[:, None, None]
    at = _kth_smallest(_rank_levels(ranks), np.broadcast_to(first, k.shape),
                       np.broadcast_to(end, k.shape), k)
    v = vals[by_value[first + at]]
    percs[order[rows]] = _np_lerp(v[..., 0], v[..., 1], gamma)
    return n_hist, percs


def metadata_features_compiled(table: traces.VMTable,
                               percs: np.ndarray) -> np.ndarray:
    """UM feature matrix from a :class:`~repro.core.traces.VMTable` and
    precomputed history percentiles — bit-identical to
    ``traces.metadata_features`` row by row (float64 columns cast to
    float32 exactly like ``np.asarray(rows, np.float32)``)."""
    cols = np.column_stack([
        percs,
        table.vm_type.astype(float), table.cores.astype(float),
        table.mem_gb, table.location.astype(float),
        table.guest_os.astype(float)])
    return cols.astype(np.float32)


# ----------------------------------------------------- compiled pipeline ---
def _sequential_mispred(full: np.ndarray, spill: np.ndarray,
                        harm: np.ndarray, spill_harm_prob: float,
                        n: int) -> float:
    """Misprediction rate accumulated in the scalar loop's float order.

    The scalar walk adds ``1.0`` (fully-pooled miss) or
    ``spill_harm_prob`` (overprediction) per offending VM in trace
    order; vectorized ``np.sum`` may differ in the last ulp for
    non-dyadic probabilities, so the few nonzero contributions are
    re-added sequentially (zeros contribute nothing in either path).
    """
    mis = 0.0
    c_full = full & harm
    c_spill = ~full & spill & harm
    for i in np.flatnonzero(c_full | c_spill):
        mis += 1.0 if c_full[i] else spill_harm_prob
    return mis / max(n, 1)


@obs.traced("policy.decisions")
def policy_decisions_compiled(vms, policy: str, control_plane=None,
                              static_pool_frac: float = 0.15,
                              latency: int = 182, pdm: float = 0.05,
                              spill_harm_prob: float = 0.25,
                              table: traces.VMTable | None = None
                              ) -> PolicyDecisions:
    """Vectorized ``cluster_sim.policy_decisions`` (bit-exact).

    One batched pass replaces the per-VM control-plane walk: history
    percentiles from one rank structure, one forest call for every VM's
    sensitivity probability, one GBM call for every untouched quantile,
    and vectorized QoS spill/mitigation sampling.  For the ``pond``
    policy the ``control_plane``'s state is advanced to the same end
    state as the scalar loop: per-customer histories extend in place
    (copy-on-first-write preserved), ``monitor.checks`` counts every
    pool-backed VM, and ``mitigation.log``/``.migrated`` gain the same
    entries in trace order.

    Requires unique ``vm_id``s (a trace invariant the loaders enforce).

    Usage::

        cp = ControlPlane(ControlPlaneConfig(li_threshold=0.05), li, um,
                          PoolManager(4096), history=dict(hist))
        dec = policy_decisions_compiled(vms, "pond", control_plane=cp)
        assert dec.n_mitigations == len(cp.mitigation.log)
    """
    table = table if table is not None else traces.vm_table(vms)
    n = len(table)
    mem = table.mem_gb
    slows = table.slow182 if latency == 182 else table.slow222
    t_mig = np.full(n, np.nan)
    fully = np.zeros(n, bool)
    n_mitig = 0

    if policy == "local":
        local, pool = mem.copy(), np.zeros(n)
    elif policy == "static":
        pool = np.floor(mem * static_pool_frac)
        local = mem - pool
    elif policy == "pond":
        cp = control_plane
        if cp is None:
            raise ValueError("the pond policy needs a control_plane")
        cfg = cp.cfg
        rec = obs.get_recorder()
        # decide: history percentiles + LI sensitivity + UM quantile
        # predictions -> local/pool split per VM
        with rec.span("policy.decide"):
            with rec.span("policy.percentiles"):
                n_hist, percs = _prefix_percentiles(table.customer,
                                                    table.untouched,
                                                    cp.history)
            rec.count("policy.percentile_rows",
                      int((n_hist >= _MIN_HIST_FEAT).sum()))
            if cp.li_model is not None:
                batch = getattr(cp.li_model, "p_sensitive_batch", None)
                p = (np.asarray(batch(table.pmu)) if batch is not None
                     else np.asarray(cp.li_model.p_sensitive(table.pmu)))
            else:
                p = np.ones(n)
            has_hist = (n_hist >= cfg.min_history_vms) \
                & (cp.li_model is not None)
            fully = has_hist & (p < cfg.li_threshold)
            if cp.um_model is not None:
                feat = metadata_features_compiled(table, percs)
                um = cp.um_model.predict(feat).astype(np.float64)
            else:
                um = np.zeros(n)
            pool = np.floor(um * mem)
            local = mem - pool
            pool[fully] = mem[fully]
            local[fully] = 0.0
        # place: every VM's untouched observation appends, per
        # customer in trace order (same end state as record_untouched)
        with rec.span("policy.place"):
            order = np.argsort(table.customer, kind="stable")
            bounds = np.flatnonzero(np.diff(table.customer[order])) + 1
            for g in np.split(order, bounds):
                cp.extend_untouched(int(table.customer[g[0]]),
                                    table.untouched[g].tolist())
        # monitor: every pool-backed VM is checked once at
        # arrival + 60s; spilled + predicted-sensitive ones migrate
        with rec.span("policy.monitor"):
            pool_pos = pool > 0
            spilled = fully | (pool > table.untouched * mem + 1e-9)
            prev = cp.mitigation.migrated
            not_prev = (~np.isin(table.vm_id,
                                 np.fromiter(prev, np.int64, len(prev)))
                        if prev else np.ones(n, bool))
            mitigate = pool_pos & spilled & not_prev \
                & (p >= cp.monitor.threshold)
            cp.monitor.checks += int(pool_pos.sum())
        with rec.span("policy.mitigate"):
            mi = np.flatnonzero(mitigate)
            t_mig[mi] = table.arrival[mi] + _MONITOR_DELAY
            for i in mi:
                cp.mitigation.migrate(int(table.vm_id[i]),
                                      float(pool[i]), float(t_mig[i]))
            n_mitig = len(mi)
    else:
        raise ValueError(policy)

    spill = pool > table.untouched * mem + 1e-9
    mispred = _sequential_mispred(fully, spill,
                                  qos.exceeds_pdm(slows, pdm),
                                  spill_harm_prob, n)
    return PolicyDecisions(local, pool, fully, t_mig, mispred, n_mitig)


# -------------------------------------------------------------- grid axis --
@dataclasses.dataclass
class PolicySetting:
    """One point of the (tau, pdm, li-threshold) policy grid.

    ``tau`` selects the untouched-memory quantile model (one fitted
    ``UntouchedMemoryModel`` per tau, see :func:`fit_um_grid`);
    ``li_threshold`` is the sensitivity-probability cut (derive one from
    an FP-rate budget with :func:`thresholds_for_fp`, the paper's FP
    knob); ``pdm`` is the slowdown margin the misprediction accounting
    charges against.
    """
    tau: float
    pdm: float = 0.05
    li_threshold: float = 0.05
    fp_target: float | None = None      # provenance when derived from FP

    @property
    def label(self) -> str:
        fp = "" if self.fp_target is None else f",fp={self.fp_target:g}"
        return (f"tau={self.tau:g},pdm={self.pdm:g},"
                f"li={self.li_threshold:g}{fp}")


def make_grid(taus=(0.05,), pdms=(0.05,), li_thresholds=(0.05,),
              fp_targets=None, li_model=None, pmu=None, slowdowns=None
              ) -> list[PolicySetting]:
    """Cartesian grid of :class:`PolicySetting`.

    With ``fp_targets`` given (instead of raw thresholds), each target
    resolves to the largest-LI threshold within the FP budget via
    ``li_model.threshold_for_fp`` on the supplied calibration set.
    """
    if fp_targets is not None:
        if li_model is None or pmu is None or slowdowns is None:
            raise ValueError("fp_targets need li_model + pmu + slowdowns "
                             "to calibrate thresholds")
        th = thresholds_for_fp(li_model, pmu, slowdowns, fp_targets)
        axis = list(zip(th, fp_targets))
    else:
        axis = [(float(t), None) for t in li_thresholds]
    return [PolicySetting(float(tau), float(pdm), float(th), fp)
            for tau, pdm, (th, fp)
            in itertools.product(taus, pdms, axis)]


def thresholds_for_fp(li_model, pmu: np.ndarray, slowdowns: np.ndarray,
                      fp_targets) -> list[float]:
    """Probability thresholds realizing each FP-rate budget (paper's
    Fig 17 knob): the largest-LI operating point with FP <= target."""
    return [float(li_model.threshold_for_fp(pmu, slowdowns, fp).threshold)
            for fp in fp_targets]


def fit_um_grid(meta_features: np.ndarray, untouched: np.ndarray, taus,
                seed: int = 0) -> dict:
    """One fitted ``UntouchedMemoryModel`` per unique tau."""
    from repro.core.predictors.models import UntouchedMemoryModel
    return {float(tau): UntouchedMemoryModel(float(tau)).fit(
        meta_features, untouched, seed=seed) for tau in set(taus)}


def grid_decisions(vms_list, settings, li_model, um_models: dict,
                   history: dict | None, min_history_vms: int = 3,
                   latency: int = 182, spill_harm_prob: float = 0.25,
                   backend: str = "numpy") -> list:
    """Price a whole policy grid against a trace batch in one pass.

    Returns ``out[s][k]`` — the :class:`PolicyDecisions` of setting
    ``settings[s]`` on trace ``vms_list[k]`` — with the shared work
    hoisted out of the grid: history percentiles and UM features are
    computed once per trace, the forest probabilities once over ALL
    traces' VMs (one batched call), and the tau axis priced either as
    one numpy ensemble walk per unique tau (``backend="numpy"``,
    bit-exact vs a scalar ``ControlPlane`` configured with the same
    setting) or as ONE vmapped multi-GBM XLA call over the stacked tau
    models (``backend="jax"``, float32-faithful; ``"auto"`` picks jax
    when importable).  Unlike :func:`policy_decisions_compiled` this
    never mutates shared state — each grid point sees the same seeded
    ``history``, exactly like pricing each setting on a fresh control
    plane.

    Usage (3 taus x 2 thresholds against 4 seeds, one call)::

        settings = make_grid(taus=(0.05, 0.1, 0.2), pdms=(0.05,),
                             li_thresholds=(0.05, 0.5))
        grid = grid_decisions(vms_list, settings, li, um_models, hist)
        flat_dec = [grid[s][k] for s in range(len(settings))
                    for k in range(len(vms_list))]
    """
    if not vms_list:
        return [[] for _ in settings]
    tables = [traces.vm_table(v) for v in vms_list]
    sizes = [len(t) for t in tables]
    splits = np.cumsum(sizes)[:-1]
    # per-trace history percentiles (each trace starts from the seed)
    per_trace = [_prefix_percentiles(t.customer, t.untouched, history)
                 for t in tables]
    n_hist = np.concatenate([nh for nh, _ in per_trace])
    feats = np.concatenate(
        [metadata_features_compiled(t, pc)
         for t, (_, pc) in zip(tables, per_trace)])
    pmu = np.concatenate([t.pmu for t in tables])
    if li_model is not None:
        batch = getattr(li_model, "p_sensitive_batch", None)
        p = (np.asarray(batch(pmu)) if batch is not None
             else np.asarray(li_model.p_sensitive(pmu)))
    else:
        p = np.ones(len(pmu))

    # tau axis: one prediction vector per unique tau over ALL VMs
    uniq_taus = sorted({s.tau for s in settings})
    if backend == "auto":
        try:
            import jax                               # noqa: F401
            backend = "jax"
        except Exception:                            # pragma: no cover
            backend = "numpy"
    if backend == "jax" and len(uniq_taus) > 1:
        from repro.core.predictors import gbm as G
        packed = G.pack_gbms([um_models[t].gbm for t in uniq_taus])
        raw = np.asarray(G.predict_gbms_jax(packed, feats))
        um_by_tau = {t: np.clip(raw[i], 0.0, 1.0).astype(np.float64)
                     for i, t in enumerate(uniq_taus)}
    else:
        um_by_tau = {t: um_models[t].predict(feats).astype(np.float64)
                     for t in uniq_taus}

    mem = np.concatenate([t.mem_gb for t in tables])
    untouched = np.concatenate([t.untouched for t in tables])
    arrival = np.concatenate([t.arrival for t in tables])
    slows = np.concatenate([(t.slow182 if latency == 182 else t.slow222)
                            for t in tables])
    has_hist_base = (n_hist >= min_history_vms) & (li_model is not None)

    out = []
    for s in settings:
        um = um_by_tau[s.tau]
        fully = has_hist_base & (p < s.li_threshold)
        pool = np.floor(um * mem)
        local = mem - pool
        pool[fully] = mem[fully]
        local[fully] = 0.0
        spill = pool > untouched * mem + 1e-9
        spilled = fully | spill
        mitigate = (pool > 0) & spilled & (p >= s.li_threshold)
        t_mig = np.where(mitigate, arrival + _MONITOR_DELAY, np.nan)
        harm = qos.exceeds_pdm(slows, s.pdm)
        row = []
        lo = 0
        for k, hi in enumerate([*splits, len(mem)]):
            sl = slice(lo, hi)
            mispred = _sequential_mispred(
                fully[sl], spill[sl], harm[sl], spill_harm_prob,
                sizes[k])
            row.append(PolicyDecisions(
                local[sl].copy(), pool[sl].copy(), fully[sl].copy(),
                t_mig[sl].copy(), mispred,
                int(np.isfinite(t_mig[sl]).sum())))
            lo = hi
        out.append(row)
    return out
