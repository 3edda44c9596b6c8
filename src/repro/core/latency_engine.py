"""Batched latency / QoS / zNUMA grid engine (Pond §4-§6 figure family).

The last scalar figure family — slowdown sensitivity (Fig 4), the CXL
latency model (Fig 7/8), zNUMA spill (Fig 15/16), the UM calibration
curve (Fig 18) and the Eq.(1) combined frontier (Fig 20) — rebuilt on
the grid machinery: every predicate evaluates over a (workload x
config) grid in one batched (and, for the event-driven spill sweep,
jitted ``lax.scan``) pass, **bit-exact** against the scalar seed code
kept as oracles:

* :func:`pond_latency_ns_grid` (+ switch-only / added / pct variants)
  == ``latency_model.pond_latency_ns`` looped — identical float-add
  order per element.
* :func:`slowdown_band_grid` == ``(s < t).mean()`` loops — bands count
  in integers, fractions divide on the host in float64 (numpy's bool
  mean is exactly count/size in float64).
* :func:`spill_grid` == replaying each ``(num_local, num_pool)`` config
  on ``znuma.ZNumaAllocator`` (:func:`scalar_spill_replay`): a
  ``lax.scan`` over alloc/free events carries per-lane free counters
  plus a (block x lane) tier map — integer state only, so the jax and
  numpy backends agree bitwise.  Config lanes pad to the sweep-core
  buckets (padding replicates the last config; results are sliced off).
* :func:`hierarchy_slowdown_grid` == ``TierHierarchy.slowdown_factor``
  looped (terms fold in tier order, matching the scalar accumulation) —
  and, through ``TierHierarchy.from_tier_model``, bit-identical to the
  two-tier ``TierModel.slowdown_factor``.
* :func:`li_curve_grid` / :func:`um_curve_grid` /
  :func:`combine_grid` == ``LatencySensitivityModel.curve`` /
  the Fig-18 tau loop / ``eqn1.combine`` — the combine grid flattens
  li-major so ``argmax`` reproduces the nested loop's first-strict-max
  tie-break.
* :func:`qos_mitigation_grid` / :func:`pdm_violation_grid` ==
  ``qos.QoSMonitor.check`` walks / the inclusive ``qos.exceeds_pdm``
  predicate over a PDM grid.

Every entry point takes ``backend="auto"|"jax"|"numpy"`` — "auto"
prefers jax when importable; both backends are parity-tested
(tests/test_latency_engine.py).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro.core import eqn1, qos, sweep_core
from repro.core.latency_model import (CXL_PORT_NS, EMC_CTRL_NS,
                                      NUMA_LOCAL_NS, RETIMER_NS, SWITCH_NS,
                                      TierHierarchy)
from repro.core.znuma import ZNumaAllocator

# spill-event kinds (pad events are no-ops on every lane)
ALLOC, FREE, PAD = 0, 1, 2


def _use_jax(backend: str) -> bool:
    if backend == "numpy":
        return False
    if backend == "jax":
        if not sweep_core.jax_importable():
            raise RuntimeError("jax backend requested but not importable")
        return True
    return sweep_core.jax_importable()


def _jnp_x64():
    """jax.numpy + the enable-x64 context: the float grids compare and
    accumulate in float64, matching the numpy oracles bitwise (jax
    defaults to float32 otherwise)."""
    import jax
    import jax.numpy as jnp
    return jnp, jax.enable_x64


# ------------------------------------------------- Fig 7/8 latency model --
def pond_latency_ns_grid(pool_sockets) -> np.ndarray:
    """Vectorized ``pond_latency_ns`` — identical add order per element."""
    s = np.asarray(pool_sockets)
    lat = np.full(s.shape, NUMA_LOCAL_NS + 2 * CXL_PORT_NS + EMC_CTRL_NS)
    lat = np.where(s > 8, lat + 2 * RETIMER_NS, lat)
    lat = np.where(s > 16, lat + (SWITCH_NS + 2 * RETIMER_NS), lat)
    lat = np.where(s > 32, lat + 2 * RETIMER_NS, lat)
    return lat


def switch_only_latency_ns_grid(pool_sockets) -> np.ndarray:
    s = np.asarray(pool_sockets)
    lat = np.full(s.shape, NUMA_LOCAL_NS + 2 * CXL_PORT_NS + EMC_CTRL_NS
                  + SWITCH_NS)
    for edge in (8, 16, 32):
        lat = np.where(s > edge, lat + 2 * RETIMER_NS, lat)
    return lat


def added_latency_ns_grid(pool_sockets) -> np.ndarray:
    return pond_latency_ns_grid(pool_sockets) - NUMA_LOCAL_NS


def latency_increase_pct_grid(pool_sockets) -> np.ndarray:
    return 100.0 * pond_latency_ns_grid(pool_sockets) / NUMA_LOCAL_NS


# -------------------------------------------------- Fig 4 slowdown bands --
def slowdown_band_grid(slow, lt=(0.01, 0.05), gt=(0.25,),
                       backend: str = "auto") -> np.ndarray:
    """Band fractions over a slowdown grid.

    ``slow``: (..., N) per-workload slowdowns (any number of leading
    batch axes: seeds, latencies, ...).  Returns (..., len(lt)+len(gt))
    float64 fractions — ``out[..., i] = (slow < lt[i]).mean(-1)`` then
    ``(slow > gt[j]).mean(-1)``, bit-exact vs the scalar means because
    the counts are integers and the division is a single float64 op.
    """
    slow = np.asarray(slow, np.float64)
    n = slow.shape[-1]
    lt_a = np.asarray(lt, np.float64)
    gt_a = np.asarray(gt, np.float64)
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            lo = jnp.sum(jnp.asarray(slow)[..., None, :]
                         < jnp.asarray(lt_a)[:, None], axis=-1)
            hi = jnp.sum(jnp.asarray(slow)[..., None, :]
                         > jnp.asarray(gt_a)[:, None], axis=-1)
            counts = np.concatenate([np.asarray(lo), np.asarray(hi)],
                                    axis=-1)
    else:
        lo = (slow[..., None, :] < lt_a[:, None]).sum(-1)
        hi = (slow[..., None, :] > gt_a[:, None]).sum(-1)
        counts = np.concatenate([lo, hi], axis=-1)
    return counts.astype(np.float64) / n


# --------------------------------------------- tier-hierarchy slowdowns --
def hierarchy_params(hierarchies) -> tuple[np.ndarray, np.ndarray]:
    """Stack (C,) hierarchies (equal depth) into ``(ratios, hits)``
    arrays for :func:`hierarchy_slowdown_grid`."""
    depths = {h.n_pool_tiers for h in hierarchies}
    if len(depths) != 1:
        raise ValueError(f"mixed hierarchy depths {sorted(depths)}")
    ratios = np.array([[h.latency_ratio(i + 1)
                        for i in range(h.n_pool_tiers)]
                       for h in hierarchies], np.float64)
    hits = np.array([h.cache_hit_rate for h in hierarchies], np.float64)
    return ratios, hits


def hierarchy_slowdown_grid(fracs, ratios, hits,
                            backend: str = "auto") -> np.ndarray:
    """Slowdown factors over a (workload x hierarchy-config) grid.

    ``fracs``: (..., T) per-pool-tier traffic fractions; ``ratios``:
    (C, T) tier latency ratios; ``hits``: (C,) DRAM-cache hit rates.
    Returns (..., C) slowdown factors.  The per-tier terms accumulate
    in tier order starting from 1.0 — the exact fold of the scalar
    ``TierHierarchy.slowdown_factor`` — so every element is bitwise the
    scalar result.
    """
    fracs = np.asarray(fracs, np.float64)
    ratios = np.asarray(ratios, np.float64)
    hits = np.asarray(hits, np.float64)
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            eff = hits[:, None] \
                + (1.0 - hits[:, None]) * jnp.asarray(ratios)
            terms = jnp.asarray(fracs)[..., None, :] * (eff - 1.0)
            out = jnp.ones(terms.shape[:-1])
            for t in range(terms.shape[-1]):
                out = out + terms[..., t]
            return np.asarray(out)
    eff = hits[:, None] + (1.0 - hits[:, None]) * ratios
    terms = fracs[..., None, :] * (eff - 1.0)
    out = np.ones(terms.shape[:-1])
    for t in range(terms.shape[-1]):
        out = out + terms[..., t]
    return out


def pdm_violation_grid(slowdown_frac, pdm_grid,
                       backend: str = "auto") -> np.ndarray:
    """Fraction of workloads at-or-beyond each PDM (inclusive predicate
    ``qos.exceeds_pdm``).  ``slowdown_frac``: (..., N) relative
    slowdowns; ``pdm_grid``: (P,).  Returns (..., P) float64."""
    s = np.asarray(slowdown_frac, np.float64)
    p = np.asarray(pdm_grid, np.float64)
    n = s.shape[-1]
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            counts = np.asarray(jnp.sum(
                jnp.asarray(s)[..., None, :] >= jnp.asarray(p)[:, None],
                axis=-1))
    else:
        counts = qos.exceeds_pdm(s[..., None, :], p[:, None]).sum(-1)
    return counts.astype(np.float64) / n


# ------------------------------------------------------ Fig 15/16 spill --
@dataclasses.dataclass
class SpillGrid:
    """Per-config zNUMA accounting (trailing axis = config lane)."""
    allocs: np.ndarray          # successful allocations
    pool_allocs: np.ndarray
    failed: np.ndarray          # MemoryError allocations (both tiers full)
    local_in_use: np.ndarray
    pool_in_use: np.ndarray

    @property
    def spill_fraction(self) -> np.ndarray:
        a = self.allocs.astype(np.float64)
        return np.where(self.allocs > 0,
                        self.pool_allocs.astype(np.float64)
                        / np.where(self.allocs > 0, a, 1.0), 0.0)


def compile_block_events(events) -> tuple[np.ndarray, np.ndarray]:
    """Compile ``[("alloc"|"free", block_key), ...]`` into int32 event
    arrays (kinds, keys).  Block keys are dense logical ids."""
    kind_of = {"alloc": ALLOC, "free": FREE}
    kinds = np.fromiter((kind_of[k] for k, _ in events), np.int32,
                        len(events))
    keys = np.fromiter((b for _, b in events), np.int32, len(events))
    return kinds, keys


def scalar_spill_replay(ev_kind, ev_key, num_local: int,
                        num_pool: int) -> SpillGrid:
    """Oracle: replay one config on ``znuma.ZNumaAllocator``.

    Failed allocations leave the key unbound; freeing an unbound key is
    a no-op (mirrors the engine's tier map)."""
    alloc = ZNumaAllocator(int(num_local), int(num_pool))
    held: dict[int, int] = {}
    failed = 0
    for kind, key in zip(ev_kind, ev_key):
        if kind == ALLOC:
            try:
                held[int(key)] = alloc.alloc()
            except MemoryError:
                failed += 1
        elif kind == FREE:
            blk = held.pop(int(key), None)
            if blk is not None:
                alloc.free(blk)
    mk = lambda v: np.asarray(v, np.int64)
    return SpillGrid(mk(alloc.allocs), mk(alloc.pool_allocs), mk(failed),
                     mk(alloc.local_in_use), mk(alloc.pool_in_use))


@functools.lru_cache(maxsize=None)
def _build_spill_sweep(batched: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(carry, ev):
        free_l, free_p, tier, allocs, pool_allocs, failed = carry
        kind, key = ev[0], ev[1]
        is_alloc = kind == ALLOC
        is_free = kind == FREE
        take_l = is_alloc & (free_l > 0)
        take_p = is_alloc & (free_l <= 0) & (free_p > 0)
        fail = is_alloc & (free_l <= 0) & (free_p <= 0)
        row = lax.dynamic_index_in_dim(tier, key, 0, keepdims=False)
        freed_l = is_free & (row == 0)
        freed_p = is_free & (row == 1)
        free_l = free_l - take_l + freed_l
        free_p = free_p - take_p + freed_p
        new_row = jnp.where(take_l, 0,
                            jnp.where(take_p, 1,
                                      jnp.where(is_free, -1, row)))
        tier = lax.dynamic_update_index_in_dim(
            tier, new_row.astype(tier.dtype), key, 0)
        allocs = allocs + (take_l | take_p)
        pool_allocs = pool_allocs + take_p
        failed = failed + fail
        return (free_l, free_p, tier, allocs, pool_allocs, failed), None

    def sweep(ev, num_local, num_pool, tier0):
        zeros = jnp.zeros_like(num_local)
        carry0 = (num_local, num_pool, tier0, zeros, zeros, zeros)
        carry, _ = lax.scan(body, carry0, ev)
        free_l, free_p, _, allocs, pool_allocs, failed = carry
        return (allocs, pool_allocs, failed,
                num_local - free_l, num_pool - free_p)

    if batched:
        sweep = jax.vmap(sweep, in_axes=(0, None, None, None))
    return jax.jit(sweep)


def _numpy_spill_sweep(ev, num_local, num_pool, n_keys: int):
    free_l = num_local.copy()
    free_p = num_pool.copy()
    tier = np.full((n_keys, len(num_local)), -1, np.int32)
    allocs = np.zeros_like(free_l)
    pool_allocs = np.zeros_like(free_l)
    failed = np.zeros_like(free_l)
    for kind, key in ev:
        if kind == ALLOC:
            take_l = free_l > 0
            take_p = ~take_l & (free_p > 0)
            fail = ~take_l & ~take_p
            free_l -= take_l
            free_p -= take_p
            tier[key] = np.where(take_l, 0, np.where(take_p, 1, tier[key]))
            allocs += take_l | take_p
            pool_allocs += take_p
            failed += fail
        elif kind == FREE:
            row = tier[key]
            free_l += row == 0
            free_p += row == 1
            tier[key] = -1
    return allocs, pool_allocs, failed, num_local - free_l, \
        num_pool - free_p


def spill_grid(ev_kind, ev_key, num_local, num_pool,
               backend: str = "auto") -> SpillGrid:
    """zNUMA spill accounting over a config grid, one scan pass.

    ``ev_kind``/``ev_key``: (E,) or (K, E) int event streams (kind
    :data:`PAD` is a no-op — the padding value for ragged batches);
    ``num_local``/``num_pool``: (C,) per-config tier sizes.  Returns a
    :class:`SpillGrid` with (C,) — or (K, C) — int64 counters, bitwise
    equal to :func:`scalar_spill_replay` per (stream, lane).

    Config lanes pad to the sweep-core bucket widths (padding
    replicates the last config; its lanes are sliced off), so XLA
    recompiles stay rare across grid shapes.
    """
    ev_kind = np.asarray(ev_kind, np.int32)
    ev_key = np.asarray(ev_key, np.int32)
    num_local = np.atleast_1d(np.asarray(num_local, np.int32))
    num_pool = np.atleast_1d(np.asarray(num_pool, np.int32))
    if num_local.shape != num_pool.shape:
        raise ValueError("num_local / num_pool shape mismatch")
    batched = ev_kind.ndim == 2
    c = len(num_local)
    width = sweep_core.bucket_width(c)
    nl = np.concatenate([num_local,
                         np.full(width - c, num_local[-1], np.int32)])
    npl = np.concatenate([num_pool,
                          np.full(width - c, num_pool[-1], np.int32)])
    n_keys = sweep_core.pad_up(int(ev_key.max(initial=0)) + 1, 32)
    ev = np.stack([ev_kind, ev_key], axis=-1)
    if _use_jax(backend):
        sweep = _build_spill_sweep(batched)
        tier0 = np.full((n_keys, width), -1, np.int32)
        out = sweep(sweep_core.device_put(ev),
                    sweep_core.device_put(nl),
                    sweep_core.device_put(npl),
                    sweep_core.device_put(tier0))
        arrs = [np.asarray(a)[..., :c].astype(np.int64) for a in out]
    elif batched:
        rows = [_numpy_spill_sweep(e, nl, npl, n_keys) for e in ev]
        arrs = [np.stack([r[i] for r in rows])[..., :c].astype(np.int64)
                for i in range(5)]
    else:
        out = _numpy_spill_sweep(ev, nl, npl, n_keys)
        arrs = [a[:c].astype(np.int64) for a in out]
    return SpillGrid(*arrs)


# --------------------------------------------------- Fig 17/18 LI + UM --
def default_li_thresholds() -> np.ndarray:
    return np.unique(np.round(np.linspace(0.0, 1.0, 101), 3))


def li_curve_grid(p, sens, thresholds=None,
                  backend: str = "auto") -> tuple[np.ndarray, np.ndarray,
                                                  np.ndarray]:
    """(LI, FP) fractions over a threshold grid in one pass.

    ``p``: (N,) sensitivity probabilities; ``sens``: (N,) bool truth
    (``qos.exceeds_pdm(slowdowns, pdm)``).  Returns ``(thresholds,
    li_frac, fp_frac)`` float64 — bit-exact vs
    ``LatencySensitivityModel.curve`` because ``li.mean()`` of a bool
    array is exactly count/size in float64.
    """
    p = np.asarray(p, np.float64)
    sens = np.asarray(sens, bool)
    ths = np.asarray(default_li_thresholds() if thresholds is None
                     else thresholds, np.float64)
    n = len(p)
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            li = jnp.asarray(p)[None, :] \
                < jnp.asarray(ths)[:, None]             # (T, N)
            li_c = np.asarray(jnp.sum(li, axis=1))
            fp_c = np.asarray(jnp.sum(
                li & jnp.asarray(sens)[None, :], axis=1))
    else:
        # sorted counts: #{p < t} and #{p_sens < t} via searchsorted
        li_c = np.searchsorted(np.sort(p), ths, side="left")
        fp_c = np.searchsorted(np.sort(p[sens]), ths, side="left")
    return ths, li_c.astype(np.float64) / n, fp_c.astype(np.float64) / n


def um_curve_grid(preds, actual) -> tuple[np.ndarray, np.ndarray]:
    """(UM, OP) per prediction row.  ``preds``: (T, N) per-tau
    predictions; ``actual``: (N,).  UM uses the same per-row float64
    ``mean`` reduction as the scalar loop; OP counts
    ``actual < pred`` in integers."""
    preds = np.asarray(preds, np.float64)
    actual = np.asarray(actual, np.float64)
    um = np.array([row.mean() for row in preds])
    op = (actual[None, :] < preds).sum(1).astype(np.float64) \
        / preds.shape[1]
    return um, op


# ------------------------------------------------- Fig 20 combine grid --
def combine_grid(li_curve, um_curve, budgets, spill_harm_prob: float = 0.25,
                 backend: str = "auto") -> list:
    """Vectorized ``eqn1.combine`` over a budget grid.

    The (L, U) candidate matrices flatten li-major so the first-
    occurrence ``argmax`` reproduces the nested loop's strict-``>``
    first-max tie-break; invalid cells mask to -inf.  Returns one
    ``eqn1.CombinedOperatingPoint`` per budget, each bitwise equal to
    the scalar ``eqn1.combine``.
    """
    li = np.asarray([c[0] for c in li_curve], np.float64)
    fp = np.asarray([c[1] for c in li_curve], np.float64)
    um = np.asarray([c[0] for c in um_curve], np.float64)
    op = np.asarray([c[1] for c in um_curve], np.float64)
    pf = li[:, None] + (1.0 - li[:, None]) * um[None, :]
    mis = fp[:, None] + op[None, :] * spill_harm_prob
    budgets = np.atleast_1d(np.asarray(budgets, np.float64))
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            ok = (jnp.asarray(fp)[None, :, None]
                  <= jnp.asarray(budgets)[:, None, None]) \
                & (jnp.asarray(mis)[None]
                   <= jnp.asarray(budgets)[:, None, None])
            cand = jnp.where(ok, jnp.asarray(pf)[None], -jnp.inf)
            flat = cand.reshape(len(budgets), -1)
            idx = np.asarray(jnp.argmax(flat, axis=1))
            best = np.asarray(jnp.max(flat, axis=1))
    else:
        ok = (fp[None, :, None] <= budgets[:, None, None]) \
            & (mis[None] <= budgets[:, None, None])
        cand = np.where(ok, pf[None], -np.inf)
        flat = cand.reshape(len(budgets), -1)
        idx = np.argmax(flat, axis=1)
        best = flat[np.arange(len(budgets)), idx]
    out = []
    n_um = len(um)
    for b in range(len(budgets)):
        if not best[b] > 0.0:               # no candidate beat the zero pt
            out.append(eqn1.CombinedOperatingPoint(0, 0, 0, 0, 0, 0))
            continue
        i, j = divmod(int(idx[b]), n_um)
        out.append(eqn1.CombinedOperatingPoint(
            float(fp[i]), float(op[j]), float(li[i]), float(um[j]),
            float(pf[i, j]), float(mis[i, j])))
    return out


# ----------------------------------------------------------- QoS grids --
def qos_mitigation_grid(p, spilled, pool_gb, thresholds, migrated=None,
                        backend: str = "auto") -> tuple[np.ndarray,
                                                        np.ndarray]:
    """The QoS monitor's mitigation predicate over a threshold grid.

    ``p``: (N,) predicted sensitivity; ``spilled``: (N,) bool;
    ``pool_gb``: (N,); ``thresholds``: (C,); ``migrated``: optional
    (N,) bool of already-migrated VMs.  Returns ``(mitigate (C, N)
    bool, n_mitigations (C,))`` — row c bitwise equals walking
    ``qos.QoSMonitor.check`` over the N VMs at threshold c.
    """
    p = np.asarray(p, np.float64)
    spilled = np.asarray(spilled, bool)
    pool_gb = np.asarray(pool_gb, np.float64)
    ths = np.atleast_1d(np.asarray(thresholds, np.float64))
    prev = np.zeros(len(p), bool) if migrated is None \
        else np.asarray(migrated, bool)
    if _use_jax(backend):
        jnp, enable_x64 = _jnp_x64()
        with enable_x64():
            mit = (~jnp.asarray(prev) & jnp.asarray(spilled)
                   & (jnp.asarray(pool_gb) > 0))[None, :] \
                & (jnp.asarray(p)[None, :] >= jnp.asarray(ths)[:, None])
            mit = np.asarray(mit)
    else:
        mit = (~prev & spilled & (pool_gb > 0))[None, :] \
            & (p[None, :] >= ths[:, None])
    return mit, mit.sum(1).astype(np.int64)


# -------------------------------------------------- tradeoff-curve interp --
def interp_tradeoff(x, xp, fp) -> np.ndarray:
    """``np.interp`` with its monotone-``xp`` precondition enforced.

    The seed Fig 18/20 paths interpolated tradeoff curves (UM vs OP)
    straight through ``np.interp``, whose result is silently garbage
    when the curve is not sorted by ``xp`` — model curves need not be
    monotone in the swept parameter.  Sorts (stable) by ``xp`` first;
    for already-sorted inputs this is bitwise ``np.interp``.
    """
    xp = np.asarray(xp, np.float64)
    fp = np.asarray(fp, np.float64)
    order = np.argsort(xp, kind="stable")
    return np.interp(x, xp[order], fp[order])
