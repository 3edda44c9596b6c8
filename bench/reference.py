"""Plain reference of the cluster replay, the static policy and the
provisioning bisection.  It imports nothing of the program.

Semantics (Pond §6.1, the simulator the paper replays traces on):

* Events are VM arrivals, departures (arrival + lifetime) and QoS
  migrations, sorted by ``(time, kind)`` with arrival < departure <
  migration at equal times, and by VM order within a kind.
* An arrival goes to the server with the fewest free cores among those
  with enough free cores, enough free local memory for the VM's local
  share and enough free pool memory in the server's pool group for its
  pool share; ties go to the lowest server index.  Where no server
  qualifies, the VM starts all-local on the best fit by cores among
  servers with room for its whole memory, and later departs as
  all-local.  Otherwise it is rejected.
* A departure returns what the VM holds.  A migration moves a placed
  VM's pool share to its server's local memory when that fits.

Memory is counted in whole slices of ``slice_gb`` (1 GB, Pond's pool
slice): demands round up to whole slices and capacities down.  With
whole-GB traces and ``slice_gb=1`` the counts are exact.  The control
of the correctness check runs the same code at ``slice_gb=2``.

Every candidate is an independent replay, one Python loop over the
events.

Pond's split (§4.3-4.4): a VM whose customer has at least three VMs of
history and whose latency-insensitivity probability (the mean of the
forest's leaf values) is under the threshold goes wholly to the pool;
any other VM pools ``floor(um * mem)`` GB, ``um`` being the GBM's
untouched-memory quantile clipped to [0, 1], over the customer's
history percentiles (80, 90, 95, 99; 0.5 each with less than three
VMs) and the VM's metadata.  Each VM's own untouched fraction joins
its customer's history after its decision.  A VM with pool memory that
it touches (pool above its untouched GB) and a probability at or above
the threshold migrates to local memory 60 s after it arrives.  The
models are the benchmark's own (``bench/predictors.py``), given here as
arrays.
"""
from __future__ import annotations

import bisect

import numpy as np

ARRIVE, DEPART, MIGRATE = 0, 1, 2


def static_split(mem_gb: np.ndarray, frac: float):
    """Static pooling: ``floor(frac * mem)`` GB in the pool, rest local."""
    pool = np.floor(mem_gb * frac)
    return mem_gb - pool, pool


HISTORY_MIN = 3
PERCENTILES = (80, 90, 95, 99)
MIGRATE_AFTER_S = 60.0


def tree_values(tree: dict, x: np.ndarray) -> np.ndarray:
    """One tree's leaf value per row of ``x``: go left while the row's
    feature is at most the node's threshold, until a leaf."""
    node = np.zeros(len(x), np.int64)
    rows = np.arange(len(x))
    while True:
        feat = tree["feature"][node]
        inner = feat >= 0
        if not inner.any():
            return tree["value"][node].astype(np.float64)
        left = x[rows, np.maximum(feat, 0)] <= tree["threshold"][node]
        node = np.where(inner, np.where(left, tree["left"][node],
                                        tree["right"][node]), node)


def pond_predictions(customer: np.ndarray, untouched: np.ndarray,
                     pmu: np.ndarray, meta: np.ndarray, models: dict):
    """Per VM, in trace order: the LI probability, the clipped UM
    quantile, and whether the customer had enough history.  ``meta``:
    type, cores, memory, location and guest OS columns."""
    p = np.mean([tree_values(t, pmu) for t in models["forest"]], axis=0)
    hist = {c: list(v) for c, v in models["history"].items()}
    n = len(customer)
    has = np.zeros(n, bool)
    percs = np.full((n, len(PERCENTILES)), 0.5)
    for i, (c, u) in enumerate(zip(customer.tolist(), untouched.tolist())):
        h = hist.setdefault(c, [])
        if len(h) >= HISTORY_MIN:
            has[i] = True
            percs[i] = np.percentile(h, PERCENTILES)
        h.append(u)
    feat = np.column_stack([percs, meta]).astype(np.float32)
    g = models["gbm"]
    um = g["f0"] + g["lr"] * np.sum([tree_values(t, feat)
                                     for t in g["stages"]], axis=0)
    return p, np.clip(um, 0.0, 1.0), has


def pond_split(mem_gb: np.ndarray, untouched: np.ndarray,
               arrival: np.ndarray, p: np.ndarray, um: np.ndarray,
               has: np.ndarray, threshold: float):
    """``(local, pool, t_migrate, fully_pooled)`` from the predictions."""
    fully = has & (p < threshold)
    pool = np.where(fully, mem_gb, np.floor(um * mem_gb))
    mig = ~fully & (pool > 0) & (pool > untouched * mem_gb + 1e-9) \
        & (p >= threshold)
    t_mig = np.where(mig, arrival + MIGRATE_AFTER_S, np.nan)
    return mem_gb - pool, pool, t_mig, fully


def events(arrival: np.ndarray, lifetime: np.ndarray,
           t_migrate: np.ndarray | None = None):
    """Sorted ``(kind, vm)`` event arrays."""
    n = len(arrival)
    times = [arrival, arrival + lifetime]
    kinds = [np.full(n, ARRIVE), np.full(n, DEPART)]
    vms = [np.arange(n), np.arange(n)]
    if t_migrate is not None:
        m = np.flatnonzero(np.isfinite(t_migrate))
        times.append(t_migrate[m])
        kinds.append(np.full(len(m), MIGRATE))
        vms.append(m)
    t, k, v = (np.concatenate(x) for x in (times, kinds, vms))
    order = np.lexsort((v, k, t))
    return k[order], v[order]


def reject_counts(trace_cols: dict, local_gb: np.ndarray,
                  pool_gb: np.ndarray, n_servers: int,
                  cores_per_server: int, servers_per_group: int,
                  server_gb, pool_group_gb, slice_gb: float = 1.0,
                  t_migrate: np.ndarray | None = None) -> np.ndarray:
    """Rejected VMs per candidate ``(server_gb[i], pool_group_gb[i])``.

    ``trace_cols`` holds ``arrival``, ``lifetime``, ``cores`` and
    ``mem_gb`` arrays.
    """
    kinds, vms = events(trace_cols["arrival"], trace_cols["lifetime"],
                        t_migrate)
    kinds, vms = kinds.tolist(), vms.tolist()
    demand = [np.ceil(np.asarray(x, float) / slice_gb).astype(np.int64)
              .tolist() for x in (local_gb, pool_gb, trace_cols["mem_gb"])]
    cores = np.asarray(trace_cols["cores"], np.int64).tolist()
    caps = zip(np.atleast_1d(np.asarray(server_gb, float)),
               np.atleast_1d(np.asarray(pool_group_gb, float)))
    return np.array([
        _replay(kinds, vms, cores, *demand, n_servers, cores_per_server,
                servers_per_group, int(s // slice_gb), int(p // slice_gb))
        for s, p in caps], np.int64)


def _replay(kinds, vms, cores, loc, pool, mem, n_servers: int,
            cores_per_server: int, servers_per_group: int, cap_s: int,
            cap_p: int) -> int:
    """One candidate's replay; returns its reject count.

    Servers sit in buckets by free cores, each bucket in index order,
    so the best fit is the first qualifying server of the lowest bucket
    that holds the VM's cores.
    """
    group_of = [s // servers_per_group for s in range(n_servers)]
    n_groups = group_of[-1] + 1
    free_c = [cores_per_server] * n_servers
    free_m = [cap_s] * n_servers
    free_p = [cap_p] * n_groups
    buckets = [[] for _ in range(cores_per_server + 1)]
    buckets[cores_per_server] = list(range(n_servers))
    placed = [-1] * len(cores)
    all_local = bytearray(len(cores))
    rejects = 0

    def move(s: int, new_c: int) -> None:
        buckets[free_c[s]].remove(s)
        bisect.insort(buckets[new_c], s)
        free_c[s] = new_c

    for kind, v in zip(kinds, vms):
        c = cores[v]
        if kind == ARRIVE:
            s = -1
            need_l, need_p = loc[v], pool[v]
            if need_p <= max(free_p):
                for fc in range(c, cores_per_server + 1):
                    for srv in buckets[fc]:
                        if free_m[srv] >= need_l and \
                                free_p[group_of[srv]] >= need_p:
                            s = srv
                            break
                    if s >= 0:
                        break
            if s >= 0:
                free_m[s] -= need_l
                free_p[group_of[s]] -= need_p
            else:                           # all-local fallback
                need_m = mem[v]
                for fc in range(c, cores_per_server + 1):
                    for srv in buckets[fc]:
                        if free_m[srv] >= need_m:
                            s = srv
                            break
                    if s >= 0:
                        break
                if s < 0:
                    rejects += 1
                    continue
                free_m[s] -= need_m
                all_local[v] = 1
            move(s, free_c[s] - c)
            placed[v] = s
            continue
        s = placed[v]
        if s < 0:
            continue
        if kind == DEPART:
            move(s, free_c[s] + c)
            if all_local[v]:
                free_m[s] += mem[v]
            else:
                free_m[s] += loc[v]
                free_p[group_of[s]] += pool[v]
            placed[v] = -1
        elif free_m[s] >= pool[v]:                      # MIGRATE
            free_m[s] -= pool[v]
            free_p[group_of[s]] += pool[v]
            all_local[v] = 1
    return rejects


def search_min(feasible, lo: float, hi: float,
               tol_frac: float = 0.02) -> float:
    """Least ``x`` in ``[lo, hi]`` with ``feasible(x)``, by bisection to
    ``tol_frac`` of ``hi``; ``hi`` when even ``hi`` is infeasible."""
    if not feasible(hi):
        return hi
    while (hi - lo) > tol_frac * max(hi, 1.0):
        mid = 0.5 * (lo + hi)
        if feasible(mid):
            hi = mid
        else:
            lo = mid
    return hi
