"""A search answer: the least uniform local DRAM per server and pool DRAM
per group that schedules each trace within the reject tolerance, and
the all-local baseline it saves against.

One answer takes the policy's decisions and runs
``cluster_sim.savings_analysis_batched(decisions=...)``: the cores-bound
reject floor, the bisection for the least server GB with an unbounded
pool, the all-local baseline bisection, and the pool search over seven
server sizes between the two, all traces in lockstep.

Check, per trace, against the plain reference (Pond §6.1 semantics):

* the decisions: the static split against the plain rule; Pond's
  against the split the reference computes from the benchmark's own
  predictors (``reference.pond_split``), every VM;
* the floor and both bisections replayed in full (the program's
  bisections follow the scalar probe sequence, so the baseline must
  come out equal to the bit), and the answer's server size on the grid
  of seven sizes between them;
* the answer's reject count and feasibility, replayed;
* that the answer is the least total of the grid (``pool_excess_pct``):
  no grid size may schedule the trace with a pool a good share under
  what the answer's total leaves it.  Reject counts are not monotone in
  the pool near the tolerance (they wobble by a few VMs from one GB to
  the next), so a pool within the search's 2% of the least feasible
  one can have feasible pools a little under it; the limit sits above
  that wobble and below what a pool search that returns half as much
  again reads.
"""
from __future__ import annotations

import numpy as np

import reference

N_SERVER_POINTS = 7       # server sizes priced between the two bounds
TOL_FRAC = 0.02           # bisection tolerance, a share of the bracket top
HI_GB_PER_CORE = 12.0     # search bracket top: 12 GB per core
EXCESS_PCT = (40.0, 20.0, 10.0, 5.0)  # undercuts tried, largest first
EXCESS_LIMIT_PCT = 10.0  # sound searches read 0-10, inflated ones 20-40
EPS_P = 1e-6              # LI probabilities this near the threshold, and
EPS_UM = 2e-5             # UM GB this near a whole GB (a share of the VM's
#                           memory), may round either way in float32


def answer(cell, clock) -> dict:
    from repro.core import cluster_sim
    with clock.layer("policy"):
        decisions = cell.decisions(cell.vms_list)
    with clock.layer("search"):
        res = cluster_sim.savings_analysis_batched(
            cell.vms_list, cell.cluster_config(), cell.policy["name"],
            decisions=decisions,
            reject_tol=float(cell.config["reject_tol"]),
            max_events_per_shard=int(cell.traffic["max_events_per_shard"]))
    return {"result": np.array([[r.server_gb, r.pool_group_gb,
                                 r.baseline_server_gb, r.reject_rate]
                                for r in res]),
            "n_vms": np.array([len(v) for v in cell.vms_list]),
            "split": [(np.asarray(d.local_gb), np.asarray(d.pool_gb),
                       np.asarray(d.t_migrate), np.asarray(d.fully_pooled))
                      for d in decisions]}


def reference_split(cell, k: int):
    """The policy's split by the plain reference: the static rule, or
    Pond's from the benchmark's predictors; and, for Pond, the
    predictions it came from."""
    tr = cell.traces[k]
    if cell.policy["name"] == "static":
        local, pool = reference.static_split(
            tr.mem_gb, float(cell.policy["static_pool_frac"]))
        n = len(tr)
        return (local, pool, np.full(n, np.nan), np.zeros(n, bool)), None
    pred = reference.pond_predictions(tr.customer, tr.untouched, tr.pmu,
                                      cell.meta(k), cell.models)
    return reference.pond_split(tr.mem_gb, tr.untouched, tr.arrival, *pred,
                                float(cell.policy["li_threshold"])), pred


def decision_faults(cell, k: int, split, ref) -> int:
    """VMs whose split differs from the reference's.  For Pond, where
    the LI probability lies within ``EPS_P`` of the threshold, or the
    UM GB within ``EPS_UM`` of the VM's memory of a whole GB, either
    side is taken; the rest of that VM's split must then follow from
    the side the program took."""
    local, pool, t_mig, fully = split
    (ref_l, ref_p, _, ref_f), pred = ref
    if pred is None:
        bad = (local != ref_l) | (pool != ref_p) | np.isfinite(t_mig) \
            | fully
        return int(bad.sum())
    tr = cell.traces[k]
    mem, thr = tr.mem_gb, float(cell.policy["li_threshold"])
    p, um, has = pred
    near_p = has & (np.abs(p - thr) <= EPS_P)
    x, w = um * mem, EPS_UM * mem
    ok = (fully == ref_f) | near_p
    ok &= np.where(fully, (pool == mem) & (local == 0),
                   (pool >= np.floor(np.maximum(x - w, 0.0)))
                   & (pool <= np.floor(x + w)) & (pool == np.floor(pool))
                   & (local == mem - pool))
    touched = ~fully & (pool > 0) & (pool > tr.untouched * mem + 1e-9)
    mig = np.isfinite(t_mig)
    ok &= (mig == (touched & (p >= thr))) | (touched & near_p)
    ok &= ~mig | (t_mig == tr.arrival + reference.MIGRATE_AFTER_S)
    return int((~ok).sum())


class TraceReference:
    """Plain replays of one trace under one split, at one slice size."""

    def __init__(self, cell, k: int, split, slice_gb: float = 1.0):
        self.cols = cell.columns(k)
        self.n = len(cell.traces[k])
        self.shape = cell.shape()
        self.local, self.pool, self.t_mig, _ = split
        self.slice_gb = slice_gb
        self.hi = cell.cores_per_server * HI_GB_PER_CORE
        self.big_pool = self.hi * cell.n_servers
        self.floor = self.count(self.hi, self.big_pool)
        self.tol = self.floor / self.n + float(cell.config["reject_tol"])

    def count(self, server_gb: float, pool_gb: float,
              all_local: bool = False) -> int:
        mem = self.cols["mem_gb"]
        local, pool, t_mig = ((mem, np.zeros_like(mem), None) if all_local
                              else (self.local, self.pool, self.t_mig))
        return int(reference.reject_counts(
            self.cols, local, pool, **self.shape, server_gb=[server_gb],
            pool_group_gb=[pool_gb], slice_gb=self.slice_gb,
            t_migrate=t_mig)[0])

    def feasible(self, server_gb: float, pool_gb: float,
                 all_local: bool = False) -> bool:
        return self.count(server_gb, pool_gb, all_local) / self.n \
            <= self.tol

    def baseline(self) -> float:
        return reference.search_min(
            lambda g: self.feasible(g, 0.0, all_local=True), 0.0, self.hi,
            TOL_FRAC)

    def min_server(self) -> float:
        return reference.search_min(
            lambda g: self.feasible(g, self.big_pool), 0.0, self.hi,
            TOL_FRAC)


def pool_excess_pct(ref, server_grid, total: float, n_servers: int,
                    n_groups: int) -> float:
    """The largest of ``EXCESS_PCT`` by which a grid point undercuts the
    answer's total and still schedules the trace; 0 where none does.

    At grid size ``s_j`` the answer's total leaves ``r_j = (total -
    n_servers * s_j) / n_groups`` GB of pool per group; the point
    undercuts by ``m`` % where the pool ``r_j - m / 100 * max(r_j, 1)``
    is feasible at ``s_j`` and at every larger size of the grid (the
    pool search carries a bracket's infeasible end from a larger size
    to the smaller ones, so one infeasible size there clears it)."""
    worst = 0.0
    for j, s_j in enumerate(server_grid):
        r_j = (total - n_servers * s_j) / n_groups
        for m in EXCESS_PCT:
            if m <= worst:
                break
            q = r_j - m / 100 * max(r_j, 1.0)
            if q >= 0 and all(ref.feasible(s, q) for s in server_grid[j:]):
                worst = m
                break
    return worst


def check(cell, outputs: list) -> dict:
    """Compared numbers, each ``(value, limit)``."""
    refs = [reference_split(cell, k) for k in range(len(cell.traces))]
    faults = max(decision_faults(cell, k, s, refs[k])
                 for out in outputs for k, s in enumerate(out["split"]))
    first = outputs[0]["result"]
    disagree = sum(int((o["result"] != first).sum()) for o in outputs[1:])
    n_groups = -(-cell.n_servers // cell.servers_per_group)
    base_gap = grid_gap = reject_gap = over = excess = 0
    for k in range(len(cell.traces)):
        # the reference replays the static split it made, or Pond's
        # split as the answer made it, which the decision check held
        # against its own
        split = refs[k][0] if refs[k][1] is None \
            else outputs[0]["split"][k]
        ref = TraceReference(cell, k, split)
        sgb, pgb, base, rate = first[k]
        base_ref = ref.baseline()
        server_grid = np.linspace(ref.min_server(), base_ref,
                                  N_SERVER_POINTS)
        count = ref.count(sgb, pgb)
        base_gap = max(base_gap, abs(base - base_ref))
        grid_gap = max(grid_gap, float(np.abs(server_grid - sgb).min()))
        reject_gap = max(reject_gap, abs(int(round(rate * ref.n)) - count))
        over += count / ref.n > ref.tol
        excess = max(excess, pool_excess_pct(
            ref, server_grid, cell.n_servers * sgb + n_groups * pgb,
            cell.n_servers, n_groups))
    return {"decisions_off_vms": (faults, 0),
            "answers_disagree": (disagree, 0),
            "baseline_gap_gb": (base_gap, 0),
            "server_grid_gap_gb": (grid_gap, 0),
            "reject_gap_vms": (reject_gap, 0),
            "over_tolerance_traces": (int(over), 0),
            "pool_excess_pct": (excess, EXCESS_LIMIT_PCT)}


def control(cell) -> list:
    """The reference in the program's place, with memory counted in
    2 GB slices: its own decisions, then a whole plain search (floor,
    both bisections, a pool bisection at each of the seven server
    sizes, the least total)."""
    n_groups = -(-cell.n_servers // cell.servers_per_group)
    rows, splits = [], []
    for k in range(len(cell.traces)):
        split = reference_split(cell, k)[0]
        splits.append(split)
        ref = TraceReference(cell, k, split, slice_gb=2.0)
        base = ref.baseline()
        best = None
        for sgb in np.linspace(ref.min_server(), base, N_SERVER_POINTS):
            pgb = reference.search_min(
                lambda g: ref.feasible(sgb, g), 0.0, ref.big_pool, TOL_FRAC)
            total = cell.n_servers * sgb + n_groups * pgb
            if best is None or total < best[0]:
                best = (total, sgb, pgb)
        _, sgb, pgb = best
        rows.append([sgb, pgb, base, ref.count(sgb, pgb) / ref.n])
    return [{"result": np.array(rows),
             "n_vms": np.array([len(tr) for tr in cell.traces]),
             "split": splits}]
