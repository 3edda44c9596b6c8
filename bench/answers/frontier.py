"""A frontier answer: the reject rate of every (server GB, pool GB per
group) candidate of a grid, for each trace, in one streamed sweep.

One answer takes the policy's decisions, compiles each trace's events
into streamed shards (``replay_engine.CompiledReplayStream``) and prices the
whole grid in one ``CompiledReplayStreamBatch.reject_rates`` call.

Check: every answer's decisions against the plain static split, every
answer's rates against the first answer's, and the reject counts of a
sample of (trace, candidate) lanes, drawn from the seed, against the
plain reference replay.
"""
from __future__ import annotations

import numpy as np

import reference


def grid(traffic: dict) -> tuple[np.ndarray, np.ndarray]:
    s_lo, s_hi, s_n = traffic["server_gb"]
    p_lo, p_hi, p_n = traffic["pool_gb"]
    s, p = np.meshgrid(np.linspace(s_lo, s_hi, int(s_n)),
                       np.linspace(p_lo, p_hi, int(p_n)), indexing="ij")
    return s.ravel(), p.ravel()


def answer(cell, clock) -> dict:
    from repro.core import replay_engine
    cfg = cell.cluster_config()
    vms_list = cell.vms_list
    with clock.layer("policy"):
        decisions = cell.decisions(vms_list)
    with clock.layer("prep"):
        streams = [replay_engine.CompiledReplayStream(
            vms, dec, cfg,
            max_events_per_shard=int(cell.traffic["max_events_per_shard"]))
            for vms, dec in zip(vms_list, decisions)]
        batch = replay_engine.CompiledReplayStreamBatch(streams)
    server_gb, pool_gb = grid(cell.traffic)
    with clock.layer("sweep"):
        rates = batch.reject_rates(server_gb, pool_gb)
    return {"rates": np.asarray(rates),
            "n_vms": np.array([len(v) for v in vms_list]),
            "split": [(np.asarray(d.local_gb), np.asarray(d.pool_gb))
                      for d in decisions]}


def check_lanes(cell, n_lanes: int) -> list[tuple[int, int]]:
    """(trace, lane) pairs to replay: per trace the tightest candidate
    with pool memory (least server GB, least positive pool GB), and
    the rest drawn from the seed."""
    server_gb, pool_gb = grid(cell.traffic)
    tight = int(np.lexsort((np.where(pool_gb > 0, pool_gb, np.inf),
                            server_gb))[0])
    rng = np.random.default_rng([cell.seed, 1])
    out = []
    for k in range(len(cell.traces)):
        rest = rng.choice(np.delete(np.arange(len(server_gb)), tight),
                          n_lanes - 1, replace=False)
        out += [(k, int(j)) for j in [tight, *sorted(rest.tolist())]]
    return out


def reference_rates(cell, lanes, slice_gb: float = 1.0) -> dict:
    """Reject counts of the plain reference for the given lanes."""
    server_gb, pool_gb = grid(cell.traffic)
    frac = float(cell.policy["static_pool_frac"])
    out = {}
    for k in sorted({k for k, _ in lanes}):
        js = [j for kk, j in lanes if kk == k]
        cols = cell.columns(k)
        local, pool = reference.static_split(cols["mem_gb"], frac)
        counts = reference.reject_counts(
            cols, local, pool, **cell.shape(), server_gb=server_gb[js],
            pool_group_gb=pool_gb[js], slice_gb=slice_gb)
        out.update({(k, j): int(c) for j, c in zip(js, counts)})
    return out


def check(cell, outputs: list) -> dict:
    """Compared numbers, each ``(value, limit)``."""
    if cell.policy["name"] != "static":
        raise ValueError("the frontier check knows the static policy only")
    frac = float(cell.policy["static_pool_frac"])
    off = 0
    for out in outputs:
        for k, (local, pool) in enumerate(out["split"]):
            ref_l, ref_p = reference.static_split(cell.traces[k].mem_gb,
                                                  frac)
            off = max(off, int(((local != ref_l) | (pool != ref_p)).sum()))
    first = outputs[0]["rates"]
    disagree = sum(int((o["rates"] != first).sum()) for o in outputs[1:])
    lanes = check_lanes(cell, int(cell.traffic["check_lanes"]))
    ref = reference_rates(cell, lanes)
    n = outputs[0]["n_vms"]
    gap = max(abs(int(round(first[k, j] * n[k])) - ref[(k, j)])
              for k, j in lanes)
    return {"decisions_off_vms": (off, 0),
            "answers_disagree": (disagree, 0),
            "reject_gap_vms": (gap, 0)}


def control(cell) -> list:
    """The reference in the program's place, with memory counted in
    2 GB slices: one answer's outputs, rates filled on the checked
    lanes."""
    server_gb, _ = grid(cell.traffic)
    frac = float(cell.policy["static_pool_frac"])
    lanes = check_lanes(cell, int(cell.traffic["check_lanes"]))
    counts = reference_rates(cell, lanes, slice_gb=2.0)
    n = np.array([len(tr) for tr in cell.traces])
    rates = np.zeros((len(n), len(server_gb)))
    for (k, j), c in counts.items():
        rates[k, j] = c / n[k]
    return [{"rates": rates, "n_vms": n,
             "split": [reference.static_split(tr.mem_gb, frac)
                       for tr in cell.traces]}]
