"""Paper Fig 21 in miniature: DRAM savings of Pond vs static vs all-local,
priced on the event-compiled batched replay engine.

The demo shows the engine API directly: compile a (vms, decisions) pair
once, price a whole frontier of (server_gb, pool_gb) candidates in one
event sweep, then batch several trace seeds into ONE vmapped sweep and
report mean ± spread savings across the seed batch.

  PYTHONPATH=src python examples/cluster_savings.py
  PYTHONPATH=src python examples/cluster_savings.py --seeds 4
  PYTHONPATH=src python examples/cluster_savings.py \\
      --trace-file path/to/trace.csv        # real-trace replay
      (columns: arrival, lifetime, cores, mem_gb — Azure public-trace
       spellings like vmcreated/vmdeleted/vmcorecount are aliased; try
       the bundled fixture via --trace-file fixture)
  PYTHONPATH=src python examples/cluster_savings.py \\
      --trace-file big.csv.gz --max-events-per-shard 250000
      # Azure-scale files: chunked ingestion (iter_trace_chunks) +
      # sharded streaming replay (CompiledReplayStream) — bounded
      # parse memory and a fixed event-tensor budget; fetch a real
      # trace with scripts/fetch_azure_trace.py
  PYTHONPATH=src python examples/cluster_savings.py \\
      --seeds 4 --max-events-per-shard 4096
      # batched STREAMING: the K seed traces replay as a
      # CompiledReplayStreamBatch — one vmapped carry sweep per shard,
      # and the savings searches below run in lockstep on it
  PYTHONPATH=src python examples/cluster_savings.py \\
      --policy-grid "tau=0.02:0.2:3,li=0.05:0.5:2"
      # ONE grid evaluation (compiled policy engine) prices every
      # (tau, pdm, li-threshold) setting against the seed batch and
      # prints a savings-vs-setting table; axes: tau, pdm, li
      # (each lo:hi:n, defaults tau=0.05, pdm=0.05, li=0.05)
"""
import argparse
import time

import numpy as np

from repro.core import (cluster_sim, compile_cache, policy_engine,
                        replay_engine, traces)
from repro.core.control_plane import ControlPlane, ControlPlaneConfig
from repro.core.pool_manager import PoolManager
from repro.core.predictors.models import (LatencySensitivityModel,
                                          UntouchedMemoryModel)


def fit_models(pop, horizon):
    train = pop.sample_vms(1200, horizon, seed=1)
    li = LatencySensitivityModel(pdm=0.05).fit(
        traces.pmu_matrix(train), traces.slowdowns(train, 182))
    hist = traces.build_history(train)
    meta = traces.metadata_features(train, hist)
    ut = np.array([v.untouched for v in train])
    um = UntouchedMemoryModel(0.05).fit(meta, ut)
    return li, um, hist, meta, ut


def parse_grid_spec(spec: str) -> dict:
    """``"tau=0.1:0.3:3,pdm=0.02:0.1:3"`` -> {axis: np.linspace values}.

    Axes: ``tau`` (UM quantile), ``pdm`` (slowdown margin), ``li``
    (sensitivity-probability threshold).  Each axis is ``lo:hi:n``; a
    single value (``tau=0.05``) pins the axis.
    """
    axes: dict[str, np.ndarray] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            name, rng = part.split("=")
            name = name.strip()
            if name not in ("tau", "pdm", "li"):
                raise ValueError(f"unknown axis {name!r}")
            pieces = [float(x) for x in rng.split(":")]
            if len(pieces) == 1:
                axes[name] = np.array(pieces)
            elif len(pieces) == 3:
                axes[name] = np.linspace(pieces[0], pieces[1],
                                         int(pieces[2]))
            else:
                raise ValueError("expected value or lo:hi:n")
        except ValueError as e:
            raise SystemExit(
                f"--policy-grid: cannot parse {part!r} ({e}); expected "
                f"axis=lo:hi:n with axes tau, pdm, li") from None
    return axes


def run_policy_grid(spec, vms_list, cfg, pop, horizon):
    """One compiled grid evaluation -> savings-vs-setting table."""
    axes = parse_grid_spec(spec)
    taus = tuple(round(float(t), 6) for t in axes.get("tau", [0.05]))
    pdms = tuple(float(p) for p in axes.get("pdm", [0.05]))
    ths = tuple(float(t) for t in axes.get("li", [0.05]))
    li, _, hist, meta, ut = fit_models(pop, horizon)
    um_models = policy_engine.fit_um_grid(meta, ut, taus)
    settings = policy_engine.make_grid(taus=taus, pdms=pdms,
                                       li_thresholds=ths)
    t0 = time.perf_counter()
    grid = policy_engine.grid_decisions(vms_list, settings, li,
                                        um_models, hist, backend="auto")
    t_grid = time.perf_counter() - t0
    k = len(vms_list)
    print(f"policy grid: {len(settings)} settings x {k} trace(s) "
          f"evaluated in {t_grid:.2f}s (one compiled pass)")
    flat_vms = [vms for _ in settings for vms in vms_list]
    flat_dec = [grid[s][i] for s in range(len(settings))
                for i in range(k)]
    cache: dict = {}
    results = cluster_sim.savings_analysis_batched(
        flat_vms, cfg, "pond-grid", decisions=flat_dec, cache=cache)
    print(f"{'setting':34s} {'savings':>14s} {'pool/group':>10s} "
          f"{'mispred':>8s}")
    for si, s in enumerate(settings):
        sm = cluster_sim.summarize_savings(results[si * k:(si + 1) * k])
        print(f"{s.label:34s} {sm['savings_mean']:+.3f}"
              f"±{sm['savings_std']:.3f}     "
              f"{sm['pool_group_gb_mean']:8.1f}GB "
              f"{sm['mispred_mean']:8.3f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-file", default=None,
                    help="replay a real VM trace file (CSV/parquet with "
                         "arrival, lifetime, cores, mem_gb columns; "
                         "'fixture' uses the bundled miniature trace)")
    ap.add_argument("--seeds", type=int, default=3,
                    help="synthetic trace seeds priced in one batched "
                         "sweep (ignored with --trace-file)")
    ap.add_argument("--servers", type=int, default=None,
                    help="cluster size (default 16, or 4 for the small "
                         "fixture trace)")
    ap.add_argument("--max-events-per-shard", type=int, default=None,
                    help="stream the replay in bounded event shards "
                         "(CompiledReplayStream) once a trace exceeds "
                         "this budget: peak EVENT-TENSOR memory stays "
                         "fixed and --trace-file ingestion goes through "
                         "the chunked reader (the VM records themselves "
                         "stay in memory for the provisioning searches)")
    ap.add_argument("--chunk-vms", type=int, default=65536,
                    help="rows per ingestion chunk when streaming a "
                         "--trace-file out of core")
    ap.add_argument("--policy-grid", default=None, metavar="SPEC",
                    help="price a (tau, pdm, li) policy grid in one "
                         "compiled evaluation and print a savings-vs-"
                         "setting table; SPEC like "
                         "'tau=0.1:0.3:3,pdm=0.02:0.1:3' (axes tau, "
                         "pdm, li; each lo:hi:n or a single value)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    horizon = 5 * 86400
    pop = traces.Population(seed=0)
    if args.trace_file:
        path = traces.fixture_trace_path() \
            if args.trace_file == "fixture" else args.trace_file
        if args.max_events_per_shard:
            # one chunked pass (bounded parse memory); the records feed
            # both the stream demo and the policy searches below
            vms_list = [[v for chunk in traces.iter_trace_chunks(
                path, chunk_vms=args.chunk_vms) for v in chunk]]
        else:
            vms_list = [traces.load_trace_file(path)]
        n_servers = args.servers or \
            (4 if path == traces.fixture_trace_path() else 16)
        label = path
    else:
        cfg0 = cluster_sim.ClusterConfig(n_servers=args.servers or 16)
        n = cluster_sim.arrivals_for_util(cfg0, 0.8, horizon)
        vms_list = [pop.sample_vms(n, horizon, seed=2 + i,
                                   start_id=10 ** 6)
                    for i in range(args.seeds)]
        n_servers = args.servers or 16
        label = f"{args.seeds} synthetic seeds"
    cfg = cluster_sim.ClusterConfig(n_servers=n_servers, pool_sockets=16,
                                    gb_per_core=4.75)

    if args.policy_grid:
        run_policy_grid(args.policy_grid, vms_list, cfg, pop, horizon)
        return

    # --- 1. price one candidate frontier in a single compiled sweep ----
    decisions, _ = cluster_sim.policy_decisions(vms_list[0], "static",
                                                static_pool_frac=0.15)
    budget = args.max_events_per_shard
    n_events = 2 * len(vms_list[0]) + \
        sum(1 for d in decisions if d.t_migrate is not None)
    if budget is not None and n_events > budget:
        # sharded path: event tensors of <= budget events, carried state
        eng = replay_engine.CompiledReplayStream(
            vms_list[0], decisions, cfg, max_events_per_shard=budget)
        print(f"[{label}] streaming: {eng.n_events} events in "
              f"{eng.n_shards} shards of <= {budget} "
              f"({eng.peak_shard_bytes / 2 ** 20:.1f} MiB peak event "
              f"tensor)")
    else:
        eng = replay_engine.CompiledReplay(vms_list[0], decisions, cfg)
    hi = cfg.cores_per_server * 6.0      # per-server DRAM probe ceiling
    server_gb = np.linspace(hi * 0.5, hi, 9)
    pool_gb = np.linspace(0.0, 2.0 * hi, 9)
    eng.reject_rates(server_gb, pool_gb)        # warm the XLA compile
    t0 = time.perf_counter()
    rates = eng.reject_rates(server_gb, pool_gb)
    dt = time.perf_counter() - t0
    print(f"[{label}] one sweep priced {len(rates)} (server_gb, pool_gb) "
          f"candidates in {dt * 1e3:.0f}ms over {eng.n_events} events:")
    for s, p, r in zip(server_gb, pool_gb, rates):
        print(f"  server={s:5.0f}GB pool={p:5.0f}GB -> reject {r:.4f}")

    # --- 2. multi-trace batch: K seeds in ONE vmapped sweep ------------
    if len(vms_list) > 1:
        decs = [cluster_sim.policy_decisions(v, "static",
                                             static_pool_frac=0.15)[0]
                for v in vms_list]
        if budget is not None:
            # batched STREAMING: K bounded-memory streams, one vmapped
            # carry sweep per shard (peak tensor = one stacked shard)
            batch = replay_engine.CompiledReplayStreamBatch(
                [replay_engine.CompiledReplayStream(
                    v, d, cfg, max_events_per_shard=budget)
                 for v, d in zip(vms_list, decs)])
            print(f"\nstream batch: {batch.k} traces x "
                  f"{batch.n_shards} shards of <= {budget} events "
                  f"({batch.peak_shard_bytes / 2 ** 20:.1f} MiB peak "
                  f"stacked tensor)")
        else:
            batch = replay_engine.CompiledReplayBatch(
                [replay_engine.CompiledReplay(v, d, cfg)
                 for v, d in zip(vms_list, decs)])
        batch.reject_rates(server_gb, pool_gb)  # warm
        t0 = time.perf_counter()
        br = batch.reject_rates(server_gb, pool_gb)
        dt = time.perf_counter() - t0
        print(f"\nbatched sweep priced {br.shape[0]} traces x "
              f"{br.shape[1]} candidates in {dt * 1e3:.0f}ms "
              f"(reject mean±std across seeds):")
        for j, (s, p) in enumerate(zip(server_gb, pool_gb)):
            print(f"  server={s:5.0f}GB pool={p:5.0f}GB -> "
                  f"{br[:, j].mean():.4f}±{br[:, j].std():.4f}")

    # --- 3. full provisioning searches, engine-backed ------------------
    li, um, hist, *_ = fit_models(pop, horizon)
    replay_engine.stats_reset()
    cache: dict = {}
    t0 = time.perf_counter()
    r_local = cluster_sim.savings_analysis_batched(
        vms_list, cfg, "local", cache=cache,
        max_events_per_shard=budget)
    r_static = cluster_sim.savings_analysis_batched(
        vms_list, cfg, "static", static_pool_frac=0.15, cache=cache,
        max_events_per_shard=budget)
    cps = [ControlPlane(
        ControlPlaneConfig(li_threshold=0.05, um_quantile=0.05), li, um,
        PoolManager(pool_gb=4096, buffer_gb=64), history=dict(hist))
        for _ in vms_list]
    r_pond = cluster_sim.savings_analysis_batched(
        vms_list, cfg, "pond", control_planes=cps, cache=cache,
        max_events_per_shard=budget)
    dt = time.perf_counter() - t0
    stats = replay_engine.stats_snapshot()
    print(f"\nthree policy searches x {len(vms_list)} trace(s) in "
          f"{dt:.2f}s ({stats['events_per_sec']:.0f} candidate-events/s):")
    for results in (r_local, r_static, r_pond):
        s = cluster_sim.summarize_savings(results)
        print(f"  {results[0].name:6s}: "
              f"server={s['server_gb_mean']:6.1f}GB "
              f"pool/group={s['pool_group_gb_mean']:6.1f}GB "
              f"savings={s['savings_mean']:+.3f}±{s['savings_std']:.3f} "
              f"reject={s['reject_rate_mean']:.4f}")


if __name__ == "__main__":
    main()
