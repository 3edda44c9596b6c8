"""Pond provisioning benchmark: seconds per answer on one TPU.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The traffic names the kind of
answer (``bench/answers/<kind>.py``); each per-layer metric is read by
``bench/metrics/<name>.py``.  Nothing here names a cell.

A run refuses to start, before any work, when JAX's first device is
not a TPU or there are fewer chips than the cell asks for.  Set-up
generates the cell's traces from ``--seed`` (``bench/tracegen.py``),
trains Pond's predictors where the policy needs them, and runs one
warm-up answer on the cell's own shapes, which compiles.  The window
then runs whole answers back to back, each from the trace with its own
decisions, engines and state, until ``--seconds`` have passed.
``answer_s`` is the window's wall time over its answers.  With
``--trace 1`` the window runs under the profiler, and the per-layer
metrics are read from the program's spans and counters and from the
device trace.  After the window, the plain reference
(``bench/reference.py``) checks what the answers produced.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` ``breakdown``), then ``checks``, each compared number
with its limit.  The same numbers are the last lines of standard
error.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import sys
import time
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
WORK_DIR = ROOT / ".bench_work"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextlib.contextmanager
def count_compiles():
    """XLA backend compiles inside the block, via ``jax.monitoring``."""
    import jax
    seen = {"n": 0, "s": 0.0}

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
            seen["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def find_workload(name: str) -> tuple[dict, dict]:
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    return bench, cells[name]


def check_device(chips: int):
    """JAX's devices, or exit before any work: the benchmark measures a
    TPU and nothing else."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX's first device is "
                         f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs


def peaks_for(kind: str) -> dict:
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def enable_compile_cache() -> None:
    """JAX's persistent compile cache at ``<checkout>/.jax_cache``; the
    program's own rule (``compile_cache.enable``) takes the directory
    from the variable set here.  The cache is never trimmed: it holds a
    few programs, and JAX's trimming reads a timestamp file per entry
    that a concurrent write (the stream's upload thread compiles too)
    may not have written yet, which fails the write and leaves every
    later run to compile again."""
    import jax
    from repro.core import compile_cache
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    compile_cache.enable()


def run_window(cell, answer_mod, seconds: float, trace_dir: Path | None):
    """Whole answers back to back until ``seconds`` have passed."""
    import jax
    from repro.core import obs, replay_engine
    from cell import LayerClock
    clock = LayerClock()
    outputs, obs_sum = [], {}
    failed = 0
    events0 = replay_engine.stats_snapshot()["events"]
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    spans = []              # the program's spans, ns from the window
    with count_compiles() as compiles, \
            jax.profiler.TraceAnnotation("bench.window"):
        t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        while True:
            epoch = time.perf_counter_ns() - t0_ns
            rec = obs.Recorder()
            try:
                with obs.use_recorder(rec):
                    out = answer_mod.answer(cell, clock)
            except Exception:                       # counted, reported
                failed += 1
                _log(f"bench: answer {len(outputs) + failed} failed:\n"
                     + traceback.format_exc())
                out = None
            m = rec.metrics()
            if trace_dir is not None:
                spans += [(f"obs.{sp['name']}", epoch + sp["ts_ns"],
                           epoch + sp["ts_ns"] + sp["dur_ns"])
                          for sp in rec.spans()]
            if m.get("replay.backend_numpy", 0) and out is not None:
                failed += 1
                _log("bench: an answer fell back to the numpy backend")
                out = None
            if out is not None:
                outputs.append(out)
            for k, v in m.items():
                if isinstance(v, (int, float)):
                    obs_sum[k] = obs_sum.get(k, 0) + v
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    steps = replay_engine.stats_snapshot()["events"] - events0
    return {"outputs": outputs, "failed": failed, "window_s": window_s,
            "obs": obs_sum, "layers": clock.seconds, "compiles": compiles,
            "steps": steps, "spans": spans}


def sweep_work(cell, win: dict) -> dict:
    """Shapes of the window's sweep calls (``bench/roofline.py``)."""
    import roofline
    m = win["obs"]
    calls = sum(m.get(k, 0) for k in ("span.batch.reject_rates.count",
                                      "span.stream_batch.reject_rates.count"))
    lanes = m.get("pad.cand_lanes_used", 0)
    whole = not (m.get("stream.shards_skipped", 0)
                 or m.get("stream.reject_cap_exits", 0))
    # sweep modules run: one per shard scan, or one per unstreamed call
    runs = m.get("span.stream.compute.count", 0) \
        + m.get("span.batch.reject_rates.count", 0)
    if not (calls and lanes and whole):
        return {"ops": None, "bytes": None, "steps": win["steps"],
                "runs": runs}
    arrivals = cell.n_vms * len(cell.traces)
    ops, nbytes = roofline.sweep_work(
        arrivals=arrivals, events=2 * arrivals, lanes=int(lanes),
        calls=int(calls), traces=len(cell.traces), servers=cell.n_servers,
        groups=-(-cell.n_servers // cell.servers_per_group))
    return {"ops": ops, "bytes": nbytes, "steps": win["steps"],
            "runs": runs}


def per_layer(bench: dict, workload: str, ctx: dict) -> dict:
    out = {}
    for metric in bench["per_layer"]:
        if workload not in metric.get("workloads", [workload]):
            continue
        reader = importlib.import_module(f"metrics.{metric['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True,
             overrides: dict | None = None) -> dict:
    """One run of a cell; returns the result object.

    ``require_tpu`` and ``overrides`` exist for the benchmark's own
    tests, which drive a run on the CPU at a small size: each key of
    ``overrides`` replaces the key of that name in the configuration's
    ``cluster``, in the configuration, or in the traffic.  Such a run
    leaves JAX's compile cache as it found it.
    """
    t_start = time.perf_counter()
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    bench, wl = find_workload(workload)
    if require_tpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        os.environ.setdefault("TPU_LOG_DIR", str(WORK_DIR / "tpu_logs"))
        devs = check_device(int(wl["chips"]))
        peaks = peaks_for(devs[0].device_kind)
        enable_compile_cache()
    else:
        import jax
        devs, peaks = jax.devices(), None
    dev = devs[0]
    from cell import Cell, LayerClock, load_json
    import devtrace
    config = load_json("configs", wl["config"])
    traffic = load_json("traffic", wl["traffic"])
    for k, v in (overrides or {}).items():
        for d in (config["cluster"], config, traffic):
            if k in d:
                d[k] = v
                break
    answer_mod = importlib.import_module(f"answers.{traffic['answer']}")
    cell = Cell(config, traffic, seed)
    _log(f"bench: {workload} seed {seed} on {len(devs)} x "
         f"{dev.device_kind}; {cell.n_servers} servers, "
         f"{traffic['traces']} traces x {cell.n_vms} VMs "
         f"over {config['trace_days']} days")

    with count_compiles() as setup_compiles:
        cell.make_traces()
        # the warm-up compiles; it runs with the program's recorder off,
        # so the jit cache keeps the bare programs
        answer_mod.answer(cell, LayerClock())
    setup_s = time.perf_counter() - t_start
    _log(f"bench: set-up {setup_s:.3f}s, {setup_compiles['n']} XLA "
         f"compiles ({setup_compiles['s']:.3f}s)")

    trace_dir = WORK_DIR / workload / "trace" if trace else None
    if trace_dir is not None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = run_window(cell, answer_mod, seconds, trace_dir)
    attempted = len(win["outputs"]) + win["failed"]
    answer_s = win["window_s"] / attempted
    _log(f"bench: window {win['window_s']:.3f}s, {attempted} answers, "
         f"{win['failed']} failed, answer_s {answer_s:.4f}; XLA compiles "
         f"in the window: {win['compiles']['n']}; layers (s) "
         + ", ".join(f"{k} {v:.3f}" for k, v in win["layers"].items()))

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    result = {"correct": False, "attempted": attempted,
              "failed": win["failed"]}
    if trace:
        path = devtrace.find_trace(trace_dir)
        summary = None
        if path is not None:
            events = devtrace.read(path)
            events["program_spans"] = win["spans"]
            summary = devtrace.summarize(events)
        if summary is not None:
            _log(f"bench: device trace {summary['window_s']:.3f}s of "
                 f"{win['window_s']:.3f}s (cut: {summary['cut']}), busy "
                 f"{summary['busy_s']:.3f}s, {summary['kernel_runs']} "
                 f"whole sweep runs in {summary['kernel_s']:.3f}s")
        else:
            _log("bench: the device trace holds no window")
        ctx = {"answers": attempted, "obs": win["obs"],
               "layers": win["layers"], "device": summary,
               "work": sweep_work(cell, win), "peaks": peaks}
        result["metrics"] = per_layer(bench, workload, ctx)
        if summary is not None:
            device.update(busy_s=summary["busy_s"],
                          window_s=summary["window_s"])
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {
            "answer_s": {"value": answer_s, "unit": units["answer_s"]},
            "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
    result["device"] = device

    outputs = win.pop("outputs")
    del win
    gc.collect()
    checks = answer_mod.check(cell, outputs) if outputs else {}
    result["correct"] = bool(outputs) and result["failed"] == 0 and all(
        v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
