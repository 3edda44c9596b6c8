"""Docs-freshness checks: the README and architecture notes exist, and
the paper-figure -> benchmark-script map only references scripts that
exist (and misses none)."""
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


def test_readme_exists_with_required_sections():
    text = _read("README.md")
    assert "python -m pytest -x -q" in text          # tier-1 command
    assert "experiments/BENCH_replay.json" in text   # perf tracking
    assert "--perf-smoke" in text                    # invocation note
    assert "docs/replay_engine.md" in text
    assert "load_trace_file" in text                 # ingestion pointer


def test_readme_covers_streaming_scale_out():
    text = _read("README.md")
    assert "Scaling to real traces" in text          # section anchor
    for topic in ("iter_trace_chunks", "CompiledReplayStream",
                  "max_events_per_shard",            # memory budget knob
                  "scripts/fetch_azure_trace.py",
                  "docs/traces.md", "docs/index.md",
                  # the composed streaming-batch axis + its benchmark
                  "CompiledReplayStreamBatch", "sweep_core",
                  "stream_batch_", "benchmarks/azure_e2e.py",
                  # robustness layer: chaos tests + resumable sweeps
                  "CheckpointSpec", "--resume", "max_bad_rows",
                  "-m chaos",
                  # multi-device scale-out: sharded sweeps + the
                  # forced-device-pool recipe + perf keys
                  "devices=", "shard_map",
                  "--xla_force_host_platform_device_count",
                  "overlap_ratio", "skip_windows", "--what device",
                  "JAX_COMPILATION_CACHE_DIR"):
        assert topic in text, f"README misses {topic!r}"
    # measured streaming numbers stay cited (events/s at K seeds x
    # N shards come from the perf-smoke artifact)
    assert "candidate-events/s" in text and "shards" in text


def test_replay_engine_doc_exists_and_covers_architecture():
    text = _read("docs", "replay_engine.md")
    for topic in ("int32", "slot", "divergence", "bit-exact",
                  "CompiledReplayBatch", "lax.scan",
                  # streaming/sharded-carry design + int16 packing rules
                  "CompiledReplayStream", "max_events_per_shard",
                  "int16", "carry",
                  # the unified sweep core (layer diagram + keyed cache
                  # + device placement) and the composed batch axis
                  "sweep_core", "keyed jit cache", "pick_state_dtype",
                  "CompiledReplayStreamBatch", "device_put", "donated",
                  "azure_e2e",
                  # the failure-domain chaos layer + availability sweep
                  "FailureSchedule", "blast radius", "remigrate",
                  "replay_with_failures", "fig_availability",
                  # checkpoint/resume + the invariant guard
                  "CheckpointSpec", "SweepInterrupted",
                  "kill_after_shards", "POND_DEBUG_INVARIANTS",
                  "SweepInvariantError",
                  # multi-device scale-out + the streaming pipeline
                  "devices=", "shard_map", "lane_shard_count",
                  "xla_force_host_platform_device_count",
                  "double-buffer", "stream.overlap_ratio",
                  "skip_windows", "shards_skipped",
                  "test_device_shard",
                  # the Pallas kernel the TPU runs in place of the scan
                  "build_pallas_sweep", "EVENT_BLOCK", "VMEM",
                  "test_sweep_pallas"):
        assert topic.lower() in text.lower(), \
            f"docs/replay_engine.md misses {topic!r}"
    # the layer diagram names each layer of the stack
    for layer in ("core/sweep_core.py", "core/replay_engine.py",
                  "core/cluster_sim.py", "benchmarks/"):
        assert layer in text, \
            f"docs/replay_engine.md layer diagram misses {layer!r}"


def test_policy_engine_doc_exists_and_covers_architecture():
    text = _read("docs", "policy_engine.md")
    for topic in ("PolicyDecisions", "policy_decisions_compiled",
                  "grid_decisions", "bit-exact", "segment",
                  "percentile", "predict_proba_batch", "pack_gbms",
                  "fig17_sensitivity", "t_migrate",
                  "--what policy", "--policy-grid"):
        assert topic.lower() in text.lower(), \
            f"docs/policy_engine.md misses {topic!r}"


def test_readme_covers_policy_engine():
    text = _read("README.md")
    for topic in ("policy_engine", "PolicyDecisions", "--policy-grid",
                  "docs/policy_engine.md", "--what policy"):
        assert topic in text, f"README misses {topic!r}"


def test_latency_engine_doc_exists_and_covers_architecture():
    text = _read("docs", "latency_engine.md")
    for topic in ("latency_engine", "slowdown_band_grid", "spill_grid",
                  "li_curve_grid", "um_curve_grid", "combine_grid",
                  "pdm_violation_grid", "hierarchy_slowdown_grid",
                  "TierHierarchy", "tiered_pricing", "bit-exact",
                  "lax.scan", "backend",
                  # the pinned seed-bug fixes
                  "exceeds_pdm", "interp_tradeoff", "spill_fraction",
                  # perf tracking
                  "latency_bench", "--what latency", "latency_*",
                  "tests/golden"):
        assert topic.lower() in text.lower(), \
            f"docs/latency_engine.md misses {topic!r}"
    # the oracle modules stay named (they remain the parity reference)
    for oracle in ("latency_model", "znuma", "qos", "eqn1"):
        assert oracle in text, \
            f"docs/latency_engine.md misses oracle {oracle!r}"


def test_readme_covers_latency_engine():
    text = _read("README.md")
    for topic in ("latency_engine", "TierHierarchy",
                  "docs/latency_engine.md", "--what latency",
                  "latency_*", "benchmarks/latency_bench.py",
                  "tests/golden"):
        assert topic in text, f"README misses {topic!r}"


def test_topology_doc_exists_and_covers_architecture():
    text = _read("docs", "topology.md")
    for topic in ("incidence", "partitioned", "overlapping", "sparse",
                  "split_pool", "reject_rates_fleet",
                  "replay_multi_pool", "bit-exact",
                  "build_pod_sweep", "pick_pod_state_dtype",
                  "granting pod", "MIGRATE", "orphan",
                  "FleetPoolManager", "fail_emc",
                  # the differential suite + perf tracking
                  "test_topology_engine", "fig_topology",
                  "topology_*", "--what topology", "golden"):
        assert topic.lower() in text.lower(), \
            f"docs/topology.md misses {topic!r}"
    # the degenerate anchors stay documented (they define the contract)
    for anchor in ("single_pool", "n_groups", "zero-member",
                   "all-orphan"):
        assert anchor in text, f"docs/topology.md misses {anchor!r}"


def test_readme_covers_topology_engine():
    text = _read("README.md")
    for topic in ("topology.py", "reject_rates_fleet",
                  "replay_multi_pool", "docs/topology.md",
                  "--what topology", "topology_*",
                  "benchmarks/fig_topology.py", "FleetPoolManager",
                  "tests/test_topology_engine.py"):
        assert topic in text, f"README misses {topic!r}"


def test_observability_doc_exists_and_covers_architecture():
    text = _read("docs", "observability.md")
    for topic in ("Recorder", "POND_TRACE", "use_recorder", "span",
                  "counter", "no-op",
                  # counter catalogue anchors
                  "jit.", "pad.", "device_put", "reject_cap",
                  "checkpoint", "policy.", "ingest.",
                  # exports + the profiler bridge
                  "to_chrome_trace", "run_manifest", "perfetto",
                  "--what obs", "TraceAnnotation", "jax.profiler",
                  "batch.compute", "sweep.steps", "test_obs",
                  "sweep.kernel.pallas", "sweep.kernel.scan", "pallas1"):
        assert topic.lower() in text.lower(), \
            f"docs/observability.md misses {topic!r}"


def test_readme_covers_observability():
    text = _read("README.md")
    for topic in ("obs.py", "POND_TRACE", "jax.profiler",
                  "docs/observability.md", "--what obs", "perfetto"):
        assert topic.lower() in text.lower(), f"README misses {topic!r}"


def test_traces_doc_covers_schema_and_ingestion():
    text = _read("docs", "traces.md")
    for topic in ("arrival", "lifetime", "cores", "mem_gb",  # schema
                  "vmcreated", "vmcorecount",                # aliases
                  "TraceSchemaError", "iter_trace_chunks",
                  "fixture_trace_path", "fetch_azure_trace.py",
                  "non-decreasing",
                  # fault-hardened ingestion + the resumable fetch
                  "max_bad_rows", "IngestReport", "io_retries",
                  "quarantine", "backoff", "Range"):
        assert topic in text, f"docs/traces.md misses {topic!r}"


def test_docs_index_links_every_docs_page_and_resolves():
    text = _read("docs", "index.md")
    linked = set(re.findall(r"\]\(([\w./-]+\.md)\)", text))
    assert linked, "docs/index.md has no markdown links"
    for rel in linked:
        target = os.path.normpath(os.path.join(REPO, "docs", rel))
        assert os.path.isfile(target), \
            f"docs/index.md links missing file {rel}"
    # ... and no docs page is orphaned from the index
    pages = {f for f in os.listdir(os.path.join(REPO, "docs"))
             if f.endswith(".md") and f != "index.md"}
    missing = pages - {os.path.basename(p) for p in linked}
    assert not missing, f"docs/index.md misses pages {sorted(missing)}"
    # the index names every core module it maps
    for mod in ("traces.py", "sweep_core.py", "replay_engine.py",
                "cluster_sim.py", "control_plane.py"):
        assert mod in text, f"docs/index.md misses module {mod}"


def test_readme_scripts_references_exist():
    text = _read("README.md")
    refs = re.findall(r"scripts/(\w+\.py)", text)
    assert refs, "README references no scripts/"
    for rel in set(refs):
        assert os.path.isfile(os.path.join(REPO, "scripts", rel)), \
            f"README references missing scripts/{rel}"


def test_readme_figure_map_references_existing_scripts():
    text = _read("README.md")
    referenced = set(re.findall(r"benchmarks/(fig\w+\.py)", text))
    assert referenced, "README has no figure -> script map"
    for script in referenced:
        assert os.path.isfile(os.path.join(REPO, "benchmarks", script)), \
            f"README references missing script benchmarks/{script}"
    # ... and the map covers every figure benchmark in the repo
    present = {f for f in os.listdir(os.path.join(REPO, "benchmarks"))
               if re.fullmatch(r"fig\w+\.py", f)}
    missing = present - referenced
    assert not missing, f"README figure map misses {sorted(missing)}"


def test_readme_examples_reference_existing_files():
    text = _read("README.md")
    for rel in re.findall(r"examples/(\w+\.py)", text):
        assert os.path.isfile(os.path.join(REPO, "examples", rel))
