"""Shared helpers for the paper-reproduction benchmarks.

Output contract (consumed by the BENCH_*.json trajectory tracking — see
benchmarks/README.md for the full schema): each benchmark module's
``run()`` writes ``results/<name>.json`` via :func:`save` and prints one
``name,us_per_call,derived`` CSV row via :func:`csv_row`.  The JSON
payload is a flat dict whose keys are stable across PRs: measured data
under ``curves``/``rows``, paper reference values under ``paper_claim``,
and one boolean per headline claim prefixed ``claim_`` (plus
free-standing booleans like ``ordering_clustered_best``).  Trajectory
tooling snapshots ``results/<name>.json`` into ``BENCH_<name>.json`` per
PR and diffs numeric leaves, so renaming or re-nesting keys breaks the
time series — add new keys instead of mutating existing ones."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core.transport import TOPOLOGIES

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(REPO_ROOT, "results")

# JSON schema version of the benchmark payloads.  v2 added the "meta"
# block (topology_meta below): results/*.json are self-describing about
# which interconnect fabric produced each number.  v3 added the
# throughput/cost fields that benchmarks riding the event-queue axis
# report per row — `events`, `events_per_sec`, `wall_s`,
# `marginal_wall_s`, `queue_impl` — plus the `paper` grid tier of
# benchmarks/topology_frontier.py.  v4 embeds the serialized
# ExperimentSpec that produced the numbers under a top-level "spec" key
# (core/experiment.py; null for benchmarks that don't ride the
# experiment engine) — every payload carries its full design-space
# provenance (see benchmarks/README.md).  v5 adds the fault-injection
# axis (DESIGN.md §13): specs may carry a "faults" list (serialized
# FaultSpecs; SPEC_VERSION 2), rows the availability columns
# `fault` / `msgs_lost` / `reroutes` / `downtime`, and fault-aware
# benchmarks a top-level `determinism_digest` (sha256 over the
# deterministic row fields, wall-clock excluded) that CI compares
# across two runs of the same fault seed.  v6 adds the fused-commit
# throughput fields (DESIGN.md §11 / PR 7): per-row `batch_pop` and
# `compile_s`, the `queue_head_to_head` block with the calendar impl
# and batched-vs-singleton bitwise claims, the `spec.batch_pops` axis,
# and the repo-root BENCH_eventq.json trajectory file.  v7 adds the
# observability axis (DESIGN.md §14): specs may carry a "trace" block
# (serialized TraceSpec; SPEC_VERSION 3), rows the histogram-derived
# percentile columns `p50/p95/p99_mgmt_latency` and
# `p50/p95/p99_response` (null when tracing was off) plus `evq_peak`
# (always-on queue high-water mark) and `trace_dropped`, and
# experiment-engine payloads a top-level "manifest" block with
# per-group wall-clock / compile-split telemetry
# (ResultFrame.manifest()).  benchmarks/trace_report.py gates the
# trace conservation claims; benchmarks/check_regression.py compares a
# fresh eventq run against the committed BENCH_eventq.json trajectory.
# v8 adds the failure-detector tier (DESIGN.md §15): specs may carry the
# susp_mult / retry_after knob axes and the avoid_suspected /
# suspect_weighted mapping policies (SPEC_VERSION 4), experiment rows
# the detector columns `susp_onsets` / `susp_clears` / `susp_false_pos`
# / `suspected_final` / `retries_tx`, and benchmarks/fault_frontier.py
# a top-level `detector_rows` block (with downtime-weighted
# `dw_availability`) plus the claim_detector_* gates; conservation
# generalizes to beacons_rx + msgs_lost == (k-1)*beacons_tx +
# retries_tx.  Existing keys are unchanged.
SCHEMA_VERSION = 8


def enable_compile_cache() -> None:
    """Keep compiled programs in JAX's persistent cache, for entry points.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set here.  Otherwise the cache lives at ``<repo>/.jax_cache/``: a fixed
    path, because the path is part of every entry's key."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(REPO_ROOT, ".jax_cache"))


def topology_meta(topologies=("ideal",), **extra) -> dict:
    """Standard self-description block for benchmark payloads: which
    fabric models the numbers were produced under ("ideal" is the
    pre-transport behavior, bitwise), plus the full topology vocabulary
    so downstream tooling can interpret per-topology keys without
    importing the simulator."""
    return {
        "schema_version": SCHEMA_VERSION,
        "topologies": list(topologies),
        "topology_vocabulary": list(TOPOLOGIES),
        "topology_default": "ideal",
        **extra,
    }


def determinism_digest(rows, exclude=("wall_s", "lane_wall_s",
                                      "events_per_sec", "marginal_wall_s",
                                      "us_per_call")) -> str:
    """sha256 over the deterministic fields of a row list (schema v5).

    Wall-clock columns are excluded; everything else — coordinates,
    knobs, simulation metrics, fault counters — must be bit-identical
    when a benchmark re-runs with the same seeds, which is exactly what
    the CI fault-smoke job asserts by diffing two digests."""
    import hashlib
    clean = [{k: v for k, v in sorted(r.items()) if k not in exclude}
             for r in rows]
    blob = json.dumps(clean, sort_keys=True, default=float)
    return hashlib.sha256(blob.encode()).hexdigest()


def save(name: str, payload: dict, spec=None):
    """Write ``results/<name>.json``.  ``spec`` is the ExperimentSpec (or
    its ``to_dict()``) that produced the payload — embedded verbatim as
    schema-v4 provenance; None marks a benchmark that doesn't ride the
    experiment engine."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    payload.setdefault("meta", topology_meta())
    if hasattr(spec, "to_dict"):
        spec = spec.to_dict()        # a benchmark may also pass a dict or
                                     # list of already-serialized specs
    payload.setdefault("spec", spec)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, time.time() - t0


def csv_row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")
