"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON payloads to
results/.  The roofline table (EXPERIMENTS.md §Roofline) comes from the
separate 512-device dry-run (python -m repro.launch.dryrun --all), which
must run in its own process because of XLA_FLAGS.

``--profile`` wraps the whole suite in a JAX profiler trace (a Perfetto/
TensorBoard-loadable dump under ``results/profile/``, flag-overridable)
and prints a per-benchmark wall-clock summary at the end — the quickest
way to see which table dominates the suite and where XLA compiles land.
Each benchmark additionally runs inside a ``jax.profiler
.TraceAnnotation`` named after it, so the Perfetto dump attributes every
compile and device launch to its benchmark instead of one anonymous
blob (open the dump in ui.perfetto.dev and search the benchmark name).
"""
from __future__ import annotations

import argparse
import contextlib
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", nargs="?", const="results/profile",
                    default=None, metavar="DIR",
                    help="record a JAX profiler trace to DIR (default "
                         "results/profile) and print per-benchmark wall "
                         "times")
    args = ap.parse_args(argv)

    from benchmarks.common import enable_compile_cache
    enable_compile_cache()
    from benchmarks import baseline_compare, fig2a, fig2b, fig3a, fig3b, table5
    from benchmarks import fault_frontier, moe_balance, scheduler_overhead
    from benchmarks import topology_frontier

    trace = None
    if args.profile is not None:
        import jax
        trace = jax.profiler.trace(args.profile)
        trace.__enter__()

    walls = []

    def step(name, fn, *a, **kw):
        # named region in the profiler dump (no-op without --profile)
        if args.profile is not None:
            import jax
            region = jax.profiler.TraceAnnotation(name)
        else:
            region = contextlib.nullcontext()
        t0 = time.time()
        with region:
            out = fn(*a, **kw)
        walls.append((name, time.time() - t0))
        return out

    print("name,us_per_call,derived")
    ok = True
    step("fig2a", fig2a.run)
    b = step("fig2b", fig2b.run)
    ok &= b["fit_ok"]
    a = step("fig3a", fig3a.run)
    ok &= a["claim_k16_band"]
    bb = step("fig3b", fig3b.run)
    ok &= bb["claim_monotone"]
    ok &= bb["compile_once_per_shape"]
    t = step("table5", table5.run)
    ok &= t["ordering_clustered_best"]
    c = step("baseline_compare", baseline_compare.run)
    ok &= c["claim_clustered_best"]
    tf = step("topology_frontier", topology_frontier.run, grid="tiny")
    ok &= tf["claim_clustered_lowest_total_mgmt_latency"]
    ok &= tf["claim_ideal_bitwise_vs_run"]
    ff = step("fault_frontier", fault_frontier.run, grid="tiny")
    ok &= ff["claims_all_pass"]
    step("scheduler_overhead", scheduler_overhead.run)
    step("moe_balance", moe_balance.run)

    if trace is not None:
        trace.__exit__(None, None, None)
        total = sum(w for _, w in walls)
        print(f"# profile: trace written to {args.profile}")
        for name, w in sorted(walls, key=lambda x: -x[1]):
            print(f"# profile: {name:20s} {w:8.2f}s "
                  f"({100 * w / max(total, 1e-9):5.1f}%)")

    print(f"# paper-claim checks {'PASS' if ok else 'FAIL'}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
