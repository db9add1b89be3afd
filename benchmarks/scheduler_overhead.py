"""Software analogue of paper Table 4 (GMN area/clock): the per-decision
cost of the two-stage mapper in this framework's scheduler, vs a flat
argmin over all m units, across cluster counts k.

Also reports the TLM sweep engine's throughput (events/s across a batch
of knob configs in one compiled program) — the batched path every
design-space benchmark (fig3a/fig3b/table5/baseline_compare) rides on."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sweep as SW
from repro.core.experiment import ExperimentSpec, WorkloadSpec
from repro.core.sim import SimParams
from repro.kernels import ops

from benchmarks.common import csv_row, enable_compile_cache, save


def _bench(fn, *args, iters=20):
    fn(*args)                                 # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def _bench_sweep(thresholds=(1, 2, 4, 8), iters=3):
    """Events/second of the sweep engine (both single-device dispatch
    strategies) on a small interference grid.

    The grid is *defined* declaratively (the spec is the payload's
    provenance), but the timed loop drives the underlying engine with
    prebuilt inputs — exactly what this benchmark has always measured —
    so the BENCH trajectory stays comparable: workload generation,
    planning and ResultFrame construction are not on the clock."""
    spec = ExperimentSpec(
        base=SimParams(m=64, k=8, n_childs=32, max_apps=64, queue_cap=1024),
        knobs={"dn_th": thresholds},
        workloads=(WorkloadSpec("interference", seeds=(0,)),),
        sim_len=3e5)
    combo = spec.plan().combos[0]
    _, wl = spec.workloads[0].build(combo.shape, spec.sim_len)
    out = {"configs": len(thresholds), "spec": spec.to_dict()}
    for mode in ("seq", "vmap"):
        st = jax.block_until_ready(
            SW.sweep(combo.shape, spec.knobs, wl, spec.sim_len, mode=mode,
                     policy=combo.policy, topology=combo.topology))
        t0 = time.time()
        for _ in range(iters):
            st = jax.block_until_ready(
                SW.sweep(combo.shape, spec.knobs, wl, spec.sim_len,
                         mode=mode, policy=combo.policy,
                         topology=combo.topology))
        dt = (time.time() - t0) / iters
        events = int(np.asarray(st["events_processed"]).sum())
        out[mode] = {"events_per_batch": events,
                     "sweep_s": dt,
                     "events_per_sec": events / dt,
                     "us_per_event": dt / events * 1e6}
    return out


def run(verbose: bool = True, m: int = 256, n_tasks: int = 100) -> dict:
    rows = {}
    costs = jnp.ones((n_tasks,), jnp.float32)

    @jax.jit
    def flat_assign(loads_flat, costs):
        def step(loads, c):
            i = jnp.argmin(loads)
            return loads.at[i].add(c), i
        return jax.lax.scan(step, loads_flat, costs)

    flat = jnp.zeros((m,), jnp.float32)
    t_flat = _bench(flat_assign, flat, costs)

    for k in (1, 8, 16, 32, 256):
        loads = jnp.zeros((k, m // k), jnp.float32)
        t = _bench(lambda l=loads: ops.assign_tasks(l, costs))
        rows[str(k)] = {"us_per_batch": t * 1e6,
                        "us_per_decision": t * 1e6 / n_tasks}
    sweep_engine = _bench_sweep()
    payload = {
        "two_stage": rows,
        "flat_argmin_us_per_batch": t_flat * 1e6,
        "sweep_engine": sweep_engine,
        "note": "paper Table 4 is 65nm silicon area (out of scope); this is "
                "the software scheduler's decision latency on this host",
    }
    save("scheduler_overhead", payload, spec=sweep_engine.pop("spec"))
    if verbose:
        csv_row("scheduler_overhead",
                rows["16"]["us_per_batch"],
                f"us_per_decision_k16={rows['16']['us_per_decision']:.2f}"
                f"|sweep_ev_per_s="
                f"{sweep_engine['seq']['events_per_sec']:.0f}")
    return payload


if __name__ == "__main__":
    enable_compile_cache()
    run()
