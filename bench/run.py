"""The chip benchmark of the transaction-level task-manager simulator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell of ``BENCHMARK.json`` per process, found by name: its
configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``), its plain reference
(``bench/<reference>.py``, ``bench/reference.py`` unless the configuration
names another), the reference's generators of that mix's stimulus and
fault schedule (``bench/stimulus/<kind>.py``, ``bench/faults/<kind>.py``)
and, with ``--trace 1``, the readers of its per-layer metrics
(``bench/metrics/<metric>.py``).

A configuration runs one static group, or one per entry of its
``shapes`` list (a frontier of cluster counts, say), dispatched as its
``mode`` says (``pmap``: one group per chip).

1. Set-up: find the accelerator (none, or fewer chips than the cell
   needs, exits nonzero with no result), turn on the persistent compile
   cache, and compile the cell's programs, one per group, by running its
   spec at a horizon that admits no event (``sim_len`` is traced, so
   these are the timed programs, and they simulate nothing).
2. The window: grid after grid through ``ExperimentSpec.run()``, each
   grid one dispatch of every group's knob x stimulus lanes, its fixed
   set of stimulus seeds in an order drawn from ``--seed``, until the
   first grid that ends after ``--seconds``.  A program compiled inside
   the window is an error, and so is a ``pmap`` grid whose groups did not
   run on distinct chips.  With ``--trace 1`` the profiler records the
   window's first grid, and the per-layer metrics are read from it.
3. The check: every lane of every group of every grid against the plain
   reference at that group's own shape, and every lane against the
   configuration's guarantee that no event is dropped.  A grid whose
   groups are not the configuration's, one for one, fails the lanes of
   each group that is missing or extra.
4. The last line of standard output is one JSON object; the numbers the
   check compared, each with its limit, are the last lines of standard
   error and the last key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH]

import trace_reduce as TRACE  # noqa: E402

# the simulator's parameters a configuration file sets
SIM_KEYS = ("m", "k", "n_childs", "max_apps", "queue_cap", "c_b", "c_s",
            "c_join", "T_b", "c_hop", "susp_mult", "retry_after", "mapping",
            "beacon", "topology", "queue_impl", "batch_pop")
# the keys of SIM_KEYS that make a static group; an entry of a
# configuration's ``shapes`` sets only these
SHAPE_KEYS = ("m", "k", "n_childs", "max_apps", "queue_cap", "queue_impl",
              "batch_pop")
# limits of the check (PERF.md gives the readings they come from): the
# guarantee and the bitwise comparison are exact; the order-dependent
# latency sum read 0.0 on every sound chip run, 4.4e-4 and up in the control
LIMITS = {"lanes_failed": 0, "events_dropped": 0,
          "mgmt_latency_rel_gap": 1e-5}
# queue internals: named differently per queue structure, not compared
QUEUE_LEAVES = ("evq_tree", "evq_root", "evq_cal", "ev_time", "ev_type",
                "ev_a")


def log(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


# --------------------------------------------------------------------------
# The cell, by name
# --------------------------------------------------------------------------

def load_cell(root: str, name: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    (cfg,) = [c for c in bm["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, cfg["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    end_to_end = [m for m in bm["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic, "end_to_end": end_to_end,
            "per_layer": per_layer, "root": root}


@functools.lru_cache(maxsize=None)
def load_part(root: str, part: str, name: str):
    """``bench/<part>/<name>.py`` as a module, found by name alone
    (``bench/<name>.py`` where ``part`` is empty)."""
    path = os.path.join(root, "bench", part, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{part}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root: str, name: str):
    return load_part(root, "metrics", name).read


def reference(cell: dict):
    """The configuration's plain reference: the module its ``reference``
    key names under ``bench/``, else ``bench/reference.py``."""
    return load_part(cell["root"], "",
                     cell["config"].get("reference", "reference"))


def group_shapes(config: dict) -> list:
    """The simulator's parameters of each static group the configuration
    runs, in dispatch order: each ``shapes`` entry over the shared keys,
    or the shared keys alone for a configuration without ``shapes``."""
    shared = {k: config[k] for k in SIM_KEYS}
    entries = config.get("shapes", [{}])
    for e in entries:
        if set(e) - set(SHAPE_KEYS):
            raise ValueError(f"a shapes entry sets only {SHAPE_KEYS}, "
                             f"not {sorted(set(e) - set(SHAPE_KEYS))}")
    return [shared | e for e in entries]


# --------------------------------------------------------------------------
# Device and compile cache
# --------------------------------------------------------------------------

def find_devices(chips: int) -> list:
    """The accelerators JAX sees; exits when there are none or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX found only CPU devices")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def enable_compile_cache(root: str) -> None:
    """JAX's persistent cache, where ``JAX_COMPILATION_CACHE_DIR`` says or
    at the fixed ``<checkout>/.jax_cache``, for every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered by JAX (each jit cache miss)."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


# --------------------------------------------------------------------------
# The traffic
# --------------------------------------------------------------------------

def fault_params(traffic: dict, sim_len: float) -> dict | None:
    f = traffic.get("faults")
    if not f:
        return None
    params = dict(f.get("params", {}))
    for key, frac in f.get("at_fraction_of_sim_len", {}).items():
        params[key] = frac * sim_len
    return params


def grid_seeds(traffic: dict, rng: np.random.Generator) -> list:
    """One dispatch's stimulus seeds: the traffic's fixed set, in an order
    drawn from the run's seed.  A fixed set keeps the work of every grid
    the same, so the window measures the simulator and not the draw."""
    return [int(s) for s in rng.permutation(traffic["stimulus_seeds"])]


def make_spec(config: dict, traffic: dict, seeds, sim_len: float):
    from repro.core.experiment import ExperimentSpec, WorkloadSpec
    from repro.core.faults import FaultSpec
    from repro.core.sim import SimParams
    fp = fault_params(traffic, config["sim_len"])
    faults = (None,) if fp is None else (FaultSpec.from_dict(
        {"kind": traffic["faults"]["kind"], "params": fp}),)
    shapes = None if "shapes" not in config else tuple(
        SimParams(**p) for p in group_shapes(config))
    return ExperimentSpec(
        base=SimParams(**{k: config[k] for k in SIM_KEYS}), shapes=shapes,
        knobs={k: tuple(v) for k, v in traffic["knobs"].items()},
        workloads=(WorkloadSpec.make(traffic["kind"], seeds=seeds,
                                     **traffic["params"]),),
        faults=faults, trace=traffic.get("trace"), sim_len=sim_len,
        mode=config.get("mode", "auto"))


# --------------------------------------------------------------------------
# Set-up, window, check
# --------------------------------------------------------------------------

def lane_knobs(spec) -> list:
    """The knob values of each knob-axis entry of a spec, in its order."""
    kn = spec.knobs
    return [{f: np.asarray(getattr(kn, f))[b].item() for f in kn._fields}
            for b in range(np.asarray(kn.dn_th).shape[0])]


def warm_up(cell: dict, rng_seed: int) -> float:
    """Compile the cell's programs, one per group: its spec at a horizon
    that admits no event.  Returns the seconds it took."""
    from repro.core import sweep as SW
    rng = np.random.default_rng(rng_seed)
    spec = make_spec(cell["config"], cell["traffic"],
                     grid_seeds(cell["traffic"], rng), 0.0)
    c0 = SW.cache_size()
    t0 = time.perf_counter()
    frame = spec.run()
    dt = time.perf_counter() - t0
    log(f"set-up run: mode={frame.mode} programs compiled "
        f"(sweep.cache_size delta)={SW.cache_size() - c0} "
        f"groups={len(frame.groups)} devices="
        f"{[g.device for g in frame.groups]} events="
        f"{sum(int(np.sum(g.state['events_processed'])) for g in frame.groups)}"
        f" seconds={dt:.3f}")
    return dt


def group_result(g, chips: dict) -> dict:
    """One group of a grid: its shape, the chip it ran on (the device's id,
    the ``n`` of the trace's ``/device:TPU:<n>``), its final state less the
    queue's internals, and its lanes' events."""
    state = {k: v for k, v in g.state.items() if k not in QUEUE_LEAVES}
    return {"shape": {k: getattr(g.combo.shape, k) for k in SHAPE_KEYS},
            "device": g.device, "chip": chips.get(g.device),
            "state": state,
            "events": int(np.sum(state["events_processed"]))}


def window(cell: dict, seed: int, seconds: float,
           profile_dir: str | None = None) -> dict:
    """Dispatch grids until the first one that ends after ``seconds``.
    With ``profile_dir``, the profiler records the first grid, inside the
    ``bench.window`` span, with the harness's spans around each step."""
    import jax
    config, traffic = cell["config"], cell["traffic"]
    rng = np.random.default_rng(seed)
    chips = {str(d): d.id for d in jax.devices()}
    counter = CompileCounter()
    grids = []
    profile = contextlib.ExitStack()
    if profile_dir is not None:
        jax.profiler.start_trace(profile_dir)
        profile.callback(jax.profiler.stop_trace)
        profile.enter_context(jax.profiler.TraceAnnotation(TRACE.WINDOW_SPAN))

    def span(name):
        return jax.profiler.TraceAnnotation(name) if grids == [] and \
            profile_dir is not None else contextlib.nullcontext()

    with profile:
        t0 = time.perf_counter()
        while True:
            with span("bench.spec_build"):
                seeds = grid_seeds(traffic, rng)
                spec = make_spec(config, traffic, seeds, config["sim_len"])
            n0 = counter.n
            with span("bench.run"):
                frame = spec.run()
            with span("bench.loop"):
                groups = [group_result(g, chips) for g in frame.groups]
                grid = {"seeds": seeds, "knobs": lane_knobs(spec),
                        "groups": groups,
                        "events": sum(g["events"] for g in groups)}
                compiled = counter.n - n0
            grids.append(grid)
            profile.close()
            log(f"grid {len(grids)}: mode={frame.mode} "
                f"events={grid['events']} wall_s={frame.wall_s:.4f} "
                f"compiles={compiled} chips={[g['chip'] for g in groups]}")
            if compiled:
                raise SystemExit(f"{compiled} programs compiled inside the "
                                 "window")
            if config.get("mode") == "pmap" and (
                    frame.mode != "pmap" or
                    len({g["chip"] for g in groups}) != len(groups)):
                raise SystemExit(f"the cell dispatches one group per chip; "
                                 f"{frame.mode} put its groups on "
                                 f"{[g['device'] for g in groups]}")
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
    return {"grids": grids, "wall_s": wall}


def matched_groups(grid: dict, shapes: list):
    """(shape, group) for each group the configuration runs, group None
    where the grid's group in that place is missing or of another shape;
    then (None, group) for each group beyond the configuration's."""
    groups = grid["groups"]
    for i, shape in enumerate(shapes):
        g = groups[i] if i < len(groups) else None
        same = g is not None and all(g["shape"][k] == shape[k]
                                     for k in SHAPE_KEYS)
        yield shape, g if same else None
    for g in groups[len(shapes):]:
        yield None, g


def lane_states(grid: dict, group: dict | None):
    """(knobs, stimulus seed, the program's final state) of every lane of
    one group of a grid; the state None where the group lacks the lane."""
    st = {} if group is None else group["state"]
    n_b, n_s = (0, 0) if group is None else np.shape(st["events_processed"])
    for b, knobs in enumerate(grid["knobs"]):
        for s, seed in enumerate(grid["seeds"]):
            lane = ({k: np.asarray(v)[b, s] for k, v in st.items()}
                    if b < n_b and s < n_s else None)
            yield knobs, seed, lane


def reference_inputs(cell: dict, shape: dict, seed: int):
    """One lane's stimulus (arrivals, gmns, lengths) and fault schedule at
    a group's shape, from the benchmark's own generators of the traffic's
    kinds."""
    config, traffic = cell["config"], cell["traffic"]
    gen = load_part(cell["root"], "stimulus", traffic["kind"]).generate
    arr, gmns, lens = gen(shape["max_apps"], shape["n_childs"], shape["k"],
                          sim_len=config["sim_len"], seed=seed,
                          **traffic["params"])
    fp = fault_params(traffic, config["sim_len"])
    faults = None if fp is None else load_part(
        cell["root"], "faults", traffic["faults"]["kind"]).generate(
            shape["k"], **fp)
    return arr, gmns, lens, faults


def reference_lane(cell: dict, shape: dict, knobs: dict, seed: int,
                   queue_cap: int | None = None) -> dict:
    """One lane of a group of the given shape, run by the configuration's
    plain reference."""
    config = cell["config"]
    arr, gmns, lens, faults = reference_inputs(cell, shape, seed)
    return reference(cell).simulate(config | shape | knobs, arr, gmns, lens,
                                    config["sim_len"], faults=faults,
                                    queue_cap=queue_cap)


def compare_lane(got: dict | None, want: dict,
                 order_dependent=()) -> tuple[list, float]:
    """Leaves of one lane that differ from the reference, and the relative
    gap of the order-dependent sums (the reference's ``ORDER_DEPENDENT``).
    A leaf differs when its dtype is not the one the reference states (f32
    times, int32 counts), when its shape differs, or when a bit differs.
    A missing lane differs on all."""
    keys = [k for k in want if k not in ("iterations",)]
    if got is None:
        return keys, 0.0
    bad, gap = [], 0.0
    for k in keys:
        if k not in got:
            bad.append(k)
            continue
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if g.dtype != w.dtype or g.shape != w.shape:
            bad.append(k)
        elif k in order_dependent:
            gap = max(gap, abs(float(g) - float(w)) / max(abs(float(w)), 1.0))
        elif not np.array_equal(g, w):
            bad.append(k)
    return bad, gap


def check(cell: dict, grids: list) -> dict:
    """Every lane of every group against the reference at the group's
    shape, and against the no-drop guarantee."""
    shapes = group_shapes(cell["config"])
    order_dependent = reference(cell).ORDER_DEPENDENT
    refs = {}
    failed = lanes = dropped = 0
    gap = 0.0
    for gi, grid in enumerate(grids):
        for shape, group in matched_groups(grid, shapes):
            for knobs, seed, got in lane_states(grid, group):
                lanes += 1
                if got is not None:
                    dropped += int(got["dropped"])
                if shape is None:
                    failed += 1
                    log(f"lane failed: grid {gi + 1} group beyond the "
                        f"configuration's: {group['shape']}")
                    continue
                key = (tuple(sorted(shape.items())), tuple(knobs.items()),
                       seed)
                if key not in refs:
                    refs[key] = reference_lane(cell, shape, knobs, seed)
                bad, g = compare_lane(got, refs[key], order_dependent)
                gap = max(gap, g)
                if bad or g > LIMITS["mgmt_latency_rel_gap"] or \
                        (got is not None and int(got["dropped"])):
                    failed += 1
                    log(f"lane failed: grid {gi + 1} k={shape['k']} "
                        f"knobs={knobs} seed={seed} leaves={bad} "
                        f"mgmt_latency_rel_gap={g:.3g}")
    return {"lanes": lanes, "failed": failed,
            "numbers": {"lanes_failed": failed, "events_dropped": dropped,
                        "mgmt_latency_rel_gap": gap}}


# --------------------------------------------------------------------------
# Per-layer readings
# --------------------------------------------------------------------------

class Reading:
    """What a per-layer metric reader may read: the window's grids (each
    with its groups), the trace of its first grid as ``trace_reduce``
    extracts it (``events``) and reduces it (``trace``), the set-up
    compile time and the compiled program of a one-group cell."""

    def __init__(self, cell, win, trace, compile_s, events=None):
        self.cell, self.grids, self.trace = cell, win["grids"], trace
        self.events = events
        self.compile_s = compile_s
        self._compiled = None

    def compiled(self):
        """A one-group cell's program as compiled for the chip (from the
        cache)."""
        if self._compiled is None:
            import jax.numpy as jnp
            from repro.core import sweep as SW
            spec = make_spec(self.cell["config"], self.cell["traffic"],
                             self.grids[0]["seeds"],
                             self.cell["config"]["sim_len"])
            (combo,) = spec.plan().combos
            _, (arr, gmns, lens) = spec.workloads[0].build(combo.shape,
                                                           spec.sim_len)
            (f,) = spec.faults
            fs = None if f is None else f.build(combo.shape.k, spec.sim_len)
            self._compiled = SW._sweep.lower(
                combo.shape, spec.knobs, jnp.asarray(arr, jnp.float32),
                jnp.asarray(gmns, jnp.int32), jnp.asarray(lens, jnp.float32),
                jnp.float32(spec.sim_len), combo.policy, combo.topology, fs,
                spec.trace).compile()
        return self._compiled


def device_info(devs, chips: int) -> dict:
    """The devices as JAX reports them, and the memory peak of the fullest
    chip the cell used: the peak of its buffers, executables included,
    plus the peak the runtime reserved apart from them for the programs'
    scratch, which the first does not count."""
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             + d.memory_stats().get("peak_bytes_reserved", 0)
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             devices=find_devices) -> dict:
    devs = devices(cell["chips"])
    d = devs[0]
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} cell chips={cell['chips']}")
    enable_compile_cache(cell["root"])
    compile_s = warm_up(cell, seed)
    setup_s = time.perf_counter() - T_START
    log(f"setup_s={setup_s:.3f}")
    profile_dir = tempfile.mkdtemp(prefix="bench_profile_") if traced \
        else None
    win = window(cell, seed, seconds, profile_dir)
    events = sum(g["events"] for g in win["grids"])
    device = device_info(devs, cell["chips"])
    trace = traced_ev = None
    if traced:
        try:
            traced_ev = TRACE.extract(profile_dir, cell["chips"])
            trace = TRACE.summarize(traced_ev)
        finally:
            shutil.rmtree(profile_dir, ignore_errors=True)
        if trace is None:
            raise SystemExit("the trace holds no window span or no device "
                             "operation")
        device |= {"busy_s": trace["busy_s"], "window_s": trace["window_s"]}
        log(f"trace: window_s={trace['window_s']!r} busy_s_per_chip="
            f"{trace['busy_s_per_chip']!r} pace_chip={trace['pace_chip']}")
    log(f"window: grids={len(win['grids'])} events={events} "
        f"wall_s={win['wall_s']:.4f}")
    result = check(cell, win["grids"])
    out = {"correct": result["failed"] == 0 and all(
               v <= LIMITS[k] for k, v in result["numbers"].items()),
           "attempted": result["lanes"], "failed": result["failed"]}
    if traced:
        reading = Reading(cell, win, trace, compile_s, traced_ev)
        metrics = {}
        for m in cell["per_layer"]:
            v = metric_reader(cell["root"], m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
    else:
        values = {"sim_events_per_s": events / win["wall_s"],
                  "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell["end_to_end"]}
    out["device"] = device
    if traced:
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in result["numbers"].items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cell = load_cell(ROOT, args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
