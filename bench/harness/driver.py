"""One run of one cell: build the deployment from the seed, warm up, drive
the program through a measured window, and keep what the check and the
metric readers need.

The window drives the program's own path: `ClusterRuntime.run` with the
storm absorber, `DormMaster` passes, `GreedyOptimizer.solve`, `JaxBackend`
(`place_run` holds the Pallas kernel on a TPU). The benchmark only wraps
the master and the backend from outside to time them, to keep a seeded
sample of what they were given and returned, and to close the window.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, traffic as traffic_gen

clock = time.perf_counter


class WindowClosed(Exception):
    """Raised from inside the runtime when the measured window is over."""


class CompileCounter:
    """Counts jax's own compile events (tracing, lowering, backend
    compiles and persistent-cache loads) while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax._src import monitoring
        self.armed = False
        self.counts: Dict[str, int] = {}
        self._mon = monitoring

        def on_duration(event, duration, **_):
            if self.armed and event in self.EVENTS:
                self.counts[event] = self.counts.get(event, 0) + 1

        def on_event(event, **_):
            if self.armed and event == "/jax/compilation_cache/cache_hits":
                self.counts[event] = self.counts.get(event, 0) + 1

        self._d, self._e = on_duration, on_event
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._d)
        self._mon.unregister_event_listener(self._e)

    @property
    def compiles(self) -> int:
        return (self.counts.get(self.EVENTS[2], 0)
                + self.counts.get("/jax/compilation_cache/cache_hits", 0))

    @property
    def traces(self) -> int:
        return self.counts.get(self.EVENTS[0], 0)


class Reservoir:
    """Seeded uniform sample of at most `size` items from a stream whose
    length is unknown in advance (algorithm R)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.seen = 0
        self.items: List[object] = []

    def offer(self) -> Optional[int]:
        """-> the slot the next item takes, or None to skip it."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(self.seen))
        return j if j < self.size else None


class Recorder:
    """The window: its clock, its passes and its samples."""

    def __init__(self, seconds: float, warmup_sim_s: float, trace: bool,
                 seed: int):
        self.seconds = seconds
        self.warmup_sim_s = warmup_sim_s
        self.trace = trace
        self.phase = "warmup"
        self.sim_t = 0.0
        self.t_open = self.t_close = None
        self.pass_wall: List[float] = []
        self.pass_k: List[int] = []
        self.tape: List[dict] = []          # every pass: what the runtime saw
        self.window_first = None            # tape index of the first timed pass
        rng = np.random.default_rng([seed, 3])
        self.pass_sample = Reservoir(check.PASS_SAMPLES, rng)
        self.call_sample = Reservoir(check.CALL_SAMPLES, rng)
        self.largest = None                 # the window pass with most events
        self.backend_s = 0.0
        self.place_run_bytes = 0.0
        self.on_open: List[Callable[[], None]] = []
        self.on_close: List[Callable[[], None]] = []

    @property
    def in_window(self) -> bool:
        return self.phase == "window"

    def span(self, name: str):
        if self.trace and self.in_window:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def before_pass(self) -> None:
        if self.phase == "warmup" and self.sim_t >= self.warmup_sim_s:
            for fn in self.on_open:
                fn()
            self.phase = "window"
            self.window_first = len(self.tape)
            self.t_open = clock()
        if self.phase == "window" and clock() >= self.t_open + self.seconds:
            self.t_close = clock()
            self.phase = "closed"
            for fn in self.on_close:
                fn()
            raise WindowClosed()


class PolicyProxy:
    """Stands between the runtime and `DormMaster`: times each pass from
    hand-off to the master until its allocation is back on the host, and
    keeps a seeded sample of passes with the state they started from."""

    def __init__(self, master, rec: Recorder):
        self.master, self.rec = master, rec

    def _pass(self, k: int, completions, arrivals, call):
        rec = self.rec
        rec.before_pass()
        if not rec.in_window:
            return call()
        m = self.master
        pre = (m.prev_alloc, tuple(m.specs), tuple(m.pending))
        with rec.span("dorm.pass"):
            t0 = clock()
            res = call()
            dt = clock() - t0
        rec.pass_wall.append(dt)
        rec.pass_k.append(k)
        idx = len(rec.tape)           # the tape entry this pass will get
        snap = {"tape": idx, "pre": pre, "res": res,
                "completions": tuple(completions),
                "arrivals": tuple(s.app_id for s in arrivals)}
        slot = rec.pass_sample.offer()
        if slot is not None:
            rec.pass_sample.items[slot] = snap
        if rec.largest is None or k > len(rec.largest["completions"]) + len(
                rec.largest["arrivals"]):
            rec.largest = snap
        return res

    def on_arrival(self, specs):
        return self._pass(len(specs), (), specs,
                          lambda: self.master.on_arrival(specs))

    def on_completion(self, app_id):
        return self._pass(1, (app_id,), (),
                          lambda: self.master.on_completion(app_id))

    def on_resize(self, app_id, n_min=None, n_max=None):
        return self._pass(1, (), (),
                          lambda: self.master.on_resize(app_id, n_min, n_max))

    def on_tick(self, t):
        return self.master.on_tick(t)

    def on_batch(self, completions, resizes, arrivals):
        k = len(completions) + len(resizes) + len(arrivals)
        return self._pass(k, completions, arrivals,
                          lambda: self.master.on_batch(completions, resizes,
                                                       arrivals))

    def containers_of(self, app_id):
        return self.master.containers_of(app_id)


def spy_backend(backend, rec: Recorder) -> None:
    """Time the backend's programs from the host (padding, dispatch,
    transfers and device time) and keep a seeded sample of `place_run`
    calls with their inputs and outputs. Patches the instance only."""
    orig_place_run = backend.place_run

    def place_run(x, free, d, inv_cap, items):
        if not rec.in_window:
            return orig_place_run(x, free, d, inv_cap, items)
        K = len(items)
        b, m = free.shape
        slot = rec.call_sample.offer() if K else None
        if slot is not None:
            idx = np.fromiter((i for i, _ in items), np.int64, K)
            uniq = np.unique(idx)
            keep = {"free": free.copy(), "inv_cap": inv_cap,
                    "d": d[idx].copy(), "app_of": idx,
                    "limits": np.fromiter((l for _, l in items), np.int64, K),
                    "bases": x[idx].sum(axis=1), "uniq": uniq,
                    "before": x[uniq].copy()}
        with rec.span("backend.place_run"):
            t0 = clock()
            out = orig_place_run(x, free, d, inv_cap, items)
            rec.backend_s += clock() - t0
        if K:
            rec.place_run_bytes += 8.0 * (2 * b * m + K * m + K * b)
        if slot is not None:
            delta = x[keep["uniq"]] - keep.pop("before")
            keep["delta"] = {int(keep["uniq"][r]): (np.flatnonzero(delta[r]),
                                                    delta[r][delta[r] != 0])
                             for r in range(len(keep["uniq"]))}
            keep["out"] = list(out)
            rec.call_sample.items[slot] = keep
        return out

    def timed(name):
        orig = getattr(backend, name)

        def call(*args):
            if not rec.in_window:
                return orig(*args)
            with rec.span("backend." + name):
                t0 = clock()
                out = orig(*args)
                rec.backend_s += clock() - t0
            return out
        return call

    backend.place_run = place_run
    backend.ladder_counts = timed("ladder_counts")
    backend.saturating_probe = timed("saturating_probe")


def build_program(cluster: dict, jobs: List[dict], guarantees: dict):
    """The deployment in the program's own types: a `DormMaster` on the
    configured engine, the runtime with its storm absorber, the jobs."""
    from repro.core import (AbsorberConfig, ApplicationSpec, ClusterRuntime,
                            ClusterSpec, DormMaster, OptimizerConfig,
                            ResourceVector, SlaveSpec, WorkloadApp)
    spec = ClusterSpec(
        resource_types=tuple(cluster["resources"]),
        slaves=tuple(SlaveSpec(sid, ResourceVector.of(*row))
                     for sid, row in zip(cluster["ids"], cluster["cap"])))
    cfg = OptimizerConfig(float(guarantees["theta1"]),
                          float(guarantees["theta2"]),
                          incremental=bool(guarantees["incremental"]),
                          backend=guarantees["engine"])
    master = DormMaster(spec, guarantees["optimizer"], cfg)
    workload = [WorkloadApp(
        spec=ApplicationSpec(
            app_id=j["id"], executor=j["executor"],
            demand=ResourceVector.of(*j["demand"]), weight=j["weight"],
            n_max=j["n_max"], n_min=j["n_min"], model=j["cls"],
            serial_work=j["work"], submit_time=j["submit"]),
        class_index=-1, base_duration_s=j["duration"]) for j in jobs]
    return master, workload, ClusterRuntime, AbsorberConfig


def warm_place_run(backend, cluster: dict, max_schedule: int) -> int:
    """Compile (or load) every `place_run` schedule-length bucket up to
    `max_schedule` at this cluster's size, through the backend's own entry
    point with a schedule that grants nothing. -> buckets warmed."""
    cap = np.asarray(cluster["cap"], np.float64)
    b, m = cap.shape
    inv_cap = 1.0 / np.maximum(cap, 1e-9)
    d = np.ones((1, m), np.float64)
    k, n = 1, 0
    while k <= max_schedule:
        x = np.zeros((1, b), np.int64)
        backend.place_run(x, cap.copy(), d, inv_cap, [(0, 0)] * k)
        k *= 2
        n += 1
    return n


def run_window(cell: dict, seed: int, seconds: float, trace_dir: Optional[str],
               t_start: float, log: Callable[[str], None],
               hooks: Optional[Callable] = None) -> dict:
    """Set-up, warm-up and the measured window of one run. -> everything
    the check, the metric readers and the result line need."""
    config, traffic = cell["config"], cell["traffic"]
    g = config["guarantees"]
    cluster = traffic_gen.build_cluster(config, seed)
    jobs = traffic_gen.build_jobs(config, traffic, seed)
    master, workload, ClusterRuntime, AbsorberConfig = build_program(
        cluster, jobs, g)
    backend = master.optimizer.backend
    rec = Recorder(seconds, float(traffic["warmup_sim_s"]),
                   trace_dir is not None, seed)
    proxy = PolicyProxy(master, rec)
    if hooks is not None:
        hooks(master, proxy, rec)
    spy_backend(backend, rec)
    warmed = warm_place_run(backend, cluster, int(traffic["warm_schedule"]))
    runtime = ClusterRuntime(proxy, adjustment_cost_s=float(
        g["adjustment_cost_s"]), horizon_s=1e15,
        absorber=AbsorberConfig(window_s=float(g["absorber_window_s"])))
    from repro.core import Reallocated, Storm, Arrival, Completion

    def on_result(ev) -> None:
        e = ev.event
        if isinstance(e, Storm):
            comp, arr = e.completions, tuple(s.app_id for s in e.arrivals)
        elif isinstance(e, Arrival):
            comp, arr = (), tuple(s.app_id for s in e.specs)
        elif isinstance(e, Completion):
            comp, arr = (e.app_id,), ()
        else:
            comp, arr = (), ()
        res = ev.result
        rec.tape.append({"t": float(ev.t), "completions": tuple(comp),
                         "arrivals": arr,
                         "changed": res.changed_counts or {},
                         "adjusted": tuple(res.adjusted_app_ids)})
        rec.sim_t = float(ev.t)

    runtime.bus.subscribe(Reallocated, on_result)
    counter = CompileCounter()
    state: Dict[str, object] = {}

    def phases() -> Dict[str, float]:
        # Raw timers: `phase_breakdown()` subtracts every compile of the
        # process (the warm-up's ran outside any solve) and clamps at 0.
        return dict(master.phase_s, drf_refill=master.optimizer.refill_s)

    def open_window() -> None:
        state["phases0"] = phases()
        state["drf0"] = (master.optimizer.drf.full_refills,
                         master.optimizer.drf.fast_hits)
        if trace_dir is not None:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans only, no tracer
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            state["window_span"] = jax.profiler.TraceAnnotation("bench.window")
            state["window_span"].__enter__()
        counter.armed = True
        # Set-up ends at the first timed pass, which starts right after.
        state["setup_s"] = clock() - t_start

    def close_window() -> None:
        counter.armed = False
        state["phases1"] = phases()
        state["drf1"] = (master.optimizer.drf.full_refills,
                         master.optimizer.drf.fast_hits)
        if trace_dir is not None:
            import jax
            state["window_span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    rec.on_open.append(open_window)
    rec.on_close.append(close_window)
    log(f"deployment: {len(cluster['ids'])} slaves, {len(jobs)} jobs in the "
        f"trace, {warmed} place_run buckets warmed, warm-up to "
        f"{rec.warmup_sim_s:.0f} s of simulated time")
    try:
        runtime.run(workload)
    except WindowClosed:
        pass
    finally:
        counter.close()
    if rec.phase != "closed":
        raise RuntimeError(
            "the trace ended before the window closed: raise the traffic's "
            f"n_apps (window passes so far: {len(rec.pass_wall)})")
    p0, p1 = state["phases0"], state["phases1"]
    return {
        "rec": rec, "cluster": cluster, "jobs": jobs, "master": master,
        "backend": backend, "setup_s": state["setup_s"],
        "window_s": rec.t_close - rec.t_open,
        "phase_delta": dict(
            {k: p1[k] - p0[k] for k in p1},
            solve=(p1["solve"] - p0["solve"])
            - (p1["drf_refill"] - p0["drf_refill"])),
        "full_refills": state["drf1"][0] - state["drf0"][0],
        "fast_hits": state["drf1"][1] - state["drf0"][1],
        "compiles": counter.compiles, "traces": counter.traces,
        "absorber_stats": runtime.absorber_stats,
    }
