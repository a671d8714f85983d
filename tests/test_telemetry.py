"""The program's spans and compile counter (`core.telemetry`): wall time
of nested spans, jax's compile events per program name, the master's
phase timers read from its spans, and the runtime's, master's,
optimizer's and backend's spans in a CPU profiler trace, nested."""
import glob
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import (AbsorberConfig, ApplicationSpec, ClusterRuntime,
                        ClusterSpec, DormMaster, OptimizerConfig,
                        RecordingProtocol, ResourceVector, WorkloadApp)
from repro.core.telemetry import Spans, compile_counter


def _cluster(n=4, cap=(8, 0, 32)):
    return ClusterSpec.homogeneous(n, ResourceVector.of(*cap))


def _workload(n=6):
    specs = [ApplicationSpec(f"app{i}", "x", ResourceVector.of(2, 0, 8), 1,
                             4, 1, serial_work=3600.0 * (1 + i % 3),
                             submit_time=600.0 * (i // 2))
             for i in range(n)]
    return [WorkloadApp(spec=s, class_index=0, base_duration_s=s.serial_work)
            for s in specs]


def _jax_master():
    return DormMaster(_cluster(), "greedy",
                      OptimizerConfig(0.2, 0.2, backend="jax"),
                      protocol=RecordingProtocol())


def test_spans_add_up_wall_time_per_name():
    outer, inner = Spans(), Spans()
    with outer.span("runtime.pass", pass_id=1, k=2):
        time.sleep(0.01)
        with inner.span("master.solve"):       # another registry's span
            time.sleep(0.02)
            with inner.span("optimizer.place"):
                time.sleep(0.01)
    solve_in_pass = inner.total_s["master.solve"]
    with inner.span("master.solve"):
        pass
    t_out, t_in = outer.total_s, inner.total_s
    # Each span books its whole duration, children included, under its
    # own name in its own registry; a second span of a name adds to it.
    assert set(t_out) == {"runtime.pass"}
    assert set(t_in) == {"master.solve", "optimizer.place"}
    assert t_out["runtime.pass"] >= solve_in_pass + 0.01
    assert solve_in_pass >= t_in["optimizer.place"] + 0.02
    assert t_in["optimizer.place"] >= 0.01
    assert t_in["master.solve"] >= solve_in_pass


def test_compile_counter_counts_each_program_once():
    compiles = compile_counter()

    @jax.jit
    def dorm_test_double(x):
        return 2 * x

    @jax.jit
    def dorm_test_square(x):
        return x * x

    before = dict(compiles.count)
    dorm_test_double(jnp.ones(3)).block_until_ready()
    dorm_test_square(jnp.ones(3)).block_until_ready()
    after = dict(compiles.count)
    assert after["dorm_test_double"] == before.get("dorm_test_double", 0) + 1
    assert after["dorm_test_square"] == before.get("dorm_test_square", 0) + 1
    assert compiles.seconds["dorm_test_double"] > 0.0
    # The same shapes again: served from the jit cache, nothing compiled.
    dorm_test_double(jnp.zeros(3)).block_until_ready()
    dorm_test_square(jnp.zeros(3)).block_until_ready()
    assert compiles.count == after
    assert compile_counter() is compiles


def test_backend_compiles_are_booked_under_their_own_names():
    """A jit elsewhere in the process named like a backend program is not
    the backend's: the backend books its dispatches as `dorm.<program>`."""
    compiles = compile_counter()
    m = _jax_master()
    ClusterRuntime(m, absorber=AbsorberConfig(window_s=60.0)).run(
        _workload())
    be = m.optimizer.backend
    mine = be.compile_s

    @jax.jit
    def place_run(x):
        return x + 1

    before = compiles.count.get("place_run", 0)
    place_run(jnp.ones(5)).block_until_ready()
    assert compiles.count["place_run"] == before + 1
    assert compiles.count["dorm.place_run"] >= 1
    assert be.compile_s == mine


def test_master_phase_timers_read_its_spans():
    m = _jax_master()
    rt = ClusterRuntime(m, absorber=AbsorberConfig(window_s=60.0))
    rt.run(_workload())
    total = m.spans.total_s
    assert m.phase_s == {p: total.get("master." + p, 0.0)
                         for p in ("solve", "enforce", "metrics", "absorb")}
    assert m.optimizer.refill_s == total["optimizer.refill"]
    assert m.phase_s["solve"] >= total["optimizer.solve"] \
        >= m.optimizer.refill_s > 0.0
    phases = m.phase_breakdown()
    assert phases["solve"] == pytest.approx(
        m.phase_s["solve"] - m.optimizer.refill_s)
    # The runtime numbers its passes; its spans hold the master's.
    rs = rt.spans.total_s
    assert rt._pass_id == rt.absorber_stats["passes"] > 0
    assert set(rs) == {"runtime.scan", "runtime.collect", "runtime.pass",
                       "runtime.finish"}
    assert rs["runtime.pass"] >= m.phase_s["solve"]
    assert {"backend.place_run." + p
            for p in ("prep", "dispatch", "wait", "apply")} <= set(total)


def test_spans_nest_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData
    m = _jax_master()
    rt = ClusterRuntime(m, absorber=AbsorberConfig(window_s=60.0))
    with jax.profiler.trace(str(tmp_path)):
        rt.run(_workload())
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for p in ProfileData.from_file(path).planes
              for line in p.lines for e in line.events
              if e.name in ("runtime.pass", "master.solve",
                            "optimizer.place", "backend.place_run.wait")]
    passes = [e for e in events if e.name == "runtime.pass"]
    assert [dict(e.stats)["pass_id"] for e in passes] == \
        list(range(1, len(passes) + 1))
    assert sum(dict(e.stats)["k"] for e in passes) == 12

    def inside(e, outer):
        return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns

    chain = ("runtime.pass", "master.solve", "optimizer.place",
             "backend.place_run.wait")
    for child_name, parent_name in zip(chain[1:], chain):
        children = [e for e in events if e.name == child_name]
        assert children
        for e in children:
            assert any(inside(e, p) for p in events
                       if p.name == parent_name), (child_name, parent_name)
