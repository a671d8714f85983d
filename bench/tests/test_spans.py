"""The program's spans in a trace of the window (`harness/spans.py`): idle
gaps split over what the host was doing, the recorded chip trace, and the
four readers of program spans on a traced CPU run of a tiny cell."""
import gzip
import os
import shutil
import time
from types import SimpleNamespace as NS

import jax
import pytest

from bench.harness import cells, report, spans, trace
from bench.tests.tiny import make_root

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = ("runtime.scan_ms_per_pass", "optimizer.self_ms_per_pass",
           "backend.prep_ms_per_pass", "backend.wait_ms_per_pass")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def straddling():
    """One idle gap [1300, 1700) runs from the end of a pass's backend call
    through the pass, the event loop ([1400, 1500) under no span, with a
    scan inside) and into the next pass."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 1000, 1000),
        ev("runtime.pass", 1000, 400), ev("master.solve", 1050, 300),
        ev("backend.place_run.wait", 1100, 200),
        ev("runtime.scan", 1420, 30),
        ev("runtime.pass", 1500, 500), ev("optimizer.place", 1600, 300),
        ev("python_internal", 1450, 100),          # not a program span
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion.1", 1100, 200),
                                   ev("fusion.2", 1700, 200)]),
        NS(name="XLA Modules", events=[])])
    return [host, dev]


def test_idle_gap_is_split_over_pass_loop_and_next_pass():
    out = spans.reduce_planes(straddling())
    idle = out["idle_s"]
    # [1000,1050) pass, [1050,1100) solve; [1300,1350) solve, [1350,1400)
    # pass, [1400,1420) loop, [1420,1450) scan, [1450,1500) loop, [1500,
    # 1600) pass, [1600,1700) place; [1900,2000) pass.
    assert idle == pytest.approx({
        "runtime.pass": 300e-9, "master.solve": 100e-9,
        "runtime.event_loop": 70e-9, "runtime.scan": 30e-9,
        "optimizer.place": 100e-9})
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert out["busy_s"] == pytest.approx(400e-9)
    # Self time: the innermost span at each instant.
    assert out["self_s"]["backend.place_run.wait"] == pytest.approx(200e-9)
    assert out["self_s"]["master.solve"] == pytest.approx(100e-9)
    assert out["total_s"]["runtime.pass"] == pytest.approx(900e-9)
    assert out["count"]["runtime.pass"] == 2
    # `trace.reduce_planes` sees only the benchmark's spans and charges
    # each gap whole to what lies at its midpoint: here, all to the loop.
    old = dict(trace.reduce_planes(straddling())["breakdown"]["idle_gaps"])
    assert old == pytest.approx({"runtime.event_loop": 600e-9})


def test_recorded_chip_trace_idle_split_sums_to_idle(tmp_path):
    """The committed TPU v5e trace (benchmark spans only): the same window
    and busy time as `trace.reduce_file`, every idle nanosecond labelled."""
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "philly-light.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    old = trace.reduce_file(str(path))
    out = spans.reduce_file(str(path))
    assert out["window_s"] == pytest.approx(old["window_s"])
    assert out["busy_s"] == pytest.approx(old["busy_s"])
    idle = out["idle_s"]
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-9)
    assert set(idle) <= {"dorm.pass", "backend.place_run",
                         "runtime.event_loop"}
    assert sum(out["self_s"].values()) == pytest.approx(out["window_s"])
    # A trace without the program's spans (the parent's) reads nothing.
    ctx = {"run": {"rec": NS(pass_wall=[0.1] * 10)}, "trace": old,
           "trace_path": str(path)}
    assert all(cells.metric_reader(name)(ctx) is None for name in READERS)


def test_readers_return_none_without_a_trace():
    ctx = {"run": {"rec": NS(pass_wall=[0.1])}, "trace": None}
    assert all(cells.metric_reader(name)(ctx) is None for name in READERS)


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_traced_run_without_its_trace_file_raises(name):
    """A traced run whose trace file no caller holds is a broken lookup,
    not a run without spans: the reader fails the run."""
    ctx = {"run": {"rec": NS(pass_wall=[0.1])}, "trace": {"window_s": 1.0}}
    with pytest.raises(LookupError):
        cells.metric_reader(name)(ctx)


def test_traced_cpu_run_reads_the_program_spans(tmp_path, monkeypatch):
    cell = cells.find_cell("tiny.mix", root=make_root(tmp_path / "root"))
    monkeypatch.setattr(trace, "peaks_for",
                        lambda kind, path=None: {"hbm_bytes_per_s": 819e9})
    line, _ = report.run(cell, 2**31 + 7, 1.0, str(tmp_path / "trace"),
                         time.perf_counter(), jax.devices(), lambda s: None)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(got[name] > 0.0 for name in READERS), got
    inside = got["backend.prep_ms_per_pass"] + got["backend.wait_ms_per_pass"]
    assert inside == pytest.approx(got["backend.host_ms_per_pass"], rel=0.05)
