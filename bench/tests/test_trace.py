"""The reduction from a profiler trace to device numbers."""
import glob
import os
from types import SimpleNamespace as NS

import pytest

from bench.harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 1000, 1000),
        ev("dorm.pass", 1000, 400), ev("backend.place_run", 1100, 250),
        ev("dorm.pass", 1500, 400), ev("backend.place_run", 1600, 200),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit_place_run(7)", 1150, 150), ev("jit_place_run(7)", 1650, 100),
            ev("jit_other", 500, 100)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 1150, 100), ev("tpu_custom_call", 1200, 100),
            ev("fusion.1", 1650, 100), ev("fusion.2", 1950, 200),
            ev("early", 0, 100)]),
    ])
    return [host, dev]


def test_synthetic_trace_reduces_to_its_hand_counts():
    out = trace.reduce_planes(synthetic())
    assert out["window_s"] == pytest.approx(1000e-9)
    # Busy: [1150, 1300) + [1650, 1750) + [1950, 2000) clipped to the window.
    assert out["busy_s"] == pytest.approx(300e-9)
    assert out["programs"]["place_run"]["count"] == 2
    assert out["programs"]["place_run"]["device_s"] == pytest.approx(250e-9)
    assert "other" not in out["programs"]       # outside the window
    gaps = dict(out["breakdown"]["idle_gaps"])
    # [1000,1150) mid 1075: in dorm.pass only; [1300,1650) mid 1475: no
    # span; [1750,1950) mid 1850: in dorm.pass only.
    assert gaps["dorm.pass"] == pytest.approx(350e-9)
    assert gaps["runtime.event_loop"] == pytest.approx(350e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-9)
    assert ops["fusion.2"] == pytest.approx(50e-9)


def test_trace_without_a_window_is_an_error():
    planes = synthetic()
    planes[0].lines[0].events = planes[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace.reduce_planes(planes)


def test_program_names():
    assert trace.program_name("jit_place_run(12)") == "place_run"
    assert trace.program_name("jit_ladder") == "ladder"
    assert trace.program_name("place_run") == "place_run"


def test_op_names_keep_the_hlo_name_and_kernel_target():
    text = ('%closed_call.17 = s32[1,8192]{1,0} custom-call(f32[3,8192] %a), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace.op_name(text) == "%closed_call.17 (tpu_custom_call)"
    assert trace.op_name("%while.21 = (u32[]) while((u32[]) %t)") == "%while.21"
    assert trace.op_name("fusion.1") == "fusion.1"



def test_recorded_chip_trace_matches_a_direct_count(tmp_path):
    """A half-second window of `philly-gpu.light` traced on a TPU v5e: the
    reduction agrees with busy time and program time counted directly
    from the raw events."""
    import gzip
    import shutil

    from jax.profiler import ProfileData
    path = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(DATA, "philly-light.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    out = trace.reduce_file(str(path))
    planes = list(ProfileData.from_file(str(path)).planes)

    w0 = w1 = None
    for p in planes:
        for line in p.lines:
            for e in line.events:
                if e.name == "bench.window":
                    w0, w1 = e.start_ns, e.start_ns + e.duration_ns
    # Busy time by a sweep over op starts and ends (a count of running ops).
    edges = []
    place_run = 0.0
    for p in planes:
        if not p.name.startswith("/device:"):
            continue
        for line in p.lines:
            for e in line.events:
                s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
                if t <= s:
                    continue
                if line.name == "XLA Ops":
                    edges += [(s, 1), (t, -1)]
                elif line.name == "XLA Modules" and "place_run" in e.name:
                    place_run += (t - s) * 1e-9
    busy, running, last = 0.0, 0, None
    for x, step in sorted(edges, key=lambda v: (v[0], -v[1])):
        if running > 0:
            busy += x - last
        running += step
        last = x
    assert out["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert out["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["programs"]["place_run"]["device_s"] == pytest.approx(place_run)
    assert out["programs"]["place_run"]["count"] > 0
