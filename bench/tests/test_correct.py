"""`correct` on a CPU run of a tiny cell: true for the program as it is,
false for the float32 control and for each fault the timed path can have
(a pass that leaves the state unchanged, an answer altered where it is
produced, half of a flood left out). Chips are not looked for: the run
goes through `report.run` with jax's CPU device, the Pallas kernel off."""
import time

import jax
import numpy as np
import pytest

from bench.harness import cells, check, driver, report
from bench.tests.tiny import make_root


def _state_unchanged(master, proxy, rec):
    orig = master.optimizer.backend.place_run

    def place_run(x, free, d, inv_cap, items):
        if rec.in_window:
            return [0] * len(items)          # grants nothing, mutates nothing
        return orig(x, free, d, inv_cap, items)
    master.optimizer.backend.place_run = place_run


def _answer_altered(master, proxy, rec):
    orig = master.optimizer.backend.place_run

    def place_run(x, free, d, inv_cap, items):
        out = orig(x, free, d, inv_cap, items)
        if rec.in_window:
            for (i, _), got in zip(items, out):
                if got:
                    j = int(np.flatnonzero(x[i])[0])
                    to = int(np.argmax((free >= d[i]).all(axis=1)))
                    if to != j and (free[to] >= d[i]).all():
                        x[i, j] -= 1
                        x[i, to] += 1
                        free[j] += d[i]
                        free[to] -= d[i]
                        break
        return out
    master.optimizer.backend.place_run = place_run


def _half_batch(master, proxy, rec):
    orig = master.on_batch

    def on_batch(completions, resizes, arrivals, chaos=()):
        if rec.in_window and len(arrivals) >= 2:
            arrivals = arrivals[:len(arrivals) // 2]
        return orig(completions, resizes, arrivals)
    master.on_batch = on_batch


FAULTS = {"state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered, "half_batch": _half_batch}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return cells.find_cell("tiny.mix",
                           root=make_root(tmp_path_factory.mktemp("tiny")))


def _run(cell, hooks=None, seed=2**31 + 99):
    return report.run(cell, seed, 1.0, None, time.perf_counter(),
                      jax.devices(), lambda s: None, hooks=hooks)


def test_program_is_correct_and_control_is_not(tiny):
    line, checks = _run(tiny)
    assert line["correct"], checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"events_per_s", "decision_p50_ms",
                                    "decision_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    r = driver.run_window(tiny, 5, 1.0, None, time.perf_counter(),
                          lambda s: None)
    g = tiny["config"]["guarantees"]
    limits = cells.load_json("limits.json")
    assert check.judge(check.numbers(r, g), limits)
    control = check.numbers(r, g, control=True)
    assert not check.judge(control, limits), control


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_timed_path_is_not_correct(tiny, fault):
    line, checks = _run(tiny, FAULTS[fault])
    assert not line["correct"], (fault, checks)
