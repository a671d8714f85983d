"""The comparison that decides `correct`.

Three layers, on the events the run decided, each against the plain
reference (`reference.py`):

* `timeline_wrong`: passes (warm-up and window) whose simulated time or
  whose completions and arrivals differ from what the reference event loop
  predicts, given the program's decisions;
* `passes_wrong`: passes of a seeded sample of the window (with its largest
  flood) whose allocation, adjusted, started or pending apps differ from
  the reference pass run from the same starting state;
* `backend_wrong`: a seeded sample of the window's `place_run` calls whose
  grants differ from the reference placement on the same inputs;
* `outcome_gap`: the widest gap of Eq-1 utilization or Eq-2 fairness loss
  between the sampled passes and the reference, over max(1, |reference|).

`wrong_decisions`, the sum of the three counts, is compared with its
limit (0); each count is printed beside it. The control is the same
reference one precision down (float32) put in the program's place;
`numbers(..., control=True)` reads it. Its placements come out the same
(integral demands keep every best-fit order in float32), so only the
timeline and the outcome gap separate it: the three counts are one
number for that reason.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import reference as ref

F64, F32 = np.float64, np.float32

# Seeded sample sizes: window passes re-run by the reference, and
# `place_run` calls re-placed by it.
PASS_SAMPLES = 200
CALL_SAMPLES = 1000


def _time_close(a: float, b: float) -> bool:
    return abs(a - b) <= 1e-9 * max(1.0, abs(b))


def timeline_wrong(run: dict, g: dict, control: bool = False) -> int:
    tape = run["rec"].tape
    want = ref.event_loop(run["jobs"], tape, float(g["absorber_window_s"]),
                          float(g["adjustment_cost_s"]), F64)
    if control:
        tape = ref.event_loop(run["jobs"], tape, float(g["absorber_window_s"]),
                              float(g["adjustment_cost_s"]), F32)
    bad = abs(len(want) - len(tape))
    for got, exp in zip(tape, want):
        if (set(got["completions"]) != set(exp["completions"])
                or set(got["arrivals"]) != set(exp["arrivals"])
                or not _time_close(got["t"], exp["t"])):
            bad += 1
    return bad


def _state_before(tape: List[dict], upto: int) -> Tuple[List[str], List[str]]:
    """Admitted apps (admission order) and waiting apps before pass `upto`,
    from the passes' events and the apps each pass started."""
    admitted: Dict[str, None] = {}
    started = set()
    for p in tape[:upto]:
        for a in p["completions"]:
            admitted.pop(a, None)
        for a in p["arrivals"]:
            admitted[a] = None
        adj = set(p["adjusted"])
        started.update(a for a in p["changed"] if a not in adj)
    order = list(admitted)
    return order, [a for a in order if a not in started]


def pass_numbers(run: dict, g: dict, control: bool = False
                 ) -> Tuple[int, float, int]:
    """-> (passes wrong, widest outcome gap, passes compared)."""
    rec = run["rec"]
    jobs = {j["id"]: j for j in run["jobs"]}
    cap = np.asarray(run["cluster"]["cap"], np.float64)
    snaps = [s for s in rec.pass_sample.items if s is not None]
    if rec.largest is not None and all(s is not rec.largest for s in snaps):
        snaps.append(rec.largest)
    wrong, gap = 0, 0.0
    th1, th2 = float(g["theta1"]), float(g["theta2"])
    for s in snaps:
        order, pending = _state_before(rec.tape, s["tape"])
        prev = s["pre"][0]
        prev_ids = prev.app_ids if prev is not None else ()
        prev_x = prev.x if prev is not None else None
        exp = ref.dorm_pass(jobs, order, pending, prev_ids, prev_x,
                            s["completions"], s["arrivals"], cap, th1, th2,
                            F64)
        if control:
            got = ref.dorm_pass(jobs, order, pending, prev_ids, prev_x,
                                s["completions"], s["arrivals"], cap, th1,
                                th2, F32)
            same_state = True
        else:
            r = s["res"]
            got = {"ids": tuple(r.allocation.app_ids), "x": r.allocation.x,
                   "adjusted": tuple(r.adjusted_app_ids),
                   "started": tuple(r.started_app_ids),
                   "pending": tuple(r.pending_app_ids),
                   "utilization": float(r.utilization),
                   "fairness_loss": float(r.fairness_loss)}
            same_state = (list(s["pre"][1]) == order
                          and list(s["pre"][2]) == pending)
        ok = (same_state and got["ids"] == exp["ids"]
              and got["x"].shape == exp["x"].shape
              and np.array_equal(got["x"], exp["x"])
              and got["adjusted"] == exp["adjusted"]
              and got["started"] == exp["started"]
              and got["pending"] == exp["pending"])
        wrong += not ok
        for key in ("utilization", "fairness_loss"):
            gap = max(gap, abs(got[key] - exp[key]) / max(1.0, abs(exp[key])))
    return wrong, gap, len(snaps)


def backend_numbers(run: dict, control: bool = False) -> Tuple[int, int]:
    """-> (place_run calls wrong, calls compared)."""
    calls = [c for c in run["rec"].call_sample.items if c is not None]
    wrong = 0
    for c in calls:
        exp = ref.place_run(c["free"], c["inv_cap"], c["d"], c["limits"],
                            c["bases"], c["app_of"], F64)
        if control:
            got = ref.place_run(c["free"], c["inv_cap"], c["d"], c["limits"],
                                c["bases"], c["app_of"], F32)
            ok = np.array_equal(got, exp)
        else:
            ok = list(c["out"]) == [int(v) for v in exp.sum(axis=1)]
            for a in c["uniq"].tolist():
                row = exp[c["app_of"] == a].sum(axis=0)
                js, vals = c["delta"][a]
                ok = ok and np.array_equal(np.flatnonzero(row), js) \
                    and np.array_equal(row[js], vals)
        wrong += not ok
    return wrong, len(calls)


def numbers(run: dict, g: dict, control: bool = False) -> Dict[str, float]:
    """Every compared number of one run (or of the control on its inputs):
    `wrong_decisions` (the three layers' counts together) and
    `outcome_gap`, with each layer's count and the sample sizes beside."""
    p_wrong, gap, n_pass = pass_numbers(run, g, control)
    b_wrong, n_calls = backend_numbers(run, control)
    t_wrong = timeline_wrong(run, g, control)
    return {"wrong_decisions": t_wrong + p_wrong + b_wrong,
            "outcome_gap": gap, "timeline_wrong": t_wrong,
            "passes_wrong": p_wrong, "backend_wrong": b_wrong,
            "passes_compared": n_pass, "calls_compared": n_calls}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """`correct`: every limited number at or under its limit, and something
    was compared on each layer."""
    ok = all(values[k] <= lim for k, lim in limits.items())
    return ok and values["passes_compared"] > 0 and values["calls_compared"] > 0
