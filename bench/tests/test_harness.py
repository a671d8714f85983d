"""The harness on the CPU: cells found from added files alone, the
refusal to run without a TPU, the peak table, and the traffic generator's
promise that a seed only reorders the work."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import cells, traffic
from bench.harness.trace import peaks_for
from bench.tests.tiny import ROOT, make_root


def test_cell_found_from_added_files_only(tmp_path):
    root = make_root(tmp_path)
    os.makedirs(os.path.join(root, "bench", "metrics"))
    with open(os.path.join(root, "bench", "metrics", "x.count.py"), "w") as fh:
        fh.write("def read(ctx):\n    return ctx['n'] * 2\n")
    cell = cells.find_cell("tiny.mix", root=root)
    assert cell["config"]["cluster"]["slaves"] == 48
    assert cell["traffic"]["n_apps"] == 3000
    assert [m["name"] for m in cell["end_to_end"]] == [
        "events_per_s", "decision_p50_ms", "decision_p95_ms", "setup_s"]
    assert cells.metric_reader("x.count", root=root)({"n": 21}) == 42
    with pytest.raises(KeyError):
        cells.find_cell("tiny.other", root=root)


def test_every_cell_of_the_benchmark_resolves():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.find_cell(w["name"])
        assert cell["per_layer"] and cell["end_to_end"]
        for m in cell["per_layer"]:
            assert callable(cells.metric_reader(m["name"]))


def test_run_without_tpu_fails_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("bench", "run.py"), "--workload",
         "table2-5000.steady", "--seed", "3000000017", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    assert "TPU" in out.stderr


def test_device_missing_from_peak_table_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("cell", ["table2-5000.steady", "table2-1000.steady"])
def test_seed_reorders_the_same_work(cell):
    c = cells.find_cell(cell)
    cfg, mix = c["config"], dict(c["traffic"], n_apps=2000)
    a = traffic.build_jobs(cfg, mix, 2**31 + 12345)
    b = traffic.build_jobs(cfg, mix, 7)
    assert traffic.build_jobs(cfg, mix, 7) == b

    def multiset(jobs, key):
        return sorted(j[key] for j in jobs)
    # Same classes and the same multiset of durations, in another order.
    assert multiset(a, "cls") == multiset(b, "cls")
    da, db = multiset(a, "duration"), multiset(b, "duration")
    np.testing.assert_allclose(da, db, rtol=1e-12)
    assert [j["cls"] for j in a] != [j["cls"] for j in b]
    ca = traffic.build_cluster(cfg, 2**31 + 12345)
    cb = traffic.build_cluster(cfg, 7)
    assert sorted(map(tuple, ca["cap"])) == sorted(map(tuple, cb["cap"]))


@pytest.mark.parametrize("cell", ["table2-5000.steady", "table2-1000.steady"])
def test_offered_load_sets_the_arrival_rate(cell):
    c = cells.find_cell(cell)
    cfg, mix = c["config"], c["traffic"]
    kind = cfg["jobs"]["kinds"]["train"]
    shares = np.asarray([k["share"] for k in kind["classes"]], np.float64)
    shares /= shares.sum()
    # CPU-seconds of work a job brings: run time x static count x CPUs.
    cpu_work = traffic.mean_duration(kind["duration_s"]) * sum(
        s * k["anchor"] * k["demand"][0] for s, k in zip(shares, kind["classes"]))
    cpus = 12 * cfg["cluster"]["slaves"]
    rate = traffic.offered_jobs_per_s(cfg, mix)
    assert rate * cpu_work / cpus == pytest.approx(
        mix["arrivals"]["offered_load"], rel=1e-9)
    jobs = traffic.build_jobs(cfg, dict(mix, n_apps=4000), 5)
    fresh = [j["submit"] for j in jobs[traffic.resident_jobs(cfg, mix):]]
    gaps = np.diff(fresh)
    assert gaps.mean() == pytest.approx(1.0 / rate, rel=0.02)
