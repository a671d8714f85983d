"""Goodput curves, work anchoring and goodput-aware allocation (PR 9).

Covers: curve invariants (monotone, concave-capped, normalized), the
roofline-derived registry curves, linear bit-exactness (attaching the
explicit linear curve changes NOTHING vs no curve), the work-anchor
regression (replay anchors at the recorded request, synthetic traces at
the elasticity midpoint -- one shared definition), `speedup_ratios`'
explicit skip accounting, knee-capped greedy allocation, colgen's
goodput-weighted objective, numpy/jax parity of the goodput-aware greedy
path, and the master's cluster-goodput metric.
"""
import numpy as np
import pytest

from repro.core import (ApplicationSpec, ClusterSimulator, ClusterSpec,
                        DormMaster, GoodputCurve, OptimizerConfig,
                        RecordingProtocol, ReferenceClusterSimulator,
                        ResourceVector, SimResult, TraceConfig, WorkloadApp,
                        amdahl_curve, anchored_serial_work,
                        curve_for_model, derive_curve, generate_trace,
                        heterogeneous_cluster, make_optimizer, paper_testbed,
                        speedup_ratios, work_anchor)
from repro.core.replay import ReplayConfig, _mk_app
from repro.configs.registry import ARCH_IDS


def app(i, cpus=2, gpus=0, ram=8, w=1, nmax=8, nmin=1, curve=None):
    return ApplicationSpec(f"app{i}", "MxNet",
                           ResourceVector.of(cpus, gpus, ram), w, nmax, nmin,
                           goodput=curve)


# --------------------------------------------------------------- invariants

def _assert_curve_invariants(c: GoodputCurve):
    tab = np.asarray(c.table)
    assert tab[0] == pytest.approx(1.0)
    marg = np.diff(tab, prepend=0.0)
    assert (marg >= -1e-12).all()                      # monotone
    assert (np.diff(marg) <= 1e-12).all()              # concave cap


def test_from_samples_enforces_invariants_on_noisy_data():
    c = GoodputCurve.from_samples([2.0, 3.9, 3.5, 8.0, 8.1])
    _assert_curve_invariants(c)
    # the N=4 spike (8.0/2.0 = 4x) must not beat concavity: marginal at 4
    # is capped by the (already capped) marginal at 3
    assert c.at(4) - c.at(3) <= c.at(3) - c.at(2) + 1e-12


def test_registry_curves_derive_and_hold_invariants():
    for arch in ARCH_IDS:
        _assert_curve_invariants(derive_curve(arch, 16))
    # MoE models saturate earlier than dense: total params drive the
    # all-reduce while only active params drive compute
    assert derive_curve("olmoe-1b-7b", 16).knee(16) < \
        derive_curve("gemma2-9b", 16).knee(16)


def test_amdahl_curve_saturates():
    c = amdahl_curve(64, alpha=0.1)
    _assert_curve_invariants(c)
    assert c.at(64) < 11.0            # 1/alpha = 10 asymptote


def test_extrapolation_past_table_is_linear_at_last_marginal():
    c = GoodputCurve.from_samples([1.0, 1.8, 2.4])
    last = c.at(3) - c.at(2)
    assert c.at(5) == pytest.approx(c.at(3) + 2 * last)
    assert c.eval(np.array([0, 1, 3, 5])).tolist() == \
        pytest.approx([0.0, 1.0, c.at(3), c.at(5)])


def test_knee_is_marginal_half_life():
    c = amdahl_curve(32, alpha=0.08)
    k = c.knee(32)
    assert 1 <= k <= 32
    assert c.at(k) - c.at(k - 1) >= 0.5 * c.at(1) - 1e-9
    if k < 32:
        assert c.at(k + 1) - c.at(k) < 0.5 * c.at(1)
    assert c.knee(4) <= 4             # n_max limits the knee
    assert GoodputCurve.linear(8).knee(8) == 8


# ------------------------------------------------------------ work anchoring

def test_work_anchor_definitions():
    assert work_anchor(1, 32, requested=20) == 20      # replay: the request
    assert work_anchor(4, 12) == 8                     # synthetic: midpoint
    assert work_anchor(1, 1) == 1
    assert anchored_serial_work(100.0, 8) == 100.0 * 8  # bit-exact, no curve
    c = amdahl_curve(8, 0.1)
    assert anchored_serial_work(100.0, 8, c) == pytest.approx(100.0 * c.at(8))


def test_replay_anchors_at_requested_count_regression():
    # Regression for the anchor inconsistency: replay previously used
    # duration * n_max while generate_trace used the midpoint with no
    # shared definition. Replay's recorded duration IS at the request.
    w = _mk_app("j1", "tf", ResourceVector.of(2, 0, 8), 1,
                n_min=2, n_max=10, duration_s=500.0, submit_time=0.0)
    assert w.spec.serial_work == 500.0 * 10
    # curved replay: work = duration * goodput(request), strictly less
    # than linear for a saturating curve
    wc = _mk_app("j1", "tf", ResourceVector.of(2, 0, 8), 1,
                 n_min=2, n_max=10, duration_s=500.0, submit_time=0.0,
                 cfg=ReplayConfig(goodput_curves=True))
    assert wc.spec.goodput is not None
    assert wc.spec.serial_work == pytest.approx(
        500.0 * wc.spec.goodput.at(10))
    assert wc.spec.serial_work < w.spec.serial_work


def test_trace_curves_attach_to_train_jobs_only():
    wl = generate_trace(TraceConfig(n_apps=40, seed=3, goodput_curves=True))
    curved = [w for w in wl if w.spec.goodput is not None]
    assert curved, "expected some curved train jobs"
    for w in curved:
        assert w.spec.model in ARCH_IDS
        assert w.spec.service_s == 0.0                 # train-class only
        _assert_curve_invariants(w.spec.goodput)
        anchor = work_anchor(w.spec.n_min, w.spec.n_max)
        assert w.spec.serial_work == pytest.approx(
            w.base_duration_s * w.spec.goodput.at(anchor))
    # default stays uncurved (bit-exact seed workload)
    assert all(w.spec.goodput is None
               for w in generate_trace(TraceConfig(n_apps=20, seed=3)))


# ------------------------------------------------------- linear bit-exactness

def _run(wl, horizon=24 * 3600.0, cfg=None, ref=False):
    m = DormMaster(paper_testbed(), "greedy",
                   cfg or OptimizerConfig(0.2, 0.2),
                   protocol=RecordingProtocol())
    sim_cls = ReferenceClusterSimulator if ref else ClusterSimulator
    return sim_cls(m, wl, adjustment_cost_s=60.0, horizon_s=horizon).run()


def _timeline(res: SimResult):
    return ([(s.t, s.utilization, s.fairness_loss, s.running, s.pending)
             for s in res.samples],
            {a: (rt.started_at, rt.finished_at)
             for a, rt in res.completions.items()})


def test_linear_curve_is_bit_exact_with_no_curve():
    wl = generate_trace(TraceConfig(n_apps=30, seed=7))
    wl_lin = [WorkloadApp(
        spec=__import__("dataclasses").replace(
            w.spec, goodput=GoodputCurve.linear(w.spec.n_max)),
        class_index=w.class_index, base_duration_s=w.base_duration_s,
        load=w.load) for w in wl]
    assert _timeline(_run(wl)) == _timeline(_run(wl_lin))


def test_runtime_matches_reference_on_curved_workload():
    wl = generate_trace(TraceConfig(n_apps=25, seed=11, goodput_curves=True,
                                    serving_fraction=0.0))
    assert _timeline(_run(wl)) == _timeline(_run(wl, ref=True))


def test_curved_jobs_progress_by_goodput_not_count():
    c = GoodputCurve.from_samples([1.0, 1.5, 1.75, 1.875])
    cluster = ClusterSpec.homogeneous(4, ResourceVector.of(8, 0, 32))
    spec = ApplicationSpec("a", "x", ResourceVector.of(2, 0, 8), 1, 4, 4,
                           serial_work=anchored_serial_work(1000.0, 4, c),
                           goodput=c)
    m = DormMaster(cluster, "greedy", OptimizerConfig(0.5, 0.5),
                   protocol=RecordingProtocol())
    res = ClusterSimulator(m, [WorkloadApp(spec=spec, class_index=0,
                                           base_duration_s=1000.0)],
                           adjustment_cost_s=0.0, horizon_s=1e6).run()
    rt = res.completions["a"]
    # pinned at N=4: finishes in exactly the anchored duration, NOT the
    # linear serial_work/4
    assert rt.finished_at - rt.started_at == pytest.approx(1000.0)


# ------------------------------------------------------------- speedup_ratios

def _result_with(durations, horizon=1000.0):
    runtimes = {}
    for a, (t0, t1) in durations.items():
        rt = AppRuntimeStub(t0, t1)
        runtimes[a] = rt
    return SimResult(samples=[], completions=runtimes,
                     total_adjustments=0, horizon_s=horizon)


class AppRuntimeStub:
    def __init__(self, t0, t1):
        self.submitted_at = t0
        self.started_at = t0
        self.finished_at = t1


def test_speedup_ratios_reports_skips_explicitly():
    dorm = _result_with({"a": (0.0, 10.0), "b": (0.0, 20.0)})
    base = _result_with({"a": (0.0, 30.0), "c": (0.0, 40.0)})
    skipped = {}
    sp = speedup_ratios(dorm, base, skipped=skipped)
    assert sp == {"a": pytest.approx(3.0)}
    assert skipped == {"b": "dorm-only", "c": "baseline-only"}


def test_speedup_ratios_raises_on_zero_duration_dorm_app():
    dorm = _result_with({"a": (5.0, 5.0)})
    base = _result_with({"a": (0.0, 30.0)})
    with pytest.raises(ValueError, match="non-positive dorm duration"):
        speedup_ratios(dorm, base)


# --------------------------------------------------- goodput-aware allocation

def test_greedy_caps_curved_app_at_knee():
    cluster = ClusterSpec.homogeneous(8, ResourceVector.of(8, 0, 32))
    c = curve_for_model("olmoe-1b-7b", 32)       # early knee (MoE)
    knee = c.knee(32)
    assert knee < 32
    opt_on = make_optimizer("greedy", OptimizerConfig(0.5, 0.5))
    opt_off = make_optimizer("greedy",
                             OptimizerConfig(0.5, 0.5, goodput_aware=False))
    apps = [app(1, nmax=32, curve=c)]
    on = opt_on.solve(apps, cluster, None)
    off = opt_off.solve(apps, cluster, None)
    assert int(off.x.sum()) == 32                # linear target: n_max
    assert int(on.x.sum()) == knee               # goodput target: the knee


def test_knee_capping_never_violates_n_min():
    cluster = ClusterSpec.homogeneous(8, ResourceVector.of(8, 0, 32))
    c = curve_for_model("olmoe-1b-7b", 32)
    apps = [app(1, nmax=32, nmin=max(c.knee(32) + 2, 2), curve=c)]
    alloc = make_optimizer("greedy", OptimizerConfig(0.5, 0.5)).solve(
        apps, cluster, None)
    assert int(alloc.x.sum()) >= apps[0].n_min


def test_colgen_objective_weights_columns_by_goodput():
    cluster = ClusterSpec.homogeneous(6, ResourceVector.of(8, 0, 32))
    moe = curve_for_model("olmoe-1b-7b", 24)
    apps = [app(1, nmax=24, curve=moe),          # saturates early
            app(2, nmax=24)]                     # linear
    opt = make_optimizer("colgen", OptimizerConfig(0.5, 0.5))
    alloc = opt.solve(apps, cluster, None)
    counts = {a: int(alloc.x[i].sum())
              for i, a in enumerate(alloc.app_ids)}
    # past the MoE knee a container buys ~0 goodput for app1 but 1.0 for
    # the linear app2: the goodput-weighted IP routes the contested
    # capacity (48 containers for 2x24 demand) to app2
    assert counts["app2"] > counts["app1"]
    assert counts["app1"] >= moe.knee(24) or counts["app1"] >= apps[0].n_min


def test_goodput_greedy_numpy_jax_parity():
    wl = generate_trace(TraceConfig(n_apps=12, seed=5, goodput_curves=True,
                                    serving_fraction=0.0))
    cluster = heterogeneous_cluster(32, seed=0)
    allocs = []
    for be in ("numpy", "jax"):
        opt = make_optimizer("greedy", OptimizerConfig(0.2, 0.2, backend=be))
        alloc = opt.solve([w.spec for w in wl], cluster, None)
        allocs.append((alloc.app_ids, alloc.x.tolist()))
    assert allocs[0] == allocs[1]


def test_master_reports_cluster_goodput():
    cluster = ClusterSpec.homogeneous(4, ResourceVector.of(8, 0, 32))
    c = curve_for_model("olmoe-1b-7b", 8)
    m = DormMaster(cluster, "greedy", OptimizerConfig(0.5, 0.5),
                   protocol=RecordingProtocol())
    res = m.submit(app(1, nmax=8, curve=c))
    n = m.containers_of("app1")
    assert res.goodput == pytest.approx(c.at(n))
    res2 = m.submit(app(2, nmax=8))              # uncurved: counts linearly
    total = res2.goodput
    assert total == pytest.approx(
        c.at(m.containers_of("app1")) + m.containers_of("app2"))
    res3 = m.complete("app1")
    assert res3.goodput == pytest.approx(float(m.containers_of("app2")))
    # uncurved masters keep the 0.0 default (metric fully gated)
    m2 = DormMaster(cluster, "greedy", OptimizerConfig(0.5, 0.5),
                    protocol=RecordingProtocol())
    assert m2.submit(app(3)).goodput == 0.0
