"""Property suite for the event-storm absorber (PR 7).

What the absorber guarantees -- and what this suite gates:

  * **No coalescing => no change.** With an absorber attached but no two
    events sharing a timestamp/window, every event dispatches through the
    per-event hooks: the whole timeline (samples, durations, adjustments,
    Reallocated stream) is bit-exact vs an absorber-free run.
  * **Absorption is deterministic across engines and backends.** The same
    flood absorbed on the SoA engine, the legacy object engine, the numpy
    backend and the jax backend produces bit-identical timelines
    event-for-event (allocation matrices included).
  * **Merge semantics** (DormMaster.on_batch): last-wins resize dedup,
    arrival<->completion cancellation, group rejection of tightening
    resizes with bound revert, dead-target drops.
  * **Invariants vs per-event processing** on mixed same-timestamp
    floods: same app universe completes, bounds/capacity always honored,
    and the absorber issues strictly fewer policy passes than events.

  Absorbed floods are NOT required to reproduce per-event allocations
  under contention: per-event processing runs one solve (one DRF target
  set, one Eq-16 adjustment budget) per event, the absorber runs ONE
  merged solve for the flood -- that amortization is the feature. The
  determinism gates above are the enforceable bit-exactness claims.

Runs under hypothesis when available; falls back to a seeded-random sweep
of the same checks otherwise."""
import dataclasses

import numpy as np
import pytest

from repro.core import (AbsorberConfig, ApplicationSpec, ClusterRuntime,
                        ClusterSpec, Completion, DormMaster, OptimizerConfig,
                        PolicyTimer, Reallocated, RecordingProtocol, Resize,
                        ResourceVector, Storm, TraceConfig, generate_trace,
                        heterogeneous_cluster)
from repro.core.types import Allocation

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False


def _master(soa=True, incremental=True, backend="numpy"):
    cfg = OptimizerConfig(0.2, 0.2, incremental=incremental, soa=soa,
                          backend=backend)
    return DormMaster(heterogeneous_cluster(12, seed=3), "greedy", cfg,
                      protocol=RecordingProtocol())


def _quantize(wl, q):
    """Snap submit times to a grid so same-timestamp floods exist."""
    out = []
    for w in wl:
        s = dataclasses.replace(w.spec,
                                submit_time=q * round(w.spec.submit_time / q))
        out.append(dataclasses.replace(w, spec=s))
    return out


def _run(cluster, wl, resizes=(), absorber=None, soa=True, incremental=True,
         backend="numpy", horizon_s=14 * 24 * 3600.0):
    cfg = OptimizerConfig(0.2, 0.2, incremental=incremental, soa=soa,
                          backend=backend)
    m = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    rt = ClusterRuntime(m, horizon_s=horizon_s, absorber=absorber)
    rt.inject(*resizes)
    allocs = []

    def record(e):
        alloc = e.result.allocation
        # The row form (shared rows on the delta path) stacks to `x`.
        np.testing.assert_array_equal(
            Allocation.from_rows(alloc.app_ids, alloc.rows, alloc.b).x,
            alloc.x)
        allocs.append((e.t, alloc.app_ids, alloc.x.copy()))

    rt.bus.subscribe(Reallocated, record)
    res = rt.run(wl)
    return res, allocs, rt


def _scenario(seed, quantum, min_slaves=8, max_slaves=20):
    """Cluster + trace + same-instant Resize storm for one example."""
    rng = np.random.default_rng(seed)
    cluster = heterogeneous_cluster(int(rng.integers(min_slaves, max_slaves)),
                                    seed=int(seed) % 17)
    wl = generate_trace(TraceConfig(
        n_apps=int(rng.integers(8, 20)), seed=seed,
        mean_interarrival_s=400.0,
        # quantum=0 is the no-ties scenario: suppress the generator's
        # same-instant serving bursts so nothing can coalesce.
        burst_prob=0.15 if quantum else 0.0))
    if quantum:
        wl = _quantize(wl, quantum)
    resizes = []
    for _ in range(int(rng.integers(2, 7))):
        w = wl[int(rng.integers(len(wl)))]
        t = w.spec.submit_time + float(rng.uniform(0, 3600.0))
        if quantum:
            t = quantum * round(t / quantum)
        lo = int(rng.integers(1, 4))
        resizes.append(Resize(t, w.spec.app_id, lo,
                              lo + int(rng.integers(0, 9))))
    return cluster, wl, resizes


def _assert_timelines_equal(a, b, ctx=""):
    (res_a, al_a, _), (res_b, al_b, _) = a, b
    assert len(al_a) == len(al_b), ctx
    for (t1, ids1, x1), (t2, ids2, x2) in zip(al_a, al_b):
        assert t1 == t2 and ids1 == ids2, ctx
        np.testing.assert_array_equal(x1, x2, err_msg=ctx)
    assert res_a.durations() == res_b.durations(), ctx
    assert len(res_a.samples) == len(res_b.samples), ctx
    for sa, sb in zip(res_a.samples, res_b.samples):
        assert sa.t == sb.t and sa.running == sb.running, ctx
        assert sa.pending == sb.pending, ctx
        assert sa.adjustment_overhead == sb.adjustment_overhead, ctx
        assert sa.utilization == pytest.approx(sb.utilization, abs=1e-9)
        assert sa.fairness_loss == pytest.approx(sb.fairness_loss, abs=1e-9)


# ------------------------------------------ 1. no coalescing => no change

def _check_no_ties_bit_exact(seed):
    cluster, wl, resizes = _scenario(seed, quantum=0)   # continuous times
    base = _run(cluster, wl, resizes)
    absorbed = _run(cluster, wl, resizes, absorber=AbsorberConfig())
    _assert_timelines_equal(base, absorbed, f"seed={seed}")
    # Continuous timestamps: ties are measure-zero, so nothing coalesces.
    st_ = absorbed[2].absorber_stats
    assert st_["absorbed_events"] == 0, st_
    # Every pass carried exactly one event, except dead-target resize
    # passes (k=0: the resize published with no solve).
    assert st_["passes"] - st_["batch_hist"].get(0, 0) == st_["events"]


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=15, deadline=None)
    def test_absorber_without_ties_is_bit_exact(seed):
        _check_no_ties_bit_exact(seed)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_absorber_without_ties_is_bit_exact(seed):
        _check_no_ties_bit_exact(seed)


# ------------------------- 2. absorbed floods: engine/backend determinism

def _check_absorbed_engines_bit_exact(seed):
    cluster, wl, resizes = _scenario(seed, quantum=900.0)
    runs = {(soa, inc): _run(cluster, wl, resizes,
                             absorber=AbsorberConfig(), soa=soa,
                             incremental=inc)
            for soa in (True, False) for inc in (True, False)}
    ref = runs[(True, True)]
    # The flood must actually coalesce for this check to mean anything.
    assert ref[2].absorber_stats["absorbed_events"] > 0, seed
    for key, run in runs.items():
        if key != (True, True):
            _assert_timelines_equal(ref, run, f"seed={seed} {key}")
        assert run[2].absorber_stats == ref[2].absorber_stats, key


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=10, deadline=None)
    def test_absorbed_floods_bit_exact_across_engines(seed):
        _check_absorbed_engines_bit_exact(seed)
else:
    @pytest.mark.parametrize("seed", range(6))
    def test_absorbed_floods_bit_exact_across_engines(seed):
        _check_absorbed_engines_bit_exact(seed)


# Seeds 38 and 67 each hold a row-form delta solve with an Eq-16 revert.
@pytest.mark.parametrize("seed", [2, 11, 38, 67])
def test_absorbed_floods_bit_exact_vs_jax_backend(seed):
    cluster, wl, resizes = _scenario(seed, quantum=900.0)
    ref = _run(cluster, wl, resizes, absorber=AbsorberConfig())
    jx = _run(cluster, wl, resizes, absorber=AbsorberConfig(),
              backend="jax")
    assert ref[2].absorber_stats["absorbed_events"] > 0, seed
    _assert_timelines_equal(ref, jx, f"seed={seed} jax")


# --------------------------- 3. mixed floods: invariants vs per-event run

def _check_flood_invariants(seed):
    # Ample capacity: on a saturated cluster, WHICH apps stay pending
    # forever legitimately depends on solve order, so completion-set
    # equality is only an invariant when every app can eventually place.
    cluster, wl, resizes = _scenario(seed, quantum=900.0,
                                     min_slaves=40, max_slaves=60)
    base = _run(cluster, wl, resizes)
    absorbed = _run(cluster, wl, resizes, absorber=AbsorberConfig())
    res_b, _, _ = base
    res_a, _, rt_a = absorbed
    # Same app universe, and every app completes in both timelines (the
    # absorber may shift completion instants -- fewer mid-flood
    # adjustment pauses -- but never loses or invents work).
    assert set(res_a.completions) == set(res_b.completions), seed
    assert set(res_a.durations()) == set(res_b.durations()) \
        == set(res_a.completions), seed
    # Fewer policy passes than events is the point of the absorber
    # (k=0 passes are dead-target resizes that never reach the solver).
    st_ = rt_a.absorber_stats
    assert st_["events"] > st_["passes"] - st_["batch_hist"].get(0, 0), st_
    assert st_["absorbed_events"] > 0, st_
    # Stats are self-consistent.
    assert sum(k * c for k, c in st_["batch_hist"].items()) == st_["events"]
    assert sum(k * c for k, c in st_["batch_hist"].items() if k >= 2) \
        == st_["absorbed_events"]
    assert sum(st_["batch_hist"].values()) == st_["passes"]


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=15, deadline=None)
    def test_absorbed_flood_invariants_vs_per_event(seed):
        _check_flood_invariants(seed)
else:
    @pytest.mark.parametrize("seed", range(8))
    def test_absorbed_flood_invariants_vs_per_event(seed):
        _check_flood_invariants(seed)


# --------------------------------------- 4. directed merge-semantics tests

def _specs(n, prefix="a", n_min=1, n_max=4):
    return [ApplicationSpec(f"{prefix}{i}", "x", ResourceVector.of(2, 0, 8),
                            1, n_max, n_min) for i in range(n)]


def test_on_batch_resize_dedup_is_last_wins():
    mA, mB = _master(), _master()
    pre = _specs(3)
    for m in (mA, mB):
        m.submit_batch(pre)
    mA.on_batch((), (("a0", 1, 5), ("a0", 2, 8)), ())
    mB.on_batch((), (("a0", 2, 8),), ())
    for a in mA.specs:
        assert mA.specs[a].n_min == mB.specs[a].n_min
        assert mA.specs[a].n_max == mB.specs[a].n_max
        assert mA.containers_of(a) == mB.containers_of(a)


def test_on_batch_arrival_completion_cancellation():
    mA, mB = _master(), _master()
    pre = _specs(2)
    ghost = ApplicationSpec("ghost", "x", ResourceVector.of(2, 0, 8), 1, 4, 1)
    for m in (mA, mB):
        m.submit_batch(pre)
    # The arrival cancels against the same-flood completion: neither side
    # of the pair survives the merge.
    mA.on_batch(("ghost",), (), (ghost,))
    mB.on_batch((), (), ())
    assert "ghost" not in mA.specs
    assert set(mA.specs) == set(mB.specs)
    assert sorted(mA.pending) == sorted(mB.pending)
    for a in mA.specs:
        assert mA.containers_of(a) == mB.containers_of(a)


def test_on_batch_group_rejects_tightening_resizes():
    # Saturate a tiny cluster, then flood it with one impossible
    # tightening (n_min above total capacity) and one relaxing resize:
    # the tightening bound must revert, the relaxing one must stick.
    cluster = ClusterSpec.homogeneous(2, ResourceVector.of(8, 0, 32))
    m = DormMaster(cluster, "greedy", OptimizerConfig(0.2, 0.2),
                   protocol=RecordingProtocol())
    a, b = (ApplicationSpec("a", "x", ResourceVector.of(2, 0, 8), 1, 4, 1),
            ApplicationSpec("b", "x", ResourceVector.of(2, 0, 8), 1, 4, 1))
    m.submit_batch([a, b])
    res = m.on_batch((), (("a", 64, 64), ("b", 1, 5)), ())
    assert res is not None
    assert (m.specs["a"].n_min, m.specs["a"].n_max) == (1, 4)   # reverted
    assert (m.specs["b"].n_min, m.specs["b"].n_max) == (1, 5)   # kept
    assert 1 <= m.containers_of("a") <= 4


def test_on_batch_drops_resizes_of_dead_apps():
    m = _master()
    m.submit_batch(_specs(2))
    res = m.on_batch(("a0",), (("a0", 2, 6), ("nope", 1, 3)), ())
    assert res is not None
    assert "a0" not in m.specs and "nope" not in m.specs
    assert m.containers_of("a1") >= 1


# ------------------------------------------ 5. runtime wiring + accounting

def test_same_timestamp_completion_flood_one_pass():
    # Two identical fixed-size jobs submitted together finish at the same
    # instant: the absorber folds both completions (and both arrivals)
    # into one pass each, and publishes a Storm carrying the constituents.
    from repro.core import WorkloadApp
    spec = ApplicationSpec("j0", "x", ResourceVector.of(2, 0, 8), 2, 2, 2,
                           serial_work=1200.0)
    wl = [WorkloadApp(spec=spec, class_index=0, base_duration_s=1200.0),
          WorkloadApp(spec=dataclasses.replace(spec, app_id="j1"),
                      class_index=0, base_duration_s=1200.0)]
    m = _master()
    rt = ClusterRuntime(m, absorber=AbsorberConfig())
    storms = []
    rt.bus.subscribe(Storm, storms.append)
    res = rt.run(wl)
    assert len(res.durations()) == 2
    st_ = rt.absorber_stats
    assert st_["batches"] == 2 and st_["absorbed_events"] == 4, st_
    kinds = [(len(s.arrivals), len(s.completions)) for s in storms]
    assert kinds == [(2, 0), (0, 2)], kinds


def test_policy_timer_amortizes_absorbed_passes():
    """An absorbed flood of K events books K entries, and each event is
    charged the whole pass's wall time, not a K-th of it."""
    m = _master()
    timer = PolicyTimer(m)
    assert hasattr(timer, "on_batch")
    timer.on_batch((), (), tuple(_specs(3)))
    absorb = [(k, s) for k, s in timer.calls if k == "absorb"]
    assert len(absorb) == 3                      # one entry per event
    assert len({s for _, s in absorb}) == 1      # all equal: the pass
    # The pass holds every phase the master timed inside it.
    assert absorb[0][1] >= sum(m.phase_s.values()) > 0.0
    assert "absorb" in m.phase_breakdown()


def test_policy_timer_hides_on_batch_for_incapable_policies():
    class NoBatch:
        def on_arrival(self, specs): raise NotImplementedError
        def on_completion(self, app_id): raise NotImplementedError
        def on_resize(self, app_id, n_min=None, n_max=None): return None
        def on_tick(self, t): return None
        def containers_of(self, app_id): return 0
    assert not hasattr(PolicyTimer(NoBatch()), "on_batch")


def test_absorber_rejects_incapable_policy_and_batch_window():
    class NoBatch:
        def on_arrival(self, specs): raise NotImplementedError
        def on_completion(self, app_id): raise NotImplementedError
        def on_resize(self, app_id, n_min=None, n_max=None): return None
        def on_tick(self, t): return None
        def containers_of(self, app_id): return 0
    with pytest.raises(ValueError, match="on_batch"):
        ClusterRuntime(NoBatch(), absorber=AbsorberConfig())
    with pytest.raises(ValueError, match="mutually exclusive"):
        ClusterRuntime(_master(), batch_window_s=60.0,
                       absorber=AbsorberConfig())


def test_windowed_absorption_batches_spread_arrivals():
    # Arrivals 10 s apart, window 60 s: one pass absorbs the whole burst
    # (the generalization of batch_window_s through the absorber path).
    from repro.core import WorkloadApp
    wl = []
    for i in range(5):
        spec = ApplicationSpec(f"w{i}", "x", ResourceVector.of(2, 0, 8),
                               1, 2, 1, submit_time=100.0 + 10.0 * i,
                               serial_work=40_000.0 + 1000.0 * i)
        wl.append(WorkloadApp(spec=spec, class_index=0,
                              base_duration_s=spec.serial_work))
    m = _master()
    rt = ClusterRuntime(m, absorber=AbsorberConfig(window_s=60.0))
    rt.run(wl)
    st_ = rt.absorber_stats
    assert st_["batch_hist"].get(5, 0) >= 1, st_
