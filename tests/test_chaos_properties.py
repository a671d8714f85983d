"""Property-based chaos-storm suite (PR 8).

Random interleavings of Arrival / Completion / Resize / SlaveFailed /
SlaveDrained / SlaveDegraded / SlaveRestored events driven through FOUR
DormMaster configurations simultaneously (SoA/legacy engine x
incremental/full re-solve). Invariants, after every single event:

  * effective per-slave capacity is never exceeded (a dead slave hosts
    nothing; a degraded slave hosts at most its fraction),
  * every PLACED app holds n_min <= count <= n_max (displaced apps that
    cannot reach n_min are parked, never left half-placed),
  * no work is lost beyond Eq-4: every displaced app is either re-placed
    (forced adjustment, charged to the Eq-4 overhead) or parked into the
    pending queue -- it never silently vanishes,
  * the four engines are bit-exact event-for-event.

Runtime-level properties mirror the absorber doctrine: with NO
same-timestamp ties, an absorber-attached chaos run is bit-exact vs an
absorber-free run; an absorbed failure flood (correlated rack loss) is
bit-exact across engines and backends (jax when available).

Runs under hypothesis when available; falls back to a seeded-random
sweep of the same checks otherwise."""
import dataclasses

import numpy as np
import pytest

from repro.core import (AbsorberConfig, ApplicationSpec, ChaosConfig,
                        ClusterRuntime, ClusterSpec, DormMaster,
                        OptimizerConfig, Reallocated, RecordingProtocol,
                        Resize, ResourceVector, SlaveDegraded, SlaveDrained,
                        SlaveFailed, SlaveRestored, TraceConfig,
                        generate_trace, heterogeneous_cluster)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

THETAS = ((0.2, 0.2), (1.0, 1.0), (0.1, 0.3))


def _masters(cluster, theta):
    out = {}
    for soa in (True, False):
        for inc in (True, False):
            cfg = OptimizerConfig(*theta, incremental=inc, soa=soa)
            out[(soa, inc)] = DormMaster(cluster, "greedy", cfg,
                                         protocol=RecordingProtocol())
    return out


def _gen_ops(rng):
    """Random chaos-heavy event script: (cluster, theta, ops)."""
    b = int(rng.integers(2, 6))
    cap = ResourceVector.of(int(rng.integers(6, 14)),
                            int(rng.integers(0, 3)),
                            int(rng.integers(16, 49)))
    cluster = ClusterSpec.homogeneous(b, cap)
    theta = THETAS[int(rng.integers(len(THETAS)))]

    ops = []
    alive = []
    down = set()
    next_id = 0
    for _ in range(int(rng.integers(10, 21))):
        choices = ["arrive", "fail", "degrade"]
        if alive:
            choices += ["complete", "resize"]
        if down:
            choices += ["restore", "restore"]
        op = choices[int(rng.integers(len(choices)))]
        if op == "arrive":
            n_min = int(rng.integers(1, 3))
            n_max = n_min + int(rng.integers(0, 7))
            spec = ApplicationSpec(
                f"a{next_id}", "x",
                ResourceVector.of(int(rng.integers(1, 4)),
                                  int(rng.integers(0, 2)),
                                  int(rng.integers(1, 13))),
                int(rng.integers(1, 4)), n_max, n_min)
            next_id += 1
            alive.append(spec.app_id)
            ops.append(("arrive", spec))
        elif op == "complete":
            app = alive.pop(int(rng.integers(len(alive))))
            ops.append(("complete", app))
        elif op == "resize":
            app = alive[int(rng.integers(len(alive)))]
            lo = int(rng.integers(1, 4))
            ops.append(("resize", app, lo, lo + int(rng.integers(0, 8))))
        elif op == "fail":
            j = int(rng.integers(b))
            down.add(j)
            kind = "fail" if rng.random() < 0.7 else "drain"
            ops.append((kind, f"slave-{j}"))
        elif op == "degrade":
            j = int(rng.integers(b))
            down.add(j)
            f = float(rng.choice([0.25, 0.5, 0.75]))
            ops.append(("degrade", f"slave-{j}", f))
        else:  # restore
            j = down.pop() if rng.random() < 0.8 else int(rng.integers(b))
            ops.append(("restore", f"slave-{j}"))
    return cluster, theta, ops


def _apply(master, op):
    kind = op[0]
    if kind == "arrive":
        return master.on_arrival((op[1],))
    if kind == "complete":
        return master.on_completion(op[1])
    if kind == "resize":
        return master.on_resize(op[1], op[2], op[3])
    if kind == "fail":
        return master.on_slave_failed(op[1])
    if kind == "drain":
        return master.on_slave_drained(op[1])
    if kind == "degrade":
        return master.on_slave_degraded(op[1], op[2])
    return master.on_slave_restored(op[1])


def _check_invariants(master, res):
    """Capacity / bounds / no-lost-work invariants from the master's own
    (post-event) view, against the EFFECTIVE cluster spec."""
    cap = master.cluster.capacity_matrix()
    used = np.zeros_like(cap, dtype=np.float64)
    placed = set()
    for app_id in list(master.partitions):
        spec = master.specs[app_id]
        if master.state is not None:
            row = master.state.placement(app_id)
        else:
            row = master._placements[app_id]
        count = int(row.sum())
        placed.add(app_id)
        assert spec.n_min <= count <= spec.n_max, \
            f"{app_id}: count {count} outside [{spec.n_min}, {spec.n_max}]"
        used += row[:, None] * spec.demand.as_array()[None, :]
    assert np.all(used <= cap + 1e-6), "effective capacity exceeded"
    # No app lost beyond Eq-4: every admitted app is placed or pending,
    # and every displaced app in this result was re-placed, parked, or
    # completed -- never dropped from the universe.
    assert placed | set(master.pending) == set(master.specs)
    if res is not None:
        assert set(res.forced_adjusted_app_ids) <= set(res.adjusted_app_ids)
        assert set(res.parked_app_ids) <= set(master.pending)
        for a in res.displaced_app_ids:
            assert (a in placed) or (a in master.pending) \
                or (a not in master.specs), f"{a} silently vanished"


def _check_storm(seed: int) -> None:
    rng = np.random.default_rng(seed)
    cluster, theta, ops = _gen_ops(rng)
    masters = _masters(cluster, theta)
    ref_key = (True, True)
    for op in ops:
        results = {}
        for key, m in masters.items():
            results[key] = _apply(m, op)
            _check_invariants(m, results[key])
        ref = results[ref_key]
        for key, res in results.items():
            if key == ref_key:
                continue
            assert (res is None) == (ref is None), (op, key)
            if ref is None:
                continue
            assert res.allocation.app_ids == ref.allocation.app_ids, (op, key)
            np.testing.assert_array_equal(res.allocation.x, ref.allocation.x,
                                          err_msg=f"{op} {key}")
            assert res.adjusted_app_ids == ref.adjusted_app_ids, (op, key)
            assert res.forced_adjusted_app_ids == \
                ref.forced_adjusted_app_ids, (op, key)
            assert res.displaced_app_ids == ref.displaced_app_ids, (op, key)
            assert res.parked_app_ids == ref.parked_app_ids, (op, key)
            assert res.started_app_ids == ref.started_app_ids, (op, key)
            assert res.pending_app_ids == ref.pending_app_ids, (op, key)
            assert res.changed_counts == ref.changed_counts, (op, key)
            assert res.utilization == pytest.approx(ref.utilization,
                                                    abs=1e-9)
            assert res.fairness_loss == pytest.approx(ref.fairness_loss,
                                                      abs=1e-9)


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=120, deadline=None)
    def test_chaos_storm_engines_bit_exact(seed):
        _check_storm(seed)
else:
    @pytest.mark.parametrize("chunk", range(8))
    def test_chaos_storm_engines_bit_exact(chunk):
        # Seeded fallback: same check, 8 chunks x 15 seeds = 120 examples.
        for k in range(15):
            _check_storm(chunk * 15 + k)


# ------------------------------------------- runtime-level chaos timelines

def _chaos_cfg(seed):
    return ChaosConfig(seed=int(seed) % 1009, crashes_per_day=20.0,
                       rack_size=2, crash_restore_s=1800.0,
                       drains_per_day=4.0, straggler_frac=0.15,
                       degrade_factor=0.5, degrade_duration_s=1800.0)


def _run(cluster, wl, chaos, absorber=None, soa=True, incremental=True,
         backend="numpy"):
    cfg = OptimizerConfig(0.2, 0.2, incremental=incremental, soa=soa,
                          backend=backend)
    m = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    rt = ClusterRuntime(m, horizon_s=12 * 3600.0, chaos=chaos,
                        absorber=absorber)
    allocs = []
    rt.bus.subscribe(Reallocated,
                     lambda e: allocs.append((e.t,
                                              e.result.allocation.app_ids,
                                              e.result.allocation.x.copy())))
    res = rt.run(wl)
    return res, allocs, rt


def _scenario(seed):
    rng = np.random.default_rng(seed)
    cluster = heterogeneous_cluster(int(rng.integers(8, 16)),
                                    seed=int(seed) % 17)
    wl = generate_trace(TraceConfig(n_apps=int(rng.integers(8, 16)),
                                    seed=seed, mean_interarrival_s=400.0,
                                    burst_prob=0.0))
    return cluster, wl


def _assert_timelines_equal(a, b, ctx=""):
    (res_a, al_a, _), (res_b, al_b, _) = a, b
    assert len(al_a) == len(al_b), ctx
    for (t1, ids1, x1), (t2, ids2, x2) in zip(al_a, al_b):
        assert t1 == t2 and ids1 == ids2, ctx
        np.testing.assert_array_equal(x1, x2, err_msg=ctx)
    assert res_a.durations() == res_b.durations(), ctx
    assert res_a.total_forced_adjustments == \
        res_b.total_forced_adjustments, ctx
    assert len(res_a.samples) == len(res_b.samples), ctx
    for sa, sb in zip(res_a.samples, res_b.samples):
        assert sa.t == sb.t and sa.running == sb.running, ctx
        assert sa.pending == sb.pending, ctx
        assert sa.adjustment_overhead == sb.adjustment_overhead, ctx
        assert sa.forced_adjustments == sb.forced_adjustments, ctx
        assert sa.utilization == pytest.approx(sb.utilization, abs=1e-9)
        assert sa.fairness_loss == pytest.approx(sb.fairness_loss, abs=1e-9)


def _check_runtime_chaos_engines(seed):
    """SoA/legacy x incremental/full timelines identical under a seeded
    failure replay (per-event path, rack floods processed one by one)."""
    cluster, wl = _scenario(seed)
    chaos = _chaos_cfg(seed)
    runs = {(soa, inc): _run(cluster, wl, chaos, soa=soa, incremental=inc)
            for soa in (True, False) for inc in (True, False)}
    ref = runs[(True, True)]
    assert ref[0].chaos_seed == chaos.seed
    for key, run in runs.items():
        if key != (True, True):
            _assert_timelines_equal(ref, run, f"seed={seed} {key}")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=8, deadline=None)
    def test_runtime_chaos_timelines_identical_across_engines(seed):
        _check_runtime_chaos_engines(seed)
else:
    @pytest.mark.parametrize("seed", range(5))
    def test_runtime_chaos_timelines_identical_across_engines(seed):
        _check_runtime_chaos_engines(seed)


def _check_no_ties_absorber_bit_exact(seed):
    """rack_size=1 + continuous trace times: no two events share an
    instant, so the absorber must not change the timeline at all."""
    cluster, wl = _scenario(seed)
    chaos = dataclasses.replace(_chaos_cfg(seed), rack_size=1)
    base = _run(cluster, wl, chaos)
    absorbed = _run(cluster, wl, chaos, absorber=AbsorberConfig())
    _assert_timelines_equal(base, absorbed, f"seed={seed}")
    assert absorbed[2].absorber_stats["absorbed_events"] == 0


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=8, deadline=None)
    def test_chaos_absorber_without_ties_is_bit_exact(seed):
        _check_no_ties_absorber_bit_exact(seed)
else:
    @pytest.mark.parametrize("seed", range(5))
    def test_chaos_absorber_without_ties_is_bit_exact(seed):
        _check_no_ties_absorber_bit_exact(seed)


def _check_absorbed_chaos_engines(seed):
    """Correlated rack loss (rack_size >= 2) coalesces; the absorbed
    recovery timeline is bit-exact across engines."""
    cluster, wl = _scenario(seed)
    chaos = dataclasses.replace(_chaos_cfg(seed), rack_size=3,
                                crashes_per_day=30.0)
    runs = {(soa, inc): _run(cluster, wl, chaos,
                             absorber=AbsorberConfig(), soa=soa,
                             incremental=inc)
            for soa in (True, False) for inc in (True, False)}
    ref = runs[(True, True)]
    assert ref[2].absorber_stats["absorbed_events"] > 0, seed
    for key, run in runs.items():
        if key != (True, True):
            _assert_timelines_equal(ref, run, f"seed={seed} {key}")
        assert run[2].absorber_stats == ref[2].absorber_stats, key


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=6, deadline=None)
    def test_absorbed_chaos_floods_bit_exact_across_engines(seed):
        _check_absorbed_chaos_engines(seed)
else:
    @pytest.mark.parametrize("seed", range(4))
    def test_absorbed_chaos_floods_bit_exact_across_engines(seed):
        _check_absorbed_chaos_engines(seed)


@pytest.mark.parametrize("seed", [3, 17])
def test_chaos_timelines_bit_exact_vs_jax_backend(seed):
    cluster, wl = _scenario(seed)
    chaos = _chaos_cfg(seed)
    ref = _run(cluster, wl, chaos)
    jx = _run(cluster, wl, chaos, backend="jax")
    _assert_timelines_equal(ref, jx, f"seed={seed} jax")
    rack = dataclasses.replace(chaos, rack_size=3, crashes_per_day=30.0)
    ref_f = _run(cluster, wl, rack, absorber=AbsorberConfig())
    jx_f = _run(cluster, wl, rack, absorber=AbsorberConfig(), backend="jax")
    assert ref_f[2].absorber_stats["absorbed_events"] > 0, seed
    _assert_timelines_equal(ref_f, jx_f, f"seed={seed} jax absorbed")
