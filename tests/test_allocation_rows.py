"""Row-form allocations (`Allocation.from_rows`): a delta solve on the SoA
state shares the previous allocation's unchanged rows, and the master's row
drops take rows by reference. Shared rows are read-only and never change
after they are made; no pass on the delta path builds an n x b matrix."""
import copy
import tracemalloc

import numpy as np
import pytest

from repro.core import (AbsorberConfig, ApplicationSpec, ClusterRuntime,
                        ClusterSpec, DormMaster, OptimizerConfig, Reallocated,
                        RecordingProtocol, ResourceVector, TraceConfig,
                        generate_trace, heterogeneous_cluster)
from repro.core.types import Allocation


def _spec(i, nmax=4, nmin=1):
    return ApplicationSpec(f"app{i}", "x", ResourceVector.of(1, 0, 2), 1,
                           nmax, nmin)


def test_allocation_forms_agree_and_rows_are_read_only():
    x = np.arange(12, dtype=np.int64).reshape(3, 4)
    dense = Allocation(("a", "b", "c"), x)
    rows = Allocation.from_rows(dense.app_ids, dense.rows)
    before = Allocation.densified
    np.testing.assert_array_equal(rows.x, dense.x)
    assert Allocation.densified == before + 1
    rows.x                                    # cached: stacked once
    assert Allocation.densified == before + 1
    assert rows.row_at(1) is dense.rows[1]
    assert rows.containers_of("b") == dense.containers_of("b") == 22
    kept = rows.take([2, 0])
    assert kept.app_ids == ("c", "a") and kept.row_at(0) is rows.row_at(2)
    np.testing.assert_array_equal(kept.x, x[[2, 0]])
    empty = rows.take([])
    assert empty.x.shape == (0, 4) and empty.b == 4
    with pytest.raises(ValueError, match="needs b"):
        Allocation.from_rows((), ())
    for row in (dense.row_at(0), rows.row_at(0)):
        with pytest.raises(ValueError):
            row[0] = 7
    with pytest.raises(ValueError):
        rows.x[0, 0] = 7
    assert x[0, 0] == 0


def test_allocations_never_change_after_they_are_made():
    """A few hundred passes of a steady runtime on the SoA engine: every
    previous allocation the optimizer is handed and every allocation a
    pass returns still equals a deep copy taken when it was made."""
    cluster = heterogeneous_cluster(120, seed=1)
    wl = generate_trace(TraceConfig(n_apps=320, seed=4,
                                    mean_interarrival_s=600.0))
    cfg = OptimizerConfig(0.1, 0.1, incremental=True, soa=True)
    master = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    opt = master.optimizer
    seen = []
    solve = opt.solve

    def spy(apps, cluster, prev=None, state=None):
        if prev is not None:
            seen.append((prev, copy.deepcopy(prev)))
        out = solve(apps, cluster, prev, state=state)
        if out is not None:
            seen.append((out, copy.deepcopy(out)))
        return out

    opt.solve = spy
    rt = ClusterRuntime(master, horizon_s=60 * 24 * 3600.0,
                        absorber=AbsorberConfig())
    passes = []
    rt.bus.subscribe(Reallocated, lambda e: passes.append(
        (e.result.allocation, copy.deepcopy(e.result.allocation))))
    rt.run(wl)
    assert len(passes) >= 300
    assert opt.rows_shared > 0 and opt.delta_solves > 100
    rows = [r for a, _ in passes for r in a.rows]
    assert len({id(r) for r in rows}) < len(rows)      # some shared
    for alloc, snap in seen + passes:
        assert alloc.app_ids == snap.app_ids
        np.testing.assert_array_equal(alloc.x, snap.x)
        for row in alloc.rows:
            assert not row.flags.writeable
    row = next(r for a, _ in passes for r in a.rows if r.any())
    with pytest.raises(ValueError):
        row[int(np.flatnonzero(row)[0])] -= 1


def test_delta_pass_builds_no_dense_matrix():
    """512 apps x 4096 slaves: a completion pass and an arrival pass on the
    delta path allocate far less than one n x b int64 matrix and stack no
    row-form allocation."""
    n, b = 512, 4096
    cluster = ClusterSpec.homogeneous(b, ResourceVector.of(12, 1, 128))
    cfg = OptimizerConfig(0.1, 0.1, incremental=True, soa=True)
    master = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    master.on_arrival([_spec(i) for i in range(n - 1)])
    master.on_arrival([_spec(n)])              # row form from here on
    opt = master.optimizer
    for step in (lambda: master.on_completion("app3"),
                 lambda: master.on_arrival([_spec(n + 1)])):
        delta0, densified0 = opt.delta_solves, Allocation.densified
        copied0 = opt.rows_copied
        tracemalloc.start()
        try:
            res = step()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert opt.delta_solves == delta0 + 1
        assert Allocation.densified == densified0
        assert opt.rows_copied - copied0 <= 1     # the scheduled app only
        assert peak < n * b * 8 / 8, peak
    assert len(res.allocation.app_ids) == n
    assert res.changed_counts == {f"app{n + 1}": 4}
    assert opt.rows_shared >= 2 * (n - 1)


@pytest.mark.parametrize("demand", [(1, 0, 2), (0.57, 0, 3.3)],
                         ids=["integral", "fractional"])
@pytest.mark.parametrize("order", ["prefix", "permuted"])
def test_every_state_delta_solve_returns_the_row_form(demand, order):
    """Fractional demands and a previous allocation whose app order is not a
    prefix take the row form too: unscheduled apps keep prev's row objects,
    and the rows equal the full re-solve's matrix."""
    cluster = ClusterSpec.homogeneous(6, ResourceVector.of(10, 0, 64))
    specs = [ApplicationSpec(f"f{i}", "x", ResourceVector.of(*demand), 1,
                             4, 1) for i in range(9)]
    out = []
    for incremental in (True, False):
        cfg = OptimizerConfig(0.2, 0.2, incremental=incremental, soa=True)
        m = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
        m.on_arrival(specs[:8])
        m.on_completion("f2")
        m.on_arrival(specs[8:])
        prev = m.prev_alloc
        if order == "permuted":
            prev = prev.take(list(range(len(prev.app_ids)))[::-1])
        apps = list(m.specs.values())
        delta0 = m.optimizer.delta_solves
        out.append((m.optimizer.solve(apps, cluster, prev, state=m.state),
                    prev, m.optimizer.delta_solves - delta0))
    (inc, prev, n_delta), (full, _, _) = out
    assert n_delta == 1
    assert inc.app_ids == full.app_ids
    np.testing.assert_array_equal(inc.x, full.x)
    pos = dict(zip(prev.app_ids, prev.rows))
    kept = [a for i, a in enumerate(inc.app_ids)
            if a in pos and inc.row_at(i) is pos[a]]
    assert len(kept) >= 6, kept
