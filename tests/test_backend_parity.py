"""Backend-parity property suite: numpy vs jax, bit-exact.

The numpy backend is the bit-exactness reference (its kernels are the
seed code extracted verbatim into `repro.core.backend`); the jax backend
re-expresses the same three scheduler kernels on `jax.jit`/`lax` with
static shapes and pow2 padding.  This suite asserts the two backends are
indistinguishable at every level:

  * kernel level -- ladder-DRF container counts, the saturating probe and
    best-fit placement produce identical results on random instances,
    including fractional demands, zero-demand columns and score ties
    (placement is compared as the dense slave->count mapping: the (js,
    counts) PAIRING is the contract, the pair ORDER is not),
  * master level -- two DormMasters differing only in
    `OptimizerConfig.backend` stay bit-exact event-for-event through
    random arrival/completion/resize storms with ~60% fractional demands:
    same allocation matrices, same adjusted/started/pending sets, same
    delta/full solve counters.

Runs under hypothesis when available (CI installs it); falls back to a
seeded-random sweep of the same checks otherwise."""
import os

import numpy as np
import pytest

from repro.core import (ApplicationSpec, ClusterSpec, DormMaster,
                        OptimizerConfig, RecordingProtocol,
                        ResourceVector, configure_compile_cache, get_backend)
from repro.core.telemetry import compile_counter
from repro.core.types import Allocation

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

# Modest example counts: every distinct padded shape jit-compiles once
# per process, and the pow2 padding contract keeps that set small.
N_KERNEL = 60
N_MASTER = 8


def _backends():
    return get_backend("numpy"), get_backend("jax")


# ------------------------------------------------- kernel-level parity

def _rand_instance(rng):
    """(d, n_min, n_max, w, total): random ladder/probe instance with
    fractional demands, occasional zero columns and tight totals."""
    n = int(rng.integers(1, 13))
    m = int(rng.integers(2, 5))
    if rng.random() < 0.5:
        d = rng.integers(1, 9, size=(n, m)).astype(np.float64)
    else:
        d = np.round(rng.uniform(0.1, 8.0, size=(n, m)), 2)
    if rng.random() < 0.3:                      # zero-demand column
        d[:, int(rng.integers(m))] = 0.0
    n_min = rng.integers(1, 4, size=n).astype(np.int64)
    n_max = n_min + rng.integers(0, 9, size=n).astype(np.int64)
    w = rng.integers(1, 4, size=n).astype(np.float64)
    # Total capacity between "almost nothing fits" and "everything fits".
    scale = float(rng.uniform(0.3, 3.0))
    total = np.maximum(d.sum(axis=0) * scale, 1.0)
    if rng.random() < 0.2:
        total[int(rng.integers(m))] = 0.0       # a depleted resource
    return d, n_min, n_max, w, total


def _check_kernel_parity(seed: int) -> None:
    rng = np.random.default_rng(seed)
    np_be, jx_be = _backends()
    for _ in range(4):
        d, n_min, n_max, w, total = _rand_instance(rng)
        ref = np_be.ladder_counts(d, n_min, n_max, w, total)
        got = jx_be.ladder_counts(d, n_min, n_max, w, total)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      err_msg=f"ladder seed={seed}")
        nm = n_max.astype(np.float64)
        assert (np_be.saturating_probe(d, nm, total)
                == jx_be.saturating_probe(d, nm, total)), f"probe {seed}"


def _check_place_parity(seed: int) -> None:
    """Dense-mapping equality for best-fit placement; forces score ties
    via duplicated slave rows."""
    rng = np.random.default_rng(seed)
    np_be, jx_be = _backends()
    for _ in range(4):
        b = int(rng.integers(2, 33))
        m = int(rng.integers(2, 5))
        cap = rng.integers(4, 17, size=(b, m)).astype(np.float64)
        if rng.random() < 0.5:                  # duplicate rows -> ties
            cap = cap[rng.integers(b, size=b)]
        used = cap * rng.uniform(0.0, 1.0, size=(b, m))
        free = cap - np.round(used, 1)
        inv_cap = np.where(cap > 0, 1.0 / np.maximum(cap, 1e-12), 0.0)
        if rng.random() < 0.5:
            di = rng.integers(1, 5, size=m).astype(np.float64)
        else:
            di = np.round(rng.uniform(0.2, 4.0, size=m), 2)
        need = int(rng.integers(1, 9))
        ref = np_be.place_counts(free, di, inv_cap, need)
        got = jx_be.place_counts(free, di, inv_cap, need)
        assert (ref is None) == (got is None), f"place feasibility {seed}"
        if ref is None:
            continue
        dense_r = np.zeros(b, dtype=np.int64)
        dense_g = np.zeros(b, dtype=np.int64)
        dense_r[ref[0]] = ref[1]
        dense_g[got[0]] = got[1]
        np.testing.assert_array_equal(dense_g, dense_r,
                                      err_msg=f"place seed={seed}")


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=N_KERNEL, deadline=None)
    def test_kernel_counts_bit_exact(seed):
        _check_kernel_parity(seed)

    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=N_KERNEL, deadline=None)
    def test_placement_mapping_identical(seed):
        _check_place_parity(seed)
else:                                                  # pragma: no cover
    @pytest.mark.parametrize("chunk", range(6))
    def test_kernel_counts_bit_exact(chunk):
        for k in range(10):
            _check_kernel_parity(chunk * 10 + k)

    @pytest.mark.parametrize("chunk", range(6))
    def test_placement_mapping_identical(chunk):
        for k in range(10):
            _check_place_parity(chunk * 10 + k)


# ------------------------------------------------- master-level storms

def _gen_storm(rng):
    """(cluster, ops): arrival/completion/resize script; ~60% of arrivals
    carry fractional demands so the delta path runs fractional too."""
    b = int(rng.integers(2, 6))
    cap = ResourceVector.of(int(rng.integers(8, 17)),
                            int(rng.integers(0, 3)),
                            int(rng.integers(24, 65)))
    cluster = ClusterSpec.homogeneous(b, cap)
    ops, alive, next_id = [], [], 0
    for _ in range(int(rng.integers(10, 19))):
        choices = ["arrive", "arrive"]
        if alive:
            choices += ["complete", "resize"]
        op = choices[int(rng.integers(len(choices)))]
        if op == "arrive":
            if rng.random() < 0.6:
                dem = ResourceVector.of(
                    round(float(rng.uniform(0.3, 3.5)), 2),
                    float(rng.integers(0, 2)),
                    round(float(rng.uniform(0.5, 9.0)), 1))
            else:
                dem = ResourceVector.of(int(rng.integers(1, 4)),
                                        int(rng.integers(0, 2)),
                                        int(rng.integers(1, 10)))
            n_min = int(rng.integers(1, 3))
            spec = ApplicationSpec(f"a{next_id}", "x", dem,
                                   int(rng.integers(1, 4)),
                                   n_min + int(rng.integers(0, 7)), n_min)
            next_id += 1
            alive.append(spec.app_id)
            ops.append(("arrive", spec))
        elif op == "complete":
            ops.append(("complete",
                        alive.pop(int(rng.integers(len(alive))))))
        else:
            lo = int(rng.integers(1, 4))
            ops.append(("resize", alive[int(rng.integers(len(alive)))],
                        lo, lo + int(rng.integers(0, 7))))
    return cluster, ops


def _apply(master, op):
    if op[0] == "arrive":
        return master.on_arrival((op[1],))
    if op[0] == "complete":
        return master.on_completion(op[1])
    return master.on_resize(op[1], op[2], op[3])


def _check_master_storm(seed: int) -> None:
    rng = np.random.default_rng(seed)
    cluster, ops = _gen_storm(rng)
    masters = {}
    for be in ("numpy", "jax"):
        cfg = OptimizerConfig(0.2, 0.2, incremental=True, soa=True,
                              backend=be)
        masters[be] = DormMaster(cluster, "greedy", cfg,
                                 protocol=RecordingProtocol())
    for op in ops:
        ref = _apply(masters["numpy"], op)
        got = _apply(masters["jax"], op)
        assert (ref is None) == (got is None), (seed, op)
        if ref is None:
            continue
        assert got.allocation.app_ids == ref.allocation.app_ids, (seed, op)
        np.testing.assert_array_equal(got.allocation.x, ref.allocation.x,
                                      err_msg=f"seed={seed} op={op}")
        assert got.adjusted_app_ids == ref.adjusted_app_ids, (seed, op)
        assert got.started_app_ids == ref.started_app_ids, (seed, op)
        assert got.pending_app_ids == ref.pending_app_ids, (seed, op)
        assert got.utilization == pytest.approx(ref.utilization, abs=1e-9)
        assert got.fairness_loss == pytest.approx(ref.fairness_loss,
                                                  abs=1e-9)
    # Same control flow, not just the same answers.
    o_ref, o_jax = masters["numpy"].optimizer, masters["jax"].optimizer
    assert o_jax.delta_solves == o_ref.delta_solves, seed
    assert o_jax.full_solves == o_ref.full_solves, seed


if HAVE_HYPOTHESIS:
    @given(st.integers(0, 2 ** 32 - 1))
    @settings(max_examples=N_MASTER, deadline=None)
    def test_master_storms_bit_exact_across_backends(seed):
        _check_master_storm(seed)
else:                                                  # pragma: no cover
    @pytest.mark.parametrize("chunk", range(4))
    def test_master_storms_bit_exact_across_backends(chunk):
        for k in range(2):
            _check_master_storm(chunk * 2 + k)


# ------------------------------------------ row-form delta with a revert

@pytest.mark.parametrize("grow", [("a0", "a1"), ("a0", "a1", "a2")])
def test_row_form_delta_revert_bit_exact_across_backends(grow):
    """A flood of relaxing resizes on the delta path: every grown app gets
    a top-up, Eq 16's budget (ceil(0.2 * 3) = 1 row) reverts all but one,
    and the reverted rows are the previous allocation's row objects. Both
    backends return the same rows, and the row form stacks to the dense
    matrix."""
    cluster = ClusterSpec.homogeneous(4, ResourceVector.of(8, 0, 32))
    specs = [ApplicationSpec(f"a{i}", "x", ResourceVector.of(1, 0, 2), 1,
                             2, 1) for i in range(3)]
    out = {}
    for be in ("numpy", "jax"):
        cfg = OptimizerConfig(0.2, 0.2, incremental=True, soa=True,
                              backend=be)
        m = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
        m.on_arrival(specs[:2])
        m.on_arrival(specs[2:])                    # row form from here on
        prev = m.prev_alloc
        res = m.on_batch((), [(a, None, 4) for a in grow], ())
        alloc, o = res.allocation, m.optimizer
        assert o.delta_solves == 2 and o.full_solves == 1, be
        assert len(res.adjusted_app_ids) == 1, be
        same = [alloc.row_at(i) is prev.row_at(i) for i in range(3)]
        assert same.count(True) == 2, (be, same)
        dense = np.stack([alloc.row_at(i) for i in range(3)])
        np.testing.assert_array_equal(
            Allocation.from_rows(alloc.app_ids, alloc.rows).x, dense)
        out[be] = (alloc.app_ids, alloc.x, res.adjusted_app_ids)
    assert out["jax"][0] == out["numpy"][0]
    np.testing.assert_array_equal(out["jax"][1], out["numpy"][1])
    assert out["jax"][2] == out["numpy"][2]


def test_jax_backend_books_compile_time():
    """jit compiles are counted per program name from jax's own events (a
    backend dispatch books its compile as `dorm.<program>`);
    backend.compile_s and DormMaster.backend_compile_s read that count."""
    rng = np.random.default_rng(7)
    cluster, ops = _gen_storm(rng)
    cfg = OptimizerConfig(0.2, 0.2, incremental=True, soa=True,
                          backend="jax")
    m = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    for op in ops:
        _apply(m, op)
    be = m.optimizer.backend
    compiles = compile_counter()
    assert compiles.count.get("dorm.place_run", 0) >= 1
    assert be.compile_s == pytest.approx(sum(
        compiles.seconds.get("dorm." + p, 0.0)
        for p in ("probe", "place", "place_run", "ladder")))
    assert be.compile_s > 0.0
    assert m.backend_compile_s == pytest.approx(be.compile_s)
    assert m.phase_breakdown()["backend_compile"] == m.backend_compile_s


# K = 3 and 8: app 0 comes back for its target after its n_min, as the
# optimizer's two best-fit passes schedule it.
SCHEDULES = {
    1: [(0, 700)],
    3: [(0, 40), (1, 300), (0, 900)],
    8: [(2, 5), (0, 60), (1, 120), (3, 2000), (0, 400), (4, 1),
        (2, 5000), (1, 250)],
}


@pytest.mark.parametrize("k", sorted(SCHEDULES))
def test_place_run_bit_exact_at_8192_padded_slaves(k):
    """The sort fill inside `place_run` over 5000 slaves (padded to 8192,
    as in the 5000-slave deployment) grants what the numpy engine grants,
    bit for bit: the testbed's 20 slave types repeated, so scores tie in
    large groups, with integral and fractional demands."""
    rng = np.random.default_rng(k)
    b, m = 5000, 3
    kinds = np.array([[12, 1, 128]] * 5 + [[12, 0, 128]] * 15, np.float64)
    cap = kinds[np.arange(b) % 20]
    used = np.floor(cap * rng.uniform(0.0, 0.9, size=(b, m)))
    used[rng.random(b) < 0.5] = 0.0            # half the slaves stay empty
    free = cap - used
    inv_cap = np.where(cap > 0, 1.0 / np.maximum(cap, 1e-12), 0.0)
    d = np.array([[1, 0, 4], [2, 1, 16], [0.5, 0, 2.5], [4, 0, 8],
                  [1.25, 0, 3.5]], np.float64)
    x = np.zeros((d.shape[0], b), np.int64)
    x[1, :3] = 1                               # app 1 starts with 3
    items = SCHEDULES[k]
    np_be, jx_be = _backends()
    x_r, free_r = x.copy(), free.copy()
    x_g, free_g = x.copy(), free.copy()
    ref = np_be.place_run(x_r, free_r, d, inv_cap, items)
    got = jx_be.place_run(x_g, free_g, d, inv_cap, items)
    assert got == ref
    assert sum(ref) > 0
    np.testing.assert_array_equal(x_g, x_r)
    np.testing.assert_array_equal(free_g, free_r)


def test_compile_cache_location(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` wins and no other location is set;
    without it the cache sits in `.jax_cache/` at the repository root."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = configure_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
