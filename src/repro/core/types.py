"""Core datatypes for the Dorm cluster-management system.

Mirrors the paper's §III definitions:
  * a *resource vector* over m resource types (e.g. <CPU, GPU, RAM-GB>),
  * a *container* -- a logical bundle of resources on a server,
  * the 6-tuple application submission spec (executor, d, w, n_max, n_min, cmd),
  * cluster / slave capacity descriptions,
  * an *allocation*: x[i, j] = number of containers of app i on slave j.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .goodput import GoodputCurve

# Canonical resource-type names for the paper's testbed (m = 3).
DEFAULT_RESOURCE_TYPES: Tuple[str, ...] = ("cpu", "gpu", "ram")


@dataclasses.dataclass(frozen=True)
class ResourceVector:
    """An m-dimensional non-negative resource quantity."""

    values: Tuple[float, ...]

    def __post_init__(self):
        if any(v < 0 for v in self.values):
            raise ValueError(f"resource vector must be non-negative: {self.values}")

    @staticmethod
    def of(*values: float) -> "ResourceVector":
        return ResourceVector(tuple(float(v) for v in values))

    @property
    def m(self) -> int:
        return len(self.values)

    def as_array(self) -> np.ndarray:
        """Cached read-only view -- this is called per container on hot
        scheduling paths; callers must not mutate the result."""
        arr = self.__dict__.get("_arr")
        if arr is None:
            arr = np.asarray(self.values, dtype=np.float64)
            arr.flags.writeable = False
            object.__setattr__(self, "_arr", arr)
        return arr

    def __add__(self, other: "ResourceVector") -> "ResourceVector":
        return ResourceVector(tuple(a + b for a, b in zip(self.values, other.values)))

    def __sub__(self, other: "ResourceVector") -> "ResourceVector":
        return ResourceVector(tuple(a - b for a, b in zip(self.values, other.values)))

    def __mul__(self, k: float) -> "ResourceVector":
        return ResourceVector(tuple(a * k for a in self.values))

    __rmul__ = __mul__

    def fits_in(self, other: "ResourceVector") -> bool:
        return all(a <= b + 1e-9 for a, b in zip(self.values, other.values))

    def __iter__(self):
        return iter(self.values)


@dataclasses.dataclass(frozen=True)
class ApplicationSpec:
    """The paper's 6-tuple: (executor, d, w, n_max, n_min, cmd)."""

    app_id: str
    executor: str                     # e.g. "MxNet", "TensorFlow", "MPI-Caffe", "Petuum"
    demand: ResourceVector            # d: per-container resource demand
    weight: int = 1                   # w
    n_max: int = 1
    n_min: int = 1
    cmd: Tuple[str, ...] = ("start.sh", "resume.sh")
    # Extra (not in the 6-tuple, used by the simulator / live integration):
    model: str = ""                   # e.g. "VGG-16"; or an assigned arch id
    serial_work: float = 0.0          # total work units; duration = work / n_containers
    submit_time: float = 0.0
    # Serving lifetime: when > 0 the app is a SERVICE -- it completes after
    # this many seconds of being up (containers > 0), independent of its
    # container count (extra containers add serving capacity, they do not
    # finish the app sooner). 0 = work-based batch job (the default).
    service_s: float = 0.0
    # Speedup model: None (default) = exact-linear goodput(N) = N, the
    # seed's bit-exact work accounting. A `GoodputCurve` makes progress
    # follow goodput(N) instead (diminishing returns) and lets the
    # optimizer target the curve's knee -- see `core.goodput`.
    goodput: Optional[GoodputCurve] = None

    def speedup(self, n: int) -> float:
        """Progress rate at n containers in container-equivalents: n under
        the linear model, goodput(n) with a curve attached."""
        if self.goodput is None:
            return float(n)
        return self.goodput.at(n)

    def __post_init__(self):
        if self.n_min < 1 or self.n_max < self.n_min:
            raise ValueError(
                f"require 1 <= n_min <= n_max, got [{self.n_min}, {self.n_max}]")
        if self.weight < 1:
            raise ValueError("weight must be >= 1")

    def with_bounds(self, n_min: Optional[int] = None,
                    n_max: Optional[int] = None) -> "ApplicationSpec":
        """Copy with new elasticity bounds (runtime `Resize` events re-bound
        an app mid-flight; None keeps the existing bound).

        Moving one bound past the other clamps the unspecified bound so
        1 <= n_min <= n_max always holds (capping n_max below the current
        n_min also lowers n_min, and vice versa); explicitly passing an
        inconsistent pair raises."""
        new_min = self.n_min if n_min is None else max(1, int(n_min))
        new_max = self.n_max if n_max is None else max(1, int(n_max))
        if n_min is None:
            new_min = min(new_min, new_max)
        if n_max is None:
            new_max = max(new_max, new_min)
        return dataclasses.replace(self, n_min=new_min, n_max=new_max)


@dataclasses.dataclass(frozen=True)
class SlaveSpec:
    """A DormSlave: one cluster server with a resource capacity c_j."""

    slave_id: str
    capacity: ResourceVector


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    """The whole cluster: resource types + the set of DormSlaves."""

    resource_types: Tuple[str, ...]
    slaves: Tuple[SlaveSpec, ...]

    @property
    def m(self) -> int:
        return len(self.resource_types)

    @property
    def b(self) -> int:
        return len(self.slaves)

    def capacity_matrix(self) -> np.ndarray:
        """(b, m) per-slave capacities (cached, read-only: stacking 1000
        slave vectors per call would dominate large-cluster scheduling)."""
        cm = self.__dict__.get("_cap_matrix")
        if cm is None:
            cm = np.stack([s.capacity.as_array() for s in self.slaves])
            cm.flags.writeable = False
            object.__setattr__(self, "_cap_matrix", cm)
        return cm

    def total_capacity(self) -> np.ndarray:
        """(m,) cluster-wide capacity  sum_h c_{h,k} (cached, read-only)."""
        tc = self.__dict__.get("_total_cap")
        if tc is None:
            tc = self.capacity_matrix().sum(axis=0)
            tc.flags.writeable = False
            object.__setattr__(self, "_total_cap", tc)
        return tc

    @staticmethod
    def homogeneous(n_slaves: int, capacity: ResourceVector,
                    resource_types: Sequence[str] = DEFAULT_RESOURCE_TYPES,
                    ) -> "ClusterSpec":
        return ClusterSpec(
            resource_types=tuple(resource_types),
            slaves=tuple(
                SlaveSpec(slave_id=f"slave-{j}", capacity=capacity)
                for j in range(n_slaves)),
        )


class Allocation:
    """x[i, j]: containers of application i on slave j (paper Table I).

    Two forms hold the same numbers. The dense form keeps the (n_apps, b)
    int64 matrix `x`; its `rows` are read-only views of it. The row form
    (`from_rows`) keeps one read-only 1-D int64 row per app, shared by
    reference with the allocations it was derived from, so an app whose
    placement did not change costs a pointer instead of b copied counts.
    Reading `x` on a row form stacks the rows once and caches the matrix,
    read-only; the class counter `densified` counts those stackings (the
    master's delta path never moves it). Rows are never written."""

    __slots__ = ("app_ids", "_x", "_rows")
    densified = 0

    def __init__(self, app_ids: Tuple[str, ...], x: np.ndarray):
        x = np.asarray(x, dtype=np.int64)
        if x.shape[0] != len(app_ids):
            raise ValueError("x rows must match app_ids")
        if (x < 0).any():
            raise ValueError("allocations must be non-negative")
        self.app_ids = app_ids
        self._x = x
        self._rows: Optional[Tuple[np.ndarray, ...]] = None

    @classmethod
    def trusted(cls, app_ids: Tuple[str, ...], x: np.ndarray) -> "Allocation":
        """Construct without the __init__ scans, for hot paths whose
        `x` is already a non-negative int64 matrix (rows gathered from a
        validated allocation or the SoA state). The full-matrix negativity
        scan costs O(n*b) per event at cluster scale."""
        out = cls.__new__(cls)
        out.app_ids = app_ids
        out._x = x
        out._rows = None
        return out

    @classmethod
    def from_rows(cls, app_ids: Tuple[str, ...],
                  rows: Tuple[np.ndarray, ...],
                  b: Optional[int] = None) -> "Allocation":
        """Row form, trusted like `trusted`: `rows` holds one read-only
        non-negative int64 row of length b per app, kept by reference.
        `b`, the slave count, is used only when there are no rows."""
        if len(rows) != len(app_ids):
            raise ValueError("rows must match app_ids")
        if not rows and b is None:
            raise ValueError("an allocation with no rows needs b")
        out = cls.__new__(cls)
        out.app_ids = app_ids
        out._rows = rows
        out._x = None if rows else np.zeros((0, b), np.int64)
        return out

    @property
    def x(self) -> np.ndarray:
        if self._x is None:
            x = np.stack(self._rows)
            x.flags.writeable = False
            self._x = x
            Allocation.densified += 1
        return self._x

    @property
    def rows(self) -> Tuple[np.ndarray, ...]:
        if self._rows is None:
            view = self._x.view()
            view.flags.writeable = False
            self._rows = tuple(view)
        return self._rows

    @property
    def b(self) -> int:
        return self._x.shape[1] if self._x is not None else \
            self._rows[0].shape[0]

    def row_at(self, i: int) -> np.ndarray:
        """App i's row, read-only, without stacking a row form."""
        if self._rows is not None:
            return self._rows[i]
        row = self._x[i]
        row.flags.writeable = False
        return row

    def take(self, keep: Sequence[int]) -> "Allocation":
        """The apps at positions `keep`, in that order, sharing their
        rows with this allocation."""
        rows = self.rows
        return Allocation.from_rows(tuple(self.app_ids[i] for i in keep),
                                    tuple(rows[i] for i in keep), self.b)

    def containers_of(self, app_id: str) -> int:
        return int(self.row(app_id).sum())

    def row(self, app_id: str) -> np.ndarray:
        return self.row_at(self.app_ids.index(app_id))

    def as_dict(self) -> Dict[str, np.ndarray]:
        return {a: self.row_at(i).copy() for i, a in enumerate(self.app_ids)}

    def __repr__(self) -> str:
        return f"Allocation(app_ids={self.app_ids!r}, x={self.x!r})"

    @staticmethod
    def empty(app_ids: Sequence[str], b: int) -> "Allocation":
        return Allocation(tuple(app_ids), np.zeros((len(app_ids), b), np.int64))


def demand_matrix(apps: Sequence[ApplicationSpec]) -> np.ndarray:
    """(n_apps, m) per-container demand d_{i,k}."""
    if not apps:
        return np.zeros((0, 0))
    return np.stack([a.demand.as_array() for a in apps])


def validate_allocation(alloc: Allocation, apps: Sequence[ApplicationSpec],
                        cluster: ClusterSpec,
                        enforce_n_min: bool = True,
                        d: Optional[np.ndarray] = None) -> None:
    """Raise if an allocation violates capacity (Eq 6) or bounds (Eqs 7-9).
    `d`: optionally reuse a precomputed demand matrix (hot solver paths)."""
    if not apps:
        if alloc.x.size:
            raise ValueError("allocation rows for zero apps")
        return
    if d is None:
        d = demand_matrix(apps)                # (n, m)
    cap = cluster.capacity_matrix()            # (b, m)
    # float64 matmul: BLAS path (int64 matmul is a slow loop), exact for
    # container counts/demands far below 2**53.
    used = alloc.x.astype(np.float64).T @ d    # (b, m)
    if (used > cap + 1e-6).any():
        j, k = np.argwhere(used > cap + 1e-6)[0]
        raise ValueError(
            f"capacity violated on slave {j} resource {k}: {used[j, k]} > {cap[j, k]}")
    totals = alloc.x.sum(axis=1)
    n = len(apps)
    nmax = np.fromiter((a.n_max for a in apps), np.int64, n)
    over = totals > nmax
    if over.any():
        i = int(np.flatnonzero(over)[0])
        raise ValueError(
            f"{apps[i].app_id}: {totals[i]} > n_max={apps[i].n_max}")
    if enforce_n_min:
        nmin = np.fromiter((a.n_min for a in apps), np.int64, n)
        under = totals < nmin
        if under.any():
            i = int(np.flatnonzero(under)[0])
            raise ValueError(
                f"{apps[i].app_id}: {totals[i]} < n_min={apps[i].n_min}")
