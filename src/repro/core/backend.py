"""Array-backend seam for the scheduler's hot kernels (PR 6).

The per-event allocation inner loops -- the ladder-DRF progressive fill
(`drf.drf_container_counts`), the saturating probe (`drf.saturating_counts`)
and the batched best-fit scatter (`optimizer._best_fit_place_batch`) -- are
pure array programs over `ClusterState`'s SoA buffers. This module puts an
explicit seam under them:

  * `NumpyBackend`  -- the host implementation, EXTRACTED (not rewritten)
    from the previous in-place code, so it is bit-identical with the seed
    by construction. It stays the bit-exactness reference, exactly like
    `ReferenceClusterSimulator` does for the simulator.
  * `JaxBackend`    -- the same three kernels as `jax.jit` programs built
    on `lax` (stable argsort + clipped-cumsum scatter, `lax.scan` for the
    inherently sequential grant loop, `lax.while_loop` for the ladder's
    exhaustion passes). On a TPU the placement inner loop is the compiled
    Pallas kernel in `repro.kernels.placement`; on other platforms it is
    the lax argsort composition. jax is a hard dependency: asking for this
    backend where jax cannot run raises.
  * `AutoBackend`   -- `backend="auto"`: problem-size dispatch between the
    two, numpy below the measured crossover (AUTO_CROSSOVER_*), jax above.

PR 7 adds `place_run`: the whole multi-app placement loop of one solver
pass as ONE backend program (one jit'd `lax.scan` over the batch schedule
on jax, one fused pass on numpy), so a storm-absorbed event flood costs
one device dispatch instead of one per app.

Static shapes + padding contract
--------------------------------
jit caches are keyed on shapes, so every entry point pads its inputs to the
next power of two before dispatch and slices the result back:

  * apps axis `n`    -> padded with zero-demand rows (`valid` mask False),
  * slaves axis `b`  -> padded with `free = -1` sentinel rows (nothing fits)
    and `inv_cap = 0`,
  * ladder levels    -> padded to the max `n_max` (entries above an app's
    bound are masked to +inf and never granted).

A steady-state cluster therefore compiles each kernel ONCE per padded-shape
bucket; subsequent events reuse the trace. jax's own compile events are
counted per program name (`telemetry.compile_counter()`; a dispatch here
books its compile as `dorm.<program>`); `Backend.compile_s` reads that
count, and `DormMaster.phase_breakdown()` / `PolicyTimer` report
it as `backend_compile`.

Spans
-----
`JaxBackend.place_run`, `ladder_counts` and `saturating_probe` record into
the owner's `telemetry.Spans`: `backend.<program>.prep` (padding and
schedule arrays), `.dispatch` (the jitted call until it returns), `.wait`
(the blocking copy of the output to the host, which holds the device time)
and, for `place_run`, `.apply` (grants written into `x` and `free`).

Exactness
---------
Integer outputs (container counts, placements) are compared bit-for-bit in
the parity suite (tests/test_backend_parity.py). For integral demands every
float intermediate is exact integer arithmetic, so numpy and jax agree
bitwise unconditionally. For fractional demands the kernels keep numpy's
float op ORDER wherever the op is sequential (scan = the python grant loop,
unrolled per-resource sums = numpy's pairwise order for m <= 8) and rely on
the 1e-9 decision epsilons dominating last-ulp reduction noise elsewhere
(cumsum); the parity suite pins the resulting counts/placements equality
empirically, fractional demands included.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .telemetry import Spans, compile_counter

_EPS = 1e-9

# --------------------------------------------------------------------------
# numpy kernel bodies (extracted verbatim from drf.py / optimizer.py)
# --------------------------------------------------------------------------


def _probe_np(d: np.ndarray, n_max: np.ndarray, total: np.ndarray) -> bool:
    """sum_i n_max_i * d_i <= total  (drf.saturating_counts' aggregate test)."""
    return bool(np.all(n_max.astype(np.float64) @ d <= total + _EPS))


def _ladder_counts_np(d: np.ndarray, n_min: np.ndarray, n_max: np.ndarray,
                      w: np.ndarray, total: np.ndarray) -> np.ndarray:
    """Vectorized weighted-DRF progressive filling over plain arrays.

    The array core of `drf.drf_container_counts` (see its docstring for the
    ladder argument); that function now builds the arrays from the specs and
    delegates here."""
    n = d.shape[0]
    pos = total > 0

    def shares_at(counts: np.ndarray) -> np.ndarray:
        with np.errstate(divide="ignore", invalid="ignore"):
            ratios = np.where(pos[None, :],
                              (counts[:, None] * d) / total[None, :], 0.0)
        return (ratios.max(axis=1) if ratios.size else np.zeros(n)) / w

    # Phase 1 -- guarantee n_min, in DRF (smallest weighted share) order.
    cnt = np.zeros(n, np.int64)
    remaining = total.copy()
    need = n_min[:, None] * d                                   # (n, m)
    if np.all(need.sum(axis=0) <= remaining + _EPS):
        # Common case: every minimum fits in aggregate -- grant all at once.
        cnt[:] = n_min
        remaining -= need.sum(axis=0)
    else:
        for i in np.argsort(shares_at(n_min), kind="stable"):
            if np.all(need[i] <= remaining + _EPS):
                cnt[i] = n_min[i]
                remaining -= need[i]

    # Phase 2 -- progressive filling above n_min: sorted ladder of per-grant
    # shares for every app that received its minimum.
    active = np.flatnonzero(cnt > 0)
    lengths = np.maximum(n_max[active] - cnt[active], 0)
    total_e = int(lengths.sum())
    if total_e:
        i_arr = np.repeat(active, lengths)
        offsets = np.concatenate(([0], np.cumsum(lengths[:-1])))
        c_arr = (np.arange(total_e)
                 - np.repeat(offsets, lengths)
                 + np.repeat(cnt[active], lengths))
        with np.errstate(divide="ignore", invalid="ignore"):
            ratios = np.where(pos[None, :],
                              (c_arr[:, None] * d[i_arr]) / total[None, :],
                              0.0)
        keys = ratios.max(axis=1) / w[i_arr]
        order_e = np.lexsort((i_arr, keys))
        i_s = i_arr[order_e]
        d_s = d[i_s]
        dropped = np.zeros(n, bool)
        while i_s.size:
            cum = np.cumsum(d_s, axis=0)
            ok = (cum <= remaining[None, :] + _EPS).all(axis=1)
            k = int(i_s.size if ok.all() else np.argmin(ok))
            if k:
                cnt += np.bincount(i_s[:k], minlength=n)
                remaining = remaining - cum[k - 1]
            if k == i_s.size:
                break
            # Retire every app that can no longer fit one container (the
            # blocked app among them); their remaining ladder entries drop.
            dropped |= ~(d <= remaining[None, :] + _EPS).all(axis=1)
            keep = ~dropped[i_s[k:]]
            i_s = i_s[k:][keep]
            d_s = d_s[k:][keep]
    return cnt


def _place_counts_np(free: np.ndarray, di: np.ndarray, inv_cap: np.ndarray,
                     need: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Batched best-fit slave counts for one app (the compute half of
    `optimizer._best_fit_place_batch`; the caller applies the mutation).

    -> (slave indices, per-slave grant counts) with counts > 0, in placement
    order, or None when no slave fits."""
    fit_js = np.flatnonzero((di <= free + _EPS).all(axis=1))
    if not fit_js.size:
        return None
    sub_free = free[fit_js]
    pos = di > 0
    if pos.any():
        q = np.floor((sub_free[:, pos] + _EPS) / di[pos]).min(axis=1)
        q = np.maximum(q, 1.0).astype(np.int64)     # max containers per slave
    else:
        q = np.full(fit_js.shape[0], need, np.int64)   # zero demand
    score = ((sub_free - di) * inv_cap[fit_js]).sum(axis=1)
    # Fast path: the best-fit slave hosts the whole batch (one argmin
    # instead of a full argsort -- the sequential loop would fill the
    # argmin slave first anyway).
    jpos = int(np.argmin(score))
    if q[jpos] >= need:
        return (fit_js[jpos:jpos + 1],
                np.array([need], dtype=np.int64))
    order = np.argsort(score, kind="stable")        # ties -> lowest index
    js = fit_js[order]
    csum = np.minimum(np.cumsum(q[order]), need)
    counts = np.diff(np.concatenate(([0], csum)))
    nz = counts > 0
    return js[nz], counts[nz]


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------


class Backend:
    """Ops protocol + the three scheduler kernels.

    The small-ops layer (argsort/cumsum/segment-sum/masked-select/cumfill)
    is what the kernels are composed from; it is exposed so future device-
    resident passes (the sharded multi-master plane) can build on the same
    seam without growing the kernel surface ad hoc."""

    name: str = "abstract"
    compile_s: float = 0.0       # cumulative jit compile time (jax only)

    def __init__(self, spans: Optional[Spans] = None):
        self.spans = spans       # the owner's span registry (jax only)

    # ---- ops protocol (host-array in, host-array out)
    def argsort(self, keys: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def cumsum(self, a: np.ndarray, axis: int = 0) -> np.ndarray:
        raise NotImplementedError

    def segment_sum(self, values: np.ndarray, segments: np.ndarray,
                    n_segments: int) -> np.ndarray:
        raise NotImplementedError

    def masked_select(self, mask: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def cumfill(self, q: np.ndarray, budget: int) -> np.ndarray:
        """Greedy prefix fill: grant min(q_i, what's left of `budget`) in
        order -- diff(min(cumsum(q), budget)). The placement scatter's
        core op."""
        raise NotImplementedError

    # ---- scheduler kernels
    def saturating_probe(self, d: np.ndarray, n_max: np.ndarray,
                         total: np.ndarray) -> bool:
        raise NotImplementedError

    def ladder_counts(self, d: np.ndarray, n_min: np.ndarray,
                      n_max: np.ndarray, weight: np.ndarray,
                      total: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def place_counts(self, free: np.ndarray, di: np.ndarray,
                     inv_cap: np.ndarray, need: int,
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """-> (slave indices, grant counts > 0) or None when nothing fits.

        The PAIRING is the contract; the order of the pairs is not (numpy
        yields fill order, jax ascending slave index -- the `place` update
        is order-independent because indices are unique). Compare results
        as the dense per-slave mapping."""
        raise NotImplementedError

    def place(self, x: np.ndarray, free: np.ndarray, d: np.ndarray,
              inv_cap: np.ndarray, i: int, limit: int) -> bool:
        """Mutating wrapper with `optimizer._best_fit_place_batch`'s exact
        signature and update arithmetic; returns True iff a grant landed."""
        di = d[i]
        need = limit - int(x[i].sum())
        if need <= 0:
            return False
        out = self.place_counts(free, di, inv_cap, need)
        if out is None:
            return False
        js, counts = out
        x[i, js] += counts
        free[js] -= counts[:, None].astype(np.float64) * di[None, :]
        return True

    def place_run(self, x: np.ndarray, free: np.ndarray, d: np.ndarray,
                  inv_cap: np.ndarray,
                  items: Sequence[Tuple[int, int]]) -> List[int]:
        """Fused multi-app placement: execute a whole placement SCHEDULE --
        ordered (app row, count limit) pairs, exactly the visits the
        optimizer's two best-fit passes would make -- in one backend call,
        mutating `x`/`free` in place.

        -> per-item granted container totals (0 = nothing placed), in
        schedule order. Sequential semantics are the contract: item k sees
        the free capacity left by items 0..k-1, and an app appearing twice
        (n_min pass then target pass) sees its own earlier grants. The base
        implementation is the literal sequential loop (bit-identical with
        per-item `place` calls by construction); `JaxBackend` overrides it
        with a single jitted program so the host dispatches once per SOLVE
        instead of once per app."""
        grants: List[int] = []
        for i, limit in items:
            di = d[i]
            need = limit - int(x[i].sum())
            if need <= 0:
                grants.append(0)
                continue
            out = self.place_counts(free, di, inv_cap, need)
            if out is None:
                grants.append(0)
                continue
            js, counts = out
            x[i, js] += counts
            free[js] -= counts[:, None].astype(np.float64) * di[None, :]
            grants.append(int(counts.sum()))
        return grants


class NumpyBackend(Backend):
    """Host reference backend (the extracted seed implementation)."""

    name = "numpy"

    def argsort(self, keys):
        return np.argsort(keys, kind="stable")

    def cumsum(self, a, axis: int = 0):
        return np.cumsum(a, axis=axis)

    def segment_sum(self, values, segments, n_segments: int):
        return np.bincount(segments, weights=values, minlength=n_segments)

    def masked_select(self, mask):
        return np.flatnonzero(mask)

    def cumfill(self, q, budget: int):
        csum = np.minimum(np.cumsum(q), budget)
        return np.diff(np.concatenate(([0], csum)))

    def saturating_probe(self, d, n_max, total) -> bool:
        return _probe_np(d, n_max, total)

    def ladder_counts(self, d, n_min, n_max, weight, total):
        return _ladder_counts_np(d, n_min, n_max, weight, total)

    def place_counts(self, free, di, inv_cap, need):
        return _place_counts_np(free, di, inv_cap, int(need))


# ---------------------------------------------------------------- jax side

def _jax_modules():
    """jax is a hard dependency of the jax engine: a missing or broken
    install raises here instead of degrading to another backend."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    return jax, jnp, lax, jax.enable_x64


# <repo>/.jax_cache: a fixed path, because the path is part of the
# persistent cache's key (a per-run directory would never hit).
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; entry points call this
    before their first compile. Where `JAX_COMPILATION_CACHE_DIR` is set,
    jax reads it and no other location is set here; otherwise the cache
    lives in `.jax_cache/` at the repository root. -> the cache directory."""
    jax = _jax_modules()[0]
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # The scheduler's programs compile in about a second each on a TPU,
    # under jax's default one-second floor for caching a program.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _pow2(n: int) -> int:
    return 1 << max(3, int(n - 1).bit_length()) if n > 1 else 8


_JAX_FNS: Dict[bool, Dict[str, object]] = {}


def _build_jax_fns(use_pallas: bool) -> Dict[str, object]:
    """Build (once per process and pallas-flag) the jitted kernel programs.

    All float work is f64 (callers wrap invocations in `enable_x64`); the
    Pallas kernel inside `place` orders slaves by an exact f32 split of the
    f64 score -- see `repro.kernels.placement`."""
    if use_pallas in _JAX_FNS:
        return _JAX_FNS[use_pallas]
    jax, jnp, lax, _ = _jax_modules()

    def rounded(prod):
        """`prod` rounded on its own before it meets an add, as numpy
        rounds it. XLA's CPU compiler otherwise contracts a product and
        the next add into one FMA (a single rounding), which breaks ties
        in the best-fit score differently from numpy. It does not contract
        through a select, and no product here is NaN."""
        return jnp.where(jnp.isnan(prod), 0.0, prod)

    @jax.jit
    def probe(d, n_max, total):
        with jax.named_scope("dorm.probe"):
            return jnp.all(n_max @ d <= total + _EPS)

    def place_core(free, di, inv_cap, need_i):
        """-> dense (b,) int64 grant counts (0 on non-granted slaves).

        Equals numpy's argsort/cumfill scatter: the argmin fast path needs
        no separate branch (a slave whose q covers `need` and whose
        (score, index) key sorts first receives the whole batch from the
        clipped cumsum too), and clipping q at `need` before the cumsum
        never changes diff(min(cumsum, need)) while keeping the int64 sums
        small enough for the Pallas kernel's int32 accumulators. `need_i`
        may be 0 (a no-op schedule entry inside `place_run`): every q is
        then clipped to 0 and no slave is granted."""
        b, m = free.shape
        need_f = need_i.astype(free.dtype)
        # Per-resource ops are unrolled over the static m (<= 8 in this
        # repo), keeping numpy's left-to-right pairwise order bit-for-bit.
        fit = di[0] <= free[:, 0] + _EPS
        for k in range(1, m):
            fit = fit & (di[k] <= free[:, k] + _EPS)
        q = None
        for k in range(m):
            qk = jnp.where(di[k] > 0.0,
                           jnp.floor((free[:, k] + _EPS)
                                     / jnp.where(di[k] > 0.0, di[k], 1.0)),
                           jnp.inf)
            q = qk if q is None else jnp.minimum(q, qk)
        q = jnp.where(jnp.isfinite(q), q, need_f)   # all-zero demand
        q = jnp.maximum(q, 1.0)
        q = jnp.minimum(q, need_f)
        qn = jnp.where(fit, q, 0.0).astype(jnp.int64)
        score = rounded((free[:, 0] - di[0]) * inv_cap[:, 0])
        for k in range(1, m):
            score = score + rounded((free[:, k] - di[k]) * inv_cap[:, k])
        masked = jnp.where(fit, score, jnp.inf)
        if use_pallas:
            from ..kernels.placement import best_fit_counts
            counts = best_fit_counts(masked, qn.astype(jnp.int32),
                                     need_i.astype(jnp.int32),
                                     interpret=False)
            return counts.astype(jnp.int64)
        order = jnp.argsort(masked, stable=True)    # ties -> lowest index
        csum = jnp.minimum(jnp.cumsum(qn[order]), need_i)
        counts = csum - jnp.concatenate([jnp.zeros(1, jnp.int64), csum[:-1]])
        return jnp.zeros(b, jnp.int64).at[order].set(counts)

    @jax.jit
    def place(free, di, inv_cap, need):
        with jax.named_scope("dorm.place"):
            return place_core(free, di, inv_cap, need.astype(jnp.int64))

    @jax.jit
    def place_run(free0, inv_cap, d_items, lims, bases, aslots):
        """Fused multi-app placement: ONE device program executes a whole
        (app, limit) placement schedule -- the per-app `place` body inside
        a lax.scan carrying the free-capacity matrix -- so the host
        dispatches once per SOLVE instead of once per app (and on TPUs the
        Pallas placement kernel runs inside this single program).

        Schedule entry k: demand row d_items[k], count limit lims[k], the
        app's container total before this run bases[k], and aslots[k] = the
        most recent earlier entry of the SAME app (-1 if none) -- totals
        are chained through that link so need = lim - base - already
        granted, exactly the sequential `x[i].sum()` recomputation.
        Zero-padded entries (need 0) provably leave the carry unchanged
        (0 * d subtracts exact zeros), preserving bit-exactness."""
        K = d_items.shape[0]

        def body(carry, inp):
            free, totals = carry
            di, lim, base, aslot, k = inp
            prev = jnp.where(aslot >= 0,
                             totals[jnp.maximum(aslot, 0)],
                             jnp.int64(0))
            need = jnp.maximum(lim - base - prev, 0)
            counts = place_core(free, di, inv_cap, need)
            free = free - rounded(counts[:, None].astype(free.dtype)
                                  * di[None, :])
            totals = totals.at[k].set(prev + counts.sum())
            return (free, totals), counts

        with jax.named_scope("dorm.place_run"):
            totals0 = jnp.zeros(K, jnp.int64)
            ks = jnp.arange(K, dtype=jnp.int64)
            (_, _), grants = lax.scan(
                body, (free0, totals0), (d_items, lims, bases, aslots, ks))
        return grants

    def ladder_fill(d, n_min, n_max, w, valid, total, levels):
        """Vectorized weighted-DRF ladder fill, masked instead of compacted.

        numpy compacts the ladder (drops granted/retired entries); here the
        grid is static (n_pad, L) and dead entries carry zero demand in the
        cumulative sums -- partial sums over the survivors are unchanged, so
        every capacity decision matches the compacted version exactly."""
        n_pad, m = d.shape
        L = levels.shape[0]
        E = n_pad * L
        pos = total > 0.0
        safe_total = jnp.where(pos, total, 1.0)

        def shares_at(counts_f):
            r = jnp.where(pos[None, :],
                          (counts_f[:, None] * d) / safe_total[None, :], 0.0)
            return r.max(axis=1) / w

        n_min_f = n_min.astype(d.dtype)
        need = rounded(n_min_f[:, None] * d)               # zero on pad rows
        tot_need = need.sum(axis=0)
        all_fit = jnp.all(tot_need <= total + _EPS)

        # Sequential phase 1 (selected when all_fit is False): lax.scan
        # replays numpy's python grant loop in the same DRF order, so the
        # capacity subtractions happen in the same sequence bit-for-bit.
        order1 = jnp.argsort(jnp.where(valid, shares_at(n_min_f), jnp.inf),
                             stable=True)

        def p1(rem, i):
            ok = valid[i] & jnp.all(need[i] <= rem + _EPS)
            return jnp.where(ok, rem - need[i], rem), ok

        rem_seq, ok_seq = lax.scan(p1, total, order1)
        granted = jnp.zeros(n_pad, bool).at[order1].set(ok_seq)
        cnt = jnp.where(all_fit, jnp.where(valid, n_min, 0),
                        jnp.where(granted, n_min, 0))
        remaining = jnp.where(all_fit, total - tot_need, rem_seq)

        # Phase 2: full (n_pad, L) grid of per-grant share keys, flattened
        # i-major -- the same order numpy's lexsort((i_arr, keys)) yields.
        active = cnt > 0
        c_abs = cnt[:, None] + levels[None, :]             # (n_pad, L)
        e_valid = (active[:, None] & valid[:, None]
                   & (c_abs < n_max[:, None]))
        keys_g = (jnp.where(pos[None, None, :],
                            (c_abs[..., None].astype(d.dtype)
                             * d[:, None, :]) / safe_total[None, None, :],
                            0.0).max(axis=2) / w[:, None])
        keys = jnp.where(e_valid, keys_g, jnp.inf).ravel()
        order_e = jnp.argsort(keys, stable=True)
        i_s = order_e // L
        d_s = d[i_s]                                       # (E, m)
        alive0 = e_valid.ravel()[order_e]
        arange_e = jnp.arange(E)

        def body(st):
            cnt, rem, alive, _ = st
            d_eff = jnp.where(alive[:, None], d_s, 0.0)
            # The parallel-prefix form of cumsum: XLA's TPU compiler takes
            # minutes over the reduce-window one in f64.
            cum = lax.associative_scan(jnp.add, d_eff, axis=0)
            ok = jnp.all(cum <= rem[None, :] + _EPS, axis=1)
            bad = alive & ~ok
            any_bad = bad.any()
            kpos = jnp.where(any_bad, jnp.argmax(bad), E)
            grant = alive & (arange_e < kpos)
            ngrant = grant.sum()
            sub = cum[jnp.maximum(kpos - 1, 0)]
            rem2 = jnp.where(ngrant > 0, rem - sub, rem)
            cnt2 = cnt + jnp.zeros_like(cnt).at[i_s].add(
                grant.astype(cnt.dtype))
            alive2 = alive & ~grant
            # Retire apps that can no longer fit one container; when no
            # entry was blocked everything was granted and the loop ends.
            fits = jnp.all(d <= rem2[None, :] + _EPS, axis=1)
            alive3 = jnp.where(any_bad, alive2 & fits[i_s], alive2)
            done = (~any_bad) | (~alive3.any())
            return (cnt2, rem2, alive3, done)

        init = (cnt, remaining, alive0, ~alive0.any())
        cnt_f, _, _, _ = lax.while_loop(lambda st: ~st[3], body, init)
        return cnt_f

    @jax.jit
    def ladder(d, n_min, n_max, w, valid, total, levels):
        with jax.named_scope("dorm.ladder"):
            return ladder_fill(d, n_min, n_max, w, valid, total, levels)

    _JAX_FNS[use_pallas] = {"probe": probe, "place": place,
                            "place_run": place_run, "ladder": ladder}
    return _JAX_FNS[use_pallas]


class JaxBackend(Backend):
    """jax.jit backend; see the module docstring for the padding contract.

    The placement inner loop is the compiled Pallas kernel on a TPU and the
    lax argsort composition on any other platform (`use_pallas=None`).
    `use_pallas=True` off a TPU raises: the kernel is never interpreted
    here. `use_pallas` records which of the two runs."""

    name = "jax"

    def __init__(self, use_pallas: Optional[bool] = None,
                 spans: Optional[Spans] = None):
        jax, jnp, _, enable_x64 = _jax_modules()
        on_tpu = jax.default_backend() == "tpu"
        if use_pallas is None:
            use_pallas = on_tpu
        if use_pallas and not on_tpu:
            raise RuntimeError(
                "the Pallas placement kernel needs a TPU; jax's default "
                f"backend is {jax.default_backend()!r}")
        self.use_pallas = bool(use_pallas)
        self._jax, self._jnp = jax, jnp
        self._x64 = enable_x64
        self._fns = _build_jax_fns(self.use_pallas)
        self.spans = spans if spans is not None else Spans()
        self._compiles = compile_counter()
        # tag -> [(shape, dtype)] of the first call: what `compiled_text`
        # lowers again.
        self._shapes: Dict[str, List[Tuple[tuple, str]]] = {}

    @property
    def compile_s(self) -> float:
        """Seconds jax spent compiling (or loading from its persistent
        cache) this engine's programs, booked as `dorm.<program>`, in this
        process; the jit caches are process-wide, so is this count."""
        sec = self._compiles.seconds
        return sum(sec.get("dorm." + tag, 0.0) for tag in self._fns)

    def _run(self, tag: str, *args):
        """Dispatch program `tag` (f64 on); returns without waiting. A
        compile on the way is counted as `dorm.<tag>`."""
        if tag not in self._shapes:
            self._shapes[tag] = [(a.shape, str(a.dtype)) for a in args]
        owner = self._compiles.owner
        owner.name = "dorm." + tag
        try:
            with self._x64():
                return self._fns[tag](*args)
        finally:
            owner.name = None

    def compiled_text(self, tag: str) -> str:
        """Optimized HLO of kernel `tag` at the first padded shapes it ran
        with (e.g. to check that `place_run` holds the Pallas kernel)."""
        jax, jnp = self._jax, self._jnp
        with self._x64():
            specs = [jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
                     for shape, dtype in self._shapes[tag]]
            return self._fns[tag].lower(*specs).compile().as_text()

    # ---- ops protocol (jnp on host arrays; f64 via the x64 scope)
    def argsort(self, keys):
        with self._x64():
            return np.asarray(self._jnp.argsort(self._jnp.asarray(keys),
                                                stable=True))

    def cumsum(self, a, axis: int = 0):
        with self._x64():
            return np.asarray(self._jnp.cumsum(self._jnp.asarray(a),
                                               axis=axis))

    def segment_sum(self, values, segments, n_segments: int):
        jnp = self._jnp
        with self._x64():
            vals = jnp.asarray(values)
            out = jnp.zeros(n_segments, vals.dtype
                            ).at[jnp.asarray(segments)].add(vals)
            return np.asarray(out)

    def masked_select(self, mask):
        with self._x64():
            return np.asarray(self._jnp.flatnonzero(self._jnp.asarray(mask)))

    def cumfill(self, q, budget: int):
        jnp = self._jnp
        with self._x64():
            qa = jnp.asarray(q)
            csum = jnp.minimum(jnp.cumsum(qa), budget)
            return np.asarray(jnp.concatenate([csum[:1],
                                               csum[1:] - csum[:-1]]))

    # ---- scheduler kernels (padded dispatch)
    def saturating_probe(self, d, n_max, total) -> bool:
        sp = self.spans
        with sp.span("backend.saturating_probe.prep"):
            n, m = d.shape
            n_pad = _pow2(n)
            d_p = np.zeros((n_pad, m), np.float64)
            d_p[:n] = d
            nm_p = np.zeros(n_pad, np.float64)
            nm_p[:n] = n_max
        with sp.span("backend.saturating_probe.dispatch"):
            out = self._run("probe", d_p, nm_p, total.astype(np.float64))
        with sp.span("backend.saturating_probe.wait"):
            return bool(out)

    def ladder_counts(self, d, n_min, n_max, weight, total):
        sp = self.spans
        with sp.span("backend.ladder_counts.prep"):
            n, m = d.shape
            n_pad = _pow2(n)
            L = _pow2(int(n_max.max()) if n else 1)
            d_p = np.zeros((n_pad, m), np.float64)
            d_p[:n] = d
            nmin_p = np.zeros(n_pad, np.int64)
            nmin_p[:n] = n_min
            nmax_p = np.zeros(n_pad, np.int64)
            nmax_p[:n] = n_max
            w_p = np.ones(n_pad, np.float64)
            w_p[:n] = weight
            valid = np.zeros(n_pad, bool)
            valid[:n] = True
            levels = np.arange(L, dtype=np.int64)
        with sp.span("backend.ladder_counts.dispatch"):
            out = self._run("ladder", d_p, nmin_p, nmax_p, w_p, valid,
                            total.astype(np.float64), levels)
        with sp.span("backend.ladder_counts.wait"):
            return np.asarray(out)[:n]

    def place_counts(self, free, di, inv_cap, need):
        b, m = free.shape
        f_p, ic_p = self._pad_slaves(free, inv_cap)
        counts = np.asarray(self._run("place", f_p, di, ic_p,
                                      np.int64(need)))[:b]
        js = np.flatnonzero(counts)
        if not js.size:
            return None
        return js, counts[js]

    def _pad_slaves(self, free, inv_cap):
        b, m = free.shape
        b_pad = _pow2(b)
        if b_pad == b:
            return free, inv_cap
        f_p = np.full((b_pad, m), -1.0)         # sentinel: nothing fits
        f_p[:b] = free
        ic_p = np.zeros((b_pad, m))
        ic_p[:b] = inv_cap
        return f_p, ic_p

    def place_run(self, x, free, d, inv_cap, items):
        """One jitted program for the whole placement schedule (see the
        jit body in `_build_jax_fns`); the host applies the resulting
        grant matrix to `x`/`free` with the same sparse arithmetic the
        numpy path uses."""
        K = len(items)
        if K == 0:
            return []
        sp = self.spans
        with sp.span("backend.place_run.prep"):
            b, m = free.shape
            # Tight pow2 (floor 1), NOT `_pow2`: its floor-8 bucket is right
            # for vectorized app axes, but the scan pays per STEP, so
            # padding a K=1 flood to 8 steps would octuple the device work.
            # Worst case this costs log2 extra one-time compiles (K_pad 1,
            # 2, 4, ...).
            K_pad = 1 << (K - 1).bit_length()
            f_p, ic_p = self._pad_slaves(free, inv_cap)
            idx = np.fromiter((i for i, _ in items), np.int64, K)
            d_items = np.zeros((K_pad, m), np.float64)
            d_items[:K] = d[idx]
            lims = np.zeros(K_pad, np.int64)
            lims[:K] = np.fromiter((lim for _, lim in items), np.int64, K)
            bases = np.zeros(K_pad, np.int64)
            bases[:K] = x[idx].sum(axis=1)
            aslots = np.full(K_pad, -1, np.int64)
            last: Dict[int, int] = {}
            for k, i in enumerate(idx.tolist()):
                j = last.get(i)
                if j is not None:
                    aslots[k] = j
                last[i] = k
        with sp.span("backend.place_run.dispatch"):
            dev = self._run("place_run", f_p, ic_p, d_items, lims, bases,
                            aslots)
        with sp.span("backend.place_run.wait"):
            grants = np.asarray(dev)[:K, :b]
        with sp.span("backend.place_run.apply"):
            out: List[int] = []
            for k in range(K):
                counts = grants[k]
                js = np.flatnonzero(counts)
                if js.size:
                    i = int(idx[k])
                    cj = counts[js]
                    x[i, js] += cj
                    free[js] -= cj[:, None].astype(np.float64) * d[i][None, :]
                    out.append(int(cj.sum()))
                else:
                    out.append(0)
        return out


# ------------------------------------------------------------------- auto


# Measured problem-size crossover (BENCH_scale.json records the live
# values): at 1000 slaves x 500 apps the jax per-event median loses to
# numpy (host dispatch dominates ~1 ms events), at 5000 x 2000 it wins
# (~0.9x). The default sits between the two measured points; override via
# the env knobs for other hardware.
AUTO_CROSSOVER_SLAVES = 2048
AUTO_CROSSOVER_APPS = 1024


class AutoBackend(Backend):
    """Problem-size dispatcher (`backend="auto"` / REPRO_BACKEND=auto):
    numpy below a measured crossover, jax above it.

    Both delegates are pinned bit-exact against each other (the parity
    suite + the bench `timeline_bit_exact_vs_jax` gate), so mixing them
    per kernel call is safe: the placement kernels switch on the SLAVE
    axis (their dominant dimension), the ladder/probe kernels on the app
    axis."""

    name = "auto"

    def __init__(self, crossover_slaves: Optional[int] = None,
                 crossover_apps: Optional[int] = None,
                 spans: Optional[Spans] = None):
        super().__init__(spans)
        self.crossover_slaves = int(
            os.environ.get("REPRO_AUTO_CROSSOVER_SLAVES",
                           AUTO_CROSSOVER_SLAVES)
            if crossover_slaves is None else crossover_slaves)
        self.crossover_apps = int(
            os.environ.get("REPRO_AUTO_CROSSOVER_APPS", AUTO_CROSSOVER_APPS)
            if crossover_apps is None else crossover_apps)
        self._np = NumpyBackend()
        self._jax: Optional[JaxBackend] = None

    def _pick(self, size: int, crossover: int) -> Backend:
        if size < crossover:
            return self._np
        if self._jax is None:                   # lazy: first large call
            self._jax = JaxBackend(spans=self.spans)
        return self._jax

    @property
    def compile_s(self) -> float:
        return self._jax.compile_s if self._jax is not None else 0.0

    # ---- ops protocol: host ops stay on numpy (never the bottleneck)
    def argsort(self, keys):
        return self._np.argsort(keys)

    def cumsum(self, a, axis: int = 0):
        return self._np.cumsum(a, axis=axis)

    def segment_sum(self, values, segments, n_segments: int):
        return self._np.segment_sum(values, segments, n_segments)

    def masked_select(self, mask):
        return self._np.masked_select(mask)

    def cumfill(self, q, budget: int):
        return self._np.cumfill(q, budget)

    # ---- scheduler kernels: size-dispatched
    def saturating_probe(self, d, n_max, total) -> bool:
        return self._pick(d.shape[0],
                          self.crossover_apps).saturating_probe(d, n_max,
                                                                total)

    def ladder_counts(self, d, n_min, n_max, weight, total):
        return self._pick(d.shape[0],
                          self.crossover_apps).ladder_counts(
            d, n_min, n_max, weight, total)

    def place_counts(self, free, di, inv_cap, need):
        return self._pick(free.shape[0],
                          self.crossover_slaves).place_counts(
            free, di, inv_cap, need)

    def place_run(self, x, free, d, inv_cap, items):
        return self._pick(free.shape[0],
                          self.crossover_slaves).place_run(
            x, free, d, inv_cap, items)


def auto_dispatch_report(n_slaves: int, n_apps: int,
                         backend: Optional["AutoBackend"] = None,
                         ) -> Dict[str, object]:
    """Which delegate `backend="auto"` picks at a given problem size.

    The placement kernels dispatch on the slave axis, the ladder/probe
    kernels on the app axis, so the two can disagree. The sharded control
    plane calls this per shard (shards are small, so the crossover that
    was moot for one 100k-slave master now decides each shard's engine)
    and `bench_shard.py` records it next to the throughput numbers."""
    be = backend if backend is not None else AutoBackend()
    return {
        "placement": be._pick(int(n_slaves), be.crossover_slaves).name,
        "ladder": be._pick(int(n_apps), be.crossover_apps).name,
        "crossover_slaves": be.crossover_slaves,
        "crossover_apps": be.crossover_apps,
    }


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_BACKENDS = {"numpy": NumpyBackend, "jax": JaxBackend, "auto": AutoBackend}


def get_backend(name: str, spans: Optional[Spans] = None) -> Backend:
    """-> a fresh backend instance that records its spans in `spans` (the
    jit caches and their compile count are process-global)."""
    try:
        cls = _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_BACKENDS)}")
    return cls(spans=spans)
