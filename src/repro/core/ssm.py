"""Optimal single-step migration (paper §3).

Three implementations, strongest assumptions last:

* ``brute_force``     — tiny instances; enumerates every partition (empty
                        intervals allowed) and solves the interval→node
                        assignment exactly with a bitmask DP (full bipartite
                        matching, no structural assumptions).  Oracle #1.
* ``simple_ssm``      — Fig. 12 equivalent: exact DP over
                        (suffix, last-used-node, #intervals) exploiting only
                        the *non-crossing* property of optimal matchings.
                        O(m^2·n·n') time.  Oracle #2 for medium sizes.
* ``ssm``             — Fig. 14: the paper's O(m^2·n') time / O(m·n') space
                        DP using Lemmas 3.2–3.5.  This is the production
                        planner.

Why non-crossing is safe (used by both DPs): if old nodes u < v (disjoint
ordered old intervals) were matched to new intervals B > A (ordered), then
gain(u,B) > 0 needs I_u.ub > B.lo >= A.hi and gain(v,A) > 0 needs
I_v.lo < A.hi <= I_u.ub <= I_v.lo — a contradiction, so at most one of any
crossing pair has positive gain and the matching can be un-crossed for free.

Free-interval placement in reconstruction cannot add gain: if it could, the
resulting assignment would beat ``maxgain``, contradicting DP optimality.
Tests assert the realized assignment's cost equals the DP's predicted cost.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro import obs

from .intervals import (
    Assignment,
    balance_cap,
    feasible_tol,
    greedy_boundaries,
    measure,
    migration_cost,
    migration_gain,
    min_cover_counts,
    min_feasible_starts,
    next_jump,
    overlap_measure,
    prefix_sum,
    satisfies_balance,
    _EPS,
)

NEG = -1e30


class Infeasible(ValueError):
    """No contiguous partition satisfies the balance cap (some single task
    exceeds (1+tau)W/n', or n' is too small)."""


@dataclass(frozen=True)
class MigrationPlan:
    old: Assignment
    new: Assignment
    gain: float
    cost: float

    @property
    def n_active(self) -> int:
        """Nodes that own at least one task after the migration."""
        return sum(1 for lo, hi in self.new.intervals if hi > lo)


def _plan(old: Assignment, new: Assignment, s: np.ndarray) -> MigrationPlan:
    g = migration_gain(old, new, s)
    c = migration_cost(old, new, s)
    return MigrationPlan(old=old, new=new, gain=g, cost=c)


# ---------------------------------------------------------------------------
# Oracle #1: full brute force (tiny m, n).
# ---------------------------------------------------------------------------

def brute_force(
    old: Assignment, n_new: int, w: np.ndarray, s: np.ndarray, tau: float
) -> MigrationPlan:
    """Exact optimum by enumerating boundary multisets (empty intervals
    allowed) and solving the assignment with a bitmask DP.  O(C(m+k,k)·2^n)."""
    m = old.m
    if m > 20 or max(old.n_nodes, n_new) > 8:
        raise ValueError("brute_force is for tiny instances only")
    Sw, Ss = prefix_sum(w), prefix_sum(s)
    cap = balance_cap(float(Sw[-1]), n_new, tau)
    tol = feasible_tol(cap)
    n_total = max(old.n_nodes, n_new)
    old_p = old.padded(n_total)

    best_gain, best_assign = NEG, None
    # nondecreasing interior boundaries => intervals in order, empties allowed
    for interior in itertools.combinations_with_replacement(
        range(m + 1), n_new - 1
    ):
        bounds = (0,) + interior + (m,)
        ivs = [(bounds[i], bounds[i + 1]) for i in range(n_new)]
        if any(measure(Sw, lo, hi) > tol for lo, hi in ivs):
            continue
        # bitmask DP over nodes: process intervals in order, each interval
        # assigned to exactly one unused node (full bipartite matching).
        # dp maps used-node-mask -> best gain after assigning a prefix.
        dp = {0: 0.0}
        for (lo, hi) in ivs:
            ndp: dict = {}
            for mask, g in dp.items():
                for node in range(n_total):
                    bit = 1 << node
                    if mask & bit:
                        continue
                    ov = overlap_measure(Ss, old_p.intervals[node], (lo, hi))
                    nm = mask | bit
                    val = g + ov
                    if val > ndp.get(nm, NEG):
                        ndp[nm] = val
            dp = ndp
        g = max(dp.values())
        if g > best_gain + 1e-12:
            best_gain = g
            # reconstruct assignment for this partition greedily re-running DP
            best_assign = (bounds, ivs)
    if best_assign is None:
        raise Infeasible("no feasible partition")
    # second pass: recover the matching for the winning partition
    bounds, ivs = best_assign
    dp = {0: (0.0, ())}
    for idx, (lo, hi) in enumerate(ivs):
        ndp: dict = {}
        for mask, (g, hist) in dp.items():
            for node in range(n_total):
                bit = 1 << node
                if mask & bit:
                    continue
                ov = overlap_measure(Ss, old_p.intervals[node], (lo, hi))
                nm = mask | bit
                val = g + ov
                if nm not in ndp or val > ndp[nm][0]:
                    ndp[nm] = (val, hist + (node,))
        dp = ndp
    g, hist = max(dp.values(), key=lambda t: t[0])
    new_ivs = [(m, m)] * n_total
    for iv, node in zip(ivs, hist):
        new_ivs[node] = iv
    return _plan(old, Assignment(m, tuple(new_ivs)), s)


# ---------------------------------------------------------------------------
# Oracle #2: Simple_SSM — exact non-crossing DP, O(m^2 · n · n').
# ---------------------------------------------------------------------------

def simple_ssm(
    old: Assignment, n_new: int, w: np.ndarray, s: np.ndarray, tau: float
) -> MigrationPlan:
    """DP over f[t][y][k] = max gain partitioning suffix [t, m) into k
    cap-feasible intervals where gaining nodes are drawn (in order) from old
    nodes with position >= y.  Transition: first interval [t, b) is either
    zero-gain or matched to some y' >= y."""
    m = old.m
    Sw, Ss = prefix_sum(w), prefix_sum(s)
    cap = balance_cap(float(Sw[-1]), n_new, tau)
    tol = feasible_tol(cap)
    items = old.nonempty()  # sorted by lo
    n_real = len(items)
    lbs = np.array([iv[0] for _, iv in items], dtype=np.int64)
    ubs = np.array([iv[1] for _, iv in items], dtype=np.int64)

    nxt = next_jump(w, cap)
    if (nxt[:-1] <= np.arange(m)).any():
        raise Infeasible("a single task exceeds the balance cap")
    cnt = min_cover_counts(nxt)
    if cnt[0] > n_new:
        raise Infeasible(f"need >= {cnt[0]} intervals, have {n_new}")

    # f[t][y][k]; y in [0, n_real]; t in [0, m]
    f = np.full((m + 1, n_real + 1, n_new + 1), NEG)
    f[m, :, :] = 0.0
    arg = np.full((m + 1, n_real + 1, n_new + 1, 2), -1, dtype=np.int64)
    for t in range(m - 1, -1, -1):
        for k in range(1, n_new + 1):
            for y in range(n_real, -1, -1):
                best, bb, byy = NEG, -1, -1
                # empty interval (consume one of the k without advancing)
                v = f[t, y, k - 1]
                if v > best:
                    best, bb, byy = v, t, -2
                for b in range(t + 1, m + 1):
                    if Sw[b] - Sw[t] > tol:
                        break
                    # zero-gain interval
                    v = f[b, y, k - 1]
                    if v > best:
                        best, bb, byy = v, b, -1
                    # gaining node y' >= y with overlap
                    for yp in range(y, n_real):
                        ov = overlap_measure(
                            Ss, (int(lbs[yp]), int(ubs[yp])), (t, b)
                        )
                        if ov <= 0:
                            continue
                        v = ov + f[b, yp + 1, k - 1]
                        if v > best:
                            best, bb, byy = v, b, yp
                f[t, y, k] = best
                arg[t, y, k] = (bb, byy)

    val = f[0, 0, n_new]
    if val <= NEG / 2:
        raise Infeasible("no feasible solution found")
    # reconstruct
    new_ivs = [(m, m)] * max(old.n_nodes, n_new)
    t, y, k = 0, 0, n_new
    free_ivs = []
    while t < m:
        b, yp = arg[t, y, k]
        b = int(b)
        if yp == -2:  # empty interval
            k = k - 1
        elif yp == -1:
            free_ivs.append((t, b))
            t, k = b, k - 1
        else:
            node_id = items[int(yp)][0]
            new_ivs[node_id] = (t, b)
            t, y, k = b, int(yp) + 1, k - 1
    used = {i for i, iv in enumerate(new_ivs) if iv[1] > iv[0]}
    free_nodes = [i for i in range(len(new_ivs)) if i not in used]
    for node_id, iv in zip(free_nodes, free_ivs):
        new_ivs[node_id] = iv
    return _plan(old, Assignment(m, tuple(new_ivs)), s)


# ---------------------------------------------------------------------------
# SSM — Fig. 14, O(m^2 · n') time, O(m · n') space.
# ---------------------------------------------------------------------------

class _SparseTableMax:
    """Static range-max with argmax in O(1) per query."""

    def __init__(self, vals: np.ndarray):
        n = len(vals)
        self.n = n
        if n == 0:
            return
        K = max(1, int(np.floor(np.log2(n))) + 1)
        self.val = np.full((K, n), NEG)
        self.idx = np.zeros((K, n), dtype=np.int64)
        self.val[0] = vals
        self.idx[0] = np.arange(n)
        j = 1
        while (1 << j) <= n:
            span = 1 << (j - 1)
            a = self.val[j - 1, : n - 2 * span + 1]
            b = self.val[j - 1, span : n - span + 1]
            take_b = b > a
            self.val[j, : n - 2 * span + 1] = np.where(take_b, b, a)
            self.idx[j, : n - 2 * span + 1] = np.where(
                take_b,
                self.idx[j - 1, span : n - span + 1],
                self.idx[j - 1, : n - 2 * span + 1],
            )
            j += 1

    def query(self, lo: int, hi: int) -> Tuple[float, int]:
        """Max over vals[lo:hi]; returns (NEG, -1) when empty."""
        if hi <= lo or self.n == 0:
            return NEG, -1
        j = int(np.floor(np.log2(hi - lo)))
        a = (self.val[j, lo], self.idx[j, lo])
        b = (self.val[j, hi - (1 << j)], self.idx[j, hi - (1 << j)])
        return a if a[0] >= b[0] else b


@dataclass
class _Pre:
    """Backend-independent precomputation shared by the ssm() backends.

    Built once in ``ssm()`` so that *every* backend makes identical
    feasibility decisions (same ``nxt``/``cnt``/``lb_global`` from the same
    canonical predicate) — Infeasible is raised before any backend runs.
    """

    m: int
    n_new: int
    n_real: int
    n_total: int
    Sw: np.ndarray
    Ss: np.ndarray
    cap: float
    tol: float
    items: tuple
    lbs: np.ndarray
    ubs: np.ndarray
    full_size: np.ndarray
    node_of: np.ndarray
    nxt: np.ndarray
    cnt: np.ndarray
    lb_global: np.ndarray


# Below this task count, "auto" stays on the numpy backend: the jit backend
# pays a one-off trace/compile per padded shape bucket, which only amortizes
# on large instances or repeated plans.
_AUTO_JIT_MIN_M = 4096


def ssm(
    old: Assignment, n_new: int, w: np.ndarray, s: np.ndarray, tau: float,
    backend: str = "auto",
) -> MigrationPlan:
    """The paper's SSM (Fig. 14).

    DP state g[x][j][k]: max gain for partitioning suffix tasks [x, m) into
    exactly k cap-feasible intervals (empties allowed) where the available
    gaining nodes are those with position >= gamma'' = node_of(x) + j,
    j ∈ {0, 1} (Lemma 3.3/3.5 canonicalization — see DESIGN.md §1).

    Transition at (x0, j, k): either complete with zero gain (k >= minimum
    cover count of [x0, m)), or choose the first gaining interval to end at
    x ∈ (x0, m]: it is [lb'(x), x) with lb'(x) = max(lb(x), x0) minimal
    feasible (Solve_P1), preceded by n_min-1 greedy zero-gain fillers, and
    matched to one of two candidate nodes (Lemma 3.5):
      cand1: the node containing task x-1;
      cand2: the best node whose old interval does not contain x (realized
             as: the straddler at lb', or the range-max of fully-contained
             old intervals inside [lb', x)).

    ``backend`` selects the DP engine — the plan *value* is identical:

    * ``"numpy"`` — the O(m²·n′) reference above, pure numpy + Python loops.
      Lowest latency for small m; no compile step; easiest to debug.
    * ``"jit"``   — jax.jit'd layered step-DP (``core.ssm_jit``): the
      bundled "n_min−1 fillers + gain" transition is decomposed into
      single-step transitions (terminal / one filler / one gain interval per
      step, each consuming exactly one of the k intervals), which bounds
      every successor to a one-jump window and removes the sequential task
      loop entirely — layer k reads only layer k−1, so the whole DP is a
      ``lax.scan`` of n′ vectorized sweeps over [window × m] gain tables
      that the compiled program builds once from O(m + window) host-made
      1-D tables.  Shapes are padded into buckets so repeated
      plans at similar sizes reuse one compilation.  ~70× faster than numpy
      at m = 10⁴ on one CPU core (see BENCH_ssm.json).
    * ``"auto"``  — ``"jit"`` when m ≥ %d, else ``"numpy"``.

    Feasibility (Infeasible) is decided *before* backend dispatch, from the
    canonical predicate in ``intervals.feasible_tol`` — both backends and
    both oracles agree exactly.  Oracle choice for differential work:
    ``brute_force`` is ground truth but only for m ≤ 20 / ≤ 8 nodes;
    ``simple_ssm`` is the readable O(m²·n·n′) reference at moderate m;
    ``benchmarks/ssm_oracles.py`` runs all four on one instance stream.
    """ % _AUTO_JIT_MIN_M
    with obs.span("plan.prep"):
        m = old.m
        if n_new < 1:
            raise ValueError("n_new >= 1 required")
        if backend not in ("auto", "numpy", "jit"):
            raise ValueError(f"unknown ssm backend: {backend!r}")
        w = np.asarray(w, dtype=np.float64)
        s = np.asarray(s, dtype=np.float64)
        Sw, Ss = prefix_sum(w), prefix_sum(s)
        cap = balance_cap(float(Sw[-1]), n_new, tau)
        tol = feasible_tol(cap)
        items = old.nonempty()
        n_real = len(items)
        n_total = max(old.n_nodes, n_new)

        nxt = next_jump(w, cap)
        if m and (nxt[:-1] <= np.arange(m)).any():
            raise Infeasible("a single task exceeds the balance cap")
        cnt = min_cover_counts(nxt)
        if cnt[0] > n_new:
            raise Infeasible(f"need >= {cnt[0]} intervals, have {n_new}")

        if n_real == 0 or m == 0:
            # bootstrap: no old state anywhere — greedy split, zero gain.
            bs = greedy_boundaries(nxt, 0, m)
            ivs = [(bs[i], bs[i + 1]) for i in range(len(bs) - 1)]
            ivs += [(m, m)] * (n_new - len(ivs))
            return _plan(old, Assignment(m, tuple(ivs)).padded(n_total), s)

        lbs = np.array([iv[0] for _, iv in items], dtype=np.int64)
        ubs = np.array([iv[1] for _, iv in items], dtype=np.int64)
        full_size = Ss[ubs] - Ss[lbs]
        # node_of[t] = position (in sorted order) of the old node owning task t
        node_of = np.zeros(m + 1, dtype=np.int64)
        for pos in range(n_real):
            node_of[lbs[pos] : ubs[pos]] = pos
        node_of[m] = n_real  # sentinel: "past the last node"

        # lb_global[x] = minimal lb with weight([lb, x)) <= cap
        lb_global = min_feasible_starts(Sw, tol, np.arange(m + 1))

        pre = _Pre(m=m, n_new=n_new, n_real=n_real, n_total=n_total, Sw=Sw,
                   Ss=Ss, cap=cap, tol=tol, items=items, lbs=lbs, ubs=ubs,
                   full_size=full_size, node_of=node_of, nxt=nxt, cnt=cnt,
                   lb_global=lb_global)
    if backend == "auto":
        backend = "jit" if m >= _AUTO_JIT_MIN_M else "numpy"
    obs.tag(backend=backend)
    if backend == "jit":
        from . import ssm_jit
        return ssm_jit.ssm_jit(old, w, s, pre)
    with obs.span("plan.numpy"):
        return _ssm_numpy(old, w, s, pre)


def _ssm_numpy(old: Assignment, w: np.ndarray, s: np.ndarray,
               pre: _Pre) -> MigrationPlan:
    """Reference backend: the Fig. 14 DP exactly as documented in ssm()."""
    m, n_new, n_real, n_total = pre.m, pre.n_new, pre.n_real, pre.n_total
    Ss, items = pre.Ss, pre.items
    lbs, ubs, node_of = pre.lbs, pre.ubs, pre.node_of
    nxt, cnt, lb_global = pre.nxt, pre.cnt, pre.lb_global
    rmq = _SparseTableMax(pre.full_size)

    # g[x][j][k] and argmax records
    g = np.full((m + 1, 2, n_new + 1), NEG)
    g[m, :, :] = 0.0
    # arg: x (end of gaining interval), cand node position, n_min
    arg_x = np.full((m + 1, 2, n_new + 1), -1, dtype=np.int64)
    arg_y = np.full((m + 1, 2, n_new + 1), -1, dtype=np.int64)
    arg_nm = np.full((m + 1, 2, n_new + 1), -1, dtype=np.int64)

    ks = np.arange(n_new + 1)

    for x0 in range(m - 1, -1, -1):
        c0 = int(node_of[x0])
        # --- per-x0 sweep arrays over x in (x0, m] --------------------------
        xs = np.arange(x0 + 1, m + 1)
        nx = len(xs)
        lbp = np.maximum(lb_global[xs], x0)  # gaining interval is [lbp, x)
        # n_min(x0, x) = 1 + greedy cover count of [x0, lbp(x))
        n_min = np.ones(nx, dtype=np.int64)
        # walk the greedy chain from x0 once; lbp is nondecreasing
        chain_pos, chain_cnt = x0, 0
        for i in range(nx):
            t = int(lbp[i])
            while chain_pos < t:
                chain_pos = int(nxt[chain_pos])
                chain_cnt += 1
            # chain_cnt jumps cover [x0, chain_pos) ⊇ [x0, t); greedy count
            # of [x0, t) is chain_cnt (last jump may be truncated to t).
            n_min[i] = 1 + chain_cnt
        # candidate gains + successor j' per x, per j in {0, 1}
        for j in (0, 1):
            gamma = c0 + j
            if gamma > n_real:
                continue
            cand_gain = np.full((2, nx), NEG)
            cand_y = np.full((2, nx), -1, dtype=np.int64)
            cand_jp = np.zeros((2, nx), dtype=np.int64)
            for i in range(nx):
                x = int(xs[i])
                lb = int(lbp[i])
                # cand1: y1 = node containing task x-1
                y1 = int(node_of[x - 1])
                if y1 >= gamma:
                    gv = Ss[x] - Ss[max(int(lbs[y1]), lb)]
                    if gv > 0:
                        cand_gain[0, i] = gv
                        cand_y[0, i] = y1
                        cx = int(node_of[x]) if x < m else n_real
                        cand_jp[0, i] = min(max(y1 + 1 - cx, 0), 1)
                # cand2: best node z >= gamma with ub_z <= x
                # straddler: node containing lb (if truncated by lb)
                zs = int(node_of[lb]) if lb < m else n_real
                best_g, best_z = NEG, -1
                if zs < n_real and zs >= gamma and int(ubs[zs]) <= x:
                    gv = Ss[int(ubs[zs])] - Ss[max(int(lbs[zs]), lb)]
                    if gv > best_g:
                        best_g, best_z = gv, zs
                # fully-contained: z with lb_z >= lb and ub_z <= x
                zlo = zs if (zs < n_real and int(lbs[zs]) >= lb) else zs + 1
                zlo = max(zlo, gamma)
                # zhi: last node with ub <= x
                cx = int(node_of[x]) if x < m else n_real
                zhi = cx if (cx < n_real and int(ubs[cx]) <= x) else cx - 1
                if zhi >= zlo:
                    gv, zidx = rmq.query(zlo, zhi + 1)
                    if gv > best_g:
                        best_g, best_z = gv, zidx
                if best_z >= 0 and best_g > 0:
                    cand_gain[1, i] = best_g
                    cand_y[1, i] = best_z
                    cand_jp[1, i] = 0  # z+1 <= node_of(x) always
            # --- fold into DP for all k (vectorized over x) ----------------
            for k in range(1, n_new + 1):
                best = 0.0 if cnt[x0] <= k else NEG
                bx, by, bnm = -1, -1, -1
                kk = k - n_min  # remaining intervals after P1
                valid = kk >= 0
                if valid.any():
                    for ci in (0, 1):
                        gains = cand_gain[ci]
                        tgt = np.where(
                            valid,
                            g[xs, cand_jp[ci], np.maximum(kk, 0)],
                            NEG,
                        )
                        tot = np.where(valid, gains + tgt, NEG)
                        bi = int(np.argmax(tot))
                        if tot[bi] > best:
                            best = float(tot[bi])
                            bx, by, bnm = int(xs[bi]), int(cand_y[ci][bi]), int(
                                n_min[bi]
                            )
                g[x0, j, k] = best
                arg_x[x0, j, k] = bx
                arg_y[x0, j, k] = by
                arg_nm[x0, j, k] = bnm

    total_gain = float(g[0, 0, n_new])
    if total_gain <= NEG / 2:
        raise Infeasible("no feasible solution found")

    # --- reconstruction ----------------------------------------------------
    new_ivs: list = [(m, m)] * n_total
    free_ivs: list = []
    x0, j, k = 0, 0, n_new
    while x0 < m:
        bx = int(arg_x[x0, j, k])
        if bx < 0:
            # zero-gain completion: greedy split [x0, m)
            bs = greedy_boundaries(nxt, x0, m)
            free_ivs += [(bs[i], bs[i + 1]) for i in range(len(bs) - 1)]
            break
        y = int(arg_y[x0, j, k])
        nm = int(arg_nm[x0, j, k])
        lb = max(int(lb_global[bx]), x0)
        if lb > x0:
            bs = greedy_boundaries(nxt, x0, lb)
            fill = [(bs[i], bs[i + 1]) for i in range(len(bs) - 1)]
            assert len(fill) == nm - 1, (fill, nm)
            free_ivs += fill
        node_id = items[y][0]
        new_ivs[node_id] = (lb, bx)
        cx = int(node_of[bx]) if bx < m else n_real
        j = min(max(y + 1 - cx, 0), 1)
        x0, k = bx, k - nm
    used = {i for i, iv in enumerate(new_ivs) if iv[1] > iv[0]}
    free_nodes = [i for i in range(n_total) if i not in used]
    for node_id, iv in zip(free_nodes, free_ivs):
        new_ivs[node_id] = iv
    assert len(free_ivs) <= len(free_nodes), "more intervals than nodes"
    new = Assignment(m, tuple(new_ivs))
    plan = _plan(old, new, s)
    # The realized gain must equal the DP's prediction (sanity invariant).
    assert abs(plan.gain - total_gain) < 1e-6 * max(1.0, abs(total_gain)), (
        plan.gain,
        total_gain,
    )
    return plan
