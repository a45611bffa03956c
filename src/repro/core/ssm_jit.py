"""JIT backend for the SSM planner (``ssm(..., backend="jit")``).

The numpy reference (Fig. 14, ``ssm._ssm_numpy``) evaluates, at every DP
state x0, *bundled* transitions "n_min−1 greedy fillers + one gaining
interval ending at any x ∈ (x0, m]" — an O(m) successor sweep per state that
cannot be expressed as a fixed-shape jax op.  This module reformulates the
recurrence as a *one-jump step-DP* with single-step transitions only:

    G[p, j, k] = max gain partitioning suffix [p, m) into ≤ k cap-feasible
                 intervals, gaining nodes restricted to positions
                 ≥ node_of(p) + j (the same Lemma 3.3/3.5 canonical state).

Transitions out of (p, j, k) — each consumes exactly one interval:

    T0  terminal     0                     if cnt[p] <= k
    TF  filler       G[q, j', k-1]         any q in (p, nxt[p]]  (zero-gain
                                           interval [p, q), possibly short)
    TG  gain         gain(p→x) + G[x, j', k-1]   for x in (p, nxt[p]]
                                           (gaining interval [p, x))

where gain(p→x) is Lemma 3.5's two-candidate maximum (the node containing
x−1; the best straddling/contained node via a range-max over old interval
sizes), with the interval starting *exactly* at p.

Equivalence with the bundled DP
-------------------------------
Every bundled transition "fillers + interval [lb, x) gaining y" decomposes
exactly: full greedy fillers are TF steps with q = nxt[p]; the truncated
filler [q, lb) is a TF step with q' = lb (feasible: lb <= nxt[q]); the
gaining interval is then a TG step *from* lb — and x <= nxt[lb] holds by
predicate duality (lb_global[x] <= lb ⟺ x <= nxt[lb_global[x]], which is
why the shared canonical ``feasible_tol`` predicate matters for
correctness, not just backend consistency).  The gamma update after a
short filler, gamma' = max(gamma, node_of(q)), preserves the exact
candidate set: any node gaining inside [lb, x) has index >= node_of(lb)
anyway.  Conversely, every step-DP path (including "wasteful" short
fillers the bundled DP never takes) realizes a feasible assignment with
the same gain, so it cannot exceed the bundled optimum: the maxima agree.

Why this shape is fast on CPU
-----------------------------
* Every transition consumes one interval, so layer k of G depends only on
  the finished layer k-1: no sequential loop over p — the DP is a
  ``lax.scan`` of n' full sweeps, each a handful of fused [W, mpad] ops.
* The window is ONE feasible jump, clamped at m (successors past m are
  dominated by the x = m option): W = max_{p<m}(min(nxt[p], m) − p).
* With the interval forced to start at p, every quantity in the gain
  formulas is a function of x alone or of p alone, combined by binary
  selects (e.g. Ss[max(lbs[y1(x)], p)] is a select between two 1-D
  tables).  The host therefore sends only 1-D tables, x-side ones of
  length mpad+W+1 and p-side ones of length mpad: O(mpad + W) bytes, not
  the planes' O(W * mpad).  The compiled program builds the [W, mpad]
  gain and mask planes from them ONCE per call (``_device_planes``: a
  scan over the window's rows, each row an unfold of the x tables by one
  ``dynamic_slice``; the range-max over contained old intervals is
  carried down the rows, since each row adds at most the one interval
  ending at its x — no gather, which a TPU does slowly) and every layer
  reuses them.  The per-layer work is just: 3 sliding-window unfolds of
  layer k-1 (built the same way), 2 adds, 3 selects, 3 maxes and 1
  reduction.
* The device returns each state's best transition, not its value.  A
  TPU's float64 is emulated and not IEEE-exact (inputs lose bits on the
  way in, adds round differently), so its values cannot be matched
  against host sums.  Every mask is therefore decided from integers or
  host-made booleans: feasibility and the filler's j' from positions,
  cand1's ``g1 > 0`` from an order code of the prefix sums (``RK``:
  Ss[a] < Ss[b] iff RK[a] < RK[b], so ``Ss[x] - Ss[a] > 0`` iff RK[x] >
  RK[a], the IEEE sign of a subtraction being exact), cand2's ``g2 > 0``
  from whether an old interval of positive size joined the range, or the
  straddler's sign.  The device marks exactly the entries the host
  marks; a gain it keeps is floored at ``GAIN_FLOOR`` > 0, so a kept gain
  is positive on the device as on the host, and so is every sum holding
  one (the other summands are >= 0).  Only the maximisation sees device
  rounding.
* The host rebuilds the layer values in IEEE float64 from the device's
  transitions (``_host_layers``: one vectorized gather + add per layer,
  with the entries evaluated pointwise at the chosen (wi, p) by the same
  ``_entries`` on the host's tables) and recomputes the full window only
  for the states the device flags as near-ties: a candidate strictly
  below the best by at most ``TIE_RTOL * max(1, Ss[m])``.  That reference
  bounds the device's error: the one subtraction the device makes,
  Ss[x] - Ss[max(lb, p)], errs by a few units of its operands' last
  place, i.e. relative to Ss[m], however small the difference; each of
  the <= n' adds of a layer path errs relative to its sum, and every
  value is a gain <= Ss[m] (a plan keeps at most all state).  So the
  device's total error is ~n' * 2^-46 * Ss[m], far below the threshold,
  while max(1, |best|) alone would not cover a gain small next to Ss[m].
  Every other state's winner is then the IEEE winner too, so the rebuilt
  layers equal a CPU run bit for bit, unless two different sums land
  within the device's resolution of each other without being equal.
* Reconstruction re-derives each optimal transition by exact float64
  value-matching against the rebuilt layers (they hold only IEEE
  adds/maxes of the very entries the decoder reads, so equality is
  bit-exact; any matching transition is a valid optimal continuation).

Shape bucketing: small instances (m <= 2048) round m, W and the layer
count to powers of two so one compilation serves many instances; large
instances round m and W to multiples of 256 and use exactly n'+1 layers
(every extra layer is a full sweep).  Padding tasks have zero weight and
zero state, which provably leaves the optimum unchanged: cnt[p >= m] := 0
so padded suffixes are free, and intervals reaching into the padding are
clamped back to m at decode time with identical gain.

The DP runs in float64 via ``repro.compat.enable_x64`` (scoped — the
rest of the process stays float32).
"""
from __future__ import annotations

from functools import lru_cache

import numpy as np

from repro import obs

from .intervals import Assignment, greedy_boundaries, max_feasible_ends
from .ssm import Infeasible, MigrationPlan, NEG, _plan, _Pre


def _pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def _ceil_to(x: int, step: int) -> int:
    return ((x + step - 1) // step) * step


# near-tie threshold of the device's top-two candidates, relative to
# max(1, Ss[m]); the device's own error is ~n' * 2^-46 of that
TIE_RTOL = 1e-9

# the least gain the device keeps: positive in an emulated float64 (whose
# exponent range may be float32's), and far inside the near-tie threshold
GAIN_FLOOR = 1e-30


def _allranges_max(fs: np.ndarray) -> np.ndarray:
    """T[a, b1] = max(fs[a:b1]) (NEG when empty), a <= len(fs)+1."""
    n = len(fs)
    T = np.full((n + 2, n + 1), NEG, dtype=np.float64)
    for a in range(n):
        acc = NEG
        for b1 in range(a + 1, n + 1):
            acc = max(acc, fs[b1 - 1])
            T[a, b1] = acc
    return T


def _entries(xp, xv, pv, c2, wi, p, j: int, floor: float = 0.0):
    """The window entries of DP state (p, j) at successor x = p + 1 + wi:
    ``FEAS`` ([p, x) is cap-feasible), ``SEL[j]`` (a filler [p, x) leaves
    j' = 1), ``G1m[j]`` and ``G2m[j]`` (the gain of [p, x) via cand1 and
    via cand2, NEG where that candidate does not gain).

    ``xv`` holds the x-side tables read at x and ``pv`` the p-side ones,
    with ``Ss`` and ``RK``, read at p (``_pad_inputs``); ``c2`` is the
    size of the largest old interval contained in [p, x) among nodes >=
    zlo_j[p], and whether that is > 0.  ``xp`` is numpy or jax.numpy.  Every mask is
    integer or boolean; the one float op is cand1's subtraction.  The host
    evaluates entries pointwise (``floor`` 0: IEEE float64, so identical
    to building whole planes); the device builds the planes row by row
    (``_device_planes``) with ``floor=GAIN_FLOOR``."""
    feas = wi <= pv["fw"]
    gam = pv["c0"] + j
    sel = xv["NOx"] < gam
    # cand1 y1 = node_of(x-1): interval [max(lbs[y1], p), x)
    own = xv["LB1"] >= p
    g1 = xv["Ss"] - xp.where(own, xv["SLB1"], pv["Ss"])
    ok1 = (feas & (xv["Y1"] >= gam)
           & (xv["RK"] > xp.where(own, xv["RLB1"], pv["RK"])))
    # cand2: the best contained node; for j = 0 also the straddler at p
    # once its old interval ends by x
    g2, ok2 = c2
    if j == 0:
        st = pv["ubst"] <= p + 1 + wi
        g2 = xp.where(st, xp.maximum(g2, pv["sval"]), g2)
        ok2 = ok2 | st
    NEGa = xp.asarray(NEG, g1.dtype)
    G1 = xp.where(ok1, xp.maximum(g1, floor), NEGa)
    G2 = xp.where(feas & ok2, xp.maximum(g2, floor), NEGa)
    return feas, sel, G1, G2


def _device_planes(xt, pt, mpad: int, W: int):
    """The [W, mpad] planes FEAS, SEL[j], G1m[j], G2m[j] (j = 0, 1) in
    jax, from ``_dp_args``' tables: a scan over rows wi, each unfolding
    the x tables by one slice at x = p + 1 + wi.  cand2's contained range
    [zlo_j[p], #nodes ending by x) grows by at most the one node ending at
    x per row, so its max is carried down the rows (no gather): a running
    max of the same host-made sizes, hence the same value as the host's
    all-ranges lookup."""
    import jax
    import jax.numpy as jnp

    ps = jnp.arange(mpad, dtype=jnp.int32)
    pv = dict(pt, Ss=xt["Ss"][:mpad], RK=xt["RK"][:mpad])
    NEGa = jnp.full((mpad,), NEG, xt["Ss"].dtype)
    no = jnp.zeros((mpad,), bool)

    def row(c2s, wi):
        xv = {k: jax.lax.dynamic_slice(t, (wi + 1,), (mpad,))
              for k, t in xt.items()}
        # the node ending at x joins (state p, j)'s range if >= zlo_j[p]
        adds = (xv["ZE"] >= pv["zlo0"], xv["ZE"] >= pv["zlo1"])
        c2s = tuple((jnp.where(add, jnp.maximum(g2, xv["FSE"]), g2),
                     ok2 | add) for (g2, ok2), add in zip(c2s, adds))
        e = [_entries(jnp, xv, pv, c2, wi, ps, j, GAIN_FLOOR)
             for j, c2 in enumerate(c2s)]
        return c2s, (e[0][0], (e[0][1], e[1][1]), (e[0][2], e[1][2]),
                     (e[0][3], e[1][3]))

    _, planes = jax.lax.scan(row, ((NEGa, no), (NEGa, no)),
                             jnp.arange(W, dtype=jnp.int32))
    return planes


@lru_cache(maxsize=64)
def _compiled_dp(mpad: int, W: int, nk: int):
    """Build + jit the layered one-jump DP for one (mpad, W, nk) bucket."""
    import jax
    import jax.numpy as jnp

    LROW = mpad + W + 1

    def dp(xt, pt, jp1x, cnt):
        f64 = xt["Ss"].dtype
        NEGa = jnp.asarray(NEG, f64)
        cntm = cnt[:mpad]
        # layer 0: zero intervals left — done iff the suffix is empty
        L0 = jnp.repeat(jnp.where(cnt == 0, 0.0, NEGa)[:, None], 2, axis=1)
        tail0 = jnp.zeros((LROW - mpad, 2), f64)
        rows = jnp.arange(LROW, dtype=jnp.int32)
        wis = jnp.arange(W, dtype=jnp.int32)
        FEAS, SEL, G1m, G2m = _device_planes(xt, pt, mpad, W)
        # near-tie reference: every value is a gain <= Ss[m]
        eps = TIE_RTOL * jnp.maximum(1.0, xt["Ss"][-1])

        def layer(L1, k):
            # three sliding-window unfolds of layer k-1: U*[wi, p] is the
            # value at successor x = p+1+wi (plane 0, plane 1, and the
            # cand1 jp1-premerged plane)
            L10, L11 = L1[:, 0], L1[:, 1]
            Lc1 = L1[rows, jp1x]

            def unf(_, wi):
                w1 = wi + 1
                return None, (
                    jax.lax.dynamic_slice(L10, (w1,), (mpad,)),
                    jax.lax.dynamic_slice(L11, (w1,), (mpad,)),
                    jax.lax.dynamic_slice(Lc1, (w1,), (mpad,)),
                )

            _, (U0, U1, Uc) = jax.lax.scan(unf, None, wis)

            cols, choice, tie = [], [], []
            for j in (0, 1):
                totF = jnp.where(FEAS, jnp.where(SEL[j], U1, U0), NEGa)
                tot1 = G1m[j] + Uc      # invalid entries hold NEG: stay
                tot2 = G2m[j] + U0      # ~-1e30, never win, never overflow
                M = jnp.maximum(jnp.maximum(totF, tot1), tot2)
                best = jnp.max(M, axis=0)                      # [mpad]
                # first wi reaching the best (two plain reductions: a
                # variadic argmax reduce is several times slower on CPU)
                bw = jnp.min(jnp.where(M == best[None, :], wis[:, None], W),
                             axis=0)
                # the winning kind at bw: filler 0, cand1 1, cand2 2
                at = [jnp.take_along_axis(t, bw[None], 0)[0]
                      for t in (totF, tot1)]
                kind = jnp.where(at[0] == best, 0,
                                 jnp.where(at[1] == best, 1, 2))
                tval = jnp.where(cntm <= k, jnp.asarray(0.0, f64), NEGa)
                # terminal iff nothing gains (values are sums of >= 0
                # terms, and a kept gain is > 0 on the device too)
                term = (cntm <= k) & (best <= 0)
                val = jnp.maximum(tval, best)
                cols.append(val)
                choice.append(jnp.where(term, -1, kind * W + bw)
                              .astype(jnp.int32))
                # a near tie: some candidate strictly below the best but
                # within eps.  One equal to the best on the device is a
                # copy of the same value (fillers carry values
                # unchanged) or the same sum, and gives the same value.
                lo = val - eps
                near = (tval >= lo) & (tval < val)
                for t in (totF, tot1, tot2):
                    near |= jnp.any((t >= lo) & (t < val), axis=0)
                tie.append(~term & (val > 0) & near)
            Lk = jnp.concatenate([jnp.stack(cols, axis=1), tail0], axis=0)
            return Lk, (jnp.stack(choice, axis=1), jnp.stack(tie, axis=1))

        ks = jnp.arange(1, nk, dtype=jnp.int32)
        _, (choices, ties) = jax.lax.scan(layer, L0, ks)
        return choices, ties                        # [nk-1, mpad, 2] each

    return jax.jit(dp)


def _pad_inputs(pre: _Pre):
    """Pad into a shape bucket and build the DP's 1-D tables (host numpy,
    O(mpad + W)): the x-side tables ``xt`` (length LROW = mpad + W + 1,
    read at successor x) and the p-side tables ``pt`` (length mpad, read
    at state p), which the device gets; ``_entries`` makes every [W, mpad]
    gain and mask entry from them.  The host's own lookups of cand2's
    contained-range max use the all-intervals table ``PM2`` at (zlo_j[p],
    ``ZH1x[x]``).

    Padding tasks (index >= m) have zero weight and zero state: they extend
    the last feasible jump for free, add no gain anywhere, and cnt[p >= m]
    is forced to 0 so reaching the padding means "done" for every k — the
    DP optimum over the padded instance equals the real optimum.
    """
    m, n_real, n_new = pre.m, pre.n_real, pre.n_new
    npad = max(n_real, 1)

    # -- bucketed shapes ----------------------------------------------------
    if m > 2048:
        mpad = _ceil_to(m, 256)
        nk = n_new + 1
    else:
        mpad = _pow2(max(m, 4))
        nk = _pow2(n_new + 1)

    Sw_pad = np.concatenate([pre.Sw, np.full(mpad - m, pre.Sw[-1])])
    Ss_pad = np.concatenate([pre.Ss, np.full(mpad - m, pre.Ss[-1])])
    nxt = max_feasible_ends(Sw_pad, pre.tol, np.arange(mpad + 1))

    # one-jump window, clamped at m (successors past m are dominated by the
    # x = m option; without the clamp, jumps running through the zero-weight
    # padding would inflate W to ~mpad - m)
    par = np.arange(m if m > 0 else 1)
    W1 = int((np.minimum(nxt[par], m) - par).max(initial=1))
    if m > 2048:
        W = min(_ceil_to(max(W1, 1), 256), mpad)
    else:
        W = min(_pow2(max(W1, 2)), mpad)
    LROW = mpad + W + 1

    # min cover counts on the padded axis; the padded suffix is free
    cnt = np.zeros(LROW, dtype=np.int64)
    for a in range(min(m, mpad) - 1, -1, -1):
        cnt[a] = 1 + cnt[nxt[a]]
    cnt = np.minimum(cnt, nk)

    # -- x-side tables over x in [0, LROW) ----------------------------------
    NOx = np.full(LROW, n_real, dtype=np.int64)        # node containing x
    NOx[: m + 1] = pre.node_of
    NOx[m:] = n_real
    lbs_e = np.full(npad, mpad, dtype=np.int64)
    ubs_e = np.full(npad, mpad, dtype=np.int64)
    lbs_e[:n_real] = pre.lbs
    ubs_e[:n_real] = pre.ubs
    fs = np.full(npad, NEG, dtype=np.float64)
    fs[:n_real] = pre.full_size
    PM2 = _allranges_max(fs)                           # [(npad+2), (npad+1)]

    Ssx = np.empty(LROW, dtype=np.float64)             # Ss at clamped x
    Ssx[: mpad + 1] = Ss_pad
    Ssx[mpad:] = Ss_pad[-1]
    # order code: Ssx[a] < Ssx[b] iff RK[a] < RK[b]
    RK = np.unique(Ssx, return_inverse=True)[1].reshape(-1)
    Y1x = np.empty(LROW, dtype=np.int64)               # node_of[x-1]
    Y1x[1:] = NOx[:-1]
    Y1x[0] = 0
    y1c = np.minimum(Y1x, npad - 1)
    LB1x = lbs_e[y1c]                                  # lbs[node_of[x-1]]
    lb1c = np.minimum(LB1x, mpad)
    jp1x = np.clip(Y1x + 1 - NOx, 0, 1)                # cand1 j' plane
    ZH1x = np.where((NOx < n_real) & (ubs_e[np.minimum(NOx, npad - 1)]
                                      <= np.arange(LROW)),
                    NOx, NOx - 1) + 1                  # contained hi + 1
    # the node ending at x (nodes are nonempty: at most one), -1 where
    # none or where it holds no state, and its size
    ends = np.diff(ZH1x, prepend=0)
    assert ends.max(initial=0) <= 1, "two old intervals end at one x"
    ZE = np.where((ends > 0) & (fs[np.maximum(ZH1x - 1, 0)] > 0),
                  ZH1x - 1, -1)
    xt = dict(NOx=NOx, Y1=np.where(Y1x < n_real, Y1x, -1), LB1=LB1x,
              SLB1=Ssx[lb1c], Ss=Ssx, RK=RK, RLB1=RK[lb1c], ZE=ZE,
              FSE=np.where(ZE >= 0, fs[np.maximum(ZE, 0)], NEG))

    # -- p-side tables over p in [0, mpad) ----------------------------------
    parange = np.arange(mpad)
    c0 = NOx[:mpad]                                    # node containing p
    c0c = np.minimum(c0, npad - 1)
    # straddler at p (only candidate z == c0; needs z >= gamma, i.e. j == 0)
    sval = Ssx[np.minimum(ubs_e[c0c], mpad)] - \
        Ssx[np.maximum(np.minimum(lbs_e[c0c], mpad), parange)]
    zlo0 = np.where((c0 < n_real) & (lbs_e[c0c] >= parange), c0, c0 + 1)
    pt = dict(fw=nxt[:mpad] - parange - 1, c0=c0, sval=sval,
              # the straddler gains for x >= ubst (never where it is empty)
              ubst=np.where((c0 < n_real) & (sval > 0), ubs_e[c0c], LROW),
              zlo0=zlo0, zlo1=np.maximum(zlo0, c0 + 1))

    return dict(mpad=mpad, W=W, nk=nk, LROW=LROW, nxt=nxt, cnt=cnt,
                NOx=NOx, jp1x=jp1x, xt=xt, pt=pt, ZH1x=ZH1x, PM2=PM2,
                ubs_e=ubs_e)


def _dp_args(pad) -> tuple:
    """``_compiled_dp``'s arguments, as the host copies them to the device:
    int32 and float64, O(mpad + W) bytes in all."""
    def dev(a):
        return a.astype(np.int32) if a.dtype.kind in "iu" else a

    return ({k: dev(t) for k, t in pad["xt"].items()},
            {k: dev(t) for k, t in pad["pt"].items()},
            dev(pad["jp1x"]), dev(pad["cnt"]))


def _entries_at(pad, wi, p, j: int):
    """``_entries`` on the host at broadcastable index arrays (wi, p)."""
    x = p + 1 + wi
    xv = {k: t[x] for k, t in pad["xt"].items()}
    pv = {k: t[p] for k, t in pad["pt"].items()}
    pv.update(Ss=pad["xt"]["Ss"][p], RK=pad["xt"]["RK"][p])
    # the contained-range max: one lookup in the all-ranges table
    g2 = pad["PM2"][pv["zlo1"] if j else pv["zlo0"], pad["ZH1x"][x]]
    return _entries(np, xv, pv, (g2, g2 > 0), wi, p, j)


def _host_layers(pad, choices: np.ndarray, ties: np.ndarray) -> np.ndarray:
    """IEEE float64 layer values L[k] (k = 0..nk-1) rebuilt on the host from
    the device's best transitions: each state's value is its chosen
    candidate's sum, and near-tied states take the max over the whole
    window — the same adds and maxes the DP makes, on exact inputs."""
    mpad, W, nk, LROW = pad["mpad"], pad["W"], pad["nk"], pad["LROW"]
    jp1x, cnt = pad["jp1x"], pad["cnt"]
    L = np.zeros((nk, LROW, 2))
    L[0] = np.where(cnt == 0, 0.0, NEG)[:, None]
    ps = np.arange(mpad)
    for k in range(1, nk):
        prev = L[k - 1]
        tval = np.where(cnt[:mpad] <= k, 0.0, NEG)
        for j in (0, 1):
            c = choices[k - 1, :, j].astype(np.int64)
            kind, wi = np.divmod(np.maximum(c, 0), W)
            x = ps + 1 + wi
            feas, sel, g1, g2 = _entries_at(pad, wi, ps, j)
            vF = np.where(feas, prev[x, sel.astype(np.int64)], NEG)
            v1 = g1 + prev[x, jp1x[x]]
            v2 = g2 + prev[x, 0]
            v = np.choose(kind, [vF, v1, v2])
            v = np.where(c < 0, 0.0, np.maximum(tval, v))
            tp = np.nonzero(ties[k - 1, :, j])[0]
            if tp.size:                           # full window, as the DP
                wis = np.arange(W)[None, :]
                xs = tp[:, None] + 1 + wis
                feas, sel, g1, g2 = _entries_at(pad, wis, tp[:, None], j)
                totF = np.where(feas, prev[xs, sel.astype(np.int64)], NEG)
                tot1 = g1 + prev[xs, jp1x[xs]]
                tot2 = g2 + prev[xs, 0]
                M = np.maximum(np.maximum(totF, tot1), tot2)
                v[tp] = np.maximum(tval[tp], M.max(axis=1))
            L[k, :mpad, j] = v
    return L


def ssm_jit(old: Assignment, w: np.ndarray, s: np.ndarray,
            pre: _Pre) -> MigrationPlan:
    """jit backend entry point; called by ``ssm()`` after the shared
    (backend-independent) feasibility checks have passed."""
    import jax
    import jax.numpy as jnp

    from ..compat import enable_x64

    with obs.span("plan.tables"):
        pad = _pad_inputs(pre)
    mpad, W, nk = pad["mpad"], pad["W"], pad["nk"]
    args = _dp_args(pad)
    in_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(args))
    with obs.span("plan.dp", mpad=mpad, W=W, nk=nk, in_bytes=in_bytes), \
            enable_x64():
        dp = _compiled_dp(mpad, W, nk)
        choices, ties = dp(*jax.tree_util.tree_map(jnp.asarray, args))
        choices, ties = np.asarray(choices), np.asarray(ties)
        obs.count("near_ties", int(ties.sum()))

    with obs.span("plan.rebuild"):
        L = _host_layers(pad, choices, ties)    # L[k] = layer k values
    with obs.span("plan.decode"):
        return _decode(old, s, pre, pad, L)


def _decode(old: Assignment, s: np.ndarray, pre: _Pre, pad,
            L: np.ndarray) -> MigrationPlan:
    """The optimal plan from the rebuilt layers ``L``."""
    m, n_new, n_real, n_total = pre.m, pre.n_new, pre.n_real, pre.n_total
    W = pad["W"]
    total_gain = float(L[n_new, 0, 0])
    if total_gain <= NEG / 2:
        raise Infeasible("no feasible solution found")

    # --- reconstruction: exact value-matching against the rebuilt layers --
    nxt, cnt, NOx, jp1x = pad["nxt"], pad["cnt"], pad["NOx"], pad["jp1x"]
    pt = pad["pt"]
    items, full_size = pre.items, pre.full_size
    nxt_real = np.minimum(nxt[: m + 1], m)
    new_ivs: list = [(m, m)] * n_total
    free_ivs: list = []
    x0, j, k = 0, 0, n_new
    while x0 < m:
        Gv = L[k, x0, j]
        if cnt[x0] <= k and Gv == 0.0:
            # zero-gain completion: greedy split of [x0, m)
            bs = greedy_boundaries(nxt_real, x0, m)
            free_ivs += [(bs[i], bs[i + 1]) for i in range(len(bs) - 1)]
            break
        assert k >= 1, "decode: positive value with no intervals left"
        gamma = int(NOx[x0]) + j
        prev = L[k - 1]
        nwin = min(int(nxt[x0]) - x0, W)
        wis = np.arange(nwin)
        xs = x0 + 1 + wis
        totF = prev[xs, (NOx[xs] < gamma).astype(np.int64)]
        hitF = np.nonzero(totF == Gv)[0]
        if hitF.size:                                  # filler [x0, q)
            q = x0 + 1 + int(hitF[0])
            free_ivs.append((x0, min(q, m)))
            j = 1 if NOx[q] < gamma else 0
            x0, k = q, k - 1
            continue
        _, _, g1, g2 = _entries_at(pad, wis, x0, j)
        tot1 = g1 + prev[xs, jp1x[xs]]
        hit1 = np.nonzero(tot1 == Gv)[0]
        if hit1.size:                                  # gain via cand1
            x = x0 + 1 + int(hit1[0])
            y = int(NOx[x - 1])
        else:                                          # gain via cand2
            tot2 = g2 + prev[xs, 0]
            hit2 = np.nonzero(tot2 == Gv)[0]
            assert hit2.size, "decode: no transition matches the DP value"
            x = x0 + 1 + int(hit2[0])
            g2v = float(g2[x - x0 - 1])
            c0 = int(NOx[x0])
            y = -1
            if (j == 0 and c0 < n_real and int(pad["ubs_e"][c0]) <= x
                    and float(pt["sval"][x0]) == g2v):
                y = c0                                 # straddler at x0
            else:
                zlo = int(pt["zlo1" if j else "zlo0"][x0])
                zhi = int(pad["ZH1x"][x]) - 1
                assert 0 <= zlo <= zhi < n_real, "decode: empty cand2 range"
                sub = full_size[zlo : zhi + 1]
                y = zlo + int(np.argmax(sub))
        node_id = items[y][0]
        new_ivs[node_id] = (x0, min(x, m))
        j = min(max(y + 1 - int(NOx[min(x, len(NOx) - 1)]), 0), 1)
        x0, k = x, k - 1
    used = {i for i, iv in enumerate(new_ivs) if iv[1] > iv[0]}
    free_nodes = [i for i in range(n_total) if i not in used]
    free_ivs = [(lo, hi) for lo, hi in free_ivs if hi > lo]
    assert len(free_ivs) <= len(free_nodes), "more intervals than nodes"
    for node_id, iv in zip(free_nodes, free_ivs):
        new_ivs[node_id] = iv
    new = Assignment(m, tuple(new_ivs))
    plan = _plan(old, new, s)
    assert abs(plan.gain - total_gain) < 1e-6 * max(1.0, abs(total_gain)), (
        plan.gain,
        total_gain,
    )
    return plan
