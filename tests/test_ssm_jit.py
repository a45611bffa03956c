"""jit SSM backend gates: oracle agreement across all solvers and
Infeasible consistency at cap boundaries.

The heavy differential sweep lives in benchmarks/ssm_oracles.py (one
harness, N solvers — also run by ``scripts/ci.sh fast``); the tests here
import it so the comparison logic cannot drift from the benchmark."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.ssm_oracles import (  # noqa: E402
    INFEASIBLE, SOLVERS, _agrees, _answer, crafted_instances,
    random_instance, run,
)
from repro.core.intervals import Assignment  # noqa: E402


@pytest.mark.slow
def test_oracle_harness_50_plus_randomized_instances():
    """brute/simple/ssm_numpy/ssm_jit agree (feasibility exactly, gain to
    rtol 1e-9) on 52 randomized + 4 crafted instances.  Raises on any
    disagreement."""
    gains = run(n_tiny=20, n_big=32, seed=0, verbose=False)
    assert len(gains["ssm_jit"]) >= 54
    assert len(gains["simple"]) == len(gains["ssm_jit"])


def test_quick_jit_vs_simple_agreement():
    """Fast-tier smoke: a dozen tiny randomized instances, jit vs simple."""
    rng = np.random.default_rng(42)
    for _ in range(12):
        inst = random_instance(rng, tiny=True)
        got = _answer(SOLVERS["ssm_jit"], inst)
        ref = _answer(SOLVERS["simple"], inst)
        assert _agrees(got, ref), (inst, got, ref)


def test_cap_boundary_crafted_instances_consistent():
    """The satellite-3 regression set: exact-cap single task, over-cap
    task, n' below the min cover count, all-zero weights."""
    for inst in crafted_instances():
        tiny = inst[0].m <= 20
        answers = {name: _answer(fn, inst)
                   for name, fn in SOLVERS.items()
                   if name != "brute" or tiny}
        ref = answers["simple"]
        for name, got in answers.items():
            assert _agrees(got, ref), (name, got, ref)


def test_exact_cap_crossing_all_solvers_agree():
    """Sweep a single hot task's weight across the cap: with n'=2, τ=0.25,
    w=[x,1,1,1] the cap (1+τ)(x+3)/2 equals x exactly at x=5.0.  Every
    solver (brute included, m=4) must flip feasibility at the same x —
    the unified feasible_tol predicate is what guarantees it."""
    s = np.array([2.0, 1.0, 1.0, 1.0])
    old = Assignment.from_boundaries(4, [0, 2, 4])
    for x in (5.0, np.nextafter(5.0, 4.0), np.nextafter(5.0, 6.0),
              5.0 * (1 - 1e-6), 5.0 * (1 + 1e-6)):
        inst = (old, 2, np.array([x, 1.0, 1.0, 1.0]), s, 0.25)
        answers = {name: _answer(fn, inst) for name, fn in SOLVERS.items()}
        ref = answers["simple"]
        for name, got in answers.items():
            assert _agrees(got, ref), (x, name, got, ref)
    # the exactly-at-cap point itself must be feasible (tolerance eats the
    # representation error), not a coin flip per solver
    inst = (old, 2, np.array([5.0, 1.0, 1.0, 1.0]), s, 0.25)
    assert _answer(SOLVERS["simple"], inst) != INFEASIBLE


def test_enable_x64_is_scoped():
    """The DP's float64 scope is restored on exit: the rest of the process
    stays float32."""
    import jax.numpy as jnp

    from repro.compat import enable_x64
    with enable_x64():
        assert jnp.asarray(np.float64(1.5)).dtype == jnp.float64
    assert jnp.asarray(np.float64(1.5)).dtype == jnp.float32


def _oracle_planes(pre):
    """The full [W, mpad] gain and mask planes, built the way the host
    built them before the device took over (numpy stride tricks over the
    1-D tables, ``g1 > 0`` and ``g2 > 0`` decided in IEEE float64): the
    reference that ``ssm_jit._entries`` must equal bit for bit."""
    from numpy.lib.stride_tricks import sliding_window_view

    from repro.core.intervals import max_feasible_ends
    from repro.core.ssm import NEG
    from repro.core.ssm_jit import _allranges_max, _ceil_to, _pow2

    m, n_real, n_new = pre.m, pre.n_real, pre.n_new
    npad = max(n_real, 1)
    if m > 2048:
        mpad = _ceil_to(m, 256)
        nk = n_new + 1
    else:
        mpad = _pow2(max(m, 4))
        nk = _pow2(n_new + 1)
    Sw_pad = np.concatenate([pre.Sw, np.full(mpad - m, pre.Sw[-1])])
    Ss_pad = np.concatenate([pre.Ss, np.full(mpad - m, pre.Ss[-1])])
    nxt = max_feasible_ends(Sw_pad, pre.tol, np.arange(mpad + 1))
    par = np.arange(m if m > 0 else 1)
    W1 = int((np.minimum(nxt[par], m) - par).max(initial=1))
    if m > 2048:
        W = min(_ceil_to(max(W1, 1), 256), mpad)
    else:
        W = min(_pow2(max(W1, 2)), mpad)
    LROW = mpad + W + 1

    NOx = np.full(LROW, n_real, dtype=np.int64)
    NOx[: m + 1] = pre.node_of
    NOx[m:] = n_real
    lbs_e = np.full(npad, mpad, dtype=np.int64)
    ubs_e = np.full(npad, mpad, dtype=np.int64)
    lbs_e[:n_real] = pre.lbs
    ubs_e[:n_real] = pre.ubs
    fs = np.full(npad, NEG, dtype=np.float64)
    fs[:n_real] = pre.full_size
    PM2 = _allranges_max(fs)
    Ssx = np.empty(LROW, dtype=np.float64)
    Ssx[: mpad + 1] = Ss_pad
    Ssx[mpad:] = Ss_pad[-1]
    Y1x = np.empty(LROW, dtype=np.int64)
    Y1x[1:] = NOx[:-1]
    Y1x[0] = 0
    y1c = np.minimum(Y1x, npad - 1)
    LB1x = lbs_e[y1c]
    SS_LB1x = Ssx[np.minimum(LB1x, mpad)]
    ZH1x = np.where((NOx < n_real) & (ubs_e[np.minimum(NOx, npad - 1)]
                                      <= np.arange(LROW)),
                    NOx, NOx - 1) + 1
    parange = np.arange(mpad)
    c0 = NOx[:mpad]
    c0c = np.minimum(c0, npad - 1)
    sval = Ssx[np.minimum(ubs_e[c0c], mpad)] - \
        Ssx[np.maximum(np.minimum(lbs_e[c0c], mpad), parange)]
    zlo0 = np.where((c0 < n_real) & (lbs_e[c0c] >= parange), c0, c0 + 1)
    zlo_j = [np.maximum(zlo0, c0 + j) for j in (0, 1)]

    def unf(T):
        return sliding_window_view(T, mpad)[1 : W + 1]

    wi_col = np.arange(W, dtype=np.int64)[:, None]
    FEAS = wi_col <= (nxt[:mpad] - parange - 1)[None, :]
    Xu = wi_col + parange[None, :] + 1
    Y1u = unf(Y1x)
    g1 = unf(Ssx) - np.where(unf(LB1x) >= parange[None, :],
                             unf(SS_LB1x), Ss_pad[:mpad][None, :])
    G1m, G2m, SEL = [], [], []
    idx_x = unf(ZH1x)
    for j in (0, 1):
        gam = (c0 + j)[None, :]
        v1 = FEAS & (Y1u >= gam) & (Y1u < n_real) & (g1 > 0)
        G1m.append(np.where(v1, g1, NEG))
        g2 = np.take(PM2.reshape(-1),
                     zlo_j[j][None, :] * (npad + 1) + idx_x)
        if j == 0:
            s_ok = (c0 < n_real)[None, :] & (ubs_e[c0c][None, :] <= Xu)
            g2 = np.maximum(g2, np.where(s_ok, sval[None, :], NEG))
        G2m.append(np.where(FEAS & (g2 > 0), g2, NEG))
        SEL.append(unf(NOx) < gam)
    return dict(mpad=mpad, W=W, nk=nk, FEAS=FEAS, SEL=SEL, G1m=G1m,
                G2m=G2m)


def _pre_of(monkeypatch, inst):
    """The shared preparation ``ssm()`` hands the jit backend."""
    from repro.core import ssm_jit
    from repro.core.ssm import ssm

    seen = []
    with monkeypatch.context() as mp:
        mp.setattr(ssm_jit, "ssm_jit", lambda old, w, s, pre: seen.append(
            pre))
        ssm(*inst, backend="jit")
    return seen[0]


def _olmo_instances():
    """The olmo churn cell's planner input: 24 requests routed into 4,096
    buckets (w 1 + 1e-9 there, 1e-9 elsewhere; 1,089 positions of 131,072
    bytes of K and V each, 0 elsewhere), tau 0.2: 2 -> 4 from the even
    split, then 4 -> 2 from that plan."""
    from repro.core.ssm import ssm
    from repro.runtime.state import route

    m = 4096
    b = route(np.arange(24) + 1000, m)
    counts = np.bincount(b, minlength=m).astype(np.float64)
    w, s = counts + 1e-9, counts * (1089 * 131072)
    old2 = Assignment.from_boundaries(m, [0, 2048, 4096])
    up = (old2, 4, w, s, 0.2)
    old4 = ssm(*up, backend="jit").new
    return {"olmo_2to4": up, "olmo_4to2": (old4, 2, w, s, 0.2)}


def _entry_cases():
    rng = np.random.default_rng(7)
    cases = {f"random{i}": random_instance(rng, tiny=i < 2)
             for i in range(4)}
    m = 2500                                  # > 2048: the 256-rounded path
    old = Assignment.from_boundaries(m, list(np.linspace(0, m, 6).round()
                                             .astype(int)))
    s = rng.uniform(0.1, 3.0, m)
    s[300:900] = 0.0
    cases["m2500"] = (old, 8, rng.uniform(0.2, 2.0, m), s, 0.4)
    return cases


@pytest.mark.parametrize("case", ["random0", "random1", "random2",
                                  "random3", "m2500", "olmo_2to4",
                                  "olmo_4to2"])
def test_entries_equal_full_plane_oracle(monkeypatch, case):
    """The pointwise entries the host rebuilds and decodes with
    (``_entries`` on ``_pad_inputs``' 1-D tables, integer masks) and the
    planes the device DP builds from the same tables (``_device_planes``,
    here on the CPU) equal the full-plane numpy build bit for bit at every
    (wi, p) for both j, NEG positions included."""
    import jax

    from repro.compat import enable_x64
    from repro.core import ssm_jit

    inst = (_olmo_instances() if case.startswith("olmo")
            else _entry_cases())[case]
    pre = _pre_of(monkeypatch, inst)
    ref = _oracle_planes(pre)
    pad = ssm_jit._pad_inputs(pre)
    mpad, W = pad["mpad"], pad["W"]
    assert (mpad, W, pad["nk"]) == (ref["mpad"], ref["W"], ref["nk"])
    ps = np.arange(mpad)[None, :]
    for lo in range(0, W, 512):                  # row blocks: bounded memory
        wis = np.arange(lo, min(lo + 512, W))[:, None]
        for j in (0, 1):
            feas, sel, g1, g2 = ssm_jit._entries_at(pad, wis, ps, j)
            rows = slice(lo, lo + len(wis))
            assert np.array_equal(feas, ref["FEAS"][rows])
            assert np.array_equal(sel, ref["SEL"][j][rows])
            for got, want in ((g1, ref["G1m"][j]), (g2, ref["G2m"][j])):
                assert np.array_equal(got.view(np.int64),
                                      want[rows].view(np.int64)), (case, j)
    xt, pt = ssm_jit._dp_args(pad)[:2]
    with enable_x64():
        FEAS, SEL, G1m, G2m = jax.jit(ssm_jit._device_planes,
                                      static_argnums=(2, 3))(xt, pt, mpad, W)
    assert np.array_equal(np.asarray(FEAS), ref["FEAS"])
    for j in (0, 1):
        assert np.array_equal(np.asarray(SEL[j]), ref["SEL"][j])
        for got, want in ((G1m[j], ref["G1m"][j]), (G2m[j], ref["G2m"][j])):
            assert np.array_equal(np.asarray(got).view(np.int64),
                                  want.view(np.int64)), (case, j)


def _plan_under_fuzz(monkeypatch, inst, rng):
    """The jit plan with every float64 input of the device DP (the 1-D
    prefix-sum tables, the straddler gains, the all-intervals table, layer
    0) perturbed by ~1e-13 relative, more than a TPU's rounding."""
    import jax
    import jax.numpy as jnp

    from repro.core import ssm_jit
    from repro.core.ssm import NEG, ssm

    real = ssm_jit._compiled_dp

    def inexact(mpad, W, nk):
        dp = real(mpad, W, nk)

        def fuzz(g):
            if g.dtype != jnp.float64:
                return g
            noise = rng.uniform(-1e-13, 1e-13, g.shape)
            return jnp.where(g > NEG / 2, g * (1 + noise), g)

        return lambda *args: dp(*jax.tree_util.tree_map(fuzz, args))

    with monkeypatch.context() as mp:
        mp.setattr(ssm_jit, "_compiled_dp", inexact)
        return ssm(*inst, backend="jit")


@pytest.mark.parametrize("m", [64, 1024])
def test_plan_independent_of_device_rounding(monkeypatch, m):
    """A TPU's float64 is emulated and not IEEE-exact.  Feed the device DP
    inputs perturbed by ~1e-13 relative (more than a TPU's rounding): the
    plan must equal the exact one, since the host rebuilds the layer
    values in IEEE float64 from the device's transitions."""
    from repro.core.ssm import ssm

    rng = np.random.default_rng(m)
    old = Assignment.from_boundaries(m, list(np.linspace(0, m, 7).round()
                                             .astype(int)))
    inst = (old, 9, rng.uniform(0.2, 2.0, m), rng.uniform(0.1, 3.0, m), 0.4)
    exact = ssm(*inst, backend="jit")
    got = _plan_under_fuzz(monkeypatch, inst, rng)
    assert got.new.intervals == exact.new.intervals
    assert got.gain == exact.gain


def test_plan_independent_of_device_rounding_small_gains(monkeypatch):
    """Gains small next to Ss[m], with zero-size tasks: one task holds
    1e8, the rest about 1 each (near-equal sums, 1e-7 apart), 1e-6 or
    nothing.
    The device's subtraction errs relative to Ss[m], far more than these
    gains differ; the near-tie reference max(1, Ss[m]) must still send
    every state it could misorder back to the host."""
    from repro.core.ssm import ssm

    m = 256
    rng = np.random.default_rng(3)
    s = 1.0 + 1e-7 * rng.integers(0, 4, m)
    s[0] = 1e8
    s[100:140] = 0.0
    s[-24:] = 1e-6              # below the device's error on Ss[x] - Ss[a]
    old = Assignment.from_boundaries(m, list(np.linspace(0, m, 7).round()
                                             .astype(int)))
    inst = (old, 9, rng.uniform(0.2, 2.0, m), s, 0.4)
    exact = ssm(*inst, backend="jit")
    assert exact.gain == ssm(*inst, backend="numpy").gain
    for _ in range(3):
        got = _plan_under_fuzz(monkeypatch, inst, rng)
        assert got.new.intervals == exact.new.intervals
        assert got.gain == exact.gain
