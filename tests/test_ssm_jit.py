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


@pytest.mark.parametrize("m", [64, 1024])
def test_plan_independent_of_device_rounding(monkeypatch, m):
    """A TPU's float64 is emulated and not IEEE-exact.  Feed the device DP
    inputs perturbed by ~1e-13 relative (more than a TPU's rounding): the
    plan must equal the exact one, since the host rebuilds the layer
    values in IEEE float64 from the device's transitions."""
    import jax.numpy as jnp

    from repro.core import ssm_jit
    from repro.core.ssm import NEG, ssm

    rng = np.random.default_rng(m)
    old = Assignment.from_boundaries(m, list(np.linspace(0, m, 7).round()
                                             .astype(int)))
    inst = (old, 9, rng.uniform(0.2, 2.0, m), rng.uniform(0.1, 3.0, m), 0.4)
    exact = ssm(*inst, backend="jit")

    real = ssm_jit._compiled_dp

    def inexact(mpad, W, nk):
        dp = real(mpad, W, nk)

        def run(G1m, G2m, *rest):
            def fuzz(g):
                noise = rng.uniform(-1e-13, 1e-13, g.shape)
                return jnp.where(g > NEG / 2, g * (1 + noise), g)
            return dp(fuzz(G1m), fuzz(G2m), *rest)
        return run

    monkeypatch.setattr(ssm_jit, "_compiled_dp", inexact)
    got = ssm(*inst, backend="jit")
    assert got.new.intervals == exact.new.intervals
    assert got.gain == exact.gain
