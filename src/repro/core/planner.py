"""ElasticPlanner — the framework-facing facade over the paper's algorithms.

A planner turns (current assignment, target node count, workload/state
statistics) into a MigrationPlan.  Policies:

    ssm        exact optimal single-step migration (paper §3, production
               default; backend="auto" — jit DP above _AUTO_JIT_MIN_M tasks)
    ssm_jit    same optimum, forced jit-compiled lax.scan DP (core/ssm_jit)
    ssm_numpy  same optimum, forced reference numpy DP (paper Fig. 14)
    mtm     MTM-aware: immediate + gamma-discounted projected cost (paper §4.2)
    simple  Simple_SSM oracle (paper Fig. 12 equivalent; small instances)
    adhoc   Storm-default analogue (paper's baseline)
    greedy  left-to-right trim heuristic

The planner also owns the tau schedule (the paper lets the user retune tau
per migration, §2.1) and the workload estimator hook used by the elastic
controller (runtime/elastic.py).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import numpy as np

from repro import obs

from .baselines import adhoc, greedy_trim
from .intervals import Assignment
from .mtm import MTM, PMCResult, PartitionTable, mtm_aware_plan, pmc
from .ssm import Infeasible, MigrationPlan, simple_ssm, ssm

Policy = Callable[[Assignment, int, np.ndarray, np.ndarray, float], MigrationPlan]

POLICIES = {
    "ssm": ssm,
    "ssm_jit": functools.partial(ssm, backend="jit"),
    "ssm_numpy": functools.partial(ssm, backend="numpy"),
    "simple": simple_ssm,
    "adhoc": adhoc,
    "greedy": greedy_trim,
}


@dataclass
class TauSchedule:
    """Per-migration load-balance threshold.  The paper suggests tightening
    tau when scaling up (latency-sensitive) and loosening it when rebalances
    thrash (§2.1)."""

    base: float = 1.2
    grow: Optional[float] = None      # tau when n' > n
    shrink: Optional[float] = None    # tau when n' < n

    def __call__(self, n_old: int, n_new: int) -> float:
        if n_new > n_old and self.grow is not None:
            return self.grow
        if n_new < n_old and self.shrink is not None:
            return self.shrink
        return self.base


@dataclass
class ElasticPlanner:
    policy: str = "ssm"
    tau: TauSchedule = field(default_factory=TauSchedule)
    # MTM-aware machinery (lazily built on first use)
    mtm: Optional[MTM] = None
    gamma: float = 0.8
    pmc_grid: int = 1
    pmc_limit_per_k: Optional[int] = 20_000
    # a pre-built PMC table (offline phase output); when set, "mtm" planning
    # uses it directly instead of rebuilding per workload snapshot
    fixed_pmc: Optional[PMCResult] = None
    # batched gain backend for mtm_aware_plan's scoring loop (e.g.
    # kernels.ops.pairwise_gain to route it through the Pallas kernel)
    mtm_gain_fn: Optional[Callable] = None
    _pmc: Optional[PMCResult] = None
    _pmc_key: Optional[tuple] = None

    def prepare(self, w: np.ndarray, s: np.ndarray, n_min: int, n_max: int,
                tau: Optional[float] = None) -> Optional[PMCResult]:
        """Precompute the PMC table (paper's offline phase).  No-op for
        non-MTM policies."""
        if self.policy != "mtm":
            return None
        tau = self.tau.base if tau is None else tau
        key = (len(w), float(np.asarray(w).sum()), n_min, n_max, tau,
               self.gamma, self.pmc_grid)
        if self._pmc is not None and self._pmc_key == key:
            return self._pmc
        if self.mtm is None:
            self.mtm = MTM.uniform(n_min, n_max)
        table = PartitionTable.build(
            np.asarray(w, dtype=np.float64), n_min, n_max, tau,
            grid=self.pmc_grid, limit_per_k=self.pmc_limit_per_k,
        )
        self._pmc = pmc(table, np.asarray(s, dtype=np.float64),
                        self.mtm, self.gamma)
        self._pmc_key = key
        return self._pmc

    # When a τ is infeasible (a single hot bucket exceeds the cap), relax it
    # geometrically up to relax_tau_max — the paper's "the user may decide to
    # loosen τ" (§2.1) as an automatic controller policy.
    relax_tau_max: float = 8.0

    def plan(
        self,
        old: Assignment,
        n_new: int,
        w: np.ndarray,
        s: np.ndarray,
        tau: Optional[float] = None,
    ) -> MigrationPlan:
        """A plan by ``policy``; an infeasible τ is relaxed geometrically
        up to ``relax_tau_max``.  Recorded as a ``plan.search`` span
        (counter ``attempts``: relaxations + 1)."""
        with obs.span("plan.search", m=old.m, policy=self.policy):
            w = np.asarray(w, dtype=np.float64)
            s = np.asarray(s, dtype=np.float64)
            n_old = sum(1 for lo, hi in old.intervals if hi > lo)
            t = self.tau(n_old, n_new) if tau is None else tau
            if self.policy == "mtm":
                res = self.fixed_pmc
                if res is None:
                    res = self.prepare(
                        w, s, min(n_old, n_new),
                        max(n_old, n_new,
                            self.mtm.n_max if self.mtm else n_new), tau=t)
                return mtm_aware_plan(old, n_new, s, res,
                                      gain_fn=self.mtm_gain_fn)
            fn = POLICIES.get(self.policy)
            if fn is None:
                raise ValueError(f"unknown policy {self.policy!r}")
            while True:
                obs.count("attempts")
                try:
                    return fn(old, n_new, w, s, t)
                except Infeasible:
                    if t >= self.relax_tau_max:
                        raise
                    t = min(t * 1.5 + 0.1, self.relax_tau_max)
