"""ElasticController: ties the planner, executor, checkpoints and fault
tolerance together — the component a cluster scheduler talks to.

Responsibilities:
* watch per-bucket workload (w_j) and state sizes (|s_j|),
* decide/accept topology changes (scale up/down, rebalance on skew,
  straggler reweighting, failure recovery),
* compute the migration strategy via ElasticPlanner (ssm | mtm | baselines),
* execute it via MigrationExecutor (live / progressive / suspend),
* keep the node-count history that estimates the MTM (paper §2.2),
* periodic checkpoints; restore-with-resharding on restart.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Set, Tuple

import numpy as np

from repro import obs
from repro.core import (
    Assignment, ElasticPlanner, MigrationPlan, MTM, satisfies_balance,
)
from .checkpoint import CheckpointManager
from .control import DecisionRecord
from .ft import SpeedTracker, recovery_plan, restored_bytes
from .migration import MigrationExecutor, MigrationReport
from .state import BucketedState


@dataclass
class ElasticEvent:
    """Legacy view of one topology change.  The controller's source of
    truth is now the ``DecisionRecord`` log shared with the closed-loop
    control plane (``runtime.control``); ``ElasticController.events``
    derives these from it."""

    kind: str                      # scale | rebalance | recover | straggler
    n_before: int
    n_after: int
    cost_bytes: float
    duration_s: float
    details: dict = field(default_factory=dict)


class ElasticController:
    def __init__(self, m: int, n_nodes: int,
                 planner: Optional[ElasticPlanner] = None,
                 executor: Optional[MigrationExecutor] = None,
                 ckpt: Optional[CheckpointManager] = None,
                 tau: float = 1.2, strategy: Optional[str] = None,
                 fluid_batch: int = 1):
        cuts = np.linspace(0, m, n_nodes + 1).round().astype(int)
        self.assign = Assignment.from_boundaries(m, list(cuts))
        self.m = m
        self.tau = tau
        self.planner = planner or ElasticPlanner(policy="ssm")
        if executor is not None and (strategy is not None
                                     or fluid_batch != 1):
            raise ValueError("pass either executor or strategy/fluid_batch, "
                             "not both (set them on the executor instead)")
        self.executor = executor or MigrationExecutor(
            mode=strategy or "live", fluid_batch=fluid_batch)
        self.ckpt = ckpt
        self.history: List[int] = [n_nodes]
        self.speeds = SpeedTracker(n_nodes)
        self.decisions: List[DecisionRecord] = []

    @property
    def events(self) -> List[ElasticEvent]:
        """Legacy event log, derived from the shared decision records."""
        return [ElasticEvent(
            kind=d.action, n_before=d.n_before, n_after=d.n_after,
            cost_bytes=d.cost_bytes, duration_s=d.duration_s,
            details=dict(d.signals)) for d in self.decisions]

    # -- observations --------------------------------------------------------
    @property
    def n_nodes(self) -> int:
        return sum(1 for lo, hi in self.assign.intervals if hi > lo)

    def balance_violated(self, w: np.ndarray) -> bool:
        return not satisfies_balance(self.assign, w, self.n_nodes, self.tau)

    def estimate_mtm(self, n_min: int, n_max: int) -> MTM:
        return MTM.estimate(self.history, n_min, n_max)

    # -- actions --------------------------------------------------------------
    def _apply(self, plan: MigrationPlan, state: BucketedState,
               kind: str, reason: str = "", **details
               ) -> Tuple[MigrationPlan, MigrationReport]:
        placement = self.assign.owner_of()
        report = self.executor.execute(plan, state, placement)
        n_before = self.n_nodes
        alive_before = {i for i, (lo, hi) in enumerate(self.assign.intervals)
                        if hi > lo}
        self.assign = plan.new
        alive_after = {i for i, (lo, hi) in enumerate(self.assign.intervals)
                       if hi > lo}
        # the EWMA tracker must follow the topology: survivors (nonempty
        # before AND after) keep their estimate, new/vacated slots reset
        self.speeds.resize(len(self.assign.intervals),
                           keep=sorted(alive_before & alive_after))
        self.history.append(self.n_nodes)
        self.decisions.append(DecisionRecord(
            t=len(self.history) - 2, action=kind, n_before=n_before,
            n_after=self.n_nodes, reason=reason,
            strategy=self.executor.mode,
            cost_bytes=plan.cost,
            restored_bytes=float(details.get("checkpoint_bytes", 0.0)),
            duration_s=report.duration_s, signals=details))
        return plan, report

    def scale(self, n_new: int, w: np.ndarray, state: BucketedState,
              tau: Optional[float] = None):
        """Plan and execute a move to ``n_new`` nodes, recorded as one
        ``elastic.scale`` span around planning, plan check and transfer."""
        with obs.span("elastic.scale", n_before=self.n_nodes) as sp:
            plan = self.planner.plan(self.assign, n_new, w,
                                     state.bucket_bytes(),
                                     tau=tau if tau is not None else self.tau)
            out = self._apply(plan, state, "scale",
                              reason=f"requested n={n_new}")
            sp.attrs["n_after"] = self.n_nodes
        return out

    def rebalance(self, w: np.ndarray, state: BucketedState,
                  reason: str = "requested"):
        plan = self.planner.plan(self.assign, self.n_nodes, w,
                                 state.bucket_bytes(), tau=self.tau)
        return self._apply(plan, state, "rebalance", reason=reason)

    def maybe_rebalance(self, w: np.ndarray, state: BucketedState):
        if self.balance_violated(w):
            return self.rebalance(w, state,
                                  reason=f"τ={self.tau} balance violated")
        return None

    def recover(self, failed: Set[int], w: np.ndarray, state: BucketedState,
                n_new: Optional[int] = None):
        """Failure recovery: lost buckets restored from checkpoint, surviving
        state kept in place (ft.recovery_plan)."""
        s = state.bucket_bytes()
        n_target = n_new if n_new is not None else self.n_nodes - len(failed)
        plan = recovery_plan(self.assign, failed, n_target, w, s, self.tau)
        ck_bytes = restored_bytes(self.assign, failed, s)
        return self._apply(plan, state, "recover",
                           reason=f"lost nodes {sorted(failed)}",
                           failed=sorted(failed), checkpoint_bytes=ck_bytes)

    def checkpoint(self, step: int, state: BucketedState, extra=None,
                   async_: bool = True):
        if self.ckpt is None:
            raise RuntimeError("no CheckpointManager configured")
        self.ckpt.save(step, state, self.assign, extra=extra, async_=async_)
