"""Migration executor (paper §5): turn a MigrationPlan into scheduled bucket
moves and run them — suspended, live, or progressive.

* ``move_list``        — diff two assignments into per-bucket moves.
* ``schedule_phases``  — Rödiger et al. [27]-style phase construction: pack
                         moves into phases so every node's uplink and
                         downlink bytes per phase are balanced; total time
                         ≈ Σ_phase max_node(bytes)/BW instead of Σ all bytes
                         through one bottleneck link.
* ``schedule_rounds``  — Megaphone-style conflict-free parallel rounds:
                         each round is a maximum bipartite matching
                         (``hopcroft_karp``) over links with pending moves,
                         so every node sends at most one bucket batch and
                         receives at most one per round; ``round_windows``
                         turns the rounds into per-bucket pause windows
                         where a bucket stops only for its own transfer.
* ``SimBackend``       — byte/clock accounting (benchmarks fig8/fig11).
* ``JaxBackend``       — executes phases on REAL jax state, wall-clock
                         measured: row-level cache resharding for
                         ``DeviceBucketedState`` (the live serving path),
                         whole-bucket device_put for host pytrees.
* ``make_migration_step`` — a jit-able resharding step for the dry run:
                         uniform-bucket state [m, ...] sharded over the
                         elastic axis migrates via gather, which XLA lowers
                         to all-to-all/collective-permute; its HLO collective
                         bytes are compared against the planner's predicted
                         cost in benchmarks/migration_dryrun.py.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core import Assignment, MigrationPlan
from .state import BucketedState


@dataclass(frozen=True)
class Move:
    bucket: int
    src: int
    dst: int
    nbytes: float


def move_list(plan: MigrationPlan, bucket_bytes: np.ndarray) -> List[Move]:
    old_owner = plan.old.owner_of()
    n_total = max(plan.old.n_nodes, plan.new.n_nodes)
    new_owner = plan.new.padded(n_total).owner_of()
    out: List[Move] = []
    for j in range(plan.old.m):
        if old_owner[j] != new_owner[j]:
            out.append(Move(j, int(old_owner[j]), int(new_owner[j]),
                            float(bucket_bytes[j])))
    return out


def schedule_phases(moves: Sequence[Move],
                    phase_budget: Optional[float] = None
                    ) -> List[List[Move]]:
    """Greedy phase packing balancing per-node up/down bytes.

    ``phase_budget`` defaults to total bytes / #endpoints (so phases are few
    but per-node balanced); pass a smaller budget (progressive mode) to
    bound simultaneously-suspended buckets.  Each phase admits a move iff
    both endpoints stay within budget; always ≥1 move per phase.
    """
    if not moves:
        return []
    max_move = max(m.nbytes for m in moves)
    if phase_budget is None:
        endpoints = {m.src for m in moves} | {m.dst for m in moves}
        total = sum(m.nbytes for m in moves)
        phase_budget = total / max(len(endpoints), 1)
    budget = max(phase_budget, max_move)
    remaining = sorted(moves, key=lambda m: -m.nbytes)
    phases: List[List[Move]] = []
    while remaining:
        up: Dict[int, float] = {}
        down: Dict[int, float] = {}
        phase: List[Move] = []
        rest: List[Move] = []
        for mv in remaining:
            if (up.get(mv.src, 0.0) + mv.nbytes <= budget
                    and down.get(mv.dst, 0.0) + mv.nbytes <= budget):
                phase.append(mv)
                up[mv.src] = up.get(mv.src, 0.0) + mv.nbytes
                down[mv.dst] = down.get(mv.dst, 0.0) + mv.nbytes
            else:
                rest.append(mv)
        if not phase:  # can't happen (budget >= max move), but stay safe
            phase, rest = [rest[0]], rest[1:]
        phases.append(phase)
        remaining = rest
    return phases


def phase_duration(phase: Sequence[Move], bw_bytes_per_s: float) -> float:
    """A phase completes when the busiest link finishes (full-duplex)."""
    up: Dict[int, float] = {}
    down: Dict[int, float] = {}
    for mv in phase:
        up[mv.src] = up.get(mv.src, 0.0) + mv.nbytes
        down[mv.dst] = down.get(mv.dst, 0.0) + mv.nbytes
    worst = max(list(up.values()) + list(down.values()) + [0.0])
    return worst / bw_bytes_per_s


def naive_duration(moves: Sequence[Move], bw_bytes_per_s: float) -> float:
    """Unscheduled baseline: the busiest node serializes ALL its traffic and
    transfers run sequentially per node pair (kill-restart style restore)."""
    total = sum(m.nbytes for m in moves)
    return total / bw_bytes_per_s


def fluid_budget(bucket_bytes: np.ndarray, batch: int) -> float:
    """Phase budget for Megaphone-style fluid migration: at most ``batch``
    buckets' worth of bytes in flight per node per phase.  batch=1 is pure
    fluid (each bucket's pause ≈ its own transfer); large batches recover
    live migration's single bulk phase; batch=max_inflight matches the
    progressive mode."""
    mx = float(bucket_bytes.max()) if len(bucket_bytes) else 1.0
    return max(batch, 1) * mx


def strategy_schedule(moves: Sequence[Move], bucket_bytes: np.ndarray,
                      mode: str, max_inflight: int = 4,
                      fluid_batch: int = 1) -> List[List[Move]]:
    """The phase/round structure strategy ``mode`` executes — the single
    dispatch shared by ``MigrationExecutor``, ``serving.strategy_windows``
    and ``analysis.plancheck``, so the verifier always checks exactly the
    schedule the runtime runs (no checker/executor drift).

    suspend / kill_restart → one bulk transfer; progressive → phases with
    ``max_inflight`` buckets' budget per node; fluid → ``fluid_budget``
    phases; batched_fluid → Hopcroft–Karp matching rounds; live → default
    balanced phases.
    """
    if not moves:
        return []
    bb = np.asarray(bucket_bytes, dtype=np.float64)
    if mode in ("suspend", "kill_restart"):
        return [list(moves)]
    if mode == "batched_fluid":
        return schedule_rounds(moves, batch=fluid_batch)
    if mode == "progressive":
        budget = max_inflight * (float(bb.max()) if len(bb) else 1.0)
        return schedule_phases(moves, phase_budget=budget)
    if mode == "fluid":
        return schedule_phases(moves,
                               phase_budget=fluid_budget(bb, fluid_batch))
    return schedule_phases(moves)                 # live


def bucket_windows(phases: Sequence[Sequence[Move]], bw_bytes_per_s: float,
                   m: int, fluid: bool = False, sync_s: float = 0.0
                   ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Per-bucket unavailability windows [from, until) implied by running the
    phases back-to-back, plus the total migration duration.

    With ``fluid=False`` (paper §5.2 live/progressive semantics) a moving
    bucket stops at its old owner when the migration *begins*, so its window
    opens at 0 and closes when its phase lands.  With ``fluid=True``
    (Megaphone, Hoffmann et al. 1812.01371) a bucket keeps processing until
    its own phase starts: the window is exactly its phase's [start, end).

    ``sync_s`` is the per-phase coordination cost (the routing-table update
    every node must apply before the next phase may start — §5.2's routing
    table, Megaphone's reconfiguration broadcast).  It extends the clock
    after every phase (including the last: the final update still has to
    propagate) but pauses no bucket — tuples routed with a stale table are
    forwarded, which the simulators charge separately.
    """
    un_from = np.zeros(m)
    un_until = np.zeros(m)
    clock = 0.0
    for ph in phases:
        dur = phase_duration(ph, bw_bytes_per_s)
        for mv in ph:
            un_from[mv.bucket] = clock if fluid else 0.0
            un_until[mv.bucket] = clock + dur
        clock += dur + sync_s
    return un_from, un_until, clock


# ---------------------------------------------------------------------------
# Batched-fluid rounds (Megaphone: conflict-free parallel mini-migrations)
# ---------------------------------------------------------------------------

def hopcroft_karp(adj: Dict[int, Sequence[int]]) -> Dict[int, int]:
    """Maximum bipartite matching, O(E·√V) (Hopcroft–Karp, pure python).

    ``adj`` maps left vertices (sender node ids) to the right vertices
    (receiver node ids) they have an edge to; the two sides are separate
    namespaces, so a node acting as both sender and receiver may appear on
    both sides under the same id.  Returns the left→right matching as a
    dict.  Deterministic: vertices are scanned in sorted order, so runs are
    reproducible and the simulators' differential tests stay exact.
    """
    from collections import deque

    INF = float("inf")
    left = sorted(adj)
    edges = {u: sorted(set(adj[u])) for u in left}
    match_l: Dict[int, Optional[int]] = {u: None for u in left}
    match_r: Dict[int, Optional[int]] = {}
    dist: Dict[int, float] = {}

    def bfs() -> bool:
        q = deque()
        for u in left:
            if match_l[u] is None:
                dist[u] = 0.0
                q.append(u)
            else:
                dist[u] = INF
        found = False
        while q:
            u = q.popleft()
            for v in edges[u]:
                w = match_r.get(v)
                if w is None:
                    found = True
                elif dist[w] == INF:
                    dist[w] = dist[u] + 1.0
                    q.append(w)
        return found

    def dfs(u: int) -> bool:
        for v in edges[u]:
            w = match_r.get(v)
            if w is None or (dist[w] == dist[u] + 1.0 and dfs(w)):
                match_l[u] = v
                match_r[v] = u
                return True
        dist[u] = INF
        return False

    while bfs():
        for u in left:
            if match_l[u] is None:
                dfs(u)
    return {u: v for u, v in match_l.items() if v is not None}


def schedule_rounds(moves: Sequence[Move], batch: int = 1
                    ) -> List[List[Move]]:
    """Conflict-free parallel rounds (Megaphone's batched migration).

    Group the moves by directed link (src, dst); while any link has pending
    buckets, build a maximum matching over those links with Hopcroft–Karp
    and let every matched link ship one *bucket batch* that round: its
    largest pending buckets up to ``batch · max(bucket bytes)`` bytes
    (always at least one) — the same per-node in-flight budget
    ``fluid_budget`` gives the fluid strategy, so the two knobs are
    directly comparable.  Each node sends at most one batch and receives
    at most one per round; no two links share an endpoint, so every
    transfer in a round proceeds at full per-link bandwidth and the round
    lasts exactly as long as its slowest link.

    Compared to ``schedule_phases`` (greedy per-node byte packing), the
    matching keeps every movable node busy every round and the batch
    amortizes the per-round coordination barrier
    (``SimConfig.phase_sync_s``) that per-bucket fluid pays once per
    phase.  Rounds cover exactly ``moves``: no bucket dropped or shipped
    twice.
    """
    if not moves:
        return []
    cap = max(int(batch), 1) * max(mv.nbytes for mv in moves)
    pending: Dict[Tuple[int, int], List[Move]] = {}
    for mv in moves:
        pending.setdefault((mv.src, mv.dst), []).append(mv)
    for q in pending.values():
        q.sort(key=lambda mv: (-mv.nbytes, mv.bucket))
    rounds: List[List[Move]] = []
    while pending:
        adj: Dict[int, List[int]] = {}
        for src, dst in pending:
            adj.setdefault(src, []).append(dst)
        matching = hopcroft_karp(adj)
        rnd: List[Move] = []
        for src in sorted(matching):
            link = (src, matching[src])
            q = pending[link]
            take, sent = 1, q[0].nbytes          # ≥ 1 move per round
            while take < len(q) and sent + q[take].nbytes <= cap:
                sent += q[take].nbytes
                take += 1
            rnd.extend(q[:take])
            del q[:take]
            if not q:
                del pending[link]
        rounds.append(rnd)    # matching is non-empty while moves pend
    return rounds


def round_windows(rounds: Sequence[Sequence[Move]], bw_bytes_per_s: float,
                  m: int, sync_s: float = 0.0
                  ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Per-bucket pause windows [start, end) for batched-fluid rounds.

    Within a round every matched link ships its batch *sequentially*, so a
    bucket is paused exactly for its own transfer (``nbytes``/BW) — the
    fluid guarantee survives batching.  The round barrier advances the
    clock by the slowest link's total plus ``sync_s`` (the routing-table
    update between rounds; see ``bucket_windows``).  Returns
    (pause_start[m], pause_end[m], total migration duration).
    """
    un_from = np.zeros(m)
    un_until = np.zeros(m)
    clock = 0.0
    for rnd in rounds:
        link_t: Dict[Tuple[int, int], float] = {}
        dur = 0.0
        for mv in rnd:
            off = link_t.get((mv.src, mv.dst), 0.0)
            t = mv.nbytes / bw_bytes_per_s
            un_from[mv.bucket] = clock + off
            un_until[mv.bucket] = clock + off + t
            link_t[(mv.src, mv.dst)] = off + t
            dur = max(dur, off + t)
        clock += dur + sync_s
    return un_from, un_until, clock


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------

class SimBackend:
    """Accounting backend: tracks bytes moved and a simulated clock."""

    def __init__(self, bw_bytes_per_s: float = 1e9):
        self.bw = bw_bytes_per_s
        self.clock = 0.0
        self.bytes_moved = 0.0

    def run_phase(self, phase: Sequence[Move], state: BucketedState,
                  placement: np.ndarray):
        self.clock += phase_duration(phase, self.bw)
        for mv in phase:
            placement[mv.bucket] = mv.dst
            self.bytes_moved += mv.nbytes


class JaxBackend:
    """Executes migration phases on REAL jax state, wall-clock measured.

    Two state layouts are supported:

    * ``DeviceBucketedState`` (runtime.state) — the live decode cache held
      as per-node device shards.  Each phase delegates to
      ``state.run_phase``: the moving buckets' request rows are gathered
      from the source shards, transferred (device-to-device when nodes map
      to distinct jax devices), and scattered into the destination shards.
      Bytes moved come from the actual leaf shapes/dtypes.
    * host ``BucketedState`` — legacy: whole bucket pytrees are
      ``device_put`` to the destination node's device.

    Same accounting protocol as ``SimBackend`` (``clock`` /
    ``bytes_moved``), except the clock advances by *measured* seconds: the
    duration of each phase's ``migrate.phase`` span, which ends at the
    phase's ``block_until_ready`` (``migrate.wait``).  ``bw`` is only the
    denominator of the executor's naive-baseline estimate.
    """

    def __init__(self, devices=None, bw_bytes_per_s: float = 1e9):
        import jax
        self.devices = list(devices) if devices is not None else jax.devices()
        self.bw = bw_bytes_per_s
        self.clock = 0.0
        self.bytes_moved = 0.0

    def run_phase(self, phase: Sequence[Move], state,
                  placement: np.ndarray):
        import jax
        # the measured time is reported, never fed back into planning
        with obs.span("migrate.phase") as sp:
            if hasattr(state, "run_phase"):   # device-resident bucketed view
                nbytes = state.run_phase(phase)
            else:                              # host bucket pytrees
                nbytes = 0.0
                moved = []
                with obs.span("migrate.dispatch"):
                    for mv in phase:
                        dev = self.devices[mv.dst % len(self.devices)]
                        state.buckets[mv.bucket] = jax.device_put(
                            state.buckets[mv.bucket], dev)
                        moved.append(state.buckets[mv.bucket])
                        nbytes += mv.nbytes
                obs.count("bytes", nbytes)
                with obs.span("migrate.wait"):
                    if moved:
                        jax.block_until_ready(moved)
        for mv in phase:
            placement[mv.bucket] = mv.dst
        self.clock += sp.dur_s
        self.bytes_moved += nbytes


@dataclass
class MigrationReport:
    moves: int
    bytes_moved: float
    phases: int
    duration_s: float
    naive_duration_s: float
    suspended_peak: int          # max simultaneously-suspended buckets/node
    # busiest-link bytes of each executed phase: the roofline input for
    # predicting transfer time on a target interconnect
    # (roofline.migration_transfer_s)
    phase_link_bytes: List[float] = field(default_factory=list)


class MigrationExecutor:
    """Executes a MigrationPlan over a backend.

    mode:
      suspend     — everything moves in one go; app paused for the duration
                    (paper §5.1 without restart).
      live        — app keeps running; move-in buckets are suspended only
                    until their phase lands (paper §5.2).
      progressive — live + mini-migrations: at most ``max_inflight`` move-in
                    buckets per node at a time (paper §5.2 last ¶).
      fluid       — Megaphone-style per-bucket sequencing: ``fluid_batch``
                    buckets per node per phase (default 1), each bucket
                    paused only for its own transfer window.
      batched_fluid — Megaphone's batched variant: conflict-free parallel
                    rounds (``schedule_rounds``, Hopcroft–Karp matching);
                    each node sends/receives at most one ``fluid_batch``-
                    bucket batch per round, each bucket paused only for its
                    own transfer.
      kill_restart— alias of suspend (full stop; the serving simulators
                    additionally charge the restart overhead).

    verify: None (default) skips checking; "warn" runs the
      ``analysis.plancheck`` rule catalog on every plan+schedule before
      executing and prints findings to stderr; "strict" raises
      ``PlanVerificationError`` instead — nothing runs on a bad plan.
    """

    MODES = ("suspend", "kill_restart", "live", "progressive", "fluid",
             "batched_fluid")
    VERIFY_LEVELS = (None, "warn", "strict")

    def __init__(self, backend=None, mode: str = "live",
                 max_inflight: int = 4, fluid_batch: int = 1,
                 verify: Optional[str] = None):
        if mode not in self.MODES:
            raise ValueError(f"mode must be one of {self.MODES}, "
                             f"got {mode!r}")
        if verify not in self.VERIFY_LEVELS:
            raise ValueError(f"verify must be one of {self.VERIFY_LEVELS}, "
                             f"got {verify!r}")
        self.backend = backend or SimBackend()
        self.mode = mode
        self.max_inflight = max_inflight
        self.fluid_batch = fluid_batch
        self.verify = verify

    def _verify(self, plan: MigrationPlan, bb: np.ndarray,
                moves: Sequence[Move],
                phases: Sequence[Sequence[Move]]) -> None:
        # lazy import: analysis imports this module at load time
        from repro.analysis import plancheck
        with obs.span("plan.check"):
            findings = plancheck.check_plan(plan, bb)
            findings += plancheck.check_moves(plan, bb, moves)
            findings += plancheck.check_schedule(moves, phases, self.mode)
            findings += plancheck.check_permutation(plan)
            obs.count("findings", len(findings))
        plancheck.handle(findings, self.verify,
                         where=f"MigrationExecutor[{self.mode}]")

    def execute(self, plan: MigrationPlan, state: BucketedState,
                placement: np.ndarray) -> MigrationReport:
        with obs.span("migrate.schedule"):
            bb = state.bucket_bytes()
            moves = move_list(plan, bb)
            phases = strategy_schedule(moves, bb, self.mode,
                                       max_inflight=self.max_inflight,
                                       fluid_batch=self.fluid_batch)
        if self.verify:
            self._verify(plan, bb, moves, phases)
        t0 = getattr(self.backend, "clock", 0.0)
        for phase in phases:
            self.backend.run_phase(phase, state, placement)
        t1 = getattr(self.backend, "clock", 0.0)
        bw = getattr(self.backend, "bw", 1e9)
        peak = 0
        for phase in phases:
            per_node: Dict[int, int] = {}
            for mv in phase:
                per_node[mv.dst] = per_node.get(mv.dst, 0) + 1
            if per_node:
                peak = max(peak, max(per_node.values()))
        return MigrationReport(
            moves=len(moves),
            bytes_moved=float(sum(m.nbytes for m in moves)),
            phases=len(phases),
            duration_s=t1 - t0,
            naive_duration_s=naive_duration(moves, bw),
            suspended_peak=peak,
            phase_link_bytes=[phase_duration(ph, 1.0) for ph in phases],
        )


# ---------------------------------------------------------------------------
# Dry-run migration step (uniform buckets, jit + GSPMD)
# ---------------------------------------------------------------------------

def make_migration_step(m: int):
    """Returns step(state, perm) -> state[perm]: uniform-bucket resharding.

    NOTE: with a *dynamic* perm GSPMD cannot see the communication pattern
    and conservatively all-gathers the whole state — measured in
    benchmarks/migration_dryrun.py as the naive baseline.  The plan-aware
    program is ``make_collective_migration`` below.
    """
    import jax.numpy as jnp

    def migration_step(state, perm):
        return jnp.take(state, perm, axis=0)

    return migration_step


def required_capacity(plan: MigrationPlan) -> int:
    """Max bucket slots any device needs: staying buckets keep their OLD
    slot index, so the requirement is max(old slot index of stayers)+1 or
    the post-migration bucket count, whichever is larger."""
    n_total = max(plan.old.n_nodes, plan.new.n_nodes)
    old_p, new_p = plan.old.padded(n_total), plan.new.padded(n_total)
    old_o, new_o = old_p.owner_of(), new_p.owner_of()
    m = plan.old.m
    old_slot = np.zeros(m, np.int64)
    for i, (lo, hi) in enumerate(old_p.intervals):
        old_slot[lo:hi] = np.arange(hi - lo)
    need = 1
    for d in range(n_total):
        stay_max = max((int(old_slot[j]) + 1 for j in range(m)
                        if old_o[j] == d and new_o[j] == d), default=0)
        count = int((new_o == d).sum())
        incoming = int(((new_o == d) & (old_o != d)).sum())
        need = max(need, stay_max + incoming, count)
    return need


def make_collective_migration(plan: MigrationPlan, n_devices: int,
                              cap: int, axis: str = "data"):
    """Compile the migration plan into a static sequence of phased
    ``lax.ppermute``s — the TPU-fabric version of the paper's §5 executor.

    State layout: [n_devices, cap, chunk] — device i holds its buckets in
    slots [0, cap).  Host-side slot maps are derived from the plan's
    interval assignments (bucket j of node i sits in slot j − lb_i).  Each
    Rödiger phase admits ≤1 outgoing and ≤1 incoming bucket per device and
    becomes ONE collective-permute whose per-device payload is the slot it
    sends that phase — so the emitted HLO moves exactly the bytes the
    planner predicted (benchmarks/migration_dryrun.py asserts this).

    Returns (fn, n_phases) where fn maps state [n, cap, chunk] -> state
    with moved buckets landed in destination slots (run under jit with the
    state sharded over ``axis``; requires a mesh with that axis in scope).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_total = max(plan.old.n_nodes, plan.new.n_nodes)
    old_o = plan.old.padded(n_total).owner_of()
    new_p = plan.new.padded(n_total)
    new_o = new_p.owner_of()
    m = plan.old.m
    old_slot = np.zeros(m, np.int64)
    for i, (lo, hi) in enumerate(plan.old.padded(n_total).intervals):
        old_slot[lo:hi] = np.arange(hi - lo)
    # "to stay" buckets keep their slot (they never move — paper §5.1);
    # "to move in" buckets take slots freed on the destination.
    need = required_capacity(plan)
    if cap < need:
        raise ValueError(f"slot capacity {cap} < required {need}")
    new_slot = old_slot.copy()
    for d in range(n_total):
        staying = {int(old_slot[j]) for j in range(m)
                   if old_o[j] == d and new_o[j] == d}
        free = iter(sorted(set(range(cap)) - staying))
        for j in range(m):
            if new_o[j] == d and old_o[j] != d:
                new_slot[j] = next(free)
    moves = [Move(j, int(old_o[j]), int(new_o[j]), 1.0)
             for j in range(m) if old_o[j] != new_o[j]]
    # one in + one out per device per phase => one ppermute per phase
    phases = schedule_phases(moves, phase_budget=1.0)
    static = []
    for ph in phases:
        perm = [(mv.src, mv.dst) for mv in ph]
        send_slot = np.zeros(n_devices, np.int64)
        recv_slot = np.zeros(n_devices, np.int64)
        is_dst = np.zeros(n_devices, bool)
        for mv in ph:
            if mv.src < n_devices:
                send_slot[mv.src] = old_slot[mv.bucket]
            if mv.dst < n_devices:
                recv_slot[mv.dst] = new_slot[mv.bucket]
                is_dst[mv.dst] = True
        static.append((tuple(perm), jnp.asarray(send_slot),
                       jnp.asarray(recv_slot), jnp.asarray(is_dst)))

    def local_fn(state):                       # [1, cap, chunk] per device
        idx = lax.axis_index(axis)
        for perm, send_slot, recv_slot, is_dst in static:
            payload = lax.dynamic_index_in_dim(
                state[0], send_slot[idx], axis=0, keepdims=False)
            recv = lax.ppermute(payload, axis, perm)
            updated = lax.dynamic_update_index_in_dim(
                state[0], recv, recv_slot[idx], axis=0)
            state = jnp.where(is_dst[idx], updated, state[0])[None]
        return state

    slot_map = {j: (int(new_o[j]), int(new_slot[j])) for j in range(m)}
    return local_fn, len(phases), slot_map


def plan_to_permutation(plan: MigrationPlan) -> np.ndarray:
    """Bucket order such that new node i's buckets are contiguous slices —
    the uniform-bucket dry-run layout (bucket j of the new assignment reads
    old bucket perm[j])."""
    n_total = max(plan.old.n_nodes, plan.new.n_nodes)
    new = plan.new.padded(n_total)
    order = []
    for i, (lo, hi) in enumerate(new.intervals):
        order.extend(range(lo, hi))
    return np.asarray(order, dtype=np.int32)


def verify_resharding(plan: MigrationPlan, state,
                      pre_buckets: Sequence) -> None:
    """Assert an executed plan actually moved the real state: walk buckets
    in ``plan_to_permutation`` order (the new contiguous-per-node layout),
    check every bucket's rows now live on its new owner, and that its
    contents are bit-identical to the pre-migration snapshot.

    ``state`` is a ``DeviceBucketedState``; ``pre_buckets`` is the
    pre-migration host view (``state.to_host().buckets``).  Raises
    AssertionError with the offending bucket on any mismatch.
    """
    n_total = max(plan.old.n_nodes, plan.new.n_nodes)
    owner_new = plan.new.padded(n_total).owner_of()
    for j in plan_to_permutation(plan):
        reqs = state.bucket_requests(int(j))
        nodes = set(int(n) for n in state.req_node[reqs])
        if len(reqs) and nodes != {int(owner_new[j])}:
            raise AssertionError(
                f"bucket {j}: rows on nodes {sorted(nodes)}, "
                f"plan owner {int(owner_new[j])}")
        import jax as _jax
        post = state.gather(reqs)
        pre_l = _jax.tree_util.tree_leaves(pre_buckets[int(j)])
        post_l = _jax.tree_util.tree_leaves(post)
        if len(pre_l) != len(post_l):
            raise AssertionError(f"bucket {j}: leaf structure changed")
        for a, b in zip(pre_l, post_l):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(
                    f"bucket {j}: contents changed across migration")
