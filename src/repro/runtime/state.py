"""Bucketed operator state (paper §2: tasks and their states).

The unit of migration is a *bucket* (the paper's task): a pytree whose
leaves all share a leading bucket axis of size m.  Concrete operator states
in this framework:

* serving: per-bucket KV/recurrent state of the requests hashed there
* streaming quickstart: per-bucket aggregation counters (word counts)
* training: per-bucket optimizer-state slices (ZeRO resharding on elastic
  events)

``bucket_bytes`` drives the planner's |s_j|; ``route`` is the paper's
partitioning function f(r) (cheap hash → bucket id); nodes own contiguous
bucket intervals so the routing table is just the interval boundaries
(paper §2.1's CPU-cache argument → here a tiny (n+1,) int array).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro import obs

try:  # jax is optional at this layer: the sim backend is pure numpy
    import jax
    import jax.numpy as jnp
except Exception:  # pragma: no cover
    jax = None
    jnp = None


@dataclass
class BucketedState:
    """Host-side view: per-bucket pytrees (list of length m)."""

    buckets: List[Any]                   # bucket id -> pytree (numpy leaves)

    @property
    def m(self) -> int:
        return len(self.buckets)

    def bucket_bytes(self) -> np.ndarray:
        out = np.zeros(self.m)
        for j, b in enumerate(self.buckets):
            leaves = _tree_leaves(b)
            out[j] = float(sum(x.size * x.itemsize for x in leaves))
        return out

    @staticmethod
    def zeros_like_spec(m: int, spec: Dict[str, tuple],
                        dtype=np.float32) -> "BucketedState":
        return BucketedState(
            [{k: np.zeros(shape, dtype) for k, shape in spec.items()}
             for _ in range(m)])


def _tree_leaves(tree) -> List[np.ndarray]:
    if isinstance(tree, dict):
        out: List[np.ndarray] = []
        for v in tree.values():
            out.extend(_tree_leaves(v))
        return out
    if isinstance(tree, (list, tuple)):
        out = []
        for v in tree:
            out.extend(_tree_leaves(v))
        return out
    return [np.asarray(tree)]


# ---------------------------------------------------------------------------
# Device-resident bucketed state: the REAL decode cache as operator state
# ---------------------------------------------------------------------------

def cache_batch_axis(names: Sequence[str]) -> int:
    """Which axis of a decode-cache leaf is the *request* (batch) axis.

    ``init_cache`` stacks the repeated-pattern layer groups (``blocks``) and
    the encoder-decoder cross K/V with a leading layer axis, so their batch
    axis is 1; ``tail`` (and any unstacked) leaves carry batch at axis 0.
    ``names`` is the leaf's key path from the cache root.  This is the rule
    serve.py's old ``per_req = prod(shape[1:])`` estimate got wrong: it
    priced every leaf as if axis 0 were batch, so stacked leaves were
    divided by the layer count instead of multiplied by it.
    """
    return 1 if names and names[0] in ("blocks", "cross_k", "cross_v") else 0


def _key_path_names(path) -> List[str]:
    return [str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))
            for p in path]


def cache_batch_axes(cache) -> Any:
    """Pytree of ints matching ``cache``: the request axis of every leaf."""
    if jax is None:  # pragma: no cover
        raise RuntimeError("cache_batch_axes requires jax")
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_batch_axis(_key_path_names(path)), cache)


class DeviceBucketedState:
    """Bucketed view whose leaves ARE the live jax decode cache.

    Serving nodes are modelled as separate device buffers: node ``i`` holds
    a cache shard whose request axis has a fixed row capacity ``cap``
    (padded rows are inert — decode on them is masked out by the caller).
    A request's KV/recurrent rows live in exactly one node's shard, located
    by ``req_node``/``req_row``; migration physically copies those rows
    between shards (true device-to-device transfers when nodes map to
    distinct jax devices, plain buffer copies on a single device).

    Satisfies the ``bucket_bytes()`` protocol of ``MigrationExecutor``, so
    the SSM planner prices buckets from the *actual* leaf shapes/dtypes:
    per-request bytes = Σ_leaf nbytes / cap (the request axis is ``cap`` in
    every shard leaf), bucket j = per-request bytes × #requests hashed to j.
    """

    def __init__(self, shards: Dict[int, Any], row_req: Dict[int, np.ndarray],
                 req_bucket: np.ndarray, m: int, cap: int,
                 devices: Optional[Sequence] = None):
        self.shards = shards                  # node id -> cache pytree
        self.row_req = row_req                # node id -> int[cap], -1 free
        self.req_bucket = np.asarray(req_bucket)
        self._m = int(m)
        self.cap = int(cap)
        self.devices = list(devices) if devices else None
        B = len(self.req_bucket)
        self.req_node = np.full(B, -1, np.int64)
        self.req_row = np.full(B, -1, np.int64)
        for i, rr in row_req.items():
            valid = rr >= 0
            self.req_node[rr[valid]] = i
            self.req_row[rr[valid]] = np.nonzero(valid)[0]
        tpl = next(iter(shards.values()))
        self._axes = cache_batch_axes(tpl)
        self.row_nbytes = float(sum(
            leaf.size * leaf.dtype.itemsize / self.cap
            for leaf in jax.tree_util.tree_leaves(tpl)))

    # -- construction -------------------------------------------------------
    @classmethod
    def from_cache(cls, cache, req_bucket: np.ndarray, owner: np.ndarray,
                   cap: Optional[int] = None,
                   devices: Optional[Sequence] = None
                   ) -> "DeviceBucketedState":
        """Split a global [B, ...]-batched cache into per-node shards.

        ``owner``: bucket id -> node id (``Assignment.owner_of()``); rows
        are laid out bucket-major inside each shard so a node's buckets are
        contiguous row runs (the paper's interval layout)."""
        req_bucket = np.asarray(req_bucket)
        B = len(req_bucket)
        cap = int(cap or B)
        axes = cache_batch_axes(cache)
        node_of_req = np.asarray(owner)[req_bucket]
        shards: Dict[int, Any] = {}
        row_req: Dict[int, np.ndarray] = {}
        for i in sorted(set(int(n) for n in node_of_req)):
            reqs = np.nonzero(node_of_req == i)[0]
            reqs = reqs[np.argsort(req_bucket[reqs], kind="stable")]
            if len(reqs) > cap:
                raise ValueError(f"node {i}: {len(reqs)} rows > cap {cap}")
            shard = jax.tree_util.tree_map(
                lambda leaf, ax: _pad_rows(
                    jnp.take(leaf, jnp.asarray(reqs), axis=ax), ax, cap),
                cache, axes)
            if devices:
                shard = jax.device_put(shard, devices[i % len(devices)])
            shards[i] = shard
            rr = np.full(cap, -1, np.int64)
            rr[: len(reqs)] = reqs
            row_req[i] = rr
        return cls(shards, row_req, req_bucket, len(np.asarray(owner)),
                   cap, devices=devices)

    # -- bucketed-state protocol -------------------------------------------
    @property
    def m(self) -> int:
        return self._m

    def bucket_bytes(self) -> np.ndarray:
        counts = np.bincount(self.req_bucket, minlength=self._m)
        return counts.astype(np.float64) * self.row_nbytes

    # -- accessors ----------------------------------------------------------
    def node_ids(self) -> List[int]:
        return sorted(self.shards)

    def device_of(self, node: int):
        if not self.devices:
            return None
        return self.devices[node % len(self.devices)]

    def bucket_requests(self, j: int) -> np.ndarray:
        return np.nonzero(self.req_bucket == j)[0]

    def _ensure_node(self, i: int) -> None:
        if i in self.shards:
            return
        tpl = next(iter(self.shards.values()))
        shard = jax.tree_util.tree_map(jnp.zeros_like, tpl)
        if self.devices:
            shard = jax.device_put(shard, self.device_of(i))
        self.shards[i] = shard
        self.row_req[i] = np.full(self.cap, -1, np.int64)

    # -- migration ----------------------------------------------------------
    def run_phase(self, phase: Sequence) -> float:
        """Physically execute one phase of bucket moves: for every
        (src, dst) pair, gather the moving buckets' request rows from the
        source shard, transfer them, and scatter into free rows of the
        destination shard.  Returns the bytes actually moved (from real
        leaf shapes).  Each pair's enqueue is a ``migrate.dispatch`` span
        (counters ``rows``, ``bytes``), the wait for the destination shards
        a ``migrate.wait``; the pairs, rows and bytes are also counted on
        the span around the call (``migrate.phase``)."""
        by_pair: Dict[tuple, List[int]] = {}
        for mv in phase:
            by_pair.setdefault((int(mv.src), int(mv.dst)), []).append(
                int(mv.bucket))
        moved = 0.0
        touched = []
        for (src, dst), bkts in sorted(by_pair.items()):
            with obs.span("migrate.dispatch", src=src, dst=dst):
                reqs = np.concatenate(
                    [self.bucket_requests(j) for j in bkts])
                if len(reqs) == 0:
                    continue
                if not (self.req_node[reqs] == src).all():
                    raise RuntimeError(
                        f"buckets {bkts}: rows not on source node {src}")
                self._ensure_node(dst)
                src_rows = jnp.asarray(self.req_row[reqs])
                vals = jax.tree_util.tree_map(
                    lambda leaf, ax: jnp.take(leaf, src_rows, axis=ax),
                    self.shards[src], self._axes)
                if self.devices:
                    vals = jax.device_put(vals, self.device_of(dst))
                free = np.nonzero(self.row_req[dst] < 0)[0][: len(reqs)]
                if len(free) < len(reqs):
                    raise RuntimeError(f"node {dst}: out of row capacity "
                                       f"({len(reqs)} in, {len(free)} free)")
                dst_rows = jnp.asarray(free)
                self.shards[dst] = jax.tree_util.tree_map(
                    lambda leaf, new, ax: _set_rows(leaf, new, ax, dst_rows),
                    self.shards[dst], vals, self._axes)
                self.row_req[src][self.req_row[reqs]] = -1
                self.row_req[dst][free] = reqs
                self.req_node[reqs] = dst
                self.req_row[reqs] = free
                nbytes = len(reqs) * self.row_nbytes
                obs.count("rows", len(reqs))
                obs.count("bytes", nbytes)
            moved += nbytes
            touched.append(self.shards[dst])
            obs.count("pairs")
            obs.count("rows", len(reqs))
            obs.count("bytes", nbytes)
        with obs.span("migrate.wait"):
            if touched:
                jax.block_until_ready(touched)
        return moved

    # -- host views ---------------------------------------------------------
    def gather(self, req_ids: np.ndarray) -> Any:
        """Reassemble the given requests' rows (host-side numpy leaves, in
        request order) — for verification and checkpointing."""
        req_ids = np.asarray(req_ids)
        parts: Dict[int, tuple] = {}
        for i in self.node_ids():
            sel = np.nonzero(np.isin(req_ids, self.row_req[i]))[0]
            if len(sel):
                parts[i] = (sel, self.req_row[req_ids[sel]])
        tpl = next(iter(self.shards.values()))

        def build(path, leaf):
            ax = cache_batch_axis(_key_path_names(path))
            shape = list(leaf.shape)
            shape[ax] = len(req_ids)
            out = np.zeros(shape, leaf.dtype)
            for i, (sel, rows) in parts.items():
                # select the rows on the device: only they cross to the host
                src = jnp.take(_leaf_at(self.shards[i], path),
                               jnp.asarray(rows), axis=ax)
                odx = [slice(None)] * out.ndim
                odx[ax] = sel
                out[tuple(odx)] = np.asarray(src)
            return out

        return jax.tree_util.tree_map_with_path(build, tpl)

    def to_host(self) -> "BucketedState":
        """Host BucketedState view: bucket j = its requests' rows (numpy)."""
        return BucketedState(
            [self.gather(self.bucket_requests(j)) for j in range(self._m)])


def _pad_rows(leaf, axis: int, cap: int):
    pad = cap - leaf.shape[axis]
    if pad <= 0:
        return leaf
    widths = [(0, 0)] * leaf.ndim
    widths[axis] = (0, pad)
    return jnp.pad(leaf, widths)


def _set_rows(leaf, new, axis: int, rows):
    idx = (slice(None),) * axis + (rows,)
    return leaf.at[idx].set(new)


def _leaf_at(tree, path):
    node = tree
    for p in path:
        key = getattr(p, "key", getattr(p, "name", getattr(p, "idx", None)))
        node = node[key]
    return node


def route(keys: np.ndarray, m: int, seed: int = 0) -> np.ndarray:
    """Partitioning function f(r): stable integer hash -> [0, m)."""
    k = np.asarray(keys, dtype=np.uint64)
    s = np.uint64((seed * 0x9E3779B97F4A7C15 + 0xBF58476D1CE4E5B9)
                  % (1 << 64))
    x = (k + s) * np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(29)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(32)
    return (x % np.uint64(m)).astype(np.int64)


def owner_lookup(boundaries: Sequence[int], bucket_ids: np.ndarray
                 ) -> np.ndarray:
    """Interval routing: node = searchsorted(boundaries, bucket) — the whole
    routing table is the boundary array (paper §2.1)."""
    b = np.asarray(boundaries)
    return np.searchsorted(b, np.asarray(bucket_ids), side="right") - 1
