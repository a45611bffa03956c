"""Serving driver: batched prefill + decode where the REAL jax KV cache is
the bucketed operator state — a live elastic resize physically reshards it.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --requests 16 --prompt-len 24 --gen 16 --resize-at 8:3

Requests are hashed into m buckets (repro.runtime.route); each serving node
owns a contiguous bucket interval and holds its requests' KV/recurrent rows
in its own device buffer (``DeviceBucketedState``: per-node cache shards,
device-to-device when multiple jax devices back the nodes).  Decode runs
per node on its local shard, with the weights copied once to each device
a node decodes on.  ``--resize-at step:n`` triggers a live
elastic event mid-decode: SSM plans the minimal KV movement from the
*actual* per-bucket byte sizes, ``MigrationExecutor`` +
``JaxBackend`` execute the phases as real row transfers between shards
(wall-clock measured), routing follows the new bucket ownership, and the
roofline model (``repro.roofline.migration_transfer_s``) predicts the
transfer cost next to the measured one.  Decode output is bit-identical to
a run without the resize — migration moves state, never mutates it
(``verify_resharding`` checks every bucket against the plan's
permutation layout).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config, get_smoke
from repro.core import ElasticPlanner
from repro.launch.compile_cache import use_compile_cache
from repro.models import decode_step, init_cache, init_params, prefill
from repro.roofline import device_peaks, migration_transfer_s
from repro.runtime import (
    DeviceBucketedState, ElasticController, JaxBackend, MigrationExecutor,
    route, verify_resharding,
)


@dataclass
class ServeResult:
    tokens: np.ndarray                 # [B, G+1] generated token ids
    step_s: List[float]                # per-decode-step wall seconds
    prefill_s: float
    req_bucket: np.ndarray             # [B] request -> bucket
    resize: Optional[Dict] = None      # metrics of the elastic event
    boundaries: List[int] = field(default_factory=list)

    @property
    def steady_s(self) -> float:
        """Median step time outside the resize step."""
        skip = self.resize["step"] if self.resize else -1
        other = [t for g, t in enumerate(self.step_s) if g != skip]
        return float(np.median(other)) if other else 0.0

    @property
    def spike_s(self) -> float:
        """Step time of the resize step (transfer + replan + decode)."""
        if not self.resize:
            return 0.0
        return float(self.step_s[self.resize["step"]])


def decode_step_fn(cfg):
    """The jitted per-node decode step: (params, cache shard, tokens [cap,1],
    pos [cap]) -> (logits, new shard)."""
    return jax.jit(lambda p, c, t, pos: decode_step(
        cfg=cfg, params=p, cache=c, tokens=t, pos=pos))


def _decode_nodes(state: DeviceBucketedState, step_fn,
                  params_on: Callable, tok: np.ndarray,
                  pos_val: int) -> np.ndarray:
    """One decode step across all serving nodes: each node decodes its own
    shard (padded rows included, masked out of the result) with the
    weights held on its own device.  Records a ``serve.step`` span with
    one ``serve.node`` per node decoded, each split into ``serve.dispatch``
    (host preparation and the step's enqueue) and ``serve.fetch`` (the
    tokens' argmax and copy to the host, where the step waits for the
    device)."""
    new_tok = tok.copy()
    nodes = rows_live = 0
    with obs.span("serve.step"):
        pos = jnp.full((state.cap,), pos_val, jnp.int32)
        for i in state.node_ids():
            rows = state.row_req[i]
            valid = rows >= 0
            if not valid.any():
                continue
            dev = state.device_of(i)
            with obs.span("serve.node", node=i,
                          device=getattr(dev, "id", None)):
                with obs.span("serve.dispatch"):
                    safe = np.where(valid, rows, 0)
                    logits, shard = step_fn(params_on(dev), state.shards[i],
                                            jax.device_put(tok[safe], dev),
                                            pos)
                    state.shards[i] = shard
                with obs.span("serve.fetch"):
                    t_local = np.asarray(
                        jnp.argmax(logits, -1).astype(jnp.int32))
                new_tok[rows[valid]] = t_local[valid]
            nodes += 1
            rows_live += int(valid.sum())
        obs.count("nodes", nodes)
        obs.count("rows_live", rows_live)
        obs.count("rows_decoded", nodes * state.cap)
    return new_tok


def _do_resize(ctl: ElasticController, state: DeviceBucketedState,
               backend: JaxBackend, n_new: int, step: int,
               verify: bool) -> Dict:
    """One elastic event: ``ctl.scale`` (its ``elastic.scale`` span is
    ``resize_s_wall``; the backend's clock, the sum of its
    ``migrate.phase`` spans, gives ``transfer_s_wall``).  With ``verify``
    a host snapshot before and ``verify_resharding`` after check the
    moved state, timed apart (``serve.verify``) as ``verify_s_wall``."""
    m = state.m
    w = np.bincount(state.req_bucket, minlength=m).astype(float) + 1e-9
    pre, verify_s = None, 0.0
    if verify:
        with obs.span("serve.verify") as sp:
            pre = state.to_host().buckets
        verify_s += sp.dur_s
    n_before = ctl.n_nodes
    clock0, bytes0 = backend.clock, backend.bytes_moved
    plan, rep = ctl.scale(n_new, w, state)
    wall_s = obs.RECORDER.last("elastic.scale").dur_s
    owner = ctl.assign.owner_of()
    routing_ok = bool(np.array_equal(owner[state.req_bucket],
                                     state.req_node))
    if verify:
        with obs.span("serve.verify") as sp:
            verify_resharding(plan, state, pre)   # raises on mismatch
        verify_s += sp.dur_s
    peaks = device_peaks(state.device_of(0))
    return {
        "step": step,
        "n_before": n_before,
        "n_after": ctl.n_nodes,
        "moves": rep.moves,
        "phases": rep.phases,
        "bytes_moved": backend.bytes_moved - bytes0,
        "plan_cost_bytes": float(plan.cost),
        "transfer_s_wall": backend.clock - clock0,
        "resize_s_wall": wall_s,
        "predicted_ici_s": migration_transfer_s(rep.phase_link_bytes,
                                                "ici", peaks),
        "predicted_hbm_s": migration_transfer_s(rep.phase_link_bytes,
                                                "hbm", peaks),
        "routing_ok": routing_ok,
        "verified": verify,
        "node_devices": [state.device_of(i).id for i in state.node_ids()],
        # host snapshot + check, kept out of the step time
        "verify_s_wall": verify_s,
    }


def run_serving(arch: str = "qwen2.5-3b", smoke: bool = True,
                requests: int = 16, prompt_len: int = 24, gen: int = 16,
                buckets: int = 16, nodes: int = 2,
                resize: Optional[Tuple[int, int]] = None,
                tau: float = 0.2, cap: Optional[int] = None,
                seed: int = 0, verify: bool = True,
                devices: Optional[Sequence] = None,
                quiet: bool = True) -> ServeResult:
    """Run the elastic serving loop; ``resize=(step, n_new)`` fires a live
    mid-decode elastic event that reshards the real KV cache.  Node ``i``
    lives on ``devices[i % len(devices)]`` (default: all jax devices);
    prefill runs on ``devices[0]``."""
    cfg = get_smoke(arch) if smoke else get_config(arch)
    devices = list(devices or jax.devices())
    key = jax.random.PRNGKey(seed)
    # the weights are made on the first device and copied once to each
    # other device a node decodes on; no step copies them
    placed = {devices[0]: jax.device_put(
        jax.jit(init_params, static_argnums=0)(cfg, key), devices[0])}

    def params_on(dev):
        if dev not in placed:
            placed[dev] = jax.device_put(placed[devices[0]], dev)
        return placed[dev]

    B, P, G = requests, prompt_len, gen
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": prompts}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.dtype(cfg.dtype))
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))

    m = buckets
    req_bucket = route(np.arange(B) + 1000, m)
    backend = JaxBackend()
    ctl = ElasticController(
        m, nodes, tau=tau, planner=ElasticPlanner(policy="ssm"),
        # verify=True also arms the pre-execution plan checker: a plan
        # violating the PLN catalog aborts before touching the live cache
        executor=MigrationExecutor(backend=backend, mode="live",
                                   verify="strict" if verify else None))

    cache = init_cache(cfg, B, P + G + 1)
    t0 = time.perf_counter()
    logits, cache = jax.jit(prefill, static_argnums=1)(
        placed[devices[0]], cfg, batch, cache)
    tok = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
    prefill_s = time.perf_counter() - t0
    if not quiet:
        print(f"prefill {B}×{P} in {prefill_s:.2f}s")

    # split the real cache into per-node device shards: THIS is the
    # operator state the elastic event migrates
    state = DeviceBucketedState.from_cache(
        cache, req_bucket, ctl.assign.owner_of(), cap=cap or B,
        devices=devices)
    del cache

    step_fn = decode_step_fn(cfg)
    out_tokens = [tok]
    step_s: List[float] = []
    resize_info = None
    for g in range(G):
        t0 = time.perf_counter()
        verify_s = 0.0
        if resize is not None and g == resize[0]:
            resize_info = _do_resize(ctl, state, backend, resize[1], g,
                                     verify)
            verify_s = resize_info["verify_s_wall"]
            if not quiet:
                r = resize_info
                print(f"  elastic resize @step {g}: n {r['n_before']}→"
                      f"{r['n_after']}, moved {r['bytes_moved']/1e6:.2f}MB "
                      f"in {r['phases']} phases "
                      f"({r['transfer_s_wall']*1e3:.1f}ms measured, "
                      f"{r['predicted_ici_s']*1e3:.3f}ms roofline ICI)")
        tok = _decode_nodes(state, step_fn, params_on, tok, P + g)
        step_s.append(time.perf_counter() - t0 - verify_s)
        out_tokens.append(tok)
    if not quiet:
        dt = sum(step_s)
        print(f"decoded {G} steps × {B} reqs in {dt:.2f}s "
              f"({B*G/dt:.1f} tok/s)")
    gen_toks = np.concatenate(out_tokens, axis=1)
    bounds = [iv[0] for iv in ctl.assign.intervals if iv[1] > iv[0]]
    return ServeResult(tokens=gen_toks, step_s=step_s, prefill_s=prefill_s,
                       req_bucket=req_bucket, resize=resize_info,
                       boundaries=bounds)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--buckets", type=int, default=16)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--cap", type=int, default=None,
                    help="per-node row capacity (default: all requests)")
    ap.add_argument("--tau", type=float, default=0.2,
                    help="balance slack: per-node cap = (1+tau)·W/n")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", dest="verify", action="store_false")
    ap.add_argument("--resize-at", default="",
                    help="step:n_new — live elastic event mid-decode")
    args = ap.parse_args(argv)
    use_compile_cache()

    resize = None
    if args.resize_at:
        a, b = args.resize_at.split(":")
        resize = (int(a), int(b))
    res = run_serving(arch=args.arch, smoke=args.smoke,
                      requests=args.requests, prompt_len=args.prompt_len,
                      gen=args.gen, buckets=args.buckets, nodes=args.nodes,
                      resize=resize, tau=args.tau, cap=args.cap,
                      seed=args.seed,
                      verify=args.verify, quiet=False)
    if res.resize:
        r = res.resize
        print(f"resize-step spike {res.spike_s*1e3:.1f}ms vs steady "
              f"{res.steady_s*1e3:.1f}ms/step; routing_ok={r['routing_ok']} "
              f"verified={r['verified']}")
    print("sample request 0 tokens:", res.tokens[0][:12])
    return res.tokens


if __name__ == "__main__":
    main()
