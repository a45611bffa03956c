"""One run of one benchmark cell: set-up, the measured window, the check
of what the window served, and the result line.

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files
and entries; nothing here changes.

The benchmark's interface into the program (the system under test):

- ``repro.configs.get_config``, ``repro.models.init_params`` (shapes only,
  to check the weights' layout), ``init_cache`` and ``prefill``;
- ``repro.runtime``: ``route``, ``DeviceBucketedState.from_cache``,
  ``ElasticController`` with ``ElasticPlanner(policy="ssm")`` and
  ``MigrationExecutor(JaxBackend(), mode="live", verify="strict")``;
- ``repro.launch.serve``: ``decode_step_fn``, ``_decode_nodes`` (one
  decode step over every node) and ``_do_resize`` (one elastic event:
  controller -> planner -> plan check -> executor -> device state).

The weights are the benchmark's own (``bench/reference``), made on the
device from the seed in the program's parameter layout, so that the
reference takes nothing the program made.
"""
from __future__ import annotations

import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import check, counts, traffic
from bench.trace import Reduced

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a traced run reads the device over the window's first TRACE_S seconds:
# the profiler drops device events of a longer decode window (a 51 s
# trace lost its last 6.6 s on a v5e), and then reports them as idle
TRACE_S = 20.0


# ---------------------------------------------------------------------------
# what a run records, and what the metric readers read
# ---------------------------------------------------------------------------

@dataclass
class Step:
    t0: float              # host seconds from the window's start
    t1: float              # when the step's tokens are on the host
    event: Optional[int]   # index into Run.events where the step fired one
    nodes: int             # serving nodes during the step's decode
    rows: int              # requests decoded
    ctx: int               # positions each request attends over


@dataclass
class Run:
    model: Any             # the configuration's reference module
    sizes: Any             # its sizes (``model.sizes(cfg)``)
    peaks: counts.Peaks
    chips: int
    tokens_per_step: int
    setup_s: float = 0.0
    window_s: float = 0.0
    steps: List[Step] = field(default_factory=list)
    events: List[Dict] = field(default_factory=list)
    peak_bytes: int = 0
    compiles_in_window: int = 0
    trace: Optional[Reduced] = None
    trace_end: float = 0.0      # window second where the traced part ends
    device_ids: List[int] = field(default_factory=list)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str) -> Callable[[Run], Optional[float]]:
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    return importlib.import_module(f"bench.metrics.{name}").read


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cfg: Dict, mix: traffic.Mix, seed: int, seconds: float,
             trace: bool, devices: Sequence, peaks: counts.Peaks,
             t_start: float, pcfg=None, controls: Sequence[str] = (),
             log=print) -> Dict:
    """Run a cell once and return the result line's object.  ``pcfg``
    replaces the program's configuration (the tests' small sizes);
    ``controls`` names lower precisions of the reference to read beside
    it (the control test's readings, under ``gaps``)."""
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from repro.configs import get_config
    from repro.core import ElasticPlanner
    from repro.launch.serve import _decode_nodes, _do_resize, decode_step_fn
    from repro.models import init_cache, init_params, prefill
    from repro.runtime import (DeviceBucketedState, ElasticController,
                               JaxBackend, MigrationExecutor, route)

    compiles = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)

    ref = check.load_reference(cfg["reference"])
    sizes = ref.sizes(cfg)
    pcfg = ref.program_config(pcfg or get_config(cfg["program_arch"]),
                              sizes)
    ref.check_program(pcfg, sizes)
    devices = list(devices)

    def settle(stage, tree):
        """Wait for a set-up stage's arrays, so that the next stage's
        buffers are allocated only once this one's temporaries are freed:
        the peak is then that of the stages in turn, whether the programs
        come from the compile cache or were compiled in this run."""
        jax.block_until_ready(tree)
        used, peak = memory(devices)
        log(f"memory after {stage} ({time.perf_counter() - t_start:.3f} s):"
            f" in use {used}, peak {peak}")

    run = Run(model=ref, sizes=sizes, peaks=peaks, chips=len(devices),
              tokens_per_step=mix.requests,
              device_ids=[d.id for d in devices])
    B, P, G = mix.requests, mix.prompt, mix.gen
    inputs = traffic.make_inputs(mix, sizes.vocab, seed)
    key = jax.random.PRNGKey(traffic.weight_key_seed(seed))

    # -- set-up -------------------------------------------------------------
    weights = ref.make_weights(sizes, key)
    want = jax.eval_shape(lambda k: init_params(pcfg, k), key)
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(weights)
            or [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(want)]
            != [(a.shape, a.dtype)
                for a in jax.tree_util.tree_leaves(weights)]):
        raise ValueError("the program's parameter layout differs from the "
                         "reference's weights")
    settle("weights", weights)
    placed = {devices[0]: weights}

    def params_on(dev):
        if dev not in placed:
            placed[dev] = jax.device_put(placed[devices[0]], dev)
        return placed[dev]

    cache = init_cache(pcfg, B, mix.cache_len)
    _, cache = jax.jit(prefill, static_argnums=1)(
        weights, pcfg, {"tokens": jnp.asarray(inputs.prompts)}, cache)
    settle("prefill", cache)
    req_bucket = route(mix.request_keys(), mix.buckets)
    backend = JaxBackend()
    ctl = ElasticController(
        mix.buckets, mix.nodes[0], tau=mix.tau,
        planner=ElasticPlanner(policy="ssm"),
        executor=MigrationExecutor(backend=backend, mode="live",
                                   verify="strict"))
    state = DeviceBucketedState.from_cache(
        cache, req_bucket, ctl.assign.owner_of(), cap=mix.cap,
        devices=devices)
    settle("state", state.shards)
    del cache
    step_fn = decode_step_fn(pcfg)

    def decode(feed, s):
        return _decode_nodes(state, step_fn, params_on, feed, P + s)

    def layout():
        return tuple(tuple(iv) for iv in ctl.assign.intervals)

    # warm-up: decode at the first topology, then whole event cycles
    # until the layout repeats, so that every plan the window can meet
    # has run (and compiled) once
    resp = 0
    feed = inputs.starts[resp][:, None]
    for s in range(2):
        feed = decode(feed, s)
    seen = {layout()}
    for _ in range(8 if mix.event_targets() else 0):
        for n in mix.event_targets():
            _do_resize(ctl, state, backend, n, -1, False)
            for s in range(2):
                feed = decode(feed, s)
        if layout() in seen:
            break
        seen.add(layout())
    resp += 1
    settle("warm-up", state.shards)
    run.setup_s = time.perf_counter() - t_start
    log(f"setup_s {run.setup_s:.3f}; warm-up layouts {len(seen)}")

    # -- the window ---------------------------------------------------------
    due = mix.events(seconds)
    responses: List[check.Response] = []
    tokens = np.zeros((B, G), np.int32)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(tdir, profiler_options=_profile_options())
    c0 = compiles[0]
    nxt, s, step, first_step = 0, 0, 0, 0
    feed = inputs.starts[resp][:, None]
    traced = jax.profiler.TraceAnnotation("window") if trace else None
    if traced:
        traced.__enter__()
    w0 = time.perf_counter()
    while True:
        t0 = time.perf_counter() - w0
        if t0 >= seconds:
            break
        ev = None
        if nxt < len(due) and t0 >= due[nxt][0]:
            before = state.req_node.copy()
            with jax.profiler.TraceAnnotation("resize"):
                info = _do_resize(ctl, state, backend, due[nxt][1],
                                  step, False)
            ev = len(run.events)
            moved = np.nonzero(state.req_node != before)[0]
            info.update(step=step, moved=moved.tolist(), row_moves=[
                (state.device_of(int(before[r])).id,
                 state.device_of(int(state.req_node[r])).id,
                 ref.row_bytes(sizes, mix.cache_len))
                for r in moved])
            run.events.append(info)
            nxt += 1
        with jax.profiler.TraceAnnotation("decode"):
            out = decode(feed, s)
        t1 = time.perf_counter() - w0
        with jax.profiler.TraceAnnotation("host"):
            run.steps.append(Step(t0, t1, ev, ctl.n_nodes, B, P + s + 1))
            if ev is not None:
                run.events[ev].update(t0=t0, t1=t1)
            tokens[:, s] = out[:, 0]
            step += 1
            s += 1
            if s == G:
                responses.append(check.Response(resp, first_step,
                                                tokens.copy()))
                resp, s, first_step = resp + 1, 0, step
                feed = inputs.starts[resp][:, None]
            else:
                feed = out
        if traced and t1 >= TRACE_S:
            traced.__exit__(None, None, None)
            traced, run.trace_end = None, t1
    run.window_s = run.steps[-1].t1 if run.steps else 0.0
    if traced:
        traced.__exit__(None, None, None)
        run.trace_end = run.window_s
    run.compiles_in_window = compiles[0] - c0
    run.peak_bytes = memory(devices)[1]
    log(f"memory after the window: in use {memory(devices)[0]}, peak "
        f"{run.peak_bytes}")
    if trace:
        jax.profiler.stop_trace()
    log(f"window {run.window_s:.3f} s, {len(run.steps)} steps, "
        f"{len(run.events)} events, {len(responses)} responses finished, "
        f"compiles_in_window {run.compiles_in_window}")

    # the routing the program reports after each event
    routing_bad = sum(not e["routing_ok"] for e in run.events)

    # free the program's state before the reference runs
    del state, placed, weights, ctl, backend, step_fn
    gc.collect()

    if trace:
        from bench.trace import reduce_file
        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        run.trace = reduce_file(files[-1]) if files else None
        shutil.rmtree(tdir, ignore_errors=True)

    # -- correct --------------------------------------------------------------
    t_check = time.perf_counter()
    picks = check.pick(responses, run.events, inputs.sample_rng,
                       mix.sample_responses)
    gaps = {}
    if picks:
        rows, served = check.sequences(responses, picks, inputs.prompts,
                                       inputs.starts)
        ref_weights = ref.make_weights(sizes, key)
        gaps = check.gaps(ref, sizes, ref_weights, rows, served, P,
                          controls)
        del ref_weights
    log(f"check_s {time.perf_counter() - t_check:.3f} for {len(picks)} "
        f"responses")
    checks, correct = check.judge(gaps.get(None), cfg["check"])
    checks["routing_mismatch"] = {"value": routing_bad, "limit": 0}
    correct = correct and routing_bad == 0
    failed = 0 if correct else max(1, len(picks))
    return dict(run=run, correct=correct, attempted=len(responses) * B,
                failed=failed, checks=checks, gaps=gaps)


def memory(devices) -> tuple:
    """(bytes in use, peak bytes in use) of the fullest of ``devices``."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(int(m.get("bytes_in_use", 0)) for m in stats),
            max(int(m.get("peak_bytes_in_use", 0)) for m in stats))


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------

def metric_entries(spec: Dict, cell: str, trace: bool) -> List[Dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def result_line(spec: Dict, cell: str, out: Dict, trace: bool,
                devices: Sequence, all_devices: Sequence) -> Dict:
    run: Run = out["run"]
    metrics = {}
    for m in metric_entries(spec, cell, trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d0 = all_devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(all_devices), "memory_peak_bytes": run.peak_bytes}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_mean_s(run.device_ids)
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": [[n, t] for n, t in run.trace.top_ops],
            "idle_gaps": [[n, t] for n, t in run.trace.idle_gaps]}
    line["checks"] = out["checks"]
    return line


def main(args, t_start: float) -> int:
    import jax
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"bench: no workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    all_devices = jax.devices()
    if all_devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX found platform "
              f"{all_devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(all_devices) < cell["chips"]:
        print(f"bench: {cell['name']} needs {cell['chips']} chips, JAX "
              f"sees {len(all_devices)}", file=sys.stderr)
        return 2
    peaks = counts.peaks(all_devices[0].device_kind)
    cfg = load_json(BENCH / "configs" / f"{cell['config']}.json")
    mix = traffic.Mix.load(cell["traffic"])
    devices = all_devices[:cell["chips"]]

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    out = run_cell(cfg, mix, args.seed, args.seconds, bool(args.trace),
                   devices, peaks, t_start, log=log)
    line = result_line(spec, cell["name"], out, bool(args.trace), devices,
                       all_devices)
    run: Run = out["run"]
    if run.trace is not None:
        log("idle by host span (s): " + json.dumps(run.trace.idle_by_span))
        log("device programs (s, calls): " + json.dumps(
            {n: [t, run.trace.module_calls[n]]
             for n, t in run.trace.module_s.items()}))
    for n in sorted({s.nodes for s in run.steps}):
        dts = sorted(1e3 * (s.t1 - s.t0) for s in run.steps
                     if s.nodes == n and s.event is None)
        if dts:
            med = dts[len(dts) // 2]
            stalls = [x - med for x in dts if x > 2 * med]
            log(f"steps at {n} nodes: {len(dts)}, median {med:.3f} ms, mean "
                f"{sum(dts) / len(dts):.3f} ms, slowest "
                f"{[round(x, 3) for x in dts[-5:]]} ms; {len(stalls)} over "
                f"twice the median, {sum(stalls):.3f} ms beyond it")
    slow = sorted(run.steps, key=lambda s: s.t0 - s.t1)[:5]
    log("slowest steps (start s, ms, event): " + json.dumps(
        [[round(s.t0, 3), round(1e3 * (s.t1 - s.t0), 3), s.event]
         for s in slow]))
    for e in run.events:
        log(f"event step {e['step']}: {e['n_before']} -> {e['n_after']} "
            f"nodes, {len(e['moved'])} rows moved, "
            f"{e['bytes_moved']:.0f} B, {e['moves']} moves,"
            f" step {1e3 * (e['t1'] - e['t0']):.3f} ms, "
            f"resize {1e3 * e['resize_s_wall']:.3f} ms, transfer "
            f"{1e3 * e['transfer_s_wall']:.3f} ms")
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(line))
    return 0
