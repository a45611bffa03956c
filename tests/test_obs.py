"""The span and counter recorder (``repro.obs``) and the spans the serving
loop, the controller, the planner, the plan check and the executor record
with it."""
import pathlib

import numpy as np
import pytest

from repro import obs
from repro.core import ElasticPlanner
from repro.runtime import (
    DeviceBucketedState, ElasticController, JaxBackend, MigrationExecutor,
    route,
)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def by_name(spans, name):
    return [s for s in spans if s.name == name]


def children(spans, parent):
    return [s for s in spans if s.parent == parent.id]


def assert_nested(spans, parent, min_cover=0.0):
    """The children of ``parent`` lie inside it, one after another, and
    cover at least ``min_cover`` of its time (at these small sizes the
    host's own work around them can take a large share under load)."""
    kids = sorted(children(spans, parent), key=lambda s: s.t0)
    assert kids, parent
    assert parent.t0 <= kids[0].t0 and kids[-1].t1 <= parent.t1
    for a, b in zip(kids, kids[1:]):
        assert a.t1 <= b.t0
    cover = sum(k.t1 - k.t0 for k in kids) / (parent.t1 - parent.t0)
    assert cover >= min_cover, (parent, kids)


def test_parent_ids_and_nesting():
    rec = obs.Recorder()
    with rec.span("a.outer", k=1) as outer:
        rec.count("n", 2)
        with rec.span("a.inner") as inner:
            rec.count("n")
            rec.tag(x="y")
        rec.count("n")
    with rec.span("a.next") as nxt:
        pass
    rec.count("n")                      # no open span: no effect
    assert [s.name for s in rec.spans()] == ["a.inner", "a.outer", "a.next"]
    assert outer.parent == 0 and nxt.parent == 0
    assert inner.parent == outer.id and nxt.id > inner.id > outer.id
    assert outer.counts == {"n": 3} and inner.counts == {"n": 1}
    assert outer.attrs == {"k": 1} and inner.attrs == {"x": "y"}
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 <= nxt.t0
    assert rec.last("a.outer") is outer and rec.last("a.none") is None


def test_a_span_ends_where_its_block_raises():
    rec = obs.Recorder()
    with pytest.raises(ValueError):
        with rec.span("a.outer"):
            with rec.span("a.inner"):
                raise ValueError
    with rec.span("a.after") as after:
        pass
    assert after.parent == 0
    assert [s.name for s in rec.spans()] == ["a.inner", "a.outer", "a.after"]


def test_ring_bound_and_drop_count():
    rec = obs.Recorder(capacity=4)
    opened = []
    for i in range(6):
        with rec.span("a.s", i=i) as s:
            opened.append(s)
    held = rec.spans()
    assert [s.attrs["i"] for s in held] == [2, 3, 4, 5]
    assert rec.dropped == 2 and rec.dropped_max_id == opened[1].id
    # a parent ends after its children, so it is dropped after them
    with rec.span("a.p") as p:
        with rec.span("a.c") as c:
            pass
    assert rec.dropped == 4 and rec.dropped_max_id == opened[3].id
    assert rec.spans()[-2:] == [c, p]
    rec.clear()
    assert rec.spans() == [] and rec.dropped == rec.dropped_max_id == 0


def test_compiles_land_on_the_innermost_open_span():
    obs.RECORDER.clear()
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(7.0)
    with obs.span("t.outer") as outer:
        with obs.span("t.inner") as inner:
            f(x).block_until_ready()
        f(x).block_until_ready()           # compiled already
    assert inner.counts.get("compiles", 0) >= 1
    assert "compiles" not in outer.counts


def small_state(B=12, m=8, nodes=2, cap=8):
    """A decode-cache-shaped pytree split over ``nodes`` nodes of ``cap``
    rows on the first device."""
    rng = np.random.default_rng(0)
    cache = {"blocks": ({"attn": {"k": jnp.asarray(
        rng.normal(size=(2, B, 4, 2)), jnp.float32)}},)}
    req_bucket = route(np.arange(B) + 7, m)
    backend = JaxBackend()
    ctl = ElasticController(
        m, nodes, tau=0.2, planner=ElasticPlanner(policy="ssm_jit"),
        executor=MigrationExecutor(backend=backend, mode="live",
                                   verify="strict"))
    state = DeviceBucketedState.from_cache(
        cache, req_bucket, ctl.assign.owner_of(), cap=cap,
        devices=jax.devices()[:1])
    return ctl, state, backend


def test_do_resize_records_planner_check_and_transfer():
    from repro.launch.serve import _do_resize
    ctl, state, backend = small_state()
    _do_resize(ctl, state, backend, 4, 0, False)     # compiles the DP
    obs.RECORDER.clear()
    info = _do_resize(ctl, state, backend, 2, 1, True)
    spans = obs.RECORDER.spans()
    scale, = by_name(spans, "elastic.scale")
    assert scale.parent == 0
    assert scale.attrs == {"n_before": 4, "n_after": 2}
    assert info["resize_s_wall"] == scale.dur_s
    phases = by_name(spans, "migrate.phase")
    assert phases and info["transfer_s_wall"] == pytest.approx(
        sum(p.dur_s for p in phases))
    assert [s.name for s in sorted(children(spans, scale),
                                   key=lambda s: s.t0)] == [
        "plan.search", "migrate.schedule", "plan.check"] + [
        "migrate.phase"] * len(phases)
    search, = by_name(spans, "plan.search")
    assert search.attrs["backend"] == "jit" and search.attrs["m"] == 8
    assert search.counts["attempts"] >= 1
    assert [s.name for s in sorted(children(spans, search),
                                   key=lambda s: s.t0)] == [
        "plan.prep", "plan.tables", "plan.dp", "plan.rebuild",
        "plan.decode"]
    assert "near_ties" in by_name(spans, "plan.dp")[0].counts
    assert by_name(spans, "plan.check")[0].counts == {"findings": 0}
    rows = 0
    for p in phases:
        kids = children(spans, p)
        pairs = by_name(kids, "migrate.dispatch")
        assert [k.name for k in kids] == ["migrate.dispatch"] * len(
            pairs) + ["migrate.wait"]
        assert p.counts["pairs"] == len(pairs)
        assert p.counts["rows"] == sum(k.counts["rows"] for k in pairs)
        assert p.counts["bytes"] == sum(k.counts["bytes"] for k in pairs)
        rows += p.counts["rows"]
    assert rows * state.row_nbytes == info["bytes_moved"] > 0
    assert len(by_name(spans, "serve.verify")) == 2
    for parent in [scale, search] + phases:
        assert_nested(spans, parent, 0.5)


def test_decode_nodes_records_one_step_per_call():
    from repro.configs import get_smoke
    from repro.launch.serve import _decode_nodes, decode_step_fn
    from repro.models import init_cache, init_params, prefill
    cfg = get_smoke("qwen2.5-3b")
    B, P, cap, m = 6, 8, 4, 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.vocab_size, jnp.int32)
    cache = init_cache(cfg, B, P + 4)
    logits, cache = prefill(params, cfg, {"tokens": prompts}, cache)
    tok = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
    req_bucket = route(np.arange(B) + 1000, m)
    ctl = ElasticController(m, 3, tau=0.5)
    state = DeviceBucketedState.from_cache(
        cache, req_bucket, ctl.assign.owner_of(), cap=cap,
        devices=jax.devices()[:1])
    nodes = [i for i in state.node_ids() if (state.row_req[i] >= 0).any()]
    step_fn = decode_step_fn(cfg)
    _decode_nodes(state, step_fn, lambda d: params, tok, P)   # compiles
    obs.RECORDER.clear()
    out = _decode_nodes(state, step_fn, lambda d: params, tok, P + 1)
    assert out.shape == tok.shape
    spans = obs.RECORDER.spans()
    step, = by_name(spans, "serve.step")
    assert step.parent == 0
    assert step.counts == {"nodes": len(nodes), "rows_live": B,
                           "rows_decoded": cap * len(nodes)}
    per_node = children(spans, step)
    assert [s.attrs for s in per_node] == [
        {"node": i, "device": jax.devices()[0].id} for i in nodes]
    for s in per_node:
        assert [k.name for k in children(spans, s)] == [
            "serve.dispatch", "serve.fetch"]
        assert_nested(spans, s)
    assert_nested(spans, step)


def test_profiler_trace_holds_each_span_by_name(tmp_path):
    obs.RECORDER.clear()
    x = jnp.arange(64.0)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("t.outer"):
            for _ in range(3):
                with obs.span("t.inner"):
                    (x * 2.0).block_until_ready()
    files = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    assert files
    traced = {}
    for plane in jax.profiler.ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("t."):
                        traced.setdefault(ev.name, []).append(ev.duration_ns)
    spans = obs.RECORDER.spans()
    assert sorted(traced) == ["t.inner", "t.outer"]
    for name, durs in traced.items():
        mine = sorted(s.t1 - s.t0 for s in by_name(spans, name))
        assert len(mine) == len(durs)
        for a, b in zip(mine, sorted(durs)):
            assert abs(a - b) <= max(0.05 * a, 50_000), (name, a, b)
