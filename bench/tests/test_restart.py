"""A restarted response starts from its request's prompt-end state.

Every response of a mix restarts at position ``prompt``.  A recurrent leaf
keeps all that was decoded since the prompt, so the harness's
``Restart`` writes the prompt-end rows of the leaves a reference names
back before each response.  Here the program's falcon-mamba-7b smoke
configuration, with its own weights, serves response A, an event moves
rows between nodes (2 -> 4 on one device, 1 -> 4 on four), and response
B is fed A's tokens from the same first token.  Restored, B's logits are
A's; without the restore they differ, as each cache leaf of a recurrent
model holds the decoded tokens.  A dense reference names no leaf, and
its restart leaves every shard as it was.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import dense
from repro.configs import get_smoke
from repro.core import ElasticPlanner
from repro.launch.serve import _decode_nodes, _do_resize, decode_step_fn
from repro.models import init_cache, init_params, prefill
from repro.runtime import (DeviceBucketedState, ElasticController,
                           JaxBackend, MigrationExecutor, route)

B, P, G, M = 8, 16, 6, 8


def recurrent(names):
    return names[-1] in ("h", "conv")


def nothing(names):
    return False


def prefilled(arch, seed=0):
    pcfg = get_smoke(arch)
    key = jax.random.PRNGKey(seed)
    params = init_params(pcfg, key)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, pcfg.vocab_size, (B, P), dtype=np.int32)
    cache = init_cache(pcfg, B, P + G + 1)
    _, cache = jax.jit(prefill, static_argnums=1)(
        params, pcfg, {"tokens": jnp.asarray(prompts)}, cache)
    first = rng.integers(0, pcfg.vocab_size, (B, 1), dtype=np.int32)
    return pcfg, params, cache, first


def serving(cache, devices, nodes, cap):
    backend = JaxBackend()
    ctl = ElasticController(
        M, nodes, tau=0.2, planner=ElasticPlanner(policy="ssm"),
        executor=MigrationExecutor(backend=backend, mode="live",
                                   verify="strict"))
    state = DeviceBucketedState.from_cache(
        cache, route(np.arange(B) + 1000, M), ctl.assign.owner_of(),
        cap=cap, devices=devices)
    return ctl, backend, state


def serve_twice(chips, named):
    """Logits [B, G, V] of response A, and of response B after an event,
    fed A's tokens; the requests the event moved."""
    devices = jax.devices()[:chips]
    assert len(devices) == chips, "XLA_FLAGS asks for fewer CPU devices"
    nodes = [2, 4] if chips == 1 else [1, 4]
    pcfg, params, cache, first = prefilled("falcon-mamba-7b")
    restart = harness.Restart(cache, named, devices)
    ctl, backend, state = serving(cache, devices, nodes[0],
                                  cap=6 if chips == 1 else B)
    placed = {d: jax.device_put(params, d) for d in devices}
    step = decode_step_fn(pcfg)
    seen = []

    def recorded(p, c, t, pos):
        logits, shard = step(p, c, t, pos)
        seen.append(np.asarray(logits, np.float32).reshape(state.cap, -1))
        return logits, shard

    def respond(feeds):
        restart(state)
        out = np.zeros((B, G, pcfg.vocab_size), np.float32)
        feed = feeds[0]
        for s in range(G):
            seen.clear()
            tok = _decode_nodes(state, recorded, placed.__getitem__, feed,
                                P + s)
            live = [i for i in state.node_ids()
                    if (state.row_req[i] >= 0).any()]
            for i, lg in zip(live, seen, strict=True):
                rows = state.row_req[i]
                out[rows[rows >= 0], s] = lg[rows >= 0]
            feeds.append(tok)
            feed = feeds[s + 1]
        return out

    fed = [first]
    a = respond(fed)
    before = state.req_node.copy()
    _do_resize(ctl, state, backend, nodes[1], -1, False)
    moved = np.nonzero(state.req_node != before)[0]
    b = respond(fed[:G])
    return a, b, moved


@pytest.mark.parametrize("chips", [1, 4])
def test_restored_response_equals_a_fresh_one(chips):
    a, b, moved = serve_twice(chips, recurrent)
    assert len(moved)
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chips", [1, 4])
def test_without_the_restore_the_response_differs(chips):
    a, b, moved = serve_twice(chips, nothing)
    assert len(moved)
    assert np.abs(b - a).max() > 0.1


def test_dense_names_no_leaf_and_its_restart_touches_nothing():
    pcfg, params, cache, first = prefilled("olmo-1b")
    names = [harness.key_names(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(cache)[0]]
    assert names and not any(dense.prefix_leaves(n) for n in names)
    restart = harness.Restart(cache, dense.prefix_leaves, jax.devices()[:1])
    assert restart.snapshot == {}
    _, _, state = serving(cache, jax.devices()[:1], 2, cap=6)
    before = {i: jax.tree_util.tree_leaves(sh)
              for i, sh in state.shards.items()}
    assert restart(state) == 0.0
    assert set(state.shards) == set(before)
    for i, sh in state.shards.items():
        after = jax.tree_util.tree_leaves(sh)
        assert all(x is y for x, y in zip(after, before[i], strict=True))
