"""``correct`` comes out false when the timed path is broken underneath.

Each test plants one fault in the program's serving path (monkeypatched,
at small sizes on the CPU), drives the rest of a run as the benchmark
does, past its look for a chip, and sees ``correct`` false under the
configuration's own limits:

- a decode step that returns its state (the KV cache shard) unchanged;
- half of the batch left out: half the requests never get a new token;
- the exchange between nodes left out: an event's rows are booked on
  their new node but never copied there;
- a token altered where it is produced.

Each runs on one chip for both configurations, and for qwen2.5-3b also on
four (four CPU devices, 1 <-> 4 nodes), where the exchange left out is
the one between chips.
"""
import jax
import jax.numpy as jnp
import pytest

import repro.launch.serve as serve
from repro.runtime.state import DeviceBucketedState

from conftest import run_small

CONFIGS = ["qwen25_3b", "olmo_1b",
           pytest.param(("qwen25_3b", 4), id="qwen25_3b-4chips")]


def run_case(case):
    name, chips = case if isinstance(case, tuple) else (case, 1)
    out = run_small(name, chips=chips)
    assert len(out["run"].device_ids) == chips
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_sound_run_is_correct(name):
    out = run_case(name)
    assert out["correct"], out["checks"]
    run = out["run"]
    assert run.events and run.compiles_in_window == 0
    across = {src != dst for e in run.events for src, dst, _ in e["row_moves"]}
    assert across == ({True} if len(run.device_ids) > 1 else {False})


@pytest.mark.parametrize("name", CONFIGS)
def test_state_left_unchanged(name, monkeypatch):
    orig = serve.decode_step_fn

    def stale(cfg):
        step = orig(cfg)
        return lambda p, c, t, pos: (step(p, c, t, pos)[0], c)

    monkeypatch.setattr(serve, "decode_step_fn", stale)
    assert not run_case(name)["correct"]


@pytest.mark.parametrize("name", CONFIGS)
def test_half_the_batch_left_out(name, monkeypatch):
    orig = serve._decode_nodes

    def half(state, step_fn, params_on, tok, pos_val):
        out = orig(state, step_fn, params_on, tok, pos_val)
        out[len(out) // 2:] = tok[len(out) // 2:]
        return out

    monkeypatch.setattr(serve, "_decode_nodes", half)
    assert not run_case(name)["correct"]


@pytest.mark.parametrize("name", CONFIGS)
def test_exchange_left_out(name, monkeypatch):
    orig = DeviceBucketedState.run_phase

    def no_copy(self, phase):
        dst = {int(mv.dst) for mv in phase}
        before = {i: self.shards[i] for i in dst if i in self.shards}
        moved = orig(self, phase)
        for i in dst:       # the rows were booked, their data never came
            self.shards[i] = before.get(i, jax.tree_util.tree_map(
                jnp.zeros_like, self.shards[i]))
        return moved

    monkeypatch.setattr(DeviceBucketedState, "run_phase", no_copy)
    out = run_case(name)
    assert out["run"].events and not out["correct"]


@pytest.mark.parametrize("name", CONFIGS)
def test_token_altered(name, monkeypatch):
    orig = serve._decode_nodes
    calls = [0]

    def altered(state, step_fn, params_on, tok, pos_val):
        out = orig(state, step_fn, params_on, tok, pos_val)
        calls[0] += 1
        r = calls[0] % len(out)
        out[r] = (out[r] + 1) % 512
        return out

    monkeypatch.setattr(serve, "_decode_nodes", altered)
    assert not run_case(name)["correct"]
