"""The readers of the program's spans (``bench/program_spans.py`` and the
metrics that use it) on a recorder filled by hand, on a clock set by
hand."""
import sys

import pytest

from bench import harness
from bench.program_spans import window
import repro
from repro import obs

READERS = ["plan_search_ms", "plan_dp_ms", "plan_check_ms",
           "transfer_dispatch_ms", "decode_host_ms", "decode_row_share"]


class Clock:
    def __init__(self):
        self.ns = 0

    def __call__(self):
        return self.ns

    def wait(self, ms):
        self.ns += int(ms * 1e6)


def decode_step(rec, clock, nodes, live, cap=8, dispatch=1.0, fetch=10.0):
    with rec.span("serve.step"):
        clock.wait(0.5)
        for i in range(nodes):
            with rec.span("serve.node", node=i, device=0):
                with rec.span("serve.dispatch"):
                    clock.wait(dispatch)
                with rec.span("serve.fetch"):
                    clock.wait(fetch)
        rec.count("nodes", nodes)
        rec.count("rows_live", live)
        rec.count("rows_decoded", nodes * cap)


def resize(rec, clock, dp=True):
    with rec.span("elastic.scale"):
        with rec.span("plan.search"):
            with rec.span("plan.prep"):
                clock.wait(3)
            with rec.span("plan.dp" if dp else "plan.numpy"):
                clock.wait(30)
        with rec.span("migrate.schedule"):
            clock.wait(1)
        with rec.span("plan.check"):
            clock.wait(4)
        with rec.span("migrate.phase"):
            for _ in range(2):
                with rec.span("migrate.dispatch"):
                    clock.wait(2)
            with rec.span("migrate.wait"):
                clock.wait(20)


def fill(monkeypatch, capacity=obs.CAPACITY):
    """A warm-up step and event, then a window of three steps, the second
    of which fires an event; the steps decode 5 live rows of 16 on 2 nodes
    of 8 rows, the event step 9 of 32 on 4."""
    clock = Clock()
    monkeypatch.setattr(obs, "_now", clock)
    rec = obs.Recorder(capacity)
    decode_step(rec, clock, 2, 5, dispatch=50.0)      # warm-up
    resize(rec, clock)
    decode_step(rec, clock, 2, 5)
    resize(rec, clock)
    decode_step(rec, clock, 4, 9)
    decode_step(rec, clock, 2, 5)
    steps = [harness.Step(0, 0, None, 2, 8, 1),
             harness.Step(0, 0, 0, 4, 8, 1),
             harness.Step(0, 0, None, 2, 8, 1)]
    run = harness.Run(model=None, sizes=None, peaks=None, chips=1,
                      tokens_per_step=8, steps=steps, events=[{}])
    return rec, run


def read_all(monkeypatch, rec, run):
    monkeypatch.setattr(obs, "RECORDER", rec)
    return {m: harness.load_reader(m)(run) for m in READERS}


def test_readers_on_a_window(monkeypatch):
    rec, run = fill(monkeypatch)
    got = read_all(monkeypatch, rec, run)
    assert got == pytest.approx({
        "plan_search_ms": 33.0, "plan_dp_ms": 30.0, "plan_check_ms": 4.0,
        "transfer_dispatch_ms": 4.0,
        # 0.5 ms before the nodes and 1 ms of dispatch on each of 2 nodes
        "decode_host_ms": 2.5,
        "decode_row_share": 100.0 * 5 / 16})


def test_window_takes_the_last_calls(monkeypatch):
    rec, run = fill(monkeypatch)
    win = window(run, rec)
    assert [s.counts["nodes"] for s in win.steps] == [2, 4, 2]
    assert len(win.events) == 1 and win.events[0].id > win.steps[0].id
    assert [s.counts["nodes"] for s in win.quiet_steps(run)] == [2, 2]


def test_numpy_planner_has_no_dp(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(obs, "_now", clock)
    rec = obs.Recorder()
    resize(rec, clock, dp=False)
    decode_step(rec, clock, 2, 5)
    run = harness.Run(model=None, sizes=None, peaks=None, chips=1,
                      tokens_per_step=8, events=[{}],
                      steps=[harness.Step(0, 0, 0, 2, 8, 1)])
    got = read_all(monkeypatch, rec, run)
    assert got["plan_dp_ms"] is None and got["plan_search_ms"] == 33.0
    # the only step fired the event
    assert got["decode_host_ms"] is None and got["decode_row_share"] is None


def test_no_window_where_spans_were_dropped(monkeypatch):
    # 7 + 10 spans of warm-up, then 7 + 10 + 13 + 7 of the window: a ring
    # of 37 holds the window alone, one of 36 drops its first span
    rec, run = fill(monkeypatch, capacity=37)
    assert rec.dropped == 17
    assert window(run, rec) is not None
    rec, run = fill(monkeypatch, capacity=36)
    assert window(run, rec) is None
    assert set(read_all(monkeypatch, rec, run).values()) == {None}


def test_no_window_without_the_recorder(monkeypatch):
    rec, run = fill(monkeypatch)
    # as in a program without the module: it cannot be imported
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert window(run) is None
    for m in READERS:
        assert harness.load_reader(m)(run) is None
