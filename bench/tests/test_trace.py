"""The trace reduction, on a small trace recorded on a TPU v5e and on
synthetic planes.

``data/small.xplane.pb`` holds a window (host span ``window``) with three
rounds of ``decode`` (a 1024x1024 bf16 matmul program), ``host`` (a sleep)
and ``resize`` (an elementwise program).  Its raw events, read once with
``jax.profiler.ProfileData`` and written out below, give the expected
numbers by hand.
"""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).parent / "data" / "small.xplane.pb"

# window span on the host line, ns
WINDOW = (42421870, 54887930)
# XLA Ops events on /device:TPU:0 (start, end) ns; the first program ran
# (by the trace's clocks) before the window opened
OPS = [(41234713, 41234727), (41234728, 41237864), (41237865, 41249722),
       (44172559, 44177371),
       (45018719, 45018732), (45018734, 45021844), (45021845, 45033701),
       (48722044, 48726925),
       (49566594, 49566607), (49566609, 49569914), (49569915, 49581771),
       (52988919, 52993789)]


def test_recorded_trace():
    r = trace.reduce_file(DATA)
    assert r.window_s == pytest.approx((WINDOW[1] - WINDOW[0]) * 1e-9)
    inside = [(s, e) for s, e in OPS if s >= WINDOW[0]]
    # the ops of one program are back to back, never overlapping
    busy = sum(e - s for s, e in inside) * 1e-9
    assert busy == pytest.approx(44716e-9)
    assert r.busy_s == {0: pytest.approx(busy)}
    # the matmul program: two runs inside the window, 14985 + 15181 ns
    f = "jit__lambda(6074760096634504725)"
    g = "jit__lambda(14206568751377546585)"
    assert r.module_s[f] == pytest.approx(30166e-9)
    assert r.module_calls == {f: 2, g: 3}
    assert r.module_s[g] == pytest.approx((4816 + 4885 + 4873) * 1e-9)
    assert r.module_time_s(r"^jit__lambda") == pytest.approx(
        (30166 + 14574) * 1e-9)
    assert r.module_time_s("decode_step") is None
    # op names shortened from their HLO text; 2 x 11856 ns of fusion
    assert r.top_ops[0] == ("fusion", pytest.approx(23712e-9))
    assert [n for n, _ in r.top_ops] == [
        "fusion", "multiply_reduce_fusion", "copy-done", "copy-start"]
    idle = r.window_s - busy
    assert sum(r.idle_by_span.values()) == pytest.approx(idle)
    assert len(r.idle_gaps) == 10


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, end_ns=e,
                               duration_ns=e - s) for n, s, e in evs])
        for ln, evs in lines.items()])


def test_synthetic_gaps_by_host_span():
    host = _plane("/host:CPU", {"python3": [
        ("window", 0, 100), ("decode", 0, 40), ("resize", 40, 90),
        ("host", 90, 100)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [("%fusion.3 = f32[] fusion()", 10, 30),
                    ("%fusion.3 = f32[] fusion()", 20, 35),   # overlaps
                    ("%copy.1 = f32[] copy()", 60, 70)],
        "XLA Modules": [("jit__lambda(1)", 10, 35), ("jit_take(2)", 60, 70)]})
    r = trace.reduce_planes([host, dev])
    assert r.busy_s[0] == pytest.approx(35e-9)   # [10,35) and [60,70)
    # gaps: [0,10) decode, [35,60) resize, [70,100): resize 20, host 10
    assert r.idle_by_span == {"decode": pytest.approx(10e-9),
                              "resize": pytest.approx(55e-9)}
    assert r.idle_gaps[0] == ("resize", pytest.approx(30e-9))
    assert r.top_ops == [("fusion.3", pytest.approx(35e-9)),
                         ("copy.1", pytest.approx(10e-9))]
    assert r.module_time_s(r"^jit__lambda") == pytest.approx(25e-9)
    assert r.busy_mean_s([0, 1]) == pytest.approx(17.5e-9)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_planes([_plane("/host:CPU", {"t": [("decode", 0, 1)]})])
