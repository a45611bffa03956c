"""BENCHMARK.json against the files that serve it, and the metric readers
on a run recorded by hand."""
import json
import re
from pathlib import Path

import pytest

from bench import counts, harness, traffic
from bench.trace import Reduced

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_entry_has_its_files():
    for c in SPEC["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (ROOT / "bench" / "reference"
                / f"{cfg['reference']}.py").exists()
        harness.check.load_reference(cfg["reference"]).sizes(cfg)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert w["config"] in configs and NAME.match(w["name"])
        traffic.Mix.load(w["traffic"])
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(harness.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    assert "setup_s" in e2e


def test_every_cell_reports_enough():
    for w in SPEC["workloads"]:
        for trace in (False, True):
            names = {m["name"] for m in harness.metric_entries(
                SPEC, w["name"], trace)}
            if trace:
                assert names
            else:
                assert "setup_s" in names and len(names) >= 2


def _run():
    model = harness.check.load_reference("dense")
    s = model.sizes(harness.load_json(
        ROOT / "bench" / "configs" / "qwen25_3b.json"))
    run = harness.Run(model=model, sizes=s, peaks=counts.PEAKS["TPU v5 lite"], chips=1,
                      tokens_per_step=32, setup_s=12.5, window_s=1.0,
                      peak_bytes=10 ** 10, device_ids=[0],
                      trace_end=1.0)
    # 9 steps of 0.1 s, the fourth fired an event and took 0.2 s (so the
    # window holds 1.0 s)
    t = 0.0
    for i in range(9):
        dt = 0.2 if i == 3 else 0.1
        run.steps.append(harness.Step(t, t + dt, 0 if i == 3 else None,
                                      2, 32, 1025 + i))
        t += dt
    run.events.append(dict(resize_s_wall=0.08, transfer_s_wall=0.05,
                           row_moves=[(0, 0, 1e8)] * 4))
    return run


def read(name, run):
    return harness.load_reader(name)(run)


def test_readers_on_a_hand_made_run():
    run = _run()
    assert read("tokens_per_s", run) == pytest.approx(9 * 32 / 1.0)
    # gaps: eight of 0.1 s and one of 0.2 s, 32 requests each; the 95th
    # percentile lies in the 0.2 s gap
    assert read("token_gap_ms_p95", run) == pytest.approx(200.0)
    assert read("resize_ms", run) == pytest.approx(200.0)
    assert read("steady_step_ms", run) == pytest.approx(100.0)
    assert read("plan_ms", run) == pytest.approx(30.0)
    assert read("transfer_ms", run) == pytest.approx(50.0)
    # 400 MB read and written over HBM on one chip, in 50 ms
    assert read("transfer_roofline", run) == pytest.approx(
        100 * 2 * 4e8 / 819e9 / 0.05)
    flops = sum(run.model.decode_flops(run.sizes, 32, s.ctx)
                for s in run.steps if s.event is None)
    assert read("decode_mfu", run) == pytest.approx(
        100 * flops / 0.8 / 197e12)
    assert read("setup_s", run) == 12.5
    assert read("peak_hbm_bytes", run) == 10 ** 10


def test_readers_find_nothing_to_read():
    run = _run()
    run.events.clear()
    for s in run.steps:
        s.event = None
    for name in ("resize_ms", "plan_ms", "transfer_ms", "transfer_roofline",
                 "decode_roofline", "device_idle_share"):
        assert read(name, run) is None
    run.trace = Reduced(window_s=1.0, busy_s={0: 0.75}, module_s={},
                        module_calls={}, top_ops=[], idle_gaps=[])
    assert read("device_idle_share", run) == pytest.approx(25.0)
    assert read("decode_roofline", run) is None   # no decode program
    run.trace.module_s["jit__lambda(7)"] = 0.5
    least = sum(max(run.model.decode_flops(run.sizes, 32, s.ctx) / 197e12,
                    run.model.decode_min_bytes(run.sizes, 32, s.ctx) / 819e9)
                for s in run.steps)
    assert read("decode_roofline", run) == pytest.approx(100 * least / 0.5)


# ---------------------------------------------------------------------------
# which cells report which metrics
# ---------------------------------------------------------------------------

CHURN = {"token_gap_ms_p95", "resize_ms", "peak_hbm_bytes", "setup_s"}
STEADY = {"tokens_per_s", "token_gap_ms_p95", "peak_hbm_bytes", "setup_s"}


def reported(cell, trace=False):
    """The metric names a result line of ``cell`` carries."""
    return {m["name"] for m in harness.metric_entries(SPEC, cell, trace)}


def test_every_per_layer_metric_names_its_cells():
    for m in SPEC["per_layer"]:
        assert m["workloads"]
        for cell in m["workloads"]:     # each reports what the metric moves
            assert m["moves"] in reported(cell), (m["name"], cell)


def test_a_cell_in_no_list_gets_the_unlisted_metrics():
    assert reported("no_such.cell") == {
        m["name"] for m in SPEC["end_to_end"] if "workloads" not in m}
    assert reported("no_such.cell", trace=True) == set()


@pytest.mark.parametrize("cell, names", [
    ("qwen25_3b.churn", CHURN),
    ("olmo_1b.churn_m4096", CHURN),
    ("qwen25_3b.steady", STEADY),
    ("qwen25_3b.churn_4chip", CHURN),
    ("olmo_1b.steady", STEADY),
])
def test_cells_report_their_end_to_end_metrics(cell, names):
    assert reported(cell) == names


def test_four_chip_cells_within_limit():
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


def test_the_four_chip_mix():
    mix = traffic.Mix.load("churn_4chip")
    assert (mix.requests, mix.prompt, mix.gen, mix.buckets, mix.cap,
            mix.tau, mix.nodes, mix.event_period_s, mix.event_phase_s,
            mix.sample_responses) == (32, 1024, 64, 32, 32, 0.2, [1, 4],
                                      5.0, 2.5, 8)
    assert len(mix.events(SPEC["run_seconds"])) == 10


def test_the_olmo_steady_mix():
    """churn_m4096's requests and sizes at its first layout, no event."""
    mix, churn = traffic.Mix.load("steady_m4096"), traffic.Mix.load(
        "churn_m4096")
    assert (mix.requests, mix.prompt, mix.gen, mix.buckets, mix.cap,
            mix.tau, mix.nodes, mix.event_period_s, mix.event_phase_s,
            mix.sample_responses) == (24, 1024, 64, 4096, 16, 0.2, [2],
                                      None, None, 8)
    assert mix.nodes == churn.nodes[:1] and all(
        getattr(mix, k) == getattr(churn, k) for k in (
            "requests", "prompt", "gen", "buckets", "cap", "tau",
            "sample_responses"))
    assert mix.event_targets() == [] and mix.events(SPEC["run_seconds"]) == []
