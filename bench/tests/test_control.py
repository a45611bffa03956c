"""The controls of ``correct`` at a size a test run can hold.

A control is the reference computed a precision below the
configuration's bfloat16 (``CONTROLS`` of ``bench/check.py``: weights
rounded to int8, and weights and matmul inputs rounded to int8), put in
the program's place.  ``check.judge`` has to find each control not
correct where it finds the program correct.

The gaps scale with the logits, which grow with the width, so at the
smoke sizes a control's gaps lie among the program's.  This test runs
at d_model 1,024 over 4 layers and a vocabulary of 16,384, where six
seeds on the CPU read (program's largest / weights-only int8's least):
qwen2.5-3b mean gap 0.000405 / 0.00102, widest gap 0.0152 / 0.0356;
OLMo-1B mean gap 0.000334 / 0.000732.  ``LIMITS`` lie between them.  The
cells' own limits, for their sizes, come from ``control_chip.py`` on the
chip, which judges every control under them (PERF.md, section 6).
"""
import pytest

from bench import check, traffic

from conftest import run_small

WIDTHS = {"qwen25_3b": dict(n_kv_heads=2),
          "olmo_1b": dict(n_kv_heads=16)}
LIMITS = {"qwen25_3b": {"widest_gap": 0.025, "mean_gap": 0.0007},
          "olmo_1b": {"mean_gap": 0.0005}}
MIX = traffic.Mix(requests=8, prompt=32, gen=16, buckets=8, cap=6, tau=0.2,
                  nodes=[2, 4], event_period_s=2.0, event_phase_s=0.2,
                  sample_responses=8)


@pytest.mark.parametrize("name", ["qwen25_3b", "olmo_1b"])
def test_controls_are_not_correct(name):
    out = run_small(name, seed=2 ** 31 + 200, seconds=8.0,
                    controls=check.CONTROLS, mix=MIX, n_layers=4,
                    d_model=1024, n_heads=16, d_ff=2816, vocab_size=16384,
                    **WIDTHS[name])
    assert out["correct"], out["checks"]
    checks, ok = check.judge(out["gaps"][None], LIMITS[name])
    assert ok, checks
    for q in check.CONTROLS:
        checks, ok = check.judge(out["gaps"][q], LIMITS[name])
        assert not ok, (q, checks)
