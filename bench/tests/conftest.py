"""Small sizes of the benchmark's configurations, for runs on the CPU.

Run these tests from the repository's root:
``JAX_PLATFORMS=cpu python3 -m pytest bench/tests``."""
import dataclasses
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# four CPU devices, for the four-chip cell's path (set before JAX starts)
_FOUR = "--xla_force_host_platform_device_count"
if _FOUR not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS"), f"{_FOUR}=4"]))

ARCH = {"qwen25_3b": "qwen2.5-3b", "olmo_1b": "olmo-1b"}


def small(name, chips=1, **widths):
    """The configuration file at the program's smoke sizes, or at the
    ``ModelConfig`` sizes given in ``widths``; the program's configuration
    at those sizes; and a small churn mix: 2 <-> 4 nodes on one chip, or
    with ``chips`` 4, 1 <-> 4 nodes, one node holding every request."""
    from bench import harness, traffic
    from repro.configs import get_smoke
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    p = dataclasses.replace(get_smoke(ARCH[name]), **widths)
    cfg["model"].update(
        num_hidden_layers=p.n_layers, hidden_size=p.d_model,
        num_attention_heads=p.n_heads, num_key_value_heads=p.n_kv_heads,
        intermediate_size=p.d_ff, vocab_size=p.vocab_size,
        rope_theta=p.rope_theta)
    mix = traffic.Mix(requests=8, prompt=16, gen=8, buckets=8,
                      cap=6 if chips == 1 else 8, tau=0.2,
                      nodes=[2, 4] if chips == 1 else [1, 4],
                      event_period_s=0.4, event_phase_s=0.2,
                      sample_responses=6)
    return cfg, mix, p


def run_small(name, seed=2 ** 31 + 5, seconds=1.0, controls=(), trace=False,
              mix=None, chips=1, **widths):
    import jax
    from bench import counts, harness
    cfg, small_mix, p = small(name, chips, **widths)
    devices = jax.devices()[:chips]
    assert len(devices) == chips, "XLA_FLAGS asks for fewer than four CPU devices"
    return harness.run_cell(
        cfg, mix or small_mix, seed, seconds, trace, devices,
        counts.PEAKS["TPU v5 lite"], time.perf_counter(), pcfg=p,
        controls=controls, log=lambda m: None)
