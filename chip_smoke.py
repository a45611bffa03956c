#!/usr/bin/env python3
"""Smoke test of the system's main path on a TPU.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: phase A across devices

Phase A (serving): ``repro.launch.serve.run_serving`` at qwen2.5-3b's full
width with random weights from a seed — 32 requests, prompt 1,024, 32
generated tokens, 32 buckets — run twice with the same seed: once straight
through, once with a live elastic resize at step 16 that plans with SSM,
checks the plan (``plancheck`` strict), moves the real KV cache rows and
verifies them.  The tokens must be bit-identical across the two runs.
On one chip, 2 -> 4 nodes share device 0.  With ``--chips 4`` only this
phase runs: 1 -> 4 nodes on devices 0-3, against the same run on device 0.

Phase B (SSM planner on the device): the ``benchmarks/fig5_ssm_runtime.py``
instance (12 -> 16 nodes, tau 0.4, seed 0).  At m = 1,024 the jit backend's
plan (assignment and gain) must equal the numpy reference's; at m = 10^4,
where numpy takes minutes, its gain must equal the recorded one.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
check, and any platform other than a TPU, exits non-zero without it.
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ARCH = "qwen2.5-3b"
SERVE = dict(requests=32, prompt_len=1024, gen=32, buckets=32, seed=0)
RESIZE_STEP = 16
# plan gain of the fig5 instance at m = 10^4 (BENCH_ssm.json: the numpy
# and jit backends on a CPU host)
SSM_GAIN_10K = 15668.43832576746


def _ms(s: float) -> str:
    return f"{s * 1e3:.3f}"


def phase_serving(devices, nodes: int, cap: int, base_devices) -> None:
    import numpy as np

    from repro.launch.serve import run_serving

    kw = dict(arch=ARCH, smoke=False, nodes=nodes, cap=cap, **SERVE)
    print(f"phase A: {ARCH} full width, {SERVE['requests']} requests x "
          f"prompt {SERVE['prompt_len']} x gen {SERVE['gen']}, "
          f"{SERVE['buckets']} buckets, cap {cap}; "
          f"{nodes} -> 4 nodes at step {RESIZE_STEP} on devices "
          f"{[d.id for d in devices]}, reference on devices "
          f"{[d.id for d in base_devices]}", flush=True)
    t0 = time.perf_counter()
    base = run_serving(resize=None, devices=base_devices, **kw)
    print(f"  reference run: {time.perf_counter() - t0:.3f} s "
          f"(compiles included), steady step {_ms(base.steady_s)} ms",
          flush=True)
    t0 = time.perf_counter()
    res = run_serving(resize=(RESIZE_STEP, 4), devices=devices, **kw)
    print(f"  resize run:    {time.perf_counter() - t0:.3f} s", flush=True)
    r = res.resize
    steps = np.asarray(res.step_s)
    print(f"  prefill_s {res.prefill_s:.6f}")
    print(f"  first_step_ms {_ms(steps[0])} (compiles included)")
    print(f"  step_ms_median before resize "
          f"{_ms(np.median(steps[1:RESIZE_STEP]))} ({nodes} nodes), after "
          f"{_ms(np.median(steps[RESIZE_STEP + 1:]))} (4 nodes)")
    print(f"  resize_step_ms {_ms(res.spike_s)} (plan + transfer + decode; "
          f"snapshot and verification excluded)")
    print(f"  transfer_ms {_ms(r['transfer_s_wall'])}")
    print(f"  bytes_moved {r['bytes_moved']:.0f} in {r['moves']} moves, "
          f"{r['phases']} phases")
    print(f"  predicted_transfer_ms ici {_ms(r['predicted_ici_s'])} "
          f"hbm {_ms(r['predicted_hbm_s'])}")
    print(f"  node devices after resize {r['node_devices']}")
    for d in sorted(set(devices) | set(base_devices), key=lambda d: d.id):
        stats = d.memory_stats() or {}
        print(f"  device {d.id} peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')}")
    V = 151_936
    B, G = SERVE["requests"], SERVE["gen"]
    assert base.tokens.shape == res.tokens.shape == (B, G + 1), \
        res.tokens.shape
    assert ((res.tokens >= 0) & (res.tokens < V)).all(), "token id range"
    same = bool(np.array_equal(base.tokens, res.tokens))
    print(f"  tokens_identical {same}")
    assert same, "decode diverged across the resize"
    assert r["bytes_moved"] > 0, "the resize moved no state"
    assert r["routing_ok"], "requests not routed by the new ownership"
    assert r["verified"], "resharding verification did not run"
    assert r["n_after"] == 4, r["n_after"]
    want = sorted({d.id for d in devices})
    assert sorted(set(r["node_devices"])) == want, r["node_devices"]


def phase_ssm() -> None:
    from benchmarks.fig5_ssm_runtime import scaling_instance
    from repro.core.ssm import ssm

    print("phase B: SSM planner, jit backend on the device", flush=True)
    inst = scaling_instance(1024)
    t0 = time.perf_counter()
    pj = ssm(*inst, backend="jit")
    tj = time.perf_counter() - t0
    t0 = time.perf_counter()
    pn = ssm(*inst, backend="numpy")
    tn = time.perf_counter() - t0
    print(f"  m=1024 jit gain {pj.gain!r} ({tj:.3f} s incl. compile), "
          f"numpy gain {pn.gain!r} ({tn:.3f} s)")
    assert pj.new.intervals == pn.new.intervals, "jit plan != numpy plan"
    assert pj.gain == pn.gain, (pj.gain, pn.gain)
    inst = scaling_instance(10_000)
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        pj = ssm(*inst, backend="jit")
        times.append(time.perf_counter() - t0)
    print(f"  m=10000 jit gain {pj.gain!r} (first {times[0]:.3f} s, "
          f"second {times[1]:.3f} s)")
    assert pj.gain == SSM_GAIN_10K, (pj.gain, SSM_GAIN_10K)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the serving resize across four chips")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform "
              f"{devs[0].platform!r}; this smoke test runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    print(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
          f"{use_compile_cache()}", flush=True)

    if args.chips == 4:
        phases = [("serving across 4 chips",
                   lambda: phase_serving(devs[:4], 1, 32, devs[:1]))]
    else:
        phases = [("ssm", phase_ssm),
                  ("serving", lambda: phase_serving(devs[:1], 2, 24,
                                                    devs[:1]))]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:  # noqa: BLE001 - reported, and the exit is 1
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
