#!/usr/bin/env python3
"""The control of ``correct``, on the chip at a cell's own size.

    python3 bench/tests/control_chip.py --workload qwen25_3b.churn \\
        --seconds 10 --seeds 11 12 13

For each seed, one run of the cell as the benchmark makes it (a window at
the cell's load, then the check), in one process; beside the program's
readings of the compared numbers, each control's (``check.CONTROLS``: the
reference with its weights, or its weights and matmul inputs, rounded to
int8), and whether ``check.judge`` finds each correct under the
configuration's limits.  The program's readings over a dozen seeds or
more give a limit's lower reading, the controls' its upper one.  Exits 1
where a control is judged correct on any seed.  The benchmark's own runs
do not run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def readings(g):
    """The numbers that may be compared, from the gaps [rows, gen], and
    each checked row's widest gap."""
    from bench import check
    out = {n: f(g) for n, f in check.NUMBERS.items()}
    out["row_widest"] = [float(x) for x in g.max(1)]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, counts, harness, traffic
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("control_chip: no TPU", file=sys.stderr)
        return 2
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = {w["name"]: w for w in spec["workloads"]}[args.workload]
    cfg = harness.load_json(harness.BENCH / "configs"
                            / f"{cell['config']}.json")
    mix = traffic.Mix.load(cell["traffic"])
    rows = []
    for seed in args.seeds:
        out = harness.run_cell(
            cfg, mix, seed, args.seconds, False, devs[:cell["chips"]],
            counts.peaks(devs[0].device_kind), time.perf_counter(),
            controls=check.CONTROLS,
            log=lambda m: print(m, file=sys.stderr, flush=True))
        row = {"seed": seed}
        for q, g in out["gaps"].items():
            row[q or "program"] = readings(g)
            row[q or "program"]["correct"] = check.judge(g, cfg["check"])[1]
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in rows[0]:
        if k != "seed":
            for n in check.NUMBERS:
                vals = [r[k][n] for r in rows]
                summary[f"{k}.{n}"] = [min(vals), max(vals)]
            summary[f"{k}.correct"] = sum(r[k]["correct"] for r in rows)
    print(json.dumps(summary))
    return int(any(summary[f"{q}.correct"] for q in check.CONTROLS))


if __name__ == "__main__":
    sys.exit(main())
