"""Migration-step dry run: planner-predicted bytes vs the collective bytes
XLA actually emits.

Two compiled resharding programs over an 8-device elastic axis:

* naive    — ``state[perm]`` with a *dynamic* permutation: GSPMD cannot see
             the pattern and conservatively all-gathers everything
             (plan-INDEPENDENT traffic — the kill-restart analogue).
* planned  — ``make_collective_migration``: the SSM plan compiled into
             phased static ``ppermute``s; per-device wire bytes ==
             phases × bucket bytes, exactly the Rödiger-phase schedule the
             planner predicted (the §5 live-migration executor on ICI).

Runs in a subprocess with 8 host CPU devices (``JAX_PLATFORMS=cpu``): it
is an HLO dry run by design, and the parent process may already hold the
accelerator, which one process owns at a time.
"""
import json
import os
import subprocess
import sys

from .common import REPO_ROOT

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import Assignment, ssm
from repro.runtime import (
    make_collective_migration, make_migration_step, plan_to_permutation,
    required_capacity,
)
from repro.roofline.hlo import analyze

m, chunk, n = 64, 16384, 8
rng = np.random.default_rng(0)
base_w = rng.uniform(0.5, 2.0, m)
s = np.full(m, chunk * 4.0)
mesh = jax.make_mesh((8,), ("data",))
rows = []
for n_old, n_new in [(8, 8), (8, 6), (8, 4), (4, 8)]:
    cuts = np.linspace(0, m, n_old + 1).round().astype(int)
    old = Assignment.from_boundaries(m, list(cuts))
    w = base_w.copy()
    if n_old == n_new:
        w[: m // 8] *= 6.0                       # skew forces a rebalance
    plan = ssm(old, n_new, w, s, 0.3)

    # naive dynamic-gather reshard
    sh = NamedSharding(mesh, P("data", None))
    step = jax.jit(make_migration_step(m), in_shardings=(sh, None),
                   out_shardings=sh)
    with mesh:
        comp = step.lower(jax.ShapeDtypeStruct((m, chunk), jnp.float32),
                          jax.ShapeDtypeStruct((m,), jnp.int32)).compile()
    naive = analyze(comp.as_text(), 8).collective_bytes

    # plan-aware ppermute program
    cap = required_capacity(plan)
    fn, phases, _ = make_collective_migration(plan, n, cap)
    from repro.compat import shard_map
    sharded = shard_map(fn, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"), check_vma=False)
    with mesh:
        comp2 = jax.jit(sharded).lower(
            jax.ShapeDtypeStruct((n, cap, chunk), jnp.float32)).compile()
    planned = analyze(comp2.as_text(), 8).collective_bytes
    rows.append({
        "n_old": n_old, "n_new": n_new,
        "plan_cost_bytes": plan.cost,
        "phases": phases,
        "naive_bytes_per_dev": naive,
        "planned_bytes_per_dev": planned,
        "expected_planned": phases * chunk * 4,
    })
print(json.dumps(rows))
"""


def main():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO_ROOT,
                         env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        print(out.stderr[-3000:])
        raise RuntimeError("migration dryrun child failed")
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    print("n_old,n_new,plan_cost_MB,phases,naive_MB_dev,planned_MB_dev,"
          "saving_x")
    for r in rows:
        saving = r["naive_bytes_per_dev"] / max(r["planned_bytes_per_dev"],
                                                1e-9)
        print(f"{r['n_old']},{r['n_new']},"
              f"{r['plan_cost_bytes']/1e6:.2f},{r['phases']},"
              f"{r['naive_bytes_per_dev']/1e6:.2f},"
              f"{r['planned_bytes_per_dev']/1e6:.2f},{saving:.1f}")
        # the compiled plan-aware program moves exactly the scheduled bytes
        assert abs(r["planned_bytes_per_dev"] - r["expected_planned"]) < 1.0
        assert r["planned_bytes_per_dev"] < r["naive_bytes_per_dev"]
    return rows


if __name__ == "__main__":
    main()
