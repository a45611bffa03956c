"""GPipe pipeline tests: schedule correctness (pipeline == sequential),
transformer-stack equivalence, and the roll→collective-permute lowering."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.pipeline import (
    pipeline_apply, pipeline_transformer_blocks, stack_stages,
)

KEY = jax.random.PRNGKey(0)
REPO_ROOT = Path(__file__).resolve().parent.parent


def test_pipeline_equals_sequential_toy():
    """4-stage matmul pipeline == applying the 4 matmuls in order."""
    S, n_micro, mb, d = 4, 6, 3, 8
    ws = jax.random.normal(KEY, (S, d, d)) / jnp.sqrt(d)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (n_micro, mb, d))

    def stage_fn(w, y):
        return jnp.tanh(y @ w)

    out = pipeline_apply(ws, x, stage_fn)
    assert out.shape == x.shape
    want = x
    for s in range(S):
        want = jnp.tanh(want @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_stack_stages_shapes():
    p = {"w": jnp.zeros((8, 3, 5)), "b": jnp.zeros((8, 5))}
    s = stack_stages(p, 4)
    assert s["w"].shape == (4, 2, 3, 5)
    assert s["b"].shape == (4, 2, 5)
    with pytest.raises(AssertionError):
        stack_stages(p, 3)


def test_pipeline_transformer_matches_scan():
    """Pipelined block stack == the model's sequential _run_depth."""
    from repro.configs import get_smoke
    from repro.models import init_params
    from repro.models.transformer import _run_depth

    cfg = get_smoke("olmo-1b")          # uniform ("attn",) pattern, 4 layers
    params = init_params(cfg, KEY)
    B, S = 4, 32
    x = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (B, S, cfg.d_model), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    want = _run_depth(x, params, cfg, positions, "masked")
    got = pipeline_transformer_blocks(
        params["blocks"], x, cfg, positions, n_stages=2, n_micro=2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_pipeline_roll_lowers_to_collective_permute():
    """With the stage dim sharded over a mesh axis, the inter-stage roll
    becomes collective-permute traffic (checked in a subprocess with 4
    devices so this process keeps 1)."""
    import json
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.pipeline import pipeline_apply
from repro.roofline.hlo import analyze

mesh = jax.make_mesh((4,), ("stage",))
S, n_micro, mb, d = 4, 8, 2, 16
def stage_fn(w, y):
    return jnp.tanh(y @ w)
sh = lambda s: NamedSharding(mesh, s)
f = jax.jit(lambda ws, x: pipeline_apply(ws, x, stage_fn),
            in_shardings=(sh(P("stage", None, None)), sh(P())),
            out_shardings=sh(P()))
with mesh:
    comp = f.lower(jax.ShapeDtypeStruct((S, d, d), jnp.float32),
                   jax.ShapeDtypeStruct((n_micro, mb, d), jnp.float32)
                   ).compile()
c = analyze(comp.as_text(), 4)
print(json.dumps({"cp": c.collective_breakdown.get("collective-permute", 0),
                  "counts": c.collective_counts}))
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1500:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["cp"] > 0, f"no collective-permute emitted: {rec}"
