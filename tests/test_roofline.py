"""Loop-aware HLO analyzer validation (the roofline's foundation)."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.roofline.hlo import analyze, parse_computations
from repro.roofline.terms import model_flops
from repro.models.config import SHAPES

REPO_ROOT = Path(__file__).resolve().parent.parent


def _costs(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    return analyze(txt, 1)


def test_xla_cost_analysis_counts_scan_body_once():
    """The empirical fact that motivates the custom analyzer."""
    def f(x, w):
        return lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    ca = jax.jit(f).lower(x, w).compile().cost_analysis()
    if isinstance(ca, list):  # jax<=0.4.x: one dict per addressable device
        ca = ca[0]
    assert ca["flops"] == pytest.approx(2 * 128 * 256 * 256)  # 1/10th!


def test_scan_flops_exact():
    def f(x, w):
        return lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    c = _costs(f, x, w)
    assert c.dot_flops == pytest.approx(10 * 2 * 128 * 256 * 256)
    assert 10 in c.while_trips


def test_nested_scan_flops_exact():
    def f(x, w):
        def outer(c, wi):
            c2, _ = lax.scan(lambda cc, _: (cc @ wi, None), c,
                             jnp.arange(5))
            return c2, None
        return lax.scan(outer, x, w)[0]
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    c = _costs(f, x, w)
    assert c.dot_flops == pytest.approx(50 * 2 * 128 * 256 * 256)
    assert sorted(c.while_trips) == [5, 10]


def test_dus_counts_slice_not_buffer():
    """In-place dynamic-update-slice must charge the slice, not the cache."""
    def f(cache, x):
        def body(c, xi):
            c = lax.dynamic_update_slice_in_dim(c, xi[None], 0, axis=0)
            return c, None
        return lax.scan(body, cache, x)[0]
    cache = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    c = _costs(f, cache, x)
    # 8 iterations × slice (256 f32) — far below 8 × full cache
    assert c.hbm_bytes < 8 * 1024 * 256 * 4


def test_collective_bytes_allreduce():
    import subprocess, sys, json
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.roofline.hlo import analyze
from repro.compat import shard_map
mesh = jax.make_mesh((8,), ("d",))
f = shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
              in_specs=P(None), out_specs=P(None))
txt = jax.jit(f).lower(jax.ShapeDtypeStruct((1024,), jnp.float32)
                       ).compile().as_text()
c = analyze(txt, 8)
print(json.dumps({"cb": c.collective_bytes,
                  "counts": c.collective_counts}))
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-1000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # ring all-reduce: 2 · bytes · (n-1)/n
    assert rec["cb"] == pytest.approx(2 * 1024 * 4 * 7 / 8)
    assert rec["counts"] == {"all-reduce": 1}


def test_model_flops_sane_across_archs():
    from repro.configs import ARCH_IDS, get_config
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            mf = model_flops(cfg, shape)
            assert mf > 0
            if shape.kind == "train":
                # 6·N·D dominates; sanity band around it
                approx = 6.0 * cfg.active_params() * shape.global_batch * \
                    shape.seq_len
                assert 0.3 * approx < mf < 12 * approx, (arch, shape.name)


def test_decode_useful_ratio_near_one_end_to_end():
    """Full pipeline check: a tiny dense decode step's analyzer flops match
    the analytic 2·N·B within tolerance (no remat/masking in decode)."""
    from repro.configs import get_smoke
    from repro.models import decode_step, init_cache, init_params
    cfg = get_smoke("qwen2.5-3b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B = 4
    cache = init_cache(cfg, B, 64)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.full((B,), 3, jnp.int32)
    txt = jax.jit(lambda p, c, t, q: decode_step(p, cfg, t, q, c)).lower(
        params, cache, tok, pos).compile().as_text()
    c = analyze(txt, 1)
    emb = cfg.vocab_size * cfg.d_model
    n_mm = cfg.n_params() - emb
    expect = 2.0 * n_mm * B
    assert 0.7 * expect < c.dot_flops < 1.6 * expect


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_device_peaks_table():
    from repro.roofline import PEAKS, TARGET_KIND, device_peaks
    v5e = PEAKS["TPU v5 lite"]
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    # 4 links x 50 GB/s is the published 1,600 Gbit/s per chip
    assert v5e.ici_links * v5e.ici_link_bw * 8 == 1600e9
    assert device_peaks(_Dev("tpu", "TPU v5 lite")) is v5e
    # a CPU run prices against the v5e target row
    assert device_peaks(_Dev("cpu", "cpu")) is PEAKS[TARGET_KIND] is v5e
    assert device_peaks() is v5e


def test_device_peaks_unknown_tpu_kind_is_an_error():
    from repro.roofline import device_peaks
    with pytest.raises(ValueError, match="TPU v99"):
        device_peaks(_Dev("tpu", "TPU v99"))


def test_migration_transfer_s_prices_the_given_peaks():
    from repro.roofline import PEAKS, migration_transfer_s
    v5e = PEAKS["TPU v5 lite"]
    assert migration_transfer_s([50e9, 25e9], "ici", v5e) == 1.5
    assert migration_transfer_s([819e9], "hbm", v5e) == 2.0
    with pytest.raises(ValueError):
        migration_transfer_s([1.0], "pcie", v5e)
