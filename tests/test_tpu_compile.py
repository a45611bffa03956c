"""Compiles for a described TPU v5e (no chip attached): the device programs
on the serving entry point's main path, at real widths.

The topology is described inside a module fixture, never at import: only one
process may load the TPU compiler's library, so describing it while pytest
collects would fail every other worker.  What the compiler refuses here
(tiling, VMEM, device memory) it would refuse on the chip.

Left out: the ``interval_gain``, ``decode_attention``, ``rglru_scan`` and
``mamba_scan`` Pallas kernels do not compile for the v5e (see ROADMAP.md,
D8); no entry point runs them.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compat import enable_x64
from repro.configs import get_config
from repro.core.ssm_jit import _compiled_dp
from repro.kernels.flash_attention import flash_attention_pallas
from repro.launch.serve import decode_step_fn
from repro.models import init_cache, init_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

V5E_HBM_BYTES = 16 * 2**30

# chip_smoke.py's serving phase: 32 requests, prompt 1,024, 32 generated
# tokens, node shards of 24 rows
PROMPT, GEN, SHARD_ROWS = 1024, 32, 24

# bucket of benchmarks/fig5_ssm_runtime.py's instance at m = 10^4
# (12 -> 16 nodes, tau 0.4): mpad 10,240, window 1,024, n' + 1 layers
SSM_MPAD, SSM_W, SSM_NK = 10_240, 1_024, 17


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache: keep it out of the cache, and the warnings quiet
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


def test_decode_step_full_width_fits_one_v5e(one_chip):
    cfg = get_config("qwen2.5-3b")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(lambda k: init_params(cfg, k), key)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, SHARD_ROWS, PROMPT + GEN + 1))
    tokens = jax.ShapeDtypeStruct((SHARD_ROWS, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((SHARD_ROWS,), jnp.int32)
    compiled = decode_step_fn(cfg).lower(
        _on(one_chip, params), _on(one_chip, cache), _on(one_chip, tokens),
        _on(one_chip, pos)).compile()
    n = _device_bytes(compiled)
    assert 0 < n <= V5E_HBM_BYTES, n


def test_ssm_dp_m10k_bucket_compiles_x64(one_chip, monkeypatch):
    # the DP's real arguments: the host's 1-D tables of the fig5 instance
    from benchmarks.fig5_ssm_runtime import scaling_instance
    from repro.core import ssm_jit
    from repro.core.ssm import ssm

    seen = []
    monkeypatch.setattr(ssm_jit, "ssm_jit",
                        lambda old, w, s, pre: seen.append(pre))
    ssm(*scaling_instance(10_000, 12, 16, 0.4, 0), backend="jit")
    pad = ssm_jit._pad_inputs(seen[0])
    assert (pad["mpad"], pad["W"], pad["nk"]) == (SSM_MPAD, SSM_W, SSM_NK)
    args = ssm_jit._dp_args(pad)
    assert sum(a.nbytes for a in jax.tree_util.tree_leaves(args)) < 2**20
    with enable_x64():
        compiled = _compiled_dp(SSM_MPAD, SSM_W, SSM_NK).lower(
            *_on(one_chip, args)).compile()
    choices, ties = compiled.out_info
    assert choices.shape == ties.shape == (SSM_NK - 1, SSM_MPAD, 2)
    assert choices.dtype == jnp.int32 and ties.dtype == jnp.bool_
    assert _device_bytes(compiled) <= V5E_HBM_BYTES


def test_flash_attention_kernel_compiles_v5e(one_chip):
    cfg = get_config("qwen2.5-3b")
    B, H, Hkv, hd = 32, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jax.ShapeDtypeStruct((B, H, PROMPT, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, Hkv, PROMPT, hd), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention_pallas).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
