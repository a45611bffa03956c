"""Plain reference of a dense decoder (Qwen2 and OLMo), and the weights
the benchmark serves.

``sizes`` reads a configuration file; ``program_config`` sets the
program's options as it states, and ``check_program`` holds the program's
configuration to it; the count functions give the operations
and bytes a decode step and a state transfer need, from the sizes alone.
``prefix_leaves`` names the cache leaves whose prompt-end rows a restarted
response gets back: none, as K and V are kept by position.

``make_weights`` draws the weights from a key, on the device, in the type
they are served in (bfloat16), laid out as the program's parameter tree
takes them: stacked layers under ``blocks``, norm scales stored as offsets
from 1 (RMSNorm) or absent (OLMo's non-parametric LayerNorm).

``logits`` is the model's full forward pass over whole sequences in
float32 with HIGHEST matmul precision: no kernels, no cache, no batching
tricks.  It follows the published architectures: RMSNorm or
non-parametric LayerNorm, rotary embeddings (rotate-half form), grouped
query attention with a causal mask, and a SwiGLU MLP.  With ``quant``
it is a control: the same pass with every weight matrix rounded to int8
first, one scale per output channel (``"w8"``), or with every matmul
input rounded to int8 as well, one scale per token (``"w8a8"``).

Nothing here imports the program.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dense:
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    vocab: int
    tied: bool
    rms: bool            # RMSNorm with a scale, else non-parametric LN
    eps: float
    theta: float
    qkv_bias: bool
    dtype_bytes: int

    @classmethod
    def of(cls, cfg: Mapping) -> "Dense":
        m = cfg["model"]
        d, h = m["hidden_size"], m["num_attention_heads"]
        rms = m["norm"] == "rms"
        return cls(layers=m["num_hidden_layers"], d=d, heads=h,
                   kv_heads=m["num_key_value_heads"],
                   hd=m.get("head_dim") or d // h,
                   ff=m["intermediate_size"], vocab=m["vocab_size"],
                   tied=bool(m["tie_word_embeddings"]), rms=rms,
                   eps=m["rms_norm_eps"] if rms else m["layer_norm_eps"],
                   theta=float(m["rope_theta"]),
                   qkv_bias=bool(m["qkv_bias"]),
                   dtype_bytes={"bfloat16": 2}[
                       m.get("serve_dtype") or m["torch_dtype"]])


def sizes(cfg: Mapping) -> Dense:
    """The sizes of a configuration file (``bench/configs/<name>.json``)."""
    return Dense.of(cfg)


def program_config(pcfg, s: Dense):
    """The program's configuration with the options it offers set as the
    configuration file states: the LM head tied to the embedding or not
    (``repro.models.ModelConfig.tie_embeddings``)."""
    return replace(pcfg, tie_embeddings=s.tied)


def check_program(pcfg, s: Dense) -> None:
    """The program's configuration (``repro.models.ModelConfig``) must be
    the one the benchmark's file states."""
    got = (pcfg.n_layers, pcfg.d_model, pcfg.n_heads, pcfg.n_kv_heads,
           pcfg.hd, pcfg.d_ff, pcfg.vocab_size, bool(pcfg.tie_embeddings),
           pcfg.norm == "rms", float(pcfg.rope_theta), bool(pcfg.qkv_bias),
           pcfg.act, pcfg.param_dtype, pcfg.family, pcfg.n_experts,
           pcfg.window, pcfg.qk_norm)
    want = (s.layers, s.d, s.heads, s.kv_heads, s.hd, s.ff, s.vocab,
            s.tied, s.rms, s.theta, s.qkv_bias, "silu", "bfloat16",
            "dense", 0, 0, False)
    if got != want:
        raise ValueError(f"program config {pcfg.name} is {got}; the "
                         f"benchmark's file states {want}")


def prefix_leaves(names) -> bool:
    """Whether the cache leaf at key path ``names`` holds a request's
    state apart from position, to be restored when a response restarts
    (``bench.harness.Restart``).  No leaf of a dense decoder does: a
    restart overwrites K and V past the prompt, and masks them by
    position."""
    return False


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def layer_linear_params(s: Dense) -> int:
    """Weights of one layer's matrix multiplications: q, k, v, o and the
    gated MLP (gate, up, down)."""
    attn = 2 * s.d * s.heads * s.hd + 2 * s.d * s.kv_heads * s.hd
    return attn + 3 * s.d * s.ff


def kv_bytes_per_token(s: Dense) -> int:
    """K and V of one position, over all layers."""
    return s.layers * s.kv_heads * s.hd * 2 * s.dtype_bytes


def weight_bytes(s: Dense) -> int:
    """All stored matrices: the layers', the embedding, and the LM head
    where it is not tied (norm scales and biases, under 0.1%, left out)."""
    tables = 1 if s.tied else 2
    return (s.layers * layer_linear_params(s)
            + tables * s.vocab * s.d) * s.dtype_bytes


def decode_flops(s: Dense, rows: int, ctx: int) -> float:
    """Operations of one decode step of ``rows`` requests that each attend
    over ``ctx`` cached positions: 2 per weight of every layer's matrices
    and of the LM head, plus QK^T and PV over the context."""
    per_token = 2 * (s.layers * layer_linear_params(s) + s.vocab * s.d)
    attn = 4 * s.layers * s.heads * s.hd * ctx
    return float(rows * (per_token + attn))


def decode_min_bytes(s: Dense, rows: int, ctx: int) -> float:
    """The least HBM traffic of one decode step: every layer's matrices and
    the LM head read once, and each live row's K and V read over its
    context and written at its new position."""
    weights = (s.layers * layer_linear_params(s) + s.vocab * s.d)
    return float(weights * s.dtype_bytes
                 + rows * (ctx + 1) * kv_bytes_per_token(s))


def row_bytes(s: Dense, cache_len: int) -> float:
    """K and V of one request's cache row: the state an event moves."""
    return float(cache_len * kv_bytes_per_token(s))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=0)
def make_weights(s: Dense, key):
    """All weights from ``key``, bfloat16, in the program's tree layout.
    Matrices are N(0, 1/fan_in); embedding tables N(0, 0.02^2); norm
    offsets and biases N(0, 0.1^2), so that every term of the
    architecture is exercised."""
    bf = jnp.bfloat16
    L, d, H, K, hd, ff, V = (s.layers, s.d, s.heads, s.kv_heads, s.hd,
                             s.ff, s.vocab)
    names = iter(range(64))

    def draw(shape, scale):
        k = jax.random.fold_in(key, next(names))
        return jax.random.normal(k, shape, bf) * jnp.asarray(scale, bf)

    attn = {"wq": draw((L, d, H, hd), d ** -0.5),
            "wk": draw((L, d, K, hd), d ** -0.5),
            "wv": draw((L, d, K, hd), d ** -0.5),
            "wo": draw((L, H, hd, d), (H * hd) ** -0.5)}
    if s.qkv_bias:
        attn.update(bq=draw((L, H, hd), 0.1), bk=draw((L, K, hd), 0.1),
                    bv=draw((L, K, hd), 0.1))
    block = {"norm1": draw((L, d), 0.1) if s.rms else None,
             "attn": attn,
             "norm2": draw((L, d), 0.1) if s.rms else None,
             "mlp": {"w_gate": draw((L, d, ff), d ** -0.5),
                     "w_up": draw((L, d, ff), d ** -0.5),
                     "w_down": draw((L, ff, d), ff ** -0.5)}}
    w = {"embed": draw((V, d), 0.02),
         "final_norm": draw((d,), 0.1) if s.rms else None,
         "blocks": (block,), "tail": ()}
    if not s.tied:
        w["unembed"] = draw((V, d), 0.02)
    return w


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fake_int8(x, axes):
    """Symmetric int8 rounding with one scale per slice over ``axes``."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale



def _norm(s: Dense, x, offset):
    if s.rms:
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s.eps)
        return y * (1.0 + offset.astype(F32))
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + s.eps)


def _rope(x, sin, cos):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnums=(0, 3, 4))
def logits(s: Dense, w, tokens, first: int, quant: Optional[str] = None):
    """Logits [b, S - first, V] (float32) at positions first..S-1 of the
    sequences ``tokens`` [b, S]: position t predicts token t + 1."""
    if quant not in (None, "w8", "w8a8"):
        raise ValueError(quant)

    def weight(m, in_axes):
        return _fake_int8(m, in_axes) if quant else m.astype(F32)

    def act(x, in_axes):
        return _fake_int8(x, in_axes) if quant == "w8a8" else x

    b, S = tokens.shape
    G = s.heads // s.kv_heads
    emb = weight(w["embed"], (1,))
    x = emb[tokens]
    half = s.hd // 2
    freqs = 1.0 / (s.theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        a = p["attn"]
        h = act(_norm(s, x, p["norm1"]), (2,))
        q = jnp.einsum("bsd,dhk->bshk", h, weight(a["wq"], (0,)),
                       precision=HI)
        k = jnp.einsum("bsd,dhk->bshk", h, weight(a["wk"], (0,)),
                       precision=HI)
        v = jnp.einsum("bsd,dhk->bshk", h, weight(a["wv"], (0,)),
                       precision=HI)
        if s.qkv_bias:
            q = q + a["bq"].astype(F32)
            k = k + a["bk"].astype(F32)
            v = v + a["bv"].astype(F32)
        q = _rope(q, sin, cos).reshape(b, S, s.kv_heads, G, s.hd)
        k = _rope(k, sin, cos)
        sc = jnp.einsum("bskgd,btkd->bkgst", q, k, precision=HI)
        sc = jnp.where(causal, sc / jnp.sqrt(F32(s.hd)), -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", pr, v, precision=HI)
        o = act(o.reshape(b, S, s.heads, s.hd), (2, 3))
        x = x + jnp.einsum("bshk,hkd->bsd", o, weight(a["wo"], (0, 1)),
                           precision=HI)
        ml = p["mlp"]
        h = act(_norm(s, x, p["norm2"]), (2,))
        g = jnp.einsum("bsd,df->bsf", h, weight(ml["w_gate"], (0,)),
                       precision=HI)
        u = jnp.einsum("bsd,df->bsf", h, weight(ml["w_up"], (0,)),
                       precision=HI)
        hh = act(jax.nn.silu(g) * u, (2,))
        x = x + jnp.einsum("bsf,fd->bsd", hh, weight(ml["w_down"], (0,)),
                           precision=HI)
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"][0])
    x = act(_norm(s, x[:, first:], w["final_norm"]), (2,))
    head = emb if s.tied else weight(w["unembed"], (1,))
    return jnp.einsum("bsd,vd->bsv", x, head, precision=HI)
