"""Roofline terms (deliverable g).

Hardware peaks come from ``PEAKS``, one row per ``jax.Device.device_kind``
with its published source.  On a TPU, ``device_peaks()`` returns the row of
the device present and an unknown kind is an error; a CPU run names the
v5e row (``TARGET_KIND``) as its target, as does the multi-pod dry run.
Collectives are charged against ONE ICI link's bandwidth (conservative:
ring collectives stream over one logical ring unless XLA splits them).

Terms per (arch × shape × mesh), from the loop-aware HLO analysis (all
per-device quantities — SPMD modules are per-device programs):

    compute_s    = dot_flops / flops
    memory_s     = hbm_bytes / hbm_bw
    collective_s = collective_bytes / ici_link_bw

plus MODEL_FLOPS (analytic 6·N·D / 2·N·D useful compute) and the useful /
compiled compute ratio that catches remat and masked-attention waste.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from repro.models.config import ModelConfig, ShapeConfig


@dataclass(frozen=True)
class DevicePeaks:
    """Published peaks of one chip."""

    flops: float           # bf16 FLOP/s
    hbm_bw: float          # HBM bytes/s
    ici_link_bw: float     # bytes/s on each ICI link
    ici_links: int
    source: str


PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, ici_link_bw=50e9, ici_links=4,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip "
               "(4 links x 50 GB/s)"),
}
TARGET_KIND = "TPU v5 lite"


def device_peaks(device=None) -> DevicePeaks:
    """Peaks of ``device`` (default: the first jax device).  A TPU kind
    missing from ``PEAKS`` raises; other platforms get the v5e target."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return PEAKS[TARGET_KIND]
    if device.device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device.device_kind!r}; add a row to PEAKS")
    return PEAKS[device.device_kind]


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic useful FLOPs for the whole cell (all devices).

    Matmul-participating active params: active_params() minus the embedding
    gather table (tied embeddings count once — as the unembedding matmul).
    Attention score/AV FLOPs added separately (they are not param FLOPs).
    """
    N = cfg.active_params()
    emb = cfg.vocab_size * cfg.d_model
    N_mm = N - emb if not cfg.tie_embeddings else N
    N_enc = cfg.encoder_params()
    N_dec = N_mm - N_enc          # decoder-side matmul params
    B, S = shape.global_batch, shape.seq_len

    def self_attn_flops(tokens: float, ctx: float) -> float:
        if cfg.attn_free:
            return 0.0
        n_attn = sum(1 for k in cfg.layer_kinds if k == "attn")
        return tokens * n_attn * 4.0 * cfg.n_heads * cfg.hd * ctx  # QK+AV

    def cross_attn_flops(tokens: float) -> float:
        if not cfg.is_encoder_decoder:
            return 0.0
        return tokens * cfg.n_layers * 4.0 * cfg.n_heads * cfg.hd * \
            cfg.encoder_seq

    def encoder_flops() -> float:
        if not cfg.is_encoder_decoder:
            return 0.0
        toks = float(B * cfg.encoder_seq)
        return 2.0 * N_enc * toks + toks * cfg.encoder_layers * 4.0 * \
            cfg.n_heads * cfg.hd * cfg.encoder_seq

    if shape.kind == "train":
        tokens = float(B * S)
        ctx = min(S, cfg.window) if cfg.window else S / 2.0
        fwd = (2.0 * N_dec * tokens + self_attn_flops(tokens, ctx)
               + cross_attn_flops(tokens) + encoder_flops())
        return 3.0 * fwd
    if shape.kind == "prefill":
        tokens = float(B * S)
        ctx = min(S, cfg.window) if cfg.window else S / 2.0
        return (2.0 * N_dec * tokens + self_attn_flops(tokens, ctx)
                + cross_attn_flops(tokens) + encoder_flops())
    # decode: one new token per sequence against a ctx-long cache; the
    # encoder is NOT re-run (cross K/V live in the cache)
    tokens = float(B)
    ctx = min(S, cfg.window) if cfg.window else S
    cross = tokens * cfg.n_layers * 4.0 * cfg.n_heads * cfg.hd * \
        cfg.encoder_seq if cfg.is_encoder_decoder else 0.0
    return 2.0 * N_dec * tokens + self_attn_flops(tokens, ctx) + cross


def migration_transfer_s(phase_link_bytes, interconnect: str = "ici",
                         peaks: DevicePeaks = PEAKS[TARGET_KIND]) -> float:
    """Roofline lower bound for a phased state migration.

    ``phase_link_bytes``: the busiest-link bytes of each executed phase
    (``MigrationReport.phase_link_bytes``) — a phase ends when its busiest
    link drains, and phases run back-to-back, so the predicted transfer
    time is the sum of per-phase busiest-link bytes over the interconnect
    bandwidth of ``peaks``: ``ici`` for device-to-device resharding (one
    link, matching the collective accounting above) or ``hbm`` for
    same-device row copies (gather + scatter both hit HBM, hence the
    factor 2).
    """
    if interconnect == "ici":
        return float(sum(b / peaks.ici_link_bw for b in phase_link_bytes))
    if interconnect == "hbm":
        return float(sum(2.0 * b / peaks.hbm_bw for b in phase_link_bytes))
    raise ValueError(f"interconnect must be 'ici' or 'hbm', "
                     f"got {interconnect!r}")


def roofline_terms(cfg: ModelConfig, shape: ShapeConfig, costs,
                   n_devices: int,
                   peaks: DevicePeaks = PEAKS[TARGET_KIND]
                   ) -> Dict[str, float]:
    compute_s = costs.dot_flops / peaks.flops
    memory_s = costs.hbm_bytes / peaks.hbm_bw
    collective_s = costs.collective_bytes / peaks.ici_link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    per_dev_useful = mf / n_devices
    total = max(compute_s, memory_s, collective_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "model_flops_total": mf,
        "useful_compute_ratio": (per_dev_useful / costs.dot_flops
                                 if costs.dot_flops else 0.0),
        "roofline_fraction": (per_dev_useful / peaks.flops) / total
        if total > 0 else 0.0,
        "step_time_lower_bound_s": total,
    }
