"""Model-FLOPs and peak-FLOPs accounting — the single source of truth.

The Megatron fwd+bwd formula and the per-chip bf16 peaks: the engine's
in-band MFU (``core/engine.py::_print_summary``) and the tuning scripts
read them here. The benchmark keeps a count of its own
(``chipbench/flops*.py``), independent of the program on purpose.
"""

from __future__ import annotations

from typing import Optional

# bf16 dense peak by device kind (jax Device.device_kind) — platform
# alone can't distinguish TPU generations and would silently mis-scale
# MFU on anything but the calibrated chip.
PEAK_FLOPS_BY_KIND = {
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}

# HBM bandwidth per chip in bytes/s, same keys. Source: Google Cloud
# TPU documentation, per-generation system-architecture pages ("TPU
# v5e": 16 GB HBM2e at 819 GB/s; v3 900, v4 1200 (1228 in the v4
# paper), v5p 2765, v6e 1640 GB/s). Roofline shares need both peaks.
HBM_BYTES_PER_SEC_BY_KIND = {
    "TPU v3": 900e9,
    "TPU v4": 1200e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
}


def model_flops_per_token(num_layers: int, hidden_size: int,
                          vocab_size: int, seq: int) -> float:
    """Megatron fwd+bwd model FLOPs per token for a GPT geometry:
    ``72*L*h^2*(1 + s/6h + V/12Lh)`` (assumes ffn = 4h; counts the
    model's own fwd+bwd only — remat recompute burns hardware FLOPs
    but does not count as model FLOPs)."""
    L, h, V = num_layers, hidden_size, vocab_size
    return 72.0 * L * h * h * (1 + seq / (6.0 * h) + V / (12.0 * L * h))


def causal_attn_flops(b: int, h: int, s: int, d: int) -> float:
    """Model FLOPs of one causal-attention forward at [b, h, s, d]:
    QK^T + PV matmuls (2 each per element), half the square live.
    Shared by the tuning/profiling scripts so the roofline accounting
    cannot drift between them."""
    return 4.0 * b * h * s * s * d * 0.5


def _tpu_peak(table, device, what):
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in table:
        raise ValueError(
            f"no {what} on record for TPU device_kind "
            f"{device.device_kind!r}: add it to observability/flops.py "
            f"with its source (a guessed peak would mis-scale every "
            f"utilization reported against it)")
    return table[device.device_kind]


def peak_flops(device=None) -> Optional[float]:
    """Per-chip bf16 peak for ``device`` (default: the first attached
    device); None off-TPU, where MFU is reported as n/a. A TPU whose
    device_kind is not in the table is an error, not a default."""
    return _tpu_peak(PEAK_FLOPS_BY_KIND, device, "bf16 peak FLOP/s")


def peak_hbm_bytes_per_sec(device=None) -> Optional[float]:
    """Per-chip HBM bandwidth, same contract as :func:`peak_flops`."""
    return _tpu_peak(HBM_BYTES_PER_SEC_BY_KIND, device,
                     "HBM bandwidth")


def mfu(tokens_per_sec: float, flops_per_token: float,
        peak_per_chip: Optional[float],
        n_chips: int = 1) -> Optional[float]:
    """Achieved model FLOPs over the aggregate peak, or None when the
    peak is unknown (non-TPU platforms)."""
    if not peak_per_chip or not tokens_per_sec:
        return None
    return tokens_per_sec * flops_per_token / (peak_per_chip * n_chips)
