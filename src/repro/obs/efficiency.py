"""Roofline-efficiency telemetry — the repro analogue of the paper's
%-of-peak figures.

GAMA's headline results are efficiency numbers: 85% of the chip's int8
peak and 86% of its bf16 peak, i.e. *achieved throughput divided by the
Eq. 1/Eq. 6 analytic peak*.  This module computes the same ratio:

* ``gemm_efficiency`` — one (M, K, N) GEMM's achieved FLOP/s over the
  device peak (single / pack / array levels);
* ``serve_efficiency`` — the serving level: achieved decode tokens/s
  times the model's GEMM FLOPs per token, over the device peak.

The peak is the published one of the chip jax runs on, looked up by its
``device_kind`` in :data:`repro.core.hw.DEVICE_PEAKS`.  A device that is
not in that table — the CPU among them — has no peak, and asking for an
efficiency there raises: callers print "not measured" instead
(:func:`device_kind` returns ``None`` off an accelerator).  A CPU
timing is never divided by an accelerator's peak.

FLOP accounting is GEMM-only (the projections + lm head — the terms
Eq. 1 models); attention score/value FLOPs and normalizations are
excluded, so the serving figure is a floor.
"""

from __future__ import annotations

from typing import Optional

from repro.core import hw


def precision_for_dtype(dtype_name: str) -> hw.Precision:
    """Map a compute dtype to the paper's nearest Precision pair.

    >>> precision_for_dtype("int8").name
    'int8-int8'
    >>> precision_for_dtype("bfloat16").name
    'bf16-bf16'
    >>> precision_for_dtype("float32").name
    'bf16-bf16'
    """
    if dtype_name.startswith(("int", "uint")):
        return hw.INT8_INT8
    # bf16 is the widest native MAC precision both device models carry;
    # f32 activations rate-limit to it (documented floor).
    return hw.BF16_BF16


def device_kind() -> Optional[str]:
    """``device_kind`` of the accelerator jax runs on, or ``None`` on
    the CPU, which has no published peak to measure against."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    return jax.devices()[0].device_kind


def resolve_device_kind(kind: Optional[str]) -> str:
    """``kind``, or the device jax runs on; raises on the CPU."""
    if kind is None:
        kind = device_kind()
    if kind is None:
        raise ValueError("no device peak on the CPU: efficiency is not "
                         "measured there")
    return kind


def peak_flops(dtype_name: str = "bfloat16",
               device_kind: Optional[str] = None) -> float:
    """Published peak ops/s of ``device_kind`` (default: the device jax
    runs on) at the dtype's precision.  Unknown kinds raise.

    >>> peak_flops("bfloat16", device_kind="TPU v5 lite")
    197000000000000.0
    """
    return hw.chip_for_kind(resolve_device_kind(device_kind)).peak_ops(
        precision_for_dtype(dtype_name))


def gemm_efficiency(m: int, k: int, n: int, us_per_call: float,
                    dtype_name: str = "float32",
                    device_kind: Optional[str] = None) -> float:
    """Achieved FLOP/s of one timed GEMM over the device's peak.

    >>> peak = peak_flops("bf16", device_kind="TPU v5 lite")
    >>> us_at_peak = 2 * 64**3 / peak * 1e6
    >>> round(gemm_efficiency(64, 64, 64, us_at_peak,
    ...                       device_kind="TPU v5 lite"), 6)
    1.0
    """
    if us_per_call <= 0:
        raise ValueError(f"us_per_call must be > 0, got {us_per_call}")
    achieved = 2.0 * m * k * n / (us_per_call / 1e6)
    return achieved / peak_flops(dtype_name, device_kind)


def model_flops_per_token(cfg) -> float:
    """GEMM FLOPs one decode token costs through a ``ModelConfig``:
    the per-layer projections (fused qkv, out, ffn up/gate/down) times
    ``n_layers``, plus the lm head — the M=1 row of the shapes
    ``serving.engine.model_gemm_shapes`` enumerates, with the layer
    multiplicity made explicit."""
    qkv_n = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head
    per_layer = (cfg.d_model * qkv_n                      # fused qkv
                 + cfg.n_heads * cfg.d_head * cfg.d_model  # out proj
                 + 2 * cfg.d_model * cfg.d_ff              # ffn up + gate
                 + cfg.d_ff * cfg.d_model)                 # ffn down
    lm_head = cfg.d_model * cfg.vocab_size
    return 2.0 * (cfg.n_layers * per_layer + lm_head)


def serve_efficiency(cfg, tok_s: float,
                     device_kind: Optional[str] = None) -> float:
    """The serving level's %-of-peak: achieved decode throughput
    (tokens/s) x GEMM FLOPs per token, over the chip's published peak
    for the model's compute dtype."""
    if tok_s <= 0:
        raise ValueError(f"tok_s must be > 0, got {tok_s}")
    achieved = tok_s * model_flops_per_token(cfg)
    dtype = getattr(cfg, "compute_dtype", "bfloat16")
    return achieved / peak_flops(dtype, device_kind)
