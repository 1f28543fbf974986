"""Hardware models for the GAMA reproduction.

Two targets live side by side:

* :class:`AIE2Device` — the paper's AMD Versal VE2802 (AIE-ML) device.  Used
  by the *faithful* reproduction path (tile search, buffer placement, pack
  and array models) that validates against the paper's Tables II-VI.
* :class:`TpuChip` — the deployment target for the JAX/Pallas framework,
  with the published peaks of each supported chip in
  :data:`DEVICE_PEAKS`, keyed by jax's ``device_kind``.

Both expose the quantities the shared analytical model in
:mod:`repro.core.gemm_model` needs: peak MAC throughput per precision, the
local-memory capacity that bounds tile sizes, and the io bandwidth that
bounds the compute-to-communication ratio gamma.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

# ---------------------------------------------------------------------------
# Precision descriptors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Precision:
    """An (input precision, output precision) pair, as in the paper."""

    name: str
    in_bytes: int
    out_bytes: int
    # Accumulator width used *inside* the engine (cascade payload on AIE2,
    # VMEM scratch dtype on TPU).
    acc_bytes: int

    @property
    def key(self) -> str:
        return self.name


# The four precisions evaluated by GAMA (Table II).
INT8_INT32 = Precision("int8-int32", in_bytes=1, out_bytes=4, acc_bytes=4)
INT8_INT16 = Precision("int8-int16", in_bytes=1, out_bytes=2, acc_bytes=4)
INT8_INT8 = Precision("int8-int8", in_bytes=1, out_bytes=1, acc_bytes=4)
BF16_BF16 = Precision("bf16-bf16", in_bytes=2, out_bytes=2, acc_bytes=4)
# Not one of the paper's precisions: f32 operands on the TPU, so the tile
# planner counts their real VMEM bytes (the MXU still runs them at the
# bf16 peak).
F32_F32 = Precision("f32-f32", in_bytes=4, out_bytes=4, acc_bytes=4)

PRECISIONS: Dict[str, Precision] = {
    p.name: p for p in (INT8_INT32, INT8_INT16, INT8_INT8, BF16_BF16)
}


# ---------------------------------------------------------------------------
# AMD Versal AIE-ML (AIE2) — the paper's device
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AIE2Device:
    """VE2802 on the VEK280 board, as used in the paper (Section V-A)."""

    name: str = "VE2802"
    rows: int = 8
    cols: int = 38
    # Local data memory per engine: 64 KB in 4 banks of 16 KB.
    mem_bytes: int = 65536
    mem_banks: int = 4
    # PL <-> AIE interface.
    plio_in: int = 112
    plio_out: int = 84
    plio_bits: int = 128
    # Clocks: AIEs run at 1.25 GHz, the PL at 300 MHz.  The paper's Eq. 2-4
    # count PLIO transfer cycles in *AIE* cycles, so every PL-side beat costs
    # freq_ratio AIE cycles.  (This ratio is implicit in the paper; Table II's
    # gamma values only reproduce once it is applied — see DESIGN.md §1.1.)
    aie_hz: float = 1.25e9
    pl_hz: float = 300e6
    # Cascade stream between neighbouring engines.
    cascade_bits: int = 512
    # Peak MAC throughput per engine per cycle (AM020): 256 int8, 128 bf16.
    macs_int8: int = 256
    macs_bf16: int = 128

    @property
    def n_engines(self) -> int:
        return self.rows * self.cols

    @property
    def freq_ratio(self) -> float:
        return self.aie_hz / self.pl_hz

    @property
    def plio_bytes_per_pl_cycle(self) -> float:
        return self.plio_bits / 8

    @property
    def bank_bytes(self) -> int:
        return self.mem_bytes // self.mem_banks

    def macs_per_cycle(self, precision: Precision) -> int:
        """Peak multiply-accumulates per cycle for a precision (per engine)."""
        if precision.in_bytes == 1:
            return self.macs_int8
        return self.macs_bf16

    def peak_ops(self, precision: Precision, engines: int | None = None) -> float:
        """Peak ops/s (1 MAC = 2 ops) for `engines` engines (default: chip)."""
        n = self.n_engines if engines is None else engines
        return n * self.macs_per_cycle(precision) * 2 * self.aie_hz


VE2802 = AIE2Device()


# ---------------------------------------------------------------------------
# TPU chips — the deployment target, keyed by jax's ``device_kind``
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TpuChip:
    """One TPU chip's published peaks plus the Pallas tiling constants
    the planner and kernels share."""

    name: str = "tpu-v5e"
    # Peak compute.
    peak_bf16_flops: float = 197e12
    peak_int8_ops: float = 393e12
    # Memory system.
    hbm_bytes: int = 16 * 10**9
    hbm_bw: float = 819e9
    # VMEM: v5e has 128 MiB per core.  Kernels compile with a scoped
    # limit of ``vmem_limit`` (the compiler's own default is 16 MiB),
    # and the planner only admits tiles whose modelled working set fits
    # ``vmem_budget`` — the gap covers what the model leaves out (the
    # pipeline's second output buffer, the dot result before it is
    # accumulated), so every admitted plan also compiles.  The budget is
    # the analogue of the AIE's 64 KB local memory in the tile search.
    vmem_bytes: int = 128 * 2**20
    vmem_limit: int = 64 * 2**20
    vmem_budget: int = 48 * 2**20
    # Chip-to-chip interconnect per link: 1,600 Gbit/s per chip over
    # four links.
    ici_bw: float = 1600e9 / 8 / 4
    # MXU geometry: 128x128 systolic, (sublane, lane) native tile (8, 128).
    mxu_dim: int = 128
    sublanes: int = 8
    lanes: int = 128

    def peak_ops(self, precision: Precision) -> float:
        if precision.in_bytes == 1:
            return self.peak_int8_ops
        return self.peak_bf16_flops

    def min_tile(self, dtype_bytes: int) -> Tuple[int, int]:
        """Native (second-minor, minor) tile for a dtype, per TPU tiling rules.

        fp32: (8, 128); bf16: (16, 128); int8/fp8: (32, 128).
        """
        packing = max(1, 4 // dtype_bytes)
        return (self.sublanes * packing, self.lanes)


# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
# ICI per chip.
TPU_V5E = TpuChip()

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: A device that is not here has no peak: asking for one is an error.
DEVICE_PEAKS: Dict[str, TpuChip] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_kind(device_kind: str) -> TpuChip:
    """The published peaks of the chip jax reports as ``device_kind``.

    >>> chip_for_kind("TPU v5 lite").peak_bf16_flops
    197000000000000.0
    >>> chip_for_kind("cpu")  # doctest: +ELLIPSIS
    Traceback (most recent call last):
    ...
    ValueError: no published peaks for device kind 'cpu' ...
    """
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)})") from None


# ---------------------------------------------------------------------------
# Pod / mesh level constants (roofline uses these)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PodSpec:
    """A pod of TPU chips joined by ICI; pods join over DCN."""

    chip: TpuChip = TPU_V5E
    chips_per_pod: int = 256
    # 2D torus per pod for v5e-class parts.
    torus: Tuple[int, int] = (16, 16)
    dcn_bw: float = 25e9  # per-host cross-pod bandwidth (model constant)


POD_V5E_256 = PodSpec()
