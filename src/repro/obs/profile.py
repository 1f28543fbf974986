"""Step-time attribution: device/host bubble accounting + per-kernel
roofline stall classification.

The paper's optimisation loop is *attribution first*: find where cycles
go (compute vs PLIO data movement vs routing congestion), then remove
the dominant stall.  :mod:`repro.obs.efficiency` already reports
%-of-peak for whole runs; this module answers the per-step and
per-kernel "why was it slow" questions for the serving stack:

* **Bubble accounting** — each engine step's wall time is split into a
  *device estimate* (the sum of timed, ``block_until_ready``-synced
  section probes: prefill chunks, the decode dispatch) and the residual
  host/dispatch **bubble** (scheduling, Python, callbacks, transfer
  glue).  Exported as the ``step.bubble_ms`` / ``step.device_ms``
  histograms and the cumulative ``serve.bubble_fraction`` gauge.  By
  construction ``device + bubble == wall`` per step (bubble is clamped
  at zero if probes over-cover the step).

* **Stall classification** — each hot kernel (matmul, flash_decode,
  flash_paged_decode, prefill chunk scatter) is classified
  compute-bound vs memory-bound from its FLOPs and bytes (taken from
  jax's compiled ``cost_analysis()`` when available) against the
  published peaks of the chip (:data:`repro.core.hw.DEVICE_PEAKS`,
  keyed by ``device_kind``): arithmetic intensity above the ridge
  point → compute-bound, below → memory-bound.  The roofline time bound
  ``max(flops/peak, bytes/bw)`` over the measured time gives the
  achieved-vs-bound ratio (1.0 = at the roofline).  On the CPU there
  is no peak: the kernel row keeps its costs and timing, and its
  roofline fields are ``None`` (not measured).

Everything is host-side and cheap; the profiler only does arithmetic on
timings the engine already takes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Tuple

from repro.core import hw
from repro.obs.efficiency import peak_flops, resolve_device_kind
from repro.obs.metrics import Registry

COMPUTE_BOUND = "compute"
MEMORY_BOUND = "memory"

#: Hot ops whose costs the engine captures from compiled executables.
HOT_OPS = ("matmul", "flash_decode", "flash_paged_decode", "prefill_chunk")


def peak_bandwidth(device_kind: Optional[str] = None) -> float:
    """Published HBM bandwidth (bytes/s) of ``device_kind`` (default:
    the device jax runs on) — the roofline's slanted roof.  Unknown
    kinds raise.

    >>> peak_bandwidth("TPU v5 lite")
    819000000000.0
    """
    return hw.chip_for_kind(resolve_device_kind(device_kind)).hbm_bw


def ridge_intensity(dtype_name: str = "bfloat16",
                    device_kind: Optional[str] = None) -> float:
    """The roofline ridge point (FLOPs/byte) where the compute roof
    meets the bandwidth roof for this dtype and chip."""
    return peak_flops(dtype_name, device_kind) / peak_bandwidth(device_kind)


def extract_costs(compiled) -> Optional[Tuple[float, float]]:
    """Pull (flops, bytes_accessed) out of a compiled jax executable's
    ``cost_analysis()``, defensively: across jax versions the call may
    raise, return ``None``, a dict, or a list of per-computation dicts,
    and interpret-mode backends may report zeros.  Returns ``None``
    whenever no usable figures exist — callers fall back to analytic
    shapes."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, Mapping):
        return None
    flops = float(ca.get("flops") or 0.0)
    nbytes = float(ca.get("bytes accessed") or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return flops, nbytes


@dataclasses.dataclass(frozen=True)
class KernelProfile:
    """One hot op's roofline placement."""

    name: str
    flops: float
    bytes: float
    measured_us: float
    intensity: float        # flops / bytes
    # Roofline placement against the chip's published peaks; all None
    # where the device has none (the CPU): not measured.
    ridge: Optional[float]          # ridge point for its dtype + chip
    stall_class: Optional[str]      # COMPUTE_BOUND | MEMORY_BOUND
    bound_us: Optional[float]       # roofline lower bound on time
    bound_ratio: Optional[float]    # bound_us / measured_us (1.0 = roof)

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


def classify_kernel(name: str, flops: float, nbytes: float,
                    measured_us: float,
                    dtype_name: str = "bfloat16",
                    device_kind: Optional[str] = None) -> KernelProfile:
    """Place one timed kernel on the roofline of ``device_kind``'s
    published peaks (unknown kinds raise).  With no ``device_kind`` (the
    CPU has no published peak) the row is recorded unplaced: its
    roofline fields are None.

    >>> p = classify_kernel("gemm", flops=2 * 4096**3,
    ...                     nbytes=3 * 4096 * 4096 * 2, measured_us=1e3,
    ...                     device_kind="TPU v5 lite")
    >>> p.stall_class
    'compute'
    >>> p = classify_kernel("scatter", flops=1e3, nbytes=1e9,
    ...                     measured_us=100.0, device_kind="TPU v5 lite")
    >>> p.stall_class
    'memory'
    """
    if flops < 0 or nbytes < 0:
        raise ValueError(f"kernel {name}: negative flops/bytes")
    if measured_us <= 0:
        raise ValueError(f"kernel {name}: measured_us must be > 0")
    intensity = flops / nbytes if nbytes > 0 else float("inf")
    if device_kind is None:
        return KernelProfile(
            name=name, flops=flops, bytes=nbytes, measured_us=measured_us,
            intensity=intensity, ridge=None, stall_class=None,
            bound_us=None, bound_ratio=None)
    peak = peak_flops(dtype_name, device_kind)
    bw = peak_bandwidth(device_kind)
    ridge = peak / bw
    stall = COMPUTE_BOUND if intensity >= ridge else MEMORY_BOUND
    bound_s = max(flops / peak, nbytes / bw)
    bound_us = bound_s * 1e6
    return KernelProfile(
        name=name, flops=flops, bytes=nbytes, measured_us=measured_us,
        intensity=intensity, ridge=ridge, stall_class=stall,
        bound_us=bound_us,
        bound_ratio=min(1.0, bound_us / measured_us),
    )


class StepProfiler:
    """Per-step wall-time decomposition + kernel roofline table.

    The engine calls :meth:`record_step` once per ``step()`` with the
    step's wall time and its device-synced section probes; it calls
    :meth:`record_kernel` once per hot op once costs and a steady-state
    timing are known (re-recording a kernel overwrites its row —
    last-wins, so the table reflects warm timings).  ``device_kind``
    names the chip whose published peaks place kernels on the roofline;
    ``None`` (the CPU) records rows with the roofline not measured.

    >>> prof = StepProfiler(Registry(), device_kind="TPU v5 lite")
    >>> rec = prof.record_step(10.0, {"decode": 6.0, "prefill": 2.0})
    >>> rec["bubble_ms"]
    2.0
    >>> round(prof.bubble_fraction(), 2)
    0.2
    """

    def __init__(self, registry: Registry,
                 device_kind: Optional[str] = None,
                 dtype_name: str = "bfloat16"):
        if device_kind is not None:
            hw.chip_for_kind(device_kind)   # an unknown chip fails here
        self.registry = registry
        self.device_kind = device_kind
        self.dtype_name = dtype_name
        self._wall_ms_total = 0.0
        self._bubble_ms_total = 0.0
        self._kernels: Dict[str, KernelProfile] = {}
        self._h_bubble = registry.histogram(
            "step.bubble_ms", "host/dispatch bubble per engine step")
        self._h_device = registry.histogram(
            "step.device_ms", "device-attributed time per engine step")
        self._g_fraction = registry.gauge(
            "serve.bubble_fraction",
            "cumulative bubble / wall over the run")

    # -- per-step decomposition --------------------------------------------

    def record_step(self, wall_ms: float,
                    sections: Mapping[str, float]) -> Dict[str, float]:
        """Attribute one step: ``sections`` maps probe name → ms of
        device-synced work; the residual is the bubble.  Returns the
        decomposition record (also what the flight recorder stores)."""
        wall_ms = float(wall_ms)
        device_ms = sum(max(0.0, float(v)) for v in sections.values())
        # Probes can marginally over-cover wall (clock granularity);
        # clamp so the decomposition identity device + bubble == wall
        # holds exactly.
        device_ms = min(device_ms, wall_ms)
        bubble_ms = wall_ms - device_ms
        self._h_bubble.observe(bubble_ms)
        self._h_device.observe(device_ms)
        self._wall_ms_total += wall_ms
        self._bubble_ms_total += bubble_ms
        self._g_fraction.set(self.bubble_fraction())
        return {"wall_ms": wall_ms, "device_ms": device_ms,
                "bubble_ms": bubble_ms,
                "bubble_fraction": (bubble_ms / wall_ms) if wall_ms else 0.0}

    def bubble_fraction(self) -> float:
        """Cumulative bubble share of wall time (0 when nothing ran)."""
        if self._wall_ms_total <= 0:
            return 0.0
        return self._bubble_ms_total / self._wall_ms_total

    @property
    def wall_ms_total(self) -> float:
        return self._wall_ms_total

    @property
    def bubble_ms_total(self) -> float:
        return self._bubble_ms_total

    def reset_totals(self) -> None:
        """Zero the cumulative decomposition (the warmup seam, next to
        ``Registry.reset_values``).  The kernel table survives — warm
        steady-state timings are exactly what it should hold."""
        self._wall_ms_total = 0.0
        self._bubble_ms_total = 0.0

    # -- per-kernel roofline ------------------------------------------------

    def record_kernel(self, name: str, flops: float, nbytes: float,
                      measured_us: float,
                      dtype_name: Optional[str] = None) -> KernelProfile:
        prof = classify_kernel(
            name, flops, nbytes, measured_us,
            dtype_name=dtype_name or self.dtype_name,
            device_kind=self.device_kind)
        self._kernels[name] = prof
        if prof.stall_class is None:
            return prof
        self.registry.gauge(
            f"profile.{name}.bound_ratio",
            "roofline bound / measured time").set(prof.bound_ratio)
        self.registry.gauge(
            f"profile.{name}.memory_bound",
            "1 if memory-bound, 0 if compute-bound").set(
                1.0 if prof.stall_class == MEMORY_BOUND else 0.0)
        return prof

    def kernel_table(self) -> List[KernelProfile]:
        """Stall table, worst (lowest bound_ratio) first; rows with the
        roofline not measured keep their recording order."""
        return sorted(self._kernels.values(),
                      key=lambda p: (p.bound_ratio is not None,
                                     p.bound_ratio or 0.0))

    def summary(self) -> Dict[str, object]:
        return {
            "wall_ms_total": self._wall_ms_total,
            "bubble_ms_total": self._bubble_ms_total,
            "bubble_fraction": self.bubble_fraction(),
            "kernels": [p.as_dict() for p in self.kernel_table()],
        }
