"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the breakdown read.

The window is the host span ``bench.window``, which the replay opens
and closes around the measured window.  On each device plane the
operations of the ``XLA Ops`` line, clipped to the window, give:

* busy time: the union of their intervals, averaged over the devices;
* device time per operation (its trace name is its HLO text), and per
  kernel (every operation whose text, or a string it carries, contains
  the kernel's name);
* idle gaps: the stretches of the window in which no operation ran,
  each named by the innermost ``bench.*`` host span open at its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
#: HLO operations whose trace events span the operations inside them.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]                    # summed over devices
    op_text: Dict[str, str]                   # name -> searchable text
    gaps: List[Tuple[str, float]]             # longest first
    devices: int

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the operations that run ``kernel``,
        averaged over the devices."""
        total = sum(s for name, s in self.op_s.items()
                    if kernel in name or kernel in self.op_text.get(name, ""))
        return total / max(1, self.devices)

    def breakdown(self) -> dict:
        """The ten operations that took most device time, by their HLO
        name (``%gama_gemm.70``), leaving out the loops and calls that
        only contain others; and the ten longest idle gaps."""
        ops = {}
        for text, s in self.op_s.items():
            name = text.split(" = ", 1)[0]
            if not name.lstrip("%").startswith(CONTAINERS):
                ops[name] = ops.get(name, 0.0) + s
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s / max(1, self.devices)]
                               for n, s in top],
                "idle_gaps": [[n, s] for n, s in self.gaps[:10]]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _stat_text(event) -> str:
    try:
        return " ".join(str(v) for _, v in event.stats if isinstance(v, str))
    except Exception:           # stats the binding cannot convert
        return ""


def reduce_file(path: Path, window: Optional[Tuple[float, float]] = None
                ) -> Reduced:
    """``window`` (start, end in the trace's ns) in place of the
    ``bench.window`` span, for a trace that has none."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    host_spans: List[Tuple[float, float, str]] = []
    for plane in planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    host_spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name))
    windows = [(a, b) for a, b, n in host_spans if n == WINDOW]
    if window is None and not windows:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = window or windows[0]
    inner = sorted(s for s in host_spans if s[2] != WINDOW)

    op_s: Dict[str, float] = {}
    op_text: Dict[str, str] = {}
    busy, gaps, n_dev = 0.0, [], 0
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not ops:
            continue
        n_dev += 1
        ivs = []
        for e in ops[0].events:
            a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            op_s[e.name] = op_s.get(e.name, 0.0) + (b - a) * 1e-9
            if e.name not in op_text:
                op_text[e.name] = _stat_text(e)
        merged = _union(ivs)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    if not n_dev:
        raise ValueError(f"no device plane with XLA Ops in {path}")
    gaps.sort(key=lambda g: g[0] - g[1])
    starts = [s[0] for s in inner]
    named = [(_label(inner, starts, (a + b) / 2), (b - a) * 1e-9)
             for a, b in gaps[:10]]
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy / n_dev,
                   op_s=op_s, op_text=op_text, gaps=named, devices=n_dev)


def _label(spans, starts, t: float) -> str:
    """The ``bench.*`` span open at ``t``: the replay's spans inside the
    window follow one another on one thread, so the last one to open
    before ``t`` is the only candidate."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return WINDOW


def reduce_dir(trace_dir: Path) -> Reduced:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1])
