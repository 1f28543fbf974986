"""Where the device's time goes, read from a ``jax.profiler`` trace.

The engine's spans (:mod:`repro.obs.trace`) land on the host plane of a
profiler session, on the same clock as the device's work, and each
engine program runs as the XLA module ``jit_engine_<name>``.  This
module reduces one ``.xplane.pb`` to:

* ``program_s``: device seconds per program (the ``XLA Modules`` line:
  one event per execution, named ``jit_<function>(<fingerprint>)``);
* ``idle_s``: device-idle seconds under each *innermost* host span,
  apportioned by sweeping the span intervals across every idle gap (a
  gap that starts under ``engine.decode.prepare`` and ends under
  ``engine.decode.dispatch`` is split between the two);
* ``gaps``: the longest idle gaps, each named by the innermost span
  open at its middle and by the program the device resumed with;
* ``program_gaps``: device-idle time between consecutive executions of
  one program (``jit_engine_decode``: what a decode step waits on).

Busy time is the union of the ``XLA Ops`` intervals.  Host spans are
those whose names start with ``engine.`` or ``bench.`` (the benchmark's
replay spans); idle time outside all of them is charged to ``OUTSIDE``.
The window is a host span (``--window bench.window``) or the device's
first to last event.  Multi-device traces average over the devices.

    python -m repro.obs.devtrace <trace dir | .xplane.pb> \\
        [--window bench.window] [--json out.json]
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIXES = ("engine.", "bench.")
OUTSIDE = "(no span)"
DECODE = "jit_engine_decode"

Interval = Tuple[float, float]
Labelled = Tuple[float, float, str]


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _complement(busy: Sequence[Interval], w0: float, w1: float
                ) -> List[Interval]:
    """The stretches of [w0, w1) that ``busy`` (sorted, disjoint) leaves
    uncovered."""
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def innermost(spans: Sequence[Labelled], w0: float, w1: float
              ) -> List[Labelled]:
    """Disjoint ``(start, end, name)`` stretches covering [w0, w1), each
    named by the innermost span open over it (``OUTSIDE`` where none
    is).  Spans of one thread nest LIFO, so an open-span stack sweeps
    them in start order.

    >>> innermost([(0, 10, "step"), (2, 4, "decode")], 0, 12)
    [(0, 2, 'step'), (2, 4, 'decode'), (4, 10, 'step'), (10, 12, '(no span)')]
    """
    out: List[Labelled] = []
    stack: List[Tuple[float, str]] = []     # open spans: (end, name)
    t = w0

    def close_to(upto: float) -> None:
        nonlocal t
        upto = min(upto, w1)
        if upto > t:
            out.append((t, upto, stack[-1][1] if stack else OUTSIDE))
            t = upto

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        if b <= w0 or a >= w1:
            continue
        while stack and stack[-1][0] <= a:
            close_to(stack[-1][0])
            stack.pop()
        close_to(a)
        stack.append((b, name))
    while stack:
        close_to(stack[-1][0])
        stack.pop()
    close_to(w1)
    return out


def apportion(idle: Sequence[Interval], segments: Sequence[Labelled]
              ) -> Dict[str, float]:
    """Length of ``idle`` (sorted, disjoint) under each segment's name;
    ``segments`` as :func:`innermost` returns them.

    >>> apportion([(3, 6)], [(0, 4, "prepare"), (4, 9, "dispatch")])
    {'prepare': 1, 'dispatch': 2}
    """
    out: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s0, s1, name = segments[k]
            lo, hi = max(a, s0), min(b, s1)
            if hi > lo:
                out[name] = out.get(name, 0) + (hi - lo)
            k += 1
    return out


def program_name(module: str) -> str:
    """``jit_engine_decode(1234)`` -> ``jit_engine_decode``."""
    return module.split("(", 1)[0]


@dataclasses.dataclass
class DeviceTrace:
    """One trace reduced to intervals, in the trace's nanoseconds."""

    window: Interval
    spans: List[Labelled]                   # host spans, by prefix
    busy: List[List[Interval]]              # per device, union of ops
    runs: List[List[Labelled]]              # per device, executions

    @property
    def devices(self) -> int:
        return len(self.busy)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        w0, w1 = self.window
        total = sum(max(0.0, min(b, w1) - max(a, w0))
                    for dev in self.busy for a, b in dev)
        return total * 1e-9 / max(1, self.devices)

    def segments(self) -> List[Labelled]:
        return innermost(self.spans, *self.window)

    def _idle(self, dev: int) -> List[Interval]:
        return _complement(self.busy[dev], *self.window)

    def program_s(self) -> Dict[str, float]:
        """Device seconds per program inside the window, most first."""
        w0, w1 = self.window
        out: Dict[str, float] = {}
        for dev in self.runs:
            for a, b, name in dev:
                d = min(b, w1) - max(a, w0)
                if d > 0:
                    out[name] = out.get(name, 0.0) + d * 1e-9
        n = max(1, self.devices)
        return dict(sorted(((k, v / n) for k, v in out.items()),
                           key=lambda kv: -kv[1]))

    def idle_s(self) -> Dict[str, float]:
        """Device-idle seconds under each innermost span, most first."""
        segs = self.segments()
        out: Dict[str, float] = {}
        for dev in range(self.devices):
            for name, ns in apportion(self._idle(dev), segs).items():
                out[name] = out.get(name, 0.0) + ns * 1e-9
        n = max(1, self.devices)
        return dict(sorted(((k, v / n) for k, v in out.items()),
                           key=lambda kv: -kv[1]))

    def gaps(self, n: int = 10) -> List[Tuple[str, float, float, str]]:
        """The ``n`` longest idle gaps of device 0 as (innermost span at
        the gap's middle, seconds, start in seconds after the window
        opened, the program whose execution ended the gap or "")."""
        if not self.devices:
            return []
        segs = self.segments()
        starts = [s[0] for s in segs]
        runs = sorted(self.runs[0])
        run_starts = [r[0] for r in runs]
        w0 = self.window[0]
        out = []
        for a, b in sorted(self._idle(0), key=lambda g: g[0] - g[1])[:n]:
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            name = segs[i][2] if i >= 0 else OUTSIDE
            j = bisect.bisect_left(run_starts, b)
            # The first execution to start at the gap's end (or the one
            # its first operation belongs to, a few ns earlier).
            if j > 0 and runs[j - 1][1] > b:
                j -= 1
            nxt = runs[j][2] if j < len(runs) else ""
            out.append((name, (b - a) * 1e-9, (a - w0) * 1e-9, nxt))
        return out

    def program_gaps(self, program: str = DECODE) -> List[float]:
        """Device-idle seconds between the end of each execution of
        ``program`` and the start of the next, on device 0, for the
        consecutive pairs inside the window."""
        if not self.devices:
            return []
        w0, w1 = self.window
        runs = sorted((a, b) for a, b, name in self.runs[0]
                      if name == program and a >= w0 and b <= w1)
        busy = self.busy[0]
        starts = [a for a, _ in busy]
        out = []
        for (_, end), (nxt, _) in zip(runs, runs[1:]):
            if nxt <= end:
                out.append(0.0)
                continue
            covered = 0.0
            i = max(0, bisect.bisect_right(starts, end) - 1)
            while i < len(busy) and busy[i][0] < nxt:
                covered += max(0.0, min(busy[i][1], nxt)
                               - max(busy[i][0], end))
                i += 1
            out.append((nxt - end - covered) * 1e-9)
        return out

    def summary(self, n_gaps: int = 10) -> dict:
        idle = self.idle_s()
        dgaps = self.program_gaps()
        w = self.window_s
        return {
            "window_s": w, "devices": self.devices, "busy_s": self.busy_s(),
            "program_s": self.program_s(),
            "idle_s": idle,
            "engine_idle_pct": (100.0 * sum(
                v for k, v in idle.items() if k.startswith("engine."))
                / w if w else None),
            "decode_gap_ms_p50": (statistics.median(dgaps) * 1e3
                                  if dgaps else None),
            "decode_pairs": len(dgaps),
            "gaps": [list(g) for g in self.gaps(n_gaps)],
        }


def load(path: Path, window: Optional[str] = None,
         prefixes: Sequence[str] = SPAN_PREFIXES) -> DeviceTrace:
    """Reduce one ``.xplane.pb``; ``window`` names the host span that
    bounds the analysis (its first occurrence), else the device's first
    to last event bound it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: List[Labelled] = []
    busy: List[List[Interval]] = []
    runs: List[List[Labelled]] = []
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tuple(prefixes)) or \
                            e.name == window:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
        elif plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            busy.append(_union([(e.start_ns, e.start_ns + e.duration_ns)
                                for e in lines["XLA Ops"].events]))
            runs.append([(e.start_ns, e.start_ns + e.duration_ns,
                          program_name(e.name))
                         for e in (lines["XLA Modules"].events
                                   if "XLA Modules" in lines else ())])
    if window is not None:
        bounds = [(a, b) for a, b, n in spans if n == window]
        if not bounds:
            raise ValueError(f"no {window!r} span in {path}")
        w = bounds[0]
    else:
        edges = [x for dev in busy for iv in dev for x in iv] + \
            [x for dev in runs for a, b, _ in dev for x in (a, b)]
        if not edges:
            raise ValueError(f"no device operations in {path}")
        w = (min(edges), max(edges))
    return DeviceTrace(window=w, spans=spans, busy=busy, runs=runs)


def find(path: Path) -> Path:
    """``path`` itself, or the newest ``.xplane.pb`` under a trace
    directory."""
    path = Path(path)
    if path.is_file():
        return path
    files = sorted(path.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise ValueError(f"no .xplane.pb under {path}")
    return files[-1]


def report(s: dict) -> str:
    """The summary as markdown tables."""
    w = s["window_s"]
    lines = [f"window {w:.3f} s, {s['devices']} device(s), busy "
             f"{s['busy_s']:.3f} s ({100 * s['busy_s'] / w:.2f}%)", "",
             "| program | device s | share |", "|---|---|---|"]
    lines += [f"| `{k}` | {v:.4f} | {100 * v / w:.2f}% |"
              for k, v in s["program_s"].items()]
    lines += ["", "| idle under | s | share |", "|---|---|---|"]
    lines += [f"| `{k}` | {v:.4f} | {100 * v / w:.2f}% |"
              for k, v in s["idle_s"].items()]
    lines += ["", "| longest idle gaps: span | s | at s | next program |",
              "|---|---|---|---|"]
    lines += [f"| `{n}` | {d:.4f} | {t:.3f} | `{p}` |"
              for n, d, t, p in s["gaps"]]
    if s["decode_gap_ms_p50"] is not None:
        lines += ["", f"decode gap p50 {s['decode_gap_ms_p50']:.3f} ms "
                      f"over {s['decode_pairs']} pairs"]
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="trace directory or .xplane.pb file")
    ap.add_argument("--window", default=None,
                    help="host span bounding the analysis "
                         "(e.g. bench.window)")
    ap.add_argument("--gaps", type=int, default=10)
    ap.add_argument("--json", default=None, help="also write the summary")
    args = ap.parse_args(argv)
    s = load(find(Path(args.trace)), args.window).summary(args.gaps)
    if args.json:
        Path(args.json).write_text(json.dumps(s, indent=1))
    print(report(s), end="")


if __name__ == "__main__":
    main()
