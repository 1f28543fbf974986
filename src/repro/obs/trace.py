"""Span tracer: engine phases on the profiler's clock, and an opt-in
Chrome-trace-event (Perfetto-loadable) exporter.

Sync spans (:meth:`Tracer.span`) are nested host-side phases inside one
engine step (``engine.step`` > ``engine.admit`` > ...).  Each one also
enters a ``jax.profiler.TraceAnnotation`` while a profiler session
records host events, so the phase lands on the host plane of the
session's ``.xplane.pb`` on the same clock as the device's ``XLA Ops``;
``step_num=`` makes it a ``StepTraceAnnotation``.  With no session and
the Chrome sink off, a span costs one check and returns one shared
null context manager: no event object, no clock read.

The Chrome sink (``Tracer(enabled=True)``, ``--trace-out``) records, on
the host clock only, three event families of the Trace Event Format
that ``chrome://tracing`` and https://ui.perfetto.dev consume:

* **sync spans** (``ph: "X"`` complete events), LIFO-nested by
  construction (a span is a context manager);
* **async spans** (``ph: "b"``/``"e"`` pairs keyed by ``(cat, id)``) —
  per-request lifecycle phases (``queued`` → ``decode``) that overlap
  arbitrarily across requests and engine steps; Chrome-only;
* **instants, counters and flows** (``ph: "i"`` / ``"C"`` / ``"s"``…);
  Chrome-only.

>>> tr = Tracer()
>>> with tr.span("step", step=0):
...     with tr.span("decode"):
...         pass
>>> tr.async_begin("request", 7, phase="queued")
>>> tr.async_end("request", 7)
>>> evs = tr.chrome_trace()["traceEvents"]
>>> sorted({e["ph"] for e in evs})
['X', 'b', 'e']
>>> Tracer(enabled=False).span("step") is _NULL_SPAN   # no session open
True
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """Reusable no-op context manager (the disabled-tracer span)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL_SPAN = _NullSpan()


def _profiling() -> bool:
    """True while a profiler session records host events.  The first
    call imports jax and rebinds this name to jax's own check, so later
    calls cost one C call."""
    global _profiling
    from jax.profiler import TraceAnnotation
    _profiling = TraceAnnotation.is_enabled
    return _profiling()


def _annotation(name: str, step_num: Optional[int],
                args: Dict[str, Any]):
    """The profiler's host event for one span."""
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    if step_num is None:
        return TraceAnnotation(name, **args)
    return StepTraceAnnotation(name, step_num=step_num, **args)


class _Span:
    """One open sync span; records an ``X`` event when it closes (and
    a profiler host event, while a session records)."""

    __slots__ = ("tracer", "name", "cat", "args", "step_num", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Dict[str, Any], step_num: Optional[int]):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args
        self.step_num = step_num
        self.t0 = 0.0
        self.ann = None

    def __enter__(self) -> "_Span":
        if _profiling():
            self.ann = _annotation(self.name, self.step_num, self.args)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        self.tracer._stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        now = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        top = self.tracer._stack.pop()
        assert top is self, (
            f"span {self.name!r} closed while {top.name!r} is open — "
            f"sync spans must nest LIFO")
        ev: Dict[str, Any] = {
            "name": self.name, "ph": "X", "pid": 0, "tid": 0,
            "ts": self.tracer._us(self.t0),
            "dur": max(0.0, (now - self.t0) * 1e6),
        }
        if self.cat:
            ev["cat"] = self.cat
        args = self.args
        if self.step_num is not None:
            args = {**args, "step_num": self.step_num}
        if args:
            ev["args"] = args
        self.tracer._events.append(ev)


class Tracer:
    """Event recorder; export with :meth:`chrome_trace` / :meth:`write`."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._epoch = time.perf_counter()
        self._events: List[Dict[str, Any]] = []
        self._stack: List[_Span] = []
        self._open_async: Dict[tuple, int] = {}   # (cat, id) -> open count

    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def _now_us(self) -> float:
        return self._us(time.perf_counter())

    # -- recording ----------------------------------------------------------

    def span(self, name: str, cat: str = "", *,
             step_num: Optional[int] = None, **args):
        """Context manager around one nested host-side phase: a Chrome
        ``X`` event when this tracer is enabled, a profiler host event
        (a step marker with ``step_num``) while a session records, and
        nothing otherwise.  ``args`` ride along on both; keep them small
        ints."""
        if self.enabled:
            return _Span(self, name, cat, args, step_num)
        if _profiling():
            return _annotation(name, step_num, args)
        return _NULL_SPAN

    def instant(self, name: str, cat: str = "", **args) -> None:
        if not self.enabled:
            return
        ev: Dict[str, Any] = {"name": name, "ph": "i", "s": "p",
                              "pid": 0, "tid": 0, "ts": self._now_us()}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self._events.append(ev)

    def counter(self, name: str, **values: float) -> None:
        """A ``C`` time-series sample (one track per value key)."""
        if not self.enabled:
            return
        self._events.append({"name": name, "ph": "C", "pid": 0,
                             "ts": self._now_us(),
                             "args": {k: float(v)
                                      for k, v in values.items()}})

    def metadata(self, name: str, /, pid: int = 0, tid: int = 0,
                 **args: Any) -> None:
        """A ``M`` metadata event (``process_name`` / ``thread_name`` …)
        naming a pid/tid lane in the Perfetto UI.  Emitted with ``ts`` 0
        so it sorts ahead of the events it labels."""
        if not self.enabled:
            return
        self._events.append({"name": name, "ph": "M", "pid": pid,
                             "tid": tid, "ts": 0.0, "args": args})

    def process_name(self, label: str, pid: int = 0) -> None:
        self.metadata("process_name", pid=pid, name=label)

    def thread_name(self, label: str, tid: int = 0, pid: int = 0) -> None:
        self.metadata("thread_name", pid=pid, tid=tid, name=label)

    _FLOW_PH = {"start": "s", "step": "t", "end": "f"}

    def flow(self, name: str, id: Any, phase: str = "step",
             cat: str = "flow", **args: Any) -> None:
        """A flow-event arrow (``s``/``t``/``f``) on the ``(cat, id)``
        flow track.  Linking one request's per-step spans with
        ``start`` → ``step``… → ``end`` draws a per-request lane across
        engine steps in Perfetto."""
        if not self.enabled:
            return
        ph = self._FLOW_PH.get(phase)
        if ph is None:
            raise ValueError(f"flow phase {phase!r} not in "
                             f"{sorted(self._FLOW_PH)}")
        ev: Dict[str, Any] = {"name": name, "ph": ph, "cat": cat,
                              "id": str(id), "pid": 0, "tid": 0,
                              "ts": self._now_us()}
        if ph == "f":
            ev["bp"] = "e"   # bind to the enclosing slice's end
        if args:
            ev["args"] = args
        self._events.append(ev)

    def async_begin(self, name: str, id: Any, cat: str = "req",
                    **args) -> None:
        """Open one async span of ``name`` on the ``(cat, id)`` track."""
        if not self.enabled:
            return
        ev: Dict[str, Any] = {"name": name, "ph": "b", "cat": cat,
                              "id": str(id), "pid": 0, "tid": 0,
                              "ts": self._now_us()}
        if args:
            ev["args"] = args
        self._events.append(ev)
        self._open_async[(cat, str(id))] = \
            self._open_async.get((cat, str(id)), 0) + 1

    def async_end(self, name: str, id: Any, cat: str = "req",
                  **args) -> None:
        if not self.enabled:
            return
        key = (cat, str(id))
        open_n = self._open_async.get(key, 0)
        if open_n <= 0:
            raise ValueError(f"async_end({name!r}, id={id!r}, cat={cat!r}) "
                             f"with no open span on that track")
        self._open_async[key] = open_n - 1
        ev: Dict[str, Any] = {"name": name, "ph": "e", "cat": cat,
                              "id": str(id), "pid": 0, "tid": 0,
                              "ts": self._now_us()}
        if args:
            ev["args"] = args
        self._events.append(ev)

    # -- introspection ------------------------------------------------------

    def open_spans(self) -> List[str]:
        """Names of sync spans currently open (outermost first)."""
        return [s.name for s in self._stack]

    def open_async_tracks(self) -> Dict[tuple, int]:
        """(cat, id) tracks with unclosed async spans."""
        return {k: n for k, n in self._open_async.items() if n > 0}

    def clear(self) -> None:
        self._events.clear()
        self._open_async.clear()

    # -- export -------------------------------------------------------------

    def chrome_trace(self) -> Dict[str, Any]:
        """The Trace Event Format document (JSON Object Format flavour,
        which both ``chrome://tracing`` and Perfetto load)."""
        events = sorted(self._events, key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> int:
        """Write the Chrome trace JSON; returns the event count."""
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"])


NULL_TRACER = Tracer(enabled=False)


def validate_chrome_trace(doc: Dict[str, Any]) -> None:
    """Structural check of an exported trace: required keys per phase,
    non-negative durations, and balanced async ``b``/``e`` pairs per
    ``(cat, id, name)`` track with ends never preceding begins.  Raises
    ``ValueError`` — used by tests and the CI artifact check."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("not a chrome trace: missing 'traceEvents'")
    opens: Dict[tuple, int] = {}
    for ev in doc["traceEvents"]:
        ph = ev.get("ph")
        if ph == "M":
            # Metadata events label lanes; ts is optional per the format.
            if "name" not in ev:
                raise ValueError(f"metadata event missing name: {ev}")
            continue
        if "name" not in ev or "ts" not in ev:
            raise ValueError(f"event missing name/ts: {ev}")
        if ph == "X":
            if ev.get("dur", -1) < 0:
                raise ValueError(f"negative duration: {ev}")
        elif ph in ("b", "e"):
            if "id" not in ev or "cat" not in ev:
                raise ValueError(f"async event missing id/cat: {ev}")
            key = (ev["cat"], ev["id"], ev["name"])
            n = opens.get(key, 0) + (1 if ph == "b" else -1)
            if n < 0:
                raise ValueError(f"async end before begin on {key}")
            opens[key] = n
        elif ph in ("s", "t", "f"):
            if "id" not in ev:
                raise ValueError(f"flow event missing id: {ev}")
        elif ph in ("i", "C"):
            pass
        else:
            raise ValueError(f"unknown phase {ph!r}: {ev}")
    dangling = {k for k, n in opens.items() if n != 0}
    if dangling:
        raise ValueError(f"unclosed async spans: {sorted(dangling)}")
