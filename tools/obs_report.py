#!/usr/bin/env python3
"""Render repro.obs artifacts into a terminal/markdown report.

Consumes the JSON artifacts a serving run leaves behind and turns them
into the tables a human actually reads during triage:

* ``--metrics`` (required) — the schema-1 snapshot from
  ``--metrics-out`` / ``benchmarks.run --json``: run summary and SLO
  window state;
* ``--trace`` (optional) — the Chrome trace from ``--trace-out``: span
  aggregates per name and the **breach log** (``slo.breach`` instants);
* ``--flight`` (optional) — the ``--flight-out`` flight record: trip
  log and the last recorded steps.

Where the device's time goes is read from a ``jax.profiler`` trace
instead, on the device's clock: ``python -m repro.obs.devtrace``.

Markdown-shaped output (pipe tables) renders in a terminal and pastes
straight into an issue.  Exit codes: 0 ok, 2 malformed input.

    python tools/obs_report.py --metrics serve_metrics.json \
        --trace serve_trace.json --flight flight.json [--out report.md]

Stdlib-only on purpose (like bench_compare.py): it must run anywhere,
including CI artifact checks, without the repro package on the path.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

BAD = 2


def _load(path: str, what: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"obs_report: cannot read {what} {path!r}: {e}")
    if not isinstance(doc, dict):
        raise SystemExit(f"obs_report: {what} {path!r} is not an object")
    return doc


def _table(headers: List[str], rows: List[List[str]]) -> List[str]:
    out = ["| " + " | ".join(headers) + " |",
           "|" + "|".join("---" for _ in headers) + "|"]
    out += ["| " + " | ".join(r) + " |" for r in rows]
    return out


def _fmt(v: Any, nd: int = 3) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        if v != v:
            return "nan"
        if v and (abs(v) >= 1e5 or abs(v) < 10 ** -nd):
            return f"{v:.2e}"
        return f"{v:.{nd}f}"
    return str(v)


def _hist_row(name: str, h: Dict[str, Any]) -> List[str]:
    return [name] + [_fmt(h.get(k)) for k in
                     ("count", "p50", "p90", "p99", "max", "sum")]


def run_summary(snap: Dict[str, Any]) -> List[str]:
    run = snap.get("run")
    if not isinstance(run, dict):
        return []
    keys = ("arch", "kv_mode", "prefill_chunk", "tokens", "tok_s",
            "p50_ms", "p99_ms", "ttft_p50_ms", "ttft_p99_ms",
            "slo_breaches")
    rows = [[k, _fmt(run[k])] for k in keys if k in run]
    eff = snap.get("gauges", {}).get("serve.efficiency", {})
    if isinstance(eff, dict) and "value" in eff:
        rows.append(["efficiency (of the published peak)",
                     _fmt(eff["value"])])
    if not rows:
        return []
    return ["## Run", ""] + _table(["key", "value"], rows) + [""]


def slo_section(snap: Dict[str, Any],
                trace: Optional[Dict[str, Any]],
                flight: Optional[Dict[str, Any]]) -> List[str]:
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    rows = []
    for series in ("ttft", "itl"):
        breaches = counters.get(f"slo.{series}.breaches")
        window = [v for k, v in gauges.items()
                  if k.startswith(f"slo.{series}.window_")
                  and isinstance(v, dict)]
        if breaches is None and not window:
            continue
        rows.append([series, _fmt(breaches or 0.0, 0),
                     _fmt(window[0].get("value") if window else None),
                     _fmt(window[0].get("high_water") if window
                          else None)])
    out: List[str] = []
    if rows:
        out += ["## SLO", ""]
        out += _table(["series", "breaches", "window p99 (ms)",
                       "window high water"], rows) + [""]
    breach_log: List[List[str]] = []
    if trace is not None:
        for ev in trace.get("traceEvents", []):
            if ev.get("ph") == "i" and ev.get("name") == "slo.breach":
                a = ev.get("args", {})
                breach_log.append(
                    [_fmt(ev.get("ts", 0.0) / 1e3, 1),
                     str(a.get("series", "?")),
                     _fmt(a.get("window_pq_ms")),
                     _fmt(a.get("target_ms"))])
    if flight is not None:
        for t in flight.get("trips", []):
            breach_log.append([_fmt(t.get("t_ms"), 1),
                               str(t.get("reason", "?")),
                               _fmt(t.get("window_ms")),
                               _fmt(t.get("target_ms"))])
    if breach_log:
        out += ["### Breach log", ""]
        out += _table(["t (ms)", "what", "window (ms)", "target (ms)"],
                      breach_log) + [""]
    return out


def trace_section(trace: Dict[str, Any]) -> List[str]:
    spans: Dict[str, List[float]] = {}
    phases: Dict[str, int] = {}
    for ev in trace.get("traceEvents", []):
        ph = ev.get("ph", "?")
        phases[ph] = phases.get(ph, 0) + 1
        if ph == "X":
            spans.setdefault(ev.get("name", "?"), []).append(
                float(ev.get("dur", 0.0)) / 1e3)
    out = ["## Trace", "",
           "events by phase: " + ", ".join(
               f"{k}={v}" for k, v in sorted(phases.items()))]
    if spans:
        rows = []
        for name in sorted(spans, key=lambda n: -sum(spans[n])):
            ds = spans[name]
            rows.append([name, str(len(ds)), _fmt(sum(ds)),
                         _fmt(sum(ds) / len(ds))])
        out += [""] + _table(["span", "count", "total (ms)",
                              "mean (ms)"], rows)
    return out + [""]


def flight_section(flight: Dict[str, Any], last: int = 8) -> List[str]:
    steps = flight.get("steps", [])
    reqs = flight.get("requests", {})
    out = ["## Flight recorder", "",
           f"reason: {flight.get('reason', '?')} — "
           f"{len(steps)} steps retained, {len(reqs)} request "
           f"timelines, {len(flight.get('trips', []))} trips"]
    if steps:
        rows = [[_fmt(s.get("step"), 0), _fmt(s.get("wall_ms")),
                 _fmt(s.get("decoded"), 0), _fmt(s.get("finished"), 0),
                 _fmt(s.get("preempted"), 0)]
                for s in steps[-last:]]
        out += [""] + _table(["step", "wall ms", "decoded", "finished",
                              "preempted"], rows)
    return out + [""]


def render(snap: Dict[str, Any], trace: Optional[Dict[str, Any]],
           flight: Optional[Dict[str, Any]]) -> str:
    lines = ["# repro.obs report", ""]
    lines += run_summary(snap)
    lines += slo_section(snap, trace, flight)
    if trace is not None:
        lines += trace_section(trace)
    if flight is not None:
        lines += flight_section(flight)
    return "\n".join(lines).rstrip() + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--metrics", required=True,
                    help="schema-1 metrics snapshot JSON")
    ap.add_argument("--trace", default=None,
                    help="Chrome trace JSON (--trace-out artifact)")
    ap.add_argument("--flight", default=None,
                    help="flight recorder JSON (--flight-out artifact)")
    ap.add_argument("--out", default=None,
                    help="write the report here instead of stdout")
    args = ap.parse_args(argv)
    snap = _load(args.metrics, "metrics snapshot")
    if "counters" not in snap or "gauges" not in snap:
        print(f"obs_report: {args.metrics!r} is not a metrics snapshot "
              f"(missing counters/gauges)", file=sys.stderr)
        return BAD
    trace = _load(args.trace, "chrome trace") if args.trace else None
    if trace is not None and "traceEvents" not in trace:
        print(f"obs_report: {args.trace!r} is not a chrome trace",
              file=sys.stderr)
        return BAD
    flight = _load(args.flight, "flight record") if args.flight else None
    if flight is not None and "steps" not in flight:
        print(f"obs_report: {args.flight!r} is not a flight record",
              file=sys.stderr)
        return BAD
    report = render(snap, trace, flight)
    if args.out:
        with open(args.out, "w") as f:
            f.write(report)
        print(f"obs_report: wrote {args.out}")
    else:
        print(report, end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
