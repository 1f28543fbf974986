"""repro.obs slo / flight: SLO breaches fire exactly at the threshold
(rolling-window property vs a reference model), flight rings never
exceed their bounds and dumps round-trip through JSON — plus the
histogram reservoir cap and the Chrome-trace metadata/flow extensions
they ride on."""

import json

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback sampler, see _hypothesis_stub
    from _hypothesis_stub import given, settings, st

from collections import deque

from repro import obs
from repro.obs.flight import FlightRecorder
from repro.obs.metrics import Histogram, Registry
from repro.obs.slo import SLOMonitor, window_percentile
from repro.obs.trace import Tracer, validate_chrome_trace


# ---------------------------------------------------------------------------
# Histogram reservoir cap (exact mode stays bounded)
# ---------------------------------------------------------------------------


def test_histogram_cap_bounds_memory_and_keeps_aggregates_exact():
    h = Histogram("h", max_samples=64)
    rng = np.random.default_rng(3)
    xs = rng.exponential(10.0, size=5000)
    for x in xs:
        h.observe(float(x))
    assert len(h._values) <= 64          # the whole point of the cap
    # count/sum/min/max are tracked outside the reservoir — bit-exact.
    assert h.count == 5000
    assert h.sum == pytest.approx(float(xs.sum()))
    assert h.min == pytest.approx(float(xs.min()))
    assert h.max == pytest.approx(float(xs.max()))
    # p100 survives every decimation (the max is explicitly re-kept).
    assert h.percentile(100) == pytest.approx(float(xs.max()))


def test_histogram_cap_percentile_error_bounded():
    """Decimation keeps every other order statistic, so capped
    percentiles track the exact ones within a few percent even at a
    ~20x over-subscribed reservoir."""
    h = Histogram("h", max_samples=512)
    rng = np.random.default_rng(7)
    xs = rng.exponential(10.0, size=10_000)
    for x in xs:
        h.observe(float(x))
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        assert h.percentile(q) == pytest.approx(exact, rel=0.10), q


def test_histogram_cap_validation_and_disable():
    with pytest.raises(ValueError):
        Histogram("h", max_samples=1)
    h = Histogram("h", max_samples=None)          # uncapped opt-out
    for v in range(Histogram.DEFAULT_MAX_SAMPLES + 8):
        h.observe(float(v))
    assert len(h._values) == Histogram.DEFAULT_MAX_SAMPLES + 8
    # Registry passthrough: capped histograms via the normal factory.
    reg = Registry()
    assert reg.histogram("x", max_samples=8).max_samples == 8


# ---------------------------------------------------------------------------
# Tracer: metadata + flow events (per-request lanes)
# ---------------------------------------------------------------------------


def test_metadata_and_flow_events_validate():
    tr = Tracer()
    tr.process_name("repro-serve")
    tr.thread_name("engine", tid=1)
    with tr.span("engine.step"):
        tr.flow("req7", 7, "start")
        tr.flow("req7", 7, "step")
        tr.flow("req7", 7, "end")
    doc = tr.chrome_trace()
    validate_chrome_trace(doc)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert [e["name"] for e in meta] == ["process_name", "thread_name"]
    # The label rides in args["name"] — the positional-only method name
    # parameter must not collide with it.
    assert meta[0]["args"]["name"] == "repro-serve"
    assert meta[1]["tid"] == 1
    flows = sorted((e for e in doc["traceEvents"] if e["ph"] in "stf"),
                   key=lambda e: e["ts"])
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == "7" for e in flows)
    assert flows[-1]["bp"] == "e"        # bind the end to its slice
    with pytest.raises(ValueError):
        tr.flow("x", 1, "bogus-phase")


def test_metadata_and_flow_noop_when_disabled_and_validator_rules():
    off = Tracer(enabled=False)
    off.process_name("x")
    off.flow("x", 1, "start")
    assert off.chrome_trace()["traceEvents"] == []
    with pytest.raises(ValueError):      # flow events need an id
        validate_chrome_trace({"traceEvents": [
            {"name": "f", "ph": "s", "ts": 0.0}]})
    with pytest.raises(ValueError):      # metadata events need a name
        validate_chrome_trace({"traceEvents": [{"ph": "M"}]})


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------


def test_slo_breach_fires_exactly_at_threshold():
    mon = SLOMonitor(Registry(), itl_target_ms=10.0, window=8)
    for _ in range(8):
        assert not mon.observe_itl(10.0)  # window p99 == target: meeting
    assert mon.breaches() == 0
    assert mon.observe_itl(10.0 + 1e-6)   # first push over: fires
    assert mon.breaches("itl") == 1
    assert mon.signals()["slo_breached"] is True


# Property: the monitor's breach count equals a reference model that
# recomputes the rolling-window percentile per observation — for any
# observation sequence, window size and integer target.
@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=50),
                min_size=1, max_size=40),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=40))
def test_slo_breach_matches_reference_model(vals, window, target):
    mon = SLOMonitor(Registry(), itl_target_ms=float(target),
                     window=window)
    ref: deque = deque(maxlen=window)
    expected = 0
    for v in vals:
        ref.append(float(v))
        if window_percentile(ref, 99.0) > target:
            expected += 1
        mon.observe_itl(float(v))
    assert mon.breaches("itl") == expected


def test_slo_untargeted_series_never_breaches_and_retargets():
    reg = Registry()
    tr = Tracer()
    mon = SLOMonitor(reg, tracer=tr, window=4)   # both targets off
    for v in (1.0, 1e6):
        mon.observe_ttft(v)
        mon.observe_itl(v)
    assert mon.breaches() == 0
    assert mon.signals()["slo_breached"] is False
    # Window gauges export even with no targets armed.
    g = reg.snapshot()["gauges"]
    assert g["slo.itl.window_p99_ms"]["value"] > 0
    mon.set_targets(ttft_ms=0.5)                 # arm one series only
    assert mon.observe_ttft(2.0) is True
    assert mon.breaches("ttft") == 1
    assert mon.breaches("itl") == 0
    # Breach emitted a trace instant for Perfetto correlation.
    instants = [e for e in tr.chrome_trace()["traceEvents"]
                if e["ph"] == "i" and e["name"] == "slo.breach"]
    assert len(instants) == 1
    assert instants[0]["args"]["series"] == "ttft"
    mon.set_targets(ttft_ms=None)                # disarm again
    assert mon.observe_ttft(1e9) is False


def test_slo_on_breach_callbacks_fire():
    mon = SLOMonitor(Registry(), itl_target_ms=1.0, window=2)
    hits = []
    mon.on_breach(lambda series, q, target: hits.append((series, target)))
    mon.observe_itl(5.0)
    assert hits == [("itl", 1.0)]


def test_window_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0]
    for q in (0, 25, 50, 90, 99, 100):
        assert window_percentile(xs, q) == \
            pytest.approx(float(np.percentile(xs, q)))
    assert window_percentile([], 50) != window_percentile([], 50)  # NaN
    with pytest.raises(ValueError):
        window_percentile(xs, 101)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_rings_never_exceed_bounds():
    fr = FlightRecorder(capacity=16, max_requests=4, max_events=8)
    for i in range(1000):
        fr.record_step(i, wall_ms=1.0)
        fr.record_request_event(i % 10, "tick", n=i)
    assert len(fr) == 16
    dump = fr.dump()
    assert len(dump["steps"]) == 16
    assert dump["steps"][-1]["step"] == 999
    assert len(dump["requests"]) <= 4
    assert all(len(tl) <= 8 for tl in dump["requests"].values())
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_flight_request_eviction_is_fifo():
    fr = FlightRecorder(max_requests=2)
    for rid in ("a", "b", "c"):
        fr.record_request_event(rid, "submitted")
    assert list(fr.dump()["requests"]) == ["b", "c"]  # oldest fell off


def test_flight_dump_round_trips_json(tmp_path):
    fr = FlightRecorder(capacity=4, path=str(tmp_path / "fl.json"))
    fr.record_step(0, wall_ms=1.5, decoded=2)
    fr.record_request_event("r1", "first_token", ttft_ms=3.25)
    fr.trip("unit_test", detail="x")     # path armed: writes immediately
    doc = fr.dump("final")
    assert json.loads(json.dumps(doc)) == doc
    on_disk = json.loads((tmp_path / "fl.json").read_text())
    assert on_disk["reason"] == "unit_test"
    assert on_disk["steps"][0]["decoded"] == 2
    assert fr.write(str(tmp_path / "fl2.json"), "end")["reason"] == "end"
    assert json.loads((tmp_path / "fl2.json").read_text())["reason"] == "end"


def test_flight_preemption_storm_trips():
    fr = FlightRecorder(storm_preemptions=3, storm_window_steps=4)
    assert not fr.note_preemption(10, rid="a")
    assert not fr.note_preemption(11, rid="b")
    assert fr.note_preemption(12, rid="a")       # 3 within 4 steps
    assert fr.trips[-1]["reason"] == "preemption_storm"
    # Spread-out preemptions never trip.
    fr2 = FlightRecorder(storm_preemptions=3, storm_window_steps=4)
    for step in (0, 10, 20, 30):
        assert not fr2.note_preemption(step)


# ---------------------------------------------------------------------------
# Engine integration (marker matches the serving suite)
# ---------------------------------------------------------------------------


@pytest.mark.serving
def test_engine_attribution_slo_and_flight(tmp_path):
    """End-to-end over a real engine: every step leaves a flight record
    of its wall time, default targets see zero breaches on the smoke
    trace, a deliberately tight target fires and trips the flight
    recorder."""
    import jax

    from repro import configs as C
    from repro.launch.serve import run_trace, synth_trace
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, ServeEngine
    bundle = obs.configure(registry=Registry(),
                           tracer=Tracer(enabled=True))
    cfg = C.get_smoke("smollm_360m")
    params = init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, ServeConfig(batch_slots=2,
                                                  max_len=64))
    trace = synth_trace(3, 8, 5, 2, cfg.vocab_size, seed=1)
    try:
        rep = run_trace(engine, trace, log=None)
        # Acceptance: zero breaches at default (unarmed) targets.
        assert engine.slo.breaches() == 0
        assert rep["slo_breaches"] == 0
        # Every retained step record carries the step's host wall time
        # and its request counts.
        dump = engine.flight.dump("test")
        assert dump["steps"]
        for s in dump["steps"]:
            assert s["wall_ms"] > 0.0
            assert {"admitted", "decoded", "finished",
                    "preempted"} <= set(s)
        # Every request has a full flight timeline.
        for t in trace:
            evs = [e["event"] for e in dump["requests"][str(t["id"])]]
            for expect in ("submitted", "admitted", "first_token",
                           "finished"):
                assert expect in evs, (t["id"], evs)
        # Now arm an impossible ITL target and replay: breaches fire,
        # the flight recorder trips and writes its snapshot.
        engine.slo.set_targets(itl_ms=1e-6)
        engine.flight.path = str(tmp_path / "flight.json")
        rep2 = run_trace(engine, trace, log=None)
        assert engine.slo.breaches("itl") > 0
        assert rep2["slo_breaches"] > 0
        on_disk = json.loads((tmp_path / "flight.json").read_text())
        assert on_disk["reason"] == "slo_breach"
        assert any(t["reason"] == "slo_breach" for t in on_disk["trips"])
        # Breach instants landed in the (still valid) trace.
        doc = bundle.tracer.chrome_trace()
        validate_chrome_trace(doc)
        assert any(e["ph"] == "i" and e["name"] == "slo.breach"
                   for e in doc["traceEvents"])
        # Per-request flow lanes got emitted alongside.
        assert {"s", "t", "f"} <= {e["ph"] for e in doc["traceEvents"]}
    finally:
        engine.close()
        obs.reset()
