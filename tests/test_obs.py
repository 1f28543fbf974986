"""repro.obs: registry/histogram units, span nesting + Chrome-trace
round-trip, no-op zero-overhead smoke, a property that concurrent
per-request span streams always nest/close correctly, roofline
efficiency sanity, and the serve-level integration (TTFT/inter-token
split, dense live KV high-water)."""

import json
import math

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback sampler, see _hypothesis_stub
    from _hypothesis_stub import given, settings, st

from repro import obs
from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               _NULL_COUNTER, _NULL_GAUGE, _NULL_HISTOGRAM)
from repro.obs.trace import Tracer, _NULL_SPAN, validate_chrome_trace


# ---------------------------------------------------------------------------
# Metrics units
# ---------------------------------------------------------------------------


def test_counter_monotonic():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_high_water():
    g = Gauge("g")
    g.set(3)
    g.set(1)
    g.add(1)
    assert (g.value, g.high_water) == (2.0, 3.0)


def test_histogram_exact_percentiles_match_numpy():
    h = Histogram("h")
    rng = np.random.default_rng(0)
    xs = rng.exponential(10.0, size=257)
    for x in xs:
        h.observe(float(x))
    for q in (0, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(np.percentile(xs, q))
    assert h.count == 257
    assert h.sum == pytest.approx(float(xs.sum()))
    assert h.min == pytest.approx(float(xs.min()))
    assert h.max == pytest.approx(float(xs.max()))


def test_histogram_bucket_mode_bounds_and_memory():
    h = Histogram("h", buckets=[1.0, 10.0, 100.0])
    for v in (0.5, 3.0, 3.0, 30.0, 300.0):
        h.observe(v)
    assert not h._values          # bucket mode stores counts only
    s = h.summary()
    assert s["buckets"] == {"le_1": 1, "le_10": 2, "le_100": 1, "inf": 1}
    p50 = h.percentile(50)
    assert 1.0 <= p50 <= 10.0     # interpolated inside the winning bucket
    assert h.percentile(100) == 300.0


def test_histogram_bucket_validation():
    with pytest.raises(ValueError):
        Histogram("h", buckets=[10.0, 1.0])
    with pytest.raises(ValueError):
        Histogram("h", buckets=[1.0, 1.0])


def test_histogram_empty_summary_is_null():
    s = Histogram("h").summary()
    assert s["count"] == 0
    assert s["p50"] is None and s["min"] is None
    with pytest.raises(ValueError):
        Histogram("h").percentile(101)
    assert math.isnan(Histogram("h").percentile(50))


def test_registry_memoizes_and_rejects_kind_collisions():
    reg = Registry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("x")


def test_prometheus_text():
    reg = Registry()
    reg.counter("a.hits", help="hits").inc(2)
    reg.gauge("b.depth").set(4)
    h = reg.histogram("c.lat_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = reg.to_prometheus()
    assert "# TYPE a_hits counter" in text
    assert "a_hits 2" in text
    assert "b_depth 4" in text
    assert "b_depth_high_water 4" in text
    assert 'c_lat_ms{quantile="0.5"} 2' in text
    assert "c_lat_ms_count 3" in text


# ---------------------------------------------------------------------------
# Snapshot schema + exporters
# ---------------------------------------------------------------------------


def test_snapshot_validate_flatten_roundtrip(tmp_path):
    reg = Registry()
    reg.counter("hits").inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("lat").observe(5.0)
    path = tmp_path / "m.json"
    snap = obs.write_metrics(str(path), reg, extra={"run": {"tok_s": 7.0}},
                             required_counters=("hits",),
                             required_gauges=("depth",),
                             required_histograms=("lat",))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(snap))
    obs.validate_snapshot(loaded, required_histograms=("lat",))
    flat = obs.flatten_snapshot(loaded)
    assert flat["hits"] == 3.0
    assert flat["depth.value"] == 2.0
    assert flat["depth.high_water"] == 2.0
    assert flat["lat.p50"] == 5.0
    assert loaded["run"]["tok_s"] == 7.0


def test_snapshot_required_keys_enforced(tmp_path):
    reg = Registry()
    with pytest.raises(ValueError, match="missing required histogram"):
        obs.write_metrics(str(tmp_path / "m.json"), reg,
                          required_histograms=("serve.ttft_ms",))
    with pytest.raises(ValueError, match="collides"):
        obs.write_metrics(str(tmp_path / "m.json"), reg,
                          extra={"counters": {}})


def test_validate_snapshot_rejects_malformed():
    with pytest.raises(ValueError):
        obs.validate_snapshot({"schema": 99})
    with pytest.raises(ValueError):
        obs.validate_snapshot({"schema": 1, "counters": {"a": "nope"},
                               "gauges": {}, "histograms": {}})
    with pytest.raises(ValueError):
        obs.validate_snapshot({"schema": 1, "counters": {},
                               "gauges": {"g": {"value": 1}},
                               "histograms": {}})


# ---------------------------------------------------------------------------
# Tracer + Chrome-trace export
# ---------------------------------------------------------------------------


def test_span_nesting_and_export_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("step", cat="engine", step=0):
        with tr.span("admit", cat="engine"):
            pass
        with tr.span("decode", cat="engine"):
            tr.instant("preempt", cat="engine", rid=3)
    tr.counter("pages", in_use=4)
    path = tmp_path / "t.json"
    n = tr.write(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n == 5
    validate_chrome_trace(doc)
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in x]
    assert set(names) == {"step", "admit", "decode"}
    # Children close before the parent and nest inside its window.
    step = next(e for e in x if e["name"] == "step")
    for child in (e for e in x if e["name"] != "step"):
        assert step["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= step["ts"] + step["dur"] + 1e-6


def test_async_balance_enforced():
    tr = Tracer()
    tr.async_begin("request", 1)
    with pytest.raises(ValueError):
        tr.async_end("request", 2)       # never began
    tr.async_end("request", 1)
    assert tr.open_async_tracks() == {}
    validate_chrome_trace(tr.chrome_trace())


def test_validate_catches_dangling_and_unknown():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "r", "ph": "b", "cat": "req", "id": "1", "ts": 0.0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "r", "ph": "?", "ts": 0.0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "r", "ph": "X", "ts": 0.0, "dur": -1.0}]})


def test_noop_mode_zero_cost():
    reg = Registry(enabled=False)
    assert reg.counter("a") is _NULL_COUNTER
    assert reg.gauge("b") is _NULL_GAUGE
    assert reg.histogram("c") is _NULL_HISTOGRAM
    reg.counter("a").inc(5)
    reg.gauge("b").set(5)
    reg.histogram("c").observe(5)
    assert _NULL_COUNTER.value == 0.0
    assert _NULL_GAUGE.value == 0.0
    assert _NULL_HISTOGRAM.count == 0
    assert reg.snapshot()["counters"] == {}
    tr = Tracer(enabled=False)
    assert tr.span("s") is tr.span("t") is _NULL_SPAN
    tr.instant("i")
    tr.async_begin("r", 1)
    tr.async_end("r", 1)
    tr.counter("c", v=1)
    assert tr.chrome_trace()["traceEvents"] == []


# Property: any interleaving of per-request lifecycle streams (queued ->
# decode, with arbitrary preemption cycles back to queued) leaves the
# trace balanced: every begin has its end per (cat, id, name) track and
# nothing stays open.  This is the schedule shape the engine emits under
# concurrent admission/preemption/completion.
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=3),
                min_size=1, max_size=6),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_concurrent_request_streams_close(preempt_cycles, seed):
    import random
    rng = random.Random(seed)
    tr = Tracer()
    # Per-request remaining transition scripts, consumed in a random
    # global interleaving — modelling requests progressing concurrently.
    scripts = {}
    for rid, cycles in enumerate(preempt_cycles):
        script = [("begin", "request"), ("begin", "queued")]
        for _ in range(cycles + 1):
            script += [("end", "queued"), ("begin", "decode")]
            script += [("end", "decode"), ("begin", "queued")]
        # The last cycle completes instead of re-queueing:
        script = script[:-1]
        script += [("end", "request")]
        scripts[rid] = script
    while any(scripts.values()):
        rid = rng.choice([r for r, s in scripts.items() if s])
        op, name = scripts[rid].pop(0)
        if op == "begin":
            tr.async_begin(name, rid)
        else:
            tr.async_end(name, rid)
    assert tr.open_async_tracks() == {}
    validate_chrome_trace(tr.chrome_trace())


# ---------------------------------------------------------------------------
# Global bundle API
# ---------------------------------------------------------------------------


def test_global_bundle_configure_reset():
    obs.reset()
    obs.count("demo.evt", 2)
    assert obs.get_obs().registry.counter("demo.evt").value == 2.0
    reg = Registry()
    tr = Tracer(enabled=True)
    bundle = obs.configure(registry=reg, tracer=tr)
    assert bundle.registry is reg and bundle.tracer is tr
    assert obs.get_obs() is bundle
    obs.reset()
    assert obs.get_obs().registry is not reg
    assert not obs.get_obs().tracer.enabled


# ---------------------------------------------------------------------------
# Roofline efficiency
# ---------------------------------------------------------------------------


def test_efficiency_sanity_on_smoke_gemm():
    """0 < achieved/peak <= 1: a host-timed GEMM can never beat the
    analytic device peak, and a finished one always achieves > 0."""
    import time

    import jax.numpy as jnp
    from repro.obs.efficiency import gemm_efficiency, peak_flops
    a = jnp.ones((64, 64), jnp.float32)
    b = jnp.ones((64, 64), jnp.float32)
    t0 = time.perf_counter()
    np.asarray(a @ b)
    us = (time.perf_counter() - t0) * 1e6
    eff = gemm_efficiency(64, 64, 64, us, "float32", device_kind="TPU v5 lite")
    assert 0.0 < eff <= 1.0
    assert peak_flops("int8", device_kind="TPU v5 lite") > peak_flops(
        "bfloat16", device_kind="TPU v5 lite")
    with pytest.raises(ValueError):
        gemm_efficiency(8, 8, 8, 0.0)


def test_serve_efficiency_uses_model_flops():
    from repro import configs as C
    from repro.obs.efficiency import (model_flops_per_token,
                                      serve_efficiency)
    cfg = C.get_smoke("smollm_360m")
    f = model_flops_per_token(cfg)
    qkv_n = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head
    per_layer = (cfg.d_model * qkv_n
                 + cfg.n_heads * cfg.d_head * cfg.d_model
                 + 2 * cfg.d_model * cfg.d_ff + cfg.d_ff * cfg.d_model)
    assert f == 2.0 * (cfg.n_layers * per_layer
                       + cfg.d_model * cfg.vocab_size)
    eff = serve_efficiency(cfg, tok_s=100.0, device_kind="TPU v5 lite")
    assert 0.0 < eff <= 1.0


# ---------------------------------------------------------------------------
# Serving integration (marker matches the serving suite)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_engine_run():
    """One instrumented dense run on a fresh bundle; shared by the
    integration assertions below."""
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
    trace = synth_trace(4, 8, 6, 2, cfg.vocab_size, seed=0)
    try:
        rep = run_trace(engine, trace, log=None)
        yield engine, rep, bundle
    finally:
        engine.close()
        obs.reset()


@pytest.mark.serving
def test_run_trace_splits_ttft_from_inter_token(smoke_engine_run):
    engine, rep, bundle = smoke_engine_run
    assert len(rep["results"]) == 4
    for key in ("p50_ms", "p99_ms", "ttft_p50_ms", "ttft_p99_ms"):
        assert np.isfinite(rep[key]) and rep[key] >= 0.0
    hists = bundle.registry.snapshot()["histograms"]
    # One TTFT sample per request (dense mode never preempts).  The
    # first token of each request is emitted by prefill and charged to
    # TTFT; every *subsequent* token gets a decode-only latency sample.
    assert hists["serve.ttft_ms"]["count"] == 4
    assert hists["serve.inter_token_ms"]["count"] == rep["tokens"] - 4


@pytest.mark.serving
def test_dense_live_high_water_below_reservation(smoke_engine_run):
    engine, rep, _ = smoke_engine_run
    hwm, reserved = rep["kv_bytes_hwm"], rep["kv_bytes_reserved"]
    # 4 staggered requests over 2 slots at 8+6 < max_len=64 tokens can
    # never come close to binding the full reservation.
    assert 0 < hwm < reserved
    # The hwm is at least the largest single resident demand seen: two
    # concurrent requests one token past their prompt.
    assert hwm >= 2 * (8 + 1) * engine.token_kv_bytes()


@pytest.mark.serving
def test_engine_trace_is_balanced_and_perfetto_valid(smoke_engine_run):
    _, rep, bundle = smoke_engine_run
    assert bundle.tracer.open_async_tracks() == {}
    doc = bundle.tracer.chrome_trace()
    validate_chrome_trace(doc)
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "b", "e"} <= phases
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"engine.step", "engine.prefill", "engine.decode.dispatch",
            "decode", "request", "queued"} <= names
