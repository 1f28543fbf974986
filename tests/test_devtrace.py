"""repro.obs.devtrace: a profiler trace reduced to device seconds per
program, idle time under the innermost engine span, and the idle gaps
between decode executions — on synthetic xspaces and on a trace
recorded on one TPU v5 lite."""

import warnings
from pathlib import Path

import pytest

from repro.obs import devtrace

RECORDED = (Path(__file__).resolve().parents[1] / "bench" / "tests" / "data"
            / "tiny.xplane.pb")


def xspace(tmp_path, host, modules, ops):
    """An .xplane.pb from (name, start_ns, dur_ns) events: ``host`` on
    the host's python thread, ``modules`` and ``ops`` on device 0's
    ``XLA Modules`` and ``XLA Ops`` lines."""
    from jax.profiler import ProfileData

    def plane(pid, name, lines):
        names = sorted({e[0] for _, events in lines for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = ""
        for lid, (line, events) in enumerate(lines, 1):
            evs = "".join(
                f"events {{ metadata_id: {ids[e[0]]} offset_ps: "
                f"{e[1] * 1000} duration_ps: {e[2] * 1000} }} "
                for e in events)
            body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                     f'{evs} }} ')
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        return f'planes {{ id: {pid} name: "{name}" {body} {meta} }} '

    text = plane(1, "/host:CPU", [("python", host)]) + plane(
        2, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def load(path, window="bench.window"):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return devtrace.load(path, window)


@pytest.fixture
def steps(tmp_path):
    """Two engine steps inside a 1000 ns window.  Device busy:
    [100, 300) decode, [450, 500) a chunk, [600, 800) decode."""
    host = [("bench.window", 0, 1000),
            ("bench.step", 0, 500), ("engine.step", 0, 500),
            ("engine.decode.prepare", 50, 150),     # [50, 200)
            ("engine.decode.dispatch", 200, 100),   # [200, 300)
            ("engine.decode.wait", 300, 150),       # [300, 450)
            ("bench.step", 500, 450), ("engine.step", 520, 400),
            ("engine.prefill_chunk", 520, 60),      # [520, 580)
            ("engine.decode.prepare", 580, 20),     # [580, 600)
            ("engine.decode.wait", 800, 100)]       # [800, 900)
    modules = [("jit_engine_decode(11)", 100, 200),
               ("jit_engine_insert_chunk(12)", 450, 50),
               ("jit_engine_decode(11)", 600, 200)]
    ops = [("fusion.1", 100, 200), ("fusion.2", 450, 50),
           ("gama_flash_paged_decode", 600, 200)]
    return load(xspace(tmp_path, host, modules, ops))


def test_innermost_span_names_each_stretch(steps):
    segs = steps.segments()
    assert segs[0] == (0, 50, "engine.step")
    named = {(a, b): n for a, b, n in segs}
    assert named[(50, 200)] == "engine.decode.prepare"
    assert named[(500, 520)] == "bench.step"
    assert named[(920, 950)] == "bench.step"
    assert named[(950, 1000)] == "bench.window"
    # Stretches tile the window with no overlap.
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    assert segs[-1][1] == 1000


def test_idle_apportioned_across_spans_a_gap_crosses(steps):
    idle = {k: round(v * 1e9) for k, v in steps.idle_s().items()}
    # Gap [300, 450) lies under decode.wait alone; gap [500, 600)
    # crosses bench.step [500, 520), the chunk [520, 580) and
    # decode.prepare [580, 600): each gets its own share.
    assert idle == {"engine.step": 70, "engine.decode.prepare": 70,
                    "engine.decode.wait": 250,
                    "engine.prefill_chunk": 60, "bench.step": 50,
                    "bench.window": 50}
    assert sum(idle.values()) == 1000 - 450
    assert steps.busy_s() == pytest.approx(450e-9)


def test_program_seconds_and_decode_gaps(steps):
    progs = steps.program_s()
    assert list(progs) == ["jit_engine_decode", "jit_engine_insert_chunk"]
    assert progs["jit_engine_decode"] == pytest.approx(400e-9)
    # Between the decodes [300, 600) the chunk insert ran 50 ns.
    assert steps.program_gaps() == [pytest.approx(250e-9)]
    s = steps.summary()
    assert s["decode_gap_ms_p50"] == pytest.approx(250e-6)
    assert s["engine_idle_pct"] == pytest.approx(100 * 450 / 1000)


def test_longest_gaps_named_at_their_middle(steps):
    gaps = steps.gaps(4)
    # [800, 1000) has its middle under the second engine.step, past
    # the wait that holds most of it.
    assert [(n, round(d * 1e9), p) for n, d, _, p in gaps] == [
        ("engine.step", 200, ""),
        ("engine.decode.wait", 150, "jit_engine_insert_chunk"),
        ("engine.decode.prepare", 100, "jit_engine_decode"),
        ("engine.prefill_chunk", 100, "jit_engine_decode")]
    # The last gap starts at 500 ns, 0.5 us after the window opened.
    assert gaps[3][2] == pytest.approx(500e-9)


def test_trace_without_engine_spans_or_decodes(tmp_path):
    host = [("bench.window", 0, 100), ("bench.step", 10, 80)]
    r = load(xspace(tmp_path, host, [("jit__lambda(3)", 20, 10)],
                    [("fusion", 20, 10)]))
    s = r.summary()
    assert s["decode_gap_ms_p50"] is None and s["decode_pairs"] == 0
    assert s["engine_idle_pct"] == 0.0
    assert set(s["idle_s"]) == {"bench.step", "bench.window"}
    assert s["program_s"] == {"jit__lambda": pytest.approx(10e-9)}
    with pytest.raises(ValueError, match="no 'engine.step' span"):
        load(xspace(tmp_path, host, [], [("f", 0, 5)]), "engine.step")


def test_recorded_chip_trace():
    """Three `gama_gemm` calls recorded on one TPU v5 lite, each its
    own `jit__lambda` execution; with no window span, the device's
    first to last event bound the analysis."""
    r = load(RECORDED, window=None)
    assert r.devices == 1
    progs = r.program_s()
    assert list(progs) == ["jit__lambda"]
    assert progs["jit__lambda"] == pytest.approx(
        (2112 + 1893 + 1897) * 1e-9, rel=1e-3)
    assert r.busy_s() == pytest.approx((2108 + 1891 + 1893) * 1e-9,
                                       rel=1e-3)
    assert len(r.program_gaps("jit__lambda")) == 2
    assert all(g > 1e-3 for g in r.program_gaps("jit__lambda"))
