"""The trace reduction: busy union, window clipping, kernel time, idle
gaps named by the host span open at the time."""

import warnings
from pathlib import Path

import pytest

from bench.lib import xtrace

DATA = Path(__file__).resolve().parent / "data"


def xspace(tmp_path, host, device):
    """An .xplane.pb from (name, start_ns, dur_ns[, text]) events."""
    from jax.profiler import ProfileData

    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[e[0]]} offset_ps: {e[1] * 1000} "
            f"duration_ps: {e[2] * 1000}"
            + (f' stats {{ metadata_id: 9 str_value: "{e[3]}" }}'
               if len(e) > 3 else "") + " } " for e in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} '
                f'stat_metadata {{ key: 9 value {{ id: 9 name: '
                f'"long_name" }} }} }} ')

    text = plane(1, "/host:CPU", "python3", host) + plane(
        2, "/device:TPU:0", "XLA Ops", device)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture
def reduced(tmp_path):
    host = [("bench.window", 100, 1000), ("bench.step", 100, 400),
            ("bench.idle_wait", 500, 300), ("bench.step", 800, 300),
            ("other", 0, 5000)]
    device = [("fusion.1", 50, 100),                   # clipped to 50
              ("fusion.2", 200, 100, "custom-call gama_gemm k"),
              ("fusion.3", 250, 100),                   # overlaps .2
              ("gama_gemm", 900, 100),
              ("copy.1", 1200, 50)]                     # after the close
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return xtrace.reduce_file(xspace(tmp_path, host, device))


def test_window_and_busy_union(reduced):
    assert reduced.window_s == pytest.approx(1000e-9)
    # [100,150) + [200,350) + [900,1000) = 50 + 150 + 100 ns
    assert reduced.busy_s == pytest.approx(300e-9)


def test_kernel_time_by_name_or_carried_text(reduced):
    assert reduced.kernel_s("gama_gemm") == pytest.approx(200e-9)
    assert reduced.kernel_s("flash") == 0


def test_idle_gaps_named_by_host_span(reduced):
    gaps = dict((round(s * 1e9), n) for n, s in reduced.gaps)
    # [350,900): middle 625 in idle_wait; [150,200): in step; [1000,1100)
    # in the second step.
    assert gaps == {550: "bench.idle_wait", 50: "bench.step",
                    100: "bench.step"}
    assert reduced.gaps[0][0] == "bench.idle_wait"


def test_breakdown_shape(reduced):
    b = reduced.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    top = dict(b["device_ops"])
    assert top["fusion.2"] == pytest.approx(100e-9)
    assert "copy.1" not in top


def test_no_window_span_is_an_error(tmp_path):
    path = xspace(tmp_path, [("bench.step", 0, 10)], [("f", 0, 5)])
    with pytest.raises(ValueError, match="bench.window"):
        xtrace.reduce_file(path)


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite: three 256x512x256 bf16
    `gama_gemm` calls, each in a `bench.step` span followed by a 2 ms
    `bench.idle_wait`.  The device's clock in it runs about 1.2 ms
    behind the host's."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        r = xtrace.reduce_file(DATA / "tiny.xplane.pb",
                               window=(45_000_000, 57_000_000))
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.012)
    gemm = r.kernel_s("gama_gemm")
    assert gemm == pytest.approx((2108 + 1891 + 1893) * 1e-9, rel=1e-3)
    assert r.busy_s == pytest.approx(gemm)
    assert {n for n, _ in r.gaps} <= {"bench.step", "bench.idle_wait",
                                      "bench.window"}
    assert "bench.idle_wait" in {n for n, _ in r.gaps}
    assert r.gaps[0][1] > 2e-3


def test_breakdown_names_ops_and_skips_containers(tmp_path):
    host = [("bench.window", 0, 1000)]
    device = [("%while.5 = (s32[]) while(...)", 0, 900),
              ("%gama_gemm.7 = bf16[16,128] custom-call(...)", 10, 300),
              ("%fusion.2 = bf16[16] fusion(...)", 400, 100)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        r = xtrace.reduce_file(xspace(tmp_path, host, device))
    ops = dict(r.breakdown()["device_ops"])
    assert set(ops) == {"%gama_gemm.7", "%fusion.2"}
    assert r.busy_s == pytest.approx(900e-9)
