"""Run one cell of the benchmark once.

    python3 -m bench.cell --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``, whose ``reference`` key names its
architecture: ``bench/arch/<name>.py`` and ``bench/reference/<name>.py``)
and a traffic mix (``bench/traffic/<mix>.json``); its output-check
limits are in ``bench/limits/<workload>.json`` and each per-layer metric
is read by ``bench/metrics/<metric>.py``.  The run draws the weights and the
requests from ``--seed``, builds the program's ``ServeEngine`` with the
mix's settings, warms up every shape the mix uses, replays the mix for
``--seconds`` through ``submit()``/``step()``, checks the served tokens
against the plain reference, and prints one JSON line last: the
end-to-end metrics with ``--trace 0``, the per-layer metrics (read from
a profiler trace of the same window) with ``--trace 1``.

It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()


def _process_age_s() -> float:
    """Seconds since this process started (Linux /proc; 0 elsewhere),
    so that set-up counts the interpreter's own start too."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        import os as _os
        return max(0.0, up - start / _os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = _process_age_s()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def fail(msg: str) -> None:
    log(f"FAIL: {msg}")
    raise SystemExit(1)


def load_cell(root: Path, workload: str) -> dict:
    """The cell's entries and files, found by name under ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        fail(f"no workload {workload!r} in BENCHMARK.json "
             f"(known: {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                     .read_text())

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": config, "mix": mix,
            "config_file": root / config["file"],
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: where the environment names
    one, else at a fixed path inside the checkout."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".cache" / "jax_compile")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    # Small programs too: a warm run then compiles nothing at all.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Backend compile seconds and persistent-cache hits and misses."""

    def __init__(self):
        import jax
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.seconds, self.hits, self.misses


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and info["platform"] != "tpu":
        fail(f"JAX finds no TPU (platform {info['platform']})")
    if require_chip and info["count"] < chips:
        fail(f"the cell needs {chips} chips, JAX finds {info['count']}")
    return info


def warm_records(mix: dict, vocab: int):
    """One request per prompt-chunk width the mix runs, two tokens each:
    the chunk programs of every width and the decode step."""
    import numpy as np
    from bench.lib.replay import Record
    from bench.lib.traffic import Request, chunk_widths
    return [Record(Request(-1 - i, 0.0, np.full((w,), 1 + i % (vocab - 1),
                                               np.int32), 2))
            for i, w in enumerate(chunk_widths(mix))]


def end_to_end(run, wanted, setup_s: float) -> dict:
    """The window's end-to-end metrics, as BENCHMARK.json defines them."""
    from bench.lib.replay import percentile
    s = run.seconds
    out_tok = sum(1 for r in run.records for t in r.times if 0 <= t <= s)
    prompt_tok = sum(len(r.req.prompt) for r in run.records
                     if r.times and 0 <= r.times[0] <= s)
    due = [r for r in run.records if 0 <= r.req.due_s <= s]
    # A request with no first token waited at least to the drain's end.
    ttft = [((r.times[0] if r.times else run.drain_end) - r.req.due_s)
            * 1e3 for r in due]
    gaps = [(b - a) * 1e3 for r in run.records
            for a, b in zip(r.times, r.times[1:]) if a >= 0 and b <= s]
    values = {"output_tok_s": out_tok / s, "prompt_tok_s": prompt_tok / s,
              "ttft_p95_ms": percentile(ttft, 95),
              "itl_p95_ms": percentile(gaps, 95), "setup_s": setup_s}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if values.get(m["name"]) is not None}


def attempted_failed(run, mix: dict) -> tuple:
    if mix["arrivals"]["kind"] == "poisson":
        tried = [r for r in run.records if r.req.due_s <= run.seconds]
    else:
        tried = [r for r in run.records
                 if not math.isnan(r.admit_t) or r.failed]
    return len(tried), sum(1 for r in tried if r.failed)


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_chip: bool = True,
             fault=None, overrides=None, rate=None,
             control: bool = False) -> dict:
    """One run of ``workload``; returns the result line's object.
    ``fault(engine)`` lets a test break the timed path underneath;
    ``overrides`` go to the engine's ``ServeConfig`` (the output check's
    control switches the program's int8 weights on this way); ``rate``
    replaces an open-loop mix's arrival rate (the knee sweep).  With
    ``control``, the output check judges the other control in the
    served tokens' place: at each of their positions, the token that
    the reference at int8 weights ranks first."""
    spec = load_cell(root, workload)
    cell, mix = spec["cell"], spec["mix"]
    if rate is not None:
        mix["arrivals"]["rate_per_s"] = rate
    from bench.lib import program
    program.import_program()
    dev = device_info(cell["chips"], require_chip)
    import jax
    log(f"device {dev}; compile cache {enable_compile_cache(root)}")
    clock = CompileClock()
    program.set_tuning_cache(root / ".cache" / "bench" / "tuning.json")

    from bench.lib import check, model, traffic
    from bench.lib.replay import Record, Replay, no_annotation
    shape = model.load_shape(root, spec["config_file"], cell["config"])
    weights = model.make_weights(shape, seed)
    engine = program.build_engine(shape, weights, mix["engine"], seed,
                                  **(overrides or {}))
    if fault is not None:
        fault(engine)
    replay = Replay(engine, jax.profiler.TraceAnnotation if trace
                    else no_annotation)
    replay.warm(warm_records(mix, shape.vocab))
    requests = traffic.generate(mix, seed, seconds, shape.vocab)
    records = [Record(r) for r in requests]
    if mix["arrivals"].get("fill_before_window"):
        head, records_in = records[:mix["engine"]["slots"]], \
            records[mix["engine"]["slots"]:]
        replay.fill(head)
    else:
        head, records_in = [], records
    trace_dir = root / ".cache" / "bench" / "trace"
    compiled_before = []

    def on_open():
        jax.block_until_ready(engine.caches)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        compiled_before[:] = clock.snapshot()

    run = replay.window(records_in, seconds,
                        drain_s=mix["check"].get("drain_s", 0),
                        on_open=on_open)
    if trace:
        jax.profiler.stop_trace()
    run.records = head + run.records
    setup_s = run.opened - T_START + AGE_AT_START
    compiled_in = [a - b for a, b in zip(clock.snapshot(), compiled_before)]
    log(f"set-up {setup_s:.3f} s; compile {compiled_before[0]:.3f} s "
        f"({compiled_before[1]} cache hits, {compiled_before[2]} misses); "
        f"in the window: {compiled_in[2]} misses, "
        f"{compiled_in[0]:.3f} s compiling")
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    attempted, failed = attempted_failed(run, mix)

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        from bench.lib import layers, xtrace
        reduced = xtrace.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev["busy_s"], dev["window_s"] = reduced.busy_s, reduced.window_s
        ctx = layers.Context(run=run, shape=shape, mix=mix, trace=reduced,
                             device_kind=dev["kind"])
        result["metrics"] = layers.read_all(root, spec["per_layer"], ctx)
        result["breakdown"] = reduced.breakdown()
    else:
        result["metrics"] = end_to_end(run, spec["end_to_end"], setup_s)
    result["device"] = dev
    for name, at in (("backlog_at_open", 0.0), ("backlog_at_close", seconds)):
        result[name] = sum(1 for r in run.records if r.req.due_s <= at
                           and not (r.times and r.times[0] <= at))

    # The output check runs with the program's state freed, so that the
    # reference neither sets the memory peak nor meets the engine's
    # arrays; it draws the weights again from the seed.
    engine.close()
    del engine, replay, weights
    gc.collect()
    limits = check.load_limits(root, workload)
    picked = check.sample(run, mix["check"]["requests"], seed)
    faults = check.structural(run.records, shape.vocab)
    t_check = time.perf_counter()
    ref_weights = model.make_weights(shape, seed)
    jax.block_until_ready(ref_weights)
    t_weights = time.perf_counter()
    got = check.gaps(shape, ref_weights, check.served_rows(picked),
                     mix["engine"]["max_len"], control)
    read = check.readings(got)
    served = sum(len(r.tokens) for r in picked)
    log(f"output check: {len(picked)} requests, {served} served tokens, "
        f"{time.perf_counter() - t_check:.3f} s (weights "
        f"{t_weights - t_check:.3f} s); widest gap "
        f"{read['widest_gap']!r}, flip share {read['flip_share']!r}")
    result["readings"] = read
    lim = limits["mean_gap"]["limit"]
    numbers = {"mean_gap": {"value": read["mean_gap"], "limit": lim},
               "served_tokens_compared": {"value": served,
                                          "limit": limits["min_tokens"]}}
    for f in faults:
        log(f"fault: {f}")
    numbers["structural_faults"] = {"value": len(faults), "limit": 0}
    result["correct"] = bool(
        read["mean_gap"] <= lim and not faults
        and served >= numbers["served_tokens_compared"]["limit"])
    for name, n in numbers.items():
        rel = ">=" if name == "served_tokens_compared" else "<="
        log(f"check {name} = {n['value']!r} (limit {rel} {n['limit']!r})")
    result["check"] = numbers
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a non-negative whole number")
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
