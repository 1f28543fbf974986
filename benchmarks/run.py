"""Benchmark harness — one function per paper table/figure, organized in
the paper's three evaluation levels.

Prints ``name,us_per_call,derived`` CSV rows.  ``--level`` selects the
scaling level, mirroring how GAMA evaluates single AIE -> pack -> array:

* ``single`` (default): the paper-table benches (Tables II-VI, Figs.
  6/7 from the analytic chain) plus the single-kernel Pallas/planner/
  tuning benches — everything that runs on one device;
* ``pack``: pack-level sharded GEMM (``distributed.pack_gemm``) on a
  simulated 8-device mesh — the three reduce schedules side by side
  (sequential staggered ring, psum baseline, K-streamed overlap;
  select with ``--reduce {ring,psum,overlap,all}``) and (P, Q) grid
  variants — plus the tuning pass that measures and caches the pack
  grid, the flash-decode split-K block and the WKV chunk;
* ``array``: the full-mesh level — packs composed over the data axis
  (``array_gemm``) and a small model served with its lm-head/ffn GEMMs
  sharded through packs;
* ``serve``: the serving level — continuous batching (slot-based KV
  cache + mid-decode admission) vs serialized one-shot batches vs the
  paged-KV engine (kvpool page pool, bit-identity checked against the
  dense run) on the same ragged staggered-arrival trace, reporting
  tokens/s, p50/p99 per-token latency and the KV footprint of the
  layout that actually ran (dense reservation vs live page high-water
  mark), the prefix-cache row on the committed shared-prompt trace
  (``serve.prefix.s4``: bit-identity vs uncached for f32/int8 pages,
  hit rate, <= 0.6x page high-water), plus the schema-v8 ``serve``
  tuning pass (batch_slots x page_size x kv_dtype x prefill_chunk x
  prefix_cache).

Run: PYTHONPATH=src python -m benchmarks.run
                              [--level single|pack|array|serve]
                                             [--filter substr]
                                             [--reduce ring|psum|overlap|all]
                                             [--json BENCH_out.json]

``--json`` additionally writes the rows as machine-readable JSON
(``{"schema": 1, "level": L, "rows": [{name, us_per_call, derived},
...]}``) so the perf trajectory can be tracked across commits (e.g.
``BENCH_pack.json``).  The pack/array levels set
``--xla_force_host_platform_device_count=8`` before jax initializes
(unless XLA_FLAGS is already set), so they run anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Callable, Dict, List, Tuple

import numpy as np


def timed(fn: Callable, reps: int = 3) -> Tuple[float, object]:
    fn()  # warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    us = (time.perf_counter() - t0) / reps * 1e6
    return us, out


ROWS: List[Dict[str, object]] = []

# Deterministic quality figures (miss rates, footprint ratios — scalars
# where *growth* is a regression, unlike the noisy timed rows).  --json
# writes them as a schema-1 metrics snapshot next to the rows file so
# ``tools/bench_compare.py --metrics`` can gate them directly.
GAUGES: Dict[str, float] = {}


def emit(name: str, us: float, derived: str) -> None:
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})
    print(f"{name},{us:.1f},{derived}")


def emit_gauge(name: str, value: float) -> None:
    GAUGES[name] = float(value)
    print(f"# gauge {name}={value:.4f}")


def _gemm_eff(m: int, k: int, n: int, us: float,
              dtype: str = "float32") -> str:
    """``eff=`` column: achieved GEMM FLOP/s over the published peak of
    the chip the bench ran on (repro.obs.efficiency) — the paper's
    %-of-peak figure.  ``eff=not_measured`` off an accelerator: a CPU
    timing has no device peak to be a share of."""
    from repro.obs.efficiency import device_kind, gemm_efficiency
    if device_kind() is None:
        return "eff=not_measured"
    return f"eff={gemm_efficiency(m, k, n, us, dtype):.2e}"


def _serve_eff(cfg, tok_s: float) -> str:
    """``eff=`` column of a serve row (see :func:`_gemm_eff`)."""
    from repro.obs.efficiency import device_kind, serve_efficiency
    if device_kind() is None:
        return "eff=not_measured"
    return f"eff={serve_efficiency(cfg, tok_s):.2e}"


# ---------------------------------------------------------------------------
# Paper tables
# ---------------------------------------------------------------------------


def bench_table2() -> None:
    from repro.core.paper_tables import table2, table2_search
    us, rows = timed(table2)
    for r in rows:
        emit(f"table2.{r['precision']}", us / len(rows),
             f"gamma={r['gamma']:.2f}(paper {r['paper_gamma']}) "
             f"mem={r['mem_bytes']}(paper {r['paper_mem_bytes']}) "
             f"util={r['mem_util']*100:.0f}%")
    us, rows = timed(table2_search)
    for r in rows:
        emit(f"table2.search.{r['precision']}", us / len(rows),
             f"found=({r['search_m']}x{r['search_k']}x{r['search_n']}) "
             f"paper=({r['paper_m']}x{r['paper_k']}x{r['paper_n']}) "
             f"match={r['match']}")


def bench_table3() -> None:
    from repro.core.paper_tables import table3
    us, rows = timed(table3)
    for r in rows:
        emit(f"table3.{r['precision']}", us / len(rows),
             f"kcc_addr={r['kcc_address']:.0f}(paper {r['paper_address']}) "
             f"kcc_loc={r['kcc_location']:.0f}(paper {r['paper_location']}) "
             f"recovered={r['recovered_pp']:.1f}pp")


def bench_table4() -> None:
    from repro.core.paper_tables import table4
    us, rows = timed(table4)
    for r in rows:
        emit(f"table4.{r['precision']}", us / len(rows),
             f"pack_kcc_addr={r['pack_kcc_address']:.0f}"
             f"(paper {r['paper_address']}) "
             f"cascade_stall={r['cascade_stall']*100:.1f}%")


def bench_fig6() -> None:
    from repro.core.aiesim import best_pack_size, fig6_curve
    us, rows = timed(lambda: fig6_curve("int8-int8"))
    g = best_pack_size("int8-int8")
    window = [r["g"] for r in rows if r["scalable"]]
    emit("fig6.int8-int8", us,
         f"best_pack={g}(paper 4) window=[{min(window)}..{max(window)}]"
         f"(paper [3..10])")


def bench_table5() -> None:
    from repro.core.paper_tables import table5
    us, rows = timed(table5)
    for r in rows:
        emit(f"table5.{r['precision']}", us / len(rows),
             f"thpt={r['throughput_tops']:.1f}T(paper {r['paper_tops']}) "
             f"TE={r['te']*100:.1f}%(paper {r['paper_te']*100:.0f}%) "
             f"Y={r['y']} G={r['g']} X={r['x']} engines={r['engines']}")


def bench_table6() -> None:
    from repro.core.paper_tables import table6
    us, rows = timed(table6)
    for r in rows:
        if r["paper_improvement_pp"] is None:
            continue
        emit(f"table6.{r['precision']}.vs_{r['prior_work']}", us / len(rows),
             f"improvement={r['improvement_pp']:.1f}pp"
             f"(paper {r['paper_improvement_pp']}pp)")


def bench_fig7() -> None:
    from repro.core.paper_tables import staggered_placement
    us, rows = timed(staggered_placement)
    chosen = next(r for r in rows if r["chosen"])
    emit("fig7.staggered", us,
         f"skew={chosen['skew']}(paper 2) "
         f"util={chosen['utilization']*100:.1f}%(paper 94.7%)")


# ---------------------------------------------------------------------------
# TPU-side: planner + kernels
# ---------------------------------------------------------------------------


def bench_tpu_planner() -> None:
    from repro.core import hw, planner
    from repro.core.tile_search import search_tpu_tiles

    def plan():
        return search_tpu_tiles(65536, 7168, 16384, hw.BF16_BF16)
    us, p = timed(plan)
    emit("tpu.tile_search", us,
         f"tile=({p.tm}x{p.tk}x{p.tn}) vmem={p.vmem_bytes/2**20:.1f}MiB "
         f"gamma={p.gamma:.2f}")

    site = planner.GemmSite("ffn", m=65536, k=7168, n=16384)
    us, choices = timed(lambda: planner.plan_cascade(site, 16, 16))
    best = min(choices, key=lambda c: c.step_s)
    emit("tpu.cascade_sweep", us,
         f"best_G={best.g} X={best.x} step={best.step_s*1e3:.2f}ms "
         f"gamma={best.gamma:.2f}")


def bench_kernels() -> None:
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)

    us, out = timed(lambda: np.asarray(
        ops.matmul(a, b, mode="kernel")), reps=2)
    err = float(np.max(np.abs(out - np.asarray(ref.ref_gemm(a, b)))))
    emit("kernel.gama_gemm.f32.256x512x256", us,
         f"interpret_maxerr={err:.2e} {_gemm_eff(256, 512, 256, us)}")

    ai = jnp.asarray(rng.integers(-128, 128, size=(128, 256)), jnp.int8)
    bi = jnp.asarray(rng.integers(-128, 128, size=(256, 128)), jnp.int8)
    us, out = timed(lambda: np.asarray(
        ops.matmul(ai, bi, out_dtype=jnp.int8, scale=0.002,
                   mode="kernel")), reps=2)
    exact = bool((out == np.asarray(ref.ref_gemm(
        ai, bi, out_dtype=jnp.int8, scale=0.002))).all())
    emit("kernel.gama_gemm.int8toint8.128x256x128", us,
         f"exact={exact} {_gemm_eff(128, 256, 128, us, 'int8')}")

    q = jnp.asarray(rng.normal(size=(1, 4, 128, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.float32)
    us, out = timed(lambda: np.asarray(
        ops.attention(q, k, v, bq=64, bk=64, mode="kernel")), reps=1)
    err = float(np.max(np.abs(out - np.asarray(ref.ref_attention(q, k, v)))))
    emit("kernel.flash_attention.gqa4to2.128", us, f"maxerr={err:.2e}")


def bench_roofline_summary() -> None:
    """Aggregate the dry-run records (if present) — deliverable (g)."""
    import glob
    import json
    files = sorted(glob.glob("experiments/dryrun/*.json"))
    if not files:
        emit("roofline.summary", 0.0, "no dry-run records found")
        return
    from repro.analysis.report import enrich, load_records
    us, recs = timed(lambda: [enrich(r) for r in load_records()], reps=1)
    doms = {}
    for r in recs:
        doms[r["terms"]["dominant"]] = doms.get(r["terms"]["dominant"], 0) + 1
    emit("roofline.summary", us,
         f"cells={len(recs)} dominant_counts={doms}")


def bench_tuning_dispatch() -> None:
    """Hot-path cost of the autotuner's dispatch (must be ~dict lookup)."""
    import jax.numpy as jnp
    from repro.tuning import dispatch

    dispatch.reset()
    us_cold, cfg = timed(
        lambda: dispatch.gemm_config(4096, 4096, 4096, jnp.bfloat16), reps=1)
    us_hot, _ = timed(
        lambda: dispatch.gemm_config(4096, 4096, 4096, jnp.bfloat16),
        reps=100)
    emit("tuning.dispatch.gemm", us_hot,
         f"cold={us_cold:.0f}us hot={us_hot:.2f}us source={cfg.source} "
         f"tile=({cfg.tm}x{cfg.tk}x{cfg.tn},{cfg.order})")


# ---------------------------------------------------------------------------
# Pack level: sharded GEMM over a simulated multi-device mesh
# ---------------------------------------------------------------------------


def _pack_mesh(data: int, model: int):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# Reduce schedules selectable with --reduce; "all" runs them side by
# side (the ring-vs-psum-vs-overlap A/B the paper's cascade motivates).
PACK_SCHEDULES = {
    "ring": dict(stagger=1, reduce="ring", overlap=False),
    "psum": dict(stagger=0, reduce="psum", overlap=False),
    "overlap": dict(stagger=1, reduce="ring", overlap=True),
}
_PACK_REDUCE = "all"


def _selected_schedules():
    return [(name, kw) for name, kw in PACK_SCHEDULES.items()
            if _PACK_REDUCE in ("all", name)]


def _best_of(fn: Callable, reps: int = 7, warmup: int = 2) -> float:
    """Best-of-N microseconds per call.  Collective benches run on a
    shared (often oversubscribed) host where slow outliers are pure
    scheduler noise; the minimum is the stable schedule comparison."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_pack_gemm() -> None:
    """Pack-level sweep: the sequential staggered ring, the psum
    baseline and the K-streamed overlap schedule side by side on one
    (P, Q) grid — jit-compiled, so the rows compare steady-state
    execution (what the deployed serving path runs) — plus a (P, Q)
    grid sweep.  Numerics vs the reference GEMM (the schedules only
    reorder the associative accumulation)."""
    import jax
    import jax.numpy as jnp

    import repro.distributed.pack_gemm as pg
    from repro.kernels import ref
    mesh = _pack_mesh(1, 8)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(384, 3072)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(3072, 384)), jnp.float32)
    want = np.asarray(ref.ref_gemm(a, b))
    # Compile all selected schedules, then time them *interleaved*
    # (round-robin, best-of): scheduler noise on a shared host hits
    # every schedule alike instead of whichever ran during a spike.
    fns, errs = {}, {}
    for name, kw in _selected_schedules():
        fn = jax.jit(lambda x, y, kw=dict(kw): pg.pack_gemm(
            x, y, mesh, p=2, q=4, **kw))
        out = np.asarray(fn(a, b))
        np.asarray(fn(a, b))
        errs[name] = float(np.max(np.abs(out - want)))
        fns[name] = fn
    best = {name: float("inf") for name in fns}
    for _ in range(10):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            np.asarray(fn(a, b))
            best[name] = min(best[name],
                             (time.perf_counter() - t0) * 1e6)
    for name in fns:
        vs = (f" vs_ring={best['ring'] / best[name]:.2f}x"
              if name == "overlap" and "ring" in best else "")
        emit(f"pack.gemm.p2q4.{name}", best[name],
             f"maxerr={errs[name]:.2e}{vs} "
             f"{_gemm_eff(384, 3072, 384, best[name])}")
    # Grid sweep under the first selected schedule (p=1 has no reduce).
    sweep_name, sweep_kw = _selected_schedules()[0]
    for (p, q) in [(1, 8), (4, 2), (8, 1)]:
        kw = dict(sweep_kw) if p > 1 else dict(stagger=0, reduce="psum",
                                               overlap=False)
        fn = jax.jit(lambda x, y, p=p, q=q, kw=kw: pg.pack_gemm(
            x, y, mesh, p=p, q=q, **kw))
        out = np.asarray(fn(a, b))
        us = _best_of(lambda: np.asarray(fn(a, b)), reps=3)
        err = float(np.max(np.abs(out - want)))
        emit(f"pack.gemm.p{p}q{q}.{sweep_name if p > 1 else 'psum'}", us,
             f"maxerr={err:.2e} {_gemm_eff(384, 3072, 384, us)}")


def bench_pack_tuning() -> None:
    """Measured pack-grid tuning on the live mesh, plus the decode bk
    and WKV chunk tunables — populates the persistent cache.  The tuned
    GEMM is compute-bound, so the analytic prior ranks the K-streamed
    overlap schedule into the measured survivors (schema v3)."""
    from repro.tuning import dispatch

    # warmup=1 is load-bearing: time_pack jit-compiles each candidate,
    # and the warmup call pays the compile so the measured rep is
    # steady-state execution, not trace+compile time.
    res = dispatch.tune_pack(512, 2048, 512, "float32", data_axis=1,
                             model_axis=8, keep=4, warmup=1, reps=1)
    n_overlap = sum(1 for t in res.trials
                    if t.get("config", {}).get("overlap"))
    emit("pack.tune.pack_grid", res.best_us or 0.0,
         f"best={res.best} measured={len(res.trials)} "
         f"overlap_measured={n_overlap} hit={res.cache_hit}")
    res = dispatch.tune_decode(512, 64, "float32", keep=3, warmup=0,
                               reps=1)
    emit("pack.tune.flash_decode_bk", res.best_us or 0.0,
         f"best={res.best} hit={res.cache_hit}")
    res = dispatch.tune_wkv(256, 32, "float32", keep=3, warmup=0, reps=1)
    emit("pack.tune.wkv_chunk", res.best_us or 0.0,
         f"best={res.best} hit={res.cache_hit}")
    from repro.tuning.cache import default_cache_path
    emit("pack.tune.cache", 0.0,
         f"entries={len(dispatch.get_cache().entries)} "
         f"path={default_cache_path()}")


# ---------------------------------------------------------------------------
# Serve level: continuous batching vs serialized one-shot batches
# ---------------------------------------------------------------------------


def _serve_trace(vocab: int):
    """Ragged staggered trace: 8 requests, 4 slots, mixed max_new.  The
    raggedness is the point — a one-shot batch decodes until its longest
    member finishes (finished rows idle), continuous batching refills
    the slot immediately."""
    from repro.launch.serve import synth_trace
    ragged_new = [4, 18, 6, 16, 4, 14, 6, 12]
    trace = synth_trace(len(ragged_new), 12, 0, 1, vocab, seed=0)
    for t, mn in zip(trace, ragged_new):
        t["max_new"] = mn
    return trace


def bench_serve_trace() -> None:
    """Continuous batching vs serialized one-shot batches on the same
    ragged staggered-arrival trace: tokens/s and p50/p99 per-token
    latency (us_per_call is per *generated token*).  Both run jitted
    and pre-compiled (first replay pays compile), so the rows compare
    steady-state scheduling, not trace time.

    KV memory is reported per layout: the dense rows carry the
    ``slots x max_len`` reservation, the paged row the **live**
    high-water mark (``pages_in_use x page_bytes``) — previously the
    serve level re-reported the dense reservation regardless of the
    layout that actually ran."""
    import jax

    from repro import configs as C
    from repro.launch.serve import run_trace
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, ServeEngine
    cfg = C.get_smoke("smollm_360m")
    params = init_params(jax.random.PRNGKey(1), cfg)
    trace = _serve_trace(cfg.vocab_size)
    slots = 4
    max_len = max(len(t["prompt"]) + t["max_new"] for t in trace) + 8
    useful = sum(t["max_new"] for t in trace)
    engine = ServeEngine(cfg, params, ServeConfig(batch_slots=slots,
                                                  max_len=max_len))
    try:
        run_trace(engine, trace, log=None)          # compile warmup
        rep = run_trace(engine, trace, log=None)
        kv_kib = engine.kv_bytes_reserved() / 1024
        emit("serve.continuous.s4", rep["wall_s"] * 1e6 / rep["tokens"],
             f"tok_s={rep['tok_s']:.1f} p50={rep['p50_ms']:.2f}ms "
             f"p99={rep['p99_ms']:.2f}ms shared_steps={rep['shared_steps']} "
             f"decode_steps={rep['decode_steps']} kv_kib={kv_kib:.0f} "
             f"{_serve_eff(cfg, rep['tok_s'])}")
        # Serialized baseline: same engine, same requests, grouped into
        # uniform one-shot batches (arrivals ignored — the baseline gets
        # every benefit of the doubt); each batch decodes to its longest
        # member, so finished rows burn slots.
        batches = [trace[i:i + slots] for i in range(0, len(trace), slots)]
        t0 = time.perf_counter()
        for group in batches:
            prompts = np.stack([g["prompt"] for g in group])
            engine.generate(prompts, max(g["max_new"] for g in group))
        wall = time.perf_counter() - t0
        ratio = (useful / wall) / rep["tok_s"]
        emit("serve.serialized.s4", wall * 1e6 / useful,
             f"tok_s={useful / wall:.1f} batches={len(batches)} "
             f"vs_continuous={ratio:.2f}x kv_kib={kv_kib:.0f}")
    finally:
        engine.close()
    # Paged engine on the same trace: same scheduling, KV bound to live
    # tokens through the kvpool block tables (greedy decode, so the
    # token streams are bit-identical to the dense run's).
    paged = ServeEngine(cfg, params, ServeConfig(
        batch_slots=slots, max_len=max_len, kv="paged", page_size=16))
    try:
        run_trace(paged, trace, log=None)           # compile warmup
        prep = run_trace(paged, trace, log=None)
        for tid, toks in rep["results"].items():
            np.testing.assert_array_equal(
                toks, prep["results"][tid],
                err_msg=f"paged diverged from dense (trace id {tid})")
        hwm_kib = prep["kv_bytes_hwm"] / 1024
        emit("serve.paged.s4", prep["wall_s"] * 1e6 / prep["tokens"],
             f"tok_s={prep['tok_s']:.1f} p50={prep['p50_ms']:.2f}ms "
             f"p99={prep['p99_ms']:.2f}ms page=16 "
             f"pages_hwm={prep['pages_hwm']} "
             f"reclaimed={prep['pages_reclaimed']} "
             f"kv_hwm_kib={hwm_kib:.0f} "
             f"dense_kib={kv_kib:.0f} "
             f"{_serve_eff(cfg, prep['tok_s'])}")
    finally:
        paged.close()
    # int8 KV pages on the same trace: per-page-row scales shrink each
    # cached token to d_head + 4 bytes (vs d_head * 4 in f32), so the
    # live high-water must come in well under half the f32 paged run's.
    # Quantization noise can flip a greedy near-tie, so the token
    # streams are held to *completion + majority bit-identity* vs the
    # f32 paged run, not exact equality (the tolerance story lives in
    # tests/test_quant.py).
    qpaged = ServeEngine(cfg, params, ServeConfig(
        batch_slots=slots, max_len=max_len, kv="paged", page_size=16,
        kv_dtype="int8"))
    try:
        run_trace(qpaged, trace, log=None)          # compile warmup
        qrep = run_trace(qpaged, trace, log=None)
        assert set(qrep["results"]) == set(prep["results"])
        for t in trace:
            assert len(qrep["results"][t["id"]]) == t["max_new"], t["id"]
        same = sum(bool(np.array_equal(qrep["results"][tid], toks))
                   for tid, toks in prep["results"].items())
        assert same >= len(trace) - 2, \
            f"int8 KV flipped {len(trace) - same}/{len(trace)} streams"
        q_hwm_kib = qrep["kv_bytes_hwm"] / 1024
        assert qrep["kv_bytes_hwm"] <= 0.5 * prep["kv_bytes_hwm"], \
            (qrep["kv_bytes_hwm"], prep["kv_bytes_hwm"])
        emit("serve.paged_int8.s4", qrep["wall_s"] * 1e6 / qrep["tokens"],
             f"tok_s={qrep['tok_s']:.1f} p50={qrep['p50_ms']:.2f}ms "
             f"p99={qrep['p99_ms']:.2f}ms page=16 kv_dtype=int8 "
             f"pages_hwm={qrep['pages_hwm']} "
             f"kv_hwm_kib={q_hwm_kib:.0f} "
             f"f32_hwm_kib={hwm_kib:.0f} "
             f"identical_streams={same}/{len(trace)} "
             f"{_serve_eff(cfg, qrep['tok_s'])}")
    finally:
        qpaged.close()
    # Chunked prefill on the same trace (page-aligned 16-token chunks
    # interleaved with in-flight decode under a token budget): the
    # token streams must stay bit-identical to the monolithic dense
    # run — chunking only changes *when* prompt KV is written, never
    # what attention over it computes.
    chunked = ServeEngine(cfg, params, ServeConfig(
        batch_slots=slots, max_len=max_len, kv="paged", page_size=16,
        prefill_chunk=16, token_budget=slots + 16))
    try:
        run_trace(chunked, trace, log=None)         # compile warmup
        crep = run_trace(chunked, trace, log=None)
        for tid, toks in rep["results"].items():
            np.testing.assert_array_equal(
                toks, crep["results"][tid],
                err_msg=f"chunked diverged from monolithic (id {tid})")
        emit("serve.chunked.s4", crep["wall_s"] * 1e6 / crep["tokens"],
             f"tok_s={crep['tok_s']:.1f} p50={crep['p50_ms']:.2f}ms "
             f"p99={crep['p99_ms']:.2f}ms chunk=16 "
             f"budget={slots + 16} chunks={crep['prefill_chunks']} "
             f"mono_p99={prep['p99_ms']:.2f}ms "
             f"{_serve_eff(cfg, crep['tok_s'])}")
    finally:
        chunked.close()
    # Prefix caching on the committed shared-system-prompt trace
    # (shared16.jsonl — 16 requests over 4 seeded system prompts): the
    # cached run must be greedy-bit-identical to the uncached paged run
    # (f32 *and* int8 pages) while the live-page high-water comes in at
    # <= 0.6x — pool bytes multiplied by sharing, not by capacity.  The
    # miss-rate and hwm-ratio figures are deterministic (seeded trace,
    # greedy decode), so they export as gauges the --metrics gate holds
    # to ~1.0x run over run.
    from repro.launch.serve import load_trace, resolve_trace_path
    strace = load_trace(resolve_trace_path("shared16"), cfg.vocab_size)
    smax_len = max(len(t["prompt"]) + t["max_new"] for t in strace) + 8
    srep = {}
    for kv_dtype in (None, "int8"):
        runs = {}
        for cached in (False, True):
            eng = ServeEngine(cfg, params, ServeConfig(
                batch_slots=slots, max_len=smax_len, kv="paged",
                page_size=16, kv_dtype=kv_dtype, prefix_cache=cached))
            try:
                run_trace(eng, strace, log=None)    # compile warmup
                r = run_trace(eng, strace, log=None)
                r["pages_hwm"] = eng.pool.high_water
                runs[cached] = r
            finally:
                eng.close()
        for tid, toks in runs[False]["results"].items():
            np.testing.assert_array_equal(
                toks, runs[True]["results"][tid],
                err_msg=f"prefix-cached diverged from uncached "
                        f"(kv_dtype={kv_dtype}, trace id {tid})")
        ratio = runs[True]["pages_hwm"] / runs[False]["pages_hwm"]
        assert ratio <= 0.6, \
            (f"prefix sharing saved too little: pages_hwm "
             f"{runs[True]['pages_hwm']} vs {runs[False]['pages_hwm']} "
             f"uncached (kv_dtype={kv_dtype})")
        assert runs[True]["prefix_hit_rate"] > 0, "no prefix hits"
        srep[kv_dtype] = runs
    f32c, f32u = srep[None][True], srep[None][False]
    emit("serve.prefix.s4", f32c["wall_s"] * 1e6 / f32c["tokens"],
         f"tok_s={f32c['tok_s']:.1f} trace=shared16 page=16 "
         f"hit_rate={f32c['prefix_hit_rate']:.2f} "
         f"pages_hwm={f32c['pages_hwm']} "
         f"uncached_hwm={f32u['pages_hwm']} "
         f"cow={f32c['cow_copies']} "
         f"int8_identical=yes "
         f"{_serve_eff(cfg, f32c['tok_s'])}")
    emit_gauge("serve.prefix.miss_rate", 1.0 - f32c["prefix_hit_rate"])
    emit_gauge("serve.prefix.pages_hwm_ratio",
               f32c["pages_hwm"] / f32u["pages_hwm"])


def bench_serve_tuning() -> None:
    """The schema-v8 serve tunable: measure (batch_slots, page_size,
    kv_dtype, prefill_chunk, prefix_cache) candidates end to end —
    dense, paged, int8-paged, chunked-prefill and prefix-cached
    variants compete on the same shared-prefix trace — and persist the
    winner."""
    from repro import configs as C
    from repro.tuning import dispatch
    cfg = C.get_smoke("smollm_360m")
    res = dispatch.tune_serve(cfg, max_len=32, prompt_len=8, max_new=6,
                              requests=6, keep=2, warmup=0, reps=1)
    emit("serve.tune.batch_slots", res.best_us or 0.0,
         f"best={res.best} measured={len(res.trials)} hit={res.cache_hit}")


# ---------------------------------------------------------------------------
# Array level: packs composed over the data axis (the full mesh)
# ---------------------------------------------------------------------------


def bench_array_gemm() -> None:
    """Full-mesh collective matmul: M over data, (P, Q) over model —
    jit-compiled, overlapped schedule wherever there is a reduce."""
    import jax
    import jax.numpy as jnp

    import repro.distributed.pack_gemm as pg
    from repro.kernels import ref
    mesh = _pack_mesh(2, 4)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    want = np.asarray(ref.ref_gemm(a, b))
    for (p, q) in [(1, 4), (2, 2), (4, 1)]:
        fn = jax.jit(lambda x, y, p=p, q=q: pg.array_gemm(
            x, y, mesh, p=p, q=q, stagger=1,
            reduce="ring" if p > 1 else "psum", overlap=p > 1))
        out = np.asarray(fn(a, b))
        us = _best_of(lambda: np.asarray(fn(a, b)), reps=3, warmup=1)
        err = float(np.max(np.abs(out - want)))
        emit(f"array.gemm.2x4.p{p}q{q}", us,
             f"maxerr={err:.2e} {_gemm_eff(256, 256, 128, us)}")


def bench_array_serve() -> None:
    """A small model served with its lm-head/ffn GEMMs sharded through
    packs (ServeConfig.pack_mesh) — the array level end to end."""
    import jax

    from repro.models import ModelConfig, init_params
    from repro.serving.engine import ServeConfig, ServeEngine
    cfg = ModelConfig(name="bench", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_head=16, d_ff=128, vocab_size=256,
                      compute_dtype="float32", cache_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = _pack_mesh(2, 4)
    scfg = ServeConfig(batch_slots=4, max_len=64, pack_mesh=mesh,
                       pack_min_flops=4e6)
    engine = ServeEngine(cfg, params, scfg)
    try:
        prompts = np.random.default_rng(0).integers(
            0, 256, size=(4, 16)).astype(np.int32)
        max_new = 4
        us, out = timed(lambda: engine.generate(prompts, max_new), reps=1)
        toks_s = 4 * max_new / (us / 1e6)
        emit("array.serve.packed", us,
             f"packed_gemms={engine.packed_gemms} "
             f"tok_s={toks_s:.1f} out_shape={out.shape}")
    finally:
        engine.close()


BENCHES = [
    ("table2", bench_table2),
    ("table3", bench_table3),
    ("table4", bench_table4),
    ("fig6", bench_fig6),
    ("table5", bench_table5),
    ("table6", bench_table6),
    ("fig7", bench_fig7),
    ("tpu_planner", bench_tpu_planner),
    ("kernels", bench_kernels),
    ("tuning", bench_tuning_dispatch),
    ("roofline", bench_roofline_summary),
]

PACK_BENCHES = [
    ("pack_gemm", bench_pack_gemm),
    ("pack_tuning", bench_pack_tuning),
]

ARRAY_BENCHES = [
    ("array_gemm", bench_array_gemm),
    ("array_serve", bench_array_serve),
]

SERVE_BENCHES = [
    ("serve_trace", bench_serve_trace),
    ("serve_tuning", bench_serve_tuning),
]

LEVELS = {"single": BENCHES, "pack": PACK_BENCHES, "array": ARRAY_BENCHES,
          "serve": SERVE_BENCHES}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--level", choices=sorted(LEVELS), default="single",
                    help="evaluation level: single kernel, pack, "
                         "full-array, or serving (pack/array simulate "
                         "an 8-device CPU mesh)")
    ap.add_argument("--filter", type=str, default="")
    ap.add_argument("--reduce", choices=("ring", "psum", "overlap", "all"),
                    default="all",
                    help="pack-level reduce schedule(s) to bench: the "
                         "sequential staggered ring, the psum baseline, "
                         "the K-streamed overlap, or all side by side")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="also write rows as JSON (e.g. BENCH_tpu.json)")
    args = ap.parse_args()
    global _PACK_REDUCE
    _PACK_REDUCE = args.reduce
    if args.level in ("pack", "array"):
        # Must precede any jax initialization (no bench imported jax
        # yet).  Append to any preexisting XLA_FLAGS; an explicit
        # device-count flag from the caller wins.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}")
    print("name,us_per_call,derived")
    for name, fn in LEVELS[args.level]:
        if args.filter and args.filter not in name:
            continue
        fn()
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "level": args.level, "rows": ROWS},
                      f, indent=1)
        print(f"# wrote {len(ROWS)} rows to {args.json}")
        if GAUGES:
            # Deterministic quality figures as a schema-1 metrics
            # snapshot (see repro.obs.export) so bench_compare.py
            # --metrics gates them at ~1.0x, unlike the noisy rows.
            mpath = os.path.splitext(args.json)[0] + "_metrics.json"
            snap = {"schema": 1, "counters": {},
                    "gauges": {k: {"value": v, "high_water": v}
                               for k, v in GAUGES.items()},
                    "histograms": {}}
            with open(mpath, "w") as f:
                json.dump(snap, f, indent=1)
            print(f"# wrote {len(GAUGES)} gauges to {mpath}")


if __name__ == "__main__":
    main()
