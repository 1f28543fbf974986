"""Serving launcher: continuous batching over a request-trace workload.

Replays a trace of requests through the continuous-batching
``ServeEngine``: requests are admitted into free KV slots mid-decode and
share decode steps with older in-flight requests.  Two replay modes:

* **step-indexed** (default): arrivals are measured in engine steps
  (``arrival``), every request is submitted up front and the scheduler
  releases them as the step counter passes — fully deterministic.
* **wall-clock**: arrivals are seconds (``arrival_s``); the launcher
  submits each request the moment the clock reaches it, as a real
  serving frontend would.  Selected automatically when the trace
  carries ``arrival_s``, or by synthesizing bursty arrivals with
  ``--arrivals {uniform,poisson,pareto}`` (seedable; ``--rate`` sets
  the mean request rate).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m \
        --requests 6 --prompt_len 12 --max_new 16 --stagger 3

    # bursty wall-clock replay, chunked prefill, latency-aware policy
    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m \
        --arrivals pareto --rate 16 --prefill-chunk 16 --policy latency

Trace file (``--trace``, JSON lines; see docs/SERVING.md)::

    {"id": 0, "arrival": 0, "prompt_len": 12, "max_new": 16}
    {"id": 1, "arrival_s": 0.25, "prompt": [17, 3, 99], "max_new": 8}

``prompt`` gives explicit token ids; ``prompt_len`` asks the launcher to
synthesize that many random tokens.  ``cancel_after: N`` cancels the
request after its Nth streamed token (``engine.cancel`` frees its slot
and pages the same step).  ``--verify`` re-runs every completed request
through a one-slot one-shot ``generate()`` with the same KV layout and
holds the continuous run to it (with ``--prefill-chunk`` this is the
chunked-vs-monolithic check too): every logits row both runs computed
from the same inputs must agree within ``VERIFY_TOL`` row standard
deviations, so greedy tokens may part only at a near-tie.  ``--kv paged`` serves through the
``repro.serving.kvpool`` page pool (``--page_size``/``--pool_pages``)
and logs page-reclaim/preemption events plus the pool high-water mark;
``--kv-dtype int8`` stores the pages quantized (per-row scales,
dequantized inside the fused decode kernel) at roughly a third of the
f32 KV bytes.  ``--prefix-cache`` (paged only) shares page-aligned
prompt prefixes across requests through a refcounted radix tree with
copy-on-write — ``--arrivals shared`` synthesizes the matching
shared-system-prompt workload (``--groups`` distinct system prompts,
group-blocked step arrivals; the committed ``shared16.jsonl`` trace) —
bit-identical to uncached runs, with the pool high-water dropping by
roughly the shared fraction.  ``--prefill-chunk N`` splits each
admitted prompt into
N-token chunks interleaved with in-flight decode (0 = monolithic,
-1 = ask the tuner); ``--token-budget``/``--policy`` control the
unified step loop's budget and admission policy.
``--mesh D,M`` installs a pack mesh so the large GEMMs run as
pack-level collective matmuls (simulate devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The arch's
smoke preset is served by default; ``--no-smoke`` serves its published
widths, and ``--platform tpu`` refuses to run anywhere but on the chip:

    python -m repro.launch.serve --arch smollm_360m --no-smoke \
        --platform tpu --kv paged --page_size 16 --prefill-chunk 128
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

# --verify: max |continuous - one-shot| over each logits row computed
# from the same inputs, in units of the one-shot row's standard
# deviation.  A wrong slot, page, position or mask moves logits by O(1)
# standard deviations; reordered bf16 sums (a 4-slot program against a
# 1-slot one on a TPU) move them by a few hundredths.  On the CPU the
# rows are bit-identical.
VERIFY_TOL = 0.1


def shared_prefix_tokens(group: int, prefix_len: int, vocab_size: int,
                         seed: int = 0) -> np.ndarray:
    """The system-prompt tokens of one share group — seeded per *group*
    (offset by 1000 so group rngs never collide with per-id suffix
    rngs), so every request in the group reloads the identical
    prefix."""
    return np.random.default_rng(seed + 1000 + int(group)).integers(
        0, vocab_size, size=(int(prefix_len),)).astype(np.int32)


def load_trace(path: str, vocab_size: int, seed: int = 0) -> List[dict]:
    """Parse a JSONL trace; synthesize prompt tokens where only
    ``prompt_len`` is given (deterministically, per request id).
    Records carrying ``group`` + ``prefix_len`` are *shared-prefix*
    requests: the first ``prefix_len`` tokens come from the group's rng
    (identical across the group — the system prompt), the remaining
    ``prompt_len - prefix_len`` from the per-id rng."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            rec = json.loads(line)
            if "prompt" in rec:
                prompt = np.asarray(rec["prompt"], np.int32)
            else:
                rng = np.random.default_rng(seed + int(rec["id"]))
                plen = int(rec["prompt_len"])
                if "group" in rec:
                    pfx = shared_prefix_tokens(rec["group"],
                                               rec.get("prefix_len", 0),
                                               vocab_size, seed)
                    sfx = rng.integers(0, vocab_size,
                                       size=(plen - len(pfx),)
                                       ).astype(np.int32)
                    prompt = np.concatenate([pfx, sfx])
                else:
                    prompt = rng.integers(0, vocab_size, size=(plen,)
                                          ).astype(np.int32)
            item = {"id": int(rec["id"]),
                    "arrival": int(rec.get("arrival", 0)),
                    "prompt": prompt,
                    "max_new": int(rec["max_new"])}
            if "group" in rec:
                item["group"] = int(rec["group"])
                item["prefix_len"] = int(rec.get("prefix_len", 0))
            if "arrival_s" in rec:
                item["arrival_s"] = float(rec["arrival_s"])
            if "cancel_after" in rec:
                item["cancel_after"] = int(rec["cancel_after"])
            out.append(item)
    return sorted(out, key=lambda r: (r.get("arrival_s", 0.0),
                                      r["arrival"], r["id"]))


def resolve_trace_path(name: str) -> str:
    """``--trace`` accepts a filesystem path or a bare trace name; bare
    names resolve to the repo's ``benchmarks/traces/<name>.jsonl``."""
    import os
    if os.path.exists(name):
        return name
    if os.sep not in name and not name.endswith(".jsonl"):
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        cand = os.path.join(repo, "benchmarks", "traces", f"{name}.jsonl")
        if os.path.exists(cand):
            return cand
    return name


def synth_trace(requests: int, prompt_len: int, max_new: int,
                stagger: int, vocab_size: int, seed: int = 0
                ) -> List[dict]:
    """Staggered-arrival synthetic trace: request i arrives at step
    ``i * stagger`` — with stagger >= 1, later requests are admitted
    while earlier ones are mid-decode."""
    rng = np.random.default_rng(seed)
    return [{"id": i, "arrival": i * stagger,
             "prompt": rng.integers(0, vocab_size, size=(prompt_len,)
                                    ).astype(np.int32),
             "max_new": max_new}
            for i in range(requests)]


def gen_arrivals(kind: str, n: int, rate: float, seed: int = 0
                 ) -> np.ndarray:
    """Seedable arrival times (seconds, first at 0) for ``n`` requests
    at a mean rate of ``rate`` req/s.

    * ``uniform`` — evenly spaced, gap 1/rate;
    * ``poisson`` — exponential inter-arrivals (memoryless load);
    * ``pareto``  — Lomax(alpha=1.5) inter-arrivals scaled to mean
      1/rate: heavy-tailed, so requests cluster into bursts separated
      by long quiet gaps.  This is the adversarial case for monolithic
      prefill — a burst admits several prompts back to back, and every
      in-flight stream stalls for each whole-prompt prefill.

    >>> a = gen_arrivals("uniform", 4, 2.0)
    >>> [round(float(x), 2) for x in a]
    [0.0, 0.5, 1.0, 1.5]
    >>> b = gen_arrivals("pareto", 100, 8.0, seed=1)
    >>> (bool(b[0] == 0.0), bool(np.all(np.diff(b) >= 0)))
    (True, True)
    """
    if rate <= 0:
        raise ValueError(f"rate must be > 0, got {rate}")
    rng = np.random.default_rng(seed)
    if kind == "uniform":
        gaps = np.full(n, 1.0 / rate)
    elif kind == "poisson":
        gaps = rng.exponential(1.0 / rate, size=n)
    elif kind == "pareto":
        alpha = 1.5
        gaps = rng.pareto(alpha, size=n) * (alpha - 1.0) / rate
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return np.cumsum(gaps) - gaps[0]


def bursty_trace(requests: int, prompt_len: int, max_new: int,
                 kind: str, rate: float, vocab_size: int, seed: int = 0
                 ) -> List[dict]:
    """Wall-clock trace with ``kind`` arrivals and heterogeneous sizes:
    prompt lengths drawn from [prompt_len/2, 2*prompt_len] so bursts mix
    short and long prefills, max_new from [max_new/2, max_new].  Prompts
    use the same per-id rng as :func:`load_trace`, so a trace dumped
    with ``--dump-trace`` (which stores only ``prompt_len``) reloads to
    bit-identical prompts."""
    rng = np.random.default_rng(seed)
    arrivals = gen_arrivals(kind, requests, rate, seed)
    out = []
    for i in range(requests):
        plen = int(rng.integers(max(1, prompt_len // 2),
                                2 * prompt_len + 1))
        mnew = int(rng.integers(max(1, max_new // 2), max_new + 1))
        prompt = np.random.default_rng(seed + i).integers(
            0, vocab_size, size=(plen,)).astype(np.int32)
        out.append({"id": i, "arrival": 0,
                    "arrival_s": round(float(arrivals[i]), 3),
                    "prompt": prompt, "max_new": mnew})
    return out


def shared_trace(requests: int, prompt_len: int, max_new: int,
                 groups: int, stagger: int, vocab_size: int,
                 seed: int = 0) -> List[dict]:
    """Shared-system-prompt trace: ``requests`` requests split over
    ``groups`` share groups, each group reusing one seeded system
    prompt of ``3 * prompt_len // 4`` tokens followed by a per-request
    suffix of ``prompt_len//8 .. prompt_len//4`` tokens (the long-
    system-prompt / short-question shape of production shared
    traffic).  Arrivals are
    *group-blocked* (all of group 0, then group 1, ...) and staggered
    one request per ``stagger`` steps, so a group's first request
    finishes prefilling — and populates the radix tree — before its
    siblings are admitted: the workload where prefix caching pays.
    Consecutive groups are spaced an extra ``max_new`` steps apart so
    one group's decode mostly drains before the next group's
    admissions: its prefix pages then drop to cache-idle residency
    (reclaimable, uncounted by the ``pages_in_use`` high-water), which
    is what lets sharing cut the pool high-water by roughly the shared
    fraction rather than merely deduplicating concurrent prompts.
    Prompts use the group/per-id rngs of :func:`load_trace`, so a
    ``--dump-trace`` file (storing only group/prefix_len/prompt_len)
    reloads to bit-identical prompts."""
    rng = np.random.default_rng(seed)
    prefix_len = max(1, (3 * prompt_len) // 4)
    out = []
    for i in range(requests):
        g = i * groups // max(1, requests)     # group-blocked order
        pfx = shared_prefix_tokens(g, prefix_len, vocab_size, seed)
        slen = int(rng.integers(max(1, prompt_len // 8),
                                max(2, prompt_len // 4) + 1))
        sfx = np.random.default_rng(seed + i).integers(
            0, vocab_size, size=(slen,)).astype(np.int32)
        out.append({"id": i, "arrival": i * stagger + g * max_new,
                    "group": g,
                    "prefix_len": prefix_len,
                    "prompt": np.concatenate([pfx, sfx]),
                    "max_new": max_new})
    return out


def dump_trace(path: str, trace: List[dict]) -> None:
    """Write ``trace`` as JSONL, storing ``prompt_len`` instead of the
    tokens (``load_trace`` re-synthesizes them per id; shared-prefix
    records keep ``group``/``prefix_len`` so the group rng rebuilds the
    common system prompt)."""
    with open(path, "w") as f:
        for t in trace:
            rec: Dict[str, object] = {"id": t["id"]}
            if "arrival_s" in t:
                rec["arrival_s"] = t["arrival_s"]
            elif t.get("arrival"):
                rec["arrival"] = t["arrival"]
            if "group" in t:
                rec["group"] = t["group"]
                rec["prefix_len"] = t["prefix_len"]
            rec["prompt_len"] = int(len(t["prompt"]))
            rec["max_new"] = t["max_new"]
            if "cancel_after" in t:
                rec["cancel_after"] = t["cancel_after"]
            f.write(json.dumps(rec) + "\n")


def run_trace(engine, trace: List[dict],
              log: Optional[Callable[[str], None]] = print, *,
              wallclock: Optional[bool] = None, speed: float = 1.0,
              stream: Optional[Callable[[int, int, bool], None]] = None
              ) -> dict:
    """Replay ``trace`` through the unified token-budgeted loop.
    Returns {results: {trace_id: tokens}, wall_s, tokens, tok_s,
    p50_ms, p99_ms, ttft_p50_ms, ttft_p99_ms, shared_steps, ...}.

    Replay mode: ``wallclock=None`` auto-selects — wall-clock when any
    record carries ``arrival_s`` (requests are submitted when the clock
    reaches them, scaled by ``speed``), step-indexed otherwise (all
    submitted up front with their step arrivals).

    Latency attribution: ``p50/p99_ms`` are *per-stream* inter-token
    gaps — the wall time between a request's consecutive emissions
    (the engine's ``itl_ms`` events; first tokens are TTFT, never ITL).
    A stream stalled while the engine prefills someone else's prompt
    shows that stall in its next gap, which is exactly what chunked
    prefill exists to bound.  ``ttft_p50/p99_ms`` cover runnable ->
    first emission (queue wait + prefill).

    ``stream(trace_id, token, done)`` is invoked per emitted token;
    trace records with ``cancel_after: N`` are cancelled from the
    stream callback after their Nth token (mid-step, same-step page
    reclaim)."""
    log = log or (lambda s: None)
    rid_to_tid: Dict[int, int] = {}
    counts: Dict[int, int] = {}
    cancelled_tids: List[int] = []
    if wallclock is None:
        wallclock = any("arrival_s" in t for t in trace)

    def _cb(t):
        limit = t.get("cancel_after")
        tid = t["id"]

        def cb(rid, tok, done):
            if stream is not None:
                stream(tid, tok, done)
            counts[rid] = counts.get(rid, 0) + 1
            if limit is not None and counts[rid] >= limit and not done:
                if engine.cancel(rid):
                    cancelled_tids.append(tid)
        return cb

    def _submit(t, arrival=None):
        need_cb = stream is not None or "cancel_after" in t
        rid = engine.submit(t["prompt"], t["max_new"], arrival=arrival,
                            on_token=_cb(t) if need_cb else None)
        rid_to_tid[rid] = t["id"]

    # Trace arrivals are relative to the replay's start: offset by the
    # engine's current step so a warm engine (e.g. a bench replaying
    # the trace after a compile warmup) still sees the stagger.
    base = engine.step_count
    pending: List[dict] = []
    if wallclock:
        pending = sorted(trace, key=lambda t: t.get("arrival_s", 0.0))
    else:
        for t in trace:
            _submit(t, base + t["arrival"])
    token_lat: List[float] = []     # per-stream inter-token gaps, s
    ttft: List[float] = []          # runnable -> first token, seconds
    paged = engine.kv_mode == "paged"
    # Per-replay deltas: the engine's counters are lifetime-cumulative,
    # and a bench replays the same trace on a warm engine.
    reclaim_base = engine.pool.total_reclaimed if paged else 0
    preempt_base = engine.stats["preemptions"]
    prefixed = paged and engine.prefix is not None
    phit_base = engine.stats["prefix_hit_tokens"]
    ptot_base = engine.stats["prefix_prompt_tokens"]
    cow_base = engine.stats["cow_copies"]
    slo_base = engine.slo.breaches()
    t0 = time.monotonic()
    while pending or not engine.sched.done():
        if pending:
            now_s = (time.monotonic() - t0) * speed
            while pending and pending[0].get("arrival_s", 0.0) <= now_s:
                _submit(pending.pop(0))
            if engine.sched.done():
                # Idle until the next arrival: nothing to decode yet.
                wait = (pending[0].get("arrival_s", 0.0) / speed
                        - (time.monotonic() - t0))
                if wait > 0:
                    time.sleep(min(wait, 0.02))
                continue
        reclaimed0 = engine.pool.total_reclaimed if paged else 0
        ev = engine.step()
        token_lat += [ms / 1e3 for ms in ev["itl_ms"].values()]
        ttft += [ms / 1e3 for ms in ev["ttft_ms"].values()]
        older = sorted(set(ev["decoded"]) - set(ev["admitted"]))
        if ev["admitted"] and older:
            log(f"[serve] step={engine.step_count - 1} "
                f"admitted={[rid_to_tid[r] for r in ev['admitted']]} "
                f"sharing decode with "
                f"{[rid_to_tid[r] for r in older]}")
        for rid in ev.get("preempted", []):
            log(f"[serve] preempted id={rid_to_tid[rid]} (pool "
                f"exhausted) — requeued at the head")
        for rid in ev.get("cancelled", []):
            log(f"[serve] cancelled id={rid_to_tid[rid]} — slot"
                f"{' and pages' if paged else ''} freed this step")
        for rid in ev["finished"]:
            n = len(engine.result(rid))
            log(f"[serve] done id={rid_to_tid[rid]} tokens={n}")
        if paged:
            delta = engine.pool.total_reclaimed - reclaimed0
            if delta:
                log(f"[serve] reclaimed {delta} pages -> "
                    f"{engine.pool.free_pages}/{engine.pool.num_pages} "
                    f"free")
    wall = time.monotonic() - t0
    results = {rid_to_tid[rid]: toks
               for rid, toks in engine.drain().items()}
    tokens = sum(len(v) for v in results.values())
    rep = {
        "results": results,
        "rids": {tid: rid for rid, tid in rid_to_tid.items()},
        "cancelled_ids": sorted(cancelled_tids),
        "wall_s": wall,
        "tokens": tokens,
        "tok_s": tokens / wall if wall > 0 else float("inf"),
        "p50_ms": float(np.percentile(token_lat, 50) * 1e3)
        if token_lat else float("nan"),
        "p99_ms": float(np.percentile(token_lat, 99) * 1e3)
        if token_lat else float("nan"),
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3)
        if ttft else float("nan"),
        "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3)
        if ttft else float("nan"),
        "shared_steps": engine.stats["shared_steps"],
        "decode_steps": engine.stats["decode_steps"],
        "prefill_chunks": engine.stats["prefill_chunks"],
        "kv_bytes_hwm": engine.kv_bytes_high_water(),
        "kv_bytes_reserved": engine.kv_bytes_reserved(),
    }
    rep["slo_breaches"] = engine.slo.breaches() - slo_base
    if paged:
        rep["pages_hwm"] = engine.pool.high_water
        rep["pages_reclaimed"] = engine.pool.total_reclaimed - reclaim_base
        rep["preemptions"] = engine.stats["preemptions"] - preempt_base
    if prefixed:
        hit = engine.stats["prefix_hit_tokens"] - phit_base
        tot = engine.stats["prefix_prompt_tokens"] - ptot_base
        rep["prefix_hit_tokens"] = hit
        rep["prefix_hit_rate"] = hit / max(1, tot)
        rep["cow_copies"] = engine.stats["cow_copies"] - cow_base
    return rep


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="smollm_360m",
                    help="config id (repro.configs); the default fits one "
                         "16 GB chip at its published widths")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's 2-layer smoke preset; "
                         "--no-smoke serves its FULL published widths")
    ap.add_argument("--platform", type=str, default=None,
                    choices=("tpu", "cpu"),
                    help="fail unless jax runs on this platform (e.g. "
                         "--platform tpu never carries on on the CPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch_slots", type=int, default=4,
                    help="KV slots (0 = resolve from the tuner)")
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--max_new", type=int, default=24)
    ap.add_argument("--stagger", type=int, default=3,
                    help="arrival gap between requests, in engine steps "
                         "(step-indexed replay)")
    ap.add_argument("--arrivals", type=str, default="steps",
                    choices=("steps", "shared", "uniform", "poisson",
                             "pareto"),
                    help="synthetic arrival process: 'steps' keeps the "
                         "deterministic --stagger replay; 'shared' is a "
                         "step-indexed shared-system-prompt trace "
                         "(--groups share groups, group-blocked "
                         "arrivals — the prefix-cache workload); the "
                         "rest generate wall-clock arrival_s at --rate "
                         "req/s (seedable via --seed) and replay in "
                         "real time")
    ap.add_argument("--groups", type=int, default=4,
                    help="share groups (distinct system prompts) for "
                         "--arrivals shared")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="mean request rate (req/s) for --arrivals")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="wall-clock replay speedup factor (2 = replay "
                         "arrival_s twice as fast)")
    ap.add_argument("--trace", type=str, default=None,
                    help="JSONL trace file, or a bare name resolved to "
                         "benchmarks/traces/<name>.jsonl (overrides "
                         "--requests/--prompt_len/--stagger)")
    ap.add_argument("--dump-trace", dest="dump_trace", type=str,
                    default=None,
                    help="write the (synthesized) trace as JSONL and "
                         "continue — how benchmarks/traces/*.jsonl are "
                         "(re)generated")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome-trace-event JSON of the run's "
                         "host spans and per-request async spans, on the "
                         "host clock (open in chrome://tracing or "
                         "ui.perfetto.dev); device-aligned spans come "
                         "from a jax.profiler trace instead")
    ap.add_argument("--metrics-out", type=str, default=None,
                    help="write the schema-1 metrics snapshot JSON "
                         "(TTFT/inter-token histograms, kvpool gauges, "
                         "roofline efficiency; see docs/OBSERVABILITY.md)")
    ap.add_argument("--prom-out", type=str, default=None,
                    help="write the metrics as Prometheus text exposition")
    ap.add_argument("--flight-out", dest="flight_out", type=str,
                    default=None,
                    help="write the flight recorder's JSON (recent step "
                         "decompositions + per-request timelines) at end "
                         "of run; mid-run tripwires — SLO breach, "
                         "preemption storm — write the same path "
                         "immediately")
    ap.add_argument("--slo-ttft-ms", dest="slo_ttft_ms", type=float,
                    default=None,
                    help="arm the SLO monitor: rolling-window p99 TTFT "
                         "target in ms (breaches count, trace, and trip "
                         "the flight recorder)")
    ap.add_argument("--slo-itl-ms", dest="slo_itl_ms", type=float,
                    default=None,
                    help="arm the SLO monitor: rolling-window p99 "
                         "inter-token target in ms")
    ap.add_argument("--warmup", action="store_true",
                    help="replay the trace once first (compiles every "
                         "program), reset the metrics, then measure — "
                         "use when --metrics-out feeds a latency gate")
    ap.add_argument("--kv", choices=("dense", "paged"), default="dense",
                    help="KV layout: dense per-slot max_len rows, or "
                         "the kvpool page pool + block tables")
    ap.add_argument("--page_size", type=int, default=0,
                    help="paged: tokens per page (0 = tuner/analytic)")
    ap.add_argument("--kv-dtype", dest="kv_dtype", type=str, default=None,
                    choices=("bfloat16", "float32", "int8"),
                    help="paged: page-pool storage dtype (default keeps "
                         "the model's cache dtype; int8 stores quantized "
                         "pages with per-row scales, dequantized inside "
                         "the decode kernel)")
    ap.add_argument("--pool_pages", type=int, default=0,
                    help="paged: pool capacity in pages (0 = the "
                         "dense-equivalent slots * ceil(max_len/page))")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true",
                    help="paged: radix-tree prefix caching — prompts "
                         "sharing page-aligned token prefixes reuse the "
                         "same physical pool pages (refcounted, "
                         "copy-on-write; bit-identical to uncached "
                         "runs)")
    ap.add_argument("--prefill-chunk", dest="prefill_chunk", type=int,
                    default=0,
                    help="split each prompt into N-token chunks "
                         "interleaved with decode (0 = monolithic "
                         "prefill, -1 = resolve from the tuner; paged "
                         "runs round N up to a page multiple)")
    ap.add_argument("--token-budget", dest="token_budget", type=int,
                    default=0,
                    help="per-step token budget for the unified loop "
                         "(0 = unbudgeted: one chunk per prefilling "
                         "slot per step)")
    ap.add_argument("--policy", type=str, default="fifo",
                    choices=("fifo", "latency"),
                    help="admission policy: fifo admits whenever a slot "
                         "fits; latency defers admission while the "
                         "decode budget is saturated or inter-token p99 "
                         "exceeds its target")
    ap.add_argument("--stream", action="store_true",
                    help="print every token as the engine emits it")
    ap.add_argument("--eos_id", type=int, default=None,
                    help="token id that ends a request early (frees its "
                         "slot and, when paged, its KV pages that step)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quantize", action="store_true",
                    help="int8 weight-only quantization (the paper's "
                         "multi-precision serving point)")
    ap.add_argument("--mesh", type=str, default=None, metavar="D,M",
                    help="install a (data, model) pack mesh")
    ap.add_argument("--pack_min_flops", type=float, default=2.0 * 1024 ** 3)
    ap.add_argument("--verify", action="store_true",
                    help="check each completed request against a "
                         "one-shot single-slot generate() (greedy only)")
    return ap


def main(argv: Optional[List[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    if args.verify and args.temperature > 0.0:
        raise SystemExit(
            "--verify requires greedy decoding (temperature=0): the "
            "sampling key folds in the slot index, which necessarily "
            "differs between the continuous engine and the one-slot "
            "verify engine")

    import jax

    from repro import configs as C, obs
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.serving.engine import ServeConfig, ServeEngine

    dev = jax.devices()[0]
    print(f"[serve] device platform={dev.platform} "
          f"kind={dev.device_kind} count={len(jax.devices())}")
    if args.platform is not None and dev.platform != args.platform:
        raise SystemExit(f"[serve] asked for --platform {args.platform} "
                         f"but jax runs on {dev.platform}")
    print(f"[serve] compile cache: {enable_compile_cache()}")

    # Fresh metrics for this run; span recording only when a trace is
    # actually being written (spans cost a clock read each).
    bundle = obs.configure(
        registry=obs.Registry(),
        tracer=obs.Tracer(enabled=args.trace_out is not None))

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    if args.trace:
        trace = load_trace(resolve_trace_path(args.trace),
                           cfg.vocab_size, seed=args.seed)
    elif args.arrivals == "shared":
        trace = shared_trace(args.requests, args.prompt_len,
                             args.max_new, args.groups, args.stagger,
                             cfg.vocab_size, seed=args.seed)
    elif args.arrivals != "steps":
        trace = bursty_trace(args.requests, args.prompt_len,
                             args.max_new, args.arrivals, args.rate,
                             cfg.vocab_size, seed=args.seed)
    else:
        trace = synth_trace(args.requests, args.prompt_len, args.max_new,
                            args.stagger, cfg.vocab_size, seed=args.seed)
    if args.dump_trace:
        dump_trace(args.dump_trace, trace)
        print(f"[serve] wrote {len(trace)} requests -> {args.dump_trace}")
    max_len = max(len(t["prompt"]) + t["max_new"] for t in trace) + 8
    mesh = None
    if args.mesh:
        from jax.sharding import AxisType
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = jax.make_mesh((d, m), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    engine = ServeEngine(cfg, params, ServeConfig(
        batch_slots=args.batch_slots, max_len=max_len,
        temperature=args.temperature, seed=args.seed,
        quantize=args.quantize, eos_id=args.eos_id,
        kv=args.kv, page_size=args.page_size, pool_pages=args.pool_pages,
        kv_dtype=args.kv_dtype, prefix_cache=args.prefix_cache,
        prefill_chunk=(None if args.prefill_chunk < 0
                       else args.prefill_chunk),
        token_budget=args.token_budget, policy=args.policy,
        pack_mesh=mesh, pack_min_flops=args.pack_min_flops))
    if args.slo_ttft_ms is not None:
        engine.slo.set_targets(ttft_ms=args.slo_ttft_ms)
    if args.slo_itl_ms is not None:
        engine.slo.set_targets(itl_ms=args.slo_itl_ms)
    if args.flight_out:
        # Armed path: mid-run tripwires (breach / preemption storm)
        # write the snapshot immediately, not just at end of run.
        engine.flight.path = args.flight_out
    stream_cb = None
    if args.stream:
        def stream_cb(tid, tok, done):
            print(f"[stream] id={tid} token={tok}"
                  f"{' (done)' if done else ''}")
    served_rows: Dict[int, Dict[int, "jax.Array"]] = {}
    try:
        if args.warmup:
            run_trace(engine, trace, log=None)
            engine.drain()
            bundle.registry.reset_values()
        if args.verify:
            engine.logits_tap = lambda rid, pos, row: served_rows.setdefault(
                rid, {}).__setitem__(pos, row)
        rep = run_trace(engine, trace, stream=stream_cb,
                        speed=args.speed)
        expected = len(trace) - len(rep["cancelled_ids"])
        assert len(rep["results"]) == expected, \
            f"only {len(rep['results'])}/{expected} requests completed"
        print(f"[serve] {rep['tokens']} tokens in {rep['wall_s']:.2f}s "
              f"({rep['tok_s']:.1f} tok/s incl. compile) "
              f"p50={rep['p50_ms']:.1f}ms p99={rep['p99_ms']:.1f}ms "
              f"ttft_p50={rep['ttft_p50_ms']:.1f}ms "
              f"ttft_p99={rep['ttft_p99_ms']:.1f}ms "
              f"shared_steps={rep['shared_steps']} "
              f"decode_steps={rep['decode_steps']} arch={cfg.name} "
              f"slots={engine.scfg.batch_slots}")
        if engine.prefill_chunk:
            print(f"[serve] chunked prefill: chunk="
                  f"{engine.prefill_chunk} "
                  f"chunks={rep['prefill_chunks']} "
                  f"budget={engine.scfg.token_budget} "
                  f"policy={engine.sched.policy.name} "
                  f"starved_steps={engine.stats['starved_steps']}")
        if rep["cancelled_ids"]:
            print(f"[serve] cancelled ids={rep['cancelled_ids']} "
                  f"(slots/pages reclaimed same-step)")
        # The paper's %-of-peak analogue: achieved decode throughput
        # over the published peak of the chip (none on the CPU).
        if obs.efficiency.device_kind() is None:
            print("[serve] efficiency=not measured (no device peak on "
                  "the cpu)")
        else:
            eff = obs.efficiency.serve_efficiency(cfg, rep["tok_s"])
            bundle.registry.gauge(
                "serve.efficiency",
                "achieved decode throughput / published peak").set(eff)
            print(f"[serve] efficiency={eff:.3e} of the published peak "
                  f"({obs.efficiency.device_kind()}, incl. compile)")
        if (args.slo_ttft_ms is not None or args.slo_itl_ms is not None
                or rep["slo_breaches"]):
            s = engine.slo.summary()
            print(f"[serve] slo: breaches={rep['slo_breaches']} "
                  f"ttft_target={args.slo_ttft_ms} "
                  f"itl_target={args.slo_itl_ms} "
                  f"ttft_breaches={s['ttft']['breaches']} "
                  f"itl_breaches={s['itl']['breaches']}")
        if engine.kv_mode == "paged":
            print(f"[serve] paged kv: page_size={engine.pool.page_size} "
                  f"kv_dtype={engine.scfg.kv_dtype or 'cache'} "
                  f"pool={engine.pool.num_pages} pages "
                  f"pages_hwm={rep['pages_hwm']} "
                  f"pages_reclaimed={rep['pages_reclaimed']} "
                  f"preemptions={rep['preemptions']} "
                  f"kv_hwm={rep['kv_bytes_hwm'] / 2**20:.2f}MiB "
                  f"(dense would reserve "
                  f"{engine.scfg.batch_slots * engine.scfg.max_len * engine.token_kv_bytes() / 2**20:.2f}MiB)")
        elif args.kv == "paged":
            print(f"[serve] paged kv bypassed: arch {cfg.name} has "
                  f"non-attention state — dense layout in effect")
        if "prefix_hit_rate" in rep:
            print(f"[serve] prefix cache: hit_rate="
                  f"{rep['prefix_hit_rate']:.3f} "
                  f"hit_tokens={rep['prefix_hit_tokens']} "
                  f"cow_copies={rep['cow_copies']} "
                  f"resident={engine.pool.pages_resident}"
                  f"/{engine.pool.num_pages} pages")
        if args.verify:
            done_trace = [t for t in trace if t["id"] in rep["results"]]
            _verify(cfg, params, done_trace, rep["results"], engine.scfg,
                    {t["id"]: served_rows[rep["rids"][t["id"]]]
                     for t in done_trace})
        if args.trace_out:
            n = bundle.tracer.write(args.trace_out)
            obs.validate_chrome_trace(bundle.tracer.chrome_trace())
            print(f"[serve] wrote {n} trace events -> {args.trace_out} "
                  f"(open in chrome://tracing or ui.perfetto.dev)")
        if args.metrics_out:
            run_section = {k: v for k, v in rep.items()
                           if k not in ("results", "rids")}
            run_section["arch"] = cfg.name
            run_section["kv_mode"] = engine.kv_mode
            run_section["prefill_chunk"] = engine.prefill_chunk
            obs.write_metrics(
                args.metrics_out, bundle.registry,
                extra={"run": run_section},
                required_histograms=("serve.ttft_ms",
                                     "serve.inter_token_ms"),
                required_gauges=("kvpool.pages_in_use", "serve.kv_tokens"))
            print(f"[serve] wrote metrics snapshot -> {args.metrics_out}")
        if args.prom_out:
            obs.write_prometheus(args.prom_out, bundle.registry)
            print(f"[serve] wrote prometheus text -> {args.prom_out}")
        if args.flight_out:
            doc = engine.flight.write(args.flight_out,
                                      reason="end_of_run")
            print(f"[serve] wrote flight record -> {args.flight_out} "
                  f"(steps={len(doc['steps'])} "
                  f"requests={len(doc['requests'])} "
                  f"trips={len(doc['trips'])})")
    finally:
        engine.close()


def _verify(cfg, params, trace, results, scfg, served_rows) -> None:
    """Re-run every request one-shot (one slot, same kernels, pack
    context, KV layout and page dtype; the whole prompt prefilled at
    once) and hold the continuous-batching run to it.  The check
    isolates the batching machinery — admission, multi-slot decode,
    paging, chunking.  ``served_rows[trace id][pos]`` are the continuous
    run's logits rows.

    Every row both runs computed from the same inputs (the prompt's last
    position, then each decode step up to and including the first step
    where the greedy tokens part) must satisfy max |continuous -
    one-shot| <= VERIFY_TOL x the one-shot row's standard deviation.
    The tokens may part only where the one-shot row's gap between the
    two tokens is within twice that bound: a near-tie, which on a TPU a
    4-slot and a 1-slot program (whose bf16 sums run in different
    orders) may break either way."""
    import dataclasses

    from repro.serving.engine import ServeEngine
    # The reference never shares pages: with ``prefix_cache`` set this
    # is also the shared-vs-private-pages check.
    one_scfg = dataclasses.replace(scfg, batch_slots=1, prefill_chunk=0,
                                   prefix_cache=False)
    ref_name = (f"one-shot {scfg.kv}/{scfg.kv_dtype or 'cache-dtype'} "
                f"generate()")
    one = ServeEngine(cfg, params, one_scfg)
    ref_rows: Dict[int, "jax.Array"] = {}
    one.logits_tap = lambda rid, pos, row: ref_rows.__setitem__(pos, row)
    try:
        bad, parted, worst = [], [], 0.0
        for t in trace:
            ref_rows.clear()
            want = one.generate(t["prompt"][None, :], t["max_new"])[0]
            got = results[t["id"]]
            n = min(len(want), len(got))
            split = next((i for i in range(n) if want[i] != got[i]), None)
            last = (n - 1) if split is None else split
            base = len(t["prompt"]) - 1
            dev = 0.0
            for i in range(last + 1):
                r = np.asarray(ref_rows[base + i], np.float32)
                c = np.asarray(served_rows[t["id"]][base + i], np.float32)
                dev = max(dev, float(np.max(np.abs(c - r)) / np.std(r)))
            worst = max(worst, dev)
            if dev > VERIFY_TOL:
                bad.append(f"id={t['id']} max|d|={dev:.6f} sigma")
            elif split is None and len(want) != len(got):
                bad.append(f"id={t['id']} {len(got)} tokens, one-shot "
                           f"{len(want)}")
            elif split is not None:
                r = np.asarray(ref_rows[base + split], np.float32)
                gap = float((r[want[split]] - r[got[split]]) / np.std(r))
                if gap > 2 * VERIFY_TOL:
                    bad.append(f"id={t['id']} parted at step {split} with "
                               f"one-shot gap {gap:.6f} sigma")
                else:
                    parted.append((t["id"], split, gap))
        for tid, step, gap in parted:
            print(f"[serve] verify: id={tid} parted at step {step} on a "
                  f"near-tie (one-shot gap {gap:.6f} sigma)")
        if bad:
            raise SystemExit(
                f"[serve] VERIFY FAILED against {ref_name}: "
                + ", ".join(bad) + f" (tolerance {VERIFY_TOL} sigma)")
        print(f"[serve] verify OK: {len(trace)} requests within "
              f"{VERIFY_TOL} sigma of {ref_name} (max|d|={worst:.6f} "
              f"sigma; tokens identical for "
              f"{len(trace) - len(parted)}/{len(trace)})")
    finally:
        one.close()


if __name__ == "__main__":
    main()
