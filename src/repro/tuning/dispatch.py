"""Hot-path dispatch: cached-best configs with an analytic fallback.

Lookup order per ``(op, shape, dtype, backend, device_kind)``:

1. in-process memo (a dict — zero search, what jit tracing hits);
2. the persistent tuning cache (loaded once per process);
3. the analytic prior (exactly the pre-tuning planner's answer).

``tune_gemm`` / ``tune_attention`` run the full pipeline — enumerate the
design space, prune with the analytic prior, measure survivors, persist
the winner — and are what the CLI and the CI smoke test drive.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.tuning import prior
from repro.tuning.cache import TuningCache, cache_key
from repro.tuning.space import (AttentionCandidate, DecodeCandidate,
                                DesignSpace, GemmCandidate, PackCandidate,
                                ServeCandidate, WkvCandidate)

# Canonical dtype spellings accepted by the CLI / config files.
_DTYPE_ALIASES = {
    "bf16": "bfloat16", "f32": "float32", "fp32": "float32",
    "f16": "float16", "fp16": "float16", "i8": "int8",
}


def canonical_dtype(dtype) -> str:
    """'bf16' / jnp.bfloat16 / np.dtype -> 'bfloat16'.

    >>> canonical_dtype("bf16")
    'bfloat16'
    >>> canonical_dtype("float32")
    'float32'
    """
    if isinstance(dtype, str):
        return _DTYPE_ALIASES.get(dtype, dtype)
    import numpy as np
    try:
        return np.dtype(dtype).name
    except TypeError:
        name = getattr(dtype, "name", None) or str(dtype)
        return _DTYPE_ALIASES.get(name, name)


def backend_fingerprint() -> Tuple[str, str]:
    """(backend, device_kind) — the hardware half of the cache key."""
    import jax
    backend = jax.default_backend()
    try:
        kind = jax.devices()[0].device_kind
    except (IndexError, RuntimeError):
        kind = backend
    return backend, str(kind).replace(" ", "_")


# ---------------------------------------------------------------------------
# Process-level state (memo + cache singleton)
# ---------------------------------------------------------------------------

_MEMO: Dict[str, object] = {}
_CACHE: Optional[TuningCache] = None
_CACHE_PATH: Optional[Path] = None


def set_cache_path(path) -> None:
    """Point dispatch at a specific cache file (tests, CLI --cache)."""
    global _CACHE, _CACHE_PATH
    _CACHE_PATH = Path(path) if path is not None else None
    _CACHE = None
    _MEMO.clear()


def reset() -> None:
    """Drop all in-process state; next lookup reloads from disk."""
    set_cache_path(None)


def get_cache() -> TuningCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = TuningCache(_CACHE_PATH).load()
    return _CACHE


# ---------------------------------------------------------------------------
# Hot-path lookups
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GemmConfig:
    tm: int
    tk: int
    tn: int
    order: str
    source: str   # "cache" | "analytic"


def gemm_config(m: int, k: int, n: int, dtype) -> GemmConfig:
    """Best-known GEMM tiling for this shape on this backend."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("gemm", m, n, k, dt, backend, kind)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        c = GemmCandidate.from_json(entry["config"])
        cfg = GemmConfig(tm=c.tm, tk=c.tk, tn=c.tn, order=c.order,
                         source="cache")
    else:
        c = prior.analytic_gemm(m, k, n, dt)
        cfg = GemmConfig(tm=c.tm, tk=c.tk, tn=c.tn, order=c.order,
                         source="analytic")
    _MEMO[key] = cfg
    return cfg


def gemm_tiles(m: int, k: int, n: int, dtype) -> Tuple[int, int, int]:
    cfg = gemm_config(m, k, n, dtype)
    return cfg.tm, cfg.tk, cfg.tn


def attention_blocks(sq: int, sk: int, d: int, dtype) -> Tuple[int, int]:
    """Best-known (bq, bk) flash-attention blocks for this shape."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("attention", sq, sk, d, dt, backend, kind)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        c = AttentionCandidate.from_json(entry["config"])
        blocks = (c.bq, c.bk)
    else:
        c = prior.analytic_attention(sq, sk, d)
        blocks = (c.bq, c.bk)
    _MEMO[key] = blocks
    return blocks


def pack_config(m: int, k: int, n: int, dtype, *, data_axis: int = 1,
                model_axis: int = 1) -> PackCandidate:
    """Best-known (P, Q, stagger, reduce, overlap) pack grid for this
    shape on a (data_axis, model_axis) mesh.  Cache miss falls back to
    the analytic prior (the planner's KCE sweep under the overlap-aware
    step model, with the staggered-ring schedule)."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("pack", m, n, k, dt, backend, kind,
                    extra=f"mesh{data_axis}x{model_axis}")
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        cand = PackCandidate.from_json(entry["config"])
    else:
        cand = prior.analytic_pack(m, k, n, data_axis, model_axis)
    _MEMO[key] = cand
    return cand


def decode_block(sk: int, d: int, dtype) -> int:
    """Best-known flash-decode split-K block for this (Sk, D) shape."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("decode", sk, d, 1, dt, backend, kind)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        bk = DecodeCandidate.from_json(entry["config"]).bk
    else:
        bk = prior.analytic_decode(sk, d).bk
    _MEMO[key] = bk
    return bk


def wkv_chunk(t: int, n: int, dtype) -> int:
    """Best-known WKV6 time-chunk for this (T, N) shape."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("wkv", t, n, 1, dt, backend, kind)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        chunk = WkvCandidate.from_json(entry["config"]).chunk
    else:
        chunk = prior.analytic_wkv(t, n).chunk
    _MEMO[key] = chunk
    return chunk


def _serve_key(cfg, max_len: int, dt: str, backend: str, kind: str) -> str:
    """Cache key for the serving slot count: the arch (name + width +
    vocab identify the compiled programs) and the cache length are the
    workload; GEMM shape slots carry (d_model, vocab, max_len)."""
    return cache_key("serve", cfg.d_model, cfg.vocab_size, max_len, dt,
                     backend, kind, extra=f"arch{cfg.name}")


def serve_config(cfg, max_len: int, dtype) -> ServeCandidate:
    """Best-known continuous-batching engine tunables for this
    arch/workload (schema v8: slot count + paged-KV page size + page
    kv_dtype + chunked-prefill chunk + prefix-cache bit), falling back
    to the analytic prior (8 slots / 32-token pages, full-precision,
    monolithic, uncached)."""
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = _serve_key(cfg, max_len, dt, backend, kind)
    hit = _MEMO.get(key)
    if hit is not None:
        return hit  # type: ignore[return-value]
    entry = get_cache().get(key)
    if entry is not None and "config" in entry:
        cand = ServeCandidate.from_json(entry["config"])
    else:
        cand = prior.analytic_serve(max_len)
    _MEMO[key] = cand
    return cand


def serve_slots(cfg, max_len: int, dtype) -> int:
    """Best-known continuous-batching slot count (the engine's
    ``batch_slots=0`` hook), falling back to the historical 8."""
    return serve_config(cfg, max_len, dtype).slots


def serve_page_size(cfg, max_len: int, dtype) -> int:
    """Best-known paged-KV page size for a ``kv="paged"`` engine
    (``ServeConfig.page_size = 0`` hook).  A tuned *dense* winner
    (page_size 0) falls back to the analytic 32: the caller already
    chose the paged layout, it only asks for the granularity."""
    tuned = serve_config(cfg, max_len, dtype).page_size
    return tuned if tuned > 0 else prior.analytic_serve(max_len).page_size


def serve_kv_dtype(cfg, max_len: int, dtype) -> Optional[str]:
    """Best-known paged-KV page dtype for a ``kv="paged"`` engine
    (``ServeConfig.kv_dtype = None`` keeps the cache dtype).  Returns
    None unless a *measured* tuned entry chose a quantized layout — a
    cache miss never silently changes numerics — and never for archs
    the page pool cannot cover (their pages fall back to dense)."""
    from repro.models.model import paged_eligible
    if not paged_eligible(cfg):
        return None
    tuned = serve_config(cfg, max_len, dtype).kv_dtype
    return tuned or None


def serve_prefill_chunk(cfg, max_len: int, dtype) -> int:
    """Best-known chunked-prefill chunk size for the unified step loop
    (``ServeConfig.prefill_chunk = None`` hook).  Returns 0 —
    monolithic, the historical behavior — unless a *measured* tuned
    entry chose a chunked candidate: a cache miss must never reshape a
    stream's latency profile.  Archs the chunked path cannot cover
    (recurrent state / enc-dec cross cache) always get 0 — the engine
    would bypass anyway."""
    from repro.models.model import paged_eligible
    if not paged_eligible(cfg):
        return 0
    return serve_config(cfg, max_len, dtype).prefill_chunk


def serve_prefix_cache(cfg, max_len: int, dtype) -> bool:
    """Best-known prefix-cache setting for a ``kv="paged"`` engine
    (``ServeConfig.prefix_cache = None`` hook).  Returns False — no
    sharing, the historical behavior — unless a *measured* tuned entry
    chose a prefix-cached candidate: a cache miss must never change
    pool accounting or admission charging.  Archs the page pool cannot
    cover always get False — there are no pages to share."""
    from repro.models.model import paged_eligible
    if not paged_eligible(cfg):
        return False
    return serve_config(cfg, max_len, dtype).prefix_cache


def warm_gemm_shapes(shapes: Sequence[Tuple[int, int, int]], dtype) -> int:
    """Pre-resolve configs for a model's GEMM shapes (serving startup) so
    the first jit trace never touches disk or runs the analytic search.
    Returns how many resolved from the persistent cache."""
    hits = 0
    for (m, k, n) in shapes:
        if gemm_config(m, k, n, dtype).source == "cache":
            hits += 1
    return hits


# ---------------------------------------------------------------------------
# Tuning pipeline (space -> prior prune -> measure -> persist)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TuneResult:
    key: str
    best: Optional[dict]           # winning candidate config (JSON form)
    best_us: Optional[float]
    cache_hit: bool                # True = nothing measured, entry existed
    trials: List[dict]             # per-candidate {config, us, max_err, ok}

    def summary(self) -> str:
        if self.cache_hit:
            return f"cache hit: {self.key} -> {self.best}"
        if self.best is None:
            return f"tuning failed: no candidate passed numerics ({self.key})"
        if self.best_us is None:
            return f"tuned {self.key} -> {self.best} (analytic, unmeasured)"
        return (f"tuned {self.key} -> {self.best} "
                f"({self.best_us:.1f} us, {len(self.trials)} measured)")


def _now() -> float:
    return time.time()


def _measure_and_store(key: str, tc: TuningCache, survivors, measure,
                       space_size: int) -> TuneResult:
    """Shared back half of the tune pipeline: measure each surviving
    candidate (a crashing candidate becomes a failed trial, not an
    aborted tune — on real hardware the compiler can reject configs the
    analytic model accepted), pick the fastest numerically-correct one,
    persist it, and invalidate the in-process memo."""
    from repro.tuning import runner
    trials: List[dict] = []
    results = []
    for c in survivors:
        try:
            meas = measure(c)
        except Exception as e:  # noqa: BLE001 - candidate, not harness
            meas = runner.Measurement(us=float("inf"), samples_us=[],
                                      max_err=float("inf"), ok=False)
            trials.append({"config": c.to_json(), **meas.to_json(),
                           "error": repr(e)})
            results.append(meas)
            continue
        results.append(meas)
        trials.append({"config": c.to_json(), **meas.to_json()})
    best_i = runner.pick_best(survivors, results)
    if best_i is None:
        return TuneResult(key=key, best=None, best_us=None,
                          cache_hit=False, trials=trials)
    best = survivors[best_i]
    entry = {
        "config": best.to_json(),
        "us": results[best_i].us,
        "max_err": results[best_i].max_err,
        "space_size": space_size,
        "measured": len(survivors),
        "tuned_at": _now(),
    }
    tc.put(key, entry)
    tc.save()
    _MEMO.pop(key, None)
    return TuneResult(key=key, best=entry["config"], best_us=entry["us"],
                      cache_hit=False, trials=trials)


def _cached_result(key: str, tc: TuningCache, force: bool, *,
                   analytic_is_hit: bool = True) -> Optional[TuneResult]:
    entry = tc.get(key)
    if entry is None or force:
        return None
    if not analytic_is_hit and entry.get("analytic"):
        # An analytic fallback stored by an under-provisioned host is
        # not a permanent answer: once this host can actually measure,
        # treat it as a miss and re-tune (the entry is overwritten).
        return None
    return TuneResult(key=key, best=entry.get("config"),
                      best_us=entry.get("us"), cache_hit=True, trials=[])


def tune_gemm(m: int, k: int, n: int, dtype, *, keep: int = 8,
              warmup: int = 1, reps: int = 3, force: bool = False,
              cache: Optional[TuningCache] = None) -> TuneResult:
    from repro.tuning import runner
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("gemm", m, n, k, dt, backend, kind)
    tc = cache if cache is not None else get_cache()
    hit = _cached_result(key, tc, force)
    if hit is not None:
        return hit
    p = prior.precision_for(dt)
    space = DesignSpace.gemm(m, k, n, p)
    survivors = prior.prune_gemm(space, m, k, n, p, keep=keep)
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_gemm(c, m, k, n, dt, warmup=warmup,
                                   reps=reps),
        space_size=len(space))


def tune_attention(sq: int, sk: int, d: int, dtype="float32", *,
                   keep: int = 6, warmup: int = 1, reps: int = 3,
                   force: bool = False,
                   cache: Optional[TuningCache] = None) -> TuneResult:
    from repro.tuning import runner
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("attention", sq, sk, d, dt, backend, kind)
    tc = cache if cache is not None else get_cache()
    hit = _cached_result(key, tc, force)
    if hit is not None:
        return hit
    import jax.numpy as jnp
    in_bytes = jnp.dtype(dt).itemsize
    space = DesignSpace.attention(sq, sk, d, in_bytes=in_bytes)
    survivors = prior.prune_attention(space, sq, sk, d, in_bytes, keep=keep)
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_attention(c, sq, sk, d, dt, warmup=warmup,
                                        reps=reps),
        space_size=len(space))


def tune_pack(m: int, k: int, n: int, dtype, *, data_axis: int = 1,
              model_axis: int = 1, keep: int = 6, warmup: int = 1,
              reps: int = 3, force: bool = False,
              cache: Optional[TuningCache] = None) -> TuneResult:
    """Tune the pack-level grid (P x Q, stagger, reduce order, overlap)
    for a sharded GEMM — schema v3; v2 lacked the K-streamed overlap
    bit, v1 was a scalar G.

    When this host exposes enough devices (a real slice, or a CPU mesh
    simulated via ``--xla_force_host_platform_device_count``), survivors
    of the analytic prune are *measured* end-to-end through
    ``pack_gemm`` on a live (data_axis, model_axis) mesh.  Otherwise the
    analytic prior is stored directly (flagged ``analytic``), exactly as
    re-deriving the planner's KCE sweep per mesh.  An analytic entry is
    only a hit while the host still cannot measure: on a host with
    enough devices it counts as a miss and is re-measured."""
    import jax
    from jax.sharding import AxisType

    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("pack", m, n, k, dt, backend, kind,
                    extra=f"mesh{data_axis}x{model_axis}")
    tc = cache if cache is not None else get_cache()
    capable = len(jax.devices()) >= data_axis * model_axis
    hit = _cached_result(key, tc, force, analytic_is_hit=not capable)
    if hit is not None:
        return hit
    space = DesignSpace.pack(m, k, n, model_axis)
    if not capable:
        best = prior.analytic_pack(m, k, n, data_axis, model_axis)
        entry = {
            "config": best.to_json(),
            "analytic": True,
            "space_size": len(space),
            "measured": 0,
            "tuned_at": _now(),
        }
        tc.put(key, entry)
        tc.save()
        _MEMO.pop(key, None)
        return TuneResult(key=key, best=entry["config"], best_us=None,
                          cache_hit=False,
                          trials=[{"config": entry["config"],
                                   "analytic": True}])
    from repro.tuning import runner
    survivors = prior.prune_pack(space, m, k, n, data_axis, model_axis,
                                 keep=keep)
    mesh = jax.make_mesh((data_axis, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    da = "data" if data_axis > 1 else None
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_pack(c, m, k, n, dt, mesh, data_axis=da,
                                   warmup=warmup, reps=reps),
        space_size=len(space))


def tune_decode(sk: int, d: int, dtype="float32", *, keep: int = 4,
                warmup: int = 1, reps: int = 3, force: bool = False,
                cache: Optional[TuningCache] = None) -> TuneResult:
    """Tune the flash-decode split-K block ``bk`` for a (Sk, D) cache."""
    from repro.tuning import runner
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("decode", sk, d, 1, dt, backend, kind)
    tc = cache if cache is not None else get_cache()
    hit = _cached_result(key, tc, force)
    if hit is not None:
        return hit
    space = DesignSpace.decode(sk, d)
    survivors = prior.prune_decode(space, sk, d, keep=keep)
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_decode(c, sk, d, dt, warmup=warmup,
                                     reps=reps),
        space_size=len(space))


def tune_serve(cfg, *, max_len: int = 64, prompt_len: int = 8,
               max_new: int = 8, requests: Optional[int] = None,
               stagger: int = 2, keep: int = 3, warmup: int = 0,
               reps: int = 1, force: bool = False,
               cache: Optional[TuningCache] = None) -> TuneResult:
    """Tune the continuous-batching engine (schema v8 ``serve`` op:
    slot count x paged-KV page size x page kv_dtype x chunked-prefill
    chunk x prefix-cache bit) for one model config: each surviving
    candidate runs a full staggered-arrival trace through
    ``ServeEngine`` — with the candidate's KV layout, prefill chunking
    and prefix sharing live (the tuning trace carries a shared prompt
    prefix so the reuse axis is actually exercised) — and is scored on
    measured us-per-token (i.e. tokens/s), with completeness as the
    numerics gate.  Quantized-page and prefix-cached candidates
    are dropped up front for archs the page pool cannot cover (the
    engine would reject them — see ``ServeConfig.kv_dtype``).  ``cfg``
    is a ``ModelConfig`` (use the smoke config of an arch — the
    tunable transfers by keying on arch + max_len)."""
    from repro.models.model import paged_eligible
    from repro.tuning import runner
    dt = canonical_dtype(cfg.cdtype)
    backend, kind = backend_fingerprint()
    key = _serve_key(cfg, max_len, dt, backend, kind)
    tc = cache if cache is not None else get_cache()
    hit = _cached_result(key, tc, force)
    if hit is not None:
        return hit
    space = DesignSpace.serve(max_len=max_len)
    if not paged_eligible(cfg):
        # The engine bypasses quantized pages (error), chunked
        # prefill, and prefix caching (both silently, with the dense
        # fallback) on these archs — chunked / cached candidates would
        # just re-measure their monolithic / uncached twin.
        space = [c for c in space if not c.kv_dtype
                 and not c.prefill_chunk and not c.prefix_cache]
    survivors = prior.prune_serve(space, max_len, keep=keep)
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_serve(c, cfg, max_len=max_len,
                                    prompt_len=prompt_len,
                                    max_new=max_new, requests=requests,
                                    stagger=stagger, warmup=warmup,
                                    reps=reps),
        space_size=len(space))


def tune_wkv(t: int, n: int, dtype="float32", *, keep: int = 4,
             warmup: int = 1, reps: int = 3, force: bool = False,
             cache: Optional[TuningCache] = None) -> TuneResult:
    """Tune the WKV6 time-chunk for a (T, N) recurrence."""
    from repro.tuning import runner
    dt = canonical_dtype(dtype)
    backend, kind = backend_fingerprint()
    key = cache_key("wkv", t, n, 1, dt, backend, kind)
    tc = cache if cache is not None else get_cache()
    hit = _cached_result(key, tc, force)
    if hit is not None:
        return hit
    space = DesignSpace.wkv(t, n)
    survivors = prior.prune_wkv(space, t, n, keep=keep)
    return _measure_and_store(
        key, tc, survivors,
        lambda c: runner.time_wkv(c, t, n, dt, warmup=warmup, reps=reps),
        space_size=len(space))
