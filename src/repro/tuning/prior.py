"""Analytic pruner — the paper's cost model as a *prior* over the space.

Ranks candidates with the same quantities the Eq. 6 search optimizes
(gamma = compute-time / stream-time, VMEM utilization, cascade depth tk)
so only the top-``keep`` survive to empirical measurement.  The #1-ranked
candidate doubles as the dispatch fallback on a cache miss: it is exactly
the plan :func:`repro.core.tile_search.search_tpu_tiles` would pick, so
untuned behavior is unchanged from the pre-tuning codebase.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from repro.core import hw
from repro.core.tile_search import (search_tpu_tiles, tile_gamma,
                                    tile_vmem_bytes)
from repro.tuning.space import (AttentionCandidate, DecodeCandidate,
                                DesignSpace, GemmCandidate, PackCandidate,
                                ServeCandidate, WkvCandidate)


def precision_for(dtype_name: str) -> hw.Precision:
    """Map a jnp dtype name onto a precision descriptor: the paper's
    int8 and bf16 pairs, and f32 with its real 4-byte operands — the
    VMEM budget is in bytes, and counting f32 blocks as bf16 admitted
    tiles twice the size the kernel's scoped VMEM holds."""
    if dtype_name in ("int8", "int16", "int32", "uint8"):
        return hw.INT8_INT8
    if dtype_name == "float32":
        return hw.F32_F32
    return hw.BF16_BF16


def gemm_score(c: GemmCandidate, m: int, k: int, n: int,
               precision: hw.Precision,
               chip: hw.TpuChip = hw.TPU_V5E) -> Tuple:
    """Sort key, higher = better.  Mirrors search_tpu_tiles' policy:
    gamma (clipped — beyond ~4x compute-bound more gamma buys nothing),
    then VMEM working set (reuse), then tk (deeper in-kernel cascade)."""
    g = tile_gamma(c.tm, c.tk, c.tn, k, precision.in_bytes,
                   precision.out_bytes, chip, precision)
    vm = tile_vmem_bytes(c.tm, c.tk, c.tn, precision.in_bytes,
                         precision.out_bytes)
    # "mn" first on ties: it is the seed kernel's order (stable prior).
    order_rank = 1 if c.order == "mn" else 0
    return (round(min(g, 4.0), 3), vm, c.tk, order_rank)


def prune_gemm(candidates: Sequence[GemmCandidate], m: int, k: int, n: int,
               precision: hw.Precision, keep: int = 8,
               chip: hw.TpuChip = hw.TPU_V5E) -> List[GemmCandidate]:
    ranked = sorted(candidates,
                    key=lambda c: gemm_score(c, m, k, n, precision, chip),
                    reverse=True)
    return ranked[:max(1, keep)]


def analytic_gemm(m: int, k: int, n: int, dtype_name: str,
                  chip: hw.TpuChip = hw.TPU_V5E) -> GemmCandidate:
    """The cache-miss fallback: the pre-tuning planner's answer.

    Reproduces kernels/ops.py's historical ``_pick_tiles`` exactly —
    search_tpu_tiles over candidate grids shrunk for small problems — so
    a cold cache dispatches identically to the seed codebase.
    """
    p = precision_for(dtype_name)
    cands = [c for c in (128, 256, 512, 1024) if c <= max(m, 128)]
    kcands = [c for c in (128, 256, 512, 1024, 2048) if c <= max(k, 128)]
    ncands = sorted(set(c for c in (128, 256, 512, 1024) if c <= max(n, 128)))
    plan = search_tpu_tiles(
        m, k, n, p, chip=chip,
        candidates=tuple(sorted(set(cands + ncands))),
        k_candidates=tuple(kcands))
    acc = "i32" if p.in_bytes == 1 else "f32"
    return GemmCandidate(tm=plan.tm, tk=plan.tk, tn=plan.tn, order="mn",
                         acc=acc)


def attention_score(c: AttentionCandidate, sq: int, sk: int, d: int,
                    in_bytes: int) -> Tuple:
    """Prior for flash attention blocks.

    Larger bk = fewer softmax-state revisits per q block (the KV axis is
    the in-kernel cascade); larger bq amortizes the K/V stream across
    more queries.  Penalize blocks that mostly pad the problem.
    """
    waste_q = (-sq) % c.bq
    waste_k = (-sk) % c.bk
    return (-(waste_q * sk + waste_k * sq), c.bk, c.bq)


def prune_attention(candidates: Sequence[AttentionCandidate], sq: int,
                    sk: int, d: int, in_bytes: int = 4,
                    keep: int = 6) -> List[AttentionCandidate]:
    ranked = sorted(
        candidates,
        key=lambda c: attention_score(c, sq, sk, d, in_bytes),
        reverse=True)
    return ranked[:max(1, keep)]


def analytic_attention(sq: int, sk: int, d: int) -> AttentionCandidate:
    """Cache-miss fallback: the seed kernels' default (128, 128) blocks."""
    return AttentionCandidate(bq=128, bk=128)


# ---------------------------------------------------------------------------
# Pack level (P x Q grid + stagger + reduce order)
# ---------------------------------------------------------------------------


def _cascade_steps(m: int, k: int, n: int, data_axis: int,
                   model_axis: int) -> dict:
    """step_s per cascade depth P, from the planner's KCE sweep (Fig. 6).
    P plays the paper's G (K shards), Q = model_axis / P plays X."""
    from repro.core import planner
    site = planner.GemmSite("tuned", m=m, k=k, n=n)
    choices = planner.plan_cascade(site, data_axis, model_axis)
    return {c.g: c for c in choices}


def pack_step_model(choice, overlap: bool) -> float:
    """Modeled pack step time (s) — exposed vs. hidden communication.

    Unoverlapped (ring or psum): the 2(p-1)-step reduce starts only
    after the full local GEMM, so its time ``ici_s`` is fully exposed
    (the planner's ``step_s``).  Overlapped (the K-streamed pipelined
    ring of ``pack_gemm``): output bands are computed just in time,
    chunk by chunk, between the ring steps — the *same* total traffic
    as the sequential ring, but the reduce-scatter phase hides behind
    the p - 2 bands still streaming through the MXU (the paper's
    cascade overlap, Figs. 3/7); the terminal all-gather, with no
    compute left to hide behind, stays exposed.  Overlap therefore
    never models slower than the sequential ring: it ties when there
    is nothing to hide behind (p == 2, or a communication-bound grid)
    and wins as gamma grows — the per-shape margin is what the
    empirical tuner measures.
    """
    comp = max(choice.compute_s, choice.hbm_s)
    if choice.g == 1:
        return comp                       # no cross-device reduce
    # The planner's ici_s models the cascade *reduce-scatter* traffic
    # (core/planner.py: out_block * (G-1)/G); the all-gather phase
    # moves the same bytes again.
    rs = ag = choice.ici_s
    if not overlap:
        return comp + rs + ag
    hidden = comp * (choice.g - 2) / choice.g
    return comp + max(0.0, rs - hidden) + ag


def pack_score(c: PackCandidate, steps: dict) -> Tuple:
    """Sort key, higher = better.  Primary: the overlap-aware modeled
    step time for this cascade depth.  Schedule tiebreak: for P > 1
    prefer the K-streamed staggered ring (offset 1 — adjacent columns
    shifted by one chunk, the Fig. 7 skew the paper lands on); P == 1
    has no reduce, keep psum."""
    step = pack_step_model(steps[c.p], c.overlap)
    if c.p == 1:
        sched = 1 if (c.reduce == "psum" and c.stagger == 0) else 0
    else:
        sched = (4 if c.overlap else 0) \
            + (2 if c.reduce == "ring" else 0) \
            + (1 if c.stagger == 1 else 0)
    return (-round(step * 1e9), sched)


def prune_pack(candidates: Sequence[PackCandidate], m: int, k: int, n: int,
               data_axis: int, model_axis: int,
               keep: int = 6) -> List[PackCandidate]:
    steps = _cascade_steps(m, k, n, data_axis, model_axis)
    ranked = sorted(candidates, key=lambda c: pack_score(c, steps),
                    reverse=True)
    return ranked[:max(1, keep)]


def analytic_pack(m: int, k: int, n: int, data_axis: int,
                  model_axis: int) -> PackCandidate:
    """Cache-miss fallback: the top-ranked candidate of the analytic
    prune — the planner's best (G, X) factoring under the overlap-aware
    step model, with the staggered-ring schedule (offset 1) whenever
    there is a reduce.  Identical by construction to ``prune_pack``'s
    #1, so dispatch-without-a-cache and the tuner's prior agree."""
    steps = _cascade_steps(m, k, n, data_axis, model_axis)
    cands = DesignSpace.pack(m, k, n, model_axis)
    return max(cands, key=lambda c: pack_score(c, steps))


# ---------------------------------------------------------------------------
# Flash decode (split-K block) and WKV (time chunk)
# ---------------------------------------------------------------------------


def decode_score(c: DecodeCandidate, sk: int, d: int) -> Tuple:
    """Fewer grid steps over the cache first (each step re-reads the
    online-softmax state), then less padding waste, then larger bk."""
    steps = -(-max(sk, 1) // c.bk)
    waste = (-sk) % c.bk
    return (-steps, -waste, c.bk)


def prune_decode(candidates: Sequence[DecodeCandidate], sk: int, d: int,
                 keep: int = 4) -> List[DecodeCandidate]:
    ranked = sorted(candidates, key=lambda c: decode_score(c, sk, d),
                    reverse=True)
    return ranked[:max(1, keep)]


def analytic_decode(sk: int, d: int) -> DecodeCandidate:
    """Cache-miss fallback: the seed kernel's default split-K block."""
    return DecodeCandidate(bk=512)


def wkv_score(c: WkvCandidate, t: int, n: int) -> Tuple:
    """Less time-padding first (pad steps are wasted recurrence work),
    then larger chunks (fewer grid steps re-entering the kernel)."""
    waste = (-t) % c.chunk
    return (-waste, c.chunk)


def prune_wkv(candidates: Sequence[WkvCandidate], t: int, n: int,
              keep: int = 4) -> List[WkvCandidate]:
    ranked = sorted(candidates, key=lambda c: wkv_score(c, t, n),
                    reverse=True)
    return ranked[:max(1, keep)]


def analytic_wkv(t: int, n: int) -> WkvCandidate:
    """Cache-miss fallback: the seed kernel's default chunk."""
    return WkvCandidate(chunk=128)


# ---------------------------------------------------------------------------
# Serving (continuous-batching slot count)
# ---------------------------------------------------------------------------

# Modeled fixed cost of one batched decode step, in per-token units: the
# jit dispatch / host round-trip / sampling overhead that slots amortize.
# Calibration of this constant is exactly what tune_serve measures.
SERVE_STEP_OVERHEAD = 8.0


def serve_score(c: ServeCandidate, max_len: int) -> Tuple:
    """Sort key, higher = better.  Primary: modeled steady-state tokens
    per step-second — slots amortize the fixed per-step cost, with
    diminishing returns once per-token work dominates.  Then the KV
    footprint the candidate binds per slot: a paged layout holds
    ~half-occupied last pages instead of a full ``max_len`` row, so
    smaller (nonzero) pages rank above larger ones and every paged
    layout ranks above dense — the paper's buffer discipline as a
    prior, which ``time_serve`` then checks empirically.  int8 pages
    store each bound row at a fraction of the full-precision bytes
    (d_head int8 elements + one f32 scale vs d_head cache-dtype
    elements), so the same dead rows cost proportionally less — the
    waste term shrinks by that byte ratio and int8 ranks above "" at
    equal geometry.  Tiebreak: fewer slots."""
    thpt = c.slots / (SERVE_STEP_OVERHEAD + c.slots)
    # Expected bound-but-dead KV rows per live request: half the last
    # page (paged) vs the whole unreached tail (dense, ~max_len/2 for a
    # uniform length mix).  Scaled by relative row bytes for quantized
    # pages (int8 row = d_head + 4 scale bytes vs 4 * d_head f32 bytes
    # at the repo's d_head >= 16: conservatively 1/2).
    waste = (c.page_size / 2) if c.page_size else (max_len / 2)
    if c.kv_dtype == "int8":
        waste /= 2
    # Chunked prefill (schema v7) trades a little dispatch overhead for
    # inter-token tail latency — a win this throughput-modeled score
    # cannot see.  Rank chunked candidates just below their monolithic
    # twin so they are measured, and win only when actually faster.
    # Prefix caching (schema v8) is the same shape: a strict win on
    # shared-prompt traffic (skipped prefill + multiplied pool
    # capacity) that this model cannot size, and pure overhead (radix
    # bookkeeping) on disjoint prompts.  Rank cached candidates
    # immediately below their uncached twin — close enough to survive
    # the prune and be measured on the tuning trace, winning only when
    # the measured reuse actually pays.
    return (round(thpt * 1e6), -waste, -c.slots, -c.prefill_chunk,
            -int(c.prefix_cache))


def prune_serve(candidates: Sequence[ServeCandidate], max_len: int,
                keep: int = 3) -> List[ServeCandidate]:
    ranked = sorted(candidates, key=lambda c: serve_score(c, max_len),
                    reverse=True)
    return ranked[:max(1, keep)]


def analytic_serve(max_len: int) -> ServeCandidate:
    """Cache-miss fallback: the engine's historical default slot count
    (``ServeConfig.batch_slots = 8``) with the default paged-KV page
    granularity (32 tokens — the middle of the 16..64 window; only
    consulted when the engine runs ``kv="paged"``, so untuned *dense*
    behavior is unchanged).  ``kv_dtype`` stays "" — quantized pages
    change numerics and must be opted into (CLI / tuner measurement),
    never silently enabled by a cache miss.  ``prefill_chunk`` stays 0
    for the same reason: chunking reshapes a stream's latency profile,
    and a cache miss must never change behavior, only a measurement.
    ``prefix_cache`` stays False likewise: sharing pages changes pool
    accounting and admission charging, so it is only turned on by an
    explicit opt-in (CLI ``--prefix-cache``) or a measured winner."""
    return ServeCandidate(slots=8, page_size=32)
