"""Continuous-batching serving engine: slot-based KV cache + scheduler.

One persistent KV-cache allocation (``batch_slots`` rows) lives for the
engine's lifetime.  A :class:`~repro.serving.scheduler.Scheduler` admits
queued requests into free slots *mid-decode*: an admission is prefilled
into its slot (one request at a time, at its own offset) and joins the
very next batched decode step alongside every older in-flight request —
the serving analogue of the paper's staggered placement (keep every
compute unit busy by offsetting work in time, Fig. 7).

API: :meth:`ServeEngine.submit` queues a request (optionally with a
streaming per-token callback), :meth:`step` runs one engine step
(admissions + chunked-prefill progress + one batched decode under a
per-step token budget), :meth:`cancel` drops a request same-step,
:meth:`drain` steps until idle and returns finished outputs.  The
legacy one-shot :meth:`generate` is reimplemented on top of the same
loop (all slots admitted at step 0) and stays numerics-identical for a
uniform batch.

``ServeConfig(prefill_chunk=N)`` replaces the monolithic per-admission
prefill with a **unified token-budgeted loop**: each admitted prompt is
split into page-aligned chunks; a slot mid-prefill sits in the
``PREFILLING`` lifecycle state carrying a prompt cursor, one (or more,
budget permitting) chunks advance per step, and the chunks run *in the
same step* as every in-flight request's batched decode — so one long
prompt can no longer blow out every stream's inter-token p99 (the
serving analogue of the paper's staggered placement: no unit stalls
behind a monolithic neighbor).  Chunked greedy outputs are bit-identical
to monolithic prefill; which requests are admitted each step is the
scheduler :class:`~repro.serving.scheduler.Policy`'s call.

Prefill and decode are separately jitted; the decode program takes a
(B,) *per-slot* position vector so ragged batches write KV at their own
offsets and attend only to their own valid prefixes.

``ServeConfig(kv="paged")`` swaps the dense per-slot ``max_len``
reservation for the ``repro.serving.kvpool`` page pool: prefill
scatters prompt pages into the pool along the slot's block table,
decode appends rows (allocating pages on demand, preempting the
youngest admission when the pool is exhausted), and completion/EOS
reclaims a request's pages the same step — KV memory tracks *live
tokens*, not ``slots x max_len``, which is what lets the paged engine
admit more concurrent requests than the dense engine at equal memory.

``ServeConfig(prefix_cache=True)`` (paged-only) adds **prefix caching**
on top of the pool: a :class:`~repro.serving.kvpool.PrefixCache` radix
tree maps each incoming prompt to already-resident pages, so admission
charges only the unshared suffix, the chunked-prefill cursor starts at
the first uncached page (cached pages are never re-forwarded), and
multiple slots' block tables point at one physical page behind a
per-page refcount.  Cached pages carry a full-precision sidecar of
their dense-scratch KV rows, restored into a hit's scratch before the
suffix chunks run — which is what keeps greedy outputs bit-identical
to uncached runs for every page dtype, int8 included (the suffix
attends over exactly the rows the uncached prefill would have
computed, not a dequantized round trip).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import (decode_step, forward, init_cache,
                          init_paged_cache, paged_eligible, prefill)
from repro.models.config import ModelConfig
from repro.obs import FlightRecorder, SLOMonitor, get_obs
from repro.serving.kvpool import (BlockTables, PagePool, PrefixCache,
                                  pages_for)
from repro.serving.scheduler import (DECODE, PREFILLING, Request,
                                     Scheduler, Slot)


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8      # KV-cache slots; 0 = resolve from the tuner
    max_len: int = 1024
    enc_len: int = 0          # encoder length for enc-dec models
    temperature: float = 0.0  # 0 = greedy
    seed: int = 0             # PRNG seed for sampled (temperature) decoding
    quantize: bool = False    # int8 weight-only (paper multi-precision)
    pretune: bool = True      # resolve tuned kernel configs at init
    eos_id: Optional[int] = None  # sampled EOS ends the request early
    # Paged KV (repro.serving.kvpool): "paged" swaps the dense per-slot
    # max_len reservation for a global page pool + per-slot block
    # tables, so KV memory tracks live tokens.  Archs with recurrent
    # mixers or an enc-dec cross cache bypass to dense transparently
    # (engine.kv_mode says which path is live).
    kv: str = "dense"         # "dense" | "paged"
    page_size: int = 0        # tokens per page; 0 = tuner (schema v6)
    pool_pages: int = 0       # pool capacity; 0 = slots * ceil(max_len/ps)
                              # (the dense-equivalent footprint)
    # Page precision: None keeps cfg.cache_dtype; a float name retypes
    # the pools; "int8" stores quantized pages with per-row scale rows
    # (serving.quant) — half the KV bytes, dequant fused into the
    # decode kernel's split-K loop.  Paged-only: explicitly requesting
    # a kv_dtype on an arch that bypasses to dense is an error (the
    # engine must not silently store full-precision pages).
    kv_dtype: Optional[str] = None
    # Prefix caching (tuner schema v8 `prefix_cache` axis): share
    # already-resident prompt pages across requests through a radix
    # tree over token-id prefixes (kvpool.PrefixCache) behind per-page
    # refcounts, copy-on-write on shared writes.  Paged-only (there is
    # nothing to share in the dense layout — requesting it with
    # kv="dense" is an error); archs that bypass the pool to dense
    # bypass the cache too, transparently.  Forces the chunked prefill
    # path (a hit moves the prompt cursor past the cached pages), with
    # prefill_chunk=0 meaning "one chunk covers the whole suffix".
    # Greedy outputs are bit-identical to uncached runs for every page
    # dtype — hits restore a full-precision scratch sidecar, so the
    # suffix prefill sees exactly the rows it would have computed.
    prefix_cache: bool = False
    # Chunked prefill (tuner schema v7 `prefill_chunk` axis): 0 =
    # monolithic per-admission prefill (the historical behavior,
    # bit-for-bit); N > 0 splits each prompt into N-token chunks
    # (paged: rounded up to a page multiple so chunk scatters write
    # whole pages) advanced across steps in a PREFILLING lifecycle
    # state, interleaved with in-flight decode; None = resolve from
    # the tuner.  Archs with recurrent state or an enc-dec cross cache
    # bypass to monolithic transparently (same eligibility predicate
    # as the page pool).
    prefill_chunk: Optional[int] = 0
    # Per-step token budget for step(): decode claims one token per
    # active slot first, the remainder is spent on prefill chunks
    # (oldest admission first).  0 = unbudgeted — every PREFILLING
    # slot then advances exactly one chunk per step (maximal
    # interleave).  Forward progress is guaranteed either way: at
    # least one chunk advances per step whenever a slot is mid-prefill.
    token_budget: int = 0
    # Admission policy: a repro.serving.scheduler.Policy name
    # ("fifo" | "latency" | anything register_policy()-ed) or instance.
    policy: Any = "fifo"
    # Pack-level sharding (repro.distributed.pack_gemm): when a mesh is
    # given, GEMMs above pack_min_flops — the lm head and the ffn
    # projections — run as pack/array collective matmuls over its model
    # (and optionally data) axis instead of single kernels.
    pack_mesh: Any = None
    pack_data_axis: Optional[str] = None
    pack_min_flops: float = 2.0 * 1024 ** 3


def model_gemm_shapes(cfg: ModelConfig, batch: int, seq: int,
                      include_decode: bool = True) -> List[tuple]:
    """The (M, K, N) GEMMs a forward pass issues, for cache pre-warming:
    prefill sees M = batch*seq tokens, decode M = batch
    (``include_decode=False`` keeps only the prefill block — used when
    warming per-slot prompt buckets, whose decode shape is the engine's
    batch, not 1).

    This enumerates *GEMM sites*, not unique shapes: swiglu FFNs issue
    the up and gate projections separately (same (M, K, N) — the second
    resolves from the memo), so pre-warming walks exactly what the
    forward pass runs.
    """
    shapes = []
    qkv_n = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head
    for m in ((batch * seq, batch) if include_decode else (batch * seq,)):
        shapes += [
            (m, cfg.d_model, qkv_n),                     # fused qkv proj
            (m, cfg.n_heads * cfg.d_head, cfg.d_model),  # out proj
            (m, cfg.d_model, cfg.d_ff),                  # ffn up
            (m, cfg.d_model, cfg.d_ff),                  # ffn gate
            (m, cfg.d_ff, cfg.d_model),                  # ffn down
            (m, cfg.d_model, cfg.vocab_size),            # lm head
        ]
    return shapes


def prefill_buckets(max_len: int, lo: int = 8) -> List[int]:
    """Power-of-two prompt buckets up to ``max_len``.  Per-slot prefill
    pads each prompt to its bucket so the number of compiled prefill
    programs is O(log max_len), not one per prompt length.

    >>> prefill_buckets(64)
    [8, 16, 32, 64]
    >>> prefill_buckets(100)
    [8, 16, 32, 64, 100]
    """
    out, b = [], lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


def _first_token(logits: jax.Array, last: jax.Array) -> tuple:
    """The greedy token at row ``last`` (traced) of a ``(1, S, V)``
    logits block, and that row (what ``logits_tap`` observes)."""
    row = jax.lax.dynamic_index_in_dim(logits[0], last, axis=0,
                                       keepdims=False)
    return jnp.argmax(row).astype(jnp.int32), row


def _to_pools(full, one, scat):
    """Each layer's page pools, ``scat(pool, dense)``-updated from the
    matching dense scratch arrays: scratch array ``n`` of a layer goes
    to its pool ``n + "_pages"`` (K and V; or the latent rows)."""
    return [{m: {f"{n}_pages": scat(fc[m][f"{n}_pages"], a)
                 for n, a in oc[m].items()} for m in oc}
            for fc, oc in zip(full, one)]


def _bucket_for(plen: int, max_len: int) -> int:
    for b in prefill_buckets(max_len):
        if plen <= b:
            return b
    raise ValueError(f"prompt of {plen} tokens exceeds max_len={max_len}")


class ServeEngine:
    """Continuous-batching engine over the tuned kernel + pack stack.

    ``ServeEngine(cfg, params, ServeConfig(...))`` pre-resolves every
    GEMM shape's kernel config (so jit tracing never searches), and —
    when ``ServeConfig.pack_mesh`` is set — installs the pack context
    that shards the large GEMMs (lm head, ffn) through
    ``repro.distributed.pack_gemm`` and pre-resolves their pack grids.

    The pack context is *process-global* (it is what ``kernels.ops``
    dispatches on), so run one packed engine at a time and call
    :meth:`close` when done with it.  ``close()`` is idempotent; any
    serving call after it raises a clear ``RuntimeError`` instead of
    tracing GEMMs through a torn-down (or another engine's) pack mesh.
    """

    def __init__(self, cfg: ModelConfig, params: Any, scfg: ServeConfig):
        if scfg.quantize:
            from repro.serving.quant import quantize_params
            params, self.quant_stats = quantize_params(params)
        else:
            self.quant_stats = None
        if scfg.kv not in ("dense", "paged"):
            raise ValueError(f"ServeConfig.kv must be 'dense' or "
                             f"'paged', got {scfg.kv!r}")
        if scfg.kv_dtype is not None:
            from repro.serving.quant import KV_PAGE_DTYPES
            if scfg.kv_dtype not in KV_PAGE_DTYPES:
                raise ValueError(
                    f"ServeConfig.kv_dtype must be one of "
                    f"{KV_PAGE_DTYPES}, got {scfg.kv_dtype!r}")
            if scfg.kv != "paged":
                raise ValueError(
                    f"ServeConfig.kv_dtype={scfg.kv_dtype!r} requires "
                    f"kv='paged' — the dense layout has no page pool to "
                    f"retype (got kv={scfg.kv!r})")
            if scfg.kv_dtype == "int8" and any(
                    spec.mixer == "mla" for spec in cfg.pattern):
                raise ValueError(
                    f"arch {cfg.name!r} keeps a latent KV pool, which has "
                    f"no int8 pages (kv_dtype='int8')")
            if not paged_eligible(cfg):
                raise ValueError(
                    f"arch {cfg.name!r} cannot honor "
                    f"kv_dtype={scfg.kv_dtype!r}: its recurrent state / "
                    f"enc-dec cross cache bypasses the page pool to the "
                    f"dense layout, which would silently store "
                    f"full-precision KV.  Drop kv_dtype (the bypass is "
                    f"only transparent for the default page precision) "
                    f"or serve an attention-only arch")
        if scfg.prefix_cache and scfg.kv != "paged":
            raise ValueError(
                f"ServeConfig.prefix_cache requires kv='paged' — the "
                f"dense layout has no page pool to share prefixes "
                f"through (got kv={scfg.kv!r})")
        if scfg.batch_slots == 0:
            # Tuned slot count (schema v5 `serve` op): measured best for
            # this arch/workload when the cache has one, else the
            # analytic default.
            from repro.tuning import dispatch
            scfg = dataclasses.replace(
                scfg, batch_slots=dispatch.serve_slots(
                    cfg, scfg.max_len, cfg.cdtype))
        # Paged KV needs every position to live in an attention page;
        # recurrent state (mamba/rwkv) is fixed-size per slot and an
        # enc-dec cross cache is length-fixed, so those archs bypass the
        # pool and keep the dense layout (without error — kv_mode
        # records the live path).
        self.kv_mode = scfg.kv
        if scfg.kv == "paged" and not paged_eligible(cfg):
            self.kv_mode = "dense"
        if self.kv_mode == "paged":
            if scfg.page_size == 0:
                from repro.tuning import dispatch
                scfg = dataclasses.replace(
                    scfg, page_size=dispatch.serve_page_size(
                        cfg, scfg.max_len, cfg.cdtype))
            ps = scfg.page_size
            self._max_pages = pages_for(scfg.max_len, ps)
            pool_pages = scfg.pool_pages or scfg.batch_slots \
                * self._max_pages
            self.pool = PagePool(pool_pages, ps)
            self.blocks = BlockTables(self.pool, scfg.batch_slots,
                                      self._max_pages)
            # Dense scratch the per-slot prefill runs against, page-
            # aligned so whole pages scatter into the pool.
            self._fresh_len = self._max_pages * ps
            if scfg.prefix_cache:
                self.prefix = PrefixCache(self.pool)
                # Pool shortfalls evict LRU cache-only pages *before*
                # alloc fails — cache eviction always precedes slot
                # preemption.
                self.pool.reclaimer = self.prefix.evict
            else:
                self.prefix = None
        else:
            self.pool = None
            self.blocks = None
            self.prefix = None
            self._fresh_len = scfg.max_len
        if scfg.prefill_chunk is None:
            # Tuned chunk size (schema v7 `serve` op): measured best
            # when the cache has one, else the analytic default
            # (monolithic — tuning must never change numerics or
            # latency shape unless measured).
            from repro.tuning import dispatch
            scfg = dataclasses.replace(
                scfg, prefill_chunk=dispatch.serve_prefill_chunk(
                    cfg, scfg.max_len, cfg.cdtype))
        self.cfg, self.params, self.scfg = cfg, params, scfg
        # Recurrent mixers (mamba/rwkv, incl. the rwkv channel-mix FFN)
        # thread state through *every* token, pad or not — a
        # bucket-padded prompt would advance the state past the real
        # prompt.  Those archs prefill at exact prompt length (one
        # compiled program per distinct length); causal attention is
        # immune, so attention-only archs keep the pow2 buckets.
        self._exact_prefill = any(
            spec.mixer not in ("attn", "mla") or spec.ffn == "rwkv_cm"
            for spec in cfg.pattern)
        # Chunked prefill shares the page pool's eligibility predicate:
        # only archs whose whole per-token state is attention KV can
        # stop a prefill mid-prompt and resume it next step (recurrent
        # state threads through every token; an enc-dec cross cache is
        # written once at full length).  Others bypass to monolithic.
        chunk = int(scfg.prefill_chunk or 0)
        if chunk < 0:
            raise ValueError(f"ServeConfig.prefill_chunk must be >= 0 "
                             f"(or None = tuner), got {chunk}")
        if chunk and not paged_eligible(cfg):
            chunk = 0
        if self.prefix is not None and chunk == 0:
            # Prefix skip rides the chunked cursor: with no explicit
            # chunk size, one page-aligned chunk covers the whole
            # uncached suffix (bit-identical to monolithic, PR 8).
            chunk = self._fresh_len
        if chunk and self.kv_mode == "paged":
            # Page-aligned chunks: every chunk's scratch span covers
            # whole pages, so the per-chunk scatter writes full pages.
            chunk = pages_for(chunk, scfg.page_size) * scfg.page_size
        self.prefill_chunk = min(chunk, self._fresh_len)
        self.tuned_gemm_hits = 0
        self.packed_gemms = 0
        self._pack_ctx = None
        self._closed = False
        if scfg.pack_mesh is not None:
            import repro.distributed.pack_gemm as pg
            from repro.tuning import dispatch
            ctx = pg.set_pack_context(scfg.pack_mesh,
                                      data_axis=scfg.pack_data_axis,
                                      min_flops=scfg.pack_min_flops)
            self._pack_ctx = ctx
            wsize = ctx.mesh.shape[ctx.model_axis]
            dsize = ctx.mesh.shape[ctx.data_axis] if ctx.data_axis else 1
            # Pre-resolve the pack grid of every GEMM that will route
            # through the pack path (cache hit or analytic KCE sweep).
            for (m, k, n) in self._all_gemm_shapes():
                if ctx.eligible(m, k, n):
                    dispatch.pack_config(m, k, n, cfg.cdtype,
                                         data_axis=dsize,
                                         model_axis=wsize)
                    self.packed_gemms += 1
        if scfg.pretune:
            # Resolve every GEMM shape's kernel config up front (cache
            # hit or analytic fallback) so jit tracing — the hot path —
            # only ever sees memoized lookups, never disk or search.
            # GEMMs dispatch on the activation dtype: layers cast to
            # cfg.cdtype, and quantized weights are dequantized to it
            # before the matmul.
            from repro.tuning import dispatch
            self.tuned_gemm_hits = dispatch.warm_gemm_shapes(
                self._all_gemm_shapes(), cfg.cdtype)
        # -- observability (repro.obs) ------------------------------------
        # The engine instruments against the process bundle: metrics are
        # always on (allocation-light); spans reach an active profiler
        # session, and the Chrome sink when the entry point enabled the
        # tracer (--trace-out).
        obs = get_obs()
        self._obs = obs
        # -- the engine's jitted programs (jit_engine_<name> on a trace) --
        program = self._program
        self._prefill = program(
            "prefill", lambda p, b, c: prefill(p, b, cfg, c))
        # A held-expert model's programs also return the (token, expert)
        # pairs its layers served and the held experts they reached,
        # read with the decode's tokens.
        held = cfg.moe is not None and cfg.moe.held is not None

        def fwd(p, b, c, pos):
            out = forward(p, b, cfg, caches=c, cache_pos=pos,
                          held_counts=held)
            return out[:2] + out[3:]

        # Full-logits prefill for per-slot admission: a ragged prompt is
        # padded to its bucket, so the next-token logits live at
        # position plen-1, not at the padded end.
        self._prefill_full = program(
            "prefill_full",
            lambda p, b, c: fwd(p, b, c, jnp.zeros((), jnp.int32)))
        # Chunk-offset prefill: the same full-logits forward, but the
        # KV write offset / RoPE base is the slot's prompt cursor
        # (traced, so one compiled program covers every cursor value).
        self._prefill_chunk_fn = program("prefill_chunk", fwd)
        if self.kv_mode == "paged":
            self._decode = program(
                "decode",
                lambda p, t, pos, bt, c: decode_step(p, t, pos, cfg, c,
                                                     block_tables=bt,
                                                     held_counts=held))
            self._insert = program("insert", self._insert_slot_pages)
            self._insert_chunk = program("insert_chunk",
                                         self._insert_chunk_pages)
            # COW page copy: duplicate pool row src -> dst across every
            # layer's pools (page axis 1, after the layer-group dim).
            self._copy_page = program(
                "copy_page",
                lambda c, src, dst: jax.tree.map(
                    lambda a: a.at[:, dst].set(a[:, src]), c))
        else:
            self._decode = program(
                "decode", lambda p, t, pos, c: decode_step(
                    p, t, pos, cfg, c, held_counts=held))
            self._insert = program("insert", self._insert_slot)
        self._sample_slots = program("sample", self._make_sampler())
        # A prompt's first token: greedy from the logits row at a traced
        # position, so no program compiles per prompt length.
        self._first_token = program("first_token", _first_token)
        self._h_ttft = obs.registry.histogram(
            "serve.ttft_ms", "runnable -> first token, per request")
        self._h_itl = obs.registry.histogram(
            "serve.inter_token_ms",
            "per-stream gap between consecutive decode tokens "
            "(first tokens are TTFT, not ITL)")
        self._c_tokens = obs.registry.counter(
            "serve.tokens_out", "tokens emitted")
        self._c_chunks = obs.registry.counter(
            "serve.prefill_chunks", "prompt chunks prefilled")
        self._c_starved = obs.registry.counter(
            "serve.decode_starved_steps",
            "steps where in-flight streams stalled behind prefill work "
            "longer than the batched decode itself")
        self._c_rejects = obs.registry.counter(
            "serve.admission_rejections",
            "arrived requests deferred by the paged fits() gate")
        self._c_pages_live = obs.registry.counter(
            "engine.decode.kv_pages_live",
            "KV pages the paged decode reads: ceil((pos + 1) / page) "
            "summed over slots, per decode step")
        self._c_pages_table = obs.registry.counter(
            "engine.decode.kv_pages_table",
            "slots x max_pages: the block-table entries a decode step "
            "holds")
        # Routed-expert telemetry (0 for a model without held experts):
        # pairs are counted over every expert layer and every row the
        # programs route, padding rows included.
        moe_layers = (sum(spec.ffn == "moe" for spec in cfg.pattern)
                      * cfg.n_groups) if held else 0
        self._moe_layers = moe_layers
        self._pairs_per_row = moe_layers * (cfg.moe.top_k if held else 0)
        self._held_dev: List[jax.Array] = []    # counts not yet read
        self._c_routed = obs.registry.counter(
            "engine.moe.routed_rows",
            "(token, expert) pairs routed: rows x top_k x expert layers")
        self._c_held = obs.registry.counter(
            "engine.moe.held_rows",
            "of those, the pairs whose expert this chip holds (served)")
        self._c_reached = obs.registry.counter(
            "engine.moe.held_experts_reached",
            "held experts that got any pair, per expert layer and "
            "program call: each has its weights read once")
        self._c_layer_calls = obs.registry.counter(
            "engine.moe.layer_calls",
            "expert-layer calls: program calls x expert layers")
        obs.registry.gauge(
            "engine.moe.held_experts",
            "experts of each expert layer held on this chip").set(
            cfg.moe.n_held if held else 0)
        obs.registry.gauge(
            "engine.cache.bytes_per_token",
            "KV bytes one token holds over every layer "
            "(token_kv_bytes)").set(self.token_kv_bytes())
        self._g_active = obs.registry.gauge(
            "serve.active_slots", "slots mid-decode")
        self._g_kv_tokens = obs.registry.gauge(
            "serve.kv_tokens", "KV rows bound to live requests")
        # Register the pages gauge in both layouts so one snapshot schema
        # covers dense and paged runs (dense holds no pages: stays 0).
        obs.registry.gauge("kvpool.pages_in_use",
                           "KV pages currently allocated")
        # Prefix-cache telemetry, registered in every layout for one
        # snapshot schema (stays 0 when the cache is off): lookups,
        # cumulative hit tokens, and the running hit-rate gauge
        # (hit_tokens / prompt tokens over all admissions).
        self._c_plookup = obs.registry.counter(
            "prefix.lookup", "prefix-cache lookups at admission")
        self._c_phit = obs.registry.counter(
            "prefix.hit_tokens",
            "prompt tokens served from cached pages (never re-forwarded)")
        self._g_phit_rate = obs.registry.gauge(
            "prefix.hit_rate",
            "cumulative hit_tokens / prompt tokens across admissions")
        self._prefix_hit_tokens = 0
        self._prefix_prompt_tokens = 0
        if self.pool is not None:
            self.pool.bind_metrics(obs.registry)
        # -- SLO monitor + flight recorder ---------------------------------
        # Rolling-window tail-latency monitor.  Targets default to off;
        # the launcher arms them (--slo-ttft-ms / --slo-itl-ms).
        self.slo = SLOMonitor(obs.registry, tracer=obs.tracer)
        # Bounded incident recorder; SLO breaches and preemption storms
        # trip it (writes happen only once a path is armed).
        self.flight = FlightRecorder()
        self.slo.on_breach(
            lambda series, q, target: self.flight.trip(
                "slo_breach", series=series, window_ms=q,
                target_ms=target))
        if obs.tracer.enabled:
            # Name the pid/tid lanes so Perfetto shows "engine" instead
            # of bare zeros (idempotent — duplicates are harmless).
            obs.tracer.process_name("repro-serve")
            obs.tracer.thread_name("engine")
        # -- continuous-batching state (persistent across calls) ----------
        self.sched = Scheduler(scfg.batch_slots, policy=scfg.policy,
                               registry=obs.registry)
        # The policy reads the engine's live load picture (token
        # budget, decode tokens in flight, measured inter-token p99).
        self.sched.signals = self._admission_signals
        self.caches = None            # allocated at first admission
        self.step_count = 0
        self._next_rid = 0
        self._tok = np.zeros((scfg.batch_slots,), np.int32)
        self._out: Dict[int, List[int]] = {}
        self._finished: Dict[int, np.ndarray] = {}
        self._slot_req: Dict[int, Request] = {}   # slot idx -> live Request
        self._runnable_at: Dict[int, float] = {}  # rid -> perf_counter stamp
        self._last_emit: Dict[int, float] = {}    # rid -> last token stamp
        self._scratch: Dict[int, Any] = {}        # slot idx -> chunk scratch
        self._on_token: Dict[int, Callable] = {}  # rid -> stream callback
        self._cancel_log: List[int] = []          # cancels since last step
        # Optional observer of every logits row the engine samples from:
        # ``logits_tap(rid, pos, logits)`` with the (V,) f32 device row
        # computed from the token at position ``pos`` (the prompt's last
        # token at prefill, then each decode input).  For checking the
        # served numerics against a reference; None costs nothing.
        self.logits_tap: Optional[Callable[[int, int, jax.Array], None]] \
            = None
        self._kv_tokens_hwm = 0       # live-token high-water (dense + paged)
        self.stats = {"admitted": 0, "finished": 0, "prefills": 0,
                      "prefill_chunks": 0, "decode_steps": 0,
                      "shared_steps": 0, "preemptions": 0,
                      "eos_exits": 0, "cancelled": 0,
                      "starved_steps": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "prefix_prompt_tokens": 0,
                      "cow_copies": 0}

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        """Release this engine's pack context and mark the engine
        closed.  Idempotent: a second ``close()`` is a no-op (the pack
        context is only released by whoever still owns it)."""
        if self._closed:
            return
        self._closed = True
        if self._pack_ctx is not None:
            import repro.distributed.pack_gemm as pg
            if pg.get_pack_context() is self._pack_ctx:
                pg.clear_pack_context()
            self._pack_ctx = None

    @property
    def closed(self) -> bool:
        return self._closed

    def _check_open(self, what: str) -> None:
        if self._closed:
            raise RuntimeError(
                f"ServeEngine.{what}() on a closed engine — close() "
                f"released the pack context, so serving would trace "
                f"GEMMs through a torn-down (or another engine's) mesh; "
                f"create a new engine instead")

    # -- helpers ------------------------------------------------------------

    def _all_gemm_shapes(self) -> List[tuple]:
        """GEMM shapes the engine can issue: the uniform-batch legacy
        shapes plus every per-slot prefill bucket (M = bucket)."""
        shapes = model_gemm_shapes(self.cfg, self.scfg.batch_slots,
                                   self.scfg.max_len)
        if not self._exact_prefill:
            for bucket in prefill_buckets(self.scfg.max_len):
                shapes += model_gemm_shapes(self.cfg, 1, bucket,
                                            include_decode=False)
        if self.prefill_chunk:
            # Chunked prefill issues M = chunk GEMMs each step.
            shapes += model_gemm_shapes(self.cfg, 1, self.prefill_chunk,
                                        include_decode=False)
        return shapes

    def new_cache(self):
        if self.kv_mode == "paged":
            return init_paged_cache(self.cfg, self.pool.num_pages,
                                    self.pool.page_size,
                                    kv_dtype=self.scfg.kv_dtype)
        return init_cache(self.cfg, self.scfg.batch_slots,
                          self.scfg.max_len, enc_len=self.scfg.enc_len)

    # -- KV memory accounting ------------------------------------------------

    def token_kv_bytes(self) -> int:
        """Bytes of attention KV one token occupies across the stack
        (k + v, every attention layer; one latent row of ``latent_dim``
        lanes per latent-attention layer).  Paged pools with a kv_dtype
        override are counted at the page dtype; int8 pages additionally
        carry one f32 scale per token row per KV head (the per-row
        scale-row layout), so the int8 figure is D + 4 bytes per head
        row, not D — roughly half of f32's 4*D for D >= 8.  These are
        the model's bytes: the pools store each row in
        ``page_row_dim(D)`` lanes, as the TPU's tiled HBM layout
        allocates a row narrower than 128 lanes anyway."""
        cfg = self.cfg
        layers = [(spec, cfg.n_groups) for spec in cfg.pattern]
        if cfg.first_dense_layers:
            layers.append((cfg.lead_spec, cfg.first_dense_layers))
        n_attn = sum(n for spec, n in layers if spec.mixer == "attn")
        n_mla = sum(n for spec, n in layers if spec.mixer == "mla")
        kv_dtype = (self.scfg.kv_dtype if self.kv_mode == "paged"
                    else None)
        itemsize = jnp.dtype(kv_dtype or cfg.cache_dtype).itemsize
        row_bytes = cfg.d_head * itemsize
        if kv_dtype == "int8":
            row_bytes += 4                       # the row's f32 scale
        latent = n_mla * cfg.mla.latent_dim * itemsize if n_mla else 0
        return 2 * n_attn * cfg.n_kv_heads * row_bytes + latent

    def kv_bytes_reserved(self) -> int:
        """Attention-KV bytes held for the engine's lifetime: the page
        pool (paged) or slots x max_len rows (dense)."""
        per_tok = self.token_kv_bytes()
        if self.kv_mode == "paged":
            return self.pool.num_pages * self.pool.page_size * per_tok
        return self.scfg.batch_slots * self.scfg.max_len * per_tok

    def kv_bytes_high_water(self) -> int:
        """Peak attention-KV bytes actually *bound to live requests*:
        ``pages_in_use`` high-water x page bytes (paged), or the live
        token high-water x per-token bytes (dense) — both measure
        resident demand, so dense-vs-paged memory rows compare
        like-for-like (the dense layout still *reserves* its full
        ``slots x max_len`` footprint; see :meth:`kv_bytes_reserved`)."""
        per_tok = self.token_kv_bytes()
        if self.kv_mode == "paged":
            return self.pool.high_water * self.pool.page_size * per_tok
        return self._kv_tokens_hwm * per_tok

    def _note_kv_tokens(self, live: int) -> None:
        """Record the current live-token count (KV rows bound to active
        requests) into the gauge and the engine's own high-water."""
        if live > self._kv_tokens_hwm:
            self._kv_tokens_hwm = live
        self._g_kv_tokens.set(live)

    def _note_routed(self, rows: int, held: List[jax.Array]) -> None:
        """Count the pairs a program call routed over ``rows`` token
        rows, and keep its device counts of held pairs and reached
        experts (``held``: empty for a model without held experts) to
        read with the next decode's tokens."""
        if held:
            self._c_routed.inc(rows * self._pairs_per_row)
            self._c_layer_calls.inc(self._moe_layers)
            self._held_dev += held

    def _insert_slot(self, full, one, slot):
        """Overwrite slot ``slot`` of the persistent cache with a
        freshly prefilled single-slot cache.  Replacing the whole row
        (KV *and* recurrent state) is what makes slot reuse leak-free:
        nothing from the previous occupant survives."""
        def upd(f, o):
            start = (0, slot) + (0,) * (f.ndim - 2)
            return jax.lax.dynamic_update_slice(f, o.astype(f.dtype), start)
        return jax.tree.map(upd, full, one)

    def _insert_slot_pages(self, full, one, bt_row):
        """Scatter a freshly prefilled single-slot *dense* cache into
        the page pools along the slot's block-table row.  Every chunk of
        the (page-aligned) dense scratch is written — chunks past the
        slot's allocation land on the null sink page (bt_row points them
        there), so one compiled program covers every prompt length.
        int8 pools quantize each token row on the way in and scatter
        its scale into the pool's scale rows."""
        mp, ps = self._max_pages, self.pool.page_size

        def chunk(dense, width):
            # dense: (G, 1, Hkv, mp*ps, D) -> (G, mp, Hkv, ps, width),
            # the rows zero-padded to the pool's row width.
            g, _, hkv, _, d = dense.shape
            pages = dense[:, 0].reshape(g, hkv, mp, ps, d) \
                .transpose(0, 2, 1, 3, 4)
            return kops.to_page_rows(pages, width)

        def scat(pool, dense):
            return pool.at[:, bt_row].set(
                chunk(dense, pool.shape[-1]).astype(pool.dtype))

        if self.scfg.kv_dtype == "int8":
            from repro.serving.quant import quantize_kv_row

            def scat_q(pool, spool, dense):
                qrows, srows = quantize_kv_row(chunk(dense, pool.shape[-1]))
                return (pool.at[:, bt_row].set(qrows),
                        spool.at[:, bt_row].set(srows))

            out = []
            for fc, oc in zip(full, one):
                kq, ks = scat_q(fc["attn"]["k_pages"],
                                fc["attn"]["k_scale"], oc["attn"]["k"])
                vq, vs = scat_q(fc["attn"]["v_pages"],
                                fc["attn"]["v_scale"], oc["attn"]["v"])
                out.append({"attn": {"k_pages": kq, "v_pages": vq,
                                     "k_scale": ks, "v_scale": vs}})
            return out

        return _to_pools(full, one, scat)

    def _insert_chunk_pages(self, full, one, page_ids, src_idx):
        """Scatter one prefill *chunk*'s pages from the dense scratch
        into the pool: ``src_idx`` (host-clamped, static length
        chunk/page_size) picks the chunk's pages out of the scratch,
        ``page_ids`` is the matching slice of the slot's block-table
        row (out-of-range entries point at the null sink, absorbing
        the clamped duplicates).  The incremental sibling of
        :meth:`_insert_slot_pages` — O(chunk) pages written per call
        instead of O(max_len)."""
        mp, ps = self._max_pages, self.pool.page_size

        def pick(dense, width):
            # dense: (G, 1, Hkv, mp*ps, D) -> chunk pages
            # (G, cpp, Hkv, ps, width), the rows zero-padded to the
            # pool's row width.
            g, _, hkv, _, d = dense.shape
            pages = dense[:, 0].reshape(g, hkv, mp, ps, d) \
                .transpose(0, 2, 1, 3, 4)
            return kops.to_page_rows(pages[:, src_idx], width)

        if self.scfg.kv_dtype == "int8":
            from repro.serving.quant import quantize_kv_row

            def scat_q(pool, spool, dense):
                qrows, srows = quantize_kv_row(pick(dense, pool.shape[-1]))
                return (pool.at[:, page_ids].set(qrows),
                        spool.at[:, page_ids].set(srows))

            out = []
            for fc, oc in zip(full, one):
                kq, ks = scat_q(fc["attn"]["k_pages"],
                                fc["attn"]["k_scale"], oc["attn"]["k"])
                vq, vs = scat_q(fc["attn"]["v_pages"],
                                fc["attn"]["v_scale"], oc["attn"]["v"])
                out.append({"attn": {"k_pages": kq, "v_pages": vq,
                                     "k_scale": ks, "v_scale": vs}})
            return out

        def scat(pool, dense):
            return pool.at[:, page_ids].set(
                pick(dense, pool.shape[-1]).astype(pool.dtype))

        return _to_pools(full, one, scat)

    # -- prefix cache (kvpool.PrefixCache) ----------------------------------

    def prefix_hit_rate(self) -> float:
        """Cumulative prefix-cache hit rate: cached prompt tokens over
        all prompt tokens admitted (0.0 when the cache is off)."""
        return self._prefix_hit_tokens / max(1, self._prefix_prompt_tokens)

    def _note_prefix(self, req: Request, hit_pages: int) -> None:
        """Account one admission's lookup outcome (counted once per
        admission, when the pinned hit is consumed — not per fits()
        probe, so deferred requests don't skew the rate)."""
        ht = hit_pages * self.pool.page_size
        self._c_plookup.inc()
        self._prefix_prompt_tokens += req.prompt_len
        self.stats["prefix_prompt_tokens"] = self._prefix_prompt_tokens
        if ht:
            self._c_phit.inc(ht)
            self._prefix_hit_tokens += ht
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] = self._prefix_hit_tokens
        self._g_phit_rate.set(self.prefix_hit_rate())

    def _slice_prefix_page(self, scratch, page_idx: int):
        """One full page of a slot's dense-scratch KV rows, every
        layer — the radix node's *full-precision sidecar* (kept at
        scratch dtype for every kv_dtype, so a later hit restores
        exactly the rows this prefill computed)."""
        ps = self.pool.page_size
        lo = page_idx * ps
        return [{m: {n: a[:, :, :, lo:lo + ps, :] for n, a in rows.items()}
                 for m, rows in lc.items()} for lc in scratch]

    def _restore_prefix(self, scratch, payloads):
        """Write a hit's sidecar pages into a fresh scratch's leading
        rows (every array of every layer's cache: K and V, or the
        latent rows)."""
        out = []
        for li, lc in enumerate(scratch):
            new = {}
            for m, arrays in lc.items():
                new[m] = {}
                for n, a in arrays.items():
                    rows = jnp.concatenate(
                        [p[li][m][n] for p in payloads], axis=3)
                    new[m][n] = jax.lax.dynamic_update_slice_in_dim(
                        a, rows.astype(a.dtype), 0, axis=3)
            out.append(new)
        return out

    def _cache_prefix(self, slot: Slot, req: Request) -> None:
        """At prefill completion, insert the prompt's full pages (and
        their scratch-row sidecars) into the radix tree.  The tree
        takes its own pool reference per newly cached page, so the
        pages outlive this slot; decode appends land strictly after
        the full-page prefix, so cached pages are never written again
        (COW guards the invariant anyway)."""
        ps = self.pool.page_size
        full = req.prompt_len // ps
        if full == 0:
            return
        scratch = self._scratch[slot.index]
        payloads = [self._slice_prefix_page(scratch, i)
                    for i in range(full)]
        self.prefix.insert(req.prompt[:full * ps],
                           self.blocks.slot_pages(slot.index)[:full],
                           payloads)

    def _make_sampler(self):
        temp = self.scfg.temperature
        base = jax.random.PRNGKey(self.scfg.seed)
        slot_ids = jnp.arange(self.scfg.batch_slots)

        def sample(logits, token_idx):
            if temp <= 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def one(lg, sid, tid):
                key = jax.random.fold_in(jax.random.fold_in(base, sid), tid)
                return jax.random.categorical(key, lg / temp)
            return jax.vmap(one)(logits, slot_ids,
                                 token_idx).astype(jnp.int32)
        return sample

    def _program(self, name: str, fn: Callable) -> Callable:
        """``jax.jit(fn)`` as the program ``jit_engine_<name>``.  JAX
        runs the Python body only when it traces (a jit cache miss), so
        the body counts ``engine.retraces.<name>`` and opens an
        ``engine.retrace`` span around the trace."""
        retraces = self._obs.registry.counter(
            f"engine.retraces.{name}",
            f"times JAX traced jit_engine_{name} (a jit cache miss)")

        def body(*args):
            retraces.inc()
            with self._obs.tracer.span("engine.retrace", cat="engine",
                                       program=name):
                return fn(*args)
        body.__name__ = body.__qualname__ = f"engine_{name}"
        return jax.jit(body)

    # -- continuous-batching API --------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int, *,
               arrival: Optional[int] = None,
               enc_embeds: Optional[np.ndarray] = None,
               on_token: Optional[Callable[[int, int, bool], None]]
               = None) -> int:
        """Queue one request; returns its request id.  ``arrival`` (in
        engine steps) defaults to "now" — pass a later step to replay a
        timed trace deterministically.  ``on_token(rid, token, done)``
        streams every emitted token the moment the step produces it
        (``done`` marks the final token); the callback runs on the
        engine thread and may call :meth:`cancel` — including on its
        own stream — mid-step."""
        self._check_open("submit")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if prompt.size + max_new > self.scfg.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new}) exceeds "
                f"max_len={self.scfg.max_len}")
        if self.kv_mode == "paged":
            need = pages_for(prompt.size + max_new,
                             self.pool.page_size)
            if need > self.pool.num_pages:
                raise ValueError(
                    f"request needs {need} pages but the pool has "
                    f"{self.pool.num_pages} — it could never run, even "
                    f"alone (raise ServeConfig.pool_pages)")
        rid = self._next_rid
        self._next_rid += 1
        arrival = self.step_count if arrival is None else int(arrival)
        self.sched.submit(Request(
            rid=rid, prompt_len=int(prompt.size), max_new=int(max_new),
            arrival=arrival, prompt=prompt, enc_embeds=enc_embeds))
        if on_token is not None:
            self._on_token[rid] = on_token
        tr = self._obs.tracer
        tr.async_begin("request", rid, prompt_len=int(prompt.size),
                       max_new=int(max_new))
        tr.async_begin("queued", rid)
        self.flight.record_request_event(
            rid, "submitted", prompt_len=int(prompt.size),
            max_new=int(max_new), arrival=arrival)
        if arrival <= self.step_count:
            # TTFT clock starts the moment the request is runnable;
            # future arrivals are stamped when their step comes up.
            self._runnable_at[rid] = time.perf_counter()
        return rid

    def step(self, token_budget: Optional[int] = None
             ) -> Dict[str, List[int]]:
        """One unified engine step under a per-step token budget:

        1. admit arrived requests into free slots (the scheduler
           policy's call) — monolithic admissions prefill whole, chunked
           admissions enter ``PREFILLING`` with a zero prompt cursor;
        2. advance chunked prefills (budget permitting) — a slot whose
           cursor reaches the prompt end emits its first token and
           joins *this* step's decode;
        3. grow paged slots' block tables for the incoming token
           (preempting — FIFO-youngest-first — when the pool is
           exhausted), then run one batched decode over every active
           slot with per-slot positions;
        4. a second admission pass follows the decode, so pages/slots
           reclaimed *this step* (EOS / completion / cancel) are
           immediately reusable by queued requests.

        Decode never starves behind a long prompt: in-flight slots
        decode every step regardless of how much prefill is pending,
        and prefill can't starve either (at least one chunk advances
        per step).  ``token_budget`` overrides ``ServeConfig``'s for
        this step.  Returns the step's events ({admitted, decoded,
        finished, preempted, cancelled} request ids, per-request
        ``ttft_ms`` for first tokens, per-stream ``itl_ms`` gaps, and
        the step's phase ``timings``)."""
        self._check_open("step")
        if self.caches is None:
            self.caches = self.new_cache()
        tr = self._obs.tracer
        t_step = time.perf_counter()
        now = t_step
        for r in self.sched.queue:
            # Trace-replayed arrivals become runnable this step; their
            # TTFT clock starts here, not at submit().
            if r.arrival <= self.step_count and r.rid not in self._runnable_at:
                self._runnable_at[r.rid] = now
        holdover = [s.rid for s in self.sched.active_slots()]
        budget = (self.scfg.token_budget if token_budget is None
                  else int(token_budget))
        events: Dict[str, Any] = {"admitted": [], "decoded": [],
                                  "finished": [], "preempted": [],
                                  "cancelled": list(self._cancel_log),
                                  "ttft_ms": {}, "itl_ms": {}}
        self._cancel_log.clear()
        with tr.span("engine.step", cat="engine",
                     step_num=self.step_count):
            self._admission_pass(events, "arrival")
            admit_ms = (time.perf_counter() - t_step) * 1e3
            t_pf = time.perf_counter()
            prefill_ms = 0.0
            if any(s.state == PREFILLING for s in self.sched.slots):
                self._advance_prefills(events, budget)
                prefill_ms = (time.perf_counter() - t_pf) * 1e3
            if self.kv_mode == "paged":
                self._grow_pages(events)
            active = self.sched.active_slots()
            decode_ms = 0.0
            if active:
                t_dec = time.perf_counter()
                with tr.span("engine.decode.prepare", cat="engine",
                             batch=len(active)):
                    # Slots not decoding stay at position 0: they
                    # decode garbage into their own dead row 0 (dense:
                    # replaced wholesale on re-admission; paged: the
                    # null sink page their table row holds), so the
                    # kernel reads one key for them, not the length a
                    # released slot's last request left.  The clamp
                    # only guards the bound.
                    pos = np.zeros((self.scfg.batch_slots,), np.int32)
                    pos_cap = (self._fresh_len if self.kv_mode == "paged"
                               else self.scfg.max_len) - 1
                    for s in active:
                        pos[s.index] = min(s.length, pos_cap)
                    token_idx = np.zeros((self.scfg.batch_slots,), np.int32)
                    for s in active:
                        token_idx[s.index] = s.generated
                    inputs = [jnp.asarray(self._tok), jnp.asarray(pos)]
                    if self.kv_mode == "paged":
                        inputs.append(jnp.asarray(self._decode_table()))
                        # Pages the kernel reads (each slot attends over
                        # pos + 1 keys) against the table it walks.
                        ps = self.pool.page_size
                        self._c_pages_live.inc(
                            int((pos // ps).sum()) + len(pos))
                        self._c_pages_table.inc(pos.size * self._max_pages)
                    token_idx = jnp.asarray(token_idx)
                with tr.span("engine.decode.dispatch", cat="engine"):
                    logits, self.caches, *held = self._decode(
                        self.params, *inputs, self.caches)
                    toks = self._sample_slots(logits, token_idx)
                    self._note_routed(self.scfg.batch_slots, held)
                with tr.span("engine.decode.wait", cat="engine"):
                    if self._held_dev:
                        # The held counts of this decode and of the
                        # chunks since the last one come back with the
                        # tokens, in the same transfer.
                        toks, counts = jax.device_get(
                            (toks, self._held_dev))
                        self._held_dev = []
                        pairs, reached = np.sum(counts, axis=0)
                        self._c_held.inc(int(pairs))
                        self._c_reached.inc(int(reached))
                    else:
                        toks = np.asarray(toks)
                decode_ms = (time.perf_counter() - t_dec) * 1e3
                self.stats["decode_steps"] += 1
                if events["admitted"] and holdover:
                    # A mid-stream admission shared this decode step
                    # with older in-flight requests — the utilization
                    # win continuous batching exists for.
                    self.stats["shared_steps"] += 1
                # Peak resident KV this step: every active slot just
                # wrote a row at position `length` (pre-increment).
                self._note_kv_tokens(sum(s.length + 1 for s in active))
                with tr.span("engine.emit", cat="engine"):
                    for s in active:
                        if s.state != DECODE:
                            continue    # cancelled mid-step by a callback
                        if self.logits_tap is not None:
                            self.logits_tap(s.rid, s.length, logits[s.index])
                        s.length += 1
                        self._tok[s.index] = toks[s.index]
                        events["decoded"].append(s.rid)
                        self._emit(s, int(toks[s.index]), events)
            if self._cancel_log:
                # Mid-step cancels: a stream callback fired during this
                # decode's emit loop and called cancel().
                events["cancelled"].extend(self._cancel_log)
                self._cancel_log.clear()
            if holdover and active and admit_ms + prefill_ms > decode_ms:
                # In-flight streams waited longer on prefill work than
                # on their own batched decode — the starvation mode
                # chunking exists to bound.
                self._c_starved.inc()
                self.stats["starved_steps"] += 1
            if events["finished"] or events["preempted"] \
                    or events["cancelled"]:
                # Same-step reuse: whatever the decode just freed can
                # admit a queued request now (joins the next decode).
                self._admission_pass(events, "reclaim")
        self._note_kv_tokens(
            sum(s.length for s in self.sched.active_slots()))
        self._g_active.set(len(self.sched.active_slots()))
        self.step_count += 1
        step_ms = (time.perf_counter() - t_step) * 1e3
        events["timings"] = {
            "admit_ms": admit_ms, "prefill_ms": prefill_ms,
            "decode_ms": decode_ms, "step_ms": step_ms,
        }
        self.flight.record_step(
            self.step_count - 1, wall_ms=round(step_ms, 3),
            admitted=len(events["admitted"]),
            decoded=len(events["decoded"]),
            finished=len(events["finished"]),
            preempted=len(events["preempted"]))
        return events

    def _decode_table(self) -> np.ndarray:
        """Block tables as the decode program sees them: slots mid
        chunked-prefill get an all-null-sink row, so the garbage token
        their (inactive) lane writes cannot land on a real page that
        prompt chunks were already scattered into.  Monolithic-only
        runs return the live table untouched (no copy)."""
        table = self.blocks.table
        pre = [s.index for s in self.sched.slots
               if s.state == PREFILLING]
        if not pre:
            return table
        table = table.copy()
        table[pre] = self.pool.num_pages    # the null sink page
        return table

    def _admission_pass(self, events: Dict[str, Any], phase: str) -> None:
        """The single admission entry point — the arrival pass at the
        top of :meth:`step` and the post-reclaim pass after decode both
        funnel through here (``phase`` tags the trace span), so there
        is exactly one place admissions happen."""
        with self._obs.tracer.span("engine.admit", cat="engine",
                                   phase=phase):
            self._admit(events)

    def _admission_signals(self) -> Dict[str, Any]:
        """Live load picture the scheduler policy decides from (the
        ``latency`` policy defers admission when the decode budget is
        saturated or the measured inter-token p99 is over target)."""
        chunk = self.prefill_chunk
        backlog = 0
        if chunk:
            for s in self.sched.slots:
                if s.state == PREFILLING:
                    req = self._slot_req.get(s.index)
                    if req is not None:
                        backlog += min(chunk,
                                       req.prompt_len - s.prefill_pos)
        sig = {
            "token_budget": self.scfg.token_budget,
            "decode_tokens": len(self.sched.active_slots()),
            "prefill_backlog": backlog,
            "itl_p99_ms": (self._h_itl.percentile(99)
                           if self._h_itl.count else None),
        }
        # Rolling-window SLO state rides along so the latency policy
        # can back off admissions while a breach is in progress.
        sig.update(self.slo.signals())
        return sig

    def _admit(self, events: Dict[str, Any]) -> None:
        """Admission pass: free slots AND (paged) enough free pages for
        each prompt, reserved cumulatively in FIFO order.

        The pass is a two-phase pipeline: phase one *dispatches* every
        admission's prefill and (paged) pool scatter without a host
        sync, phase two reads the first tokens back.  JAX async
        dispatch then overlaps admission i's pool scatter with
        admission i+1's prefill attention — the engine-level analogue
        of the kernel's ping-pong page gather (nothing blocks between
        one chunk's scatter and the next chunk's compute)."""
        fits = None
        pins: Dict[int, tuple] = {}     # rid -> pinned (pages, payloads)
        if self.kv_mode == "paged":
            budget = self.pool.free_pages
            state = {"reserved": 0}

            def fits(req):
                # +1: the first decode token writes KV at position
                # prompt_len — for a page-aligned prompt that is a
                # fresh page, and admitting without it would prefill
                # only to self-preempt in _grow_pages the same step.
                ps = self.pool.page_size
                need = pages_for(req.prompt_len + 1, ps)
                if self.prefix is None:
                    headroom = budget
                else:
                    if req.rid not in pins:
                        # Hit capped below the last prompt token: the
                        # final token is always forwarded (its logits
                        # seed decode), so only *full* pages strictly
                        # before it can come from the cache.
                        cap = (req.prompt_len - 1) // ps
                        hit = self.prefix.lookup(req.prompt,
                                                 max_pages=cap)
                        if hit[0]:
                            # Pin: the shared ref keeps these pages out
                            # of this pass's evictable() headroom and
                            # off the evictor entirely.
                            self.pool.share(hit[0])
                        pins[req.rid] = hit
                    # Charge only the unshared suffix; LRU cache-only
                    # pages count as headroom (alloc evicts them via
                    # the pool's reclaimer hook).
                    need -= len(pins[req.rid][0])
                    headroom = (self.pool.free_pages
                                + self.prefix.evictable())
                if state["reserved"] + need > headroom:
                    self._c_rejects.inc()
                    return False
                state["reserved"] += need
                return True
        tr = self._obs.tracer
        inflight = []
        for req in self.sched.pop_admissible(self.step_count, fits=fits):
            if self.prefill_chunk:
                # Chunked admission: the slot enters PREFILLING with a
                # zero prompt cursor and a fresh dense scratch; chunks
                # advance in _advance_prefills under the step budget
                # (the first one this very step).
                slot = self.sched.admit(req, state=PREFILLING)
            else:
                slot = self.sched.admit(req)
            tr.async_end("queued", req.rid)
            tr.async_begin("decode", req.rid, slot=slot.index)
            hit_pages, hit_payloads = pins.pop(req.rid, ([], []))
            if self.kv_mode == "paged":
                # The slot takes ownership of the pinned shared prefix
                # (refs transfer; release is symmetric) and allocates
                # only the unshared suffix.
                pages = self.blocks.assign(slot.index, req.prompt_len,
                                           shared=hit_pages)
                assert pages is not None, "admission fits() reserved these"
                if self.prefix is not None:
                    self._note_prefix(req, len(hit_pages))
            self._slot_req[slot.index] = req
            if self.prefill_chunk:
                self._scratch[slot.index] = init_cache(
                    self.cfg, 1, self._fresh_len,
                    enc_len=self.scfg.enc_len)
                if hit_pages:
                    # Restore the cached pages' full-precision KV rows
                    # into the scratch and start the prompt cursor at
                    # the first uncached page: cached tokens are never
                    # re-forwarded, and the suffix chunks attend over
                    # exactly the rows an uncached prefill would have
                    # computed (bit-identity, any page dtype).
                    self._scratch[slot.index] = self._restore_prefix(
                        self._scratch[slot.index], hit_payloads)
                    slot.prefill_pos = \
                        len(hit_pages) * self.pool.page_size
            else:
                inflight.append((slot, req,
                                 self._prefill_slot(slot, req)))
            self.stats["admitted"] += 1
            events["admitted"].append(req.rid)
            self.flight.record_request_event(
                req.rid, "admitted", slot=slot.index,
                step=self.step_count)
        # Unpin fits()-approved requests the policy did not select this
        # pass (they stay queued; the next pass re-pins).
        for hp, _ in pins.values():
            if hp:
                self.pool.release(hp)
        for slot, req, tok0_dev in inflight:
            # First host sync of the pass: every later admission's
            # prefill + scatter is already in the device queue.
            with tr.span("engine.first_token", cat="engine", rid=req.rid):
                tok0 = int(np.asarray(tok0_dev))
            self._tok[slot.index] = tok0
            with tr.span("engine.emit", cat="engine"):
                self._emit(slot, tok0, events)
        self._note_kv_tokens(
            sum(s.length for s in self.sched.active_slots()))

    def _advance_prefills(self, events: Dict[str, Any],
                          budget: int) -> None:
        """Spend the step's prefill token allowance on prompt chunks,
        oldest admission first.  Decode claims one budget token per
        active slot up front (decode never starves); what's left buys
        chunks.  Unbudgeted (``budget == 0``) every PREFILLING slot
        advances exactly one chunk — maximal interleave.  Forward
        progress is guaranteed either way: the first chunk always runs,
        so prefill can't starve behind a saturated decode."""
        chunk = self.prefill_chunk
        avail = None
        if budget > 0:
            avail = budget - len(self.sched.active_slots())
        advanced = 0
        for slot in self.sched.prefilling_slots():
            while slot.state == PREFILLING:
                if advanced and avail is not None and avail < chunk:
                    return
                self._prefill_chunk_step(slot, events)
                advanced += 1
                if avail is not None:
                    avail -= chunk
                if budget <= 0:
                    break       # unbudgeted: one chunk per slot per step

    def _prefill_chunk_step(self, slot: Slot, events: Dict[str, Any]
                            ) -> None:
        """Advance one slot's prefill by one chunk: run prompt tokens
        [cursor, cursor+chunk) against the slot's dense scratch at the
        cursor's offset (causal attention over the scratch's growing
        prefix — the write offset and RoPE base are the cursor), then
        (paged) scatter exactly that chunk's pages into the pool along
        the slot's block-table row.  The final chunk yields the seed
        token — greedy from the prompt's last-position logits, exactly
        the monolithic path's — and flips the slot to DECODE so it
        joins the current step's batch."""
        req = self._slot_req[slot.index]
        chunk, plen = self.prefill_chunk, req.prompt_len
        c0 = slot.prefill_pos
        take = min(chunk, plen - c0)
        # Buffer sized to the page-aligned take, never the full chunk:
        # the KV write window is [c0, c0+buf), and a full-chunk buffer
        # on a tail chunk (or a cursor advanced past cached pages) can
        # cross the scratch end — dynamic_update_slice would *clamp*
        # the start and corrupt rows below the cursor.  Aligned take
        # keeps c0+buf <= ceil(plen/ps)*ps <= scratch rows, always.
        if self.kv_mode == "paged":
            ps = self.pool.page_size
            buf = pages_for(take, ps) * ps
        else:
            buf = min(chunk, self._fresh_len - c0)
        tr = self._obs.tracer
        with tr.span("engine.prefill_chunk", cat="engine", rid=req.rid,
                     lo=c0, take=take):
            toks = np.zeros((1, buf), np.int32)
            toks[0, :take] = req.prompt[c0:c0 + take]
            batch: Dict[str, jax.Array] = {"tokens": jnp.asarray(toks)}
            if req.enc_embeds is not None:
                batch["enc_embeds"] = jnp.asarray(req.enc_embeds)
            logits, self._scratch[slot.index], *held = \
                self._prefill_chunk_fn(self.params, batch,
                                       self._scratch[slot.index],
                                       jnp.asarray(c0, jnp.int32))
            self._note_routed(buf, held)
            if self.kv_mode == "paged":
                # Incremental page scatter: only this chunk's pages
                # move.  Chunks are page-aligned, so the cursor sits on
                # a page boundary; spans past the slot's table (or the
                # scratch) clamp onto the null sink / last page — the
                # sink absorbs what the clamp duplicates.
                cpp = buf // ps
                p_lo = c0 // ps
                mp = self._max_pages
                ids = np.full((cpp,), self.pool.num_pages, np.int32)
                seg = self.blocks.table[slot.index][p_lo:p_lo + cpp]
                ids[:seg.size] = seg
                src = np.clip(np.arange(p_lo, p_lo + cpp), 0, mp - 1) \
                    .astype(np.int32)
                self.caches = self._insert_chunk(
                    self.caches, self._scratch[slot.index],
                    jnp.asarray(ids), jnp.asarray(src))
            self.stats["prefill_chunks"] += 1
            self._c_chunks.inc()
        slot.prefill_pos = c0 + take
        if slot.prefill_pos < plen:
            return
        # Last chunk: dense mode inserts the whole scratch row (KV and
        # all — same leak-free slot replacement as monolithic); paged
        # mode already scattered every page.  Seed token, then DECODE.
        if self.kv_mode != "paged":
            self.caches = self._insert(
                self.caches, self._scratch[slot.index],
                jnp.asarray(slot.index, jnp.int32))
        if self.prefix is not None:
            # Cache the completed prompt's full pages (+ sidecars)
            # while the scratch still holds their full-precision rows.
            self._cache_prefix(slot, req)
        self._scratch.pop(slot.index, None)
        with tr.span("engine.first_token", cat="engine", rid=req.rid):
            tok0, row = self._first_token(
                logits, jnp.asarray(take - 1, jnp.int32))
            if self.logits_tap is not None:
                self.logits_tap(req.rid, plen - 1, row)
            tok0 = int(np.asarray(tok0))
        slot.state = DECODE
        slot.length = plen
        self.stats["prefills"] += 1
        self._tok[slot.index] = tok0
        with tr.span("engine.emit", cat="engine"):
            self._emit(slot, tok0, events)

    def cancel(self, rid: int) -> bool:
        """Drop a request wherever it is — queued, mid chunked-prefill,
        or mid-decode — releasing its slot and (paged) its pages the
        same step, so a reclaim admission pass can reuse them before
        the next decode.  Partial output is discarded.  Safe to call
        from an ``on_token`` stream callback (including the stream's
        own).  Returns False when ``rid`` is unknown or already
        finished (finished results stay retrievable via
        :meth:`result`)."""
        self._check_open("cancel")
        tr = self._obs.tracer
        req = self.sched.cancel(rid)
        if req is not None:                      # still queued
            self._runnable_at.pop(rid, None)
            self._on_token.pop(rid, None)
            self.stats["cancelled"] += 1
            self._cancel_log.append(rid)
            tr.async_end("queued", rid)
            tr.async_end("request", rid, cancelled=True)
            self.flight.record_request_event(rid, "cancelled",
                                             queued=True)
            return True
        for slot in self.sched.slots:
            if slot.rid == rid and slot.state in (DECODE, PREFILLING):
                self._out.pop(rid, None)
                self._scratch.pop(slot.index, None)
                self._slot_req.pop(slot.index, None)
                if self.kv_mode == "paged":
                    # Same-step reclaim, exactly like EOS/completion.
                    self.blocks.release(slot.index)
                self.sched.release(slot)
                self._runnable_at.pop(rid, None)
                self._last_emit.pop(rid, None)
                self._on_token.pop(rid, None)
                self.stats["cancelled"] += 1
                self._cancel_log.append(rid)
                tr.instant("cancel", cat="engine", rid=rid)
                tr.async_end("decode", rid)
                tr.async_end("request", rid, cancelled=True)
                self.flight.record_request_event(rid, "cancelled",
                                                 queued=False)
                return True
        return False

    def _grow_pages(self, events: Dict[str, List[int]]) -> None:
        """Before a paged decode, every active slot needs a table entry
        for the KV row the incoming token writes (position ``length``).
        When the pool is exhausted, the *youngest* admission (largest
        admit_seq) is preempted — pages reclaimed, request requeued at
        the head — until the append succeeds; oldest slots grow first,
        so the policy is deterministic and FIFO-fair (a victim can
        never be older than the slot it yields to)."""
        with self._obs.tracer.span("engine.grow_pages", cat="engine"):
            for s in sorted(self.sched.active_slots(),
                            key=lambda s: s.admit_seq):
                if s.state != DECODE:
                    continue        # preempted by an earlier iteration
                if self.prefix is not None \
                        and not self._cow_guard(s, events):
                    continue        # s preempted itself finding a copy
                while not self.blocks.extend_to(s.index, s.length + 1):
                    victim = max(self.sched.active_slots(),
                                 key=lambda v: v.admit_seq)
                    self._preempt(victim, events)
                    if victim is s:
                        break       # s yielded its own pages; skip it

    def _cow_guard(self, s: Slot, events: Dict[str, Any]) -> bool:
        """Copy-on-write before a decode write lands in a shared page:
        if the page covering position ``length`` (this step's KV write)
        has other referents, duplicate it into a fresh exclusive page
        first — sharers keep the original bits.  By construction cached
        pages sit strictly before the first decode position, so this is
        a safety invariant, not a hot path.  False means the slot
        preempted itself paying for the copy (skip its extend)."""
        idx = s.length // self.pool.page_size
        spages = self.blocks.slot_pages(s.index)
        if idx >= len(spages) or self.pool.refcount(spages[idx]) <= 1:
            return True
        res = self.blocks.cow(s.index, idx)
        while res is None:          # pool exhausted even after eviction
            victim = max(self.sched.active_slots(),
                         key=lambda v: v.admit_seq)
            self._preempt(victim, events)
            if victim is s:
                return False
            res = self.blocks.cow(s.index, idx)
        src, dst = res
        if src != dst:
            self.caches = self._copy_page(
                self.caches, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32))
            self.stats["cow_copies"] += 1
        return True

    def _preempt(self, slot: Slot, events: Dict[str, Any]) -> None:
        """Evict a mid-decode request to reclaim its pages: partial
        output is discarded and the original request returns to the
        head of the queue (greedy decoding regenerates the identical
        stream on re-admission)."""
        rid = slot.rid
        self._out.pop(rid, None)
        self._last_emit.pop(rid, None)
        self.blocks.release(slot.index)
        req = self._slot_req.pop(slot.index)
        self.sched.release(slot)
        self.sched.requeue(req)
        self.stats["preemptions"] += 1
        events["preempted"].append(rid)
        tr = self._obs.tracer
        tr.instant("preempt", cat="engine", rid=rid)
        tr.async_end("decode", rid)
        tr.async_begin("queued", rid)
        # Storm detection: enough preemptions inside one window of
        # steps trips the flight recorder.
        self.flight.note_preemption(self.step_count, rid)
        # The regenerated stream re-measures TTFT from the eviction.
        self._runnable_at[rid] = time.perf_counter()

    def drain(self) -> Dict[int, np.ndarray]:
        """Step until the queue and all slots are empty; returns (and
        clears) every finished request's tokens, keyed by request id."""
        self._check_open("drain")
        while not self.sched.done():
            self.step()
        out, self._finished = self._finished, {}
        return out

    def result(self, rid: int) -> Optional[np.ndarray]:
        """Finished tokens for ``rid`` (None while still in flight)."""
        return self._finished.get(rid)

    def _emit(self, slot: Slot, tok: int, events: Dict[str, Any]
              ) -> None:
        rid = slot.rid
        self._out.setdefault(rid, []).append(int(tok))
        slot.generated += 1
        self._c_tokens.inc()
        now = time.perf_counter()
        tr = self._obs.tracer
        t0 = self._runnable_at.pop(rid, None)
        if t0 is not None:
            # First token since the request became runnable (or since
            # its last preemption): this IS the TTFT sample.
            ttft_ms = (now - t0) * 1e3
            self._h_ttft.observe(ttft_ms)
            self.slo.observe_ttft(ttft_ms)
            events["ttft_ms"][rid] = ttft_ms
            self.flight.record_request_event(
                rid, "first_token", ttft_ms=round(ttft_ms, 3))
            tr.flow(f"req{rid}", rid, "start", cat="reqflow")
        else:
            prev = self._last_emit.get(rid)
            if prev is not None:
                # Inter-token latency is what the *stream* sees: the
                # wall-clock gap since this request's previous token —
                # so a monolithic neighbor's prefill blowing up a step
                # shows here, where per-step decode timing would hide
                # it.  First tokens are TTFT, never ITL.
                gap_ms = (now - prev) * 1e3
                self._h_itl.observe(gap_ms)
                self.slo.observe_itl(gap_ms)
                events["itl_ms"][rid] = gap_ms
                tr.flow(f"req{rid}", rid, "step", cat="reqflow")
        self._last_emit[rid] = now
        eos = (self.scfg.eos_id is not None
               and int(tok) == int(self.scfg.eos_id))
        if eos:
            self.stats["eos_exits"] += 1
        done = slot.generated >= slot.max_new or eos
        if done:
            self._finished[rid] = np.asarray(self._out.pop(rid), np.int32)
            self.stats["finished"] += 1
            events["finished"].append(rid)
            self._slot_req.pop(slot.index, None)
            self._last_emit.pop(rid, None)
            if self.kv_mode == "paged":
                # Immediate reclaim: the slot's pages return to the pool
                # the step the request ends, not when the slot refills.
                self.blocks.release(slot.index)
            self.sched.release(slot)
            tr.flow(f"req{rid}", rid, "end", cat="reqflow")
            tr.async_end("decode", rid)
            tr.async_end("request", rid, tokens=slot.generated, eos=eos)
            self.flight.record_request_event(
                rid, "finished", tokens=int(slot.generated),
                eos=bool(eos))
        cb = (self._on_token.pop(rid, None) if done
              else self._on_token.get(rid))
        if cb is not None:
            # Streamed to the caller the moment the step produced it;
            # the callback may cancel() any stream, including this one.
            cb(rid, int(tok), done)

    def _prefill_slot(self, slot: Slot, req: Request) -> jax.Array:
        """Dispatch one admission's prefill into its slot: pad the
        prompt to its bucket, run it against a *fresh* single-slot
        cache (zero recurrent state, zero KV — no leakage from the
        previous occupant), insert the result at the slot index, and
        return the first generated token (greedy from the prompt's
        last-position logits, exactly the legacy generate() seed token)
        as an *unsynced device value* — the caller reads it back after
        dispatching every admission in the pass, so this prefill's pool
        scatter overlaps the next admission's attention."""
        plen = req.prompt_len
        bucket = (plen if self._exact_prefill
                  else _bucket_for(plen, self.scfg.max_len))
        with self._obs.tracer.span("engine.prefill", cat="engine",
                                   rid=req.rid, plen=plen, bucket=bucket):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :plen] = req.prompt
            batch: Dict[str, jax.Array] = {"tokens": jnp.asarray(toks)}
            if req.enc_embeds is not None:
                batch["enc_embeds"] = jnp.asarray(req.enc_embeds)
            fresh = init_cache(self.cfg, 1, self._fresh_len,
                               enc_len=self.scfg.enc_len)
            logits, one, *held = self._prefill_full(self.params, batch,
                                                    fresh)
            self._note_routed(bucket, held)
            if self.kv_mode == "paged":
                # Scatter the dense scratch into the pool along this
                # slot's block-table row (prompt pages; the tail lands
                # on the null sink) — prefill *inserts pages*, decode
                # appends rows.  Dispatched, not synced: it pipelines
                # behind whatever the caller launches next.
                self.caches = self._insert(
                    self.caches, one,
                    jnp.asarray(self.blocks.table[slot.index]))
            else:
                self.caches = self._insert(
                    self.caches, one, jnp.asarray(slot.index, jnp.int32))
            self.stats["prefills"] += 1
            slot.length = plen
            tok0, row = self._first_token(logits,
                                          jnp.asarray(plen - 1, jnp.int32))
            if self.logits_tap is not None:
                self.logits_tap(req.rid, plen - 1, row)
            return tok0

    # -- legacy one-shot API (reimplemented on the continuous loop) ---------

    def generate(self, prompts: np.ndarray, max_new: int,
                 enc_embeds: Optional[np.ndarray] = None
                 ) -> np.ndarray:
        """prompts: (B, S) int32 (B == batch_slots); returns (B, max_new).

        All B requests are admitted at the same step and decode in
        lockstep — the uniform-batch special case of the continuous
        loop, numerics-identical to the historical one-shot engine for
        greedy decoding (row i never sees any other row's state).  With
        ``eos_id`` set, a row that exits early is right-padded with the
        eos token to ``max_new`` so the result stays rectangular (the
        pad *is* the stream's terminator; use submit()/drain() for the
        unpadded ragged outputs).
        """
        self._check_open("generate")
        b, s = prompts.shape
        assert b == self.scfg.batch_slots
        if not self.sched.done():
            raise RuntimeError(
                "generate() needs an idle engine; drain() in-flight "
                "requests first (or use submit()/step() throughout)")
        rids = []
        for i in range(b):
            ee = None if enc_embeds is None else \
                np.asarray(enc_embeds[i:i + 1])
            rids.append(self.submit(prompts[i], max_new, enc_embeds=ee))
        res = self.drain()
        rows = []
        for r in rids:
            row = res[r]
            if row.size < max_new:          # EOS early exit
                row = np.concatenate(
                    [row, np.full((max_new - row.size,), self.scfg.eos_id,
                                  np.int32)])
            rows.append(row)
        return np.stack(rows)
