"""Continuous-batching serving: ragged-batch numerics vs one-shot
generation, slot eviction/re-admission hygiene, arrival-order
invariance (property), scheduler bookkeeping, and the engine-lifecycle
regression (close() idempotency / use-after-close)."""

import dataclasses

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # deterministic fallback sampler, see _hypothesis_stub
    from _hypothesis_stub import given, settings, st

from repro import configs as C
from repro.models import init_params
from repro.serving.engine import (ServeConfig, ServeEngine, _bucket_for,
                                  prefill_buckets)
from repro.serving.scheduler import Request, Scheduler

pytestmark = pytest.mark.serving

CFG = C.get_smoke("smollm_360m")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)

# Ragged prompt lengths from the issue: a 3-slot batch at 5/17/1.
RAGGED = (5, 17, 1)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return {L: rng.integers(0, CFG.vocab_size, size=(L,)).astype(np.int32)
            for L in lengths}


def _oneshot(cfg, params, prompt, max_new, **scfg_kw):
    """Reference: a single request through a 1-slot engine."""
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=1, max_len=64,
                                               **scfg_kw))
    try:
        return eng.generate(prompt[None, :], max_new)[0]
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Ragged-batch numerics
# ---------------------------------------------------------------------------


def test_ragged_three_slot_bit_identical_int8():
    """A ragged 3-slot batch (lengths 5/17/1) under int8 weight-only
    quantization decodes bit-identically to three independent one-shot
    generate() calls: per-slot positions, per-slot length masking and
    the slot-wise prefill insert must keep every row fully independent."""
    prompts = _prompts(RAGGED)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=3, max_len=64,
                                               quantize=True))
    try:
        rids = {L: eng.submit(prompts[L], 8) for L in RAGGED}
        res = eng.drain()
    finally:
        eng.close()
    for L in RAGGED:
        want = _oneshot(CFG, PARAMS, prompts[L], 8, quantize=True)
        np.testing.assert_array_equal(
            want, res[rids[L]],
            err_msg=f"slot with prompt_len={L} diverged from one-shot")


def test_ragged_three_slot_bf16_tolerance():
    """Same ragged batch on a bf16 compute/cache config: greedy token
    streams must agree within float tolerance (cache *bugs* produce
    chance-level ~1/vocab agreement, rounding-order drift at worst a
    few near-tie flips)."""
    cfg = dataclasses.replace(CFG, name="smoke-bf16",
                              compute_dtype="bfloat16",
                              cache_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(RAGGED)
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=3, max_len=64))
    try:
        rids = {L: eng.submit(prompts[L], 8) for L in RAGGED}
        res = eng.drain()
    finally:
        eng.close()
    for L in RAGGED:
        want = _oneshot(cfg, params, prompts[L], 8)
        agree = float(np.mean(want == res[rids[L]]))
        assert agree >= 0.75, \
            f"prompt_len={L}: {agree:.2f} agreement — stale cache?"


def test_uniform_generate_matches_oneshot_rows():
    """The legacy generate() (reimplemented on the continuous loop) is
    numerics-identical for a uniform batch: every row matches the same
    prompt run alone."""
    rng = np.random.default_rng(3)
    prompts = rng.integers(0, CFG.vocab_size, size=(3, 8)).astype(np.int32)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=3, max_len=64))
    try:
        out = eng.generate(prompts, max_new=6)
        again = eng.generate(prompts, max_new=6)
    finally:
        eng.close()
    np.testing.assert_array_equal(out, again)   # greedy + persistent cache
    for i in range(3):
        np.testing.assert_array_equal(
            out[i], _oneshot(CFG, PARAMS, prompts[i], 6))


def _manual_greedy(cfg, params, prompt, max_new):
    """Exact-length prefill + scalar-position decode through the raw
    model API (the pre-continuous-batching path): an engine-independent
    oracle.  A bucket-padded prefill that let pad tokens advance
    recurrent state (mamba/rwkv shift/SSM/WKV) would diverge from it."""
    import jax.numpy as jnp

    from repro.models import decode_step, init_cache, prefill
    s = len(prompt)
    caches = init_cache(cfg, 1, s + max_new + 4)
    last, caches = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                           cfg, caches)
    out = []
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    for i in range(max_new):
        out.append(int(tok[0]))
        lg, caches = decode_step(params, tok, jnp.asarray(s + i), cfg,
                                 caches)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    return np.asarray(out, np.int32)


def test_ragged_recurrent_arch_matches_model_oracle():
    """Ragged continuous batching over a *stateful* mixer (RWKV): the
    per-slot prefill insert must carry recurrent state (not just KV)
    into the right slot, and prompt padding must not advance that state
    past the real prompt — so the engine must match an exact-length
    prefill + decode loop through the raw model API (prompt length 11
    is deliberately off-bucket)."""
    cfg = C.get_smoke("rwkv6_3b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    prompts = {L: rng.integers(0, cfg.vocab_size, size=(L,)
                               ).astype(np.int32) for L in (4, 11)}
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=2, max_len=64))
    try:
        rids = {L: eng.submit(prompts[L], 6) for L in (4, 11)}
        res = eng.drain()
    finally:
        eng.close()
    for L in (4, 11):
        np.testing.assert_array_equal(
            res[rids[L]], _manual_greedy(cfg, params, prompts[L], 6),
            err_msg=f"recurrent state corrupted (prompt_len={L})")


def test_bucketed_prefill_matches_model_oracle():
    """Attention-only archs prefill off-bucket prompts padded to a pow2
    bucket; causal masking + length masking must make the pads
    invisible — the engine must equal an exact-length prefill + decode
    loop through the raw model API."""
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, CFG.vocab_size, size=(13,)).astype(np.int32)
    got = _oneshot(CFG, PARAMS, prompt, 6)          # bucket = 16 > 13
    np.testing.assert_array_equal(got, _manual_greedy(CFG, PARAMS,
                                                      prompt, 6))


# ---------------------------------------------------------------------------
# Slot reuse / eviction hygiene
# ---------------------------------------------------------------------------


def test_eviction_readmission_no_stale_kv():
    """A slot that served a long request must serve a later (shorter)
    one without any KV/state leakage: the re-admitted request's output
    equals a fresh engine's."""
    prompts = _prompts((20, 4), seed=5)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=64))
    try:
        first = eng.submit(prompts[20], 10)
        res1 = eng.drain()
        assert len(res1[first]) == 10
        second = eng.submit(prompts[4], 6)     # reuses slot 0
        res2 = eng.drain()
    finally:
        eng.close()
    np.testing.assert_array_equal(
        res2[second], _oneshot(CFG, PARAMS, prompts[4], 6),
        err_msg="re-admitted slot leaked the previous occupant's KV")


def test_midstream_admission_shares_decode_step():
    """A request arriving mid-decode must join an older request's decode
    step (the continuous-batching utilization win), and the engine must
    count it."""
    prompts = _prompts((6, 7), seed=7)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=2, max_len=64))
    try:
        eng.submit(prompts[6], 10, arrival=0)
        eng.submit(prompts[7], 6, arrival=3)
        shared = False
        while not eng.sched.done():
            ev = eng.step()
            older = set(ev["decoded"]) - set(ev["admitted"])
            if ev["admitted"] and older:
                shared = True
        assert shared
        assert eng.stats["shared_steps"] >= 1
        assert eng.stats["finished"] == 2
    finally:
        eng.close()


def test_decode_page_counters_and_released_slot_length():
    """Each paged decode step counts the KV pages its kernel reads
    (``ceil((pos + 1) / page)`` summed over slots) and the table it
    holds (slots x max_pages).  Only decoding slots carry their length:
    a released slot and a never-used one decode at position 0, so the
    kernel reads one key for them, not a finished request's length."""
    from repro import obs
    obs.reset()
    prompts = _prompts((20, 7), seed=9)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=3, max_len=64, kv="paged", page_size=16))
    seen = []
    decode = eng._decode

    def spy(params, tok, pos, table, caches):
        seen.append(np.asarray(pos).copy())
        return decode(params, tok, pos, table, caches)

    eng._decode = spy
    try:
        long_rid = eng.submit(prompts[20], 8)
        short_rid = eng.submit(prompts[7], 2)
        res = eng.drain()
        counters = obs.get_obs().registry.counters
        live = counters["engine.decode.kv_pages_live"].value
        table = counters["engine.decode.kv_pages_table"].value
    finally:
        eng.close()
        obs.reset()
    assert len(res[long_rid]) == 8 and len(res[short_rid]) == 2
    pos = np.stack(seen)                      # (decode steps, slots)
    # The long request decodes from its prompt length on, one a step.
    np.testing.assert_array_equal(pos[:, 0], 20 + np.arange(len(pos)))
    # The short one decodes at 7, then its released slot sits at 0
    # while the long one runs on; the third slot never held a request.
    assert pos[0, 1] == 7 and (pos[1:, 1] == 0).all() and len(pos) > 1
    assert (pos[:, 2] == 0).all()
    assert live == sum(int(np.ceil((p + 1) / 16).sum()) for p in pos)
    assert live == sum(-(-(20 + i + 1) // 16) + 2 for i in range(len(pos)))
    assert table == len(pos) * 3 * (64 // 16)

# ---------------------------------------------------------------------------
# Property: outputs are invariant to arrival order/spacing
# ---------------------------------------------------------------------------

_PROP_LENGTHS = (3, 9, 5, 12)
_PROP_MAX_NEW = (6, 4, 8, 5)
_PROP_PROMPTS = _prompts(_PROP_LENGTHS, seed=11)
_PROP_REFS = {}


def _prop_ref(L, max_new):
    if L not in _PROP_REFS:
        _PROP_REFS[L] = _oneshot(CFG, PARAMS, _PROP_PROMPTS[L], max_new)
    return _PROP_REFS[L]


_PROP_ENGINE = None


def _get_prop_engine():
    """One shared 2-slot engine for every drawn example: the compiled
    programs are reused, and a drained engine is (by design) safe to
    reuse — slot hygiene is exactly what the property exercises."""
    global _PROP_ENGINE
    if _PROP_ENGINE is None:
        _PROP_ENGINE = ServeEngine(CFG, PARAMS,
                                   ServeConfig(batch_slots=2, max_len=64))
    return _PROP_ENGINE


@given(st.tuples(
    st.integers(min_value=0, max_value=3),       # permutation index seed
    st.integers(min_value=0, max_value=4),       # arrival stagger
))
@settings(max_examples=8, deadline=None)
def test_arrival_order_invariance(draw):
    """Whatever order requests arrive in — and however their arrivals
    interleave with in-flight decodes — each request's tokens equal its
    one-shot reference (the schedule affects *when*, never *what*)."""
    perm_seed, stagger = draw
    order = list(np.random.default_rng(perm_seed).permutation(
        len(_PROP_LENGTHS)))
    eng = _get_prop_engine()
    assert eng.sched.done()
    rids = {}
    base = eng.step_count
    for j, i in enumerate(order):
        L, mn = _PROP_LENGTHS[i], _PROP_MAX_NEW[i]
        rids[L] = (eng.submit(_PROP_PROMPTS[L], mn,
                              arrival=base + j * stagger), mn)
    res = eng.drain()
    for L, (rid, mn) in rids.items():
        np.testing.assert_array_equal(
            res[rid], _prop_ref(L, mn),
            err_msg=f"order={order} stagger={stagger} prompt_len={L}")


# ---------------------------------------------------------------------------
# Scheduler bookkeeping + engine lifecycle
# ---------------------------------------------------------------------------


def test_scheduler_fifo_and_arrival_gating():
    s = Scheduler(2)
    for rid, arr in ((0, 0), (1, 0), (2, 0), (3, 9)):
        s.submit(Request(rid=rid, prompt_len=4, max_new=2, arrival=arr))
    picked = s.pop_admissible(step=0)
    assert [r.rid for r in picked] == [0, 1]     # FIFO, capped by slots
    slots = [s.admit(r) for r in picked]
    assert s.admissible(step=0) == []            # no free slot
    s.release(slots[0])
    assert [r.rid for r in s.admissible(step=0)] == [2]
    assert [r.rid for r in s.admissible(step=9)] == [2]  # still 1 slot
    s.admit(s.pop_admissible(step=9)[0])
    assert not s.done()                          # rid 3 still queued


def test_submit_validation():
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=16,
                                               pretune=False))
    try:
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="max_new"):
            eng.submit(np.zeros((4,), np.int32), 0)
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.zeros((10,), np.int32), 10)
    finally:
        eng.close()


def test_close_idempotent_and_use_after_close_raises():
    """Regression: close() must be safely idempotent, and any serving
    call on a closed engine must fail with a clear error instead of
    tracing GEMMs through a torn-down pack context."""
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=32,
                                               pretune=False))
    eng.close()
    eng.close()                                   # idempotent, no raise
    assert eng.closed
    prompts = np.zeros((1, 4), np.int32)
    with pytest.raises(RuntimeError, match="closed"):
        eng.generate(prompts, 2)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(prompts[0], 2)
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()
    with pytest.raises(RuntimeError, match="closed"):
        eng.drain()


def test_prefill_buckets():
    assert prefill_buckets(64) == [8, 16, 32, 64]
    assert prefill_buckets(100)[-1] == 100
    assert _bucket_for(5, 64) == 8
    assert _bucket_for(64, 64) == 64
    with pytest.raises(ValueError, match="exceeds"):
        _bucket_for(65, 64)


# ---------------------------------------------------------------------------
# Chunked prefill: bit-identity across chunk sizes / layouts / dtypes
# ---------------------------------------------------------------------------

# Prompt lengths chosen to hit every chunk-boundary shape at least once
# across the drawn chunk sizes: chunk == page_size (8), a 1-token tail
# (17 = 2*8+1, 33 = 2*16+1), chunk > prompt (64 > everything), and a
# single-token prompt.
_CHUNK_LENGTHS = (5, 17, 33, 1, 24)
_CHUNK_MAX_NEW = (6, 4, 8, 5, 3)
_CHUNK_PROMPTS = _prompts(_CHUNK_LENGTHS, seed=21)
_CHUNK_REFS = {}


def _chunked_outputs(chunk, kv, kv_dtype=None, budget=0, policy="fifo"):
    """Replay the fixed staggered workload through a 4-slot engine with
    the given chunking knobs; returns (per-request outputs, stats)."""
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=4, max_len=64, kv=kv, page_size=8,
        kv_dtype=kv_dtype, prefill_chunk=chunk, token_budget=budget,
        policy=policy))
    try:
        rids = [eng.submit(_CHUNK_PROMPTS[L], mn, arrival=i)
                for i, (L, mn) in enumerate(zip(_CHUNK_LENGTHS,
                                                _CHUNK_MAX_NEW))]
        res = eng.drain()
        stats = dict(eng.stats)
    finally:
        eng.close()
    return [res[r] for r in rids], stats


def _monolithic_ref(kv, kv_dtype=None):
    key = (kv, kv_dtype)
    if key not in _CHUNK_REFS:
        outs, stats = _chunked_outputs(0, kv, kv_dtype)
        assert stats["prefill_chunks"] == 0
        _CHUNK_REFS[key] = outs
    return _CHUNK_REFS[key]


@given(st.tuples(
    st.sampled_from((3, 8, 16, 64)),     # page-size, odd tails, > prompt
    st.sampled_from(("dense", "paged")),
    st.sampled_from((0, 12)),            # unbudgeted vs tight budget
))
@settings(max_examples=8, deadline=None)
def test_chunked_prefill_bit_identical(draw):
    """Chunked prefill must be invisible in the outputs: for greedy
    decode, every request's token stream equals the monolithic run's
    whatever the chunk size (page-aligned, 1-token tail, chunk larger
    than the whole prompt), KV layout, or step token budget — chunking
    changes *when* prompt KV is written, never what attention over it
    computes.  (Paged mode rounds chunk 3 up to the 8-token page.)"""
    chunk, kv, budget = draw
    want = _monolithic_ref(kv)
    got, stats = _chunked_outputs(chunk, kv, budget=budget)
    assert stats["prefill_chunks"] > 0      # the chunked path really ran
    for L, w, g in zip(_CHUNK_LENGTHS, want, got):
        np.testing.assert_array_equal(
            w, g, err_msg=f"chunk={chunk} kv={kv} budget={budget} "
                          f"prompt_len={L} diverged from monolithic")


def test_chunked_prefill_bit_identical_int8_pages():
    """Same invariant through the quantized page pool: the int8 chunked
    run must match the int8 monolithic run exactly (both quantize the
    same K/V rows — per chunk vs per prompt — so even the quantization
    noise is identical)."""
    want = _monolithic_ref("paged", "int8")
    for chunk, budget in ((8, 0), (16, 10)):
        got, stats = _chunked_outputs(chunk, "paged", kv_dtype="int8",
                                      budget=budget)
        assert stats["prefill_chunks"] > 0
        for L, w, g in zip(_CHUNK_LENGTHS, want, got):
            np.testing.assert_array_equal(
                w, g, err_msg=f"int8 chunk={chunk} budget={budget} "
                              f"prompt_len={L}")


def test_chunked_prefill_validation_and_rounding():
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=32,
                                             prefill_chunk=-2,
                                             pretune=False))
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=1, max_len=32, kv="paged", page_size=8,
        prefill_chunk=3, pretune=False))
    try:
        assert eng.prefill_chunk == 8       # rounded up to the page
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Cancellation: same-step reclaim, later streams unaffected
# ---------------------------------------------------------------------------


def test_cancel_reclaims_same_step_and_streams_unaffected():
    """Cancelling a mid-decode request from its own stream callback
    must free its slot *and* KV pages within the same engine step — the
    queued request is admitted by that step's reclaim pass — and must
    not perturb any other stream's tokens."""
    prompts = _prompts((9, 7, 6), seed=31)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=2, max_len=64,
                                               kv="paged", page_size=8))
    try:
        seen = []

        def cb(rid, tok, done):
            seen.append(tok)
            if len(seen) == 3:
                assert eng.cancel(rid)

        ra = eng.submit(prompts[9], 12, on_token=cb)
        rb = eng.submit(prompts[7], 8)
        rc = eng.submit(prompts[6], 6)      # queued: both slots busy
        cancel_ev = None
        while not eng.sched.done():
            ev = eng.step()
            if ra in ev["cancelled"]:
                assert cancel_ev is None
                cancel_ev = ev
        assert cancel_ev is not None and len(seen) == 3
        # Same-step reuse: the queued request took the cancelled slot.
        assert rc in cancel_ev["admitted"], \
            "cancelled slot/pages not re-admitted in the same step"
        assert eng.stats["cancelled"] == 1
        res = eng.drain()
        assert ra not in res                # cancelled: no result
        # All pages returned once everything drained.
        assert eng.pool.free_pages == eng.pool.num_pages
    finally:
        eng.close()
    np.testing.assert_array_equal(
        res[rb], _oneshot(CFG, PARAMS, prompts[7], 8),
        err_msg="stream decoding beside the cancel diverged")
    np.testing.assert_array_equal(
        res[rc], _oneshot(CFG, PARAMS, prompts[6], 6),
        err_msg="stream admitted into the cancelled slot diverged")


def test_cancel_queued_and_unknown():
    prompts = _prompts((4, 5), seed=33)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=64))
    try:
        ra = eng.submit(prompts[4], 4)
        rb = eng.submit(prompts[5], 4)      # waits behind ra
        assert eng.cancel(rb)               # still queued
        assert not eng.cancel(10_000)       # unknown rid
        res = eng.drain()
        assert set(res) == {ra}
        assert not eng.cancel(ra)           # already finished
    finally:
        eng.close()


def test_cancel_mid_chunked_prefill_releases_scratch_and_pages():
    """A request cancelled while its prompt is still PREFILLING (cursor
    mid-prompt) must drop its dense scratch and give its pages back."""
    prompts = _prompts((33, 6), seed=35)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=64,
                                               kv="paged", page_size=8,
                                               prefill_chunk=8))
    try:
        ra = eng.submit(prompts[33], 4)
        rb = eng.submit(prompts[6], 4)
        ev = eng.step()                     # first chunk only: 8 < 33
        assert ra not in ev["finished"] and not ev["decoded"]
        assert eng.cancel(ra)
        assert not eng._scratch             # scratch freed immediately
        res = eng.drain()
        assert set(res) == {rb}
        assert eng.pool.free_pages == eng.pool.num_pages
    finally:
        eng.close()
    np.testing.assert_array_equal(res[rb],
                                  _oneshot(CFG, PARAMS, prompts[6], 4))


# ---------------------------------------------------------------------------
# Admission passes: freed capacity is reusable the same step
# ---------------------------------------------------------------------------


def test_freed_slot_readmitted_same_step():
    """Regression for the post-decode reclaim pass (`_admission_pass`):
    the step in which a request finishes must admit the next queued
    request — its freed slot and pages may not idle a step."""
    prompts = _prompts((4, 5), seed=41)
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=64,
                                               kv="paged", page_size=8))
    try:
        ra = eng.submit(prompts[4], 3)
        rb = eng.submit(prompts[5], 3)
        finish_ev = None
        while not eng.sched.done():
            ev = eng.step()
            if ra in ev["finished"]:
                finish_ev = ev
        assert finish_ev is not None
        assert rb in finish_ev["admitted"], \
            "freed slot/pages not re-admitted in the finishing step"
        res = eng.drain()
    finally:
        eng.close()
    np.testing.assert_array_equal(res[rb],
                                  _oneshot(CFG, PARAMS, prompts[5], 3))


# ---------------------------------------------------------------------------
# Scheduler policies
# ---------------------------------------------------------------------------


def test_policy_registry_and_unknown_name():
    from repro.serving.scheduler import (FifoPolicy, LatencyPolicy,
                                         make_policy, register_policy)
    assert isinstance(make_policy(None), FifoPolicy)
    assert isinstance(make_policy("latency"), LatencyPolicy)
    custom = FifoPolicy()
    assert make_policy(custom) is custom
    with pytest.raises(ValueError, match="unknown scheduler policy"):
        make_policy("nope")
    register_policy("test-fifo2", FifoPolicy)
    try:
        assert Scheduler(1, policy="test-fifo2").policy.name == "fifo"
    finally:
        from repro.serving.scheduler import POLICIES
        POLICIES.pop("test-fifo2")


def test_latency_policy_defers_under_pressure_else_fifo():
    """The latency policy admits exactly what FIFO would — until the
    engine-published signals show decode saturation (budgeted) or a
    blown inter-token p99, when it admits nothing."""
    from repro.serving.scheduler import LatencyPolicy
    s = Scheduler(2, policy="latency")
    for rid in (0, 1, 2):
        s.submit(Request(rid=rid, prompt_len=4, max_new=2))
    # No pressure: identical to the FIFO scan (2 free slots -> 2 picks).
    s.signals = lambda: {"token_budget": 0, "decode_tokens": 4,
                         "prefill_backlog": 9, "itl_p99_ms": None}
    assert [r.rid for r in s.admissible(step=0)] == [0, 1]
    # Budget saturated by in-flight decode + pending chunks: defer.
    s.signals = lambda: {"token_budget": 8, "decode_tokens": 4,
                         "prefill_backlog": 4, "itl_p99_ms": None}
    assert s.admissible(step=0) == []
    # Headroom again: back to FIFO.
    s.signals = lambda: {"token_budget": 8, "decode_tokens": 2,
                         "prefill_backlog": 0, "itl_p99_ms": None}
    assert [r.rid for r in s.admissible(step=0)] == [0, 1]
    # p99 over target (explicit target): defer.
    s.policy = LatencyPolicy(target_p99_ms=5.0)
    s.signals = lambda: {"token_budget": 0, "decode_tokens": 0,
                         "prefill_backlog": 0, "itl_p99_ms": 7.5}
    assert s.admissible(step=0) == []


def test_latency_policy_engine_run_completes_bit_identical():
    """End to end under the latency policy + tight budget: deferral
    changes admission timing only — every request completes with its
    monolithic-FIFO tokens."""
    want = _monolithic_ref("paged")
    got, stats = _chunked_outputs(8, "paged", budget=6, policy="latency")
    assert stats["finished"] == len(_CHUNK_LENGTHS)
    for L, w, g in zip(_CHUNK_LENGTHS, want, got):
        np.testing.assert_array_equal(w, g, err_msg=f"prompt_len={L}")


def test_launcher_smoke_switch_selects_preset_or_full():
    """``--smoke`` is a real switch: the default serves the preset,
    ``--no-smoke`` the published widths, and the default arch is one
    that fits a single 16 GB chip."""
    from repro.launch.serve import build_parser
    ap = build_parser()
    assert ap.parse_args([]).smoke is True
    assert ap.parse_args([]).arch == "smollm_360m"
    args = ap.parse_args(["--arch", "smollm_360m", "--no-smoke"])
    assert args.smoke is False
    assert ap.parse_args(["--smoke"]).smoke is True


def test_launcher_platform_request_fails_off_that_platform(capsys):
    """Asked for the chip on a host without one, the launcher exits
    before building anything — it never carries on on the CPU."""
    import jax
    from repro.launch.serve import main
    other = "cpu" if jax.default_backend() == "tpu" else "tpu"
    with pytest.raises(SystemExit, match=f"--platform {other}"):
        main(["--platform", other])
    assert "[serve] device platform=" in capsys.readouterr().out


def test_launcher_verify_holds_logits_to_a_bound(capsys):
    """``--verify`` taps both runs' logits rows: the continuous run
    passes against its one-shot reference; a row moved by a fraction of
    a logit standard deviation, or a token that parts from the
    reference where the reference has no near-tie, fails it."""
    from repro.launch.serve import VERIFY_TOL, _verify, run_trace
    prompts = _prompts(RAGGED)
    trace = [{"id": i, "arrival": i, "max_new": 6, "prompt": prompts[L]}
             for i, L in enumerate(RAGGED)]
    scfg = ServeConfig(batch_slots=3, max_len=64, kv="paged", page_size=8)
    eng = ServeEngine(CFG, PARAMS, scfg)
    taps: dict = {}
    eng.logits_tap = lambda rid, pos, row: taps.setdefault(rid, {}).update(
        {pos: np.asarray(row)})
    try:
        rep = run_trace(eng, trace, log=None)
    finally:
        eng.close()
    rows = {tid: taps[rid] for tid, rid in rep["rids"].items()}
    results = rep["results"]
    _verify(CFG, PARAMS, trace, results, scfg, rows)
    assert "verify OK: 3 requests" in capsys.readouterr().out

    pos = len(prompts[RAGGED[1]]) - 1 + 2        # request 1, step 2
    moved = dict(rows[1])
    row = moved[pos].copy()
    row[0] += 3 * VERIFY_TOL * np.std(row)
    moved[pos] = row
    with pytest.raises(SystemExit, match=r"id=1 max\|d\|"):
        _verify(CFG, PARAMS, trace, results, scfg, {**rows, 1: moved})

    flipped = {**results, 2: results[2].copy()}
    r = rows[2][len(prompts[RAGGED[2]]) - 1 + 5]
    flipped[2][5] = int(np.argmin(r))            # far from any tie
    with pytest.raises(SystemExit, match="id=2 parted at step 5"):
        _verify(CFG, PARAMS, trace, flipped, scfg, rows)
