"""Paged KV cache (repro.serving.kvpool): allocator invariants +
fragmentation property, paged-vs-dense engine numerics on the smoke6
trace (int8/f32 bit-identity, bf16 tolerance), recurrent-arch bypass,
EOS early exit with same-step page reuse, pool-exhaustion preemption,
and the over-subscription acceptance case (paged admits more concurrent
requests than dense at equal KV memory)."""

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
from repro.serving.engine import ServeConfig, ServeEngine
from repro.serving.kvpool import BlockTables, PagePool, pages_for
from repro.serving.scheduler import Request, Scheduler

pytestmark = pytest.mark.serving

CFG = C.get_smoke("smollm_360m")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return {L: rng.integers(0, CFG.vocab_size, size=(L,)).astype(np.int32)
            for L in lengths}


def _drain_all(eng, reqs):
    """Submit [(prompt, max_new), ...]; return list of token arrays."""
    rids = [eng.submit(p, mn) for p, mn in reqs]
    res = eng.drain()
    return [res[r] for r in rids]


# ---------------------------------------------------------------------------
# Allocator unit behavior
# ---------------------------------------------------------------------------


def test_pool_alloc_release_deterministic():
    p = PagePool(num_pages=6, page_size=16)
    assert p.alloc(3) == [0, 1, 2]
    assert p.alloc(2) == [3, 4]
    p.release([1, 3])
    assert p.alloc(3) == [1, 3, 5]        # lowest ids first, reused
    assert p.alloc(1) is None             # exhausted -> None, not raise
    assert (p.pages_in_use, p.free_pages) == (6, 0)
    assert p.high_water == 6
    p.check()


def test_pool_double_free_raises():
    p = PagePool(num_pages=4, page_size=8)
    pages = p.alloc(2)
    p.release(pages)
    with pytest.raises(ValueError, match="not in use"):
        p.release(pages)                  # double free
    with pytest.raises(ValueError, match="not in use"):
        p.release([3])                    # never allocated


def test_block_tables_assign_extend_release():
    pool = PagePool(num_pages=5, page_size=8)
    bt = BlockTables(pool, n_slots=2, max_pages=3)
    assert bt.assign(0, tokens=9) == [0, 1]          # 2 pages
    assert (bt.table[0] == [0, 1, pool.null_page]).all()
    assert bt.extend_to(0, tokens=17)                # 3rd page
    assert bt.table[0, 2] == 2
    assert bt.assign(1, tokens=8) == [3]
    assert not bt.extend_to(1, tokens=17)            # needs 2, only 1 free
    assert bt.extend_to(1, tokens=16)                # needs 1, 1 free
    assert bt.release(0) == 3
    assert (bt.table[0] == pool.null_page).all()
    assert bt.extend_to(1, tokens=17)                # now pages are free
    pool.check()


# ---------------------------------------------------------------------------
# Fragmentation property: random admit/complete never leaks/double-frees
# ---------------------------------------------------------------------------


@given(st.tuples(
    st.integers(min_value=0, max_value=10 ** 9),     # op-sequence seed
    st.integers(min_value=1, max_value=4),           # page size
))
@settings(max_examples=20, deadline=None)
def test_pool_fragmentation_property(draw):
    """Random interleavings of assign / extend / release over a small
    pool keep the free/used partition exact at every step and drain to
    a fully free pool — no leaks, no double allocation, ever."""
    seed, ps = draw
    rng = np.random.default_rng(seed)
    pool = PagePool(num_pages=int(rng.integers(2, 12)), page_size=ps)
    n_slots = int(rng.integers(1, 5))
    bt = BlockTables(pool, n_slots=n_slots, max_pages=pool.num_pages)
    tokens = {}                                      # live slot -> tokens
    for _ in range(60):
        op = rng.integers(0, 3)
        if op == 0 and len(tokens) < n_slots:        # admit
            slot = next(i for i in range(n_slots) if i not in tokens)
            want = int(rng.integers(1, pool.num_pages * ps + 1))
            if bt.assign(slot, want) is not None:
                tokens[slot] = want
        elif op == 1 and tokens:                     # decode append
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            grown = tokens[slot] + int(rng.integers(1, ps + 1))
            if pages_for(grown, ps) <= bt.max_pages \
                    and bt.extend_to(slot, grown):
                tokens[slot] = grown
        elif op == 2 and tokens:                     # complete / evict
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            freed = bt.release(slot)
            assert freed == pages_for(tokens.pop(slot), ps)
        pool.check()
        live = sum(pages_for(t, ps) for t in tokens.values())
        assert pool.pages_in_use == live
    for slot in sorted(tokens):
        bt.release(slot)
    pool.check()
    assert pool.pages_in_use == 0 and pool.free_pages == pool.num_pages


@given(st.tuples(
    st.integers(min_value=0, max_value=10 ** 9),     # op-sequence seed
    st.integers(min_value=1, max_value=4),           # page size
))
@settings(max_examples=20, deadline=None)
def test_pool_sharing_property(draw):
    """Fragmentation property extended with prefix-sharing traffic:
    random interleavings of assign / extend / cache-pin / cache-unpin /
    copy-on-write / release keep every refcount invariant exact — a
    referenced page is never reclaimed, the last release reclaims
    exactly once, live vs resident accounting never drifts, and the
    drained pool is fully free with double-free still a loud error."""
    seed, ps = draw
    rng = np.random.default_rng(seed)
    pool = PagePool(num_pages=int(rng.integers(4, 12)), page_size=ps)
    n_slots = int(rng.integers(1, 4))
    bt = BlockTables(pool, n_slots=n_slots, max_pages=pool.num_pages)
    tokens = {}                  # live slot -> tokens
    cache_pins = []              # lists of pages holding one cache ref
    for _ in range(80):
        op = rng.integers(0, 6)
        if op == 0 and len(tokens) < n_slots:        # admit
            slot = next(i for i in range(n_slots) if i not in tokens)
            want = int(rng.integers(1, pool.num_pages * ps + 1))
            if bt.assign(slot, want) is not None:
                tokens[slot] = want
        elif op == 1 and tokens:                     # decode append
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            grown = tokens[slot] + int(rng.integers(1, ps + 1))
            if pages_for(grown, ps) <= bt.max_pages \
                    and bt.extend_to(slot, grown):
                tokens[slot] = grown
        elif op == 2 and tokens:                     # complete / evict
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            pages = list(bt.slot_pages(slot))
            pinned = {p for pin in cache_pins for p in pin}
            freed = bt.release(slot)
            del tokens[slot]
            # Cache-pinned pages survive the slot (never reclaimed
            # while referenced); unpinned ones are freed exactly once.
            assert freed == sum(1 for p in pages if p not in pinned)
            assert all(pool.refcount(p) >= 1 for p in pages
                       if p in pinned)
        elif op == 3 and tokens:                     # radix-tree pin
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            pages = list(bt.slot_pages(slot))
            if pages:
                pool.share(pages, cache=True)
                cache_pins.append(pages)
        elif op == 4 and cache_pins:                 # radix-tree evict
            pin = cache_pins.pop(int(rng.integers(0, len(cache_pins))))
            pool.release(pin, cache=True)
        elif op == 5 and tokens:                     # copy-on-write
            slot = sorted(tokens)[int(rng.integers(0, len(tokens)))]
            pages = bt.slot_pages(slot)
            if pages:
                idx = int(rng.integers(0, len(pages)))
                was_shared = pool.refcount(pages[idx]) > 1
                res = bt.cow(slot, idx)
                if res is not None:
                    src, dst = res
                    assert (src != dst) == was_shared
                    if src != dst:
                        # Fresh exclusive copy; sharers keep the source.
                        assert pool.refcount(dst) == 1
                        assert pool.refcount(src) >= 1
                        assert bt.slot_pages(slot)[idx] == dst
        pool.check()
        live = {p for s in tokens for p in bt.slot_pages(s)}
        resident = live | {p for pin in cache_pins for p in pin}
        assert pool.pages_in_use == len(live)
        assert pool.pages_resident == len(resident)
    for slot in sorted(tokens):
        bt.release(slot)
    for pin in cache_pins:
        pool.release(pin, cache=True)
    pool.check()
    assert pool.pages_in_use == 0 and pool.free_pages == pool.num_pages
    # Double-free is still a loud error after all the sharing traffic.
    pg = pool.alloc(1)
    pool.release(pg)
    with pytest.raises(ValueError, match="not in use"):
        pool.release(pg)


# ---------------------------------------------------------------------------
# Scheduler integration: capacity gate + requeue
# ---------------------------------------------------------------------------


def test_scheduler_fits_gate_is_strict_fifo():
    s = Scheduler(4)
    for rid, plen in ((0, 4), (1, 30), (2, 2)):
        s.submit(Request(rid=rid, prompt_len=plen, max_new=2))
    budget = {"left": 8}

    def fits(req):
        if req.prompt_len > budget["left"]:
            return False
        budget["left"] -= req.prompt_len
        return True
    # rid 1 doesn't fit -> the scan stops; rid 2 must NOT leapfrog it.
    assert [r.rid for r in s.pop_admissible(step=0, fits=fits)] == [0]
    assert [r.rid for r in s.queue] == [1, 2]


def test_scheduler_requeue_goes_to_head():
    s = Scheduler(1)
    s.submit(Request(rid=0, prompt_len=4, max_new=2))
    s.submit(Request(rid=1, prompt_len=4, max_new=2))
    victim = s.pop_admissible(step=0)[0]     # rid 0 (1 slot); rid 1 waits
    s.requeue(victim)
    assert [r.rid for r in s.queue] == [0, 1]


# ---------------------------------------------------------------------------
# Paged vs dense engine numerics (smoke6 trace)
# ---------------------------------------------------------------------------


def _smoke6_trace(vocab):
    from repro.launch.serve import load_trace
    return load_trace("benchmarks/traces/smoke6.jsonl", vocab)


def _run_trace_outputs(cfg, params, trace, **scfg_kw):
    from repro.launch.serve import run_trace
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=3, max_len=64,
                                               **scfg_kw))
    try:
        rep = run_trace(eng, trace, log=None)
        return rep["results"], eng
    finally:
        eng.close()


@pytest.mark.parametrize("quantize", [False, True],
                         ids=["f32", "int8"])
def test_smoke6_paged_bit_identical_to_dense(quantize):
    """The committed 6-request staggered trace must decode bit-
    identically through the paged engine and the dense engine (f32 and
    int8-quantized) — the paged layout changes *where* KV lives, never
    what attention computes."""
    trace = _smoke6_trace(CFG.vocab_size)
    dense, _ = _run_trace_outputs(CFG, PARAMS, trace, kv="dense",
                                  quantize=quantize)
    paged, eng = _run_trace_outputs(CFG, PARAMS, trace, kv="paged",
                                    page_size=16, quantize=quantize)
    assert eng.kv_mode == "paged"
    assert eng.pool.total_reclaimed > 0          # completion reclaims
    assert eng.pool.pages_in_use == 0            # drained pool is empty
    for tid in dense:
        np.testing.assert_array_equal(
            dense[tid], paged[tid],
            err_msg=f"trace id {tid} diverged under paging")


def test_smoke6_paged_bf16_tolerance():
    """bf16 compute/cache: paged vs dense greedy streams agree within
    float tolerance (a paging bug would drop agreement to ~1/vocab)."""
    cfg = dataclasses.replace(CFG, name="smoke-bf16",
                              compute_dtype="bfloat16",
                              cache_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    trace = _smoke6_trace(cfg.vocab_size)
    dense, _ = _run_trace_outputs(cfg, params, trace, kv="dense")
    paged, _ = _run_trace_outputs(cfg, params, trace, kv="paged",
                                  page_size=16)
    for tid in dense:
        agree = float(np.mean(dense[tid] == paged[tid]))
        assert agree >= 0.75, \
            f"trace id {tid}: {agree:.2f} agreement — paging bug?"


# ---------------------------------------------------------------------------
# int8 KV pages (ServeConfig.kv_dtype): numerics, memory, validation
# ---------------------------------------------------------------------------


def _staggered_trace(n=6, plen=12, seed=1):
    """Synthetic staggered-arrival trace (mixed max_new, arrival=i)."""
    rng = np.random.default_rng(seed)
    max_new = [4, 8, 6, 8, 4, 6]
    return [{"id": i, "arrival": i,
             "prompt": rng.integers(0, CFG.vocab_size,
                                    size=(plen,)).astype(np.int32),
             "max_new": max_new[i % len(max_new)]}
            for i in range(n)]


def test_int8_kv_greedy_outputs_unchanged():
    """Integration: a staggered 6-request trace decodes under int8 KV
    pages to the dense f32 engine's greedy tokens wherever the choice
    is not a near-tie, and its logits stay within the int8 noise bound.

    Every logits row the two engines compute from the same inputs (the
    prompt's last position, then each decode step until their greedy
    tokens part) must agree to 0.1 logit standard deviations: per-row
    symmetric int8 moves a KV row by at most scale/2 = max|row|/254,
    which perturbs a logit by ~1% of the logits' spread (4.2% measured
    at worst here), while a wrong scale, page or row is an O(1) error.
    Greedy argmax survives that perturbation only when the dense top-2
    gap exceeds twice the bound, so token equality is asserted on
    exactly those rows.  (These seeds contain one near-tie: trace id 5,
    step 2, top-2 gap 6e-4 against a 4e-3 perturbation.)"""
    params = init_params(jax.random.PRNGKey(5), CFG)
    trace = _staggered_trace(seed=0)
    dense, dense_rows = _run_trace_logits(CFG, params, trace, kv="dense")
    paged, paged_rows = _run_trace_logits(CFG, params, trace, kv="paged",
                                          page_size=16, kv_dtype="int8")
    compared = decided = 0
    for t in trace:
        tid, plen = t["id"], len(t["prompt"])
        a, b = dense[tid], paged[tid]
        split = int(np.argmax(a != b)) if (a != b).any() else len(a)
        ref = dense_rows[tid]
        for pos, row in sorted(paged_rows[tid].items()):
            if pos > plen - 1 + split:
                break           # inputs differ past the first parted token
            bound = 0.1 * float(ref[pos].std())
            err = float(np.abs(row - ref[pos]).max())
            assert err <= bound, (tid, pos, err, bound)
            compared += 1
            top2 = np.sort(ref[pos])[-2:]
            if top2[1] - top2[0] > 2 * bound:
                decided += 1
                assert int(np.argmax(row)) == int(np.argmax(ref[pos])), \
                    f"trace id {tid} diverged at a decided step (pos {pos})"
    # The bound is doing real work: most rows are compared and decided.
    assert compared >= sum(t["max_new"] for t in trace) // 2
    assert decided >= compared // 2


def _run_trace_logits(cfg, params, trace, **scfg_kw):
    """Like :func:`_run_trace_outputs`, plus every logits row the engine
    sampled from: rows[trace id][pos] (rid == trace id on a fresh
    engine replaying a step-indexed trace in order)."""
    from repro.launch.serve import run_trace
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=3, max_len=64,
                                               **scfg_kw))
    rows: dict = {}
    eng.logits_tap = lambda rid, pos, row: rows.setdefault(rid, {}).update(
        {pos: np.asarray(row)})
    try:
        rep = run_trace(eng, trace, log=None)
    finally:
        eng.close()
    return rep["results"], rows


def test_int8_kv_halves_kv_high_water():
    """The whole point of quantized pages: at d_head=16 f32, an int8
    row costs 16 + 4 (scale) = 20 bytes vs 64 — the engine's KV
    high-water accounting must show the 0.3125x ratio on the same
    trace (ISSUE acceptance: 'roughly halved')."""
    trace = _staggered_trace(seed=1)
    _, f32_eng = _run_trace_outputs(CFG, PARAMS, trace, kv="paged",
                                    page_size=16)
    _, i8_eng = _run_trace_outputs(CFG, PARAMS, trace, kv="paged",
                                   page_size=16, kv_dtype="int8")
    f32_hwm = f32_eng.kv_bytes_high_water()
    i8_hwm = i8_eng.kv_bytes_high_water()
    assert f32_hwm > 0
    full_row = CFG.d_head * np.dtype(CFG.cache_dtype).itemsize
    want = (CFG.d_head * 1 + 4) / full_row      # int8 row + f32 scale
    assert i8_hwm / f32_hwm == pytest.approx(want)
    assert i8_hwm / f32_hwm <= 0.5


def test_kv_dtype_requires_paged_layout():
    """Satellite regression: kv_dtype on the dense layout must be a
    loud error — there is no page pool to retype, and silently serving
    full-precision would misreport the memory the user asked for."""
    with pytest.raises(ValueError, match="kv_dtype.*kv='paged'"):
        ServeEngine(CFG, PARAMS, ServeConfig(
            batch_slots=2, max_len=64, kv="dense", kv_dtype="int8",
            pretune=False))


def test_kv_dtype_rejected_for_recurrent_arch():
    """Satellite regression: an arch whose state bypasses the page pool
    (recurrent mixers / enc-dec cross caches fall back to the dense
    layout) cannot honor kv_dtype — the engine must refuse rather than
    silently store full-precision KV."""
    cfg = C.get_smoke("rwkv6_3b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=64, kv="paged", page_size=16,
            kv_dtype="int8", pretune=False))


def test_kv_dtype_rejects_unknown_name():
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeEngine(CFG, PARAMS, ServeConfig(
            batch_slots=2, max_len=64, kv="paged", kv_dtype="int4",
            pretune=False))


def test_recurrent_arch_bypasses_kvpool():
    """mamba/rwkv state is fixed-size per slot — nothing to page.  A
    paged config on such an arch must transparently serve on the dense
    path (kv_mode == 'dense', no pool) with unchanged outputs."""
    cfg = C.get_smoke("rwkv6_3b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=(7,)).astype(np.int32)
    eng = ServeEngine(cfg, params, ServeConfig(batch_slots=2, max_len=64,
                                               kv="paged", page_size=16))
    try:
        assert eng.kv_mode == "dense" and eng.pool is None
        out = _drain_all(eng, [(prompt, 6)])[0]
    finally:
        eng.close()
    ref_eng = ServeEngine(cfg, params,
                          ServeConfig(batch_slots=1, max_len=64))
    try:
        want = ref_eng.generate(prompt[None, :], 6)[0]
    finally:
        ref_eng.close()
    np.testing.assert_array_equal(out, want)


# ---------------------------------------------------------------------------
# EOS early exit + same-step page reuse
# ---------------------------------------------------------------------------


def _expected_with_eos(full, eos_id):
    hits = np.flatnonzero(full == eos_id)
    return full[:hits[0] + 1] if hits.size else full


def test_eos_early_exit_frees_pages_for_queued_request():
    """A slot whose sampled token hits eos_id must finish *that step* —
    freeing its slot and its KV pages — and a queued request gated on
    those pages must be admitted the same step (the post-decode
    admission pass), reusing the reclaimed page ids."""
    prompts = _prompts((12, 16), seed=21)
    # Find a token the first request actually emits mid-stream (greedy,
    # so the stream is deterministic).
    probe = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1,
                                                 max_len=64))
    try:
        full_a = probe.generate(prompts[12][None, :], 10)[0]
        full_b = probe.generate(prompts[16][None, :], 10)[0]
    finally:
        probe.close()
    eos = int(full_a[4])
    want_a = _expected_with_eos(full_a, eos)
    want_b = _expected_with_eos(full_b, eos)
    assert len(want_a) < 10                  # it really exits early

    # Pool sized so only one request fits at a time: B's page-aligned
    # prompt needs both pages (admission reserves prompt + 1 rows), so
    # it stays page-gated until A's EOS reclaim.
    ps = 16
    pool = pages_for(12 + 10, ps)            # = what A could ever need
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=2, max_len=32, kv="paged", page_size=ps,
        pool_pages=pool, eos_id=eos))
    try:
        rid_a = eng.submit(prompts[12], 10)
        rid_b = eng.submit(prompts[16], 10)
        finished_step = {}
        admitted_step = {}
        while not eng.sched.done():
            ev = eng.step()
            for r in ev["admitted"]:
                admitted_step[r] = eng.step_count - 1
            for r in ev["finished"]:
                finished_step[r] = eng.step_count - 1
        res = dict(eng._finished)
        assert eng.stats["eos_exits"] >= 1
        # Same-step reuse: B admitted in the step A's EOS freed pages.
        assert admitted_step[rid_b] == finished_step[rid_a]
        assert eng.pool.pages_in_use == 0
    finally:
        eng.close()
    np.testing.assert_array_equal(res[rid_a], want_a)
    np.testing.assert_array_equal(res[rid_b], want_b)


def test_eos_early_exit_dense_engine():
    """EOS exit is layout-independent: the dense engine stops at the
    sampled eos_id too (ROADMAP 'EOS-token early exit')."""
    prompts = _prompts((9,), seed=23)
    probe = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1,
                                                 max_len=64))
    try:
        full = probe.generate(prompts[9][None, :], 8)[0]
    finally:
        probe.close()
    eos = int(full[2])
    eng = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1, max_len=64,
                                               eos_id=eos))
    try:
        out = _drain_all(eng, [(prompts[9], 8)])[0]
        assert eng.stats["eos_exits"] == 1
        # generate() must stay rectangular under EOS: early-exit rows
        # are right-padded with the eos token (regression: np.stack
        # used to crash on the ragged results).
        padded = eng.generate(prompts[9][None, :], 8)
        assert padded.shape == (1, 8)
    finally:
        eng.close()
    want = _expected_with_eos(full, eos)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(padded[0, :len(want)], want)
    assert (padded[0, len(want):] == eos).all()


# ---------------------------------------------------------------------------
# Pool exhaustion: deterministic preemption -> requeue
# ---------------------------------------------------------------------------


def test_preemption_requeues_and_outputs_match():
    """Two requests whose joint growth exceeds the pool: the younger is
    preempted mid-decode (pages reclaimed, requeued at the head) and
    re-served after the older finishes — both token streams must still
    equal their one-shot references (greedy regeneration)."""
    ps = 8
    prompts = _prompts((8, 6), seed=31)
    refs = {}
    probe = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1,
                                                 max_len=64))
    try:
        for L in (8, 6):
            refs[L] = probe.generate(prompts[L][None, :], 12)[0]
    finally:
        probe.close()
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=2, max_len=32, kv="paged", page_size=ps,
        pool_pages=4))   # each request needs 3 pages to finish
    try:
        out_a, out_b = _drain_all(eng, [(prompts[8], 12),
                                        (prompts[6], 12)])
        assert eng.stats["preemptions"] >= 1
        assert eng.pool.pages_in_use == 0
    finally:
        eng.close()
    np.testing.assert_array_equal(out_a, refs[8])
    np.testing.assert_array_equal(out_b, refs[6])


def test_submit_rejects_request_larger_than_pool():
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=1, max_len=64, kv="paged", page_size=8,
        pool_pages=2, pretune=False))
    try:
        with pytest.raises(ValueError, match="pool"):
            eng.submit(np.zeros((20,), np.int32), 10)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# Acceptance: paged admits more concurrency than dense at equal memory
# ---------------------------------------------------------------------------


def test_paged_oversubscribes_dense_reservation():
    """A staggered trace whose live tokens fit the pool even though the
    dense reservation for the same concurrency would not: with a pool
    of HALF the dense engine's slots x max_len rows, the paged engine
    must still run MORE concurrent requests than a dense engine of
    equal KV memory could even hold, with every output bit-identical
    to one-shot references."""
    slots, max_len, ps = 4, 64, 16
    pool_pages = (slots * max_len // ps) // 2       # half the dense rows
    dense_equiv_slots = (pool_pages * ps) // max_len
    assert dense_equiv_slots == 2                   # dense: 2 slots max
    rng = np.random.default_rng(41)
    reqs = [rng.integers(0, CFG.vocab_size, size=(12,)).astype(np.int32)
            for _ in range(6)]
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=slots, max_len=max_len, kv="paged", page_size=ps,
        pool_pages=pool_pages))
    try:
        rids = [eng.submit(p, 8, arrival=2 * i)
                for i, p in enumerate(reqs)]
        peak = 0
        while not eng.sched.done():
            ev = eng.step()
            # Requests sharing this step's batched decode = the live
            # concurrency the pool carried.
            peak = max(peak, len(ev["decoded"]))
        res = dict(eng._finished)
        # Live-token accounting let all 4 slots decode concurrently —
        # strictly more than the 2 a dense engine of this memory holds.
        assert peak > dense_equiv_slots
        assert peak == slots
        assert eng.stats["preemptions"] == 0        # it genuinely fit
        assert eng.pool.high_water <= pool_pages
    finally:
        eng.close()
    one = ServeEngine(CFG, PARAMS, ServeConfig(batch_slots=1,
                                               max_len=max_len))
    try:
        for rid, p in zip(rids, reqs):
            np.testing.assert_array_equal(
                res[rid], one.generate(p[None, :], 8)[0])
    finally:
        one.close()


# ---------------------------------------------------------------------------
# Tuner schema v8: page_size + kv_dtype + prefill_chunk + prefix_cache
# ---------------------------------------------------------------------------


def test_serve_candidate_v8_roundtrip_and_dispatch():
    from repro.tuning import dispatch
    from repro.tuning.space import DesignSpace, ServeCandidate
    c = ServeCandidate(slots=4, page_size=32, kv_dtype="int8",
                       prefill_chunk=32, prefix_cache=True)
    assert ServeCandidate.from_json(c.to_json()) == c
    # v4..v7-era JSON (progressively fewer axes) still parses.
    assert ServeCandidate.from_json({"slots": 8}).page_size == 0
    assert ServeCandidate.from_json({"slots": 8,
                                     "page_size": 16}).kv_dtype == ""
    assert ServeCandidate.from_json(
        {"slots": 8, "page_size": 16, "kv_dtype": ""}).prefill_chunk == 0
    assert ServeCandidate.from_json(
        {"slots": 8, "page_size": 16, "kv_dtype": "",
         "prefill_chunk": 0}).prefix_cache is False
    space = DesignSpace.serve(max_len=64)
    assert {c.page_size for c in space} == {0, 16, 32, 64}
    assert {c.kv_dtype for c in space} == {"", "int8"}
    assert {c.prefill_chunk for c in space} == {0, 16, 32}
    assert {c.prefix_cache for c in space} == {False, True}
    # int8 and prefix sharing are page-pool properties: never crossed
    # with the dense layout.
    assert not any(c.kv_dtype and c.page_size == 0 for c in space)
    assert not any(c.prefix_cache and c.page_size == 0 for c in space)
    # Paged chunks are page-aligned; every chunk is below max_len.
    assert all(c.prefill_chunk % c.page_size == 0 for c in space
               if c.prefill_chunk and c.page_size)
    assert all(c.prefill_chunk < 64 for c in space)
    # Analytic fallbacks: slots unchanged from v4, page granularity 32,
    # kv_dtype never quantized by default, prefill monolithic by
    # default, prefix sharing off by default (a miss must not change
    # numerics, reshape latency, or pool accounting).
    assert dispatch.serve_slots(CFG, 64, "float32") == 8
    assert dispatch.serve_page_size(CFG, 64, "float32") == 32
    assert dispatch.serve_kv_dtype(CFG, 64, "float32") is None
    assert dispatch.serve_prefill_chunk(CFG, 64, "float32") == 0
    assert dispatch.serve_prefix_cache(CFG, 64, "float32") is False
    # Archs the pool cannot cover never get a quantized dtype, a
    # chunked prefill, or a shared prefix, tuned or not (their pages
    # silently fall back to the dense layout).
    assert dispatch.serve_kv_dtype(C.get_smoke("rwkv6_3b"), 64,
                                   "float32") is None
    assert dispatch.serve_prefill_chunk(C.get_smoke("rwkv6_3b"), 64,
                                        "float32") == 0
    assert dispatch.serve_prefix_cache(C.get_smoke("rwkv6_3b"), 64,
                                       "float32") is False


def test_schema_v8_discards_v7_serve_entries(tmp_path):
    """A v7 cache file — even with a well-formed serve entry — must be
    invalidated wholesale: its winners never competed against the
    prefix_cache axis, and a stale uncached winner would silently keep
    shared-prompt traffic on the unshared pool accounting."""
    import json

    from repro.tuning.cache import SCHEMA_VERSION, TuningCache, cache_key
    assert SCHEMA_VERSION == 8
    path = tmp_path / "tuning_cache.json"
    key = cache_key("serve", CFG.d_model, CFG.vocab_size, 64, "float32",
                    "cpu", "cpu", extra=f"arch{CFG.name}")
    path.write_text(json.dumps({
        "schema": 7,
        "entries": {key: {"config": {"slots": 16, "page_size": 64,
                                     "kv_dtype": "", "prefill_chunk": 0},
                          "us": 1.0}},
    }))
    tc = TuningCache(path).load()
    assert tc.get(key) is None


def test_engine_resolves_page_size_from_tuner():
    eng = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=2, max_len=64, kv="paged", pretune=False))
    try:
        assert eng.scfg.page_size == 32      # analytic v5 default
        assert eng.pool.page_size == 32
    finally:
        eng.close()
