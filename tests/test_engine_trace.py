"""The engine on the profiler's clock: its phases as host events of a
``jax.profiler`` session, its jitted programs named
``jit_engine_<name>``, its retraces counted in the registry, and spans
that cost one check with no session and no Chrome sink."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro import obs
from repro.models import init_params
from repro.obs.metrics import Registry
from repro.obs.trace import _NULL_SPAN, Tracer
from repro.serving.engine import ServeConfig, ServeEngine

pytestmark = pytest.mark.serving

CFG = C.get_smoke("smollm_360m")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
# Two prompts that take two 16-token chunks and one.
PROMPTS = [np.random.default_rng(5).integers(1, CFG.vocab_size, n)
           .astype(np.int32) for n in (20, 7)]

PHASES = {"engine.admit", "engine.prefill_chunk", "engine.first_token",
          "engine.grow_pages", "engine.decode.prepare",
          "engine.decode.dispatch", "engine.decode.wait", "engine.emit"}


def engine(**kw):
    return ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=2, max_len=64, kv="paged", page_size=16,
        prefill_chunk=16, **kw))


def serve(eng, max_new=3):
    rids = [eng.submit(p, max_new) for p in PROMPTS]
    steps = 0
    while not eng.sched.done():
        eng.step()
        steps += 1
    res = eng.drain()
    return [res[r] for r in rids], steps


def host_events(trace_dir):
    """(name, start, end, stats) of every ``engine.*`` host event."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("**/*.xplane.pb")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        data = ProfileData.from_file(str(path))
        return [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats))
                for p in data.planes if p.name.startswith("/host")
                for ln in p.lines for e in ln.events
                if e.name.startswith("engine.")]


@pytest.fixture(autouse=True)
def fresh_obs():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One paged chunked run inside a profiler session."""
    obs.reset()
    trace_dir = tmp_path_factory.mktemp("xplane")
    eng = engine()
    try:
        with jax.profiler.trace(str(trace_dir)):
            outs, steps = serve(eng)
    finally:
        eng.close()
    return outs, steps, host_events(trace_dir)


def test_engine_spans_on_the_profiler_trace(traced):
    _, steps, events = traced
    step_spans = sorted((e for e in events if e[0] == "engine.step"),
                        key=lambda e: e[1])
    # One step marker per step() call, numbered like step_count.
    assert [e[3]["step_num"] for e in step_spans] == list(range(steps))
    seen = {e[0] for e in events}
    assert PHASES <= seen
    for name, a, b, stats in events:
        if name in ("engine.step", "engine.retrace"):
            continue
        # Every phase nests inside one step marker.
        assert any(s[1] <= a and b <= s[2] for s in step_spans), name
        if name in ("engine.prefill_chunk", "engine.first_token"):
            assert stats["rid"] in (0, 1), (name, stats)
    chunks = [e[3] for e in events if e[0] == "engine.prefill_chunk"]
    assert sorted((c["rid"], c["lo"], c["take"]) for c in chunks) == [
        (0, 0, 16), (0, 16, 4), (1, 0, 7)]
    # Retraces name their program; the first decode traces inside the
    # decode dispatch.
    retraced = {e[3]["program"] for e in events if e[0] == "engine.retrace"}
    assert {"prefill_chunk", "insert_chunk", "decode", "sample",
            "first_token"} <= retraced


def test_greedy_outputs_identical_without_a_session(traced):
    outs, _, _ = traced
    eng = engine()
    try:
        plain, _ = serve(eng)
    finally:
        eng.close()
    for a, b in zip(outs, plain):
        np.testing.assert_array_equal(a, b)


def test_retraces_counted_per_program():
    eng = engine()
    counters = obs.get_obs().registry.counters

    def count():
        return {k.rsplit(".", 1)[1]: c.value for k, c in counters.items()
                if k.startswith("engine.retraces.")}
    try:
        serve(eng)
        first = count()
        for prog in ("prefill_chunk", "insert_chunk", "decode", "sample",
                     "first_token"):
            assert first[prog] == 1, (prog, first)
        assert first["prefill"] == first["copy_page"] == 0
        serve(eng)                          # the same shapes again
        assert count() == first
        # A 5-token prompt also runs in a 16-row chunk buffer.
        eng.submit(PROMPTS[0][:5], 2)
        eng.drain()
        assert count() == first
    finally:
        eng.close()
    # A new chunk width (32) traces the chunk programs once more.
    obs.reset()
    counters = obs.get_obs().registry.counters
    wide = ServeEngine(CFG, PARAMS, ServeConfig(
        batch_slots=2, max_len=64, kv="paged", page_size=16,
        prefill_chunk=32))
    try:
        assert set(count().values()) == {0}
        wide.submit(PROMPTS[1], 2)          # 7 tokens: a 16-row buffer
        wide.drain()
        mid = count()
        wide.submit(PROMPTS[0], 2)          # 20 tokens: a 32-row buffer
        wide.drain()
        after = count()
        assert after["prefill_chunk"] == mid["prefill_chunk"] + 1 == 2
        assert after["insert_chunk"] == mid["insert_chunk"] + 1 == 2
        assert after["decode"] == mid["decode"] == 1
    finally:
        wide.close()


def test_engine_programs_carry_their_names():
    eng = engine()
    try:
        serve(eng, max_new=1)
        caches = eng.caches
        tok = jnp.zeros((2,), jnp.int32)
        table = jnp.asarray(eng.blocks.table)
        logits = jnp.zeros((1, 16, CFG.vocab_size), jnp.float32)
        lowered = {
            "decode": eng._decode.lower(eng.params, tok, tok, table, caches),
            "sample": eng._sample_slots.lower(
                jnp.zeros((2, CFG.vocab_size)), tok),
            "first_token": eng._first_token.lower(logits, jnp.int32(3)),
            "copy_page": eng._copy_page.lower(caches, jnp.int32(0),
                                              jnp.int32(1)),
        }
        for name, low in lowered.items():
            assert f"module @jit_engine_{name} " in low.as_text(), name
        for attr, name in (("_prefill", "prefill"),
                           ("_prefill_full", "prefill_full"),
                           ("_prefill_chunk_fn", "prefill_chunk"),
                           ("_insert", "insert"),
                           ("_insert_chunk", "insert_chunk")):
            fn = getattr(eng, attr)
            assert fn.__name__ == f"engine_{name}", attr
        for name in ("gama_gemm", "gama_flash_paged_decode"):
            assert not any(name in getattr(eng, a).__name__ for a in (
                "_decode", "_prefill_chunk_fn", "_insert_chunk"))
    finally:
        eng.close()


def test_span_without_session_or_sink_is_one_shared_noop():
    tr = Tracer(enabled=False)
    assert tr.span("engine.step", step_num=3) is _NULL_SPAN
    assert tr.span("engine.admit", phase="arrival") is _NULL_SPAN
    assert tr.chrome_trace()["traceEvents"] == []


def test_spans_reach_the_session_with_and_without_the_chrome_sink(
        tmp_path):
    sink = Tracer(enabled=True)
    off = Tracer(enabled=False)
    with jax.profiler.trace(str(tmp_path)):
        assert off.span("engine.x") is not _NULL_SPAN
        with sink.span("engine.step", cat="engine", step_num=7):
            with off.span("engine.decode.wait", rid=4):
                pass
    events = {e[0]: e[3] for e in host_events(tmp_path)}
    assert events["engine.step"]["step_num"] == 7
    assert events["engine.decode.wait"]["rid"] == 4
    # The Chrome sink keeps its own host-clock event, step_num included.
    (x,) = [e for e in sink.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert x["name"] == "engine.step" and x["args"]["step_num"] == 7
    assert sink.open_spans() == []


def test_chrome_sink_carries_the_engine_span_set():
    bundle = obs.configure(registry=Registry(), tracer=Tracer(enabled=True))
    eng = engine()
    try:
        serve(eng)
    finally:
        eng.close()
    names = {e["name"] for e in bundle.tracer.chrome_trace()["traceEvents"]
             if e["ph"] == "X"}
    assert PHASES | {"engine.step", "engine.retrace"} <= names
    assert not names & {"admit", "prefill_chunk", "decode", "prefill"}
