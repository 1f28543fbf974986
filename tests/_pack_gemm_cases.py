"""Pack/array-level GEMM correctness on a simulated 8-device mesh
(tests/test_pack_gemm.py drives this in a subprocess; the device-count
flag must be set before jax initializes)."""

import os
import tempfile

# Append to (not overwrite) any caller-provided XLA flags; an explicit
# device-count flag from the environment wins.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["REPRO_TUNING_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="repro_pack_test_"), "tuning_cache.json")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

import repro.distributed.pack_gemm as pg  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402


def _mesh(shape):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def check_pack_numerics():
    """pack_gemm vs the jnp oracle across (P, Q) grids, stagger offsets,
    reduce orders and the K-streamed overlap schedule, on divisible and
    deliberately awkward shapes."""
    rng = np.random.default_rng(0)
    mesh = _mesh((1, 8))
    shapes = [(16, 32, 24),     # divisible everywhere
              (13, 100, 27)]    # M/K/N all non-divisible by any grid
    configs = [(1, 8, 0, "psum", False), (2, 4, 0, "psum", False),
               (2, 4, 0, "ring", False), (2, 4, 1, "ring", False),
               (4, 2, 1, "ring", False), (4, 2, 3, "ring", False),
               (8, 1, 1, "ring", False),
               (2, 4, 1, "ring", True), (4, 2, 1, "ring", True),
               (4, 2, 3, "ring", True), (8, 1, 1, "ring", True),
               (4, 2, 1, "overlap", None)]   # the bench flag's spelling
    for (m, k, n) in shapes:
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        want = np.asarray(ref.ref_gemm(a, b))
        for (p, q, stagger, red, ov) in configs:
            got = np.asarray(pg.pack_gemm(a, b, mesh, p=p, q=q,
                                          stagger=stagger, reduce=red,
                                          overlap=ov))
            err = float(np.max(np.abs(got - want)))
            assert err < 1e-4, (m, k, n, p, q, stagger, red, ov, err)
    # bf16 in, bf16 out (f32 accumulation inside the pack).
    a = jnp.asarray(rng.normal(size=(16, 64)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(64, 24)), jnp.bfloat16)
    want = np.asarray(ref.ref_gemm(a, b).astype(jnp.float32))
    for ov in (False, True):
        got = np.asarray(pg.pack_gemm(a, b, mesh, p=2, q=4, stagger=1,
                                      reduce="ring",
                                      overlap=ov).astype(jnp.float32))
        assert float(np.max(np.abs(got - want))) < 0.2
    print("pack numerics OK")


def check_pack_int8():
    """int8 requantizes once after the full reduction — exact match for
    both the barrier ring and the K-streamed overlap (int32 partial
    sums are associative, so the chunk order cannot matter)."""
    rng = np.random.default_rng(1)
    mesh = _mesh((1, 8))
    ai = jnp.asarray(rng.integers(-128, 128, size=(16, 96)), jnp.int8)
    bi = jnp.asarray(rng.integers(-128, 128, size=(96, 24)), jnp.int8)
    want = np.asarray(ref.ref_gemm(ai, bi, out_dtype=jnp.int8,
                                   scale=0.002))
    for ov in (False, True):
        got = np.asarray(pg.pack_gemm(ai, bi, mesh, p=4, q=2, stagger=1,
                                      reduce="ring", overlap=ov,
                                      out_dtype=jnp.int8, scale=0.002))
        assert (got == want).all(), f"overlap={ov}"
    print("pack int8 OK")


def check_overlap_invariance():
    """Property: the result is invariant to the stagger offset and to
    overlap on/off — both only reorder associative accumulations.
    int8 must be bit-exact across every schedule; float agrees to a
    tight tolerance.  Also: the staged A entering shard_map is the
    q-free (d, p, Md, cyc*kb) tensor, never a Q-fold replica."""
    rng = np.random.default_rng(5)
    mesh = _mesh((1, 8))

    # int8: every (stagger, overlap) schedule is bit-identical.
    ai = jnp.asarray(rng.integers(-128, 128, size=(13, 100)), jnp.int8)
    bi = jnp.asarray(rng.integers(-128, 128, size=(100, 27)), jnp.int8)
    outs = [np.asarray(pg.pack_gemm(ai, bi, mesh, p=4, q=2, stagger=s,
                                    reduce="ring", overlap=ov,
                                    out_dtype=jnp.int8, scale=0.004))
            for s in range(4) for ov in (False, True)]
    for o in outs[1:]:
        assert (o == outs[0]).all(), "int8 schedules must be bit-exact"

    # float: schedules agree within summation-order tolerance.
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    fouts = [np.asarray(pg.pack_gemm(a, b, mesh, p=4, q=2, stagger=s,
                                     reduce="ring", overlap=ov))
             for s in range(4) for ov in (False, True)]
    for o in fouts[1:]:
        assert float(np.max(np.abs(o - fouts[0]))) < 1e-5

    # Q-free staging: the host-side A block layout has no q dimension.
    d, p, cyc, kb, md = 1, 4, 2, 8, 16
    ap = jnp.zeros((md * d, p * cyc * kb), jnp.float32)
    assert pg.stage_a_blocks(ap, d, p, cyc, kb).shape \
        == (d, p, md, cyc * kb)
    assert pg.stage_b_blocks(jnp.zeros((p * cyc * kb, 6 * 2)), p, 2,
                             cyc, kb).shape == (2, p, cyc * kb, 6)
    print("overlap invariance OK")


def check_array_level():
    """array_gemm: M sharded over data, packs over model; edge shapes."""
    rng = np.random.default_rng(2)
    mesh = _mesh((2, 4))
    for (m, k, n) in [(16, 32, 24), (13, 100, 27)]:
        a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        want = np.asarray(ref.ref_gemm(a, b))
        for (p, q) in [(1, 4), (2, 2), (4, 1)]:
            for ov in (False, True):
                got = np.asarray(pg.array_gemm(
                    a, b, mesh, p=p, q=q, stagger=1,
                    reduce="ring" if p > 1 else "psum",
                    overlap=ov and p > 1))
                err = float(np.max(np.abs(got - want)))
                assert err < 1e-4, (m, k, n, p, q, ov, err)
    print("array level OK")


def check_ops_dispatch():
    """ops.matmul routes through the pack above the context threshold
    and stays single-kernel below it / without a context."""
    rng = np.random.default_rng(3)
    mesh = _mesh((1, 8))
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    want = np.asarray(ref.ref_gemm(a, b))
    with pg.pack_context(mesh, min_flops=0):
        assert ops.pack_eligible(32, 64, 48)
        got = np.asarray(ops.matmul(a, b))
        # mode="ref" stays the pure single-process oracle.
        got_ref = np.asarray(ops.matmul(a, b, mode="ref"))
    assert not ops.pack_eligible(32, 64, 48)
    assert float(np.max(np.abs(got - want))) < 1e-4
    assert float(np.max(np.abs(got_ref - want))) == 0.0
    with pg.pack_context(mesh, min_flops=1e18):
        assert not ops.pack_eligible(32, 64, 48)  # below threshold
    print("ops dispatch OK")


def check_overlap_resolution():
    """Explicit overlap=True pins the ring schedule family even when
    the tuner's cached pick for the shape is psum (it must not raise
    based on cache state), and a fully-specified psum call never
    consults the tuner."""
    from repro.tuning import dispatch
    from repro.tuning.cache import cache_key

    mesh = _mesh((1, 8))
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 24)), jnp.float32)
    want = np.asarray(ref.ref_gemm(a, b))

    backend, kind = dispatch.backend_fingerprint()
    key = cache_key("pack", 16, 24, 64, "float32", backend, kind,
                    extra="mesh1x8")
    tc = dispatch.get_cache()
    tc.put(key, {"config": {"p": 2, "q": 4, "stagger": 0,
                            "reduce": "psum", "overlap": False},
                 "us": 1.0})
    tc.save()
    got = np.asarray(pg.pack_gemm(a, b, mesh, overlap=True))
    assert float(np.max(np.abs(got - want))) < 1e-4

    orig = dispatch.pack_config
    def boom(*a_, **k_):
        raise AssertionError("fully-specified call consulted the tuner")
    dispatch.pack_config = boom
    try:
        got = np.asarray(pg.pack_gemm(a, b, mesh, p=2, q=4, stagger=0,
                                      reduce="psum"))
    finally:
        dispatch.pack_config = orig
    assert float(np.max(np.abs(got - want))) < 1e-4
    print("overlap resolution OK")


def check_engine_pack():
    """ServeEngine with pack_mesh: lm-head/ffn GEMMs shard through
    packs; prefill logits match the unpacked engine and generation runs."""
    from repro.models import ModelConfig, init_cache, init_params, prefill
    from repro.serving.engine import ServeConfig, ServeEngine

    cfg = ModelConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_head=16, d_ff=128, vocab_size=256,
                      compute_dtype="float32", cache_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = _mesh((1, 8))
    rng = np.random.default_rng(4)
    prompts = rng.integers(0, 256, size=(2, 16)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompts)}

    # Reference logits: no pack context.
    caches = init_cache(cfg, 2, 32)
    logits_ref, _ = prefill(params, batch, cfg, caches)

    # lm head at prefill is (2*16, 64, 256): 2*32*64*256 FLOPs ~ 1.05e6.
    scfg = ServeConfig(batch_slots=2, max_len=32, pack_mesh=mesh,
                       pack_min_flops=1e6)
    engine = ServeEngine(cfg, params, scfg)
    try:
        assert engine.packed_gemms > 0, "no GEMM cleared the pack threshold"
        assert pg.get_pack_context() is not None
        caches = engine.new_cache()
        logits_pack, _ = engine._prefill(engine.params, batch, caches)
        err = float(jnp.max(jnp.abs(logits_pack - logits_ref)))
        assert err < 1e-3, err
        out = engine.generate(prompts, max_new=3)
        assert out.shape == (2, 3)
    finally:
        engine.close()
    assert pg.get_pack_context() is None, "close() must release the context"
    print("engine pack OK")


def check_tune_pack_measured():
    """tune_pack measures survivors (schema v3: overlap included) on
    the live mesh and dispatch then serves the tuned grid from the
    cache."""
    from repro.tuning import dispatch

    res = dispatch.tune_pack(16, 32, 24, "float32", data_axis=2,
                             model_axis=4, keep=3, warmup=0, reps=1)
    assert not res.cache_hit and res.best is not None
    assert len(res.trials) == 3
    assert all("us" in t for t in res.trials), "expected measured trials"
    assert all("overlap" in t["config"] for t in res.trials), \
        "schema v3 candidates carry the overlap bit"
    cand = dispatch.pack_config(16, 32, 24, jnp.float32, data_axis=2,
                                model_axis=4)
    assert (cand.p, cand.q, cand.stagger, cand.reduce, cand.overlap) == (
        res.best["p"], res.best["q"], res.best["stagger"],
        res.best["reduce"], res.best["overlap"])
    res2 = dispatch.tune_pack(16, 32, 24, "float32", data_axis=2,
                              model_axis=4)
    assert res2.cache_hit
    print("tune pack measured OK")


def check_analytic_entry_remeasured():
    """A cached analytic fallback entry is NOT a permanent hit: on a
    host with enough devices tune_pack re-measures and overwrites it
    (the dispatch.py:_cached_result bugfix)."""
    from repro.tuning import dispatch
    from repro.tuning.cache import cache_key
    from repro.tuning.prior import analytic_pack

    backend, kind = dispatch.backend_fingerprint()
    key = cache_key("pack", 24, 16, 48, "float32", backend, kind,
                    extra="mesh2x4")
    tc = dispatch.get_cache()
    # Simulate an under-provisioned host's leftover: analytic-flagged.
    tc.put(key, {"config": analytic_pack(24, 48, 16, 2, 4).to_json(),
                 "analytic": True, "space_size": 0, "measured": 0,
                 "tuned_at": 0.0})
    tc.save()
    # This host has 8 devices >= 2*4: the analytic entry is a miss.
    res = dispatch.tune_pack(24, 48, 16, "float32", data_axis=2,
                             model_axis=4, keep=2, warmup=0, reps=1)
    assert not res.cache_hit, "analytic entry must be re-measured"
    assert res.trials and all("us" in t for t in res.trials)
    assert not tc.get(key).get("analytic"), "entry must now be measured"
    # Once measured, it IS a permanent hit.
    assert dispatch.tune_pack(24, 48, 16, "float32", data_axis=2,
                              model_axis=4).cache_hit
    print("analytic remeasure OK")


if __name__ == "__main__":
    check_pack_numerics()
    check_pack_int8()
    check_overlap_invariance()
    check_array_level()
    check_ops_dispatch()
    check_overlap_resolution()
    check_engine_pack()
    check_tune_pack_measured()
    check_analytic_entry_remeasured()
    print("ALL PACK OK")
