"""The DeepSeek-V2 architecture: its cost terms, its mapping onto the
program, and a CPU-sized configuration served through ``ServeEngine``
against the plain reference."""

import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell
from bench.lib import costs, model, program
from bench.tests.test_faults import SEED, altered
from bench.tests.tiny import DATA, make_root

program.import_program()

# matmul_params, gemm_flops(forward_gemms(s, 512, 1)),
# attention_flops(s, causal_keys(512, 512)), paged_decode_cost(s,
# [1024] * 32), gemm_flops(expert_gemms(s, 48)): worked out by hand from
# the published widths (PERF.md §4).
FULL_COSTS = (1_270_480_896, 1_086_643_503_104, 108_787_138_560,
              (30_802_968_576, 1_049_296_896), 16_194_207_744)


def full_shape():
    return model.load_shape(cell.ROOT, cell.ROOT / "bench" / "configs"
                            / "deepseek_v2_lite.json", "deepseek_v2_lite")


def tiny_shape():
    return model.load_shape(cell.ROOT, DATA / "tiny_dsv2_config.json",
                            "tiny_dsv2")


def test_cost_terms_pinned():
    s = full_shape()
    arch = model.arch_of(s)
    params, gemm, attn, decode, experts = FULL_COSTS
    assert arch.matmul_params(s) == params
    assert costs.gemm_flops(arch.forward_gemms(s, 512, 1)) == gemm
    assert arch.attention_flops(s, costs.causal_keys(512, 512)) == attn
    assert arch.paged_decode_cost(s, [1024] * 32) == decode
    assert costs.gemm_flops(arch.expert_gemms(s, 48)) == experts
    # 36 held pairs a 48-row call; 8 reached experts' gate, up and down
    # (69,206,016 weights) and the pairs' rows in and out, 26 layers.
    assert arch.expert_gmm_cost(s, 48, 8) == (experts, 3_606_380_544)
    # A decode key reads one 576-lane latent row a layer: 31,104 bytes.
    assert arch.paged_decode_cost(s, [1])[1] \
        - arch.paged_decode_cost(s, [0])[1] == 27 * 576 * 2


def test_published_widths_and_memory():
    s = full_shape()
    assert (s.layers, s.dense_layers, s.d_model, s.heads, s.kv_lora_rank,
            s.qk_nope, s.qk_rope, s.v_head, s.dense_d_ff, s.expert_d_ff,
            s.router_experts, s.top_k, s.shared_experts, s.vocab) == (
        27, 1, 2048, 16, 512, 128, 64, 128, 10944, 1408, 64, 6, 2, 102400)
    assert (s.held_experts, s.first_held, s.norm_topk) == (8, 0, False)
    shapes, _ = model.arch_of(s).weight_shapes(s)
    assert sum(int(np.prod(v)) for v in shapes.values()) == 3_110_989_312


def test_program_mapping():
    s = full_shape()
    arch = model.arch_of(s)
    cfg = arch.program_config(s)
    assert (cfg.n_layers, cfg.first_dense_layers, cfg.n_groups,
            cfg.d_ff, cfg.moe.held, cfg.moe.num_experts,
            cfg.moe.top_k, cfg.moe.norm_topk_prob) == (
        27, 1, 26, 10944, (0, 8), 64, 6, False)
    assert cfg.mla.latent_dim == 576
    shapes, _ = arch.weight_shapes(s)
    w = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}
    leaves = jax.tree_util.tree_leaves(arch.program_params(w))
    # Every canonical weight is one leaf of the program's tree, as is.
    assert sorted(map(id, leaves)) == sorted(map(id, w.values()))


def test_latent_cache_bytes_and_no_int8_pages():
    from repro.serving.engine import ServeConfig, ServeEngine
    s = full_shape()
    arch = model.arch_of(s)
    shapes, _ = arch.weight_shapes(s)
    w = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}
    cfg, params = arch.program_config(s), arch.program_params(w)
    scfg = ServeConfig(batch_slots=2, max_len=64, kv="paged", page_size=16,
                       prefill_chunk=32, pretune=False)
    assert ServeEngine(cfg, params, scfg).token_kv_bytes() == 31_104
    with pytest.raises(ValueError, match="int8"):
        ServeEngine(cfg, params, ServeConfig(
            batch_slots=2, max_len=64, kv="paged", page_size=16,
            kv_dtype="int8", pretune=False))


def test_reference_matches_program_forward():
    from repro.models import forward
    s = tiny_shape()
    arch, ref = model.arch_of(s), model.reference_of(s)
    w = model.make_weights(s, SEED)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, s.vocab, 40),
                       jnp.int32)
    got, _, _ = forward(arch.program_params(w), {"tokens": toks[None]},
                        arch.program_config(s))
    want = ref.logits(w, toks, s)
    np.testing.assert_allclose(got[0], want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_served_logits_match_reference(prefix_cache):
    """Chunked prefill into the latent pool, paged absorbed decode and,
    with the prefix cache, prompts that share pages: every logits row
    the engine sampled from is the reference's at that position."""
    from repro.serving.engine import ServeConfig, ServeEngine
    s = tiny_shape()
    arch, ref = model.arch_of(s), model.reference_of(s)
    w = model.make_weights(s, SEED)
    eng = ServeEngine(arch.program_config(s), arch.program_params(w),
                      ServeConfig(batch_slots=3, max_len=64, kv="paged",
                                  page_size=4, prefill_chunk=8,
                                  prefix_cache=prefix_cache))
    rows = {}
    eng.logits_tap = lambda rid, pos, lg: rows.setdefault(rid, {}).update(
        {pos: np.asarray(lg)})
    rng = np.random.default_rng(1)
    base = rng.integers(1, s.vocab, 30).astype(np.int32)
    prompts = [base[:21], base[:27], rng.integers(1, s.vocab, 9)
               .astype(np.int32), base[:25]]
    rids = [eng.submit(p, 8) for p in prompts]
    out = eng.drain()
    for p, r in zip(prompts, rids):
        seq = np.concatenate([p, out[r][:-1]]).astype(np.int32)
        want = np.asarray(ref.logits(w, jnp.asarray(seq), s))
        for pos, got in rows[r].items():
            np.testing.assert_allclose(got, want[pos], atol=2e-4, rtol=2e-4)
    if prefix_cache:
        assert eng.stats["prefix_hits"] > 0
    counters = eng._obs.registry.counters
    assert 0 < counters["engine.moe.held_rows"].value \
        <= counters["engine.moe.routed_rows"].value
    # Each expert-layer call reaches at most the held experts, and only
    # those that got a pair.
    reached = counters["engine.moe.held_experts_reached"].value
    assert 0 < reached <= min(
        counters["engine.moe.held_rows"].value,
        counters["engine.moe.layer_calls"].value * s.held_experts)


def test_copy_page_moves_latent_rows():
    from repro.serving.engine import ServeConfig, ServeEngine
    s = tiny_shape()
    arch = model.arch_of(s)
    w = model.make_weights(s, SEED)
    eng = ServeEngine(arch.program_config(s), arch.program_params(w),
                      ServeConfig(batch_slots=2, max_len=32, kv="paged",
                                  page_size=4, prefill_chunk=8,
                                  prefix_cache=True))
    prompt = np.arange(1, 14, dtype=np.int32)
    eng.submit(prompt, 3)
    eng.drain()
    cached, _ = eng.prefix.lookup(prompt)       # the prompt's full pages
    src, dst = cached[0], 15
    caches = eng._copy_page(eng.caches, jnp.int32(src), jnp.int32(dst))
    for before, after in zip(eng.caches, caches):
        pool, new = before["mla"]["lat_pages"], after["mla"]["lat_pages"]
        np.testing.assert_array_equal(new[:, dst], pool[:, src])
        assert np.any(np.asarray(new[:, dst]))


@pytest.fixture(scope="module")
def dsv2_root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    shutil.copy(DATA / "tiny_dsv2_config.json",
                root / "bench" / "configs" / "tiny_dsv2.json")
    shutil.copy(DATA / "tiny_chat.json",
                root / "bench" / "traffic" / "tiny_dsv2_chat.json")
    (root / "bench" / "limits" / "tiny_dsv2.chat.json").write_text(
        json.dumps({"mean_gap": {"limit": 1e-3}, "min_tokens": 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_dsv2", "source": "test",
                             "file": "bench/configs/tiny_dsv2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_dsv2.chat",
                               "config": "tiny_dsv2",
                               "traffic": "tiny_dsv2_chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cell_correct_on_cpu(dsv2_root):
    res = cell.run_cell(dsv2_root, "tiny_dsv2.chat", SEED, 1.5, False,
                        require_chip=False)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_cell_catches_altered_token(dsv2_root):
    res = cell.run_cell(dsv2_root, "tiny_dsv2.chat", SEED, 1.5, False,
                        require_chip=False, fault=altered)
    assert not res["correct"]
    assert res["check"]["mean_gap"]["value"] > 1e-3


@pytest.mark.parametrize("metric", ["latent_decode_roofline.out",
                                    "expert_gemm_roofline.out"])
def test_new_readers_silent_without_their_kernels(metric):
    """On a trace with no latent decode or expert kernel (a dense model,
    or a program that lacks them) the reader returns None."""
    import warnings

    from bench.lib import layers, xtrace
    from bench.lib.replay import Record, Run, Step
    from bench.lib.traffic import Request
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        trace = xtrace.reduce_file(DATA / "tiny.xplane.pb",
                                   window=(45_000_000, 57_000_000))
    mix = {"engine": {"prefill_chunk": 32}}
    rec = Record(Request(0, -1.0, np.ones(10, np.int32), 4))
    rec.tokens, rec.times, rec.first_step = [1, 2], [-0.5, 0.05], -1
    run = Run(1.0, [rec], [Step(0, 0.0, 0.1, [0])])
    for s in (tiny_shape(),
              model.load_shape(cell.ROOT, DATA / "tiny_config.json", "t")):
        ctx = layers.Context(run=run, shape=s, mix=mix, trace=trace,
                             device_kind="TPU v5 lite")
        assert layers.load_reader(cell.ROOT, metric)(ctx) is None


def test_expert_roofline_counts_staged_weights():
    """The expert reader's least bytes count the held experts reached (the
    program's counters), and its time adds to the kernel's the operations
    that stage the held weight stacks; other operations are left out."""
    from bench.lib import layers, xtrace
    from bench.lib.replay import Record, Run, Step
    from bench.lib.traffic import Request
    from repro import obs
    s = full_shape()
    arch = model.arch_of(s)
    stack = "bf16[8,2048,1408]{2,1,0:T(8,128)(2,1)S(1)}"
    trace = xtrace.Reduced(
        window_s=1.0, busy_s=1.0, devices=1, gaps=[],
        op_s={"%gama_expert_gmm.27 = bf16[384,1408] custom-call(%w.1)": 0.25,
              f"%dynamic-slice_bitcast_fusion.24 = {stack} fusion(%p)": 0.5,
              "%fusion.3": 0.75,
              "%copy.9 = bf16[8,1408,2048]{2,1,0} copy(%q)": 0.125,
              "%gama_gemm.76 = bf16[48,102400] custom-call(%x)": 2.0},
        op_text={"%fusion.3": f"%fusion.3 = {stack} fusion(%r)"})
    staged = 0.5 + 0.75 + 0.125
    assert metric_module().staging_s(trace, s) == pytest.approx(staged)
    reg = obs.reset().registry
    reader = layers.load_reader(cell.ROOT, "expert_gemm_roofline.out")
    rec = Record(Request(0, -1.0, np.ones(1000, np.int32), 8))
    rec.tokens, rec.times, rec.first_step = [1, 2, 3], [-0.5, 0.1, 0.2], -1
    run = Run(1.0, [rec], [Step(0, 0.0, 0.1, [0]), Step(1, 0.1, 0.2, [0])])
    ctx = layers.Context(run=run, shape=s, mix={"engine": {
        "prefill_chunk": 256}}, trace=trace, device_kind="TPU v5 lite")
    assert [(c.kind, c.rows) for c in ctx.calls()] == [("decode", 1)] * 2
    try:
        assert reader(ctx) is None          # no counters: nothing to read
        reg.counter("engine.moe.held_experts_reached").inc(26 * 6)
        reg.counter("engine.moe.layer_calls").inc(26)
        _, nbytes = arch.expert_gmm_cost(s, 1, 6.0)
        want = 100 * 2 * nbytes / 819e9 / (0.25 + staged)
        assert reader(ctx) == pytest.approx(want)
    finally:
        obs.reset()


def metric_module():
    return model.module(cell.ROOT, "metrics", "expert_gemm_roofline.out")
