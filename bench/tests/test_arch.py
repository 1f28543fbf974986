"""The dense GQA architecture module gives the same weights, cost terms
and program mapping as the harness did before architectures became
files of their own: the numbers below were computed by that harness."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cell import ROOT
from bench.lib import costs, model
from bench.tests.tiny import DATA

COSTS = {
    # matmul_params, gemm_flops(forward_gemms(s, 512, 1)),
    # attention_flops(s, causal_keys(512, 512)),
    # paged_decode_cost(s, [1024] * 32)
    "smollm_360m": (361_758_720, 322_216_919_040, 48_349_839_360,
                    (4_026_531_840, 1_346_109_440)),
    "phi3m": (2_890_465_280, 2_792_057_077_760, 64_466_452_480,
              (5_368_709_120, 1_347_420_160)),
}

# sha256 of each array's bytes, first 16 digits: tiny_config.json's
# weights from the seed 2**33 + 7 (both 32-bit words of the seed used).
TINY_WEIGHTS = {
    "down": "ed85efa00899b48c", "embed": "aaac39f1eccf02ff",
    "final_norm": "9c452913ea196ecc", "gate": "209190bcba3df8fc",
    "ln1": "58392b977192bb38", "ln2": "863d432a01ec61b7",
    "up": "089592621fcab8ee", "wk": "5174ef797e861d83",
    "wo": "16e8522bbd7f475a", "wq": "fc537595d55ad798",
    "wv": "fc2fb076fbc81d52",
}

# Where the program's parameter tree holds each canonical weight.
PARAMS = {
    **{f"['blocks'][0]['attn']['{n}']['w']": n
       for n in ("wq", "wk", "wv", "wo")},
    **{f"['blocks'][0]['mlp']['{n}']['w']": n
       for n in ("gate", "up", "down")},
    "['blocks'][0]['ln1']['scale']": "ln1",
    "['blocks'][0]['ln2']['scale']": "ln2",
    "['embed']['table']": "embed", "['final_norm']['scale']": "final_norm",
}


def shape_of(name):
    return model.load_shape(ROOT, ROOT / "bench" / "configs" / f"{name}.json",
                            name)


@pytest.mark.parametrize("name", sorted(COSTS))
def test_cost_terms_pinned(name):
    s = shape_of(name)
    arch = model.arch_of(s)
    params, gemm, attn, decode = COSTS[name]
    assert arch.matmul_params(s) == params
    assert costs.gemm_flops(arch.forward_gemms(s, 512, 1)) == gemm
    assert arch.attention_flops(s, costs.causal_keys(512, 512)) == attn
    assert arch.paged_decode_cost(s, [1024] * 32) == decode


def test_tiny_weights_bit_for_bit():
    s = model.load_shape(ROOT, DATA / "tiny_config.json", "tiny")
    w = model.make_weights(s, 2**33 + 7)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in w.items()}
    assert got == TINY_WEIGHTS


@pytest.mark.parametrize("name", sorted(COSTS))
def test_program_mapping(name):
    from bench.lib import program
    program.import_program()
    from repro.models.config import BlockSpec
    s = shape_of(name)
    arch = model.arch_of(s)
    cfg = arch.program_config(s)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_head, cfg.d_ff, cfg.vocab_size, cfg.tie_embeddings) == (
        s.layers, s.d_model, s.heads, s.kv_heads, s.d_head, s.d_ff,
        s.vocab, s.tied)
    assert cfg.pattern == (BlockSpec("attn", "dense"),) and cfg.moe is None
    assert (cfg.param_dtype, cfg.compute_dtype, cfg.cache_dtype) == (
        "bfloat16",) * 3
    assert (cfg.rope_theta, cfg.norm_eps) == (10000.0, 1e-5)

    shapes, norms = arch.weight_shapes(s)
    assert norms == {"ln1", "ln2", "final_norm"}
    w = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}
    leaves = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_leaves_with_path(arch.program_params(w))}
    want = dict(PARAMS, **({} if s.tied else {"['head']['w']": "head"}))
    assert leaves.keys() == want.keys()
    for path, leaf in leaves.items():
        assert leaf is w[want[path]], path
