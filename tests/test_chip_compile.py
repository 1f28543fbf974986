"""Main-path kernels compiled at the served model's real widths for a
described TPU v5e chip (no chip attached).

Interpret mode, which every other kernel test uses, cannot see what the
TPU compiler refuses: block shapes that break the (8, 128) tiling rule,
kernels that claim more VMEM than the scoped limit, DMA slices that are
not lane-aligned.  Each case here lowers one kernel at a
``smollm_360m`` FULL shape (32 layers, d_model 960, 15/5 heads of 64,
d_ff 2560, vocab 49152) and compiles it for ``v5e:2x2``'s first chip —
about a second each, no chip time.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, so a description at import
would make every other test worker fail or collect different tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as C
from repro.kernels.decode_attention import (flash_decode, flash_paged_decode,
                                           latent_paged_decode, page_row_dim)
from repro.kernels.expert_gmm import expert_gmm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gama_gemm
from repro.kernels.ops import _round_up
from repro.serving.engine import model_gemm_shapes
from repro.tuning import dispatch
from repro.tuning.space import DesignSpace

CFG = C.get("smollm_360m")
SLOTS = 4           # decode batch: several slots share every decode step
PROMPT = 512        # prefill chunk / prompt tokens


def _unique(shapes):
    return sorted(set(shapes), key=shapes.index)


DECODE_GEMMS = _unique(model_gemm_shapes(CFG, SLOTS, 1,
                                         include_decode=False))
PREFILL_GEMMS = _unique(model_gemm_shapes(CFG, 1, PROMPT,
                                          include_decode=False))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep it out.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def analytic_plans(tmp_path_factory):
    """Tiles from the analytic prior only: an empty tuning cache."""
    dispatch.set_cache_path(
        tmp_path_factory.mktemp("tuning") / "tuning_cache.json")
    yield
    dispatch.reset()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile for the described chip; raises what the TPU
    compiler raises.  Returns the compiled text for content checks."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_gemm(sharding, m, k, n, dtype, tiles=None, order=None):
    if tiles is None:
        cfg = dispatch.gemm_config(m, k, n, dtype)
        tiles, order = (cfg.tm, cfg.tk, cfg.tn), cfg.order
    tm, tk, tn = tiles
    # The clamps and padding ops.matmul applies before the kernel.
    tm, tk, tn = (min(tm, _round_up(m, 8)), min(tk, _round_up(k, 128)),
                  min(tn, _round_up(n, 128)))
    mp, kp, np_ = _round_up(m, tm), _round_up(k, tk), _round_up(n, tn)
    text = _compile(
        lambda a, b: gama_gemm(a, b, tm=tm, tk=tk, tn=tn,
                               order=order or "mn"),
        _spec(sharding, (mp, kp), dtype), _spec(sharding, (kp, np_), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", DECODE_GEMMS + PREFILL_GEMMS,
                         ids=lambda v: str(v))
def test_gemm_bf16_model_shapes(one_chip, analytic_plans, m, k, n):
    """Every distinct GEMM of a decode step (M = slots) and of a prompt
    chunk (M = 512), at the analytic plan's tiles."""
    _compile_gemm(one_chip, m, k, n, jnp.bfloat16)


def test_gemm_f32_analytic_plan_fits_vmem(one_chip, analytic_plans):
    """The analytic f32 plan for the ffn down projection of a 512-token
    chunk is (512, 2048, 512): 19 MiB of blocks, over the compiler's
    16 MiB default scoped VMEM — it compiles only because the kernel
    passes the planner's limit."""
    cfg = dispatch.gemm_config(512, CFG.d_ff, CFG.d_model, jnp.float32)
    assert (cfg.tm, cfg.tk, cfg.tn) == (512, 2048, 512)
    _compile_gemm(one_chip, 512, CFG.d_ff, CFG.d_model, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gemm_largest_admitted_plan_compiles(one_chip, dtype):
    """The tuner's design space admits tiles up to the VMEM budget; the
    largest-footprint candidate over all of the model's GEMMs must still
    compile, so no plan the tuner may pick is refused."""
    from repro.core.tile_search import tile_vmem_bytes
    from repro.tuning.prior import precision_for
    prec = precision_for(jnp.dtype(dtype).name)

    def footprint(c):
        return tile_vmem_bytes(c.tm, c.tk, c.tn, prec.in_bytes,
                               prec.out_bytes)
    (m, k, n), worst = max(
        ((shape, c) for shape in DECODE_GEMMS + PREFILL_GEMMS
         for c in DesignSpace.gemm(*shape, prec)),
        key=lambda sc: footprint(sc[1]))
    if dtype == jnp.float32:
        # Over the compiler's 16 MiB default scoped VMEM.
        assert footprint(worst) > 16 * 2**20
    _compile_gemm(one_chip, m, k, n, dtype, tiles=(worst.tm, worst.tk,
                                                   worst.tn),
                  order=worst.order)


def test_flash_attention_full_prompt(one_chip):
    """Causal prefill attention over a 512-token prompt, GQA 15/5."""
    h, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
    q = _spec(one_chip, (1, h, PROMPT, d), jnp.bfloat16)
    kv = _spec(one_chip, (1, hkv, PROMPT, d), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, bq=128, bk=128,
                                             causal=True), q, kv, kv)


def test_flash_attention_chunk_at_traced_cursor(one_chip):
    """A 128-token prefill chunk against the slot's 512-row scratch at a
    cursor that is traced (one program for every chunk): the cursor is
    a scalar-prefetch operand, not a constant captured by the kernel."""
    h, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.d_head
    q = _spec(one_chip, (1, h, 128, d), jnp.bfloat16)
    kv = _spec(one_chip, (1, hkv, PROMPT, d), jnp.bfloat16)
    _compile(lambda q, k, v, off: flash_attention(
        q, k, v, bq=128, bk=128, causal=True, q_offset=off),
        q, kv, kv, _spec(one_chip, (), jnp.int32))


def _decode_q(sharding):
    # ops.decode pads each KV head's query group (3 heads) to 8 rows.
    return _spec(sharding, (SLOTS, CFG.n_kv_heads * 8, CFG.d_head),
                 jnp.bfloat16)


def test_flash_decode_batched(one_chip):
    """Dense split-K decode with a per-slot length for every slot: the
    length is a scalar-prefetch operand, not a (1, 1) VMEM block."""
    kv = _spec(one_chip, (SLOTS, CFG.n_kv_heads, 1024, CFG.d_head),
               jnp.bfloat16)
    _compile(lambda q, k, v, ln: flash_decode(q, k, v, length=ln, bk=512),
             _decode_q(one_chip), kv, kv,
             _spec(one_chip, (SLOTS,), jnp.int32))


def _paged_args(sharding, row_dim, page_size, kv_dtype, n_pages=64,
                max_pages=8, slots=SLOTS, hkv=CFG.n_kv_heads,
                d_head=CFG.d_head):
    """Arguments at the served geometry: head dim 64, page rows of
    ``row_dim`` lanes (the serving pools use ``page_row_dim(64)``), the
    query group padded to 8 rows as ``ops.decode_paged`` pads it."""
    q = _spec(sharding, (slots, hkv * 8, d_head), jnp.bfloat16)
    pages = _spec(sharding, (n_pages, hkv, page_size, row_dim), kv_dtype)
    args = [q, pages, pages,
            _spec(sharding, (slots, max_pages), jnp.int32),
            _spec(sharding, (slots,), jnp.int32)]
    if kv_dtype == jnp.int8:
        rows = _spec(sharding, (n_pages, hkv, page_size), jnp.float32)
        args += [rows, rows]
    return args


def _paged_fn(buffers, quantized):
    if quantized:
        return lambda q, k, v, bt, ln, ks, vs: flash_paged_decode(
            q, k, v, bt, length=ln, k_scale=ks, v_scale=vs,
            buffers=buffers)
    return lambda q, k, v, bt, ln: flash_paged_decode(
        q, k, v, bt, length=ln, buffers=buffers)


SERVED_ROWS = page_row_dim(CFG.d_head)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("page_size", [16, 32])
@pytest.mark.parametrize("row_dim", [SERVED_ROWS, CFG.d_head],
                         ids=["rows128", "rows64"])
def test_flash_paged_decode_blockspec_gather(one_chip, kv_dtype, page_size,
                                             row_dim):
    """buffers=1 at the served geometry (head dim 64), with the serving
    pools' lane-padded rows and with unpadded rows: the block table and
    the per-slot lengths are both scalar-prefetched, and int8 pages
    read their (Hkv, page_size) scale block whole."""
    _compile(_paged_fn(1, kv_dtype == jnp.int8),
             *_paged_args(one_chip, row_dim, page_size, kv_dtype))


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("page_size", [16, 32])
def test_flash_paged_decode_dma_gather(one_chip, kv_dtype, page_size):
    """buffers=2 (explicit DMA ping-pong of blocks of whole pages) at
    the served geometries, each page copy lane-aligned and int8 scale
    rows coming through the BlockSpec pipeline: a short table (one
    block), smollm_360m's 32 slots over a 2048-token table (several
    blocks of 256 keys), and Phi-3-medium's 16 slots of 10 KV heads of
    128 with a group of 4 padded to 8.  The kernel keeps its name,
    which the benchmark reads its device time by."""
    tokens = 2048 // page_size
    geometries = [
        {},
        {"slots": 32, "max_pages": tokens, "n_pages": 4096},
        {"slots": 16, "max_pages": tokens, "n_pages": 4096, "hkv": 10,
         "d_head": 128},
    ]
    for geometry in geometries:
        row_dim = page_row_dim(geometry.get("d_head", CFG.d_head))
        text = _compile(_paged_fn(2, kv_dtype == jnp.int8),
                        *_paged_args(one_chip, row_dim, page_size,
                                     kv_dtype, **geometry))
        assert "gama_flash_paged_decode_b2" in text, geometry


def test_flash_paged_decode_dma_gather_refuses_narrow_heads(one_chip):
    """Page rows of 64 lanes cannot be sliced by a DMA on the TPU; the
    kernel says so up front instead of failing deep in Mosaic."""
    with pytest.raises(ValueError, match="page_row_dim"):
        _compile(_paged_fn(2, False),
                 *_paged_args(one_chip, CFG.d_head, 16, jnp.bfloat16))


def test_latent_paged_decode_at_deepseek_v2_lite(one_chip):
    """The latent decode at DeepSeek-V2-Lite's served geometry: 48
    slots, 16 heads over one latent row a key, 576 lanes stored in
    ``page_row_dim(576)`` = 640 (the compiler refuses a DMA of a
    576-lane row), 128 pages of 16 a table.  The kernel keeps its name,
    which the benchmark reads its device time by."""
    w = page_row_dim(576)
    text = _compile(
        lambda q, p, bt, ln: latent_paged_decode(
            q, p, bt, length=ln, value_dim=512, scale=0.114721),
        _spec(one_chip, (48, 16, w), jnp.bfloat16),
        _spec(one_chip, (48 * 128 + 1, 1, 16, w), jnp.bfloat16),
        _spec(one_chip, (48, 128), jnp.int32),
        _spec(one_chip, (48,), jnp.int32))
    assert "gama_latent_paged_decode" in text


@pytest.mark.parametrize("m,k,n", [(384, 2048, 1408), (1536, 1408, 2048)],
                         ids=["decode_gate", "chunk_down"])
def test_expert_gmm_at_deepseek_v2_lite(one_chip, m, k, n):
    """The held experts' grouped matmul over 8 experts of 1408 at
    DeepSeek-V2-Lite's widths: a 48-slot decode's 288 pairs (tiles of
    128 rows) and a 256-row chunk's 1536; each expert's whole (K, N)
    weight block is one VMEM block."""
    text = _compile(expert_gmm, _spec(one_chip, (m, k), jnp.bfloat16),
                    _spec(one_chip, (8, k, n), jnp.bfloat16),
                    _spec(one_chip, (8,), jnp.int32))
    assert "gama_expert_gmm" in text
