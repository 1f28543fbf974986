"""The system under test, as the benchmark drives it: the repository's
``ServeEngine`` built for a configuration and a mix.

This is the one module that imports the program (``src/repro``).  It
turns the benchmark's sizes into the program's ``ModelConfig``, hands
the canonical weights over in the program's parameter layout (the same
arrays, regrouped), and builds the engine with the mix's settings.
"""

from __future__ import annotations

import sys
from pathlib import Path

from bench.lib.model import Shape

CHECKOUT = Path(__file__).resolve().parents[2]


def import_program() -> None:
    """Put the checkout's ``src`` on the path; fails where it is absent."""
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"no program under {src}: run from a checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def model_config(s: Shape):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=s.name, n_layers=s.layers, d_model=s.d_model, n_heads=s.heads,
        n_kv_heads=s.kv_heads, d_head=s.d_head, d_ff=s.d_ff,
        vocab_size=s.vocab, tie_embeddings=s.tied, rope_theta=s.rope_theta,
        norm_eps=s.eps, param_dtype=s.dtype, compute_dtype=s.dtype,
        cache_dtype=s.dtype)


def program_params(w: dict) -> dict:
    """The canonical weights in the program's tree (one dense block
    pattern, layers stacked on the leading axis)."""
    block = {
        "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
        "attn": {n: {"w": w[n]} for n in ("wq", "wk", "wv", "wo")},
        "mlp": {n: {"w": w[n]} for n in ("gate", "up", "down")},
    }
    p = {"embed": {"table": w["embed"]},
         "final_norm": {"scale": w["final_norm"]}, "blocks": [block]}
    if "head" in w:
        p["head"] = {"w": w["head"]}
    return p


def build_engine(s: Shape, weights: dict, engine: dict, seed: int,
                 **overrides):
    """A paged, chunked-prefill ``ServeEngine`` with the mix's settings;
    ``overrides`` go to ``ServeConfig`` as they are (the output check's
    control switches the program's int8 paths on this way)."""
    from repro.serving.engine import ServeConfig, ServeEngine
    scfg = ServeConfig(
        batch_slots=engine["slots"], max_len=engine["max_len"],
        seed=seed & 0x7FFFFFFF, kv="paged", page_size=engine["page_size"],
        prefill_chunk=engine["prefill_chunk"],
        prefix_cache=engine["prefix_cache"], **overrides)
    return ServeEngine(model_config(s), program_params(weights), scfg)


def set_tuning_cache(path: Path) -> None:
    """Kernel tiles from the analytic prior: an empty tuning cache at a
    fixed path in the checkout, so no cache elsewhere changes them."""
    from repro.tuning import dispatch
    path.parent.mkdir(parents=True, exist_ok=True)
    path.unlink(missing_ok=True)
    dispatch.set_cache_path(path)
