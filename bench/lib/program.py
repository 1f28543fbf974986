"""The system under test, as the benchmark drives it: the repository's
``ServeEngine`` built for a configuration and a mix.

This module puts the program (``src/repro``) on the path and builds the
engine with the mix's settings.  The configuration's architecture
module (:mod:`bench.lib.model`) turns the benchmark's sizes into the
program's ``ModelConfig`` and hands the canonical weights over in the
program's parameter layout (the same arrays, regrouped).
"""

from __future__ import annotations

import sys
from pathlib import Path

from bench.lib.model import arch_of

CHECKOUT = Path(__file__).resolve().parents[2]


def import_program() -> None:
    """Put the checkout's ``src`` on the path; fails where it is absent."""
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"no program under {src}: run from a checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def build_engine(s, weights: dict, engine: dict, seed: int,
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
    arch = arch_of(s)
    return ServeEngine(arch.program_config(s), arch.program_params(weights),
                       scfg)


def set_tuning_cache(path: Path) -> None:
    """Kernel tiles from the analytic prior: an empty tuning cache at a
    fixed path in the checkout, so no cache elsewhere changes them."""
    from repro.tuning import dispatch
    path.parent.mkdir(parents=True, exist_ok=True)
    path.unlink(missing_ok=True)
    dispatch.set_cache_path(path)
