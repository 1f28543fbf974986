"""A throwaway benchmark root with one CPU-sized configuration and two
cells, for the harness's own tests."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"

CELLS = {"tiny.chat": "tiny_chat", "tiny.backlog": "tiny_backlog"}


def make_root(tmp: Path, limit: float = 1e-3) -> Path:
    """``tmp`` laid out as a checkout: BENCHMARK.json naming the tiny
    cells, their config, mixes and limits, and the real metric readers,
    architecture modules and references."""
    (tmp / "bench" / "traffic").mkdir(parents=True)
    (tmp / "bench" / "limits").mkdir()
    (tmp / "bench" / "configs").mkdir()
    for kind in ("metrics", "arch", "reference"):
        shutil.copytree(BENCH / kind, tmp / "bench" / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny_config.json", tmp / "bench" / "configs"
                / "tiny.json")
    for cell, mix in CELLS.items():
        shutil.copy(DATA / f"{mix}.json", tmp / "bench" / "traffic"
                    / f"{mix}.json")
        (tmp / "bench" / "limits" / f"{cell}.json").write_text(json.dumps(
            {"mean_gap": {"limit": limit}, "min_tokens": 1}))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = dict(real)
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": c, "config": "tiny", "traffic": m,
                           "chips": 1, "why": "test"}
                          for c, m in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m) for m in real[group]]
        for m in bench[group]:
            m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
