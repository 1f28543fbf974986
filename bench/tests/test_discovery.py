"""A cell, a configuration, an architecture, a mix and a per-layer metric
are found by name from new files alone."""

import json
import shutil

import pytest

from bench import cell
from bench.lib import layers, model
from bench.tests.test_faults import SEED, altered
from bench.tests.tiny import DATA, make_root


def test_new_files_only(tmp_path):
    root = make_root(tmp_path)
    # A later change adds a configuration, a mix, a metric and a cell:
    # new files and new entries, no edit to a file that is there.
    shutil.copy(root / "bench" / "configs" / "tiny.json",
                root / "bench" / "configs" / "tiny2.json")
    mix = json.loads((root / "bench" / "traffic" / "tiny_chat.json")
                     .read_text())
    mix["arrivals"]["rate_per_s"] = 3.0
    (root / "bench" / "traffic" / "tiny_slow.json").write_text(
        json.dumps(mix))
    (root / "bench" / "metrics" / "answer.new.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny2", "source": "test",
                             "file": "bench/configs/tiny2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny2.slow", "config": "tiny2",
                               "traffic": "tiny_slow", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answer.new", "unit": "u",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "output_tok_s",
                               "workloads": ["tiny2.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = cell.load_cell(root, "tiny2.slow")
    assert spec["config_file"] == root / "bench" / "configs" / "tiny2.json"
    assert spec["mix"]["arrivals"]["rate_per_s"] == 3.0
    assert "answer.new" in [m["name"] for m in spec["per_layer"]]
    # The metric is read in its own cell and no other.
    assert "answer.new" not in [
        m["name"] for m in cell.load_cell(root, "tiny.chat")["per_layer"]]
    got = layers.read_all(root, [m for m in spec["per_layer"]
                                 if m["name"] == "answer.new"], None)
    assert got == {"answer.new": {"value": 42.0, "unit": "u"}}


def test_a_reader_with_nothing_to_read_is_left_out(tmp_path):
    root = make_root(tmp_path)
    (root / "bench" / "metrics" / "silent.py").write_text(
        "def read(ctx):\n    return None\n")
    assert layers.read_all(root, [{"name": "silent", "unit": "%"}],
                           None) == {}


def test_unknown_workload_fails(tmp_path):
    with pytest.raises(SystemExit):
        cell.load_cell(make_root(tmp_path), "no.such.cell")


def test_every_named_file_exists():
    root = cell.ROOT
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (root / c["file"]).is_file()
    for w in bench["workloads"]:
        spec = cell.load_cell(root, w["name"])
        assert spec["mix"]["engine"]["slots"] > 0
        assert (root / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in bench["per_layer"]:
        assert (root / "bench" / "metrics" / f"{m['name']}.py").is_file()


MOE_FILES = {"moe_gqa_arch.py": "arch/moe_gqa.py",
             "moe_gqa_reference.py": "reference/moe_gqa.py",
             "tiny_moe_config.json": "configs/tiny_moe.json",
             "tiny_chat.json": "traffic/tiny_moe_chat.json"}


def snapshot(root):
    return {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """A root to which a configuration of a new architecture (dense GQA
    attention with routed experts) is added: its architecture module,
    reference, configuration, mix and limits as new files, and new
    entries in BENCHMARK.json."""
    root = make_root(tmp_path_factory.mktemp("bench"))
    before = snapshot(root)
    for src, dst in MOE_FILES.items():
        shutil.copy(DATA / src, root / "bench" / dst)
    (root / "bench" / "limits" / "tiny_moe.chat.json").write_text(
        json.dumps({"mean_gap": {"limit": 1e-3}, "min_tokens": 1}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    old = {k: list(v) for k, v in bench.items() if isinstance(v, list)}
    bench["configs"].append({"name": "tiny_moe", "source": "test",
                             "file": "bench/configs/tiny_moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_moe.chat", "config": "tiny_moe",
                               "traffic": "tiny_moe_chat", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = snapshot(root)
    assert {p for p in before if after[p] != before[p]} == {
        root / "BENCHMARK.json"}
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in old.items():
        assert new[key][:len(entries)] == entries
    return root


def test_new_architecture_from_files_only(moe_root):
    res = cell.run_cell(moe_root, "tiny_moe.chat", SEED, 1.5, False,
                        require_chip=False)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    shape = model.load_shape(moe_root, moe_root / "bench" / "configs"
                             / "tiny_moe.json", "tiny_moe")
    assert (shape.experts, shape.top_k) == (8, 2)
    assert model.arch_of(shape).__file__ == str(
        moe_root / "bench" / "arch" / "moe_gqa.py")


def test_new_architecture_catches_altered_token(moe_root):
    res = cell.run_cell(moe_root, "tiny_moe.chat", SEED, 1.5, False,
                        require_chip=False, fault=altered)
    assert not res["correct"]
    assert res["check"]["mean_gap"]["value"] > 1e-3


@pytest.mark.parametrize("missing", ["arch", "reference"])
def test_unknown_architecture_fails_before_weights(tmp_path, monkeypatch,
                                                   missing):
    root = make_root(tmp_path)
    for kind in ("arch", "reference"):
        if kind != missing:
            shutil.copy(root / "bench" / kind / "dense_gqa.py",
                        root / "bench" / kind / "nope.py")
    c = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    c["reference"] = "nope"
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))

    def drawn(*_):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(model, "make_weights", drawn)
    with pytest.raises(FileNotFoundError, match=f"bench/{missing}/nope.py"):
        cell.run_cell(root, "tiny.chat", SEED, 1.0, False,
                      require_chip=False)
