"""A cell, a configuration, a mix and a per-layer metric are found by
name from new files alone."""

import json
import shutil

import pytest

from bench import cell
from bench.lib import layers
from bench.tests.tiny import make_root


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
