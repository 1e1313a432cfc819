"""The harness finds a cell, its files and its metrics by name alone: a
configuration, a traffic mix, a cell and a per-layer metric added as new
files and entries need no edit of a file that is there."""
import json
import shutil

import pytest

from bench import common


def test_every_committed_cell_resolves():
    bench = common.benchmark()
    for w in bench["workloads"]:
        cell = common.Cell(w["name"])
        assert cell.job == "train"
        assert (common.BENCH / "jobs" / f"{cell.job}.py").exists()
        assert cell.limits["compare"]
        for m in cell.per_layer():
            assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = common.benchmark()
    bench["workloads"].append({
        "name": "qwen3-0.6b.finetune-2k", "config": "qwen3-0.6b",
        "traffic": "finetune-2k", "chips": 1, "why": "longer sequences"})
    bench["per_layer"].append({
        "name": "new_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "train_tokens_per_s",
        "workloads": ["qwen3-0.6b.finetune-2k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((common.BENCH / "traffic" / "finetune-1k.json")
                         .read_text())
    traffic.update(seq_len=2048, batch=2)
    (root / "bench" / "traffic" / "finetune-2k.json").write_text(
        json.dumps(traffic))
    shutil.copy(common.BENCH / "limits" / "qwen3-0.6b.finetune-1k.json",
                root / "bench" / "limits" / "qwen3-0.6b.finetune-2k.json")
    (root / "bench" / "metrics" / "new_share.py").write_text(
        "def read(r):\n    return 100.0 * r['trace']['busy_s']\n")

    cell = common.Cell("qwen3-0.6b.finetune-2k", bench=bench,
                       bench_dir=root / "bench")
    assert cell.traffic["seq_len"] == 2048
    assert cell.config["model"]["d_model"] == 1024
    names = [m["name"] for m in cell.per_layer()]
    assert names == ["new_share"]
    reader = common.load_module(root / "bench" / "metrics" / "new_share.py")
    assert reader.read({"trace": {"busy_s": 0.5}}) == 50.0
    # the old cells read what they read before
    old = common.Cell("qwen3-0.6b.finetune-1k", bench=bench,
                      bench_dir=root / "bench")
    assert "new_share" not in [m["name"] for m in old.per_layer()]


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        common.Cell("no-such.cell")


def test_a_per_layer_metric_is_read_only_in_the_cells_it_lists():
    bench = common.benchmark()
    bench["per_layer"].append({
        "name": "listed_nowhere", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "train_tokens_per_s", "workloads": []})
    for w in bench["workloads"]:
        cell = common.Cell(w["name"], bench=bench)
        names = [m["name"] for m in cell.per_layer()]
        assert "listed_nowhere" not in names
        assert names == [m["name"] for m in bench["per_layer"]
                         if w["name"] in m["workloads"]]
