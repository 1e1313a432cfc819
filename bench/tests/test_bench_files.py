"""The harness finds a cell, its files and its metrics by name alone: a
configuration, a traffic mix, a cell and a per-layer metric added as new
files and entries need no edit of a file that is there."""
import json
import pathlib
import shutil

import pytest

from bench import common


# what a reference module supplies to the fine-tuning job
REFERENCE = ("model_from", "train_loss", "PROGRAM_FIELDS", "weight_std",
             "weight_const", "reference_path", "per_layer")


def test_every_committed_cell_resolves():
    bench = common.benchmark()
    for w in bench["workloads"]:
        cell = common.Cell(w["name"])
        assert (common.BENCH / "jobs" / f"{cell.job}.py").exists()
        for key in ("reference", "work"):
            assert (common.BENCH / key
                    / f"{cell.config[key]}.py").exists(), key
        ref = common.reference_of(cell)
        missing = [n for n in REFERENCE if not hasattr(ref, n)]
        assert not missing, missing
        assert set(ref.PROGRAM_FIELDS) <= set(cell.config["model"])
        assert callable(common.work_of(cell).train_flops_per_token)
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


def test_a_configuration_that_names_a_new_reference_file_is_checked_by_it(
        tmp_path):
    from bench import program
    root = tmp_path / "checkout"
    shutil.copytree(common.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = common.benchmark()
    bench["configs"].append({"name": "qwen3-0.6b-copy",
                             "file": "bench/configs/qwen3-0.6b-copy.json"})
    bench["workloads"].append({
        "name": "qwen3-0.6b-copy.finetune-1k", "config": "qwen3-0.6b-copy",
        "traffic": "finetune-1k", "chips": 1, "why": "a new reference"})
    ref_dir = root / "bench" / "reference"
    # a copy of the SPT reference under another name, whose map of
    # program fields pairs the model's d_model with the program's d_ff
    (ref_dir / "spt_copy.py").write_text(
        (ref_dir / "spt.py").read_text()
        + '\nPROGRAM_FIELDS = {**PROGRAM_FIELDS, "d_model": "d_ff"}\n')
    conf = common.load_json(common.BENCH / "configs" / "qwen3-0.6b.json")
    conf["reference"] = "spt_copy"
    (root / "bench" / "configs" / "qwen3-0.6b-copy.json").write_text(
        json.dumps(conf))
    shutil.copy(common.BENCH / "limits" / "qwen3-0.6b.finetune-1k.json",
                root / "bench" / "limits"
                / "qwen3-0.6b-copy.finetune-1k.json")

    cell = common.Cell("qwen3-0.6b-copy.finetune-1k", bench=bench,
                       bench_dir=root / "bench")
    ref = common.reference_of(cell)
    assert pathlib.Path(ref.__file__) == ref_dir / "spt_copy.py"
    assert ref.PROGRAM_FIELDS["d_model"] == "d_ff"
    with pytest.raises(ValueError, match="d_ff=3072"):
        program.model_config(cell.config, cell.bench_dir)
    # the committed reference, found the same way, takes the same file
    conf["reference"] = "spt"
    assert program.model_config(conf, cell.bench_dir).d_ff == 3072


@pytest.mark.parametrize("key", ["reference", "work"])
def test_a_configuration_without_its_reference_or_work_is_refused_by_key(
        key):
    conf = common.load_json(common.BENCH / "configs" / "qwen3-0.6b.json")
    missing = {k: v for k, v in conf.items() if k != key}
    with pytest.raises(KeyError, match=key):
        common.named_module(missing, key)
    with pytest.raises(FileNotFoundError, match=key):
        common.named_module(dict(conf, **{key: "no_such_model"}), key)


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
