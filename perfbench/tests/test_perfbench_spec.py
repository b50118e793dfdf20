"""A new cell, configuration, traffic mix and metric are new files plus a
new entry in BENCHMARK.json: nothing that is there changes."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from pbench import spec  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _copy_of_checkout(tmp_path: Path) -> Path:
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def _add_files_and_entries(root: Path) -> dict:
    b = root / "perfbench"
    shutil.copy(FIXTURES / "toy.json", b / "configs" / "toy-gqa.json")
    shutil.copy(FIXTURES / "toy_open.json", b / "traffic" / "toy_chat.json")
    (b / "limits" / "toy-gqa.toy_chat.json").write_text(
        json.dumps({"logit_gap": 0.05, "min_compared_tokens": 8}))
    (b / "metrics" / "served_requests.toy.py").write_text(
        "def read(run):\n    return float(len(run))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-gqa", "source": "tests",
                             "file": "perfbench/configs/toy-gqa.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy-gqa.toy_chat",
                               "config": "toy-gqa", "traffic": "toy_chat",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p90_ms":
            m["workloads"].append("toy-gqa.toy_chat")
    bench["per_layer"].append({
        "name": "served_requests.toy", "unit": "1", "better": "higher",
        "source": "host_clock", "layer": "client", "moves": "ttft_p90_ms",
        "workloads": ["toy-gqa.toy_chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def test_new_cell_config_traffic_and_metric_from_new_files(tmp_path):
    root = _copy_of_checkout(tmp_path)
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    _add_files_and_entries(root)
    for p, data in before.items():          # nothing that was there changed
        assert p.read_bytes() == data
    cell = spec.load_cell("toy-gqa.toy_chat", root)
    assert cell.config["d_model"] == 256
    assert cell.traffic["loop"] == "open"
    assert cell.limits["logit_gap"] == 0.05
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p90_ms", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["served_requests.toy"]
    assert cell.reader("served_requests.toy")([1, 2, 3]) == 3.0
    # the cells that were there are as they were
    old = spec.load_cell("deepseek-67b-s6.docqa", root)
    assert "served_requests.toy" not in [m["name"] for m in old.per_layer]


def test_metric_without_cell_list_goes_where_its_metric_is(tmp_path):
    root = _copy_of_checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "ttft_p90_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "client", "moves": "ttft_p90_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    docqa = spec.load_cell("deepseek-67b-s6.docqa", root)
    batch = spec.load_cell("deepseek-67b-s6.batch", root)
    assert "ttft_p90_ms" in [m["name"] for m in docqa.per_layer]
    assert "ttft_p90_ms" not in [m["name"] for m in batch.per_layer]


def test_every_declared_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_reader(BENCH, m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert "logit_gap" in cell.limits


def test_missing_pieces_are_errors(tmp_path):
    root = _copy_of_checkout(tmp_path)
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("nope.nothing", root)
    (root / "perfbench" / "traffic" / "docqa.json").unlink()
    with pytest.raises(spec.SpecError, match="missing"):
        spec.load_cell("deepseek-67b-s6.docqa", root)
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.load_reader(root / "perfbench", "no_such_metric")


def _run_py(cwd: Path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "deepseek-67b-s6.batch", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_no_accelerator_no_result():
    p = _run_py(ROOT)
    assert p.returncode == 3 and p.stdout == ""
    assert "accelerator" in p.stderr


def test_unknown_cell_no_result(tmp_path):
    root = _copy_of_checkout(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != "deepseek-67b-s6.batch"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = _run_py(root)
    assert p.returncode == 2 and p.stdout == ""
