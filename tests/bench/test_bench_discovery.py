"""The harness finds cells, configurations, traffic mixes and metric
readers by file name, agrees with ``BENCHMARK.json``, and refuses to run
without its TPU chips or without the program."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_an_added_cell_file_is_found_without_an_edit(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.loads((bench / "workloads"
                       / "gpt2-large.rmnp.b8s1024.json").read_text())
    (bench / "traffic" / "rmnp.b4s4096.json").write_text(json.dumps(
        dict(json.loads((bench / "traffic" / "rmnp.b8s1024.json")
                        .read_text()), batch=4, seq=4096)))
    (bench / "workloads" / "gpt2-large.rmnp.b4s4096.json").write_text(
        json.dumps(dict(cell, traffic="rmnp.b4s4096")))
    monkeypatch.setattr(harness, "BENCH", bench)
    assert "gpt2-large.rmnp.b4s4096" in harness.cell_names()
    got = harness.load_cell("gpt2-large.rmnp.b4s4096")
    assert got["traffic_spec"]["seq"] == 4096
    assert got["config_spec"]["model"]["d_model"] == 1280
    with pytest.raises(harness.BenchError, match="no-such-cell"):
        harness.load_cell("no-such-cell")


def test_benchmark_json_matches_the_files():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert set(harness.cell_names()) == {w["name"] for w in
                                         SPEC["workloads"]}
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        c = configs[w["config"]]
        assert c["file"] == f"bench/configs/{w['config']}.json"
        assert c["reduced"] == cell["config_spec"]["reduced"]
        assert c["source"] == cell["config_spec"]["source"]
        # a number the control and the faults leave unseparated is read,
        # not compared; the momentum gap separates the control everywhere
        assert "moment_gap" in cell["limits"]
        assert set(cell["limits"]) <= {"loss_rel", "moment_gap",
                                       "change_gap"}
    for m in SPEC["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert m["moves"] == "tokens_per_s"
        assert set(m["workloads"]) <= set(harness.cell_names())
    for w in SPEC["workloads"]:
        # every cell reports at least one per-layer metric
        assert harness.per_layer_metrics(w["name"])


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gpt2-large.rmnp.b8s1024", "--seed", "2147483701",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def _no_result(proc):
    return proc.returncode != 0 and not any(
        line.startswith("{") for line in proc.stdout.splitlines())


def test_run_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert _no_result(proc), proc.stdout
    assert "needs 1 TPU chip" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert _no_result(proc), proc.stdout
    assert "no program to measure" in proc.stderr
