"""BENCHMARK.json resolves to the benchmark's files, keeps to its format,
and the command refuses to run without a TPU."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import counts, harness

from .conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = harness.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in BM["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("conf", BM["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    data = harness.load_json(os.path.join(ROOT, conf["file"]))
    assert conf["file"].startswith("bench/configs/")
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    assert counts.body_params(data["as_run"]) == data["body_parameters"]
    assert any(w["config"] == conf["name"] for w in BM["workloads"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve(BM, name)
    assert cell["chips"] == 1
    kinds = {"queue", "inproc", "finetune"}
    assert cell["traffic_file"]["kind"] in kinds
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        # every per-layer metric has a reader, and the cell reports the
        # end-to-end metric it moves
        assert callable(harness.metric_reader(m["name"]))
        assert m["moves"] in e2e
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_to_run_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_null_limit_is_not_compared_and_a_missing_one_is_an_error():
    from bench.drivers import checks_from

    checks = checks_from({"a": 0.5, "b": None}, {"a": 0.7, "b": 9.0})
    assert [(c.name, c.ok) for c in checks] == [("a", False)]
    with pytest.raises(KeyError):
        checks_from({"a": 0.5}, {"a": 0.1, "b": 0.1})


@pytest.mark.parametrize("name", CELLS)
def test_every_reading_of_a_cell_has_a_limit(name):
    limits = harness.resolve(BM, name)["traffic_file"]["limits"]
    assert [k for k, v in limits.items() if v is not None]
    for v in limits.values():
        assert v is None or v >= 0
