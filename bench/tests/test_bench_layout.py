"""BENCHMARK.json resolves to the benchmark's files, keeps to its format,
and the command refuses to run without a TPU."""
import os
import re
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.drivers import program_config, reference_module

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
    # the count comes from the reference module the file names
    ref = reference_module(data)
    assert ref.body_params(data["as_run"]) == data["body_parameters"]
    assert any(w["config"] == conf["name"] for w in BM["workloads"])


def _leaves(tree):
    return [(jax.tree_util.keystr(p), x.shape, x.dtype)
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _conf(name):
    return harness.load_json(os.path.join(ROOT, "bench", "configs",
                                          name + ".json"))


def test_granite_reference_body_is_the_programs_layout():
    """The reference's seeded body has the program's leaves (``init_lm``
    of the configuration as run): names, shapes and dtypes, at the
    published widths."""
    from repro.models.transformer import init_lm

    conf = _conf("granite-moe-1b-a400m")
    cfg = program_config(conf)
    key = jax.ShapeDtypeStruct((2,), "uint32")
    prog = jax.eval_shape(lambda k: init_lm(cfg, k), key)
    body = jax.eval_shape(
        lambda k: reference_module(conf).init_body(k, conf["as_run"]), key)
    assert _leaves(body) == _leaves(prog)
    assert len(_leaves(body)) == 12
    assert sum(x.size for x in jax.tree.leaves(prog)) == conf["body_parameters"]
    # depth alone is cut: at the published 24 layers the count is the
    # published one
    whole = dict(conf["as_run"], num_layers=24)
    n = reference_module(conf).body_params(whole)
    assert n == conf["body_parameters_published"] == 1_334_628_352


def test_program_config_replaces_a_nested_group_field_by_field():
    conf = _conf("granite-moe-1b-a400m")
    cfg = program_config(conf)
    # a dataclass still, with the fields the file leaves out kept
    assert cfg.moe.num_experts == 32 and cfg.moe.experts_per_token == 8
    assert cfg.moe.routing == "gshard"
    assert cfg.num_layers == conf["as_run"]["num_layers"]
    bad = dict(conf, as_run=dict(conf["as_run"], moe={"num_expert": 32}))
    with pytest.raises(ValueError, match="num_expert"):
        program_config(bad)
    bad = dict(conf, as_run=dict(conf["as_run"], layers=3))
    with pytest.raises(ValueError, match="layers"):
        program_config(bad)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve(BM, name)
    assert cell["chips"] in (1, 4)
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


def test_four_chip_cells_are_within_the_cap():
    four = [w["name"] for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 2), four


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
