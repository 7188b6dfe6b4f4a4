"""The harness finds every part of a cell by name, keeps to the contract's
shape, and refuses to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import jax  # noqa: F401  (imported before run.main sets its cache path)
import pytest

from chipbench import run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    return spec.benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      json.load(open(os.path.join(
                                          spec.ROOT, "BENCHMARK.json")))
                                      ["workloads"]])
def test_every_cell_finds_its_parts_by_name(workload):
    c = spec.cell(workload)
    assert c.traffic["kind"] == "solve"
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.reader(m["name"]))
    for part in (c.config, c.traffic, c.limits):
        assert isinstance(part, dict) and part
    assert c.chips in (1, 4)


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for x in b["configs"] + b["workloads"]
             + b["end_to_end"] + b["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in b["configs"]:
        path = os.path.join(spec.ROOT, c["file"])
        assert path.startswith(os.path.join(spec.ROOT, "chipbench"))
        assert json.load(open(path))["name"] == c["name"]
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    bad = [m["name"] for m in b["end_to_end"] if m["bound"] > 0.25]
    assert not bad


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.cell("no.such.cell")


def test_refuses_a_cpu(capsys, monkeypatch):
    # the sandbox's JAX holds the CPU: no result line, a non-zero exit
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    saved = list(sys.path)
    try:
        code = run.main(["--workload", "ba-r4-75k.solve", "--seed", "3",
                         "--seconds", "1"])
    finally:
        sys.path[:] = saved
    out = capsys.readouterr()
    assert code != 0
    assert "needs a TPU" in out.err
    assert out.out == ""


def test_refuses_a_checkout_without_the_program(tmp_path):
    # only BENCHMARK.json and the benchmark's own files
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "ba-r4-75k.solve",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
