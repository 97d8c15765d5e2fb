"""``BENCHMARK.json`` and the files it names, and the refusal to run
without a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run as R
from bench.tests.tiny import REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_names_files_that_exist():
    bench = R.Bench(REPO)
    for w in SPEC["workloads"]:
        cell = R.Cell(bench, w["name"])           # loads every file by name
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert cell.cfg["name"] == w["config"]
        assert w["why"] == cell.spec["why"]
        assert cell.cfg["widths"][0] == cell.cfg["graph"]["features"]
        for m in bench.per_layer(w["name"]):
            assert hasattr(bench.reader(m["name"]), "read")
        names = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert bench.per_layer(w["name"])


def test_benchmark_file_keeps_to_its_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])
    lines = [x["why"] for k in ("configs", "workloads") for x in SPEC[k]]
    lines += list(layers) + [c["source"] for c in SPEC["configs"]]
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
               for s in lines)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert len(json.dumps(SPEC)) < 64 * 1024


def _run_cli(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _last_json(stdout: str):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        return json.loads(lines[-1])
    except ValueError:
        return None


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout", "benchmark-files-alone"])
def test_without_a_chip_the_run_exits_nonzero_with_no_result(tmp_path,
                                                             alone):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cwd = REPO
    if alone:
        cwd = tmp_path / "alone"
        shutil.copytree(REPO / "bench", cwd / "bench",
                        ignore=shutil.ignore_patterns("out", "__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", cwd / "BENCHMARK.json")
        env.pop("PYTHONPATH", None)
    got = _run_cli(cwd, env)
    assert got.returncode != 0
    assert _last_json(got.stdout) is None
