"""The command's refusals, and the shape of ``BENCHMARK.json`` against the
files it names."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny

RUN = os.path.join(tiny.CHIP, "run.py")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    cell = _bench()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", cell, "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(tiny.REPO, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_refuses_in_interpret_mode():
    r = _run(tiny.REPO, {"JAX_PLATFORMS": "cpu", "REPRO_PALLAS_INTERPRET": "1"})
    assert r.returncode != 0 and r.stdout == ""


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.CHIP, os.path.join(tmp_path, "benchmarks", "chip"),
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    r = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0 and r.stdout == ""


def test_unknown_device_kind_is_an_error():
    from peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v4")


def test_benchmark_json_names_files_that_exist():
    b = _bench()
    assert b["command"] == ["python3", "benchmarks/chip/run.py"]
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"] and body["name"] == c["name"]
        assert os.path.isfile(os.path.join(tiny.CHIP, "references",
                                           body["reference"] + ".py"))
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert os.path.isfile(os.path.join(tiny.CHIP, "traffic",
                                           w["traffic"] + ".json"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", [])) <= cells
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(tiny.CHIP, "metrics",
                                           m["name"] + ".py"))
