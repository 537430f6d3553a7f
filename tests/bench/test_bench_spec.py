"""BENCHMARK.json keeps to the benchmark's contract, and every file it
names is there; a configuration, a mix or a metric is added as a file."""

import json
import re
import subprocess
import sys

import pytest

from bench import harness, traffic

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16 and all((ROOT / p).is_dir() for p in SPEC["paths"])
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(not w.startswith("/") and ".." not in w for w in SPEC["command"])
    assert (ROOT / SPEC["command"][1]).is_file()
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_configs_and_cells():
    configs = {c["name"]: c for c in SPEC["configs"]}
    assert len(configs) == len(SPEC["configs"]) and 1 <= len(configs) <= 24
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert (ROOT / c["file"]).is_file() and c["reduced"] == []
        assert harness.load_config(c["name"])["model"]["name"] == c["name"]
    used = set()
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic.load_mix(w["traffic"])
        limits = harness.load_limits(w["name"])
        assert limits["checks"] and limits["sample_tokens"] > 0
        for c in limits["checks"].values():
            assert c["lower"] < c["limit"] < c["upper"] and 3 * c["lower"] <= c["upper"]
    assert used == set(configs)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        harness.load_reader(m["name"])
        for cell in m.get("workloads", [w["name"] for w in SPEC["workloads"]]):
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for w in SPEC["workloads"]:
        got = [m["name"] for m in harness.metrics_for(SPEC, w["name"], "end_to_end")]
        assert "setup_s" in got and len(got) >= 2
        assert harness.metrics_for(SPEC, w["name"], "per_layer")


def test_shares_are_named_for_what_they_are():
    for m in SPEC["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_metric_and_a_config_are_added_as_files(tmp_path):
    (tmp_path / "queue_wait_ms.py").write_text(
        "def read(r):\n    return None if not r.spans else 1.5\n")
    read = harness.load_reader("queue_wait_ms", root=tmp_path)
    assert read(harness.Readings({}, {}, 1, {}, 1.0, [1], None, [])) == 1.5
    assert read(harness.Readings({}, {}, 1, {}, 1.0, [], None, [])) is None
    (tmp_path / "new-model.json").write_text(json.dumps({"model": {"name": "new-model"}}))
    assert harness.load_config("new-model", root=tmp_path)["model"]["name"] == "new-model"
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric", root=tmp_path)


def test_percentile_is_numpys_linear():
    import numpy as np

    xs = [5.0, 1.0, 9.0, 3.0, 7.5, 2.25, 11.0]
    for q in (50, 90, 95):
        assert harness.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def _run(cwd, env_extra=None):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "ds7b-chat", "--seed",
                           "2147483700", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0 and "needs 1 TPU chip" in p.stderr
    assert '"correct"' not in p.stdout


def test_the_benchmark_alone_does_not_run(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and '"correct"' not in p.stdout
