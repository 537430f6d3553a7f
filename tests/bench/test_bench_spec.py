"""BENCHMARK.json keeps to the benchmark's contract, and every file it
names is there; a configuration, cut or whole, a mix or a metric is added
as files and entries alone."""

import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness, traffic

ROOT = harness.ROOT
SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CONTRACT = ("logit_gaps", "logits", "weight_pairs", "serve_flops", "cache_bytes")
# Widths are never cut: hidden, intermediate, head, latent and state sizes,
# expansion factors, the router's width and the experts a token is routed to.
WIDTH = re.compile(r"(_dim|_rank|(?<!vocab)_size)$|^(d_model|d_ff|d_ff_expert|expand|num_experts"
                   r"|top_k|num_experts_per_tok)$")
# The router's width and experts per token, by the program's key in ``moe``
# and the published config's names for them: both stay as published.
ROUTER = {"num_experts": ("num_experts", "n_routed_experts", "num_local_experts"),
          "top_k": ("num_experts_per_tok",)}
HELD = "moe.experts_held"  # the experts of each layer that this chip holds, in a chip-share cut
MIN_EXPERTS = 8  # a share holds at least 8 routed experts in each layer that has them
MIN_LAYERS = 4  # a cut in depth keeps at least 4 layers


def resolve(model: dict, key: str):
    """The value a ``reduced`` key names: a dotted path into the
    configuration's ``model`` dict."""
    node = model
    for part in key.split("."):
        assert isinstance(node, dict) and part in node, f"reduced key {key!r} names nothing"
        node = node[part]
    return node


def check_cut(entry: dict, config: dict):
    """A configuration's cut: the same list in BENCHMARK.json and in its
    file, each key a path into ``model`` that resolves and none a width, a
    deployment stated for a cut, the router as published, and a chip's
    share of the experts written as its own count (``HELD``), at least
    MIN_EXPERTS a layer."""
    model = config["model"]
    assert entry["reduced"] == config["reduced"], "reduced differs from the configuration file"
    for key in config["reduced"]:
        assert isinstance(key, str) and NAME.match(key)
        assert not WIDTH.search(key.rsplit(".", 1)[-1]), f"{key!r} is a width"
        resolve(model, key)
    if config["reduced"]:
        assert config.get("deployment"), "a cut configuration states its deployment"
    if "n_layers" in config["reduced"]:
        assert model["n_layers"] >= MIN_LAYERS, "too few layers kept"
    moe = model.get("moe")
    if not moe:
        return
    published = config.get("published", {})
    for field, names in ROUTER.items():
        for name in names:
            if name in published:
                assert moe[field] == published[name], f"the router's {field} is not as published"
    if "experts_held" in moe:
        assert HELD in config["reduced"], f"{HELD} is a cut and is listed in reduced"
        assert moe["experts_held"] >= MIN_EXPERTS, "too few experts held"
        assert moe["experts_held"] <= moe["num_experts"], "more experts held than the router has"


def check_configs_and_cells(spec: dict, root):
    bench = root / "bench"
    configs = {c["name"]: c for c in spec["configs"]}
    assert len(configs) == len(spec["configs"]) and 1 <= len(configs) <= 24
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"] == f"bench/configs/{c['name']}.json"
        config = harness.load_config(c["name"], root=bench / "configs")
        assert config["model"]["name"] == c["name"]
        check_cut(c, config)
        ref = harness.load_reference(config["reference"], root=bench / "reference")
        assert all(callable(getattr(ref, f, None)) for f in CONTRACT)
    used = set()
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic.load_mix(w["traffic"], root=bench / "traffic")
        limits = harness.load_limits(w["name"], root=bench / "limits")
        assert limits["checks"] and limits["sample_tokens"] > 0
        for c in limits["checks"].values():
            assert c["lower"] < c["limit"] < c["upper"] and 3 * c["lower"] <= c["upper"]
    assert used == set(configs)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(spec["workloads"]) // 2)


def check_metrics(spec: dict, root):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        harness.load_reader(m["name"], root=root / "bench" / "metrics")
        for cell in m.get("workloads", [w["name"] for w in spec["workloads"]]):
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for w in spec["workloads"]:
        got = [m["name"] for m in harness.metrics_for(spec, w["name"], "end_to_end")]
        assert "setup_s" in got and len(got) >= 2
        assert harness.metrics_for(spec, w["name"], "per_layer")


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
    check_configs_and_cells(SPEC, ROOT)


def test_metrics():
    check_metrics(SPEC, ROOT)


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


def _env():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def _run(cwd):
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "ds7b-chat", "--seed",
                           "2147483700", "--seconds", "1", "--trace", "0"], cwd=cwd, env=_env(),
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


CUT_CELL = "olmoe-stage-chat"
CUT_REFERENCE = '''"""The reference of one pipeline stage of OLMoE: decoder's layer
equations over the layers the configuration holds."""

from pathlib import Path

from bench import harness

_decoder = harness.load_reference("decoder", root=Path(__file__).parent)
logit_gaps, logits, weight_pairs = _decoder.logit_gaps, _decoder.logits, _decoder.weight_pairs
serve_flops, cache_bytes = _decoder.serve_flops, _decoder.cache_bytes
'''
# Runs the cut cell through the copy's own harness at the program's reduced
# size on the CPU (the look for a chip skipped), as test_bench_serve does.
RUN_CUT_CELL = f'''
import sys, time
from pathlib import Path
root, src = sys.argv[1:3]
sys.path[:0] = [root, src]
import jax
from bench import harness, serve
assert Path(harness.__file__).resolve().is_relative_to(Path(root).resolve())
spec = harness.load_spec()
cell = harness.find_cell(spec, "{CUT_CELL}")
config = harness.load_config(cell["config"])
r = harness.Readings(config["model"], {{}}, 1, None, 1.0, [], None, [], config=config)
assert Path(r.reference().__file__).name == "stagemoe.py"
serve.run(cell=cell, spec=spec, seed=2**31 + 11, seconds=0.0, traced=False,
          t_start=time.perf_counter(), devices=jax.devices(),
          cfg=serve.model_config(config["model"]).reduced(), bursts=1)
'''


def add_config(root, name, cell, *, model, reduced, deployment, reference="decoder"):
    """A copy of the benchmark under ``root`` with the configuration
    ``name`` (olmoe-1b-7b with ``model``'s changes), a cell ``cell`` on the
    chat mix and its limits, added as new files and new entries."""
    if not (root / "BENCHMARK.json").exists():
        shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
        for p in SPEC["paths"]:
            shutil.copytree(ROOT / p, root / p, ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "bench"
    config = harness.load_config("olmoe-1b-7b")
    config.update(model=dict(config["model"], name=name, **model), reference=reference,
                  reduced=reduced, deployment=deployment)
    (bench / "configs" / f"{name}.json").write_text(json.dumps(config, indent=2))
    shutil.copy(bench / "limits" / "olmoe-code.json", bench / "limits" / f"{cell}.json")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": name, "source": "OLMoE, arXiv:2409.02060",
                            "file": f"bench/configs/{name}.json", "reduced": reduced,
                            "why": deployment})
    spec["workloads"].append({"name": cell, "config": name, "traffic": "chat",
                              "chips": 1, "why": "chat turns on a chip's part of the model"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return spec


def with_a_stage(root):
    """A cut in depth: OLMoE's 16 layers as two pipeline stages of 8, with
    a reference module of its own."""
    spec = add_config(root, "olmoe-stage", CUT_CELL, model={"n_layers": 8}, reduced=["n_layers"],
                      deployment="bf16, 16 layers as two pipeline stages of 8 on two chips: "
                                 "this chip holds one stage", reference="stagemoe")
    (root / "bench" / "reference" / "stagemoe.py").write_text(CUT_REFERENCE)
    return spec


def with_an_expert_share(root):
    """A chip's share of the experts: the router keeps its 64 outputs and
    top-8, and this chip holds 16 experts of each layer (``HELD``). Checked
    by the spec checks only: the program's MoE config has no held count
    until an expert layer that computes a share is added with the
    configuration that needs it."""
    moe = dict(harness.load_config("olmoe-1b-7b")["model"]["moe"], experts_held=16)
    return add_config(root, "olmoe-share", "olmoe-share-chat", model={"moe": moe},
                      reduced=[HELD], deployment="bf16, each layer's 64 experts shared by four "
                                                 "chips: this chip holds 16 and routes over 64")


def test_a_cut_configuration_with_its_own_reference_is_added_as_files_alone(tmp_path):
    spec = with_a_stage(tmp_path)
    check_configs_and_cells(spec, tmp_path)
    check_metrics(spec, tmp_path)
    p = subprocess.run([sys.executable, "-c", RUN_CUT_CELL, str(tmp_path), str(ROOT / "src")],
                       cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0, line["checks"]
    assert line["checks"]["tokens_compared"]["value"] >= line["checks"]["tokens_compared"]["limit"]


def test_a_chips_share_of_the_experts_passes_the_spec_checks(tmp_path):
    spec = with_an_expert_share(tmp_path)
    check_configs_and_cells(spec, tmp_path)
    check_metrics(spec, tmp_path)


def _unresolved(spec, config):
    config["reduced"] = spec["configs"][-1]["reduced"] = ["n_layer"]


def _differs(spec, config):
    spec["configs"][-1]["reduced"] = []


def _no_deployment(spec, config):
    del config["deployment"]


def _a_width(spec, config):
    config["reduced"] = spec["configs"][-1]["reduced"] = ["n_layers", "moe.d_ff_expert"]


def _a_published_key(spec, config):
    config["reduced"] = spec["configs"][-1]["reduced"] = ["num_hidden_layers"]


def _too_few_layers(spec, config):
    config["model"]["n_layers"] = MIN_LAYERS - 1


def _too_few_experts(spec, config):
    config["model"]["moe"]["experts_held"] = MIN_EXPERTS - 1


def _more_experts_than_routed(spec, config):
    config["model"]["moe"]["experts_held"] = 65


def _a_narrower_router(spec, config):
    config["model"]["moe"]["num_experts"] = 16


def _fewer_experts_a_token(spec, config):
    config["model"]["moe"]["top_k"] = 2


def _a_share_not_listed(spec, config):
    config["reduced"] = spec["configs"][-1]["reduced"] = ["n_layers"]


@pytest.mark.parametrize("cut,fault,why", [
    (with_a_stage, _unresolved, "names nothing"), (with_a_stage, _differs, "differs"),
    (with_a_stage, _no_deployment, "deployment"), (with_a_stage, _a_width, "is a width"),
    (with_a_stage, _a_published_key, "names nothing"),
    (with_a_stage, _too_few_layers, "too few layers"),
    (with_an_expert_share, _too_few_experts, "too few experts"),
    (with_an_expert_share, _more_experts_than_routed, "more experts held"),
    (with_an_expert_share, _a_narrower_router, "num_experts is not as published"),
    (with_an_expert_share, _fewer_experts_a_token, "top_k is not as published"),
    (with_an_expert_share, _a_share_not_listed, "listed in reduced")],
    ids=["unresolved", "differs", "no-deployment", "a-width", "a-published-key",
         "too-few-layers", "too-few-experts", "more-than-routed", "narrower-router",
         "fewer-a-token", "share-not-listed"])
def test_the_spec_checks_refuse_a_bad_cut(tmp_path, cut, fault, why):
    spec = cut(tmp_path)
    path = tmp_path / spec["configs"][-1]["file"]
    config = json.loads(path.read_text())
    fault(spec, config)
    path.write_text(json.dumps(config))
    with pytest.raises(AssertionError, match=why):
        check_configs_and_cells(spec, tmp_path)
