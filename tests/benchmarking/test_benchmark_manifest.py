"""BENCHMARK.json against the contract's limits and the files it names;
the tests' own tiny manifest shows that a cell, a configuration and a mix
are new files and entries only, and that the layer-metric readers serve
cells they have never heard of; a third manifest, built here from a copy
of BENCHMARK.json with one of everything APPENDED, is the standing proof
that an addition edits no file and no entry that is there."""

import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import layers, run, traffic

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
DATA = os.path.join(os.path.dirname(__file__), "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# (manifest, the directory of cells, configurations and mixes, the
# directory the manifest's `file` paths start from); "appended" is built
# by `_append_one_of_everything` in a temporary directory.
MANIFESTS = {
    "BENCHMARK.json": (os.path.join(REPO, "BENCHMARK.json"), run.HERE, REPO),
    "tests-tiny": (os.path.join(DATA, "manifest.json"), DATA, REPO),
    "appended": None}
ADDED = {"config": "added-model", "traffic": "added-mix",
         "cell": "added-model.added-mix", "metric": "added.counter"}


def _append_one_of_everything(tmp: str) -> tuple[str, str, str]:
    """What a later PR does, and all it does: new files beside the old
    ones and entries at the END of the manifest's lists."""
    root = os.path.join(tmp, "benchmarks")
    for d in ("cells", "configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(run.HERE, d), os.path.join(root, d))
    before = {d: sorted(os.listdir(os.path.join(root, d)))
              for d in os.listdir(root)}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)

    def write(kind, name, obj):
        with open(os.path.join(root, kind, f"{name}.json"), "x") as f:
            json.dump(obj, f)

    config = dict(run.load_json(run.HERE, "configs", "olmo2-1b.json"),
                  num_hidden_layers=4, reduced=["num_hidden_layers"],
                  source="test only: a configuration that a later PR adds")
    write("configs", ADDED["config"], config)
    write("traffic", ADDED["traffic"], dict(
        run.load_json(run.HERE, "traffic", "decode-heavy.json"),
        output_len={"dist": "uniform", "min": 16, "max": 32}))
    write("cells", ADDED["cell"], {
        "config": ADDED["config"], "traffic": ADDED["traffic"], "chips": 1,
        "load": {"clients": 4}, "why": "test"})
    write("layer_metrics", ADDED["metric"], {
        "source": {"kind": "prometheus", "metric": "vdt:added_total",
                   "reduce": "delta"}, "what": "test"})
    m["configs"].append({
        "name": ADDED["config"], "source": config["source"],
        "file": f"benchmarks/configs/{ADDED['config']}.json",
        "reduced": config["reduced"], "why": "test"})
    m["workloads"].append({
        "name": ADDED["cell"], "config": ADDED["config"],
        "traffic": ADDED["traffic"], "chips": 1, "why": "test"})
    m["per_layer"].append({
        "name": ADDED["metric"], "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "added layer",
        "moves": "tpot_p50_ms", "workloads": [ADDED["cell"]]})
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    # Nothing that was there was touched: one new file in each directory.
    for d, names in before.items():
        now = sorted(os.listdir(os.path.join(root, d)))
        assert len(now) == len(names) + 1 and set(names) < set(now)
    return path, root, tmp


@pytest.fixture(params=list(MANIFESTS))
def manifest(request, tmp_path):
    path, root, files_from = MANIFESTS[request.param] or \
        _append_one_of_everything(str(tmp_path))
    with open(path) as f:
        return json.load(f), root, files_from


def test_keys_names_units_and_limits(manifest):
    m, root, _ = manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert m["paths"] == ["benchmarks", "tests/benchmarking"]
    assert len(json.dumps(m)) < 64 * 1024
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        ns = [x["name"] for x in m[kind]]
        assert len(ns) == len(set(ns))
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        # BENCHMARK.json names the cells of every per-layer metric, so a
        # new cell inherits none (the tests' tiny manifest keeps the
        # entries without a list that the contract also allows).
        assert "workloads" in x or root == DATA, x["name"]
    assert "setup_s" in [x["name"] for x in m["end_to_end"]]
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_named_file_exists_and_parses(manifest):
    m, root, files_from = manifest
    configs = {c["name"]: c for c in m["configs"]}
    used = set()
    for w in m["workloads"]:
        cell = run.load_cell(w["name"], root)      # cell, config and mix
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        assert ("rate_rps" in cell["load"]) == (cell["mix"]["loop"] == "open")
        used.add(w["config"])
        cf = cell["config_file"]
        assert configs[w["config"]]["reduced"] == cf["reduced"]
        assert configs[w["config"]]["source"] == cf["source"]
        for key in ("assumed", "deployment", "engine_args", "reference",
                    "tolerance", "architectures", "vocab_size"):
            assert key in cf, key
        plan = run.build_plan(cell, 2 ** 31 + 1, 2.0, True, 1, "x")
        assert plan["scoring"] and plan["warmup"] and plan["trace"]
        assert traffic.describe(plan)["requests"] > 0
    assert used == set(configs)
    for c in m["configs"]:
        assert os.path.exists(os.path.join(files_from, c["file"]))
    readers = root if os.path.isdir(os.path.join(root, "layer_metrics")) \
        else run.HERE
    for x in m["per_layer"]:
        # A reader file says how to read and nothing else: unit, layer,
        # `moves` and the cells stand once, in the manifest, so a PR that
        # adds a cell edits no file that is there. (The tests' tiny
        # manifest reads the SAME files under benchmarks/layer_metrics/.)
        spec = layers.load_spec(x["name"], readers)
        assert set(spec) == {"source", "what"}, x["name"]
        assert spec["source"]["kind"] in layers.READERS
        if spec["source"].get("reduce") == "roofline_share":
            work = importlib.import_module(
                f"benchmarks.rooflines.{spec['source']['work']}").work
            assert callable(work) and work.__doc__


def test_moves_is_reported_wherever_the_layer_metric_is(manifest):
    m, _, _ = manifest
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x.get("workloads", cells) for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e, x
        for c in x.get("workloads", cells):
            assert c in e2e[x["moves"]], (x["name"], c)
    for c in cells:
        assert len(run.cell_metrics(m, c, "end_to_end")) >= 2
        assert run.cell_metrics(m, c, "per_layer")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    layer_names = {x["layer"] for x in m["per_layer"]}
    assert all("\n" not in n and len(n) <= 200 for n in layer_names)


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """OLMoE's file holds every number of the catalog row's config, but
    for the keys in `reduced` (catalog: the model-configs guide)."""
    catalog = {"attention_bias": False, "clip_qkv": None,
               "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096,
               "model_type": "olmoe", "norm_topk_prob": False,
               "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
               "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    cf = run.load_json(run.HERE, "configs", "olmoe-1b-7b.json")
    differing = [k for k, v in catalog.items() if cf.get(k, "absent") != v]
    assert differing == cf["reduced"] == ["num_hidden_layers"]
    with open(os.path.join(run.HERE, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)


def test_run_refuses_a_platform_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "olmo2-1b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert '"correct"' not in p.stdout
    assert "need 1 tpu device" in p.stderr
