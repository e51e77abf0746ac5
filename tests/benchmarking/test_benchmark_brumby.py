"""What PR 50 added to the benchmark: the configuration's file against
the catalog row and against the model's own shapes, the work module at a
hand-reckoned step, the reader files and the manifest's entries, and the
harness's whole order on a toy cell under every per-layer entry that
``BENCHMARK.json`` gives the new cell. Entries are held by name, by
membership and by their order among themselves, never by position or by
count: what a later PR appends stands beside them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import brumby
# Counters of a made-up capture, as that file's own tests make them.
from test_benchmark_cohere_moe import _Ctx

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CONFIG = "brumby-14b"
CELL = "brumby-14b.long-document"
TINY = "tiny-brumby.tiny-decode"
ARCH = "BrumbyForCausalLM"
CATALOG = "brumby-14b-base.catalog.json"
NEW_METRICS = ("kernel.power_retention_share",
               "kernel.power_retention_roofline",
               "kernel.retention_chunk_tokens_per_step")
REDUCED = ["num_hidden_layers"]


def config_file():
    return run.load_json(run.HERE, "configs", f"{CONFIG}.json")


def catalog_row() -> dict:
    """The catalog's row (the model-configs guide), where this sandbox
    has the guide; the copy kept with the tests elsewhere."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "Brumby-14B-Base")[
            "config"]
    return run.load_json(DATA, CATALOG)["config"]


def test_the_kept_copy_of_the_catalog_row_is_the_catalog_s():
    kept = run.load_json(DATA, CATALOG)
    assert kept["config"] == catalog_row()
    assert kept["source_url"] == config_file()["source"]


def test_catalog_numbers_are_kept_key_for_key_and_the_depth_alone_reduced():
    """The file holds every key of the catalog row's config with the
    row's value, except the depth, which ``reduced`` names and nothing
    else; every width is the row's; the manifest's entry says the same;
    the cell is the issue's."""
    cf, row = config_file(), catalog_row()
    differs = {k for k, v in row.items() if cf.get(k, "absent") != v}
    assert differs == set(REDUCED) and cf["reduced"] == REDUCED
    assert cf["num_hidden_layers"] == 6
    assert cf["published"]["num_hidden_layers"] == \
        row["num_hidden_layers"] == 40
    assert (cf["hidden_size"], cf["num_attention_heads"],
            cf["num_key_value_heads"], cf["head_dim"],
            cf["intermediate_size"], cf["vocab_size"],
            cf["tie_word_embeddings"], cf["rope_theta"],
            cf["rms_norm_eps"], cf["attention_bias"], cf["model_type"],
            cf["max_window_layers"], cf["use_sliding_window"]) == (
                5120, 40, 8, 128, 17408, 151936, False, 1000000, 1e-6,
                False, "brumby", 40, False)
    assert cf["architectures"] == [ARCH]
    assert cf["reference"] == "brumby"
    ea = cf["engine_args"]
    assert {k: v for k, v in ea.items() if k != "max_num_seqs"} == {
        "dtype": "bfloat16", "block_size": 16, "max_model_len": 32768,
        "max_num_batched_tokens": 2048}
    assert ea["max_model_len"] == cf["max_position_embeddings"]
    # 16, or fewer in steps of 2 where the chip's profile said so.
    assert ea["max_num_seqs"] in (16, 14, 12, 10, 8)
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200 and len(cf["assumed"]) >= 9
    listed = " ".join(cf["assumed"])
    for point in ("architectures", "degree", "K/V HEAD", "logsigmoid",
                  "per-head RMS norm", "half-rotation", "no epsilon",
                  "float32", "max_window_layers", "switch over",
                  "torch_dtype", "tokenizer", "log-uniform"):
        assert point in listed, point
    for point in ("quadratic form", "NO state", "no_gate", "degree_4",
                  "no_normalizer", "no_rope", "no_qk_norm",
                  "gate_per_query_head", "Departures"):
        assert point in brumby.__doc__, point
    for point in ("four stages of ten", "ten layers to six", "BOTH ends",
                  "idle share"):
        assert point in cf["deployment"], point
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == REDUCED
    assert entry[0]["source"] == cf["source"] == \
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/" \
        "config.json"
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    cells = [w for w in cells if w["name"] == CELL]
    assert len(cells) == 1
    assert cells[0]["traffic"] == "long-document" and cells[0]["chips"] == 1
    cell = run.load_cell(CELL)
    assert cell["load"] == {"clients": ea["max_num_seqs"]}
    assert cell["why"] == cells[0]["why"] and len(cell["why"]) <= 200
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["shared_prefix"] is None
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.4, "min": 6144, "max": 24576}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= \
        ea["max_model_len"]


def test_the_file_s_arithmetic_is_the_model_s():
    """3 538 M parameters = 7.08 GB, 330.4 M a layer, a state row of
    206.2 MB whatever the sequence's length and no page, as the file's
    ``deployment`` reckons, from the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from benchmarks.rooflines import power_retention_state
    from vllm_distributed_tpu.models.brumby import BrumbyForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    cf = config_file()
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    BrumbyForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    seqs = cf["engine_args"]["max_num_seqs"]
    arch.state_slots = seqs
    model = BrumbyForCausalLM(arch)
    assert (arch.qk_norm, arch.rope_theta, arch.rope_scaling,
            arch.sliding_window, arch.window_pattern, arch.hidden_act) == (
                True, 1000000, None, None, None, "silu")
    assert (arch.num_q_heads, arch.num_kv_heads, arch.head_dim,
            arch.intermediate_size, arch.tie_word_embeddings) == (
                40, 8, 128, 17408, False)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6) == 3538
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(7.08, abs=0.01)
    lay = shapes["layers"]
    per_layer = sum(x.size for x in jax.tree.leaves(lay)) / 6e6
    assert round(per_layer, 1) == 330.4
    assert round((40 * per_layer + 2 * 151936 * 5120 / 1e6) / 1e3, 2) \
        == 14.77
    assert lay["wq"].shape == (6, 5120, 5120) == lay["wo"].shape
    assert lay["wk"].shape == lay["wv"].shape == (6, 5120, 1024)
    assert lay["wg"].shape == (6, 5120, 8) and lay["bg"].shape == (6, 8)
    assert lay["bg"].dtype == jnp.float32
    assert lay["q_norm"].shape == lay["k_norm"].shape == (6, 128)
    assert lay["gate"].shape == lay["up"].shape == (6, 5120, 17408)
    assert shapes["embed"].shape == (151936, 5120)
    assert shapes["lm_head"].shape == (5120, 151936)
    caches = jax.eval_shape(lambda: model.make_kv_caches(32, 16))
    assert caches["ret"].shape == (6, seqs + 1, 8, 65, 128, 128)
    assert caches["ret_z"].shape == (6, seqs + 1, 8, 72, 128)
    assert {c.dtype for c in caches.values()} == {jnp.dtype("float32")}
    assert model.kv_cache_page_bytes(16) == 0
    row = 6 * 8 * (65 * 128 * 128 + 72 * 128) * 4
    assert model.state_row_bytes() == row == 206_241_792
    assert model.fixed_cache_bytes() == (seqs + 1) * row
    # The symmetric square's count, which the work module charges: 0.8%
    # and the normaliser's padded rows under the program's.
    assert 6 * power_retention_state.state_bytes(cf) == \
        6 * 8 * 8256 * 129 * 4 == 204_484_608
    for text in ("330.4 M", "1 555.8 M", "14.77 B", "29.5 GB", "3 538 M",
                 "7.08 GB", "206.2 MB", "204.5 MB", "1.37 GB", "8 320"):
        assert text in cf["deployment"], text


def test_the_work_module_at_a_hand_reckoned_step():
    """A decode step of 16 tokens: 6.5 GB of state read and written
    (bytes-bound: 8.0 ms at 819 GB/s against 0.05 ms of operations); a
    chunk step of 15 + 2 033: operations-bound; always at the 8 256 rows
    of the symmetric square; nothing for another configuration."""
    from benchmarks.rooflines import power_retention_state as module
    cf = config_file()
    assert module.symmetric_rows(cf) == 8256
    assert module.state_bytes(cf) == 8 * 8256 * 129 * 4 == 34_080_768
    rows = ((2 * 40 + 2 * 8) * 128 + 8) * 4
    entries = 8256 * 129
    decode = {("vdt:step_tokens", "decode"): 16.0,
              ("vdt:step_tokens", "prefill"): 0.0}
    got = module.work(cf, _Ctx(decode))
    assert got["bytes"] == 6 * (2 * 16 * 34_080_768 + 16 * rows)
    assert got["bytes"] / 1e9 == pytest.approx(6.55, abs=0.01)
    assert got["flops"] == 16 * 6 * (2 * 40 + 3 * 8) * entries
    assert got["flops"] / 16 / 6 / 1e6 == pytest.approx(110.8, abs=0.1)
    assert got["bytes"] / 819e9 == pytest.approx(7.99e-3, rel=0.01)
    assert got["bytes"] / 819e9 > 100 * got["flops"] / 197e12
    chunk = {("vdt:step_tokens", "decode"): 15.0,
             ("vdt:step_tokens", "prefill"): 2033.0}
    big = module.work(cf, _Ctx(chunk))
    assert big["flops"] == 2048 * 6 * 104 * entries
    assert big["bytes"] == 6 * (2 * 15 * 34_080_768 + 2048 * rows)
    assert big["flops"] / 197e12 < big["bytes"] / 819e9 < \
        1.2 * big["flops"] / 197e12
    assert module.work(cf, _Ctx({})) is None
    for name in os.listdir(os.path.join(run.HERE, "configs")):
        other = run.load_json(run.HERE, "configs", name)
        if other["architectures"] != [ARCH]:
            assert module.work(other, _Ctx(decode)) is None, name
    for point in ("LOW", "INSIDE a chunk", "passes", "8 320"):
        assert point in module.work.__doc__ + module.symmetric_rows.__doc__, \
            point


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the entries list
    the new cell, move what it reports and keep their order among
    themselves (wherever in the list they stand)."""
    from vllm_distributed_tpu.ops import power_retention
    spec = layers.load_spec(name)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    src = spec["source"]
    assert src["kind"] in layers.READERS
    manifest = run.load_json(run.MANIFEST)
    order = [m["name"] for m in manifest["per_layer"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    entry = entries[name]
    assert CELL in entry["workloads"]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["layer"] == "kernels" and entry["better"] == "higher"
    if src["kind"] == "trace":
        assert entry["moves"] == "tpot_p50_ms"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert src["line"] == "XLA Ops"
        assert src["match"] == "power_retention_"
        for kernel in (power_retention.KERNEL_RECURRENT,
                       power_retention.KERNEL_CHUNK):
            assert kernel.startswith(src["match"])
        if name.endswith("_roofline"):
            assert src["reduce"] == "roofline_share"
            assert src["work"] == "power_retention_state"
            assert os.path.exists(os.path.join(
                run.HERE, "rooflines", f"{src['work']}.py"))
        else:
            assert src["reduce"] == "share_of_busy" and "work" not in src
    else:
        assert entry["moves"] == "itl_p95_ms"
        assert entry["source"] == "program_counter"
        assert entry["unit"] == "tokens"
        from vllm_distributed_tpu.metrics import prometheus
        assert src["metric"] == "vdt:step_retention_chunk_tokens"
        assert src["metric"] in open(prometheus.__file__).read()
        assert src["reduce"] == "hist_mean"


def test_lists_that_take_the_cell_and_lists_that_do_not():
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    takes = {n for n, m in entries.items() if CELL in m["workloads"]}
    moved = {"tpot_p50_ms", "setup_s"} | {
        n for n, m in e2e.items() if CELL in m.get("workloads", [])}
    # `output_tokens_per_s` is owed (an accepted test closes its list:
    # PERF.md, section 7, From PR 47 (a)) and may join them.
    assert {"tpot_p50_ms", "setup_s", "itl_p95_ms"} <= moved
    assert {"device.idle_share", "model.step_device_ms_p50",
            "engine.step_period_mean_ms", "engine.host_gap_mean_ms",
            "runner.launch_mean_ms", "runner.host_transfers_per_step",
            "runner.mixed_device_wait_p50_ms", "sched.running_mean",
            "frontend.process_outputs_mean_ms", "frontend.step_cpu_mean_ms",
            *NEW_METRICS} <= takes
    for name in takes:
        assert entries[name]["moves"] in moved, name
    # No page, no attention kernel, no expert, no other state kernel:
    # what reads those can find nothing here. (`kv.state_bytes_held`
    # would read true and is owed: PERF.md, section 7.)
    for absent in ("kernel.attn_share", "kernel.attn_roofline",
                   "kernel.kv_tokens_read_per_step", "kernel.moe_gemm_share",
                   "kv.preemptions", "kernel.kda_share", "kernel.ssd_share",
                   "kernel.ssd_chunk_tokens_per_step",
                   "kv.window_pages_held"):
        assert absent not in takes, absent
    # Appended: the cell stands behind the cell of the PR before it,
    # wherever a later PR's cells then stand (never by position).
    for m in (*manifest["per_layer"], *manifest["end_to_end"]):
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            assert m["workloads"].index(CELL) > m["workloads"].index(
                "smallthinker-21b-a3b.mixed-length"), m["name"]


def tiny_manifest(tmp_path) -> str:
    """A copy of BENCHMARK.json with the toy cell in the new cell's
    place: the same end-to-end and per-layer entries, whatever the
    manifest lists for the new cell."""
    m = run.load_json(run.MANIFEST)
    config, traffic_name = TINY.split(".")
    m["configs"] = [{"name": config, "source": "test only", "reduced": [],
                     "file": f"tests/benchmarking/data/configs/{config}.json",
                     "why": "test"}]
    m["workloads"] = [{"name": TINY, "config": config, "chips": 8,
                       "traffic": traffic_name, "why": "test"}]
    for kind in ("end_to_end", "per_layer"):
        kept = []
        for x in m[kind]:
            if "workloads" in x:
                if CELL not in x["workloads"]:
                    continue
                x["workloads"] = [TINY]
            kept.append(x)
        m[kind] = kept
    path = tmp_path / "manifest-brumby.json"
    path.write_text(json.dumps(m))
    return str(path)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
from benchmarks.references import brumby
# Every control, those the chip's prompts catch and those held in
# float32.
brumby.CONTROLS[{arch!r}] = {controls!r}
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 50, 4.0, True, data_root={data!r},
                    manifest_path={manifest!r}, require_platform="cpu",
                    ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", {cell!r}, "--seed",
                          str(2**31 + 50)], data_root={data!r})
print(json.dumps(line))
"""


def test_run_cell_serves_the_toy_cell_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the run ends ``correct`` with every control caught
    at three times the toy's tolerance, the new counter is read (prompts
    took the chunked form), the trace metrics find no device plane on a
    CPU and are left out; and ``reference_precision`` on what the run
    kept."""
    every = list(brumby.ALL_CONTROLS)
    assert set(brumby.CONTROLS[ARCH]) <= set(every)
    manifest = tiny_manifest(tmp_path)
    wanted = {x["name"] for x in run.load_json(manifest)["per_layer"]}
    assert set(NEW_METRICS) <= wanted and len(wanted) >= 30
    env = dict(os.environ, JAX_PLATFORMS="cpu", VDT_PALLAS_INTERPRET="1",
               VDT_PRECOMPILE="1", HF_HUB_OFFLINE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, "-c", RUN_SCRIPT.format(
            repo=REPO, work=str(tmp_path), data=DATA, cell=TINY,
            manifest=manifest, arch=ARCH, controls=every)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[len(lines) - 1])
    assert last["correct"] is True, (lines[-3:], last["compared"])
    assert last["attempted"] > 0 and last["failed"] == 0
    got = last["metrics"]
    assert {"sched.running_mean", "runner.host_transfers_per_step",
            "engine.step_period_mean_ms",
            "kernel.retention_chunk_tokens_per_step"} <= set(got), sorted(got)
    assert not {"kernel.power_retention_share",
                "kernel.power_retention_roofline",
                "device.idle_share"} & set(got)
    assert got["runner.host_transfers_per_step"]["value"] == 3.0
    assert got["kernel.retention_chunk_tokens_per_step"]["value"] > 0
    for control in every:
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    prec = json.loads(lines[len(lines) - 2])
    assert prec["float32_highest"]["max"] < 2e-5 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


def test_a_program_without_the_family_refuses_the_file():
    """What makes the parent fail cleanly in the new cell: a named
    architecture that the registry lacks is refused at load
    (``resolve_architecture``), and this one is in it now."""
    from types import SimpleNamespace

    from vllm_distributed_tpu.models.registry import (
        resolve_architecture, supported_architectures)
    assert ARCH in supported_architectures()
    assert resolve_architecture(SimpleNamespace(
        architectures=config_file()["architectures"])).__name__ == ARCH
    with pytest.raises(ValueError, match="BrumbyNextForCausalLM"):
        resolve_architecture(SimpleNamespace(
            architectures=["BrumbyNextForCausalLM"],
            model_type="brumby", num_hidden_layers=6))
