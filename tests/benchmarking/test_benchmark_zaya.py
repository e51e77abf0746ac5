"""What PR 41 added to the benchmark: the ZAYA1 reference
(``benchmarks/references/zaya.py``) against the program's engine at a
toy width on the CPU through the harness's own comparison, the whole
order of a run on the toy cell under every per-layer entry that
``BENCHMARK.json`` gives the new cell, the configuration's file against
the catalog row and against the model's own shapes, the work module's
counts and the reader files. Entries are held by name and relative
order, never by position or by count: what a later PR appends stands
beside them."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import zaya
from test_benchmark_bailing import _Ctx
from test_benchmark_deepseek import _serve_scoring

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CONFIG = "zaya1-8b"
CELL = "zaya1-8b.decode-heavy"
TINY = "tiny-zaya.tiny-decode"
NEW_METRICS = ("kernel.moe_top1_gemm_roofline", "kernel.cca_rows_share")
ARCH = "ZayaForCausalLM"


def config_file():
    return run.load_json(run.HERE, "configs", f"{CONFIG}.json")


def test_engine_matches_plain_zaya_reference(tmp_path, monkeypatch):
    """Prefill in chunks of 64, then decode, through the carried rows,
    the pages, the router's state over depth and the dispatch with its
    skipped tokens, against the reference's whole-sequence forward, by
    the comparison that decides `correct`: every control the chip runs
    must catch, and those that the float32 tests alone hold, are caught
    at three times the toy's tolerance."""
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell(TINY, DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, seed=2 ** 31 + 7)
    scoring = _serve_scoring(cell, args, cf, model_dir)
    got = run.compare_with_reference(cell, args, scoring)
    assert got["positions"] == sum(len(s["tokens"]) - 1 for s in scoring)
    assert got["max_abs_logprob_dev"] < 2e-5, got
    assert got["ok"], json.dumps(got["controls"])
    on_chip = zaya.CONTROLS[ARCH]
    assert set(got["controls"]) == set(on_chip) < set(zaya.EVERY_CONTROL)
    extra = [c for c in zaya.EVERY_CONTROL if c not in on_chip]
    monkeypatch.setitem(zaya.CONTROLS, ARCH, extra)
    more = run.compare_with_reference(cell, args, scoring)["controls"]
    assert set(more) == set(extra)
    for name, c in {**got["controls"], **more}.items():
        assert c["caught"] and c["max"] > 100 * got["max_abs_logprob_dev"], \
            (name, c)


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
    path = tmp_path / "manifest-zaya.json"
    path.write_text(json.dumps(m))
    return str(path)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 17, 4.0, True, data_root={data!r},
                    manifest_path={manifest!r}, require_platform="cpu",
                    ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", {cell!r}, "--seed",
                          str(2**31 + 17)], data_root={data!r})
print(json.dumps(line))
"""


def test_run_cell_serves_zaya_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the counters are read, the trace metrics find no
    device plane on a CPU and are left out, every control stands beside
    its limit; and `reference_precision` on what the run kept."""
    manifest = tiny_manifest(tmp_path)
    wanted = {x["name"] for x in run.load_json(manifest)["per_layer"]}
    assert set(NEW_METRICS) <= wanted and len(wanted) >= 30
    env = dict(os.environ, JAX_PLATFORMS="cpu", VDT_PALLAS_INTERPRET="1",
               VDT_PRECOMPILE="1", HF_HUB_OFFLINE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, "-c", RUN_SCRIPT.format(
            repo=REPO, work=str(tmp_path), data=DATA, cell=TINY,
            manifest=manifest)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1])
    assert last["correct"] is True, (lines[-3:], last["compared"])
    assert last["attempted"] > 0 and last["failed"] == 0
    got = set(last["metrics"])
    assert {"kernel.kv_tokens_read_per_step", "sched.running_mean",
            "kv.preemptions", "runner.host_transfers_per_step"} <= got, got
    assert not {*NEW_METRICS, "kernel.moe_gemm_share", "kernel.attn_share",
                "kernel.attn_roofline", "device.idle_share"} & got
    assert last["metrics"]["kv.preemptions"]["value"] == 0
    for control in zaya.CONTROLS[ARCH]:
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    prec = json.loads(lines[-2])
    assert prec["float32_highest"]["max"] < 2e-5 < 0.002 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


def catalog_row() -> dict:
    """The catalog's row (the model-configs guide), where this sandbox
    has the guide; the copy kept with the tests elsewhere."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "ZAYA1-8B")
    return run.load_json(DATA, "zaya1-8b.catalog.json")


def test_the_kept_copy_of_the_catalog_row_is_the_catalog_s():
    kept = run.load_json(DATA, "zaya1-8b.catalog.json")
    row = catalog_row()
    assert kept == {k: row[k] for k in ("name", "source_url", "config")}


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """The file holds every key of the catalog row's config with the
    row's value, except the depth and the layer list cut with it, which
    `reduced` names; every width, all 16 experts and the whole
    vocabulary are the row's; the manifest's entry says the same."""
    cf, row = config_file(), catalog_row()
    differs = {k for k, v in row["config"].items()
               if cf.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "layer_types"} == \
        set(cf["reduced"])
    assert cf["num_hidden_layers"] == 16 and \
        cf["layer_types"] == ["hybrid"] * 16
    assert cf["published"] == {"num_hidden_layers": 40}
    assert (cf["hidden_size"], cf["head_dim"], cf["num_attention_heads"],
            cf["num_key_value_heads"], cf["router_hidden_size"],
            cf["num_experts"], cf["num_experts_per_tok"],
            cf["moe_intermediate_size"], cf["vocab_size"]) == (
                2048, 128, 8, 2, 256, 16, 1, 2048, 262272)
    assert cf["source"] == row["source_url"]
    assert cf["architectures"] == [ARCH]
    assert cf["engine_args"] == {
        "dtype": "bfloat16", "block_size": 16, "max_model_len": 4096,
        "max_num_batched_tokens": 2048, "max_num_seqs": 64}
    assert cf["reference"] == "zaya"
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200 and len(cf["assumed"]) >= 8
    # The departures are listed in the file and in the reference alike.
    listed = " ".join(cf["assumed"])
    for point in ("merge", "bias", "padding", "temperature", "rotation",
                  "GELU", "p x", "torch_dtype", "tokenizer"):
        assert point in listed, point
    for point in ("merge", "biases", "padding", "tau", "rotation", "GELU",
                  "p x", "torch_dtype"):
        assert point in zaya.__doc__, point
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == cf["reduced"]
    assert entry[0]["source"] == cf["source"]
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["traffic"] == "decode-heavy" and cells[0]["chips"] == 1
    clients = run.load_cell(CELL)["load"]["clients"]
    assert clients == cf["engine_args"]["max_num_seqs"] == 64
    assert "64 clients" in cells[0]["why"]
    # Every cell of the benchmark runs on one chip.
    assert {w["chips"] for w in manifest["workloads"]} == {1}


def test_the_file_s_arithmetic_is_the_model_s(monkeypatch):
    """3 858.5 M parameters = 7.717 GB at depth 16, 207.6 M a layer,
    90 112 B of rows a sequence and 16 384 B of K/V a token, as the
    file's `deployment` reckons, from the shapes the program would
    allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    from vllm_distributed_tpu.models.zaya import ZayaForCausalLM
    cf = config_file()
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    ZayaForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    arch.state_slots = cf["engine_args"]["max_num_seqs"]
    model = ZayaForCausalLM(arch)
    assert (arch.rope_theta, arch.rotary_dim, arch.num_experts,
            arch.moe_intermediate_size, arch.router_hidden_size) == (
                5e6, 64, 16, 2048, 256)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6, 1) == 3858.5
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(7.717, abs=0.001)
    assert "lm_head" not in shapes                     # tied, no copy
    lay = shapes["layers"]
    assert round(sum(x.size for x in jax.tree.leaves(lay)) / 16e6, 1) \
        == 207.6
    assert lay["wqkv"].shape == (16, 2048, 1024 + 256 + 128 + 128)
    assert lay["wo"].shape == (16, 1024, 2048)
    assert lay["conv1_w"].shape == (16, 2, 10, 128, 128)
    assert lay["router_w3"].shape == (16, 256, 17)
    assert lay["w_gate"].shape == (16, 16, 2048, 2048)
    assert shapes["embed"].shape == (262272, 2048)
    # u (1 280) and c0 + vb (1 408 carried as 1 536: whole tiles).
    assert model.state_row_bytes() == 90_112 == 16 * (1280 + 1536) * 2
    assert model.fixed_cache_bytes() == 65 * 90_112
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.kv_cache_page_bytes(16) == 16 * 16_384
    caches = jax.eval_shape(lambda: model.make_kv_caches(32, 16))
    assert caches["k"].shape == (16, 32, 2, 16, 128)
    assert caches["cca_u"].shape == (16, 65, 1, 2, 640)
    assert caches["cca_cv"].shape == (16, 65, 1, 4, 384)
    for text in ("3 858.5 M", "7.717 GB", "207.6 M", "90 112 B",
                 "16 384 B"):
        assert text in cf["deployment"], text


def test_work_module_counts_what_its_docstring_says():
    from benchmarks.rooflines import top1_grouped_gemm as work
    cf = config_file()
    assert work.experts_hit(16, 64) == pytest.approx(15.67, abs=0.005)
    assert work.experts_hit(16, 1) == pytest.approx(16 / 17)
    assert work.experts_hit(16, 2056) == pytest.approx(16.0)
    one = 3 * 2048 * 2048
    assert one * 2 == 25_165_824                       # 25.2 MB an expert
    ctx = _Ctx({("vdt:step_tokens", "decode"): 60.0,
                ("vdt:step_tokens", "prefill"): 4.0})
    w = work.work(cf, ctx)
    assert w["bytes"] == pytest.approx(16 * 15.67 * one * 2, rel=1e-3)
    assert w["bytes"] / 1e9 == pytest.approx(6.31, abs=0.01)
    assert w["flops"] == 2.0 * 64 * 16 / 17 * one * 16
    # 64 rows: 7.7 ms of weights at the memory's peak against 0.1 ms of
    # operations; even a 2 048-token step (128 rows an expert) is bound
    # by the weights, two to one.
    assert w["bytes"] / 819e9 == pytest.approx(7.7e-3, rel=0.01)
    assert w["flops"] / 197e12 < 0.02 * w["bytes"] / 819e9
    big = work.work(cf, _Ctx({("vdt:step_tokens", "decode"): 60.0,
                              ("vdt:step_tokens", "prefill"): 1996.0}))
    assert big["bytes"] == 16 * 16.0 * one * 2
    assert big["flops"] / 197e12 == pytest.approx(
        0.5 * big["bytes"] / 819e9, rel=0.02)
    # It reads nothing of a configuration without such a router, and the
    # accepted module reads nothing of this one (no `intermediate_size`).
    for name in os.listdir(os.path.join(run.HERE, "configs")):
        other = run.load_json(run.HERE, "configs", name)
        if other["architectures"] != [ARCH]:
            assert work.work(other, ctx) is None, name
    assert work.work(cf, _Ctx({})) is None
    assert "intermediate_size" not in cf


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the new share's
    reader matches the carried-row kernel's name and no other kernel's;
    the entries list the new cell, move what it reports and keep their
    order among themselves (wherever in the list they stand)."""
    from vllm_distributed_tpu.ops import conv1d, kda, ssd
    spec = layers.load_spec(name)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    src = spec["source"]
    assert src["kind"] == "trace" and src["kind"] in layers.READERS
    manifest = run.load_json(run.MANIFEST)
    order = [m["name"] for m in manifest["per_layer"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    assert CELL in entries[name]["workloads"]
    assert entries[name]["moves"] == "tpot_p50_ms"
    assert entries[name]["layer"] == "kernels"
    assert entries[name]["source"] == "device_trace"
    others = ("unified_write_attend", "ragged_paged_attention",
              "mla_latent_pages", "mla_latent_prefill", kda.KERNEL_RECURRENT,
              kda.KERNEL_CHUNK, ssd.KERNEL_RECURRENT, ssd.KERNEL_CHUNK)
    if name == "kernel.cca_rows_share":
        assert (src["match"], src["reduce"]) == ("conv_rows",
                                                 "share_of_busy")
        assert re.search(src["match"], conv1d.KERNEL)
        for other in (*others, "ragged-dot"):
            assert not re.search(src["match"], other), other
        # No reader that was there counts the carried-row kernel.
        for theirs in order:
            their = layers.load_spec(theirs)["source"]
            if theirs != name and their["kind"] == "trace" \
                    and "match" in their:
                assert not re.search(their["match"], conv1d.KERNEL), theirs
    else:
        assert (src["match"], src["reduce"], src["work"]) == (
            "ragged", "roofline_share", "top1_grouped_gemm")
        assert re.search(src["match"], "ragged-dot")
        # As the accepted `kernel.moe_gemm_roofline` matches them: no
        # kernel of the serving path but the grouped GEMMs has the word.
        for other in (*others, conv1d.KERNEL):
            if other != "ragged_paged_attention":
                assert not re.search(src["match"], other), other
        assert entries[name]["unit"] == "%"


def test_lists_that_take_the_cell_and_lists_that_do_not():
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    # Every per-layer entry lists its cells, so a new cell inherits none.
    assert all("workloads" in m for m in manifest["per_layer"])
    takes = {n for n, m in entries.items() if CELL in m["workloads"]}
    moved = {"tpot_p50_ms", "setup_s"} | {
        n for n, m in e2e.items() if CELL in m.get("workloads", [])}
    # `itl_p95_ms` spread 2.1-2.8% in two sets of six, so the cell
    # reports it and what moves it; `output_tokens_per_s` read 5.5% low in
    # one run of twelve and is left off, as in Granite's cell (PERF.md,
    # Findings PR 41).
    assert {"tpot_p50_ms", "setup_s", "itl_p95_ms"} == moved
    assert {"device.idle_share", "model.step_device_ms_p50",
            "kernel.kv_tokens_read_per_step", "kernel.attn_share",
            "kernel.attn_roofline", "kernel.moe_gemm_share",
            "engine.step_period_mean_ms", "runner.launch_mean_ms",
            "sched.running_mean", "kv.preemptions",
            "frontend.process_outputs_mean_ms",
            "frontend.output_lag_p50_ms", *NEW_METRICS} <= takes
    for name in takes:
        assert entries[name]["moves"] in moved, name
    # What lists Granite's decode-heavy cell lists this one, less the
    # Mamba-2 kernels' three.
    granite = {n for n, m in entries.items()
               if "granite-4.0-h-micro.decode-heavy" in m["workloads"]}
    assert granite - takes == {"kernel.ssd_share", "kernel.ssd_roofline",
                               "kernel.ssd_chunk_tokens_per_step"}
    for absent in ("kernel.kda_share", "kernel.kda_roofline",
                   "kv.state_bytes_held", "kernel.mla_share",
                   "kernel.mla_roofline", "kernel.moe_gemm_roofline",
                   "kernel.moe_local_gemm_roofline", "client.itl_p95_ms",
                   "client.send_lateness_p95_ms", "sched.waiting_mean",
                   "sched.queue_wait_p50_ms",
                   "sched.step_prefill_tokens_mean"):
        assert absent not in takes, absent
    assert not [n for n in takes if n.endswith(".tpot")]
    # The cell is the last name of every list that takes it: appended.
    for m in (*manifest["per_layer"], *manifest["end_to_end"]):
        if CELL in m.get("workloads", []):
            assert m["workloads"].index(CELL) >= \
                len(m["workloads"]) - 1 - sum(
                    1 for w in m["workloads"][m["workloads"].index(CELL):]
                    if w != CELL), m["name"]
