"""What PR 43 added to the benchmark: the Command A+ reference
(``benchmarks/references/cohere_moe.py``) against the program's engine at
a toy width on the CPU through the harness's own comparison, the whole
order of a run on the toy cell under every per-layer entry that
``BENCHMARK.json`` gives the new cell, the configuration's file against
the catalog row and against the model's own shapes, the two work modules'
counts and the reader files. Entries are held by name and relative order,
never by position or by count: what a later PR appends stands beside
them."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import cohere_moe
from test_benchmark_deepseek import _serve_scoring

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CONFIG = "command-a-plus-ep8"
CELL = "command-a-plus-ep8.long-document"
TINY = "tiny-cohere-moe.tiny-long"
ARCH = "Cohere2MoeForCausalLM"
NEW_METRICS = ("kernel.attn_window_roofline",
               "kernel.kv_window_tokens_read_per_step",
               "kv.window_pages_freed", "kv.window_pages_held",
               "kv.full_pages_held", "kernel.moe_share_gemm_roofline")
EVERY_CONTROL = ("rope_on_full_layers", "no_shared_experts",
                 "shared_sum_not_mean", "softmax_router")


class _Ctx:
    """Counters of a made-up capture: {(family, label value or None):
    the mean a step}."""

    def __init__(self, means):
        self.means = means

    def step_mean(self, name, labels=None):
        return self.means.get((name, next(iter((labels or {}).values()),
                                          None)))


def config_file():
    return run.load_json(run.HERE, "configs", f"{CONFIG}.json")


def test_engine_matches_plain_reference_through_the_harness(tmp_path,
                                                            monkeypatch):
    """Prefill in chunks of 64, then decode, through both cache groups,
    the trimmed kernel and the share's dispatch, against the reference's
    whole-sequence forward, by the comparison that decides `correct`:
    every control the chip runs must catch, and those that the float32
    tests alone hold, are caught at three times the toy's tolerance (the
    scoring prompts are 6 to 26 of the toy's 8-token windows long)."""
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
    on_chip = cohere_moe.CONTROLS[ARCH]
    assert set(got["controls"]) == set(on_chip) <= set(EVERY_CONTROL)
    extra = [c for c in (*EVERY_CONTROL, "no_window") if c not in on_chip]
    monkeypatch.setitem(cohere_moe.CONTROLS, ARCH, extra)
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
    path = tmp_path / "manifest-cohere-moe.json"
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


def test_run_cell_serves_the_toy_cell_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the new counters are read (pages held by group,
    pages freed, what a window layer reads), the trace metrics find no
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
    got = last["metrics"]
    assert {"kernel.kv_tokens_read_per_step", "sched.running_mean",
            "kv.preemptions", "runner.host_transfers_per_step",
            "kernel.kv_window_tokens_read_per_step",
            "kv.window_pages_freed", "kv.window_pages_held",
            "kv.full_pages_held"} <= set(got), sorted(got)
    assert not {"kernel.attn_window_roofline",
                "kernel.moe_share_gemm_roofline", "kernel.moe_gemm_share",
                "kernel.attn_share", "device.idle_share"} & set(got)
    assert got["kv.preemptions"]["value"] == 0
    assert got["kv.window_pages_freed"]["value"] > 0
    assert 0 < got["kernel.kv_window_tokens_read_per_step"]["value"] < \
        got["kernel.kv_tokens_read_per_step"]["value"]
    assert 0 < got["kv.window_pages_held"]["value"] < \
        got["kv.full_pages_held"]["value"]
    for control in cohere_moe.CONTROLS[ARCH]:
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    prec = json.loads(lines[-2])
    assert prec["float32_highest"]["max"] < 2e-5 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


def catalog_row() -> dict:
    """The catalog's row (the model-configs guide), where this sandbox
    has the guide; the copy kept with the tests elsewhere."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows
                    if r["name"] == "command-a-plus-05-2026")["config"]
    return run.load_json(DATA, "command-a-plus-05-2026.catalog.json")


def test_the_kept_copy_of_the_catalog_row_is_the_catalog_s():
    assert run.load_json(DATA, "command-a-plus-05-2026.catalog.json") == \
        catalog_row()


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """The file holds every key of the catalog row's config with the
    row's value, except the depth (and the layer list cut with it), the
    experts held and the vocabulary's slice, which `reduced` names; every
    width is the row's; the manifest's entry says the same."""
    cf, row = config_file(), catalog_row()
    differs = {k for k, v in row.items() if cf.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "layer_types", "num_experts",
                       "vocab_size"}
    assert set(cf["reduced"]) == differs - {"layer_types"}
    assert cf["layer_types"] == row["layer_types"][:4] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert cf["published"] == {k: row[k] for k in cf["reduced"]}
    assert (cf["hidden_size"], cf["num_attention_heads"],
            cf["num_key_value_heads"], cf["head_dim"],
            cf["intermediate_size"], cf["num_experts_per_tok"],
            cf["num_shared_experts"], cf["sliding_window"],
            cf["num_experts"], cf["vocab_size"]) == (
                4096, 128, 8, 128, 4096, 8, 4, 4096, 16, 32768)
    assert cf["expert_share"] == {"published_experts": 128, "chips": 8,
                                  "index": 0}
    assert cf["architectures"] == [ARCH] and cf["reference"] == "cohere_moe"
    assert cf["engine_args"] == {
        "dtype": "bfloat16", "block_size": 16, "max_model_len": 32768,
        "max_num_batched_tokens": 2048, "max_num_seqs": 24}
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200 and len(cf["assumed"]) >= 6
    listed = " ".join(cf["assumed"])
    for point in ("average", "intermediate_size", "architectures",
                  "first_k_dense_replace", "torch_dtype", "tokenizer",
                  "tower"):
        assert point in listed, point
    for point in ("average", "intermediate_size", "first_k_dense_replace",
                  "expert_share", "no_window"):
        assert point in cohere_moe.__doc__, point
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == cf["reduced"]
    assert entry[0]["source"] == cf["source"] == \
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/" \
        "blob/main/config.json"
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["traffic"] == "long-document" and cells[0]["chips"] == 1
    cell = run.load_cell(CELL)
    assert cell["load"] == {"clients": 24}
    assert cell["load"]["clients"] == cf["engine_args"]["max_num_seqs"]
    assert cell["why"] == cells[0]["why"] and len(cell["why"]) <= 200
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["shared_prefix"] is None
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 12288,
                                 "sigma": 0.4, "min": 6144, "max": 24576}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 512}
    assert mix["prompt_len"]["min"] > cf["sliding_window"]


def test_the_file_s_arithmetic_is_the_model_s():
    """4 867.5 M parameters = 9.735 GB with the tied head held twice (9.47
    GB counting it once), 1 149.8 M a layer, 4 096 B of K/V a token a
    layer and a window pool of 6 368 pages, as the file's `deployment`
    reckons, from the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_distributed_tpu.core.kv_cache_utils import \
        window_group_pool_blocks
    from vllm_distributed_tpu.models.cohere_moe import \
        Cohere2MoeForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    cf = config_file()
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    Cohere2MoeForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    ea = cf["engine_args"]
    arch.window_group_pages = window_group_pool_blocks(
        arch.window_group, ea["block_size"], ea["max_num_seqs"],
        ea["max_num_batched_tokens"], ea["max_model_len"] // 16)
    arch.window_group_page_size = ea["block_size"]
    assert arch.window_group_pages == 24 * (256 + 4) + 128 == 6368
    model = Cohere2MoeForCausalLM(arch)
    assert (arch.window_group, arch.window_pattern, arch.nope_layers,
            arch.experts_held, arch.router_experts, arch.rope_theta) == (
                4096, (4096, 4096, 4096, 0), (False, False, False, True),
                (0, 16), 128, 50000)
    assert (arch.norm_type, arch.parallel_block, arch.shared_block_ln,
            arch.rope_interleaved, arch.tie_word_embeddings) == (
                "layernorm", True, True, True, True)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6, 1) == 4867.5
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(9.735, abs=0.001)
    lay = shapes["layers"]
    assert round(sum(x.size for x in jax.tree.leaves(lay)) / 4e6, 1) \
        == 1149.8
    assert lay["wq"].shape == (4, 4096, 16384)
    assert lay["wk"].shape == lay["wv"].shape == (4, 4096, 1024)
    assert lay["router"].shape == (4, 4096, 128)
    assert lay["w_gate"].shape == (4, 16, 4096, 4096)
    assert lay["shared_gate"].shape == (4, 4096, 16384)
    assert lay["shared_down"].shape == (4, 16384, 4096)
    assert shapes["embed"].shape == (32768, 4096)
    caches = jax.eval_shape(lambda: model.make_kv_caches(32, 16))
    assert caches["k"].shape == (1, 32, 8, 16, 128)
    assert caches["k_win"].shape == (3, 6368, 8, 16, 128)
    assert model.fixed_cache_bytes() == 6368 * 16 * 3 * 4096
    assert model.fixed_cache_bytes() / 1e9 == pytest.approx(1.25, abs=0.01)
    for text in ("142.6 M", "201.3 M", "805.3 M", "1 149.8 M", "4 867.5 M",
                 "9.735 GB", "9.47 GB", "4 096 B", "6 368 pages", "1.25 GB"):
        assert text in cf["deployment"], text


def test_work_modules_count_what_their_docstrings_say():
    from benchmarks.rooflines import (cohere_share_grouped_gemm,
                                      local_grouped_gemm,
                                      windowed_paged_attention)
    cf = config_file()
    assert windowed_paged_attention.layer_kinds(cf) == (1, 3)
    assert windowed_paged_attention.kv_bytes_per_token_layer(cf) == 4096
    # A decode step of 24 requests at context 13 000: the full layer
    # reads them whole, a window layer 4 096 of each.
    counters = {("vdt:step_kv_tokens_read", None): 24 * 13000.0,
                ("vdt:step_kv_window_tokens_read", None): 24 * 4096.0,
                ("vdt:step_attn_pairs", "full"): 24 * 13000.0,
                ("vdt:step_attn_pairs", "window"): 24 * 4096.0}
    w = windowed_paged_attention.work(cf, _Ctx(counters))
    assert w["bytes"] == (24 * 13000 + 3 * 24 * 4096) * 4096
    assert w["bytes"] / 1e9 == pytest.approx(2.49, abs=0.01)
    assert w["flops"] == (24 * 13000 + 3 * 24 * 4096) * 4 * 128 * 128
    # Bytes bound: 3.0 ms at the memory's peak, 0.2 ms of operations.
    assert w["bytes"] / 819e9 > 10 * w["flops"] / 197e12
    # A 2 048-token chunk at context 16 384: operations bound.
    chunk = 2048 * (16384 - 2048) + 2048 * 2049 // 2
    big = windowed_paged_attention.work(cf, _Ctx({
        ("vdt:step_kv_tokens_read", None): 16384.0,
        ("vdt:step_kv_window_tokens_read", None): 4095.0 + 2048,
        ("vdt:step_attn_pairs", "full"): float(chunk),
        ("vdt:step_attn_pairs", "window"): 2048.0 * 4096}))
    assert big["flops"] / 1e12 == pytest.approx(3.71, abs=0.02)
    assert big["flops"] / 197e12 > 50 * big["bytes"] / 819e9
    for missing in counters:
        assert windowed_paged_attention.work(
            cf, _Ctx({k: v for k, v in counters.items()
                      if k != missing})) is None
    # The share's GEMMs: local_grouped_gemm's count on this file's keys.
    tokens = {("vdt:step_tokens", "decode"): 24.0,
              ("vdt:step_tokens", "prefill"): 0.0}
    got = cohere_share_grouped_gemm.work(cf, _Ctx(tokens))
    one = 3 * 4096 * 4096
    picks = 24 * 8 * 16 / 128
    hit = 16 * (1 - (15 / 16) ** picks)
    assert got["bytes"] == pytest.approx(4 * hit * one * 2)
    assert got["flops"] == 2.0 * picks * one * 4
    assert got == local_grouped_gemm.work(
        {**cf, "n_routed_experts": 16, "moe_intermediate_size": 4096}, _Ctx(
            tokens))
    assert cohere_share_grouped_gemm.work(cf, _Ctx({})) is None
    # They read nothing of a configuration without these keys.
    for name in os.listdir(os.path.join(run.HERE, "configs")):
        other = run.load_json(run.HERE, "configs", name)
        if other["architectures"] != [ARCH]:
            assert windowed_paged_attention.work(
                other, _Ctx({})) is None, name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the entries list
    the new cell alone, move what it reports and keep their order among
    themselves (wherever in the list they stand)."""
    spec = layers.load_spec(name)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    src = spec["source"]
    assert src["kind"] in layers.READERS
    manifest = run.load_json(run.MANIFEST)
    order = [m["name"] for m in manifest["per_layer"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    entry = entries[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tpot_p50_ms"
    assert entry["layer"] == ("kernels" if name.startswith("kernel.")
                              else "KV manager")
    if src["kind"] == "trace":
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert src["reduce"] == "roofline_share"
        assert (src["match"], src["work"]) == {
            "kernel.attn_window_roofline": (
                "unified_write_attend", "windowed_paged_attention"),
            "kernel.moe_share_gemm_roofline": (
                "ragged", "cohere_share_grouped_gemm")}[name]
        assert os.path.exists(os.path.join(
            run.HERE, "rooflines", f"{src['work']}.py"))
    else:
        assert entry["source"] == "program_counter"
        from vllm_distributed_tpu.metrics import prometheus
        assert src["metric"] in open(prometheus.__file__).read()
        assert src["reduce"] == {
            "kernel.kv_window_tokens_read_per_step": "hist_mean",
            "kv.window_pages_freed": "delta"}.get(name, "gauge_mean")
        if src["reduce"] == "gauge_mean":
            assert src["labels"] == {"group": name.split(".")[1].split(
                "_")[0]}


def test_lists_that_take_the_cell_and_lists_that_do_not():
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    takes = {n for n, m in entries.items() if CELL in m["workloads"]}
    moved = {"tpot_p50_ms", "setup_s"} | {
        n for n, m in e2e.items() if CELL in m.get("workloads", [])}
    assert moved == {"tpot_p50_ms", "setup_s", "itl_p95_ms",
                     "output_tokens_per_s"}
    assert {"device.idle_share", "model.step_device_ms_p50",
            "kernel.kv_tokens_read_per_step", "kernel.attn_share",
            "kernel.moe_gemm_share", "engine.step_period_mean_ms",
            "runner.launch_mean_ms", "sched.running_mean", "kv.preemptions",
            "frontend.process_outputs_mean_ms",
            "frontend.output_lag_p50_ms", *NEW_METRICS} <= takes
    for name in takes:
        assert entries[name]["moves"] in moved, name
    # The accepted attention roofline charges every layer with every
    # request's whole context, which a trimmed window layer no longer
    # reads: the cell is not on its list.
    for absent in ("kernel.attn_roofline", "kernel.moe_gemm_roofline",
                   "kernel.moe_local_gemm_roofline", "kernel.mla_share",
                   "kv.state_bytes_held", "client.itl_p95_ms",
                   "client.send_lateness_p95_ms", "sched.waiting_mean",
                   "sched.queue_wait_p50_ms",
                   "sched.step_prefill_tokens_mean"):
        assert absent not in takes, absent
    assert not [n for n in takes if n.endswith(".tpot")]
    # What lists gigachat's long-context cell lists this one, less the
    # latent kernel's and the accepted share roofline's.
    giga = {n for n, m in entries.items()
            if "gigachat3-702b-ep16.long-context" in m["workloads"]}
    assert giga - takes == {"kernel.mla_share", "kernel.mla_roofline",
                            "kernel.moe_local_gemm_roofline"}
    # Appended: nothing that was there stands behind the cell's name.
    for m in (*manifest["per_layer"], *manifest["end_to_end"]):
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL or \
                m["workloads"].index(CELL) > m["workloads"].index(
                    "zaya1-8b.decode-heavy"), m["name"]


def test_a_named_architecture_that_is_unknown_is_refused():
    """`resolve_architecture` (ROADMAP D15): a configuration that NAMES
    an architecture the registry lacks is refused, whatever else it
    holds; one that names none is a Llama, as the tiny test
    configurations rely on."""
    from types import SimpleNamespace

    from vllm_distributed_tpu.models.llama import LlamaForCausalLM
    from vllm_distributed_tpu.models.registry import resolve_architecture
    with pytest.raises(ValueError, match="NotAModelForCausalLM"):
        resolve_architecture(SimpleNamespace(
            architectures=["NotAModelForCausalLM"], model_type="nope",
            num_hidden_layers=2))
    assert resolve_architecture(SimpleNamespace(
        architectures=None, num_hidden_layers=2)) is LlamaForCausalLM
    assert resolve_architecture(SimpleNamespace(
        architectures=["NotAModelForCausalLM", ARCH])).__name__ == ARCH
    with pytest.raises(ValueError, match="names no architecture"):
        resolve_architecture(SimpleNamespace(architectures=[]))
