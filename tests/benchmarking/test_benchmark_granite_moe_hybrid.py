"""What PR 55 added to the benchmark: the reference of Granite 4.0-H with
routed experts (``benchmarks/references/granite_moe_hybrid.py``) against
the program's engine at a toy width on the CPU through the harness's own
comparison, the whole order of a run on the toy cell under every
per-layer entry that ``BENCHMARK.json`` gives the new cell, the
configuration's file against the catalog row and against the model's own
shapes, the work module's counts and the reader files. Entries are held
by name and by their order among themselves, never by position, count or
last place: what a later PR appends stands beside them."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import granite_moe_hybrid
from test_benchmark_bailing import _Ctx
from test_benchmark_deepseek import _serve_scoring

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CONFIG = "granite-4.0-h-small-ep2"
CELL = "granite-4.0-h-small-ep2.decode-heavy"
TINY = "tiny-granite-moe.tiny-decode"
ARCH = "GraniteMoeHybridForCausalLM"
CATALOG = "granite-4.0-h-small"
NEW_METRICS = ("kernel.moe_hybrid_share_gemm_roofline",
               "kernel.ssd_wide_chunk_tokens_per_step")
REDUCED = ("num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size")
CONTROLS = ("no_decay", "no_conv", "no_gate_norm", "no_shared_mlp")
# Held here, in float32, and not on the chip: seeded weights cannot push
# them past three tolerances there (the configuration's
# `tolerance_reason`).
CPU_ONLY_CONTROLS = ("no_renorm", "all_experts")


def small():
    return run.load_json(run.HERE, "configs", f"{CONFIG}.json")


def test_engine_matches_plain_reference_through_the_harness(tmp_path,
                                                            monkeypatch):
    """Prefill in chunks of 64, then decode, through state rows, packed
    K/V pages, the scan over two periods and the share's dispatch
    (experts 4-7 of 8, top-3) beside the shared MLP, against the
    reference's token-by-token recurrence and its scan over the held
    experts, by the comparison that decides `correct`: every control,
    those the chip runs must catch and the one held here alone, moves
    the reference and is caught at three times the toy's tolerance."""
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell(TINY, DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, seed=2 ** 31 + 7)
    scoring = _serve_scoring(cell, args, cf, model_dir)
    got = run.compare_with_reference(cell, args, scoring)
    assert got["positions"] == sum(len(s["tokens"]) - 1 for s in scoring)
    assert got["max_abs_logprob_dev"] < 1e-5, got
    assert got["ok"], json.dumps(got["controls"])
    assert tuple(got["controls"]) == CONTROLS == tuple(
        granite_moe_hybrid.CONTROLS[ARCH])
    monkeypatch.setitem(granite_moe_hybrid.CONTROLS, ARCH,
                        list(CPU_ONLY_CONTROLS))
    more = run.compare_with_reference(cell, args, scoring)["controls"]
    assert tuple(more) == CPU_ONLY_CONTROLS
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
    path = tmp_path / "manifest-granite-moe.json"
    path.write_text(json.dumps(m))
    return str(path)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 23, 4.0, True, data_root={data!r},
                    manifest_path={manifest!r}, require_platform="cpu",
                    ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", {cell!r}, "--seed",
                          str(2**31 + 23)], data_root={data!r})
print(json.dumps(line))
"""


def test_run_cell_serves_the_share_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the counters are read (the chunked form's among
    them, three host transfers a step), the trace metrics find no device
    plane on a CPU and are left out, every control stands beside its
    limit; and `reference_precision` on what the run kept: a forward
    rounded through float8 is further off than one in bfloat16."""
    manifest = tiny_manifest(tmp_path)
    wanted = {x["name"] for x in run.load_json(manifest)["per_layer"]}
    assert set(NEW_METRICS) <= wanted and len(wanted) >= 22
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
    assert {"kernel.ssd_wide_chunk_tokens_per_step",
            "kernel.kv_tokens_read_per_step", "sched.running_mean",
            "runner.host_transfers_per_step"} <= got, got
    assert not {"kernel.moe_hybrid_share_gemm_roofline",
                "kernel.moe_gemm_share", "kernel.attn_share",
                "device.idle_share"} & got
    assert last["metrics"]["kernel.ssd_wide_chunk_tokens_per_step"][
        "value"] > 0
    assert last["metrics"]["runner.host_transfers_per_step"]["value"] == 3.0
    for control in CONTROLS:
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    prec = json.loads(lines[-2])
    assert prec["float32_highest"]["max"] < 1e-5 < 0.002 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


def catalog_row() -> dict:
    """The catalog's row (the model-configs guide), where this sandbox
    has the guide; the copy kept with the tests elsewhere."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == CATALOG)
    return run.load_json(DATA, f"{CATALOG}.catalog.json")


def test_the_kept_copy_of_the_catalog_row_is_the_catalog_s():
    kept = run.load_json(DATA, f"{CATALOG}.catalog.json")
    row = catalog_row()
    assert kept == {k: row[k] for k in ("name", "source_url", "config")}


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """Every key of the catalog row's config is in the file at the row's
    value, or is one of the four under `reduced` with the row's value
    under `published` beside it; no width is among them; and the
    manifest's entry says the same."""
    cf, row = small(), catalog_row()
    assert tuple(cf["reduced"]) == REDUCED
    for key, value in row["config"].items():
        if key in REDUCED:
            assert cf["published"][key] == value and cf[key] != value, key
        else:
            assert cf.get(key, "absent") == value, key
    assert set(cf["published"]) == set(REDUCED)
    assert (cf["hidden_size"], cf["intermediate_size"],
            cf["shared_intermediate_size"], cf["num_experts_per_tok"],
            cf["mamba_n_heads"], cf["mamba_d_head"], cf["mamba_d_state"],
            cf["num_attention_heads"], cf["num_key_value_heads"]) == (
                4096, 768, 1536, 10, 128, 64, 128, 32, 8)
    assert (cf["num_hidden_layers"], cf["num_local_experts"],
            cf["vocab_size"]) == (10, 36, 50176)
    assert cf["layer_types"] == row["config"]["layer_types"][:10] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cf["expert_share"] == {"published_experts": 72, "chips": 2,
                                  "index": 0}
    assert cf["source"] == row["source_url"]
    assert cf["architectures"] == [ARCH]
    # 64: the HBM profile admits the rows, 32 snapshot slots and 64
    # full-length requests' pages together (the file's `deployment`).
    seqs = 64
    assert cf["engine_args"] == {
        "dtype": "bfloat16", "block_size": 16, "max_model_len": 4096,
        "max_num_batched_tokens": 2048, "max_num_seqs": seqs}
    assert cf["reference"] == "granite_moe_hybrid"
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200 and len(cf["assumed"]) >= 4
    assert any("intermediate_size" in a and "inference" in a
               for a in cf["assumed"])
    for text in ("two chips", "4 stages", "share 0"):
        assert text in cf["deployment"], text
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and tuple(entry[0]["reduced"]) == REDUCED
    assert entry[0]["source"] == cf["source"]
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["traffic"] == "decode-heavy" and cells[0]["chips"] == 1
    assert run.load_cell(CELL)["load"]["clients"] == seqs
    assert f"{seqs} clients" in cells[0]["why"]
    assert cells[0]["why"] == run.load_cell(CELL)["why"]
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]


def test_the_file_s_arithmetic_is_the_model_s(monkeypatch):
    """4 757.2 M parameters = 9.514 GB, 38 204 928 B of state a sequence
    and 4 096 B of K/V a token, as the file's `deployment` reckons, from
    the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_distributed_tpu.models.granite_hybrid import \
        GraniteMoeHybridForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    cf = small()
    seqs = cf["engine_args"]["max_num_seqs"]
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    GraniteMoeHybridForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    arch.state_slots = seqs
    model = GraniteMoeHybridForCausalLM(arch)
    assert model._attn_layers == [5] and model._period() == 10
    assert (arch.sm_scale_override, arch.embed_scale, arch.rotary_dim,
            arch.residual_multiplier, arch.logit_multiplier, arch.kv_pack
            ) == (1 / 128, 12.0, 0, 0.22, 1 / 16, 1)
    assert (arch.experts_held, arch.router_experts,
            arch.num_experts_per_tok, arch.moe_intermediate_size,
            arch.intermediate_size) == ((0, 36), 72, 10, 768, 1536)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6, 1) == 4757.2
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(9.514, abs=0.001)
    assert "lm_head" not in shapes                     # tied, no copy
    assert shapes["embed"].shape == (50176, 4096)
    lay = shapes["layers"]
    assert lay["m_in_x"].shape == (9, 4096, 8192)
    assert lay["m_w_gate"].shape == (9, 36, 4096, 768)
    assert lay["a_w_down"].shape == (1, 36, 768, 4096)
    assert lay["m_router"].shape == (9, 4096, 72)
    assert lay["m_gate"].shape == (9, 4096, 1536)
    assert lay["a_wk"].shape == (1, 4096, 1024)
    assert model.state_row_bytes() == 38_204_928 == \
        9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    assert model.fixed_cache_bytes() == (seqs + 1) * 38_204_928
    # A decode step's picks are worked on whole, a chunk's in one pass
    # of what an even router lands here and a half more.
    assert model.share_capacity(seqs, 10) == seqs * 10
    assert model.share_capacity(2048, 10) == 15_488
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.kv_cache_page_bytes(16) == 16 * 4096
    caches = jax.eval_shape(lambda: model.make_kv_caches(32, 16))
    assert caches["k"].shape == (1, 32, 8, 16, 128)
    assert caches["ssm"].shape == (9, seqs + 1, 128, 8192)
    for text in ("4 757.2 M", "9.514 GB", "38 204 928 B", "4 096 B"):
        assert text in cf["deployment"], text


def test_work_module_counts_what_its_docstring_says():
    """The share's grouped GEMMs at the published widths: a decode
    step's even picks hit all 36 held experts, 6.79 GB a step; the
    bytes are the decode tokens' alone, the operations all tokens';
    without the counters, or without a share, nothing."""
    from benchmarks.rooflines import granite_share_grouped_gemm as work
    from benchmarks.rooflines import ssd_state
    cf = small()
    one_expert = 3 * 4096 * 768
    decode = {("vdt:step_tokens", "decode"): 62.0,
              ("vdt:step_tokens", "prefill"): 0.0}
    w = work.work(cf, _Ctx(decode))
    assert w["bytes"] == pytest.approx(10 * 36 * one_expert * 2, rel=1e-3)
    assert w["bytes"] / 1e9 == pytest.approx(6.79, abs=0.01)
    assert w["flops"] == 2.0 * 62 * 10 * 36 / 72 * one_expert * 10
    mixed = work.work(cf, _Ctx({("vdt:step_tokens", "decode"): 62.0,
                                ("vdt:step_tokens", "prefill"): 400.0}))
    assert mixed["bytes"] == w["bytes"]
    assert mixed["flops"] == pytest.approx(w["flops"] * 462 / 62)
    # The bytes win a decode step by twenty to one: 8.3 ms at the peak.
    assert w["bytes"] / 819e9 == pytest.approx(8.3e-3, rel=0.01)
    assert w["flops"] / 197e12 < 0.05 * w["bytes"] / 819e9
    assert work.work(cf, _Ctx({})) is None
    assert work.work({**cf, "expert_share": None}, _Ctx(decode)) is None
    for other in ("olmo2-1b", "olmoe-1b-7b", "granite-4.0-h-micro",
                  "command-a-plus-ep8"):
        assert work.work(run.load_json(run.HERE, "configs",
                                       f"{other}.json"), _Ctx(decode)) \
            is None, other
    # The state-space kernel's count reads this file's keys as they are:
    # 4 MiB a layer, 75.5 MB a decode token over nine layers.
    assert ssd_state.mamba_layers(cf) == 9
    assert ssd_state.state_bytes(cf) == 2 ** 22
    s = ssd_state.work(cf, _Ctx(decode))
    assert s["bytes"] == 9 * (2 * 62 * 2 ** 22
                              + 62 * (3 * 8192 + 2 * 128) * 4)


RECORDED = [
    # (decode tokens, prefill tokens, ms of ragged-dot a step, percent):
    # made-up captures at the published widths around the 8.3 ms bound.
    (62.0, 0.0, 11.0, 75.4), (62.0, 30.0, 12.5, 66.4),
    (40.0, 0.0, 10.0, 82.7)]


@pytest.mark.parametrize("decode,prefill,kernel_ms,want", RECORDED)
def test_the_roofline_share_stays_under_a_hundred(decode, prefill,
                                                  kernel_ms, want):
    """``layers._roofline_share``'s arithmetic on the work module's
    count: the least time over the kernel's, in percent, under 100
    wherever the kernel takes what the bytes alone take or more."""
    from benchmarks.rooflines import granite_share_grouped_gemm as work
    peaks = layers.load_peaks("TPU v5 lite")
    w = work.work(small(), _Ctx({("vdt:step_tokens", "decode"): decode,
                                 ("vdt:step_tokens", "prefill"): prefill}))
    least = max(w["bytes"] / peaks["hbm_bytes_per_s"],
                w["flops"] / peaks["bf16_flops_per_s"])
    share = 100.0 * least / (1e-3 * kernel_ms)
    assert share == pytest.approx(want, abs=0.1) and share < 100.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the entries are
    there once, list the new cell, move what it reports and keep their
    order among themselves (wherever in the list they stand)."""
    spec = layers.load_spec(name)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    src = spec["source"]
    assert src["kind"] in layers.READERS
    manifest = run.load_json(run.MANIFEST)
    order = [m["name"] for m in manifest["per_layer"]]
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert len(order) == len(set(order))
    assert [n for n in order if n in NEW_METRICS] == list(NEW_METRICS)
    assert CELL in entries[name]["workloads"]
    assert entries[name]["moves"] == "tpot_p50_ms"
    assert entries[name]["layer"] == "kernels"
    if name == "kernel.moe_hybrid_share_gemm_roofline":
        assert (src["kind"], src["match"], src["reduce"], src["work"]) == (
            "trace", "ragged", "roofline_share",
            "granite_share_grouped_gemm")
        assert re.search(src["match"], "ragged-dot-none.3")
        from vllm_distributed_tpu.ops import ssd
        for other in (ssd.KERNEL_RECURRENT, ssd.KERNEL_CHUNK, "conv_rows",
                      "unified_write_attend", "fusion.12"):
            assert not re.search(src["match"], other), other
        assert entries[name]["source"] == "device_trace"
    else:
        assert (src["metric"], src["reduce"]) == (
            "vdt:step_ssd_chunk_tokens", "hist_mean")
        assert src == layers.load_spec(
            "kernel.ssd_chunk_tokens_per_step")["source"]
        assert entries[name]["source"] == "program_counter"


def test_lists_that_take_the_cell_and_lists_that_do_not():
    """The cell reports `tpot_p50_ms`, `itl_p95_ms` and `setup_s`, every
    host, engine and scheduler metric that moves the first, what
    Micro's cell reports less the three lists an accepted test holds to
    that cell, and the grouped GEMMs' share; nothing of a kernel it does
    not run."""
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    takes = {n for n, m in entries.items() if CELL in m.get("workloads", [])}
    moved = {"tpot_p50_ms", "setup_s"} | {
        n for n, m in e2e.items() if CELL in m.get("workloads", [])}
    assert {"tpot_p50_ms", "setup_s", "itl_p95_ms"} == moved
    assert {"device.idle_share", "model.step_device_ms_p50",
            "kernel.kv_tokens_read_per_step", "kernel.attn_share",
            "kernel.decode_kv_tokens_walked_per_step",
            "kernel.moe_gemm_share", "engine.step_period_mean_ms",
            "runner.launch_mean_ms", "runner.host_transfers_per_step",
            "sched.running_mean", "kv.preemptions",
            "frontend.process_outputs_mean_ms",
            "frontend.output_lag_p50_ms", *NEW_METRICS} <= takes
    for name in takes:
        assert entries[name]["moves"] in moved, name
        assert entries[name]["workloads"].count(CELL) == 1, name
    for name, m in entries.items():
        group = name.split(".")[0]
        if group in ("engine", "runner", "sched") and \
                m["moves"] == "tpot_p50_ms" and not name.endswith(".tpot"):
            assert name in takes, name
    micro = {n for n, m in entries.items()
             if "granite-4.0-h-micro.decode-heavy" in m.get("workloads", [])}
    assert micro - takes == {"kernel.ssd_share", "kernel.ssd_roofline",
                             "kernel.ssd_chunk_tokens_per_step"}
    assert takes - micro == {"kernel.moe_gemm_share", *NEW_METRICS}
    for absent in ("kernel.attn_roofline", "kernel.kda_share",
                   "kernel.mla_share", "kernel.moe_gemm_roofline",
                   "kernel.moe_local_gemm_roofline",
                   "kernel.moe_share_gemm_roofline", "kv.state_bytes_held",
                   "client.itl_p95_ms", "sched.waiting_mean"):
        assert absent not in takes, absent
    assert CELL not in e2e["ttft_p50_ms"]["workloads"]
    assert CELL not in e2e["output_tokens_per_s"]["workloads"]
