"""What PR 47 added to the benchmark: the configuration's file against
the catalog row and against the model's own shapes, the two work modules
against the accepted functions they call, the reader files and the
manifest's entries, and the harness's whole order on a toy cell under
every per-layer entry that ``BENCHMARK.json`` gives the new cell. Entries
are held by name, by membership and by their order among themselves,
never by position or by count: what a later PR appends stands beside
them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import smallthinker
# Counters of a made-up capture, as that file's own tests make them.
from test_benchmark_cohere_moe import _Ctx

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CONFIG = "smallthinker-21b-a3b"
CELL = "smallthinker-21b-a3b.mixed-length"
TINY = "tiny-smallthinker.tiny-mixed"
ARCH = "SmallThinkerForCausalLM"
CATALOG = "smallthinker-21ba3b-instruct.catalog.json"
NEW_METRICS = ("kernel.moe_reglu_gemm_roofline",
               "kernel.attn_layout_window_roofline",
               "sched.requests_past_window_per_step")
REDUCED = ["num_hidden_layers", "sliding_window_layout", "rope_layout"]


def config_file():
    return run.load_json(run.HERE, "configs", f"{CONFIG}.json")


def catalog_row() -> dict:
    """The catalog's row (the model-configs guide), where this sandbox
    has the guide; the copy kept with the tests elsewhere."""
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows
                    if r["name"] == "SmallThinker-21BA3B-Instruct")["config"]
    return run.load_json(DATA, CATALOG)["config"]


def test_the_kept_copy_of_the_catalog_row_is_the_catalog_s():
    kept = run.load_json(DATA, CATALOG)
    assert kept["config"] == catalog_row()
    assert kept["source_url"] == config_file()["source"]


def test_catalog_numbers_are_kept_key_for_key_or_listed_as_reduced():
    """The file holds every key of the catalog row's config with the
    row's value, except the depth and the two layouts cut with it, which
    ``reduced`` names and nothing else; every width is the row's; the
    manifest's entry says the same."""
    cf, row = config_file(), catalog_row()
    differs = {k for k, v in row.items() if cf.get(k, "absent") != v}
    assert differs == set(REDUCED) and cf["reduced"] == REDUCED
    assert cf["num_hidden_layers"] == 8
    for key in ("sliding_window_layout", "rope_layout"):
        assert cf[key] == row[key][:8] == [0, 1, 1, 1] * 2
    assert cf["published"]["num_hidden_layers"] == \
        row["num_hidden_layers"] == 52
    assert (cf["hidden_size"], cf["num_attention_heads"],
            cf["num_key_value_heads"], cf["head_dim"],
            cf["moe_ffn_hidden_size"], cf["moe_num_primary_experts"],
            cf["moe_num_active_primary_experts"],
            cf["sliding_window_size"], cf["vocab_size"],
            cf["tie_word_embeddings"], cf["rope_theta"],
            cf["moe_primary_router_apply_softmax"]) == (
                2560, 28, 4, 128, 768, 64, 6, 4096, 151936, False,
                1500000, True)
    assert cf["architectures"] == [ARCH]
    assert cf["reference"] == "smallthinker"
    assert cf["engine_args"] == {
        "dtype": "bfloat16", "block_size": 16, "max_model_len": 16384,
        "max_num_batched_tokens": 2048, "max_num_seqs": 32}
    assert cf["engine_args"]["max_model_len"] == \
        cf["max_position_embeddings"]
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200 and len(cf["assumed"]) >= 7
    listed = " ".join(cf["assumed"])
    for point in ("architectures", "ReLU", "half-rotation", "own position",
                  "norm_topk_prob", "torch_dtype", "secondary",
                  "tokenizer"):
        assert point in listed, point
    for point in ("ATTENTION's input", "ReLU", "half-rotation",
                  "norm_topk_prob", "router_after_attention",
                  "silu_experts", "rope_on_full_layers", "no_rope",
                  "no_window"):
        assert point in smallthinker.__doc__, point
    for point in ("four stages of 13", "13 layers to 8", "BOTH ends",
                  "idle share"):
        assert point in cf["deployment"], point
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["reduced"] == REDUCED
    assert entry[0]["source"] == cf["source"] == \
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/" \
        "blob/main/config.json"
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cells = [w for w in manifest["workloads"] if w["config"] == CONFIG]
    assert CELL in [w["name"] for w in cells]
    mine = next(w for w in cells if w["name"] == CELL)
    assert mine["traffic"] == "mixed-length" and mine["chips"] == 1
    cell = run.load_cell(CELL)
    assert cell["load"] == {"clients": 32}
    assert cell["load"]["clients"] == cf["engine_args"]["max_num_seqs"]
    assert cell["why"] == mine["why"] and len(cell["why"]) <= 200
    mix = cell["mix"]
    assert mix["loop"] == "closed" and mix["shared_prefix"] is None
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 0.8, "min": 256, "max": 12288}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}


def test_the_mix_puts_both_kinds_of_request_in_one_round():
    """Thirty-two stratified prompts a round against the 4 096 window:
    most never reach it whatever their output, a few cross it while they
    decode, a third are past it from their first chunks; every context
    fits ``max_model_len``."""
    from benchmarks import traffic
    cf = config_file()
    mix = run.load_cell(CELL)["mix"]
    window = cf["sliding_window_size"]
    prompts = traffic.stratified_lengths(mix["prompt_len"], 32)
    longest_out = mix["output_len"]["max"]
    inside = int((prompts + longest_out <= window).sum())
    past = int((prompts > window).sum())
    assert (inside, 32 - inside - past, past) == (17, 3, 12)
    assert prompts.max() + longest_out == 13056 <= \
        cf["engine_args"]["max_model_len"]
    assert int(prompts.sum()) == 128022


def test_the_file_s_arithmetic_is_the_model_s():
    """3 967 M parameters = 7.93 GB, 398.6 M a layer, 2 048 B of K/V a
    token a layer in groups of two full and six window layers, and a
    window pool of 8 448 pages, as the file's ``deployment`` reckons,
    from the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_distributed_tpu.core.kv_cache_utils import \
        window_group_pool_blocks
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    from vllm_distributed_tpu.models.smallthinker import \
        SmallThinkerForCausalLM
    cf = config_file()
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    SmallThinkerForCausalLM.configure_arch(arch, hf)
    arch.quantization = None
    ea = cf["engine_args"]
    arch.window_group_pages = window_group_pool_blocks(
        arch.window_group, ea["block_size"], ea["max_num_seqs"],
        ea["max_num_batched_tokens"], ea["max_model_len"] // 16)
    arch.window_group_page_size = ea["block_size"]
    assert arch.window_group_pages == 32 * (256 + 4) + 128 == 8448
    model = SmallThinkerForCausalLM(arch)
    assert (arch.window_group, arch.window_pattern, arch.nope_layers,
            arch.rope_theta, arch.rope_scaling, arch.hidden_act) == (
                4096, (0, 4096, 4096, 4096) * 2,
                (True, False, False, False) * 2, 1500000, None, "relu")
    assert (arch.num_experts, arch.num_experts_per_tok,
            arch.moe_intermediate_size, arch.num_q_heads, arch.num_kv_heads,
            arch.head_dim, arch.tie_word_embeddings) == (
                64, 6, 768, 28, 4, 128, False)
    assert getattr(arch, "experts_held", None) is None   # all 64 are here
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6) == 3967
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(7.93, abs=0.01)
    lay = shapes["layers"]
    assert round(sum(x.size for x in jax.tree.leaves(lay)) / 8e6, 1) \
        == 398.6
    assert lay["wq"].shape == (8, 2560, 3584)
    assert lay["wk"].shape == lay["wv"].shape == (8, 2560, 512)
    assert lay["wo"].shape == (8, 3584, 2560)
    assert lay["router"].shape == (8, 2560, 64)
    assert lay["w_gate"].shape == lay["w_up"].shape == (8, 64, 2560, 768)
    assert lay["w_down"].shape == (8, 64, 768, 2560)
    assert not {"gate", "up", "down"} & set(lay)      # no dense MLP
    assert shapes["embed"].shape == (151936, 2560)
    assert shapes["lm_head"].shape == (2560, 151936)
    caches = jax.eval_shape(lambda: model.make_kv_caches(32, 16))
    assert caches["k"].shape == (2, 32, 4, 16, 128)
    assert caches["k_win"].shape == (6, 8448, 4, 16, 128)
    assert model.fixed_cache_bytes() == 8448 * 16 * 6 * 2048
    assert model.fixed_cache_bytes() / 1e9 == pytest.approx(1.66, abs=0.01)
    for text in ("20.97 M", "5.898 M", "377.5 M", "398.6 M", "777.9 M",
                 "3 967 M", "7.93 GB", "2 048 B", "8 448 pages", "1.66 GB",
                 "43.0 GB"):
        assert text in cf["deployment"], text


def test_work_modules_give_what_the_accepted_functions_give():
    """The two new modules translate the published keys and call
    ``grouped_gemm.work`` and ``windowed_paged_attention.work``: the same
    numbers, exactly, as those give on the translated keys."""
    from benchmarks.rooflines import (grouped_gemm,
                                      smallthinker_grouped_gemm,
                                      smallthinker_paged_attention,
                                      windowed_paged_attention)
    cf = config_file()
    tokens = {("vdt:step_tokens", "decode"): 32.0,
              ("vdt:step_tokens", "prefill"): 0.0}
    got = smallthinker_grouped_gemm.work(cf, _Ctx(tokens))
    mapped = {**cf, "num_experts": 64, "num_experts_per_tok": 6,
              "intermediate_size": 768}
    assert got == grouped_gemm.work(mapped, _Ctx(tokens))
    one = 3 * 2560 * 768
    # 32 rows of 6 picks reach past the 64 experts: all are charged.
    assert got["bytes"] == 8 * 64 * one * 2
    assert got["bytes"] / 1e9 == pytest.approx(6.04, abs=0.01)
    assert got["flops"] == 2.0 * 32 * 6 * one * 8
    # Bytes bound at a decode batch (7.4 ms against 0.09 ms).
    assert got["bytes"] / 819e9 > 50 * got["flops"] / 197e12
    # What an even router would hit, which the count bounds from above.
    assert 64 * (1 - (1 - 6 / 64) ** 32) == pytest.approx(61.26, abs=0.01)
    chunk = {("vdt:step_tokens", "decode"): 31.0,
             ("vdt:step_tokens", "prefill"): 2017.0}
    big = smallthinker_grouped_gemm.work(cf, _Ctx(chunk))
    assert big["flops"] == 2.0 * 2048 * 6 * one * 8
    assert big["flops"] / 197e12 < big["bytes"] / 819e9     # still bytes
    assert smallthinker_grouped_gemm.work(cf, _Ctx({})) is None

    layout = smallthinker_paged_attention.translated(cf)
    assert layout["layer_types"] == (
        ["full_attention"] + ["sliding_attention"] * 3) * 2
    assert windowed_paged_attention.layer_kinds(layout) == (2, 6)
    assert windowed_paged_attention.kv_bytes_per_token_layer(layout) == 2048
    # A decode step of 32 requests, 12 past the window at context 8 000
    # and 20 inside it at 2 000.
    full = 12 * 8000.0 + 20 * 2000.0
    window = 12 * 4096.0 + 20 * 2000.0
    counters = {("vdt:step_kv_tokens_read", None): full,
                ("vdt:step_kv_window_tokens_read", None): window,
                ("vdt:step_attn_pairs", "full"): full,
                ("vdt:step_attn_pairs", "window"): window}
    w = smallthinker_paged_attention.work(cf, _Ctx(counters))
    assert w == windowed_paged_attention.work(layout, _Ctx(counters))
    assert w["bytes"] == (2 * full + 6 * window) * 2048
    # The operations are the 28 published heads', not the padded 32's.
    assert w["flops"] == (2 * full + 6 * window) * 4 * 128 * 28
    assert w["bytes"] / 819e9 > 10 * w["flops"] / 197e12
    for missing in counters:
        assert smallthinker_paged_attention.work(
            cf, _Ctx({k: v for k, v in counters.items()
                      if k != missing})) is None
    # They read nothing of a configuration without these keys, whatever
    # its counters.
    for name in os.listdir(os.path.join(run.HERE, "configs")):
        other = run.load_json(run.HERE, "configs", name)
        if other["architectures"] != [ARCH]:
            for module in (smallthinker_grouped_gemm,
                           smallthinker_paged_attention):
                assert module.work(other, _Ctx({**tokens, **counters})) \
                    is None, name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the entries list
    the new cell, move what it reports and keep their order among
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
    assert CELL in entry["workloads"]
    if src["kind"] == "trace":
        assert entry["moves"] == "tpot_p50_ms"
        assert entry["layer"] == "kernels"
        assert entry["source"] == "device_trace" and entry["unit"] == "%"
        assert src["reduce"] == "roofline_share"
        assert (src["match"], src["work"]) == {
            "kernel.moe_reglu_gemm_roofline": (
                "ragged", "smallthinker_grouped_gemm"),
            "kernel.attn_layout_window_roofline": (
                "unified_write_attend", "smallthinker_paged_attention")}[
                    name]
        assert os.path.exists(os.path.join(
            run.HERE, "rooflines", f"{src['work']}.py"))
    else:
        # `itl_p95_ms`, not the `tpot_p50_ms` ISSUE 47 named: an accepted
        # test holds every `sched.*` metric that moves `tpot_p50_ms` to
        # Granite's cell, which has no window (PERF.md, section 7).
        assert entry["moves"] == "itl_p95_ms"
        assert entry["layer"] == "scheduler"
        assert entry["source"] == "program_counter"
        from vllm_distributed_tpu.metrics import prometheus
        assert src["metric"] == "vdt:step_requests_past_window"
        assert src["metric"] in open(prometheus.__file__).read()
        assert src["reduce"] == "hist_mean"


def test_lists_that_take_the_cell_and_lists_that_do_not():
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    takes = {n for n, m in entries.items() if CELL in m["workloads"]}
    moved = {"tpot_p50_ms", "setup_s"} | {
        n for n, m in e2e.items() if CELL in m.get("workloads", [])}
    # `output_tokens_per_s` is owed (an accepted test holds its list's
    # order: PERF.md, section 7).
    assert moved == {"tpot_p50_ms", "setup_s", "itl_p95_ms"}
    assert {"device.idle_share", "model.step_device_ms_p50",
            "kernel.kv_tokens_read_per_step", "kernel.attn_share",
            "kernel.moe_gemm_share", "engine.step_period_mean_ms",
            "runner.launch_mean_ms", "sched.running_mean", "kv.preemptions",
            "frontend.process_outputs_mean_ms",
            "frontend.output_lag_p50_ms", *NEW_METRICS} <= takes
    for name in takes:
        assert entries[name]["moves"] in moved, name
    # The lists an accepted test holds to Command A+'s cell alone, and
    # the accepted rooflines whose counts are another layout's.
    for absent in ("kernel.attn_window_roofline",
                   "kernel.kv_window_tokens_read_per_step",
                   "kv.window_pages_freed", "kv.window_pages_held",
                   "kv.full_pages_held", "kernel.moe_share_gemm_roofline",
                   "kernel.attn_roofline", "kernel.moe_gemm_roofline",
                   "kernel.mla_share", "kv.state_bytes_held",
                   "sched.waiting_mean", "sched.queue_wait_p50_ms"):
        assert absent not in takes, absent
    assert not [n for n in takes if n.endswith(".tpot")]
    # What lists Command A+'s cell lists this one, less those six.
    cohere = {n for n, m in entries.items()
              if "command-a-plus-ep8.long-document" in m["workloads"]}
    assert len(cohere - takes) == 6 and takes - cohere == set(NEW_METRICS)
    # Appended: nothing that was there before this PR stands behind the
    # cell's name.
    for m in (*manifest["per_layer"], *manifest["end_to_end"]):
        if CELL in m.get("workloads", []) and m["name"] not in NEW_METRICS:
            assert m["workloads"].index(CELL) > m["workloads"].index(
                "command-a-plus-ep8.long-document"), m["name"]


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
    path = tmp_path / "manifest-smallthinker.json"
    path.write_text(json.dumps(m))
    return str(path)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
from benchmarks.references import smallthinker
# Every control, those the chip's short prompts catch and those held in
# float32: the toy's scoring prompts are 6 to 26 windows long.
smallthinker.CONTROLS[{arch!r}] = {controls!r}
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 47, 4.0, True, data_root={data!r},
                    manifest_path={manifest!r}, require_platform="cpu",
                    ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", {cell!r}, "--seed",
                          str(2**31 + 47)], data_root={data!r})
print(json.dumps(line))
"""
EVERY_CONTROL = ["router_after_attention", "silu_experts",
                 "rope_on_full_layers", "no_rope", "no_window"]


def test_run_cell_serves_the_toy_cell_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the run ends ``correct`` with every control caught
    at three times the toy's tolerance, the new counter is read (some of
    the step's requests are past the window, not all), the trace metrics
    find no device plane on a CPU and are left out; and
    ``reference_precision`` on what the run kept."""
    assert set(smallthinker.CONTROLS[ARCH]) <= set(EVERY_CONTROL)
    manifest = tiny_manifest(tmp_path)
    wanted = {x["name"] for x in run.load_json(manifest)["per_layer"]}
    assert set(NEW_METRICS) <= wanted and len(wanted) >= 30
    env = dict(os.environ, JAX_PLATFORMS="cpu", VDT_PALLAS_INTERPRET="1",
               VDT_PRECOMPILE="1", HF_HUB_OFFLINE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, "-c", RUN_SCRIPT.format(
            repo=REPO, work=str(tmp_path), data=DATA, cell=TINY,
            manifest=manifest, arch=ARCH, controls=EVERY_CONTROL)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[len(lines) - 1])
    assert last["correct"] is True, (lines[-3:], last["compared"])
    assert last["attempted"] > 0 and last["failed"] == 0
    got = last["metrics"]
    assert {"kernel.kv_tokens_read_per_step", "sched.running_mean",
            "kv.preemptions", "runner.host_transfers_per_step",
            "sched.requests_past_window_per_step"} <= set(got), sorted(got)
    assert not {"kernel.moe_reglu_gemm_roofline",
                "kernel.attn_layout_window_roofline",
                "kernel.moe_gemm_share", "kernel.attn_share",
                "device.idle_share"} & set(got)
    assert got["kv.preemptions"]["value"] == 0
    assert got["runner.host_transfers_per_step"]["value"] == 3.0
    assert 0 < got["sched.requests_past_window_per_step"]["value"] < \
        got["sched.running_mean"]["value"] + 1
    for control in EVERY_CONTROL:
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
    with pytest.raises(ValueError, match="SmallThinkerNextForCausalLM"):
        resolve_architecture(SimpleNamespace(
            architectures=["SmallThinkerNextForCausalLM"],
            model_type=None, num_hidden_layers=8))
