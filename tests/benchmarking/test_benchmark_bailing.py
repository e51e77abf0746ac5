"""What PR 33 added to the benchmark: the Ling-3.0 reference
(``benchmarks/references/bailing.py``) against the program's engine at a
toy width on the CPU through the harness's own comparison, the whole
order of a run on the toy cell under every per-layer entry that
``BENCHMARK.json`` gives the new cell, the catalog numbers and the
arithmetic of ``ling3-flash-ep4.json``, the work module's counts and the
reader files."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import layers, run
from benchmarks.references import bailing
from test_benchmark_deepseek import _serve_scoring

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
CELL = "ling3-flash-ep4.long-context"
TINY = "tiny-ling.tiny-decode"
NEW_METRICS = ("kernel.kda_share", "kernel.kda_roofline",
               "kv.state_bytes_held")
ARCH = "BailingMoeV3ForCausalLM"
ALL_CONTROLS = ("no_delta", "no_decay", "no_conv", "no_head_gate",
                "first_experts")


def ling():
    return run.load_json(run.HERE, "configs", "ling3-flash-ep4.json")


def test_engine_matches_plain_bailing_reference(tmp_path, monkeypatch):
    """Prefill in chunks of 64, then decode, through state rows and
    latent pages, against the reference's token-by-token scan and dense
    attention, by the comparison that decides `correct`: the two
    controls every chip run must catch, and the three the float32 tests
    hold."""
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell(TINY, DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, seed=2 ** 31 + 7)
    scoring = _serve_scoring(cell, args, cf, model_dir)
    got = run.compare_with_reference(cell, args, scoring)
    assert got["positions"] == sum(len(s["tokens"]) - 1 for s in scoring)
    assert got["max_abs_logprob_dev"] < 2e-4, got
    assert got["ok"], json.dumps(got["controls"])
    on_chip = bailing.CONTROLS[ARCH]
    assert set(got["controls"]) == set(on_chip) and len(on_chip) == 2
    assert set(on_chip) < set(ALL_CONTROLS)
    extra = [c for c in ALL_CONTROLS if c not in on_chip]
    monkeypatch.setitem(bailing.CONTROLS, ARCH, extra)
    more = run.compare_with_reference(cell, args, scoring)["controls"]
    for name, c in {**got["controls"], **more}.items():
        assert c["caught"] and c["max"] > 100 * got["max_abs_logprob_dev"], \
            (name, c)


def tiny_manifest(tmp_path) -> str:
    """A copy of BENCHMARK.json with the toy cell in the new cell's
    place: the same end-to-end and per-layer entries, whatever the
    manifest lists for ``ling3-flash-ep4.long-context``."""
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
    path = tmp_path / "manifest-ling.json"
    path.write_text(json.dumps(m))
    return str(path)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 13, 4.0, True, data_root={data!r},
                    manifest_path={manifest!r}, require_platform="cpu",
                    ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", {cell!r}, "--seed",
                          str(2**31 + 13)], data_root={data!r})
print(json.dumps(line))
"""


def test_run_cell_serves_the_hybrid_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy cell with the new cell's own
    manifest entries: the counters and the new gauge are read, the trace
    metrics find no device plane on a CPU and are left out, the two
    controls stand beside their limits; and `reference_precision` on
    what the run kept."""
    manifest = tiny_manifest(tmp_path)
    wanted = {x["name"] for x in run.load_json(manifest)["per_layer"]}
    assert set(NEW_METRICS) <= wanted and len(wanted) >= 25
    assert not {"kernel.mla_roofline", "kernel.moe_local_gemm_roofline",
                "kernel.attn_share"} & wanted
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
    assert {"kv.state_bytes_held", "kernel.kv_tokens_read_per_step",
            "sched.running_mean", "runner.host_transfers_per_step",
            "kv.preemptions", "frontend.output_lag_p50_ms"} <= got, got
    assert not {"kernel.kda_share", "kernel.kda_roofline",
                "kernel.moe_gemm_share", "device.idle_share"} & got
    assert last["metrics"]["runner.host_transfers_per_step"]["value"] == 3.0
    for control in bailing.CONTROLS[ARCH]:
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    prec = json.loads(lines[-2])
    assert prec["float32_highest"]["max"] < 2e-4 < 0.002 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


# The catalog row's config (Ling-3.0-flash-VL, the model-configs guide),
# but for the two limit lists, which the file copies whole.
CATALOG = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560,
    "intermediate_size": 6144, "first_k_dense_replace": 2,
    "max_position_embeddings": 131072, "moe_intermediate_size": 768,
    "num_experts_per_tok": 8, "num_attention_heads": 32,
    "q_lora_rank": None, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 512,
    "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False,
    "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True}
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """The file holds every number of the catalog row's config but for
    the four keys in `reduced`, none of them a width; it states the
    published counts, the deployment and what was assumed; and the
    manifest's entry says the same."""
    cf = ling()
    differing = [k for k, v in CATALOG.items() if cf.get(k, "absent") != v]
    assert differing == cf["reduced"] == REDUCED
    assert cf["published"] == {k: CATALOG[k] for k in REDUCED}
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        assert len(cf[key]) == 42 and not any(cf[key][:13])
    assert cf["expert_swiglu_limit_list"][35:] == [4] * 7
    share = cf["expert_share"]
    assert share == {"published_experts": 512, "chips": 4, "index": 0}
    assert cf["num_experts"] * share["chips"] == share["published_experts"]
    # The floors of a `model_config` PR: a whole period and four layers
    # past the dense one, 8 experts, an eighth of the vocabulary.
    sparse = cf["num_hidden_layers"] - cf["first_k_dense_replace"]
    assert sparse >= max(4, cf["layer_group_size"])
    assert cf["num_experts"] >= 8
    assert cf["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert cf["vocab_size"] <= min(CATALOG[k] for k in CATALOG
                                   if k.endswith("_token"))
    assert len(cf["assumed"]) >= 8 and all(
        any(a.startswith(f"({x})") for a in cf["assumed"]) for x in "abcd")
    assert cf["engine_args"]["max_num_seqs"] == 96
    assert set(cf["tolerance"]) == {"max", "mean"}
    assert len(cf["tolerance_reason"]) > 200
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"] if c["name"] == "ling3-flash-ep4"]
    assert entry == manifest["configs"][-1:]           # appended
    assert entry[0]["reduced"] == cf["reduced"]
    assert entry[0]["source"] == cf["source"]
    assert manifest["workloads"][-1]["name"] == CELL
    assert run.load_cell(CELL)["load"] == {"clients": 96}


def test_the_file_s_arithmetic_is_the_model_s(monkeypatch):
    """5 169 M parameters = 10.34 GB, 13 025 280 B of state a sequence
    and 1 280 B of latent rows a token, as the file's `deployment`
    reckons, from the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import PretrainedConfig

    from vllm_distributed_tpu.models.bailing import BailingMoeV3ForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    cf = ling()
    hf = PretrainedConfig(**run.hf_config(cf))
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    BailingMoeV3ForCausalLM.configure_arch(arch, hf)
    arch.expert_parallel, arch.quantization = False, None
    arch.state_slots = cf["engine_args"]["max_num_seqs"]
    model = BailingMoeV3ForCausalLM(arch)
    assert (model._attn_layers, model._n_dense) == ([5], 1)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert round(sum(x.size for x in leaves) / 1e6, 1) == 5169.4
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(10.339, abs=0.001)
    lay = shapes["layers"]
    assert lay["router"].shape == (6, 2560, 512)
    assert [w.shape for w in lay["w_gate"]] == [(128, 2560, 768)] * 6
    assert lay["k_wqkv"].shape == (6, 2560, 3 * 4096)
    assert lay["wq"].shape == (1, 2560, 32 * 192)
    assert model.state_row_bytes() == 13_025_280
    assert model.fixed_cache_bytes() == 97 * 13_025_280
    for text in ("5 169.4 M", "10.339 GB", "13 025 280 B", "1 280 B"):
        assert text in cf["deployment"], text
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.kv_cache_page_bytes(16) == 16 * 1280


class _Ctx:
    def __init__(self, means):
        self.means = means

    def step_mean(self, name, labels=None):
        return self.means.get((name, (labels or {}).get("kind")))


def test_work_module_counts_what_its_docstring_says():
    from benchmarks.rooflines import gated_delta_state as work
    cf = ling()
    assert work.kda_layers(cf) == 6
    ctx = _Ctx({("vdt:step_tokens", "decode"): 90.0,
                ("vdt:step_tokens", "prefill"): 300.0})
    w = work.work(cf, ctx)
    state = 32 * 128 * 128 * 4                       # 2 MiB a layer
    assert state == 2 ** 21
    rows = 5 * 32 * 128 * 4
    assert w["bytes"] == 6 * (2 * 90 * state + 390 * rows)
    assert w["flops"] == 390 * 6 * 32 * 7 * 128 * 128
    # Under one operation a byte: the bytes win by two orders.
    assert w["flops"] / 197e12 < 0.02 * w["bytes"] / 819e9
    # A full decode batch alone: 2.46 GB, 3.0 ms at the memory's peak.
    full = work.work(cf, _Ctx({("vdt:step_tokens", "decode"): 96.0,
                               ("vdt:step_tokens", "prefill"): 0.0}))
    assert full["bytes"] / 819e9 == pytest.approx(3.007e-3, rel=0.001)
    for other in ("olmoe-1b-7b", "gigachat3-702b-ep16"):
        assert work.work(run.load_json(run.HERE, "configs",
                                       f"{other}.json"), ctx) is None
    assert work.work(cf, _Ctx({})) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_files_and_their_manifest_entries(name):
    """A reader file says how to read and nothing else; the kernels'
    readers match both kernels' names and no other reader does; the
    entries are appended, list the new cell alone and move what it
    reports."""
    from vllm_distributed_tpu.ops import kda
    spec = layers.load_spec(name)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    src = spec["source"]
    assert src["kind"] in layers.READERS
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-3:]] == \
        list(NEW_METRICS)
    assert entries[name]["workloads"] == [CELL]
    assert entries[name]["moves"] == "tpot_p50_ms"
    if src["kind"] == "trace":
        for kernel in (kda.KERNEL_RECURRENT, kda.KERNEL_CHUNK):
            assert re.search(src["match"], kernel)
            for other in ("kernel.attn_share", "kernel.mla_share",
                          "kernel.moe_gemm_share"):
                assert not re.search(
                    layers.load_spec(other)["source"]["match"], kernel)
        assert not re.search(src["match"], "mla_latent_pages")
    else:
        assert (src["metric"], src["reduce"]) == (
            "vdt:ssm_state_bytes_held", "gauge_mean")
    # The two rooflines whose work modules cannot read this
    # configuration (PERF.md, Open questions) do not list the cell.
    for absent in ("kernel.mla_roofline", "kernel.moe_local_gemm_roofline",
                   "kernel.attn_share", "kernel.attn_roofline"):
        assert CELL not in entries[absent]["workloads"]
    assert CELL in entries["kernel.moe_gemm_share"]["workloads"]
    # `test_benchmark_deepseek.py` holds `kernel.mla_share` to the GigaChat
    # cell alone, and that file is the benchmark's (PERF.md, section 7).
    assert CELL not in entries["kernel.mla_share"]["workloads"]
    assert CELL in entries["kernel.kv_tokens_read_per_step"]["workloads"]
