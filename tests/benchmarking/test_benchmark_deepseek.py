"""What PR 28 added to the benchmark: the DeepSeek-V3 reference
(``benchmarks/references/deepseek.py``) against the program's engine at
a toy width on the CPU, the catalog numbers of
``gigachat3-702b-ep16.json``, and the two work modules' counts."""

import json
import os

import pytest

from benchmarks import run

DATA = os.path.join(os.path.dirname(__file__), "data")


def _serve_scoring(cell, args, cf, model_dir):
    from vllm_distributed_tpu import LLM, SamplingParams

    from benchmarks import traffic
    llm = LLM(model=model_dir, load_format="dummy", seed=args.seed,
              skip_tokenizer_init=True, **cf["engine_args"])
    reqs = traffic.scoring_requests(5, cf["vocab_size"])
    outs = llm.generate(
        [r["prompt"] for r in reqs],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       logprobs=1, prompt_logprobs=1))
    scoring = []
    for r, o in zip(reqs, outs):
        c = o.outputs[0]
        assert len(c.token_ids) == 8
        plp = [d[t] for t, d in zip(r["prompt"][1:], o.prompt_logprobs[1:])]
        glp = [d[t] for t, d in zip(c.token_ids, c.logprobs)]
        scoring.append({"tokens": r["prompt"] + list(c.token_ids),
                        "token_logprobs": [None] + plp + glp})
    del llm
    return scoring


def test_engine_matches_plain_deepseek_reference(tmp_path, monkeypatch):
    """Prefill in chunks of 64, then decode, through the paged latent
    cache and the absorbed form, against the reference's one full
    non-absorbed forward: share 1 of 4, v_head_dim 24 beside a no-rope
    key of 16, q_lora_rank, one dense layer, YaRN with both mscales past
    its original context."""
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell("tiny-gigachat.tiny-decode", DATA)
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, seed=2 ** 31 + 7)
    scoring = _serve_scoring(cell, args, cf, model_dir)
    got = run.compare_with_reference(cell, args, scoring)
    # float32 engine against a float32 reference: rounding only (sums in
    # another order: absorbed against not, pages against one sequence).
    assert got["positions"] == sum(len(s["tokens"]) - 1 for s in scoring)
    assert got["max_abs_logprob_dev"] < 2e-4, got
    assert got["ok"], json.dumps(got["controls"])
    from benchmarks.references import deepseek
    assert set(got["controls"]) == set(
        deepseek.CONTROLS["DeepseekV3ForCausalLM"])
    for name, c in got["controls"].items():
        assert c["caught"] and c["max"] > 100 * got["max_abs_logprob_dev"], \
            (name, c)
    # The controls a bf16 chip run cannot hold at three tolerances in
    # every run (the reference's docstring) are caught here, in float32:
    # top-8 without the group limit, the shared expert left out, the
    # router's choice replaced.
    extra = ["no_group_limit", "no_shared_expert", "first_experts"]
    monkeypatch.setitem(deepseek.CONTROLS, "DeepseekV3ForCausalLM", extra)
    more = run.compare_with_reference(cell, args, scoring)["controls"]
    assert set(more) == set(extra)
    for name, c in more.items():
        assert c["caught"] and c["max"] > 100 * got["max_abs_logprob_dev"], \
            (name, c)


RUN_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
run.WORK = {work!r}
line = run.run_cell("tiny-gigachat.tiny-decode", 2**31 + 11, 4.0, True,
                    data_root={data!r},
                    manifest_path={data!r} + "/manifest-gigachat.json",
                    require_platform="cpu", ramp_s=1.5)
from benchmarks import reference_precision
reference_precision.main(["--workload", "tiny-gigachat.tiny-decode",
                          "--seed", str(2**31 + 11)], data_root={data!r})
print(json.dumps(line))
"""


def test_run_cell_serves_the_share_end_to_end_on_cpu(tmp_path):
    """The harness's whole order on the toy share (server, load
    generator, scoring, reference, controls, per-layer readers), with
    every per-layer entry that BENCHMARK.json gives the new cell: the
    counters are read, and the three trace metrics find no device plane
    on a CPU and are left out, as on a program that lacks them."""
    import subprocess
    import sys
    repo = os.path.normpath(os.path.join(DATA, "..", "..", ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu", VDT_PALLAS_INTERPRET="1",
               VDT_PRECOMPILE="1", HF_HUB_OFFLINE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, "-c", RUN_SCRIPT.format(
            repo=repo, work=str(tmp_path), data=DATA)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1])
    assert last["correct"] is True, (lines[-3:], last["compared"])
    assert last["attempted"] > 0 and last["failed"] == 0
    got = set(last["metrics"])
    assert {"kernel.kv_tokens_read_per_step", "sched.running_mean",
            "runner.host_transfers_per_step", "kv.preemptions",
            "frontend.output_lag_p50_ms"} <= got, got
    assert not {"kernel.mla_share", "kernel.mla_roofline",
                "kernel.moe_local_gemm_roofline",
                "kernel.attn_share"} & got
    assert last["metrics"]["runner.host_transfers_per_step"]["value"] == 3.0
    for control in ("no_rope_key", "no_yarn"):
        assert any(c.startswith(f"compared control.{control}.max")
                   for c in last["compared"]), control
    # benchmarks.reference_precision on what the run kept: the float32
    # reading is the run's own, the bfloat16 one far outside the limits.
    prec = json.loads(lines[-2])
    assert prec["float32_highest"]["max"] < 2e-4 < 0.002 < \
        prec["bfloat16_default"]["max"] < \
        prec["float8_e4m3_rounded"]["max"], prec


CATALOG = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 192, "qk_nope_head_dim": 128,
    "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4,
    "num_experts_per_tok": 8, "moe_layer_freq": 1,
    "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "deepseek_v3"}


def gigachat():
    return run.load_json(run.HERE, "configs", "gigachat3-702b-ep16.json")


def test_catalog_numbers_are_kept_or_listed_as_reduced():
    """The file holds every number of the catalog row's config
    (GigaChat3.1-702B-A36B, the model-configs guide) but for the five
    keys in `reduced`, none of them a width; and it states the published
    counts, the deployment and what was assumed."""
    cf = gigachat()
    differing = sorted(k for k, v in CATALOG.items()
                       if cf.get(k, "absent") != v)
    assert differing == sorted(cf["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"])
    assert not [k for k in cf["reduced"] if k.endswith(("_dim", "_rank",
                                                       "_size"))
                and k != "vocab_size"]
    assert cf["published"] == {k: CATALOG[k] for k in cf["reduced"]}
    share = cf["expert_share"]
    assert share == {"published_experts": CATALOG["n_routed_experts"],
                     "chips": 16, "index": 0}
    assert cf["n_routed_experts"] * share["chips"] == \
        share["published_experts"]
    # The floors of a `model_config` PR: a whole period and four layers
    # past the dense ones, 8 experts, an eighth of the vocabulary.
    assert cf["first_k_dense_replace"] >= 1
    assert cf["num_hidden_layers"] - cf["first_k_dense_replace"] >= 4
    assert cf["n_routed_experts"] >= 8
    assert cf["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert "16" in cf["deployment"] and len(cf["assumed"]) == 2
    manifest = run.load_json(run.MANIFEST)
    entry = [c for c in manifest["configs"]
             if c["name"] == "gigachat3-702b-ep16"][0]
    assert entry["reduced"] == cf["reduced"]
    assert entry["source"] == cf["source"]


def test_the_file_s_arithmetic_is_the_model_s(monkeypatch):
    """5 174 M parameters = 10.35 GB, as the file's `deployment` reckons,
    from the shapes the program would allocate."""
    import jax
    import jax.numpy as jnp
    from transformers import DeepseekV3Config

    from vllm_distributed_tpu.models.deepseek import DeepseekV3ForCausalLM
    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    hf = DeepseekV3Config(**{k: v for k, v in run.hf_config(
        gigachat()).items() if k not in ("architectures", "model_type")})
    arch = LlamaArchConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    DeepseekV3ForCausalLM.configure_arch(arch, hf)
    arch.expert_parallel, arch.quantization = False, None
    model = DeepseekV3ForCausalLM(arch)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    n = sum(x.size for x in leaves)
    assert round(n / 1e6) == 5174
    assert sum(x.size * x.dtype.itemsize for x in leaves) / 1e9 == \
        pytest.approx(10.35, abs=0.005)
    lay = shapes["layers"]
    assert lay["router"].shape == (5, 7168, 256)
    assert [w.shape for w in lay["w_gate"]] == [(16, 7168, 2048)] * 5
    # 576 lanes as they are, 640 as the TPU stores them (whole 128-lane
    # tiles), x 2 B x 6 layers a token, 16 tokens a page.
    assert model.kv_cache_page_bytes(16) == 16 * 6 * 576 * 2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert model.kv_cache_page_bytes(16) == 16 * 7680


class _Ctx:
    def __init__(self, means):
        self.means = means

    def step_mean(self, name, labels=None):
        return self.means.get((name, (labels or {}).get("kind")))


def test_work_modules_count_what_their_docstrings_say():
    from benchmarks.rooflines import latent_attention, local_grouped_gemm
    cf = gigachat()
    assert latent_attention.latent_bytes_per_token(cf) == 7680
    ctx = _Ctx({("vdt:step_kv_tokens_read", None): 130000.0,
                ("vdt:step_tokens", "decode"): 47.0,
                ("vdt:step_tokens", "prefill"): 1.0})
    w = latent_attention.work(cf, ctx)
    assert w["bytes"] == 130000 * 7680
    assert w["flops"] == 130000 * 6 * 2 * (576 + 512) * 64
    # Bytes bound at one query a request: 109 operations a byte.
    assert w["flops"] / w["bytes"] == pytest.approx(108.8)
    g = local_grouped_gemm.work(cf, ctx)
    picks = 48 * 8 * 16 / 256
    one = 3 * 7168 * 2048
    assert g["flops"] == 2 * picks * one * 5
    hit = 16 * (1 - (15 / 16) ** picks)
    assert 12 < hit < 13 and g["bytes"] == pytest.approx(5 * hit * one * 2)
    # Nothing to read: another block form, a missing counter.
    olmoe = run.load_json(run.HERE, "configs", "olmoe-1b-7b.json")
    assert latent_attention.work(olmoe, ctx) is None
    assert local_grouped_gemm.work(olmoe, ctx) is None
    assert latent_attention.work(cf, _Ctx({})) is None
    assert local_grouped_gemm.work(cf, _Ctx({})) is None


def test_the_latent_kernel_has_a_name_of_its_own():
    """`kernel.attn_share` matches `attend|attention`; the latent
    kernel's custom call must not, and its own readers must find it."""
    import re

    from benchmarks import layers
    from vllm_distributed_tpu.ops.pallas_mla import KERNEL_NAME
    attn = layers.load_spec("kernel.attn_share")["source"]["match"]
    assert not re.search(attn, KERNEL_NAME)
    for name in ("kernel.mla_share", "kernel.mla_roofline"):
        assert re.search(layers.load_spec(name)["source"]["match"],
                         KERNEL_NAME)
    manifest = run.load_json(run.MANIFEST)
    cell = "gigachat3-702b-ep16.long-context"
    lists = {m["name"]: m["workloads"] for m in manifest["per_layer"]}
    for name in ("kernel.attn_share", "kernel.attn_roofline",
                 "kernel.moe_gemm_roofline"):
        assert cell not in lists[name]
    for name in ("kernel.mla_share", "kernel.mla_roofline",
                 "kernel.moe_local_gemm_roofline"):
        assert lists[name] == [cell]
    assert cell in lists["kernel.moe_gemm_share"]
