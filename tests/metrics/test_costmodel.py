"""Analytic cost model (metrics/costmodel.py): hand-computed FLOP and
HBM-byte counts for the layouts the attribution plane must price —
dense and GQA llama blocks, a DeepSeek MLA layer under TPLA TP=2 (the
per-rank latent slice is read once per rank, the score psum is counted
ONCE) and an SSM (Mamba) scan — plus the roofline classifier and the
per-chip peak tables."""

import types

import pytest

from vllm_distributed_tpu.metrics.costmodel import (
    HOST_PEAK_FLOPS, HOST_PEAK_HBM, PEAK_FLOPS_PER_CHIP, CostModel,
    classify_roofline, peak_flops_per_chip, peak_hbm_per_chip)


def _arch(**kw):
    a = types.SimpleNamespace(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_layers=2, num_q_heads=4, num_kv_heads=4, head_dim=16,
        dtype="float32", mlp_gated=True)
    for k, v in kw.items():
        setattr(a, k, v)
    return a


# Shared toy dims: H=64, I=128, L=2, V=128, 4 q heads x 16.
H, I, L, V = 64, 128, 2, 128


def test_dense_llama_hand_count():
    """kvh == qh: per-layer proj = QKV (2*H*3*Dq) + O (2*Dq*H), MLP =
    3 gated mats of [H, I]; attention pair = 4 FLOPs per (q head,
    lane)."""
    cm = CostModel.from_arch(_arch(), kv_row_bytes=512.0)
    Dq = 4 * 16  # == H
    per_layer = 2 * H * (Dq + 2 * Dq) + 2 * Dq * H + 3 * 2 * H * I
    assert cm.linear_flops_per_token == L * per_layer
    assert cm.attn_flops_per_token_kv == L * 4 * 4 * 16
    assert cm.lm_head_flops_per_row == 2 * H * V
    # fp32 weights: per-layer mats + 2 norms, + LM head; embed rows
    # ride act_bytes (gather, not a stream).
    w = (L * (H * 3 * Dq + Dq * H + 3 * H * I) + V * H) * 4 \
        + 2 * L * H * 4
    assert cm.dense_weight_bytes == w
    # One decode token at context 9: 10 attended positions.
    c = cm.wave_cost(1, 10.0, 1)
    assert c.flops == (cm.linear_flops_per_token +
                       10 * cm.attn_flops_per_token_kv +
                       cm.lm_head_flops_per_row)
    assert c.kv_read_bytes == 10 * 512.0
    assert c.kv_write_bytes == 512.0
    assert c.act_bytes == (4 * L * H + H) * 4 + V * 4


def test_gqa_hand_count():
    """2 KV heads against 4 q heads: the QKV stream shrinks, the
    attention pair count (per q head) does not."""
    cm = CostModel.from_arch(_arch(num_kv_heads=2),
                             kv_row_bytes=256.0)
    Dq, Dkv = 64, 32
    per_layer = 2 * H * (Dq + 2 * Dkv) + 2 * Dq * H + 3 * 2 * H * I
    assert cm.linear_flops_per_token == L * per_layer
    assert cm.attn_flops_per_token_kv == L * 4 * 4 * 16  # q heads


def test_prefill_wave_composition():
    """A causal prefill chunk of n tokens at context c attends
    n*c + n(n+1)/2 pairs; weights stream once regardless of width."""
    cm = CostModel.from_arch(_arch(), kv_row_bytes=512.0)
    n, ctx = 8, 4
    pairs = n * ctx + n * (n + 1) / 2
    c = cm.wave_cost(n, pairs, 2)
    assert c.flops == (n * cm.linear_flops_per_token +
                       pairs * cm.attn_flops_per_token_kv +
                       2 * cm.lm_head_flops_per_row)
    assert c.weight_bytes == cm.dense_weight_bytes
    wide = cm.wave_cost(4 * n, pairs, 2)
    assert wide.weight_bytes == c.weight_bytes


def test_multi_pass_burst_streams_weights_per_pass():
    cm = CostModel.from_arch(_arch(), kv_row_bytes=512.0)
    c = cm.wave_cost(8, 80.0, 8, passes=4)
    assert c.weight_bytes == 4 * cm.dense_weight_bytes


def test_mla_tpla_hand_count():
    """DeepSeek MLA geometry (no q_lora): Lkv=64, rope 8, nope 16,
    v 16, 4 heads. Attention pair = scores over the latent (psum
    counted ONCE — per-rank slices are disjoint) + rope scores + PV
    over the latent; INDEPENDENT of the TPLA shard count. Per-rank KV
    row bytes: each rank reads its Lkv/TP slice plus its OWN rope
    sidecar copy, so TP=2 total row bytes exceed the replicated row by
    one extra rope sidecar."""
    Lkv, dr, dn, dv, N = 64, 8, 16, 16, 4
    base = dict(mla=True, kv_lora_rank=Lkv, qk_rope_head_dim=dr,
                qk_nope_head_dim=dn, v_head_dim=dv, q_lora_rank=None,
                num_q_heads=N, num_layers=3)
    # CPU storage: no 128-lane padding, float32.
    row_repl = 3 * (Lkv + dr) * 4.0
    row_tpla = 2 * (3 * (Lkv // 2 + dr) * 4.0)  # 2 ranks' slices+rope
    cm1 = CostModel.from_arch(_arch(**base, tpla_shards=1),
                              kv_row_bytes=row_repl)
    cm2 = CostModel.from_arch(_arch(**base, tpla_shards=2),
                              kv_row_bytes=row_tpla)
    pair = 2 * N * (Lkv + dr) + 2 * N * Lkv
    assert cm1.attn_flops_per_token_kv == 3 * pair
    # Exactness of TPLA: useful attention FLOPs identical to the
    # replicated layout — the psum reassembles full scores, counted
    # once, never per rank.
    assert cm2.attn_flops_per_token_kv == cm1.attn_flops_per_token_kv
    assert cm2.linear_flops_per_token == cm1.linear_flops_per_token
    # Projections, hand-counted per layer: q + kv-down + absorbed
    # q*W_UK + out*W_UV + o-proj.
    attn_proj = (2 * H * N * (dn + dr) + 2 * H * (Lkv + dr)
                 + 2 * N * dn * Lkv + 2 * N * Lkv * dv
                 + 2 * N * dv * H)
    mlp = 3 * 2 * H * I
    assert cm1.linear_flops_per_token == 3 * (attn_proj + mlp)
    # The TPLA layout's real HBM trade: +1 rope sidecar per extra rank.
    assert cm2.kv_row_read_bytes - cm1.kv_row_read_bytes == \
        pytest.approx(3 * dr * 4.0)


def test_mla_via_real_deepseek_model():
    """from_model prices the real DeepseekModel page layout: per-rank
    page bytes x shard count, matching the model's own accounting."""
    pytest.importorskip("transformers")
    from transformers import DeepseekV2Config

    from vllm_distributed_tpu.models.llama import LlamaArchConfig
    from vllm_distributed_tpu.models.registry import resolve_architecture
    hf = DeepseekV2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
        kv_lora_rank=64, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=4, num_experts_per_tok=2,
        n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=1.0, topk_method="greedy", n_group=1,
        topk_group=1, norm_topk_prob=False, max_position_embeddings=64,
        eos_token_id=1, head_dim=8,
        architectures=["DeepseekV2ForCausalLM"])
    model_cls = resolve_architecture(hf)
    import jax.numpy as jnp
    rows = {}
    for shards in (1, 2):
        arch = LlamaArchConfig.from_hf_config(
            model_cls.arch_config_source(hf), dtype=jnp.float32)
        model_cls.configure_arch(arch, hf)
        arch.tpla_shards = shards
        model = model_cls(arch)
        config = types.SimpleNamespace(
            cache_config=types.SimpleNamespace(block_size=4))
        cm = CostModel.from_model(model, config)
        rows[shards] = cm.kv_row_read_bytes
        assert cm.kv_row_read_bytes == pytest.approx(
            model.kv_cache_page_bytes(4) / 4 * shards)
        assert cm.moe_layers == 2 and cm.num_experts == 4
    # TP=2 aggregate row costs one extra replicated rope sidecar.
    assert rows[2] > rows[1]


def test_ssm_scan_hand_count():
    """Pure Mamba: no FFN, no paged KV; per-layer cost = in_proj +
    conv + x_proj + dt_proj + scan + out_proj; state traffic =
    (Di*N + Di*(K-1)) fp32 read+write per token per layer."""
    Di, N, K, R = 128, 16, 4, 4
    cm = CostModel.from_arch(
        _arch(stateful=True, d_inner=Di, ssm_state_size=N,
              conv_kernel=K, dt_rank=R, intermediate_size=Di),
        kv_row_bytes=0.0)
    per_layer = (2 * H * 2 * Di + 2 * Di * K + 2 * Di * (R + 2 * N)
                 + 2 * R * Di + 6 * Di * N + 2 * Di * H)
    assert cm.linear_flops_per_token == L * per_layer
    assert cm.attn_flops_per_token_kv == 0
    state = L * (Di * N + Di * (K - 1)) * 4.0
    assert cm.state_read_bytes_per_token == state
    c = cm.wave_cost(3, 0.0, 3)
    assert c.kv_read_bytes == 3 * state
    assert c.kv_write_bytes == 3 * state


def test_sliding_window_clamps_span():
    cm = CostModel.from_arch(_arch(sliding_window=32),
                             kv_row_bytes=512.0)
    assert cm.attn_window == 32
    assert cm.clamp_span(10) == 10
    assert cm.clamp_span(1000) == 32
    # Closed-form span_sum == the per-token reference, across the
    # regimes: all-under-window, straddling, all-saturated.
    for ctx, n in ((0, 8), (20, 30), (100, 16), (31, 1), (32, 1)):
        ref = sum(cm.clamp_span(ctx + j) for j in range(1, n + 1))
        assert cm.span_sum(ctx, n) == pytest.approx(ref), (ctx, n)
    full = CostModel.from_arch(_arch(), kv_row_bytes=512.0)
    assert full.span_sum(10, 4) == 4 * 10 + 4 * 5 / 2
    # Uniform window pattern resolves; mixed pattern does not.
    cm2 = CostModel.from_arch(_arch(window_pattern=(16, 16)),
                              kv_row_bytes=512.0)
    assert cm2.attn_window == 16
    cm3 = CostModel.from_arch(_arch(window_pattern=(16, 0)),
                              kv_row_bytes=512.0)
    assert cm3.attn_window is None


def test_peak_tables_and_aliases():
    assert peak_flops_per_chip("TPU v5 lite") == \
        PEAK_FLOPS_PER_CHIP["v5e"]
    assert peak_flops_per_chip("TPU v4") == PEAK_FLOPS_PER_CHIP["v4"]
    assert peak_hbm_per_chip("TPU v5p") == 2765e9
    assert peak_flops_per_chip("cpu") == HOST_PEAK_FLOPS
    assert peak_hbm_per_chip("") == HOST_PEAK_HBM


@pytest.mark.parametrize("lookup", [peak_flops_per_chip, peak_hbm_per_chip])
def test_unknown_accelerator_kind_raises(lookup):
    with pytest.raises(ValueError, match="no published peaks"):
        lookup("TPU v9 mega")


def test_mesh_scales_peaks():
    cm = CostModel.from_arch(_arch(), kv_row_bytes=512.0,
                             num_chips=4, device_kind="TPU v4")
    assert cm.peak_flops == 4 * PEAK_FLOPS_PER_CHIP["v4"]


def test_classify_roofline():
    peaks = {"flops": 100.0, "hbm": 100.0}
    # Device busy, FLOP fraction dominates -> compute.
    assert classify_roofline(
        {"device_seconds": 1.0, "host_seconds": 0.1, "flops": 80.0,
         "bytes": 10.0}, peaks) == "compute"
    # Byte fraction dominates -> bandwidth.
    assert classify_roofline(
        {"device_seconds": 1.0, "host_seconds": 0.1, "flops": 10.0,
         "bytes": 80.0}, peaks) == "bandwidth"
    # Host time above device time -> host-bound regardless of rates.
    assert classify_roofline(
        {"device_seconds": 0.1, "host_seconds": 1.0, "flops": 9.0,
         "bytes": 1.0}, peaks) == "host"
    assert classify_roofline({"device_seconds": 0.0}, peaks) == "host"


def test_mfu_mbu_helpers():
    cm = CostModel.from_arch(_arch(), kv_row_bytes=512.0)
    assert cm.mfu(cm.peak_flops * 2.0, 2.0) == pytest.approx(1.0)
    assert cm.mbu(cm.peak_hbm * 0.5, 1.0) == pytest.approx(0.5)
    assert cm.mfu(1e9, 0.0) == 0.0
    # decode_flops_per_token credits attention at the given context.
    assert cm.decode_flops_per_token(99) == (
        cm.linear_flops_per_token +
        100 * cm.attn_flops_per_token_kv + cm.lm_head_flops_per_row)
