"""Llama-family decoder in functional JAX (covers Llama 2/3, Mistral,
Qwen2 — any HF ``LlamaForCausalLM``-shaped config, incl. attention bias).

TPU-first equivalent of the reference's vllm/model_executor/models/llama.py
(which composes ColumnParallelLinear/RowParallelLinear with explicit NCCL
allreduce): here weights are one pytree with ``PartitionSpec`` annotations;
``jit`` + GSPMD insert the TP collectives over ICI. Layers execute under
``lax.scan`` over a stacked [L, ...] parameter tree, which keeps compile
time O(1) in depth — the TPU answer to the reference's CUDA-graph capture
per shape.

Weight layout mirrors HF checkpoint tensors transposed to right-multiply
form (x @ W), stacked on a leading layer axis.

**Two layouts of one tree.** The loader's tree (``get_model``, the
state-dict mappers, quantization, EPLB, pipeline slicing) is the
canonical one: every per-layer tensor stacked ``[L, ...]``. What the
runner hands the jitted programs is ``serving_layout`` of it: the same
tree for every dense model, and for the Mixtral family the expert
weights as tuples of per-layer buffers (models/mixtral.py says why).
``run_layers`` scans whichever it is given. Tuple leaves stay outside
the scanned tree, as constants of the scan, and the one consumer that
wants them, the MLP block, runs under a ``lax.switch`` on the layer
index with one branch a layer, each reading its own layer's buffers
(PERF.md, Findings PR 28 and PR 29).
"""

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (AttentionBatch,
                                                compute_rope_cos_sin,
                                                rms_norm)
from vllm_distributed_tpu.ops.attention import write_kv_and_attend

MODEL_AXIS = "model"
TOKEN_AXIS = "token"


def _replicate_kv_heads(w: jax.Array, num_kv_heads: int,
                        replicas: int) -> jax.Array:
    """Repeat each KV head's slice of the last axis ``replicas`` times.

    Per-head repetition (not tiling) keeps the q-head→kv-head map of GQA
    intact: q heads grouped onto checkpoint head h land on one of h's
    replicas, so attention output is bit-identical to the un-replicated
    model."""
    if replicas == 1:
        return w
    *lead, dkv = w.shape
    head_dim = dkv // num_kv_heads
    w = w.reshape(*lead, num_kv_heads, head_dim)
    w = jnp.repeat(w, replicas, axis=-2)
    return w.reshape(*lead, num_kv_heads * replicas * head_dim)


@dataclass
class LlamaArchConfig:
    """Subset of the HF config the forward pass needs (static)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_q_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style qkv bias
    # Mixture-of-experts (Mixtral-style); 0 experts = dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Renormalize top-k routing weights (Mixtral yes, Qwen2-MoE no —
    # reference: the renormalize flag of FusedMoE).
    norm_topk_prob: bool = True
    # Per-expert FFN width when it differs from intermediate_size
    # (Qwen2-MoE moe_intermediate_size); None = intermediate_size.
    moe_intermediate_size: Optional[int] = None
    # Qwen2-MoE shared expert: a dense SwiGLU of this width runs for
    # every token, sigmoid-gated, added to the routed output. 0 = none.
    shared_expert_intermediate_size: int = 0
    # Physical expert slots for EPLB (reference: distributed/eplb/):
    # 0 means = num_experts (no redundancy). Extra slots host replicas
    # of hot experts; the router maps logical -> physical through a
    # param-tree buffer so rebalances never recompile.
    num_physical_experts: int = 0
    # Shard experts over the "model" mesh axis (EP spans the TP group,
    # reference: parallel_state.py:1189-1204) instead of TP inside each
    # expert's FFN.
    expert_parallel: bool = False
    # Rank count of the expert-sharding axis (loader sets = tp under
    # expert_parallel); EPLB packs replicas rank-aware with it.
    expert_parallel_ranks: int = 1
    # KV-head replication factor for tp > num_kv_heads (reference:
    # QKVParallelLinear kv-head replication in
    # vllm/model_executor/layers/linear.py — each rank holds one whole
    # KV head when TP exceeds the head count). Each checkpoint KV head's
    # weights and cache rows are repeated this many times so the kv-head
    # dimension divides the model mesh axis; repeat-per-head preserves
    # GQA grouping exactly.
    num_kv_head_replicas: int = 1
    # Weight quantization scheme (None | "int8" | "fp8"); see
    # quantize_params.
    quantization: Optional[str] = None
    # int4g group width along the input dim; set from the checkpoint's
    # quantization_config.group_size so a GPTQ/AWQ re-quantization
    # reuses the original group lattice (lossless).
    quant_group_size: int = 128
    # M-RoPE (Qwen2-VL): per-frequency (temporal, height, width) section
    # widths over the half head dim; None = plain rope (reference:
    # rope_scaling.mrope_section of qwen2_vl.py).
    mrope_section: Optional[tuple] = None
    # Per-layer NoPE mask (True = this layer skips rotary): SmolLM3's
    # no_rope_layers, and the hybrid families whose FULL-attention
    # layers are position-free while sliding layers rope (Cohere2,
    # EXAONE-4). None = rotary everywhere.
    nope_layers: Optional[tuple] = None
    # Mixed MoE layouts (ERNIE-4.5 / GLM-4-MoE): this many leading
    # layers are PLAIN dense decoder blocks in their own stacked
    # subtree (models/moe_mixed.py); 0 = uniform stack.
    dense_prefix: int = 0
    # ERNIE routing-weight normalizer clamp (moe_norm_min).
    moe_norm_min: float = 1e-12
    # Multi-LoRA slots (0 disables; see models/lora.py).
    max_loras: int = 0
    max_lora_rank: int = 16
    # Sliding-window attention size (Mistral-style); None = full
    # causal. Compute-level only: pages outside the window stay
    # allocated (freeing them is a kv-cache-manager extension).
    sliding_window: Optional[int] = None
    # Per-layer window layout for models mixing sliding and full-causal
    # layers: entry i is layer i's window (0 = full causal). None means
    # ``sliding_window`` applies uniformly. Gemma2 alternates
    # sliding/full; Qwen2's max_window_layers keeps the first N layers
    # full (reference: per-layer sliding_window in models/gemma2.py and
    # models/qwen2.py attention construction).
    window_pattern: Optional[tuple] = None
    # The window of a second cache group, for a family that keeps its
    # window layers' pages apart from its full layers' (set by its
    # ``configure_arch`` from ``LlamaForCausalLM.window_group_of``; None:
    # one group), and that group's pool as models/loader.py sizes it.
    window_group: Optional[int] = None
    window_group_pages: int = 0
    window_group_page_size: int = 0
    # Logit soft-capping, cap*tanh(x/cap); 0 disables (Gemma2,
    # reference: attn_logit_softcapping/final_logit_softcapping in
    # models/gemma2.py).
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    # Attention score scale override: scores use this value**-0.5
    # instead of head_dim**-0.5 (Gemma2 query_pre_attn_scalar).
    query_pre_attn_scalar: Optional[float] = None
    # Gemma2-style sandwich norms: an extra RMSNorm on each sub-block's
    # OUTPUT (attention and MLP) before the residual add.
    extra_layer_norms: bool = False
    # Sequence parallelism: constrain the residual stream token-sharded
    # on the model axis between blocks (see ParallelConfig.
    # enable_sequence_parallel).
    sequence_parallel: bool = False
    # Family knobs reused by Llama-shaped variants: embedding scale
    # (Gemma multiplies by sqrt(H)), MLP activation, per-head q/k
    # RMSNorm (Qwen3).
    embed_scale: float = 1.0
    hidden_act: str = "silu"  # silu | gelu_tanh | gelu | relu2
    qk_norm: bool = False
    # ---- generic block-structure knobs (GPT-NeoX / Phi / StableLM /
    # Starcoder2 / Cohere / Olmo2 / Granite families) ----
    # Norm flavor: "rms" or mean-centering "layernorm" (+ optional beta).
    norm_type: str = "rms"
    norm_bias: bool = False
    # Partial rotary: rope covers only the first rotary_dim lanes of
    # each head (GPT-NeoX rotary_pct, Phi partial_rotary_factor);
    # None = full head_dim.
    rotary_dim: Optional[int] = None
    # Pairwise (complex) rope instead of rotate-half (Cohere, GLM).
    rope_interleaved: bool = False
    # Parallel residual: h += attn(ln1(h)) + mlp(ln2(h)) (GPT-NeoX);
    # shared_block_ln feeds BOTH sub-blocks from ln1 (Phi, Cohere).
    parallel_block: bool = False
    shared_block_ln: bool = False
    # False + extra_layer_norms: post-norm block (Olmo2 — sublayer
    # inputs un-normed, outputs normed before the residual add).
    pre_norm: bool = True
    # Non-gated MLP: fc1 -> act -> fc2 (GPT-NeoX/Phi/Starcoder2).
    mlp_gated: bool = True
    mlp_bias: bool = False
    attention_out_bias: bool = False
    # Full-row q/k RMSNorm before the head reshape (Olmo2) — distinct
    # from the per-head qk_norm.
    qk_norm_full: bool = False
    # Per-head qk norms carry a bias (Persimmon's LayerNorm flavor;
    # the norm kind follows norm_type).
    qk_norm_bias: bool = False
    # Clamp q/k/v projections to [-clip, clip] (OLMo clip_qkv).
    qkv_clip: Optional[float] = None
    # Separate rope base for SLIDING-window layers (Gemma3: local
    # theta 10k on sliding layers, global theta 1M + scaling on full
    # layers). None = one table for every layer.
    rope_theta_local: Optional[float] = None
    # Score scale as a direct multiplier (Granite attention_multiplier);
    # overrides the head-dim rule and query_pre_attn_scalar.
    sm_scale_override: Optional[float] = None
    # Position encoding: "rope" (default) or "learned" absolute tables
    # added at embed time (GPT-2 / OPT / GPTBigCode lineage; reference:
    # the get_position_embeddings path of models/gpt2.py, opt.py). The
    # table is params["embed_pos"] [max_position_embeddings, H];
    # pos_offset shifts lookups (OPT writes positions starting at 2).
    pos_embedding: str = "rope"
    max_position_embeddings: int = 0
    pos_offset: int = 0
    # ALiBi attention bias (Bloom/MPT; usually with pos_embedding =
    # "none"): slope * (kv_pos - q_pos) added per head before masking.
    alibi: bool = False
    # Apply the final norm before the LM head (False for post-norm
    # encoder-decoder stacks like BART, whose last sublayer already
    # normalized).
    final_norm: bool = True
    # Learned per-head attention-sink logits joining each softmax
    # denominator (gpt-oss; params carry layers["sinks"] [L, heads]).
    attn_sinks: bool = False
    # LayerNorm directly after the embedding lookup (Bloom's
    # word_embeddings_layernorm).
    embed_ln: bool = False
    # Residual-branch multiplier (Granite residual_multiplier).
    residual_multiplier: float = 1.0
    # Final-logit multiplier (Cohere logit_scale; Granite
    # 1/logits_scaling).
    logit_multiplier: float = 1.0
    dtype: Any = jnp.bfloat16

    @property
    def total_kv_heads(self) -> int:
        """KV heads actually materialized (checkpoint heads × replicas)."""
        return self.num_kv_heads * self.num_kv_head_replicas

    @staticmethod
    def _resolve_sliding_window(hf):
        """HF sliding-window semantics -> (window, per_layer_pattern).

        Uniform layouts (Mistral) give (window, None); mixed layouts
        give a per-layer pattern — preferably from ``hf.layer_types``
        ("sliding_attention"/"full_attention" per layer: Gemma2
        alternates, Qwen2 marks layers >= max_window_layers), falling
        back to max_window_layers arithmetic for configs without it.
        Returns (None, None) when no layer is windowed."""
        window = getattr(hf, "sliding_window", None)
        if not window or not getattr(hf, "use_sliding_window", True):
            return None, None
        window = int(window)
        L = hf.num_hidden_layers
        layer_types = getattr(hf, "layer_types", None)
        if layer_types:
            pattern = tuple(window if t == "sliding_attention" else 0
                            for t in layer_types)
            if not any(pattern):
                return None, None
            if all(pattern):
                return window, None
            return window, pattern
        mwl = getattr(hf, "max_window_layers", None)
        if mwl is None or mwl <= 0:
            return window, None
        if mwl >= L:
            return None, None  # every layer full attention
        # First mwl layers full-causal, the rest windowed (Qwen2).
        return window, (0, ) * mwl + (window, ) * (L - mwl)

    @classmethod
    def from_hf_config(cls, hf, dtype=jnp.bfloat16) -> "LlamaArchConfig":
        head_dim = getattr(hf, "head_dim", None) or (
            hf.hidden_size // hf.num_attention_heads)
        sliding_window, window_pattern = cls._resolve_sliding_window(hf)
        rope_scaling = getattr(hf, "rope_scaling", None)
        rtype = (rope_scaling or {}).get(
            "rope_type", (rope_scaling or {}).get("type"))
        if rtype == "longrope":
            # LongRoPE selects long/short factors by the serving window
            # vs the pretraining window; fold both config-level fields
            # into the dict so the rope math stays self-contained.
            rope_scaling = dict(
                rope_scaling,
                original_max_position_embeddings=getattr(
                    hf, "original_max_position_embeddings",
                    hf.max_position_embeddings),
                max_position_embeddings=hf.max_position_embeddings)
        return cls(
            vocab_size=hf.vocab_size,
            hidden_size=hf.hidden_size,
            intermediate_size=(
                getattr(hf, "intermediate_size", None)
                or getattr(hf, "ffn_hidden_size", None)  # Falcon
                or 4 * hf.hidden_size),
            num_layers=hf.num_hidden_layers,
            num_q_heads=hf.num_attention_heads,
            num_kv_heads=getattr(hf, "num_key_value_heads",
                                 hf.num_attention_heads),
            head_dim=head_dim,
            rope_theta=getattr(hf, "rope_theta", 10000.0),
            rope_scaling=rope_scaling,
            rms_norm_eps=getattr(hf, "rms_norm_eps", 1e-6),
            tie_word_embeddings=getattr(hf, "tie_word_embeddings", False),
            attention_bias=getattr(hf, "attention_bias", False),
            sliding_window=sliding_window,
            window_pattern=window_pattern,
            num_experts=getattr(hf, "num_local_experts", 0),
            num_experts_per_tok=getattr(hf, "num_experts_per_tok", 2),
            # HF Llama semantics: attention_bias also biases o_proj and
            # mlp_bias biases the gated MLP (families whose HF code
            # deviates override in configure_arch).
            attention_out_bias=bool(getattr(hf, "attention_bias",
                                            False)),
            mlp_bias=bool(getattr(hf, "mlp_bias", False)),
            dtype=dtype,
        )


class LlamaForCausalLM:
    """Stateless model: holds config + param specs; params live outside."""

    # Matrix weights eligible for int8 quantize-on-load (reference:
    # quantization/tpu_int8.py quantizes the linear layers; embed stays
    # fp for the gather, lm_head for logit fidelity).
    QUANT_TARGETS = ("wq", "wk", "wv", "wo", "gate", "up", "down")
    # Matrices that accept LoRA adapters (reference: lora/layers.py
    # wrapping every parallel linear; MoE models restrict to attention).
    LORA_TARGETS = ("wq", "wk", "wv", "wo", "gate", "up", "down")
    # Families with a biased LM head (Phi, GPT-J): specs/init/load key
    # on this; the forward applies params["lm_head_b"] when present.
    LM_HEAD_BIAS = False

    def __init__(self, cfg: LlamaArchConfig) -> None:
        self.cfg = cfg

    @classmethod
    def arch_config_source(cls, hf):
        """The HF (sub-)config carrying the decoder dims (wrapper
        configs like llava point at text_config)."""
        return hf

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        """Family-specific arch-config tweaks, applied by the loader
        after the generic from_hf_config mapping (subclass hook)."""

    # ------------------------------------------------------------------
    # Quantization (w8a16)
    # ------------------------------------------------------------------
    def quantize_params(self, params: dict) -> dict:
        """Weight-only quantization of the listed layer matrices, w8a16
        style (reference: quantization/tpu_int8.py + the fp8 configs):

        * "int8": symmetric per-output-channel, scale = absmax/127.
        * "fp8": float8_e4m3fn payloads with the same per-channel
          scaling (absmax mapped to the e4m3 max of 448).
        * "int4": symmetric per-channel absmax/7 in jnp.int4 — XLA
          packs int4 two-per-byte in TPU HBM, a native 4-bit weight
          datapath (a "-GPTQ"/"-AWQ" checkpoint + --quantization int4
          keeps the 4-bit HBM footprint after the load-time dequant;
          reference: the W4A16 serving path of quantization/gptq.py).

        * "w8a8": int8 weights (same per-channel scaling) AND dynamic
          per-token int8 activations — the dot runs int8 x int8 on the
          MXU with an int32 accumulator, rescaled by the product of
          scales (reference: the w8a8 schemes of
          quantization/compressed_tensors + csrc int8 quant kernels).

        Matmuls dequantize at read (XLA fuses convert*scale into the
        dot's operand load); w8a8 instead quantizes the activation at
        the dot via _mm."""
        scheme = self.cfg.quantization
        if scheme == "w8a8":
            scheme = "int8"  # same weight payloads; _mm changes the dot
        if scheme == "int4g":
            return self._quantize_groupwise(params)
        if scheme not in ("int4", "int8", "fp8"):
            return params
        layers = params["layers"]
        for name in self.QUANT_TARGETS:
            w = layers.get(name)
            if w is None:
                continue
            w32 = np.asarray(w, np.float32)
            absmax = np.max(np.abs(w32), axis=-2, keepdims=True)
            if scheme == "int8":
                scale = np.maximum(absmax / 127.0, 1e-8)
                q = jnp.asarray(
                    np.clip(np.round(w32 / scale), -127,
                            127).astype(np.int8))
            elif scheme == "int4":
                import ml_dtypes
                scale = np.maximum(absmax / 7.0, 1e-8)
                q = jnp.asarray(
                    np.clip(np.round(w32 / scale), -8,
                            7).astype(ml_dtypes.int4))
            else:
                import ml_dtypes
                scale = np.maximum(absmax / 448.0, 1e-8)
                # Cast HOST-side so only fp8 bytes ever hit device HBM
                # (same contract as the int8 branch).
                q = jnp.asarray(
                    (w32 / scale).astype(ml_dtypes.float8_e4m3fn))
            layers[name] = q
            layers[name + "_scale"] = jnp.asarray(scale, jnp.float32)
        return params

    GROUP_SIZE = 128  # int4g quantization group along the input dim

    def _quantize_groupwise(self, params: dict) -> dict:
        """Group-wise asymmetric uint4 ("int4g"): per (128-input-row
        group, output channel) scale/min. A GPTQ/AWQ checkpoint's
        load-time fp reconstruction lies exactly on each group's
        4-bit lattice, so this re-quantization recovers the original
        packed values bit-exactly (up to fp rounding) — the 4-bit HBM
        footprint and group fidelity survive into serving (reference:
        the gptq_marlin W4A16 serving path)."""
        import ml_dtypes
        layers = params["layers"]
        for name in self.QUANT_TARGETS:
            w = layers.get(name)
            if w is None:
                continue
            w32 = np.asarray(w, np.float32)  # [L, K, N]
            K = w32.shape[-2]
            g = self.cfg.quant_group_size
            if K % g:
                g = self.GROUP_SIZE if K % self.GROUP_SIZE == 0 else K
            shp = w32.shape[:-2] + (K // g, g) + w32.shape[-1:]
            wg = w32.reshape(shp)
            wmin = wg.min(axis=-2)  # [L, G, N]
            wmax = wg.max(axis=-2)
            scale = np.maximum((wmax - wmin) / 15.0, 1e-8)
            q = np.clip(
                np.round((wg - wmin[..., None, :]) / scale[..., None, :]),
                0, 15).astype(ml_dtypes.uint4)
            layers[name] = jnp.asarray(q.reshape(w32.shape))
            layers[name + "_gscale"] = jnp.asarray(scale, jnp.float32)
            layers[name + "_gmin"] = jnp.asarray(wmin, jnp.float32)
        return params

    _QUANT_DTYPES = (jnp.int8, jnp.float8_e4m3fn, jnp.int4)
    # Row-parallel projections whose combining all-reduce the quantized
    # communication plane may take over (see _mm).
    _ROW_PARALLEL = ("wo", "down", "fc2")

    def _use_quant_kernel(self) -> bool:
        """Fused dequant-GEMM eligibility: pallas backend on one chip
        (pallas_call is opaque to GSPMD — sharded dots keep the XLA
        dequant-in-dot path, whose convert fuses into the operand
        load)."""
        from vllm_distributed_tpu.ops.attention import \
            resolve_attention_backend
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        if resolve_attention_backend() != "pallas":
            return False
        return (not mesh_state.has_global_mesh()
                or mesh_state.tp_size() == 1)

    def _w(self, lp: dict, name: str) -> jax.Array:
        """Dequantizing weight accessor: identity for fp weights."""
        w = lp[name]
        if w.dtype == jnp.uint4:
            # int4g group-wise: w = q * scale[g] + min[g] along the
            # input dim (XLA fuses the reshape/broadcast into the dot).
            K, N = w.shape[-2], w.shape[-1]
            G = lp[name + "_gscale"].shape[-2]
            g = K // G
            wq = w.astype(jnp.float32).reshape(*w.shape[:-2], G, g, N)
            wf = (wq * lp[name + "_gscale"][..., :, None, :] +
                  lp[name + "_gmin"][..., :, None, :])
            return wf.reshape(w.shape).astype(self.cfg.dtype)
        if w.dtype in self._QUANT_DTYPES:
            return (w.astype(self.cfg.dtype) *
                    lp[name + "_scale"].astype(self.cfg.dtype))
        return w

    def _mm(self, lp: dict, name: str, x: jax.Array) -> jax.Array:
        """Quantization-aware matmul ``x @ w``: under w8a8 the
        activation is dynamically quantized per token (absmax/127) and
        the dot runs int8 x int8 -> int32 on the MXU, rescaled by
        act_scale * weight_scale; every other scheme dequantizes the
        weight into a normal fp dot (reference: the per-token dynamic
        activation quant of csrc/quantization/ int8 kernels). Small
        (decode-sized) weight-only dots on a single chip take the fused
        Pallas dequant-GEMM so only packed bytes stream from HBM
        (ops/pallas_quant_matmul.py; reference capability:
        csrc/quantization/gptq_marlin).

        Row-parallel output projections (wo / down / fc2: input dim
        sharded over the model axis, the dot's combining all-reduce is
        the dense-TP wire cost) route through the explicit quantized
        reduce when VDT_QCOMM enables the "tp" path — shard_map makes
        GSPMD's implicit psum OURS to quantize
        (parallel/collectives.row_parallel_dot). Quantized-weight
        layouts and sequence parallelism (whose reduce is already
        rewritten to reduce_scatter + all_gather) keep the GSPMD
        path."""
        w = lp[name]
        if (name in self._ROW_PARALLEL and w.ndim == 2 and x.ndim == 2
                and not self.cfg.sequence_parallel
                and w.dtype not in self._QUANT_DTYPES
                and w.dtype != jnp.uint4):
            from vllm_distributed_tpu.parallel import collectives
            if collectives.tp_reduce_applicable():
                return collectives.row_parallel_dot(x, w)
        if (w.dtype == jnp.uint4 and x.ndim == 2 and x.shape[0] <= 64
                and self._use_quant_kernel()):
            from vllm_distributed_tpu import envs
            from vllm_distributed_tpu.ops.pallas_quant_matmul import \
                quant_matmul_grouped
            return quant_matmul_grouped(
                x, w, lp[name + "_gscale"], lp[name + "_gmin"],
                interpret=envs.VDT_PALLAS_INTERPRET)
        if (w.dtype in self._QUANT_DTYPES
                and self.cfg.quantization != "w8a8"
                and x.ndim == 2 and x.shape[0] <= 64
                and self._use_quant_kernel()):
            from vllm_distributed_tpu import envs
            from vllm_distributed_tpu.ops.pallas_quant_matmul import \
                quant_matmul
            return quant_matmul(x, w, lp[name + "_scale"],
                                interpret=envs.VDT_PALLAS_INTERPRET)
        if self.cfg.quantization == "w8a8" and w.dtype == jnp.int8:
            amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                           keepdims=True)
            xs = jnp.maximum(amax / 127.0, 1e-8)
            xq = jnp.clip(jnp.round(x.astype(jnp.float32) / xs),
                          -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(
                xq, w, (((x.ndim - 1, ), (0, )), ((), ())),
                preferred_element_type=jnp.int32)
            out = (acc.astype(jnp.float32) * xs *
                   lp[name + "_scale"].astype(jnp.float32))
            return out.astype(x.dtype)
        return x @ self._w(lp, name)

    # ------------------------------------------------------------------
    # Parameter tree
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        """PartitionSpecs matching self.init_params' tree: TP shards the
        head/ffn dimension on the "model" mesh axis (Megatron layout:
        column-parallel up-projections, row-parallel down-projections —
        reference vllm/model_executor/layers/linear.py, re-expressed as
        GSPMD shardings)."""
        c = self.cfg
        layer = {
            "input_ln": P(None, None),
            "wq": P(None, None, MODEL_AXIS),
            "wk": P(None, None, MODEL_AXIS),
            "wv": P(None, None, MODEL_AXIS),
            "wo": P(None, MODEL_AXIS, None),
            "post_ln": P(None, None),
        }
        if c.mlp_gated:
            layer.update({
                "gate": P(None, None, MODEL_AXIS),
                "up": P(None, None, MODEL_AXIS),
                "down": P(None, MODEL_AXIS, None),
            })
            if c.mlp_bias:
                layer.update({"gate_bias": P(None, MODEL_AXIS),
                              "up_bias": P(None, MODEL_AXIS),
                              "down_bias": P(None, None)})
        else:
            layer.update({
                "fc1": P(None, None, MODEL_AXIS),
                "fc2": P(None, MODEL_AXIS, None),
            })
            if c.mlp_bias:
                layer.update({"fc1_b": P(None, MODEL_AXIS),
                              "fc2_b": P(None, None)})
        if c.norm_bias:
            layer.update({"input_ln_b": P(None, None),
                          "post_ln_b": P(None, None)})
        if c.attention_out_bias:
            layer["bo"] = P(None, None)
        if c.parallel_block and c.shared_block_ln:
            # Single shared pre-norm: no post_ln in the block.
            layer.pop("post_ln")
            layer.pop("post_ln_b", None)
        if not c.pre_norm:
            layer.pop("input_ln")
            layer.pop("post_ln", None)
            layer.pop("input_ln_b", None)
            layer.pop("post_ln_b", None)
        if c.attention_bias:
            layer.update({
                "bq": P(None, MODEL_AXIS),
                "bk": P(None, MODEL_AXIS),
                "bv": P(None, MODEL_AXIS),
            })
        if c.qk_norm or c.qk_norm_full:
            layer.update({
                "q_norm": P(None, None),
                "k_norm": P(None, None),
            })
            if c.qk_norm_bias:
                layer.update({"q_norm_b": P(None, None),
                              "k_norm_b": P(None, None)})
        if c.extra_layer_norms:
            layer.update({
                "post_attn_ln": P(None, None),
                "post_ffw_ln": P(None, None),
            })
        self._add_scale_specs(layer)
        self._add_lora_specs(layer)
        specs = {
            "embed": P(None, None),
            "layers": layer,
            "final_ln": P(None),
            "lm_head": P(None, MODEL_AXIS),
        }
        if c.pos_embedding == "learned":
            specs["embed_pos"] = P(None, None)
        if c.embed_ln:
            specs["embed_ln_w"] = P(None)
            if c.norm_bias:
                specs["embed_ln_b"] = P(None)
        if self.LM_HEAD_BIAS:
            specs["lm_head_b"] = P(MODEL_AXIS)
        if c.norm_bias:
            specs["final_ln_b"] = P(None)
        return specs

    def _add_lora_specs(self, layer: dict) -> None:
        """Adapter-buffer shardings: B follows the base weight's output
        sharding, A its input sharding; rank never shards."""
        if self.cfg.max_loras == 0:
            return
        for name in self.LORA_TARGETS:
            wspec = layer.get(name)
            if wspec is None:
                continue
            entries = list(wspec)  # [L, in, out]
            layer[name + "_a"] = P(None, None, entries[1], None)
            layer[name + "_b"] = P(None, None, None, entries[2])

    def _install_lora_buffers(self, layers: dict) -> None:
        if self.cfg.max_loras == 0:
            return
        from vllm_distributed_tpu.models.lora import init_lora_buffers
        targets = [t for t in self.LORA_TARGETS if t in layers]
        layers.update(init_lora_buffers(self.cfg, targets))

    def _lora_delta(self, lp: dict, name: str, x, ctx):
        """Adapter contribution for one matmul; zero-cost branch when
        LoRA is disabled (static)."""
        if ctx is None or (name + "_a") not in lp:
            return 0
        from vllm_distributed_tpu.models.lora import lora_apply
        return lora_apply(x, lp[name + "_a"], lp[name + "_b"], ctx)

    def _add_scale_specs(self, layer: dict) -> None:
        """Per-channel scale specs mirror their weight's spec with the
        reduced (input) axis unsharded — scales broadcast over it. All
        weight specs here are written at full rank, so the scale keeps
        the spec with only the second-to-last entry cleared."""
        for name in list(layer):
            if name.endswith(("_scale", "_gscale", "_gmin")):
                del layer[name]
        if self.cfg.quantization not in ("int4", "int8", "fp8", "w8a8",
                                         "int4g"):
            return
        for name in self.QUANT_TARGETS:
            spec = layer.get(name)
            if spec is None:
                continue
            if self.cfg.quantization == "int4g":
                # The group dim subdivides the input dim, so it shards
                # exactly as the weight's input axis does.
                layer[name + "_gscale"] = spec
                layer[name + "_gmin"] = spec
            else:
                entries = list(spec)
                entries[-2] = None
                layer[name + "_scale"] = P(*entries)

    def kv_cache_specs(self) -> dict:
        # [L, pages, kv_heads, page_size, head_dim]: pages shard on the
        # token-parallel axis (each rank's page-pool partition is its
        # shard; no-op when the axis is 1) and kv heads on the TP axis
        # (head-major page layout; see ops/attention.write_kv_pages).
        specs = {
            "k": P(None, TOKEN_AXIS, MODEL_AXIS, None, None),
            "v": P(None, TOKEN_AXIS, MODEL_AXIS, None, None),
        }
        if self.cfg.window_group:
            # The window layers' own cache group (``window_group_of``).
            specs.update(k_win=specs["k"], v_win=specs["v"])
        return specs

    # ------------------------------------------------------------------
    # Two kinds of K/V: a family whose window and full layers are mixed
    # opts into a cache group for each by setting ``cfg.window_group =
    # window_group_of(arch)`` in its ``configure_arch``
    # (models/cohere_moe.py, models/smallthinker.py). The full layers'
    # pages (``k`` / ``v``, from the pool the worker sizes) hold every
    # token of a request; the window layers' (``k_win`` / ``v_win``,
    # ``cfg.window_group_pages`` of them, set by models/loader.py: all
    # the group can ever hold) hold what lies inside the window and are
    # handed back behind it (core/kv_cache_manager.py
    # ``GroupedKVCacheManager``). The walk is ``_run_layers_by_group``.
    # ------------------------------------------------------------------
    @staticmethod
    def window_group_of(arch) -> Optional[int]:
        """The window of the second cache group, where window layers and
        full layers are mixed; a model of one kind of layer keeps one
        group (and ``resolve_free_window`` frees behind an all-window
        model's one table)."""
        pattern = arch.window_pattern
        if pattern is None:
            return None
        if len(set(pattern)) != 2:
            raise ValueError(
                f"the layer pattern mixes more than one window with full "
                f"layers ({sorted(set(pattern))}); a cache group a window "
                "is not wired")
        return max(pattern)

    def _group_depths(self) -> tuple[int, int]:
        """(full layers, window layers)."""
        pattern = self.cfg.window_pattern or (0, ) * self.cfg.num_layers
        n_window = sum(1 for w in pattern if w)
        return len(pattern) - n_window, n_window

    def fixed_cache_bytes(self) -> int:
        """The window group's pool, which does not grow with the pool the
        worker sizes (worker/worker.py charges it before the pages)."""
        if not self.cfg.window_group:
            return 0
        shapes = jax.eval_shape(lambda: self.make_kv_caches(
            1, self.cfg.window_group_page_size))
        return sum(int(np.prod(shapes[k].shape)) * shapes[k].dtype.itemsize
                   for k in ("k_win", "v_win"))

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        """Random (dummy-loader) initialization, HF-shaped."""
        c = self.cfg
        L, H, I = c.num_layers, c.hidden_size, c.intermediate_size
        Dq = c.num_q_heads * c.head_dim
        Dkv = c.num_kv_heads * c.head_dim
        keys = iter(jax.random.split(rng, 12))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = {
            "input_ln": jnp.ones((L, H), c.dtype),
            "wq": norm(next(keys), (L, H, Dq)),
            "wk": norm(next(keys), (L, H, Dkv)),
            "wv": norm(next(keys), (L, H, Dkv)),
            "wo": norm(next(keys), (L, Dq, H)),
            "post_ln": jnp.ones((L, H), c.dtype),
        }
        if c.mlp_gated:
            layers.update({
                "gate": norm(next(keys), (L, H, I)),
                "up": norm(next(keys), (L, H, I)),
                "down": norm(next(keys), (L, I, H)),
            })
            if c.mlp_bias:
                layers.update({
                    "gate_bias": jnp.zeros((L, I), c.dtype),
                    "up_bias": jnp.zeros((L, I), c.dtype),
                    "down_bias": jnp.zeros((L, H), c.dtype),
                })
        else:
            layers.update({
                "fc1": norm(next(keys), (L, H, I)),
                "fc2": norm(next(keys), (L, I, H)),
            })
            if c.mlp_bias:
                layers.update({"fc1_b": jnp.zeros((L, I), c.dtype),
                               "fc2_b": jnp.zeros((L, H), c.dtype)})
        if c.norm_bias:
            layers.update({"input_ln_b": jnp.zeros((L, H), c.dtype),
                           "post_ln_b": jnp.zeros((L, H), c.dtype)})
        if c.attention_out_bias:
            layers["bo"] = jnp.zeros((L, H), c.dtype)
        if c.parallel_block and c.shared_block_ln:
            layers.pop("post_ln")
            layers.pop("post_ln_b", None)
        if not c.pre_norm:
            for k in ("input_ln", "post_ln", "input_ln_b", "post_ln_b"):
                layers.pop(k, None)
        if c.attention_bias:
            layers.update({
                "bq": jnp.zeros((L, Dq), c.dtype),
                "bk": jnp.zeros((L, Dkv), c.dtype),
                "bv": jnp.zeros((L, Dkv), c.dtype),
            })
        if c.qk_norm:
            layers.update({
                "q_norm": jnp.ones((L, c.head_dim), c.dtype),
                "k_norm": jnp.ones((L, c.head_dim), c.dtype),
            })
            if c.qk_norm_bias:
                layers.update({
                    "q_norm_b": jnp.zeros((L, c.head_dim), c.dtype),
                    "k_norm_b": jnp.zeros((L, c.head_dim), c.dtype),
                })
        if c.qk_norm_full:
            layers.update({
                "q_norm": jnp.ones((L, Dq), c.dtype),
                "k_norm": jnp.ones((L, Dkv), c.dtype),
            })
        if c.extra_layer_norms:
            layers.update({
                "post_attn_ln": jnp.ones((L, H), c.dtype),
                "post_ffw_ln": jnp.ones((L, H), c.dtype),
            })
        self._maybe_replicate_kv(layers)
        self._install_lora_buffers(layers)
        embed = norm(next(keys), (c.vocab_size, H))
        out = {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.ones((H, ), c.dtype),
            "lm_head": (embed.T if c.tie_word_embeddings else norm(
                next(keys), (H, c.vocab_size))),
        }
        if c.pos_embedding == "learned":
            out["embed_pos"] = norm(next(keys),
                                    (c.max_position_embeddings, H))
        if c.embed_ln:
            out["embed_ln_w"] = jnp.ones((H, ), c.dtype)
            if c.norm_bias:
                out["embed_ln_b"] = jnp.zeros((H, ), c.dtype)
        if self.LM_HEAD_BIAS:
            out["lm_head_b"] = jnp.zeros((c.vocab_size, ), c.dtype)
        if c.norm_bias:
            out["final_ln_b"] = jnp.zeros((H, ), c.dtype)
        return out

    def _maybe_replicate_kv(self, layers: dict) -> None:
        """Expand K/V projection weights in place when KV heads are
        replicated for tp > num_kv_heads."""
        c = self.cfg
        if c.num_kv_head_replicas == 1:
            return
        names = ["wk", "wv", "bk", "bv"]
        if c.qk_norm_full:
            # Olmo2's full-row k norm is per-lane; widen with the heads.
            names.append("k_norm")
        for name in names:
            if name in layers:
                layers[name] = _replicate_kv_heads(
                    layers[name], c.num_kv_heads, c.num_kv_head_replicas)

    def cache_dtype(self):
        """KV-page dtype: the model dtype, or fp8 under
        --kv-cache-dtype fp8 (reference: the kv_cache_dtype flag and
        csrc fp8 cache kernels; values dequantize at the attention
        read, scale 1.0 like the reference default)."""
        return getattr(self.cfg, "kv_cache_dtype", None) or self.cfg.dtype

    def kv_cache_page_bytes(self, page_size: int) -> int:
        """HBM bytes one page costs across all layers (the worker sizes
        the pool from this; models with non-K/V cache layouts override)."""
        from vllm_distributed_tpu.ops.attention import storage_head_dim
        c = self.cfg
        return (2 * c.num_layers * page_size * c.total_kv_heads *
                storage_head_dim(c.head_dim) *
                jnp.dtype(self.cache_dtype()).itemsize)

    def slice_layer_params(self, layers: dict, start: int,
                           end: int) -> dict:
        """A pipeline stage's slice of the stacked per-layer params;
        models whose stacks have per-kind depths override (deepseek)."""
        return {k: v[start:end] for k, v in layers.items()}

    def serving_layout(self, layers: dict) -> dict:
        """The layer tree as the jitted serving programs take it, made
        from the canonical stacked one wherever the runner takes or
        re-places weights. A dense model serves from the stacks as they
        are; families whose kernels want a layer's weights as a buffer
        of its own override (models/mixtral.py)."""
        return layers

    def canonical_layout(self, layers: dict) -> dict:
        """Inverse of ``serving_layout``, on the host: the tree as the
        loader returns it and a sharded-state checkpoint stores it."""
        return layers

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        """Stacked [L, ...] cache; ``num_layers`` overrides the depth for
        a pipeline stage's local slice."""
        from vllm_distributed_tpu.ops.attention import storage_head_dim
        c = self.cfg
        dtype = cache_dtype or self.cache_dtype()

        def pages(depth: int, count: int) -> jax.Array:
            return jnp.zeros((depth, count, c.total_kv_heads, page_size,
                              storage_head_dim(c.head_dim)), dtype)

        if c.window_group:
            assert num_layers is None, \
                "cache groups are not sliced per stage"
            n_full, n_window = self._group_depths()
            return {"k": pages(n_full, num_pages),
                    "v": pages(n_full, num_pages),
                    "k_win": pages(n_window, c.window_group_pages),
                    "v_win": pages(n_window, c.window_group_pages)}
        depth = num_layers if num_layers is not None else c.num_layers
        return {"k": pages(depth, num_pages), "v": pages(depth, num_pages)}

    # ------------------------------------------------------------------
    # Weight loading from an HF checkpoint state dict
    # ------------------------------------------------------------------
    def params_from_hf_state_dict(self, tensors: dict[str, np.ndarray],
                                  ) -> dict:
        """Map HF LlamaForCausalLM tensor names to the stacked tree.

        ``tensors`` maps HF names to numpy arrays (loaded by the
        model_loader from safetensors shards). Torch Linear stores
        [out, in]; we transpose to right-multiply layout.
        """
        c = self.cfg
        L = c.num_layers
        # Auto-detect bias tensors the config did not declare (Qwen2
        # hardcodes qkv biases with no attention_bias attr; dropping
        # them silently would mis-serve real checkpoints). cfg flags
        # are trace-time statics, so flipping them before param_specs
        # keeps specs/forward consistent.
        if (not c.attention_bias
                and "model.layers.0.self_attn.q_proj.bias" in tensors):
            c.attention_bias = True
        if (not c.attention_out_bias
                and "model.layers.0.self_attn.o_proj.bias" in tensors):
            c.attention_out_bias = True
        if (not c.mlp_bias and c.mlp_gated
                and "model.layers.0.mlp.gate_proj.bias" in tensors):
            c.mlp_bias = True

        def t(name):
            return np.asarray(tensors[name])

        def stack(fmt, transpose=True):
            mats = [t(fmt.format(i)) for i in range(L)]
            arr = np.stack([m.T if transpose else m for m in mats])
            return jnp.asarray(arr, dtype=c.dtype)

        layers = {
            "wq": stack("model.layers.{}.self_attn.q_proj.weight"),
            "wk": stack("model.layers.{}.self_attn.k_proj.weight"),
            "wv": stack("model.layers.{}.self_attn.v_proj.weight"),
            "wo": stack("model.layers.{}.self_attn.o_proj.weight"),
        }
        if c.pre_norm:
            layers["input_ln"] = stack(
                "model.layers.{}.input_layernorm.weight",
                transpose=False)
            if not (c.parallel_block and c.shared_block_ln):
                layers["post_ln"] = stack(
                    "model.layers.{}.post_attention_layernorm.weight",
                    transpose=False)
            if c.norm_bias:
                layers["input_ln_b"] = stack(
                    "model.layers.{}.input_layernorm.bias",
                    transpose=False)
                if "post_ln" in layers:
                    layers["post_ln_b"] = stack(
                        "model.layers.{}.post_attention_layernorm.bias",
                        transpose=False)
        if c.mlp_gated:
            layers.update({
                "gate": stack("model.layers.{}.mlp.gate_proj.weight"),
                "up": stack("model.layers.{}.mlp.up_proj.weight"),
                "down": stack("model.layers.{}.mlp.down_proj.weight"),
            })
            if c.mlp_bias:
                layers.update({
                    "gate_bias": stack(
                        "model.layers.{}.mlp.gate_proj.bias", False),
                    "up_bias": stack(
                        "model.layers.{}.mlp.up_proj.bias", False),
                    "down_bias": stack(
                        "model.layers.{}.mlp.down_proj.bias", False),
                })
        else:
            # Canonical plain-MLP names; family subclasses rename their
            # checkpoint tensors (dense_h_to_4h, c_fc, ...) onto these.
            layers.update({
                "fc1": stack("model.layers.{}.mlp.fc1.weight"),
                "fc2": stack("model.layers.{}.mlp.fc2.weight"),
            })
            if c.mlp_bias:
                layers.update({
                    "fc1_b": stack("model.layers.{}.mlp.fc1.bias",
                                   transpose=False),
                    "fc2_b": stack("model.layers.{}.mlp.fc2.bias",
                                   transpose=False),
                })
        if c.attention_out_bias:
            layers["bo"] = stack(
                "model.layers.{}.self_attn.o_proj.bias",
                transpose=False)
        if c.attention_bias:
            layers.update({
                "bq": stack("model.layers.{}.self_attn.q_proj.bias",
                            transpose=False),
                "bk": stack("model.layers.{}.self_attn.k_proj.bias",
                            transpose=False),
                "bv": stack("model.layers.{}.self_attn.v_proj.bias",
                            transpose=False),
            })
        if c.qk_norm or c.qk_norm_full:
            layers.update({
                "q_norm": stack("model.layers.{}.self_attn.q_norm.weight",
                                transpose=False),
                "k_norm": stack("model.layers.{}.self_attn.k_norm.weight",
                                transpose=False),
            })
            if c.qk_norm_bias:
                layers.update({
                    "q_norm_b": stack(
                        "model.layers.{}.self_attn.q_norm.bias",
                        transpose=False),
                    "k_norm_b": stack(
                        "model.layers.{}.self_attn.k_norm.bias",
                        transpose=False),
                })
        if c.extra_layer_norms:
            # Gemma2's 4-norm block renames the roles: HF
            # post_attention_layernorm norms the attention OUTPUT (our
            # post_attn_ln) and pre_feedforward_layernorm is the
            # pre-MLP norm (our post_ln). Post-norm blocks (Olmo2,
            # pre_norm=False) have only the two output norms.
            layers.update({
                "post_attn_ln": stack(
                    "model.layers.{}.post_attention_layernorm.weight",
                    transpose=False),
                "post_ffw_ln": stack(
                    "model.layers.{}.post_feedforward_layernorm.weight",
                    transpose=False),
            })
            if c.pre_norm:
                layers["post_ln"] = stack(
                    "model.layers.{}.pre_feedforward_layernorm.weight",
                    transpose=False)
        self._maybe_replicate_kv(layers)
        embed = jnp.asarray(t("model.embed_tokens.weight"), dtype=c.dtype)
        if c.tie_word_embeddings or "lm_head.weight" not in tensors:
            lm_head = embed.T
        else:
            lm_head = jnp.asarray(t("lm_head.weight").T, dtype=c.dtype)
        self._install_lora_buffers(layers)
        out = {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.asarray(t("model.norm.weight"), dtype=c.dtype),
            "lm_head": lm_head,
        }
        if c.pos_embedding == "learned":
            # Families rename their table to this canonical name.
            out["embed_pos"] = jnp.asarray(
                t("model.embed_positions.weight"), dtype=c.dtype)
        if c.embed_ln:
            out["embed_ln_w"] = jnp.asarray(
                t("model.embed_layernorm.weight"), dtype=c.dtype)
            if c.norm_bias:
                out["embed_ln_b"] = jnp.asarray(
                    t("model.embed_layernorm.bias"), dtype=c.dtype)
        if self.LM_HEAD_BIAS:
            out["lm_head_b"] = jnp.asarray(
                np.asarray(tensors.get(
                    "lm_head.bias",
                    np.zeros((c.vocab_size, ), np.float32))),
                dtype=c.dtype)
        if c.norm_bias and "model.norm.bias" in tensors:
            out["final_ln_b"] = jnp.asarray(t("model.norm.bias"),
                                            dtype=c.dtype)
        return out

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def _act(self, x: jax.Array) -> jax.Array:
        act = self.cfg.hidden_act
        if act in ("gelu_tanh", "gelu_new", "gelu_pytorch_tanh"):
            return jax.nn.gelu(x, approximate=True)
        if act == "gelu":
            return jax.nn.gelu(x, approximate=False)
        if act == "relu2":
            r = jax.nn.relu(x)
            return r * r
        if act == "relu":
            return jax.nn.relu(x)
        if act == "quick_gelu":
            return x * jax.nn.sigmoid(1.702 * x)
        if act in ("silu", "swish", None):
            return jax.nn.silu(x)
        raise ValueError(
            f"unsupported hidden_act {act!r} (add it to _act rather "
            "than silently running the wrong activation)")

    def _norm(self, x: jax.Array, w: jax.Array,
              b: Optional[jax.Array] = None) -> jax.Array:
        """RMSNorm or mean-centering LayerNorm per cfg.norm_type."""
        c = self.cfg
        if c.norm_type == "rms":
            return rms_norm(x, w, c.rms_norm_eps)
        x32 = x.astype(jnp.float32)
        mu = x32.mean(axis=-1, keepdims=True)
        var = ((x32 - mu) ** 2).mean(axis=-1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + c.rms_norm_eps)
        out = out * w.astype(jnp.float32)
        if b is not None:
            out = out + b.astype(jnp.float32)
        return out.astype(x.dtype)

    def pre_attention(self, lp: dict, x: jax.Array):
        """What the layer's feed-forward needs of the ATTENTION's input
        ``x`` (normed where the block is pre-norm): nothing, for every
        family but one whose router stands before attention
        (models/smallthinker.py). What is returned reaches the family's
        own ``mlp_block`` as ``early=``; None adds nothing to the
        program."""
        return None

    def mlp_block(self, lp: dict, x: jax.Array,
                  lora_ctx=None) -> jax.Array:
        """Per-layer feed-forward; MoE models override this (the MLP is
        the only structural difference in the decoder block)."""
        c = self.cfg
        if not c.mlp_gated:
            h = self._mm(lp, "fc1", x)
            if c.mlp_bias:
                h = h + lp["fc1_b"]
            h = self._mm(lp, "fc2", self._act(h))
            if c.mlp_bias:
                h = h + lp["fc2_b"]
            return h
        gb = lp["gate_bias"] if c.mlp_bias else 0
        ub = lp["up_bias"] if c.mlp_bias else 0
        db = lp["down_bias"] if c.mlp_bias else 0
        if lora_ctx is None or ("gate_a") not in lp:
            g = self._act(self._mm(lp, "gate", x) + gb)
            return self._mm(lp, "down",
                            g * (self._mm(lp, "up", x) + ub)) + db
        g = self._act(self._mm(lp, "gate", x) + gb +
                      self._lora_delta(lp, "gate", x, lora_ctx))
        u = (self._mm(lp, "up", x) + ub +
             self._lora_delta(lp, "up", x, lora_ctx))
        gu = g * u
        return (self._mm(lp, "down", gu) + db +
                self._lora_delta(lp, "down", gu, lora_ctx))

    def embed(self, params: dict, token_ids: jax.Array,
              positions: jax.Array = None) -> jax.Array:
        """Token embedding (pipeline stage 0 front; reference: the
        VocabParallelEmbedding layer; learned-position families add
        their absolute table here like GPT2Model.wpe)."""
        h = params["embed"][token_ids]
        if self.cfg.embed_scale != 1.0:
            # Gemma normalizer semantics: the scale is cast to the
            # activation dtype before multiplying (HF parity).
            h = h * jnp.asarray(self.cfg.embed_scale, h.dtype)
        if self.cfg.pos_embedding == "learned":
            assert positions is not None, \
                "learned-position models need positions at embed time"
            idx = jnp.clip(positions + self.cfg.pos_offset, 0,
                           self.cfg.max_position_embeddings - 1)
            h = h + params["embed_pos"][idx]
        if self.cfg.embed_ln:
            h = self._norm(h, params["embed_ln_w"],
                           params.get("embed_ln_b"))
        return h

    @staticmethod
    def _plan_window_segments(windows: tuple) -> list:
        """Split a per-layer window tuple into scan segments.

        Returns [(start, count, pattern)]: layers [start, start+count)
        repeat ``pattern``. A short repeating period (Gemma2 alternates
        sliding/full -> period 2) becomes ONE lax.scan whose step
        unrolls the period with a static window each; otherwise runs of
        constant window (Qwen2's first-N-full layouts -> 2 runs) each
        get their own scan. Every attention mask stays STATIC per scan
        step — the XLA-friendly alternative to a traced window bound.

        Odd-length slices of a periodic layout (a Gemma2 PP stage with
        21 of 42 layers) keep the periodic bulk and peel only the
        remainder — two scans, not a per-layer unroll."""
        n = len(windows)
        for period in range(1, min(8, n) + 1):
            bulk = period * (n // period)
            # Require >= 2 repetitions: any period trivially "matches" a
            # bulk of itself, which would mis-plan run layouts.
            if n // period >= 2 and all(windows[i] == windows[i % period]
                                        for i in range(bulk)):
                segments = [(0, bulk, tuple(windows[:period]))]
                if bulk < n:
                    segments.append(
                        (bulk, n - bulk, tuple(windows[bulk:])))
                return segments
        segments = []
        i = 0
        while i < n:
            j = i
            while j < n and windows[j] == windows[i]:
                j += 1
            segments.append((i, j - i, (windows[i], )))
            i = j
        return segments

    def _layer_windows(self, first_layer: int, num_layers: int) -> tuple:
        """Static window per layer for a [first_layer, +num_layers)
        slice of the model."""
        c = self.cfg
        if c.window_pattern is not None:
            return tuple(
                c.window_pattern[first_layer:first_layer + num_layers])
        return (c.sliding_window or 0, ) * num_layers

    def run_layers(
        self,
        layer_params: dict,
        kv_caches: dict,
        hidden: jax.Array,  # [T, H]
        batch: AttentionBatch,
        first_layer: int = 0,
        cache_layer_offset: int = 0,
    ) -> tuple[jax.Array, dict]:
        """Run a contiguous slice of decoder layers over the hidden
        states. ``layer_params`` is a stacked [Ls, ...] subtree (in a
        serving layout some leaves are tuples of Ls buffers: they stay
        out of the scan, below) and ``kv_caches`` that slice's own
        [Ls, ...] cache — under pipeline parallelism each stage calls
        this with its local slice (reference: the per-stage module list
        built by get_pp_indices,
        distributed/utils.py:89). ``first_layer`` is the slice's global
        offset, selecting the right rows of mixed window layouts
        (static — PP keys its stage jit on it for patterned models).
        ``cache_layer_offset`` shifts KV reads/writes into deeper rows
        of a taller stacked cache — the EAGLE drafter's layers append
        to the target's cache stack and index past its depth."""
        c = self.cfg
        T = hidden.shape[0]
        if c.sm_scale_override is not None:
            sm_scale = c.sm_scale_override
        else:
            sm_scale = (c.query_pre_attn_scalar or c.head_dim) ** -0.5
        # A tuple leaf is one buffer a layer (serving_layout): it is not
        # scanned. A slice of a stack, a scan's dynamic one or an
        # unrolled loop's static one, is copied whole for a custom-call
        # consumer like ragged_dot (PERF.md, Findings PR 28), so those
        # buffers reach mlp_block as they are (``mlp`` below).
        per_layer = {k: v for k, v in layer_params.items()
                     if isinstance(v, tuple)}
        layer_params = {k: v for k, v in layer_params.items()
                        if k not in per_layer}
        num_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        rd = c.rotary_dim or c.head_dim
        if c.pos_embedding != "rope":
            cos = sin = cos_l = sin_l = None
        elif (c.mrope_section is not None
              and getattr(batch, "mrope_positions", None) is not None):
            from vllm_distributed_tpu.models.common import \
                compute_mrope_cos_sin
            cos, sin = compute_mrope_cos_sin(
                batch.mrope_positions, rd, c.rope_theta,
                tuple(c.mrope_section))
        elif c.rope_interleaved:
            from vllm_distributed_tpu.models.common import \
                compute_rope_cos_sin_pairwise
            cos, sin = compute_rope_cos_sin_pairwise(
                batch.positions, rd, c.rope_theta, c.rope_scaling)
        else:
            cos, sin = compute_rope_cos_sin(batch.positions, rd,
                                            c.rope_theta, c.rope_scaling,
                                            dtype=jnp.float32)
        if c.rope_theta_local is not None:
            # Gemma3: sliding layers rope with the LOCAL base and no
            # scaling; full layers keep the global table above.
            cos_l, sin_l = compute_rope_cos_sin(
                batch.positions, rd, c.rope_theta_local, None,
                dtype=jnp.float32)
        else:
            cos_l, sin_l = cos, sin

        has_bias = c.attention_bias
        if c.alibi:
            from vllm_distributed_tpu.models.common import alibi_slopes
            slopes = alibi_slopes(c.num_q_heads)
        else:
            slopes = None

        # The stacked caches thread through the layer scan as CARRIES and
        # every cache op indexes [layer, ...] internally: slicing the
        # cache per layer (scan xs/ys) would copy the entire cache through
        # HBM every step — the Pallas write kernel updates pages in place
        # via input/output aliasing instead (reference analogue:
        # v1/attention/backends/pallas.py:282 aliased kv_cache_update).
        lora_ctx = batch.lora

        # Sequence parallelism (reference: the sequence_parallelism
        # compile pass rewriting allreduce -> reduce_scatter +
        # all_gather): pin the residual stream token-sharded on the
        # model axis at block boundaries; GSPMD then scatters the
        # row-parallel matmul reductions and gathers before the next
        # column-parallel one, and norms/adds run on T/tp tokens. The
        # sharding binds to the registered engine mesh so the constraint
        # works under jit without an ambient mesh context.
        # The token dim shards over data x model jointly so mesh-mode DP
        # keeps its batch split (equivalent to model-only when the data
        # axis is 1, i.e. the serving engine path).
        sp_spec = P(("data", MODEL_AXIS), None)
        sp_sharding = None
        if c.sequence_parallel:
            from jax.sharding import NamedSharding

            from vllm_distributed_tpu.parallel import mesh as mesh_state
            if mesh_state.has_global_mesh():
                sp_sharding = NamedSharding(
                    mesh_state.get_global_mesh(), sp_spec)

        def sp(h):
            if not c.sequence_parallel:
                return h
            return jax.lax.with_sharding_constraint(
                h, sp_sharding if sp_sharding is not None else sp_spec)

        def apply_rotary(x, local=False):
            """Rope on the first ``rd`` lanes (fp32; partial rotary
            passes the tail through — GPT-NeoX rotary_pct semantics);
            ``local`` picks the sliding-layer table (Gemma3). Learned-
            position families skip rotation entirely."""
            if c.pos_embedding != "rope":
                return x
            from vllm_distributed_tpu.models.common import (
                apply_rope_pairwise, apply_rope_single)
            cs, sn = (cos_l, sin_l) if local else (cos, sin)
            x32 = x.astype(jnp.float32)
            rot = x32[..., :rd]
            rot = (apply_rope_pairwise(rot, cs, sn)
                   if c.rope_interleaved else
                   apply_rope_single(rot, cs, sn))
            if rd == c.head_dim:
                return rot.astype(c.dtype)
            return jnp.concatenate([rot, x32[..., rd:]],
                                   axis=-1).astype(c.dtype)

        rm = c.residual_multiplier

        def mlp(lp, x, layer_idx, early=None):
            """The MLP block of the layer at ``layer_idx`` (traced). Its
            per-layer buffers are picked by a switch with one branch a
            layer, so each branch's kernels read buffers that are
            arguments of the program, in place. ``early`` is what the
            family's ``pre_attention`` made of the attention's input
            (None for all but models/smallthinker.py): it reaches
            ``mlp_block`` as a keyword, through the switch as an operand."""
            if not per_layer or all(k in lp for k in per_layer):
                # Nothing a layer, or the walk is unrolled and the
                # layer's buffers stand in ``lp`` already.
                if early is None:
                    return self.mlp_block(lp, x, lora_ctx)
                return self.mlp_block(lp, x, lora_ctx, early=early)
            # One jitted block for all branches: traced once, called
            # with each layer's buffers.
            block = jax.jit(self.mlp_block)
            branches = [functools.partial(
                block, {**lp, **{k: v[i] for k, v in per_layer.items()}},
                lora_ctx=lora_ctx) for i in range(num_layers)]
            index = layer_idx[0] - cache_layer_offset
            if early is None:
                return jax.lax.switch(index, branches, x)
            return jax.lax.switch(
                index, [lambda x, e, b=b: b(x, early=e) for b in branches],
                x, early)

        def layer_body(h, k_all, v_all, lp, layer_idx, window,
                       nope=False, cache_idx=None, cache_batch=batch):
            """``cache_idx`` / ``cache_batch``: the layer's row in the
            cache it is handed and the batch its cache ops read, where a
            window layer's K/V lives in a cache group of its own (below);
            else the layer's index and the step's batch."""
            if c.pre_norm:
                x = self._norm(h, lp["input_ln"], lp.get("input_ln_b"))
            else:
                x = h  # Olmo2 post-norm block: sub-layers see raw h
            early = self.pre_attention(lp, x)
            q = self._mm(lp, "wq", x) + self._lora_delta(lp, "wq", x,
                                                         lora_ctx)
            k = self._mm(lp, "wk", x) + self._lora_delta(lp, "wk", x,
                                                         lora_ctx)
            v = self._mm(lp, "wv", x) + self._lora_delta(lp, "wv", x,
                                                         lora_ctx)
            if has_bias:
                q = q + lp["bq"]
                k = k + lp["bk"]
                v = v + lp["bv"]
            if c.qkv_clip is not None:
                q = jnp.clip(q, -c.qkv_clip, c.qkv_clip)
                k = jnp.clip(k, -c.qkv_clip, c.qkv_clip)
                v = jnp.clip(v, -c.qkv_clip, c.qkv_clip)
            if c.qk_norm_full:
                # Olmo2: RMSNorm over the whole projection row, before
                # the head reshape.
                q = rms_norm(q, lp["q_norm"], c.rms_norm_eps)
                k = rms_norm(k, lp["k_norm"], c.rms_norm_eps)
            q = q.reshape(T, c.num_q_heads, c.head_dim)
            k = k.reshape(T, c.total_kv_heads, c.head_dim)
            if c.qk_norm:
                # Per-head norm ahead of RoPE (Qwen3 RMS; Persimmon
                # LayerNorm+bias via norm_type/qk_norm_bias).
                q = self._norm(q, lp["q_norm"], lp.get("q_norm_b"))
                k = self._norm(k, lp["k_norm"], lp.get("k_norm_b"))
            v = v.reshape(T, c.total_kv_heads, c.head_dim)
            local_rope = bool(window) and c.rope_theta_local is not None
            if not nope:
                q = apply_rotary(q, local=local_rope)
                k = apply_rotary(k, local=local_rope)
            # One fused Pallas pass writes the step's K/V pages and
            # attends in the same kernel call where the layout permits
            # (mega-kernel descriptor batches); otherwise this is the
            # classic write-then-attend pair.
            k_all, v_all, attn = write_kv_and_attend(
                q, k_all, v_all, k, v, cache_batch, sm_scale=sm_scale,
                layer=layer_idx if cache_idx is None else cache_idx,
                window=window,
                logit_cap=c.attn_logit_softcap, alibi_slopes=slopes,
                sinks=(lp["sinks"] if c.attn_sinks else None))
            attn2d = attn.reshape(T, -1)
            attn_out = (self._mm(lp, "wo", attn2d) +
                        self._lora_delta(lp, "wo", attn2d, lora_ctx))
            if c.attention_out_bias:
                attn_out = attn_out + lp["bo"]
            if "post_attn_ln" in lp:
                # Sandwich/post norm on the attention output (Gemma2,
                # Olmo2).
                attn_out = self._norm(attn_out, lp["post_attn_ln"],
                                      lp.get("post_attn_ln_b"))
            if c.parallel_block:
                # GPT-NeoX/Phi/Cohere: both sub-blocks read the same
                # input state; one residual add.
                x2 = (x if c.shared_block_ln else
                      self._norm(h, lp["post_ln"], lp.get("post_ln_b")))
                mlp_out = mlp(lp, x2, layer_idx, early)
                h = sp(h + rm * (attn_out + mlp_out))
                return h, k_all, v_all
            h = sp(h + rm * attn_out)
            x2 = (self._norm(h, lp["post_ln"], lp.get("post_ln_b"))
                  if c.pre_norm else h)
            mlp_out = mlp(lp, x2, layer_idx, early)
            if "post_ffw_ln" in lp:
                mlp_out = self._norm(mlp_out, lp["post_ffw_ln"],
                                     lp.get("post_ffw_ln_b"))
            h = sp(h + rm * mlp_out)
            return h, k_all, v_all

        windows = self._layer_windows(first_layer, num_layers)
        # Per-layer static attributes segment TOGETHER: the scan
        # pattern keys on (window, nope) pairs so a NoPE/rope layout
        # (SmolLM3, Cohere2, EXAONE-4 hybrids) plans like a window
        # layout.
        if c.nope_layers is not None:
            nope = tuple(bool(c.nope_layers[first_layer + i])
                         for i in range(num_layers))
        else:
            nope = (False, ) * num_layers
        layer_keys = tuple(zip(windows, nope))
        segments = self._plan_window_segments(layer_keys)
        layer_ids = (jnp.arange(num_layers, dtype=jnp.int32)[:, None]
                     + cache_layer_offset)
        if "k_win" in kv_caches:
            assert first_layer == 0 and cache_layer_offset == 0, \
                "cache groups are not sliced per stage"
            return self._run_layers_by_group(
                layer_body, layer_params, per_layer, kv_caches,
                sp(hidden), batch, layer_keys)
        carry = (sp(hidden), kv_caches["k"], kv_caches["v"])
        for start, count, pattern in segments:
            if len(segments) == 1:
                lp_seg, ids_seg = layer_params, layer_ids
            else:
                lp_seg = jax.tree.map(lambda a: a[start:start + count],
                                      layer_params)
                ids_seg = layer_ids[start:start + count]
            period = len(pattern)
            steps = count // period
            lp_seg = jax.tree.map(
                lambda a: a.reshape(steps, period, *a.shape[1:]), lp_seg)
            ids_seg = ids_seg.reshape(steps, period, 1)

            def scan_fn(car, xs, pattern=pattern):
                h, k_all, v_all = car
                lp_grp, id_grp = xs
                for j, (w, np_) in enumerate(pattern):
                    lp_j = jax.tree.map(lambda a: a[j], lp_grp)
                    h, k_all, v_all = layer_body(h, k_all, v_all, lp_j,
                                                 id_grp[j], w,
                                                 nope=np_)
                return (h, k_all, v_all), None

            carry, _ = jax.lax.scan(scan_fn, carry, (lp_seg, ids_seg))
        hidden, k_all, v_all = carry
        return hidden, {"k": k_all, "v": v_all}

    def _run_layers_by_group(self, layer_body, layer_params: dict,
                             per_layer: dict, kv_caches: dict, hidden,
                             batch: AttentionBatch, layer_keys: tuple):
        """The layer walk over two kinds of K/V (``window_group_of``):
        the window layers' pages live in ``k_win`` / ``v_win`` under a
        table of their own (``batch.for_window_group()``), the full
        layers' in ``k`` / ``v``; a layer's row in its group's cache is
        its rank among the layers of its kind. The walk is UNROLLED and
        every large weight is a buffer a layer (``serving_layout``): a
        scan whose step is one period slices the period's weights out of
        their stacks, and on the TPU each slice that feeds a product is
        made as a copy (1.7 GB of temporaries a step at Command A+'s
        widths, whatever the tokens)."""
        window_batch = batch.for_window_group()
        h = hidden
        full = (kv_caches["k"], kv_caches["v"])
        window = (kv_caches["k_win"], kv_caches["v_win"])
        rows = {True: 0, False: 0}
        for i, (w, nope) in enumerate(layer_keys):
            lp = {**{k: v[i] for k, v in layer_params.items()},
                  **{k: v[i] for k, v in per_layer.items()}}
            row = jnp.full((1, ), rows[bool(w)], jnp.int32)
            rows[bool(w)] += 1
            layer_idx = jnp.full((1, ), i, jnp.int32)
            if w:
                h, *window = layer_body(h, *window, lp, layer_idx, w,
                                        nope=nope, cache_idx=row,
                                        cache_batch=window_batch)
            else:
                h, *full = layer_body(h, *full, lp, layer_idx, w,
                                      nope=nope, cache_idx=row)
        return h, {"k": full[0], "v": full[1], "k_win": window[0],
                   "v_win": window[1]}

    def forward(
        self,
        params: dict,
        kv_caches: dict,
        token_ids: jax.Array,  # [T] int32
        batch: AttentionBatch,
    ) -> tuple[jax.Array, dict]:
        """Run the decoder over a flat ragged token batch; returns final
        hidden states [T, H] and the updated KV caches."""
        hidden = self.embed(params, token_ids, batch.positions)
        if getattr(batch, "mm_embeds", None) is not None:
            # Image placeholder positions take their pre-computed
            # encoder rows (reference: the inputs_embeds merge of
            # llava-style models, get_input_embeddings + masked_scatter
            # in vllm/model_executor/models/llava.py). The override
            # rows arrive post-projector, so no embed scaling applies.
            hidden = jnp.where(batch.mm_mask[:, None],
                               batch.mm_embeds.astype(hidden.dtype),
                               hidden)
        dense = params.get("layers_dense")
        if dense is not None:
            # Mixed layouts (Ernie-4.5-MoE / GLM-4-MoE style): a dense
            # PREFIX of plain decoder layers runs first from its own
            # stacked subtree, then the sparse stack continues with its
            # cache rows offset past the prefix.
            k = jax.tree_util.tree_leaves(dense)[0].shape[0]
            hidden, kv_caches = self.run_layers(dense, kv_caches,
                                                hidden, batch)
            return self.run_layers(params["layers"], kv_caches, hidden,
                                   batch, first_layer=k,
                                   cache_layer_offset=k)
        return self.run_layers(params["layers"], kv_caches, hidden, batch)

    def compute_logits(self, params: dict,
                       hidden: jax.Array) -> jax.Array:
        """Final norm + LM head on selected rows; fp32 logits."""
        if self.cfg.final_norm:
            x = self._norm(hidden, params["final_ln"],
                           params.get("final_ln_b"))
        else:
            x = hidden
        logits = jnp.dot(x, params["lm_head"],
                         preferred_element_type=jnp.float32)
        if "lm_head_b" in params:
            logits = logits + params["lm_head_b"].astype(jnp.float32)
        if self.cfg.logit_multiplier != 1.0:
            # Cohere logit_scale / Granite 1/logits_scaling.
            logits = logits * self.cfg.logit_multiplier
        cap = self.cfg.final_logit_softcap
        if cap:
            # Gemma2 final soft-capping (monotone: greedy order kept,
            # logprobs match HF).
            logits = cap * jnp.tanh(logits / cap)
        return logits
