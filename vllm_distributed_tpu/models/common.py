"""Shared model-layer math: RMSNorm, rotary embeddings, attention batch
descriptor.

Equivalents of the reference's vllm/model_executor/layers/{layernorm.py,
rotary_embedding.py}; on TPU these are plain jnp expressions XLA fuses into
the surrounding matmuls (SURVEY.md §2.7: "XLA fuses this natively").
"""

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass
class TknpAttentionBatch:
    """Per-token-parallel-rank attention metadata, stacked on a leading
    rank axis that is sharded over the ``token`` mesh axis inside
    shard_map — each rank reads only its own slab.

    TPU analogue of the fork's TokenParallelMetadata + _tknp_slicing
    (vllm/v1/worker/gpu_model_runner.py:334,392): the host slices the
    step's metadata per rank; page ids are LOCAL to the rank's shard of
    the page-sharded KV cache, and tokens of requests owned by other
    ranks appear as padding (slot -1), so each rank computes attention
    only for its own requests and a psum over the token axis merges the
    disjoint outputs.
    """

    # [K, T] int32 local flat slots; -1 where this rank does not own the
    # token's request.
    slot_mapping: jax.Array
    # [K, max_reqs, pages_per_req] int32 rank-local page tables (rows of
    # non-owned requests are garbage; never dereferenced).
    block_tables: jax.Array
    # [K, max_reqs, 4] / [K, 1]: per-rank compacted seq runs.
    seq_info: jax.Array
    num_seqs: jax.Array
    # [K, G, 4] / [K, 1]: per-rank KV-write runs with local page ids.
    kv_runs: jax.Array
    num_kv_runs: jax.Array
    # [K, P, 3] / [K, max_reqs]: per-rank mega-kernel partition
    # descriptors over the rank's compacted seq runs (None routes the
    # legacy per-composition kernels).
    desc: Optional[jax.Array] = None
    decode_list: Optional[jax.Array] = None


jax.tree_util.register_dataclass(
    TknpAttentionBatch,
    data_fields=[f.name for f in dataclasses.fields(TknpAttentionBatch)],
    meta_fields=[],
)


@dataclass
class AttentionBatch:
    """Flat ragged batch descriptor consumed by every attention layer.

    Built once per step by the model runner (equivalent of the reference's
    per-backend AttentionMetadata, v1/attention/backends/pallas.py
    PallasMetadata). Carries both token-centric metadata (XLA reference
    attention path) and sequence-centric run metadata (Pallas kernel path).
    """

    # [T] int32: owning request row for each token.
    req_idx: jax.Array
    # [T] int32: absolute sequence position of each token.
    positions: jax.Array
    # [T] int32: flat KV slot (page * page_size + offset), -1 for padding.
    slot_mapping: jax.Array
    # [max_reqs, pages_per_req] int32 page table.
    block_tables: jax.Array
    # [max_reqs] int32 total context length per request (0 = inactive).
    seq_lens: jax.Array
    # [max_reqs, 4] int32 per-sequence runs in batch order:
    # (q_start, q_len, kv_len_incl_new, batch_row). Rows >= num_seqs zero.
    seq_info: Optional[jax.Array] = None
    # [1] int32: number of active runs in seq_info.
    num_seqs: Optional[jax.Array] = None
    # [G, 4] int32 page-write runs for the Pallas KV-write kernel:
    # (page, off_start, window_start, run_len); see ops/pallas_kv_write.py.
    kv_runs: Optional[jax.Array] = None
    # [1] int32: number of active rows in kv_runs.
    num_kv_runs: Optional[jax.Array] = None
    # Per-rank stacked metadata when token parallelism is on (see
    # TknpAttentionBatch); None otherwise.
    tknp: Optional[TknpAttentionBatch] = None
    # Multi-LoRA token routing (None when LoRA is disabled): tokens
    # sorted by adapter slot, consumed by the grouped-GEMM LoRA apply
    # (models/lora.py; the TPU answer to the reference's punica SGMV).
    lora: Optional["LoraBatch"] = None
    # Cascade attention: page ids of the batch-wide shared prefix
    # ([S] int32, static S; None disables — see
    # ops/attention.cascade_ragged_paged_attention).
    cascade_shared_ids: Optional[jax.Array] = None
    # Multimodal: [T, H] embedding-override rows + [T] bool mask
    # (placeholder positions take the image rows; None on text-only
    # steps — a distinct pytree, so mm steps compile their own variant
    # like every other static flag).
    mm_embeds: Optional[jax.Array] = None
    mm_mask: Optional[jax.Array] = None
    # M-RoPE (Qwen2-VL): [T, 3] (temporal, height, width) rotary ids;
    # None = all three equal `positions` (plain rope — exact for
    # text-only requests). Reference: the mrope position ids of
    # model_executor/models/qwen2_vl.py get_rope_index.
    mrope_positions: Optional[jax.Array] = None
    # Mega-kernel partition descriptor ([P, 3] int32) + decode row list
    # ([max_reqs] int32): the host-built program partition consumed by
    # ops/pallas_attention.py's unified kernel (kv-write runs, prefill
    # q-tiles, SB decode groups — see the descriptor contract there).
    # None routes legacy per-composition dispatch (in-jit batches built
    # by the multi-step scan / EAGLE, and MLA models).
    attn_desc: Optional[jax.Array] = None
    decode_list: Optional[jax.Array] = None
    # The window group's page ids (None: the model has one cache group).
    # A model that mixes window and full layers keeps a pool and a table
    # for each kind (core/kv_cache_manager.py GroupedKVCacheManager);
    # the tables are position-aligned, so everything else of the batch
    # (runs, descriptor, positions) serves both, and only what names a
    # page differs: ``slot_mapping``, ``block_tables`` and the page
    # column of ``kv_runs``. ``for_window_group`` swaps them in.
    window_slot_mapping: Optional[jax.Array] = None
    window_block_tables: Optional[jax.Array] = None
    window_kv_runs: Optional[jax.Array] = None
    # Static: per-sequence query-length bucket (1 for pure decode);
    # changing it recompiles, like every other shape bucket. Ignored by
    # the unified kernel (pinned to 1 by the runner when a descriptor is
    # present), still consulted by the legacy dispatch and MLA.
    max_q: int = 1
    # Static mega-kernel tile parameters (prefill_tile_size /
    # decode_group_size): fixed per model+sharding, passed through the
    # batch so the host descriptor builder and the kernel can never
    # disagree. 0 when no descriptor is attached.
    attn_bq: int = 0
    attn_sb: int = 0

    def for_window_group(self) -> "AttentionBatch":
        """The batch as a window layer's cache ops read it: the window
        group's pages where the full group's stood."""
        return dataclasses.replace(
            self, slot_mapping=self.window_slot_mapping,
            block_tables=self.window_block_tables,
            kv_runs=self.window_kv_runs)


@dataclasses.dataclass
class LoraBatch:
    """Token->adapter-slot grouping, built once per step and shared by
    every LoRA-wrapped matmul in the forward."""

    # [T] int32 permutation sorting tokens by adapter slot.
    order: jax.Array
    # [T] int32 inverse permutation (back to batch order).
    inv: jax.Array
    # [S] int32 tokens per slot in sorted order (S = max_loras + 1).
    group_sizes: jax.Array
    # [T] float32 per-token adapter scaling (alpha/r; 0 for slot 0), in
    # SORTED order.
    scaling: jax.Array


jax.tree_util.register_dataclass(
    LoraBatch,
    data_fields=[f.name for f in dataclasses.fields(LoraBatch)],
    meta_fields=[],
)


jax.tree_util.register_dataclass(
    AttentionBatch,
    data_fields=[
        f.name for f in dataclasses.fields(AttentionBatch)
        if f.name not in ("max_q", "attn_bq", "attn_sb")
    ],
    meta_fields=["max_q", "attn_bq", "attn_sb"],
)


def alibi_slopes(num_heads: int) -> tuple:
    """Standard ALiBi head slopes (geometric 2^(-8i/n) ladder, with the
    interleaved extension for non-power-of-two head counts; reference:
    the _get_alibi_slopes helpers of models/bloom.py / mpt.py — the
    published train-short-test-long recipe)."""
    import math

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return tuple(pow2(num_heads))
    closest = 2 ** math.floor(math.log2(num_heads))
    return tuple(pow2(closest) +
                 pow2(2 * closest)[0::2][:num_heads - closest])


def subconfig_get(cfg, key, default):
    """Read a key from an HF sub-config that may be a dict or an
    attribute-style object (MPT attn_config, DBRX attn/ffn_config)."""
    if cfg is None:
        return default
    if isinstance(cfg, dict):
        return cfg.get(key, default)
    return getattr(cfg, key, default)


def rename_tensors(tensors: dict, table) -> dict:
    """Substring-rename checkpoint tensor names onto the canonical
    layout (shared by the family loaders; rules apply in order)."""
    out = {}
    for name, t in tensors.items():
        for old, new in table:
            if old in name:
                name = name.replace(old, new)
        out[name] = t
    return out


def rms_norm(x: jax.Array, weight: jax.Array,
             eps: float = 1e-6) -> jax.Array:
    """Llama RMSNorm; accumulate in fp32 regardless of activation dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def make_inv_freq(head_dim: int, rope_theta: float,
                  rope_scaling: dict | None = None) -> jax.Array:
    """Rotary inverse frequencies, with Llama-3.1-style piecewise NTK
    scaling when ``rope_scaling["rope_type"] == "llama3"`` (reference:
    vllm/model_executor/layers/rotary_embedding.py Llama3RotaryEmbedding)."""
    inv_freq = 1.0 / (rope_theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    rtype = (rope_scaling or {}).get(
        "rope_type", (rope_scaling or {}).get("type"))
    if rope_scaling and rtype == "linear":
        # Position-interpolation scaling (Gemma3 global layers).
        inv_freq = inv_freq / rope_scaling["factor"]
    if rope_scaling and rope_scaling.get("rope_type",
                                        rope_scaling.get("type")) == "llama3":
        factor = rope_scaling["factor"]
        low = rope_scaling["low_freq_factor"]
        high = rope_scaling["high_freq_factor"]
        orig = rope_scaling["original_max_position_embeddings"]
        wavelen = 2 * jnp.pi / inv_freq
        low_wavelen = orig / low
        high_wavelen = orig / high
        # Long wavelengths scaled down by factor; short kept; smooth ramp
        # in between.
        smooth = (orig / wavelen - low) / (high - low)
        scaled = jnp.where(
            wavelen > low_wavelen, inv_freq / factor,
            jnp.where(wavelen < high_wavelen, inv_freq,
                      (1 - smooth) * inv_freq / factor + smooth * inv_freq))
        inv_freq = scaled
    if rope_scaling and rtype == "yarn":
        # NTK-by-parts scaling (gpt-oss, Qwen long-context checkpoints);
        # the attention factor is applied by compute_rope_cos_sin.
        orig = rope_scaling.get("original_max_position_embeddings")
        if not orig:
            raise ValueError(
                "yarn rope_scaling needs original_max_position_"
                "embeddings")
        inv_freq, _ = yarn_inv_freq(head_dim, rope_theta, rope_scaling,
                                    orig)
    if rope_scaling and rtype == "longrope":
        # Phi-3 LongRoPE: per-dim rescale factors, long set active when
        # the serving window exceeds the pretraining window (reference:
        # modeling_rope_utils._compute_longrope_parameters; from_hf_
        # config folds the two window fields into the dict).
        orig = rope_scaling.get("original_max_position_embeddings")
        maxp = rope_scaling.get("max_position_embeddings")
        if not orig or not maxp:
            raise ValueError(
                "longrope rope_scaling needs original_ and "
                "max_position_embeddings (from_hf_config adds them)")
        ext = (rope_scaling["long_factor"] if maxp > orig
               else rope_scaling["short_factor"])
        inv_freq = 1.0 / (
            jnp.asarray(ext, jnp.float32) * rope_theta ** (
                jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    return inv_freq


def _rope_attention_factor(rope_scaling: dict | None) -> float:
    """YaRN's mscale: multiplies cos/sin (reference: the
    attention_scaling of modeling_rope_utils._compute_yarn_parameters).
    Shares yarn_inv_freq's formula (yarn_attention_factor)."""
    import math
    rtype = (rope_scaling or {}).get(
        "rope_type", (rope_scaling or {}).get("type"))
    if rope_scaling and rtype == "yarn":
        return yarn_attention_factor(rope_scaling)
    if rope_scaling and rtype == "longrope":
        af = rope_scaling.get("attention_factor")
        if af is not None:
            return float(af)
        orig = rope_scaling["original_max_position_embeddings"]
        maxp = rope_scaling["max_position_embeddings"]
        # Phi-3.5-MoE carries explicit per-regime mscales.
        mscale = (rope_scaling.get("long_mscale") if maxp > orig
                  else rope_scaling.get("short_mscale"))
        if mscale:
            return float(mscale)
        factor = rope_scaling.get("factor") or maxp / orig
        if factor <= 1.0:
            return 1.0
        return math.sqrt(1 + math.log(factor) / math.log(orig))
    return 1.0


def compute_rope_cos_sin(positions: jax.Array, head_dim: int,
                         rope_theta: float,
                         rope_scaling: dict | None = None,
                         dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given positions, HF-llama layout: inv_freq
    over even dims, duplicated across both halves of the head."""
    inv_freq = make_inv_freq(head_dim, rope_theta, rope_scaling)
    att = _rope_attention_factor(rope_scaling)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [T, D]
    return (jnp.cos(emb).astype(dtype) * att,
            jnp.sin(emb).astype(dtype) * att)


def compute_mrope_cos_sin(mrope_positions: jax.Array,  # [T, 3]
                          head_dim: int, rope_theta: float,
                          sections: tuple,
                          dtype=jnp.float32) -> tuple[jax.Array,
                                                      jax.Array]:
    """Multimodal (3D) rotary tables, Qwen2-VL layout (reference:
    apply_multimodal_rotary_pos_emb of qwen2_vl.py): frequency index i
    reads its angle from the (temporal | height | width) position id
    its ``mrope_section`` assigns it; text-only ids (all three equal)
    reduce exactly to plain rope."""
    inv_freq = make_inv_freq(head_dim, rope_theta, None)
    half = inv_freq.shape[0]
    assert sum(sections) == half, (sections, half)
    # [3, T, half] angle per position stream.
    freqs = (mrope_positions.astype(jnp.float32).T[:, :, None] *
             inv_freq[None, None, :])
    parts = []
    start = 0
    for k, width in enumerate(sections):
        parts.append(freqs[k, :, start:start + width])
        start += width
    sel = jnp.concatenate(parts, axis=-1)  # [T, half]
    emb = jnp.concatenate([sel, sel], axis=-1)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(q: jax.Array, k: jax.Array, cos: jax.Array,
               sin: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Apply rotary embedding; q/k are [T, heads, head_dim], cos/sin [T, D].
    Matches HF transformers' apply_rotary_pos_emb exactly (parity tests
    depend on bit-level agreement up to dtype rounding)."""
    cos = cos[:, None, :]
    sin = sin[:, None, :]
    q_out = q * cos + _rotate_half(q) * sin
    k_out = k * cos + _rotate_half(k) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)


def swiglu(x: jax.Array, gate_w: jax.Array, up_w: jax.Array,
           down_w: jax.Array, act=jax.nn.silu) -> jax.Array:
    """Gated MLP (reference: csrc/activation_kernels.cu fused silu-mul /
    gelu variants; XLA fuses the elementwise chain into the matmuls)."""
    gate = act(x @ gate_w)
    return (gate * (x @ up_w)) @ down_w


def yarn_attention_factor(scaling: dict) -> float:
    """YaRN's attention (mscale) factor — the cos/sin multiplier
    (reference: modeling_rope_utils._compute_yarn_parameters)."""
    import math
    factor = scaling["factor"]
    af = scaling.get("attention_factor")
    if af is not None:
        return float(af)

    def g(scale: float, m: float = 1.0) -> float:
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    mscale = scaling.get("mscale")
    msd = scaling.get("mscale_all_dim")
    if mscale and msd:
        return float(g(factor, mscale) / g(factor, msd))
    return float(g(factor))


def yarn_inv_freq(head_dim: int, rope_theta: float, scaling: dict,
                  max_position_embeddings: int) -> tuple[jax.Array, float]:
    """YaRN NTK-by-parts inverse frequencies -> (inv_freq, attention
    factor). Mirrors transformers' modeling_rope_utils.
    _compute_yarn_parameters (the init the reference's
    DeepseekScalingRotaryEmbedding shares, vllm/model_executor/layers/
    rotary_embedding.py yarn_* helpers); the attention factor multiplies
    cos/sin downstream."""
    import math
    factor = scaling["factor"]
    orig = (scaling.get("original_max_position_embeddings")
            or max_position_embeddings)
    attention_factor = yarn_attention_factor(scaling)
    beta_fast = scaling.get("beta_fast") or 32
    beta_slow = scaling.get("beta_slow") or 1

    def corr_dim(num_rotations: float) -> float:
        return (head_dim * math.log(orig / (num_rotations * 2 * math.pi))
                ) / (2 * math.log(rope_theta))

    low, high = corr_dim(beta_fast), corr_dim(beta_slow)
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001  # avoid the ramp singularity
    pos_freqs = rope_theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    extrap = 1.0 / pos_freqs
    interp = 1.0 / (factor * pos_freqs)
    ramp = jnp.clip(
        (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) /
        (high - low), 0, 1)
    inv_freq = interp * ramp + extrap * (1 - ramp)
    return inv_freq, float(attention_factor)


def compute_rope_cos_sin_pairwise(
        positions: jax.Array, head_dim: int, rope_theta: float,
        rope_scaling: dict | None = None,
        max_position_embeddings: int = 4096,
) -> tuple[jax.Array, jax.Array]:
    """cos/sin [T, head_dim//2] for PAIRWISE (complex) rotary — the form
    DeepSeek MLA applies to its decoupled rope dims (HF
    modeling_deepseek_v2.apply_rotary_emb on freqs_cis; V3's
    de-interleave variant is score-equivalent because the same
    permutation hits q and k). YaRN scaling folds its attention factor
    into the returned tables, matching HF's freqs_cis * scaling."""
    rtype = (rope_scaling or {}).get(
        "rope_type", (rope_scaling or {}).get("type"))
    if rope_scaling and rtype == "yarn":
        inv_freq, att = yarn_inv_freq(head_dim, rope_theta, rope_scaling,
                                      max_position_embeddings)
    else:
        inv_freq = make_inv_freq(head_dim, rope_theta, rope_scaling)
        att = 1.0
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(freqs) * att, jnp.sin(freqs) * att


def apply_rope_pairwise(x: jax.Array, cos: jax.Array,
                        sin: jax.Array) -> jax.Array:
    """Rotate adjacent pairs (x[2i], x[2i+1]) of [T, heads, D] by the
    i-th angle — HF DeepSeek's complex-multiply rope."""
    T, H, D = x.shape
    xr = x.astype(jnp.float32).reshape(T, H, D // 2, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    c = cos[:, None, :]
    s = sin[:, None, :]
    out = jnp.stack([x0 * c - x1 * s, x0 * s + x1 * c], axis=-1)
    return out.reshape(T, H, D).astype(x.dtype)


def apply_rope_single(x: jax.Array, cos: jax.Array,
                      sin: jax.Array) -> jax.Array:
    """Rotate-half rope on one [T, heads, D] tensor (partial-rotary
    callers rope q and k slices independently)."""
    c = cos[:, None, :]
    s = sin[:, None, :]
    return (x * c + _rotate_half(x) * s).astype(x.dtype)
