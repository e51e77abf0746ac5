"""Weight loading (reference: vllm/model_executor/model_loader/ — default
safetensors streaming loader, dummy_loader.py for perf tests, tpu.py).

Loads HF checkpoints from a local directory (safetensors shards or a
pytorch_model.bin fallback) into the stacked JAX parameter tree, placing
shards directly with their NamedShardings so each device only materializes
its slice (the GSPMD analogue of the reference's per-rank weight_loader
callbacks on ColumnParallelLinear et al.).
"""

import glob
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.models.llama import LlamaArchConfig
from vllm_distributed_tpu.models.registry import resolve_architecture

logger = init_logger(__name__)


def _dtype_from_str(name: str):
    return {
        "bfloat16": jnp.bfloat16,
        "float16": jnp.float16,
        "float32": jnp.float32,
    }[name]


def load_hf_state_dict(model_path: str,
                       prefixes: tuple = ()) -> dict[str, np.ndarray]:
    """Read a local HF checkpoint into numpy; with ``prefixes``, only
    tensors whose name starts with one of them (partial reads keep the
    vision-tower load off the full-checkpoint path)."""

    def want(name: str) -> bool:
        return not prefixes or name.startswith(prefixes)

    if model_path.endswith(".gguf"):
        # GGUF single-file checkpoints (reference: gguf_loader.py);
        # dequantized host-side into the standard fp path.
        from vllm_distributed_tpu.models.gguf import (
            gguf_to_hf_state_dict, read_gguf)
        meta, raw = read_gguf(model_path)
        return {k: v for k, v in
                gguf_to_hf_state_dict(meta, raw).items() if want(k)}

    st_files = sorted(glob.glob(os.path.join(model_path, "*.safetensors")))
    tensors: dict[str, np.ndarray] = {}
    if st_files:
        from safetensors import safe_open
        for path in st_files:
            with safe_open(path, framework="np") as f:
                for name in f.keys():
                    if want(name):
                        tensors[name] = f.get_tensor(name)
        return tensors
    bin_path = os.path.join(model_path, "pytorch_model.bin")
    if os.path.exists(bin_path):
        import torch
        sd = torch.load(bin_path, map_location="cpu", weights_only=True)
        return {k: v.float().numpy() for k, v in sd.items() if want(k)}
    raise FileNotFoundError(
        f"no safetensors/pytorch_model.bin under {model_path}")


def get_model(config: EngineConfig, mesh,
              shard: bool = True) -> tuple[Any, dict]:
    """Build the model class for the config and return (model, params) with
    params placed on the mesh according to the model's PartitionSpecs.
    ``shard=False`` returns host-resident params (the pipeline-parallel
    runner slices layers per stage and places each slice itself)."""
    hf_config = config.model_config.maybe_load_hf_config()
    model_cls = resolve_architecture(hf_config)
    dtype = _dtype_from_str(config.model_config.dtype)
    arch = LlamaArchConfig.from_hf_config(
        model_cls.arch_config_source(hf_config), dtype=dtype)
    model_cls.configure_arch(arch, hf_config)
    arch.expert_parallel = config.parallel_config.enable_expert_parallel
    if getattr(arch, "dense_prefix", 0):
        if config.parallel_config.pipeline_parallel_size > 1:
            raise ValueError(
                "mixed dense/sparse MoE layouts are not wired for "
                "pipeline parallelism (stage slicing assumes one "
                "uniform layer stack)")
        if config.lora_config.enable_lora:
            raise ValueError(
                "LoRA for mixed dense/sparse MoE layouts is not wired "
                "(adapter buffers assume one uniform layer stack)")
    if (config.parallel_config.enable_sequence_parallel
            and config.parallel_config.token_parallel_size > 1):
        raise ValueError(
            "sequence parallelism under token parallelism is not wired "
            "(the TKNP attention shard_maps assume token-replicated "
            "activations); disable one of the two")
    if (config.parallel_config.enable_sequence_parallel
            and config.parallel_config.pipeline_parallel_size > 1):
        raise ValueError(
            "sequence parallelism under pipeline parallelism is not "
            "wired (the SP constraint binds the full mesh, but PP "
            "stages jit over per-stage sub-meshes); disable one")
    arch.sequence_parallel = (
        config.parallel_config.enable_sequence_parallel
        and config.parallel_config.tensor_parallel_size > 1)
    arch.quantization = config.model_config.quantization
    qcfg = getattr(hf_config, "quantization_config", None)
    if qcfg is not None:
        get = (qcfg.get if isinstance(qcfg, dict)
               else lambda k, d=None: getattr(qcfg, k, d))
        gs = int(get("group_size", get("q_group_size", 0)) or 0)
        if gs > 0:
            # int4g reuses the checkpoint's own group lattice so the
            # re-quantization after the load-time dequant is lossless.
            arch.quant_group_size = gs
    kv_dtype = config.cache_config.cache_dtype
    if kv_dtype not in ("auto", None):
        if kv_dtype not in ("fp8", "fp8_e4m3", "fp8_e5m2"):
            raise ValueError(
                f"unsupported kv cache dtype {kv_dtype!r} "
                "(supported: auto, fp8, fp8_e4m3, fp8_e5m2)")
        if (getattr(model_cls, "STATEFUL", False)
                or getattr(model_cls, "ENCODER_ONLY", False)
                or getattr(arch, "mla", False)):
            raise ValueError(
                "--kv-cache-dtype fp8 is wired for standard paged K/V "
                "only (SSM state rows / MLA latent pages / encoder "
                "models keep the model dtype); drop the flag")
        if config.kv_transfer_config.kv_connector:
            raise ValueError(
                "--kv-cache-dtype fp8 with KV transfer is not wired "
                "(the connectors' wire layout carries model-dtype "
                "pages); drop one")
        if config.parallel_config.token_parallel_size > 1:
            raise ValueError(
                "--kv-cache-dtype fp8 under token parallelism is not "
                "wired (the per-rank attention path has no fp8 "
                "dequant); drop one")
        arch.kv_cache_dtype = (jnp.float8_e5m2
                               if kv_dtype == "fp8_e5m2"
                               else jnp.float8_e4m3fn)
        logger.warning(
            "fp8 KV cache: attention and cache writes run the XLA "
            "path (the Pallas kernels' fp8 dequant is a follow-up) — "
            "halved KV HBM, some per-step throughput cost on TPU")
    if arch.quantization == "w8a8" and getattr(arch, "num_experts", 0):
        # The MoE expert dots (the dominant FLOPs) run through
        # ragged_dot/shard_map paths that dequantize weights (w8a16);
        # serving "w8a8" there would silently not apply where its
        # benefit lies — refuse instead.
        raise ValueError(
            "w8a8 is not wired for MoE expert layers yet; use "
            "--quantization int8 (weight-only) for MoE models")
    if getattr(arch, "moe_bias", False) and (
            config.parallel_config.enable_expert_parallel
            or config.parallel_config.num_redundant_experts):
        # The EP all-to-all / EPLB paths run the plain SwiGLU expert
        # kernels without per-expert biases or the clamped GLU
        # (gpt-oss); serving through them would be silently wrong.
        raise ValueError(
            "expert parallelism / EPLB for biased-expert MoE (gpt-oss) "
            "is not wired yet; disable enable_expert_parallel / "
            "num_redundant_experts")
    if arch.num_experts and config.parallel_config.num_redundant_experts:
        arch.num_physical_experts = (
            arch.num_experts +
            config.parallel_config.num_redundant_experts)
    if arch.num_experts and arch.expert_parallel:
        ep = config.parallel_config.tensor_parallel_size
        arch.expert_parallel_ranks = ep
        physical = arch.num_physical_experts or arch.num_experts
        if physical % ep != 0:
            raise ValueError(
                f"expert parallelism needs the physical expert count "
                f"({physical} = {arch.num_experts} experts + "
                f"{physical - arch.num_experts} redundant) divisible by "
                f"tensor_parallel_size={ep}")
    if config.lora_config.enable_lora:
        arch.max_loras = config.lora_config.max_loras
        arch.max_lora_rank = config.lora_config.max_lora_rank
    if getattr(arch, "stateful", False):
        # Stateful (SSM) families: one state row per schedulable request
        # (the TPU form of the reference's MambaSpec one-block-per-
        # request cache, v1/kv_cache_interface.py).
        arch.state_slots = config.scheduler_config.max_num_seqs
        if config.speculative_config.num_speculative_tokens:
            # Draft rejection rolls num_computed_tokens back, but a
            # recurrence's state row cannot rewind past verified tokens.
            raise ValueError(
                "speculative decoding over stateful (SSM) models is not "
                "wired (rejected drafts cannot rewind recurrence state); "
                "disable speculative decoding")
        if config.kv_transfer_config.kv_connector:
            raise ValueError(
                "KV transfer for stateful (SSM) models is not wired "
                "(their state lives in per-request rows, not pages); "
                "drop the kv-transfer config")
        if config.parallel_config.token_parallel_size > 1:
            raise ValueError(
                "token parallelism over stateful (SSM) models is not "
                "wired (state rows are not partitioned per rank); "
                "disable one")
        if config.parallel_config.pipeline_parallel_size > 1:
            raise ValueError(
                "pipeline parallelism over stateful (SSM) models is "
                "not wired (hybrid per-kind stacks don't slice per "
                "stage); disable one")
        if config.parallel_config.enable_expert_parallel:
            raise ValueError(
                "expert parallelism over stateful hybrid models is not "
                "wired; disable enable_expert_parallel")
        if config.parallel_config.num_redundant_experts:
            raise ValueError(
                "EPLB redundant experts over stateful hybrid models "
                "are not wired; drop num_redundant_experts")
        if (getattr(arch, "kda", False)
                and config.parallel_config.tensor_parallel_size > 1):
            raise ValueError(
                "tensor parallelism over gated delta-rule (KDA) layers "
                "is not wired (the state kernels of ops/kda.py run "
                "unsharded); set tensor_parallel_size=1")
        if (getattr(arch, "power_retention", False)
                and config.parallel_config.tensor_parallel_size > 1):
            raise ValueError(
                "tensor parallelism over power-retention layers is not "
                "wired (the state kernels of ops/power_retention.py run "
                "unsharded); set tensor_parallel_size=1")
        if (getattr(arch, "cca", False)
                and config.parallel_config.tensor_parallel_size > 1):
            raise ValueError(
                "tensor parallelism over compressed convolutional "
                "attention (Zaya) is not wired (the carried rows and the "
                "router's state are unsharded); set "
                "tensor_parallel_size=1")
    if getattr(arch, "window_group", None):
        # Window and full layers in cache groups of their own
        # (models/cohere_moe.py, core/kv_cache_manager.py
        # GroupedKVCacheManager): the window group's pool is all it can
        # ever hold, a function of the configuration that the scheduler
        # computes alike.
        from vllm_distributed_tpu.core.kv_cache_utils import \
            config_window_group_pool_blocks
        sc, pc = config.scheduler_config, config.parallel_config
        arch.window_group_pages = config_window_group_pool_blocks(
            config, arch.window_group)
        arch.window_group_page_size = config.cache_config.block_size
        for bad, what in (
                (pc.token_parallel_size > 1, "token parallelism"),
                (pc.pipeline_parallel_size > 1, "pipeline parallelism"),
                (config.kv_transfer_config.kv_connector, "KV transfer"),
                (config.speculative_config.num_speculative_tokens,
                 "speculative decoding"),
                (sc.num_scheduler_steps > 1, "multi-step scheduling"),
                (kv_dtype not in ("auto", None), "an fp8 KV cache"),
                (config.lora_config.enable_lora, "LoRA")):
            if bad:
                raise ValueError(
                    f"a model that keeps window and full layers in cache "
                    f"groups of their own cannot be combined with {what} "
                    "(each builds its batches over ONE page table)")
    if arch.pos_embedding == "learned":
        capacity = arch.max_position_embeddings - arch.pos_offset
        if config.scheduler_config.max_model_len > capacity:
            # A clip would silently reuse the last table row past the
            # window (degenerate output, no error) — refuse instead.
            raise ValueError(
                f"max_model_len={config.scheduler_config.max_model_len} "
                f"exceeds the model's learned-position capacity "
                f"({capacity}); lower --max-model-len")
    if getattr(arch, "encoder_only", False):
        pc = config.parallel_config
        bad = []
        if pc.pipeline_parallel_size > 1:
            bad.append("pipeline parallelism")
        if pc.token_parallel_size > 1:
            bad.append("token parallelism")
        if pc.enable_sequence_parallel:
            bad.append("sequence parallelism")
        if config.lora_config.enable_lora:
            bad.append("LoRA")
        if config.speculative_config.num_speculative_tokens:
            bad.append("speculative decoding")
        if config.kv_transfer_config.kv_connector:
            bad.append("KV transfer")
        if bad:
            raise ValueError(
                f"encoder-only models do not compose with "
                f"{', '.join(bad)} (no KV cache, no decode steps); "
                f"drop those options")
    if ((arch.sliding_window or arch.window_pattern
         or arch.attn_logit_softcap or arch.alibi or arch.attn_sinks)
            and config.parallel_config.token_parallel_size > 1):
        raise ValueError(
            "sliding-window attention / attention logit soft-capping / "
            "ALiBi under token parallelism is not wired yet (the "
            "per-rank attention path carries none of these); serve "
            "this model without token parallelism")
    if getattr(arch, "mla", False):
        # MLA family intersections not wired this round; reject with
        # clear errors instead of silently mis-serving.
        if config.parallel_config.token_parallel_size > 1:
            raise ValueError(
                "MLA (DeepSeek) under token parallelism is not wired "
                "yet (per-rank latent page pools); disable one")
        if config.parallel_config.num_redundant_experts:
            raise ValueError(
                "EPLB redundant experts are not wired for the DeepSeek "
                "family yet")
        # TPLA (ops/mla.py): shard the latent cache over the TP axis so
        # the per-rank latent pool is ~1/TP the bytes. Decided ONCE here
        # (weights, cache layout and attention all key on it); VDT_TPLA=0
        # reverts wholesale to the replicated layout.
        from vllm_distributed_tpu import envs as _envs
        from vllm_distributed_tpu.ops.mla import tpla_applicable
        mla_tp = config.parallel_config.tensor_parallel_size
        arch.tpla_shards = 1
        if _envs.VDT_TPLA and mla_tp > 1:
            if config.parallel_config.pipeline_parallel_size > 1:
                logger.info(
                    "TPLA disabled under pipeline parallelism (stage "
                    "sub-meshes don't carry the latent shard_map); "
                    "serving the replicated latent layout")
            elif not tpla_applicable(arch.kv_lora_rank, mla_tp):
                logger.warning(
                    "TPLA disabled: kv_lora_rank=%d does not divide "
                    "tensor_parallel_size=%d; serving the replicated "
                    "latent layout", arch.kv_lora_rank, mla_tp)
            else:
                arch.tpla_shards = mla_tp
                logger.info(
                    "TPLA: latent cache sharded %d ways over the TP "
                    "axis (%d lanes/rank of kv_lora_rank=%d + %d rope "
                    "lanes replicated)", mla_tp,
                    arch.kv_lora_rank // mla_tp, arch.kv_lora_rank,
                    arch.qk_rope_head_dim)
    if getattr(arch, "experts_held", None) is not None:
        # One chip's share of an expert layer (models/deepseek.py): the
        # program computes that share and nothing of the other chips'.
        pc = config.parallel_config
        for bad, what in (
                (pc.enable_expert_parallel,
                 "enable_expert_parallel (the share is one rank of an "
                 "expert-parallel deployment already)"),
                (getattr(arch, "tpla_shards", 1) > 1,
                 "TPLA (set VDT_TPLA=0 or tensor_parallel_size=1)"),
                (pc.pipeline_parallel_size > 1,
                 "pipeline parallelism (the layers left out of the "
                 "share's file are the further stages)"),
                (getattr(arch, "stateful", False)
                 and pc.tensor_parallel_size > 1,
                 "tensor parallelism over a state-space stack (the "
                 "share's mixers are whole on each chip)")):
            if bad:
                raise ValueError(
                    f"expert_share (config.json) cannot be combined "
                    f"with {what}")
    # KV-head replication when TP exceeds the checkpoint's KV-head count
    # (reference: QKVParallelLinear kv replication, layers/linear.py):
    # repeat heads to the lcm so the kv-head dim divides the model axis.
    tp = config.parallel_config.tensor_parallel_size
    if getattr(arch, "mla", False):
        pass  # latent cache is MQA-shared; no KV-head replication
    elif arch.num_kv_heads % tp != 0:
        import math
        arch.num_kv_head_replicas = (
            math.lcm(arch.num_kv_heads, tp) // arch.num_kv_heads)
        logger.info(
            "replicating %d KV heads x%d to cover tensor_parallel_size=%d",
            arch.num_kv_heads, arch.num_kv_head_replicas, tp)
    # K/V heads that share a page row (models/bamba.py) stay on one rank.
    while (getattr(arch, "kv_pack", 1) > 1
           and arch.total_kv_heads % (arch.kv_pack * tp)):
        arch.kv_pack //= 2
    model = model_cls(arch)

    load_format = config.load_config.load_format
    model_path = config.model_config.model
    if load_format == "sharded_state":
        # Orbax tree written by save_sharded_state: already transposed,
        # stacked, replicated and quantized — restore host-side and let
        # the placement pass below shard it (reference:
        # model_loader/sharded_state_loader.py skipping the per-tensor
        # weight_loader work).
        import orbax.checkpoint as ocp
        ckpt_dir = config.load_config.sharded_state_path or model_path
        params = ocp.StandardCheckpointer().restore(
            os.path.abspath(ckpt_dir))
        logger.info("restored sharded state from %s", ckpt_dir)
    elif load_format == "dummy":
        rng = jax.random.PRNGKey(config.model_config.seed)
        params = _init_dummy_params(model, rng, mesh if shard else None)
    elif not (os.path.isdir(model_path) or
              (model_path.endswith(".gguf") and os.path.isfile(model_path))):
        raise ValueError(
            f"{model_path!r} is not a local checkpoint directory (or "
            f".gguf file); random weights are served only when asked for "
            f"by name (load_format='dummy')")
    else:
        tensors = load_hf_state_dict(model_path)
        from vllm_distributed_tpu.models.gptq import maybe_dequantize_gptq
        tensors = maybe_dequantize_gptq(tensors, hf_config,
                                        model_path)
        params = model.params_from_hf_state_dict(tensors)
        logger.info("loaded %d tensors from %s", len(tensors), model_path)

    # Quantize-on-load (reference: tpu_int8.py process_weights_after_
    # loading) before placement, so only int8 bytes hit device HBM.
    # Sharded-state trees were saved post-quantization already.
    if load_format != "sharded_state":
        params = model.quantize_params(params)

    if not shard:
        return model, params

    specs = model.param_specs()

    def place(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    # Walk the params tree key-by-key so family-specific extras
    # (final_ln_b / lm_head_b biases, encoder embedding tables,
    # pooler/classifier heads) get their shardings too.
    def place_tree(p, s):
        if isinstance(p, dict):
            return {k: place_tree(v, s[k]) for k, v in p.items()}
        return place(p, s)

    params = place_tree(params, specs)
    return model, params


def _init_dummy_params(model, rng, mesh) -> dict:
    """Seeded random weights, generated by ONE jitted program in the
    target dtype and — when ``mesh`` is given — directly into the target
    shardings. XLA fuses each tensor's generate/scale/cast chain into
    its output, so no tensor ever exists unsharded, in f32, or on a
    device that does not own it: the HBM profile that follows reads
    weights + workspace and nothing else, and a TP mesh never funnels
    the whole model through its first device. Threefry is partitionable,
    so the values do not depend on the mesh."""
    if mesh is None:
        return jax.jit(model.init_params)(rng)
    abstract = jax.eval_shape(model.init_params, rng)
    specs = model.param_specs()

    def shardings(node, spec):
        if isinstance(node, dict):
            return {k: shardings(v, spec.get(k) if spec else None)
                    for k, v in node.items()}
        return NamedSharding(mesh, spec if spec is not None else P())

    return jax.jit(model.init_params,
                   out_shardings=shardings(abstract, specs))(rng)


def resolve_encoder_only(model_config) -> bool:
    """True for encoder-only (BERT-family) archs: the worker swaps in
    the dense EncoderModelRunner and the scheduler disables chunked
    prefill + prefix caching (a bidirectional layer needs the whole
    sequence in one step; a cached page boundary is meaningless without
    causality). Reference: the pooling-model runner split of
    v1/worker/gpu_model_runner.py + models/bert.py."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
    except Exception:  # noqa: BLE001 - conservative
        return False
    return bool(getattr(model_cls, "ENCODER_ONLY", False))


def resolve_encoder_limits(model_config) -> "tuple[bool, Optional[int]]":
    """(is_cross_encoder, max_encodable_tokens) for encoder-only archs.

    Cross-encoder = checkpoint with a classification head ("score"
    pooling is only admissible there — a bad request must 400 at the
    front-end, never raise inside the engine step). The token bound is
    the position table minus the family's position offset (RoBERTa
    writes positions starting at padding_idx + 1 = 2, so a 514-row
    table only covers 512 tokens)."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
        if not getattr(model_cls, "ENCODER_ONLY", False):
            return False, None
        offset = int(getattr(model_cls, "POS_OFFSET", 0))
        max_pos = int(getattr(hf_config, "max_position_embeddings", 0))
    except Exception:  # noqa: BLE001 - conservative
        return False, None
    limit = max_pos - offset if max_pos else None
    return bool(getattr(model_cls, "CLASSIFY", False)), limit


def resolve_stateful(model_config) -> bool:
    """True when the model carries non-pageable per-request state (SSM
    layers): the scheduler must disable prefix caching — a cached page
    boundary is not a re-enterable point for a recurrence (the
    reference likewise disables prefix caching for mamba models)."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
    except Exception:  # noqa: BLE001 - conservative
        return False
    return bool(getattr(model_cls, "STATEFUL", False))


def resolve_state_snapshotable(model_config) -> bool:
    """True when the model's per-request state is SNAPSHOTABLE — it
    exposes ``state_shapes()`` (SSM conv/ssm rows, Mamba/Jamba/Bamba),
    so the state cache can checkpoint/restore it. STATEFUL alone is
    not enough: Whisper/BART are stateful (fixed cross-attention state
    rows, no prefix caching) but carry no re-enterable recurrence
    state — activating the snapshot pool for them crashes the runner
    at ``state_shapes`` and buys nothing."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
    except Exception:  # noqa: BLE001 - conservative
        return False
    return hasattr(model_cls, "state_shapes")


def resolve_state_only(model_config) -> bool:
    """True for pure-SSM stacks (Mamba family): pages carry no KV
    bytes, so a state snapshot alone is a complete resume point and the
    state cache skips the page-residency requirement hybrid stacks
    (Jamba/Bamba) need for coherent re-entry."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
    except Exception:  # noqa: BLE001 - conservative
        return False
    return bool(getattr(model_cls, "STATE_ONLY", False))


def _resolved_arch(model_config):
    """The arch config as get_model resolves it (family overrides
    honoured: Gemma2 alternating layouts, Qwen2 max_window_layers), or
    None where the configuration cannot be resolved."""
    try:
        hf_config = model_config.maybe_load_hf_config()
        model_cls = resolve_architecture(hf_config)
        arch = LlamaArchConfig.from_hf_config(
            model_cls.arch_config_source(hf_config))
        model_cls.configure_arch(arch, hf_config)
    except Exception:  # noqa: BLE001 - conservative
        return None
    return arch


def resolve_free_window(model_config) -> Optional[int]:
    """Token window below which ALL of a request's KV pages can be freed
    mid-request: the layer window when EVERY attention layer is windowed
    alike, else None (some layer needs more history). A family that
    mixes window and full layers and keeps a cache group for each frees
    behind the window in its window group only (``resolve_window_group``)
    and answers None here (reference: the per-group window specs of
    v1/kv_cache_interface.py SlidingWindowSpec)."""
    arch = _resolved_arch(model_config)
    if arch is None:
        return None
    if arch.window_pattern is not None:
        pattern = arch.window_pattern
        # Only a UNIFORM all-windowed pattern is safe to free against on
        # ONE table: with unequal windows the larger-window layers still
        # attend pages the smaller window has left behind (freeing at
        # min(pattern) would hand live history to the pool). Window and
        # full layers mixed take two cache groups
        # (core/kv_cache_manager.py GroupedKVCacheManager), which a
        # family opts into (``window_group``); unequal windows would
        # take a group a window and are not wired.
        if all(pattern) and len(set(pattern)) == 1:
            return pattern[0]
        return None
    return arch.sliding_window


def resolve_window_group(model_config) -> Optional[int]:
    """The window of the model's second cache group, for a family whose
    window layers and full layers each have a pool and a page table
    (models/cohere_moe.py sets ``window_group``); None for every other
    model, which keeps one table."""
    arch = _resolved_arch(model_config)
    return getattr(arch, "window_group", None) if arch else None
