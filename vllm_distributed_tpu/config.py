"""Configuration system.

Mirrors the reference's single-aggregate design (vllm/config.py:4364
``VllmConfig`` holding ~15 sub-configs, each a validated dataclass) but is
TPU-native: parallelism is expressed as mesh axis sizes (data/pipe/model/
token/expert) that map onto a ``jax.sharding.Mesh``, and cache sizing speaks
HBM pages instead of CUDA blocks.

The aggregate ``EngineConfig`` is passed down through every layer as one
object, exactly like the reference's ``VllmConfig``.
"""

import hashlib
import os
from dataclasses import dataclass, field
from typing import Any, Optional

from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.utils import cdiv

logger = init_logger(__name__)

# ---------------------------------------------------------------------------
# ModelConfig (reference: vllm/config.py:230)
# ---------------------------------------------------------------------------


@dataclass
class ModelConfig:
    """Which model to run and how to interpret its checkpoint."""

    model: str = "meta-llama/Meta-Llama-3-8B"
    tokenizer: Optional[str] = None
    # Skip tokenizer loading; prompts/outputs are token ids only
    # (reference: vllm/config.py ModelConfig.skip_tokenizer_init).
    skip_tokenizer_init: bool = False
    trust_remote_code: bool = False
    dtype: str = "bfloat16"  # bfloat16 | float32 (TPU-native dtypes)
    # Quantization: None (full precision), weight-only "int4" / "int8" /
    # "fp8", "w8a8" (int8 weights + dynamic int8 activations), or
    # "int4g" (group-wise asymmetric uint4, group 128 — the scheme that
    # preserves GPTQ/AWQ checkpoints' group structure losslessly;
    # "gptq"/"awq" are accepted aliases) (reference:
    # quantization/tpu_int8.py + fp8 configs + gptq_marlin serving).
    quantization: Optional[str] = None
    seed: int = 0
    max_model_len: Optional[int] = None
    # Overrides applied on top of the HF config (tests use this to build tiny
    # models without a checkpoint on disk).
    hf_overrides: dict[str, Any] = field(default_factory=dict)
    # Populated lazily by maybe_load_hf_config().
    hf_config: Any = None

    def __post_init__(self) -> None:
        if self.tokenizer is None:
            self.tokenizer = self.model
        if self.dtype not in ("bfloat16", "float32", "float16"):
            raise ValueError(f"unsupported dtype {self.dtype!r}")
        if self.quantization in ("gptq", "awq"):
            self.quantization = "int4g"
        if self.quantization not in (None, "int4", "int8", "fp8",
                                     "w8a8", "int4g"):
            raise ValueError(
                f"unsupported quantization {self.quantization!r} "
                "(supported: int4, int4g/gptq/awq, int8, fp8, w8a8)")

    def maybe_load_hf_config(self) -> Any:
        """Load (and cache) the HF config for the model.

        Non-path model names normally resolve via the HF hub; when the hub
        is unreachable (air-gapped TPU pods, CI) and ``hf_overrides``
        describes the architecture, fall back to a LlamaConfig built purely
        from the overrides so dummy-weight runs never need the network.
        """
        if self.hf_config is None:
            if self.model.endswith(".gguf"):
                # Single-file GGUF: the architecture config lives in
                # the file's own metadata (reference: gguf_loader.py).
                from transformers import LlamaConfig

                from vllm_distributed_tpu.models.gguf import (
                    hf_config_dict_from_gguf, read_gguf)
                meta, tensors = read_gguf(self.model)
                cfg = hf_config_dict_from_gguf(meta, tensors)
                cfg.update(self.hf_overrides)
                self.hf_config = LlamaConfig(**cfg)
                return self.hf_config
            try:
                from transformers import AutoConfig
                try:
                    # Local path / populated cache first: skips minutes of
                    # hub-retry backoff on air-gapped hosts.
                    hf_config = AutoConfig.from_pretrained(
                        self.model, trust_remote_code=self.trust_remote_code,
                        local_files_only=True)
                except Exception:
                    hf_config = AutoConfig.from_pretrained(
                        self.model,
                        trust_remote_code=self.trust_remote_code)
            except Exception:
                hf_config = self._local_config_of_known_architecture()
                # Otherwise only fall back when the overrides actually pin
                # down the architecture — a partial override on top of
                # LlamaConfig defaults would silently run a different
                # model.
                required = {"vocab_size", "hidden_size",
                            "num_hidden_layers", "num_attention_heads"}
                if hf_config is None:
                    if not required.issubset(self.hf_overrides):
                        raise
                    from transformers import LlamaConfig
                    logger.warning(
                        "could not resolve HF config for %r; building a "
                        "LlamaConfig from hf_overrides", self.model)
                    hf_config = LlamaConfig()
            for k, v in self.hf_overrides.items():
                setattr(hf_config, k, v)
            self.hf_config = hf_config
        if self.max_model_len is None:
            derived = getattr(self.hf_config, "max_position_embeddings", 2048)
            self.max_model_len = int(derived)
        return self.hf_config

    def _local_config_of_known_architecture(self) -> Any:
        """A local ``config.json`` whose ``model_type`` transformers does
        not know (or that has none) but whose ``architectures`` this
        program registers: its keys as a plain ``PretrainedConfig``."""
        import json
        import os
        path = os.path.join(self.model, "config.json")
        if not os.path.isfile(path):
            return None
        with open(path) as f:
            keys = json.load(f)
        from vllm_distributed_tpu.models.registry import \
            supported_architectures
        if not set(keys.get("architectures") or ()) & set(
                supported_architectures()):
            return None
        from transformers import PretrainedConfig
        return PretrainedConfig(**keys)

    # -- Introspection helpers used by the worker/scheduler ---------------
    def get_vocab_size(self) -> int:
        return int(self.maybe_load_hf_config().vocab_size)

    def get_hidden_size(self) -> int:
        return int(self.maybe_load_hf_config().hidden_size)

    def get_num_layers(self) -> int:
        return int(self.maybe_load_hf_config().num_hidden_layers)

    def get_num_attention_heads(self) -> int:
        return int(self.maybe_load_hf_config().num_attention_heads)

    def get_num_kv_heads(self) -> int:
        cfg = self.maybe_load_hf_config()
        return int(
            getattr(cfg, "num_key_value_heads", cfg.num_attention_heads))

    def get_head_size(self) -> int:
        cfg = self.maybe_load_hf_config()
        if getattr(cfg, "head_dim", None) is not None:
            return int(cfg.head_dim)
        return cfg.hidden_size // cfg.num_attention_heads


# ---------------------------------------------------------------------------
# CacheConfig (reference: vllm/config.py:1511)
# ---------------------------------------------------------------------------


@dataclass
class CacheConfig:
    """Paged-KV-cache geometry and sizing."""

    # Tokens per KV page. On TPU the page size interacts with the ragged
    # paged attention kernel's block shapes; multiples of 16 keep bf16 tiles
    # aligned (reference TPU backend pads similarly: v1/attention/backends/
    # pallas.py:71-76 derives min page size from SMEM budget).
    block_size: int = 16
    # Fraction of device HBM the engine may use (weights + KV + workspace).
    gpu_memory_utilization: float = 0.90
    # Explicit page count override (None -> profiled at startup).
    num_gpu_blocks_override: Optional[int] = None
    # Number of pages decided at init time (set by the engine after
    # profiling, like determine_available_memory in the reference).
    num_gpu_blocks: Optional[int] = None
    # Slots of the state-snapshot pool (core/state_cache.py) where the
    # HBM profile left less room than the default of twice
    # max_num_seqs (set by the worker beside num_gpu_blocks; None ->
    # the default).
    num_state_slots: Optional[int] = None
    enable_prefix_caching: bool = True
    # KV cache dtype ("auto" follows model dtype).
    cache_dtype: str = "auto"

    def __post_init__(self) -> None:
        if self.block_size <= 0:
            raise ValueError("block_size must be positive")
        if not 0.0 < self.gpu_memory_utilization <= 1.0:
            raise ValueError("gpu_memory_utilization must be in (0, 1]")


# ---------------------------------------------------------------------------
# ParallelConfig (reference: vllm/config.py:1798, incl. the fork's
# token_parallel_size at :1899)
# ---------------------------------------------------------------------------

MESH_AXIS_DATA = "data"
MESH_AXIS_PIPE = "pipe"
MESH_AXIS_MODEL = "model"
# The fork's token-parallel (TKNP) axis: extra devices that hold only KV
# cache + attention state (reference: parallel_state.py:883-913).  On TPU we
# realize it as a mesh axis that shards requests' KV across devices while
# weights live on the "model" axis only.
MESH_AXIS_TOKEN = "token"
# Expert parallelism for MoE dispatch (reference: parallel_state.py:1189).
MESH_AXIS_EXPERT = "expert"


@dataclass
class ParallelConfig:
    """Mesh geometry.

    The reference builds process groups ExternalDP x (DP|TKNP) x PP x TP
    (parallel_state.py:1116-1126); here the same axes are sizes of a single
    ``jax.sharding.Mesh`` and XLA inserts the collectives.
    """

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    token_parallel_size: int = 1
    enable_expert_parallel: bool = False
    # Megatron-style sequence parallelism over the TP group (reference:
    # CompilationConfig.pass_config.enable_sequence_parallelism + the
    # sequence_parallelism.py compile pass): the residual stream is
    # constrained token-sharded on the "model" axis between blocks, so
    # XLA turns each TP all-reduce into reduce-scatter + all-gather and
    # norms/elementwise run on 1/tp of the tokens. GSPMD does the
    # rewrite the reference implements as a custom torch.fx pass.
    enable_sequence_parallel: bool = False
    # EPLB: extra physical expert slots hosting replicas of hot experts
    # (reference: ParallelConfig num_redundant_experts + eplb config).
    num_redundant_experts: int = 0
    # How data parallelism is realized (reference: one DPEngineCoreProc
    # per DP rank behind a balancing coordinator, v1/engine/core.py:812 +
    # coordinator.py:21):
    #  - "engine": data_parallel_size full engine replicas (scheduler +
    #    KV pool + mesh slice each) behind a balancing front-end client.
    #    The serving path. Replicas share no collectives, so the
    #    reference's lockstep dummy batches / wave sync are unnecessary
    #    by construction (EP spans the model axis inside one replica,
    #    never the data axis across replicas).
    #  - "mesh": a single engine whose mesh carries a "data" axis and
    #    shards the batch SPMD (the dryrun/training-style layout).
    data_parallel_mode: str = "engine"
    # This replica's rank under "engine" mode (set by the DP front-end;
    # selects the replica's device slice).
    data_parallel_rank: int = 0
    # Explicit first-device index of this replica's slice (set by the
    # disagg pool planner when pools have asymmetric TP degrees, so
    # replica world sizes differ and rank * world_size no longer
    # addresses the right devices). None = legacy rank-based slicing.
    data_parallel_device_offset: Optional[int] = None
    # Route DP requests through a separate coordinator PROCESS (the
    # reference's DPCoordinator, v1/engine/coordinator.py) instead of
    # front-end-local accounting — the serving-plane scale-out hook.
    data_parallel_coordinator: bool = False
    # Run the engine core (scheduler + executor busy loop) in its own
    # process with ZMQ transport (reference: EngineCoreProc, core.py:362).
    multiprocess_engine_core: bool = False
    # Multi-host SPMD (reference boundary: one worker process per host,
    # v1/executor/multiproc_executor.py:42 + StatelessProcessGroup
    # bootstrap, distributed/utils.py:138; JAX analogue:
    # jax.distributed.initialize + one controller process per host whose
    # jax.devices() spans the whole pod). num_hosts > 1 makes the worker
    # initialize the distributed runtime before touching devices.
    num_hosts: int = 1
    host_rank: int = 0
    # coordinator "ip:port" (host 0); None lets JAX auto-detect on TPU
    # pods (GCE metadata).
    coordinator_address: Optional[str] = None
    # ZMQ endpoint host 0 binds for SchedulerOutput broadcast to
    # follower hosts (e.g. "tcp://0.0.0.0:5560"); required when
    # num_hosts > 1 with the MultiHostExecutor.
    broadcast_addr: Optional[str] = None
    # Multi-host: processes per pod slice (jax.distributed).
    distributed_init_method: Optional[str] = None

    def __post_init__(self) -> None:
        for name in ("tensor_parallel_size", "pipeline_parallel_size",
                     "data_parallel_size", "token_parallel_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")
        if self.data_parallel_mode not in ("engine", "mesh"):
            raise ValueError(
                f"data_parallel_mode must be 'engine' or 'mesh', got "
                f"{self.data_parallel_mode!r}")
        if self.token_parallel_size > 1 and self.data_parallel_size > 1:
            # Mirrors the reference's DP|TKNP exclusivity
            # (parallel_state.py:1116-1126).
            raise ValueError(
                "token parallelism and data parallelism are mutually "
                "exclusive")

    @property
    def world_size(self) -> int:
        return (self.tensor_parallel_size * self.pipeline_parallel_size *
                self.data_parallel_size * self.token_parallel_size)

    @property
    def mesh_shape(self) -> dict[str, int]:
        """Axis-name -> size for the device mesh (order matters: outermost
        axes map to DCN, innermost to ICI)."""
        return {
            MESH_AXIS_DATA: self.data_parallel_size,
            MESH_AXIS_TOKEN: self.token_parallel_size,
            MESH_AXIS_PIPE: self.pipeline_parallel_size,
            MESH_AXIS_MODEL: self.tensor_parallel_size,
        }


# ---------------------------------------------------------------------------
# SchedulerConfig (reference: vllm/config.py:2139)
# ---------------------------------------------------------------------------


@dataclass
class SchedulerConfig:
    """Continuous-batching budget knobs."""

    max_num_batched_tokens: int = 8192
    max_num_seqs: int = 256
    max_model_len: int = 8192
    enable_chunked_prefill: bool = True
    # Requests with more than this many prompt tokens remaining are
    # considered "long" and capped per step (reference:
    # sched/scheduler.py:457 long_prefill_token_threshold).
    long_prefill_token_threshold: int = 0
    policy: str = "fcfs"  # fcfs | priority
    # Fused decode steps per host roundtrip (reference: V0 multi-step
    # scheduling / --num-scheduler-steps; on TPU the burst is one jitted
    # lax.scan, see worker/model_runner.py). 1 disables.
    num_scheduler_steps: int = 1
    # Total encoder (vision) output tokens admitted concurrently
    # (reference: encoder_cache_size / max_num_encoder_input_tokens,
    # v1/core/encoder_cache_manager.py); image requests past the budget
    # wait.
    encoder_cache_budget: int = 8192
    # The pipelined step (async scheduling; reference: the V1
    # --async-scheduling path): ONE engine-core step schedules, prepares
    # and launches step N+1 and then fetches and reconciles step N, so
    # the next forward is in the device's queue before the host blocks
    # (engine/core.py _step_pipelined, a batch queue of depth 2 on the
    # non-PP path). The scheduler grants N+1 by advancing each running
    # decode request one speculative position while N executes; the
    # forward reads the input tokens the host does not know yet from a
    # device-resident mirror that N's sampler wrote, so no sampled token
    # round-trips through the host on the hot path. Stop/EOS detection
    # lags one step: the over-issued position's work is discarded and
    # the stream still ends on the stop token exactly.
    # WHO RUNS IT: `vdt serve` (run_server) by default; a library
    # construction (LLM, LLMEngine, an EngineCore stepped by hand) keeps
    # the synchronous step unless EngineArgs.async_scheduling says
    # otherwise (None there means "the entry decides").
    # Auto-disabled (see EngineConfig.__post_init__) with pipeline
    # parallelism, speculative decoding, multi-step bursts, KV-transfer
    # connectors, token parallelism, and multi-host execution; requests
    # needing host-synchronous sampling state (structured output,
    # prompt_logprobs, pooling, penalties/min-tokens) individually fall
    # back to the synchronous step, one batch at a time
    # (Scheduler._needs_sync_step).
    async_scheduling: bool = False

    def __post_init__(self) -> None:
        if self.policy not in ("fcfs", "priority"):
            raise ValueError(f"unknown scheduling policy {self.policy!r}")
        if not self.enable_chunked_prefill:
            # Without chunked prefill a whole prompt must fit in one step.
            self.max_num_batched_tokens = max(self.max_num_batched_tokens,
                                              self.max_model_len)


# ---------------------------------------------------------------------------
# Remaining sub-configs
# ---------------------------------------------------------------------------


@dataclass
class DeviceConfig:
    """Which JAX platform to run on ("auto" picks TPU when present)."""

    device: str = "auto"  # auto | tpu | cpu


@dataclass
class LoadConfig:
    """Weight loading (reference: vllm/config.py:1711 + model_loader/)."""

    # auto | safetensors | dummy | sharded_state (orbax tree saved by
    # save_sharded_state; model still names the HF dir for the config).
    load_format: str = "auto"
    download_dir: Optional[str] = None
    # Directory of the orbax tree for load_format="sharded_state"
    # (defaults to model_config.model).
    sharded_state_path: Optional[str] = None


@dataclass
class SpeculativeConfig:
    """Speculative decoding (reference: vllm/config.py:2502)."""

    method: Optional[str] = None  # ngram | draft_model | eagle | None
    num_speculative_tokens: int = 0
    # ngram proposer window (reference: v1/spec_decode/ngram_proposer.py).
    prompt_lookup_max: int = 4
    prompt_lookup_min: int = 1
    # draft_model proposer (reference: the draft-model speculative path,
    # vllm/v1/spec_decode/eagle.py + config.py SpeculativeConfig.model):
    # local checkpoint of a small causal LM proposing k greedy tokens,
    # verified in-step by the existing S+1-position sampler.
    model: Optional[str] = None
    # Context window the draft sees (stateless re-prefill of the last
    # W tokens each proposal — no second paged cache to manage; RoPE
    # scores depend on relative distance so the window offset is sound).
    draft_window: int = 32


@dataclass
class KVTransferConfig:
    """Disaggregated prefill/decode (reference: vllm/config.py:3826)."""

    kv_connector: Optional[str] = None
    kv_role: Optional[str] = None  # kv_producer | kv_consumer | kv_both
    kv_connector_extra_config: dict[str, Any] = field(default_factory=dict)
    # Disaggregated serving tier (engine/disagg.py): which pool this
    # engine replica belongs to — "prefill" | "decode" | None
    # (monolithic). Read by the model runner to prune the precompile
    # lattice per role (a prefill replica never warms decode-burst
    # graph variants; a decode replica's token ladder is capped by its
    # pool config).
    pool_role: Optional[str] = None

    @property
    def is_kv_producer(self) -> bool:
        return self.kv_role in ("kv_producer", "kv_both")

    @property
    def is_kv_consumer(self) -> bool:
        return self.kv_role in ("kv_consumer", "kv_both")


@dataclass
class KVEventsConfig:
    """ZMQ publishing of prefix-cache block events for external routers
    (reference: vllm/config.py:3922 KVEventsConfig +
    distributed/kv_events.py)."""

    enable_kv_cache_events: bool = False
    endpoint: str = "tcp://127.0.0.1:5557"
    replay_endpoint: Optional[str] = None
    buffer_steps: int = 1000


@dataclass
class LoRAConfig:
    """Multi-LoRA serving (reference: vllm/config.py:2999 LoRAConfig).

    Static-shape discipline: ``max_loras`` adapter SLOTS of fixed
    ``max_lora_rank`` are baked into the compiled graphs (slot 0 is the
    always-zero "no adapter" slot); adapters hot-swap by writing slot
    buffers, never by recompiling."""

    enable_lora: bool = False
    max_loras: int = 4
    max_lora_rank: int = 16

    def __post_init__(self) -> None:
        if self.enable_lora:
            if self.max_loras < 1:
                raise ValueError("max_loras must be >= 1")
            if self.max_lora_rank < 1:
                raise ValueError("max_lora_rank must be >= 1")


@dataclass
class ObservabilityConfig:
    """Tracing/metrics switches (reference: vllm/config.py:3735)."""

    otlp_traces_endpoint: Optional[str] = None
    collect_detailed_traces: bool = False


@dataclass
class FaultToleranceConfig:
    """Timeouts and retry budgets for the fault-tolerance layer: the
    scheduler's remote-KV watchdog, the KV-transfer retry policy, and
    the engine-core health monitor. Degradation order for a failed
    remote pull: retry the pull (bounded) -> local prefill recompute ->
    request error; an unresponsive engine core fails pending requests
    with EngineDeadError instead of blocking forever."""

    # Watchdog: max seconds a request may sit in WAITING_FOR_REMOTE_KVS
    # before the sweep fails the pull (0 disables the sweep).
    kv_pull_timeout_s: float = 120.0
    # Request-level pull retries before degrading to local recompute.
    kv_pull_max_retries: int = 1
    # Socket-level retry policy for one pull / registry call.
    retry_max_attempts: int = 3
    retry_base_delay_s: float = 0.05
    retry_max_delay_s: float = 2.0
    # Backstop (seconds) after which pages parked for a timed-out,
    # still-in-flight pull are reclaimed even without a worker report.
    # Safe regardless of transfer duration: the sweep issues a
    # cancel_pull, and the worker discards (never applies) a transfer
    # whose id was cancelled — the backstop only covers connectors/
    # pulls that never report at all.
    kv_pull_abandon_timeout_s: float = 240.0
    # Engine-core liveness: heartbeat send period (0 disables the
    # beater) and the staleness window after which the client declares
    # the core dead. The window is deliberately generous by default —
    # first-compile stalls are legitimate; tests tighten it.
    heartbeat_interval_s: float = 1.0
    heartbeat_timeout_s: float = 300.0
    # Restart supervisor: when the health monitor declares a core dead,
    # respawn it with exponential backoff, at most restart_max_attempts
    # times within restart_window_s before circuit-breaking to the
    # terminal EngineDeadError (0 attempts disables recovery — death
    # stays terminal, the pre-supervisor behavior).
    restart_max_attempts: int = 3
    restart_window_s: float = 300.0
    restart_backoff_base_s: float = 0.5
    restart_backoff_max_s: float = 30.0
    # DP failover: how often the front-end probes a downed replica for
    # resurrection (0 disables probing — a failed-over replica stays
    # out of rotation for the process lifetime).
    replica_probe_interval_s: float = 10.0

    def __post_init__(self) -> None:
        if (self.kv_pull_timeout_s < 0 or self.heartbeat_interval_s < 0
                or self.heartbeat_timeout_s < 0
                or self.kv_pull_abandon_timeout_s < 0
                or self.retry_base_delay_s < 0
                or self.retry_max_delay_s < 0
                or self.restart_window_s < 0
                or self.restart_backoff_base_s < 0
                or self.restart_backoff_max_s < 0
                or self.replica_probe_interval_s < 0):
            raise ValueError("fault-tolerance timeouts must be >= 0")
        if self.kv_pull_max_retries < 0:
            raise ValueError("kv_pull_max_retries must be >= 0")
        if self.restart_max_attempts < 0:
            raise ValueError("restart_max_attempts must be >= 0")
        if self.retry_max_attempts < 1:
            # 0 would make every retried IO call fail without a single
            # attempt ("no retries" is retry_max_attempts=1).
            raise ValueError("retry_max_attempts must be >= 1")
        if self.heartbeat_interval_s == 0 and self.heartbeat_timeout_s > 0:
            # No beater -> the client-side staleness window would fire
            # on any quiet-but-healthy stretch; disable it together.
            logger.warning("heartbeat_interval_s=0 disables the beater; "
                           "disabling heartbeat_timeout_s with it")
            self.heartbeat_timeout_s = 0.0


# ---------------------------------------------------------------------------
# Aggregate
# ---------------------------------------------------------------------------


@dataclass
class EngineConfig:
    """The one object passed through every layer (reference: VllmConfig,
    vllm/config.py:4364)."""

    model_config: ModelConfig = field(default_factory=ModelConfig)
    cache_config: CacheConfig = field(default_factory=CacheConfig)
    parallel_config: ParallelConfig = field(default_factory=ParallelConfig)
    scheduler_config: SchedulerConfig = field(default_factory=SchedulerConfig)
    device_config: DeviceConfig = field(default_factory=DeviceConfig)
    load_config: LoadConfig = field(default_factory=LoadConfig)
    speculative_config: SpeculativeConfig = field(
        default_factory=SpeculativeConfig)
    kv_transfer_config: KVTransferConfig = field(
        default_factory=KVTransferConfig)
    lora_config: LoRAConfig = field(default_factory=LoRAConfig)
    kv_events_config: KVEventsConfig = field(
        default_factory=KVEventsConfig)
    observability_config: ObservabilityConfig = field(
        default_factory=ObservabilityConfig)
    fault_tolerance_config: FaultToleranceConfig = field(
        default_factory=FaultToleranceConfig)

    def __post_init__(self) -> None:
        # Clamp scheduler limits to the model context window once known,
        # re-applying the non-chunked-prefill invariant (a whole prompt
        # must fit in one step's budget) on the updated value.
        if self.model_config.max_model_len is not None:
            self.scheduler_config.max_model_len = \
                self.model_config.max_model_len
            if not self.scheduler_config.enable_chunked_prefill:
                self.scheduler_config.max_num_batched_tokens = max(
                    self.scheduler_config.max_num_batched_tokens,
                    self.scheduler_config.max_model_len)
        for reason, incompatible in (
                # The fused multi-step burst cannot refresh per-rank
                # token-parallel metadata on device.
                ("token parallelism",
                 self.parallel_config.token_parallel_size > 1),
                # The fused burst is a single-program graph; the staged
                # pipeline replaces it.
                ("pipeline parallelism",
                 self.parallel_config.pipeline_parallel_size > 1),
                # Connector load/save hooks run at step boundaries; the
                # fused burst would silently skip them.
                ("a KV-transfer connector",
                 bool(self.kv_transfer_config.kv_connector)),
                # The burst's scanned decode graph carries no per-token
                # adapter slots.
                ("LoRA", self.lora_config.enable_lora),
                # The burst calls the target forward alone — EAGLE's
                # in-step draft-KV advance would be skipped, starving
                # the drafter of context.
                ("EAGLE speculative decoding",
                 self.speculative_config is not None
                 and self.speculative_config.method == "eagle"),
        ):
            if incompatible and self.scheduler_config.num_scheduler_steps > 1:
                logger.warning(
                    "num_scheduler_steps=%d is incompatible with %s; "
                    "forcing single-step scheduling",
                    self.scheduler_config.num_scheduler_steps, reason)
                self.scheduler_config.num_scheduler_steps = 1
        if self.scheduler_config.async_scheduling:
            for reason, incompatible in (
                    # The PP batch queue already pipelines microbatches;
                    # layering speculative grants on top would re-grant
                    # stage-straddling requests.
                    ("pipeline parallelism (the PP batch queue already "
                     "overlaps)",
                     self.parallel_config.pipeline_parallel_size > 1),
                    # Draft tokens round-trip through the host between
                    # steps (propose -> schedule -> verify).
                    ("speculative decoding",
                     self.speculative_config is not None
                     and self.speculative_config.method is not None),
                    # The fused burst is the deeper device-side answer to
                    # the same host gap; both at once would double-grant.
                    ("multi-step decode bursts (num_scheduler_steps > 1)",
                     self.scheduler_config.num_scheduler_steps > 1),
                    # Connector load/save + deferred-free hooks assume
                    # step-synchronous page ownership.
                    ("a KV-transfer connector",
                     bool(self.kv_transfer_config.kv_connector)),
                    # Per-rank pool accounting under speculative grants is
                    # unvalidated; keep the TKNP path synchronous.
                    ("token parallelism",
                     self.parallel_config.token_parallel_size > 1),
                    # The broadcast executor has no async dispatch path.
                    ("multi-host execution",
                     self.parallel_config.num_hosts > 1),
            ):
                if incompatible:
                    logger.warning(
                        "async scheduling is incompatible with %s; "
                        "falling back to synchronous stepping", reason)
                    self.scheduler_config.async_scheduling = False
                    break
        override = self.cache_config.num_gpu_blocks_override
        tknp = self.parallel_config.token_parallel_size
        if override and tknp > 1 and (override % tknp or override < tknp):
            raise ValueError(
                f"num_gpu_blocks_override={override} must be a positive "
                f"multiple of token_parallel_size={tknp}")
        if (self.speculative_config is not None
                and self.speculative_config.method == "eagle"
                and self.parallel_config.pipeline_parallel_size > 1):
            raise ValueError(
                "EAGLE speculative decoding is not supported with "
                "pipeline parallelism (the draft layers stack onto the "
                "single-program cache; stage-sliced caches don't carry "
                "them)")
        if (self.speculative_config is not None
                and self.speculative_config.method == "eagle"
                and self.parallel_config.token_parallel_size > 1):
            raise ValueError(
                "EAGLE speculative decoding is not supported with "
                "token parallelism (the propose path reads the draft "
                "cache without the per-rank TKNP metadata)")

    def compute_hash(self) -> str:
        """Stable hash of the config for compilation-cache keys."""
        parts = repr((self.model_config, self.cache_config,
                      self.parallel_config, self.scheduler_config))
        return hashlib.sha256(parts.encode()).hexdigest()[:16]

    @property
    def max_pages_per_req(self) -> int:
        return cdiv(self.scheduler_config.max_model_len,
                    self.cache_config.block_size)


_current_engine_config: list[EngineConfig] = []


def get_current_engine_config() -> Optional[EngineConfig]:
    """Contextvar-style accessor so deep code can read the config without
    threading it (reference: get_current_vllm_config,
    parallel_state.py:1087-1093)."""
    return _current_engine_config[-1] if _current_engine_config else None


class set_current_engine_config:
    def __init__(self, config: EngineConfig) -> None:
        self.config = config

    def __enter__(self) -> EngineConfig:
        _current_engine_config.append(self.config)
        return self.config

    def __exit__(self, *args) -> None:
        _current_engine_config.pop()
