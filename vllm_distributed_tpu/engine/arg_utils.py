"""CLI/programmatic engine arguments -> EngineConfig.

Reference: vllm/engine/arg_utils.py (``EngineArgs`` mirrors every config
field as a --kebab-case flag; the fork's TKNP flags at arg_utils.py:339).
"""

import argparse
from dataclasses import dataclass, fields
from typing import Optional

from vllm_distributed_tpu.config import (CacheConfig, DeviceConfig,
                                         EngineConfig,
                                         FaultToleranceConfig,
                                         KVEventsConfig,
                                         KVTransferConfig, LoadConfig,
                                         LoRAConfig, ModelConfig,
                                         ObservabilityConfig,
                                         ParallelConfig, SchedulerConfig,
                                         SpeculativeConfig)


# Help text of the switches whose default is not plain on or off.
_CLI_HELP = {
    "async_scheduling":
    "the pipelined engine step: step N+1 is scheduled, prepared and "
    "launched while the device runs step N (one forward a step, its "
    "outputs one call later). Default: on under `vdt serve`, off for "
    "library use. Falls back to the synchronous step with pipeline "
    "parallelism, speculative decoding, --num-scheduler-steps > 1, a "
    "KV connector, token parallelism or several hosts, and per request "
    "for structured output, prompt logprobs, pooling, penalties and "
    "min_tokens. A stop token is detected one step late and the stream "
    "still ends on it exactly.",
}


@dataclass
class EngineArgs:
    model: str = "meta-llama/Meta-Llama-3-8B"
    tokenizer: Optional[str] = None
    skip_tokenizer_init: bool = False
    trust_remote_code: bool = False
    dtype: str = "bfloat16"
    quantization: Optional[str] = None
    seed: int = 0
    max_model_len: Optional[int] = None

    block_size: int = 16
    kv_cache_dtype: str = "auto"
    gpu_memory_utilization: float = 0.90
    num_gpu_blocks_override: Optional[int] = None
    enable_prefix_caching: bool = True
    swap_space: int = 0  # accepted for CLI parity; unused on TPU

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    data_parallel_mode: str = "engine"  # engine replicas | mesh axis
    data_parallel_coordinator: bool = False
    token_parallel_size: int = 1
    enable_expert_parallel: bool = False
    enable_sequence_parallel: bool = False
    num_redundant_experts: int = 0
    multiprocess_engine_core: bool = False
    # Multi-host SPMD: this engine process's place in the pod.
    num_hosts: int = 1
    host_rank: int = 0
    coordinator_address: Optional[str] = None

    # Multi-LoRA serving.
    enable_lora: bool = False
    max_loras: int = 4
    max_lora_rank: int = 16

    max_num_batched_tokens: int = 8192
    max_num_seqs: int = 256
    enable_chunked_prefill: bool = True
    long_prefill_token_threshold: int = 0
    scheduling_policy: str = "fcfs"
    num_scheduler_steps: int = 1
    encoder_cache_budget: int = 8192
    # The pipelined step: step N+1 scheduled, prepared and launched
    # while the device runs step N (see SchedulerConfig.async_scheduling
    # for the exclusions, the per-request fallback and the one-step lag
    # of stop detection). None: the entry decides — `vdt serve`
    # (run_server) turns it on, a library construction (LLM, LLMEngine,
    # EngineCore driven by hand) keeps the synchronous step, whose
    # step() returns the outputs of the batch it scheduled.
    # --async-scheduling / --no-async-scheduling override either.
    async_scheduling: Optional[bool] = None

    device: str = "auto"
    load_format: str = "auto"
    sharded_state_path: Optional[str] = None

    speculative_method: Optional[str] = None
    num_speculative_tokens: int = 0
    speculative_model: Optional[str] = None
    speculative_draft_window: int = 32

    kv_connector: Optional[str] = None
    kv_role: Optional[str] = None
    kv_connector_extra_config: Optional[dict] = None

    otlp_traces_endpoint: Optional[str] = None

    # Fault tolerance: remote-KV watchdog + engine health monitor +
    # restart supervisor (0 attempts = death stays terminal).
    kv_pull_timeout_s: float = 120.0
    kv_pull_max_retries: int = 1
    heartbeat_interval_s: float = 1.0
    heartbeat_timeout_s: float = 300.0
    restart_max_attempts: int = 3
    restart_window_s: float = 300.0
    restart_backoff_base_s: float = 0.5
    restart_backoff_max_s: float = 30.0
    replica_probe_interval_s: float = 10.0

    # KV cache event publishing (external prefix-aware routers).
    enable_kv_cache_events: bool = False
    kv_events_endpoint: str = "tcp://127.0.0.1:5557"
    kv_events_replay_endpoint: Optional[str] = None

    def create_engine_config(self) -> EngineConfig:
        model_config = ModelConfig(
            model=self.model,
            tokenizer=self.tokenizer,
            skip_tokenizer_init=self.skip_tokenizer_init,
            trust_remote_code=self.trust_remote_code,
            dtype=self.dtype,
            quantization=self.quantization,
            seed=self.seed,
            max_model_len=self.max_model_len,
        )
        model_config.maybe_load_hf_config()
        return EngineConfig(
            model_config=model_config,
            cache_config=CacheConfig(
                block_size=self.block_size,
                gpu_memory_utilization=self.gpu_memory_utilization,
                num_gpu_blocks_override=self.num_gpu_blocks_override,
                enable_prefix_caching=self.enable_prefix_caching,
                cache_dtype=self.kv_cache_dtype,
            ),
            parallel_config=ParallelConfig(
                tensor_parallel_size=self.tensor_parallel_size,
                pipeline_parallel_size=self.pipeline_parallel_size,
                data_parallel_size=self.data_parallel_size,
                data_parallel_mode=self.data_parallel_mode,
                data_parallel_coordinator=self.data_parallel_coordinator,
                token_parallel_size=self.token_parallel_size,
                enable_expert_parallel=self.enable_expert_parallel,
                enable_sequence_parallel=self.enable_sequence_parallel,
                num_redundant_experts=self.num_redundant_experts,
                multiprocess_engine_core=self.multiprocess_engine_core,
                num_hosts=self.num_hosts,
                host_rank=self.host_rank,
                coordinator_address=self.coordinator_address,
            ),
            scheduler_config=SchedulerConfig(
                max_num_batched_tokens=self.max_num_batched_tokens,
                max_num_seqs=self.max_num_seqs,
                max_model_len=model_config.max_model_len or 8192,
                enable_chunked_prefill=self.enable_chunked_prefill,
                long_prefill_token_threshold=self.
                long_prefill_token_threshold,
                policy=self.scheduling_policy,
                num_scheduler_steps=self.num_scheduler_steps,
                encoder_cache_budget=self.encoder_cache_budget,
                async_scheduling=bool(self.async_scheduling),
            ),
            device_config=DeviceConfig(device=self.device),
            load_config=LoadConfig(
                load_format=self.load_format,
                sharded_state_path=self.sharded_state_path),
            speculative_config=SpeculativeConfig(
                method=self.speculative_method,
                num_speculative_tokens=self.num_speculative_tokens,
                model=self.speculative_model,
                draft_window=self.speculative_draft_window,
            ),
            kv_transfer_config=KVTransferConfig(
                kv_connector=self.kv_connector,
                kv_role=self.kv_role,
                kv_connector_extra_config=(
                    self.kv_connector_extra_config or {}),
            ),
            lora_config=LoRAConfig(
                enable_lora=self.enable_lora,
                max_loras=self.max_loras,
                max_lora_rank=self.max_lora_rank,
            ),
            kv_events_config=KVEventsConfig(
                enable_kv_cache_events=self.enable_kv_cache_events,
                endpoint=self.kv_events_endpoint,
                replay_endpoint=self.kv_events_replay_endpoint,
            ),
            observability_config=ObservabilityConfig(
                otlp_traces_endpoint=self.otlp_traces_endpoint),
            fault_tolerance_config=FaultToleranceConfig(
                kv_pull_timeout_s=self.kv_pull_timeout_s,
                kv_pull_max_retries=self.kv_pull_max_retries,
                heartbeat_interval_s=self.heartbeat_interval_s,
                heartbeat_timeout_s=self.heartbeat_timeout_s,
                restart_max_attempts=self.restart_max_attempts,
                restart_window_s=self.restart_window_s,
                restart_backoff_base_s=self.restart_backoff_base_s,
                restart_backoff_max_s=self.restart_backoff_max_s,
                replica_probe_interval_s=self.replica_probe_interval_s,
            ),
        )

    # ------------------------------------------------------------------
    @staticmethod
    def add_cli_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        for f in fields(EngineArgs):
            name = "--" + f.name.replace("_", "-")
            # f.type may be the annotation object or its string form
            # depending on `from __future__ import annotations`.
            ts = f.type if isinstance(f.type, str) else str(f.type)
            if ts in ("bool", str(bool)) or "bool" in ts:
                parser.add_argument(name,
                                    action=argparse.BooleanOptionalAction,
                                    default=f.default,
                                    help=_CLI_HELP.get(f.name))
            else:
                typ = str
                if "int" in ts:
                    typ = int
                elif "float" in ts:
                    typ = float
                parser.add_argument(name, type=typ, default=f.default)
        return parser

    @classmethod
    def from_cli_args(cls, args: argparse.Namespace) -> "EngineArgs":
        attrs = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in vars(args).items() if k in attrs})
