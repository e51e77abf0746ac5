"""TPU worker: device/mesh init, memory profiling for KV sizing, model
execution entry.

Reference: vllm/v1/worker/gpu_worker.py:44 (init_device:129,
determine_available_memory:200, execute_model:313) and tpu_worker.py:34.
In SPMD mode one worker drives the whole mesh (the reference's per-rank
process world collapses into GSPMD sharding).
"""

import os
import time
from typing import Optional

import jax

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.output import (ModelRunnerOutput,
                                                    SchedulerOutput)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.parallel.mesh import (build_mesh, global_mesh,
                                                set_global_mesh)
from vllm_distributed_tpu.worker.model_runner import TPUModelRunner

logger = init_logger(__name__)

# Floor so tiny test configs still schedule (matches the spirit of the
# reference's num_gpu_blocks_override escape hatch).
_MIN_PAGES = 16
# Granularity of a profiled KV pool (pages); see
# determine_num_available_blocks.
_POOL_QUANTUM = 128
_POOL_QUANTUM_BYTES = 12 << 20
# Granularity of a state-snapshot pool that the profile cuts (slots).
_SLOT_QUANTUM = 8

# Where the persistent compile cache lives when the environment does not
# place it: one fixed path inside the checkout (the path is part of the
# cache key, so a directory that moves never hits).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. JAX_COMPILATION_CACHE_DIR, when set, places the cache and
    nothing here overrides it; otherwise it goes to
    DEFAULT_COMPILE_CACHE_DIR."""
    # Cache every graph: the bucketed lattice is many small compiles
    # below the default time threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


class TPUWorker:

    def __init__(self, config: EngineConfig) -> None:
        self.config = config
        self.mesh = None
        self.model_runner: Optional[TPUModelRunner] = None

    # ------------------------------------------------------------------
    def init_device(self) -> None:
        self._maybe_init_multihost()
        devices = jax.devices()
        logger.info("devices: %s", devices)
        if devices[0].platform != "cpu":
            # CPU is excluded: its AOT cache reload warns about
            # machine-feature mismatches (possible SIGILL) and CPU
            # compiles are cheap.
            configure_compile_cache()
        pc = self.config.parallel_config
        if pc.data_parallel_mode == "engine" and (
                pc.data_parallel_rank
                or pc.data_parallel_device_offset is not None):
            # Engine-replicated DP: each replica owns a disjoint
            # contiguous device slice (requires all replica devices
            # visible in-process — single host; multi-host DP carves by
            # process instead). The disagg pool planner sets an explicit
            # offset when pools have asymmetric TP degrees (replica
            # world sizes differ, so rank * world_size is wrong).
            per = pc.world_size
            start = (pc.data_parallel_device_offset
                     if pc.data_parallel_device_offset is not None
                     else pc.data_parallel_rank * per)
            if start + per > len(devices):
                raise ValueError(
                    f"DP rank {pc.data_parallel_rank} needs devices "
                    f"[{start}, {start + per}), only {len(devices)} exist")
            devices = devices[start:start + per]
        self.mesh = build_mesh(pc, devices)
        set_global_mesh(self.mesh)
        from vllm_distributed_tpu.models.loader import resolve_encoder_only
        if resolve_encoder_only(self.config.model_config):
            from vllm_distributed_tpu.worker.encoder_runner import (
                EncoderModelRunner)
            self.model_runner = EncoderModelRunner(self.config, self.mesh)
        elif self.config.parallel_config.pipeline_parallel_size > 1:
            from vllm_distributed_tpu.worker.pp_runner import PPModelRunner
            self.model_runner = PPModelRunner(self.config, self.mesh)
        else:
            self.model_runner = TPUModelRunner(self.config, self.mesh)

    def _maybe_init_multihost(self) -> None:
        """Join the pod-wide distributed runtime BEFORE any device access
        (reference boundary: per-rank process bootstrap,
        multiproc_executor.py:42 / StatelessProcessGroup,
        distributed/utils.py:138). After this, ``jax.devices()`` spans
        every host's chips and one SPMD mesh covers the pod; each host
        runs this same engine program multi-controller style."""
        pc = self.config.parallel_config
        if pc.num_hosts <= 1:
            return
        # NOTE: jax.process_count() would itself initialize the backend,
        # which must not happen before jax.distributed.initialize —
        # consult the distributed runtime's own state instead. This is a
        # PRIVATE module (no public "already initialized?" query exists);
        # checked against jax 0.9.0: global_state.client is None until
        # initialize() has run.
        from jax._src import distributed as _dist
        if getattr(_dist.global_state, "client", None) is not None:
            return  # already joined (e.g. a second engine in-process)
        logger.info("joining multi-host runtime: rank %d/%d via %s",
                    pc.host_rank, pc.num_hosts,
                    pc.coordinator_address or "auto-detect")
        jax.distributed.initialize(
            coordinator_address=pc.coordinator_address,
            num_processes=pc.num_hosts,
            process_id=pc.host_rank)

    def load_model(self) -> None:
        # Every entry point re-asserts this worker's mesh as the global
        # one: with in-process DP engine replicas, another replica's init
        # may have pointed the global mesh elsewhere between calls (the
        # collective helpers in ops/ read it during jit tracing).
        start = time.perf_counter()
        with global_mesh(self.mesh):
            self.model_runner.load_model()
        logger.info("model loaded in %.1fs", time.perf_counter() - start)

    def determine_num_available_blocks(self) -> int:
        """Size the KV pool from measured HBM after a profiled dummy
        forward at the largest token shape (reference: gpu_worker.py:200
        determine_available_memory runs profile_run before reading free
        memory; TPU variant tpu_worker.py:163)."""
        # The page array shards evenly over the token-parallel axis.
        tknp = self.config.parallel_config.token_parallel_size

        def rounded(pages: int) -> int:
            pages = max(pages, _MIN_PAGES)
            return (pages // tknp) * tknp if tknp > 1 else pages

        override = self.config.cache_config.num_gpu_blocks_override
        if override:
            # Honored verbatim (tests use tiny pools to force preemption);
            # token-axis divisibility was validated at config time.
            return override
        with global_mesh(self.mesh):
            # None = the backend reports no memory stats (CPU).
            avail = self.model_runner.profile_memory_bytes()
        page_bytes = self.model_runner.kv_cache_bytes_per_page()
        # Fixed-size per-request state (SSM conv/ssm rows) PLUS the
        # state-snapshot pool (core/state_cache.py) are charged up
        # front; the page pool only gets what remains.
        rows = self.model_runner.model_fixed_cache_bytes()
        if avail is not None:
            self._size_state_pool(avail - rows, page_bytes)
        fixed = rows + getattr(self.model_runner, "state_pool_bytes",
                               lambda: 0)()
        if avail is not None:
            if fixed and fixed > avail:
                raise RuntimeError(
                    f"per-request SSM state + snapshot pool "
                    f"({fixed / 2**30:.2f} GiB for "
                    f"{self.config.scheduler_config.max_num_seqs} slots) "
                    f"exceeds free HBM ({avail / 2**30:.2f} GiB); lower "
                    f"max_num_seqs or VDT_SSM_STATE_CACHE_SLOTS")
            avail -= fixed
        if page_bytes == 0:
            # Stateful-only models (pure Mamba): pages carry no bytes, so
            # give every schedulable request full-length coverage.
            pages = (self.config.max_pages_per_req *
                     self.config.scheduler_config.max_num_seqs)
            logger.info("no paged layers; %d free KV pages", pages)
            return rounded(pages)
        if avail is None:
            # No memory stats (CPU tests): cover max_model_len for
            # max_num_seqs/4 requests.
            pages = (self.config.max_pages_per_req *
                     max(self.config.scheduler_config.max_num_seqs // 4, 4))
            logger.info("no memory stats; defaulting to %d KV pages", pages)
            return rounded(pages)
        if avail < page_bytes * _MIN_PAGES:
            raise RuntimeError(
                f"the HBM profile leaves {avail / 2**20:.1f} MiB for the "
                f"KV cache, less than {_MIN_PAGES} pages of {page_bytes} "
                f"bytes: weights + workspace exceed "
                f"gpu_memory_utilization="
                f"{self.config.cache_config.gpu_memory_utilization} of "
                f"the device's memory")
        pages = avail // page_bytes
        # In bytes too: a pool of small pages (one latent layer's rows:
        # 20 KB a page) would else jitter with every few MB.
        quantum = max(_POOL_QUANTUM, _POOL_QUANTUM_BYTES // page_bytes)
        if pages > 8 * quantum:
            # The page count is a dimension of every forward program, so
            # the persistent compile cache only hits when two starts
            # profile the SAME count — and bytes-in-use jitters by a few
            # MB between starts (loaded executables, stray buffers).
            # Round down to a coarse quantum: <= 1.6% of the pool.
            pages -= pages % quantum
        shards = getattr(
            getattr(self.model_runner.model, "cfg", None),
            "tpla_shards", 1) or 1
        if shards > 1:
            # TPLA (ops/mla.py): page_bytes is the PER-RANK cost of a
            # latent page (1/TP of the replicated row plus the rope
            # sidecar), so the same per-device budget admits ~TP x the
            # pages — the capacity win this layout exists for.
            logger.info(
                "HBM for KV: %.2f GiB -> %d latent pages of %d "
                "bytes/rank (TPLA x%d sharding)",
                avail / 2**30, pages, page_bytes, shards)
        else:
            logger.info("HBM for KV: %.2f GiB -> %d pages of %d bytes",
                        avail / 2**30, pages, page_bytes)
        return rounded(pages)

    def _size_state_pool(self, room: int, page_bytes: int) -> None:
        """Cut the state-snapshot pool (core/state_cache.py) to what the
        profile left where its default of twice ``max_num_seqs`` slots
        does not fit: the pages that every schedulable request needs at
        full length come first (a snapshot resumes a request only
        beside its prefix's pages), then snapshot slots up to the
        default, then the pages get the rest. A default pool that costs
        less than the pages of ONE full-length request is kept whole
        whatever fits: cutting it buys no request (rows of 88 KB under
        pages of 16 KB a token, models/zaya.py: 128 slots are 11 MB).
        A model WITHOUT pages (models/brumby.py, models/mamba.py) has
        no request to reserve for and none to take room from: its pool
        is cut to the slots that fit, in eights while eight fit and
        one by one under that, down to one (a slot of 206 MB: eight
        would be 1.6 GB that the profile did not leave).
        Written to ``cache_config.num_state_slots``, where the
        scheduler's manager and the runner's pool both read it."""
        from vllm_distributed_tpu import envs
        slot_bytes = getattr(self.model_runner, "state_pool_slot_bytes",
                             lambda: 0)()
        if not slot_bytes or envs.VDT_SSM_STATE_CACHE_SLOTS:
            return
        from vllm_distributed_tpu.core.state_cache import \
            default_state_slots
        want = default_state_slots(self.config)
        one_request = page_bytes * self.config.max_pages_per_req
        if want * slot_bytes <= one_request:
            return
        reserve = one_request * self.config.scheduler_config.max_num_seqs
        fit = max(room - reserve, 0) // slot_bytes
        if fit < want:
            # A dimension of the snapshot copies, and what the pages
            # get depends on it: coarse, like the page count.
            if page_bytes:
                fit -= fit % _SLOT_QUANTUM
                slots = int(max(fit, min(want, _SLOT_QUANTUM)))
            else:
                # No pages to take the room from: a slot that does not
                # fit is not there. One is kept, as the scheduler's
                # manager and the runner's copies are built for a pool.
                if fit >= _SLOT_QUANTUM:
                    fit -= fit % _SLOT_QUANTUM
                slots = int(max(fit, 1))
            self.config.cache_config.num_state_slots = slots
            logger.info(
                "state-snapshot pool cut to %d slots of %.1f MiB (default "
                "%d): %.2f GiB are left after the state rows, %.2f GiB of "
                "them for the pages %d full-length requests need", slots,
                slot_bytes / 2**20, want, room / 2**30,
                reserve / 2**30,
                self.config.scheduler_config.max_num_seqs)

    def initialize_kv_cache(self, num_pages: int) -> None:
        with global_mesh(self.mesh):
            self.model_runner.initialize_kv_cache(num_pages)

    def compile_or_warm_up_model(self) -> None:
        from vllm_distributed_tpu import envs
        mode = envs.VDT_PRECOMPILE
        if mode == "0":
            return
        platform = next(iter(self.mesh.devices.flat)).platform
        if mode == "auto" and platform == "cpu":
            return  # lazy compiles are cheap on the CPU test mesh
        with global_mesh(self.mesh):
            self.model_runner.precompile()

    # ------------------------------------------------------------------
    def execute_model(self,
                      scheduler_output: SchedulerOutput) -> ModelRunnerOutput:
        with global_mesh(self.mesh):
            return self.model_runner.execute_model(scheduler_output)

    def dispatch_model(self, scheduler_output: SchedulerOutput):
        with global_mesh(self.mesh):
            return self.model_runner.dispatch_model(scheduler_output)

    def wait_model(self, handle) -> ModelRunnerOutput:
        with global_mesh(self.mesh):
            return self.model_runner.wait_model(handle)

    def get_stats(self) -> dict:
        """Runner stats plus this worker's labeled telemetry entry.

        The per-worker keys MOVE into ``workers[label]`` (they are not
        left flat): the DP aggregator sums flat numeric leaves, and a
        summed "peak device memory" or a twice-counted recompile would
        fabricate fleet state. ``num_recompiles`` stays flat as well as
        labeled — it is a counter, so the flat DP sum is the correct
        fleet total while the labeled copy says WHICH worker leaked a
        shape."""
        stats = self.model_runner.get_stats()
        from vllm_distributed_tpu.metrics import telemetry
        per_worker = {}
        for key in ("device_wait_seconds", "device_memory_peak_bytes",
                    "device_memory_in_use_bytes", "tpla_latent_shards",
                    "mla_latent_page_bytes", "mla_latent_token_bytes",
                    "moe_experts_held", "moe_experts_published",
                    "state_row_bytes"):
            if key in stats:
                per_worker[key] = stats.pop(key)
        if "num_recompiles" in stats:
            per_worker["num_recompiles"] = stats["num_recompiles"]
        if per_worker:
            label = telemetry.worker_label(self.config.parallel_config)
            stats["workers"] = {label: per_worker}
        return stats
