"""TPU model runner: flat ragged batches, bucketed static shapes, one
jitted step.

Reference: vllm/v1/worker/gpu_model_runner.py:101 (``GPUModelRunner``:
_prepare_inputs :892, execute_model :1614, CUDA-graph capture :2683) and
the TPU variant tpu_model_runner.py:98 (bucketed precompilation
:1248-1443). TPU-native re-design:

* The whole forward + logits + sampling step is ONE jitted function; KV
  caches are donated so XLA updates them in place.
* Dynamic quantities (num tokens T, num sampling reqs R) are padded to a
  bucket lattice; each (T, R) pair compiles once. There is no CUDA-graph
  equivalent to manage — jit caching plays that role.
* Sharding: params/caches carry NamedShardings over the engine mesh; the
  same runner code is TP=1 and TP=N (GSPMD inserts the collectives).
"""

import functools
import os
import time
from contextlib import contextmanager
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.output import (ModelRunnerOutput,
                                                    SchedulerOutput)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics.stats import (ATTN_PAIR_BUCKETS,
                                                DEVICE_STEP_BUCKETS,
                                                STEP_REQUEST_BUCKETS,
                                                STEP_TOKEN_BUCKETS,
                                                STEP_TRANSFER_BUCKETS,
                                                Histogram, StepPhases)
from vllm_distributed_tpu.models.common import (AttentionBatch,
                                                TknpAttentionBatch)
from vllm_distributed_tpu.ops.attention import resolve_attention_backend
from vllm_distributed_tpu.sample.metadata import (ExtendedSamplingMetadata,
                                                  SamplingMetadata,
                                                  pack_sampling,
                                                  record_sampled,
                                                  unpack_sampling)
from vllm_distributed_tpu.sample.sampler import (MAX_LOGPROBS, sample_tokens,
                                                 sample_tokens_extended)
from vllm_distributed_tpu.utils import cdiv, make_buckets, pad_to_bucket
from vllm_distributed_tpu.worker.input_batch import InputBatch
from vllm_distributed_tpu.worker.step_inputs import StepInputs, StepLayout

logger = init_logger(__name__)


class TPUModelRunner:

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def _hist_apply_full(dev, rows, vals):
        """Overwrite whole history rows (admission/resume); padding rows
        carry an out-of-range index and drop."""
        return dev.at[rows].set(vals, mode="drop")

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def _hist_apply_delta(dev, d_rows, d_start, d_toks, d_len):
        """Append newly committed tokens per row (width = the
        runner's _hist_delta)."""
        D = d_toks.shape[1]
        pos = d_start[:, None] + jnp.arange(D, dtype=jnp.int32)[None, :]
        valid = jnp.arange(D, dtype=jnp.int32)[None, :] < d_len[:, None]
        rowm = jnp.broadcast_to(d_rows[:, None], pos.shape)
        pos = jnp.where(valid, pos, dev.shape[1])
        return dev.at[rowm, pos].set(d_toks, mode="drop")

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def _state_row_to_pool(pool, cache, row, slot):
        """SSM state snapshot: copy one request's state rows (axis 1 of
        every layer) into a snapshot-pool slot. Dispatched AFTER the
        step's forward, so program order guarantees the copied state is
        exactly the post-step (boundary) state."""
        return pool.at[:, slot].set(cache[:, row])

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def _state_pool_to_row(cache, pool, row, slot):
        """SSM state restore: fill a request's state rows from a pool
        slot. Dispatched BEFORE the forward — the segmented scan then
        re-enters mid-sequence through its has_init carry path
        (ops/mamba.build_segment_info)."""
        return cache.at[:, row].set(pool[:, slot])

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def _state_put_row(cache, value, row):
        """SSM state restore from a host checkpoint (crash recovery):
        upload the journaled state directly into the request's rows."""
        return cache.at[:, row].set(value)

    def __init__(self, config: EngineConfig, mesh,
                 model=None, params=None) -> None:
        self.config = config
        self.mesh = mesh
        sched_cfg = config.scheduler_config
        self.page_size = config.cache_config.block_size
        self.max_num_reqs = sched_cfg.max_num_seqs
        self.max_model_len = sched_cfg.max_model_len
        self.max_pages_per_req = cdiv(self.max_model_len, self.page_size)

        self.model = model
        self.params = params
        self.kv_caches: Optional[dict] = None
        # The pipelined step (async scheduling): device-resident
        # [max_num_reqs] mirror of each row's most recently sampled
        # token, written by the step's own sampler program
        # (record_sampled) and read by the next step's forward as it
        # unpacks its inputs (StepInputs.unpack) — the host never
        # round-trips decode tokens on the hot path, and the chain costs
        # no program, dispatch or transfer of its own.
        self._async_chain = config.scheduler_config.async_scheduling
        self._last_sampled_dev: Optional[jax.Array] = None
        # Device-resident sampling-history mirror (see _hist_rows_device).
        self._hist_dev: Optional[jax.Array] = None
        self._hist_len = np.zeros((self.max_num_reqs, ), np.int32)
        self._hist_ver = np.full((self.max_num_reqs, ), -1, np.int64)
        # Token parallelism: requests' pages live on one token-axis rank;
        # per-rank metadata is built each step (reference:
        # gpu_model_runner.py:334 _build_token_parallel_metadata).
        self.tknp_size = config.parallel_config.token_parallel_size

        # Worker-side KV connector (disaggregated prefill; reference:
        # gpu_model_runner.py maybe_setup_kv_connector :2047).
        # Multi-LoRA adapter slots (set up in load_model, which knows
        # the arch config).
        self.lora_manager = None
        from vllm_distributed_tpu.distributed.kv_transfer import (
            KVConnectorRole, create_kv_connector)
        self.kv_connector = create_kv_connector(config,
                                                KVConnectorRole.WORKER)
        # KV transfer composes with token parallelism: connectors
        # address pages by GLOBAL page id, and the eager gather/scatter
        # in kv_transfer/page_io works on the token-axis-sharded cache
        # (XLA moves the touched shards; validated by
        # tests/kv_transfer/test_shared_storage.py tknp case).

        self.input_batch = InputBatch(
            max_num_reqs=self.max_num_reqs,
            max_model_len=self.max_model_len,
            max_pages_per_req=self.max_pages_per_req,
            page_size=self.page_size,
        )

        self.token_buckets = make_buckets(
            16, sched_cfg.max_num_batched_tokens)
        self.req_buckets = make_buckets(8, self.max_num_reqs)
        # Disagg pool role ("prefill" | "decode" | None): prunes the
        # precompile lattice per role — a prefill replica skips the
        # multi-step decode variants, a decode replica
        # skips the prompt-logprob graphs (plp requests are exempt from
        # handoff and serve on the prefill pool); the decode pool's
        # token ladder itself is already capped by its pool config.
        self.pool_role = config.kv_transfer_config.pool_role

        # The runner's part of a step (update_states, prepare_inputs,
        # launch, device_wait, finish) as phases and spans. The engine
        # core puts its own StepPhases here, so that one timeline holds
        # the whole step; a runner driven without a core keeps this one.
        self.phases = StepPhases()
        # K/V tokens the step's queries attend to (context lengths
        # summed over the scheduled requests): what the attention kernel
        # must read, in tokens.
        self.kv_tokens_read_hist = Histogram(STEP_TOKEN_BUCKETS)
        # K/V token slots the paged attention kernel's decode groups
        # move in one full layer (whole kv blocks up to each
        # single-token sequence's end): beside kv_tokens_read's
        # single-token part, what the groups move for what they must.
        # A formula over the step's lengths (the block rounding), not a
        # reading of the kernel.
        self.decode_kv_walked_hist = Histogram(STEP_TOKEN_BUCKETS)
        # ... in kv blocks of this many positions: set once, with
        # _tile_params' memo.
        self._decode_kv_block = 0
        # Query rows of the step that latent attention served in the
        # multi-head form (ops/mla.latent_attention_values): 0 on a
        # decode step and for a model without a latent cache.
        self.latent_prefill_tokens_hist = Histogram(STEP_TOKEN_BUCKETS)
        self.ssd_chunk_tokens_hist = Histogram(STEP_TOKEN_BUCKETS)
        self.retention_chunk_tokens_hist = Histogram(STEP_TOKEN_BUCKETS)
        # Under a window cache group (_fill_window_group): the tokens ONE
        # window layer reads a step, and the query x key pairs a step
        # inside the masks of one window layer and of one full layer.
        self.kv_window_tokens_read_hist = Histogram(STEP_TOKEN_BUCKETS)
        self.attn_pairs_hist = {kind: Histogram(ATTN_PAIR_BUCKETS)
                                for kind in ("full", "window")}
        # ... and how many of the step's requests have a context longer
        # than the window: the ones whose window pages are being freed.
        self.requests_past_window_hist = Histogram(STEP_REQUEST_BUCKETS)
        self._latent_kernels: Optional[bool] = None
        # Host arrays that _prepare_inputs and the launch handed to the
        # device for the step through _h2d (explicit hand-overs only:
        # not the scalars an eager op puts, not the multi-step burst):
        # three on the default path (the packed forward buffer, the
        # packed sampling buffer, the sampled-row indices), more with an
        # optional field.
        self.host_transfers_hist = Histogram(STEP_TRANSFER_BUCKETS)
        self._step_transfers = 0
        # Device/compilation telemetry (metrics/telemetry.py): the
        # blocking device-fetch wait per step and the recompile counter
        # behind vdt:recompiles_total — a steady-state recompile is the
        # classic silent TPU perf killer, so it must be a counter an
        # alert can watch, not only a log line. The enable flag and the
        # engine core's transport recorder are captured ONCE at
        # construction (the envs registry re-reads os.environ per
        # access; the recorder install window only spans construction).
        from vllm_distributed_tpu.metrics import telemetry
        self._device_telemetry = telemetry.device_telemetry_enabled()
        self._telemetry = telemetry.current_recorder()
        self.device_wait_hist = Histogram(DEVICE_STEP_BUCKETS)
        self.num_recompiles = 0

        # Speculative decoding (ngram drafts verified in-step; reference:
        # v1/spec_decode/ngram_proposer.py + rejection_sampler.py). The
        # sampler runs on S+1 positions per sampling request; acceptance
        # is a host-side prefix match of the per-position target samples
        # against the drafts — unbiased (the emitted token at each
        # position IS the target sample) and zero extra device code.
        spec = config.speculative_config
        self.spec_k = (spec.num_speculative_tokens
                       if spec and spec.method in ("ngram", "draft_model",
                                                   "eagle") else 0)
        self.proposer = None
        self._draft_spec = None
        self._eagle_spec = None
        self._eagle = None
        # Per-request truncated draft-support metadata ([S, K] ids and
        # probs) written at proposal time, read by next step's
        # rejection verifier (see sample/sampler.py
        # spec_verify_rejection).
        self._draft_meta: dict[str, tuple] = {}
        if self.spec_k and spec.method == "ngram":
            from vllm_distributed_tpu.spec_decode.ngram_proposer import \
                NgramProposer
            self.proposer = NgramProposer(spec)
        elif self.spec_k and spec.method == "eagle":
            # EAGLE drafter builds with the target model (load_model
            # knows the geometry); its KV layers stack onto the
            # target's cache.
            self._eagle_spec = spec
        elif self.spec_k:
            # Draft model loads with the target model (load_model knows
            # the dtype); until then proposals are empty.
            self._draft_spec = spec
        # Max per-step append a history row can absorb without a full
        # re-upload: a step commits up to spec_k + 1 tokens per row
        # (accepted drafts + the target sample).
        self._hist_delta = max(8, self.spec_k + 1)
        # KV-write runs: worst case one partial page per request plus the
        # full pages the step writes. Padded as a deterministic function of
        # T (see _batch_shape) so it adds no lattice dimension.
        max_runs = (cdiv(sched_cfg.max_num_batched_tokens + 128,
                         self.page_size) + self.max_num_reqs)
        self.kv_run_buckets = make_buckets(8, max_runs)

        self._forward_fn = None
        self._sample_fn = None
        # Correctness-sentinel numerics watch (correctness_plane.py):
        # a tiny jitted logits reduction dispatched every
        # NUMERICS_TAP_STRIDE sample launches (it re-derives logits, so
        # per-step would double the lm-head cost), harvested one step
        # behind. Off (None) by default — VDT_CORRECTNESS=0 must keep
        # this path byte-identical. The countdown starts at 1 so the
        # first sample of a fresh runner is tapped (deterministic for
        # drills) and the stride paces steady state.
        self._numerics = None
        self._numerics_fn = None
        self._numerics_countdown = 1
        from vllm_distributed_tpu import envs
        if envs.VDT_CORRECTNESS:
            from vllm_distributed_tpu.correctness_plane import NumericsTap
            self._numerics = NumericsTap()
        # M-RoPE (Qwen2-VL): per-row ([prompt_len, 3] id table, decode
        # delta); active when the model declares mrope_section.
        self._mrope: dict[int, tuple] = {}
        self._mrope_on = False
        self._rng = np.random.default_rng(config.model_config.seed)
        # Spec-decode acceptance counters (reference:
        # v1/metrics SpecDecodingStats).
        self.spec_num_drafts = 0
        self.spec_num_draft_tokens = 0
        self.spec_num_accepted_tokens = 0
        # Steps that took the cascade (shared-prefix) attention path.
        self.cascade_steps = 0
        # Memoized "model uses the standard K/V page layout" (see
        # _detect_cascade); None until the model is loaded.
        self._cascade_layout_ok: Optional[bool] = None
        # Shapes warmed by precompile(); execute-time compiles outside this
        # set are recompile-guard violations (reference:
        # tpu_model_runner.py:318 _update_num_xla_graphs).
        self._compiled_shapes: set[tuple] = set()
        self._precompiled = False
        # Mega-kernel partition parameters: resolved once the model is
        # loaded (None until then). _unified gates the collapsed compile
        # lattice + descriptor batches; (bq, sb) are the fixed
        # prefill-tile / decode-group sizes shared by the host
        # descriptor builder and the kernel.
        self._unified: Optional[bool] = None
        self._tile_params_memo: Optional[tuple[int, int]] = None
        # (T, G) -> StepLayout of the packed step buffer.
        self._step_layouts: dict[tuple[int, int], StepLayout] = {}
        self._xla_route_memo: Optional[bool] = None
        # Kernel-dispatch observability: one count per step per kernel
        # family (unified|decode|general|cascade|naive: five outcomes)
        # behind vdt:attn_kernel_calls_total, plus the warmed-graph
        # count behind vdt:precompile_graphs_total.
        self.attn_kernel_calls: dict[str, int] = {}
        self.precompile_graphs = 0
        # SSM state-snapshot pool (core/state_cache.py): per-state-array
        # device buffers of `resolve_state_slots` slots, written/read by
        # the scheduler's state_saves/state_restores directives. Built
        # in initialize_kv_cache once the model (and its state
        # geometry) exists; None for stateless models or with the cache
        # disabled.
        self._state_pool: Optional[dict] = None
        self._state_keys: list[str] = []
        self.num_state_checkpoints = 0
        self.num_state_restores = 0
        # Hierarchical KV tiering (core/kv_tier.py): the scheduler's
        # tier manager, shared in-proc (wired by the engine core after
        # construction). The runner executes the device legs — the
        # pre-forward demotion gather / promotion scatter directives
        # riding SchedulerOutput. None = untiered.
        self.kv_tier = None

    # ------------------------------------------------------------------
    def load_model(self) -> None:
        """Build the model and load weights per LoadConfig."""
        from vllm_distributed_tpu.models.loader import get_model
        self.model, self.params = get_model(self.config, self.mesh)
        self._to_serving_layout()
        self._mrope_on = bool(
            getattr(self.model.cfg, "mrope_section", None))
        if getattr(self.model, "CROSS_ATTENTION", False):
            # install_cross_states projects through the loaded cross
            # weights at admission time.
            self.model.params_ref = self.params
        self._init_lora_manager()
        if self._draft_spec is not None:
            from vllm_distributed_tpu.spec_decode.draft_model import \
                DraftModelProposer
            self.proposer = DraftModelProposer(
                self._draft_spec, self.model.cfg.dtype,
                max_num_reqs=self.max_num_reqs)
        if self._eagle_spec is not None:
            from vllm_distributed_tpu.spec_decode.eagle import EagleDrafter
            self._eagle = EagleDrafter(self._eagle_spec, self.model,
                                       self.max_num_reqs, self.page_size)
            host = self._eagle.load_params(self.params)
            specs = self._eagle.param_specs()
            with self.mesh:
                def place(p, key_specs):
                    if isinstance(p, dict):
                        return {k: place(v, key_specs[k])
                                for k, v in p.items()}
                    return jax.device_put(
                        p, NamedSharding(self.mesh, key_specs))

                placed = place(host, specs)
            self.params["eagle"] = placed
            self._eagle.eparams = placed

    def _to_serving_layout(self) -> None:
        """The loader returns the canonical layer-stacked tree; the
        jitted programs take the model's serving layout of it
        (LlamaForCausalLM.serving_layout: the same tree for a dense
        model). Called wherever weights are taken or re-placed."""
        self.params["layers"] = self.model.serving_layout(
            self.params["layers"])

    def _init_lora_manager(self) -> None:
        if self.config.lora_config.enable_lora:
            from vllm_distributed_tpu.models.lora import LoRASlotManager
            self.lora_manager = LoRASlotManager(
                self.model.cfg, self.config.lora_config.max_loras)

    def lora_buffer_trees(self):
        """(param-dict, (layer_start, layer_end)) pairs holding the
        stacked LoRA buffers — one pair for the single-program runner,
        one per stage under pipeline parallelism."""
        return [(self.params["layers"], (0, self.model.cfg.num_layers))]

    def _cache_layout(self, num_pages: int, mesh=None,
                      depth: Optional[int] = None) -> tuple:
        """(make, shapes, shardings) of the stacked caches on ``mesh``
        (default: the whole mesh); ``depth`` overrides the layer count
        for a pipeline stage's slice."""
        mesh = self.mesh if mesh is None else mesh
        if depth is None and self._eagle is not None:
            # EAGLE's draft KV layers stack onto the target's cache
            # (same pages/block tables; see spec_decode/eagle.py).
            depth = (self.model.cfg.num_layers +
                     self._eagle.num_layers)

        def make():
            return self.model.make_kv_caches(num_pages, self.page_size,
                                             num_layers=depth)

        shapes = jax.eval_shape(make)
        shardings = jax.tree.map(lambda _, s: NamedSharding(mesh, s),
                                 shapes, self.model.kv_cache_specs())
        return make, shapes, shardings

    def _make_sharded_caches(self, num_pages: int, mesh=None,
                             depth: Optional[int] = None) -> dict:
        """The stacked caches, created directly in their shardings: a
        jitted zeros program with ``out_shardings`` — built unsharded
        and then placed, the whole pool would first have to fit on the
        mesh's first device."""
        make, _, shardings = self._cache_layout(num_pages, mesh, depth)
        with self.mesh if mesh is None else mesh:
            return jax.jit(make, out_shardings=shardings)()

    def initialize_kv_cache(self, num_pages: int) -> None:
        assert self.model is not None
        self.num_pages = num_pages
        self.kv_caches = self._make_sharded_caches(num_pages)
        self._init_state_pool()
        if self._forward_fn is None:
            self._build_step_fn()

    # ------------------------------------------------------------------
    # SSM state-snapshot pool (core/state_cache.py device half)
    # ------------------------------------------------------------------
    def _state_cache_active(self) -> bool:
        # Snapshotable state only: Whisper/BART are STATEFUL (fixed
        # cross-attention rows) but expose no state_shapes() — the
        # snapshot pool must not activate for them (same gate the
        # scheduler applies via loader.resolve_state_snapshotable).
        if (self.model is None
                or not getattr(self.model, "STATEFUL", False)
                or not hasattr(self.model, "state_shapes")):
            return False
        from vllm_distributed_tpu.core.state_cache import \
            state_cache_enabled
        return state_cache_enabled(self.config, True)

    def _init_state_pool(self) -> None:
        if not self._state_cache_active():
            return
        from vllm_distributed_tpu.core.state_cache import \
            resolve_state_slots
        n_slots = resolve_state_slots(self.config)
        shapes = self.model.state_shapes()
        specs = self.model.kv_cache_specs()
        self._state_keys = sorted(shapes)
        with self.mesh:
            self._state_pool = {
                name: jax.device_put(
                    jnp.zeros((shape[0], n_slots) + shape[2:], dtype),
                    NamedSharding(self.mesh, specs[name]))
                for name, (shape, dtype) in shapes.items()
            }
        logger.info("SSM state pool: %d slots, %.2f MiB",
                    n_slots, self.state_pool_bytes() / 2**20)

    def _state_fingerprint(self) -> bytes:
        """Journal geometry fingerprint (core/state_cache.py): stamped
        into every checkpoint file and checked at lookup so a shared
        VDT_SSM_CKPT_DIR never serves a shape-foreign snapshot."""
        from vllm_distributed_tpu.core.state_cache import \
            state_fingerprint
        return state_fingerprint(self.model.state_shapes())

    def state_pool_slot_bytes(self) -> int:
        """Device bytes of ONE snapshot (all state arrays, all layers)."""
        if not self._state_cache_active():
            return 0
        return sum(
            int(np.prod((shape[0], ) + shape[2:]))
            * jnp.dtype(dtype).itemsize
            for shape, dtype in self.model.state_shapes().values())

    def state_pool_bytes(self) -> int:
        """Total pool footprint, charged against the fixed-state HBM
        budget by worker.determine_num_available_blocks."""
        if not self._state_cache_active():
            return 0
        from vllm_distributed_tpu.core.state_cache import \
            resolve_state_slots
        return resolve_state_slots(self.config) * \
            self.state_pool_slot_bytes()

    def _apply_state_restores(self, scheduler_output) -> None:
        """Execute state_restores BEFORE the forward: the restored rows
        are the carry the segmented scan re-enters with."""
        restores = getattr(scheduler_output, "state_restores", None)
        if not restores or self._state_pool is None:
            return
        from vllm_distributed_tpu.core.state_cache import read_journal
        with self.mesh, self.phases.span("vdt.state_copy",
                                         restores=len(restores)):
            self._run_state_restores(restores, read_journal)

    def _run_state_restores(self, restores, read_journal) -> None:
        for d in restores:
            row = self.input_batch.req_id_to_index.get(d.req_id)
            if row is None:
                logger.warning("state restore for unknown request %s",
                               d.req_id)
                continue
            if d.slot >= 0:
                for name in self._state_keys:
                    with self._compile_watch(("ssm_restore", name)):
                        self.kv_caches[name] = self._state_pool_to_row(
                            self.kv_caches[name], self._state_pool[name],
                            row, d.slot)
            else:
                # Crash-recovery journal hit: the scheduler verified the
                # checksum at lookup and carried the payload on the
                # (in-proc) directive. A re-read that fails must fail
                # loudly — uploading nothing would silently resume from
                # another request's state.
                arrays = d.arrays or read_journal(d.journal)
                if arrays is None:
                    raise RuntimeError(
                        f"SSM checkpoint {d.journal} became unreadable "
                        f"between scheduler lookup and restore")
                for name in self._state_keys:
                    with self._compile_watch(("ssm_put", name)):
                        self.kv_caches[name] = self._state_put_row(
                            self.kv_caches[name],
                            jnp.asarray(arrays[name]), row)
            self.num_state_restores += 1

    # ------------------------------------------------------------------
    # Hierarchical KV tiering (core/kv_tier.py device legs)
    # ------------------------------------------------------------------
    def _apply_kv_tier_pre(self, scheduler_output):
        """Pre-forward KV-tier device legs. The demotion gather
        dispatches FIRST — device program order pins the evicted
        pages' pre-overwrite contents while the actual device->host
        DMA overlaps the forward (the host fetch happens in
        ``_apply_kv_tier_post``). Promote scatters follow: staged
        wire-layout arrays land in their freshly allocated pages via
        the existing page_io staging + chunked donated scatter, all
        before the forward reads them."""
        tier = self.kv_tier
        if tier is None:
            return None
        demote = getattr(scheduler_output, "kv_demotes", None)
        promotes = getattr(scheduler_output, "kv_promotes", None)
        if demote is None and not promotes:
            return None
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.distributed.kv_transfer import page_io
        handle = None
        if demote is not None:
            handle = page_io.gather_pages_start(self, demote.page_ids)
        for d in promotes or ():
            t0 = time.perf_counter()
            k_np = np.stack([kv[0] for kv in d.arrays], axis=1)
            v_np = np.stack([kv[1] for kv in d.arrays], axis=1)
            k_dev, v_dev = page_io.stage_pages(self, k_np, v_np)
            chunk = max(1, int(envs.VDT_KV_APPLY_CHUNK_PAGES))
            for lo in range(0, len(d.page_ids), chunk):
                page_io.scatter_pages_chunk(self, d.page_ids, k_dev,
                                            v_dev, lo, chunk)
            # Histogram records the host-side dispatch cost (the
            # scatter itself overlaps the forward; correctness rides
            # program order, not completion).
            tier.record_promotion(d, time.perf_counter() - t0)
        return (demote, handle) if handle is not None else None

    def _apply_kv_tier_post(self, pending) -> None:
        """Post-dispatch half of a demotion: complete the (already
        in-flight) device->host copies and land each page in the host
        tier — the fetch, and any host->disk spill it triggers, run
        while the forward executes on device."""
        if pending is None:
            return
        demote, handle = pending
        from vllm_distributed_tpu.distributed.kv_transfer import page_io
        k_np, v_np = page_io.gather_pages_finish(self, handle)
        for i, key in enumerate(demote.keys):
            self.kv_tier.insert_host(key, k_np[:, i], v_np[:, i])

    def _apply_state_saves(self, scheduler_output) -> None:
        """Execute state_saves AFTER the forward dispatch: program order
        on the cache arrays guarantees the copy sees the post-step
        (exact-boundary) state. Journal-tagged saves additionally
        serialize the slot to the host checkpoint journal (a blocking
        device fetch — only taken when VDT_SSM_CKPT_DIR is set)."""
        saves = getattr(scheduler_output, "state_saves", None)
        if not saves or self._state_pool is None:
            return
        from vllm_distributed_tpu.core.state_cache import write_journal
        with self.mesh, self.phases.span("vdt.state_copy",
                                         saves=len(saves)):
            self._run_state_saves(saves, write_journal)

    def _run_state_saves(self, saves, write_journal) -> None:
        for d in saves:
            if not getattr(d, "persist_only", False):
                row = self.input_batch.req_id_to_index.get(d.req_id)
                if row is None:
                    logger.warning("state save for unknown request %s",
                                   d.req_id)
                    continue
                for name in self._state_keys:
                    with self._compile_watch(("ssm_save", name)):
                        self._state_pool[name] = self._state_row_to_pool(
                            self._state_pool[name], self.kv_caches[name],
                            row, d.slot)
                self.num_state_checkpoints += 1
            if d.journal:
                # persist_only: journal an already-committed slot whose
                # key (async save) only resolved at commit time.
                arrays = {
                    name: np.asarray(
                        jax.device_get(self._state_pool[name][:, d.slot]))
                    for name in self._state_keys
                }
                write_journal(d.journal, arrays, d.num_tokens,
                              fingerprint=self._state_fingerprint())

    # ------------------------------------------------------------------
    # Sharded-state checkpoints (reference: model_loader/
    # sharded_state_loader.py + Worker.save_sharded_state — pre-sharded
    # per-rank checkpoints for fast reload; here orbax writes each
    # array's shards in parallel from wherever they live on the mesh)
    # ------------------------------------------------------------------
    def save_sharded_state(self, path: str) -> None:
        import orbax.checkpoint as ocp
        ckpt = ocp.StandardCheckpointer()
        # On disk the tree is the canonical one the loader restores.
        ckpt.save(os.path.abspath(path), dict(
            self.params,
            layers=self.model.canonical_layout(self.params["layers"])))
        ckpt.wait_until_finished()
        logger.info("saved sharded state to %s", path)

    # ------------------------------------------------------------------
    # Sleep / wake (RLHF colocation; reference: CuMemAllocator tag-based
    # discard/offload, device_allocator/cumem.py:106, driven by the
    # EngineCore.sleep/wake_up RPCs, core.py:312-319)
    # ------------------------------------------------------------------
    def sleep(self, level: int = 1) -> int:
        """Release device HBM. Level 1 offloads weights to host and
        frees the KV cache; level 2 also drops the host copy (wake
        reloads from the checkpoint). Returns bytes released (approx:
        weights + KV)."""
        assert self.kv_caches is not None, "engine not initialized"
        freed = sum(x.nbytes
                    for x in jax.tree_util.tree_leaves(self.params))
        freed += sum(x.nbytes
                     for x in jax.tree_util.tree_leaves(self.kv_caches))
        if level == 1:
            # Each leaf goes back where it was: the shardings follow the
            # tree, whatever layout the model serves from.
            self._host_params = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), self.params)
            self._host_shardings = jax.tree.map(
                lambda x: x.sharding, self.params)
        else:
            self._host_params = None
        if self._hist_dev is not None:
            self._hist_dev.delete()
            self._hist_dev = None
            self._hist_ver[:] = -1
        if self._last_sampled_dev is not None:
            self._last_sampled_dev.delete()
            self._last_sampled_dev = None
        if self._state_pool is not None:
            # Snapshots die with the HBM; the engine core resets the
            # scheduler-side index so no stale slot is ever restored.
            freed += sum(x.nbytes for x in self._state_pool.values())
            for leaf in self._state_pool.values():
                leaf.delete()
            self._state_pool = None
        for leaf in jax.tree_util.tree_leaves(self.params):
            leaf.delete()
        for leaf in jax.tree_util.tree_leaves(self.kv_caches):
            leaf.delete()
        self.params = None
        self.kv_caches = None
        self._sleeping = True
        logger.info("sleeping: released ~%.2f GiB HBM (level %d)",
                    freed / 2**30, level)
        return freed

    def wake_up(self) -> None:
        """Restore weights + a fresh (empty) KV cache. Compiled step
        functions persist — shapes are unchanged, so no recompiles."""
        assert getattr(self, "_sleeping", False), "not sleeping"
        if self._host_params is not None:
            self.params = jax.device_put(self._host_params,
                                         self._host_shardings)
            self._host_params = self._host_shardings = None
        else:
            from vllm_distributed_tpu.models.loader import get_model
            self.model, self.params = get_model(self.config, self.mesh)
            self._to_serving_layout()
            if self.lora_manager is not None:
                # The reload came with fresh zero adapter buffers; the
                # slot map must forget its names or old adapters would
                # "resolve" to zeroed slots and silently serve the base
                # model. Safe: sleep requires an idle engine.
                self._init_lora_manager()
        if getattr(self.model, "CROSS_ATTENTION", False):
            self.model.params_ref = self.params  # old arrays deleted
        if self._eagle is not None and "eagle" in (self.params or {}):
            self._eagle.eparams = self.params["eagle"]
        self.kv_caches = self._make_sharded_caches(self.num_pages)
        self._init_state_pool()
        self._sleeping = False
        logger.info("awake: weights restored, KV cache reset")

    def kv_cache_bytes_per_page(self) -> int:
        """ONE device's HBM bytes per pool page — the worker budgets one
        device's memory — read off the layout the runner allocates
        (``_cache_layout``), whatever the model: how much a device's
        shards of the cache arrays grow per page added to the pool. A
        device holds only its shard of every page array (kv heads or
        latent lanes over the model axis, pages over the token axis);
        arrays that do not grow with the pool (SSM state rows) cost
        nothing per page."""
        import math

        def device_bytes(num_pages: int) -> int:
            _, shapes, shardings = self._cache_layout(num_pages)
            return sum(
                math.prod(sh.shard_shape(x.shape)) * x.dtype.itemsize
                for x, sh in zip(jax.tree.leaves(shapes),
                                 jax.tree.leaves(shardings)))

        # Any product of mesh axes divides a step of mesh.size pages.
        n = self.mesh.size
        return (device_bytes(2 * n) - device_bytes(n)) // n

    def model_fixed_cache_bytes(self) -> int:
        """Per-request fixed state bytes (SSM rows); 0 for paged-KV-only
        models."""
        fn = getattr(self.model, "fixed_cache_bytes", None)
        return fn() if fn is not None else 0

    def _build_step_fn(self) -> None:
        """Two jits instead of one: forward (shapes keyed by the token
        bucket T) and logits+sample (keyed by the sampling-rows bucket R).
        The split makes the precompile lattice ADDITIVE (|T| + |R| graphs)
        instead of multiplicative (|T| x |R|) — the TPU answer to the
        reference's per-shape warm-up suite (tpu_model_runner.py:1248).
        The [R]-row gather between them runs op-by-op (one XLA gather)."""
        model = self.model
        eagle = self._eagle

        def forward(params, kv_caches, inputs: StepInputs):
            token_ids, batch = inputs.unpack()
            hidden, kv_caches = model.forward(params, kv_caches, token_ids,
                                              batch)
            if eagle is not None:
                # The drafter advances its KV in the SAME program: every
                # scheduled token's (embedding, target hidden) runs the
                # eagle layers, writing cache rows past the target's
                # depth (reference: eagle.py:120 advances per step).
                kv_caches = eagle.advance(params["eagle"], kv_caches,
                                          token_ids, hidden, batch)
            return kv_caches, hidden

        # The samplers take the [5, R] packed sampling buffer
        # (sample/metadata.py pack_sampling) and unpack it first.
        # ``last`` (the pipelined step): the mirror of each row's last
        # sample, donated, returned with this step's tokens recorded
        # and replicated as it came.
        replicated = NamedSharding(self.mesh, PartitionSpec())

        def record(last, sampling_packed, tokens):
            if last is None:
                return None
            return jax.lax.with_sharding_constraint(
                record_sampled(last, sampling_packed, tokens), replicated)

        def sample(params, hidden_sel, sampling_packed, last=None):
            logits = model.compute_logits(params, hidden_sel)
            tokens, logprobs = sample_tokens(
                logits, unpack_sampling(sampling_packed))
            return tokens, logprobs, record(last, sampling_packed, tokens)

        def sample_ext(params, hidden_sel, sampling_packed,
                       ext: ExtendedSamplingMetadata, want_topk: bool,
                       vocab_mask=None, last=None):
            logits = model.compute_logits(params, hidden_sel)
            out = sample_tokens_extended(
                logits, unpack_sampling(sampling_packed), ext, want_topk,
                vocab_mask)
            return *out, record(last, sampling_packed, out[0])

        def prompt_lp(params, sel, targets):
            """Score prompt positions: log-softmax over the LM head at
            the pre-gathered rows [P, H], returning the target (= actual
            next prompt token) logprob plus the top-k alternatives
            (reference: the prompt_logprobs gather of
            gpu_model_runner._get_prompt_logprobs_dict). The row gather
            runs op-by-op outside so the graph keys only on the P
            bucket — ADDITIVE with the forward lattice, like the
            forward/sample split."""
            logits = model.compute_logits(params, sel)
            lp = jax.nn.log_softmax(logits, axis=-1)
            tgt = jnp.take_along_axis(lp, targets[:, None], axis=1)[:, 0]
            topv, topi = jax.lax.top_k(
                lp, min(MAX_LOGPROBS, lp.shape[-1]))
            return tgt, topv, topi

        def spec_verify(params, hidden_sel, drafts, q_ids, q_probs,
                        sampling_packed, truncate: bool = False):
            """Logits + true rejection-sampling verification in one
            graph (reference: v1/sample/rejection_sampler.py:23); keyed
            by the R bucket like the plain sampler."""
            import dataclasses as _dc

            from vllm_distributed_tpu.sample.sampler import \
                spec_verify_rejection
            logits = model.compute_logits(params, hidden_sel)
            sampling_md = unpack_sampling(sampling_packed)
            R = drafts.shape[0]
            S1 = hidden_sel.shape[0] // R
            # The dispatch path builds [R*S1]-expanded metadata (the
            # plain sampler's layout); the verifier wants per-row fields
            # and the per-position seeds.
            md_r = _dc.replace(
                sampling_md,
                temperature=sampling_md.temperature.reshape(R, S1)[:, 0],
                top_k=sampling_md.top_k.reshape(R, S1)[:, 0],
                top_p=sampling_md.top_p.reshape(R, S1)[:, 0],
                min_p=sampling_md.min_p.reshape(R, S1)[:, 0])
            return spec_verify_rejection(
                logits.reshape(R, S1, logits.shape[-1]), drafts, q_ids,
                q_probs, md_r, truncate=truncate)

        def numerics(params, hidden_sel):
            """Correctness-sentinel reduction over the SAME rows the
            sampler consumes: [nonfinite logits, mean entropy, mean
            top-1/top-2 margin]. One extra LM-head matmul per step —
            the sentinel's documented device cost."""
            logits = model.compute_logits(params, hidden_sel)
            bad = jnp.sum(~jnp.isfinite(logits)).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits, axis=-1)
            ent = -jnp.sum(jnp.exp(lp) * lp, axis=-1)
            top2 = jax.lax.top_k(logits, 2)[0]
            return jnp.stack([
                bad, jnp.mean(ent), jnp.mean(top2[:, 0] - top2[:, 1])
            ])

        # Donate the caches: XLA aliases them in place of a copy.
        self._forward_fn = jax.jit(forward, donate_argnums=(1, ))
        if self._numerics is not None:
            self._numerics_fn = jax.jit(numerics)
        self._plp_fn = jax.jit(prompt_lp)
        self._sample_fn = jax.jit(sample, donate_argnames=("last", ))
        self._sample_ext_fn = jax.jit(sample_ext,
                                      static_argnames=("want_topk", ),
                                      donate_argnames=("last", ))
        self._spec_verify_fn = jax.jit(spec_verify,
                                       static_argnames=("truncate", ))
        self._build_multi_step_fn()

    def _build_multi_step_fn(self) -> None:
        """N fused decode steps in one jitted lax.scan: the host pays one
        dispatch+sync per burst instead of per token (TPU answer to the
        reference's multi-step scheduling + advance_step.cu in-place input
        update; sampled tokens feed the next step on-device)."""
        import dataclasses

        model = self.model
        page_size = self.page_size
        mrope_on = self._mrope_on

        def multi_step(params, kv_caches, tok0, pos0, block_tables,
                       sampling_md: SamplingMetadata, seeds, num_active,
                       mrope_deltas):
            R = tok0.shape[0]
            rows = jnp.arange(R, dtype=jnp.int32)
            ones = jnp.ones((R, ), jnp.int32)

            def one(carry, seeds_t):
                kv, tok, pos = carry
                active = rows < num_active[0]
                page = block_tables[rows, pos // page_size]
                off = pos % page_size
                slot = jnp.where(active, page * page_size + off, -1)
                seq_info = jnp.stack([rows, ones, pos + 1, rows], axis=1)
                # One single-token page-write run per active request.
                kv_runs = jnp.stack(
                    [page, off, rows - off + page_size,
                     jnp.where(active, 1, 0)], axis=1)
                mrope = None
                if mrope_on:
                    # Decode ids continue at position + delta on all
                    # three rotary dims (qwen2_vl get_rope_index).
                    mrope = jnp.broadcast_to(
                        (pos + mrope_deltas)[:, None], (R, 3))
                batch = AttentionBatch(
                    req_idx=rows, positions=pos, slot_mapping=slot,
                    block_tables=block_tables, seq_lens=pos + 1,
                    seq_info=seq_info, num_seqs=num_active,
                    kv_runs=kv_runs, num_kv_runs=num_active,
                    mrope_positions=mrope, max_q=1)
                hidden, kv = model.forward(params, kv, tok, batch)
                logits = model.compute_logits(params, hidden)
                md_t = dataclasses.replace(sampling_md, seeds=seeds_t)
                tok_next, logprobs = sample_tokens(logits, md_t)
                return (kv, tok_next, pos + 1), (tok_next, logprobs)

            (kv, _, _), (toks, lps) = jax.lax.scan(
                one, (kv_caches, tok0, pos0), seeds)
            return kv, toks, lps

        self._multi_step_fn = jax.jit(multi_step, donate_argnums=(1, ))

    # ------------------------------------------------------------------
    def _update_states(self, scheduler_output: SchedulerOutput) -> None:
        for req_id in scheduler_output.finished_req_ids:
            if self.lora_manager is not None:
                row = self.input_batch.req_id_to_index.get(req_id)
                if row is not None and self.input_batch.lora_slot[row]:
                    self.lora_manager.release(
                        int(self.input_batch.lora_slot[row]))
            self._draft_meta.pop(req_id, None)
            self.input_batch.remove_request(req_id)
        for new_req in scheduler_output.scheduled_new_reqs:
            row = self.input_batch.add_request(new_req)
            if self._mrope_on:
                from vllm_distributed_tpu.multimodal import \
                    compute_mrope_positions
                if new_req.mm_inputs:
                    self._mrope[row] = compute_mrope_positions(
                        len(new_req.prompt_token_ids),
                        new_req.mm_inputs)
                else:
                    self._mrope[row] = (None, 0)
            if getattr(self.model, "CROSS_ATTENTION", False):
                # Encoder-decoder (whisper): project the audio
                # encoder's hidden states into this request's
                # cross-KV state row (offset=-1 payloads; reference:
                # the cross-attn KV fill of models/whisper.py). A row
                # claimed WITHOUT a payload must have its stale state
                # masked — the previous occupant's audio/document would
                # otherwise leak into this request's cross-attention.
                installed = False
                for inp in (new_req.mm_inputs or ()):
                    if inp.offset < 0:
                        self.kv_caches = self.model.install_cross_states(
                            self.kv_caches, row, inp.embeds)
                        installed = True
                if not installed:
                    self.kv_caches = self.model.clear_cross_states(
                        self.kv_caches, row)
            if new_req.lora_request is not None:
                if self.lora_manager is None:
                    raise ValueError(
                        "request carries a LoRA adapter but the engine "
                        "was built without enable_lora")
                self.input_batch.lora_slot[row] = \
                    self.lora_manager.acquire(
                        new_req.lora_request["name"],
                        new_req.lora_request["path"], self)
        self.input_batch.update_cached(scheduler_output.scheduled_cached_reqs)

    def _use_unified(self) -> bool:
        """Mega-kernel (partition-descriptor) batches: on for every
        model with the standard K/V page layout. MLA models (latent
        cache, own kernel keyed by max_q) keep the legacy composition-
        split shapes."""
        if self._unified is None:
            if self.model is None:
                return False  # don't memoize before the model exists
            self._unified = "k" in self.model.kv_cache_specs()
        return self._unified

    def _latent_prefill_tokens(self, q_lens: np.ndarray,
                               max_q: int) -> int:
        """Query rows of a step that the latent layers attend in the
        multi-head form: the condition ``ops/mla.latent_attention_values``
        tests when it traces the program (a chunk program, on the
        latent kernels, no TPLA) and its rule for a sequence."""
        if self._latent_kernels is None:
            from vllm_distributed_tpu.ops.attention import \
                resolve_attention_backend
            self._latent_kernels = (
                bool(getattr(self.model.cfg, "mla", False))
                and getattr(self.model, "tpla_shards", 1) <= 1
                and resolve_attention_backend() == "pallas")
        if max_q <= 1 or not self._latent_kernels:
            return 0
        from vllm_distributed_tpu.ops.mla import takes_multi_head_form
        return int(q_lens[takes_multi_head_form(q_lens)].sum())

    def _chunk_form_tokens(self, q_lens: np.ndarray, T: int) -> dict:
        """Tokens of a step that the model's state kernels take in the
        chunked form, by kernel family (the model's own
        ``chunk_form_tokens``: its op's rule for a program of ``T``
        tokens); nothing for a model without such layers."""
        hook = getattr(self.model, "chunk_form_tokens", None)
        return hook(q_lens, T) if hook is not None else {}

    def _tile_params(self) -> tuple[int, int]:
        """The fixed (prefill tile rows, decode group width) of the
        mega-kernel, computed from LOCAL head counts (the kernel runs
        per-shard under tensor parallelism) and the storage head dim.
        The same values ride the batch as statics so the host-built
        descriptor and the kernel can never disagree."""
        if self._tile_params_memo is None:
            from vllm_distributed_tpu.ops.attention import \
                storage_head_dim
            from vllm_distributed_tpu.ops.pallas_attention import (
                decode_group_size, kv_block_pages, prefill_tile_size)
            cfg = self.model.cfg
            tp = max(1, self.config.parallel_config.tensor_parallel_size)
            qh = max(1, cfg.num_q_heads // tp)
            kvh = max(1, getattr(cfg, "total_kv_heads",
                                 cfg.num_kv_heads) // tp)
            hd = storage_head_dim(cfg.head_dim)
            self._tile_params_memo = (prefill_tile_size(qh, hd),
                                      decode_group_size(qh, kvh))
            self._decode_kv_block = self.page_size * kv_block_pages(
                self.page_size, self.max_pages_per_req)
        return self._tile_params_memo

    def _batch_shape(self, total_tokens: int,
                     max_sched: int) -> tuple[int, int, int]:
        """Static (T, max_q, G) for a step.

        Unified (mega-kernel) models: the batch composition is carried
        by the partition descriptor, NOT by any static — ``max_q`` is
        pinned to 1 and T = t_bucket + Q_TILE_PAD for every mix of
        prefill and decode, so the forward lattice is exactly one graph
        per token bucket (decode buckets coincide with small token
        buckets and dedupe away).

        Legacy (MLA) models: ``max_q`` is 1 for pure decode, else the
        token bucket (the kernel grid skips tiles past each sequence's
        q_len), splitting each bucket into a decode and a prefill
        variant. G (KV-write run bucket) is a deterministic function of
        T in both modes."""
        t_bucket = pad_to_bucket(total_tokens, self.token_buckets)
        if self._use_unified():
            from vllm_distributed_tpu.ops.pallas_attention import \
                Q_TILE_PAD
            max_q = 1
            T = t_bucket + Q_TILE_PAD
        else:
            max_q = 1 if max_sched <= 1 else t_bucket
            T = t_bucket + min(max_q, 128)
        G = pad_to_bucket(cdiv(T, self.page_size) + self.max_num_reqs,
                          self.kv_run_buckets)
        return T, max_q, G

    def _fast_decode_rows(self, scheduler_output: SchedulerOutput):
        """Vectorized-prep eligibility: a pure single-token decode batch
        with no new/resumed rows and none of the per-token features the
        general loop handles (spec drafts, M-RoPE tables, LoRA slot
        grouping, token-parallel rank views, prompt-logprob scoring, mm
        placeholder substitution). Returns (rows, req_ids) when every
        scheduled request is one decode token past its prompt, else
        None — _prepare_inputs then fills the flat arrays with numpy
        gathers instead of the per-request python loop (delta-style
        prep for the decode steady state, where host time is the
        throughput ceiling)."""
        num_sched = scheduler_output.num_scheduled_tokens
        if (self.spec_k or self.tknp_size > 1 or self._mrope_on
                or self.lora_manager is not None
                or scheduler_output.scheduled_new_reqs
                or scheduler_output.total_num_scheduled_tokens
                != len(num_sched)):
            return None
        ib = self.input_batch
        req_ids = list(num_sched)
        rows = np.fromiter((ib.req_id_to_index[r] for r in req_ids),
                           np.int32, count=len(req_ids))
        starts = ib.num_computed[rows]
        # Every row past its prompt (no plp entries, no mm windows) and
        # sampling this step (start+1 reaches all committed tokens —
        # continuation prefills with backlog take the general loop).
        if not (np.all(starts >= ib.prompt_len[rows])
                and np.all(starts + 1 >= ib.num_tokens[rows])):
            return None
        return rows, req_ids

    def _last_sampled(self) -> Optional[jax.Array]:
        """The pipelined step's mirror of each row's last sample; None
        off that path."""
        if not self._async_chain:
            return None
        if self._last_sampled_dev is None:
            # Replicated over the mesh, as the samplers return it: one
            # sharding whoever made it, so one forward program.
            self._last_sampled_dev = jax.device_put(
                np.zeros((self.max_num_reqs, ), np.int32),
                NamedSharding(self.mesh, PartitionSpec()))
        return self._last_sampled_dev

    def _h2d(self, x) -> jax.Array:
        """One explicit host-to-device hand-over of the step, counted
        into vdt:step_host_transfers."""
        self._step_transfers += 1
        return jnp.asarray(x)

    @property
    def _window_group(self) -> Optional[int]:
        """The window of the model's second cache group; None: one
        group, the packed step buffer and the programs every model had."""
        return getattr(getattr(self.model, "cfg", None), "window_group",
                       None)

    def _fill_window_group(self, f: dict, num_tokens: int, num_runs: int,
                           n_kv_runs: int) -> None:
        """The window group's part of the packed buffer, from the full
        group's: the tables are position-aligned, so a token's slot and
        a write run's page are looked up at the same table index. The
        slots the manager freed behind the window are nulled by its own
        rule (``num_window_dead_blocks`` of the step's computed tokens):
        the kernel never gets a freed page's id. Feeds the histograms of
        what a window layer and a full layer do this step."""
        from vllm_distributed_tpu.core.kv_cache_utils import (
            num_window_dead_blocks, window_step_work)
        ib = self.input_batch
        W, ps = self._window_group, self.page_size
        table = f["window_block_tables"]
        table[:] = ib.window_block_table
        dead = num_window_dead_blocks(ib.num_computed, W, ps)
        table[np.arange(table.shape[1])[None, :] < dead[:, None]] = 0
        rows = f["req_idx"][:num_tokens]
        pos = f["positions"][:num_tokens]
        f["window_slot_mapping"][:num_tokens] = (
            table[rows, pos // ps] * ps + pos % ps)
        runs = f["kv_runs"][:n_kv_runs]
        first = runs[:, 2] + runs[:, 1] - ps   # a run's first token
        f["window_kv_runs"][:n_kv_runs] = runs
        f["window_kv_runs"][:n_kv_runs, 0] = table[
            f["req_idx"][first], f["positions"][first] // ps]
        q_len, end = f["seq_info"][:num_runs, 1], f["seq_info"][:num_runs, 2]
        tokens_read, full, window = window_step_work(q_len, end, W)
        self.kv_window_tokens_read_hist.observe(int(tokens_read.sum()))
        self.requests_past_window_hist.observe(int((end > W).sum()))
        self.attn_pairs_hist["full"].observe(int(full.sum()))
        self.attn_pairs_hist["window"].observe(int(window.sum()))

    def _step_layout(self, T: int, G: int) -> StepLayout:
        """The packed buffer's layout at forward shape (T, *, G)."""
        layout = self._step_layouts.get((T, G))
        if layout is None:
            P_desc = 0
            if self._use_unified():
                from vllm_distributed_tpu.ops.pallas_attention import (
                    Q_TILE_PAD, num_partition_programs)
                bq, sb = self._tile_params()
                P_desc = num_partition_programs(
                    T - Q_TILE_PAD, self.max_num_reqs, bq=bq, sb=sb,
                    num_kv_writes=G)
            layout = self._step_layouts[(T, G)] = StepLayout(
                T, G, self.max_num_reqs, self.max_pages_per_req, P_desc,
                window_group=bool(self._window_group),
                chain=self._async_chain)
        return layout

    def _prepare_inputs(self, scheduler_output: SchedulerOutput):
        """Flatten the scheduled requests into padded per-token arrays:
        views of ONE packed int32 buffer (worker/step_inputs.py), shipped
        with one transfer."""
        ib = self.input_batch
        num_sched = scheduler_output.num_scheduled_tokens
        total_tokens = scheduler_output.total_num_scheduled_tokens
        self._step_transfers = 0
        # Static shape bucket; token arrays carry one extra q tile of
        # padding so a sequence's final tile may spill past its q_len
        # (see ops/pallas_attention.py).
        T, max_q, G = self._batch_shape(total_tokens,
                                        max(num_sched.values()))
        layout = self._step_layout(T, G)
        buf = layout.new_buffer()
        f = layout.views(buf)
        token_ids, positions = f["token_ids"], f["positions"]
        req_idx, slot_mapping = f["req_idx"], f["slot_mapping"]
        seq_info, kv_runs_arr = f["seq_info"], f["kv_runs"]
        mrope_np = (np.zeros((T, 3), np.int32) if self._mrope_on
                    else None)
        kv_runs: list[tuple[int, int, int, int]] = []
        ps = self.page_size

        K = self.tknp_size
        if K > 1:
            # Per-rank views: a request's owner rank is implied by its
            # page range (the scheduler allocates each request's pages
            # from one rank's pool partition).
            Nl = self.num_pages // K
            tk_slot = np.full((K, T), -1, np.int32)
            tk_bt = np.zeros(
                (K, self.max_num_reqs, self.max_pages_per_req), np.int32)
            tk_seq_info = np.zeros((K, self.max_num_reqs, 4), np.int32)
            tk_num_seqs = np.zeros((K, 1), np.int32)
            tk_kv_runs = np.zeros((K, G, 4), np.int32)
            tk_num_kv_runs = np.zeros((K, 1), np.int32)

        sampling_rows: list[int] = []
        sampling_req_ids: list[str] = []
        logits_idx: list[int] = []
        spec_drafts: list[list[int]] = []
        # Prompt-logprob rows: flat row index + next-prompt-token target
        # per scored position (reference: the prompt_logprobs path of
        # gpu_model_runner._get_prompt_logprobs_dict).
        plp_rows: list[int] = []
        plp_targets: list[int] = []
        # (req_id, entry_index, k, target_token) per scored position.
        plp_meta: list[tuple[str, int, int, int]] = []
        # The pipelined step: a flat position whose input token is still
        # on the device (the sample of the step before, not yet landed
        # on the host) names the batch row to take it from.
        chain_src = f.get("chain_src")

        fast = self._fast_decode_rows(scheduler_output)
        if fast is not None:
            # Pure single-token decode: fill the flat arrays with
            # vectorized gathers against the persistent batch instead
            # of the per-request python loop — the decode steady state
            # is where per-step host time matters most.
            rows_np, fast_req_ids = fast
            N = len(rows_np)
            starts = ib.num_computed[rows_np].astype(np.int32)
            idx = np.arange(N, dtype=np.int32)
            token_ids[:N] = ib.token_ids[rows_np, starts]
            positions[:N] = starts
            req_idx[:N] = rows_np
            pages = ib.block_table[rows_np, starts // ps]
            offs = starts % ps
            slot_mapping[:N] = pages * ps + offs
            seq_info[:N] = np.stack(
                [idx, np.ones(N, np.int32), starts + 1, rows_np], axis=1)
            num_runs = N
            kv_runs_arr[:N] = np.stack(
                [pages, offs, idx - offs + ps,
                 np.ones(N, np.int32)], axis=1)
            n_kv_runs = N
            sampling_rows = [int(r) for r in rows_np]
            sampling_req_ids = fast_req_ids
            logits_idx = [int(i) for i in idx]
            if chain_src is not None:
                chain_src[:N] = np.where(starts >= ib.num_tokens[rows_np],
                                         rows_np, -1)
            t = N
            loop_items = ()
        else:
            loop_items = num_sched.items()
            t = 0
            num_runs = 0
        for req_id, n in loop_items:
            row = ib.req_id_to_index[req_id]
            start = ib.num_computed[row]
            end = start + n
            if chain_src is not None:
                # Positions past the host's committed tokens take the
                # previous step's on-device sample (run-ahead).
                known = max(start, int(ib.num_tokens[row]))
                chain_src[t + (known - start):t + n] = row
            drafts = (scheduler_output.scheduled_spec_decode_tokens.get(
                req_id, []) if self.spec_k else [])
            if drafts:
                # Draft tokens are not committed history: stage them into
                # the row's scratch tail so the flat slice below sees them
                # (they sit exactly at positions [end-D, end)).
                ib.token_ids[row, end - len(drafts):end] = drafts
            token_ids[t:t + n] = ib.token_ids[row, start:end]
            positions[t:t + n] = np.arange(start, end, dtype=np.int32)
            req_idx[t:t + n] = row
            if mrope_np is not None:
                # Prompt positions read the request's 3D id table;
                # generated positions continue at position + delta on
                # all three dims (reference: qwen2_vl get_rope_index).
                table, delta = self._mrope.get(row, (None, 0))
                seg = np.arange(start, end)
                vals = np.repeat((seg + delta)[:, None], 3, axis=1)
                if table is not None:
                    in_prompt = seg < table.shape[0]
                    vals[in_prompt] = table[seg[in_prompt]]
                mrope_np[t:t + n] = vals
            pos = np.arange(start, end)
            slot_mapping[t:t + n] = (
                ib.block_table[row, pos // ps] * ps + pos % ps)
            seq_info[num_runs] = (t, n, end, row)
            num_runs += 1
            k_plp = int(ib.prompt_logprobs[row])
            if k_plp >= 0 and start < int(ib.prompt_len[row]):
                # Row at position p predicts prompt token p+1; the row
                # at prompt_len-1 predicts the first OUTPUT token and is
                # the sampling row, not a prompt entry.
                for p in range(start,
                               min(end, int(ib.prompt_len[row]) - 1)):
                    tgt = int(ib.token_ids[row, p + 1])
                    plp_rows.append(t + (p - start))
                    plp_targets.append(tgt)
                    plp_meta.append((req_id, p + 1, k_plp, tgt))
                if end >= int(ib.prompt_len[row]):
                    # Final chunk scored: stop re-scoring on a
                    # preempt-resume re-run of an already-delivered
                    # prompt (the row persists across preemption).
                    ib.prompt_logprobs[row] = -1
            if K > 1:
                owner = int(ib.block_table[row, 0]) // Nl
                tk_slot[owner, t:t + n] = \
                    slot_mapping[t:t + n] - owner * Nl * ps
                tk_bt[owner, row] = np.maximum(
                    ib.block_table[row] - owner * Nl, 0)
                i_r = tk_num_seqs[owner, 0]
                tk_seq_info[owner, i_r] = (t, n, end, row)
                tk_num_seqs[owner, 0] = i_r + 1
            # Page-write runs for the Pallas KV-write kernel: maximal
            # consecutive-slot spans within one page.
            consumed = 0
            while consumed < n:
                p = start + consumed
                off = p % ps
                run_len = min(ps - off, n - consumed)
                src = t + consumed
                page_id = int(ib.block_table[row, p // ps])
                kv_runs.append((page_id, off, src - off + ps, run_len))
                if K > 1:
                    g = tk_num_kv_runs[owner, 0]
                    tk_kv_runs[owner, g] = (page_id - owner * Nl, off,
                                            src - off + ps, run_len)
                    tk_num_kv_runs[owner, 0] = g + 1
                consumed += run_len
            if end >= ib.num_tokens[row]:
                # This step finishes all known tokens: sample.
                sampling_rows.append(row)
                sampling_req_ids.append(req_id)
                logits_idx.append(t + n - 1)
                spec_drafts.append(drafts)
            t += n

        if fast is None:
            if kv_runs:
                kv_runs_arr[:len(kv_runs)] = kv_runs
            n_kv_runs = len(kv_runs)
        # seq_info rows are (q start, q len, kv len, batch row): kv len
        # is the context (computed + scheduled) a request's queries see.
        self.kv_tokens_read_hist.observe(int(seq_info[:num_runs, 2].sum()))
        self.latent_prefill_tokens_hist.observe(
            self._latent_prefill_tokens(seq_info[:num_runs, 1], max_q))
        chunked = self._chunk_form_tokens(seq_info[:num_runs, 1], T)
        self.ssd_chunk_tokens_hist.observe(chunked.get("ssd", 0))
        self.retention_chunk_tokens_hist.observe(
            chunked.get("retention", 0))
        if self._window_group:
            self._fill_window_group(f, t, num_runs, n_kv_runs)

        # Mega-kernel partition descriptor: kv-write rows first (the
        # fused write+attend pass needs them to precede every attention
        # program), then prefill q-tiles and SB decode groups. The fast
        # decode path feeds its row vector directly (no q_len scan).
        bq = sb = 0
        P_desc = layout.desc_programs
        if P_desc:
            from vllm_distributed_tpu.ops.pallas_attention import (
                build_partition_descriptor, decode_kv_tokens_walked)
            bq, sb = self._tile_params()
            kv_lens = seq_info[:num_runs, 2]
            if fast is None:
                kv_lens = kv_lens[seq_info[:num_runs, 1] == 1]
            self.decode_kv_walked_hist.observe(
                decode_kv_tokens_walked(kv_lens, self._decode_kv_block))
            f["attn_desc"][:], f["decode_list"][:] = \
                build_partition_descriptor(
                    seq_info, num_runs, bq=bq, sb=sb,
                    num_programs=P_desc, num_kv_writes=n_kv_runs,
                    decode_rows=(np.arange(num_runs, dtype=np.int32)
                                 if fast is not None else None))
            if K > 1:
                tk_desc = np.zeros((K, P_desc, 3), np.int32)
                tk_dl = np.zeros((K, self.max_num_reqs), np.int32)
                for kk in range(K):
                    tk_desc[kk], tk_dl[kk] = build_partition_descriptor(
                        tk_seq_info[kk], int(tk_num_seqs[kk, 0]),
                        bq=bq, sb=sb, num_programs=P_desc)

        S1 = self.spec_k + 1  # sampled positions per sampling request
        R = pad_to_bucket(max(len(sampling_rows), 1), self.req_buckets)
        rows = np.asarray(sampling_rows +
                          [0] * (R - len(sampling_rows)), np.int32)
        if self.spec_k:
            # Each sampling request samples at its last D+1 positions
            # (the committed token + its drafts), padded to S+1 rows by
            # repeating the last index; drafts pad with -1 (never equal a
            # sampled token, so padding positions reject).
            from vllm_distributed_tpu.spec_decode.draft_model import \
                SUPPORT_K
            verify_idx = np.zeros((R, S1), np.int32)
            drafts_arr = np.full((R, self.spec_k), -1, np.int32)
            # Draft-support metadata for rejection-sampling verification:
            # proposers that sampled stochastically recorded their
            # truncated support; deterministic proposals (ngram, greedy
            # drafts) are a delta at the draft token — min(1, p/q) with
            # q = 1 accepts with exactly prob p(d), the same rate the
            # old prefix match achieved, so one verifier serves all.
            q_ids = np.zeros((R, self.spec_k, SUPPORT_K), np.int32)
            q_probs = np.zeros((R, self.spec_k, SUPPORT_K), np.float32)
            for i, li in enumerate(logits_idx):
                D = len(spec_drafts[i])
                verify_idx[i] = li  # default: repeat the last position
                verify_idx[i, :D + 1] = np.arange(li - D, li + 1)
                if D:
                    drafts_arr[i, :D] = spec_drafts[i]
                    meta = self._draft_meta.get(sampling_req_ids[i])
                    if meta is not None and meta[0].shape[1] == SUPPORT_K:
                        m_ids, m_probs = meta
                        q_ids[i, :D] = m_ids[:D]
                        q_probs[i, :D] = m_probs[:D]
                    else:
                        q_ids[i, :D, 0] = spec_drafts[i]
                        q_probs[i, :D, 0] = 1.0
            logits_indices = verify_idx.reshape(-1)
        else:
            drafts_arr = None
            q_ids = q_probs = None
            logits_indices = np.asarray(logits_idx + [0] *
                                        (R - len(logits_idx)), np.int32)

        # Seeds: seeded requests fold (user_seed, step-in-request) so runs
        # reproduce; unseeded draw from the engine rng.
        user_seed = ib.seed[rows]
        step_in_req = ib.num_tokens[rows].astype(np.int64)
        random_part = self._rng.integers(0, 2**31 - 1, size=R)
        seeds = np.where(user_seed >= 0,
                         user_seed * 1000003 + step_in_req, random_part)

        def expand(x):
            return np.repeat(x, S1, axis=0) if self.spec_k else x

        # Per-position seed offsets keep sampled positions independent.
        seeds_e = expand(seeds)
        if self.spec_k:
            seeds_e = seeds_e + 7919 * np.tile(np.arange(S1), R)
        # Shipped by _launch_sample, once the forward is on the device.
        record_rows = None
        if self._async_chain:
            # Where the sampler records each token for the next step's
            # forward; padding rows drop. (No spec decode on this path:
            # one sampled position a request.)
            record_rows = np.full((R, ), self.max_num_reqs, np.int32)
            record_rows[:len(sampling_rows)] = sampling_rows
        sampling_packed = pack_sampling(
            expand(ib.temperature[rows]), expand(ib.top_k[rows]),
            expand(ib.top_p[rows]), expand(ib.min_p[rows]), seeds_e,
            record_rows)
        ext_md = None
        want_topk = False
        if any(ib.extended_active(r) for r in sampling_rows):
            ext_md = self._build_extended_md(rows, expand)
            want_topk = bool(any(ib.num_logprobs[r] > 0
                                 for r in sampling_rows))
        # Structured-output grammar masks (reference: grammar bitmask on
        # the scheduler output, applied at gpu_model_runner.py:1433).
        # Dense [R, V] bool, padding/unconstrained rows all-True; only
        # built when a scheduled sampling request has a grammar.
        vocab_mask = None
        struct_masks = getattr(scheduler_output, "structured_masks",
                               None) or {}
        if struct_masks and any(rid in struct_masks
                                for rid in sampling_req_ids):
            V = self.model.cfg.vocab_size
            mask_np = np.ones((R, V), bool)
            for i, rid in enumerate(sampling_req_ids):
                m = struct_masks.get(rid)
                if m is not None:
                    # Tokenizer and model vocab sizes can differ (padded
                    # embeddings / unused ids): ids beyond the grammar
                    # table are never valid grammar bytes -> disallowed.
                    n = min(len(m), V)
                    mask_np[i, :n] = m[:n]
                    mask_np[i, n:] = False
            if self.spec_k:
                # Structured rows never carry drafts (the extended path
                # disables proposals), so only position 0 of each S1
                # group is ever emitted — repeating the pre-advance mask
                # across the group masks real samples correctly and the
                # discarded padding positions don't matter.
                mask_np = np.repeat(mask_np, S1, axis=0)
            vocab_mask = self._h2d(mask_np)
        tknp = None
        if K > 1:
            tknp = TknpAttentionBatch(
                slot_mapping=self._h2d(tk_slot),
                block_tables=self._h2d(tk_bt),
                seq_info=self._h2d(tk_seq_info),
                num_seqs=self._h2d(tk_num_seqs),
                kv_runs=self._h2d(tk_kv_runs),
                num_kv_runs=self._h2d(tk_num_kv_runs),
                desc=self._h2d(tk_desc) if P_desc else None,
                decode_list=self._h2d(tk_dl) if P_desc else None,
            )
        cascade_ids = self._detect_cascade(scheduler_output)
        lora_ctx = None
        if self.lora_manager is not None:
            # Token -> adapter-slot grouping, shared by every LoRA
            # matmul this step (padding tokens inherit row 0's slot —
            # their outputs are never read).
            from vllm_distributed_tpu.models.common import LoraBatch
            slots = ib.lora_slot[req_idx]
            order = np.argsort(slots, kind="stable")
            S = self.config.lora_config.max_loras + 1
            lora_ctx = LoraBatch(
                order=self._h2d(order.astype(np.int32)),
                inv=self._h2d(np.argsort(order).astype(np.int32)),
                group_sizes=self._h2d(
                    np.bincount(slots, minlength=S)[:S].astype(np.int32)),
                scaling=self._h2d(
                    self.lora_manager.scaling[slots[order]]),
            )
        # Multimodal: placeholder positions scheduled this step take
        # their pre-computed encoder rows (reference: the scheduled
        # encoder inputs of v1/core/sched/output.py + the embedding
        # merge in gpu_model_runner._execute_mm_encoder). Host loop over
        # real tokens only, and only on steps with an image request.
        mm_embeds = mm_mask = None
        def _mm_scheduled():
            # Cheap gate: a row needs substitution only while scheduled
            # positions can still fall inside a placeholder span (never
            # on decode steps; the row's first position this step is its
            # pre-step num_computed).
            for r in num_sched:
                row = ib.req_id_to_index[r]
                # offset < 0 marks cross-attention payloads (whisper
                # audio), consumed at admission, never substituted.
                mm_list = [inp for inp in (ib.mm[row] or ())
                           if inp.offset >= 0]
                if mm_list and ib.num_computed[row] < max(
                        inp.offset + inp.num_tokens for inp in mm_list):
                    return True
            return False
        if _mm_scheduled():
            Hd = self.model.cfg.hidden_size
            ov = np.zeros((T, Hd), np.float32)
            mk = np.zeros((T, ), bool)
            for ti in range(total_tokens):
                mm_list = ib.mm[req_idx[ti]]
                if not mm_list:
                    continue
                p = int(positions[ti])
                for inp in mm_list:
                    if 0 <= inp.offset <= p < inp.offset + inp.num_tokens:
                        ov[ti] = inp.embeds[p - inp.offset]
                        mk[ti] = True
                        break
            if mk.any():
                mm_embeds = self._h2d(ov)
                mm_mask = self._h2d(mk)
            # else: pure-decode step of an image request — no placeholder
            # positions scheduled; take the text-only graph (no [T, H]
            # upload, no mm-variant compile).

        f["block_tables"][:] = ib.block_table
        f["seq_lens"][:] = ib.num_computed
        f["num_seqs"][0] = num_runs
        f["num_kv_runs"][0] = n_kv_runs
        inputs = StepInputs.of(
            self._h2d(buf),
            layout,
            chain_last=self._last_sampled(),
            tknp=tknp,
            lora=lora_ctx,
            cascade_shared_ids=cascade_ids,
            mm_embeds=mm_embeds,
            mm_mask=mm_mask,
            mrope_positions=(self._h2d(mrope_np)
                             if mrope_np is not None else None),
            max_q=max_q,
            attn_bq=bq,
            attn_sb=sb,
        )
        plp = None
        if plp_rows:
            Pb = pad_to_bucket(len(plp_rows), self.token_buckets)
            rows_np = np.zeros((Pb, ), np.int32)
            tgt_np = np.zeros((Pb, ), np.int32)
            rows_np[:len(plp_rows)] = plp_rows
            tgt_np[:len(plp_targets)] = plp_targets
            plp = (self._h2d(rows_np), self._h2d(tgt_np), plp_meta)
        # Verifier truncation only when some batch row needs it (static
        # jit arg: the default-sampling serving case keeps the cheaper
        # untruncated verify graph; padding rows sit at the no-op
        # defaults so they never flip it).
        spec_truncate = bool(self.spec_k) and bool(
            (ib.top_k[rows] > 0).any() or (ib.top_p[rows] < 1.0).any()
            or (ib.min_p[rows] > 0.0).any())
        return (inputs, logits_indices, sampling_packed,
                sampling_req_ids, (T, max_q, G), R,
                (drafts_arr, q_ids, q_probs, spec_truncate), ext_md,
                want_topk, vocab_mask, plp)

    # Fixed sparse-bias width; keeps the graph keyed by R. Admission-time
    # validation in SamplingParams guarantees every request fits.
    from vllm_distributed_tpu.sampling_params import \
        BIAS_BUF_WIDTH as _BIAS_BUF

    def _hist_rows_device(self, rows: np.ndarray, expand) -> jax.Array:
        """[R(*S1), max_model_len] token history for the penalty kernels,
        gathered from a DEVICE-RESIDENT mirror of the input batch's
        token table. Per-step host->device traffic is O(R * _hist_delta)
        (the newly committed tokens), independent of max_model_len —
        round-2/3 ADVICE flagged the previous full [R, max_model_len]
        upload every penalty step. Rows re-upload in full only when
        their content was rewritten (admission, preemption resume) or
        drifted more than _hist_delta tokens while off the extended
        path."""
        ib = self.input_batch
        L = self.max_model_len
        max_reqs = ib.token_ids.shape[0]
        if self._hist_dev is None:
            self._hist_dev = jnp.zeros((max_reqs, L), jnp.int32)
        D = self._hist_delta
        R = len(rows)
        uniq = np.unique(rows)
        full_rows: list[int] = []
        d_rows = np.full((R, ), max_reqs, np.int32)  # pad -> dropped
        d_start = np.zeros((R, ), np.int32)
        d_toks = np.zeros((R, D), np.int32)
        d_len = np.zeros((R, ), np.int32)
        nd = 0
        for r in uniq:
            r = int(r)
            n = int(ib.num_tokens[r])
            behind = n - int(self._hist_len[r])
            if (self._hist_ver[r] != ib.row_version[r]
                    or not 0 <= behind <= D):
                full_rows.append(r)
                self._hist_ver[r] = ib.row_version[r]
            elif behind:
                s = n - behind
                d_rows[nd] = r
                d_start[nd] = s
                d_toks[nd, :behind] = ib.token_ids[r, s:n]
                d_len[nd] = behind
                nd += 1
            self._hist_len[r] = n
        if full_rows:
            fr = np.full((R, ), max_reqs, np.int32)
            fr[:len(full_rows)] = full_rows
            vals = np.zeros((R, L), np.int32)
            vals[:len(full_rows)] = ib.token_ids[full_rows]
            self._hist_dev = self._hist_apply_full(self._hist_dev,
                                              self._h2d(fr),
                                              self._h2d(vals))
        if nd:
            self._hist_dev = self._hist_apply_delta(
                self._hist_dev, self._h2d(d_rows),
                self._h2d(d_start), self._h2d(d_toks),
                self._h2d(d_len))
        rows_pad = np.asarray(expand(rows), np.int32)
        return self._hist_dev[self._h2d(rows_pad)]

    def _build_extended_md(self, rows: np.ndarray,
                           expand) -> ExtendedSamplingMetadata:
        """Lower per-row python sampling extras to the fixed-shape
        ExtendedSamplingMetadata (see sample/metadata.py). ``rows`` is the
        padded [R] array of input-batch row indices."""
        ib = self.input_batch
        R = len(rows)
        B = self._BIAS_BUF
        pad_id = self.model.cfg.vocab_size  # out of vocab -> scatter drops
        bias_ids = np.full((R, B), pad_id, np.int32)
        bias_vals = np.zeros((R, B), np.float32)
        base_fill = np.zeros((R, ), np.float32)
        for i, row in enumerate(rows):
            allowed = ib.allowed_token_ids[row]
            bias = ib.logit_bias[row]
            entries: dict[int, float] = {}
            if allowed is not None:
                base_fill[i] = float("-inf")
                entries = {t: (bias or {}).get(t, 0.0) for t in allowed}
            elif bias:
                entries = dict(bias)
            n_out = int(ib.num_tokens[row] - ib.prompt_len[row])
            if n_out < ib.min_tokens[row]:
                for s in ib.stop_token_ids[row]:
                    entries[s] = float("-inf")
            if len(entries) > B:
                raise ValueError(
                    f"request needs {len(entries)} logit-bias/mask entries; "
                    f"the static buffer holds {B}")
            for j, (t, v) in enumerate(entries.items()):
                bias_ids[i, j] = t
                bias_vals[i, j] = v
        return ExtendedSamplingMetadata(
            hist_tokens=self._hist_rows_device(rows, expand),
            prompt_len=self._h2d(expand(ib.prompt_len[rows])),
            total_len=self._h2d(expand(ib.num_tokens[rows])),
            presence_penalty=self._h2d(expand(ib.presence_penalty[rows])),
            frequency_penalty=self._h2d(expand(
                ib.frequency_penalty[rows])),
            repetition_penalty=self._h2d(expand(
                ib.repetition_penalty[rows])),
            bias_ids=self._h2d(expand(bias_ids)),
            bias_vals=self._h2d(expand(bias_vals)),
            base_fill=self._h2d(expand(base_fill)),
        )

    # ------------------------------------------------------------------
    def execute_model(self,
                      scheduler_output: SchedulerOutput) -> ModelRunnerOutput:
        return self.wait_model(self.dispatch_model(scheduler_output))

    def dispatch_model(self, scheduler_output: SchedulerOutput) -> dict:
        """Non-blocking half of a step: sync batch state, enqueue the
        device work, return a handle for wait_model(). The engine core's
        pipeline-parallel batch queue dispatches several of these before
        waiting on the oldest (reference: core.py:242
        step_with_batch_queue); requests in a dispatched batch are
        excluded from scheduling until their batch retires."""
        with self.phases.span(
                "vdt.dispatch_model",
                tokens=scheduler_output.total_num_scheduled_tokens,
                prefill_tokens=scheduler_output.num_prefill_tokens,
                decode_reqs=scheduler_output.num_decode_reqs):
            return self._dispatch_model(scheduler_output)

    def _dispatch_model(self, scheduler_output: SchedulerOutput) -> dict:
        ph = self.phases
        with ph.span("vdt._update_states", "update_states"):
            self._update_states(scheduler_output)
            # State restores BEFORE the forward (the scan's re-entry
            # carry); zero-token outputs never carry them (scheduler
            # invariant: the zero-token path does no device work).
            self._apply_state_restores(scheduler_output)
            # KV-tier demotion gather + promotion scatter, also
            # pre-forward (and, like state ops, never on zero-token
            # outputs).
            tier_pending = self._apply_kv_tier_pre(scheduler_output)
        if scheduler_output.total_num_scheduled_tokens == 0:
            # Nothing to run, but async KV transfers may need servicing:
            # hand queued peer reads / completed pulls to the connector
            # and report completion notifications (reference:
            # gpu_model_runner.py kv_connector_no_forward path).
            # CONTRACT: no device dispatch on this path — the PP batch
            # queue's sync fallback (engine/core.py) runs zero-token
            # batches while async batches are in flight and relies on it.
            out = ModelRunnerOutput()
            self._poll_kv_connector(scheduler_output, out)
            return {"ready": out}
        if scheduler_output.multi_step > 1:
            # The burst blocks for its device results inside
            # _execute_multi_step.
            with ph.span("vdt.device_wait", "device_wait"):
                out = self._execute_multi_step(scheduler_output)
            self._apply_kv_tier_post(tier_pending)
            return {"ready": out}

        with ph.span("vdt._prepare_inputs", "prepare_inputs"):
            (inputs, logits_indices, sampling_packed, sampling_req_ids,
             fwd_shape, R, spec_pack, ext_md, want_topk, vocab_mask,
             plp) = self._prepare_inputs(scheduler_output)
        # Launch: from the prepared inputs to the handle (forward and
        # sampler launches, state saves).
        with ph.span("vdt.launch", "launch"):
            attn_label = self._attn_kernel_label(inputs)
            self._count_attn_dispatch(attn_label)
            drafts_arr, q_ids, q_probs, spec_truncate = spec_pack
            kv_meta = scheduler_output.kv_connector_metadata
            if self.kv_connector is not None and kv_meta is not None:
                # External KV lands in the paged cache BEFORE the forward
                # (reference: maybe_setup_kv_connector/start_load_kv).
                self.kv_connector.start_load_kv(kv_meta, self)

            # Rejection-sampling verification handles every spec batch
            # except extended/structured ones (those rows never carry
            # drafts; the plain expanded sampler + host prefix match stays
            # exact for them).
            spec_q = None
            if (self.spec_k and ext_md is None and vocab_mask is None):
                spec_q = (self._h2d(drafts_arr), self._h2d(q_ids),
                          self._h2d(q_probs), spec_truncate)
            with ph.forward_issue(*self._step_shape(scheduler_output)):
                dev = self._launch_device_step(
                    inputs, logits_indices, sampling_packed, fwd_shape,
                    ext_md, want_topk, vocab_mask, plp=plp, spec_q=spec_q)
            # What the fetch will wait for: the sampled tokens, or the
            # verifier's first output.
            flight = ph.in_flight(dev[0][0] if spec_q is not None
                                  else dev[0])
            # State snapshots AFTER the forward dispatch: program order on
            # the (donated) cache arrays makes the copy read post-step rows.
            self._apply_state_saves(scheduler_output)
            # Demotion host fetch AFTER the forward dispatch: the copies
            # were started pre-forward, so they complete while the device
            # runs the step.
            self._apply_kv_tier_post(tier_pending)
            self.host_transfers_hist.observe(self._step_transfers)
            return {"so": scheduler_output, "dev": dev, "kv_meta": kv_meta,
                    "flight": flight,
                    "sampling_req_ids": sampling_req_ids,
                    "drafts_arr": drafts_arr, "R": R,
                    "specv": spec_q is not None,
                    "plp_meta": plp[2] if plp else None}

    def _step_shape(self, scheduler_output: SchedulerOutput
                    ) -> tuple[str, int]:
        """(shape, token bucket) of a step, as StepPhases labels the
        chip's time for it: ``decode`` without a prefill token, ``chunk``
        with one in the largest token bucket this configuration compiles
        (a whole ``max_num_batched_tokens`` step), ``mixed`` otherwise."""
        t_bucket = pad_to_bucket(
            scheduler_output.total_num_scheduled_tokens,
            self.token_buckets)
        if not scheduler_output.num_prefill_tokens:
            return "decode", t_bucket
        return ("chunk" if t_bucket == self.token_buckets[-1]
                else "mixed"), t_bucket

    def wait_model(self, handle: dict) -> ModelRunnerOutput:
        """Blocking half: fetch the sampled tokens, fold them into the
        persistent batch, build the runner output."""
        if "ready" in handle:
            return handle["ready"]
        # This fetch is where the host blocks on the device (everything
        # since dispatch ran async). Under the pipelined step the next
        # forward is already issued when it starts, and its results may
        # be there already: ``ph.fetch`` asks first (``ready``), so a
        # wait is told from a copy and a waited fetch's return dates the
        # step's end on the device (StepPhases).
        ph = self.phases
        with ph.fetch(handle.get("flight")) as wait:
            if handle.get("specv"):
                verify = tuple(np.asarray(jax.device_get(x))
                               for x in handle["dev"][0])
                sample = (None, None, None)
            else:
                verify = None
                sample = self._fetch_sample(handle["dev"])
        wait_s = wait.seconds
        if self._device_telemetry:
            self.device_wait_hist.observe(wait_s)
        with ph.span("vdt.finish", "finish"):
            return self._finish_step(handle, verify, sample)

    def _finish_step(self, handle: dict, verify,
                     sample) -> ModelRunnerOutput:
        """The rest of wait_model once the samples are on the host."""
        scheduler_output = handle["so"]
        kv_meta = handle["kv_meta"]
        sampling_req_ids = handle["sampling_req_ids"]
        drafts_arr = handle["drafts_arr"]
        R = handle["R"]
        tokens_np, logprobs_np, topk_np = sample
        if verify is not None:
            (accept_np, residual_np, bonus_np, lp_cand_np,
             lp_bonus_np) = verify

        # Embedding requests: the pooled hidden state of the sampled row
        # is the result; no token is emitted (reference: pooling path of
        # the runner, v1/pool/). "last" pooling = the final prompt
        # position's hidden state, exact under chunked prefill too.
        pooled: dict[str, list[float]] = {}
        # .get: under async scheduling a trailing speculative batch can
        # retire after its request finished and left the input batch.
        pool_rows = [
            (i, rid)
            for i, rid in enumerate(handle["sampling_req_ids"])
            if (row := self.input_batch.req_id_to_index.get(rid))
            is not None and self.input_batch.pooling[row] is not None
        ]
        if pool_rows:
            S1 = self.spec_k + 1
            hidden_sel = handle["dev"][3]
            # Final-norm the pooled vectors so they match HF
            # last_hidden_state semantics (the model applies model.norm
            # before returning hidden states). One host transfer for
            # the weight (cached) and one for all pooled rows.
            if not hasattr(self, "_final_ln_np"):
                self._final_ln_np = np.asarray(
                    jax.device_get(self.params["final_ln"]), np.float32)
            w = self._final_ln_np
            eps = self.model.cfg.rms_norm_eps
            idx = np.asarray([i * S1 for i, _ in pool_rows], np.int32)
            vecs = np.asarray(jax.device_get(hidden_sel[idx]), np.float32)
            norms = np.sqrt(np.mean(vecs * vecs, axis=-1,
                                    keepdims=True) + eps)
            normed = vecs / norms * w
            for (_, rid), vec in zip(pool_rows, normed):
                pooled[rid] = [float(x) for x in vec]

        if self.kv_connector is not None and kv_meta is not None:
            # The forward wrote this step's KV; persist producer pages
            # (reference: save_kv_layer/wait_for_save, collapsed to one
            # post-step call — XLA ran the whole forward already).
            self.kv_connector.save_kv(kv_meta, self)

        req_ids, sampled, lps = [], [], []
        spec_out: Optional[list[list[int]]] = [] if self.spec_k else None
        if self.spec_k and handle.get("specv"):
            # Rejection-sampling verification (reference:
            # v1/sample/rejection_sampler.py): the longest accepted
            # draft prefix, then either the exact-residual resample at
            # the first rejection or the bonus sample after a clean
            # sweep. Emitted tokens are distributed exactly as the
            # target regardless of draft quality.
            S = self.spec_k
            n_acc = np.cumprod(accept_np.astype(np.int64),
                               axis=1).sum(axis=1)
            emitted_map: dict[str, list[int]] = {}
            for i, req_id in enumerate(sampling_req_ids):
                n_draft = int((drafts_arr[i] >= 0).sum())
                if n_draft:
                    self.spec_num_drafts += 1
                    self.spec_num_draft_tokens += n_draft
                    self.spec_num_accepted_tokens += int(n_acc[i])
                if req_id in pooled:
                    req_ids.append(req_id)
                    sampled.append([])
                    lps.append([])
                    continue
                na = int(n_acc[i])
                emitted = [int(t) for t in drafts_arr[i, :na]]
                elps = [float(x) for x in lp_cand_np[i, :na, 0]]
                if na == S:
                    emitted.append(int(bonus_np[i]))
                    elps.append(float(lp_bonus_np[i]))
                else:
                    emitted.append(int(residual_np[i, na]))
                    elps.append(float(lp_cand_np[i, na, 1]))
                for tok in emitted:
                    self.input_batch.append_token(req_id, tok)
                emitted_map[req_id] = emitted
                req_ids.append(req_id)
                sampled.append(emitted)
                lps.append([{tok: lp}
                            for tok, lp in zip(emitted, elps)])
            if self._eagle is not None:
                draft_map = self._propose_drafts_eagle(
                    sampling_req_ids, emitted_map, handle)
            else:
                draft_map = self._propose_drafts_all(
                    [r for r in sampling_req_ids if r not in pooled])
            spec_out.extend(draft_map.get(r, []) if r not in pooled
                            else [] for r in sampling_req_ids)
        elif self.spec_k:
            S1 = self.spec_k + 1
            toks = tokens_np.reshape(R, S1)
            lp2 = logprobs_np.reshape(R, S1)
            # Extended/structured batches: accept the longest draft
            # prefix the per-position target samples agree with;
            # position i's sample IS the emitted token, so the output
            # distribution equals non-spec sampling (the deterministic
            # limit of rejection sampling).
            match = toks[:, :self.spec_k] == drafts_arr
            accepted = np.cumprod(match.astype(np.int64), axis=1)
            num_emitted = 1 + accepted.sum(axis=1)
            for i in range(len(sampling_req_ids)):
                n_draft = int((drafts_arr[i] >= 0).sum())
                if n_draft:
                    self.spec_num_drafts += 1
                    self.spec_num_draft_tokens += n_draft
                    self.spec_num_accepted_tokens += int(num_emitted[i] - 1)
            for i, req_id in enumerate(sampling_req_ids):
                if req_id in pooled:
                    req_ids.append(req_id)
                    sampled.append([])
                    lps.append([])
                    continue
                emitted = [int(t) for t in toks[i, :num_emitted[i]]]
                for tok in emitted:
                    self.input_batch.append_token(req_id, tok)
                req_ids.append(req_id)
                sampled.append(emitted)
                lps.append([
                    self._lp_dict(req_id, i * S1 + p, tok,
                                  lp2[i, p], topk_np)
                    for p, tok in enumerate(emitted)
                ])
            # Next-step drafts AFTER every row committed its tokens —
            # one batched call for draft-model proposers.
            draft_map = self._propose_drafts_all(
                [r for r in sampling_req_ids if r not in pooled])
            spec_out.extend(draft_map.get(r, []) if r not in pooled
                            else [] for r in sampling_req_ids)
        else:
            # Record sampled tokens so next step's inputs include them.
            for i, req_id in enumerate(sampling_req_ids):
                if req_id in pooled:
                    req_ids.append(req_id)
                    sampled.append([])
                    lps.append([])
                    continue
                token = int(tokens_np[i])
                self.input_batch.append_token(req_id, token)
                req_ids.append(req_id)
                sampled.append([token])
                lps.append([self._lp_dict(req_id, i, token,
                                          logprobs_np[i], topk_np)])
        # Partial-prefill requests report no samples.
        sampling_set = set(sampling_req_ids)
        for req_id in scheduler_output.num_scheduled_tokens:
            if req_id not in sampling_set:
                req_ids.append(req_id)
                sampled.append([])
                lps.append([])
                if spec_out is not None:
                    spec_out.append([])
        out = ModelRunnerOutput(req_ids=req_ids,
                                sampled_token_ids=sampled,
                                logprobs=lps,
                                spec_token_ids=spec_out,
                                pooled=pooled or None,
                                prompt_logprobs=self._fetch_plp(handle))
        self._poll_kv_connector(scheduler_output, out)
        return out

    @staticmethod
    def _fetch_plp(handle) -> Optional[dict[str, list]]:
        """Assemble this step's prompt-logprob chunk: per scored prompt
        position, {actual_token: lp} plus the request's top-k."""
        meta = handle.get("plp_meta")
        if not meta:
            return None
        tgt, topv, topi = (np.asarray(jax.device_get(x))
                           for x in handle["dev"][4])
        chunks: dict[str, list] = {}
        for i, (req_id, entry, k, target) in enumerate(meta):
            d = {int(topi[i, j]): float(topv[i, j])
                 for j in range(min(k, topi.shape[1]))}
            # The actual prompt token's logprob is always present.
            d[int(target)] = float(tgt[i])
            chunks.setdefault(req_id, []).append((entry, d))
        return chunks

    def _detect_cascade(self, scheduler_output: SchedulerOutput):
        """Batch-wide shared-prefix detection for cascade attention
        (reference: use_cascade_attention, gpu_model_runner.py:1111):
        fires when EVERY scheduled request's first S page-table slots
        hold identical page ids (prefix-cache hits make them literally
        the same pages). Opt-in via VDT_CASCADE_ATTENTION."""
        from vllm_distributed_tpu import envs
        if self._cascade_layout_ok is None:
            # Cascade rides the standard K/V page layout (MLA's latent
            # cache has its own attention path); both backends (XLA scan
            # and the Pallas kernel via its emit_state merge) support it.
            self._cascade_layout_ok = "k" in self.model.kv_cache_specs()
        if (not envs.VDT_CASCADE_ATTENTION or self.tknp_size > 1
                or self.config.parallel_config.pipeline_parallel_size > 1
                or getattr(self.model.cfg, "sliding_window", None)
                or getattr(self.model.cfg, "alibi", False)
                or not self._cascade_layout_ok):
            return None
        S = envs.VDT_CASCADE_SHARED_PAGES
        rows = [self.input_batch.req_id_to_index[r]
                for r in scheduler_output.num_scheduled_tokens]
        if len(rows) < 2:
            return None
        ib = self.input_batch
        # Strictly more than S blocks: the suffix phase needs at least
        # one per-request page past the shared prefix.
        if any(ib.num_blocks[r] <= S for r in rows):
            return None
        first = ib.block_table[rows[0], :S]
        for r in rows[1:]:
            if not np.array_equal(ib.block_table[r, :S], first):
                return None
        self.cascade_steps += 1
        return self._h2d(first)

    def _poll_kv_connector(self, scheduler_output: SchedulerOutput,
                           out: ModelRunnerOutput) -> None:
        """Give the connector its per-step main-thread slot: service
        queued async work against the live ``kv_caches`` reference and
        collect (finished_sending, finished_recving) notifications
        (reference: gpu_model_runner.py get_finished_kv_transfers)."""
        if self.kv_connector is None:
            return
        meta = scheduler_output.kv_connector_metadata
        if meta is not None and scheduler_output.total_num_scheduled_tokens == 0:
            # The pre-forward start_load_kv site didn't run this step
            # (nothing scheduled); async pull kickoffs still must.
            self.kv_connector.start_load_kv(meta, self)
        sending, recving, failed = self.kv_connector.get_finished(self)
        if sending or recving or failed:
            out.finished_sending = sending
            out.finished_recving = recving
            out.failed_recving = failed

    def _launch_device_step(self, inputs: StepInputs, logits_indices,
                            sampling_packed, fwd_shape, ext_md, want_topk,
                            vocab_mask=None, plp=None, spec_q=None):
        """Enqueue one step's device work WITHOUT blocking: JAX dispatch
        is asynchronous, so the host returns as soon as the programs are
        queued. The pipeline-parallel engine core exploits this to keep
        several microbatches in flight (its batch queue blocks only on
        the oldest, reference core.py:242 step_with_batch_queue); the
        pipeline-parallel runner overrides only the forward half."""
        with self.mesh:
            cascade = inputs.rest.cascade_shared_ids is not None
            with self._compile_watch(("fwd", ) + fwd_shape + (cascade, )):
                self.kv_caches, hidden = self._forward_fn(
                    self.params, self.kv_caches, inputs)
            return self._launch_sample(hidden, logits_indices,
                                       sampling_packed, ext_md, want_topk,
                                       self.mesh, vocab_mask, plp=plp,
                                       spec_q=spec_q)

    def _launch_sample(self, hidden, logits_indices, sampling_packed,
                       ext_md, want_topk, mesh, vocab_mask=None, plp=None,
                       spec_q=None):
        """Row gather + (extended) sampling on ``mesh``, dispatch only;
        shared by the single-program and pipeline-parallel step paths.
        ``logits_indices`` and ``sampling_packed`` arrive as host arrays
        and are shipped here, behind the forward's launch: the device is
        already at work while they travel. Returns device arrays
        (tokens, logprobs, (topv, topi) | None); with ``spec_q`` the
        first slot instead carries the rejection verifier's output
        tuple."""
        with self.phases.span("vdt._launch_sample"):
            n_rows = logits_indices.shape[0]  # R or R*(S+1) with spec
            logits_indices = self._h2d(logits_indices)
            sampling_packed = self._h2d(sampling_packed)
            topk_dev = None
            plp_dev = None
            if plp is not None:
                rows, targets, _meta = plp
                sel = self._gather_sample_rows(hidden, rows, mesh=mesh)
                with self._compile_watch(("plp", rows.shape[0])):
                    plp_dev = self._plp_fn(self.params, sel, targets)
            hidden_sel = self._gather_sample_rows(hidden, logits_indices,
                                                  mesh=mesh)
            if self._numerics_fn is not None:
                # Dispatch-only like the sampler; the tap harvests the
                # PREVIOUS step's reduction, so this never blocks the step.
                # Strided (the reduction re-derives logits — an extra
                # lm-head pass — so tapping every step would be a ~2x
                # logits cost). Fused multi-step bursts bypass
                # _launch_sample and are not tapped (documented sentinel
                # limitation).
                self._numerics_countdown -= 1
                if self._numerics_countdown <= 0:
                    from vllm_distributed_tpu.correctness_plane import \
                        NUMERICS_TAP_STRIDE
                    self._numerics_countdown = NUMERICS_TAP_STRIDE
                    with self._compile_watch(("numerics", n_rows)):
                        self._numerics.dispatch(
                            self._numerics_fn(self.params, hidden_sel))
            if spec_q is not None:
                drafts_d, q_ids_d, q_probs_d, truncate = spec_q
                with self._compile_watch(("specv", n_rows, truncate)):
                    verify = self._spec_verify_fn(
                        self.params, hidden_sel, drafts_d, q_ids_d,
                        q_probs_d, sampling_packed, truncate=truncate)
                return verify, None, None, hidden_sel, plp_dev
            if ext_md is not None:
                with self._compile_watch(("sampleX", n_rows, want_topk,
                                          vocab_mask is not None)):
                    tokens, logprobs, topv, topi, last = \
                        self._sample_ext_fn(
                            self.params, hidden_sel, sampling_packed,
                            ext_md, want_topk, vocab_mask,
                            last=self._last_sampled())
                if want_topk:
                    topk_dev = (topv, topi)
            else:
                with self._compile_watch(("sample", n_rows)):
                    tokens, logprobs, last = self._sample_fn(
                        self.params, hidden_sel, sampling_packed,
                        last=self._last_sampled())
            # The pipelined step's mirror, with this step's tokens
            # recorded by the sampler itself (donated in, None off that
            # path): program order puts the next forward's read behind
            # this write.
            self._last_sampled_dev = last
            # hidden_sel rides along for pooling requests (fetched lazily).
            return tokens, logprobs, topk_dev, hidden_sel, plp_dev

    @staticmethod
    def _fetch_sample(dev):
        """Blocking half: device arrays -> host numpy."""
        tokens, logprobs, topk_dev, _hidden_sel, _plp_dev = dev
        topk_np = None
        if topk_dev is not None:
            topk_np = (np.asarray(jax.device_get(topk_dev[0])),
                       np.asarray(jax.device_get(topk_dev[1])))
        return (np.asarray(jax.device_get(tokens)),
                np.asarray(jax.device_get(logprobs)), topk_np)

    def _lp_dict(self, req_id: str, flat_row: int, token: int,
                 chosen_lp: float, topk_np) -> dict[int, float]:
        """Per-token logprob dict: the sampled token first (the output
        processor's cumulative-logprob reads the first value), then the
        request's `logprobs=k` top entries when requested."""
        d = {int(token): float(chosen_lp)}
        # Row may be gone when a trailing async batch retires after its
        # request finished; the scheduler drops the output anyway.
        row = self.input_batch.req_id_to_index.get(req_id)
        k = 0 if row is None else int(self.input_batch.num_logprobs[row])
        if topk_np is not None and k > 0:
            vals, ids = topk_np
            for v, t in zip(vals[flat_row, :k], ids[flat_row, :k]):
                d.setdefault(int(t), float(v))
        return d

    def _draft_eligible(self, req_id: str) -> Optional[np.ndarray]:
        """The request's committed token history, or None when it must
        not receive drafts. Extended-sampling rows get none: penalties
        change the target distribution position-by-position, so draft
        verification there would be biased."""
        ib = self.input_batch
        row = ib.req_id_to_index[req_id]
        if ib.extended_active(row):
            return None
        n = int(ib.num_tokens[row])
        if n >= self.max_model_len:
            return None
        return ib.token_ids[row, :n]

    def _propose_drafts_all(self,
                            req_ids: list[str]) -> dict[str, list[int]]:
        """Next-step drafts for every eligible request (reference:
        gpu_model_runner.py:1925 propose_draft_token_ids). Ngram runs
        per-request on the host; the draft model proposes the whole
        batch in one jitted call."""
        if self.proposer is None:
            return {}
        eligible: list[tuple[str, np.ndarray]] = []
        for req_id in req_ids:
            hist = self._draft_eligible(req_id)
            if hist is not None:
                eligible.append((req_id, hist))
        if not eligible:
            return {}
        if hasattr(self.proposer, "propose_batch"):
            ib = self.input_batch
            rows = [ib.req_id_to_index[rid] for rid, _ in eligible]
            # Stochastic proposals sample with each request's own
            # temperature; the support metadata feeds next step's
            # rejection verifier (seed stream distinct from the
            # verifier's so draft and accept randomness stay
            # independent for seeded requests).
            temps = ib.temperature[rows].astype(np.float32)
            user_seed = ib.seed[rows]
            seeds = np.where(
                user_seed >= 0,
                user_seed * 999983 + ib.num_tokens[rows],
                self._rng.integers(0, 2**31 - 1, size=len(rows)))
            drafts, meta = self.proposer.propose_batch(
                [h for _, h in eligible], temps, seeds)
            for (rid, _), m in zip(eligible, meta):
                self._draft_meta[rid] = m
            return {rid: d for (rid, _), d in zip(eligible, drafts)}
        return {rid: self.proposer.propose(h) for rid, h in eligible}

    def _propose_drafts_eagle(self, sampling_req_ids: list[str],
                              emitted_map: dict[str, list[int]],
                              handle: dict) -> dict[str, list[int]]:
        """EAGLE proposals for next step: one batched jit consuming the
        target hidden states already on device (handle's hidden_sel
        rows) — the draft KV advanced in-step during the forward, so
        proposing is k tiny decode steps over the eagle layers
        (reference: eagle.py propose per verified step)."""
        ib = self.input_batch
        S1 = self.spec_k + 1
        entries, rows_l = [], []
        for i, req_id in enumerate(sampling_req_ids):
            emitted = emitted_map.get(req_id)
            if not emitted or self._draft_eligible(req_id) is None:
                continue
            row = ib.req_id_to_index[req_id]
            flat = i * S1 + (len(emitted) - 1)
            pos_last = int(ib.num_tokens[row]) - 1
            entries.append((req_id, flat, emitted[-1], pos_last))
            rows_l.append(row)
        if not entries:
            return {}
        rows_a = np.asarray(rows_l)
        temps = ib.temperature[rows_a].astype(np.float32)
        user_seed = ib.seed[rows_a]
        seeds = np.where(
            user_seed >= 0,
            user_seed * 999983 + ib.num_tokens[rows_a],
            self._rng.integers(0, 2**31 - 1, size=len(rows_a)))
        hidden_sel = handle["dev"][3]
        with self.mesh:
            self.kv_caches, drafts, meta = self._eagle.propose_batch(
                self.kv_caches, entries, hidden_sel, temps, seeds,
                ib.block_table[rows_a], ib.num_blocks[rows_a])
        for (rid, *_), m in zip(entries, meta):
            self._draft_meta[rid] = m
        return {rid: d for (rid, *_), d in zip(entries, drafts)}

    # ------------------------------------------------------------------
    def _execute_multi_step(
            self, scheduler_output: SchedulerOutput) -> ModelRunnerOutput:
        """Run scheduler_output.multi_step fused decode steps (pure-decode
        batch; one host roundtrip for the whole burst)."""
        self._count_attn_dispatch(self._multi_step_label())
        ib = self.input_batch
        n_steps = scheduler_output.multi_step
        req_ids = list(scheduler_output.num_scheduled_tokens)
        num_active = len(req_ids)
        R = pad_to_bucket(num_active, self.req_buckets)
        rows = np.zeros((R, ), np.int32)
        rows[:num_active] = [ib.req_id_to_index[r] for r in req_ids]

        pos0 = ib.num_computed[rows].astype(np.int32)
        tok0 = ib.token_ids[rows, pos0].astype(np.int32)
        block_tables = ib.block_table[rows]

        user_seed = ib.seed[rows]
        step_in_req = ib.num_tokens[rows].astype(np.int64)
        seeds = np.empty((n_steps, R), np.int64)
        for t in range(n_steps):
            random_part = self._rng.integers(0, 2**31 - 1, size=R)
            seeds[t] = np.where(user_seed >= 0,
                                user_seed * 1000003 + step_in_req + t,
                                random_part)
        sampling_md = SamplingMetadata(
            temperature=jnp.asarray(ib.temperature[rows]),
            top_k=jnp.asarray(ib.top_k[rows]),
            top_p=jnp.asarray(ib.top_p[rows]),
            min_p=jnp.asarray(ib.min_p[rows]),
            seeds=jnp.asarray(seeds[0]),
        )

        deltas = np.zeros((R, ), np.int32)
        if self._mrope_on:
            for i, r in enumerate(rows):
                deltas[i] = self._mrope.get(int(r), (None, 0))[1]
        with self.mesh:
            with self._compile_watch(("multi", n_steps, R)):
                self.kv_caches, toks, lps = self._multi_step_fn(
                    self.params, self.kv_caches, jnp.asarray(tok0),
                    jnp.asarray(pos0), jnp.asarray(block_tables),
                    sampling_md, jnp.asarray(seeds),
                    jnp.asarray([num_active], np.int32),
                    jnp.asarray(deltas))

        toks_np = np.asarray(jax.device_get(toks))  # [n_steps, R]
        lps_np = np.asarray(jax.device_get(lps))

        out_req_ids, sampled, out_lps = [], [], []
        for i, req_id in enumerate(req_ids):
            tokens = [int(t) for t in toks_np[:, i]]
            for tok in tokens:
                self.input_batch.append_token(req_id, tok)
            out_req_ids.append(req_id)
            sampled.append(tokens)
            out_lps.append([{tok: float(lp)}
                            for tok, lp in zip(tokens, lps_np[:, i])])
        out = ModelRunnerOutput(req_ids=out_req_ids,
                                sampled_token_ids=sampled,
                                logprobs=out_lps)
        # Config normalization forces num_scheduler_steps=1 whenever a
        # KV connector is configured, so this is a no-op today — kept so
        # the invariant lives here, not in a distant config rule.
        self._poll_kv_connector(scheduler_output, out)
        return out

    # ------------------------------------------------------------------
    def _model_routes_xla(self) -> bool:
        """True when the model carries a feature the Pallas kernels do
        not: since sliding window / softcap / ALiBi / sinks folded into
        the mega-kernel's per-layer statics + head-feature sidecar, the
        only remaining model-level XLA forcer is an fp8 KV cache (the
        kernels' fp8 dequant is a follow-up)."""
        if getattr(self, "_xla_route_memo", None) is None:
            cfg = self.model.cfg if self.model is not None else None
            if cfg is None:
                return False  # don't memoize before the model exists
            self._xla_route_memo = bool(
                "fp8" in str(
                    self.config.cache_config.cache_dtype).lower())
        return self._xla_route_memo

    def _model_has_attn_features(self) -> bool:
        """Sliding window / softcap / ALiBi / sinks anywhere in the
        model: these reach the Pallas path only through the mega-kernel
        descriptor, so descriptor-less batches still fall back."""
        cfg = self.model.cfg if self.model is not None else None
        if cfg is None:
            return False
        return bool(
            getattr(cfg, "sliding_window", None)
            or getattr(cfg, "window_pattern", None)
            or getattr(cfg, "attn_logit_softcap", 0)
            or getattr(cfg, "alibi", False)
            or getattr(cfg, "attn_sinks", False))

    def _attn_kernel_label(self, inputs: StepInputs) -> str:
        """Which attention kernel family this step's batch dispatches to
        (mirrors the ops/attention.py routing, including the feature
        gates that force the XLA path): the vdt:attn_kernel_calls_total
        {kernel} observability for the dispatch layer. Five outcomes:
        naive, cascade, unified, decode, general."""
        if (resolve_attention_backend() != "pallas"
                or self._model_routes_xla()):
            return "naive"
        if inputs.rest.cascade_shared_ids is not None:
            return ("naive" if self._model_has_attn_features()
                    else "cascade")
        if inputs.layout.desc_programs:
            return "unified"
        if self._model_has_attn_features():
            return "naive"  # descriptor-less legacy path keeps XLA
        return "decode" if inputs.rest.max_q == 1 else "general"

    def _count_attn_dispatch(self, label: str) -> None:
        self.attn_kernel_calls[label] = (
            self.attn_kernel_calls.get(label, 0) + 1)

    def _multi_step_label(self) -> str:
        """Kernel family the fused multi-step burst dispatches: the
        in-jit batches carry no partition descriptor, so they ride the
        legacy SB decode kernel on the Pallas backend (and
        window/softcap/ALiBi/sink models the XLA path)."""
        return ("decode" if (resolve_attention_backend() == "pallas"
                             and not self._model_routes_xla()
                             and not self._model_has_attn_features())
                else "naive")

    # ------------------------------------------------------------------
    @contextmanager
    def _compile_watch(self, key: tuple):
        """Track/log compilations; after precompile() has run, any new
        shape is a recompile-guard violation (reference:
        tpu_model_runner.py:318 _update_num_xla_graphs /
        _verify_num_xla_graphs). A forward's key has five elements:
        ("fwd", T, max_q, G, cascade), the batch shape of
        ``_batch_shape`` and whether the shared-prefix ids ride along."""
        new = key not in self._compiled_shapes
        if new:
            if self._precompiled:
                from vllm_distributed_tpu import envs
                # Counted BEFORE the assert gate so vdt:recompiles_total
                # reflects the violation either way (the raise is a test
                # harness mode; production watches the counter).
                self.num_recompiles += 1
                msg = (f"compiling shape {key} AFTER precompile warm-up - "
                       "the shape lattice is leaking")
                if envs.VDT_ASSERT_NO_RECOMPILE:
                    raise RuntimeError(msg)
                logger.warning(msg)
            else:
                logger.info("compiling shape %s", key)
            start = time.perf_counter()
        yield
        if new:
            self._compiled_shapes.add(key)
            logger.info("compiled %s in %.1fs", key,
                        time.perf_counter() - start)

    def _gather_sample_rows(self, hidden, logits_indices, mesh=None):
        """[R]-row gather between the forward and sample jits, committed to
        a REPLICATED sharding: jax.jit keys its cache on input sharding, so
        the sampler must see the same sharding at warm-up and serving or
        every ('sample', R) shape would recompile on a >1-device mesh."""
        sel = hidden[logits_indices]
        return jax.device_put(sel, NamedSharding(mesh or self.mesh,
                                                 PartitionSpec()))

    def _dummy_step_inputs(self, T: int, max_q: int,
                           G: int) -> StepInputs:
        """Inert inputs for one forward at shape (T, max_q, G), in the
        packed signature a served step has: padding slots (-1) and zero
        run/seq counts make every write a no-op (an all-noop partition
        descriptor likewise runs zero programs)."""
        K = self.tknp_size
        layout = self._step_layout(T, G)
        P_desc = layout.desc_programs
        bq, sb = self._tile_params() if P_desc else (0, 0)
        tknp = None
        if K > 1:
            tknp = TknpAttentionBatch(
                slot_mapping=jnp.full((K, T), -1, jnp.int32),
                block_tables=jnp.zeros(
                    (K, self.max_num_reqs, self.max_pages_per_req),
                    jnp.int32),
                seq_info=jnp.zeros((K, self.max_num_reqs, 4), jnp.int32),
                num_seqs=jnp.zeros((K, 1), jnp.int32),
                kv_runs=jnp.zeros((K, G, 4), jnp.int32),
                num_kv_runs=jnp.zeros((K, 1), jnp.int32),
                desc=(jnp.zeros((K, P_desc, 3), jnp.int32)
                      if P_desc else None),
                decode_list=(jnp.zeros((K, self.max_num_reqs),
                                       jnp.int32)
                             if P_desc else None),
            )
        return StepInputs.of(
            jnp.asarray(layout.new_buffer()),
            layout,
            chain_last=self._last_sampled(),
            tknp=tknp,
            lora=self._dummy_lora_batch(T),
            mrope_positions=(jnp.zeros((T, 3), jnp.int32)
                             if self._mrope_on else None),
            max_q=max_q,
            attn_bq=bq,
            attn_sb=sb,
        )

    def _dummy_lora_batch(self, T: int):
        """Inert LoRA routing for warm-up (all tokens in slot 0): the
        compiled graph's pytree must match real steps' when LoRA is on."""
        if self.lora_manager is None:
            return None
        from vllm_distributed_tpu.models.common import LoraBatch
        S = self.config.lora_config.max_loras + 1
        gs = np.zeros((S, ), np.int32)
        gs[0] = T
        return LoraBatch(
            order=jnp.arange(T, dtype=jnp.int32),
            inv=jnp.arange(T, dtype=jnp.int32),
            group_sizes=jnp.asarray(gs),
            scaling=jnp.zeros((T, ), jnp.float32),
        )

    def forward_shapes(self) -> set[tuple[int, int, int]]:
        """Every (T, max_q, G) the runner can present. Unified
        (mega-kernel) models: composition is descriptor-carried, so
        decode shapes coincide with the small token buckets and the set
        collapses to one shape per token bucket — strictly fewer warmed
        graphs than the legacy decode+prefill split at the same bucket
        config. Legacy (MLA) models keep both variants."""
        shapes = set()
        for r in self.req_buckets:
            shapes.add(self._batch_shape(r, 1))
        for t in self.token_buckets:
            shapes.add(self._batch_shape(t, 2))
        return shapes

    def precompile(self) -> None:
        """Warm every step graph before serving (reference:
        tpu_model_runner.py:1248-1443 precompilation suite): all forward
        shapes, all sampler shapes, and the fused multi-step graph. After
        this, a compile during serving is a bug (_compile_watch)."""
        import dataclasses

        from vllm_distributed_tpu import envs
        assert self.kv_caches is not None, "initialize_kv_cache first"
        start = time.perf_counter()
        n = 0
        cascade = envs.VDT_CASCADE_ATTENTION
        with self.mesh:
            for T, max_q, G in sorted(self.forward_shapes()):
                inputs = self._dummy_step_inputs(T, max_q, G)
                with self._compile_watch(("fwd", T, max_q, G, False)):
                    self.kv_caches, hidden = self._forward_fn(
                        self.params, self.kv_caches, inputs)
                jax.block_until_ready(hidden)
                n += 1
                self._precompile_row_gathers(hidden, self.mesh)
                if cascade:
                    shared = jnp.zeros((envs.VDT_CASCADE_SHARED_PAGES, ),
                                       jnp.int32)
                    with self._compile_watch(("fwd", T, max_q, G, True)):
                        self.kv_caches, hidden = self._forward_fn(
                            self.params, self.kv_caches,
                            dataclasses.replace(
                                inputs, rest=dataclasses.replace(
                                    inputs.rest,
                                    cascade_shared_ids=shared)))
                    jax.block_until_ready(hidden)
                    n += 1
            n += self._precompile_samplers(self.mesh)
            n += self._precompile_plp(self.mesh)
            n += self._precompile_state_cache()
            # Burst graphs that can never dispatch are not warmed.
            n_steps = self._multi_step_steps()
            if n_steps > 1:
                for R in self.req_buckets:
                    self._precompile_multi_step(n_steps, R)
                    n += 1
            if self.proposer is not None and hasattr(
                    self.proposer, "precompile"):
                n += self.proposer.precompile()
            if self._eagle is not None:
                self.kv_caches, ne = self._eagle.precompile(
                    self.kv_caches, self.model.cfg.hidden_size,
                    self.model.cfg.dtype, self.max_pages_per_req)
                n += ne
        self._precompiled = True
        self.precompile_graphs = n
        logger.info("precompiled %d graphs in %.1fs", n,
                    time.perf_counter() - start)

    def _precompile_row_gathers(self, hidden, mesh) -> None:
        """The row gather between the forward and the samplers runs op
        by op, one small program a (token shape, row bucket) pair: warm
        every row bucket on a forward's own output, so that no pair
        waits for traffic that fills the largest row bucket beside every
        token bucket (``max_num_seqs`` 96 has a bucket of 96 that eight,
        sixteen, ... decoders never reach)."""
        S1 = self.spec_k + 1
        for R in self.req_buckets:
            sel = self._gather_sample_rows(
                hidden, jnp.asarray(np.zeros((R * S1, ), np.int32)),
                mesh=mesh)
        jax.block_until_ready(sel)

    def _precompile_state_cache(self) -> int:
        """Warm the SSM snapshot/restore copies (one graph per state
        array per direction) so a serving-time checkpoint is never a
        recompile-guard violation. Copies between slot 0 and row 0 of
        the zero-initialized arrays are inert."""
        if self._state_pool is None:
            return 0
        n = 0
        shapes = self.model.state_shapes()
        for name in self._state_keys:
            with self._compile_watch(("ssm_save", name)):
                self._state_pool[name] = self._state_row_to_pool(
                    self._state_pool[name], self.kv_caches[name], 0, 0)
            with self._compile_watch(("ssm_restore", name)):
                self.kv_caches[name] = self._state_pool_to_row(
                    self.kv_caches[name], self._state_pool[name], 0, 0)
            shape, dtype = shapes[name]
            value = jnp.asarray(
                np.zeros((shape[0], ) + shape[2:], jnp.dtype(dtype)))
            with self._compile_watch(("ssm_put", name)):
                self.kv_caches[name] = self._state_put_row(
                    self.kv_caches[name], value, 0)
            jax.block_until_ready(self.kv_caches[name])
            n += 3
        return n

    def _precompile_plp(self, mesh) -> int:
        """Warm the prompt-logprob graphs — one per P bucket (the row
        gather runs outside the jit, so the lattice is additive with
        the forward shapes). Disagg decode-pool replicas skip the
        family: prompt_logprobs requests are exempt from handoff and
        serve monolithically on the prefill pool (a pool_down degraded
        placement compiles lazily with a recompile warning)."""
        if self.pool_role == "decode":
            return 0
        n = 0
        for P_ in self.token_buckets:
            with self._compile_watch(("plp", P_)):
                tgt, _, _ = self._plp_fn(self.params,
                                         *self._plp_dummy_args(P_, mesh))
            jax.block_until_ready(tgt)
            n += 1
        return n

    def _plp_dummy_args(self, P_: int, mesh) -> tuple:
        sel = self._gather_sample_rows(
            jnp.zeros((P_, self.model.cfg.hidden_size),
                      self.model.cfg.dtype),
            jnp.arange(P_, dtype=jnp.int32), mesh=mesh)
        return sel, jnp.zeros((P_, ), jnp.int32)

    def _precompile_samplers(self, mesh) -> int:
        """Warm the plain + extended sampler graphs for every row bucket
        on ``mesh`` (the last stage's sub-mesh under PP). Returns the
        number of graphs compiled."""
        n = 0
        S1 = self.spec_k + 1
        for R in self.req_buckets:
            rows = R * S1  # sampler sees S+1 rows/request with spec
            hidden_sel, md, ext, mask = self._sampler_dummy_args(rows, mesh)
            with self._compile_watch(("sample", rows)):
                tokens, _, self._last_sampled_dev = self._sample_fn(
                    self.params, hidden_sel, md,
                    last=self._last_sampled())
            jax.block_until_ready(tokens)
            n += 1
            if self._numerics_fn is not None:
                # Warm the sentinel reduction on the sampler's own row
                # lattice (discarded — warm-up must not pollute the
                # tap's histograms/window).
                with self._compile_watch(("numerics", rows)):
                    nm = self._numerics_fn(self.params, hidden_sel)
                jax.block_until_ready(nm)
                n += 1
            if self.spec_k:
                with self._compile_watch(("specv", rows)):
                    verify = self._spec_verify_fn(
                        self.params, hidden_sel,
                        *self._spec_verify_dummy_args(R), md)
                jax.block_until_ready(verify[0])
                n += 1
            for want_topk in (False, True):
                for vocab_mask in (None, mask):
                    with self._compile_watch(("sampleX", rows, want_topk,
                                              vocab_mask is not None)):
                        tokens, _, _, _, self._last_sampled_dev = \
                            self._sample_ext_fn(
                                self.params, hidden_sel, md, ext,
                                want_topk, vocab_mask,
                                last=self._last_sampled())
                    jax.block_until_ready(tokens)
                    n += 1
        return n

    def _spec_verify_dummy_args(self, R: int) -> tuple:
        """(drafts, q_ids, q_probs) of the verification graph."""
        from vllm_distributed_tpu.spec_decode.draft_model import SUPPORT_K
        return (jnp.full((R, self.spec_k), -1, jnp.int32),
                jnp.zeros((R, self.spec_k, SUPPORT_K), jnp.int32),
                jnp.zeros((R, self.spec_k, SUPPORT_K), jnp.float32))

    def _sampler_dummy_args(self, rows: int, mesh) -> tuple:
        """(hidden_sel, packed sampling md, extended md, vocab mask) for
        one sampler row bucket. On the pipelined path the buffer has its
        record row, every entry out of range."""
        zeros = np.zeros((rows, ), np.float32)
        md = jnp.asarray(pack_sampling(
            zeros, zeros, zeros + 1, zeros, zeros,
            zeros + self.max_num_reqs if self._async_chain else None))
        hidden_sel = self._gather_sample_rows(
            jnp.zeros((rows, self.model.cfg.hidden_size),
                      self.model.cfg.dtype),
            jnp.arange(rows, dtype=jnp.int32), mesh=mesh)
        ext = ExtendedSamplingMetadata(
            hist_tokens=jnp.zeros((rows, self.max_model_len),
                                  jnp.int32),
            prompt_len=jnp.zeros((rows, ), jnp.int32),
            total_len=jnp.zeros((rows, ), jnp.int32),
            presence_penalty=jnp.zeros((rows, ), jnp.float32),
            frequency_penalty=jnp.zeros((rows, ), jnp.float32),
            repetition_penalty=jnp.ones((rows, ), jnp.float32),
            bias_ids=jnp.zeros((rows, self._BIAS_BUF), jnp.int32),
            bias_vals=jnp.zeros((rows, self._BIAS_BUF), jnp.float32),
            base_fill=jnp.zeros((rows, ), jnp.float32),
        )
        mask = jnp.ones((rows, self.model.cfg.vocab_size), jnp.bool_)
        return hidden_sel, md, ext, mask

    def _multi_step_steps(self) -> int:
        """Steps of the fused decode burst this engine can dispatch (1 =
        never). The scheduler forces multi-step to 1 for stateful models
        with the state cache on (fused bursts would cross snapshot
        boundaries mid-burst)."""
        if self._state_cache_active():
            return 1
        return self.config.scheduler_config.num_scheduler_steps

    def _multi_step_dummy_args(self, n_steps: int, R: int) -> tuple:
        """The burst graph's arguments after (params, kv_caches)."""
        md = SamplingMetadata(
            temperature=jnp.zeros((R, ), jnp.float32),
            top_k=jnp.zeros((R, ), jnp.int32),
            top_p=jnp.ones((R, ), jnp.float32),
            min_p=jnp.zeros((R, ), jnp.float32),
            seeds=jnp.zeros((R, ), jnp.int64),
        )
        return (jnp.zeros((R, ), jnp.int32), jnp.zeros((R, ), jnp.int32),
                jnp.zeros((R, self.max_pages_per_req), jnp.int32), md,
                jnp.zeros((n_steps, R), jnp.int64),
                jnp.zeros((1, ), jnp.int32), jnp.zeros((R, ), jnp.int32))

    def _precompile_multi_step(self, n_steps: int, R: int) -> None:
        with self._compile_watch(("multi", n_steps, R)):
            self.kv_caches, toks, _ = self._multi_step_fn(
                self.params, self.kv_caches,
                *self._multi_step_dummy_args(n_steps, R))
        jax.block_until_ready(toks)

    def get_stats(self) -> dict[str, float]:
        """Runner-side stats (spec-decode acceptance; reference:
        v1/metrics/stats.py SpecDecodingStats) plus the K/V tokens read
        per step and the device/compilation telemetry (recompiles,
        device wait, HBM high-water mark). The runner's step phases are
        reported by the engine core, which shares ``self.phases``."""
        stats: dict = {
            "step_kv_tokens_read": self.kv_tokens_read_hist.to_dict(),
            "step_latent_prefill_tokens":
            self.latent_prefill_tokens_hist.to_dict(),
            "step_ssd_chunk_tokens": self.ssd_chunk_tokens_hist.to_dict(),
            "step_retention_chunk_tokens":
            self.retention_chunk_tokens_hist.to_dict(),
            "step_host_transfers": self.host_transfers_hist.to_dict(),
            "num_recompiles": self.num_recompiles,
            # Kernel-dispatch + lattice observability (vdt:attn_kernel_
            # calls_total{kernel} / vdt:precompile_graphs_total).
            "attn_kernel_calls": dict(self.attn_kernel_calls),
            "precompile_graphs": self.precompile_graphs,
        }
        if self._numerics is not None:
            # Correctness-sentinel numerics (per replica; the DP merge
            # keys this by replica index, never numeric-summed).
            stats["numerics"] = self._numerics.stats()
        if self.model is not None and getattr(self.model.cfg, "mla",
                                              False):
            # MLA latent-pool geometry (vdt:tpla_latent_shards /
            # vdt:mla_latent_page_bytes{worker}): shards > 1 proves the
            # TPLA layout is live; page bytes is the PER-RANK cost one
            # latent page charges against this worker's HBM — together
            # with vdt:kv_blocks they quantify the ~TP x capacity win.
            stats["tpla_latent_shards"] = int(
                getattr(self.model.cfg, "tpla_shards", 1) or 1)
            stats["mla_latent_page_bytes"] = int(
                self.model.kv_cache_page_bytes(self.page_size))
            # One token's latent row over every layer (7 680 B where an
            # OLMo-2 token costs 128 KiB): what vdt:step_kv_tokens_read
            # is multiplied by for this family.
            stats["mla_latent_token_bytes"] = (
                stats["mla_latent_page_bytes"] // self.page_size)
        held = (getattr(self.model.cfg, "experts_held", None)
                if self.model is not None else None)
        if held is not None:
            # One chip's share of an expert layer (models/mixtral.py
            # moe_dispatch): experts whose weights are here, of the
            # experts the router scores.
            stats["moe_experts_held"] = int(held[1])
            stats["moe_experts_published"] = int(
                self.model.cfg.router_experts)
        if self._use_unified():
            stats["step_decode_kv_tokens_walked"] = \
                self.decode_kv_walked_hist.to_dict()
        if self._window_group:
            stats["step_kv_window_tokens_read"] = \
                self.kv_window_tokens_read_hist.to_dict()
            stats["step_attn_pairs"] = {
                kind: h.to_dict()
                for kind, h in self.attn_pairs_hist.items()}
            stats["step_requests_past_window"] = \
                self.requests_past_window_hist.to_dict()
        row_bytes = getattr(self.model, "state_row_bytes", None)
        if row_bytes is not None:
            # One sequence's recurrent and convolution state over every
            # layer: what a request costs whatever its length
            # (models/bailing.py, models/mamba.py).
            stats["state_row_bytes"] = int(row_bytes())
        if self._device_telemetry:
            from vllm_distributed_tpu.metrics import telemetry
            stats["device_wait_seconds"] = self.device_wait_hist.to_dict()
            stats.update(telemetry.device_memory_stats(self.mesh))
        if self.spec_k:
            stats.update({
                "spec_num_drafts": self.spec_num_drafts,
                "spec_num_draft_tokens": self.spec_num_draft_tokens,
                "spec_num_accepted_tokens": self.spec_num_accepted_tokens,
                "spec_acceptance_rate":
                (self.spec_num_accepted_tokens /
                 max(self.spec_num_draft_tokens, 1)),
            })
        return stats

    def profile_memory_bytes(self) -> Optional[int]:
        """Bytes of HBM available for KV pages: the device's limit times
        ``gpu_memory_utilization``, less what is in use once the
        largest-shape forward has run against a small scratch cache
        (the weights — measured), less the largest workspace any one
        serving program needs while it runs (asked of the compiler).
        Mirrors the reference's profile run (gpu_worker.py:200,
        tpu_worker.py:163). Returns None when the platform exposes no
        memory stats (the CPU backend); on a device that reports
        ``bytes_limit`` a failing profile run raises.

        Deliberately not ``peak_bytes_in_use``: that is a peak over the
        process's life, so it charges a second engine in one process
        (sleep/wake, tests, a restart supervisor) for the first one's
        pool, and it never includes program scratch anyway."""
        # This process's devices: memory stats exist only for those.
        devices = self.mesh.local_devices
        limit = int((devices[0].memory_stats() or {}).get("bytes_limit", 0))
        if not limit:
            return None
        util = self.config.cache_config.gpu_memory_utilization
        forwards = self._profile_run()
        in_use = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
                     for d in devices)
        workspace = self._program_workspace_bytes(forwards)
        logger.info("profiled HBM: %.2f GiB in use (weights) + %.2f GiB "
                    "program workspace", in_use / 2**30,
                    workspace / 2**30)
        return int(limit * util) - in_use - workspace

    def _profile_run(self) -> list:
        """Execute the largest forward shape with a 16-page scratch
        cache — the largest program must load and run before the pool is
        carved out of what it leaves — and return, lowered, every
        serving program that takes the caches (that forward, and the
        largest fused decode burst where one can dispatch), for the
        compiler's account of their workspace."""
        assert self.model is not None
        scratch = self._make_sharded_caches(16)
        if self._forward_fn is None:
            self._build_step_fn()
        T, max_q, G = max(self.forward_shapes())
        inputs = self._dummy_step_inputs(T, max_q, G)
        with self.mesh:
            lowered = [self._forward_fn.lower(self.params, scratch,
                                              inputs)]
            n_steps = self._multi_step_steps()
            if n_steps > 1:
                lowered.append(self._multi_step_fn.lower(
                    self.params, scratch, *self._multi_step_dummy_args(
                        n_steps, self.req_buckets[-1])))
            # The caches are donated: only the returned ones are live.
            scratch, hidden = self._forward_fn(self.params, scratch,
                                               inputs)
            jax.block_until_ready(hidden)
        return lowered

    def _program_workspace_bytes(self, programs: list) -> int:
        """Largest workspace any one of the serving programs — the
        lowered cache-taking ``programs`` plus ``_head_programs`` —
        needs while it runs: its temp buffers plus the outputs it does
        not alias onto donated inputs. The TPU runtime takes a program's
        temp buffers from the bottom of HBM when the program loads, and
        ``memory_stats()`` never counts them — so a pool sized from
        measured usage alone grows into them. The compiler knows the
        size; ask it for the largest member of each family. Programs
        run one at a time, so the maximum is what must stay free."""

        def need(program) -> int:
            ma = program.compile().memory_analysis()
            return int(ma.temp_size_in_bytes + ma.output_size_in_bytes
                       - ma.alias_size_in_bytes)

        return max(need(p) for p in programs + self._head_programs())

    def _head_programs(self) -> list:
        """The largest sampler, verification and prompt-logprob graphs,
        lowered (the prompt-logprob graph at the largest token bucket
        holds [T, vocab] f32 logits: ~2 GB at a 128k vocabulary)."""
        from vllm_distributed_tpu.parallel.mesh import global_mesh
        mesh = self._head_mesh()
        with global_mesh(mesh), mesh:
            R = self.req_buckets[-1]
            hidden_sel, md, ext, mask = self._sampler_dummy_args(
                R * (self.spec_k + 1), mesh)
            programs = [self._sample_ext_fn.lower(
                self.params, hidden_sel, md, ext, True, mask,
                last=self._last_sampled())]
            if self.spec_k:
                programs.append(self._spec_verify_fn.lower(
                    self.params, hidden_sel,
                    *self._spec_verify_dummy_args(R), md))
            if self.pool_role != "decode":
                programs.append(self._plp_fn.lower(
                    self.params,
                    *self._plp_dummy_args(self.token_buckets[-1], mesh)))
        return programs

    def _head_mesh(self):
        """The mesh the sampler / prompt-logprob graphs run on."""
        return self.mesh
