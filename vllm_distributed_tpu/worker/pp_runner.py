"""Pipeline-parallel model runner: per-stage sub-meshes, staged jits,
activation handoff via device_put.

TPU-native PP (vs the reference's one-process-per-rank design sending
IntermediateTensors over NCCL, vllm/v1/worker/gpu_model_runner.py +
parallel_state.py:629 send_tensor_dict): the ``pipe`` axis of the global
mesh is sliced into P sub-meshes; stage p holds its contiguous layer
slice's weights and KV cache on its sub-mesh and runs ONE jitted
program (models/llama.py run_layers). Activations hop stages with
``jax.device_put`` — an ICI/DCN device-to-device copy the runtime
overlaps with compute via async dispatch, so consecutive engine steps
pipeline across stages without an explicit microbatch queue (the engine
core's batch-queue overlap, reference core.py:242, adds depth on top).

Tensor parallelism composes: each sub-mesh keeps the (token, model) axes,
so GSPMD TP and the shard_map'd Pallas kernels work per stage unchanged.
"""

import time
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.parallel.mesh import global_mesh
from vllm_distributed_tpu.parallel.pipeline import (partition_layers,
                                                    stage_submesh)
from vllm_distributed_tpu.worker.model_runner import TPUModelRunner

logger = init_logger(__name__)


class PPModelRunner(TPUModelRunner):

    def __init__(self, config: EngineConfig, mesh,
                 model=None, params=None) -> None:
        super().__init__(config, mesh, model, params)
        self.pp = config.parallel_config.pipeline_parallel_size
        assert self.pp > 1
        self.stage_meshes = [stage_submesh(mesh, p) for p in range(self.pp)]
        self.layer_ranges: Optional[list[tuple[int, int]]] = None
        self.stage_params: list[dict] = []
        self.embed_params: Optional[dict] = None

    # ------------------------------------------------------------------
    def load_model(self) -> None:
        from vllm_distributed_tpu.models.loader import get_model
        self.model, host_params = get_model(self.config, self.mesh,
                                            shard=False)
        L = self.model.cfg.num_layers
        if self.pp > L:
            raise ValueError(
                f"pipeline_parallel_size={self.pp} exceeds the model's "
                f"{L} layers")
        self.layer_ranges = partition_layers(L, self.pp)
        logger.info("pipeline stages (layer ranges): %s", self.layer_ranges)
        specs = self.model.param_specs()
        self.stage_params = []
        for p, (s, e) in enumerate(self.layer_ranges):
            sm = self.stage_meshes[p]
            sliced = self.model.slice_layer_params(
                host_params["layers"], s, e)
            self.stage_params.append(self.model.serving_layout({
                k: jax.device_put(v, NamedSharding(sm,
                                                   specs["layers"][k]))
                for k, v in sliced.items()
            }))
        sm0, sml = self.stage_meshes[0], self.stage_meshes[-1]
        self.embed_params = {
            "embed": jax.device_put(host_params["embed"],
                                    NamedSharding(sm0, specs["embed"])),
        }
        for extra in ("embed_pos", "embed_ln_w", "embed_ln_b"):
            # Learned-position tables / embedding norms ride stage 0.
            if extra in host_params:
                self.embed_params[extra] = jax.device_put(
                    host_params[extra], NamedSharding(sm0, specs[extra]))
        self._init_lora_manager()
        # The sampler's params (final norm + LM head) live with the last
        # stage; the base class passes self.params to the sample fns.
        self.params = {
            "final_ln": jax.device_put(
                host_params["final_ln"],
                NamedSharding(sml, specs["final_ln"])),
            "lm_head": jax.device_put(
                host_params["lm_head"],
                NamedSharding(sml, specs["lm_head"])),
        }
        for extra in ("final_ln_b", "lm_head_b"):
            if extra in host_params:
                self.params[extra] = jax.device_put(
                    host_params[extra], NamedSharding(sml, specs[extra]))

    def lora_buffer_trees(self):
        return [(self.stage_params[p], rng)
                for p, rng in enumerate(self.layer_ranges)]

    # ------------------------------------------------------------------
    def _stage_caches(self, num_pages: int) -> list[dict]:
        return [
            self._make_sharded_caches(num_pages, self.stage_meshes[p], e - s)
            for p, (s, e) in enumerate(self.layer_ranges)]

    def initialize_kv_cache(self, num_pages: int) -> None:
        assert self.model is not None
        self.num_pages = num_pages
        # List of per-stage {"k","v"} slices instead of one stacked cache.
        self.kv_caches = self._stage_caches(num_pages)
        if self._forward_fn is None:
            self._build_step_fn()

    def _build_step_fn(self) -> None:
        model = self.model

        # Embed and every stage take the packed step inputs that
        # _prepare_inputs returns and unpack them first, like the
        # single-program forward: one buffer hops to each stage's
        # sub-mesh instead of a dozen arrays.
        def embed(params, packed, layout):
            f = layout.views(packed)
            h = model.embed(params, f["token_ids"], f["positions"])
            # Replicate INSIDE the jit (GSPMD all-gather over the stage
            # mesh, where collectives are legal) so the inter-stage hop
            # only moves locally-complete values — multi-controller
            # device_put cannot gather across hosts (see _hop).
            return jax.lax.with_sharding_constraint(h, PartitionSpec())

        def stage(layer_params, kv_caches, hidden, inputs, first_layer=0):
            _, batch = inputs.unpack()
            hidden, kv_caches = model.run_layers(layer_params, kv_caches,
                                                 hidden, batch,
                                                 first_layer=first_layer)
            hidden = jax.lax.with_sharding_constraint(
                hidden, PartitionSpec())
            return kv_caches, hidden

        self._embed_fn = jax.jit(embed, static_argnames=("layout", ))
        self._stage_fn = jax.jit(stage, donate_argnums=(1, ),
                                 static_argnames=("first_layer", ))
        # Base sampler jits (compute_logits + sampling) work unchanged —
        # they only touch self.params (final_ln/lm_head on the last
        # stage's sub-mesh).
        super()._build_step_fn()
        self._forward_fn = self._not_supported  # stage loop replaces it
        self._multi_step_fn = self._not_supported

    @staticmethod
    def _not_supported(*_a, **_k):  # pragma: no cover - guard
        raise RuntimeError("single-program forward is not used under PP")

    # ------------------------------------------------------------------
    def _stage_first_layer(self, p: int) -> int:
        """Global layer offset of stage p — nonzero only for mixed
        window layouts, so uniform models keep sharing one compiled
        stage program across equal-shape stages."""
        return (self.layer_ranges[p][0]
                if self.model.cfg.window_pattern else 0)

    def _hop(self, hidden, sm):
        """Activation handoff onto stage ``sm`` (reference analogue:
        IntermediateTensors send/recv). Single-controller: one async
        device_put over ICI. Multi-controller: the stage jits emit the
        activation REPLICATED (each process holds the full value on its
        own stage devices), so the hop rebuilds the array from the
        local shard — no cross-host device_put, which multi-controller
        JAX restricts to identical device sets."""
        target = NamedSharding(sm, PartitionSpec())
        if jax.process_count() == 1:
            return jax.device_put(hidden, target)
        if hidden.sharding.device_set == target.device_set:
            return jax.device_put(hidden, target)
        import numpy as np
        local = np.asarray(hidden.addressable_shards[0].data)
        return jax.make_array_from_callback(
            hidden.shape, target, lambda idx: local[idx])

    def _launch_device_step(self, inputs, logits_indices,
                            sampling_packed, fwd_shape, ext_md, want_topk,
                            vocab_mask=None, plp=None, spec_q=None):
        sm0 = self.stage_meshes[0]
        with global_mesh(sm0), sm0:
            with self._compile_watch(("embed", fwd_shape[0])):
                hidden = self._embed_fn(self.embed_params, inputs.packed,
                                        layout=inputs.layout)
        for p in range(self.pp):
            sm = self.stage_meshes[p]
            # Activation handoff: ICI/DCN copy to the next stage's
            # sub-mesh. Dispatch is async end-to-end on one controller:
            # nothing here blocks the host, so when the engine core
            # keeps multiple batches in flight, stage p of batch i+1
            # runs under stage p+1 of batch i (each stage's KV cache
            # chains only to ITS OWN previous-batch output).
            hidden = self._hop(hidden, sm)
            with global_mesh(sm), sm:
                with self._compile_watch(("stage", p) + fwd_shape):
                    self.kv_caches[p], hidden = self._stage_fn(
                        self.stage_params[p], self.kv_caches[p], hidden,
                        inputs, first_layer=self._stage_first_layer(p))
        sml = self._head_mesh()
        with global_mesh(sml), sml:
            return self._launch_sample(hidden, logits_indices,
                                       sampling_packed, ext_md, want_topk,
                                       sml, vocab_mask, plp=plp,
                                       spec_q=spec_q)

    # ------------------------------------------------------------------
    def precompile(self) -> None:
        """Warm embed + every stage + samplers over the shape lattice
        (reference: tpu_model_runner.py:1248; PP warms per-stage graphs)."""
        assert self.kv_caches is not None, "initialize_kv_cache first"
        import time
        start = time.perf_counter()
        n = 0
        for T, max_q, G in sorted(self.forward_shapes()):
            inputs = self._dummy_step_inputs(T, max_q, G)
            sm0 = self.stage_meshes[0]
            with global_mesh(sm0), sm0:
                with self._compile_watch(("embed", T)):
                    hidden = self._embed_fn(self.embed_params,
                                            inputs.packed,
                                            layout=inputs.layout)
            n += 1
            for p in range(self.pp):
                sm = self.stage_meshes[p]
                hidden = self._hop(hidden, sm)
                with global_mesh(sm), sm:
                    with self._compile_watch(("stage", p, T, max_q, G)):
                        self.kv_caches[p], hidden = self._stage_fn(
                            self.stage_params[p], self.kv_caches[p],
                            hidden, inputs,
                            first_layer=self._stage_first_layer(p))
                n += 1
            jax.block_until_ready(hidden)
        sml = self._head_mesh()
        with global_mesh(sml), sml:
            self._precompile_samplers(sml)
            self._precompile_plp(sml)
        self._precompiled = True
        self.precompile_graphs = n
        logger.info("PP precompile done in %.1fs",
                    time.perf_counter() - start)

    # ------------------------------------------------------------------
    def kv_cache_bytes_per_page(self) -> int:
        # Per-DEVICE bytes, sized by the LARGEST stage's layer count (an
        # uneven split gives the early stages the remainder layers).
        from vllm_distributed_tpu.utils import cdiv
        full = super().kv_cache_bytes_per_page()
        L = self.model.cfg.num_layers
        if self.layer_ranges is not None:
            max_layers = max(e - s for s, e in self.layer_ranges)
        else:
            max_layers = cdiv(L, self.pp)
        return max(full * max_layers // L, 1)

    def _profile_run(self) -> list:
        """Largest-shape pipeline pass against per-stage scratch caches;
        returns every stage's program lowered. (The base class's
        limit/util logic wraps this and charges the largest program's
        compiler-reported workspace against every device.)"""
        scratch = self._stage_caches(16)
        if self._forward_fn is None:
            self._build_step_fn()
        T, max_q, G = max(self.forward_shapes())
        inputs = self._dummy_step_inputs(T, max_q, G)
        sm0 = self.stage_meshes[0]
        with global_mesh(sm0), sm0:
            hidden = self._embed_fn(self.embed_params, inputs.packed,
                                    layout=inputs.layout)
        lowered = []
        for p in range(self.pp):
            sm = self.stage_meshes[p]
            hidden = self._hop(hidden, sm)
            with global_mesh(sm), sm:
                args = (self.stage_params[p], scratch[p], hidden, inputs)
                first = self._stage_first_layer(p)
                lowered.append(self._stage_fn.lower(*args,
                                                    first_layer=first))
                scratch[p], hidden = self._stage_fn(*args,
                                                    first_layer=first)
        jax.block_until_ready(hidden)
        return lowered

    def _head_mesh(self):
        return self.stage_meshes[-1]
