"""Mixtral-family sparse-MoE decoder (covers HF ``MixtralForCausalLM``).

TPU-first equivalent of the reference's vllm/model_executor/models/
mixtral.py + layers/fused_moe/layer.py:593 ``FusedMoE`` (CUDA grouped-GEMM
kernels with all-to-all dispatch over the EP group): here every expert's
FFN weights are STACKED on a leading expert axis and the whole MoE block
is three einsums — router top-k gates, batched expert FFNs, weighted
combine. Under expert parallelism the expert axis is sharded over the
``model`` mesh axis (EP spans the TP group, reference
parallel_state.py:1189-1204); GSPMD turns the combine contraction into
the psum that replaces the reference's all-to-all combine. Every selected
token is computed exactly (no capacity-factor drops), matching HF
numerics for parity tests.

The attention/embedding/norm stack is inherited from the Llama decoder
(Mixtral is architecturally Llama + MoE MLP).

**Where the expert weights live.** The loader's tree holds them
layer-stacked (``[L, E, H, I]``, the canonical layout every mapper,
``quantize_params``, EPLB and the pipeline slicer keep). The serving
programs take ``serving_layout`` of it: ``w_gate`` / ``w_up`` /
``w_down`` (and what rides on them) as tuples of L per-layer buffers, so
that every ``jax.lax.ragged_dot`` reads a buffer that is an argument of
the jitted program. ``ragged_dot`` is a custom call that wants a whole
operand: a slice of a stack, a scan's dynamic one or an unrolled loop's
static one, is materialised for it as a copy of the slab. On a v5e that
copy was the largest device operation of both OLMoE cells: 6.4 GB read
and written a step, 19.6 ms of a 35.5 ms step, more than the GEMMs
themselves (PERF.md, Findings PR 28 "Experts read in place" and PR 29).
Everything else in the tree stays stacked and scanned;
``LlamaForCausalLM.run_layers`` keeps the tuples out of its scan and
reaches ``mlp_block`` through a switch with one branch a layer, each
holding its own layer's buffers (a Python loop over the layers reads
in place as well, but traces the whole layer body once a layer: on the
chip the 36 serving programs of OLMoE then took 71 s to come up from a
warm compile cache where they take 29 s, PERF.md Findings PR 29).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.llama import (MODEL_AXIS,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.parallel.mesh import shard_map
from vllm_distributed_tpu.utils import cdiv, round_up

# A pass of an expert share's dispatch: its rows come in tiles of this
# many, and a step of at most so many picks is one pass over all of them.
_ROW_TILE = 128
_WHOLE_PICKS = 1024


# XLA's ``ragged_dot`` on the TPU works in row tiles of the largest power
# of two that divides its rows, this many at most, and a (tile, group)
# pair that shares a row is one visit: tiles + groups - 1 of them at
# worst. A visit loads the group's matrix whatever the tile holds of the
# group, which on a v5e costs what the product of about 330 rows costs
# (scripts/ragged_rows.py; PERF.md section 5 has the table).
_MAX_ROW_TILE = 4 * _ROW_TILE
_VISIT_ROWS = 330


def _ragged_dot_cost(rows: int, groups: int) -> int:
    tile = min(rows & -rows, _MAX_ROW_TILE)
    return (rows // tile + groups - 1) * (_VISIT_ROWS + tile)


def padded_picks(picks: int, groups: int) -> int:
    """Rows the one-chip whole-expert dispatch hands ``ragged_dot`` for
    ``picks`` sorted picks over ``groups`` experts: ``picks`` as they
    are or, where ``_ragged_dot_cost`` is lower there, the next odd
    multiple of 128 or of 256 (odd, so that the tile is that and no
    larger) or the next multiple of 512. 2 056 tokens of 6 picks over
    64 experts are 12 336 rows in tiles of 16, 12 544 are 49 tiles of
    256 and three and a half times faster; 32 tokens' 240 rows stay
    240. A function of the program's shapes alone."""
    return min([picks,
                (cdiv(picks, _ROW_TILE) | 1) * _ROW_TILE,
                (cdiv(picks, 2 * _ROW_TILE) | 1) * 2 * _ROW_TILE,
                round_up(picks, _MAX_ROW_TILE)],
               key=lambda rows: _ragged_dot_cost(rows, groups))


def read_expert_share(arch, hf, key: str) -> None:
    """``expert_share`` of a configuration file, read for every family
    that serves one chip's share of an expert layer (models/deepseek.py,
    models/cohere_moe.py, models/granite_hybrid.py): sets
    ``arch.router_experts`` (the router's width: the published count
    under a share, else the experts there are) and ``arch.experts_held``
    (``(first, count)`` of the experts whose weights are here, ``None``
    without a share: ``moe_dispatch`` asks it). ``key`` is the family's
    name for the count of experts held, for the refusal's text."""
    arch.router_experts = arch.num_experts
    arch.experts_held = None
    share = getattr(hf, "expert_share", None)
    if not share:
        return
    published, chips, index = (int(share[k]) for k in (
        "published_experts", "chips", "index"))
    if published != arch.num_experts * chips or not 0 <= index < chips:
        raise ValueError(
            f"expert_share {dict(share)} does not describe "
            f"{key}={arch.num_experts} held experts: published_experts "
            f"must be {key} x chips and index one of the chips")
    arch.router_experts = published
    arch.experts_held = (index * arch.num_experts, arch.num_experts)


@functools.lru_cache(maxsize=None)
def _take_layer(sharding):
    return jax.jit(
        lambda a, l: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
        out_shardings=sharding)


def _unstack(stack) -> tuple:
    """``[L, ...]`` -> L buffers ``[...]``, each with the stack's
    sharding less its layer axis. One program for all layers of a shape
    (the index is an argument)."""
    sharding = stack.sharding
    if isinstance(sharding, NamedSharding):
        sharding = NamedSharding(sharding.mesh, P(*sharding.spec[1:]))
    else:
        sharding = None     # one device: the result stays where it is
    take = _take_layer(sharding)
    return tuple(take(stack, np.int32(l)) for l in range(stack.shape[0]))


class MixtralForCausalLM(LlamaForCausalLM):

    QUANT_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    # LoRA on the attention projections only (per-expert adapters would
    # need expert-grouped LoRA GEMMs; the reference likewise restricts
    # MoE LoRA support).
    LORA_TARGETS = ("wq", "wk", "wv", "wo")
    _EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
    # Per-layer buffers in the serving layout, with the quantization
    # scales that ride on them (module docstring).
    _PER_LAYER = _EXPERT_WEIGHTS

    @property
    def num_physical(self) -> int:
        """Physical expert slots (>= logical; extra slots hold EPLB
        replicas of hot experts, reference: distributed/eplb/)."""
        return self.cfg.num_physical_experts or self.cfg.num_experts

    @property
    def _replica_cap(self) -> int:
        # One expert could absorb every spare slot: static buffer bound.
        return self.num_physical - self.cfg.num_experts + 1

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        specs = super().param_specs()
        layer = specs["layers"]
        for k in ("gate", "up", "down"):
            layer.pop(k)
        layer["router"] = P(None, None, None)  # [L, H, E] replicated
        if self.num_physical > self.cfg.num_experts:
            # EPLB routing buffers: replicated (tiny int tables).
            layer["expert_map"] = P(None, None, None)
            layer["expert_replicas"] = P(None, None)
        if self.cfg.expert_parallel:
            # Experts sharded over the model axis: each rank holds
            # E/ep_size whole experts (reference: FusedMoE EP path).
            ffn = P(None, MODEL_AXIS, None, None)
            layer.update({"w_gate": ffn, "w_up": ffn, "w_down": ffn})
        else:
            # TP inside each expert's FFN (Megatron layout per expert).
            layer.update({
                "w_gate": P(None, None, None, MODEL_AXIS),
                "w_up": P(None, None, None, MODEL_AXIS),
                "w_down": P(None, None, MODEL_AXIS, None),
            })
        self._add_scale_specs(layer)
        return specs

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        params = super().init_params(rng, scale)
        c = self.cfg
        L, H, E = c.num_layers, c.hidden_size, c.num_experts
        I = c.moe_intermediate_size or c.intermediate_size
        keys = iter(jax.random.split(jax.random.fold_in(rng, 17), 4))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = params["layers"]
        for k in ("gate", "up", "down"):
            layers.pop(k)
        layers["router"] = norm(next(keys), (L, H, E))
        layers["w_gate"] = norm(next(keys), (L, E, H, I))
        layers["w_up"] = norm(next(keys), (L, E, H, I))
        layers["w_down"] = norm(next(keys), (L, E, I, H))
        self._install_physical_experts(layers)
        return params

    def params_from_hf_state_dict(self, tensors: dict[str, np.ndarray],
                                  ) -> dict:
        c = self.cfg
        L, E = c.num_layers, c.num_experts
        # The base mapper handles every non-MLP tensor but requires the
        # dense-MLP names; alias them to expert 0's weights (the dense
        # entries are dropped right after) and stack the real expert
        # tensors below.
        alias = dict(tensors)
        for i in range(L):
            alias[f"model.layers.{i}.mlp.gate_proj.weight"] = tensors[
                f"model.layers.{i}.block_sparse_moe.experts.0.w1.weight"]
            alias[f"model.layers.{i}.mlp.up_proj.weight"] = tensors[
                f"model.layers.{i}.block_sparse_moe.experts.0.w3.weight"]
            alias[f"model.layers.{i}.mlp.down_proj.weight"] = tensors[
                f"model.layers.{i}.block_sparse_moe.experts.0.w2.weight"]
        params = super().params_from_hf_state_dict(alias)
        layers = params["layers"]
        for k in ("gate", "up", "down"):
            layers.pop(k)

        def stack_experts(fmt, transpose=True):
            per_layer = []
            for i in range(L):
                mats = [np.asarray(tensors[fmt.format(i, e)])
                        for e in range(E)]
                per_layer.append(
                    np.stack([m.T if transpose else m for m in mats]))
            return jnp.asarray(np.stack(per_layer), dtype=c.dtype)

        layers["router"] = jnp.asarray(
            np.stack([
                np.asarray(
                    tensors[f"model.layers.{i}.block_sparse_moe"
                            f".gate.weight"]).T for i in range(L)
            ]), dtype=c.dtype)
        layers["w_gate"] = stack_experts(
            "model.layers.{}.block_sparse_moe.experts.{}.w1.weight")
        layers["w_up"] = stack_experts(
            "model.layers.{}.block_sparse_moe.experts.{}.w3.weight")
        layers["w_down"] = stack_experts(
            "model.layers.{}.block_sparse_moe.experts.{}.w2.weight")
        self._install_physical_experts(layers)
        return params

    # ------------------------------------------------------------------
    # EPLB: physical expert slots + logical->physical routing buffers
    # ------------------------------------------------------------------
    def _install_physical_experts(self, layers: dict) -> None:
        """Expand the logical [L, E, ...] expert stacks to the physical
        slot count with an initial balanced placement, and install the
        routing buffers the forward reads."""
        if self.num_physical == self.cfg.num_experts:
            return
        from vllm_distributed_tpu.parallel.eplb import rebalance_experts
        L, E = self.cfg.num_layers, self.cfg.num_experts
        placement = rebalance_experts(np.ones((L, E)), self.num_physical,
                                      self.cfg.expert_parallel_ranks)
        self._scatter_placement(layers, placement)

    def _scatter_placement(self, layers: dict, placement) -> None:
        """Gather expert weights into physical-slot order and refresh
        the routing buffers. The logical source for slot p is the
        CURRENT first replica of placement's logical id — so this works
        both at install time (logical order) and on a live rebalance."""
        L, E = self.cfg.num_layers, self.cfg.num_experts
        p2l = placement.phys_to_logical  # [L, P]
        have_map = "expert_map" in layers

        if have_map:
            cur_first = np.asarray(layers["expert_map"])[:, :, 0]  # [L, E]

        for name in self._EXPERT_WEIGHTS:
            for key in (name, name + "_scale"):
                if key not in layers:
                    continue
                # One layer at a time, so a stack and a serving
                # layout's tuple of per-layer buffers answer alike, and
                # the result keeps the layout it came in. jnp, not np:
                # the weights may be traced (the dummy loader jits
                # init_params); the index tables are host.
                arr = layers[key]
                moved = []
                for l in range(L):
                    logical = arr[l][cur_first[l]] if have_map else arr[l]
                    moved.append(logical[p2l[l]])
                layers[key] = (tuple(moved) if isinstance(arr, tuple)
                               else jnp.stack(moved))
        r_cap = self._replica_cap
        emap = np.zeros((L, E, r_cap), np.int32)
        for l in range(L):
            for e in range(E):
                ids = placement.logical_to_phys[l, e]
                ids = ids[ids >= 0]
                emap[l, e, :len(ids)] = ids
                emap[l, e, len(ids):] = ids[0]  # safe padding
        layers["expert_map"] = jnp.asarray(emap)
        layers["expert_replicas"] = jnp.asarray(
            placement.logical_replicas.astype(np.int32))

    def apply_rebalance(self, params: dict, placement) -> dict:
        """Live EPLB step: move expert weights to the new placement and
        swap the routing buffers (reference: rebalance_execute.py, done
        here as host gathers + re-placement; the runner re-places the
        returned tree with its param shardings)."""
        self._scatter_placement(params["layers"], placement)
        return params

    # ------------------------------------------------------------------
    # Serving layout: expert weights as per-layer buffers
    # ------------------------------------------------------------------
    def _per_layer_keys(self, layers: dict) -> list:
        return [name + rider for name in self._PER_LAYER
                for rider in ("", "_scale", "_gscale", "_gmin")
                if name + rider in layers]

    def serving_layout(self, layers: dict) -> dict:
        """Replace each layer-stacked expert tensor by a tuple of its
        layers, each a device buffer of its own with the stack's
        sharding less the layer axis (module docstring). In place and
        ONE KEY AT A TIME, the stack dropped before the next is split:
        a stack and its leaves coexist once, never all three (OLMoE at
        8 layers: 2.15 GB beside 7.1 GB of weights, before the HBM
        profile and the pool exist). One device copy of the expert
        weights at load, outside every window."""
        for key in self._per_layer_keys(layers):
            if not isinstance(layers[key], tuple):  # else: per layer already
                # pop: the leaves hold the only copy once they are made.
                layers[key] = jax.block_until_ready(
                    _unstack(layers.pop(key)))
        return layers

    def canonical_layout(self, layers: dict) -> dict:
        """Stack, on the host, what ``serving_layout`` split: the tuples
        whose canonical spec has the layer axis their leaves lack (a
        family whose canonical leaves are per layer keeps them)."""
        specs = self.param_specs()["layers"]
        out = dict(layers)
        for key in self._per_layer_keys(layers):
            leaves = layers[key]
            if (isinstance(leaves, tuple)
                    and len(specs[key]) == leaves[0].ndim + 1):
                out[key] = np.stack(
                    [np.asarray(jax.device_get(x)) for x in leaves])
        return out

    # ------------------------------------------------------------------
    def mlp_block(self, lp: dict, x: jax.Array,
                  lora_ctx=None) -> jax.Array:
        """Sparse-MoE FFN via grouped (ragged) matmuls, computed exactly
        for every selected token:

        router softmax -> top-k -> renormalize (HF Mixtral semantics,
        reference models/mixtral.py MixtralMoE.forward), then the TPU
        dispatch: flatten the T*k (token, expert) assignments, sort by
        expert, run ``jax.lax.ragged_dot`` against the expert-stacked
        weights (the XLA grouped-GEMM that replaces the reference's
        fused_moe CUDA kernels / moe_pallas.py seed), and segment-sum
        the weighted rows back. Cost is k/E of the dense all-expert
        form — only selected (token, expert) pairs hit the MXU. On one
        chip the sorted picks are padded behind the last group to a row
        count that ``ragged_dot`` tiles well (``padded_picks``: the
        same products in larger tiles), and every row in no group, the
        padding and a pick past the last expert alike, is selected away
        before the sum.

        VDT_MOE_BACKEND=dense restores the all-expert einsum baseline
        (also used by the FLOP-reduction regression test).

        The ``moe_*`` named scopes only label the operations (they show
        in a device trace's op metadata): router, dispatch (sort and row
        gather), the three grouped GEMMs, combine."""
        with jax.named_scope("moe_router"):
            top_idx, top_vals = self._route(lp, x)
        return self.moe_dispatch(lp, x, top_idx, top_vals)

    def _route(self, lp: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Router softmax -> top-k -> optional renormalize (HF Mixtral
        semantics); subclasses override for other gating schemes."""
        c = self.cfg
        # Router in fp32 for parity with the HF reference.
        logits = (x.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32))  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, c.num_experts_per_tok)
        if c.norm_topk_prob:
            top_vals = top_vals / top_vals.sum(axis=-1, keepdims=True)
        return top_idx, top_vals

    def moe_dispatch(self, lp: dict, x: jax.Array, top_idx: jax.Array,
                     top_vals: jax.Array) -> jax.Array:
        """Routing-agnostic grouped-GEMM dispatch of (token, expert)
        assignments (see mlp_block docstring for the mechanism); under
        an expert share (``cfg.experts_held``, set by the families that
        read ``expert_share``: models/deepseek.py, models/cohere_moe.py,
        models/granite_hybrid.py) the share's own dispatch."""
        from vllm_distributed_tpu import envs
        c = self.cfg
        if getattr(c, "experts_held", None) is not None:
            return self._share_dispatch(lp, x, top_idx, top_vals)
        T = x.shape[0]
        k = top_idx.shape[-1]
        E = c.num_experts

        if envs.VDT_MOE_BACKEND == "dense":
            return self._moe_dense(lp, x, top_idx, top_vals)

        if c.expert_parallel and self._a2a_applicable(T):
            # True all-to-all dispatch: tokens shard over the EP axis,
            # rows travel to their expert-owner rank and back.
            return self._moe_ep_a2a(lp, x, top_idx, top_vals)

        # Flatten assignments and sort by expert id: each expert's rows
        # become contiguous, exactly what ragged_dot's group_sizes
        # describe (reference: moe_align_block_size kernels, csrc/moe/).
        with jax.named_scope("moe_dispatch"):
            flat_e = top_idx.astype(jnp.int32).reshape(-1)    # [T*k]
            flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
            flat_w = top_vals.reshape(-1)
            Pn = self.num_physical
            if Pn > E:
                # EPLB indirection: each assignment picks one of its
                # logical expert's physical replicas, spread round-robin
                # by token row (reference: eplb_state.py replica
                # selection).
                choice = flat_t % lp["expert_replicas"][flat_e]
                flat_e = lp["expert_map"][flat_e, choice]
            order = jnp.argsort(flat_e, stable=True)
            se, st = flat_e[order], flat_t[order]
            sw = flat_w[order]
            if not c.expert_parallel:
                # Rows that ragged_dot tiles well (padded_picks): the
                # padding is no expert's, so it sorts behind every group
                # as a pick past the last expert does (models/zaya.py).
                pad = (0, padded_picks(T * k, Pn) - T * k)
                se = jnp.pad(se, pad, constant_values=Pn)
                st, sw = jnp.pad(st, pad), jnp.pad(sw, pad)
            xs = x[st]                                        # [rows, H]

        if c.expert_parallel:
            y = self._moe_ep_ragged(lp, xs, se, sw)  # weights its own rows
        else:
            y = self._expert_ffn(lp, xs, jnp.bincount(se, length=Pn))
            with jax.named_scope("moe_combine"):
                # Rows in no group were in no GEMM: selected away, not
                # multiplied by a weight of 0 (_expert_ffn's docstring).
                y = jnp.where((se < Pn)[:, None],
                              y * sw[:, None].astype(y.dtype), 0)
        # Un-sort + combine the k expert outputs per token.
        with jax.named_scope("moe_combine"):
            out = jax.ops.segment_sum(y, st, num_segments=T)
        return out.astype(x.dtype)

    def share_capacity(self, T: int, k: int) -> int:
        """Rows of one pass of the share's dispatch: what an even router
        puts on the held experts at ``T`` tokens of ``k`` picks and a
        half more, in whole row tiles, never above ``T x k``. The count
        of tiles is odd, and a step of few picks is worked on whole:
        ``ragged_dot``'s time on a v5e follows the largest power of two
        that divides its rows (at 16 groups of 70 rows, 128: 3.7 ms for
        the three GEMMs, 512: 5.2, 32: 6.6, 8: 19.4), and under a
        thousand rows it does not fall with them (PERF.md, Findings
        PR 40). The whole-expert branch of ``moe_dispatch`` works on all
        ``T x k`` picks and pads them by the same finding, priced a
        visit at a time (``padded_picks``, PR 48)."""
        picks = T * k
        if picks <= _WHOLE_PICKS:
            return picks
        c = self.cfg
        expected = cdiv(picks * c.experts_held[1], c.router_experts)
        tiles = cdiv(expected + expected // 2, _ROW_TILE) | 1
        return min(picks, tiles * _ROW_TILE)

    def _share_dispatch(self, lp: dict, x: jax.Array, top_idx: jax.Array,
                        top_vals: jax.Array) -> jax.Array:
        """One chip's share of an expert layer (``cfg.experts_held``;
        models/deepseek.py's module docstring has the deployment it
        stands for): the picks among the held experts ``[lo, lo + n)``
        sort to the front by local id and the rest behind them, and only
        the front is worked on, ``share_capacity`` rows a pass: one pass
        wherever the router is near even, as many as the held picks need
        wherever it is not (all ``T x k`` may land here), so no pick is
        dropped. A pass is ``_moe_ep_ragged``'s ``rank_fn`` on one rank,
        without the ``psum``, over its window of the sorted picks.
        ``ragged_dot`` does NOT zero the rows past its groups on the TPU
        (they come back uninitialised, NaN among them, as measured on a
        v5e in PR 28), so those rows are selected away, not multiplied
        by a weight of 0."""
        held = self.cfg.experts_held
        lo, n = held
        T, k = top_idx.shape
        C = self.share_capacity(T, k)
        with jax.named_scope("moe_dispatch"):
            local = top_idx.astype(jnp.int32).reshape(-1) - lo
            local = jnp.where((local >= 0) & (local < n), local, n)
            # Padded to whole passes: a window never runs off the end.
            order = jnp.pad(jnp.argsort(local, stable=True),
                            (0, round_up(T * k, C) - T * k))
            sizes = jnp.bincount(local, length=n + 1)[:n]
            ends = jnp.cumsum(sizes)
            starts = ends - sizes
            weights = top_vals.reshape(-1)

        def one_pass(carry):
            at, out = carry
            with jax.named_scope("moe_dispatch"):
                picks = jax.lax.dynamic_slice(order, (at,), (C,))
                st = picks // k
                xs = x[st]
                # Each group clipped to the window [at, at + C).
                group_sizes = jnp.maximum(
                    jnp.minimum(ends, at + C) - jnp.maximum(starts, at), 0)
            y = self._expert_ffn(lp, xs, group_sizes)
            with jax.named_scope("moe_combine"):
                in_group = at + jnp.arange(C) < ends[-1]
                y = jnp.where(in_group[:, None],
                              y * weights[picks][:, None].astype(y.dtype), 0)
                out = out.at[st].add(y)
            return at + C, out

        _, out = jax.lax.while_loop(
            lambda carry: carry[0] < ends[-1], one_pass,
            (jnp.int32(0), jnp.zeros_like(x)))
        return out


    def _expert_ffn(self, lp: dict, xs: jax.Array,
                    group_sizes: jax.Array) -> jax.Array:
        """Gated experts over expert-sorted rows, ``(act(x Wg) * (x
        Wu)) Wd`` in three grouped GEMMs; ``act`` is the family's
        ``_act`` (SiLU where ``hidden_act`` is unset: SwiGLU; ReLU for
        models/smallthinker.py: ReGLU). Rows beyond sum(group_sizes)
        come back zero on the CPU and UNINITIALISED on the TPU (measured
        on a v5e, PR 28: NaN among them): a caller that leaves rows out
        of the groups selects them away afterwards."""
        with jax.named_scope("moe_gate_gemm"):
            g = self._act(jax.lax.ragged_dot(
                xs, self._w(lp, "w_gate"), group_sizes))
        with jax.named_scope("moe_up_gemm"):
            u = jax.lax.ragged_dot(xs, self._w(lp, "w_up"), group_sizes)
        with jax.named_scope("moe_down_gemm"):
            return jax.lax.ragged_dot(g * u, self._w(lp, "w_down"),
                                      group_sizes)

    def _a2a_applicable(self, T: int) -> bool:
        """The all-to-all dispatch needs the token bucket divisible by
        the EP width (static per-rank slices) and the mode not forced
        off; EPLB redundancy composes (the physical-replica indirection
        runs per rank on its token slice with global token indices).
        Non-applicable cases fall back to the exact replicate+psum
        path."""
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        if envs.VDT_MOE_EP_MODE != "a2a":
            return False
        if not mesh_state.has_global_mesh():
            return False
        ep = mesh_state.get_global_mesh().shape[MODEL_AXIS]
        return (ep > 1 and T % ep == 0
                and self.num_physical % ep == 0)

    def _moe_ep_a2a(self, lp: dict, x: jax.Array, top_idx: jax.Array,
                    top_vals: jax.Array) -> jax.Array:
        """Expert-parallel MoE with TRUE all-to-all dispatch (reference:
        device_communicators/all2all.py NaiveAll2AllManager and the
        dispatch/combine hooks at parallel_state.py:790-803).

        Each rank of the ``model`` axis owns E/ep whole experts AND a
        T/ep slice of the token batch. A rank buckets its own (token,
        expert) assignments by owner rank into fixed-capacity send
        buffers (capacity = its full T/ep*k rows, so no assignment is
        ever dropped — static shapes, exact compute), `lax.all_to_all`s
        rows to their expert owners, runs the grouped GEMMs locally,
        `all_to_all`s the weighted outputs back, combines its own
        tokens' k rows, and one tiled all_gather re-replicates the
        output for the (activation-replicated) engine (all three
        shuffles ride the quantized plane under VDT_QCOMM_PATHS "ep").

        ICI volume per MoE layer is O(T*k*H) each way plus the [T, H]
        gather — vs the replicate+psum path's O(ep * T * k * H) psum.
        The worst-case capacity keeps this exact; a capacity-factor
        (dropping) variant would trade exactness for bandwidth, which
        the parity tests forbid."""
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        mesh = mesh_state.get_global_mesh()
        ep = mesh.shape[MODEL_AXIS]
        E_local = self.num_physical // ep
        T = x.shape[0]
        k = top_idx.shape[-1]
        Tl = T // ep
        Rk = Tl * k  # send capacity per destination (worst case)
        H = x.shape[-1]
        eplb = self.num_physical > self.cfg.num_experts

        def rank_fn(w_gate, w_up, w_down, x_, ti_, tv_, emap_, erep_):
            r = jax.lax.axis_index(MODEL_AXIS)
            xs = jax.lax.dynamic_slice_in_dim(x_, r * Tl, Tl)
            til = jax.lax.dynamic_slice_in_dim(ti_, r * Tl, Tl)
            tvl = jax.lax.dynamic_slice_in_dim(tv_, r * Tl, Tl)
            flat_e = til.astype(jnp.int32).reshape(-1)       # [Rk]
            flat_w = tvl.reshape(-1)
            flat_tok = jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), k)
            if eplb:
                # EPLB indirection with GLOBAL token indices so replica
                # spreading matches the replicate-path semantics
                # (dispatch docstring; eplb_state.py replica choice).
                gtok = r * Tl + flat_tok
                choice = gtok % erep_[flat_e]
                flat_e = emap_[flat_e, choice]
            dest = flat_e // E_local
            order = jnp.argsort(dest, stable=True)
            d_sorted = dest[order]
            # Position within the destination bucket: index minus the
            # bucket's first index in the sorted order.
            within = (jnp.arange(Rk, dtype=jnp.int32) -
                      jnp.searchsorted(d_sorted, d_sorted,
                                       side="left").astype(jnp.int32))
            slot = d_sorted * Rk + within                    # unique
            send_x = jnp.zeros((ep * Rk, H), x_.dtype).at[slot].set(
                xs[flat_tok[order]])
            send_e = jnp.full((ep * Rk, ), -1, jnp.int32).at[slot].set(
                flat_e[order] % E_local)
            send_w = jnp.zeros((ep * Rk, ), flat_w.dtype).at[slot].set(
                flat_w[order])
            # Rows travel to their expert owner... (the [ep, Rk, H]
            # activation shuffle is the dominant EP wire cost; VDT_QCOMM
            # ships it block-scaled int8 — routing ids/weights stay raw,
            # they are a K/H fraction of the volume).
            from vllm_distributed_tpu.parallel import collectives
            recv_x = collectives.all_to_all(
                send_x.reshape(ep, Rk, H), MODEL_AXIS, 0, 0, path="ep")
            recv_e = jax.lax.all_to_all(
                send_e.reshape(ep, Rk), MODEL_AXIS, 0, 0).reshape(-1)
            recv_w = jax.lax.all_to_all(
                send_w.reshape(ep, Rk), MODEL_AXIS, 0, 0).reshape(-1)
            # ...grouped GEMMs over the received rows (padding rows sort
            # into the dropped E_local bucket and come back zero)...
            eid = jnp.where(recv_e >= 0, recv_e, E_local)
            order2 = jnp.argsort(eid, stable=True)
            xs2 = recv_x.reshape(ep * Rk, H)[order2]
            gs = jnp.bincount(eid[order2], length=E_local + 1)[:-1]
            g = self._act(jax.lax.ragged_dot(xs2, w_gate, gs))
            u = jax.lax.ragged_dot(xs2, w_up, gs)
            y = jax.lax.ragged_dot(g * u, w_down, gs)
            y = y * recv_w[order2][:, None].astype(y.dtype)
            y = y[jnp.argsort(order2)]                       # recv order
            # ...and back to their owner (all_to_all is positionally an
            # involution here: my receive slice j returns as slice j).
            back = collectives.all_to_all(
                y.reshape(ep, Rk, H), MODEL_AXIS, 0, 0,
                path="ep").reshape(ep * Rk, H)
            # Combine this rank's k rows per token; slot layout gives
            # each row's source token.
            src_tok = jnp.full((ep * Rk, ), Tl, jnp.int32).at[slot].set(
                flat_tok[order])
            out_local = jax.ops.segment_sum(back, src_tok,
                                            num_segments=Tl + 1)[:Tl]
            # Re-replicate for the activation-replicated engine — the
            # [T, H] gather is the EP path's remaining wire cost after
            # the quantized a2a legs; VDT_QCOMM ships it block-scaled
            # int8 under the same "ep" path.
            return collectives.all_gather(out_local, MODEL_AXIS,
                                          tiled=True, path="ep")

        emap = (lp["expert_map"] if eplb else
                jnp.zeros((1, 1), jnp.int32))
        erep = (lp["expert_replicas"] if eplb else
                jnp.ones((1, ), jnp.int32))
        out = shard_map(
            rank_fn, mesh=mesh,
            in_specs=(P(MODEL_AXIS, None, None), P(MODEL_AXIS, None, None),
                      P(MODEL_AXIS, None, None), P(), P(), P(), P(), P()),
            out_specs=P(),
            check_vma=False)(self._w(lp, "w_gate"), self._w(lp, "w_up"),
                             self._w(lp, "w_down"), x,
                             top_idx.astype(jnp.int32),
                             top_vals.astype(jnp.float32), emap, erep)
        return out.astype(x.dtype)

    def _moe_ep_ragged(self, lp: dict, xs: jax.Array, se: jax.Array,
                       sw: jax.Array) -> jax.Array:
        """Expert-parallel dispatch: each rank of the ``model`` axis
        holds E/ep whole experts (reference: FusedMoE EP + all2all
        managers, device_communicators/all2all.py). Activations are
        replicated across the axis, so "dispatch" is a local partition —
        every rank stable-partitions ITS experts' rows to the front,
        runs the grouped GEMMs on its local expert slab (ragged_dot
        zero-fills the foreign rows), and the combine is one psum over
        ICI. Exact compute: no capacity factor, no dropped tokens."""
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        mesh = mesh_state.get_global_mesh()
        ep = mesh.shape[MODEL_AXIS]
        E_local = self.num_physical // ep

        def rank_fn(w_gate, w_up, w_down, xs_, se_, sw_):
            r = jax.lax.axis_index(MODEL_AXIS)
            lo = r * E_local
            is_local = (se_ >= lo) & (se_ < lo + E_local)
            part = jnp.argsort(~is_local, stable=True)  # local rows first
            xs_l = xs_[part]
            local_ids = jnp.where(is_local[part], se_[part] - lo, E_local)
            # Foreign rows bucket into a virtual group E_local that is
            # dropped from group_sizes; ragged_dot leaves their rows
            # uninitialised on the TPU, so they are selected away.
            group_sizes = jnp.bincount(local_ids, length=E_local + 1)[:-1]
            g = self._act(jax.lax.ragged_dot(xs_l, w_gate, group_sizes))
            u = jax.lax.ragged_dot(xs_l, w_up, group_sizes)
            y = jax.lax.ragged_dot(g * u, w_down, group_sizes)
            y = jnp.where(is_local[part][:, None],
                          y * sw_[part][:, None].astype(y.dtype), 0)
            y = y[jnp.argsort(part)]  # back to expert-sorted order
            from vllm_distributed_tpu.parallel import collectives
            return collectives.psum(y, MODEL_AXIS, path="ep")

        return shard_map(
            rank_fn, mesh=mesh,
            in_specs=(P(MODEL_AXIS, None, None), P(MODEL_AXIS, None, None),
                      P(MODEL_AXIS, None, None), P(), P(), P()),
            out_specs=P(),
            check_vma=False)(self._w(lp, "w_gate"),
                             self._w(lp, "w_up"),
                             self._w(lp, "w_down"), xs, se, sw)

    def _moe_dense(self, lp: dict, x: jax.Array, top_idx: jax.Array,
                   top_vals: jax.Array) -> jax.Array:
        """All-expert einsum baseline (E/k x the needed FLOPs); kept for
        A/B testing and the FLOP-reduction regression test."""
        c = self.cfg
        T = x.shape[0]
        rows = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[:, None],
            (T, c.num_experts_per_tok))
        if self.num_physical > c.num_experts:
            # EPLB: address each logical expert's first physical replica
            # (the dense baseline doesn't spread load).
            top_idx = lp["expert_map"][top_idx, 0]
        gates = jnp.zeros((T, self.num_physical), jnp.float32).at[
            rows, top_idx].set(top_vals)
        g = self._act(
            jnp.einsum("th,ehi->eti", x, self._w(lp, "w_gate")))
        u = jnp.einsum("th,ehi->eti", x, self._w(lp, "w_up"))
        y = jnp.einsum("eti,eih->eth", g * u, self._w(lp, "w_down"))
        out = jnp.einsum("te,eth->th", gates.astype(y.dtype), y)
        return out.astype(x.dtype)


class Qwen2MoeForCausalLM(MixtralForCausalLM):
    """Qwen2-MoE (reference: vllm/model_executor/models/qwen2_moe.py):
    the Mixtral routed-expert block plus a sigmoid-gated SHARED expert
    that runs densely for every token, qkv bias, non-renormalized top-k
    routing weights, and a narrower per-expert FFN
    (moe_intermediate_size). Checkpoint names map onto the Mixtral
    layout; the shared expert adds three stacked dense tensors and the
    [H, 1] gate."""

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.num_experts = hf.num_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.attention_bias = True  # Qwen2-style qkv bias, always on
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", False))
        arch.moe_intermediate_size = hf.moe_intermediate_size
        arch.shared_expert_intermediate_size = \
            hf.shared_expert_intermediate_size
        if (getattr(hf, "mlp_only_layers", None)
                or getattr(hf, "decoder_sparse_step", 1) != 1):
            raise ValueError(
                "Qwen2-MoE layouts mixing dense and sparse MLP layers "
                "(mlp_only_layers / decoder_sparse_step != 1) are not "
                "supported; every layer must be sparse")

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        specs = super().param_specs()
        layer = specs["layers"]
        # Shared expert: Megatron dense-MLP layout; the tiny sigmoid
        # gate is replicated.
        layer.update({
            "shared_gate": P(None, None, MODEL_AXIS),
            "shared_up": P(None, None, MODEL_AXIS),
            "shared_down": P(None, MODEL_AXIS, None),
            "shared_egate": P(None, None, None),
        })
        return specs

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        params = super().init_params(rng, scale)
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        Is = c.shared_expert_intermediate_size or c.intermediate_size
        keys = iter(jax.random.split(jax.random.fold_in(rng, 23), 4))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        params["layers"].update({
            "shared_gate": norm(next(keys), (L, H, Is)),
            "shared_up": norm(next(keys), (L, H, Is)),
            "shared_down": norm(next(keys), (L, Is, H)),
            "shared_egate": norm(next(keys), (L, H, 1)),
        })
        return params

    def params_from_hf_state_dict(self, tensors: dict[str, np.ndarray],
                                  ) -> dict:
        c = self.cfg
        L, E = c.num_layers, c.num_experts
        # Rename onto the Mixtral checkpoint layout, then stack the
        # shared-expert tensors on top.
        alias = dict(tensors)
        for i in range(L):
            src = f"model.layers.{i}.mlp"
            dst = f"model.layers.{i}.block_sparse_moe"
            alias[f"{dst}.gate.weight"] = tensors[f"{src}.gate.weight"]
            for e in range(E):
                for a, b in (("gate_proj", "w1"), ("down_proj", "w2"),
                             ("up_proj", "w3")):
                    alias[f"{dst}.experts.{e}.{b}.weight"] = \
                        tensors[f"{src}.experts.{e}.{a}.weight"]
        params = super().params_from_hf_state_dict(alias)

        def stack(fmt):
            return jnp.asarray(
                np.stack([np.asarray(tensors[fmt.format(i)]).T
                          for i in range(L)]), dtype=c.dtype)

        params["layers"].update({
            "shared_gate": stack(
                "model.layers.{}.mlp.shared_expert.gate_proj.weight"),
            "shared_up": stack(
                "model.layers.{}.mlp.shared_expert.up_proj.weight"),
            "shared_down": stack(
                "model.layers.{}.mlp.shared_expert.down_proj.weight"),
            "shared_egate": stack(
                "model.layers.{}.mlp.shared_expert_gate.weight"),
        })
        return params

    # ------------------------------------------------------------------
    def mlp_block(self, lp: dict, x: jax.Array,
                  lora_ctx=None) -> jax.Array:
        routed = super().mlp_block(lp, x, lora_ctx)
        from vllm_distributed_tpu.models.common import swiglu
        shared = swiglu(x, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"], act=self._act)
        # Sigmoid gate in fp32 (HF computes the gate on fp hidden).
        gate = jax.nn.sigmoid(x.astype(jnp.float32)
                              @ lp["shared_egate"].astype(jnp.float32))
        return routed + gate.astype(x.dtype) * shared
