"""Mamba (selective state-space) model family.

Reference surface: vllm/model_executor/models/mamba.py (pure Mamba-1
MambaForCausalLM) built on layers/mamba/mamba_mixer.py, with per-request
SSM state held in the KV cache as a MambaSpec "one block per request"
group (vllm/v1/kv_cache_interface.py) and chunk metadata from
v1/attention/backends/mamba_attn.py.

TPU design: the mixer runs directly on the engine's flat ragged token
batch via the segmented associative scan in ops/mamba.py — no
prefill/decode split, no chunk-index tables; decode, chunked prefill and
mixed batches are one compiled program per token bucket. State lives in
fixed-size per-request rows indexed by the runner's persistent
input-batch slots (state is O(1) per request, so paging buys nothing);
the page pool is sized to "free" (kv_cache_page_bytes == 0) and the
worker charges the fixed state bytes instead (fixed_cache_bytes).

Tensor parallelism shards the d_inner channel axis: in/out projections
column/row-parallel, conv + scan fully elementwise in the shard, B/C/dt
projections replicated (they are per-token vectors of rank << d_inner).
Prefix caching is disabled for stateful families at scheduler
construction (models/loader.resolve_stateful): SSM state cannot be
re-entered at an arbitrary page boundary — matching the reference,
which likewise rejects prefix caching for mamba models.
"""

from __future__ import annotations

import math
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import rms_norm
from vllm_distributed_tpu.models.llama import (MODEL_AXIS,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.ops.conv1d import (causal_conv1d_rows, rows_shape,
                                             step_entries)
from vllm_distributed_tpu.ops.mamba import (build_segment_info,
                                            causal_conv1d_ragged,
                                            selective_scan_ragged)
from vllm_distributed_tpu.ops.ssd import chunk_form_tokens, ssd_ragged


def _softplus(x: jax.Array) -> jax.Array:
    return jax.nn.softplus(x)


def ssd_on_mesh(xh, dt, A, B, C, D, ssm_all, layer, seg, chunk_size: int,
                decode_only: bool):
    """ops/ssd.py on this rank's heads (the state kernels know no
    mesh): under tensor parallelism each rank runs them on its
    d_inner shard, with B and C cut (or first repeated) so that a
    rank holds the groups of its own heads."""
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    kw = dict(chunk_size=chunk_size, decode_only=decode_only)
    tp = (mesh_state.tp_size() if mesh_state.has_global_mesh() else 1)
    if tp == 1:
        return ssd_ragged(xh, dt, A, B, C, D, ssm_all, layer, seg, **kw)
    rep = tp // math.gcd(B.shape[1], tp)
    B, C = (jnp.repeat(v, rep, axis=1) for v in (B, C))
    heads, vec = P(None, MODEL_AXIS, None), P(MODEL_AXIS)
    rows = P(None, None, None, MODEL_AXIS)
    return mesh_state.shard_map(
        lambda *a: ssd_ragged(*a, **kw),
        mesh=mesh_state.get_global_mesh(),
        in_specs=(heads, P(None, MODEL_AXIS), vec, heads, heads, vec,
                  rows, P(), P()),
        out_specs=(heads, rows), check_vma=False)(
            xh, dt, A, B, C, D, ssm_all, layer, seg)


def conv_on_mesh(x, weight, bias, rows, layer, seg, entries,
                 sharded: bool):
    """ops/conv1d.py on this rank's channels (the kernel knows no
    mesh): under tensor parallelism each rank runs it on its shard of
    the channels (``sharded``: d_inner) or on all of them (B and C, which
    every rank holds)."""
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    if not mesh_state.has_global_mesh() or mesh_state.tp_size() == 1:
        return causal_conv1d_rows(x, weight, bias, rows, layer, seg,
                                  entries)
    axis = MODEL_AXIS if sharded else None
    cols, tiles = P(None, axis), P(None, None, None, axis, None)
    return mesh_state.shard_map(
        causal_conv1d_rows, mesh=mesh_state.get_global_mesh(),
        in_specs=(cols, cols, None if bias is None else P(axis), tiles,
                  P(), P(), P()),
        out_specs=(cols, tiles), check_vma=False)(
            x, weight, bias, rows, layer, seg, entries)


class MambaForCausalLM(LlamaForCausalLM):
    """Pure Mamba-1 stack: L x (RMSNorm -> MambaMixer) + final norm.

    HF checkpoint layout: backbone.embeddings, backbone.layers.{i}.norm
    + .mixer.{in_proj,conv1d,x_proj,dt_proj,A_log,D,out_proj},
    backbone.norm_f, tied lm_head.
    """

    QUANT_TARGETS = ()  # weight quantization for SSM stacks: follow-up
    LORA_TARGETS = ()
    STATEFUL = True
    # Pure-SSM stack: pages carry no bytes, so a state snapshot alone is
    # a complete resume point (hybrid stacks set this False — their
    # restores must re-enter coherently with cached attention pages).
    STATE_ONLY = True

    @classmethod
    def arch_config_source(cls, hf):
        """MambaConfig lacks the attention fields from_hf_config reads;
        present a shim with inert attention values."""
        d_inner = getattr(hf, "intermediate_size", None) or (
            hf.expand * hf.hidden_size)
        return SimpleNamespace(
            vocab_size=hf.vocab_size,
            hidden_size=hf.hidden_size,
            intermediate_size=d_inner,
            num_hidden_layers=hf.num_hidden_layers,
            num_attention_heads=1,
            num_key_value_heads=1,
            head_dim=hf.hidden_size,
            rms_norm_eps=getattr(hf, "layer_norm_epsilon", 1e-5),
            tie_word_embeddings=getattr(hf, "tie_word_embeddings", True),
        )

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.stateful = True
        arch.ssm_state_size = hf.state_size
        arch.conv_kernel = hf.conv_kernel
        arch.d_inner = arch.intermediate_size
        dt_rank = getattr(hf, "time_step_rank", None)
        if dt_rank is None or dt_rank == "auto":
            dt_rank = math.ceil(hf.hidden_size / 16)
        arch.dt_rank = int(dt_rank)
        arch.use_conv_bias = bool(getattr(hf, "use_conv_bias", True))
        arch.use_bias = bool(getattr(hf, "use_bias", False))
        # Filled by the loader from SchedulerConfig.max_num_seqs; tests
        # constructing the model directly set it on the arch first.
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    def quantize_params(self, params: dict) -> dict:
        if self.cfg.quantization:
            raise ValueError(
                "weight quantization for SSM stacks is not wired yet; "
                "drop --quantization for Mamba-family models")
        return params

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        c = self.cfg
        layer = {
            "norm": P(None, None),
            "in_x": P(None, None, MODEL_AXIS),
            "in_z": P(None, None, MODEL_AXIS),
            "conv_w": P(None, None, MODEL_AXIS),
            "x_proj": P(None, MODEL_AXIS, None),
            "dt_w": P(None, None, MODEL_AXIS),
            "dt_b": P(None, MODEL_AXIS),
            "A_log": P(None, MODEL_AXIS, None),
            "D": P(None, MODEL_AXIS),
            "out_proj": P(None, MODEL_AXIS, None),
        }
        if c.use_conv_bias:
            layer["conv_b"] = P(None, MODEL_AXIS)
        if c.use_bias:
            layer["in_x_b"] = P(None, MODEL_AXIS)
            layer["in_z_b"] = P(None, MODEL_AXIS)
            layer["out_b"] = P(None, None)
        return {
            "embed": P(None, None),
            "layers": layer,
            "final_ln": P(None, ),
            "lm_head": P(None, MODEL_AXIS),
        }

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        Di, N, K, R = c.d_inner, c.ssm_state_size, c.conv_kernel, c.dt_rank
        keys = iter(jax.random.split(rng, 10))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = {
            "norm": jnp.ones((L, H), c.dtype),
            "in_x": norm(next(keys), (L, H, Di)),
            "in_z": norm(next(keys), (L, H, Di)),
            "conv_w": norm(next(keys), (L, K, Di)),
            "x_proj": norm(next(keys), (L, Di, R + 2 * N)),
            "dt_w": norm(next(keys), (L, R, Di)),
            "dt_b": jnp.zeros((L, Di), jnp.float32),
            # S4D-real init: A = -(1..N) per channel, like the published
            # Mamba initialization.
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (L, Di, N)) * jnp.ones((L, Di, 1), jnp.float32),
            "D": jnp.ones((L, Di), jnp.float32),
            "out_proj": norm(next(keys), (L, Di, H)),
        }
        if c.use_conv_bias:
            layers["conv_b"] = jnp.zeros((L, Di), c.dtype)
        if c.use_bias:
            layers["in_x_b"] = jnp.zeros((L, Di), c.dtype)
            layers["in_z_b"] = jnp.zeros((L, Di), c.dtype)
            layers["out_b"] = jnp.zeros((L, H), c.dtype)
        embed = norm(next(keys), (c.vocab_size, H))
        return {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.ones((H, ), c.dtype),
            "lm_head": (embed.T if c.tie_word_embeddings else norm(
                next(keys), (H, c.vocab_size))),
        }

    def _hf_stackers(self, tensors: dict):
        """(t, stack) helpers shared by the family's checkpoint maps."""
        L = self.cfg.num_layers

        def t(name):
            return np.asarray(tensors[name])

        def stack(fmt, f):
            return jnp.asarray(
                np.stack([f(t(fmt.format(i))) for i in range(L)]))

        return t, stack

    def _hf_tail(self, tensors: dict, layers: dict,
                 prefix: str) -> dict:
        """Assemble the param tree around a family's layers dict
        (embeddings / final norm / tied-or-separate lm_head)."""
        c = self.cfg
        t, _ = self._hf_stackers(tensors)
        embed = jnp.asarray(t(prefix + ".embeddings.weight")).astype(
            c.dtype)
        if c.tie_word_embeddings or "lm_head.weight" not in tensors:
            lm_head = embed.T
        else:
            lm_head = jnp.asarray(t("lm_head.weight")).T.astype(c.dtype)
        return {
            "embed": embed,
            "layers": layers,
            "final_ln":
            jnp.asarray(t(prefix + ".norm_f.weight")).astype(c.dtype),
            "lm_head": lm_head,
        }

    def params_from_hf_state_dict(self, tensors: dict,
                                  prefix: str = "backbone") -> dict:
        c = self.cfg
        Di = c.d_inner
        t, stack = self._hf_stackers(tensors)

        def lin(a):  # torch Linear weight [out, in] -> [in, out]
            return a.T

        mx = prefix + ".layers.{}.mixer."
        layers = {
            "norm":
            stack(prefix + ".layers.{}.norm.weight",
                  lambda a: a).astype(c.dtype),
            "in_x":
            stack(mx + "in_proj.weight",
                  lambda a: lin(a[:Di])).astype(c.dtype),
            "in_z":
            stack(mx + "in_proj.weight",
                  lambda a: lin(a[Di:])).astype(c.dtype),
            # conv1d depthwise weight [Di, 1, K] -> taps-major [K, Di].
            "conv_w":
            stack(mx + "conv1d.weight",
                  lambda a: a[:, 0, :].T).astype(c.dtype),
            "x_proj":
            stack(mx + "x_proj.weight", lin).astype(c.dtype),
            "dt_w":
            stack(mx + "dt_proj.weight", lin).astype(c.dtype),
            "dt_b":
            stack(mx + "dt_proj.bias", lambda a: a).astype(jnp.float32),
            "A_log":
            stack(mx + "A_log", lambda a: a).astype(jnp.float32),
            "D":
            stack(mx + "D", lambda a: a).astype(jnp.float32),
            "out_proj":
            stack(mx + "out_proj.weight", lin).astype(c.dtype),
        }
        if c.use_conv_bias:
            layers["conv_b"] = stack(mx + "conv1d.bias",
                                     lambda a: a).astype(c.dtype)
        if c.use_bias:
            layers["in_x_b"] = stack(mx + "in_proj.bias",
                                     lambda a: a[:Di]).astype(c.dtype)
            layers["in_z_b"] = stack(mx + "in_proj.bias",
                                     lambda a: a[Di:]).astype(c.dtype)
            layers["out_b"] = stack(mx + "out_proj.bias",
                                    lambda a: a).astype(c.dtype)
        return self._hf_tail(tensors, layers, prefix)

    # ------------------------------------------------------------------
    # State cache (replaces paged KV)
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        return {
            "conv": P(None, None, None, MODEL_AXIS),
            "ssm": P(None, None, MODEL_AXIS, None),
        }

    def _state_shapes(self, depth: int) -> dict:
        """One source of truth for state-cache shapes/dtypes, shared by
        make_kv_caches and fixed_cache_bytes so the worker's memory
        accounting can never drift from the arrays it allocates."""
        c = self.cfg
        S = (c.state_slots or 256) + 1  # +1 dump row for padding writes
        return {
            "conv": ((depth, S, c.conv_kernel - 1, c.d_inner), c.dtype),
            "ssm": ((depth, S, c.d_inner, c.ssm_state_size),
                    jnp.float32),
        }

    def state_shapes(self) -> dict:
        """State-array geometry for the snapshot pool
        (core/state_cache.py): {name: ((depth, S+1, ...), dtype)} —
        axis 1 is the per-request slot axis the runner's snapshot
        copies gather/scatter along."""
        return self._state_shapes(self.cfg.num_layers)

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        c = self.cfg
        depth = num_layers if num_layers is not None else c.num_layers
        return {
            name: jnp.zeros(shape, dtype)
            for name, (shape, dtype) in self._state_shapes(depth).items()
        }

    def kv_cache_page_bytes(self, page_size: int) -> int:
        # SSM state is per-request, not per-token: pages are free; the
        # worker charges fixed_cache_bytes instead.
        return 0

    def fixed_cache_bytes(self) -> int:
        return sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for shape, dtype in self._state_shapes(
                self.cfg.num_layers).values())

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def _mixer(self, lp: dict, x: jax.Array, conv_state, ssm_state, seg):
        """One Mamba-1 mixer over flat tokens x [T, Di-projected]."""
        c = self.cfg
        N, R = c.ssm_state_size, c.dt_rank
        xin = x @ lp["in_x"]
        z = x @ lp["in_z"]
        if c.use_bias:
            xin = xin + lp["in_x_b"]
            z = z + lp["in_z_b"]
        xc, conv_state = causal_conv1d_ragged(
            xin, lp["conv_w"], lp.get("conv_b"), conv_state, seg)
        xc = jax.nn.silu(xc)
        ssm_p = xc @ lp["x_proj"]  # [T, R + 2N]
        dt_r = ssm_p[:, :R]
        B = ssm_p[:, R:R + N]
        C = ssm_p[:, R + N:]
        eps = getattr(c, "mixer_rms_eps", None)
        if eps is not None:
            # FalconMamba: weightless RMSNorm on dt/B/C before use.
            ones = jnp.ones((1, ), jnp.float32)
            dt_r = rms_norm(dt_r.astype(jnp.float32), ones, eps)
            B = rms_norm(B.astype(jnp.float32), ones, eps)
            C = rms_norm(C.astype(jnp.float32), ones, eps)
        if "dt_ln" in lp:
            # Jamba: learned RMSNorms on the selection vectors
            # (dt_layernorm/b_layernorm/c_layernorm).
            dt_r = rms_norm(dt_r, lp["dt_ln"], c.rms_norm_eps)
            B = rms_norm(B, lp["b_ln"], c.rms_norm_eps)
            C = rms_norm(C, lp["c_ln"], c.rms_norm_eps)
        dt = _softplus(
            dt_r @ lp["dt_w"] + lp["dt_b"])  # [T, Di] f32 bias
        A = -jnp.exp(lp["A_log"])  # [Di, N] f32
        y, ssm_state = selective_scan_ragged(
            xc.astype(jnp.float32), dt, A, B, C, lp["D"], ssm_state, seg)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        out = y.astype(c.dtype) @ lp["out_proj"]
        if c.use_bias:
            out = out + lp["out_b"]
        return out, conv_state, ssm_state

    def run_layers(
        self,
        layer_params: dict,
        kv_caches: dict,
        hidden: jax.Array,  # [T, H]
        batch,
        first_layer: int = 0,
    ) -> tuple[jax.Array, dict]:
        c = self.cfg
        seg = build_segment_info(batch, kv_caches["ssm"].shape[1] - 1)
        num_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        layer_ids = jnp.arange(num_layers, dtype=jnp.int32)

        def layer_body(carry, xs):
            h, conv_all, ssm_all = carry
            lp, li = xs
            x = rms_norm(h, lp["norm"], c.rms_norm_eps)
            out, conv_new, ssm_new = self._mixer(
                lp, x, conv_all[li], ssm_all[li], seg)
            conv_all = jax.lax.dynamic_update_index_in_dim(
                conv_all, conv_new, li, 0)
            ssm_all = jax.lax.dynamic_update_index_in_dim(
                ssm_all, ssm_new, li, 0)
            return (h + out, conv_all, ssm_all), None

        carry = (hidden, kv_caches["conv"], kv_caches["ssm"])
        carry, _ = jax.lax.scan(layer_body, carry,
                                (layer_params, layer_ids))
        hidden, conv_all, ssm_all = carry
        return hidden, {"conv": conv_all, "ssm": ssm_all}


class FalconMambaForCausalLM(MambaForCausalLM):
    """FalconMamba: Mamba-1 with a weightless RMSNorm applied to the
    dt/B/C selection vectors (reference:
    vllm/model_executor/models/falcon_mamba.py mixer_rms_eps)."""

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        MambaForCausalLM.configure_arch(arch, hf)
        arch.mixer_rms_eps = getattr(hf, "mixer_rms_eps", 1e-6)


class Mamba2ForCausalLM(MambaForCausalLM):
    """Mamba-2 (SSD) stack: scalar decay per head, grouped B/C, x/B/C
    convolved together, gated RMSNorm before out_proj.

    Reference: vllm/model_executor/models/mamba2.py on
    layers/mamba/mamba_mixer2.py (chunked-SSD CUDA kernels). Here the
    recurrence is ops/ssd.py: two Pallas kernels (one token a step, and
    the chunked form) that update the layer-stacked state rows in
    place, with no state per token; the conv splits into x and B/C
    halves (depthwise, so two convs == one) to keep x head-sharded and
    B/C replicated under TP, where the kernels run per rank on its
    heads.
    """

    @classmethod
    def arch_config_source(cls, hf):
        src = MambaForCausalLM.arch_config_source(hf)
        src.tie_word_embeddings = getattr(hf, "tie_word_embeddings",
                                          False)
        return src

    def chunk_form_tokens(self, q_lens, num_tokens: int) -> dict:
        """Tokens of a step (``q_lens`` its segments' lengths, in a
        program of ``num_tokens``) that the layers' state kernel takes in
        the chunked form, under the kernel family's name: what the
        runner's ``vdt:step_ssd_chunk_tokens`` counts."""
        return {"ssd": chunk_form_tokens(q_lens, num_tokens,
                                         self.cfg.ssd_chunk_size)}

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.stateful = True
        arch.ssm_state_size = hf.state_size
        arch.conv_kernel = hf.conv_kernel
        arch.d_inner = arch.intermediate_size
        arch.num_ssm_heads = hf.num_heads
        arch.ssm_head_dim = getattr(hf, "head_dim",
                                    arch.d_inner // hf.num_heads)
        arch.n_groups = getattr(hf, "n_groups", 1)
        arch.time_step_limit = tuple(
            getattr(hf, "time_step_limit", (0.0, float("inf"))))
        arch.ssd_chunk_size = int(getattr(hf, "chunk_size", 256))
        arch.use_conv_bias = bool(getattr(hf, "use_conv_bias", True))
        arch.use_bias = bool(getattr(hf, "use_bias", False))
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        c = self.cfg
        layer = {
            "norm": P(None, None),
            "gated_norm": P(None, MODEL_AXIS),
            "in_gate": P(None, None, MODEL_AXIS),
            "in_x": P(None, None, MODEL_AXIS),
            "in_bc": P(None, None, None),
            "in_dt": P(None, None, MODEL_AXIS),
            "conv_x_w": P(None, None, MODEL_AXIS),
            "conv_bc_w": P(None, None, None),
            "dt_bias": P(None, MODEL_AXIS),
            "A_log": P(None, MODEL_AXIS),
            "D": P(None, MODEL_AXIS),
            "out_proj": P(None, MODEL_AXIS, None),
        }
        if c.use_conv_bias:
            layer["conv_x_b"] = P(None, MODEL_AXIS)
            layer["conv_bc_b"] = P(None, None)
        if c.use_bias:
            layer["in_b"] = P(None, None)
            layer["out_b"] = P(None, None)
        return {
            "embed": P(None, None),
            "layers": layer,
            "final_ln": P(None, ),
            "lm_head": P(None, MODEL_AXIS),
        }

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        Di, N, K = c.d_inner, c.ssm_state_size, c.conv_kernel
        Hm, G = c.num_ssm_heads, c.n_groups
        keys = iter(jax.random.split(rng, 10))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = {
            "norm": jnp.ones((L, H), c.dtype),
            "gated_norm": jnp.ones((L, Di), c.dtype),
            "in_gate": norm(next(keys), (L, H, Di)),
            "in_x": norm(next(keys), (L, H, Di)),
            "in_bc": norm(next(keys), (L, H, 2 * G * N)),
            "in_dt": norm(next(keys), (L, H, Hm)),
            "conv_x_w": norm(next(keys), (L, K, Di)),
            "conv_bc_w": norm(next(keys), (L, K, 2 * G * N)),
            "dt_bias": jnp.zeros((L, Hm), jnp.float32),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, Hm + 1, dtype=jnp.float32)),
                (L, Hm)),
            "D": jnp.ones((L, Hm), jnp.float32),
            "out_proj": norm(next(keys), (L, Di, H)),
        }
        if c.use_conv_bias:
            layers["conv_x_b"] = jnp.zeros((L, Di), c.dtype)
            layers["conv_bc_b"] = jnp.zeros((L, 2 * G * N), c.dtype)
        if c.use_bias:
            layers["in_b"] = jnp.zeros((L, 2 * Di + 2 * G * N + Hm),
                                       c.dtype)
            layers["out_b"] = jnp.zeros((L, H), c.dtype)
        embed = norm(next(keys), (c.vocab_size, H))
        return {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.ones((H, ), c.dtype),
            "lm_head": (embed.T if c.tie_word_embeddings else norm(
                next(keys), (H, c.vocab_size))),
        }

    def params_from_hf_state_dict(self, tensors: dict,
                                  prefix: str = "backbone") -> dict:
        c = self.cfg
        Di = c.d_inner
        GN2 = 2 * c.n_groups * c.ssm_state_size
        t, stack = self._hf_stackers(tensors)
        mx = prefix + ".layers.{}.mixer."
        # in_proj rows: [gate(Di), x(Di), B/C(2GN), dt(Hm)] (d_mlp = 0
        # for the published Mamba-2 checkpoints).
        layers = {
            "norm":
            stack(prefix + ".layers.{}.norm.weight",
                  lambda a: a).astype(c.dtype),
            "gated_norm":
            stack(mx + "norm.weight", lambda a: a).astype(c.dtype),
            "in_gate":
            stack(mx + "in_proj.weight",
                  lambda a: a[:Di].T).astype(c.dtype),
            "in_x":
            stack(mx + "in_proj.weight",
                  lambda a: a[Di:2 * Di].T).astype(c.dtype),
            "in_bc":
            stack(mx + "in_proj.weight",
                  lambda a: a[2 * Di:2 * Di + GN2].T).astype(c.dtype),
            "in_dt":
            stack(mx + "in_proj.weight",
                  lambda a: a[2 * Di + GN2:].T).astype(c.dtype),
            "conv_x_w":
            stack(mx + "conv1d.weight",
                  lambda a: a[:Di, 0, :].T).astype(c.dtype),
            "conv_bc_w":
            stack(mx + "conv1d.weight",
                  lambda a: a[Di:, 0, :].T).astype(c.dtype),
            "dt_bias":
            stack(mx + "dt_bias", lambda a: a).astype(jnp.float32),
            "A_log":
            stack(mx + "A_log", lambda a: a).astype(jnp.float32),
            "D":
            stack(mx + "D", lambda a: a).astype(jnp.float32),
            "out_proj":
            stack(mx + "out_proj.weight", lambda a: a.T).astype(c.dtype),
        }
        if c.use_conv_bias:
            layers["conv_x_b"] = stack(mx + "conv1d.bias",
                                       lambda a: a[:Di]).astype(c.dtype)
            layers["conv_bc_b"] = stack(mx + "conv1d.bias",
                                        lambda a: a[Di:]).astype(c.dtype)
        if c.use_bias:
            layers["in_b"] = stack(mx + "in_proj.bias",
                                   lambda a: a).astype(c.dtype)
            layers["out_b"] = stack(mx + "out_proj.bias",
                                    lambda a: a).astype(c.dtype)
        return self._hf_tail(tensors, layers, prefix)

    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        return {
            "conv": P(None, None, None, MODEL_AXIS, None),
            "conv_bc": P(None, None, None, None, None),
            "ssm": P(None, None, None, MODEL_AXIS),
        }

    def _state_shapes(self, depth: int) -> dict:
        c = self.cfg
        S = (c.state_slots or 256) + 1
        GN2 = 2 * c.n_groups * c.ssm_state_size
        return {
            # x and B/C carry separate conv states (their convs split),
            # each input in tiles of 128 lanes (ops/conv1d.py).
            "conv": (rows_shape(depth, S, c.conv_kernel, c.d_inner),
                     c.dtype),
            "conv_bc": (rows_shape(depth, S, c.conv_kernel, GN2), c.dtype),
            # State-major (ops/ssd.py): every head's channels on the
            # lanes, so d_inner shards as it does everywhere else.
            "ssm": ((depth, S, c.ssm_state_size, c.d_inner), jnp.float32),
        }

    def state_row_bytes(self) -> int:
        """One sequence's recurrent and convolution state, all layers."""
        return sum(int(np.prod(shape[2:])) * shape[0]
                   * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    def _mixer(self, lp: dict, x: jax.Array, caches: dict, layer, seg,
               conv_entries: dict, decode_only: bool = False):
        """The mixer of Mamba layer ``layer`` (a traced index into the
        layer-stacked state rows ``caches``: ``conv``, ``conv_bc``,
        ``ssm``) on the normed input; ``conv_entries`` the step's
        ``ops/conv1d.step_entries``. Returns (out, caches)."""
        c = self.cfg
        Hm, Pd, N, G = (c.num_ssm_heads, c.ssm_head_dim,
                        c.ssm_state_size, c.n_groups)
        f32 = jnp.float32
        gate = x @ lp["in_gate"]
        xin = x @ lp["in_x"]
        bc = x @ lp["in_bc"]
        dt_r = x @ lp["in_dt"]  # [T, Hm]
        if c.use_bias:
            b = lp["in_b"]
            Di = c.d_inner
            gate = gate + b[:Di]
            xin = xin + b[Di:2 * Di]
            bc = bc + b[2 * Di:2 * Di + 2 * G * N]
            dt_r = dt_r + b[2 * Di + 2 * G * N:]
        caches = dict(caches)
        layer = jnp.reshape(layer, (1, )).astype(jnp.int32)

        def conv(name, v, w, bias):
            # Accumulated in float32 over the inputs the row carries in
            # the model's dtype, the rows replaced where they lie.
            y, caches[name] = conv_on_mesh(
                v, w, bias, caches[name], layer, seg, conv_entries,
                sharded=name == "conv")
            return jax.nn.silu(y)

        xc = conv("conv", xin, lp["conv_x_w"], lp.get("conv_x_b"))
        bcc = conv("conv_bc", bc, lp["conv_bc_w"], lp.get("conv_bc_b"))
        B = bcc[:, :G * N].reshape(-1, G, N)
        C = bcc[:, G * N:].reshape(-1, G, N)
        dt = _softplus(dt_r.astype(f32) + lp["dt_bias"])
        lo, hi = c.time_step_limit
        if lo > 0.0 or hi != float("inf"):
            dt = jnp.clip(dt, lo, hi)
        A = -jnp.exp(lp["A_log"])  # [Hm]
        y, caches["ssm"] = ssd_on_mesh(
            xc.astype(c.dtype).reshape(-1, Hm, Pd), dt, A, B, C, lp["D"],
            caches["ssm"], layer, seg, c.ssd_chunk_size, decode_only)
        y = y.reshape(-1, Hm * Pd)
        # Gated RMSNorm (norm(y * silu(gate)) * weight), f32 like HF.
        y = y * jax.nn.silu(gate.astype(f32))
        y = rms_norm(y, lp["gated_norm"].astype(f32), c.rms_norm_eps)
        out = y.astype(c.dtype) @ lp["out_proj"]
        if c.use_bias:
            out = out + lp["out_b"]
        return out, caches

    def run_layers(
        self,
        layer_params: dict,
        kv_caches: dict,
        hidden: jax.Array,
        batch,
        first_layer: int = 0,
    ) -> tuple[jax.Array, dict]:
        c = self.cfg
        seg = build_segment_info(batch, kv_caches["ssm"].shape[1] - 1)
        decode_only = getattr(batch, "max_q", 0) == 1
        entries = step_entries(seg, hidden.shape[0], c.conv_kernel,
                               decode_only)
        num_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        layer_ids = jnp.arange(num_layers, dtype=jnp.int32)

        def layer_body(carry, xs):
            h, caches = carry
            lp, li = xs
            x = rms_norm(h, lp["norm"], c.rms_norm_eps)
            out, caches = self._mixer(lp, x, caches, li, seg, entries,
                                      decode_only)
            return (h + out, caches), None

        (hidden, caches), _ = jax.lax.scan(
            layer_body, (hidden, dict(kv_caches)),
            (layer_params, layer_ids))
        return hidden, caches
