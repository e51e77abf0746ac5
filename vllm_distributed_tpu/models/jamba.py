"""Jamba: hybrid Mamba-1 / attention decoder with interleaved MoE.

Reference surface: vllm/model_executor/models/jamba.py (layer pattern
from attn_layer_period/offset + expert_layer_period/offset, Mamba mixer
with learned dt/B/C layernorms, NoPE attention, Mixtral-style MoE
without top-k renormalization), with hybrid KV groups sizing attention
pages separately from mamba state
(vllm/v1/kv_cache_interface.py FullAttentionSpec + MambaSpec groups).

TPU design: this is the framework's hybrid-cache-group model — the
cache dict carries BOTH paged K/V stacked over the attention layers
only ([La, pages, ...]: kv_cache_page_bytes charges La, not L — a
4x page-memory saving at Jamba's 1:7 attention:mamba ratio) and
fixed-size per-request conv/ssm state rows stacked over the mamba
layers ([Lm, S, ...], charged via fixed_cache_bytes). The mamba mixers
run the segmented associative scan of ops/mamba.py on the flat ragged
batch; attention layers are plain paged attention without rotary
embeddings (Jamba uses none). MoE layers reuse the Mixtral grouped-GEMM
dispatch verbatim (models/mixtral.py moe_dispatch).

Layers are heterogeneous, so run_layers walks them as an unrolled
Python loop over the four block kinds (attn/mamba x dense/moe), each
kind's parameters stacked separately; at Jamba's scale (32 layers) the
unroll compiles once per token bucket like every other model.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import rms_norm, swiglu
from vllm_distributed_tpu.models.llama import MODEL_AXIS, TOKEN_AXIS
from vllm_distributed_tpu.models.mamba import MambaForCausalLM
from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
from vllm_distributed_tpu.ops.attention import (paged_attention,
                                                storage_head_dim,
                                                write_kv_cache)
from vllm_distributed_tpu.ops.mamba import build_segment_info


class JambaForCausalLM(MixtralForCausalLM):
    """Hybrid attention/Mamba stack with periodic MoE FFNs."""

    QUANT_TARGETS = ()
    LORA_TARGETS = ()
    # The serving layout's per-layer buffers under this tree's names
    # (models/mixtral.py); run_layers' sub() indexes them by position.
    _PER_LAYER = ("e_w_gate", "e_w_up", "e_w_down")
    STATEFUL = True
    # Hybrid stack: state restores must re-enter coherently with the
    # attention layers' cached KV pages (core/state_cache.py requires
    # every prefix page resident for a hit).
    STATE_ONLY = False

    def quantize_params(self, params: dict) -> dict:
        if self.cfg.quantization:
            raise ValueError(
                "weight quantization for hybrid SSM stacks is not "
                "wired yet; drop --quantization for Jamba")
        return params

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.stateful = True
        # Mamba mixer geometry (names shared with models/mamba.py so
        # MambaForCausalLM._mixer runs unchanged).
        arch.ssm_state_size = hf.mamba_d_state
        arch.conv_kernel = hf.mamba_d_conv
        arch.d_inner = hf.mamba_expand * hf.hidden_size
        arch.dt_rank = (hf.mamba_dt_rank if hf.mamba_dt_rank != "auto"
                        else -(-hf.hidden_size // 16))
        arch.use_conv_bias = bool(getattr(hf, "mamba_conv_bias", True))
        if getattr(hf, "mamba_proj_bias", False):
            raise ValueError(
                "Jamba mamba_proj_bias checkpoints are not supported "
                "(no published model sets it)")
        arch.use_bias = False
        # Layer pattern.
        arch.attn_period = hf.attn_layer_period
        arch.attn_offset = hf.attn_layer_offset
        arch.expert_period = hf.expert_layer_period
        arch.expert_offset = hf.expert_layer_offset
        n_exp = getattr(hf, "num_experts", 1)
        arch.num_experts = n_exp if n_exp > 1 else 0
        arch.num_experts_per_tok = getattr(hf, "num_experts_per_tok", 2)
        arch.norm_topk_prob = False  # Jamba does not renormalize top-k
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    # ------------------------------------------------------------------
    # Layer pattern helpers (static python ints — part of the compiled
    # program structure, like the window segments of models/llama.py)
    # ------------------------------------------------------------------
    def _is_attn(self, i: int) -> bool:
        return i % self.cfg.attn_period == self.cfg.attn_offset

    def _is_moe(self, i: int) -> bool:
        return (self.cfg.num_experts > 0
                and i % self.cfg.expert_period == self.cfg.expert_offset)

    @property
    def _attn_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers) if self._is_attn(i)]

    @property
    def _mamba_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers)
                if not self._is_attn(i)]

    @property
    def _moe_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers) if self._is_moe(i)]

    @property
    def _dense_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers)
                if not self._is_moe(i)]

    # ------------------------------------------------------------------
    # Parameters: one stacked subtree per block kind, flat "a_/m_/d_/e_"
    # prefixed keys so the loader's per-key placement applies unchanged.
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        c = self.cfg
        col = P(None, None, MODEL_AXIS)
        row = P(None, MODEL_AXIS, None)
        layer = {
            # attention stack [La, ...]
            "a_ln": P(None, None),
            "a_wq": col, "a_wk": col, "a_wv": col, "a_wo": row,
            # mamba stack [Lm, ...]
            "m_norm": P(None, None),
            "m_in_x": col, "m_in_z": col,
            "m_conv_w": col,
            "m_x_proj": row,
            "m_dt_w": col, "m_dt_b": P(None, MODEL_AXIS),
            "m_dt_ln": P(None, None), "m_b_ln": P(None, None),
            "m_c_ln": P(None, None),
            "m_A_log": P(None, MODEL_AXIS, None),
            "m_D": P(None, MODEL_AXIS),
            "m_out_proj": row,
            # dense-FFN stack [Ld, ...]
            "d_pre_ln": P(None, None),
            "d_gate": col, "d_up": col, "d_down": row,
        }
        if c.use_conv_bias:
            layer["m_conv_b"] = P(None, MODEL_AXIS)
        if c.num_experts:
            ffn = P(None, None, None, MODEL_AXIS)
            layer.update({
                "e_pre_ln": P(None, None),
                "e_router": P(None, None, None),
                "e_w_gate": ffn, "e_w_up": ffn,
                "e_w_down": P(None, None, MODEL_AXIS, None),
            })
        return {
            "embed": P(None, None),
            "layers": layer,
            "final_ln": P(None, ),
            "lm_head": P(None, MODEL_AXIS),
        }

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        H, I = c.hidden_size, c.intermediate_size
        Di, N, K, R = c.d_inner, c.ssm_state_size, c.conv_kernel, c.dt_rank
        La, Lm = len(self._attn_layers), len(self._mamba_layers)
        Ld, Le = len(self._dense_layers), len(self._moe_layers)
        Dq = c.num_q_heads * c.head_dim
        Dkv = c.total_kv_heads * c.head_dim
        keys = iter(jax.random.split(rng, 20))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = {
            "a_ln": jnp.ones((La, H), c.dtype),
            "a_wq": norm(next(keys), (La, H, Dq)),
            "a_wk": norm(next(keys), (La, H, Dkv)),
            "a_wv": norm(next(keys), (La, H, Dkv)),
            "a_wo": norm(next(keys), (La, Dq, H)),
            "m_norm": jnp.ones((Lm, H), c.dtype),
            "m_in_x": norm(next(keys), (Lm, H, Di)),
            "m_in_z": norm(next(keys), (Lm, H, Di)),
            "m_conv_w": norm(next(keys), (Lm, K, Di)),
            "m_x_proj": norm(next(keys), (Lm, Di, R + 2 * N)),
            "m_dt_w": norm(next(keys), (Lm, R, Di)),
            "m_dt_b": jnp.zeros((Lm, Di), jnp.float32),
            "m_dt_ln": jnp.ones((Lm, R), c.dtype),
            "m_b_ln": jnp.ones((Lm, N), c.dtype),
            "m_c_ln": jnp.ones((Lm, N), c.dtype),
            "m_A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)),
                (Lm, Di, N)) * jnp.ones((Lm, Di, 1), jnp.float32),
            "m_D": jnp.ones((Lm, Di), jnp.float32),
            "m_out_proj": norm(next(keys), (Lm, Di, H)),
            "d_pre_ln": jnp.ones((Ld, H), c.dtype),
            "d_gate": norm(next(keys), (Ld, H, I)),
            "d_up": norm(next(keys), (Ld, H, I)),
            "d_down": norm(next(keys), (Ld, I, H)),
        }
        if c.use_conv_bias:
            layers["m_conv_b"] = jnp.zeros((Lm, Di), c.dtype)
        if c.num_experts:
            E = c.num_experts
            layers.update({
                "e_pre_ln": jnp.ones((Le, H), c.dtype),
                "e_router": norm(next(keys), (Le, H, E)),
                "e_w_gate": norm(next(keys), (Le, E, H, I)),
                "e_w_up": norm(next(keys), (Le, E, H, I)),
                "e_w_down": norm(next(keys), (Le, E, I, H)),
            })
        embed = norm(next(keys), (c.vocab_size, H))
        return {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.ones((H, ), c.dtype),
            "lm_head": (embed.T if c.tie_word_embeddings else norm(
                next(keys), (H, c.vocab_size))),
        }

    def params_from_hf_state_dict(self, tensors: dict,
                                  prefix: str = "model") -> dict:
        c = self.cfg
        Di = c.d_inner

        def t(name):
            return np.asarray(tensors[name])

        def stack(ids, fmt, f=lambda a: a, dtype=None):
            return jnp.asarray(np.stack(
                [f(t(fmt.format(i))) for i in ids])).astype(
                    dtype or c.dtype)

        A, M = self._attn_layers, self._mamba_layers
        D, E = self._dense_layers, self._moe_layers
        ly = prefix + ".layers.{}."
        layers = {
            "a_ln": stack(A, ly + "input_layernorm.weight"),
            "a_wq": stack(A, ly + "self_attn.q_proj.weight",
                          lambda a: a.T),
            "a_wk": stack(A, ly + "self_attn.k_proj.weight",
                          lambda a: a.T),
            "a_wv": stack(A, ly + "self_attn.v_proj.weight",
                          lambda a: a.T),
            "a_wo": stack(A, ly + "self_attn.o_proj.weight",
                          lambda a: a.T),
            "m_norm": stack(M, ly + "input_layernorm.weight"),
            "m_in_x": stack(M, ly + "mamba.in_proj.weight",
                            lambda a: a[:Di].T),
            "m_in_z": stack(M, ly + "mamba.in_proj.weight",
                            lambda a: a[Di:].T),
            "m_conv_w": stack(M, ly + "mamba.conv1d.weight",
                              lambda a: a[:, 0, :].T),
            "m_x_proj": stack(M, ly + "mamba.x_proj.weight",
                              lambda a: a.T),
            "m_dt_w": stack(M, ly + "mamba.dt_proj.weight",
                            lambda a: a.T),
            "m_dt_b": stack(M, ly + "mamba.dt_proj.bias",
                            dtype=jnp.float32),
            "m_dt_ln": stack(M, ly + "mamba.dt_layernorm.weight"),
            "m_b_ln": stack(M, ly + "mamba.b_layernorm.weight"),
            "m_c_ln": stack(M, ly + "mamba.c_layernorm.weight"),
            "m_A_log": stack(M, ly + "mamba.A_log", dtype=jnp.float32),
            "m_D": stack(M, ly + "mamba.D", dtype=jnp.float32),
            "m_out_proj": stack(M, ly + "mamba.out_proj.weight",
                                lambda a: a.T),
            "d_pre_ln": stack(D, ly + "pre_ff_layernorm.weight"),
            "d_gate": stack(D, ly + "feed_forward.gate_proj.weight",
                            lambda a: a.T),
            "d_up": stack(D, ly + "feed_forward.up_proj.weight",
                          lambda a: a.T),
            "d_down": stack(D, ly + "feed_forward.down_proj.weight",
                            lambda a: a.T),
        }
        if c.use_conv_bias:
            layers["m_conv_b"] = stack(M, ly + "mamba.conv1d.bias")
        if c.num_experts:
            ex = ly + "feed_forward.experts.{}.{}_proj.weight"

            def stack_experts(which):
                return jnp.asarray(np.stack([
                    np.stack([
                        t(ex.format(i, e_i, which)).T
                        for e_i in range(c.num_experts)
                    ]) for i in E
                ])).astype(c.dtype)

            layers.update({
                "e_pre_ln": stack(E, ly + "pre_ff_layernorm.weight"),
                "e_router": stack(E, ly + "feed_forward.router.weight",
                                  lambda a: a.T),
                "e_w_gate": stack_experts("gate"),
                "e_w_up": stack_experts("up"),
                "e_w_down": stack_experts("down"),
            })
        if c.num_kv_head_replicas > 1:
            # KV-head replication for tp > kv_heads (see
            # models/llama.py _maybe_replicate_kv).
            from vllm_distributed_tpu.models.llama import \
                _replicate_kv_heads
            for name in ("a_wk", "a_wv"):
                layers[name] = _replicate_kv_heads(
                    layers[name], c.num_kv_heads, c.num_kv_head_replicas)
        embed = jnp.asarray(t(prefix + ".embed_tokens.weight")).astype(
            c.dtype)
        if c.tie_word_embeddings or "lm_head.weight" not in tensors:
            lm_head = embed.T
        else:
            lm_head = jnp.asarray(t("lm_head.weight")).T.astype(c.dtype)
        return {
            "embed": embed,
            "layers": layers,
            "final_ln": jnp.asarray(
                t(prefix + ".final_layernorm.weight")).astype(c.dtype),
            "lm_head": lm_head,
        }

    # ------------------------------------------------------------------
    # Hybrid cache groups: paged K/V over attention layers + state rows
    # over mamba layers (reference: kv_cache_coordinator grouping,
    # v1/core/kv_cache_coordinator.py).
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        return {
            "k": P(None, TOKEN_AXIS, MODEL_AXIS, None, None),
            "v": P(None, TOKEN_AXIS, MODEL_AXIS, None, None),
            "conv": P(None, None, None, MODEL_AXIS),
            "ssm": P(None, None, MODEL_AXIS, None),
        }

    def _state_shapes(self, depth: int) -> dict:
        """Single source of truth for the mamba-state arrays (same
        contract as models/mamba.py _state_shapes)."""
        c = self.cfg
        S = (c.state_slots or 256) + 1
        return {
            "conv": ((depth, S, c.conv_kernel - 1, c.d_inner), c.dtype),
            "ssm": ((depth, S, c.d_inner, c.ssm_state_size),
                    jnp.float32),
        }

    def state_shapes(self) -> dict:
        """Snapshot-pool geometry (core/state_cache.py): the mamba
        stack's state arrays only — paged K/V re-enters through the
        ordinary prefix cache."""
        return self._state_shapes(len(self._mamba_layers))

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        c = self.cfg
        assert num_layers is None or num_layers == c.num_layers, \
            "hybrid stacks are not sliceable per stage (no PP)"
        La, Lm = len(self._attn_layers), len(self._mamba_layers)
        dtype = cache_dtype or c.dtype
        shape = (La, num_pages, c.total_kv_heads, page_size,
                 storage_head_dim(c.head_dim))
        caches = {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
        }
        caches.update({
            name: jnp.zeros(s, d)
            for name, (s, d) in self._state_shapes(Lm).items()
        })
        return caches

    def kv_cache_page_bytes(self, page_size: int) -> int:
        c = self.cfg
        La = len(self._attn_layers)
        return (2 * La * page_size * c.total_kv_heads *
                storage_head_dim(c.head_dim) *
                jnp.dtype(c.dtype).itemsize)

    def fixed_cache_bytes(self) -> int:
        return sum(
            int(np.prod(shape)) * jnp.dtype(dtype).itemsize
            for shape, dtype in self._state_shapes(
                len(self._mamba_layers)).values())

    def slice_layer_params(self, layers: dict, start: int, end: int):
        raise ValueError(
            "pipeline parallelism over hybrid attention/mamba stacks "
            "is not wired (per-kind stack depths differ per stage)")

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def run_layers(
        self,
        layer_params: dict,
        kv_caches: dict,
        hidden: jax.Array,
        batch,
        first_layer: int = 0,
    ) -> tuple[jax.Array, dict]:
        c = self.cfg
        T = hidden.shape[0]
        seg = build_segment_info(batch, kv_caches["ssm"].shape[1] - 1)
        sm_scale = c.head_dim**-0.5

        def sub(prefix, j):
            return {
                k[len(prefix):]: v[j]
                for k, v in layer_params.items() if k.startswith(prefix)
            }

        h = hidden
        k_all, v_all = kv_caches["k"], kv_caches["v"]
        conv_all, ssm_all = kv_caches["conv"], kv_caches["ssm"]
        ai = mi = di = ei = 0
        for i in range(c.num_layers):
            if self._is_attn(i):
                lp = sub("a_", ai)
                x = rms_norm(h, lp["ln"], c.rms_norm_eps)
                q = (x @ lp["wq"]).reshape(T, c.num_q_heads, c.head_dim)
                k = (x @ lp["wk"]).reshape(T, c.total_kv_heads,
                                           c.head_dim)
                v = (x @ lp["wv"]).reshape(T, c.total_kv_heads,
                                           c.head_dim)
                # NoPE: Jamba attention applies no rotary embedding.
                li = jnp.full((1, ), ai, jnp.int32)
                k_all, v_all = write_kv_cache(k_all, v_all, k, v, batch,
                                              li)
                attn = paged_attention(q, k_all, v_all, batch,
                                       sm_scale=sm_scale, layer=li,
                                       window=0)
                h = h + attn.reshape(T, -1) @ lp["wo"]
                ai += 1
            else:
                lp = sub("m_", mi)
                x = rms_norm(h, lp["norm"], c.rms_norm_eps)
                out, conv_new, ssm_new = MambaForCausalLM._mixer(
                    self, lp, x, conv_all[mi], ssm_all[mi], seg)
                conv_all = conv_all.at[mi].set(conv_new)
                ssm_all = ssm_all.at[mi].set(ssm_new)
                h = h + out
                mi += 1
            if self._is_moe(i):
                # sub() yields exactly the router/w_gate/w_up/w_down
                # keys the Mixtral dispatch reads.
                lp = sub("e_", ei)
                x = rms_norm(h, lp["pre_ln"], c.rms_norm_eps)
                h = h + MixtralForCausalLM.mlp_block(self, lp, x)
                ei += 1
            else:
                lp = sub("d_", di)
                x = rms_norm(h, lp["pre_ln"], c.rms_norm_eps)
                h = h + swiglu(x, lp["gate"], lp["up"], lp["down"])
                di += 1
        return h, {"k": k_all, "v": v_all, "conv": conv_all,
                   "ssm": ssm_all}
