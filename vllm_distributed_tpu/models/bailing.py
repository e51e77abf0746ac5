"""Ling-3.0-flash (inclusionAI): a hybrid of gated delta-rule linear
attention (Kimi Delta Attention, KDA; arXiv:2510.26692) and DeepSeek-V2
latent attention, over a DeepSeek-V3 expert layer.

Pre-norm residual block, ``h += Attn_i(norm(h)); h += MLP_i(norm(h))``.
Layer ``i`` attends by latent attention where ``(i + 1) %
layer_group_size == 0`` and by KDA elsewhere; its MLP is dense for
``i < first_k_dense_replace`` and sparse after.

* **KDA** (``ops/kda.py`` has the recurrence and its two kernels):
  ``q, k, v = SiLU(conv4(x W))`` through causal depthwise convolutions
  (``ops/mamba.causal_conv1d_ragged``: the same carried-input state
  rows as the Mamba mixers), ``q`` and ``k`` divided by their 2-norm
  over the head, ``q`` scaled by ``Dk ** -0.5``; a decay per key
  channel ``g = kda_lower_bound * sigmoid(exp(A) * (x W_f + b_f))``;
  ``b = sigmoid(x W_b)`` a head; the output through a learned RMS norm
  over each head's channels and a gate of one scalar a head,
  ``sigmoid(x W_g)``, before ``W_o``. No rotation.
* **Latent attention**: ``DeepseekV2ForCausalLM.latent_attention_block``
  with ``q_lora_rank`` null, in the absorbed form over the latent pages,
  each head's output times the same head-wise gate.
* **Sparse MLP**: DeepSeek-V3's router, dispatch and shared expert,
  inherited unchanged, ``expert_share`` included (models/deepseek.py).

Two kinds of cache for one request: latent pages for the latent layers
(``c``: ``[Lm, pages, PS, 640]``) and, for the KDA layers, a recurrent
state row (``kda``: ``[Lk, S + 1, N, Dk, Dv]`` float32) and a
convolution row (``conv``: the last three inputs of q, k and v) that do
not grow with the context. The state rows are the state cache's
(core/state_cache.py): a snapshot restores them together with the
prefix's latent pages.

The registry name ``BailingMoeV3ForCausalLM`` follows the family's
convention (``BailingMoeV2ForCausalLM`` is Ling 2.0's); the published
configuration names neither an architecture nor a ``model_type``. The
checkpoint's tensor names are not published where this was written, so
only seeded weights load (``load_format="dummy"``). The
multi-token-prediction module is a draft head outside the next-token
logits and is not loaded.

Not wired (rejected at load): tensor parallelism (the state kernels run
unsharded), and what the stateful, latent and share refusals of
``models/loader.py`` list.
"""

from __future__ import annotations

import types
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    compute_rope_cos_sin_pairwise, rms_norm)
from vllm_distributed_tpu.models.deepseek import (_DENSE_KEYS, _MOE_KEYS,
                                                  DeepseekV3ForCausalLM)
from vllm_distributed_tpu.models.llama import (TOKEN_AXIS,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.ops import kda
from vllm_distributed_tpu.ops.conv1d import (causal_conv1d_rows, rows_shape,
                                             step_entries)
from vllm_distributed_tpu.ops.mamba import build_segment_info
from vllm_distributed_tpu.ops.mla import latent_storage_dim

_LATENT_KEYS = ("wq", "kv_a", "kv_a_ln", "w_uk", "w_uv", "wo", "w_hg")


class BailingMoeV3ForCausalLM(DeepseekV3ForCausalLM):

    STATEFUL = True
    # A snapshot of the state rows resumes a request only together with
    # the latent pages of its prefix (core/state_cache.py).
    STATE_ONLY = False

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        # The expert layer and the latent attention under DeepSeek's
        # names for the keys this family spells differently.
        moe_width = hf.moe_intermediate_size
        shared = getattr(hf, "moe_shared_expert_intermediate_size", 0) or 0
        if getattr(hf, "score_function", "sigmoid") != "sigmoid":
            raise ValueError("only score_function 'sigmoid' is wired for "
                             "the Bailing family")
        if not getattr(hf, "moe_router_enable_expert_bias", True):
            raise ValueError("moe_router_enable_expert_bias false is not "
                             "wired for the Bailing family")
        view = types.SimpleNamespace(**{
            **hf.to_dict(),
            "n_routed_experts": hf.num_experts,
            "n_shared_experts": shared // moe_width,
            "q_lora_rank": getattr(hf, "q_lora_rank", None),
        })
        super().configure_arch(arch, view)
        arch.stateful = True
        arch.kda = True
        arch.layer_group_size = int(hf.layer_group_size)
        arch.kda_head_dim = int(hf.head_dim)
        arch.conv_kernel = int(hf.short_conv_kernel_size)
        arch.kda_lower_bound = float(hf.kda_lower_bound)
        if not getattr(hf, "kda_safe_gate", False):
            raise ValueError("KDA without kda_safe_gate is not wired: the "
                             "chunked form needs the bounded decay")
        if -arch.kda_lower_bound * kda.SUB > 80.0:
            raise ValueError(
                f"kda_lower_bound {arch.kda_lower_bound} lets a "
                f"{kda.SUB}-token sub-chunk decay past float32")
        for key, want in (("use_qk_norm", True), ("linear_silu", True),
                          ("no_kda_lora", True), ("group_norm_size", 1),
                          ("gated_attention_proj_granularity_type",
                           "head_wise")):
            if getattr(hf, key, want) != want:
                raise ValueError(f"{key}={getattr(hf, key)!r} is not "
                                 f"wired for the Bailing family")
        for key in ("use_nGPT", "scale_router_input", "value_norm",
                    "up_proj_norm", "use_mla_nope", "use_kda_lora",
                    "num_kv_heads_for_linear_attn"):
            if getattr(hf, key, None):
                raise ValueError(f"{key}={getattr(hf, key)!r} is not "
                                 f"wired for the Bailing family")
        kept = range(arch.num_layers)
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = getattr(hf, key, None) or []
            if any(limits[i] for i in kept if i < len(limits)):
                raise ValueError(f"{key}: a clamped SwiGLU is not wired")
        # Names the cost model reads for the recurrent layers.
        arch.d_inner = arch.num_q_heads * arch.kda_head_dim
        arch.ssm_state_size = arch.kda_head_dim
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    # ------------------------------------------------------------------
    # Layer pattern
    # ------------------------------------------------------------------
    def _is_latent(self, i: int) -> bool:
        return (i + 1) % self.cfg.layer_group_size == 0

    @property
    def _attn_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers) if self._is_latent(i)]

    @property
    def _kda_layers(self) -> list:
        return [i for i in range(self.cfg.num_layers)
                if not self._is_latent(i)]

    # ------------------------------------------------------------------
    # Parameters: common norms over all layers, one stacked subtree per
    # attention kind ("k_" the KDA layers', DeepSeek's names the latent
    # layers') and per MLP kind (DeepSeek's own).
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        c = self.cfg
        if c.max_loras:
            raise ValueError("LoRA is not supported for the Bailing "
                             "family yet")
        specs = DeepseekV3ForCausalLM.param_specs(self)
        rep2, rep3 = P(None, None), P(None, None, None)
        layer = {k: v for k, v in specs["layers"].items()
                 if k in _DENSE_KEYS or k in _MOE_KEYS
                 or k in ("input_ln", "post_ln")}
        layer.update({
            "wq": rep3, "kv_a": rep3, "kv_a_ln": rep2,
            "w_uk": P(None, None, None, None),
            "w_uv": P(None, None, None, None), "wo": rep3, "w_hg": rep3,
            "k_wqkv": rep3, "k_conv": rep3, "k_wf": rep3, "k_bf": rep2,
            "k_A": rep2, "k_wb": rep3, "k_wg": rep3, "k_norm": rep2,
            "k_wo": rep3,
        })
        specs["layers"] = layer
        return specs

    def slice_layer_params(self, layers: dict, start: int, end: int):
        raise ValueError(
            "pipeline parallelism over the hybrid KDA/latent stack is "
            "not wired (per-kind stack depths differ per stage)")

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        k_latent, k_kda = jax.random.split(jax.random.fold_in(rng, 1))
        # The expert layers, the norms, the embedding and the head as
        # DeepSeek-V3 draws them; its attention stack is drawn at the
        # latent layers' depth.
        base = DeepseekV3ForCausalLM.init_params(self, rng, scale)
        lay = {k: v for k, v in base["layers"].items()
               if k in _DENSE_KEYS or k in _MOE_KEYS
               or k in ("input_ln", "post_ln")}
        H, N, D = c.hidden_size, c.num_q_heads, c.kda_head_dim
        Pn, R, V = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        Lkv, K = c.kv_lora_rank, c.conv_kernel
        Lm, Lk = len(self._attn_layers), len(self._kda_layers)
        keys = iter(jax.random.split(k_latent, 8))

        def norm(shape, dtype=None):
            return scale * jax.random.normal(next(keys), shape,
                                             dtype or c.dtype)

        lay.update({
            "wq": norm((Lm, H, N * (Pn + R))),
            "kv_a": norm((Lm, H, Lkv + R)),
            "kv_a_ln": jnp.ones((Lm, Lkv), c.dtype),
            "w_uk": norm((Lm, Lkv, N, Pn)),
            "w_uv": norm((Lm, Lkv, N, V)),
            "wo": norm((Lm, N * V, H)),
            "w_hg": norm((Lm, H, N)),
        })
        keys = iter(jax.random.split(k_kda, 12))
        lay.update({
            "k_wqkv": norm((Lk, H, 3 * N * D)),
            # Taps of order one, as a trained short convolution has:
            # at the projections' scale the four taps would shrink
            # q, k and v fifty-fold before the 2-norm's eps.
            "k_conv": (0.5 * jax.random.normal(
                next(keys), (Lk, K, 3 * N * D), jnp.float32)
            ).astype(c.dtype),
            "k_wf": norm((Lk, H, N * D)),
            # Seeded around -4, not 0: a trained gate decays slowly
            # (sigmoid(-4) x -5 is -0.09 a token, e^-5 a token at 0), and
            # only a state that outlives a few tokens shows the delta
            # rule's correction (benchmarks/references/bailing.py).
            "k_bf": -4.0 + jax.random.normal(next(keys), (Lk, N * D),
                                             jnp.float32),
            "k_A": 0.25 * jax.random.normal(next(keys), (Lk, N),
                                            jnp.float32),
            "k_wb": norm((Lk, H, N)),
            "k_wg": norm((Lk, H, N)),
            "k_norm": jnp.ones((Lk, D), c.dtype),
            "k_wo": norm((Lk, N * D, H)),
        })
        return {**base, "layers": lay}

    def params_from_hf_state_dict(self, tensors: dict) -> dict:
        raise NotImplementedError(
            "the Bailing (Ling-3.0) checkpoint's tensor names are not "
            "published where this loader was written; only seeded "
            "weights load (load_format='dummy')")

    # ------------------------------------------------------------------
    # Caches: latent pages for the latent layers, state rows for KDA
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        return {"c": P(None, TOKEN_AXIS, None, None),
                "kda": P(None, None, None, None, None),
                "conv": P(None, None, None, None, None)}

    def _state_shapes(self, depth: int) -> dict:
        c = self.cfg
        S = (c.state_slots or 256) + 1       # + the dump row
        N, D = c.num_q_heads, c.kda_head_dim
        return {
            "kda": ((depth, S, N, D, D), jnp.float32),
            "conv": (rows_shape(depth, S, c.conv_kernel, 3 * N * D),
                     c.dtype),
        }

    def state_shapes(self) -> dict:
        """Snapshot-pool geometry (core/state_cache.py): the KDA
        layers' rows; the latent pages re-enter through the prefix
        cache."""
        return self._state_shapes(len(self._kda_layers))

    def state_row_bytes(self) -> int:
        """One sequence's recurrent and convolution state, all layers."""
        return sum(int(np.prod(shape[2:])) * shape[0]
                   * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        c = self.cfg
        assert num_layers is None or num_layers == c.num_layers, \
            "the hybrid stack is not sliceable per stage (no PP)"
        Cs = latent_storage_dim(c.kv_lora_rank, c.qk_rope_head_dim)
        caches = {"c": jnp.zeros(
            (len(self._attn_layers), num_pages, page_size, Cs),
            cache_dtype or c.dtype)}
        caches.update({name: jnp.zeros(shape, dtype) for name, (
            shape, dtype) in self.state_shapes().items()})
        return caches

    def kv_cache_page_bytes(self, page_size: int) -> int:
        c = self.cfg
        return (len(self._attn_layers) * page_size
                * latent_storage_dim(c.kv_lora_rank, c.qk_rope_head_dim)
                * jnp.dtype(c.dtype).itemsize)

    def fixed_cache_bytes(self) -> int:
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def _head_gate(self, x: jax.Array, w: jax.Array) -> jax.Array:
        return jax.nn.sigmoid((x @ w).astype(jnp.float32))      # [T, N]

    def kda_block(self, lp: dict, x: jax.Array, caches: dict, j: int,
                  batch, seg, conv_entries: dict) -> tuple[jax.Array, dict]:
        """KDA layer ``j`` (of the KDA stack) on the normed input;
        ``conv_entries`` the step's ``ops/conv1d.step_entries``."""
        c = self.cfg
        T = x.shape[0]
        N, D = c.num_q_heads, c.kda_head_dim
        f32 = jnp.float32
        layer = jnp.full((1, ), j, jnp.int32)
        # The convolutions accumulate in float32 over the bf16 inputs
        # the state row carries, the rows replaced where they lie.
        qkv, conv = causal_conv1d_rows(
            x @ lp["k_wqkv"], lp["k_conv"], None, caches["conv"], layer,
            seg, conv_entries)
        qkv = jax.nn.silu(qkv).reshape(T, 3, N, D)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]

        def unit(y):
            return y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)

        gate_in = (x @ lp["k_wf"]).astype(f32) + lp["k_bf"]
        g = c.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(lp["k_A"])[None, :, None] * gate_in.reshape(T, N, D))
        beta = jax.nn.sigmoid((x @ lp["k_wb"]).astype(f32))
        o, state = kda.kda_ragged(
            unit(q) * D ** -0.5, unit(k), v, g, beta, caches["kda"], layer,
            seg, decode_only=getattr(batch, "max_q", 0) == 1)
        o = rms_norm(o, lp["k_norm"], c.rms_norm_eps)
        o = o * self._head_gate(x, lp["k_wg"])[:, :, None]
        caches = {**caches, "kda": state, "conv": conv}
        return o.reshape(T, N * D).astype(c.dtype) @ lp["k_wo"], caches

    def run_layers(self, layer_params: dict, kv_caches: dict,
                   hidden: jax.Array, batch, first_layer: int = 0,
                   ) -> tuple[jax.Array, dict]:
        c = self.cfg
        seg = build_segment_info(batch, kv_caches["kda"].shape[1] - 1)
        conv_entries = step_entries(seg, hidden.shape[0], c.conv_kernel,
                                    getattr(batch, "max_q", 0) == 1)
        cos, sin = compute_rope_cos_sin_pairwise(
            batch.positions, c.qk_rope_head_dim, c.rope_theta,
            c.rope_scaling, c.max_position_embeddings)

        def sub(keys, j):
            # In the tree's own order: a set's order changes from process
            # to process, and with it the program and its cache key.
            return {k: v[j] for k, v in layer_params.items() if k in keys}

        kda_keys = [k for k in layer_params if k.startswith("k_")]
        h, caches = hidden, dict(kv_caches)
        ai = ki = 0
        for i in range(c.num_layers):
            x = rms_norm(h, layer_params["input_ln"][i], c.rms_norm_eps)
            if self._is_latent(i):
                lp = sub(_LATENT_KEYS, ai)
                o, caches = self.latent_attention_block(
                    lp, x, caches, jnp.full((1, ), ai, jnp.int32), batch,
                    cos, sin, head_gate=self._head_gate(x, lp["w_hg"]))
                ai += 1
            else:
                o, caches = self.kda_block(sub(kda_keys, ki), x, caches,
                                           ki, batch, seg, conv_entries)
                ki += 1
            h = h + o
            x2 = rms_norm(h, layer_params["post_ln"][i], c.rms_norm_eps)
            if i < self._n_dense:
                h = h + LlamaForCausalLM.mlp_block(
                    self, sub(_DENSE_KEYS, i), x2)
            else:
                h = h + self.mlp_block(sub(_MOE_KEYS, i - self._n_dense),
                                       x2)
        return h, caches
