"""Bamba: hybrid Mamba-2 / attention decoder (IBM Bamba family).

Reference surface: vllm/model_executor/models/bamba.py — Mamba-2 (SSD)
mixers on most layers, GQA attention with PARTIAL rotary embeddings on
the layers named by attn_layer_indices, a dense SwiGLU FFN on every
layer, hybrid cache groups sizing attention pages separately from SSM
state.

TPU design mirrors models/jamba.py (per-kind stacked parameter
subtrees) with the Mamba-2 mixer of models/mamba.py (ops/ssd.py's two
kernels on in-place state rows, split x / B-C depthwise convs) and
llama-style partial rotary on the attention layers; the heterogeneous
layer walk is a scan over the periods of the layer pattern
(``run_layers``). models/granite_hybrid.py builds on it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (apply_rope_single,
                                                compute_rope_cos_sin,
                                                rms_norm, swiglu)
from vllm_distributed_tpu.models.jamba import JambaForCausalLM
from vllm_distributed_tpu.models.llama import MODEL_AXIS
from vllm_distributed_tpu.models.mamba import Mamba2ForCausalLM
from vllm_distributed_tpu.ops.attention import (storage_head_dim,
                                                write_kv_and_attend)
from vllm_distributed_tpu.ops.conv1d import step_entries
from vllm_distributed_tpu.ops.mamba import build_segment_info


class BambaForCausalLM(JambaForCausalLM):
    """Hybrid Mamba-2 / partial-rotary-attention stack."""

    chunk_form_tokens = Mamba2ForCausalLM.chunk_form_tokens

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.stateful = True
        # Mamba-2 mixer geometry (names shared with models/mamba.py
        # Mamba2ForCausalLM._mixer).
        arch.ssm_state_size = hf.mamba_d_state
        arch.conv_kernel = hf.mamba_d_conv
        arch.d_inner = hf.mamba_expand * hf.hidden_size
        arch.num_ssm_heads = hf.mamba_n_heads
        arch.ssm_head_dim = getattr(
            hf, "mamba_d_head", arch.d_inner // hf.mamba_n_heads)
        arch.n_groups = getattr(hf, "mamba_n_groups", 1)
        arch.time_step_limit = tuple(
            getattr(hf, "time_step_limit", None)
            or (0.0, float("inf")))
        arch.ssd_chunk_size = int(getattr(hf, "mamba_chunk_size", 256))
        arch.use_conv_bias = bool(getattr(hf, "mamba_conv_bias", True))
        if getattr(hf, "mamba_proj_bias", False):
            raise ValueError(
                "Bamba mamba_proj_bias checkpoints are not supported")
        arch.use_bias = False
        # Attention layer set + partial rotary.
        idx = getattr(hf, "attn_layer_indices", None) or []
        arch.attn_indices = tuple(idx)
        factor = getattr(hf, "partial_rotary_factor", None) or 1.0
        arch.rotary_dim = int(arch.head_dim * factor)
        # K/V heads that share one page row (see ``_attention``).
        arch.kv_pack = 1
        arch.num_experts = 0
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    def _is_attn(self, i: int) -> bool:
        return i in self.cfg.attn_indices

    def _is_moe(self, i: int) -> bool:
        return False

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        c = self.cfg
        col = P(None, None, MODEL_AXIS)
        row = P(None, MODEL_AXIS, None)
        layer = {
            "a_ln": P(None, None),
            "a_wq": col, "a_wk": col, "a_wv": col, "a_wo": row,
            "a_pre_ln": P(None, None),
            "a_gate": col, "a_up": col, "a_down": row,
            "m_norm": P(None, None),
            "m_gated_norm": P(None, MODEL_AXIS),
            "m_in_gate": col, "m_in_x": col,
            "m_in_bc": P(None, None, None),
            "m_in_dt": col,
            "m_conv_x_w": col,
            "m_conv_bc_w": P(None, None, None),
            "m_dt_bias": P(None, MODEL_AXIS),
            "m_A_log": P(None, MODEL_AXIS),
            "m_D": P(None, MODEL_AXIS),
            "m_out_proj": row,
            "m_pre_ln": P(None, None),
            "m_gate": col, "m_up": col, "m_down": row,
        }
        if c.use_conv_bias:
            layer["m_conv_x_b"] = P(None, MODEL_AXIS)
            layer["m_conv_bc_b"] = P(None, None)
        return {
            "embed": P(None, None),
            "layers": layer,
            "final_ln": P(None, ),
            "lm_head": P(None, MODEL_AXIS),
        }

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        H, I = c.hidden_size, c.intermediate_size
        Di, N, K = c.d_inner, c.ssm_state_size, c.conv_kernel
        Hm, G = c.num_ssm_heads, c.n_groups
        La, Lm = len(self._attn_layers), len(self._mamba_layers)
        Dq = c.num_q_heads * c.head_dim
        Dkv = c.total_kv_heads * c.head_dim
        keys = iter(jax.random.split(rng, 24))

        def norm(key, shape):
            return (scale * jax.random.normal(key, shape,
                                              jnp.float32)).astype(c.dtype)

        layers = {
            "a_ln": jnp.ones((La, H), c.dtype),
            "a_wq": norm(next(keys), (La, H, Dq)),
            "a_wk": norm(next(keys), (La, H, Dkv)),
            "a_wv": norm(next(keys), (La, H, Dkv)),
            "a_wo": norm(next(keys), (La, Dq, H)),
            "a_pre_ln": jnp.ones((La, H), c.dtype),
            "a_gate": norm(next(keys), (La, H, I)),
            "a_up": norm(next(keys), (La, H, I)),
            "a_down": norm(next(keys), (La, I, H)),
            "m_norm": jnp.ones((Lm, H), c.dtype),
            "m_gated_norm": jnp.ones((Lm, Di), c.dtype),
            "m_in_gate": norm(next(keys), (Lm, H, Di)),
            "m_in_x": norm(next(keys), (Lm, H, Di)),
            "m_in_bc": norm(next(keys), (Lm, H, 2 * G * N)),
            "m_in_dt": norm(next(keys), (Lm, H, Hm)),
            "m_conv_x_w": norm(next(keys), (Lm, K, Di)),
            "m_conv_bc_w": norm(next(keys), (Lm, K, 2 * G * N)),
            "m_dt_bias": jnp.zeros((Lm, Hm), jnp.float32),
            "m_A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, Hm + 1, dtype=jnp.float32)),
                (Lm, Hm)),
            "m_D": jnp.ones((Lm, Hm), jnp.float32),
            "m_out_proj": norm(next(keys), (Lm, Di, H)),
            "m_pre_ln": jnp.ones((Lm, H), c.dtype),
            "m_gate": norm(next(keys), (Lm, H, I)),
            "m_up": norm(next(keys), (Lm, H, I)),
            "m_down": norm(next(keys), (Lm, I, H)),
        }
        if c.use_conv_bias:
            layers["m_conv_x_b"] = jnp.zeros((Lm, Di), c.dtype)
            layers["m_conv_bc_b"] = jnp.zeros((Lm, 2 * G * N), c.dtype)
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
        GN2 = 2 * c.n_groups * c.ssm_state_size

        def t(name):
            return np.asarray(tensors[name])

        def stack(ids, fmt, f=lambda a: a, dtype=None):
            return jnp.asarray(np.stack(
                [f(t(fmt.format(i))) for i in ids])).astype(
                    dtype or c.dtype)

        A, M = self._attn_layers, self._mamba_layers
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
            "a_pre_ln": stack(A, ly + "pre_ff_layernorm.weight"),
            "a_gate": stack(A, ly + "feed_forward.gate_proj.weight",
                            lambda a: a.T),
            "a_up": stack(A, ly + "feed_forward.up_proj.weight",
                          lambda a: a.T),
            "a_down": stack(A, ly + "feed_forward.down_proj.weight",
                            lambda a: a.T),
            "m_norm": stack(M, ly + "input_layernorm.weight"),
            "m_gated_norm": stack(M, ly + "mamba.norm.weight"),
            "m_in_gate": stack(M, ly + "mamba.in_proj.weight",
                               lambda a: a[:Di].T),
            "m_in_x": stack(M, ly + "mamba.in_proj.weight",
                            lambda a: a[Di:2 * Di].T),
            "m_in_bc": stack(M, ly + "mamba.in_proj.weight",
                             lambda a: a[2 * Di:2 * Di + GN2].T),
            "m_in_dt": stack(M, ly + "mamba.in_proj.weight",
                             lambda a: a[2 * Di + GN2:].T),
            "m_conv_x_w": stack(M, ly + "mamba.conv1d.weight",
                                lambda a: a[:Di, 0, :].T),
            "m_conv_bc_w": stack(M, ly + "mamba.conv1d.weight",
                                 lambda a: a[Di:, 0, :].T),
            "m_dt_bias": stack(M, ly + "mamba.dt_bias",
                               dtype=jnp.float32),
            "m_A_log": stack(M, ly + "mamba.A_log", dtype=jnp.float32),
            "m_D": stack(M, ly + "mamba.D", dtype=jnp.float32),
            "m_out_proj": stack(M, ly + "mamba.out_proj.weight",
                                lambda a: a.T),
            "m_pre_ln": stack(M, ly + "pre_ff_layernorm.weight"),
            "m_gate": stack(M, ly + "feed_forward.gate_proj.weight",
                            lambda a: a.T),
            "m_up": stack(M, ly + "feed_forward.up_proj.weight",
                          lambda a: a.T),
            "m_down": stack(M, ly + "feed_forward.down_proj.weight",
                            lambda a: a.T),
        }
        if c.use_conv_bias:
            layers["m_conv_x_b"] = stack(M, ly + "mamba.conv1d.bias",
                                         lambda a: a[:Di])
            layers["m_conv_bc_b"] = stack(M, ly + "mamba.conv1d.bias",
                                          lambda a: a[Di:])
        if c.num_kv_head_replicas > 1:
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
    # state_shapes() (snapshot-pool geometry for core/state_cache.py)
    # is inherited from Jamba: mamba-stack depth with THIS override's
    # Mamba-2 arrays (conv + conv_bc + ssm), so hybrid Mamba-2
    # checkpoints snapshot all three state tensors coherently.
    def _state_shapes(self, depth: int) -> dict:
        # Must match the Mamba-2 mixer's state layout exactly: delegate
        # to the single source of truth in models/mamba.py.
        return Mamba2ForCausalLM._state_shapes(self, depth)

    def state_row_bytes(self) -> int:
        return Mamba2ForCausalLM.state_row_bytes(self)

    def kv_cache_specs(self) -> dict:
        # Paged K/V specs from the hybrid base + Mamba-2 state specs.
        return {**JambaForCausalLM.kv_cache_specs(self),
                **Mamba2ForCausalLM.kv_cache_specs(self)}

    def _page_row(self) -> tuple[int, int]:
        """K/V heads and lanes of a page row (``kv_pack`` heads share
        one row of the lane tile)."""
        c = self.cfg
        return (c.total_kv_heads // c.kv_pack,
                storage_head_dim(c.head_dim * c.kv_pack))

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None, num_layers=None) -> dict:
        c = self.cfg
        assert num_layers is None or num_layers == c.num_layers, \
            "hybrid stacks are not sliceable per stage (no PP)"
        heads, lanes = self._page_row()
        shape = (len(self._attn_layers), num_pages, heads, page_size, lanes)
        caches = {name: jnp.zeros(shape, cache_dtype or c.dtype)
                  for name in ("k", "v")}
        caches.update({name: jnp.zeros(s, d) for name, (s, d)
                       in self.state_shapes().items()})
        return caches

    def kv_cache_page_bytes(self, page_size: int) -> int:
        heads, lanes = self._page_row()
        return (2 * len(self._attn_layers) * page_size * heads * lanes
                * jnp.dtype(self.cfg.dtype).itemsize)

    # ------------------------------------------------------------------
    # Forward: the layer kinds repeat with some period (the whole stack
    # where they do not), so the walk is a scan of one period's body
    # over the periods, the pages and state rows carried through it and
    # updated in place. A forward program's size and compile time are a
    # period's.
    # ------------------------------------------------------------------
    def _period(self) -> int:
        L = self.cfg.num_layers
        kinds = [self._is_attn(i) for i in range(L)]
        return next(p for p in range(1, L + 1) if L % p == 0
                    and all(kinds[i] == kinds[i % p] for i in range(L)))

    def _attention(self, lp: dict, x: jax.Array, caches: dict, layer,
                   batch, rope) -> tuple[jax.Array, dict]:
        """GQA over the pages of attention layer ``layer``. With
        ``kv_pack`` p > 1, p K/V heads of ``head_dim`` lanes share one
        page row of ``p * head_dim``: the kernel sees ``KVH / p`` heads
        of that width, a query head's lanes are zero outside its own
        K/V head's slot (so its scores are its own), and of its output
        only that slot is kept."""
        c = self.cfg
        T = x.shape[0]
        QH, KVH, D, p = (c.num_q_heads, c.total_kv_heads, c.head_dim,
                         c.kv_pack)
        q = (x @ lp["wq"]).reshape(T, QH, D)
        k = (x @ lp["wk"]).reshape(T, KVH, D)
        v = (x @ lp["wv"]).reshape(T, KVH, D)
        if rope is not None:
            q, k = rope(q), rope(k)
        if p > 1:
            slot = (np.arange(QH) // (QH // KVH)) % p
            own = jnp.asarray(slot[:, None] == np.arange(p), q.dtype)
            q = (q[:, :, None, :] * own[None, :, :, None]).reshape(
                T, QH, p * D)
            k = k.reshape(T, KVH // p, p * D)
            v = v.reshape(T, KVH // p, p * D)
        k_all, v_all, attn = write_kv_and_attend(
            q, caches["k"], caches["v"], k, v, batch,
            sm_scale=c.sm_scale_override or D ** -0.5,
            layer=jnp.reshape(layer, (1, )).astype(jnp.int32), window=0)
        if p > 1:
            attn = jnp.sum(attn.reshape(T, QH, p, D)
                           * own[None, :, :, None], axis=2)
        return (attn.reshape(T, QH * D) @ lp["wo"],
                {**caches, "k": k_all, "v": v_all})

    def _mlp(self, lp: dict, x: jax.Array) -> jax.Array:
        """What follows a layer's mixer, of the normed stream: a dense
        SwiGLU here; models/granite_hybrid.py adds routed experts."""
        return swiglu(x, lp["gate"], lp["up"], lp["down"])

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
        # Static only where the batch's shape says so: under the unified
        # attention kernel one program a token bucket serves every mix
        # (max_q is pinned to 1) and ops/ssd.py picks the form at run
        # time.
        decode_only = (getattr(batch, "max_q", 0) == 1
                       and getattr(batch, "attn_desc", None) is None)
        entries = step_entries(seg, hidden.shape[0], c.conv_kernel,
                               decode_only)
        rm = c.residual_multiplier
        rope = None
        if c.rotary_dim:
            rd = c.rotary_dim
            cos, sin = compute_rope_cos_sin(batch.positions, rd,
                                            c.rope_theta, c.rope_scaling,
                                            dtype=jnp.float32)

            def rope(x):
                x32 = x.astype(jnp.float32)
                rot = apply_rope_single(x32[..., :rd], cos, sin)
                if rd == c.head_dim:
                    return rot.astype(c.dtype)
                return jnp.concatenate([rot, x32[..., rd:]],
                                       axis=-1).astype(c.dtype)

        period = self._period()
        periods = c.num_layers // period
        kinds = [self._is_attn(i) for i in range(period)]
        per = {"a_": sum(kinds), "m_": period - sum(kinds)}
        # A tuple leaf is one buffer a layer (``serving_layout``,
        # models/mixtral.py: what ``ragged_dot`` must read in place); it
        # is not sliced by the walk but picked for the MLP alone.
        per_layer = {k: v for k, v in layer_params.items()
                     if isinstance(v, tuple)}
        layer_params = {k: v for k, v in layer_params.items()
                        if k not in per_layer}

        def mlp(lp, x, kind, pi, at):
            """``_mlp`` of the layer at place ``at`` of its kind in
            period ``pi``, with the layer's own buffers: picked outright
            where the walk is one period, else by a switch with one
            branch a period (models/llama.py ``run_layers``)."""
            own = {k[2:]: v for k, v in per_layer.items()
                   if k.startswith(kind)}
            if not own or periods == 1:
                return self._mlp(
                    {**lp, **{k: v[at] for k, v in own.items()}}, x)
            block = jax.jit(self._mlp)
            return jax.lax.switch(pi, [functools.partial(block, {
                **lp, **{k: v[p * per[kind] + at] for k, v in own.items()}})
                for p in range(periods)], x)

        def body(carry, pi):
            h, caches = carry
            at = {"a_": 0, "m_": 0}
            for is_attn in kinds:
                kind = "a_" if is_attn else "m_"
                # The layer's index in its kind's stack; its weights are
                # read where they lie (a period's would be a copy).
                layer = pi * per[kind] + at[kind]
                lp = {k[2:]: jax.lax.dynamic_index_in_dim(v, layer, 0, False)
                      for k, v in layer_params.items()
                      if k.startswith(kind)}
                if is_attn:
                    x = rms_norm(h, lp["ln"], c.rms_norm_eps)
                    out, caches = self._attention(lp, x, caches, layer,
                                                  batch, rope)
                else:
                    x = rms_norm(h, lp["norm"], c.rms_norm_eps)
                    out, caches = Mamba2ForCausalLM._mixer(
                        self, lp, x, caches, layer, seg, entries,
                        decode_only)
                h = h + rm * out
                x2 = rms_norm(h, lp["pre_ln"], c.rms_norm_eps)
                h = h + rm * mlp(lp, x2, kind, pi, at[kind])
                at[kind] += 1
            return (h, caches), None

        carry = (hidden, dict(kv_caches))
        if periods == 1:
            carry, _ = body(carry, 0)
        else:
            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(periods, dtype=jnp.int32))
        return carry
