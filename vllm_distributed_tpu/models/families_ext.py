"""Extended model families on the generic block knobs.

Reference: vllm/model_executor/models/{gpt_neox,phi,stablelm,
starcoder2,commandr,olmo2,granite,qwen3_moe,nemotron}.py — each family
is the Llama decoder with a structural twist now expressed as
LlamaArchConfig knobs (norm flavor, partial rotary, parallel residual,
non-gated MLP, multipliers); these subclasses set the knobs and map the
checkpoint tensor names onto the canonical layout."""

import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.llama import (MODEL_AXIS,
                                               LlamaArchConfig,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM


def _alias_moe_experts(tensors: dict, num_layers: int,
                       num_experts: int) -> dict:
    """Map the HF mlp.experts.{e}.{gate,up,down}_proj / mlp.gate naming
    (Qwen3-MoE, OLMoE) onto the Mixtral layout the base loader stacks."""
    alias = dict(tensors)
    for i in range(num_layers):
        for e in range(num_experts):
            for src, dst in (("gate_proj", "w1"), ("down_proj", "w2"),
                             ("up_proj", "w3")):
                alias[f"model.layers.{i}.block_sparse_moe.experts."
                      f"{e}.{dst}.weight"] = tensors[
                          f"model.layers.{i}.mlp.experts.{e}."
                          f"{src}.weight"]
        alias[f"model.layers.{i}.block_sparse_moe.gate.weight"] = \
            tensors[f"model.layers.{i}.mlp.gate.weight"]
    return alias


from vllm_distributed_tpu.models.common import rename_tensors as _rename


class GraniteForCausalLM(LlamaForCausalLM):
    """IBM Granite: Llama weights + the four scale multipliers
    (reference: models/granite.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.embed_scale = float(getattr(hf, "embedding_multiplier", 1.0))
        arch.residual_multiplier = float(
            getattr(hf, "residual_multiplier", 1.0))
        arch.sm_scale_override = float(
            getattr(hf, "attention_multiplier", None)
            or arch.head_dim ** -0.5)
        ls = float(getattr(hf, "logits_scaling", 1.0) or 1.0)
        arch.logit_multiplier = 1.0 / ls
        arch.attention_bias = bool(getattr(hf, "attention_bias", False))


class Qwen3MoeForCausalLM(MixtralForCausalLM):
    """Qwen3-MoE: Mixtral-style routed experts (normalized top-k) +
    Qwen3 per-head qk norm, no shared expert (reference:
    models/qwen3_moe.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.num_experts = hf.num_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", True))
        arch.moe_intermediate_size = hf.moe_intermediate_size
        arch.qk_norm = True
        if getattr(hf, "mlp_only_layers", None) or \
                getattr(hf, "decoder_sparse_step", 1) != 1:
            raise ValueError(
                "Qwen3-MoE layouts mixing dense and sparse layers are "
                "not supported; every layer must be sparse")

    def params_from_hf_state_dict(self, tensors) -> dict:
        return super().params_from_hf_state_dict(_alias_moe_experts(
            tensors, self.cfg.num_layers, self.cfg.num_experts))


class GraniteMoeForCausalLM(MixtralForCausalLM):
    """IBM Granite-MoE: Mixtral-style routed experts stored as FUSED
    per-expert tensors (input_linear packs [gate; up] rows) + the four
    Granite multipliers (reference: models/granitemoe.py). Its top-k-
    then-softmax gating equals Mixtral's softmax-then-renormalize, so
    norm_topk_prob=True reproduces it exactly."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        # The four multipliers + attention bias are exactly Granite's.
        GraniteForCausalLM.configure_arch(arch, hf)
        arch.num_experts = hf.num_local_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.norm_topk_prob = True
        arch.moe_intermediate_size = hf.intermediate_size

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        I = c.moe_intermediate_size
        alias = dict(tensors)
        for i in range(c.num_layers):
            pre = f"model.layers.{i}.block_sparse_moe."
            fused = np.asarray(alias.pop(pre + "input_linear.weight"))
            out_w = np.asarray(alias.pop(pre + "output_linear.weight"))
            alias[pre + "gate.weight"] = np.asarray(
                alias.pop(pre + "router.layer.weight"))
            for e in range(c.num_experts):
                # [2I, H] per expert: first I rows gate, rest up
                # (HF chunk(2, dim=-1) of the fused projection).
                alias[pre + f"experts.{e}.w1.weight"] = fused[e, :I]
                alias[pre + f"experts.{e}.w3.weight"] = fused[e, I:]
                alias[pre + f"experts.{e}.w2.weight"] = out_w[e]
        return super().params_from_hf_state_dict(alias)


class DbrxForCausalLM(MixtralForCausalLM):
    """Databricks DBRX: MoE with experts stored as FLAT stacked
    [E*ffn, H] tensors (w1 gate, v1 up, w2 down applied untransposed),
    fused Wqkv with clipping, bias-free LayerNorms (reference:
    models/dbrx.py incl. its expert unflatten in the weight loader)."""

    @classmethod
    def arch_config_source(cls, hf):
        from types import SimpleNamespace

        from vllm_distributed_tpu.models.common import subconfig_get \
            as get
        attn = getattr(hf, "attn_config", None)
        ffn = getattr(hf, "ffn_config", None)
        return SimpleNamespace(
            vocab_size=hf.vocab_size,
            hidden_size=hf.d_model,
            intermediate_size=int(get(ffn, "ffn_hidden_size",
                                      4 * hf.d_model)),
            num_hidden_layers=hf.n_layers,
            num_attention_heads=hf.n_heads,
            num_key_value_heads=int(get(attn, "kv_n_heads", hf.n_heads)),
            head_dim=hf.d_model // hf.n_heads,
            rms_norm_eps=1e-5,
            rope_theta=float(get(attn, "rope_theta", 10000.0)),
            tie_word_embeddings=False,
        )

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        from vllm_distributed_tpu.models.common import subconfig_get \
            as get
        ffn = getattr(hf, "ffn_config", None)
        attn = getattr(hf, "attn_config", None)
        arch.num_experts = int(get(ffn, "moe_num_experts", 8))
        arch.num_experts_per_tok = int(get(ffn, "moe_top_k", 2))
        # moe_normalize_expert_weights is a p-norm order; only the L1
        # renormalization (1 / None) maps onto the router — reject
        # other orders rather than silently approximating them.
        p_norm = get(ffn, "moe_normalize_expert_weights", 1)
        if p_norm not in (None, 0, 1, 1.0):
            raise ValueError(
                f"DBRX moe_normalize_expert_weights={p_norm} is not "
                f"supported (only L1 renormalization)")
        arch.norm_topk_prob = bool(p_norm)
        arch.moe_intermediate_size = arch.intermediate_size
        arch.norm_type = "layernorm"
        clip = get(attn, "clip_qkv", None)
        arch.qkv_clip = float(clip) if clip else None

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        H = c.hidden_size
        I = c.moe_intermediate_size
        kv = c.num_kv_heads * c.head_dim
        alias = {}
        for name, t in tensors.items():
            name = name.replace("transformer.blocks.", "model.layers.")
            name = name.replace("transformer.wte.", "model.embed_tokens.")
            name = name.replace("transformer.norm_f.", "model.norm.")
            name = name.replace(".norm_attn_norm.norm_1.",
                                ".input_layernorm.")
            name = name.replace(".norm_attn_norm.norm_2.",
                                ".post_attention_layernorm.")
            name = name.replace(".norm_attn_norm.attn.out_proj.",
                                ".self_attn.o_proj.")
            alias[name] = np.asarray(t)
        for i in range(c.num_layers):
            base = f"model.layers.{i}."
            w = alias.pop(base + "norm_attn_norm.attn.Wqkv.weight")
            A = base + "self_attn."
            alias[A + "q_proj.weight"] = w[:H]
            alias[A + "k_proj.weight"] = w[H:H + kv]
            alias[A + "v_proj.weight"] = w[H + kv:]
            moe = base + "block_sparse_moe."
            alias[moe + "gate.weight"] = alias.pop(
                base + "ffn.router.layer.weight")
            w1 = alias.pop(base + "ffn.experts.mlp.w1")  # [E*I, H]
            v1 = alias.pop(base + "ffn.experts.mlp.v1")
            w2 = alias.pop(base + "ffn.experts.mlp.w2")
            for e in range(c.num_experts):
                rows = slice(e * I, (e + 1) * I)
                alias[moe + f"experts.{e}.w1.weight"] = w1[rows]
                alias[moe + f"experts.{e}.w3.weight"] = v1[rows]
                # w2 chunks apply UNtransposed (h @ w2_e); canonical
                # w2.weight is torch [out, in], so hand over the
                # transpose.
                alias[moe + f"experts.{e}.w2.weight"] = w2[rows].T
        return super().params_from_hf_state_dict(alias)


class PhimoeForCausalLM(MixtralForCausalLM):
    """Phi-3.5-MoE: Mixtral expert layout + LayerNorm blocks, biased
    projections, and SPARSEMIXER routing — each of the two experts is
    the argmax over jitter-thresholded scores, weighted by a softmax
    over the surviving entries (reference: models/phimoe.py
    phimoe_routing_function; deterministic at inference)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        if hf.num_experts_per_tok != 2:
            raise ValueError("sparsemixer routing requires top_k == 2")
        arch.num_experts = hf.num_local_experts
        arch.num_experts_per_tok = 2
        arch.moe_intermediate_size = hf.intermediate_size
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.attention_bias = bool(getattr(hf, "attention_bias", True))
        arch.attention_out_bias = arch.attention_bias
        arch.router_jitter_eps = float(
            getattr(hf, "router_jitter_eps", 0.01))
        if getattr(hf, "lm_head_bias", False):
            raise ValueError("Phimoe lm_head_bias checkpoints are not "
                             "supported yet")

    def _route(self, lp: dict, x):
        import jax
        import jax.numpy as jnp
        eps = self.cfg.router_jitter_eps
        scores = (x.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32))  # [T, E]
        neg = jnp.float32(-jnp.inf)

        def pick(cand):
            # cand: scores with already-taken experts at -inf. The
            # threshold compares against the ORIGINAL scores (HF
            # sparsemixer semantics).
            mx = cand.max(axis=-1, keepdims=True)
            factor = jnp.maximum(jnp.abs(scores), mx)
            drop = ((mx - scores) / factor) > (2 * eps)
            gates = jnp.where(drop, neg, cand)
            sel = jnp.argmax(cand, axis=-1)
            w = jnp.take_along_axis(jax.nn.softmax(gates, axis=-1),
                                    sel[:, None], axis=-1)[:, 0]
            return sel, w

        sel1, w1 = pick(scores)
        masked = scores.at[jnp.arange(scores.shape[0]), sel1].set(neg)
        sel2, w2 = pick(masked)
        return (jnp.stack([sel1, sel2], axis=-1),
                jnp.stack([w1, w2], axis=-1))


class GptOssForCausalLM(MixtralForCausalLM):
    """OpenAI gpt-oss: attention sinks, alternating sliding/full
    layers, biased projections, MoE with interleaved gate_up expert
    tensors, per-expert biases and the clamped (up+1)*glu activation
    (reference: models/gpt_oss.py)."""

    # The per-expert biases ride on the expert weights into the serving
    # layout (models/mixtral.py).
    _PER_LAYER = MixtralForCausalLM._PER_LAYER + ("b_gate", "b_up",
                                                  "b_down")

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.num_experts = hf.num_local_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.norm_topk_prob = True  # topk-then-softmax == renormalized
        arch.moe_intermediate_size = hf.intermediate_size
        arch.attention_bias = True
        arch.attention_out_bias = True
        arch.attn_sinks = True
        arch.moe_bias = True
        arch.router_bias = True
        # Clamped-GLU activation constants (HF GptOssExperts).
        arch.glu_alpha = 1.702
        arch.glu_limit = float(getattr(hf, "swiglu_limit", 7.0))

    def param_specs(self) -> dict:
        specs = super().param_specs()
        layer = specs["layers"]
        layer["sinks"] = P(None, MODEL_AXIS)
        layer["router_b"] = P(None, None)
        ax = layer["w_gate"]  # [L, E, H, I] spec; biases follow it
        layer["b_gate"] = P(ax[0], ax[1], ax[3])
        layer["b_up"] = P(ax[0], ax[1], ax[3])
        layer["b_down"] = P(ax[0], ax[1], None)
        return specs

    def init_params(self, rng, scale: float = 0.02) -> dict:
        import jax.numpy as jnp
        params = super().init_params(rng, scale)
        c = self.cfg
        L, E = c.num_layers, c.num_experts
        I = c.moe_intermediate_size
        layers = params["layers"]
        layers["sinks"] = jnp.zeros((L, c.num_q_heads), c.dtype)
        layers["router_b"] = jnp.zeros((L, E), c.dtype)
        layers["b_gate"] = jnp.zeros((L, E, I), c.dtype)
        layers["b_up"] = jnp.zeros((L, E, I), c.dtype)
        layers["b_down"] = jnp.zeros((L, E, c.hidden_size), c.dtype)
        return params

    def params_from_hf_state_dict(self, tensors) -> dict:
        import jax.numpy as jnp
        c = self.cfg
        L, E = c.num_layers, c.num_experts
        I = c.moe_intermediate_size
        alias = dict(tensors)
        gu = np.stack([np.asarray(
            alias.pop(f"model.layers.{i}.mlp.experts.gate_up_proj"))
            for i in range(L)])              # [L, E, H, 2I]
        gub = np.stack([np.asarray(
            alias.pop(f"model.layers.{i}.mlp.experts.gate_up_proj_bias"))
            for i in range(L)])              # [L, E, 2I]
        dn = np.stack([np.asarray(
            alias.pop(f"model.layers.{i}.mlp.experts.down_proj"))
            for i in range(L)])              # [L, E, I, H]
        dnb = np.stack([np.asarray(
            alias.pop(f"model.layers.{i}.mlp.experts.down_proj_bias"))
            for i in range(L)])              # [L, E, H]
        for i in range(L):
            # Base mapper wants dense-MLP + per-expert w1/w2/w3 names;
            # hand over torch-Linear [out, in] layouts (it transposes).
            pre = f"model.layers.{i}."
            alias[pre + "block_sparse_moe.gate.weight"] = np.asarray(
                alias.pop(pre + "mlp.router.weight"))
            for e in range(E):
                alias[pre + f"block_sparse_moe.experts.{e}.w1.weight"] \
                    = gu[i, e, :, ::2].T
                alias[pre + f"block_sparse_moe.experts.{e}.w3.weight"] \
                    = gu[i, e, :, 1::2].T
                alias[pre + f"block_sparse_moe.experts.{e}.w2.weight"] \
                    = dn[i, e].T
        params = super().params_from_hf_state_dict(alias)
        layers = params["layers"]
        layers["sinks"] = jnp.asarray(np.stack([
            np.asarray(tensors[f"model.layers.{i}.self_attn.sinks"])
            for i in range(L)]), c.dtype)
        layers["router_b"] = jnp.asarray(np.stack([
            np.asarray(tensors[f"model.layers.{i}.mlp.router.bias"])
            for i in range(L)]), c.dtype)
        layers["b_gate"] = jnp.asarray(gub[..., ::2], c.dtype)
        layers["b_up"] = jnp.asarray(gub[..., 1::2], c.dtype)
        layers["b_down"] = jnp.asarray(dnb, c.dtype)
        return params

    def _moe_dense(self, lp, x, top_idx, top_vals):
        raise ValueError(
            "VDT_MOE_BACKEND=dense is not wired for gpt-oss (its "
            "experts carry biases + a clamped GLU the dense einsum "
            "baseline lacks); unset the env var")

    def _route(self, lp: dict, x):
        import jax
        import jax.numpy as jnp
        c = self.cfg
        logits = (x.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32)
                  + lp["router_b"].astype(jnp.float32))
        # HF gpt-oss: top-k over logits, softmax over the selected k.
        top_logits, top_idx = jax.lax.top_k(logits,
                                            c.num_experts_per_tok)
        top_vals = jax.nn.softmax(top_logits, axis=-1)
        return top_idx, top_vals

    def _expert_ffn(self, lp: dict, xs, group_sizes):
        import jax
        import jax.numpy as jnp
        c = self.cfg
        rows = xs.shape[0]
        # Expert id per sorted row, for the per-expert biases.
        bounds = jnp.cumsum(group_sizes)
        row_e = jnp.searchsorted(bounds,
                                 jnp.arange(rows, dtype=jnp.int32),
                                 side="right")
        row_e = jnp.minimum(row_e, group_sizes.shape[0] - 1)
        g = (jax.lax.ragged_dot(xs, self._w(lp, "w_gate"), group_sizes)
             + lp["b_gate"][row_e])
        u = (jax.lax.ragged_dot(xs, self._w(lp, "w_up"), group_sizes)
             + lp["b_up"][row_e])
        # Clamped GLU (HF GptOssExperts): gate capped above, up capped
        # both ways, sigmoid(alpha * gate) gating, (up + 1) residual.
        limit, alpha = c.glu_limit, c.glu_alpha
        g = jnp.minimum(g, limit)
        u = jnp.clip(u, -limit, limit)
        glu = g * jax.nn.sigmoid(g * alpha)
        y = jax.lax.ragged_dot(((u + 1.0) * glu).astype(xs.dtype),
                               self._w(lp, "w_down"), group_sizes)
        return y + lp["b_down"][row_e]


class Starcoder2ForCausalLM(LlamaForCausalLM):
    """StarCoder2: LayerNorm(+bias), non-gated gelu MLP with biases,
    qkv + output biases (reference: models/starcoder2.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.mlp_gated = False
        arch.mlp_bias = bool(getattr(hf, "use_bias", True))
        arch.attention_bias = bool(getattr(hf, "use_bias", True))
        arch.attention_out_bias = bool(getattr(hf, "use_bias", True))
        arch.hidden_act = getattr(hf, "hidden_act", "gelu_pytorch_tanh")
        arch.rms_norm_eps = float(getattr(hf, "norm_epsilon", 1e-5))
        arch.tie_word_embeddings = bool(
            getattr(hf, "tie_word_embeddings", True))

    def params_from_hf_state_dict(self, tensors) -> dict:
        return super().params_from_hf_state_dict(_rename(tensors, [
            (".mlp.c_fc.", ".mlp.fc1."),
            (".mlp.c_proj.", ".mlp.fc2."),
        ]))


class StableLmForCausalLM(LlamaForCausalLM):
    """StableLM: partial rotary + LayerNorm(+bias) around a gated silu
    MLP (reference: models/stablelm.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = bool(getattr(hf, "layer_norm_bias", True))
        arch.rotary_dim = int(arch.head_dim *
                              float(getattr(hf, "partial_rotary_factor",
                                            0.25)))
        arch.attention_bias = bool(getattr(hf, "use_qkv_bias", False))
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_eps", 1e-5))


class GPTNeoXForCausalLM(LlamaForCausalLM):
    """GPT-NeoX (Pythia): parallel residual with separate norms,
    LayerNorm(+bias), fused per-head-interleaved QKV, partial rotary,
    non-gated gelu MLP, every Linear biased (reference:
    models/gpt_neox.py incl. its fused-QKV de-interleave)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.parallel_block = bool(
            getattr(hf, "use_parallel_residual", True))
        arch.mlp_gated = False
        arch.mlp_bias = True
        arch.attention_bias = True
        arch.attention_out_bias = True
        arch.hidden_act = getattr(hf, "hidden_act", "gelu")
        arch.rotary_dim = int(arch.head_dim *
                              float(getattr(hf, "rotary_pct", 0.25)))
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_eps", 1e-5))
        arch.tie_word_embeddings = False

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        D, H = c.head_dim, c.hidden_size
        N = c.num_q_heads
        out = {}
        for name, t in tensors.items():
            name = name.replace("gpt_neox.layers.", "model.layers.")
            name = name.replace("gpt_neox.final_layer_norm.",
                                "model.norm.")
            name = name.replace("gpt_neox.embed_in.",
                                "model.embed_tokens.")
            name = name.replace("embed_out.", "lm_head.")
            name = name.replace(".attention.dense.", ".self_attn.o_proj.")
            name = name.replace(".mlp.dense_h_to_4h.", ".mlp.fc1.")
            name = name.replace(".mlp.dense_4h_to_h.", ".mlp.fc2.")
            out[name] = t
        # De-interleave the fused QKV: rows pack [h0_q, h0_k, h0_v,
        # h1_q, ...] (reference: gpt_neox.py attention weight loader).
        for i in range(c.num_layers):
            base = f"model.layers.{i}.attention.query_key_value"
            w = np.asarray(out.pop(base + ".weight"))  # [3*N*D, H]
            b = np.asarray(out.pop(base + ".bias"))
            w = w.reshape(N, 3, D, H)
            b = b.reshape(N, 3, D)
            A = f"model.layers.{i}.self_attn."
            out[A + "q_proj.weight"] = w[:, 0].reshape(N * D, H)
            out[A + "k_proj.weight"] = w[:, 1].reshape(N * D, H)
            out[A + "v_proj.weight"] = w[:, 2].reshape(N * D, H)
            out[A + "q_proj.bias"] = b[:, 0].reshape(N * D)
            out[A + "k_proj.bias"] = b[:, 1].reshape(N * D)
            out[A + "v_proj.bias"] = b[:, 2].reshape(N * D)
        return super().params_from_hf_state_dict(out)


class PhiForCausalLM(LlamaForCausalLM):
    """Phi-1/1.5/2: parallel residual from ONE shared input norm,
    LayerNorm(+bias), partial rotary, non-gated gelu MLP with biases,
    biased LM head (reference: models/phi.py)."""

    LM_HEAD_BIAS = True

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.parallel_block = True
        arch.shared_block_ln = True
        arch.mlp_gated = False
        arch.mlp_bias = True
        arch.attention_bias = True
        arch.attention_out_bias = True
        arch.hidden_act = getattr(hf, "hidden_act", "gelu_new")
        arch.rotary_dim = int(arch.head_dim *
                              float(getattr(hf, "partial_rotary_factor",
                                            0.5)))
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_eps", 1e-5))

    def params_from_hf_state_dict(self, tensors) -> dict:
        # lm_head.bias flows through the base LM_HEAD_BIAS hook.
        return super().params_from_hf_state_dict(_rename(tensors, [
            (".self_attn.dense.", ".self_attn.o_proj."),
            ("model.final_layernorm.", "model.norm."),
        ]))


class CohereForCausalLM(LlamaForCausalLM):
    """Cohere Command-R: parallel residual from one shared LayerNorm
    (no bias), interleaved rope, logit_scale, tied embeddings
    (reference: models/commandr.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.parallel_block = True
        arch.shared_block_ln = True
        arch.rope_interleaved = True
        arch.logit_multiplier = float(getattr(hf, "logit_scale", 1.0))
        arch.tie_word_embeddings = True
        arch.attention_bias = bool(getattr(hf, "attention_bias", False))
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_eps", 1e-5))
        if getattr(hf, "use_qk_norm", False):
            raise ValueError("Cohere use_qk_norm checkpoints are not "
                             "supported yet")


class Olmo2ForCausalLM(LlamaForCausalLM):
    """OLMo 2: post-norm block (sub-layers read the raw residual
    stream, outputs are RMS-normed before the add) + full-row q/k norms
    (reference: models/olmo2.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.pre_norm = False
        arch.extra_layer_norms = True
        arch.qk_norm_full = True

    # The base loader handles the post-norm layout directly: with
    # pre_norm=False it skips input_ln/post_ln and stacks only the two
    # output norms (post_attention/post_feedforward), which is exactly
    # olmo2's checkpoint naming — no override needed.


class Olmo3ForCausalLM(Olmo2ForCausalLM):
    """OLMo-3: the OLMo-2 post-norm block + per-layer sliding windows;
    rope SCALING applies only to full-attention layers — sliding
    layers keep the default unscaled rope (reference: models/olmo3.py
    building separate rotary tables per layer type)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        Olmo2ForCausalLM.configure_arch(arch, hf)
        if arch.rope_scaling is not None:
            # Same base, no scaling, on the windowed layers (the
            # rope_theta_local table carries no scaling by design).
            arch.rope_theta_local = arch.rope_theta


class NemotronForCausalLM(LlamaForCausalLM):
    """Nemotron: LayerNorm1p (weight+1, folded at load), relu^2
    non-gated MLP, partial rotary (reference: models/nemotron.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.mlp_gated = False
        arch.hidden_act = "relu2"
        arch.rotary_dim = int(
            arch.head_dim * float(getattr(hf, "partial_rotary_factor",
                                          0.5)))
        arch.rms_norm_eps = float(getattr(hf, "norm_eps", 1e-5))

    def params_from_hf_state_dict(self, tensors) -> dict:
        params = super().params_from_hf_state_dict(_rename(tensors, [
            (".mlp.up_proj.", ".mlp.fc1."),
            (".mlp.down_proj.", ".mlp.fc2."),
        ]))
        # LayerNorm1p: (1 + w) * normed + b — fold the +1.
        layers = params["layers"]
        for key in ("input_ln", "post_ln"):
            layers[key] = layers[key] + 1.0
        params["final_ln"] = params["final_ln"] + 1.0
        return params


class OlmoForCausalLM(LlamaForCausalLM):
    """OLMo v1: NON-parametric LayerNorm (no weight/bias tensors in the
    checkpoint — synthesized as ones/zeros at load), optional qkv
    clamping (reference: models/olmo.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.rms_norm_eps = 1e-5  # OlmoLayerNorm's fixed eps
        clip = getattr(hf, "clip_qkv", None)
        arch.qkv_clip = float(clip) if clip else None

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        ones = np.ones((c.hidden_size, ), np.float32)
        alias = dict(tensors)
        for i in range(c.num_layers):
            alias[f"model.layers.{i}.input_layernorm.weight"] = ones
            alias[f"model.layers.{i}.post_attention_layernorm.weight"] \
                = ones
        alias["model.norm.weight"] = ones
        return super().params_from_hf_state_dict(alias)


class OlmoeForCausalLM(MixtralForCausalLM):
    """OLMoE: Mixtral-style routed experts (softmax, norm_topk_prob
    False) + full-row q/k RMSNorms (reference: models/olmoe.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.num_experts = hf.num_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", False))
        arch.qk_norm_full = True

    def params_from_hf_state_dict(self, tensors) -> dict:
        return super().params_from_hf_state_dict(_alias_moe_experts(
            tensors, self.cfg.num_layers, self.cfg.num_experts))


class GlmForCausalLM(LlamaForCausalLM):
    """GLM-4 (hf-format): partial INTERLEAVED rotary on the first half
    of each head, qkv bias, standard pre-norm gated block (reference:
    models/glm.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.rope_interleaved = True
        arch.rotary_dim = int(arch.head_dim *
                              float(getattr(hf, "partial_rotary_factor",
                                            0.5)))
        arch.attention_bias = bool(getattr(hf, "attention_bias", True))
        # GLM's o_proj is bias-free even when attention_bias is set
        # (HF GlmAttention hardcodes bias=False on o_proj).
        arch.attention_out_bias = False

    def params_from_hf_state_dict(self, tensors) -> dict:
        # GLM fuses gate|up like Phi-3; split for the base layout.
        out = dict(tensors)
        for i in range(self.cfg.num_layers):
            gu = np.asarray(
                tensors[f"model.layers.{i}.mlp.gate_up_proj.weight"])
            half = gu.shape[0] // 2
            out[f"model.layers.{i}.mlp.gate_proj.weight"] = gu[:half]
            out[f"model.layers.{i}.mlp.up_proj.weight"] = gu[half:]
        return super().params_from_hf_state_dict(out)


class Glm4ForCausalLM(GlmForCausalLM):
    """GLM-4-0414: the GLM block plus Gemma2-style sandwich norms on
    each sub-block's output (post_self_attn / post_mlp layernorms;
    reference: models/glm4.py)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        GlmForCausalLM.configure_arch(arch, hf)
        arch.extra_layer_norms = True

    def params_from_hf_state_dict(self, tensors) -> dict:
        # Role renames onto the Gemma2-style 4-norm canonical layout;
        # ORDER matters (the true pre-MLP norm carries the name the
        # attention-output norm must end up with).
        return super().params_from_hf_state_dict(_rename(tensors, [
            (".post_attention_layernorm.", ".pre_feedforward_layernorm."),
            (".post_self_attn_layernorm.", ".post_attention_layernorm."),
            (".post_mlp_layernorm.", ".post_feedforward_layernorm."),
        ]))


class FalconForCausalLM(LlamaForCausalLM):
    """Falcon (reference: models/falcon.py): parallel-residual block —
    one shared norm for 7B-style checkpoints, separate ln_attn/ln_mlp
    for the new decoder architecture (40B/180B) — non-gated gelu MLP,
    grouped fused QKV (q heads of each kv group packed with that
    group's k and v), multi-query or grouped kv."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        if getattr(hf, "alibi", False):
            raise ValueError("ALiBi Falcon checkpoints (falcon-rw) are "
                             "not supported (no rotary)")
        if not getattr(hf, "parallel_attn", True):
            raise ValueError("sequential-attention Falcon "
                             "(parallel_attn=false) is not supported")
        new = bool(getattr(hf, "new_decoder_architecture", False))
        arch.parallel_block = True
        # Falcon2-11B is new-arch but keeps ONE shared norm
        # (num_ln_in_parallel_attn=1 -> no ln_attn/ln_mlp tensors).
        arch.shared_block_ln = (not new or getattr(
            hf, "num_ln_in_parallel_attn", None) == 1)
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.mlp_gated = False
        bias = bool(getattr(hf, "bias", False))
        arch.mlp_bias = bias
        arch.attention_bias = bias
        arch.attention_out_bias = bias
        # HF FalconMLP honors config.activation; _act raises on
        # anything unmappable instead of silently running gelu.
        arch.hidden_act = getattr(hf, "activation", "gelu") or "gelu"
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_epsilon",
                                          1e-5))
        if new:
            arch.num_kv_heads = int(hf.num_kv_heads)
        elif getattr(hf, "multi_query", True):
            arch.num_kv_heads = 1
        arch.tie_word_embeddings = False

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        D, H = c.head_dim, c.hidden_size
        G = c.num_kv_heads
        qpg = c.num_q_heads // G
        out = {}
        for name, t in tensors.items():
            name = name.replace("transformer.h.", "model.layers.")
            name = name.replace("transformer.ln_f.", "model.norm.")
            name = name.replace("transformer.word_embeddings.",
                                "model.embed_tokens.")
            name = name.replace(".self_attention.dense.",
                                ".self_attn.o_proj.")
            name = name.replace(".mlp.dense_h_to_4h.", ".mlp.fc1.")
            name = name.replace(".mlp.dense_4h_to_h.", ".mlp.fc2.")
            # ln_attn feeds attention (our input_ln); ln_mlp the MLP
            # (our post_ln); old-style shares input_layernorm.
            name = name.replace(".ln_attn.", ".input_layernorm.")
            name = name.replace(".ln_mlp.", ".post_attention_layernorm.")
            out[name] = t
        # Grouped fused QKV: per kv group, q_per_group q heads then that
        # group's k and v (reference: falcon.py _split_heads).
        from vllm_distributed_tpu.models.families import \
            split_grouped_qkv
        for i in range(c.num_layers):
            base = f"model.layers.{i}.self_attention.query_key_value"
            w = np.asarray(out.pop(base + ".weight"))
            A = f"model.layers.{i}.self_attn."
            (out[A + "q_proj.weight"], out[A + "k_proj.weight"],
             out[A + "v_proj.weight"]) = split_grouped_qkv(
                w, G, qpg, D)
            if base + ".bias" in out:
                b = np.asarray(out.pop(base + ".bias")).reshape(-1, 1)
                qb, kb, vb = split_grouped_qkv(b, G, qpg, D)
                out[A + "q_proj.bias"] = qb.reshape(-1)
                out[A + "k_proj.bias"] = kb.reshape(-1)
                out[A + "v_proj.bias"] = vb.reshape(-1)
        return super().params_from_hf_state_dict(out)


class PersimmonForCausalLM(LlamaForCausalLM):
    """Persimmon (Adept; reference: models/persimmon.py): LayerNorm
    block with biases, relu^2 non-gated MLP, partial rotary, per-head
    qk LayerNorms WITH biases, NeoX-style per-head-interleaved fused
    QKV."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.norm_type = "layernorm"
        arch.norm_bias = True
        arch.mlp_gated = False
        arch.mlp_bias = True
        arch.attention_bias = True
        arch.attention_out_bias = True
        arch.hidden_act = getattr(hf, "hidden_act", "relu2")
        arch.rotary_dim = int(arch.head_dim *
                              float(getattr(hf, "partial_rotary_factor",
                                            0.5)))
        arch.rms_norm_eps = float(getattr(hf, "layer_norm_eps", 1e-5))
        if getattr(hf, "qk_layernorm", True):
            arch.qk_norm = True
            arch.qk_norm_bias = True

    def params_from_hf_state_dict(self, tensors) -> dict:
        c = self.cfg
        D, H = c.head_dim, c.hidden_size
        N = c.num_q_heads
        out = {}
        for name, t in tensors.items():
            name = name.replace(".self_attn.dense.", ".self_attn.o_proj.")
            name = name.replace(".self_attn.q_layernorm.",
                                ".self_attn.q_norm.")
            name = name.replace(".self_attn.k_layernorm.",
                                ".self_attn.k_norm.")
            name = name.replace("model.final_layernorm.", "model.norm.")
            name = name.replace(".mlp.dense_h_to_4h.", ".mlp.fc1.")
            name = name.replace(".mlp.dense_4h_to_h.", ".mlp.fc2.")
            out[name] = t
        from vllm_distributed_tpu.models.families import \
            split_grouped_qkv
        for i in range(c.num_layers):
            base = f"model.layers.{i}.self_attn.query_key_value"
            # NeoX-style per-head [q, k, v] triplets = the grouped
            # layout with one q head per "group".
            w = np.asarray(out.pop(base + ".weight")).reshape(
                N, 3, D, H).reshape(N * 3 * D, H)
            A = f"model.layers.{i}.self_attn."
            (out[A + "q_proj.weight"], out[A + "k_proj.weight"],
             out[A + "v_proj.weight"]) = split_grouped_qkv(w, N, 1, D)
            b = np.asarray(out.pop(base + ".bias")).reshape(-1, 1)
            qb, kb, vb = split_grouped_qkv(b, N, 1, D)
            out[A + "q_proj.bias"] = qb.reshape(-1)
            out[A + "k_proj.bias"] = kb.reshape(-1)
            out[A + "v_proj.bias"] = vb.reshape(-1)
        return super().params_from_hf_state_dict(out)


class Cohere2ForCausalLM(CohereForCausalLM):
    """Cohere2 / Command-R7B (reference: models/commandr.py Cohere2
    variant): the Cohere parallel block + 3:1 sliding/full interleave
    where the FULL-attention layers are NoPE — rotary applies only
    under the sliding window (modeling_cohere2.Cohere2Attention gates
    apply_rotary_pos_emb on sliding_window)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        super().configure_arch(arch, hf)
        if arch.window_pattern is not None:
            arch.nope_layers = tuple(
                w == 0 for w in arch.window_pattern)


class SmolLM3ForCausalLM(LlamaForCausalLM):
    """SmolLM3 (reference: models/smollm3.py): llama block with every
    fourth layer NoPE (config.no_rope_layers, 0 = skip rotary)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        nrl = getattr(hf, "no_rope_layers", None)
        if nrl:
            arch.nope_layers = tuple(not bool(v) for v in nrl)


class Exaone4ForCausalLM(LlamaForCausalLM):
    """EXAONE-4 (reference: models/exaone4.py): POST-norm block (the
    sublayer output is normed before the residual add — the Olmo2
    layout), per-head q/k RMSNorm ahead of rope, and a 3:1
    sliding/full hybrid whose full-attention layers are NoPE
    ("global NoPE", modeling_exaone4.Exaone4Attention)."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.pre_norm = False
        arch.extra_layer_norms = True
        arch.qk_norm = True
        if arch.window_pattern is not None:
            arch.nope_layers = tuple(
                w == 0 for w in arch.window_pattern)


class VaultGemmaForCausalLM(LlamaForCausalLM):
    """VaultGemma (reference: models/vaultgemma.py): the Gemma block
    (scaled embeddings, gelu-tanh, +1-offset RMSNorm weights,
    query_pre_attn_scalar, attention + final logit soft-capping,
    alternating windows) but WITHOUT Gemma2's sandwich norms — the MLP
    pre-norm ships as ``pre_feedforward_layernorm``."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        import math
        arch.embed_scale = math.sqrt(arch.hidden_size)
        arch.hidden_act = "gelu_tanh"
        arch.tie_word_embeddings = True
        arch.attn_logit_softcap = float(
            getattr(hf, "attn_logit_softcapping", None) or 0.0)
        arch.final_logit_softcap = float(
            getattr(hf, "final_logit_softcapping", None) or 0.0)
        qpas = getattr(hf, "query_pre_attn_scalar", None)
        arch.query_pre_attn_scalar = float(qpas) if qpas else None

    def params_from_hf_state_dict(self, tensors) -> dict:
        renamed = {}
        for name, t in tensors.items():
            renamed[name.replace("pre_feedforward_layernorm",
                                 "post_attention_layernorm")] = t
        params = super().params_from_hf_state_dict(renamed)
        layers = params["layers"]
        for key in ("input_ln", "post_ln"):
            layers[key] = layers[key] + 1.0
        params["final_ln"] = params["final_ln"] + 1.0
        return params


class HunYuanDenseV1ForCausalLM(LlamaForCausalLM):
    """Tencent HunYuan dense v1 (reference: models/hunyuan_v1.py): the
    llama block + per-head q/k RMSNorm ahead of rope, shipped as
    ``query_layernorm``/``key_layernorm``."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.qk_norm = True

    def params_from_hf_state_dict(self, tensors) -> dict:
        renamed = {}
        for name, t in tensors.items():
            name = name.replace(".query_layernorm.", ".q_norm.")
            name = name.replace(".key_layernorm.", ".k_norm.")
            renamed[name] = t
        return super().params_from_hf_state_dict(renamed)


class FlexOlmoForCausalLM(MixtralForCausalLM):
    """FlexOlmo (reference: models/flex_olmo.py): the OLMo-2 POST-norm
    block (output norms before the residual adds, full-row q/k norms)
    with OLMoE-style routed experts."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        arch.pre_norm = False
        arch.extra_layer_norms = True
        arch.qk_norm_full = True
        arch.num_experts = hf.num_experts
        arch.num_experts_per_tok = hf.num_experts_per_tok
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", False))

    def params_from_hf_state_dict(self, tensors) -> dict:
        return super().params_from_hf_state_dict(_alias_moe_experts(
            tensors, self.cfg.num_layers, self.cfg.num_experts))


class GraniteMoeSharedForCausalLM(GraniteMoeForCausalLM):
    """GraniteMoeShared (reference: models/granitemoeshared.py): the
    GraniteMoe block plus an UNGATED dense shared MLP added to every
    token's routed output, shipped fused like the experts
    (shared_mlp.input_linear packs [gate; up])."""

    @classmethod
    def configure_arch(cls, arch: LlamaArchConfig, hf) -> None:
        super().configure_arch(arch, hf)
        arch.shared_expert_intermediate_size = int(
            getattr(hf, "shared_intermediate_size", 0) or 0)

    def param_specs(self) -> dict:
        specs = super().param_specs()
        if self.cfg.shared_expert_intermediate_size:
            specs["layers"].update({
                "shared_gate": P(None, None, MODEL_AXIS),
                "shared_up": P(None, None, MODEL_AXIS),
                "shared_down": P(None, MODEL_AXIS, None),
            })
        return specs

    def init_params(self, rng, scale: float = 0.02) -> dict:
        import jax
        import jax.numpy as jnp
        params = super().init_params(rng, scale)
        c = self.cfg
        Is = c.shared_expert_intermediate_size
        if Is:
            L, H = c.num_layers, c.hidden_size
            keys = iter(jax.random.split(jax.random.fold_in(rng, 29), 3))

            def norm(key, shape):
                return (scale * jax.random.normal(
                    key, shape, jnp.float32)).astype(c.dtype)

            params["layers"].update({
                "shared_gate": norm(next(keys), (L, H, Is)),
                "shared_up": norm(next(keys), (L, H, Is)),
                "shared_down": norm(next(keys), (L, Is, H)),
            })
        return params

    def params_from_hf_state_dict(self, tensors) -> dict:
        import jax.numpy as jnp
        c = self.cfg
        params = super().params_from_hf_state_dict(tensors)
        Is = c.shared_expert_intermediate_size
        if Is:
            gates, ups, downs = [], [], []
            for i in range(c.num_layers):
                pre = f"model.layers.{i}.shared_mlp."
                fused = np.asarray(tensors[pre + "input_linear.weight"])
                gates.append(fused[:Is].T)   # [H, Is]
                ups.append(fused[Is:].T)
                downs.append(np.asarray(
                    tensors[pre + "output_linear.weight"]).T)
            params["layers"].update({
                "shared_gate": jnp.asarray(np.stack(gates), c.dtype),
                "shared_up": jnp.asarray(np.stack(ups), c.dtype),
                "shared_down": jnp.asarray(np.stack(downs), c.dtype),
            })
        return params

    def mlp_block(self, lp: dict, x, lora_ctx=None):
        routed = super().mlp_block(lp, x, lora_ctx)
        if not self.cfg.shared_expert_intermediate_size:
            return routed
        from vllm_distributed_tpu.models.common import swiglu
        return routed + swiglu(x, lp["shared_gate"], lp["shared_up"],
                               lp["shared_down"], act=self._act)
