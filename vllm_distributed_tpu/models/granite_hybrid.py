"""Granite 4.0-H (IBM, ``model_type`` ``granitemoehybrid``): Mamba-2
layers with a GQA layer where ``layer_types`` says ``attention``, a
dense SwiGLU (``shared_mlp``) after every mixer, Granite's four
multipliers, no positional rotation, a tied head.

    h0 = embedding_multiplier * embed[ids]
    h  = h + residual_multiplier * mix(norm1(h))
    h  = h + residual_multiplier * mlp(norm2(h))
    logits = (norm_f(h) embed^T) / logits_scaling

``mix`` on an attention layer is plain causal GQA with the softmax scale
``attention_multiplier`` (1/64 at 64 lanes a head, not 1/8); on a mamba
layer the Mamba-2 mixer of models/mamba.py (``ops/ssd.py``). The stack
is Bamba's (models/bamba.py): per-kind stacked parameters, a scan over
the periods of ``layer_types``, K/V pages beside state rows. What
differs from Bamba: the multipliers, no rotation, the layer list's key,
the checkpoint's names (``shared_mlp.input_linear`` holds gate and up
fused), the head tied to the embedding without a transposed copy, and
two 64-lane K/V heads sharing one 128-lane page row.

Not wired (refused at load, by name): routed experts
(``num_local_experts`` > 0: the 4.0-H Tiny and Small models) and any
``position_embedding_type`` but ``nope``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.models.bamba import BambaForCausalLM
from vllm_distributed_tpu.models.llama import LlamaForCausalLM

_LANES = 128


class GraniteMoeHybridForCausalLM(BambaForCausalLM):

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        if getattr(hf, "num_local_experts", 0):
            raise ValueError(
                f"num_local_experts={hf.num_local_experts}: the routed "
                f"experts of GraniteMoeHybrid are not wired (only the "
                f"dense shared_mlp of the 4.0-H Micro family)")
        pos = getattr(hf, "position_embedding_type", "nope")
        if pos != "nope":
            raise ValueError(
                f"position_embedding_type={pos!r} is not wired for "
                f"GraniteMoeHybrid (only 'nope': no rotation)")
        kinds = list(hf.layer_types)
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {sorted(set(kinds))} are not "
                             f"wired for GraniteMoeHybrid")
        BambaForCausalLM.configure_arch(arch, hf)
        arch.attn_indices = tuple(i for i, kind in enumerate(kinds)
                                  if kind == "attention")
        arch.rotary_dim = 0
        arch.intermediate_size = int(hf.shared_intermediate_size)
        arch.embed_scale = float(getattr(hf, "embedding_multiplier", 1.0))
        arch.residual_multiplier = float(
            getattr(hf, "residual_multiplier", 1.0))
        arch.sm_scale_override = float(
            getattr(hf, "attention_multiplier", None)
            or arch.head_dim ** -0.5)
        arch.logit_multiplier = 1.0 / float(
            getattr(hf, "logits_scaling", 1.0) or 1.0)
        arch.tie_word_embeddings = bool(
            getattr(hf, "tie_word_embeddings", True))
        # As many K/V heads to a page row as fill the lane tile.
        pack = 1
        while (2 * pack * arch.head_dim <= _LANES
               and arch.num_kv_heads % (2 * pack) == 0):
            pack *= 2
        arch.kv_pack = pack

    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        specs = BambaForCausalLM.param_specs(self)
        if self.cfg.tie_word_embeddings:
            del specs["lm_head"]
        return specs

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        """Seeded weights: normal x ``scale`` as for every model, except
        what a trained Mamba-2 layer would not have at that scale (the
        published initialisation, arXiv:2405.21060): ``A`` uniform in
        [1, 16], ``dt_bias`` the inverse softplus of a log-uniform step
        in [0.001, 0.1] (Bamba's ``log(1..Hm)`` with a zero bias decays
        a state to nothing in two tokens), convolution taps of order
        one with a bias; queries and keys at the scale that gives
        scores a standard deviation of one AFTER ``attention_multiplier``
        (at 0.02 and 1/64 they would have 0.1 and every query would
        attend evenly, whatever the scale or a rotation did); and a
        final norm of ``logits_scaling``, so the tied head's logits have
        a standard deviation near 1 after the division."""
        c = self.cfg
        params = BambaForCausalLM.init_params(self, rng, scale)
        lay = params["layers"]
        keys = iter(jax.random.split(jax.random.fold_in(rng, 2), 8))
        Lm, Hm = lay["m_A_log"].shape
        f32 = jnp.float32
        lay["m_A_log"] = jnp.log(jax.random.uniform(
            next(keys), (Lm, Hm), f32, 1.0, 16.0))
        step = jnp.exp(jax.random.uniform(
            next(keys), (Lm, Hm), f32, np.log(1e-3), np.log(1e-1)))
        lay["m_dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        for name in ("m_conv_x_w", "m_conv_bc_w"):
            lay[name] = (0.5 * jax.random.normal(
                next(keys), lay[name].shape, f32)).astype(c.dtype)
        if c.use_conv_bias:
            for name in ("m_conv_x_b", "m_conv_bc_b"):
                lay[name] = (0.1 * jax.random.normal(
                    next(keys), lay[name].shape, f32)).astype(c.dtype)
        qk = (c.head_dim ** 0.5 * c.hidden_size
              * c.sm_scale_override) ** -0.5
        for name in ("a_wq", "a_wk"):
            lay[name] = (qk * jax.random.normal(
                next(keys), lay[name].shape, f32)).astype(c.dtype)
        params["final_ln"] = jnp.full_like(
            params["final_ln"], 1.0 / c.logit_multiplier)
        if c.tie_word_embeddings:
            del params["lm_head"]
        return params

    def params_from_hf_state_dict(self, tensors: dict,
                                  prefix: str = "model") -> dict:
        """The source's names under Bamba's: ``shared_mlp.input_linear``
        is gate over up, ``post_attention_layernorm`` the norm before
        the MLP, ``model.norm`` the last."""
        I = self.cfg.intermediate_size
        alias = dict(tensors)
        for i in range(self.cfg.num_layers):
            ly = f"{prefix}.layers.{i}."
            fused = np.asarray(tensors[ly + "shared_mlp.input_linear.weight"])
            alias[ly + "feed_forward.gate_proj.weight"] = fused[:I]
            alias[ly + "feed_forward.up_proj.weight"] = fused[I:]
            alias[ly + "feed_forward.down_proj.weight"] = tensors[
                ly + "shared_mlp.output_linear.weight"]
            alias[ly + "pre_ff_layernorm.weight"] = tensors[
                ly + "post_attention_layernorm.weight"]
        alias[prefix + ".final_layernorm.weight"] = tensors[
            prefix + ".norm.weight"]
        params = BambaForCausalLM.params_from_hf_state_dict(
            self, alias, prefix)
        if self.cfg.tie_word_embeddings:
            del params["lm_head"]
        return params

    def compute_logits(self, params: dict, hidden: jax.Array) -> jax.Array:
        if "lm_head" in params:
            return LlamaForCausalLM.compute_logits(self, params, hidden)
        # Tied: contract with the embedding's rows, no transposed copy.
        x = self._norm(hidden, params["final_ln"])
        logits = jnp.einsum("th,vh->tv", x, params["embed"],
                            preferred_element_type=jnp.float32)
        return logits * self.cfg.logit_multiplier
