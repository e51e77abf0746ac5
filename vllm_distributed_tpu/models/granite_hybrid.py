"""Granite 4.0-H (IBM, ``model_type`` ``granitemoehybrid``): Mamba-2
layers with a GQA layer where ``layer_types`` says ``attention``, a
dense SwiGLU (``shared_mlp``) after every mixer and, where
``num_local_experts`` > 0 (the 4.0-H Tiny and Small models), routed
experts beside it; Granite's four multipliers, no positional rotation,
a tied head.

    h0 = embedding_multiplier * embed[ids]
    a  = h + residual_multiplier * mix(norm1(h))
    x  = norm2(a)
    h' = a + residual_multiplier * (moe(x) + shared_mlp(x))
    logits = (norm_f(h) embed^T) / logits_scaling

``mix`` on an attention layer is plain causal GQA with the softmax scale
``attention_multiplier`` (1/64 at 64 lanes a head, not 1/8); on a mamba
layer the Mamba-2 mixer of models/mamba.py (``ops/ssd.py``). The stack
is Bamba's (models/bamba.py): per-kind stacked parameters, a scan over
the periods of ``layer_types``, K/V pages beside state rows. What
differs from Bamba: the multipliers, no rotation, the layer list's key,
the checkpoint's names (``shared_mlp.input_linear`` holds gate and up
fused), the head tied to the embedding without a transposed copy,
two 64-lane K/V heads sharing one 128-lane page row, and the experts.

**Routed experts** (``moe``; absent with ``num_local_experts`` 0, the
Micro family, whose tree and programs are what they were): the router
(``block_sparse_moe.router.layer``, no bias) scores every published
expert, the ``num_experts_per_tok`` largest LOGITS are kept and a softmax
over the kept ones weighs them (equal to a softmax over all, top-k,
renormalised: ``MixtralForCausalLM._route`` with ``norm_topk_prob``; a
test holds the two equal); an expert is a SwiGLU of width
``intermediate_size`` (the catalog row's note reads that key as the
expert's width; ``shared_intermediate_size`` is the shared MLP's). The
dispatch is models/mixtral.py's ``moe_dispatch``: the experts live in
the per-kind stacks (``a_w_gate`` ``[La, E, H, I]`` ...), which
``serving_layout`` splits into a buffer a layer for ``ragged_dot`` to
read in place, and Bamba's walk hands ``_mlp`` its layer's buffers.
With ``expert_share`` in ``config.json`` the chip holds
``num_local_experts`` of ``published_experts`` (models/deepseek.py's
docstring has the deployment this stands for): the router keeps its
published width, picks on experts held elsewhere add nothing, the
shared MLP and the mixers are whole, and the partial sum goes on.

Not wired (refused at load, by name): any ``position_embedding_type``
but ``nope``; for the routed experts expert parallelism, EPLB and
pipeline stages (refused for every stateful hybrid, models/loader.py)
and, under ``expert_share``, tensor parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.bamba import BambaForCausalLM
from vllm_distributed_tpu.models.llama import MODEL_AXIS, LlamaForCausalLM
from vllm_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                 read_expert_share)

_LANES = 128


class GraniteMoeHybridForCausalLM(BambaForCausalLM):

    # The experts' stacks, one a kind of layer (``serving_layout``
    # splits them into a buffer a layer, models/mixtral.py).
    _PER_LAYER = tuple(kind + name for kind in ("a_", "m_")
                       for name in MixtralForCausalLM._EXPERT_WEIGHTS)

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
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
        arch.num_experts = int(getattr(hf, "num_local_experts", 0) or 0)
        if arch.num_experts:
            arch.num_experts_per_tok = int(hf.num_experts_per_tok)
            # The width of ONE routed expert (the catalog row's reading
            # of the key; the shared MLP has a key of its own).
            arch.moe_intermediate_size = int(hf.intermediate_size)
        read_expert_share(arch, hf, "num_local_experts")
        if arch.num_experts and not (0 < arch.num_experts_per_tok
                                     <= arch.router_experts):
            raise ValueError(
                f"num_local_experts={arch.num_experts} needs "
                f"num_experts_per_tok between 1 and the router's width "
                f"({arch.router_experts}), not {arch.num_experts_per_tok}")
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
        if self.cfg.num_experts:
            for kind in ("a_", "m_"):
                specs["layers"].update({
                    kind + "router": P(None, None, None),
                    kind + "w_gate": P(None, None, None, MODEL_AXIS),
                    kind + "w_up": P(None, None, None, MODEL_AXIS),
                    kind + "w_down": P(None, None, MODEL_AXIS, None)})
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
        a standard deviation near 1 after the division. The routed
        experts and the router are at ``scale`` like the rest: at 0.02
        and 4096 channels a router logit has a standard deviation of
        1.28, so the kept ten of 72 weigh from about 0.26 down to 0.05."""
        c = self.cfg
        params = BambaForCausalLM.init_params(self, rng, scale)
        lay = params["layers"]
        if c.num_experts:
            H, E, I = c.hidden_size, c.num_experts, c.moe_intermediate_size
            ekeys = iter(jax.random.split(jax.random.fold_in(rng, 3), 8))
            for kind in ("a_", "m_"):
                depth = lay[kind + "pre_ln"].shape[0]
                for name, shape in (("router", (H, c.router_experts)),
                                    ("w_gate", (E, H, I)),
                                    ("w_up", (E, H, I)),
                                    ("w_down", (E, I, H))):
                    lay[kind + name] = (scale * jax.random.normal(
                        next(ekeys), (depth, *shape),
                        jnp.float32)).astype(c.dtype)
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
        if self.cfg.num_experts:
            params["layers"].update(self._experts_from_hf(tensors, prefix))
        return params

    def _experts_from_hf(self, tensors: dict, prefix: str) -> dict:
        """``block_sparse_moe``: ``input_linear.weight`` ``[E, 2 I, H]``
        (gate rows over up rows), ``output_linear.weight`` ``[E, H, I]``,
        ``router.layer.weight`` ``[experts, H]``, into the per-kind
        stacks; under an expert share the held experts' slabs alone."""
        c = self.cfg
        I = c.moe_intermediate_size
        lo, n = c.experts_held or (0, c.num_experts)
        moe = prefix + ".layers.{}.block_sparse_moe."

        def stack(ids, name, f):
            return jnp.asarray(np.stack(
                [f(np.asarray(tensors[moe.format(i) + name]))
                 for i in ids])).astype(c.dtype)

        out = {}
        for kind, ids in (("a_", self._attn_layers),
                          ("m_", self._mamba_layers)):
            out[kind + "router"] = stack(
                ids, "router.layer.weight", lambda a: a.T)
            out[kind + "w_gate"] = stack(
                ids, "input_linear.weight",
                lambda a: a[lo:lo + n, :I].transpose(0, 2, 1))
            out[kind + "w_up"] = stack(
                ids, "input_linear.weight",
                lambda a: a[lo:lo + n, I:].transpose(0, 2, 1))
            out[kind + "w_down"] = stack(
                ids, "output_linear.weight",
                lambda a: a[lo:lo + n].transpose(0, 2, 1))
        return out

    # ------------------------------------------------------------------
    def _route(self, lp: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """The published order: the largest logits kept, a softmax over
        the kept ones (float32, as every router here)."""
        logits = (x.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32))
        top_vals, top_idx = jax.lax.top_k(logits,
                                          self.cfg.num_experts_per_tok)
        return top_idx, jax.nn.softmax(top_vals, axis=-1)

    def _mlp(self, lp: dict, x: jax.Array) -> jax.Array:
        if not self.cfg.num_experts:
            return BambaForCausalLM._mlp(self, lp, x)
        routed = MixtralForCausalLM.mlp_block(self, lp, x)
        with jax.named_scope("moe_shared_mlp"):
            return routed + BambaForCausalLM._mlp(self, lp, x)

    def compute_logits(self, params: dict, hidden: jax.Array) -> jax.Array:
        if "lm_head" in params:
            return LlamaForCausalLM.compute_logits(self, params, hidden)
        # Tied: contract with the embedding's rows, no transposed copy.
        x = self._norm(hidden, params["final_ln"])
        logits = jnp.einsum("th,vh->tv", x, params["embed"],
                            preferred_element_type=jnp.float32)
        return logits * self.cfg.logit_multiplier
