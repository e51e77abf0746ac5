"""Command A+ (``cohere2_moe``): Cohere's parallel block over a mixture of
experts, window and full attention layers in cache groups of their own.

The block is ``CohereForCausalLM``'s (models/families_ext.py): ONE
mean-centring LayerNorm without bias feeds attention and the feed-forward
alike, one residual add takes both (``h' = h + attention(x) + ffn(x)``),
interleaved rope, a tied head times ``logit_scale``; and
``Cohere2ForCausalLM``'s layer pattern: ``layer_types`` gives three
``sliding_attention`` layers (rope, a window of ``sliding_window``
positions) before each ``full_attention`` layer (NO rope, every
position). All of that runs in ``LlamaForCausalLM.run_layers``. What this
family adds:

* **The feed-forward is a mixture of experts.** Router ``s = sigmoid(x
  W_r)`` in float32 over all published experts, the ``num_experts_per_tok``
  largest are picked, their scores renormalised to sum 1
  (``norm_topk_prob``); no groups, no correction bias, no scaling factor:
  the published configuration has none. Routed experts are gated SiLU
  ``H -> I -> H`` through ``MixtralForCausalLM``'s grouped GEMMs.
  ``num_shared_experts`` further experts of the same width see every
  token and are combined by their MEAN
  (``shared_expert_combination_strategy: "average"``), added to the
  routed sum. The shared experts are held as ONE ``H -> n I -> H``
  product (their gate / up matrices side by side, their down matrices
  stacked) with the ``1 / n`` applied to its output: the same
  mathematics, three GEMMs where there were ``3 n``.
* **One chip's share of an expert layer** (``expert_share`` in
  ``config.json``, as models/deepseek.py documents it): ``num_experts``
  counts the experts HELD, the router keeps the published width, a pick
  on an absent expert adds nothing, the weights are renormalised over
  all picks, the shared experts and attention are whole. The dispatch is
  ``MixtralForCausalLM._share_dispatch`` over ``share_capacity`` rows,
  the one the DeepSeek family uses.
* **Two kinds of K/V** (``cfg.window_group``, which this family sets:
  ``LlamaForCausalLM.window_group_of``, where the cache-group methods
  live since a second family, models/smallthinker.py, shares them): the
  full layers' pages hold every token of a request, the window layers'
  pages hold what lies inside the window and are handed back behind it
  (core/kv_cache_manager.py ``GroupedKVCacheManager``). A window layer's
  cache ops read ``AttentionBatch.for_window_group()``, and the
  mega-kernel starts its page loop at the first block inside the window.

Expert weights are per-layer leaves in the serving layout
(``MixtralForCausalLM.serving_layout``), for the reason models/mixtral.py
gives, and so are the attention and shared-expert matrices: with two
cache groups the layer walk is unrolled
(``LlamaForCausalLM._run_layers_by_group``), as models/deepseek.py
unrolls its expert segment, and each product reads its layer's buffer in
place.

Not wired, refused at load by name: ``use_qk_norm``; a dense prefix
(``first_k_dense_replace`` > 0; the published value is 0, so
``prefix_dense_intermediate_size`` is unused); an expert share with
``enable_expert_parallel``, TPLA or pipeline stages (models/loader.py);
the cache groups with token or pipeline parallelism, KV transfer,
speculative decoding, multi-step scheduling, an fp8 cache or LoRA. The
vision tower of the published model is not in its ``config.json`` and is
not loaded. A checkpoint's tensor names are not available offline:
``params_from_hf_state_dict`` refuses.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.families_ext import Cohere2ForCausalLM
from vllm_distributed_tpu.models.llama import MODEL_AXIS
from vllm_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                 read_expert_share)


def _published(hf, key: str, want) -> None:
    got = getattr(hf, key, want)
    if got != want:
        raise ValueError(
            f"cohere2_moe: {key}={got!r} is not wired (the published "
            f"configuration has {want!r})")


class Cohere2MoeForCausalLM(MixtralForCausalLM):

    # Every large weight is a buffer a layer in the serving layout, the
    # dense ones too: the layer walk over two cache groups is unrolled
    # (LlamaForCausalLM._run_layers_by_group), and a product reads a
    # whole buffer in place where a slice of a stack is made as a copy.
    _PER_LAYER = (*MixtralForCausalLM._EXPERT_WEIGHTS, "wq", "wk", "wv",
                  "wo", "shared_gate", "shared_up", "shared_down")

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        Cohere2ForCausalLM.configure_arch(arch, hf)
        for key, want in (("expert_selection_fn", "sigmoid"),
                          ("shared_expert_combination_strategy", "average"),
                          ("first_k_dense_replace", 0),
                          ("use_parallel_block", True),
                          ("use_gated_activation", True),
                          ("position_embedding_type", "rope_gptj"),
                          ("rotary_pct", 1), ("hidden_act", "silu")):
            _published(hf, key, want)
        arch.num_experts = int(hf.num_experts)
        arch.num_experts_per_tok = int(hf.num_experts_per_tok)
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", True))
        # intermediate_size is the width of ONE expert, routed or shared
        # (the configuration has no key of its own for it).
        arch.moe_intermediate_size = int(hf.intermediate_size)
        arch.num_shared_experts = int(
            getattr(hf, "num_shared_experts", 0) or 0)
        arch.shared_expert_intermediate_size = (
            arch.num_shared_experts * arch.moe_intermediate_size)
        read_expert_share(arch, hf, "num_experts")
        arch.window_group = cls.window_group_of(arch)

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        specs = super().param_specs()
        if self.cfg.num_shared_experts:
            specs["layers"].update({
                "shared_gate": P(None, None, MODEL_AXIS),
                "shared_up": P(None, None, MODEL_AXIS),
                "shared_down": P(None, MODEL_AXIS, None),
            })
        return specs

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        params = super().init_params(rng, scale)
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        Is = c.shared_expert_intermediate_size
        keys = iter(jax.random.split(jax.random.fold_in(rng, 43), 4))

        def norm(shape):
            return (scale * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(c.dtype)

        layers = params["layers"]
        # The router scores every published expert, held here or not.
        layers["router"] = norm((L, H, c.router_experts))
        if Is:
            layers["shared_gate"] = norm((L, H, Is))
            layers["shared_up"] = norm((L, H, Is))
            layers["shared_down"] = norm((L, Is, H))
        return params

    def params_from_hf_state_dict(self, tensors) -> dict:
        raise NotImplementedError(
            "cohere2_moe: a checkpoint's tensor names are not available "
            "offline; only load_format='dummy' is wired")

    # ------------------------------------------------------------------
    # The feed-forward
    # ------------------------------------------------------------------
    def _route(self, lp: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Sigmoid scores in float32 over every published expert, top-k,
        renormalised over the picks (held or not)."""
        c = self.cfg
        scores = jax.nn.sigmoid(
            x.astype(jnp.float32) @ lp["router"].astype(jnp.float32))
        top_vals, top_idx = jax.lax.top_k(scores, c.num_experts_per_tok)
        if c.norm_topk_prob:
            top_vals = top_vals / top_vals.sum(axis=-1, keepdims=True)
        return top_idx, top_vals

    def mlp_block(self, lp: dict, x: jax.Array,
                  lora_ctx=None) -> jax.Array:
        with jax.named_scope("moe_router"):
            top_idx, top_vals = self._route(lp, x)
        out = self.moe_dispatch(lp, x, top_idx, top_vals)
        n = self.cfg.num_shared_experts
        if n:
            with jax.named_scope("moe_shared"):
                g = jax.nn.silu(x @ self._w(lp, "shared_gate"))
                u = x @ self._w(lp, "shared_up")
                shared = (g * u) @ self._w(lp, "shared_down")
                out = out + shared * jnp.asarray(1.0 / n, shared.dtype)
        return out.astype(x.dtype)
