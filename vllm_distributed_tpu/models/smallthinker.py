"""SmallThinker (PowerInfer ``SmallThinker-21BA3B-Instruct`` /
``4BA0.6B``): a pre-norm block whose ROUTER STANDS BEFORE ATTENTION,
ReLU-gated experts, and full layers without rope among window layers
with it.

One layer, as published (T tokens, no bias anywhere)::

    x      = RMSNorm(h; w_in)
    logits = x W_r                                  float32
    (v, idx) = top-k(logits);  p = softmax(v)       over the k picks
    q, k, v  = x W_q, x W_k, x W_v                  (no q/k norm)
    rope_layout[l] == 1: rotate q and k;  0: no position signal (NoPE)
    sliding_window_layout[l] == 1: causal inside the window;  0: causal
    h1 = h + softmax(q k^T / sqrt(d)) v W_o
    y  = RMSNorm(h1; w_post)
    h' = h1 + sum_{e in idx} p_e (relu(y Wg_e) * (y Wu_e)) Wd_e

Attention, norms, rope, the window mask and the layer walk are
``LlamaForCausalLM.run_layers``; the grouped GEMMs and their dispatch
are ``MixtralForCausalLM.moe_dispatch`` with every expert held. What
this family adds:

* **The early router.** The picks and their weights are made from ``x``,
  the ATTENTION's normed input, and used on ``y`` after attention:
  ``pre_attention`` (the one hook ``layer_body`` has for it; a no-op in
  every other family) computes them under the named scope
  ``moe_early_router``, ahead of the layer's ``unified_write_attend``,
  and ``mlp_block`` receives them as ``early=``. In a deployment that
  streams expert weights from slower memory this is what lets the fetch
  overlap attention; here every expert is resident and the order is
  simply the model's mathematics.
* **ReGLU experts**: ``hidden_act`` is ``relu`` (the configuration has
  no such key; the model card says "sparse ReGLU") and
  ``MixtralForCausalLM._expert_ffn`` takes its activation from ``_act``.
* **The layouts**: ``sliding_window_layout`` with ``sliding_window_size``
  gives ``window_pattern``, ``rope_layout`` gives ``nope_layers`` (0 = no
  rope). Where window and full layers are mixed, each kind gets a cache
  group (``LlamaForCausalLM.window_group_of``, shared with
  models/cohere_moe.py), the walk is unrolled and the attention
  matrices are a buffer a layer beside the experts' (``_PER_LAYER``),
  for the reason ``_run_layers_by_group`` gives.
* **28 query heads in groups of 7** reach the attention kernel as they
  are; its launchers pad each group to 8 rows
  (``ops/pallas_attention.padded_q_heads``).

Refused at load, by name: ``moe_primary_router_apply_softmax: false``
(sigmoid scores; the 4B sibling), a ``rope_scaling``, windows of
unequal size, and what the window cache group refuses
(models/loader.py). The model card's "secondary experts" have no key in
``config.json`` and none is built. A checkpoint's tensor names are not
available offline: ``params_from_hf_state_dict`` refuses.
"""

import jax
import jax.numpy as jnp

from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM


class SmallThinkerForCausalLM(MixtralForCausalLM):

    def __init__(self, cfg) -> None:
        super().__init__(cfg)
        if cfg.window_group:
            # The walk over two cache groups is unrolled: every large
            # weight is a buffer a layer (models/cohere_moe.py).
            self._PER_LAYER = (*self._EXPERT_WEIGHTS, "wq", "wk", "wv",
                               "wo")

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        if not getattr(hf, "moe_primary_router_apply_softmax", True):
            raise ValueError(
                "smallthinker: moe_primary_router_apply_softmax=false "
                "(sigmoid routing weights) is not wired")
        if getattr(hf, "rope_scaling", None):
            raise ValueError(
                f"smallthinker: rope_scaling={hf.rope_scaling!r} is not "
                "wired (the published configuration has none)")
        L = arch.num_layers
        arch.num_experts = int(hf.moe_num_primary_experts)
        arch.num_experts_per_tok = int(hf.moe_num_active_primary_experts)
        arch.moe_intermediate_size = int(hf.moe_ffn_hidden_size)
        # No dense feed-forward anywhere: the base's stacks of it are
        # made at the expert's width and dropped (MixtralForCausalLM).
        arch.intermediate_size = arch.moe_intermediate_size
        arch.hidden_act = getattr(hf, "hidden_act", None) or "relu"
        arch.rope_scaling = None

        def layout(key: str) -> tuple:
            bits = tuple(int(b) for b in getattr(hf, key, None) or ())
            if len(bits) != L or set(bits) - {0, 1}:
                raise ValueError(
                    f"smallthinker: {key} must give 0 or 1 for each of "
                    f"the {L} layers, got {bits}")
            return bits

        window = getattr(hf, "sliding_window_size", None)
        if isinstance(window, (list, tuple)):
            if len(set(window)) > 1:
                raise ValueError(
                    f"smallthinker: sliding_window_size={list(window)}: "
                    "windows of unequal size are not wired")
            window = window[0] if window else None
        windowed = layout("sliding_window_layout")
        if any(windowed) and not window:
            raise ValueError("smallthinker: sliding_window_layout marks "
                             "window layers and sliding_window_size is "
                             "not set")
        arch.sliding_window = int(window) if all(windowed) else None
        arch.window_pattern = (
            tuple(int(window) if b else 0 for b in windowed)
            if any(windowed) and not all(windowed) else None)
        roped = layout("rope_layout")
        arch.nope_layers = (None if all(roped)
                            else tuple(not b for b in roped))
        arch.window_group = cls.window_group_of(arch)

    def params_from_hf_state_dict(self, tensors) -> dict:
        raise NotImplementedError(
            "smallthinker: a checkpoint's tensor names are not available "
            "offline; only load_format='dummy' is wired")

    # ------------------------------------------------------------------
    # The router before attention, the experts after it
    # ------------------------------------------------------------------
    def _route(self, lp: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """(picks ``[T, k]``, weights ``[T, k]``): logits in float32, the
        k largest, a softmax over the picks alone (so ``norm_topk_prob``
        changes nothing)."""
        logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        top_logits, top_idx = jax.lax.top_k(logits,
                                            self.cfg.num_experts_per_tok)
        return top_idx, jax.nn.softmax(top_logits, axis=-1)

    def pre_attention(self, lp: dict, x: jax.Array):
        """The family's ``_route`` of the attention's normed input."""
        with jax.named_scope("moe_early_router"):
            return self._route(lp, x)

    def mlp_block(self, lp: dict, x: jax.Array, lora_ctx=None,
                  early=None) -> jax.Array:
        """The experts over ``x`` (the rows after attention) at the picks
        ``early`` that ``pre_attention`` made before it. The block has no
        router of its own: without ``early`` there is nothing to route
        by, and routing on ``x`` would be another model."""
        if early is None:
            raise ValueError(
                "smallthinker: mlp_block needs the picks its router made "
                "from the attention's input (pre_attention)")
        return self.moe_dispatch(lp, x, *early)
