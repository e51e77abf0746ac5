"""Plain reference forward passes of OLMo-2 (``Olmo2ForCausalLM``) and
OLMoE (``OlmoeForCausalLM``): one whole sequence, dense causal
attention, no cache, no kernels, no batching, straightforward
``jax.numpy`` in float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the
served configuration's own (bf16) values, upcast one layer at a time
inside the scan over layers, so OLMoE's expert stack never exists in
float32 as a whole.

After the published modelling code (HF ``modeling_olmo2.py`` /
``modeling_olmoe.py``):

- both: rotary embedding in the rotate-half form over the whole head,
  theta from the config, no scaling; RMSNorm over the WHOLE q and k
  projection rows (not per head) before the head reshape; SwiGLU.
- OLMo-2: post-norm block. ``x = x + norm(attn(x))``,
  ``x = x + norm(mlp(x))``: sub-layers read the raw residual stream.
- OLMoE: pre-norm block; router = softmax over all experts in float32,
  top-k, weights NOT renormalised unless ``norm_topk_prob``; every
  expert is computed for every token here and weighted by its routing
  weight (0 where not chosen): the definition, not a dispatch.

``control`` makes the negative controls: ``"no_attention"`` zeroes the
attention output; ``"first_experts"`` replaces the router's choice by
experts 0..k-1 (keeping their own probabilities).
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def logprobs(params: dict, cfg: dict, ids, control: str | None = None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    S = ids.shape[0]
    QH = cfg["num_attention_heads"]
    KVH = cfg.get("num_key_value_heads") or QH
    H = cfg["hidden_size"]
    D = cfg.get("head_dim") or H // QH
    eps = cfg["rms_norm_eps"]
    moe = "num_experts" in cfg
    top_k = cfg.get("num_experts_per_tok")

    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]

    def rope(x):
        half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], -1)
        return x * cos + half * sin

    causal = jnp.tril(jnp.ones((S, S), bool))

    def attention(x, lp):
        q = _rms(x @ lp["wq"], lp["q_norm"], eps)
        k = _rms(x @ lp["wk"], lp["k_norm"], eps)
        v = x @ lp["wv"]
        q = rope(q.reshape(S, QH, D))
        k = rope(k.reshape(S, KVH, D))
        v = v.reshape(S, KVH, D)
        k = jnp.repeat(k, QH // KVH, axis=1)
        v = jnp.repeat(v, QH // KVH, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * D ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v).reshape(S, QH * D) @ lp["wo"]
        return jnp.zeros_like(o) if control == "no_attention" else o

    def dense_mlp(x, lp):
        return (jax.nn.silu(x @ lp["gate"]) * (x @ lp["up"])) @ lp["down"]

    def moe_mlp(x, lp):
        probs = jax.nn.softmax(x @ lp["router"], axis=-1)       # [S, E]
        if control == "first_experts":
            idx = jnp.broadcast_to(jnp.arange(top_k), (S, top_k))
            vals = probs[:, :top_k]
        else:
            vals, idx = jax.lax.top_k(probs, top_k)
        if cfg.get("norm_topk_prob"):
            vals = vals / vals.sum(-1, keepdims=True)
        weight = jnp.zeros_like(probs).at[
            jnp.arange(S)[:, None], idx].set(vals)               # [S, E]
        g = jnp.einsum("sh,ehi->esi", x, lp["w_gate"])
        u = jnp.einsum("sh,ehi->esi", x, lp["w_up"])
        y = jnp.einsum("esi,eih->esh", jax.nn.silu(g) * u, lp["w_down"])
        return jnp.einsum("esh,se->sh", y, weight)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        if moe:   # OLMoE: pre-norm
            x = x + attention(_rms(x, lp["input_ln"], eps), lp)
            x = x + moe_mlp(_rms(x, lp["post_ln"], eps), lp)
        else:     # OLMo-2: post-norm
            x = x + _rms(attention(x, lp), lp["post_attn_ln"], eps)
            x = x + _rms(dense_mlp(x, lp), lp["post_ffw_ln"], eps)
        return x, None

    keys = ["wq", "wk", "wv", "wo", "q_norm", "k_norm"]
    keys += (["input_ln", "post_ln", "router", "w_gate", "w_up", "w_down"]
             if moe else
             ["post_attn_ln", "post_ffw_ln", "gate", "up", "down"])
    x = params["embed"][ids].astype(F32)
    x, _ = jax.lax.scan(layer, x, {k: params["layers"][k] for k in keys})
    x = _rms(x, params["final_ln"].astype(F32), eps)
    logits = x @ params["lm_head"].astype(F32)
    return jax.nn.log_softmax(logits, axis=-1)


CONTROLS = {"Olmo2ForCausalLM": ["no_attention"],
            "OlmoeForCausalLM": ["no_attention", "first_experts"]}
