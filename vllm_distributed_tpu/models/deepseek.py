"""DeepSeek-V2/V3 family: Multi-head Latent Attention + DeepSeekMoE.

TPU-first re-design of the reference's DeepSeek support
(vllm/model_executor/models/deepseek_v2.py + the MLA backend family in
vllm/v1/attention/backends/mla/common.py and csrc/attention/mla/):

* **MLA** — the KV cache holds one compressed row per token (kv_c latent
  of width kv_lora_rank ++ the shared rope key k_pe) instead of per-head
  K/V. This implementation runs the ABSORBED form uniformly: W_UK folds
  into the query before attention and W_UV applies to the latent
  output after (common.py:96-120 `_forward_decode`), so attention is
  MQA over the latent cache (ops/mla.py) and the bucket lattice stays
  additive — no separate prefill/decode kernels.
* **DeepSeekMoE** — the Mixtral grouped-GEMM machinery (moe_dispatch)
  with DeepSeek gating on top: softmax scores with greedy or
  group-limited top-k and routed_scaling_factor (V2, HF 4.57 semantics),
  or sigmoid scores + e_score_correction_bias + top-2-sum group
  selection (V3 "noaux_tc"); plus ungated shared experts and the first
  ``first_k_dense_replace`` layers dense.

Parity target is transformers' DeepseekV2/V3 implementations (the V3
de-interleaved rope is score-equivalent to the V2 complex form because
the same permutation hits q and k; see models/common.py
apply_rope_pairwise).

**One chip's share of an expert layer** — ``expert_share`` in
``config.json`` (``{"published_experts": 256, "chips": 16, "index": 0}``)
says that ``n_routed_experts`` counts the experts HELD here, share
``index`` of a deployment in which ``chips`` chips divide each layer's
``published_experts``. The router and its correction bias keep the
published width; group selection and top-k run over all of it; picks
that land on absent experts contribute nothing; the shared expert is
computed whole. That partial sum is the layer's output: what
``_moe_ep_ragged``'s ``rank_fn`` computes on one rank, without its
``psum``. Nothing stands in for the absent chips. The dispatch works on
the rows this chip can be expected to hold (``share_capacity``: T x k x
held / published and a half more), not on all T x k picks, and takes
further passes of as many rows when a router puts more here. The
dispatch itself (``share_capacity``, ``_share_dispatch``) lives in
models/mixtral.py, where models/cohere_moe.py reaches it too.

**Expert weights are per-layer leaves** — ``w_gate`` / ``w_up`` /
``w_down`` are tuples of ``[E, H, Im]`` arrays, one a MoE layer, and
the MoE segment of ``run_layers`` is unrolled: ``jax.lax.ragged_dot``
then reads its layer's buffer in place. A slice of a layer-stacked
operand, static or dynamic, is materialised as a copy of the whole
slab for it (PERF.md, findings of PR 28). Everything else stays
stacked; the dense segment stays a scan.

The multi-token-prediction module (``num_nextn_predict_layers``) is a
draft head that does not enter the next-token logits; its weights are
not loaded (as transformers' own DeepseekV3 drops them).

Not wired (rejected at load with clear errors): token parallelism,
LoRA, quantization and EPLB redundancy for this family; and with an
expert share: ``enable_expert_parallel`` (the share IS one rank of it),
TPLA and pipeline parallelism (models/loader.py).
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (AttentionBatch,
                                                apply_rope_pairwise,
                                                compute_rope_cos_sin_pairwise,
                                                rms_norm)
from vllm_distributed_tpu.models.llama import (MODEL_AXIS, TOKEN_AXIS,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.models.mixtral import (MixtralForCausalLM,
                                                 read_expert_share)
from vllm_distributed_tpu.ops.mla import (latent_attention_values,
                                          latent_shard_dim,
                                          latent_storage_dim,
                                          tpla_latent_attention,
                                          write_latent_cache,
                                          write_latent_cache_tpla)

_DENSE_KEYS = frozenset({"gate", "up", "down"})
_MOE_KEYS = frozenset({"router", "router_bias", "w_gate", "w_up", "w_down",
                       "shared_gate", "shared_up", "shared_down"})


class DeepseekV2ForCausalLM(MixtralForCausalLM):

    # Quantized / LoRA serving of the absorbed projections is follow-up
    # work; both are rejected at load for this family.
    QUANT_TARGETS = ()
    LORA_TARGETS = ()
    SCORING = "softmax"  # V3 overrides to sigmoid + correction bias

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        arch.mla = True
        arch.q_lora_rank = getattr(hf, "q_lora_rank", None)
        arch.kv_lora_rank = hf.kv_lora_rank
        arch.qk_nope_head_dim = hf.qk_nope_head_dim
        arch.qk_rope_head_dim = hf.qk_rope_head_dim
        arch.v_head_dim = hf.v_head_dim
        arch.max_position_embeddings = getattr(
            hf, "max_position_embeddings", 4096)
        arch.num_experts = getattr(hf, "n_routed_experts", 0) or 0
        arch.num_experts_per_tok = getattr(hf, "num_experts_per_tok", 1)
        arch.moe_intermediate_size = getattr(hf, "moe_intermediate_size",
                                             None)
        n_shared = getattr(hf, "n_shared_experts", None) or 0
        arch.shared_expert_intermediate_size = (
            n_shared * (arch.moe_intermediate_size or 0))
        arch.first_k_dense_replace = (
            getattr(hf, "first_k_dense_replace", 0)
            if arch.num_experts else arch.num_layers)
        arch.routed_scaling_factor = getattr(hf, "routed_scaling_factor",
                                             1.0)
        arch.topk_method = getattr(hf, "topk_method", "greedy")
        arch.n_group = getattr(hf, "n_group", 1) or 1
        arch.topk_group = getattr(hf, "topk_group", 1) or 1
        arch.norm_topk_prob = bool(getattr(hf, "norm_topk_prob", False))
        # Router width: the published expert count under an expert share
        # (module docstring), else the experts there are.
        read_expert_share(arch, hf, "n_routed_experts")
        if getattr(hf, "moe_layer_freq", 1) not in (None, 1):
            raise ValueError("DeepSeek moe_layer_freq != 1 layouts are "
                             "not supported")
        if getattr(hf, "attention_bias", False):
            raise ValueError("DeepSeek attention_bias checkpoints are "
                             "not supported (no published model uses it)")

    # ------------------------------------------------------------------
    # Parameter layout
    # ------------------------------------------------------------------
    @property
    def tpla_shards(self) -> int:
        """TP shards of the latent cache (ops/mla.py TPLA layout); 1 =
        replicated (VDT_TPLA=0 / TP 1 / indivisible kv_lora_rank —
        models/loader.py decides once at load)."""
        return max(1, int(getattr(self.cfg, "tpla_shards", 1) or 1))

    @property
    def _n_dense(self) -> int:
        return min(self.cfg.first_k_dense_replace, self.cfg.num_layers)

    @property
    def _n_moe(self) -> int:
        return self.cfg.num_layers - self._n_dense

    def param_specs(self) -> dict:
        c = self.cfg
        if c.max_loras:
            raise ValueError("LoRA is not supported for the DeepSeek "
                             "family yet")
        specs = LlamaForCausalLM.param_specs(self)
        layer: dict = {
            "input_ln": P(None, None),
            "post_ln": P(None, None),
            # Latent projections: the down-projections and the shared
            # latent path are replicated (their outputs are per-token,
            # not per-head); the up-projections shard on the head dim —
            # or, under TPLA, on the LATENT dim (the paper's layout:
            # every rank runs all heads against its kv_lora_rank/TP
            # slice, so W_UK/W_UV shard where the cache does and the
            # absorbed ql comes out latent-sharded with no collective).
            "kv_a": P(None, None, None),
            "kv_a_ln": P(None, None),
        }
        if self.tpla_shards > 1:
            layer.update({
                "w_uk": P(None, MODEL_AXIS, None, None),
                "w_uv": P(None, MODEL_AXIS, None, None),
                # q projections and wo replicate under TPLA (all heads
                # on every rank; weight bytes are O(params), the latent
                # pool — the concurrency bottleneck — is what shards).
                "wo": P(None, None, None),
            })
        else:
            layer.update({
                "w_uk": P(None, None, MODEL_AXIS, None),
                "w_uv": P(None, None, MODEL_AXIS, None),
                "wo": P(None, MODEL_AXIS, None),
            })
        q_out = (P(None, None, None) if self.tpla_shards > 1
                 else P(None, None, MODEL_AXIS))
        if c.q_lora_rank:
            layer.update({
                "q_a": P(None, None, None),
                "q_a_ln": P(None, None),
                "q_b": q_out,
            })
        else:
            layer["wq"] = q_out
        if self._n_dense:
            layer.update({
                "gate": P(None, None, MODEL_AXIS),
                "up": P(None, None, MODEL_AXIS),
                "down": P(None, MODEL_AXIS, None),
            })
        if self._n_moe:
            layer["router"] = P(None, None, None)
            if self.SCORING == "sigmoid":
                layer["router_bias"] = P(None, None)
            # Per-layer leaves [E, H, Im] (module docstring): one spec
            # stands for every leaf of the tuple (a pytree prefix).
            if c.expert_parallel:
                ffn = P(MODEL_AXIS, None, None)
                layer.update({"w_gate": ffn, "w_up": ffn, "w_down": ffn})
            else:
                layer.update({
                    "w_gate": P(None, None, MODEL_AXIS),
                    "w_up": P(None, None, MODEL_AXIS),
                    "w_down": P(None, MODEL_AXIS, None),
                })
            if c.shared_expert_intermediate_size:
                layer.update({
                    "shared_gate": P(None, None, MODEL_AXIS),
                    "shared_up": P(None, None, MODEL_AXIS),
                    "shared_down": P(None, MODEL_AXIS, None),
                })
        specs["layers"] = layer
        return specs

    def slice_layer_params(self, layers: dict, start: int,
                           end: int) -> dict:
        """PP stage slicing with per-kind depths: attention tensors are
        stacked over all L layers, dense-MLP tensors over the first
        ``first_k_dense_replace`` and expert tensors over the rest."""
        fkd = self._n_dense
        ds, de = min(start, fkd), min(end, fkd)
        ms, me = max(start, fkd) - fkd, max(end, fkd) - fkd
        out = {}
        for k, v in layers.items():
            if k in _DENSE_KEYS:
                out[k] = v[ds:de]
            elif k in _MOE_KEYS:
                out[k] = v[ms:me]
            else:
                out[k] = v[start:end]
        return out

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        N = c.num_q_heads
        Pn, R, V = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        Lkv = c.kv_lora_rank
        nd, nm = self._n_dense, self._n_moe
        keys = iter(jax.random.split(rng, 24 + 3 * nm))

        def norm(shape):
            # Drawn in the serving dtype: a float32 draw of one layer's
            # expert slab alone is 0.94 GB at the 702B widths.
            return scale * jax.random.normal(next(keys), shape, c.dtype)

        layer: dict = {
            "input_ln": jnp.ones((L, H), c.dtype),
            "post_ln": jnp.ones((L, H), c.dtype),
            "kv_a": norm((L, H, Lkv + R)),
            "kv_a_ln": jnp.ones((L, Lkv), c.dtype),
            "w_uk": norm((L, Lkv, N, Pn)),
            "w_uv": norm((L, Lkv, N, V)),
            "wo": norm((L, N * V, H)),
        }
        if c.q_lora_rank:
            layer.update({
                "q_a": norm((L, H, c.q_lora_rank)),
                "q_a_ln": jnp.ones((L, c.q_lora_rank), c.dtype),
                "q_b": norm((L, c.q_lora_rank, N * (Pn + R))),
            })
        else:
            layer["wq"] = norm((L, H, N * (Pn + R)))
        if nd:
            I = c.intermediate_size
            layer.update({
                "gate": norm((nd, H, I)),
                "up": norm((nd, H, I)),
                "down": norm((nd, I, H)),
            })
        if nm:
            E = c.num_experts
            Im = c.moe_intermediate_size or c.intermediate_size
            layer.update({
                "router": norm((nm, H, c.router_experts)),
                "w_gate": tuple(norm((E, H, Im)) for _ in range(nm)),
                "w_up": tuple(norm((E, H, Im)) for _ in range(nm)),
                "w_down": tuple(norm((E, Im, H)) for _ in range(nm)),
            })
            if self.SCORING == "sigmoid":
                # Seeded, not zero: with a zero bias the choice and the
                # weights read the same scores. Small, as a trained
                # balancing bias is: sigmoid scores of a seeded router
                # crowd near 1 at the top, and a bias of 0.1 there sends
                # half the picks to the same hundred experts (105 of 256
                # hit by 512 picks, against 190 at 0.02 and 215 at 0;
                # PERF.md, findings of PR 28).
                layer["router_bias"] = 0.25 * scale * jax.random.normal(
                    next(keys), (nm, c.router_experts), jnp.float32)
            Is = c.shared_expert_intermediate_size
            if Is:
                layer.update({
                    "shared_gate": norm((nm, H, Is)),
                    "shared_up": norm((nm, H, Is)),
                    "shared_down": norm((nm, Is, H)),
                })
        embed = norm((c.vocab_size, H))
        return {
            "embed": embed,
            "layers": layer,
            "final_ln": jnp.ones((H, ), c.dtype),
            "lm_head": (embed.T if c.tie_word_embeddings else norm(
                (H, c.vocab_size))),
        }

    def params_from_hf_state_dict(self, tensors: dict[str, np.ndarray],
                                  ) -> dict:
        c = self.cfg
        L, H = c.num_layers, c.hidden_size
        N = c.num_q_heads
        Pn, R, V = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        Lkv = c.kv_lora_rank

        def t(name):
            return np.asarray(tensors[name])

        def stack(fmt, layers_range=range(L), transpose=True):
            mats = [t(fmt.format(i)) for i in layers_range]
            return jnp.asarray(
                np.stack([m.T if transpose else m for m in mats]),
                dtype=c.dtype)

        A = "model.layers.{}.self_attn."
        layer: dict = {
            "input_ln": stack("model.layers.{}.input_layernorm.weight",
                              transpose=False),
            "post_ln": stack(
                "model.layers.{}.post_attention_layernorm.weight",
                transpose=False),
            "kv_a": stack(A + "kv_a_proj_with_mqa.weight"),
            "kv_a_ln": stack(A + "kv_a_layernorm.weight",
                             transpose=False),
            "wo": stack(A + "o_proj.weight"),
        }
        # kv_b_proj [N*(P+V), Lkv] splits into the absorbed halves.
        uk, uv = [], []
        for i in range(L):
            kv_b = t(A.format(i) + "kv_b_proj.weight").reshape(
                N, Pn + V, Lkv)
            uk.append(kv_b[:, :Pn, :].transpose(2, 0, 1))  # [Lkv, N, P]
            uv.append(kv_b[:, Pn:, :].transpose(2, 0, 1))  # [Lkv, N, V]
        layer["w_uk"] = jnp.asarray(np.stack(uk), dtype=c.dtype)
        layer["w_uv"] = jnp.asarray(np.stack(uv), dtype=c.dtype)
        if c.q_lora_rank:
            layer.update({
                "q_a": stack(A + "q_a_proj.weight"),
                "q_a_ln": stack(A + "q_a_layernorm.weight",
                                transpose=False),
                "q_b": stack(A + "q_b_proj.weight"),
            })
        else:
            layer["wq"] = stack(A + "q_proj.weight")
        nd, nm = self._n_dense, self._n_moe
        M = "model.layers.{}.mlp."
        if nd:
            dense = range(nd)
            layer.update({
                "gate": stack(M + "gate_proj.weight", dense),
                "up": stack(M + "up_proj.weight", dense),
                "down": stack(M + "down_proj.weight", dense),
            })
        if nm:
            moe = range(nd, L)
            E = c.num_experts
            layer["router"] = stack(M + "gate.weight", moe)
            if self.SCORING == "sigmoid":
                layer["router_bias"] = jnp.asarray(np.stack([
                    t(M.format(i) + "gate.e_score_correction_bias")
                    for i in moe]), dtype=jnp.float32)

            def stack_experts(proj, transpose=True):
                per_layer = []
                for i in moe:
                    mats = [t(M.format(i) + f"experts.{e}.{proj}.weight")
                            for e in range(E)]
                    per_layer.append(jnp.asarray(np.stack(
                        [m.T if transpose else m for m in mats]),
                        dtype=c.dtype))
                return tuple(per_layer)

            layer["w_gate"] = stack_experts("gate_proj")
            layer["w_up"] = stack_experts("up_proj")
            layer["w_down"] = stack_experts("down_proj")
            if c.shared_expert_intermediate_size:
                layer.update({
                    "shared_gate": stack(
                        M + "shared_experts.gate_proj.weight", moe),
                    "shared_up": stack(
                        M + "shared_experts.up_proj.weight", moe),
                    "shared_down": stack(
                        M + "shared_experts.down_proj.weight", moe),
                })
        embed = jnp.asarray(t("model.embed_tokens.weight"), dtype=c.dtype)
        if c.tie_word_embeddings or "lm_head.weight" not in tensors:
            lm_head = embed.T
        else:
            lm_head = jnp.asarray(t("lm_head.weight").T, dtype=c.dtype)
        return {
            "embed": embed,
            "layers": layer,
            "final_ln": jnp.asarray(t("model.norm.weight"),
                                    dtype=c.dtype),
            "lm_head": lm_head,
        }

    # ------------------------------------------------------------------
    # KV cache: one latent row per token
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        # Replicated layout: latent rows are shared by every head (MQA),
        # so the cache replicates over the model axis; pages shard over
        # the token axis like the standard cache. TPLA layout: the "c"
        # lanes shard over the model axis (each rank holds its
        # kv_lora_rank/TP slice of every row) and the rope sidecar "pe"
        # replicates.
        if self.tpla_shards > 1:
            return {"c": P(None, TOKEN_AXIS, None, MODEL_AXIS),
                    "pe": P(None, TOKEN_AXIS, None, None)}
        return {"c": P(None, TOKEN_AXIS, None, None)}

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        c = self.cfg
        depth = num_layers if num_layers is not None else c.num_layers
        S = self.tpla_shards
        if S > 1:
            Cs = S * latent_shard_dim(c.kv_lora_rank, S)
            Rs = latent_storage_dim(c.qk_rope_head_dim, 0)
            dtype = cache_dtype or c.dtype
            return {
                "c": jnp.zeros((depth, num_pages, page_size, Cs), dtype),
                "pe": jnp.zeros((depth, num_pages, page_size, Rs), dtype),
            }
        Cs = latent_storage_dim(c.kv_lora_rank, c.qk_rope_head_dim)
        return {"c": jnp.zeros((depth, num_pages, page_size, Cs),
                               cache_dtype or c.dtype)}

    def kv_cache_page_bytes(self, page_size: int) -> int:
        """PER-RANK HBM bytes one page costs (what the worker divides a
        device's free HBM by). Replicated layout: the full latent row on
        every rank. TPLA: one kv_lora_rank/TP latent shard plus the
        replicated rope sidecar — ~1/TP the bytes, so ~TP x the pages
        fit the same per-device budget."""
        c = self.cfg
        S = self.tpla_shards
        if S > 1:
            lanes = (latent_shard_dim(c.kv_lora_rank, S) +
                     latent_storage_dim(c.qk_rope_head_dim, 0))
        else:
            lanes = latent_storage_dim(c.kv_lora_rank, c.qk_rope_head_dim)
        return (c.num_layers * page_size * lanes *
                jnp.dtype(c.dtype).itemsize)

    def quantize_params(self, params: dict) -> dict:
        if self.cfg.quantization:
            raise ValueError("quantization is not supported for the "
                             "DeepSeek family yet")
        return params

    # ------------------------------------------------------------------
    # Routing (overrides the Mixtral softmax+topk gate)
    # ------------------------------------------------------------------
    def _route(self, lp: dict, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        c = self.cfg
        T = x.shape[0]
        k = c.num_experts_per_tok
        E = c.router_experts
        logits = (x.astype(jnp.float32)
                  @ lp["router"].astype(jnp.float32))  # [T, E]
        if self.SCORING == "sigmoid":
            # V3 "noaux_tc" (HF DeepseekV3TopkRouter): sigmoid scores,
            # group selection by sum of each group's top-2 biased
            # scores, weights gathered from the UNbiased scores.
            scores = jax.nn.sigmoid(logits)
            choice = scores + lp["router_bias"][None, :]
            G = c.n_group
            grp = choice.reshape(T, G, E // G)
            top2 = jax.lax.top_k(grp, min(2, E // G))[0].sum(axis=-1)
            sel = self._group_mask(top2, c.topk_group, G, E)
            masked = jnp.where(sel, choice, 0.0)
            top_idx = jax.lax.top_k(masked, k)[1]
            top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
            if c.norm_topk_prob:
                top_vals = top_vals / (
                    top_vals.sum(axis=-1, keepdims=True) + 1e-20)
        else:
            # V2 (HF 4.57 DeepseekV2MoEGate): softmax scores; greedy or
            # group-limited-greedy selection. NOTE: HF 4.57 never
            # applies norm_topk_prob for V2 — mirrored here for parity.
            scores = jax.nn.softmax(logits, axis=-1)
            if c.topk_method == "group_limited_greedy":
                G = c.n_group
                grp_max = scores.reshape(T, G, E // G).max(axis=-1)
                sel = self._group_mask(grp_max, c.topk_group, G, E)
                masked = jnp.where(sel, scores, 0.0)
                top_vals, top_idx = jax.lax.top_k(masked, k)
            else:
                top_vals, top_idx = jax.lax.top_k(scores, k)
        return top_idx, top_vals * c.routed_scaling_factor

    @staticmethod
    def _group_mask(group_scores: jax.Array, topk_group: int, G: int,
                    E: int) -> jax.Array:
        """[T, G] group scores -> [T, E] bool mask keeping the top
        ``topk_group`` groups' experts."""
        T = group_scores.shape[0]
        gidx = jax.lax.top_k(group_scores, topk_group)[1]  # [T, kg]
        gmask = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], gidx].set(True)
        return jnp.repeat(gmask, E // G, axis=-1)

    def mlp_block(self, lp: dict, x: jax.Array,
                  lora_ctx=None) -> jax.Array:
        """MoE layer: routed experts + ungated shared experts (HF
        DeepseekV2MoE: shared output added on top, no gate — unlike
        Qwen2-MoE's sigmoid-gated shared expert)."""
        top_idx, top_vals = self._route(lp, x)
        out = self.moe_dispatch(lp, x, top_idx, top_vals)
        if self.cfg.shared_expert_intermediate_size:
            g = jax.nn.silu(x @ self._w(lp, "shared_gate"))
            u = x @ self._w(lp, "shared_up")
            out = out + (g * u) @ self._w(lp, "shared_down")
        return out.astype(x.dtype)

    def _sm_scale(self) -> float:
        """(P+R)^-0.5; V3 (HF DeepseekV3Attention) additionally folds
        the YaRN mscale^2 into the score scale when rope_scaling carries
        mscale_all_dim — real V3/R1 checkpoints all do. HF's V2 does
        NOT apply it (its yarn attention factor rides the cos/sin
        tables instead, models/common.py compute_rope_cos_sin_pairwise);
        each subclass mirrors its HF parity target exactly."""
        import math
        c = self.cfg
        scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
        if self.SCORING == "sigmoid" and c.rope_scaling:
            mscale_all_dim = c.rope_scaling.get("mscale_all_dim", 0)
            factor = c.rope_scaling.get("factor", 1.0)
            if mscale_all_dim and factor > 1:
                mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0
                scale = scale * mscale * mscale
        return scale

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def latent_attention_block(self, lp: dict, x: jax.Array, caches: dict,
                               layer_idx: jax.Array, batch: AttentionBatch,
                               cos: jax.Array, sin: jax.Array,
                               head_gate: Optional[jax.Array] = None,
                               ) -> tuple[jax.Array, dict]:
        """One layer's latent attention on the normed input ``x``
        ``[T, H]``: writes the step's latent rows into ``caches`` at
        ``layer_idx`` and returns (the block's output after ``wo``,
        caches). ``head_gate`` ``[T, N]`` multiplies each head's output
        before ``wo`` (models/bailing.py)."""
        c = self.cfg
        T = x.shape[0]
        N = c.num_q_heads
        Pn, R, V = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        Lkv = c.kv_lora_rank
        sm_scale = self._sm_scale()
        tpla = self.tpla_shards
        if c.q_lora_rank:
            qc = rms_norm(x @ self._w(lp, "q_a"), lp["q_a_ln"],
                          c.rms_norm_eps)
            q = qc @ self._w(lp, "q_b")
        else:
            q = x @ self._w(lp, "wq")
        q = q.reshape(T, N, Pn + R)
        q_nope, q_pe = q[..., :Pn], q[..., Pn:]
        ckv = x @ self._w(lp, "kv_a")  # [T, Lkv + R]
        kv_c = rms_norm(ckv[..., :Lkv], lp["kv_a_ln"], c.rms_norm_eps)
        k_pe = apply_rope_pairwise(
            ckv[..., Lkv:][:, None, :].astype(jnp.float32), cos,
            sin)[:, 0].astype(c.dtype)
        q_pe = apply_rope_pairwise(q_pe.astype(jnp.float32), cos,
                                   sin).astype(c.dtype)
        if tpla > 1:
            # Absorb W_UK into the query: MQA over the latent cache.
            # Under TPLA w_uk is latent-sharded, so ql comes out sharded
            # on its last dim — exactly the layout the sharded cache
            # attention consumes (no collective here).
            ql = jnp.einsum("tnp,knp->tnk", q_nope.astype(jnp.float32),
                            self._w(lp, "w_uk").astype(jnp.float32))
            c_all, pe_all = write_latent_cache_tpla(
                caches["c"], caches["pe"], kv_c, k_pe, batch,
                layer_idx, shards=tpla, kv_lora_rank=Lkv)
            caches = {**caches, "c": c_all, "pe": pe_all}
            v = tpla_latent_attention(
                ql.astype(c.dtype), q_pe, c_all, pe_all, batch,
                self._w(lp, "w_uv"), sm_scale=sm_scale,
                kv_lora_rank=Lkv, rope_dim=R, shards=tpla,
                layer=layer_idx).astype(jnp.float32)
        else:
            cache = write_latent_cache(
                caches["c"], jnp.concatenate([kv_c, k_pe], axis=-1),
                batch, layer_idx)
            caches = {**caches, "c": cache}
            # Absorbed for a decoding row, multi-head for the rows of a
            # chunk: ops/mla.latent_attention_values picks.
            v = latent_attention_values(
                q_nope, q_pe, cache, batch, self._w(lp, "w_uk"),
                self._w(lp, "w_uv"), sm_scale=sm_scale, kv_lora_rank=Lkv,
                rope_dim=R, layer=layer_idx)
        if head_gate is not None:
            v = v * head_gate.astype(jnp.float32)[:, :, None]
        o = v.reshape(T, N * V).astype(c.dtype) @ self._w(lp, "wo")
        return o, caches

    def run_layers(
        self,
        layer_params: dict,
        kv_caches: dict,
        hidden: jax.Array,  # [T, H]
        batch: AttentionBatch,
        first_layer: int = 0,
    ) -> tuple[jax.Array, dict]:
        c = self.cfg
        num_layers = layer_params["input_ln"].shape[0]
        cos, sin = compute_rope_cos_sin_pairwise(
            batch.positions, c.qk_rope_head_dim, c.rope_theta,
            c.rope_scaling, c.max_position_embeddings)

        def attn_block(lp, h, caches, layer_idx):
            x = rms_norm(h, lp["input_ln"], c.rms_norm_eps)
            o, caches = self.latent_attention_block(
                lp, x, caches, layer_idx, batch, cos, sin)
            return h + o, caches

        attn_keys = [k for k in layer_params
                     if k not in _DENSE_KEYS and k not in _MOE_KEYS]
        mlp_keys = {
            "dense": [k for k in layer_params if k in _DENSE_KEYS],
            "moe": [k for k in layer_params if k in _MOE_KEYS],
        }
        # Local segment split: stage covers global layers
        # [first_layer, first_layer + num_layers); the dense/MoE
        # boundary is first_k_dense_replace.
        nd_local = max(
            0, min(first_layer + num_layers, self._n_dense) - first_layer)

        def layer_body(kind, car, a_lp, m_lp, layer_idx):
            h, caches = car
            h, caches = attn_block(a_lp, h, caches, layer_idx)
            x2 = rms_norm(h, a_lp["post_ln"], c.rms_norm_eps)
            if kind == "dense":
                return h + LlamaForCausalLM.mlp_block(self, m_lp, x2), caches
            return h + self.mlp_block(m_lp, x2), caches

        def dense_scan(carry, seg_len):
            if seg_len == 0:
                return carry
            attn_lp = {k: layer_params[k][:seg_len] for k in attn_keys}
            # Dense/MoE stacks are indexed in their OWN depth space and
            # slice_layer_params already rebased them per stage, so each
            # kind's stack starts at 0 locally.
            mlp_lp = {k: layer_params[k][:seg_len]
                      for k in mlp_keys["dense"]}
            ids = jnp.arange(seg_len, dtype=jnp.int32)[:, None]

            def body(car, xs):
                return layer_body("dense", car, *xs), None

            carry, _ = jax.lax.scan(body, carry, (attn_lp, mlp_lp, ids))
            return carry

        carry = (hidden, dict(kv_caches))
        carry = dense_scan(carry, nd_local)
        # The MoE segment is unrolled: each layer's expert weights are
        # leaves of their own, which a scan cannot index and which
        # ragged_dot then reads in place (module docstring).
        for j in range(num_layers - nd_local):
            i = nd_local + j
            carry = layer_body(
                "moe", carry, {k: layer_params[k][i] for k in attn_keys},
                {k: layer_params[k][j] for k in mlp_keys["moe"]},
                jnp.full((1, ), i, jnp.int32))
        hidden, caches = carry
        return hidden, caches


class DeepseekV3ForCausalLM(DeepseekV2ForCausalLM):
    """DeepSeek-V3/R1: V2's MLA + MoE structure with sigmoid scoring,
    the aux-loss-free correction bias, and top-2-sum group selection
    (HF DeepseekV3TopkRouter; reference:
    vllm/model_executor/models/deepseek_v3.py)."""

    SCORING = "sigmoid"

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        super().configure_arch(arch, hf)
        arch.topk_method = "noaux_tc"
