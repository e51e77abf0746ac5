"""ZAYA1 (Zyphra, ``model_type`` ``zaya``): compressed convolutional
attention (CCA, arXiv:2510.04476) and a top-1 expert layer behind an MLP
router (ZAYA1 technical report, arXiv:2511.17127), every layer of one
kind (``layer_types``: ``hybrid``), a tied head.

With ``s`` the stream ``[T, H]`` (the embedding's rows at the start) and
``r`` the router's state ``[T, R]`` (zero before layer 0), a layer is

    s = merge_a(s, cca(norm1(s)))
    y, r = experts(norm2(s), r);  s = merge_m(s, y)
    merge(s, y) = a_s * (s + b_s) + a_y * (y + b_y)      (four H-vectors)

and ``logits = norm_f(s) embed^T``.

* **CCA.** ``x W`` gives ``u = [q~, k~]`` (``C = Lq + Lk`` channels,
  ``Lq = Hq d``, ``Lk = Hk d``: queries, keys and values are projected
  DOWN and attention runs inside that space) and two value halves
  ``va``, ``vb`` of ``Lk / 2``. Head 0 of the values is ``va_t``, head 1
  the PREVIOUS token's ``vb_{t-1}``. ``u`` passes two causal two-tap
  convolutions over the sequence, ``c0`` depthwise and ``c1`` mixing the
  ``d`` channels inside each of the ``Hq + Hk`` heads
  (``c1_t[h] = c0_{t-1}[h] W1[0, h] + c0_t[h] W1[1, h] + b1[h]``), zeros
  before the sequence on ``u`` once (so ``c0 = b0`` there). The mean of
  the pre-convolution latents is added back (``mq[h] = (q~[h] +
  k~[h // G]) / 2``, ``mk[g]`` its mean over the group), each head is
  divided by its 2-norm and scaled by ``sqrt(d)`` (the keys also by a
  learned temperature a K/V head), the first ``rotary_dim`` lanes of a
  head are rotated, and plain causal GQA with the scale ``d ** -0.5``
  follows; ``W_O`` is ``[Lq, H]``.
* **What a token needs of its predecessor** is carried in two state
  rows a layer a sequence beside the K/V pages, in ``ops/conv1d.py``'s
  whole-tile layout and read and replaced in place by its kernel, once
  each: ``cca_u`` holds ``u_{t-1}`` (the depthwise convolution's own
  carried input), ``cca_cv`` holds ``c0_{t-1}`` and ``vb_{t-1}`` (a
  two-tap pass with the taps (1, 0): a shift by one token). The kernel
  reads a row of a sequence at position 0 as zeros; ``c0_{-1} = b0`` is
  put in its place by a select, so a fresh row is ``(0, b0, 0)``.
* **Experts.** ``r_l = x W_D + b_D + g_l * r_{l-1}``, ``z = norm(r_l)``,
  ``logits = W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2)`` over ``E + 1``
  outcomes, ``p = softmax`` in float32, the pick ``argmax(p + beta)``,
  its weight the picked ``p``. Picks ``0..E-1`` are SwiGLU experts
  through ``MixtralForCausalLM.moe_dispatch``; pick ``E`` is no expert:
  the token takes ``p x`` and reaches no grouped GEMM's groups. The
  router runs in float32 at the highest matrix precision (a few
  thousand operations a token): a pick is discrete, and a product
  rounded to bfloat16 flips it where two outcomes lie close.

The stack is a ``lax.scan`` over the layer index that carries
``(s, r, caches)`` and reads the layer-stacked weights where they lie;
the expert weights are per-layer buffers (``serving_layout``) behind a
switch, as in ``LlamaForCausalLM.run_layers``.

What the published configuration does not settle is listed in
``benchmarks/configs/zaya1-8b.json`` (``assumed``) and in the
reference's docstring (``benchmarks/references/zaya.py``). The
checkpoint's tensor names are not available where this was written:
only seeded weights load (``load_format="dummy"``).

Not wired (refused at load, by name): tensor, expert and pipeline
parallelism (the rows and the router state are unsharded), a sliding
window (``hybrid_sliding``: the family's 74B), convolutions of other
than two taps, more than one expert a token, weight quantization, LoRA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (apply_rope_single,
                                                compute_rope_cos_sin,
                                                rms_norm)
from vllm_distributed_tpu.models.llama import (TOKEN_AXIS,
                                               LlamaForCausalLM)
from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
from vllm_distributed_tpu.ops.attention import write_kv_and_attend
from vllm_distributed_tpu.ops.conv1d import (causal_conv1d_rows, rows_shape,
                                             step_entries, whole_tiles)
from vllm_distributed_tpu.ops.mamba import build_segment_info

_TAPS = 2
_HIGHEST = jax.lax.Precision.HIGHEST
# Under the root of a head's 2-norm.
_NORM_EPS = 1e-6
# What a router's logits are scaled to at seeded weights: the top pick
# of E + 1 then weighs about a third, not 1 / (E + 1).
_SEED_LOGIT_STD = 1.75


def merge(s: jax.Array, y: jax.Array, m: jax.Array) -> jax.Array:
    """``a_s * (s + b_s) + a_y * (y + b_y)``, ``m`` the four vectors in
    that order (ones and zeros: the plain residual)."""
    f32 = jnp.float32
    a_s, b_s, a_y, b_y = m.astype(f32)
    return (a_s * (s.astype(f32) + b_s)
            + a_y * (y.astype(f32) + b_y)).astype(s.dtype)


def unit_heads(x: jax.Array) -> jax.Array:
    """Each head of ``[T, heads, d]`` float32 over its 2-norm, times
    ``sqrt(d)``."""
    d = x.shape[-1]
    return x * (d ** 0.5 * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + _NORM_EPS))


def carried_latents(u: jax.Array, vb: jax.Array, w0: jax.Array,
                    b0: jax.Array, rows_u: jax.Array, rows_cv: jax.Array,
                    layer: jax.Array, seg, entries: dict):
    """What a step's tokens need of their predecessors, over the flat
    ragged batch: ``c0_t`` (the depthwise two-tap convolution of ``u``
    ``[T, C]``, taps ``w0`` ``[2, C]`` with tap 0 the older, bias
    ``b0``), ``c0_{t-1}`` and ``vb_{t-1}`` (``vb`` ``[T, Lk / 2]``), a
    chunk's first token reading them from its sequence's rows in the
    layer-stacked ``rows_u`` (``u_{t-1}``) and ``rows_cv`` (``c0_{t-1}``,
    ``vb_{t-1}``), which come back holding the chunk's last token's.
    ``c0`` is accumulated in float32 over the inputs the row carries and
    then held as the row holds it, so a chunk that ends anywhere and a
    decode step read the same ``c0_{t-1}``. Returns (c0, c0_prev,
    vb_prev, rows_u, rows_cv)."""
    C = u.shape[1]
    f32 = jnp.float32
    with jax.named_scope("cca_mix"):
        c0, rows_u = causal_conv1d_rows(u, w0, b0, rows_u, layer, seg,
                                        entries)
        c0 = c0.astype(u.dtype)
    with jax.named_scope("cca_shift"):
        # Taps (1, 0): the previous token's input, the row's at a
        # chunk's first token.
        half = vb.shape[1]
        width = whole_tiles(C + half)
        shift = jnp.zeros((_TAPS, width), f32).at[0].set(1.0)
        pad = jnp.zeros((u.shape[0], width - C - half), u.dtype)
        prev, rows_cv = causal_conv1d_rows(
            jnp.concatenate([c0, vb, pad], axis=-1), shift, None, rows_cv,
            layer, seg, entries)
        prev = prev.astype(u.dtype)
        # Before a sequence's first token u = 0, so c0 = b0 there; the
        # kernel reads that row as zeros.
        has_prev = (seg.off > 0) | seg.has_init
        c0_prev = jnp.where(has_prev[:, None], prev[:, :C], b0)
    return c0, c0_prev, prev[:, C:C + half], rows_u, rows_cv


class ZayaForCausalLM(MixtralForCausalLM):

    QUANT_TARGETS = ()
    LORA_TARGETS = ()
    STATEFUL = True
    # A snapshot of the rows resumes a request only together with the
    # K/V pages of its prefix (core/state_cache.py).
    STATE_ONLY = False

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        kinds = set(hf.layer_types)
        if kinds != {"hybrid"} or getattr(hf, "sliding_window", None):
            raise ValueError(
                f"layer_types {sorted(kinds)} with sliding_window="
                f"{getattr(hf, 'sliding_window', None)!r} are not wired "
                f"for Zaya (only 'hybrid': full causal attention)")
        for key in ("cca_time0", "cca_time1"):
            if int(getattr(hf, key)) != _TAPS:
                raise ValueError(f"{key}={getattr(hf, key)} is not wired "
                                 f"for Zaya (two taps only)")
        if int(hf.num_experts_per_tok) != 1:
            raise ValueError(
                f"num_experts_per_tok={hf.num_experts_per_tok} is not "
                f"wired for Zaya (the router picks one outcome)")
        for key, want in (("attention_bias", False), ("lm_head_bias", False),
                          ("tie_word_embeddings", True),
                          ("hidden_act", "silu"), ("zaya_use_mod", True),
                          ("zaya_use_eda", True),
                          ("scale_residual_merge", True)):
            if getattr(hf, key, want) != want:
                raise ValueError(f"{key}={getattr(hf, key)!r} is not "
                                 f"wired for Zaya")
        if arch.num_q_heads % arch.num_kv_heads or arch.num_kv_heads % 2:
            raise ValueError("Zaya needs an even count of K/V heads that "
                             "divides the query heads")
        rope = hf.rope_parameters["hybrid"]
        arch.rope_theta = float(rope["rope_theta"])
        arch.rope_scaling = None
        arch.rotary_dim = int(arch.head_dim * rope["partial_rotary_factor"])
        arch.num_experts = int(hf.num_experts)
        arch.num_experts_per_tok = 1
        arch.norm_topk_prob = False
        arch.moe_intermediate_size = int(hf.moe_intermediate_size)
        arch.router_hidden_size = int(hf.router_hidden_size)
        arch.stateful = True
        arch.cca = True
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    def quantize_params(self, params: dict) -> dict:
        if self.cfg.quantization:
            raise ValueError("weight quantization is not wired for Zaya; "
                             "drop --quantization")
        return params

    # ------------------------------------------------------------------
    # Geometry
    # ------------------------------------------------------------------
    @property
    def _widths(self) -> tuple[int, int, int]:
        """(Lq, Lk, C): the query and key latents and their sum, the
        convolutions' channels."""
        c = self.cfg
        Lq, Lk = c.num_q_heads * c.head_dim, c.num_kv_heads * c.head_dim
        return Lq, Lk, Lq + Lk

    def param_specs(self) -> dict:
        if self.cfg.max_loras:
            raise ValueError("LoRA is not wired for Zaya")
        rep = {n: P(*([None] * n)) for n in (1, 2, 3, 4, 5)}
        layer = {
            "input_ln": rep[2], "post_ln": rep[2],
            "wqkv": rep[3], "wo": rep[3],
            "conv0_w": rep[3], "conv0_b": rep[2],
            "conv1_w": rep[5], "conv1_b": rep[2], "k_temp": rep[2],
            "attn_merge": rep[3], "mlp_merge": rep[3],
            "router_down": rep[3], "router_down_b": rep[2],
            "router_eda": rep[2], "router_ln": rep[2],
            "router_w1": rep[3], "router_b1": rep[2],
            "router_w2": rep[3], "router_b2": rep[2],
            "router_w3": rep[3], "router_beta": rep[2],
            "w_gate": rep[4], "w_up": rep[4], "w_down": rep[4],
        }
        return {"embed": rep[2], "layers": layer, "final_ln": rep[1]}

    def slice_layer_params(self, layers: dict, start: int, end: int):
        raise ValueError(
            "pipeline parallelism over Zaya is not wired (the router's "
            "state crosses every layer boundary)")

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        """Seeded weights: normal x ``scale`` except where that would
        hide a mechanism from a comparison (each choice is listed under
        ``assumed`` in ``benchmarks/configs/zaya1-8b.json``): ``W_O`` at
        three times the scale, so that attention carries the stream (at
        ``scale`` one routing pick flipped by bfloat16 rounding moved a
        served logprob by up to 1.0 on the chip, level with what taking
        a convolution away moves, and at twice it by up to 0.4);
        depthwise
        taps of order one (x 0.5) and ``W1`` at ``(2 d) ** -0.5``, so
        that ``c1`` stands beside the mean it is added to; biases x 0.1;
        the keys' temperature uniform in [0.5, 2]; merge scales uniform
        in [0.8, 1.2] and merge biases x 0.05; the router state's
        ``g`` uniform in [0.3, 0.9]; the router's two inner layers at
        ``fan_in ** -0.5`` (what a GELU keeps of a unit input: 0.425)
        and its last scaled to logits of standard deviation 1.75, where
        the top pick of E + 1 weighs about a third, the second and the
        last with columns that sum to zero (a GELU's output has a mean:
        uncentred, one expert in three drew under a hundredth of the
        tokens and 64 tokens hit 13 of 16, not the 15.4 they hit so);
        ``beta`` x 0.02; the tied
        embedding at ``H ** -0.5``: logits of standard deviation one."""
        c = self.cfg
        L, H, E = c.num_layers, c.hidden_size, c.num_experts
        I, R, D = c.moe_intermediate_size, c.router_hidden_size, c.head_dim
        Lq, Lk, C = self._widths
        heads = c.num_q_heads + c.num_kv_heads
        f32 = jnp.float32
        keys = iter(jax.random.split(rng, 32))

        def normal(shape, std=scale, dtype=None):
            return (std * jax.random.normal(next(keys), shape, f32)
                    ).astype(dtype or c.dtype)

        def uniform(shape, lo, hi, dtype=None):
            return jax.random.uniform(next(keys), shape, f32, lo, hi
                                      ).astype(dtype or c.dtype)

        def centred(shape, std):
            # Columns that sum to zero over their inputs: a GELU's
            # output has a mean, and it would favour some outcomes.
            w = std * jax.random.normal(next(keys), shape, f32)
            return (w - jnp.mean(w, axis=-2, keepdims=True)).astype(c.dtype)

        def merge_vectors():
            return jnp.stack([uniform((L, H), 0.8, 1.2), normal((L, H), 0.05),
                              uniform((L, H), 0.8, 1.2),
                              normal((L, H), 0.05)], axis=1)

        kept = 0.425 * R         # E[gelu(n)^2] of a unit normal, R times
        layers = {
            "input_ln": jnp.ones((L, H), c.dtype),
            "post_ln": jnp.ones((L, H), c.dtype),
            # Columns: q~ (Lq), k~ (Lk), va, vb (Lk / 2 each).
            "wqkv": normal((L, H, C + Lk)),
            "wo": normal((L, Lq, H), 3 * scale),
            "conv0_w": normal((L, _TAPS, C), 0.5),       # tap 0 the older
            "conv0_b": normal((L, C), 0.1),
            "conv1_w": normal((L, _TAPS, heads, D, D), (2 * D) ** -0.5),
            "conv1_b": normal((L, C), 0.1),
            "k_temp": uniform((L, c.num_kv_heads), 0.5, 2.0, f32),
            "attn_merge": merge_vectors(),
            "mlp_merge": merge_vectors(),
            "router_down": normal((L, H, R)),
            "router_down_b": normal((L, R), 0.1),
            "router_eda": uniform((L, R), 0.3, 0.9),
            "router_ln": jnp.ones((L, R), c.dtype),
            "router_w1": normal((L, R, R), R ** -0.5),
            "router_b1": normal((L, R), 0.1),
            "router_w2": centred((L, R, R), kept ** -0.5),
            "router_b2": normal((L, R), 0.1),
            "router_w3": centred((L, R, E + 1),
                                 _SEED_LOGIT_STD * kept ** -0.5),
            "router_beta": normal((L, E + 1), 0.02, f32),
            "w_gate": normal((L, E, H, I)),
            "w_up": normal((L, E, H, I)),
            "w_down": normal((L, E, I, H)),
        }
        return {"embed": normal((c.vocab_size, H), H ** -0.5),
                "layers": layers, "final_ln": jnp.ones((H, ), c.dtype)}

    def params_from_hf_state_dict(self, tensors: dict) -> dict:
        raise NotImplementedError(
            "the ZAYA1 checkpoint's tensor names are not available where "
            "this loader was written; only seeded weights load "
            "(load_format='dummy')")

    # ------------------------------------------------------------------
    # Caches: K/V pages and two state rows in every layer
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        rows = P(None, None, None, None, None)
        return {"k": P(None, TOKEN_AXIS, None, None, None),
                "v": P(None, TOKEN_AXIS, None, None, None),
                "cca_u": rows, "cca_cv": rows}

    def _state_shapes(self, depth: int) -> dict:
        c = self.cfg
        S = (c.state_slots or 256) + 1       # + the dump row
        _, Lk, C = self._widths
        return {
            # u_{t-1}: the depthwise convolution's carried input.
            "cca_u": (rows_shape(depth, S, _TAPS, C), c.dtype),
            # c0_{t-1} and vb_{t-1}, widened to whole tiles.
            "cca_cv": (rows_shape(depth, S, _TAPS,
                                  whole_tiles(C + Lk // 2)), c.dtype),
        }

    def state_shapes(self) -> dict:
        """Snapshot-pool geometry (core/state_cache.py): the rows; the
        pages re-enter through the prefix cache."""
        return self._state_shapes(self.cfg.num_layers)

    def state_row_bytes(self) -> int:
        """What one sequence carries beside its pages, all layers."""
        return sum(int(np.prod(shape[2:])) * shape[0]
                   * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    def fixed_cache_bytes(self) -> int:
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        assert num_layers is None or num_layers == self.cfg.num_layers, \
            "the stack is not sliceable per stage (no PP)"
        caches = LlamaForCausalLM.make_kv_caches(self, num_pages, page_size,
                                                 cache_dtype)
        caches.update({name: jnp.zeros(shape, dtype) for name, (
            shape, dtype) in self.state_shapes().items()})
        return caches

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def _cca(self, lp: dict, x: jax.Array, caches: dict, layer, batch,
             seg, entries: dict, rope) -> tuple[jax.Array, dict]:
        """Compressed convolutional attention of layer ``layer`` (a
        traced ``[1]`` index into the pages and the rows) on the normed
        input."""
        c = self.cfg
        T = x.shape[0]
        Hq, Hk, D = c.num_q_heads, c.num_kv_heads, c.head_dim
        Lq, Lk, C = self._widths
        f32 = jnp.float32
        with jax.named_scope("cca_project"):
            proj = x @ lp["wqkv"]
            u, va, vb = (proj[:, :C], proj[:, C:C + Lk // 2],
                         proj[:, C + Lk // 2:])
        c0, c0_prev, vb_prev, rows_u, rows_cv = carried_latents(
            u, vb, lp["conv0_w"], lp["conv0_b"], caches["cca_u"],
            caches["cca_cv"], layer, seg, entries)
        v = jnp.concatenate([va, vb_prev], axis=-1)
        with jax.named_scope("cca_mix"):
            heads = Hq + Hk
            w1 = lp["conv1_w"]
            c1 = (jnp.einsum("thd,hde->the", c0_prev.reshape(T, heads, D),
                             w1[0], preferred_element_type=f32)
                  + jnp.einsum("thd,hde->the", c0.reshape(T, heads, D),
                               w1[1], preferred_element_type=f32)
                  + lp["conv1_b"].astype(f32).reshape(heads, D))
            qh = u[:, :Lq].astype(f32).reshape(T, Hk, Hq // Hk, D)
            kh = u[:, Lq:].astype(f32).reshape(T, Hk, 1, D)
            mq = 0.5 * (qh + kh)
            q = c1[:, :Hq] + mq.reshape(T, Hq, D)
            k = c1[:, Hq:] + jnp.mean(mq, axis=2)
            q = rope(unit_heads(q))
            k = rope(unit_heads(k) * lp["k_temp"][None, :, None])
        k_all, v_all, attn = write_kv_and_attend(
            q, caches["k"], caches["v"], k, v.reshape(T, Hk, D), batch,
            sm_scale=D ** -0.5, layer=layer, window=0)
        caches = {**caches, "k": k_all, "v": v_all, "cca_u": rows_u,
                  "cca_cv": rows_cv}
        return attn.reshape(T, Lq) @ lp["wo"], caches

    def _route(self, lp: dict, x: jax.Array, r_prev: jax.Array):
        """The pick of each token, its weight and the router's state
        for the next layer: (``[T, 1]`` int32, ``[T, 1]`` float32,
        ``[T, R]`` float32)."""
        c = self.cfg
        f32 = jnp.float32

        def dense(a, w, b=None):
            y = jnp.dot(a, lp[w].astype(f32), precision=_HIGHEST)
            return y if b is None else y + lp[b].astype(f32)

        r = dense(x.astype(f32), "router_down", "router_down_b")
        r = r + lp["router_eda"].astype(f32) * r_prev
        z = rms_norm(r, lp["router_ln"], c.rms_norm_eps)
        h = jax.nn.gelu(dense(z, "router_w1", "router_b1"),
                        approximate=False)
        h = jax.nn.gelu(dense(h, "router_w2", "router_b2"),
                        approximate=False)
        p = jax.nn.softmax(dense(h, "router_w3"), axis=-1)
        pick = jnp.argmax(p + lp["router_beta"], axis=-1)[:, None]
        return (pick.astype(jnp.int32),
                jnp.take_along_axis(p, pick, axis=1), r)

    def _experts(self, lp: dict, x: jax.Array, pick: jax.Array,
                 weight: jax.Array) -> jax.Array:
        """The picked expert's output times the pick's weight; a token
        whose pick is no expert takes ``p x``."""
        y = self.moe_dispatch(lp, x, pick, weight)
        with jax.named_scope("moe_skip"):
            skipped = pick == self.cfg.num_experts
            return y + jnp.where(skipped, weight, 0.0).astype(x.dtype) * x

    def run_layers(self, layer_params: dict, kv_caches: dict,
                   hidden: jax.Array, batch, first_layer: int = 0,
                   ) -> tuple[jax.Array, dict]:
        c = self.cfg
        T = hidden.shape[0]
        # One buffer a layer (serving_layout): out of the scan, behind
        # a switch (LlamaForCausalLM.run_layers has the reason).
        per_layer = {k: v for k, v in layer_params.items()
                     if isinstance(v, tuple)}
        stacked = {k: v for k, v in layer_params.items()
                   if k not in per_layer}
        seg = build_segment_info(batch, kv_caches["cca_u"].shape[1] - 1)
        decode_only = (getattr(batch, "max_q", 0) == 1
                       and getattr(batch, "attn_desc", None) is None)
        entries = step_entries(seg, T, _TAPS, decode_only)
        rd = c.rotary_dim
        cos, sin = compute_rope_cos_sin(batch.positions, rd, c.rope_theta,
                                        None, dtype=jnp.float32)

        def rope(x):
            return jnp.concatenate(
                [apply_rope_single(x[..., :rd], cos, sin), x[..., rd:]],
                axis=-1).astype(c.dtype)

        experts = jax.jit(self._experts)

        def body(carry, l):
            s, r, caches = carry
            lp = {k: jax.lax.dynamic_index_in_dim(v, l, 0, False)
                  for k, v in stacked.items()}
            x = rms_norm(s, lp["input_ln"], c.rms_norm_eps)
            y, caches = self._cca(lp, x, caches, jnp.reshape(l, (1, )),
                                  batch, seg, entries, rope)
            s = merge(s, y, lp["attn_merge"])
            x = rms_norm(s, lp["post_ln"], c.rms_norm_eps)
            with jax.named_scope("zaya_router"):
                pick, weight, r = self._route(lp, x, r)
            if per_layer:
                y = jax.lax.switch(l, [
                    functools.partial(
                        experts, {k: v[i] for k, v in per_layer.items()})
                    for i in range(c.num_layers)], x, pick, weight)
            else:
                y = self._experts(lp, x, pick, weight)
            return (merge(s, y, lp["mlp_merge"]), r, caches), None

        carry = (hidden,
                 jnp.zeros((T, c.router_hidden_size), jnp.float32),
                 dict(kv_caches))
        (s, _, caches), _ = jax.lax.scan(
            body, carry, jnp.arange(c.num_layers, dtype=jnp.int32))
        return s, caches

    def compute_logits(self, params: dict, hidden: jax.Array) -> jax.Array:
        # Tied: contract with the embedding's rows, no transposed copy.
        x = self._norm(hidden, params["final_ln"])
        return jnp.einsum("th,vh->tv", x, params["embed"],
                          preferred_element_type=jnp.float32)
