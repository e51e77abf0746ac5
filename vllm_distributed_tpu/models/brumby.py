"""Brumby (Manifest AI ``Brumby-14B-Base``, October 2025): Qwen3's block,
width for width, with the softmax attention of EVERY layer replaced by
power retention of degree 2 under a learned decay. No K/V page
anywhere: a sequence's whole memory is a fixed float32 state a layer.

One layer, as published (T tokens, ``x`` the layer's normed input)::

    q = rope(rmsnorm_head(x Wq))  [T, Hq, D]    k = rope(rmsnorm_head(x Wk))  [T, Hkv, D]
    v = x Wv                      [T, Hkv, D]   log g = logsigmoid(x Wg + bg) [T, Hkv], float32
    a[t, j] = (q[t, n] . k[j, h])^2 * exp(sum over l in (j, t] of log g[l, h])
    o[t, n] = (sum_j a[t, j] v[j, h]) / (sum_j a[t, j])           j <= t, h the K/V head of n
    h1 = h + concat_n(o) Wo ;  h' = h1 + SwiGLU(rmsnorm(h1))

Norms, rotation (half-rotation layout over the whole head), the SwiGLU,
the embedding and the head are ``LlamaForCausalLM``'s; the layer walk is
one scan, every layer alike. ``ops/power_retention.py`` has the
recurrence, its basis and the two kernels; a scale on ``q . k`` cancels
in the quotient and is ``D ** -0.5`` here, for range.

**State.** For each layer and sequence ``ret`` ``[Hkv, D/2 + 1, D, D]``
and its normaliser ``ret_z`` ``[Hkv, D/2 + 1 in whole sublane tiles, D]``,
float32: at the published size 8 x (65 x 128 x 128 + 72 x 128) x 4 B =
34.4 MB a layer (the cyclic basis of 8 320 rows, 0.8% over the symmetric
square's 8 256), whatever the sequence's length. They live in the runner's state rows
(``ops/mamba.py``'s contract) and the snapshot pool (core/state_cache.py)
like every other recurrent model's; ``STATE_ONLY``: a snapshot alone
resumes a request, there are no pages to go with it.

**What the configuration does not say** and is taken from the family's
public convention (``benchmarks/configs/brumby-14b.json`` lists each
under ``assumed``): degree 2; one gate a K/V head and token, a linear
map of the layer's input with a bias through ``logsigmoid``; q and k
keep Qwen3's per-head RMS norm and rotation; the quotient has no
epsilon; state and normaliser are float32.

**Seeded weights** (``load_format="dummy"``): normal x 0.02 as
elsewhere, except the gate's bias ``bg``: drawn so that ``-log g`` at a
zero projection is log-uniform over 1e-4 ... 1e-1 (half-lives of 7 to
7 000 tokens), ``bg = -log(expm1(r))`` with ``log r`` uniform. At a zero
bias every state would halve each token and no comparison would see the
recurrence.

Refused at load, by name: a ``rope_scaling``, ``use_sliding_window``
true, ``attention_bias`` true; tensor parallelism (the state kernels run
unsharded: models/loader.py); quantization and LoRA. A checkpoint's
tensor names are not available offline: ``params_from_hf_state_dict``
refuses.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (apply_rope_single,
                                                compute_rope_cos_sin,
                                                rms_norm)
from vllm_distributed_tpu.models.llama import LlamaForCausalLM
from vllm_distributed_tpu.ops import power_retention
from vllm_distributed_tpu.ops.mamba import (build_segment_info,
                                            chunk_form_tokens)

# -log g at a zero projection, the ends of the log-uniform draw.
GATE_DECAY_RANGE = (1e-4, 1e-1)


class BrumbyForCausalLM(LlamaForCausalLM):

    QUANT_TARGETS = ()
    LORA_TARGETS = ()
    STATEFUL = True
    # No paged layer: a snapshot of the state rows is a whole resume
    # point (core/state_cache.py).
    STATE_ONLY = True

    @classmethod
    def configure_arch(cls, arch, hf) -> None:
        if getattr(hf, "rope_scaling", None):
            raise ValueError(
                f"brumby: rope_scaling={hf.rope_scaling!r} is not wired "
                "(the published configuration has none)")
        if getattr(hf, "use_sliding_window", False):
            raise ValueError(
                "brumby: use_sliding_window=true is not wired (a "
                "retention layer has no window; the published "
                "configuration says false)")
        if getattr(hf, "attention_bias", False):
            raise ValueError(
                "brumby: attention_bias=true is not wired (the published "
                "projections have no bias; only the gate has one)")
        if arch.head_dim % 2:
            raise ValueError(
                f"brumby: head_dim={arch.head_dim} is odd; the state's "
                "basis pairs lane i with lane i + d (ops/power_retention.py)")
        arch.qk_norm = True
        arch.rope_scaling = None
        # max_window_layers with use_sliding_window false is inert.
        arch.sliding_window = None
        arch.window_pattern = None
        arch.stateful = True
        arch.power_retention = True
        if not hasattr(arch, "state_slots"):
            arch.state_slots = 0

    def quantize_params(self, params: dict) -> dict:
        if self.cfg.quantization:
            raise ValueError("brumby: weight quantization is not wired")
        return params

    # ------------------------------------------------------------------
    # Parameters: Qwen3's, plus the gate
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        if self.cfg.max_loras:
            raise ValueError("brumby: LoRA is not wired")
        specs = super().param_specs()
        specs["layers"].update(wg=P(None, None, None), bg=P(None, None))
        return specs

    def init_params(self, rng: jax.Array, scale: float = 0.02) -> dict:
        c = self.cfg
        params = super().init_params(rng, scale)
        k_w, k_b = jax.random.split(jax.random.fold_in(rng, 50))
        L, H, Hkv = c.num_layers, c.hidden_size, c.num_kv_heads
        lo, hi = np.log(GATE_DECAY_RANGE)
        rate = jnp.exp(jax.random.uniform(k_b, (L, Hkv), jnp.float32, lo,
                                          hi))
        params["layers"].update(
            wg=(scale * jax.random.normal(k_w, (L, H, Hkv), jnp.float32)
                ).astype(c.dtype),
            # logsigmoid(bg) = -rate.
            bg=-jnp.log(jnp.expm1(rate)))
        return params

    def params_from_hf_state_dict(self, tensors) -> dict:
        raise NotImplementedError(
            "brumby: a checkpoint's tensor names are not available "
            "offline; only load_format='dummy' is wired")

    def slice_layer_params(self, layers: dict, start: int, end: int):
        raise ValueError("brumby: pipeline parallelism over the state "
                         "rows is not wired")

    def chunk_form_tokens(self, q_lens, num_tokens: int) -> dict:
        """Tokens of a step that ``retention_ragged`` takes in the chunked
        form: what the runner's ``vdt:step_retention_chunk_tokens``
        counts."""
        return {"retention": chunk_form_tokens(q_lens, num_tokens,
                                               power_retention.CHUNK)}

    # ------------------------------------------------------------------
    # State rows (no paged K/V)
    # ------------------------------------------------------------------
    def kv_cache_specs(self) -> dict:
        return {"ret": P(None, None, None, None, None, None),
                "ret_z": P(None, None, None, None, None)}

    def state_shapes(self) -> dict:
        """The rows' geometry, for the caches and for the snapshot pool
        (core/state_cache.py): axis 1 is the row of a request."""
        c = self.cfg
        S = (c.state_slots or 256) + 1       # + the dump row
        ret, ret_z = power_retention.state_shapes(
            c.num_layers, S, c.num_kv_heads, c.head_dim)
        return {"ret": (ret, jnp.float32), "ret_z": (ret_z, jnp.float32)}

    def state_row_bytes(self) -> int:
        """One sequence's state and normaliser, all layers."""
        return sum(int(np.prod(shape[2:])) * shape[0]
                   * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    def make_kv_caches(self, num_pages: int, page_size: int,
                       cache_dtype=None,
                       num_layers: Optional[int] = None) -> dict:
        assert num_layers in (None, self.cfg.num_layers), \
            "the state rows are not sliced per stage (no PP)"
        return {name: jnp.zeros(shape, dtype) for name, (shape, dtype)
                in self.state_shapes().items()}

    def kv_cache_page_bytes(self, page_size: int) -> int:
        return 0

    def fixed_cache_bytes(self) -> int:
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for shape, dtype in self.state_shapes().values())

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------
    def retention_block(self, lp: dict, x: jax.Array, caches: dict, layer,
                        seg, cos, sin, decode_only: bool):
        """The retention layer ``layer`` (a traced index into the
        layer-stacked rows) on the normed input. Returns (out, caches)."""
        c = self.cfg
        T = x.shape[0]
        Hq, Hkv, D = c.num_q_heads, c.num_kv_heads, c.head_dim
        f32 = jnp.float32

        def head(y, heads, norm):
            y = self._norm(y.reshape(T, heads, D), norm)
            return apply_rope_single(y.astype(f32), cos, sin).astype(c.dtype)

        q = head(x @ lp["wq"], Hq, lp["q_norm"])
        k = head(x @ lp["wk"], Hkv, lp["k_norm"])
        v = (x @ lp["wv"]).reshape(T, Hkv, D)
        log_g = jax.nn.log_sigmoid((x @ lp["wg"]).astype(f32) + lp["bg"])
        o, ret, ret_z = power_retention.retention_ragged(
            q.astype(f32) * D ** -0.5, k, v, log_g, caches["ret"],
            caches["ret_z"], jnp.reshape(layer, (1, )).astype(jnp.int32),
            seg, decode_only=decode_only)
        out = o.reshape(T, Hq * D).astype(c.dtype) @ lp["wo"]
        return out, {**caches, "ret": ret, "ret_z": ret_z}

    def run_layers(self, layer_params: dict, kv_caches: dict,
                   hidden: jax.Array, batch, first_layer: int = 0,
                   ) -> tuple[jax.Array, dict]:
        c = self.cfg
        seg = build_segment_info(batch, kv_caches["ret"].shape[1] - 1)
        decode_only = getattr(batch, "max_q", 0) == 1
        cos, sin = compute_rope_cos_sin(batch.positions, c.head_dim,
                                        c.rope_theta, None,
                                        dtype=jnp.float32)
        num_layers = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
        layer_ids = jnp.arange(num_layers, dtype=jnp.int32)

        def layer_body(carry, xs):
            h, caches = carry
            lp, li = xs
            x = rms_norm(h, lp["input_ln"], c.rms_norm_eps)
            out, caches = self.retention_block(lp, x, caches, li, seg, cos,
                                               sin, decode_only)
            h = h + out
            x2 = rms_norm(h, lp["post_ln"], c.rms_norm_eps)
            return (h + self.mlp_block(lp, x2), caches), None

        (hidden, caches), _ = jax.lax.scan(
            layer_body, (hidden, dict(kv_caches)),
            (layer_params, layer_ids))
        return hidden, caches
