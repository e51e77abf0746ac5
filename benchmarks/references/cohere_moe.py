"""Plain reference forward pass of the Command A+ language model
(``Cohere2MoeForCausalLM``, ``model_type`` ``cohere2_moe``): one whole
sequence, no cache, no kernels, no batching, straightforward
``jax.numpy`` in float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the served
configuration's own (bf16) values, upcast where they are used, one
routed expert at a time.

With ``h`` the stream ``[S, hidden]``, layer by layer:

- ``x = LN(h)``: mean-centred LayerNorm, a weight and no bias, ``eps =
  layer_norm_eps``. ONE norm feeds attention and the feed-forward.
- Attention: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``, no bias, no q/k
  norm, scale ``head_dim ** -0.5``. ``layer_types[l] ==
  "sliding_attention"``: rope on q and k (``rope_theta``, interleaved
  pairs ``(x0, x1), (x2, x3), ...``, every lane: ``rotary_pct`` 1) and
  the mask ``0 <= i - j < sliding_window``. ``"full_attention"``: NO
  rope, the causal mask alone. ``a = concat(heads) W_o``.
- Router: ``s = sigmoid(x W_r)`` in float32 over ALL published experts;
  the ``num_experts_per_tok`` largest are picked; ``w_e = s_e / sum of
  the picked s`` (``norm_topk_prob``). No groups, no correction bias, no
  scaling factor: the configuration has none.
- Routed: ``r = sum over the picks of w_e x ((silu(x Wg_e) * (x Wu_e))
  Wd_e)``, each expert ``hidden -> intermediate_size -> hidden``.
- Shared: ``num_shared_experts`` gated SiLU experts of the same width,
  ``u = (1 / n) sum_j E_j(x)``
  (``shared_expert_combination_strategy: "average"``).
- The parallel block, one residual add: ``h' = h + a + r + u``.
- After the last layer ``LN``, then ``logits = logit_scale x (x E^T)``
  with the tied embedding.

Readings the configuration's file lists under ``assumed``, as computed
here: "average" is the mean of the shared experts' outputs, ADDED to the
routed sum (the other reading: the mean of shared and routed results);
``intermediate_size`` is the width of one expert, routed or shared;
``first_k_dense_replace`` is 0, so no layer is dense and
``prefix_dense_intermediate_size`` is unused.

Departures from the published description, each because the
configuration file states it:

- **The chip's share** (``expert_share``): of the ``published_experts``
  the router scores, only experts ``[index x n, (index + 1) x n)`` exist
  here (``n = num_experts`` held). A pick outside them adds nothing, the
  weights are still renormalised over all picks, the shared experts and
  attention are whole; the partial sum goes on to the next layer. Every
  held expert is computed for every token and weighted by its routing
  weight (0 where not picked): the definition, not a dispatch.
- The vocabulary is the file's ``vocab_size`` rows; the depth its
  ``num_hidden_layers`` with ``layer_types`` cut to as many.
- The shared experts' weights arrive as the program holds them: gate and
  up matrices side by side ``[hidden, n x I]``, down matrices stacked
  ``[n x I, hidden]``. They are cut back into ``n`` experts here and
  each is computed on its own.

Attention is computed a K/V head (its part of ``concat(heads) W_o``
added to the others') and a block of ``QUERY_BLOCK`` queries at a time,
and the head ``ROW_BLOCK`` positions at a time: plain loops
over the same formulas, so that a sequence longer than the window fits
beside the weights (``scripts/long_window_check.py``); the harness's
scoring prompts are one block. (The feed-forward is NOT looped over
positions: a loop's body takes the expert slabs it closes over as copies
of their slices of the layer stacks, half a gigabyte each.)

``control`` makes the negative controls, each breaking one path:
``"no_window"`` drops the window mask of the sliding layers (it needs a
sequence longer than ``sliding_window`` to show: the harness's scoring
prompts are 48 to 209 tokens, so it is NOT in ``CONTROLS`` and is scored
by ``scripts/long_window_check.py`` on the chip and by the tests at a
tiny window); ``"rope_on_full_layers"`` rotates q and k in the full
layers too; ``"no_shared_experts"`` leaves the shared experts out;
``"shared_sum_not_mean"`` adds them up without the ``1 / n``;
``"softmax_router"`` scores with a softmax over the published experts.
``CONTROLS`` lists what a chip run's short prompts must catch at three
tolerances; the others are held in float32 on the CPU
(``tests/models/test_cohere_moe.py``,
``tests/benchmarking/test_benchmark_cohere_moe.py``): PERF.md, Findings
PR 43, has the readings. ``compute_dtype`` and ``round_dtype`` are for
measuring what a lower precision costs
(``benchmarks/reference_precision.py``), as in ``deepseek.py``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
ROW_BLOCK = 1024


def _by_rows(fn, x):
    """``fn`` of the rows of ``x`` (a function of each row alone),
    ``ROW_BLOCK`` rows at a time where there are more: the same numbers,
    a block's temporaries at a time."""
    S = x.shape[0]
    if S <= ROW_BLOCK:
        return fn(x)
    blocks = jnp.pad(x, ((0, -S % ROW_BLOCK), (0, 0))).reshape(
        -1, ROW_BLOCK, x.shape[1])
    y = jax.lax.map(fn, blocks)
    return y.reshape(-1, y.shape[-1])[:S]


def _layer_norm(x, w, eps):
    y = x.astype(F32)
    y = y - jnp.mean(y, -1, keepdims=True)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _rope(x, theta: float):
    """x ``[S, heads, d]`` at positions 0..S-1: each pair of neighbouring
    lanes ``(x_2i, x_2i+1)`` turned by ``pos x theta^(-2i/d)``."""
    S, _, d = x.shape
    x = x.astype(F32)
    freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def _caster(dt, round_dtype=None):
    """Weights and block inputs as the measured precision holds them."""
    if round_dtype is None:
        return lambda a: a.astype(dt)
    return lambda a: a.astype(round_dtype).astype(dt)


def attention(x, lp: dict, cfg: dict, window: int, rope: bool, dt=F32,
              round_dtype=None):
    """One layer's attention output ``[S, hidden]``; ``window`` 0 is the
    causal mask alone. A K/V head at a time (its group of query heads
    projected, attended and taken through its rows of ``W_o``, the heads'
    parts added up: ``concat(heads) W_o`` written as a sum), and within
    it a block of ``QUERY_BLOCK`` queries at a time."""
    w_ = _caster(dt, round_dtype)
    S, H = x.shape
    N, K, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    G = N // K
    k = (x @ w_(lp["wk"])).reshape(S, K, D)
    v = (x @ w_(lp["wv"])).reshape(S, K, D)
    if rope:
        k = _rope(k, cfg["rope_theta"]).astype(dt)
    qb = min(S, QUERY_BLOCK)
    pad = -S % qb
    j = jnp.arange(S)

    def one_head(acc, args):
        wq_h, wo_h, k_h, v_h = args      # [H, G D], [G D, H], [S, D] x 2
        q = (x @ w_(wq_h)).reshape(S, G, D)
        if rope:
            q = _rope(q, cfg["rope_theta"]).astype(dt)
        blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, qb, G, D)

        def one_block(blk):
            q_b, first = blk
            i = first + jnp.arange(qb)
            seen = j[None, :] <= i[:, None]
            if window:
                seen &= j[None, :] > i[:, None] - window
            s = jnp.einsum("qgd,kd->gqk", q_b, k_h).astype(F32) * D ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", p.astype(dt), v_h)

        o = jax.lax.map(one_block,
                        (blocks, jnp.arange(blocks.shape[0]) * qb))
        return acc + o.reshape(S + pad, G * D)[:S] @ w_(wo_h), None

    out, _ = jax.lax.scan(one_head, jnp.zeros((S, H), dt), (
        lp["wq"].reshape(H, K, G * D).transpose(1, 0, 2),
        lp["wo"].reshape(K, G * D, H),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out


def route(x, router, cfg: dict, control=None):
    """``[S, published experts]`` routing weights (0 where not picked)."""
    S = x.shape[0]
    logits = x.astype(F32) @ router.astype(F32)
    scores = (jax.nn.softmax(logits, -1) if control == "softmax_router"
              else jax.nn.sigmoid(logits))
    vals, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / vals.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[jnp.arange(S)[:, None], idx].set(vals)


def held_range(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts that exist here."""
    n = cfg["num_experts"]
    share = cfg.get("expert_share")
    return (share["index"] * n if share else 0), n


def moe(x, lp: dict, cfg: dict, control=None, dt=F32, round_dtype=None):
    """One layer's feed-forward for ``x`` ``[S, hidden]``: the held
    routed experts' weighted sum plus the mean of the shared experts."""
    w_ = _caster(dt, round_dtype)
    lo, n = held_range(cfg)
    weight = route(x, lp["router"], cfg, control)[:, lo:lo + n].astype(dt)

    def expert(wg, wu, wd):
        return (jax.nn.silu(x @ w_(wg)) * (x @ w_(wu))) @ w_(wd)

    y = jnp.zeros_like(x)
    for e in range(n):            # one expert at a time
        y = y + expert(lp["w_gate"][e], lp["w_up"][e],
                       lp["w_down"][e]) * weight[:, e:e + 1]
    ns = cfg.get("num_shared_experts", 0)
    if ns and control != "no_shared_experts":
        width = cfg["intermediate_size"]
        shared = jnp.zeros_like(x)
        for j in range(ns):
            cut = slice(j * width, (j + 1) * width)
            shared = shared + expert(lp["shared_gate"][:, cut],
                                     lp["shared_up"][:, cut],
                                     lp["shared_down"][cut, :])
        y = y + (shared if control == "shared_sum_not_mean"
                 else shared / ns)
    return y


LAYER_KEYS = ("input_ln", "wq", "wk", "wv", "wo", "router", "w_gate",
              "w_up", "w_down", "shared_gate", "shared_up", "shared_down")


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    eps = cfg["layer_norm_eps"]
    lay = params["layers"]
    h = params["embed"][ids].astype(dt)
    for i in range(cfg["num_hidden_layers"]):
        lp = {k: lay[k][i] for k in LAYER_KEYS if k in lay}
        for k in ("w_gate", "w_up", "w_down"):
            # One expert's matrix at a time out of the layer stack: a
            # layer's slab sliced first is made as a copy of the slab.
            if not isinstance(lay[k], tuple):
                lp[k] = [lay[k][i, e] for e in range(lay[k].shape[1])]
        sliding = cfg["layer_types"][i] == "sliding_attention"
        x = w_(_layer_norm(h, lp["input_ln"], eps))
        a = attention(
            x, lp, cfg,
            window=(cfg["sliding_window"]
                    if sliding and control != "no_window" else 0),
            rope=sliding or control == "rope_on_full_layers",
            dt=dt, round_dtype=round_dtype)
        h = h + a + moe(x, lp, cfg, control, dt, round_dtype)

    def head(rows):
        rows = _layer_norm(rows, params["final_ln"], eps)
        logits = (rows @ w_(params["lm_head"])).astype(F32) * \
            cfg.get("logit_scale", 1.0)
        return jax.nn.log_softmax(logits, axis=-1)

    return _by_rows(head, h)


CONTROLS = {"Cohere2MoeForCausalLM": ["no_shared_experts"]}
