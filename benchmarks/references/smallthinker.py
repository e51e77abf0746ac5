"""Plain reference forward pass of SmallThinker
(``SmallThinkerForCausalLM``, PowerInfer ``SmallThinker-21BA3B-Instruct``):
one whole sequence, no cache, no kernels, no batching, straightforward
``jax.numpy`` in float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the served
configuration's own (bf16) values, upcast where they are used, one
expert at a time.

With ``h`` the stream ``[S, hidden]``, layer ``l`` (no bias anywhere):

- ``x = RMSNorm(h; w_in)``, ``eps = rms_norm_eps``, the weight applied
  to the normalised row.
- **The router reads ``x``, the ATTENTION's input**: ``logits = x W_r``
  in float32 over the ``moe_num_primary_experts`` experts; the
  ``moe_num_active_primary_experts`` largest logits are picked; ``p =
  softmax`` over the PICKED logits alone
  (``moe_primary_router_apply_softmax: true``).
- Attention: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim`` (28 on 4: seven
  query heads share a K/V head), no q/k norm, scale ``head_dim **
  -0.5``. ``rope_layout[l] == 1``: q and k rotated (``rope_theta``, no
  scaling; lane ``i`` pairs with lane ``i + head_dim / 2``);  ``0``: no
  position signal at all. ``sliding_window_layout[l] == 1``: the mask
  ``0 <= i - j < sliding_window_size``; ``0``: the causal mask alone.
  ``h1 = h + concat(heads) W_o``.
- ``y = RMSNorm(h1; w_post)``.
- Experts, at the picks made BEFORE attention: ``m = sum over the picks
  of p_e x ((relu(y Wg_e) * (y Wu_e)) Wd_e)``, each expert ``hidden ->
  moe_ffn_hidden_size -> hidden``. ``h' = h1 + m``.
- After the last layer RMSNorm, then ``logits = x W_head`` (untied).

Readings the configuration's file lists under ``assumed``, as computed
here: the gate's activation is ReLU (the model card's "sparse ReGLU";
``config.json`` has no ``hidden_act``); rope is the half-rotation layout
over the whole head; the window counts the query's own position;
``norm_topk_prob`` changes nothing once the softmax is over the picks;
no secondary experts, no shared expert, no dense layer: ``config.json``
has no key for any of them.

Departures from the published description, each because the
configuration file states it: the depth is the file's
``num_hidden_layers`` with both layouts cut to as many entries; nothing
else (every expert is held, the vocabulary is whole). Every expert is
computed for every token and weighted by its routing weight (0 where not
picked): the definition, not a dispatch.

Attention is computed a K/V head (its part of ``concat(heads) W_o``
added to the others') and a block of ``QUERY_BLOCK`` queries at a time,
the experts as a loop over the layer's expert axis, and the head
``ROW_BLOCK`` positions at a time, as ``references/cohere_moe.py`` does
and for its reason: a sequence longer than the window fits beside the
weights (``scripts/long_window_check.py``; with this vocabulary the
``[S, vocab]`` table of 6 100 tokens is 3.7 GB, so the head's blocks are
written into it in place).

``control`` makes the negative controls, each breaking one path:
``"router_after_attention"`` routes on ``y`` (where every other
mixture of experts in this repository has its router);
``"silu_experts"`` gates with SiLU; ``"rope_on_full_layers"`` rotates
q and k in the ``rope_layout`` 0 layers too; ``"no_rope"`` rotates
nowhere; ``"no_window"`` drops the window mask (it needs a sequence
longer than ``sliding_window_size`` to show, so it is scored by
``scripts/long_window_check.py`` on the chip and by the tests at a tiny
window). ``CONTROLS`` lists what a chip run's 48 to 209-token scoring
prompts must catch at three tolerances; the others are held in float32
on the CPU (``tests/models/test_smallthinker.py``). ``compute_dtype``
and ``round_dtype`` are for measuring what a lower precision costs
(``benchmarks/reference_precision.py``), as in ``deepseek.py``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
# Rows of the head at a time: a block's logits over 151 936 words are
# 156 MB in float32, and the whole table of a 6 100-token sequence is 3.7
# GB beside 7.9 GB of weights.
ROW_BLOCK = 256


def _by_rows(fn, x):
    """``fn`` of the rows of ``x`` (a function of each row alone),
    ``ROW_BLOCK`` rows at a time where there are more, each block written
    into its place in the result (the last block starts ``ROW_BLOCK``
    rows before the end and computes a few rows a second time, to the
    same values): the table is made once, not stacked and then cut."""
    S = x.shape[0]
    if S <= ROW_BLOCK:
        return fn(x)
    like = jax.eval_shape(fn, x[:ROW_BLOCK])

    def one_block(b, out):
        start = jnp.minimum(b * ROW_BLOCK, S - ROW_BLOCK)
        rows = jax.lax.dynamic_slice_in_dim(x, start, ROW_BLOCK)
        return jax.lax.dynamic_update_slice_in_dim(out, fn(rows), start, 0)

    return jax.lax.fori_loop(0, -(-S // ROW_BLOCK), one_block,
                             jnp.zeros((S, like.shape[1]), like.dtype))


def _rms_norm(x, w, eps):
    y = x.astype(F32)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def _rope(x, theta: float):
    """x ``[S, heads, d]`` at positions 0..S-1: lanes ``(x_i, x_{i +
    d/2})`` turned by ``pos x theta^(-2i/d)``."""
    S, _, d = x.shape
    x = x.astype(F32)
    freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _caster(dt, round_dtype=None):
    """Weights and block inputs as the measured precision holds them."""
    if round_dtype is None:
        return lambda a: a.astype(dt)
    return lambda a: a.astype(round_dtype).astype(dt)


def attention(x, lp: dict, cfg: dict, window: int, rope: bool, dt=F32,
              round_dtype=None):
    """One layer's attention output ``[S, hidden]``; ``window`` 0 is the
    causal mask alone. A K/V head at a time and within it a block of
    ``QUERY_BLOCK`` queries at a time."""
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


def route(x, router, cfg: dict):
    """``[S, experts]`` routing weights (0 where not picked): the k
    largest logits, a softmax over them alone."""
    S = x.shape[0]
    logits = x.astype(F32) @ router.astype(F32)
    vals, idx = jax.lax.top_k(logits,
                              cfg["moe_num_active_primary_experts"])
    return jnp.zeros_like(logits).at[jnp.arange(S)[:, None], idx].set(
        jax.nn.softmax(vals, -1))


def _expert_matrix(w, e):
    """Expert ``e``'s matrix of a layer: ``w`` is the layer's ``[E, ...]``
    array, or ``(stack [L, E, ...], layer)`` where the weights come
    layer-stacked: ONE expert's matrix is cut out of the stack, where a
    layer's slab cut first is made as a copy of the slab (0.75 GB a
    layer here)."""
    if isinstance(w, tuple):
        stack, layer = w
        return stack[layer, e]
    return w[e]


def experts(y, weight, lp: dict, control=None, dt=F32, round_dtype=None):
    """``sum_e weight[:, e] x ((act(y Wg_e) * (y Wu_e)) Wd_e)``, one expert
    at a time in the experts' own order, as a loop: 512 expert bodies
    written out (64 x 8 layers) made a program whose compilation for a
    4 300-token sequence grew past 40 GiB of the chip's host (my chip
    runs, PR 47)."""
    w_ = _caster(dt, round_dtype)
    act = jax.nn.silu if control == "silu_experts" else jax.nn.relu
    weight = weight.astype(dt)

    def one_expert(e, m):
        wg, wu, wd = (_expert_matrix(lp[k], e)
                      for k in ("w_gate", "w_up", "w_down"))
        w_e = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return m + ((act(y @ w_(wg)) * (y @ w_(wu))) @ w_(wd)) * w_e

    return jax.lax.fori_loop(0, weight.shape[1], one_expert,
                             jnp.zeros_like(y))


LAYER_KEYS = ("input_ln", "post_ln", "wq", "wk", "wv", "wo", "router",
              "w_gate", "w_up", "w_down")


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    eps = cfg["rms_norm_eps"]
    lay = params["layers"]
    h = params["embed"][ids].astype(dt)
    for i in range(cfg["num_hidden_layers"]):
        lp = {k: lay[k][i] if isinstance(lay[k], tuple)
              or k not in ("w_gate", "w_up", "w_down") else (lay[k], i)
              for k in LAYER_KEYS}
        windowed = bool(cfg["sliding_window_layout"][i])
        roped = bool(cfg["rope_layout"][i])
        x = w_(_rms_norm(h, lp["input_ln"], eps))
        h = h + attention(
            x, lp, cfg,
            window=(cfg["sliding_window_size"]
                    if windowed and control != "no_window" else 0),
            rope=((roped or control == "rope_on_full_layers")
                  and control != "no_rope"),
            dt=dt, round_dtype=round_dtype)
        y = w_(_rms_norm(h, lp["post_ln"], eps))
        weight = route(y if control == "router_after_attention" else x,
                       lp["router"], cfg)
        h = h + experts(y, weight, lp, control, dt, round_dtype)

    def head(rows):
        rows = _rms_norm(rows, params["final_ln"], eps)
        logits = (rows @ w_(params["lm_head"])).astype(F32)
        return jax.nn.log_softmax(logits, axis=-1)

    return _by_rows(head, h)


# None: on the chip every control misses by three tolerances IN THE MEAN
# and none does so reliably AT MOST (``router_after_attention`` reads
# 0.85-1.27 against 3 x 0.35 over nine weight seeds; PERF.md, Findings
# PR 47), so all five are held in float32 on the CPU.
CONTROLS = {"SmallThinkerForCausalLM": []}
