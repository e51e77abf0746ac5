"""Plain reference forward pass of Brumby (``BrumbyForCausalLM``, Manifest
AI ``Brumby-14B-Base``): one whole sequence, no state, no kernels, no
batching, straightforward ``jax.numpy`` in float32. The caller wraps the
call in ``jax.default_matmul_precision("highest")``. The weights are the
served configuration's own (bf16) values, upcast where they are used.

With ``h`` the stream ``[S, hidden]``, layer ``l`` (no bias but the
gate's):

- ``x = RMSNorm(h; w_in)``, ``eps = rms_norm_eps``.
- ``q = rope(RMSNorm_head(x W_q))``, ``k = rope(RMSNorm_head(x W_k))``:
  ``num_attention_heads`` query heads on ``num_key_value_heads`` K/V
  heads of ``head_dim`` (40 on 8: five query heads share a K/V head),
  Qwen3's per-head RMS norm with a learned weight, then the rotation
  (``rope_theta``, no scaling; lane ``i`` pairs with lane ``i +
  head_dim / 2``). ``v = x W_v``.
- The gate: ``log g = logsigmoid(x W_g + b_g)`` in float32, one scalar a
  K/V head and token.
- **Power retention, the quadratic form**: for query head ``n`` on K/V
  head ``h``, positions ``j <= t``: ``a[t, j] = (q[t, n] . k[j, h])^2 x
  exp(c[t, h] - c[j, h])``, ``c`` the cumulative sum of ``log g`` over
  the sequence; ``o[t, n] = (sum_j a[t, j] v[j, h]) / (sum_j a[t, j])``,
  no epsilon. The scores are scaled by ``head_dim ** -0.5`` before the
  square, which cancels in the quotient. NO ``phi`` and NO state is ever
  formed: the served program's recurrent and chunked forms
  (``ops/power_retention.py``) are another way to the same function, and
  this file is independent of both.
- ``h1 = h + concat(heads) W_o``; ``h' = h1 + (silu(y W_gate) * (y
  W_up)) W_down`` with ``y = RMSNorm(h1; w_post)``.
- After the last layer RMSNorm, then ``logits = x W_head`` (untied).

Readings the configuration's file lists under ``assumed``, as computed
here: degree 2; the gate a K/V head; q and k normed and rotated as in
Qwen3; no epsilon in the quotient; ``max_window_layers`` with
``use_sliding_window`` false is inert.

Departures from the published description: the depth is the file's
``num_hidden_layers``; nothing else. The published inference path keeps
keys and values for short sequences and a state for long ones; both
compute the function written above.

Retention is computed a K/V head (its part of ``concat(heads) W_o``
added to the others') and a block of ``QUERY_BLOCK`` queries at a time,
and the head a block of positions at a time, so that a sequence of
9 000 tokens fits beside the weights (``scripts/long_window_check.py --targets``).

``control`` makes the negative controls, each breaking one path:
``"no_gate"`` sets ``g = 1`` (no decay); ``"degree_4"`` raises the score
to the fourth power; ``"no_normalizer"`` returns the numerator alone;
``"no_rope"`` rotates nowhere; ``"no_qk_norm"`` skips the per-head
norms. ``gate_per_query_head`` was dropped: the configuration has one
gate a K/V head and no weights for another, so it cannot be told apart
from a different model. ``CONTROLS`` lists what a chip run's scoring
prompts must catch at three tolerances (all but ``no_qk_norm``); every
one is held in float32 on the CPU (``tests/models/test_brumby.py``). ``compute_dtype`` and
``round_dtype`` are for measuring what a lower precision costs
(``benchmarks/reference_precision.py``), as in ``deepseek.py``.
"""

import jax
import jax.numpy as jnp

# The norm, the rotation, the rounding and the head's row blocks as the
# other Qwen-vocabulary reference has them (a block's logits over 151 936
# words are 156 MB in float32).
from benchmarks.references.smallthinker import (_by_rows, _caster,
                                                _rms_norm, _rope)

F32 = jnp.float32
QUERY_BLOCK = 512


def retention(x, lp: dict, cfg: dict, control=None, dt=F32,
              round_dtype=None):
    """One layer's retention output ``[S, hidden]``: a K/V head at a
    time and within it a block of ``QUERY_BLOCK`` queries at a time, each
    against every key of the sequence under the causal mask."""
    w_ = _caster(dt, round_dtype)
    S, H = x.shape
    N, K, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    G = N // K
    eps = cfg["rms_norm_eps"]
    power = 4 if control == "degree_4" else 2

    def heads(y, count, norm):
        y = y.reshape(S, count, D)
        if control != "no_qk_norm":
            y = _rms_norm(y, norm, eps)
        if control != "no_rope":
            y = _rope(y, cfg["rope_theta"]).astype(dt)
        return y

    k = heads(x @ w_(lp["wk"]), K, lp["k_norm"])
    v = (x @ w_(lp["wv"])).reshape(S, K, D)
    log_g = jax.nn.log_sigmoid((x @ w_(lp["wg"])).astype(F32)
                               + lp["bg"].astype(F32))          # [S, K]
    if control == "no_gate":
        log_g = jnp.zeros_like(log_g)
    reach = jnp.cumsum(log_g, axis=0)
    qb = min(S, QUERY_BLOCK)
    pad = -S % qb
    j = jnp.arange(S)

    def one_head(acc, args):
        wq_h, wo_h, k_h, v_h, reach_h = args
        q = heads(x @ w_(wq_h), G, lp["q_norm"])
        blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, qb, G, D)
        reach_b = jnp.pad(reach_h, (0, pad)).reshape(-1, qb)

        def one_block(blk):
            q_b, reach_q, first = blk
            i = first + jnp.arange(qb)
            seen = j[None, :] <= i[:, None]
            s = jnp.einsum("qgd,kd->gqk", q_b, k_h).astype(F32) * D ** -0.5
            decay = jnp.exp(jnp.minimum(reach_q[:, None] - reach_h[None, :],
                                        0.0))
            a = jnp.where(seen[None], s ** power * decay[None], 0.0)
            top = jnp.einsum("gqk,kd->qgd", a.astype(dt), v_h).astype(F32)
            if control == "no_normalizer":
                return top.astype(dt)
            return (top / jnp.sum(a, axis=-1).T[:, :, None]).astype(dt)

        o = jax.lax.map(one_block, (
            blocks, reach_b, jnp.arange(blocks.shape[0]) * qb))
        o = o.reshape(S + pad, G * D)[:S]
        if round_dtype is not None:
            o = w_(o)
        return acc + o @ w_(wo_h), None

    out, _ = jax.lax.scan(one_head, jnp.zeros((S, H), dt), (
        lp["wq"].reshape(H, K, G * D).transpose(1, 0, 2),
        lp["wo"].reshape(K, G * D, H),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2), reach.T))
    return out


LAYER_KEYS = ("input_ln", "post_ln", "wq", "wk", "wv", "wo", "wg", "bg",
              "q_norm", "k_norm", "gate", "up", "down")


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None, targets=None):
    """[S, vocab] log-softmax at every position of ``ids``; with
    ``targets`` ``[S]`` the ``[S, 1]`` log-probabilities of those tokens
    alone (a 9 000-token table over this vocabulary would be 5.5 GB)."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    eps = cfg["rms_norm_eps"]
    lay = params["layers"]
    h = params["embed"][ids].astype(dt)
    for i in range(cfg["num_hidden_layers"]):
        lp = {k: lay[k][i] for k in LAYER_KEYS}
        x = w_(_rms_norm(h, lp["input_ln"], eps))
        h = h + retention(x, lp, cfg, control, dt, round_dtype)
        y = w_(_rms_norm(h, lp["post_ln"], eps))
        h = h + (w_(jax.nn.silu(y @ w_(lp["gate"])) * (y @ w_(lp["up"])))
                 @ w_(lp["down"]))

    def head(rows):
        if targets is not None:
            rows, want = rows[:, :-1], rows[:, -1].astype(jnp.int32)
        rows = _rms_norm(rows, params["final_ln"], eps)
        logits = (rows @ w_(params["lm_head"])).astype(F32)
        table = jax.nn.log_softmax(logits, axis=-1)
        if targets is None:
            return table
        return jnp.take_along_axis(table, want[:, None], axis=1)

    if targets is not None:
        # The target rides beside its row through the blocks (exact in
        # float32 up to 2^24 words).
        h = jnp.concatenate([h.astype(F32),
                             targets.astype(F32)[:, None]], axis=1)
    return _by_rows(head, h)


# Four of five on the chip (benchmarks/configs/brumby-14b.json,
# ``tolerance_reason``): over fifteen weight seeds these miss by 4.5 times
# three tolerances at most and 5.2 times in the mean at least.
# ``no_qk_norm`` does not: with seeded norm weights of one the per-head
# norm is nearly one scale for every key, which the quotient cancels; it
# read 1.28-1.71 at most against 3 x 0.35, so it is held in float32 on
# the CPU (tests/models/test_brumby.py), as PRs 37 and 47 held theirs.
ALL_CONTROLS = ("no_gate", "degree_4", "no_normalizer", "no_rope",
                "no_qk_norm")
CONTROLS = {"BrumbyForCausalLM": ["no_gate", "degree_4", "no_normalizer",
                                  "no_rope"]}
