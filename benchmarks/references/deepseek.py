"""Plain reference forward pass of the DeepSeek-V3 block
(``DeepseekV3ForCausalLM``; GigaChat3.1-702B-A36B publishes it with
``v_head_dim`` 192): one whole sequence, dense causal attention, no
cache, no kernels, no batching, straightforward ``jax.numpy`` in
float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the served
configuration's own (bf16) values, upcast where they are used, one
routed expert at a time, so no expert slab exists in float32 as a whole.

After the published description (DeepSeek-V3 technical report, section
2.1, and the model's own ``modeling_deepseek.py``), **not in the
absorbed form** the program serves:

- Latent attention. ``c_q = norm(x W_DQ)``, ``q = c_q W_UQ`` split per
  head into a no-rope part (``qk_nope_head_dim``) and a rope part
  (``qk_rope_head_dim``). ``[c_kv ; k_r] = x W_DKV``,
  ``c = norm(c_kv)``. Per head ``k = [W_UK c ; rope(k_r)]`` (the rope
  key is one for all heads), ``v = W_UV c`` (``v_head_dim`` wide),
  ``q = [q_nope ; rope(q_r)]``; plain causal softmax of
  ``q k^T x scale``; the heads' outputs concatenated through ``W_O``.
- Rotary embedding on the rope parts only, in the published
  de-interleaved form: a head's ``(x0, x1, x2, ...)`` is reordered to
  ``(x0, x2, ... ; x1, x3, ...)`` and rotated by halves.
- YaRN, written out: with ``d`` the rope size, ``b`` the base, ``s``
  the factor and ``L`` ``original_max_position_embeddings``, the
  dimension at which a wavelength makes ``r`` rotations over ``L`` is
  ``dim(r) = d ln(L / (2 pi r)) / (2 ln b)``; ``low = floor(dim(
  beta_fast))``, ``high = ceil(dim(beta_slow))``, clamped to
  ``[0, d - 1]``; the ramp ``g_i = clip((i - low) / (high - low), 0,
  1)`` over the ``d / 2`` frequencies blends the extrapolated
  ``b^(-2i/d)`` (``g = 0``) with the interpolated ``b^(-2i/d) / s``
  (``g = 1``). ``m(t) = 0.1 t ln s + 1``: the cos and sin tables carry
  ``m(mscale) / m(mscale_all_dim)`` and the score scale carries
  ``m(mscale_all_dim)^2`` beside ``(nope + rope)^-0.5``.
- The first ``first_k_dense_replace`` layers have a SwiGLU MLP of
  ``intermediate_size``. The rest: router ``s = sigmoid(x W_r)`` in
  float32 over ALL published experts; the choice reads ``s + b`` (the
  correction bias), groups are ranked by the sum of their two largest
  ``s + b``, the best ``topk_group`` groups stay, the top
  ``num_experts_per_tok`` of what stays are picked; the weights are the
  picked experts' UNBIASED ``s``, normalised to sum 1
  (``norm_topk_prob``) and multiplied by ``routed_scaling_factor``;
  one shared expert, ungated, added on top.

Departures from the published description, each because the
configuration file states it:

- **The chip's share** (``expert_share`` in the file): of the
  ``published_experts`` the router scores, only experts ``[index x n,
  (index + 1) x n)`` exist here (``n = n_routed_experts`` held). A pick
  outside them adds nothing; the partial sum, with the shared expert
  whole, is the layer's output and goes on to the next layer. Every
  held expert is computed for every token here and weighted by its
  routing weight (0 where not picked): the definition, not a dispatch.
- The vocabulary is the file's ``vocab_size`` rows (a slice is a
  smaller vocabulary).
- The multi-token-prediction module is not part of the next-token
  logits and is not computed (``num_nextn_predict_layers`` 0 in the
  file).
- Unpicked groups are masked with 0, as transformers does, where the
  model's own code uses -inf: the same picks while at least
  ``num_experts_per_tok`` biased scores in the kept groups are
  positive, which sigmoid scores with a small bias are.

``control`` makes the negative controls, each breaking one path:
``"no_rope_key"`` zeroes the shared rope key; ``"no_yarn"`` rotates by
the plain frequencies with neither mscale; ``"first_experts"`` replaces
the router's choice by the first ``num_experts_per_tok`` held experts
(each keeping its own score as its weight); ``"no_shared_expert"``
leaves the shared expert out; ``"no_group_limit"`` picks the top
experts over all groups. The first two are in ``CONTROLS``, which
every chip run must catch at three tolerances (3.0 at most AND 0.24 in
the mean): at the published widths they move a served logprob by
7.7-11.0 / 1.83-2.04 and 4.7-6.0 / 1.16-1.26 (my chip runs, PR 28).
The other three are caught by the float32 tests on the CPU only
(``tests/benchmarking/test_benchmark_deepseek.py``). Their means would
do on the chip (0.84-0.97 for the two that break the expert layer's
sum), but their largest move is 2.95-4.19 and 3.24-4.33, which fell
under 3.0 in two runs of seven; and without the group limit a logprob
moves by 0.73-0.91 / 0.109-0.114, 3.5 times the bf16 program's own
distance from this reference and not three tolerances, because only
the picks that land on the 16 held experts show. (Taking the weights
from the biased scores was tried too and is not kept: after
``norm_topk_prob`` it moves a logprob by 0.008 at most.)
``compute_dtype`` and ``round_dtype`` are for measuring what a
lower precision costs (``benchmarks/reference_precision.py``): with
``compute_dtype`` bfloat16 every weight and activation is held in it
and ``highest`` is not asked for by that caller; ``round_dtype`` (a
float8 type) also rounds every weight, and the normed input of every
attention and MLP block, through that type on the way.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    y = x.astype(F32)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return (y * w.astype(F32)).astype(x.dtype)


def yarn_tables(cfg: dict, S: int, control=None):
    """cos and sin ``[S, d/2]`` of positions 0..S-1, and the factor the
    score scale carries."""
    d = cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    i = jnp.arange(d // 2, dtype=F32)
    freq = base ** (-2.0 * i / d)
    rs = cfg.get("rope_scaling") or {}
    table_scale, score_scale = 1.0, 1.0
    if rs.get("rope_type", rs.get("type")) == "yarn" and \
            control != "no_yarn":
        s, L = float(rs["factor"]), rs["original_max_position_embeddings"]

        def dim(rotations):
            return d * math.log(L / (2 * math.pi * rotations)) / \
                (2 * math.log(base))

        low = max(math.floor(dim(rs.get("beta_fast", 32))), 0)
        high = min(math.ceil(dim(rs.get("beta_slow", 1))), d - 1)
        g = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        freq = freq * (1 - g) + freq / s * g

        def m(t):
            return 0.1 * t * math.log(s) + 1.0 if s > 1 else 1.0

        table_scale = m(rs.get("mscale", 1)) / m(rs.get("mscale_all_dim", 0))
        score_scale = m(rs.get("mscale_all_dim", 0)) ** 2
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    return jnp.cos(ang) * table_scale, jnp.sin(ang) * table_scale, score_scale


def _rope(x, cos, sin):
    """x ``[S, heads, d]``: de-interleave, then rotate by halves."""
    x = x.astype(F32)
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def route(x, router, bias, cfg: dict, control=None, first: int = 0):
    """[S, published experts] routing weights (0 where not picked).
    ``first``: where the ``"first_experts"`` control's picks start."""
    S = x.shape[0]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x.astype(F32) @ router.astype(F32))
    choice = scores + bias.astype(F32)[None, :]
    E = scores.shape[1]
    G, keep = cfg.get("n_group") or 1, cfg.get("topk_group") or 1
    if control == "first_experts":
        e = jnp.arange(E)
        choice = jnp.broadcast_to(
            jnp.where((e >= first) & (e < first + k), 2.0, 0.0), (S, E))
    elif control != "no_group_limit" and G > 1:
        per_group = choice.reshape(S, G, E // G)
        rank = jax.lax.top_k(per_group, 2)[0].sum(-1)            # [S, G]
        best = jax.lax.top_k(rank, keep)[1]
        kept = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], best].set(True)
        choice = jnp.where(jnp.repeat(kept, E // G, axis=1), choice, 0.0)
    idx = jax.lax.top_k(choice, k)[1]
    vals = jnp.take_along_axis(scores, idx, axis=1)
    if cfg.get("norm_topk_prob"):
        vals = vals / (vals.sum(-1, keepdims=True) + 1e-20)
    vals = vals * cfg.get("routed_scaling_factor", 1.0)
    return jnp.zeros_like(scores).at[jnp.arange(S)[:, None], idx].set(vals)


def held_range(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts that exist here."""
    n = cfg["n_routed_experts"]
    share = cfg.get("expert_share")
    return (share["index"] * n if share else 0), n


def _caster(dt, round_dtype=None):
    """Weights and block inputs as the measured precision holds them."""
    if round_dtype is None:
        return lambda a: a.astype(dt)
    return lambda a: a.astype(round_dtype).astype(dt)


def moe(x, lp: dict, cfg: dict, control=None, dt=F32, round_dtype=None):
    """One expert layer's output for ``x`` ``[S, H]``: the held routed
    experts' weighted sum plus the shared expert."""
    w_ = _caster(dt, round_dtype)
    lo, n = held_range(cfg)
    weight = route(x, lp["router"], lp["router_bias"], cfg, control, lo)
    weight = weight[:, lo:lo + n].astype(dt)                      # [S, n]

    def one(acc, e):
        wg, wu, wd, w = e
        h = jax.nn.silu(x @ w_(wg)) * (x @ w_(wu))
        return acc + (h @ w_(wd)) * w[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    if "shared_gate" in lp and control != "no_shared_expert":
        h = jax.nn.silu(x @ w_(lp["shared_gate"])) * \
            (x @ w_(lp["shared_up"]))
        y = y + h @ w_(lp["shared_down"])
    return y


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    S = ids.shape[0]
    N = cfg["num_attention_heads"]
    Pn, R = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    V, Lkv = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    n_layers = cfg["num_hidden_layers"]
    n_dense = min(cfg.get("first_k_dense_replace", 0), n_layers)
    cos, sin, score_scale = yarn_tables(cfg, S, control)
    scale = (Pn + R) ** -0.5 * score_scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    lay = params["layers"]

    def attention(x, lp):
        if "q_a" in lp:
            q = _rms(x @ w_(lp["q_a"]), lp["q_a_ln"], eps) @ \
                w_(lp["q_b"])
        else:
            q = x @ w_(lp["wq"])
        q = q.reshape(S, N, Pn + R)
        ckv = x @ w_(lp["kv_a"])
        c = _rms(ckv[:, :Lkv], lp["kv_a_ln"], eps)
        k_r = _rope(ckv[:, None, Lkv:], cos, sin).astype(dt)     # [S, 1, R]
        if control == "no_rope_key":
            k_r = jnp.zeros_like(k_r)
        q_r = _rope(q[..., Pn:], cos, sin).astype(dt)
        k_nope = jnp.einsum("sk,knp->snp", c, w_(lp["w_uk"]))
        v = jnp.einsum("sk,knv->snv", c, w_(lp["w_uv"]))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (S, N, R))], -1)
        qf = jnp.concatenate([q[..., :Pn], q_r], -1)
        s = jnp.einsum("qnd,knd->nqk", qf, k).astype(F32) * scale
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = jnp.einsum("nqk,knv->qnv", w.astype(dt), v)
        return o.reshape(S, N * V) @ w_(lp["wo"])

    attn_keys = [k for k in ("q_a", "q_a_ln", "q_b", "wq", "kv_a",
                             "kv_a_ln", "w_uk", "w_uv", "wo", "input_ln",
                             "post_ln") if k in lay]
    x = params["embed"][ids].astype(dt)
    for i in range(n_layers):
        lp = {k: lay[k][i] for k in attn_keys}
        x = x + attention(w_(_rms(x, lp["input_ln"], eps)), lp)
        h = w_(_rms(x, lp["post_ln"], eps))
        if i < n_dense:
            g, u, d = (w_(lay[k][i]) for k in ("gate", "up", "down"))
            x = x + (jax.nn.silu(h @ g) * (h @ u)) @ d
        else:
            j = i - n_dense
            x = x + moe(h, {k: lay[k][j] for k in (
                "router", "router_bias", "w_gate", "w_up", "w_down",
                "shared_gate", "shared_up", "shared_down") if k in lay},
                cfg, control, dt, round_dtype)
    x = _rms(x, params["final_ln"], eps)
    logits = (x @ w_(params["lm_head"])).astype(F32)
    return jax.nn.log_softmax(logits, axis=-1)


CONTROLS = {"DeepseekV3ForCausalLM": ["no_rope_key", "no_yarn"]}
