"""Plain reference forward pass of Granite 4.0-H
(``GraniteMoeHybridForCausalLM``, ``model_type`` ``granitemoehybrid``):
one whole sequence, the Mamba-2 state advanced token by token in a
``lax.scan``, full causal attention by a mask, no chunked form, no
cache, no kernels, no batching, straightforward ``jax.numpy`` in
float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the served
configuration's own (bf16) values, upcast where they are used. Nothing
of ``vllm_distributed_tpu/ops`` or ``models`` is imported.

The model, as the source's keys give it. With ``h`` the stream and
``rm = residual_multiplier``:

    h0 = embedding_multiplier * embed[ids]
    h  = h + rm * mix(norm1(h));  h = h + rm * mlp(norm2(h))
    logits = (norm_f(h) embed^T) / logits_scaling        (the head tied)

RMSNorm ``rms_norm_eps``; ``mlp(x) = W_down (silu(x W_gate) * (x W_up))``
(the source fuses gate and up in ``shared_mlp.input_linear``; the
program's loader splits them, and these are the split weights). Layer
``i`` is what ``layer_types[i]`` says:

- ``attention``: causal GQA, ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``hidden_size /
  num_attention_heads`` channels, ``softmax(q k^T attention_multiplier)
  v``, no bias, no rotation (``position_embedding_type`` ``nope``).
- ``mamba``: ``[z, x, BC, dt] = u W_in`` (``d_inner``, ``d_inner``,
  ``2 n_groups d_state``, ``n_heads`` columns); ``x`` and ``BC`` through
  a causal depthwise convolution of ``mamba_d_conv`` taps with a bias
  (zeros before the sequence), then SiLU; ``dt = softplus(dt +
  dt_bias)`` (clipped to ``time_step_limit`` where given); ``A =
  -exp(A_log)``, one scalar a head. Per head the state ``S`` is
  ``[d_head, d_state]`` float32, zero before the sequence:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; ``out = W_out rmsnorm(y * silu(z))``, the
  gated norm over all ``d_inner`` channels (as the source's own code
  has it, whatever ``n_groups``).

``control`` breaks one thing: ``"no_decay"`` sets ``exp(dt A)`` to 1;
``"no_conv"`` replaces the convolution by the identity (bias and all);
``"no_gate_norm"`` leaves the gated norm out (``W_out (y * silu(z))``);
``"rope_on"`` rotates q and k (``rope_theta``, rotate-half) where the
source has no rotation; ``"scale_sqrt"`` scales the scores by
``head ** -0.5`` (1/8) for ``attention_multiplier`` (1/64);
``"no_embed_mult"``, ``"no_resid_mult"`` and ``"no_logit_scale"`` take
one multiplier away each. ``CONTROLS`` names those every chip run must
catch at three tolerances (1.5 at most AND 0.24 in the mean): at the
published widths ``no_decay`` moves a served logprob by 3.93-5.26 /
0.89-0.97, ``no_conv`` by 4.00-6.69 / 0.94-1.08 and ``no_gate_norm``
by 4.06-6.15 / 0.92-1.07 (my chip runs, PR 37, 20 weight seeds). The
others are held by the float32 tests on the CPU only
(``tests/models/test_granite_hybrid.py``,
``tests/benchmarking/test_benchmark_granite_hybrid.py``), ``scale_sqrt``
(1.45-1.50 / 0.32-0.33) and ``rope_on`` (0.35-0.42 / 0.08) among them,
against ISSUE 37's wish: attention is four layers of forty, so a wrong
scale or a rotation moves the logits less than three times any limit
that clears the program's own bf16 rounding
(``benchmarks/configs/granite-4.0-h-micro.json``, ``tolerance_reason``). ``compute_dtype`` and ``round_dtype`` are for
``benchmarks/reference_precision.py``; the recurrent state stays
float32 in every reading, as the program holds it.
"""

import jax
import jax.numpy as jnp

from benchmarks.references.deepseek import _caster, _rms

F32 = jnp.float32


def _rope(x, theta: float):
    """Rotate-half rotation of ``[S, heads, D]`` at positions 0..S-1
    (the ``rope_on`` control only: the model has none)."""
    S, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2].astype(F32), x[..., D // 2:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(x, lp: dict, cfg: dict, control=None, dt=F32,
              round_dtype=None):
    w_ = _caster(dt, round_dtype)
    S = x.shape[0]
    QH, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // QH
    q = (x @ w_(lp["a_wq"])).reshape(S, QH, D)
    k = (x @ w_(lp["a_wk"])).reshape(S, KVH, D)
    v = (x @ w_(lp["a_wv"])).reshape(S, KVH, D)
    if control == "rope_on":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, QH // KVH, axis=1) for t in (k, v))
    scale = D ** -0.5 if control == "scale_sqrt" else \
        cfg["attention_multiplier"]
    s = jnp.einsum("qnd,knd->nqk", q, k).astype(F32) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
    o = jnp.einsum("nqk,knd->qnd", w.astype(dt), v)
    return o.reshape(S, QH * D) @ w_(lp["a_wo"])


def ssm_scan(x, dt, A, B, C, control=None):
    """The recurrence over one sequence: x ``[S, Hm, P]``, dt
    ``[S, Hm]``, A ``[Hm]``, B and C ``[S, Hm, N]`` (a group's, repeated
    over its heads), float32. Returns ``S_t C_t`` ``[S, Hm, P]``."""
    Hm, P, N = x.shape[1], x.shape[2], B.shape[2]

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        decay = jnp.ones_like(dt_t) if control == "no_decay" else \
            jnp.exp(dt_t * A)
        state = decay[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), F32), (x, dt, B, C))
    return y


def mamba(u, lp: dict, cfg: dict, control=None, dt=F32, round_dtype=None):
    w_ = _caster(dt, round_dtype)
    S = u.shape[0]
    Hm, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    N, G, K = cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    Di = Hm * P
    z = u @ w_(lp["m_in_gate"])
    xbc = jnp.concatenate([u @ w_(lp["m_in_x"]), u @ w_(lp["m_in_bc"])], -1)
    step = (u @ w_(lp["m_in_dt"])).astype(F32)
    if control != "no_conv":
        taps = jnp.concatenate([w_(lp["m_conv_x_w"]),
                                w_(lp["m_conv_bc_w"])], -1)   # tap 0 oldest
        past = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), dt), xbc])
        xbc = sum(taps[j] * past[j:j + S] for j in range(K))
        if cfg.get("mamba_conv_bias", True):
            xbc = xbc + jnp.concatenate([w_(lp["m_conv_x_b"]),
                                         w_(lp["m_conv_bc_b"])])
    xbc = jax.nn.silu(xbc).astype(F32)
    x = xbc[:, :Di].reshape(S, Hm, P)
    B = jnp.repeat(xbc[:, Di:Di + G * N].reshape(S, G, N), Hm // G, axis=1)
    C = jnp.repeat(xbc[:, Di + G * N:].reshape(S, G, N), Hm // G, axis=1)
    step = jax.nn.softplus(step + lp["m_dt_bias"].astype(F32))
    limit = cfg.get("time_step_limit")
    if limit:
        step = jnp.clip(step, limit[0], limit[1])
    A = -jnp.exp(lp["m_A_log"].astype(F32))
    y = ssm_scan(x, step, A, B, C, control)
    y = y + lp["m_D"].astype(F32)[None, :, None] * x
    y = y.reshape(S, Di) * jax.nn.silu(z.astype(F32))
    if control != "no_gate_norm":
        y = _rms(y, lp["m_gated_norm"], cfg["rms_norm_eps"])
    return y.astype(dt) @ w_(lp["m_out_proj"])


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    eps = cfg["rms_norm_eps"]
    lay = params["layers"]
    rm = 1.0 if control == "no_resid_mult" else cfg["residual_multiplier"]
    x = params["embed"][ids].astype(dt)
    if control != "no_embed_mult":
        x = x * jnp.asarray(cfg["embedding_multiplier"], dt)
    at = {"a_": 0, "m_": 0}
    for kind in cfg["layer_types"]:
        pre = "a_" if kind == "attention" else "m_"
        lp = {k: v[at[pre]] for k, v in lay.items() if k.startswith(pre)}
        at[pre] += 1
        if kind == "attention":
            mix = attention(w_(_rms(x, lp["a_ln"], eps)), lp, cfg, control,
                            dt, round_dtype)
        else:
            mix = mamba(w_(_rms(x, lp["m_norm"], eps)), lp, cfg, control,
                        dt, round_dtype)
        x = x + rm * mix
        h = w_(_rms(x, lp[pre + "pre_ln"], eps))
        g, u, d = (w_(lp[pre + k]) for k in ("gate", "up", "down"))
        x = x + rm * ((jax.nn.silu(h @ g) * (h @ u)) @ d)
    x = _rms(x, params["final_ln"], eps)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    logits = (x @ w_(head)).astype(F32)
    if control != "no_logit_scale":
        logits = logits / cfg["logits_scaling"]
    return jax.nn.log_softmax(logits, axis=-1)


CONTROLS = {"GraniteMoeHybridForCausalLM": ["no_decay", "no_conv",
                                            "no_gate_norm"]}
