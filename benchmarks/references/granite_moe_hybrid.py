"""Plain reference forward pass of Granite 4.0-H with routed experts
(``GraniteMoeHybridForCausalLM`` with ``num_local_experts`` > 0: the
4.0-H Tiny and Small models): one whole sequence, the Mamba-2 state
advanced token by token, full causal attention by a mask, every held
expert computed for every token, no cache, no kernels, no batching,
``jax.numpy`` in float32. The caller wraps the call in
``jax.default_matmul_precision("highest")``. The weights are the served
configuration's own (bf16) values, upcast where they are used. Nothing of
``vllm_distributed_tpu/ops`` or ``models`` is imported; the two mixers
and the recurrence are ``benchmarks/references/granite_hybrid.py``'s
(``mamba``, ``attention``, ``ssm_scan``: the Micro model's, at other
widths), which is not edited.

With ``h`` the stream and ``rm = residual_multiplier``, each layer:

    a      = h + rm * mix(norm1(h))
    x      = norm2(a)
    logits = x W_r^T                        (router.layer, no bias, float32)
    (v, e) = top_k(logits);  g = softmax(v) (over the kept ones)
    moe(x) = sum_j g_j * (silu(x Wg[e_j]) * (x Wu[e_j])) Wd[e_j]
    shr(x) = (silu(x Sg) * (x Su)) Sd       (shared_mlp)
    h'     = a + rm * (moe(x) + shr(x))

and ``h0 = embedding_multiplier * embed[ids]``, ``out = (norm_f(h_L)
embed^T) / logits_scaling``. An expert's width is ``intermediate_size``,
the shared MLP's ``shared_intermediate_size``.

Departures from the published description, each because the
configuration file states it:

- **The chip's share** (``expert_share``): of the ``published_experts``
  the router scores, only experts ``[index x n, (index + 1) x n)`` exist
  here (``n = num_local_experts`` held). The router is whole (its top-k
  and its softmax are over all published experts); a pick outside the
  held range adds nothing; the mixers and the shared MLP are whole; the
  partial sum goes on to the next layer. Every held expert is computed
  for every token, in a scan over the held ones, and weighted by its
  routing weight (0 where not picked): the definition, not a dispatch.
- The vocabulary is the file's ``vocab_size`` rows of the tied table;
  the depth its ``num_hidden_layers`` with ``layer_types`` cut to as
  many.
- The weights arrive as the program's loader holds them: per kind of
  layer (``a_`` / ``m_``) stacked, gate and up split, ``[E, H, I]`` and
  ``[E, I, H]`` an expert stack, the router ``[H, experts]``.

``control`` breaks one thing. The Micro reference's ``no_decay``,
``no_conv`` and ``no_gate_norm`` (passed to its ``mamba``), and for what
is new: ``"no_shared_mlp"`` leaves ``shr`` out; ``"no_renorm"`` weighs
the kept experts by the softmax over ALL published experts, not
renormalised over the kept; ``"all_experts"`` works on every pick as an
uncut layer would, a pick on an expert held elsewhere going to the held
expert at the same place of this chip's range (the only weights there
are): what a program computes that stands in for the absent chips. It
moves nothing without a share. ``CONTROLS`` names those every chip run
must catch at three tolerances (1.5 at most AND 0.24 in the mean): at the
published widths ``no_decay`` moves a served logprob by 6.0-9.0 /
1.45-1.69, ``no_conv`` by 6.3-8.4 / 1.62-1.83, ``no_gate_norm`` by
9.5-15.3 / 2.91-3.27 and ``no_shared_mlp`` by 5.5-7.8 / 1.51-1.69 (my
chip runs, PR 55, 16 to 19 weight seeds). ``no_renorm`` (0.66-0.81 / 0.15-0.17)
and ``all_experts`` (1.28-2.31 / 0.33-0.39) are held by the float32 tests
on the CPU only (``tests/models/test_granite_moe_hybrid.py``,
``tests/benchmarking/test_benchmark_granite_moe_hybrid.py``): at weights
seeded alike the held half of ten picks of a 768-wide expert adds a sixth
of what the 1536-wide shared MLP adds, so a fault in the routed sum moves
a logprob past the limits themselves (0.5 / 0.08) but not three times past
them (``benchmarks/configs/granite-4.0-h-small-ep2.json``,
``tolerance_reason``). ``compute_dtype`` and
``round_dtype`` are for ``benchmarks/reference_precision.py``; the
recurrent state stays float32 in every reading, as the program holds it.
"""

import jax
import jax.numpy as jnp

from benchmarks.references.deepseek import _caster, _rms
from benchmarks.references.granite_hybrid import attention, mamba

F32 = jnp.float32


def held_range(cfg: dict) -> tuple[int, int]:
    """(first, count) of the routed experts that exist here."""
    n = cfg["num_local_experts"]
    share = cfg.get("expert_share")
    return (share["index"] * n if share else 0), n


def route(x, router, cfg: dict, control=None):
    """``[S, published experts]`` routing weights (0 where not picked):
    the ``num_experts_per_tok`` largest logits, a softmax over them."""
    S = x.shape[0]
    logits = x.astype(F32) @ router.astype(F32)
    vals, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    if control == "no_renorm":
        kept = jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, 1)
    else:
        kept = jax.nn.softmax(vals, -1)
    return jnp.zeros_like(logits).at[jnp.arange(S)[:, None], idx].set(kept)


def moe(x, stacks: dict, i: int, cfg: dict, control=None, dt=F32,
        round_dtype=None):
    """The routed part of one layer for ``x`` ``[S, H]``: the held
    experts' weighted sum. ``stacks`` holds ``router`` ``[L, H, R]`` and
    ``w_gate`` / ``w_up`` / ``w_down`` ``[L, E, ...]`` of the layer's
    kind, ``i`` the layer's place in them; an expert's matrices are read
    out of the stack one expert at a time."""
    w_ = _caster(dt, round_dtype)
    lo, n = held_range(cfg)
    weight = route(x, stacks["router"][i], cfg, control)
    if control == "all_experts":
        held = sum(weight[:, c:c + n] for c in range(0, weight.shape[1], n))
    else:
        held = weight[:, lo:lo + n]
    held = held.astype(dt)

    def one(acc, e):
        # A stack, or the serving layout's buffer a layer.
        wg, wu, wd = (w_(stacks[k][i][e] if isinstance(stacks[k], tuple)
                         else stacks[k][i, e])
                      for k in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return acc + y * jnp.take(held, e, axis=1)[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n))
    return y


def shared_mlp(x, lp: dict, pre: str, dt=F32, round_dtype=None):
    w_ = _caster(dt, round_dtype)
    g, u, d = (w_(lp[pre + k]) for k in ("gate", "up", "down"))
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def logprobs(params: dict, cfg: dict, ids, control: str | None = None,
             compute_dtype=F32, round_dtype=None):
    """[S, vocab] log-softmax at every position of ``ids``."""
    dt = compute_dtype
    w_ = _caster(dt, round_dtype)
    eps = cfg["rms_norm_eps"]
    lay = params["layers"]
    rm = cfg["residual_multiplier"]
    x = params["embed"][ids].astype(dt) * jnp.asarray(
        cfg["embedding_multiplier"], dt)
    at = {"a_": 0, "m_": 0}
    for kind in cfg["layer_types"]:
        pre = "a_" if kind == "attention" else "m_"
        i = at[pre]
        at[pre] += 1
        # Everything of the layer but its expert stacks (a layer's slab
        # sliced whole would be made as a copy of the slab).
        lp = {k: v[i] for k, v in lay.items()
              if k.startswith(pre) and k[2:4] != "w_"}
        if kind == "attention":
            mix = attention(w_(_rms(x, lp["a_ln"], eps)), lp, cfg, control,
                            dt, round_dtype)
        else:
            mix = mamba(w_(_rms(x, lp["m_norm"], eps)), lp, cfg, control,
                        dt, round_dtype)
        x = x + rm * mix
        h = w_(_rms(x, lp[pre + "pre_ln"], eps))
        stacks = {k: lay[pre + k]
                  for k in ("router", "w_gate", "w_up", "w_down")}
        y = moe(h, stacks, i, cfg, control, dt, round_dtype)
        if control != "no_shared_mlp":
            y = y + shared_mlp(h, lp, pre, dt, round_dtype)
        x = x + rm * y
    x = _rms(x, params["final_ln"], eps)
    logits = (x @ w_(params["embed"].T)).astype(F32) / cfg["logits_scaling"]
    return jax.nn.log_softmax(logits, axis=-1)


CONTROLS = {"GraniteMoeHybridForCausalLM": [
    "no_decay", "no_conv", "no_gate_norm", "no_shared_mlp"]}
