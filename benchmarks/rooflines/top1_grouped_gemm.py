"""The grouped GEMMs of a top-1 expert layer whose router has one
outcome more than it has experts (``jax.lax.ragged_dot`` through
``models/mixtral.py`` ``moe_dispatch`` from ``models/zaya.py``: a token
picks one of E experts or none)."""

from benchmarks.rooflines import item_bytes


def experts_hit(experts: int, tokens: float) -> float:
    """Experts that ``tokens`` picks, each uniform over ``experts + 1``
    outcomes, hit on average: 15.7 of 16 at 64 tokens."""
    return experts * (1.0 - (1.0 - 1.0 / (experts + 1)) ** tokens)


def work(config: dict, ctx) -> dict | None:
    """With T the tokens of a step (``vdt:step_tokens``, decode and
    prefill, mean over the capture's steps), E experts, H and I the
    hidden and the expert's intermediate size
    (``moe_intermediate_size``):

    - bytes: layers x E (1 - (1 - 1/(E + 1))^T) x 3 x H x I x item size:
      the gate, up and down weights of every expert that T picks over
      E + 1 equally likely outcomes hit, read once a layer.
    - operations: 2 x T x E/(E + 1) x 3 x H x I x layers: a token in
      seventeen reaches no expert.

    The larger of the two times wins in the reader. Which way it errs: a
    seeded or a trained router is not uniform, so fewer experts are hit
    than the uniform count says and the bytes err HIGH at small T, the
    share with them: by under 2% at T = 64 (15.7 of 16 counted; a
    router that never picked one expert would hit 15), by more below
    that; and the count of the mean step overstates the mean of the
    counts (the hit count is concave in T). Left out, LOW: the
    activations read and written (T rows of H and of I), the sort, the
    gather and the scatter around the GEMMs, the router. Taking bytes
    and operations from the mean step understates a mix of memory-bound
    and compute-bound steps: LOW. A configuration without such a router
    has no such kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    if None in tokens or "router_hidden_size" not in config \
            or config.get("num_experts_per_tok") != 1:
        return None
    t, experts = sum(tokens), config["num_experts"]
    one_expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    layers = config["num_hidden_layers"]
    return {"bytes": float(layers * experts_hit(experts, t) * one_expert
                           * item_bytes(config)),
            "flops": 2.0 * t * experts / (experts + 1) * one_expert * layers}
