"""The grouped GEMMs of a sparse expert layer (``jax.lax.ragged_dot`` in
``models/mixtral.py``: gate, up and down projections over the tokens
sorted by expert)."""

from benchmarks.rooflines import item_bytes


def work(config: dict, ctx) -> dict | None:
    """With T the tokens of a step (``vdt:step_tokens``, decode and
    prefill, mean over the capture's steps), k experts a token, E
    experts, H and I the hidden and the expert's intermediate size:

    - bytes: layers x min(E, T x k) x 3 x H x I x item size: the gate,
      up and down weights of every expert that is hit, read once a layer.
      min(E, T x k) bounds the experts hit from ABOVE: T x k draws miss
      an expert with probability (1 - k/E)^T, 1.8% at T = 30, k = 8,
      E = 64, so the count errs high by that much at the chat cell's
      batch and by 0.3% at 44 tokens; below T = E / k a mean T also
      overstates the mean of the minimum.
    - operations: 2 x T x k x 3 x H x I x layers.

    The larger of the two times wins in the reader; taking each from the
    mean step understates a mix of memory-bound and compute-bound steps
    (the mean of the larger is at least the larger of the means): LOW.
    Left out, LOW: the activations read and written (T x k rows of H and
    of I), the sort and the scatter around the GEMMs, the router. A
    configuration without experts has no such kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    if None in tokens or "num_experts" not in config:
        return None
    t, k = sum(tokens), config["num_experts_per_tok"]
    one_expert = 3 * config["hidden_size"] * config["intermediate_size"]
    layers = config["num_hidden_layers"]
    return {"bytes": float(layers * min(config["num_experts"], t * k)
                           * one_expert * item_bytes(config)),
            "flops": 2.0 * t * k * one_expert * layers}
