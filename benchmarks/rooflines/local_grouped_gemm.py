"""The grouped GEMMs of an expert layer of which this chip holds a
share (``expert_share`` in the configuration file;
``DeepseekV2ForCausalLM.moe_dispatch`` in ``models/deepseek.py``): the
router scores all published experts, and the gate, up and down
projections run over the picks that land on the experts held here."""

from benchmarks.rooflines import item_bytes


def work(config: dict, ctx) -> dict | None:
    """With T the tokens of a step (``vdt:step_tokens``, decode and
    prefill, mean over the capture's steps), k experts a token, n held
    of E published experts, H and I the hidden and the expert's
    intermediate size, and the expert layers alone (the leading dense
    layers have no such GEMM):

    - picks on held experts: p = T x k x n / E. The program exports no
      counter of them (PERF.md, Open questions), so this is what a
      router that spreads its picks evenly gives; seeded random routers
      do, a trained one need not.
    - bytes: layers x n x (1 - (1 - 1/n)^p) x 3 x H x I x item size:
      the weights of the held experts that p even picks are expected to
      hit, read once a layer. Taken at the mean T it errs HIGH where T
      swings (the expectation is concave in p), by under 2% between a
      decode step's 48 tokens and a chunk step's 2 100; group-limited
      routing puts a token's picks in few groups, which changes how
      many tokens reach this chip's half group and not how many picks.
    - operations: 2 x p x 3 x H x I x layers.

    The larger of the two times wins in the reader; taking each from the
    mean step understates a mix of memory-bound and compute-bound steps:
    LOW. Left out, LOW: the activations read and written (the dispatch
    gathers T x k rows of H, held or not), the sort and the scatter, the
    router over E, the shared expert (a plain matrix product, not this
    kernel). A configuration without an expert share has no such
    kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    share = config.get("expert_share")
    if None in tokens or not share:
        return None
    held = config["n_routed_experts"]
    picks = (sum(tokens) * config["num_experts_per_tok"] * held
             / share["published_experts"])
    one_expert = 3 * config["hidden_size"] * config["moe_intermediate_size"]
    layers = (config["num_hidden_layers"]
              - config.get("first_k_dense_replace", 0))
    hit = held * (1.0 - (1.0 - 1.0 / held) ** picks)
    return {"bytes": float(layers * hit * one_expert * item_bytes(config)),
            "flops": 2.0 * picks * one_expert * layers}
