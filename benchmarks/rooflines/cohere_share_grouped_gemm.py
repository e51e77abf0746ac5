"""The grouped GEMMs of a Command A+ expert layer of which this chip
holds a share (``expert_share`` in the configuration file;
``MixtralForCausalLM.moe_dispatch`` from ``models/cohere_moe.py``): the
count is ``local_grouped_gemm``'s, this file says where a ``cohere2_moe``
configuration keeps the numbers it reads and which tokens it charges the
weights to."""

from benchmarks.rooflines import local_grouped_gemm


class _DecodeTokensOnly:
    """The capture's counters with no prefill tokens."""

    def __init__(self, ctx):
        self.ctx = ctx

    def step_mean(self, name, labels=None):
        value = self.ctx.step_mean(name, labels)
        if value is not None and name == "vdt:step_tokens" and \
                (labels or {}).get("kind") == "prefill":
            return 0.0
        return value


def work(config: dict, ctx) -> dict | None:
    """``local_grouped_gemm.work`` (its docstring says which way each
    count errs) on this family's keys: the experts held are
    ``num_experts``, an expert's width is ``intermediate_size`` (the
    configuration has no key of its own for it), and no layer is dense
    (``first_k_dense_replace`` 0: ``prefix_dense_intermediate_size`` is
    unused). Picks on held experts are what an even router gives, T x 8 x
    16 / 128 = T: the program exports no counter of them.

    The operations are counted from all of a step's tokens, the BYTES
    from its decode tokens alone. A capture here mixes decode steps of
    about 21 tokens, whose picks hit 12 of the 16 held experts, with
    chunk steps of 2 069, whose picks hit all 16; the mean T of such a
    mix (several hundred) would charge all 16 to every step, a third too
    many for the decode steps that are most of them, and a capture with
    few chunk steps could then read over 100%. Every step holds the
    decode tokens, so their expected hits are read by every step: LOW by
    the experts only a chunk's picks reach. Left out besides, LOW: the
    four shared experts (plain matrix products, not this kernel), the
    activations (since PR 40 the dispatch gathers ``share_capacity``
    rows, not T x k), the sort, the scatter and the router."""
    if "num_experts" not in config or "intermediate_size" not in config:
        return None
    mapped = {**config, "n_routed_experts": config["num_experts"],
              "moe_intermediate_size": config["intermediate_size"],
              "first_k_dense_replace": 0}
    whole = local_grouped_gemm.work(mapped, ctx)
    decode = local_grouped_gemm.work(mapped, _DecodeTokensOnly(ctx))
    if whole is None or decode is None:
        return None
    return {"bytes": decode["bytes"], "flops": whole["flops"]}
