"""The grouped GEMMs of a Granite 4.0-H expert layer of which this chip
holds a share (``expert_share`` in the configuration file;
``MixtralForCausalLM.moe_dispatch`` from ``models/granite_hybrid.py``):
the count is ``local_grouped_gemm``'s, this file says where a
``granitemoehybrid`` configuration keeps the numbers it reads and which
tokens it charges the weights to."""

from benchmarks.rooflines import local_grouped_gemm
from benchmarks.rooflines.cohere_share_grouped_gemm import _DecodeTokensOnly


def work(config: dict, ctx) -> dict | None:
    """``local_grouped_gemm.work`` (its docstring says which way each
    count errs) on this family's keys: the experts held are
    ``num_local_experts``, an expert's width is ``intermediate_size``
    (the configuration has no key of its own for it; the shared MLP's
    ``shared_intermediate_size`` is a plain matrix product and not this
    kernel), and every layer has experts (no dense layer). Picks on held
    experts are what an even router gives, T x 10 x 36 / 72 = 5 T: the
    program exports no counter of them (PERF.md, owed list).

    As ``cohere_share_grouped_gemm`` does, the operations are counted
    from all of a step's tokens and the BYTES from its decode tokens
    alone: a capture mixes decode steps of about 64 tokens with the one
    step in ten that carries a prompt besides, and the weights are read
    once a step whatever it holds. Here a decode step's 320 even picks
    hit all 36 held experts (one is missed with probability 1e-4), so
    the two readings of the bytes agree: layers x 36 x 3 x 4096 x 768 x
    2 B = 6.79 GB a step at the published widths, 8.3 ms at 819 GB/s.
    Left out, LOW: the activations (``share_capacity`` rows of 4096
    gathered, 768-wide products written and read, rows of 4096
    scattered), the sort and the router. A configuration without an
    expert share has no such kernel: ``None``."""
    if "num_local_experts" not in config or \
            "intermediate_size" not in config:
        return None
    mapped = {**config, "n_routed_experts": config["num_local_experts"],
              "moe_intermediate_size": config["intermediate_size"],
              "first_k_dense_replace": 0}
    whole = local_grouped_gemm.work(mapped, ctx)
    decode = local_grouped_gemm.work(mapped, _DecodeTokensOnly(ctx))
    if whole is None or decode is None:
        return None
    return {"bytes": decode["bytes"], "flops": whole["flops"]}
