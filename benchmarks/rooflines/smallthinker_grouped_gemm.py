"""The grouped GEMMs of a SmallThinker expert layer
(``MixtralForCausalLM.moe_dispatch`` from ``models/smallthinker.py``:
ReLU-gated experts, every one of them held, at picks made before
attention): the count is ``grouped_gemm``'s, this file says where a
SmallThinker configuration keeps the numbers it reads."""

from benchmarks.rooflines import grouped_gemm


def translated(config: dict) -> dict | None:
    """The configuration under the keys ``grouped_gemm.work`` reads:
    ``moe_num_primary_experts`` experts of width ``moe_ffn_hidden_size``,
    ``moe_num_active_primary_experts`` a token. Another family's
    configuration gives ``None``."""
    keys = ("moe_num_primary_experts", "moe_num_active_primary_experts",
            "moe_ffn_hidden_size")
    if any(k not in config for k in keys):
        return None
    return {**config, "num_experts": config[keys[0]],
            "num_experts_per_tok": config[keys[1]],
            "intermediate_size": config[keys[2]]}


def work(config: dict, ctx) -> dict | None:
    """``grouped_gemm.work`` (its docstring says what is left out, LOW)
    on this family's keys. The activation (ReLU for SiLU) and where the
    picks are made change neither the bytes nor the operations of the
    three products.

    Which way the count errs HERE: the experts hit are bounded from
    above by ``min(E, T x k)``. At this cell's decode batch, 32 rows of 6
    picks over 64 experts, an even router misses an expert with
    probability (1 - 6/64)^32 = 4.3%, so about 61 of the 64 are read and
    the bytes err HIGH by 4%; a step that carries a chunk hits all 64.
    The grouped GEMMs run far enough under their roofline (a few rows an
    expert) that 4% cannot carry the share past 100%."""
    mapped = translated(config)
    return None if mapped is None else grouped_gemm.work(mapped, ctx)
