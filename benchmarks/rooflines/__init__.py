"""Work modules of the ``roofline_share`` readers (``benchmarks/layers.py``),
one kernel each. A module has one function,

    work(config: dict, ctx) -> {"bytes": float, "flops": float} | None

the bytes and operations that ONE STEP of the cell cannot avoid moving
through that kernel, from the cell's configuration file (the sizes) and
the run's counters (``ctx.step_mean``: what the steps of the capture
held). It counts low where it must guess, so that a share above 100% is
a fault of the count and never a result; its docstring says what it
leaves out. A counter it needs that the program does not export gives
``None``. Nothing here imports the program.
"""

ITEM_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def item_bytes(config: dict) -> int:
    """Bytes of one element as the cell serves it: the engine's dtype,
    else the configuration's own."""
    dtype = config.get("engine_args", {}).get("dtype") or \
        config["torch_dtype"]
    return ITEM_BYTES[dtype]
