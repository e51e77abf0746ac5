"""The paged attention kernel (``ops/pallas_attention.py``,
``unified_write_attend_pallas``): bound by the memory, by the K and V it
has to read."""

from benchmarks.rooflines import item_bytes


def kv_bytes_per_token(config: dict) -> int:
    """K and V of one token over every layer (128 KiB for ``olmo2-1b``,
    64 KiB for ``olmoe-1b-7b`` at depth 8)."""
    head = config.get("head_dim") or \
        config["hidden_size"] // config["num_attention_heads"]
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * head * item_bytes(config))


def work(config: dict, ctx) -> dict | None:
    """Bytes a step: the context lengths summed over the step's requests
    (``vdt:step_kv_tokens_read``, mean over the capture's steps) times
    the K/V bytes a token: every layer reads every request's whole
    context once.

    Left out, so the count errs LOW and the share with it: the queries
    read and the outputs written (one row a scheduled token), the K/V of
    the step's own tokens WRITTEN into the pages, the page tables, and a
    long prefill chunk's K/V read once per block of its queries and not
    once. Operations are not counted (the counter has no q x kv product):
    a step of decodes does 4 x head size operations for every K/V element
    read, far under the chip's 240 operations a byte; a step that is
    mostly one long prefill chunk may be compute bound, and reads lower
    than it should."""
    tokens = ctx.step_mean("vdt:step_kv_tokens_read")
    if tokens is None:
        return None
    return {"bytes": tokens * kv_bytes_per_token(config), "flops": 0.0}
