"""The paged attention kernel (``ops/pallas_attention.py``,
``unified_write_attend_pallas``) of a model that mixes window layers
with full layers (``models/cohere_moe.py``): a full layer reads every
request's whole context, a window layer starts its page loop at the
first block inside the window."""

from benchmarks.rooflines import item_bytes


def layer_kinds(config: dict) -> tuple[int, int]:
    """(full layers, window layers) of the configuration as it is run."""
    types = config["layer_types"][:config["num_hidden_layers"]]
    window = sum(1 for t in types if t == "sliding_attention")
    return len(types) - window, window


def kv_bytes_per_token_layer(config: dict) -> int:
    """K and V of one token in ONE layer (4 096 B for
    ``command-a-plus-ep8``)."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * item_bytes(config))


def work(config: dict, ctx) -> dict | None:
    """Bytes a step: ``vdt:step_kv_tokens_read`` (context lengths summed
    over the step's requests) x the K/V bytes a token a layer x the full
    layers, plus ``vdt:step_kv_window_tokens_read`` (``min(context,
    window - 1 + scheduled)`` summed likewise: what one window layer must
    read) x the same x the window layers. Operations a step:
    ``vdt:step_attn_pairs`` (query x key pairs inside the masks of one
    layer of each kind) x the layers of that kind x 4 x head size x query
    heads (a multiply and an add for the score and for the weighted
    value, in every query head). The larger of the two times wins in the
    reader. All three are means over the capture's steps.

    Which way each errs. Bytes, LOW: the kernel reads whole blocks of
    128 positions, so a window layer reads up to 127 positions in front
    of the window and a decode group walks as many blocks as its longest
    sequence (the shorter sequence's last block is fetched again under
    the mask); a prefill chunk's K/V is read once per tile of 16 queries
    and not once; queries, outputs, the K/V written and the page tables
    are left out. Operations, LOW: the kernel multiplies whole blocks and
    masks afterwards (a tile's diagonal block and the window's edge
    blocks count in full on the chip, by their unmasked pairs here), and
    the float32 upcast of K costs several bf16 passes a product, which
    the peak of 197 TFLOP/s does not know. The larger-of-means against
    the mean of the larger: LOW where decode steps (bytes bound) and
    chunk steps (operations bound) mix in one capture. A program without
    the window counters (no window cache group) gives ``None``."""
    full_tokens = ctx.step_mean("vdt:step_kv_tokens_read")
    window_tokens = ctx.step_mean("vdt:step_kv_window_tokens_read")
    pairs = {kind: ctx.step_mean("vdt:step_attn_pairs",
                                 {"layer_kind": kind})
             for kind in ("full", "window")}
    if None in (full_tokens, window_tokens, *pairs.values()):
        return None
    n_full, n_window = layer_kinds(config)
    per_pair = 4 * config["head_dim"] * config["num_attention_heads"]
    return {
        "bytes": float((full_tokens * n_full + window_tokens * n_window)
                       * kv_bytes_per_token_layer(config)),
        "flops": float((pairs["full"] * n_full
                        + pairs["window"] * n_window) * per_pair),
    }
