"""The paged latent-attention kernel (``ops/pallas_mla.py``,
``mla_latent_pages`` in the trace): MQA in the absorbed form over one
latent row a token, ``kv_lora_rank`` lanes of latent and
``qk_rope_head_dim`` of rope key, shared by every head."""

from benchmarks.rooflines import item_bytes

LANE_TILE = 128   # the TPU cache pads a row to whole 128-lane tiles


def row_lanes(config: dict) -> int:
    """Lanes one latent row occupies in the device's cache (576 -> 640
    for kv_lora_rank 512 + rope 64; ops/mla.latent_storage_dim)."""
    lanes = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-lanes // LANE_TILE) * LANE_TILE


def latent_bytes_per_token(config: dict) -> int:
    """One token's rows over every layer (7 680 B for
    ``gigachat3-702b-ep16``: 640 lanes x 2 B x 6 layers)."""
    return (config["num_hidden_layers"] * row_lanes(config)
            * item_bytes(config))


def work(config: dict, ctx) -> dict | None:
    """With N the context lengths summed over a step's requests
    (``vdt:step_kv_tokens_read``, mean over the capture's steps: every
    layer reads every request's whole context once):

    - bytes: N x the stored row (padded lanes included: the kernel's
      DMA moves whole rows) x layers.
    - operations: N x layers x 2 x (kv_lora_rank + rope + kv_lora_rank)
      x heads: each row read meets AT LEAST one query of every head,
      in the score product (576 wide) and in the value product (512).
      The counter has no q x kv product, so a prefill chunk, whose
      every query meets the rows before it, is counted as one query a
      request: LOW, by up to the chunk's length.

    The larger of the two times wins in the reader; at one query a
    request the kernel does 109 operations a byte, under the chip's 240,
    so the bytes win and the count is the decode steps' bound.
    Left out, so the count errs LOW and the share with it: the queries
    read ([T, heads, 640]) and the outputs written ([T, heads, 512]),
    the page tables, a long chunk's rows read once per tile of its
    queries and not once, and its operations. A configuration without a
    latent cache has no such kernel: ``None``."""
    tokens = ctx.step_mean("vdt:step_kv_tokens_read")
    if tokens is None or "kv_lora_rank" not in config:
        return None
    rank, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    per_row = 2.0 * (rank + rope + rank) * config["num_attention_heads"]
    return {"bytes": float(tokens * latent_bytes_per_token(config)),
            "flops": tokens * config["num_hidden_layers"] * per_row}
