"""The power-retention kernels (``ops/power_retention.py``;
``power_retention_recurrent`` and ``power_retention_chunk`` in the
trace): per K/V head a float32 state over the symmetric square of the
key, ``D (D + 1) / 2`` rows of ``D`` value channels and one normaliser
column, that every token decays and adds to and that each of the head's
query heads reads; one state row a request in every layer."""

STATE_ITEM = 4     # the state and the kernels' rows are float32


def symmetric_rows(config: dict) -> int:
    """Products ``k_i k_j, i <= j`` of one head: 8 256 at a head of 128.
    ALWAYS this count, whatever basis the program holds the state in (its
    cyclic basis has 8 320): padding or redundancy then reads as lost
    efficiency, and a later kernel is read against the same work."""
    dim = config["head_dim"]
    return dim * (dim + 1) // 2


def state_bytes(config: dict) -> int:
    """One layer's state and normaliser of one sequence (34.1 MB for
    ``brumby-14b``: 8 K/V heads x 8 256 x 129 x 4 B)."""
    return (config["num_key_value_heads"] * symmetric_rows(config)
            * (config["head_dim"] + 1) * STATE_ITEM)


def work(config: dict, ctx) -> dict | None:
    """With d the decode and p the prefill tokens of a step
    (``vdt:step_tokens{kind}``, mean over the capture's steps), N query
    heads on K K/V heads of D channels, R = D (D + 1) / 2 and L layers:

    - bytes: d x L x 2 x K x R x (D + 1) x 4 B, the state and normaliser
      of every decoding request read once and written once in every
      layer, plus (d + p) x L x ((2 N + 2 K) x D + K) x 4 B, each token's
      query, key and value rows and its gates read and its output rows
      written.
    - operations: (d + p) x L x (2 N + 3 K) x R x (D + 1): per token, for
      each K/V head the decay of the state and the rank-one ``phi(k)
      v^T`` added (3 an entry), for each query head the read against it
      (2 an entry).

    The larger of the two times wins in the reader, over the MEAN step of
    the capture: a decode step is bound by its bytes (the state is five
    thousand times a token's rows and the recurrent kernel does 1.6
    operations a byte), a 2 048-token chunk step by its operations, and
    on the mean the operations win once about one step in six is a chunk
    step. So the share is a mixture (some 79% on decode steps, 16-25% on
    chunk steps) and moves with the chunk steps a capture of three
    seconds happens to hold as much as with either kernel: read it
    beside ``kernel.retention_chunk_tokens_per_step`` of the same run.

    Which way each count errs. The BYTES err low: left out are a prefill
    segment's state row read and written once a step, the 0.8% of the
    program's cyclic basis over R, and the dump row that padding slots
    of a decode batch read and write once. The OPERATIONS err both ways
    for a prefill token: HIGH by the decay, charged a token (one of the
    three operations an entry and K/V head: 8 of 104, 7.7%) where the
    chunked form decays the state once a chunk of 128; LOW by the
    products INSIDE a chunk (the quadratic form among a chunk's 128
    tokens: 2 x 2 x 128 x 128 a token and query head, 1.5% of the read
    against the state) and by the float32 products' passes (the state's
    read is three bfloat16 passes and the other products six, which the
    peak of 197 TFLOP/s does not know: a chunk step at 100% of this
    count would sit at a third of the MXU's peak at best). For a decode
    token they are as counted. A configuration without such layers has
    no such kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    if None in tokens or config.get("model_type") != "brumby":
        return None
    decode, prefill = tokens
    layers = config["num_hidden_layers"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["head_dim"]
    rows = ((2 * heads + 2 * kv_heads) * dim + kv_heads) * STATE_ITEM
    entries = symmetric_rows(config) * (dim + 1)
    return {"bytes": float(layers * (2 * decode * state_bytes(config)
                                     + (decode + prefill) * rows)),
            "flops": float((decode + prefill) * layers
                           * (2 * heads + 3 * kv_heads) * entries)}
