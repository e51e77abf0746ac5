"""The gated delta-rule kernels (``ops/kda.py``; ``kda_recurrent`` and
``kda_chunk`` in the trace): per head a ``[head_dim, head_dim]`` float32
state that every token decays, corrects and writes, one state row a
request in each linear-attention layer."""

STATE_ITEM = 4     # the state and the kernels' rows are float32
ROWS_A_TOKEN = 5   # q, k, v and the decay read, the output written


def kda_layers(config: dict) -> int:
    """Layers that attend by the delta rule: all but every
    ``layer_group_size``-th (models/bailing.py)."""
    group = config["layer_group_size"]
    return sum(1 for i in range(config["num_hidden_layers"])
               if (i + 1) % group)


def work(config: dict, ctx) -> dict | None:
    """With d the decode and p the prefill tokens of a step
    (``vdt:step_tokens{kind}``, mean over the capture's steps), N heads
    of D channels and Lk linear-attention layers:

    - bytes: d x Lk x 2 x N x D x D x 4 B, the state of every decoding
      request read once and written once in every layer, plus (d + p) x
      Lk x 5 x N x D x 4 B, each token's query, key, value and decay rows
      read and its output row written.
    - operations: (d + p) x Lk x N x 7 x D x D: per token and head the
      decay of the state (D x D), the state's answer for the key, the
      rank-one correction and the read against the query (2 x D x D
      each).

    The larger of the two times wins in the reader; the state is 128
    times a token's rows and the kernel does under one operation a
    byte, so the bytes win by two orders of magnitude and the count is
    the decode steps' bound. Left out, so the count errs LOW and the
    share with it: a prefill segment's state row read and written once a
    step, the state handed from chunk to chunk in VMEM, the chunked
    form's products inside a chunk (matrix products of 64 x 128 x 128
    a head, and the solve, most of them plain XLA beside the kernel and
    not in the kernel's time either), the lane padding of the rows the
    recurrent kernel reads as columns, and the dump row that padding
    slots of a decode batch read and write once. A configuration
    without such layers has no such kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    if None in tokens or "kda_lower_bound" not in config:
        return None
    decode, prefill = tokens
    layers = kda_layers(config)
    heads, dim = config["num_attention_heads"], config["head_dim"]
    state = heads * dim * dim * STATE_ITEM
    rows = ROWS_A_TOKEN * heads * dim * STATE_ITEM
    return {"bytes": float(layers * (2 * decode * state
                                     + (decode + prefill) * rows)),
            "flops": float((decode + prefill) * layers * heads
                           * 7 * dim * dim)}
