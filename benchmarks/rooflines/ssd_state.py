"""The Mamba-2 kernels (``ops/ssd.py``; ``ssd_recurrent`` and
``ssd_chunk`` in the trace): per head a ``[d_head, d_state]`` float32
state that every token decays and adds to, one state row a request in
each mamba layer."""

STATE_ITEM = 4     # the state and the kernels' rows are float32
ROWS_A_TOKEN = 3   # the decay and dt x read, y written, over d_inner


def mamba_layers(config: dict) -> int:
    """Layers that ``layer_types`` calls ``mamba``
    (models/granite_hybrid.py)."""
    return sum(1 for kind in config["layer_types"] if kind == "mamba")


def state_bytes(config: dict) -> int:
    """One layer's recurrent state of one sequence (2 MiB for
    ``granite-4.0-h-micro``: 64 heads x 64 x 128 x 4 B)."""
    return (config["mamba_n_heads"] * config["mamba_d_head"]
            * config["mamba_d_state"] * STATE_ITEM)


def work(config: dict, ctx) -> dict | None:
    """With d the decode and p the prefill tokens of a step
    (``vdt:step_tokens{kind}``, mean over the capture's steps), Hm heads
    of P channels, N state channels, G groups and Lm mamba layers:

    - bytes: d x Lm x 2 x Hm x P x N x 4 B, the state of every decoding
      request read once and written once in every layer, plus (d + p) x
      Lm x (3 x Hm x P + 2 x G x N) x 4 B, each token's decay and
      ``dt x`` rows and its ``B`` and ``C`` read and its ``y`` written.
    - operations: (d + p) x Lm x Hm x 6 x P x N: per token and head the
      decay of the state, the rank-one ``dt x B^T`` added and the read
      against ``C`` (2 x P x N each).

    The larger of the two times wins in the reader; the state is 128
    times a token's rows and the kernel does under one operation a
    byte, so the bytes win by two orders of magnitude and the count is
    the decode steps' bound. Left out, so the count errs LOW and the
    share with it: a prefill segment's state row read and written once
    in each block of lanes, the state handed from chunk to chunk in
    VMEM, the chunked form's rows (``exp(L)`` and ``y`` of ``d_inner``
    lanes a token, the chunk's ``dS``: about 45 KB a token and 2 MiB a
    chunk a layer) and its products (``C S`` in the kernel, the rest
    plain XLA beside it and in no kernel's time), the sublane padding of
    the one-row blocks the recurrent kernel reads, and the dump row that
    padding slots of a decode batch read and write once. A configuration
    without such layers has no such kernel: ``None``."""
    tokens = [ctx.step_mean("vdt:step_tokens", {"kind": kind})
              for kind in ("decode", "prefill")]
    if None in tokens or "mamba_d_state" not in config \
            or "layer_types" not in config:
        return None
    decode, prefill = tokens
    layers = mamba_layers(config)
    heads, dim = config["mamba_n_heads"], config["mamba_d_head"]
    state_ch, groups = config["mamba_d_state"], config["mamba_n_groups"]
    rows = (ROWS_A_TOKEN * heads * dim + 2 * groups * state_ch) * STATE_ITEM
    return {"bytes": float(layers * (2 * decode * state_bytes(config)
                                     + (decode + prefill) * rows)),
            "flops": float((decode + prefill) * layers * heads
                           * 6 * dim * state_ch)}
