"""Per-step sampling tensors (reference: vllm/v1/sample/metadata.py
``SamplingMetadata`` + the TPU variant in v1/sample/tpu/).

Every field is a dense [R] array so any mix of per-request parameters
lowers to the same compiled graph — adding a request never recompiles.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclass
class SamplingMetadata:
    # [R] float32; 0.0 means greedy.
    temperature: jax.Array
    # [R] int32; 0 disables top-k.
    top_k: jax.Array
    # [R] float32; 1.0 disables top-p.
    top_p: jax.Array
    # [R] float32; 0.0 disables min-p.
    min_p: jax.Array
    # [R] int32 per-step fold-in values: derived from (user seed, step) for
    # seeded requests or (engine rng, step) otherwise, built on the host
    # in int64 and wrapped to the 32 bits the device holds.
    seeds: jax.Array


def pack_sampling(temperature, top_k, top_p, min_p, seeds,
                  record_rows=None) -> np.ndarray:
    """The five [R] host arrays as one [5, R] int32 buffer, one transfer
    a step. Floats ride as their bit patterns (bit-exact); the int64
    seeds wrap to the int32 the programs have always received (JAX
    without x64 made the same cast on the way to the device).
    ``record_rows`` (the pipelined step) rides as a sixth row: the batch
    row each sampled token is recorded under (``record_sampled``)."""
    fields = [
        np.asarray(temperature, np.float32).view(np.int32),
        np.asarray(top_k, np.int32),
        np.asarray(top_p, np.float32).view(np.int32),
        np.asarray(min_p, np.float32).view(np.int32),
        np.asarray(seeds).astype(np.int32),
    ]
    if record_rows is not None:
        fields.append(np.asarray(record_rows, np.int32))
    return np.stack(fields)


def unpack_sampling(packed: jax.Array) -> SamplingMetadata:
    """Inverse of ``pack_sampling``, inside the jitted sampler."""

    def f32(row):
        return jax.lax.bitcast_convert_type(row, jnp.float32)

    return SamplingMetadata(temperature=f32(packed[0]), top_k=packed[1],
                            top_p=f32(packed[2]), min_p=f32(packed[3]),
                            seeds=packed[4])


def record_sampled(last: jax.Array, packed: jax.Array,
                   tokens: jax.Array) -> jax.Array:
    """Inside the jitted sampler: the step's sampled tokens scattered
    into ``last``, the [max_num_reqs] mirror of each batch row's last
    sample that the next step's forward reads its unknown input tokens
    from (worker/step_inputs.py). Padding rows carry an out-of-range
    index and drop."""
    return last.at[packed[5]].set(tokens, mode="drop")


@jax.tree_util.register_dataclass
@dataclass
class ExtendedSamplingMetadata:
    """Logits-processor inputs for the extended sampling path (penalties,
    logit bias, allowed-token masks, min-tokens stop suppression;
    reference: vllm/v1/sample/sampler.py:18 apply_penalties +
    logits_processor.py:517). Static shapes: the history buffer is always
    [R, max_model_len] and the sparse bias buffer is a fixed [R, B] so the
    extended graph is keyed only by R.
    """

    # [R, L] int32 token history (prompt + generated). Entries past
    # total_len may hold ANY id (the input batch pads with 0): the
    # penalty scatters weight them by the in-window masks, so padding
    # contributes zero regardless of its value.
    hist_tokens: jax.Array
    # [R] int32 prompt length (presence/frequency penalize output only).
    prompt_len: jax.Array
    # [R] int32 total tokens so far (prompt + output).
    total_len: jax.Array
    # [R] float32 penalties; 0 / 0 / 1 disable.
    presence_penalty: jax.Array
    frequency_penalty: jax.Array
    repetition_penalty: jax.Array
    # Sparse additive bias applied with set(): [R, B] token ids (pad: out of
    # vocab, dropped) and values. Carries user logit_bias, min-tokens stop
    # suppression (-inf at stop ids), and allowed_token_ids (base_fill=-inf
    # with 0-valued entries at the allowed ids).
    bias_ids: jax.Array
    bias_vals: jax.Array
    # [R] float32 fill applied to the whole row before the sparse set():
    # 0.0 normally, -inf for allowed_token_ids rows.
    base_fill: jax.Array
