"""Selective-state-space (Mamba) ops over the flat ragged token batch.

The reference implements Mamba with CUDA kernels operating on a
[batch, seq] layout plus per-request state tensors indexed through the
block table (csrc/mamba/mamba_ssm/ selective scan;
vllm/v1/attention/backends/mamba_attn.py builds chunk metadata so
varlen prefills can share one kernel launch).

The TPU design takes a different route: the engine's native batch layout
is already a FLAT ragged token array [T] (each request's scheduled chunk
occupies a contiguous run — see worker/model_runner._prepare_inputs), so
the recurrence runs directly on it as a SEGMENTED associative scan:

    h_t = a_t * h_{t-1} + b_t          (elementwise in [Di, N])

with a reset flag raised at each request's first token. The combine

    (a1, b1, f1) ∘ (a2, b2, f2) =
        f2 ? (a2, b2, f2) : (a1*a2, a2*b1 + b2, f1|f2)

is associative, so ``jax.lax.associative_scan`` evaluates every
request's recurrence in O(log T) depth with no [R, max_q] dense buffer
(which would be quadratic in the worst case: R and max_q both scale
with the token bucket). Chunk-resumed prefills fold their carried state
into the drive term of the chunk's first token; the final state of each
request is scattered back from its last token. Decode, chunked prefill,
and mixed batches are all the same code path — one compiled program per
token bucket, exactly like the attention layers.

State tensors are indexed by INPUT-BATCH ROW (the runner's persistent
request slots), not through the page pool: SSM state is fixed-size per
request, so paging buys nothing — this is the TPU form of the
reference's MambaSpec "one block per request" cache
(vllm/v1/kv_cache_interface.py MambaSpec, block_size = max_model_len).
Row S (= max_reqs) is a dump slot for padding writes.

The state cache (core/state_cache.py) re-enters the scan mid-sequence
through exactly this machinery: a restore fills the request's state
rows before the forward, and because the restored request is admitted
as a continuation (chunk_pos0 > 0), ``build_segment_info`` raises its
``has_init`` flag and the scan folds the restored carry into the
chunk's first token — no scan-side special case exists or is needed.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class SegmentInfo:
    """Per-token segment metadata for stateful (scan) layers, built once
    per forward from the AttentionBatch (ops/mamba.build_segment_info).

    All fields are device arrays with static shapes; ``row`` routes
    padding tokens to the dump slot S so every scatter stays masked.
    """

    # [T] int32: state-slot row per token (== input-batch row; S for
    # padding tokens).
    row: jax.Array
    # [T] bool: real (non-padding) token.
    valid: jax.Array
    # [T] int32: offset of the token within its request's scheduled
    # chunk (garbage at padding).
    off: jax.Array
    # [T] bool: first / last token of its request's chunk.
    start: jax.Array
    end: jax.Array
    # [T] bool: the token's request carries resumable state (its chunk
    # does not begin at position 0).
    has_init: jax.Array
    # [S+1] int32: scheduled chunk length per state row (0 = inactive).
    q_len_by_row: jax.Array
    # [S+1] int32: flat index of the chunk's first token per state row
    # (garbage where inactive).
    q_start_by_row: jax.Array
    # [S+1] bool: the row's chunk resumes carried state.
    has_init_by_row: jax.Array


jax.tree_util.register_dataclass(
    SegmentInfo,
    data_fields=[f.name for f in dataclasses.fields(SegmentInfo)],
    meta_fields=[],
)


def build_segment_info(batch, num_state_rows: int) -> SegmentInfo:
    """Derive SegmentInfo from an AttentionBatch.

    ``num_state_rows`` is S (the runner's max_num_reqs); tokens of
    inactive rows and padding scatter to dump row S.
    """
    T = batch.req_idx.shape[0]
    S = num_state_rows
    valid = batch.slot_mapping >= 0
    row = jnp.where(valid, batch.req_idx, S)

    # Per-row chunk geometry from seq_info (active rows only; inactive
    # seq_info rows are zero and must not clobber row 0).
    si = batch.seq_info  # [max_reqs, 4] = (q_start, q_len, kv_len, row)
    n_active = batch.num_seqs[0]
    idx = jnp.where(
        jnp.arange(si.shape[0]) < n_active, si[:, 3], S)
    q_start_by_row = jnp.zeros((S + 1, ), jnp.int32).at[idx].set(si[:, 0])
    q_len_by_row = jnp.zeros((S + 1, ), jnp.int32).at[idx].set(si[:, 1])
    # Position of the chunk's first token = kv_len - q_len.
    chunk_pos0 = jnp.zeros((S + 1, ), jnp.int32).at[idx].set(
        si[:, 2] - si[:, 1])

    off = batch.positions - chunk_pos0[row]
    q_len_tok = q_len_by_row[row]
    start = valid & (off == 0)
    end = valid & (off == q_len_tok - 1)
    has_init = valid & (chunk_pos0[row] > 0)
    return SegmentInfo(row=row, valid=valid, off=off, start=start,
                       end=end, has_init=has_init,
                       q_len_by_row=q_len_by_row,
                       q_start_by_row=q_start_by_row,
                       has_init_by_row=(q_len_by_row > 0)
                       & (chunk_pos0 > 0))


# ---------------------------------------------------------------------
# A step's segments by kind, for the state kernels that run one-token
# segments slot by slot and longer ones chunk by chunk (ops/kda.py,
# ops/power_retention.py): the slots, the chunks and the choice of form.
# ---------------------------------------------------------------------
# What a kernel is told of a slot or chunk: live; the row's stored state
# enters; the state of the grid step before stays (a continued run).
SLOT_VALID, SLOT_HAS_INIT, SLOT_CONT = 1, 2, 4
# Multi-token segments a step may bring beyond its bucket's own chunks
# and still run the chunked form (each may add one partial chunk).
MAX_CHUNK_SEGMENTS = 4


def slot_flags(valid, has_init, cont):
    return (valid.astype(jnp.int32) * SLOT_VALID
            + has_init.astype(jnp.int32) * SLOT_HAS_INIT
            + cont.astype(jnp.int32) * SLOT_CONT)


def num_chunks_for(num_tokens: int, chunk: int) -> int:
    """The chunked form's static chunk count in a program of
    ``num_tokens``."""
    return -(-num_tokens // chunk) + MAX_CHUNK_SEGMENTS


def chunk_form_tokens(q_lens, num_tokens: int, chunk: int) -> int:
    """Tokens of a step (``q_lens`` its segments' lengths as a numpy
    array, in a program of ``num_tokens``) that take the chunked form:
    ``by_form``'s rule, for the runner's counter."""
    multi = q_lens[q_lens > 1]
    if int((-(-multi // chunk)).sum()) > num_chunks_for(num_tokens, chunk):
        return 0
    return int(multi.sum())


def decode_slots(seg: SegmentInfo):
    """One-token segments, one slot a state row, live rows first:
    (state row, flags, flat token, live) a slot."""
    S = seg.q_len_by_row.shape[0] - 1
    live = seg.q_len_by_row[:S] == 1
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    live = live[order]
    tok = jnp.where(live, seg.q_start_by_row[order], 0)
    return (jnp.where(live, order, S),
            slot_flags(live, seg.has_init_by_row[order],
                       jnp.zeros_like(live)), tok, live)


def scatter_slots(o, tok, live, num_tokens: int):
    """The slots' outputs at their tokens of the flat batch, zero
    elsewhere."""
    out = jnp.zeros((num_tokens, ) + o.shape[1:], o.dtype)
    return out.at[jnp.where(live, tok, num_tokens)].set(o, mode="drop")


def token_slots(seg: SegmentInfo, multi):
    """Multi-token segments token by token: (state row, flags) a token
    of the flat batch."""
    S = seg.q_len_by_row.shape[0] - 1
    return (jnp.where(multi, seg.row, S),
            slot_flags(multi, seg.has_init, jnp.logical_not(seg.start)))


def multi_token_segments(seg: SegmentInfo, chunk: int):
    """(``multi`` ``[T]``: the token is of a segment longer than one;
    ``chunks`` ``[S + 1]``: a row's chunks of ``chunk`` tokens, each
    segment starting a chunk of its own; ``chunk_base`` ``[S + 1]``: the
    chunks of the segments that start earlier in the flat batch)."""
    S = seg.q_len_by_row.shape[0] - 1
    q_len = seg.q_len_by_row.at[S].set(0)
    row_multi = q_len > 1
    multi = seg.valid & row_multi[seg.row]
    chunks = jnp.where(row_multi, -(-q_len // chunk), 0)
    earlier = seg.q_start_by_row[None, :] < seg.q_start_by_row[:, None]
    chunk_base = jnp.sum(jnp.where(earlier, chunks[None, :], 0), axis=1)
    return multi, chunks, chunk_base


def chunk_windows(seg: SegmentInfo, chunk_base, chunks, num_chunks: int,
                  chunk: int):
    """The step's ``num_chunks`` chunks in order: (``used``; the state
    ``row``, S where unused; ``done``, the segment's tokens before the
    chunk; ``first``, its first token's flat index; ``count``, its
    tokens)."""
    S = seg.q_len_by_row.shape[0] - 1
    n = jnp.arange(num_chunks, dtype=jnp.int32)
    holds = ((chunk_base[None, :] <= n[:, None])
             & (n[:, None] < (chunk_base + chunks)[None, :]))
    used = holds.any(axis=1)
    row = jnp.where(used, jnp.argmax(holds, axis=1).astype(jnp.int32), S)
    done = (n - chunk_base[row]) * chunk         # of the segment, before n
    first = seg.q_start_by_row[row] + done
    count = jnp.where(used, jnp.minimum(seg.q_len_by_row[row] - done, chunk),
                      0)
    return used, row, done, first, count


def chunk_of_token(seg: SegmentInfo, multi, chunk_base, chunk: int):
    """The chunk that holds each token of a multi-token segment (0
    elsewhere); the token stands ``seg.off % chunk`` into it."""
    return jnp.where(multi, chunk_base[seg.row] + seg.off // chunk, 0)


def by_form(prefill_form: str, chunks, num_chunks: int, none, chunked,
            by_token, *carry):
    """Run the multi-token segments' form on ``carry``: nothing where
    the step has none, ``chunked`` where their chunks fit the static
    count, else ``by_token``; ``prefill_form`` ``"chunk"`` /
    ``"recurrent"`` pins it (tests)."""
    if prefill_form == "chunk":
        return chunked(*carry)
    if prefill_form == "recurrent":
        return by_token(*carry)
    total = chunks.sum()
    which = jnp.where(total == 0, 0, jnp.where(total <= num_chunks, 1, 2))
    return jax.lax.switch(which, (none, chunked, by_token), *carry)


def _bshape(flag: jax.Array, like: jax.Array) -> jax.Array:
    """Reshape a [T] flag for broadcasting against [T, ...]."""
    return flag.reshape(flag.shape + (1, ) * (like.ndim - 1))


def segmented_linear_scan(a: jax.Array, b: jax.Array,
                          reset: jax.Array) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t with h reset to 0 where ``reset``.

    a, b: [T, ...] (elementwise recurrence), reset: [T] bool.
    Returns h: [T, ...]. O(log T) depth via associative_scan.
    """
    # The flag leaf stays [T, 1, ...] — associative_scan only requires
    # equal length along the scanned axis, and a broadcastable flag
    # keeps the combine's bookkeeping O(T) instead of O(T * state).
    f = _bshape(reset, a)

    def combine(left, right):
        a1, b1, f1 = left
        a2, b2, f2 = right
        return (jnp.where(f2, a2, a1 * a2),
                jnp.where(f2, b2, a2 * b1 + b2),
                f1 | f2)

    _, h, _ = jax.lax.associative_scan(combine, (a, b, f), axis=0)
    return h


def causal_conv1d_ragged(
    x: jax.Array,  # [T, Di] pre-activation conv inputs
    weight: jax.Array,  # [K, Di] depthwise taps (tap 0 = oldest)
    bias: Optional[jax.Array],  # [Di] or None
    conv_state: jax.Array,  # [S+1, K-1, Di] carried inputs per row
    seg: SegmentInfo,
) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over the ragged batch with carried state:
    Mamba-1's (and Jamba's) on a layer's rows, and the oracle of
    ``ops/conv1d.causal_conv1d_rows``, which the Mamba-2 and KDA mixers
    run on the whole stack in place.

    Within a chunk, tap j reads x[t - j]; reads that cross the chunk
    start come from ``conv_state`` (the last K-1 inputs before the
    chunk), or zero when the request starts at position 0 — the same
    left-pad the reference's causal_conv1d kernel applies.
    Returns (y [T, Di], new_conv_state).
    """
    T, Di = x.shape
    K = weight.shape[0]
    xz = jnp.where(_bshape(seg.valid, x), x, 0.0)

    y = jnp.broadcast_to(weight[K - 1] * xz, xz.shape)  # tap at lag 0
    for j in range(1, K):
        # In-chunk read: x[t - j] when the token is >= j deep.
        shifted = jnp.concatenate([jnp.zeros((j, Di), x.dtype),
                                   xz[:T - j]], axis=0)
        in_chunk = seg.off >= j
        # Carried read: conv_state[row, K-1 + off - j].
        cs_idx = jnp.clip(K - 1 + seg.off - j, 0, K - 2)
        carried = conv_state[seg.row, cs_idx]
        carried = jnp.where(_bshape(seg.has_init, carried), carried, 0.0)
        tap = jnp.where(_bshape(in_chunk, shifted), shifted, carried)
        y = y + weight[K - 1 - j] * tap
    if bias is not None:
        y = y + bias

    # New carried state per row: the last K-1 inputs of the chunk,
    # reaching back into the old state when the chunk is shorter.
    q_len = seg.q_len_by_row  # [S+1]
    q_start = seg.q_start_by_row
    i = jnp.arange(K - 1)
    # Wanted input offset within the chunk: q_len - (K-1) + i.
    want = q_len[:, None] - (K - 1) + i[None, :]  # [S+1, K-1]
    from_chunk = want >= 0
    flat_idx = jnp.clip(q_start[:, None] + want, 0, T - 1)
    chunk_vals = xz[flat_idx]  # [S+1, K-1, Di]
    old_idx = jnp.clip(q_len[:, None] + i[None, :], 0, K - 2)
    old_vals = jnp.take_along_axis(
        conv_state, jnp.broadcast_to(
            old_idx[:, :, None], (conv_state.shape[0], K - 1, 1)), axis=1)
    # Fresh chunks (position 0) left-pad with zeros, not stale state.
    old_vals = jnp.where(seg.has_init_by_row[:, None, None], old_vals,
                         0.0)
    new_state = jnp.where(from_chunk[:, :, None], chunk_vals, old_vals)
    # Inactive rows keep their state verbatim.
    active = (q_len > 0)[:, None, None]
    new_state = jnp.where(active, new_state, conv_state)
    return y, new_state


def selective_scan_ragged(
    x: jax.Array,  # [T, Di] activated conv output (f32 recommended)
    dt: jax.Array,  # [T, Di] softplus'd step sizes
    A: jax.Array,  # [Di, N] negative reals
    B: jax.Array,  # [T, N]
    C: jax.Array,  # [T, N]
    D: jax.Array,  # [Di]
    ssm_state: jax.Array,  # [S+1, Di, N] carried state (f32)
    seg: SegmentInfo,
) -> tuple[jax.Array, jax.Array]:
    """Mamba-1 selective scan over the ragged batch.

    Discretization follows the published recurrence (and the
    reference's selective_scan_fwd semantics): a_t = exp(dt ⊙ A),
    b_t = dt ⊙ B_t ⊙ x_t; y_t = C_t · h_t + D ⊙ x_t.
    Returns (y [T, Di] f32, new_ssm_state [S+1, Di, N] f32).
    """
    x32 = x.astype(jnp.float32)
    # Zero dt at padding -> identity transition (dt_proj bias would
    # otherwise give padding steps a real decay).
    dt32 = jnp.where(_bshape(seg.valid, dt), dt.astype(jnp.float32), 0.0)
    a = jnp.exp(dt32[:, :, None] * A[None, :, :])  # [T, Di, N]
    b = (dt32 * x32)[:, :, None] * B[:, None, :].astype(jnp.float32)

    # Fold carried state into the first token of resumed chunks:
    # h_t0 = a_t0 * h_carry + b_t0.
    h_carry = ssm_state[seg.row]  # [T, Di, N]
    inject = _bshape(seg.start & seg.has_init, h_carry)
    b = b + jnp.where(inject, a * h_carry, 0.0)

    h = segmented_linear_scan(a, b, seg.start)
    y = jnp.einsum("tdn,tn->td", h,
                   C.astype(jnp.float32)) + D[None, :] * x32

    # Scatter each request's final state back to its row.
    dump = ssm_state.shape[0] - 1
    wrow = jnp.where(seg.end, seg.row, dump)
    new_state = ssm_state.at[wrow].set(h)
    # Repair the dump row to a fixed value so donation stays clean.
    new_state = new_state.at[dump].set(0.0)
    return y, new_state
