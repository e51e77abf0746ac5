"""Pallas ragged paged attention kernel for TPU.

TPU-native replacement for the reference's paged-attention CUDA kernels
(csrc/attention/paged_attention_v{1,2}.cu) and the torch_xla
ragged_paged_attention op its TPU backend calls
(vllm/v1/attention/backends/pallas.py:232). Re-designed for Pallas rather
than translated:

* Grid ``(seq, q_tile)``; each program runs the whole flash-attention
  loop over that sequence's KV pages as a dynamic-trip-count
  ``fori_loop`` (decode cost is O(kv_len), not O(max_model_len)), with
  online-softmax accumulators as loop carries.
* Per-sequence metadata (q_start, q_len, kv_len, batch row) is
  scalar-prefetched into SMEM; KV pages are gathered from HBM by manual
  async DMA using page ids read from the prefetched block table (the
  paging side of csrc/attention is pure DMA here).
* Mixed prefill/decode in one call: each sequence brings q_len query rows
  (1 for decode, up to max_q for a chunked-prefill step).
* Mosaic-friendly compute: the KV cache page layout is head-major
  [page, kv_head, page_size, head_dim] so each page DMAs into a
  contiguous [kv_head, block, head_dim] VMEM block; scores are 2-D
  matmuls per kv head (GQA queries of a group fold into rows), avoiding
  batched dots and sub-tile DMA slices entirely.

Layout contract with the model runner:

* Token arrays are the flat ragged batch; each sequence's q rows are
  contiguous, sequence runs are back-to-back in run order r = 0..num_seqs.
* ``q`` and the returned output have at least ``q_tile`` padding rows at
  the end: a sequence's final tile may spill past its q_len; spilled rows
  of sequence r are garbage but are rewritten by sequence r+1's own tile
  flush (the TPU grid executes sequentially in order), and the last
  sequence spills into the padding rows.
* ``seq_info[r] = (q_start, q_len, kv_len, batch_row)``; ``kv_len``
  includes tokens written this step. ``block_tables[batch_row]`` holds the
  page ids (rows are input-batch rows, indirected through batch_row).
* ``page_size`` must be a multiple of 8 (sublane tiling of the DMA
  destination slices).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# ---------------------------------------------------------------------------
# Mega-kernel partition descriptor
#
# One Pallas call consumes an arbitrary mixed prefill+decode batch: the
# grid is a flat program list and a host-built descriptor row tells each
# program what it is. Programs execute in order (the TPU grid is
# sequential), which is what lets KV-write programs land the step's new
# K/V pages before any attention program reads them.
#
#   desc[p] = (kind, a, b) int32
#     kind 0 (noop)     — padding row, program does nothing.
#     kind 1 (prefill)  — a = seq_info row r, b = tile start within the
#                         sequence's q run; runs the flash loop over a
#                         fixed ``bq`` q tile (independent of the token
#                         bucket). Writeback is EXACT (8-row chunks + a
#                         per-row tail), so tiles never spill into
#                         neighbouring rows and program order between
#                         attention programs does not matter.
#     kind 2 (decode)   — a = start index into ``decode_list``, b =
#                         number of active slots (<= sb); stacks sb
#                         single-token sequences as virtual heads so one
#                         MXU dot scores every sequence at once (the
#                         _decode_kernel trick), even when prefill tiles
#                         share the wave.
#     kind 3 (kv write) — a = row into ``kv_runs``; the in-place paged
#                         RMW of ops/pallas_kv_write.py, compiled into
#                         the kernel only for the fused write+attend
#                         variant (attention-only calls treat kind 3 as
#                         a noop).
#
# ``decode_list`` holds the seq_info row indices of every q_len == 1
# sequence; any single-token run (a decode step OR a one-token chunked-
# prefill tail — the attention math is identical) lands there, LONGEST
# kv_len FIRST (arrival order among equals): a group walks as many kv
# blocks as its longest sequence has, so sequences of like length share
# a group, and a slot moves no page past its own sequence's last block.
#
# The compile-lattice math: descriptor length and q padding are
# deterministic functions of the token bucket, and no kernel static
# depends on the batch composition — the forward graph count collapses
# from O(|T| x compositions) kernel variants to one kernel x |T| input
# shapes.
# ---------------------------------------------------------------------------

KIND_NOOP = 0
KIND_PREFILL = 1
KIND_DECODE = 2
KIND_KV_WRITE = 3

# Token arrays carry this many padding rows past the token bucket: a
# prefill tile's final 8-row read chunk may start at the last valid row
# (q reads are 8-row-aligned; writes are exact and never need it).
Q_TILE_PAD = 8


def padded_q_heads(num_q_heads: int, num_kv_heads: int) -> int:
    """Query heads as the kernels see them. The staged q and out tiles
    are ``[rows, QH, D]`` with the heads on the sublanes, and Mosaic
    takes a slice of them only in whole tiles of 8 ("Slice shape along
    dimension 1 must be aligned to tiling (8), but is 28"). A head count
    that is no multiple of 8 (28 heads in groups of 7 on 4 K/V heads)
    is padded group by group to the next one that is (groups of 8): the
    launchers add zero query rows and drop their output rows
    (``_pad_query_groups``), no K/V byte more is read, and an eighth of
    the score rows is spent on nothing. Groups of 1, 4 and 16 on head
    counts of 16, 32 and 128 come back as they are."""
    group = num_q_heads // num_kv_heads
    while (group * num_kv_heads) % 8:
        group += 1
    return group * num_kv_heads


def _pad_query_groups(x: jax.Array, num_kv_heads: int,
                      axis: int = 1) -> jax.Array:
    """``x`` with each K/V head's group of query heads, along ``axis``,
    zero-padded to ``padded_q_heads``' group."""
    heads = x.shape[axis]
    padded = padded_q_heads(heads, num_kv_heads)
    if padded == heads:
        return x
    shape = x.shape
    x = x.reshape(*shape[:axis], num_kv_heads, heads // num_kv_heads,
                  *shape[axis + 1:])
    pad = [(0, 0)] * x.ndim
    pad[axis + 1] = (0, (padded - heads) // num_kv_heads)
    return jnp.pad(x, pad).reshape(*shape[:axis], padded,
                                   *shape[axis + 1:])


def _unpad_query_groups(x: jax.Array, num_q_heads: int,
                        num_kv_heads: int) -> jax.Array:
    """The ``num_q_heads`` real heads of a kernel output ``[T, padded,
    D]``."""
    T, padded, D = x.shape
    if padded == num_q_heads:
        return x
    x = x.reshape(T, num_kv_heads, padded // num_kv_heads, D)
    return x[:, :, :num_q_heads // num_kv_heads].reshape(T, num_q_heads, D)


def prefill_tile_size(num_q_heads: int, head_dim: int) -> int:
    """Static prefill q-tile rows. Fixed (never a function of the token
    bucket) so the kernel has no per-composition statics; 32 rows fold to
    32*group score rows per kv head — MXU-filling for GQA groups >= 4.
    Shrinks (staying a multiple of 8, the IO chunk) for wide-head models
    so per-program staging stays inside the VMEM budget: 128 query heads
    of 128 lanes (a group of 16 on each of 8 K/V heads) take 16 rows, so
    a tile is 256 score rows a K/V head and its float32 accumulators
    (8 x 256 x 128) a megabyte."""
    bq = 32
    while bq > 8 and bq * num_q_heads * head_dim * 32 > 12 * 1024**2:
        bq //= 2
    return bq


def decode_group_size(num_q_heads: int, num_kv_heads: int) -> int:
    """Static decode-group width (sequences stacked as virtual heads per
    program). Independent of the runtime batch size — inactive slots are
    masked and move no page — and sized against the worst-case
    128-position kv block so the same sb is valid for every caller (the
    cascade suffix call sees a shorter block table than the main call).
    128 query heads on 8 K/V heads give 2: 256 score rows against
    2 x 8 x 128 key columns; 28 on 4 are sized as the 32 they are padded
    to (``padded_q_heads``): 8. WHICH sequences share a group is the
    descriptor's choice (``build_partition_descriptor``: by length); a
    group's trips are its longest sequence's blocks and each trip's
    product runs over all ``sb x KVH x blk`` columns, so the width is
    also what a group of unlike lengths wastes."""
    num_q_heads = padded_q_heads(num_q_heads, num_kv_heads)
    sb = max(1, min(8, 128 // max(1, num_q_heads // 4)))
    while sb > 1 and (sb * num_q_heads) * (sb * num_kv_heads * 128) * 8 \
            > 8 * 1024**2:
        sb //= 2
    return sb


def num_partition_programs(t_bucket: int, max_num_reqs: int, *, bq: int,
                           sb: int, num_kv_writes: int = 0) -> int:
    """Descriptor length bound as a deterministic function of the token
    bucket: worst-case prefill tiles (every sequence pays one partial
    tile) + decode groups + kv-write rows. Adds no lattice dimension."""
    return (num_kv_writes + -(-t_bucket // bq) + max_num_reqs +
            -(-max_num_reqs // sb))


def kv_block_pages(page_size: int, pages_per_req: int) -> int:
    """Pages a kv block of a kernel's page loop: as many as 128
    positions hold, at least one, at most the table's width, and a
    divisor of it."""
    ppb = max(1, min(128 // page_size, pages_per_req))
    while pages_per_req % ppb:
        ppb -= 1
    return ppb


def decode_kv_tokens_walked(kv_lens: np.ndarray, blk: int) -> int:
    """K/V token slots that the decode groups' page DMAs move in one
    full layer for single-token sequences of these context lengths:
    every sequence's whole kv blocks of ``blk`` positions up to its own
    end and none past it, whoever shares its group. Over
    ``kv_lens.sum()`` it is the last block's rounding alone."""
    return int((-(-kv_lens // blk)).sum()) * blk


def build_partition_descriptor(
    seq_info: np.ndarray,  # [R, 4] int32 host copy
    num_seqs: int,
    *,
    bq: int,
    sb: int,
    num_programs: int,
    num_kv_writes: int = 0,
    decode_rows: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side partition of a step into mega-kernel programs.

    Returns ``(desc [num_programs, 3], decode_list [R])``. Pass
    ``decode_rows`` (row indices into seq_info) to skip the q_len scan —
    the runner's pure-decode fast path feeds its row vector directly.

    The single-token rows are cut into groups of ``sb`` LONGEST kv_len
    FIRST, arrival order among equals (one stable argsort, on both ways
    in): a group takes as many trips as its longest sequence has kv
    blocks, so like lengths share a group, and the one group that may be
    short of ``sb`` holds the shortest. ``decode_list`` carries the
    order and outputs land through ``q_start``, so nothing downstream
    sees it; under a window the same order serves the window layers
    (``min(kv_len, window)`` is monotone in kv_len)."""
    R = seq_info.shape[0]
    desc = np.zeros((num_programs, 3), np.int32)
    dl = np.zeros((R, ), np.int32)
    p = num_kv_writes
    if num_kv_writes:
        desc[:p, 0] = KIND_KV_WRITE
        desc[:p, 1] = np.arange(num_kv_writes, dtype=np.int32)
    if decode_rows is None:
        q_lens = seq_info[:num_seqs, 1]
        decode_rows = np.nonzero(q_lens == 1)[0]
        for r in np.nonzero(q_lens > 1)[0]:
            nt = -(-int(q_lens[r]) // bq)
            desc[p:p + nt, 0] = KIND_PREFILL
            desc[p:p + nt, 1] = r
            desc[p:p + nt, 2] = np.arange(nt, dtype=np.int32) * bq
            p += nt
    n_dec = len(decode_rows)
    dl[:n_dec] = decode_rows[np.argsort(-seq_info[decode_rows, 2],
                                        kind="stable")]
    starts = np.arange(0, n_dec, sb, dtype=np.int32)
    ng = len(starts)
    assert p + ng <= num_programs, "partition descriptor overflow"
    desc[p:p + ng, 0] = KIND_DECODE
    desc[p:p + ng, 1] = starts
    desc[p:p + ng, 2] = np.minimum(sb, n_dec - starts)
    return desc, dl


def _kernel(
    # scalar prefetch
    seq_info_ref,  # [R, 4] int32: q_start, q_len, kv_len, batch_row
    num_seqs_ref,  # [1] int32
    layer_ref,  # [1] int32
    block_tables_ref,  # [max_reqs, pages_per_req] int32
    # tensor inputs (HBM)
    q_hbm,  # [T_pad, QH, D]
    k_hbm,  # [L, num_pages, KVH, PS, D] (full stacked cache)
    v_hbm,
    # outputs (HBM): out_hbm, then state_hbm when emit_state
    *refs,
    sm_scale: float,
    bq: int,
    ppb: int,
    page_size: int,
    group: int,
    emit_state: bool,
):
    if emit_state:
        (out_hbm, state_hbm, q_vmem, k_vmem, v_vmem, out_stage,
         state_stage, q_sem, kv_sems, out_sem, state_sem) = refs
    else:
        (out_hbm, q_vmem, k_vmem, v_vmem, out_stage, q_sem, kv_sems,
         out_sem) = refs
        state_hbm = state_stage = state_sem = None
    r = pl.program_id(0)
    qt = pl.program_id(1)

    q_start = seq_info_ref[r, 0]
    q_len = seq_info_ref[r, 1]
    kv_len = seq_info_ref[r, 2]
    row = seq_info_ref[r, 3]
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    num_q_heads = q_vmem.shape[1]
    num_kv_heads = k_vmem.shape[1]  # [slot, KVH, blk, D]
    head_dim = q_vmem.shape[2]

    blk = ppb * page_size
    tile_start = qt * bq
    # Absolute position of the last query row in this tile; kv blocks past
    # it are causally invisible and never fetched.
    q_pos_max = kv_len - q_len + jnp.minimum(tile_start + bq, q_len) - 1
    active = jnp.logical_and(
        r < num_seqs,
        jnp.logical_and(tile_start < q_len, kv_len > 0))

    @pl.when(active)
    def _run():
        # Whole q tile in one leading-dim DMA (token rows are the major
        # axis; head/lane dims stay intact — Mosaic constrains sub-tile
        # slicing of the minor two dims).
        q_dma = pltpu.make_async_copy(
            q_hbm.at[pl.ds(q_start + tile_start, bq)], q_vmem, q_sem)
        q_dma.start()
        num_blocks = q_pos_max // blk + 1

        # Double-buffered KV pipeline: block b+1's pages stream from HBM
        # while block b computes, so the MXU never idles on a fetch
        # (the reference's paged_attention_v2.cu overlaps its gathers
        # the same way via cp.async).
        def fetch(b, slot):
            for i in range(ppb):
                page_id = block_tables_ref[row, b * ppb + i]
                pltpu.make_async_copy(
                    k_hbm.at[layer, page_id],
                    k_vmem.at[slot, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 0, i]).start()
                pltpu.make_async_copy(
                    v_hbm.at[layer, page_id],
                    v_vmem.at[slot, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 1, i]).start()

        fetch(0, 0)  # warm-up overlaps the q DMA in flight
        q_dma.wait()

        q_tile = q_vmem[...].astype(jnp.float32) * sm_scale  # [BQ, QH, D]
        if bq == 1:
            # Decode: rows are heads; group slices are leading-dim slices.
            q_flat = q_tile.reshape(num_q_heads, head_dim)
            q_heads = [
                q_flat[h * group:(h + 1) * group]
                for h in range(num_kv_heads)
            ]
        else:
            q_heads = [
                q_tile[:, h * group:(h + 1) * group, :].reshape(
                    bq * group, head_dim) for h in range(num_kv_heads)
            ]
        rows = bq * group

        row_pos = (kv_len - q_len + tile_start +
                   jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) //
                   group)
        col_base = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
        row_valid = (jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) //
                     group + tile_start) < q_len

        def body(b, carry):
            ms, ls, accs = carry
            kv_start = b * blk
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < num_blocks)
            def _prefetch():
                fetch(b + 1, jax.lax.rem(b + 1, 2))

            for i in range(ppb):
                pltpu.make_async_copy(
                    k_hbm.at[0, 0],
                    k_vmem.at[slot, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 0, i]).wait()
                pltpu.make_async_copy(
                    v_hbm.at[0, 0],
                    v_vmem.at[slot, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 1, i]).wait()
            k_blk = k_vmem[slot]  # [KVH, BLK, D]
            v_blk = v_vmem[slot]

            kv_pos = kv_start + col_base
            mask = jnp.logical_and(kv_pos <= row_pos, row_valid)

            new_ms, new_ls, new_accs = [], [], []
            for h in range(num_kv_heads):
                k_h = k_blk[h]  # [BLK, D]
                v_h = v_blk[h]
                s = jax.lax.dot_general(
                    q_heads[h], k_h.astype(jnp.float32),
                    dimension_numbers=(((1, ), (1, )), ((), ())),
                    preferred_element_type=jnp.float32)  # [rows, BLK]
                s = jnp.where(mask, s, _MASK_VALUE)
                m_prev, l_prev, acc_prev = ms[h], ls[h], accs[h]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v_h.dtype), v_h,
                    dimension_numbers=(((1, ), (0, )), ((), ())),
                    preferred_element_type=jnp.float32)  # [rows, D]
                acc_new = acc_prev * alpha + pv
                new_ms.append(m_new)
                new_ls.append(l_new)
                new_accs.append(acc_new)
            return tuple(new_ms), tuple(new_ls), tuple(new_accs)

        init = (
            tuple(
                jnp.full((rows, 1), _MASK_VALUE, jnp.float32)
                for _ in range(num_kv_heads)),
            tuple(
                jnp.zeros((rows, 1), jnp.float32)
                for _ in range(num_kv_heads)),
            tuple(
                jnp.zeros((rows, head_dim), jnp.float32)
                for _ in range(num_kv_heads)),
        )
        ms, ls, accs = jax.lax.fori_loop(0, num_blocks, body, init)

        half = head_dim // 2
        for h in range(num_kv_heads):
            o_h = accs[h] / jnp.maximum(ls[h], 1e-20)  # [rows, D]
            if bq == 1:
                out_stage[0, h * group:(h + 1) * group, :] = (
                    o_h.astype(out_stage.dtype))
            else:
                out_stage[:, h * group:(h + 1) * group, :] = (
                    o_h.reshape(bq, group, head_dim).astype(
                        out_stage.dtype))
            if emit_state:
                # Online-softmax partial state for exact merging with
                # another KV range (cascade): m broadcast over the low
                # lanes, l over the high — lane-sliced out by the
                # caller. Full-D staging keeps the DMA tile-aligned.
                st = jnp.concatenate([
                    jnp.broadcast_to(ms[h], (rows, half)),
                    jnp.broadcast_to(ls[h], (rows, head_dim - half)),
                ], axis=-1)
                if bq == 1:
                    state_stage[0, h * group:(h + 1) * group, :] = st
                else:
                    state_stage[:, h * group:(h + 1) * group, :] = (
                        st.reshape(bq, group, head_dim))
        out_dma = pltpu.make_async_copy(
            out_stage, out_hbm.at[pl.ds(q_start + tile_start, bq)],
            out_sem)
        out_dma.start()
        if emit_state:
            st_dma = pltpu.make_async_copy(
                state_stage,
                state_hbm.at[pl.ds(q_start + tile_start, bq)], state_sem)
            st_dma.start()
            st_dma.wait()
        out_dma.wait()


def _decode_kernel(
    # scalar prefetch
    seq_info_ref,  # [R, 4] int32: q_start, q_len, kv_len, batch_row
    num_seqs_ref,  # [1] int32
    layer_ref,  # [1] int32
    block_tables_ref,  # [max_reqs, pages_per_req] int32
    # tensor inputs (HBM)
    q_hbm,  # [T_pad, QH, D]
    k_hbm,  # [L, num_pages, KVH, PS, D]
    v_hbm,
    out_hbm,
    # scratch
    q_vmem,  # [SB, QH, D]
    k_vmem,  # [2, SB, KVH, blk, D] double-buffered
    v_vmem,
    out_stage,  # [SB, QH, D]
    q_sem,
    kv_sems,  # [2, 2, SB, ppb]
    out_sem,
    *,
    sm_scale: float,
    sb: int,
    ppb: int,
    page_size: int,
    group: int,
):
    """Decode-specialized attention: SB sequences per grid program.

    Decode starves the MXU when each sequence's score dot is only
    ``group`` rows (VERDICT r4: 4–8 rows on a 128x128 array). Here the
    SB sequences x KVH kv-heads of a program are stacked as SB*KVH
    "virtual heads": ONE [SB*QH, D] x [D, SB*KVH*blk] dot scores every
    sequence at once, with a block-diagonal mask (virtual head of query
    row == virtual head of kv column) recovering per-sequence/per-head
    attention. Cross-terms cost flops the DMA-bound loop has to spare;
    rows go from `group` to SB*QH. KV pages double-buffer across the
    block loop exactly like the general kernel.

    Layout contract (decode steps only): every scheduled sequence has
    q_len == 1; its query row is read through seq_info's q_start, so
    compacted/scattered layouts (token parallelism's per-rank lists)
    work unchanged.
    """
    p = pl.program_id(0)
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    QH = q_vmem.shape[1]
    KVH = k_vmem.shape[2]
    D = q_vmem.shape[2]
    blk = ppb * page_size
    base = p * sb
    ROWS = sb * QH
    C = sb * KVH * blk

    # Per-sequence scalars (static unroll over the SB slots). Inactive
    # slots read row 0's metadata but mask everything via kv_len = 0.
    idx = [jnp.minimum(base + i, seq_info_ref.shape[0] - 1)
           for i in range(sb)]
    kv_lens = [
        jnp.where(base + i < num_seqs, seq_info_ref[idx[i], 2], 0)
        for i in range(sb)
    ]
    rows_ = [seq_info_ref[idx[i], 3] for i in range(sb)]
    q_starts = [seq_info_ref[idx[i], 0] for i in range(sb)]

    max_kv = kv_lens[0]
    for i in range(1, sb):
        max_kv = jnp.maximum(max_kv, kv_lens[i])
    num_blocks = jax.lax.div(max_kv - 1, blk) + 1  # 0 when all inactive

    @pl.when(base < num_seqs)
    def _run():
        for i in range(sb):
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(q_starts[i], 1)],
                q_vmem.at[pl.ds(i, 1)], q_sem.at[i]).start()

        def fetch(b, slot):
            for i in range(sb):
                # Clamp past-the-end blocks of shorter sequences to
                # their last valid block: the DMA stays in-bounds and
                # the mask discards the stale columns.
                bi = jnp.clip(b, 0,
                              jnp.maximum(
                                  jax.lax.div(kv_lens[i] - 1, blk), 0))
                for j in range(ppb):
                    page_id = block_tables_ref[rows_[i], bi * ppb + j]
                    pltpu.make_async_copy(
                        k_hbm.at[layer, page_id],
                        k_vmem.at[slot, i, :,
                                  pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 0, i, j]).start()
                    pltpu.make_async_copy(
                        v_hbm.at[layer, page_id],
                        v_vmem.at[slot, i, :,
                                  pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 1, i, j]).start()

        fetch(0, 0)
        for i in range(sb):
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(0, 1)], q_vmem.at[pl.ds(i, 1)],
                q_sem.at[i]).wait()
        q_all = (q_vmem[...].astype(jnp.float32) * sm_scale).reshape(
            ROWS, D)

        # Block-diagonal structure: query row r belongs to virtual head
        # r // group (rows are seq-major then head-major, QH = KVH *
        # group); kv column c belongs to virtual head c // blk.
        vh_r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 0) // group
        vh_c = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) // blk
        diag = vh_r == vh_c
        col_off = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) % blk
        kvlen_rows = jnp.concatenate(
            [jnp.full((QH, ), kv_lens[i], jnp.int32) for i in range(sb)])

        def body(b, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(b, 2)

            @pl.when(b + 1 < num_blocks)
            def _prefetch():
                fetch(b + 1, jax.lax.rem(b + 1, 2))

            for i in range(sb):
                for j in range(ppb):
                    pltpu.make_async_copy(
                        k_hbm.at[0, 0],
                        k_vmem.at[slot, i, :,
                                  pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 0, i, j]).wait()
                    pltpu.make_async_copy(
                        v_hbm.at[0, 0],
                        v_vmem.at[slot, i, :,
                                  pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 1, i, j]).wait()
            k_all = k_vmem[slot].reshape(C, D)  # [SB*KVH*blk, D]
            v_all = v_vmem[slot].reshape(C, D)

            s = jax.lax.dot_general(
                q_all, k_all.astype(jnp.float32),
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32)  # [ROWS, C]
            mask = jnp.logical_and(
                diag, b * blk + col_off < kvlen_rows[:, None])
            s = jnp.where(mask, s, _MASK_VALUE)

            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            # Zero the off-diagonal terms so the PV dot sums only each
            # row's own block (exp(_MASK_VALUE - m) underflows to 0
            # already; the where guards m == _MASK_VALUE rows).
            pr = jnp.where(mask, pr, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + pr.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(v_all.dtype), v_all,
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)  # [ROWS, D]
            return m_new, l_new, acc_prev * alpha + pv

        init = (
            jnp.full((ROWS, 1), _MASK_VALUE, jnp.float32),
            jnp.zeros((ROWS, 1), jnp.float32),
            jnp.zeros((ROWS, D), jnp.float32),
        )
        _m, l_fin, acc = jax.lax.fori_loop(0, num_blocks, body, init)
        out = acc / jnp.maximum(l_fin, 1e-20)
        out_stage[...] = out.reshape(sb, QH, D).astype(out_stage.dtype)
        # Per-sequence writeback through q_start; inactive slots MUST
        # NOT write (their q_start aliases row 0 — a real token).
        for i in range(sb):
            @pl.when(base + i < num_seqs)
            def _wb(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(q_starts[i], 1)],
                    out_sem.at[i]).start()
        for i in range(sb):
            @pl.when(base + i < num_seqs)
            def _wb_wait(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(0, 1)], out_sem.at[i]).wait()


def _decode_call(q, k_pages, v_pages, seq_info, num_seqs, block_tables,
                 layer, *, sm_scale, interpret):
    """Launch the SB-batched decode kernel (max_q == 1, no state)."""
    T_pad, num_q_heads, head_dim = q.shape
    _, _, num_kv_heads, page_size, _ = k_pages.shape
    group = num_q_heads // num_kv_heads
    R = seq_info.shape[0]
    pages_per_req = block_tables.shape[1]
    ppb = max(1, min(128 // page_size, pages_per_req))
    while pages_per_req % ppb:
        ppb -= 1
    blk = ppb * page_size

    sb = max(1, min(8, R, 128 // max(1, num_q_heads // 4)))
    # Score tile [sb*QH, sb*KVH*blk] f32 (+ exp copy) dominates VMEM.
    while sb > 1 and (sb * num_q_heads) * (sb * num_kv_heads * blk) * 8 \
            > 8 * 1024**2:
        sb //= 2
    assert T_pad >= R, "decode q must cover one row per sequence"
    # The last program reads/writes rows [base, base+sb); keep that
    # inside the q padding when R is not a multiple of sb.
    while sb > 1 and pl.cdiv(R, sb) * sb > T_pad:
        sb //= 2

    grid = (pl.cdiv(R, sb), )
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, sb=sb, ppb=ppb,
        page_size=page_size, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # q
            pl.BlockSpec(memory_space=pl.ANY),  # k_pages
            pl.BlockSpec(memory_space=pl.ANY),  # v_pages
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((sb, num_q_heads, head_dim), q.dtype),
            pltpu.VMEM((2, sb, num_kv_heads, blk, head_dim),
                       k_pages.dtype),
            pltpu.VMEM((2, sb, num_kv_heads, blk, head_dim),
                       v_pages.dtype),
            pltpu.VMEM((sb, num_q_heads, head_dim), q.dtype),
            pltpu.SemaphoreType.DMA((sb, )),
            pltpu.SemaphoreType.DMA((2, 2, sb, ppb)),
            pltpu.SemaphoreType.DMA((sb, )),
        ],
    )
    (out, ) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        interpret=interpret,
    )(seq_info, num_seqs, layer, block_tables, q, k_pages, v_pages)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "max_q", "interpret", "emit_state"))
def ragged_paged_attention_pallas(
    q: jax.Array,  # [T_pad, QH, D]; T_pad >= T + q_tile padding
    k_pages: jax.Array,  # [L, num_pages, KVH, PS, D] full stacked cache
    v_pages: jax.Array,
    seq_info: jax.Array,  # [R, 4] int32 (q_start, q_len, kv_len, row)
    num_seqs: jax.Array,  # [1] int32
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    layer: jax.Array | None = None,  # [1] int32
    *,
    sm_scale: float,
    max_q: int,
    interpret: bool | None = None,
    emit_state: bool = False,
):
    """Unified prefill/decode attention over the paged KV cache.

    ``max_q`` is the static per-sequence query bucket (1 for pure decode).
    The cache keeps its stacked layer dim; ``layer`` selects the slice to
    read (pages are DMA'd as [layer, page] — no layer copy materializes).
    Returns [T_pad, QH, D]; rows past each sequence's q_len are garbage.

    ``emit_state=True`` additionally returns the online-softmax partial
    state as an f32 [T_pad, QH, D] array with the row max broadcast over
    lanes [0, D/2) and the exp-sum over [D/2, D) — what cascade needs to
    merge this call's KV range with a shared-prefix phase exactly
    (reference: csrc/attention/merge_attn_states.cu exports the same
    (max, sumexp) pair).
    """
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    if k_pages.ndim == 4:
        # Single-layer convenience form (tests).
        k_pages = k_pages[None]
        v_pages = v_pages[None]
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    true_q_heads = q.shape[1]
    _, num_pages, num_kv_heads, page_size, _ = k_pages.shape
    assert true_q_heads % num_kv_heads == 0
    q = _pad_query_groups(q, num_kv_heads)
    T_pad, num_q_heads, head_dim = q.shape
    group = num_q_heads // num_kv_heads
    R = seq_info.shape[0]
    pages_per_req = block_tables.shape[1]

    def unpad(x):
        return _unpad_query_groups(x, true_q_heads, num_kv_heads)

    if max_q == 1 and not emit_state:
        # Pure decode: the SB-batched kernel fills the MXU (see
        # _decode_kernel). Cascade's emit_state decode stays on the
        # general kernel (it exports per-row softmax state).
        return unpad(_decode_call(
            q, k_pages, v_pages, seq_info, num_seqs, block_tables, layer,
            sm_scale=sm_scale, interpret=interpret))

    bq = min(max_q, 128)
    # Keep the per-program footprint (q/out staging, f32 accumulators and
    # their loop-carry double buffers, per-head score tiles) inside the
    # ~16MB VMEM budget: shrink the q tile for wide-head models.
    while bq > 8 and bq * num_q_heads * head_dim * 32 > 12 * 1024**2:
        bq //= 2
    num_q_tiles = pl.cdiv(max_q, bq)
    assert T_pad >= bq, "q must be padded to at least one tile"
    ppb = kv_block_pages(page_size, pages_per_req)
    blk = ppb * page_size

    grid = (R, num_q_tiles)
    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, bq=bq, ppb=ppb, page_size=page_size,
        group=group, emit_state=emit_state)

    scratch = [
        pltpu.VMEM((bq, num_q_heads, head_dim), q.dtype),
        pltpu.VMEM((2, num_kv_heads, blk, head_dim), k_pages.dtype),
        pltpu.VMEM((2, num_kv_heads, blk, head_dim), v_pages.dtype),
        pltpu.VMEM((bq, num_q_heads, head_dim), q.dtype),
    ]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if emit_state:
        scratch.append(
            pltpu.VMEM((bq, num_q_heads, head_dim), jnp.float32))
        out_shape.append(
            jax.ShapeDtypeStruct(q.shape, jnp.float32))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    scratch += [
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((2, 2, ppb)),  # [slot, k/v, page]
        pltpu.SemaphoreType.DMA(()),
    ]
    if emit_state:
        scratch.append(pltpu.SemaphoreType.DMA(()))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # q
            pl.BlockSpec(memory_space=pl.ANY),  # k_pages
            pl.BlockSpec(memory_space=pl.ANY),  # v_pages
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    result = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(seq_info, num_seqs, layer, block_tables, q, k_pages, v_pages)
    if emit_state:
        return tuple(unpad(x) for x in result)
    return unpad(result[0])


# ---------------------------------------------------------------------------
# Mixed-batch attention mega-kernel
# ---------------------------------------------------------------------------


def _mega_kernel(
    # scalar prefetch
    desc_ref,  # [P, 3] int32: (kind, a, b) — see module header
    seq_info_ref,  # [R, 4] int32: q_start, q_len, kv_len, batch_row
    dl_ref,  # [R] int32: seq_info rows of q_len == 1 sequences
    kv_runs_ref,  # [G, 4] int32 page-write runs (fuse_write only)
    layer_ref,  # [1] int32
    block_tables_ref,  # [max_reqs, pages_per_req] int32
    *refs,
    sm_scale: float,
    bq: int,
    sb: int,
    ppb: int,
    page_size: int,
    group: int,
    emit_state: bool,
    fuse_write: bool,
    window: int,
    logit_cap: float,
    has_alibi: bool,
    has_sinks: bool,
):
    """One program list, three program types (see the partition
    descriptor contract in the module docstring). Prefill tiles run the
    general flash loop at a FIXED bq; decode groups keep the SB
    virtual-head batching even when prefill tiles share the wave; kv
    writes (fused variant) land first so attention reads this step's
    pages.

    A decode group takes as many trips as its longest sequence has kv
    blocks in sight, and the host puts like lengths together
    (``build_partition_descriptor``). On each trip a slot starts, and
    waits for, its page DMAs only while its OWN sequence has a block
    left; an inactive slot moves nothing. The columns of a slot that
    sat a trip out are masked like any other invisible position (their
    probabilities are exactly 0 and the carries come through
    bit-for-bit: ``alpha = 1``, ``pv = 0``), which asks one thing of
    the buffers: what a skipped slot's V holds must be finite, so the
    V scratch is zeroed by the call's first program and only pages of
    the cache are written to it after.

    Per-model attention features ride along so windowed / soft-capped /
    ALiBi / sink models reach this kernel instead of the XLA fallback:
    ``window`` (sliding-window bound) and ``logit_cap`` (tanh
    soft-capping) are per-layer STATICS like the model's scan-segment
    plan; ALiBi slopes and sink logits arrive in the tiny ``feat``
    input ([2, QH] f32: row 0 slopes, row 1 sinks) so learned sinks and
    TP-sharded head slices stay dynamic. With ``window > 0`` the page
    loop is TRIMMED as well as masked: a prefill tile whose queries sit
    at ``[p0, p0 + bq)`` starts at the kv block that holds
    ``p0 - window + 1`` and a decode group's sequence at the block that
    holds ``kv_len - window``, so a window layer reads (and the table
    needs live page ids for) at most ``window + bq`` positions rounded
    out to whole kv blocks, whatever the context. Slots in front of
    that block are never read, which is what lets the window group of
    core/kv_cache_manager.py hand those pages back mid-request; the few
    freed slots inside the first block read the placeholder page under
    the mask. ``window == 0`` traces exactly the untrimmed program."""
    if fuse_write:
        (q_hbm, k_new, v_new, _k_in, _v_in, feat_ref,
         out_hbm, k_cache, v_cache,
         q_vmem, k_vmem, v_vmem, out_stage,
         k_page, v_page, k_win, v_win,
         q_sems, kv_sems, out_sems, w_sems) = refs
        state_hbm = state_stage = state_sems = None
    elif emit_state:
        (q_hbm, k_cache, v_cache, feat_ref, out_hbm, state_hbm,
         q_vmem, k_vmem, v_vmem, out_stage, state_stage,
         q_sems, kv_sems, out_sems, state_sems) = refs
    else:
        (q_hbm, k_cache, v_cache, feat_ref, out_hbm,
         q_vmem, k_vmem, v_vmem, out_stage,
         q_sems, kv_sems, out_sems) = refs
        state_hbm = state_stage = state_sems = None

    p = pl.program_id(0)
    kind = desc_ref[p, 0]
    a = desc_ref[p, 1]
    b = desc_ref[p, 2]
    layer = layer_ref[0]
    QH = q_vmem.shape[1]
    KVH = k_vmem.shape[2]
    D = q_vmem.shape[2]
    blk = ppb * page_size
    half = D // 2
    nck = bq // 8  # 8-row IO chunks per prefill tile

    @pl.when(p == 0)
    def _clear():
        # A decode slot that moves no page on a trip (its sequence has
        # ended, or the slot is inactive) leaves its V buffer as it was,
        # and the group's ``pr @ v_all`` multiplies those bits by a zero
        # probability: they have to be finite. A scratch buffer starts
        # as whatever VMEM held; everything written to it later is a
        # page of the cache. k_vmem needs none of this: the stale
        # columns of a score are replaced by ``where`` before the max,
        # never multiplied. Once, at program 0, serves every later
        # program only while the grid runs in order on one core
        # (_mega_call sets no dimension_semantics).
        v_vmem[...] = jnp.zeros(v_vmem.shape, v_vmem.dtype)

    if fuse_write:

        @pl.when(kind == KIND_KV_WRITE)
        def _kv_write():
            # The page-RMW body shared with ops/pallas_kv_write.py
            # (page-aligned 2*PS window + one-hot shift matmul). Runs
            # precede every attention program in the descriptor, and the
            # grid executes in order, so attention below reads the
            # freshly written pages — through the aliased OUTPUT refs.
            from vllm_distributed_tpu.ops.pallas_kv_write import page_rmw
            run_len = kv_runs_ref[a, 3]

            @pl.when(run_len > 0)
            def _run():
                page_rmw(kv_runs_ref[a, 0], kv_runs_ref[a, 1],
                         kv_runs_ref[a, 2], run_len, layer, k_new,
                         v_new, k_cache, v_cache, k_page, v_page, k_win,
                         v_win, w_sems, page_size=page_size)

    @pl.when(kind == KIND_PREFILL)
    def _prefill():
        r = a
        tile_start = b
        q_start = seq_info_ref[r, 0]
        q_len = seq_info_ref[r, 1]
        kv_len = seq_info_ref[r, 2]
        row = seq_info_ref[r, 3]
        n_valid = jnp.minimum(q_len - tile_start, bq)
        q_pos_max = kv_len - q_len + tile_start + n_valid - 1
        num_blocks = q_pos_max // blk + 1

        # q tile read in 8-row chunks: chunks starting past q_len are
        # skipped (their stale VMEM rows are masked out of the scores),
        # so reads never pass q_start + q_len + 7 — inside the token
        # array's Q_TILE_PAD padding even for the layout's last tile.
        for c in range(nck):
            @pl.when(tile_start + 8 * c < q_len)
            def _rd(c=c):
                pltpu.make_async_copy(
                    q_hbm.at[pl.ds(q_start + tile_start + 8 * c, 8)],
                    q_vmem.at[pl.ds(8 * c, 8)], q_sems.at[c]).start()

        def fetch(bi, slot):
            for i in range(ppb):
                page_id = block_tables_ref[row, bi * ppb + i]
                pltpu.make_async_copy(
                    k_cache.at[layer, page_id],
                    k_vmem.at[slot, 0, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 0, 0, i]).start()
                pltpu.make_async_copy(
                    v_cache.at[layer, page_id],
                    v_vmem.at[slot, 0, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 1, 0, i]).start()

        if window > 0:
            # The first kv block the tile's FIRST query can see; later
            # rows see no further back.
            first_block = jnp.maximum(
                kv_len - q_len + tile_start - window + 1, 0) // blk
            fetch(first_block, jax.lax.rem(first_block, 2))
        else:
            first_block = 0
            fetch(0, 0)  # overlaps the q chunk DMAs in flight
        for c in range(nck):
            @pl.when(tile_start + 8 * c < q_len)
            def _rdw(c=c):
                pltpu.make_async_copy(
                    q_hbm.at[pl.ds(0, 8)], q_vmem.at[pl.ds(8 * c, 8)],
                    q_sems.at[c]).wait()

        q_tile = q_vmem[...][:bq].astype(jnp.float32) * sm_scale
        q_heads = [
            q_tile[:, h * group:(h + 1) * group, :].reshape(
                bq * group, D) for h in range(KVH)
        ]
        rows = bq * group
        # The scores are formed TRANSPOSED, ``[BLK, rows]`` with the
        # queries on the lanes: the running max and sum are ``[1, rows]``
        # (two vregs at 256 rows, where ``[rows, 1]`` takes 32 each with
        # one lane in 128 used), their reductions run down the sublanes
        # as elementwise operations instead of one cross-lane reduce a
        # vreg, and the accumulator is ``[D, rows]``, turned once when
        # the tile is written. With the keys on the lanes the page loop
        # was bound by the vector load and store slots, filled by spills
        # of exactly these carries (PERF.md, Findings PR 44).
        q_row = jax.lax.broadcasted_iota(jnp.int32, (blk, rows),
                                         1) // group
        row_pos = kv_len - q_len + tile_start + q_row
        col_base = jax.lax.broadcasted_iota(jnp.int32, (blk, rows), 0)
        row_valid = (q_row + tile_start) < q_len
        feat_val = (feat_ref[...].astype(jnp.float32)
                    if (has_alibi or has_sinks) else None)

        def head_rows(feat_row, h):
            """A K/V head's entries of a ``[QH]`` feature row along its
            score rows (q-row-major, then group: row r belongs to q head
            ``h * group + r % group``), as ``[1, rows]``."""
            return jnp.tile(feat_val[feat_row, h * group:(h + 1) * group],
                            (bq, ))[None, :]

        if has_alibi:
            slopes_rows = [head_rows(0, h) for h in range(KVH)]

        def body(bi, carry):
            ms, ls, accs = carry
            kv_start = bi * blk
            slot = jax.lax.rem(bi, 2)

            @pl.when(bi + 1 < num_blocks)
            def _prefetch():
                fetch(bi + 1, jax.lax.rem(bi + 1, 2))

            for i in range(ppb):
                pltpu.make_async_copy(
                    k_cache.at[0, 0],
                    k_vmem.at[slot, 0, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 0, 0, i]).wait()
                pltpu.make_async_copy(
                    v_cache.at[0, 0],
                    v_vmem.at[slot, 0, :, pl.ds(i * page_size, page_size)],
                    kv_sems.at[slot, 1, 0, i]).wait()
            k_blk = k_vmem[slot, 0]  # [KVH, BLK, D]
            v_blk = v_vmem[slot, 0]
            kv_pos = kv_start + col_base
            mask = jnp.logical_and(kv_pos <= row_pos, row_valid)
            if window > 0:
                mask = jnp.logical_and(mask, kv_pos > row_pos - window)
            new_ms, new_ls, new_accs = [], [], []
            for h in range(KVH):
                s = jax.lax.dot_general(
                    k_blk[h].astype(jnp.float32), q_heads[h],
                    dimension_numbers=(((1, ), (1, )), ((), ())),
                    preferred_element_type=jnp.float32)  # [BLK, rows]
                if logit_cap > 0:
                    s = logit_cap * jnp.tanh(s / logit_cap)
                if has_alibi:
                    s = s + slopes_rows[h] * (
                        kv_pos - row_pos).astype(jnp.float32)
                s = jnp.where(mask, s, _MASK_VALUE)
                m_prev, l_prev, acc_prev = ms[h], ls[h], accs[h]
                m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
                pr = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + pr.sum(axis=0, keepdims=True)
                # [BLK, D] x [BLK, rows] -> [D, rows]: the keys contract.
                pv = jax.lax.dot_general(
                    v_blk[h], pr.astype(v_blk.dtype),
                    dimension_numbers=(((0, ), (0, )), ((), ())),
                    preferred_element_type=jnp.float32)
                new_ms.append(m_new)
                new_ls.append(l_new)
                new_accs.append(acc_prev * alpha + pv)
            return tuple(new_ms), tuple(new_ls), tuple(new_accs)

        init = (
            tuple(jnp.full((1, rows), _MASK_VALUE, jnp.float32)
                  for _ in range(KVH)),
            tuple(jnp.zeros((1, rows), jnp.float32) for _ in range(KVH)),
            tuple(jnp.zeros((D, rows), jnp.float32) for _ in range(KVH)),
        )
        ms, ls, accs = jax.lax.fori_loop(first_block, num_blocks, body,
                                         init)

        if has_sinks:
            # Learned per-head virtual key joining only the softmax
            # denominator (softmax shift-invariance makes the running
            # max of the REAL scores a valid reference point).
            ls = tuple(ls[h] + jnp.exp(head_rows(1, h) - ms[h])
                       for h in range(KVH))
        for h in range(KVH):
            o_h = (accs[h] / jnp.maximum(ls[h], 1e-20)).T  # [rows, D]
            out_stage[0:bq, h * group:(h + 1) * group, :] = (
                o_h.reshape(bq, group, D).astype(out_stage.dtype))
            if emit_state:
                st = jnp.concatenate([
                    jnp.broadcast_to(ms[h], (half, rows)),
                    jnp.broadcast_to(ls[h], (D - half, rows)),
                ], axis=0).T
                state_stage[0:bq, h * group:(h + 1) * group, :] = (
                    st.reshape(bq, group, D))

        # EXACT writeback: full 8-row chunks, then a per-row tail for
        # the partial chunk — a tile never writes a row it does not own,
        # so program order between attention programs is irrelevant and
        # the token array needs no bq-sized spill padding.
        def flush(stage, hbm, sems):
            for c in range(nck):
                @pl.when(8 * (c + 1) <= n_valid)
                def _wc(c=c):
                    pltpu.make_async_copy(
                        stage.at[pl.ds(8 * c, 8)],
                        hbm.at[pl.ds(q_start + tile_start + 8 * c, 8)],
                        sems.at[c]).start()
            for rr in range(bq):
                @pl.when(jnp.logical_and(rr // 8 == n_valid // 8,
                                         rr < n_valid))
                def _wr(rr=rr):
                    pltpu.make_async_copy(
                        stage.at[pl.ds(rr, 1)],
                        hbm.at[pl.ds(q_start + tile_start + rr, 1)],
                        sems.at[rr]).start()
            for c in range(nck):
                @pl.when(8 * (c + 1) <= n_valid)
                def _wcw(c=c):
                    pltpu.make_async_copy(
                        stage.at[pl.ds(8 * c, 8)],
                        hbm.at[pl.ds(0, 8)], sems.at[c]).wait()
            for rr in range(bq):
                @pl.when(jnp.logical_and(rr // 8 == n_valid // 8,
                                         rr < n_valid))
                def _wrw(rr=rr):
                    pltpu.make_async_copy(
                        stage.at[pl.ds(rr, 1)],
                        hbm.at[pl.ds(0, 1)], sems.at[rr]).wait()

        flush(out_stage, out_hbm, out_sems)
        if emit_state:
            flush(state_stage, state_hbm, state_sems)

    @pl.when(kind == KIND_DECODE)
    def _decode():
        # SB-batched decode (see _decode_kernel): the group's sequences
        # x kv heads stack as virtual heads; ONE dot scores every
        # sequence, a block-diagonal mask recovers per-sequence
        # attention. Slots address sequences through decode_list, so
        # decode rows keep MXU-filling batching in mixed waves.
        cnt = b
        R_dl = dl_ref.shape[0]
        idx = [dl_ref[jnp.minimum(a + i, R_dl - 1)] for i in range(sb)]
        live = [jnp.asarray(i) < cnt for i in range(sb)]
        # An inactive slot aliases a live sequence's row; a kv_len of 0
        # masks its scores and it moves no page.
        kv_lens = [jnp.where(live[i], seq_info_ref[idx[i], 2], 0)
                   for i in range(sb)]
        rows_ = [seq_info_ref[idx[i], 3] for i in range(sb)]
        q_starts = [seq_info_ref[idx[i], 0] for i in range(sb)]
        last_blocks = [jnp.maximum(jax.lax.div(kv_lens[i] - 1, blk), 0)
                       for i in range(sb)]
        if window > 0:
            # Each sequence walks from the block that holds the oldest
            # position its one query (at kv_len - 1) can see; the loop
            # index is relative to that block.
            first_blocks = [jnp.maximum(kv_lens[i] - window, 0) // blk
                            for i in range(sb)]
        else:
            first_blocks = [0] * sb
        # The kv blocks a slot's query can see. A slot moves those and
        # no other: the group takes its longest slot's trips (the
        # descriptor puts like lengths together), and a trip past a
        # slot's own end leaves the slot's buffer as it was.
        trips = [jnp.where(live[i], last_blocks[i] - first_blocks[i] + 1, 0)
                 for i in range(sb)]
        num_blocks = trips[0]
        for i in range(1, sb):
            num_blocks = jnp.maximum(num_blocks, trips[i])
        ROWS = sb * QH
        C = sb * KVH * blk

        for i in range(sb):
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(q_starts[i], 1)],
                q_vmem.at[pl.ds(i, 1)], q_sems.at[i]).start()

        def fetch(bi, slot):
            for i in range(sb):
                @pl.when(bi < trips[i])
                def _start(i=i):
                    ci = first_blocks[i] + bi
                    for j in range(ppb):
                        page_id = block_tables_ref[rows_[i], ci * ppb + j]
                        pltpu.make_async_copy(
                            k_cache.at[layer, page_id],
                            k_vmem.at[slot, i, :,
                                      pl.ds(j * page_size, page_size)],
                            kv_sems.at[slot, 0, i, j]).start()
                        pltpu.make_async_copy(
                            v_cache.at[layer, page_id],
                            v_vmem.at[slot, i, :,
                                      pl.ds(j * page_size, page_size)],
                            kv_sems.at[slot, 1, i, j]).start()

        fetch(0, 0)
        for i in range(sb):
            pltpu.make_async_copy(
                q_hbm.at[pl.ds(0, 1)], q_vmem.at[pl.ds(i, 1)],
                q_sems.at[i]).wait()
        q_all = (q_vmem[...][:sb].astype(jnp.float32) *
                 sm_scale).reshape(ROWS, D)

        vh_r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 0) // group
        vh_c = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) // blk
        diag = vh_r == vh_c
        col_off = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) % blk

        def per_row(values):
            """``values[i]`` (a scalar a sequence) on sequence i's QH
            score rows, as a ``[ROWS, 1]`` column. Mosaic joins 1-D
            vectors only inside one 128-lane tile, which ``sb x QH``
            outgrows at 128 query heads: such a group selects the column
            from an iota instead (a narrower one keeps the program it
            had)."""
            if ROWS <= 128:
                return jnp.concatenate(
                    [jnp.full((QH, ), values[i], jnp.int32)
                     for i in range(sb)])[:, None]
            seq = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0) // QH
            col = jnp.zeros((ROWS, 1), jnp.int32)
            for i in range(sb):
                col = jnp.where(seq == i, values[i], col)
            return col

        kvlen_rows = per_row(kv_lens)
        if window > 0:
            first_rows = per_row(first_blocks)
        feat_val = (feat_ref[...].astype(jnp.float32)
                    if (has_alibi or has_sinks) else None)
        if has_alibi:
            # Decode rows are seq-major then q-head-major: row i*QH + qh.
            slope_rows = jnp.tile(feat_val[0], (sb, ))[:, None]

        def body(bi, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(bi, 2)

            @pl.when(bi + 1 < num_blocks)
            def _prefetch():
                fetch(bi + 1, jax.lax.rem(bi + 1, 2))

            for i in range(sb):
                @pl.when(bi < trips[i])
                def _wait(i=i):
                    for j in range(ppb):
                        pltpu.make_async_copy(
                            k_cache.at[0, 0],
                            k_vmem.at[slot, i, :,
                                      pl.ds(j * page_size, page_size)],
                            kv_sems.at[slot, 0, i, j]).wait()
                        pltpu.make_async_copy(
                            v_cache.at[0, 0],
                            v_vmem.at[slot, i, :,
                                      pl.ds(j * page_size, page_size)],
                            kv_sems.at[slot, 1, i, j]).wait()
            k_all = k_vmem[slot].reshape(C, D)
            v_all = v_vmem[slot].reshape(C, D)
            s = jax.lax.dot_general(
                q_all, k_all.astype(jnp.float32),
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32)
            if logit_cap > 0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            if window > 0:
                kv_pos = (first_rows + bi) * blk + col_off
            else:
                kv_pos = bi * blk + col_off
            if has_alibi:
                # Decode q position is kv_len - 1 per sequence.
                s = s + slope_rows * (
                    kv_pos - (kvlen_rows - 1)).astype(jnp.float32)
            mask = jnp.logical_and(diag, kv_pos < kvlen_rows)
            if window > 0:
                mask = jnp.logical_and(
                    mask, kv_pos > kvlen_rows - 1 - window)
            s = jnp.where(mask, s, _MASK_VALUE)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            pr = jnp.where(mask, pr, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + pr.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(v_all.dtype), v_all,
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_prev * alpha + pv

        init = (
            jnp.full((ROWS, 1), _MASK_VALUE, jnp.float32),
            jnp.zeros((ROWS, 1), jnp.float32),
            jnp.zeros((ROWS, D), jnp.float32),
        )
        m_fin, l_fin, acc = jax.lax.fori_loop(0, num_blocks, body, init)
        if has_sinks:
            l_fin = l_fin + jnp.exp(
                jnp.tile(feat_val[1], (sb, ))[:, None] - m_fin)
        out = acc / jnp.maximum(l_fin, 1e-20)
        out_stage[0:sb, :, :] = out.reshape(sb, QH, D).astype(
            out_stage.dtype)
        if emit_state:
            st = jnp.concatenate([
                jnp.broadcast_to(m_fin, (ROWS, half)),
                jnp.broadcast_to(l_fin, (ROWS, D - half)),
            ], axis=-1)
            state_stage[0:sb, :, :] = st.reshape(sb, QH, D)
        # Per-sequence writeback through q_start; inactive slots MUST
        # NOT write (their q_start aliases a real token's row).
        for i in range(sb):
            @pl.when(jnp.asarray(i) < cnt)
            def _wb(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(q_starts[i], 1)],
                    out_sems.at[i]).start()
                if emit_state:
                    pltpu.make_async_copy(
                        state_stage.at[pl.ds(i, 1)],
                        state_hbm.at[pl.ds(q_starts[i], 1)],
                        state_sems.at[i]).start()
        for i in range(sb):
            @pl.when(jnp.asarray(i) < cnt)
            def _wbw(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(0, 1)], out_sems.at[i]).wait()
                if emit_state:
                    pltpu.make_async_copy(
                        state_stage.at[pl.ds(i, 1)],
                        state_hbm.at[pl.ds(0, 1)], state_sems.at[i]).wait()


def _mega_call(q, k_pages, v_pages, desc, seq_info, decode_list, kv_runs,
               block_tables, layer, k_new_hl, v_new_hl, *, sm_scale, bq,
               sb, interpret, emit_state, fuse_write, feat=None,
               window=0, logit_cap=0.0, has_alibi=False,
               has_sinks=False):
    """Shared launcher for the attention-only and fused write+attend
    variants of the mega-kernel."""
    true_q_heads = q.shape[1]
    _, _, num_kv_heads, page_size, _ = k_pages.shape
    assert true_q_heads % num_kv_heads == 0
    assert bq % 8 == 0 and bq >= 8
    q = _pad_query_groups(q, num_kv_heads)
    if feat is not None:
        feat = _pad_query_groups(feat, num_kv_heads)
    T_pad, num_q_heads, head_dim = q.shape
    group = num_q_heads // num_kv_heads
    ppb = kv_block_pages(page_size, block_tables.shape[1])
    blk = ppb * page_size
    stage_rows = max(bq, sb)
    if feat is None:
        feat = jnp.zeros((2, num_q_heads), jnp.float32)

    kernel = functools.partial(
        _mega_kernel, sm_scale=sm_scale, bq=bq, sb=sb, ppb=ppb,
        page_size=page_size, group=group, emit_state=emit_state,
        fuse_write=fuse_write, window=window, logit_cap=logit_cap,
        has_alibi=has_alibi, has_sinks=has_sinks)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]  # q
    operands = [q]
    if fuse_write:
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [k_new_hl, v_new_hl]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands += [k_pages, v_pages]
    # Head-feature sidecar (ALiBi slopes / sink logits): whole-array
    # VMEM block, read as a value by the attention bodies.
    in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)]
    operands += [feat]

    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    aliases = {}
    if fuse_write:
        out_shape += [
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        # Flat operand indices: 6 scalar-prefetch args, then q, k_new,
        # v_new, k_pages (9), v_pages (10) alias outputs 1 and 2.
        aliases = {9: 1, 10: 2}
    if emit_state:
        out_shape.append(jax.ShapeDtypeStruct(q.shape, jnp.float32))
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    scratch = [
        pltpu.VMEM((stage_rows, num_q_heads, head_dim), q.dtype),
        pltpu.VMEM((2, sb, num_kv_heads, blk, head_dim), k_pages.dtype),
        pltpu.VMEM((2, sb, num_kv_heads, blk, head_dim), v_pages.dtype),
        pltpu.VMEM((stage_rows, num_q_heads, head_dim), q.dtype),
    ]
    if emit_state:
        scratch.append(
            pltpu.VMEM((stage_rows, num_q_heads, head_dim), jnp.float32))
    if fuse_write:
        scratch += [
            pltpu.VMEM((num_kv_heads, page_size, head_dim),
                       k_pages.dtype),
            pltpu.VMEM((num_kv_heads, page_size, head_dim),
                       v_pages.dtype),
            pltpu.VMEM((num_kv_heads, 2 * page_size, head_dim),
                       k_pages.dtype),
            pltpu.VMEM((num_kv_heads, 2 * page_size, head_dim),
                       v_pages.dtype),
        ]
    scratch += [
        pltpu.SemaphoreType.DMA((max(sb, bq // 8), )),  # q reads
        pltpu.SemaphoreType.DMA((2, 2, sb, ppb)),  # kv double buffer
        pltpu.SemaphoreType.DMA((stage_rows, )),  # out flush
    ]
    if emit_state:
        scratch.append(pltpu.SemaphoreType.DMA((stage_rows, )))
    if fuse_write:
        scratch.append(pltpu.SemaphoreType.DMA((4, )))

    # The grid must stay sequential (no ``dimension_semantics``, so
    # "arbitrary"): the kv-write programs precede the attention programs
    # that read their pages, and ``_clear`` at p == 0 zeroes V's scratch
    # for every later program. Marked "parallel" on a two-core chip, the
    # second core's scratch would start as whatever VMEM held.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(desc.shape[0], ),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    result = list(pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(desc, seq_info, decode_list, kv_runs, layer, block_tables,
      *operands))
    if num_q_heads != true_q_heads:
        # out first, the cascade state last: both [T, padded heads, D].
        result[0] = _unpad_query_groups(result[0], true_q_heads,
                                        num_kv_heads)
        if emit_state:
            result[-1] = _unpad_query_groups(result[-1], true_q_heads,
                                             num_kv_heads)
    return tuple(result)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "bq", "sb", "interpret", "emit_state",
                     "window", "logit_cap", "has_alibi", "has_sinks"))
def unified_ragged_paged_attention_pallas(
    q: jax.Array,  # [T_pad, QH, D]; T_pad >= T + Q_TILE_PAD
    k_pages: jax.Array,  # [L, num_pages, KVH, PS, D] stacked cache
    v_pages: jax.Array,
    desc: jax.Array,  # [P, 3] int32 partition descriptor
    seq_info: jax.Array,  # [R, 4] int32 (q_start, q_len, kv_len, row)
    decode_list: jax.Array,  # [R] int32
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    layer: jax.Array | None = None,  # [1] int32
    feat: jax.Array | None = None,  # [2, QH] f32 (slopes, sinks)
    *,
    sm_scale: float,
    bq: int,
    sb: int,
    interpret: bool | None = None,
    emit_state: bool = False,
    window: int = 0,
    logit_cap: float = 0.0,
    has_alibi: bool = False,
    has_sinks: bool = False,
):
    """Mixed-batch attention in ONE kernel call, partitioned by ``desc``
    (see the module docstring for the descriptor contract). No static
    depends on the batch composition: ``bq``/``sb`` are fixed per model
    (prefill_tile_size / decode_group_size), so the compile lattice is
    one kernel x |T| input shapes. Rows the descriptor does not cover
    (padding tokens) are left unwritten — callers mask them.

    ``emit_state=True`` additionally returns the online-softmax partial
    state as an f32 [T_pad, QH, D] array (row max broadcast over lanes
    [0, D/2), exp-sum over [D/2, D)) for exact cascade merging, from
    BOTH prefill tiles and decode groups."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    if k_pages.ndim == 4:
        k_pages = k_pages[None]
        v_pages = v_pages[None]
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    result = _mega_call(
        q, k_pages, v_pages, desc, seq_info, decode_list,
        jnp.zeros((1, 4), jnp.int32), block_tables, layer, None, None,
        sm_scale=sm_scale, bq=bq, sb=sb, interpret=interpret,
        emit_state=emit_state, fuse_write=False, feat=feat,
        window=window, logit_cap=logit_cap, has_alibi=has_alibi,
        has_sinks=has_sinks)
    if emit_state:
        return result  # (out, state)
    return result[0]


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "bq", "sb", "interpret",
                              "window", "logit_cap", "has_alibi",
                              "has_sinks"))
def unified_write_attend_pallas(
    q: jax.Array,  # [T_pad, QH, D]
    k_pages: jax.Array,  # [L, num_pages, KVH, PS, D] (aliased in place)
    v_pages: jax.Array,
    k_new_hl: jax.Array,  # [KVH, T_pad + 3*PS, D] head-leading, padded
    v_new_hl: jax.Array,
    desc: jax.Array,  # [P, 3] with kind-3 kv-write rows FIRST
    seq_info: jax.Array,
    decode_list: jax.Array,
    kv_runs: jax.Array,  # [G, 4] int32 (page, off, window_start, len)
    block_tables: jax.Array,
    layer: jax.Array,  # [1] int32
    feat: jax.Array | None = None,  # [2, QH] f32 (slopes, sinks)
    *,
    sm_scale: float,
    bq: int,
    sb: int,
    interpret: bool | None = None,
    window: int = 0,
    logit_cap: float = 0.0,
    has_alibi: bool = False,
    has_sinks: bool = False,
):
    """Fused KV-page write + mixed-batch attention: ONE pass over the
    cache per layer. The descriptor's kind-3 programs land the step's
    new K/V pages in place (input/output aliasing), and because the TPU
    grid executes programs in order, every attention program reads the
    freshly written pages. Returns (out, k_pages, v_pages)."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    out, k2, v2 = _mega_call(
        q, k_pages, v_pages, desc, seq_info, decode_list, kv_runs,
        block_tables, layer, k_new_hl, v_new_hl, sm_scale=sm_scale,
        bq=bq, sb=sb, interpret=interpret, emit_state=False,
        fuse_write=True, feat=feat, window=window, logit_cap=logit_cap,
        has_alibi=has_alibi, has_sinks=has_sinks)
    return out, k2, v2
