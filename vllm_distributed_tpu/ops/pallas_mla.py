"""Pallas MLA (latent MQA) attention kernel for TPU.

TPU-native counterpart of the reference's MLA decode kernels
(csrc/attention/mla/cutlass_mla_kernels.cu, sm100 FlashMLA): attention
over the paged LATENT cache — one [kv_lora_rank + rope] row per token,
shared by every head (MQA). Mirrors ops/pallas_attention.py's design
(grid (seq, q_tile), scalar-prefetched per-sequence runs, async page
DMA, online-softmax carries) with the MLA twists:

* ONE kv "head": all q heads fold into the score-matrix rows, so the
  per-block compute is two plain MXU matmuls — [rows, Cs] x
  [Cs, BLK] scores and [rows, BLK] x [BLK, Lkv] accumulate, on the
  operands as stored (bf16 in serving) with float32 accumulators; the
  block's pages are fetched into one of two buffers while the block
  before is multiplied.
* The value matrix IS the key latent slice (absorbed form): the
  accumulator carries [rows, Lkv] and the caller applies W_UV after.

Layout contract matches the base kernel (flat ragged q, seq_info runs,
padded q tiles); the cache is [L, num_pages, PS, Cs] with the latent in
lanes [0, Lkv) and the rope key in [Lkv, Lkv + R).

A second kernel, ``mla_latent_prefill``, serves the rows of a prefill
chunk in the plain multi-head form (ops/mla.py decides which rows take
which): causal flash attention of one sequence's chunk against its
context, K and V already up-projected, the rope key shared by the
heads.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# Tile of the multi-head kernel: 512 query rows by 512 context rows of
# float32 scores (1 MiB) beside one head's K and V blocks.
_PREFILL_BQ = 512
_PREFILL_BK = 512
KERNEL_NAME = "mla_latent_pages"
# Both names start with "mla_latent": the benchmark's readers of the
# latent kernel's device time match that prefix.
PREFILL_KERNEL_NAME = "mla_latent_prefill"


def _kernel(
    # scalar prefetch
    seq_info_ref,  # [R, 4] int32: q_start, q_len, kv_len, batch_row
    num_seqs_ref,  # [1] int32
    layer_ref,  # [1] int32
    block_tables_ref,  # [max_reqs, pages_per_req] int32
    # tensor inputs (HBM)
    q_hbm,  # [T_pad, N, kdim_pad]
    c_hbm,  # [L, num_pages, PS, Cs]
    # output (HBM)
    out_hbm,  # [T_pad, N, Lkv_pad]
    # scratch
    q_vmem,  # [BQ, N, kdim_pad]
    c_vmem,  # [2, BLK, Cs]
    out_stage,  # [BQ, N, Lkv_pad]
    q_sem,
    c_sems,  # [2, PPB]
    out_sem,
    *,
    sm_scale: float,
    bq: int,
    ppb: int,
    page_size: int,
    lkv: int,
):
    r = pl.program_id(0)
    qt = pl.program_id(1)
    q_start = seq_info_ref[r, 0]
    q_len = seq_info_ref[r, 1]
    kv_len = seq_info_ref[r, 2]
    row = seq_info_ref[r, 3]
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    N = q_vmem.shape[1]
    lkv_pad = out_stage.shape[2]

    blk = ppb * page_size
    tile_start = qt * bq
    q_pos_max = kv_len - q_len + jnp.minimum(tile_start + bq, q_len) - 1
    active = jnp.logical_and(
        r < num_seqs,
        jnp.logical_and(tile_start < q_len, kv_len > 0))

    @pl.when(active)
    def _run():
        q_dma = pltpu.make_async_copy(
            q_hbm.at[pl.ds(q_start + tile_start, bq)], q_vmem, q_sem)
        q_dma.start()
        num_blocks = q_pos_max // blk + 1
        q_dma.wait()

        rows = bq * N
        # The MXU takes the operands as stored (bf16 in serving): the
        # products are exact in the float32 accumulator either way, and
        # the scale multiplies the scores after. q's lanes past kdim are
        # zero (the caller's padding), so the contraction runs over the
        # whole stored row and needs no slice off the 128-lane tiling.
        q_tile = q_vmem[...].reshape(rows, -1)

        row_pos = (kv_len - q_len + tile_start +
                   jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) //
                   N)
        col_base = jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
        row_valid = (jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) //
                     N + tile_start) < q_len

        def page_copy(b, slot, i):
            return pltpu.make_async_copy(
                c_hbm.at[layer, block_tables_ref[row, b * ppb + i]],
                c_vmem.at[slot, pl.ds(i * page_size, page_size)],
                c_sems.at[slot, i])

        def fetch(b, slot):
            for i in range(ppb):
                page_copy(b, slot, i).start()

        # Two buffers: block b + 1 is on its way while block b is
        # multiplied, so only the first block's latency is exposed.
        fetch(0, 0)

        def body(b, carry):
            m_prev, l_prev, acc_prev = carry
            slot = b % 2

            @pl.when(b + 1 < num_blocks)
            def _prefetch():
                fetch(b + 1, 1 - slot)

            for i in range(ppb):
                page_copy(0, slot, i).wait()

            kv_pos = b * blk + col_base
            mask = jnp.logical_and(kv_pos <= row_pos, row_valid)

            c_blk = c_vmem[slot]  # [BLK, Cs]
            s = jax.lax.dot_general(
                q_tile, c_blk,
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(mask, s, _MASK_VALUE)  # [rows, BLK]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(c_blk.dtype), c_blk[:, :lkv],
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)  # [rows, Lkv]
            acc_new = acc_prev * alpha + pv
            return m_new, l_new, acc_new

        init = (jnp.full((rows, 1), _MASK_VALUE, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, lkv), jnp.float32))
        m, l, acc = jax.lax.fori_loop(0, num_blocks, body, init)

        o = acc / jnp.maximum(l, 1e-20)  # [rows, Lkv]
        if lkv_pad > lkv:
            o = jnp.pad(o, ((0, 0), (0, lkv_pad - lkv)))
        out_stage[...] = o.reshape(bq, N, lkv_pad).astype(
            out_stage.dtype)
        out_dma = pltpu.make_async_copy(
            out_stage, out_hbm.at[pl.ds(q_start + tile_start, bq)],
            out_sem)
        out_dma.start()
        out_dma.wait()


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "max_q", "kv_lora_rank", "rope_dim",
                     "interpret"))
def ragged_latent_attention_pallas(
    q: jax.Array,  # [T_pad, N, kdim_pad] (ql ++ q_pe, lane-padded)
    c_pages: jax.Array,  # [L, num_pages, PS, Cs]
    seq_info: jax.Array,  # [R, 4] int32
    num_seqs: jax.Array,  # [1] int32
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    layer: jax.Array | None = None,  # [1] int32
    *,
    sm_scale: float,
    max_q: int,
    kv_lora_rank: int,
    rope_dim: int,
    interpret: bool | None = None,
) -> jax.Array:
    """MLA attention over the latent cache; returns [T_pad, N, Lkv_pad]
    (lanes past kv_lora_rank are zero; caller slices). Rows past each
    sequence's q_len are garbage, like the base kernel."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    T_pad, N, kdim_pad = q.shape
    _, num_pages, page_size, Cs = c_pages.shape
    assert kdim_pad == Cs and kv_lora_rank + rope_dim <= Cs, (
        "q carries the stored row's lanes, zero past kv_lora_rank + rope")
    R = seq_info.shape[0]
    pages_per_req = block_tables.shape[1]
    from vllm_distributed_tpu.ops.mla import latent_storage_dim
    lkv_pad = latent_storage_dim(kv_lora_rank, 0)

    bq = min(max_q, 32)
    # VMEM: q tile + f32 accumulators over Lkv lanes per row.
    while bq > 1 and bq * N * (kdim_pad + 3 * kv_lora_rank) * 4 > \
            10 * 1024**2:
        bq //= 2
    num_q_tiles = pl.cdiv(max_q, bq)
    assert T_pad >= bq, "q must be padded to at least one tile"
    ppb = max(1, min(128 // page_size, pages_per_req))
    while pages_per_req % ppb:
        ppb -= 1
    blk = ppb * page_size

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, bq=bq, ppb=ppb,
        page_size=page_size, lkv=kv_lora_rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R, num_q_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # q
            pl.BlockSpec(memory_space=pl.ANY),  # c_pages
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((bq, N, kdim_pad), q.dtype),
            pltpu.VMEM((2, blk, Cs), c_pages.dtype),
            pltpu.VMEM((bq, N, lkv_pad), q.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, ppb)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T_pad, N, lkv_pad), q.dtype),
        interpret=interpret,
        # The device trace names the custom call by this: a name of its
        # own, which the paged kernel's readers ("attend|attention")
        # do not match.
        name=KERNEL_NAME,
    )(seq_info, num_seqs, layer, block_tables, q, c_pages)


# Rows of a bf16 tile in device memory: a q tile starts at a sequence's
# first row rounded down to it, so that every block copy is aligned.
_ROW_ALIGN = 16


def prefill_blocks(max_q: int, ctx_rows: int) -> tuple[int, int, int]:
    """(q tile, kv block, context rows) of the multi-head kernel for a
    chunk of up to ``max_q`` rows against up to ``ctx_rows`` of context:
    the context is padded to whole kv blocks of whole 128-lane score
    tiles, and the caller's K and V buffers carry that many rows."""
    bq = min(_PREFILL_BQ, -(-max_q // _ROW_ALIGN) * _ROW_ALIGN)
    bk = min(_PREFILL_BK, -(-ctx_rows // 128) * 128)
    return bq, bk, -(-ctx_rows // bk) * bk


def _prefill_kernel(
    seq_ref,  # [3] int32 scalar prefetch: q_start, q_len, kv_len
    qn_hbm,  # [T, N * P] the step's no-rope queries, heads side by side
    qp_hbm,  # [T, N * R] its rope queries
    kn_hbm,  # [S, N * P] the sequence's no-rope keys
    kp_hbm,  # [S, R] its rope keys, shared by the heads
    v_hbm,  # [S, N * V]
    _,  # the output as it stands (aliased)
    out_hbm,  # [T, N * V]
    qn_buf,  # [BQ, P]
    qp_buf,  # [BQ, R]
    kn_buf,  # [2, BK, P]
    kp_buf,  # [2, BK, R]
    v_buf,  # [2, BK, V]
    o_buf,  # [BQ, V]
    m_ref,  # [BQ, 1] f32 running maximum
    l_ref,  # [BQ, 1] f32 running sum
    acc_ref,  # [BQ, V] f32
    q_sems,  # [2]
    kv_sems,  # [2, 3]
    o_sem,
    *,
    sm_scale: float,
    bq: int,
    bk: int,
):
    h = pl.program_id(0)
    qi = pl.program_id(1)
    q_start, q_len, kv_len = seq_ref[0], seq_ref[1], seq_ref[2]

    def head_lanes(buf):
        """Head h's window of a row whose heads lie side by side, as
        wide as ``buf``'s rows."""
        width = buf.shape[-1]
        return pl.ds(pl.multiple_of(h * width, width), width)

    # Tile qi holds rows [row0, row0 + bq) of the step. The first starts
    # at q_start rounded down to the tiling, so it may open with rows of
    # the sequence before, as the last may close with rows of the next:
    # they are computed and not stored.
    row0 = pl.multiple_of(
        q_start // _ROW_ALIGN * _ROW_ALIGN + qi * bq, _ROW_ALIGN)
    # The chunk's row i stands at position kv_len - q_len + i.
    pos0 = kv_len - q_len + (row0 - q_start)
    first_pos = jnp.maximum(pos0, kv_len - q_len)
    last_pos = jnp.minimum(pos0 + bq, kv_len) - 1

    @pl.when(row0 < q_start + q_len)
    def _run():
        rows = pl.ds(row0, bq)
        head_q = (
            pltpu.make_async_copy(qn_hbm.at[rows, head_lanes(qn_buf)],
                                  qn_buf, q_sems.at[0]),
            pltpu.make_async_copy(qp_hbm.at[rows, head_lanes(qp_buf)],
                                  qp_buf, q_sems.at[1]))
        head_out = out_hbm.at[rows, head_lanes(o_buf)]
        for c in head_q:
            c.start()
        num_blocks = last_pos // bk + 1
        # Blocks that end at or before the tile's first position are
        # seen whole; only those the diagonal crosses pay for the mask.
        num_whole = (first_pos + 1) // bk

        def copies(b, slot):
            at = pl.ds(b * bk, bk)
            return (
                pltpu.make_async_copy(kn_hbm.at[at, head_lanes(kn_buf)],
                                      kn_buf.at[slot], kv_sems.at[slot, 0]),
                pltpu.make_async_copy(kp_hbm.at[at], kp_buf.at[slot],
                                      kv_sems.at[slot, 1]),
                pltpu.make_async_copy(v_hbm.at[at, head_lanes(v_buf)],
                                      v_buf.at[slot], kv_sems.at[slot, 2]))

        def fetch(b, slot):
            for c in copies(b, slot):
                c.start()

        # Two buffers, as in the latent kernel: block b + 1 is on its
        # way while block b is multiplied.
        fetch(0, 0)
        m_ref[...] = jnp.full(m_ref.shape, _MASK_VALUE, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        for c in head_q:
            c.wait()

        def block(b, masked: bool):
            slot = b % 2

            @pl.when(b + 1 < num_blocks)
            def _prefetch():
                fetch(b + 1, 1 - slot)

            for c in copies(b, slot):
                c.wait()
            # bf16 operands into the MXU, float32 scores, as the latent
            # kernel takes them.
            s = jax.lax.dot_general(
                qn_buf[...], kn_buf[slot],
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(
                qp_buf[...], kp_buf[slot],
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32)
            s = s * sm_scale  # [BQ, BK]
            if masked:
                q_pos = pos0 + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 0)
                kv_pos = b * bk + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                s = jnp.where(kv_pos <= q_pos, s, _MASK_VALUE)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1,
                                                    keepdims=True)
            m_ref[...] = m_new
            v = v_buf[slot]
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, num_whole,
                          lambda b, _: block(b, False), None)
        jax.lax.fori_loop(num_whole, num_blocks,
                          lambda b, _: block(b, True), None)

        # The rows of other sequences in the tile keep what they hold.
        held = pltpu.make_async_copy(head_out, o_buf, o_sem)
        held.start()
        held.wait()
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        mine = jnp.logical_and(row >= q_start, row < q_start + q_len)
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
        o_buf[...] = jnp.where(mine, o.astype(o_buf.dtype), o_buf[...])
        store = pltpu.make_async_copy(o_buf, head_out, o_sem)
        store.start()
        store.wait()


@functools.partial(jax.jit,
                   static_argnames=("num_heads", "max_q", "sm_scale",
                                    "interpret"))
def latent_prefill_attention_pallas(
    q_nope: jax.Array,  # [T, N * P] the step's rows, heads side by side
    q_pe: jax.Array,  # [T, N * R]
    k_nope: jax.Array,  # [S, N * P] one sequence's context, up-projected
    k_pe: jax.Array,  # [S, R] its rope keys, shared by the heads
    v: jax.Array,  # [S, N * V]
    out: jax.Array,  # [T, N * V] the step's output so far
    seq: jax.Array,  # [3] int32: q_start, q_len, kv_len (chunk included)
    *,
    num_heads: int,
    max_q: int,
    sm_scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal flash attention of one sequence's chunk, rows ``[q_start,
    q_start + q_len)`` of the step (the last ``q_len`` positions of
    ``kv_len``), in the plain multi-head form, written into those rows
    of ``out`` in place; every other row of ``out`` keeps what it holds.

    Rows stay token-major with a row's heads side by side, as the
    projections leave them, so nothing is gathered or transposed for the
    kernel: a head's block is a window of lanes (on the chip P, R and V
    are whole 128-lane tiles: the caller pads) and a q tile a window of
    the step's rows, the first from ``q_start`` rounded down to the row
    tiling. T has ``prefill_blocks``'s q tile of room past the last
    live row; S is whole kv blocks. Tiles past the chunk's end do
    nothing and kv blocks past a tile's last position are never read,
    so K and V need to hold nothing past the block that kv_len ends
    in."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    N = num_heads
    S, R = k_pe.shape
    P, V = k_nope.shape[1] // N, v.shape[1] // N
    bq, bk, s_pad = prefill_blocks(max_q, S)
    assert s_pad == S and q_nope.shape[0] >= bq, (S, bq, bk)
    dtype = q_nope.dtype

    # In device memory and nowhere else: given the choice, the compiler
    # stages whole operands through faster memory and back, a copy of
    # the step's output a sequence.
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # A chunk of max_q rows that starts _ROW_ALIGN - 1 rows into
        # its first tile.
        grid=(N, pl.cdiv(max_q + _ROW_ALIGN - 1, bq)),
        in_specs=[hbm] * 6,
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((bq, P), dtype),
            pltpu.VMEM((bq, R), dtype),
            pltpu.VMEM((2, bk, P), k_nope.dtype),
            pltpu.VMEM((2, bk, R), k_pe.dtype),
            pltpu.VMEM((2, bk, V), v.dtype),
            pltpu.VMEM((bq, V), out.dtype),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, V), jnp.float32),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    return pl.pallas_call(
        functools.partial(_prefill_kernel, sm_scale=sm_scale, bq=bq,
                          bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out.shape, out.dtype),
        # seq is input 0: `out` (6) is the output, in place.
        input_output_aliases={6: 0},
        interpret=interpret,
        name=PREFILL_KERNEL_NAME,
    )(seq, q_nope, q_pe, k_nope, k_pe, v, out)
