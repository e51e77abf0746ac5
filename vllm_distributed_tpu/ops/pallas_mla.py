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
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
KERNEL_NAME = "mla_latent_pages"


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
