"""Fused transformer-block decode: ONE Pallas call per layer.

ROADMAP item 1 (PAPERS.md "ClusterFusion++"): the mega-kernel collapsed
attention to one Pallas call per layer, but a decode step still bounced
through XLA op boundaries — RMSNorm, three projection matmuls, rope, the
KV write, attention, the output projection and the gated MLP each
round-tripped the [T, H] activations through HBM. At decode batch sizes
those activations are tiny next to the weights, so every boundary costs
a kernel launch plus an HBM write+read of the residual stream for no
reason. This kernel chains the WHOLE layer for decode-only waves:

    RMSNorm -> fused QKV projection (one re-laid [H, Dq+2*Dkv] weight)
      -> rope -> KV-page write (in-place RMW) -> paged attention
      -> O-projection -> residual add -> RMSNorm -> gated MLP
      -> residual add

with the activations living in VMEM across the entire layer. Weights
stream through VMEM in column/row tiles (decode is weight-bandwidth
bound; the stream is the same HBM traffic the separate matmuls paid,
minus all the activation round-trips). The gated MLP is tile-fused too:
gate/up/down consume one intermediate tile at a time, so the [T, I]
intermediate never materializes anywhere.

Design notes:

* Grid = decode groups of ``sb`` sequences (the decode_group_size
  batching of ops/pallas_attention.py); each program runs the full
  layer for its group. Sequences address their token row through
  seq_info's q_start, so the runner's decode layout works unchanged.
* The current token's K/V contribution folds into the online softmax
  IN REGISTER (one extra score column per head): attention walks only
  the kv_len - 1 CACHED positions, so there is no write-then-read
  hazard on the cache page the program itself just updated. The page
  write is still performed (future steps read it) as an in-place RMW
  aliased on the cache refs, like ops/pallas_kv_write.py.
* Sliding window / softcap / ALiBi / sinks ride the same per-layer
  statics + [2, QH] head-feature sidecar as the mega-kernel, so
  feature models that pass the block-shape eligibility keep the fused
  path.
* Weight tile streams are DOUBLE-BUFFERED: every stream (fused-QKV
  columns, O-proj rows, the gate/up/down MLP trio) prefetches tile
  i+1 into its second VMEM slot while tile i multiplies, so the HBM
  weight read — the bandwidth bound of decode — overlaps the MXU
  work instead of serializing with it. Two slots per stream keep the
  VMEM footprint flat by halving the per-tile cap (weight_tile cap
  256 vs the single-buffered 512). Eligibility (decided once in
  models/loader.py) pins TP=1 and the standard dense block, so no
  shard_map wrapping is needed here.

``fused_block_decode_xla`` is the XLA-composed correctness reference:
the same math built from the reference ops (rms_norm, rope helpers,
the flat-scatter KV write and the XLA ragged attention), used by the
parity suite and as the non-Pallas fallback.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def weight_tile(n: int, cap: int = 256) -> int:
    """Streaming tile width along a weight dimension: the largest
    divisor of ``n`` that is <= cap and lane-aligned (multiple of 128)
    when one exists, else the largest divisor <= cap. Small dims (CPU
    tests) stream as one tile. The cap is half the single-buffered
    512: each stream now holds TWO tiles in VMEM (double buffering),
    so the finer tile keeps the footprint flat and pipelines the HBM
    read behind the previous tile's matmul."""
    if n <= cap:
        return n
    for t in range(cap, 0, -128):
        if t % 128 == 0 and n % t == 0:
            return t
    for t in range(cap, 0, -1):
        if n % t == 0:
            return t
    return n


def fused_block_group_size(num_q_heads: int, num_kv_heads: int,
                           num_reqs: int) -> int:
    """Sequences per fused-block program: the decode-group width of the
    mega-kernel (virtual-head batching keeps the score dot MXU-filling),
    re-derived here so the two kernels can diverge independently."""
    from vllm_distributed_tpu.ops.pallas_attention import decode_group_size
    return max(1, min(decode_group_size(num_q_heads, num_kv_heads),
                      num_reqs))


def _rot_half_matrix(hd: int):
    """[hd, hd] f32 permutation: x @ P == rotate_half(x). Built from
    iotas in-kernel (Mosaic has no lane-dim dynamic slicing on values;
    a 0/-1/+1 matmul keeps the rotation exact and MXU-friendly)."""
    r = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
    half = hd // 2
    return (jnp.where(r == c + half, -1.0, 0.0) +
            jnp.where(r + half == c, 1.0, 0.0)).astype(jnp.float32)


def _kernel(
    # scalar prefetch
    seq_info_ref,  # [R, 4] int32: q_start, q_len, kv_len, batch_row
    num_seqs_ref,  # [1] int32
    layer_ref,  # [1] int32
    block_tables_ref,  # [max_reqs, pages_per_req] int32
    # tensor inputs
    hidden_hbm,  # [T_pad, H] (aliased -> out)
    wqkv_hbm,  # [H, Dq + 2*Dkv]
    wo_hbm,  # [Dq, H]
    wg_hbm,  # [H, I]
    wu_hbm,  # [H, I]
    wd_hbm,  # [I, H]
    lnw_ref,  # [2, H] VMEM: input_ln, post_ln
    rope_hbm,  # [2, T_pad, hd] f32: cos, sin
    feat_ref,  # [2, QH] f32 VMEM: ALiBi slopes, sink logits
    _k_in,  # aliased cache inputs
    _v_in,
    # outputs
    out_hbm,  # [T_pad, H] (aliased to hidden)
    k_cache,  # [L, N, KVH, PS, D] (aliased)
    v_cache,
    # scratch
    x_vmem,  # [sb, H] io dtype
    rope_buf,  # [2, sb, hd] f32
    col_buf,  # [2, H, TQ] weight dtype (QKV column tiles, 2 slots)
    row_buf,  # [2, TO, H] weight dtype (O-proj row tiles, 2 slots)
    wg_buf,  # [2, H, TI] (double-buffered MLP streams)
    wu_buf,  # [2, H, TI]
    wd_buf,  # [2, TI, H]
    kbuf,  # [2, sb, KVH, blk, D] cache dtype
    vbuf,
    kpage,  # [KVH, PS, D]
    vpage,
    out_stage,  # [sb, H] io dtype
    x_sems,  # DMA [sb]
    rope_sems,  # DMA [2, sb]
    w_sems,  # DMA [5, 2] (per weight stream x buffer slot)
    kv_sems,  # DMA [2, 2, sb, ppb]
    page_sems,  # DMA [2]
    out_sems,  # DMA [sb]
    *,
    sm_scale: float,
    eps: float,
    sb: int,
    ppb: int,
    page_size: int,
    group: int,
    tq: int,
    to: int,
    ti: int,
    window: int,
    logit_cap: float,
    has_alibi: bool,
    has_sinks: bool,
):
    p = pl.program_id(0)
    num_seqs = num_seqs_ref[0]
    layer = layer_ref[0]
    H = x_vmem.shape[1]
    QH = feat_ref.shape[1]
    KVH = kbuf.shape[2]
    hd = rope_buf.shape[2]
    Dq = QH * hd
    Dkv = KVH * hd
    Dtot = Dq + 2 * Dkv
    I = wg_hbm.shape[1]
    blk = ppb * page_size
    base = p * sb
    ROWS = sb * QH
    C = sb * KVH * blk

    # Per-sequence scalars (static unroll over sb slots; inactive slots
    # clamp to row 0's metadata and mask everything via kv_len = 0).
    idx = [jnp.minimum(base + i, seq_info_ref.shape[0] - 1)
           for i in range(sb)]
    kv_lens = [
        jnp.where(base + i < num_seqs, seq_info_ref[idx[i], 2], 0)
        for i in range(sb)
    ]
    rows_ = [seq_info_ref[idx[i], 3] for i in range(sb)]
    q_starts = [seq_info_ref[idx[i], 0] for i in range(sb)]
    cached = [jnp.maximum(kv_lens[i] - 1, 0) for i in range(sb)]

    @pl.when(base < num_seqs)
    def _run():
        # ---- stage the group's hidden rows + rope rows --------------
        for i in range(sb):
            pltpu.make_async_copy(
                hidden_hbm.at[pl.ds(q_starts[i], 1)],
                x_vmem.at[pl.ds(i, 1)], x_sems.at[i]).start()
            for rr in range(2):
                pltpu.make_async_copy(
                    rope_hbm.at[rr, pl.ds(q_starts[i], 1)],
                    rope_buf.at[rr, pl.ds(i, 1)],
                    rope_sems.at[rr, i]).start()
        for i in range(sb):
            pltpu.make_async_copy(
                hidden_hbm.at[pl.ds(0, 1)], x_vmem.at[pl.ds(i, 1)],
                x_sems.at[i]).wait()
            for rr in range(2):
                pltpu.make_async_copy(
                    rope_hbm.at[0, pl.ds(0, 1)],
                    rope_buf.at[rr, pl.ds(i, 1)],
                    rope_sems.at[rr, i]).wait()

        h0 = x_vmem[...].astype(jnp.float32)  # [sb, H] residual stream
        io_dtype = x_vmem.dtype
        w_dtype = col_buf.dtype
        lnw = lnw_ref[...].astype(jnp.float32)

        def rms(x32, w_row):
            var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            return ((x32 * jax.lax.rsqrt(var + eps)) *
                    lnw[w_row][None, :]).astype(io_dtype)

        # ---- RMSNorm -> fused QKV (double-buffered column tiles) ----
        # Tile t+1's DMA streams into the other VMEM slot while tile
        # t multiplies; the wait() below re-constructs the matching
        # copy descriptor (only the semaphore/slot identity matters).
        xn = rms(h0, 0).astype(w_dtype)
        nq = Dtot // tq

        def qkv_copy(t):
            return pltpu.make_async_copy(
                wqkv_hbm.at[:, pl.ds(t * tq, tq)], col_buf.at[t % 2],
                w_sems.at[0, t % 2])

        qkv_copy(0).start()
        parts = []
        for t in range(nq):
            if t + 1 < nq:
                qkv_copy(t + 1).start()
            qkv_copy(t).wait()
            parts.append(
                jax.lax.dot_general(
                    xn, col_buf[t % 2],
                    dimension_numbers=(((1, ), (0, )), ((), ())),
                    preferred_element_type=jnp.float32))
        qkv = jnp.concatenate(parts, axis=-1).astype(io_dtype)
        q = qkv[:, :Dq].reshape(sb, QH, hd)
        k = qkv[:, Dq:Dq + Dkv].reshape(sb, KVH, hd)
        v = qkv[:, Dq + Dkv:].reshape(sb, KVH, hd)

        # ---- rope (rotate-half as an exact 0/±1 matmul) -------------
        rot = _rot_half_matrix(hd)
        cos = rope_buf[0][:, None, :]  # [sb, 1, hd]
        sin = rope_buf[1][:, None, :]

        def rope_apply(x):
            x32 = x.astype(jnp.float32)
            xr = jax.lax.dot_general(
                x32.reshape(sb * x.shape[1], hd), rot,
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32).reshape(x32.shape)
            return (x32 * cos + xr * sin).astype(io_dtype)

        q = rope_apply(q)
        k = rope_apply(k)

        # ---- KV-page write: in-place RMW of each slot's page --------
        # One new row per sequence at position kv_len - 1; sequences
        # own distinct pages, so the RMWs are race-free. Attention
        # below reads only CACHED positions (< kv_len - 1), so program
        # order vs this write is irrelevant within the program.
        for i in range(sb):
            @pl.when(jnp.logical_and(base + i < num_seqs,
                                     kv_lens[i] > 0))
            def _write(i=i):
                pos = kv_lens[i] - 1
                page = block_tables_ref[rows_[i],
                                        jax.lax.div(pos, page_size)]
                off = jax.lax.rem(pos, page_size)
                kp = pltpu.make_async_copy(k_cache.at[layer, page],
                                           kpage, page_sems.at[0])
                vp = pltpu.make_async_copy(v_cache.at[layer, page],
                                           vpage, page_sems.at[1])
                kp.start()
                vp.start()
                kp.wait()
                vp.wait()
                row_sel = (jax.lax.broadcasted_iota(
                    jnp.int32, (1, page_size, 1), 1) == off)
                kpage[...] = jnp.where(
                    row_sel, k[i].astype(kpage.dtype)[:, None, :],
                    kpage[...])
                vpage[...] = jnp.where(
                    row_sel, v[i].astype(vpage.dtype)[:, None, :],
                    vpage[...])
                kb = pltpu.make_async_copy(kpage,
                                           k_cache.at[layer, page],
                                           page_sems.at[0])
                vb = pltpu.make_async_copy(vpage,
                                           v_cache.at[layer, page],
                                           page_sems.at[1])
                kb.start()
                vb.start()
                kb.wait()
                vb.wait()

        # ---- paged attention over the CACHED positions --------------
        max_cached = cached[0]
        for i in range(1, sb):
            max_cached = jnp.maximum(max_cached, cached[i])
        num_blocks = jnp.where(
            max_cached > 0, jax.lax.div(max_cached - 1, blk) + 1, 0)

        def fetch(bi, slot):
            for i in range(sb):
                ci = jnp.clip(bi, 0,
                              jnp.maximum(
                                  jax.lax.div(cached[i] - 1, blk), 0))
                for j in range(ppb):
                    page_id = block_tables_ref[rows_[i], ci * ppb + j]
                    pltpu.make_async_copy(
                        k_cache.at[layer, page_id],
                        kbuf.at[slot, i, :,
                                pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 0, i, j]).start()
                    pltpu.make_async_copy(
                        v_cache.at[layer, page_id],
                        vbuf.at[slot, i, :,
                                pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 1, i, j]).start()

        # Warm-up fetch only when the loop will run: with zero cached
        # blocks (every slot at kv_len <= 1) nothing ever waits the kv
        # semaphores, and a started-but-unwaited DMA is a Mosaic error.
        @pl.when(num_blocks > 0)
        def _warmup():
            fetch(0, 0)

        q_all = (q.astype(jnp.float32) * sm_scale).reshape(ROWS, hd)
        vh_r = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 0) // group
        vh_c = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) // blk
        diag = vh_r == vh_c
        col_off = jax.lax.broadcasted_iota(jnp.int32, (ROWS, C), 1) % blk
        cached_rows = jnp.concatenate(
            [jnp.full((QH, ), cached[i], jnp.int32) for i in range(sb)])
        feat_val = (feat_ref[...].astype(jnp.float32)
                    if (has_alibi or has_sinks) else None)
        if has_alibi:
            slope_rows = jnp.tile(feat_val[0], (sb, ))[:, None]

        def body(bi, carry):
            m_prev, l_prev, acc_prev = carry
            slot = jax.lax.rem(bi, 2)

            @pl.when(bi + 1 < num_blocks)
            def _prefetch():
                fetch(bi + 1, jax.lax.rem(bi + 1, 2))

            for i in range(sb):
                for j in range(ppb):
                    pltpu.make_async_copy(
                        k_cache.at[0, 0],
                        kbuf.at[slot, i, :,
                                pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 0, i, j]).wait()
                    pltpu.make_async_copy(
                        v_cache.at[0, 0],
                        vbuf.at[slot, i, :,
                                pl.ds(j * page_size, page_size)],
                        kv_sems.at[slot, 1, i, j]).wait()
            k_all = kbuf[slot].reshape(C, hd)
            v_all = vbuf[slot].reshape(C, hd)
            s = jax.lax.dot_general(
                q_all, k_all.astype(jnp.float32),
                dimension_numbers=(((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32)
            if logit_cap > 0:
                s = logit_cap * jnp.tanh(s / logit_cap)
            if has_alibi:
                s = s + slope_rows * (
                    bi * blk + col_off -
                    cached_rows[:, None]).astype(jnp.float32)
            mask = jnp.logical_and(
                diag, bi * blk + col_off < cached_rows[:, None])
            if window > 0:
                # q position is cached (== kv_len - 1) per sequence.
                mask = jnp.logical_and(
                    mask,
                    bi * blk + col_off > cached_rows[:, None] - window)
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
            jnp.zeros((ROWS, hd), jnp.float32),
        )
        m_fin, l_fin, acc = jax.lax.fori_loop(0, num_blocks, body, init)

        # Fold the CURRENT token in register: one extra score column
        # per row against this program's freshly computed K/V rows.
        kexp = jnp.repeat(k.astype(jnp.float32), group,
                          axis=1).reshape(ROWS, hd)
        vexp = jnp.repeat(v.astype(jnp.float32), group,
                          axis=1).reshape(ROWS, hd)
        s_cur = jnp.sum(q_all * kexp, axis=-1, keepdims=True)
        if logit_cap > 0:
            s_cur = logit_cap * jnp.tanh(s_cur / logit_cap)
        # ALiBi distance is 0 for the current token; window always
        # admits it. Inactive slots mask to _MASK_VALUE.
        active_rows = jnp.concatenate([
            jnp.full((QH, ), base + i < num_seqs, jnp.bool_)
            for i in range(sb)
        ])[:, None]
        s_cur = jnp.where(active_rows, s_cur, _MASK_VALUE)
        m2 = jnp.maximum(m_fin, s_cur)
        alpha = jnp.exp(m_fin - m2)
        p_cur = jnp.where(active_rows, jnp.exp(s_cur - m2), 0.0)
        l2 = l_fin * alpha + p_cur
        acc2 = acc * alpha + p_cur * vexp
        if has_sinks:
            l2 = l2 + jnp.exp(jnp.tile(feat_val[1], (sb, ))[:, None] - m2)
        attn = (acc2 / jnp.maximum(l2, 1e-20)).astype(io_dtype)
        attn2d = attn.reshape(sb, Dq).astype(w_dtype)

        # ---- O-projection (double-buffered contraction tiles) -------
        acc_h = jnp.zeros((sb, H), jnp.float32)
        no = Dq // to

        def o_copy(t):
            return pltpu.make_async_copy(
                wo_hbm.at[pl.ds(t * to, to)], row_buf.at[t % 2],
                w_sems.at[1, t % 2])

        o_copy(0).start()
        for t in range(no):
            if t + 1 < no:
                o_copy(t + 1).start()
            o_copy(t).wait()
            acc_h = acc_h + jax.lax.dot_general(
                attn2d[:, t * to:(t + 1) * to], row_buf[t % 2],
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
        h1 = h0 + acc_h

        # ---- RMSNorm -> tile-fused gated MLP + residual -------------
        # gate/up/down consume ONE intermediate tile at a time; the
        # [sb, I] intermediate never exists outside this loop body.
        x2 = rms(h1, 1).astype(w_dtype)
        acc_mlp = jnp.zeros((sb, H), jnp.float32)
        ni = I // ti

        def mlp_copies(t):
            s = t % 2
            return (
                pltpu.make_async_copy(
                    wg_hbm.at[:, pl.ds(t * ti, ti)], wg_buf.at[s],
                    w_sems.at[2, s]),
                pltpu.make_async_copy(
                    wu_hbm.at[:, pl.ds(t * ti, ti)], wu_buf.at[s],
                    w_sems.at[3, s]),
                pltpu.make_async_copy(
                    wd_hbm.at[pl.ds(t * ti, ti)], wd_buf.at[s],
                    w_sems.at[4, s]),
            )

        for cp in mlp_copies(0):
            cp.start()
        for t in range(ni):
            if t + 1 < ni:
                # Prefetch the NEXT tile's gate/up/down trio into the
                # other slot while this tile's three matmuls run.
                for cp in mlp_copies(t + 1):
                    cp.start()
            s = t % 2
            cg, cu, cd = mlp_copies(t)
            cg.wait()
            cu.wait()
            g_t = jax.lax.dot_general(
                x2, wg_buf[s],
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            u_t = jax.lax.dot_general(
                x2, wu_buf[s],
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            gu_t = (jax.nn.silu(g_t) * u_t).astype(io_dtype)
            cd.wait()
            acc_mlp = acc_mlp + jax.lax.dot_general(
                gu_t.astype(w_dtype), wd_buf[s],
                dimension_numbers=(((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
        h2 = h1 + acc_mlp

        # ---- writeback (active rows only; inactive rows keep their
        # aliased input values) ---------------------------------------
        out_stage[...] = h2.astype(io_dtype)
        for i in range(sb):
            @pl.when(base + i < num_seqs)
            def _wb(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(q_starts[i], 1)],
                    out_sems.at[i]).start()
        for i in range(sb):
            @pl.when(base + i < num_seqs)
            def _wbw(i=i):
                pltpu.make_async_copy(
                    out_stage.at[pl.ds(i, 1)],
                    out_hbm.at[pl.ds(0, 1)], out_sems.at[i]).wait()


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "eps", "num_q_heads", "head_dim",
                     "interpret", "window", "logit_cap", "has_alibi",
                     "has_sinks"))
def fused_block_decode_pallas(
    hidden: jax.Array,  # [T_pad, H]
    k_pages: jax.Array,  # [L, N, KVH, PS, D] stacked cache (aliased)
    v_pages: jax.Array,
    wqkv: jax.Array,  # [H, Dq + 2*Dkv] re-laid fused projection
    wo: jax.Array,  # [Dq, H]
    w_gate: jax.Array,  # [H, I]
    w_up: jax.Array,  # [H, I]
    w_down: jax.Array,  # [I, H]
    ln_w: jax.Array,  # [2, H]: input_ln, post_ln
    rope: jax.Array,  # [2, T_pad, head_dim] f32: cos, sin
    feat: jax.Array,  # [2, QH] f32: ALiBi slopes, sink logits
    seq_info: jax.Array,  # [R, 4] int32
    num_seqs: jax.Array,  # [1] int32
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    layer: jax.Array,  # [1] int32
    *,
    sm_scale: float,
    eps: float,
    num_q_heads: int,
    head_dim: int,
    interpret: bool | None = None,
    window: int = 0,
    logit_cap: float = 0.0,
    has_alibi: bool = False,
    has_sinks: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused transformer-block decode layer; returns
    (hidden, k_pages, v_pages) with all three updated in place via
    input/output aliasing. Decode-only contract: every active seq_info
    row has q_len == 1 and kv_len counting this step's token."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    T_pad, H = hidden.shape
    L, N, KVH, PS, D = k_pages.shape
    assert D == head_dim, "lane-padded caches need the XLA path"
    QH = num_q_heads
    assert QH % KVH == 0
    group = QH // KVH
    Dq = QH * head_dim
    Dtot = Dq + 2 * KVH * head_dim
    I = w_gate.shape[1]
    R = seq_info.shape[0]
    pages_per_req = block_tables.shape[1]
    ppb = max(1, min(128 // PS, pages_per_req))
    while pages_per_req % ppb:
        ppb -= 1
    blk = ppb * PS

    sb = fused_block_group_size(QH, KVH, R)
    tq = weight_tile(Dtot)
    to = weight_tile(Dq)
    ti = weight_tile(I)
    grid = (pl.cdiv(R, sb), )

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, eps=eps, sb=sb, ppb=ppb,
        page_size=PS, group=group, tq=tq, to=to, ti=ti, window=window,
        logit_cap=logit_cap, has_alibi=has_alibi, has_sinks=has_sinks)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    vmem_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            any_spec,  # hidden
            any_spec,  # wqkv
            any_spec,  # wo
            any_spec,  # w_gate
            any_spec,  # w_up
            any_spec,  # w_down
            vmem_spec,  # ln_w
            any_spec,  # rope
            vmem_spec,  # feat
            any_spec,  # k_pages
            any_spec,  # v_pages
        ],
        out_specs=[any_spec, any_spec, any_spec],
        scratch_shapes=[
            pltpu.VMEM((sb, H), hidden.dtype),
            pltpu.VMEM((2, sb, head_dim), jnp.float32),
            # Weight streams carry TWO tile slots each (double
            # buffering): tile t+1 DMAs into slot (t+1)%2 while tile
            # t multiplies out of slot t%2.
            pltpu.VMEM((2, H, tq), wqkv.dtype),
            pltpu.VMEM((2, to, H), wo.dtype),
            pltpu.VMEM((2, H, ti), w_gate.dtype),
            pltpu.VMEM((2, H, ti), w_up.dtype),
            pltpu.VMEM((2, ti, H), w_down.dtype),
            pltpu.VMEM((2, sb, KVH, blk, D), k_pages.dtype),
            pltpu.VMEM((2, sb, KVH, blk, D), v_pages.dtype),
            pltpu.VMEM((KVH, PS, D), k_pages.dtype),
            pltpu.VMEM((KVH, PS, D), v_pages.dtype),
            pltpu.VMEM((sb, H), hidden.dtype),
            pltpu.SemaphoreType.DMA((sb, )),
            pltpu.SemaphoreType.DMA((2, sb)),
            pltpu.SemaphoreType.DMA((5, 2)),
            pltpu.SemaphoreType.DMA((2, 2, sb, ppb)),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.SemaphoreType.DMA((sb, )),
        ],
    )
    # Flat operand indices: 4 scalar-prefetch args, then hidden (4) ...
    # k_pages (13), v_pages (14) alias outputs 0, 1, 2.
    out, k2, v2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(hidden.shape, hidden.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        input_output_aliases={4: 0, 13: 1, 14: 2},
        interpret=interpret,
    )(seq_info, num_seqs, layer, block_tables, hidden, wqkv, wo,
      w_gate, w_up, w_down, ln_w, rope, feat, k_pages, v_pages)
    return out, k2, v2


def fused_block_decode_xla(
    hidden, k_pages, v_pages, wqkv, wo, w_gate, w_up, w_down, ln_w,
    rope, feat, seq_info, num_seqs, block_tables, layer, *, sm_scale,
    eps, num_q_heads, head_dim, window=0, logit_cap=0.0,
    has_alibi=False, has_sinks=False,
):
    """XLA-composed correctness reference / non-Pallas fallback for the
    fused decode block: the same math built from the reference ops (the
    flat-scatter KV write and the XLA ragged attention), driven purely
    by seq_info. Used by the parity suite; the serving path only
    dispatches the fused block on the Pallas backend."""
    from vllm_distributed_tpu.models.common import rms_norm
    from vllm_distributed_tpu.ops.attention import (_scatter_kv_flat,
                                                    ragged_paged_attention)
    L, N, KVH, PS, D = k_pages.shape
    QH = num_q_heads
    R = seq_info.shape[0]
    io_dtype = hidden.dtype
    active = jnp.arange(R, dtype=jnp.int32) < num_seqs[0]
    kv_len = seq_info[:, 2]
    row = seq_info[:, 3]
    q_start = seq_info[:, 0]
    pos = jnp.maximum(kv_len - 1, 0)

    x = hidden[q_start]  # [R, H]
    xn = rms_norm(x, ln_w[0], eps)
    qkv = xn @ wqkv
    Dq = QH * head_dim
    Dkv = KVH * head_dim
    q = qkv[:, :Dq].reshape(R, QH, head_dim)
    k = qkv[:, Dq:Dq + Dkv].reshape(R, KVH, head_dim)
    v = qkv[:, Dq + Dkv:].reshape(R, KVH, head_dim)

    from vllm_distributed_tpu.models.common import apply_rope_single
    cos = rope[0][q_start]
    sin = rope[1][q_start]
    q = apply_rope_single(q.astype(jnp.float32), cos, sin).astype(io_dtype)
    k = apply_rope_single(k.astype(jnp.float32), cos, sin).astype(io_dtype)

    page = jnp.take_along_axis(block_tables[row],
                               (pos // PS)[:, None], axis=1)[:, 0]
    slot = jnp.where(active, page * PS + pos % PS, -1)
    k_pages, v_pages = _scatter_kv_flat(k_pages, v_pages, k, v, slot,
                                        layer, PS)

    slopes = tuple(
        float(s) for s in jax.device_get(feat[0])) if has_alibi else None
    sinks = feat[1].astype(jnp.float32) if has_sinks else None
    attn = ragged_paged_attention(
        q, k_pages[layer[0]], v_pages[layer[0]], block_tables, row, pos,
        sm_scale=sm_scale, window=window, logit_cap=logit_cap,
        alibi_slopes=slopes, sinks=sinks)
    attn = jnp.where(active[:, None, None], attn, 0)

    h1 = x.astype(jnp.float32) + (attn.reshape(R, Dq) @ wo).astype(
        jnp.float32)
    h1 = h1.astype(io_dtype)
    x2 = rms_norm(h1, ln_w[1], eps)
    gu = jax.nn.silu(x2 @ w_gate) * (x2 @ w_up)
    h2 = h1 + (gu @ w_down)

    hidden = hidden.at[jnp.where(active, q_start,
                                 hidden.shape[0])].set(h2, mode="drop")
    return hidden, k_pages, v_pages
