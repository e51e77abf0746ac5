"""Multi-head Latent Attention (MLA) ops over a paged latent cache.

TPU-native equivalent of the reference's MLA backend family
(vllm/v1/attention/backends/mla/common.py, csrc/attention/mla/): the KV
cache stores ONE compressed row per token — the kv_lora_rank latent
``kv_c`` concatenated with the shared rope key ``k_pe`` — instead of
per-head K and V, cutting KV memory by ~an order of magnitude for
DeepSeek-shaped models.

Two factorisations of the one attention, as the reference keeps them
(common.py's separate prefill and decode paths), picked by what a step
brings (``latent_attention_values`` has the arithmetic and the rule):

* **Absorbed**, the reference's "data-movement friendly" form
  (common.py:96-120 `_forward_decode`): W_UK is absorbed into the query
  (`ql = q_nope · W_UK`) so attention is MQA with qk dim = Lkv + R and
  v dim = Lkv; W_UV is applied to the output afterwards. Every row of a
  decode program, of the XLA scan (the CPU path and the kernels'
  correctness reference) and of TPLA, and the single-token rows of a
  chunk program.
* **Multi-head**, the "compute friendly" form: K and V up-projected from
  a sequence's latent rows once a step and attended by plain causal
  flash attention at (P + R) and V lanes a head. The sequences that
  bring more than one query row to a chunk program on the Pallas
  backend.

The latent cache, its page layout and the write are the same under both.

Sharding — two layouts, selected at load (models/loader.py sets
``arch.tpla_shards``):

* **Replicated** (``VDT_TPLA=0`` or TP == 1): kv_c/k_pe are shared by
  all heads (that is the point of MLA), so each TP rank attends with
  its local head shard against the full cache, and GSPMD needs no
  collective inside the op. This is the pre-TPLA layout, byte-identical
  under the kill switch.
* **TPLA** (PAPERS.md "TPLA: Tensor Parallel Latent Attention"): the
  latent dimension of every cache row shards over the model (TP) axis —
  rank r stores lanes [r*Lkv/TP, (r+1)*Lkv/TP) of kv_c in the "c" pages
  while the rope key k_pe lives in a small replicated "pe" sidecar (the
  paper's layout: latent sharded, rope broadcast). The per-rank latent
  pool is ~1/TP the bytes, so max concurrent MLA requests scales
  ~TP-fold at fixed HBM. Attention runs EXACTLY (token-identical to the
  replicated layout): inside a shard_map each rank computes partial
  scores ql_shard·kv_c_shard per page block, a psum over the model axis
  plus the locally-computed q_pe·k_pe reassembles the full scores, the
  per-block (m, l, acc) state merges through the cascade emit-state
  machinery (ops/attention.merge_attention_states) with the value
  accumulator carrying only the rank's latent slice, and the absorbed
  W_UV output projection contracts each rank's slice with its W_UV
  shard — that final [T, N, V] combine is the layer's one reduced
  collective and rides the quantized plane under VDT_QCOMM_PATHS
  "tpla" (parallel/collectives.py). The score psum itself stays exact
  (lax.psum): pre-softmax logits are the one tensor a block-scaled
  round-trip can visibly move.

Pages still shard over the token-parallel axis like the standard cache
(not yet wired: the loader rejects MLA x TKNP). A TPLA-aware Pallas
latent kernel needs the score psum between its two MXU matmuls
(a two-kernel split); until then the TPLA path runs this module's
blockwise scan on every backend and the Pallas kernel keeps serving the
replicated layout.
"""

import functools

import jax
import jax.numpy as jnp

from vllm_distributed_tpu.ops.attention import (_MASK_VALUE,
                                                _pad_last_dim,
                                                merge_attention_states)
from vllm_distributed_tpu.parallel.mesh import shard_map


def latent_storage_dim(kv_lora_rank: int, rope_dim: int) -> int:
    """Last-dim storage size for the latent cache: Lkv + R padded to the
    128-lane tile on TPU (see ops/attention.storage_head_dim)."""
    c = kv_lora_rank + rope_dim
    if jax.default_backend() == "tpu":
        return -(-c // 128) * 128
    return c


def latent_shard_dim(kv_lora_rank: int, shards: int) -> int:
    """Per-rank storage lanes of one TPLA latent shard: Lkv/shards,
    padded to the 128-lane tile on TPU so each rank's slice DMAs whole
    tiles. The global "c" last dim is ``shards *`` this."""
    assert kv_lora_rank % shards == 0, (kv_lora_rank, shards)
    return latent_storage_dim(kv_lora_rank // shards, 0)


def tpla_applicable(kv_lora_rank: int, shards: int) -> bool:
    """Can the latent dim split evenly over ``shards`` ranks? The loader
    falls back to the replicated layout (with a log) when not."""
    return shards > 1 and kv_lora_rank % shards == 0


def write_latent_cache(
    c_all: jax.Array,  # [L, num_pages, page_size, Cs] stacked cache
    c_new: jax.Array,  # [T, Lkv + R] new latent rows (kv_c ++ k_pe)
    batch,  # AttentionBatch
    layer: jax.Array,  # [1] int32
) -> jax.Array:
    """Scatter the step's latent rows into layer ``layer`` of the stacked
    cache (XLA contiguous-row scatter; slots < 0 drop). Equivalent of
    the reference's concat_and_cache_mla (csrc/cache_kernels.cu)."""
    L, NP, PS, Cs = c_all.shape
    c_new = _pad_last_dim(c_new, Cs)
    slot = batch.slot_mapping
    total = L * NP * PS
    rows = layer[0] * (NP * PS) + slot
    rows = jnp.where(slot < 0, total, rows)
    flat = c_all.reshape(total, Cs)
    flat = flat.at[rows].set(c_new.astype(flat.dtype), mode="drop")
    return flat.reshape(c_all.shape)


def ragged_latent_attention(
    ql: jax.Array,  # [T, N, Lkv] absorbed no-rope queries (q_nope · W_UK)
    q_pe: jax.Array,  # [T, N, R] rope queries
    c_pages: jax.Array,  # [num_pages, page_size, Cs] one layer's cache
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    req_idx: jax.Array,  # [T] int32 owning request row per token
    q_pos: jax.Array,  # [T] int32 absolute position per query token
    *,
    sm_scale: float,
    kv_lora_rank: int,
    rope_dim: int,
) -> jax.Array:  # [T, N, Lkv] latent-space attention output
    """Unified ragged MQA over the latent cache: token t attends to
    latent rows 0..q_pos[t] of its request. Scores are
    ql·kv_c + q_pe·k_pe (the absorbed form); the value accumulated is
    kv_c itself, so the caller applies W_UV to the [T, N, Lkv] result.
    Online-softmax scan over pages, like ops/attention.
    ragged_paged_attention."""
    T, N, Lkv = ql.shape
    PS = c_pages.shape[1]
    pages_per_req = block_tables.shape[1]
    # [T, N, Lkv + R] combined queries, pre-scaled.
    qc = jnp.concatenate([ql.astype(jnp.float32),
                          q_pe.astype(jnp.float32)], axis=-1) * sm_scale
    token_pages = block_tables[req_idx]  # [T, pages_per_req]
    kdim = kv_lora_rank + rope_dim

    def body(carry, page_i):
        m, l, acc = carry  # [T,N,1], [T,N,1], [T,N,Lkv]
        page_ids = token_pages[:, page_i]  # [T]
        blk = c_pages[page_ids, :, :kdim].astype(jnp.float32)  # [T,PS,kd]
        scores = jnp.einsum("tnc,tpc->tnp", qc, blk)  # [T, N, PS]
        kv_pos = page_i * PS + jnp.arange(PS, dtype=jnp.int32)
        valid = kv_pos[None, :] <= q_pos[:, None]  # [T, PS] causal
        scores = jnp.where(valid[:, None, :], scores, _MASK_VALUE)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("tnp,tpl->tnl", p,
                                           blk[..., :Lkv])
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((T, N, 1), _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((T, N, 1), jnp.float32)
    acc0 = jnp.zeros((T, N, Lkv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        jnp.arange(pages_per_req, dtype=jnp.int32))
    out = acc / jnp.maximum(l, 1e-20)
    return out.astype(ql.dtype)


def write_latent_cache_tpla(
    c_all: jax.Array,  # [L, NP, PS, shards * shard_pad] latent-sharded
    pe_all: jax.Array,  # [L, NP, PS, R_pad] replicated rope sidecar
    kv_c: jax.Array,  # [T, Lkv] new latent rows
    k_pe: jax.Array,  # [T, R] new rope keys
    batch,  # AttentionBatch
    layer: jax.Array,  # [1] int32
    *,
    shards: int,
    kv_lora_rank: int,
) -> tuple[jax.Array, jax.Array]:
    """TPLA cache write: scatter each rank's latent slice into its "c"
    shard and the shared rope key into the replicated "pe" sidecar. The
    new rows are re-laid out [T, shards, Lkv/shards] -> per-shard lane
    padding -> [T, shards * shard_pad], so the (elementwise on the lane
    dim) scatter writes every rank's slice locally — GSPMD moves no
    data."""
    shard_pad = c_all.shape[-1] // shards
    lkv_local = kv_lora_rank // shards
    T = kv_c.shape[0]
    rows = kv_c.reshape(T, shards, lkv_local)
    if shard_pad > lkv_local:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, shard_pad - lkv_local)))
    c_new = rows.reshape(T, shards * shard_pad)
    c_all = write_latent_cache(c_all, c_new, batch, layer)
    pe_all = write_latent_cache(pe_all, k_pe, batch, layer)
    return c_all, pe_all


def tpla_latent_attention(
    ql: jax.Array,  # [T, N, Lkv] absorbed queries, latent-dim sharded
    q_pe: jax.Array,  # [T, N, R] rope queries, replicated
    c_all: jax.Array,  # [L, NP, PS, shards * shard_pad] latent-sharded
    pe_all: jax.Array,  # [L, NP, PS, R_pad] replicated rope sidecar
    batch,  # AttentionBatch
    w_uv: jax.Array,  # [Lkv, N, V] this layer's W_UV, latent-dim sharded
    *,
    sm_scale: float,
    kv_lora_rank: int,
    rope_dim: int,
    shards: int,
    layer: jax.Array,  # [1] int32
) -> jax.Array:  # [T, N, V] replicated value-space output
    """TPLA ragged latent attention + absorbed W_UV, exact (see module
    docstring): per-block partial scores psum over the model axis, the
    rope term computed locally from the replicated sidecar, online
    softmax carried through merge_attention_states, per-rank latent
    value slices contracted against the rank's W_UV shard and combined
    with ONE psum (quantized plane path "tpla")."""
    from jax.sharding import PartitionSpec as P

    from vllm_distributed_tpu.config import MESH_AXIS_MODEL
    from vllm_distributed_tpu.parallel import collectives
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    if getattr(batch, "tknp", None) is not None:
        raise NotImplementedError(
            "MLA under token parallelism is not wired (per-rank latent "
            "page pools); models/loader.py rejects the combination")
    lkv_local = kv_lora_rank // shards
    shard_pad = c_all.shape[-1] // shards
    PS = c_all.shape[2]
    pages_per_req = batch.block_tables.shape[1]

    def rank_fn(ql_, qpe_, c_, pe_, bt_, req_idx_, q_pos_, wuv_, layer_):
        # ql_ [T, N, lkv_local]; c_ [L, NP, PS, shard_pad] (this rank's
        # latent lanes); pe_ replicated; wuv_ [lkv_local, N, V].
        c_layer = c_[layer_[0]]
        pe_layer = pe_[layer_[0]]
        ql32 = ql_.astype(jnp.float32) * sm_scale
        qpe32 = qpe_.astype(jnp.float32) * sm_scale
        token_pages = bt_[req_idx_]  # [T, pages_per_req]
        T, N = ql_.shape[0], ql_.shape[1]

        def body(carry, page_i):
            page_ids = token_pages[:, page_i]  # [T]
            c_blk = c_layer[page_ids, :, :lkv_local].astype(jnp.float32)
            pe_blk = pe_layer[page_ids, :, :rope_dim].astype(jnp.float32)
            # Partial scores from this rank's latent slice; the psum
            # over the model axis reassembles the full ql·kv_c term so
            # every rank softmaxes the EXACT scores (identical m/l).
            part = jnp.einsum("tnc,tpc->tnp", ql32, c_blk)
            s = jax.lax.psum(part, MESH_AXIS_MODEL)
            s = s + jnp.einsum("tnr,tpr->tnp", qpe32, pe_blk)
            kv_pos = page_i * PS + jnp.arange(PS, dtype=jnp.int32)
            valid = kv_pos[None, :] <= q_pos_[:, None]  # [T, PS] causal
            s = jnp.where(valid[:, None, :], s, _MASK_VALUE)
            # Per-block dense state, folded into the carry through the
            # cascade m/l emit-state merge (a fully-masked block's
            # m = _MASK_VALUE, so its alpha underflows to exactly 0).
            m_b = s.max(axis=-1, keepdims=True)
            p = jnp.exp(s - m_b)
            l_b = p.sum(axis=-1, keepdims=True)
            acc_b = jnp.einsum("tnp,tpl->tnl", p, c_blk)
            return merge_attention_states(carry, (m_b, l_b, acc_b)), None

        init = (jnp.full((T, N, 1), _MASK_VALUE, jnp.float32),
                jnp.zeros((T, N, 1), jnp.float32),
                jnp.zeros((T, N, lkv_local), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            body, init, jnp.arange(pages_per_req, dtype=jnp.int32))
        out_r = acc / jnp.maximum(l, 1e-20)  # [T, N, lkv_local]
        # Absorbed W_UV on the rank's slice; the combine is the layer's
        # one reduced collective — quantized plane path "tpla".
        v_r = jnp.einsum("tnk,knv->tnv", out_r,
                         wuv_.astype(jnp.float32))
        return collectives.psum(v_r, MESH_AXIS_MODEL, path="tpla")

    M = MESH_AXIS_MODEL
    out = shard_map(
        rank_fn, mesh=mesh_state.get_global_mesh(),
        in_specs=(P(None, None, M), P(), P(None, None, None, M), P(),
                  P(), P(), P(), P(M, None, None), P()),
        out_specs=P(), check_vma=False)(
            ql, q_pe, c_all, pe_all, batch.block_tables, batch.req_idx,
            batch.positions, w_uv, layer)
    return out.astype(ql.dtype)


def _on_latent_kernel(c_all: jax.Array, batch) -> bool:
    """Does this batch run on the Pallas latent kernels? They take the
    stacked cache and the runner's per-sequence runs; everything else
    (the CPU, in-jit batches without runs) takes the XLA scan."""
    from vllm_distributed_tpu.ops.attention import \
        resolve_attention_backend
    return (resolve_attention_backend() == "pallas"
            and getattr(batch, "seq_info", None) is not None
            and c_all.ndim == 4)


def _over_local_heads(fn, *head_sharded):
    """Run ``fn`` on each tensor-parallel rank's slice of the head axis
    (axis 1 of every argument and of the result). The latent cache is
    MQA-shared and replicated, so a rank attends with its heads against
    the whole cache and no collective is needed."""
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    if not (mesh_state.has_global_mesh() and mesh_state.tp_size() > 1):
        return fn(*head_sharded)
    from jax.sharding import PartitionSpec as P

    from vllm_distributed_tpu.config import MESH_AXIS_MODEL
    head_spec = P(None, MESH_AXIS_MODEL, None)
    return shard_map(
        fn, mesh=mesh_state.get_global_mesh(),
        in_specs=(head_spec, ) * len(head_sharded),
        out_specs=head_spec, check_vma=False)(*head_sharded)


def _absorbed_kernel(qc, c_all, seq_info, batch, layer, *, sm_scale,
                     max_q, kv_lora_rank, rope_dim):
    """The latent kernel on absorbed queries ``qc`` [rows, N, Lkv + R]
    laid out as ``seq_info`` says; [rows, N, Lkv], whatever a row past
    its sequence's q_len holds."""
    from vllm_distributed_tpu.ops.pallas_mla import \
        ragged_latent_attention_pallas
    out = ragged_latent_attention_pallas(
        _pad_last_dim(qc, c_all.shape[-1]), c_all, seq_info,
        batch.num_seqs, batch.block_tables, layer, sm_scale=sm_scale,
        max_q=max_q, kv_lora_rank=kv_lora_rank, rope_dim=rope_dim)
    return out[..., :kv_lora_rank]


def latent_attention(q_absorbed, q_pe, c_all, batch, *, sm_scale,
                     kv_lora_rank, rope_dim, layer=None):
    """Model-facing entry of the absorbed form: select the layer's page
    slab and run the ragged latent attention (the MLA analogue of
    ops/attention.paged_attention). Dispatches to the Pallas latent
    kernel (ops/pallas_mla.py) on the pallas backend; the XLA scan is
    the correctness reference and the CPU path. Token parallelism is
    rejected upstream by the loader."""
    if getattr(batch, "tknp", None) is not None:
        raise NotImplementedError(
            "MLA under token parallelism (per-rank latent page pools "
            "are not wired; models/loader.py rejects the combination "
            "at admission — this trace-time guard is the backstop)")
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    if _on_latent_kernel(c_all, batch):

        def call(q_):
            out = _absorbed_kernel(
                q_, c_all, batch.seq_info, batch, layer,
                sm_scale=sm_scale, max_q=batch.max_q,
                kv_lora_rank=kv_lora_rank, rope_dim=rope_dim)
            # Rows the kernel never writes are uninitialized HBM; zero
            # them (padding tokens carry slot -1).
            valid = (batch.slot_mapping >= 0)[:, None, None]
            return jnp.where(valid, out, 0)

        return _over_local_heads(
            call, jnp.concatenate([q_absorbed, q_pe], axis=-1))
    c_layer = c_all[layer[0]] if c_all.ndim == 4 else c_all
    return ragged_latent_attention(
        q_absorbed, q_pe, c_layer, batch.block_tables, batch.req_idx,
        batch.positions, sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
        rope_dim=rope_dim)


def takes_multi_head_form(q_len):
    """Which sequences of a chunk-bearing step on the latent kernels
    attend in the multi-head form: those with more than one query row
    (``latent_attention_values`` has the arithmetic). One line for the
    traced program and for the runner's counter of the rows it served
    (a host integer, a NumPy array and a traced array alike)."""
    return q_len > 1


def latent_attention_values(q_nope, q_pe, c_all, batch, w_uk, w_uv, *,
                            sm_scale, kv_lora_rank, rope_dim, layer):
    """One layer's attention over the latent cache from the queries as
    projected (``q_nope`` [T, N, P], ``q_pe`` [T, N, R], rope applied)
    to the heads' values [T, N, V] in float32, ready for ``wo``.

    One attention over one cache in two factorisations, and what picks
    between them is the ratio of queries to context:

    * **Absorbed** (``ql = q_nope . w_uk``, MQA over the stored rows at
      Lkv + R lanes, ``. w_uv`` after): a decoding row reads each
      576-lane row once for every head. 2 (Lkv + R + Lkv) operations a
      (query, key) pair and a head, nothing a context row.
    * **Multi-head** (K and V up-projected from the context once, plain
      causal flash attention at P + R and V lanes a head): 2 (P + R + V)
      a pair and a head, and 2 Lkv (P + V) a context row and a head for
      the up-projection. At DeepSeek-V3's widths that is 768 against
      2 176 a pair and 327 680 a context row, so it wins from about
      233 query rows a sequence in operations alone, and earlier in
      time: the absorbed kernel's q tiles hold a sequence's rows times
      all its heads, so a chunk's tiles are small and the kernel ran a
      2 048-row chunk at a sixth of its roofline (PERF.md, Findings
      PR 36, has the chip's readings).

    A program that can hold a chunk (static ``batch.max_q`` > 1, on the
    latent kernels) gives every sequence with more than one query row
    the multi-head form and every single-row sequence the absorbed one,
    each form's projections over its own rows only
    (``_mixed_latent_attention``). A pure decode program, the XLA scan
    and TPLA keep the absorbed form for every row."""
    if _on_latent_kernel(c_all, batch) and batch.max_q > 1:
        if getattr(batch, "tknp", None) is not None:
            raise NotImplementedError(
                "MLA under token parallelism is not wired")

        def call(qn_, qp_, wuk_, wuv_):
            return _mixed_latent_attention(
                qn_, qp_, c_all, batch, wuk_, wuv_, layer,
                sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
                rope_dim=rope_dim)

        # [Lkv, N, *] -> heads on axis 1, like the queries.
        return _over_local_heads(call, q_nope, q_pe, w_uk, w_uv)
    ql = jnp.einsum("tnp,knp->tnk", q_nope.astype(jnp.float32),
                    w_uk.astype(jnp.float32))
    out_l = latent_attention(
        ql.astype(q_nope.dtype), q_pe, c_all, batch, sm_scale=sm_scale,
        kv_lora_rank=kv_lora_rank, rope_dim=rope_dim, layer=layer)
    return jnp.einsum("tnk,knv->tnv", out_l.astype(jnp.float32),
                      w_uv.astype(jnp.float32))


# Jitted so that a model's like layers share one trace and one lowered
# function: six copies of it doubled the time a chunk program takes to
# lower, which every start of a server pays for every bucket.
@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "kv_lora_rank", "rope_dim"))
def _mixed_latent_attention(q_nope, q_pe, c_all, batch, w_uk, w_uv, layer,
                            *, sm_scale, kv_lora_rank, rope_dim):
    """A chunk-bearing step on the latent kernels (see
    ``latent_attention_values``): [T, N, V] float32, zero on padding
    rows (slot -1).

    Decoding rows (q_len == 1, at most one a sequence: ``seq_info``'s
    row count, max_num_seqs) are gathered by their q_start, absorbed,
    attended by ``mla_latent_pages`` at one q tile a sequence, taken
    through ``w_uv`` and scattered back. Sequences with q_len > 1 are
    walked one at a time, so one sequence's K and V are live: its
    context rows (earlier chunks and this one, as ``write_latent_cache``
    left them in its pages) are gathered and up-projected a block at a
    time, only as far as kv_len reaches, and ``mla_latent_prefill``
    attends the chunk against them, reading its query rows from the
    step's rows and writing its output among them in place."""
    from vllm_distributed_tpu.ops.pallas_mla import (
        latent_prefill_attention_pallas, prefill_blocks)
    T, N, Pn = q_nope.shape
    Lkv, V = kv_lora_rank, w_uv.shape[-1]
    dtype = q_nope.dtype
    seq_info = batch.seq_info
    num_rows = seq_info.shape[0]
    q_start, q_len, kv_len = seq_info[:, 0], seq_info[:, 1], seq_info[:, 2]
    live = jnp.arange(num_rows, dtype=jnp.int32) < batch.num_seqs[0]
    in_chunk = live & takes_multi_head_form(q_len)
    decoding = live & jnp.logical_not(in_chunk)

    # --- The absorbed form over the decoding rows.
    src = jnp.where(decoding, q_start, 0)
    ql = jnp.einsum("tnp,knp->tnk", q_nope[src].astype(jnp.float32),
                    w_uk.astype(jnp.float32))
    row_info = jnp.stack(
        [jnp.arange(num_rows, dtype=jnp.int32),
         decoding.astype(jnp.int32), kv_len, seq_info[:, 3]], axis=1)
    out_l = _absorbed_kernel(
        jnp.concatenate([ql.astype(dtype), q_pe[src]], axis=-1), c_all,
        row_info, batch, layer, sm_scale=sm_scale, max_q=1,
        kv_lora_rank=Lkv, rope_dim=rope_dim)
    # A sequence the kernel skips leaves its row uninitialized.
    out_l = jnp.where(decoding[:, None, None], out_l, 0)
    v_dec = jnp.einsum("tnk,knv->tnv", out_l.astype(jnp.float32),
                       w_uv.astype(jnp.float32))

    # --- The multi-head form over the chunk sequences.
    PS = c_all.shape[2]
    bq, bk, S = prefill_blocks(batch.max_q,
                               batch.block_tables.shape[1] * PS)
    # Context rows up-projected at a time: whole kv blocks of whole
    # pages, about a thousand rows.
    cb = bk * max(1, min(1024, S) // bk)
    while S % cb:
        cb -= bk
    assert cb % PS == 0, (cb, PS)
    tables = _pad_last_dim(batch.block_tables, S // PS)
    # The kernel reads a head's block as a window of a row's lanes, in
    # whole 128-lane tiles: the rope lanes and V's are padded with
    # zeros, V's in the weights (a row of V at 192 lanes takes 256 in
    # device memory anyway). Queries, K, V and the output are
    # two-dimensional, a row's heads side by side, as the matrix
    # products leave them; a q tile of room follows the step's rows.
    Rp, Vp = -(-rope_dim // 128) * 128, -(-V // 128) * 128
    w_k = w_uk.astype(dtype).reshape(Lkv, N * Pn)
    w_v = _pad_last_dim(w_uv.astype(dtype), Vp).reshape(Lkv, N * Vp)
    qn = jnp.pad(q_nope.reshape(T, N * Pn), ((0, bq), (0, 0)))
    qp = jnp.pad(_pad_last_dim(q_pe, Rp).reshape(T, N * Rp),
                 ((0, bq), (0, 0)))
    chunk_order = jnp.argsort(jnp.logical_not(in_chunk), stable=True)

    def one_sequence(i, carry):
        out, k_nope, k_pe, v = carry
        qs, qn_rows, kvn, row = (seq_info[chunk_order[i], j]
                                 for j in range(4))

        def up_project(b, kv):
            k_nope, k_pe, v = kv
            pages = jax.lax.dynamic_slice(tables[row], (b * (cb // PS), ),
                                          (cb // PS, ))
            rows = c_all[layer[0], pages].reshape(cb, -1).astype(dtype)
            latent = rows[:, :Lkv]
            # bf16 operands, float32 accumulation.
            kb = jnp.dot(latent, w_k, preferred_element_type=jnp.float32)
            vb = jnp.dot(latent, w_v, preferred_element_type=jnp.float32)
            at = b * cb
            return (
                jax.lax.dynamic_update_slice(k_nope, kb.astype(dtype),
                                             (at, 0)),
                jax.lax.dynamic_update_slice(
                    k_pe, _pad_last_dim(rows[:, Lkv:Lkv + rope_dim], Rp),
                    (at, 0)),
                jax.lax.dynamic_update_slice(v, vb.astype(dtype), (at, 0)))

        k_nope, k_pe, v = jax.lax.fori_loop(
            0, (kvn + cb - 1) // cb, up_project, (k_nope, k_pe, v))
        out = latent_prefill_attention_pallas(
            qn, qp, k_nope, k_pe, v, out, jnp.stack([qs, qn_rows, kvn]),
            num_heads=N, max_q=batch.max_q, sm_scale=sm_scale)
        return out, k_nope, k_pe, v

    # Nothing is filled beforehand: the kernel reads K and V no further
    # than the block that kv_len ends in, which the walk has written,
    # and the rows of the output that no sequence writes are padding.
    out, _, _, _ = jax.lax.fori_loop(
        0, in_chunk.sum(), one_sequence,
        (jax.lax.empty((T + bq, N * Vp), dtype),
         jax.lax.empty((S, N * Pn), dtype), jax.lax.empty((S, Rp), dtype),
         jax.lax.empty((S, N * Vp), dtype)))
    # The decoding rows join in the program's dtype, as the chunks' rows
    # are; the widening is the caller's first elementwise pass.
    out = out[:T].reshape(T, N, Vp)[..., :V]
    out = out.at[jnp.where(decoding, q_start, T)].set(
        v_dec.astype(dtype), mode="drop")
    valid = (batch.slot_mapping >= 0)[:, None, None]
    return jnp.where(valid, out, 0).astype(jnp.float32)
