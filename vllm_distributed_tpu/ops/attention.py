"""Ragged paged attention over a paged KV cache — XLA implementation.

This is the TPU-native equivalent of the reference's unified attention path
(vllm/attention/layer.py:398 ``unified_attention`` dispatching to the CUDA
paged-attention kernels in csrc/attention/ and, on its TPU backend, to
torch.ops.xla.ragged_paged_attention — v1/attention/backends/pallas.py:232).

Two ops:

* ``write_kv_pages`` — scatter newly-computed K/V for a flat batch of
  tokens into the paged cache via a precomputed slot mapping (equivalent of
  csrc/cache_kernels.cu reshape_and_cache, pallas_kv_cache_update.py).
  On TPU this lowers to a dynamic-update-scatter XLA handles well.

* ``ragged_paged_attention`` — token-centric unified prefill/decode
  attention: every query token attends to its request's pages up to its own
  position. Implemented as a lax.scan over page indices with an online
  (flash-style) softmax so peak memory is O(T * page_size) instead of
  O(T * max_model_len). Handles GQA, mixed prefill+decode in one batch,
  and same-step prefix sharing (KV must be written before calling).

A Pallas kernel (ops/pallas/) replaces the scan for performance; this XLA
version is the correctness reference and the CPU/interpret fallback
(selected via VDT_ATTENTION_BACKEND).
"""

from functools import partial

import jax
import jax.numpy as jnp

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.parallel.mesh import shard_map

# Set to a large negative number rather than -inf so fully-masked rows
# produce 0-weight rows instead of NaNs.
_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)

# fp8 cache payload dtypes (--kv-cache-dtype): these route the XLA
# attention/write paths (Pallas fp8 dequant is a follow-up).
_FP8_DTYPES = (jnp.float8_e4m3fn, jnp.float8_e5m2)


def storage_head_dim(head_dim: int) -> int:
    """Head dim used for KV-cache storage: padded to the 128-lane tile on
    TPU (reference: v1/attention/backends/pallas.py:25 pads head size to
    128; Mosaic cannot DMA sub-tile lane slices). Zero-padding K and V
    leaves attention numerics unchanged."""
    if jax.default_backend() == "tpu":
        return -(-head_dim // 128) * 128
    return head_dim


def _pad_last_dim(x: jax.Array, target: int) -> jax.Array:
    if x.shape[-1] == target:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, target - x.shape[-1])]
    return jnp.pad(x, pad)


def build_head_feat(num_q_heads: int, alibi_slopes, sinks) -> jax.Array:
    """The mega-kernel's per-head feature sidecar: [2, QH] f32 with
    ALiBi slopes in row 0 and attention-sink logits in row 1 (zeros for
    disabled features — the has_alibi/has_sinks statics gate the math,
    so the zero rows are never read). An ARRAY rather than statics so
    learned sinks stay traced and TP shard_maps slice per-rank head
    ranges naturally."""
    zeros = jnp.zeros((num_q_heads, ), jnp.float32)
    return jnp.stack([
        (jnp.asarray(alibi_slopes, jnp.float32)
         if alibi_slopes is not None else zeros),
        (sinks.astype(jnp.float32) if sinks is not None else zeros),
    ])


def write_kv_pages(
    k_pages: jax.Array,  # [num_pages, num_kv_heads, page_size, head_dim]
    v_pages: jax.Array,  # [num_pages, num_kv_heads, page_size, head_dim]
    k_new: jax.Array,  # [T, num_kv_heads, head_dim]
    v_new: jax.Array,  # [T, num_kv_heads, head_dim]
    slot_mapping: jax.Array,  # [T] int32 flat slot = page*page_size + off
) -> tuple[jax.Array, jax.Array]:
    """Scatter new K/V rows into a single-layer paged cache (XLA path).

    The cache page layout is head-major [page, kv_head, page_size, head_dim]
    so the Pallas attention kernel can DMA each page directly into
    head-leading VMEM blocks (Mosaic wants batch/head dims leading). The
    scatter is expressed as contiguous [1, head_dim] row updates on the
    flattened cache — the only scatter shape XLA lowers efficiently.
    Padded tokens must carry an out-of-range slot (e.g. -1): scatter mode
    'drop' discards them.
    """
    num_pages, num_kv_heads, page_size, head_dim = k_pages.shape
    T = k_new.shape[0]
    k_new = _pad_last_dim(k_new, head_dim)
    v_new = _pad_last_dim(v_new, head_dim)
    if k_pages.dtype in _FP8_DTYPES:
        # Saturate like the reference fp8 cache kernels: a bare astype
        # maps overflow to NaN, and one NaN row poisons its page.
        lim = float(jnp.finfo(k_pages.dtype).max)
        k_new = jnp.clip(k_new.astype(jnp.float32), -lim, lim)
        v_new = jnp.clip(v_new.astype(jnp.float32), -lim, lim)
    page = slot_mapping // page_size
    off = slot_mapping % page_size
    # Flat row per (token, head): ((page * KVH) + h) * PS + off.
    rows = ((page[:, None] * num_kv_heads +
             jnp.arange(num_kv_heads, dtype=jnp.int32)[None, :]) *
            page_size + off[:, None])
    total = num_pages * num_kv_heads * page_size
    rows = jnp.where(slot_mapping[:, None] < 0, total, rows).reshape(-1)
    k_flat = k_pages.reshape(total, head_dim)
    v_flat = v_pages.reshape(total, head_dim)
    k_flat = k_flat.at[rows].set(
        k_new.reshape(T * num_kv_heads, head_dim).astype(k_flat.dtype),
        mode="drop")
    v_flat = v_flat.at[rows].set(
        v_new.reshape(T * num_kv_heads, head_dim).astype(v_flat.dtype),
        mode="drop")
    return (k_flat.reshape(k_pages.shape), v_flat.reshape(v_pages.shape))


@partial(jax.jit, static_argnames=("sm_scale", "window", "logit_cap",
                                   "alibi_slopes"))
def ragged_paged_attention(
    q: jax.Array,  # [T, num_q_heads, head_dim]
    k_pages: jax.Array,  # [num_pages, num_kv_heads, page_size, head_dim]
    v_pages: jax.Array,  # [num_pages, num_kv_heads, page_size, head_dim]
    block_tables: jax.Array,  # [max_reqs, pages_per_req] int32
    req_idx: jax.Array,  # [T] int32: owning request row per token
    q_pos: jax.Array,  # [T] int32: absolute position of each query token
    *,
    sm_scale: float,
    window: int = 0,  # sliding window size; 0 = full causal
    logit_cap: float = 0.0,  # Gemma2 attn soft-capping; 0 = off
    alibi_slopes: tuple = None,  # per-q-head ALiBi slopes; None = off
    sinks: jax.Array = None,  # [num_q_heads] attention-sink logits
) -> jax.Array:  # [T, num_q_heads, head_dim]
    """Unified ragged attention: token t attends to kv positions
    0..q_pos[t] of request req_idx[t] (causal over the paged cache);
    a positive ``window`` restricts to the last ``window`` positions
    (Mistral-style sliding window, reference: sliding_window plumbed
    through the attention backends); a positive ``logit_cap`` bounds
    scores with cap*tanh(s/cap) before masking (Gemma2 soft-capping,
    reference: the softcap arg of the attention backends);
    ``alibi_slopes`` adds slope * (kv_pos - q_pos) per head before
    masking (Bloom/MPT ALiBi, reference: the alibi_slopes arg of the
    attention backends / csrc attention kernels)."""
    T, num_q_heads, head_dim = q.shape
    num_pages, num_kv_heads, page_size, _ = k_pages.shape
    assert num_q_heads % num_kv_heads == 0
    group = num_q_heads // num_kv_heads
    pages_per_req = block_tables.shape[1]

    # [T, Hkv, G, D] queries grouped by kv head.
    qg = q.reshape(T, num_kv_heads, group, head_dim).astype(jnp.float32)
    qg = qg * sm_scale
    # Per-token page lists: [T, pages_per_req].
    token_pages = block_tables[req_idx]

    def body(carry, page_i):
        m, l, acc = carry  # [T,Hkv,G,1], [T,Hkv,G,1], [T,Hkv,G,D]
        page_ids = token_pages[:, page_i]  # [T]
        k_blk = k_pages[page_ids, ..., :head_dim].astype(jnp.float32)
        v_blk = v_pages[page_ids, ..., :head_dim].astype(jnp.float32)
        # scores [T, Hkv, G, ps]
        scores = jnp.einsum("thgd,thpd->thgp", qg, k_blk)
        if logit_cap > 0:
            scores = logit_cap * jnp.tanh(scores / logit_cap)
        kv_pos = page_i * page_size + jnp.arange(page_size, dtype=jnp.int32)
        if alibi_slopes is not None:
            slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(
                num_kv_heads, group)
            dist = (kv_pos[None, :] - q_pos[:, None]).astype(jnp.float32)
            scores = scores + (slopes[None, :, :, None] *
                               dist[:, None, None, :])
        valid = kv_pos[None, :] <= q_pos[:, None]  # [T, ps] causal
        if window > 0:
            valid &= kv_pos[None, :] > (q_pos[:, None] - window)
        scores = jnp.where(valid[:, None, None, :], scores, _MASK_VALUE)

        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)  # [T,Hkv,G,ps]
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("thgp,thpd->thgd", p, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((T, num_kv_heads, group, 1), _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((T, num_kv_heads, group, 1), jnp.float32)
    acc0 = jnp.zeros((T, num_kv_heads, group, head_dim), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                  jnp.arange(pages_per_req,
                                             dtype=jnp.int32))
    if sinks is not None:
        # A learned per-head virtual key joins the softmax denominator
        # only (gpt-oss attention sinks; softmax shift-invariance makes
        # the running max of the REAL scores a valid reference point).
        sk = sinks.astype(jnp.float32).reshape(num_kv_heads, group)
        l = l + jnp.exp(sk[None, :, :, None] - m)
    out = acc / jnp.maximum(l, 1e-20)
    return out.reshape(T, num_q_heads, head_dim).astype(q.dtype)


def _shared_prefix_state(q, k_pages, v_pages, shared_page_ids, q_pos,
                         sm_scale):
    """Dense online-softmax partial state of all T query tokens against
    the batch-wide shared-prefix pages: one gather + MXU matmuls,
    loaded once for the whole batch. Returns (m, l, acc) shaped
    [T, QH, 1/1/D] for merging with a suffix phase."""
    T, num_q_heads, head_dim = q.shape
    num_kv_heads, page_size = k_pages.shape[1], k_pages.shape[2]
    group = num_q_heads // num_kv_heads
    S = shared_page_ids.shape[0]
    qg = (q.reshape(T, num_kv_heads, group, head_dim)
          .astype(jnp.float32) * sm_scale)
    k_sh = k_pages[shared_page_ids, ..., :head_dim].astype(jnp.float32)
    v_sh = v_pages[shared_page_ids, ..., :head_dim].astype(jnp.float32)
    scores = jnp.einsum("thgd,shpd->thgsp", qg, k_sh)
    kv_pos = (jnp.arange(S, dtype=jnp.int32)[:, None] * page_size +
              jnp.arange(page_size, dtype=jnp.int32)[None, :])
    valid = kv_pos.reshape(-1)[None, :] <= q_pos[:, None]  # [T, S*ps]
    scores = scores.reshape(T, num_kv_heads, group, S * page_size)
    scores = jnp.where(valid[:, None, None, :], scores, _MASK_VALUE)
    m = scores.max(axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    l = p.sum(axis=-1, keepdims=True)
    acc = jnp.einsum(
        "thgj,thjd->thgd", p,
        jnp.broadcast_to(
            v_sh.swapaxes(0, 1).reshape(1, num_kv_heads,
                                        S * page_size, head_dim),
            (T, num_kv_heads, S * page_size, head_dim)))
    return (m.reshape(T, num_q_heads, 1), l.reshape(T, num_q_heads, 1),
            acc.reshape(T, num_q_heads, head_dim))


def merge_attention_states(state_a, state_b):
    """Combine two online-softmax partial states (m, l, acc) over
    disjoint KV ranges — the XLA equivalent of the reference's
    csrc/attention/merge_attn_states.cu (used there for cascade and
    split-KV attention)."""
    m_a, l_a, acc_a = state_a
    m_b, l_b, acc_b = state_b
    m = jnp.maximum(m_a, m_b)
    alpha_a = jnp.exp(m_a - m)
    alpha_b = jnp.exp(m_b - m)
    l = l_a * alpha_a + l_b * alpha_b
    acc = acc_a * alpha_a + acc_b * alpha_b
    return m, l, acc


def cascade_ragged_paged_attention(
    q: jax.Array,  # [T, num_q_heads, head_dim]
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,  # [max_reqs, pages_per_req]
    req_idx: jax.Array,  # [T]
    q_pos: jax.Array,  # [T]
    shared_page_ids: jax.Array,  # [S] int32: batch-wide common prefix
    *,
    sm_scale: float,
) -> jax.Array:
    """Shared-prefix (cascade) attention: every scheduled request's
    first S page-table slots hold the SAME pages (prefix-cache hits), so
    their KV is loaded ONCE and attended as a dense block for all T
    query tokens — one gather and one MXU-friendly matmul instead of T
    per-token page gathers (reference: the cascade path of
    v1/attention/backends/flash_attn.py + merge_attn_states.cu). The
    remaining per-request suffix runs the normal online-softmax page
    scan over a STATICALLY shortened slot range, and the two partial
    states merge exactly."""
    T, num_q_heads, head_dim = q.shape
    num_pages, num_kv_heads, page_size, _ = k_pages.shape
    group = num_q_heads // num_kv_heads
    S = shared_page_ids.shape[0]
    pages_per_req = block_tables.shape[1]

    qg = (q.reshape(T, num_kv_heads, group, head_dim)
          .astype(jnp.float32) * sm_scale)

    # ---- shared phase: dense attention over the common S pages ----
    m_sh, l_sh, acc_sh = _shared_prefix_state(q, k_pages, v_pages,
                                              shared_page_ids, q_pos,
                                              sm_scale)
    m_sh = m_sh.reshape(T, num_kv_heads, group, 1)
    l_sh = l_sh.reshape(T, num_kv_heads, group, 1)
    acc_sh = acc_sh.reshape(T, num_kv_heads, group, head_dim)

    # ---- suffix phase: the usual scan, slots [S, pages_per_req) ----
    token_pages = block_tables[req_idx]

    def body(carry, page_i):
        m, l, acc = carry
        page_ids = token_pages[:, page_i]
        k_blk = k_pages[page_ids, ..., :head_dim].astype(jnp.float32)
        v_blk = v_pages[page_ids, ..., :head_dim].astype(jnp.float32)
        s = jnp.einsum("thgd,thpd->thgp", qg, k_blk)
        pos = page_i * page_size + jnp.arange(page_size, dtype=jnp.int32)
        ok = pos[None, :] <= q_pos[:, None]
        s = jnp.where(ok[:, None, None, :], s, _MASK_VALUE)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        pj = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + pj.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("thgp,thpd->thgd", pj, v_blk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((T, num_kv_heads, group, 1), _MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((T, num_kv_heads, group, 1), jnp.float32)
    acc0 = jnp.zeros((T, num_kv_heads, group, head_dim), jnp.float32)
    (m_sf, l_sf, acc_sf), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        jnp.arange(S, pages_per_req, dtype=jnp.int32))

    _, l, acc = merge_attention_states((m_sh, l_sh, acc_sh),
                                       (m_sf, l_sf, acc_sf))
    out = acc / jnp.maximum(l, 1e-20)
    return out.reshape(T, num_q_heads, head_dim).astype(q.dtype)


def naive_ragged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    req_idx: jax.Array,
    q_pos: jax.Array,
    *,
    sm_scale: float,
    window: int = 0,
    logit_cap: float = 0.0,
    alibi_slopes: tuple = None,
    sinks: jax.Array = None,
) -> jax.Array:
    """O(T * max_kv) dense-gather reference used only by unit tests."""
    T, num_q_heads, head_dim = q.shape
    num_pages, num_kv_heads, page_size, _ = k_pages.shape
    group = num_q_heads // num_kv_heads
    pages_per_req = block_tables.shape[1]
    max_kv = pages_per_req * page_size

    token_pages = block_tables[req_idx]  # [T, P]
    # Gather each token's full KV run: [T, P, Hkv, ps, D] -> [T, Hkv, max_kv, D]
    k_all = jnp.moveaxis(k_pages[token_pages, ..., :head_dim], 2,
                         1).reshape(T, num_kv_heads, max_kv, head_dim)
    v_all = jnp.moveaxis(v_pages[token_pages, ..., :head_dim], 2,
                         1).reshape(T, num_kv_heads, max_kv, head_dim)
    qg = q.reshape(T, num_kv_heads, group, head_dim).astype(jnp.float32)
    scores = jnp.einsum("thgd,thjd->thgj", qg * sm_scale,
                        k_all.astype(jnp.float32))
    if logit_cap > 0:
        scores = logit_cap * jnp.tanh(scores / logit_cap)
    kv_pos = jnp.arange(max_kv, dtype=jnp.int32)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(
            num_kv_heads, group)
        dist = (kv_pos[None, :] - q_pos[:, None]).astype(jnp.float32)
        scores = scores + slopes[None, :, :, None] * dist[:, None, None, :]
    valid = kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        valid &= kv_pos[None, :] > (q_pos[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, _MASK_VALUE)
    if sinks is not None:
        sk = sinks.astype(jnp.float32).reshape(num_kv_heads, group)
        m = scores.max(axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        denom = p.sum(axis=-1, keepdims=True) + jnp.exp(
            sk[None, :, :, None] - m)
        weights = p / denom
    else:
        weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("thgj,thjd->thgd", weights, v_all.astype(jnp.float32))
    return out.reshape(T, num_q_heads, head_dim).astype(q.dtype)


def resolve_attention_backend() -> str:
    """Pick the attention implementation: 'pallas' on TPU (or when
    interpret-mode testing requests it), 'xla' otherwise (reference:
    vllm/attention/selector.py:109 get_attn_backend / platforms/tpu.py:45).

    The platform is taken from the engine mesh when one is set (the
    process default backend can be TPU while a test mesh runs on virtual
    CPU devices), else from the default backend."""
    backend = envs.VDT_ATTENTION_BACKEND
    if backend == "auto":
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        if mesh_state.has_global_mesh():
            platform = next(iter(
                mesh_state.get_global_mesh().devices.flat)).platform
        else:
            platform = jax.default_backend()
        return "pallas" if platform == "tpu" else "xla"
    return backend


def _scatter_kv_flat(k_all, v_all, k_new, v_new, slot, layer, PS):
    """Contiguous-row scatter of new K/V into the flattened stacked cache
    (XLA path; slots < 0 drop)."""
    L, N, KVH, _, D = k_all.shape
    T = k_new.shape[0]
    k_new = _pad_last_dim(k_new, D)
    v_new = _pad_last_dim(v_new, D)
    page = slot // PS
    off = slot % PS
    rows = (((layer[0] * N + page[:, None]) * KVH +
             jnp.arange(KVH, dtype=jnp.int32)[None, :]) * PS +
            off[:, None])
    total = L * N * KVH * PS
    rows = jnp.where(slot[:, None] < 0, total, rows).reshape(-1)
    k_flat = k_all.reshape(total, D)
    v_flat = v_all.reshape(total, D)
    k_flat = k_flat.at[rows].set(
        k_new.reshape(T * KVH, D).astype(k_flat.dtype), mode="drop")
    v_flat = v_flat.at[rows].set(
        v_new.reshape(T * KVH, D).astype(v_flat.dtype), mode="drop")
    return k_flat.reshape(k_all.shape), v_flat.reshape(v_all.shape)


def _tknp_cache_specs():
    from jax.sharding import PartitionSpec as P

    from vllm_distributed_tpu.config import (MESH_AXIS_MODEL,
                                             MESH_AXIS_TOKEN)
    cache = P(None, MESH_AXIS_TOKEN, MESH_AXIS_MODEL, None, None)
    heads = P(None, MESH_AXIS_MODEL, None)
    return cache, heads, MESH_AXIS_TOKEN


def _tknp_apply_new_kv(k_all_, v_all_, k_new_, v_new_, kv_runs_,
                       n_runs_, slot_, layer, use_pallas):
    """Apply one rank's KV-write runs/slots to its cache shard — the
    per-rank body shared by the raw and quantized (tknp_kv) shuffle
    paths, so the halo-pad layout and the pallas-vs-scatter branch
    can never diverge between them."""
    PS, D = k_all_.shape[3], k_all_.shape[4]
    if use_pallas:
        from vllm_distributed_tpu.ops.pallas_kv_write import (
            write_kv_pages_pallas)
        pad = [(0, 0), (PS, 2 * PS), (0, 0)]
        k_hl = jnp.pad(_pad_last_dim(k_new_, D).swapaxes(0, 1), pad)
        v_hl = jnp.pad(_pad_last_dim(v_new_, D).swapaxes(0, 1), pad)
        return write_kv_pages_pallas(
            k_all_, v_all_, k_hl.astype(k_all_.dtype),
            v_hl.astype(v_all_.dtype), kv_runs_, n_runs_, layer)
    return _scatter_kv_flat(k_all_, v_all_, k_new_, v_new_, slot_,
                            layer, PS)


def _write_kv_cache_tknp(k_all, v_all, k_new, v_new, batch, layer):
    """Token-parallel KV write: the cache page axis is sharded over the
    ``token`` mesh axis; each rank applies only its own KV-write runs /
    slots (local page ids, prepared by the runner — TPU analogue of the
    fork's per-rank KV write path).

    The KV-write SHUFFLE — the step's new K/V rows crossing the
    shard_map boundary to the page-owning rank — is the last raw
    collective of ROADMAP item 5: under VDT_QCOMM_PATHS "tknp_kv" the
    payload crosses as block-scaled int8 + fp32 scales (quantized
    BEFORE the boundary, dequantized per-rank after), with the standard
    no-win fallback counting."""
    from vllm_distributed_tpu.parallel import collectives
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    tk = batch.tknp
    use_pallas = resolve_attention_backend() == "pallas"
    cache_spec, new_spec, token_axis = _tknp_cache_specs()
    from jax.sharding import PartitionSpec as P

    q_pack = collectives.kv_shuffle_quantize(
        k_new, v_new, mesh_state.tknp_size())
    if q_pack is not None:
        return _write_kv_cache_tknp_quant(k_all, v_all, q_pack,
                                          k_new.dtype, batch, layer,
                                          use_pallas)

    def call(k_all_, v_all_, k_new_, v_new_, kv_runs_, n_runs_, slot_):
        return _tknp_apply_new_kv(k_all_, v_all_, k_new_, v_new_,
                                  kv_runs_[0], n_runs_[0], slot_[0],
                                  layer, use_pallas)

    return shard_map(
        call, mesh=mesh_state.get_global_mesh(),
        in_specs=(cache_spec, cache_spec, new_spec, new_spec,
                  P(token_axis, None, None), P(token_axis, None),
                  P(token_axis, None)),
        out_specs=(cache_spec, cache_spec),
        check_vma=False)(k_all, v_all, k_new, v_new, tk.kv_runs,
                         tk.num_kv_runs, tk.slot_mapping)


def _write_kv_cache_tknp_quant(k_all, v_all, q_pack, new_dtype, batch,
                               layer, use_pallas):
    """Quantized TKNP KV-write shuffle: the int8 payload + fp32 scales
    cross the token-axis shard_map boundary instead of the model-dtype
    K/V rows; each rank dequantizes and applies its own page runs.
    Cache writes land the quantized round-trip of the new rows — the
    same bounded per-block divergence the other VDT_QCOMM paths carry
    (tests/ops/test_quantized_comms.py pins the bound)."""
    from vllm_distributed_tpu.config import MESH_AXIS_MODEL
    from vllm_distributed_tpu.parallel import collectives
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    tk = batch.tknp
    cache_spec, _new_spec, token_axis = _tknp_cache_specs()
    from jax.sharding import PartitionSpec as P
    k_q, k_s, v_q, v_s = q_pack
    # Payload [T, KVH, D/b, b] + scales [T, KVH, D/b, 1]: kv heads stay
    # sharded over the model axis, replication over the token axis is
    # the (now int8) shuffle leg.
    pay_spec = P(None, MESH_AXIS_MODEL, None, None)

    def call(k_all_, v_all_, k_q_, k_s_, v_q_, v_s_, kv_runs_, n_runs_,
             slot_):
        k_new_, v_new_ = collectives.kv_shuffle_dequantize(
            k_q_, k_s_, v_q_, v_s_, new_dtype)
        return _tknp_apply_new_kv(k_all_, v_all_, k_new_, v_new_,
                                  kv_runs_[0], n_runs_[0], slot_[0],
                                  layer, use_pallas)

    return shard_map(
        call, mesh=mesh_state.get_global_mesh(),
        in_specs=(cache_spec, cache_spec, pay_spec, pay_spec, pay_spec,
                  pay_spec, P(token_axis, None, None),
                  P(token_axis, None), P(token_axis, None)),
        out_specs=(cache_spec, cache_spec),
        check_vma=False)(k_all, v_all, k_q, k_s, v_q, v_s, tk.kv_runs,
                         tk.num_kv_runs, tk.slot_mapping)


def write_kv_cache(
    k_all: jax.Array,  # [L, N, KVH, PS, D]
    v_all: jax.Array,
    k_new: jax.Array,  # [T, KVH, d_model]
    v_new: jax.Array,
    batch,  # AttentionBatch
    layer: jax.Array,  # [1] int32
) -> tuple[jax.Array, jax.Array]:
    """Write the step's K/V into layer ``layer`` of the stacked cache.

    Pallas path: in-place aliased page RMW kernel (no cache copy; see
    ops/pallas_kv_write.py). XLA path: flat row scatter with a layer
    offset (CPU tests / debugging). Token-parallel batches route to the
    page-sharded per-rank write.
    """
    if getattr(batch, "tknp", None) is not None:
        return _write_kv_cache_tknp(k_all, v_all, k_new, v_new, batch,
                                    layer)
    L, N, KVH, PS, D = k_all.shape
    if (resolve_attention_backend() == "pallas"
            and k_all.dtype not in _FP8_DTYPES
            and getattr(batch, "kv_runs", None) is not None):
        from vllm_distributed_tpu.ops.pallas_kv_write import (
            write_kv_pages_pallas)

        def call(k_all_, v_all_, k_new_, v_new_):
            pad = [(0, 0), (PS, 2 * PS), (0, 0)]
            k_hl = jnp.pad(
                _pad_last_dim(k_new_, D).swapaxes(0, 1), pad)
            v_hl = jnp.pad(
                _pad_last_dim(v_new_, D).swapaxes(0, 1), pad)
            return write_kv_pages_pallas(
                k_all_, v_all_, k_hl.astype(k_all_.dtype),
                v_hl.astype(v_all_.dtype), batch.kv_runs,
                batch.num_kv_runs, layer)

        from vllm_distributed_tpu.config import MESH_AXIS_MODEL
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        if mesh_state.has_global_mesh() and mesh_state.tp_size() > 1:
            from jax.sharding import PartitionSpec as P
            cache_spec = P(None, None, MESH_AXIS_MODEL, None, None)
            new_spec = P(None, MESH_AXIS_MODEL, None)
            return shard_map(
                call, mesh=mesh_state.get_global_mesh(),
                in_specs=(cache_spec, cache_spec, new_spec, new_spec),
                out_specs=(cache_spec, cache_spec),
                check_vma=False)(k_all, v_all, k_new, v_new)
        return call(k_all, v_all, k_new, v_new)

    # XLA fallback: contiguous-row scatter over the flattened cache.
    return _scatter_kv_flat(k_all, v_all, k_new, v_new,
                            batch.slot_mapping, layer, PS)


def _paged_attention_tknp(q, k_pages, v_pages, batch, *, sm_scale, layer):
    """Token-parallel attention: each ``token``-axis rank computes
    attention only for the requests whose KV pages live in its shard
    (per-rank compacted seq lists / local page tables built by the
    runner), zeroes the rows it does not own, and a psum over the token
    axis merges the disjoint per-rank outputs.

    This is the SPMD re-expression of the fork's TKNP decode-attention
    scaling (token_parallel_linear.py:39 scatter -> per-rank attention on
    local KV -> gather): activations stay replicated over the token axis
    (no scatter/gather), while KV memory and attention FLOPs/bandwidth
    split K ways.
    """
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    tk = batch.tknp
    head_dim = q.shape[-1]
    use_pallas = (resolve_attention_backend() == "pallas"
                  and batch.seq_info is not None)
    cache_spec, head_spec, token_axis = _tknp_cache_specs()
    from jax.sharding import PartitionSpec as P

    unified = use_pallas and getattr(tk, "desc", None) is not None

    def call(q_, k_, v_, seq_info_, num_seqs_, bt_, slot_, desc_, dl_):
        seq_info_ = seq_info_[0]
        num_seqs_ = num_seqs_[0]
        bt_ = bt_[0]
        slot_ = slot_[0]
        if unified:
            from vllm_distributed_tpu.ops.pallas_attention import (
                unified_ragged_paged_attention_pallas)
            q_p = _pad_last_dim(q_, k_.shape[-1])
            out = unified_ragged_paged_attention_pallas(
                q_p, k_, v_, desc_[0], seq_info_, dl_[0], bt_, layer,
                sm_scale=sm_scale, bq=batch.attn_bq,
                sb=batch.attn_sb)[..., :head_dim]
        elif use_pallas:
            from vllm_distributed_tpu.ops.pallas_attention import (
                ragged_paged_attention_pallas)
            q_p = _pad_last_dim(q_, k_.shape[-1])
            out = ragged_paged_attention_pallas(
                q_p, k_, v_, seq_info_, num_seqs_, bt_, layer,
                sm_scale=sm_scale, max_q=batch.max_q)[..., :head_dim]
        else:
            out = ragged_paged_attention(
                q_, k_[layer[0]], v_[layer[0]], bt_, batch.req_idx,
                batch.positions, sm_scale=sm_scale)
        # Zero rows this rank does not own (incl. padding / kernel spill),
        # then merge the disjoint rank outputs. The psum is the decode
        # hot path's dominant wire cost; VDT_QCOMM ships it block-scaled
        # int8 (parallel/collectives.py).
        out = jnp.where((slot_ >= 0)[:, None, None], out, 0)
        from vllm_distributed_tpu.parallel import collectives
        return collectives.psum(out, token_axis, path="tknp")

    K = tk.seq_info.shape[0]
    desc = tk.desc if unified else jnp.zeros((K, 1, 3), jnp.int32)
    dl = (tk.decode_list if unified
          else jnp.zeros((K, tk.seq_info.shape[1]), jnp.int32))
    return shard_map(
        call, mesh=mesh_state.get_global_mesh(),
        in_specs=(head_spec, cache_spec, cache_spec,
                  P(token_axis, None, None), P(token_axis, None),
                  P(token_axis, None, None), P(token_axis, None),
                  P(token_axis, None, None), P(token_axis, None)),
        out_specs=head_spec,
        check_vma=False)(q, k_pages, v_pages, tk.seq_info, tk.num_seqs,
                         tk.block_tables, tk.slot_mapping, desc, dl)


def _pallas_cascade(q, q_p, k_all, v_all, batch, layer, sm_scale,
                    head_dim):
    """Cascade attention on the Pallas backend: the batch-wide shared
    prefix runs as ONE dense XLA phase (a single gather + MXU matmuls —
    there is nothing a kernel would add over XLA's own fusion here),
    the per-request suffix runs the Pallas kernel over a block table
    with the shared slots stripped and kv_len shifted (relative
    causality is preserved), and the kernel's exported (m, l) state
    merges the two exactly (reference: flash_attn.py cascade +
    merge_attn_states.cu). With a partition descriptor on the batch the
    suffix phase runs the mega-kernel (decode rows keep SB batching and
    export their state too); the descriptor is reused verbatim — only
    kv_len shifts, which the kernel reads dynamically."""
    shared = batch.cascade_shared_ids
    S = shared.shape[0]
    page_size = k_all.shape[3]
    D = k_all.shape[-1]
    k_layer = k_all[layer[0]]
    v_layer = v_all[layer[0]]
    m_sh, l_sh, acc_sh = _shared_prefix_state(
        q, k_layer, v_layer, shared, batch.positions, sm_scale)

    shift = S * page_size
    si = batch.seq_info
    si_sfx = si.at[:, 2].set(jnp.maximum(si[:, 2] - shift, 0))
    if getattr(batch, "attn_desc", None) is not None:
        from vllm_distributed_tpu.ops.pallas_attention import (
            unified_ragged_paged_attention_pallas)
        out_sf, st_sf = unified_ragged_paged_attention_pallas(
            q_p, k_all, v_all, batch.attn_desc, si_sfx,
            batch.decode_list, batch.block_tables[:, S:], layer,
            sm_scale=sm_scale, bq=batch.attn_bq, sb=batch.attn_sb,
            emit_state=True)
    else:
        from vllm_distributed_tpu.ops.pallas_attention import (
            ragged_paged_attention_pallas)
        out_sf, st_sf = ragged_paged_attention_pallas(
            q_p, k_all, v_all, si_sfx, batch.num_seqs,
            batch.block_tables[:, S:], layer, sm_scale=sm_scale,
            max_q=batch.max_q, emit_state=True)
    m_sf = st_sf[..., 0:1]                      # [T, QH, 1] f32
    l_sf = st_sf[..., D // 2:D // 2 + 1]
    acc_sf = out_sf[..., :head_dim].astype(jnp.float32) * l_sf

    _, l, acc = merge_attention_states((m_sh, l_sh, acc_sh),
                                       (m_sf, l_sf, acc_sf))
    return (acc / jnp.maximum(l, 1e-20)).astype(q.dtype)


def paged_attention(
    q: jax.Array,  # [T, num_q_heads, head_dim]
    k_pages: jax.Array,  # [L, N, KVH, PS, D] stacked cache
    v_pages: jax.Array,
    batch,  # AttentionBatch
    *,
    sm_scale: float,
    layer: jax.Array | None = None,  # [1] int32
    window: int = 0,  # sliding window; 0 = full causal
    logit_cap: float = 0.0,  # attn logit soft-capping; 0 = off
    alibi_slopes: tuple = None,  # Bloom/MPT ALiBi; None = off
    sinks: jax.Array = None,  # gpt-oss attention sinks; None = off
) -> jax.Array:
    """Unified entry used by every model's attention layer; dispatches to
    the Pallas kernel or the XLA reference path per backend selection.
    A sliding window rides the mega-kernel as a per-layer static (its
    page loop starts at the first block inside the window); the XLA
    reference path gathers a request's whole table and masks, reading
    the placeholder page under the mask where a window cache group has
    handed a slot back.

    On a >1-wide tensor-parallel mesh the Pallas call is wrapped in
    shard_map over the "model" (head) axis — pallas_call is opaque to
    GSPMD, so the kernel must be launched per-shard with local head counts
    (the TPU analogue of the reference's per-rank attention backends).
    """
    if layer is None:
        layer = jnp.zeros((1, ), jnp.int32)
    if getattr(batch, "tknp", None) is not None:
        if (window or logit_cap or alibi_slopes or sinks is not None
                or k_pages.dtype in _FP8_DTYPES):
            raise NotImplementedError(
                "sliding window / softcap / ALiBi / sinks / fp8 KV under token "
                "parallelism (the per-rank attention path carries none "
                "of these; models/loader.py get_model rejects the "
                "combinations at admission — this trace-time guard is "
                "the backstop)")
        return _paged_attention_tknp(q, k_pages, v_pages, batch,
                                     sm_scale=sm_scale, layer=layer)
    # Sliding window / softcap / ALiBi / sinks fold into the unified
    # mega-kernel (per-layer statics + the [2, QH] head-feature sidecar)
    # — Gemma/Mistral/Bloom/gpt-oss-class models reach the Pallas path
    # whenever the batch carries a partition descriptor. Feature waves
    # WITHOUT a descriptor (in-jit multi-step/EAGLE batches) and fp8 KV
    # keep the XLA reference below.
    features = bool(window or logit_cap or alibi_slopes is not None
                    or sinks is not None)
    if (k_pages.dtype not in _FP8_DTYPES
            and resolve_attention_backend() == "pallas"
            and batch.seq_info is not None
            and (not features
                 or (getattr(batch, "attn_desc", None) is not None
                     and getattr(batch, "cascade_shared_ids", None)
                     is None))):
        head_dim = q.shape[-1]
        feat = build_head_feat(q.shape[1], alibi_slopes, sinks)

        def call(q_, k_, v_, feat_):
            # Cache storage may be lane-padded (storage_head_dim); pad q to
            # match and slice the padding back off the output.
            q_p = _pad_last_dim(q_, k_.shape[-1])
            shared = getattr(batch, "cascade_shared_ids", None)
            if shared is not None:
                out = _pallas_cascade(q_, q_p, k_, v_, batch, layer,
                                      sm_scale, head_dim)
            elif getattr(batch, "attn_desc", None) is not None:
                # Mixed-batch mega-kernel: one call, prefill q-tiles +
                # SB decode groups partitioned by the host descriptor —
                # decode rows keep MXU-filling batching even when a
                # chunked-prefill chunk shares the wave, and no kernel
                # static depends on the batch composition.
                from vllm_distributed_tpu.ops.pallas_attention import (
                    unified_ragged_paged_attention_pallas)
                out = unified_ragged_paged_attention_pallas(
                    q_p, k_, v_, batch.attn_desc, batch.seq_info,
                    batch.decode_list, batch.block_tables, layer, feat_,
                    sm_scale=sm_scale, bq=batch.attn_bq,
                    sb=batch.attn_sb, window=window,
                    logit_cap=logit_cap,
                    has_alibi=alibi_slopes is not None,
                    has_sinks=sinks is not None)[..., :head_dim]
            else:
                from vllm_distributed_tpu.ops.pallas_attention import (
                    ragged_paged_attention_pallas)
                out = ragged_paged_attention_pallas(
                    q_p, k_, v_, batch.seq_info, batch.num_seqs,
                    batch.block_tables, layer, sm_scale=sm_scale,
                    max_q=batch.max_q)[..., :head_dim]
            # Rows the kernel never writes (padding tokens, tile spill past
            # the last sequence) are uninitialized HBM — possibly NaN/Inf
            # bit patterns. Zero them so garbage can't propagate through
            # later layers' projections (padding tokens have slot -1).
            valid = (batch.slot_mapping >= 0)[:, None, None]
            return jnp.where(valid, out, 0)

        from vllm_distributed_tpu.config import MESH_AXIS_MODEL
        from vllm_distributed_tpu.parallel import mesh as mesh_state
        if (mesh_state.has_global_mesh()
                and mesh_state.tp_size() > 1):
            from jax.sharding import PartitionSpec as P
            head_spec = P(None, MESH_AXIS_MODEL, None)
            kv_spec = P(None, None, MESH_AXIS_MODEL, None, None)
            return shard_map(
                call, mesh=mesh_state.get_global_mesh(),
                in_specs=(head_spec, kv_spec, kv_spec,
                          P(None, MESH_AXIS_MODEL)),
                out_specs=head_spec, check_vma=False)(q, k_pages,
                                                      v_pages, feat)
        return call(q, k_pages, v_pages, feat)
    if k_pages.ndim == 5:
        k_layer = k_pages[layer[0]]
        v_layer = v_pages[layer[0]]
    else:
        k_layer, v_layer = k_pages, v_pages
    if (window == 0 and logit_cap == 0 and alibi_slopes is None
            and sinks is None
            and getattr(batch, "cascade_shared_ids", None) is not None):
        return cascade_ragged_paged_attention(
            q, k_layer, v_layer, batch.block_tables, batch.req_idx,
            batch.positions, batch.cascade_shared_ids,
            sm_scale=sm_scale)
    return ragged_paged_attention(q, k_layer, v_layer, batch.block_tables,
                                  batch.req_idx, batch.positions,
                                  sm_scale=sm_scale, window=window,
                                  logit_cap=logit_cap,
                                  alibi_slopes=alibi_slopes, sinks=sinks)


def write_kv_and_attend(
    q: jax.Array,  # [T, num_q_heads, head_dim]
    k_pages: jax.Array,  # [L, N, KVH, PS, D] stacked cache
    v_pages: jax.Array,
    k_new: jax.Array,  # [T, KVH, head_dim]
    v_new: jax.Array,
    batch,  # AttentionBatch
    *,
    sm_scale: float,
    layer: jax.Array,  # [1] int32
    window: int = 0,
    logit_cap: float = 0.0,
    alibi_slopes: tuple = None,
    sinks: jax.Array = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """KV-page write + attention for one layer, fused into ONE Pallas
    pass over the cache when the layout permits: the mega-kernel's
    kind-3 programs land the step's new K/V pages in place (aliased),
    and the attention programs that follow in the sequential grid read
    them — a mixed step makes one pass over the KV cache instead of two.
    Returns (k_pages, v_pages, attn_out).

    Falls back to write_kv_cache + paged_attention whenever the layout
    rules the fused kernel out (fp8 KV / token parallelism / cascade),
    when the batch has no partition descriptor (in-jit batches from the
    multi-step scan or EAGLE), or when VDT_FUSED_KV_WRITE=0. Sliding
    window / softcap / ALiBi / sinks ride the kernel's per-layer
    statics + head-feature sidecar and no longer force the XLA path."""
    fused = (envs.VDT_FUSED_KV_WRITE
             and k_pages.dtype not in _FP8_DTYPES
             and getattr(batch, "tknp", None) is None
             and getattr(batch, "cascade_shared_ids", None) is None
             and getattr(batch, "attn_desc", None) is not None
             and getattr(batch, "kv_runs", None) is not None
             and resolve_attention_backend() == "pallas")
    if not fused:
        k_pages, v_pages = write_kv_cache(k_pages, v_pages, k_new, v_new,
                                          batch, layer)
        out = paged_attention(q, k_pages, v_pages, batch,
                              sm_scale=sm_scale, layer=layer,
                              window=window, logit_cap=logit_cap,
                              alibi_slopes=alibi_slopes, sinks=sinks)
        return k_pages, v_pages, out

    from vllm_distributed_tpu.ops.pallas_attention import (
        unified_write_attend_pallas)
    L, N, KVH, PS, D = k_pages.shape
    head_dim = q.shape[-1]
    feat = build_head_feat(q.shape[1], alibi_slopes, sinks)

    def call(q_, k_, v_, kn_, vn_, feat_):
        pad = [(0, 0), (PS, 2 * PS), (0, 0)]
        k_hl = jnp.pad(_pad_last_dim(kn_, D).swapaxes(0, 1),
                       pad).astype(k_.dtype)
        v_hl = jnp.pad(_pad_last_dim(vn_, D).swapaxes(0, 1),
                       pad).astype(v_.dtype)
        q_p = _pad_last_dim(q_, D)
        out, k2, v2 = unified_write_attend_pallas(
            q_p, k_, v_, k_hl, v_hl, batch.attn_desc, batch.seq_info,
            batch.decode_list, batch.kv_runs, batch.block_tables, layer,
            feat_, sm_scale=sm_scale, bq=batch.attn_bq,
            sb=batch.attn_sb, window=window, logit_cap=logit_cap,
            has_alibi=alibi_slopes is not None,
            has_sinks=sinks is not None)
        out = out[..., :head_dim]
        # Rows no program wrote (padding tokens) are uninitialized HBM;
        # zero them so garbage can't reach later layers' projections.
        valid = (batch.slot_mapping >= 0)[:, None, None]
        return k2, v2, jnp.where(valid, out, 0)

    from vllm_distributed_tpu.config import MESH_AXIS_MODEL
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    if mesh_state.has_global_mesh() and mesh_state.tp_size() > 1:
        from jax.sharding import PartitionSpec as P
        head_spec = P(None, MESH_AXIS_MODEL, None)
        cache_spec = P(None, None, MESH_AXIS_MODEL, None, None)
        return shard_map(
            call, mesh=mesh_state.get_global_mesh(),
            in_specs=(head_spec, cache_spec, cache_spec, head_spec,
                      head_spec, P(None, MESH_AXIS_MODEL)),
            out_specs=(cache_spec, cache_spec, head_spec),
            check_vma=False)(q, k_pages, v_pages, k_new, v_new, feat)
    return call(q, k_pages, v_pages, k_new, v_new, feat)
