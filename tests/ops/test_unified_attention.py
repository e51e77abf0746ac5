"""Mixed-batch attention mega-kernel vs the dense reference.

Interpret-mode parity matrix for ops/pallas_attention.py's unified
kernel (ISSUE 6 tentpole): mixed prefill+decode batches, GQA grouping,
q_len spanning page boundaries and multiple q tiles, single-token
prefill tails, the emit_state cascade path, and the fused KV-write +
attend variant. The partition-descriptor builder is unit-tested on the
same cases."""

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.ops.attention import (_shared_prefix_state,
                                                merge_attention_states,
                                                naive_ragged_attention,
                                                write_kv_pages)
from vllm_distributed_tpu.ops.pallas_attention import (
    KIND_DECODE, KIND_KV_WRITE, KIND_NOOP, KIND_PREFILL, Q_TILE_PAD,
    build_partition_descriptor, decode_group_size, num_partition_programs,
    prefill_tile_size, unified_ragged_paged_attention_pallas,
    unified_write_attend_pallas)


def build_case(rng, *, seqs, page_size, pages_per_req, num_q_heads,
               num_kv_heads, head_dim, dtype=jnp.float32, bq=None, sb=None):
    """seqs: list of (q_len, kv_len) with kv_len >= q_len. ``bq`` and
    ``sb`` default to the model's own tile sizes; a smaller pair traces
    a smaller interpreted program."""
    R = len(seqs)
    max_reqs = R + 1  # one inactive padding row
    num_pages = max_reqs * pages_per_req
    T = sum(q for q, _ in seqs)
    T_pad = T + Q_TILE_PAD

    k_pages = jnp.asarray(rng.standard_normal(
        (num_pages, num_kv_heads, page_size, head_dim)), dtype)
    v_pages = jnp.asarray(rng.standard_normal(
        (num_pages, num_kv_heads, page_size, head_dim)), dtype)
    q = jnp.asarray(rng.standard_normal((T_pad, num_q_heads, head_dim)),
                    dtype)

    bt = np.zeros((max_reqs, pages_per_req), np.int32)
    for r in range(max_reqs):
        bt[r] = np.arange(r * pages_per_req, (r + 1) * pages_per_req)

    seq_info = np.zeros((max_reqs, 4), np.int32)
    req_idx = np.zeros((T_pad, ), np.int32)
    q_pos = np.zeros((T_pad, ), np.int32)
    t = 0
    for r, (q_len, kv_len) in enumerate(seqs):
        seq_info[r] = (t, q_len, kv_len, r)
        req_idx[t:t + q_len] = r
        q_pos[t:t + q_len] = np.arange(kv_len - q_len, kv_len)
        t += q_len

    bq = bq or prefill_tile_size(num_q_heads, head_dim)
    sb = sb or decode_group_size(num_q_heads, num_kv_heads)
    P = num_partition_programs(T, max_reqs, bq=bq, sb=sb)
    desc, dl = build_partition_descriptor(seq_info, R, bq=bq, sb=sb,
                                          num_programs=P)
    return dict(
        q=q, k_pages=k_pages, v_pages=v_pages,
        seq_info=jnp.asarray(seq_info), seq_info_np=seq_info,
        desc=jnp.asarray(desc), desc_np=desc,
        decode_list=jnp.asarray(dl),
        block_tables=jnp.asarray(bt), block_tables_np=bt,
        req_idx=jnp.asarray(req_idx), q_pos=jnp.asarray(q_pos),
        T=T, bq=bq, sb=sb, num_seqs=R,
    )


def run_both(case, sm_scale=0.125):
    out = unified_ragged_paged_attention_pallas(
        case["q"], case["k_pages"], case["v_pages"], case["desc"],
        case["seq_info"], case["decode_list"], case["block_tables"],
        sm_scale=sm_scale, bq=case["bq"], sb=case["sb"], interpret=True)
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=sm_scale)
    T = case["T"]
    return np.asarray(out)[:T], np.asarray(want)[:T]


@pytest.mark.parametrize("seqs", [
    # Pure decode: one token per sequence, varying kv lens.
    [(1, 1), (1, 5), (1, 17), (1, 32)],
    # Pure prefill from scratch.
    [(7, 7), (16, 16), (3, 3)],
    # Chunked prefill: later chunk attends earlier kv; q spans a page
    # boundary (page_size 8, q_len 8 starting mid-page).
    [(8, 24), (4, 9)],
    # Mixed prefill + decode in one wave — the mega-kernel's target.
    [(1, 13), (12, 12), (1, 30), (5, 21)],
    # Single-token prefill tails (q_len == 1 with backlog) ride the
    # decode-group path; the math is identical to decode.
    [(1, 13), (2, 2), (1, 9)],
])
def test_matches_reference(seqs):
    rng = np.random.default_rng(0)
    case = build_case(rng, seqs=seqs, page_size=8, pages_per_req=4,
                      num_q_heads=8, num_kv_heads=4, head_dim=128)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_gqa_group_and_mha(kvh):
    rng = np.random.default_rng(1)
    case = build_case(rng, seqs=[(3, 11), (1, 4)], page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=kvh,
                      head_dim=128)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_multi_tile_prefill_spans_tiles_and_pages():
    """q_len > bq spans several prefill tiles of one sequence; kv spans
    several pages. The exact chunked writeback must stitch tiles
    seamlessly (no spill into the neighbouring decode row)."""
    rng = np.random.default_rng(2)
    case = build_case(rng, seqs=[(40, 40), (1, 30), (33, 48)],
                      page_size=8, pages_per_req=8, num_q_heads=4,
                      num_kv_heads=4, head_dim=128)
    assert case["bq"] < 40  # the case really is multi-tile
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_many_decode_seqs_ragged_groups():
    """Enough decode sequences for several SB groups, with a group count
    that does not divide the batch."""
    rng = np.random.default_rng(7)
    seqs = [(1, k) for k in (1, 5, 17, 32, 9, 25, 13, 2, 31, 8, 20)]
    case = build_case(rng, seqs=seqs, page_size=8, pages_per_req=4,
                      num_q_heads=8, num_kv_heads=4, head_dim=128)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_bf16_mixed():
    """bf16 at the model's own tile and group sizes."""
    rng = np.random.default_rng(3)
    case = build_case(rng, seqs=[(1, 9), (6, 6), (1, 3)], page_size=8,
                      pages_per_req=2, num_q_heads=4, num_kv_heads=2,
                      head_dim=128, dtype=jnp.bfloat16)
    got, want = run_both(case)
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("heads,tiles", [
    ((8, 4), {}),
    # 128 score rows a K/V head: the tile's lanes full.
    ((16, 1), dict(bq=8, sb=2)),
], ids=["rows64", "rows128"])
def test_emit_state_cascade_merge_matches_full_attention(heads, tiles):
    """The cascade contract: shared-prefix dense phase + mega-kernel
    suffix phase (kv_len shifted, shared slots stripped) merged via the
    exported (m, l) state must equal plain attention over the full KV —
    including decode rows, whose state now comes from the decode-group
    branch."""
    rng = np.random.default_rng(4)
    page_size, S = 8, 2
    case = build_case(rng, seqs=[(1, 20), (4, 24), (1, 33), (6, 22)],
                      page_size=page_size, pages_per_req=6,
                      num_q_heads=heads[0], num_kv_heads=heads[1],
                      head_dim=128, **tiles)
    D = case["k_pages"].shape[-1]
    # Make the first S page-table slots literally shared.
    bt = case["block_tables_np"].copy()
    shared = bt[0, :S].copy()
    for r in range(case["num_seqs"]):
        bt[r, :S] = shared
    shift = S * page_size
    si_sfx = case["seq_info_np"].copy()
    si_sfx[:, 2] = np.maximum(si_sfx[:, 2] - shift, 0)

    out_sf, st_sf = unified_ragged_paged_attention_pallas(
        case["q"], case["k_pages"], case["v_pages"], case["desc"],
        jnp.asarray(si_sfx), case["decode_list"],
        jnp.asarray(bt[:, S:]), sm_scale=0.125, bq=case["bq"],
        sb=case["sb"], interpret=True, emit_state=True)
    m_sh, l_sh, acc_sh = _shared_prefix_state(
        case["q"], case["k_pages"], case["v_pages"], jnp.asarray(shared),
        case["q_pos"], 0.125)
    m_sf = st_sf[..., 0:1]
    l_sf = st_sf[..., D // 2:D // 2 + 1]
    acc_sf = out_sf.astype(jnp.float32) * l_sf
    _, l, acc = merge_attention_states((m_sh, l_sh, acc_sh),
                                       (m_sf, l_sf, acc_sf))
    got = np.asarray(acc / jnp.maximum(l, 1e-20))[:case["T"]]
    want = np.asarray(naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], jnp.asarray(bt),
        case["req_idx"], case["q_pos"], sm_scale=0.125))[:case["T"]]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_fused_write_attend_matches_write_then_naive():
    """kind-3 kv-write programs + attention in ONE call: the cache must
    come back bit-identical to the XLA scatter, and the attention output
    must see this step's freshly written pages."""
    rng = np.random.default_rng(5)
    page_size = 8
    seqs = [(1, 20), (5, 24), (1, 33), (7, 22)]
    case = build_case(rng, seqs=seqs, page_size=page_size,
                      pages_per_req=6, num_q_heads=8, num_kv_heads=4,
                      head_dim=128)
    T, bq, sb = case["T"], case["bq"], case["sb"]
    max_reqs = case["seq_info_np"].shape[0]
    kvh, hd = 4, 128
    bt = case["block_tables_np"]
    k_new = jnp.asarray(
        rng.standard_normal((T + Q_TILE_PAD, kvh, hd)), jnp.float32)
    v_new = jnp.asarray(
        rng.standard_normal((T + Q_TILE_PAD, kvh, hd)), jnp.float32)

    slot = np.full((T + Q_TILE_PAD, ), -1, np.int32)
    kv_runs = []
    t = 0
    for r, (ql, kl) in enumerate(seqs):
        start = kl - ql
        pos = np.arange(start, kl)
        slot[t:t + ql] = (bt[r, pos // page_size] * page_size +
                          pos % page_size)
        consumed = 0
        while consumed < ql:
            p = start + consumed
            off = p % page_size
            run_len = min(page_size - off, ql - consumed)
            src = t + consumed
            kv_runs.append((int(bt[r, p // page_size]), off,
                            src - off + page_size, run_len))
            consumed += run_len
        t += ql
    G = len(kv_runs)
    P = num_partition_programs(T, max_reqs, bq=bq, sb=sb,
                               num_kv_writes=G)
    desc, dl = build_partition_descriptor(
        case["seq_info_np"], case["num_seqs"], bq=bq, sb=sb,
        num_programs=P, num_kv_writes=G)
    assert (desc[:G, 0] == KIND_KV_WRITE).all()

    pad = [(0, 0), (page_size, 2 * page_size), (0, 0)]
    k_hl = jnp.pad(k_new.swapaxes(0, 1), pad)
    v_hl = jnp.pad(v_new.swapaxes(0, 1), pad)
    out, k2, v2 = unified_write_attend_pallas(
        case["q"], case["k_pages"][None], case["v_pages"][None], k_hl,
        v_hl, jnp.asarray(desc), case["seq_info"], jnp.asarray(dl),
        jnp.asarray(np.asarray(kv_runs, np.int32)), case["block_tables"],
        jnp.zeros((1, ), jnp.int32), sm_scale=0.125, bq=bq, sb=sb,
        interpret=True)

    k_ref, v_ref = write_kv_pages(case["k_pages"], case["v_pages"],
                                  k_new, v_new, jnp.asarray(slot))
    np.testing.assert_array_equal(np.asarray(k2[0]), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v2[0]), np.asarray(v_ref))
    want = np.asarray(naive_ragged_attention(
        case["q"], k_ref, v_ref, case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=0.125))[:T]
    np.testing.assert_allclose(np.asarray(out)[:T], want, rtol=2e-3,
                               atol=2e-3)


# ---------------------------------------------------------------------------
# Attention features (sliding window / softcap / ALiBi / sinks) folded
# into the mega-kernel — ISSUE 11 satellite: Gemma/Mistral/Bloom/
# gpt-oss-class models stop forcing the XLA fallback.
# ---------------------------------------------------------------------------


def run_both_feat(case, sm_scale=0.125, *, window=0, logit_cap=0.0,
                  slopes=None, sinks=None, reference=True):
    QH = case["q"].shape[1]
    feat = jnp.stack([
        jnp.asarray(slopes if slopes is not None else np.zeros(QH),
                    jnp.float32),
        jnp.asarray(sinks if sinks is not None else np.zeros(QH),
                    jnp.float32),
    ])
    out = unified_ragged_paged_attention_pallas(
        case["q"], case["k_pages"], case["v_pages"], case["desc"],
        case["seq_info"], case["decode_list"], case["block_tables"],
        None, feat, sm_scale=sm_scale, bq=case["bq"], sb=case["sb"],
        interpret=True, window=window, logit_cap=logit_cap,
        has_alibi=slopes is not None, has_sinks=sinks is not None)
    got = np.asarray(out)[:case["T"]]
    if not reference:
        return got, None
    return got, reference_feat(case, sm_scale, window=window,
                               logit_cap=logit_cap, slopes=slopes,
                               sinks=sinks)


def reference_feat(case, sm_scale=0.125, *, window=0, logit_cap=0.0,
                   slopes=None, sinks=None):
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"],
        case["block_tables"], case["req_idx"], case["q_pos"],
        sm_scale=sm_scale, window=window, logit_cap=logit_cap,
        alibi_slopes=(tuple(slopes) if slopes is not None else None),
        sinks=(jnp.asarray(sinks) if sinks is not None else None))
    return np.asarray(want)[:case["T"]]


MIXED_SEQS = [(1, 13), (12, 12), (1, 30), (5, 21)]


def test_sliding_window_mixed_wave():
    rng = np.random.default_rng(10)
    case = build_case(rng, seqs=MIXED_SEQS, page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=4,
                      head_dim=128)
    got, want = run_both_feat(case, window=9)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # The window genuinely restricts attention (long kv sequences
    # diverge from full-causal; the reference's, which the cases above
    # hold the kernel to, so that no second program is traced).
    assert np.max(np.abs(got - reference_feat(case))) > 1e-3


def test_softcap_mixed_wave():
    rng = np.random.default_rng(11)
    case = build_case(rng, seqs=MIXED_SEQS, page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=4,
                      head_dim=128)
    got, want = run_both_feat(case, logit_cap=5.0)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_alibi_mixed_wave():
    from vllm_distributed_tpu.models.common import alibi_slopes
    rng = np.random.default_rng(12)
    case = build_case(rng, seqs=MIXED_SEQS, page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=4,
                      head_dim=128)
    got, want = run_both_feat(case, slopes=np.asarray(alibi_slopes(8)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_sinks_mixed_wave():
    rng = np.random.default_rng(13)
    case = build_case(rng, seqs=MIXED_SEQS, page_size=8,
                      pages_per_req=4, num_q_heads=8, num_kv_heads=4,
                      head_dim=128)
    got, want = run_both_feat(
        case, sinks=rng.standard_normal(8).astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_all_features_together_mixed_wave():
    from vllm_distributed_tpu.models.common import alibi_slopes
    rng = np.random.default_rng(14)
    seqs = [(1, 13), (12, 12), (1, 30), (5, 21), (1, 9), (8, 17)]
    case = build_case(rng, seqs=seqs, page_size=8, pages_per_req=4,
                      num_q_heads=8, num_kv_heads=4, head_dim=128)
    got, want = run_both_feat(
        case, window=11, logit_cap=4.0,
        slopes=np.asarray(alibi_slopes(8)),
        sinks=rng.standard_normal(8).astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# A prefill tile whose score rows fill the lanes they lie on (128 a K/V
# head: 16 query heads on one K/V head under the smallest tile), and bf16
# Q and pages as every served path passes them. The bf16 cases are held
# to a float32 reference of the SAME bf16 inputs, so what is measured is
# the kernel's own rounding (the probabilities and the output) and not
# the inputs'.
# ---------------------------------------------------------------------------

BF16_SEQS = [(1, 13), (12, 12), (1, 30), (5, 21)]
NARROW, WIDE = (4, 2), (16, 1)   # 16 and 128 score rows a K/V head
BF16_CASES = {
    "prefill": ([(7, 7), (12, 20)], NARROW, {}),
    "decode": ([(1, 9), (1, 3), (1, 17)], NARROW, {}),
    "mixed_wide": (BF16_SEQS, WIDE, {}),
    "window": (BF16_SEQS, WIDE, dict(window=9)),
    "logit_cap": (BF16_SEQS, WIDE, dict(logit_cap=5.0)),
    "alibi": (BF16_SEQS, WIDE,
              dict(slopes=np.float32(2.0) ** -np.arange(1, 17, dtype=np.float32))),
    "sinks": (BF16_SEQS, WIDE,
              dict(sinks=np.linspace(-1.5, 2.0, 16, dtype=np.float32))),
}


def test_all_features_together_at_128_score_rows():
    """Window, soft cap, ALiBi and sinks at once on a tile of 128 score
    rows a K/V head, in float32 at the float32 tolerance."""
    rng = np.random.default_rng(15)
    qh, kvh = WIDE
    case = build_case(rng, seqs=BF16_SEQS, page_size=16, pages_per_req=2,
                      num_q_heads=qh, num_kv_heads=kvh, head_dim=128,
                      bq=8, sb=2)
    got, want = run_both_feat(
        case, window=11, logit_cap=4.0,
        slopes=np.float32(2.0) ** -np.arange(1, qh + 1, dtype=np.float32),
        sinks=rng.standard_normal(qh).astype(np.float32))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("name", list(BF16_CASES))
def test_bf16_matches_float32_reference(name):
    seqs, (qh, kvh), feats = BF16_CASES[name]
    rng = np.random.default_rng(3)
    # The smallest tile and group: a trace of seconds (``test_bf16_mixed``
    # and the float32 cases above run the model's own sizes).
    case = build_case(rng, seqs=seqs, page_size=16, pages_per_req=2,
                      num_q_heads=qh, num_kv_heads=kvh, head_dim=128,
                      dtype=jnp.bfloat16, bq=8, sb=2)
    assert case["q"].dtype == case["k_pages"].dtype == jnp.bfloat16
    got, _ = run_both_feat(case, reference=False, **feats)
    assert got.dtype == jnp.bfloat16
    upcast = dict(case, **{k: case[k].astype(jnp.float32)
                           for k in ("q", "k_pages", "v_pages")})
    want = reference_feat(upcast, **feats)
    np.testing.assert_allclose(np.float32(got), want, rtol=3e-2,
                               atol=3e-2)


# ---------------------------------------------------------------------------
# Descriptor builder
# ---------------------------------------------------------------------------


def test_descriptor_partition_shape():
    """Mixed batch: kv-write rows first, one prefill tile per bq rows,
    decode groups of sb covering every q_len == 1 sequence, noop
    padding after."""
    si = np.zeros((8, 4), np.int32)
    # rows: 40-token prefill, decode, 3-token prefill, decode, decode
    for r, (ql, kl) in enumerate([(40, 40), (1, 9), (3, 7), (1, 2),
                                  (1, 30)]):
        si[r] = (0, ql, kl, r)
    bq, sb = 32, 2
    P = num_partition_programs(64, 8, bq=bq, sb=sb, num_kv_writes=4)
    desc, dl = build_partition_descriptor(si, 5, bq=bq, sb=sb,
                                          num_programs=P,
                                          num_kv_writes=4)
    kinds = desc[:, 0]
    assert list(kinds[:4]) == [KIND_KV_WRITE] * 4
    assert list(desc[:4, 1]) == [0, 1, 2, 3]
    prefill = desc[kinds == KIND_PREFILL]
    # 40 tokens -> tiles at 0 and 32; 3 tokens -> one tile.
    assert {(int(a), int(b)) for _, a, b in prefill} == {
        (0, 0), (0, 32), (2, 0)}
    groups = desc[kinds == KIND_DECODE]
    # 3 decode rows in sb=2 groups: (start 0, 2 slots), (start 2, 1).
    assert [(int(a), int(b)) for _, a, b in groups] == [(0, 2), (2, 1)]
    assert list(dl[:3]) == [1, 3, 4]
    # Everything else is noop padding.
    n_active = 4 + len(prefill) + len(groups)
    assert (kinds[n_active:] == KIND_NOOP).all()


def test_descriptor_fast_decode_rows_bypass():
    """The runner's pure-decode fast path hands its row vector straight
    in; the builder must not rescan q_lens."""
    si = np.zeros((4, 4), np.int32)
    si[:, 1] = 99  # garbage q_lens: must be ignored with decode_rows
    desc, dl = build_partition_descriptor(
        si, 3, bq=32, sb=8,
        num_programs=num_partition_programs(16, 4, bq=32, sb=8),
        decode_rows=np.arange(3, dtype=np.int32))
    kinds = desc[:, 0]
    assert (kinds != KIND_PREFILL).all()
    groups = desc[kinds == KIND_DECODE]
    assert [(int(a), int(b)) for _, a, b in groups] == [(0, 3)]
    assert list(dl[:3]) == [0, 1, 2]


@pytest.mark.parametrize("t", [16, 64, 256])
def test_descriptor_length_is_deterministic_in_bucket(t):
    """num_partition_programs depends only on (t_bucket, max_reqs, bq,
    sb, kv bound) — the descriptor adds no compile-lattice dimension."""
    sizes = {
        num_partition_programs(t, 8, bq=32, sb=4, num_kv_writes=g)
        for g in (0, 0, 0)
    }
    assert len(sizes) == 1
