"""Mixed-batch attention mega-kernel vs the dense reference.

Interpret-mode parity matrix for ops/pallas_attention.py's unified
kernel (ISSUE 6 tentpole): mixed prefill+decode batches, GQA grouping,
q_len spanning page boundaries and multiple q tiles, single-token
prefill tails, the emit_state cascade path, and the fused KV-write +
attend variant. The partition-descriptor builder is unit-tested on the
same cases; the page loop under a window (trimmed, not only masked) is
held to poisoned table slots at the end of the file.

**What a case costs.** The data are free; the program is not. A call
with new shapes or new static arguments lowers ``_mega_kernel``'s
interpreted body again (the first call of a geometry is seconds to a
minute, four fifths of it jaxpr-to-MLIR conversion that no cache keeps;
the second is hundredths of a second). The body's size, and so the time,
grows with what the kernel unrolls in Python: ``sb x ppb`` (a decode
group's page copies; ``ppb`` is pages a 128-position kv block, at most
the table's width) first, then ``bq`` (a tile's 8-row chunks). Query and
K/V heads, ``head_dim``, the number of pages and of programs cost
nothing. So a new case that differs from an old one only in its data
(lengths, seed, poisoned slots) passes the old one's ``t_bucket`` and
``max_reqs`` to ``build_case`` and reuses its program, as the runner's
own buckets do; a case that needs statics of its own (a feature, a
window, ``emit_state``) takes ``SMALL`` (the 8-row tile, groups of two,
two pages a kv block: 3-5 s) unless its point is the model's own tile,
and says so."""

import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.core.kv_cache_utils import num_window_dead_blocks
from vllm_distributed_tpu.ops.attention import (_shared_prefix_state,
                                                merge_attention_states,
                                                naive_ragged_attention,
                                                write_kv_pages)
from vllm_distributed_tpu.ops.pallas_attention import (
    KIND_DECODE, KIND_KV_WRITE, KIND_NOOP, KIND_PREFILL, Q_TILE_PAD,
    build_partition_descriptor, decode_group_size, decode_kv_tokens_walked,
    kv_block_pages, num_partition_programs, prefill_tile_size,
    unified_ragged_paged_attention_pallas, unified_write_attend_pallas)


# The cheapest geometry to lower: the smallest tile and group over two
# pages a kv block (the module docstring says what the time grows with).
SMALL = dict(page_size=16, pages_per_req=2, bq=8, sb=2)


def build_case(rng, *, seqs, page_size, pages_per_req, num_q_heads,
               num_kv_heads, head_dim, dtype=jnp.float32, bq=None, sb=None,
               t_bucket=None, max_reqs=None):
    """seqs: list of (q_len, kv_len) with kv_len >= q_len. ``bq`` and
    ``sb`` default to the model's own tile sizes; a smaller pair traces
    a smaller interpreted program. ``t_bucket`` (token rows) and
    ``max_reqs`` (table rows) pad the arrays as the runner's buckets do:
    cases that pass the same pair, geometry and statics share one
    compiled program. Left out, they fit the case."""
    R = len(seqs)
    max_reqs = max_reqs or R + 1  # at least one inactive padding row
    assert max_reqs > R
    num_pages = max_reqs * pages_per_req
    T = sum(q for q, _ in seqs)
    t_bucket = t_bucket or T
    assert t_bucket >= T
    T_pad = t_bucket + Q_TILE_PAD

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
    P = num_partition_programs(t_bucket, max_reqs, bq=bq, sb=sb)
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


# The model's own tile and group (32 rows, 8 sequences) over four pages a
# kv block, in a bucket of 32 tokens and 12 table rows: every float32
# case without a feature that differs from another only in its lengths
# runs this ONE program.
MODEL_TILE = dict(page_size=8, pages_per_req=4, num_q_heads=8,
                  num_kv_heads=4, head_dim=128, t_bucket=32, max_reqs=12)


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
    case = build_case(rng, seqs=seqs, **MODEL_TILE)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kvh", [1, 2, 8])
def test_gqa_group_and_mha(kvh):
    # The grouping is the kernel's ``group`` static and the K/V buffers'
    # width, whatever the tile: the small one.
    rng = np.random.default_rng(1)
    case = build_case(rng, seqs=[(3, 11), (1, 4)], num_q_heads=8,
                      num_kv_heads=kvh, head_dim=128, **SMALL)
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_multi_tile_prefill_spans_tiles_and_pages():
    """q_len > bq spans several prefill tiles of one sequence; kv spans
    several pages. The exact chunked writeback must stitch tiles
    seamlessly (no spill into the neighbouring decode row). A tile of
    16 rows is two 8-row chunks, so both a tile's inner stitch and the
    stitch between tiles are there: three tiles a sequence, the second
    one's last of a single row."""
    rng = np.random.default_rng(2)
    case = build_case(rng, seqs=[(40, 40), (1, 30), (33, 48)],
                      page_size=8, pages_per_req=8, num_q_heads=4,
                      num_kv_heads=4, head_dim=128, bq=16, sb=2)
    assert 8 < case["bq"] < 33  # the case really is multi-tile
    got, want = run_both(case)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_many_decode_seqs_ragged_groups():
    """Enough decode sequences for several SB groups, with a group count
    that does not divide the batch."""
    rng = np.random.default_rng(7)
    seqs = [(1, k) for k in (1, 5, 17, 32, 9, 25, 13, 2, 31, 8, 20)]
    case = build_case(rng, seqs=seqs, **MODEL_TILE)
    assert len(seqs) % case["sb"] and len(seqs) > case["sb"]
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
    # Sixteen shared positions in one page: the suffix call's table is
    # then two pages wide, one kv block of two page copies a sequence.
    page_size, S = 16, 1
    case = build_case(rng, seqs=[(1, 20), (4, 24), (1, 33), (6, 22)],
                      page_size=page_size, pages_per_req=3,
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
    must see this step's freshly written pages. The kv-write programs
    do not know the attention's tile: the small one (the engine tests
    run the fused call at the model's own)."""
    rng = np.random.default_rng(5)
    page_size = 8
    seqs = [(1, 20), (5, 24), (1, 33), (7, 22)]
    case = build_case(rng, seqs=seqs, page_size=page_size,
                      pages_per_req=6, num_q_heads=8, num_kv_heads=4,
                      head_dim=128, bq=8, sb=2)
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
# Each feature is a static argument and so a program of its own: on the
# small tile (a 12-token prompt is still two tiles, four decode rows two
# groups), the sequences of 30 and 21 past the window of 9.
MIXED = dict(seqs=MIXED_SEQS, num_q_heads=8, num_kv_heads=4, head_dim=128,
             **SMALL)


def test_sliding_window_mixed_wave():
    rng = np.random.default_rng(10)
    case = build_case(rng, **MIXED)
    got, want = run_both_feat(case, window=9)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # The window genuinely restricts attention (long kv sequences
    # diverge from full-causal; the reference's, which the cases above
    # hold the kernel to, so that no second program is traced).
    assert np.max(np.abs(got - reference_feat(case))) > 1e-3


def test_softcap_mixed_wave():
    rng = np.random.default_rng(11)
    case = build_case(rng, **MIXED)
    got, want = run_both_feat(case, logit_cap=5.0)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_alibi_mixed_wave():
    from vllm_distributed_tpu.models.common import alibi_slopes
    rng = np.random.default_rng(12)
    case = build_case(rng, **MIXED)
    got, want = run_both_feat(case, slopes=np.asarray(alibi_slopes(8)))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_sinks_mixed_wave():
    rng = np.random.default_rng(13)
    case = build_case(rng, **MIXED)
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
    case = build_case(rng, seqs=BF16_SEQS, num_q_heads=qh,
                      num_kv_heads=kvh, head_dim=128, **SMALL)
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
    # One bucket of 24 tokens and 5 table rows, so that cases with the
    # same heads and features ("prefill", "decode") are one program.
    case = build_case(rng, seqs=seqs, num_q_heads=qh, num_kv_heads=kvh,
                      head_dim=128, dtype=jnp.bfloat16, t_bucket=24,
                      max_reqs=5, **SMALL)
    assert case["q"].dtype == case["k_pages"].dtype == jnp.bfloat16
    got, _ = run_both_feat(case, reference=False, **feats)
    assert got.dtype == jnp.bfloat16
    upcast = dict(case, **{k: case[k].astype(jnp.float32)
                           for k in ("q", "k_pages", "v_pages")})
    want = reference_feat(upcast, **feats)
    np.testing.assert_allclose(np.float32(got), want, rtol=3e-2,
                               atol=3e-2)


# ---------------------------------------------------------------------------
# The page loop under a window: trimmed, not only masked.
#
# A window layer's prefill tile starts at the kv block that holds the
# oldest position its first query sees and a decode sequence at the block
# that holds ``kv_len - window`` (``_mega_kernel``). Held against the
# masked XLA path over the WHOLE table, with every table slot in front of
# the first visited block pointed at a page of NaN: an untrimmed loop
# multiplies a zero probability by NaN and shows it. Dead slots inside the
# first visited block carry the placeholder page, as the window group's
# tables do (core/kv_cache_manager.py).
#
# What the trim needs is whole 128-position kv blocks in front of the
# window: four a request, as before. What it does not need is sixteen
# pages in each (the 8-token page these cases had: a minute a program to
# lower): pages of 32 make a block of four, so a first visited block still
# holds up to three dead slots.
# ---------------------------------------------------------------------------

TRIM_PAGE, TRIM_PAGES_PER_REQ, BLK = 32, 16, 128

# (q_len, kv_len): prefill tiles past the window, decode groups whose
# sequences start their walks at different blocks, a short one.
TRIM_SEQS = [(1, 500), (40, 300), (1, 140), (1, 9), (24, 480), (1, 260)]


def _trimmed_table(case, window: int):
    """The case's table as a window group hands it over: slots that
    ``num_window_dead_blocks`` frees read the placeholder page 0, and
    every slot in front of a sequence's first visited kv block reads
    the poisoned page."""
    bt = case["block_tables_np"].copy()
    poison = int(bt.max()) + 1
    ppb = BLK // TRIM_PAGE
    placeholders = 0
    for q_start, q_len, kv_len, row in case["seq_info_np"][:case["num_seqs"]]:
        computed = kv_len - q_len
        dead = num_window_dead_blocks(computed, window, TRIM_PAGE)
        first_block = max(computed - window + 1, 0) // BLK
        bt[row, :dead] = 0
        bt[row, :first_block * ppb] = poison
        placeholders += max(dead - first_block * ppb, 0)
    return bt, poison, placeholders


def _run_on_poisoned_table(case, bt, **static):
    nan_page = jnp.full((1, ) + case["k_pages"].shape[1:], jnp.nan,
                        case["k_pages"].dtype)
    # Page 0 is request row 0's first page: live data of another
    # request, finite, read under the mask only.
    got = unified_ragged_paged_attention_pallas(
        case["q"], jnp.concatenate([case["k_pages"], nan_page]),
        jnp.concatenate([case["v_pages"], nan_page]), case["desc"],
        case["seq_info"], case["decode_list"], jnp.asarray(bt),
        sm_scale=0.125, bq=case["bq"], sb=case["sb"], interpret=True,
        **static)
    return np.asarray(got)[:case["T"]]


@pytest.mark.parametrize("window,qh,kvh,tiles", [
    # A multiple of the 128-position kv block, and two that are not,
    # under a group of 16 query heads on one K/V head (128 score rows a
    # tile of 8: the window is a static, so each is a program, and the
    # small tile's; its groups of two still pair sequences that start
    # their walks at different blocks); two blocks under a group of 2 on
    # four, at the model's own tile and group.
    (128, 16, 1, dict(bq=8, sb=2)), (100, 16, 1, dict(bq=8, sb=2)),
    (130, 16, 1, dict(bq=8, sb=2)), (256, 8, 4, {})],
    ids=["128-16-1", "100-16-1", "130-16-1", "256-8-4"])
def test_trimmed_loop_matches_masked_reference(window, qh, kvh, tiles):
    rng = np.random.default_rng(window + qh)
    case = build_case(rng, seqs=TRIM_SEQS, page_size=TRIM_PAGE,
                      pages_per_req=TRIM_PAGES_PER_REQ, num_q_heads=qh,
                      num_kv_heads=kvh, head_dim=128, **tiles)
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=0.125, window=window)
    bt, poison, placeholders = _trimmed_table(case, window)
    assert (bt == poison).any(), "the case must trim something"
    assert placeholders, "no dead slot inside a first visited block"
    got = _run_on_poisoned_table(case, bt, window=window)
    assert np.isfinite(got).all(), "a slot in front of the window was read"
    np.testing.assert_allclose(got, np.asarray(want)[:case["T"]],
                               rtol=2e-3, atol=2e-3)


def test_untrimmed_program_reads_the_whole_table():
    """The control of the test above: without a window the same table
    poisons the output, so the poison is where the loop would walk."""
    rng = np.random.default_rng(5)
    case = build_case(rng, seqs=TRIM_SEQS[:2], page_size=TRIM_PAGE,
                      pages_per_req=TRIM_PAGES_PER_REQ, num_q_heads=8,
                      num_kv_heads=4, head_dim=128, bq=8, sb=2)
    bt, poison, _ = _trimmed_table(case, 128)
    assert not np.isfinite(_run_on_poisoned_table(case, bt)).all()


# ---------------------------------------------------------------------------
# Decode groups: formed by length, and no page moved past a sequence's end.
#
# ``build_partition_descriptor`` cuts the single-token rows into groups
# longest context first, and a group's slot starts and waits for its page
# copies only while its own sequence has a kv block left
# (``_mega_kernel``). Who shares a group changes no row's result (the
# block-diagonal mask) and a trip a slot sits out adds exactly nothing to
# it, so the two orders give the same BITS; the slot's buffers then hold
# what an earlier trip, an earlier program or nobody left there, which is
# why the poisoned tables below also alias the inactive slots to a table
# row of NaN pages (interpret mode starts scratch buffers as NaN).
# ---------------------------------------------------------------------------

# Thirteen single-token sequences of 1 to 512 positions, some equal, over
# kv blocks of 128 (two pages of 64): 13 divides by none of the widths.
ORDER_LENS = (1, 5, 130, 512, 9, 257, 128, 2, 384, 300, 129, 130, 9)
ORDER = dict(seqs=[(1, k) for k in ORDER_LENS], page_size=64,
             pages_per_req=8, num_q_heads=4, num_kv_heads=2, head_dim=128,
             bq=8)


@pytest.mark.parametrize("sb", [2, 4, 8])
@pytest.mark.parametrize("window", [0, 200], ids=["full", "window200"])
def test_length_order_and_arrival_order_give_the_same_bits(window, sb):
    rng = np.random.default_rng(100 * sb + window)
    case = build_case(rng, sb=sb, **ORDER)
    n = len(ORDER_LENS)
    assert n % sb and n > sb
    by_length = np.asarray(case["decode_list"])
    assert (np.diff(np.asarray(ORDER_LENS)[by_length[:n]]) <= 0).all()
    arrival = by_length.copy()
    arrival[:n] = np.arange(n)
    assert (arrival != by_length).any()
    got = [np.asarray(unified_ragged_paged_attention_pallas(
        case["q"], case["k_pages"], case["v_pages"], case["desc"],
        case["seq_info"], jnp.asarray(dl), case["block_tables"],
        sm_scale=0.125, bq=case["bq"], sb=sb, interpret=True,
        window=window))[:n] for dl in (by_length, arrival)]
    np.testing.assert_array_equal(got[0], got[1])
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=0.125, window=window)
    np.testing.assert_allclose(got[0], np.asarray(want)[:n], rtol=2e-3,
                               atol=2e-3)


# Five single-token rows in groups of two (500 and 390, 260 and 140, 9
# beside an inactive slot) and two prefill rows.
ENDED_SEQS = TRIM_SEQS + [(1, 390)]


def _ended_case(window: int):
    """The trimmed-loop geometry with, besides what ``_trimmed_table``
    poisons in front of a window, every table slot past a sequence's
    last kv block poisoned, and the inactive slots aliased to a padding
    row whose whole table row is poisoned (they alias sequence 0 as the
    builder leaves them: a live row, which proves nothing)."""
    rng = np.random.default_rng(window + 7)
    case = build_case(rng, seqs=ENDED_SEQS, page_size=TRIM_PAGE,
                      pages_per_req=TRIM_PAGES_PER_REQ, num_q_heads=16,
                      num_kv_heads=1, head_dim=128, bq=8, sb=2)
    if window:
        bt, poison, _ = _trimmed_table(case, window)
    else:
        bt = case["block_tables_np"].copy()
        poison = int(bt.max()) + 1
    ppb = BLK // TRIM_PAGE
    R = case["num_seqs"]
    for _, _, kv_len, row in case["seq_info_np"][:R]:
        bt[row, -(-kv_len // BLK) * ppb:] = poison
    assert (bt[:R] == poison).sum() >= 5 * ppb
    bt[R] = poison
    si = case["seq_info_np"].copy()
    si[R] = (0, 0, 0, R)
    dl = np.asarray(case["decode_list"]).copy()
    n_dec = int((si[:R, 1] == 1).sum())
    assert n_dec % case["sb"], "no inactive slot"
    dl[n_dec:] = R
    case.update(seq_info=jnp.asarray(si), decode_list=jnp.asarray(dl))
    return case, bt


@pytest.mark.parametrize("window", [0, 128], ids=["full", "window128"])
def test_no_page_moved_past_a_sequences_end_nor_by_an_inactive_slot(window):
    case, bt = _ended_case(window)
    static = dict(window=window) if window else {}
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=0.125, **static)
    got = _run_on_poisoned_table(case, bt, **static)
    assert np.isfinite(got).all(), "a page nobody can see was moved"
    np.testing.assert_allclose(got, np.asarray(want)[:case["T"]],
                               rtol=2e-3, atol=2e-3)
    # The control: the same table with the last group's inactive slot
    # counted as live (a kv_len of 0 still fetches one block) brings the
    # aliased row's NaN into the group.
    desc = case["desc_np"].copy()
    last = np.nonzero(desc[:, 0] == KIND_DECODE)[0][-1]
    assert desc[last, 2] == 1
    desc[last, 2] = 2
    case["desc"] = jnp.asarray(desc)
    assert not np.isfinite(_run_on_poisoned_table(case, bt, **static)).all()


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
    # Longest context first: rows 4 (30), 1 (9), 3 (2).
    assert list(dl[:3]) == [4, 1, 3]
    # Everything else is noop padding.
    n_active = 4 + len(prefill) + len(groups)
    assert (kinds[n_active:] == KIND_NOOP).all()


def test_descriptor_fast_decode_rows_bypass():
    """The runner's pure-decode fast path hands its row vector straight
    in; the builder must not rescan q_lens. The rows are cut by length
    on this way in too."""
    si = np.zeros((4, 4), np.int32)
    si[:, 1] = 99  # garbage q_lens: must be ignored with decode_rows
    si[:3, 2] = (7, 300, 41)
    desc, dl = build_partition_descriptor(
        si, 3, bq=32, sb=8,
        num_programs=num_partition_programs(16, 4, bq=32, sb=8),
        decode_rows=np.arange(3, dtype=np.int32))
    kinds = desc[:, 0]
    assert (kinds != KIND_PREFILL).all()
    groups = desc[kinds == KIND_DECODE]
    assert [(int(a), int(b)) for _, a, b in groups] == [(0, 3)]
    assert list(dl[:3]) == [1, 2, 0]


@pytest.mark.parametrize("fast", [False, True], ids=["scan", "decode_rows"])
def test_descriptor_cuts_groups_by_length_stable_among_equals(fast):
    """Both ways in: the single-token rows longest context first, rows
    of one length in arrival order, prefill rows where they were; the
    groups' starts and counts are those of the arrival order."""
    kv = [40, 7, 300, 7, 129, 40, 7, 512, 1, 300, 40]
    si = np.zeros((16, 4), np.int32)
    si[:len(kv), 1] = 1
    si[:len(kv), 2] = kv
    rows = np.arange(len(kv), dtype=np.int32)
    if not fast:
        si[4, 1] = si[4, 2] = 20   # a prefill row among them
        rows = np.delete(rows, 4)
    sb = 4
    desc, dl = build_partition_descriptor(
        si, len(kv), bq=32, sb=sb,
        num_programs=num_partition_programs(64, 16, bq=32, sb=sb),
        decode_rows=rows if fast else None)
    want = sorted(rows, key=lambda r: -si[r, 2])   # sorted() is stable
    assert list(dl[:len(rows)]) == want
    assert (dl[len(rows):] == 0).all()
    lens = si[dl[:len(rows)], 2]
    assert (np.diff(lens) <= 0).all()
    groups = desc[desc[:, 0] == KIND_DECODE]
    n = len(rows)
    assert [(int(a), int(b)) for _, a, b in groups] == [
        (g, min(sb, n - g)) for g in range(0, n, sb)]
    if not fast:
        assert [(int(a), int(b)) for _, a, b in
                desc[desc[:, 0] == KIND_PREFILL]] == [(4, 0)]


def test_walked_counter_is_what_the_descriptor_groups_fetch():
    """``decode_kv_tokens_walked`` (the runner's
    ``vdt:step_decode_kv_tokens_walked``) against a walk over the
    descriptor of a hand-made step as ``_mega_kernel`` makes it: a
    group's slot fetches a block a trip while its own sequence has one
    left. Beside it the rule before the groups were cut by length
    (every live slot fetched on every trip of its group's longest
    walk), on the arrival order: what ordering and skipping save."""
    kv = np.array([40, 700, 129, 1, 128, 1030, 257, 90, 513, 300, 5],
                  np.int32)
    si = np.zeros((16, 4), np.int32)
    si[:len(kv), 1] = 1
    si[:len(kv), 2] = kv
    si[3, 1] = si[3, 2] = 77    # a prefill row: not a decode slot
    sb, page_size, pages_per_req = 4, 16, 80
    blk = page_size * kv_block_pages(page_size, pages_per_req)
    assert blk == 128
    desc, dl = build_partition_descriptor(
        si, len(kv), bq=32, sb=sb,
        num_programs=num_partition_programs(128, 16, bq=32, sb=sb))
    fetched = arrival = 0
    decode_rows = np.nonzero(si[:len(kv), 1] == 1)[0]
    for _, a, cnt in desc[desc[:, 0] == KIND_DECODE]:
        blocks = [-(-int(si[dl[a + i], 2]) // blk) for i in range(cnt)]
        for trip in range(max(blocks)):
            fetched += sum(trip < n for n in blocks)
        old = [-(-int(si[r, 2]) // blk) for r in decode_rows[a:a + cnt]]
        arrival += cnt * max(old)
    got = decode_kv_tokens_walked(si[decode_rows, 2], blk)
    assert got == fetched * blk == 32 * 128
    assert arrival * blk == 66 * 128


@pytest.mark.parametrize("t", [16, 64, 256])
def test_descriptor_length_is_deterministic_in_bucket(t):
    """num_partition_programs depends only on (t_bucket, max_reqs, bq,
    sb, kv bound) — the descriptor adds no compile-lattice dimension."""
    sizes = {
        num_partition_programs(t, 8, bq=32, sb=4, num_kv_writes=g)
        for g in (0, 0, 0)
    }
    assert len(sizes) == 1
