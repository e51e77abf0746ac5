"""The mega-kernel's page loop under a window: trimmed, not only masked.

A window layer's prefill tile starts at the kv block that holds the
oldest position its first query sees and a decode sequence at the block
that holds ``kv_len - window`` (ops/pallas_attention.py ``_mega_kernel``).
Held against the masked XLA path over the WHOLE table, with every table
slot in front of the first visited block pointed at a page of NaN: an
untrimmed loop multiplies a zero probability by NaN and shows it. Dead
slots inside the first visited block carry the placeholder page, as the
window group's tables do (core/kv_cache_manager.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.ops.test_unified_attention import build_case
from vllm_distributed_tpu.core.kv_cache_utils import num_window_dead_blocks
from vllm_distributed_tpu.ops.attention import naive_ragged_attention
from vllm_distributed_tpu.ops.pallas_attention import (
    unified_ragged_paged_attention_pallas)

PAGE, PAGES_PER_REQ, BLK = 8, 64, 128     # four kv blocks a request

# (q_len, kv_len): prefill tiles past the window, decode groups whose
# sequences start their walks at different blocks, a short one.
SEQS = [(1, 500), (40, 300), (1, 140), (1, 9), (24, 480), (1, 260)]


def _trimmed_table(case, window: int):
    """The case's table as a window group hands it over: slots that
    ``num_window_dead_blocks`` frees read the placeholder page 0, and
    every slot in front of a sequence's first visited kv block reads
    the poisoned page."""
    bt = case["block_tables_np"].copy()
    poison = int(bt.max()) + 1
    ppb = BLK // PAGE
    for q_start, q_len, kv_len, row in case["seq_info_np"][:case["num_seqs"]]:
        computed = kv_len - q_len
        dead = num_window_dead_blocks(computed, window, PAGE)
        first_block = max(computed - window + 1, 0) // BLK
        bt[row, :dead] = 0
        bt[row, :first_block * ppb] = poison
    return bt, poison


@pytest.mark.parametrize("window,qh,kvh", [
    # A multiple of the 128-position kv block, and two that are not,
    # under a group of 16 query heads on one K/V head; two blocks under
    # a group of 2 on four.
    (128, 16, 1), (100, 16, 1), (130, 16, 1), (256, 8, 4)])
def test_trimmed_loop_matches_masked_reference(window, qh, kvh):
    rng = np.random.default_rng(window + qh)
    case = build_case(rng, seqs=SEQS, page_size=PAGE,
                      pages_per_req=PAGES_PER_REQ, num_q_heads=qh,
                      num_kv_heads=kvh, head_dim=128)
    want = naive_ragged_attention(
        case["q"], case["k_pages"], case["v_pages"], case["block_tables"],
        case["req_idx"], case["q_pos"], sm_scale=0.125, window=window)
    bt, poison = _trimmed_table(case, window)
    assert (bt == poison).any(), "the case must trim something"
    nan_page = jnp.full((1, ) + case["k_pages"].shape[1:], jnp.nan,
                        case["k_pages"].dtype)
    # Page 0 is request row 0's first page: live data of another
    # request, finite, read under the mask only.
    got = unified_ragged_paged_attention_pallas(
        case["q"], jnp.concatenate([case["k_pages"], nan_page]),
        jnp.concatenate([case["v_pages"], nan_page]), case["desc"],
        case["seq_info"], case["decode_list"], jnp.asarray(bt),
        sm_scale=0.125, bq=case["bq"], sb=case["sb"], interpret=True,
        window=window)
    T = case["T"]
    got = np.asarray(got)[:T]
    assert np.isfinite(got).all(), "a slot in front of the window was read"
    np.testing.assert_allclose(got, np.asarray(want)[:T], rtol=2e-3,
                               atol=2e-3)


def test_untrimmed_program_reads_the_whole_table():
    """The control of the test above: without a window the same table
    poisons the output, so the poison is where the loop would walk."""
    rng = np.random.default_rng(5)
    case = build_case(rng, seqs=SEQS[:2], page_size=PAGE,
                      pages_per_req=PAGES_PER_REQ, num_q_heads=8,
                      num_kv_heads=4, head_dim=128)
    bt, poison = _trimmed_table(case, 128)
    nan_page = jnp.full((1, ) + case["k_pages"].shape[1:], jnp.nan,
                        case["k_pages"].dtype)
    got = unified_ragged_paged_attention_pallas(
        case["q"], jnp.concatenate([case["k_pages"], nan_page]),
        jnp.concatenate([case["v_pages"], nan_page]), case["desc"],
        case["seq_info"], case["decode_list"], jnp.asarray(bt),
        sm_scale=0.125, bq=case["bq"], sb=case["sb"], interpret=True)
    assert not np.isfinite(np.asarray(got)[:case["T"]]).all()
