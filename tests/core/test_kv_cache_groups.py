"""Two kinds of K/V in one manager (``GroupedKVCacheManager``): pages held
by group against the bound as a request grows, is chunked, preempted and
resumed; a grant is whole or nothing; what the worker is handed; a model
with one group builds the single table it always built."""

import numpy as np
import pytest

from vllm_distributed_tpu.core.kv_cache_manager import (
    GroupedKVCacheManager, KVCacheManager)
from vllm_distributed_tpu.core.kv_cache_utils import (
    num_window_dead_blocks, window_group_pool_blocks)
from vllm_distributed_tpu.core.sched.output import (CachedRequestData,
                                                    NewRequestData)
from vllm_distributed_tpu.request import Request
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.worker.input_batch import InputBatch
from vllm_distributed_tpu.worker.step_inputs import StepLayout

BLOCK, WINDOW = 16, 64


def make_request(rid: str, n: int) -> Request:
    return Request(request_id=rid, prompt_token_ids=list(range(n)),
                   sampling_params=SamplingParams(max_tokens=10_000))


def grouped(full=256, window=None, seqs=4, budget=128, max_len=4096):
    window = window or window_group_pool_blocks(
        WINDOW, BLOCK, seqs, budget, max_len // BLOCK)
    return GroupedKVCacheManager(BLOCK, full, window, WINDOW)


def held(mgr, rid):
    return (sum(b is not None for b in mgr.full_group.req_to_blocks[rid]),
            sum(b is not None for b in mgr.window_group.req_to_blocks[rid]))


@pytest.mark.parametrize("chunk", [128, 48, 16, 7])
def test_pages_held_by_group_as_a_request_grows(chunk):
    """A 1 000-token prompt in chunks, then 200 decode steps: the full
    group holds cdiv(context, block) pages, the window group never more
    than (window + scheduled) / block + 2, and the slots it nulled are
    exactly those no query can see."""
    mgr = grouped()
    req = make_request("r", 1000)
    freed_before = 0
    while req.num_computed_tokens < 1200:
        n = (min(chunk, 1000 - req.num_computed_tokens)
             if req.num_computed_tokens < 1000 else 1)
        got = mgr.allocate_slots(req, n)
        assert got is not None
        assert len(got.get_block_ids()) == len(got.get_window_block_ids())
        full, win = held(mgr, "r")
        context = req.num_computed_tokens + n
        assert full == -(-context // BLOCK)
        assert win <= (WINDOW + n) // BLOCK + 2
        dead = int(num_window_dead_blocks(req.num_computed_tokens, WINDOW,
                                          BLOCK))
        ids = mgr.get_window_block_ids("r")
        assert len(ids) == len(mgr.get_block_ids("r")) == full
        assert ids[:dead] == [0] * dead
        assert [b is None for b in
                mgr.window_group.req_to_blocks["r"]].count(True) == dead
        assert mgr.window_group.window_pages_freed == dead >= freed_before
        freed_before = dead
        req.num_computed_tokens += n
        if req.num_computed_tokens >= 1000:
            req.append_output_token_ids(7)
    stats = mgr.group_stats()
    assert stats["kv_full_pages_held"] == 75
    assert stats["kv_window_pages_held"] <= WINDOW // BLOCK + 2
    assert stats["kv_window_pages_freed"] == dead > 60
    mgr.free(req)
    assert mgr.get_num_free_blocks() == 256 + \
        mgr.window_group.block_pool.num_blocks
    assert mgr.usage == 0.0


def test_the_window_pool_s_bound_holds_every_sequence_at_once():
    """max_num_seqs requests deep in their contexts, one of them taking
    the step's whole token budget: the pool sized by
    ``window_group_pool_blocks`` grants all of them."""
    seqs, budget = 6, 256
    pool = window_group_pool_blocks(WINDOW, BLOCK, seqs, budget, 4096)
    assert pool == seqs * (WINDOW // BLOCK + 4) + budget // BLOCK
    mgr = grouped(full=4096, window=pool, seqs=seqs, budget=budget)
    reqs = [make_request(f"r{i}", 2000) for i in range(seqs)]
    for step in range(12):
        for i, req in enumerate(reqs):
            n = budget if i == step % seqs else 1
            assert mgr.allocate_slots(req, n) is not None, (step, i)
            req.num_computed_tokens += n
    assert mgr.window_group.get_num_free_blocks() >= 0
    assert mgr.group_stats()["kv_window_pages_held"] <= pool
    # A short context is capped by the table, not the window.
    assert window_group_pool_blocks(4096, 16, 2, 64, 8) == 2 * 8 + 4


def test_a_grant_is_whole_or_nothing():
    """The full group out of pages: nothing is taken from the window
    group either. The window group out of pages: the full group's
    tentative pages go back."""
    mgr = grouped(full=4, window=64)
    a, b = make_request("a", 48), make_request("b", 48)
    assert mgr.allocate_slots(a, 48) is not None
    free = mgr.window_group.get_num_free_blocks()
    assert mgr.allocate_slots(b, 48) is None
    assert mgr.window_group.get_num_free_blocks() == free
    assert mgr.full_group.get_num_free_blocks() == 1
    mgr = grouped(full=64, window=4)
    assert mgr.allocate_slots(a, 48) is not None
    assert mgr.allocate_slots(b, 48) is None
    assert mgr.full_group.get_num_free_blocks() == 61
    assert not mgr.full_group.req_to_blocks.get("b")
    assert mgr.usage == pytest.approx(6 / 68)
    assert mgr.get_num_free_blocks() == 62


def test_preempted_and_resumed_from_token_zero():
    mgr = grouped()
    req = make_request("r", 500)
    for _ in range(4):
        assert mgr.allocate_slots(req, 100) is not None
        req.num_computed_tokens += 100
    assert held(mgr, "r")[0] == 25 and held(mgr, "r")[1] <= 12
    mgr.free(req)                                   # preempted
    assert mgr.get_num_free_blocks() == 256 + \
        mgr.window_group.block_pool.num_blocks
    req.num_computed_tokens = 0
    got = mgr.allocate_slots(req, 100)              # resumed
    assert len(got.get_block_ids()) == len(got.get_window_block_ids()) == 7
    assert mgr.get_window_block_ids("r") == got.get_window_block_ids()
    assert held(mgr, "r") == (7, 7)
    mgr.transfer_ownership("r", "tomb")
    assert held(mgr, "tomb") == (7, 7)
    assert mgr.get_computed_blocks(req)[1] == 0     # nothing is shared
    assert mgr.make_prefix_cache_stats() == {"queries": 0, "hits": 0}


def test_the_worker_s_tables_follow_the_groups():
    """The input batch keeps a second table beside the first, slot for
    slot, through admission, growth and a resume; a model with one group
    never makes it, and its packed step buffer is the one it had."""
    ib = InputBatch(4, 256, 16, BLOCK)
    sp = SamplingParams()
    ib.add_request(NewRequestData("a", [1] * 40, sp, [5, 6, 7], 0,
                                  window_block_ids=[9, 8, 7]))
    row = ib.req_id_to_index["a"]
    assert ib.window_block_table[row, :4].tolist() == [9, 8, 7, 0]
    ib.update_cached(CachedRequestData(
        ["a"], [False], [[2]], [[11]], [[3]], [48]))
    assert ib.block_table[row, :5].tolist() == [5, 6, 7, 11, 0]
    assert ib.window_block_table[row, :5].tolist() == [9, 8, 7, 3, 0]
    ib.update_cached(CachedRequestData(
        ["a"], [True], [[1] * 50], [[1, 2]], [[4, 5]], [0]))
    assert ib.block_table[row, :3].tolist() == [1, 2, 0]
    assert ib.window_block_table[row, :3].tolist() == [4, 5, 0]
    ib.remove_request("a")
    assert not ib.window_block_table.any()

    one = InputBatch(4, 256, 16, BLOCK)
    one.add_request(NewRequestData("a", [1] * 40, sp, [5, 6, 7], 0))
    one.update_cached(CachedRequestData(["a"], [False], [[2]], [[11]],
                                        num_computed_tokens=[48]))
    assert one.window_block_table is None
    names = [n for n, *_ in StepLayout(72, 8, 4, 16, 20).fields]
    assert names == ["token_ids", "req_idx", "positions", "slot_mapping",
                     "block_tables", "seq_lens", "seq_info", "num_seqs",
                     "kv_runs", "num_kv_runs", "attn_desc", "decode_list"]
    two = StepLayout(72, 8, 4, 16, 20, window_group=True)
    assert [n for n, *_ in two.fields][len(names):] == [
        "window_slot_mapping", "window_block_tables", "window_kv_runs"]
    assert two.size == StepLayout(72, 8, 4, 16, 20).size + 72 + 64 + 32
    blank = two.views(two.new_buffer())
    assert (blank["window_slot_mapping"] == -1).all()
    assert (blank["slot_mapping"] == -1).all()


def test_a_one_group_manager_is_what_it_was():
    """No window: every page is kept, the allocation carries no second
    list; an all-window model frees behind its one table."""
    mgr = KVCacheManager(BLOCK, 64, enable_caching=False)
    req = make_request("r", 300)
    got = mgr.allocate_slots(req, 300)
    assert got.window_blocks is None and got.get_window_block_ids() is None
    req.num_computed_tokens = 300
    assert mgr.allocate_slots(req, 1) is not None
    assert all(b is not None for b in mgr.req_to_blocks["r"])
    assert mgr.window_pages_freed == 0
    windowed = KVCacheManager(BLOCK, 64, enable_caching=False,
                              free_window=WINDOW)
    req = make_request("w", 300)
    windowed.allocate_slots(req, 300)
    req.num_computed_tokens = 300
    windowed.allocate_slots(req, 1)
    assert windowed.window_pages_freed == (300 - WINDOW + 1) // BLOCK
    assert np.all(num_window_dead_blocks(
        np.array([0, 63, 64, 79, 80, 300]), WINDOW, BLOCK)
        == [0, 0, 0, 1, 1, 14])
