"""The HBM profile's accounting, as far as the CPU can hold it to. The
CPU backend reports no memory, so ``profile_memory_bytes`` returns before
it reaches these pieces; the tests call them directly: every serving
program of an engine is lowered and charged, and a page is priced at
what ONE device holds of it."""

import jax
import pytest

from tests.engine.test_llm_engine import checkpoint, make_engine  # noqa: F401


def _runner(engine):
    return engine.engine_core.executor.worker.model_runner


@pytest.mark.parametrize("overrides, cache_programs, head_programs", [
    pytest.param({}, 1, 2, id="plain"),
    pytest.param({"num_scheduler_steps": 4}, 2, 2, id="multi-step"),
    pytest.param({"speculative_method": "ngram",
                  "num_speculative_tokens": 2}, 1, 3, id="spec-verify"),
    pytest.param({"pipeline_parallel_size": 2}, 2, 2, id="pp-stages"),
])
def test_profile_charges_every_serving_program(checkpoint, overrides,
                                               cache_programs,
                                               head_programs):
    path, _ = checkpoint
    r = _runner(make_engine(path, max_num_batched_tokens=16, max_num_seqs=4,
                            **overrides))
    programs = r._profile_run()
    assert len(programs) == cache_programs
    assert len(r._head_programs()) == head_programs
    workspace = r._program_workspace_bytes(programs)
    assert workspace > 0
    # The maximum over all of them: no single program needs more.
    for program in programs:
        ma = program.compile().memory_analysis()
        assert workspace >= ma.temp_size_in_bytes


@pytest.mark.parametrize("parallel", [
    pytest.param({}, id="one-device"),
    pytest.param({"tensor_parallel_size": 2}, id="tp2"),
    pytest.param({"token_parallel_size": 2}, id="tknp2"),
    pytest.param({"tensor_parallel_size": 2, "token_parallel_size": 2},
                 id="tknp2-tp2"),
    pytest.param({"pipeline_parallel_size": 2}, id="pp2"),
])
def test_page_is_priced_at_one_devices_share(checkpoint, parallel):
    """num_pages * kv_cache_bytes_per_page() is exactly what the fullest
    device holds of the pool the runner allocates."""
    path, _ = checkpoint
    r = _runner(make_engine(path, **parallel))
    caches = r.kv_caches if isinstance(r.kv_caches, list) else [r.kv_caches]
    held: dict = {}
    for leaf in jax.tree.leaves(caches):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (held.get(shard.device.id, 0) +
                                     shard.data.nbytes)
    assert max(held.values()) == r.num_pages * r.kv_cache_bytes_per_page()
    shards = (parallel.get("tensor_parallel_size", 1) *
              parallel.get("token_parallel_size", 1) *
              parallel.get("pipeline_parallel_size", 1))
    assert (r.kv_cache_bytes_per_page() * shards ==
            r.model.kv_cache_page_bytes(r.page_size))


@pytest.mark.parametrize("name, slot, page, pages_per_req, seqs, room, want", [
    # granite-4.0-h-micro at 64 rows (PR 37, call 1): 2.04 GiB after the
    # rows, 2.00 GiB of them the pages of 64 full-length requests.
    pytest.param("rows of 73 MiB", 76_437_504, 131_072, 256, 64,
                 int(2.04 * 2 ** 30), 8, id="granite"),
    # granite-4.0-h-small-ep2 at 64 rows beside 9.51 GB of weights
    # (PR 55, call A): rows of 38.2 MB, pages of 4 KiB a token; 2.17 GiB
    # after the rows, 1.00 GiB of them the pages of 64 full-length
    # requests; the rest buys 32 slots, in eights.
    pytest.param("rows of 36 MiB", 38_204_928, 65_536, 256, 64,
                 int(2.17 * 2 ** 30), 32, id="granite-small-ep2"),
    # The same rows where the room is the pages' alone: eight are kept.
    pytest.param("rows of 36 MiB, no room", 38_204_928, 65_536, 256, 64,
                 int(1.10 * 2 ** 30), 8, id="granite-small-ep2-tight"),
    # zaya1-8b (PR 41, call 1): 2.96 GiB after the rows, under the
    # 4.00 GiB that 64 full-length requests' pages would need, and a
    # default pool of 128 slots that costs 11 MB: kept whole.
    pytest.param("rows of 88 KiB", 90_112, 262_144, 256, 64,
                 int(2.96 * 2 ** 30), None, id="zaya"),
    # The same small rows where even the pages fit: nothing to cut.
    pytest.param("room for all", 90_112, 262_144, 256, 64,
                 int(9.0 * 2 ** 30), None, id="zaya-roomy"),
    # Rows as large as a request's pages are cut as before.
    pytest.param("rows of 64 MiB", 2 ** 26, 262_144, 256, 64,
                 int(5.0 * 2 ** 30), 16, id="large-rows"),
])
def test_snapshot_pool_is_cut_only_where_a_cut_buys_pages(
        name, slot, page, pages_per_req, seqs, room, want):
    """``TPUWorker._size_state_pool`` on the published geometries: the
    pages of every schedulable request at full length come first and
    the pool is cut to what is left, in eights, never under eight; but
    a default pool (twice ``max_num_seqs`` slots) that costs less than
    ONE full-length request's pages is left whole, whatever the room:
    eight slots of 88 KiB for 64 sequences would save 10 MB of 3 GiB and
    leave every chunk-boundary snapshot but eight without a slot."""
    from types import SimpleNamespace

    from vllm_distributed_tpu.worker.worker import TPUWorker
    cache = SimpleNamespace(num_state_slots=None)
    fake = SimpleNamespace(
        model_runner=SimpleNamespace(state_pool_slot_bytes=lambda: slot),
        config=SimpleNamespace(
            cache_config=cache, max_pages_per_req=pages_per_req,
            scheduler_config=SimpleNamespace(max_num_seqs=seqs)))
    TPUWorker._size_state_pool(fake, room, page)
    assert cache.num_state_slots == want, name


@pytest.mark.parametrize("name, slot, seqs, room, want", [
    # brumby-14b at 16 rows: a slot is the row, 6 layers x 8 K/V heads x
    # (65 x 128 x 128 + 72 x 128) float32; the default asks for 32 of
    # them, 6.6 GB. What the profile leaves after the rows decides.
    pytest.param("four fit", 206_241_792, 16, int(0.95 * 2 ** 30), 4,
                 id="brumby-four"),
    pytest.param("one fits", 206_241_792, 16, int(0.35 * 2 ** 30), 1,
                 id="brumby-one"),
    pytest.param("none fits: one is kept", 206_241_792, 16, 10 << 20, 1,
                 id="brumby-none"),
    pytest.param("eleven fit: eight", 206_241_792, 16, int(2.2 * 2 ** 30),
                 8, id="brumby-eights"),
    # A small pure-state model (Mamba-130m: rows of 2.6 MB, 256 slots of
    # them 0.7 GB): room for all, nothing written.
    pytest.param("room for all", 2_654_208, 128, 4 << 30, None,
                 id="mamba-roomy"),
])
def test_a_model_without_pages_gets_the_slots_that_fit(name, slot, seqs,
                                                       room, want):
    """``TPUWorker._size_state_pool`` with ``page_bytes`` 0 (no paged
    layer: models/brumby.py, models/mamba.py): there is no request's
    pages to reserve and none to take room from, so the pool is cut to
    the slots that fit, in eights while eight fit and one by one under
    that, never under one; where pages exist the floor of eight stands
    (the cases above: Granite's 8, a 64 MiB row's 16)."""
    from types import SimpleNamespace

    from vllm_distributed_tpu.worker.worker import TPUWorker
    cache = SimpleNamespace(num_state_slots=None)
    fake = SimpleNamespace(
        model_runner=SimpleNamespace(state_pool_slot_bytes=lambda: slot),
        config=SimpleNamespace(
            cache_config=cache, max_pages_per_req=2048,
            scheduler_config=SimpleNamespace(max_num_seqs=seqs)))
    TPUWorker._size_state_pool(fake, room, 0)
    assert cache.num_state_slots == want, name
