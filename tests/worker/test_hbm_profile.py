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
