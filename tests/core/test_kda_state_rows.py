"""State rows beside latent pages (Ling-3.0's two kinds of cache for one
request; ``models/bailing.py``, ``core/state_cache.py``) through the
scheduler, the KV manager and the runner at a toy width: a preempted
request parks its row and resumes to the logits of an uninterrupted
run; a snapshot taken at a chunk boundary restores the state, and only
while the latent pages of its prefix are resident."""

import os

import pytest

from benchmarks import run
from vllm_distributed_tpu import SamplingParams
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine

DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking", "data")


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    cell = run.load_cell("tiny-ling.tiny-decode", DATA)
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("kda-rows"))
    try:
        return run.write_model_dir(cell["config"], cell["config_file"])
    finally:
        run.WORK = work


def make_engine(model_dir, monkeypatch, cache_on=True, interval=16,
                **over):
    # The scheduler and the runner read the flags at construction.
    monkeypatch.setenv("VDT_SSM_STATE_CACHE", "1" if cache_on else "0")
    monkeypatch.setenv("VDT_SSM_CKPT_INTERVAL", str(interval))
    args = dict(model=model_dir, load_format="dummy", seed=5,
                dtype="float32", block_size=16,
                num_gpu_blocks_override=128, max_model_len=128,
                max_num_batched_tokens=64, max_num_seqs=4,
                skip_tokenizer_init=True)
    args.update(over)
    return LLMEngine(EngineArgs(**args).create_engine_config())


def drain(engine, max_steps=600):
    """{request: (tokens, the chosen tokens' logprobs)}."""
    done = {}
    for _ in range(max_steps):
        for out in engine.step():
            if out.finished:
                c = out.outputs[0]
                done[out.request_id] = (
                    list(c.token_ids),
                    [d[t] for t, d in zip(c.token_ids, c.logprobs)])
        if not engine.has_unfinished_requests():
            break
    return done


def ssm_stats(engine):
    return {k: v for k, v in engine.get_stats().items()
            if k.startswith("ssm_")}


def close(a, b, tol=2e-4):
    return len(a) == len(b) and all(abs(x - y) < tol for x, y in zip(a, b))


def test_preempted_request_parks_its_row_and_resumes(model_dir,
                                                     monkeypatch):
    """Four requests of 24 + 40 tokens against a pool of 15 pages of 16
    (a request needs 4, so the last to want its fourth page is
    preempted while the others hold theirs to the end): its row is
    parked in the snapshot pool, the pages of its prefix stay in the
    prefix cache, and it resumes as a continuation. Tokens and logprobs
    equal those of a run that was never interrupted (a pool of 128
    pages)."""
    sp = SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True,
                        logprobs=1)
    prompts = [[(i * 37 + j * 11 + 3) % 500 for j in range(24)]
               for i in range(4)]

    def go(**over):
        engine = make_engine(model_dir, monkeypatch, **over)
        for i, p in enumerate(prompts):
            engine.add_request(f"r-{i}", p, sp)
        done = drain(engine)
        stats = engine.get_stats()
        free = engine.engine_core.engine_core.scheduler \
            .kv_cache_manager.get_num_free_blocks()
        return done, int(stats["num_preemptions"]), ssm_stats(engine), free

    calm, none, _, _ = go()
    tight, preempted, stats, free = go(num_gpu_blocks_override=15)
    assert none == 0 and preempted > 0
    assert free == 15                       # nothing leaked
    for rid, (tokens, lps) in calm.items():
        assert tight[rid][0] == tokens, rid
        assert close(tight[rid][1], lps), rid
    assert stats["ssm_state_cache_hits"] >= 1, stats
    assert stats["ssm_resume_tokens_saved"] > 0, stats


def test_chunk_boundary_snapshot_needs_the_prefix_pages(model_dir,
                                                        monkeypatch):
    """A second turn whose prompt extends the first restores the state
    row from the snapshot at the last chunk boundary AND reuses the
    latent pages of that prefix. With the pages dropped from the prefix
    cache the snapshot alone is no resume point: no hit, and the whole
    prompt is prefilled again. The logits are the same every way."""
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                        logprobs=1)
    first = [(j * 7 + 3) % 500 for j in range(70)]

    def two_turns(cache_on, drop_pages=False):
        engine = make_engine(model_dir, monkeypatch, cache_on=cache_on)
        engine.add_request("t-0", list(first), sp)
        tokens, _ = drain(engine)["t-0"]
        before = ssm_stats(engine)
        sched = engine.engine_core.engine_core.scheduler
        if drop_pages:
            assert sched.kv_cache_manager.reset_prefix_cache()
        engine.add_request("t-1", first + tokens + [17, 18, 19], sp)
        return drain(engine)["t-1"], before, ssm_stats(engine), sched

    plain, _, _, _ = two_turns(False)
    hit, before, after, sched = two_turns(True)
    assert sched.kv_cache_manager.enable_caching     # forced on: hybrid
    assert sched.state_cache.paged_kv
    assert after["ssm_state_cache_hits"] == \
        before.get("ssm_state_cache_hits", 0) + 1, (before, after)
    # 64 of the 79 prompt tokens were not computed again.
    assert after["ssm_resume_tokens_saved"] >= 64
    miss, before, after, _ = two_turns(True, drop_pages=True)
    assert after["ssm_state_cache_hits"] == \
        before.get("ssm_state_cache_hits", 0), (before, after)
    for got in (hit, miss):
        assert got[0] == plain[0]
        assert close(got[1], plain[1])
