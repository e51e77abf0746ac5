"""Async scheduling: the depth-2 in-flight batch pipeline on the
non-PP path (reference: the V1 --async-scheduling overlap of host
scheduling/input-prep with device execution).

Acceptance contract: the async path is token-identical to sync under
greedy sampling (the same contract crash-replay locked in PR 2), abort
and preemption stay safe with batches in flight, the zero-token-grant
contract extends to the async queue, and incompatible features force
sync (config-level auto-off + per-request fallback)."""

import asyncio

import numpy as np
import pytest
import torch
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.utils import fault_injection as fi


@pytest.fixture(scope="module")
def checkpoint(tmp_path_factory):
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=64,
                      eos_token_id=1)
    hf = HFLlama(cfg).eval()
    path = tmp_path_factory.mktemp("tiny_llama_async")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


def make_engine(path, **overrides) -> LLMEngine:
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


PROMPTS = [
    [3, 17, 92, 45, 8],
    [7, 7, 7, 7, 7, 7, 7, 7, 7, 7, 7],
    [120, 44],
    [1, 2, 3, 4, 5, 6],
]

_TAG = [0]


def run(engine, prompts, sps):
    _TAG[0] += 1
    for i, (p, sp) in enumerate(zip(prompts, sps)):
        engine.add_request(f"as{_TAG[0]}-{i}", p, sp)
    done = {}
    for _ in range(500):
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    order = sorted(done, key=lambda s: int(s.split("-")[-1]))
    return [done[k].outputs[0].token_ids for k in order]


def greedy_sps(n, max_tokens=8, **kw):
    return [SamplingParams(temperature=0.0, max_tokens=max_tokens,
                           ignore_eos=True, **kw) for _ in range(n)]


def core_of(engine):
    return engine.engine_core.engine_core


# ---------------------------------------------------------------------------
# Greedy token parity + overlap actually happening
# ---------------------------------------------------------------------------

def test_async_greedy_matches_sync(checkpoint):
    baseline = run(make_engine(checkpoint), PROMPTS, greedy_sps(4))
    engine = make_engine(checkpoint, async_scheduling=True)
    core = core_of(engine)
    assert core.async_scheduling
    assert core.batch_queue is not None and core.batch_queue_size == 2
    got = run(engine, PROMPTS, greedy_sps(4))
    assert got == baseline
    # The pipeline really ran ahead: >= 2 batches in flight at once and
    # speculative grants were issued.
    assert core.max_concurrent_batches >= 2
    assert core.scheduler.num_async_spec_grants > 0
    assert core.steps_overlapped > 0
    # No pages leaked through the pending-retire path.
    pool = core.scheduler.kv_cache_manager.block_pool
    assert pool.get_num_free_blocks() == pool.num_blocks
    assert not core.scheduler._finished_pending_retire
    assert not core.scheduler.in_flight_req_ids


def test_async_chunked_prefill_matches_sync(checkpoint):
    prompt = [int(x) for x in
              np.random.default_rng(0).integers(2, 127, size=40)]
    baseline = run(make_engine(checkpoint, max_num_batched_tokens=16),
                   [prompt], greedy_sps(1, max_tokens=5))
    got = run(make_engine(checkpoint, max_num_batched_tokens=16,
                          async_scheduling=True),
              [prompt], greedy_sps(1, max_tokens=5))
    assert got == baseline


def test_async_stop_token_lags_but_truncates_exactly(checkpoint):
    """EOS/stop detection lags one step under async (the over-issued
    position's sample is discarded); the emitted stream must still stop
    on exactly the same token as sync."""
    sync = make_engine(checkpoint)
    base = run(sync, [PROMPTS[0]], greedy_sps(1, max_tokens=10))[0]
    stop_tok = base[4]
    sps = [SamplingParams(temperature=0.0, max_tokens=10, ignore_eos=True,
                          stop_token_ids=[stop_tok])]
    expect = run(sync, [PROMPTS[0]], sps)
    got = run(make_engine(checkpoint, async_scheduling=True),
              [PROMPTS[0]], sps)
    assert got == expect
    # Truncated at the FIRST occurrence of the stop token, exactly.
    assert got[0] == base[:base.index(stop_tok) + 1]


def test_async_mixed_sync_fallback_requests(checkpoint):
    """A batch mixing plain greedy rows (chained device-to-device) with
    requests that need host-synchronous sampling (penalties) stays
    token-identical to the sync engine for every stream."""
    sps = [
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       repetition_penalty=1.3),
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       presence_penalty=0.8),
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True),
    ]
    baseline = run(make_engine(checkpoint), PROMPTS, sps)
    got = run(make_engine(checkpoint, async_scheduling=True), PROMPTS, sps)
    assert got == baseline


def test_async_sync_only_requests_never_speculate(checkpoint):
    """A workload of ONLY host-synchronous requests degrades to
    PP-style one-batch-at-a-time scheduling: no speculative grants."""
    engine = make_engine(checkpoint, async_scheduling=True)
    sps = [SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                          repetition_penalty=1.2) for _ in range(2)]
    run(engine, PROMPTS[:2], sps)
    core = core_of(engine)
    assert core.scheduler.num_async_spec_grants == 0
    # Nothing can be granted behind such a batch, so the step that
    # launches it retires it: the synchronous step, one vdt_step and one
    # forward a call, nothing overlapped.
    assert core.phases.step == core.steps_dispatched > 0
    assert core.steps_overlapped == 0 and core.max_concurrent_batches == 1


# ---------------------------------------------------------------------------
# Abort / preemption with batches in flight
# ---------------------------------------------------------------------------

def test_async_abort_in_flight_is_safe(checkpoint):
    engine = make_engine(checkpoint, async_scheduling=True)
    core = core_of(engine)
    sp = SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True)
    for i, p in enumerate(PROMPTS):
        engine.add_request(f"abort-{i}", p, sp)
    aborted = None
    for _ in range(50):
        engine.step()
        if core.scheduler.in_flight_req_ids:
            aborted = next(iter(core.scheduler.in_flight_req_ids))
            engine.abort_request([aborted])
            break
    assert aborted is not None
    done = set()
    for _ in range(300):
        for out in engine.step():
            if out.finished:
                done.add(out.request_id)
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    assert done == {f"abort-{i}" for i in range(4)} - {aborted}
    assert not core.scheduler._deferred_finishes
    assert not core.scheduler._finished_pending_retire
    pool = core.scheduler.kv_cache_manager.block_pool
    assert pool.get_num_free_blocks() == pool.num_blocks


def test_async_preemption_with_batch_in_flight(checkpoint):
    """A page pool too small for the full batch forces preemption while
    the pipeline is active: in-flight requests are never evicted (their
    pages are being written), and the greedy output still matches an
    ample-pool baseline exactly (preempted requests recompute)."""
    prompts = [[i * 11 + j for j in range(1, 9)] for i in range(3)]
    baseline = run(make_engine(checkpoint), prompts,
                   greedy_sps(3, max_tokens=12))
    # 12 pages x 4 tokens = 48-token capacity < 3 x (8 prompt + 12 out)
    # = 60 tokens needed -> at least one preemption is forced.
    engine = make_engine(checkpoint, async_scheduling=True,
                         num_gpu_blocks_override=12)
    got = run(engine, prompts, greedy_sps(3, max_tokens=12))
    assert got == baseline
    core = core_of(engine)
    assert core.scheduler.num_preemptions >= 1
    pool = core.scheduler.kv_cache_manager.block_pool
    assert pool.get_num_free_blocks() == pool.num_blocks


# ---------------------------------------------------------------------------
# Zero-token-grant contract (async sibling of
# test_zero_token_dispatch_does_no_device_work)
# ---------------------------------------------------------------------------

def test_async_zero_token_dispatch_does_no_device_work(checkpoint):
    """The async queue keeps the PP queue's contract: an empty grant
    resolves entirely at dispatch time (no device work that could
    interleave with in-flight speculative batches)."""
    from vllm_distributed_tpu.core.sched.output import SchedulerOutput
    engine = make_engine(checkpoint, async_scheduling=True)
    core = core_of(engine)
    assert core.batch_queue is not None  # the async queue is active
    runner = core.executor.worker.model_runner
    handle = runner.dispatch_model(SchedulerOutput(async_scheduled=True))
    assert "ready" in handle and "dev" not in handle
    out = runner.wait_model(handle)
    assert not out.sampled_token_ids


# ---------------------------------------------------------------------------
# Auto-fallback matrix: incompatible features force sync
# ---------------------------------------------------------------------------

_EXCLUSIONS = [
    dict(speculative_method="ngram", num_speculative_tokens=3),
    dict(pipeline_parallel_size=2),
    dict(num_scheduler_steps=4),
    dict(kv_connector="SharedStorageConnector", kv_role="kv_both"),
    dict(token_parallel_size=2),
    dict(num_hosts=2),
]


def _served_engine_args(monkeypatch, engine_args):
    """The EngineArgs that ``run_server`` hands the engine, nothing
    built and nothing served."""
    from vllm_distributed_tpu.entrypoints.openai import api_server
    seen = []
    monkeypatch.setattr(api_server.AsyncLLM, "from_engine_args",
                        classmethod(lambda cls, a: seen.append(a)))
    monkeypatch.setattr(api_server, "serve", lambda *a, **k: None)
    monkeypatch.setattr(api_server.asyncio, "run", lambda coro: None)
    api_server.run_server(engine_args)
    return seen[0]


@pytest.mark.parametrize("asked, served, library", [
    (None, True, False),        # the entry decides
    (True, True, True),         # --async-scheduling
    (False, False, False),      # --no-async-scheduling
])
def test_async_auto_off_matrix_entry_resolution(monkeypatch, checkpoint,
                                                asked, served, library):
    """``EngineArgs.async_scheduling`` None means "the entry decides":
    ``run_server`` (what ``vdt serve`` and the benchmark call) resolves
    it to the pipelined step, a library construction to the synchronous
    one; the CLI switch overrides either. The caller's object is left
    as it was."""
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    import argparse
    flag = {None: [], True: ["--async-scheduling"],
            False: ["--no-async-scheduling"]}[asked]
    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    args = EngineArgs.from_cli_args(parser.parse_args(
        ["--model", checkpoint, "--max-model-len", "64",
         "--skip-tokenizer-init"] + flag))
    assert args.async_scheduling is asked
    got = _served_engine_args(monkeypatch, args)
    assert got.async_scheduling is served
    assert args.async_scheduling is asked
    for a, want in ((got, served), (args, library)):
        assert a.create_engine_config().scheduler_config.async_scheduling \
            is want


@pytest.mark.parametrize("overrides", _EXCLUSIONS)
def test_async_auto_off_matrix_served_default(monkeypatch, checkpoint,
                                              overrides):
    """Each exclusion the config can observe takes the SERVER's default
    (None resolved to on by ``run_server``) back to the synchronous
    step, as it takes the explicit switch back (the case below)."""
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    got = _served_engine_args(monkeypatch, EngineArgs(
        model=checkpoint, max_model_len=64, skip_tokenizer_init=True,
        **overrides))
    assert got.async_scheduling is True
    assert got.create_engine_config().scheduler_config.async_scheduling \
        is False


@pytest.mark.parametrize("overrides", _EXCLUSIONS)
def test_async_auto_off_matrix(overrides):
    """Config-level auto-off: features whose step contract conflicts
    with run-ahead grants force async_scheduling back to False at
    config normalization (spec decode / PP / multi-step / KV connector
    / token parallelism / multi-host). Build only the config (no
    engine): normalization happens in EngineConfig.__post_init__."""
    from vllm_distributed_tpu.config import (EngineConfig, KVTransferConfig,
                                             ModelConfig, ParallelConfig,
                                             SchedulerConfig,
                                             SpeculativeConfig)
    config = EngineConfig(
        model_config=ModelConfig(model="dummy", max_model_len=64),
        scheduler_config=SchedulerConfig(
            async_scheduling=True,
            num_scheduler_steps=overrides.get("num_scheduler_steps", 1),
            max_model_len=64),
        parallel_config=ParallelConfig(
            pipeline_parallel_size=overrides.get(
                "pipeline_parallel_size", 1),
            token_parallel_size=overrides.get("token_parallel_size", 1),
            num_hosts=overrides.get("num_hosts", 1)),
        speculative_config=SpeculativeConfig(
            method=overrides.get("speculative_method"),
            num_speculative_tokens=overrides.get(
                "num_speculative_tokens", 0)),
        kv_transfer_config=KVTransferConfig(
            kv_connector=overrides.get("kv_connector"),
            kv_role=overrides.get("kv_role")),
    )
    assert config.scheduler_config.async_scheduling is False


def test_async_stays_on_for_plain_config():
    from vllm_distributed_tpu.config import (EngineConfig, ModelConfig,
                                             SchedulerConfig)
    config = EngineConfig(
        model_config=ModelConfig(model="dummy", max_model_len=64),
        scheduler_config=SchedulerConfig(async_scheduling=True,
                                         max_model_len=64),
    )
    assert config.scheduler_config.async_scheduling is True


# ---------------------------------------------------------------------------
# CI smoke: overlap through AsyncLLM on a toy model (tier-1-safe)
# ---------------------------------------------------------------------------

def _make_async_llm(checkpoint, **overrides):
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM
    args = dict(model=checkpoint, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=64,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True, async_scheduling=True,
                restart_backoff_base_s=0.01, restart_backoff_max_s=0.05)
    args.update(overrides)
    return AsyncLLM(EngineArgs(**args).create_engine_config(),
                    load_tokenizer=False)


async def _collect_one(engine, prompt, request_id, max_tokens=16,
                       arm_fault=None):
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True)
    final = None
    got_first = False
    async for out in engine.generate(prompt, sp, request_id=request_id):
        if not got_first:
            got_first = True
            if arm_fault:
                arm_fault()
        final = out
    assert final is not None and final.finished
    return final.outputs[0].token_ids


def test_asyncllm_overlap_smoke(checkpoint):
    """CPU smoke for the tentpole: a toy model served through AsyncLLM
    must actually keep >= 2 batches in flight (max_concurrent_batches),
    proving the overlap engages outside hand-driven step() loops."""
    engine = _make_async_llm(checkpoint)

    async def go():
        return await asyncio.gather(*[
            _collect_one(engine, PROMPTS[i], f"smoke-{i}")
            for i in range(4)
        ])

    try:
        outs = asyncio.run(asyncio.wait_for(go(), timeout=120.0))
        assert all(len(o) == 16 for o in outs)
        core = engine.core.core  # BackgroundEngineCore -> EngineCore
        assert core.max_concurrent_batches >= 2
        stats = core.get_stats()
        assert stats["decode_overlap_frac"] > 0
        assert stats["step_host_gap_seconds"]["count"] > 0
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# Faults: the crash-recovery ladder still fires with batches in flight
# ---------------------------------------------------------------------------

@pytest.fixture
def _clean_faults():
    fi.clear()
    yield
    fi.clear()


@pytest.mark.faults
def test_reconcile_stall_death_recovers_mid_pipeline(checkpoint,
                                                     _clean_faults):
    """step.reconcile_stall (raise mode) kills the core at the batch
    queue's reconcile point — i.e. with speculative batches in flight.
    The PR 1/2 ladder (health monitor -> supervisor respawn -> journal
    replay) must recover token-identically."""
    base_engine = _make_async_llm(checkpoint)
    try:
        baseline = asyncio.run(asyncio.wait_for(
            _collect_one(base_engine, PROMPTS[0], "rs-base",
                         max_tokens=20), timeout=120.0))
    finally:
        base_engine.shutdown()

    engine = _make_async_llm(checkpoint)
    try:
        resumed = asyncio.run(asyncio.wait_for(
            _collect_one(
                engine, PROMPTS[0], "rs-die", max_tokens=20,
                arm_fault=lambda: fi.inject("step.reconcile_stall",
                                            max_fires=1)),
            timeout=180.0))
        assert resumed == baseline
        assert not engine.errored
        stats = engine.output_processor.stats
        assert stats.num_engine_deaths >= 1
        assert stats.num_requests_replayed >= 1
        assert fi.counters().get("step.reconcile_stall", 0) >= 1
    finally:
        engine.shutdown()


@pytest.mark.faults
def test_reconcile_stall_delay_is_survived(checkpoint, _clean_faults):
    """Delay mode: a host stall between device completion and
    reconciliation is absorbed (paced, not fatal) — the stream
    completes and the engine stays healthy."""
    engine = _make_async_llm(checkpoint)
    try:
        fi.inject("step.reconcile_stall", rate=0.25, delay_s=0.02)
        out = asyncio.run(asyncio.wait_for(
            _collect_one(engine, PROMPTS[0], "rs-delay", max_tokens=12),
            timeout=120.0))
        assert len(out) == 12
        assert not engine.errored
        assert engine.output_processor.stats.num_engine_deaths == 0
        assert fi.counters().get("step.reconcile_stall", 0) >= 1
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# The pipelined step's accounts: one vdt_step, one period a forward
# ---------------------------------------------------------------------------

from tests.metrics.test_step_phases import (PARTITION,  # noqa: E402
                                           _counts_and_sums, _delta)


def _accounts(core) -> dict:
    """The synchronous step's accounts (tests/metrics) and what the
    pipelined step adds to them."""
    stats = core.get_stats()
    out = _counts_and_sums(stats)
    out.update({f"kind.{k}": (h["count"], h["sum"])
                for k, h in stats["step_kind_seconds"].items()})
    out["step_cpu_seconds"] = (stats["step_cpu_seconds"]["count"],
                               stats["step_cpu_seconds"]["sum"])
    out["vdt_step"] = (core.phases.step, 0.0)
    out["forwards"] = (core.steps_dispatched, 0.0)
    out["overlapped"] = (core.steps_overlapped, 0.0)
    return out


def test_pipelined_step_accounts(checkpoint):
    """Over a run of K forwards the pipelined engine opens K vdt_step
    annotations (one more for the drain), observes one period a step
    whose kinds add up, and its eight phases partition the thread's
    time as on the synchronous path: mid-run their sum IS the periods'
    sum, the two boundary steps' own time taken out."""
    engine = make_engine(checkpoint, async_scheduling=True,
                         max_model_len=128, max_num_batched_tokens=16)
    core = core_of(engine)
    ph = core.phases
    assert ph.sync and ph is core.executor.worker.model_runner.phases
    sp = SamplingParams(temperature=0.0, max_tokens=60, ignore_eos=True)

    # One busy period, idle to idle: a prompt of three chunks (mixed
    # steps) beside a short one, then decode steps.
    before = _accounts(core)
    engine.add_request("acc-a", list(range(2, 42)), sp)
    engine.add_request("acc-b", PROMPTS[0], sp)
    calls = 0
    while engine.has_unfinished_requests():
        engine.step()
        calls += 1
    d = _delta(before, _accounts(core))
    K = d["forwards"][0]
    assert K > 60 and calls == K + 1        # fill ... drain
    assert d["vdt_step"][0] == calls
    assert d["overlapped"][0] == K - 1      # all but the fill's
    for p in PARTITION[1:]:
        assert d[f"phase.{p}"][0] == K, p   # once a forward
    assert d["phase.loop"][0] == calls
    assert d["phase.dispatch"][0] == d["phase.wait"][0] == 0
    periods = d["step_period_seconds"][0]
    assert periods == d["step_cpu_seconds"][0] == calls - 1 == K
    assert d["kind.decode/period"][0] + d["kind.mixed/period"][0] \
        == periods
    assert d["kind.mixed/period"][0] >= 3
    assert d["kind.decode/device_wait"][0] \
        + d["kind.mixed/device_wait"][0] == K
    # One gap a fetch (the drain's fetch opens one that no launch
    # closes); the stall from one fetch to the next.
    assert d["step_host_gap_seconds"][0] == K - 1
    assert d["step_host_stall_seconds"][0] == K - 1
    assert ph.idle

    # Mid-run: n steps, each one forward, one period and no host gap.
    engine.add_request("acc-c", PROMPTS[1], sp)
    engine.add_request("acc-d", PROMPTS[3], sp)
    for _ in range(6):
        engine.step()
    own_a = ph.mark - ph.step_start
    a = _accounts(core)
    n = 40
    for _ in range(n):
        assert len(engine.step()) == 2      # the outputs of the retired
    own_b = ph.mark - ph.step_start
    d = _delta(a, _accounts(core))
    assert d["forwards"][0] == d["overlapped"][0] == d["vdt_step"][0] == n
    assert all(d[f"phase.{p}"][0] == n for p in PARTITION)
    assert d["step_period_seconds"][0] == d["kind.decode/period"][0] == n
    phase_sum = sum(d[f"phase.{p}"][1] for p in PARTITION)
    period_sum = d["step_period_seconds"][1]
    assert phase_sum == pytest.approx(period_sum + own_b - own_a,
                                      rel=1e-6)
    assert abs(phase_sum - period_sum) < 0.01 * period_sum * 40 / n + \
        own_a + own_b
    assert d["step_host_gap_seconds"] == (n, 0.0)
    while engine.has_unfinished_requests():
        engine.step()
    assert core.steps_overlapped / core.steps_dispatched > 0.95


def test_a_fetch_with_a_forward_queued_leaves_no_gap():
    """StepPhases alone: the pipelined order (launch N+1, fetch N) reads
    a zero gap a fetch; a fetch with nothing queued opens a gap that the
    next launch closes, once."""
    from vllm_distributed_tpu.metrics.stats import StepPhases
    ph = StepPhases()

    def step(launch: bool, fetch: bool, queued: bool):
        with ph.begin_step():
            if launch:
                with ph.span("vdt.launch", "launch"):
                    ph.launch_issued()
            if fetch:
                ph.forward_queued = queued
                with ph.span("vdt.device_wait", "device_wait"):
                    pass
        ph.end_step(more_work=True)

    step(True, False, False)        # fill
    assert ph.host_gap.count == 0
    step(True, True, True)          # steady state
    step(True, True, True)
    assert (ph.host_gap.count, ph.host_gap.total) == (2, 0.0)
    step(False, True, False)        # drain: nothing behind this fetch
    assert ph.host_gap.count == 2
    step(True, False, False)        # the next launch closes that gap
    assert ph.host_gap.count == 3 and ph.host_gap.total > 0
    step(True, True, True)
    assert ph.host_gap.count == 4


# ---------------------------------------------------------------------------
# Token-for-token equality over the cache shapes the cells serve
# ---------------------------------------------------------------------------

def _toy_engine(tmp_path, name: str, **overrides) -> LLMEngine:
    """One of the benchmark's toy configurations (seeded weights made
    at load), as its cell builds it."""
    import dataclasses
    import json
    import os

    from benchmarks import run as bench
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarking", "data", "configs",
                           f"{name}.json")) as f:
        config_file = json.load(f)
    work, bench.WORK = bench.WORK, str(tmp_path)
    try:
        model_dir = bench.write_model_dir(name, config_file)
    finally:
        bench.WORK = work
    args = dataclasses.replace(
        bench.engine_args(model_dir, config_file, 7),
        skip_tokenizer_init=True, **overrides)
    return LLMEngine(args.create_engine_config())


@pytest.mark.parametrize("name, shape", [
    ("tiny-olmo2", "one K/V group"),
    ("tiny-cohere-moe", "two groups, one behind a window of 8"),
    ("tiny-granite-h", "pages and SSM state rows, a snapshot each 16"),
    ("tiny-brumby", "state rows and no page"),
    ("tiny-olmoe", "sparse experts"),
])
def test_pipelined_greedy_matches_sync_by_cache_shape(tmp_path, monkeypatch,
                                                      name, shape):
    """The pipelined step against the synchronous one, greedy, token for
    token, on a toy model of each cache shape the benchmark's cells
    serve: a prompt of two chunks, contexts past the window, snapshots
    of the state rows landing on chunk and on decode boundaries, and
    what the managers hold afterwards."""
    monkeypatch.setenv("VDT_SSM_CKPT_INTERVAL", "16")
    rng = np.random.default_rng(3)
    prompts = [[int(x) for x in rng.integers(2, 200, size=n)]
               for n in (90, 7, 21)]
    sps = greedy_sps(3, max_tokens=14)
    got, saves = {}, {}
    for mode in (False, True):
        engine = _toy_engine(tmp_path, name, async_scheduling=mode)
        core = core_of(engine)
        assert core.async_scheduling is mode
        # The same prompts again: prefix pages and state snapshots hit.
        got[mode] = (run(engine, prompts, sps), run(engine, prompts, sps))
        sched = core.scheduler
        runner = core.executor.worker.model_runner
        got[mode] += (sched.num_preemptions,
                      getattr(runner, "num_state_restores", 0))
        saves[mode] = getattr(runner, "num_state_checkpoints", 0)
        if mode:
            assert core.steps_overlapped > 0
            assert sched.num_async_spec_grants > 0
        assert not sched._finished_pending_retire
        assert not sched.in_flight_req_ids
        for pool in sched._block_pools():
            assert pool.get_num_free_blocks() == pool.num_blocks
        engine.engine_core.shutdown()
    assert got[True] == got[False], shape
    if "snapshot" in shape:
        # A run-ahead grant that lands on a boundary cannot know its
        # key yet (the token is on the device): the repeat's three
        # decode boundaries are copied and dropped at commit, where the
        # synchronous step saw the first run's snapshots at grant time.
        assert got[True][3] == 2 and saves[False] == 6
        assert saves[True] == saves[False] + 3


# ---------------------------------------------------------------------------
# A request that finished with a later batch in flight keeps its row
# ---------------------------------------------------------------------------

def test_a_parked_finish_keeps_its_row_and_an_abort_leaves_the_device(
        checkpoint):
    """A full batch whose requests stop on a token (found one step
    late, the run-ahead batch still in flight) while others wait: the
    waiting ones are admitted only into rows the worker has freed (the
    input batch would overflow otherwise). And an abort that arrives
    while its request is in flight is granted nothing more: the request
    leaves once that batch retires, long before its max_tokens."""
    sync = make_engine(checkpoint, max_num_seqs=4)
    prompts = [[5 + i, 9, 40 + i, 77] for i in range(8)]
    base = run(sync, prompts, greedy_sps(8, max_tokens=12))
    sps = [SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True,
                          stop_token_ids=[b[3 + i % 3]])
           for i, b in enumerate(base)]
    expect = run(sync, prompts, sps)
    engine = make_engine(checkpoint, max_num_seqs=4, async_scheduling=True)
    assert run(engine, prompts, sps) == expect
    core = core_of(engine)
    pool = core.scheduler.kv_cache_manager.block_pool
    assert pool.get_num_free_blocks() == pool.num_blocks

    sp = SamplingParams(temperature=0.0, max_tokens=50, ignore_eos=True)
    engine.add_request("leave-0", PROMPTS[0], sp)
    engine.add_request("stay-1", PROMPTS[1], sp)
    for _ in range(4):
        engine.step()
    assert "leave-0" in core.scheduler.in_flight_req_ids
    engine.abort_request(["leave-0"])
    for _ in range(3):
        engine.step()
    assert "leave-0" not in core.scheduler.requests
    assert not core.scheduler._deferred_finishes
    while engine.has_unfinished_requests():
        engine.step()
    assert pool.get_num_free_blocks() == pool.num_blocks


# ---------------------------------------------------------------------------
# Nothing compiles while serving
# ---------------------------------------------------------------------------

def test_precompile_covers_the_pipelined_step(checkpoint, monkeypatch):
    """After ``precompile`` a pipelined run that crosses a token bucket
    and a request bucket compiles nothing: the recompile guard stays at
    zero AND the backend compiles no program (JAX's own event, which
    the benchmark counts inside its window): the chain rides inside the
    forward and the sampler, no program of its own, and three host
    arrays a step as on the synchronous path."""
    from jax._src import monitoring
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    engine = make_engine(checkpoint, async_scheduling=True, max_num_seqs=16,
                         max_num_batched_tokens=32)
    core = core_of(engine)
    runner = core.executor.worker.model_runner
    assert runner._precompiled and len(runner.req_buckets) == 2 \
        and len(runner.token_buckets) == 2
    compiled = []

    def on_duration(event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            compiled.append(event)

    prompts = [[3 + i, 50, 9 + i] for i in range(12)] + [list(range(2, 30))]
    sps = greedy_sps(13, max_tokens=6)
    _TAG[0] += 1
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for i, (p, sp) in enumerate(zip(prompts[:6] + prompts[12:],
                                        sps[:6] + sps[12:])):
            engine.add_request(f"pc{_TAG[0]}-{i}", p, sp)
        for _ in range(3):
            engine.step()
        for i, (p, sp) in enumerate(zip(prompts[6:12], sps[6:12])):
            engine.add_request(f"pc{_TAG[0]}-late-{i}", p, sp)
        while engine.has_unfinished_requests():
            engine.step()
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert core.steps_overlapped > 0
    assert runner.num_recompiles == 0 and not compiled
    assert len({k for k in runner._compiled_shapes if k[0] == "fwd"}) == 2
    stats = core.get_stats()
    assert stats["num_recompiles"] == 0
    h = stats["step_host_transfers"]
    assert h["count"] == core.steps_dispatched and h["sum"] == 3 * h["count"]
