"""The step's phases and counters (ISSUE 25), counts and structure only:
phases that partition the synchronous step, the period they sum to, the
host gap and the host stall; the scheduler's queue wait and step tokens;
the runner's K/V tokens read."""

import pytest
from transformers import LlamaConfig

from tests.conftest import make_config, make_request
from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         SchedulerConfig)
from vllm_distributed_tpu.core.sched.output import ModelRunnerOutput
from vllm_distributed_tpu.core.sched.scheduler import Scheduler
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.metrics.stats import (FrontendStats, Histogram,
                                                StepPhases, span)
from vllm_distributed_tpu.request import RequestStatus
from vllm_distributed_tpu.sampling_params import SamplingParams

HF = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
          num_hidden_layers=2, num_attention_heads=4,
          num_key_value_heads=2, max_position_embeddings=256,
          architectures=["LlamaForCausalLM"])
PARTITION = ("loop", "schedule", "update_states", "prepare_inputs",
             "launch", "device_wait", "finish", "update")


@pytest.fixture(scope="module")
def engine():
    config = EngineConfig(
        model_config=ModelConfig(model="tiny-phase-dummy", dtype="float32",
                                 max_model_len=256, hf_overrides=HF,
                                 skip_tokenizer_init=True),
        cache_config=CacheConfig(block_size=4,
                                 num_gpu_blocks_override=256),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                         max_num_seqs=8,
                                         max_model_len=256),
        load_config=LoadConfig(load_format="dummy"))
    config.model_config.hf_config = LlamaConfig(**HF)
    engine = LLMEngine(config, load_tokenizer=False)
    yield engine
    engine.engine_core.shutdown()


def _core(engine):
    return engine.engine_core.engine_core


def _add(engine, rid: str, prompt_len: int, max_tokens: int) -> None:
    engine.add_request(
        rid, [2 + (sum(map(ord, rid)) + j) % 120
              for j in range(prompt_len)],
        SamplingParams(temperature=0.0, max_tokens=max_tokens,
                       ignore_eos=True))


def _counts_and_sums(stats: dict) -> dict:
    out = {f"phase.{p}": (h["count"], h["sum"])
           for p, h in stats["step_phase_seconds"].items()}
    for key in ("step_period_seconds", "step_host_gap_seconds",
                "step_host_stall_seconds"):
        out[key] = (stats[key]["count"], stats[key]["sum"])
    return out


def _delta(a: dict, b: dict) -> dict:
    return {k: (b[k][0] - a[k][0], b[k][1] - a[k][1]) for k in b}


def test_phases_partition_the_synchronous_step(engine):
    core = _core(engine)
    assert core.phases.sync and core.phases is \
        core.executor.worker.model_runner.phases
    # One busy period from idle: N steps.
    before = _counts_and_sums(core.get_stats())
    _add(engine, "whole-a", 10, 30)
    _add(engine, "whole-b", 12, 30)
    steps = 0
    while engine.has_unfinished_requests():
        engine.step()
        steps += 1
    d = _delta(before, _counts_and_sums(core.get_stats()))
    assert steps == 30
    for p in PARTITION:
        assert d[f"phase.{p}"][0] == steps, p
    assert d["phase.wait"][0] == 0 and d["phase.dispatch"][0] == 0
    assert d["step_period_seconds"][0] == steps - 1
    assert d["step_host_gap_seconds"][0] == steps - 1
    assert d["step_host_stall_seconds"][0] == steps - 1
    assert core.phases.idle

    # N steps in the middle of a busy period. The phases of steps k+1 to
    # k+N and the periods k to k+N-1 differ by the two boundary steps'
    # own time (schedule to update), which the timeline holds right after
    # a step; that taken out, the sums are EQUAL: no hole, no overlap.
    # (So over many steps they agree as closely as one step is to all.)
    _add(engine, "mid-a", 10, 50)
    _add(engine, "mid-b", 40, 50)
    for _ in range(5):
        engine.step()
    own_a = core.phases.mark - core.phases.step_start
    a = _counts_and_sums(core.get_stats())
    n = 40
    for _ in range(n):
        engine.step()
    own_b = core.phases.mark - core.phases.step_start
    d = _delta(a, _counts_and_sums(core.get_stats()))
    phase_sum = sum(d[f"phase.{p}"][1] for p in PARTITION)
    period_sum = d["step_period_seconds"][1]
    assert all(d[f"phase.{p}"][0] == n for p in PARTITION)
    assert d["step_period_seconds"][0] == n
    assert d["step_host_gap_seconds"][0] == n
    assert phase_sum == pytest.approx(period_sum + own_b - own_a,
                                      rel=1e-6)
    # The host gap of a step lies inside its period; the stall inside
    # the time outside the device wait.
    assert 0 < d["step_host_gap_seconds"][1] < period_sum
    assert 0 <= d["step_host_stall_seconds"][1] <= \
        period_sum - d["phase.device_wait"][1]
    while engine.has_unfinished_requests():
        engine.step()


def test_kv_tokens_read_of_a_prefill_chunk_and_two_decodes(engine):
    core = _core(engine)

    def kv_read():
        h = core.get_stats()["step_kv_tokens_read"]
        return h["count"], h["sum"]

    _add(engine, "kv-a", 10, 8)
    _add(engine, "kv-b", 13, 8)
    engine.step()     # both prompts: contexts 10 and 13
    engine.step()     # two decodes: contexts 11 and 14
    c0, s0 = kv_read()
    # A 100-token prompt joins: the step's 64-token budget grants the
    # two decodes (contexts 12 and 15) and a 62-token chunk of it.
    _add(engine, "kv-c", 100, 2)
    before_tokens = core.scheduler.step_tokens["prefill"].total
    engine.step()
    c1, s1 = kv_read()
    assert core.scheduler.step_tokens["prefill"].total \
        - before_tokens == 62
    assert (c1 - c0, s1 - s0) == (1, 12 + 15 + 62)
    # Next: decodes at 13 and 16, the prompt's last 38 tokens at 100.
    engine.step()
    c2, s2 = kv_read()
    assert (c2 - c1, s2 - s1) == (1, 13 + 16 + 100)
    while engine.has_unfinished_requests():
        engine.step()
    text = render_metrics(core.get_stats())
    for family in ("vdt:step_kv_tokens_read_count",
                   'vdt:step_tokens_count{kind="decode"}',
                   'vdt:step_tokens_count{kind="prefill"}',
                   "vdt:request_queue_seconds_count",
                   "vdt:step_period_seconds_count",
                   "vdt:step_host_stall_seconds_count",
                   'vdt:step_phase_seconds_count{phase="loop"}'):
        assert family in text, family


@pytest.mark.parametrize("mla,tpla,backend,max_q,want", [
    # A chunk program on the latent kernels: the rows of sequences with
    # more than one query row (17 + 2), not the decoding rows.
    (True, 1, "pallas", 32, 19),
    (True, 1, "pallas", 1, 0),       # a decode program
    (True, 1, "xla", 32, 0),         # the XLA scan keeps the absorbed form
    (True, 2, "pallas", 32, 0),      # so does TPLA
    (False, 1, "pallas", 32, 0),     # no latent cache
])
def test_latent_prefill_tokens_of_a_step(monkeypatch, mla, tpla, backend,
                                         max_q, want):
    """``vdt:step_latent_prefill_tokens`` counts what
    ``ops/mla.latent_attention_values`` serves in the multi-head form."""
    import types

    import numpy as np

    from vllm_distributed_tpu.worker.model_runner import TPUModelRunner
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", backend)
    runner = types.SimpleNamespace(
        _latent_kernels=None,
        model=types.SimpleNamespace(
            cfg=types.SimpleNamespace(mla=mla), tpla_shards=tpla))
    q_lens = np.asarray([1, 17, 1, 2, 1], np.int32)
    if max_q == 1:
        q_lens = np.ones(5, np.int32)
    assert TPUModelRunner._latent_prefill_tokens(runner, q_lens,
                                                 max_q) == want


def test_latent_prefill_tokens_are_rendered(engine):
    """A model without a latent cache observes 0 every step, and the
    family is there for the benchmark's reader."""
    core = _core(engine)
    _add(engine, "lp-a", 70, 2)
    while engine.has_unfinished_requests():
        engine.step()
    stats = core.get_stats()
    h = stats["step_latent_prefill_tokens"]
    assert h["count"] == stats["step_kv_tokens_read"]["count"] > 0
    assert h["sum"] == 0
    assert "vdt:step_latent_prefill_tokens_count" in render_metrics(stats)


def _sched_step(scheduler):
    out = scheduler.schedule()
    if out.total_num_scheduled_tokens == 0:
        return out
    req_ids, sampled = [], []
    for req_id, n in out.num_scheduled_tokens.items():
        req = scheduler.requests[req_id]
        req_ids.append(req_id)
        sampled.append([42] if req.num_computed_tokens + n
                       >= req.num_tokens else [])
    scheduler.update_from_output(
        out, ModelRunnerOutput(req_ids=req_ids, sampled_token_ids=sampled))
    return out


def test_queue_wait_counts_each_request_once_also_across_a_preemption():
    # 8 pages of 4 tokens: two requests of 15 fill the pool and the
    # second is preempted when decoding needs a ninth page.
    scheduler = Scheduler(make_config(num_blocks=8,
                                      max_num_batched_tokens=32,
                                      max_num_seqs=2))
    a = make_request(num_tokens=15, max_tokens=30)
    b = make_request(num_tokens=15, max_tokens=8)
    c = make_request(num_tokens=4, max_tokens=2)
    for r in (a, b, c):
        scheduler.add_request(r)
    assert all(r.queued_at is not None for r in (a, b, c))
    _sched_step(scheduler)            # a and b granted; c waits (2 seqs)
    assert scheduler.queue_wait.count == 2
    assert a.queued_at is None and c.queued_at is not None
    for _ in range(10):
        _sched_step(scheduler)
        if b.num_preemptions:
            break
    assert b.num_preemptions == 1
    scheduler.finish_requests(a.request_id, RequestStatus.FINISHED_ABORTED)
    for _ in range(40):
        _sched_step(scheduler)
        if not scheduler.has_requests():
            break
    assert not scheduler.has_requests()
    # b was granted tokens twice (before and after its preemption), and
    # every request counts once.
    assert scheduler.queue_wait.count == 3
    stats = scheduler.get_stats()
    assert stats["request_queue_seconds"]["count"] == 3
    steps = stats["step_tokens"]["decode"]["count"]
    assert steps == stats["step_tokens"]["prefill"]["count"] > 0
    assert stats["step_tokens"]["prefill"]["sum"] >= 15 + 15 + 4


def test_grant_composition_rides_the_scheduler_output():
    scheduler = Scheduler(make_config(max_num_batched_tokens=16))
    short = make_request(num_tokens=6, max_tokens=4)
    scheduler.add_request(short)
    out = _sched_step(scheduler)
    assert (out.num_prefill_tokens, out.num_decode_reqs) == (6, 0)
    scheduler.add_request(make_request(num_tokens=40, max_tokens=4))
    out = _sched_step(scheduler)      # one decode and a 15-token chunk
    assert (out.total_num_scheduled_tokens, out.num_prefill_tokens,
            out.num_decode_reqs) == (16, 15, 1)


def test_span_observes_its_histogram_and_a_batch_queue_timeline_does_not_chain():
    h = Histogram((0.5, ))
    with span("vdt.test", h, step=3) as s:
        pass
    assert h.count == 1 and h.total == s.seconds >= 0
    ph = StepPhases(sync=False)
    with ph.begin_step():
        with ph.span("vdt.schedule", "schedule") as first:
            pass
        with ph.span("vdt.wait", "wait") as second:
            pass
    assert second.start >= first.end      # its own clock read, no chain
    assert ph.hists["loop"].count == 0 and ph.period.count == 0
    ph.launch_issued()
    ph.fetch_begins(second.start)
    ph.fetch_done(second.end)
    assert ph.host_gap.count == ph.host_stall.count == 0
    assert set(ph.to_dict()) == {
        "step_phase_seconds", "step_period_seconds",
        "step_host_gap_seconds", "step_host_stall_seconds"}


def test_front_end_histograms_render():
    stats = FrontendStats()
    stats.process_outputs.observe(0.002)
    stats.output_lag.observe(0.003)
    text = stats.render()
    assert "vdt:frontend_process_outputs_seconds_count 1" in text
    assert "vdt:frontend_output_lag_seconds_count 1" in text
    assert 'vdt:frontend_output_lag_seconds_bucket{le="0.004"} 1' in text
