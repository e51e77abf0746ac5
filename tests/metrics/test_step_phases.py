"""The step's phases and counters (ISSUE 25), counts and structure only:
phases that partition the synchronous and the pipelined step, the period they sum to, the
host gap and the host stall; the scheduler's queue wait and step tokens;
the runner's K/V tokens read and its one closed form of a step's attention
pairs."""

import numpy as np
import pytest
from transformers import LlamaConfig

from tests.conftest import make_config, make_request
from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         SchedulerConfig)
from vllm_distributed_tpu.core.kv_cache_utils import window_step_work
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
# The thread's CPU seconds: two phases and the rest (ISSUE 52).
CPU_PARTS = ("prepare_inputs", "launch", "other")


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
    # (Not held at zero step by step: the two clocks are read a few
    # microseconds apart.)
    assert -n * 2e-5 - 0.011 <= d["step_host_stall_seconds"][1] <= \
        period_sum - d["phase.device_wait"][1] + 0.011
    while engine.has_unfinished_requests():
        engine.step()


def test_cpu_and_kinds_of_a_running_engine(engine):
    """The real clocks: counts, the CPU partition and the kinds' sum."""
    core = _core(engine)
    ph = core.phases
    _add(engine, "cpu-a", 10, 60)
    for _ in range(5):
        engine.step()
    # ``other`` closes with the period; the two phases of the step that
    # has not closed yet are the boundary.
    own_a = ph.cpu_named
    a = core.get_stats()
    _add(engine, "cpu-b", 70, 4)    # a 64-token budget: two mixed steps
    n = 30
    for _ in range(n):
        engine.step()
    own_b = ph.cpu_named
    b = core.get_stats()

    def delta(key, sub=None):
        ha, hb = (a[key], b[key]) if sub is None \
            else (a[key][sub], b[key][sub])
        return hb["count"] - ha["count"], hb["sum"] - ha["sum"]

    assert set(b["step_phase_cpu_seconds"]) == \
        set(CPU_PARTS) | {"dispatch", "wait"}
    cpu = {p: delta("step_phase_cpu_seconds", p) for p in CPU_PARTS}
    wall = {p: delta("step_phase_seconds", p) for p in PARTITION}
    assert all(c[0] == n for c in cpu.values())
    assert delta("step_cpu_seconds")[0] == n
    assert sum(c[1] for c in cpu.values()) == pytest.approx(
        delta("step_cpu_seconds")[1] + own_b - own_a, rel=1e-6, abs=1e-9)
    # CPU <= wall up to the clocks' resolution: microseconds a read here,
    # a 10 ms tick of the thread clock on a sandboxed host.
    for p in CPU_PARTS[:2]:
        assert cpu[p][1] <= wall[p][1] + n * 2e-5 + 0.011, p
    assert cpu["other"][1] <= sum(
        wall[p][1] for p in PARTITION if p not in CPU_PARTS) \
        + n * 2e-5 + 0.011
    kinds = {k: delta("step_kind_seconds", k)
             for k in b["step_kind_seconds"]}
    # The period of step k is observed at step k+1's start: of the n
    # periods counted here the first belongs to the decode step before.
    assert kinds["mixed/period"][0] == kinds["mixed/device_wait"][0] == 2
    assert kinds["decode/period"][0] == n - 2
    assert kinds["mixed/period"][1] + kinds["decode/period"][1] == \
        pytest.approx(delta("step_period_seconds")[1], rel=1e-9)
    assert kinds["mixed/device_wait"][1] + kinds["decode/device_wait"][1] \
        == pytest.approx(wall["device_wait"][1], rel=1e-9)
    # The synchronous engine stamps no request: none observed.
    assert b["input_queue_seconds"]["count"] == 0
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


def test_decode_kv_tokens_walked_of_decodes_beside_a_prefill_chunk(engine):
    """``vdt:step_decode_kv_tokens_walked``: a step's single-token rows
    at whole kv blocks up to each one's own end (pages of 4 in blocks of
    128 here), a prefill chunk's rows left out, observed once a step
    beside ``vdt:step_kv_tokens_read``."""
    core = _core(engine)
    # Ids whose prompts (``_add``) share no first block with another
    # test's: a prefix-cache hit would move the chunks.

    def walked():
        stats = core.get_stats()
        h = stats["step_decode_kv_tokens_walked"]
        assert h["count"] == stats["step_kv_tokens_read"]["count"]
        return h["sum"]

    _add(engine, "dw-x", 10, 12)
    _add(engine, "dw-y", 126, 12)
    s0 = walked()
    engine.step()     # dw-x's prompt and 54 tokens of dw-y's: no decode
    assert walked() - s0 == 0
    engine.step()     # dw-x at 11, a chunk of 63
    engine.step()     # dw-x at 12, dw-y's last 9
    assert walked() - s0 == 2 * 128
    sums = []
    for _ in range(4):  # dw-x at 13..16, dw-y at 127, 128, 129, 130
        engine.step()
        sums.append(walked())
    assert np.diff([s0 + 2 * 128] + sums).tolist() == [
        128 + 128, 128 + 128, 128 + 256, 128 + 256]
    while engine.has_unfinished_requests():
        engine.step()
    assert "vdt:step_decode_kv_tokens_walked_count" in render_metrics(
        core.get_stats())


def _work_token_by_token(rows, window: int):
    """(window tokens read, full pairs, window pairs) of each row, one
    query at a time: the query at position p sees the keys (p - window,
    p], all of them in a full layer; a window layer reads a key once
    however many of the row's queries see it."""
    out = []
    for ctx, n in rows:
        read, full, windowed = set(), 0, 0
        for p in range(ctx, ctx + n):
            seen = range(max(0, p - window + 1), p + 1)
            read.update(seen)
            windowed += len(seen)
            full += p + 1
        out.append((len(read), full, windowed))
    return out


_MIXED = [(40, 24), (63, 1), (7, 1)]
_SEVERAL = [(0, 8), (20, 30), (100, 16), (31, 1), (5, 64)]


@pytest.mark.parametrize("rows, window", [
    ([(0, 8)], 32),      # a prompt inside the window
    ([(20, 30)], 32),    # a chunk that crosses it
    ([(100, 16)], 32),   # a chunk wholly past it
    ([(31, 1)], 32),     # the last decode that sees everything
    ([(32, 1)], 32),     # the first that does not
    ([(0, 1)], 32),
    ([(31, 2)], 32),     # straddles by one
    ([(5, 64)], 32),     # a chunk longer than the window, from under it
    (_MIXED, 32),        # a chunk and two decodes
    (_SEVERAL, 32),
    (_SEVERAL, 4096),    # a window no context reaches
], ids=lambda v: "-".join(f"{c}+{n}" for c, n in v)
    if isinstance(v, list) else f"W{v}")
def test_a_steps_attention_work_token_by_token(rows, window):
    """(context, scheduled) rows as ``_prepare_inputs`` hands them over:
    ``seq_info``'s query lengths and context ends, int32."""
    q_len = np.array([n for _, n in rows], np.int32)
    end = np.array([c + n for c, n in rows], np.int32)
    got = np.stack(window_step_work(q_len, end, window), axis=1)
    assert got.dtype == np.int64
    assert got.tolist() == [list(r) for r in
                            _work_token_by_token(rows, window)]
    # A batch is its rows, one by one.
    alone = [np.stack(window_step_work(q_len[i:i + 1], end[i:i + 1],
                                       window), axis=1)[0]
             for i in range(len(rows))]
    assert got.sum(axis=0).tolist() == np.sum(alone, axis=0).tolist()
    if window > int(end.max()):
        assert got[:, 0].tolist() == end.tolist()
        assert got[:, 1].tolist() == got[:, 2].tolist()


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
    assert ph.host_gap.count == ph.host_stall.count == 0
    assert set(ph.to_dict()) == {
        "step_phase_seconds", "step_phase_cpu_seconds",
        "step_kind_seconds", "step_period_seconds", "step_cpu_seconds",
        "step_host_gap_seconds", "step_host_stall_seconds",
        "input_queue_seconds", "step_device_seconds", "step_device_seen",
        "step_device_idle_at_issue", "step_fetch_seconds"}


def test_front_end_histograms_render():
    stats = FrontendStats()
    stats.process_outputs.observe(0.002)
    stats.output_lag.observe(0.003)
    text = stats.render()
    assert "vdt:frontend_process_outputs_seconds_count 1" in text
    assert "vdt:frontend_output_lag_seconds_count 1" in text
    assert 'vdt:frontend_output_lag_seconds_bucket{le="0.004"} 1' in text


# ---------------------------------------------------------------------
# CPU beside wall, and steps by kind (ISSUE 39), on scripted clocks: the
# wall clock and the thread's CPU clock of metrics/stats.py advance only
# where the test says, in multiples of 1/1024 s, so sums are exact.
# ---------------------------------------------------------------------
class _Clocks:
    def __init__(self) -> None:
        self.wall, self.cpu = 100.0, 7.0

    def perf_counter(self) -> float:
        return self.wall

    def thread_time(self) -> float:
        return self.cpu

    def monotonic(self) -> float:
        return self.wall

    def advance(self, wall: int, cpu: int) -> None:
        self.wall += wall / 1024
        self.cpu += cpu / 1024


@pytest.fixture()
def clocks(monkeypatch):
    from vllm_distributed_tpu.metrics import stats
    c = _Clocks()
    monkeypatch.setattr(stats, "time", c)
    return c


# (wall, cpu) of each phase in 1/1024 s; loop is what follows the step.
_EVEN = {p: (4, 4) for p in PARTITION}
_WAITS = dict(_EVEN, prepare_inputs=(9, 3), launch=(11, 5),
              device_wait=(16, 1), loop=(6, 2))
_CPU_IN_THE_WAIT = dict(_WAITS, device_wait=(16, 5), schedule=(3, 3))


def _scripted_step(ph: StepPhases, clocks: _Clocks, costs: dict,
                   kind: str) -> None:
    with ph.begin_step():
        for p in PARTITION[1:]:
            with ph.span(f"vdt.{p}", p):
                clocks.advance(*costs[p])
            if p == "schedule":
                ph.kind = kind      # as EngineCore._step_sync does
            if p == "launch":
                ph.launch_issued()
    ph.end_step(more_work=True)
    clocks.advance(*costs["loop"])


@pytest.mark.parametrize("costs", [_EVEN, _WAITS, _CPU_IN_THE_WAIT],
                         ids=["no-wait", "waits", "cpu-in-the-wait"])
def test_cpu_partitions_the_step_as_wall_does(clocks, costs):
    ph = StepPhases()
    n = 5
    for _ in range(n):
        _scripted_step(ph, clocks, costs, "decode")
    ph.begin_step()                     # closes the n-th period
    wall = {p: ph.hists[p].total for p in PARTITION}
    cpu = {p: ph.cpu_hists[p].total for p in CPU_PARTS}
    # The first loop is the instant between resume() and the first step.
    assert ph.hists["loop"].count == n + 1
    assert all(ph.cpu_hists[p].count == ph.hists[p].count
               for p in CPU_PARTS[:2])
    assert ph.period.count == ph.step_cpu.count \
        == ph.cpu_hists["other"].count == n
    assert sum(wall.values()) == ph.period.total \
        == n * sum(w for w, _ in costs.values()) / 1024
    # The three parts: two phases and, by subtraction, the rest.
    assert sum(cpu.values()) == ph.step_cpu.total \
        == n * sum(c for _, c in costs.values()) / 1024
    for p in CPU_PARTS[:2]:
        assert cpu[p] <= wall[p], p
        assert cpu[p] == n * costs[p][1] / 1024
    assert cpu["other"] == n * sum(
        c for p, (_, c) in costs.items() if p not in CPU_PARTS) / 1024
    # The stall: wall less CPU of every phase between one fetch's return
    # and the next fetch's start, which is every phase but device_wait.
    waited = sum(w - c for p, (w, c) in costs.items()
                 if p != "device_wait") / 1024
    assert ph.host_stall.count == n - 1
    assert ph.host_stall.total == (n - 1) * waited
    # period - step CPU - device wait differs from it by the CPU that
    # the thread burnt inside the wait.
    per_step = (ph.period.total - ph.step_cpu.total
                - wall["device_wait"]) / n
    assert per_step == waited - costs["device_wait"][1] / 1024
    assert ph.host_gap.count == n - 1


def test_the_stall_of_a_ticking_cpu_clock_is_right_in_the_mean(clocks):
    """A thread clock that advances in ticks longer than a step (10 ms
    on the chip's sandboxed host): a step's stall reads a tick too low
    or too high, below zero too, and the sum is still the phases' wall
    less CPU outside device_wait."""
    ph = StepPhases()
    quiet = {p: (4, 0) for p in PARTITION}          # no tick in this step
    ticked = dict(quiet, launch=(4, 40))            # a tick lands in launch
    n = 6
    for i in range(n):
        _scripted_step(ph, clocks, ticked if i % 2 else quiet, "decode")
    ph.begin_step()
    # Windows from one fetch's return to the next fetch's start: finish,
    # update, loop of step i, then schedule .. launch of step i + 1.
    want = [(3 * 4 + 4 * 4) - (40 if (i + 1) % 2 else 0)
            for i in range(n - 1)]
    assert min(want) < 0 < max(want)
    assert ph.host_stall.count == n - 1
    assert ph.host_stall.total == sum(want) / 1024
    assert ph.period.total - ph.step_cpu.total \
        - ph.hists["device_wait"].total \
        == sum((w - c) for costs in (quiet, ticked) for p, (w, c)
               in costs.items() if p != "device_wait") * (n // 2) / 1024


def test_a_period_has_the_kind_of_the_step_that_ended(clocks):
    ph = StepPhases()
    mixed = dict(_WAITS, device_wait=(64, 2))
    kinds = ["mixed", "decode", "decode", "mixed", "decode"]
    for kind in kinds:
        _scripted_step(ph, clocks, mixed if kind == "mixed" else _WAITS,
                       kind)
    ph.begin_step()
    got = {k: (h.count, h.total) for k, h in ph.kind_hists.items()}
    period = {"mixed": sum(w for w, _ in mixed.values()) / 1024,
              "decode": sum(w for w, _ in _WAITS.values()) / 1024}
    assert got["mixed/period"] == (2, 2 * period["mixed"])
    assert got["decode/period"] == (3, 3 * period["decode"])
    assert got["mixed/device_wait"] == (2, 2 * 64 / 1024)
    assert got["decode/device_wait"] == (3, 3 * 16 / 1024)
    # The two kinds add up to the unlabelled families.
    assert got["mixed/period"][0] + got["decode/period"][0] \
        == ph.period.count
    assert got["mixed/period"][1] + got["decode/period"][1] \
        == ph.period.total
    assert got["mixed/device_wait"][1] + got["decode/device_wait"][1] \
        == ph.hists["device_wait"].total


def _pipelined_step(ph: StepPhases, clocks: _Clocks, costs: dict,
                    launches, retires) -> None:
    """EngineCore._step_pipelined's order: the first five phases for the
    batch it launches, the last three for the one it retires (None:
    pipeline fill / drain). A batch is its kind."""
    with ph.begin_step():
        if launches is not None:
            for p in PARTITION[1:5]:
                with ph.span(f"vdt.{p}", p):
                    clocks.advance(*costs[p])
                if p == "launch":
                    ph.launch_issued()
        if retires is None:
            ph.kind = launches
        else:
            ph.kind = retires
            ph.forward_queued = launches is not None
            for p in PARTITION[5:]:
                with ph.span(f"vdt.{p}", p):
                    clocks.advance(*costs[p])
    ph.end_step(more_work=True)
    clocks.advance(*costs["loop"])


@pytest.mark.parametrize("batches", [
    ["mixed", "decode", "decode", "mixed", "decode", "decode"],
    ["decode"] * 4,
    ["mixed", "mixed", "decode"],
], ids=["chunks-between-decodes", "decodes", "two-chunks"])
def test_a_pipelined_step_has_one_period_and_the_kind_it_retires(clocks,
                                                                 batches):
    """K forwards through the depth-2 pipeline: K + 1 steps (fill ..
    drain), K periods, each of the kind of the batch the step RETIRED
    (the fill's: of the batch it launched), the phases partitioning wall
    and CPU time as on the synchronous path, a zero gap for every fetch
    that had a forward queued behind it."""
    ph = StepPhases()
    costs = {"mixed": dict(_WAITS, device_wait=(64, 2)), "decode": _WAITS}
    K = len(batches)
    for launches, retires in zip(batches + [None], [None] + batches):
        _pipelined_step(ph, clocks, costs[retires or launches], launches,
                        retires)
    assert ph.step == K + 1
    ph.begin_step()                     # closes the drain's period
    assert ph.period.count == ph.step_cpu.count == K + 1
    wall = sum(ph.hists[p].total for p in PARTITION)
    assert wall == ph.period.total
    assert sum(ph.cpu_hists[p].total for p in CPU_PARTS) \
        == ph.step_cpu.total
    assert all(ph.hists[p].count == K for p in PARTITION[1:])
    got = {k: h.count for k, h in ph.kind_hists.items()}
    # The fill's period bears the first batch's kind a second time.
    want = {k: batches.count(k) + (batches[0] == k)
            for k in ("mixed", "decode")}
    assert got["mixed/period"] == want["mixed"]
    assert got["decode/period"] == want["decode"]
    assert got["mixed/device_wait"] == batches.count("mixed")
    assert got["decode/device_wait"] == batches.count("decode")
    assert ph.kind_hists["mixed/device_wait"].total \
        == batches.count("mixed") * 64 / 1024
    assert got["mixed/period"] + got["decode/period"] == ph.period.count
    # Every fetch but the drain's had its successor queued: no gap; the
    # stall is counted from fetch to fetch as ever.
    assert (ph.host_gap.count, ph.host_gap.total) == (K - 1, 0.0)
    assert ph.host_stall.count == K - 1


def test_an_idle_wait_is_in_no_cpu_account(clocks):
    ph = StepPhases()
    _scripted_step(ph, clocks, _WAITS, "decode")
    with ph.begin_step():
        pass
    ph.end_step(more_work=False)
    before = (ph.step_cpu.count, ph.step_cpu.total,
              ph.cpu_hists["other"].total)
    clocks.advance(5000, 3)             # idle, then work arrives
    ph.resume()
    _scripted_step(ph, clocks, _WAITS, "decode")
    assert (ph.step_cpu.count, ph.step_cpu.total,
            ph.cpu_hists["other"].total) == before
    assert ph.host_stall.count == 0


def test_a_batch_queue_timeline_claims_no_partition_of_cpu_or_kind(clocks):
    ph = StepPhases(sync=False)
    with ph.begin_step():
        with ph.span("vdt.schedule", "schedule"):
            clocks.advance(2, 2)
        clocks.advance(7, 7)            # between blocks: nobody's
        with ph.span("vdt.dispatch", "dispatch"):
            with ph.span("vdt._prepare_inputs", "prepare_inputs"):
                clocks.advance(3, 1)
            clocks.advance(4, 2)
        with ph.span("vdt.wait", "wait"):
            with ph.span("vdt.device_wait", "device_wait"):
                clocks.advance(16, 1)
    ph.end_step(more_work=True)
    ph.begin_step()
    # Every block that has a CPU account its own CPU; dispatch and wait
    # contain the runner's. (``schedule`` lost its account with the five
    # other series nothing read: ISSUE 52.)
    assert "schedule" not in ph.cpu_hists
    assert ph.hists["schedule"].total == 2 / 1024
    assert ph.cpu_hists["prepare_inputs"].total == 1 / 1024
    assert ph.cpu_hists["dispatch"].total == 3 / 1024
    assert ph.hists["dispatch"].total == 7 / 1024
    assert ph.cpu_hists["wait"].total == 1 / 1024
    assert ph.step_cpu.count == ph.period.count == 0
    assert ph.cpu_hists["other"].count == 0
    assert all(h.count == 0 for h in ph.kind_hists.values())
    assert ph.host_stall.count == 0


def test_the_new_families_render_with_their_labels():
    ph = StepPhases()
    ph.kind_hists["mixed/period"].observe(0.05)
    ph.cpu_hists["launch"].observe(0.004)
    ph.step_cpu.observe(0.009)
    ph.input_queue.observe(0.008)
    text = render_metrics(ph.to_dict())
    for line in (
            'vdt:step_kind_seconds_count{kind="mixed",part="period"} 1',
            'vdt:step_kind_seconds_bucket{kind="mixed",part="period",'
            'le="0.05"} 1',
            'vdt:step_kind_seconds_count{kind="decode",'
            'part="device_wait"} 0',
            'vdt:step_phase_cpu_seconds_count{phase="launch"} 1',
            "vdt:step_cpu_seconds_count 1",
            'vdt:input_queue_seconds_bucket{le="0.008"} 1'):
        assert line in text, line
    front = FrontendStats()
    front.step_cpu.observe(0.006)
    front.admit.observe(0.001)
    front.metrics_render.observe(0.003)
    front.batches_posted, front.batches_begun = 9, 7
    text = front.render()
    for line in ("vdt:frontend_step_cpu_seconds_count 1",
                 "vdt:frontend_batches_pending 2",
                 "vdt:frontend_admit_seconds_count 1",
                 "vdt:metrics_render_seconds_count 1"):
        assert line in text, line


# ---------------------------------------------------------------------
# The chip's side of the step, read on the host (ISSUE 52): scripted
# clocks, and a fetch array whose readiness the test decides.
# ---------------------------------------------------------------------
class _Results:
    """What a forward's fetch waits for: ``jax.Array.is_ready()``."""

    def __init__(self) -> None:
        self.ready = False

    def is_ready(self) -> bool:
        return self.ready


def _device_counts(ph: StepPhases) -> dict:
    return {"idle": (ph.idle_at_issue.count, ph.idle_at_issue.total),
            "seen": (ph.device_seen.count, ph.device_seen.total),
            "device": {s: h.count for s, h in ph.device_step.items()},
            "fetch": {r: h.count for r, h in ph.fetch_hists.items()}}


def _issue(ph: StepPhases, clocks: _Clocks, shape: str = "decode"):
    """prepare_inputs and launch of one forward: (flight, array, stamp
    of the issue)."""
    with ph.span("vdt._prepare_inputs", "prepare_inputs"):
        clocks.advance(3, 3)
    with ph.span("vdt.launch", "launch"):
        clocks.advance(1, 1)
        with ph.forward_issue(shape, 16):
            issued = clocks.wall
            clocks.advance(2, 2)
        array = _Results()
        return ph.in_flight(array), array, issued


def _retire(ph: StepPhases, clocks: _Clocks, flight, array,
            ready: bool) -> float:
    """device_wait, finish and update of one forward: the fetch's
    return."""
    array.ready = ready
    with ph.fetch(flight):
        clocks.advance(1 if ready else 8, 1)
    returned = clocks.wall
    array.ready = True
    for p in ("finish", "update"):
        with ph.span(f"vdt.{p}", p):
            clocks.advance(1, 1)
    return returned


def _pipelined_forwards(ph: StepPhases, clocks: _Clocks, script) -> list:
    """EngineCore._step_pipelined's order over ``script``, one entry a
    forward: (it ended before the next forward's issue, its results were
    there when its fetch began). -> [(issue stamp, fetch's return)]."""
    flights, stamps = [], []
    K = len(script)
    for k in range(K + 1):
        with ph.begin_step():
            if k < K:
                if k and script[k - 1][0]:
                    flights[k - 1][1].ready = True
                flights.append(_issue(ph, clocks))
            if k:
                ph.forward_queued = k < K
                flight, array, issued = flights[k - 1]
                stamps.append((issued, _retire(ph, clocks, flight, array,
                                               script[k - 1][1])))
        ph.end_step(more_work=k < K)
        clocks.advance(1, 1)
    return stamps


_BUSY, _DONE_EARLY, _DONE_AT_FETCH = (False, False), (True, True), \
    (False, True)


@pytest.mark.parametrize("script, idle, seen, starts", [
    # Device-bound: every issue but the first finds the chip busy, every
    # fetch waits: a step lasts from fetch return to fetch return.
    ([_BUSY] * 4, [1, 0, 0, 0], [1, 1, 1, 1],
     ["issue", "prev", "prev", "prev"]),
    # Host-bound: every forward has ended before the next is issued, no
    # fetch waits, no step's end is seen.
    ([_DONE_EARLY] * 4, [1, 1, 1, 1], [0, 0, 0, 0], [None] * 4),
    # Found idle and a waited fetch: from the issue to the return; the
    # step behind it starts where it ended.
    ([_DONE_EARLY, _BUSY, _BUSY], [1, 1, 0], [0, 1, 1],
     [None, "issue", "prev"]),
    # Ready at the fetch: no end, so the step queued behind it has no
    # start either, though its own fetch waited.
    ([_BUSY, _DONE_AT_FETCH, _BUSY, _BUSY], [1, 0, 0, 0], [1, 0, 0, 1],
     ["issue", None, None, "prev"]),
], ids=["device-bound", "host-bound", "idle-then-waited",
        "ready-at-the-fetch"])
def test_the_devices_timeline_step_by_step(clocks, script, idle, seen,
                                           starts):
    ph = StepPhases()
    stamps = _pipelined_forwards(ph, clocks, script)
    K = len(script)
    # Once a forward: the first of a busy period counts as found idle.
    assert (ph.idle_at_issue.count, ph.idle_at_issue.total) \
        == (K, sum(idle))
    assert (ph.device_seen.count, ph.device_seen.total) == (K, sum(seen))
    want = [stamps[k][1] - (stamps[k][0] if starts[k] == "issue"
                            else stamps[k - 1][1])
            for k in range(K) if seen[k]]
    assert ph.device_step["decode"].count == sum(seen)
    assert ph.device_step["decode"].total == pytest.approx(sum(want),
                                                           abs=1e-12)
    assert all(v > 0 for v in want)
    ready = [r for _, r in script]
    assert ph.fetch_hists["1"].count == sum(ready)
    assert ph.fetch_hists["0"].count == K - sum(ready)
    # A fetch is timed from its own start: 8 ticks waited, 1 copied.
    assert ph.fetch_hists["0"].total == (K - sum(ready)) * 8 / 1024
    assert ph.fetch_hists["1"].total == sum(ready) * 1 / 1024
    # The host's bookkeeping says nothing of all this: 0 a fetch.
    assert (ph.host_gap.count, ph.host_gap.total) == (K - 1, 0.0)
    # ... and the timeline holds no array once its fetch is over.
    assert ph._newest.array is None and ph._newest.after is None


@pytest.mark.parametrize("ready", [False, True],
                         ids=["waited", "ready-at-the-fetch"])
def test_the_synchronous_step_obeys_the_same_rules(clocks, ready):
    """LLM.generate's step: every forward finds the chip idle; a fetch
    that waited gives ret - issue, one that found its results gives no
    device time."""
    ph = StepPhases()
    n, want = 4, []
    for _ in range(n):
        with ph.begin_step():
            with ph.span("vdt.schedule", "schedule"):
                clocks.advance(1, 1)
            flight, array, issued = _issue(ph, clocks, "mixed")
            want.append(_retire(ph, clocks, flight, array, ready) - issued)
        ph.end_step(more_work=True)
        clocks.advance(1, 1)
    assert (ph.idle_at_issue.count, ph.idle_at_issue.total) == (n, n)
    assert (ph.device_seen.count, ph.device_seen.total) \
        == (n, 0 if ready else n)
    assert ph.device_step["mixed"].count == (0 if ready else n)
    assert ph.device_step["mixed"].total == pytest.approx(
        0 if ready else sum(want), abs=1e-12)
    assert ph.device_step["decode"].count == 0
    assert ph.fetch_hists["1" if ready else "0"].count == n


def test_pipeline_parallelism_and_the_burst_observe_no_device_time(clocks):
    nothing = {"idle": (0, 0.0), "seen": (0, 0.0),
               "device": {"decode": 0, "mixed": 0, "chunk": 0},
               "fetch": {"0": 0, "1": 0}}
    # Pipeline parallelism's queue: no flight, a plain device_wait.
    pp = StepPhases(sync=False)
    with pp.begin_step():
        with pp.span("vdt.dispatch", "dispatch"):
            flight, _, _ = _issue(pp, clocks)
        assert flight is None
        with pp.span("vdt.wait", "wait"):
            with pp.fetch(flight):
                clocks.advance(8, 1)
    pp.end_step(more_work=True)
    assert _device_counts(pp) == nothing
    assert pp.hists["device_wait"].count == 1
    # The multi-step burst blocks inside a device_wait of its own: a
    # fetch of the timeline (kind, stall), no forward of the device's.
    ph = StepPhases()
    for _ in range(3):
        with ph.begin_step():
            with ph.span("vdt.device_wait", "device_wait"):
                clocks.advance(16, 1)
        ph.end_step(more_work=True)
        clocks.advance(1, 1)
    assert _device_counts(ph) == nothing
    assert ph.kind_hists["decode/device_wait"].count == 3
    assert ph.host_stall.count == 2


def test_a_pipelined_step_reads_the_thread_clock_five_times(monkeypatch):
    """PR 39's debt: eight reads a step at 6 us each on the chip's host.
    Five are left: the step's start, both ends of prepare_inputs, the
    end of launch (where the fetch starts) and the fetch's return."""
    from vllm_distributed_tpu.metrics import stats

    class Counting(_Clocks):
        reads = 0

        def thread_time(self) -> float:
            self.reads += 1
            return self.cpu

    c = Counting()
    monkeypatch.setattr(stats, "time", c)
    ph = StepPhases()
    flights = []
    per_step = []
    for k in range(6):
        before = c.reads
        with ph.begin_step():
            with ph.span("vdt.schedule", "schedule"):
                c.advance(1, 1)
            with ph.span("vdt._update_states", "update_states"):
                c.advance(1, 1)
            flights.append(_issue(ph, c))
            if k:
                ph.forward_queued = True
                flight, array, _ = flights[k - 1]
                _retire(ph, c, flight, array, ready=False)
        ph.end_step(more_work=True)
        c.advance(1, 1)
        per_step.append(c.reads - before)
    assert per_step == [4] + [5] * 5     # the fill fetches nothing
    # A drain (nothing launched): the start and the fetch's return.
    before = c.reads
    with ph.begin_step():
        flight, array, _ = flights[-1]
        _retire(ph, c, flight, array, ready=False)
    ph.end_step(more_work=False)
    assert c.reads - before == 2
    # And the partition of three still sums, with the stall as it was.
    assert sum(ph.cpu_hists[p].total for p in CPU_PARTS) \
        == ph.step_cpu.total
    assert ph.host_stall.count == 5


@pytest.mark.parametrize("tokens, prefill, want", [
    (5, 0, ("decode", 16)),
    (64, 0, ("decode", 64)),         # the largest bucket, all decodes
    (20, 12, ("mixed", 32)),
    (32, 32, ("mixed", 32)),         # below the largest bucket
    (33, 30, ("chunk", 64)),         # pads into the largest bucket
    (64, 60, ("chunk", 64)),
], ids=["decode", "decode-64", "mixed", "mixed-32", "chunk-33", "chunk"])
def test_shape_of_a_step_from_its_scheduler_output(engine, tokens, prefill,
                                                   want):
    from types import SimpleNamespace
    runner = _core(engine).executor.worker.model_runner
    assert runner.token_buckets[-1] == 64
    out = SimpleNamespace(total_num_scheduled_tokens=tokens,
                          num_prefill_tokens=prefill)
    assert runner._step_shape(out) == want


def test_a_running_engine_observes_each_forward_once(engine):
    """The real runner on the synchronous step: one observation a
    forward in the three per-forward families, every issue finds the
    chip idle, a 64-token chunk is a ``chunk`` and its tail a
    ``mixed``."""
    core = _core(engine)
    ph = core.phases
    a = _device_counts(ph)
    _add(engine, "dev-a", 70, 6)     # 64-token budget: a chunk, a tail
    steps = 0
    while engine.has_unfinished_requests():
        engine.step()
        steps += 1
    b = _device_counts(ph)
    assert steps == 7
    assert b["idle"][0] - a["idle"][0] == steps
    assert b["idle"][1] - a["idle"][1] == steps
    assert b["seen"][0] - a["seen"][0] == steps
    fetches = sum(b["fetch"].values()) - sum(a["fetch"].values())
    assert fetches == steps
    seen = b["seen"][1] - a["seen"][1]
    by_shape = {s: b["device"][s] - a["device"][s] for s in b["device"]}
    assert sum(by_shape.values()) == seen
    # What was seen is what waited (on this backend most fetches do).
    assert seen == b["fetch"]["0"] - a["fetch"]["0"]
    assert by_shape["chunk"] <= 1 and by_shape["mixed"] <= 1
    assert by_shape["decode"] <= 5


def test_the_devices_families_render_and_have_readers():
    from vllm_distributed_tpu.metrics.prometheus import (
        HISTOGRAMS, LABELED_HISTOGRAMS, LABELED_METRICS)
    from vllm_distributed_tpu.metrics.stats import (DEVICE_STEP_BUCKETS,
                                                    STEP_PHASE_BUCKETS)
    ph = StepPhases()
    # Every stats key of the timeline has a renderer, and every labelled
    # family its label set in the registry scripts/lint_metrics.py reads.
    for key in ph.to_dict():
        assert key in HISTOGRAMS or key in LABELED_HISTOGRAMS, key
        if key in LABELED_HISTOGRAMS:
            name, _, labels = LABELED_HISTOGRAMS[key]
            assert LABELED_METRICS[name] == labels
    ph.device_step["chunk"].observe(0.113)
    ph.device_seen.observe(1)
    ph.device_seen.observe(0)
    ph.idle_at_issue.observe(0)
    ph.fetch_hists["1"].observe(0.0011)
    text = render_metrics(ph.to_dict())
    for line in (
            'vdt:step_device_seconds_count{shape="chunk"} 1',
            'vdt:step_device_seconds_bucket{shape="chunk",le="0.115"} 1',
            'vdt:step_device_seconds_bucket{shape="chunk",le="0.11"} 0',
            'vdt:step_device_seconds_count{shape="decode"} 0',
            'vdt:step_device_seen_bucket{le="0"} 1',
            "vdt:step_device_seen_count 2",
            "vdt:step_device_seen_sum 1",
            'vdt:step_device_idle_at_issue_bucket{le="0"} 1',
            'vdt:step_fetch_seconds_count{ready="1"} 1',
            'vdt:step_fetch_seconds_bucket{ready="1",le="0.00125"} 1',
            'vdt:step_fetch_seconds_count{ready="0"} 0',
            'vdt:step_phase_cpu_seconds_count{phase="other"} 0'):
        assert line in text, line
    # The six series nothing read are gone from the exposition.
    for gone in ("loop", "schedule", "update_states", "device_wait",
                 "finish", "update"):
        assert f'vdt:step_phase_cpu_seconds_count{{phase="{gone}"}}' \
            not in text
        assert f'vdt:step_phase_seconds_count{{phase="{gone}"}}' in text
    # Read by quantile: neighbours at most 1.25x apart under 200 ms, from
    # 1 ms to 1 s; the phase families, read by their means, keep theirs.
    edges = DEVICE_STEP_BUCKETS
    assert edges[0] == 0.001 and edges[-1] == 1.0
    assert all(b / a <= 1.25 + 1e-9 for a, b in zip(edges, edges[1:])
               if b <= 0.2)
    assert ph.fetch_hists["0"].buckets == edges
    assert ph.hists["device_wait"].buckets == STEP_PHASE_BUCKETS


# Observed before the window and inside it, seconds (flags: 0 / 1).
_RECORDED = {
    "idle": ([1, 1], [1, 0, 0, 0]),
    "seen": ([0, 0], [1, 1, 1, 0]),
    # 4 in (16 ms, 17 ms]: rank 2 of 4 lies halfway through it.
    "decode": ([0.3], [0.0165] * 4),
    # 2 in (110 ms, 115 ms], one a bucket up: rank 1.5 of 3.
    "chunk": ([0.9], [0.113, 0.113, 0.118]),
    # 6 in (1 ms, 1.25 ms].
    "ready": ([0.5], [0.0011] * 6),
}
_READ = {
    "engine.device_found_idle_share": 25.0,
    "engine.device_step_seen_share": 75.0,
    "engine.device_decode_step_ms_p50": 16.5,
    "engine.device_chunk_step_ms_p50": 110 + 5 * 1.5 / 2,
    "runner.fetch_ready_p50_ms": 1.125,
}


def _recorded_scrape(upto: int) -> list:
    from benchmarks import metrics
    ph = StepPhases()
    hists = {"idle": ph.idle_at_issue, "seen": ph.device_seen,
             "decode": ph.device_step["decode"],
             "chunk": ph.device_step["chunk"],
             "ready": ph.fetch_hists["1"]}
    for key, h in hists.items():
        for part in _RECORDED[key][:upto]:
            for v in part:
                h.observe(v)
    return metrics.parse_prometheus(render_metrics(ph.to_dict()))


@pytest.mark.parametrize("name", list(_READ))
def test_the_five_reader_files_read_two_recorded_scrapes(name):
    import json
    import os

    from benchmarks import layers, metrics

    def ctx(before, after):
        return layers.Context(before=before, after=after, gauges=[],
                              client={}, trace_dir="/nonexistent")

    got = layers.evaluate_all([{"name": name}],
                              ctx(_recorded_scrape(1), _recorded_scrape(2)))
    assert got == {name: pytest.approx(_READ[name])}
    # Nothing observed inside the window, and a program without the
    # family (the parent commit): no value, no error.
    assert layers.evaluate_all(
        [{"name": name}],
        ctx(_recorded_scrape(2), _recorded_scrape(2))) == {name: None}
    parent = metrics.parse_prometheus("vdt:step_period_seconds_count 7\n")
    assert layers.evaluate_all([{"name": name}],
                               ctx(parent, parent)) == {name: None}
    # Its manifest entry: appended, a program counter, listed only where
    # the end-to-end metric it moves is reported.
    repo = os.path.normpath(os.path.join(os.path.dirname(__file__), "..",
                                         ".."))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in names if n in _READ] == [
        "engine.device_found_idle_share",
        "engine.device_decode_step_ms_p50",
        "engine.device_step_seen_share", "runner.fetch_ready_p50_ms",
        "engine.device_chunk_step_ms_p50"]
    entry = manifest["per_layer"][names.index(name)]
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {e["name"]: e.get("workloads", cells)
               for e in manifest["end_to_end"]}
    assert set(entry["workloads"]) <= set(reports[entry["moves"]])
    assert entry["source"] == "program_counter"


def test_the_timeline_check_holds_the_hosts_answers_to_a_device_plane():
    """scripts/device_timeline_check.py on a capture written by hand:
    three steps on the device, the host right about two issues and two
    fetches and wrong about one of each."""
    import importlib.util
    import os
    path = os.path.normpath(os.path.join(
        os.path.dirname(__file__), "..", "..", "scripts",
        "device_timeline_check.py"))
    spec = importlib.util.spec_from_file_location("timeline_check", path)
    check = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check)

    def step(t, forward=0.010, sampler=0.002):
        return [("jit_forward(123)", t, forward),
                ("jit_gather(7)", t + forward, 0.0),
                ("jit_sample(45)", t + forward, sampler)]

    modules = [("jit_add(1)", 0.990, 0.001)] + step(1.000) + step(1.012) \
        + step(1.040) + [("jit__state_row_to_pool(9)", 1.052, 0.001)]
    ops = [("fusion.1", s, d) for _, s, d in modules if d]

    def issue(t, step, shape, idle):
        return (t, 0.0005, {"step": step, "shape": shape, "t_bucket": 16,
                            "found_idle": idle, "_r": 1})

    issued = [issue(0.999, 1, "decode", 1),     # idle: right
              issue(1.005, 2, "decode", 0),     # forward 1 runs: right
              issue(1.039, 3, "chunk", 0),      # idle since 1.024: WRONG
              issue(2.000, 4, "decode", 1)]     # past the capture
    waits = [(1.006, 0.006, {"step": 2, "ready": 0, "issued": 1}),
             (1.030, 0.001, {"step": 3, "ready": 1, "issued": 2}),
             (1.045, 0.001, {"step": 4, "ready": 1, "issued": 3}),  # WRONG
             (1.046, 0.001, {"step": 4}),                # a burst's wait
             (2.100, 0.001, {"step": 5, "ready": 0, "issued": 4})]
    got = check.timeline(ops, modules, issued, waits)
    assert got["issues"] == 3
    assert got["found_idle"]["agree"] == 2
    assert got["found_idle"]["share"] == pytest.approx(66.67)
    assert got["found_idle"]["host_said_idle"] == 1
    assert [d["step"] for d in got["found_idle"]["disagree"]] == [3]
    assert got["ready"]["events"] == 3 and got["ready"]["agree"] == 2
    assert got["ready"]["disagree"][0]["issued"] == 3
    assert got["ready"]["disagree"][0]["sampler_end_before_fetch_ms"] \
        == pytest.approx(-7.0)
    assert got["shapes"]["decode"] == {
        "steps_in_capture": 2, "span_ms_p50": pytest.approx(12.0),
        "period_ms_p50": pytest.approx(12.0)}      # one period: step 2's
    assert got["shapes"]["chunk"] == {
        "steps_in_capture": 1, "span_ms_p50": pytest.approx(12.0),
        "period_ms_p50": pytest.approx(28.0)}
    # Beside the run's own medians, where the window's scrapes are given.
    before, after = _recorded_scrape(1), _recorded_scrape(2)
    got = check.timeline(ops, modules, issued, waits, (before, after))
    assert got["shapes"]["decode"]["host_ms_p50"] == pytest.approx(16.5)
    assert got["shapes"]["decode"]["host_steps_seen"] == 4
    assert got["shapes"]["decode"]["host_over_device_pct"] \
        == pytest.approx(37.5)
    assert got["found_idle_share_window"] == 25.0
    assert got["seen_share_window"] == 75.0
    # A capture of the parent (no such events): nothing judged, no error.
    assert check.timeline(ops, modules, [], [])["found_idle"]["share"] \
        is None
    # The capture's two clocks, 2 ms apart (the chip's captures read 0.8
    # and 2.1): the offset is read off the waited fetches' returns, less
    # what the copies cost (the ready fetches' length), and the verdicts
    # are the same.
    ahead = 0.002
    late = [(t + ahead, d, st) for t, d, st in issued]
    copies = [(1.2 + k / 100 + ahead, 0.001, {"ready": 1, "issued": 90 + k})
              for k in range(5)]
    # ... a waited fetch returns a copy's length after its sampler's end.
    shifted = [(t + ahead, d + (0.001 if not st.get("ready", 1) else 0),
                st) for t, d, st in waits] + copies
    moved = check.timeline(ops, modules, late, shifted)
    assert moved["host_clock_ahead_ms"] == pytest.approx(2.0)
    assert moved["found_idle"]["agree"] == 2 and moved["issues"] == 3
    assert moved["ready"]["events"] == 3 and moved["ready"]["agree"] == 2
    assert check.timeline(ops, modules, issued, waits)[
        "host_clock_ahead_ms"] == 0.0
