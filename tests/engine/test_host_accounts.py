"""The host's accounts of a served tiny model (ISSUE 39), counts and
structure only, on the CPU: the engine thread's CPU beside wall, the API
thread's CPU a step and its pending batches, the stamps of a request's
way in, the step stamps of both transports, steps by kind, and a capture
that runs off the event loop's thread. One AsyncLLM a transport: the
in-process core and ``multiprocess_engine_core`` (msgpack over ZMQ)."""

import asyncio
import threading
import time
import types

import pytest
from transformers import LlamaConfig

from tests.metrics.test_step_phases import CPU_PARTS, HF, PARTITION
from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         ParallelConfig, SchedulerConfig)
from vllm_distributed_tpu.engine import async_llm, core_proc, serial
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.entrypoints.openai import api_server
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import StepPhases
from vllm_distributed_tpu.request import (EngineCoreRequest,
                                          continuation_request)
from vllm_distributed_tpu.sampling_params import SamplingParams

REQUESTS = 7


def make_config(multiprocess: bool) -> EngineConfig:
    config = EngineConfig(
        model_config=ModelConfig(model="tiny-accounts-dummy",
                                 dtype="float32", max_model_len=256,
                                 hf_overrides=HF,
                                 skip_tokenizer_init=True),
        cache_config=CacheConfig(block_size=4,
                                 num_gpu_blocks_override=256),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                         max_num_seqs=8,
                                         max_model_len=256),
        parallel_config=ParallelConfig(
            multiprocess_engine_core=multiprocess),
        load_config=LoadConfig(load_format="dummy"))
    config.model_config.hf_config = LlamaConfig(**HF)
    return config


async def _generate(engine, n: int) -> None:
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)

    async def one(i):
        prompt = [2 + (i * 7 + j) % 120 for j in range(10 + 30 * (i % 2))]
        async for _ in engine.generate(prompt, sp, request_id=f"r{i}"):
            pass

    await asyncio.gather(*[one(i) for i in range(n)])


def _fake_request(engine):
    return types.SimpleNamespace(app={api_server.ENGINE_KEY: engine})


@pytest.fixture(scope="module", params=["inproc", "mp"])
def served(request):
    """What one served run leaves behind: the engine core's stats, the
    front end's, every batch as ``_process_batch`` took it, the ``step``
    that ``vdt.process_outputs`` was given, two /metrics texts."""
    batches, span_steps = [], []

    def recording_span(name, hist=None, **attrs):
        if name == "vdt.process_outputs":
            span_steps.append(attrs["step"])
        return real_span(name, hist, **attrs)

    real_span = async_llm.span

    async def run():
        engine = AsyncLLM(make_config(request.param == "mp"),
                          load_tokenizer=False)
        process = engine._process_batch

        def recording_process(outs):
            batches.append((type(outs), outs.step, outs.handed_over,
                            time.monotonic()))
            process(outs)

        engine._process_batch = recording_process
        try:
            await _generate(engine, REQUESTS)
            first = (await api_server.metrics(_fake_request(engine))).text
            second = (await api_server.metrics(_fake_request(engine))).text
            return {"core": await engine.get_stats(),
                    "front": engine.output_processor.stats,
                    "events": engine.output_processor.events.snapshot(),
                    "metrics": (first, second)}
        finally:
            engine.shutdown()

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("VDT_RPC_TIMEOUT", "300")
        mp.setattr(async_llm, "span", recording_span)
        out = asyncio.run(run())
    out.update(batches=batches, span_steps=span_steps)
    return out


def test_the_engine_threads_cpu_is_exported_phase_by_phase(served):
    core = served["core"]
    steps = core["step_phase_seconds"]["schedule"]["count"]
    assert steps >= 12
    cpu = core["step_phase_cpu_seconds"]
    # Two phases and the rest (ISSUE 52): the six series nothing read
    # are no longer kept up, on either transport.
    assert set(cpu) == set(CPU_PARTS) | {"dispatch", "wait"}
    assert not set(cpu) & (set(PARTITION) - set(CPU_PARTS))
    for p in CPU_PARTS[:2]:
        assert cpu[p]["count"] == core["step_phase_seconds"][p]["count"]
        # CPU <= wall up to the clocks' resolution: microseconds a read
        # here, a 10 ms tick of the thread clock on a sandboxed host.
        assert cpu[p]["sum"] <= core["step_phase_seconds"][p]["sum"] \
            + steps * 2e-5 + 0.011, p
    periods = core["step_period_seconds"]["count"]
    assert core["step_cpu_seconds"]["count"] == periods > 0
    assert cpu["other"]["count"] == periods
    assert 0 <= core["step_cpu_seconds"]["sum"] \
        <= core["step_period_seconds"]["sum"] + 0.011
    # One busy period or several: the three parts sum to step_cpu but
    # for the two phases of the steps whose period never closed (the
    # last of a busy period), which only add.
    parts = sum(cpu[p]["sum"] for p in CPU_PARTS)
    assert parts >= core["step_cpu_seconds"]["sum"] - 1e-9


def test_the_kinds_add_up_to_the_period_and_the_device_wait(served):
    core = served["core"]
    kinds = core["step_kind_seconds"]
    assert set(kinds) == {"decode/period", "decode/device_wait",
                          "mixed/period", "mixed/device_wait"}
    assert kinds["mixed/period"]["count"] > 0
    assert kinds["decode/period"]["count"] > 0
    assert kinds["mixed/period"]["count"] + \
        kinds["decode/period"]["count"] == \
        core["step_period_seconds"]["count"]
    assert kinds["mixed/device_wait"]["count"] + \
        kinds["decode/device_wait"]["count"] == \
        core["step_phase_seconds"]["device_wait"]["count"]


def test_the_devices_timeline_counts_each_forward_once(served):
    """The four families of ISSUE 52 over either transport's stats feed:
    one observation a forward, the synchronous step finds the chip idle
    at every issue, and a device time wherever the fetch waited."""
    core = served["core"]
    forwards = core["step_phase_seconds"]["launch"]["count"]
    assert forwards >= 12
    idle, seen = core["step_device_idle_at_issue"], core["step_device_seen"]
    assert idle["buckets"] == seen["buckets"] == [0, 1]
    assert idle["count"] == seen["count"] == forwards
    assert idle["sum"] == forwards
    fetch, device = core["step_fetch_seconds"], core["step_device_seconds"]
    assert set(fetch) == {"0", "1"}
    assert set(device) == {"decode", "mixed", "chunk"}
    assert fetch["0"]["count"] + fetch["1"]["count"] == forwards
    assert sum(h["count"] for h in device.values()) == seen["sum"] \
        == fetch["0"]["count"]
    assert device["mixed"]["count"] + device["chunk"]["count"] <= \
        core["step_kind_seconds"]["mixed/device_wait"]["count"]
    # Read by quantile, so on the fine edges; the phases keep theirs.
    assert len(device["decode"]["buckets"]) > 40
    assert fetch["1"]["buckets"] == device["decode"]["buckets"]
    assert len(core["step_phase_seconds"]["device_wait"]["buckets"]) == 16


def test_a_pipelined_engine_asks_the_device_once_a_forward():
    """The real runner under the pipelined step (what ``vdt serve``
    runs): one ``is_ready()`` at each issue and one before each fetch, a
    busy period's first forward found idle, and a device time for every
    forward whose start and end the host saw, none for the rest."""
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    config = make_config(False)
    config.scheduler_config.async_scheduling = True
    engine = LLMEngine(config, load_tokenizer=False)
    try:
        core = engine.engine_core.engine_core
        assert core.async_scheduling and core.phases.sync
        sp = SamplingParams(temperature=0.0, max_tokens=24,
                            ignore_eos=True)
        for period in range(2):
            for i in range(3):
                engine.add_request(
                    f"p{period}-{i}",
                    [2 + (i * 7 + j) % 120 for j in range(10 + 60 * (i % 2))],
                    sp)
            while engine.has_unfinished_requests():
                engine.step()
        d = core.phases.to_dict()
        forwards = core.steps_dispatched
        idle, seen = d["step_device_idle_at_issue"], d["step_device_seen"]
        assert idle["count"] == seen["count"] == forwards > 40
        # Each busy period's first forward finds nothing in flight.
        assert 2 <= idle["sum"] <= forwards
        fetch = d["step_fetch_seconds"]
        assert fetch["0"]["count"] + fetch["1"]["count"] == forwards
        by_shape = {s: h["count"]
                    for s, h in d["step_device_seconds"].items()}
        assert sum(by_shape.values()) == seen["sum"] <= fetch["0"]["count"]
        # A 70-token prompt under a 64-token budget: one whole chunk.
        assert by_shape["chunk"] <= 2
        # An is_ready() is no transfer.
        assert core.get_stats()["step_host_transfers"]["sum"] \
            == 3 * forwards
        # The timeline keeps no forward's array past its fetch.
        assert core.phases._newest.array is None
    finally:
        engine.engine_core.shutdown()


def test_each_request_is_stamped_once_on_its_way_in(served):
    assert served["front"].admit.count == REQUESTS
    assert served["core"]["input_queue_seconds"]["count"] == REQUESTS
    # Both stamps are on the request's timeline (/debug/requests).
    by_event = {}
    for ts, rid, event, _ in served["events"]:
        by_event.setdefault(event, {})[rid] = ts
    assert set(by_event[ev.RECEIVED]) == set(by_event[ev.SUBMITTED]) \
        == {f"r{i}" for i in range(REQUESTS)}
    assert all(by_event[ev.RECEIVED][r] <= by_event[ev.SUBMITTED][r]
               for r in by_event[ev.RECEIVED])


def test_a_batch_carries_its_step_and_hand_over_on_either_transport(
        served):
    batches, front = served["batches"], served["front"]
    assert len(batches) >= 12
    assert all(kind is core_proc.StepOutputs for kind, *_ in batches)
    steps = [step for _, step, _, _ in batches]
    assert steps[0] >= 1 and steps == sorted(steps)
    assert steps[-1] == served["core"]["step_phase_seconds"][
        "schedule"]["count"]
    assert all(handed <= received for _, _, handed, received in batches)
    # vdt.process_outputs is given the batch's step, and the lag, the
    # API thread's CPU a step and the pending gauge are fed.
    assert served["span_steps"] == steps
    assert front.output_lag.count == front.process_outputs.count \
        == front.step_cpu.count == len(batches)
    assert front.step_cpu.total >= 0
    assert front.batches_posted == front.batches_begun == len(batches)


def test_metrics_carries_every_new_family_and_times_its_rendering(served):
    first, second = served["metrics"]
    for family in ("vdt:step_cpu_seconds_count",
                   'vdt:step_phase_cpu_seconds_count{phase="launch"}',
                   'vdt:step_phase_cpu_seconds_count{phase="other"}',
                   'vdt:step_device_seconds_count{shape="decode"}',
                   'vdt:step_device_seconds_count{shape="chunk"}',
                   "vdt:step_device_seen_count",
                   "vdt:step_device_idle_at_issue_count",
                   'vdt:step_fetch_seconds_count{ready="0"}',
                   'vdt:step_fetch_seconds_count{ready="1"}',
                   'vdt:device_wait_seconds_bucket{worker="dp0-h0",'
                   'le="0.017"}',
                   'vdt:step_kind_seconds_count{kind="mixed",'
                   'part="period"}',
                   'vdt:step_kind_seconds_count{kind="decode",'
                   'part="device_wait"}',
                   f"vdt:input_queue_seconds_count {REQUESTS}",
                   f"vdt:frontend_admit_seconds_count {REQUESTS}",
                   "vdt:frontend_step_cpu_seconds_count",
                   "vdt:frontend_batches_pending 0",
                   "vdt:frontend_output_lag_seconds_count"):
        assert family in first, family
    assert "vdt:frontend_output_lag_seconds_count 0" not in first
    for gone in ("loop", "schedule", "update_states", "device_wait",
                 "finish", "update"):
        assert f'vdt:step_phase_cpu_seconds_count{{phase="{gone}"}}' \
            not in first, gone
    # One observation a scrape, seen by the next.
    assert "vdt:metrics_render_seconds_count 0" in first
    assert "vdt:metrics_render_seconds_count 1" in second


# ---------------------------------------------------------------------
def _request(**kw) -> EngineCoreRequest:
    return EngineCoreRequest(
        request_id="wire", prompt_token_ids=[3, 4, 5],
        sampling_params=SamplingParams(max_tokens=2), **kw)


def test_an_old_peers_request_is_taken_and_not_observed():
    """The stamp is an additive wire key: absent, the request decodes
    with ``None`` and ``_try_add`` observes nothing."""
    added = []
    core = types.SimpleNamespace(phases=StepPhases(),
                                 add_request=added.append)
    old_wire = serial.encode_request(_request())
    assert "submitted_at" not in old_wire
    req = serial.decode_request(serial.unpack(serial.pack(old_wire)))
    assert req.submitted_at is None
    assert core_proc._try_add(core, req) is None
    assert added == [req] and core.phases.input_queue.count == 0
    # A stamped one crosses the wire and is observed once.
    stamp = time.monotonic() - 0.01
    req = serial.decode_request(serial.unpack(serial.pack(
        serial.encode_request(_request(submitted_at=stamp)))))
    assert req.submitted_at == stamp
    assert core_proc._try_add(core, req) is None
    assert core.phases.input_queue.count == 1
    assert 0.01 <= core.phases.input_queue.total < 5.0
    # A replay is stamped by whoever puts it on a transport again.
    assert continuation_request(req, [7]).submitted_at is None


def test_an_old_cores_outputs_message_still_makes_a_batch():
    """``recv_outputs`` of the socket client: ``step`` and
    ``handed_over`` from the message, 0 and the receipt without them."""
    from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
    from vllm_distributed_tpu.engine.core_client import SyncMPClient
    out = serial.encode_output(EngineCoreOutput(req_id="a",
                                                new_token_ids=[1]))
    client = SyncMPClient.__new__(SyncMPClient)
    client._serial, client._live = serial, {"a"}
    msgs = [{"t": "outputs", "outs": [out], "step": 41,
             "handed_over": 12.5},
            {"t": "outputs", "outs": [out]}]
    client._recv = lambda timeout_ms: msgs.pop(0)
    new = client.recv_outputs(10)
    assert (type(new), new.step, new.handed_over, new[0].req_id) == \
        (core_proc.StepOutputs, 41, 12.5, "a")
    before = time.monotonic()
    old = client.recv_outputs(10)
    assert old.step == 0 and before <= old.handed_over <= time.monotonic()


def test_the_capture_starts_and_stops_off_the_event_loops_thread(
        monkeypatch, tmp_path):
    """``profile`` runs ``start_trace`` / ``stop_trace`` on an executor
    thread while the loop keeps turning, and a second start during the
    first is still refused, as 409 over HTTP."""
    import jax
    calls = []

    def slow(name):
        def call(*args, **kwargs):
            calls.append((name, threading.get_ident()))
            time.sleep(0.3)
        return call

    monkeypatch.setattr(jax.profiler, "start_trace", slow("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace", slow("stop"))
    monkeypatch.setenv("VDT_PROFILER_DIR", str(tmp_path))

    async def run():
        engine = AsyncLLM(make_config(False), load_tokenizer=False)
        loop_thread = threading.get_ident()
        ticks = 0

        async def tick():
            nonlocal ticks
            while True:
                await asyncio.sleep(0.01)
                ticks += 1

        ticker = asyncio.ensure_future(tick())
        try:
            fake = _fake_request(engine)
            one, two = await asyncio.gather(api_server.start_profile(fake),
                                            api_server.start_profile(fake))
            during_start = ticks
            stopped = await api_server.stop_profile(fake)
            return (loop_thread, sorted((one.status, two.status)),
                    stopped.status, during_start, ticks)
        finally:
            ticker.cancel()
            engine.shutdown()

    loop_thread, statuses, stopped, during_start, ticks = asyncio.run(run())
    assert statuses == [200, 409] and stopped == 200
    assert [name for name, _ in calls] == ["start", "stop"]
    assert all(ident != loop_thread for _, ident in calls)
    # 0.3 s of start_trace and 0.3 s of stop_trace at a tick every 10 ms.
    assert during_start >= 10 and ticks - during_start >= 10
