"""Debug introspection: GET /debug/requests and /debug/engine serve
live JSON during an in-flight request and are exempt from the admission
gate; the SIGUSR1 dump logs the same state plus thread stacks without
disturbing serving."""

import asyncio
import json
import logging
import threading

import httpx
import pytest
import torch
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

from vllm_distributed_tpu.utils import get_open_port

VOCAB = 128


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    import os

    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM

    path = str(tmp_path_factory.mktemp("tiny_debug"))
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512, eos_token_id=1)
    HFLlama(cfg).eval().save_pretrained(path, safe_serialization=True)

    saved = {k: os.environ.get(k) for k in
             ("VDT_ADMISSION_HIGH_WATERMARK",
              "VDT_ADMISSION_LOW_WATERMARK")}
    # Watermark 1: one in-flight generation fills the gate, so the
    # exemption of the GET /debug routes is directly observable.
    os.environ["VDT_ADMISSION_HIGH_WATERMARK"] = "1"
    os.environ["VDT_ADMISSION_LOW_WATERMARK"] = "1"

    engine = AsyncLLM(EngineArgs(
        model=path, dtype="float32", block_size=4,
        num_gpu_blocks_override=256, max_model_len=512,
        max_num_batched_tokens=512, max_num_seqs=8,
        skip_tokenizer_init=True).create_engine_config(),
        load_tokenizer=False)
    port = get_open_port()
    ready = threading.Event()
    stop_holder = {}

    def run():
        from vllm_distributed_tpu.entrypoints.openai.api_server import \
            serve
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        stop = asyncio.Event()
        stop_holder["stop"] = stop
        stop_holder["loop"] = loop
        loop.run_until_complete(serve(engine, path, "127.0.0.1", port,
                                      ready_event=ready,
                                      stop_event=stop))
        loop.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert ready.wait(timeout=120), "server did not start"
    yield f"http://127.0.0.1:{port}", engine
    stop_holder["loop"].call_soon_threadsafe(stop_holder["stop"].set)
    t.join(timeout=30)
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


BODY = {"model": "tiny", "prompt": [3, 17, 92], "max_tokens": 4,
        "temperature": 0.0, "ignore_eos": True}


class _InflightStream:
    """Holds one long streaming completion open (first chunk consumed,
    the rest drained on close) so the admission slot stays occupied
    while the test pokes the debug endpoints."""

    def __init__(self, url: str):
        self.url = url
        self.started = threading.Event()
        self.finished = threading.Event()
        self._thread = threading.Thread(target=self._consume, daemon=True)

    def _consume(self):
        body = dict(BODY, max_tokens=400, stream=True)
        try:
            with httpx.stream("POST", f"{self.url}/v1/completions",
                              json=body, timeout=300) as r:
                # Headers arrive once the stream response is prepared
                # (admission slot held, generation submitted); the
                # token-less tiny server writes no delta chunks until
                # finish, so first-line would mean "already done".
                assert r.status_code == 200, r.status_code
                self.started.set()
                for _line in r.iter_lines():
                    pass
        finally:
            self.started.set()
            self.finished.set()

    def __enter__(self):
        self._thread.start()
        assert self.started.wait(timeout=120), "stream never started"
        return self

    def __exit__(self, *exc):
        self.finished.wait(timeout=300)
        self._thread.join(timeout=30)


def test_debug_endpoints_live_json_and_admission_exempt(server):
    url, _engine = server
    with _InflightStream(url) as stream:
        assert not stream.finished.is_set()
        # The single admission slot is held: generation is shed...
        shed = httpx.post(f"{url}/v1/completions", timeout=60, json=BODY)
        assert shed.status_code == 429, shed.text
        # ...while the GET debug routes stay exempt and serve live
        # JSON. (Headers land just before the generate submission;
        # poll briefly for the request to appear.)
        import time as _time
        for _ in range(100):
            dr = httpx.get(f"{url}/debug/requests", timeout=60)
            assert dr.status_code == 200, dr.text
            data = dr.json()
            if data["num_requests"] >= 1:
                break
            _time.sleep(0.1)
        de = httpx.get(f"{url}/debug/engine", timeout=60)
        assert data["num_requests"] >= 1
        req = next(r for r in data["requests"]
                   if r.get("phase") is not None)
        assert req["phase"] in ("queued", "prefill", "decode",
                                "preempted", "kv_pull")
        assert req["prompt_tokens"] == 3
        assert isinstance(req["phase_age_s"], dict)
        # Core-side enrichment: scheduler status + progress counters.
        assert req.get("status") in ("WAITING", "RUNNING", "PREEMPTED",
                                     "WAITING_FOR_REMOTE_KVS", None)
        assert de.status_code == 200, de.text
        eng = de.json()
        assert eng["supervisor"]["core"] == "BackgroundEngineCore"
        assert eng["supervisor"]["errored"] is False
        assert eng["admission"]["enabled"] is True
        assert eng["admission"]["depth"] >= 1
        assert eng["admission"]["high_watermark"] == 1
        assert len(eng["engine_cores"]) == 1
        sched = eng["engine_cores"][0]["scheduler"]
        assert sched["num_running"] + sched["num_waiting"] >= 1
        assert "requests" not in sched  # summary endpoint stays lean
    # Gate released: generation serves again.
    ok = httpx.post(f"{url}/v1/completions", timeout=300, json=BODY)
    assert ok.status_code == 200, ok.text


def test_debug_kv_cache_live_mid_request(server):
    """GET /debug/kv_cache serves live block-pool state while a
    request is in flight (and, like the other debug GETs, bypasses the
    admission gate — the stream below holds the single slot)."""
    import time as _time
    url, _engine = server
    with _InflightStream(url):
        data = {}
        for _ in range(100):
            r = httpx.get(f"{url}/debug/kv_cache", timeout=60)
            assert r.status_code == 200, r.text
            data = r.json()
            cores = data.get("engine_cores") or []
            if cores and any(req.get("kv_blocks")
                             for req in cores[0]["requests"]):
                break
            _time.sleep(0.1)
        assert cores, data
        kv = cores[0]["kv_cache"]
        assert kv["total_blocks"] > 0
        assert kv["free_blocks"] + kv["used_blocks"] == \
            kv["total_blocks"]
        assert kv["used_blocks"] >= 1  # the in-flight request's pages
        assert 0.0 <= kv["fragmentation_frac"] <= 1.0
        assert 0.0 <= kv["window_hit_rate"] <= 1.0
        assert isinstance(kv["preemption_causes"], dict)
        req = next(r for r in cores[0]["requests"]
                   if r.get("kv_blocks"))
        assert req["kv_blocks"] >= 1
        assert req["status"] in ("WAITING", "RUNNING", "PREEMPTED",
                                 "WAITING_FOR_REMOTE_KVS")


def test_debug_endpoints_idle_shapes(server):
    url, _engine = server
    data = httpx.get(f"{url}/debug/requests", timeout=60).json()
    assert "requests" in data and "num_requests" in data
    eng = httpx.get(f"{url}/debug/engine", timeout=60).json()
    assert "recent_events" in eng
    # The lifecycle ledger saw earlier arrivals/finishes.
    names = {e[2] for e in eng["recent_events"]}
    assert names & {"arrived", "finished", "aborted", "shed"}


def test_debug_trace_404_when_plane_disabled(server):
    # This server runs with VDT_TRACE_PLANE unset (the default): the
    # endpoint must refuse with a hint, not serve an empty trace.
    url, _engine = server
    r = httpx.get(f"{url}/debug/trace", timeout=60)
    assert r.status_code == 404
    assert "VDT_TRACE_PLANE" in r.json()["error"]


def test_debug_perf_is_gone(server):
    """The attribution table went with the analytic cost model (PR 46):
    utilisation is the benchmark's traced runs' to measure."""
    url, _engine = server
    assert httpx.get(f"{url}/debug/perf", timeout=60).status_code == 404


def test_stop_profile_answers_the_trace_dirs_only(server, tmp_path,
                                                  monkeypatch):
    import jax
    url, _engine = server
    monkeypatch.setenv("VDT_PROFILER_DIR", str(tmp_path))
    # The core is a thread of this process: the capture itself is held
    # by tests/entrypoints/test_openai_server.py, the answer's keys here.
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda *a, **k: None)
    started = httpx.post(f"{url}/start_profile", timeout=60)
    assert started.status_code == 200, started.text
    stopped = httpx.post(f"{url}/stop_profile", timeout=60)
    assert stopped.status_code == 200, stopped.text
    body = stopped.json()
    assert set(body) == {"status", "dir", "dirs"}
    assert body["dirs"] == [body["dir"]] == started.json()["dirs"]


def test_metrics_name_no_attribution_family(server):
    url, _engine = server
    ok = httpx.post(f"{url}/v1/completions", timeout=300, json=BODY)
    assert ok.status_code == 200, ok.text
    text = httpx.get(f"{url}/metrics", timeout=60).text
    assert "vdt:generation_tokens_total" in text
    for family in ("vdt:mfu", "vdt:mbu", "vdt:hbm_bytes_total",
                   "vdt:roofline_bound", "vdt:model_flops_total"):
        assert family not in text, family


def test_sigusr1_dump_logs_without_disturbing_serving(server):
    """The SIGUSR1 path (exercised directly — the test server's loop
    runs off the main thread, where signal handlers cannot register)
    logs the /debug state and every thread's stack, and serving
    continues untouched."""
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        _dump_debug_to_log, _thread_stacks, build_app)
    url, engine = server

    stacks = _thread_stacks()
    assert "--- thread" in stacks and "MainThread" in stacks

    app = build_app(engine, "tiny")
    records: list[logging.LogRecord] = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    root = logging.getLogger("vllm_distributed_tpu")
    handler = _Capture()
    root.addHandler(handler)
    try:
        asyncio.run(_dump_debug_to_log(app))
    finally:
        root.removeHandler(handler)
    dump = [r for r in records if "SIGUSR1 debug dump" in r.getMessage()]
    assert len(dump) == 1
    message = dump[0].getMessage()
    assert "/debug/engine" in message and "thread stacks" in message
    # The KV summary rides the same dump.
    assert "/debug/kv_cache" in message
    kv_payload = message.split("/debug/kv_cache: ", 1)[1].split(
        "\nthread stacks", 1)[0]
    assert "engine_cores" in json.loads(kv_payload)
    # The dumped engine state is valid JSON with supervisor detail.
    payload = message.split("/debug/engine: ", 1)[1].split(
        "\n/debug/requests:", 1)[0]
    assert json.loads(payload)["supervisor"]["core"] == \
        "BackgroundEngineCore"
    # Serving is undisturbed.
    ok = httpx.post(f"{url}/v1/completions", timeout=300, json=BODY)
    assert ok.status_code == 200, ok.text
