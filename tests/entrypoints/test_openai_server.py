"""OpenAI-server HTTP tests over a live aiohttp server + tiny checkpoint
(reference pattern: tests/utils.py:74 RemoteOpenAIServer speaking real
HTTP to a served model)."""

import asyncio
import json
import pathlib
import re
import threading
import time

import httpx
import pytest
import torch
from transformers import LlamaConfig
from transformers import LlamaForCausalLM as HFLlama

from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.utils import get_open_port

VOCAB = 128
# The served engine and its event loop, for the tests that watch the loop.
_SERVER = {}


def _save_checkpoint_with_tokenizer(path) -> HFLlama:
    torch.manual_seed(0)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64, eos_token_id=1)
    hf = HFLlama(cfg).eval()
    hf.save_pretrained(path, safe_serialization=True)

    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast
    # Ids 110-117 carry JSON-ish / choice words so structured-output
    # grammars have something to allow.
    special_words = {"{": 110, "}": 111, '"a"': 112, ":": 113,
                     "true": 114, "false": 115, "yes": 116, "no": 117}
    vocab = {f"w{i}": i for i in range(VOCAB - 2)
             if i not in special_words.values()}
    vocab.update(special_words)
    vocab["<unk>"] = VOCAB - 2
    vocab["</s>"] = 1
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Whitespace()
    fast = PreTrainedTokenizerFast(tokenizer_object=tok,
                                   unk_token="<unk>", eos_token="</s>")
    fast.save_pretrained(path)
    return hf


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tiny_served"))
    hf = _save_checkpoint_with_tokenizer(path)

    engine_args = EngineArgs(model=path, dtype="float32", block_size=4,
                             num_gpu_blocks_override=128, max_model_len=64,
                             max_num_batched_tokens=64, max_num_seqs=8)
    engine = AsyncLLM(engine_args.create_engine_config())
    port = get_open_port()
    ready = threading.Event()
    stop_holder = {}

    def run():
        from vllm_distributed_tpu.entrypoints.openai.api_server import serve
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        stop = asyncio.Event()
        stop_holder["stop"] = stop
        stop_holder["loop"] = loop
        _SERVER.update(loop=loop, engine=engine)
        loop.run_until_complete(serve(engine, path, "127.0.0.1", port,
                                      ready_event=ready, stop_event=stop))
        loop.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert ready.wait(timeout=120), "server did not start"
    yield f"http://127.0.0.1:{port}", hf
    stop_holder["loop"].call_soon_threadsafe(stop_holder["stop"].set)
    t.join(timeout=30)


def hf_greedy(hf, prompt_ids, n):
    with torch.no_grad():
        out = hf.generate(torch.tensor([prompt_ids]), max_new_tokens=n,
                          do_sample=False, eos_token_id=None)
    return out[0].tolist()[len(prompt_ids):]


def test_health_and_models(server):
    base, _ = server
    assert httpx.get(f"{base}/health", timeout=30).status_code == 200
    models = httpx.get(f"{base}/v1/models", timeout=30).json()
    assert models["object"] == "list" and len(models["data"]) == 1


def test_completion_token_parity(server):
    base, hf = server
    prompt = "w3 w17 w92 w45 w8"
    prompt_ids = [3, 17, 92, 45, 8]
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": prompt, "max_tokens": 6,
        "temperature": 0.0, "ignore_eos": True,
    })
    assert r.status_code == 200, r.text
    body = r.json()
    want = hf_greedy(hf, prompt_ids, 6)
    got_text = body["choices"][0]["text"]
    assert got_text.split() == [f"w{t}" for t in want]
    assert body["usage"]["prompt_tokens"] == 5
    assert body["usage"]["completion_tokens"] == 6
    assert body["choices"][0]["finish_reason"] == "length"


def test_completion_streaming_matches_nonstream(server):
    base, _ = server
    req = {"model": "tiny", "prompt": "w9 w8 w7", "max_tokens": 8,
           "temperature": 0.0, "ignore_eos": True}
    full = httpx.post(f"{base}/v1/completions", timeout=300,
                      json=req).json()["choices"][0]["text"]
    chunks = []
    with httpx.stream("POST", f"{base}/v1/completions", timeout=300,
                      json=dict(req, stream=True)) as r:
        assert r.headers["content-type"].startswith("text/event-stream")
        for line in r.iter_lines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            chunks.append(json.loads(payload))
    text = "".join(c["choices"][0]["text"] for c in chunks)
    assert text == full
    assert len(chunks) >= 2, "streaming must deliver incremental chunks"
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"


def test_metrics_expose_latency_histograms(server):
    """After at least one completion, /metrics must expose the TTFT /
    ITL / e2e histograms with real observations (reference:
    v1/metrics/loggers.py:143 histogram families)."""
    base, _ = server
    httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w2 w3", "max_tokens": 4,
        "temperature": 0.0, "ignore_eos": True,
    })
    text = httpx.get(f"{base}/metrics", timeout=30).text
    assert "vdt:time_to_first_token_seconds_bucket" in text
    assert "vdt:inter_token_latency_seconds_bucket" in text
    assert "vdt:e2e_request_latency_seconds_count" in text
    count = [line for line in text.splitlines()
             if line.startswith("vdt:time_to_first_token_seconds_count")]
    assert count and float(count[0].split()[-1]) >= 1
    gen = [line for line in text.splitlines()
           if line.startswith("vdt:generation_tokens_total ")]
    assert gen and float(gen[0].split()[-1]) >= 4


def test_profile_rpc_produces_trace(server, tmp_path, monkeypatch):
    """start/stop profile RPC drives jax.profiler on the core
    (reference: tpu_worker.py:246-256 profile RPC)."""
    import os
    base, _ = server
    r = httpx.post(f"{base}/start_profile", timeout=60)
    assert r.status_code == 200, r.text
    httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w4", "max_tokens": 2,
        "temperature": 0.0, "ignore_eos": True,
    })
    r = httpx.post(f"{base}/stop_profile", timeout=60)
    assert r.status_code == 200, r.text
    trace_dir = r.json()["dir"]
    assert os.path.isdir(trace_dir)
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found += [f for f in files if "trace" in f or f.endswith(".pb")]
    assert found, f"no trace artifacts under {trace_dir}"


def test_json_mode_always_parses(server):
    """Served structured output: response_format json_object makes the
    (random-weight) model emit valid JSON, every time."""
    base, _ = server
    for seed in range(3):
        r = httpx.post(f"{base}/v1/chat/completions", timeout=300, json={
            "model": "tiny",
            "messages": [{"role": "user", "content": "w1 w2"}],
            "max_tokens": 40, "temperature": 1.0, "seed": seed,
            "response_format": {"type": "json_object"},
        })
        assert r.status_code == 200, r.text
        content = r.json()["choices"][0]["message"]["content"]
        parsed = json.loads(content)
        assert isinstance(parsed, dict), content


def test_guided_choice_served(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w3 w4", "max_tokens": 10,
        "temperature": 1.0, "seed": 5, "guided_choice": ["yes", "no"],
    })
    assert r.status_code == 200, r.text
    text = r.json()["choices"][0]["text"].strip()
    assert text in ("yes", "no"), text


def test_completion_n_gt_1(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w5 w6", "n": 2, "max_tokens": 4,
        "temperature": 0.0, "ignore_eos": True,
    }).json()
    assert len(r["choices"]) == 2
    assert [c["index"] for c in r["choices"]] == [0, 1]
    # Greedy: both samples identical.
    assert r["choices"][0]["text"] == r["choices"][1]["text"]


def test_chat_completion(server):
    base, _ = server
    req = {"model": "tiny",
           "messages": [{"role": "user", "content": "w11 w12"}],
           "max_tokens": 4, "temperature": 0.0, "ignore_eos": True}
    r = httpx.post(f"{base}/v1/chat/completions", timeout=300, json=req)
    assert r.status_code == 200, r.text
    body = r.json()
    msg = body["choices"][0]["message"]
    assert msg["role"] == "assistant"
    assert msg["content"]
    # Streaming variant assembles to the same content.
    deltas = []
    with httpx.stream("POST", f"{base}/v1/chat/completions", timeout=300,
                      json=dict(req, stream=True)) as s:
        for line in s.iter_lines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            c = json.loads(payload)["choices"][0]
            deltas.append(c["delta"].get("content", ""))
    assert "".join(deltas) == msg["content"]


def test_validation_errors(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=30, json={
        "model": "tiny", "max_tokens": 4})
    assert r.status_code == 400
    assert r.json()["error"]["type"] == "invalid_request_error"
    r = httpx.post(f"{base}/v1/completions", timeout=30, json={
        "model": "tiny", "prompt": "w1", "temperature": -1.0})
    assert r.status_code == 400


def test_metrics_endpoint(server):
    base, _ = server
    r = httpx.get(f"{base}/metrics", timeout=60)
    assert r.status_code == 200
    assert "vdt:num_requests_running" in r.text
    assert "vdt:prefix_cache_hits_total" in r.text


def test_logit_bias_over_api(server):
    """OpenAI-style logit_bias (string token-id keys) is honored."""
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w3 w17 w92", "max_tokens": 3,
        "temperature": 0.0, "ignore_eos": True,
        "logit_bias": {"77": 100.0},
    })
    assert r.status_code == 200, r.text
    assert r.json()["choices"][0]["text"].split() == ["w77"] * 3


def test_logprobs_over_api(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w3 w17 w92 w45", "max_tokens": 3,
        "temperature": 0.0, "ignore_eos": True, "logprobs": 4,
    })
    assert r.status_code == 200, r.text
    lp = r.json()["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 3
    assert all(len(d) >= 4 for d in lp["top_logprobs"])


def test_embeddings_endpoint(server):
    """/v1/embeddings over the pooling path (reference:
    serving_embedding.py)."""
    base, _ = server
    r = httpx.post(f"{base}/v1/embeddings", timeout=300, json={
        "model": "tiny", "input": ["w1 w2 w3", "w4 w5"],
    })
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "list"
    assert len(body["data"]) == 2
    assert all(len(d["embedding"]) == 64 for d in body["data"])
    assert body["usage"]["prompt_tokens"] == 5


def test_tool_calls_forced_function(server):
    """Forced tool choice rides structured output: arguments ALWAYS
    parse against the function schema (reference: serving_chat tool
    handling + tool parsers)."""
    base, _ = server
    r = httpx.post(f"{base}/v1/chat/completions", timeout=300, json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "w1 w2"}],
        "max_tokens": 30, "temperature": 1.0, "seed": 11,
        "tools": [{"type": "function", "function": {
            "name": "set_flag",
            "parameters": {"type": "object",
                           "properties": {"a": {"type": "boolean"}},
                           "required": ["a"]}}}],
        "tool_choice": {"type": "function",
                        "function": {"name": "set_flag"}},
    })
    assert r.status_code == 200, r.text
    choice = r.json()["choices"][0]
    assert choice["finish_reason"] == "tool_calls"
    (call, ) = choice["message"]["tool_calls"]
    assert call["function"]["name"] == "set_flag"
    args = json.loads(call["function"]["arguments"])
    assert isinstance(args.get("a"), bool)


def test_completion_echo_with_logprobs(server):
    """echo=true returns prompt + completion text and leads the
    logprobs arrays with the scored prompt positions (first None)."""
    base, hf = server
    prompt = "w3 w17 w92 w45 w8"
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": prompt, "max_tokens": 3,
        "temperature": 0.0, "ignore_eos": True, "echo": True,
        "logprobs": 3,
    })
    assert r.status_code == 200, r.text
    choice = r.json()["choices"][0]
    assert choice["text"].startswith(prompt)
    lp = choice["logprobs"]
    # 5 prompt tokens + 3 completion tokens; first prompt entry None.
    assert len(lp["tokens"]) == 8
    assert lp["token_logprobs"][0] is None
    assert all(isinstance(v, float) for v in lp["token_logprobs"][1:])
    import torch as _torch
    ids = [3, 17, 92, 45, 8]
    with _torch.no_grad():
        ref = _torch.log_softmax(
            hf(_torch.tensor([ids])).logits[0].float(), -1).numpy()
    for i in range(1, 5):
        assert abs(lp["token_logprobs"][i] - float(ref[i - 1, ids[i]])) \
            < 1e-3


def test_completion_echo_stream_rejected(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/completions", timeout=300, json={
        "model": "tiny", "prompt": "w1 w2", "max_tokens": 2,
        "stream": True, "echo": True,
    })
    assert r.status_code == 400


def test_tokenize_detokenize_roundtrip(server):
    base, _ = server
    r = httpx.post(f"{base}/tokenize", timeout=60,
                   json={"prompt": "w3 w17 w92"})
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["tokens"] == [3, 17, 92] and body["count"] == 3
    r2 = httpx.post(f"{base}/detokenize", timeout=60,
                    json={"tokens": body["tokens"]})
    assert r2.status_code == 200
    assert r2.json()["prompt"].split() == ["w3", "w17", "w92"]


def test_responses_api_minimal(server):
    """/v1/responses wraps a chat completion in the Responses item
    shape (reference: serving_responses.py)."""
    base, _ = server
    r = httpx.post(f"{base}/v1/responses", timeout=300, json={
        "input": "w3 w17 w92", "max_output_tokens": 4,
        "temperature": 0.0, "ignore_eos": True,
    })
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "response"
    assert body["status"] == "completed"
    item = body["output"][0]
    assert item["role"] == "assistant"
    text = item["content"][0]["text"]
    assert text and body["output_text"] == text
    assert body["usage"]["output_tokens"] == 4
    # background mode refuses honestly
    r2 = httpx.post(f"{base}/v1/responses", timeout=60, json={
        "input": "w3", "background": True})
    assert r2.status_code == 400


def test_responses_typed_input_and_stream_rejection(server):
    base, _ = server
    r = httpx.post(f"{base}/v1/responses", timeout=300, json={
        "input": [{"role": "user", "content": [
            {"type": "input_text", "text": "w3 w17"}]}],
        "max_output_tokens": 3, "temperature": 0.0, "ignore_eos": True,
    })
    assert r.status_code == 200, r.text
    assert r.json()["output_text"]
    r2 = httpx.post(f"{base}/v1/responses", timeout=60, json={
        "input": "w3", "stream": True})
    assert r2.status_code == 400


def test_detokenize_rejects_string_tokens(server):
    base, _ = server
    r = httpx.post(f"{base}/detokenize", timeout=60,
                   json={"tokens": "123"})
    assert r.status_code == 400


def test_tokenize_messages_path(server):
    base, _ = server
    r = httpx.post(f"{base}/tokenize", timeout=60, json={
        "messages": [{"role": "user", "content": "w3 w17"}]})
    assert r.status_code == 200, r.text
    body = r.json()
    # Template-less fallback: role-prefixed prompt, same path chat
    # generation uses; the real ids 3 and 17 appear in the encoding.
    assert body["count"] == len(body["tokens"]) > 0


def test_spec_stats_render_in_metrics():
    """Spec-decode counters surface in the Prometheus text (reference:
    the vllm:spec_decode_* family of v1/metrics)."""
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    text = render_metrics({
        "spec_num_draft_tokens": 30,
        "spec_num_accepted_tokens": 21,
        "spec_num_drafts": 10,
        "spec_acceptance_rate": 0.7,
    })
    assert "vdt:spec_decode_num_draft_tokens_total 30.0" in text
    assert "vdt:spec_decode_num_accepted_tokens_total 21.0" in text
    assert "vdt:spec_decode_acceptance_rate 0.7" in text


# ---------------------------------------------------------------------------
# The per-token path (PR 49): bytes on the wire, the liveness guard, no
# Task a token.
def _sse_bytes(base: str, path: str, body: dict) -> bytes:
    """A streamed response's raw bytes, ``id``, ``created`` and
    ``model`` (random, wall-clock, a temporary path) normalised, chunk
    boundaries as the server wrote them."""
    with httpx.stream("POST", f"{base}{path}", timeout=300,
                      json=dict(body, stream=True)) as r:
        assert r.status_code == 200
        raw = b"".join(r.iter_raw())
    raw = re.sub(rb'"id": "[^"]+"', b'"id": "ID"', raw)
    raw = re.sub(rb'"model": "[^"]+"', b'"model": "M"', raw)
    return re.sub(rb'"created": \d+', b'"created": 0', raw)


_GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
_GOLDEN_REQUESTS = {
    "stream_completion_greedy": ("/v1/completions", {
        "model": "tiny", "prompt": "w9 w8 w7", "max_tokens": 8,
        "temperature": 0.0, "ignore_eos": True}),
    "stream_completion_seeded": ("/v1/completions", {
        "model": "tiny", "prompt": "w3 w17 w92 w45 w8", "max_tokens": 12,
        "temperature": 0.8, "seed": 1234, "ignore_eos": True}),
    "stream_completion_stop": ("/v1/completions", {
        "model": "tiny", "prompt": "w9 w8 w7", "max_tokens": 8,
        "temperature": 0.0, "ignore_eos": True, "stop": ["w63 w80"]}),
    "stream_chat_greedy": ("/v1/chat/completions", {
        "model": "tiny",
        "messages": [{"role": "user", "content": "w11 w12"}],
        "max_tokens": 6, "temperature": 0.0, "ignore_eos": True}),
    "stream_chat_seeded": ("/v1/chat/completions", {
        "model": "tiny",
        "messages": [{"role": "user", "content": "w5 w6 w7"}],
        "max_tokens": 10, "temperature": 0.8, "seed": 99,
        "ignore_eos": True}),
}


@pytest.mark.parametrize("name", sorted(_GOLDEN_REQUESTS))
def test_streamed_bytes_equal_parent_golden(server, name):
    """The SSE bytes of seeded streams, chunk for chunk, equal what the
    commit before the delta path (PR 48) wrote for the same requests
    (tests/entrypoints/golden/, taken there with this function)."""
    base, _ = server
    path, body = _GOLDEN_REQUESTS[name]
    got = _sse_bytes(base, path, body)
    assert got == (_GOLDEN_DIR / f"{name}.txt").read_bytes()


def _tasks_created_while(fn) -> int:
    """asyncio Tasks the server's event loop creates while ``fn`` runs
    (here, on the test's thread), counted through a task factory."""
    loop = _SERVER["loop"]
    created = []

    def factory(loop, coro, **kwargs):
        created.append(coro)
        return asyncio.Task(coro, loop=loop, **kwargs)

    loop.call_soon_threadsafe(loop.set_task_factory, factory)
    try:
        fn()
    finally:
        loop.call_soon_threadsafe(loop.set_task_factory, None)
    return len(created)


def test_stream_creates_no_task_a_token(server):
    """Four child streams of 50 tokens against four of 5: the loop's
    Tasks are the connection's, the handler's and the four pumps', the
    same for 200 tokens as for 20 (the parent made one more a token)."""
    base, _ = server
    body = {"model": "tiny", "prompt": "w9 w8 w7", "n": 4,
            "temperature": 0.0, "ignore_eos": True}
    counts = {}
    for max_tokens in (5, 50, 5):
        def stream():
            raw = _sse_bytes(base, "/v1/completions",
                             dict(body, max_tokens=max_tokens))
            assert raw.count(b"data: ") == 4 * max_tokens + 1
        counts.setdefault(max_tokens, []).append(
            _tasks_created_while(stream))
    assert min(counts[5]) >= 4
    assert counts[50][0] <= max(counts[5]) + 2, counts
    # All of it went out as deltas over the one-decode path.
    stats = _SERVER["engine"].output_processor.stats
    assert stats.request_outputs["delta"] >= 240
    assert stats.detokenized_tokens["window"] == 0


class _FakeTransport:
    closing = False

    def is_closing(self):
        return self.closing


class _FakeRequest(dict):
    """What the stream guard reads of a web.Request."""

    def __init__(self):
        super().__init__()
        self.transport = _FakeTransport()


@pytest.mark.parametrize("cause", ["disconnect", "deadline", "cancel",
                                   "alive"])
def test_stream_guard_without_any_output(cause):
    """An engine that yields nothing for 1.5 s: the guard still sees a
    client gone or a deadline passed within about a second, cancels the
    waiting handler (whose generate() unwinds into its abort path) and
    raises what it found; someone else's cancellation stays one; a
    stream that is alive is left alone and its timer taken down."""
    from vllm_distributed_tpu.entrypoints.openai import api_server

    async def main():
        request = _FakeRequest()
        unwound = []

        async def silent_engine():
            try:
                await asyncio.sleep(1.5)
                yield "late"
            finally:
                unwound.append(time.monotonic())

        async def pump():
            return [out async for out in silent_engine()]

        async def handler():
            with api_server._stream_guard(request):
                return await asyncio.gather(pump(), pump())

        loop = asyncio.get_running_loop()
        t0 = time.monotonic()
        task = asyncio.ensure_future(handler())
        if cause == "disconnect":
            loop.call_later(0.2, setattr, request.transport, "closing",
                            True)
            want, within = ConnectionResetError, 1.4
        elif cause == "deadline":
            request[api_server.DEADLINE_AT_KEY] = t0 + 0.3
            want, within = asyncio.TimeoutError, 0.6
        elif cause == "cancel":
            loop.call_later(0.2, task.cancel)
            want, within = asyncio.CancelledError, 0.4
        else:
            assert await task == [["late"], ["late"]]
            await asyncio.sleep(1.1)  # a timer left armed would fire
            return
        with pytest.raises(want):
            await task
        took = time.monotonic() - t0
        assert took < within, took
        assert len(unwound) == 2 and max(unwound) - t0 < within

    asyncio.run(main())
