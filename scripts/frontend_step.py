#!/usr/bin/env python3
"""What one step costs the API thread, per request-token, without a chip.

    python3 scripts/frontend_step.py [--streams 48] [--steps 500]
        [--vocab 100352] [--reps 3] [--tree DIR]

One decode step of N streaming requests hands the front end N
``EngineCoreOutput``s of one token each. This drives what the API
(event loop) thread then does, on this machine's CPU and with no engine
behind it: ``OutputProcessor.process_outputs`` over the N outputs, the
puts on the N request queues (as ``AsyncLLM._process_batch`` makes them),
N consumers shaped like ``AsyncLLM.generate``'s loop (``await
queue.get()``), and the server's own ``_stream_completions`` for each of
the N responses, one handler Task a stream as aiohttp runs them. The
tokenizer is the benchmark's: word-level, ``--vocab`` entries, behind
``PreTrainedTokenizerFast`` (``benchmarks/run.py`` ``write_model_dir``).

``--tree DIR`` imports ``vllm_distributed_tpu`` from another checkout
(say a ``git archive`` of the parent commit) in place of this one, so two
commits can be priced by two runs; the script reads only what both have
(a tree without output kinds gets the cumulative outputs it knows).

Rows, in microseconds a request-token (the least over ``--reps`` of the
mean over ``--steps`` steps; ``time.perf_counter`` on one thread with
nothing else running, so wall is CPU):

- ``process_outputs``: the call alone, for each output kind the tree has;
- ``queues + handlers, null writer``: the rest of the step with the
  response's payload writer replaced by one that drops the bytes: the
  queue hops, the pump, the chunk's JSON, ``StreamResponse.write``;
- ``queues + handlers, aiohttp writer``: the same through aiohttp's own
  ``StreamWriter`` (chunked encoding) over a transport that drops the
  bytes; the difference to the row above is aiohttp's write. The socket
  call itself is in neither.

The chip's host runs this Python 4-5 times slower (PERF.md, Findings
PR 49). Nothing a cell runs imports this.
"""

import argparse
import asyncio
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NullTransport(asyncio.Transport):
    """Drops what is written; never closing."""

    def write(self, data) -> None:
        pass

    def is_closing(self) -> bool:
        return False


class NullProtocol:
    """What aiohttp's StreamWriter asks of its protocol."""

    def __init__(self) -> None:
        self.transport = NullTransport()
        self.writing_paused = self._paused = False
        self.ssl_context = self.peername = None

    async def _drain_helper(self) -> None:
        pass


class NullWriter:
    """A payload writer that drops the bytes (aiohttp's
    AbstractStreamWriter, as far as a StreamResponse calls it)."""

    buffer_size = output_size = 0
    length = None

    async def write(self, chunk) -> None:
        pass

    async def write_eof(self, chunk: bytes = b"") -> None:
        pass

    async def drain(self) -> None:
        pass

    def enable_compression(self, *args, **kwargs) -> None:
        pass

    def enable_chunking(self) -> None:
        pass

    async def write_headers(self, status_line, headers) -> None:
        pass

    def send_headers(self) -> None:
        pass


def tokenizer_of(vocab: int):
    from transformers import AutoTokenizer

    from benchmarks import run
    run.WORK = tempfile.mkdtemp(prefix="frontend_step_")
    path = run.write_model_dir("words", {"vocab_size": vocab})
    return AutoTokenizer.from_pretrained(path, local_files_only=True)


def make_processor(tokenizer):
    from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                             ModelConfig, SchedulerConfig)
    from vllm_distributed_tpu.engine.output_processor import OutputProcessor
    config = EngineConfig(
        model_config=ModelConfig(model="dummy", max_model_len=4096),
        cache_config=CacheConfig(block_size=16, num_gpu_blocks=64),
        scheduler_config=SchedulerConfig(max_model_len=4096))
    return OutputProcessor(config, tokenizer)


def output_kinds() -> dict:
    """label -> add_request's keyword arguments, for the kinds this tree
    has."""
    try:
        from vllm_distributed_tpu.outputs import RequestOutputKind
    except ImportError:
        return {"cumulative": {}}
    return {kind.value: {"output_kind": kind} for kind in RequestOutputKind}


def add_requests(proc, streams: int, steps: int, kind: dict) -> list[str]:
    from vllm_distributed_tpu.request import EngineCoreRequest
    from vllm_distributed_tpu.sampling_params import SamplingParams
    ids = [f"cmpl-{i}-0" for i in range(streams)]
    for rid in ids:
        proc.add_request(EngineCoreRequest(
            request_id=rid, prompt_token_ids=[1, 2, 3],
            sampling_params=SamplingParams(max_tokens=steps)), **kind)
    return ids


def step_outputs(ids: list[str], step: int, steps: int, vocab: int):
    from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
    finish = "length" if step == steps - 1 else None
    return [EngineCoreOutput(rid, [(step * 7919 + i * 104729) % vocab],
                             finish_reason=finish)
            for i, rid in enumerate(ids)]


def time_process_outputs(tokenizer, args, kind: dict) -> float:
    proc = make_processor(tokenizer)
    ids = add_requests(proc, args.streams, args.steps, kind)
    batches = [step_outputs(ids, s, args.steps, args.vocab)
               for s in range(args.steps)]
    t0 = time.perf_counter()
    for batch in batches:
        proc.process_outputs(batch)
    return time.perf_counter() - t0


async def time_step(tokenizer, args, writer: str) -> tuple[float, float]:
    """(seconds in process_outputs, seconds in all the rest) of
    ``--steps`` steps through the server's streaming handler."""
    from aiohttp.http_writer import StreamWriter
    from aiohttp.test_utils import make_mocked_request

    from vllm_distributed_tpu.entrypoints.openai import api_server
    loop = asyncio.get_running_loop()
    proc = make_processor(tokenizer)
    kinds = output_kinds()
    ids = add_requests(proc, args.streams, args.steps,
                       kinds.get("delta", kinds["cumulative"]))
    queues = {rid: asyncio.Queue() for rid in ids}

    async def generate(rid):
        while True:
            item = await queues[rid].get()
            yield item
            if item.finished:
                return

    handlers = []
    for i, rid in enumerate(ids):
        protocol = NullProtocol()
        request = make_mocked_request(
            "POST", "/v1/completions", protocol=protocol,
            transport=protocol.transport,
            writer=(NullWriter() if writer == "null"
                    else StreamWriter(protocol, loop)))
        handlers.append(asyncio.ensure_future(
            api_server._stream_completions(
                request, f"cmpl-{i}", 0, "model", [(0, generate(rid))])))
    await asyncio.sleep(0.05)  # every response prepared, every pump waits
    batches = [step_outputs(ids, s, args.steps, args.vocab)
               for s in range(args.steps)]
    in_process = 0.0
    t0 = time.perf_counter()
    for batch in batches:
        t = time.perf_counter()
        processed = proc.process_outputs(batch)
        in_process += time.perf_counter() - t
        for ro in processed.request_outputs:
            queues[ro.request_id].put_nowait(ro)
        # Let what the step woke run dry, as the loop does between two
        # batches: a handler resumes at most a few callbacks deep.
        for _ in range(4):
            await asyncio.sleep(0)
    await asyncio.gather(*handlers)
    total = time.perf_counter() - t0
    return in_process, total - in_process


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=48)
    ap.add_argument("--steps", type=int, default=500)
    ap.add_argument("--vocab", type=int, default=100352)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tree", default=REPO,
                    help="checkout to import vllm_distributed_tpu from")
    args = ap.parse_args()
    sys.path.insert(0, REPO)  # benchmarks.run (the tokenizer's recipe)
    sys.path.insert(0, os.path.abspath(args.tree))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import vllm_distributed_tpu
    tokenizer = tokenizer_of(args.vocab)
    per = 1e6 / (args.streams * args.steps)
    rows = []
    for label, kind in output_kinds().items():
        best = min(time_process_outputs(tokenizer, args, kind)
                   for _ in range(args.reps))
        rows.append((f"process_outputs, {label}", best * per))
    for writer in ("null", "aiohttp"):
        runs = [asyncio.run(time_step(tokenizer, args, writer))
                for _ in range(args.reps)]
        rows.append((f"queues + handlers, {writer} writer",
                     min(rest for _, rest in runs) * per))
        rows.append(("  process_outputs inside that run",
                     min(inside for inside, _ in runs) * per))
    print(f"# tree {os.path.dirname(vllm_distributed_tpu.__path__[0])}")
    print(f"# {args.streams} streams x {args.steps} steps, vocabulary "
          f"{args.vocab}, least of {args.reps}; us a request-token")
    for name, us in rows:
        print(f"{name:<44} {us:8.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
