"""Engine core as a subprocess: ZMQ transport + busy loop.

Reference: vllm/v1/engine/core.py:362 (``EngineCoreProc``: run_busy_loop
:598, _process_input_queue :608, _send_engine_dead :679). The TPU variant
keeps the same actor shape — requests in over one socket, outputs out over
another, a ready handshake, and a dead sentinel — with msgpack instead of
msgspec and a single-threaded poll loop (the GIL-heavy input/output
threads of the reference buy nothing under an in-process XLA dispatch).
"""

import queue
import signal
import threading
import time
import traceback
from typing import Optional

import zmq

from vllm_distributed_tpu.engine import serial
from vllm_distributed_tpu.engine.core import EngineCore
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)

# Poll interval while idle (ms); while busy the input queue is drained
# without blocking between steps.
_IDLE_POLL_MS = 100


def run_engine_core(config, input_addr: str, output_addr: str) -> None:
    """Subprocess entry: build the core, handshake, busy-loop until a
    shutdown message (or parent death) arrives."""
    # Die cleanly with the parent instead of leaking the device.
    signal.signal(signal.SIGTERM, lambda *_: _raise_shutdown())

    ctx = zmq.Context()
    inp = ctx.socket(zmq.PULL)
    inp.connect(input_addr)
    out = ctx.socket(zmq.PUSH)
    out.connect(output_addr)

    core = None
    hb_stop = threading.Event()
    try:
        core = EngineCore(config)
        out.send(serial.pack({
            "t": "ready",
            "num_pages": config.cache_config.num_gpu_blocks,
        }))
        # Liveness heartbeat on its own thread + its own PUSH socket
        # (zmq sockets are not thread-safe; multiple PUSH sockets may
        # connect to one PULL endpoint). It keeps beating through long
        # compiles — XLA releases the GIL — so the client's staleness
        # window only fires when the whole process is wedged or dead.
        interval = config.fault_tolerance_config.heartbeat_interval_s
        if interval > 0:
            threading.Thread(target=_heartbeat_loop,
                             args=(ctx, output_addr, interval, hb_stop),
                             name="engine-core-heartbeat",
                             daemon=True).start()
        _busy_loop(core, inp, out)
    except _Shutdown:
        pass
    except Exception as e:  # noqa: BLE001 - report then die
        logger.error("engine core died: %s", e)
        traceback.print_exc()
        try:
            out.send(serial.pack({
                "t": "dead",
                "error": f"{type(e).__name__}: {e}",
            }))
            time.sleep(0.2)  # let the sentinel flush
        except Exception:
            pass
    finally:
        hb_stop.set()
        if core is not None:
            core.shutdown()
        inp.close(linger=0)
        out.close(linger=0)
        ctx.term()


def _heartbeat_loop(ctx: zmq.Context, output_addr: str, interval: float,
                    stop: threading.Event) -> None:
    """Liveness beats to the client (reference analogue: the reference
    core's EngineCoreProc monitor thread / process liveness checks)."""
    sock = ctx.socket(zmq.PUSH)
    # Bounded send: a PUSH with no live peer blocks forever by default,
    # which would wedge this thread (and ctx.term) after parent death.
    sock.setsockopt(zmq.SNDTIMEO, 1000)
    sock.connect(output_addr)
    try:
        while not stop.wait(interval):
            if fault_injection.should_fire("heartbeat.stall"):
                continue  # injected stall: skip this beat
            try:
                sock.send(serial.pack(  # wallclock-ok: informational beat ts
                    {"t": "hb", "ts": time.time()}))
            except zmq.Again:
                # Transient: the client hasn't drained in a while (idle
                # sync user) and the HWM is full. Keep beating — exiting
                # here would later declare a HEALTHY core dead on its
                # first legitimate long stall.
                continue
            except zmq.ZMQError:
                return  # terminal (ctx terminated / socket closed)
    finally:
        try:
            sock.close(linger=0)
        except Exception:  # noqa: BLE001 - teardown race with ctx.term
            pass


def _try_add(core: EngineCore, req):
    """Add a request; a rejectable failure (e.g. a grammar the
    front-end validator missed) must not take the busy loop down — it
    bounces back as an aborted output so the client unblocks. Returns
    the synthetic output, or None on success. This is where the engine
    thread takes a request off the transport: the wait since the front
    end put it there (one ``time.monotonic()`` for all processes of a
    host) is observed, unless an old peer sent no stamp."""
    if req.submitted_at is not None:
        core.phases.input_queue.observe(
            time.monotonic() - req.submitted_at)
    try:
        core.add_request(req)
        return None
    except Exception as e:  # noqa: BLE001 - any admission failure is
        # rejectable (grammar compile, tokenizer load, bad params);
        # request state hasn't entered the scheduler yet, so bouncing is
        # always safe and beats killing every in-flight request.
        logger.warning("rejected request %s: %s", req.request_id, e)
        from vllm_distributed_tpu.core.sched.scheduler import \
            EngineCoreOutput
        return EngineCoreOutput(req_id=req.request_id, new_token_ids=[],
                                finish_reason="abort")


class _Shutdown(Exception):
    pass


def _raise_shutdown() -> None:
    raise _Shutdown()


def _send_outputs(core: EngineCore, out: zmq.Socket, outputs) -> None:
    """One step's outputs with what ``StepOutputs`` carries in process:
    the step's number and ``time.monotonic()`` of the hand-over."""
    out.send(serial.pack({
        "t": "outputs",
        "outs": [serial.encode_output(o) for o in outputs],
        "step": core.phases.step,
        "handed_over": time.monotonic(),
    }))


def _handle_msg(core: EngineCore, out: zmq.Socket, msg: dict) -> None:
    t = msg["t"]
    if t == "add":
        req = serial.decode_request(msg["req"])
        rejected = _try_add(core, req)
        if rejected is not None:
            _send_outputs(core, out, [rejected])
    elif t == "abort":
        core.abort_requests(list(msg["ids"]))
    elif t == "call":
        # Generic utility RPC (get_stats, profiling hooks, ...). A bad
        # RPC must not take the core (and every in-flight request) down:
        # failures travel back as an error result.
        try:
            value = getattr(core, msg["method"])(*msg.get("args", ()))
            reply = {"t": "result", "call_id": msg["call_id"],
                     "value": value}
            out.send(serial.pack(reply))
        except Exception as e:  # noqa: BLE001 - reported to caller
            logger.warning("utility RPC %s failed: %s", msg["method"], e)
            out.send(serial.pack({
                "t": "result", "call_id": msg["call_id"], "value": None,
                "error": f"{type(e).__name__}: {e}",
            }))
    elif t == "shutdown":
        raise _Shutdown()
    else:  # pragma: no cover - protocol error
        raise ValueError(f"unknown message type {t!r}")


def _busy_loop(core: EngineCore, inp: zmq.Socket, out: zmq.Socket) -> None:
    """reference: core.py:598 run_busy_loop — block on input when idle,
    otherwise drain input without blocking and step."""
    poller = zmq.Poller()
    poller.register(inp, zmq.POLLIN)
    while True:
        fault_injection.fire_or_raise("engine_core.die")
        busy = (core.has_unfinished_requests()
                or core.has_kv_transfer_work())
        timeout = 0 if busy else _IDLE_POLL_MS
        while poller.poll(timeout):
            _handle_msg(core, out, serial.unpack(inp.recv()))
            timeout = 0
        if not (core.has_unfinished_requests()
                or core.has_kv_transfer_work()):
            continue
        outputs = core.step()
        if outputs:
            _send_outputs(core, out, outputs)
        elif (not core.last_step_scheduled
              and not core.has_inflight_batches()):
            # Nothing ran on device (all requests held on async KV
            # transfers / deferred sends): each step is a host-only
            # poll, so pace it instead of busy-spinning a core for the
            # transfer's duration. Never pace while a dispatched batch
            # awaits its wait_model — sleeping there would park the
            # retire (and the next dispatch) behind the sleep quantum.
            time.sleep(0.005)


# ---------------------------------------------------------------------------
# In-process background core (thread) — used by AsyncLLM when a subprocess
# is unnecessary; shares run-loop semantics with the proc variant.
# ---------------------------------------------------------------------------


class StepOutputs(list):
    """One step's outputs as a transport hands them to the front end:
    the list, the step's number (the ``step`` of its spans) and
    ``time.monotonic()`` of the hand-over (now, in process; the core's
    stamp, off a socket)."""

    __slots__ = ("step", "handed_over")

    def __init__(self, outputs, step: int,
                 handed_over: Optional[float] = None) -> None:
        super().__init__(outputs)
        self.step = step
        self.handed_over = (time.monotonic() if handed_over is None
                            else handed_over)


class BackgroundEngineCore:
    """EngineCore driven by a daemon thread with queue transport.

    Same contract as the ZMQ proc (add/abort in, output batches out) for
    single-process async serving; reference analogue: the in-process
    core_client InprocClient paired with AsyncLLM's output handler.
    """

    def __init__(self, config) -> None:
        fault_injection.fire_or_raise("core_proc.spawn_fail")
        self.config = config
        self.core = EngineCore(config)
        self.input_queue: "queue.Queue[tuple]" = queue.Queue()
        self.output_queue: "queue.Queue[object]" = queue.Queue()
        self._dead = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="engine-core")
        self._thread.start()

    def restart(self) -> None:
        """Rebuild the core + run thread after a death. The queue
        OBJECTS survive (an add_request racing the restart lands in the
        same input queue the fresh thread drains); stale items queued
        before the restart are discarded first. In-flight request state
        is gone — the caller replays its journal."""
        fault_injection.fire_or_raise("core_proc.spawn_fail")
        try:
            self.core.shutdown()
        except Exception:  # noqa: BLE001 - dead core teardown
            pass
        for q in (self.input_queue, self.output_queue):
            while True:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        self.core = EngineCore(self.config)
        self._dead = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="engine-core")
        self._thread.start()

    def check_health(self) -> None:
        """Raise EngineDeadError when the core thread died without
        reporting its error (reference: v1 core_client engine-dead
        detection; here the thread-transport analogue). No staleness
        window for the thread transport: in-process, a wedged step is
        indistinguishable from a legitimate long first compile (the
        subprocess transport gets stall detection from its dedicated
        heartbeat thread, which keeps beating through compiles)."""
        from vllm_distributed_tpu.engine.core_client import EngineDeadError
        if self._dead:
            return  # the terminal error is already in the output queue
        if not self._thread.is_alive():
            raise EngineDeadError(
                "engine core thread exited without reporting")

    def _run(self) -> None:
        try:
            has_kv_connector = \
                self.core.scheduler.kv_connector is not None
            while True:
                fault_injection.fire_or_raise("engine_core.die")
                busy = (self.core.has_unfinished_requests()
                        or self.core.has_kv_transfer_work())
                block = not busy
                # Bounded block only when a KV connector exists: async
                # work can then arrive from a peer's socket with no local
                # input message. Without one, idle blocks indefinitely.
                idle_timeout = 0.05 if has_kv_connector else None
                try:
                    while True:
                        kind, payload = self.input_queue.get(
                            block=block,
                            timeout=idle_timeout if block else 0)
                        if kind == "add":
                            rejected = _try_add(self.core, payload)
                            if rejected is not None:
                                self.output_queue.put(StepOutputs(
                                    [rejected], self.core.phases.step))
                        elif kind == "abort":
                            self.core.abort_requests(payload)
                        elif kind == "shutdown":
                            return
                        block = False
                except queue.Empty:
                    pass
                outputs = self.core.step()
                if outputs:
                    self.output_queue.put(
                        StepOutputs(outputs, self.core.phases.step))
                elif (busy and not self.core.last_step_scheduled
                      and not self.core.has_inflight_batches()):
                    # Host-only poll step (async KV transfer in
                    # flight): pace instead of spinning. A pending
                    # wait_model is NOT paced — the retire must chase
                    # the device, not a sleep quantum.
                    time.sleep(0.005)
        except Exception as e:  # noqa: BLE001
            logger.error("background engine core died: %s", e)
            traceback.print_exc()
            self._dead = True
            self.output_queue.put(e)

    @property
    def is_alive(self) -> bool:
        return self._thread.is_alive() and not self._dead

    def add_request(self, req) -> None:
        self.input_queue.put(("add", req))

    def abort_requests(self, ids: list[str]) -> None:
        self.input_queue.put(("abort", ids))

    def shutdown(self) -> None:
        self.input_queue.put(("shutdown", None))
        self._thread.join(timeout=5)
        self.core.shutdown()
