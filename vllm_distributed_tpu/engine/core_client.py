"""Client side of the engine-core transport.

Reference: vllm/v1/engine/core_client.py:44 (``EngineCoreClient.make_client``
:56 choosing InprocClient :219 / SyncMPClient / AsyncMPClient) and
v1/engine/exceptions.py (EngineDeadError). The multiprocess client spawns
``core_proc.run_engine_core`` and speaks msgpack over ZMQ ipc sockets; the
in-process client wraps EngineCore directly (CPU tests, offline runs).
"""

import os
import tempfile
import time
import uuid
from typing import Optional

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.engine.core_proc import StepOutputs
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.request import EngineCoreRequest

logger = init_logger(__name__)


class EngineDeadError(RuntimeError):
    """The engine core died or stopped responding (reference:
    v1/engine/exceptions.py EngineDeadError). Structured: ``reason``
    carries the detection detail and ``replica`` the DP rank it came
    from (None for a single-core engine), so the OpenAI server can
    surface both in its 503 body."""

    def __init__(self, reason: str = "engine core is dead",
                 replica: Optional[int] = None) -> None:
        self.reason = reason
        self.replica = replica
        detail = (f"[dp replica {replica}] {reason}"
                  if replica is not None else reason)
        super().__init__(detail)


class RestartSupervisor:
    """Restart budget + backoff policy for a dead engine core.

    The recovery ladder's "respawn" rung: each death asks
    ``next_delay()``; the supervisor grants at most ``max_attempts``
    restarts inside a sliding ``window_s`` window, with exponential
    backoff between grants, and returns None once the budget is burnt —
    the caller then circuit-breaks to the terminal EngineDeadError
    (reference analogue: the crash-loop backoff any production
    supervisor, e.g. systemd's StartLimitIntervalSec, applies)."""

    def __init__(self, max_attempts: int, window_s: float,
                 backoff_base_s: float, backoff_max_s: float) -> None:
        self.max_attempts = max_attempts
        self.window_s = window_s
        self.backoff_base_s = backoff_base_s
        self.backoff_max_s = backoff_max_s
        self._attempts: list[float] = []  # monotonic grant times

    @classmethod
    def from_config(cls, config: EngineConfig) -> "RestartSupervisor":
        ft = config.fault_tolerance_config
        return cls(ft.restart_max_attempts, ft.restart_window_s,
                   ft.restart_backoff_base_s, ft.restart_backoff_max_s)

    @property
    def exhausted(self) -> bool:
        self._expire()
        return len(self._attempts) >= self.max_attempts

    def _expire(self) -> None:
        cutoff = time.monotonic() - self.window_s
        self._attempts = [t for t in self._attempts if t > cutoff]

    def peek(self) -> tuple[int, bool]:
        """Read-only ``(attempts_in_window, exhausted)`` for debug
        surfaces: computed from a C-level (GIL-atomic) copy so a poll
        from another thread never rebuilds ``_attempts`` under a
        concurrent ``next_delay()``."""
        cutoff = time.monotonic() - self.window_s
        in_window = sum(1 for t in list(self._attempts) if t > cutoff)
        return in_window, in_window >= self.max_attempts

    def next_delay(self) -> Optional[float]:
        """Grant one restart attempt: the backoff to sleep before it,
        or None when the budget inside the window is exhausted (the
        circuit breaker). max_attempts=0 always refuses (recovery
        disabled)."""
        self._expire()
        if len(self._attempts) >= self.max_attempts:
            return None
        delay = min(self.backoff_base_s * (2 ** len(self._attempts)),
                    self.backoff_max_s)
        self._attempts.append(time.monotonic())
        return delay


class EngineCoreClient:

    @staticmethod
    def make_client(config: EngineConfig) -> "EngineCoreClient":
        from vllm_distributed_tpu import envs
        pc = config.parallel_config
        if pc.data_parallel_size > 1 and pc.data_parallel_mode == "engine":
            from vllm_distributed_tpu.engine.dp_client import DPEngineClient
            return DPEngineClient(config)
        if pc.multiprocess_engine_core or envs.VDT_ENABLE_MP_ENGINE:
            return SyncMPClient(config)
        return InprocClient(config)

    # Interface ---------------------------------------------------------
    def add_request(self, request: EngineCoreRequest) -> None:
        raise NotImplementedError

    def abort_requests(self, request_ids: list[str]) -> None:
        raise NotImplementedError

    def get_output(self) -> list[EngineCoreOutput]:
        """Next batch of per-request output deltas (blocking when work is
        in flight)."""
        raise NotImplementedError

    def has_unfinished_requests(self) -> bool:
        raise NotImplementedError

    def get_stats(self) -> dict:
        raise NotImplementedError

    def call_utility(self, method: str, *args):
        """Generic core RPC (sleep/wake_up/profile/...)."""
        raise NotImplementedError

    def shutdown(self) -> None:
        pass


class InprocClient(EngineCoreClient):
    """Reference: core_client.py:219 InprocClient — step() inline."""

    def __init__(self, config: EngineConfig) -> None:
        from vllm_distributed_tpu.engine.core import EngineCore
        from vllm_distributed_tpu.utils import fault_injection
        fault_injection.fire_or_raise("core_proc.spawn_fail")
        self.config = config
        self.engine_core = EngineCore(config)

    def restart(self) -> None:
        """Rebuild the in-process core (DP resurrection probe). The old
        core's requests are gone — the caller replays its journal."""
        from vllm_distributed_tpu.engine.core import EngineCore
        from vllm_distributed_tpu.utils import fault_injection
        fault_injection.fire_or_raise("core_proc.spawn_fail")
        try:
            self.engine_core.shutdown()
        except Exception:  # noqa: BLE001 - the dead core may be torn
            pass
        self.engine_core = EngineCore(self.config)

    def add_request(self, request: EngineCoreRequest) -> None:
        self.engine_core.add_request(request)

    def abort_requests(self, request_ids: list[str]) -> None:
        self.engine_core.abort_requests(request_ids)

    def get_output(self) -> list[EngineCoreOutput]:
        return self.engine_core.step()

    def has_unfinished_requests(self) -> bool:
        return self.engine_core.has_unfinished_requests()

    def get_stats(self) -> dict:
        return self.engine_core.get_stats()

    def call_utility(self, method: str, *args):
        return getattr(self.engine_core, method)(*args)

    def shutdown(self) -> None:
        self.engine_core.shutdown()

    # Introspection conveniences for tests/tools (in-proc only).
    @property
    def scheduler(self):
        return self.engine_core.scheduler

    @property
    def executor(self):
        return self.engine_core.executor


class SyncMPClient(EngineCoreClient):
    """Engine core in a spawned subprocess, msgpack over ZMQ ipc.

    reference: core_client.py SyncMPClient + MPClient (ready handshake,
    output queue, engine-dead sentinel, startup timeout).

    On an accelerator host the spawned core claims every chip of the
    host, so the parent must not have touched JAX (a chip belongs to
    one process at a time), and only one such core fits a host
    (engine/dp_client.py refuses subprocess DP replicas there).
    """

    def __init__(self, config: EngineConfig) -> None:
        import zmq

        from vllm_distributed_tpu.engine import serial
        self._serial = serial
        self.config = config

        self._sock_dir = tempfile.mkdtemp(prefix="vdt-zmq-")
        self.ctx = zmq.Context()
        self.input_sock = None
        self.output_sock = None
        self.proc = None

        # Live request ids (NOT a counter: a client-side stop abort can
        # race a core-side finish for the same request; set-discard makes
        # the accounting idempotent).
        self._live: set[str] = set()
        self._call_id = 0
        self._pending_outputs: list[list[EngineCoreOutput]] = []
        # Utility-RPC results stashed by recv_outputs (async/pump mode).
        self._results: dict[int, object] = {}
        # Health monitor: every received message (including the core's
        # dedicated heartbeat beats) refreshes liveness; a stale window
        # with work in flight means the core process is wedged even
        # though the OS still reports it alive.
        self.heartbeat_timeout_s = \
            config.fault_tolerance_config.heartbeat_timeout_s
        self._last_alive = time.monotonic()

        self._spawn()

    def _spawn(self) -> None:
        """Spawn the core subprocess and run the ready handshake. Each
        incarnation gets FRESH ipc endpoints: messages buffered toward
        (or from) a dead incarnation must never reach its replacement —
        the journal replay, not the socket backlog, is the source of
        truth after a restart."""
        import multiprocessing

        import zmq

        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.utils import fault_injection
        fault_injection.fire_or_raise("core_proc.spawn_fail")

        for sock in (self.input_sock, self.output_sock):
            if sock is not None:
                sock.close(linger=0)
        rid = uuid.uuid4().hex[:8]
        input_addr = f"ipc://{self._sock_dir}/input-{rid}"
        output_addr = f"ipc://{self._sock_dir}/output-{rid}"
        self.input_sock = self.ctx.socket(zmq.PUSH)
        self.input_sock.bind(input_addr)
        self.output_sock = self.ctx.socket(zmq.PULL)
        self.output_sock.bind(output_addr)

        # spawn (not fork): the child must initialize its own JAX backend.
        mp_ctx = multiprocessing.get_context("spawn")
        from vllm_distributed_tpu.engine.core_proc import run_engine_core
        self.proc = mp_ctx.Process(
            target=run_engine_core,
            args=(self.config, input_addr, output_addr),
            daemon=True, name="vdt-engine-core")
        self.proc.start()

        # Ready handshake (the child compiles/loads weights first).
        timeout_ms = int(envs.VDT_RPC_TIMEOUT * 1000)
        if not self.output_sock.poll(timeout_ms):
            self._kill()
            raise EngineDeadError(
                f"engine core did not become ready in {timeout_ms} ms")
        msg = self._serial.unpack(self.output_sock.recv())
        if msg.get("t") != "ready":
            self._kill()
            raise EngineDeadError(f"bad handshake: {msg}")
        self.config.cache_config.num_gpu_blocks = msg.get("num_pages")
        self._last_alive = time.monotonic()
        logger.info("engine core proc ready (pid %d)", self.proc.pid)

    def restart(self) -> None:
        """Respawn a dead core subprocess. In-flight state is gone —
        the caller (AsyncLLM's supervisor / the DP failover path)
        replays its journal afterwards."""
        self._kill()
        self._live.clear()
        self._pending_outputs.clear()
        self._results.clear()
        self._spawn()

    # ------------------------------------------------------------------
    def _kill(self) -> None:
        if self.proc is not None and self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=5)

    def _send(self, msg: dict) -> None:
        if not self.proc.is_alive():
            raise EngineDeadError("engine core process is not alive")
        self.input_sock.send(self._serial.pack(msg))

    def _recv(self, timeout_ms: int) -> Optional[dict]:
        import zmq
        deadline_poll = timeout_ms
        while True:
            if not self.output_sock.poll(deadline_poll):
                if not self.proc.is_alive():
                    raise EngineDeadError("engine core process died")
                self._check_heartbeat()
                return None
            msg = self._serial.unpack(self.output_sock.recv(zmq.NOBLOCK))
            self._last_alive = time.monotonic()
            if msg.get("t") == "dead":
                raise EngineDeadError(msg.get("error", "engine core died"))
            if msg.get("t") == "hb":
                # Liveness beat only; nothing for the caller.
                return None
            return msg

    def _check_heartbeat(self) -> None:
        """Wedged-process detection: the core's heartbeat thread beats
        through long compiles, so staleness past the window with work in
        flight means the process is hung, not slow."""
        if self.heartbeat_timeout_s <= 0 or not self._live:
            return
        stale = time.monotonic() - self._last_alive
        if stale > self.heartbeat_timeout_s:
            raise EngineDeadError(
                f"engine core unresponsive for {stale:.1f}s (heartbeat "
                f"window {self.heartbeat_timeout_s:.1f}s) with requests "
                f"in flight")

    # ------------------------------------------------------------------
    def _mark_finished(self, outs: list[EngineCoreOutput]) -> None:
        for o in outs:
            if o.finished:
                self._live.discard(o.req_id)

    def add_request(self, request: EngineCoreRequest) -> None:
        self._send({"t": "add", "req": self._serial.encode_request(request)})
        self._live.add(request.request_id)

    def abort_requests(self, request_ids: list[str]) -> None:
        if not request_ids:
            return
        self._send({"t": "abort", "ids": request_ids})
        for rid in request_ids:
            self._live.discard(rid)

    def get_output(self) -> list[EngineCoreOutput]:
        if self._pending_outputs:
            return self._pending_outputs.pop(0)
        if not self._live:
            return []
        while True:
            msg = self._recv(timeout_ms=200)
            if msg is None:
                continue  # core is busy compiling/stepping; keep waiting
            if msg["t"] == "outputs":
                outs = [self._serial.decode_output(v) for v in msg["outs"]]
                self._mark_finished(outs)
                return outs
            # Utility results arriving out of band are queued by caller.
            logger.debug("ignoring out-of-band message %s", msg["t"])

    def recv_outputs(
            self, timeout_ms: int) -> Optional[list[EngineCoreOutput]]:
        """Pump-thread receive (AsyncLLM): next output batch (as
        ``StepOutputs``, with the step's number and the core's stamp of
        the hand-over) or None on timeout; utility results are stashed
        for fetch_result(). All receives must come from ONE thread — zmq
        sockets are not thread-safe."""
        msg = self._recv(timeout_ms)
        if msg is None:
            return None
        if msg["t"] == "outputs":
            outs = StepOutputs(
                [self._serial.decode_output(v) for v in msg["outs"]],
                msg.get("step", 0), msg.get("handed_over"))
            self._mark_finished(outs)
            return outs
        if msg["t"] == "result":
            if msg.get("error") is not None:
                self._results[msg["call_id"]] = RuntimeError(msg["error"])
            else:
                self._results[msg["call_id"]] = msg["value"]
        return None

    def send_utility(self, method: str, *args) -> int:
        """Fire a utility RPC; the result lands in fetch_result() once the
        receive thread pumps it."""
        self._call_id += 1
        self._send({"t": "call", "method": method, "args": list(args),
                    "call_id": self._call_id})
        return self._call_id

    def fetch_result(self, call_id: int, default=None):
        return self._results.pop(call_id, default)

    def has_unfinished_requests(self) -> bool:
        return bool(self._live)

    def get_stats(self) -> dict:
        return self.call_utility("get_stats")

    def call_utility(self, method: str, *args):
        from vllm_distributed_tpu import envs
        self._call_id += 1
        call_id = self._call_id
        self._send({"t": "call", "method": method, "args": list(args),
                    "call_id": call_id})
        deadline = time.monotonic() + envs.VDT_RPC_TIMEOUT
        while True:
            remaining_ms = int((deadline - time.monotonic()) * 1000)
            if remaining_ms <= 0:
                raise EngineDeadError(f"RPC {method} timed out")
            # Bounded polls: heartbeat beats and output batches arrive
            # between polls without consuming the whole RPC budget.
            msg = self._recv(timeout_ms=min(remaining_ms, 1000))
            if msg is None:
                continue
            if msg["t"] == "result" and msg["call_id"] == call_id:
                if msg.get("error") is not None:
                    raise RuntimeError(
                        f"RPC {method} failed in core: {msg['error']}")
                return msg["value"]
            if msg["t"] == "outputs":
                outs = [self._serial.decode_output(v) for v in msg["outs"]]
                self._mark_finished(outs)
                self._pending_outputs.append(outs)

    def shutdown(self) -> None:
        try:
            if self.proc is not None and self.proc.is_alive():
                self.input_sock.send(self._serial.pack({"t": "shutdown"}))
                self.proc.join(timeout=10)
        except Exception:
            pass
        self._kill()
        self.input_sock.close(linger=0)
        self.output_sock.close(linger=0)
        self.ctx.term()
        try:
            import shutil
            shutil.rmtree(self._sock_dir, ignore_errors=True)
        except Exception:
            pass
