"""Shared-memory broadcast MessageQueue over the native ring buffer.

TPU-native equivalent of the reference's
vllm/distributed/device_communicators/shm_broadcast.py (ShmRingBuffer +
MessageQueue): one writer process broadcasts pickled control messages
(scheduler outputs, RPCs) to N same-host reader processes through a
lock-free shared-memory ring — no socket hop, no per-message syscalls.
The ring itself is C++ (native/shm_ring.cpp, built on first use with the
system g++ and loaded via ctypes); this layer adds chunked framing for
messages larger than one slot and the writer/reader handshake.

Wire format: 8-byte little-endian payload length, then the pickle bytes;
the stream is split into chunk_size slots (the reference sizes its
"small" slots at 10 MiB and overflows to a side channel — here large
messages just span slots, which keeps one code path).
"""

import ctypes
import os
import pickle
import subprocess
import threading
from typing import Optional

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native",
                    "shm_ring.cpp")
_BUILD_DIR = os.path.join(os.path.dirname(_SRC), "_build")

_lib = None
_lib_lock = threading.Lock()

DEFAULT_CHUNK = 1 << 20  # 1 MiB slots
DEFAULT_CHUNKS = 16


class ShmRingError(RuntimeError):
    pass


class ShmRingOverrun(ShmRingError):
    """The writer lapped this reader: the slot it needed was reused."""


def _compile_lib() -> str:
    """Build the .so from the C++ source once, keyed by a hash of the
    source (a copied checkout keeps neither mtimes nor the right to
    trust a stale build). Needs ``g++`` on the machine."""
    import hashlib
    os.makedirs(_BUILD_DIR, exist_ok=True)
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"shm_ring.{digest}.so")
    if os.path.exists(so):
        return so
    tmp = so + f".tmp.{os.getpid()}"
    # -lrt: shm_open/shm_unlink live in librt on glibc < 2.34 (a no-op
    # link on newer glibc where they merged into libc). Without it the
    # .so only loads when some earlier import already mapped librt into
    # the process — load-order-dependent dlopen failures.
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp,
           "-lrt"]
    logger.info("building shm ring: %s", " ".join(cmd))
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so)  # atomic vs concurrent builders
    return so


def _get_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(_compile_lib())
        lib.shm_ring_create.restype = ctypes.c_void_p
        lib.shm_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                        ctypes.c_uint64]
        lib.shm_ring_open.restype = ctypes.c_void_p
        lib.shm_ring_open.argtypes = [ctypes.c_char_p, ctypes.c_double]
        lib.shm_ring_register_reader.restype = ctypes.c_int64
        lib.shm_ring_register_reader.argtypes = [ctypes.c_void_p]
        lib.shm_ring_chunk_size.restype = ctypes.c_uint64
        lib.shm_ring_chunk_size.argtypes = [ctypes.c_void_p]
        lib.shm_ring_num_chunks.restype = ctypes.c_uint64
        lib.shm_ring_num_chunks.argtypes = [ctypes.c_void_p]
        lib.shm_ring_write.restype = ctypes.c_int64
        lib.shm_ring_write.argtypes = [ctypes.c_void_p,
                                       ctypes.c_char_p, ctypes.c_uint64,
                                       ctypes.c_double]
        lib.shm_ring_writer_seq.restype = ctypes.c_uint64
        lib.shm_ring_writer_seq.argtypes = [ctypes.c_void_p]
        lib.shm_ring_reader_count.restype = ctypes.c_uint64
        lib.shm_ring_reader_count.argtypes = [ctypes.c_void_p]
        lib.shm_ring_read.restype = ctypes.c_int64
        lib.shm_ring_read.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_uint64, ctypes.c_char_p,
                                      ctypes.c_double]
        lib.shm_ring_close.restype = None
        lib.shm_ring_close.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        _lib = lib
        return _lib


class MessageQueue:
    """One-writer N-reader broadcast queue.

    Writer: ``MessageQueue.create(name, num_readers)`` then ``enqueue``;
    the first enqueue blocks until all declared readers joined (the
    reference's handshake in MessageQueue.wait_until_ready). Readers:
    ``MessageQueue.join(name)`` then ``dequeue`` in a loop. FIFO,
    every reader sees every message.
    """

    def __init__(self, handle, name: str, is_writer: bool,
                 num_readers: int = 0, rank: int = -1,
                 start_seq: Optional[int] = None):
        self._lib = _get_lib()
        self._h = handle
        self._name = name
        self._is_writer = is_writer
        self._num_readers = num_readers
        self._rank = rank
        self._seq = (start_seq if start_seq is not None else
                     self._lib.shm_ring_writer_seq(handle))
        self._chunk = self._lib.shm_ring_chunk_size(handle)
        self._ready = False
        self._broken = False
        self._buf = ctypes.create_string_buffer(self._chunk)
        # Ring telemetry (metrics/telemetry.py): per-message wall time
        # spent blocked in the native write/read calls, plus the reader
        # backlog (writer_seq - reader_seq). Captured at construction —
        # the engine core installs its recorder only for that window.
        from vllm_distributed_tpu.metrics import telemetry
        self._telemetry = telemetry.current_recorder()

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, name: str, num_readers: int,
               chunk_size: int = DEFAULT_CHUNK,
               num_chunks: int = DEFAULT_CHUNKS) -> "MessageQueue":
        lib = _get_lib()
        h = lib.shm_ring_create(name.encode(), chunk_size, num_chunks)
        if not h:
            raise ShmRingError(f"shm_ring_create({name!r}) failed")
        return cls(h, name, is_writer=True, num_readers=num_readers)

    @classmethod
    def join(cls, name: str, timeout: float = 30.0) -> "MessageQueue":
        lib = _get_lib()
        h = lib.shm_ring_open(name.encode(), timeout)
        if not h:
            raise ShmRingError(f"shm_ring_open({name!r}) timed out")
        # Capture the start cursor BEFORE registering: the writer's join
        # handshake can release it the instant the last reader registers,
        # and a message sent between register and a later seq capture
        # would be skipped forever.
        start_seq = lib.shm_ring_writer_seq(h)
        rank = lib.shm_ring_register_reader(h)
        if rank < 0:
            lib.shm_ring_close(h, None)
            raise ShmRingError("shm ring reader table full")
        return cls(h, name, is_writer=False, rank=rank,
                   start_seq=start_seq)

    # ------------------------------------------------------------------
    def _wait_ready(self, timeout: float) -> None:
        """Writer-side: block until every declared reader registered, so
        lap-accounting covers them from message 0."""
        import time
        deadline = time.monotonic() + timeout
        while True:
            if self._reader_count() >= self._num_readers:
                self._ready = True
                return
            if time.monotonic() >= deadline:
                raise ShmRingError(
                    f"only {self._reader_count()} of {self._num_readers} "
                    f"readers joined {self._name!r} within {timeout}s")
            time.sleep(0.005)

    def _reader_count(self) -> int:
        return self._lib.shm_ring_reader_count(self._h)

    def enqueue_bytes(self, payload: bytes, timeout: float = 30.0) -> None:
        """Broadcast raw bytes (callers that already serialized — e.g.
        the multi-host executor pickles SchedulerOutput once for both
        transports — skip a second pickle round)."""
        assert self._is_writer
        if self._broken:
            raise ShmRingError(
                f"queue {self._name!r} is broken: an earlier enqueue "
                "timed out mid-message, readers are desynced")
        if not self._ready:
            self._wait_ready(timeout)
        import time
        t0 = time.perf_counter()
        stream = len(payload).to_bytes(8, "little") + payload
        for off in range(0, len(stream), self._chunk):
            piece = stream[off:off + self._chunk]
            rc = self._lib.shm_ring_write(self._h, piece, len(piece),
                                          timeout)
            if rc == 0:
                continue
            # A timeout after the first chunk leaves a truncated message
            # in the ring; later writes would be parsed as its tail.
            # There is no broadcast rollback — poison the queue instead
            # of silently corrupting every reader's framing.
            if off > 0:
                self._broken = True
            if rc == -2:
                raise ShmRingError(
                    f"enqueue timed out: a reader of {self._name!r} has "
                    f"not drained the ring in {timeout}s")
            raise ShmRingError(f"shm_ring_write failed rc={rc}")
        self._telemetry.record_shm("write", time.perf_counter() - t0)

    def enqueue(self, obj, timeout: float = 30.0) -> None:
        self.enqueue_bytes(
            pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL), timeout)

    def dequeue_bytes(self, timeout: float = 30.0) -> bytes:
        assert not self._is_writer
        import time
        t0 = time.perf_counter()
        first = self._read_chunk(timeout)
        total = int.from_bytes(first[:8], "little")
        data = first[8:8 + total]
        while len(data) < total:
            piece = self._read_chunk(timeout)
            data += piece[:total - len(data)]
        # Backlog AFTER consuming this message: chunks the writer has
        # published that this reader has not yet dequeued (a persistent
        # positive lag means this reader is the pod's straggler).
        lag = max(
            int(self._lib.shm_ring_writer_seq(self._h)) - self._seq, 0)
        self._telemetry.record_shm("read", time.perf_counter() - t0,
                                   lag=lag)
        return data

    def dequeue(self, timeout: float = 30.0):
        return pickle.loads(self.dequeue_bytes(timeout))

    def _read_chunk(self, timeout: float) -> bytes:
        rc = self._lib.shm_ring_read(self._h, self._rank, self._seq,
                                     self._buf, timeout)
        if rc == -2:
            raise TimeoutError(
                f"dequeue timed out after {timeout}s on {self._name!r}")
        if rc == -3:
            raise ShmRingOverrun(
                f"reader {self._rank} lapped on {self._name!r}: raise "
                "num_chunks or drain faster")
        if rc < 0:
            raise ShmRingError(f"shm_ring_read failed rc={rc}")
        self._seq += 1
        # rc is the payload length: only that many bytes were copied.
        return self._buf[:rc]

    def close(self) -> None:
        if self._h is not None:
            unlink = self._name.encode() if self._is_writer else None
            self._lib.shm_ring_close(self._h, unlink)
            self._h = None

    def __del__(self):  # pragma: no cover - GC-order best effort
        try:
            self.close()
        except Exception:
            pass
