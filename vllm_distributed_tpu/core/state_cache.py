"""First-class SSM state cache: O(1) checkpoint/restore for stateful
(Mamba/Jamba/Bamba) serving.

Paged KV can be re-derived at any page boundary by continuation prefill,
so prefix caching, preemption and crash recovery all come for free for
attention models. An SSM recurrence cannot re-enter at an arbitrary
boundary — but its state is CONSTANT-SIZE, so a snapshot of the
``(conv_state, ssm_state)`` rows at a token boundary is a complete
resume point (PAPERS.md "Compiler-First State Space Duality and Portable
O(1) Autoregressive Caching"). This module gives that snapshot the same
rights paged KV already has:

* **Prefix "caching"** — a bounded device-side pool of per-request state
  snapshots keyed by the chained ``BlockHash`` of the token prefix (the
  exact hashing the page prefix cache uses, ``core/kv_cache_utils``),
  with LRU eviction. A WAITING stateful request whose prompt prefix
  matches a snapshot is admitted as a continuation at the snapshot
  boundary instead of token 0 — shared system prompts and multi-turn
  sessions skip the re-prefill entirely.
* **Preemption parks state** — ``Scheduler._preempt`` snapshots the
  victim's state rows into the pool instead of discarding; resume
  restores the rows and continues, re-prefilling at most the tail since
  the last checkpoint boundary.
* **O(1) crash recovery** — snapshots optionally serialize to a host
  checkpoint journal (``VDT_SSM_CKPT_DIR``; one atomically-renamed file
  per snapshot, the shared_storage connector's tmp+rename .npz
  discipline). A respawned core's journal replay finds the last
  checkpoint by content hash and re-prefills only the tail — bounded by
  ``VDT_SSM_CKPT_INTERVAL`` tokens instead of O(prompt).

Boundaries are page-aligned multiples of the checkpoint interval: the
scheduler clips prefill chunks to land exactly on a boundary, so the
state rows hold exactly-the-boundary state when the snapshot copy runs.
Hybrid models (Jamba/Bamba) must restore state rows AND attention KV
pages coherently, so a hit additionally requires every prefix page to
still be resident in the block pool's prefix cache; pure-SSM models
(``STATE_ONLY``) carry no KV bytes and skip the page requirement.

This manager is pure host-side control plane (no jax): the scheduler
owns the bookkeeping and ships ``state_saves`` / ``state_restores``
directives on ``SchedulerOutput``; the model runner executes them as
jitted row<->pool copies (``worker/model_runner.py``) in dispatch
program order, which is what makes same-step restore-then-evict safe
(restores run before the forward, saves after it).
"""

import os
import zlib
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from vllm_distributed_tpu.core.kv_cache_utils import (hash_block_tokens,
                                                      request_hash_seed)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.request import Request

logger = init_logger(__name__)


def state_cache_enabled(config, stateful: bool) -> bool:
    """Single gate shared by the scheduler (bookkeeping) and the runner
    (device pool) so the two sides can never disagree about whether
    directives will be executed. The cache needs one runner driving one
    mesh (no token-parallel page partitions, no PP stage split, no
    follower hosts replaying broadcast outputs) and no KV connector
    (external-KV admission and state restore would race over
    num_computed_tokens)."""
    if not stateful:
        return False
    from vllm_distributed_tpu import envs
    if not envs.VDT_SSM_STATE_CACHE:
        return False
    pc = config.parallel_config
    kv_cfg = config.kv_transfer_config
    return (pc.token_parallel_size == 1
            and pc.pipeline_parallel_size == 1
            and pc.num_hosts <= 1
            and not (kv_cfg is not None and kv_cfg.kv_connector))


def resolve_state_slots(config) -> int:
    """Snapshot-pool slot count (device rows per state array). Shared by
    the scheduler and the runner — both must size identically."""
    from vllm_distributed_tpu import envs
    n = envs.VDT_SSM_STATE_CACHE_SLOTS
    if n > 0:
        return n
    # What the worker's HBM profile cut the default to, if it had to.
    sized = config.cache_config.num_state_slots
    return sized or default_state_slots(config)


def default_state_slots(config) -> int:
    return max(2 * config.scheduler_config.max_num_seqs, 8)


def resolve_ckpt_interval(config) -> int:
    """Checkpoint cadence in tokens, rounded UP to a page multiple so
    every snapshot boundary is also a block-hash boundary."""
    from vllm_distributed_tpu import envs
    bs = config.cache_config.block_size
    interval = max(envs.VDT_SSM_CKPT_INTERVAL, bs)
    return ((interval + bs - 1) // bs) * bs


# ---------------------------------------------------------------------------
# Host checkpoint journal (shared_storage connector file discipline:
# one file per snapshot, tmp + atomic rename, content-hash key).
# ---------------------------------------------------------------------------
def journal_path(journal_dir: str, key: bytes) -> str:
    return os.path.join(journal_dir, f"ssm_{key.hex()}.npz")


def _fs_now(dirpath: str) -> Optional[float]:
    """Current time on the FILESYSTEM's clock: the mtime of a
    just-written probe file. Journal TTLs compare against os.stat
    mtimes, so age arithmetic must read the clock that stamped them —
    never the process wall clock, whose view can skew from the
    filesystem's (remote mounts, clock steps between writer and
    sweeper)."""
    import tempfile
    try:
        fd, path = tempfile.mkstemp(prefix=".sweep_probe_", dir=dirpath)
    except OSError:
        return None
    try:
        os.close(fd)
        return os.stat(path).st_mtime
    except OSError:
        return None
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


def sweep_journal(journal_dir: str, *, max_bytes: int, ttl_s: float,
                  keep=frozenset(), now: float = None) -> tuple[int, int]:
    """Bounded-retention sweep of a checkpoint-journal directory:
    deletes ``ssm_*.npz`` files older than ``ttl_s`` seconds (0 = no
    TTL), then — if the survivors still exceed ``max_bytes`` (0 =
    unbounded) — the oldest first until the directory fits. Paths in
    ``keep`` (checkpoints an unshipped persist directive still owes, or
    the blocked-admission memo) are never reclaimed; neither is
    anything that is not a journal file. Returns (files_removed,
    bytes_removed).

    Content-addressed journal files deliberately outlive their requests
    (they ARE the crash-recovery tier), so this sweep — run at manager
    init and on sleep() — is the only thing bounding the directory."""
    if not journal_dir or not os.path.isdir(journal_dir):
        return 0, 0
    if now is None:
        # File ages only compare meaningfully on the clock that stamped
        # the mtimes. A probe write reads "filesystem now" from that
        # same clock — the monotonic-clock policy's answer for file
        # TTLs, where a process wall-clock read would re-introduce the
        # process-vs-filesystem skew the deadline lint bans. A failed
        # probe (read-only or FULL disk — exactly when reclamation
        # matters most) skips only the TTL pass below; the size prune
        # is mtime-ORDER only and needs no clock, so it still runs.
        now = _fs_now(journal_dir)
    entries = []
    for name in os.listdir(journal_dir):
        if not (name.startswith("ssm_") and name.endswith(".npz")):
            continue
        path = os.path.join(journal_dir, name)
        if path in keep:
            continue
        try:
            st = os.stat(path)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, path))
    entries.sort()  # oldest first
    removed = removed_bytes = 0

    def reclaim(mtime, size, path) -> bool:
        nonlocal removed, removed_bytes
        try:
            os.remove(path)
        except OSError:
            return False
        removed += 1
        removed_bytes += size
        return True

    survivors = []
    for mtime, size, path in entries:
        if ttl_s > 0 and now is not None and now - mtime > ttl_s:
            reclaim(mtime, size, path)
        else:
            survivors.append((mtime, size, path))
    if max_bytes > 0:
        total = sum(size for _, size, _ in survivors)
        for mtime, size, path in survivors:
            if total <= max_bytes:
                break
            if reclaim(mtime, size, path):
                total -= size
    if removed:
        logger.info(
            "SSM checkpoint journal sweep: reclaimed %d files "
            "(%.1f MiB) from %s", removed, removed_bytes / 2**20,
            journal_dir)
    return removed, removed_bytes


def state_fingerprint(shapes: dict) -> bytes:
    """Geometry fingerprint of a model's state arrays ({name: ((shape),
    dtype)}): stored in every journal file and checked at lookup so a
    VDT_SSM_CKPT_DIR shared across models/revisions can never serve a
    CRC-valid but shape-foreign checkpoint into the runner."""
    import hashlib
    desc = sorted((name, tuple(int(x) for x in shape), str(dtype))
                  for name, (shape, dtype) in shapes.items())
    return hashlib.sha256(repr(desc).encode()).digest()[:16]


def write_journal(path: str, arrays: dict[str, np.ndarray],
                  num_tokens: int, fingerprint: bytes = b"") -> None:
    """Serialize one snapshot's state arrays. Arrays are stored as raw
    bytes + (shape, dtype) metadata so bfloat16 (ml_dtypes) rows
    round-trip without numpy's native-dtype restrictions; a CRC32 over
    the payload guards restores against torn/corrupt files."""
    payload: dict[str, np.ndarray] = {
        "num_tokens": np.asarray([num_tokens], np.int64),
        "fingerprint": np.frombuffer(fingerprint, np.uint8),
    }
    crc = 0
    for name in sorted(arrays):
        a = np.ascontiguousarray(np.asarray(arrays[name]))
        data = a.tobytes()
        crc = zlib.crc32(data, crc)
        payload[f"{name}.data"] = np.frombuffer(data, np.uint8)
        payload[f"{name}.shape"] = np.asarray(a.shape, np.int64)
        payload[f"{name}.dtype"] = np.frombuffer(
            a.dtype.name.encode(), np.uint8)
    payload["checksum"] = np.asarray([crc & 0xFFFFFFFF], np.uint64)
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, path)


def read_journal(path: str) -> Optional[dict[str, np.ndarray]]:
    """Load + checksum-verify one snapshot file. Returns the state
    arrays (keyed by state-cache name), or None on any corruption —
    including the deterministic ``ssm.restore_corrupt`` fault point,
    which simulates a checksum mismatch so the degrade-to-re-prefill
    path can be drilled."""
    from vllm_distributed_tpu.utils import fault_injection
    try:
        with np.load(path) as f:
            stored = int(f["checksum"][0])
            names = sorted(k[:-5] for k in f.files if k.endswith(".data"))
            fingerprint = (bytes(f["fingerprint"])
                           if "fingerprint" in f.files else b"")
            crc = 0
            out: dict[str, np.ndarray] = {}
            for name in names:
                data = f[f"{name}.data"].tobytes()
                crc = zlib.crc32(data, crc)
                shape = tuple(int(x) for x in f[f"{name}.shape"])
                dtype_name = bytes(f[f"{name}.dtype"]).decode()
                try:
                    dtype = np.dtype(dtype_name)
                except TypeError:
                    import ml_dtypes  # registers bfloat16 et al.
                    dtype = np.dtype(getattr(ml_dtypes, dtype_name))
                out[name] = np.frombuffer(data, dtype).reshape(shape)
    except Exception as e:  # noqa: BLE001 - torn/missing file
        logger.warning("unreadable SSM checkpoint %s: %s", path, e)
        return None
    if (crc & 0xFFFFFFFF) != stored or fault_injection.should_fire(
            "ssm.restore_corrupt"):
        logger.warning("SSM checkpoint %s failed its checksum; "
                       "degrading to full re-prefill", path)
        return None
    out["__fingerprint__"] = fingerprint
    return out


# ---------------------------------------------------------------------------
@dataclass
class StateSnapshot:
    """One committed (or pending) snapshot: pool slot ``slot`` holds the
    state after exactly ``num_tokens`` tokens whose chained page hash is
    ``key`` (None while a speculative save's tokens are unconfirmed)."""

    slot: int
    num_tokens: int
    key: Optional[bytes] = None
    journal: Optional[str] = None
    last_used: int = 0
    # A deferred journal write (async saves resolve their key only at
    # commit) is still owed for this slot: eviction must not reuse it
    # until the persist directive ships.
    journal_pending: bool = False


@dataclass
class SaveDirective:
    """Wire form of one pending snapshot copy (SchedulerOutput
    ``state_saves``): the runner copies input-batch row(req_id) into
    pool slot ``slot`` after the step's forward, and, when ``journal``
    is set, serializes the slot to that path. ``persist_only``
    directives skip the copy — they journal an already-committed slot
    whose key (and therefore path) only became known at commit time
    (async run-ahead saves)."""

    req_id: str
    slot: int
    num_tokens: int
    journal: Optional[str] = None
    persist_only: bool = False


@dataclass
class RestoreDirective:
    """Wire form of one restore (SchedulerOutput ``state_restores``):
    before the step's forward the runner fills input-batch row(req_id)
    from pool slot ``slot``, or — for a crash-recovery journal hit
    (slot < 0) — from the checkpoint at ``journal`` (``arrays`` carries
    the scheduler's already-verified payload; directives never cross a
    process boundary, so the runner reuses it instead of re-reading)."""

    req_id: str
    slot: int
    num_tokens: int
    journal: Optional[str] = None
    arrays: Optional[dict] = None


@dataclass
class StateCacheManager:
    """Scheduler-side bookkeeping for the snapshot pool. Pure python —
    device copies happen in the runner, driven by the directives this
    manager emits."""

    num_slots: int
    block_size: int
    interval: int
    paged_kv: bool
    journal_dir: str = ""
    # Per-slot device bytes (conv + ssm rows across layers) and the
    # journal geometry fingerprint; wired by the engine core from the
    # runner's pool geometry after construction (the scheduler never
    # touches device arrays).
    bytes_per_slot: int = 0
    journal_fingerprint: bytes = b""
    # Hierarchical KV/state tiering (VDT_KV_TIERING): LRU eviction
    # DEMOTES a committed snapshot to the checkpoint journal (one owed
    # persist_only directive; the slot stays pinned until it ships)
    # instead of discarding device-only state — the journal is the
    # snapshot pool's second tier, closing the "eviction discards"
    # gap. Restores of demoted snapshots ride the existing journal
    # fallback of get_computed_state.
    demote_on_evict: bool = False

    by_key: dict[bytes, StateSnapshot] = field(default_factory=dict)
    by_slot: dict[int, StateSnapshot] = field(default_factory=dict)
    free_slots: list[int] = field(default_factory=list)
    # (req_id, num_tokens) -> snapshot issued but not yet committed by
    # update_from_output (the copy may be in flight on device).
    pending: dict[tuple[str, int], StateSnapshot] = field(
        default_factory=dict)
    # Deferred journal writes for committed async saves (key resolved
    # at commit): drained into the next non-empty SchedulerOutput as
    # persist_only directives.
    pending_persists: list = field(default_factory=list)
    # Incremental per-request hash chains (same chaining as
    # hash_request_tokens; dropped on finish).
    _chains: dict[str, list] = field(default_factory=dict)
    # (path, verified arrays) of the most recent journal read: blocked
    # admissions retry the same lookup every step.
    _last_journal: Optional[tuple] = None
    _clock: int = 0

    # Stats (flat numeric keys so the DP aggregator's numeric-sum loop
    # merges them across replicas without special cases).
    hits: int = 0
    queries: int = 0
    evictions: int = 0
    checkpoints: int = 0
    resume_tokens_saved: int = 0
    restore_corruptions: int = 0
    journal_files_reclaimed: int = 0
    journal_demotions: int = 0

    def __post_init__(self) -> None:
        self.free_slots = list(range(self.num_slots - 1, -1, -1))
        if self.journal_dir:
            os.makedirs(self.journal_dir, exist_ok=True)
            # Retention sweep at init: expired / over-budget files from
            # prior runs are reclaimed BEFORE any of them could serve a
            # replay (recent checkpoints — the ones recovery actually
            # wants — sort last and survive).
            self._sweep_journal()

    def _sweep_journal(self) -> None:
        from vllm_distributed_tpu import envs
        keep = {s.journal for s in self.by_key.values() if s.journal}
        keep.update(d.journal for d in self.pending_persists
                    if getattr(d, "journal", None))
        if self._last_journal is not None:
            keep.add(self._last_journal[0])
        removed, _ = sweep_journal(
            self.journal_dir,
            max_bytes=envs.VDT_SSM_CKPT_MAX_MB * 2**20,
            ttl_s=envs.VDT_SSM_CKPT_TTL_S, keep=keep)
        self.journal_files_reclaimed += removed

    # ------------------------------------------------------------------
    # Hash chains
    # ------------------------------------------------------------------
    def _chain(self, request: Request, num_tokens: int) -> list:
        """Chained page hashes covering tokens[0:num_tokens] (page
        multiple), extended incrementally per request."""
        chain = self._chains.setdefault(request.request_id, [])
        want = num_tokens // self.block_size
        tokens = request.all_token_ids
        parent = (chain[-1].hash_value if chain
                  else request_hash_seed(request))
        while len(chain) < want:
            start = len(chain) * self.block_size
            bh = hash_block_tokens(
                parent, tuple(tokens[start:start + self.block_size]))
            chain.append(bh)
            parent = bh.hash_value
        return chain[:want]

    def _key_at(self, request: Request, num_tokens: int) -> bytes:
        return self._chain(request, num_tokens)[-1].hash_value

    def drop_request(self, req_id: str) -> None:
        """Forget per-request scratch on finish (snapshots themselves
        are content-addressed and deliberately outlive the request —
        they ARE the multi-turn prefix cache). The journal memo exists
        only to serve a BLOCKED admission's retries; once requests
        finish it must not pin a checkpoint's host arrays forever."""
        self._chains.pop(req_id, None)
        self._last_journal = None

    # ------------------------------------------------------------------
    # Grant shaping
    # ------------------------------------------------------------------
    def clip_grant(self, num_computed: int, granted: int) -> int:
        """Clip a prefill grant so it ENDS exactly on the LAST snapshot
        boundary it can reach — the state rows then hold
        exactly-the-boundary state when the save directive's copy runs.
        Clipping to the furthest (not the next) boundary keeps prefill
        chunks at the token budget, not the interval: a grant loses at
        most ``interval - 1`` tokens, never ``granted - interval``."""
        end = num_computed + granted
        boundary = (end // self.interval) * self.interval
        if boundary > num_computed and boundary < end:
            return boundary - num_computed
        return granted

    # ------------------------------------------------------------------
    # Saves
    # ------------------------------------------------------------------
    def maybe_save(self, request: Request,
                   num_tokens: int) -> Optional[SaveDirective]:
        """Snapshot directive for a request whose computed-token count
        reaches ``num_tokens`` this step, or None (off-boundary,
        already snapshotted, or the pool is fully pinned by pending
        copies). ``num_tokens`` may exceed the host-known tokens under
        async run-ahead — the key is then resolved at commit time, once
        the speculative token has reconciled."""
        if num_tokens <= 0 or num_tokens % self.interval != 0:
            return None
        if (request.request_id, num_tokens) in self.pending:
            return None
        key = None
        journal = None
        if num_tokens <= request.num_tokens:
            key = self._key_at(request, num_tokens)
            snap = self.by_key.get(key)
            if snap is not None:
                self._touch(snap)
                return None  # identical prefix already snapshotted
            if self.journal_dir:
                journal = journal_path(self.journal_dir, key)
        slot = self._take_slot()
        if slot is None:
            return None
        snap = StateSnapshot(slot=slot, num_tokens=num_tokens, key=key,
                             journal=journal)
        self.by_slot[slot] = snap
        self.pending[(request.request_id, num_tokens)] = snap
        return SaveDirective(req_id=request.request_id, slot=slot,
                             num_tokens=num_tokens, journal=journal)

    def commit_save(self, directive: SaveDirective,
                    request: Optional[Request]) -> None:
        """Finalize (or discard) a shipped save once its step
        reconciled: the snapshot enters the lookup index only if the
        request actually committed tokens through the boundary — an
        async run-ahead that stopped short must not advertise state
        containing a discarded token."""
        snap = self.pending.pop((directive.req_id, directive.num_tokens),
                                None)
        if snap is None:
            return  # aborted (restart-from-scratch / external finish)
        valid = (request is not None
                 and request.num_tokens >= directive.num_tokens)
        if valid and snap.key is None:
            snap.key = self._key_at(request, directive.num_tokens)
        if valid and self.by_key.get(snap.key) is not None:
            # Two requests with an identical prefix raced their pending
            # saves; the first committed copy wins (same content).
            valid = False
        if not valid:
            self._release(snap)
            return
        self.by_key[snap.key] = snap
        self._touch(snap)
        self.checkpoints += 1
        if (self.journal_dir and snap.journal is None):
            # Async save whose key only resolved now: the journal write
            # could not ride the original copy. Owe a persist_only
            # directive (next non-empty output); the slot is pinned
            # against eviction until it ships.
            snap.journal = journal_path(self.journal_dir, snap.key)
            if not os.path.exists(snap.journal):
                snap.journal_pending = True
                self.pending_persists.append(SaveDirective(
                    req_id=directive.req_id, slot=snap.slot,
                    num_tokens=snap.num_tokens, journal=snap.journal,
                    persist_only=True))

    def abort_pending(self, req_id: str) -> None:
        """Drop every uncommitted save of ``req_id`` — called when the
        request restarts its recurrence from an earlier point (resume
        from scratch or from an older snapshot) or finishes externally:
        a later copy of its row would capture state the pending
        boundary no longer describes."""
        for pkey in [k for k in self.pending if k[0] == req_id]:
            self._release(self.pending.pop(pkey))

    def is_pending(self, directive: SaveDirective) -> bool:
        return (directive.req_id, directive.num_tokens) in self.pending

    def take_persists(self) -> list:
        """Drain the owed journal writes. Un-pinning at drain time is
        safe: the directives dispatch within this very step, and any
        later eviction's overwriting copy is dispatched after them —
        device program order serializes the reads before the write."""
        if not self.pending_persists:
            return []
        out = []
        for d in self.pending_persists:
            snap = self.by_slot.get(d.slot)
            if snap is None or snap.journal != d.journal:
                continue  # snapshot reset/released meanwhile
            snap.journal_pending = False
            out.append(d)
        self.pending_persists = []
        return out

    # ------------------------------------------------------------------
    # Lookup / restore
    # ------------------------------------------------------------------
    def get_computed_state(self, request: Request, block_pool) -> tuple[
            list, int, Optional[RestoreDirective]]:
        """Longest-prefix snapshot lookup for a WAITING stateful
        request. Returns (cached prefix pages, boundary, restore
        directive) — ([], 0, None) on miss. Hybrid models additionally
        require every prefix page resident in ``block_pool`` (state
        rows and attention KV must re-enter coherently); pure-SSM
        models carry no KV bytes and skip the page check. Device-pool
        misses fall back to the host checkpoint journal (crash
        recovery), checksum-verified before admission. The HIT counter
        is incremented by the scheduler at successful admission, not
        here — a blocked queue head retries this lookup every step and
        must not inflate the hit rate."""
        self.queries += 1
        # At least one token must remain to be computed (same rule as
        # the page prefix cache: the last token must produce a logit).
        max_tokens = request.num_tokens - 1
        boundary = (max_tokens // self.interval) * self.interval
        resident: list = []
        if self.paged_kv and boundary > 0:
            # ONE forward walk of the page chain finds the longest
            # resident prefix; it caps the boundary scan so the lookup
            # is O(pages), not O(boundaries x pages). Residency must be
            # re-checked on every admission attempt — ref-0 cached
            # pages can be evicted between retries of a blocked queue
            # head, and a stale block handle would be page corruption.
            for bh in self._chain(request, boundary):
                block = block_pool.get_cached_block(bh)
                if block is None:
                    break
                resident.append(block)
            boundary = min(boundary,
                           (len(resident) * self.block_size
                            // self.interval) * self.interval)
        while boundary > 0:
            chain = self._chain(request, boundary)
            key = chain[-1].hash_value
            blocks = (resident[:boundary // self.block_size]
                      if self.paged_kv else [])
            snap = self.by_key.get(key)
            if snap is not None:
                self._touch(snap)
                return blocks, boundary, RestoreDirective(
                    req_id=request.request_id, slot=snap.slot,
                    num_tokens=boundary)
            if self.journal_dir:
                path = journal_path(self.journal_dir, key)
                if os.path.exists(path):
                    # One-entry memo: a blocked admission retries the
                    # same queue head every step, and the file content
                    # is immutable (content-addressed, atomic rename),
                    # so re-reading + re-CRC'ing multi-MB state per
                    # step would be pure waste.
                    if (self._last_journal is not None
                            and self._last_journal[0] == path):
                        arrays = self._last_journal[1]
                    else:
                        arrays = read_journal(path)
                    if arrays is None:
                        # Quarantine: a corrupt checkpoint must not be
                        # re-verified (and re-counted) on every later
                        # admission of the same prefix.
                        self.restore_corruptions += 1
                        try:
                            os.remove(path)
                        except OSError:
                            pass
                        boundary -= self.interval
                        continue
                    stored_fp = arrays.get("__fingerprint__", b"")
                    if (self.journal_fingerprint and stored_fp
                            and stored_fp != self.journal_fingerprint):
                        # A shared journal dir serving another model's
                        # geometry: miss (do NOT delete — the file is
                        # someone else's valid checkpoint).
                        logger.warning(
                            "SSM checkpoint %s has a foreign state "
                            "geometry; ignoring", path)
                        boundary -= self.interval
                        continue
                    self._last_journal = (path, arrays)
                    return blocks, boundary, RestoreDirective(
                        req_id=request.request_id, slot=-1,
                        num_tokens=boundary, journal=path,
                        arrays=arrays)
            boundary -= self.interval
        return [], 0, None

    # ------------------------------------------------------------------
    # Slots / LRU
    # ------------------------------------------------------------------
    def _touch(self, snap: StateSnapshot) -> None:
        self._clock += 1
        snap.last_used = self._clock

    def _release(self, snap: StateSnapshot) -> None:
        self.by_slot.pop(snap.slot, None)
        if snap.key is not None:
            existing = self.by_key.get(snap.key)
            if existing is snap:
                del self.by_key[snap.key]
        self.free_slots.append(snap.slot)

    def _take_slot(self) -> Optional[int]:
        if self.free_slots:
            return self.free_slots.pop()
        # Evict the least-recently-used COMMITTED snapshot. Pending
        # slots are skipped (their device copy may be in flight), but a
        # committed victim's slot can be reused immediately: the
        # overwriting copy is dispatched after any restore that still
        # references the old content, and device program order
        # serializes them (restores run pre-forward, saves
        # post-forward).
        while True:
            committed = [s for s in self.by_slot.values()
                         if s.key is not None and s.key in self.by_key
                         and self.by_key[s.key] is s
                         and not s.journal_pending]
            if not committed:
                return None
            victim = min(committed, key=lambda s: s.last_used)
            if (self.demote_on_evict and self.journal_dir
                    and victim.key is not None):
                # Journal-as-second-tier (VDT_KV_TIERING): a victim
                # whose checkpoint file is missing (journal written
                # lazily, or reclaimed by the sweep) is DEMOTED, not
                # discarded — owe its journal write as a persist_only
                # directive and pin the slot until it ships; the LRU
                # walk picks another victim this round. Once the file
                # exists the slot evicts normally and the journal
                # fallback of get_computed_state serves restores.
                if victim.journal is None:
                    victim.journal = journal_path(self.journal_dir,
                                                  victim.key)
                if not os.path.exists(victim.journal):
                    victim.journal_pending = True
                    self.pending_persists.append(SaveDirective(
                        req_id="", slot=victim.slot,
                        num_tokens=victim.num_tokens,
                        journal=victim.journal, persist_only=True))
                    self.journal_demotions += 1
                    continue
            self._release(victim)
            self.evictions += 1
            return self.free_slots.pop()

    def reset(self) -> None:
        """Forget every snapshot (sleep/wake released the pool's HBM).
        Counters survive — they are lifetime totals. The sleep boundary
        also runs the journal retention sweep: an idle engine is the
        cheapest moment to reclaim expired / over-budget checkpoint
        files. The sweep runs BEFORE the bookkeeping clears so a
        checkpoint an unshipped persist directive still references is
        protected at the moment of the sweep."""
        if self.journal_dir:
            self._sweep_journal()
        self.by_key.clear()
        self.by_slot.clear()
        self.pending.clear()
        self.pending_persists.clear()
        self._chains.clear()
        self._last_journal = None
        self.free_slots = list(range(self.num_slots - 1, -1, -1))

    # ------------------------------------------------------------------
    def stats(self) -> dict[str, int]:
        return {
            "ssm_state_cache_hits": self.hits,
            "ssm_state_cache_queries": self.queries,
            "ssm_state_cache_evictions": self.evictions,
            "ssm_checkpoints": self.checkpoints,
            "ssm_state_bytes_held": len(self.by_key) * self.bytes_per_slot,
            "ssm_resume_tokens_saved": self.resume_tokens_saved,
            "ssm_restore_corruptions": self.restore_corruptions,
            "ssm_journal_reclaimed": self.journal_files_reclaimed,
            "ssm_journal_demotions": self.journal_demotions,
        }
