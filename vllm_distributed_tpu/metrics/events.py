"""Request-lifecycle event timeline.

The reference records one flat span per finished request
(vllm/tracing.py SpanAttributes) — enough for dashboards, useless for
answering "where did this request's 4 seconds go" across queue ->
KV-pull -> prefill -> preemption -> decode -> replay. This module is the
shared recording substrate for that question:

* ``EventRecorder`` — a bounded, lock-light ring buffer of
  ``(monotonic_ts, request_id, event, detail)`` tuples. Each component
  (scheduler, engine core, output processor) owns its own recorder, so
  the hot paths never contend on a global lock; buffers are drained on
  ``get_stats`` and ship over the existing stats RPC (DP-merged like the
  step-gap histograms).
* per-request event lists — the scheduler accumulates a request's
  lifecycle transitions on the ``Request`` itself and attaches them to
  the next ``EngineCoreOutput`` for that request, so the front-end's
  ``OutputProcessor`` can stitch them (plus its own arrival/first-token/
  replay events) into one parent span with child phase spans.
* ``phases_from_timeline`` — turns a request's merged event timeline
  into phase intervals (queue, kv_pull, prefill, decode, stalls).

Recording is on by default and costs one list-append per lifecycle
TRANSITION (not per token/step); ``VDT_REQUEST_TIMELINE=0`` disables it
globally (the bench harness runs both legs to bound the overhead).
"""

import threading
import time
from typing import Any, Optional

# Lifecycle event names (one vocabulary across all components).
RECEIVED = "received"  # top of the HTTP handler (stamp of the way in)
ARRIVED = "arrived"  # front-end accepted the request
SUBMITTED = "submitted"  # put on the engine core's transport
QUEUED = "queued"  # entered the scheduler's waiting queue
SCHEDULED = "scheduled"  # first tokens granted (prefill start)
PREFILL_CHUNK = "prefill_chunk"  # chunked-prefill progress
FIRST_TOKEN = "first_token"  # first output token reached the front-end
KV_PULL_WAIT = "kv_pull_wait"  # entered WAITING_FOR_REMOTE_KVS
KV_PULL_DONE = "kv_pull_done"  # async pull landed; back in the queue
KV_PULL_RETRY = "kv_pull_retry"  # failed pull re-staged
KV_PULL_TIMEOUT = "kv_pull_timeout"  # watchdog swept the hold
KV_PULL_LOCAL = "kv_pull_local_fallback"  # degraded to local recompute
PREEMPTED = "preempted"
RESUMED = "resumed"
SPEC_GRANT = "spec_grant"  # entered async run-ahead mode (first grant)
BATCH_DISPATCH = "batch_dispatch"  # engine-core batch in flight (rid="")
BATCH_RETIRE = "batch_retire"  # engine-core batch retired (rid="")
ENGINE_DEATH = "engine_death"  # core died with this request in flight
JOURNAL_REPLAY = "journal_replay"  # replayed as a continuation prefill
SHED = "shed"  # refused at the admission gate (rid="")
FINISHED = "finished"
ABORTED = "aborted"
# Elastic-fleet control-loop actions (engine/fleet.py; all rid="").
FLEET_SCALE_OUT = "fleet_scale_out"  # replica entered rotation
FLEET_SCALE_IN = "fleet_scale_in"  # replica drained and retired
FLEET_RESPLIT = "fleet_resplit"  # replica converted between pools
FLEET_WEDGE_CYCLE = "fleet_wedge_cycle"  # stuck replica force-cycled
FLEET_FREEZE = "fleet_freeze"  # actuation skipped (stale/budget/...)
# HA control plane (engine/control_plane.py; all rid="").
FLEET_LEADER_TAKEOVER = "fleet_leader_takeover"  # lease acquired
FLEET_FENCED = "fleet_fenced"  # stale-epoch actuation rejected
FLEET_JOURNAL_REPLAY = "fleet_journal_replay"  # successor resumed a
# half-done drain from the actuation journal
FLEET_CONTROLLER_DOWN = "fleet_controller_down"  # controller died
# (fleet.controller_die drill) — standbys take over within the TTL
# Trace-plane additions (front-end + scheduler; PR 19).
ROUTER_PICK = "router_pick"  # front-end placement decision (replica/pool)
DISAGG_HANDOFF = "disagg_handoff"  # prefill producer re-admitted the
# request to its decode home (the consumer's kv_pull span links back)
KV_TIER_PROMOTE = "kv_tier_promote"  # spill-tier pages scattered back
KV_TIER_DEMOTE = "kv_tier_demote"  # evicted pages demoted to a tier
# (page-level batch; rid="")
# Correctness sentinel (correctness_plane.py; both rid="" — the
# detail map carries the replica and the divergence cause).
CANARY_DIVERGENCE = "canary_divergence"  # canary probe strayed from
# the reference journal / the cross-replica vote
FLEET_QUARANTINE = "fleet_quarantine"  # suspect replica force-cycled
# on the sentinel's quarantine hint (VDT_FLEET_SIGNALS)

# Canonical event registry: every name recordable via
# ``EventRecorder.record`` with a one-line operator-facing doc.
# scripts/lint_events.py enforces that each module-level event constant
# above appears here AND as a backticked row in the README event table
# (the lint_metrics contract, applied to trace span types) — an event
# name that drifts undocumented fails tier-1.
EVENT_REGISTRY: dict[str, str] = {
    RECEIVED: "HTTP handler entered (vdt:frontend_admit_seconds starts)",
    ARRIVED: "front-end accepted the request",
    SUBMITTED: "put on the engine core's transport (admit ends)",
    QUEUED: "entered the scheduler's waiting queue",
    SCHEDULED: "first tokens granted (prefill start)",
    PREFILL_CHUNK: "chunked-prefill progress",
    FIRST_TOKEN: "first output token reached the front-end",
    KV_PULL_WAIT: "entered WAITING_FOR_REMOTE_KVS",
    KV_PULL_DONE: "async pull landed; back in the queue",
    KV_PULL_RETRY: "failed pull re-staged",
    KV_PULL_TIMEOUT: "watchdog swept the hold",
    KV_PULL_LOCAL: "degraded to local recompute",
    PREEMPTED: "pages reclaimed; request parked",
    RESUMED: "preempted request granted again",
    SPEC_GRANT: "entered async run-ahead mode (first grant)",
    BATCH_DISPATCH: "engine-core batch in flight (rid=\"\")",
    BATCH_RETIRE: "engine-core batch retired (rid=\"\")",
    ENGINE_DEATH: "core died with this request in flight",
    JOURNAL_REPLAY: "replayed as a continuation prefill",
    SHED: "refused at the admission gate (rid=\"\")",
    FINISHED: "request completed",
    ABORTED: "request aborted",
    FLEET_SCALE_OUT: "replica entered rotation",
    FLEET_SCALE_IN: "replica drained and retired",
    FLEET_RESPLIT: "replica converted between pools",
    FLEET_WEDGE_CYCLE: "stuck replica force-cycled",
    FLEET_FREEZE: "actuation skipped (stale/budget/...)",
    FLEET_LEADER_TAKEOVER: "lease acquired by this controller",
    FLEET_FENCED: "stale-epoch actuation rejected",
    FLEET_JOURNAL_REPLAY: "successor resumed a journaled action",
    FLEET_CONTROLLER_DOWN: "controller died; standbys take over",
    ROUTER_PICK: "front-end placement decision (replica/pool)",
    DISAGG_HANDOFF: "prefill producer handed the request to decode",
    KV_TIER_PROMOTE: "spill-tier pages scattered back to HBM",
    KV_TIER_DEMOTE: "evicted pages demoted to a spill tier (rid=\"\")",
    CANARY_DIVERGENCE: "canary probe strayed from reference/vote",
    FLEET_QUARANTINE: "suspect replica force-cycled on sentinel hint",
}


def timeline_enabled() -> bool:
    """Read once per recorder (NOT per event): the envs registry
    re-evaluates os.getenv on every attribute access. The trace plane
    rides the event stream, so VDT_TRACE_PLANE=1 implies recording even
    if the operator disabled the plain timeline."""
    from vllm_distributed_tpu import envs
    return envs.VDT_REQUEST_TIMELINE or envs.VDT_TRACE_PLANE


def trace_plane_enabled() -> bool:
    """Read once per component at construction (same discipline as
    timeline_enabled): the distributed trace plane's master switch."""
    from vllm_distributed_tpu import envs
    return envs.VDT_TRACE_PLANE


# Reserved detail keys the trace plane merges into event details.
# Compact on purpose: every stamped event carries them over the stats
# wire. "tr" = trace id (hex), "rep" = DP replica index the event was
# drained from (stamped by the front-end aggregator, pid of the
# Perfetto export), "co" = monotonic clock offset already applied.
TRACE_KEY = "tr"
REPLICA_KEY = "rep"


def stamp_trace(detail: Optional[dict],
                trace_ctx: Optional[dict]) -> Optional[dict]:
    """Merge the compact trace id into an event detail dict. Returns
    ``detail`` untouched (possibly None) when there is no trace context
    — the stamped path allocates a fresh dict so callers may share
    detail literals."""
    if not trace_ctx:
        return detail
    tid = trace_ctx.get("trace_id")
    if not tid:
        return detail
    d = dict(detail) if detail else {}
    d[TRACE_KEY] = tid
    return d


class EventRecorder:
    """Bounded ring buffer of lifecycle events for one component.

    ``record`` is the hot call: one tuple append under a lock (appends
    are rare — lifecycle transitions, not tokens). ``drain`` hands the
    buffered events to the stats RPC and clears; ``snapshot`` reads
    without clearing (debug endpoints). Overflow drops the OLDEST
    events — forensics care about the recent past.
    """

    def __init__(self, maxlen: int = 4096,
                 enabled: Optional[bool] = None) -> None:
        from collections import deque
        self.maxlen = maxlen
        self.enabled = (timeline_enabled()
                        if enabled is None else enabled)
        self._lock = threading.Lock()
        # deque(maxlen) drops the oldest in O(1); a plain list would
        # memmove the whole ring per append once full (which an
        # unpolled recorder permanently is).
        self._events: "deque[tuple]" = deque(maxlen=maxlen)
        self.num_dropped = 0

    def record(self, request_id: str, event: str,
               detail: Optional[dict] = None,
               ts: Optional[float] = None) -> None:
        if not self.enabled:
            return
        entry = (time.monotonic() if ts is None else ts,
                 request_id, event, detail)
        with self._lock:
            if len(self._events) == self.maxlen:
                self.num_dropped += 1
            self._events.append(entry)

    def drain(self) -> list[list]:
        """Take (and clear) the buffered events in wire shape:
        ``[ts, request_id, event, detail]`` lists (msgpack-friendly)."""
        with self._lock:
            events = list(self._events)
            self._events.clear()
        return [[ts, rid, ev, detail] for ts, rid, ev, detail in events]

    def absorb(self, events: list) -> None:
        """Retain wire-shape events drained from ANOTHER recorder (the
        core-side rings ship over the stats RPC; the front end keeps
        them here so /debug/engine's recent-events view covers the
        scheduler/engine stream, not just front-end events)."""
        if not events:
            return
        with self._lock:
            for e in events:
                if len(self._events) == self.maxlen:
                    self.num_dropped += 1
                self._events.append(tuple(e))

    def snapshot(self, limit: int = 256) -> list[list]:
        """Most recent events without clearing (debug endpoints)."""
        with self._lock:
            events = list(self._events)[-limit:]
        return [[ts, rid, ev, detail] for ts, rid, ev, detail in events]

    def __len__(self) -> int:
        return len(self._events)


# ---------------------------------------------------------------------------
# Phase stitching: merged event timeline -> phase intervals
# ---------------------------------------------------------------------------

# Backward jump (seconds) in an arrival-ordered timeline past which the
# clock is treated as a fresh monotonic epoch (restarted engine core /
# another host) rather than cross-recorder jitter. Jitter between the
# front-end and core recorders is sub-second; an epoch reset jumps back
# by the old core's whole uptime.
EPOCH_RESET_S = 30.0


def rebase_epochs(timeline: list,
                  threshold: float = EPOCH_RESET_S) -> list:
    """Re-base timestamps across monotonic-epoch resets.

    ``timeline`` is one request's events in ARRIVAL order (``(ts, ...)``
    tuples or wire-shape lists). Events absorbed from a restarted engine
    core carry a fresh monotonic epoch: their timestamps jump backward
    by the dead core's uptime, so sorting by ts misorders the lifecycle
    and phase math goes negative. A backward jump beyond ``threshold``
    is treated as an epoch reset: the offending event and everything
    after it in the same epoch shift forward to continue just past the
    latest re-based timestamp. Sane timelines pass through unchanged
    (identity for jitter under the threshold); multiple resets (restart
    storms) accumulate. Element shape (tuple vs list) is preserved.
    """
    if not timeline:
        return timeline
    out: list = []
    offset = 0.0
    high: Optional[float] = None
    for entry in timeline:
        ts = entry[0]
        if high is not None and ts + offset < high - threshold:
            offset = high - ts + 1e-6
        rebased = ts + offset
        if high is None or rebased > high:
            high = rebased
        rest = entry[1:]
        out.append([rebased, *rest] if isinstance(entry, list)
                   else (rebased, *rest))
    return out


def _first(timeline: list[tuple], *names: str) -> Optional[tuple]:
    for entry in timeline:
        if entry[1] in names:
            return entry
    return None


def phases_from_timeline(timeline: list[tuple],
                         now: Optional[float] = None) -> list[dict]:
    """Phase intervals from one request's merged timeline of
    ``(ts, event, detail)`` tuples (sorted by ts by the caller):

    * ``queue``   — arrival to the first grant (or kv-pull hold),
    * ``kv_pull`` — each WAITING_FOR_REMOTE_KVS hold,
    * ``prefill`` — first grant to the first output token,
    * ``decode``  — first output token to finish,
    * ``stall``   — each preemption hold and each engine-death ->
      journal-replay window.

    Open-ended phases (request still live) end at ``now``. Returns
    ``[{"phase", "start", "end"}...]`` in monotonic-clock seconds.
    """
    now = time.monotonic() if now is None else now
    phases: list[dict] = []

    def add(phase: str, start: float, end: float) -> None:
        if end >= start:
            phases.append({"phase": phase, "start": start, "end": end})

    arrived = _first(timeline, ARRIVED, QUEUED)
    granted = _first(timeline, SCHEDULED)
    first_tok = _first(timeline, FIRST_TOKEN)
    done = _first(timeline, FINISHED, ABORTED)
    end_ts = done[0] if done else now

    if arrived:
        queue_end = min(
            (e[0] for e in (granted, _first(timeline, KV_PULL_WAIT))
             if e is not None), default=end_ts)
        add("queue", arrived[0], queue_end)

    # KV-pull holds (possibly several across retries).
    hold_start: Optional[float] = None
    for ts, ev, _detail in timeline:
        if ev == KV_PULL_WAIT and hold_start is None:
            hold_start = ts
        elif hold_start is not None and ev in (
                KV_PULL_DONE, KV_PULL_TIMEOUT, KV_PULL_LOCAL,
                KV_PULL_RETRY, FINISHED, ABORTED):
            add("kv_pull", hold_start, ts)
            hold_start = None
    if hold_start is not None:
        add("kv_pull", hold_start, end_ts)

    if granted:
        add("prefill", granted[0], first_tok[0] if first_tok else end_ts)
    if first_tok:
        add("decode", first_tok[0], end_ts)

    # Stalls: preemption holds and engine-death -> replay windows.
    stall_start: Optional[float] = None
    for ts, ev, _detail in timeline:
        if ev in (PREEMPTED, ENGINE_DEATH) and stall_start is None:
            stall_start = ts
        elif stall_start is not None and ev in (RESUMED, JOURNAL_REPLAY,
                                                SCHEDULED, FINISHED,
                                                ABORTED):
            add("stall", stall_start, ts)
            stall_start = None
    if stall_start is not None:
        add("stall", stall_start, end_ts)
    return phases


def phase_durations(phases: list[dict]) -> dict[str, float]:
    """Total seconds per phase name (stall windows sum)."""
    out: dict[str, float] = {}
    for p in phases:
        out[p["phase"]] = (out.get(p["phase"], 0.0)
                           + p["end"] - p["start"])
    return out


def current_phase(timeline: list[tuple]) -> Optional[str]:
    """Best-effort current phase of a LIVE request (debug endpoints):
    the last lifecycle transition wins. Grant events after the first
    output token map to "decode", not "prefill" — a preempted-then-
    resumed (or replayed) decode request is still decoding from the
    operator's viewpoint, matching phases_from_timeline's accounting
    (the hold itself is a stall; decode runs first_token -> finish).
    An EMPTY timeline (VDT_REQUEST_TIMELINE=0) returns None — "no
    timeline" must not read as a server full of queued requests."""
    if not timeline:
        return None
    phase = "queued"
    decoding = False
    for _ts, ev, _detail in timeline:
        if ev in (ARRIVED, QUEUED):
            phase = "queued"
        elif ev == KV_PULL_WAIT:
            phase = "kv_pull"
        elif ev in (SCHEDULED, PREFILL_CHUNK, KV_PULL_DONE, RESUMED,
                    JOURNAL_REPLAY):
            phase = "decode" if decoding else "prefill"
        elif ev == FIRST_TOKEN:
            decoding = True
            phase = "decode"
        elif ev == PREEMPTED:
            phase = "preempted"
        elif ev == ENGINE_DEATH:
            phase = "replaying"
        elif ev == FINISHED:
            phase = "finished"
        elif ev == ABORTED:
            phase = "aborted"
    return phase


def merge_event_lists(*lists: Any) -> list[list]:
    """Merge drained event lists (e.g. per-DP-replica) by timestamp."""
    merged: list[list] = []
    for events in lists:
        if events:
            merged.extend(events)
    merged.sort(key=lambda e: e[0])
    return merged
