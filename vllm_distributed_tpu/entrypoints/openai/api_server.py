"""OpenAI-compatible HTTP server on aiohttp.

Reference: vllm/entrypoints/openai/api_server.py (run_server :1672,
build_async_engine_client :149, route set) and serving_chat/completion.
FastAPI/uvicorn are not in this image; aiohttp provides the same
lifecycle (background AsyncLLM, SSE streaming, graceful shutdown on
engine death — reference: entrypoints/launcher.py).
"""

import asyncio
import contextlib
import dataclasses
import json
import signal
import time
from typing import Optional

from aiohttp import web

from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.engine.core_client import EngineDeadError
from vllm_distributed_tpu.entrypoints.openai import protocol
from vllm_distributed_tpu.entrypoints.openai.admission import (
    AdmissionController, AdmissionRejected)
from vllm_distributed_tpu.entrypoints.openai.protocol import RequestError
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics.stats import span
from vllm_distributed_tpu.outputs import RequestOutputKind
from vllm_distributed_tpu.utils import random_uuid

logger = init_logger(__name__)

ENGINE_KEY = web.AppKey("engine", AsyncLLM)
MODEL_KEY = web.AppKey("model_name", str)
TOOL_PARSER_KEY = web.AppKey("tool_parser", object)
# Served LoRA adapters: name -> checkpoint path (reference: the
# --lora-modules serve flag; requests select one via the "model" field).
LORA_MODULES_KEY = web.AppKey("lora_modules", dict)
# Admission gate (overload shedding + drain mode) for the generation
# endpoints below; health/metrics stay exempt so operators can observe
# an overloaded or draining server.
ADMISSION_KEY = web.AppKey("admission", AdmissionController)

GENERATION_PATHS = frozenset({
    "/v1/completions", "/v1/chat/completions", "/v1/embeddings",
    "/v1/score", "/v1/rerank", "/rerank", "/v1/responses",
    "/v1/audio/transcriptions",
})


def _error_response(e: Exception) -> web.Response:
    if isinstance(e, RequestError):
        return web.json_response(e.json(), status=e.code)
    if isinstance(e, EngineDeadError):
        # 503: the engine is gone/unresponsive — a load balancer should
        # stop routing here; the structured detail says why (and which
        # DP replica, when one died).
        detail = {"message": str(e), "type": "engine_unavailable",
                  "code": 503}
        if getattr(e, "replica", None) is not None:
            detail["replica"] = e.replica
        return web.json_response({"error": detail}, status=503)
    if isinstance(e, ValueError):
        # Admission-time validation (processor rejects) is the client's
        # fault: 400, matching the reference server's error mapping.
        return web.json_response(
            {"error": {"message": str(e), "type": "invalid_request_error",
                       "code": 400}}, status=400)
    return web.json_response(
        {"error": {"message": f"{type(e).__name__}: {e}",
                   "type": "internal_server_error", "code": 500}},
        status=500)


# Monotonic instant (stashed on the request by the admission
# middleware) past which a STREAMING handler must abort its pumps; the
# stream's guard (_stream_guard) enforces it because a fresh 408
# response cannot be written once the SSE stream has started.
DEADLINE_AT_KEY = "vdt_deadline_at"


async def _admission_fields(
        request: web.Request) -> tuple[float, bool, int]:
    """Admission-relevant body fields: the per-request wall-clock
    deadline (the JSON body's ``timeout_s`` overrides
    VDT_REQUEST_TIMEOUT_S; 0 disables), whether the request asked for
    streaming, and its priority class (``priority`` body field, lower =
    more important; > 0 marks best-effort traffic the weighted shed
    gate evicts first)."""
    from vllm_distributed_tpu import envs
    deadline = envs.VDT_REQUEST_TIMEOUT_S
    stream = False
    priority = 0
    if request.content_type == "application/json":
        try:
            # Cheap byte scan first: most requests carry none of these
            # keys, and a full json.loads here would double the parse
            # cost of every body (the handler parses the cached bytes
            # again).
            raw = await request.read()
            if (b'"timeout_s"' in raw or b'"stream"' in raw
                    or b'"priority"' in raw):
                body = await request.json()
                if isinstance(body, dict):
                    stream = bool(body.get("stream"))
                    if body.get("timeout_s") is not None:
                        deadline = float(body["timeout_s"])
                    if body.get("priority") is not None:
                        priority = int(body["priority"])
        except Exception:  # noqa: BLE001 - handler reports bad JSON
            pass
    return max(0.0, deadline), stream, priority


async def _admission_middleware_factory(app, handler):
    """Overload protection for the generation endpoints: bounded
    admission with watermark shedding (429 + Retry-After), drain-mode
    refusal (503 + Retry-After), and a per-request deadline that aborts
    overdue work through the engine's abort path (cancelling the
    handler unwinds every generate() into AsyncLLM.abort)."""

    async def middleware(request: web.Request):
        ctrl = request.app.get(ADMISSION_KEY)
        if (ctrl is None or request.method != "POST"
                or request.path not in GENERATION_PATHS):
            return await handler(request)
        # Read the body BEFORE acquire only when the gate's answer can
        # actually depend on the priority class: a shed storm must stay
        # O(1) per refusal (no body buffering/parsing for requests the
        # gate refuses regardless of class). Admitted requests reuse
        # the parse (or do the one parse) right after.
        fields = None
        if ctrl.class_sensitive():
            fields = await _admission_fields(request)
        try:
            await ctrl.acquire(priority=fields[2] if fields else 0)
        except AdmissionRejected as e:
            kind = ("service_unavailable" if e.status == 503
                    else "overloaded")
            return web.json_response(
                {"error": {"message": str(e), "type": kind,
                           "code": e.status}},
                status=e.status,
                headers={"Retry-After": str(e.retry_after_s)})
        try:
            if fields is None:
                fields = await _admission_fields(request)
            deadline, stream, _ = fields
            if deadline > 0 and stream:
                # A 408 cannot be written once the SSE stream begins:
                # the stream's guard arms its timer for this instant and
                # ends the stream cleanly (abort + [DONE]-less EOF) when
                # it passes.
                request[DEADLINE_AT_KEY] = time.monotonic() + deadline
            elif deadline > 0:
                try:
                    return await asyncio.wait_for(handler(request),
                                                  deadline)
                except asyncio.TimeoutError:
                    logger.warning("request on %s exceeded its %.1fs "
                                   "deadline; aborted", request.path,
                                   deadline)
                    return web.json_response(
                        {"error": {
                            "message": f"request exceeded its "
                                       f"{deadline:.1f}s deadline",
                            "type": "timeout_error", "code": 408}},
                        status=408)
            return await handler(request)
        finally:
            ctrl.release()

    return middleware


async def _auth_middleware_factory(app, handler):
    from vllm_distributed_tpu import envs
    api_key = envs.VDT_API_KEY

    async def middleware(request: web.Request):
        if api_key and request.path.startswith("/v1"):
            auth = request.headers.get("Authorization", "")
            if auth != f"Bearer {api_key}":
                return web.json_response(
                    {"error": {"message": "invalid API key",
                               "type": "authentication_error",
                               "code": 401}}, status=401)
        return await handler(request)

    return middleware


# ---------------------------------------------------------------------------
async def health(request: web.Request) -> web.Response:
    engine = request.app[ENGINE_KEY]
    if engine.errored:
        return web.Response(status=503,
                            text=f"engine dead: {engine.dead_error}")
    # SLO burn-rate verdict: alive-but-degraded stays 200 (the probe
    # must not take a burning server out of rotation — autoscaling
    # reacts via the fleet hint), but the body flags it for operators
    # and external watchdogs.
    burn = getattr(getattr(getattr(engine, "output_processor", None),
                           "stats", None), "burn", None)
    if burn is not None and burn.degraded():
        return web.Response(text="OK (slo degraded)")
    # Correctness sentinel (VDT_CORRECTNESS=1): live replica suspicion
    # flags the same way — serving continues (quarantine is the fleet
    # controller's call), the body warns operators.
    plane = getattr(getattr(engine, "engine_core", None),
                    "correctness", None)
    if plane is not None and plane.suspects():
        return web.Response(text="OK (replica suspect)")
    return web.Response(text="OK")


async def list_models(request: web.Request) -> web.Response:
    return web.json_response({
        "object": "list",
        "data": [protocol.model_card(request.app[MODEL_KEY])] + [
            protocol.model_card(name)
            for name in request.app[LORA_MODULES_KEY]
        ],
    })


async def metrics(request: web.Request) -> web.Response:
    """Prometheus-format scrape of engine stats (reference:
    v1/metrics/prometheus.py mounted at /metrics)."""
    engine = request.app[ENGINE_KEY]
    try:
        stats = await engine.get_stats()
    except Exception:  # noqa: BLE001 - engine busy/dead
        stats = {}
    # One synchronous block on the API thread, timed and named in a
    # capture (a traced benchmark run scrapes twice a second).
    processor = getattr(engine, "output_processor", None)
    with span("vdt.metrics_render",
              processor.stats.metrics_render if processor else None):
        text = _metrics_text(request.app, engine, processor, stats)
    return web.Response(text=text, content_type="text/plain")


def _metrics_text(app: web.Application, engine, processor,
                  stats: dict) -> str:
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    text = render_metrics(stats)
    # Front-end latency histograms (TTFT / ITL / e2e; reference:
    # v1/metrics/loggers.py:143 PrometheusStatLogger families).
    if processor is not None:
        # Follower-process counter snapshots (pid-deduped by the DP
        # aggregator) fold into the front-end's render so /metrics is
        # fleet-exact, not process-local.
        text += processor.stats.render(
            fault_extra=stats.get("fault_injection_counts_remote"))
        # Per-tenant goodput feed into the fleet controller's richer
        # scaling signals (VDT_FLEET_SIGNALS): the front-end's SLO
        # scoring is the only place goodput exists, and the scrape is
        # its natural cadence. getattr-guarded — only the DP client
        # grows observe_goodput. A degraded burn-rate verdict rides the
        # same feed as a scale-up hint.
        feed = getattr(getattr(engine, "engine_core", None),
                       "observe_goodput", None)
        slo = getattr(processor.stats, "slo_by_tenant", None)
        if feed is not None and slo:
            burn = getattr(processor.stats, "burn", None)
            feed({t: good / max(scored, 1)
                  for t, (scored, good) in list(slo.items())},
                 degraded=(burn is not None and burn.degraded()))
    ctrl = app.get(ADMISSION_KEY)
    if ctrl is not None and ctrl.enabled:
        text += (
            "# HELP vdt:admission_queue_depth Admitted, unfinished "
            "generation requests at the API gate\n"
            "# TYPE vdt:admission_queue_depth gauge\n"
            f"vdt:admission_queue_depth {ctrl.depth}\n"
            "# HELP vdt:admission_draining 1 while the server is in "
            "SIGTERM drain mode\n"
            "# TYPE vdt:admission_draining gauge\n"
            f"vdt:admission_draining {int(ctrl.draining)}\n")
        if ctrl.shed_by_class:
            text += (
                "# HELP vdt:requests_shed_by_class_total Requests "
                "refused at the admission gate per priority class "
                "(weighted shedding evicts best_effort first)\n"
                "# TYPE vdt:requests_shed_by_class_total counter\n")
            text += "".join(
                f'vdt:requests_shed_by_class_total{{class="{c}"}} '
                f"{n}\n"
                for c, n in sorted(ctrl.shed_by_class.items()))
    return text


def _profile_dirs(result) -> list[str]:
    # DP fan-out returns one dir per replica; uniproc returns a string.
    return result if isinstance(result, list) else [result]


# ---------------------------------------------------------------------------
# Debug introspection: GET /debug/requests, GET /debug/engine, SIGUSR1.
# GET routes are outside GENERATION_PATHS, so the admission gate never
# sheds them — an overloaded or draining server stays observable.
# ---------------------------------------------------------------------------
async def _core_debug_states(engine: AsyncLLM) -> list[dict]:
    """Per-core get_debug_state dicts (one per DP replica). Bounded and
    failure-tolerant: a dead/busy core degrades the dump to the
    front-end view instead of 500ing the endpoint."""
    try:
        dbg = await asyncio.wait_for(engine.get_debug_state(), timeout=2.0)
    except Exception:  # noqa: BLE001 - core dead/restarting/slow
        return []
    if not isinstance(dbg, dict):
        return []
    if "dp_replicas" in dbg:
        # DP fan-out aggregates dicts; the raw per-replica states
        # survive under dp_replicas.
        return [d for d in dbg["dp_replicas"] if isinstance(d, dict)]
    return [dbg]


def _phase_from_status(status: Optional[str], computed: Optional[int],
                       prompt_tokens: int) -> Optional[str]:
    """Map a scheduler RequestStatus name to a timeline phase name —
    the fallback when the front-end's per-request timeline is behind
    the core (events ride outputs, which stalled requests don't emit)
    or disabled."""
    if status == "WAITING_FOR_REMOTE_KVS":
        return "kv_pull"
    if status == "PREEMPTED":
        return "preempted"
    if status == "WAITING":
        return "queued"
    if status == "RUNNING":
        return ("prefill" if (computed or 0) < prompt_tokens
                else "decode")
    return None


async def _debug_requests_json(engine: AsyncLLM) -> dict:
    from vllm_distributed_tpu.metrics import events as ev
    core_reqs: dict[str, dict] = {}
    core_states = await _core_debug_states(engine)
    # AFTER the (up-to-2s) core RPC: events recorded during the await
    # would otherwise postdate `now` and phases_from_timeline would
    # silently drop the open phase they start.
    now = time.monotonic()
    for i, core in enumerate(core_states):
        for entry in core.get("scheduler", {}).get("requests", ()):
            entry = dict(entry, replica=i if len(core_states) > 1
                         else None)
            core_reqs[entry["request_id"]] = entry
    requests = []
    for rid, state in list(engine.output_processor.request_states.items()):
        # Re-base epoch resets (restarted core = fresh monotonic clock)
        # in arrival order BEFORE sorting — a raw sort interleaves the
        # replayed lifecycle into the pre-death one and both the phase
        # math and current_phase misreport the request.
        timeline = sorted(ev.rebase_epochs(state.timeline),
                          key=lambda e: e[0])
        phases = ev.phases_from_timeline(timeline, now=now)
        times = state.times
        entry = {
            "request_id": rid,
            "phase": ev.current_phase(timeline),
            "age_s": round(now - times.arrival, 3) if times else None,
            "phase_age_s": {p: round(d, 4) for p, d in
                            ev.phase_durations(phases).items()},
            "prompt_tokens": len(state.prompt_token_ids),
            "tokens_emitted": len(state.output_token_ids),
            "num_events": len(timeline),
        }
        core = core_reqs.pop(rid, None)
        if core is not None:
            entry.update({
                "status": core["status"],
                "tokens_computed": core["num_computed_tokens"],
                "kv_blocks": core["kv_blocks"],
                "inflight_refcount": core["inflight_refcount"],
                "num_preemptions": core["num_preemptions"],
                "replica": core.get("replica"),
            })
            if entry["phase"] in (None, "queued"):
                # Core-side events only reach the front-end riding an
                # EngineCoreOutput, which a request stuck mid-prefill
                # or in a KV-pull hold never emits — exactly the
                # requests this endpoint must diagnose. When the
                # timeline lags (or is disabled), derive the phase from
                # the authoritative scheduler status instead.
                entry["phase"] = _phase_from_status(
                    core["status"], core["num_computed_tokens"],
                    entry["prompt_tokens"]) or entry["phase"]
        requests.append(entry)
    # Core-only requests (e.g. a replay the front-end already dropped).
    for rid, core in core_reqs.items():
        requests.append(dict(core, phase=None, core_only=True))
    return {"now_monotonic": now, "num_requests": len(requests),
            "requests": requests}


async def _debug_engine_json(app: web.Application) -> dict:
    from vllm_distributed_tpu.metrics import events as ev
    engine = app[ENGINE_KEY]
    core_states = await _core_debug_states(engine)
    schedulers = []
    for core in core_states:
        sched = dict(core.get("scheduler", {}))
        sched.pop("requests", None)  # per-request detail lives in
        # /debug/requests; keep this endpoint a queue/pipeline summary.
        schedulers.append({
            "scheduler": sched,
            "batch_queue_depth": core.get("batch_queue_depth"),
            "batch_queue_size": core.get("batch_queue_size"),
            "async_scheduling": core.get("async_scheduling"),
            "steps_dispatched": core.get("steps_dispatched"),
            "max_concurrent_batches":
                core.get("max_concurrent_batches"),
        })
    try:
        # include_events=False: the drain is destructive and this
        # wait_for may abandon the RPC — a timed-out debug poll (the
        # wedged-engine case) must not discard the incident window's
        # events. The /metrics scrape is the draining consumer.
        stats = await asyncio.wait_for(
            engine.get_stats(include_events=False), timeout=2.0)
    except Exception:  # noqa: BLE001 - engine busy/dead
        stats = {}
    ctrl = app.get(ADMISSION_KEY)
    admission = None
    if ctrl is not None:
        admission = {
            "enabled": ctrl.enabled,
            "depth": ctrl.depth,
            "max_depth_seen": ctrl.max_depth_seen,
            "high_watermark": ctrl.high_watermark,
            "low_watermark": ctrl.low_watermark,
            "kv_high": ctrl.kv_high,
            "shedding": sorted(ctrl._shedding),
            "draining": ctrl.draining,
        }
    transport = stats.get("transport")
    from vllm_distributed_tpu.parallel import collectives
    qcomm = collectives.merged_qcomm_view(
        (transport or {}).get("qcomm")
        if isinstance(transport, dict) else None,
        stats.get("qcomm_traced_remote"))
    burn = getattr(engine.output_processor.stats, "burn", None)
    slo = None
    if burn is not None:
        slo = {"burn_rates": {w: round(r, 4)
                              for w, r in burn.burn_rates().items()},
               "degraded": burn.degraded(),
               "target": burn.target,
               "threshold": burn.threshold}
    return {
        "supervisor": engine.supervisor_state(),
        "engine_cores": schedulers,
        # Quantized communication plane: per-path bytes saved +
        # raw-precision fallbacks (empty when the plane never fired).
        "qcomm": qcomm,
        "kv_cache_usage": stats.get("kv_cache_usage"),
        "num_running_reqs": stats.get("num_running_reqs"),
        "num_waiting_reqs": stats.get("num_waiting_reqs"),
        "inflight_batches": stats.get("inflight_batches"),
        "admission": admission,
        # SLO burn-rate watchdog (None when no SLO target is set).
        "slo_burn": slo,
        # Correctness sentinel summary (None while VDT_CORRECTNESS=0;
        # the full view lives at /debug/correctness).
        "correctness": stats.get("correctness"),
        # Front-end ledger merged with the core-side events absorbed
        # from /metrics scrapes (the draining stats consumer).
        "recent_events": ev.merge_event_lists(
            engine.output_processor.events.snapshot(100),
            engine.output_processor.core_events.snapshot(100)),
    }


async def _debug_kv_cache_json(engine: AsyncLLM) -> dict:
    """Live block-pool state per engine core: pool occupancy
    (free/used/tombstoned/cached-free pages), fragmentation, the
    windowed prefix-cache hit rate, preemption causes, and each
    request's page footprint — the paged-KV view of the same scheduler
    snapshot /debug/requests reads."""
    cores = []
    for i, core in enumerate(await _core_debug_states(engine)):
        sched = core.get("scheduler", {})
        cores.append({
            "replica": i,
            "kv_cache": sched.get("kv_cache"),
            "kv_cache_usage": sched.get("kv_cache_usage"),
            "requests": [
                {"request_id": r.get("request_id"),
                 "status": r.get("status"),
                 "kv_blocks": r.get("kv_blocks"),
                 "num_computed_tokens": r.get("num_computed_tokens"),
                 "tknp_rank": r.get("tknp_rank")}
                for r in sched.get("requests", ())
            ],
            "waiting_for_remote_kvs":
                sched.get("waiting_for_remote_kvs"),
            "cancelled_remote_kv": sched.get("cancelled_remote_kv"),
        })
    return {"now_monotonic": time.monotonic(), "engine_cores": cores}


async def debug_requests(request: web.Request) -> web.Response:
    """Live per-request state: current phase, per-phase ages from the
    lifecycle timeline, progress counters, KV footprint."""
    return web.json_response(
        await _debug_requests_json(request.app[ENGINE_KEY]))


async def debug_kv_cache(request: web.Request) -> web.Response:
    """Live paged-KV introspection: block-pool occupancy,
    fragmentation, windowed prefix-cache hit rate, preemption causes,
    per-request page footprints. Admission-exempt GET — a server
    shedding for KV pressure stays diagnosable."""
    return web.json_response(
        await _debug_kv_cache_json(request.app[ENGINE_KEY]))


async def debug_engine(request: web.Request) -> web.Response:
    """Live engine state: scheduler queues, batch pipeline, KV usage,
    restart-supervisor state, admission watermarks."""
    return web.json_response(await _debug_engine_json(request.app))


async def debug_trace(request: web.Request) -> web.Response:
    """One stitched causal trace as Chrome/Perfetto trace-event JSON
    (``?request_id=`` or ``?trace_id=``; ``?format=raw`` for the
    un-rendered event list, no params lists known trace ids). Requires
    VDT_TRACE_PLANE=1; the stats poll below drains any core-ring
    events not yet fed to the assembler so a trace requested right
    after a request finishes is already complete."""
    from vllm_distributed_tpu import trace_plane
    engine = request.app[ENGINE_KEY]
    assembler = getattr(getattr(engine, "output_processor", None),
                        "assembler", None)
    if assembler is None:
        return web.json_response(
            {"error": "trace plane disabled (set VDT_TRACE_PLANE=1)"},
            status=404)
    try:
        await asyncio.wait_for(engine.get_stats(), timeout=2.0)
    except Exception:  # noqa: BLE001 - engine busy/dead; serve cached
        pass
    rid = request.query.get("request_id")
    tid = request.query.get("trace_id")
    if not rid and not tid:
        return web.json_response({"trace_ids": assembler.trace_ids()})
    trace = assembler.get(request_id=rid, trace_id=tid)
    if trace is None:
        return web.json_response(
            {"error": f"no trace for {rid or tid!r}"}, status=404)
    if request.query.get("format") == "raw":
        return web.json_response(trace)
    return web.json_response(trace_plane.perfetto(trace))


async def debug_correctness(request: web.Request) -> web.Response:
    """Correctness-sentinel introspection (admission-exempt, like every
    /debug endpoint — registered outside the admission gate's guarded
    routes): canary probe/divergence counters, per-replica suspicion,
    the numerics snapshots and the quarantine tally. Requires
    VDT_CORRECTNESS=1."""
    engine = request.app[ENGINE_KEY]
    plane = getattr(getattr(engine, "engine_core", None),
                    "correctness", None)
    if plane is None:
        return web.json_response(
            {"error": "correctness sentinel disabled "
                      "(set VDT_CORRECTNESS=1)"},
            status=404)
    try:
        # include_events=False: the destructive event drain belongs to
        # the /metrics scrape (the debug_engine discipline).
        stats = await asyncio.wait_for(
            engine.get_stats(include_events=False), timeout=2.0)
    except Exception:  # noqa: BLE001 - engine busy/dead; the plane's
        # own counters below still serve
        stats = {}
    return web.json_response({
        "correctness": stats.get("correctness") or plane.get_stats(),
        "numerics": stats.get("numerics"),
        "fleet_quarantines": (stats.get("fleet") or {}).get(
            "quarantines"),
    })


def _thread_stacks() -> str:
    import sys
    import threading
    import traceback
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    chunks = []
    for ident, frame in frames.items():
        chunks.append(f"--- thread {names.get(ident, '?')} ({ident}) ---\n"
                      + "".join(traceback.format_stack(frame)))
    return "\n".join(chunks)


async def _dump_debug_to_log(app: web.Application) -> None:
    """SIGUSR1 forensics: the same JSON the /debug endpoints serve, plus
    every thread's stack, to the log — for the hung-server case where
    HTTP may no longer answer (a hung engine core or blocked handler;
    the loop itself stays alive since engine work runs off-loop).
    Never raises, never blocks serving. The signal callback logs the
    thread stacks synchronously BEFORE scheduling this coroutine, so
    the await-free part of the dump lands even when the engine calls
    in here stall."""
    try:
        engine_state = await _debug_engine_json(app)
        request_state = await _debug_requests_json(app[ENGINE_KEY])
        kv_state = await _debug_kv_cache_json(app[ENGINE_KEY])
        logger.warning(
            "SIGUSR1 debug dump:\n/debug/engine: %s\n/debug/requests: "
            "%s\n/debug/kv_cache: %s\nthread stacks:\n%s",
            json.dumps(engine_state, default=str),
            json.dumps(request_state, default=str),
            json.dumps(kv_state, default=str),
            _thread_stacks())
    except Exception:  # noqa: BLE001 - forensics must not kill serving
        logger.exception("SIGUSR1 debug dump failed")


async def embeddings(request: web.Request) -> web.Response:
    """OpenAI /v1/embeddings over the pooling path (reference:
    serving_embedding.py)."""
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        inputs = body.get("input")
        if inputs is None:
            raise RequestError("embeddings need 'input'")
        if isinstance(inputs, str) or (isinstance(inputs, list) and inputs
                                       and isinstance(inputs[0], int)):
            inputs = [inputs]
        results = await asyncio.gather(
            *(engine.encode(item) for item in inputs))
        data = [{
            "object": "embedding",
            "index": i,
            "embedding": out.embedding,
        } for i, out in enumerate(results)]
        prompt_tokens = sum(out.num_prompt_tokens for out in results)
        return web.json_response({
            "object": "list",
            "data": data,
            "model": body.get("model", model),
            "usage": protocol.usage(prompt_tokens, 0),
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))
    except EngineDeadError as e:
        return _error_response(RequestError(str(e), code=500))


def _score_pairs(engine, queries, documents):
    """Build (token_ids, pooling) per pair for cross-encoder scoring."""
    from vllm_distributed_tpu.entrypoints.score_utils import (
        build_score_pair)
    return [build_score_pair(engine.tokenizer, q, d)
            for q, d in zip(queries, documents)]


async def _pair_scores(engine, queries, documents):
    """(scores, prompt_tokens) for query x document pairs.

    Cross-encoder checkpoints run each pair through the classification
    head; embedding models fall back to cosine similarity over the
    encode path — the same two modes LLM.score serves (reference:
    serving_score.py supports both over HTTP)."""
    import math
    if engine.processor.is_cross_encoder:
        pairs = _score_pairs(engine, queries, documents)
        results = await asyncio.gather(
            *(engine.encode(ids, pooling_params=pooling)
              for ids, pooling in pairs))
        return ([out.embedding[0] for out in results],
                sum(out.num_prompt_tokens for out in results))
    # Embedding model: encode each distinct text once, score by cosine.
    unique: dict = {}
    for text in list(queries) + list(documents):
        unique.setdefault(text, None)
    texts = list(unique)
    results = await asyncio.gather(
        *(engine.encode(t) for t in texts))
    by_text = {t: out.embedding for t, out in zip(texts, results)}

    def cos(a, b):
        dot = sum(x * y for x, y in zip(a, b))
        return dot / (math.sqrt(sum(x * x for x in a)) *
                      math.sqrt(sum(x * x for x in b)) + 1e-12)

    return ([cos(by_text[q], by_text[d])
             for q, d in zip(queries, documents)],
            sum(out.num_prompt_tokens for out in results))


async def score(request: web.Request) -> web.Response:
    """/v1/score: cross-encoder relevance of text_1 x text_2 pairs
    (reference: serving_score.py)."""
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        t1, t2 = body.get("text_1"), body.get("text_2")
        if t1 is None or t2 is None:
            raise RequestError("score needs 'text_1' and 'text_2'")
        if isinstance(t1, str):
            t1 = [t1]
        if isinstance(t2, str):
            t2 = [t2]
        if len(t1) == 1 and len(t2) > 1:
            t1 = t1 * len(t2)
        elif len(t2) == 1 and len(t1) > 1:
            t2 = t2 * len(t1)
        if len(t1) != len(t2):
            raise RequestError(
                f"text_1 x text_2 must match (or broadcast); got "
                f"{len(t1)} x {len(t2)}")
        scores, prompt_tokens = await _pair_scores(engine, t1, t2)
        data = [{
            "object": "score",
            "index": i,
            "score": s,
        } for i, s in enumerate(scores)]
        return web.json_response({
            "object": "list",
            "data": data,
            "model": body.get("model", model),
            "usage": protocol.usage(prompt_tokens, 0),
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))
    except EngineDeadError as e:
        return _error_response(RequestError(str(e), code=500))


async def rerank(request: web.Request) -> web.Response:
    """/v1/rerank (and /rerank): order documents by cross-encoder
    relevance to a query (reference: serving_score.py rerank API)."""
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        query = body.get("query")
        documents = body.get("documents")
        if isinstance(documents, str):
            documents = [documents]
        if query is None or not documents:
            raise RequestError("rerank needs 'query' and 'documents'")
        scores, prompt_tokens = await _pair_scores(
            engine, [query] * len(documents), documents)
        ranked = sorted(((s, i) for i, s in enumerate(scores)),
                        reverse=True)
        top_n = body.get("top_n", len(documents))
        data = [{
            "index": i,
            "relevance_score": s,
            "document": {"text": documents[i]},
        } for s, i in ranked[:top_n]]
        return web.json_response({
            "model": body.get("model", model),
            "results": data,
            "usage": protocol.usage(prompt_tokens, 0),
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))
    except EngineDeadError as e:
        return _error_response(RequestError(str(e), code=500))


async def tokenize(request: web.Request) -> web.Response:
    """/tokenize (reference: the tokenize route of api_server.py:453):
    text (or chat messages) -> token ids."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        tokenizer = engine.tokenizer
        if tokenizer is None:
            raise RequestError("server has no tokenizer", code=400)
        if body.get("messages") is not None:
            # Same templating path chat generation uses (incl. the
            # template-less fallback); special tokens default OFF for
            # chat — the template already embeds them (reference:
            # the tokenize route's chat defaults, api_server.py:453).
            prompt, _mm = _chat_prompt(engine, body["messages"])
            add_special = bool(body.get("add_special_tokens", False))
        else:
            prompt = body.get("prompt")
            if prompt is None:
                raise RequestError("tokenize needs 'prompt' or "
                                   "'messages'")
            add_special = bool(body.get("add_special_tokens", True))
        if isinstance(prompt, list):
            # Templated chat paths return token ids directly.
            ids = [int(t) for t in prompt]
        else:
            ids = tokenizer.encode(prompt,
                                   add_special_tokens=add_special)
        return web.json_response({
            "tokens": ids,
            "count": len(ids),
            "max_model_len":
                engine.config.scheduler_config.max_model_len,
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))


async def detokenize(request: web.Request) -> web.Response:
    """/detokenize (reference: api_server.py:491): token ids -> text."""
    engine = request.app[ENGINE_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        tokenizer = engine.tokenizer
        if tokenizer is None:
            raise RequestError("server has no tokenizer", code=400)
        tokens = body.get("tokens")
        if not isinstance(tokens, list):
            raise RequestError("detokenize needs 'tokens' as a list "
                               "of token ids")
        text = tokenizer.decode([int(t) for t in tokens])
        return web.json_response({"prompt": text})
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))


async def responses(request: web.Request) -> web.Response:
    """/v1/responses minimal surface (reference: serving_responses.py):
    'input' (string or message list) + optional 'instructions' run as a
    chat completion; the reply is wrapped in the Responses output item
    shape. Background mode / response stores are not implemented."""
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        if body.get("background"):
            raise RequestError(
                "background responses are not supported")
        if body.get("stream"):
            raise RequestError(
                "streaming responses are not supported; set "
                "stream=false")
        inp = body.get("input")
        if inp is None:
            raise RequestError("responses need 'input'")
        messages = ([{"role": "user", "content": inp}]
                    if isinstance(inp, str) else [
                        ({"role": "user", "content": m}
                         if isinstance(m, str) else m)
                        for m in inp
                    ])
        # Normalize Responses-typed content parts onto the chat part
        # types _chat_prompt knows (input_text -> text, input_image ->
        # image_url).
        for m in messages:
            if not isinstance(m, dict):
                raise RequestError(
                    "input items must be strings or message objects")
            parts = m.get("content")
            if isinstance(parts, list):
                m["content"] = [
                    ({**p, "type": "text"}
                     if p.get("type") == "input_text" else
                     {"type": "image_url",
                      "image_url": {"url": p.get("image_url")}}
                     if p.get("type") == "input_image" else p)
                    for p in parts
                ]
        if body.get("instructions"):
            messages.insert(
                0, {"role": "system", "content": body["instructions"]})
        max_len = engine.config.scheduler_config.max_model_len
        chat_body = dict(body, messages=messages)
        chat_body.pop("input", None)
        if "max_output_tokens" in body:
            chat_body["max_tokens"] = body["max_output_tokens"]
        params = protocol.sampling_params_from_request(chat_body,
                                                       max_len)
        prompt, mm = _chat_prompt(engine, messages)
        if mm is not None:
            # Image parts: encode pixels once, like chat_completions.
            mm = {"image_embeds": engine.processor._encode_pixels(
                mm["pixel_values"])}
        lora = _resolve_lora(request.app, body)
        rid = protocol.completion_id().replace("cmpl", "resp")
        priority, tenant = _priority_tenant(body)
        final = await _drain(engine.generate(prompt, params,
                                             request_id=rid,
                                             priority=priority,
                                             tenant=tenant,
                                             lora_request=lora,
                                             multi_modal_data=mm,
                                             output_kind=RequestOutputKind.FINAL_ONLY))
        text = final.outputs[0].text
        return web.json_response({
            "id": rid,
            "object": "response",
            "created_at": int(time.time()),  # wallclock-ok
            "model": body.get("model", model),
            "status": "completed",
            "output": [{
                "type": "message",
                "id": f"msg-{rid}",
                "role": "assistant",
                "status": "completed",
                "content": [{"type": "output_text", "text": text,
                             "annotations": []}],
            }],
            "output_text": text,
            "usage": {
                "input_tokens": len(final.prompt_token_ids),
                "output_tokens": len(final.outputs[0].token_ids),
                "total_tokens": (len(final.prompt_token_ids) +
                                 len(final.outputs[0].token_ids)),
            },
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))
    except EngineDeadError as e:
        return _error_response(RequestError(str(e), code=500))


def _decode_wav(data: bytes):
    """PCM WAV bytes -> (mono float32 waveform, sample_rate) using only
    the stdlib (no audio libs in the image)."""
    import io
    import wave

    import numpy as np
    try:
        with wave.open(io.BytesIO(data)) as w:
            rate = w.getframerate()
            n = w.getnframes()
            width = w.getsampwidth()
            channels = w.getnchannels()
            raw = w.readframes(n)
    except (wave.Error, EOFError) as e:
        raise RequestError(f"invalid WAV payload: {e}") from e
    if width == 2:
        x = np.frombuffer(raw, np.int16).astype(np.float32) / 32768.0
    elif width == 4:
        x = np.frombuffer(raw, np.int32).astype(np.float32) / 2**31
    elif width == 1:
        x = (np.frombuffer(raw, np.uint8).astype(np.float32) - 128) / 128
    else:
        raise RequestError(f"unsupported WAV sample width {width}")
    if channels > 1:
        x = x.reshape(-1, channels).mean(axis=1)
    return x, rate


def _transcription_prompt(engine) -> list[int]:
    """Decoder prompt for transcription: decoder_start + any forced ids
    from the generation config (<|lang|><|transcribe|><|notimestamps|>;
    reference: the prompt assembly of serving_transcription.py)."""
    hf = engine.config.model_config.maybe_load_hf_config()
    ids = [int(getattr(hf, "decoder_start_token_id", 0) or 0)]
    forced = getattr(hf, "forced_decoder_ids", None)
    if forced:
        ids.extend(int(t) for _, t in forced)
    return ids


async def transcriptions(request: web.Request) -> web.Response:
    """/v1/audio/transcriptions (reference: serving_transcription.py):
    multipart form with a WAV `file`, or JSON {"audio": <base64 wav>}.
    Requires a Whisper-family model."""
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        if request.content_type.startswith("multipart/"):
            reader = await request.multipart()
            data = None
            async for part in reader:
                if part.name == "file":
                    data = await part.read()
                else:
                    await part.read()
            if data is None:
                raise RequestError("multipart needs a 'file' part")
        else:
            import base64
            body = await request.json()
            if body.get("audio") is None:
                raise RequestError(
                    "transcriptions need a multipart 'file' or JSON "
                    "'audio' (base64 WAV)")
            data = base64.b64decode(body["audio"])
        wav, rate = _decode_wav(data)
        if rate != 16000:
            raise RequestError(
                f"audio must be 16 kHz PCM WAV (got {rate} Hz); "
                f"resample client-side")
        from vllm_distributed_tpu.sampling_params import SamplingParams
        params = SamplingParams(
            temperature=0.0,
            max_tokens=engine.config.scheduler_config.max_model_len // 2)
        prompt = _transcription_prompt(engine)
        final = await _drain(engine.generate(
            prompt, params,
            request_id=protocol.completion_id().replace("cmpl", "trsc"),
            multi_modal_data={"audio": wav},
            output_kind=RequestOutputKind.FINAL_ONLY))
        return web.json_response({
            "text": final.outputs[0].text,
            "model": model,
        })
    except (RequestError, ValueError) as e:
        return _error_response(e if isinstance(e, RequestError)
                               else RequestError(str(e)))
    except EngineDeadError as e:
        return _error_response(RequestError(str(e), code=500))


async def start_profile(request: web.Request) -> web.Response:
    """Begin a device trace (reference: api_server /start_profile).
    Hardened (ISSUE 14): the engine core auto-names each capture's
    trace dir, bounds it with a VDT_PROFILE_MAX_S force-stop deadline,
    and rejects a second concurrent capture — surfaced as 409 here so
    a retrying client can tell "busy" from "broken"."""
    try:
        dirs = _profile_dirs(
            await request.app[ENGINE_KEY].profile("start"))
    except ValueError as e:
        return web.json_response(
            {"error": {"message": str(e), "type": "capture_conflict",
                       "code": 409}}, status=409)
    return web.json_response({"status": "profiling", "dir": dirs[0],
                              "dirs": dirs})


async def stop_profile(request: web.Request) -> web.Response:
    """End the capture; the answer names the trace directories."""
    try:
        dirs = _profile_dirs(
            await request.app[ENGINE_KEY].profile("stop"))
    except ValueError as e:
        return web.json_response(
            {"error": {"message": str(e), "type": "capture_conflict",
                       "code": 409}}, status=409)
    return web.json_response({"status": "stopped", "dir": dirs[0],
                              "dirs": dirs})


# ---------------------------------------------------------------------------
def _gen_prompts(body: dict) -> list:
    """Completions `prompt` can be str | [str] | [int] | [[int]]."""
    prompt = body.get("prompt")
    if prompt is None:
        raise RequestError("`prompt` is required")
    if isinstance(prompt, str):
        return [prompt]
    if isinstance(prompt, list):
        if not prompt:
            raise RequestError("`prompt` must not be empty")
        if isinstance(prompt[0], int):
            return [prompt]
        return list(prompt)
    raise RequestError("`prompt` must be a string or list")


def _priority_tenant(body: dict) -> tuple[int, Optional[str]]:
    """Scheduling class + tenant identity off an OpenAI request body:
    ``priority`` (int, lower = more important, > 0 = best-effort) and
    ``tenant`` (falling back to the standard OpenAI ``user`` field).
    Both ride EngineCoreRequest: priority drives the scheduler's
    priority policy and the admission gate's weighted shedding, tenant
    labels introspection."""
    try:
        priority = int(body.get("priority", 0) or 0)
    except (TypeError, ValueError) as e:
        raise RequestError(f"invalid priority: {e}") from e
    tenant = body.get("tenant", body.get("user"))
    if tenant is not None:
        tenant = str(tenant)
    return priority, tenant


async def completions(request: web.Request) -> web.StreamResponse:
    received_at = time.monotonic()  # vdt:frontend_admit_seconds starts
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        prompts = _gen_prompts(body)
        n = int(body.get("n", 1) or 1)
        max_len = engine.config.scheduler_config.max_model_len
        params = protocol.sampling_params_from_request(body, max_len)
        stream = bool(body.get("stream", False))
        echo_texts = None
        if body.get("echo"):
            if stream:
                raise RequestError(
                    "echo with stream is not supported")
            if params.logprobs is not None:
                # Echoed logprobs need the prompt positions scored
                # (reference: the echo path of serving_completion.py).
                params.prompt_logprobs = params.logprobs
            # Token-id prompts echo their detokenized text so the text
            # stays aligned with the echoed logprobs arrays.
            tokenizer = engine.tokenizer
            echo_texts = [
                p if isinstance(p, str) else
                (tokenizer.decode(p) if tokenizer is not None else
                 " ".join(str(t) for t in p))
                for p in prompts
            ]
        cid = protocol.completion_id()
        created = int(time.time())  # wallclock-ok
        priority, tenant = _priority_tenant(body)

        # Fan out: one engine request per (prompt, sample) pair; choice
        # index follows OpenAI semantics (prompt-major, then n). Seeded
        # requests offset the seed per child so samples differ.
        lora = _resolve_lora(request.app, body)
        # Encoder-decoder text (BART): the source document rides an
        # extra body field and encodes once at admission (reference:
        # the encoder_prompt of the reference's encoder-decoder
        # serving).
        enc_mm = None
        if body.get("encoder_text") is not None:
            enc_mm = {"encoder_text": str(body["encoder_text"])}
        elif body.get("encoder_input_ids") is not None:
            ids = body["encoder_input_ids"]
            if (not isinstance(ids, list)
                    or not all(isinstance(t, int) for t in ids)):
                raise RequestError(
                    "encoder_input_ids must be a list of token ids")
            enc_mm = {"encoder_input_ids": ids}
        gens = []
        for pi, prompt in enumerate(prompts):
            for s in range(n):
                idx = pi * n + s
                child = params
                if n > 1 and params.seed is not None:
                    import copy as _copy
                    child = _copy.copy(params)
                    child.seed = params.seed + s
                gens.append((idx, engine.generate(
                    prompt, child, request_id=f"{cid}-{idx}",
                    priority=priority, tenant=tenant,
                    lora_request=lora, multi_modal_data=enc_mm,
                    received_at=received_at,
                    output_kind=_output_kind(stream))))

        if stream:
            return await _stream_completions(request, cid, created, model,
                                             gens)
        # Drain all generators CONCURRENTLY: engine.generate is an async
        # generator, so nothing is submitted until iteration starts —
        # sequential draining would serialize the batch.
        finals = await asyncio.gather(*(_drain(gen) for _, gen in gens))
        choices = [None] * len(gens)
        prompt_tokens = 0
        completion_tokens = 0
        for (idx, _), final in zip(gens, finals):
            prompt_tokens += len(final.prompt_token_ids) if idx % n == 0 \
                else 0
            completion_tokens += len(final.outputs[0].token_ids)
            choices[idx] = _completion_choice(
                idx, final, body,
                echo_text=(echo_texts[idx // n]
                           if echo_texts is not None else None))
        return web.json_response({
            "id": cid,
            "object": "text_completion",
            "created": created,
            "model": model,
            "choices": choices,
            "usage": protocol.usage(prompt_tokens, completion_tokens),
        })
    except (RequestError, EngineDeadError, ValueError) as e:
        return _error_response(e)


def _output_kind(stream: bool) -> RequestOutputKind:
    """What a handler asks generate() for, from its own request: the
    streaming handlers write deltas, every other (``_drain``) reads the
    last output alone."""
    return (RequestOutputKind.DELTA if stream
            else RequestOutputKind.FINAL_ONLY)


async def _drain(gen):
    final = None
    async for out in gen:
        final = out
    return final


def _completion_choice(idx: int, out, body: dict,
                       echo_text: str = None) -> dict:
    comp = out.outputs[0]
    echo = bool(body.get("echo"))
    prefix = (echo_text if echo_text is not None else
              (out.prompt or "")) if echo else ""
    choice = {
        "index": idx,
        "text": prefix + comp.text,
        "finish_reason": comp.finish_reason,
    }
    if body.get("logprobs") is not None and comp.logprobs:
        token_ids = list(comp.token_ids)
        token_lps = [lp.get(tok) if lp else None
                     for tok, lp in zip(comp.token_ids, comp.logprobs)]
        top = [{str(k): v for k, v in lp.items()} for lp in comp.logprobs]
        if echo and out.prompt_logprobs is not None:
            # Prompt positions lead (first entry None, OpenAI echo
            # semantics); ids follow the same str() convention as the
            # completion tokens.
            p_ids = list(out.prompt_token_ids)
            p_lps = [None] + [
                (d.get(t) if d else None)
                for t, d in zip(p_ids[1:], out.prompt_logprobs[1:])
            ]
            p_top = [({str(k): v for k, v in d.items()} if d else None)
                     for d in out.prompt_logprobs]
            token_ids = p_ids + token_ids
            token_lps = p_lps + token_lps
            top = p_top + top
        choice["logprobs"] = {
            # The sampled token's own logprob (keyed lookup — the map may
            # also carry top-k alternatives with higher probability).
            "token_logprobs": token_lps,
            "tokens": [str(t) for t in token_ids],
            "top_logprobs": top,
        }
    return choice


def _client_disconnected(request: web.Request) -> bool:
    """A dropped client closes the transport; the stream loops poll this
    so generation stops instead of running to completion unwatched
    (reference: the is_disconnected() checks of serving_completion)."""
    transport = request.transport
    return transport is None or transport.is_closing()


def _check_stream_alive(request: web.Request) -> None:
    """Stream guard: raises on client disconnect or on an expired
    per-request deadline (both end the stream through the engine's
    abort path)."""
    if _client_disconnected(request):
        raise ConnectionResetError("client disconnected")
    deadline_at = request.get(DEADLINE_AT_KEY)
    if deadline_at is not None and time.monotonic() > deadline_at:
        raise asyncio.TimeoutError("stream exceeded its deadline")


@contextlib.contextmanager
def _stream_guard(request: web.Request):
    """The liveness guard of one streamed response: ONE timer, re-armed
    once a second (sooner where the deadline falls sooner), runs
    ``_check_stream_alive`` whether or not any output arrives — a
    request stalled in the engine (queued, remote-KV hold) must still
    honor disconnects and deadlines instead of keeping its slot until
    the next token. When the guard trips it cancels the handler's task,
    which unwinds every generate() it waits on into the abort path, and
    the block raises what the guard found in the cancellation's place.
    The pumps inside await the engine's streams directly: no Task, no
    timer handle a token."""
    loop = asyncio.get_running_loop()
    task = asyncio.current_task()
    deadline_at = request.get(DEADLINE_AT_KEY)
    tripped: list[Exception] = []
    handle = None

    def arm() -> None:
        nonlocal handle
        delay = 1.0
        if deadline_at is not None:
            delay = min(delay, max(deadline_at - time.monotonic(), 0.0) + 1e-3)
        handle = loop.call_later(delay, check)

    def check() -> None:
        try:
            _check_stream_alive(request)
        except (ConnectionResetError, asyncio.TimeoutError) as e:
            tripped.append(e)
            task.cancel()
        else:
            arm()

    arm()
    try:
        yield
    except asyncio.CancelledError:
        if not tripped or task.uncancel() > 0:
            raise  # cancelled by someone else (as well)
        raise tripped[0] from None
    finally:
        handle.cancel()


async def _abort_stream(request: web.Request, cid: str,
                        gens: list) -> None:
    """Abort every child request of a dropped stream through the
    engine's abort path (frees their KV pages and scheduler slots)."""
    engine = request.app[ENGINE_KEY]
    for idx, _gen in gens:
        try:
            await engine.abort(f"{cid}-{idx}")
        except Exception:  # noqa: BLE001 - engine dead/racing shutdown
            pass


async def _stream_sse(request, cid, obj, created, model, gens, choice,
                      opening=None) -> web.StreamResponse:
    """One SSE response over the delta streams ``gens`` (generate() with
    RequestOutputKind.DELTA): a chunk for every output that carries text
    or a finish, its one choice built by ``choice(idx, delta, finish)``,
    after ``opening(idx)``'s chunk where the dialect has one."""
    resp = web.StreamResponse(headers={
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
    })
    await resp.prepare(request)
    # The chunk's constant fields, rendered once a stream: json.dumps of
    # the whole chunk puts exactly these bytes in front of its choices.
    head = json.dumps({"id": cid, "object": obj, "created": created,
                       "model": model})
    head = f'data: {head[:-1]}, "choices": '

    async def send(one_choice: dict) -> None:
        await resp.write(
            f"{head}{json.dumps([one_choice])}}}\n\n".encode())

    async def pump(idx, gen):
        if opening is not None:
            await send(opening(idx))
        async for out in gen:
            comp = out.outputs[0]
            if comp.text or comp.finish_reason:
                await send(choice(idx, comp.text, comp.finish_reason))

    try:
        with _stream_guard(request):
            await asyncio.gather(*(pump(idx, gen) for idx, gen in gens))
        await resp.write(b"data: [DONE]\n\n")
    except (EngineDeadError, ConnectionResetError,
            asyncio.TimeoutError) as e:
        logger.warning("stream aborted: %s", e)
        await _abort_stream(request, cid, gens)
    await resp.write_eof()
    return resp


def _stream_completions(request, cid, created, model, gens):
    return _stream_sse(
        request, cid, "text_completion", created, model, gens,
        lambda idx, delta, finish: {
            "index": idx, "text": delta, "finish_reason": finish})


# ---------------------------------------------------------------------------
def _chat_prompt(engine: AsyncLLM, messages: list):
    """-> (prompt, multi_modal_data | None). OpenAI structured content
    parts flatten to text with the model's image placeholder token
    standing in for each image (reference: entrypoints/chat_utils.py);
    data-URL images preprocess through the checkpoint's CLIP recipe."""
    tokenizer = engine.tokenizer
    if tokenizer is None:
        raise RequestError("chat requires a tokenizer for this model")
    hf = engine.config.model_config.maybe_load_hf_config()
    try:
        from vllm_distributed_tpu.models.registry import \
            resolve_architecture
        qwen_vl = getattr(resolve_architecture(hf), "VISION_STYLE",
                          None) == "qwen2_vl"
    except Exception:  # noqa: BLE001 - toy configs
        qwen_vl = False
    image_urls: list[str] = []
    video_frames: list[list[str]] = []
    flat: list[dict] = []
    for m in messages:
        content = m.get("content")
        if isinstance(content, list):
            from vllm_distributed_tpu.multimodal.image_processing import \
                image_token_string
            tok = image_token_string(tokenizer, hf)
            vtok = None
            if qwen_vl:
                from vllm_distributed_tpu.multimodal.qwen2_vl_processing \
                    import media_token_strings
                tok, vtok = media_token_strings(tokenizer, hf)
            parts: list[str] = []
            for part in content:
                ptype = part.get("type")
                if ptype == "text":
                    parts.append(part.get("text", ""))
                elif ptype == "image_url":
                    if tok is None:
                        raise RequestError(
                            "this model does not accept image inputs")
                    image_urls.append(
                        (part.get("image_url") or {}).get("url", ""))
                    parts.append(tok)
                elif ptype == "video_url":
                    # Videos arrive as FRAME LISTS of data-URL images
                    # (what the reference's video loader produces after
                    # container decode; multimodal/video.py).
                    if vtok is None:
                        raise RequestError(
                            "this model does not accept video inputs")
                    url = (part.get("video_url") or {}).get("url")
                    frames = url if isinstance(url, list) else [url]
                    video_frames.append([f or "" for f in frames])
                    parts.append(vtok)
                else:
                    raise RequestError(
                        f"unsupported content part type {ptype!r}")
            flat.append(dict(m, content="".join(parts)))
        else:
            flat.append(m)
    mm = None
    if qwen_vl and (image_urls or video_frames):
        from vllm_distributed_tpu.multimodal.qwen2_vl_processing import \
            preprocess_chat_media
        try:
            mm = preprocess_chat_media(image_urls, video_frames, hf)
        except ValueError as e:
            raise RequestError(str(e)) from e
    elif video_frames:
        raise RequestError("this model does not accept video inputs")
    elif image_urls:
        from vllm_distributed_tpu.multimodal.image_processing import \
            preprocess_data_urls
        try:
            pixels = preprocess_data_urls(
                image_urls, engine.config.model_config.model,
                engine.config.model_config.maybe_load_hf_config())
        except ValueError as e:
            raise RequestError(str(e)) from e
        mm = {"pixel_values": pixels}
    if getattr(tokenizer, "chat_template", None):
        prompt = tokenizer.apply_chat_template(
            flat, tokenize=True, add_generation_prompt=True)
    else:
        # Template-less tiny/test models: plain role-prefixed transcript.
        prompt = "".join(
            f"{m.get('role', 'user')}: {m.get('content', '')}\n"
            for m in flat) + "assistant:"
    return prompt, mm


async def chat_completions(request: web.Request) -> web.StreamResponse:
    received_at = time.monotonic()  # vdt:frontend_admit_seconds starts
    engine = request.app[ENGINE_KEY]
    model = request.app[MODEL_KEY]
    try:
        body = await request.json()
    except json.JSONDecodeError as e:
        return _error_response(RequestError(f"invalid JSON: {e}"))
    try:
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            raise RequestError("`messages` must be a non-empty list")
        prompt, mm = _chat_prompt(engine, messages)
        n = int(body.get("n", 1) or 1)
        if mm is not None and "image_grid_thw" not in mm \
                and "video_grid_thw" not in mm:
            # llava path: encode pixels ONCE; the n samples (and the
            # scheduler) reuse the embeddings instead of n vision-tower
            # passes. (Qwen2-VL grid payloads encode at admission.)
            mm = {"image_embeds": engine.processor._encode_pixels(
                mm["pixel_values"])}
        max_len = engine.config.scheduler_config.max_model_len
        params = protocol.sampling_params_from_request(body, max_len)
        stream = bool(body.get("stream", False))
        cid = protocol.chat_id()
        created = int(time.time())  # wallclock-ok
        lora = _resolve_lora(request.app, body)
        forced_tool = protocol.apply_tool_constraints(body, params)
        if stream and forced_tool is not None:
            raise RequestError(
                "streaming with a forced tool_choice is not supported "
                "yet; set stream=false")
        priority, tenant = _priority_tenant(body)
        gens = [(i, engine.generate(prompt, params,
                                    request_id=f"{cid}-{i}",
                                    priority=priority, tenant=tenant,
                                    lora_request=lora,
                                    multi_modal_data=mm,
                                    received_at=received_at,
                                    output_kind=_output_kind(stream)))
                for i in range(n)]
        if stream:
            return await _stream_chat(request, cid, created, model, gens)
        finals = await asyncio.gather(*(_drain(gen) for _, gen in gens))
        choices = [None] * n
        prompt_tokens = 0
        completion_tokens = 0
        for (idx, _), final in zip(gens, finals):
            if idx == 0:
                prompt_tokens = len(final.prompt_token_ids)
            completion_tokens += len(final.outputs[0].token_ids)
            text = final.outputs[0].text
            parse_tools = (None if body.get("tool_choice") == "none"
                           else body.get("tools"))
            tool_calls = None
            content = text
            dialect = request.app[TOOL_PARSER_KEY]
            if (forced_tool is None and parse_tools
                    and dialect is not None):
                # Model-specific dialect parser (reference:
                # tool_parsers/): splits content from the dialect's
                # tool-call wrapping.
                content, calls = dialect.parse(text)
                if calls:
                    tool_calls = protocol.wrap_tool_calls(calls)
            else:
                tool_calls = protocol.parse_tool_calls(
                    text, forced_tool, parse_tools)
            if tool_calls is not None:
                message = {"role": "assistant",
                           "content": content or None,
                           "tool_calls": tool_calls}
                finish = "tool_calls"
            else:
                message = {"role": "assistant", "content": text}
                finish = final.outputs[0].finish_reason
            choices[idx] = {
                "index": idx,
                "message": message,
                "finish_reason": finish,
            }
        return web.json_response({
            "id": cid,
            "object": "chat.completion",
            "created": created,
            "model": model,
            "choices": choices,
            "usage": protocol.usage(prompt_tokens, completion_tokens),
        })
    except (RequestError, EngineDeadError, ValueError) as e:
        return _error_response(e)


def _stream_chat(request, cid, created, model, gens):
    return _stream_sse(
        request, cid, "chat.completion.chunk", created, model, gens,
        lambda idx, delta, finish: {
            "index": idx, "delta": {"content": delta} if delta else {},
            "finish_reason": finish},
        opening=lambda idx: {
            "index": idx, "delta": {"role": "assistant", "content": ""},
            "finish_reason": None})


# ---------------------------------------------------------------------------
def _resolve_lora(app: web.Application, body: dict) -> Optional[dict]:
    """A request whose ``model`` names a served adapter gets that
    adapter (reference: lora-modules model aliasing)."""
    name = body.get("model")
    path = app[LORA_MODULES_KEY].get(name)
    if path is None:
        return None
    return {"name": name, "path": path}


def build_app(engine: AsyncLLM, model_name: str,
              lora_modules: Optional[dict] = None,
              tool_call_parser: Optional[str] = None) -> web.Application:
    app = web.Application(middlewares=[_auth_middleware_factory,
                                       _admission_middleware_factory])
    app[ENGINE_KEY] = engine
    app[MODEL_KEY] = model_name
    app[LORA_MODULES_KEY] = dict(lora_modules or {})
    app[ADMISSION_KEY] = AdmissionController.from_envs(engine)
    if tool_call_parser:
        from vllm_distributed_tpu.entrypoints.openai.tool_parsers import \
            get_tool_parser
        app[TOOL_PARSER_KEY] = get_tool_parser(tool_call_parser)
    else:
        app[TOOL_PARSER_KEY] = None
    app.router.add_get("/health", health)
    app.router.add_get("/v1/models", list_models)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/requests", debug_requests)
    app.router.add_get("/debug/engine", debug_engine)
    app.router.add_get("/debug/kv_cache", debug_kv_cache)
    app.router.add_get("/debug/trace", debug_trace)
    app.router.add_get("/debug/correctness", debug_correctness)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_post("/v1/score", score)
    app.router.add_post("/tokenize", tokenize)
    app.router.add_post("/detokenize", detokenize)
    app.router.add_post("/v1/responses", responses)
    app.router.add_post("/v1/audio/transcriptions", transcriptions)
    app.router.add_post("/v1/rerank", rerank)
    app.router.add_post("/rerank", rerank)
    app.router.add_post("/start_profile", start_profile)
    app.router.add_post("/stop_profile", stop_profile)
    return app


async def drain_and_stop(controller: AdmissionController,
                         stop_event: asyncio.Event,
                         timeout_s: Optional[float] = None) -> float:
    """SIGTERM path: stop admitting, let in-flight requests finish (up
    to the drain deadline), then stop the server. Returns the drain
    duration (also recorded as vdt:drain_duration_seconds)."""
    from vllm_distributed_tpu import envs
    if timeout_s is None:
        timeout_s = envs.VDT_DRAIN_TIMEOUT_S
    controller.begin_drain()
    duration = await controller.wait_drained(timeout_s)
    logger.warning("graceful drain finished in %.2fs; stopping server",
                   duration)
    stop_event.set()
    return duration


async def serve(engine: AsyncLLM, model_name: str, host: str,
                port: int, ready_event=None,
                stop_event: Optional[asyncio.Event] = None,
                lora_modules: Optional[dict] = None,
                tool_call_parser: Optional[str] = None) -> None:
    """Run until stop_event (or SIGTERM drain); graceful engine
    shutdown on exit (reference: entrypoints/launcher.py serve_http)."""
    app = build_app(engine, model_name, lora_modules,
                    tool_call_parser=tool_call_parser)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    logger.info("serving on http://%s:%d", host, port)
    if stop_event is None:
        stop_event = asyncio.Event()
    loop = asyncio.get_running_loop()
    drain_task = None

    def _on_sigterm() -> None:
        nonlocal drain_task
        if drain_task is None:
            drain_task = asyncio.ensure_future(
                drain_and_stop(app[ADMISSION_KEY], stop_event))

    def _on_sigusr1() -> None:
        # Hung-server forensics. Thread stacks first and SYNCHRONOUSLY
        # — they need no awaits, so they land even when the engine is
        # wedged and the async state dump below would stall on it.
        try:
            logger.warning("SIGUSR1 thread stacks:\n%s",
                           _thread_stacks())
        except Exception:  # noqa: BLE001 - forensics must not kill
            logger.exception("SIGUSR1 stack dump failed")
        asyncio.ensure_future(_dump_debug_to_log(app))

    try:
        loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
        loop.add_signal_handler(signal.SIGUSR1, _on_sigusr1)
    except (NotImplementedError, ValueError, RuntimeError):
        # Non-main-thread loops (tests) and platforms without signal
        # support: drain stays reachable via drain_and_stop directly,
        # the debug dump via _dump_debug_to_log.
        pass
    if ready_event is not None:
        ready_event.set()
    try:
        await stop_event.wait()
    finally:
        if drain_task is not None:
            drain_task.cancel()
        try:
            loop.remove_signal_handler(signal.SIGTERM)
            loop.remove_signal_handler(signal.SIGUSR1)
        except (NotImplementedError, ValueError, RuntimeError):
            pass
        await runner.cleanup()
        engine.shutdown()


def run_server(engine_args, host: str = "0.0.0.0", port: int = 8000,
               lora_modules: Optional[dict] = None,
               tool_call_parser: Optional[str] = None) -> None:
    """Blocking entry used by the CLI (reference: api_server.py:1672).
    The server runs the engine core's pipelined step unless told
    otherwise (``EngineArgs.async_scheduling`` None: this entry decides,
    and ``EngineConfig`` still falls back where the step cannot run)."""
    if engine_args.async_scheduling is None:
        engine_args = dataclasses.replace(engine_args,
                                          async_scheduling=True)
    engine = AsyncLLM.from_engine_args(engine_args)
    asyncio.run(serve(engine, engine_args.model, host, port,
                      lora_modules=lora_modules,
                      tool_call_parser=tool_call_parser))
