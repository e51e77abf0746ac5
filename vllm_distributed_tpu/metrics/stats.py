"""Front-end request latency stats: TTFT / ITL / e2e histograms.

Reference: vllm/v1/metrics/stats.py (IterationStats computing TTFT and
inter-token latency from arrival/first-token timestamps) + loggers.py:50
(LoggingStatLogger's periodic throughput lines) and :143
(PrometheusStatLogger histogram families). Rendered without the
prometheus_client registry for the same reason as metrics/prometheus.py:
the global registry complicates multi-engine tests.
"""

import time
from bisect import bisect_left
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

# Bucket boundaries (seconds) mirroring the reference's latency families.
TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25,
                0.5, 0.75, 1.0, 2.5, 5.0, 7.5, 10.0, 20.0, 40.0, 80.0)
ITL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1,
               0.25, 0.5, 1.0, 2.5, 5.0)
E2E_BUCKETS = (0.3, 0.5, 0.8, 1.0, 1.5, 2.0, 2.5, 5.0, 10.0, 15.0, 30.0,
               40.0, 50.0, 60.0, 120.0, 240.0, 480.0, 960.0)
# Engine-core host gap (wait_model return -> next dispatch): the device
# idle window async scheduling exists to hide; sub-millisecond when a
# batch was already waiting, tens of milliseconds when the host
# schedules synchronously between steps.
HOST_GAP_BUCKETS = (0.0002, 0.0005, 0.001, 0.002, 0.005, 0.01, 0.02,
                    0.05, 0.1, 0.25, 1.0)
# Engine-core step phases (schedule / prepare_inputs / dispatch /
# device wait / update_from_output): microseconds for the host control
# plane up to seconds for a first-compile device wait. Edges a factor
# 2 to 2.5 apart: for families read by their MEANS (a quantile read off
# them is a bucket's middle wherever a window's values share a bucket;
# what is read by quantile takes DEVICE_STEP_BUCKETS or
# QUEUE_LAG_BUCKETS).
STEP_PHASE_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                      0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                      5.0, 10.0)
# One step on the device, and a fetch of its results (families read by
# quantile): 1 ms to 1 s, neighbours at most a factor 1.25 apart under
# 200 ms and a tenth or less where the cells' steps lie (8 to 200 ms),
# so that an interpolated median is within a few percent of the value.
DEVICE_STEP_BUCKETS = tuple(ms / 1000 for ms in (
    1, 1.25, 1.5, 1.75, 2, 2.5, 3, 3.5, 4, 4.5, 5, 5.5, 6, 6.5, 7, 7.5, 8,
    8.5, 9, 9.5, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 22, 24, 26,
    28, 30, 32, 34, 36, 38, 40, 44, 48, 52, 56, 60, 65, 70, 75, 80, 85,
    90, 95, 100, 105, 110, 115, 120, 125, 130, 140, 150, 160, 170, 180,
    190, 200, 250, 320, 400, 500, 640, 800, 1000))
# A yes or no of one step, observed as 0 or 1: the mean is the share.
FLAG_BUCKETS = (0, 1)
# Waits read by quantile (a request's wait for its first grant, a step's
# outputs on their way to the request queues): 0.25 ms to 2 s, at most a
# factor 2 apart below 100 ms so that a median is resolved.
QUEUE_LAG_BUCKETS = (0.00025, 0.0005, 0.001, 0.002, 0.004, 0.008, 0.016,
                     0.032, 0.064, 0.1, 0.25, 0.5, 1.0, 2.0)
# Tokens of one step (granted, or attended to by the step's queries).
STEP_TOKEN_BUCKETS = tuple(2**i for i in range(21))
# Query x key pairs of one step in one attention layer: a decode token
# at a short context (256) to a token budget of queries over a model's
# longest context (4**16 = 4.3e9).
ATTN_PAIR_BUCKETS = tuple(4**i for i in range(4, 17))
# Requests of one step (a count of the scheduled ones that share some
# property): 0 and 1 have a bucket each, then powers of two to the
# largest running batch.
STEP_REQUEST_BUCKETS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
# Host-to-device transfers of one step: each count up to 8 has a bucket
# of its own (the default path issues 3), then the per-array hand-over's
# range and the optional fields'.
STEP_TRANSFER_BUCKETS = (1, 2, 3, 4, 5, 6, 7, 8, 12, 16, 20, 24, 32, 48,
                         64)


def render_histogram_lines(name: str, help_text: str, buckets, counts,
                           total: float, count: int, label: str = "",
                           header: bool = True) -> list[str]:
    """Prometheus exposition lines for one histogram family: cumulative
    ``_bucket`` series (``counts`` carries one trailing +Inf slot),
    ``_sum`` and ``_count``. Single source of truth for the shape —
    shared by live Histogram objects and the serialized-dict stats
    entries engines ship over the stats RPC. ``label`` (e.g.
    ``phase="dispatch"``) renders one labeled series of a family;
    pass ``header=False`` for every series after the first."""
    lines = ([f"# HELP {name} {help_text}", f"# TYPE {name} histogram"]
             if header else [])
    lbl = f"{label}," if label else ""
    suffix = f"{{{label}}}" if label else ""
    cumulative = 0
    for b, c in zip(buckets, counts):
        cumulative += int(c)
        lines.append(f'{name}_bucket{{{lbl}le="{b}"}} {cumulative}')
    if counts:
        cumulative += int(counts[-1])
    lines.append(f'{name}_bucket{{{lbl}le="+Inf"}} {cumulative}')
    lines.append(f"{name}_sum{suffix} {total}")
    lines.append(f"{name}_count{suffix} {count}")
    return lines


class Histogram:
    """Fixed-bucket histogram in Prometheus exposition shape."""

    def __init__(self, buckets: tuple[float, ...]) -> None:
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)  # last = +Inf
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self.count += 1
        self.total += value
        # bisect_left finds the first bucket with value <= bound (runs
        # per token on the ITL path; a linear scan of ~20 bounds costs
        # more than the observation it records).
        self.counts[bisect_left(self.buckets, value)] += 1

    def render(self, name: str, help_text: str) -> list[str]:
        return render_histogram_lines(name, help_text, self.buckets,
                                      self.counts, self.total, self.count)

    def to_dict(self) -> dict:
        """Serialized stats-RPC form; render_histogram_lines over this
        dict is byte-identical to render() on the live object."""
        return {"buckets": list(self.buckets), "counts": list(self.counts),
                "sum": self.total, "count": self.count}


class span:
    """Times a block into ``hist`` (``time.perf_counter()``) and opens a
    ``jax.profiler.TraceAnnotation`` named ``name`` around it, so that the
    same block shows in a profiler capture, on the device trace's clock,
    with ``attrs`` as the event's stats. The annotation costs about half
    a microsecond while no capture runs, so it is never switched off.
    ``start``, ``end`` and ``seconds`` are readable once the block has
    been left."""

    __slots__ = ("hist", "start", "end", "_annotation")

    def __init__(self, name: str, hist: Optional[Histogram] = None,
                 **attrs) -> None:
        self.hist = hist
        self._annotation = TraceAnnotation(name, **attrs)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self.hist is not None:
            self.hist.observe(self.end - self.start)
        self._annotation.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _PhaseSpan(span):
    """A span of a ``StepPhases`` timeline. Inside a step of the one
    timeline (``sync``: every step but pipeline parallelism's) a phase
    starts where the timeline's last phase ended (``mark``), so that the
    phases leave no hole between them. The thread's CPU clock is read
    only at the ends that an account needs (``StepPhases.cpu_hists`` and
    the fetch, for ``host_stall``): a read costs microseconds on a
    sandboxed host."""

    __slots__ = ("_phases", "_phase", "_chained", "_timed", "_cpu_start")

    def __init__(self, phases: "StepPhases", name: str,
                 phase: Optional[str], attrs: dict) -> None:
        super().__init__(name, phases.hists[phase] if phase else None,
                         step=phases.step, **attrs)
        self._phases = phases
        self._phase = phase

    def __enter__(self) -> "span":
        super().__enter__()
        phase, phases = self._phase, self._phases
        if phase is None:
            return self
        self._chained = phases.sync and not phases.idle
        self._timed = phase in phases.cpu_hists
        if self._chained:
            if phase == "device_wait":
                phases.fetch_begins(self.start)
            self.start = phases.mark
            if self._timed:
                self._cpu_start = phases.cpu_at_mark()
        elif self._timed:
            self._cpu_start = time.thread_time()
        return self

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        phase, phases = self._phase, self._phases
        if phase is None:
            return
        phases.mark = self.end
        fetched = self._chained and phase == "device_wait"
        if self._timed or fetched:
            cpu = time.thread_time()
            phases.cpu_mark, phases.cpu_mark_at = cpu, self.end
            if self._timed:
                spent = cpu - self._cpu_start
                phases.cpu_hists[phase].observe(spent)
                if self._chained:
                    phases.cpu_named += spent
        if fetched:
            phases.fetch_done(self.end - self.start)


class _Flight:
    """One forward between its issue and its fetch, as the host saw it
    (``StepPhases``, the device's timeline): the array its fetch will
    wait for, its ``shape``, the issue's stamp where the issue found
    the chip idle (else ``after``, the forward it queued behind), and
    ``end``, the fetch's return where that fetch truly waited."""

    __slots__ = ("array", "step", "shape", "start", "after", "ready",
                 "end")

    def __init__(self, step: int, shape: str, start: Optional[float],
                 after: Optional["_Flight"]) -> None:
        self.array = None
        self.step, self.shape = step, shape
        self.start, self.after = start, after
        self.ready = False
        self.end: Optional[float] = None


class StepPhases:
    """Where the time of the engine-core thread goes, step by step, and
    what the chip did meanwhile as far as the host can know: one object
    per engine core, shared with its model runner.

    On the one timeline (``sync``: the synchronous step and the
    pipelined step, engine/core.py) the phases ``loop, schedule,
    update_states, prepare_inputs, launch, device_wait, finish, update``
    partition the thread's time between two step starts: each starts at
    ``mark``, the end of the one before it, so they neither overlap nor
    leave holes, and their sums add up to the sum of ``period``. A
    pipelined step runs the same phases in the same order, the first
    five for the batch it launches and the last three for the batch it
    retires, the one launched a step earlier: one ``vdt_step`` and one
    period a forward either way. Under pipeline parallelism (``sync``
    false) every phase times its own block, ``dispatch`` and ``wait``
    contain the runner's phases, and there is no period.

    The thread's CPU time (``time.thread_time()``) is partitioned in
    three: ``cpu_hists`` ``prepare_inputs``, ``launch`` and ``other``
    sum to ``step_cpu``, ``other`` being ``step_cpu`` less the two by
    subtraction, once a period. The clock is read five times a step (the
    step's start, both ends of ``prepare_inputs``, the end of ``launch``
    where the fetch starts, the fetch's return) and not at every phase's
    end: a read costs 6 us on the chip's host. Under pipeline
    parallelism ``prepare_inputs``, ``launch``, ``dispatch`` and
    ``wait`` read it around their own blocks and nothing sums.
    ``host_stall`` is the time this thread wanted to run and did not:
    wall time less CPU time from one fetch's return to the next fetch's
    start (every phase but ``device_wait``). Near zero, the host time of
    a step is this thread's own work; several milliseconds, another
    thread held the GIL, the core was taken, or a launch blocked inside
    the runtime. Read the CPU accounts by their means:
    ``time.thread_time()`` may advance in ticks far longer than a phase,
    so one observation says little.

    The device's timeline, forward by forward, from two questions that
    do not block (``jax.Array.is_ready()`` of the array a fetch waits
    for: the sampled tokens, or the verifier's first output). At the
    ISSUE of forward N (``forward_issue``): is nothing in flight, or is
    the forward in flight done already? Then the chip had run dry
    (``idle_at_issue`` 1) and N starts at its issue; else N starts where
    the forward before it ends. Just before N's FETCH (``fetch``): is
    N's array there already (``ready``)? Then the fetch only copies
    (``fetch_hists["1"]``: what ``_fetch_sample`` costs) and N's end was
    not seen; else the fetch waited (``fetch_hists["0"]``) and its
    return IS N's end on the device. Where both ends of N are known,
    ``device_step[shape]`` observes ``end - start``, the chip's time for
    the forward and its sampler on the host's clock, and ``device_seen``
    1; else ``device_seen`` 0. A device-bound engine sees nearly every
    step; a CPU-bound one nearly none, and says so by ``idle_at_issue``.
    ``shape`` is the runner's: ``decode`` (no prefill token), ``chunk``
    (a prefill token in the largest token bucket the configuration
    compiles), ``mixed`` (any other step with a prefill token). The
    synchronous step obeys the same rules (every issue finds the chip
    idle); pipeline parallelism and the multi-step burst observe
    nothing.

    ``host_gap`` is the HOST's bookkeeping of the same gap and says less
    since the pipelined step: from a fetch's return to the issue of the
    next forward, or 0 where that forward was issued before the fetch
    began (``forward_queued``, which the pipelined step sets from its
    queue). It reads 0 on every pipelined step whether or not the
    forward in the queue had already ended; ``idle_at_issue`` is the
    question asked of the device.

    ``kind`` is ``mixed`` for a step whose batch holds a prefill token
    and ``decode`` otherwise; ``kind_hists`` split the period and the
    device wait by it. The batch is the one whose results the step
    FETCHES: the one it scheduled on the synchronous path, the one it
    retires on the pipelined path, since that batch's device time is
    what the period held (a step that only fills the pipeline takes the
    kind of the batch it launches). ``input_queue`` is a request's wait
    on the transport, from the front end's put to this thread's take."""

    PHASES = ("loop", "schedule", "update_states", "prepare_inputs",
              "launch", "device_wait", "finish", "update", "dispatch",
              "wait")
    # The phases whose CPU seconds something reads (``other`` is no
    # phase: the rest of a period, by subtraction).
    CPU_PHASES = ("prepare_inputs", "launch", "dispatch", "wait")
    KINDS = ("decode", "mixed")
    SHAPES = ("decode", "mixed", "chunk")

    def __init__(self, sync: bool = True) -> None:
        self.hists = {p: Histogram(STEP_PHASE_BUCKETS)
                      for p in self.PHASES}
        self.cpu_hists = {p: Histogram(STEP_PHASE_BUCKETS)
                          for p in self.CPU_PHASES + ("other", )}
        self.period = Histogram(STEP_PHASE_BUCKETS)
        self.step_cpu = Histogram(STEP_PHASE_BUCKETS)
        self.kind_hists = {f"{k}/{part}": Histogram(STEP_PHASE_BUCKETS)
                           for k in self.KINDS
                           for part in ("period", "device_wait")}
        self.host_gap = Histogram(HOST_GAP_BUCKETS)
        self.host_stall = Histogram(HOST_GAP_BUCKETS)
        self.input_queue = Histogram(QUEUE_LAG_BUCKETS)
        self.device_step = {s: Histogram(DEVICE_STEP_BUCKETS)
                            for s in self.SHAPES}
        self.device_seen = Histogram(FLAG_BUCKETS)
        self.idle_at_issue = Histogram(FLAG_BUCKETS)
        self.fetch_hists = {r: Histogram(DEVICE_STEP_BUCKETS)
                            for r in ("0", "1")}
        self.sync = sync
        self.step = 0      # number of the current (or last) vdt_step
        self.idle = True   # the last step left no work behind
        self.kind = "decode"   # of the current (or last) step
        # A forward is in the device's queue behind the one whose fetch
        # comes next (set by the pipelined step before each fetch).
        self.forward_queued = False
        self.mark = 0.0    # end of the last phase of this busy period
        self.cpu_mark = 0.0    # the thread's CPU time ...
        self.cpu_mark_at = -1.0    # ... at this value of ``mark``
        self.cpu_named = 0.0   # prepare_inputs + launch of this period
        self.step_start: Optional[float] = None
        self._cpu_at_step_start = 0.0
        self._fetch_done: Optional[float] = None
        self._cpu_at_fetch_done = 0.0
        self._gap_open = False   # a fetch returned, no forward issued since
        self._loop_span: Optional[span] = None
        self._newest: Optional[_Flight] = None   # the forward issued last
        self._fetching: Optional[_Flight] = None
        self._fetch_start = 0.0

    def span(self, name: str, phase: Optional[str] = None,
             **attrs) -> span:
        """A span of the current step; with ``phase``, one that observes
        that phase's histograms as well."""
        return _PhaseSpan(self, name, phase, attrs)

    def cpu_at_mark(self) -> float:
        """The thread's CPU time at ``mark``: read now, unless the phase
        that ended there read it."""
        if self.cpu_mark_at != self.mark:
            self.cpu_mark, self.cpu_mark_at = time.thread_time(), self.mark
        return self.cpu_mark

    def resume(self) -> None:
        """Work arrived at an idle engine: the wait for it belongs to no
        phase, period, gap or stall."""
        self.idle = False
        self.mark = time.perf_counter()
        self.step_start = self._fetch_done = None
        self._gap_open = False

    def begin_step(self) -> StepTraceAnnotation:
        """At the start of a step that has work: close ``loop`` and the
        period. Returns the step's parent annotation, ``vdt_step``."""
        if self._loop_span is not None:
            self._loop_span.__exit__(None, None, None)
            self._loop_span = None
        if self.idle:
            self.resume()
        if self.sync:
            now, cpu = time.perf_counter(), time.thread_time()
            self.hists["loop"].observe(now - self.mark)
            self.mark = self.cpu_mark_at = now
            self.cpu_mark = cpu
            if self.step_start is not None:
                # ``kind`` is still that of the step that ended.
                period = now - self.step_start
                self.period.observe(period)
                self.kind_hists[f"{self.kind}/period"].observe(period)
                step_cpu = cpu - self._cpu_at_step_start
                self.step_cpu.observe(step_cpu)
                self.cpu_hists["other"].observe(
                    step_cpu - self.cpu_named)
            self.step_start, self._cpu_at_step_start = now, cpu
            self.cpu_named = 0.0
        self.step += 1
        return StepTraceAnnotation("vdt_step", step_num=self.step)

    def end_step(self, more_work: bool) -> None:
        """What follows a step until the next one is ``loop``, unless the
        engine goes idle."""
        self.idle = not more_work
        if more_work:
            self._loop_span = span("vdt.loop", step=self.step)
            self._loop_span.__enter__()

    def launch_issued(self) -> None:
        if self._gap_open:
            self._gap_open = False
            self.host_gap.observe(time.perf_counter() - self._fetch_done)

    def forward_issue(self, shape: str, t_bucket: int) -> span:
        """Around the dispatch of a forward and its sampler (the span
        ``vdt.forward_issue``): closes the host's gap and asks whether
        the chip has anything left to run (``found_idle``). The caller
        hands the array that this forward's fetch will wait for to
        ``in_flight`` once the dispatch has returned it."""
        self.launch_issued()
        attrs = {}
        if self.sync:
            before = self._newest
            found_idle = (before is None or before.array is None
                          or before.array.is_ready())
            self.idle_at_issue.observe(int(found_idle))
            self._newest = _Flight(
                self.step, shape,
                time.perf_counter() if found_idle else None,
                None if found_idle else before)
            attrs["found_idle"] = int(found_idle)
        return span("vdt.forward_issue", step=self.step, shape=shape,
                    t_bucket=t_bucket, **attrs)

    def in_flight(self, array) -> Optional[_Flight]:
        """The forward just issued, for its handle (``fetch`` takes it
        back); ``None`` under pipeline parallelism."""
        if not self.sync:
            return None
        self._newest.array = array
        return self._newest

    def fetch(self, flight: Optional[_Flight]) -> span:
        """The ``device_wait`` phase of a forward that ``in_flight``
        returned: asks first whether its results are there already
        (``ready``: the fetch copies and does not wait). ``issued`` is
        the step whose ``vdt.forward_issue`` this fetch retires."""
        attrs = {}
        if flight is not None and flight.array is not None:
            flight.ready = bool(flight.array.is_ready())
            attrs = {"ready": int(flight.ready), "issued": flight.step}
            self._fetching = flight
        return _PhaseSpan(self, "vdt.device_wait", "device_wait", attrs)

    def fetch_begins(self, now: float) -> None:
        """A step's ``device_wait`` starts at ``mark`` (``now`` on the
        clock: the block's own start): what the phases since the last
        fetch's return waited. Not held at zero: where the thread's CPU
        clock advances in ticks (10 ms on a sandboxed host) one step's
        reading is a tick too high or too low, and only an unclamped sum
        keeps the mean unbiased and equal to the phases' wall less
        CPU."""
        self._fetch_start = now
        cpu = self.cpu_at_mark()
        if self._fetch_done is not None:
            self.host_stall.observe(
                (self.mark - self._fetch_done)
                - (cpu - self._cpu_at_fetch_done))

    def fetch_done(self, waited: float) -> None:
        """... and has ended at ``mark``, after ``waited`` seconds. By
        the host's bookkeeping the device idles from here to the next
        forward's issue, unless that forward is queued already: no gap
        then. Where the fetch was of a forward in flight, its return is
        that forward's end on the device if it truly waited."""
        self._fetch_done = self.mark
        self._cpu_at_fetch_done = self.cpu_mark
        self._gap_open = not self.forward_queued
        if self.forward_queued:
            self.host_gap.observe(0.0)
        self.kind_hists[f"{self.kind}/device_wait"].observe(waited)
        flight, self._fetching = self._fetching, None
        if flight is None:
            return
        self.fetch_hists["1" if flight.ready else "0"].observe(
            self.mark - self._fetch_start)
        if not flight.ready:
            flight.end = self.mark
        start = (flight.start if flight.after is None
                 else flight.after.end)
        seen = flight.end is not None and start is not None
        if seen:
            self.device_step[flight.shape].observe(flight.end - start)
        self.device_seen.observe(int(seen))
        flight.array = flight.after = None

    def to_dict(self) -> dict:
        """The stats-feed entries of this timeline."""
        return {
            "step_phase_seconds": {p: h.to_dict()
                                   for p, h in self.hists.items()},
            "step_phase_cpu_seconds": {p: h.to_dict()
                                       for p, h in self.cpu_hists.items()},
            "step_kind_seconds": {k: h.to_dict()
                                  for k, h in self.kind_hists.items()},
            "step_period_seconds": self.period.to_dict(),
            "step_cpu_seconds": self.step_cpu.to_dict(),
            "step_host_gap_seconds": self.host_gap.to_dict(),
            "step_host_stall_seconds": self.host_stall.to_dict(),
            "input_queue_seconds": self.input_queue.to_dict(),
            "step_device_seconds": {s: h.to_dict()
                                    for s, h in self.device_step.items()},
            "step_device_seen": self.device_seen.to_dict(),
            "step_device_idle_at_issue": self.idle_at_issue.to_dict(),
            "step_fetch_seconds": {r: h.to_dict()
                                   for r, h in self.fetch_hists.items()},
        }


def merge_histogram_dicts(hists: list[dict]) -> Optional[dict]:
    """Element-wise merge of serialized histogram dicts (DP stats
    aggregation). Mismatched bucket layouts (mixed versions mid-upgrade)
    are skipped rather than mis-summed."""
    hists = [h for h in hists if isinstance(h, dict) and h.get("buckets")]
    if not hists:
        return None
    merged = {"buckets": list(hists[0]["buckets"]),
              "counts": [0] * len(hists[0]["counts"]),
              "sum": 0.0, "count": 0}
    for h in hists:
        if list(h["buckets"]) != merged["buckets"]:
            continue
        merged["counts"] = [a + b for a, b in zip(merged["counts"],
                                                  h["counts"])]
        merged["sum"] += h["sum"]
        merged["count"] += h["count"]
    return merged


class BurnRateWatchdog:
    """Multi-window SLO burn-rate watchdog over the goodput plane.

    The SRE-standard burn-rate alert: a window's burn rate is its miss
    fraction divided by the error budget (1 - VDT_SLO_TARGET), so 1.0
    means "missing exactly as fast as the budget allows" and 14 means
    "the whole monthly budget gone in ~2 days". DEGRADED requires BOTH
    the fast (1 m) and slow (10 m) windows to burn past
    VDT_SLO_BURN_THRESHOLD — the fast window confirms the problem is
    live, the slow one that it is sustained, which is what makes the
    flag safe to feed the fleet controller as scale-out pressure.

    Per-request verdicts bucket into coarse time bins (one
    [scored, missed] pair per bin, pruned past the slow window), so
    memory is O(windows), not O(traffic).
    """

    WINDOWS = (("1m", 60.0), ("10m", 600.0))
    BIN_S = 5.0

    def __init__(self, target: Optional[float] = None,
                 threshold: Optional[float] = None) -> None:
        from vllm_distributed_tpu import envs
        self.target = (envs.VDT_SLO_TARGET
                       if target is None else target)
        self.threshold = (envs.VDT_SLO_BURN_THRESHOLD
                          if threshold is None else threshold)
        self.budget = max(1e-6, 1.0 - self.target)
        self._horizon = max(w for _, w in self.WINDOWS) + self.BIN_S
        self._bins: "OrderedDict[int, list[int]]" = OrderedDict()

    def observe(self, good: bool, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        key = int(now // self.BIN_S)
        bucket = self._bins.get(key)
        if bucket is None:
            bucket = self._bins[key] = [0, 0]
            cutoff = key - int(self._horizon // self.BIN_S) - 1
            while self._bins and next(iter(self._bins)) < cutoff:
                self._bins.popitem(last=False)
        bucket[0] += 1
        if not good:
            bucket[1] += 1

    def burn_rates(self, now: Optional[float] = None) -> dict[str, float]:
        """{window: burn rate} (0.0 for an empty window — no traffic
        is not an SLO violation)."""
        now = time.monotonic() if now is None else now
        rates: dict[str, float] = {}
        for name, w in self.WINDOWS:
            cutoff = int((now - w) // self.BIN_S)
            scored = missed = 0
            for key, (s, m) in self._bins.items():
                if key >= cutoff:
                    scored += s
                    missed += m
            frac = missed / scored if scored else 0.0
            rates[name] = frac / self.budget
        return rates

    def degraded(self, now: Optional[float] = None) -> bool:
        if self.threshold <= 0:
            return False
        rates = self.burn_rates(now)
        return all(r > self.threshold for r in rates.values())


@dataclass
class RequestTimes:
    """Per-request timestamps the output processor maintains."""

    arrival: float
    first_token: Optional[float] = None
    last_token: Optional[float] = None


@dataclass
class FrontendStats:
    """Latency histograms + throughput counters, updated by the output
    processor as tokens stream out, rendered into /metrics."""

    ttft: Histogram = field(default_factory=lambda: Histogram(TTFT_BUCKETS))
    itl: Histogram = field(default_factory=lambda: Histogram(ITL_BUCKETS))
    e2e: Histogram = field(default_factory=lambda: Histogram(E2E_BUCKETS))
    # The front end's own share of a step (AsyncLLM._process_batch): the
    # time in OutputProcessor.process_outputs, and from the engine core
    # handing a step's outputs over to their RequestOutputs being on the
    # request queues (in-process transport; one monotonic clock).
    process_outputs: Histogram = field(
        default_factory=lambda: Histogram(STEP_PHASE_BUCKETS))
    output_lag: Histogram = field(
        default_factory=lambda: Histogram(QUEUE_LAG_BUCKETS))
    # The API thread's accounts (all observed on the event loop's
    # thread): its CPU time (``time.thread_time()``) from one step
    # batch's start to the next, which is everything it did for a step
    # since an idle loop burns none; the batches the pump thread has
    # posted to the loop and those _process_batch has begun; a request's
    # way from the HTTP handler's first line to the put on the engine
    # core's transport; one /metrics rendering.
    step_cpu: Histogram = field(
        default_factory=lambda: Histogram(STEP_PHASE_BUCKETS))
    batches_posted: int = 0
    batches_begun: int = 0
    admit: Histogram = field(
        default_factory=lambda: Histogram(QUEUE_LAG_BUCKETS))
    metrics_render: Histogram = field(
        default_factory=lambda: Histogram(STEP_PHASE_BUCKETS))
    # The per-token path's two counts (OutputProcessor.process_outputs):
    # tokens detokenized, by the carrier that decoded them
    # (engine/detokenizer.py: one DecodeStream step, or the two-decode
    # window), and RequestOutputs built, by the kind their caller asked
    # for (outputs.RequestOutputKind).
    detokenized_tokens: dict = field(
        default_factory=lambda: {"stream": 0, "window": 0})
    request_outputs: dict = field(
        default_factory=lambda: {"cumulative": 0, "delta": 0, "final": 0})
    num_prompt_tokens: int = 0
    num_generation_tokens: int = 0
    num_finished: int = 0
    # Engine-core death/restart events detected by the health monitor
    # (AsyncLLM increments when it fails pending requests).
    num_engine_deaths: int = 0
    # Recovery-layer counters: journaled requests resubmitted to a
    # freshly restarted core as continuation prefills, and requests
    # refused at the API admission gate (429/503 shed).
    num_requests_replayed: int = 0
    num_requests_shed: int = 0
    # Wall seconds the last SIGTERM drain took from "stop admitting" to
    # "in-flight work finished" (0 until a drain runs).
    drain_duration_seconds: float = 0.0
    # SLO goodput accounting: TTFT / TPOT budgets in milliseconds
    # (VDT_SLO_TTFT_MS / VDT_SLO_TPOT_MS; 0 disables that target, both
    # 0 disables scoring — the vdt:slo_* families are then not
    # rendered). A request is GOOD when it met every enabled target;
    # goodput_frac = good / scored, the paper-standard "fraction of
    # traffic that met its latency target at this load".
    slo_ttft_ms: float = 0.0
    slo_tpot_ms: float = 0.0
    slo_scored: int = 0
    slo_good: int = 0
    slo_ttft_misses: int = 0
    slo_tpot_misses: int = 0
    # Per-tenant goodput (vdt:tenant_goodput_frac{tenant}; QoS plane):
    # {tenant bucket: [scored, good]}. Fed only when the output
    # processor runs with VDT_QOS=1 — keys are already
    # bounded-cardinality buckets (qos.bucket_tenant), so rendering one
    # series per key is safe.
    slo_by_tenant: dict = field(default_factory=dict)
    # SLO burn-rate watchdog (constructed by the output processor when
    # any SLO target is enabled; None otherwise): multi-window burn
    # rates + the degraded flag /health and /debug/engine surface.
    burn: Optional[BurnRateWatchdog] = None
    # Periodic logging window (LoggingStatLogger equivalent).
    _window_start: float = field(default_factory=time.monotonic)
    _window_gen_tokens: int = 0
    log_interval_s: float = 10.0

    def on_tokens(self, times: RequestTimes, num_new: int,
                  now: Optional[float] = None) -> None:
        if num_new <= 0:
            return
        now = time.monotonic() if now is None else now
        if times.first_token is None:
            times.first_token = now
            self.ttft.observe(now - times.arrival)
            extra = num_new - 1
        else:
            extra = num_new
        if extra > 0 and times.last_token is not None:
            per_token = (now - times.last_token) / extra
            for _ in range(extra):
                self.itl.observe(per_token)
        times.last_token = now
        self.num_generation_tokens += num_new
        self._window_gen_tokens += num_new
        self._maybe_log(now)

    def on_finished(self, times: RequestTimes, num_prompt_tokens: int,
                    now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        self.e2e.observe(now - times.arrival)
        self.num_prompt_tokens += num_prompt_tokens
        self.num_finished += 1

    @property
    def slo_enabled(self) -> bool:
        return self.slo_ttft_ms > 0 or self.slo_tpot_ms > 0

    def on_slo(self, times: RequestTimes, num_output_tokens: int,
               tenant: Optional[str] = None) -> None:
        """Score one finished request against the configured SLO
        targets. Only token-producing requests score (an aborted
        request that never emitted is an availability event, not a
        latency one); TPOT needs >= 2 tokens to be defined. A request
        where NO enabled target was evaluable (e.g. only TPOT enabled
        and max_tokens=1) is not scored at all — counting it as good
        would inflate goodput with requests the targets never saw.
        ``tenant`` (an already-bucketed QoS tenant key, or None when
        the QoS plane is off) additionally banks the verdict into the
        per-tenant goodput family."""
        if not self.slo_enabled:
            return
        if times is None or times.first_token is None:
            return
        evaluated = False
        good = True
        if self.slo_ttft_ms > 0:
            evaluated = True
            ttft_ms = (times.first_token - times.arrival) * 1e3
            if ttft_ms > self.slo_ttft_ms:
                self.slo_ttft_misses += 1
                good = False
        if (self.slo_tpot_ms > 0 and num_output_tokens > 1
                and times.last_token is not None):
            evaluated = True
            tpot_ms = ((times.last_token - times.first_token) * 1e3
                       / (num_output_tokens - 1))
            if tpot_ms > self.slo_tpot_ms:
                self.slo_tpot_misses += 1
                good = False
        if not evaluated:
            return
        self.slo_scored += 1
        if good:
            self.slo_good += 1
        if self.burn is not None:
            self.burn.observe(good)
        if tenant is not None:
            bank = self.slo_by_tenant.setdefault(tenant, [0, 0])
            bank[0] += 1
            if good:
                bank[1] += 1

    def _maybe_log(self, now: float) -> None:
        dt = now - self._window_start
        if dt < self.log_interval_s:
            return
        logger.info("engine throughput: %.1f tok/s generation, "
                    "%d finished requests total",
                    self._window_gen_tokens / dt, self.num_finished)
        self._window_start = now
        self._window_gen_tokens = 0

    # ------------------------------------------------------------------
    def render(self, fault_extra: Optional[dict] = None) -> str:
        """Exposition text. ``fault_extra`` merges follower-process
        fault-injection counter snapshots (shipped over the get_stats
        feed and pid-deduped by dp_client) so the
        vdt:fault_injections_total family is fleet-exact instead of
        front-end-process-local."""
        lines = self.ttft.render(
            "vdt:time_to_first_token_seconds",
            "Time from request arrival to first output token")
        lines += self.itl.render(
            "vdt:inter_token_latency_seconds",
            "Latency between consecutive output tokens")
        lines += self.e2e.render(
            "vdt:e2e_request_latency_seconds",
            "Request arrival to completion latency")
        lines += self.process_outputs.render(
            "vdt:frontend_process_outputs_seconds",
            "Front-end time per step in OutputProcessor.process_outputs "
            "(detokenizing, stop strings, latency stats)")
        lines += self.output_lag.render(
            "vdt:frontend_output_lag_seconds",
            "Engine core handing a step's outputs over to their "
            "RequestOutputs being on the request queues")
        lines += self.step_cpu.render(
            "vdt:frontend_step_cpu_seconds",
            "CPU seconds of the API (event loop) thread from one step "
            "batch's start to the next: process_outputs, the SSE writes "
            "it woke, HTTP handling, admissions, scrapes")
        lines += [
            "# HELP vdt:frontend_batches_pending Step batches posted to "
            "the event loop that _process_batch has not begun",
            "# TYPE vdt:frontend_batches_pending gauge",
            "vdt:frontend_batches_pending "
            f"{self.batches_posted - self.batches_begun}",
        ]
        for name, help_text, label, counts in (
            ("vdt:frontend_detokenized_tokens_total",
             "Tokens detokenized, by carrier: one DecodeStream step a "
             "token (fast tokenizer backend) or the two-decode window",
             "path", self.detokenized_tokens),
            ("vdt:frontend_request_outputs_total",
             "RequestOutputs built by process_outputs, by the output "
             "kind the request's caller asked for", "kind",
             self.request_outputs),
        ):
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} counter"]
            lines += [f'{name}{{{label}="{value}"}} {count}'
                      for value, count in counts.items()]
        lines += self.admit.render(
            "vdt:frontend_admit_seconds",
            "Top of the HTTP handler (AsyncLLM.generate for other "
            "callers) to the request's put on the engine core's "
            "transport: parsing, templating, tokenisation, the executor "
            "hop")
        lines += self.metrics_render.render(
            "vdt:metrics_render_seconds",
            "One /metrics rendering on the API thread (span "
            "vdt.metrics_render), the engine core's stats in hand")
        for name, help_text, value in (
            ("vdt:prompt_tokens_total",
             "Cumulative prompt tokens of finished requests",
             self.num_prompt_tokens),
            ("vdt:generation_tokens_total",
             "Cumulative generated output tokens",
             self.num_generation_tokens),
            ("vdt:request_success_total",
             "Cumulative finished requests", self.num_finished),
            ("vdt:engine_restarts_total",
             "Engine-core death/restart events detected by the health "
             "monitor", self.num_engine_deaths),
            ("vdt:requests_replayed_total",
             "Journaled requests resubmitted to a restarted engine core "
             "as continuation prefills", self.num_requests_replayed),
            ("vdt:requests_shed_total",
             "Requests refused at the API admission gate (overload "
             "shed / drain mode)", self.num_requests_shed),
        ):
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} counter", f"{name} {value}"]
        lines += [
            "# HELP vdt:drain_duration_seconds Duration of the last "
            "SIGTERM graceful drain",
            "# TYPE vdt:drain_duration_seconds gauge",
            f"vdt:drain_duration_seconds {self.drain_duration_seconds}",
        ]
        if self.slo_enabled:
            goodput = self.slo_good / max(self.slo_scored, 1)
            lines += [
                "# HELP vdt:slo_goodput_frac Fraction of scored "
                "requests that met every enabled SLO target "
                "(VDT_SLO_TTFT_MS / VDT_SLO_TPOT_MS)",
                "# TYPE vdt:slo_goodput_frac gauge",
                f"vdt:slo_goodput_frac {round(goodput, 6)}",
                "# HELP vdt:slo_requests_scored_total Finished "
                "token-producing requests scored against the SLO "
                "targets",
                "# TYPE vdt:slo_requests_scored_total counter",
                f"vdt:slo_requests_scored_total {self.slo_scored}",
                "# HELP vdt:slo_ttft_misses_total Requests whose time "
                "to first token exceeded VDT_SLO_TTFT_MS",
                "# TYPE vdt:slo_ttft_misses_total counter",
                f"vdt:slo_ttft_misses_total {self.slo_ttft_misses}",
                "# HELP vdt:slo_tpot_misses_total Requests whose mean "
                "time per output token exceeded VDT_SLO_TPOT_MS",
                "# TYPE vdt:slo_tpot_misses_total counter",
                f"vdt:slo_tpot_misses_total {self.slo_tpot_misses}",
            ]
            if self.slo_by_tenant:
                name = "vdt:tenant_goodput_frac"
                lines += [
                    f"# HELP {name} Fraction of a tenant bucket's "
                    "scored requests that met every enabled SLO target "
                    "(QoS plane; bucketing bounded by "
                    "VDT_QOS_MAX_TRACKED_TENANTS)",
                    f"# TYPE {name} gauge",
                ]
                lines += [
                    f'{name}{{tenant="{t}"}} '
                    f"{round(good / max(scored, 1), 6)}"
                    for t, (scored, good)
                    in sorted(self.slo_by_tenant.items())
                ]
            if self.burn is not None:
                rates = self.burn.burn_rates()
                name = "vdt:slo_burn_rate"
                lines += [
                    f"# HELP {name} SLO error-budget burn rate per "
                    "window (miss fraction / (1 - VDT_SLO_TARGET); "
                    "1.0 = burning exactly at budget)",
                    f"# TYPE {name} gauge",
                ]
                lines += [f'{name}{{window="{w}"}} {round(r, 6)}'
                          for w, r in sorted(rates.items())]
                lines += [
                    "# HELP vdt:slo_degraded 1 when every burn window "
                    "exceeds VDT_SLO_BURN_THRESHOLD (sustained SLO "
                    "burn; also surfaced in /health)",
                    "# TYPE vdt:slo_degraded gauge",
                    f"vdt:slo_degraded {int(self.burn.degraded())}",
                ]
        lines += render_fault_injections(fault_extra)
        return "\n".join(lines) + "\n"


def render_fault_injections(extra: Optional[dict] = None) -> list[str]:
    """Per-fault-point fire counters (empty when no faults configured),
    so robustness drills show up on the same /metrics scrape as their
    effects. ``extra`` ({point: n}) folds in follower-process snapshots
    — the in-process registry only sees THIS process's fires, so
    spawned engine cores' drills were invisible here until PR 19."""
    from vllm_distributed_tpu.utils import fault_injection
    counts = dict(fault_injection.counters())
    for point, n in (extra or {}).items():
        counts[point] = counts.get(point, 0) + int(n)
    if not counts:
        return []
    name = "vdt:fault_injections_total"
    lines = [f"# HELP {name} Injected fault fires per fault point",
             f"# TYPE {name} counter"]
    lines += [f'{name}{{point="{point}"}} {n}'
              for point, n in sorted(counts.items())]
    return lines
