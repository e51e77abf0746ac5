"""Per-layer metrics, each read as a file of its own says
(``benchmarks/layer_metrics/<name>.json``: ``source`` and, for people,
``what``). Unit, layer, ``moves`` and the cells that report a metric are
stated once, in ``BENCHMARK.json``; a reader file names no cell, so a new
cell edits none. ``source`` is one of:

- ``{"kind": "prometheus", "metric": ..., "labels": {...}, "reduce":
  "delta" | "hist_mean" | "hist_quantile" | "gauge_mean", "q": 0.5,
  "scale": 1000}``: from the two ``/metrics`` scrapes at the window's
  ends (deltas) or the samples taken every 0.5 s inside it (gauges).
- ``{"kind": "trace", "line": "XLA Ops" | "XLA Modules", "match":
  <regex>, "reduce": "idle_share" | "share_of_busy" | "median_ms" |
  "ms_per_step"}``: from the device plane(s) of the profiler trace,
  averaged over the chips.
- ``{"kind": "trace", "line": "XLA Ops", "match": <regex>, "reduce":
  "roofline_share", "work": <module>}``: the least time the chip could
  take for one step of the matching kernel over the time it took, in
  percent. ``benchmarks/rooflines/<module>.py`` has one function,
  ``work(config, ctx)``, which gives the bytes and operations one step
  of this cell cannot avoid, from the cell's configuration file and the
  run's counters; the peaks are ``benchmarks/peaks.json``'s under the
  device kind the run found.
- ``{"kind": "client", "metric": ...}``: from the load generator's own
  clock.

A source that finds nothing to read gives ``None`` and the metric is left
out of the line. A PR that adds a counter to the program adds one such
file and a ``BENCHMARK.json`` entry, and no code here; one that adds a
kernel adds a work module besides.
"""

import importlib
import json
import os

from benchmarks import metrics, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

# Host spans that name a device-idle gap (the innermost one that covers
# the gap's middle wins): the program's own step annotation and step
# phases, and, where the capture traced Python, the functions of the
# step path.
HOST_SPANS = (r"^vdt_step|[ .]_prepare_inputs$|[ .]dispatch_model$|"
              r"[ .]_launch_sample$|[ .]schedule$|[ .]update_from_output$|"
              r"[ .]process_outputs$|[ .]_blocking_recv$|[ .]launch$|"
              r"[ .]device_wait$|[ .]finish$|[ .]loop$|[ .]_update_states$")
STEP_SPAN = r"^vdt_step"


class Context:
    """What one traced run left behind, read lazily."""

    def __init__(self, before, after, gauges, client, trace_dir,
                 config=None, device_kind=None, capture=None):
        self.before, self.after, self.gauges = before, after, gauges
        self.client, self.trace_dir = client, trace_dir
        # The cell's configuration file and the device kind the run
        # found, for the roofline shares; ``capture``: the two scrapes
        # (of ``gauges``) that lie inside the profiler capture, if any.
        self.config, self.device_kind = config, device_kind
        self.capture = capture
        self._planes = None
        self.trace_file = None

    @property
    def planes(self) -> dict:
        if self._planes is None:
            self.trace_file = tr.find_trace(self.trace_dir)
            self._planes = tr.load(self.trace_file) if self.trace_file \
                else {}
        return self._planes

    def device_lines(self, line: str) -> list[list]:
        """The named line of every device plane (one list per chip)."""
        return [self.planes[p].get(line, [])
                for p in tr.device_planes(self.planes)]

    def window(self) -> tuple[float, float] | None:
        """The traced span: first start to last end of any device op."""
        evs = [e for line in self.device_lines(tr.OPS_LINE) for e in line]
        return tr.span(evs) if evs else None

    def host_steps(self) -> int:
        """The ``vdt_step`` events of every host thread in the capture."""
        return sum(len(tr.matching(line, STEP_SPAN))
                   for line in self.planes.get(tr.HOST_PLANE, {}).values())

    def steps(self) -> int:
        return self.host_steps() or int(metrics.counter_delta(
            self.before, self.after, "vdt:engine_steps_dispatched_total"))

    def engine_thread(self, pattern: str = HOST_SPANS) -> list:
        """Matching events of the one host thread that runs the steps
        (the line with most ``vdt_step`` events)."""
        host = self.planes.get(tr.HOST_PLANE, {})
        line = max(host.values(), default=[],
                   key=lambda evs: len(tr.matching(evs, STEP_SPAN)))
        return tr.matching(line, pattern)

    def step_mean(self, name: str, labels: dict | None = None):
        """Mean per step of a histogram family that the program observes
        once a step: over the steps between the two scrapes inside the
        capture where the run kept them (the steps a trace metric's
        kernel times are of), else over the window. ``None`` where the
        family is missing or saw no step."""
        if self.capture:
            v = metrics.histogram_mean(*self.capture, name, labels)
            if v is not None:
                return v
        return metrics.histogram_mean(self.before, self.after, name, labels)

    # -- the contract's `device` and `breakdown` additions ------------
    def device_times(self) -> dict:
        w = self.window()
        if w is None:
            return {}
        busy = [tr.busy_seconds(line, *w)
                for line in self.device_lines(tr.OPS_LINE)]
        return {"busy_s": sum(busy) / len(busy), "window_s": w[1] - w[0]}

    def breakdown(self) -> dict | None:
        w = self.window()
        if w is None:
            return None
        ops = self.device_lines(tr.OPS_LINE)[0]
        return {"device_ops": tr.top_ops(ops, 10, *w),
                "idle_gaps": tr.idle_gaps(ops, self.engine_thread(), *w)}

    def facts(self) -> dict:
        w = self.window()
        return {"trace_file": self.trace_file,
                "trace_bytes": (os.path.getsize(self.trace_file)
                                if self.trace_file else 0),
                "planes": {p: {ln: len(ev) for ln, ev in lines.items()}
                           for p, lines in self.planes.items()
                           if tr.DEVICE_PLANE.match(p)},
                "host_steps": self.host_steps(),
                "window_s": (w[1] - w[0]) if w else None,
                "modules": tr.top_ops(
                    [e for line in self.device_lines(tr.MODULES_LINE)
                     for e in line], 6)}


def capture_scrapes(gauges: list, marks: dict):
    """The first and the last of the timed scrapes ``[(t, samples)]``
    taken while the profiler captured (between the return of
    ``/start_profile`` and the call of ``/stop_profile``, the load
    generator's marks), or ``None`` where there are not two."""
    a, b = marks.get("profile_started"), marks.get("profile_stop_call")
    if a is None or b is None:
        return None
    inside = [g for t, g in gauges if a <= t <= b]
    return (inside[0], inside[-1]) if len(inside) >= 2 else None


def _prometheus(src: dict, ctx: Context):
    name, labels = src["metric"], src.get("labels")
    red = src["reduce"]
    if red != "delta" and not any(n.startswith(name)
                                  for n, _, _ in ctx.after):
        return None   # the program does not export this family
    if red == "delta":
        v = metrics.counter_delta(ctx.before, ctx.after, name, labels)
    elif red == "hist_mean":
        v = metrics.histogram_mean(ctx.before, ctx.after, name, labels)
    elif red == "hist_quantile":
        v = metrics.histogram_quantile(ctx.before, ctx.after, name,
                                       src["q"], labels)
    elif red == "gauge_mean":
        v = metrics.gauge_mean(ctx.gauges, name, labels)
    else:
        raise ValueError(f"unknown prometheus reduction {red!r}")
    return None if v is None else v * src.get("scale", 1)


def load_peaks(device_kind) -> dict | None:
    with open(os.path.join(HERE, "peaks.json")) as f:
        return json.load(f).get(device_kind)


def _roofline_share(src: dict, ctx: Context):
    """Least seconds a step (the larger of bytes over the memory's peak
    and operations over the bf16 peak) over the matching operations'
    seconds a step, percent. Nothing to divide (no peaks for the device,
    a counter the work module needs missing, no such kernel in the
    capture) gives ``None``, never 0."""
    peaks = load_peaks(ctx.device_kind)
    if peaks is None or ctx.config is None:
        return None
    module = importlib.import_module(f"benchmarks.rooflines.{src['work']}")
    work = module.work(ctx.config, ctx)
    if work is None:
        return None
    least_s = max(work["bytes"] / peaks["hbm_bytes_per_s"],
                  work["flops"] / peaks["bf16_flops_per_s"])
    kernel_ms = [tr.ms_per_step(line, src["match"], ctx.steps())
                 for line in ctx.device_lines(src.get("line", tr.OPS_LINE))]
    kernel_ms = [v for v in kernel_ms if v]
    if not kernel_ms or least_s <= 0:
        return None
    return 100.0 * least_s / (1e-3 * sum(kernel_ms) / len(kernel_ms))


def _trace(src: dict, ctx: Context):
    w = ctx.window()
    if w is None:
        return None
    if src["reduce"] == "roofline_share":
        return _roofline_share(src, ctx)
    red, vals = src["reduce"], []
    for line in ctx.device_lines(src.get("line", tr.OPS_LINE)):
        if red == "idle_share":
            vals.append(tr.idle_share(line, *w))
        elif red == "share_of_busy":
            vals.append(tr.share_of_busy(line, src["match"], *w))
        elif red == "median_ms":
            vals.append(tr.median_ms(line, src["match"]))
        elif red == "ms_per_step":
            vals.append(tr.ms_per_step(line, src["match"], ctx.steps()))
        else:
            raise ValueError(f"unknown trace reduction {red!r}")
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None


READERS = {"prometheus": _prometheus, "trace": _trace,
           "client": lambda src, ctx: ctx.client.get(src["metric"])}


def load_spec(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def evaluate_all(wanted: list[dict], ctx: Context) -> dict:
    """{metric name: value or None} for the manifest entries given."""
    out = {}
    for m in wanted:
        src = load_spec(m["name"])["source"]
        out[m["name"]] = READERS[src["kind"]](src, ctx)
    return out
