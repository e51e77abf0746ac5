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
- ``{"kind": "client", "metric": ...}``: from the load generator's own
  clock.

A source that finds nothing to read gives ``None`` and the metric is left
out of the line. A PR that adds a counter to the program adds one such
file and a ``BENCHMARK.json`` entry, and no code here.
"""

import json
import os

from benchmarks import metrics, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

# Host spans that name a device-idle gap (the innermost one that covers
# the gap's middle wins): the program's own step annotation, and, where
# the capture traced Python, the functions of the step path.
HOST_SPANS = (r"^vdt_step|[ .]_prepare_inputs$|[ .]dispatch_model$|"
              r"[ .]_launch_sample$|[ .]schedule$|[ .]update_from_output$|"
              r"[ .]process_outputs$|[ .]_blocking_recv$")


class Context:
    """What one traced run left behind, read lazily."""

    def __init__(self, before, after, gauges, client, trace_dir):
        self.before, self.after, self.gauges = before, after, gauges
        self.client, self.trace_dir = client, trace_dir
        self._planes = None
        self._host: dict = {}     # pattern -> matching host events
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

    def host_events(self, pattern: str = HOST_SPANS) -> list:
        """Matching events of every host thread (a capture that traced
        Python holds millions: each pattern is scanned once)."""
        if pattern not in self._host:
            host = self.planes.get(tr.HOST_PLANE, {})
            self._host[pattern] = [e for line in host.values()
                                   for e in tr.matching(line, pattern)]
        return self._host[pattern]

    def steps(self) -> int:
        return len(self.host_events(r"^vdt_step")) or int(metrics.counter_delta(
            self.before, self.after, "vdt:engine_steps_dispatched_total"))

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
                "idle_gaps": tr.idle_gaps(ops, self.host_events(), *w)}

    def facts(self) -> dict:
        w = self.window()
        return {"trace_file": self.trace_file,
                "trace_bytes": (os.path.getsize(self.trace_file)
                                if self.trace_file else 0),
                "planes": {p: {ln: len(ev) for ln, ev in lines.items()}
                           for p, lines in self.planes.items()
                           if tr.DEVICE_PLANE.match(p)},
                "host_steps": len(self.host_events(r"^vdt_step")),
                "window_s": (w[1] - w[0]) if w else None,
                "modules": tr.top_ops(
                    [e for line in self.device_lines(tr.MODULES_LINE)
                     for e in line], 6)}


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


def _trace(src: dict, ctx: Context):
    w = ctx.window()
    if w is None:
        return None
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


def load_spec(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def evaluate_all(wanted: list[dict], ctx: Context) -> dict:
    """{metric name: value or None} for the manifest entries given."""
    out = {}
    for m in wanted:
        src = load_spec(m["name"])["source"]
        out[m["name"]] = READERS[src["kind"]](src, ctx)
    return out
