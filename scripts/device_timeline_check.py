#!/usr/bin/env python3
"""What the host believed of the chip, held against a capture's device
plane (ISSUE 52).

    python3 scripts/device_timeline_check.py --cell <cell>
    python3 scripts/device_timeline_check.py --trace <dir|file.xplane.pb>
        [--loadgen <loadgen.json>]

The step's timeline (``metrics/stats.py`` ``StepPhases``) asks the device
two questions that do not block and puts the answers on the capture's
clock: ``vdt.forward_issue`` carries ``found_idle`` (at a forward's
issue nothing was left to run) and ``vdt.device_wait`` carries ``ready``
(the results were there when the fetch began) and ``issued`` (the step
of the issue it retires). From one traced run of the benchmark (``--cell``
reads ``.bench_work/<config>/trace`` and ``loadgen.json`` of this
checkout, as ``benchmarks.run --trace 1`` leaves them) this script
prints one JSON line:

- ``host_clock_ahead_ms``: the offset between the capture's two clocks,
  read off the capture itself (``clock_offset``: a fetch that truly
  waited returns when its step's sampler ends); every host stamp below
  is moved onto the device plane's clock by it;
- ``found_idle``: of the issues inside the capture, the share whose
  answer agrees with the device plane (idle there means that no ``XLA
  Ops`` event covers the issue's stamp), and the first disagreements
  with what the device plane shows around the stamp;
- ``ready``: of the fetches whose forward the capture holds, the share
  whose answer agrees with the device plane (ready there means that the
  step's sampler module had ended at the fetch's stamp);
- ``shapes``: per shape, the median device time of a step by the ``XLA
  Modules`` line (``span_ms``: the forward's start to its sampler's end;
  ``period_ms``: sampler's end to sampler's end, which is what the host's
  rule measures behind a busy issue) beside the run's
  ``vdt:step_device_seconds`` median over the whole window, from the
  window's two scrapes, and how many steps each saw.

It reads; it judges nothing and edits nothing. Not part of a benchmark
run (``benchmarks.trace_reduce`` is imported for its loader alone).
"""

import argparse
import bisect
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import metrics, trace_reduce as tr  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORWARD = re.compile(r"^jit_forward\b")
SAMPLER = re.compile(r"^jit_(sample|sample_ext|spec_verify)\b")


def host_events(path: str, names: tuple) -> dict:
    """{name: [(start_s, dur_s, stats)]} of the host plane's events with
    one of ``names`` (``trace_reduce.load`` keeps no stats)."""
    from jax.profiler import ProfileData
    out = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    out[ev.name].append((ev.start_ns * 1e-9,
                                         ev.duration_ns * 1e-9,
                                         dict(ev.stats)))
    for evs in out.values():
        evs.sort(key=lambda e: e[0])
    return out


class Busy:
    """The merged intervals of a device line."""

    def __init__(self, events) -> None:
        merged = tr.merge(tr.intervals(events))
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]

    def covers(self, t: float) -> bool:
        i = bisect.bisect_right(self.starts, t) - 1
        return i >= 0 and t < self.ends[i]

    def around(self, t: float, modules: list) -> dict:
        """For a disagreement's report: the module that runs at ``t``
        and how far ``t`` is from the busy interval's ends, or the idle
        gap ``t`` lies in."""
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.ends[i]:
            running = [n.split("(")[0] for n, s, d in modules
                       if s <= t < s + d]
            return {"busy": True, "module": running[:1],
                    "since_ms": round((t - self.starts[i]) * 1e3, 4),
                    "until_ms": round((self.ends[i] - t) * 1e3, 4)}
        gap_from = self.ends[i] if i >= 0 else None
        gap_to = self.starts[i + 1] if i + 1 < len(self.starts) else None
        return {"busy": False,
                "idle_since_ms": None if gap_from is None
                else round((t - gap_from) * 1e3, 4),
                "idle_until_ms": None if gap_to is None
                else round((gap_to - t) * 1e3, 4)}


def clock_offset(steps: list, waits: list, copies_s=None) -> float:
    """Seconds by which the host's stamps run ahead of the device
    plane's in this capture (2.2 and 2.9 ms in the first two captures
    this was written for), from the capture itself: a fetch that truly
    waited (``ready`` 0) returns when its step's sampler has ended AND
    its results are copied, and the copies are what a fetch costs that
    found them ready (``ready`` 1: the median of those in the capture,
    else ``copies_s``, the window's). So the offset is the median of
    (fetch's return - the nearest sampler's end on the device plane)
    less that. 0.0 where no fetch waited within 6 ms of a sampler's end
    (the two planes are then compared as they are)."""
    ends = sorted(e for _, e in steps if e is not None)
    deltas = []
    for t, dur, st in waits:
        if "ready" not in st or int(st["ready"]) or not ends:
            continue
        ret = t + dur
        i = bisect.bisect_left(ends, ret)
        near = min(ends[max(i - 1, 0):i + 1], key=lambda e: abs(ret - e))
        if abs(ret - near) < 6e-3:
            deltas.append(ret - near)
    if not deltas:
        return 0.0
    copied = [dur for _, dur, st in waits if int(st.get("ready", 0))]
    copies = statistics.median(copied) if len(copied) >= 5 \
        else (copies_s or 0.0)
    return statistics.median(deltas) - copies


def device_steps(modules: list) -> list:
    """[(forward's start, sampler's end or None)] of the ``XLA Modules``
    line, a forward with what ran behind it until the next forward."""
    modules = sorted(modules, key=lambda e: e[1])
    steps = []
    for name, start, dur in modules:
        if FORWARD.search(name):
            steps.append([start, None])
        elif steps and SAMPLER.search(name):
            steps[-1][1] = start + dur
    return steps


def check(trace: str, loadgen: str | None) -> dict:
    path = trace if trace.endswith(".pb") else tr.find_trace(trace)
    if path is None:
        return {"error": f"no capture under {trace}"}
    planes = tr.load(path)
    devices = tr.device_planes(planes)
    if not devices:
        return {"error": "the capture holds no device plane"}
    host = host_events(path, ("vdt.forward_issue", "vdt.device_wait"))
    scrapes = None
    if loadgen and os.path.exists(loadgen):
        with open(loadgen) as f:
            raw = json.load(f)["scrapes"]
        scrapes = (metrics.parse_prometheus(raw["before"]),
                   metrics.parse_prometheus(raw["after"]))
    out = {"trace": os.path.relpath(path, REPO)}
    out.update(timeline(planes[devices[0]].get(tr.OPS_LINE, []),
                        planes[devices[0]].get(tr.MODULES_LINE, []),
                        host["vdt.forward_issue"], host["vdt.device_wait"],
                        scrapes))
    return out


def timeline(ops: list, modules: list, issued: list, waits: list,
             scrapes=None) -> dict:
    """The comparison itself: ``ops`` and ``modules`` are a device
    plane's lines as ``trace_reduce.load`` gives them, ``issued`` and
    ``waits`` the host's ``vdt.forward_issue`` and ``vdt.device_wait``
    events as ``host_events`` does, ``scrapes`` the window's two parsed
    ``/metrics`` texts."""
    window = tr.span(ops) if ops else (0.0, 0.0)
    busy = Busy(ops)
    steps = device_steps(modules)
    offset = clock_offset(
        steps, waits, metrics.histogram_quantile(
            *scrapes, "vdt:step_fetch_seconds", 0.5, {"ready": "1"})
        if scrapes else None)
    # The host's stamps on the device plane's clock.
    issues = [(t - offset, d, st) for t, d, st in issued
              if "found_idle" in st
              and window[0] <= t - offset <= window[1]]
    waits = [(t - offset, d, st) for t, d, st in waits]
    out: dict = {"window_s": round(window[1] - window[0], 4),
                 "issues": len(issues),
                 "host_clock_ahead_ms": round(offset * 1e3, 4)}

    # found_idle against the device plane at the issue's stamp.
    wrong = [(t, st) for t, _, st in issues
             if bool(int(st["found_idle"])) == busy.covers(t)]
    out["found_idle"] = {
        "events": len(issues), "agree": len(issues) - len(wrong),
        "share": round(100 * (1 - len(wrong) / len(issues)), 2)
        if issues else None,
        "host_said_idle": sum(int(st["found_idle"]) for _, _, st in issues),
        "disagree": [{"t_s": round(t - window[0], 6),
                      "device": busy.around(t, modules), **_plain(st)}
                     for t, st in wrong[:8]]}

    # Each issue's step on the device: the forward module that starts at
    # or after the issue and before the next one.
    starts = [s for s, _ in steps]
    by_step, shapes = {}, {}
    for k, (t, _, st) in enumerate(issues):
        nxt = issues[k + 1][0] if k + 1 < len(issues) else window[1]
        i = bisect.bisect_left(starts, t)
        if i < len(steps) and steps[i][0] < nxt:
            by_step[int(st["step"])] = (i, st.get("shape", "?"))
    for i, shape in by_step.values():
        start, end = steps[i]
        if end is None:
            continue
        rec = shapes.setdefault(shape, {"span": [], "period": []})
        rec["span"].append(end - start)
        if i and steps[i - 1][1] is not None:
            rec["period"].append(end - steps[i - 1][1])

    # ready against the sampler's end of the step the fetch retires.
    judged = wrong_ready = said_ready = 0
    bad = []
    for t, _, st in waits:
        if "ready" not in st or int(st.get("issued", -1)) not in by_step:
            continue
        end = steps[by_step[int(st["issued"])][0]][1]
        if end is None:
            continue
        judged += 1
        said_ready += int(st["ready"])
        if bool(int(st["ready"])) != (end <= t):
            wrong_ready += 1
            if len(bad) < 8:
                bad.append({"t_s": round(t - window[0], 6),
                            "sampler_end_before_fetch_ms":
                            round((t - end) * 1e3, 4), **_plain(st)})
    out["ready"] = {"events": judged, "agree": judged - wrong_ready,
                    "share": round(100 * (1 - wrong_ready / judged), 2)
                    if judged else None,
                    "host_said_ready": said_ready, "disagree": bad}

    # The run's own medians over the whole window, beside the capture's.
    out["shapes"] = {}
    for shape in sorted(set(shapes) | {"decode", "mixed", "chunk"}):
        rec = shapes.get(shape, {"span": [], "period": []})
        row = {"steps_in_capture": len(rec["span"]),
               "span_ms_p50": _median_ms(rec["span"]),
               "period_ms_p50": _median_ms(rec["period"])}
        if scrapes:
            labels = {"shape": shape}
            q = metrics.histogram_quantile(
                *scrapes, "vdt:step_device_seconds", 0.5, labels)
            _, _, seen = metrics.histogram_delta(
                *scrapes, "vdt:step_device_seconds", labels)
            row["host_ms_p50"] = None if q is None else round(q * 1e3, 3)
            row["host_steps_seen"] = int(seen)
            base = row["period_ms_p50"] or row["span_ms_p50"]
            if q is not None and base:
                row["host_over_device_pct"] = round(
                    100 * (q * 1e3 / base - 1), 2)
        if row["steps_in_capture"] or row.get("host_steps_seen"):
            out["shapes"][shape] = row
    if scrapes:
        for key, name in (("found_idle_share_window",
                           "vdt:step_device_idle_at_issue"),
                          ("seen_share_window", "vdt:step_device_seen")):
            v = metrics.histogram_mean(*scrapes, name)
            out[key] = None if v is None else round(100 * v, 2)
    return out


def _plain(stats: dict) -> dict:
    return {k: (v if isinstance(v, (int, float, str)) else str(v))
            for k, v in stats.items() if not k.startswith("_")}


def _median_ms(values: list):
    return round(statistics.median(values) * 1e3, 3) if values else None


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cell")
    parser.add_argument("--trace")
    parser.add_argument("--loadgen")
    parser.add_argument("--out", help="also write the line to this file")
    args = parser.parse_args()
    if args.cell:
        work = os.path.join(REPO, ".bench_work",
                            args.cell.rsplit(".", 1)[0])
        args.trace = args.trace or os.path.join(work, "trace")
        args.loadgen = args.loadgen or os.path.join(work, "loadgen.json")
    if not args.trace:
        parser.error("--cell or --trace")
    result = check(args.trace, args.loadgen)
    if args.cell:
        result["cell"] = args.cell
    line = json.dumps(result)
    print(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 1 if "error" in result else 0


if __name__ == "__main__":
    sys.exit(main())
