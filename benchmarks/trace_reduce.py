"""From a profiler trace (``.xplane.pb``) to numbers. The arithmetic
works on plain event lists ``[(name, start_s, duration_s), ...]`` so it
can be checked on hand-built lists; ``load`` turns a trace file into
such lists with nothing but ``jax.profiler.ProfileData``.

What a v5e trace looks like (read by hand in PR 24, see PERF.md): each
chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per launched program (``jit_<fn>(<fingerprint>)``), its line
``XLA Ops`` one event per operation inside them (fusions, custom calls
of the Pallas kernels, copies), and ``Steps`` the step markers. Host
threads are lines of the plane ``/host:CPU``, all named ``python3``; the
program's ``StepTraceAnnotation("vdt_step")`` and its ``vdt.*`` phases
show on the engine-core thread's line. Every plane is on one clock.
"""

import glob
import os
import re
from statistics import median

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def find_trace(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_s, dur_s)]}}. A host
    thread is a line of its own: the threads of one process all carry
    the process's name (``python3``), so a repeated name of the host
    plane gets ``#2``, ``#3``. Repeated names of other planes merge."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            name, n = line.name, 1
            while plane.name == HOST_PLANE and name in lines:
                n += 1
                name = f"{line.name}#{n}"
            evs = lines.setdefault(name, [])
            for ev in line.events:
                evs.append((short_name(ev.name), ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9))
    return planes


def short_name(name: str) -> str:
    """A device op's event is named by its whole HLO instruction
    (``%fusion.12 = (bf16[...]...) fusion(...)``): keep the result's
    name. Other events keep theirs."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def device_planes(planes: dict) -> list[str]:
    return sorted(p for p in planes if DEVICE_PLANE.match(p))


# ---------------------------------------------------------------------
# Arithmetic on event lists
# ---------------------------------------------------------------------
def merge(intervals) -> list[tuple[float, float]]:
    """Union of [start, end) intervals, as a sorted disjoint list."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intervals(events, t0=None, t1=None):
    """Events as intervals, clipped to [t0, t1] where given."""
    out = []
    for _, s, d in events:
        a, b = s, s + d
        if t0 is not None:
            a = max(a, t0)
        if t1 is not None:
            b = min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def busy_seconds(events, t0=None, t1=None) -> float:
    return sum(b - a for a, b in merge(intervals(events, t0, t1)))


def span(events) -> tuple[float, float]:
    """First start and last end of a list of events."""
    return (min(s for _, s, _ in events), max(s + d for _, s, d in events))


def idle_share(events, t0: float, t1: float) -> float:
    """1 - union of the events' intervals over [t0, t1], in percent."""
    return 100.0 * (1.0 - busy_seconds(events, t0, t1) / (t1 - t0))


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def share_of_busy(events, pattern: str, t0=None, t1=None) -> float | None:
    """Time of the matching events over the device's busy time, percent.
    Both are unions, so nested or overlapping events count once."""
    busy = busy_seconds(events, t0, t1)
    if busy <= 0:
        return None
    return 100.0 * busy_seconds(matching(events, pattern), t0, t1) / busy


def median_ms(events, pattern: str) -> float | None:
    d = [e[2] for e in matching(events, pattern)]
    return 1e3 * median(d) if d else None


def ms_per_step(events, pattern: str, steps: int) -> float | None:
    """Summed time of the matching events over a number of steps."""
    if steps <= 0:
        return None
    return 1e3 * sum(e[2] for e in matching(events, pattern)) / steps


def self_times(events) -> list:
    """[(name, start, self seconds)]: an event's duration less what the
    events nested inside it on the same line cover (a ``while`` holds the
    layer loop's operations; counting both would count them twice)."""
    out, stack = [], []   # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            i = stack[-1][1]
            out[i] = (out[i][0], out[i][1], out[i][2] - d)
        out.append((name, s, d))
        stack.append([s + d, len(out) - 1])
    return out


def top_ops(events, n: int = 10, t0=None, t1=None) -> list:
    """[name, seconds] of the operations with most device SELF time.
    Trailing instance numbers (``fusion.123``) are folded together."""
    acc: dict = {}
    for name, s, d in self_times(events):
        if (t0 is None or s >= t0) and (t1 is None or s < t1):
            key = re.sub(r"[.\d]+$", "", name) or name
            acc[key] = acc.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, host_events, t0: float, t1: float,
              n: int = 10) -> list:
    """The longest device-idle gaps in [t0, t1], each named by what the
    host thread whose events are given was in at its middle:
    ``in:<host event>`` (the innermost event that spans it) or
    ``between_steps``; summed by name, [name, seconds], longest first."""
    busy = merge(intervals(events, t0, t1))
    gaps, prev = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    acc: dict = {}
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        mid = (a + b) / 2
        inside = [(d, name) for name, s, d in host_events
                  if s <= mid < s + d]
        label = f"in:{min(inside)[1]}" if inside else "between_steps"
        acc[label] = acc.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def main(argv=None) -> int:
    """By-hand reading: ``python3 -m benchmarks.trace_reduce <file|dir>``
    prints every plane, its lines with their event counts and spans, and
    each line's events that took most time."""
    import sys
    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = find_trace(path)
    planes = load(path)
    print(path, os.path.getsize(path), "bytes")
    for pname, lines in planes.items():
        print(f"plane {pname!r}")
        for lname, evs in lines.items():
            if not evs:
                continue
            a, b = span(evs)
            print(f"  line {lname!r}: {len(evs)} events, span "
                  f"{b - a:.4f}s from {a:.4f}, busy {busy_seconds(evs):.4f}s")
            for name, secs in top_ops(evs, 12):
                print(f"      {secs:10.5f}s  {name[:110]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
