#!/usr/bin/env python3
"""What the step's always-on timeline costs the engine-core thread.

    python3 scripts/timeline_cost.py [--steps 20000] [--tree DIR ...]

One step's WHOLE timeline (``metrics/stats.py`` ``StepPhases``: the
``vdt_step`` annotation, the seven phase spans and ``loop`` with their
annotations, clock reads and observations, the issue's and the fetch's
questions where the tree has them) with nothing inside the spans, in the
synchronous and in the pipelined order, ``--steps`` steps, twice, in
microseconds a step; beside it what one ``time.thread_time()``, one
``time.perf_counter()``, one ``jax.Array.is_ready()`` (of an array that
is there, and of one still being computed) and one ``TraceAnnotation``
of four attributes cost on this host. As PR 39 measured it (``PERF.md``
section 5, "What the instrumentation costs"): run it through the chip
tool, since a clock read costs twenty times more on the chip's sandboxed
host than here. Each ``--tree`` (default: this checkout) is measured in
a process of its own, one after the other, so that each imports its own
package and the chip has one owner at a time; a tree without
``forward_issue`` (before PR 52) runs the timeline it has. One JSON line
a tree. Not part of a benchmark run.
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def measure(steps: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from vllm_distributed_tpu.metrics.stats import StepPhases

    ready = jnp.zeros((64, ), jnp.int32) + 1
    ready.block_until_ready()
    new = hasattr(StepPhases, "forward_issue")

    def launch(ph):
        with ph.span("vdt.schedule", "schedule"):
            pass
        with ph.span("vdt._update_states", "update_states"):
            pass
        with ph.span("vdt._prepare_inputs", "prepare_inputs"):
            pass
        with ph.span("vdt.launch", "launch"):
            if not new:
                ph.launch_issued()
                return None
            with ph.forward_issue("decode", 64):
                pass
            return ph.in_flight(ready)

    def retire(ph, flight):
        with (ph.fetch(flight) if new
              else ph.span("vdt.device_wait", "device_wait")):
            pass
        with ph.span("vdt.finish", "finish"):
            pass
        with ph.span("vdt.update_from_output", "update"):
            pass

    def run(pipelined: bool) -> float:
        ph = StepPhases()
        flight = None
        with ph.begin_step():          # the fill, outside the timing
            flight = launch(ph)
        ph.end_step(more_work=True)
        t0 = time.perf_counter()
        for _ in range(steps):
            with ph.begin_step():
                if pipelined:
                    ph.forward_queued = True
                    nxt = launch(ph)
                    retire(ph, flight)
                    flight = nxt
                else:
                    retire(ph, flight)      # as _step_sync: launch, fetch
                    flight = launch(ph)
            ph.end_step(more_work=True)
        return (time.perf_counter() - t0) / steps * 1e6

    def each(fn, n: int = 20000) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e6

    def annotation():
        with TraceAnnotation("vdt.cost", step=1, shape="decode",
                             t_bucket=64, found_idle=0):
            pass

    out = {"device": jax.devices()[0].device_kind,
           "platform": jax.devices()[0].platform,
           "tree_has_device_timeline": new, "steps": steps,
           "sync_us_a_step": [round(run(False), 2) for _ in range(2)],
           "pipelined_us_a_step": [round(run(True), 2) for _ in range(2)],
           "thread_time_us": round(each(time.thread_time), 3),
           "perf_counter_us": round(each(time.perf_counter), 3),
           "is_ready_us": round(each(ready.is_ready), 3),
           "trace_annotation_us": round(each(annotation), 3)}
    # is_ready() of an array the chip is still computing: asked in a loop
    # behind a chain of products until it says yes.
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    chain = jax.jit(lambda a: jax.lax.fori_loop(
        0, 400, lambda _, b: (b @ a) * 1e-3, a))
    chain(x).block_until_ready()
    busy = chain(x)
    asked, t0 = 0, time.perf_counter()
    while not busy.is_ready():
        asked += 1
    spent = time.perf_counter() - t0
    out["is_ready_while_busy_us"] = round(spent / asked * 1e6, 3) \
        if asked else None
    out["is_ready_while_busy_asked"] = asked
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--steps", type=int, default=20000)
    parser.add_argument("--tree", nargs="*", default=None)
    parser.add_argument("--in-tree", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.in_tree:
        print(json.dumps(measure(args.steps)), flush=True)
        return 0
    rc = 0
    for tree in args.tree or [REPO]:
        tree = os.path.abspath(tree)
        env = dict(os.environ, PYTHONPATH=tree)
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--in-tree",
             "--steps", str(args.steps)], cwd=tree, env=env,
            capture_output=True, text=True)
        line = next((ln for ln in reversed(p.stdout.splitlines())
                     if ln.startswith("{")), None)
        if line is None:
            rc = 1
            print(json.dumps({"tree": tree, "rc": p.returncode,
                              "stderr": p.stderr[-2000:]}), flush=True)
        else:
            print(json.dumps({"tree": os.path.relpath(tree, REPO),
                              **json.loads(line)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
