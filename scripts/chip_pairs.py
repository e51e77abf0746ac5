#!/usr/bin/env python3
"""Runs of the benchmark on two trees in ONE chip call, so that both sides
of a comparison are measured on the same chip (written for PR 51).

    python3 scripts/chip_pairs.py <tree>:<cell>:<seed>:<trace> ...

``tree`` is ``change`` (this checkout) or ``parent``: a copy of the parent
commit unpacked into ``.bench_checkout/parent`` (git-ignored, copied to the
chip with every call: ``git archive <commit> | tar -x -C
.bench_checkout/parent``). Runs go in the order given (parent, change,
change, parent); the two sides of one comparison share a seed. Each run's
JSON lines go to ``chiprun_out/pairs/<time>-<tree>-<cell>-t<trace>-<seed>
.jsonl`` with the end of its standard error, the window's two ``/metrics``
scrapes and the share of forwards issued while another was in flight
(``vdt:engine_steps_overlapped_total`` over
``vdt:engine_steps_dispatched_total``) beside it, and for a traced run what
``scripts/device_timeline_check.py`` reads off its capture
(``.timeline.json``; with ``CHIP_PAIRS_KEEP_TRACE`` 1 or the cell's name the capture
itself comes back too, 6 to 17 MB a run); one summary line a run is
printed. Nothing here is a benchmark file: the cells, their traffic and
their metrics are ``BENCHMARK.json``'s.
"""
import glob
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = {"change": ROOT,
         "parent": os.path.join(ROOT, ".bench_checkout", "parent")}
OUT = os.path.join(ROOT, "chiprun_out", "pairs")
os.makedirs(OUT, exist_ok=True)
KEYS = ("tpot_p50_ms", "itl_p95_ms", "output_tokens_per_s", "ttft_p50_ms",
        "setup_s")

for n, spec in enumerate(sys.argv[1:]):
    tree, cell, seed, trace = spec.split(":")
    tag = f"{int(time.time())}-{tree}-{cell}-t{trace}-{seed}"
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", cell,
         "--seed", seed, "--seconds", "51", "--trace", trace],
        cwd=TREES[tree], capture_output=True, text=True)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    with open(os.path.join(OUT, tag + ".jsonl"), "w") as f:
        f.write("\n".join(lines) + "\n")
    with open(os.path.join(OUT, tag + ".err"), "w") as f:
        f.write(p.stderr[-40000:])
    last = {}
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            pass
    m = last.get("metrics", {})
    brief = {k: (m[k]["value"] if isinstance(m.get(k), dict) else m.get(k))
             for k in KEYS if k in m}
    extra = {k: (v["value"] if isinstance(v, dict) else v)
             for k, v in m.items()
             if k.startswith(("engine.", "device.", "model.", "kernel."))
             and ("roofline" in k or "period" in k or "idle" in k
                  or "step_device" in k or "step_cpu" in k
                  or "host_gap" in k or "host_stall" in k
                  or "device_" in k)}
    extra.update({k: (v["value"] if isinstance(v, dict) else v)
                  for k, v in m.items()
                  if k.startswith("runner.") and ("device_wait" in k
                                                  or "fetch" in k
                                                  or "prepare_inputs" in k)
                  or k.startswith("kernel.") and ("attn_share" in k
                                                  or "tokens" in k)})
    # The engagement counters, from the window's two /metrics scrapes.
    overlap = {}
    try:
        with open(os.path.join(TREES[tree], ".bench_work",
                               cell.rsplit(".", 1)[0], "loadgen.json")) as f:
            scrapes = json.load(f)["scrapes"]

        with open(os.path.join(OUT, tag + ".scrapes.json"), "w") as f:
            json.dump({k: scrapes[k] for k in ("before", "after")}, f)

        def counter(text, name):
            for ln in text.splitlines():
                if ln.startswith(name + " ") or ln.startswith(name + "{"):
                    return float(ln.rsplit(" ", 1)[1])
            return None
        for short, name in (("overlapped",
                             "vdt:engine_steps_overlapped_total"),
                            ("dispatched",
                             "vdt:engine_steps_dispatched_total")):
            a, b = (counter(scrapes[k], name) for k in ("before", "after"))
            if a is not None and b is not None:
                overlap[short] = b - a
        if overlap.get("dispatched"):
            overlap["overlap_share"] = round(
                overlap["overlapped"] / overlap["dispatched"], 4)
    except Exception as e:  # noqa: BLE001 - a run that kept no scrapes
        overlap = {"overlap_error": repr(e)[:200]}
    with open(os.path.join(OUT, tag + ".overlap.json"), "w") as f:
        json.dump(overlap, f)
    # A traced run's capture, held against what the host believed of the
    # chip (PR 52), while the capture is still there.
    check = os.path.join(TREES[tree], "scripts", "device_timeline_check.py")
    if trace == "1" and os.path.exists(check):
        c = subprocess.run(
            [sys.executable, check, "--cell", cell, "--out",
             os.path.join(OUT, tag + ".timeline.json")],
            cwd=TREES[tree], capture_output=True, text=True)
        print(c.stdout.strip()[-6000:] or c.stderr[-2000:], flush=True)
        if os.environ.get("CHIP_PAIRS_KEEP_TRACE") in ("1", cell):
            found = sorted(glob.glob(os.path.join(
                TREES[tree], ".bench_work", cell.rsplit(".", 1)[0],
                "trace", "**", "*.xplane.pb"), recursive=True))
            if found:
                shutil.copy(found[-1], os.path.join(OUT,
                                                    tag + ".xplane.pb"))
    print(json.dumps({"run": tag, "rc": p.returncode, **overlap,
                      "wall_s": round(time.time() - t0, 1),
                      "correct": last.get("correct"),
                      "failed": last.get("failed"),
                      "device": (last.get("device") or {}).get("kind"),
                      **brief, **extra}), flush=True)
    if p.returncode != 0:
        print(p.stderr[-3000:], flush=True)
