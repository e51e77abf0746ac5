#!/usr/bin/env python3
"""The builder's tool for finding an open-loop cell's knee, once, on the
chip. Not part of a benchmark run.

    python3 -m benchmarks.sweep --workload <cell> --rates 3,4,5,6,7,8 \
        --step-seconds 18

One process, one server start; the offered rate steps up through
``--rates`` without a pause, each step with the cell's own mix. For each
step it prints completed over offered, the requests in flight every 5 s
from the step's start to its end, and the tails of the requests due in
it. The knee is the highest rate at which the number in flight does not
grow over the step and nothing fails (PERF.md, section 4, says how much
counts as growing); the cell's file holds about 0.8 of it.
"""

import argparse
import json
import os
import sys

from benchmarks import metrics, run, traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=18.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    rates = [float(r) for r in a.rates.split(",")]
    cell = run.load_cell(a.workload)
    if cell["mix"]["loop"] != "open":
        raise SystemExit("a sweep steps an open-loop cell's rate")
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    device = run.device_facts(cell["chips"])
    r = run.Run()
    r.listen()
    cf = cell["config_file"]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, a.seed)
    out_path = os.path.join(run.WORK, cell["config"], "sweep.json")
    steps = [("ramp", rates[0], 5.0)] + [
        (f"r{i}", rate, a.step_seconds) for i, rate in enumerate(rates)]

    def plan_for_port(port):
        plan = traffic.open_segments(
            cell["mix"], steps, traffic.run_rng(a.seed, 1),
            cf["vocab_size"], cf["engine_args"]["max_model_len"])
        span = sum(s[2] for s in steps)
        ea = cf["engine_args"]
        plan.update(warmup=traffic.shape_warmup(
            a.seed, cf["vocab_size"], ea["max_num_seqs"],
            ea["max_num_batched_tokens"]), port=port, model=cell["config"], out=out_path,
                    drain_cap_s=30.0, scoring=[], gauge_every_s=None,
                    trace=None, window={"start": 5.0, "seconds": span - 5.0})
        return plan

    res = r.serve(args, plan_for_port)
    recs, t_start = res["records"], res["marks"]["t_start"]
    print(json.dumps({"phase": "sweep", "device": device,
                      "workload": a.workload, **r.startup_facts()}))

    def in_flight(t):
        return sum(1 for x in recs if x["segment"] != "warmup"
                   and x["sent"] is not None and x["sent"] <= t
                   and (x["end"] is None or x["end"] > t))

    t = t_start + 5.0
    for name, rate, dur in steps[1:]:
        e = metrics.end_to_end(recs, "open", t, t + dur)
        m = e["metrics"]
        errs = sorted({str(x["error"]) for x in recs if x["error"]
                       and t <= x["due"] < t + dur})
        print(json.dumps({
            "errors": errs[:3], "rate_rps": rate, "offered": e["attempted"],
            "completed_ok": e["attempted"] - e["failed"],
            "in_flight_every_5s": [in_flight(t + k * 5.0) for k in range(
                int(dur // 5) + 1)],
            **{k: round(m[k], 1) for k in (
                "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "itl_p95_ms",
                "output_tokens_per_s") if k in m}}), flush=True)
        t += dur
    return 0


if __name__ == "__main__":
    sys.exit(main())
