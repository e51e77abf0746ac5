"""The reader files that ISSUE 25 adds, one case each, through
``layers.evaluate_all`` on two ``/metrics`` snapshots. The snapshots are
written by the program's own renderers from observations chosen by hand,
so a case also fails when a reader file and the program part on a
family's or a label's name."""

import json
import os

import pytest

from benchmarks import layers, metrics
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.metrics.stats import (QUEUE_LAG_BUCKETS,
                                                STEP_TOKEN_BUCKETS,
                                                FrontendStats, Histogram,
                                                StepPhases)

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))

# What the program observes before the window and inside it: seconds, or
# tokens. A reader sees only the window's part.
OBSERVED = {
    "period": ([0.050] * 2, [0.020] * 3 + [0.040]),
    "host_gap": ([0.030], [0.010, 0.014]),
    "host_stall": ([0.009], [0.001, 0.002, 0.003]),
    "loop": ([0.004], [0.0005] * 4),
    "update_states": ([0.004], [0.0007, 0.0009]),
    "launch": ([0.010], [0.003] * 5),
    "finish": ([0.010], [0.001, 0.002]),
    "process_outputs": ([0.020], [0.0015] * 4),
    # 10 in (2 ms, 4 ms]: the median is the bucket's middle.
    "output_lag": ([0.5] * 3, [0.003] * 10),
    # 8 in (8 ms, 16 ms]: rank 4 of 8 lies halfway through it.
    "queue_wait": ([1.5], [0.012] * 8),
    "decode_tokens": ([1], [40, 44]),
    "prefill_tokens": ([2048], [0, 0, 300]),
    "kv_tokens_read": ([5], [18000, 20000]),
}
EXPECTED = {
    "engine.step_period_mean_ms": 25.0,
    "engine.host_gap_mean_ms": 12.0,
    "engine.host_stall_mean_ms": 2.0,
    "engine.loop_mean_ms": 0.5,
    "runner.update_states_mean_ms": 0.8,
    "runner.launch_mean_ms": 3.0,
    "runner.finish_mean_ms": 1.5,
    "frontend.process_outputs_mean_ms": 1.5,
    "frontend.output_lag_p50_ms": 3.0,
    "sched.queue_wait_p50_ms": 12.0,
    "sched.step_decode_tokens_mean": 42.0,
    "sched.step_prefill_tokens_mean": 100.0,
    "kernel.kv_tokens_read_per_step": 19000.0,
}


def _scrape(upto: int) -> list:
    """The program's /metrics text after the first ``upto`` of the two
    lists of observations, parsed as the harness parses a scrape."""
    def seen(key):
        return [v for part in OBSERVED[key][:upto] for v in part]

    def hist(buckets, key):
        h = Histogram(buckets)
        for v in seen(key):
            h.observe(v)
        return h

    phases = StepPhases()
    for name in ("loop", "update_states", "launch", "finish"):
        for v in seen(name):
            phases.hists[name].observe(v)
    for h, key in ((phases.period, "period"), (phases.host_gap, "host_gap"),
                   (phases.host_stall, "host_stall")):
        for v in seen(key):
            h.observe(v)
    stats = phases.to_dict()
    stats["request_queue_seconds"] = hist(QUEUE_LAG_BUCKETS,
                                          "queue_wait").to_dict()
    stats["step_kv_tokens_read"] = hist(STEP_TOKEN_BUCKETS,
                                        "kv_tokens_read").to_dict()
    stats["step_tokens"] = {
        kind: hist(STEP_TOKEN_BUCKETS, f"{kind}_tokens").to_dict()
        for kind in ("decode", "prefill")}
    front = FrontendStats()
    for v in seen("process_outputs"):
        front.process_outputs.observe(v)
    for v in seen("output_lag"):
        front.output_lag.observe(v)
    return metrics.parse_prometheus(render_metrics(stats) + front.render())


def _context(before, after) -> layers.Context:
    return layers.Context(before=before, after=after, gauges=[], client={},
                          trace_dir="/nonexistent")


def test_the_manifest_lists_the_thirteen_new_readers_last():
    """The thirteen are in ``per_layer`` in this order among themselves,
    wherever they stand: a later PR appends its entries after them (the
    name is PR 25's, when they were the newest)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    new = [m for m in manifest["per_layer"] if m["name"] in EXPECTED]
    assert [m["name"] for m in new] == list(EXPECTED)
    cells = [w["name"] for w in manifest["workloads"]]
    for m in new:
        assert m["source"] == "program_counter"
        assert set(m["workloads"]) <= set(cells)
        spec = layers.load_spec(m["name"])["source"]
        assert spec["kind"] == "prometheus"
        assert spec["reduce"] in ("hist_mean", "hist_quantile", "delta")


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_file_reads_the_window(name):
    ctx = _context(_scrape(1), _scrape(2))
    got = layers.evaluate_all([{"name": name}], ctx)
    assert got == {name: pytest.approx(EXPECTED[name])}
    # Nothing observed inside the window, or a program that does not
    # export the family (the parent commit): no value, and no error.
    idle = _context(_scrape(2), _scrape(2))
    assert layers.evaluate_all([{"name": name}], idle) == {name: None}
    parent = metrics.parse_prometheus(
        'vdt:step_phase_seconds_count{phase="schedule"} 7\n'
        'vdt:step_phase_seconds_sum{phase="schedule"} 0.1\n'
        "vdt:step_host_gap_seconds_count 0\n"
        "vdt:step_host_gap_seconds_sum 0.0\n")
    assert layers.evaluate_all(
        [{"name": name}], _context(parent, parent)) == {name: None}
