"""The eleven reader files that ISSUE 39 adds (the host's accounts: CPU
beside wall, the API thread's CPU a step, a request's way in, steps by
kind), one case each, through ``layers.evaluate_all`` on two ``/metrics``
snapshots. As in ``test_benchmark_step_readers.py`` the snapshots are
written by the program's own renderers from observations chosen by hand,
so a case also fails when a reader file and the program part on a
family's or a label's name."""

import json
import os

import pytest

from benchmarks import layers, metrics
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.metrics.stats import FrontendStats, StepPhases

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))

# What the program observes before the window and inside it, seconds. A
# reader sees only the window's part.
OBSERVED = {
    "step_cpu": ([0.050], [0.008, 0.009, 0.010]),
    "cpu.prepare_inputs": ([0.020], [0.0015, 0.0025]),
    "cpu.launch": ([0.030], [0.004, 0.005, 0.006]),
    "frontend_step_cpu": ([0.040] * 2, [0.006, 0.007, 0.008]),
    # 6 in (2 ms, 4 ms]: the median is the bucket's middle.
    "admit": ([0.3], [0.003] * 6),
    # 8 in (8 ms, 16 ms]: rank 4 of 8 lies halfway through it.
    "input_queue": ([1.5], [0.012] * 8),
    "decode/period": ([0.3], [0.016, 0.018, 0.020]),
    "mixed/period": ([0.7], [0.060, 0.080]),
    # 4 in (10 ms, 25 ms]: halfway through.
    "decode/device_wait": ([0.4], [0.012] * 4),
    # 2 in (50 ms, 100 ms]: halfway through.
    "mixed/device_wait": ([0.9], [0.060, 0.070]),
}
# The pending gauge at the scrapes inside the window.
PENDING = [0, 3, 0, 1]
EXPECTED = {
    "engine.step_cpu_mean_ms": 9.0,
    "runner.prepare_inputs_cpu_mean_ms": 2.0,
    "runner.launch_cpu_mean_ms": 5.0,
    "frontend.step_cpu_mean_ms": 7.0,
    "frontend.batches_pending_mean": 1.0,
    "frontend.admit_p50_ms": 3.0,
    "engine.input_wait_p50_ms": 12.0,
    "engine.decode_step_period_mean_ms": 18.0,
    "engine.mixed_step_period_mean_ms": 70.0,
    "runner.decode_device_wait_p50_ms": 17.5,
    "runner.mixed_device_wait_p50_ms": 75.0,
}


def _scrape(upto: int, pending: int = 0) -> list:
    """The program's /metrics text after the first ``upto`` of the two
    lists of observations, parsed as the harness parses a scrape."""
    phases, front = StepPhases(), FrontendStats()
    hists = {
        "step_cpu": phases.step_cpu,
        "cpu.prepare_inputs": phases.cpu_hists["prepare_inputs"],
        "cpu.launch": phases.cpu_hists["launch"],
        "frontend_step_cpu": front.step_cpu,
        "admit": front.admit,
        "input_queue": phases.input_queue,
        **{k: phases.kind_hists[k] for k in phases.kind_hists},
    }
    assert set(hists) == set(OBSERVED)
    for key, h in hists.items():
        for part in OBSERVED[key][:upto]:
            for v in part:
                h.observe(v)
    front.batches_posted, front.batches_begun = 40 + pending, 40
    return metrics.parse_prometheus(
        render_metrics(phases.to_dict()) + front.render())


def _context(before, after, gauges=()) -> layers.Context:
    return layers.Context(before=before, after=after, gauges=list(gauges),
                          client={}, trace_dir="/nonexistent")


def test_the_manifest_lists_the_eleven_in_this_order_somewhere():
    """Among themselves in ISSUE 39's order, wherever they stand: a
    later PR appends its entries after them."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    new = [m for m in manifest["per_layer"] if m["name"] in EXPECTED]
    assert [m["name"] for m in new] == list(EXPECTED)
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in manifest["end_to_end"]}
    for m in new:
        assert m["source"] == "program_counter" and m["better"] == "lower"
        # A metric is listed only where its end-to-end metric is reported.
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        spec = layers.load_spec(m["name"])["source"]
        assert spec["kind"] == "prometheus"
        assert spec["reduce"] in ("hist_mean", "hist_quantile",
                                  "gauge_mean")
    by_name = {m["name"]: m for m in new}
    assert set(by_name["frontend.admit_p50_ms"]["workloads"]) == \
        set(by_name["engine.input_wait_p50_ms"]["workloads"]) == \
        reports["ttft_p50_ms"]
    assert set(by_name["engine.mixed_step_period_mean_ms"]["workloads"]) \
        == reports["itl_p95_ms"]
    assert set(by_name["engine.step_cpu_mean_ms"]["workloads"]) == \
        set(cells)


@pytest.mark.parametrize("name", list(EXPECTED))
def test_reader_file_reads_the_window(name):
    gauges = [_scrape(2, pending) for pending in PENDING]
    ctx = _context(_scrape(1), _scrape(2), gauges)
    got = layers.evaluate_all([{"name": name}], ctx)
    assert got == {name: pytest.approx(EXPECTED[name])}
    # Nothing observed inside the window: no value (the gauge, sampled
    # and not observed, reads what it read), and no error.
    idle = _context(_scrape(2), _scrape(2), gauges)
    want = EXPECTED[name] if name.endswith("pending_mean") else None
    assert layers.evaluate_all([{"name": name}], idle) == {name: want}
    # A program that does not export the family (the parent commit): no
    # value, and no error.
    parent = metrics.parse_prometheus(
        'vdt:step_phase_seconds_count{phase="launch"} 7\n'
        'vdt:step_phase_seconds_sum{phase="launch"} 0.1\n'
        "vdt:step_period_seconds_count 7\n"
        "vdt:frontend_output_lag_seconds_count 7\n")
    assert layers.evaluate_all(
        [{"name": name}],
        _context(parent, parent, [parent])) == {name: None}
