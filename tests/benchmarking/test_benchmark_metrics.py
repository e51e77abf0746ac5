"""Metric arithmetic on hand-made timestamps and Prometheus snapshots."""

import pytest

from benchmarks import metrics as M


def rec(due, sent, chunk_times, want=None, end="auto", ok=True, **kw):
    chunks = [[t, 1] for t in chunk_times]
    return dict(due=due, sent=sent, chunks=chunks,
                end=(chunk_times[-1] if end == "auto" else end), ok=ok,
                want=len(chunks) if want is None else want, error=None, **kw)


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert M.percentile(v, 95) == 95 and M.percentile(v, 50) == 50
    assert M.percentile([3.0], 95) == 3.0
    assert M.percentile([1, 2, 3, 4], 95) == 4
    with pytest.raises(ValueError):
        M.percentile([], 50)


def test_open_loop_metrics_time_from_due():
    recs = [
        # due 10.0, sent late at 10.2, first token 10.5: TTFT is 0.5
        rec(10.0, 10.2, [10.5, 10.6, 10.8, 11.1]),
        rec(11.0, 11.0, [11.1, 11.2]),
        # due before the window: not measured
        rec(5.0, 5.0, [5.1, 5.2]),
        # failed: counted, in no latency sample
        rec(12.0, 12.0, [12.5], ok=False),
        # cut short (asked for 5 tokens, got 2)
        rec(13.0, 13.0, [13.1, 13.2], want=5),
        # never finished before the drain cap
        rec(14.0, 14.0, [14.5, 14.9], end=None),
    ]
    out = M.end_to_end(recs, "open", 10.0, 20.0)
    assert out["attempted"] == 5 and out["failed"] == 3
    m = out["metrics"]
    assert m["ttft_p95_ms"] == pytest.approx(500.0)
    assert m["ttft_p50_ms"] == pytest.approx(100.0)
    # gaps of the good requests: .1 .2 .3 and .1 -> p95 is the largest
    assert m["itl_p95_ms"] == pytest.approx(300.0)
    # tpot: (11.1-10.5)/3 = .2 and (11.2-11.1)/1 = .1 -> median (nearest
    # rank of 2) is the lower
    assert m["tpot_p50_ms"] == pytest.approx(100.0)
    assert m["client.send_lateness_p95_ms"] == pytest.approx(200.0)
    assert out["samples"] == {"ttft": 2, "gaps": 4, "tpot": 2,
                              "tokens_in_window": 11}


def test_chunks_with_several_tokens_count_tokens_not_chunks():
    r = dict(due=0.0, sent=0.0, chunks=[[1.0, 1], [2.0, 3]], end=2.0,
             ok=True, want=4, error=None)
    assert M.request_ok(r) and M.tpot_s(r) == pytest.approx(1.0 / 3)
    assert M.gaps_s(r) == [1.0]
    assert M.tpot_s(dict(r, chunks=[[1.0, 1]])) is None


def test_closed_loop_counts_what_fell_in_the_window():
    recs = [
        rec(0.0, 0.0, [9.0, 9.5, 10.5, 11.0]),            # ended inside
        rec(0.0, 0.0, [18.0, 19.0, 21.0]),                # ended after
        dict(rec(0.0, 15.0, [16.0], end=None), cancelled=True),  # cut
        rec(0.0, 12.0, [12.5], ok=False),                 # failed inside
    ]
    out = M.end_to_end(recs, "closed", 10.0, 20.0)
    assert out["attempted"] == 2 and out["failed"] == 1
    # tokens received in [10, 20): 10.5 11.0 18.0 19.0 16.0 12.5
    assert out["metrics"]["output_tokens_per_s"] == pytest.approx(0.6)
    # gaps whose later chunk fell inside: 1.0 (9.5->10.5) .5 and 1.0
    assert out["metrics"]["itl_p95_ms"] == pytest.approx(1000.0)
    assert "ttft_p95_ms" not in out["metrics"]


BEFORE = """# HELP vdt:num_preemptions_total x
# TYPE vdt:num_preemptions_total counter
vdt:num_preemptions_total 3.0
vdt:recompiles_total{worker="dp0-h0"} 0.0
vdt:step_phase_seconds_bucket{phase="schedule",le="0.001"} 10
vdt:step_phase_seconds_bucket{phase="schedule",le="0.002"} 10
vdt:step_phase_seconds_bucket{phase="schedule",le="+Inf"} 10
vdt:step_phase_seconds_sum{phase="schedule"} 0.005
vdt:step_phase_seconds_count{phase="schedule"} 10
vdt:step_phase_seconds_bucket{phase="wait",le="0.001"} 0
vdt:step_phase_seconds_bucket{phase="wait",le="+Inf"} 10
vdt:step_phase_seconds_sum{phase="wait"} 1.0
vdt:step_phase_seconds_count{phase="wait"} 10
vdt:num_requests_waiting 2.0
"""
AFTER = """vdt:num_preemptions_total 5.0
vdt:recompiles_total{worker="dp0-h0"} 1.0
vdt:step_phase_seconds_bucket{phase="schedule",le="0.001"} 20
vdt:step_phase_seconds_bucket{phase="schedule",le="0.002"} 50
vdt:step_phase_seconds_bucket{phase="schedule",le="+Inf"} 110
vdt:step_phase_seconds_sum{phase="schedule"} 0.305
vdt:step_phase_seconds_count{phase="schedule"} 110
vdt:step_phase_seconds_bucket{phase="wait",le="0.001"} 0
vdt:step_phase_seconds_bucket{phase="wait",le="+Inf"} 20
vdt:step_phase_seconds_sum{phase="wait"} 3.0
vdt:step_phase_seconds_count{phase="wait"} 20
vdt:num_requests_waiting 6.0
"""


def test_prometheus_reductions_on_two_snapshots():
    b, a = M.parse_prometheus(BEFORE), M.parse_prometheus(AFTER)
    assert ("vdt:recompiles_total", {"worker": "dp0-h0"}, 1.0) in a
    assert M.counter_delta(b, a, "vdt:num_preemptions_total") == 2.0
    assert M.counter_delta(b, a, "vdt:recompiles_total") == 1.0
    sched = {"phase": "schedule"}
    name = "vdt:step_phase_seconds"
    # 100 observations in the window summing to 0.3 s
    assert M.histogram_mean(b, a, name, sched) == pytest.approx(0.003)
    assert M.histogram_mean(b, a, name, {"phase": "wait"}) == \
        pytest.approx(0.2)
    # window buckets: <=1ms 10, <=2ms 40, +Inf 100. The median (rank 50)
    # lies in +Inf: the last finite bound. Rank 25 lies halfway through
    # the (1ms, 2ms] bucket.
    assert M.histogram_quantile(b, a, name, 0.5, sched) == 0.002
    assert M.histogram_quantile(b, a, name, 0.25, sched) == \
        pytest.approx(0.0015)
    assert M.histogram_quantile(b, a, name, 0.05, sched) == \
        pytest.approx(0.0005)
    assert M.histogram_quantile(b, b, name, 0.5, sched) is None
    assert M.gauge_mean([b, a], "vdt:num_requests_waiting") == 4.0
    assert M.gauge_mean([], "vdt:num_requests_waiting") is None
