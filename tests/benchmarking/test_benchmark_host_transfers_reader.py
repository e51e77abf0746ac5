"""The reader file that ISSUE 26 adds, ``runner.host_transfers_per_step``,
through ``layers.evaluate_all`` on ``/metrics`` snapshots written by the
program's own renderer, as ``test_benchmark_step_readers`` does for ISSUE
25's thirteen."""

import json
import os

import pytest

from benchmarks import layers, metrics
from tests.benchmarking.test_benchmark_step_readers import _context
from vllm_distributed_tpu.metrics.prometheus import (HISTOGRAMS,
                                                     render_metrics)
from vllm_distributed_tpu.metrics.stats import (STEP_TRANSFER_BUCKETS,
                                                Histogram)

NAME = "runner.host_transfers_per_step"
# Transfers a step, before the window (one per array, as the parent
# ships them) and inside it (the default path, and one step that scored
# prompt logprobs).
OBSERVED = ([18, 18], [3, 3, 3, 5])


def _scrape(upto: int) -> list:
    h = Histogram(STEP_TRANSFER_BUCKETS)
    for part in OBSERVED[:upto]:
        for v in part:
            h.observe(v)
    return metrics.parse_prometheus(
        render_metrics({"step_host_transfers": h.to_dict()}))


def test_reader_file_reads_the_window():
    ctx = _context(_scrape(1), _scrape(2))
    assert layers.evaluate_all([{"name": NAME}], ctx) == {
        NAME: pytest.approx(3.5)}
    # Nothing observed inside the window, or a program that does not
    # export the family (the parent commit): no value, and no error.
    idle = _context(_scrape(2), _scrape(2))
    assert layers.evaluate_all([{"name": NAME}], idle) == {NAME: None}
    parent = metrics.parse_prometheus(
        "vdt:step_kv_tokens_read_count 7\n"
        "vdt:step_kv_tokens_read_sum 70.0\n")
    assert layers.evaluate_all(
        [{"name": NAME}], _context(parent, parent)) == {NAME: None}


def test_the_reader_names_a_family_the_program_exports():
    """The file has the shape of its neighbours, the family it names is
    one that ``/metrics`` renders, and the manifest's entry (ISSUE 27)
    reports it in the four cells of its day (a later cell adds its name
    to the list where it wants the count, and need not)."""
    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry, ) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "model runner",
                     "moves": "tpot_p50_ms",
                     "workloads": entry["workloads"]}
    assert {"olmo2-1b.chat", "olmoe-1b-7b.chat", "olmo2-1b.decode-heavy",
            "olmoe-1b-7b.decode-heavy"} <= set(entry["workloads"]) <= {
                w["name"] for w in manifest["workloads"]}
    spec = layers.load_spec(NAME)
    assert set(spec) == {"source", "what"}
    assert spec["source"] == {"kind": "prometheus",
                              "metric": "vdt:step_host_transfers",
                              "reduce": "hist_mean"}
    assert HISTOGRAMS["step_host_transfers"][0] == spec["source"]["metric"]
