"""The reader file that ISSUE 36 adds, ``kernel.mla_prefill_tokens_per_step``,
through ``layers.evaluate_all`` on ``/metrics`` snapshots written by the
program's own renderer, as ``test_benchmark_host_transfers_reader`` does
for ISSUE 26's. The file is data and nothing that was there is edited:
the manifest loads as before, and its entry waits for a ``benchmark`` PR
(PERF.md, Open questions: ``test_benchmark_bailing.py`` pins the last
three entries of ``per_layer`` by position)."""

import json
import os

import pytest

from benchmarks import layers, metrics, run
from tests.benchmarking.test_benchmark_step_readers import _context
from vllm_distributed_tpu.metrics.prometheus import (HISTOGRAMS,
                                                     render_metrics)
from vllm_distributed_tpu.metrics.stats import (STEP_TOKEN_BUCKETS,
                                                Histogram)

NAME = "kernel.mla_prefill_tokens_per_step"
# Rows served in the multi-head form, a step: before the window, and
# inside it seven decode steps around one chunk-bearing step.
OBSERVED = ([0, 2001], [0, 0, 0, 2001, 0, 0, 0, 0])


def _scrape(upto: int) -> list:
    h = Histogram(STEP_TOKEN_BUCKETS)
    for part in OBSERVED[:upto]:
        for v in part:
            h.observe(v)
    return metrics.parse_prometheus(
        render_metrics({"step_latent_prefill_tokens": h.to_dict()}))


@pytest.mark.parametrize("before,after,want", [
    (1, 2, 2001 / 8),   # the window's steps
    (2, 2, None),       # nothing observed inside the window
    (None, None, None),  # the parent: a program without the family
])
def test_reader_file_reads_the_window(before, after, want):
    parent = metrics.parse_prometheus(
        "vdt:step_kv_tokens_read_count 7\nvdt:step_kv_tokens_read_sum 70.0\n")
    ctx = _context(_scrape(before) if before else parent,
                   _scrape(after) if after else parent)
    got = layers.evaluate_all([{"name": NAME}], ctx)
    assert got == {NAME: None if want is None else pytest.approx(want)}


def test_the_reader_names_a_family_the_program_exports():
    spec = layers.load_spec(NAME)
    assert set(spec) == {"source", "what"} and len(spec["what"]) > 80
    assert spec["source"] == {"kind": "prometheus",
                              "metric": "vdt:step_latent_prefill_tokens",
                              "reduce": "hist_mean"}
    assert HISTOGRAMS["step_latent_prefill_tokens"][0] == \
        spec["source"]["metric"]


def test_the_manifest_still_loads():
    """Every cell of the accepted manifest builds its plan with the new
    file beside the old ones, and both long-context cells report the
    ``itl_p95_ms`` that the owed entry is to move."""
    manifest = run.load_json(run.MANIFEST)
    assert NAME not in {m["name"] for m in manifest["per_layer"]}
    for w in manifest["workloads"]:
        cell = run.load_cell(w["name"], run.HERE)
        assert run.cell_metrics(manifest, w["name"], "per_layer")
        assert cell["config"] == w["config"]
    for cell in ("gigachat3-702b-ep16.long-context",
                 "ling3-flash-ep4.long-context"):
        assert "itl_p95_ms" in {
            m["name"] for m in run.cell_metrics(manifest, cell,
                                                "end_to_end")}
    with open(os.path.join(run.HERE, "layer_metrics", f"{NAME}.json")) as f:
        assert set(json.load(f)) == {"source", "what"}
