"""The reader file and manifest entry that ISSUE 54 adds,
``kernel.decode_kv_tokens_walked_per_step``, through
``layers.evaluate_all`` on ``/metrics`` snapshots written by the program's
own renderer, as ``test_benchmark_mla_prefill_tokens`` does for ISSUE
36's. The file is data, and nothing that was there is edited."""

import pytest

from benchmarks import layers, metrics, run
from tests.benchmarking.test_benchmark_step_readers import _context
from vllm_distributed_tpu.metrics.prometheus import (HISTOGRAMS,
                                                     render_metrics)
from vllm_distributed_tpu.metrics.stats import (STEP_TOKEN_BUCKETS,
                                                Histogram)

NAME = "kernel.decode_kv_tokens_walked_per_step"
PAGED = ["olmo2-1b.chat", "olmoe-1b-7b.chat", "olmo2-1b.decode-heavy",
         "olmoe-1b-7b.decode-heavy", "granite-4.0-h-micro.decode-heavy",
         "zaya1-8b.decode-heavy", "command-a-plus-ep8.long-document",
         "smallthinker-21b-a3b.mixed-length"]
# Token slots the decode groups moved, a step: before the window, and
# the window's four steps.
OBSERVED = ([23808, 24064], [23936, 24192, 23808, 24320])


def _scrape(upto: int) -> list:
    h = Histogram(STEP_TOKEN_BUCKETS)
    for part in OBSERVED[:upto]:
        for v in part:
            h.observe(v)
    return metrics.parse_prometheus(
        render_metrics({"step_decode_kv_tokens_walked": h.to_dict()}))


@pytest.mark.parametrize("before,after,want", [
    (1, 2, sum(OBSERVED[1]) / 4),   # the window's steps
    (2, 2, None),                   # nothing observed inside the window
    (None, None, None),             # the parent: no such family
], ids=["window", "empty_window", "parent"])
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
                              "metric": "vdt:step_decode_kv_tokens_walked",
                              "reduce": "hist_mean"}
    assert HISTOGRAMS["step_decode_kv_tokens_walked"][0] == \
        spec["source"]["metric"]


def test_the_entry_agrees_with_the_reader_and_lists_the_paged_cells():
    """The entry agrees with the reader: looked up by name, the eight
    cells whose decode path runs the paged attention kernel (all among
    those ``kernel.attn_share`` lists), each of which reports the
    ``tpot_p50_ms`` it moves and the ``kernel.kv_tokens_read_per_step``
    it is read against. Where the entry sits, and which cells later PRs
    append to either list, is not this test's to say."""
    manifest = run.load_json(run.MANIFEST)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    entry = dict(entries[NAME])
    listed = entry.pop("workloads")
    assert entry == {"name": NAME, "unit": "tokens", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tpot_p50_ms"}
    assert set(PAGED) <= set(listed)
    assert set(PAGED) <= set(entries["kernel.attn_share"]["workloads"])
    for cell in PAGED:
        assert cell in entries["kernel.kv_tokens_read_per_step"]["workloads"]
        assert "tpot_p50_ms" in {
            m["name"] for m in run.cell_metrics(manifest, cell,
                                                "end_to_end")}
        assert NAME in {m["name"] for m in run.cell_metrics(
            manifest, cell, "per_layer")}
    for w in manifest["workloads"]:
        if w["name"] not in listed:
            assert NAME not in {m["name"] for m in run.cell_metrics(
                manifest, w["name"], "per_layer")}
