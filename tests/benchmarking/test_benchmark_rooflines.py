"""The ``roofline_share`` reader and its work modules on the CPU: a tiny
synthetic trace with two ``/metrics`` scrapes gives a known percent; what
cannot be read gives ``None``; each work function agrees with a count by
hand for both configuration files; and the readings recorded from chip
runs (``PERF.md`` section 5, the ledger's PR 26 lines) stay under 100%,
above which a count is at fault."""

import json
import os

import pytest

from benchmarks import layers, metrics, run
from benchmarks.rooflines import grouped_gemm, paged_attention

V5E = "TPU v5 lite"
CONFIGS = {name: run.load_json(run.HERE, "configs", f"{name}.json")
           for name in ("olmo2-1b", "olmoe-1b-7b")}
ATTN = {"name": "kernel.attn_roofline"}
GEMM = {"name": "kernel.moe_gemm_roofline"}


def scrape(steps: int, kv_tokens: float, decode: float, prefill: float,
           without=()) -> list:
    """A /metrics scrape after ``steps`` steps that each held these many
    tokens (histogram sums and counts are all the readers take)."""
    text = ""
    for fam, labels, per_step in (
            ("vdt:step_kv_tokens_read", "", kv_tokens),
            ("vdt:step_tokens", 'kind="decode"', decode),
            ("vdt:step_tokens", 'kind="prefill"', prefill)):
        if fam in without:
            continue
        lab = f"{{{labels}}}" if labels else ""
        text += (f"{fam}_sum{lab} {steps * per_step}\n"
                 f"{fam}_count{lab} {steps}\n")
    return metrics.parse_prometheus(text)


def context(config, ops, before, after, kind=V5E, steps=2, capture=None):
    ctx = layers.Context(before, after, [], {}, "/nonexistent",
                         config=CONFIGS[config], device_kind=kind,
                         capture=capture)
    ctx._planes = {"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {
        "python3": [("vdt_step", float(i), 0.5) for i in range(steps)]}}
    return ctx


# Two steps: the attention kernel 16 times 0.5 ms in each (8 ms a step),
# three grouped GEMMs in 8 layers at 0.5 ms each (12 ms a step).
OPS = [("unified_write_attend_pallas.6", s + 0.001 * i, 0.0005)
       for s in (0.0, 1.0) for i in range(16)] + \
      [("ragged-dot-none.3", s + 0.1 + 0.001 * i, 0.0005)
       for s in (0.0, 1.0) for i in range(24)] + \
      [("fusion.9", 0.3, 0.004)]


def test_a_known_kernel_time_and_token_count_give_a_known_percent():
    before, after = scrape(10, 5, 1, 0), scrape(30, 20000, 30, 0)
    # (30 x 20000 - 10 x 5) / 20 steps = 29 997.5 tokens x 128 KiB over
    # 819 GB/s = 4.8007 ms, of 8 ms.
    ctx = context("olmo2-1b", OPS, before, after)
    want = 100 * (29997.5 * 131072 / 819e9) / 8e-3
    assert layers.evaluate_all([ATTN], ctx) == {
        ATTN["name"]: pytest.approx(want)}
    assert 59 < want < 61
    # OLMoE, 44.5 tokens a step: every expert of 8 layers, 6.44 GB over
    # 819 GB/s = 7.866 ms of 12 ms; the operations (0.46 ms) lose.
    ctx = context("olmoe-1b-7b", OPS, before, after)
    got = layers.evaluate_all([ATTN, GEMM], ctx)
    assert got[GEMM["name"]] == pytest.approx(
        100 * (8 * 64 * 3 * 2048 * 1024 * 2 / 819e9) / 12e-3)
    assert got[ATTN["name"]] == pytest.approx(want / 2)   # 64 KiB a token


def test_the_capture_s_own_steps_are_counted_where_scrapes_lie_in_it():
    window = (scrape(10, 5, 1, 0), scrape(30, 20000, 30, 0))
    inside = (scrape(20, 1000, 1, 0), scrape(24, 10000, 30, 0))
    ctx = context("olmo2-1b", OPS, *window, capture=inside)
    assert ctx.step_mean("vdt:step_kv_tokens_read") == pytest.approx(
        (24 * 10000 - 20 * 1000) / 4)
    got = layers.evaluate_all([ATTN], ctx)[ATTN["name"]]
    assert got == pytest.approx(100 * (55000 * 131072 / 819e9) / 8e-3)
    # Two scrapes with no step between them: the window's mean stands in.
    ctx = context("olmo2-1b", OPS, *window, capture=(inside[0], inside[0]))
    assert ctx.step_mean("vdt:step_kv_tokens_read") == pytest.approx(29997.5)
    # The scrapes of a run: those between the profiler's two marks.
    timed = [(t, [("x", {}, t)]) for t in (1.0, 2.0, 2.5, 3.0, 4.0)]
    marks = {"profile_started": 1.5, "profile_stop_call": 3.5}
    assert layers.capture_scrapes(timed, marks) == (timed[1][1], timed[3][1])
    assert layers.capture_scrapes(timed[:2], marks) is None
    assert layers.capture_scrapes(timed, {}) is None


@pytest.mark.parametrize("why,kwargs", [
    ("a counter the work module needs is missing",
     {"without": ("vdt:step_kv_tokens_read", "vdt:step_tokens")}),
    ("no peaks for this device kind", {"kind": "TPU v9 imagined"}),
    ("no such kernel in the capture", {"ops": [("fusion.9", 0.3, 0.004)]}),
    ("no step between the scrapes", {"idle": True}),
    ("no device plane at all", {"ops": []})])
def test_nothing_to_divide_gives_none_and_no_exception(why, kwargs):
    without = kwargs.get("without", ())
    before = scrape(10, 5, 1, 0, without)
    after = before if kwargs.get("idle") else \
        scrape(30, 20000, 30, 0, without)
    ctx = context("olmoe-1b-7b", kwargs.get("ops", OPS), before, after,
                  kind=kwargs.get("kind", V5E))
    assert layers.evaluate_all([ATTN, GEMM], ctx) == {
        ATTN["name"]: None, GEMM["name"]: None}, why


def test_work_functions_against_a_count_by_hand():
    assert paged_attention.kv_bytes_per_token(CONFIGS["olmo2-1b"]) == \
        16 * 2 * 16 * 128 * 2 == 128 * 1024
    assert paged_attention.kv_bytes_per_token(CONFIGS["olmoe-1b-7b"]) == \
        8 * 2 * 16 * 128 * 2 == 64 * 1024
    before, after = scrape(0, 0, 0, 0), scrape(100, 19903, 30, 0)
    for name, per_token in (("olmo2-1b", 131072), ("olmoe-1b-7b", 65536)):
        ctx = context(name, OPS, before, after)
        assert paged_attention.work(CONFIGS[name], ctx) == {
            "bytes": pytest.approx(19903 * per_token), "flops": 0.0}
    # 2.609 GB a step for `olmo2-1b` (PERF.md section 5: 2.61 GB).
    assert 19903 * 131072 == pytest.approx(2.609e9, rel=1e-3)
    # The dense model has no expert layer: nothing to count.
    assert grouped_gemm.work(CONFIGS["olmo2-1b"], ctx) is None
    one_expert = 3 * 2048 * 1024
    ctx = context("olmoe-1b-7b", OPS, before, after)
    assert grouped_gemm.work(CONFIGS["olmoe-1b-7b"], ctx) == {
        "bytes": 8 * 64 * one_expert * 2,          # 6.44 GB: all 64 hit
        "flops": 2.0 * 30 * 8 * one_expert * 8}    # 24.2 GFLOP
    assert 8 * 64 * one_expert * 2 == 6442450944
    # Five tokens hit at most 40 of the 64 experts; 200 prefill tokens
    # beside them make the operations 1.65e11, still under the bytes.
    few = context("olmoe-1b-7b", OPS, before, scrape(100, 900, 5, 0))
    assert grouped_gemm.work(CONFIGS["olmoe-1b-7b"], few)["bytes"] == \
        8 * 40 * one_expert * 2
    mixed = context("olmoe-1b-7b", OPS, before, scrape(100, 900, 5, 200))
    assert grouped_gemm.work(CONFIGS["olmoe-1b-7b"], mixed) == {
        "bytes": 8 * 64 * one_expert * 2,
        "flops": 2.0 * 205 * 8 * one_expert * 8}


with open(os.path.join(os.path.dirname(__file__), "data",
                       "recorded_rooflines.json")) as _f:
    RECORDED = json.load(_f)


@pytest.mark.parametrize("rec", RECORDED, ids=[
    f"{r['cell']}-{r['origin']}" for r in RECORDED])
def test_recorded_chip_readings_stay_under_100_percent(rec):
    """Kernel seconds and steps of a capture, and the counters of the
    same run, as chip runs gave them (the file says which): the share
    each gives is the one recorded, and no count has outrun the chip."""
    config = rec["cell"].rpartition(".")[0]
    ops = [(rec["op"], 0.0, rec["kernel_s"])]
    after = scrape(1000, rec["kv_tokens"], rec["decode_tokens"],
                   rec["prefill_tokens"])
    ctx = context(config, ops, scrape(0, 0, 0, 0), after,
                  steps=rec["steps"])
    got = layers.evaluate_all([{"name": rec["metric"]}], ctx)[rec["metric"]]
    assert got == pytest.approx(rec["share"], rel=0.02)
    assert 5 < got < 100
