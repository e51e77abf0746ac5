#!/usr/bin/env python3
"""What the paged attention kernel's decode groups move for what their
queries see, by a closed-loop cell's own lengths and no chip (PR 54).

    python3 scripts/decode_group_fill.py [<cell> ...]
    python3 scripts/decode_group_fill.py --cost [<other tree>]

A cell's clients hold the (prompt, output) pairs ``benchmarks/traffic.py``
deals them; a step of the steady batch is every client at a uniformly
drawn token of its reply, the rows in a drawn order (a slot is taken when
a request ends: arrival order knows nothing of length). Over ``STEPS``
such steps, per step: the K/V tokens read (``vdt:step_kv_tokens_read``),
the token slots the groups' DMAs move in one full layer by
``ops/pallas_attention.decode_kv_tokens_walked``
(``vdt:step_decode_kv_tokens_walked``: whole 128-position blocks up to
each sequence's own end), the same by the rule that stood before PR 54
(groups of ``sb`` in arrival order, every live slot fetching on every
trip of its group's longest walk: ``cnt x trips x blk`` a group), and the
groups' trips (each a product over ``sb x KVH x blk`` columns) in arrival
order and in length order. The rule that stood before is given twice:
with the rows in the drawn order, and ``_admitted``, oldest request first
(the scheduler's running order; where replies are longer than prompts,
the ``decode-heavy`` cells, that is nearly length order already). A window layer's walk is the same with
``min(kv_len, window)`` positions from the block that holds
``kv_len - window``. Prints one JSON line a cell.

``--cost``: what the order and the counter cost the host a step, on the
host this runs on (through the chip tool: the chip's host; it touches no
chip): microseconds a call of ``build_partition_descriptor`` on the fast
decode path and on the ``q_len`` scan for 48 and 96 single-token rows,
this tree's and, given another checkout, that tree's, and of the
counter's own lines.
"""
import importlib.util
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import traffic  # noqa: E402
from vllm_distributed_tpu.ops.pallas_attention import (  # noqa: E402
    decode_group_size, decode_kv_tokens_walked)

BLK, STEPS = 128, 2000


def blocks(kv: np.ndarray, window: int) -> np.ndarray:
    """The kv blocks a single query at ``kv - 1`` sees."""
    first = np.maximum(kv - window, 0) // BLK if window else 0
    return (kv - 1) // BLK - first + 1


def group_sums(n_blocks: np.ndarray, sb: int) -> tuple[int, int]:
    """(slots x trips, trips) summed over groups of ``sb`` in the order
    given."""
    moved = trips = 0
    for g in range(0, len(n_blocks), sb):
        part = n_blocks[g:g + sb]
        moved += len(part) * int(part.max())
        trips += int(part.max())
    return moved, trips


def load_cell(cell: str) -> tuple[dict, dict, dict]:
    """(the cell, its configuration, its traffic mix)."""
    with open(os.path.join(ROOT, "benchmarks", "cells", cell + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           spec["config"] + ".json")) as f:
        cf = json.load(f)
    return spec, cf, traffic.load_mix(spec["traffic"])


def window_of(cf: dict) -> int:
    return cf.get("sliding_window") or cf.get("sliding_window_size") or 0


def draw_steps(spec: dict, cf: dict, mix: dict, steps: int):
    """``steps`` steps of the cell's steady batch: each the clients'
    context lengths in a drawn row order, and how many tokens of its
    reply each has behind it."""
    n = int(spec["load"]["clients"])
    prompts = traffic.stratified_lengths(mix["prompt_len"], n)
    outputs = np.random.default_rng(n).permutation(
        traffic.stratified_lengths(mix["output_len"], n))
    prompts = np.minimum(prompts, cf["engine_args"]["max_model_len"]
                         - outputs).astype(np.int64)
    rng = np.random.default_rng(54)
    for _ in range(steps):
        done = (rng.random(n) * outputs).astype(np.int64)
        rows = rng.permutation(n)
        yield (prompts + 1 + done)[rows], done[rows]


def cell_fill(cell: str) -> dict:
    spec, cf, mix = load_cell(cell)
    if mix["loop"] != "closed":
        return {"cell": cell, "skipped": "open loop: read the two "
                "counters off a run's /metrics"}
    n = int(spec["load"]["clients"])
    sb = decode_group_size(cf["num_attention_heads"],
                           cf["num_key_value_heads"])
    window = window_of(cf)
    tot = dict.fromkeys(("read", "walked", "walked_before", "trips",
                         "trips_before", "walked_before_admitted",
                         "trips_before_admitted", "window_walked",
                         "window_walked_before", "window_read"), 0)
    for kv, done in draw_steps(spec, cf, mix, STEPS):
        by_length = kv[np.argsort(-kv, kind="stable")]
        admitted = kv[np.argsort(-done, kind="stable")]
        tot["read"] += int(kv.sum())
        tot["walked"] += decode_kv_tokens_walked(kv, BLK)
        moved, trips = group_sums(blocks(kv, 0), sb)
        tot["walked_before"] += moved * BLK
        tot["trips_before"] += trips
        moved, trips = group_sums(blocks(admitted, 0), sb)
        tot["walked_before_admitted"] += moved * BLK
        tot["trips_before_admitted"] += trips
        tot["trips"] += group_sums(blocks(by_length, 0), sb)[1]
        if window:
            tot["window_read"] += int(np.minimum(kv, window).sum())
            tot["window_walked"] += int(blocks(kv, window).sum()) * BLK
            tot["window_walked_before"] += group_sums(
                blocks(kv, window), sb)[0] * BLK
    out = {"cell": cell, "clients": n, "sb": sb, "window": window,
           "kv_tokens_read_per_step": tot["read"] / STEPS,
           "walked_per_step": tot["walked"] / STEPS,
           "walked_per_step_before": tot["walked_before"] / STEPS,
           "walked_over_read": tot["walked"] / tot["read"],
           "walked_over_read_before": tot["walked_before"] / tot["read"],
           "walked_over_read_before_admitted": (
               tot["walked_before_admitted"] / tot["read"]),
           "trips_per_step": tot["trips"] / STEPS,
           "trips_per_step_before": tot["trips_before"] / STEPS,
           "trips_per_step_before_admitted": (
               tot["trips_before_admitted"] / STEPS)}
    if window:
        out.update(
            window_walked_over_read=tot["window_walked"] / tot["window_read"],
            window_walked_over_read_before=(tot["window_walked_before"]
                                            / tot["window_read"]))
    return out


def host_cost(other: str | None) -> None:
    import vllm_distributed_tpu.ops.pallas_attention as this
    trees = {"this": this}
    if other:
        spec = importlib.util.spec_from_file_location(
            "pallas_attention_other", os.path.join(
                other, "vllm_distributed_tpu", "ops", "pallas_attention.py"))
        trees["other"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trees["other"])

    def us(fn, calls=20000):
        fn()
        t0 = time.perf_counter()
        for _ in range(calls):
            fn()
        return round((time.perf_counter() - t0) / calls * 1e6, 2)

    rng = np.random.default_rng(54)
    for n in (48, 96):
        si = np.zeros((128, 4), np.int32)
        si[:n] = np.stack([np.arange(n), np.ones(n, np.int64),
                           rng.integers(32, 12288, n), np.arange(n)], axis=1)
        out = {"rows": n}
        for name, ops in trees.items():
            P = ops.num_partition_programs(64, 128, bq=32, sb=4,
                                           num_kv_writes=n)
            for way, rows in (("decode_rows", True), ("scan", False)):
                out[f"{name}.{way}_us"] = us(
                    lambda: ops.build_partition_descriptor(
                        si, n, bq=32, sb=4, num_programs=P, num_kv_writes=n,
                        decode_rows=(np.arange(n, dtype=np.int32)
                                     if rows else None)))
        out["counter_us"] = us(lambda: this.decode_kv_tokens_walked(
            si[:n, 2][si[:n, 1] == 1], 16 * this.kv_block_pages(16, 256)))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--cost"]:
        host_cost(sys.argv[2] if len(sys.argv) > 2 else None)
        sys.exit(0)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        paged = next(m["workloads"] for m in json.load(f)["per_layer"]
                     if m["name"] == "kernel.attn_share")
    for cell in sys.argv[1:] or paged:
        print(json.dumps(cell_fill(cell)), flush=True)
