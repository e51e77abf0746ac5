#!/usr/bin/env python3
"""What a grouped GEMM's row count costs, the kernel alone on the chip.

    python3 scripts/ragged_rows.py [--configs olmoe,zaya,smallthinker]
        [--reps 3] [--iters 10] [--seed 0]

XLA's ``jax.lax.ragged_dot`` takes its row tile from what divides the
rows it is handed (PERF.md, Findings PR 40 and PR 48), so the one-chip
whole-expert dispatch (``models/mixtral.py`` ``moe_dispatch``) pads its
sorted picks behind the last group up to ``padded_picks`` rows. This is
the measurement that rule was chosen from, kept so that it can be made
again on another chip or another jaxlib. For each whole-expert
configuration of the benchmark (groups, H, I, k) and every token count
its programs have (the token buckets 16 ... 2 048, plus the attention
kernel's 8), it times at ``T x k`` rows and at each candidate above them
(the next odd multiples of 32, 64, 128 and 256, the next multiple of
512, and whatever ``padded_picks`` gives today):

- ``gemm``: one ``ragged_dot`` ``[rows, H] x [G, H, I]``;
- ``dispatch``: the branch's whole work on sorted picks: the row gather,
  the three products of ``_expert_ffn``, the select that drops the rows
  no group holds, the weights and the ``segment_sum`` back to T rows;

with the group sizes an even router gives (a multinomial draw) and a
skewed one's (Zipf over the experts). The padding rows lie in NO group,
as in the program. The weights are arguments of every jitted piece, not
closed over (PR 40). Times are milliseconds a call: the least over
``--reps`` of the mean over ``--iters`` calls behind one
``block_until_ready``. Prints one table and writes it to
``chiprun_out/ragged_rows.json``. Nothing a cell runs imports this.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vllm_distributed_tpu.models.mixtral import padded_picks  # noqa: E402
from vllm_distributed_tpu.ops.pallas_attention import Q_TILE_PAD  # noqa: E402
from vllm_distributed_tpu.utils import (cdiv, make_buckets,  # noqa: E402
                                        round_up)

# (groups, H, I, k, outcomes): ``outcomes`` counts a pick that is no
# expert (ZAYA's skip), whose rows sort behind every group.
CONFIGS = {
    "olmoe": (64, 2048, 1024, 8, 64),
    "zaya": (16, 2048, 2048, 1, 17),
    "smallthinker": (64, 2560, 768, 6, 64),
}
TILES = (32, 64, 128, 256)


def candidates(rows: int, groups: int) -> list[int]:
    """``rows`` as they are, what the rule makes of them, the next odd
    multiple of each tile (so that the tile is that and no larger) and
    the next multiple of 512, the largest tile there is."""
    out = {rows, padded_picks(rows, groups), round_up(rows, 512)}
    out.update((cdiv(rows, t) | 1) * t for t in TILES)
    return sorted(out)


def group_sizes(rng, rows: int, groups: int, outcomes: int,
                skewed: bool) -> np.ndarray:
    p = 1.0 / np.arange(1, outcomes + 1) if skewed else np.ones(outcomes)
    return rng.multinomial(rows, p / p.sum())[:groups].astype(np.int32)


@jax.jit
def gemm(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes)


@jax.jit
def dispatch(x, w_gate, w_up, w_down, st, sw, sizes):
    xs = x[st]
    g = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes))
    u = jax.lax.ragged_dot(xs, w_up, sizes)
    y = jax.lax.ragged_dot(g * u, w_down, sizes)
    in_group = jnp.arange(xs.shape[0]) < sizes.sum()
    y = jnp.where(in_group[:, None], y, 0) * sw[:, None].astype(y.dtype)
    return jax.ops.segment_sum(y, st, num_segments=x.shape[0])


def timed(fn, args, reps: int, iters: int) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--tokens", default="",
                    help="comma list of T; default: the programs' own")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    rng = np.random.default_rng(a.seed)
    tokens = ([int(t) for t in a.tokens.split(",")] if a.tokens else
              [t + Q_TILE_PAD for t in make_buckets(16, 2048)])
    dev = jax.devices()[0]
    table = []
    print(f"# {dev.platform} {dev.device_kind} jax {jax.__version__}")
    print("config T rows padded | gemm even skew | dispatch even skew"
          " | rule")
    for name in a.configs.split(","):
        G, H, I, k, outcomes = CONFIGS[name]
        keys = jax.random.split(jax.random.PRNGKey(a.seed), 4)
        w_gate, w_up = (jax.random.normal(kk, (G, H, I), jnp.bfloat16) * .02
                        for kk in keys[:2])
        w_down = jax.random.normal(keys[2], (G, I, H), jnp.bfloat16) * .02
        for T in tokens:
            rows = T * k
            x = jax.random.normal(keys[3], (T, H), jnp.bfloat16)
            sizes = [group_sizes(rng, rows, G, outcomes, s)
                     for s in (False, True)]
            rule = padded_picks(rows, G)
            for padded in candidates(rows, G):
                # Sorted picks: any token order does for a timing; the
                # padding rows read token 0 with weight 0.
                st = np.zeros(padded, np.int32)
                st[:rows] = rng.permutation(rows) // k
                sw = np.zeros(padded, np.float32)
                sw[:rows] = 1.0 / k
                st, sw = jnp.asarray(st), jnp.asarray(sw)
                xs = x[st]
                row = {"config": name, "T": T, "rows": rows,
                       "padded": padded, "rule": padded == rule}
                for label, sz in zip(("even", "skew"), sizes):
                    sz = jnp.asarray(sz)
                    row[f"gemm_{label}_ms"] = timed(
                        gemm, (xs, w_gate, sz), a.reps, a.iters)
                    row[f"dispatch_{label}_ms"] = timed(
                        dispatch, (x, w_gate, w_up, w_down, st, sw, sz),
                        a.reps, a.iters)
                table.append(row)
                print(f"{name} {T} {rows} {padded} | "
                      f"{row['gemm_even_ms']:.3f} {row['gemm_skew_ms']:.3f}"
                      f" | {row['dispatch_even_ms']:.3f} "
                      f"{row['dispatch_skew_ms']:.3f} | "
                      f"{'<-' if row['rule'] else ''}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ragged_rows.json", "w") as f:
        json.dump({"device": dev.device_kind, "jax": jax.__version__,
                   "rows": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
